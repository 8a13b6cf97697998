"""Stateless light-client header verification.

Reference: light/verifier.go:32-245. Three entry points:

  verify_adjacent      — height X → X+1: the new valset hash must equal the
                         trusted header's next_validators_hash, then +2/3 of
                         the new set must have signed.
  verify_non_adjacent  — height X → Y > X+1: trust-level (default 1/3) of the
                         TRUSTED valset must appear in the new commit, then
                         +2/3 of the new set must have signed.
  verify               — dispatch on adjacency.

Both commit checks ride the batch-first crypto boundary
(types/validation.py): on the TPU backend every signature row of a commit is
one device batch — for the 500-validator BASELINE config-4 chains that is
the whole workload, so bisection hops verify at device batch throughput
rather than per-signature host speed.
"""

from __future__ import annotations

from cometbft_tpu.libs import trace
from cometbft_tpu.types.light import LightBlock, SignedHeader
from cometbft_tpu.types.validation import (
    ErrNotEnoughVotingPowerSigned,
    Fraction,
    prefetch_staged,
    stage_verify_commit_light,
    stage_verify_commit_light_trusting,
    verify_commit_light,
)
from cometbft_tpu.types.validator import ValidatorSet
from cometbft_tpu.utils import cmttime

from cometbft_tpu.light.errors import (
    ErrInvalidHeader,
    ErrNewValSetCantBeTrusted,
    ErrOldHeaderExpired,
)

# light/verifier.go:16 — one correct validator is enough to trust a new header
DEFAULT_TRUST_LEVEL = Fraction(1, 3)


def validate_trust_level(lvl: Fraction) -> None:
    """light/verifier.go:197-205: trust level must be in [1/3, 1]."""
    if (
        lvl.numerator * 3 < lvl.denominator
        or lvl.numerator > lvl.denominator
        or lvl.denominator == 0
    ):
        raise ValueError(f"trustLevel must be within [1/3, 1], given {lvl}")


def header_expired(h: SignedHeader, trusting_period_ns: int, now: cmttime.Timestamp) -> bool:
    """light/verifier.go:208-211."""
    expiration_ns = h.time.unix_ns() + trusting_period_ns
    return expiration_ns <= now.unix_ns()


def _verify_new_header_and_vals(
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusted_header: SignedHeader,
    now: cmttime.Timestamp,
    max_clock_drift_ns: int,
) -> None:
    """light/verifier.go:153-193."""
    with trace.span("light.header", cat="header",
                    height=untrusted_header.height):
        _check_new_header_and_vals(
            untrusted_header, untrusted_vals, trusted_header, now,
            max_clock_drift_ns)


def _check_new_header_and_vals(
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusted_header: SignedHeader,
    now: cmttime.Timestamp,
    max_clock_drift_ns: int,
) -> None:
    try:
        untrusted_header.validate_basic(trusted_header.chain_id)
    except ValueError as e:
        raise ErrInvalidHeader(f"untrusted header invalid: {e}") from e
    if untrusted_header.height <= trusted_header.height:
        raise ErrInvalidHeader(
            f"expected new header height {untrusted_header.height} to be greater "
            f"than trusted header height {trusted_header.height}"
        )
    if untrusted_header.time.unix_ns() <= trusted_header.time.unix_ns():
        raise ErrInvalidHeader(
            f"expected new header time {untrusted_header.time} to be after "
            f"old header time {trusted_header.time}"
        )
    if untrusted_header.time.unix_ns() >= now.unix_ns() + max_clock_drift_ns:
        raise ErrInvalidHeader(
            f"new header has a time from the future {untrusted_header.time} "
            f"(now: {now}; max clock drift: {max_clock_drift_ns}ns)"
        )
    if untrusted_header.header.validators_hash != untrusted_vals.hash():
        raise ErrInvalidHeader(
            f"expected new header validators ({untrusted_header.header.validators_hash.hex()}) "
            f"to match those supplied ({untrusted_vals.hash().hex()}) "
            f"at height {untrusted_header.height}"
        )


def verify_adjacent(
    trusted_header: SignedHeader,
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period_ns: int,
    now: cmttime.Timestamp,
    max_clock_drift_ns: int,
) -> None:
    """light/verifier.go:93-135."""
    if untrusted_header.height != trusted_header.height + 1:
        raise ValueError("headers must be adjacent in height")
    if header_expired(trusted_header, trusting_period_ns, now):
        raise ErrOldHeaderExpired(
            trusted_header.time.add_ns(trusting_period_ns), now
        )
    _verify_new_header_and_vals(
        untrusted_header, untrusted_vals, trusted_header, now, max_clock_drift_ns
    )
    if untrusted_header.header.validators_hash != trusted_header.header.next_validators_hash:
        raise ErrInvalidHeader(
            f"expected old header next validators "
            f"({trusted_header.header.next_validators_hash.hex()}) to match "
            f"those from new header ({untrusted_header.header.validators_hash.hex()})"
        )
    try:
        # sync class by default: a light hop must not preempt consensus
        # flushes in the global verify scheduler. The fleet service
        # (light/fleet.py) sets the ambient LIGHT class around its
        # bisections — external serving traffic yields to a catching-up
        # node's own sync windows too — and that choice is respected here.
        from cometbft_tpu import sched

        klass = sched.LIGHT if sched.current_class() == sched.LIGHT else sched.SYNC
        with sched.work_class(klass):
            verify_commit_light(
                trusted_header.chain_id,
                untrusted_vals,
                untrusted_header.commit.block_id,
                untrusted_header.height,
                untrusted_header.commit,
            )
    except Exception as e:  # noqa: BLE001 — uniform ErrInvalidHeader wrapping
        raise ErrInvalidHeader(e) from e


def verify_non_adjacent(
    trusted_header: SignedHeader,
    trusted_vals: ValidatorSet,
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period_ns: int,
    now: cmttime.Timestamp,
    max_clock_drift_ns: int,
    trust_level: Fraction = DEFAULT_TRUST_LEVEL,
) -> None:
    """light/verifier.go:32-90."""
    if untrusted_header.height == trusted_header.height + 1:
        raise ValueError("headers must be non adjacent in height")
    if header_expired(trusted_header, trusting_period_ns, now):
        raise ErrOldHeaderExpired(
            trusted_header.time.add_ns(trusting_period_ns), now
        )
    _verify_new_header_and_vals(
        untrusted_header, untrusted_vals, trusted_header, now, max_clock_drift_ns
    )
    # Both signature checks of a bisection hop — trust-level of the OLD set
    # and +2/3 of the NEW set over the same commit — are staged on the
    # device together and resolved with ONE fetch (the sync path paid two
    # sequential round trips per hop; over a high-RTT link that dominated
    # bisection wall time). With the reduced-fetch protocol that one fetch
    # is 8 bytes/batch of headers on the happy path — the per-lane masks
    # cross the link only when a commit actually fails. Power thresholds
    # still raise synchronously at staging, with the reference's error
    # mapping preserved.
    #
    # DoS guard (verifier.go:69-72 ordering): untrusted_vals is attacker-
    # chosen, so the coalesced form only runs when the new set is within a
    # small factor of the trusted one (honest valsets churn gradually); a
    # suspiciously large new set pays the trusted-set check IN FULL before
    # any work proportional to its own size.
    coalesce = len(untrusted_vals.validators) <= 4 * max(
        len(trusted_vals.validators), 1)
    try:
        staged_trust = stage_verify_commit_light_trusting(
            trusted_header.chain_id, trusted_vals, untrusted_header.commit, trust_level
        )
        if not coalesce:
            staged_trust.finish()
    except ErrNotEnoughVotingPowerSigned as e:
        raise ErrNewValSetCantBeTrusted(e) from e
    try:
        staged_new = stage_verify_commit_light(
            trusted_header.chain_id,
            untrusted_vals,
            untrusted_header.commit.block_id,
            untrusted_header.height,
            untrusted_header.commit,
        )
    except Exception as e:  # noqa: BLE001 - verifier.go:69-72 wrapping
        raise ErrInvalidHeader(e) from e
    from cometbft_tpu import sched as _sched

    prefetch_staged([staged_trust, staged_new],
                    klass=_sched.LIGHT
                    if _sched.current_class() == _sched.LIGHT else "sync")
    try:
        staged_trust.finish()
    except ErrNotEnoughVotingPowerSigned as e:
        raise ErrNewValSetCantBeTrusted(e) from e
    try:
        staged_new.finish()
    except Exception as e:  # noqa: BLE001
        raise ErrInvalidHeader(e) from e


def verify(
    trusted_header: SignedHeader,
    trusted_vals: ValidatorSet,
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period_ns: int,
    now: cmttime.Timestamp,
    max_clock_drift_ns: int,
    trust_level: Fraction = DEFAULT_TRUST_LEVEL,
) -> None:
    """light/verifier.go:138-151. One hop of a light client: the root
    span `light.verify` says between which heights, whether they are
    adjacent, and how the hop was answered (`accepted`; `untrusted`: the
    trusted set's share of the new commit is too small, a bisecting
    client's cue; `rejected`: anything else raised)."""
    adjacent = untrusted_header.height == trusted_header.height + 1
    answer = "rejected"
    with trace.span("light.verify", cat="node", adjacent=adjacent,
                    trusted_height=trusted_header.height,
                    height=untrusted_header.height) as sp:
        try:
            if adjacent:
                verify_adjacent(
                    trusted_header, untrusted_header, untrusted_vals,
                    trusting_period_ns, now, max_clock_drift_ns,
                )
            else:
                verify_non_adjacent(
                    trusted_header, trusted_vals, untrusted_header,
                    untrusted_vals, trusting_period_ns, now,
                    max_clock_drift_ns, trust_level,
                )
            answer = "accepted"
        except ErrNewValSetCantBeTrusted:
            answer = "untrusted"
            raise
        finally:
            sp.set(answer=answer)
            trace.count("light", "hops")
            if answer == "untrusted":
                trace.count("light", "hops_untrusted")


def verify_with_certificate(
    trusted_header: SignedHeader,
    trusted_vals: ValidatorSet,
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period_ns: int,
    now: cmttime.Timestamp,
    max_clock_drift_ns: int,
    trust_level: Fraction,
    cert,
) -> bool:
    """A bisection hop decided by a commit certificate (cert/): the
    non-crypto header checks run EXACTLY as the classic path runs them
    (and raise identically), then the certificate stands in for the
    per-vote commit checks — a >2/3 bitmap tally plus ONE pairing —
    when it attests this header's served commit byte-for-byte
    (attests_commit pins the signer set, timestamps AND the signature
    sum, making cert-accept equivalent to the aggregate-first per-vote
    path on this exact commit).

    Returns True when the hop is decided (accepted). Returns False when
    the certificate is unusable here — mismatched, forged, failing its
    pairing, or (non-adjacent) not carrying trust-level power of the
    OLD set — and the caller MUST run the classic path, which then
    produces the canonical verdict or error. Accept-only: a certificate
    can decide a hop positively or get out of the way; it can never
    reject one. ErrInvalidKey (BLS set with the backend off) propagates
    — misconfiguration stays loud on this path too."""
    from cometbft_tpu.cert.certificate import (
        ErrCertInvalid,
        attests_commit,
        verify_certificate,
    )

    adjacent = untrusted_header.height == trusted_header.height + 1
    if header_expired(trusted_header, trusting_period_ns, now):
        raise ErrOldHeaderExpired(
            trusted_header.time.add_ns(trusting_period_ns), now
        )
    _verify_new_header_and_vals(
        untrusted_header, untrusted_vals, trusted_header, now, max_clock_drift_ns
    )
    if adjacent and (untrusted_header.header.validators_hash
                     != trusted_header.header.next_validators_hash):
        raise ErrInvalidHeader(
            f"expected old header next validators "
            f"({trusted_header.header.next_validators_hash.hex()}) to match "
            f"those from new header ({untrusted_header.header.validators_hash.hex()})"
        )
    commit = untrusted_header.commit
    if not attests_commit(cert, commit):
        return False
    if not adjacent:
        # trust-level tally of the OLD set over the certified signers —
        # the same address-keyed sum the classic trusting check runs
        # (signature validity is covered by the certificate's aggregate)
        tallied = 0
        from cometbft_tpu.types.basic import BlockIDFlag as _Flag

        for cs in commit.signatures:
            if cs.block_id_flag != _Flag.COMMIT:
                continue
            _, val = trusted_vals.get_by_address(cs.validator_address)
            if val is not None:
                tallied += val.voting_power
        needed = (trusted_vals.total_voting_power()
                  * trust_level.numerator // trust_level.denominator)
        if tallied <= needed:
            return False
    try:
        verify_certificate(cert, trusted_header.chain_id, untrusted_vals)
    except ErrCertInvalid:
        return False
    return True


def verify_backwards(untrusted_header, trusted_header) -> None:
    """light/verifier.go:214-245 — headers, not signed headers: walk the
    LastBlockID hash chain one step down."""
    try:
        untrusted_header.validate_basic()
    except ValueError as e:
        raise ErrInvalidHeader(e) from e
    if untrusted_header.chain_id != trusted_header.chain_id:
        raise ErrInvalidHeader("header belongs to another chain")
    if untrusted_header.time.unix_ns() >= trusted_header.time.unix_ns():
        raise ErrInvalidHeader(
            f"expected older header time {untrusted_header.time} to be before "
            f"new header time {trusted_header.time}"
        )
    if untrusted_header.hash() != trusted_header.last_block_id.hash:
        raise ErrInvalidHeader(
            f"older header hash {untrusted_header.hash().hex()} does not match "
            f"trusted header's last block {trusted_header.last_block_id.hash.hex()}"
        )
