"""Commit verification over the batch-first crypto boundary.

Reference: types/validation.go. All three entry points funnel signature rows
into one BatchVerifier (TPU kernel or CPU loop, crypto/batch dispatch) —
on failure the per-lane mask pinpoints the first bad signature without the
reference's serial re-verify pass (types/validation.go:266).

Semantics preserved exactly:
  verify_commit            — counts only COMMIT flags, verifies ALL non-absent
                             signatures (incentivization rule,
                             types/validation.go:19-25), 1:1 index lookup.
  verify_commit_light      — counts all non-ignored, stops at +2/3, 1:1 index.
  verify_commit_light_trusting — trust-fraction threshold, lookup by address
                             (valset may differ from the commit's), duplicate
                             detection.
"""

from __future__ import annotations

from dataclasses import dataclass
from itertools import compress, islice, repeat

import numpy as np

from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.libs import trace
from cometbft_tpu.libs.rowblock import RowBlock
from cometbft_tpu.types.basic import BlockID, BlockIDFlag
from cometbft_tpu.types.commit import ROW_BLOCK_MIN, Commit, CommitSig
from cometbft_tpu.types.validator import ValidatorSet

BATCH_VERIFY_THRESHOLD = 2  # types/validation.go:13


@dataclass(frozen=True)
class Fraction:
    """libs/math Fraction — light-client trust level."""

    numerator: int
    denominator: int


class ErrNotEnoughVotingPowerSigned(Exception):
    def __init__(self, got: int, needed: int):
        super().__init__(f"invalid commit -- insufficient voting power: got {got}, needed more than {needed}")
        self.got = got
        self.needed = needed


class ErrInvalidCommitSignature(Exception):
    pass


def _root_span(name: str, commit: Commit | None, path: str):
    """The root span of one call into this module (libs/trace.py, cat
    `node`): whatever no finer span below it covers is its SELF time, the
    unattributed host time of the commit path."""
    if commit is None:
        return trace.span(name, cat="node", path=path)
    return trace.span(name, cat="node", path=path, height=commit.height,
                      sigs=len(commit.signatures))


def _verify_basic(vals: ValidatorSet, commit: Commit, height: int, block_id: BlockID) -> None:
    """types/validation.go verifyBasicValsAndCommit."""
    if vals is None or vals.is_nil_or_empty():
        raise ValueError("nil or empty validator set")
    if commit is None:
        raise ValueError("nil commit")
    if len(vals) != len(commit.signatures):
        raise ValueError(
            f"invalid commit -- wrong set size: {len(vals)} vs {len(commit.signatures)}"
        )
    if height != commit.height:
        raise ValueError(f"invalid commit -- wrong height: {height} vs {commit.height}")
    if block_id != commit.block_id:
        raise ValueError(
            f"invalid commit -- wrong block ID: want {block_id}, got {commit.block_id}"
        )


def _should_batch_verify(vals: ValidatorSet, commit: Commit) -> bool:
    return len(commit.signatures) >= BATCH_VERIFY_THRESHOLD and crypto_batch.supports_batch_verifier(
        vals.get_proposer().pub_key if vals.get_proposer() else None
    )


def _ignored(cs: CommitSig, commit_only: bool) -> bool:
    """Which signatures a check leaves out: all but the COMMIT ones
    (verify_commit_light and the trusting check, which tally every row
    they take), or the ABSENT ones alone (verify_commit, which checks NIL
    votes too and tallies the COMMIT ones). _select_block applies the
    same two tests to the whole flag vector."""
    if commit_only:
        return cs.block_id_flag != BlockIDFlag.COMMIT
    return cs.block_id_flag == BlockIDFlag.ABSENT


def _commit_rows(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    commit_only: bool,
    count_all_signatures: bool,
    lookup_by_index: bool,
) -> tuple[RowBlock, "np.ndarray | list[int]"]:
    """The shared row-builder behind every batched commit verification
    (types/validation.go:153-257 loop body): select signatures, tally power,
    enforce the threshold. Returns (the rows as a libs/rowblock.RowBlock,
    their indices in the commit); raises ErrNotEnoughVotingPowerSigned
    below threshold.

    One selection, two ways to run it, chosen from what is at hand: a
    commit of ROW_BLOCK_MIN rows or more whose sign-rows the array pass
    built is selected, tallied and cut with index vectors (_select_block:
    no object a lane; where the validators are looked up by address, the
    trusting check, the commit's addresses are first joined to the set's
    indices); every other one walks its signatures (_select_lanes: small
    commits, stamps past int64) and turns its lists into the same block
    with one lane loop. The span says which ran (`path`: `block` or
    `lane`), how many rows it handed over (`rows`) and, on the trusting
    check, that the set was looked up by `address`."""
    with trace.span("commit.rows", cat="collect") as sp:
        sign_rows = commit.vote_sign_bytes_all(chain_id)
        # epoch-keyed device residency (reduced-send protocol): announce the
        # active validator set so the kernels' resident key tables pin its
        # rows and churn ships only deltas (ops/residency.py; never raises)
        try:
            from cometbft_tpu.ops import residency as _residency

            _residency.announce_validator_set(vals)
        except Exception:  # noqa: BLE001 - residency is an optimization layer
            pass
        if (sign_rows.block is not None
                and len(commit.signatures) >= ROW_BLOCK_MIN):
            path = "block"
            block, idxs = _select_block(
                vals, commit, sign_rows, voting_power_needed, commit_only,
                count_all_signatures, lookup_by_index)
        else:
            path = "lane"
            block, idxs = _select_lanes(
                vals, commit, sign_rows, voting_power_needed, commit_only,
                count_all_signatures, lookup_by_index)
        sp.set(path=path, rows=len(block))
        if not lookup_by_index:
            sp.set(lookup="address")
            trace.count("trusting_rows",
                        "joined" if path == "block" else "scanned", len(block))
        return block, idxs


def _select_lanes(vals, commit, sign_rows, voting_power_needed,
                  commit_only, count_all_signatures, lookup_by_index):
    """_commit_rows a lane at a time, as the reference's loop has it."""
    seen_vals: dict[int, int] = {}
    pubs: list = []
    sigs: list[bytes] = []
    idxs: list[int] = []
    tallied = 0
    for idx, cs in enumerate(commit.signatures):
        if _ignored(cs, commit_only):
            continue
        if lookup_by_index:
            val = vals.validators[idx]
        else:
            val_idx, val = vals.get_by_address(cs.validator_address)
            if val is None:
                continue
            if val_idx in seen_vals:
                raise ValueError(
                    f"double vote from {val.address.hex()} ({seen_vals[val_idx]} and {idx})"
                )
            seen_vals[val_idx] = idx
        pubs.append(val.pub_key)
        sigs.append(cs.signature)
        idxs.append(idx)
        if commit_only or cs.block_id_flag == BlockIDFlag.COMMIT:
            tallied += val.voting_power
        if not count_all_signatures and tallied > voting_power_needed:
            break
    if tallied <= voting_power_needed:
        raise ErrNotEnoughVotingPowerSigned(got=tallied, needed=voting_power_needed)
    # factored (shared-prefix) rows: the staging fast path reassembles
    # whole runs with one prefix broadcast instead of N per-row copies
    # (libs/prefixrows.py)
    return RowBlock.from_rows(pubs, sign_rows.take(idxs), sigs), idxs


def _select_block(vals, commit, sign_rows, voting_power_needed,
                  commit_only, count_all_signatures, lookup_by_index=True):
    """_commit_rows over columns: the flags and the signatures are read
    from the commit in one pass each (fresh every call: a commit's
    signatures may be set after its sign-rows were built), the keys, key
    types and powers come from the set's cached columns, and selection,
    tally, threshold and the split by key type are index arithmetic. The
    same rows, the same tally and the same errors as _select_lanes.

    Looked up by index, signature i is validator i's. Looked up by address
    (the trusting check: the commit is another set's), the commit's
    addresses are joined to the set's indices through its address map
    (ValidatorSet.address_index), signatures of validators the set does
    not have are dropped, and a validator met twice before the tally
    passes the threshold is refused as the loop refuses it."""
    signatures = commit.signatures
    n = len(signatures)
    cols = vals.columns()
    flags = [cs.block_id_flag for cs in signatures]
    try:  # the three flags are small: one byte a row is the fastest way in
        flags = np.frombuffer(bytes(flags), dtype=np.uint8)
    except ValueError:  # a flag no commit should carry; the tests below
        flags = np.fromiter(flags, np.int64, n)  # treat it as the loop does
    taken = (flags == BlockIDFlag.COMMIT if commit_only
             else flags != BlockIDFlag.ABSENT)
    if lookup_by_index:
        idxs = val_idxs = np.flatnonzero(taken)
    else:
        joined = np.fromiter(
            map(vals.address_index().get,
                [cs.validator_address for cs in signatures], repeat(-1)),
            np.intp, n)
        taken &= joined >= 0
        idxs = np.flatnonzero(taken)
        val_idxs = joined[idxs]
    power = cols.powers[val_idxs]
    if not commit_only:
        power = np.where(flags[idxs] == BlockIDFlag.COMMIT, power, 0)
    if count_all_signatures:
        tallied = int(power.sum())
    else:
        # the loop's `break`: the first row at which the running tally
        # passes the threshold is the last one taken
        running = np.cumsum(power)
        stop = int(np.searchsorted(running, voting_power_needed,
                                   side="right"))
        if stop < len(idxs):
            idxs, val_idxs = idxs[:stop + 1], val_idxs[:stop + 1]
        tallied = int(running[min(stop, len(running) - 1)]) if len(
            running) else 0
    if not lookup_by_index:
        _refuse_double_vote(vals, idxs, val_idxs)
    if tallied <= voting_power_needed:
        raise ErrNotEnoughVotingPowerSigned(got=tallied, needed=voting_power_needed)
    sigs = [cs.signature for cs in signatures]
    if len(idxs) < n:
        sigs = list(islice(compress(sigs, taken.tolist()), len(idxs)))
    return RowBlock.from_set(cols, val_idxs, sign_rows.block, sigs,
                             msg_idxs=idxs), idxs


def _refuse_double_vote(vals, idxs, val_idxs) -> None:
    """The loop's double-vote refusal over the rows taken (signatures
    `idxs` of validators `val_idxs`, cut at the row where the tally
    passed the threshold): the loop meets a validator's second signature
    only if it comes no later than that row, and refuses it before it
    tallies it, whatever the tally would have said. The rows before a
    second signature hold each validator once, so the cut, taken over a
    tally that counted the second one too, is the loop's wherever it
    falls before it."""
    of_vals = val_idxs.tolist()
    if len(set(of_vals)) == len(of_vals):
        return
    seen: dict[int, int] = {}
    for idx, val_idx in zip(idxs.tolist(), of_vals):
        if val_idx in seen:
            raise ValueError(
                f"double vote from {vals.validators[val_idx].address.hex()} "
                f"({seen[val_idx]} and {idx})")
        seen[val_idx] = idx


def _bls_aggregate_ok(pubs, msgs, sigs) -> bool | None:
    """The BLS aggregate commit path (ops/bls_kernel.aggregate_verify):
    when EVERY signer in the commit is a bls12381 key, the whole commit
    decides with one pairing-product check — signatures sum to a single
    G2 point, pubkeys aggregate per distinct sign-bytes (PoP semantics),
    cost ~independent of committee size. Returns None when the commit is
    not BLS-shaped (callers fall through to per-lane batching), True on
    an accepted aggregate, False when the aggregate fails — the caller
    then re-runs the per-lane path to PINPOINT the offending signature
    (the aggregate check is a commit-level verdict, not a mask).

    Never raises on verification trouble: a device fault inside
    aggregate_verify already degrades to the exact CPU oracle."""
    if not pubs or any(p.type_() != "bls12381" for p in pubs):
        return None
    from cometbft_tpu.crypto import bls12381

    if not bls12381.enabled():
        # loud misconfiguration, same rule as crypto/batch
        raise crypto_batch.crypto.ErrInvalidKey(
            "bls12381 validator set but crypto.bls_enabled is off")
    from cometbft_tpu.libs.prefixrows import as_bytes
    from cometbft_tpu.ops import bls_kernel

    return bls_kernel.aggregate_verify(
        [p.bytes_() for p in pubs], [as_bytes(m) for m in msgs],
        [bytes(s) for s in sigs])


def _bls_aggregate_agg_ok(pubs, msgs, agg_sig) -> bool | None:
    """Certificate-path sibling of _bls_aggregate_ok: the G2 side
    arrives ALREADY aggregated (a CommitCertificate's 96 B signature)
    so the one-pairing check runs without a summing stage. Same
    contract: None when the set is not BLS-shaped, ErrInvalidKey loud
    when the set is BLS but the backend is off, True/False for the
    pairing verdict. Never raises on verification trouble — device
    faults degrade to the exact CPU oracle inside the kernel."""
    if not pubs or any(p.type_() != "bls12381" for p in pubs):
        return None
    from cometbft_tpu.crypto import bls12381

    if not bls12381.enabled():
        # loud misconfiguration, same rule as crypto/batch
        raise crypto_batch.crypto.ErrInvalidKey(
            "bls12381 validator set but crypto.bls_enabled is off")
    from cometbft_tpu.libs.prefixrows import as_bytes
    from cometbft_tpu.ops import bls_kernel

    return bls_kernel.aggregate_verify_agg(
        [p.bytes_() for p in pubs], [as_bytes(m) for m in msgs],
        bytes(agg_sig))


def _raise_first_bad(commit: Commit, idxs, mask) -> None:
    with trace.span("commit.verdict", cat="collect"):
        bad = np.flatnonzero(~np.asarray(mask, dtype=bool))
        if len(bad):
            idx = int(idxs[bad[0]])
            raise ErrInvalidCommitSignature(
                f"wrong signature (#{idx}): {commit.signatures[idx].signature.hex()}"
            )


def _bls_only(block: RowBlock) -> bool:
    """Every row's key is bls12381: the commit may decide with one
    aggregate check (_bls_aggregate_ok)."""
    return set(block.parts) == {"bls12381"}


def _verify_commit_batch(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    commit_only: bool,
    count_all_signatures: bool,
    lookup_by_index: bool,
) -> None:
    """types/validation.go:153-257."""
    block, idxs = _commit_rows(
        chain_id, vals, commit, voting_power_needed,
        commit_only, count_all_signatures, lookup_by_index,
    )
    # all-BLS validator set: one pairing-product check per commit; a
    # failed aggregate falls through to the per-lane path to pinpoint
    if _bls_only(block) and _bls_aggregate_ok(*block.lists()):
        return
    # mixed-scheme coalescing: each key type becomes one device sub-batch
    # (BASELINE config 5 mega-commits mix ed25519 + sr25519 validators)
    bv = crypto_batch.create_mixed_batch_verifier()
    try:
        with trace.span("commit.rows", cat="collect"):
            bv.add_block(block)
    except Exception as e:  # noqa: BLE001 - unbatchable key type in the set
        from cometbft_tpu.libs import log as _log

        _log.default().info(
            "commit verification falling back to serial", reason=str(e))
        return _verify_commit_single(
            chain_id, vals, commit, voting_power_needed,
            commit_only, count_all_signatures, lookup_by_index,
        )
    ok, valid_sigs = bv.verify()
    if ok:
        return
    _raise_first_bad(commit, idxs, valid_sigs)
    raise RuntimeError("BUG: batch verification failed with no invalid signatures")


def _verify_commit_single(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    commit_only: bool,
    count_all_signatures: bool,
    lookup_by_index: bool,
) -> None:
    """types/validation.go:266-330."""
    seen_vals: dict[int, int] = {}
    tallied = 0
    # one span around the loop, never one a signature: on this path the
    # sign-bytes are encoded per index between the host verifications
    with trace.span("commit.sign_bytes", cat="signbytes", serial=True):
        for idx, cs in enumerate(commit.signatures):
            if _ignored(cs, commit_only):
                continue
            if lookup_by_index:
                val = vals.validators[idx]
            else:
                val_idx, val = vals.get_by_address(cs.validator_address)
                if val is None:
                    continue
                if val_idx in seen_vals:
                    raise ValueError(
                        f"double vote from {val.address.hex()} ({seen_vals[val_idx]} and {idx})"
                    )
                seen_vals[val_idx] = idx
            sign_bytes = commit.vote_sign_bytes(chain_id, idx)
            if not val.pub_key.verify_signature(sign_bytes, cs.signature):
                raise ErrInvalidCommitSignature(
                    f"wrong signature (#{idx}): {cs.signature.hex()}"
                )
            if commit_only or cs.block_id_flag == BlockIDFlag.COMMIT:
                tallied += val.voting_power
            if not count_all_signatures and tallied > voting_power_needed:
                return
    if tallied <= voting_power_needed:
        raise ErrNotEnoughVotingPowerSigned(got=tallied, needed=voting_power_needed)


def _verify_commit_rows(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    commit_only: bool,
    count_all_signatures: bool,
    lookup_by_index: bool,
) -> None:
    """Batched where the set's keys allow it, else serial."""
    verify = (_verify_commit_batch if _should_batch_verify(vals, commit)
              else _verify_commit_single)
    verify(chain_id, vals, commit, voting_power_needed,
           commit_only, count_all_signatures, lookup_by_index)


def verify_commit(
    chain_id: str, vals: ValidatorSet, block_id: BlockID, height: int, commit: Commit
) -> None:
    """+2/3 signed; checks ALL signatures (types/validation.go:26-57)."""
    with _root_span("commit.verify", commit, "full"):
        _verify_basic(vals, commit, height, block_id)
        needed = vals.total_voting_power() * 2 // 3
        _verify_commit_rows(
            chain_id, vals, commit, needed,
            commit_only=False,
            count_all_signatures=True,
            lookup_by_index=True,
        )


def verify_commit_light(
    chain_id: str, vals: ValidatorSet, block_id: BlockID, height: int, commit: Commit
) -> None:
    """+2/3 signed; stops early (types/validation.go:60-92)."""
    with _root_span("commit.verify", commit, "light"):
        _verify_basic(vals, commit, height, block_id)
        needed = vals.total_voting_power() * 2 // 3
        _verify_commit_rows(
            chain_id, vals, commit, needed,
            commit_only=True,
            count_all_signatures=False,
            lookup_by_index=True,
        )


def _trusting_needed(vals: ValidatorSet, commit: Commit,
                     trust_level: Fraction) -> int:
    """The argument checks and the power threshold of the trusting path
    (types/validation.go:95-110)."""
    if vals is None:
        raise ValueError("nil validator set")
    if trust_level.denominator == 0:
        raise ValueError("trustLevel has zero Denominator")
    if commit is None:
        raise ValueError("nil commit")
    return vals.total_voting_power() * trust_level.numerator // trust_level.denominator


def verify_commit_light_trusting(
    chain_id: str, vals: ValidatorSet, commit: Commit, trust_level: Fraction
) -> None:
    """trustLevel of the (possibly different) valset signed
    (types/validation.go:95-131)."""
    with _root_span("commit.verify", commit, "trusting"):
        needed = _trusting_needed(vals, commit, trust_level)
        _verify_commit_rows(
            chain_id, vals, commit, needed,
            commit_only=True,
            count_all_signatures=False,
            lookup_by_index=False,
        )


# ---------------------------------------------------------------------------
# Streaming (async) commit verification — the blocksync/light-client seam.
#
# The reference verifies each commit synchronously, twice (VerifyCommitLight
# in the blocksync reactor, then VerifyCommit again inside validateBlock,
# blocksync/reactor.go:463 + state/validation.go:92). TPU-first redesign:
# stage ONE full-semantics verification per commit without verifying it,
# resolve a whole window of heights as one scheduler batch (prefetch_staged),
# and let ApplyBlock skip the redundant re-verification
# (last_commit_verified).
# ---------------------------------------------------------------------------


class StagedCommitVerification:
    """A staged-but-unresolved verify_commit: finish() raises exactly what
    the sync path would. The rows (one libs/rowblock.RowBlock, whatever
    the scheme, the backend or the path that selected them) are NOT
    verified at staging time — prefetch_staged coalesces every staged
    commit in a window into ONE scheduler batch (one transfer, one kernel
    dispatch, one device->host fetch on the device backend), which is
    what makes the blocksync window pipeline device-bound instead of
    dispatch-overhead-bound."""

    def __init__(self, commit: Commit, rows: RowBlock, sig_idxs):
        """The rows as _commit_rows returns them."""
        self.commit = commit
        self.sig_idxs = sig_idxs
        self._rows = rows
        # every key is bls12381: finish() tries ONE aggregate
        # pairing-product check first (blocksync/light windows decide a
        # BLS commit with it); only a failed aggregate pays the per-lane
        # pinpoint pass
        self._bls_rows = _bls_only(rows)
        self._mask = None
        self._passed = False

    def finish(self, mask=None) -> None:
        """Materialize the mask (or use the window-resolved one) and apply
        the reference error semantics: first invalid signature raises.
        Idempotent once passed (a caller may finish early for ordering and
        again after a window prefetch)."""
        if self._passed:
            return
        with _root_span("commit.resolve", self.commit, "finish"):
            self._finish(mask)

    def _finish(self, mask) -> None:
        if mask is None:
            mask = self._mask
        if mask is None and self._bls_rows and _bls_aggregate_ok(
                *self._rows.lists()):
            self._passed = True
            return
        if mask is None:
            # solo finish without a window prefetch (or the pinpoint pass
            # of a failed BLS aggregate): this commit's rows as their own
            # scheduler batch
            bv = crypto_batch.create_mixed_batch_verifier()
            try:
                bv.add_block(self._rows)
                _, mask = bv.verify()
            except Exception:  # noqa: BLE001 - unbatchable key type
                # schemes outside the batch registry (secp256k1) take
                # raw bytes only
                mask = [p.verify_signature(m, s)
                        for p, m, s in zip(*self._rows.lists())]
        _raise_first_bad(self.commit, self.sig_idxs, mask)
        self._passed = True


def stage_verify_commit(
    chain_id: str, vals: ValidatorSet, block_id: BlockID, height: int, commit: Commit
) -> StagedCommitVerification:
    """verify_commit (full semantics: every non-absent signature checked,
    COMMIT flags tallied, types/validation.go:26-57) staged asynchronously.
    Structural checks + the voting-power threshold run here, synchronously;
    signature validity is deferred to .finish()."""
    with _root_span("commit.stage_verify", commit, "full"):
        _verify_basic(vals, commit, height, block_id)
        needed = vals.total_voting_power() * 2 // 3
        rows = _commit_rows(
            chain_id, vals, commit, needed,
            commit_only=False,
            count_all_signatures=True,
            lookup_by_index=True,
        )
        return StagedCommitVerification(commit, *rows)


def stage_verify_commit_light(
    chain_id: str, vals: ValidatorSet, block_id: BlockID, height: int, commit: Commit
) -> StagedCommitVerification:
    """verify_commit_light staged: the light client's +2/3-of-new-set check
    (types/validation.go:60-92), deferred so a bisection hop's two checks
    resolve with ONE device fetch."""
    with _root_span("commit.stage_verify", commit, "light"):
        _verify_basic(vals, commit, height, block_id)
        needed = vals.total_voting_power() * 2 // 3
        rows = _commit_rows(
            chain_id, vals, commit, needed,
            commit_only=True,
            count_all_signatures=False,
            lookup_by_index=True,
        )
        return StagedCommitVerification(commit, *rows)


def stage_verify_commit_light_trusting(
    chain_id: str, vals: ValidatorSet, commit: Commit, trust_level: Fraction
) -> StagedCommitVerification:
    """verify_commit_light_trusting staged (types/validation.go:95-131).
    The voting-power threshold (raising ErrNotEnoughVotingPowerSigned)
    runs here synchronously; signature validity at finish()."""
    with _root_span("commit.stage_verify", commit, "trusting"):
        needed = _trusting_needed(vals, commit, trust_level)
        rows = _commit_rows(
            chain_id, vals, commit, needed,
            commit_only=True,
            count_all_signatures=False,
            lookup_by_index=False,
        )
        return StagedCommitVerification(commit, *rows)


def prefetch_staged(staged: list[StagedCommitVerification],
                    klass: str | None = None) -> None:
    """Resolve every staged commit in the window with ONE scheduler batch
    (sched/scheduler.py): one group per commit, so each keeps its own
    host-oracle recheck budget, under `klass` (default SYNC: blocksync
    and light-client windows yield the device to consensus flushes);
    queued mempool-admission work rides the same batch as filler. The
    scheduler picks the backend per dispatch and chunks the window below
    the kernel's lane cap; on the device the fetch rides the reduced-fetch
    protocol (a happy window — every commit valid, the steady state —
    transfers 8 bytes per batch). Subsequent finish() calls are pure host
    work (per-commit error isolation stays with the caller). All-BLS
    commits are left to finish(): one aggregate check each."""
    from cometbft_tpu import sched

    with trace.span("commit.prefetch", cat="node", commits=len(staged)):
        with trace.span("commit.rows", cat="collect"):
            todo = [s for s in staged
                    if not (s._passed or s._mask is not None or s._bls_rows)]
        if todo:
            masks = sched.get().verify_many(
                [s._rows for s in todo], klass or sched.SYNC)
            for s, mask in zip(todo, masks):
                s._mask = mask


def resolve_staged(staged: list[StagedCommitVerification]) -> None:
    """Finish a window of staged verifications with one device fetch.
    Raises on the first bad commit, in window order."""
    with trace.span("commit.resolve", cat="node", commits=len(staged)):
        prefetch_staged(staged)
        for s in staged:
            s.finish()
