"""Plain reference for one hop of a light client: CometBFT's light.Verify.

Works on the benchmark's own plain records (commit_ref's ValsetSpec and
CommitSpec, HeaderSpec and LightBlockSpec here: bytes and integers, made
by the cell's driver from the seed) and imports nothing of the program.
The semantics are light/verifier.go's (VerifyNonAdjacent, VerifyAdjacent)
over types/validation.go's VerifyCommitLightTrusting and VerifyCommitLight,
types/validator_set.go's Hash (an RFC 6962 Merkle root of the set's
SimpleValidator leaves, crypto/merkle/tree.go) and types/block.go's
Header.Hash:

  the trusted header must not have expired; the new header is for the same
  chain, higher and later than the trusted one and not from the future, its
  validators_hash is the root of the set handed over with it, and its commit
  is for that header and height (a hop to the next height also wants
  validators_hash equal to the trusted header's next_validators_hash);
  a hop over more than one height then wants more than `trust_level` of the
  TRUSTED set's power among the new commit's signatures for the block,
  looked up BY ADDRESS, in the commit's order until the threshold is passed
  and no further, a validator met twice refused; then every hop wants more
  than 2/3 of the NEW set's power, looked up by index, in order until the
  threshold is passed and no further. Every signature so taken is checked;
  the first wrong one is named by its index in the commit.

Verdicts are strings: "accept"; "reject:untrusted" (the trusted set's share
is too small: a bisecting client's cue, not a fault); "reject:power" (the
new set's 2/3 is not there); "reject#<index>" (first wrong signature);
"reject:header" (any header check); "reject:expired" (the trusted header
is past its trusting period); "reject:double-vote".
"""

from __future__ import annotations

import dataclasses
import hashlib

from benchmarks.reference import commit_ref
from benchmarks.reference.commit_ref import (CommitSpec, ValsetSpec,
                                             _field_bytes, _field_varint)

BLOCK_PROTOCOL = 11  # version/version.go
# crypto.PublicKey's oneof (proto/tendermint/crypto/keys.proto)
KEY_FIELD = {"ed25519": 1, "sr25519": 3}


@dataclasses.dataclass(frozen=True)
class HeaderSpec:
    chain_id: str
    height: int
    time: tuple[int, int]               # (seconds, nanos)
    last_block_hash: bytes
    last_parts_total: int
    last_parts_hash: bytes
    last_commit_hash: bytes
    data_hash: bytes
    validators_hash: bytes
    next_validators_hash: bytes
    consensus_hash: bytes
    app_hash: bytes
    last_results_hash: bytes
    evidence_hash: bytes
    proposer_address: bytes
    version: tuple[int, int] = (BLOCK_PROTOCOL, 0)   # (block, app)


@dataclasses.dataclass(frozen=True)
class LightBlockSpec:
    """A signed header and the validator set handed over with it. The
    commit's signature i carries `addresses[i]`: by default the address of
    the set's validator i, whose vote it is. A CommitSpec's signatures are
    all for the block; the lanes in `absent` hold BlockIDFlag.ABSENT in
    their place (none in the benchmark's data: the tests' "power")."""

    header: HeaderSpec
    commit: CommitSpec
    vals: ValsetSpec
    addresses: tuple[bytes, ...] = None
    absent: frozenset = frozenset()

    def __post_init__(self):
        if self.addresses is None:
            object.__setattr__(self, "addresses",
                               tuple(map(address, self.vals.pubs)))


@dataclasses.dataclass(frozen=True)
class Params:
    trusting_period_ns: int
    now_ns: int
    max_clock_drift_ns: int
    trust_level: tuple[int, int] = (1, 3)


def address(pub: bytes) -> bytes:
    """An ed25519 or sr25519 key's address: SHA256-20."""
    return hashlib.sha256(pub).digest()[:20]


def unix_ns(stamp: tuple[int, int]) -> int:
    return stamp[0] * 1_000_000_000 + stamp[1]


# ------------------------------------------------------------------ hashes


def merkle_root(leaves: list[bytes]) -> bytes:
    """RFC 6962: leaf SHA256(0x00 || leaf), inner SHA256(0x01 || l || r),
    split at the largest power of two under n; the empty tree SHA256("")."""
    n = len(leaves)
    if n == 0:
        return hashlib.sha256(b"").digest()
    if n == 1:
        return hashlib.sha256(b"\x00" + leaves[0]).digest()
    k = 1 << ((n - 1).bit_length() - 1)
    return hashlib.sha256(b"\x01" + merkle_root(leaves[:k])
                          + merkle_root(leaves[k:])).digest()


def valset_hash(vals: ValsetSpec) -> bytes:
    """The root over SimpleValidator{pub_key = 1, voting_power = 2}."""
    return merkle_root([
        _field_bytes(1, _field_bytes(KEY_FIELD[scheme], pub))
        + _field_varint(2, power)
        for scheme, pub, power in zip(vals.schemes, vals.pubs, vals.powers)])


def header_hash(h: HeaderSpec) -> bytes:
    """types/block.go Header.Hash: the root over the fourteen fields, the
    plain ones wrapped (StringValue, Int64Value, BytesValue)."""
    parts = (_field_varint(1, h.last_parts_total)
             + _field_bytes(2, h.last_parts_hash))
    last_block_id = ((_field_bytes(1, h.last_block_hash)
                      if h.last_block_hash else b"")
                     + _field_bytes(2, parts))   # non-nullable: always there

    def wrapped_bytes(b: bytes) -> bytes:
        return _field_bytes(1, b) if b else b""

    return merkle_root([
        _field_varint(1, h.version[0]) + _field_varint(2, h.version[1]),
        wrapped_bytes(h.chain_id.encode()),
        _field_varint(1, h.height),
        _field_varint(1, h.time[0]) + _field_varint(2, h.time[1]),
        last_block_id,
        wrapped_bytes(h.last_commit_hash), wrapped_bytes(h.data_hash),
        wrapped_bytes(h.validators_hash),
        wrapped_bytes(h.next_validators_hash),
        wrapped_bytes(h.consensus_hash), wrapped_bytes(h.app_hash),
        wrapped_bytes(h.last_results_hash), wrapped_bytes(h.evidence_hash),
        wrapped_bytes(h.proposer_address)])


# -------------------------------------------------------------------- rows
#
# A row is (scheme, public key, the signature's index in the commit): what
# a check takes. lanes_of() makes rows the (scheme, pub, msg, sig) that
# commit_ref.verify_lane checks; a caller that knows every signature to be
# sound (the driver's walk of a bisection over commits not yet signed)
# never makes one.


def lanes_of(chain_id: str, commit: CommitSpec, rows: list) -> list:
    return [(scheme, pub, commit_ref.vote_sign_bytes(chain_id, commit, i),
             commit.sigs[i]) for scheme, pub, i in rows]


def light_rows(vals: ValsetSpec, commit: CommitSpec,
               absent: frozenset = frozenset()):
    """VerifyCommitLight's selection: (rows, None) or (None, verdict).
    Every signature has a stamp, also before it is signed."""
    if len(commit.stamps) != len(vals.pubs):
        return None, "reject:header"    # wrong set size
    needed = sum(vals.powers) * 2 // 3
    rows, tallied = [], 0
    for i, power in enumerate(vals.powers):
        if i in absent:
            continue
        rows.append((vals.schemes[i], vals.pubs[i], i))
        tallied += power
        if tallied > needed:
            return rows, None
    return None, "reject:power"


def trusting_rows(trusted: ValsetSpec, addresses: tuple,
                  trust_level: tuple[int, int],
                  absent: frozenset = frozenset()):
    """VerifyCommitLightTrusting's selection: (rows, None) or (None,
    verdict)."""
    by_address = {}
    for j, pub in enumerate(trusted.pubs):
        by_address.setdefault(address(pub), j)
    needed = sum(trusted.powers) * trust_level[0] // trust_level[1]
    rows, seen, tallied = [], set(), 0
    for i, addr in enumerate(addresses):
        j = by_address.get(addr)
        if j is None or i in absent:
            continue
        if j in seen:
            return None, "reject:double-vote"
        seen.add(j)
        rows.append((trusted.schemes[j], trusted.pubs[j], i))
        tallied += trusted.powers[j]
        if tallied > needed:
            return rows, None
    return None, "reject:untrusted"


def header_verdict(trusted: LightBlockSpec, new: LightBlockSpec,
                   params: Params) -> str | None:
    """The checks before any signature (verifyNewHeaderAndVals and what
    stands around it), or None where they hold."""
    t, h = trusted.header, new.header
    if unix_ns(t.time) + params.trusting_period_ns <= params.now_ns:
        return "reject:expired"
    ok = (h.chain_id == t.chain_id
          and new.commit.height == h.height
          and new.commit.block_hash == header_hash(h)
          and h.height > t.height
          and unix_ns(h.time) > unix_ns(t.time)
          and unix_ns(h.time) < params.now_ns + params.max_clock_drift_ns
          and h.validators_hash == valset_hash(new.vals)
          and (h.height != t.height + 1
               or h.validators_hash == t.next_validators_hash))
    return None if ok else "reject:header"


def hop_rows(trusted: LightBlockSpec, new: LightBlockSpec,
             params: Params) -> tuple[list, list, str | None]:
    """(the trusting check's rows, the new set's rows, the verdict where
    the hop is answered before any signature is checked, else None)."""
    verdict = header_verdict(trusted, new, params)
    if verdict:
        return [], [], verdict
    first: list = []
    if new.header.height != trusted.header.height + 1:
        first, verdict = trusting_rows(trusted.vals, new.addresses,
                                       params.trust_level, new.absent)
        if verdict:
            return [], [], verdict
    second, verdict = light_rows(new.vals, new.commit, new.absent)
    if verdict:
        return [], [], verdict
    return first, second, None


def hop_lanes(trusted: LightBlockSpec, new: LightBlockSpec,
              params: Params) -> tuple[list, str | None]:
    """([(index in the commit, lane)] in the order the hop checks them: the
    trusting check's, then the new set's; the verdict where the hop is
    answered before any signature is checked, else None)."""
    first, second, verdict = hop_rows(trusted, new, params)
    rows = first + second
    lanes = lanes_of(new.header.chain_id, new.commit, rows)
    return [(row[2], lane) for row, lane in zip(rows, lanes)], verdict


def verify(trusted: LightBlockSpec, new: LightBlockSpec, params: Params,
           lane_ok) -> str:
    """light.Verify's answer, given lane_ok(lane) -> bool for each lane it
    takes (a memo over commit_ref.verify_lane, so that hops which share
    lanes pay for them once)."""
    lanes, verdict = hop_lanes(trusted, new, params)
    if verdict:
        return verdict
    for i, lane in lanes:
        if not lane_ok(lane):
            return f"reject#{i}"
    return "accept"
