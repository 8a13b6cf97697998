"""Plain reference for one commit's verdict: CometBFT's VerifyCommit.

Works on the benchmark's own plain records (ValsetSpec, CommitSpec: bytes
and integers, made by benchmarks/datagen.py from the seed) and imports
nothing of the program. The semantics are types/validation.go
VerifyCommit's: the commit carries one signature per validator, in the
set's order; every signature that is not absent is checked over its
CanonicalVote sign-bytes; more than 2/3 of the total power must have signed
for the block; the first wrong signature is named.

Verdicts are strings: "accept", "reject#<index>" (first wrong signature),
"reject:power" (not more than 2/3).
"""

from __future__ import annotations

import dataclasses

from benchmarks.reference import ed25519_ref, sr25519_ref

PRECOMMIT = 2  # SignedMsgType


@dataclasses.dataclass(frozen=True)
class ValsetSpec:
    chain_id: str
    schemes: tuple[str, ...]   # "ed25519" | "sr25519", in validator-set order
    pubs: tuple[bytes, ...]
    powers: tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class CommitSpec:
    height: int
    round: int
    block_hash: bytes
    parts_total: int
    parts_hash: bytes
    stamps: tuple[tuple[int, int], ...]  # (seconds, nanos) per validator
    sigs: tuple[bytes, ...]              # every validator signs for the block

    def with_flipped(self, lane: int) -> "CommitSpec":
        """The same commit with the first byte of one signature flipped in
        its lowest bit: the generator's one way of corrupting."""
        sigs = list(self.sigs)
        sigs[lane] = bytes([sigs[lane][0] ^ 1]) + sigs[lane][1:]
        return dataclasses.replace(self, sigs=tuple(sigs))


# ------------------------------------------------------- canonical encoding


def uvarint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _field_bytes(num: int, data: bytes) -> bytes:
    return uvarint(num << 3 | 2) + uvarint(len(data)) + data


def _field_varint(num: int, value: int) -> bytes:
    """proto3 scalar: omitted when zero."""
    return uvarint(num << 3) + uvarint(value) if value else b""


def _field_sfixed64(num: int, value: int) -> bytes:
    return (uvarint(num << 3 | 1) + value.to_bytes(8, "little", signed=True)
            if value else b"")


def vote_sign_bytes(chain_id: str, commit: CommitSpec, lane: int) -> bytes:
    """CanonicalVote of a precommit for the block, length-delimited
    (proto/tendermint/types/canonical.proto; the timestamp is always
    written, its zero fields are not)."""
    seconds, nanos = commit.stamps[lane]
    parts = (_field_varint(1, commit.parts_total)
             + _field_bytes(2, commit.parts_hash))
    block_id = _field_bytes(1, commit.block_hash) + _field_bytes(2, parts)
    body = (_field_varint(1, PRECOMMIT)
            + _field_sfixed64(2, commit.height)
            + _field_sfixed64(3, commit.round)
            + _field_bytes(4, block_id)
            + _field_bytes(5, _field_varint(1, seconds)
                           + _field_varint(2, nanos))
            + (_field_bytes(6, chain_id.encode()) if chain_id else b""))
    return uvarint(len(body)) + body


# ------------------------------------------------------------------- lanes


def verify_lane(lane: tuple[str, bytes, bytes, bytes]) -> bool:
    scheme, pub, msg, sig = lane
    if scheme == "sr25519":
        return sr25519_ref.verify(pub, msg, sig)
    if scheme == "ed25519":
        return ed25519_ref.verify_zip215(pub, msg, sig)
    raise ValueError(f"no reference for scheme {scheme!r}")


def commit_lanes(vals: ValsetSpec, commit: CommitSpec) -> list[tuple]:
    return [(vals.schemes[i], vals.pubs[i],
             vote_sign_bytes(vals.chain_id, commit, i), commit.sigs[i])
            for i in range(len(commit.sigs))]


def verdict(vals: ValsetSpec, commit: CommitSpec, lane_ok) -> str:
    """VerifyCommit's answer, given lane_ok(lane) -> bool for each of the
    commit's lanes (a memo over verify_lane, so that commits which share
    lanes pay for them once)."""
    if len(commit.sigs) != len(vals.pubs):
        return "reject:size"
    # a CommitSpec holds no absent or nil vote: the tally is the total
    signed = sum(vals.powers)
    if signed <= sum(vals.powers) * 2 // 3:
        return "reject:power"
    for i, lane in enumerate(commit_lanes(vals, commit)):
        if not lane_ok(lane):
            return f"reject#{i}"
    return "accept"
