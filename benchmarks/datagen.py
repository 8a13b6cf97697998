"""Seeded data and traffic: validator keys, a ring of presigned commits, and
the order in which a window offers them.

Everything here follows from (configuration file, traffic file, --seed) and
from nothing else: no clock, no `secrets`. It imports nothing of the
program: keys and signatures are made by `cryptography` (ed25519) and by
the benchmark's own plain sr25519 (benchmarks/reference/sr25519_ref.py),
over sign-bytes the benchmark encodes itself, so the program is handed
inputs it had no part in making.

sr25519 in Python integers is ~10 ms a signature, so keys and signatures
of that scheme are made by plain worker processes (benchmarks/workers.py:
they never import JAX) that have ended before anything is timed.
"""

from __future__ import annotations

import hashlib
import random

from benchmarks import workers
from benchmarks.reference import sr25519_ref
from benchmarks.reference.commit_ref import (CommitSpec, ValsetSpec,
                                             vote_sign_bytes)

GOLDEN = 0.6180339887498949


def _secret(seed: int, scheme: str, i: int) -> bytes:
    return hashlib.sha256(b"tpu-bft-bench/%s/%d/%d"
                          % (scheme.encode(), seed, i)).digest()


def sr_keypair(secret: bytes):
    return sr25519_ref.keypair_from_mini(secret)


def sr_sign_many(job):
    pair, msgs = job
    return [sr25519_ref.sign(pair, m) for m in msgs]


def address(pub: bytes) -> bytes:
    """CometBFT's address of an ed25519 or sr25519 key: SHA256-20."""
    return hashlib.sha256(pub).digest()[:20]


def make_validators(config: dict, seed: int) -> tuple[ValsetSpec, list]:
    """The configuration's validator set: equal power, ordered as CometBFT
    orders a set (power descending, then address ascending). Also the
    validators' private halves in that order: an Ed25519PrivateKey, or
    sr25519's (scalar, nonce, public key)."""
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey)

    n_ed = int(config["validators"].get("ed25519", 0))
    n_sr = int(config["validators"].get("sr25519", 0))
    members = []
    for i in range(n_ed):
        key = Ed25519PrivateKey.from_private_bytes(_secret(seed, "ed25519", i))
        members.append(("ed25519", key.public_key().public_bytes_raw(), key))
    if n_sr:
        pairs = workers.map(
            "sr_keypair", [_secret(seed, "sr25519", i) for i in range(n_sr)])
        members += [("sr25519", pair[2], pair) for pair in pairs]
    members.sort(key=lambda m: address(m[1]))
    power = int(config["voting_power"])
    vals = ValsetSpec(chain_id=config["chain_id"],
                      schemes=tuple(m[0] for m in members),
                      pubs=tuple(m[1] for m in members),
                      powers=(power,) * len(members))
    return vals, [m[2] for m in members]


def make_ring(config: dict, vals: ValsetSpec, signers: list,
              seed: int) -> list[CommitSpec]:
    """`ring_heights` consecutive full commits: every validator signs for
    the block, with a millisecond-grained stamp inside the height's own
    second, as a live round produces them. Each scheme's lanes get the
    same set of milliseconds, spread evenly over the second, in every
    commit of every seed, in another order: a stamp's length on the wire
    follows its value, so the bytes signed (and every buffer the program
    sizes from them) add up to the same length whatever the seed."""
    rng = random.Random(seed)
    stamp = config["timestamps"]
    n = len(vals.pubs)
    lanes_of: dict[str, list[int]] = {}
    for i, scheme in enumerate(vals.schemes):
        lanes_of.setdefault(scheme, []).append(i)
    ring = []
    for k in range(int(config["ring_heights"])):
        seconds = int(stamp["first_second"]) + k * int(stamp["block_seconds"])
        block_hash, parts_hash = rng.randbytes(32), rng.randbytes(32)
        millis = [0] * n
        for scheme in sorted(lanes_of):
            lanes = lanes_of[scheme]
            spread = [j * 1000 // len(lanes) for j in range(len(lanes))]
            rng.shuffle(spread)
            for i, ms in zip(lanes, spread):
                millis[i] = ms
        ring.append(CommitSpec(
            height=int(config["first_height"]) + k, round=int(config["round"]),
            block_hash=block_hash, parts_total=1, parts_hash=parts_hash,
            stamps=tuple((seconds, ms * 1_000_000) for ms in millis),
            sigs=()))
    msgs = [[vote_sign_bytes(vals.chain_id, c, i) for c in ring]
            for i in range(n)]
    sr_lanes = [i for i in range(n) if vals.schemes[i] == "sr25519"]
    sr_sigs = dict(zip(sr_lanes, workers.map(
        "sr_sign_many", [(signers[i], msgs[i]) for i in sr_lanes])))
    by_lane = [sr_sigs[i] if i in sr_sigs
               else [signers[i].sign(m) for m in msgs[i]]
               for i in range(n)]
    return [CommitSpec(**{**c.__dict__,
                          "sigs": tuple(by_lane[i][k] for i in range(n))})
            for k, c in enumerate(ring)]


class Schedule:
    """Which commit the k-th operation of a run offers: the ring in order
    from a seeded start, every `corrupt_every`-th operation (seeded phase)
    with one signature flipped, in a lane that strides the whole set (a
    golden-ratio stride: any five corrupt operations in a row reach every
    third of the set). Every seed offers the same mix in another order."""

    def __init__(self, traffic: dict, ring_len: int, lanes: int, seed: int):
        rng = random.Random(seed ^ 0x5EED)
        self.ring_len = ring_len
        self.lanes = lanes
        self.start = rng.randrange(ring_len)
        self.every = int(traffic["corrupt_every"])
        self.phase = rng.randrange(self.every)
        self.base = rng.random()

    def op(self, k: int) -> tuple[int, int | None]:
        """(ring index, lane to corrupt or None) of operation k."""
        ring_idx = (self.start + k) % self.ring_len
        if (k + self.phase) % self.every:
            return ring_idx, None
        j = (k + self.phase) // self.every
        return ring_idx, int((self.base + j * GOLDEN) % 1.0 * self.lanes)
