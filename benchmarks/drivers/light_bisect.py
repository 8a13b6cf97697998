"""Driver `light_bisect`: one caller, closed loop; the stream is bisection
after bisection of a light client that skips over a long chain whose
validator set drifts.

An OPERATION is one hop: one call of light.verifier.verify(trusted header,
trusted set, new header, new set, trusting period, now, clock drift, trust
level) with the pair that upstream's verifySkipping (light/client.go) reaches
next, in its order. Each bisection is a client that holds only the trust
root and wants a target height drawn from the seed, uniform over the
chain, each draw independent of the others; no cache is shared between
bisections. A hop over validator sets that have drifted too far
apart is answered "the new set cannot be trusted" after the header checks
and the trusting check's whole selection, with no signature checked and no
device work: those hops are what makes a client bisect, and they are
operations like the others.

A FRESH OBJECT: every operation hands the program a new Commit and a new
ValidatorSet object for the NEW block, made before the operation's t_start:
a client decodes each light block it is sent once, so no hash stamp, no
columns and no address map of an earlier hop ride it. The trusted block
keeps its objects, as a client's store keeps the block it verified.

THE CORRUPT LANE: every `corrupt_every`-th of the operations whose clean
answer is accept (seeded phase) carries the new commit with one signature's
first byte flipped, in a lane that strides, by the golden ratio, the lanes
the new set's 2/3 check takes (the first 334 of 500); some of them are
among the rows the trusting check takes too, and the answer names the same
index whichever check meets it first.

The module brings the cell's seams (benchmarks/README.md): make_data (the
chain with its drift, the bisections walked by the plain reference, only
the light blocks they visit made and signed), build_program_objects,
entries / control_entries (benchmarks/program_light.py), reference_verdicts
(benchmarks/reference/light_ref.py) and sigs_of (the rows a hop really
verifies: none on a hop that cannot be trusted).
"""

from __future__ import annotations

import dataclasses
import hashlib
import random
import time

from benchmarks import workers
from benchmarks.drivers import Record, commit
from benchmarks.reference import light_ref
from benchmarks.reference.commit_ref import CommitSpec, ValsetSpec

GOLDEN = 0.6180339887498949
# rows of the program's resident prefix table (ops/challenge.py TABLE_ROWS;
# a plain number here: nothing of the program is imported). A ring whose
# verified heights are fewer fits the table, and a hop of the window then
# hits it where a client's hop, which verifies a height once, misses:
# make_data says on which side a run's ring falls.
PREFIX_TABLE_ROWS = 256


@dataclasses.dataclass(frozen=True)
class Hop:
    """One operation of the ring: verify `new` from `trusted` (heights)."""

    trusted: int
    new: int
    verdict: str         # the clean answer: "accept" | "reject:untrusted"
    trusting_rows: int   # signatures the trusting check takes (0: adjacent)
    quorum_rows: int     # signatures the new set's 2/3 check takes


def _digest(seed: int, what: str, *n: int) -> bytes:
    return hashlib.sha256(
        f"tpu-bft-bench/light/{seed}/{what}/{n}".encode()).digest()


class Chain:
    """The configuration's chain, as far as a run looks at it: the validator
    set of every epoch and the light blocks of the heights asked for, all
    from (configuration, seed). A block is made unsigned (its header, the
    stamps of its commit) and signed only if a hop verifies it."""

    def __init__(self, config: dict, seed: int):
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PrivateKey)

        self.config, self.seed = config, seed
        self.chain_id = config["chain_id"]
        self.heights = int(config["heights"])
        drift = config["drift"]
        self.epoch_heights = int(drift["epoch_heights"])
        n = int(config["validators"]["ed25519"])
        rotated = int(drift["rotated_per_epoch"])
        power = int(config["voting_power"])

        def member(i: int):
            key = Ed25519PrivateKey.from_private_bytes(
                _digest(seed, "key", i))
            pub = key.public_key().public_bytes_raw()
            return light_ref.address(pub), pub, key

        # CometBFT's order: power descending (equal here), address ascending
        members = sorted(member(i) for i in range(n))
        self.sets: list[ValsetSpec] = []
        self.signers: list[dict] = []   # per epoch: {pub: private key}
        made = n
        for epoch in range(self.heights // self.epoch_heights + 1):
            if epoch:
                rng = random.Random(f"{seed}/epoch/{epoch}")
                leaving = set(rng.sample(range(n), rotated))
                members = sorted(
                    [m for i, m in enumerate(members) if i not in leaving]
                    + [member(made + j) for j in range(rotated)])
                made += rotated
            self.sets.append(ValsetSpec(
                chain_id=self.chain_id, schemes=("ed25519",) * n,
                pubs=tuple(m[1] for m in members), powers=(power,) * n))
            self.signers.append({m[1]: m[2] for m in members})
        self.distinct_keys = made
        self._set_hashes: dict[int, bytes] = {}
        self._blocks: dict[int, light_ref.LightBlockSpec] = {}
        stamp, light = config["timestamps"], config["light"]
        self._first_second = int(stamp["first_second"])
        self._block_seconds = int(stamp["block_seconds"])
        self.params = light_ref.Params(
            trusting_period_ns=int(light["trusting_period_s"]) * 10**9,
            # the time of the last height plus one block, never the clock
            now_ns=(self._second(self.heights) + self._block_seconds) * 10**9,
            max_clock_drift_ns=int(light["max_clock_drift_s"]) * 10**9,
            trust_level=tuple(light["trust_level"]))
        self.pivot = tuple(light["pivot"])

    def _second(self, height: int) -> int:
        return self._first_second + (height - 1) * self._block_seconds

    def epoch(self, height: int) -> int:
        return height // self.epoch_heights

    def set_hash(self, epoch: int) -> bytes:
        if epoch not in self._set_hashes:
            self._set_hashes[epoch] = light_ref.valset_hash(self.sets[epoch])
        return self._set_hashes[epoch]

    def block(self, height: int) -> light_ref.LightBlockSpec:
        """The light block of a height, unsigned until sign() has seen
        it: every validator of the height's set votes for the block, with
        a millisecond-grained stamp inside the height's own second, the
        same milliseconds in every commit in another order (hub-150's
        stamps)."""
        if height in self._blocks:
            return self._blocks[height]
        seed, vals = self.seed, self.sets[self.epoch(height)]
        n = len(vals.pubs)
        header = light_ref.HeaderSpec(
            chain_id=self.chain_id, height=height,
            time=(self._second(height), 0),
            last_block_hash=_digest(seed, "block", height - 1),
            last_parts_total=1,
            last_parts_hash=_digest(seed, "parts", height - 1),
            last_commit_hash=_digest(seed, "last-commit", height),
            data_hash=_digest(seed, "data", height),
            validators_hash=self.set_hash(self.epoch(height)),
            next_validators_hash=self.set_hash(
                min(self.epoch(height + 1), len(self.sets) - 1)),
            consensus_hash=_digest(seed, "consensus"),
            app_hash=_digest(seed, "app", height),
            last_results_hash=_digest(seed, "results", height),
            evidence_hash=_digest(seed, "evidence", height),
            proposer_address=light_ref.address(vals.pubs[height % n]))
        millis = [j * 1000 // n for j in range(n)]
        random.Random(f"{seed}/stamps/{height}").shuffle(millis)
        second = self._second(height)
        spec = light_ref.LightBlockSpec(
            header=header, vals=vals, commit=CommitSpec(
                height=height, round=int(self.config["round"]),
                block_hash=light_ref.header_hash(header), parts_total=1,
                parts_hash=_digest(seed, "parts", height),
                stamps=tuple((second, ms * 1_000_000) for ms in millis),
                sigs=()))
        self._blocks[height] = spec
        return spec

    def sign(self, heights) -> int:
        """Sign the commits of `heights`; returns the signatures made."""
        made = 0
        for height in heights:
            spec = self._blocks[height]
            keys = self.signers[self.epoch(height)]
            rows = [(scheme, pub, i) for i, (scheme, pub) in enumerate(
                zip(spec.vals.schemes, spec.vals.pubs))]
            lanes = light_ref.lanes_of(
                self.chain_id,
                dataclasses.replace(spec.commit, sigs=(b"",) * len(rows)),
                rows)
            sigs = tuple(keys[pub].sign(msg) for _s, pub, msg, _ in lanes)
            self._blocks[height] = dataclasses.replace(
                spec, commit=dataclasses.replace(spec.commit, sigs=sigs))
            made += len(sigs)
        return made

    def bisect(self, target: int, root: int) -> list[Hop]:
        """The hops upstream's verifySkipping (light/client.go:706-775)
        makes from the trust root to `target`, in its order, each answered
        by the plain reference over blocks whose signatures are sound."""
        hops: list[Hop] = []
        cache, depth, verified = [target], 0, root
        while True:
            new = cache[depth]
            first, second, verdict = light_ref.hop_rows(
                self.block(verified), self.block(new), self.params)
            hops.append(Hop(verified, new, verdict or "accept",
                            len(first), len(second)))
            if verdict == "reject:untrusted":
                if depth == len(cache) - 1:
                    cache.append(verified + (new - verified)
                                 * self.pivot[0] // self.pivot[1])
                depth += 1
            elif verdict:
                raise RuntimeError(
                    f"the chain as configured answers {verdict} on the hop "
                    f"{verified} -> {new}")
            elif depth == 0:
                return hops
            else:
                verified, cache, depth = new, cache[:depth], 0


class HopSchedule:
    """Which hop the k-th operation of a run is, and whether it is corrupt:
    the ring in order from its first hop; of the operations whose clean
    answer is accept every `corrupt_every`-th (seeded phase) with one
    signature flipped, in a lane that strides the new set's quorum rows
    by the golden ratio."""

    def __init__(self, traffic: dict, hops: list[Hop], seed: int):
        rng = random.Random(seed ^ 0x5EED)
        self.hops = hops
        self.every = int(traffic["corrupt_every"])
        self.phase = rng.randrange(self.every)
        self.base = rng.random()
        self.accepts_before = [0]
        for hop in hops:
            self.accepts_before.append(
                self.accepts_before[-1] + (hop.verdict == "accept"))

    def op(self, k: int) -> tuple[int, int | None]:
        """(ring index, lane to corrupt or None) of operation k."""
        cycle, ring_idx = divmod(k, len(self.hops))
        hop = self.hops[ring_idx]
        if hop.verdict != "accept":
            return ring_idx, None
        j = (cycle * self.accepts_before[-1]
             + self.accepts_before[ring_idx] + self.phase)
        if j % self.every:
            return ring_idx, None
        return ring_idx, int((self.base + j // self.every * GOLDEN)
                             % 1.0 * hop.quorum_rows)


# ------------------------------------------------------------------- seams


def make_data(cell, seed: int) -> None:
    """The chain, the ring of hops and the order of operations, from the
    seed; plain Python, nothing of JAX or of the program."""
    t0 = time.perf_counter()
    config = cell.config
    chain = cell.chain = Chain(config, seed)
    root = int(config["light"]["trust_root_height"])
    # the targets: independent draws, uniform over root + 1 .. heights
    targets = random.Random(seed ^ 0xB15EC7)
    hops: list[Hop] = []
    bisections = 0
    while len(hops) < int(config["ring_hops"]):
        hops += chain.bisect(targets.randint(root + 1, chain.heights), root)
        bisections += 1
    cell.hops = hops[:int(config["ring_hops"])]
    new_heights = sorted({hop.new for hop in cell.hops})
    verified = {hop.new for hop in cell.hops if hop.verdict == "accept"}
    signatures = chain.sign(new_heights)
    cell.schedule = HopSchedule(cell.traffic, cell.hops, seed)
    untrusted = sum(hop.verdict != "accept" for hop in cell.hops)
    print(f"[set-up] light-bisect: {len(chain.sets)} epochs, "
          f"{chain.distinct_keys} distinct keys; {bisections} bisections fill "
          f"{len(cell.hops)} hops ({untrusted} answered untrusted, "
          f"{len(cell.hops) - untrusted} verified), new blocks on "
          f"{len(new_heights)} distinct heights ({len(verified)} of them "
          f"verified on the device: "
          f"{'over' if len(verified) > PREFIX_TABLE_ROWS else 'within'} the "
          f"prefix table's {PREFIX_TABLE_ROWS} rows), {signatures} "
          f"signatures from seed {seed}: {time.perf_counter() - t0:.1f} s",
          flush=True)


def build_program_objects(cell) -> None:
    from benchmarks import program, program_light

    heights = sorted({h for hop in cell.hops for h in (hop.trusted, hop.new)})
    cell.blocks = {h: program_light.build_light_block(cell.chain.block(h))
                   for h in heights}
    # a second set object a new block, never handed to the program: what
    # every operation's fresh ValidatorSet is a copy of
    cell.pristine = {
        h: program.build_validator_set(cell.chain.block(h).vals)
        for h in {hop.new for hop in cell.hops}}
    cell.verify_args = program_light.verify_args(cell.chain.params)


def entries() -> dict:
    from benchmarks import program_light

    return program_light.entries()


def control_entries() -> dict:
    from benchmarks import program_light

    return program_light.control_entries()


def _specs(cell, record):
    hop = cell.hops[record.ring_idx]
    new = cell.chain.block(hop.new)
    if record.corrupt_lane is not None:
        new = dataclasses.replace(
            new, commit=new.commit.with_flipped(record.corrupt_lane))
    return cell.chain.block(hop.trusted), new


def reference_verdicts(cell, sample: list) -> tuple[dict, int]:
    """({record.k: light_ref's verdict}, lanes verified) for the sampled
    hops; the lanes run in plain worker processes, shared between hops."""
    params = cell.chain.params
    hops = {r.k: _specs(cell, r) for r in sample}
    lanes = sorted({lane for trusted, new in hops.values()
                    for _i, lane in light_ref.hop_lanes(trusted, new,
                                                        params)[0]})
    memo = dict(zip(lanes, workers.map("verify_lane", lanes)))
    return ({k: light_ref.verify(trusted, new, params, memo.__getitem__)
             for k, (trusted, new) in hops.items()}, len(lanes))


def sigs_of(cell, record) -> dict:
    """The signatures the hop puts on the device: the trusting check's
    rows and the new set's, or none where the hop is answered untrusted."""
    hop = cell.hops[record.ring_idx]
    if hop.verdict != "accept":
        return {}
    return {"ed25519": hop.trusting_rows + hop.quorum_rows}


# -------------------------------------------------------------------- loop


class Driver(commit.Driver):
    """commit's window (operations 0, 1, 2 ... until the seconds have
    passed) over hops."""

    def __init__(self, cell, entries: dict):
        # not at the module's top: run.load_cell imports this module before
        # the data is made, and nothing of JAX may be imported by then
        from jax.profiler import TraceAnnotation

        from benchmarks import program_light

        self.annotate = TraceAnnotation
        self.program = program_light
        self.cell = cell
        self.verify = entries["verify"]

    def _one(self, k: int) -> Record:
        cell = self.cell
        ring_idx, lane = cell.schedule.op(k)
        hop = cell.hops[ring_idx]
        trusted = cell.blocks[hop.trusted]
        header, vals = self.program.fresh_block(
            cell.blocks[hop.new], cell.pristine[hop.new], lane)
        t0 = time.perf_counter()
        with self.annotate("bench.light_verify"):
            verdict = self.program.verdict_of(lambda: self.verify(
                trusted.signed_header, trusted.validator_set, header, vals,
                *cell.verify_args))
        return Record(k, ring_idx, lane, verdict, t0, time.perf_counter())

    def warm(self) -> int:
        """One pass over the ring in the window's own order: every derive
        geometry, every key the ring can ask for (the key table holds all
        of them from here on: the window runs no delta upload) and the
        corrupt operations that fall in it."""
        for k in range(len(self.cell.hops)):
            self._one(k)
        return len(self.cell.hops)
