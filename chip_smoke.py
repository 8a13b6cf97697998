#!/usr/bin/env python3
"""chip_smoke.py — does the verify path still run on the chip?

One process, one TPU chip, no options: `python3 chip_smoke.py`. It sets
the device plane up exactly as a node does (node.configure_device_plane),
warms the bucket shapes it will use, verifies commits at published
committee sizes through the node's own path (types.validation.verify_commit
-> ScheduledBatchVerifier -> VerifyScheduler -> residency table -> device
challenge -> Pallas kernel), runs a 4-validator in-process net that commits
kvstore txs, and after every phase ACCOUNTS FOR THE RUNG: the product's
fault ladder (TPU Pallas -> XLA -> host oracle) returns right verdicts from
any rung, so a smoke that only checked verdicts would pass on the CPU.

`python3 chip_smoke.py --mesh` (four chips; the builder runs it, the driver
never does) runs ONLY the 10,240-validator commit and the mixed
5,120+5,120 mega-commit through VerifyMesh over every chip, and the same
two commits on one chip of the same process as the comparison. An ed25519
shard is the one-chip trip on its own chip (device challenge, the Pallas
program): every one must show as a pallas.ed25519 success; sr25519 shards
keep the XLA ladder.

The last line of stdout is one JSON object,
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}};
everything else worth knowing is printed on earlier lines. Any failed
assertion ends the run at once: "ok": false, exit code 1. There is no CPU
mode, no size option and no environment switch — the phases are plain
functions of their sizes, and tests/test_chip_smoke.py calls them small.

The readings printed per phase are SMOKE READINGS (host clock around
resolved results, a handful of repeats) — not benchmark results.
"""

from __future__ import annotations

import asyncio
import collections
import json
import os
import random
import statistics
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))

SEED = 22
CHAIN_ID = "chip-smoke"
# (name, ed25519 validators, sr25519 validators) — BASELINE.json configs
# 2 and 5 plus the all-ed25519 10k committee; buckets 256 / 10,240 /
# 6,144 + 6,144
PHASE_A_SHAPES = (("hub-150", 150, 0), ("committee-10240", 10240, 0),
                  ("mixed-5120+5120", 5120, 5120))
MESH_SHAPES = PHASE_A_SHAPES[1:]
NET_VALIDATORS = 4
NET_CHAIN_ID = CHAIN_ID + "-net"
NET_HEIGHTS = 5
NET_TXS = 6
NET_DEADLINE_S = 180.0
REPEATS = 5
WARMUP_WATCHDOG_S = 1800.0
MESH_CHIPS = 4
# programs one new challenge-derive geometry builds at most: the derive
# program alone (it slices R and s out of its flat block and checksums
# it, so no later program's shape follows the block's length)
PROGRAMS_PER_DERIVE_GEOMETRY = 1
# env switches that exist to take the device OFF the path
REFUSED_ENV = ("CBFT_NO_PALLAS", "CBFT_CHAOS")


class SmokeFailure(Exception):
    """A smoke assertion failed; the run stops."""


def say(msg: str = "") -> None:
    print(msg, flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ------------------------------------------------------------------ device


def refuse_off_device_env(environ=os.environ) -> None:
    armed = [k for k in REFUSED_ENV if environ.get(k)]
    require(not armed, f"refusing to start with {armed} set: they exist to "
            "take the device off the verify path")


def probe_device(want_count: int) -> dict:
    """Device first: a TPU or nothing. Never continues on the CPU."""
    import jax
    import jaxlib

    from cometbft_tpu import native
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.ops import hashvec

    device = crypto_batch.device_info()
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 - version string only
        libtpu = "unknown"
    say(f"device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}  jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    say(f"host staging: hashvec rung={hashvec.active_rung()} "
        f"native cores={native.status()}")
    require(device["platform"] == "tpu",
            f"JAX reports platform {device['platform']!r}, not a TPU")
    require(device["count"] == want_count,
            f"need {want_count} chip(s), JAX reports {device['count']}")
    return device


def boot_device_plane() -> None:
    """A Config the way `init` builds it, backend "tpu", applied through
    the same callable Node.__init__ uses."""
    from cometbft_tpu.config import Config
    from cometbft_tpu.libs import log as cmtlog
    from cometbft_tpu.node import node as node_mod
    from cometbft_tpu.ops import compile_cache

    config = Config(home="")
    config.crypto.backend = "tpu"
    config.validate_basic()
    record = node_mod.configure_device_plane(
        config.crypto, cmtlog.Logger(level=cmtlog.parse_level("info")))
    cache_dir = record["compile_cache"]
    require(cache_dir is not None, "compile cache was not armed")
    placed = ("placed by " + compile_cache.ENV_VAR
              if os.environ.get(compile_cache.ENV_VAR)
              else "checkout default")
    say(f"compile cache: {cache_dir} ({placed})")


# ---------------------------------------------------------------- workload


def make_commit(n_ed: int, n_sr: int, seed: int, chain_id: str = CHAIN_ID,
                nanos: list[int] | None = None):
    """A seeded validator set (n_ed ed25519 + n_sr sr25519 keys, equal
    power) and a full commit for it: every validator signs the canonical
    precommit bytes, with millisecond-grained timestamps inside one
    second as a live round produces them (or the given nanosecond field
    per validator). Returns (vals, block_id, commit)."""
    from cometbft_tpu.crypto import ed25519, sr25519
    from cometbft_tpu.types.basic import BlockID, BlockIDFlag, PartSetHeader
    from cometbft_tpu.types.commit import Commit, CommitSig
    from cometbft_tpu.types.validator import Validator, ValidatorSet
    from cometbft_tpu.utils import cmttime

    rng = random.Random(seed)
    privs = [ed25519.gen_priv_key_from_secret(
        b"chip-smoke-ed-%d-%d" % (seed, i)) for i in range(n_ed)]
    privs += [sr25519.gen_priv_key_from_secret(
        b"chip-smoke-sr-%d-%d" % (seed, i)) for i in range(n_sr)]
    vals = ValidatorSet([Validator.new(p.pub_key(), 10) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    block_id = BlockID(
        hash=rng.randbytes(32),
        part_set_header=PartSetHeader(total=1, hash=rng.randbytes(32)))
    if nanos is None:
        nanos = [rng.randrange(1000) * 1_000_000 for _ in privs]
    sigs = [CommitSig(
        block_id_flag=BlockIDFlag.COMMIT, validator_address=v.address,
        timestamp=cmttime.Timestamp(1_790_000_000, ns))
        for v, ns in zip(vals.validators, nanos)]
    commit = Commit(height=7, round_=0, block_id=block_id, signatures=sigs)
    for i, v in enumerate(vals.validators):
        sigs[i].signature = by_addr[v.address].sign(
            commit.vote_sign_bytes(chain_id, i))
    return vals, block_id, commit


def vote_geometry_nanos(n: int):
    """The nanosecond fields of n votes, once for every challenge-derive
    geometry a full flush of for-block votes at round 0 can ask for.
    ops.challenge.plan_batch compiles against (prefix length, varying
    suffix bytes, common trailing bytes); for votes of one height and
    round the suffix is the timestamp plus the chain-id trailer, so the
    geometry follows two things the clock decides: how many varint bytes
    the millisecond-grained nanos field takes (none at 0 ms, 3 at 1-2 ms,
    4 up to 268 ms, 5 above) and which of them is the last one to differ
    between the votes (none when the stamps are identical)."""
    yield [0] * n
    for length in (3, 4, 5):
        top = 128 ** (length - 1)
        yield [top] * n
        for last_differing in range(length):
            yield [top + (i % 3) * 128 ** last_differing for i in range(n)]


def fresh(commit, corrupt_idx: int | None = None):
    """A new Commit object over the same signatures (the sign-bytes cache
    rides the object: a node verifies a commit object once), optionally
    with one signature's first byte flipped."""
    import dataclasses

    from cometbft_tpu.types.commit import Commit

    sigs = list(commit.signatures)
    if corrupt_idx is not None:
        cs = sigs[corrupt_idx]
        sigs[corrupt_idx] = dataclasses.replace(
            cs, signature=bytes([cs.signature[0] ^ 1]) + cs.signature[1:])
    return Commit(height=commit.height, round_=commit.round_,
                  block_id=commit.block_id, signatures=sigs)


def commit_rows(vals, commit, chain_id: str = CHAIN_ID):
    """(pubkeys, sign-bytes, sigs) for every signature of the commit, in
    commit order — the very rows verify_commit batches, sign-bytes still
    factored (shared prefix + per-lane suffix) as validation._commit_rows
    hands them to the verifier."""
    rows = commit.vote_sign_bytes_all(chain_id)
    return ([v.pub_key for v in vals.validators],
            rows.rows_for(list(range(len(commit.signatures)))),
            [cs.signature for cs in commit.signatures])


def verifier_mask(pubs, msgs, sigs) -> list[bool]:
    """Per-lane mask of the SAME verifier verify_commit uses."""
    from cometbft_tpu.crypto import batch as crypto_batch

    bv = crypto_batch.create_mixed_batch_verifier()
    for row in zip(pubs, msgs, sigs):
        bv.add(*row)
    return bv.verify()[1]


_oracle_memo: dict[int, list[bool]] = {}


def oracle_mask(commit, rows) -> list[bool]:
    """The host oracle over every lane of a workload's clean commit, once
    per process (the mesh run compares two planes against the same
    oracle; 10,240 pure-Python verifies are half a minute)."""
    if id(commit) not in _oracle_memo:
        _oracle_memo[id(commit)] = [oracle_lane(*r) for r in zip(*rows)]
    return list(_oracle_memo[id(commit)])


def oracle_lane(pub, msg, sig: bytes) -> bool:
    """The exact host oracle for one lane (crypto/ed25519_math,
    crypto/sr25519_math) — independent of every device path."""
    from cometbft_tpu.crypto import ed25519_math, sr25519_math
    from cometbft_tpu.libs.prefixrows import as_bytes

    msg = as_bytes(msg)
    if pub.type_() == "sr25519":
        return bool(sr25519_math.verify(pub.bytes_(), msg, sig))
    return bool(ed25519_math.verify_zip215(pub.bytes_(), msg, sig))


# ------------------------------------------------------------- accounting


class Accounting:
    """Reads the rung-accounting surface (ops.dispatch.health_snapshot,
    libs.metrics.crypto_metrics, the kernels' own counters) as ONE compact
    snapshot, relative to the last mark(): supervisors and resettable
    counters are reset at the mark, process-cumulative metrics are
    differenced against it."""

    def __init__(self) -> None:
        self._base: dict = {}
        # programs built (every jit miss, whether XLA compiled it or the
        # persistent cache served it) and how many of those the
        # persistent cache served
        self._compiles = 0
        self.cache_hits = 0
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def close(self) -> None:
        """Stop listening (a process that outlives its Accounting)."""
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self._compiles += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @staticmethod
    def _cumulative() -> dict:
        from cometbft_tpu.libs import metrics

        cm = metrics.crypto_metrics()
        mm = metrics.mesh_metrics()
        return {
            "fallback_verifies": cm.fallback_verifies.total(),
            "mask_oracle_disagreement": cm.mask_oracle_disagreement.total(),
            "transfer_checksum_mismatch":
                cm.transfer_checksum_mismatch.total(),
            "mask_echo_mismatch": cm.mask_echo_mismatch.total(),
            "mesh_fallback_total": mm.mesh_fallback_total.total(),
            "device_batches_ed25519": cm.device_batches.value("ed25519"),
            "device_batches_sr25519": cm.device_batches.value("sr25519"),
            "device_lanes": cm.device_lanes.total(),
        }

    def cache_report(self) -> str:
        """Was the persistent compile cache cold or warm? Only programs
        that took over 2 s to compile are kept in it; tracing is never
        cached."""
        state = "warm" if self.cache_hits else "cold"
        return (f"compile cache {state}: {self._compiles} programs built, "
                f"{self.cache_hits} of them loaded from the cache")

    def mark(self) -> None:
        """Start a phase from zero."""
        from cometbft_tpu import sched
        from cometbft_tpu.ops import challenge, dispatch, ed25519_kernel
        from cometbft_tpu.ops import residency

        dispatch.reset_supervision()
        challenge.reset_stats()
        residency.reset_send_stats()
        ed25519_kernel.reset_fetch_stats()
        self._base = self._cumulative()
        self._base["compiles"] = self._compiles
        h = sched.get().health()
        self._base["sched_batches"] = h["batches"]
        self._base["sched_class_rows"] = dict(h["class_rows"])
        self._base["mesh_shards"] = {
            i: c["shards_total"] for i, c in
            (dispatch.health_snapshot()["mesh"].get("chips") or {}).items()}

    def snapshot(self) -> dict:
        from cometbft_tpu.ops import dispatch, ed25519_kernel

        health = dispatch.health_snapshot()
        counters = {k: int(v - self._base.get(k, 0))
                    for k, v in self._cumulative().items()}
        vs = health["verify_sched"]
        base_rows = self._base.get("sched_class_rows", {})
        base_shards = self._base.get("mesh_shards", {})
        mesh = health["mesh"]
        return {
            "configured_backend": health["configured_backend"],
            "active_backend": health["active_backend"],
            "device": health["device"],
            "supervisors": {
                name: {"breaker": s["breaker"]["state"],
                       "failures": s["failures"], "retries": s["retries"],
                       "successes": s["successes"],
                       "last_error": s["last_error"]}
                for name, s in health["supervisors"].items()},
            "counters": counters,
            "challenge": health["staging"]["challenge"]["counters"],
            "wire": {p: health["staging"]["wire"][p]["sends"]
                     for p in ("indexed", "delta", "full")},
            "bytes_per_sig": health["staging"]["wire"].get(
                "steady_state_bytes_per_sig"),
            "table_devices": {
                name: t["devices"] for name, t in
                health["staging"]["wire"]["tables"].items()},
            "fetch": health["staging"]["fetch"],
            "link": health["link"],
            "dispatched_shapes": ed25519_kernel.dispatched_shapes(),
            "compiles": self._compiles - self._base.get("compiles", 0),
            "sched": {
                "batches": vs["batches"] - self._base.get(
                    "sched_batches", 0),
                "class_rows": {k: v - base_rows.get(k, 0)
                               for k, v in vs["class_rows"].items()},
                "chaos_fallbacks": vs["chaos_fallbacks"],
            },
            "mesh": {k: mesh.get(k) for k in (
                "active", "devices", "live", "evictions", "readmissions",
                "redispatched_batches", "fallbacks", "shard_program")} | {
                "chips": {i: {"successes": c["successes"],
                              "failures": c["failures"],
                              "shards": (c["shards_total"]
                                         - base_shards.get(i, 0)),
                              "shard_lanes": c["shard_lanes"],
                              "array_devices": c["array_devices"]}
                          for i, c in (mesh.get("chips") or {}).items()}},
        }


def check_rungs(snap: dict, *, aligned_ed: int, aligned_sr: int,
                warmed: set[int], want_challenge: bool = True,
                mesh_chips: int = 0) -> list[str]:
    """THE assertion this script exists for: which rung served? Returns
    the list of violations (empty = every batch of the phase ran on the
    rung it should). aligned_ed / aligned_sr: how many 128-aligned device
    batches the phase dispatched per scheme on the single-chip plane —
    each must show as one pallas.<scheme> success. want_challenge: the
    phase's batches are wide enough that the device-challenge planner
    must have taken some (whatever it took must have been derived on the
    device either way). mesh_chips > 0 checks the mesh plane instead:
    aligned_ed is then the phase's ed25519 SHARDS on 128-aligned buckets
    (each the one-chip trip on its own chip: one pallas.ed25519 success a
    shard, and the mesh must say its ed25519 shards run Pallas); sr25519
    shards run the XLA ladder, so aligned_sr is 0 there."""
    bad: list[str] = []

    def need(cond: bool, what: str) -> None:
        if not cond:
            bad.append(what)

    need(snap["configured_backend"] == "tpu"
         and snap["active_backend"] == "tpu",
         f"backend configured={snap['configured_backend']} "
         f"active={snap['active_backend']}, want tpu/tpu")
    need((snap.get("device") or {}).get("platform") == "tpu",
         f"health reports device {snap.get('device')}")
    for name, s in snap["supervisors"].items():
        need(s["breaker"] == "closed", f"{name}: breaker {s['breaker']}")
        need(s["failures"] == 0, f"{name}: {s['failures']} failures")
        need(s["retries"] == 0, f"{name}: {s['retries']} retries")
        need(not s["last_error"], f"{name}: last_error {s['last_error']}")
    sups = snap["supervisors"]
    c = snap["counters"]
    for scheme, want in (("ed25519", aligned_ed), ("sr25519", aligned_sr)):
        got = sups.get(f"pallas.{scheme}", {}).get("successes", 0)
        need(got == want,
             f"pallas.{scheme}: {got} successes for {want} 128-aligned "
             "batches — a batch was served by a rung below Pallas"
             if got < want else
             f"pallas.{scheme}: {got} successes, expected {want}")
        if not mesh_chips:
            need(c[f"device_batches_{scheme}"] >= want,
                 f"{scheme}: {c[f'device_batches_{scheme}']} device "
                 f"batches counted, {want} aligned batches dispatched")
    for k in ("fallback_verifies", "mask_oracle_disagreement",
              "transfer_checksum_mismatch", "mask_echo_mismatch",
              "mesh_fallback_total"):
        need(c[k] == 0, f"{k} = {c[k]}")
    need(snap["sched"]["chaos_fallbacks"] == 0,
         f"scheduler chaos_fallbacks = {snap['sched']['chaos_fallbacks']}")
    ch = snap["challenge"]
    plans = ch.get("plans", 0)
    derived = sups.get("ed25519.challenge", {}).get("successes", 0)
    if want_challenge:
        need(plans > 0, "no batch cleared the device-challenge planner")
    need(derived == plans,
         f"{plans} batches planned a device challenge, {derived} derive "
         "programs ran on the device")
    need((ch.get("lanes_device", 0) > 0) == (plans > 0),
         f"challenge plane: {plans} plans but lanes_device = "
         f"{ch.get('lanes_device', 0)}")
    for k in ("plan_upload_failed", "plan_breaker_open", "derive_failed",
              "batch_host_fallback", "enc_not_resident"):
        need(ch.get(k, 0) == 0, f"challenge plane: {k} = {ch.get(k)}")
    need(snap["wire"]["indexed"] > 0, "no indexed (resident-table) send")
    need(set(snap["dispatched_shapes"]) <= warmed,
         f"dispatched shapes {snap['dispatched_shapes']} outside the "
         f"warmed set {sorted(warmed)}: a shape compiled inside a phase")
    # nothing compiles inside a phase: the warm-up ran every program. The
    # one thing a live net can still ask for is a derive geometry outside
    # the warmed family (a nil vote, a round above 0); it is counted by
    # the challenge plane itself and bounds what may have been built.
    unwarmed = ch.get("derive_programs", 0)
    need(snap["compiles"] <= PROGRAMS_PER_DERIVE_GEOMETRY * unwarmed,
         f"{snap['compiles']} program(s) built inside the phase with "
         f"{unwarmed} challenge-derive geometries outside the warmed set")
    mesh = snap["mesh"]
    if mesh_chips:
        need(mesh.get("active") is True, "mesh not active")
        if aligned_ed:
            program = (mesh.get("shard_program") or {}).get("ed25519")
            need(program == "pallas",
                 f"the mesh reports ed25519 shards on {program!r}, not on "
                 "the Pallas program")
        need(mesh.get("devices") == mesh_chips
             and mesh.get("live") == mesh_chips,
             f"mesh devices={mesh.get('devices')} live={mesh.get('live')}, "
             f"want {mesh_chips}")
        for k in ("evictions", "readmissions", "redispatched_batches",
                  "fallbacks"):
            need(mesh.get(k) == 0, f"mesh {k} = {mesh.get(k)}")
        for i, chip in mesh["chips"].items():
            need(chip["successes"] > 0 and chip["failures"] == 0,
                 f"mesh chip {i}: {chip}")
        # nothing put everything on the first device: each chip's shard
        # arrays on its own device, and so its resident tables
        placed = [tuple(c["array_devices"]) for c in mesh["chips"].values()]
        need(all(len(p) == 1 for p in placed)
             and len(set(placed)) == mesh_chips,
             f"shard arrays live on {placed}, want one distinct device "
             "per chip")
        tables: dict[str, set[str]] = {}
        for name, devs in snap["table_devices"].items():
            scheme, _, put_key = name.partition("/")
            if put_key.startswith("dev"):
                tables.setdefault(scheme, set()).update(devs or ())
        for scheme, devs in tables.items():
            need(len(devs) == mesh_chips,
                 f"{scheme} resident tables live on {sorted(devs)}, want "
                 f"{mesh_chips} distinct devices")
    return bad


_IDLE_SUPERVISOR = {"breaker": "closed", "failures": 0, "retries": 0,
                    "successes": 0, "last_error": None}


def assert_rungs(label: str, acct: Accounting, **expect) -> dict:
    snap = acct.snapshot()
    idle = sorted(n for n, s in snap["supervisors"].items()
                  if s == _IDLE_SUPERVISOR)
    shown = dict(snap, idle_supervisors=idle, supervisors={
        n: s for n, s in snap["supervisors"].items() if n not in idle})
    say(f"[{label}] rung accounting: "
        + json.dumps(shown, separators=(",", ":"), sort_keys=True))
    bad = check_rungs(snap, **expect)
    require(not bad, f"[{label}] rung accounting failed: " + "; ".join(bad))
    say(f"[{label}] rung accounting: OK")
    return snap


# ------------------------------------------------------------------ phases


def _verify(vals, block_id, commit, chain_id: str = CHAIN_ID) -> None:
    from cometbft_tpu.types import validation

    validation.verify_commit(chain_id, vals, block_id, commit.height, commit)


def _expect_bad_signature(vals, block_id, commit, idx: int) -> None:
    from cometbft_tpu.types import validation

    try:
        _verify(vals, block_id, commit)
    except validation.ErrInvalidCommitSignature as exc:
        require(f"(#{idx})" in str(exc),
                f"verify_commit blamed the wrong signature: {exc}")
        return
    raise SmokeFailure(
        f"verify_commit accepted a commit with signature #{idx} corrupted")


def warm_up(workloads: list, net_validators: int, seed: int) -> float:
    """Compile every program the phases will run, by running exactly what
    they run (a clean and a corrupted verify per shape, plus commits of
    the net's size in every challenge-derive geometry its vote flushes
    can take), with the watchdog raised for the warm-up only: a cold XLA
    ladder rung is about a minute, and a compile that outlasts the
    configured watchdog would be recorded as a device failure and served
    by the host oracle. Timed as set-up."""
    from cometbft_tpu.ops import dispatch

    configured = dispatch.watchdog_timeout()
    dispatch.configure(watchdog_timeout=WARMUP_WATCHDOG_S)
    t0 = time.perf_counter()
    try:
        if net_validators:
            t1 = time.perf_counter()
            geometries = 0
            for nanos in vote_geometry_nanos(net_validators):
                vals, bid, commit = make_commit(
                    net_validators, 0, seed + 99, NET_CHAIN_ID, nanos)
                _verify(vals, bid, commit, NET_CHAIN_ID)
                geometries += 1
            # a 2-vote flush stays under the device-challenge planner's
            # lane floor and rides the host-challenge program instead
            pubs, msgs, sigs = commit_rows(vals, commit, NET_CHAIN_ID)
            verifier_mask(pubs[:2], msgs[:2], sigs[:2])
            say(f"[warm-up] {net_validators}-validator commits (net bucket, "
                f"{geometries} vote-timestamp geometries): "
                f"{time.perf_counter() - t1:.1f} s")
        for name, vals, bid, commit, bad_idx in workloads:
            t1 = time.perf_counter()
            _verify(vals, bid, fresh(commit))
            _expect_bad_signature(vals, bid, fresh(commit, bad_idx), bad_idx)
            say(f"[warm-up] {name}: {time.perf_counter() - t1:.1f} s")
    finally:
        dispatch.configure(watchdog_timeout=configured)
    return time.perf_counter() - t0


def mesh_shard_lanes(n: int) -> list[int]:
    """The lane counts of the shards the mesh cuts n rows of one scheme
    into (its own plan over its live chips, under the class a commit's
    verification runs in)."""
    from cometbft_tpu import sched
    from cometbft_tpu.ops import ed25519_kernel as EK
    from cometbft_tpu.parallel import mesh as verify_mesh

    vm = verify_mesh.get()
    return [EK.bucket_size(hi - lo) for _chip, lo, hi in vm._plan(
        n, sched.current_class(), vm.live_chips())] if n else []


def _aligned(scheme: str, n: int, batches: int, mesh_chips: int) -> int:
    """How many of a phase's device batches of one scheme are Pallas's to
    serve. One chip: each of the `batches` verifies is one batch, Pallas's
    when the scheme's n rows pad to a 128-aligned bucket. The mesh: every
    ed25519 SHARD is a batch of the one-chip trip, Pallas's when its own
    bucket is 128-aligned; sr25519 shards run the XLA ladder by design."""
    from cometbft_tpu.ops import ed25519_kernel as EK
    from cometbft_tpu.ops import pallas_verify as PV

    if mesh_chips:
        lanes = mesh_shard_lanes(n) if scheme == "ed25519" else []
        return batches * sum(b % PV.LANES == 0 for b in lanes)
    if not n or EK.bucket_size(n) % PV.LANES:
        return 0
    return batches


def phase_a(workloads: list, acct: Accounting, warmed: set[int],
            repeats: int, mesh_chips: int = 0,
            label: str = "phase A") -> dict:
    """Commits at published widths through the node's own path."""
    readings = {}
    for name, vals, bid, commit, bad_idx in workloads:
        n = len(commit.signatures)
        lanes = collections.Counter(
            v.pub_key.type_() for v in vals.validators)
        acct.mark()
        walls = []
        for _ in range(repeats):
            c = fresh(commit)
            t0 = time.perf_counter()
            _verify(vals, bid, c)  # returns after the masks are resolved
            walls.append((time.perf_counter() - t0) * 1e3)
        _expect_bad_signature(vals, bid, fresh(commit, bad_idx), bad_idx)
        batches = repeats + 1
        mid = acct.snapshot()
        # outside any timing: the same verifier's per-lane mask against
        # the host oracle, clean and corrupted
        rows = commit_rows(vals, commit)
        bad_rows = commit_rows(vals, fresh(commit, bad_idx))
        mask = verifier_mask(*rows)
        bad_mask = verifier_mask(*bad_rows)
        batches += 2
        t0 = time.perf_counter()
        oracle = oracle_mask(commit, rows)
        oracle_bad = list(oracle)
        oracle_bad[bad_idx] = oracle_lane(
            bad_rows[0][bad_idx], bad_rows[1][bad_idx], bad_rows[2][bad_idx])
        oracle_s = time.perf_counter() - t0
        require(all(oracle), f"[{name}] host oracle rejects a clean lane")
        require(not oracle_bad[bad_idx],
                f"[{name}] host oracle accepts the corrupted lane")
        require(list(mask) == oracle,
                f"[{name}] device mask != host oracle on the clean commit")
        require(list(bad_mask) == oracle_bad,
                f"[{name}] device mask != host oracle on the corrupted "
                f"commit (lane {bad_idx})")
        med = statistics.median(walls)
        link = mid["link"]
        readings[name] = {
            "validators": n, "wall_ms_median": med, "wall_ms_all": walls,
            "bytes_per_sig": mid["bytes_per_sig"],
            "link_bandwidth_mb_per_s": link.get("bandwidth_mb_per_s"),
            "link_rtt_ms": link.get("rtt_ms"),
            "link_converged": link.get("converged"),
        }
        say(f"[{label}] {name}: {n} signatures, smoke reading "
            f"{med:.3f} ms per verify_commit (median of {repeats}: "
            + ", ".join(f"{w:.3f}" for w in walls) + f"), "
            f"{mid['bytes_per_sig']} B/sig on the wire, link model "
            f"{json.dumps(link, separators=(',', ':'))}; corrupted lane "
            f"{bad_idx} pinpointed; masks == host oracle "
            f"({n} lanes, oracle {oracle_s:.1f} s)")
        if mesh_chips:
            chips = acct.snapshot()["mesh"]["chips"].values()
            say(f"[{label}] {name}: {sum(c['shards'] for c in chips)} "
                f"shards for {batches} verifies, dispatched at lane shapes "
                f"{sorted({b for c in chips for b in c['shard_lanes']})}")
        assert_rungs(
            f"{label} {name}", acct, warmed=warmed, mesh_chips=mesh_chips,
            **{f"aligned_{s[:2]}": _aligned(s, lanes[s], batches,
                                            mesh_chips)
               for s in ("ed25519", "sr25519")})
    return readings


def phase_b(acct: Accounting, warmed: set[int], n_vals: int, heights: int,
            n_txs: int, deadline_s: float) -> dict:
    """A net that commits: the in-process validators bench.py drives,
    backend tpu, kvstore txs through one node's mempool."""
    sys.path.insert(0, os.path.join(_ROOT, "tests"))
    from net_harness import make_net

    from cometbft_tpu.consensus.config import test_consensus_config

    txs = [b"smoke-%d=%d" % (i, SEED + i) for i in range(n_txs)]

    async def run():
        cfg = test_consensus_config()
        cfg.batch_vote_verification = True
        net = await make_net(n_vals, config=cfg, chain_id=NET_CHAIN_ID)
        t0 = time.perf_counter()
        await net.start()
        try:
            for tx in txs:
                res = await net.nodes[0].mempool.check_tx(tx)
                require(res.is_ok(), f"CheckTx rejected {tx!r}")
            found: set[bytes] = set()

            def done() -> bool:
                h = min(n.block_store.height() for n in net.nodes)
                if h < heights:
                    return False
                for hh in range(1, h + 1):
                    found.update(
                        net.nodes[-1].block_store.load_block(hh).data.txs)
                return found >= set(txs)

            while not done():
                require(time.perf_counter() - t0 < deadline_s,
                        f"net did not commit {heights} heights with every "
                        f"tx inside {deadline_s:.0f} s (heights "
                        f"{[n.block_store.height() for n in net.nodes]}, "
                        f"txs found {len(found)}/{len(txs)})")
                await asyncio.sleep(0.01)
            wall = time.perf_counter() - t0
            return wall, [n.block_store.height() for n in net.nodes]
        finally:
            await net.stop()

    acct.mark()
    wall, node_heights = asyncio.run(run())
    h = min(node_heights)
    snap = acct.snapshot()
    rows = snap["sched"]["class_rows"].get("consensus", 0)
    dev_batches = snap["counters"]["device_batches_ed25519"]
    say(f"[phase B] {n_vals} validators committed heights {node_heights} "
        f"in {wall:.2f} s: smoke reading {wall / h * 1e3:.1f} ms per "
        f"height; all {len(txs)} txs found in committed blocks; scheduler "
        f"batches {snap['sched']['batches']}, consensus-class rows {rows}, "
        f"device batches {dev_batches}, device lanes "
        f"{snap['counters']['device_lanes']}; challenge plane "
        f"{json.dumps(snap['challenge'], separators=(',', ':'))}; "
        f"{snap['compiles']} program(s) built inside the phase, "
        f"{snap['challenge'].get('derive_programs', 0)} challenge-derive "
        "geometries outside the warmed family")
    require(dev_batches >= h,
            f"only {dev_batches} device batches for {h} heights: vote "
            "flushes stayed under VoteSet.flush_pending's 2-vote batching "
            "threshold and were verified singly on the host — the phase "
            "has shown nothing")
    require(rows >= 2 * h, f"only {rows} consensus-class rows in {h} heights")
    # No batch here is 128-aligned: Pallas stays 0. A flush rides the
    # device-challenge plane only when it holds all four votes and their
    # timestamps encode to one length, which is the clock's to decide —
    # so plans are not required here (phase A requires them at every
    # width), but every plan made must have been derived on the device.
    assert_rungs("phase B", acct, aligned_ed=0, aligned_sr=0, warmed=warmed,
                 want_challenge=False)
    return {"heights": h, "seconds": wall, "ms_per_height": wall / h * 1e3,
            "consensus_rows": rows, "device_batches": dev_batches}


# ----------------------------------------------------------------- drivers


def build_workloads(shapes, seed: int) -> list:
    out = []
    rng = random.Random(seed)
    for k, (name, n_ed, n_sr) in enumerate(shapes):
        t0 = time.perf_counter()
        vals, bid, commit = make_commit(n_ed, n_sr, seed + k)
        bad_idx = rng.randrange(n_ed + n_sr)
        say(f"[set-up] {name}: {n_ed} ed25519 + {n_sr} sr25519 validators "
            f"signed in {time.perf_counter() - t0:.1f} s (seed {seed + k}, "
            f"corrupt lane {bad_idx})")
        out.append((name, vals, bid, commit, bad_idx))
    return out


def bucket_set(workloads, net_validators: int) -> set[int]:
    from cometbft_tpu.ops import ed25519_kernel as EK

    out = {EK.bucket_size(net_validators)} if net_validators else set()
    for _name, vals, *_ in workloads:
        n_ed = sum(v.pub_key.type_() == "ed25519" for v in vals.validators)
        if n_ed:
            out.add(EK.bucket_size(n_ed))
    return out


def run_one_chip() -> dict:
    device = probe_device(want_count=1)
    boot_device_plane()
    acct = Accounting()
    workloads = build_workloads(PHASE_A_SHAPES, SEED)
    warmed = bucket_set(workloads, NET_VALIDATORS)
    set_up = warm_up(workloads, NET_VALIDATORS, SEED)
    say(f"[warm-up] buckets {sorted(warmed)}: {set_up:.1f} s of set-up; "
        + acct.cache_report())
    phase_a(workloads, acct, warmed, REPEATS)
    phase_b(acct, warmed, NET_VALIDATORS, NET_HEIGHTS, NET_TXS,
            NET_DEADLINE_S)
    return device


def mesh_phase(workloads: list, acct: Accounting, repeats: int) -> dict:
    """The commits through VerifyMesh over MESH_CHIPS devices (default
    config: mesh active, class_aware)."""
    from cometbft_tpu.ops import dispatch

    programs = dispatch.health_snapshot()["mesh"].get("shard_program")
    say(f"[mesh] shard programs: {programs}: an ed25519 shard is the "
        "one-chip trip on its own chip (device challenge, the Pallas "
        "verify program through PallasGate: one pallas.ed25519 success a "
        "shard); an sr25519 shard the XLA ladder "
        "(sr25519_kernel._verify_kernel_ok), pallas.sr25519 successes 0")
    set_up = warm_up(workloads, 0, SEED)
    say(f"[mesh warm-up] {set_up:.1f} s of set-up (a program is compiled "
        "once and loaded on every chip); " + acct.cache_report())
    # the ed25519 shards' buckets enter ed25519_kernel's shape log
    warmed = {b for _name, vals, *_ in workloads for b in mesh_shard_lanes(
        sum(v.pub_key.type_() == "ed25519" for v in vals.validators))}
    return phase_a(workloads, acct, warmed, repeats, mesh_chips=MESH_CHIPS,
                   label="mesh")


def one_chip_comparison(workloads: list, acct: Accounting, repeats: int,
                        mesh_readings: dict) -> None:
    """The same commits on one chip of this process."""
    from cometbft_tpu.parallel import mesh as verify_mesh

    verify_mesh.configure(enabled=False)
    acct.mark()
    set_up = warm_up(workloads, 0, SEED)
    say(f"[one-chip warm-up] {set_up:.1f} s of set-up")
    one_readings = phase_a(workloads, acct, bucket_set(workloads, 0),
                           repeats, label="one chip")
    for name in mesh_readings:
        say(f"[mesh vs one chip] {name}: smoke readings "
            f"{mesh_readings[name]['wall_ms_median']:.3f} ms on "
            f"{MESH_CHIPS} chips, "
            f"{one_readings[name]['wall_ms_median']:.3f} ms on one chip; "
            "verdicts identical (both equal the host oracle lane for lane)")


def run_mesh() -> dict:
    """Four chips, and no other phase."""
    device = probe_device(want_count=MESH_CHIPS)
    boot_device_plane()
    acct = Accounting()
    workloads = build_workloads(MESH_SHAPES, SEED + 1)
    one_chip_comparison(workloads, acct, REPEATS,
                        mesh_phase(workloads, acct, REPEATS))
    return device


def main(argv: list[str]) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", action="store_true",
                    help="four chips: only the two 10k commits through "
                         "VerifyMesh and their one-chip comparison")
    args = ap.parse_args(argv)
    try:
        refuse_off_device_env()
        device = run_mesh() if args.mesh else run_one_chip()
    except Exception as exc:  # noqa: BLE001 - any failure ends the run
        if not isinstance(exc, SmokeFailure):
            import traceback

            traceback.print_exc()
        say(f"SMOKE FAILED: {type(exc).__name__}: {exc}")
        print(json.dumps({"ok": False}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
