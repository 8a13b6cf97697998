"""Multi-chip verify mesh with per-chip fault domains.

Two layers live here:

1. The original shard_map data plane (batch_mesh / shard_verify_kernel /
   sharded_verify_batch): one SPMD program over a 1-D "sig" mesh. It is
   the fastest way to run ONE healthy batch over N healthy chips — and
   exactly as fragile as that sentence implies: a single device fault
   fails the whole sharded dispatch.

2. VerifyMesh — the fault-tolerant production plane. Every chip is its
   own FAULT DOMAIN with a dedicated PR 2 DeviceSupervisor/CircuitBreaker
   (registry names "mesh.devN", so the node's supervision knobs apply).
   A batch is split into per-chip shards, each dispatched as an
   independent single-device program under its chip's supervisor:

     evict       a chip whose breaker opens drops out of placement; the
                 mesh re-shards over the survivors
     redispatch  a shard in flight when its chip dies is re-dispatched
                 across the surviving chips — no verify future is ever
                 lost to a device fault
     re-probe    an open breaker whose cooldown elapsed re-enters
                 placement as the half-open probe; success readmits the
                 chip, failure re-opens it (hysteresis: transient faults
                 retry in place and never evict)
     degrade     only an ALL-chips-dead mesh falls back to the existing
                 single-chip TPU->XLA->CPU ladder (ops/ed25519_kernel /
                 ops/sr25519_kernel), which carries its own supervisor

   An ed25519 shard IS the one-chip trip (ed25519_kernel.verify_batch_async)
   aimed at its chip (ed25519_kernel.Target): a slice of the batch's
   columns, that chip's replicas of the key table and the prefix table, one
   un-awaited upload, the derive program and the Pallas verify program on
   that chip, one blocking wait. Every shard of a batch is staged and
   dispatched from the caller's thread before the first is fetched.
   sr25519 and BLS shards keep the path they had (host staging, awaited
   uploads, the XLA ladder, one pool thread a shard).

   Placement is class-aware (the VerifyScheduler passes its batch class):
   consensus batches pin to the least-loaded chip (one dispatch, lowest
   latency — a vote flush must not pay an 8-way scatter/gather), while
   sync/mempool batches spread across all live chips for throughput.

   Chaos sites "ed25519.dispatch.devN" / "sr25519.dispatch.devN"
   (libs/chaos.py) fire inside each shard dispatch next to the plain
   scheme site, so a CBFT_CHAOS schedule can kill or flap exactly one
   fault domain deterministically.

Compile economics: a program is traced and lowered once a process and
compiled once: the persistent compilation cache keys a one-device program
without its device (ops/compile_cache.py), so the first chip's compile is
every other chip's load. Shard planning keeps every shard on the shared
bucket ladder — the compiled-shape count is bounded by the ladder's
length, not by traffic or mesh size.
"""

from __future__ import annotations

import functools
import threading
import time as _time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cometbft_tpu.libs import trace as _trace
from cometbft_tpu.ops import ed25519_kernel as K

SIG_AXIS = "sig"

# placement policies (config: crypto.mesh_placement)
CLASS_AWARE = "class_aware"  # consensus pinned, sync/mempool spread
SPREAD = "spread"            # every batch spread over the live mesh
PINNED = "pinned"            # every batch on the least-loaded chip
PLACEMENTS = (CLASS_AWARE, SPREAD, PINNED)

# a spread shard below this many rows pads more than it parallelizes
MIN_SHARD_ROWS = K.MIN_BUCKET

# pinning exists for LATENCY (one dispatch for a vote flush); a batch
# bigger than this spreads even under a pin policy — the scheduler's
# rider budget scales with the live mesh size, and funneling a
# mesh-sized coalesced batch onto one chip would pay N x the per-chip
# latency pinning was meant to avoid (plus a one-off compile for a shard
# shape no single-chip path ever traces)
PIN_MAX_ROWS = 2048

# spread shards are capped too: every shard stays on the power-of-two
# end of the bucket ladder, so the mesh compiles at most the 9 small
# ladder shapes instead of one giant program per mega-commit size —
# chips take multiple shards round-robin (a 100k-row commit becomes ~49
# pipelined 2048-lane shards, not 8 one-off 14336-lane executables).
# Read on four v5e chips at 10,240 ed25519 rows (tools/mesh_plan_crossover,
# PR 33; PERF.md section 6): five shards of 2,048, one chip twice, 39.7 ms
# a verify_commit; one shard a chip (2,560 rows in a 4,096-lane bucket)
# 41.7; ten of 1,024 55.0 — a shard costs the caller ~3 ms whatever its
# size, a lane of padding costs too: full 2,048-lane shards win both ways
MAX_SHARD_ROWS = 2048


def host_mesh_env(base_env: dict, n_devices: int) -> dict:
    """Subprocess env for an n-device CPU host mesh: JAX_PLATFORMS=cpu
    (set before the child imports jax, so a parent that holds the chip
    never starts a child that wants it) and the host platform forced to
    n_devices. bench's mesh child and the e2e chip perturbations both
    spawn through it."""
    env = dict(base_env)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    return env


def batch_mesh(devices: list | None = None) -> Mesh:
    """1-D mesh over the given (or all) devices, axis name 'sig'."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.array(devices), axis_names=(SIG_AXIS,))


@functools.lru_cache(maxsize=8)
def shard_verify_kernel(mesh: Mesh):
    """jit-compiled shard_map of the verify program over `mesh`. The lane
    (batch) axis must divide the mesh size; bucket padding guarantees
    power-of-two batches. Inputs follow ed25519_kernel.verify_math:
    4x A-coords (20, B) int32, then r/s/k packed words (8, B) uint32."""
    spec_tail = P(None, SIG_AXIS)
    in_specs = (spec_tail,) * 7
    out_specs = P(SIG_AXIS)
    fn = jax.shard_map(
        K.verify_math, mesh=mesh, in_specs=in_specs, out_specs=out_specs
    )
    return jax.jit(fn)


def _mesh_bucket(n: int, n_dev: int) -> int:
    b = K.bucket_size(n)
    if b % n_dev:
        b = ((b + n_dev - 1) // n_dev) * n_dev
    return b


def sharded_verify_batch(
    pubs: list[bytes],
    msgs: list[bytes],
    sigs: list[bytes],
    mesh: Mesh | None = None,
    cache: K.PubKeyCache | None = None,
) -> tuple[bool, list[bool]]:
    """Multi-chip analog of ops.ed25519_kernel.verify_batch: same host glue
    (structural checks, SHA-512 challenges, bucket padding — shared via
    stage_batch), with the device batch sharded over the mesh's 'sig'
    axis. SPMD, all-chips-healthy path (the bench scaling probe);
    VerifyMesh is the fault-tolerant production plane."""
    n = len(sigs)
    if n == 0:
        return True, []
    if mesh is None:
        mesh = batch_mesh()
    n_dev = mesh.devices.size
    cache = cache or K._default_cache

    b = _mesh_bucket(n, n_dev)
    pre_ok, safe_pubs, r_words, s_words, k_words = K.stage_batch(pubs, msgs, sigs, b)

    tail = NamedSharding(mesh, P(None, SIG_AXIS))
    put = functools.partial(jax.device_put, device=tail)
    # stable cache key: device ids, not id(mesh) (addresses get reused)
    mesh_key = "mesh-" + ",".join(str(d.id) for d in mesh.devices.flat)
    ok_a, a_dev = cache.stage(safe_pubs, b, put=put, put_key=mesh_key)
    fn = shard_verify_kernel(mesh)
    mask_dev = fn(
        *a_dev,
        jax.device_put(r_words, tail),
        jax.device_put(s_words, tail),
        jax.device_put(k_words, tail),
    )
    mask = np.asarray(mask_dev)[:n] & pre_ok & ok_a
    return bool(mask.all()), mask.tolist()


# ---------------------------------------------------------------------------
# VerifyMesh — per-chip fault domains
# ---------------------------------------------------------------------------


def _mesh_metrics():
    """Lazy process-global MeshMetrics; never raises (metrics must not
    break verification)."""
    try:
        from cometbft_tpu.libs import metrics as m

        return m.mesh_metrics()
    except Exception:  # noqa: BLE001
        return None


class _Chip:
    """One fault domain: a device plus its dedicated supervisor/breaker
    and the load counters placement reads."""

    __slots__ = ("index", "device", "name", "target", "inflight_lanes",
                 "lanes_total", "shards_total", "shard_lanes",
                 "array_devices")

    def __init__(self, index: int, device):
        self.index = index
        self.device = device
        self.name = f"mesh.dev{index}"
        # what aims the one-chip ed25519 trip at this chip
        self.target = K.Target(device=device, index=index,
                               put_key=f"dev{index}", supervisor=self.name,
                               strict=True)
        self.inflight_lanes = 0
        self.lanes_total = 0
        self.shards_total = 0
        # what actually ran here: the lane shapes of this chip's shards
        # and the devices their staged arrays (words and pubkey
        # coordinates) were committed to
        self.shard_lanes: set[int] = set()
        self.array_devices: set[str] = set()

    @property
    def supervisor(self):
        from cometbft_tpu.ops import dispatch

        return dispatch.supervisor(self.name)


class VerifyMesh:
    """The elastic multi-chip verify plane: shards bucket-ladder batches
    (ed25519 AND sr25519) across all devices, each chip its own fault
    domain. See the module docstring for the shrink/grow/redispatch
    semantics."""

    def __init__(self, devices: list | None = None,
                 placement: str = CLASS_AWARE):
        if devices is None:
            devices = jax.devices()
        if placement not in PLACEMENTS:
            raise ValueError(
                f"unknown mesh placement {placement!r} (choices: {PLACEMENTS})")
        self.chips = [_Chip(i, d) for i, d in enumerate(devices)]
        self.placement = placement
        # pubkey staging strategy: a real accelerator mesh keeps the
        # decompressed valset device-resident per chip (digest cache +
        # device-side gather — wire bytes dominate there); a forced-host
        # CPU mesh (tests, the bench child) stages coordinates host-side
        # and device_puts them directly, because every extra per-device
        # jit (gather, upload checksum) costs a compile per chip and the
        # "wire" is a memcpy
        self._device_cache = bool(devices) and devices[0].platform != "cpu"
        if self._device_cache:
            # the default device-slot budget (8) was sized for ONE chip;
            # an N-chip mesh keys entries per chip (put_key devN) and
            # per bucket, so scale the FIFO or every batch re-pays the
            # checksummed coordinate upload the cache exists to avoid
            try:
                K._default_cache.device_slots = max(
                    K._default_cache.device_slots, 4 * len(devices))
                from cometbft_tpu.ops import sr25519_kernel as SRK

                SRK._default_cache.device_slots = max(
                    SRK._default_cache.device_slots, 4 * len(devices))
            except Exception:  # noqa: BLE001 - cache sizing is advisory
                pass
        self._lock = threading.Lock()
        self._pool = None
        # eviction/readmission accounting: last observed per-chip
        # breaker-open state (state-based, so a half-open probe in flight
        # is not prematurely counted readmitted)
        self._was_open = [False] * len(self.chips)
        self.evictions = 0
        self.readmissions = 0
        self.redispatches = 0
        self.fallbacks = 0
        self.batches = 0
        self.rows_total = 0

    # ------------------------------------------------------------ plumbing

    def _executor(self):
        if self._pool is None:
            import concurrent.futures

            self._pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=max(2, len(self.chips)),
                thread_name_prefix="mesh-verify")
        return self._pool

    @staticmethod
    def _scheme_ops(scheme: str) -> dict:
        if scheme == "ed25519":
            from cometbft_tpu.crypto import ed25519_math as _oracle

            return {
                # a shard rides the one-chip trip, aimed at its chip
                "trip": K.verify_batch_async,
                # the curve math of the trip's verify program; None: the
                # trip's own (Pallas, behind it XLA). The tests' seam.
                "kernel": None,
                "verify_fn": _oracle.verify_zip215,
                "fallback_async": K.verify_batch_async,
            }
        # sr25519: the *_ok variant is the SAME compiled program the
        # single-chip path traces
        if scheme == "sr25519":
            from cometbft_tpu.crypto import sr25519_math as _srm
            from cometbft_tpu.ops import sr25519_kernel as SRK

            return {
                "stage": lambda p, m, s, b, out=None: SRK.stage_rows_sr(
                    p, m, s, b, out=out),
                "kernel": SRK._verify_kernel_ok,
                "cache": lambda: SRK._default_cache,
                "verify_fn": _srm.verify,
                "fallback_async": SRK.verify_batch_async,
            }
        if scheme == "bls12381":
            from cometbft_tpu.ops import bls_kernel as BLSK

            return {
                # pairing kernels stage/dispatch through their own piece
                # pipeline — the mesh delegates the whole shard to it
                # (per-chip placement via the committed device of the
                # staged block) instead of the rw/sw/kw word contract
                "shard_verify": BLSK.mesh_shard_verify,
                "verify_fn": BLSK.oracle_verify,
                "fallback_async": BLSK.verify_batch_async,
            }
        raise ValueError(f"mesh has no verify program for scheme {scheme!r}")

    @staticmethod
    def _host_coords(cache, pubs: list[bytes],
                     bucket: int) -> tuple[np.ndarray, tuple]:
        """Host-staged A-coordinates: decompress through the scheme
        cache's host level, identity-pad + transpose via the kernel's
        shared pad_coords_batch_minor, ready for a per-chip device_put.
        The direct-path twin of ed25519_kernel._stage_gather."""
        ok_a, coords = cache.lookup_or_decompress(pubs)
        return ok_a, K.pad_coords_batch_minor(coords, bucket)

    # ------------------------------------------------------------ liveness

    def live_chips(self) -> list[_Chip]:
        """Chips whose breaker currently admits shards (peek: an OPEN
        breaker past its cooldown is included — dispatching to it IS the
        half-open re-probe that can readmit the chip). Also the
        eviction/readmission accounting site and the mesh gauges'
        publish point."""
        from cometbft_tpu.ops import dispatch as D

        live: list[_Chip] = []
        mm = _mesh_metrics()
        with self._lock:
            for chip in self.chips:
                br = chip.supervisor.breaker
                state = br.state
                is_open = state == D.OPEN
                if is_open and not self._was_open[chip.index]:
                    self.evictions += 1
                    _trace.event("mesh.evict", cat="device",
                                 device=chip.index)
                    if mm is not None:
                        try:
                            mm.mesh_evictions_total.inc()
                        except Exception:  # noqa: BLE001
                            pass
                elif self._was_open[chip.index] and not is_open:
                    self.readmissions += 1
                    _trace.event("mesh.readmit", cat="device",
                                 device=chip.index)
                    # re-seed ONLY this fault domain's reduced-send
                    # replicas: a healed chip must not serve validator
                    # tables staged before its fault, and its mesh-mates'
                    # resident sets stay untouched
                    try:
                        from cometbft_tpu.ops import residency

                        residency.invalidate_device(chip.index)
                        from cometbft_tpu.ops import challenge

                        challenge.invalidate(f"dev{chip.index}")
                    except Exception:  # noqa: BLE001 - never block healing
                        pass
                    if mm is not None:
                        try:
                            mm.mesh_readmissions_total.inc()
                        except Exception:  # noqa: BLE001
                            pass
                self._was_open[chip.index] = is_open
                if mm is not None:
                    try:
                        mm.mesh_breaker_state.labels(str(chip.index)).set(
                            {D.CLOSED: 0, D.HALF_OPEN: 1, D.OPEN: 2}[state])
                    except Exception:  # noqa: BLE001
                        pass
                if br.peek():
                    live.append(chip)
        if mm is not None:
            try:
                mm.verify_mesh_size.set(len(live))
                mm.mesh_devices.set(len(self.chips))
            except Exception:  # noqa: BLE001
                pass
        return live

    def live_size(self) -> int:
        return len(self.live_chips())

    def live_size_hint(self) -> int:
        """Lock-light live count for hot-path budget math (no
        eviction/readmission accounting, no gauge publishes — the
        dispatch path runs the full live_chips() scan anyway)."""
        return sum(1 for c in self.chips if c.supervisor.breaker.peek())

    # ----------------------------------------------------------- placement

    def _plan(self, m: int, klass: str,
              chips: list[_Chip]) -> list[tuple[_Chip, int, int]]:
        """Split m rows into contiguous per-chip shards. Consensus (and
        the "pinned" policy) pins the whole group to the least-loaded
        chip; everything else spreads across the live mesh, never
        creating a shard smaller than MIN_SHARD_ROWS."""
        by_load = sorted(
            chips, key=lambda c: (c.inflight_lanes, c.lanes_total, c.index))
        pin = (self.placement == PINNED or (
            self.placement == CLASS_AWARE and klass == "consensus")
        ) and m <= PIN_MAX_ROWS
        if pin or m < 2 * MIN_SHARD_ROWS or len(chips) == 1:
            return [(by_load[0], 0, m)]
        n_shards = max(1, min(len(chips), m // MIN_SHARD_ROWS))
        # shard-size cap: chips take multiple ladder-sized shards
        # round-robin instead of one giant per-chip program
        n_shards = max(n_shards, -(-m // MAX_SHARD_ROWS))
        targets = [by_load[i % len(by_load)] for i in range(n_shards)]
        out: list[tuple[_Chip, int, int]] = []
        base, rem = divmod(m, n_shards)
        lo = 0
        for i, chip in enumerate(targets):
            hi = lo + base + (1 if i < rem else 0)
            if hi > lo:
                out.append((chip, lo, hi))
            lo = hi
        return out

    # ------------------------------------------------------------ dispatch

    def _count_shard(self, chip: _Chip, lanes: int, placed=()) -> None:
        """A shard has run on `chip`: the load counters placement reads,
        the lane shapes and devices crypto_health shows, /metrics."""
        mm = _mesh_metrics()
        if mm is not None:
            try:
                mm.mesh_shard_lanes.labels(str(chip.index)).inc(lanes)
            except Exception:  # noqa: BLE001
                pass
        with self._lock:
            chip.lanes_total += lanes
            chip.shards_total += 1
            chip.shard_lanes.add(lanes)
            chip.array_devices |= set(placed)

    def _shard_op(self, ops: dict, scheme: str, chip: _Chip,
                  pubs: list, msgs: list, sigs: list):
        """One chip's sr25519 or BLS shard: stage host-side, place on the
        chip, run the scheme's verify program, fetch the mask. Runs under
        the chip's supervisor (transient retry in place; failures feed its
        breaker). Returns (mask (n,), eligible (n,)). (An ed25519 shard is
        the one-chip trip: _trip_shard.)

        Known gap vs the single-chip plane: these shards reuse the exact
        _verify_kernel_ok executable and therefore do NOT carry the
        staged-word transfer checksum of _integrity_parts — the
        host-oracle recheck still catches reject-direction corruption,
        but an accept-direction h2d bit flip is undetected on this path."""
        from cometbft_tpu.libs import chaos
        from cometbft_tpu.libs import linkmodel as _linkmodel
        from cometbft_tpu.ops.dispatch import KERNEL_DISPATCH_LOCK

        chaos.fire(f"{scheme}.dispatch")
        chaos.fire(f"{scheme}.dispatch.dev{chip.index}")
        n = len(sigs)
        b = K.bucket_size(n)
        shard_verify = ops.get("shard_verify")
        if shard_verify is not None:
            # scheme-owned shard path (bls12381): the kernel stages,
            # places on this chip and fetches; the mesh keeps fault-
            # domain accounting and placement
            with _trace.span(f"{scheme}.dispatch", cat="compute",
                             lanes=b, device=chip.index):
                mask, eligible = shard_verify(chip.device, pubs, msgs, sigs)
            K._count_device_batch(scheme, b)
            self._count_shard(chip, b)
            return mask, eligible
        with _trace.span(f"{scheme}.stage", cat="stage", sig_rows=n,
                         lanes=b, device=chip.index):
            pre_ok, safe_pubs, rw, sw, kw = ops["stage"](pubs, msgs, sigs, b)
        host_arrs = None
        send_path, staging_tx = "full", 0
        # the scheme cache serializes itself (PubKeyCache._tlock): shard
        # workers, scheduler drains, and blocksync stagers all share it
        with _trace.span(f"{scheme}.stage_pubkeys", cat="transfer",
                         lanes=b, device=chip.index):
            if self._device_cache:
                # per-chip reduced-send replica: put_key carries the
                # fault-domain index, so each chip holds its own
                # resident validator table (residency.invalidate_device
                # drops exactly one replica on readmission)
                ok_a, a_dev, send_path, staging_tx = K._stage_gather(
                    ops["cache"](), safe_pubs, b,
                    put_key=f"dev{chip.index}", device=chip.device)
            else:
                ok_a, host_arrs = self._host_coords(
                    ops["cache"](), safe_pubs, b)
        # per-fault-domain in-flight gate: each chip holds its own two
        # slots, so shard N's h2d overlaps shard N-1's compute ON THE
        # SAME CHIP while a third shard queues — and a chip degraded to
        # single-buffer (chaos / device trouble) serializes only its own
        # fault domain, never its mesh siblings
        from cometbft_tpu.ops import dispatch as _dispatchmod

        with _trace.span(f"{scheme}.slot", cat="queue", lanes=b,
                         device=chip.index):
            rel = _dispatchmod.doublebuffer(f"dev{chip.index}").acquire()
        try:
            with _trace.span(f"{scheme}.h2d", cat="transfer", lanes=b,
                             device=chip.index) as sp:
                t0 = _time.perf_counter()
                rwd = jax.device_put(rw, chip.device)
                swd = jax.device_put(sw, chip.device)
                kwd = jax.device_put(kw, chip.device)
                nbytes = rw.nbytes + sw.nbytes + kw.nbytes
                if host_arrs is not None:
                    a_dev = tuple(
                        jax.device_put(a, chip.device) for a in host_arrs)
                    nbytes += sum(a.nbytes for a in host_arrs)
                staged = (rwd, swd, kwd) + tuple(a_dev)
                jax.block_until_ready(staged)
                placed = {str(d) for a in staged for d in a.devices()}
                _linkmodel.link().observe_transfer(
                    nbytes, _time.perf_counter() - t0)
                sp.add_bytes(tx=nbytes)
            try:
                from cometbft_tpu.ops import residency as _residency

                _residency.record_send(send_path, staging_tx + nbytes, sigs=n)
            except Exception:  # noqa: BLE001 - accounting must not break shards
                pass
            with _trace.span(f"{scheme}.dispatch", cat="compute", lanes=b,
                             device=chip.index):
                with KERNEL_DISPATCH_LOCK:
                    mask_dev, _allok = ops["kernel"](*a_dev, rwd, swd, kwd)
        finally:
            rel()
        with _trace.span(f"{scheme}.d2h", cat="fetch",
                         device=chip.index) as sp:
            mask = np.asarray(mask_dev)
            sp.add_bytes(rx=mask.nbytes)
        K._count_device_batch(scheme, b)
        self._count_shard(chip, b, placed)
        eligible = pre_ok & ok_a
        return mask[:n] & eligible, eligible

    @staticmethod
    def _cut(rows: tuple, idx: np.ndarray) -> tuple:
        """The rows `idx` (ascending) of a batch, column for column:
        slices where idx is one run (a first round's shard: views of the
        scheduler's matrices, no per-lane object), index vectors where it
        is not (a redispatch's leftovers). A column is a list, an array
        (signatures, key rows), a prefixrows.MsgBlock, or None."""
        from cometbft_tpu.libs.prefixrows import MsgBlock

        run = None
        if len(idx) and int(idx[-1]) - int(idx[0]) + 1 == len(idx):
            run = slice(int(idx[0]), int(idx[-1]) + 1)

        def cut(col):
            if col is None:
                return None
            if run is not None:
                return col[run]
            if isinstance(col, np.ndarray):
                return col[idx]
            if isinstance(col, MsgBlock):
                return col.take(idx)
            return [col[i] for i in idx]

        return tuple(cut(col) for col in rows)

    def _trip_shard(self, ops: dict, chip: _Chip, rows: tuple,
                    sub_idx: np.ndarray, recheck_groups):
        """One chip's ed25519 shard: the one-chip trip aimed at the chip,
        staged and dispatched here, on the caller's thread, un-awaited.
        Returns the waiter _join calls: () -> (mask (n,), None), raising
        what the chip's supervisor recorded (the trip is strict). The
        trip applies the host-oracle recheck itself, under the producers'
        group budgets remapped onto the shard: None for `eligible` keeps
        the mesh-level recheck off these rows."""
        lanes = K.bucket_size(len(sub_idx))
        with _trace.span("mesh.shard", cat="stage", device=chip.index,
                         rows=len(sub_idx), lanes=lanes):
            pubs, msgs, sigs, pub_rows = self._cut(rows, sub_idx)
            try:
                thunk = ops["trip"](
                    pubs, msgs, sigs, pub_rows=pub_rows,
                    recheck_groups=self._remap_groups(
                        recheck_groups, sub_idx),
                    target=chip.target, ladder=ops["kernel"])
            except Exception as exc:  # noqa: BLE001 - the waiter raises it
                failed = exc

                def refused():
                    raise failed

                return refused

        def wait():
            mask = thunk()
            self._count_shard(chip, lanes, thunk.placed())
            return mask, None

        return wait

    def _submit_round(self, ops: dict, scheme: str, rows: tuple,
                      idx: np.ndarray, klass: str, chips: list[_Chip],
                      recheck_groups=None):
        """Shard idx's rows over `chips` and dispatch every shard: an
        ed25519 shard from this thread (_trip_shard), an sr25519 or BLS
        one on the mesh pool. Returns [(chip, sub_idx, waiter)]."""
        from cometbft_tpu.ops import dispatch as D

        with _trace.span("mesh.plan", cat="stage", rows=len(idx),
                         chips=len(chips)):
            plan = self._plan(len(idx), klass, chips)
        submitted = []
        for chip, lo, hi in plan:
            sub_idx = idx[lo:hi]
            with self._lock:
                chip.inflight_lanes += K.bucket_size(len(sub_idx))
            if "trip" in ops:
                wait = self._trip_shard(ops, chip, rows, sub_idx,
                                        recheck_groups)
            else:
                fut = self._executor().submit(
                    _trace.wrap_ctx(chip.supervisor.run),
                    functools.partial(self._shard_op, ops, scheme, chip,
                                      *self._cut(rows[:3], sub_idx)))

                def wait(fut=fut):
                    return fut.result(timeout=D.watchdog_timeout())
            submitted.append((chip, sub_idx, wait))
        return submitted

    @staticmethod
    def _remap_groups(groups, idx: np.ndarray):
        """Translate full-batch recheck-group bounds onto the fallback
        sub-batch (idx is ascending): each producer keeps its own
        host-oracle recheck budget even on the degraded path."""
        if not groups:
            return None
        out = []
        for a, b in groups:
            lo = int(np.searchsorted(idx, a))
            hi = int(np.searchsorted(idx, b))
            if hi > lo:
                out.append((lo, hi))
        return out or None

    def _fallback(self, ops: dict, scheme: str, rows: tuple,
                  idx: np.ndarray, mask: np.ndarray,
                  eligible: np.ndarray, recheck_groups=None) -> None:
        """All fault domains dead: those rows ride the existing
        single-chip TPU->XLA->CPU ladder (which applies its own
        host-oracle recheck, under the producers' remapped per-group
        budgets — the rows are marked ineligible so the mesh-level
        recheck never double-spends a budget on them)."""
        self.fallbacks += 1
        mm = _mesh_metrics()
        if mm is not None:
            try:
                mm.mesh_fallback_total.inc()
            except Exception:  # noqa: BLE001
                pass
        _trace.event("mesh.fallback", cat="device", scheme=scheme,
                     rows=len(idx))
        try:
            from cometbft_tpu.libs import log as _log

            _log.default().error(
                "verify mesh has no live fault domains; degrading to the "
                "single-chip ladder", scheme=scheme, rows=str(len(idx)))
        except Exception:  # noqa: BLE001
            pass
        pubs, msgs, sigs, pub_rows = self._cut(rows, idx)
        kwargs = {}
        if scheme == "ed25519":
            # sr25519's async path has no recheck_groups parameter (its
            # single-chip recheck is budgeted whole-batch)
            kwargs = {"pub_rows": pub_rows, "recheck_groups":
                      self._remap_groups(recheck_groups, idx)}
        mask[idx] = ops["fallback_async"](pubs, msgs, sigs, **kwargs)()
        eligible[idx] = False

    def verify_async(self, scheme: str, pubs: list[bytes], msgs, sigs,
                     klass: str = "sync",
                     recheck_groups: list[tuple[int, int]] | None = None,
                     pub_rows: np.ndarray | None = None):
        """Shard + dispatch across the live mesh without blocking; returns
        a thunk materializing the (N,) bool mask. A shard whose chip dies
        mid-flight is re-dispatched over the survivors inside the thunk —
        the caller's futures always resolve.

        The rows as lists of bytes, or, for ed25519, as the scheduler has
        them (libs/rowblock.SigColumns, the arguments of
        ed25519_kernel.verify_batch_async): msgs a prefixrows.MsgBlock,
        sigs the (N, 64) matrix, pub_rows the (N, 32) key matrix beside
        the key list. A shard is a slice of them."""
        n = len(sigs)
        assert len(pubs) == n and len(msgs) == n
        ops = self._scheme_ops(scheme)
        if n == 0:
            return lambda: np.zeros(0, dtype=bool)
        rows = (pubs, msgs, sigs, pub_rows)
        idx = np.arange(n)
        chips = self.live_chips()
        pending = (self._submit_round(ops, scheme, rows, idx, klass, chips,
                                      recheck_groups)
                   if chips else [])

        def thunk() -> np.ndarray:
            return self._join(ops, scheme, rows, n, idx, pending, klass,
                              recheck_groups)

        return thunk

    def verify(self, scheme: str, pubs, msgs, sigs, klass: str = "sync",
               recheck_groups=None, pub_rows=None) -> np.ndarray:
        return self.verify_async(
            scheme, pubs, msgs, sigs, klass, recheck_groups, pub_rows)()

    def _join(self, ops: dict, scheme: str, rows: tuple, n: int,
              idx0: np.ndarray, pending: list, klass: str,
              recheck_groups) -> np.ndarray:
        # the caller blocked on its shards, the slowest decides: a stage
        # of its own (`join`), so that the wait for the chips' dispatch
        # closures is not read as `fetch` (the per-shard d2h spans inside
        # are, and are taken off this span's self time)
        with _trace.span("mesh.join", cat="join", scheme=scheme,
                         shards=len(pending)):
            mask, eligible = self._join_rounds(
                ops, scheme, rows, n, idx0, pending, klass, recheck_groups)
        info = (ops["verify_fn"], scheme, recheck_groups)
        return K.apply_recheck(mask, eligible, rows[:3], info)

    def _join_rounds(self, ops: dict, scheme: str, rows: tuple, n: int,
                     idx0: np.ndarray, pending: list, klass: str,
                     recheck_groups) -> tuple[np.ndarray, np.ndarray]:
        from cometbft_tpu.ops import dispatch as D

        mask = np.zeros(n, dtype=bool)
        eligible = np.zeros(n, dtype=bool)
        mm = _mesh_metrics()
        if not pending:  # mesh was already fully dead at submit time
            self._fallback(ops, scheme, rows, idx0, mask, eligible,
                           recheck_groups=recheck_groups)
        rounds = 0
        # each failed round opens at least one consecutive-failure notch
        # on some breaker, so this bound is generous, not load-bearing
        max_rounds = 4 * len(self.chips) + 2
        while pending:
            failed_idx: list[np.ndarray] = []
            reasons: list[str] = []
            for chip, sub_idx, wait in pending:
                try:
                    m, el = wait()
                    mask[sub_idx] = m
                    # None: the shard rechecked its own lanes (the trip)
                    eligible[sub_idx] = False if el is None else el
                except (D.DeviceUnavailable, D.DeviceOpFailed) as exc:
                    cause = exc.__cause__ or exc
                    reason = ("unavailable"
                              if isinstance(exc, D.DeviceUnavailable)
                              else D.classify_failure(cause))
                    failed_idx.append(sub_idx)
                    reasons.append(reason)
                except Exception as exc:  # noqa: BLE001 - watchdog etc.
                    # same watchdog-abandonment semantics as the single-
                    # chip plane (supervised_device_thunk._acquire): the
                    # wedged worker keeps its pool slot until jax gives
                    # up, and if the op later resolves inside
                    # supervisor.run it re-records — the breaker sees a
                    # hung chip slightly twice rather than not at all
                    chip.supervisor.record_op_failure(exc)
                    failed_idx.append(sub_idx)
                    reasons.append("timeout")
                finally:
                    with self._lock:
                        chip.inflight_lanes -= K.bucket_size(len(sub_idx))
            pending = []
            if not failed_idx:
                break
            retry_idx = np.concatenate(failed_idx)
            with self._lock:
                self.redispatches += len(failed_idx)
            for reason in reasons:
                _trace.event("mesh.redispatch", cat="device", scheme=scheme,
                             reason=reason)
                if mm is not None:
                    try:
                        mm.mesh_redispatch_total.labels(reason).inc()
                    except Exception:  # noqa: BLE001
                        pass
            rounds += 1
            chips = self.live_chips()
            if not chips or rounds > max_rounds:
                self._fallback(ops, scheme, rows, retry_idx, mask, eligible,
                               recheck_groups=recheck_groups)
                break
            pending = self._submit_round(
                ops, scheme, rows, retry_idx, klass, chips, recheck_groups)
        with self._lock:
            self.batches += 1
            self.rows_total += n
        # refresh liveness accounting NOW: a successful half-open probe in
        # this batch just re-closed its breaker, and the readmission (and
        # the mesh-size gauge) must be visible before the next flush
        self.live_chips()
        return mask, eligible

    # -------------------------------------------------------------- warmup

    def warmup(self, buckets: list[int]) -> list[int]:
        """Instantiate the ed25519 shard programs of the given lane counts
        on every live chip, one chip after the other: the first chip's
        compile is every other chip's load from the compilation cache
        (ops/compile_cache.py). Rows as the scheduler's own warm-up makes
        them; no batch or shard is counted. Returns the lane counts every
        live chip ran."""
        ops = self._scheme_ops("ed25519")
        done: list[int] = []
        for b in buckets:
            try:
                for chip in self.live_chips():
                    ops["trip"](*K.warmup_rows(b), target=chip.target,
                                ladder=ops["kernel"])()
                done.append(b)
            except Exception:  # noqa: BLE001 - the chip's supervisor owns it
                break
        return done

    # -------------------------------------------------------------- health

    def health(self) -> dict:
        """The crypto_health `mesh` section: live size, per-chip breaker
        state, eviction/readmission/redispatch churn, fallback count."""
        from cometbft_tpu.ops import dispatch as D

        chips = {}
        live = 0
        for chip in self.chips:
            sup = chip.supervisor
            alive = sup.breaker.peek()
            live += bool(alive)
            chips[str(chip.index)] = {
                "state": sup.breaker.state,
                "live": bool(alive),
                "inflight_lanes": chip.inflight_lanes,
                "lanes_total": chip.lanes_total,
                "shards_total": chip.shards_total,
                "shard_lanes": sorted(chip.shard_lanes),
                "array_devices": sorted(chip.array_devices),
                "failures": sup.failures,
                "successes": sup.successes,
            }
        with self._lock:
            return {
                "devices": len(self.chips),
                "live": live,
                "placement": self.placement,
                "evictions": self.evictions,
                "readmissions": self.readmissions,
                "redispatched_batches": self.redispatches,
                "fallbacks": self.fallbacks,
                "batches": self.batches,
                "rows_total": self.rows_total,
                # over every chip: rows_total / lanes_total is the fill of
                # the shards' buckets, shards_total / batches the split
                "shards_total": sum(c["shards_total"]
                                    for c in chips.values()),
                "lanes_total": sum(c["lanes_total"] for c in chips.values()),
                # the verify program a shard of each scheme runs
                "shard_program": self.shard_programs(),
                "chips": chips,
            }

    @staticmethod
    def shard_programs() -> dict:
        """ed25519: the one-chip trip's, so Pallas on a TPU for a
        128-aligned bucket (behind its gate the XLA ladder); sr25519 and
        BLS shards: their XLA programs."""
        return {"ed25519": "pallas" if K._pallas_available() else "xla",
                "sr25519": "xla", "bls12381": "xla"}


# ---------------------------------------------------------------------------
# process-global mesh singleton + knobs (configured from config.crypto at
# node boot; tests poke configure()/reset() directly)
# ---------------------------------------------------------------------------

_cfg = {
    "enabled": True,
    # below this many devices the mesh adds dispatch overhead without
    # adding a second fault domain — the single-chip path already exists
    "min_devices": 2,
    "placement": CLASS_AWARE,
}

_mesh_lock = threading.Lock()
_mesh: VerifyMesh | None = None


def configure(enabled: bool | None = None, min_devices: int | None = None,
              placement: str | None = None) -> None:
    """Apply config.crypto mesh knobs. The live mesh picks up a placement
    change in place; device-set changes need reset() (a process sees one
    device topology for its lifetime)."""
    global _mesh
    with _mesh_lock:
        if enabled is not None:
            _cfg["enabled"] = bool(enabled)
        if min_devices is not None:
            if min_devices < 1:
                raise ValueError("mesh_min_devices must be >= 1")
            _cfg["min_devices"] = int(min_devices)
        if placement is not None:
            if placement not in PLACEMENTS:
                raise ValueError(
                    f"unknown mesh placement {placement!r} "
                    f"(choices: {PLACEMENTS})")
            _cfg["placement"] = placement
            if _mesh is not None:
                _mesh.placement = placement


def get() -> VerifyMesh:
    """The process-global VerifyMesh over every visible device (built
    lazily — health snapshots must not force device discovery)."""
    global _mesh
    if _mesh is None:
        with _mesh_lock:
            if _mesh is None:
                _mesh = VerifyMesh(placement=_cfg["placement"])
    return _mesh


def _set_for_testing(mesh: VerifyMesh | None) -> None:
    """Install a specific mesh instance (tests build meshes over device
    subsets to bound per-device compile cost)."""
    global _mesh
    with _mesh_lock:
        _mesh = mesh


def reset() -> None:
    """Forget the mesh (tests; per-chip supervisors live in the
    ops/dispatch registry and are cleared by reset_supervision)."""
    _set_for_testing(None)


def active() -> VerifyMesh | None:
    """The mesh the scheduler should route through, or None (disabled or
    too few devices). Builds the mesh on first use — DISPATCH paths only.
    An all-chips-dead mesh is still ACTIVE — its internal fallback IS the
    degradation ladder; only topology/config turn the mesh off."""
    if not _cfg["enabled"]:
        return None
    m = get()
    if len(m.chips) < _cfg["min_devices"]:
        return None
    return m


def peek_active() -> VerifyMesh | None:
    """active() without building: telemetry and planning paths (health
    snapshots, rider-budget math) must not force device discovery or
    register per-chip supervisors."""
    if not _cfg["enabled"] or _mesh is None:
        return None
    if len(_mesh.chips) < _cfg["min_devices"]:
        return None
    return _mesh


def enabled() -> bool:
    return _cfg["enabled"]


def health_snapshot() -> dict:
    """The crypto_health `mesh` section. Reports config even before the
    mesh is built (building it is cheap but creates per-chip supervisors;
    a health poll must not mutate the supervision registry)."""
    out = {
        "enabled": _cfg["enabled"],
        "min_devices": _cfg["min_devices"],
        "placement": _cfg["placement"],
        "built": _mesh is not None,
    }
    if _mesh is not None:
        out.update(_mesh.health())
        out["active"] = active() is not None
    return out
