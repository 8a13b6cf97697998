"""VerifyScheduler — node-wide continuous batching of signature work.

Before this subsystem the TPU only ever saw whatever one caller had on
hand: each VoteSet flushed its own staged batch, blocksync and the light
client formed their own windows, evidence checks dispatched two-row
batches, and mempool admission had no batch path at all. Under real
traffic the device ran many small, shape-diverse batches instead of a few
full ones — and batch size is the dominant term in committee verification
cost (arXiv:2302.00418); the FPGA verification-engine work
(arXiv:2112.02229) gets its throughput from exactly one shared,
always-full hardware verification queue fed by all protocol components.

This module is that queue, built the way an inference server does
continuous batching:

  producers  consensus vote flushes, blocksync/light commit windows,
             evidence checks, mempool admission — all submit rows of
             (pub_key, msg, sig) instead of owning device dispatch.
  classes    CONSENSUS > SYNC > MEMPOOL. A consensus (or sync) caller
             uses verify_now()/verify_many(): the batch drains
             IMMEDIATELY, inline on the calling thread, and coalesces
             whatever compatible queued work fits the bucket as filler.
             Mempool-class work uses submit(): per-item futures, flushed
             by the next inline drain riding along, or by the deadline
             worker when no higher-priority flush arrives in time.
  bucketing  every dispatched batch is padded (by the kernel) to the
             shared bucket ladder (ops/ed25519_kernel.bucket_size):
             powers of two to 2048, then multiples of 2048 — so XLA/
             Pallas compiles a handful of shapes once instead of once
             per unique batch size. warmup() pre-traces the ladder.
  fairness   bounded per-class queues; mempool admission is REJECTED
             (SchedulerSaturated) while consensus/sync backlog already
             fills buckets without it; a starvation guard promotes any
             group overdue past `starvation_limit` into the next batch
             regardless of class order.
  seams      dispatch rides the existing crypto/batch + ops/dispatch
             ladder unchanged: backend resolution consults the circuit
             breaker, device batches run under the DeviceSupervisor with
             the ed25519.*/sr25519.*/pallas.trace/mixed.resolve chaos
             sites armed, and every failure degrades to the CPU oracle.
             The scheduler adds its own chaos site ("sched.flush"): an
             injected scheduler fault falls back to per-group fragmented
             dispatch — verification survives the scheduler dying.

Thread model: the core is lock-guarded and asyncio-free. Inline drains
run on the caller's thread (consensus event loop, blocksync executor).
One lazy daemon worker thread serves deadline flushes; it parks on a
condition variable and only exists once something queues with a deadline.
"""

from __future__ import annotations

import concurrent.futures
import contextvars
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass

import numpy as np

from cometbft_tpu.libs import trace
from cometbft_tpu.libs.rowblock import RowBlock, SigColumns

# priority classes, highest first (the wire values appear in metrics
# labels and the crypto_health snapshot — keep in sync with README).
# LIGHT is the serving plane's class (light/fleet.py): fleet bisections
# ride below node-critical sync (a catching-up node beats external
# clients) but above mempool filler — and unlike mempool they are never
# rejected at admission (the fleet applies its own saturation gate).
CONSENSUS = "consensus"
SYNC = "sync"
LIGHT = "light"
MEMPOOL = "mempool"
CLASSES = (CONSENSUS, SYNC, LIGHT, MEMPOOL)

# grace beyond a group's deadline before its flush counts as a miss (the
# worker wakes AT the deadline; only contention pushes past this)
_MISS_SLACK = 0.005


class SchedulerSaturated(Exception):
    """Mempool-class admission rejected: the queues already hold more
    work than the next buckets can absorb. Callers shed load (mempool
    turns this into ErrMempoolIsFull) instead of queuing unboundedly."""


# --------------------------------------------------------------- work class
#
# Ambient class for call sites that reach the scheduler through the
# crypto/batch verifier seam (create_batch_verifier has no class
# parameter — its callers predate the scheduler). Consensus-critical is
# the safe default: unlabeled paths (LastCommit reconstruction on
# restart, RPC-triggered verifies) must never be starved behind filler.
#
# A ContextVar, NOT threading.local: the fleet service holds
# work_class(LIGHT) across awaits (provider fetches suspend mid-extent),
# and a thread-local would leak the class to every other coroutine
# interleaving on the loop thread — worse, two overlapping extents
# exiting non-LIFO would poison the ambient class permanently.
# ContextVars are per-task under asyncio and per-thread otherwise, and
# token-based reset is exact under any interleaving.

_ambient: contextvars.ContextVar = contextvars.ContextVar(
    "verify_work_class", default=None)


def current_class() -> str:
    return _ambient.get() or CONSENSUS


@contextmanager
def work_class(klass: str):
    """Set the ambient priority class for verifiers created in this
    dynamic extent — per-task under asyncio, per-thread otherwise
    (blocksync/light/evidence label their verification SYNC through
    this; the fleet labels its bisections LIGHT)."""
    if klass not in CLASSES:
        raise ValueError(f"unknown verify class {klass!r} (classes: {CLASSES})")
    token = _ambient.set(klass)
    try:
        yield
    finally:
        _ambient.reset(token)


# ------------------------------------------------------------------- groups


@dataclass(eq=False)  # identity semantics: groups are queue entries
class _Group:
    """One producer group: rows verified together, one recheck budget (a
    commit's rows must not spend a window-mate's oracle-recheck allowance
    — see ops/ed25519_kernel.apply_recheck). `unit` identifies the
    producer SUBMISSION the group arrived in (a verify_many window is one
    unit of several groups): the fragmented-baseline accounting pads each
    unit to its own bucket, which is exactly what the pre-scheduler
    architecture dispatched — one device batch per producer call."""

    klass: str
    # [(crypto.PubKey, bytes msg, bytes sig)], or the same rows as one
    # libs/rowblock.RowBlock (a commit's rows arrive so)
    rows: "list | RowBlock"
    submitted_at: float
    unit: int = 0
    deadline: float | None = None  # monotonic; None = inline-only
    futures: list[concurrent.futures.Future] | None = None
    mask: np.ndarray | None = None

    def resolve(self, mask: np.ndarray) -> None:
        self.mask = mask
        if self.futures is not None:
            for fut, ok in zip(self.futures, mask):
                if not fut.done():
                    fut.set_result(bool(ok))

    def fail(self, exc: BaseException) -> None:
        if self.futures is not None:
            for fut in self.futures:
                if not fut.done():
                    fut.set_exception(exc)


class VerifyScheduler:
    """The node-wide verify queue. One instance per process (module-level
    get() in cometbft_tpu/sched/__init__.py) — the device is a
    process-global resource, so its scheduler is too."""

    def __init__(
        self,
        max_lanes: int = 16384,
        sync_deadline: float = 0.002,
        light_deadline: float = 0.004,
        mempool_deadline: float = 0.010,
        queue_limit: int = 16384,
        starvation_limit: float = 0.25,
        clock=time.monotonic,
    ):
        self.max_lanes = max_lanes
        self.class_deadline = {
            CONSENSUS: 0.0, SYNC: sync_deadline, LIGHT: light_deadline,
            MEMPOOL: mempool_deadline,
        }
        self.queue_limit = queue_limit
        self.starvation_limit = starvation_limit
        self._clock = clock
        self._cond = threading.Condition()
        self._queues: dict[str, list[_Group]] = {k: [] for k in CLASSES}
        # running row counts per class (kept in lockstep with _queues so
        # the admission hot path never scans the backlog)
        self._depth: dict[str, int] = {k: 0 for k in CLASSES}
        self._worker: threading.Thread | None = None
        self._stop = False
        # ---- stats (lock: self._cond's lock via _stat calls under lock,
        # or the GIL for single int/float bumps)
        self.batches = 0
        self.rows_total = 0
        self.lanes_total = 0
        # what the SAME groups would have cost dispatched fragment-by-
        # fragment (each producer its own padded batch) — the pre-
        # scheduler architecture, measured on live traffic so fill-ratio
        # gains are asserted against real load, not synthetic replays
        self.frag_lanes_total = 0
        self.deadline_misses = 0
        # per-class attribution: the overload soak asserts consensus
        # flushes miss ZERO deadlines while mempool-class work sheds
        self.deadline_miss_by_class = {k: 0 for k in CLASSES}
        self.rejected = 0
        self.chaos_fallbacks = 0
        self.worker_flushes = 0
        self._shapes: set[int] = set()
        self._class_rows = {k: 0 for k in CLASSES}
        self._unit_seq = 0
        # bounded submit->dispatch latency samples per class (bench/test
        # percentile source; the histogram metric is the scrape surface)
        self._lat: dict[str, list[float]] = {k: [] for k in CLASSES}
        # warm the kernel import chain (jax + ops, ~2s cold) at
        # construction: the first flush must never pay module imports
        # inside its span — they would dominate its latency budget (a
        # phantom slow-batch capture) and sink per-batch span coverage
        from cometbft_tpu.ops import bls_kernel  # noqa: F401
        from cometbft_tpu.ops import ed25519_kernel  # noqa: F401
        from cometbft_tpu.ops import sr25519_kernel  # noqa: F401

    # ------------------------------------------------------------ metrics

    @staticmethod
    def _metrics():
        try:
            from cometbft_tpu.libs import metrics as m

            return m.sched_metrics()
        except Exception:  # noqa: BLE001 - metrics must never break verify
            return None

    def _publish_depth(self) -> None:
        m = self._metrics()
        if m is None:
            return
        try:
            for k in CLASSES:
                m.queue_depth.labels(k).set(self._depth[k])
        except Exception:  # noqa: BLE001
            pass

    # ------------------------------------------------------------- bucket

    @staticmethod
    def bucket_lanes(n: int) -> int:
        """The padded lane count a batch of n rows dispatches at — the
        single source of truth is the kernel's bucket ladder."""
        from cometbft_tpu.ops import ed25519_kernel

        return ed25519_kernel.bucket_size(max(n, 1))

    def bucket_ladder(self, cap: int | None = None) -> list[int]:
        """Every distinct device shape batches can dispatch at, up to
        cap lanes. len() of this bounds compiled-program count."""
        from cometbft_tpu.ops import ed25519_kernel as EK

        cap = cap or self.max_lanes
        out: list[int] = []
        b = EK.MIN_BUCKET
        while b <= cap and b < EK._POW2_CAP:
            out.append(b)
            b *= 2
        m = EK._POW2_CAP
        while m <= cap:
            out.append(m)
            m += EK._POW2_CAP
        return out

    def _next_unit(self) -> int:
        with self._cond:
            self._unit_seq += 1
            return self._unit_seq

    # ------------------------------------------------------------- submit

    def submit(self, rows, klass: str = MEMPOOL,
               deadline: float | None = None) -> list[concurrent.futures.Future]:
        """Queue rows for the next batch; returns one Future[bool] per
        row. The work rides the next inline drain as filler, or the
        deadline worker flushes it within the class deadline. Raises
        SchedulerSaturated for mempool-class work when the queues are
        already full (backpressure — shed at admission, not at dispatch).
        """
        if klass not in CLASSES:
            raise ValueError(f"unknown verify class {klass!r}")
        if not rows:
            return []
        now = self._clock()
        if deadline is None:
            deadline = now + self.class_deadline[klass]
        grp = _Group(klass=klass, rows=list(rows), submitted_at=now,
                     unit=self._next_unit(), deadline=deadline,
                     futures=[concurrent.futures.Future() for _ in rows])
        trace.event("sched.submit", cat="sched", klass=klass,
                    rows=len(grp.rows))
        with self._cond:
            depth = self._depth[klass]
            if klass == MEMPOOL:
                # reject when this class is full OR when higher-priority
                # backlog already fills the next buckets without filler
                higher = (self._depth[CONSENSUS] + self._depth[SYNC]
                          + self._depth[LIGHT])
                if depth + len(rows) > self.queue_limit or higher >= self.queue_limit:
                    self.rejected += 1
                    raise SchedulerSaturated(
                        f"mempool verify queue at {depth} rows "
                        f"(limit {self.queue_limit}, higher-class backlog {higher})")
            elif depth + len(rows) > 4 * self.queue_limit:
                # consensus/sync never reject (liveness) but a runaway
                # producer must surface loudly, not OOM silently
                try:
                    from cometbft_tpu.libs import log as _log

                    _log.default().error(
                        "verify scheduler queue overflow",
                        klass=klass, depth=str(depth))
                except Exception:  # noqa: BLE001
                    pass
            self._queues[klass].append(grp)
            self._depth[klass] += len(grp.rows)
            self._ensure_worker_locked()
            self._publish_depth()
            self._cond.notify_all()
        return grp.futures

    # ------------------------------------------------------- inline drain

    def verify_now(self, rows, klass: str = CONSENSUS) -> np.ndarray:
        """Verify rows NOW: one inline device batch on the calling
        thread, coalescing queued filler up to the bucket. Returns the
        (N,) bool mask for the caller's rows."""
        return self.verify_many([rows], klass)[0]

    def verify_many(self, rowlists, klass: str = CONSENSUS) -> list[np.ndarray]:
        """verify_now for a window of groups (blocksync stages a window
        of commits; each keeps its own recheck budget) — one coalesced
        dispatch, one mask per group."""
        unit = self._next_unit()
        own = [
            _Group(klass=klass,
                   rows=rows if isinstance(rows, RowBlock) else list(rows),
                   submitted_at=self._clock(), unit=unit)
            for rows in rowlists
        ]
        n_own = sum(len(g.rows) for g in own)
        if n_own == 0:
            for g in own:
                g.resolve(np.zeros(0, dtype=bool))
            return [g.mask for g in own]
        # root span: one inline drain == one batch lifecycle; a drain
        # slower than the latency budget keeps its full tree (slow-batch
        # capture ring)
        with trace.span("sched.verify", cat="sched", klass=klass,
                        rows=n_own, groups=len(own)) as sp:
            riders = self._take_riders(n_own)
            if riders:
                sp.set(rider_rows=sum(len(g.rows) for g in riders))
            self._dispatch(own + riders)
        return [g.mask for g in own]

    def flush(self) -> int:
        """Drain everything queued right now (tests, shutdown, bench).
        Returns the number of rows dispatched."""
        with self._cond:
            groups = [g for k in CLASSES for g in self._queues[k]]
            for k in CLASSES:
                self._queues[k].clear()
                self._depth[k] = 0
            self._publish_depth()
        if not groups:
            return 0
        self._dispatch(groups)
        return sum(len(g.rows) for g in groups)

    @staticmethod
    def _mesh(build: bool = False):
        """The active multi-chip verify mesh, or None (disabled, too few
        devices, not yet built, or the parallel plane failed to import).
        Only the dispatch path builds (build=True); telemetry and
        rider-budget math peek, so a health poll never registers
        per-chip supervisors. Never raises — the scheduler must dispatch
        with the mesh module broken."""
        try:
            from cometbft_tpu.parallel import mesh as _mesh_mod

            return (_mesh_mod.active() if build
                    else _mesh_mod.peek_active())
        except Exception:  # noqa: BLE001
            return None

    def _effective_max_lanes(self) -> int:
        """The lane budget one flush may coalesce: per-chip max_lanes
        times the LIVE mesh size — the scheduler fills per-chip lanes
        against the current topology, so an 8-chip mesh absorbs 8x the
        filler and a shrunken mesh stops over-coalescing into its
        survivors. Single-chip (mesh off) keeps the classic budget."""
        mesh = self._mesh()
        if mesh is None:
            return self.max_lanes
        from cometbft_tpu.ops import ed25519_kernel as EK

        return min(self.max_lanes * max(1, mesh.live_size_hint()),
                   1 << EK.MAX_BUCKET_LOG2)

    def _take_riders(self, n_own: int) -> list[_Group]:
        """Pop queued groups to fill the bucket the inline batch will
        dispatch at anyway. Starvation guard first: any group overdue
        past starvation_limit rides along regardless of class order."""
        with self._cond:
            queued = sum(self._depth.values())
            if queued == 0:
                return []
            target = self.bucket_lanes(
                min(n_own + queued, self._effective_max_lanes()))
            space = target - n_own
            out: list[_Group] = []
            now = self._clock()
            # overdue first (oldest first), then strict class priority
            overdue = sorted(
                (g for k in CLASSES for g in self._queues[k]
                 if now - g.submitted_at > self.starvation_limit),
                key=lambda g: g.submitted_at)
            seen = set(map(id, overdue))
            candidates = overdue + [
                g for k in CLASSES for g in self._queues[k]
                if id(g) not in seen
            ]
            for g in candidates:
                if len(g.rows) > space:
                    continue
                out.append(g)
                space -= len(g.rows)
            for g in out:
                self._queues[g.klass].remove(g)
                self._depth[g.klass] -= len(g.rows)
            self._publish_depth()
            return out

    # ----------------------------------------------------------- dispatch

    def _dispatch(self, groups: list[_Group]) -> None:
        """Form and run device batches for the groups (chunked at
        max_lanes, groups never split), resolve every mask/future. The
        scheduler's own chaos site fires here: an injected scheduler
        fault degrades to per-group fragmented dispatch — the pre-PR
        architecture — so verification survives scheduler failure."""
        if not groups:
            return
        try:
            from cometbft_tpu.libs import chaos

            chaos.fire("sched.flush")
        except Exception as exc:  # noqa: BLE001 - scheduler fault injected
            self.chaos_fallbacks += 1
            try:
                from cometbft_tpu.libs import log as _log

                _log.default().error(
                    "verify scheduler flush fault; dispatching fragmented",
                    err=str(exc))
            except Exception:  # noqa: BLE001
                pass
            for g in groups:
                try:
                    self._dispatch_core([g])
                except Exception:  # noqa: BLE001 - group's futures failed;
                    pass           # later groups must still dispatch
            return
        # chunk: groups are never split; a chunk holds up to the
        # effective lane budget (per-chip max_lanes x live mesh size)
        # unless a single group alone exceeds it (a 10k mega-commit
        # dispatches alone — the kernel's lane cap is far above it).
        # A failing chunk fails ITS futures (in _dispatch_core) and must
        # not strand the remaining chunks' futures — a hung future would
        # wedge a mempool admission await forever.
        lane_budget = self._effective_max_lanes()
        chunks: list[list[_Group]] = []
        chunk: list[_Group] = []
        chunk_rows = 0
        for g in groups:
            if chunk and chunk_rows + len(g.rows) > lane_budget:
                chunks.append(chunk)
                chunk, chunk_rows = [], 0
            chunk.append(g)
            chunk_rows += len(g.rows)
        if chunk:
            chunks.append(chunk)
        first_exc: Exception | None = None
        for c in chunks:
            try:
                self._dispatch_core(c)
            except Exception as exc:  # noqa: BLE001
                first_exc = first_exc or exc
        if first_exc is not None:
            raise first_exc

    def _dispatch_core(self, groups: list[_Group]) -> None:
        """One device batch: group rows by scheme, dispatch each scheme's
        sub-batch through the existing ladder (TPU kernels under the
        supervisor/breaker, else the registry CPU verifier), resolve all
        device thunks with ONE fetch, slice masks back per group."""
        n_rows = sum(len(g.rows) for g in groups)
        if trace.enabled():
            # queue attribution: each group's submit->dispatch wait (an
            # interval on the group, not a span on any one thread).
            # Inline-drain own groups contribute only their ~µs of
            # residence, so the queue share stays dominated by groups
            # that genuinely sat in the queue.
            t_flush = self._clock()
            for g in groups:
                wait = t_flush - g.submitted_at
                if wait > 0:
                    trace.account("queue", wait)
        lanes = self.bucket_lanes(n_rows)
        flush_sp = trace.span("sched.flush", cat="sched", rows=n_rows,
                              groups=len(groups), lanes=lanes,
                              classes=",".join(sorted(
                                  {g.klass for g in groups})))
        try:
            with flush_sp:
                masks = self._run_batch(groups)
        except Exception as exc:  # noqa: BLE001 - must not lose futures
            for g in groups:
                g.fail(exc)
            raise
        now = self._clock()
        # ---- stats (under the lock: worker and inline drains dispatch
        # concurrently) + metrics
        misses = 0
        with self._cond:
            self.batches += 1
            self.rows_total += n_rows
            self.lanes_total += lanes
            self._shapes.add(lanes)
            unit_rows: dict[int, int] = {}
            for g in groups:
                unit_rows[g.unit] = unit_rows.get(g.unit, 0) + len(g.rows)
                self._class_rows[g.klass] += len(g.rows)
            for nr in unit_rows.values():
                self.frag_lanes_total += self.bucket_lanes(nr)
            for g in groups:
                buf = self._lat[g.klass]
                buf.append(now - g.submitted_at)
                if len(buf) > 4096:
                    del buf[:2048]
                if g.deadline is not None and now > g.deadline + _MISS_SLACK:
                    misses += 1
                    self.deadline_miss_by_class[g.klass] += 1
            self.deadline_misses += misses
        m = self._metrics()
        if m is not None:
            try:
                m.batch_lanes.observe(lanes)
                m.fill_ratio.observe(n_rows / lanes)
                if misses:
                    m.flush_deadline_misses.inc(misses)
                for g in groups:
                    m.flush_latency.labels(g.klass).observe(
                        now - g.submitted_at)
            except Exception:  # noqa: BLE001
                pass
        for g, mask in zip(groups, masks):
            g.resolve(mask)

    def _run_batch(self, groups: list[_Group]) -> list[np.ndarray]:
        """The scheme-grouped verification core. Device thunks for every
        scheme resolve together (one device->host fetch); per-group row
        boundaries become the kernel's recheck groups so each producer
        keeps its own host-oracle recheck budget.

        Topology routing: on the tpu backend with an active multi-chip
        mesh (parallel/mesh.py), each scheme's sub-batch is sharded over
        the live mesh with class-aware placement — the batch's highest
        priority class decides (consensus pins to the least-loaded chip
        for latency; sync/mempool spread for throughput). A chip dying
        mid-flush re-shards inside the mesh; only an all-chips-dead mesh
        degrades to the single-chip ladder this method otherwise uses."""
        from cometbft_tpu.crypto import batch as crypto_batch
        from cometbft_tpu.ops import ed25519_kernel

        # scheme -> (the sub-batch's SigColumns, the recheck groups' bounds
        # in it, [(group, the group's lanes, from, to)])
        per: dict[str, tuple] = {}
        # batch preparation is all "stage": backend selection plus the
        # scheme grouping/bounds pass (the span starts before
        # resolve_backend so flush glue stays inside the coverage model)
        with trace.span("sched.group_rows", cat="stage",
                        rows=sum(len(g.rows) for g in groups)):
            backend = crypto_batch.resolve_backend()
            mesh = self._mesh(build=True) if backend == "tpu" else None
            klasses = {g.klass for g in groups}
            # the batch's placement class: its highest-priority member
            batch_klass = next(k for k in CLASSES if k in klasses)
            # the door: a group that came as tuples (riders, vote flushes,
            # evidence) becomes a block here, by the one lane loop; from
            # here on rows are columns (libs/rowblock.py) and a scheme's
            # sub-batch is its columns in every group, end to end
            members: dict[str, list] = {}
            for gi, g in enumerate(groups):
                block = (g.rows if isinstance(g.rows, RowBlock)
                         else RowBlock.from_tuples(g.rows))
                for scheme, (lanes, cols) in block.parts.items():
                    members.setdefault(scheme, []).append((gi, lanes, cols))
            for scheme, found in members.items():
                bounds, where, a = [], [], 0
                for gi, lanes, cols in found:
                    bounds.append((a, a + len(cols)))
                    where.append((gi, lanes, a, a + len(cols)))
                    a += len(cols)
                per[scheme] = (SigColumns.concat([f[2] for f in found]),
                               bounds, where)
        thunks: list = []
        thunk_schemes: list[str] = []
        host_masks: dict[str, np.ndarray] = {}
        # the whole dispatch-and-resolve phase sits inside one counted
        # span so per-scheme loop glue, thunk construction, and the
        # resolve call are covered flush time; nested counted children
        # (host_verify here, the kernels' stage/transfer/fetch spans on
        # the device path) subtract from its self time, leaving only the
        # true glue attributed as compute
        mesh_thunks: list[tuple[str, object]] = []
        with trace.span("sched.dispatch", cat="compute",
                        schemes=len(per)):
            for scheme, (cols, bounds, _where) in per.items():
                if mesh is not None and scheme == "ed25519":
                    # mesh shards dispatch eagerly inside verify_async;
                    # every scheme's shards are in flight before any join.
                    # An ed25519 shard is a slice of the columns
                    mesh_thunks.append((scheme, mesh.verify_async(
                        scheme, cols.pubs, cols.msgs, cols.sigs,
                        klass=batch_klass, recheck_groups=bounds,
                        pub_rows=cols.pub_rows)))
                elif mesh is not None and scheme in ("sr25519", "bls12381"):
                    mesh_thunks.append((scheme, mesh.verify_async(
                        scheme, cols.pubs, cols.msgs.tolist(),
                        cols.sig_list(), klass=batch_klass,
                        recheck_groups=bounds)))
                elif backend == "tpu" and scheme == "ed25519":
                    thunks.append(ed25519_kernel.verify_batch_async(
                        cols.pubs, cols.msgs, cols.sigs,
                        recheck_groups=bounds, pub_rows=cols.pub_rows))
                    thunk_schemes.append(scheme)
                elif backend == "tpu" and scheme == "sr25519":
                    from cometbft_tpu.ops import sr25519_kernel

                    thunks.append(sr25519_kernel.verify_batch_async(
                        cols.pubs, cols.msgs, cols.sigs,
                        pub_rows=cols.pub_rows))
                    thunk_schemes.append(scheme)
                elif backend == "tpu" and scheme == "bls12381":
                    from cometbft_tpu.ops import bls_kernel

                    thunks.append(bls_kernel.verify_batch_async(
                        cols.pubs, cols.msgs.tolist(), cols.sig_list(),
                        recheck_groups=bounds))
                    thunk_schemes.append(scheme)
                else:
                    # sig_rows marks THE counting site for these rows
                    # (rolling attribution row totals; every other span
                    # annotates informational `rows` only)
                    with trace.span("sched.host_verify", cat="compute",
                                    scheme=scheme, sig_rows=len(cols)):
                        host_masks[scheme] = self._host_mask(scheme, cols)
            if thunks:
                resolved = ed25519_kernel.resolve_batches(thunks)
                for scheme, mask in zip(thunk_schemes, resolved):
                    host_masks[scheme] = np.asarray(mask, dtype=bool)
            # every mesh thunk must be JOINED even if an earlier one
            # raises — a skipped join would strand its shards' inflight
            # accounting and skew placement for the process lifetime
            mesh_err: Exception | None = None
            for scheme, thunk in mesh_thunks:
                try:
                    host_masks[scheme] = np.asarray(thunk(), dtype=bool)
                except Exception as exc:  # noqa: BLE001
                    mesh_err = mesh_err or exc
            if mesh_err is not None:
                raise mesh_err
        with trace.span("sched.slice_masks", cat="resolve"):
            out = [np.zeros(len(g.rows), dtype=bool) for g in groups]
            for scheme, (_cols, _bounds, where) in per.items():
                mask = host_masks[scheme]
                for gi, lanes, a, b in where:
                    out[gi][lanes] = mask[a:b]
        return out

    @staticmethod
    def _host_mask(scheme: str, cols: SigColumns) -> np.ndarray:
        """CPU rung for one scheme's rows: the registry batch verifier
        when the scheme has one, else a serial host loop (an unbatchable
        key type — secp256k1 — must still verify, not crash the batch).
        A structurally-bad row fails alone instead of raising."""
        from cometbft_tpu.crypto import batch as crypto_batch

        n = len(cols)
        rows = list(zip(cols.keys, cols.msgs.tolist(), cols.sig_list()))
        factory = crypto_batch._REGISTRY.get(scheme)
        if factory is not None:
            bv = factory()
            staged: list[int] = []
            mask = np.zeros(n, dtype=bool)
            for i, row in enumerate(rows):
                try:
                    bv.add(*row)
                    staged.append(i)
                except Exception:  # noqa: BLE001 - structural reject
                    pass
            if staged:
                _, sub = bv.verify()
                for i, ok in zip(staged, sub):
                    mask[i] = bool(ok)
            return mask
        mask = np.zeros(n, dtype=bool)
        for i, (key, msg, sig) in enumerate(rows):
            try:
                mask[i] = bool(key.verify_signature(msg, sig))
            except Exception:  # noqa: BLE001
                mask[i] = False
        return mask

    # ------------------------------------------------------ deadline worker

    def _ensure_worker_locked(self) -> None:
        if self._worker is not None and self._worker.is_alive():
            return
        self._stop = False
        self._worker = threading.Thread(
            target=self._worker_loop, name="verify-sched", daemon=True)
        self._worker.start()

    def _worker_loop(self) -> None:
        """Flush queued groups when their deadlines come due and no
        inline drain picked them up as filler first."""
        while True:
            with self._cond:
                if self._stop:
                    return
                deadlines = [
                    g.deadline for k in CLASSES for g in self._queues[k]
                    if g.deadline is not None
                ]
                now = self._clock()
                if not deadlines:
                    self._cond.wait(timeout=0.25)
                    continue
                dl = min(deadlines)
                if dl > now:
                    self._cond.wait(timeout=min(dl - now, 0.25))
                    continue
                groups = [g for k in CLASSES for g in self._queues[k]]
                for k in CLASSES:
                    self._queues[k].clear()
                    self._depth[k] = 0
                self._publish_depth()
            if groups:
                self.worker_flushes += 1
                try:
                    self._dispatch(groups)
                except Exception:  # noqa: BLE001 - futures already failed
                    pass

    def stop(self) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._worker is not None:
            self._worker.join(timeout=2.0)
            self._worker = None

    # -------------------------------------------------------------- warmup

    def warmup(self, max_lanes: int | None = None) -> list[int]:
        """Pre-trace the bucket ladder on the device so the first real
        consensus flush doesn't pay a cold compile mid-round: on every
        live chip of an active mesh (VerifyMesh.warmup: its shards are
        what a flush rides there), else on the one chip. No-op off the
        TPU backend (CPU programs compile in milliseconds and tests pin
        the CPU backend). Returns the lane counts traced."""
        from cometbft_tpu.crypto import batch as crypto_batch

        if crypto_batch.resolve_backend() != "tpu":
            return []
        from cometbft_tpu.ops import ed25519_kernel as EK
        from cometbft_tpu.ops import limbs as _limbs

        ladder = self.bucket_ladder(max_lanes or 2048)
        mesh = self._mesh(build=True)
        if mesh is not None:
            for b in ladder:
                _limbs.POOL.warm(b)
            return mesh.warmup(ladder)
        traced: list[int] = []
        for b in ladder:
            # double-buffer pair per rung: the first real flushes must
            # not allocate staging blocks on the hot path
            _limbs.POOL.warm(b)
            try:
                from cometbft_tpu.ops import challenge as _challenge

                if _challenge.enabled():
                    # worst-case flat wire block for the device-challenge
                    # path (smaller vars warm organically on first use)
                    _limbs.POOL.warm_flat(
                        _challenge.block_words(b, _challenge.MAX_VAR))
            except Exception:  # noqa: BLE001 - warmup is best-effort
                pass
            try:
                EK.resolve_batches(
                    [EK.verify_batch_async(*EK.warmup_rows(b))])
                traced.append(b)
            except Exception:  # noqa: BLE001 - device trouble: supervisor owns it
                break
        return traced

    # ------------------------------------------------------------ snapshot

    def latency_quantiles(self) -> dict:
        """Per-class submit->dispatch latency p50/p99 in ms from the
        bounded sample buffers (None for classes with no traffic)."""
        out = {}
        for k in CLASSES:
            buf = sorted(self._lat[k])
            if not buf:
                out[k] = None
                continue
            out[k] = {
                "n": len(buf),
                "p50_ms": round(buf[len(buf) // 2] * 1e3, 3),
                "p99_ms": round(buf[min(len(buf) - 1,
                                        int(len(buf) * 0.99))] * 1e3, 3),
            }
        return out

    def health(self) -> dict:
        """The crypto_health `verify_sched` section (rpc/core.py) and the
        assertion surface for tests/bench."""
        with self._cond:
            depth = dict(self._depth)
        fill = self.rows_total / self.lanes_total if self.lanes_total else None
        frag = (self.rows_total / self.frag_lanes_total
                if self.frag_lanes_total else None)
        return {
            "batches": self.batches,
            "rows_total": self.rows_total,
            "lanes_total": self.lanes_total,
            "fill_ratio_mean": round(fill, 4) if fill is not None else None,
            "fragmented_fill_ratio_mean":
                round(frag, 4) if frag is not None else None,
            "dispatch_shapes": sorted(self._shapes),
            "bucket_ladder_len": len(self.bucket_ladder()),
            "queue_depth": depth,
            "class_rows": dict(self._class_rows),
            "deadline_misses": self.deadline_misses,
            "deadline_miss_by_class": dict(self.deadline_miss_by_class),
            "rejected": self.rejected,
            "chaos_fallbacks": self.chaos_fallbacks,
            "worker_flushes": self.worker_flushes,
            "worker_alive": bool(self._worker and self._worker.is_alive()),
            "max_lanes": self.max_lanes,
            "effective_max_lanes": self._effective_max_lanes(),
            "mesh": self._mesh_view(),
            "deadlines": dict(self.class_deadline),
            "link": self._link_view(),
        }

    def _mesh_view(self) -> dict:
        """The scheduler's live view of the multi-chip topology it fills
        lanes against (never raises — telemetry)."""
        mesh = self._mesh()
        if mesh is None:
            return {"active": False}
        try:
            return {
                "active": True,
                "devices": len(mesh.chips),
                "live": mesh.live_size(),
                "placement": mesh.placement,
            }
        except Exception:  # noqa: BLE001
            return {"active": True}

    @staticmethod
    def planning_bytes_per_sig() -> float:
        """The live wire cost of one signature used for flush planning:
        the reduced-send accounting's measured rate (ops/residency.py —
        the number PR 6's trace attribution also records; with device
        challenge derivation on, the measured steady state is ~66-82
        B/sig because the k plane never crosses the wire), falling back
        to the rolling attribution model, then to the pre-reduced-send
        96 B/sig constant only when the process has not sent a single
        batch yet."""
        try:
            from cometbft_tpu.ops import residency

            measured = residency.measured_bytes_per_sig()
            if measured:
                return float(measured)
        except Exception:  # noqa: BLE001 - planning must never raise
            pass
        try:
            from cometbft_tpu.libs import trace as _trace

            attr = _trace.attribution()
            bps = attr.get("bytes_per_sig_tx")
            if bps:
                return float(bps)
        except Exception:  # noqa: BLE001
            pass
        return 96.0

    def _link_view(self) -> dict:
        """The scheduler's live view of the host<->device link
        (libs/linkmodel.py, fed by the kernels' measured transfers):
        estimated bandwidth/RTT plus the predicted wall cost of a
        full-lane flush at the MEASURED bytes-per-sig (reduced-send
        accounting; the hardcoded 96 B/sig planning constant is gone —
        it is only the cold-start fallback before any batch has been
        sent). Never raises (telemetry)."""
        try:
            from cometbft_tpu.libs import linkmodel

            lnk = linkmodel.link()
            out = lnk.snapshot()
            bps = self.planning_bytes_per_sig()
            out["planning_bytes_per_sig"] = round(bps, 2)
            # current wire cost of one maximally-coalesced flush
            est = lnk.transfer_seconds(int(bps * self.max_lanes))
            out["full_flush_wire_ms_at_measured_bytes_per_sig"] = (
                round(est * 1e3, 2) if est is not None else None)
            return out
        except Exception:  # noqa: BLE001
            return {}
