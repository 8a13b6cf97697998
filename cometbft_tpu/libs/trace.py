"""Verify-plane flight recorder: structured span tracing with wall-time
attribution.

Every number on the bench trajectory so far was *inferred* from aggregate
output; nothing in the node could say, for one batch or one consensus
height, how many microseconds went to staging vs host->device transfer vs
kernel compute vs result fetch vs queueing. This module is that
instrument — the software analog of how FPGA verification engines
instrument their offload pipelines to find the PCIe-vs-compute split
(arXiv:2112.02229) and how committee-consensus signature studies break
cost down per pipeline stage (arXiv:2302.00418).

Design constraints, in priority order:

  near-zero when off   `span()` returns a shared no-op after one module-
                       global bool read; nothing allocates, nothing locks.
                       Tier-1 asserts <3% overhead on a 1k-row verify.
  cheap when on        finished spans are plain dicts dropped into a
                       bounded ring buffer (preallocated list + atomic-
                       under-the-GIL monotonic counter); no I/O, no
                       serialization until an exporter asks.
  attributable         spans carry a stage category; on finish, a span's
                       SELF time (duration minus stage-categorized
                       descendants) is accounted into rolling per-stage
                       totals — the `attribution` section of crypto_health
                       and the number the mesh / reduced-send PRs are
                       judged against. Wire bytes ride the spans
                       (`add_bytes`) so bytes-per-sig is measured, not
                       estimated.
  exportable           Chrome trace-event JSON (Perfetto-loadable) via
                       chrome_trace(); served by the `trace_dump` RPC
                       route and the `trace-dump` CLI subcommand.
  post-mortem          root spans slower than `slow_ms` keep their full
                       span tree in a bounded capture ring — a slow batch
                       or height is examinable after the fact, and its
                       log lines correlate by trace/span id (libs/log.py
                       stamps them automatically).

Stage categories (the attribution model), from the entry down:

  node       a call's root (`commit.verify`, `commit.stage_verify`,
             `commit.prefetch`, `commit.resolve`; `light.verify` around a
             light client's hop): its SELF time is what no finer span
             below covers — the unattributed host time of the commit path
  header     a light client's checks of a new header before any
             signature (`light.header`: light/verifier.py
             _verify_new_header_and_vals, with the Merkle roots of the
             header and of the validator set handed over) and the Merkle
             root residency pays to announce a validator set object it
             has not seen (`residency.announce`)
  signbytes  CanonicalVote sign-bytes encoding (`commit.sign_bytes`; on
             the serial path, attr `serial`, one span around the loop
             that encodes and verifies a signature at a time) and the
             sr25519 Merlin/STROBE transcripts (`sr25519.transcript`)
  collect    a commit turned into rows (`commit.rows`) and a mask turned
             back into the answer (`commit.verdict`)
  queue      submit->dispatch wait in the verify scheduler
  stage      host staging: structural checks, hashing, packing
  transfer   host->device bytes (staged words, pubkey coordinate tables)
  challenge  challenge derivation (device SHA-512+Barrett, or the host-k
             fallback rungs of ops/challenge.py)
  compute    device dispatch / host-oracle verification
  fetch      device->host result bytes (reduced-fetch headers, payloads)
  join       the multi-chip mesh's caller blocked on its shards (`mesh.join`,
             parallel/mesh.py) until every chip's dispatch closure has
             handed its programs over; the per-shard fetches inside are
             `fetch`
  resolve    mask decode, integrity checks, host re-checks, slicing
  gc         a full (generation 2) collection's pause (`gc.full`), child
             of whatever span was open on the thread it stopped

One clock with the device trace: while the tracer is on, a span entered
as a context manager also enters a `jax.profiler.TraceAnnotation` of its
name on its own thread, so a profiler trace taken meanwhile holds the
program's spans beside the runtime's events and the device's idle gaps
(benchmarks/reduce.py names each gap by the innermost host event over
it). `begin()` timelines, `event()` instants and a `finish()` without
`__exit__` enter none: a TraceMe is bound to its thread's stack. Without
JAX there is no annotation and no error. Device-side overlap of transfer
and compute is read from that trace, not inferred from host spans.

Span parenting uses a contextvars.ContextVar, so nesting is correct per
thread AND per asyncio task with no explicit plumbing; `wrap_ctx()` hands
a context-carrying callable to thread pools (the kernel transfer/fetch
pools) so device-side spans stay in their batch's tree.
"""

from __future__ import annotations

import contextvars
import gc
import itertools
import json
import threading
import time
from collections import deque
from typing import Any, Callable, Optional

# Stage categories counted by the attribution model. Spans with any other
# cat ("sched", "consensus", "sync", "mempool", "device", ...) appear in
# the trace but never in stage shares — they are containers, not stages.
STAGES = ("node", "header", "signbytes", "collect", "queue", "stage",
          "transfer", "challenge", "compute", "fetch", "join", "resolve",
          "gc")

# The builders of a commit's sign-rows (types/commit.py): a finished
# `commit.sign_bytes` span that built rows says which one ran (`path`) and
# how many rows it made (`rows`); a cache hit or the serial loop says neither.
SIGN_ROW_PATHS = ("vector", "scalar")

# The ways types/validation._commit_rows turns a commit into rows: a
# finished `commit.rows` span of its says which ran (`path`: over columns,
# or a lane at a time) and how many rows it handed over (`rows`); the
# other `commit.rows` spans (the verifier's add, a window's selection)
# say neither. The trusting check's span adds `lookup` = `address` (its
# block path joins the commit's addresses to the set's indices).
COMMIT_ROW_PATHS = ("block", "lane")

# span name -> (the attribution key its rows are summed under, its paths)
_ROW_PATHS = {"commit.sign_bytes": ("sign_rows", SIGN_ROW_PATHS),
              "commit.rows": ("commit_rows", COMMIT_ROW_PATHS)}

# Plain counts kept beside the stage times while the tracer is on
# (count(); `attribution.<group>.<name>` in crypto_health): a light
# client's hops and those of them answered "cannot be trusted"
# (light/verifier.verify; the span's `answer` has the rest), the rows the
# trusting check selected by the address join or by get_by_address a
# signature (types/validation._commit_rows), the Merkle roots of validator
# sets computed and the calls the kept root answered (ValidatorSet.hash).
COUNTS = {"light": ("hops", "hops_untrusted"),
          "trusting_rows": ("joined", "scanned"),
          "valset": ("hashes", "kept")}


def _no_rows_by_path() -> dict:
    return {key: dict.fromkeys(paths, 0)
            for key, paths in _ROW_PATHS.values()}


def _no_counts() -> dict:
    return {group: dict.fromkeys(names, 0)
            for group, names in COUNTS.items()}

_enabled = False  # module-global fast path: read before anything else

_current: contextvars.ContextVar[Optional["Span"]] = contextvars.ContextVar(
    "cbft_trace_span", default=None)


class Span:
    """One live span. Use as a context manager (the normal case) or via
    begin()/finish() for spans that outlive a single frame (the per-height
    consensus timeline). Attribute writes after finish are ignored."""

    __slots__ = ("id", "parent", "trace_id", "name", "cat", "t0", "t1",
                 "tid", "attrs", "bytes_tx", "bytes_rx", "_covered",
                 "_token", "_done", "_ann")

    def __init__(self, id_: int, parent: Optional["Span"], name: str,
                 cat: str, attrs: dict, t0: int):
        self.id = id_
        self.parent = parent
        self.trace_id = parent.trace_id if parent is not None else id_
        self.name = name
        self.cat = cat
        self.t0 = t0
        self.t1 = 0
        self.tid = threading.get_ident()
        self.attrs = attrs
        self.bytes_tx = 0
        self.bytes_rx = 0
        self._covered = 0  # ns of stage-categorized descendant time
        self._token = None
        self._done = False
        self._ann = None  # the profiler annotation entered with the span

    # ------------------------------------------------------------- attrs

    def set(self, **kv: Any) -> "Span":
        if not self._done:
            self.attrs.update(kv)
        return self

    def add_bytes(self, tx: int = 0, rx: int = 0) -> "Span":
        """Record wire bytes moved inside this span (host->device tx,
        device->host rx) — the measured-bytes-per-sig source."""
        if not self._done:
            self.bytes_tx += tx
            self.bytes_rx += rx
        return self

    # ------------------------------------------------------- context mgr

    def __enter__(self) -> "Span":
        self._token = _current.set(self)
        ann = _annotation
        if ann is not None:
            # the same interval on the profiler's clock, on this thread:
            # left in __exit__ only (a TraceMe is bound to its thread)
            self._ann = ann(self.name)
            self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self.finish()
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(*exc)
        return False

    def finish(self) -> None:
        if self._done:
            return
        self._done = True
        if self._token is not None:
            # entered via `with` (or bare __enter__): pop ourselves off
            # the context stack even when finish() is called directly —
            # a leaked token would silently reparent every later span
            try:
                _current.reset(self._token)
            except ValueError:
                pass  # finished from a different Context than entered
            self._token = None
        t = _T
        if t is not None:
            t._finish(self)


class _NopSpan:
    """The shared disabled-mode span: every method is a no-op returning
    self, so instrumented code needs no enabled checks of its own."""

    __slots__ = ()

    def __enter__(self) -> "_NopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **kv: Any) -> "_NopSpan":
        return self

    def add_bytes(self, tx: int = 0, rx: int = 0) -> "_NopSpan":
        return self

    def finish(self) -> None:
        pass


_NOP = _NopSpan()


class Tracer:
    """Ring buffer + attribution accumulator + slow-batch capture ring.
    One per process (the verify plane is process-global); `clock` is
    injectable (ns) so tests run on a fake timeline."""

    def __init__(self, capacity: int = 65536, slow_ms: float = 250.0,
                 slow_captures: int = 32, clock=time.monotonic_ns):
        if capacity < 1:
            raise ValueError("trace capacity must be >= 1")
        self.capacity = capacity
        self.slow_ms = slow_ms
        self._clock = clock
        self._buf: list = [None] * capacity
        self._ctr = itertools.count()
        self._pos = 0  # next write index (== spans finished so far)
        self._ids = itertools.count(1)
        self.t_origin = clock()
        self._slow: deque = deque(maxlen=max(1, slow_captures))
        self._lock = threading.Lock()
        # rolling attribution: stage -> ns of SELF time, plus wire bytes
        # and signature rows (account() and stage-span finishes feed this)
        self._attr_ns = {s: 0 for s in STAGES}
        self._attr_rows = 0
        self._attr_tx = 0
        self._attr_rx = 0
        self._rows_by_path = _no_rows_by_path()
        self._counts = _no_counts()
        # collector pauses: the gc hook stamps them here lock-free (it
        # runs wherever an allocation triggers a collection, also inside
        # _finish under self._lock); the next _finish or attribution()
        # folds them in
        self._gc_pending: deque = deque()
        self._gc_open: Optional[tuple[int, int]] = None  # (generation, t0)
        self._gc_counts = [0, 0, 0]
        # thread -> the span whose _finish is running there: it has left
        # the context stack already, and a pause inside its bookkeeping
        # is still its own
        self._finishing: dict[int, Span] = {}

    # ------------------------------------------------------------- spans

    def start(self, name: str, cat: str, attrs: dict) -> Span:
        # clock FIRST: id allocation and the contextvar read are span-
        # creation overhead — stamping t0 before them bills that cost to
        # the new span instead of leaking it into the parent's uncovered
        # gap (per-batch coverage is an acceptance number)
        t0 = self._clock()
        return Span(next(self._ids), _current.get(), name, cat, attrs, t0)

    def _finish(self, span: Span) -> None:
        tid = threading.get_ident()
        self._finishing[tid] = span
        try:
            self._account_finish(span)
        finally:
            del self._finishing[tid]

    def _account_finish(self, span: Span) -> None:
        if self._gc_pending:
            # before this span's own coverage is read: a pause that
            # stopped it or a descendant must not count twice
            with self._lock:
                self._fold_gc()
        # ring write FIRST, before t1 is read: the Span object itself
        # goes into the ring (rendered to a dict lazily by snapshot()),
        # so the bulk of finish bookkeeping is timed INSIDE the span
        # rather than leaking into the parent's uncovered gap — per-batch
        # coverage is an acceptance number, tracer self-time must not
        # erode it. The counter bump is atomic under the GIL; a torn
        # read during snapshot() costs at most one stale slot, never a
        # crash — the price of keeping the hot path lock-free.
        self._ring_put(span)
        counted = span.cat in STAGES
        instant = span.attrs.get("instant", False)
        parent = span.parent
        if counted:
            # rows are NOT read off span attrs here: many spans along one
            # batch's path describe the same rows. Leaf verification
            # sites mark theirs with `sig_rows`; everything else
            # annotates `rows` informationally.
            rows = span.attrs.get("sig_rows", 0)
            if not isinstance(rows, int):
                rows = 0
            built = _rows_said(span.name, span.attrs)
            # attribution is updated inline with the lock taken BEFORE t1
            # is read: lock acquisition and the dict updates are tracer
            # overhead that must be timed inside the span, not in the
            # parent's uncovered gap. The parent-coverage += rides the
            # same lock: siblings of one parent finish concurrently
            # (kernel pool threads vs the flush thread), and a lost
            # update there would double-count the child at the parent.
            with self._lock:
                span.t1 = self._clock()
                dur = 0 if instant else max(0, span.t1 - span.t0)
                self._attr_ns[span.cat] += max(0, dur - span._covered)
                self._attr_rows += rows
                self._attr_tx += span.bytes_tx
                self._attr_rx += span.bytes_rx
                if built:
                    self._rows_by_path[built[0]][built[1]] += built[2]
                if parent is not None and not parent._done:
                    # a counted span covers its full duration at the parent
                    parent._covered += dur
        else:
            span.t1 = self._clock()
            dur = 0 if instant else max(0, span.t1 - span.t0)
            if parent is not None and not parent._done and span._covered:
                # an uncounted container passes through what its children
                # covered
                with self._lock:
                    if not parent._done:
                        parent._covered += span._covered
        # instants (event()) are points, not intervals: the wall ns
        # between start and finish is tracer overhead, not span duration
        if instant:
            span.t1 = span.t0
        if parent is None and self.slow_ms >= 0:
            # a root may carry its own latency budget (consensus heights
            # include unavoidable protocol waits and would flood the
            # capture ring under the global default)
            budget_ms = span.attrs.get("slow_ms", self.slow_ms)
            if dur >= budget_ms * 1e6:
                self._capture_slow(span)

    def _ring_put(self, span: Span) -> None:
        pos = next(self._ctr)
        self._buf[pos % self.capacity] = span
        self._pos = pos + 1

    # ---------------------------------------------------- collector pauses

    def _on_gc(self, phase: str, info: dict) -> None:
        """The gc.callbacks hook. Takes no lock and makes no span: it may
        run under self._lock, which is not re-entrant. Collections never
        nest and run with the GIL held, so the one open slot is enough."""
        if phase == "start":
            self._gc_open = (info["generation"], self._clock())
            return
        opened, self._gc_open = self._gc_open, None
        if opened is None:
            return  # hooked between a collection's start and its stop
        gen, t0 = opened
        self._gc_counts[gen] += 1
        if gen == 2:
            tid = threading.get_ident()
            self._gc_pending.append((t0, self._clock(), tid,
                                     self._finishing.get(tid)
                                     or _current.get()))

    def _fold_gc(self) -> None:
        """Turn the pauses the hook stamped into `gc.full` spans (under
        self._lock), each the child of the innermost span it lies in: it
        covers its time at the nearest ancestor still running, or comes
        off the stage of the first one that finished with the pause
        inside it."""
        while self._gc_pending:
            t0, t1, tid, parent = self._gc_pending.popleft()
            while parent is not None and parent.t1 and t0 >= parent.t1:
                # stamped in that span's _finish, after its end was read
                parent = parent.parent
            span = Span(next(self._ids), parent, "gc.full", "gc",
                        {"generation": 2}, t0)
            span.tid, span.t1, span._done = tid, t1, True
            self._ring_put(span)
            dur = max(0, t1 - t0)
            self._attr_ns["gc"] += dur
            while parent is not None:
                if not parent.t1:
                    parent._covered += dur
                    break
                if parent.cat in STAGES:
                    self._attr_ns[parent.cat] = max(
                        0, self._attr_ns[parent.cat] - dur)
                    break
                parent = parent.parent

    def _render(self, span: Span) -> dict:
        dur = 0 if span.attrs.get("instant") \
            else max(0, span.t1 - span.t0)
        parent = span.parent
        return {
            "id": span.id,
            "parent_id": parent.id if parent is not None else None,
            "trace_id": span.trace_id,
            "name": span.name,
            "cat": span.cat,
            "t0_ns": span.t0 - self.t_origin,
            "dur_ns": dur,
            "tid": span.tid,
            "bytes_tx": span.bytes_tx,
            "bytes_rx": span.bytes_rx,
            "attrs": span.attrs,
        }

    def _capture_slow(self, root: Span) -> None:
        """A root span blew its latency budget: keep its full span tree
        (everything in the ring sharing its trace_id) for post-mortem.
        Filter on the raw Span objects first — rendering the whole ring
        to dicts per capture would cost tens of ms at full capacity."""
        tree = [self._render(s) for s in self._raw()
                if s.trace_id == root.trace_id]
        self._slow.append({
            "trace_id": root.trace_id,
            "root": root.name,
            "dur_ms": round(max(0, root.t1 - root.t0) / 1e6, 3),
            "attrs": root.attrs,
            "spans": tree,
        })

    # ------------------------------------------------------- attribution

    def account(self, stage: str, seconds: float, rows: int = 0,
                tx_bytes: int = 0, rx_bytes: int = 0) -> None:
        """Feed the rolling attribution directly (the scheduler accounts
        queue wait this way — queue time is an interval on the group, not
        a span on any one thread)."""
        ns = int(seconds * 1e9)
        with self._lock:
            self._attr_ns[stage] = self._attr_ns.get(stage, 0) + ns
            self._attr_rows += rows
            self._attr_tx += tx_bytes
            self._attr_rx += rx_bytes

    def count(self, group: str, name: str, n: int) -> None:
        with self._lock:
            self._counts[group][name] += n

    def attribution(self) -> dict:
        with self._lock:
            self._fold_gc()
            ns = dict(self._attr_ns)
            rows, tx, rx = self._attr_rows, self._attr_tx, self._attr_rx
            by_path = {k: dict(v) for k, v in self._rows_by_path.items()}
            counts = {k: dict(v) for k, v in self._counts.items()}
            gen0, gen1, gen2 = self._gc_counts
        out = _attribution_dict(ns, rows, tx, rx, by_path)
        out["gc_collections"] = {"gen0": gen0, "gen1": gen1, "gen2": gen2}
        out.update(counts)
        return out

    def reset_attribution(self) -> None:
        with self._lock:
            self._attr_ns = {s: 0 for s in STAGES}
            self._attr_rows = 0
            self._attr_tx = 0
            self._attr_rx = 0
            self._rows_by_path = _no_rows_by_path()
            self._counts = _no_counts()
            self._gc_pending.clear()
            self._gc_counts = [0, 0, 0]

    # ----------------------------------------------------------- reading

    def _raw(self) -> list:
        """Finished Span objects, oldest first (up to capacity)."""
        pos = self._pos
        if pos <= self.capacity:
            out = self._buf[:pos]
        else:
            i = pos % self.capacity
            out = self._buf[i:] + self._buf[:i]
        return [s for s in out if s is not None]

    def snapshot(self) -> list[dict]:
        """Finished spans, oldest first (up to capacity), rendered to
        plain dicts. A span caught mid-finish (ring slot written, t1 not
        yet stamped) renders with dur 0 — a torn read, not a crash."""
        return [self._render(s) for s in self._raw()]

    def dropped(self) -> int:
        return max(0, self._pos - self.capacity)

    def slow_captures(self) -> list[dict]:
        return list(self._slow)


_T: Optional[Tracer] = None
_cfg_lock = threading.Lock()

# jax.profiler.TraceAnnotation while the tracer is on and JAX imports,
# else None: Span.__enter__ reads it once
_annotation: Any = None


def _gc_hook(phase: str, info: dict) -> None:
    t = _T
    if t is not None:
        t._on_gc(phase, info)


def _switch_hooks(on: bool) -> None:
    """The tracer's two hooks into the process, held only while it is on
    (under _cfg_lock): the profiler annotation class and the collector's
    callback."""
    global _annotation
    if _gc_hook in gc.callbacks:
        gc.callbacks.remove(_gc_hook)
    _annotation = None
    if not on:
        return
    try:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    except Exception:  # noqa: BLE001 - no JAX: spans without annotations
        pass
    gc.callbacks.append(_gc_hook)


# ------------------------------------------------------------- public API


def span(name: str, cat: str = "", parent: Any = None, **attrs: Any):
    """Start a span (context manager). Near-free when tracing is off.
    `parent` overrides the contextvar parent — the consensus height
    timeline hands its begin()-span here so flush/commit spans join the
    height's tree even though the timeline outlives any one frame."""
    # snapshot _T: reset() flips _enabled then drops the tracer, and an
    # in-flight pool thread may pass the bool check just before — tracing
    # must degrade to a no-op, never AttributeError inside a verify batch
    t = _T
    if not _enabled or t is None:
        return _NOP
    s = t.start(name, cat, attrs)
    if isinstance(parent, Span):
        s.parent = parent
        s.trace_id = parent.trace_id
    return s


def begin(name: str, cat: str = "", **attrs: Any):
    """A span NOT bound to the calling frame's context (no contextvar
    touch): for timelines spanning many frames/tasks, e.g. one consensus
    height. Finish with .finish()."""
    t = _T
    if not _enabled or t is None:
        return _NOP
    s = t.start(name, cat, attrs)
    s.parent = None  # context-free: always a root
    s.trace_id = s.id
    return s


def event(name: str, cat: str = "", parent: Any = None, **attrs: Any) -> None:
    """An instant event (zero-duration span) — step transitions etc.
    `parent` joins the event to a begin()-timeline's tree (consensus round
    steps onto their height span)."""
    t = _T
    if not _enabled or t is None:
        return
    s = t.start(name, cat, attrs)
    if isinstance(parent, Span):
        s.parent = parent
        s.trace_id = parent.trace_id
    s.attrs["instant"] = True
    s.finish()


def account(stage: str, seconds: float, rows: int = 0,
            tx_bytes: int = 0, rx_bytes: int = 0) -> None:
    t = _T
    if _enabled and t is not None:
        t.account(stage, seconds, rows=rows, tx_bytes=tx_bytes,
                  rx_bytes=rx_bytes)


def count(group: str, name: str, n: int = 1) -> None:
    """Add n to one of COUNTS while the tracer is on; nothing when off."""
    t = _T
    if _enabled and t is not None:
        t.count(group, name, n)


def add_bytes(tx: int = 0, rx: int = 0) -> None:
    """Record wire bytes against the active span (or straight into the
    rolling totals when no span is active) — lets deep transfer sites
    (the pubkey-coordinate upload inside PubKeyCache.stage) report bytes
    without threading a span handle through."""
    t = _T
    if not _enabled or t is None:
        return
    s = _current.get()
    if s is not None:
        s.add_bytes(tx=tx, rx=rx)
    else:
        t.account("transfer", 0.0, tx_bytes=tx, rx_bytes=rx)


def enabled() -> bool:
    return _enabled


def current_ids() -> Optional[tuple[int, int]]:
    """(trace_id, span_id) of the active span, or None. The log-line
    correlation hook (libs/log.py) — must be cheap when disabled."""
    if not _enabled:
        return None
    s = _current.get()
    if s is None:
        return None
    return s.trace_id, s.id


def wrap_ctx(fn: Callable) -> Callable:
    """Carry the caller's trace context into a thread-pool worker so
    device-side spans (transfer/fetch on the kernel pools) stay inside
    their batch's span tree. Identity when tracing is off."""
    if not _enabled:
        return fn
    ctx = contextvars.copy_context()

    def run(*a, **kw):
        return ctx.run(fn, *a, **kw)

    return run


def configure(enabled: bool | None = None, capacity: int | None = None,
              slow_ms: float | None = None,
              slow_captures: int | None = None, clock=None) -> None:
    """(Re)configure the process tracer. Changing capacity rebuilds the
    ring (existing spans are dropped); toggling enabled keeps it."""
    global _enabled, _T
    if capacity is not None and capacity < 1:
        raise ValueError("trace capacity must be >= 1")
    with _cfg_lock:
        rebuild = _T is None or capacity is not None or clock is not None \
            or slow_captures is not None
        if rebuild:
            _T = Tracer(
                capacity=capacity or (_T.capacity if _T else 65536),
                slow_ms=slow_ms if slow_ms is not None
                else (_T.slow_ms if _T else 250.0),
                slow_captures=slow_captures
                if slow_captures is not None
                else (_T._slow.maxlen if _T else 32),
                clock=clock or time.monotonic_ns)
        elif slow_ms is not None:
            _T.slow_ms = slow_ms
        if enabled is not None:
            _enabled = enabled
            _switch_hooks(enabled)


def reset() -> None:
    """Drop all spans, captures, and attribution; disable. (Tests.)"""
    global _enabled, _T
    with _cfg_lock:
        _enabled = False
        _T = None
        _switch_hooks(False)


def snapshot() -> list[dict]:
    return _T.snapshot() if _T is not None else []


def dropped() -> int:
    return _T.dropped() if _T is not None else 0


def slow_captures() -> list[dict]:
    return _T.slow_captures() if _T is not None else []


def capacity() -> int:
    """Configured ring size (the default when no tracer is built yet) —
    lets callers that temporarily re-configure() restore the prior ring."""
    return _T.capacity if _T is not None else 65536


def slow_budget_ms() -> float:
    """The configured global slow-capture budget (roots layering extra
    allowance on top — the consensus height timeline — start from this)."""
    return _T.slow_ms if _T is not None else 250.0


def attribution() -> dict:
    """Rolling stage-share percentages + measured bytes-per-sig — the
    crypto_health `attribution` section."""
    if _T is None:
        return {"enabled": False}
    out = _T.attribution()
    out["enabled"] = _enabled
    return out


def reset_attribution() -> None:
    if _T is not None:
        _T.reset_attribution()


# --------------------------------------------------------- the model


def _rows_said(name: str, attrs: dict) -> Optional[tuple[str, str, int]]:
    """(attribution key, path, rows) of a `commit.sign_bytes` span that
    built its rows, or of the `commit.rows` span that selected a commit's."""
    key, paths = _ROW_PATHS.get(name, (None, ()))
    path, rows = attrs.get("path"), attrs.get("rows", 0)
    if path not in paths or not isinstance(rows, int):
        return None
    return key, path, rows


def _attribution_dict(ns: dict, rows: int, tx: int, rx: int,
                      by_path: dict) -> dict:
    total = sum(ns.get(s, 0) for s in STAGES)
    shares = {
        s: (round(ns.get(s, 0) / total, 4) if total else 0.0)
        for s in STAGES
    }
    return {
        "stage_us": {s: round(ns.get(s, 0) / 1e3, 1) for s in STAGES},
        "stage_share": shares,
        "total_us": round(total / 1e3, 1),
        "rows": rows,
        "wire_tx_bytes": tx,
        "wire_rx_bytes": rx,
        "bytes_per_sig_tx": round(tx / rows, 2) if rows else None,
        "bytes_per_sig_rx": round(rx / rows, 2) if rows else None,
        **by_path,
    }


def attribution_of(spans: list[dict]) -> dict:
    """The wall-time attribution model applied to a span list (snapshot()
    records or a recorded fixture): per-stage SELF time — a stage span's
    duration minus its stage-categorized descendants — summed into stage
    shares, with wire bytes and signature rows totaled from the spans.
    The perf regression test replays a recorded trace through this and
    fails if the share math drifts."""
    by_id = {r["id"]: r for r in spans}
    covered: dict[int, int] = {}
    # children finish before parents, so a single pass over spans sorted
    # by END time ascending propagates coverage bottom-up
    order = sorted(spans, key=lambda r: r["t0_ns"] + r["dur_ns"])
    ns = {s: 0 for s in STAGES}
    rows = tx = rx = 0
    by_path = _no_rows_by_path()
    for r in order:
        counted = r["cat"] in STAGES
        cov = covered.get(r["id"], 0)
        if counted:
            ns[r["cat"]] += max(0, r["dur_ns"] - cov)
            n = r["attrs"].get("sig_rows", 0)
            rows += n if isinstance(n, int) else 0
            tx += r.get("bytes_tx", 0)
            rx += r.get("bytes_rx", 0)
            built = _rows_said(r["name"], r["attrs"])
            if built:
                by_path[built[0]][built[1]] += built[2]
        pid = r.get("parent_id")
        if pid is not None and pid in by_id:
            covered[pid] = covered.get(pid, 0) + (
                r["dur_ns"] if counted else cov)
    return _attribution_dict(ns, rows, tx, rx, by_path)


# ----------------------------------------------------------- exporters


def chrome_trace(spans: list[dict] | None = None) -> dict:
    """Chrome trace-event JSON (Perfetto-loadable): complete ("X") events
    in microseconds with span/trace ids and wire bytes in args, plus
    thread-name metadata. json.dump the return value (or the
    `trace-dump` CLI does it for you) and load it at ui.perfetto.dev."""
    if spans is None:
        spans = snapshot()
    tids: dict[int, int] = {}
    events: list[dict] = []
    for r in spans:
        tid = tids.setdefault(r["tid"], len(tids) + 1)
        args = dict(r["attrs"])
        args["span_id"] = r["id"]
        args["trace_id"] = r["trace_id"]
        if r.get("parent_id") is not None:
            args["parent_id"] = r["parent_id"]
        if r.get("bytes_tx"):
            args["bytes_tx"] = r["bytes_tx"]
        if r.get("bytes_rx"):
            args["bytes_rx"] = r["bytes_rx"]
        ph = "i" if args.pop("instant", False) else "X"
        ev = {
            "name": r["name"],
            "cat": r["cat"] or "span",
            "ph": ph,
            "ts": r["t0_ns"] / 1e3,
            "pid": 1,
            "tid": tid,
            "args": args,
        }
        if ph == "X":
            ev["dur"] = r["dur_ns"] / 1e3
        else:
            ev["s"] = "t"  # instant scope: thread
        events.append(ev)
    meta = [
        {"name": "thread_name", "ph": "M", "pid": 1, "tid": idx,
         "args": {"name": f"thread-{idx}"}}
        for idx in sorted(tids.values())
    ]
    return {"traceEvents": meta + events,
            "displayTimeUnit": "ms"}


def write_chrome_trace(path: str, spans: list[dict] | None = None) -> int:
    """Write a Perfetto-loadable trace file; returns the event count."""
    doc = chrome_trace(spans)
    with open(path, "w") as f:
        json.dump(doc, f)
    return len(doc["traceEvents"])
