"""Shared kernel-dispatch lock + the device-fault supervision layer.

The Pallas Ed25519 kernel trace temporarily swaps the field/curve module
constants for VMEM refs (pallas_verify._verify_block_kernel). ANY other
trace that reads those module globals — the sr25519 XLA ladder, the
ed25519 XLA fallback — must never interleave with that swap, or it bakes
another kernel's refs/tracers into its compiled program. Every jit
dispatch of a curve kernel therefore serializes on this one lock
(compiled-cache dispatch under the lock is sub-ms; the expensive
host<->device transfers stay outside it).

Supervision (the device-fault resilience layer): the node's hot path lives
on an accelerator that can time out, OOM, lose its Mosaic compile, or
vanish. Instead of the old one-way `broken`
latch, every device operation runs under a DeviceSupervisor:

  classify   transient (XlaRuntimeError RESOURCE_EXHAUSTED/UNAVAILABLE,
             timeouts) vs permanent (Mosaic/lowering death)
  retry      transients retry with capped exponential backoff + jitter
  break      N consecutive failed operations (or one permanent) open a
             circuit breaker — new batches skip the device entirely
  re-probe   after `cooldown` the breaker half-opens and ONE batch probes
             the device; success closes the breaker and reclaims the
             device, failure re-opens it

The supervisor only decides *whether* the device is used; the verify
ladder TPU (Pallas) -> XLA -> CPU (exact host oracle) does the falling
back, in ops/ed25519_kernel.py / ops/sr25519_kernel.py and
crypto/batch.resolve_backend. Fault injection for all of this lives in
libs/chaos.py.
"""

from __future__ import annotations

import random
import threading
import time

from cometbft_tpu.libs import trace as _trace

KERNEL_DISPATCH_LOCK = threading.Lock()

# failure classes
TRANSIENT = "transient"
PERMANENT = "permanent"
TIMEOUT = "timeout"

# breaker states (gauge encoding: the wire values are part of the
# metrics/RPC contract, keep in sync with README)
CLOSED = "closed"
HALF_OPEN = "half_open"
OPEN = "open"
_STATE_GAUGE = {CLOSED: 0, HALF_OPEN: 1, OPEN: 2}


class DeviceUnavailable(Exception):
    """Breaker open: the device is sidelined until the next re-probe."""


class DeviceOpFailed(Exception):
    """A supervised device operation failed (after retries). The original
    exception rides __cause__; the supervisor has already recorded it —
    catchers fall back without double-counting."""


# transient markers in XlaRuntimeError/RuntimeError text (gRPC-style codes
# the PJRT client surfaces for contended/hung/OOM devices)
_TRANSIENT_MARKERS = (
    "RESOURCE_EXHAUSTED", "DEADLINE_EXCEEDED", "UNAVAILABLE", "ABORTED",
    "CANCELLED", "connection reset", "timed out", "temporarily",
)
# permanent markers: a failing Mosaic trace/lowering costs seconds and
# will fail the same way every time for this program shape
_PERMANENT_MARKERS = (
    "Mosaic", "mosaic", "lowering", "Unsupported", "NOT_FOUND",
    "UNIMPLEMENTED", "INVALID_ARGUMENT",
)


def classify_failure(exc: BaseException) -> str:
    """Map a device-op exception to a failure class. Unknown errors count
    as transient: a flapping device produces novel error text, and the
    breaker bounds how long we keep trying."""
    from cometbft_tpu.libs import chaos

    if isinstance(exc, chaos.ChaosPermanentError):
        return PERMANENT
    if isinstance(exc, chaos.ChaosTransientError):
        return TRANSIENT
    if isinstance(exc, (chaos.ChaosTimeout, TimeoutError)):
        return TIMEOUT
    text = f"{type(exc).__name__}: {exc}"
    if any(m in text for m in _PERMANENT_MARKERS):
        return PERMANENT
    if any(m in text for m in _TRANSIENT_MARKERS):
        return TRANSIENT
    return TRANSIENT


def _metrics():
    """Lazy process-global CryptoMetrics; never raises (metrics must not
    break verification)."""
    try:
        from cometbft_tpu.libs import metrics as m

        return m.crypto_metrics()
    except Exception:  # noqa: BLE001
        return None


class CircuitBreaker:
    """closed -> (N consecutive failures | 1 permanent) -> open ->
    (cooldown elapses) -> half_open -> one probe -> closed | open."""

    def __init__(self, name: str, failure_threshold: int = 3,
                 cooldown: float = 30.0, clock=time.monotonic):
        self.name = name
        self.failure_threshold = failure_threshold
        self.cooldown = cooldown
        self._clock = clock
        self._lock = threading.Lock()
        self._state = CLOSED
        self._consecutive = 0
        self._opened_at = 0.0
        self._probe_inflight = False
        self._publish(CLOSED, transition=False)

    def _publish(self, state: str, transition: bool = True) -> None:
        if transition:
            # breaker flips land in the flight recorder as instant events:
            # a trace showing a fetch stall next to `breaker.open` answers
            # "did the device die or did the wire?" without log archaeology
            _trace.event(f"breaker.{state}", cat="device", breaker=self.name)
        m = _metrics()
        if m is None:
            return
        try:
            m.breaker_state.labels(self.name).set(_STATE_GAUGE[state])
            if transition:
                m.breaker_transitions.labels(self.name, state).inc()
        except Exception:  # noqa: BLE001
            pass

    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    @property
    def consecutive_failures(self) -> int:
        with self._lock:
            return self._consecutive

    def allow(self) -> bool:
        """Claim permission for a device operation. An OPEN breaker whose
        cooldown has elapsed half-opens and admits the caller as THE probe;
        while that probe is in flight every other caller is refused — one
        batch tests a possibly-dead device, not a whole blocksync window.
        Read-only callers (health snapshots, backend resolution at staging
        time) must use peek() instead: allow() is a state transition."""
        with self._lock:
            if self._state == OPEN:
                if self._clock() - self._opened_at < self.cooldown:
                    return False
                self._state = HALF_OPEN
                self._probe_inflight = True
                self._publish(HALF_OPEN)
                return True
            if self._state == HALF_OPEN:
                if self._probe_inflight:
                    return False
                self._probe_inflight = True
            return True

    def peek(self) -> bool:
        """Would a device operation be admitted now? No transitions, no
        probe claim — safe for health snapshots and staging decisions."""
        with self._lock:
            if self._state == OPEN:
                return self._clock() - self._opened_at >= self.cooldown
            if self._state == HALF_OPEN:
                return not self._probe_inflight
            return True

    def record_success(self) -> None:
        with self._lock:
            self._consecutive = 0
            self._probe_inflight = False
            if self._state != CLOSED:
                self._state = CLOSED
                self._publish(CLOSED)

    def record_failure(self, fclass: str) -> None:
        """A failed operation (retries exhausted). Permanent failures and a
        failed half-open probe open immediately; transients open at the
        threshold."""
        with self._lock:
            self._consecutive += 1
            self._probe_inflight = False
            opens = (
                fclass == PERMANENT
                or self._state == HALF_OPEN
                or self._consecutive >= self.failure_threshold
            )
            if opens and self._state != OPEN:
                self._state = OPEN
                self._opened_at = self._clock()
                self._publish(OPEN)
            elif self._state == OPEN:
                self._opened_at = self._clock()  # failed probe: restart timer

    def health(self) -> dict:
        with self._lock:
            out = {
                "state": self._state,
                "consecutive_failures": self._consecutive,
                "failure_threshold": self.failure_threshold,
                "cooldown_seconds": self.cooldown,
            }
            if self._state == OPEN:
                out["reprobe_in_seconds"] = round(
                    max(0.0, self.cooldown - (self._clock() - self._opened_at)), 3)
            return out


class DeviceSupervisor:
    """Retry/backoff + breaker + bookkeeping around one class of device
    operation. `sleep`/`clock` are injectable so chaos tests run on a fake
    timeline."""

    def __init__(self, name: str, failure_threshold: int = 3,
                 cooldown: float = 30.0, retry_attempts: int = 2,
                 retry_base: float = 0.05, retry_cap: float = 1.0,
                 sleep=time.sleep, clock=time.monotonic):
        self.name = name
        self.breaker = CircuitBreaker(
            name, failure_threshold=failure_threshold, cooldown=cooldown,
            clock=clock)
        self.retry_attempts = retry_attempts
        self.retry_base = retry_base
        self.retry_cap = retry_cap
        self._sleep = sleep
        self._lock = threading.Lock()
        self.retries = 0
        self.failures = 0
        self.successes = 0
        self.last_error: str | None = None

    # ------------------------------------------------------------ stats

    def _count_retry(self) -> None:
        with self._lock:
            self.retries += 1
        _trace.event("device.retry", cat="device", supervisor=self.name)
        m = _metrics()
        if m is not None:
            try:
                m.device_retries.labels(self.name).inc()
            except Exception:  # noqa: BLE001
                pass

    def _count_failure(self, fclass: str, exc: BaseException) -> None:
        with self._lock:
            self.failures += 1
            self.last_error = f"{fclass}: {type(exc).__name__}: {exc}"
        m = _metrics()
        if m is not None:
            try:
                m.device_failures.labels(self.name, fclass).inc()
            except Exception:  # noqa: BLE001
                pass

    # -------------------------------------------------------------- run

    def run(self, fn, *args, **kwargs):
        """Run fn under supervision. Raises DeviceUnavailable (breaker open,
        nothing attempted) or DeviceOpFailed (attempted and failed; already
        recorded). Success resets the breaker."""
        if not self.breaker.allow():
            raise DeviceUnavailable(self.name)
        attempt = 0
        while True:
            try:
                out = fn(*args, **kwargs)
            except Exception as exc:  # noqa: BLE001 - classified below
                fclass = classify_failure(exc)
                if fclass == TRANSIENT and attempt < self.retry_attempts:
                    self._count_retry()
                    delay = min(self.retry_cap, self.retry_base * (2 ** attempt))
                    self._sleep(delay * (0.5 + random.random() / 2))
                    attempt += 1
                    continue
                self._count_failure(fclass, exc)
                self.breaker.record_failure(fclass)
                try:
                    from cometbft_tpu.libs import log as _log

                    _log.default().error(
                        "supervised device operation failed",
                        supervisor=self.name, failure_class=fclass,
                        attempts=str(attempt + 1),
                        breaker=self.breaker.state, err=str(exc))
                except Exception:  # noqa: BLE001
                    pass
                raise DeviceOpFailed(
                    f"{self.name}: {fclass} device failure "
                    f"after {attempt + 1} attempt(s)") from exc
            with self._lock:
                self.successes += 1
            self.breaker.record_success()
            return out

    def record_op_failure(self, exc: BaseException) -> str:
        """Record a failure observed outside run() (e.g. a watchdog timeout
        on the fetch side). Returns the failure class."""
        fclass = classify_failure(exc)
        self._count_failure(fclass, exc)
        self.breaker.record_failure(fclass)
        return fclass

    def health(self) -> dict:
        with self._lock:
            out = {
                "retries": self.retries,
                "failures": self.failures,
                "successes": self.successes,
                "last_error": self.last_error,
            }
        out["breaker"] = self.breaker.health()
        return out


# ---------------------------------------------------------------------------
# process-global supervisor registry + knobs (configured from
# config.crypto at node boot; tests poke configure() directly)
# ---------------------------------------------------------------------------

_config = {
    "failure_threshold": 3,
    "cooldown": 30.0,
    "retry_attempts": 2,
    "retry_base": 0.05,
    "retry_cap": 1.0,
    # must comfortably cover a COLD first-dispatch compile (Mosaic traces
    # run tens of seconds; the per-call watchdog cannot tell compile from
    # hang) while still bounding a wedged fetch to well under a blocksync
    # window retry
    "watchdog_timeout": 120.0,
    # Pallas gets a longer leash: a failed Mosaic trace costs seconds, so
    # re-probe it an order of magnitude less often than the XLA/device path
    "pallas_cooldown": 300.0,
}

_registry_lock = threading.Lock()
_supervisors: dict[str, DeviceSupervisor] = {}


def configure(**kwargs) -> None:
    """Set supervision knobs (unknown keys rejected). Existing supervisors
    pick up the new values in place so a node reconfig (or a test) does not
    orphan live breakers."""
    with _registry_lock:
        for k, v in kwargs.items():
            if k not in _config:
                raise ValueError(f"unknown supervision knob {k!r}")
            _config[k] = v
        for name, sup in _supervisors.items():
            pallas = name.startswith("pallas")
            sup.breaker.failure_threshold = _config["failure_threshold"]
            sup.breaker.cooldown = (
                _config["pallas_cooldown"] if pallas else _config["cooldown"])
            # pallas rungs never retry in place: a transient re-runs as XLA
            # now and Pallas is re-probed on the next aligned batch
            sup.retry_attempts = 0 if pallas else _config["retry_attempts"]
            sup.retry_base = _config["retry_base"]
            sup.retry_cap = _config["retry_cap"]


def watchdog_timeout() -> float:
    return _config["watchdog_timeout"]


def supervisor(name: str) -> DeviceSupervisor:
    with _registry_lock:
        sup = _supervisors.get(name)
        if sup is None:
            pallas = name.startswith("pallas")
            sup = DeviceSupervisor(
                name,
                failure_threshold=_config["failure_threshold"],
                cooldown=(_config["pallas_cooldown"] if pallas
                          else _config["cooldown"]),
                retry_attempts=0 if pallas else _config["retry_attempts"],
                retry_base=_config["retry_base"],
                retry_cap=_config["retry_cap"],
            )
            _supervisors[name] = sup
        return sup


def device_allowed() -> bool:
    """May a NEW batch target the device? Side-effect-free peek: False
    while the device breaker is open or another probe is mid-flight
    (crypto/batch.resolve_backend degrades to the CPU ladder on this).
    The authoritative probe CLAIM happens inside DeviceSupervisor.run via
    breaker.allow() — health snapshots and staging decisions polling this
    never change failover state."""
    return supervisor("device").breaker.peek()


def reset_supervision() -> None:
    """Forget breakers/counters (tests; a fresh process state)."""
    with _registry_lock:
        _supervisors.clear()
    with _doublebuf_lock:
        _doublebufs.clear()


# ---------------------------------------------------------------------------
# double-buffered dispatch gate
# ---------------------------------------------------------------------------


def _release_once(fn):
    lock = threading.Lock()
    state = {"done": False}

    def release() -> None:
        with lock:
            if state["done"]:
                return
            state["done"] = True
        fn()

    return release


class DoubleBuffer:
    """Two-slot in-flight gate per fault domain — the dispatch-side half of
    the StagingPool double-buffer contract (ops/limbs.py). A batch acquires
    a slot BEFORE its h2d transfer and releases it as soon as its verify
    dispatch is enqueued (the slot is scoped inside the dispatch closure,
    never held to batch resolution — an abandoned thunk must not wedge the
    gate), so with two slots batch N's host->device transfer overlaps
    batch N-1's compute while batch N+2 queues behind the gate: bounded
    in-flight staging, overlap by construction, no unbounded donated-buffer
    growth.

    Fault seam: chaos site `dispatch.doublebuf` fires at acquire. An
    injected fault (a poisoned donated buffer) records against the domain's
    `doublebuf.<domain>` supervisor and degrades the gate to SERIALIZED
    single-buffer dispatch (one batch in flight end-to-end) while the
    breaker is not admitting — overlap lost, verdicts untouched — and the
    normal half-open schedule restores double-buffering. acquire() never
    raises: a buffer-gate fault must degrade, not fail the batch."""

    def __init__(self, domain: str, slots: int = 2) -> None:
        self.domain = domain
        self.slots = slots
        self._sem = threading.BoundedSemaphore(slots)
        self._serial = threading.Lock()
        self._lock = threading.Lock()
        self.acquires = 0
        self.waits = 0
        self.degraded = 0

    def acquire(self):
        """Block until a slot is free; returns a one-shot release callable
        (safe to call from any thread, extra calls are no-ops)."""
        from cometbft_tpu.libs import chaos

        sup = supervisor(f"doublebuf.{self.domain}")
        degraded = False
        try:
            chaos.fire("dispatch.doublebuf")
            if sup.breaker.allow():
                sup.breaker.record_success()
            else:
                degraded = True
        except Exception as exc:  # noqa: BLE001 - injected/poisoned buffer
            sup.record_op_failure(exc)
            degraded = True
        with self._lock:
            self.acquires += 1
            if degraded:
                self.degraded += 1
        if degraded:
            self._serial.acquire()
            return _release_once(self._serial.release)
        if not self._sem.acquire(blocking=False):
            with self._lock:
                self.waits += 1
            self._sem.acquire()
        return _release_once(self._sem.release)

    def stats(self) -> dict:
        with self._lock:
            return {"slots": self.slots, "acquires": self.acquires,
                    "waits": self.waits, "degraded": self.degraded}


_doublebuf_lock = threading.Lock()
_doublebufs: dict[str, DoubleBuffer] = {}


def doublebuffer(domain: str = "dev0") -> DoubleBuffer:
    """The per-fault-domain dispatch gate (single-chip kernels use dev0;
    the mesh keys one per chip)."""
    with _doublebuf_lock:
        db = _doublebufs.get(domain)
        if db is None:
            db = DoubleBuffer(domain)
            _doublebufs[domain] = db
        return db


def doublebuffer_stats() -> dict:
    with _doublebuf_lock:
        return {d: db.stats() for d, db in _doublebufs.items()}


def _mesh_health() -> dict:
    """The mesh section of crypto_health; never raises (health must
    render even when jax/device discovery is mid-import or broken)."""
    try:
        from cometbft_tpu.parallel import mesh as _mesh

        return _mesh.health_snapshot()
    except Exception:  # noqa: BLE001
        return {"enabled": False, "built": False}


def health_snapshot() -> dict:
    """The RPC-visible crypto-health snapshot (rpc crypto_health route)."""
    from cometbft_tpu import sched
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.libs import chaos

    from cometbft_tpu.libs import linkmodel as _linkmodel

    with _registry_lock:
        sups = dict(_supervisors)
    snap = {
        "configured_backend": crypto_batch.get_backend(),
        "active_backend": crypto_batch.resolve_backend(),
        # platform / device_kind / count as JAX reported them at boot
        # (None until a device backend has probed: a health poll is never
        # what first touches the chip)
        "device": crypto_batch.device_info(probe=False),
        "watchdog_timeout_seconds": _config["watchdog_timeout"],
        "supervisors": {name: sup.health() for name, sup in sups.items()},
        "chaos": chaos.snapshot(),
        # the verify plane's batching layer: producers feed the global
        # scheduler, the scheduler feeds these supervisors
        "verify_sched": sched.health_snapshot(),
        # the multi-chip plane (parallel/mesh.py): live mesh size,
        # per-chip fault-domain breaker states, eviction/readmission/
        # redispatch churn, all-chips-dead fallback count
        "mesh": _mesh_health(),
        # rolling per-batch wall-time attribution (libs/trace.py): stage-
        # share percentages + measured bytes-per-sig — the number the
        # mesh / reduced-send PRs are judged against
        "attribution": _trace.attribution(),
        # live host<->device link model (libs/linkmodel.py): EWMA
        # bandwidth/RTT fed by the transfers that are awaited anyway:
        # post-header payload pulls, key-table and coordinate uploads,
        # sr25519 / BLS / mesh block uploads. An ed25519 batch's own
        # upload is un-awaited and feeds nothing (staging.trip counts the
        # waits that remain)
        "link": _linkmodel.link().snapshot(),
    }
    try:
        # staging plane: hash rung usage, reduced-fetch happy/full split,
        # pubkey cache hit rates, staging-buffer pool reuse
        from cometbft_tpu.ops import ed25519_kernel as _ek
        from cometbft_tpu.ops import hashvec as _hv
        from cometbft_tpu.ops import limbs as _limbs
        from cometbft_tpu.ops import residency as _residency

        snap["staging"] = {
            "hashvec_native": _hv.native_available(),
            "hashvec_rows": _hv.stats(),
            "fetch": _ek.fetch_stats(),
            # send-side twin of `fetch` (reduced-send protocol): per-path
            # wire accounting + steady-state bytes/sig + per-replica
            # validator-table counters
            "wire": _residency.stats(),
            # the trip of a batch to the device and back: batches,
            # compiled programs called, places the host blocked on the
            # device (a happy resident ed25519 batch: 2 programs, 1 wait)
            "trip": _residency.trip_stats(),
            "pubkey_cache": _ek.cache_stats(),
            "staging_pool": _limbs.POOL.stats(),
            # the dispatch-side half of the double-buffer contract:
            # per-fault-domain slot acquires/waits/degraded counts
            "doublebuf": doublebuffer_stats(),
        }
        # device-challenge plane (ops/challenge.py): plans, per-lane
        # device/host split, degradation reasons, prefix-table churn
        from cometbft_tpu.ops import challenge as _challenge

        snap["staging"]["challenge"] = {
            "enabled": _challenge.enabled(),
            "counters": _challenge.stats(),
            "tables": _challenge.table_stats(),
        }
    except Exception:  # noqa: BLE001 - health must render even mid-import
        pass
    return snap


class PallasGate:
    """Dispatch policy for a Pallas kernel with an XLA fallback: lane-aligned
    batches go to Pallas while its breaker is closed; a Mosaic failure opens
    the breaker (a failing trace costs seconds — never pay it per batch) and
    the half-open schedule re-probes, so a recovered device is reclaimed
    instead of abandoned for the process lifetime. Callers hold
    KERNEL_DISPATCH_LOCK around run()."""

    def __init__(self, name: str = "pallas") -> None:
        self.name = name

    @property
    def supervisor(self) -> DeviceSupervisor:
        return supervisor(self.name)

    @property
    def broken(self) -> bool:
        """Back-compat view of the old one-way latch (bench.py reads it):
        True while the breaker is sidelining Pallas — open, or half-open
        with the probe already claimed."""
        return not self.supervisor.breaker.peek()

    def run(self, pallas_fn, xla_fn, args, lane_count: int):
        from cometbft_tpu.libs import chaos
        from cometbft_tpu.ops import pallas_verify as PV
        from cometbft_tpu.ops.ed25519_kernel import _pallas_available

        if _pallas_available() and lane_count % PV.LANES == 0:
            def _probe():
                chaos.fire("pallas.trace")
                return pallas_fn(*args)

            try:
                # pallas supervisors are created with retry_attempts=0 (see
                # supervisor()): a transient re-runs as XLA below and
                # Pallas is re-probed on the next aligned batch
                return self.supervisor.run(_probe)
            except (DeviceUnavailable, DeviceOpFailed):
                pass
        return xla_fn(*args)
