"""Global verify scheduler — continuous batching of all signature work.

Public surface: the process-global VerifyScheduler singleton (get()),
the priority-class constants and the ambient-class context manager
(work_class), plus configure()/reset() for node boot and tests.
See cometbft_tpu/sched/scheduler.py for the design.
"""

from __future__ import annotations

import threading

from cometbft_tpu.sched.scheduler import (  # noqa: F401 - public re-exports
    CLASSES,
    CONSENSUS,
    LIGHT,
    MEMPOOL,
    SYNC,
    SchedulerSaturated,
    VerifyScheduler,
    current_class,
    work_class,
)

_lock = threading.Lock()
_sched: VerifyScheduler | None = None

# constructor kwargs applied at (re)creation — configure() records them so
# a get() after reset() rebuilds with the node's knobs, not the defaults
_kwargs: dict = {}


def get() -> VerifyScheduler:
    global _sched
    if _sched is None:
        with _lock:
            if _sched is None:
                _sched = VerifyScheduler(**_kwargs)
    return _sched


def configure(**kwargs) -> None:
    """Apply config.crypto scheduler knobs (node boot; tests poke
    directly). Unknown knobs raise. Live instance updated in place so a
    reconfig doesn't orphan queued work."""
    allowed = {"max_lanes", "sync_deadline", "light_deadline",
               "mempool_deadline", "queue_limit", "starvation_limit"}
    bad = set(kwargs) - allowed
    if bad:
        raise ValueError(f"unknown scheduler knob(s) {sorted(bad)}")
    with _lock:
        _kwargs.update(kwargs)
        if _sched is not None:
            if "max_lanes" in kwargs:
                _sched.max_lanes = kwargs["max_lanes"]
            if "sync_deadline" in kwargs:
                _sched.class_deadline[SYNC] = kwargs["sync_deadline"]
            if "light_deadline" in kwargs:
                _sched.class_deadline[LIGHT] = kwargs["light_deadline"]
            if "mempool_deadline" in kwargs:
                _sched.class_deadline[MEMPOOL] = kwargs["mempool_deadline"]
            if "queue_limit" in kwargs:
                _sched.queue_limit = kwargs["queue_limit"]
            if "starvation_limit" in kwargs:
                _sched.starvation_limit = kwargs["starvation_limit"]


def reset() -> None:
    """Stop the worker and forget all state (tests; fresh process
    semantics). Queued futures are failed, not leaked."""
    global _sched
    with _lock:
        sched, _sched = _sched, None
    if sched is not None:
        try:
            sched.flush()
        except Exception:  # noqa: BLE001 - draining is best-effort
            pass
        sched.stop()


def health_snapshot() -> dict:
    """The crypto_health `verify_sched` section. Never creates the
    singleton implicitly beyond what get() would."""
    snap = get().health()
    snap["enabled"] = True  # the only route; crypto_health readers keep the key
    return snap
