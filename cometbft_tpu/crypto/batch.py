"""Batch-verifier dispatch (reference: crypto/batch/batch.go:11-32).

The reference keys verifier creation on pubkey *type*; this framework adds the
backend dimension — "cpu" (OpenSSL loop), "tpu" (JAX/Pallas device kernel),
or "auto" (tpu when an accelerator is present, else cpu). The chosen backend
is process-global, set once from config (config.crypto.backend) at node boot.
Every verifier handed out here is a client of the node-wide verify scheduler
(sched/scheduler.py): VerifyScheduler._run_batch alone decides which backend
and which kernel serve a batch's rows.
"""

from __future__ import annotations

from typing import Callable, Optional

from cometbft_tpu import crypto
from cometbft_tpu.crypto import ed25519
from cometbft_tpu.libs import trace
from cometbft_tpu.libs.prefixrows import PrefixedMsg

_BACKEND = "auto"
_device: Optional[dict] = None

# batchable key type -> host batch verifier factory (the scheduler's CPU
# rung, VerifyScheduler._host_mask)
_REGISTRY: dict[str, Callable[[], crypto.BatchVerifier]] = {}


def register(key_type: str,
             factory: Callable[[], crypto.BatchVerifier]) -> None:
    _REGISTRY[key_type] = factory


def set_backend(backend: str) -> None:
    global _BACKEND
    if backend not in ("auto", "cpu", "tpu"):
        raise ValueError(f"unknown crypto backend {backend!r}")
    _BACKEND = backend


def get_backend() -> str:
    return _BACKEND


def device_info(probe: bool = True) -> dict | None:
    """platform / device_kind / count as JAX reports them, probed once per
    process. probe=False only reads what an earlier call cached (health
    snapshots must not be what first touches the device: a backend="cpu"
    node never claims a chip)."""
    global _device
    if _device is None and probe:
        import jax

        devs = jax.devices()
        _device = {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs)}
    return _device


def _device_present() -> bool:
    """Is the device the "tpu" backend is written for attached? Only a
    TPU counts, and an import or runtime error surfaces: "jax is broken"
    must not read as "no chip, use the CPU"."""
    return device_info()["platform"] == "tpu"


def resolve_backend() -> str:
    """The backend that a batch staged NOW should target. "auto" prefers
    the device when one is present; either way a "tpu" resolution defers
    to the device supervisor's circuit breaker (ops/dispatch.py) — while
    the breaker is open the whole node runs the CPU ladder, and the
    half-open re-probe window routes batches back to the device so a
    recovered chip is reclaimed."""
    backend = _BACKEND
    if backend == "auto":
        backend = "tpu" if _device_present() else "cpu"
    if backend == "tpu":
        from cometbft_tpu.ops import dispatch

        if not dispatch.device_allowed():
            backend = "cpu"
    _publish_active(backend)
    return backend


def _publish_active(backend: str) -> None:
    try:
        from cometbft_tpu.libs import metrics

        g = metrics.crypto_metrics().backend_active
        for b in ("cpu", "tpu"):
            g.labels(b).set(1.0 if b == backend else 0.0)
    except Exception:  # noqa: BLE001 - metrics must never break dispatch
        pass


def configure(crypto_cfg) -> None:
    """Apply config.crypto at node boot: backend selection, supervision
    knobs (retry/backoff/breaker/watchdog), verify-scheduler knobs, and
    any chaos schedule."""
    set_backend(crypto_cfg.backend)
    from cometbft_tpu.ops import dispatch

    dispatch.configure(
        failure_threshold=crypto_cfg.breaker_failure_threshold,
        cooldown=crypto_cfg.breaker_cooldown,
        retry_attempts=crypto_cfg.retry_max_attempts,
        retry_base=crypto_cfg.retry_backoff_base,
        retry_cap=crypto_cfg.retry_backoff_cap,
        watchdog_timeout=crypto_cfg.watchdog_timeout,
    )
    from cometbft_tpu import sched

    sched.configure(
        max_lanes=crypto_cfg.sched_max_lanes,
        sync_deadline=crypto_cfg.sched_sync_deadline,
        light_deadline=crypto_cfg.sched_light_deadline,
        mempool_deadline=crypto_cfg.sched_mempool_deadline,
        queue_limit=crypto_cfg.sched_queue_limit,
        starvation_limit=crypto_cfg.sched_starvation_limit,
    )
    from cometbft_tpu.parallel import mesh as verify_mesh

    verify_mesh.configure(
        enabled=crypto_cfg.mesh_enabled,
        min_devices=crypto_cfg.mesh_min_devices,
        placement=crypto_cfg.mesh_placement,
    )
    from cometbft_tpu.ops import residency

    residency.configure(
        enabled=crypto_cfg.wire_indexed_sends,
        rows=crypto_cfg.wire_table_rows,
    )
    from cometbft_tpu.ops import challenge

    challenge.configure(
        enabled=crypto_cfg.wire_device_challenge,
    )
    from cometbft_tpu.crypto import bls12381

    bls12381.set_enabled(crypto_cfg.bls_enabled)
    if crypto_cfg.chaos:
        from cometbft_tpu.libs import chaos

        chaos.arm_spec(crypto_cfg.chaos)


def _check_bls_enabled(key_type: str) -> None:
    """A BLS key arriving with crypto.bls_enabled off is a CONFIGURATION
    error and must fail loudly (the light-proxy https-refusal rule) —
    a silent CPU fallback would hide that aggregate commit verification
    is off while the validator set expects it."""
    if key_type != "bls12381":
        return
    from cometbft_tpu.crypto import bls12381

    if not bls12381.enabled():
        raise crypto.ErrInvalidKey(
            "bls12381 key reached the batch-verify seam but the scheme is "
            "disabled (crypto.bls_enabled = false); enable it in config "
            "or remove BLS keys from the validator set")


def supports_batch_verifier(pub_key: crypto.PubKey | None) -> bool:
    """reference: crypto/batch/batch.go:26-32 — secp256k1 has no batch
    path. Raises ErrInvalidKey (not False) for a BLS key while
    crypto.bls_enabled is off: misconfiguration must be loud."""
    if pub_key is None:
        return False
    _check_bls_enabled(pub_key.type_())
    return pub_key.type_() in _REGISTRY


def create_batch_verifier(pub_key: crypto.PubKey) -> crypto.BatchVerifier:
    """Create a verifier for this key type. Raises ErrInvalidKey for
    unbatchable key types (caller falls back to serial verification, as
    the reference does).

    The returned verifier is a CLIENT of the node-wide scheduler: verify()
    drains as one inline batch that coalesces whatever compatible queued
    work fits the bucket (sched/scheduler.py). The producer does not own
    device dispatch — that inversion is what keeps the device running few
    full batches instead of many fragmented ones."""
    if pub_key.type_() not in _REGISTRY:
        raise crypto.ErrInvalidKey(
            f"key type {pub_key.type_()!r} has no batch verifier")
    return ScheduledBatchVerifier()


class ScheduledBatchVerifier(crypto.BatchVerifier):
    """The scheduler-client face of crypto.BatchVerifier: add() stages
    rows host-side (cheap structural checks, same contract as the CPU
    verifiers); verify() submits the rows to the global VerifyScheduler
    as ONE group under the caller's ambient priority class
    (sched.work_class) and drains inline, coalescing queued filler.
    Mixed key types are accepted — the scheduler groups rows per scheme
    into per-scheme device sub-batches resolved with one fetch."""

    # per-scheme signature sizes (BLS G2 signatures are 96 bytes)
    SIGNATURE_SIZES = {"ed25519": 64, "sr25519": 64, "bls12381": 96}

    def __init__(self, klass: str | None = None):
        from cometbft_tpu import sched

        self._klass = klass or sched.current_class()
        # rows added one at a time, or one libs/rowblock.RowBlock added
        # whole (add_block): never both
        self._rows: list[tuple[crypto.PubKey, bytes, bytes]] = []
        self._block = None

    @staticmethod
    def _check_key_type(kt: str) -> None:
        _check_bls_enabled(kt)
        if kt not in _REGISTRY:
            raise crypto.ErrInvalidKey(
                f"key type {kt!r} has no batch verifier")

    def add(self, pub_key: crypto.PubKey, msg: bytes, sig: bytes) -> None:
        if self._block is not None:
            raise RuntimeError("add() after add_block()")
        kt = pub_key.type_()
        self._check_key_type(kt)
        if len(sig) != self.SIGNATURE_SIZES.get(kt, 64):
            raise crypto.ErrInvalidSignature("bad signature length")
        # shared-prefix rows (libs/prefixrows.py) ride to the scheduler
        # factored — kernel staging broadcasts each run's prefix once
        self._rows.append((
            pub_key,
            msg if isinstance(msg, PrefixedMsg) else bytes(msg),
            bytes(sig)))

    def add_block(self, block) -> None:
        """add() for a whole RowBlock (a commit's rows): the same
        structural rules once a key type, not once a row; raises what
        add() would have raised at the first row that breaks one. The
        block goes to the scheduler as it is."""
        if self._rows or self._block is not None:
            raise RuntimeError("add_block() on a verifier that has rows")
        for kt, (_lanes, cols) in block.parts.items():
            self._check_key_type(kt)
            if cols.sig_widths() - {self.SIGNATURE_SIZES.get(kt, 64)}:
                raise crypto.ErrInvalidSignature("bad signature length")
        self._block = block

    def verify(self) -> tuple[bool, list[bool]]:
        """(all valid, the per-row verdicts): a list for rows that were
        add()ed, the scheduler's (N,) bool array for a block."""
        from cometbft_tpu import sched

        if self._block is not None:
            if not len(self._block):
                return True, []
            mask = sched.get().verify_now(self._block, self._klass)
            with trace.span("commit.verdict", cat="collect"):
                return bool(mask.all()), mask
        if not self._rows:
            return True, []
        mask = sched.get().verify_now(self._rows, self._klass)
        with trace.span("commit.verdict", cat="collect"):
            out = [bool(x) for x in mask]
            return all(out), out

    def count(self) -> int:
        return len(self._rows) if self._block is None else len(self._block)


def create_mixed_batch_verifier() -> crypto.BatchVerifier:
    """A verifier for rows of any mix of batchable key types (BASELINE
    config 5: ed25519 + sr25519 mega-commits)."""
    return ScheduledBatchVerifier()


def _cpu_sr25519_factory() -> crypto.BatchVerifier:
    from cometbft_tpu.crypto import sr25519

    return sr25519.CPUBatchVerifier()


def _cpu_bls_factory() -> crypto.BatchVerifier:
    from cometbft_tpu.crypto import bls12381

    return bls12381.CPUBatchVerifier()


register(ed25519.KEY_TYPE, ed25519.CPUBatchVerifier)
register("sr25519", _cpu_sr25519_factory)
register("bls12381", _cpu_bls_factory)
