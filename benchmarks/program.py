"""The benchmark's one seam to the program: the system under test, its
entries, its counters.

Everything the benchmark takes from cometbft_tpu is named here: the device
plane's boot (node.configure_device_plane, as Node.__init__ calls it), the
types a commit and a validator set are handed over in, the entries the
windows drive (types.validation), and the counters the per-layer metrics
read (ops.dispatch.health_snapshot, libs.metrics, jax.monitoring compile
events, libs.trace's attribution). Device probe, boot and the counter
reading follow chip_smoke.py (PR 22), of which this is the benchmark's own
copy: later PRs may change the smoke, not the yardstick.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os

from benchmarks.reference.commit_ref import CommitSpec, ValsetSpec

WARMUP_WATCHDOG_S = 1800.0
# env switches that exist to take the device off the verify path
REFUSED_ENV = ("CBFT_NO_PALLAS", "CBFT_CHAOS")


class BenchFailure(Exception):
    """The run cannot give a result (no chip, set-up at fault)."""


# ------------------------------------------------------------------ device


def probe_device(want_chips: int) -> dict:
    """A TPU with the cell's number of chips, or no run. The dict is what
    the result's `device` key starts from."""
    armed = [k for k in REFUSED_ENV if os.environ.get(k)]
    if armed:
        raise BenchFailure(f"refusing to run with {armed} set")
    from cometbft_tpu.crypto import batch as crypto_batch

    device = dict(crypto_batch.device_info())
    if device["platform"] != "tpu":
        raise BenchFailure(
            f"JAX reports platform {device['platform']!r}, not a TPU")
    if device["count"] < want_chips:
        raise BenchFailure(
            f"the cell needs {want_chips} chip(s), JAX reports "
            f"{device['count']}")
    return device


def boot_device_plane() -> str:
    """Backend "tpu" through the callable Node.__init__ uses; returns the
    compile cache directory in force (JAX_COMPILATION_CACHE_DIR if set,
    else <checkout>/.jax_cache: ops/compile_cache.py)."""
    from cometbft_tpu.config import Config
    from cometbft_tpu.libs import log as cmtlog
    from cometbft_tpu.node import node as node_mod

    config = Config(home="")
    config.crypto.backend = "tpu"
    config.validate_basic()
    record = node_mod.configure_device_plane(
        config.crypto, cmtlog.Logger(level=cmtlog.parse_level("error")))
    if record["compile_cache"] is None:
        raise BenchFailure("compile cache was not armed")
    # the program keeps only compiles over 2 s; a run here builds a dozen
    # smaller programs, and every run of every check is a new process
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return record["compile_cache"]


@contextlib.contextmanager
def warmup_watchdog():
    """The dispatch watchdog raised for the warm-up only: a cold compile
    that outlasts the configured one would be recorded as a device failure
    and served by the host oracle (PR 22)."""
    from cometbft_tpu.ops import dispatch

    configured = dispatch.watchdog_timeout()
    dispatch.configure(watchdog_timeout=WARMUP_WATCHDOG_S)
    try:
        yield
    finally:
        dispatch.configure(watchdog_timeout=configured)


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


# ----------------------------------------------------------------- objects


def build_validator_set(spec: ValsetSpec):
    """The program's ValidatorSet for a ValsetSpec; the program's own
    ordering has to be the spec's (CometBFT's), lane for lane."""
    from cometbft_tpu.crypto import ed25519, sr25519
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    make = {"ed25519": ed25519.PubKey, "sr25519": sr25519.PubKey}
    vals = ValidatorSet([Validator.new(make[s](p), w) for s, p, w in
                         zip(spec.schemes, spec.pubs, spec.powers)])
    if [v.pub_key.bytes_() for v in vals.validators] != list(spec.pubs):
        raise BenchFailure("the program orders the validator set otherwise "
                           "than the configuration's reference does")
    return vals


def build_commit(vals, spec: CommitSpec):
    """(block_id, Commit) as a peer would hand them over."""
    from cometbft_tpu.types.basic import BlockID, BlockIDFlag, PartSetHeader
    from cometbft_tpu.types.commit import Commit, CommitSig
    from cometbft_tpu.utils import cmttime

    block_id = BlockID(hash=spec.block_hash, part_set_header=PartSetHeader(
        total=spec.parts_total, hash=spec.parts_hash))
    sigs = [CommitSig(block_id_flag=BlockIDFlag.COMMIT,
                      validator_address=v.address,
                      timestamp=cmttime.Timestamp(*stamp), signature=sig)
            for v, stamp, sig in zip(vals.validators, spec.stamps, spec.sigs)]
    return block_id, Commit(height=spec.height, round_=spec.round,
                            block_id=block_id, signatures=sigs)


def fresh(commit, corrupt_lane: int | None = None):
    """A new Commit object over the same signatures (the sign-bytes cache
    rides the object, and a node sees each commit object once), with one
    signature's first byte flipped in its lowest bit if asked: the same
    flip as CommitSpec.with_flipped."""
    from cometbft_tpu.types.commit import Commit

    sigs = list(commit.signatures)
    if corrupt_lane is not None:
        cs = sigs[corrupt_lane]
        sigs[corrupt_lane] = dataclasses.replace(
            cs, signature=bytes([cs.signature[0] ^ 1]) + cs.signature[1:])
    return Commit(height=commit.height, round_=commit.round_,
                  block_id=commit.block_id, signatures=sigs)


# ----------------------------------------------------------------- entries


def entries() -> dict:
    """The program's entries the windows drive, by the names the drivers
    use. The control and the fault tests hand the drivers other ones."""
    from cometbft_tpu.types import validation

    return {"verify_commit": validation.verify_commit,
            "stage_verify_commit": validation.stage_verify_commit,
            "prefetch_staged": validation.prefetch_staged}


def control_entries() -> dict:
    """The control: the program's own quorum-only paths (VerifyCommitLight:
    stops once more than 2/3 of the power has signed), which break the
    configurations' guarantee that every signature is checked."""
    from cometbft_tpu.types import validation

    return {"verify_commit": validation.verify_commit_light,
            "stage_verify_commit": validation.stage_verify_commit_light,
            "prefetch_staged": validation.prefetch_staged}


def verdict_of(call) -> str:
    """Run one verification to its end and say what it answered, in the
    reference's words. Anything but an answer is "error:<type>"."""
    from cometbft_tpu.types import validation

    try:
        call()
    except validation.ErrInvalidCommitSignature as exc:
        text = str(exc)
        return "reject#" + text[text.index("(#") + 2:text.index(")")]
    except validation.ErrNotEnoughVotingPowerSigned:
        return "reject:power"
    except Exception as exc:  # noqa: BLE001 - the answer is the failure
        return f"error:{type(exc).__name__}"
    return "accept"


# ---------------------------------------------------------------- counters


def _flatten(node, prefix: str, out: dict) -> None:
    for key, value in node.items():
        path = f"{prefix}{key}"
        if isinstance(value, dict):
            _flatten(value, path + ".", out)
        elif isinstance(value, (int, float)) and not isinstance(value, bool):
            out[path] = value


class Counters:
    """The program's counters as one flat {path: number} reading, and the
    difference of two readings. Paths are those of the crypto_health
    snapshot (ops.dispatch.health_snapshot), plus `metrics.*` for the
    process-cumulative libs.metrics counters and `jax.*` for the compile
    events JAX reports while this object listens."""

    def __init__(self) -> None:
        import jax.monitoring

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def read(self) -> dict:
        from cometbft_tpu.libs import metrics
        from cometbft_tpu.ops import dispatch

        out: dict = {}
        _flatten(dispatch.health_snapshot(), "", out)
        cm = metrics.crypto_metrics()
        out["metrics.fallback_verifies"] = cm.fallback_verifies.total()
        out["metrics.mask_oracle_disagreement"] = (
            cm.mask_oracle_disagreement.total())
        out["metrics.transfer_checksum_mismatch"] = (
            cm.transfer_checksum_mismatch.total())
        out["metrics.device_batches.ed25519"] = cm.device_batches.value(
            "ed25519")
        out["metrics.device_batches.sr25519"] = cm.device_batches.value(
            "sr25519")
        out["metrics.device_lanes"] = cm.device_lanes.total()
        out["jax.programs_built"] = self.compiles
        out["jax.cache_hits"] = self.cache_hits
        return out

    @staticmethod
    def diff(before: dict, after: dict) -> dict:
        """after - before for every path; a path born inside the window
        (a supervisor's first batch) counts from nought."""
        return {k: v - before.get(k, 0) for k, v in after.items()}


def switch_host_tracer(on: bool) -> None:
    """The program's own tracer (libs/trace), on for traced runs only: it
    costs host time. Its attribution starts from nought."""
    from cometbft_tpu.libs import trace

    trace.configure(enabled=on)
    trace.reset_attribution()


def host_attribution() -> dict | None:
    """libs/trace's rolling attribution of HOST self time by stage (queue,
    stage, resolve ...), or None while the tracer is off."""
    from cometbft_tpu.libs import trace

    att = trace.attribution()
    return att if att.get("enabled") else None
