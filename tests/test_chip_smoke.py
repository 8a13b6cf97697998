"""CPU-side checks of chip_smoke.py's own logic: the rung accounting must
FAIL on every way a batch can be served below the rung it should ride, the
script must never complete off the chip, the compile-cache helper must be
placeable from outside, and the phases run end to end at tiny sizes (the
on-chip-measurement guide's rehearsal 2.1) — the script itself has no CPU
mode, so the rehearsal steers it from here."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

from cometbft_tpu import sched  # noqa: E402
from cometbft_tpu.crypto import batch as crypto_batch  # noqa: E402
from cometbft_tpu.libs import chaos, linkmodel  # noqa: E402
from cometbft_tpu.ops import challenge, compile_cache, dispatch  # noqa: E402
from cometbft_tpu.ops import ed25519_kernel as EK  # noqa: E402
from cometbft_tpu.ops import residency  # noqa: E402


def _sup(successes=0, failures=0, retries=0, breaker="closed", err=None):
    return {"breaker": breaker, "failures": failures, "retries": retries,
            "successes": successes, "last_error": err}


def _clean_snapshot() -> dict:
    """What phase A looks like when every batch rode Pallas: 3 aligned
    ed25519 batches, 3 aligned sr25519 batches."""
    return {
        "configured_backend": "tpu", "active_backend": "tpu",
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "supervisors": {
            "device": _sup(6), "pallas.ed25519": _sup(3),
            "pallas.sr25519": _sup(3), "ed25519.challenge": _sup(3),
            "doublebuf.dev0": _sup()},
        "counters": {
            "fallback_verifies": 0, "mask_oracle_disagreement": 0,
            "transfer_checksum_mismatch": 0, "mask_echo_mismatch": 0,
            "mesh_fallback_total": 0, "device_batches_ed25519": 3,
            "device_batches_sr25519": 3, "device_lanes": 36864},
        "challenge": {"plans": 3, "lanes_device": 11000,
                      "lanes_host_fallback": 4000},
        "wire": {"indexed": 6, "delta": 0, "full": 0},
        "bytes_per_sig": 80.0, "fetch": {}, "link": {},
        "table_devices": {"ed25519": ["TPU_0"], "sr25519": ["TPU_0"]},
        "dispatched_shapes": [6144], "compiles": 0,
        "sched": {"batches": 3, "class_rows": {"consensus": 30720},
                  "chaos_fallbacks": 0},
        "mesh": {"active": False, "devices": 1, "live": 1, "evictions": 0,
                 "readmissions": 0, "redispatched_batches": 0,
                 "fallbacks": 0, "chips": {}},
    }


EXPECT = dict(aligned_ed=3, aligned_sr=3, warmed={8, 256, 6144, 10240})


def test_rung_accounting_passes_a_clean_snapshot():
    assert chip_smoke.check_rungs(_clean_snapshot(), **EXPECT) == []


def _xla_served(s):  # a 128-aligned batch fell through PallasGate to XLA
    s["supervisors"]["pallas.ed25519"] = _sup(
        2, failures=1, breaker="open", err="permanent: Mosaic")


def _pallas_never_ran(s):  # e.g. CBFT_NO_PALLAS: no supervisor, no trace
    del s["supervisors"]["pallas.sr25519"]


def _cpu_served(s):  # the host oracle verified the lanes
    s["counters"]["fallback_verifies"] = 6144
    s["supervisors"]["device"] = _sup(
        5, failures=1, err="timeout: TimeoutError")


def _breaker_open(s):
    s["supervisors"]["device"]["breaker"] = "open"
    s["active_backend"] = "cpu"


def _retried(s):
    s["supervisors"]["device"]["retries"] = 2


def _oracle_disagreed(s):
    s["counters"]["mask_oracle_disagreement"] = 1


def _checksum_mismatch(s):
    s["counters"]["transfer_checksum_mismatch"] = 1


def _host_challenge(s):
    s["challenge"] = {"plan_breaker_open": 3}


def _derive_failed(s):
    s["challenge"]["derive_failed"] = 1


def _no_indexed_send(s):
    s["wire"] = {"indexed": 0, "delta": 0, "full": 6}


def _compiled_in_phase(s):
    s["dispatched_shapes"] = [4096, 6144]


def _program_built_in_phase(s):
    s["compiles"] = 1


def _more_built_than_new_derive_geometries_explain(s):
    s["challenge"]["derive_programs"] = 1
    s["compiles"] = chip_smoke.PROGRAMS_PER_DERIVE_GEOMETRY + 1


def _planned_but_not_derived_on_device(s):
    s["supervisors"]["ed25519.challenge"] = _sup(2)


def _no_plan_at_full_width(s):
    s["challenge"] = {}
    s["supervisors"]["ed25519.challenge"] = _sup()


def _on_the_cpu(s):
    s["device"] = {"platform": "cpu", "kind": "cpu", "count": 1}


def _sched_degraded(s):
    s["sched"]["chaos_fallbacks"] = 1


@pytest.mark.parametrize("spoil", [
    _xla_served, _pallas_never_ran, _cpu_served, _breaker_open, _retried,
    _oracle_disagreed, _checksum_mismatch, _host_challenge, _derive_failed,
    _no_indexed_send, _compiled_in_phase, _program_built_in_phase,
    _more_built_than_new_derive_geometries_explain,
    _planned_but_not_derived_on_device, _no_plan_at_full_width,
    _on_the_cpu, _sched_degraded,
], ids=lambda f: f.__name__.lstrip("_"))
def test_rung_accounting_fails_when_a_lower_rung_served(spoil):
    snap = _clean_snapshot()
    spoil(snap)
    assert chip_smoke.check_rungs(snap, **EXPECT) != []


def test_rung_accounting_of_a_vote_flush_phase():
    """Phase B's reading of the same rule: narrow flushes need not plan a
    device challenge, and a derive geometry outside the warmed family (a
    nil vote, a round above 0) may build its few programs — no more."""
    snap = _clean_snapshot()
    _no_plan_at_full_width(snap)
    assert chip_smoke.check_rungs(snap, **EXPECT, want_challenge=False) == []
    snap = _clean_snapshot()
    snap["challenge"]["derive_programs"] = 1
    snap["compiles"] = chip_smoke.PROGRAMS_PER_DERIVE_GEOMETRY
    assert chip_smoke.check_rungs(snap, **EXPECT) == []


def _mesh_snapshot() -> dict:
    """Sixteen ed25519 shards on 2,048-lane buckets, each the one-chip
    trip on its own chip (a Pallas success, a plan, a derive); sr25519
    shards on the XLA ladder."""
    s = _clean_snapshot()
    del s["supervisors"]["pallas.sr25519"]
    s["supervisors"]["pallas.ed25519"] = _sup(16)
    s["supervisors"]["ed25519.challenge"] = _sup(16)
    s["challenge"]["plans"] = 16
    s["counters"]["device_batches_ed25519"] = 16
    s["dispatched_shapes"] = [2048]
    s["mesh"] = {"active": True, "devices": 4, "live": 4, "evictions": 0,
                 "readmissions": 0, "redispatched_batches": 0,
                 "fallbacks": 0,
                 "shard_program": {"ed25519": "pallas", "sr25519": "xla",
                                   "bls12381": "xla"},
                 "chips": {str(i): {"successes": 4, "failures": 0,
                                    "shards": 4, "shard_lanes": [2048],
                                    "array_devices": [f"TPU_{i}"]}
                           for i in range(4)}}
    s["table_devices"] = {f"{scheme}/dev{i}": [f"TPU_{i}"]
                          for scheme in ("ed25519", "sr25519")
                          for i in range(4)}
    return s


MESH_EXPECT = dict(aligned_ed=16, aligned_sr=0, warmed={2048}, mesh_chips=4)


def test_mesh_accounting_passes_four_live_chips():
    assert chip_smoke.check_rungs(_mesh_snapshot(), **MESH_EXPECT) == []


def _chip_idle(s):
    s["mesh"]["chips"]["3"].update(successes=0, shards=0)


def _everything_on_the_first_device(s):
    for chip in s["mesh"]["chips"].values():
        chip["array_devices"] = ["TPU_0"]


def _tables_on_the_first_device(s):
    s["table_devices"] = dict.fromkeys(s["table_devices"], ["TPU_0"])


def _chip_evicted(s):
    s["mesh"]["live"], s["mesh"]["evictions"] = 3, 1


def _mesh_fell_back(s):
    s["mesh"]["fallbacks"] = 1
    s["counters"]["mesh_fallback_total"] = 1


def _redispatched(s):
    s["mesh"]["redispatched_batches"] = 1


def _shards_on_the_xla_ladder(s):  # the mesh of before PR 33
    s["supervisors"]["pallas.ed25519"] = _sup(0)


def _mesh_does_not_say_pallas(s):
    s["mesh"]["shard_program"]["ed25519"] = "xla"


def _shards_with_host_challenges(s):
    s["challenge"]["plans"] = 0
    s["supervisors"]["ed25519.challenge"] = _sup(0)


@pytest.mark.parametrize("spoil", [
    _chip_idle, _chip_evicted, _mesh_fell_back, _redispatched,
    _everything_on_the_first_device, _tables_on_the_first_device,
    _shards_on_the_xla_ladder, _mesh_does_not_say_pallas,
    _shards_with_host_challenges,
], ids=lambda f: f.__name__.lstrip("_"))
def test_mesh_accounting_fails_on_a_missing_chip(spoil):
    snap = _mesh_snapshot()
    spoil(snap)
    assert chip_smoke.check_rungs(snap, **MESH_EXPECT) != []


@pytest.mark.parametrize("var", ["CBFT_NO_PALLAS", "CBFT_CHAOS"])
def test_refuses_env_that_takes_the_device_off_the_path(var):
    value = "1" if var == "CBFT_NO_PALLAS" else "pallas.trace=permanent"
    with pytest.raises(chip_smoke.SmokeFailure, match=var):
        chip_smoke.refuse_off_device_env({var: value})
    chip_smoke.refuse_off_device_env({})


def _run_script(extra_env: dict) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra_env)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")], cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("extra_env", [
    {}, {"CBFT_NO_PALLAS": "1"}, {"CBFT_CHAOS": "pallas.trace=permanent"},
], ids=["cpu-only-host", "no-pallas-env", "chaos-env"])
def test_script_never_completes_off_the_chip(extra_env):
    """The script as the driver runs it, on a host with no accelerator:
    non-zero exit, and the last line says ok: false."""
    proc = _run_script(extra_env)
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False


def test_compile_cache_honours_the_env_variable(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.arm() == str(tmp_path)
    # placed from outside: no directory is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert compile_cache.arm() == want
    assert jax.config.jax_compilation_cache_dir == want


# ---------------------------------------------------------------- rehearsal


@pytest.fixture
def device_plane(monkeypatch):
    """The smoke's set-up on this host: backend "tpu" through the node's
    own callable (the XLA programs then run on the host CPU), with the
    health surface told it sees a TPU — the one thing a CPU rehearsal has
    to steer, and it is steered here, not by an option of the script."""
    from cometbft_tpu.config import Config
    from cometbft_tpu.libs import log as cmtlog
    from cometbft_tpu.node import node as node_mod

    from cometbft_tpu.parallel import mesh as verify_mesh

    def clean():
        chaos.reset()
        sched.reset()
        dispatch.reset_supervision()
        residency.reset()
        challenge.reset()
        linkmodel.reset()
        EK.reset_shape_log()
        verify_mesh.reset()

    clean()
    # one chip, as the driver's machine has: the tests' 8 forced host
    # devices would otherwise switch the mesh on
    verify_mesh._set_for_testing(
        verify_mesh.VerifyMesh(devices=jax.devices()[:1]))
    prev = crypto_batch.get_backend()
    cfg = Config(home="")
    cfg.crypto.backend = "tpu"
    node_mod.configure_device_plane(
        cfg.crypto, cmtlog.Logger(level=cmtlog.parse_level("error")))
    monkeypatch.setattr(
        crypto_batch, "_device",
        {"platform": "tpu", "kind": "rehearsal", "count": 1})
    acct = chip_smoke.Accounting()
    yield acct
    acct.close()
    clean()
    crypto_batch.set_backend(prev)
    dispatch.configure(watchdog_timeout=120.0)


def test_phases_rehearse_on_cpu_at_8_and_16_validators(device_plane):
    acct = device_plane
    workloads = chip_smoke.build_workloads(
        (("ed-8", 8, 0), ("mixed-16", 8, 8)), seed=5)
    warmed = chip_smoke.bucket_set(workloads, 4)
    assert warmed == {8}
    chip_smoke.warm_up(workloads, 4, seed=5)
    readings = chip_smoke.phase_a(workloads, acct, warmed, repeats=2)
    assert set(readings) == {"ed-8", "mixed-16"}
    out = chip_smoke.phase_b(acct, warmed, n_vals=4, heights=3, n_txs=3,
                             deadline_s=120.0)
    assert out["heights"] >= 3 and out["device_batches"] >= 3


@pytest.mark.slow  # ~4 min cold: every virtual chip builds its own programs
def test_mesh_phase_rehearses_on_four_virtual_devices(device_plane):
    """Guide 2.2: the four-chip path on four forced host devices, steered
    onto the branch an accelerator mesh takes (per-chip resident tables)
    — the shards' arrays and tables must land on four distinct devices.
    Run it before a `chiprun --chips 4` call: -m slow -k four_virtual."""
    from cometbft_tpu.parallel import mesh as verify_mesh

    acct = device_plane
    mesh = verify_mesh.VerifyMesh(devices=jax.devices()[:4])
    mesh._device_cache = True  # the sr25519 shards' branch on a chip
    verify_mesh._set_for_testing(mesh)
    # every stamp in one encoding length: a shard of 8 rows then plans the
    # geometry every other shard plans (at 2,048 rows a shard the common
    # stamps dominate by themselves), and the warm-up's two commits build
    # every program the phase runs
    workloads = []
    for k, (name, n_ed, n_sr) in enumerate(
            (("ed-32", 32, 0), ("mixed-32+32", 32, 32))):
        nanos = [(500 + i) * 1_000_000 for i in range(n_ed + n_sr)]
        workloads.append((name, *chip_smoke.make_commit(
            n_ed, n_sr, 6 + k, nanos=nanos), 5 + 36 * k))
    # a consensus-class batch this small is pinned to one chip
    with sched.work_class("sync"):
        readings = chip_smoke.mesh_phase(workloads, acct, repeats=1)
    assert set(readings) == {"ed-32", "mixed-32+32"}
    chips = acct.snapshot()["mesh"]["chips"]
    assert [c["shard_lanes"] for c in chips.values()] == [[8]] * 4
    assert len({tuple(c["array_devices"]) for c in chips.values()}) == 4


def test_vote_geometry_family_is_what_the_planner_sees(device_plane):
    """Every entry of the warm-up's timestamp family plans its own
    derive geometry, all on the net's prefix length."""
    geometries = set()
    for nanos in chip_smoke.vote_geometry_nanos(4):
        vals, _bid, commit = chip_smoke.make_commit(
            4, 0, 7, chip_smoke.NET_CHAIN_ID, nanos)
        _pubs, msgs, _sigs = chip_smoke.commit_rows(
            vals, commit, chip_smoke.NET_CHAIN_ID)
        plan = challenge.plan_batch(msgs, [True] * 4)
        assert plan is not None and plan.n_eligible == 4
        geometries.add((plan.plen, plan.var, plan.tlen))
    assert len(geometries) == 16
    assert len({plen for plen, _var, _tlen in geometries}) == 1


def test_rung_accounting_catches_a_live_pallas_fault(device_plane,
                                                     monkeypatch):
    """pallas.trace=permanent on an aligned batch: PallasGate swallows the
    fault and the XLA ladder returns the right verdict — which is exactly
    what the accounting exists to catch."""
    from cometbft_tpu.ops import pallas_verify as PV

    acct = device_plane
    monkeypatch.setattr(EK, "_use_pallas", True)
    monkeypatch.setattr(PV, "LANES", 8)  # an 8-lane bucket counts aligned
    vals, bid, commit = chip_smoke.make_commit(8, 0, seed=9)
    acct.mark()
    chaos.arm("pallas.trace", "permanent")
    chip_smoke._verify(vals, bid, commit)  # verdict right, rung wrong
    snap = acct.snapshot()
    pallas = snap["supervisors"]["pallas.ed25519"]
    assert pallas["successes"] == 0 and pallas["failures"] == 1
    bad = chip_smoke.check_rungs(snap, aligned_ed=1, aligned_sr=0,
                                 warmed={8})
    assert any("pallas.ed25519" in b for b in bad)


# ------------------------------------------------------- the loud boot line


class _Log:
    def __init__(self):
        self.lines: list[tuple[str, str, dict]] = []

    def info(self, msg, **kw):
        self.lines.append(("info", msg, kw))

    def error(self, msg, **kw):
        self.lines.append(("error", msg, kw))


def test_boot_says_at_error_level_that_tpu_backend_has_no_tpu(device_plane,
                                                              monkeypatch):
    """backend="tpu" on a host with no TPU stays legal (the e2e device
    perturbations run that way) but is no longer invisible; and
    crypto_health carries the device the node booted on."""
    from cometbft_tpu.config import Config
    from cometbft_tpu.node import node as node_mod

    monkeypatch.setattr(crypto_batch, "_device", None)  # probe for real
    cfg = Config(home="")
    cfg.crypto.backend = "tpu"
    log = _Log()
    record = node_mod.configure_device_plane(cfg.crypto, log)
    assert record["platform"] == "cpu" and record["resolved_backend"] == "tpu"
    (level, msg, fields), = log.lines
    assert level == "error" and "host CPU" in msg
    assert fields["configured_backend"] == "tpu" and fields["kind"] == "cpu"
    assert dispatch.health_snapshot()["device"] == {
        "platform": "cpu", "kind": "cpu", "count": len(jax.devices())}


def test_auto_backend_needs_a_tpu_not_just_any_device(monkeypatch):
    """"auto" resolves to the device path only for platform "tpu": any
    other accelerator is not what the kernels were written for."""
    prev = crypto_batch.get_backend()
    try:
        crypto_batch.set_backend("auto")
        for platform, want in (("tpu", "tpu"), ("gpu", "cpu"),
                               ("cpu", "cpu")):
            monkeypatch.setattr(
                crypto_batch, "_device",
                {"platform": platform, "kind": "x", "count": 1})
            assert crypto_batch.resolve_backend() == want
    finally:
        crypto_batch.set_backend(prev)
