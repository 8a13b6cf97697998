"""A mesh shard is the one-chip ed25519 trip aimed at a chip (PR 33):
parallel/mesh.VerifyMesh hands a slice of the scheduler's columns to
ops/ed25519_kernel.verify_batch_async with the chip as its Target.

On the forced host devices conftest pins. As in tests/test_mesh.py, only
the 2-chip test runs the real curve math (instantiating a ladder costs tens
of seconds a program); the 4-chip ones stub it alone, through the one seam
VerifyMesh._scheme_ops()["kernel"]: columns, per-chip tables and uploads,
the derive program, integrity, supervisors, redispatch and the spans all
run for real.

  (a) a seeded committee's commit spread over 2 chips, real kernels:
      verify_commit through scheduler and mesh answers as the benchmark's
      plain reference, clean and with one signature corrupt in each shard
  (b) 4 chips: the shards' masks laid together are the one-chip mask, every
      shard got columns, every chip's arrays lie on its own device, one
      blocking wait a shard
  (c) a chip killed mid-flush loses no lane on the new shard path
  (d) the mesh's spans in attribution(), its counters in health_snapshot()
"""

from __future__ import annotations

import json
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import datagen, program  # noqa: E402
from benchmarks.reference import commit_ref  # noqa: E402

from cometbft_tpu import sched  # noqa: E402
from cometbft_tpu.crypto import batch as crypto_batch  # noqa: E402
from cometbft_tpu.libs import chaos, linkmodel, trace  # noqa: E402
from cometbft_tpu.libs.prefixrows import MsgBlock  # noqa: E402
from cometbft_tpu.ops import challenge, residency  # noqa: E402
from cometbft_tpu.ops import dispatch as D  # noqa: E402
from cometbft_tpu.ops import ed25519_kernel as EK  # noqa: E402
from cometbft_tpu.parallel import mesh as M  # noqa: E402
from cometbft_tpu.types import validation  # noqa: E402

SEED = 2_147_489_033  # over 2**31, as the driver's are


def _clean():
    chaos.reset()
    sched.reset()
    D.reset_supervision()
    residency.reset()
    challenge.reset()
    linkmodel.reset()
    M.reset()
    M.configure(enabled=True, min_devices=2, placement="class_aware")


@pytest.fixture(autouse=True)
def _mesh_state():
    _clean()
    D.configure(failure_threshold=3, cooldown=30.0, retry_attempts=2,
                retry_base=0.0, retry_cap=0.0, watchdog_timeout=600.0)
    prev = crypto_batch.get_backend()
    crypto_batch.set_backend("tpu")
    yield
    crypto_batch.set_backend(prev)
    D.configure(failure_threshold=3, cooldown=30.0, retry_attempts=2,
                retry_base=0.05, retry_cap=1.0, watchdog_timeout=120.0)
    trace.configure(enabled=False)
    trace.reset()
    _clean()


def _mesh(k: int) -> M.VerifyMesh:
    vm = M.VerifyMesh(jax.devices("cpu")[:k])
    M._set_for_testing(vm)
    return vm


def _stub_curve_math(monkeypatch, bad_lanes_of=None):
    """The curve math alone becomes an instant program, all lanes valid but
    those whose R word 0 is in `bad_lanes_of` (a set of uint32): every
    lane's first R word is its own in these tests, so a stubbed shard can
    still condemn the lane the test corrupted."""
    import jax.numpy as jnp

    real = M.VerifyMesh._scheme_ops
    bad = np.asarray(sorted(bad_lanes_of or ()), dtype=np.uint32)

    def ladder(ax, ay, az, at, rw, sw, kw):
        mask = ~jnp.isin(rw[0], bad)
        return mask, mask.all()

    def fake(scheme):
        ops = dict(real(scheme))
        ops["kernel"] = ladder
        return ops

    monkeypatch.setattr(M.VerifyMesh, "_scheme_ops", staticmethod(fake))
    return ladder


def _committee(n: int):
    """benchmarks/configs/committee-10k-ed.json cut to n validators: the
    specs, the program's validator set and its commits."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "committee-10k-ed.json")) as fh:
        config = json.load(fh)
    assert config["validators"] == {"ed25519": 10240}
    config["validators"] = {"ed25519": n}
    config["ring_heights"] = 2
    vals_spec, signers = datagen.make_validators(config, SEED)
    ring = datagen.make_ring(config, vals_spec, signers, SEED)
    vals = program.build_validator_set(vals_spec)
    return vals_spec, ring, vals, [program.build_commit(vals, spec)
                                   for spec in ring]


@pytest.fixture(scope="module")
def committee16():
    return _committee(16)


@pytest.fixture(scope="module")
def committee400():
    return _committee(400)


def _verify(vals_spec, vals, block_id, commit) -> str:
    # a commit under PIN_MAX_ROWS spreads only outside the consensus class
    # (blocksync's: sched.SYNC); the cell's 10,240 rows spread in any
    with sched.work_class(sched.SYNC):
        return program.verdict_of(lambda: validation.verify_commit(
            vals_spec.chain_id, vals, block_id, commit.height, commit))


# ------------------------------------------------------- (a) real kernels


@pytest.mark.parametrize("lane", [None, 2, 13],
                         ids=["clean", "corrupt-in-shard-0",
                              "corrupt-in-shard-1"])
def test_commit_over_two_chips_answers_as_the_reference(committee16, lane):
    vals_spec, ring, vals, commits = committee16
    vm = _mesh(2)
    spec = ring[0] if lane is None else ring[0].with_flipped(lane)
    block_id, commit = commits[0]
    want = commit_ref.verdict(vals_spec, spec, commit_ref.verify_lane)
    assert want == ("accept" if lane is None else f"reject#{lane}")

    counters = program.Counters()
    try:
        before = counters.read()
        with program.warmup_watchdog():
            got = _verify(vals_spec, vals, block_id,
                          program.fresh(commit, lane))
        moved = program.Counters.diff(before, counters.read())
    finally:
        counters.close()
    assert got == want
    # 16 rows over 2 chips: two shards of 8, each the trip on its own chip
    assert moved["mesh.batches"] == 1 and moved["mesh.rows_total"] == 16
    assert moved["mesh.shards_total"] == 2
    assert moved["mesh.lanes_total"] == 16
    assert moved["metrics.device_batches.ed25519"] == 2
    assert moved["staging.trip.batches"] == 2
    assert (moved["mesh.fallbacks"] == moved["mesh.evictions"]
            == moved["mesh.redispatched_batches"] == 0)
    # the verdict is the devices' own
    assert moved["metrics.fallback_verifies"] == 0
    assert moved["metrics.mask_oracle_disagreement"] == 0
    chips = vm.health()["chips"]
    assert [c["shards_total"] for c in chips.values()] == [1, 1]
    assert [c["array_devices"] for c in chips.values()] == [
        [str(d)] for d in jax.devices("cpu")[:2]]


# ------------------------------------------------ (b) four chips, stubbed


def _first_r_words(commit) -> np.ndarray:
    return np.frombuffer(b"".join(cs.signature[:4]
                                  for cs in commit.signatures), dtype="<u4")


def test_shards_are_column_slices_on_their_own_chips(committee400,
                                                     monkeypatch):
    vals_spec, _ring, vals, commits = committee400
    block_id, commit = commits[0]
    bad_lanes = [5, 150, 399]  # shards 0, 1 and 3 of four of 100 rows
    corrupt = program.fresh(commit, bad_lanes[0])
    for lane in bad_lanes[1:]:
        corrupt = program.fresh(corrupt, lane)
    r0 = _first_r_words(corrupt)
    assert len(set(r0.tolist())) == 400
    ladder = _stub_curve_math(monkeypatch, set(r0[bad_lanes].tolist()))

    seen = []
    real_trip = EK.verify_batch_async

    def spy(pubs, msgs, sigs, **kw):
        seen.append((pubs, msgs, sigs, kw))
        return real_trip(pubs, msgs, sigs, **kw)

    monkeypatch.setattr(EK, "verify_batch_async", spy)

    # the same rows on one chip (mesh off), under the same curve math
    rows, _idxs = validation._commit_rows(
        vals_spec.chain_id, vals, corrupt, vals.total_voting_power() * 2 // 3,
        False, True, True)
    cols = rows.parts["ed25519"][1]
    assert isinstance(cols.msgs, MsgBlock) and cols.pub_rows is not None
    one_chip = real_trip(cols.pubs, cols.msgs, cols.sigs,
                         pub_rows=cols.pub_rows, ladder=ladder)()
    assert np.flatnonzero(~one_chip).tolist() == bad_lanes

    vm = _mesh(4)
    # first call: every chip's key table and prefix row go up
    mesh_mask = vm.verify("ed25519", cols.pubs, cols.msgs, cols.sigs,
                          klass="sync", pub_rows=cols.pub_rows)
    assert mesh_mask.tolist() == one_chip.tolist()  # lane for lane
    seen.clear()
    residency.reset_send_stats()

    assert _verify(vals_spec, vals, block_id, program.fresh(corrupt)) == (
        f"reject#{bad_lanes[0]}")
    # every shard got columns: views of the batch's matrices, no list of
    # bytes made for it
    assert len(seen) == 4
    for k, (pubs, msgs, sigs, kw) in enumerate(seen):
        assert isinstance(msgs, MsgBlock) and len(msgs) == 100
        assert isinstance(sigs, np.ndarray) and sigs.shape == (100, 64)
        assert sigs.base is not None
        assert kw["pub_rows"].shape == (100, 32)
        assert kw["pub_rows"].base is not None
        assert kw["target"].strict and kw["target"].put_key.startswith("dev")
        assert pubs == cols.pubs[100 * k:100 * (k + 1)]
    assert sorted(kw["target"].index for *_, kw in seen) == [0, 1, 2, 3]
    # the steady state: a batch a shard, one blocking wait a shard plus
    # the payload pull of each of the three shards that hold a bad lane
    trip = residency.trip_stats()
    assert trip["batches"] == 4
    assert trip["blocking_waits"] == 4 + 3
    assert trip["device_programs"] == 2 * 4
    # each chip's arrays, key table and prefix table on its own device
    devices = [str(d) for d in jax.devices("cpu")[:4]]
    health = vm.health()
    assert [c["array_devices"] for c in health["chips"].values()] == [
        [d] for d in devices]
    tables = residency.stats()["tables"]
    assert [tables[f"ed25519/dev{i}"]["devices"] for i in range(4)] == [
        [d] for d in devices]
    prefix = challenge.table_stats()
    assert [prefix[f"dev{i}"]["devices"] for i in range(4)] == [
        [d] for d in devices]
    assert health["shard_program"]["ed25519"] == "xla"  # no TPU here


# ------------------------------------------------------ (c) a chip killed


def test_chip_killed_mid_flush_loses_no_lane_of_a_commit(committee400,
                                                         monkeypatch):
    vals_spec, _ring, vals, commits = committee400
    block_id, commit = commits[1]
    corrupt = program.fresh(commit, 250)  # a lane of the dying chip's shard
    _stub_curve_math(monkeypatch, {int(_first_r_words(corrupt)[250])})
    vm = _mesh(4)
    # the chip stays dead for the whole test: a cooldown no compile under
    # load can outlast (at 30 s a slow first call half-opened the breaker
    # and the mesh counted four live chips again)
    D.configure(failure_threshold=1, cooldown=3600.0)
    chaos.arm("ed25519.dispatch.dev2", "permanent")
    assert _verify(vals_spec, vals, block_id, program.fresh(commit)) == (
        "accept")
    h = vm.health()
    assert h["evictions"] == 1 and h["live"] == 3
    assert h["redispatched_batches"] >= 1 and h["fallbacks"] == 0
    assert h["chips"]["2"]["state"] == D.OPEN
    assert h["chips"]["2"]["shards_total"] == 0
    # the dead chip's rows were verified, not waved through: the corrupt
    # lane among them is named, on the shrunken mesh
    assert _verify(vals_spec, vals, block_id, corrupt) == "reject#250"
    assert vm.health()["fallbacks"] == 0
    assert vm.health()["rows_total"] == 800
    # no slot of any chip's in-flight gate was lost with the shard
    for dom in D.doublebuffer_stats():
        gate = D.doublebuffer(dom)
        assert gate._sem._value == gate.slots


# ------------------------------------------- (d) what the mesh reports


def test_mesh_spans_and_counters(committee400, monkeypatch):
    vals_spec, _ring, vals, commits = committee400
    block_id, commit = commits[0]
    _stub_curve_math(monkeypatch)
    _mesh(4)
    assert _verify(vals_spec, vals, block_id, program.fresh(commit)) == (
        "accept")  # tables up, programs built
    assert "join" in trace.STAGES
    trace.configure(enabled=True, capacity=4096)
    trace.reset_attribution()
    before = D.health_snapshot()["mesh"]
    assert _verify(vals_spec, vals, block_id, program.fresh(commit)) == (
        "accept")
    spans = trace.snapshot()
    att = trace.attribution()
    after = D.health_snapshot()["mesh"]

    by_name: dict = {}
    for sp in spans:
        by_name.setdefault(sp["name"], []).append(sp)
    # once a batch, once a shard
    assert len(by_name["mesh.plan"]) == 1
    assert len(by_name["mesh.join"]) == 1
    assert len(by_name["mesh.shard"]) == 4
    assert by_name["mesh.plan"][0]["cat"] == "stage"
    assert by_name["mesh.join"][0]["cat"] == "join"
    assert sorted(sp["attrs"]["device"] for sp in by_name["mesh.shard"]) == [
        0, 1, 2, 3]
    assert all(sp["cat"] == "stage" and sp["attrs"]["rows"] == 100
               and sp["attrs"]["lanes"] == 128
               for sp in by_name["mesh.shard"])
    # the per-shard trip spans say their chip (a challenge span with a
    # `rung` is the host's work for lanes whose stamp has another length)
    for name in ("ed25519.stage", "ed25519.challenge", "ed25519.dispatch"):
        assert sorted(sp["attrs"]["device"] for sp in by_name[name]
                      if "rung" not in sp["attrs"]) == [0, 1, 2, 3], name
    # each row counted once, and the join is a stage of its own
    assert att["rows"] == 400
    assert att["stage_us"]["join"] > 0
    join = by_name["mesh.join"][0]
    fetched = sum(sp["dur_ns"] for sp in by_name["ed25519.d2h"])
    assert att["stage_us"]["join"] * 1e3 <= join["dur_ns"] - fetched + 2e3

    moved = {k: after[k] - before[k] for k in (
        "batches", "rows_total", "shards_total", "lanes_total", "evictions",
        "redispatched_batches", "fallbacks")}
    assert moved == {"batches": 1, "rows_total": 400, "shards_total": 4,
                     "lanes_total": 512, "evictions": 0,
                     "redispatched_batches": 0, "fallbacks": 0}
    assert after["active"] is True and after["live"] == 4
    assert after["shard_program"] == {"ed25519": "xla", "sr25519": "xla",
                                      "bls12381": "xla"}


def test_plan_of_the_10k_committee():
    """10,240 rows over four chips: five shards of 2,048 rows, the least
    loaded chip twice; over three, still 2,048-lane shards."""
    vm = _mesh(4)
    plan = vm._plan(10240, "consensus", vm.chips)
    assert [hi - lo for _, lo, hi in plan] == [2048] * 5
    assert [c.index for c, _, _ in plan] == [0, 1, 2, 3, 0]
    plan3 = vm._plan(10240, "consensus", vm.chips[:3])
    assert [hi - lo for _, lo, hi in plan3] == [2048] * 5
