"""The trip's host time by name (ISSUE 37): the wait for
ops/dispatch.KERNEL_DISPATCH_LOCK (`dispatch.lock`, counted under
`attribution.dispatch_lock`), the caller's wait for the transfer pool's
dispatch (`resolve.await_dispatch`), the staging checksum
(`ed25519.checksum`), the header decode (`resolve.headers`) and a mesh
shard's cut of the columns (`mesh.cut`); the threads' CPU clock
(`attribution.oncpu`); and the six readers of benchmarks/metrics/ that
read them.

On the CPU backend with the "tpu" rung, the curve math stubbed through the
trip's `ladder` seam (an instant program: the derive program, uploads,
integrity, fetches and spans all run for real).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import threading
import time

import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from benchmarks import program, readers  # noqa: E402

from cometbft_tpu import sched  # noqa: E402
from cometbft_tpu.crypto import batch as crypto_batch  # noqa: E402
from cometbft_tpu.libs import trace  # noqa: E402
from cometbft_tpu.ops import challenge, residency  # noqa: E402
from cometbft_tpu.ops import dispatch as D  # noqa: E402
from cometbft_tpu.ops import ed25519_kernel as EK  # noqa: E402
from cometbft_tpu.parallel import mesh as M  # noqa: E402
from cometbft_tpu.types import validation  # noqa: E402

METRICS_DIR = os.path.join(ROOT, "benchmarks", "metrics")
TRIP_SPANS = ("dispatch.lock", "resolve.await_dispatch", "ed25519.checksum",
              "resolve.headers")
READERS = ("host_oncpu_pct", "challenge_us_per_sig", "dispatch_us_per_sig",
           "queue_wait_us_per_sig", "dispatch_lock_wait_us_per_batch",
           "resolve_us_per_sig")


def _ladder(ax, ay, az, at, rw, sw, kw):
    """The curve math as an instant program: every lane valid."""
    mask = jnp.ones(rw.shape[1], dtype=bool)
    return mask, mask.all()


def _clean() -> None:
    trace.reset()
    sched.reset()
    residency.reset()
    challenge.reset()
    M.reset()


def _verify_traced(vals, block_id, commit) -> tuple[list, dict]:
    """verify_commit once untraced (first-use set-up), once traced: the
    traced call's spans and the flattened crypto_health after it."""
    validation.verify_commit(chip_smoke.CHAIN_ID, vals, block_id,
                             commit.height, chip_smoke.fresh(commit))
    trace.configure(enabled=True, capacity=4096, slow_ms=-1.0)
    trace.reset_attribution()
    validation.verify_commit(chip_smoke.CHAIN_ID, vals, block_id,
                             commit.height, chip_smoke.fresh(commit))
    flat: dict = {}
    program._flatten(D.health_snapshot(), "", flat)
    return trace.snapshot(), flat


@pytest.fixture(scope="module")
def hub16():
    return chip_smoke.make_commit(16, 0, seed=37)


@pytest.fixture(scope="module")
def one_chip(hub16):
    """A traced verify_commit through the scheduler on one chip (mesh
    off), the curve math stubbed."""
    prev = crypto_batch.get_backend()
    _clean()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(EK, "verify_batch_async", functools.partial(
            EK.verify_batch_async, ladder=_ladder))
        M.configure(enabled=False)
        crypto_batch.set_backend("tpu")
        try:
            yield _verify_traced(*hub16)
        finally:
            crypto_batch.set_backend(prev)
            M.configure(enabled=True)
            _clean()


def _by_name(spans: list, name: str) -> list:
    return [r for r in spans if r["name"] == name]


class TestTheTripsSpans:
    def test_every_new_span_is_in_the_batchs_tree(self, one_chip):
        spans, _flat = one_chip
        (root,) = [r for r in spans if r["parent_id"] is None]
        assert root["name"] == "commit.verify"
        # one tree: the pool threads' spans joined it through wrap_ctx
        assert {r["trace_id"] for r in spans} == {root["trace_id"]}
        assert len({r["tid"] for r in spans}) >= 2
        names = {r["name"] for r in spans}
        assert set(TRIP_SPANS) <= names
        by_id = {r["id"]: r for r in spans}
        parent = {r["id"]: by_id[r["parent_id"]]["name"]
                  for r in spans if r["parent_id"] is not None}
        for name in ("resolve.await_dispatch", "ed25519.checksum",
                     "resolve.headers"):
            (rec,) = _by_name(spans, name)
            assert parent[rec["id"]] == "sched.dispatch"
            assert rec["tid"] == root["tid"]  # on the caller
        cats = {r["name"]: r["cat"] for r in spans}
        assert (cats["dispatch.lock"], cats["resolve.await_dispatch"],
                cats["ed25519.checksum"], cats["resolve.headers"]) == (
                    "queue", "", "stage", "resolve")
        # the lock's two takers of a device-challenge batch, each under
        # its program's call on the transfer pool
        locks = {r["attrs"]["site"]: parent[r["id"]]
                 for r in _by_name(spans, "dispatch.lock")}
        assert locks == {"ed25519.derive": "ed25519.challenge",
                         "ed25519.verify": "ed25519.dispatch"}

    def test_the_root_has_cpu_time_and_the_lock_was_counted(self,
                                                            one_chip):
        spans, flat = one_chip
        (root,) = [r for r in spans if r["parent_id"] is None]
        assert 0 <= root["cpu_ns"] <= root["dur_ns"]
        assert {r["cpu_ns"] for r in spans if r is not root} == {None}
        assert flat["attribution.dispatch_lock.acquires"] == 2
        assert flat["attribution.dispatch_lock.contended"] == 0
        assert flat["attribution.dispatch_lock.wait_us"] >= 0
        assert not D.KERNEL_DISPATCH_LOCK.locked()

    def test_the_threads_cpu_lies_inside_their_wall_time(self, one_chip):
        """The caller's root and the transfer pool's call each counted
        once, on this process's own clocks: CPU never outruns wall."""
        _spans, flat = one_chip
        for kind in ("root", "pool"):
            wall = flat[f"attribution.oncpu.{kind}_wall_us"]
            cpu = flat[f"attribution.oncpu.{kind}_cpu_us"]
            assert 0 < cpu <= wall, kind

    def test_the_wait_for_the_pool_is_no_stage(self, one_chip):
        """resolve.await_dispatch names the caller's wait; the pool's
        spans count that time, so stage `queue` does not count it again."""
        spans, flat = one_chip
        (wait,) = _by_name(spans, "resolve.await_dispatch")
        pooled = sum(r["dur_ns"] for r in spans
                     if r["tid"] != wait["tid"] and r["cat"])
        assert pooled > 0
        assert flat["attribution.stage_us.queue"] * 1e3 < wait["dur_ns"]

    def test_every_path_the_readers_name_is_in_crypto_health(self,
                                                             one_chip):
        _spans, flat = one_chip
        for name in READERS:
            source = readers.load_metric(METRICS_DIR, name)["source"]
            for path in source["num"] + source["den"]:
                assert path in flat, (name, path)


class TestDispatchLocked:
    @pytest.fixture(autouse=True)
    def _fresh(self):
        trace.reset()
        yield
        trace.reset()

    def test_off_it_is_the_bare_lock_and_counts_nothing(self):
        trace.configure(enabled=False)
        assert D.dispatch_locked("x") is D.KERNEL_DISPATCH_LOCK
        with D.dispatch_locked("x"):
            assert D.KERNEL_DISPATCH_LOCK.locked()
        assert not D.KERNEL_DISPATCH_LOCK.locked()
        assert trace.snapshot() == []
        assert set(trace.attribution()["dispatch_lock"].values()) == {0}

    def test_on_an_error_inside_releases_the_lock(self):
        trace.configure(enabled=True, slow_ms=-1.0)
        with pytest.raises(RuntimeError):
            with D.dispatch_locked("x"):
                assert D.KERNEL_DISPATCH_LOCK.locked()
                raise RuntimeError("program call failed")
        assert not D.KERNEL_DISPATCH_LOCK.locked()
        (rec,) = trace.snapshot()
        assert (rec["name"], rec["cat"], rec["attrs"]) == (
            "dispatch.lock", "queue", {"site": "x"})
        counts = trace.attribution()["dispatch_lock"]
        assert (counts["acquires"], counts["contended"]) == (1, 0)

    def test_a_contended_acquire_is_counted_and_waits_off_the_cpu(self):
        trace.configure(enabled=True, slow_ms=-1.0)
        holding, release, done = (threading.Event(), threading.Event(),
                                  threading.Event())

        def holder():
            with D.dispatch_locked("test.holder"):
                holding.set()
                release.wait(10)

        def waiter():
            with D.dispatch_locked("test.waiter"):
                pass
            done.set()

        first = threading.Thread(target=holder)
        first.start()
        assert holding.wait(10)
        second = threading.Thread(target=waiter)
        second.start()
        time.sleep(0.05)
        assert not done.is_set()  # it waits for the holder
        release.set()
        first.join(10)
        second.join(10)
        assert not first.is_alive() and not second.is_alive()
        assert done.is_set() and not D.KERNEL_DISPATCH_LOCK.locked()
        counts = trace.attribution()["dispatch_lock"]
        assert (counts["acquires"], counts["contended"]) == (2, 1)
        assert counts["wait_us"] >= 40_000
        waited = next(r for r in trace.snapshot()
                      if r["attrs"].get("site") == "test.waiter")
        assert waited["dur_ns"] >= 40_000_000
        # the CPU clock tells the wait from work: each thread's lock span
        # is its root, and both slept in it
        assert waited["cpu_ns"] < waited["dur_ns"] // 4
        oncpu = trace.attribution()["oncpu"]
        assert oncpu["root_cpu_us"] < oncpu["root_wall_us"] / 4


@pytest.fixture
def mesh_of_two(monkeypatch):
    """A two-chip VerifyMesh on the forced host devices, the curve math
    stubbed through its `kernel` seam."""
    import jax

    prev = crypto_batch.get_backend()
    _clean()
    real = M.VerifyMesh._scheme_ops

    def ops(scheme):
        out = dict(real(scheme))
        out["kernel"] = _ladder
        return out

    monkeypatch.setattr(M.VerifyMesh, "_scheme_ops", staticmethod(ops))
    M.configure(enabled=True, min_devices=2, placement="class_aware")
    M._set_for_testing(M.VerifyMesh(jax.devices("cpu")[:2]))
    crypto_batch.set_backend("tpu")
    yield
    crypto_batch.set_backend(prev)
    D.reset_supervision()
    _clean()


def test_a_mesh_shard_names_its_cut(hub16, mesh_of_two):
    """A shard's self time is split: mesh.cut (the columns' slices) and
    the checksum under it, beside the trip's staging and its lock."""
    spans, _flat = _verify_traced(*hub16)
    by_id = {r["id"]: r for r in spans}
    (shard,) = _by_name(spans, "mesh.shard")
    (cut,) = _by_name(spans, "mesh.cut")
    assert cut["parent_id"] == shard["id"]
    assert (cut["cat"], cut["attrs"]["rows"]) == ("stage", 16)
    kids = {r["name"] for r in spans if r["parent_id"] == shard["id"]}
    assert {"mesh.cut", "ed25519.stage", "ed25519.checksum"} <= kids
    assert {by_id[r["parent_id"]]["name"]
            for r in _by_name(spans, "dispatch.lock")} == {
                "ed25519.challenge", "ed25519.dispatch"}


# ------------------------------------------------------------ the readers


def _planted() -> dict:
    """The counters of a traced slice of 100 hub calls (15,000 rows, 100
    batches) as benchmarks/run.py differences them."""
    stages = {"node": 9_000.0, "header": 0.0, "signbytes": 40_000.0,
              "collect": 18_000.0, "stage": 45_000.0, "transfer": 20_000.0,
              "challenge": 110_000.0, "compute": 60_000.0,
              "resolve": 3_000.0, "queue": 150_000.0, "fetch": 120_000.0,
              "join": 0.0, "gc": 0.0}
    counters = {f"attribution.stage_us.{s}": us for s, us in stages.items()}
    counters.update({"attribution.oncpu.root_wall_us": 500_000.0,
                     "attribution.oncpu.root_cpu_us": 300_000.0,
                     "attribution.oncpu.pool_wall_us": 200_000.0,
                     "attribution.oncpu.pool_cpu_us": 180_000.0,
                     "attribution.rows": 15_000,
                     "attribution.dispatch_lock.acquires": 200,
                     "attribution.dispatch_lock.contended": 3,
                     "attribution.dispatch_lock.wait_us": 1_250.0,
                     "staging.trip.batches": 100})
    return {"counters": counters, "attribution": None}


EXPECTED = {
    "host_oncpu_pct": (100 * 480_000.0 / 700_000.0, "%"),
    "challenge_us_per_sig": (110_000.0 / 15_000, "us/sig"),
    "dispatch_us_per_sig": (60_000.0 / 15_000, "us/sig"),
    "queue_wait_us_per_sig": (150_000.0 / 15_000, "us/sig"),
    "dispatch_lock_wait_us_per_batch": (12.5, "us/batch"),
    "resolve_us_per_sig": (3_000.0 / 15_000, "us/sig"),
}


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_the_planted_value(name):
    value, unit = EXPECTED[name]
    reading = readers.read_metric(METRICS_DIR, name + ".commit", _planted())
    assert reading["unit"] == unit
    assert reading["value"] == pytest.approx(value, rel=1e-12)
    source = readers.load_metric(METRICS_DIR, name)["source"]
    assert source["kind"] == "counter_ratio"
    assert not os.path.exists(os.path.join(METRICS_DIR, name + ".py"))


def test_on_cpu_share_reads_the_threads_totals():
    source = readers.load_metric(METRICS_DIR, "host_oncpu_pct")["source"]
    assert source["num"] == [f"attribution.oncpu.{k}_cpu_us"
                             for k in ("root", "pool")]
    assert source["den"] == [f"attribution.oncpu.{k}_wall_us"
                             for k in ("root", "pool")]
    obs = _planted()
    # no stage's time enters it
    for path in [p for p in obs["counters"] if ".stage_us." in p]:
        obs["counters"][path] *= 3
    assert readers.read_metric(METRICS_DIR, "host_oncpu_pct.catchup",
                               obs)["value"] == pytest.approx(
                                   EXPECTED["host_oncpu_pct"][0])


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_without_its_paths(name):
    source = readers.load_metric(METRICS_DIR, name)["source"]
    obs = _planted()
    for path in source["num"]:
        del obs["counters"][path]
    assert readers.read_metric(METRICS_DIR, name + ".commit", obs) is None
    assert readers.read_metric(METRICS_DIR, name + ".commit",
                               {"counters": {}, "attribution": None}) is None


def test_a_parents_traced_run_reads_the_stages_alone():
    """The parent laid under these files: its tracer has the stages but
    no CPU clock and no lock counter, so those readers read nothing."""
    obs = _planted()
    obs["counters"] = {k: v for k, v in obs["counters"].items()
                       if not k.startswith(("attribution.oncpu.",
                                            "attribution.dispatch_lock."))}
    read = {name: readers.read_metric(METRICS_DIR, name + ".commit", obs)
            for name in READERS}
    assert {name for name, r in read.items() if r is None} == {
        "host_oncpu_pct", "dispatch_lock_wait_us_per_batch"}


def test_benchmark_json_appends_the_entries_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    layer = [m["name"] for m in bench["per_layer"]]
    first = layer.index("host_oncpu_pct.commit")
    assert first > layer.index("valset_hashes_per_hop.commit")
    entries = {m["name"]: m for m in bench["per_layer"][first:first + 11]}
    assert len(entries) == 11
    # what later entries append behind them reads other quantities
    assert not {n.rpartition(".")[0] for n in layer[first + 11:]} & set(READERS)
    cells = {w["name"] for w in bench["workloads"]}
    for name, entry in entries.items():
        reader, _, family = name.rpartition(".")
        assert reader in READERS
        assert entry["moves"] == {"commit": "commit_verify_ms",
                                  "catchup": "catchup_blocks_per_s"}[family]
        assert entry["unit"] == EXPECTED[reader][1]
        assert entry["source"] in ("program_span", "program_counter")
        assert set(entry["workloads"]) <= cells
        assert all(w.endswith(".catchup") == (family == "catchup")
                   for w in entry["workloads"])
    assert entries["dispatch_lock_wait_us_per_batch.commit"]["workloads"] == [
        "committee-10k-ed.mesh4", "committee-10k-mixed.commit"]
