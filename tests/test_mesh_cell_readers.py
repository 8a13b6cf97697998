"""The four `mesh` layer metrics `committee-10k-ed.mesh4` brings (PR 33),
read from a planted `obs` as benchmarks/run.py builds it: each reads the
value it should, and reads None without failing where the program lacks
the counter or the stage, as the parent laid under these files does (its
mesh has no `shards_total`, its tracer no stage `join`). Also the cell's
entries in BENCHMARK.json, its files, and the seam that refuses a run on a
mesh that stayed off (benchmarks/program_mesh.py)."""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import readers  # noqa: E402

METRICS_DIR = os.path.join(ROOT, "benchmarks", "metrics")
CELL = "committee-10k-ed.mesh4"


def planted_obs() -> dict:
    """A window of 100 commits of 10,240 rows, each five shards of 2,048
    lanes; 300 us of join wait a commit; one shard redispatched once."""
    return {"counters": {
        "mesh.batches": 100, "mesh.rows_total": 1_024_000,
        "mesh.shards_total": 501, "mesh.lanes_total": 501 * 2048,
        "mesh.fallbacks": 0, "mesh.redispatched_batches": 1,
        "mesh.evictions": 0, "mesh.readmissions": 0,
        "attribution.rows": 1_024_000,
        "attribution.stage_us.join": 30_000.0,
        "attribution.stage_us.fetch": 90_000.0}}


EXPECTED = {
    "mesh_shards_per_batch.commit": (5.01, "shards/batch"),
    "mesh_lane_fill_pct.commit": (100.0 * 1_024_000 / (501 * 2048), "%"),
    "mesh_join_wait_us_per_sig.commit": (30_000.0 / 1_024_000, "us/sig"),
    "mesh_faults.commit": (1, "events"),
}

# what each metric reads, so: what a program without it lacks
READS = {
    "mesh_shards_per_batch.commit": "mesh.shards_total",
    "mesh_lane_fill_pct.commit": "mesh.lanes_total",
    "mesh_join_wait_us_per_sig.commit": "attribution.stage_us.join",
    "mesh_faults.commit": "mesh.",
}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_reads_the_planted_value(metric):
    value, unit = EXPECTED[metric]
    reading = readers.read_metric(METRICS_DIR, metric, planted_obs())
    assert reading["unit"] == unit
    assert reading["value"] == pytest.approx(value, rel=1e-12)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_reads_nothing_and_does_not_fail_on_a_parent(metric):
    obs = planted_obs()
    obs["counters"] = {k: v for k, v in obs["counters"].items()
                       if not k.startswith(READS[metric])}
    assert readers.read_metric(METRICS_DIR, metric, obs) is None
    assert readers.read_metric(METRICS_DIR, metric, {"counters": {}}) is None


def test_a_sound_window_reads_no_fault():
    obs = planted_obs()
    obs["counters"]["mesh.redispatched_batches"] = 0
    assert readers.read_metric(
        METRICS_DIR, "mesh_faults.commit", obs)["value"] == 0


def test_benchmark_json_brings_the_cell_with_entries_alone():
    from benchmarks import run

    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    # by name and by place: later PRs append after them
    conf = bench["configs"][2]
    assert conf["name"] == "committee-10k-ed"
    assert conf["file"] == "benchmarks/configs/committee-10k-ed.json"
    assert conf["reduced"] == ["ring_heights"]
    work = bench["workloads"][3]
    assert work == {"name": CELL, "config": "committee-10k-ed",
                    "traffic": "commit-serial-mesh", "chips": 4,
                    "why": work["why"]}
    assert len(work["why"]) <= 200
    # one four-chip cell of the first four
    assert [w["chips"] for w in bench["workloads"]][:4] == [1, 1, 1, 4]
    # the four came last (later PRs append after them), in the layer
    # `mesh`, for this cell alone
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("mesh_shards_per_batch.commit")
    assert names[first:first + 4] == [
        "mesh_shards_per_batch.commit", "mesh_lane_fill_pct.commit",
        "mesh_join_wait_us_per_sig.commit", "mesh_faults.commit"]
    for entry in bench["per_layer"][first:first + 4]:
        assert entry["layer"] == "mesh" and entry["workloads"] == [CELL]
        assert entry["moves"] == "commit_verify_ms"
        assert entry["unit"] == EXPECTED[entry["name"]][1]

    cell = run.load_cell(ROOT, CELL)
    mixed = run.load_cell(ROOT, "committee-10k-mixed.commit")
    assert cell.chips == 4
    assert cell.end_to_end == ["commit_verify_ms", "setup_s"]
    # every `.commit` layer metric of the mixed cell but the sr25519
    # kernel's own roofline, and the four of the mesh
    assert set(cell.per_layer) == (
        set(mixed.per_layer) - {"sr25519_kernel_roofline.commit"}
        | set(EXPECTED))
    assert cell.config["validators"] == {"ed25519": 10240}
    assert cell.config["ring_heights"] == 8
    assert cell.config["chain_id"] == "committee-10k-ed"
    rung = cell.config["guarantees"]["rung"]
    assert {"mesh.fallbacks", "mesh.redispatched_batches",
            "mesh.evictions"} <= set(rung["plus"])
    assert rung["minus"] == ["supervisors.pallas.ed25519.successes"]


def test_traffic_is_commit_serial_number_for_number():
    def traffic(name):
        with open(os.path.join(ROOT, "benchmarks", "traffic",
                               name + ".json")) as fh:
            return json.load(fh)

    serial, mesh = traffic("commit-serial"), traffic("commit-serial-mesh")
    assert mesh["driver"] == "commit_mesh"
    assert set(serial) == set(mesh)
    for key, value in serial.items():
        if not isinstance(value, str):
            assert mesh[key] == value, key
    assert (mesh["corrupt_every"], mesh["check_clean_sample"],
            mesh["trace_slice_s"], mesh["trace_slice_ticks"]) == (
                24, 24, 5, 100)


def test_the_driver_is_commits_with_one_seam():
    from benchmarks.drivers import commit, commit_mesh

    assert commit_mesh.Driver is commit.Driver
    seams = {"make_data", "build_program_objects", "entries",
             "control_entries", "reference_verdicts", "sigs_of"}
    assert {s for s in seams if hasattr(commit_mesh, s)} == {
        "build_program_objects"}


class TestRequireMesh:
    """benchmarks/program_mesh.require_mesh against the program's own
    report, on the forced host devices."""

    @pytest.fixture(autouse=True)
    def _mesh(self):
        import jax

        from cometbft_tpu.ops import dispatch
        from cometbft_tpu.parallel import mesh

        dispatch.reset_supervision()
        mesh.reset()
        mesh.configure(enabled=True, min_devices=2, placement="class_aware")
        mesh._set_for_testing(mesh.VerifyMesh(jax.devices("cpu")[:4]))
        yield mesh
        mesh.reset()
        mesh.configure(enabled=True, min_devices=2, placement="class_aware")
        dispatch.reset_supervision()

    def test_refuses_a_mesh_whose_shards_do_not_ride_pallas(self):
        from benchmarks import program, program_mesh

        # four live chips, but no TPU here: the shards would run XLA
        with pytest.raises(program.BenchFailure, match="Pallas"):
            program_mesh.require_mesh(4)

    def test_accepts_pallas_shards_on_as_many_live_chips(self, monkeypatch):
        from benchmarks import program, program_mesh

        from cometbft_tpu.ops import ed25519_kernel as EK

        monkeypatch.setattr(EK, "_use_pallas", True)
        report = program_mesh.require_mesh(4)
        assert report["live"] == 4 and report["shards_total"] == 0
        with pytest.raises(program.BenchFailure, match="4 live chips|of 2"):
            program_mesh.require_mesh(2)

    def test_refuses_a_program_without_the_report(self, monkeypatch, _mesh):
        from benchmarks import program, program_mesh

        # the parent: a mesh that reports nothing of its shards' program
        real = _mesh.VerifyMesh.health

        def parents(self):
            out = real(self)
            out.pop("shard_program")
            return out

        monkeypatch.setattr(_mesh.VerifyMesh, "health", parents)
        with pytest.raises(program.BenchFailure, match="Pallas"):
            program_mesh.require_mesh(4)

    def test_refuses_when_the_mesh_is_off(self, _mesh):
        from benchmarks import program, program_mesh

        _mesh.configure(enabled=False)
        with pytest.raises(program.BenchFailure, match="not active"):
            program_mesh.require_mesh(4)
