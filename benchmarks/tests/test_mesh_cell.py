"""`committee-10k-ed.mesh4` end to end on the CPU, over four of the forced
host devices, at a 32-validator committee: the same run_cell() the chips
run. The refusal of program_mesh.require_mesh is kept but for what no CPU
can give (shards on the Pallas program), and commits of 32 rows are made to
spread as the cell's 10,240 do (a consensus batch up to mesh.PIN_MAX_ROWS
rows is pinned to one chip): both here, in the test, by no option of the
program or of run.py. (A traced run needs a TPU's device plane in the
profile: the mesh's own metrics are read from planted observations in
tests/test_mesh_cell_readers.py and from the program's counters in
tests/test_mesh_trip.py.)"""

import os
import shutil

# four host devices, asked for before JAX starts its backend (collection
# imports this file before any test of the directory runs; the other
# rehearsals pin their mesh to one device whatever the count)
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()

import pytest  # noqa: E402

from benchmarks import run  # noqa: E402
from benchmarks.tests.conftest import ROOT, _read, _write  # noqa: E402

CELL = "committee-10k-ed.mesh4"


@pytest.fixture(scope="module")
def mesh_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("mesh_root"))
    bench = _read(os.path.join(ROOT, "BENCHMARK.json"))
    shutil.copytree(os.path.join(ROOT, "benchmarks", "metrics"),
                    os.path.join(root, "benchmarks", "metrics"))
    conf = next(c for c in bench["configs"] if c["name"] == "committee-10k-ed")
    body = _read(os.path.join(ROOT, conf["file"]))
    body["validators"] = {"ed25519": 32}
    _write(os.path.join(root, conf["file"]), body)
    rel = os.path.join("benchmarks", "traffic", "commit-serial-mesh.json")
    body = _read(os.path.join(ROOT, rel))
    body["corrupt_every"] = 3
    _write(os.path.join(root, rel), body)
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


@pytest.fixture
def four_chip_mesh(monkeypatch):
    import jax

    from benchmarks import program_mesh
    from cometbft_tpu.ops import dispatch
    from cometbft_tpu.parallel import mesh

    real = program_mesh.require_mesh

    def but_for_pallas(chips):
        with monkeypatch.context() as m:
            m.setattr(mesh.VerifyMesh, "shard_programs", staticmethod(
                lambda: {"ed25519": "pallas"}))
            return real(chips)

    if len(jax.devices()) < 4:
        pytest.skip("JAX started with fewer than four host devices")
    monkeypatch.setattr(program_mesh, "require_mesh", but_for_pallas)
    monkeypatch.setattr(mesh, "PIN_MAX_ROWS", 0)
    dispatch.reset_supervision()
    mesh._set_for_testing(mesh.VerifyMesh(devices=jax.devices()[:4]))
    yield
    mesh.reset()
    dispatch.reset_supervision()


def _numbers(result):
    return {k: v["value"] for k, v in result["compared"].items()}


def test_verdicts_equal_the_references_over_four_chips(mesh_root,
                                                       four_chip_mesh):
    from cometbft_tpu.ops import dispatch

    result = run.run_cell(mesh_root, CELL, 2**31 + 33, 15.0, False,
                          on_chip=False)
    numbers = _numbers(result)
    assert numbers["verdict_mismatches"] == 0 and numbers["errors"] == 0
    assert numbers["host_rescued_lanes"] == 0
    assert numbers["corrupt_compared"] >= 5
    assert set(result["metrics"]) == {"commit_verify_ms", "setup_s"}
    report = dispatch.health_snapshot()["mesh"]
    assert report["batches"] >= result["attempted"]
    assert report["shards_total"] == 4 * report["batches"]
    assert report["fallbacks"] == report["evictions"] == 0
    assert all(c["shards_total"] for c in report["chips"].values())


def test_the_control_is_not_correct(mesh_root, four_chip_mesh):
    result = run.run_cell(mesh_root, CELL, 11, 8.0, False,
                          entries="control_entries", on_chip=False)
    assert not result["correct"]
    assert _numbers(result)["verdict_mismatches"] >= 1


def test_a_mesh_that_stayed_off_is_refused(mesh_root, monkeypatch):
    from benchmarks import program
    from cometbft_tpu.parallel import mesh

    mesh.reset()
    with pytest.raises(program.BenchFailure):
        run.run_cell(mesh_root, CELL, 12, 5.0, False, on_chip=False)
    mesh.reset()
