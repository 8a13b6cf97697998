"""The benchmark's own tests: `pytest benchmarks/tests` by hand, on the CPU
(outside tier-1's tests/). The rehearsals run the same run_cell() the chip
runs, at a 4-validator committee, with the look for a TPU skipped here, in
the tests, and by no option of run.py."""

import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402


def _read(path):
    with open(path) as fh:
        return json.load(fh)


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(obj, fh)


def with_waiting_cells(bench: dict) -> dict:
    """BENCHMARK.json as the PR that brings the waiting cells back will
    leave it (PERF.md section 7): new entries over files that are there,
    and the cells' names added to the end-to-end metric they report."""
    bench["configs"].append({
        "name": "committee-10k-mixed",
        "file": "benchmarks/configs/committee-10k-mixed.json"})
    bench["workloads"] += [
        {"name": "committee-10k-mixed.commit",
         "config": "committee-10k-mixed",
         "traffic": "commit-serial", "chips": 1},
        {"name": "hub-150.catchup", "config": "hub-150",
         "traffic": "catchup-window8", "chips": 1}]
    for metric in bench["end_to_end"]:
        if metric["name"] == "commit_verify_ms":
            metric["workloads"].append("committee-10k-mixed.commit")
    bench["end_to_end"].append({
        "name": "catchup_blocks_per_s", "unit": "blocks/s",
        "workloads": ["hub-150.catchup"]})
    bench["per_layer"] += [
        {"name": "sched_fill_pct.catchup", "unit": "%",
         "moves": "catchup_blocks_per_s"},
        {"name": "device_idle_pct.catchup", "unit": "%",
         "moves": "catchup_blocks_per_s"}]
    return bench


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """A checkout-shaped directory: the repo's BENCHMARK.json with the
    waiting cells added, its metrics and traffic files, with configurations
    cut to 4 (+4) validators and a ring of 24, and every fifth operation
    corrupt."""
    root = str(tmp_path_factory.mktemp("root"))
    bench = with_waiting_cells(_read(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copytree(os.path.join(ROOT, "benchmarks", "metrics"),
                    os.path.join(root, "benchmarks", "metrics"))
    for conf in bench["configs"]:
        body = _read(os.path.join(ROOT, conf["file"]))
        body["validators"] = {k: 4 if v else 0
                              for k, v in body["validators"].items()}
        body["ring_heights"] = 24
        _write(os.path.join(root, conf["file"]), body)
    for traffic in {w["traffic"] for w in bench["workloads"]}:
        rel = os.path.join("benchmarks", "traffic", traffic + ".json")
        body = _read(os.path.join(ROOT, rel))
        body["corrupt_every"] = 5
        _write(os.path.join(root, rel), body)
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


@pytest.fixture(scope="session")
def device_plane():
    """One chip's worth of the CPU's devices, as the driver's machine has:
    the mesh plane stays off."""
    import jax

    from cometbft_tpu.parallel import mesh as verify_mesh

    verify_mesh._set_for_testing(
        verify_mesh.VerifyMesh(devices=jax.devices()[:1]))
    yield
    verify_mesh.reset()
