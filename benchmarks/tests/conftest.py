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


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """A checkout-shaped directory: the repo's BENCHMARK.json, its metrics
    and traffic files, with configurations cut to 4 (+4) validators and a
    ring of 24, and every fifth operation corrupt."""
    root = str(tmp_path_factory.mktemp("root"))
    bench = _read(os.path.join(ROOT, "BENCHMARK.json"))
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
