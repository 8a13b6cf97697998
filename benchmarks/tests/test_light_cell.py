"""`light-500.bisect` end to end on the CPU at a small chain (24 validators,
2 rotated an epoch of 16 heights, 2,000 heights, a ring of 96 hops): the
same run_cell() the chip runs, with the cell's own data, driver, program
objects, reference and control (drivers/light_bisect.py, program_light.py,
reference/light_ref.py). By hand, like the other rehearsals."""

import os
import shutil

import pytest

from benchmarks import run
from benchmarks.tests.conftest import ROOT, _read, _write

CELL = "light-500.bisect"


@pytest.fixture(scope="module")
def light_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("light_root"))
    bench = _read(os.path.join(ROOT, "BENCHMARK.json"))
    shutil.copytree(os.path.join(ROOT, "benchmarks", "metrics"),
                    os.path.join(root, "benchmarks", "metrics"))
    conf = next(c for c in bench["configs"] if c["name"] == "light-500")
    body = _read(os.path.join(ROOT, conf["file"]))
    body["validators"] = {"ed25519": 24}
    body["heights"] = 2000
    body["drift"].update(epoch_heights=16, rotated_per_epoch=2)
    body["ring_hops"] = 96
    # the rung that is due on the CPU, so that `correct` can read true
    # here: no batch served by the host oracle
    body["guarantees"]["rung"] = {"plus": ["metrics.fallback_verifies"]}
    _write(os.path.join(root, conf["file"]), body)
    rel = os.path.join("benchmarks", "traffic", "light-bisect.json")
    body = _read(os.path.join(ROOT, rel))
    body["corrupt_every"] = 2
    _write(os.path.join(root, rel), body)
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


def _numbers(result):
    return {k: v["value"] for k, v in result["compared"].items()}


def test_every_hop_is_answered_as_the_reference_answers_it(
        light_root, device_plane, capfd):
    result = run.run_cell(light_root, CELL, 2**31 + 35, 14.0, False,
                          on_chip=False)
    out = capfd.readouterr().out
    numbers = _numbers(result)
    assert numbers.pop("corrupt_compared") >= 5
    assert numbers == {"verdict_mismatches": 0, "errors": 0,
                       "offchip_batches": 0, "host_rescued_lanes": 0}
    assert result["correct"] and result["failed"] == 0
    assert set(result["metrics"]) == {"commit_verify_ms", "setup_s"}
    assert "answered untrusted" in out


def test_the_hops_are_verify_skippings_and_the_answers_the_schedules(
        light_root, device_plane, monkeypatch):
    """What the window offered: the ring's hops in order, each answered
    with its clean answer or, where the schedule corrupted it, with the
    corrupt lane's index; bisections that need several halvings."""
    from benchmarks import check

    seen = []
    real = check.compare

    def compare(cell, records, *args):
        seen.append((cell, records))
        return real(cell, records, *args)

    monkeypatch.setattr(check, "compare", compare)
    run.run_cell(light_root, CELL, 36, 14.0, False, on_chip=False)
    (cell, records), = seen
    hops = cell.hops
    assert len(hops) == 96
    untrusted = [h for h in hops if h.verdict == "reject:untrusted"]
    accepted = [h for h in hops if h.verdict == "accept"]
    assert len(untrusted) + len(accepted) == 96 and untrusted and accepted
    # every bisection starts at the trust root, every hop goes up
    assert hops[0].trusted == 1 and all(h.new > h.trusted for h in hops)
    for r in records:
        ring_idx, lane = cell.schedule.op(r.k)
        assert (r.ring_idx, r.corrupt_lane) == (ring_idx, lane)
        hop = hops[ring_idx]
        assert r.verdict == (hop.verdict if lane is None
                             else f"reject#{lane}")
        assert lane is None or (hop.verdict == "accept"
                                and lane < hop.quorum_rows)
    assert sum(r.corrupt_lane is not None for r in records) >= 5


def test_the_control_is_not_correct(light_root, device_plane):
    result = run.run_cell(light_root, CELL, 37, 10.0, False,
                          entries="control_entries", on_chip=False)
    assert not result["correct"]
    assert _numbers(result)["verdict_mismatches"] >= 1
