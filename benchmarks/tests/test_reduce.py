"""The reduction from a trace to numbers, on a recorded slice small enough
to work out by hand (trace_slice.json: three verify_commit calls cut from a
traced run of hub-150.commit on the v5e, PR 25; device operations under
20 us and host events under 300 us left out)."""

import json
import os

import pytest

from benchmarks import reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def _planes(events_by_line, host):
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": name, "events": evs}
            for name, evs in events_by_line.items()]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": host}]},
    ]


def test_busy_modules_and_gaps_by_hand():
    # window: the annotations span 1,000 .. 11,000 ns
    planes = _planes(
        {"XLA Modules": [["jit_f(11)", 2_000, 1_000],
                         ["jit_f(11)", 2_500, 1_000],   # overlaps: union
                         ["jit__verify_pallas_bench(7)", 6_000, 2_000],
                         ["jit_late(3)", 10_500, 4_000]],  # cut at 11,000
         "XLA Ops": [["%k.1 = s32[] custom-call(...)", 6_000, 1_500],
                     ["%add.2 = s32[] add(...)", 2_000, 500]]},
        [["bench.verify_commit", 1_000, 5_000],
         ["np.asarray(jax.Array)", 3_600, 2_000],
         ["bench.verify_commit", 7_000, 4_000]])
    out = reduce.reduce_planes(planes)
    assert out["window_s"] == pytest.approx(10_000e-9)
    # busy: [2000,3500) + [6000,8000) + [10500,11000) = 4,000 ns
    assert out["busy_s"] == pytest.approx(4_000e-9)
    assert out["modules"]["jit_f"] == {"count": 2, "seconds": 2_000e-9}
    assert out["modules"]["jit__verify_pallas_bench"]["seconds"] == (
        pytest.approx(2_000e-9))
    assert out["modules"]["jit_late"]["seconds"] == pytest.approx(500e-9)
    assert out["device_ops"][0] == ["k.1", pytest.approx(1_500e-9)]
    # gaps: [1000,2000) mid 1500 in verify_commit; [3500,6000) mid 4750 in
    # np.asarray (the innermost); [8000,10500) mid 9250 in verify_commit
    gaps = dict(out["idle_gaps"])
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(2_500e-9)
    assert gaps["bench.verify_commit"] == pytest.approx(3_500e-9)
    seconds, count = reduce.module_seconds(out, ["verify_pallas"])
    assert (count, seconds) == (1, pytest.approx(2_000e-9))


def test_a_trace_without_annotations_or_device_is_refused():
    with pytest.raises(ValueError, match="annotations"):
        reduce.reduce_planes(_planes({"XLA Modules": []}, []))
    with pytest.raises(ValueError, match="device plane"):
        reduce.reduce_planes([{"name": "/host:CPU", "lines": [
            {"name": "main", "events": [["bench.x", 0, 10]]}]}])


def test_recorded_slice_of_the_chip():
    with open(os.path.join(HERE, "trace_slice.json")) as fh:
        planes = json.load(fh)
    out = reduce.reduce_planes(planes)
    with open(os.path.join(HERE, "trace_slice.expected.json")) as fh:
        expected = json.load(fh)
    assert out["window_s"] == pytest.approx(expected["window_s"])
    assert out["busy_s"] == pytest.approx(expected["busy_s"])
    assert 100 * (1 - out["busy_s"] / out["window_s"]) == pytest.approx(
        expected["idle_pct"])
    for name, seconds in expected["module_seconds"].items():
        assert out["modules"][name]["seconds"] == pytest.approx(seconds)
    assert out["modules"]["jit__verify_pallas_bench"]["count"] == 3
