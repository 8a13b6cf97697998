"""The four per-layer metrics `committee-10k-mixed.commit` brings (PR 28),
read from a planted `obs` as benchmarks/run.py builds it: each reads the
value it should, and reads None without failing where the program lacks
the path, the stage or the module, as a parent laid under these files does
(the driver's traced runs of every cell; PERF.md section 3)."""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import peaks, readers  # noqa: E402

METRICS_DIR = os.path.join(ROOT, "benchmarks", "metrics")
CELL = "committee-10k-mixed.commit"
KIND = "TPU v5 lite"


def _bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def planted_obs() -> dict:
    """A traced slice of 10 mixed commits: 5,120 signatures of either
    scheme each, one batch a scheme, the sr25519 ladder 8.5 ms a batch."""
    return {
        "device": {"kind": KIND},
        "slice_sigs": {"ed25519": 51_200, "sr25519": 51_200},
        "slice_wire_bytes": 12_000_000,
        "trace": {"modules": {
            "jit_verify_pallas_sr_ok": {"seconds": 0.085, "count": 10},
            "jit_verify_pallas_derived": {"seconds": 0.084, "count": 10},
            "jit_derive_challenge": {"seconds": 0.02, "count": 10}}},
        "counters": {
            "attribution.rows": 102_400,
            "attribution.stage_us.transfer": 213_000.0,
            "attribution.stage_us.fetch": 146_000.0,
            "staging.trip.batches": 20,
            "staging.trip.device_programs": 80,
            "staging.trip.blocking_waits": 30},
    }


def _sr_least_seconds(sigs: int) -> float:
    return (sigs * peaks.FIELD_MULS_PER_VERIFY["sr25519"]
            * peaks.FIELD_MUL_INT_OPS / peaks.PEAKS[KIND]["int8_ops_per_s"])


EXPECTED = {
    "sr25519_kernel_roofline.commit":
        (100.0 * _sr_least_seconds(51_200) / 0.085, "%"),
    "transfer_us_per_sig.commit": (213_000.0 / 102_400, "us/sig"),
    "fetch_wait_us_per_sig.commit": (146_000.0 / 102_400, "us/sig"),
    "blocking_waits_per_batch.commit": (1.5, "waits/batch"),
}


def _without(obs: dict, metric: str) -> list[dict]:
    """The same observation as programs that lack what the metric reads
    would leave it."""
    out = []
    if metric.startswith("sr25519_kernel_roofline"):
        # the parent's name for the shared ladder program; no trace at all;
        # a slice that verified no sr25519 signature
        renamed = json.loads(json.dumps(obs))
        mods = renamed["trace"]["modules"]
        mods["jit__verify_pallas_bench"] = mods.pop("jit_verify_pallas_sr_ok")
        out.append(renamed)
        out.append({**obs, "trace": None})
        out.append({**obs, "slice_sigs": {"ed25519": 51_200}})
        return out
    gone = {"transfer_us_per_sig.commit": "attribution.",
            "fetch_wait_us_per_sig.commit": "attribution.",
            "blocking_waits_per_batch.commit": "staging.trip."}[metric]
    out.append({**obs, "counters": {k: v for k, v in obs["counters"].items()
                                    if not k.startswith(gone)}})
    if gone == "attribution.":  # the tracer is there, the stage is not
        stage = "attribution.stage_us." + (
            "transfer" if metric.startswith("transfer") else "fetch")
        out.append({**obs, "counters": {
            k: v for k, v in obs["counters"].items() if k != stage}})
    return out


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_reads_the_planted_value(metric):
    value, unit = EXPECTED[metric]
    reading = readers.read_metric(METRICS_DIR, metric, planted_obs())
    assert reading["unit"] == unit
    assert reading["value"] == pytest.approx(value, rel=1e-12)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_reads_nothing_and_does_not_fail_on_a_parent(metric):
    for obs in _without(planted_obs(), metric):
        assert readers.read_metric(METRICS_DIR, metric, obs) is None


def test_sr25519_roofline_counts_its_own_scheme_over_its_own_module():
    """About 1.3% at the planted 8.5 ms a 5,120-signature batch, and
    under the two-scheme roofline's reading of the same slice only by what
    the ed25519 modules add: it is no share of `verify_kernel_roofline`."""
    obs = planted_obs()
    own = readers.read_metric(
        METRICS_DIR, "sr25519_kernel_roofline.commit", obs)["value"]
    both = readers.read_metric(
        METRICS_DIR, "verify_kernel_roofline.commit", obs)["value"]
    assert 1.0 < own < 2.0 and 1.0 < both < 2.0
    # twice the device time, the same work: half the share
    obs["trace"]["modules"]["jit_verify_pallas_sr_ok"]["seconds"] *= 2
    assert readers.read_metric(
        METRICS_DIR, "sr25519_kernel_roofline.commit",
        obs)["value"] == pytest.approx(own / 2)


def test_the_sr25519_program_is_found_by_both_rooflines():
    """The program's name (ops/pallas_verify.verify_pallas_sr_ok) matches
    this cell's own pattern and still the two-scheme `verify_pallas`; the
    ed25519 programs match only the latter."""
    import re

    from cometbft_tpu.ops import ed25519_kernel as EK
    from cometbft_tpu.ops import pallas_verify as PV

    own = readers.load_metric(
        METRICS_DIR, "sr25519_kernel_roofline.commit")["source"]["modules"]
    both = readers.load_metric(
        METRICS_DIR, "verify_kernel_roofline.commit")["source"]["modules"]
    module = "jit_" + PV.verify_pallas_sr_ok.__name__
    assert any(re.search(p, module) for p in own)
    assert any(re.search(p, module) for p in both)
    for hostk in (False, True):
        ed = "jit_" + EK._verify_programs(hostk)[0].__name__
        assert not any(re.search(p, ed) for p in own)
        assert any(re.search(p, ed) for p in both)
    assert not any(re.search(p, "jit__verify_pallas_bench") for p in own)


def test_benchmark_json_brings_the_cell_with_entries_alone():
    """committee-10k-mixed.commit as ISSUE 28 spells it: the configuration
    over the file that was there, one chip, `commit-serial`; the cell
    reports commit_verify_ms, setup_s, the hub cell's seventeen `.commit`
    metrics and the four new ones; the hub cell's lists are as they were."""
    from benchmarks import run

    bench = _bench()
    conf = next(c for c in bench["configs"]
                if c["name"] == "committee-10k-mixed")
    assert conf["file"] == "benchmarks/configs/committee-10k-mixed.json"
    assert conf["reduced"] == ["ring_heights"]
    assert bench["configs"][0]["name"] == "hub-150"
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert work == {"name": CELL, "config": "committee-10k-mixed",
                    "traffic": "commit-serial", "chips": 1,
                    "why": work["why"]}
    assert len(work["why"]) <= 200

    cell = run.load_cell(ROOT, CELL)
    hub = run.load_cell(ROOT, "hub-150.commit")
    assert cell.end_to_end == hub.end_to_end == ["commit_verify_ms",
                                                 "setup_s"]
    # every layer metric of the hub cell (but the share of new prefix
    # rows a derive carried, PR 34: a ring of 2 always hits the table and
    # has no new row to count), and the four of its own
    assert len(hub.per_layer) >= 17
    assert set(hub.per_layer) - {"prefix_rows_carried_pct.commit"} <= set(
        cell.per_layer)
    assert set(EXPECTED) <= set(cell.per_layer)
    # the sr25519 kernel's own roofline is this cell's alone; the three
    # of the trip may be given to any cell that has something for them
    assert "sr25519_kernel_roofline.commit" not in hub.per_layer
    assert cell.config["validators"] == {"ed25519": 5120, "sr25519": 5120}
    assert cell.config["ring_heights"] == 2
    for name in EXPECTED:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        # the cell they came with first; later cells that have something
        # for them to read come behind it
        assert entry["workloads"][0] == CELL
        assert entry["moves"] == "commit_verify_ms"
    sr = next(m for m in bench["per_layer"]
              if m["name"] == "sr25519_kernel_roofline.commit")
    assert sr["workloads"] == [CELL]  # no other cell runs the sr25519 kernel
