"""The six per-layer metrics that read the program's own spans, counters
and program names (PR 26): each reader on what a run observes, on what a
program without the span or the name gives (nothing, and no error: the
traced run of a parent commit reads them too), and the cell's list."""

import json
import os

import pytest

from benchmarks import readers, reduce, run
from benchmarks.tests.conftest import ROOT

METRICS_DIR = os.path.join(ROOT, "benchmarks", "metrics")
HERE = os.path.dirname(os.path.abspath(__file__))

OLD_TEN = [
    "commit_verify_p50_ms.commit", "commit_verify_p95_ms.commit",
    "sched_fill_pct.commit", "host_stage_us_per_sig.commit",
    "wire_bytes_per_sig.commit", "offchip_batches.commit",
    "host_rescued_lanes.commit", "compiles_in_window.commit",
    "verify_kernel_roofline.commit", "device_idle_pct.commit"]
NEW_SIX = [
    "sign_bytes_us_per_sig.commit", "commit_rows_us_per_sig.commit",
    "host_unattributed_pct.commit", "gc_pause_us_per_sig.commit",
    "gc_full_collections.commit", "derive_device_us_per_batch.commit"]
FROM_THE_PROGRAM = NEW_SIX[:5]


def test_the_hub_cell_reports_the_six_beside_the_ten():
    """In BENCHMARK.json's order; later PRs append after them."""
    assert run.load_cell(ROOT, "hub-150.commit").per_layer[:16] == (
        OLD_TEN + NEW_SIX)


def test_a_later_commit_cell_is_not_held_to_them(tiny_root):
    """They list their cells: a commit cell that a later PR adds reports
    the ten that list none, and these only once it is appended to their
    lists (as PR 28 appended the mixed cell)."""
    bench_path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(bench_path) as fh:
        kept = fh.read()
    bench = json.loads(kept)
    bench["workloads"].append({
        "name": "hub-150.later", "config": "hub-150",
        "traffic": "commit-serial", "chips": 1})
    for metric in bench["end_to_end"]:
        if metric["name"] == "commit_verify_ms":
            metric["workloads"].append("hub-150.later")
    try:
        with open(bench_path, "w") as fh:
            json.dump(bench, fh)
        assert run.load_cell(tiny_root, "hub-150.later").per_layer == OLD_TEN
    finally:
        with open(bench_path, "w") as fh:
            fh.write(kept)


def _recorded_slice() -> dict:
    with open(os.path.join(HERE, "trace_slice.json")) as fh:
        return reduce.reduce_planes(json.load(fh))


def test_derive_time_a_batch_on_the_recorded_slice_by_hand():
    """trace_slice.json is of PR 25's program, whose derive program was
    one of two modules called jit_f: three executions of ~477 us (derive)
    and three of ~4.5 us. By hand: 477,543 + 477,323 + 477,352 + 4,483 +
    4,600 + 4,480 = 1,445,781 ns over 6 executions."""
    obs = {"trace": _recorded_slice()}
    reading = readers._read(
        METRICS_DIR, "derive_device_us_per_batch",
        {"modules": ["^jit_f$"]}, obs)
    assert reading == pytest.approx(1445.781 / 6)
    # one pattern, one module: the verify kernel's three executions
    assert readers._read(
        METRICS_DIR, "derive_device_us_per_batch",
        {"modules": ["verify_pallas"]}, obs) == pytest.approx(
            (367.152 + 367.207 + 367.145) / 3)


def test_derive_time_reads_nothing_where_nothing_matches():
    """The metric's own pattern on the parent's trace (no module of that
    name), and a run without a trace."""
    name = "derive_device_us_per_batch.commit"
    assert readers.read_metric(
        METRICS_DIR, name, {"trace": _recorded_slice()}) is None
    assert readers.read_metric(METRICS_DIR, name, {"trace": None}) is None
    renamed = _recorded_slice()
    renamed["modules"]["jit_derive_challenge"] = {
        "count": 3, "seconds": 1432.218e-6}
    assert readers.read_metric(METRICS_DIR, name, {"trace": renamed}) == {
        "value": pytest.approx(1432.218 / 3), "unit": "us/batch"}


class _NoProfiler:
    """In TraceSlice's place: the CPU has no device plane to trace."""

    state = "waiting"

    def __init__(self, *_a, **_kw):
        pass

    def tick(self, _elapsed):
        pass

    def stop(self):
        pass


def test_a_traced_rehearsal_reads_the_five_from_the_program(
        tiny_root, device_plane, monkeypatch):
    """run_cell as a `--trace 1` run makes it, on the CPU at a 4-validator
    committee and without the profiler: the program's tracer is on for
    the window, and the five readers find their paths in the counters."""
    monkeypatch.setattr(run, "TraceSlice", _NoProfiler)
    result = run.run_cell(tiny_root, "hub-150.commit", 2**31 + 26, 3.0,
                          True, on_chip=False)
    metrics = result["metrics"]
    assert set(FROM_THE_PROGRAM) <= set(metrics)
    assert "derive_device_us_per_batch.commit" not in metrics  # no trace
    assert "host_stage_us_per_sig.commit" in metrics
    assert metrics["sign_bytes_us_per_sig.commit"]["value"] > 0
    assert metrics["commit_rows_us_per_sig.commit"]["value"] > 0
    assert 0 < metrics["host_unattributed_pct.commit"]["value"] < 100
    assert metrics["gc_pause_us_per_sig.commit"]["value"] >= 0
    collections = metrics["gc_full_collections.commit"]["value"]
    assert collections == int(collections) >= 0
    assert {m: metrics[m]["unit"] for m in FROM_THE_PROGRAM} == {
        "sign_bytes_us_per_sig.commit": "us/sig",
        "commit_rows_us_per_sig.commit": "us/sig",
        "host_unattributed_pct.commit": "%",
        "gc_pause_us_per_sig.commit": "us/sig",
        "gc_full_collections.commit": "collections"}


@pytest.mark.parametrize("name", FROM_THE_PROGRAM)
def test_a_program_without_the_stage_reads_nothing(name):
    """The counters of the parent's traced run: its attribution has seven
    stages and no collector counts."""
    parent = {f"attribution.stage_us.{s}": 1.0 for s in (
        "queue", "stage", "transfer", "challenge", "compute", "fetch",
        "resolve")}
    parent.update({"attribution.rows": 15000, "attribution.total_us": 7.0})
    assert readers.read_metric(
        METRICS_DIR, name, {"counters": parent}) is None


def test_kind_attribution_reads_nothing_on_the_parents_traced_run():
    """The check lays a PR's benchmark files over the parent's checkout
    for its traced runs and run.py does not catch a reader's error: kind
    `attribution` reads nothing for a stage the program lacks (until PR 32
    it raised, which is why the span-fed readers are `counter_ratio` over
    the flattened `attribution.*` paths)."""
    parent = {"rows": 15000, "stage_us": {s: 1.0 for s in (
        "queue", "stage", "transfer", "challenge", "compute", "fetch",
        "resolve")}}
    assert readers.attribution(
        {"attribution": parent}, {"stage": "stage"}) == 1.0 / 15000
    assert readers.attribution(
        {"attribution": parent}, {"stage": "signbytes"}) is None


def test_the_readers_arithmetic():
    counters = {"attribution.stage_us.node": 50.0,
                "attribution.stage_us.signbytes": 3000.0,
                "attribution.stage_us.collect": 1500.0,
                "attribution.stage_us.gc": 125000.0,
                "attribution.total_us": 1000.0,
                "attribution.rows": 15000,
                "attribution.gc_collections.gen2": 2}
    values = {name: readers.read_metric(
        METRICS_DIR, name, {"counters": counters})["value"]
        for name in FROM_THE_PROGRAM}
    assert values == {
        "sign_bytes_us_per_sig.commit": pytest.approx(0.2),
        "commit_rows_us_per_sig.commit": pytest.approx(0.1),
        "host_unattributed_pct.commit": pytest.approx(5.0),
        "gc_pause_us_per_sig.commit": pytest.approx(125000.0 / 15000),
        "gc_full_collections.commit": 2}
