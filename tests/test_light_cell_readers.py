"""The four `node path` metrics `light-500.bisect` brings (PR 35), read
from a planted `obs` as benchmarks/run.py builds it: each reads the value it
should, and reads None without failing where the program lacks the counter
or the stage, as the parent laid under these files does (its tracer has no
stage `header`, no counts of hops, joined rows or set hashes). Also the
cell's entries in BENCHMARK.json and its files."""

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
CELL = "light-500.bisect"


def planted_obs() -> dict:
    """A slice of 100 hops, 55 of them answered untrusted: 45 hops of 501
    rows, 167 of each joined; a header check of 1.2 ms a hop; one root in
    the header check of every hop and one more where a hop verifies."""
    rows = 45 * 501
    return {
        "attribution": {"rows": rows, "stage_us": {
            "header": 120_000.0, "stage": 9_000.0}},
        "counters": {
            "attribution.light.hops": 100,
            "attribution.light.hops_untrusted": 55,
            "attribution.trusting_rows.joined": 45 * 167,
            "attribution.trusting_rows.scanned": 0,
            "attribution.valset.hashes": 145}}


EXPECTED = {
    "light_header_us_per_sig.commit": (120_000.0 / (45 * 501), "us/sig"),
    "light_untrusted_hops_pct.commit": (55.0, "%"),
    "trusting_rows_joined_pct.commit": (100.0, "%"),
    "valset_hashes_per_hop.commit": (1.45, "hashes/hop"),
}


def without(metric: str) -> dict:
    """The planted observation as a program without the metric's source
    gives it."""
    obs = planted_obs()
    if metric == "light_header_us_per_sig.commit":
        del obs["attribution"]["stage_us"]["header"]
        return obs
    lacks = {"light_untrusted_hops_pct.commit": "attribution.light.",
             "trusting_rows_joined_pct.commit": "attribution.trusting_rows.",
             "valset_hashes_per_hop.commit": "attribution.valset."}[metric]
    obs["counters"] = {k: v for k, v in obs["counters"].items()
                       if not k.startswith(lacks)}
    return obs


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_reads_the_planted_value(metric):
    value, unit = EXPECTED[metric]
    reading = readers.read_metric(METRICS_DIR, metric, planted_obs())
    assert reading["unit"] == unit
    assert reading["value"] == pytest.approx(value, rel=1e-12)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_reads_nothing_and_does_not_fail_on_a_parent(metric):
    assert readers.read_metric(METRICS_DIR, metric, without(metric)) is None
    assert readers.read_metric(
        METRICS_DIR, metric, {"counters": {}, "attribution": None}) is None


def test_the_scan_reads_nought_joined():
    obs = planted_obs()
    obs["counters"].update({"attribution.trusting_rows.joined": 0,
                            "attribution.trusting_rows.scanned": 7515})
    assert readers.read_metric(
        METRICS_DIR, "trusting_rows_joined_pct.commit", obs)["value"] == 0.0


def test_the_readers_are_of_kinds_the_harness_has():
    kinds = {}
    for metric in EXPECTED:
        spec = readers.load_metric(METRICS_DIR, metric)
        kinds[metric] = spec["source"]["kind"]
        assert spec["source"]["kind"] in readers.KINDS
        assert not os.path.exists(os.path.join(
            METRICS_DIR, readers.reader_name(METRICS_DIR, metric) + ".py"))
    assert kinds == {
        "light_header_us_per_sig.commit": "attribution",
        "light_untrusted_hops_pct.commit": "counter_ratio",
        "trusting_rows_joined_pct.commit": "counter_ratio",
        "valset_hashes_per_hop.commit": "counter_ratio"}
    assert readers.load_metric(
        METRICS_DIR, "light_header_us_per_sig.commit")["source"][
            "stage"] == "header"


def test_benchmark_json_brings_the_cell_with_entries_alone():
    from benchmarks import run

    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    conf = next(c for c in bench["configs"] if c["name"] == "light-500")
    assert conf["file"] == "benchmarks/configs/light-500.json"
    assert conf["reduced"] == ["materialised_heights", "ring_hops"]
    assert conf["source"].startswith("BASELINE.json configs[3] ")
    assert len(conf["source"]) <= 200 and len(conf["why"]) <= 200
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert work == {"name": CELL, "config": "light-500",
                    "traffic": "light-bisect", "chips": 1,
                    "why": work["why"]}
    assert len(work["why"]) <= 200
    # the cell came after the four that were there
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) == 4
    assert [w["chips"] for w in bench["workloads"]][:5] == [1, 1, 1, 4, 1]
    commit_ms = next(m for m in bench["end_to_end"]
                     if m["name"] == "commit_verify_ms")
    assert commit_ms["workloads"][:3] == [
        "hub-150.commit", "committee-10k-mixed.commit",
        "committee-10k-ed.mesh4"]
    assert CELL in commit_ms["workloads"]
    # the four: in the layer `node path`, for this cell FIRST (a later
    # cell may be appended), after everything that was there
    layer = [m["name"] for m in bench["per_layer"]]
    first = layer.index("light_header_us_per_sig.commit")
    assert first > layer.index("prefix_rows_carried_pct.catchup")
    assert layer[first:first + 4] == [
        "light_header_us_per_sig.commit", "light_untrusted_hops_pct.commit",
        "trusting_rows_joined_pct.commit", "valset_hashes_per_hop.commit"]
    for entry in bench["per_layer"][first:first + 4]:
        assert entry["layer"] == "node path"
        assert entry["workloads"][0] == CELL
        assert entry["moves"] == "commit_verify_ms"
        assert entry["unit"] == EXPECTED[entry["name"]][1]
    better = {m["name"]: m["better"] for m in bench["per_layer"]}
    assert better["trusting_rows_joined_pct.commit"] == "higher"
    assert better["light_untrusted_hops_pct.commit"] == "lower"

    cell = run.load_cell(ROOT, CELL)
    hub = run.load_cell(ROOT, "hub-150.commit")
    assert cell.chips == 1
    assert cell.end_to_end == ["commit_verify_ms", "setup_s"]
    # every `.commit` metric of the hub cell but the prefix table's (the
    # ring's heights fit the table: a window brings it no new row, so
    # that reader finds nothing here), the trip's three that list their
    # cells, and the four
    assert set(cell.per_layer) == (
        set(hub.per_layer) - {"prefix_rows_carried_pct.commit"}
        | set(EXPECTED) | {
            "transfer_us_per_sig.commit", "fetch_wait_us_per_sig.commit",
            "blocking_waits_per_batch.commit"})
    assert cell.driver.__name__ == "benchmarks.drivers.light_bisect"


def test_the_configuration_states_the_deployment():
    from benchmarks import run

    config = run.load_cell(ROOT, CELL).config
    assert config["validators"] == {"ed25519": 500}
    assert (config["chain_id"], config["heights"], config["voting_power"]) == (
        "light-500", 100_000, 10)
    assert config["drift"]["epoch_heights"] == 256
    assert config["drift"]["rotated_per_epoch"] == 8
    light = config["light"]
    assert (light["trust_root_height"], light["trusting_period_s"],
            light["max_clock_drift_s"], light["trust_level"],
            light["pivot"]) == (1, 1_209_600, 10, [1, 3], [1, 2])
    assert config["timestamps"]["block_seconds"] == 6
    assert config["ring_hops"] == 512
    assert set(config["reduced"]) == {"materialised_heights", "ring_hops"}
    assert "ErrNewValSetCantBeTrusted" in config["guarantees"]["verdicts"]
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "hub-150.json")) as fh:
        hub = json.load(fh)
    assert config["guarantees"]["rung"] == hub["guarantees"]["rung"]


def test_the_traffic_has_the_issues_parameters():
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "light-bisect.json")) as fh:
        mix = json.load(fh)
    assert mix["driver"] == "light_bisect"
    assert (mix["loop"], mix["callers"]) == ("closed", 1)
    assert (mix["corrupt_every"], mix["check_clean_sample"],
            mix["trace_slice_s"], mix["trace_slice_ticks"]) == (
                24, 24, 5, 100)


def test_the_driver_brings_every_seam_and_the_reference_nothing_of_the_program():
    from benchmarks.drivers import commit, light_bisect

    seams = {"make_data", "build_program_objects", "entries",
             "control_entries", "reference_verdicts", "sigs_of"}
    assert {s for s in seams if hasattr(light_bisect, s)} == seams
    assert issubclass(light_bisect.Driver, commit.Driver)
    assert light_bisect.Driver.window is commit.Driver.window
    for rel in ("benchmarks/reference/light_ref.py",
                "benchmarks/drivers/light_bisect.py"):
        with open(os.path.join(ROOT, rel)) as fh:
            source = fh.read()
        assert "cometbft_tpu" not in source.replace(
            "imports nothing of the program", "")
        assert "import jax" not in source.split("class Driver")[0]


def test_the_schedule_corrupts_every_24th_accepted_hop_in_a_quorum_lane():
    from benchmarks.drivers.light_bisect import Hop, HopSchedule

    hops = [Hop(1, 50_000, "reject:untrusted", 0, 0),
            Hop(1, 25_000, "reject:untrusted", 0, 0),
            Hop(1, 12_500, "accept", 167, 334),
            Hop(12_500, 50_000, "reject:untrusted", 0, 0),
            Hop(12_500, 31_250, "accept", 167, 334)]
    for seed in (1, 2**31 + 35):
        schedule = HopSchedule({"corrupt_every": 24}, hops, seed)
        ops = [schedule.op(k) for k in range(5 * 24 * 10)]
        assert [i for i, _lane in ops] == list(range(5)) * 240
        accepted = [lane for i, lane in ops if hops[i].verdict == "accept"]
        assert all(lane is None for i, lane in ops
                   if hops[i].verdict != "accept")
        corrupt = [k for k, lane in enumerate(accepted) if lane is not None]
        assert len(corrupt) == len(accepted) // 24 == 20
        assert {b - a for a, b in zip(corrupt, corrupt[1:])} == {24}
        assert corrupt[0] == (-schedule.phase) % 24
        lanes = [accepted[k] for k in corrupt]
        assert all(0 <= lane < 334 for lane in lanes)
        # a golden-ratio stride: any five in a row reach every third
        for at in range(len(lanes) - 4):
            assert {lane * 3 // 334 for lane in lanes[at:at + 5]} == {0, 1, 2}
