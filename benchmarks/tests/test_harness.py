"""The harness is data: cells, configurations, mixes and metrics are found
by name; a run off the chip fails and prints no result."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import peaks, readers, run
from benchmarks.tests.conftest import ROOT, _read, _write


def test_benchmark_json_names_only_files_that_exist():
    bench = _read(os.path.join(ROOT, "BENCHMARK.json"))
    for conf in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, conf["file"]))
    for work in bench["workloads"]:
        cell = run.load_cell(ROOT, work["name"])
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "drivers", cell.traffic["driver"] + ".py"))
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        assert cell.per_layer
    metrics_dir = os.path.join(ROOT, "benchmarks", "metrics")
    for metric in bench["end_to_end"] + bench["per_layer"]:
        spec = readers.load_metric(metrics_dir, metric["name"])
        assert spec["unit"] == metric["unit"]
        # a kind of readers.py, or a reader of the metric's own beside it
        assert spec["source"]["kind"] in readers.KINDS or os.path.exists(
            os.path.join(metrics_dir, readers.reader_name(
                metrics_dir, metric["name"]) + ".py"))
    moved = {m["name"] for m in bench["end_to_end"]}
    cells = [run.load_cell(ROOT, w["name"]) for w in bench["workloads"]]
    for metric in bench["per_layer"]:
        assert metric["moves"] in moved
        for cell in cells:
            assert (metric["name"] in cell.per_layer) == (
                metric["moves"] in cell.end_to_end
                and cell.name in metric.get("workloads", [cell.name]))


def test_a_later_cell_reports_the_family_readers_without_new_files(tiny_root):
    """hub-150.catchup came in by entries alone (PR 32):
    `sched_fill_pct.catchup` finds the reader file sched_fill_pct.json, and
    a per-layer metric with no `workloads` goes to every cell that reports
    what it moves."""
    bench = _read(os.path.join(tiny_root, "BENCHMARK.json"))
    commit = run.load_cell(tiny_root, "hub-150.commit")
    catchup = run.load_cell(tiny_root, "hub-150.catchup")
    mixed = run.load_cell(tiny_root, "committee-10k-mixed.commit")
    for family, moved in ((".commit", "commit_verify_ms"),
                          (".catchup", "catchup_blocks_per_s")):
        every_cell = [m["name"] for m in bench["per_layer"]
                      if m["moves"] == moved and "workloads" not in m]
        assert len(every_cell) == 10
        assert all(name.endswith(family) for name in every_cell)
        for cell in (commit, mixed, catchup):
            assert set(every_cell) <= set(cell.per_layer) or (
                moved not in cell.end_to_end
                and not set(every_cell) & set(cell.per_layer))
    assert commit.end_to_end == mixed.end_to_end == ["commit_verify_ms",
                                                     "setup_s"]
    assert catchup.end_to_end == ["catchup_blocks_per_s", "setup_s"]
    assert all(name.endswith(".catchup") for name in catchup.per_layer)
    metrics_dir = os.path.join(tiny_root, "benchmarks", "metrics")
    for name in catchup.per_layer:  # no file of its own: the family's
        assert not os.path.exists(os.path.join(metrics_dir, name + ".json"))
        assert readers.reader_name(metrics_dir, name) == name[:-len(
            ".catchup")]
    assert readers.reader_name(metrics_dir, "setup_s") == "setup_s"
    obs = {"counters": {"verify_sched.rows_total": 1200,
                        "verify_sched.lanes_total": 2048}}
    assert readers.read_metric(metrics_dir, "sched_fill_pct.catchup", obs) == {
        "value": 100 * 1200 / 2048, "unit": "%"}


def test_new_config_mix_and_metric_are_found_without_an_edit(tiny_root):
    """What a later PR does: new files and new entries, no file changed."""
    bench = _read(os.path.join(tiny_root, "BENCHMARK.json"))
    conf = _read(os.path.join(tiny_root, bench["configs"][0]["file"]))
    _write(os.path.join(tiny_root, "benchmarks/configs/hub-7.json"),
           dict(conf, name="hub-7", validators={"ed25519": 7, "sr25519": 0}))
    mix = _read(os.path.join(tiny_root,
                             "benchmarks/traffic/commit-serial.json"))
    _write(os.path.join(tiny_root, "benchmarks/traffic/commit-rare.json"),
           dict(mix, corrupt_every=7))
    _write(os.path.join(tiny_root, "benchmarks/metrics/sched_batches.json"),
           {"unit": "batches", "source": {
               "kind": "counter_sum", "plus": ["verify_sched.batches"]}})
    with open(os.path.join(tiny_root,
                           "benchmarks/metrics/ops_twice.py"), "w") as fh:
        fh.write("def read(obs, params):\n"
                 "    return params['times'] * len(obs['records'])\n")
    _write(os.path.join(tiny_root, "benchmarks/metrics/ops_twice.json"),
           {"unit": "ops",
            "source": {"kind": "own", "times": 2}})
    bench["configs"].append({"name": "hub-7",
                             "file": "benchmarks/configs/hub-7.json"})
    bench["workloads"].append({"name": "hub-7.rare", "config": "hub-7",
                               "traffic": "commit-rare", "chips": 1})
    bench["per_layer"] += [
        {"name": "sched_batches", "moves": "setup_s",
         "workloads": ["hub-7.rare"]},
        {"name": "ops_twice", "moves": "setup_s",
         "workloads": ["hub-7.rare"]}]
    _write(os.path.join(tiny_root, "BENCHMARK.json"), bench)
    cell = run.load_cell(tiny_root, "hub-7.rare")
    assert cell.config["validators"]["ed25519"] == 7
    assert cell.traffic["corrupt_every"] == 7
    assert cell.per_layer == ["sched_batches", "ops_twice"]
    obs = {"counters": {"verify_sched.batches": 3}, "records": [0] * 5}
    metrics_dir = os.path.join(tiny_root, "benchmarks", "metrics")
    assert readers.read_metric(metrics_dir, "sched_batches", obs) == {
        "value": 3, "unit": "batches"}
    assert readers.read_metric(metrics_dir, "ops_twice", obs)["value"] == 10


def test_a_reader_with_nothing_to_read_returns_nothing():
    obs = {"counters": {}, "trace": None, "attribution": None}
    assert readers.trace_idle(obs, {}) is None
    assert readers.trace_roofline(obs, {"modules": ["x"]}) is None
    assert readers.attribution(obs, {"stage": "stage"}) is None
    # a stage the program's tracer lacks (a parent's traced run)
    parent = {"rows": 15000, "stage_us": {"stage": 1.0}}
    assert readers.attribution(
        {"attribution": parent}, {"stage": "stage"}) == 1.0 / 15000
    assert readers.attribution(
        {"attribution": parent}, {"stage": "signbytes"}) is None
    assert readers.counter_ratio(obs, {"num": ["a"], "den": ["b"]}) is None


def test_roofline_refuses_an_unknown_device_kind():
    with pytest.raises(KeyError, match="no published peak"):
        peaks.roofline_seconds("TPU v9 imaginary", {"ed25519": 1.0}, 100.0)
    least, bound = peaks.roofline_seconds("TPU v5 lite", {"ed25519": 1e6}, 1e8)
    # 4,286 field multiplications x 2,048 integer operations a signature
    assert peaks.FIELD_MULS_PER_VERIFY["ed25519"] == 4286
    assert bound == "operations"
    assert least == pytest.approx(1e6 * 4286 * 2048 / 393e12)


def test_run_fails_and_prints_no_result_without_a_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "hub-150.commit", "--seed", "1",
         "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "not a TPU" in out.stderr
    for line in out.stdout.splitlines():
        assert not line.startswith("{"), line


def test_every_seed_signs_bytes_of_the_same_total_length():
    """Same sizes in another order: per scheme, the stamps (whose varint
    length follows their value) are one set for every seed and height."""
    from benchmarks import datagen
    from benchmarks.reference import commit_ref

    config = dict(_read(os.path.join(ROOT, "benchmarks/configs/hub-150.json")),
                  validators={"ed25519": 37, "sr25519": 0}, ring_heights=3)
    seen = set()
    for seed in (3, 2**31 + 11):
        vals, signers = datagen.make_validators(config, seed)
        ring = datagen.make_ring(config, vals, signers, seed)
        for commit in ring:
            nanos = sorted(stamp[1] for stamp in commit.stamps)
            assert nanos == [j * 1000 // 37 * 1_000_000 for j in range(37)]
            seen.add(sum(len(commit_ref.vote_sign_bytes(vals.chain_id, commit,
                                                        i)) for i in range(37)))
        assert [c.stamps for c in ring][0] != [c.stamps for c in ring][1]
    assert len(seen) == 1


def test_the_reference_accepts_the_signatures_the_kernels_condemn():
    """The witnesses of PERF.md section 7 item 1: OpenSSL and the plain
    reference accept every one (what the program's ladder says of them is
    the program's to repair, and is not asserted here)."""
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey)

    from benchmarks.reference import ed25519_ref

    cases = _read(os.path.join(
        ROOT, "benchmarks/tests/condemned_valid_signatures.json"))["cases"]
    assert len(cases) == 5
    for case in cases:
        pub, msg, sig = (bytes.fromhex(case[k]) for k in ("pub", "msg", "sig"))
        Ed25519PublicKey.from_public_bytes(pub).verify(sig, msg)
        assert ed25519_ref.verify_zip215(pub, msg, sig)
