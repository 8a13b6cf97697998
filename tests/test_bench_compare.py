"""Bench regression sentinel tests (ISSUE 8): direction-aware thresholds,
snapshot-shape handling (driver records with parsed=null tails), and the
injected-regression self-test over synthetic driver snapshots of the two
shapes a driver has shipped (with `parsed`; `parsed: null` with a
front-truncated tail). perf-marked (tier-1-safe, selectable via
`pytest -m perf` as the fast perf smoke)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from tools import bench_compare as bc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _record(**overrides) -> dict:
    rec = {
        "metric": "ed25519_verify_throughput",
        "value": 800_000.0,
        "unit": "sigs/sec/chip (device-bound)",
        "detail": {
            "device_sigs_per_s": 800_000.0,
            "device_compute_ms_per_batch": 12.8,
            "stream_sigs_per_s": 100_000.0,
            "fetch_bytes_happy_path": 8,
            "staging_us_per_row": {"ed25519": 0.7, "sr25519": 2.0},
            "sched": {"fill_ratio_mean": 0.9},
            "a_note": "strings are not metrics",
            "runs": [1.0, 2.0],
        },
    }
    for k, v in overrides.items():
        rec["detail"][k] = v
    return rec


def _driver_snapshot(tmp_path, parsed: bool) -> str:
    """A driver snapshot written into tmp_path, in one of the two shapes
    drivers have shipped: {n, cmd, rc, tail, parsed} with the parsed
    record, or parsed=null and a tail whose FRONT was truncated away
    mid-token (the tail keeps the end of the bench's one JSON line)."""
    rec = _record(sr25519_device_compute_ms=1.99,
                  blocksync_blocks_per_s=25.1)
    rec["value"] = 804844.9
    rec["detail"]["device_compute_ms_per_batch"] = 12.72
    line = json.dumps(rec)
    progress = "[bench +  123.2s] bench_blocksync\n"
    doc = {"n": 4 if parsed else 5, "cmd": "python bench.py", "rc": 0,
           "tail": progress + line if parsed
           else line[line.index('"device_compute_ms_per_batch"') + 9:],
           "parsed": rec if parsed else None}
    path = tmp_path / ("with_parsed.json" if parsed else "null_parsed.json")
    path.write_text(json.dumps(doc))
    return str(path)


class TestFlatten:
    def test_nested_numeric_leaves(self):
        flat = bc.flatten(_record())
        assert flat["value"] == 800_000.0
        assert flat["staging_us_per_row.ed25519"] == 0.7
        assert flat["sched.fill_ratio_mean"] == 0.9
        assert "a_note" not in flat
        assert "runs" not in flat  # lists are not comparable scalars


class TestDirectionAwareCompare:
    def test_identical_passes(self):
        v = bc.compare(_record(), _record())
        assert v["verdict"] == "pass"
        assert v["regressions"] == []
        assert v["tracked"] > 0

    def test_throughput_drop_fails_and_rise_passes(self):
        old = _record()
        worse = _record()
        worse["value"] = 500_000.0  # -37.5% vs 20% threshold
        v = bc.compare(old, worse)
        assert v["verdict"] == "fail"
        assert "value" in v["regressions"]
        assert v["metrics"]["value"]["verdict"] == "fail"
        # the same delta as an improvement must PASS (direction-aware)
        assert bc.compare(worse, old)["verdict"] == "pass"

    def test_latency_rise_fails_and_drop_passes(self):
        old = _record()
        worse = _record(device_compute_ms_per_batch=20.0)  # +56%
        v = bc.compare(old, worse)
        assert "device_compute_ms_per_batch" in v["regressions"]
        assert bc.compare(worse, old)["verdict"] == "pass"

    def test_wire_bound_metrics_never_fail(self):
        old = _record(blocksync_blocks_per_s=30.0)
        worse = _record(blocksync_blocks_per_s=3.0)  # -90%, wire-bound
        v = bc.compare(old, worse)
        assert v["verdict"] == "pass"
        row = v["metrics"]["blocksync_blocks_per_s"]
        assert row["verdict"] == "info"
        assert "wire-bound" in row["why_info"]

    def test_stream_sigs_promoted_to_enforced_higher_better(self):
        """stream_sigs_per_s graduated from WIRE_BOUND (ISSUE 20): with
        device-side challenge derivation the stream is no longer
        send-bound, so a drop past 50% FAILS, the same delta as an
        improvement passes, and the verdict row carries the promotion
        rationale (why) so a failing run explains its own contract."""
        assert "stream_sigs_per_s" not in bc.WIRE_BOUND
        old = _record()  # stream_sigs_per_s=100_000
        worse = _record(stream_sigs_per_s=40_000.0)  # -60% vs 50%
        v = bc.compare(old, worse)
        assert v["verdict"] == "fail"
        assert "stream_sigs_per_s" in v["regressions"]
        row = v["metrics"]["stream_sigs_per_s"]
        assert row["direction"] == bc.HIGHER
        assert "promoted from wire-bound" in row["why"]
        assert bc.compare(worse, old)["verdict"] == "pass"
        # within the wide threshold: link RTT wiggle still tolerated
        v2 = bc.compare(old, _record(stream_sigs_per_s=60_000.0))  # -40%
        assert v2["metrics"]["stream_sigs_per_s"]["verdict"] == "pass"

    def test_stream_sentinel_self_test_case(self):
        """--self-test contract on a stream-shaped record: an injected
        stream-throughput regression is flagged; the identical snapshot
        and the improvement direction are not."""
        rec = _record()
        worse, metric, pct = bc.inject_regression(
            rec, metric="stream_sigs_per_s")
        assert metric == "stream_sigs_per_s" and pct > 50.0
        assert worse["detail"]["stream_sigs_per_s"] < 100_000.0  # HIGHER
        caught = bc.compare(rec, worse)
        assert caught["verdict"] == "fail"
        assert metric in caught["regressions"]
        assert bc.compare(rec, rec)["verdict"] == "pass"
        assert bc.compare(worse, rec)["verdict"] == "pass"


class TestAbsoluteWireBounds:
    """The device-challenge wire-format ceiling: steady-state bytes/sig
    must stay <= 82 in any snapshot that shows device-derived lanes —
    an ABSOLUTE bound on the new snapshot, not a relative diff."""

    def test_bound_fails_over_ceiling_with_evidence(self):
        new = _record(wire={"steady_state_bytes_per_sig": 91.0},
                      challenge={"lanes_device": 1024.0})
        v = bc.compare(_record(), new)
        assert v["verdict"] == "fail"
        assert "bound:wire.steady_state_bytes_per_sig" in v["regressions"]
        row = v["bounds"]["wire.steady_state_bytes_per_sig"]
        assert row["verdict"] == "fail"
        assert row["ceiling"] == 82.0
        assert "82 B/sig" in row["why"]

    def test_bound_passes_at_or_under_ceiling(self):
        new = _record(wire={"steady_state_bytes_per_sig": 76.0},
                      challenge={"lanes_device": 1024.0})
        v = bc.compare(_record(), new)
        assert v["verdict"] == "pass"
        assert v["bounds"]["wire.steady_state_bytes_per_sig"][
            "verdict"] == "pass"

    def test_bound_disarmed_without_device_challenge_evidence(self):
        """A knob-off run (or a pre-knob baseline) legitimately rides the
        98 B/sig host-k format — the bound must report info, not fail."""
        for challenge in ({}, {"lanes_device": 0.0}):
            new = _record(wire={"steady_state_bytes_per_sig": 98.0},
                          challenge=challenge)
            v = bc.compare(_record(), new)
            assert v["verdict"] == "pass"
            row = v["bounds"]["wire.steady_state_bytes_per_sig"]
            assert row["verdict"] == "info"
            assert "disarmed" in row["why_info"]

    def test_bound_absent_metric_is_silent(self):
        v = bc.compare(_record(), _record())
        assert "bounds" not in v

    def test_within_threshold_passes(self):
        v = bc.compare(_record(), dict(_record(), value=700_000.0))  # -12.5%
        assert v["metrics"]["value"]["verdict"] == "pass"

    def test_new_and_missing_are_informational(self):
        old = _record()
        new = _record()
        del new["detail"]["fetch_bytes_happy_path"]
        new["detail"]["brand_new_metric"] = 42.0
        v = bc.compare(old, new)
        assert v["verdict"] == "pass"
        assert v["metrics"]["fetch_bytes_happy_path"]["verdict"] == "missing"
        assert v["metrics"]["brand_new_metric"]["verdict"] == "new"

    def test_non_positive_baseline_is_info(self):
        old = _record(sr25519_device_compute_ms=-4.58)
        new = _record(sr25519_device_compute_ms=2.0)
        row = bc.compare(old, new)["metrics"]["sr25519_device_compute_ms"]
        assert row["verdict"] == "info"
        assert "non-positive" in row["why_info"]

    def test_threshold_scale_widens(self):
        old = _record()
        worse = dict(_record(), value=620_000.0)  # -22.5%
        assert bc.compare(old, worse)["verdict"] == "fail"
        assert bc.compare(old, worse,
                          threshold_scale=1.5)["verdict"] == "pass"

    def test_fleet_amortized_is_enforced_lower_better(self):
        """Serving-plane sentinel wiring: lc_amortized_ms regressing UP
        past 50% fails; the same delta as an improvement passes; the
        hit rate is informational with a stated why."""
        old = _record(lc_amortized_ms=4.0, lc_cache_hit_rate=0.85)
        worse = _record(lc_amortized_ms=9.0, lc_cache_hit_rate=0.2)
        v = bc.compare(old, worse)
        assert "lc_amortized_ms" in v["regressions"]
        assert bc.compare(worse, old)["verdict"] == "pass"
        row = v["metrics"]["lc_cache_hit_rate"]
        assert row["verdict"] == "info"
        assert "workload-mix" in row["why_info"]

    def test_fleet_sentinel_self_test_case(self):
        """The --self-test contract holds on a fleet-shaped record: an
        injected lc_amortized_ms regression is flagged, the identical
        snapshot and the improvement direction are not."""
        rec = _record(lc_amortized_ms=4.0, lc_cache_hit_rate=0.85)
        worse, metric, pct = bc.inject_regression(
            rec, metric="lc_amortized_ms")
        assert metric == "lc_amortized_ms" and pct > 50.0
        caught = bc.compare(rec, worse)
        assert caught["verdict"] == "fail"
        assert "lc_amortized_ms" in caught["regressions"]
        assert bc.compare(rec, rec)["verdict"] == "pass"
        assert bc.compare(worse, rec)["verdict"] == "pass"

    def test_gossip_amplification_is_enforced_lower_better(self):
        """Gossip-plane sentinel wiring (ISSUE 12): amplification rising
        past 25% fails; falling (reconciliation improving) passes; the
        fleet-rate and heal-latency curves are informational with a
        stated why."""
        old = _record(gossip_votes_per_vote_needed=1.2,
                      fleet_heights_per_s_50node=1.5,
                      partition_heal_p99_ms=900.0)
        worse = _record(gossip_votes_per_vote_needed=1.8,
                        fleet_heights_per_s_50node=0.4,
                        partition_heal_p99_ms=9000.0)
        v = bc.compare(old, worse)
        assert "gossip_votes_per_vote_needed" in v["regressions"]
        assert v["regressions"] == ["gossip_votes_per_vote_needed"]
        assert bc.compare(worse, old)["verdict"] == "pass"
        for name, why in (("fleet_heights_per_s_50node", "quiet round"),
                          ("partition_heal_p99_ms", "heal latency")):
            row = v["metrics"][name]
            assert row["verdict"] == "info"
            assert why in row["why_info"]

    def test_gossip_sentinel_self_test_case(self):
        """--self-test contract on a gossip-fleet-shaped record: the
        injected amplification regression is flagged; identical and
        improved snapshots are not."""
        rec = _record(gossip_votes_per_vote_needed=1.15)
        worse, metric, pct = bc.inject_regression(
            rec, metric="gossip_votes_per_vote_needed")
        assert metric == "gossip_votes_per_vote_needed" and pct > 25.0
        caught = bc.compare(rec, worse)
        assert caught["verdict"] == "fail"
        assert metric in caught["regressions"]
        assert bc.compare(rec, rec)["verdict"] == "pass"
        assert bc.compare(worse, rec)["verdict"] == "pass"

    def test_bls_aggregate_is_enforced_lower_better(self):
        """BLS sentinel wiring (ISSUE 13): the 10k-validator aggregate
        commit-verify time regressing UP past 50% fails — both the bare
        detail key and the bls.-prefixed section key; the same delta as
        an improvement passes; the crossover committee size is
        informational with a stated why (it is a backend property, not a
        regression surface)."""
        old = _record(bls_aggregate_verify_ms_10k=120.0,
                      bls={"bls_aggregate_verify_ms_10k": 120.0,
                           "crossover_validators": 30_000.0})
        worse = _record(bls_aggregate_verify_ms_10k=260.0,
                        bls={"bls_aggregate_verify_ms_10k": 260.0,
                             "crossover_validators": 500_000.0})
        v = bc.compare(old, worse)
        assert v["verdict"] == "fail"
        assert "bls_aggregate_verify_ms_10k" in v["regressions"]
        assert "bls.bls_aggregate_verify_ms_10k" in v["regressions"]
        assert bc.compare(worse, old)["verdict"] == "pass"
        row = v["metrics"]["bls.crossover_validators"]
        assert row["verdict"] == "info"
        assert "backend-dependent" in row["why_info"]

    def test_cert_verify_is_enforced_lower_better(self):
        """Cert-plane sentinel wiring (ISSUE 19): the 10k-validator
        certificate verify time regressing UP past 50% fails — both the
        bare detail key and the cert.-prefixed section key; the same
        delta as an improvement passes; the exact serve-bytes figure is
        informational with a stated why (a change there is a wire-format
        change, reviewed as a codec change)."""
        old = _record(cert_verify_ms_10k=140.0,
                      cert={"cert_verify_ms_10k": 140.0,
                            "serve_bytes_per_commit": 1450.0})
        worse = _record(cert_verify_ms_10k=300.0,
                        cert={"cert_verify_ms_10k": 300.0,
                              "serve_bytes_per_commit": 9000.0})
        v = bc.compare(old, worse)
        assert v["verdict"] == "fail"
        assert "cert_verify_ms_10k" in v["regressions"]
        assert "cert.cert_verify_ms_10k" in v["regressions"]
        assert bc.compare(worse, old)["verdict"] == "pass"
        row = v["metrics"]["cert.serve_bytes_per_commit"]
        assert row["verdict"] == "info"
        assert "wire format" in row["why_info"]

    def test_cert_sentinel_self_test_case(self):
        """--self-test contract on a cert-shaped record: the injected
        cert_verify_ms_10k regression is flagged; identical and improved
        snapshots are not."""
        rec = _record(cert_verify_ms_10k=140.0)
        worse, metric, pct = bc.inject_regression(
            rec, metric="cert_verify_ms_10k")
        assert metric == "cert_verify_ms_10k" and pct > 50.0
        caught = bc.compare(rec, worse)
        assert caught["verdict"] == "fail"
        assert metric in caught["regressions"]
        assert bc.compare(rec, rec)["verdict"] == "pass"
        assert bc.compare(worse, rec)["verdict"] == "pass"

    def test_wal_fsync_is_enforced_lower_better(self):
        """Storage sentinel wiring (ISSUE 14): the consensus-WAL fsync
        p99 regressing UP past 75% fails — both the bare detail key and
        the storage.-prefixed section key; the same delta as an
        improvement passes."""
        old = _record(wal_fsync_p99_ms=2.0,
                      storage={"wal_fsync_p99_ms": 2.0,
                               "db_write_p50_ms": 0.4})
        worse = _record(wal_fsync_p99_ms=6.0,
                        storage={"wal_fsync_p99_ms": 6.0,
                                 "db_write_p50_ms": 0.4})
        v = bc.compare(old, worse)
        assert v["verdict"] == "fail"
        assert "wal_fsync_p99_ms" in v["regressions"]
        assert "storage.wal_fsync_p99_ms" in v["regressions"]
        assert bc.compare(worse, old)["verdict"] == "pass"

    def test_wal_fsync_sentinel_self_test_case(self):
        """--self-test contract on a storage-shaped record: an injected
        wal-fsync regression is flagged; the identical snapshot and the
        improvement direction are not."""
        rec = _record(wal_fsync_p99_ms=2.0)
        worse, metric, pct = bc.inject_regression(
            rec, metric="wal_fsync_p99_ms")
        assert metric == "wal_fsync_p99_ms" and pct > 75.0
        caught = bc.compare(rec, worse)
        assert caught["verdict"] == "fail"
        assert metric in caught["regressions"]
        assert bc.compare(rec, rec)["verdict"] == "pass"
        assert bc.compare(worse, rec)["verdict"] == "pass"

    def test_bls_sentinel_self_test_case(self):
        """--self-test contract on a bls-shaped record: an injected
        aggregate-ms regression is flagged; the identical snapshot and
        the improvement direction are not."""
        rec = _record(bls_aggregate_verify_ms_10k=120.0)
        worse, metric, pct = bc.inject_regression(
            rec, metric="bls_aggregate_verify_ms_10k")
        assert metric == "bls_aggregate_verify_ms_10k" and pct > 50.0
        caught = bc.compare(rec, worse)
        assert caught["verdict"] == "fail"
        assert metric in caught["regressions"]
        assert bc.compare(rec, rec)["verdict"] == "pass"
        assert bc.compare(worse, rec)["verdict"] == "pass"

    def test_height_phase_total_is_enforced_lower_better(self):
        """Heightline sentinel wiring (ISSUE 16): the fleet-aggregated
        per-height phase total regressing UP past 75% fails — both the
        bare detail key and the consensus.-prefixed section key; the
        same delta as an improvement passes; the per-phase split and the
        propagation p99 are informational with a stated why."""
        old = _record(height_phase_total_ms=40.0,
                      height_phase_ms={"propose": 5.0, "prevote": 15.0,
                                       "precommit": 10.0, "commit": 4.0,
                                       "apply": 6.0},
                      proposal_propagation_p99_ms=3.0,
                      consensus={"height_phase_total_ms": 40.0})
        worse = _record(height_phase_total_ms=90.0,
                        height_phase_ms={"propose": 50.0, "prevote": 15.0,
                                         "precommit": 10.0, "commit": 4.0,
                                         "apply": 11.0},
                        proposal_propagation_p99_ms=40.0,
                        consensus={"height_phase_total_ms": 90.0})
        v = bc.compare(old, worse)
        assert v["verdict"] == "fail"
        assert "height_phase_total_ms" in v["regressions"]
        assert "consensus.height_phase_total_ms" in v["regressions"]
        assert bc.compare(worse, old)["verdict"] == "pass"
        # the split is attribution for the enforced total, not its own
        # regression surface; the p99 stays a trend line
        for name, why in (("height_phase_ms.propose", "phase split"),
                          ("proposal_propagation_p99_ms", "trend")):
            row = v["metrics"][name]
            assert row["verdict"] == "info"
            assert why in row["why_info"]

    def test_height_phase_missing_baseline_guard(self):
        """A baseline recorded before the heightline existed must not
        fail the current run: absent-in-baseline reports `new`,
        absent-in-current reports `missing` — both informational."""
        old = _record()  # no heightline metrics at all
        new = _record(height_phase_total_ms=40.0,
                      proposal_propagation_p99_ms=3.0)
        v = bc.compare(old, new)
        assert v["verdict"] == "pass"
        assert v["metrics"]["height_phase_total_ms"]["verdict"] == "new"
        back = bc.compare(new, old)
        assert back["verdict"] == "pass"
        assert back["metrics"]["height_phase_total_ms"]["verdict"] == "missing"

    def test_heightline_sentinel_self_test_case(self):
        """--self-test contract on a heightline-shaped record: an
        injected phase-total regression is flagged; the identical
        snapshot and the improvement direction are not."""
        rec = _record(height_phase_total_ms=40.0)
        worse, metric, pct = bc.inject_regression(
            rec, metric="height_phase_total_ms")
        assert metric == "height_phase_total_ms" and pct > 75.0
        assert worse["detail"]["height_phase_total_ms"] > 40.0  # LOWER dir
        caught = bc.compare(rec, worse)
        assert caught["verdict"] == "fail"
        assert metric in caught["regressions"]
        assert bc.compare(rec, rec)["verdict"] == "pass"
        assert bc.compare(worse, rec)["verdict"] == "pass"

    def test_soak_p99_is_enforced_lower_better(self):
        """Overload-soak sentinel wiring (ISSUE 17): the p99 inter-height
        gap under saturation regressing UP past 75% fails — both the
        bare detail key and the soak.-prefixed section key; the same
        delta as an improvement passes; the commit/admission rates are
        informational with a stated why (offered-load-shape properties,
        not code properties)."""
        old = _record(height_p99_under_load_ms=160.0,
                      soak_heights_per_s=8.0,
                      admission_txs_per_s=2700.0,
                      soak={"height_p99_under_load_ms": 160.0})
        worse = _record(height_p99_under_load_ms=420.0,
                        soak_heights_per_s=2.0,
                        admission_txs_per_s=400.0,
                        soak={"height_p99_under_load_ms": 420.0})
        v = bc.compare(old, worse)
        assert v["verdict"] == "fail"
        assert "height_p99_under_load_ms" in v["regressions"]
        assert "soak.height_p99_under_load_ms" in v["regressions"]
        assert bc.compare(worse, old)["verdict"] == "pass"
        for name, why in (("soak_heights_per_s", "height_p99_under_load_ms"),
                          ("admission_txs_per_s", "trend")):
            row = v["metrics"][name]
            assert row["verdict"] == "info"
            assert why in row["why_info"]

    def test_soak_sentinel_self_test_case(self):
        """--self-test contract on a soak-shaped record: an injected
        under-load p99 regression is flagged; the identical snapshot and
        the improvement direction are not."""
        rec = _record(height_p99_under_load_ms=160.0)
        worse, metric, pct = bc.inject_regression(
            rec, metric="height_p99_under_load_ms")
        assert metric == "height_p99_under_load_ms" and pct > 75.0
        assert worse["detail"]["height_p99_under_load_ms"] > 160.0
        caught = bc.compare(rec, worse)
        assert caught["verdict"] == "fail"
        assert metric in caught["regressions"]
        assert bc.compare(rec, rec)["verdict"] == "pass"
        assert bc.compare(worse, rec)["verdict"] == "pass"

    def test_bootstrap_convergence_is_enforced_lower_better(self):
        """Discovery-plane sentinel wiring: organic bootstrap convergence
        regressing UP past 75% fails — both the bare detail key and the
        discovery.-prefixed section key; the same delta as an improvement
        passes; the eclipse occupancy is informational with a stated why
        (the contract is the geometric bound asserted in tests)."""
        old = _record(bootstrap_convergence_s=18.0,
                      eclipse_book_occupancy_pct=9.4,
                      discovery={"bootstrap_convergence_s": 18.0})
        worse = _record(bootstrap_convergence_s=48.0,
                        eclipse_book_occupancy_pct=12.5,
                        discovery={"bootstrap_convergence_s": 48.0})
        v = bc.compare(old, worse)
        assert v["verdict"] == "fail"
        assert "bootstrap_convergence_s" in v["regressions"]
        assert "discovery.bootstrap_convergence_s" in v["regressions"]
        assert bc.compare(worse, old)["verdict"] == "pass"
        row = v["metrics"]["eclipse_book_occupancy_pct"]
        assert row["verdict"] == "info"
        assert "geometric bound" in row["why_info"]

    def test_discovery_sentinel_self_test_case(self):
        """--self-test contract on a discovery-shaped record: an injected
        bootstrap-convergence regression is flagged; the identical
        snapshot and the improvement direction are not."""
        rec = _record(bootstrap_convergence_s=18.0)
        worse, metric, pct = bc.inject_regression(
            rec, metric="bootstrap_convergence_s")
        assert metric == "bootstrap_convergence_s" and pct > 75.0
        assert worse["detail"]["bootstrap_convergence_s"] > 18.0
        caught = bc.compare(rec, worse)
        assert caught["verdict"] == "fail"
        assert metric in caught["regressions"]
        assert bc.compare(rec, rec)["verdict"] == "pass"
        assert bc.compare(worse, rec)["verdict"] == "pass"

    def test_fleet_curve_leaves_are_informational(self):
        """Nested fleet curve values (fleet.curve.<n>.*) flatten into
        dotted names that are NOT tracked — they must report as info,
        never fail a run."""
        old = _record(fleet={"curve": {"16": {"heights_per_s": 2.0}}})
        worse = _record(fleet={"curve": {"16": {"heights_per_s": 0.1}}})
        v = bc.compare(old, worse)
        assert v["verdict"] == "pass"
        assert v["metrics"]["fleet.curve.16.heights_per_s"]["verdict"] == "info"


class TestSnapshotShapes:
    def test_driver_record_with_parsed(self, tmp_path):
        rec = bc.load_snapshot(_driver_snapshot(tmp_path, parsed=True))
        assert rec["value"] == 804844.9
        assert bc.flatten(rec)["device_compute_ms_per_batch"] == 12.72

    def test_driver_record_with_null_parsed_recovers_tail(self, tmp_path):
        """A driver snapshot can ship parsed=null and a front-truncated
        tail; the sentinel must still recover comparable metrics."""
        rec = bc.load_snapshot(_driver_snapshot(tmp_path, parsed=False))
        flat = bc.flatten(rec)
        assert flat["sr25519_device_compute_ms"] == 1.99
        assert flat["blocksync_blocks_per_s"] == 25.1

    def test_raw_bench_line(self, tmp_path):
        p = tmp_path / "cur.json"
        p.write_text(json.dumps(_record()))
        assert bc.load_snapshot(str(p))["value"] == 800_000.0

    def test_unrecognized_shape_raises(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text('{"unrelated": 1}')
        with pytest.raises(bc.SnapshotError):
            bc.load_snapshot(str(p))


@pytest.mark.perf
class TestSentinelSelfTest:
    """The CI perf smoke: a synthetically injected regression into a
    copied snapshot MUST be flagged; the unmodified copy must not."""

    def test_injected_regression_flagged_on_synthetic(self, tmp_path):
        p = tmp_path / "base.json"
        p.write_text(json.dumps(_record()))
        res = bc.self_test(str(p), pct=30.0)
        assert res["ok"], res
        assert res["regression_verdict"] == "fail"
        assert res["identical_verdict"] == "pass"
        assert res["improvement_verdict"] == "pass"

    def test_injected_regression_flagged_on_driver_snapshots(self, tmp_path):
        for parsed in (True, False):
            res = bc.self_test(_driver_snapshot(tmp_path, parsed), pct=30.0)
            assert res["ok"], (parsed, res)
            assert res["injected_metric"] in res["regression_flagged"]

    def test_injection_is_direction_aware(self):
        base = _record()
        worse, metric, pct = bc.inject_regression(base, pct=30.0,
                                                  metric="value")
        assert metric == "value" and pct == 30.0
        assert worse["value"] == pytest.approx(800_000.0 * 0.7)
        worse, _, _ = bc.inject_regression(
            base, pct=30.0, metric="device_compute_ms_per_batch")
        assert worse["detail"]["device_compute_ms_per_batch"] == \
            pytest.approx(12.8 * 1.3)


@pytest.mark.perf
class TestEntryPoints:
    def test_module_cli_self_test(self, tmp_path):
        out = subprocess.run(
            [sys.executable, "-m", "tools.bench_compare", "--self-test",
             _driver_snapshot(tmp_path, parsed=True)],
            capture_output=True, text=True, cwd=REPO, timeout=60)
        assert out.returncode == 0, out.stdout + out.stderr
        assert json.loads(out.stdout)["ok"] is True

    def test_module_cli_flags_regression(self, tmp_path):
        base = tmp_path / "base.json"
        cur = tmp_path / "cur.json"
        base.write_text(json.dumps(_record()))
        worse, _, _ = bc.inject_regression(_record(), pct=35.0,
                                           metric="value")
        cur.write_text(json.dumps(worse))
        out = subprocess.run(
            [sys.executable, "-m", "tools.bench_compare",
             str(base), str(cur)],
            capture_output=True, text=True, cwd=REPO, timeout=60)
        assert out.returncode == 1
        assert "value" in json.loads(out.stdout)["regressions"]

    def test_bench_py_compare_current_mode(self, tmp_path):
        """bench.py --compare OLD --current NEW diffs without running the
        bench (no device, no jax import needed)."""
        base = tmp_path / "base.json"
        base.write_text(json.dumps(_record()))
        out = subprocess.run(
            [sys.executable, "bench.py", "--compare", str(base),
             "--current", str(base)],
            capture_output=True, text=True, cwd=REPO, timeout=60)
        assert out.returncode == 0, out.stdout + out.stderr
        assert json.loads(out.stdout.splitlines()[-1])["verdict"] == "pass"
        worse, _, _ = bc.inject_regression(_record(), pct=35.0,
                                           metric="value")
        cur = tmp_path / "cur.json"
        cur.write_text(json.dumps(worse))
        out = subprocess.run(
            [sys.executable, "bench.py", "--compare", str(base),
             "--current", str(cur)],
            capture_output=True, text=True, cwd=REPO, timeout=60)
        assert out.returncode == 1
        assert json.loads(out.stdout.splitlines()[-1])["verdict"] == "fail"


class TestHonestSpreadStats:
    """Satellite: the bench's device-timing repeatability stat must report
    the spread over ALL post-warmup runs (median + p90 + spread_pct), not
    a min-vs-min agreement that hides bimodality."""

    def test_bimodal_runs_report_honest_spread(self):
        sys.path.insert(0, REPO)
        import bench

        # the exact r05 list that reported 4.3% "repeatability"
        runs = [2.08, 8.63, 8.53, 8.66, 8.5, 1.99]
        stats = bench._run_stats(runs, converged=True)
        assert stats["runs"] == 6
        assert stats["min_ms"] == 1.99
        assert stats["median_ms"] == pytest.approx(8.52, abs=0.05)
        assert stats["p90_ms"] == pytest.approx(8.66, abs=0.01)
        # the honest spread is ~335%, not 4.3%
        assert stats["spread_pct"] > 300

    def test_single_run_spread_is_none_not_zero(self):
        import bench

        stats = bench._run_stats([5.0], converged=False)
        assert stats["spread_pct"] is None
        assert stats["median_ms"] == 5.0
