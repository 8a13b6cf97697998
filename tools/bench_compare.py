"""Bench regression sentinel: diff two bench snapshots with per-metric
direction-aware thresholds and emit a machine-readable verdict.

The BENCH_r*.json trajectory was archaeology: numbers moved between
rounds and nothing but a human reading the diff decided whether a move
was a regression. This turns it into an enforced contract:

    python -m tools.bench_compare baseline.json current.json
    python bench.py --compare baseline.json          # run, then diff
    python -m tools.bench_compare --self-test baseline.json

Inputs may be either shape the repo actually contains:
  - a raw bench record: {"metric", "value", "unit", "detail": {...}}
    (one bench.py stdout line saved to a file), or
  - a driver snapshot: {"n", "cmd", "rc", "tail", "parsed"} — `parsed`
    preferred; when it is null (BENCH_r05) the record is recovered from
    the `tail` text (the tail may be truncated at the FRONT, so recovery
    tries progressively later JSON start points, then falls back to
    scraping flat "key": number pairs).

The metric table below is deliberately curated: only device/host-bound,
repeatable numbers are ENFORCED (fail the verdict); wire-bound numbers
(blocksync on a contended link, anything paying the dev-box RTT) swing
multiples between runs with no code change, so they are reported as
informational drift and never fail a run. stream_sigs_per_s graduated
out of that set: with device-side challenge derivation only signature
material crosses the wire, so the stream is no longer send-bound and is
enforced (higher_better, wide threshold). Direction is explicit per
metric — throughput regressing DOWN fails, latency regressing UP fails,
and an improvement in either direction always passes.

On top of the relative diffs, BOUNDS holds absolute ceilings checked
against the NEW snapshot alone (e.g. steady-state wire bytes/sig <= 82
under the device-challenge format), armed only when the snapshot itself
carries evidence the knob was on (challenge.lanes_device > 0); a tripped
bound lands in `regressions` as "bound:<name>".

Verdict schema (one JSON object):
  {"verdict": "pass"|"fail", "regressions": [name...],
   "metrics": {name: {"old", "new", "change_pct", "direction",
                      "threshold_pct", "verdict"}},
   "bounds": {name: {"value", "ceiling", "evidence", "verdict"}}}
per-metric verdict: "pass" | "fail" | "info" (untracked or wire-bound) |
"new" (no baseline value) | "missing" (baseline metric absent now —
informational; benches grow sections across rounds).
"""

from __future__ import annotations

import argparse
import json
import re
import sys

HIGHER = "higher_better"
LOWER = "lower_better"

# metric name (flattened: detail keys verbatim, nested via ".") ->
# (direction, fail threshold in %). Everything else is informational.
TRACKED: dict[str, tuple[str, float]] = {
    # headline + device-bound throughput (rep-differenced, repeatable)
    "value": (HIGHER, 20.0),
    "device_sigs_per_s": (HIGHER, 20.0),
    "device_compute_ms_per_batch": (LOWER, 25.0),
    "vote_flush_device_ms": (LOWER, 50.0),
    "sr25519_device_compute_ms": (LOWER, 50.0),
    # host staging plane (pure host work; contention-light)
    "staging_us_per_row.ed25519": (LOWER, 50.0),
    "staging_us_per_row.sr25519": (LOWER, 50.0),
    "mixed_host_staging_ms": (LOWER, 50.0),
    "mixed_host_challenge_us_per_row": (LOWER, 50.0),
    # protocol properties (bytes on the wire — stable by construction)
    "fetch_bytes_happy_path": (LOWER, 10.0),
    "attribution.bytes_per_sig_tx": (LOWER, 25.0),
    "attribution.bytes_per_sig_rx": (LOWER, 25.0),
    # reduced-send protocol: measured steady-state send cost per
    # signature (ops/residency.py accounting) — enforced lower-is-better
    # because bytes on the wire are a property of the protocol, not of
    # link contention
    "wire_bytes_per_sig": (LOWER, 25.0),
    "wire.steady_state_bytes_per_sig": (LOWER, 25.0),
    # scheduler batching quality (ratio of the same load, not wall time)
    "sched.fill_ratio_mean": (HIGHER, 25.0),
    "sched.fill_gain": (HIGHER, 25.0),
    # multi-chip mesh scenario (forced-host devices: CPU-bound and box-
    # contention-sensitive, so thresholds are wide; the SHAPE of the
    # scaling curve is the contract, not the absolute rate). The same
    # keys appear bare when diffing MULTICHIP_rNN records directly and
    # under "mesh." when the section rides a full bench record.
    "device_sigs_per_s_8dev": (HIGHER, 40.0),
    "mesh.device_sigs_per_s_8dev": (HIGHER, 40.0),
    "scaling_x8": (HIGHER, 30.0),
    "mesh.scaling_x8": (HIGHER, 30.0),
    "mega_commit_sigs_per_s": (HIGHER, 40.0),
    "mesh.mega_commit_sigs_per_s": (HIGHER, 40.0),
    # light-client fleet serving plane (bench_light_fleet): amortized
    # per-client cost of the 10k-client soak — the millions-of-users
    # headline. Wide threshold: the soak runs on a shared host, but the
    # amortization (coalescing + cache) is a code property and an
    # order-of-magnitude regression means the serving plane broke.
    "lc_amortized_ms": (LOWER, 50.0),
    # gossip-plane vote amplification (bench_fleet, largest size): votes
    # received per vote actually needed. ENFORCED lower-is-better — like
    # wire_bytes_per_sig, redundant sends are a property of the
    # reconciliation protocol, not of host contention, and a jump means
    # the compact vote-set summaries stopped doing their job.
    "gossip_votes_per_vote_needed": (LOWER, 25.0),
    # BLS aggregate commit verify at 10k validators (bench_bls): the
    # one-pairing-product headline. Wide threshold — the host share is
    # O(n) oracle point adds on a contended box — but a multiple-of-
    # itself regression means aggregation stopped amortizing. Bare and
    # section-prefixed like the mesh keys.
    "bls_aggregate_verify_ms_10k": (LOWER, 50.0),
    "bls.bls_aggregate_verify_ms_10k": (LOWER, 50.0),
    # commit-certificate verify at 10k validators (bench_cert): the full
    # consumer path — decode-shaped cert, bitmap tally, sign-bytes
    # reconstruction, signer-pubkey aggregation, ONE pairing. Same wide
    # threshold and O(n)-host-share caveats as the bls headline above;
    # a multiple-of-itself jump means the certificate stopped being a
    # single-pairing object. Bare and cert.-prefixed like the bls keys.
    "cert_verify_ms_10k": (LOWER, 50.0),
    "cert.cert_verify_ms_10k": (LOWER, 50.0),
    # consensus-WAL fsync p99 (bench_storage): the disk floor under
    # every committed height. Wide threshold — absolute fsync latency is
    # a property of the bench host's disk — but a multiple-of-itself
    # jump means the WAL write path grew extra syncs/copies. Bare and
    # storage.-prefixed like the mesh/bls keys.
    "wal_fsync_p99_ms": (LOWER, 75.0),
    "storage.wal_fsync_p99_ms": (LOWER, 75.0),
    # consensus heightline (bench_consensus_tpu + consensus/timeline.py):
    # the sum of the five per-phase fleet maxima
    # (propose/prevote/precommit/commit/apply) over the 4-val in-proc
    # net. ENFORCED lower-is-better with a wide threshold — the absolute
    # number rides host contention, but a multiple-of-itself jump means
    # a consensus phase grew real work. Bare and consensus.-prefixed
    # like the mesh/bls/storage keys.
    "height_phase_total_ms": (LOWER, 75.0),
    "consensus.height_phase_total_ms": (LOWER, 75.0),
    # overload soak (bench_soak): p99 inter-height gap while the
    # saturation generator sheds against the admission ceiling — the
    # graded liveness headline of the overload plane. ENFORCED
    # lower-is-better with a wide threshold: the absolute gap rides
    # host contention, but a multiple-of-itself jump means consensus
    # stopped being insulated from mempool/RPC pressure. Bare and
    # soak.-prefixed like the mesh/bls/storage/consensus keys.
    "height_p99_under_load_ms": (LOWER, 75.0),
    "soak.height_p99_under_load_ms": (LOWER, 75.0),
    # discovery plane (bench --discovery): wall seconds for an organic
    # fleet (one seed, empty address books, no persistent wiring) to go
    # from spawn to every node committing — PEX convergence IS the
    # critical path. ENFORCED lower-is-better with a wide threshold: the
    # absolute number rides process-boot cost on a shared host, but a
    # multiple-of-itself jump means discovery gossip stopped converging.
    # Bare and discovery.-prefixed like the mesh/bls/storage/soak keys.
    "bootstrap_convergence_s": (LOWER, 75.0),
    "discovery.bootstrap_convergence_s": (LOWER, 75.0),
    # streaming verify throughput: PROMOTED from WIRE_BOUND after the
    # device-challenge protocol (k derived on-chip, only signature
    # material crosses the wire) cut the send cost below the link's
    # contention floor — see TRACKED_WHY for the full rationale
    "stream_sigs_per_s": (HIGHER, 50.0),
}

# enforced metrics whose promotion history matters: the why rides every
# verdict row so a failing run explains its own contract instead of
# pointing at repo archaeology
TRACKED_WHY: dict[str, str] = {
    "stream_sigs_per_s":
        "promoted from wire-bound: with device-side challenge derivation "
        "the stream ships only R/s limbs + per-lane descriptors, so "
        "throughput is a code property again (send-bound no longer). The "
        "50% threshold leaves room for the link RTT that still rides "
        "the measurement",
}

# absolute ceilings on the NEW snapshot (not relative to a baseline):
# metric -> (ceiling, evidence key, why). The bound is armed only when
# the evidence key is present and positive in the SAME snapshot — a
# bench run with the device-challenge knob off (or a pre-knob baseline)
# must not fail a bound that describes the knob-on wire format.
BOUNDS: dict[str, tuple[float, str, str]] = {
    "wire.steady_state_bytes_per_sig": (
        82.0, "challenge.lanes_device",
        "device-challenge wire format: R/s limbs + 2-byte descriptor + "
        "<= MAX_VAR suffix bytes per lane must stay at or under 82 B/sig "
        "in steady state (vs 98 for the host-k block)"),
    "wire_bytes_per_sig": (
        82.0, "challenge.lanes_device",
        "bare-key twin of wire.steady_state_bytes_per_sig"),
}

# informational-by-design (wire-bound): listed so the verdict can
# say WHY they are not enforced instead of silently defaulting.
WIRE_BOUND = {
    "blocksync_blocks_per_s", "blocksync_sigs_per_s",
    "blocksync_device_busy_fraction", "p50_batch_latency_ms",
    "mixed_megacommit_ms", "mixed_colocated_estimate_ms",
    "lc_bisection_s", "lc_client_s", "consensus_tpu_height_p50_ms",
}

# informational-by-design for OTHER reasons than link contention —
# same contract as WIRE_BOUND (reported with a why, never enforced)
INFORMATIONAL = {
    "lc_cache_hit_rate": "workload-mix property (request distribution), "
                         "not a code property — tracked for trend only",
    "fleet.p99_heal_ms": "post-outage recovery latency: depends on the "
                         "injected outage shape and host contention",
    # fleet-size curves (bench_fleet): informational until a quiet round
    # establishes run-to-run variance — 50 OS processes on a shared CI
    # host swing with whatever else runs; promote to TRACKED only after
    # a quiet baseline exists
    "fleet_heights_per_s_50node": "50-node commit rate: host-contention-"
                                  "bound until a quiet round establishes "
                                  "variance — then promote to TRACKED",
    "partition_heal_p99_ms": "heal latency depends on redial backoff "
                             "phase and host contention; tracked for "
                             "trend until a quiet round",
    # bench_bls crossover: the committee size where one pairing-product
    # check beats per-lane ed25519 — informational because it is a
    # BACKEND property (host point-add rate vs lane-verify rate), not a
    # regression surface; it moves legitimately between CPU-extrapolated
    # and accelerator-measured rounds
    "bls.crossover_validators": "backend-dependent crossover point "
                                "(aggregate vs batched-ed25519); moves "
                                "between CPU and accelerator rounds by "
                                "design — tracked for trend only",
    # heightline per-phase breakdown + propagation tail: the TOTAL is
    # enforced (height_phase_total_ms above); the split between phases
    # shifts legitimately with scheduler/timeout phasing, and the p99 of
    # a 4-val in-proc net is a handful of samples
    "height_phase_ms.propose": "phase split of the enforced "
                               "height_phase_total_ms — shifts between "
                               "phases are not regressions by themselves",
    "height_phase_ms.prevote": "see height_phase_ms.propose",
    "height_phase_ms.precommit": "see height_phase_ms.propose",
    "height_phase_ms.commit": "see height_phase_ms.propose",
    "height_phase_ms.apply": "see height_phase_ms.propose",
    "proposal_propagation_p99_ms": "p99 over tens of in-proc samples: "
                                   "tracked for trend until a quiet "
                                   "round establishes variance",
    # overload-soak companions to the enforced height_p99_under_load_ms:
    # both are offered-load-shape properties (how hard the generator
    # pushes on this host), not code properties
    "soak_heights_per_s": "commit rate under saturation: rides host "
                          "contention and generator pacing — the "
                          "enforced contract is height_p99_under_load_ms",
    "admission_txs_per_s": "admitted-tx rate under saturation: a "
                           "property of pool size vs drain rate on this "
                           "host, tracked for trend only",
    # discovery-plane companion to the enforced bootstrap_convergence_s:
    # the occupancy is bound-checked in tests (<= the hashed-bucket
    # geometric bound), and its exact value below the bound is a hash
    # artifact of the flood's forged claim set, not a regression surface
    "eclipse_book_occupancy_pct": "worst per-/16 share of the NEW set "
                                  "under the bench sybil flood: the "
                                  "CONTRACT is the geometric bound "
                                  "asserted in tests; the value below "
                                  "the bound is a hash artifact",
    # cert-plane transport companion to the enforced cert_verify_ms_10k:
    # bytes are exact by construction (one bit per validator + fixed
    # header), so a change is a WIRE-FORMAT change, reviewed as such —
    # informational so a deliberate codec evolution doesn't fail CI
    "cert.serve_bytes_per_commit": "exact encoded certificate size at "
                                   "10k validators: changes only with "
                                   "the wire format itself, reviewed as "
                                   "a codec change rather than enforced",
}


class SnapshotError(Exception):
    pass


# ------------------------------------------------------------- loading


def load_snapshot(path: str) -> dict:
    """Load a bench record from either supported file shape. For a
    DRIVER snapshot, an out-file written by `bench.py --out` (the
    untruncatable full record) is consulted: one named by the
    snapshot's explicit `out` key always wins (the driver opted in);
    the `<stem>.out.json` naming convention is used only when the
    snapshot's own `parsed` content is unusable (the BENCH_r05
    `"parsed": null` truncation shape) — a stale leftover sibling must
    never silently shadow a good parsed record."""
    import os

    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and "detail" not in doc and "metric" not in doc:
        snapshot_ok = isinstance(doc.get("parsed"), dict)
        for cand in _out_file_candidates(path, doc,
                                         include_siblings=not snapshot_ok):
            if cand and os.path.exists(cand):
                try:
                    with open(cand) as f:
                        return coerce_record(json.load(f))
                except (OSError, json.JSONDecodeError, SnapshotError):
                    pass  # fall back to the snapshot's own content
    return coerce_record(doc)


def _out_file_candidates(path: str, doc: dict,
                         include_siblings: bool = True) -> list[str]:
    """Where `bench.py --out` full records live next to a driver
    snapshot: an explicit `out` key in the snapshot, then (only when
    the caller needs recovery) the `<stem>.out.json` convention."""
    import os

    out = []
    if isinstance(doc.get("out"), str):
        # a relative `out` resolves against the SNAPSHOT's directory
        # first — the CWD may hold a stale same-named artifact from an
        # earlier round
        if not os.path.isabs(doc["out"]):
            out.append(os.path.join(os.path.dirname(path) or ".",
                                    doc["out"]))
        out.append(doc["out"])
    if include_siblings:
        stem = os.path.splitext(path)[0]
        out += [stem + ".out.json", path + ".out"]
    return out


def coerce_record(doc: dict) -> dict:
    """A raw bench record passes through; a driver snapshot resolves to
    its parsed record or a tail-recovered one."""
    if not isinstance(doc, dict):
        raise SnapshotError(f"snapshot is {type(doc).__name__}, want object")
    if "detail" in doc or "metric" in doc:
        return doc
    if "parsed" in doc or "tail" in doc:
        if isinstance(doc.get("parsed"), dict):
            return doc["parsed"]
        rec = recover_from_tail(doc.get("tail") or "")
        if rec is not None:
            return rec
        raise SnapshotError("driver snapshot has no parsed record and the "
                            "tail could not be recovered")
    raise SnapshotError("unrecognized snapshot shape "
                        f"(keys {sorted(doc)[:6]})")


def recover_from_tail(tail: str) -> dict | None:
    """Recover a (possibly partial) record from a driver snapshot's
    stdout tail. The tail keeps the END of the line, so the front may be
    cut mid-token: try the full JSON first, then progressively later
    start points re-opened with '{' (dropping surplus closing braces),
    then fall back to scraping flat numeric pairs."""
    tail = tail.strip()
    start = tail.find('{"metric"')
    if start >= 0:
        try:
            return json.loads(tail[start:])
        except json.JSONDecodeError:
            pass
    # re-open at a later key boundary; surplus trailing '}' (we started
    # inside nested objects) are trimmed one at a time
    starts = [m.start() for m in re.finditer(r'"[A-Za-z0-9_]+":', tail)][:64]
    for i in starts:
        body = "{" + tail[i:]
        for trim in range(4):
            try:
                got = json.loads(body[: len(body) - trim if trim else None])
            except json.JSONDecodeError:
                continue
            if isinstance(got, dict) and got:
                return {"detail": got}
    flat = {}
    for m in re.finditer(r'"([A-Za-z0-9_]+)": (-?\d+(?:\.\d+)?)\b', tail):
        flat.setdefault(m.group(1), float(m.group(2)))
    return {"detail": flat} if flat else None


# ----------------------------------------------------------- flattening


def flatten(record: dict) -> dict[str, float]:
    """Numeric leaves of a bench record, keyed the way TRACKED names them:
    top-level "value", then detail keys verbatim with nested dicts dotted
    (lists and strings are skipped — runs arrays and notes are not
    comparable scalars)."""
    out: dict[str, float] = {}
    v = record.get("value")
    if isinstance(v, (int, float)) and not isinstance(v, bool):
        out["value"] = float(v)

    def walk(prefix: str, node: dict) -> None:
        for k, val in node.items():
            key = prefix + str(k)
            if isinstance(val, dict):
                walk(key + ".", val)
            elif isinstance(val, (int, float)) and not isinstance(val, bool):
                out[key] = float(val)

    detail = record.get("detail")
    if isinstance(detail, dict):
        walk("", detail)
    return out


# ------------------------------------------------------------ comparing


def compare(old_record: dict, new_record: dict,
            threshold_scale: float = 1.0) -> dict:
    """The sentinel: per-metric direction-aware diff. `threshold_scale`
    widens (>1) or tightens (<1) every tracked threshold uniformly —
    a knob for noisy CI hosts."""
    old = flatten(old_record)
    new = flatten(new_record)
    metrics: dict[str, dict] = {}
    regressions: list[str] = []
    for name in sorted(set(old) | set(new)):
        spec = TRACKED.get(name)
        o, n = old.get(name), new.get(name)
        row: dict = {"old": o, "new": n}
        if spec is not None:
            row["direction"] = spec[0]
            row["threshold_pct"] = round(spec[1] * threshold_scale, 3)
            if name in TRACKED_WHY:
                row["why"] = TRACKED_WHY[name]
        if o is None:
            row["verdict"] = "new"
        elif n is None:
            row["verdict"] = "missing"
        else:
            change = (n - o) / o * 100 if o else (0.0 if n == o else None)
            row["change_pct"] = (round(change, 2) if change is not None
                                 else None)
            if spec is not None and o <= 0:
                # a non-positive baseline (a failed measurement recorded
                # honestly, e.g. r04's negative sr25519 slope) cannot
                # anchor a percentage — report, never judge
                row["verdict"] = "info"
                row["why_info"] = "non-positive baseline value"
            elif spec is None or change is None:
                row["verdict"] = "info"
                if name in WIRE_BOUND:
                    row["why_info"] = "wire-bound: swings with link " \
                                      "contention, not code"
                elif name in INFORMATIONAL:
                    row["why_info"] = INFORMATIONAL[name]
            else:
                direction, threshold = spec
                threshold *= threshold_scale
                worse = -change if direction == HIGHER else change
                if worse > threshold:
                    row["verdict"] = "fail"
                    regressions.append(name)
                else:
                    row["verdict"] = "pass"
        metrics[name] = row
    bounds: dict[str, dict] = {}
    for name, (ceiling, evidence, why) in BOUNDS.items():
        val = new.get(name)
        if val is None:
            continue
        ev = new.get(evidence, 0.0)
        brow = {"value": val, "ceiling": ceiling, "evidence": evidence,
                "evidence_value": ev, "why": why}
        if ev > 0:
            if val > ceiling:
                brow["verdict"] = "fail"
                regressions.append(f"bound:{name}")
            else:
                brow["verdict"] = "pass"
        else:
            # no device-challenge lanes in this snapshot: the knob was
            # off (or the record predates it) — the bound is disarmed
            brow["verdict"] = "info"
            brow["why_info"] = f"bound disarmed: {evidence} absent or zero"
        bounds[name] = brow
    out = {
        "verdict": "fail" if regressions else "pass",
        "regressions": regressions,
        "tracked": sum(1 for r in metrics.values()
                       if r.get("verdict") in ("pass", "fail")),
        "metrics": metrics,
    }
    if bounds:
        out["bounds"] = bounds
    return out


def compare_files(old_path: str, new_path: str,
                  threshold_scale: float = 1.0) -> dict:
    return compare(load_snapshot(old_path), load_snapshot(new_path),
                   threshold_scale=threshold_scale)


# ------------------------------------------------------------- self-test


def inject_regression(record: dict, pct: float = 30.0,
                      metric: str | None = None) -> tuple[dict, str]:
    """Copy `record` with one tracked metric worsened (direction-aware:
    throughput shrinks, latency grows). Returns (copy, metric,
    injected_pct). When none is named, picks the tracked metric with the
    smallest threshold present; the injection is at least pct and always
    big enough to trip the chosen metric's threshold (a partial snapshot
    may only carry wide-threshold metrics)."""
    flat = flatten(record)
    if metric is None:
        present = [(thr, m) for m, (_, thr) in TRACKED.items()
                   if m in flat and flat[m]]
        metric = min(present)[1] if present else None
    if metric is None or metric not in flat:
        raise SnapshotError("no tracked metric present to inject into")
    direction, thr = TRACKED[metric]
    if pct <= thr:  # the injection must be able to trip the threshold
        pct = thr * 1.25
    factor = (1 - pct / 100) if direction == HIGHER else (1 + pct / 100)
    copy = json.loads(json.dumps(record))
    # write the worsened value back through the dotted path
    if metric == "value":
        copy["value"] = flat[metric] * factor
    else:
        node = copy.setdefault("detail", {})
        parts = metric.split(".")
        for p in parts[:-1]:
            node = node[p]
        node[parts[-1]] = flat[metric] * factor
    return copy, metric, pct


def self_test(path: str, pct: float = 30.0) -> dict:
    """The sentinel must catch a synthetic pct% regression injected into
    a copy of `path`, and must NOT flag the identical snapshot or a pct%
    improvement. Returns a machine-readable result; 'ok' is the gate."""
    base = load_snapshot(path)
    same = compare(base, base)
    worse, metric, injected = inject_regression(base, pct=pct)
    caught = compare(base, worse)
    better = compare(worse, base)  # the same delta, as an improvement
    ok = (same["verdict"] == "pass"
          and caught["verdict"] == "fail" and metric in caught["regressions"]
          and better["verdict"] == "pass")
    return {
        "ok": ok,
        "injected_metric": metric,
        "injected_pct": injected,
        "identical_verdict": same["verdict"],
        "regression_verdict": caught["verdict"],
        "regression_flagged": caught["regressions"],
        "improvement_verdict": better["verdict"],
    }


# ------------------------------------------------------------------ CLI


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="bench_compare",
        description="diff two bench snapshots with direction-aware "
                    "per-metric thresholds; exit 1 on regression")
    p.add_argument("baseline", help="prior snapshot (BENCH_rNN.json or a "
                                    "saved bench.py line)")
    p.add_argument("current", nargs="?", default="",
                   help="current snapshot (omit with --self-test)")
    p.add_argument("--threshold-scale", type=float, default=1.0,
                   help="multiply every tracked threshold (noisy hosts)")
    p.add_argument("--self-test", action="store_true",
                   help="inject a fake regression into a copy of BASELINE "
                        "and verify the sentinel flags it")
    p.add_argument("--inject-pct", type=float, default=30.0,
                   help="self-test regression size in percent")
    args = p.parse_args(argv)
    try:
        if args.self_test:
            res = self_test(args.baseline, pct=args.inject_pct)
            print(json.dumps(res, indent=1))
            return 0 if res["ok"] else 1
        if not args.current:
            p.error("current snapshot required (or pass --self-test)")
        verdict = compare_files(args.baseline, args.current,
                                threshold_scale=args.threshold_scale)
        print(json.dumps(verdict, indent=1))
        return 0 if verdict["verdict"] == "pass" else 1
    except (SnapshotError, OSError, json.JSONDecodeError) as e:
        print(json.dumps({"error": str(e)}))
        return 2


if __name__ == "__main__":
    sys.exit(main())
