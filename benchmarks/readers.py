"""The per-layer metrics' readers, by kind.

A reader is a file benchmarks/metrics/<reader>.json: {"what", "unit",
"source": {"kind": ..., params}}. A metric of BENCHMARK.json finds its
reader by name: <name>.json, or, where a quantity stands once for each
end-to-end metric it moves (`sched_fill_pct.commit`, `.catchup`), the name
without its last `.suffix`. Layer, `moves` and `workloads` are stated in
BENCHMARK.json alone. The kinds here read what a run has observed (`obs`, made by run.py): the
window's records, the program's counters differenced over the window, the
program tracer's host attribution, the reduced profiler trace. Where no
kind fits, benchmarks/metrics/<name>.py with a function read(obs, params)
is the reader. A reader that finds nothing to read returns None, and the
metric is left out of the line.
"""

from __future__ import annotations

import importlib.util
import json
import os
import statistics

from benchmarks import peaks, reduce


def _sum(counters: dict, paths: list[str]) -> float | None:
    found = [counters[p] for p in paths if p in counters]
    return sum(found) if found else None


def counter_sum(obs, p):
    """sum(plus) - sum(minus) of counters over the window."""
    plus = _sum(obs["counters"], p["plus"])
    if plus is None:
        return None
    return plus - (_sum(obs["counters"], p.get("minus", [])) or 0)


def counter_ratio(obs, p):
    """scale * sum(num) / sum(den) of counters over the window."""
    num, den = _sum(obs["counters"], p["num"]), _sum(obs["counters"], p["den"])
    if num is None or not den:
        return None
    return p.get("scale", 1) * num / den


def rung_deficit(obs, _p):
    """Batches served below the rung the configuration's guarantee names
    (its `guarantees.rung`: batches that went to the device, less the ones
    the due rung served, plus what the host oracle served)."""
    return counter_sum(obs, obs["cell"].config["guarantees"]["rung"])


def attribution(obs, p):
    """Host self time of one stage of libs/trace's attribution, in
    microseconds a row (traced runs: the program's tracer is on). Nothing
    where the program's tracer has no such stage (a parent's traced run)."""
    att = obs.get("attribution")
    if not att or not att.get("rows") or p["stage"] not in att["stage_us"]:
        return None
    return att["stage_us"][p["stage"]] / att["rows"]


def latency_percentile(obs, p):
    """A percentile of the latencies of ALL operations of the window."""
    ms = sorted(r.ms for r in obs["records"])
    if len(ms) < 2:
        return None
    if p["q"] == 50:
        return statistics.median(ms)
    return statistics.quantiles(ms, n=100, method="inclusive")[p["q"] - 1]


def trace_idle(obs, _p):
    """1 - busy / window of the traced slice, in percent."""
    tr = obs.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def trace_roofline(obs, p):
    """The least time the chip could take for the signatures verified in
    the traced slice, over the device time of the verify programs there."""
    tr = obs.get("trace")
    if not tr:
        return None
    seconds, count = reduce.module_seconds(tr, p["modules"])
    if not count or not seconds:
        return None
    least, _bound = peaks.roofline_seconds(
        obs["device"]["kind"], obs["slice_sigs"], obs["slice_wire_bytes"])
    return 100.0 * least / seconds


def window_ms_per_op(obs, _p):
    """The whole window over all the operations answered in it."""
    return obs["window_s"] * 1e3 / len(obs["records"])


def ops_per_s(obs, _p):
    """All the operations answered over all the seconds of the window."""
    return len(obs["records"]) / obs["window_s"]


def setup_seconds(obs, _p):
    """Process start to the first timed operation."""
    return obs["setup_s"]


KINDS = {f.__name__: f for f in (
    counter_sum, counter_ratio, rung_deficit, attribution,
    latency_percentile, trace_idle, trace_roofline, window_ms_per_op,
    ops_per_s, setup_seconds)}


def reader_name(metrics_dir: str, name: str) -> str:
    """The metric's own file if it has one, else its family's: the name
    without its last `.suffix`."""
    if os.path.exists(os.path.join(metrics_dir, name + ".json")):
        return name
    return name.rpartition(".")[0] or name


def load_metric(metrics_dir: str, name: str) -> dict:
    path = os.path.join(metrics_dir, reader_name(metrics_dir, name) + ".json")
    with open(path) as fh:
        return json.load(fh)


def read_metric(metrics_dir: str, name: str, obs: dict) -> dict | None:
    """{"value", "unit"} of one metric in this run, or None."""
    spec = load_metric(metrics_dir, name)
    value = _read(metrics_dir, reader_name(metrics_dir, name),
                  spec["source"], obs)
    return None if value is None else {"value": value, "unit": spec["unit"]}


def _read(metrics_dir: str, name: str, source: dict, obs: dict):
    own = os.path.join(metrics_dir, name + ".py")
    if os.path.exists(own):
        mod_spec = importlib.util.spec_from_file_location(
            "benchmarks.metrics." + name.replace(".", "_"), own)
        module = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(module)
        return module.read(obs, source)
    return KINDS[source["kind"]](obs, source)
