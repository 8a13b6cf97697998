#!/usr/bin/env python3
"""One run of one cell of the benchmark, on the chip.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

looks the cell up in BENCHMARK.json, loads its configuration
(benchmarks/configs/), its traffic mix (benchmarks/traffic/) and the driver
the mix names (benchmarks/drivers/), makes keys and presigned commits from
the seed, boots the device plane as a node does, warms the cell's own
shapes, measures for --seconds, and then compares a sample of the answers
with the plain reference. Where the driver's module brings a function of
its own for the cell's data, the program's objects, its entries, its
reference or the signatures an operation verifies (`seam` below), that one
runs; where it brings none, the functions of this file, program.py and
check.py do, as for every cell before PR 32. --trace 0 reports the cell's
end-to-end metrics; --trace 1 wraps a slice of the window in a profiler
trace and reports its per-layer metrics (benchmarks/metrics/<name>.json
each). The last line of stdout is the result; everything else goes before
it or to stderr.

There is no CPU mode, no size option and no environment switch: without a
TPU the run fails and prints no result. benchmarks/tests/ rehearses the
same code on the CPU at a tiny committee by calling run_cell() itself.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_DIR = os.path.join(ROOT, ".bench_trace")
TRACE_SLICE_START_S = 1.0


def say(msg: str) -> None:
    print(msg, flush=True)


@dataclasses.dataclass
class Cell:
    """One entry of BENCHMARK.json's workloads, with its files read."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[str]
    per_layer: list[str]
    driver: object          # the module benchmarks/drivers/<traffic's driver>
    # filled by set-up
    vals_spec: object = None
    ring: list = None
    schedule: object = None
    vals: object = None
    commits: list = None


def _read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_cell(root: str, name: str) -> Cell:
    """Everything a cell is, found by the names in BENCHMARK.json."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    work = next((w for w in bench["workloads"] if w["name"] == name), None)
    if work is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == work["config"])

    def reported(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    # a per-layer metric without `workloads` is reported by every cell
    # that reports the end-to-end metric it moves
    end_to_end = [m["name"] for m in bench["end_to_end"] if reported(m)]
    traffic = _read_json(os.path.join(
        root, "benchmarks", "traffic", work["traffic"] + ".json"))
    return Cell(
        name=name, chips=int(work["chips"]),
        config=_read_json(os.path.join(root, conf["file"])),
        traffic=traffic, end_to_end=end_to_end,
        per_layer=[m["name"] for m in bench["per_layer"]
                   if reported(m) and m["moves"] in end_to_end],
        # imported before the data is made, so the module itself imports
        # nothing of JAX or of the program (see make_data)
        driver=importlib.import_module(
            "benchmarks.drivers." + traffic["driver"]))


def seam(cell: Cell, name: str, default):
    """The cell's own `name` where its driver's module brings one, else
    `default`: what ran for every cell before there were seams. The five:
    make_data(cell, seed), build_program_objects(cell), entries() /
    control_entries(), reference_verdicts(cell, sample) (check.py) and
    sigs_of(cell, record)."""
    return getattr(cell.driver, name, default)


class TraceSlice:
    """Starts and stops the profiler around a slice of the window, from
    the driver's tick between operations, and keeps the counters read at
    both edges. The slice ends after `length_s` seconds or `max_ticks`
    ticks, whichever comes first: the device line of the trace holds
    thousands of events for every derive program run, and stop_trace
    takes about a second for every two megabytes of them."""

    def __init__(self, counters, length_s: float, max_ticks: int):
        self.counters = counters
        self.length_s = length_s
        self.ticks_left = max_ticks
        self.state = "waiting"
        self.edges: list[tuple[float, dict]] = []

    def tick(self, elapsed: float) -> None:
        import jax

        if self.state == "tracing":
            self.ticks_left -= 1
        if self.state == "waiting" and elapsed >= TRACE_SLICE_START_S:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
            self.edges.append((time.perf_counter(), self.counters.read()))
            self.state = "tracing"
        elif self.state == "tracing" and (
                self.ticks_left <= 0 or time.perf_counter()
                - self.edges[0][0] >= self.length_s):
            self.stop()

    def stop(self) -> None:
        import jax

        if self.state == "tracing":
            self.edges.append((time.perf_counter(), self.counters.read()))
            jax.profiler.stop_trace()
            self.state = "done"


def make_data(cell: Cell, seed: int) -> None:
    """Seeded keys, the ring of presigned commits and the order of the
    window's operations: the benchmark's own data, made before anything
    of the program or of JAX is imported; run_cell then keeps it out of
    Python's collector (gc.freeze): it stays for the whole run, and no
    node holds it. Everything made after this (the program's modules, its
    validator set, caches and tables, the Commit objects a peer would hand
    over, what warm-up leaves behind) stays in the collector's generations,
    so a full collection inside the window costs what it costs a node."""
    from benchmarks import datagen

    t0 = time.perf_counter()
    cell.vals_spec, signers = datagen.make_validators(cell.config, seed)
    cell.ring = datagen.make_ring(cell.config, cell.vals_spec, signers, seed)
    cell.schedule = datagen.Schedule(cell.traffic, len(cell.ring),
                                     len(cell.vals_spec.pubs), seed)
    say(f"[set-up] {len(cell.vals_spec.pubs)} validators, "
        f"{len(cell.ring)} presigned commits from seed {seed}: "
        f"{time.perf_counter() - t0:.1f} s")


def build_program_objects(cell: Cell) -> None:
    """The program's validator set and the commits as a peer hands them
    over, from the benchmark's data."""
    from benchmarks import program

    cell.vals = program.build_validator_set(cell.vals_spec)
    cell.commits = [program.build_commit(cell.vals, spec)
                    for spec in cell.ring]


def sigs_of(cell: Cell, _record) -> dict:
    """{scheme: signatures} that one operation verifies: every validator
    of the set (a full commit under VerifyCommit). What the rooflines
    count as the work of the traced slice."""
    return collections.Counter(cell.vals_spec.schemes)


def sum_sigs(cell: Cell, records) -> dict:
    count = seam(cell, "sigs_of", sigs_of)
    total = collections.Counter()
    for record in records:
        total.update(count(cell, record))
    return dict(total)


def observe_trace(tracer: TraceSlice, obs: dict) -> dict | None:
    """Reduce the traced slice into obs (the trace, the signatures and wire
    bytes of the slice, the device's busy_s and window_s) and return the
    result's `breakdown`."""
    from benchmarks import program, reduce

    if tracer.state != "done":
        return None
    tr = reduce.reduce_trace_dir(TRACE_DIR)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    (t_a, c_a), (t_b, c_b) = tracer.edges
    in_slice = [r for r in obs["records"]
                if t_a <= (r.t_start + r.t_end) / 2 < t_b]
    sliced = program.Counters.diff(c_a, c_b)
    obs["trace"] = tr
    obs["slice_sigs"] = sum_sigs(obs["cell"], in_slice)
    obs["slice_wire_bytes"] = sum(
        sliced.get(f"staging.wire.{p}.bytes", 0)
        for p in ("indexed", "delta", "full"))
    obs["device"]["busy_s"] = tr["busy_s"]
    obs["device"]["window_s"] = tr["window_s"]
    say(f"[trace] slice of {tr['window_s']:.3f} s, {len(in_slice)} "
        f"operations in it; modules: "
        + json.dumps(tr["modules"], sort_keys=True))
    return {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}


def run_cell(root: str, name: str, seed: int, seconds: float, trace: bool,
             *, entries: dict | str = "entries",
             on_chip: bool = True) -> dict:
    """The whole of a run but the printing. `entries` names the seam that
    gives the callables the window drives ("entries": the program's;
    "control_entries": the control's), or is a dict of other callables in
    their place (the tests' planted faults); on_chip=False skips the look
    for a TPU (the tests' CPU rehearsal)."""
    cell = load_cell(root, name)
    seam(cell, "make_data", make_data)(cell, seed)
    gc.collect()
    gc.freeze()
    from benchmarks import check, program, readers

    device = program.probe_device(cell.chips) if on_chip else {
        "platform": "cpu", "kind": "rehearsal", "count": 1}
    cache_dir = program.boot_device_plane()
    say(f"[set-up] device {device}; compile cache {cache_dir}")
    counters = program.Counters()
    try:
        seam(cell, "build_program_objects", build_program_objects)(cell)
        if isinstance(entries, str):
            entries = seam(cell, entries, getattr(program, entries))()
        driver = cell.driver.Driver(cell, entries)
        t0 = time.perf_counter()
        with program.warmup_watchdog():
            warmed = driver.warm()
        say(f"[set-up] warm-up, {warmed} operations: "
            f"{time.perf_counter() - t0:.1f} s; {counters.compiles} programs "
            f"built, {counters.cache_hits} of them loaded from the cache")

        tracer = None
        if trace:
            program.switch_host_tracer(True)
            tracer = TraceSlice(
                counters,
                min(float(cell.traffic["trace_slice_s"]), seconds / 2),
                int(cell.traffic["trace_slice_ticks"]))
        before = counters.read()
        setup_s = time.perf_counter() - PROCESS_START
        records, window_s = driver.window(
            seconds, tracer.tick if tracer else None)
        if tracer:
            tracer.stop()
        after = counters.read()
        attribution = program.host_attribution() if trace else None
        if trace:
            program.switch_host_tracer(False)
        device["memory_peak_bytes"] = program.memory_peak_bytes()
    finally:
        counters.close()

    obs = {"cell": cell, "device": device, "records": records,
           "window_s": window_s, "setup_s": setup_s,
           "attribution": attribution,
           "counters": program.Counters.diff(before, after), "trace": None}
    sigs = sum(sum_sigs(cell, records).values())
    say(f"[window] {len(records)} operations ({sigs} signatures) in "
        f"{window_s:.3f} s: {sigs / window_s:.0f} signatures/s; "
        f"{sum(r.corrupt_lane is not None for r in records)} corrupt "
        f"operations offered; link model "
        f"{ {k[5:]: v for k, v in after.items() if k.startswith('link.')} }")
    clean_ms = [r.ms for r in records if r.corrupt_lane is None]
    corrupt_ms = [r.ms for r in records if r.corrupt_lane is not None]
    if clean_ms and corrupt_ms:
        say(f"[window] a call, host clock: clean {len(clean_ms)} of mean "
            f"{sum(clean_ms) / len(clean_ms):.3f} ms, corrupt "
            f"{len(corrupt_ms)} of mean "
            f"{sum(corrupt_ms) / len(corrupt_ms):.3f} ms, all "
            f"{sum(clean_ms + corrupt_ms) / len(records):.3f} ms; "
            f"collector: {gc.get_stats()}")
    sixths = [[r.ms for r in records
               if i <= 6 * (r.t_start - records[0].t_start) / window_s < i + 1]
              for i in range(6)]
    say("[window] mean of a call in each sixth of the window, ms: "
        + ", ".join(f"{sum(s) / len(s):.3f}" for s in sixths if s))
    slowest = sorted(records, key=lambda r: -r.ms)[:12]
    say("[window] slowest operations, ms (c = corrupt): " + ", ".join(
        f"{r.ms:.1f}{'c' if r.corrupt_lane is not None else ''}@{r.k}"
        for r in slowest) + "; host-rescued lanes (device mask overturned "
        f"by the host oracle): "
        f"{obs['counters'].get('metrics.mask_oracle_disagreement')}")
    breakdown = observe_trace(tracer, obs) if tracer else None

    metrics_dir = os.path.join(root, "benchmarks", "metrics")
    metrics = {}
    for metric in (cell.per_layer if trace else cell.end_to_end):
        reading = readers.read_metric(metrics_dir, metric, obs)
        if reading is not None:
            metrics[metric] = reading

    t0 = time.perf_counter()
    verdict = check.compare(
        cell, records, readers.rung_deficit(obs, None),
        obs["counters"].get("metrics.mask_oracle_disagreement"), seed)
    say(f"[check] {verdict['sampled']} answers compared with the "
        f"reference over {verdict['reference_lanes']} lanes in "
        f"{time.perf_counter() - t0:.1f} s; first differences: "
        f"{verdict['first_wrong']}")
    return {
        "correct": verdict["correct"],
        "attempted": len(records),
        "failed": sum(r.verdict.startswith("error:") for r in records),
        "metrics": metrics,
        "device": device,
        **({"breakdown": breakdown} if breakdown else {}),
        "compared": verdict["compared"],
    }


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchmarks import program

    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except program.BenchFailure as exc:
        print(f"benchmark cannot run: {exc}", file=sys.stderr, flush=True)
        return 1
    for name, n in result["compared"].items():
        print(f"compared {name}: {n['value']} (limit {n['limit']}, "
              f"{n['sense']})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
