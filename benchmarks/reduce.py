"""From a profiler trace (.xplane.pb) to numbers: device busy time, the
device time of each XLA module, the operations that took most time, and
the longest idle gaps named by what the host was doing.

The reduction works on a plain form of the trace (`planes`: a list of
{"name", "lines": [{"name", "events": [[name, start_ns, dur_ns], ...]}]}),
so that a small recorded slice can be checked by hand
(benchmarks/tests/trace_slice.json). load_xplane() makes that form from
the file jax.profiler wrote, with nothing but JAX.

On a TPU v5e the device plane is "/device:TPU:<n>". Its line "XLA Modules"
holds one event per execution of a compiled program, named
"<module>(<fingerprint>)"; "XLA Ops" holds the operations inside them. The
host plane "/host:CPU" has one line per thread; the benchmark's own
TraceAnnotations are the events named "bench.*" there.

`python3 -m benchmarks.reduce <file.xplane.pb>` prints a summary (planes,
lines, the names that took most time): look at one before trusting a
pattern.
"""

from __future__ import annotations

import glob
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
ANNOTATION_PREFIX = "bench."
TOP = 10


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> list[dict]:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not (DEVICE_PLANE.match(plane.name) or plane.name == HOST_PLANE):
            continue
        planes.append({"name": plane.name, "lines": [
            {"name": line.name,
             "events": [[e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in line.events]}
            for line in plane.lines]})
    return planes


def op_name(event_name: str) -> str:
    """An XLA op's event name is its whole HLO line: keep what is left of
    the " = ", without the "%"."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def module_name(event_name: str) -> str:
    """"jit__verify(1234567)" -> "jit__verify": the fingerprint changes
    with every build of the program."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def _clip(events, lo: int, hi: int):
    """Events cut to [lo, hi): [(name, start, end)], empty ones dropped."""
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            yield name, s, e


def _window(planes: list[dict]) -> tuple[int, int]:
    """The traced slice on the trace's clock: from the start of the first
    of the benchmark's annotations to the end of the last."""
    marks = [(s, s + d) for p in planes if p["name"] == HOST_PLANE
             for line in p["lines"] for n, s, d in line["events"]
             if n.startswith(ANNOTATION_PREFIX)]
    if not marks:
        raise ValueError("the trace holds none of the benchmark's "
                         f"annotations ({ANNOTATION_PREFIX}*)")
    return min(m[0] for m in marks), max(m[1] for m in marks)


def _host_names_at(host_events: list[tuple[str, int, int]],
                   instants: list[int]) -> dict[int, str]:
    """For each instant, the innermost (shortest) host event that covers
    it: one sweep over both, sorted by time."""
    events = sorted(host_events, key=lambda ev: ev[1])
    names: dict[int, str] = {}
    active: list[tuple[str, int, int]] = []
    i = 0
    for t in sorted(set(instants)):
        while i < len(events) and events[i][1] <= t:
            active.append(events[i])
            i += 1
        active = [ev for ev in active if ev[2] > t]
        names[t] = (min(active, key=lambda ev: ev[2] - ev[1])[0]
                    if active else "(no host event)")
    return names


def reduce_planes(planes: list[dict]) -> dict:
    """Seconds throughout. busy_s and idle gaps are averaged over the
    device planes; module and op times are summed over them."""
    lo, hi = _window(planes)
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    host_events = [ev for p in planes if p["name"] == HOST_PLANE
                   for line in p["lines"]
                   for ev in _clip(line["events"], lo, hi)]
    busy_ns = 0
    modules: dict[str, list] = {}
    ops: dict[str, int] = {}
    idle: list[tuple[int, int]] = []  # (midpoint, length) of every gap
    for plane in devices:
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        # a program's execution is one interval: the thousands of small
        # operations inside it add nothing to the union but reading time
        running = lines.get(MODULES_LINE) or lines.get(OPS_LINE) or []
        busy = _union([(s, e) for _n, s, e in _clip(running, lo, hi)])
        busy_ns += sum(e - s for s, e in busy)
        for name, s, e in _clip(lines.get(MODULES_LINE, []), lo, hi):
            entry = modules.setdefault(module_name(name), [0, 0])
            entry[0] += 1
            entry[1] += e - s
        for name, s, e in _clip(lines.get(OPS_LINE, []), lo, hi):
            name = op_name(name)
            ops[name] = ops.get(name, 0) + e - s
        edges = [(lo, lo)] + busy + [(hi, hi)]
        for (_s0, e0), (s1, _e1) in zip(edges, edges[1:]):
            if s1 > e0:
                idle.append(((e0 + s1) // 2, s1 - e0))
    names = _host_names_at(host_events, [mid for mid, _len in idle])
    gaps: dict[str, int] = {}
    for mid, length in idle:
        gaps[names[mid]] = gaps.get(names[mid], 0) + length
    n = len(devices)

    def top(table: dict) -> list:
        return [[k, v / 1e9] for k, v in sorted(
            table.items(), key=lambda kv: -kv[1])[:TOP]]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "modules": {k: {"count": c, "seconds": ns / 1e9}
                    for k, (c, ns) in modules.items()},
        "device_ops": top(ops or {k: v[1] for k, v in modules.items()}),
        "idle_gaps": top({k: v // n for k, v in gaps.items()}),
    }


def reduce_trace_dir(trace_dir: str) -> dict:
    return reduce_planes(load_xplane(find_xplane(trace_dir)))


def module_seconds(reduced: dict, patterns: list[str]) -> tuple[float, int]:
    """(seconds, executions) of the modules whose name matches any of the
    regular expressions."""
    seconds, count = 0.0, 0
    for name, entry in reduced["modules"].items():
        if any(re.search(p, name) for p in patterns):
            seconds += entry["seconds"]
            count += entry["count"]
    return seconds, count


def summary(path: str) -> str:
    """A look at a trace by hand."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            totals: dict[str, list] = {}
            n = 0
            for e in line.events:
                n += 1
                t = totals.setdefault(op_name(module_name(e.name)), [0, 0.0])
                t[0] += 1
                t[1] += e.duration_ns
            out.append(f"  LINE {line.name}: {n} events")
            for name, (c, ns) in sorted(totals.items(),
                                        key=lambda kv: -kv[1][1])[:25]:
                out.append(f"    {ns / 1e6:12.3f} ms  x{c:<7d} {name[:110]}")
    return "\n".join(out)


if __name__ == "__main__":
    print(summary(sys.argv[1]))
