"""Least chip time for the sr25519 signatures of the traced slice alone
(benchmarks/peaks.py: the textbook count, whatever implements it) over the
device time of the sr25519 verify program there, in percent.
`source.modules` are regular expressions on the module's name
(benchmarks/reduce.module_seconds); `trace_roofline` counts every scheme's
signatures over every verify module, this one scheme's over its own. The
slice's wire bytes are not split by scheme and are left out: operations
bound this kernel by two orders of magnitude (peaks.roofline_seconds).
None without a trace, where the slice verified no sr25519 signature, and
where no module matches: a program whose sr25519 ladder runs under another
name (until PR 28 it shared `jit__verify_pallas_bench`) reads nothing."""

from benchmarks import peaks, reduce


def read(obs, params):
    trace = obs.get("trace")
    if not trace:
        return None
    seconds, executions = reduce.module_seconds(trace, params["modules"])
    sigs = (obs.get("slice_sigs") or {}).get("sr25519")
    if not executions or not seconds or not sigs:
        return None
    least, _bound = peaks.roofline_seconds(
        obs["device"]["kind"], {"sr25519": sigs}, 0)
    return 100.0 * least / seconds
