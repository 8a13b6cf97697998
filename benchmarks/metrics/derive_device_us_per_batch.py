"""Device time of the matching XLA modules in the traced slice over their
executions there, in microseconds: `source.modules` are regular
expressions on the module's name (benchmarks/reduce.module_seconds). None
without a trace, and None where no module matches: a program whose derive
program has another name reads nothing."""

from benchmarks import reduce


def read(obs, params):
    trace = obs.get("trace")
    if not trace:
        return None
    seconds, executions = reduce.module_seconds(trace, params["modules"])
    if not executions:
        return None
    return 1e6 * seconds / executions
