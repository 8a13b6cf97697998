"""Driver `commit`: one caller, closed loop. A node verifies one block's
commit at a time: the next `verify_commit` starts when the last returned.

Drives types.validation.verify_commit(chain_id, vals, block_id, height,
commit) and nothing below it.
"""

from __future__ import annotations

import time

from benchmarks import program
from benchmarks.drivers import Record


class Driver:
    def __init__(self, cell, entries: dict):
        # not at the module's top: run.load_cell imports this module before
        # the data is made, and nothing of JAX may be imported by then
        from jax.profiler import TraceAnnotation

        self.annotate = TraceAnnotation
        self.cell = cell
        self.verify_commit = entries["verify_commit"]

    def _one(self, k: int) -> Record:
        cell = self.cell
        ring_idx, lane = cell.schedule.op(k)
        block_id, commit = cell.commits[ring_idx]
        commit = program.fresh(commit, lane)
        t0 = time.perf_counter()
        with self.annotate("bench.verify_commit"):
            verdict = program.verdict_of(lambda: self.verify_commit(
                cell.vals_spec.chain_id, cell.vals, block_id, commit.height,
                commit))
        return Record(k, ring_idx, lane, verdict, t0, time.perf_counter())

    def warm(self) -> int:
        """One pass over the ring in the window's own order (every derive
        geometry the ring can ask for), then the first corrupt operations
        of the window's schedule until each scheme has rejected once."""
        cell = self.cell
        ops = list(range(len(cell.commits)))
        schemes = set(cell.vals_spec.schemes)
        k = 0
        while schemes and k < 64 * cell.schedule.every:
            lane = cell.schedule.op(k)[1]
            if lane is not None and cell.vals_spec.schemes[lane] in schemes:
                schemes.discard(cell.vals_spec.schemes[lane])
                ops.append(k)
            k += 1
        for k in ops:
            self._one(k)
        return len(ops)

    def window(self, seconds: float, tick=None) -> tuple[list[Record], float]:
        """Operations 0, 1, 2 ... until `seconds` have passed; the call in
        flight at the deadline is finished and counted, and the window is
        as long as it took. tick(elapsed) is called between operations."""
        records: list[Record] = []
        start = time.perf_counter()
        k = 0
        while True:
            elapsed = time.perf_counter() - start
            if elapsed >= seconds:
                break
            if tick is not None:
                tick(elapsed)
            records.append(self._one(k))
            k += 1
        return records, records[-1].t_end - start
