"""Driver `catchup`: blocksync's verify pipeline without pool, store or
ABCI apply (blocksync/reactor.py `_pool_routine`): stage a window of
heights, resolve it with one `prefetch_staged(window, klass="sync")` on a
worker thread while the next window is staged, then `finish()` each commit.

Drives types.validation.stage_verify_commit, prefetch_staged and
StagedCommitVerification.finish, and nothing below them. An operation is
one block.
"""

from __future__ import annotations

import concurrent.futures
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
        self.stage_verify_commit = entries["stage_verify_commit"]
        self.prefetch_staged = entries["prefetch_staged"]
        self.heights = int(cell.traffic["window_heights"])

    def _stage(self, w: int) -> list:
        """Stage window w: [(k, ring_idx, lane, staged | verdict, t0)]."""
        cell = self.cell
        out = []
        with self.annotate("bench.stage_window"):
            for k in range(w * self.heights, (w + 1) * self.heights):
                ring_idx, lane = cell.schedule.op(k)
                block_id, commit = cell.commits[ring_idx]
                commit = program.fresh(commit, lane)
                t0 = time.perf_counter()
                box = []
                verdict = program.verdict_of(lambda: box.append(
                    self.stage_verify_commit(
                        cell.vals_spec.chain_id, cell.vals, block_id,
                        commit.height, commit)))
                out.append((k, ring_idx, lane,
                            box[0] if box else verdict, t0))
        return out

    def _prefetch(self, staged: list) -> None:
        with self.annotate("bench.prefetch_staged"):
            self.prefetch_staged(
                [s[3] for s in staged if not isinstance(s[3], str)],
                klass="sync")

    def _run(self, stop, tick=None) -> tuple[list[Record], float]:
        """Windows 0, 1, 2 ... while stop(elapsed, w) is false when the
        next one is due to be staged; what is staged is always finished."""
        records: list[Record] = []
        start = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(1) as worker:
            w = 0
            staged = self._stage(w)
            while staged:
                fetch = worker.submit(self._prefetch, staged)
                w += 1
                elapsed = time.perf_counter() - start
                if tick is not None:
                    tick(elapsed)
                ahead = [] if stop(elapsed, w) else self._stage(w)
                fetch_error = None
                try:
                    fetch.result()
                except Exception as exc:  # noqa: BLE001 - answers say so
                    fetch_error = f"error:{type(exc).__name__}"
                with self.annotate("bench.finish_window"):
                    for k, ring_idx, lane, item, t0 in staged:
                        verdict = (item if isinstance(item, str)
                                   else fetch_error
                                   or program.verdict_of(item.finish))
                        records.append(Record(k, ring_idx, lane, verdict, t0,
                                              time.perf_counter()))
                staged = ahead
        return records, records[-1].t_end - start

    def warm(self) -> int:
        """One pass over the ring, window by window as the measured window
        will group it, then on until a corrupt block has been rejected."""
        cell = self.cell
        windows = -(-len(cell.commits) // self.heights)
        first_corrupt = next(k for k in range(64 * cell.schedule.every)
                             if cell.schedule.op(k)[1] is not None)
        windows = max(windows, first_corrupt // self.heights + 1)
        records, _ = self._run(lambda _elapsed, w: w >= windows)
        return len(records)

    def window(self, seconds: float, tick=None) -> tuple[list[Record], float]:
        return self._run(lambda elapsed, _w: elapsed >= seconds, tick)
