"""Drivers: the loops that offer a cell's traffic to the program's entries.

A driver is a module `benchmarks/drivers/<name>.py` named by a traffic
file's "driver" key. It has a class Driver(cell, entries) with warm() and
window(seconds, tick) -> (records, window_seconds). An operation verifies
one commit of the cell's validator set. A new driver is a new file.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Record:
    """One operation (one commit; one block in a catch-up) and its answer."""

    k: int                  # the schedule's operation number
    ring_idx: int
    corrupt_lane: int | None
    verdict: str            # "accept" | "reject#i" | "reject:power" | "error:*"
    t_start: float          # perf_counter seconds
    t_end: float

    @property
    def ms(self) -> float:
        return (self.t_end - self.t_start) * 1e3
