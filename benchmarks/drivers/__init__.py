"""Drivers: the loops that offer a cell's traffic to the program's entries.

A driver is a module `benchmarks/drivers/<name>.py` named by a traffic
file's "driver" key. It has a class Driver(cell, entries) with warm() and
window(seconds, tick) -> (records, window_seconds). A new driver is a new
file. run.load_cell imports it before the cell's data is made, so at its
top it imports nothing of JAX and nothing of the program (benchmarks.program
imports both only inside its functions).

With nothing else on the module an operation verifies one full commit of
the cell's ONE validator set, made by run.make_data, handed to the program
by run.build_program_objects, driven through program.entries() and judged
by check.reference_verdicts (VerifyCommit). A cell of another shape (several
validator sets, light blocks, absent votes, another entry of the program)
brings, on the same module, any of these in their place (run.seam):

    make_data(cell, seed)             fills the cell from the seed; imports
                                      nothing of JAX or of the program
    build_program_objects(cell)       the program's objects for that data
    entries(), control_entries()      {name: callable} the Driver is handed;
                                      what imports cometbft_tpu for them is
                                      a file benchmarks/program_<x>.py
    reference_verdicts(cell, sample)  ({record.k: verdict}, lanes verified),
                                      from a plain benchmarks/reference/<x>_ref.py
    sigs_of(cell, record)             {scheme: signatures} the operation
                                      really verifies (the rooflines' count)

Every record has Record's fields whatever the cell: check.draw_sample and
check.compare read k, corrupt_lane and verdict; the readers t_start, t_end.
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
