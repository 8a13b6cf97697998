"""Driver `commit_mesh`: driver `commit` as it is (one caller, closed loop,
one `verify_commit` at a time), for a cell whose commits have to cross the
multi-chip verify mesh (parallel/mesh.VerifyMesh).

It brings one seam, `build_program_objects`: the default's objects, and
then a refusal of the run (program_mesh.require_mesh) unless the mesh is
active with as many live chips as the cell's `chips` and reports that its
shards run the Pallas program. run_cell calls it after the boot of the
device plane and before the warm-up, so a program whose mesh stayed off,
or that cannot say what its shards run, ends within seconds, and can never
pass as a four-chip reading.
"""

from __future__ import annotations

from benchmarks.drivers.commit import Driver  # noqa: F401 - the cell's loop


def build_program_objects(cell) -> None:
    from benchmarks import program_mesh, run

    run.build_program_objects(cell)
    program_mesh.require_mesh(cell.chips)
