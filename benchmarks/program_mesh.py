"""What a mesh cell takes from the program besides program.py's: the
report the multi-chip verify mesh gives of itself.

`require_mesh(chips)` builds the mesh as the scheduler's first batch would
(parallel.mesh.active(): the node's default `crypto` section, applied by
program.boot_device_plane, has it on from two chips) and reads the `mesh`
section of the crypto_health snapshot (ops.dispatch.health_snapshot). The
run is refused unless that section says: active, as many devices and live
chips as the cell asks for, and ed25519 shards on the Pallas program
(`shard_program`). A program without that report (every tree before
PR 33, whose shards ran the XLA ladder) is refused here, before any
warm-up. chip_smoke.py's check_rungs holds the smoke to the like.
"""

from __future__ import annotations

from benchmarks.program import BenchFailure


def require_mesh(chips: int) -> dict:
    """The mesh's own report, or no run."""
    from cometbft_tpu.ops import dispatch
    from cometbft_tpu.parallel import mesh

    if mesh.active() is None:
        raise BenchFailure(
            "the verify mesh is not active: the cell's commits would ride "
            "one chip")
    report = dispatch.health_snapshot()["mesh"]
    if not (report.get("active") is True and report.get("devices") == chips
            and report.get("live") == chips):
        raise BenchFailure(
            f"the cell needs a mesh of {chips} live chips, the program "
            f"reports active={report.get('active')} "
            f"devices={report.get('devices')} live={report.get('live')}")
    programs = report.get("shard_program")
    if not isinstance(programs, dict) or programs.get("ed25519") != "pallas":
        raise BenchFailure(
            "the mesh does not report ed25519 shards on the Pallas program "
            f"(shard_program = {programs!r}): a reading of it would be a "
            "reading of the XLA ladder")
    return report
