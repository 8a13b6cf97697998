"""The comparison that decides `correct`.

After the window has closed: every corrupt operation of the window and a
sample of its clean ones, drawn from the seed, are compared with the plain
reference's verdict on the same commit (benchmarks/reference/commit_ref.py
over the benchmark's own records; the lanes are verified by plain worker
processes and shared between commits). A cell whose operations are not
VerifyCommit's brings its own `reference_verdicts(cell, sample)` on its
driver's module, over a plain reference of its own; the sample, the five
numbers and their limits are the same for every cell. Every number compared
is printed beside its limit.

  verdict_mismatches  compared answers that differ from the reference's 0
  errors              answers of the WHOLE window that are no verdict   0
  offchip_batches     batches served below the rung that is due         0
  host_rescued_lanes  lanes of the WHOLE window that the device
                      condemned and the host-oracle re-check accepted   0
  corrupt_compared    corrupt operations among those compared         >=5

All are exact counts, so their limits are the configuration's own: every
verdict equals the reference's, every batch on its rung, every verdict the
device's own (a right answer from the host oracle is one that the device
path did not give). Five corrupt operations in a row reach every third of
the validator set (datagen.Schedule), so the control, which stops at the
quorum, gets one of them wrong on every seed.
"""

from __future__ import annotations

import random

from benchmarks import workers
from benchmarks.reference import commit_ref


MIN_CORRUPT = 5


def draw_sample(records: list, clean_size: int, seed: int) -> list:
    """Every corrupt operation of the window, and `clean_size` of the
    clean ones."""
    rng = random.Random(seed ^ 0xC4EC)
    corrupt = [r for r in records if r.corrupt_lane is not None]
    clean = [r for r in records if r.corrupt_lane is None]
    if len(clean) > clean_size:
        clean = rng.sample(clean, clean_size)
    return sorted(corrupt + clean, key=lambda r: r.k)


def reference_verdicts(cell, sample: list) -> tuple[dict, int]:
    """({record.k: the reference's verdict}, lanes verified) for the
    sampled operations."""
    specs = {}
    for r in sample:
        spec = cell.ring[r.ring_idx]
        specs[r.k] = (spec if r.corrupt_lane is None
                      else spec.with_flipped(r.corrupt_lane))
    lanes = sorted({lane for spec in specs.values()
                    for lane in commit_ref.commit_lanes(cell.vals_spec, spec)})
    memo = dict(zip(lanes, workers.map("verify_lane", lanes)))
    return ({k: commit_ref.verdict(cell.vals_spec, spec, memo.__getitem__)
             for k, spec in specs.items()}, len(lanes))


def compare(cell, records: list, offchip_batches, host_rescued_lanes,
            seed: int) -> dict:
    """{"correct": bool, "compared": {name: {value, limit, sense}}, ...}.
    The two counters are the program's, differenced over the window."""
    sample = draw_sample(records, int(cell.traffic["check_clean_sample"]),
                         seed)
    # the cell's own reference where its driver's module brings one
    # (run.seam), else VerifyCommit's
    expected, n_lanes = getattr(cell.driver, "reference_verdicts",
                                reference_verdicts)(cell, sample)
    wrong = [(r.k, r.verdict, expected[r.k]) for r in sample
             if r.verdict != expected[r.k]]
    errors = sum(r.verdict.startswith("error:") for r in records)
    compared = {
        "verdict_mismatches": {"value": len(wrong), "limit": 0,
                               "sense": "max"},
        "errors": {"value": errors, "limit": 0, "sense": "max"},
        # a counter that could not be read shows nothing: -1 fails
        "offchip_batches": {
            "value": offchip_batches if offchip_batches is not None else -1,
            "limit": 0, "sense": "max"},
        "host_rescued_lanes": {
            "value": (host_rescued_lanes if host_rescued_lanes is not None
                      else -1),
            "limit": 0, "sense": "max"},
        "corrupt_compared": {
            "value": sum(r.corrupt_lane is not None for r in sample),
            "limit": MIN_CORRUPT, "sense": "min"},
    }
    ok = all((n["value"] >= n["limit"]) if n["sense"] == "min"
             else (0 <= n["value"] <= n["limit"]) for n in compared.values())
    return {"correct": ok, "compared": compared, "sampled": len(sample),
            "reference_lanes": n_lanes, "first_wrong": wrong[:5]}
