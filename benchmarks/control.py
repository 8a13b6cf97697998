#!/usr/bin/env python3
"""The control of a cell: the same run, with the program's own quorum-only
paths (`verify_commit_light`, `stage_verify_commit_light`: they stop once
more than 2/3 of the power has signed) in the place of the full ones. That
breaks the guarantee every configuration states (every non-absent signature
is checked), so the result has to read `"correct": false`: a corrupt
signature behind the quorum is accepted where the reference rejects it.
A cell whose driver module brings `control_entries()` is given those
(run.seam): the control that breaks ITS configuration's guarantee.

    python3 benchmarks/control.py --workload <name> --seed <n> --seconds <s>

The driver's check never runs this; it is how the limits in PERF.md got
their upper readings, and benchmarks/tests/ keeps it at a small size.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from benchmarks import run

    result = run.run_cell(ROOT, args.workload, args.seed, args.seconds, False,
                          entries="control_entries")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
