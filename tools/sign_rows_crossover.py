"""Where the array pass of a commit's sign-rows first beats one Writer a
signature: the readings behind types/commit.VECTOR_SIGN_ROWS_MIN.

    python3 -m tools.sign_rows_crossover [rows ...]

Host only (no JAX, no device): times Commit._build_sign_rows on full
commits with the benchmark's stamp shape (a millisecond grid inside one
second), with every size sent to the scalar builder and to the vector
one in alternating blocks of repeats, and prints one JSON line a size:
the median and the least of the repeats, in microseconds a build.
"""

from __future__ import annotations

import json
import random
import statistics
import sys
import time

from cometbft_tpu.types import commit as commit_mod
from cometbft_tpu.types.basic import BlockID, BlockIDFlag, PartSetHeader
from cometbft_tpu.types.commit import Commit, CommitSig
from cometbft_tpu.utils import cmttime

CHAIN_ID = "committee-10k"
SIZES = (1, 4, 8, 16, 24, 32, 36, 40, 48, 64, 150, 10_240)
BUILDERS = {"scalar": 1 << 62, "vector": 0}  # the constant that forces each


def grid_commit(n: int, seed: int = 29) -> Commit:
    millis = [j * 1000 // n for j in range(n)]
    random.Random(seed).shuffle(millis)
    sigs = [CommitSig(BlockIDFlag.COMMIT, i.to_bytes(20, "big"),
                      cmttime.Timestamp(1_790_000_000, ms * 1_000_000),
                      bytes(64))
            for i, ms in enumerate(millis)]
    block_id = BlockID(hash=b"\x01" * 32,
                       part_set_header=PartSetHeader(total=1,
                                                     hash=b"\x02" * 32))
    return Commit(height=1_000_000, round_=0, block_id=block_id,
                  signatures=sigs)


def time_builds(n: int, repeats: int, blocks: int = 10) -> dict:
    """us a build by builder; the builders take turns by blocks of
    repeats, so that neither runs in the other's wake nor in another
    stretch of the host's."""
    commit = grid_commit(n)
    took: dict[str, list[float]] = {name: [] for name in BUILDERS}
    was = commit_mod.VECTOR_SIGN_ROWS_MIN
    try:
        for _block in range(blocks):
            for name, forced in BUILDERS.items():
                commit_mod.VECTOR_SIGN_ROWS_MIN = forced
                for _ in range(max(1, repeats // blocks)):
                    commit._sign_rows = {}
                    t0 = time.perf_counter()
                    commit._build_sign_rows(CHAIN_ID)
                    took[name].append((time.perf_counter() - t0) * 1e6)
    finally:
        commit_mod.VECTOR_SIGN_ROWS_MIN = was
    return {name: {"median_us": round(statistics.median(us), 2),
                   "min_us": round(min(us), 2)}
            for name, us in took.items()}


def main(argv: list[str]) -> None:
    for n in [int(a) for a in argv] or SIZES:
        repeats = 2000 if n <= 150 else 30
        time_builds(n, max(3, repeats // 10))  # warm
        print(json.dumps({"rows": n, "repeats": repeats,
                          **time_builds(n, repeats)}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
