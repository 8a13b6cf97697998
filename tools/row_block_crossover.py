"""Where selecting a commit's rows over columns first beats walking its
signatures: the readings behind types/commit.ROW_BLOCK_MIN.

    python3 -m tools.row_block_crossover [rows ...]

Host only (the scheduler's host rung is stubbed to an all-true mask, so no
signature is verified and no device touched): times verify_commit from
types/validation._commit_rows through crypto/batch and the scheduler's
grouping to the rows a kernel's staging entry is handed, and back through
the mask slicing and the verdict, on full commits with the benchmark's
stamp shape and the sign-rows memoised (the array pass is the same on both
paths). Every size is sent down the lane path and down the block path in
alternating blocks of repeats, with one key type and with two interleaved,
and one JSON line a size and key-type count is printed: the median and the
least of the repeats, in microseconds a call.
"""

from __future__ import annotations

import json
import random
import statistics
import sys
import time

import numpy as np

from cometbft_tpu import sched
from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.crypto import ed25519, sr25519
from cometbft_tpu.sched.scheduler import VerifyScheduler
from cometbft_tpu.types import validation
from cometbft_tpu.types.validator import Validator, ValidatorSet
from tools.sign_rows_crossover import CHAIN_ID, grid_commit

SIZES = (1, 4, 16, 36, 48, 64, 96, 128, 150, 256, 1024, 10_240)
PATHS = {"lane": 1 << 62, "block": 0}  # the constant that forces each


def committee(n: int, schemes: int, seed: int = 31):
    """(validator set, full commit over it): random 32-byte keys (nothing
    is verified), equal powers, the commit's addresses the set's."""
    rng = random.Random(seed)
    kinds = (ed25519.PubKey, sr25519.PubKey)[:schemes]
    vals = ValidatorSet([
        Validator.new(kinds[i % schemes](rng.randbytes(32)), 10)
        for i in range(n)])
    commit = grid_commit(n)
    for cs, v in zip(commit.signatures, vals.validators):
        cs.validator_address = v.address
        cs.signature = rng.randbytes(64)
    return vals, commit


def time_calls(n: int, schemes: int, repeats: int, blocks: int = 10) -> dict:
    """us a verify_commit by path; the paths take turns by blocks of
    repeats (tools/sign_rows_crossover.time_builds)."""
    vals, commit = committee(n, schemes)
    commit.vote_sign_bytes_all(CHAIN_ID)
    took: dict[str, list[float]] = {name: [] for name in PATHS}
    was = validation.ROW_BLOCK_MIN
    try:
        for _block in range(blocks):
            for name, forced in PATHS.items():
                validation.ROW_BLOCK_MIN = forced
                for _ in range(max(1, repeats // blocks)):
                    t0 = time.perf_counter()
                    validation.verify_commit(
                        CHAIN_ID, vals, commit.block_id, commit.height,
                        commit)
                    took[name].append((time.perf_counter() - t0) * 1e6)
    finally:
        validation.ROW_BLOCK_MIN = was
    return {name: {"median_us": round(statistics.median(us), 2),
                   "min_us": round(min(us), 2)}
            for name, us in took.items()}


def main(argv: list[str]) -> None:
    crypto_batch.set_backend("cpu")
    VerifyScheduler._host_mask = staticmethod(
        lambda scheme, cols: np.ones(len(cols), dtype=bool))
    sched.reset()
    for n in [int(a) for a in argv] or SIZES:
        for schemes in (1, 2):
            if n < 2:
                continue  # one signature is verified serially, unbatched
            repeats = 1000 if n <= 256 else 30
            time_calls(n, schemes, max(3, repeats // 10))  # warm
            print(json.dumps({"rows": n, "schemes": schemes,
                              "repeats": repeats,
                              **time_calls(n, schemes, repeats)}),
                  flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
