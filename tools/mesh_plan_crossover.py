"""How finely the verify mesh should divide one commit: the readings behind
parallel/mesh.MAX_SHARD_ROWS.

    chiprun --chips 4 -- python3 -m tools.mesh_plan_crossover [--validators N]
        [--calls K] [--caps 1024,2048,2560] [--one-chip]

On the chips only (a TPU with at least two devices, or nothing): boots the
device plane as a node does, signs one N-validator ed25519 commit
(chip_smoke.make_commit), and for every cap in turn sets MAX_SHARD_ROWS to
it, warms the shapes that plan asks for, and times K verify_commit calls of
a fresh commit object each through the node's path (scheduler, VerifyMesh,
one trip a shard). 10,240 rows over four chips: cap 2,048 is five shards of
2,048 lanes, one chip twice; cap 2,560 one shard of 2,560 rows a chip in a
4,096-lane bucket; cap 1,024 ten shards. --one-chip adds the same commits
with the mesh off (the one-chip trip at the whole commit's bucket). One
JSON line a plan: the median, the least and the mean of the calls in ms,
the shards a call and their lanes, and what set-up the plan cost.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time


def time_plan(label: str, calls: int, vals, bid, commit) -> dict:
    import chip_smoke
    from cometbft_tpu.ops import dispatch

    t0 = time.perf_counter()
    configured = dispatch.watchdog_timeout()
    dispatch.configure(watchdog_timeout=chip_smoke.WARMUP_WATCHDOG_S)
    try:
        for _ in range(3):
            chip_smoke._verify(vals, bid, chip_smoke.fresh(commit))
    finally:
        dispatch.configure(watchdog_timeout=configured)
    warm_s = time.perf_counter() - t0
    before = dispatch.health_snapshot()["mesh"]
    walls = []
    for _ in range(calls):
        c = chip_smoke.fresh(commit)
        t0 = time.perf_counter()
        chip_smoke._verify(vals, bid, c)
        walls.append((time.perf_counter() - t0) * 1e3)
    after = dispatch.health_snapshot()["mesh"]
    shards = after.get("shards_total", 0) - before.get("shards_total", 0)
    lanes = after.get("lanes_total", 0) - before.get("lanes_total", 0)
    return {"plan": label, "calls": calls,
            "ms_median": statistics.median(walls), "ms_min": min(walls),
            "ms_mean": statistics.fmean(walls),
            "ms_p95": statistics.quantiles(walls, n=20)[-1],
            "shards_a_call": shards / calls,
            "lanes_a_shard": lanes / shards if shards else None,
            "fallbacks": after.get("fallbacks"), "warm_s": warm_s}


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--validators", type=int, default=10240)
    ap.add_argument("--calls", type=int, default=150)
    ap.add_argument("--caps", default="2048,2560,1024")
    ap.add_argument("--one-chip", action="store_true")
    args = ap.parse_args(argv)
    import chip_smoke
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.parallel import mesh

    chip_smoke.refuse_off_device_env()
    device = crypto_batch.device_info()
    if device["platform"] != "tpu" or device["count"] < 2:
        print(f"needs a TPU with at least two chips, JAX reports {device}",
              file=sys.stderr)
        return 1
    chip_smoke.boot_device_plane()
    vals, bid, commit = chip_smoke.make_commit(args.validators, 0, 33)
    for cap in (int(c) for c in args.caps.split(",")):
        mesh.MAX_SHARD_ROWS = cap
        print(json.dumps(time_plan(f"cap {cap}", args.calls, vals, bid,
                                   commit)), flush=True)
    if args.one_chip:
        mesh.configure(enabled=False)
        print(json.dumps(time_plan("one chip", args.calls, vals, bid,
                                   commit)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
