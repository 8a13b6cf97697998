"""The sustained-saturation soak (ISSUE 17 acceptance): a 4-validator
in-process net must keep committing heights with bounded latency while
the loadtime saturation generator drives admission at a multiple of the
mempool ceiling. Marked `soak` (implies slow via conftest) — the
tier-1-safe unit coverage lives in test_overload.py; `bench.py --soak`
emits the same scenario's metrics for tools/bench_compare.py."""

from __future__ import annotations

import asyncio
import time

import pytest

from cometbft_tpu import loadtime, sched
from cometbft_tpu.consensus.config import test_consensus_config
from cometbft_tpu.libs.overload import OverloadRegistry
from cometbft_tpu.mempool.mempool import ErrMempoolIsFull

from tests.net_harness import make_net

POOL = 256  # admission ceiling: each pump cycle offers 4x this
INFLIGHT = 64  # mirrors the RPC write budget (see generate_saturation)
HEIGHTS = 30
QUIET = 8


async def _collect_heights(node, n: int, timeout: float) -> list[float]:
    stamps: list[float] = []
    last = node.block_store.height()
    deadline = time.monotonic() + timeout
    while len(stamps) < n and time.monotonic() < deadline:
        h = node.block_store.height()
        if h > last:
            stamps.extend(time.monotonic() for _ in range(h - last))
            last = h
        await asyncio.sleep(0.005)
    return stamps


def _p99_gap_ms(stamps: list[float]) -> float:
    gaps = sorted(b - a for a, b in zip(stamps, stamps[1:]))
    if not gaps:
        return 0.0
    return gaps[min(len(gaps) - 1, int(len(gaps) * 0.99))] * 1e3


@pytest.mark.soak
def test_saturation_soak_graded_liveness():
    """>= 30 heights under sustained 2x+ overload; zero consensus/sync
    verify-flush deadline misses; nonzero mempool sheds (saturation was
    real); p99 inter-height gap bounded vs the unloaded baseline."""
    sched.reset()

    async def main():
        cfg = test_consensus_config()
        cfg.batch_vote_verification = True  # consensus flushes ride sched
        net = await make_net(4, config=cfg, chain_id="soak-net")
        node = net.nodes[0]
        node.mempool.config.size = POOL
        reg = OverloadRegistry()
        node.mempool.attach_overload(reg)
        reg.register("sched", lambda: (
            sum(sched.get()._depth.values())
            / max(1, sched.get().queue_limit)))
        await net.start()
        try:
            quiet = await _collect_heights(node, QUIET, 60.0)
            assert len(quiet) == QUIET, "unloaded baseline never committed"

            async def submit(tx: bytes) -> bool:
                try:
                    return (await node.mempool.check_tx(tx)).is_ok()
                except ErrMempoolIsFull:
                    return False
                except Exception:  # noqa: BLE001 - cache dupes etc.
                    return False

            totals = loadtime.LoadResult()
            stop = asyncio.Event()

            async def pump() -> None:
                while not stop.is_set():
                    _, res = await loadtime.generate_saturation(
                        submit, waves=4, wave_size=POOL, size=192,
                        interval=0.005, max_inflight=INFLIGHT)
                    totals.sent += res.sent
                    totals.accepted += res.accepted
                    totals.rejected += res.rejected
                    totals.errors += res.errors

            ptask = asyncio.create_task(pump())
            loaded = await _collect_heights(node, HEIGHTS, 300.0)
            stop.set()
            await ptask
        finally:
            await net.stop()

        # liveness: the chain kept committing under sustained overload
        assert len(loaded) >= HEIGHTS

        # saturation was actually reached, and only admission-plane work
        # was shed for it
        assert totals.rejected > 0
        assert reg.sheds("mempool") > 0

        # consensus insulation: the verify scheduler never missed a
        # CONSENSUS or SYNC flush deadline while the mempool plane shed
        misses = sched.get().health().get("deadline_miss_by_class", {})
        assert misses.get("consensus", 0) == 0, misses
        assert misses.get("sync", 0) == 0, misses

        # bounded height latency: p99 gap under load stays within 3x the
        # unloaded baseline (floored — a near-zero quiet p99 on a fast
        # host must not turn jitter into a failure)
        p99_quiet = _p99_gap_ms(quiet)
        p99_loaded = _p99_gap_ms(loaded)
        bound = max(3.0 * p99_quiet, 250.0)
        assert p99_loaded <= bound, (p99_loaded, p99_quiet)

    asyncio.run(main())


@pytest.mark.soak
def test_soak_recheck_storms_are_windowed():
    """Under the soak a loaded commit triggers recheck storms; the
    pressure ladder must bound them into windows (>= 2 with a window
    smaller than the pool) without starving admission to zero."""
    sched.reset()

    async def main():
        cfg = test_consensus_config()
        net = await make_net(4, config=cfg, chain_id="soak-recheck-net")
        node = net.nodes[0]
        node.mempool.config.size = POOL
        node.mempool.config.recheck_window = POOL // 4
        reg = OverloadRegistry()
        node.mempool.attach_overload(reg)
        await net.start()
        try:
            async def submit(tx: bytes) -> bool:
                try:
                    return (await node.mempool.check_tx(tx)).is_ok()
                except Exception:  # noqa: BLE001
                    return False

            totals = loadtime.LoadResult()
            stop = asyncio.Event()

            async def pump() -> None:
                while not stop.is_set():
                    _, res = await loadtime.generate_saturation(
                        submit, waves=2, wave_size=POOL, size=192,
                        interval=0.005, max_inflight=INFLIGHT)
                    totals.accepted += res.accepted

            ptask = asyncio.create_task(pump())
            await _collect_heights(node, 10, 120.0)
            stop.set()
            await ptask
            windows = node.mempool.recheck_windows_last
            windows_total = node.mempool.recheck_windows_total
        finally:
            await net.stop()

        # a loaded pool rechecked in bounded windows, repeatedly
        assert windows_total >= 2, windows_total
        assert windows >= 1
        # admission kept flowing between windows (no starvation)
        assert totals.accepted > 0

    asyncio.run(main())
