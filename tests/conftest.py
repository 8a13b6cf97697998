"""Test configuration.

Tests run on the CPU: JAX_PLATFORMS=cpu (the driver sets it; set here too
for a bare `pytest`) with 8 forced host devices, so the multi-chip
sharding tests can build their Mesh from jax.devices("cpu"). Host logic
tests pin the crypto batch backend to "cpu" so they never trigger a
device-kernel compile (a node booting with backend "auto" resolves to
"cpu" here anyway: there is no TPU), and the persistent compilation cache
is armed (ops/compile_cache.py: JAX_COMPILATION_CACHE_DIR if set, else
<checkout>/.jax_cache) so kernel tests pay XLA compile once per machine,
not once per pytest run. chip_smoke.py and bench.py are the entry points
that target the chip.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

from cometbft_tpu.ops import compile_cache  # noqa: E402

jax.config.update("jax_default_device", jax.devices("cpu")[0])
compile_cache.arm()

import pytest  # noqa: E402

from cometbft_tpu.crypto import batch as crypto_batch  # noqa: E402

crypto_batch.set_backend("cpu")


@pytest.fixture
def sched_rng(request):
    """xdist-safe deterministic RNG for scheduler tests: seeded from the
    test's nodeid alone, so every worker (and every rerun) of a given
    test sees the same stream, no worker shares mutable global random
    state, and two different tests never correlate."""
    import hashlib
    import random

    seed = int.from_bytes(
        hashlib.sha256(request.node.nodeid.encode()).digest()[:8], "big")
    return random.Random(seed)


@pytest.fixture(scope="session")
def jax_cpu_devices():
    devs = jax.devices("cpu")
    assert len(devs) >= 8, f"expected 8 virtual cpu devices, got {devs}"
    return devs


# --------------------------------------------------------------- task leaks
#
# asyncio.run() silently cancels whatever is still pending when the main
# coroutine returns, which is how the PR-2 class of teardown bugs (services
# leaving stray tasks behind) survived unnoticed until they wedged a real
# node. This autouse fixture wraps asyncio.run for the duration of each
# test and fails the test if its main coroutine returns while tasks it
# spawned are still pending — teardown must actually tear down.
# Opt out per-test with @pytest.mark.allow_task_leaks (for tests that
# deliberately abandon work mid-flight).

import asyncio  # noqa: E402


@pytest.fixture(autouse=True)
def fail_on_leaked_asyncio_tasks(request):
    if request.node.get_closest_marker("allow_task_leaks"):
        yield
        return
    leaks: list[str] = []
    orig_run = asyncio.run

    def checked_run(coro, **kwargs):
        async def _main():
            try:
                return await coro
            finally:
                stray = [
                    t for t in asyncio.all_tasks()
                    if t is not asyncio.current_task() and not t.done()
                ]
                if stray:
                    # grace period: a task cancel()ed during teardown is
                    # still "pending" until the loop delivers the
                    # CancelledError — only tasks that survive the grace
                    # window are leaks
                    await asyncio.wait(stray, timeout=0.5)
                leaks.extend(
                    f"{t.get_name()}: {t.get_coro()!r}"
                    for t in stray if not t.done()
                )

        return orig_run(_main(), **kwargs)

    asyncio.run = checked_run
    try:
        yield
    finally:
        asyncio.run = orig_run
    if leaks:
        pytest.fail(
            "test left pending asyncio tasks behind (stop your services):\n  "
            + "\n  ".join(sorted(leaks)), pytrace=False)


def pytest_collection_modifyitems(config, items):
    """`pairing` and `soak` imply `slow`: the BLS pairing pipeline's
    cold XLA compile takes minutes and the saturation soaks commit tens
    of heights under load, and tier-1 is pinned to -m "not slow" — the
    markers document WHY a test is excluded while -m pairing / -m soak
    still select exactly those suites."""
    import pytest as _pytest

    for item in items:
        if (("pairing" in item.keywords or "soak" in item.keywords)
                and "slow" not in item.keywords):
            item.add_marker(_pytest.mark.slow)


@pytest.fixture(autouse=True)
def _reset_shared_checkpoint_caches():
    """The per-chain shared CheckpointCache (light/fleet.shared_cache)
    is process-global by design; tests reusing chain ids must not leak
    trusted checkpoints into each other."""
    yield
    try:
        from cometbft_tpu.light import fleet as _fleet

        _fleet.reset_shared_caches()
    except Exception:  # noqa: BLE001 - light plane may be unimportable
        pass
