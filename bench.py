"""Headline benchmark: Ed25519 signatures verified per second per chip.

Reproduces BASELINE.json shapes on the real device:
  config 1/5 — a stream of 10k-signature mega-batches (the 10k-validator
    commit cap, types/vote_set.go:17) through the TPU pipeline end-to-end
    with the device pubkey cache warm. HEADLINE: streaming sigs/s/chip.
  config 3 — blocksync catch-up: 1,000 consecutive 150-validator commits
    through the windowed stage/prefetch pipeline (types/validation.py,
    blocksync/reactor.py shape): blocks/s + device busy fraction.
  config 4 — light-client bisection across a simulated 100k-height,
    500-validator chain with valset churn (every hop's commit checks ride
    the device batch verifier).
  consensus-on-TPU — a 4-validator in-process net with the batched vote
    path flushing through the REAL device (tests force the CPU backend;
    this is the latency evidence VERDICT r2 item 8 asked for).

Baselines (both reported):
  vs_serial — measured serial OpenSSL single-verify on this host's core.
  vs_batch_pinned — serial extrapolated by a PINNED 4x batch-speedup
    factor for the reference's curve25519-voi batch verifier
    (crypto/ed25519/ed25519.go:208-241). No Go toolchain exists in this
    image to measure it directly; published curve25519-voi/ed25519-dalek
    batch-verification numbers sit at ~2-3x serial on one core, so 4x is
    a deliberately conservative (baseline-favoring) bound.

NOTE: single-batch p50 latency includes the host<->device link; the
device_compute_ms figure isolates kernel time by rep-differencing (time
of k+N chained kernels minus time of k, over N).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

from __future__ import annotations

import json
import os
import secrets
import sys
import time

os.environ.setdefault("XLA_FLAGS", "")

BATCH = int(os.environ.get("BENCH_BATCH", "10240"))
CPU_SAMPLE = int(os.environ.get("BENCH_CPU_SAMPLE", "2048"))
ITERS = int(os.environ.get("BENCH_ITERS", "5"))
STREAM_BATCHES = int(os.environ.get("BENCH_STREAM_BATCHES", "16"))
BS_HEIGHTS = int(os.environ.get("BENCH_BS_HEIGHTS", "1000"))
BS_VALS = int(os.environ.get("BENCH_BS_VALS", "150"))
LC_HEIGHT = int(os.environ.get("BENCH_LC_HEIGHT", "100000"))
LC_VALS = int(os.environ.get("BENCH_LC_VALS", "500"))
# light-client fleet serving scenario (bench_light_fleet)
FLEET_CLIENTS = int(os.environ.get("BENCH_FLEET_CLIENTS", "10000"))
FLEET_HEIGHT = int(os.environ.get("BENCH_FLEET_HEIGHT", "20000"))
FLEET_VALS = int(os.environ.get("BENCH_FLEET_VALS", "64"))
MIXED_BATCH = int(os.environ.get("BENCH_MIXED", "10240"))
PINNED_VOI_BATCH_FACTOR = 4.0
VS_BATCH_NOTE = (
    "serial OpenSSL x pinned 4.0 factor for curve25519-voi batch verify "
    "(published numbers ~2-3x; 4x chosen to favor the baseline)"
)


def _progress(msg: str) -> None:
    """Stage progress on stderr (the driver parses stdout's single JSON
    line; stderr shows where a run is if it stalls)."""
    print(f"[bench +{time.perf_counter() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.perf_counter()


def _mk_sigs(n, n_keys):
    from cometbft_tpu.crypto import ed25519

    privs = [ed25519.gen_priv_key() for _ in range(n_keys)]
    pubs, msgs, sigs = [], [], []
    for i in range(n):
        p = privs[i % n_keys]
        msg = b"bench-vote-" + i.to_bytes(4, "big") + secrets.token_bytes(8)
        pubs.append(p.pub_key().bytes_())
        msgs.append(msg)
        sigs.append(p.sign(msg))
    return privs, pubs, msgs, sigs


_run_n_cache: dict = {}


def _get_run_n(verify_fn):
    """One jitted repeat-runner per verify program: a fresh closure per
    timing call would miss the in-process jit cache and re-enter the
    compile path (link-expensive) on every retry."""
    fn = _run_n_cache.get(verify_fn)
    if fn is None:
        import functools

        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("reps",))
        def run_n(ax, ay, az, at, rw, sw, kw, reps=1):
            acc = jnp.zeros((), jnp.int32)
            for i in range(reps):
                acc = acc + verify_fn(
                    ax, ay, az, at, rw, sw + jnp.uint32(i), kw).sum()
            return acc

        fn = _run_n_cache[verify_fn] = run_n
    return fn


def bench_device_compute(verify_fn, a_dev, rwd, swd, kwd,
                         rep_pair=(2, 8)) -> float:
    """Kernel-only ms per batch via rep-differencing through the link.
    rep_pair must put enough device work between the two points to clear
    the link noise — small batches need a wide pair like (8, 64).
    verify_fn: the per-chip verify program (Pallas or XLA path)."""
    run_n = _get_run_n(verify_fn)
    lo, hi = rep_pair
    out = {}
    for reps in rep_pair:
        run_n(*a_dev, rwd, swd, kwd, reps=reps).block_until_ready()
        ts = []
        for _ in range(4):
            t0 = time.perf_counter()
            run_n(*a_dev, rwd, swd, kwd, reps=reps).block_until_ready()
            ts.append(time.perf_counter() - t0)
        out[reps] = min(ts)
    return (out[hi] - out[lo]) / (hi - lo) * 1e3


def _run_stats(runs: list[float], converged: bool) -> dict:
    """Honest spread over ALL post-warmup runs: median + p90 +
    spread_pct ((p90 - min) / min). The old artifact reported min-vs-min
    agreement as 'repeatability', which hid bimodal run lists like
    [2.08, 8.63, 8.53, 8.66, 8.5, 1.99] behind a 4.3% figure."""
    s = sorted(runs)
    n = len(s)
    median = s[n // 2] if n % 2 else (s[n // 2 - 1] + s[n // 2]) / 2
    p90 = s[min(n - 1, int(0.9 * (n - 1) + 0.999))]
    return {
        "runs": n,
        "min_ms": round(s[0], 2),
        "median_ms": round(median, 2),
        "p90_ms": round(p90, 2),
        "spread_pct": round((p90 - s[0]) / s[0] * 100, 1) if n > 1 else None,
        "best_pair_converged": converged,
    }


def measure_device_compute(verify_fn, a_dev, rwd, swd, kwd, rep_pair=(2, 8),
                           tol_pct=10.0, max_tries=6, budget_s=240.0):
    """Defensible device-compute time: rep-difference repeatedly until the
    two SMALLEST runs agree within tol_pct (dev-box contention only ever
    inflates a slope, so the two quietest runs bracket the true kernel
    time), refusing non-positive slopes (a too-narrow pair under link
    noise). Returns (best_ms, runs_ms, stats): best is the min of the two
    converged quietest runs (the defensible kernel-time claim), while
    `stats` reports the HONEST spread over every post-warmup run —
    median + p90 + spread_pct (_run_stats) — identically for every scheme
    that calls this. A spread far above tol_pct means the box was noisy or
    the measurement bimodal; both are recorded as-is so the artifact is
    honest about its own quality. Raises only if no positive slope was
    ever measured."""
    runs: list[float] = []
    pair = rep_pair
    converged = False
    deadline = time.perf_counter() + budget_s  # contention must not stall
    for _ in range(max_tries):
        if time.perf_counter() > deadline and runs:
            break
        ms = bench_device_compute(verify_fn, a_dev, rwd, swd, kwd, pair)
        if ms <= 0:
            # widen: more device work between the two points (capped — a
            # runaway widening loop under heavy box contention must not
            # stall the whole bench; each retry also consumes a try)
            pair = (pair[0], min(pair[1] * 2, 64))
            continue
        runs.append(ms)
        if len(runs) >= 2:
            lo2 = sorted(runs)[:2]
            if (lo2[1] - lo2[0]) / lo2[0] * 100 <= tol_pct:
                converged = True
                break
    if not runs:
        raise RuntimeError(
            f"no positive slope after {max_tries} tries (pair widened to {pair})")
    return (min(runs), [round(r, 2) for r in runs],
            _run_stats(runs, converged))


def bench_blocksync(detail: dict) -> None:
    """BASELINE config 3: stream BS_HEIGHTS consecutive commits from a
    BS_VALS-validator chain through the stage/prefetch window pipeline —
    the exact device path blocksync's pool routine drives."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests"))
    from light_harness import LightChain

    from cometbft_tpu.types import validation

    chain = LightChain("bench-bs", BS_HEIGHTS + 1, n_vals=BS_VALS)
    vals = chain.valsets[1]
    window = 32
    heights = list(range(1, BS_HEIGHTS + 1))
    # warm the kernel for this bucket size (compile happens once per shape)
    lb1 = chain.blocks[1]
    warm = validation.stage_verify_commit(
        "bench-bs", vals, lb1.commit.block_id, 1, lb1.commit)
    validation.prefetch_staged([warm])

    def stage(hs):
        out = []
        for h in hs:
            lb = chain.blocks[h]
            out.append(validation.stage_verify_commit(
                "bench-bs", vals, lb.commit.block_id, h, lb.commit))
        return out

    # pipelined like blocksync._pool_routine: stage window N+1 on the host
    # while window N's masks are fetched from the device in a thread.
    # device_busy = time the fetch itself took (it overlaps host staging),
    # so the fraction reads "share of wall-clock the device was working".
    import concurrent.futures

    def timed_prefetch(batch):
        tb = time.perf_counter()
        validation.prefetch_staged(batch)
        return time.perf_counter() - tb

    ex = concurrent.futures.ThreadPoolExecutor(1)
    t0 = time.perf_counter()
    device_busy = 0.0
    done = 0
    staged = stage(heights[:window])
    while staged:
        fut = ex.submit(timed_prefetch, staged)
        nxt = done + len(staged)
        staged_next = stage(heights[nxt:nxt + window])
        device_busy += fut.result()
        for s in staged:
            s.finish()
        done = nxt
        staged = staged_next
    wall = time.perf_counter() - t0
    ex.shutdown()
    detail["blocksync_blocks_per_s"] = round(BS_HEIGHTS / wall, 1)
    detail["blocksync_sigs_per_s"] = round(BS_HEIGHTS * BS_VALS / wall, 1)
    detail["blocksync_device_busy_fraction"] = round(device_busy / wall, 3)
    detail["blocksync_shape"] = f"{BS_HEIGHTS} heights x {BS_VALS} validators, window {window}"
    detail["blocksync_note"] = (
        "busy fraction ~1.0 means wall time IS the device round-trip path "
        "(transfer + dispatch + fetch over the host<->device link); "
        "host staging fully overlaps (see link_cap_note)")


def bench_mixed_megacommit(detail: dict) -> None:
    """BASELINE config 5: a mixed ed25519+sr25519 10k-validator mega-commit
    through the verify scheduler — half the rows each scheme, one device batch
    per scheme, both dispatched async and resolved with one fetch. Reports
    wall latency (link-inclusive), a host-staging/device/link
    decomposition, and the sr25519 kernel's rep-differenced device time."""
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.crypto import ed25519, sr25519

    n_half = MIXED_BATCH // 2
    ed_keys = [ed25519.gen_priv_key() for _ in range(min(n_half, 1024))]
    sr_keys = [sr25519.gen_priv_key() for _ in range(min(n_half, 128))]
    rows = []
    for i in range(n_half):
        k = ed_keys[i % len(ed_keys)]
        m = b"mixed-ed-" + i.to_bytes(4, "big")
        rows.append((k.pub_key(), m, k.sign(m)))
    # sr25519 signing is ~5 ms/sig in the pure-Python schnorrkel host path;
    # sign 512 distinct rows and tile them — verification cost per lane is
    # content-independent, and the verifier recomputes every row's
    # challenge, so the measured verify() wall is not flattered
    distinct = []
    for i in range(min(n_half, 512)):
        k = sr_keys[i % len(sr_keys)]
        m = b"mixed-sr-" + i.to_bytes(4, "big")
        distinct.append((k.pub_key(), m, k.sign(m)))
    for i in range(n_half):
        rows.append(distinct[i % len(distinct)])

    def run() -> float:
        v = crypto_batch.create_mixed_batch_verifier()
        for pk, m, s in rows:
            v.add(pk, m, s)
        t0 = time.perf_counter()
        ok, mask = v.verify()
        dt = time.perf_counter() - t0
        if not ok:
            bad = [i for i, b in enumerate(mask) if not b]
            kinds = sorted({rows[i][0].type_() for i in bad})
            raise AssertionError(
                f"mixed mega-commit failed verification: {len(bad)} bad "
                f"lanes, schemes {kinds}, first {bad[:8]}")
        return dt

    run()  # warm both kernels' compiles
    detail["mixed_megacommit_ms"] = round(min(run() for _ in range(3)) * 1e3, 2)
    detail["mixed_megacommit_shape"] = f"{n_half} ed25519 + {n_half} sr25519"
    # reduced-fetch accounting: a happy window resolves from the 8-byte
    # headers; the full per-lane masks cross the link only on failure
    from cometbft_tpu.ops import ed25519_kernel as _EK

    _EK.reset_fetch_stats()
    run()
    _fs = _EK.fetch_stats()
    if _fs["happy_fetches"]:
        detail["fetch_bytes_happy_path"] = (
            _fs["happy_bytes"] // _fs["happy_fetches"])
    detail["fetch_stats"] = _fs
    # decomposition: host staging (pure host work, measured directly) vs
    # device compute (rep-differenced below) vs the link round trip the
    # synchronous mask fetch pays. staging+device is the
    # co-located estimate — what the commit-verify costs with the chip
    # attached to the host (BASELINE's <5 ms north star assumes that).
    from cometbft_tpu.crypto import sr25519_math as srm
    from cometbft_tpu.ops import ed25519_kernel as EK
    from cometbft_tpu.ops import pallas_verify as PVsr
    from cometbft_tpu.ops import sr25519_kernel as SRK

    ed_rows = rows[:n_half]
    sr_rows = rows[n_half:]
    t0 = time.perf_counter()
    eb = EK.bucket_size(n_half)
    EK.stage_batch([p.bytes_() for p, _, _ in ed_rows],
                   [m for _, m, _ in ed_rows],
                   [s for _, _, s in ed_rows], eb)
    t_ed_stage = time.perf_counter() - t0
    t0 = time.perf_counter()
    pubs = [pk.bytes_() for pk, _, _ in sr_rows]
    msgs = [m for _, m, _ in sr_rows]
    sigs = [s for _, _, s in sr_rows]
    _, _, _, a_dev, rw, sw, kw = SRK.stage_batch_sr(pubs, msgs, sigs)
    t_sr_stage = time.perf_counter() - t0
    # rep-differencing must not re-transfer per call: pin the word arrays
    # on device once
    import jax.numpy as jnp

    rw, sw, kw = jnp.asarray(rw), jnp.asarray(sw), jnp.asarray(kw)
    detail["mixed_host_staging_ms"] = round((t_ed_stage + t_sr_stage) * 1e3, 1)
    detail["mixed_host_staging_split_ms"] = {
        "ed25519": round(t_ed_stage * 1e3, 1),
        "sr25519": round(t_sr_stage * 1e3, 1),
    }
    detail["staging_us_per_row"] = {
        "ed25519": round(t_ed_stage / n_half * 1e6, 2),
        "sr25519": round(t_sr_stage / n_half * 1e6, 2),
    }
    from cometbft_tpu.ops import hashvec as _hv

    detail["hashvec_native"] = _hv.native_available()
    detail["hashvec_rows"] = _hv.stats()
    # per-row Merlin challenge cost (native batch path), for comparison
    # with r4's 0.03 ms/row ctypes-per-op number
    t0 = time.perf_counter()
    srm.batch_compute_challenges(
        pubs[:1024], [s[:32] for s in sigs[:1024]], msgs[:1024])
    detail["mixed_host_challenge_us_per_row"] = round(
        (time.perf_counter() - t0) / 1024 * 1e6, 2)

    # sr25519 device compute, rep-differenced on the staged sub-batch via
    # the production Pallas path (falls back to the XLA ladder only if the
    # Pallas trace fails). Pair (2, 8) puts ~60 ms of device work between
    # the two timing points (r4's (1, 4) was swamped by link noise and
    # recorded a negative slope); measure_device_compute refuses
    # non-positive slopes and loops until two quiet runs agree.
    use_pallas = (EK._pallas_available()
                  and rw.shape[1] % PVsr.LANES == 0
                  and not SRK._pallas_gate.broken)
    sr_fn = PVsr.verify_pallas_sr if use_pallas else SRK.verify_math_sr
    detail["sr25519_device_path"] = "pallas" if use_pallas else "xla"
    sr_best, sr_runs, sr_stats = measure_device_compute(
        sr_fn, a_dev, rw, sw, kw, rep_pair=(2, 8))
    detail["sr25519_device_compute_ms"] = round(sr_best, 2)
    detail["sr25519_device_runs_ms"] = sr_runs
    # honest spread over ALL post-warmup runs (median/p90/spread_pct) —
    # repeatability_pct IS the spread now, same stat as ed25519's
    detail["sr25519_device_repeatability_pct"] = sr_stats["spread_pct"]
    detail["sr25519_device_run_stats"] = sr_stats
    detail["sr25519_device_batch"] = rw.shape[1]
    ed_ms = detail.get("device_compute_ms_per_batch")
    if isinstance(ed_ms, (int, float)):
        # scale the 10240-lane ed number to this bench's ed sub-batch
        ed_share = ed_ms * EK.bucket_size(n_half) / EK.bucket_size(BATCH)
        detail["mixed_colocated_estimate_ms"] = round(
            detail["mixed_host_staging_ms"] + ed_share + sr_best, 1)
        detail["mixed_colocated_note"] = (
            "host staging + both schemes' rep-differenced device compute; "
            "the wall number above additionally pays the host<->device "
            "link (one round trip on the mask fetch plus the transfers)")


def bench_attribution(detail: dict) -> None:
    """ISSUE 6 flight recorder: arm libs/trace.py around a streaming
    verify window and record WHERE the wall time went — rolling stage
    shares (queue/stage/transfer/compute/fetch/resolve) and MEASURED
    bytes-per-sig from the spans' wire-byte counters — so the r06+
    trajectory records why a number moved, not just that it did. The
    mesh and reduced-send PRs are judged against these shares (the
    link-bound claim predicts transfer+fetch dominate)."""
    from cometbft_tpu.libs import trace
    from cometbft_tpu.ops import ed25519_kernel as K

    n = min(BATCH, 4096)
    _, pubs, msgs, sigs = _mk_sigs(n, min(n, 1024))
    cache = K.PubKeyCache()
    ok, _ = K.verify_batch(pubs, msgs, sigs, cache=cache)  # warm compile
    assert ok, "attribution warm-up batch failed"
    prev_enabled = trace.enabled()
    prev_capacity = trace.capacity()
    prev_slow = trace.slow_budget_ms()
    trace.configure(enabled=True, capacity=65536, slow_ms=-1.0)
    trace.reset_attribution()
    try:
        t0 = time.perf_counter()
        thunks = [K.verify_batch_async(pubs, msgs, sigs, cache=cache)
                  for _ in range(4)]
        results = K.resolve_batches(thunks)
        wall = time.perf_counter() - t0
        assert all(m.all() for m in results)
        attr = trace.attribution()
    finally:
        if prev_enabled:
            # an operator armed the tracer (CBFT_TRACE=1) for the whole
            # bench session — re-arm with their ring size and slow budget
            # rather than disarming. Their pre-bench spans were already
            # dropped when this scenario took over the ring; skip a
            # second rebuild (which would also drop this window's spans)
            # when the ring size already matches.
            trace.configure(
                enabled=True,
                capacity=None if prev_capacity == trace.capacity()
                else prev_capacity,
                slow_ms=prev_slow)
        else:
            trace.reset()
    # coverage: the fraction of the window's wall time the stage-
    # categorized spans explain (acceptance asks >=95% on the per-batch
    # path; the remainder is Python glue between spans)
    attr["trace_coverage"] = round(
        min(1.0, attr["total_us"] / 1e6 / wall), 4)
    attr["window_wall_ms"] = round(wall * 1e3, 2)
    attr["window_rows"] = 4 * n
    attr["note"] = (
        "rolling stage shares over a 4-batch streaming window; "
        "bytes_per_sig_* are measured off span wire-byte counters "
        "(h2d staged words + pubkey tables tx, reduced-fetch headers/"
        "payloads rx), not estimated from shapes")
    # the live link estimator's view of the same window lands once in
    # the artifact, as the top-level `link_model` detail (main())
    detail["attribution"] = attr


def bench_challenge(detail: dict) -> None:
    """ISSUE 20 device challenge derivation: per-row cost of
    k = SHA-512(R||A||M) mod L on the host path (vectorized hashvec) vs
    the device path (plan + descriptor-stream pack + lane-parallel
    SHA-512/Barrett derive), over vote-shaped rows (shared prefix,
    8-byte variable timestamp, common chain-id trailer) — the message
    geometry the wire-bound ≤82 B/sig sentinel is judged on."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.libs.prefixrows import PrefixedMsg
    from cometbft_tpu.ops import challenge as CH
    from cometbft_tpu.ops import ed25519_kernel as EK
    from cometbft_tpu.ops import hashvec as hv

    n = 1024
    prefix = b"bench-challenge-" + b"p" * 89  # one shared 105 B prefix
    privs = [ed25519.gen_priv_key() for _ in range(64)]
    pubs, msgs, sigs = [], [], []
    for i in range(n):
        p = privs[i % 64]
        m = PrefixedMsg(prefix,
                        secrets.token_bytes(8) + b"|bench-chain")
        pubs.append(p.pub_key().bytes_())
        msgs.append(m)
        sigs.append(p.sign(bytes(m)))
    b = EK.bucket_size(n)
    pre_ok, _safe, sig_rows, pub_rows = EK._structural_stage(pubs, sigs)

    # host path: the exact vectorized twin the kernel's fallback rungs use
    datas = [sigs[i][:32] + pubs[i] + bytes(msgs[i]) for i in range(n)]
    t0 = time.perf_counter()
    hv.sha512_mod_l_words(datas)
    host_us = (time.perf_counter() - t0) / n * 1e6

    # device path: plan + pack + derive, everything a real batch pays
    # per flush once the prefix table is resident
    CH.reset()
    plan = CH.plan_batch(msgs, pre_ok, put_key="bench")
    if plan is None:
        detail["challenge_us_per_row"] = {
            "host": round(host_us, 2), "device": None,
            "note": f"plan_batch declined: {CH.stats()}"}
        return
    block = np.zeros(CH.block_words(b, plan.var), dtype=np.uint32)
    # a b-row key table whose lane i reads row i (the derive program
    # gathers coordinates and key encodings by index; the coordinates
    # are not what is timed here)
    aw = np.zeros((8, b), dtype=np.uint32)
    aw[0, :] = 1
    aw[:, :n] = np.ascontiguousarray(pub_rows).view("<u4").T
    coords = (jnp.zeros((20, b), jnp.int32),) * 4
    table = (np.arange(b, dtype=np.uint16), *coords, jnp.asarray(aw))
    run = CH.derive_fn(b, plan.var, plan.plen, plan.tlen, 0)
    EK._pack_device_block(sig_rows, b, plan, block)
    out = run(block, *table, plan.dev_tab)
    jax.block_until_ready(out)  # compile outside the timed window
    reps = 8
    t0 = time.perf_counter()
    for _ in range(reps):
        p = CH.plan_batch(msgs, pre_ok, put_key="bench")
        EK._pack_device_block(sig_rows, b, p, block)
        out = run(block, *table, p.dev_tab)
    jax.block_until_ready(out)
    dev_us = (time.perf_counter() - t0) / (reps * n) * 1e6

    detail["challenge_us_per_row"] = {
        "host": round(host_us, 2),
        "device": round(dev_us, 2),
    }
    detail["challenge"] = {
        "lanes": n,
        "lanes_device": plan.n_eligible,
        "lanes_host_fallback": plan.n_fallback,
        "geometry": {"plen": plan.plen, "tlen": plan.tlen,
                     "var": plan.var},
        "wire_block_bytes": int(block.nbytes),
        "wire_bytes_per_sig": round(block.nbytes / n, 1),
        "counters": CH.stats(),
        "note": (
            "device path includes plan + descriptor pack + lane-parallel "
            "SHA-512/Barrett derive; wire_bytes_per_sig is the flat-block "
            "cost (R/s + descriptors) the k plane no longer adds 32 B to"),
    }


def bench_light_client(detail: dict) -> None:
    """BASELINE config 4: bisection over a lazily-generated LC_HEIGHT-high
    chain with LC_VALS validators and periodic valset churn; every hop is
    two device-batched commit verifications."""
    import asyncio

    from cometbft_tpu import light
    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.light.provider import Provider
    from cometbft_tpu.light.store import LightStore
    from cometbft_tpu.store import MemDB
    from cometbft_tpu.types.basic import BlockID, PartSetHeader, SignedMsgType
    from cometbft_tpu.types.block import Header
    from cometbft_tpu.types.light import LightBlock, SignedHeader
    from cometbft_tpu.types.validator import Validator, ValidatorSet
    from cometbft_tpu.types.vote import Vote
    from cometbft_tpu.types.vote_set import VoteSet
    from cometbft_tpu.utils import cmttime

    CHURN_EVERY = max(LC_HEIGHT // 8, 1)  # 8 valset versions across the chain
    REPLACE_FRAC = 0.5  # half the set changes per version: forces pivots
    base_time = cmttime.now().seconds - LC_HEIGHT - 1000

    # pool must not wrap across the 8 valset versions, or a distant version
    # aliases the trusted one and bisection degenerates to a single jump
    pool = [ed25519.gen_priv_key() for _ in range(LC_VALS * 8)]

    class LazyChain(Provider):
        def __init__(self):
            self._valsets: dict[int, tuple] = {}
            self._blocks: dict[int, LightBlock] = {}
            self.gen_s = 0.0  # harness block-generation time (Python
            # signing of LC_VALS votes/block — NOT client work)

        def _valset(self, h):
            ver = h // CHURN_EVERY
            got = self._valsets.get(ver)
            if got is None:
                # deterministic rolling selection from the key pool
                start = (ver * int(LC_VALS * REPLACE_FRAC)) % (len(pool) - LC_VALS)
                privs = pool[start:start + LC_VALS]
                vs = ValidatorSet([Validator.new(p.pub_key(), 10) for p in privs])
                by_addr = {p.pub_key().address(): p for p in privs}
                privs = [by_addr[v.address] for v in vs.validators]
                got = (vs, privs)
                self._valsets[ver] = got
            return got

        def _block(self, h):
            lb = self._blocks.get(h)
            if lb is not None:
                return lb
            _t0 = time.perf_counter()
            lb = self._gen_block(h)
            self.gen_s += time.perf_counter() - _t0
            return lb

        def _gen_block(self, h):
            vs, privs = self._valset(h)
            nvs, _ = self._valset(h + 1)
            header = Header(
                chain_id="bench-lc", height=h,
                time=cmttime.Timestamp(base_time + h, 0),
                last_block_id=BlockID(
                    hash=b"\x07" * 32,
                    part_set_header=PartSetHeader(total=1, hash=b"\x08" * 32)),
                validators_hash=vs.hash(), next_validators_hash=nvs.hash(),
                consensus_hash=b"\x01" * 32, app_hash=b"\x02" * 32,
                last_results_hash=b"\x03" * 32, data_hash=b"\x04" * 32,
                last_commit_hash=b"\x05" * 32, evidence_hash=b"\x06" * 32,
                proposer_address=vs.validators[0].address,
            )
            bid = BlockID(hash=header.hash(),
                          part_set_header=PartSetHeader(total=1, hash=b"\x09" * 32))
            vote_set = VoteSet("bench-lc", h, 1, SignedMsgType.PRECOMMIT, vs)
            for i, p in enumerate(privs):
                v = Vote(type_=SignedMsgType.PRECOMMIT, height=h, round_=1,
                         block_id=bid, timestamp=cmttime.canonical_now_ms(),
                         validator_address=p.pub_key().address(), validator_index=i)
                v.signature = p.sign(v.sign_bytes("bench-lc"))
                vote_set.add_vote(v)
            lb = LightBlock(
                signed_header=SignedHeader(header=header, commit=vote_set.make_commit()),
                validator_set=vs)
            self._blocks[h] = lb
            return lb

        async def light_block(self, height):
            return self._block(height if height else LC_HEIGHT)

        async def report_evidence(self, ev):
            pass

    async def run():
        provider = LazyChain()
        first = provider._block(1)
        client = light.Client(
            "bench-lc",
            light.TrustOptions(
                period_ns=10**18, height=1, hash_=first.hash()),
            provider, [LazyChain()], LightStore(MemDB()),
        )
        await client.initialize()
        # decompose the hop: harness generation (provider.gen_s), device
        # prefetch (wrapped), remainder = client host work
        from cometbft_tpu.types import validation as _val

        fetch = {"s": 0.0}
        orig = _val.prefetch_staged

        def timed_prefetch(staged):
            t0 = time.perf_counter()
            try:
                return orig(staged)
            finally:
                fetch["s"] += time.perf_counter() - t0

        _val.prefetch_staged = timed_prefetch
        # the verifier imported the symbol directly — patch there too
        from cometbft_tpu.light import verifier as _verif

        _verif.prefetch_staged = timed_prefetch
        gen0 = provider.gen_s
        try:
            t0 = time.perf_counter()
            await client.verify_light_block_at_height(LC_HEIGHT)
            wall = time.perf_counter() - t0
        finally:
            _val.prefetch_staged = orig
            _verif.prefetch_staged = orig
        return wall, client.store.size(), provider.gen_s - gen0, fetch["s"]

    wall, hops, gen_s, fetch_s = asyncio.run(run())
    detail["lc_bisection_s"] = round(wall, 2)
    detail["lc_bisection_hops"] = hops
    detail["lc_client_s"] = round(wall - gen_s, 2)
    detail["lc_hop_breakdown_ms"] = {
        "harness_block_generation": round(gen_s / max(hops, 1) * 1e3, 1),
        "device_prefetch": round(fetch_s / max(hops, 1) * 1e3, 1),
        "client_host_other": round(
            (wall - gen_s - fetch_s) / max(hops, 1) * 1e3, 1),
    }
    detail["lc_shape"] = f"height {LC_HEIGHT}, {LC_VALS} validators, churn every {CHURN_EVERY}"


def bench_light_fleet(detail: dict) -> None:
    """Serving-plane scenario (light/fleet.py): FLEET_CLIENTS simulated
    concurrent light clients hit ONE LightFleet over a provider link
    degraded by the armed netchaos profile (latency+jitter+drop sampled
    from p2p/netchaos's link config — the same model the conn wrapper
    applies to real sockets). Requests follow a serving mix: most
    clients want the head, a tail bisects random history. Mid-soak the
    link suffers a full outage (the partition analog) and heals; the
    post-heal p99 is reported. Headline numbers: lc_amortized_ms
    (total wall / clients — the millions-of-users metric, enforced
    lower-is-better by the sentinel) and lc_cache_hit_rate
    (informational: a workload-mix property)."""
    import asyncio
    import random as _random

    from cometbft_tpu import light
    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.light.provider import Provider
    from cometbft_tpu.p2p import netchaos
    from cometbft_tpu.types.basic import BlockID, PartSetHeader, SignedMsgType
    from cometbft_tpu.types.block import Header
    from cometbft_tpu.types.light import LightBlock, SignedHeader
    from cometbft_tpu.types.validator import Validator, ValidatorSet
    from cometbft_tpu.types.vote import Vote
    from cometbft_tpu.types.vote_set import VoteSet
    from cometbft_tpu.utils import cmttime

    CHURN_EVERY = max(FLEET_HEIGHT // 8, 1)
    base_time = cmttime.now().seconds - FLEET_HEIGHT - 1000
    pool = [ed25519.gen_priv_key() for _ in range(FLEET_VALS * 4)]

    class LazyChain(Provider):
        def __init__(self):
            self._valsets: dict[int, tuple] = {}
            self._blocks: dict[int, LightBlock] = {}
            self.calls = 0

        def _valset(self, h):
            ver = h // CHURN_EVERY
            got = self._valsets.get(ver)
            if got is None:
                start = (ver * (FLEET_VALS // 2)) % (len(pool) - FLEET_VALS)
                privs = pool[start:start + FLEET_VALS]
                vs = ValidatorSet(
                    [Validator.new(p.pub_key(), 10) for p in privs])
                by_addr = {p.pub_key().address(): p for p in privs}
                got = (vs, [by_addr[v.address] for v in vs.validators])
                self._valsets[ver] = got
            return got

        def _block(self, h):
            lb = self._blocks.get(h)
            if lb is None:
                vs, privs = self._valset(h)
                nvs, _ = self._valset(h + 1)
                header = Header(
                    chain_id="bench-fleet", height=h,
                    time=cmttime.Timestamp(base_time + h, 0),
                    last_block_id=BlockID(
                        hash=b"\x07" * 32,
                        part_set_header=PartSetHeader(total=1, hash=b"\x08" * 32)),
                    validators_hash=vs.hash(), next_validators_hash=nvs.hash(),
                    consensus_hash=b"\x01" * 32, app_hash=b"\x02" * 32,
                    last_results_hash=b"\x03" * 32, data_hash=b"\x04" * 32,
                    last_commit_hash=b"\x05" * 32, evidence_hash=b"\x06" * 32,
                    proposer_address=vs.validators[0].address,
                )
                bid = BlockID(hash=header.hash(),
                              part_set_header=PartSetHeader(total=1,
                                                            hash=b"\x09" * 32))
                vote_set = VoteSet("bench-fleet", h, 1,
                                   SignedMsgType.PRECOMMIT, vs)
                for i, p in enumerate(privs):
                    v = Vote(type_=SignedMsgType.PRECOMMIT, height=h, round_=1,
                             block_id=bid, timestamp=cmttime.canonical_now_ms(),
                             validator_address=p.pub_key().address(),
                             validator_index=i)
                    v.signature = p.sign(v.sign_bytes("bench-fleet"))
                    vote_set.add_vote(v)
                lb = LightBlock(
                    signed_header=SignedHeader(header=header,
                                               commit=vote_set.make_commit()),
                    validator_set=vs)
                self._blocks[h] = lb
            return lb

        async def light_block(self, height):
            self.calls += 1
            return self._block(height if height else FLEET_HEIGHT)

        async def report_evidence(self, ev):
            pass

    class DegradedLink(Provider):
        """The provider behind a lossy wire: per-fetch delay and drop
        sampled from the ARMED netchaos link config (the fleet pays the
        same latency model real sockets would under ChaosConn)."""

        def __init__(self, inner):
            self.inner = inner
            self.rng = _random.Random(7)
            self.outage = False
            self.dropped = 0

        @property
        def calls(self):
            return self.inner.calls

        async def light_block(self, height):
            if self.outage:
                raise light.errors.ErrLightBlockNotFound("link outage")
            cfg = (netchaos.snapshot().get("config") or {})
            delay = cfg.get("latency", 0.0) + self.rng.uniform(
                0, cfg.get("jitter", 0.0))
            if delay:
                await asyncio.sleep(delay)
            if cfg.get("drop", 0.0) and self.rng.random() < cfg["drop"]:
                self.dropped += 1
                raise light.errors.ErrLightBlockNotFound(
                    "netchaos: fetch dropped")
            return await self.inner.light_block(height)

        async def report_evidence(self, ev):
            pass

    async def run():
        netchaos.reset()
        # armed for this scenario only: the finally below must clear it
        # even on a mid-soak failure, or every later bench section runs
        # over silently degraded in-process links
        netchaos.arm_spec("latency=0.002,jitter=0.002,drop=0.002,seed=7")
        try:
            return await _soak()
        finally:
            netchaos.reset()

    async def _soak():
        chain = LazyChain()
        link = DegradedLink(chain)
        first = chain._block(1)
        fleet = light.LightFleet(
            "bench-fleet", link,
            light.TrustOptions(period_ns=10 ** 18, height=1,
                               hash_=first.hash()),
            cache_capacity=4096, skip_base=16, trust_period_ns=10 ** 18,
            max_inflight=4096)
        await fleet.initialize()
        rng = _random.Random(11)
        # serving mix: 70% want the head, 20% a hot recent window, 10%
        # bisect random history
        heights = []
        for _ in range(FLEET_CLIENTS):
            r = rng.random()
            if r < 0.70:
                heights.append(FLEET_HEIGHT)
            elif r < 0.90:
                heights.append(FLEET_HEIGHT - rng.randint(1, 64))
            else:
                heights.append(rng.randint(FLEET_HEIGHT // 2, FLEET_HEIGHT))
        lat: list[float] = []
        errors = 0

        async def one(h):
            # a real client retries a failed request once (the degraded
            # link drops ~0.2% of fetches, and one drop mid-bisection
            # fails every coalesced waiter on that flight)
            nonlocal errors
            t0 = time.perf_counter()
            for attempt in (0, 1):
                try:
                    await fleet.verify_height(h)
                    lat.append(time.perf_counter() - t0)
                    return
                except light.LightClientError:
                    if attempt:
                        errors += 1

        # clients arrive in waves (the serving arrival process), not as
        # one synchronized burst: the first wave coalesces onto shared
        # flights, later waves hit the checkpoint cache
        wave = max(256, FLEET_CLIENTS // 20)
        t0 = time.perf_counter()
        for i in range(0, len(heights), wave):
            await asyncio.gather(*(one(h) for h in heights[i:i + wave]))
        wall = time.perf_counter() - t0

        # ---- outage + heal: the partition analog on the provider link.
        # Requests during the outage fail fast; after the heal a fresh
        # burst must recover to a serving p99
        link.outage = True
        out_err = 0
        for h in range(FLEET_HEIGHT - 200, FLEET_HEIGHT - 180):
            try:
                await fleet.verify_height(h)
            except light.LightClientError:
                out_err += 1
        link.outage = False
        heal_lat: list[float] = []
        for h in range(FLEET_HEIGHT - 200, FLEET_HEIGHT - 100):
            t1 = time.perf_counter()
            try:
                await fleet.verify_height(h)
                heal_lat.append(time.perf_counter() - t1)
            except light.LightClientError:
                pass
        return fleet, link, wall, lat, errors, out_err, heal_lat

    fleet, link, wall, lat, errors, out_err, heal_lat = asyncio.run(run())
    h = fleet.health()
    lat.sort()
    heal_lat.sort()
    detail["lc_amortized_ms"] = round(wall / max(FLEET_CLIENTS, 1) * 1e3, 3)
    detail["lc_cache_hit_rate"] = h["cache"]["hit_rate"]
    detail["fleet"] = {
        "clients": FLEET_CLIENTS,
        "wall_s": round(wall, 2),
        "requests": h["requests"],
        "cache_hits": h["cache_hits"],
        "coalesced": h["coalesced"],
        "verified": h["verified"],
        "amortization": h["amortization"],
        "errors": errors,
        "provider_fetches": link.calls,
        "fetches_dropped": link.dropped,
        "hops_per_verification": round(link.calls / h["verified"], 2)
        if h["verified"] else None,
        "p50_ms": round(lat[len(lat) // 2] * 1e3, 3) if lat else None,
        "p99_ms": round(lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3,
                        3) if lat else None,
        "outage_errors": out_err,
        "p99_heal_ms": round(
            heal_lat[min(len(heal_lat) - 1, int(len(heal_lat) * 0.99))]
            * 1e3, 3) if heal_lat else None,
        "shape": f"height {FLEET_HEIGHT}, {FLEET_VALS} validators, "
                 f"churn every {CHURN_EVERY}, netchaos "
                 f"latency=2ms jitter=2ms drop=0.2%",
    }


def bench_bls(detail: dict) -> None:
    """BLS12-381 scenario: aggregate-BLS vs batched-ed25519 commit
    verify at BENCH_BLS_SIZES validators (default 1k/10k/100k), with the
    crossover committee size recorded. Same-sign-bytes votes (the BLS
    commit-certificate shape: vote bytes carry no validator-specific
    field, and PoP aggregation folds identical messages), so aggregate
    cost is sig-sum + ONE pairing-product check while batched ed25519
    stays one lane-verify per validator.

    On a host without an accelerator the larger sizes are extrapolated
    from the measured linear model (aggregate = a + b*n; every O(n) term
    is cheap point adds) and marked as such — a TPU round measures all
    sizes directly. BENCH_BLS_SIZES / BENCH_BLS_MEASURE_CAP override."""
    from cometbft_tpu.crypto import fallback as O

    sizes = [int(s) for s in os.environ.get(
        "BENCH_BLS_SIZES", "1000,10000,100000").split(",")]
    import jax as _jax

    on_accel = any(d.platform != "cpu" for d in _jax.devices())
    cap = int(os.environ.get(
        "BENCH_BLS_MEASURE_CAP", "0" if on_accel else "4096"))
    _progress("bls: building incremental keys/sigs")
    d: dict = {"sizes": sizes, "aggregate_ms": {}, "batched_ed25519_ms": {},
               "distinct_messages": 1,
               "note": "same-sign-bytes votes aggregate their pubkeys "
                       "(PoP); aggregate cost = O(n) point adds + one "
                       "pairing-product check"}
    n_max = max(sizes)
    n_meas = min(n_max, cap) if cap else n_max
    msg = b"bench-bls-commit-height-12345"
    dstb = __import__(
        "cometbft_tpu.crypto.bls12381", fromlist=["DST"]).DST
    h = O.bls_hash_to_g2(msg, dstb)
    # sk_i = i + 1: pk/sig chains advance by one affine add per lane
    pubs_all, sigs_all = [], []
    pk_j = O._ec_from_affine(O.BLS_G1)
    sg_j = O._ec_from_affine(h)
    g1_j = O._ec_from_affine(O.BLS_G1)
    h_j = O._ec_from_affine(h)
    for _ in range(n_meas):
        pubs_all.append(O.bls_g1_compress(O._ec_affine(O._FpOps, pk_j)))
        sigs_all.append(O.bls_g2_compress(O._ec_affine(O._Fp2Ops, sg_j)))
        pk_j = O._ec_add(O._FpOps, pk_j, g1_j)
        sg_j = O._ec_add(O._Fp2Ops, sg_j, h_j)
    # aggregate timings: oracle path (self-contained; the device path's
    # verdict is bit-identical and its cost is recorded by BENCH rounds
    # on real hardware). KeyValidate subgroup scans are amortized per
    # validator set in the serving path, so the steady-state measurement
    # pre-validates the set once outside the timed window.
    meas = sorted({min(s, n_meas) for s in sizes})
    fit_pts = []
    for n in meas:
        _progress(f"bls: aggregate verify n={n}")
        pubs, sigs = pubs_all[:n], sigs_all[:n]
        for p in pubs:
            assert O.bls_pubkey_validate(p)  # amortized KeyValidate
        t0 = time.perf_counter()
        agg = O.bls_aggregate(sigs)
        groups = [O.bls_g1_decompress(p) for p in pubs]
        acc = None
        for aff in groups:
            acc = O._ec_add(O._FpOps, acc, O._ec_from_affine(aff))
        ok = O.bls_pairing_product_is_one(
            [(O._NEG_G1, O.bls_g2_decompress(agg)),
             (O._ec_affine(O._FpOps, acc), h)])
        dt = (time.perf_counter() - t0) * 1e3
        assert ok
        fit_pts.append((n, dt))
    # linear model over the measured points (everything is O(n) adds +
    # an O(1) pairing product)
    if len(fit_pts) >= 2:
        (n1, t1), (n2, t2) = fit_pts[0], fit_pts[-1]
        slope = (t2 - t1) / max(1, (n2 - n1))
        base = t1 - slope * n1
    else:
        slope, base = 0.0, fit_pts[0][1]
    measured_ns = {n for n, _ in fit_pts}
    for n in sizes:
        if n in measured_ns:
            d["aggregate_ms"][str(n)] = round(dict(fit_pts)[n], 1)
        else:
            d["aggregate_ms"][str(n)] = round(base + slope * n, 1)
    d["aggregate_mode"] = ("measured" if n_meas >= n_max else
                           f"measured to {n_meas}, extrapolated beyond "
                           f"(linear in n; BENCH_BLS_MEASURE_CAP)")
    # batched-ed25519 comparison: measured per-sig rate on the standard
    # batch, linear in committee size
    _progress("bls: batched ed25519 comparison")
    from cometbft_tpu.ops import ed25519_kernel as EK

    edn = min(2048, n_meas)
    _, epubs, emsgs, esigs = _mk_sigs(edn, min(edn, 256))
    EK.verify_batch(epubs, emsgs, esigs)  # warm the shape
    t0 = time.perf_counter()
    ok, _m = EK.verify_batch(epubs, emsgs, esigs)
    ed_ms = (time.perf_counter() - t0) * 1e3
    assert ok
    ed_per_sig = ed_ms / edn
    for n in sizes:
        d["batched_ed25519_ms"][str(n)] = round(ed_per_sig * n, 1)
    d["batched_ed25519_note"] = (
        f"measured {edn}-sig batch on this backend, scaled linearly")
    # crossover: aggregate = base + slope*n vs ed = ed_per_sig*n
    if ed_per_sig > slope:
        cross = base / (ed_per_sig - slope)
        d["crossover_validators"] = int(max(0, cross))
        d["crossover_note"] = (
            "committee size above which one pairing-product check beats "
            "per-lane ed25519 batch verify on this backend")
    else:
        d["crossover_validators"] = None
        d["crossover_note"] = (
            "no crossover on this backend: per-signature aggregation "
            "cost exceeds the ed25519 lane rate (expect a crossover on "
            "accelerator rounds where point adds vectorize)")
    ten_k = d["aggregate_ms"].get("10000")
    if ten_k is not None:
        d["bls_aggregate_verify_ms_10k"] = ten_k
        detail["bls_aggregate_verify_ms_10k"] = ten_k
    detail["bls"] = d


def bench_cert(detail: dict) -> None:
    """Commit-certificate scenario (cometbft_tpu/cert/): the FULL
    consumer path — decode-shaped CommitCertificate -> bitmap tally ->
    sign-bytes reconstruction -> signer-pubkey aggregation -> ONE
    pairing-product check (verify_certificate) — graded against the raw
    aggregate path (sig-sum + pairing, what bench_bls measures) and
    batched per-lane ed25519, at BENCH_CERT_SIZES validators.

    Like bench_bls, sizes above BENCH_CERT_MEASURE_CAP are extrapolated
    from the measured linear model on CPU hosts (every O(n) term is
    point adds / row reconstruction; the pairing is O(1)). Serve bytes
    are EXACT at every size — encoding needs no crypto — and make the
    transport headline: certificate bytes per commit grow one BIT per
    validator (the bitmap) vs ~sig+timestamp per validator classic."""
    from cometbft_tpu.cert import build_certificate, verify_certificate
    from cometbft_tpu.crypto import bls12381
    from cometbft_tpu.crypto import fallback as O
    from cometbft_tpu.libs.bits import BitArray
    from cometbft_tpu.types.basic import BlockID, BlockIDFlag, PartSetHeader
    from cometbft_tpu.types.commit import Commit, CommitSig
    from cometbft_tpu.types.validator import Validator, ValidatorSet
    from cometbft_tpu.utils import cmttime as _ct

    sizes = [int(s) for s in os.environ.get(
        "BENCH_CERT_SIZES", "1000,10000,100000").split(",")]
    import jax as _jax

    on_accel = any(d.platform != "cpu" for d in _jax.devices())
    cap = int(os.environ.get(
        "BENCH_CERT_MEASURE_CAP", "0" if on_accel else "2048"))
    chain_id = "bench-cert"
    height, round_ = 12345, 0
    block_id = BlockID(hash=b"\x11" * 32,
                       part_set_header=PartSetHeader(1, b"\x22" * 32))
    ts = _ct.Timestamp(1_700_000_000, 0)
    d: dict = {"sizes": sizes, "cert_verify_ms": {}, "cert_build_ms": {},
               "aggregate_ms": {}, "batched_ed25519_ms": {},
               "serve_bytes": {}, "classic_commit_bytes": {}}
    n_max = max(sizes)
    n_meas = min(n_max, cap) if cap else n_max
    # canonical precommit sign-bytes for this (chain, height, block):
    # identical for every signer (one shared timestamp), so sig_i =
    # sk_i * H(m) chains by one G2 add per lane — same incremental
    # material trick as bench_bls, but the pubkeys land in a REAL
    # ValidatorSet and the commit is a REAL Commit
    probe = Commit(height=height, round_=round_, block_id=block_id,
                   signatures=[CommitSig(block_id_flag=BlockIDFlag.COMMIT,
                                         timestamp=ts)])
    from cometbft_tpu.libs.prefixrows import as_bytes as _as_bytes
    msg = _as_bytes(probe.vote_sign_bytes_all(chain_id).rows_for([0])[0])
    h = O.bls_hash_to_g2(msg, bls12381.DST)
    _progress("cert: building incremental keys/sigs")
    pubs_all, sigs_all = [], []
    pk_j = O._ec_from_affine(O.BLS_G1)
    sg_j = O._ec_from_affine(h)
    g1_j = O._ec_from_affine(O.BLS_G1)
    h_j = O._ec_from_affine(h)
    for _ in range(n_meas):
        pubs_all.append(O.bls_g1_compress(O._ec_affine(O._FpOps, pk_j)))
        sigs_all.append(O.bls_g2_compress(O._ec_affine(O._Fp2Ops, sg_j)))
        pk_j = O._ec_add(O._FpOps, pk_j, g1_j)
        sg_j = O._ec_add(O._Fp2Ops, sg_j, h_j)
    meas = sorted({min(s, n_meas) for s in sizes})
    fit_v, fit_b, fit_a = [], [], []
    for n in meas:
        _progress(f"cert: build+verify n={n}")
        vals = ValidatorSet([
            Validator(address=i.to_bytes(20, "big"),
                      pub_key=bls12381.PubKey(pubs_all[i]), voting_power=10)
            for i in range(n)])
        commit = Commit(height=height, round_=round_, block_id=block_id,
                        signatures=[
                            CommitSig(block_id_flag=BlockIDFlag.COMMIT,
                                      timestamp=ts, signature=sigs_all[i])
                            for i in range(n)])
        t0 = time.perf_counter()
        cert = build_certificate(chain_id, vals, commit)
        tb = (time.perf_counter() - t0) * 1e3
        assert cert is not None
        t0 = time.perf_counter()
        verify_certificate(cert, chain_id, vals)  # raises on failure
        tv = (time.perf_counter() - t0) * 1e3
        # raw aggregate comparison on the same material: sig-sum +
        # summed-pubkey pairing, no certificate object in the loop
        t0 = time.perf_counter()
        agg = O.bls_aggregate(sigs_all[:n])
        acc = None
        for p in pubs_all[:n]:
            acc = O._ec_add(O._FpOps, acc,
                            O._ec_from_affine(O.bls_g1_decompress(p)))
        assert O.bls_pairing_product_is_one(
            [(O._NEG_G1, O.bls_g2_decompress(agg)),
             (O._ec_affine(O._FpOps, acc), h)])
        ta = (time.perf_counter() - t0) * 1e3
        fit_v.append((n, tv))
        fit_b.append((n, tb))
        fit_a.append((n, ta))

    def _fit(pts):
        if len(pts) >= 2:
            (n1, t1), (n2, t2) = pts[0], pts[-1]
            slope = (t2 - t1) / max(1, (n2 - n1))
            return t1 - slope * n1, slope
        return pts[0][1], 0.0

    for key, pts in (("cert_verify_ms", fit_v), ("cert_build_ms", fit_b),
                     ("aggregate_ms", fit_a)):
        base, slope = _fit(pts)
        got = dict(pts)
        for n in sizes:
            d[key][str(n)] = round(got[n] if n in got else base + slope * n, 1)
    d["mode"] = ("measured" if n_meas >= n_max else
                 f"measured to {n_meas}, extrapolated beyond (linear in n; "
                 f"BENCH_CERT_MEASURE_CAP)")
    # exact transport bytes at every size (no crypto needed to encode)
    from cometbft_tpu.cert import CommitCertificate
    for n in sizes:
        k = n - n // 3  # >2/3 signer bitmap
        ba = BitArray(n)
        for i in range(k):
            ba.set_index(i, True)
        c = CommitCertificate(
            chain_id=chain_id, height=height, round_=round_,
            block_id=block_id, valset_hash=b"\x33" * 32, n_vals=n,
            signers=ba, ts_base=ts, ts_deltas=[0] * k, agg_sig=b"\x44" * 96)
        d["serve_bytes"][str(n)] = len(c.encode())
        # classic transport: k real sigs + timestamps + flags
        classic = Commit(height=height, round_=round_, block_id=block_id,
                         signatures=[
                             CommitSig(block_id_flag=BlockIDFlag.COMMIT,
                                       timestamp=ts,
                                       validator_address=b"\x55" * 20,
                                       signature=b"\x66" * 96)
                             if i < k else CommitSig.absent()
                             for i in range(n)])
        d["classic_commit_bytes"][str(n)] = len(classic.to_proto())
    # batched-ed25519 per-lane comparison (same method as bench_bls)
    _progress("cert: batched ed25519 comparison")
    from cometbft_tpu.ops import ed25519_kernel as EK
    edn = min(2048, n_meas)
    _, epubs, emsgs, esigs = _mk_sigs(edn, min(edn, 256))
    EK.verify_batch(epubs, emsgs, esigs)  # warm the shape
    t0 = time.perf_counter()
    ok, _m = EK.verify_batch(epubs, emsgs, esigs)
    ed_per_sig = (time.perf_counter() - t0) * 1e3 / edn
    assert ok
    for n in sizes:
        d["batched_ed25519_ms"][str(n)] = round(ed_per_sig * n, 1)
    ten_k = d["cert_verify_ms"].get("10000")
    if ten_k is not None:
        d["cert_verify_ms_10k"] = ten_k
        detail["cert_verify_ms_10k"] = ten_k
    sb = d["serve_bytes"].get("10000")
    if sb is not None:
        d["serve_bytes_per_commit"] = sb
    d["note"] = ("cert verify = bitmap tally + signer-pubkey aggregation "
                 "+ ONE pairing; serve bytes grow 1 bit/validator vs "
                 "~100 B/validator classic")
    detail["cert"] = d


def bench_consensus_tpu(detail: dict) -> None:
    """VERDICT r2 item 8: the N=4 in-process net with batch_vote_verification
    flushing through the REAL device backend — per-height commit latency."""
    import asyncio

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests"))
    from net_harness import make_net

    from cometbft_tpu.consensus import timeline as cmttimeline
    from cometbft_tpu.consensus.config import test_consensus_config
    from cometbft_tpu.crypto import batch as crypto_batch

    crypto_batch.set_backend("tpu")
    # heightline armed for the run: the per-phase anatomy
    # (propose/prevote/precommit/commit/apply) of the same heights the
    # p50 below times, and the fleet propagation p99
    cmttimeline.configure(enabled=True)

    async def run():
        cfg = test_consensus_config()
        cfg.batch_vote_verification = True
        net = await make_net(4, config=cfg, chain_id="bench-consensus")
        for nd in net.nodes:
            nd.cs.timeline.node = nd.name
        heights = 10  # r4 verdict: 6 heights gave ~5 gaps, too thin a p50
        stamps = {}

        await net.start()
        try:
            last = 0
            deadline = time.monotonic() + 180
            while last < heights and time.monotonic() < deadline:
                h = min(n.block_store.height() for n in net.nodes)
                if h > last:
                    # stamp only observed transitions; a multi-height jump
                    # between polls would fabricate ~0 gaps, so record the
                    # jump at its top height only
                    stamps[h] = time.perf_counter()
                    last = h
                await asyncio.sleep(0.005)
        finally:
            await net.stop()
        docs = [{"node_id": nd.name, "heights": nd.cs.timeline.snapshot(),
                 "skew": {}} for nd in net.nodes]
        agg = cmttimeline.aggregate(docs)
        if len(stamps) < 2:
            return None, agg
        # gaps only between ADJACENT observed heights (both really seen)
        gaps = sorted(
            stamps[i + 1] - stamps[i]
            for i in stamps if i + 1 in stamps
        )
        if not gaps:
            return None, agg
        return (gaps[len(gaps) // 2], len(stamps)), agg

    try:
        out, agg = asyncio.run(run())
    finally:
        crypto_batch.set_backend("auto")
        cmttimeline.reset()
    s = agg.get("summary") or {}
    if s.get("phase_ms"):
        detail["height_phase_ms"] = s["phase_ms"]
    if s.get("phase_total_ms") is not None:
        detail["height_phase_total_ms"] = s["phase_total_ms"]
    if s.get("proposal_propagation_p99_ms") is not None:
        detail["proposal_propagation_p99_ms"] = s[
            "proposal_propagation_p99_ms"]
    if out is None:
        detail["consensus_tpu"] = "FAILED: net did not commit 2+ heights in 120s"
    else:
        p50, committed = out
        detail["consensus_tpu_height_p50_ms"] = round(p50 * 1e3, 1)
        detail["consensus_tpu_heights_committed"] = committed
        detail["consensus_tpu_note"] = (
            "4-validator in-proc net, vote flushes on the real device "
            "(each flush pays a host<->device round trip)")


def _host_mesh_env(n_devices: int) -> dict:
    """Subprocess env for an n-device CPU host mesh (the shared
    recipe lives in parallel/mesh.host_mesh_env)."""
    from cometbft_tpu.parallel.mesh import host_mesh_env

    env = host_mesh_env(os.environ, n_devices)
    env["BENCH_MESH_DEVICES"] = str(n_devices)
    return env


def run_mesh_bench(n_devices: int = 8, timeout: float | None = None) -> dict:
    """Run the VerifyMesh scaling scenario on an n-device host mesh in a
    child process (pinned to the CPU before it imports jax: the one
    robust way to guarantee a CPU-only mesh beside a parent that may
    hold the chip) and return its record — the real-numbers
    replacement for the old MULTICHIP dryrun."""
    import subprocess

    if timeout is None:
        # a machine-cold compilation cache pays one executable
        # instantiation per (chip, ladder shape); warm reruns finish in
        # minutes
        timeout = float(os.environ.get("BENCH_MESH_TIMEOUT", "3600"))
    repo = os.path.dirname(os.path.abspath(__file__))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"), "--mesh-child"],
        env=_host_mesh_env(n_devices), cwd=repo,
        capture_output=True, text=True, timeout=timeout,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"mesh bench child failed (rc={proc.returncode}):\n"
            f"stdout: {proc.stdout[-2000:]}\nstderr: {proc.stderr[-4000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def mesh_child_main() -> dict:
    """The in-child mesh scenario (bench.py --mesh-child): a real
    VerifyMesh scaling curve at 1/2/4/8 devices (weak scaling: constant
    per-chip rows, so every chip compiles exactly one shard shape), a
    corrupted-lane pinpoint across shards (the old dryrun's correctness
    property, kept), and a 100k-validator mega-commit through the full
    mesh. Prints ONE JSON record line."""
    import jax

    from cometbft_tpu.ops import compile_cache

    compile_cache.arm()
    import numpy as np

    from cometbft_tpu.crypto import ed25519_math as oracle
    from cometbft_tpu.parallel.mesh import VerifyMesh

    devices = jax.devices()
    assert devices[0].platform == "cpu", f"mesh child must be cpu: {devices}"
    want = int(os.environ.get("BENCH_MESH_DEVICES", "8"))
    assert len(devices) >= want, f"need {want} devices, have {len(devices)}"
    devices = devices[:want]

    per_chip = int(os.environ.get("BENCH_MESH_PER_CHIP", "256"))
    mega_rows = int(os.environ.get("BENCH_MESH_MEGA", "100000"))
    reps = int(os.environ.get("BENCH_MESH_REPS", "3"))

    n_keys = 64
    rng = np.random.default_rng(1234)
    base = []
    for i in range(n_keys):
        seed = rng.bytes(32)
        msg = b"mesh-bench-" + i.to_bytes(4, "big")
        base.append((oracle.public_key_from_seed(seed), msg,
                     oracle.sign(seed, msg)))

    def make(n):
        rows = [base[i % n_keys] for i in range(n)]
        return ([r[0] for r in rows], [r[1] for r in rows],
                [r[2] for r in rows])

    detail: dict = {
        "backend": "cpu (forced host devices)",
        "devices": len(devices),
        "per_chip_rows": per_chip,
        "note": ("weak-scaling curve: per-chip rows held constant so "
                 "every chip runs one ladder-bucket shard shape; "
                 "sigs/s on forced HOST devices — the shape of the "
                 "curve, not TPU magnitude, is the tracked signal"),
    }
    curve: dict = {}
    sizes = [k for k in (1, 2, 4, 8) if k <= len(devices)]
    for k in sizes:
        vm = VerifyMesh(devices[:k], placement="spread")
        n = per_chip * k
        pubs, msgs, sigs = make(n)
        mask = vm.verify("ed25519", pubs, msgs, sigs, klass="sync")
        assert mask.all(), f"warm-up mesh batch failed at {k} devices"
        runs = []
        for _ in range(reps):
            t0 = time.perf_counter()
            mask = vm.verify("ed25519", pubs, msgs, sigs, klass="sync")
            runs.append(time.perf_counter() - t0)
            assert mask.all()
        best = min(runs)
        curve[str(k)] = {
            "rows": n, "best_s": round(best, 4),
            "runs_s": [round(r, 4) for r in runs],
            "sigs_per_s": round(n / best, 1),
        }
        detail[f"device_sigs_per_s_{k}dev"] = round(n / best, 1)
        h = vm.health()
        assert h["fallbacks"] == 0 and h["evictions"] == 0, h
    detail["curve"] = curve
    if "1" in curve and str(sizes[-1]) in curve:
        detail["scaling_x%d" % sizes[-1]] = round(
            curve[str(sizes[-1])]["sigs_per_s"] / curve["1"]["sigs_per_s"], 3)

    # correctness across shards (the dryrun's verification property): a
    # corrupted lane in the middle of the batch is pinpointed, the rest
    # stay valid
    vm = VerifyMesh(devices, placement="spread")
    n = per_chip * len(devices)
    pubs, msgs, sigs = make(n)
    bad = n // 2 + 1
    sigs = list(sigs)
    sigs[bad] = sigs[bad][:32] + sigs[(bad + 1) % n][32:]
    mask = vm.verify("ed25519", pubs, msgs, sigs, klass="sync")
    want_mask = [i != bad for i in range(n)]
    assert mask.tolist() == want_mask, "sharded mask did not pinpoint"
    detail["corrupt_lane_pinpointed"] = True

    # the 100k-validator mega-commit: one batch, whole mesh
    vm = VerifyMesh(devices, placement="spread")
    pubs, msgs, sigs = make(mega_rows)
    t0 = time.perf_counter()
    mask = vm.verify("ed25519", pubs, msgs, sigs, klass="sync")
    warm = time.perf_counter() - t0  # includes the mega-shard compile
    assert mask.all()
    t0 = time.perf_counter()
    mask = vm.verify("ed25519", pubs, msgs, sigs, klass="sync")
    wall = time.perf_counter() - t0
    assert mask.all()
    detail["mega_commit_rows"] = mega_rows
    detail["mega_commit_s"] = round(wall, 3)
    detail["mega_commit_first_s"] = round(warm, 3)
    detail["mega_commit_sigs_per_s"] = round(mega_rows / wall, 1)

    headline = detail.get(f"device_sigs_per_s_{sizes[-1]}dev", 0.0)
    record = {
        "metric": "mesh_verify_scaling",
        "value": headline,
        "unit": f"sigs/sec ({sizes[-1]}-chip forced-host mesh)",
        "vs_baseline": (round(headline / curve["1"]["sigs_per_s"], 2)
                        if curve.get("1") else None),
        "detail": detail,
    }
    print(json.dumps(record))
    return record


def bench_mesh(detail: dict) -> None:
    """Multi-chip mesh scenario (subprocess on forced host devices; the
    record also stands alone as MULTICHIP_rNN via __graft_entry__).
    BENCH_MESH=0 skips it — the child pays per-device XLA compiles on a
    cold compilation cache."""
    if os.environ.get("BENCH_MESH", "1") == "0":
        detail["mesh"] = "skipped: BENCH_MESH=0"
        return
    record = run_mesh_bench(int(os.environ.get("BENCH_MESH_DEVICES", "8")))
    detail["mesh"] = record["detail"]


def bench_fleet(detail: dict) -> None:
    """Fleet-size curves over REAL OS-process testnets (ISSUE 12): for
    each size in BENCH_FLEET_SIZES (default "4,16"; the acceptance curve
    adds 50), boot a regional topology with WAN cross-region links, soak,
    and report per size:

      heights_per_s                    committed heights per wall second
      wire_bytes_per_height_per_node   p2p send bytes per height per node
      gossip_votes_per_vote_needed     vote amplification (lower = the
                                       reconciliation plane is working)
      partition_heal_p99_ms            worst partition-heal latency over
                                       BENCH_FLEET_HEAL_CYCLES cycles

    The largest size's amplification + heal numbers are lifted to the
    record top level under the sentinel's names. Env knobs:
    BENCH_FLEET=0 skips, BENCH_FLEET_SIZES, BENCH_FLEET_SOAK_S,
    BENCH_FLEET_HEAL_CYCLES, BENCH_FLEET_BASE_PORT."""
    if os.environ.get("BENCH_FLEET", "1") == "0":
        detail["fleet"] = "skipped: BENCH_FLEET=0"
        return
    import tempfile
    import urllib.parse

    from cometbft_tpu.e2e import runner as R
    from cometbft_tpu.e2e.generator import generate_fleet_manifest

    sizes = [int(s) for s in
             os.environ.get("BENCH_FLEET_SIZES", "4,16").split(",")
             if s.strip()]
    heal_cycles = int(os.environ.get("BENCH_FLEET_HEAL_CYCLES", "2"))
    soak_s = float(os.environ.get("BENCH_FLEET_SOAK_S", "12"))
    # port spans must stay BELOW the kernel ephemeral range (the guard
    # enforces it for big sizes; this container's range starts at
    # 16000): stride 2100 covers the p2p/rpc/abci port strides
    base_port = int(os.environ.get("BENCH_FLEET_BASE_PORT", "8000"))
    curve: dict = {}
    for n in sizes:
        R._resource_guard(n, base_port)
        regions = 2 if n < 8 else 4
        m = generate_fleet_manifest(n, topology="regional", regions=regions,
                                    link_profile="wan",
                                    name=f"bench-fleet-{n}")
        d = tempfile.mkdtemp(prefix=f"bench-fleet-{n}-")
        net = R.setup(m, d, base_port)
        base_port += 2100
        names = sorted(m.nodes)
        row: dict = {}
        try:
            net.app_procs = [None] * n
            R._boot_staggered(net)
            R._wait(lambda: all(R._height(net, i) >= 3 for i in range(n)),
                    150 + 4 * n, f"{n}-node bench fleet booting")

            def _tele():
                return [R._rpc(net, i, "net_telemetry", timeout=10.0)
                        .get("result", {}) for i in range(n)]

            _progress(f"fleet {n}: soaking {soak_s:.0f}s")
            h0 = max(R._height(net, i) for i in range(n))
            tele0 = _tele()
            t0 = time.perf_counter()
            time.sleep(soak_s)
            h1 = max(R._height(net, i) for i in range(n))
            dt = time.perf_counter() - t0
            tele1 = _tele()
            dh = max(1, h1 - h0)
            send = (sum(t.get("totals", {}).get("send_bytes", 0)
                        for t in tele1)
                    - sum(t.get("totals", {}).get("send_bytes", 0)
                          for t in tele0))
            row["heights_per_s"] = round((h1 - h0) / dt, 3)
            row["wire_bytes_per_height_per_node"] = round(send / dh / n, 1)
            g: dict = {}
            for t in tele1:
                for k, v in ((t.get("gossip") or {})
                             .get("totals") or {}).items():
                    g[k] = g.get(k, 0) + v
            needed = g.get("votes_recv_needed", 0)
            row["gossip_votes_per_vote_needed"] = (
                round(g.get("votes_recv", 0) / needed, 3) if needed
                else None)
            row["gossip_totals"] = g

            # partition/heal cycles: region 0 vs. the rest
            _progress(f"fleet {n}: {heal_cycles} partition-heal cycles")
            ids = R._node_ids(net)
            regs = [m.nodes[nm].region for nm in names]
            cut = [i for i in range(n) if regs[i] == 0]
            spec = ("partition=" + ".".join(ids[i] for i in cut) + "|"
                    + ".".join(ids[i] for i in range(n) if regs[i] != 0))
            arg = urllib.parse.quote(f'"{spec}"')
            heals = []

            def _heal_gauges():
                return [R._metric_value(
                    R._metrics_text(net, j),
                    "cometbft_p2p_partition_heal_seconds")
                    for j in range(n)]

            for _ in range(heal_cycles):
                # the heal gauge PERSISTS per node across cycles, so each
                # cycle's sample is the max over gauges that CHANGED from
                # their pre-cycle value — never a stale max from an
                # earlier cycle
                pre = _heal_gauges()
                for j in range(n):
                    R._rpc(net, j, f"unsafe_net_chaos?spec={arg}",
                           timeout=10.0)
                time.sleep(2.0)
                hq = max(R._height(net, i) for i in range(n))
                for j in range(n):
                    R._rpc(net, j, "unsafe_net_chaos?heal=true",
                           timeout=10.0)
                R._wait(lambda: min(R._height(net, i) for i in range(n))
                        >= hq + 1, 120 + 2 * n, "post-heal catch-up")
                post = _heal_gauges()
                changed = [v for v, p in zip(post, pre) if v != p]
                if changed:
                    heals.append(round(max(changed) * 1e3, 1))
            heals.sort()
            row["heal_samples_ms"] = heals
            row["partition_heal_p99_ms"] = heals[-1] if heals else None
        finally:
            for p in net.node_procs:
                R._kill(p)
        curve[str(n)] = row
    detail["fleet"] = {"sizes": sizes, "curve": curve}
    big = str(max(sizes))
    # sentinel names (tools/bench_compare.py): amplification is ENFORCED
    # lower-is-better; the fleet rate + heal latency stay informational
    # until a quiet round establishes their variance
    detail["gossip_votes_per_vote_needed"] = \
        curve[big].get("gossip_votes_per_vote_needed")
    detail["partition_heal_p99_ms"] = curve[big].get("partition_heal_p99_ms")
    if "50" in curve:
        detail["fleet_heights_per_s_50node"] = curve["50"]["heights_per_s"]


def bench_discovery(detail: dict) -> None:
    """Discovery-plane scenario (peer-discovery resilience PR):

      bootstrap_convergence_s     wall seconds for an ORGANIC fleet
                                  (BENCH_DISCOVERY_NODES nodes, one seed,
                                  empty address books, NO persistent
                                  wiring) to go from process spawn to
                                  every node committing — discovery IS
                                  the critical path, so this clocks the
                                  PEX plane end to end
      eclipse_book_occupancy_pct  worst per-/16-source-group share of the
                                  NEW set after a 32-identity sybil flood
                                  through the real book-intake path;
                                  the hashed-bucket geometry bounds it at
                                  stats()["src_group_occupancy_bound_pct"]

    Env knobs: BENCH_DISCOVERY=0 skips, BENCH_DISCOVERY_NODES,
    BENCH_DISCOVERY_BASE_PORT."""
    if os.environ.get("BENCH_DISCOVERY", "1") == "0":
        detail["discovery"] = "skipped: BENCH_DISCOVERY=0"
        return
    import tempfile

    from cometbft_tpu.e2e import runner as R
    from cometbft_tpu.e2e.generator import generate_fleet_manifest
    from cometbft_tpu.p2p.pex import AddrBook
    from cometbft_tpu.p2p.pex.byzantine import ByzantinePexHarness

    n = int(os.environ.get("BENCH_DISCOVERY_NODES", "6"))
    base_port = int(os.environ.get("BENCH_DISCOVERY_BASE_PORT", "8000"))
    R._resource_guard(n, base_port)
    m = generate_fleet_manifest(n, topology="organic", regions=1,
                                name=f"bench-discovery-{n}")
    d = tempfile.mkdtemp(prefix=f"bench-discovery-{n}-")
    net = R.setup(m, d, base_port)
    _progress(f"discovery: booting {n}-node organic fleet (one seed)")
    books: dict = {}
    try:
        net.app_procs = [None] * n
        t0 = time.perf_counter()
        R._boot_staggered(net)
        R._wait(lambda: all(R._height(net, i) >= m.initial_height + 2
                            for i in range(n)),
                150 + 4 * n, f"{n}-node organic fleet converging via PEX")
        boot_s = time.perf_counter() - t0
        for i in range(n):
            doc = R._rpc(net, i, "net_telemetry", timeout=10.0)
            disc = doc.get("result", {}).get("discovery") or {}
            books[f"node{i:03d}"] = disc.get("size", 0)
    finally:
        for p in net.node_procs:
            R._kill(p)

    # eclipse occupancy: the socket-free flood through the SAME intake
    # path the wire uses (32 identities, one /16, diverse forged claims)
    book = AddrBook(our_id="bench")
    ledger = ByzantinePexHarness.flood_book(book, n_identities=32,
                                            claims_per_identity=128)
    s = book.stats()
    detail["discovery"] = {
        "organic_nodes": n,
        "bootstrap_convergence_s": round(boot_s, 2),
        "addrbook_sizes": books,
        "eclipse_flood": ledger,
        "eclipse_book_occupancy_pct": s["max_src_group_occupancy_pct"],
        "eclipse_occupancy_bound_pct": s["src_group_occupancy_bound_pct"],
    }
    # sentinel names (tools/bench_compare.py)
    detail["bootstrap_convergence_s"] = round(boot_s, 2)
    detail["eclipse_book_occupancy_pct"] = s["max_src_group_occupancy_pct"]


def bench_storage(detail: dict) -> None:
    """Storage-plane scenario: consensus-WAL fsync latency (the disk
    floor under every committed height — the write_sync path EndHeight
    rides) and sqlite transactional write latency, measured on a fresh
    temp dir. Emits wal_fsync_p99_ms (TRACKED lower in
    tools/bench_compare.py) bare and under detail["storage"]."""
    import shutil
    import tempfile

    from cometbft_tpu.consensus.wal import WAL, EndHeightMessage
    from cometbft_tpu.store.db import SQLiteDB

    n = int(os.environ.get("BENCH_STORAGE_OPS", "300"))
    d = tempfile.mkdtemp(prefix="bench-storage-")
    try:
        wal = WAL(os.path.join(d, "wal", "wal.bin"))
        lat = []
        for h in range(1, n + 1):
            t0 = time.perf_counter()
            wal.write_sync(EndHeightMessage(h))
            lat.append(time.perf_counter() - t0)
        wal.close()
        lat.sort()
        p50 = lat[len(lat) // 2] * 1e3
        p99 = lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3

        db = SQLiteDB(os.path.join(d, "kv.db"))
        dlat = []
        payload = b"\x5a" * 512
        for i in range(n):
            t0 = time.perf_counter()
            db.set(b"bench-%06d" % i, payload)
            dlat.append(time.perf_counter() - t0)
        db.close()
        dlat.sort()
        detail["wal_fsync_p99_ms"] = round(p99, 3)
        detail["storage"] = {
            "wal_fsync_p50_ms": round(p50, 3),
            "wal_fsync_p99_ms": round(p99, 3),
            "db_write_p50_ms": round(dlat[len(dlat) // 2] * 1e3, 3),
            "db_write_p99_ms": round(
                dlat[min(len(dlat) - 1, int(len(dlat) * 0.99))] * 1e3, 3),
            "ops": n,
            "note": ("fsync latency on the bench host's disk; wide "
                     "sentinel threshold — the contract is that the WAL "
                     "write path stays one write+fsync, not the disk"),
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def bench_scheduler(detail: dict) -> None:
    """Global verify scheduler under a mixed offered load (ISSUE 4
    acceptance): a 4-validator in-process net committing with batched
    vote verification (consensus class) while mempool-admission
    signature rows pump concurrently (mempool class, deadline-flushed or
    riding consensus flushes as filler) and blocksync-shaped commit
    windows verify (sync class). Reports:

      sched_fill_ratio_mean       rows/lanes over every dispatched batch
      sched_fragmented_fill_mean  the SAME groups dispatched one-batch-
                                  per-producer (the pre-scheduler
                                  architecture), measured on this load
      sched_latency_per_class     submit->dispatch p50/p99 ms
      sched_direct_flush_*        consensus flush-sized batches through
                                  the scheduler (with filler queued)
    """
    import asyncio

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests"))
    from light_harness import LightChain
    from net_harness import make_net

    from cometbft_tpu import sched
    from cometbft_tpu.consensus.config import test_consensus_config
    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.types import validation

    sched.reset()
    out: dict = {}

    # ---- live mixed load: 4-val net + mempool pump + sync windows
    chain = LightChain("bench-sched", 12, n_vals=32)
    svals = chain.valsets[1]

    def _mempool_rows(n):
        rows = []
        for i in range(n):
            p = ed25519.gen_priv_key()
            m = b"bench-sched-tx-%d" % i
            rows.append((p.pub_key(), m, p.sign(m)))
        return rows

    pump_rows = _mempool_rows(8)

    async def run_net():
        cfg = test_consensus_config()
        cfg.batch_vote_verification = True
        net = await make_net(4, config=cfg, chain_id="bench-sched-net")
        submitted = rejected = 0
        await net.start()
        try:
            deadline = time.monotonic() + 60
            height_goal = 8
            sync_h = 1

            async def pump():
                # one row per submit — the real admission shape: every
                # check_tx stages a single signature row, which pre-PR
                # would have been its own (8-lane-padded) device batch
                nonlocal submitted, rejected
                while time.monotonic() < deadline:
                    for row in pump_rows:
                        try:
                            sched.get().submit([row], klass=sched.MEMPOOL)
                            submitted += 1
                        except sched.SchedulerSaturated:
                            rejected += 1
                    await asyncio.sleep(0.004)
                    if min(n.block_store.height() for n in net.nodes) >= height_goal:
                        return

            async def sync_windows():
                nonlocal sync_h
                while time.monotonic() < deadline:
                    staged = []
                    for h in range(sync_h, min(sync_h + 3, 12)):
                        lb = chain.blocks[h]
                        staged.append(validation.stage_verify_commit(
                            "bench-sched", svals, lb.commit.block_id, h,
                            lb.commit))
                    sync_h = sync_h + 3 if sync_h + 3 < 12 else 1
                    await asyncio.get_running_loop().run_in_executor(
                        None, validation.prefetch_staged, staged, "sync")
                    for s in staged:
                        s.finish()
                    await asyncio.sleep(0.02)
                    if min(n.block_store.height() for n in net.nodes) >= height_goal:
                        return

            tasks = [asyncio.create_task(pump()),
                     asyncio.create_task(sync_windows())]
            while time.monotonic() < deadline:
                if min(n.block_store.height() for n in net.nodes) >= height_goal:
                    break
                await asyncio.sleep(0.01)
            for t in tasks:
                await t
        finally:
            await net.stop()
        return (min(n.block_store.height() for n in net.nodes),
                submitted, rejected)

    height, submitted, rejected = asyncio.run(run_net())
    sched.get().flush()
    snap = sched.get().health()
    out["net_height"] = height
    out["mempool_rows_offered"] = submitted
    out["mempool_rows_rejected_backpressure"] = rejected
    out["fill_ratio_mean"] = snap["fill_ratio_mean"]
    out["fragmented_fill_ratio_mean"] = snap["fragmented_fill_ratio_mean"]
    out["fill_gain"] = (
        round(snap["fill_ratio_mean"] / snap["fragmented_fill_ratio_mean"], 3)
        if snap["fragmented_fill_ratio_mean"] else None)
    out["batches"] = snap["batches"]
    out["rows_total"] = snap["rows_total"]
    out["class_rows"] = snap["class_rows"]
    out["deadline_misses"] = snap["deadline_misses"]
    out["dispatch_shapes"] = snap["dispatch_shapes"]
    out["latency_per_class"] = sched.get().latency_quantiles()

    # ---- direct-flush no-regression check: flush-sized (128-row)
    # consensus batches through the scheduler (mempool filler queued)
    # vs the pre-scheduler fragmented verifier on identical rows
    privs = [ed25519.gen_priv_key() for _ in range(128)]
    rows = []
    for i, p in enumerate(privs):
        m = b"bench-flush-%d" % i
        rows.append((p.pub_key(), m, p.sign(m)))

    def p50p99(ts):
        ts = sorted(ts)
        return (round(ts[len(ts) // 2] * 1e3, 3),
                round(ts[min(len(ts) - 1, int(len(ts) * 0.99))] * 1e3, 3))

    sched_ts = []
    for _ in range(20):
        for row in pump_rows:
            try:
                sched.get().submit([row], klass=sched.MEMPOOL)
            except sched.SchedulerSaturated:
                pass
        t0 = time.perf_counter()
        mask = sched.get().verify_now(rows, sched.CONSENSUS)
        sched_ts.append(time.perf_counter() - t0)
        assert all(mask)
    out["direct_flush_sched_p50_ms"], out["direct_flush_sched_p99_ms"] = p50p99(sched_ts)
    out["note"] = (
        "fill_ratio_mean vs fragmented_fill_ratio_mean measures the SAME "
        "live load batched by the scheduler vs one-batch-per-producer; "
        "direct_flush_sched_* is the consensus-flush latency through the "
        "scheduler with filler queued")
    detail["sched"] = out


def bench_soak(detail: dict) -> None:
    """Sustained-saturation soak (the overload plane's acceptance
    scenario): a 4-validator in-process net commits heights while the
    loadtime saturation generator drives admission waves well past the
    mempool ceiling. The chain must keep committing with bounded height
    latency while the mempool plane sheds — graded liveness under
    overload. Emits:

      soak_heights_per_s        committed heights/s under sustained load
      admission_txs_per_s       accepted (admitted) txs/s while shedding
      height_p99_under_load_ms  p99 inter-height gap under load (TRACKED
                                lower in tools/bench_compare.py)

    plus the per-plane shed counts, the unloaded-baseline p99, and the
    scheduler's per-class deadline-miss attribution (consensus must
    read zero)."""
    import asyncio

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tests"))
    from net_harness import make_net

    from cometbft_tpu import loadtime, sched
    from cometbft_tpu.consensus.config import test_consensus_config
    from cometbft_tpu.libs.overload import OverloadRegistry
    from cometbft_tpu.mempool.mempool import ErrMempoolIsFull

    sched.reset()
    heights_goal = int(os.environ.get("BENCH_SOAK_HEIGHTS", "30"))
    quiet_goal = int(os.environ.get("BENCH_SOAK_QUIET_HEIGHTS", "8"))
    pool_size = int(os.environ.get("BENCH_SOAK_POOL", "512"))
    inflight = int(os.environ.get("BENCH_SOAK_INFLIGHT", "64"))

    async def collect_heights(node, n: int, timeout: float) -> list[float]:
        """Stamp the next n committed heights on node's store."""
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

    def p99_gap_ms(stamps: list[float]) -> float:
        gaps = sorted(b - a for a, b in zip(stamps, stamps[1:]))
        if not gaps:
            return 0.0
        return round(gaps[min(len(gaps) - 1, int(len(gaps) * 0.99))] * 1e3, 2)

    async def run() -> dict:
        cfg = test_consensus_config()
        cfg.batch_vote_verification = True  # consensus flushes ride the sched
        net = await make_net(4, config=cfg, chain_id="bench-soak-net")
        node = net.nodes[0]
        # a small pool makes saturation reachable without millions of txs;
        # the watermark dynamics are ratio-based so nothing else changes
        node.mempool.config.size = pool_size
        reg = OverloadRegistry()
        node.mempool.attach_overload(reg)
        reg.register("sched", lambda: (
            sum(sched.get()._depth.values())
            / max(1, sched.get().queue_limit)))
        await net.start()
        try:
            quiet = await collect_heights(node, quiet_goal, 60.0)

            async def submit(tx: bytes) -> bool:
                try:
                    res = await node.mempool.check_tx(tx)
                    return res.is_ok()
                except ErrMempoolIsFull:
                    return False
                except Exception:  # noqa: BLE001 - cache dupes etc.
                    return False

            totals = loadtime.LoadResult()
            stop = asyncio.Event()

            async def pump() -> None:
                # each cycle offers 4*pool_size txs — ≥2x the admission
                # ceiling even if every commit fully drains the pool.
                # max_inflight mirrors the RPC write budget: calling
                # check_tx directly bypasses the server's in-flight
                # guard, and an unbounded task wave starves the in-proc
                # validators' consensus coroutines (they share this
                # event loop — a flood the RPC guard sheds in production)
                while not stop.is_set():
                    _, res = await loadtime.generate_saturation(
                        submit, waves=4, wave_size=pool_size,
                        size=192, interval=0.005, max_inflight=inflight)
                    totals.sent += res.sent
                    totals.accepted += res.accepted
                    totals.rejected += res.rejected
                    totals.errors += res.errors

            t0 = time.monotonic()
            ptask = asyncio.create_task(pump())
            loaded = await collect_heights(node, heights_goal, 300.0)
            stop.set()
            await ptask
            elapsed = time.monotonic() - t0
        finally:
            await net.stop()
        snap = sched.get().health()
        return {
            "heights_under_load": len(loaded),
            "elapsed_s": round(elapsed, 2),
            "soak_heights_per_s": round(len(loaded) / elapsed, 2),
            "admission_txs_per_s": round(totals.accepted / elapsed, 1),
            "height_p99_unloaded_ms": p99_gap_ms(quiet),
            "height_p99_under_load_ms": p99_gap_ms(loaded),
            "offered": totals.sent,
            "accepted": totals.accepted,
            "rejected": totals.rejected,
            "errors": totals.errors,
            "sheds": {p: reg.sheds(p) for p in reg.planes()},
            "overload": reg.health(),
            "deadline_miss_by_class": snap.get("deadline_miss_by_class", {}),
            "note": ("the chain must keep committing while the mempool "
                     "plane sheds: rejected > 0 proves saturation was "
                     "reached, deadline_miss_by_class['consensus'] == 0 "
                     "proves consensus flushes never degraded"),
        }

    out = asyncio.run(run())
    detail["soak_heights_per_s"] = out["soak_heights_per_s"]
    detail["admission_txs_per_s"] = out["admission_txs_per_s"]
    detail["height_p99_under_load_ms"] = out["height_p99_under_load_ms"]
    detail["soak"] = out


def main() -> dict:
    import jax

    from cometbft_tpu.ops import compile_cache

    compile_cache.arm()

    import jax.numpy as jnp

    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.ops import ed25519_kernel as K

    detail: dict = {"backend": jax.devices()[0].platform, "batch": BATCH}

    # -- build the batch: one "validator set" signing distinct messages
    _progress("building batch")
    privs, pubs, msgs, sigs = _mk_sigs(BATCH, min(BATCH, 10240))

    cache = K.PubKeyCache()
    _progress("warm-up compile")
    ok, _ = K.verify_batch(pubs, msgs, sigs, cache=cache)  # warm-up compile
    assert ok, "warm-up batch failed verification"

    _progress("p50 latency")
    # -- p50 synchronous single-batch latency
    lat = []
    for _ in range(ITERS):
        t0 = time.perf_counter()
        ok, mask = K.verify_batch(pubs, msgs, sigs, cache=cache)
        lat.append(time.perf_counter() - t0)
        assert ok
    detail["p50_batch_latency_ms"] = round(sorted(lat)[len(lat) // 2] * 1e3, 2)

    # -- kernel-only device compute (rep-differencing), run TWICE: the
    # device-bound co-headline must be repeatable to be comparable across
    # rounds (the stream number below is link-bound and collapses under
    # dev-box contention; this one must not).
    b = K.bucket_size(BATCH)
    _, safe_pubs, rw, sw, kw = K.stage_batch(pubs, msgs, sigs, b)
    _, a_dev = cache.stage(safe_pubs, b)
    device_sigs_per_s = None
    _progress("device compute rep-differencing")
    try:
        from cometbft_tpu.ops import pallas_verify as PV

        ed_fn = PV.verify_pallas if K._pallas_available() else K.verify_math
        args = (jnp.asarray(rw), jnp.asarray(sw), jnp.asarray(kw))
        best, runs, stats = measure_device_compute(ed_fn, a_dev, *args)
        detail["device_compute_ms_per_batch"] = round(best, 2)
        detail["device_compute_runs_ms"] = runs
        # same honest-spread stat as sr25519 (median/p90/spread over all
        # post-warmup runs; min-vs-min agreement only as converged flag)
        detail["device_repeatability_pct"] = stats["spread_pct"]
        detail["device_compute_run_stats"] = stats
        device_sigs_per_s = BATCH / (best / 1e3)
        detail["device_sigs_per_s"] = round(device_sigs_per_s, 1)
        # Roofline statement (VERDICT r4 weak-9): the verify program
        # executes 2,815 field mul+sq per 128-lane block — 51-window
        # double-scalar ladder (50 scanned window steps at 30M+20S) +
        # 17-entry table build (112M+32S) + R decompression and identity
        # check (exact counts: traced op census over the scan body and
        # surrounding program). At the microbench-measured ~40 ns per
        # 128-lane field mul (pre-rolled conv 15 ns + interval-checker-
        # proved-minimal carry/fold rounds) the multiply floor is 9.0 ms
        # per 10,240 sigs; add/sub chains (~2,639 ops/block) add ~2 ms.
        # Quiet-box measurements sit AT this floor (r4 best 9.8 ms), so
        # the kernel is VPU-arithmetic-bound: the <5 ms north star needs
        # a cheaper field mul, and the conv core already runs at the ~4
        # vreg-ops/cycle issue limit. Recorded dead ends: Karatsuba,
        # cross-lane MSM, int16 tables, stacked-coordinate conv.
        detail["kernel_roofline"] = {
            "mul_sq_per_128_lanes": 2815,
            "addsub_per_128_lanes": 2639,
            "ns_per_mul_measured": 40,
            "mul_floor_ms_per_10240": 9.0,
            "floor_with_addsub_ms": 11.1,
            "floor_note": "floor uses the contention-inclusive 40 ns/mul "
                          "microbench rate; quiet-link batch measurements "
                          "as low as ~7.5 ms imply the true amortized rate "
                          "is ~30-35 ns/mul — the program sits at its "
                          "arithmetic bound either way",
            "bound": "VPU arithmetic (field-mul issue rate); conv core at "
                     "~4 vreg-ops/cycle — <5 ms requires a cheaper mul, "
                     "not more tuning of this program",
        }
    except Exception as e:  # noqa: BLE001 - CPU backend has no pallas path
        detail["device_compute_ms_per_batch"] = f"skipped: {e}"

    # -- vote-flush device latency (VERDICT r3 weak item 5): the consensus
    # hot path flushes ~100-200 vote signatures per round; this is the
    # rep-differenced device time for one flush-sized batch — the
    # non-link cost of a vote-path flush
    try:
        from cometbft_tpu.ops import pallas_verify as PV

        ed_fn = PV.verify_pallas if K._pallas_available() else K.verify_math
        fb = K.bucket_size(128)
        _, fp, frw, fsw, fkw = K.stage_batch(pubs[:128], msgs[:128], sigs[:128], fb)
        _, fa_dev = cache.stage(fp, fb)
        fl_best, _, _ = measure_device_compute(
            ed_fn, fa_dev, jnp.asarray(frw), jnp.asarray(fsw),
            jnp.asarray(fkw), rep_pair=(8, 64))
        detail["vote_flush_device_ms"] = round(fl_best, 3)
    except Exception as e:  # noqa: BLE001
        detail["vote_flush_device_ms"] = f"skipped: {e}"

    _progress("streaming throughput")
    # -- streaming throughput (wire-bound; link-capped on this dev box).
    # Send-path accounting resets here so the stream window measures the
    # STEADY-STATE wire cost per signature (the validator table is warm
    # after the batches above) — the reduced-send protocol's headline.
    from cometbft_tpu.ops import residency as _residency

    _residency.reset_send_stats()
    t0 = time.perf_counter()
    thunks = [
        K.verify_batch_async(pubs, msgs, sigs, cache=cache)
        for _ in range(STREAM_BATCHES)
    ]
    results = K.resolve_batches(thunks)
    t_stream = time.perf_counter() - t0
    assert all(m.all() for m in results)
    tpu_sigs_per_s = STREAM_BATCHES * BATCH / t_stream
    detail["stream_batches"] = STREAM_BATCHES
    detail["stream_sigs_per_s"] = round(tpu_sigs_per_s, 1)
    wire = _residency.send_stats()
    detail["wire"] = wire
    detail["wire_bytes_per_sig"] = (
        wire["steady_state_bytes_per_sig"] or wire["full_path_bytes_per_sig"])

    _progress("cpu baselines")
    # -- CPU baselines: best-of-3 trials, so dev-box contention lowers the
    # baseline (and inflates the ratio) as little as possible — the
    # comparison must not get easier when the box is busy
    pk_objs = [ed25519.PubKey(pubs[i]) for i in range(CPU_SAMPLE)]
    cpu_serial = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        for i in range(CPU_SAMPLE):
            assert pk_objs[i].verify_signature(msgs[i], sigs[i])
        cpu_serial = max(cpu_serial, CPU_SAMPLE / (time.perf_counter() - t0))
    cpu_batch_pinned = cpu_serial * PINNED_VOI_BATCH_FACTOR
    detail["cpu_serial_sigs_per_s"] = round(cpu_serial, 1)
    detail["cpu_batch_pinned_sigs_per_s"] = round(cpu_batch_pinned, 1)
    detail["vs_serial"] = round(tpu_sigs_per_s / cpu_serial, 2)
    detail["vs_batch_pinned"] = round(tpu_sigs_per_s / cpu_batch_pinned, 2)
    detail["vs_batch_note"] = VS_BATCH_NOTE
    if device_sigs_per_s is not None:
        detail["device_vs_batch_pinned"] = round(
            device_sigs_per_s / cpu_batch_pinned, 2)
    # live link model (libs/linkmodel.py): the streaming window above
    # fed the estimator with every measured h2d/fetch transfer, so the
    # link cap is MEASURED per run, never assumed
    from cometbft_tpu.libs import linkmodel

    lnk = linkmodel.link()
    detail["link_model"] = lnk.snapshot()
    bw, rtt = lnk.bandwidth_bps(), lnk.rtt_seconds()
    if rtt > 0:
        detail["link_note"] = (
            f"single-batch latency includes the measured ~{rtt * 1e3:.0f} "
            f"ms link RTT floor (live estimate)")
    bps = detail.get("wire_bytes_per_sig") or 96.0
    if lnk.converged() and bw > 0:
        detail["link_cap_sigs_per_s"] = round(bw / bps, 1)
        detail["link_cap_note"] = (
            f"stream headline is wire-bound: measured {bps:.0f} B/sig "
            f"(reduced-send accounting, was 96 pre-r06) over a measured "
            f"~{bw / 1e6:.1f} MB/s, ~{rtt * 1e3:.0f} ms RTT link (live "
            f"EWMA estimate, libs/linkmodel.py) caps it near "
            f"~{bw / bps / 1e3:.0f}k sigs/s regardless of kernel speed; "
            f"device_sigs_per_s is the chip-bound co-headline")
    else:
        detail["link_cap_note"] = (
            f"link estimator not converged this run: no link cap is "
            f"stated (measured {bps:.0f} B/sig); device_sigs_per_s is "
            f"the chip-bound co-headline")

    # -- subsystem benches (each guarded: a failure reports, not aborts)
    for fn in (bench_blocksync, bench_mixed_megacommit, bench_attribution,
               bench_challenge,
               bench_light_client, bench_light_fleet, bench_bls,
               bench_cert, bench_consensus_tpu, bench_scheduler, bench_storage,
               bench_soak, bench_mesh, bench_fleet):
        try:
            _progress(fn.__name__)
            fn(detail)
        except Exception as e:  # noqa: BLE001
            detail[fn.__name__] = f"FAILED: {type(e).__name__}: {e}"

    # HEADLINE: device-bound throughput (rep-differenced, repeatable to a
    # few % across runs). The wire-bound stream number collapses under
    # link contention and is kept in detail with the cap stated.
    headline = device_sigs_per_s if device_sigs_per_s else tpu_sigs_per_s
    record = {
        "metric": "ed25519_verify_throughput",
        "value": round(headline, 1),
        "unit": "sigs/sec/chip (device-bound)",
        "vs_baseline": round(headline / cpu_batch_pinned, 2),
        "detail": detail,
    }
    print(json.dumps(record))
    return record


def _write_out(record: dict, path: str) -> None:
    """Write the FULL bench record to a file, atomically (tmp + rename):
    the driver captures stdout with a bounded tail, which truncated
    BENCH_r05 into a `"parsed": null` round — the out-file is the
    untruncatable copy. tools/bench_compare.load_snapshot auto-discovers
    `<snapshot stem>.out.json` next to a driver snapshot and prefers it."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(record, f)
        f.write("\n")
    os.replace(tmp, path)
    print(f"[bench] full record written to {path}", file=sys.stderr,
          flush=True)


def _cli() -> int:
    """Plain `python bench.py` prints the one headline JSON line (the
    driver contract, unchanged). `--out FILE` additionally writes the
    full record to FILE so stdout truncation can never lose a round.
    `--compare BENCH_rNN.json` additionally runs the regression sentinel
    (tools/bench_compare.py) against the prior snapshot and prints its
    machine-readable verdict as a second line — exit 1 when a tracked
    metric regressed past its threshold. `--current saved.json` skips
    the run and diffs two files."""
    import argparse

    p = argparse.ArgumentParser(prog="bench.py")
    p.add_argument("--out", default="",
                   help="also write the full JSON record to this file "
                        "(atomic; name it <snapshot stem>.out.json and "
                        "bench_compare auto-discovers it)")
    p.add_argument("--compare", default="",
                   help="prior snapshot (BENCH_rNN.json or a saved bench "
                        "line) to diff this run against")
    p.add_argument("--current", default="",
                   help="with --compare: diff this saved run instead of "
                        "running the bench")
    p.add_argument("--mesh", action="store_true",
                   help="run ONLY the multi-chip mesh scenario (subprocess "
                        "on forced host devices) and print its record")
    p.add_argument("--fleet", action="store_true",
                   help="run ONLY the fleet-size-curve scenario (OS-process "
                        "testnets at BENCH_FLEET_SIZES) and print its record")
    p.add_argument("--soak", action="store_true",
                   help="run ONLY the saturation soak (overload plane): "
                        "4-val in-proc net under 2x-ceiling admission "
                        "waves; emits soak_heights_per_s, "
                        "admission_txs_per_s, height_p99_under_load_ms")
    p.add_argument("--discovery", action="store_true",
                   help="run ONLY the discovery-plane scenario: an organic "
                        "fleet bootstrapping from one seed via PEX "
                        "(bootstrap_convergence_s) + a sybil flood against "
                        "the hashed-bucket address book "
                        "(eclipse_book_occupancy_pct)")
    p.add_argument("--mesh-child", action="store_true",
                   help="internal: the in-process mesh scenario (must run "
                        "under JAX_PLATFORMS=cpu with forced host devices)")
    args = p.parse_args()
    if args.mesh_child:
        record = mesh_child_main()
        if args.out:
            _write_out(record, args.out)
        return 0
    if args.mesh:
        record = run_mesh_bench(int(os.environ.get("BENCH_MESH_DEVICES", "8")))
        print(json.dumps(record))
        if args.out:
            _write_out(record, args.out)
        return 0
    if args.soak:
        detail: dict = {}
        bench_soak(detail)
        # no top-level "value": the headline here, height_p99_under_load_ms,
        # is LOWER-better and lives under its own TRACKED name
        record = {"metric": "overload_soak",
                  "value": None,
                  "unit": "see detail.height_p99_under_load_ms (lower is "
                          "better) + soak_heights_per_s/admission_txs_per_s",
                  "detail": detail}
        print(json.dumps(record))
        if args.out:
            _write_out(record, args.out)
        return 0
    if args.discovery:
        detail: dict = {}
        bench_discovery(detail)
        # no top-level "value": the headline, bootstrap_convergence_s,
        # is LOWER-better and lives under its own TRACKED name;
        # eclipse occupancy is a bound check, informational
        record = {"metric": "discovery_plane",
                  "value": None,
                  "unit": "see detail.bootstrap_convergence_s (lower is "
                          "better) + eclipse_book_occupancy_pct",
                  "detail": detail}
        print(json.dumps(record))
        if args.out:
            _write_out(record, args.out)
        return 0
    if args.fleet:
        detail: dict = {}
        bench_fleet(detail)
        # no top-level "value": the sentinel's generic value entry is
        # higher-better (the main bench's sigs/s headline) — this
        # record's headline, amplification, is LOWER-better and lives
        # under its own correctly-directioned TRACKED name
        record = {"metric": "fleet_testnet_curves",
                  "value": None,
                  "unit": "see detail.gossip_votes_per_vote_needed "
                          "(amplification; lower is better) + fleet curve",
                  "detail": detail}
        print(json.dumps(record))
        if args.out:
            _write_out(record, args.out)
        return 0
    if not args.compare:
        record = main()
        if args.out:
            _write_out(record, args.out)
        return 0
    from tools import bench_compare

    if args.current:
        record = bench_compare.load_snapshot(args.current)
    else:
        record = main()
        if args.out:
            _write_out(record, args.out)
    verdict = bench_compare.compare(
        bench_compare.load_snapshot(args.compare), record)
    print(json.dumps(verdict))
    return 0 if verdict["verdict"] == "pass" else 1


if __name__ == "__main__":
    sys.exit(_cli())
