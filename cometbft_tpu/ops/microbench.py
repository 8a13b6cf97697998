"""Device microbenchmarks for the Ed25519 kernel components.

Dev tool, not part of the node runtime: isolates where the Pallas ladder's
device time goes (field mul, carry rounds, table selects, point ops) so
kernel-optimization rounds are driven by measurement instead of vreg-count
guesses. All timings are slope-based: each probe runs its body I and 2*I
times inside one fused kernel and reports (t(2I) - t(I)) / I, which cancels
dispatch, transfer, and fixed per-kernel overhead — link-proof by
construction.

Usage:  python -m cometbft_tpu.ops.microbench [probe ...]
"""

from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cometbft_tpu.ops import curve
from cometbft_tpu.ops import field as F
from cometbft_tpu.ops import pallas_verify as PV
from cometbft_tpu.ops import unpack as U

LANES = 128


def _time(fn, *args) -> float:
    """Median-of-5 wall time of fn(*args) fully materialized, seconds."""
    jax.tree_util.tree_map(lambda x: x.block_until_ready(), fn(*args))
    out = []
    for _ in range(5):
        t0 = time.perf_counter()
        r = fn(*args)
        jax.tree_util.tree_map(lambda x: x.block_until_ready(), r)
        out.append(time.perf_counter() - t0)
    return sorted(out)[2]


def _loop_kernel_factory(body, n_state: int, iters: int):
    """Pallas kernel: state = body(state) run `iters` times. body maps a
    tuple of n_state (20, LANES) arrays to the same. Constants enter as in
    pallas_verify (module-constant swap)."""

    def kernel(*refs):
        consts = refs[: PV._N_CONSTS]
        ins = refs[PV._N_CONSTS : PV._N_CONSTS + n_state]
        outs = refs[PV._N_CONSTS + n_state :]
        saved_f = {n: getattr(F, n) for n in PV._FIELD_CONST_NAMES}
        saved_table = curve._BASE_TABLE17
        try:
            for n, ref in zip(PV._FIELD_CONST_NAMES, consts):
                setattr(F, n, ref[:])
            curve._BASE_TABLE17 = tuple(
                r[:] for r in consts[len(PV._FIELD_CONST_NAMES) :]
            )
            state = tuple(r[:] for r in ins)
            state = jax.lax.fori_loop(
                0, iters, lambda _, s: body(s), state
            )
            for o, s in zip(outs, state):
                o[:, :] = s
        finally:
            for n, v in saved_f.items():
                setattr(F, n, v)
            curve._BASE_TABLE17 = saved_table

    @jax.jit
    def run(*arrs):
        spec = pl.BlockSpec(
            (F.NLIMBS, LANES), lambda: (0, 0), memory_space=pltpu.VMEM
        )
        const_specs = [
            pl.BlockSpec((F.NLIMBS, LANES), lambda: (0, 0), memory_space=pltpu.VMEM)
        ] * len(PV._FIELD_CONST_NAMES) + [
            pl.BlockSpec(
                (curve.TABLE17, F.NLIMBS, LANES),
                lambda: (0, 0, 0),
                memory_space=pltpu.VMEM,
            )
        ] * 4
        return pl.pallas_call(
            kernel,
            in_specs=const_specs + [spec] * n_state,
            out_specs=tuple([spec] * n_state),
            out_shape=tuple(
                jax.ShapeDtypeStruct((F.NLIMBS, LANES), jnp.int32)
                for _ in range(n_state)
            ),
        )(*PV._const_args(), *arrs)

    return run


def probe_loop(name: str, body, n_state: int, base_iters: int) -> float:
    """Per-iteration device time (us) of body via the I vs 2I slope."""
    rng = np.random.default_rng(0)
    arrs = [
        jnp.asarray(
            rng.integers(0, 8000, size=(F.NLIMBS, LANES)), dtype=jnp.int32
        )
        for _ in range(n_state)
    ]
    t1 = _time(_loop_kernel_factory(body, n_state, base_iters), *arrs)
    t2 = _time(_loop_kernel_factory(body, n_state, 2 * base_iters), *arrs)
    per = (t2 - t1) / base_iters * 1e6
    print(f"  {name:<32} {per:9.3f} us/iter  (I={base_iters}, t1={t1*1e3:.1f}ms t2={t2*1e3:.1f}ms)")
    return per


def _verify_reps_timer(batch: int, n_windows: int = 0, stages: str = "full"):
    rng = np.random.default_rng(1)
    # random valid-shaped inputs: timing only, validity irrelevant
    a = rng.integers(0, 8000, size=(4, F.NLIMBS, batch)).astype(np.int32)
    w = rng.integers(0, 2**32, size=(3, 8, batch), dtype=np.uint64).astype(np.uint32)
    args = [jnp.asarray(x) for x in (*a, *w)]

    @functools.partial(jax.jit, static_argnums=(7,))
    def reps(ax, ay, az, at, rw, sw, kw, n):
        def body(_, acc):
            m, _ok = PV._verify_pallas_bench(
                ax, ay, az, at, rw, sw, kw,
                n_windows=n_windows, stages=stages,
            )
            return acc + m.astype(jnp.int32)

        return jax.lax.fori_loop(0, n, body, jnp.zeros((batch,), jnp.int32))

    t1 = _time(reps, *args, 4)
    t2 = _time(reps, *args, 12)
    return (t2 - t1) / 8


def probe_full_verify(batch: int = 10240) -> None:
    """End-to-end verify_pallas device time, slope-based via rep loop."""
    per = _verify_reps_timer(batch)
    print(f"  verify_pallas[{batch}]            {per*1e3:9.2f} ms/batch  "
          f"({batch/per:,.0f} sigs/s)")


def probe_bisect(batch: int = 10240) -> None:
    """In-context stage costs: truncate the ladder / skip decompression and
    difference the slopes."""
    full = _verify_reps_timer(batch)
    half = _verify_reps_timer(batch, n_windows=26)
    nodec = _verify_reps_timer(batch, stages="nodecomp")
    per_win = (full - half) / 25
    blocks = batch // LANES
    print(f"  full                  {full*1e3:8.2f} ms")
    print(f"  26-window ladder      {half*1e3:8.2f} ms")
    print(f"  no R-decompress       {nodec*1e3:8.2f} ms")
    print(f"  => per-window         {per_win*1e6/blocks:8.3f} us/block")
    print(f"  => decompress         {(full-nodec)*1e6/blocks:8.3f} us/block")
    print(f"  => fixed (non-ladder) {(half - 26/51*(full-half+half))*1e3:8.2f} ms-ish")


# --------------------------------------------------------------------------
# Experimental variants (measured here before being promoted into field.py).
# --------------------------------------------------------------------------


_NCONV = 2 * F.NLIMBS


def _carry_round40(x: jnp.ndarray) -> jnp.ndarray:
    """Historical 40-column carry round (replaced in field.py by the split
    lo/hi reduce); kept here so the variant probes remain comparable."""
    c = x >> F.RADIX
    r = x & F.MASK
    shifted = jnp.concatenate(
        [
            jnp.zeros_like(c[:1]),
            c[: F.NLIMBS - 1],
            c[F.NLIMBS - 1 : F.NLIMBS] + c[_NCONV - 1 :] * F.FOLD,
            c[F.NLIMBS : _NCONV - 1],
        ],
        axis=0,
    )
    return r + shifted


def _reduce_v2(conv: jnp.ndarray) -> jnp.ndarray:
    """2x carry40 + fold + 3x carry20 (the pre-split reduce shape)."""
    for _ in range(2):
        conv = _carry_round40(conv)
    folded = conv[: F.NLIMBS] + F.FOLD * conv[F.NLIMBS :]
    for _ in range(3):
        folded = F._carry_round20(folded)
    return folded


def _mul_v2(a, b):
    return _reduce_v2(F._conv(a, b))


def _add_1round(a, b):
    return F._carry_round20(a + b)


def _conv_roll(a, b):
    """Pre-rolled 40-col conv: no jnp.pad, rows accumulate via sublane roll
    of the zero-extended b."""
    bz = jnp.concatenate([b, jnp.zeros_like(b)], axis=0)  # (40, B)
    acc = a[0:1] * bz
    for i in range(1, F.NLIMBS):
        acc = acc + a[i : i + 1] * jnp.roll(bz, i, axis=0)
    return acc


def _mul_roll(a, b):
    return _reduce_v2(_conv_roll(a, b))


def _conv_split(a, b):
    """Cyclic 20-col conv split into (lo, hi): lo = sum of products with
    i+j < 20 at col i+j, hi = products with i+j >= 20 at col i+j-20."""
    cyc = a[0:1] * b
    hi = jnp.zeros_like(b)
    row_idx = jax.lax.broadcasted_iota(jnp.int32, b.shape, 0)
    for i in range(1, F.NLIMBS):
        prod = a[i : i + 1] * jnp.roll(b, i, axis=0)
        cyc = cyc + prod
        hi = hi + jnp.where(row_idx < i, prod, 0)
    return cyc - hi, hi


def _reduce_split(lo, hi):
    """Reduce (lo, hi) 20-col accumulators: carry hi twice, twist by
    2^260 mod p = 608, add, carry lo."""
    for _ in range(2):
        hi = F._carry_round20(hi)
    x = lo + F.FOLD * hi
    for _ in range(4):
        x = F._carry_round20(x)
    return x


def _mul_split(a, b):
    return _reduce_split(*_conv_split(a, b))


def _conv_stacked(a, b):
    """Conv on stacked coords (4, 20, B): axis-1 rolls. Probes whether
    filling sublane tiles exactly (80 = 10 vregs, no 20->24 padding) beats
    4 separate (20, B) convs."""
    pad = jnp.zeros_like(b)
    bz = jnp.concatenate([b, pad], axis=1)  # (4, 40, B)
    acc = a[:, 0:1] * bz
    for i in range(1, F.NLIMBS):
        acc = acc + a[:, i : i + 1] * jnp.roll(bz, i, axis=1)
    return acc


def probe_stacked() -> None:
    print("stacked-coord conv (4x (20,128) jointly):")
    rng = np.random.default_rng(0)
    arrs4 = [
        jnp.asarray(rng.integers(0, 8000, size=(4, F.NLIMBS, LANES)), dtype=jnp.int32)
        for _ in range(2)
    ]

    def factory(iters):
        def kernel(a_ref, b_ref, o_ref):
            a, b = a_ref[:], b_ref[:]

            def body(_, s):
                c = _conv_stacked(s, b)
                return c[:, : F.NLIMBS] & 0x1FFF  # cheap feedback, shape-stable

            o_ref[:] = jax.lax.fori_loop(0, iters, body, a)

        spec = pl.BlockSpec((4, F.NLIMBS, LANES), lambda: (0, 0, 0), memory_space=pltpu.VMEM)
        return jax.jit(
            lambda a, b: pl.pallas_call(
                kernel,
                in_specs=[spec, spec],
                out_specs=spec,
                out_shape=jax.ShapeDtypeStruct((4, F.NLIMBS, LANES), jnp.int32),
            )(a, b)
        )

    t1 = _time(factory(100_000), *arrs4)
    t2 = _time(factory(200_000), *arrs4)
    per = (t2 - t1) / 100_000 * 1e6
    print(f"  4-stacked conv                   {per:9.3f} us/iter  (= {per/4:.3f} us per conv)  t1={t1*1e3:.1f}ms t2={t2*1e3:.1f}ms")


def _select17_int16(table16, digit):
    """Experimental: where-tree over int16 tables, upcast after select."""
    neg_mask = (digit < 0)[None, :]
    mag = jnp.abs(digit).astype(jnp.int16)
    coords = [c[:16] for c in table16]
    for level in (3, 2, 1, 0):
        bit = ((mag >> level) & 1)[None, None, :] == 1
        half = coords[0].shape[0] // 2
        coords = [jnp.where(bit, c[half:], c[:half]) for c in coords]
    is16 = (mag == 16)[None, :]
    out = [jnp.where(is16, t[16], c[0]).astype(jnp.int32)
           for t, c in zip(table16, coords)]
    x, y, z, t = out
    x = jnp.where(neg_mask, F.neg(x), x)
    t = jnp.where(neg_mask, F.neg(t), t)
    return curve.Point(x, y, z, t)


def probe_select16() -> None:
    print("select int16 experiment (per 128-lane block):")
    probe_loop(
        "select17 int32 (current)",
        lambda s: (
            curve._select17_signed(curve._BASE_TABLE17, s[0][0]).x,
            s[0], s[1], s[2],
        ),
        4, 200_000,
    )

    table16 = tuple(
        jnp.broadcast_to(c, (curve.TABLE17, F.NLIMBS, LANES)).astype(jnp.int16)
        for c in curve._BASE_TABLE17
    )

    def probe16(s):
        p = _select17_int16(table16, s[0][0])
        return (p.x, s[0], s[1], s[2])

    # note: table16 closes over device constants — run via XLA-level loop
    # instead of the pallas harness for a comparable slope
    import functools

    arrs = [jnp.asarray(np.random.default_rng(0).integers(
        -16, 16, size=(F.NLIMBS, LANES)), dtype=jnp.int32) for _ in range(4)]

    @functools.partial(jax.jit, static_argnums=(4,))
    def loop16(a, b, c, d, iters):
        def body(_, s):
            return probe16(s)

        return jax.lax.fori_loop(0, iters, body, (a, b, c, d))

    @functools.partial(jax.jit, static_argnums=(4,))
    def loop32(a, b, c, d, iters):
        def body(_, s):
            return (curve._select17_signed(curve._BASE_TABLE17, s[0][0]).x,
                    s[0], s[1], s[2])

        return jax.lax.fori_loop(0, iters, body, (a, b, c, d))

    for name, fn in (("xla select int16", loop16), ("xla select int32", loop32)):
        t1 = _time(fn, *arrs, 100_000)
        t2 = _time(fn, *arrs, 200_000)
        print(f"  {name:<32} {(t2-t1)/100_000*1e6:9.3f} us/iter")


def probe_variants2() -> None:
    print("variants2 (per 128-lane block):")
    probe_loop("split-conv mul", lambda s: (_mul_split(s[0], s[1]), s[0]), 2, 300_000)
    probe_loop(
        "conv_split only",
        lambda s: (_conv_split(s[0], s[1])[0], s[0]),
        2,
        300_000,
    )
    probe_loop(
        "current field.mul", lambda s: (F.mul(s[0], s[1]), s[0]), 2, 300_000
    )
    probe_loop(
        "current field.sub", lambda s: (F.sub(s[0], s[1]), s[0]), 2, 1_000_000
    )


def probe_variants() -> None:
    print("variants (per 128-lane block):")
    probe_loop("loop overhead (s+1)", lambda s: (s[0] + 1,), 1, 2_000_000)
    probe_loop("reduce_v2 mul", lambda s: (_mul_v2(s[0], s[1]), s[0]), 2, 300_000)
    probe_loop("roll-conv mul", lambda s: (_mul_roll(s[0], s[1]), s[0]), 2, 300_000)
    probe_loop(
        "conv_roll only", lambda s: (_conv_roll(s[0], s[1])[:20], s[0]), 2, 300_000
    )
    probe_loop("add 1-round", lambda s: (_add_1round(s[0], s[1]), s[0]), 2, 1_000_000)


def probe_staging(n: int = 10240, mlen: int = 110) -> None:
    """Host-staging fast path: serial per-row hashers vs the vectorized
    batch rungs (ops/hashvec + BatchStrobe128), us/row. Pure host work —
    no device involved; this is the 48 ms of BENCH_r05's
    mixed_host_staging_ms decomposed."""
    import hashlib
    import os
    import time

    from cometbft_tpu.crypto import sr25519_math as srm
    from cometbft_tpu.ops import hashvec

    rng = __import__("numpy").random.default_rng(0)
    datas = [rng.bytes(mlen) for _ in range(n)]
    print(f"  hashvec native core: {hashvec.native_available()}")

    t0 = time.perf_counter()
    for d in datas:
        hashlib.sha512(d).digest()
    t_serial = time.perf_counter() - t0
    t0 = time.perf_counter()
    hashvec.sha512_many(datas)
    t_vec = time.perf_counter() - t0
    print(f"  sha512      serial {t_serial / n * 1e6:7.2f} us/row | "
          f"vectorized {t_vec / n * 1e6:7.2f} us/row "
          f"({t_serial / t_vec:.1f}x)")

    t0 = time.perf_counter()
    hashvec.sha512_mod_l_words(datas)
    t_pipe = time.perf_counter() - t0
    print(f"  sha512+modL pipeline          | "
          f"vectorized {t_pipe / n * 1e6:7.2f} us/row")

    m = n // 4  # serial strobe is slow; measure a quarter and scale
    pubs = [rng.bytes(32) for _ in range(m)]
    rs = [rng.bytes(32) for _ in range(m)]
    msgs = [rng.bytes(mlen) for _ in range(m)]
    prior = os.environ.get("CBFT_HASHVEC")
    os.environ["CBFT_HASHVEC"] = "serial"
    try:
        t0 = time.perf_counter()
        srm.batch_compute_challenges(pubs, rs, msgs)
        t_serial = time.perf_counter() - t0
    finally:
        if prior is None:
            del os.environ["CBFT_HASHVEC"]
        else:
            os.environ["CBFT_HASHVEC"] = prior
    t0 = time.perf_counter()
    srm.batch_compute_challenges(pubs, rs, msgs)
    t_vec = time.perf_counter() - t0
    print(f"  sr challenge serial {t_serial / m * 1e6:6.2f} us/row | "
          f"batch STROBE {t_vec / m * 1e6:5.2f} us/row "
          f"({t_serial / t_vec:.1f}x)")


def main(argv: list[str]) -> None:
    probes = set(argv) or {"all"}
    print(f"backend={jax.default_backend()} device={jax.devices()[0]}")

    if probes & {"all", "staging"}:
        print("host staging (serial vs vectorized hashers):")
        probe_staging()

    if probes & {"all", "verify"}:
        print("full verify:")
        probe_full_verify()

    if probes & {"bisect"}:
        print("stage bisection:")
        probe_bisect()

    if probes & {"all", "field"}:
        print("field ops (per 128-lane block):")
        probe_loop("mul", lambda s: (F.mul(s[0], s[1]), s[0]), 2, 300_000)
        probe_loop("sq", lambda s: (F.sq(s[0]),), 1, 300_000)
        probe_loop("add(3-round carry)", lambda s: (F.add(s[0], s[1]), s[0]), 2, 1_000_000)
        probe_loop("sub(3-round carry)", lambda s: (F.sub(s[0], s[1]), s[0]), 2, 1_000_000)
        probe_loop("raw add (no carry)", lambda s: ((s[0] + s[1]) & 0x1FFF, s[0]), 2, 2_000_000)
        probe_loop("carry_round20", lambda s: (F._carry_round20(s[0]),), 1, 2_000_000)
        probe_loop(
            "conv only (no reduce)",
            lambda s: (F._conv(s[0], s[1])[:20], s[0]),
            2,
            300_000,
        )

    if probes & {"all", "variants"}:
        probe_variants()

    if probes & {"all", "variants2"}:
        probe_variants2()

    if probes & {"all", "stacked"}:
        probe_stacked()

    if probes & {"select16"}:
        probe_select16()

    if probes & {"all", "window"}:
        print("ladder window (per 128-lane block):")

        def win(s):
            p = curve.Point(s[0], s[1], s[2], s[3])
            table_a = (s[0][None] + curve._BASE_TABLE17[0],) * 4
            ds = s[0][0] & 15
            p = curve.window_step(p, ds, ds, curve._BASE_TABLE17, table_a, out_t=False)
            return tuple(p)

        probe_loop("window_step(out_t=False)", win, 4, 20_000)

        def dbl5(s):
            p = curve.Point(s[0], s[1], s[2], s[3])
            for _ in range(4):
                p = curve.double_no_t(p)
            p = curve.double(p)
            return tuple(p)

        probe_loop("5 doublings only", dbl5, 4, 20_000)

    if probes & {"all", "curve"}:
        print("curve ops (per 128-lane block):")
        probe_loop(
            "double_no_t",
            lambda s: tuple(curve.double_no_t(curve.Point(*s)))[:4],
            4,
            40_000,
        )
        probe_loop(
            "double",
            lambda s: tuple(curve.double(curve.Point(*s))),
            4,
            40_000,
        )
        probe_loop(
            "madd_pre",
            lambda s: tuple(
                curve.madd_pre(
                    curve.Point(*s), curve._select17_signed(curve._BASE_TABLE17, s[0][0])
                )
            ),
            4,
            40_000,
        )
        probe_loop(
            "select17 only",
            lambda s: (
                curve._select17_signed(curve._BASE_TABLE17, s[0][0]).x,
                s[0],
                s[1],
                s[2],
            ),
            4,
            100_000,
        )


if __name__ == "__main__":
    main(sys.argv[1:])
