"""Pallas TPU kernel for the Ed25519 ZIP-215 batch-verify ladder.

Same math as ed25519_kernel.verify_math, but executed as ONE fused device
program per 128-lane block with every intermediate held in VMEM. The
XLA-compiled ladder materializes each field-op result to HBM (a (20, B)
int32 array per op, ~2.6k field muls per verify), which makes the kernel
HBM-bound ~20x off the VPU roofline; the Pallas version streams each block
of signatures through VMEM once: reads 4x(20,128) A-coords, one (8,128)
packed R block and 2x(51,128) signed window digits, writes a (1,128) mask,
and does the entire signed-window double-scalar ladder + R decompression
in on-chip memory.

Ladder: 51 windows of signed 5-bit digits — 5 doublings (4 of them
skipping the unused T output) + a mixed premultiplied-T base add + a
premultiplied-T point add per window (curve.windowed_double_scalar_signed
is the shape-polymorphic source of truth; the kernel body inlines its loop
so Mosaic sees a flat fori_loop).

The kernel body reuses the shape-polymorphic field/curve jnp code
(field.py, curve.py) — Pallas traces it onto Mosaic. Pallas forbids
closing over device constants, so the field constants (M_SUB, D2, the
17-entry [d]B window table, ...) enter as broadcast kernel inputs and are
swapped into the field/curve modules for the duration of the
(single-threaded) kernel trace. Signed digit recoding runs as a tiny XLA
prelude (unpack.words_to_digits5_signed) — its 51-step carry scan is
hostile to the fused kernel but trivial for XLA.

Reference seam: crypto/ed25519/ed25519.go:208-241 (curve25519-voi batch
verifier) — this is its device replacement.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cometbft_tpu.ops import curve
from cometbft_tpu.ops import field as F
from cometbft_tpu.ops import unpack as U

LANES = 128  # one VPU lane row per block; VMEM use ~3 MB/block
NDIG = U.NDIGITS5

# Constants the traced field/curve code needs, pre-broadcast to the lane
# width so they're ordinary VMEM blocks (index_map pins them to block 0).
_FIELD_CONST_NAMES = ("M_SUB", "D", "D2", "SQRT_M1", "ONE")


def _const_args() -> tuple[np.ndarray, ...]:
    out = [
        np.ascontiguousarray(
            np.broadcast_to(np.asarray(getattr(F, n)), (F.NLIMBS, LANES))
        )
        for n in _FIELD_CONST_NAMES
    ]
    for t in curve._BASE_TABLE17:
        out.append(
            np.ascontiguousarray(
                np.broadcast_to(np.asarray(t), (curve.TABLE17, F.NLIMBS, LANES))
            )
        )
    return tuple(out)


_N_CONSTS = len(_FIELD_CONST_NAMES) + 4


def _verify_block_kernel(*refs, n_windows: int = 0, stages: str = "full",
                         scheme: str = "ed25519"):
    """consts..., A-coords (20, L) int32, packed R words (8, L) uint32,
    signed digits s/k (51, L) int32, out (1, L) int32 mask.

    scheme selects the decode + cofactor pair: "ed25519" = ZIP-215
    decompression + [8] coset check; "sr25519" = ristretto255 decode + [4]
    coset check (ristretto equality). The ladder between them is byte-for-
    byte the same program.

    n_windows/stages are microbench bisection knobs (ops/microbench.py):
    n_windows truncates the ladder, stages="nodecomp" skips the R
    decompression — both produce WRONG masks and exist only to slope out
    per-stage in-context device cost. Production callers use the defaults.

    A second (1, 1) SMEM output accumulates the batch-wide all-ok scalar
    across grid blocks (TPU grid iterations run sequentially, so the
    revisited block is a running AND) — the reduced-fetch header
    (ed25519_kernel._integrity_parts) rides on it without materializing a
    separate mask reduction."""
    consts = refs[:_N_CONSTS]
    ax, ay, az, at, rw, sdig_ref, kdig_ref, out, ok_out = refs[_N_CONSTS:]

    saved_f = {n: getattr(F, n) for n in _FIELD_CONST_NAMES}
    saved_table = curve._BASE_TABLE17
    saved_sqn = F.SQN_UNROLL_LIMIT
    try:
        for n, ref in zip(_FIELD_CONST_NAMES, consts):
            setattr(F, n, ref[:])
        curve._BASE_TABLE17 = tuple(
            r[:] for r in consts[len(_FIELD_CONST_NAMES):]
        )
        # fully unroll squaring runs: Mosaic loop overhead per iteration is
        # comparable to one squaring (see field.SQN_UNROLL_LIMIT)
        F.SQN_UNROLL_LIMIT = 1 << 30
        table_b = curve._BASE_TABLE17

        a = curve.Point(ax[:], ay[:], az[:], at[:])
        if stages == "nodecomp":
            ok_r, r = jnp.ones(a.x.shape[1:], dtype=bool), a
        elif scheme == "sr25519":
            from cometbft_tpu.ops import sr25519_kernel as SRK

            ok_r, r = SRK.ristretto_decode_device(rw[:])
        else:
            r_words = rw[:]
            y_r = U.words_to_y_limbs(r_words)
            sign_r = U.words_sign(r_words)
            ok_r, r = curve.decompress_zip215(y_r, sign_r)

        neg_a = curve.neg(a)
        table_a = curve.build_point_table17(neg_a)

        zero = jnp.zeros_like(neg_a.x)
        one = zero + F.ONE
        init = curve.Point(zero, one, one, zero)

        nw = n_windows or NDIG

        def body(j, acc):
            # most-significant digit first: index nw-1-j
            i = nw - 1 - j
            ds = sdig_ref[pl.ds(i, 1), :][0]
            dk = kdig_ref[pl.ds(i, 1), :][0]
            return curve.window_step(acc, ds, dk, table_b, table_a, out_t=False)

        acc = jax.lax.fori_loop(0, nw - 1, body, init)
        # final (LSB) window outside the loop: the only one whose A-add must
        # materialize T (the add of -R below reads it)
        sb_ka = curve.window_step(
            acc, sdig_ref[pl.ds(0, 1), :][0], kdig_ref[pl.ds(0, 1), :][0],
            table_b, table_a, out_t=True,
        )
        diff = curve.add(sb_ka, curve.neg(r))
        if scheme == "sr25519":  # cofactor 4: ristretto equality
            coset = curve.double(curve.double(diff))
        else:  # cofactor 8: ZIP-215
            coset = curve.mul_by_cofactor(diff)
        valid = curve.is_identity(coset)
        blk = (valid & ok_r).astype(jnp.int32)
        out[0, :] = blk
        blk_ok = blk.min()  # 1 iff every lane in this 128-lane block passed

        @pl.when(pl.program_id(0) == 0)
        def _init_ok():
            ok_out[0, 0] = blk_ok

        @pl.when(pl.program_id(0) != 0)
        def _and_ok():
            ok_out[0, 0] = jnp.minimum(ok_out[0, 0], blk_ok)
    finally:
        for n, v in saved_f.items():
            setattr(F, n, v)
        curve._BASE_TABLE17 = saved_table
        F.SQN_UNROLL_LIMIT = saved_sqn


@functools.partial(
    jax.jit, static_argnames=("interpret", "n_windows", "stages", "scheme")
)
def _verify_pallas_bench(
    ax, ay, az, at, r_words, s_words, k_words, interpret=False,
    n_windows=0, stages="full", scheme="ed25519",
):
    """Internal entry with microbench bisection knobs (n_windows/stages,
    see _verify_block_kernel) — non-default knob values produce WRONG
    masks. Production code uses verify_pallas, which cannot express them."""
    b = ax.shape[1]
    assert b % LANES == 0, f"batch {b} not a multiple of {LANES}"
    s_dig = U.words_to_digits5_signed(s_words)
    k_dig = U.words_to_digits5_signed(k_words)
    grid = (b // LANES,)
    const_specs = [
        pl.BlockSpec((F.NLIMBS, LANES), lambda i: (0, 0), memory_space=pltpu.VMEM)
    ] * len(_FIELD_CONST_NAMES) + [
        pl.BlockSpec(
            (curve.TABLE17, F.NLIMBS, LANES), lambda i: (0, 0, 0),
            memory_space=pltpu.VMEM,
        )
    ] * 4
    limb_spec = pl.BlockSpec((F.NLIMBS, LANES), lambda i: (0, i), memory_space=pltpu.VMEM)
    word_spec = pl.BlockSpec((U.WORDS, LANES), lambda i: (0, i), memory_space=pltpu.VMEM)
    dig_spec = pl.BlockSpec((NDIG, LANES), lambda i: (0, i), memory_space=pltpu.VMEM)
    out_spec = pl.BlockSpec((1, LANES), lambda i: (0, i), memory_space=pltpu.VMEM)
    ok_spec = pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)
    mask, allok = pl.pallas_call(
        functools.partial(
            _verify_block_kernel, n_windows=n_windows, stages=stages,
            scheme=scheme,
        ),
        grid=grid,
        in_specs=const_specs + [limb_spec] * 4 + [word_spec] + [dig_spec] * 2,
        out_specs=(out_spec, ok_spec),
        out_shape=(
            jax.ShapeDtypeStruct((1, b), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ),
        interpret=interpret,
        name=f"verify_ladder_{scheme}",
    )(*_const_args(), ax, ay, az, at, r_words, s_dig, k_dig)
    return mask[0] != 0, allok[0, 0] != 0


def verify_pallas(ax, ay, az, at, r_words, s_words, k_words, interpret=False):
    """(20, B) int32 A-coords + (8, B) uint32 packed r/s/k words ->
    (B,) bool mask (ed25519 ZIP-215). B must be a multiple of LANES
    (callers fall back to the XLA path for smaller buckets)."""
    return _verify_pallas_bench(
        ax, ay, az, at, r_words, s_words, k_words, interpret=interpret
    )[0]


def verify_pallas_ok(ax, ay, az, at, r_words, s_words, k_words,
                     interpret=False):
    """verify_pallas plus the fused all-ok scalar — the reduced-fetch
    header's device-side reduction (kernel-accumulated, see
    _verify_block_kernel). Pairs with ed25519_kernel.verify_math_ok as the
    PallasGate (pallas_fn, xla_fn) couple."""
    return _verify_pallas_bench(
        ax, ay, az, at, r_words, s_words, k_words, interpret=interpret
    )


def verify_pallas_ok_traced(ax, ay, az, at, r_words, s_words, k_words):
    """verify_pallas_ok for a caller that is itself being traced (the
    ed25519 trip's verify program, ed25519_kernel._verify_programs): the
    entry's body WITHOUT its jit. A jit nested in the caller's made the
    kernel's lowering eleven times slower on the chip (52.0 s against
    4.7 s at 256 lanes, PERF.md PR 27 k3), every process, cache or no
    cache; compiling for a described chip in the sandbox does not show
    it."""
    return _verify_pallas_bench.__wrapped__(
        ax, ay, az, at, r_words, s_words, k_words)


@functools.partial(jax.jit, static_argnames=("interpret",))
def verify_pallas_sr_ok(ax, ay, az, at, r_words, s_words, k_words,
                        interpret=False):
    """sr25519 (schnorrkel/ristretto) variant of verify_pallas_ok (mask,
    all-ok scalar): same ladder, ristretto decode, cofactor-4 coset check.
    A program of its own name, `jit_verify_pallas_sr_ok`, so that a device
    trace tells the sr25519 ladder from the ed25519 ones (the benchmark's
    sr25519_kernel_roofline finds it by `verify_pallas_sr`; `verify_pallas`
    still matches it for the roofline of both schemes). The entry's body
    without its jit: no program nests one (verify_pallas_ok_traced)."""
    return _verify_pallas_bench.__wrapped__(
        ax, ay, az, at, r_words, s_words, k_words, interpret=interpret,
        scheme="sr25519",
    )


def verify_pallas_sr(ax, ay, az, at, r_words, s_words, k_words,
                     interpret=False):
    """The mask of verify_pallas_sr_ok alone."""
    return verify_pallas_sr_ok(
        ax, ay, az, at, r_words, s_words, k_words, interpret=interpret)[0]
