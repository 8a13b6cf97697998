"""GF(2^255 - 19) arithmetic on TPU vector lanes.

Representation: radix-2^13, 20 limbs (260 bits), little-endian, int32,
LIMB-AXIS FIRST: a field element batch is shape (20, B). The batch axis is
minor-most so it lands on the TPU's 128-wide vector lanes (one lane = one
element); the 20-limb axis sits on sublanes. The transposed layout is worth
~6x utilization over (B, 20), where the limb axis would waste 108/128 lanes.
Chosen so every intermediate of a schoolbook 20x20 limb convolution fits
signed int32 — the TPU VPU's native integer width (no int64, no widening
multiply).

Invariant ("carried"): per-limb SIGNED intervals — the least fixpoint of
{mul, sq, add, sub, neg} over their own outputs, computed and proved int32-
safe by tests/test_field_intervals.py (see the block comment above
CARRIED_MAX; the naive "every limb small enough for any column sum" bound
does NOT hold). add/sub/mul/sq take and return carried values. Values are
redundant mod p (roughly [0, 2^260), and a wrap round can leave limbs that
spell a small NEGATIVE integer); canonicalize() produces the unique
representative in [0, p), exactly, for every such input: comparisons,
parity checks and re-compression rest on it.

Reference seam: this replaces the 64-bit limb arithmetic inside
curve25519-voi that the Go reference leans on (crypto/ed25519/ed25519.go:37);
the design here is TPU-native, not a translation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from cometbft_tpu.ops import limbs as L

RADIX = L.RADIX
NLIMBS = L.NLIMBS
MASK = L.MASK

P = 2**255 - 19
# 2^260 mod p = 2^5 * 19: the fold multiplier for carry-out of limb 19.
FOLD = 19 << (NLIMBS * RADIX - 255)  # 608

# d, 2d, sqrt(-1) as limb constants.
_D_INT = (-121665 * pow(121666, P - 2, P)) % P
_SQRT_M1_INT = pow(2, (P - 1) // 4, P)


def _const(x: int) -> jnp.ndarray:
    """(20, 1) so constants broadcast over the trailing batch axis."""
    return jnp.asarray(L.int_to_limbs(x), dtype=jnp.int32)[:, None]


def _const_loose(x: int) -> jnp.ndarray:
    """Constant whose top limb may exceed 13 bits (used for the subtraction
    bias M = 33p, which is 261 bits)."""
    out = np.zeros(NLIMBS, dtype=np.int64)
    for i in range(NLIMBS - 1):
        out[i] = x & MASK
        x >>= RADIX
    out[NLIMBS - 1] = x
    assert x < 2**15
    return jnp.asarray(out, dtype=jnp.int32)[:, None]


D = _const(_D_INT)
D2 = _const((2 * _D_INT) % P)
SQRT_M1 = _const(_SQRT_M1_INT)
ONE = _const(1)
# Subtraction bias: smallest multiple of p that dominates any carried value
# (carried max ~ 2^260 + 2^251 < 33p), keeping a + M - b positive.
M_SUB = _const_loose(33 * P)


def zeros_like(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.zeros_like(a)


# Carried-limb invariant ("C"): per-limb signed intervals, the least
# fixpoint of {mul, sq, add, sub, neg} over their own outputs, mechanically
# verified by tests/test_field_intervals.py, which mirrors every op below in
# exact interval arithmetic and proves (a) closure, (b) every intermediate —
# conv columns included — fits int32, (c) the value bound stays under the
# subtraction bias M = 33p. The fixpoint's shape: limbs 0 and 1 reach ~25.5k
# (the 2^260 wrap concentrates carry mass there), limbs 2..19 stay ~8.2k —
# the naive "every limb below sqrt(2^31/20)" bound is FALSE, and only the
# per-limb exact analysis shows the conv columns still fit int32 (columns
# pair at most two oversized limbs). CARRIED_MAX is the checker-proved
# per-limb ceiling.
CARRIED_MAX = 25600

# Carry-round counts per op, tuned on-device (ops/microbench.py) and proved
# sufficient by the interval checker. One round is a whole-array
# shift/mask/roll; each extra round costs ~20 ns per 128-lane block inside
# the Pallas ladder, and the ladder runs ~2.6k reduced ops per signature —
# round counts are THE device-time knob of the whole kernel.
ADD_ROUNDS = 1
SUB_ROUNDS = 1
HI_ROUNDS = 1
CONV20_ROUNDS = 2


def _carry_round20(x: jnp.ndarray) -> jnp.ndarray:
    """One parallel carry round on 20 limbs with top wrap (2^260 = FOLD):
    whole-array shift/mask/roll — no sequential limb chain, so the HLO stays
    tiny and XLA vectorizes across the batch AND limb axes. Arithmetic
    right-shift floors, so negative intermediates (from sub) carry
    correctly."""
    c = x >> RADIX
    r = x & MASK
    shifted = jnp.concatenate([c[NLIMBS - 1:] * FOLD, c[: NLIMBS - 1]], axis=0)
    return r + shifted


def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    x = a + b
    for _ in range(ADD_ROUNDS):
        x = _carry_round20(x)
    return x


def sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    x = a + M_SUB - b
    for _ in range(SUB_ROUNDS):
        x = _carry_round20(x)
    return x


def neg(a: jnp.ndarray) -> jnp.ndarray:
    x = M_SUB - a
    for _ in range(SUB_ROUNDS):
        x = _carry_round20(x)
    return x


_NCONV = 2 * NLIMBS  # 39 product columns + 1 carry headroom column


def _carry_round20_nowrap(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One carry round WITHOUT the 2^260 wrap: returns (rounded, top carry
    (1, B)). Used on the high half of the product, whose own wrap factor
    would be FOLD^2 — the top carry is folded in exactly once at the end."""
    c = x >> RADIX
    r = x & MASK
    shifted = jnp.concatenate([jnp.zeros_like(c[:1]), c[: NLIMBS - 1]], axis=0)
    return r + shifted, c[NLIMBS - 1:]


def _conv_reduce(conv: jnp.ndarray) -> jnp.ndarray:
    """(..., 40) product columns (col 39 zero) -> carried (..., 20).

    Split form: lo = cols 0..19, hi = cols 20..39 (weight 2^260 = FOLD per
    lo-column). hi is carried on 20 columns only (no 40-wide vector ever
    materializes — measured faster than carry rounds on the (40, B) array,
    ops/microbench.py), its top carries (weight 2^520 = FOLD^2 at column 0)
    are accumulated separately, then everything folds into lo and two
    20-column rounds restore the carried invariant. Round counts proved by
    tests/test_field_intervals.py."""
    lo, hi = conv[:NLIMBS], conv[NLIMBS:]
    top = None
    for _ in range(HI_ROUNDS):
        hi, t = _carry_round20_nowrap(hi)
        top = t if top is None else top + t
    folded = lo + FOLD * hi
    folded = jnp.concatenate(
        [folded[:1] + (FOLD * FOLD) * top, folded[1:]], axis=0
    )
    for _ in range(CONV20_ROUNDS):
        folded = _carry_round20(folded)
    return folded


def _conv(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Schoolbook polynomial product, pre-rolled form: row i (a_i * b) lands
    at columns i..i+19 of the zero-extended accumulator via a sublane roll
    of the zero-padded b — Mosaic turns each roll into cheap vreg funnel
    shifts, measured 3x faster per conv than materializing jnp.pad'ed rows
    (ops/microbench.py)."""
    pad_shape = list(b.shape)
    pad_shape[0] = _NCONV - NLIMBS
    bz = jnp.concatenate([b, jnp.zeros(pad_shape, dtype=b.dtype)], axis=0)
    acc = a[0:1] * bz
    for i in range(1, NLIMBS):
        acc = acc + a[i: i + 1] * jnp.roll(bz, i, axis=0)
    return acc


def mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return _conv_reduce(_conv(a, b))


def sq(a: jnp.ndarray) -> jnp.ndarray:
    return _conv_reduce(_conv(a, a))


# Squaring-run unroll threshold. Default keeps the XLA HLO small (runs of
# up to 100 squarings become fori_loops). The Pallas kernel raises it for
# the duration of its trace (pallas_verify._verify_block_kernel's
# constant-swap try/finally): inside Mosaic a fori_loop whose body is ONE
# squaring pays per-iteration loop overhead comparable to the squaring
# itself — unrolling the pow22523 chain cut the R-decompression stage ~3x
# on device (ops/microbench.py bisect probe).
SQN_UNROLL_LIMIT = 4


def _sqn(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """x^(2^n) via n squarings."""
    if n <= SQN_UNROLL_LIMIT:
        for _ in range(n):
            x = sq(x)
        return x
    return jax.lax.fori_loop(0, n, lambda _, v: sq(v), x)


def pow22523(z: jnp.ndarray) -> jnp.ndarray:
    """z^((p-5)/8) = z^(2^252 - 3) — the exponentiation at the heart of
    modular sqrt / point decompression. Standard ref10 addition chain
    (254 squarings + 11 multiplies), expressed with fori_loop squaring runs."""
    z2 = sq(z)
    z9 = mul(_sqn(z2, 2), z)
    z11 = mul(z9, z2)
    z_5_0 = mul(sq(z11), z9)  # 2^5 - 2^0
    z_10_0 = mul(_sqn(z_5_0, 5), z_5_0)
    z_20_0 = mul(_sqn(z_10_0, 10), z_10_0)
    z_40_0 = mul(_sqn(z_20_0, 20), z_20_0)
    z_50_0 = mul(_sqn(z_40_0, 10), z_10_0)
    z_100_0 = mul(_sqn(z_50_0, 50), z_50_0)
    z_200_0 = mul(_sqn(z_100_0, 100), z_100_0)
    z_250_0 = mul(_sqn(z_200_0, 50), z_50_0)
    return mul(_sqn(z_250_0, 2), z)


_TOP_SHIFT = 255 - (NLIMBS - 1) * RADIX  # bit 255 sits at bit 8 of limb 19
_P_INTS = tuple(int(v) for v in L.int_to_limbs(P))


def _ripple(l: list[jnp.ndarray]) -> list[jnp.ndarray]:
    """One sequential carry over limbs 0..18 into limb 19, no wrap: every
    carry reaches the top however long the run of full limbs it crosses.
    Leaves limbs 0..18 in [0, MASK] and the value unchanged."""
    out = []
    c = jnp.zeros_like(l[0])
    for i in range(NLIMBS - 1):
        v = l[i] + c
        out.append(v & MASK)
        c = v >> RADIX
    out.append(l[NLIMBS - 1] + c)
    return out


def canonicalize(x: jnp.ndarray) -> jnp.ndarray:
    """Unique representative mod p, limbs canonical, value in [0, p), for
    EVERY input with limbs in [-M_SUB limb, 2^30) — the carried invariant
    and anything an add/sub of carried values can hold. Exact, not
    probabilistic: the parallel rounds of the ops above move a carry one
    limb a round, and a multiple of p written with a run of 8191 limbs
    (p's own limbs 1..18) needs it to cross up to 19; here two sequential
    ripples do that. Steps, each mirrored in exact interval arithmetic by
    tests/test_field_intervals.py:
      1. bias by 33p limb-wise (M_SUB): every limb, so the value, is >= 0
         (redundant limbs CAN encode a negative integer after a wrap
         round), and no borrow can run off the top;
      2. ripple: limbs 0..18 canonical, limb 19 holds all the excess;
      3. fold bits >= 255 of limb 19 into limb 0 (2^255 = 19 mod p);
      4. ripple again: limb 19 in [0, 256], value < 2^255 + 2^247 < 2p;
      5. one conditional subtract of p.
    The scope names its ops in the XLA ladder's device trace; the Pallas
    kernel traces this same function into its one custom call."""
    with jax.named_scope("canonical_ripple"):
        x = x + M_SUB
        l = _ripple([x[i] for i in range(NLIMBS)])
        hi = l[NLIMBS - 1] >> _TOP_SHIFT
        l[NLIMBS - 1] = l[NLIMBS - 1] & ((1 << _TOP_SHIFT) - 1)
        l[0] = l[0] + 19 * hi
        l = _ripple(l)
        borrow = jnp.zeros_like(l[0])
        sub_l = []
        for i in range(NLIMBS):
            v = l[i] - _P_INTS[i] - borrow
            borrow = (v < 0).astype(jnp.int32)
            sub_l.append(v + (borrow << RADIX))
        ge_p = borrow == 0
        out = [jnp.where(ge_p, sub_l[i], l[i]) for i in range(NLIMBS)]
        return jnp.stack(out, axis=0)


def is_zero(x: jnp.ndarray) -> jnp.ndarray:
    """(20, ...) -> (...,) bool: x == 0 mod p."""
    return jnp.all(canonicalize(x) == 0, axis=0)


def eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return is_zero(sub(a, b))


def parity(x: jnp.ndarray) -> jnp.ndarray:
    """LSB of the canonical representative (the compressed sign bit)."""
    return canonicalize(x)[0] & 1
