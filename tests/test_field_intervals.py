"""Mechanical interval analysis of the GF(2^255-19) limb arithmetic.

field.py's carry-round counts (ADD_ROUNDS/SUB_ROUNDS/HI_ROUNDS/
CONV20_ROUNDS) are the device-time knob of the whole Ed25519 kernel: each
round costs ~20 ns per 128-lane block and the ladder runs ~2.6k reduced ops
per signature. This test PROVES the configured counts sound instead of
trusting hand analysis: it mirrors every op of field.py in exact per-limb
interval arithmetic (Python ints, no overflow), computes the least fixpoint
of {mul, sq, add, sub, neg} over their own outputs starting from canonical
inputs, and asserts:

  1. closure — the fixpoint exists and every op maps it into itself;
  2. int32 safety — every intermediate (conv columns included) stays inside
     signed 32-bit range, with the multiply-by-FOLD checked pre-add;
  3. bias domination — the max value representable by carried limbs stays
     below the subtraction bias M = 33p, so a + M - b never goes negative;
  4. the documented CARRIED_MAX really is a per-limb ceiling;
  5. canonicalize's sequential steps (bias, ripple, fold, ripple, subtract)
     stay inside int32, land every limb in canonical range and leave a
     value under 2p for the one conditional subtract, for every input from
     canonical limbs to limbs of 2^30.

If someone lowers a round count that the hardware could not absorb, this
test fails before any random test would (random inputs almost never reach
the interval extremes).
"""

from __future__ import annotations

import numpy as np
import pytest

from cometbft_tpu.ops import field as F

RADIX = F.RADIX
MASK = F.MASK
FOLD = F.FOLD
N = F.NLIMBS
NCONV = F._NCONV
INT32_MIN, INT32_MAX = -(2**31), 2**31 - 1

M_SUB = [int(x) for x in np.asarray(F.M_SUB)[:, 0]]

Interval = tuple[int, int]


def _chk(iv: Interval) -> Interval:
    lo, hi = iv
    assert lo <= hi
    assert INT32_MIN <= lo and hi <= INT32_MAX, f"int32 overflow: [{lo}, {hi}]"
    return iv


def iv_add(a: Interval, b: Interval) -> Interval:
    return _chk((a[0] + b[0], a[1] + b[1]))


def iv_sub(a: Interval, b: Interval) -> Interval:
    return _chk((a[0] - b[1], a[1] - b[0]))


def iv_mul(a: Interval, b: Interval) -> Interval:
    ps = [a[0] * b[0], a[0] * b[1], a[1] * b[0], a[1] * b[1]]
    return _chk((min(ps), max(ps)))


def iv_scale(k: int, a: Interval) -> Interval:
    return _chk((k * a[0], k * a[1])) if k >= 0 else _chk((k * a[1], k * a[0]))


def iv_shift_by(a: Interval, k: int) -> Interval:
    return (a[0] >> k, a[1] >> k)


def iv_low_bits(a: Interval, k: int) -> Interval:
    # exact when the interval sits inside one 2^k-block, else [0, 2^k - 1]
    if (a[0] >> k) == (a[1] >> k):
        return (a[0] & ((1 << k) - 1), a[1] & ((1 << k) - 1))
    return (0, (1 << k) - 1)


def iv_shift(a: Interval) -> Interval:
    return iv_shift_by(a, RADIX)


def iv_mask(a: Interval) -> Interval:
    return iv_low_bits(a, RADIX)


def iv_join(a: Interval, b: Interval) -> Interval:
    return (min(a[0], b[0]), max(a[1], b[1]))


Vec = list  # list of Interval, one per limb/column


def carry_round20(x: Vec) -> Vec:
    c = [iv_shift(v) for v in x]
    r = [iv_mask(v) for v in x]
    shifted = [iv_scale(FOLD, c[N - 1])] + c[: N - 1]
    return [iv_add(ri, si) for ri, si in zip(r, shifted)]


def carry_round20_nowrap(x: Vec) -> tuple[Vec, Interval]:
    c = [iv_shift(v) for v in x]
    r = [iv_mask(v) for v in x]
    shifted = [(0, 0)] + c[: N - 1]
    return [iv_add(ri, si) for ri, si in zip(r, shifted)], c[N - 1]


def conv(a: Vec, b: Vec) -> Vec:
    cols: Vec = [(0, 0)] * NCONV
    for i in range(N):
        for j in range(N):
            cols[i + j] = iv_add(cols[i + j], iv_mul(a[i], b[j]))
    return cols


def conv_reduce(cols: Vec) -> Vec:
    lo, hi = cols[:N], cols[N:]
    top: Interval = (0, 0)
    for _ in range(F.HI_ROUNDS):
        hi, t = carry_round20_nowrap(hi)
        top = iv_add(top, t)
    folded = [iv_add(lo[i], iv_scale(FOLD, hi[i])) for i in range(N)]
    folded[0] = iv_add(folded[0], iv_scale(FOLD * FOLD, top))
    for _ in range(F.CONV20_ROUNDS):
        folded = carry_round20(folded)
    return folded


def op_mul(a: Vec, b: Vec) -> Vec:
    return conv_reduce(conv(a, b))


def op_add(a: Vec, b: Vec) -> Vec:
    x = [iv_add(ai, bi) for ai, bi in zip(a, b)]
    for _ in range(F.ADD_ROUNDS):
        x = carry_round20(x)
    return x


def op_sub(a: Vec, b: Vec) -> Vec:
    x = [iv_sub(iv_add(ai, (mi, mi)), bi) for ai, bi, mi in zip(a, b, M_SUB)]
    for _ in range(F.SUB_ROUNDS):
        x = carry_round20(x)
    return x


def op_neg(a: Vec) -> Vec:
    x = [iv_sub((mi, mi), ai) for ai, mi in zip(a, M_SUB)]
    for _ in range(F.SUB_ROUNDS):
        x = carry_round20(x)
    return x


CANONICAL: Vec = [(0, MASK)] * N  # constants, unpacked wire inputs


def compute_fixpoint(max_iters: int = 64) -> Vec:
    c = list(CANONICAL)
    for _ in range(max_iters):
        outs = [op_mul(c, c), op_add(c, c), op_sub(c, c), op_neg(c)]
        joined = list(c)
        for o in outs:
            joined = [iv_join(x, y) for x, y in zip(joined, o)]
        if joined == c:
            return c
        c = joined
    pytest.fail("carried-limb invariant did not reach a fixpoint")


def test_fixpoint_closure_and_int32_safety():
    """Closure + int32 safety: computing the fixpoint runs every op over
    interval extremes; _chk raises inside if anything can overflow."""
    c = compute_fixpoint()
    # the ops map the fixpoint into itself (re-verify explicitly)
    for out in (op_mul(c, c), op_add(c, c), op_sub(c, c), op_neg(c)):
        for limb_out, limb_c in zip(out, c):
            assert limb_c[0] <= limb_out[0] and limb_out[1] <= limb_c[1]


def test_carried_max_is_a_ceiling():
    c = compute_fixpoint()
    worst = max(hi for _, hi in c)
    assert worst <= F.CARRIED_MAX, (
        f"fixpoint limb max {worst} exceeds documented CARRIED_MAX "
        f"{F.CARRIED_MAX}"
    )
    # int32 safety of the conv does NOT follow from a naive
    # 20 * CARRIED_MAX^2 bound (that is ~1.3e10) — it holds only because the
    # oversized limbs sit at fixed positions, which compute_fixpoint checks
    # column-exactly via _chk inside conv().


def test_sub_bias_dominates_every_carried_value():
    """a + M - b >= 0 requires M >= value(b) for every carried b."""
    c = compute_fixpoint()
    max_value = sum(hi * (1 << (RADIX * i)) for i, (_, hi) in enumerate(c))
    m_value = sum(mi * (1 << (RADIX * i)) for i, mi in enumerate(M_SUB))
    assert m_value == 33 * F.P
    assert max_value < m_value, (
        f"carried value can reach {max_value:#x}, bias is only {m_value:#x}"
    )


# ---- canonicalize: the exact form (two sequential ripples, one fold) ------

TOP_SHIFT = F._TOP_SHIFT
P_INTS = list(F._P_INTS)
# Every input canonicalize promises to take: limbs from minus the bias's
# own limb (what step 1 can lift to zero) up to 2^30.
WIDEST: Vec = [(-m, 2**30) for m in M_SUB]


def ripple(l: Vec) -> Vec:
    """field._ripple: limbs 0..18 masked, each carry added to the next
    limb, the last one into limb 19; no wrap."""
    out: Vec = []
    c: Interval = (0, 0)
    for i in range(N - 1):
        v = iv_add(l[i], c)
        out.append(iv_mask(v))
        c = iv_shift(v)
    out.append(iv_add(l[N - 1], c))
    return out


def value_max(x: Vec) -> int:
    return sum(hi << (RADIX * i) for i, (_, hi) in enumerate(x))


def canonicalize_steps(x: Vec) -> dict[str, Vec]:
    """Mirror of field.canonicalize up to the conditional subtract; _chk
    inside every iv_* raises on an int32 overflow."""
    biased = [iv_add(xi, (mi, mi)) for xi, mi in zip(x, M_SUB)]
    first = ripple(biased)
    hi = iv_shift_by(first[N - 1], TOP_SHIFT)
    folded = list(first)
    folded[N - 1] = iv_low_bits(first[N - 1], TOP_SHIFT)
    folded[0] = iv_add(first[0], iv_scale(19, hi))
    second = ripple(folded)
    return {"biased": biased, "first": first, "folded": folded,
            "second": second}


def conditional_subtract(l: Vec) -> Vec:
    """The borrow chain of field.canonicalize, both arms of the select
    joined. `v + (borrow << RADIX)` with borrow = (v < 0) is mirrored by
    the sign of v: its negative part lifted by 2^13, its other part as is.
    The subtracted arm is taken only where the LAST borrow is 0, so limb
    19 gives it its non-negative part alone."""
    borrow: Interval = (0, 0)
    out: Vec = []
    for i in range(N):
        v = iv_sub(iv_sub(l[i], (P_INTS[i], P_INTS[i])), borrow)
        parts = []
        if v[0] < 0 and i < N - 1:
            parts.append(_chk((v[0] + (1 << RADIX),
                               min(v[1], -1) + (1 << RADIX))))
        if v[1] >= 0:
            parts.append((max(v[0], 0), v[1]))
        borrow = (int(v[1] < 0), int(v[0] < 0))
        arm = l[i]
        for part in parts:
            arm = iv_join(arm, part)
        out.append(arm)
    return out


def _domain(name: str) -> Vec:
    if name == "fixpoint":
        return compute_fixpoint()
    if name == "add_of_carried":  # a caller that skips add's carry round
        c = compute_fixpoint()
        return [iv_add(a, a) for a in c]
    return {"canonical": CANONICAL, "widest": WIDEST}[name]


DOMAINS = ["canonical", "fixpoint", "add_of_carried", "widest"]


@pytest.mark.parametrize("domain", DOMAINS)
def test_canonicalize_bias_makes_every_limb_nonnegative(domain):
    """Step 1: after x + M_SUB no limb, so no value, is negative: redundant
    limbs CAN spell a negative integer (a wrap round subtracts 32p by limb
    19's carry alone), and a borrow must never run off the top."""
    steps = canonicalize_steps(_domain(domain))
    assert all(lo >= 0 for lo, _ in steps["biased"])


@pytest.mark.parametrize("domain", DOMAINS)
def test_canonicalize_ripples_land_limbs_in_canonical_range(domain):
    """Steps 2 and 4: after either ripple limbs 0..18 lie in [0, MASK];
    after the second limb 19 lies in [0, 2^8]: at most one unit over the
    255 bits, for the conditional subtract to take."""
    steps = canonicalize_steps(_domain(domain))
    for name in ("first", "second"):
        for lo, hi in steps[name][: N - 1]:
            assert 0 <= lo and hi <= MASK, (name, lo, hi)
    lo, hi = steps["second"][N - 1]
    assert 0 <= lo and hi <= 1 << TOP_SHIFT


@pytest.mark.parametrize("domain", DOMAINS)
def test_canonicalize_value_is_below_2p_before_the_subtract(domain):
    """One conditional subtract of p is enough: the largest value the
    limbs can spell after the second ripple is under 2p."""
    steps = canonicalize_steps(_domain(domain))
    assert value_max(steps["second"]) < 2 * F.P


@pytest.mark.parametrize("domain", DOMAINS)
def test_canonicalize_closes_into_canonical_limbs(domain):
    """Closure: whatever arm the select takes, the output limbs are
    canonical ones (so canonicalize maps its output to itself, and the
    sr25519 decode's `canon == limbs` compares like with like), and every
    intermediate of the borrow chain fits int32."""
    out = conditional_subtract(canonicalize_steps(_domain(domain))["second"])
    for lo, hi in out[: N - 1]:
        assert 0 <= lo and hi <= MASK
    # 2^8 itself is excluded by the value, not by the limb: the property
    # test against Python integers (tests/test_canonical_exact.py) has it
    assert 0 <= out[N - 1][0] and out[N - 1][1] <= 1 << TOP_SHIFT


def test_canonicalize_mirror_is_the_function():
    """The mirror above follows field.canonicalize step for step: on random
    limbs of the widest domain, the real function's output lies inside the
    mirror's intervals and equals x mod p by Python integers."""
    import jax.numpy as jnp

    rng = np.random.default_rng(11)
    x = np.stack([rng.integers(lo, hi + 1, size=64) for lo, hi in WIDEST])
    got = np.asarray(F.canonicalize(jnp.asarray(x, dtype=jnp.int32)))
    bounds = conditional_subtract(canonicalize_steps(WIDEST)["second"])
    for i, (lo, hi) in enumerate(bounds):
        assert lo <= got[i].min() and got[i].max() <= hi
    for col in range(x.shape[1]):
        want = sum(int(v) << (RADIX * i) for i, v in enumerate(x[:, col]))
        have = sum(int(v) << (RADIX * i) for i, v in enumerate(got[:, col]))
        assert have == want % F.P


def test_conv_matches_schoolbook_on_randoms():
    """The pre-rolled conv in field._conv is algebraically the schoolbook
    product: cross-check column-exactly against a numpy reference."""
    rng = np.random.default_rng(7)
    c = compute_fixpoint()  # draw within the proved invariant, per limb
    a = np.stack([rng.integers(lo, hi + 1, size=33) for lo, hi in c])
    b = np.stack([rng.integers(lo, hi + 1, size=33) for lo, hi in c])
    import jax.numpy as jnp

    got = np.asarray(
        F._conv(jnp.asarray(a, dtype=jnp.int32), jnp.asarray(b, dtype=jnp.int32))
    )
    want = np.zeros((NCONV, 33), dtype=np.int64)
    for i in range(N):
        for j in range(N):
            want[i + j] += a[i] * b[j]
    np.testing.assert_array_equal(got, want)
