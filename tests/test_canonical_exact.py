"""field.canonicalize is exact: the unique representative for EVERY carried
input, so the device condemns no valid signature.

Until PR 28 canonicalize carried in parallel rounds (seven between a `sub`
and the conditional subtract), each moving a carry one limb. A multiple of p
is written [8192 - 19k, 8191 x 18, 256k - 1]: eighteen full limbs in a row.
With a borrow waiting at limb j and its carry at limb j + 1 (the pair
(-1, 8192), which `sub` leaves behind) every round moves the pair up one
limb, and from the low limbs it never reached the top: the limbs did not
come out zero, `curve.is_identity` read false for the identity, and a valid
signature read invalid about once in 650,000 (PERF.md, PR 25 and PR 28).
The five signatures the benchmark met that way are the first cases here.
"""

from __future__ import annotations

import json
import os
import random

import numpy as np
import pytest

from cometbft_tpu.ops import field as F

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
P = F.P
N = F.NLIMBS
RADIX = F.RADIX
BASE = 1 << RADIX
FULL = BASE - 1

with open(os.path.join(ROOT, "benchmarks", "tests",
                       "condemned_valid_signatures.json")) as _fh:
    CONDEMNED = json.load(_fh)["cases"]


# ------------------------------------------------ the five witnesses, whole


@pytest.mark.parametrize(
    "case", CONDEMNED, ids=[f"seed{c['seed']}-lane{c['lane']}"
                            for c in CONDEMNED])
def test_device_path_accepts_a_once_condemned_signature(case, monkeypatch):
    """Through ed25519_kernel.verify_batch (here the XLA ladder on the
    CPU): the DEVICE's mask is true, and the host-oracle re-check, which
    used to overturn the device's verdict on each of these, is never
    entered."""
    from cometbft_tpu.libs import metrics
    from cometbft_tpu.ops import ed25519_kernel as K

    entered = []
    real = K.recheck_failed_lanes

    def spy(mask, *args, **kw):
        entered.append(len(mask))
        return real(mask, *args, **kw)

    monkeypatch.setattr(K, "recheck_failed_lanes", spy)
    cm = metrics.crypto_metrics()
    before = (cm.mask_oracle_disagreement.total(),
              cm.fallback_verifies.total())
    ok, mask = K.verify_batch([bytes.fromhex(case["pub"])],
                              [bytes.fromhex(case["msg"])],
                              [bytes.fromhex(case["sig"])])
    assert ok and mask == [True]
    assert entered == []
    assert (cm.mask_oracle_disagreement.total(),
            cm.fallback_verifies.total()) == before


# ------------------------------------- planted representations, by families


def _limbs(value: int) -> list[int]:
    """Canonical limbs 0..18 of a non-negative value, all the rest in limb
    19 (loose: it may pass 13 bits, as M_SUB's does)."""
    out = [(value >> (RADIX * i)) & FULL for i in range(N - 1)]
    return out + [value >> (RADIX * (N - 1))]


def _value(limbs) -> int:
    return sum(int(v) << (RADIX * i) for i, v in enumerate(limbs))


def _waiting_pairs(limbs: list[int]) -> list[list[int]]:
    """The same value with a borrow waiting at limb j and its carry at limb
    j + 1, for every j: a parallel round moves the pair up one limb where
    limb j + 1 is full, and it has to cross every full limb above it."""
    out = []
    for j in range(N - 1):
        rep = list(limbs)
        rep[j] -= BASE
        rep[j + 1] += 1
        out.append(rep)
        rep = list(limbs)  # and the mirrored pair, which ripples over zeros
        rep[j] += BASE
        rep[j + 1] -= 1
        out.append(rep)
    return out


def multiples_of_p() -> list[list[int]]:
    """k * p for k in 0..33 (33p is the subtraction bias: what `sub` of two
    equal values spells before its carry), each as it stands and with a
    waiting pair at every limb."""
    reps = []
    for k in range(34):
        base = _limbs(k * P)
        reps.append(base)
        reps += _waiting_pairs(base)
    return reps


def full_runs() -> list[list[int]]:
    """Runs of full (8191) limbs of every length 1..19 at every position,
    with a carry waiting below the run (the limb under it holds 2^13 more;
    at position 0 the run's own first limb does), the other limbs seeded
    random canonical ones."""
    rng = random.Random(28)
    reps = []
    for length in range(1, N):
        for start in range(0, N - length):
            rep = [rng.randrange(BASE) for _ in range(N - 1)]
            rep.append(rng.randrange(1 << 8))
            for i in range(start, start + length):
                rep[i] = FULL
            rep[max(start - 1, 0)] += BASE
            reps.append(rep)
    # length 19: limbs 0..18 all full, the carry waiting in limb 0
    reps.append([FULL + BASE] + [FULL] * (N - 2) + [255])
    return reps


def edges() -> list[list[int]]:
    """Values just under and over p, 2p and 2^255, as they stand and with
    waiting pairs."""
    reps = []
    for centre in (P, 2 * P, 1 << 255, 0, 33 * P):
        for d in range(-20, 21):
            if centre + d >= 0:
                base = _limbs(centre + d)
                reps.append(base)
                reps += _waiting_pairs(base)[::7]
    return reps


def random_carried() -> list[list[int]]:
    """Seeded random limbs over the whole carried range, signs included
    (redundant limbs can spell a negative integer), and up to CARRIED_MAX
    in every limb, not only the two that reach it."""
    rng = random.Random(2028)
    reps = [[rng.randint(-F.FOLD, F.CARRIED_MAX) for _ in range(N)]
            for _ in range(4096)]
    reps += [[rng.choice((-F.FOLD, -1, 0, FULL, BASE, F.CARRIED_MAX))
              for _ in range(N)] for _ in range(2048)]
    return reps


FAMILIES = {"multiples_of_p": multiples_of_p, "full_runs": full_runs,
            "edges": edges, "random_carried": random_carried}


def _batch(reps: list[list[int]]):
    import jax.numpy as jnp

    return jnp.asarray(np.array(reps, dtype=np.int64).T.astype(np.int32))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_canonicalize_equals_python_mod_p(family):
    """canonicalize(x) is x mod p by Python integers, in canonical limbs;
    is_zero and parity follow it."""
    reps = FAMILIES[family]()
    x = _batch(reps)
    got = np.asarray(F.canonicalize(x)).T
    zero = np.asarray(F.is_zero(x))
    odd = np.asarray(F.parity(x))
    assert got.min() >= 0 and got[:, : N - 1].max() <= FULL
    assert got[:, N - 1].max() <= 255
    for rep, limbs, z, o in zip(reps, got, zero, odd):
        want = _value(rep) % P
        assert _value(limbs) == want, rep
        assert bool(z) == (want == 0), rep
        assert int(o) == want & 1, rep


def test_every_multiple_of_p_reads_zero():
    """The fault's own shape: every planted multiple of p is zero. (The
    parent, f4285b3, read false for 50 of these 1,326.)"""
    reps = multiples_of_p()
    assert len(reps) == 34 * (1 + 2 * (N - 1))
    assert np.asarray(F.is_zero(_batch(reps))).all()


def test_sub_of_equal_values_in_other_limbs_reads_zero():
    """eq(a, b) where a and b spell one value differently: b canonical, a
    the same with a waiting pair; through `sub`, as curve.is_identity and
    the decompressions compare."""
    rng = random.Random(5)
    a_reps, b_reps = [], []
    for _ in range(64):
        base = _limbs(rng.randrange(P))
        for rep in _waiting_pairs(base):
            a_reps.append(rep)
            b_reps.append(base)
    assert np.asarray(F.eq(_batch(a_reps), _batch(b_reps))).all()
    assert np.asarray(F.eq(_batch(b_reps), _batch(a_reps))).all()


def test_canonicalize_is_named_in_the_lowered_program():
    """One form, under one scope: the XLA ladder's device trace shows the
    ripples as `canonical_ripple` ops (inside the Pallas kernel they are
    part of the one custom call, and cost what the kernel's time says)."""
    import jax

    text = jax.jit(F.is_zero).lower(_batch(multiples_of_p()[:8])).as_text(
        debug_info=True)
    assert "canonical_ripple" in text
    assert not hasattr(F, "weak_carry")


# -------------------------------------------- the callers' own comparisons


def _identity_reps() -> tuple[list[list[int]], list[list[int]]]:
    """(Y, Z) limb pairs of projective identities, Y == Z mod p, chosen so
    that sub(Y, Z) = carry(Y + 33p - Z) is a multiple of p with a waiting
    pair under the long run of full limbs: Z canonical, Y = Z plus a zero
    written as (+2^13 at limb j, -1 at limb j + 1), and the two swapped."""
    rng = random.Random(17)
    ys, zs = [], []
    for j in range(N - 1):
        for _ in range(4):
            z = _limbs(rng.randrange(1, P))
            y = list(z)
            y[j] += BASE
            y[j + 1] -= 1
            ys += [y, z]
            zs += [z, y]
    return ys, zs


def test_is_identity_holds_for_an_identity_in_any_limbs():
    """curve.is_identity: X a multiple of p with a waiting pair, Y and Z
    one value in different limbs."""
    from cometbft_tpu.ops import curve

    ys, zs = _identity_reps()
    zeros = random.Random(3).sample(multiples_of_p(), len(ys))
    x, y, z = _batch(zeros), _batch(ys), _batch(zs)
    point = curve.Point(x, y, z, x)
    assert np.asarray(curve.is_identity(point)).all()
    # and it still tells a point that is not the identity
    one = np.zeros((N, len(ys)), dtype=np.int32)
    one[0] = 1
    import jax.numpy as jnp

    off = curve.Point(x, F.add(y, jnp.asarray(one)), z, x)
    assert not np.asarray(curve.is_identity(off)).any()


def test_ristretto_canonical_check_on_the_planted_limbs():
    """sr25519's decode precondition (s < p in canonical limbs, even, bit
    255 clear) compares canonicalize's output with the limbs as given: true
    exactly for canonical limbs of an even value under p, whatever else the
    limbs spell."""
    from cometbft_tpu.ops import sr25519_kernel as SRK

    rng = random.Random(9)
    reps = multiples_of_p() + full_runs() + edges()
    reps += [_limbs(rng.randrange(P)) for _ in range(256)]
    reps += [_limbs(v) for v in (0, 2, P - 1, P - 3, P, P + 1)]
    hi = np.zeros(len(reps), dtype=np.uint32)
    import jax.numpy as jnp

    got = np.asarray(SRK._is_canonical_even(_batch(reps), jnp.asarray(hi)))
    for rep, g in zip(reps, got):
        canonical = (all(0 <= v <= FULL for v in rep[: N - 1])
                     and 0 <= rep[N - 1] and _value(rep) < P)
        assert bool(g) == (canonical and rep[0] % 2 == 0), rep
    assert got.any() and not got.all()
