"""Vectorized BLS12-381 (ops/bls12381/, ops/bls_kernel.py) vs the exact
CPU oracle.

Tier-1-safe parts: the packed-limb field, the towers, and the point
layer compile in seconds and are checked bit-for-bit against pure-int
oracle arithmetic. The Miller-loop/final-exponentiation pipeline and the
kernel end-to-end paths carry the `pairing` marker (conftest adds `slow`:
the cold XLA compile of the pairing pieces takes minutes) — run them
with -m pairing. The mixed-scheme scheduler test stays tier-1-safe by
riding the CPU rung (the per-lane MASK ORDER contract is
backend-independent)."""

from __future__ import annotations

import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from cometbft_tpu.crypto import bls12381 as bls  # noqa: E402
from cometbft_tpu.crypto import fallback as o  # noqa: E402
from cometbft_tpu.ops.bls12381 import fp  # noqa: E402
from cometbft_tpu.ops.bls12381 import fp2  # noqa: E402
from cometbft_tpu.ops.bls12381 import points as pts  # noqa: E402
from cometbft_tpu.ops.bls12381 import tower  # noqa: E402

P = o.BLS_P
_RINV = pow(fp.R_INT, -1, P)


def _load_fp(vals):
    return jnp.asarray(fp.ints_to_limbs([v * fp.R_MOD_P % P for v in vals]))


def _read_fp(a):
    return [v * _RINV % P for v in
            fp.limbs_to_ints(np.asarray(fp.canon(a)))]


def _rand_ints(n, seed):
    rng = random.Random(seed)
    return [rng.randrange(P) for _ in range(n)]


# ------------------------------------------------------------------- field


def test_fp_matches_int_arithmetic():
    xs = _rand_ints(6, 1) + [0, 1, P - 1]
    ys = _rand_ints(6, 2) + [P - 1, P - 1, P - 1]
    X, Y = _load_fp(xs), _load_fp(ys)
    assert _read_fp(fp.add(X, Y)) == [(a + b) % P for a, b in zip(xs, ys)]
    assert _read_fp(fp.sub(X, Y)) == [(a - b) % P for a, b in zip(xs, ys)]
    assert _read_fp(fp.mul(X, Y)) == [a * b % P for a, b in zip(xs, ys)]
    assert _read_fp(fp.inv(X)) == [pow(a, P - 2, P) if a else 0 for a in xs]


def test_fp_carried_limbs_stay_int32_safe_under_stress():
    xs, ys = _rand_ints(5, 3), _rand_ints(5, 4)
    a, b = _load_fp(xs), _load_fp(ys)
    av, bv = list(xs), list(ys)
    for _ in range(25):
        a, av = fp.mul(a, b), [x * y % P for x, y in zip(av, bv)]
        b, bv = (fp.sub(fp.add(b, a), fp.sq(a)),
                 [((y + x) - x * x) % P for x, y in zip(av, bv)])
        assert int(np.abs(np.asarray(b)).max()) < (1 << 13)
    assert _read_fp(a) == av and _read_fp(b) == bv


def test_fp_bytes_packing_roundtrip():
    xs = _rand_ints(7, 5) + [0, P - 1]
    be = np.stack([np.frombuffer(v.to_bytes(48, "big"), np.uint8)
                   for v in xs])
    limbs = fp.bytes_be_to_limbs(be)
    assert fp.limbs_to_ints(limbs) == xs
    assert (fp.limbs_to_bytes_be(limbs) == be).all()


def test_fp_sqrt_and_sgn0():
    xs = _rand_ints(6, 6)
    X = _load_fp(xs)
    ok, r = fp.sqrt(fp.sq(X))
    assert bool(np.asarray(ok).all())
    got = _read_fp(r)
    assert all(g * g % P == x * x % P for g, x in zip(got, xs))
    assert np.asarray(fp.sgn0(X)).tolist() == [x & 1 for x in xs]


def _rand_f2(n, seed):
    rng = random.Random(seed)
    return [(rng.randrange(P), rng.randrange(P)) for _ in range(n)]


def test_fp2_matches_oracle():
    xs, ys = _rand_f2(6, 7), _rand_f2(6, 8)
    X, Y = fp2.from_oracle_ints(xs), fp2.from_oracle_ints(ys)
    assert fp2.to_oracle_ints(fp2.mul(X, Y)) == [
        o.f2_mul(a, b) for a, b in zip(xs, ys)]
    assert fp2.to_oracle_ints(fp2.sq(X)) == [o.f2_sq(a) for a in xs]
    assert fp2.to_oracle_ints(fp2.inv(X)) == [o.f2_inv(a) for a in xs]
    assert fp2.to_oracle_ints(fp2.mul_xi(X)) == [o.f2_mul_xi(a) for a in xs]
    isq = np.asarray(fp2.is_square(X))
    for i, a in enumerate(xs):
        assert bool(isq[i]) == o.f2_legendre_is_square(a)
    sg = np.asarray(fp2.sgn0(X))
    for i, a in enumerate(xs):
        assert int(sg[i]) == o.f2_sgn0(a)


@pytest.mark.pairing
def test_fp2_sqrt_matches_oracle_semantics():
    sqs = [o.f2_sq(c) for c in _rand_f2(4, 9)]
    ok, r = fp2.sqrt(fp2.from_oracle_ints(sqs))
    assert bool(np.asarray(ok).all())
    for got, want_sq in zip(fp2.to_oracle_ints(r), sqs):
        assert o.f2_sq(got) == want_sq
    non = [c for c in _rand_f2(16, 10)
           if not o.f2_legendre_is_square(c)][:4]
    ok, _ = fp2.sqrt(fp2.from_oracle_ints(non))
    assert not np.asarray(ok).any()


def _load_f12(els):
    comps = list(zip(*[(e[0][0], e[0][1], e[0][2],
                        e[1][0], e[1][1], e[1][2]) for e in els]))
    f2s = [fp2.from_oracle_ints(list(c)) for c in comps]
    return tower.Fp12(tower.Fp6(f2s[0], f2s[1], f2s[2]),
                      tower.Fp6(f2s[3], f2s[4], f2s[5]))


@pytest.mark.pairing
def test_fp12_tower_matches_oracle():
    rng = random.Random(11)

    def rnd12():
        def r2():
            return (rng.randrange(P), rng.randrange(P))

        return ((r2(), r2(), r2()), (r2(), r2(), r2()))

    xs = [rnd12() for _ in range(3)]
    ys = [rnd12() for _ in range(3)]
    X, Y = _load_f12(xs), _load_f12(ys)
    assert tower.to_oracle(tower.f12_mul(X, Y)) == [
        o.f12_mul(a, b) for a, b in zip(xs, ys)]
    assert tower.to_oracle(tower.f12_sq(X)) == [o.f12_sq(a) for a in xs]
    assert tower.to_oracle(tower.f12_inv(X)) == [o.f12_inv(a) for a in xs]
    for n in (1, 2):
        assert tower.to_oracle(tower.f12_frob(X, n)) == [
            o.f12_frob(a, n) for a in xs]
    e = -o.BLS_X
    assert tower.to_oracle(tower.f12_exp_const(X, e)) == [
        o.f12_pow(a, e) for a in xs]


# ------------------------------------------------------------------ points


def _oracle_g1_points(n, seed):
    rng = random.Random(seed)
    g1 = o._ec_from_affine(o.BLS_G1)
    return [o._ec_affine(o._FpOps,
                         o._ec_mul(o._FpOps, rng.randrange(1, o.BLS_R), g1))
            for _ in range(n)]


def _load_g1(affs):
    return pts.from_affine(
        pts.G1Field,
        _load_fp([a[0] for a in affs]), _load_fp([a[1] for a in affs]))


def _read_g1(p):
    x, y, isid = pts.to_affine(pts.G1Field, p)
    xs = fp.limbs_to_ints(np.asarray(fp.from_mont(x)))
    ys = fp.limbs_to_ints(np.asarray(fp.from_mont(y)))
    ii = np.asarray(isid)
    return [None if ii[j] else (xs[j], ys[j]) for j in range(len(xs))]


def test_point_add_dbl_complete_cases_match_oracle():
    a1 = _oracle_g1_points(5, 12)
    P1 = _load_g1(a1)
    want_dbl = [o._ec_affine(o._FpOps, o._ec_dbl(
        o._FpOps, o._ec_from_affine(a))) for a in a1]
    assert _read_g1(pts.dbl(pts.G1Field, P1)) == want_dbl
    assert _read_g1(pts.add(pts.G1Field, P1, P1)) == want_dbl  # P+P = 2P
    rolled = a1[1:] + a1[:1]
    want = [o._ec_affine(o._FpOps, o._ec_add(
        o._FpOps, o._ec_from_affine(a), o._ec_from_affine(b)))
        for a, b in zip(a1, rolled)]
    assert _read_g1(pts.add(pts.G1Field, P1, _load_g1(rolled))) == want
    neg = pts.neg_point(pts.G1Field, P1)
    assert np.asarray(pts.is_identity(
        pts.G1Field, pts.add(pts.G1Field, P1, neg))).all()
    ident = pts.identity_like(pts.G1Field, P1.y)
    assert _read_g1(pts.add(pts.G1Field, P1, ident)) == a1
    assert np.asarray(pts.on_curve(pts.G1Field, P1)).all()


@pytest.mark.pairing
def test_scalar_mul_and_sum_tree_match_oracle():
    a1 = _oracle_g1_points(5, 13)
    P1 = _load_g1(a1)
    k = 0xDEADBEEFCAFE
    want = [o._ec_affine(o._FpOps, o._ec_mul(
        o._FpOps, k, o._ec_from_affine(a))) for a in a1]
    assert _read_g1(pts.mul_const(pts.G1Field, P1, k)) == want
    acc = None
    for a in a1:
        acc = o._ec_add(o._FpOps, acc, o._ec_from_affine(a))
    assert _read_g1(pts.sum_tree(pts.G1Field, P1, 5)) == [
        o._ec_affine(o._FpOps, acc)]


@pytest.mark.pairing
def test_subgroup_check_accepts_real_rejects_low_order():
    a1 = _oracle_g1_points(3, 14)
    assert np.asarray(pts.in_subgroup(pts.G1Field, _load_g1(a1))).all()
    # (0, 2) has order 3 on y^2 = x^3 + 4 — not in the r-subgroup
    low = _load_g1([(0, 2)])
    assert np.asarray(pts.on_curve(pts.G1Field, low)).all()
    assert not np.asarray(pts.in_subgroup(pts.G1Field, low)).any()


def test_decompression_matches_oracle_serialization():
    a1 = _oracle_g1_points(4, 15)
    enc = np.stack([np.frombuffer(o.bls_g1_compress(a), np.uint8)
                    for a in a1])
    sign = (enc[:, 0] & 0x20) != 0
    body = enc.copy()
    body[:, 0] &= 0x1F
    ok, p = pts.g1_decompress(
        jnp.asarray(fp.bytes_be_to_limbs(body)), jnp.asarray(sign))
    assert np.asarray(ok).all()
    assert _read_g1(p) == a1


# ------------------------------------------------- svdw map / hash-to-curve


@pytest.mark.pairing
def test_svdw_map_matches_oracle():
    from cometbft_tpu.ops.bls12381 import htc

    us = _rand_f2(4, 16) + [(0, 0), (1, 0)]
    got = htc.svdw_map(fp2.from_oracle_ints(us))
    x, y, isid = pts.to_affine(pts.G2Field, got)
    assert not np.asarray(isid).any()
    xs = fp2.to_oracle_ints(x)
    ys = fp2.to_oracle_ints(y)
    consts = o._bls_setup()["svdw"]
    for i, u in enumerate(us):
        assert (xs[i], ys[i]) == o._svdw_map_fp2(u, consts)


@pytest.mark.pairing
def test_hash_to_g2_device_matches_oracle():
    from cometbft_tpu.ops.bls12381 import htc

    msgs = [b"", b"abc", b"vote-bytes-xyz"]
    h = htc.hash_to_g2_device(msgs, bls.DST)
    x, y, isid = pts.to_affine(pts.G2Field, h)
    assert not np.asarray(isid).any()
    xs, ys = fp2.to_oracle_ints(x), fp2.to_oracle_ints(y)
    for i, m in enumerate(msgs):
        assert (xs[i], ys[i]) == o.bls_hash_to_g2(m, bls.DST)


# ------------------------------------------------------------ pairing/kernel


@pytest.fixture(scope="module")
def _device_env():
    """Raise the dispatch watchdog over the cold pairing compile and pin
    the tpu backend resolution (the XLA-on-host rung) for kernel paths;
    restore afterwards."""
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.ops import dispatch as D

    from cometbft_tpu.ops import compile_cache

    compile_cache.arm()
    D.configure(watchdog_timeout=900.0)
    prev = crypto_batch.get_backend()
    crypto_batch.set_backend("tpu")
    yield
    crypto_batch.set_backend(prev)
    D.configure(watchdog_timeout=120.0)


@pytest.mark.pairing
def test_pairing_device_bit_identical_to_oracle(_device_env):
    from cometbft_tpu.ops.bls12381 import pairing

    rng = random.Random(17)
    g1 = o._ec_from_affine(o.BLS_G1)
    g2 = o._ec_from_affine(o.BLS_G2)
    a1 = [o._ec_affine(o._FpOps, o._ec_mul(
        o._FpOps, rng.randrange(1, o.BLS_R), g1)) for _ in range(3)]
    a2 = [o._ec_affine(o._Fp2Ops, o._ec_mul(
        o._Fp2Ops, rng.randrange(1, o.BLS_R), g2)) for _ in range(3)]
    px = _load_fp([a[0] for a in a1])
    py = _load_fp([a[1] for a in a1])
    qx = fp2.from_oracle_ints([a[0] for a in a2])
    qy = fp2.from_oracle_ints([a[1] for a in a2])
    f = pairing.miller_loop(px, py, qx, qy)
    for final in (pairing.final_exp, pairing.final_exp_composed):
        got = tower.to_oracle(final(f))
        assert got == [o.bls_pairing(p, q) for p, q in zip(a1, a2)]


@pytest.mark.pairing
def test_kernel_batch_verify_matches_oracle_on_all_rungs(_device_env):
    """Acceptance: wrong sig / garbage / infinity rejected identically on
    the device path, the breaker-open host path, and the raw oracle."""
    from cometbft_tpu.ops import bls_kernel as K
    from cometbft_tpu.ops import dispatch as D

    keys = [bls.gen_priv_key_from_secret(b"rung-%d" % i) for i in range(5)]
    msgs = [b"msg-%d" % i for i in range(5)]
    pubs = [k.pub_key().bytes_() for k in keys]
    sigs = [k.sign(m) for k, m in zip(keys, msgs)]
    sigs[1] = keys[1].sign(b"wrong")          # valid sig, wrong message
    sigs[2] = b"\x00" * 96                     # structural garbage
    sigs[3] = bytes([0xC0]) + bytes(95)        # infinity point
    want = [o.bls_verify(p, m, s, bls.DST)
            for p, m, s in zip(pubs, msgs, sigs)]
    assert want == [True, False, False, False, True]
    _, device_mask = K.verify_batch(pubs, msgs, sigs)
    assert device_mask == want
    # breaker-open rung: the kernel must produce the identical mask from
    # the host oracle without touching the device
    sup = D.supervisor("device")
    sup.breaker.record_failure("permanent")  # opens immediately
    try:
        assert not D.device_allowed()
        _, host_mask = K.verify_batch(pubs, msgs, sigs)
    finally:
        sup.breaker.record_success()
    assert host_mask == want


@pytest.mark.pairing
def test_kernel_aggregate_matches_oracle_on_randomized_commits(_device_env):
    """Acceptance: aggregate commit verify is bit-consistent with the
    oracle on randomized commits with bad lanes — wrong sig, wrong
    signer bitmap, infinity pubkey — on the device and host rungs."""
    from cometbft_tpu.ops import bls_kernel as K
    from cometbft_tpu.ops import dispatch as D

    keys = [bls.gen_priv_key_from_secret(b"agg-rung-%d" % i)
            for i in range(4)]
    pubs = [k.pub_key().bytes_() for k in keys]
    msgs = [b"h5-vote-%d" % i for i in range(4)]
    sigs = [k.sign(m) for k, m in zip(keys, msgs)]

    def oracle_agg(ps, ms, ss):
        try:
            agg = o.bls_aggregate([bytes(s) for s in ss])
        except ValueError:
            return False
        return o.bls_aggregate_verify(ps, ms, agg, bls.DST)

    cases = [
        (pubs, msgs, sigs),                                   # clean
        (pubs, msgs, [sigs[0], keys[1].sign(b"forged")] + sigs[2:]),
        (pubs[:3], msgs[:3], sigs[:3]),                       # sub-commit
        (pubs, msgs, sigs[:3] + [sigs[0]]),                   # wrong bitmap
        ([bytes([0xC0]) + bytes(47)] + pubs[1:], msgs, sigs),  # inf pk
        (pubs, [b"same"] * 4, [k.sign(b"same") for k in keys]),  # PoP
    ]
    for ps, ms, ss in cases:
        want = oracle_agg(ps, ms, ss)
        assert K.aggregate_verify(ps, ms, ss) == want, (ps, ms)
    sup = D.supervisor("device")
    sup.breaker.record_failure("permanent")  # opens immediately
    try:
        for ps, ms, ss in cases:
            assert K.aggregate_verify(ps, ms, ss) == oracle_agg(ps, ms, ss)
    finally:
        sup.breaker.record_success()


@pytest.mark.pairing
def test_scheduler_mixed_three_scheme_batch_device(_device_env):
    _run_mixed_scheduler_case()


def test_scheduler_mixed_three_scheme_batch_cpu_rung():
    """Satellite: scheduler end-to-end mixed ed25519+sr25519+BLS batch
    with per-lane mask order asserted — tier-1-safe on the CPU rung (the
    mask-order contract is backend-independent)."""
    _run_mixed_scheduler_case()


def _run_mixed_scheduler_case():
    from cometbft_tpu import sched
    from cometbft_tpu.crypto import ed25519, sr25519

    scheduler = sched.VerifyScheduler(max_lanes=64)
    ed_k = ed25519.gen_priv_key()
    sr_k = sr25519.gen_priv_key_from_secret(b"mixed-sr")
    bl_k = bls.gen_priv_key_from_secret(b"mixed-bls")
    rows = [
        (ed_k.pub_key(), b"ed-m", ed_k.sign(b"ed-m")),
        (bl_k.pub_key(), b"bls-m", bl_k.sign(b"bls-m")),
        (sr_k.pub_key(), b"sr-m", sr_k.sign(b"sr-m")),
        (bl_k.pub_key(), b"bls-bad", bl_k.sign(b"bls-m")),  # wrong msg
        (ed_k.pub_key(), b"ed-bad", ed_k.sign(b"ed-m")),    # wrong msg
        (sr_k.pub_key(), b"sr-m2", sr_k.sign(b"sr-m2")),
    ]
    mask = scheduler.verify_now(rows)
    assert mask.tolist() == [True, True, True, False, False, True]
    scheduler.stop()


# ------------------------------------------------- mesh shard integrity seam


def _mk_bls_rows(n, seed=b"mesh"):
    privs = [bls.gen_priv_key_from_secret(seed + b"-%d" % i)
             for i in range(n)]
    pubs = [p.pub_key().bytes_() for p in privs]
    msgs = [b"mesh-msg-%d" % i for i in range(n)]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]
    return pubs, msgs, sigs


def _payload(mask_b, chk_ok=True, echo_ok=True):
    mask_b = np.asarray(mask_b, dtype=bool)
    echo = ~mask_b if echo_ok else mask_b.copy()
    return np.concatenate([mask_b, echo, np.asarray([chk_ok])])


def test_mesh_shard_validates_transfer_integrity(monkeypatch):
    """mesh_shard_verify must enforce the same transfer-integrity
    contract as the single-chip resolver (ed25519_kernel.decode_payload):
    checksum bit + mask/echo complement validated, one fresh-transfer
    retry, then the shard FAILS (DeviceOpFailed -> mesh redispatch) — a
    flipped bit in the link never becomes an accepted signature.
    Device pipeline stubbed: the contract is pure host logic."""
    from cometbft_tpu.ops import bls_kernel as K
    from cometbft_tpu.ops import dispatch as D

    pubs, msgs, sigs = _mk_bls_rows(3)
    b = K.bucket_size(3)
    dev = jax.devices()[0]
    good = np.array([True, False, True] + [True] * (b - 3))

    # happy path: verdict sliced to the live lanes
    monkeypatch.setattr(
        K, "_verify_device", lambda *_a: (None, _payload(good)))
    mask, eligible = K.mesh_shard_verify(dev, pubs, msgs, sigs)
    assert mask.tolist() == [True, False, True]
    assert eligible.all()

    # poisoned first fetch, clean retry: the retry's verdict wins
    calls = iter([_payload(~good, chk_ok=False), _payload(good)])
    monkeypatch.setattr(
        K, "_verify_device", lambda *_a: (None, next(calls)))
    mask, _ = K.mesh_shard_verify(dev, pubs, msgs, sigs)
    assert mask.tolist() == [True, False, True]

    # double corruption (checksum, then echo): the shard fails loudly
    calls = iter([_payload(good, chk_ok=False),
                  _payload(good, echo_ok=False)])
    monkeypatch.setattr(
        K, "_verify_device", lambda *_a: (None, next(calls)))
    with pytest.raises(D.DeviceOpFailed):
        K.mesh_shard_verify(dev, pubs, msgs, sigs)


def test_stage_batch_bls_skips_hash_planes_for_aggregate():
    """msgs=None staging (the aggregate path) must zero the u-planes and
    leave the pk/sig limb planes byte-identical to full staging — the
    aggregate path hashes only the DISTINCT messages, so per-lane
    hash-to-field would be O(n) dead work."""
    from cometbft_tpu.ops import bls_kernel as K

    pubs, msgs, sigs = _mk_bls_rows(5, seed=b"agg")
    b = K.bucket_size(5)
    ok_full, block_full, flags_full = K.stage_batch_bls(pubs, msgs, sigs, b)
    ok_agg, block_agg, flags_agg = K.stage_batch_bls(pubs, None, sigs, b)
    assert ok_full.tolist() == ok_agg.tolist()
    assert (flags_full == flags_agg).all()
    assert (block_full[:3] == block_agg[:3]).all()
    assert not block_agg[3:].any()
    assert block_full[3:].any()  # full staging really does hash
