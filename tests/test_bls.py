"""BLS12-381 scheme tests — the CPU oracle (crypto/fallback.py) and the
crypto/bls12381.py key layer.

Vector strategy in this container (no network): expand_message_xmd is
checked against the RFC 9380 reference vectors verbatim; the curve
parameters verify each other through the BLS family's integer identities
(r = x^4 - x^2 + 1, 3p = (x-1)^2 r + 3x) plus generator/subgroup/
bilinearity checks — a transcription error in ANY core constant fails
one of these; and the full sign/verify/aggregate pipeline is pinned by
golden known-answer vectors generated from the oracle, so hash-to-curve,
serialization, or pairing drift can never land silently. The
zero-pubkey and infinity-point rejection cases follow the BLS draft's
required behavior. (The registered G2 SSWU ciphersuite's isogeny
constants are deliberately not reproduced — the suite uses the generic
SvdW map under its own DST; see crypto/fallback.py.)
"""

from __future__ import annotations

import pytest

from cometbft_tpu import crypto
from cometbft_tpu.crypto import bls12381 as bls
from cometbft_tpu.crypto import fallback as o

DST = bls.DST
INF_G1 = bytes([0xC0]) + bytes(47)
INF_G2 = bytes([0xC0]) + bytes(95)


def k(seed: bytes) -> bls.PrivKey:
    return bls.gen_priv_key_from_secret(seed)


# ------------------------------------------------------------- parameters


def test_family_identities_tie_constants_together():
    x = o.BLS_X
    assert o.BLS_R == x**4 - x**2 + 1
    assert 3 * o.BLS_P == (x - 1) ** 2 * o.BLS_R + 3 * x
    assert o.BLS_P % 4 == 3  # the sqrt exponent (p+1)/4 depends on this


def test_generators_on_curve_and_order_r():
    assert o._ec_on_curve(o._FpOps, o.BLS_G1)
    assert o._ec_on_curve(o._Fp2Ops, o.BLS_G2)
    assert o._ec_mul(o._FpOps, o.BLS_R, o._ec_from_affine(o.BLS_G1)) is None
    assert o._ec_mul(o._Fp2Ops, o.BLS_R, o._ec_from_affine(o.BLS_G2)) is None


def test_g2_cofactor_calibration_matches_family_polynomial():
    x = o.BLS_X
    h2_poly = (x**8 - 4 * x**7 + 5 * x**6 - 4 * x**4 + 6 * x**3
               - 4 * x**2 - 4 * x + 13) // 9
    assert o._bls_setup()["h2"] == h2_poly
    assert o._bls_setup()["h1"] == (x - 1) ** 2 // 3


# --------------------------------------------------- expand_message (RFC)


RFC9380_XMD_DST = b"QUUX-V01-CS02-with-expander-SHA256-128"
RFC9380_XMD_VECTORS = [
    (b"", "68a985b87eb6b46952128911f2a4412bbc302a9d759667f87f7a21d803f07235"),
    (b"abc", "d8ccab23b5985ccea865c6c97b6e5b8350e794e603b4b97902f53a8a0d605615"),
    (b"abcdef0123456789",
     "eff31487c770a893cfb36f912fbfcbff40d5661771ca4b2cb4eafe524333f5c1"),
]


def test_expand_message_xmd_rfc9380_vectors():
    for msg, want in RFC9380_XMD_VECTORS:
        got = o.bls_expand_message_xmd(msg, RFC9380_XMD_DST, 0x20)
        assert got.hex() == want


def test_expand_message_xmd_long_output_chains():
    out = o.bls_expand_message_xmd(b"m", DST, 256)
    assert len(out) == 256
    # deterministic and prefix-incompatible with a different length
    assert out == o.bls_expand_message_xmd(b"m", DST, 256)
    assert out[:32] != o.bls_expand_message_xmd(b"m", DST, 32)


def test_hash_to_field_range_and_determinism():
    els = o.bls_hash_to_field_fp2(b"msg", DST, 2)
    assert len(els) == 2
    for e in els:
        assert 0 <= e[0] < o.BLS_P and 0 <= e[1] < o.BLS_P
    assert els == o.bls_hash_to_field_fp2(b"msg", DST, 2)


def test_hash_to_g2_lands_in_subgroup():
    for msg in (b"", b"a", b"vote-bytes"):
        h = o.bls_hash_to_g2(msg, DST)
        assert o._ec_on_curve(o._Fp2Ops, h)
        assert o._ec_mul(o._Fp2Ops, o.BLS_R, o._ec_from_affine(h)) is None


# ---------------------------------------------------------------- pairing


def test_pairing_bilinear_and_nondegenerate():
    g1 = o._ec_from_affine(o.BLS_G1)
    g2 = o._ec_from_affine(o.BLS_G2)
    e = o.bls_pairing(o.BLS_G1, o.BLS_G2)
    assert e != o.F12_ONE
    e2p = o.bls_pairing(
        o._ec_affine(o._FpOps, o._ec_mul(o._FpOps, 2, g1)), o.BLS_G2)
    e2q = o.bls_pairing(
        o.BLS_G1, o._ec_affine(o._Fp2Ops, o._ec_mul(o._Fp2Ops, 2, g2)))
    assert e2p == o.f12_mul(e, e) == e2q


def test_pairing_product_inverse_pair_is_one():
    neg = (o.BLS_G1[0], (-o.BLS_G1[1]) % o.BLS_P)
    assert o.bls_pairing_product_is_one(
        [(o.BLS_G1, o.BLS_G2), (neg, o.BLS_G2)])
    assert not o.bls_pairing_product_is_one([(o.BLS_G1, o.BLS_G2)])


# ---------------------------------------------------------- serialization


def test_serialization_roundtrip_and_sign_bit():
    key = k(b"ser")
    pub = key.pub_key().bytes_()
    assert len(pub) == 48 and pub[0] & 0x80
    aff = o.bls_g1_decompress(pub)
    assert o.bls_g1_compress(aff) == pub
    # the other root decodes under the flipped sign bit
    flipped = bytearray(pub)
    flipped[0] ^= 0x20
    other = o.bls_g1_decompress(bytes(flipped))
    assert other == (aff[0], o.BLS_P - aff[1])
    sig = key.sign(b"m")
    assert o.bls_g2_compress(o.bls_g2_decompress(sig)) == sig


def test_serialization_structural_rejects():
    with pytest.raises(ValueError):
        o.bls_g1_decompress(bytes(48))  # compression flag clear
    over = bytearray(o.BLS_P.to_bytes(48, "big"))  # x = p: out of range
    over[0] |= 0x80
    with pytest.raises(ValueError):
        o.bls_g1_decompress(bytes(over))
    with pytest.raises(ValueError):
        o.bls_g1_decompress(bytes([0xE0]) + bytes(47))  # inf + sign set
    with pytest.raises(ValueError):
        o.bls_g2_decompress(bytes(96))
    # x not on curve (x^3 + 4 a non-residue): search the first such x —
    # roughly half of all x qualify, so this terminates immediately
    x = next(v for v in range(2, 40)
             if pow((v**3 + 4) % o.BLS_P, (o.BLS_P - 1) // 2, o.BLS_P)
             == o.BLS_P - 1)
    enc = bytearray(x.to_bytes(48, "big"))
    enc[0] |= 0x80
    with pytest.raises(ValueError):
        o.bls_g1_decompress(bytes(enc))


def test_infinity_encodings_decode_but_are_rejected_by_validation():
    assert o.bls_g1_decompress(INF_G1) is None
    assert o.bls_g2_decompress(INF_G2) is None
    assert not o.bls_pubkey_validate(INF_G1)       # zero pubkey rejected
    assert o.bls_signature_validate(INF_G2) is None  # infinity sig rejected


# ------------------------------------------------------------ sign/verify


def test_sign_verify_roundtrip_and_rejections():
    key = k(b"sv")
    pub = key.pub_key()
    sig = key.sign(b"height-5-round-0")
    assert pub.verify_signature(b"height-5-round-0", sig)
    assert not pub.verify_signature(b"height-5-round-1", sig)
    assert not k(b"other").pub_key().verify_signature(
        b"height-5-round-0", sig)
    assert not pub.verify_signature(b"height-5-round-0", sig[:64])
    assert not pub.verify_signature(b"height-5-round-0", INF_G2)


def test_golden_vectors_pin_the_pipeline():
    """Known-answer regression vectors: any drift in hash-to-curve,
    serialization, or the pairing chain breaks these."""
    k1, k2 = k(b"golden-1"), k(b"golden-2")
    assert k1.pub_key().bytes_().hex() == (
        "909edd39025e6c8572bbf691efc5d31689be064e0c283b18527211f9afe7dcd6"
        "54d511c7361d22407ccd505e38b6eede")
    assert k2.pub_key().bytes_().hex() == (
        "ad8c0ddb08bb45a22504b25f0c8cd4c663ba53a33b83722370b45ed23eb3a168"
        "e4d9f7f26921aa5d56b78c3ebb7f5e47")
    assert k1.sign(b"bls golden vector message 1").hex() == (
        "967e3839676b9699aab1b2165f63c212a6eb6ed92fbc3e85862897b2ebf85591"
        "80d06a18c6e34390859e130e613245e8047f9a8642662d59726e6681ff1b127d"
        "399bc364db4c5fd608b0631734f8761e1e64a046b8204cbb54693e85f5d1789e")
    agg = bls.aggregate_signatures(
        [k1.sign(b"shared"), k2.sign(b"shared")])
    assert agg.hex() == (
        "83704a060593708169feb6dc89a093120338245121a4cdf710452e62b50bec52"
        "6751697e986386eee680fafa7cacbfa40aeee1e31e6125da53535e5b8d71b421"
        "c2c9e0c6c43372f6ddea9a278ed30583425e3935c77aff7ed2a876b1b622165b")


# -------------------------------------------------------------- aggregate


def test_aggregate_verify_distinct_and_repeated_messages():
    keys = [k(b"agg-%d" % i) for i in range(4)]
    pubs = [key.pub_key().bytes_() for key in keys]
    msgs = [b"m1", b"m1", b"m2", b"m3"]  # PoP: repeats aggregate
    sigs = [key.sign(m) for key, m in zip(keys, msgs)]
    agg = bls.aggregate_signatures(sigs)
    assert bls.aggregate_verify(pubs, msgs, agg)
    assert not bls.aggregate_verify(pubs, [b"m1"] * 4, agg)
    # wrong signer bitmap: a subset's aggregate must not verify as the
    # full set (and vice versa)
    sub = bls.aggregate_signatures(sigs[:3])
    assert not bls.aggregate_verify(pubs, msgs, sub)
    assert not bls.aggregate_verify(pubs[:3], msgs[:3], agg)
    assert bls.aggregate_verify(pubs[:3], msgs[:3], sub)


def test_aggregate_rejects_infinity_and_garbage_inputs():
    keys = [k(b"ai-%d" % i) for i in range(2)]
    sigs = [key.sign(b"m") for key in keys]
    with pytest.raises(ValueError):
        bls.aggregate_signatures([])
    with pytest.raises(ValueError):
        bls.aggregate_signatures([sigs[0], INF_G2])
    with pytest.raises(ValueError):
        bls.aggregate_signatures([sigs[0], b"\x00" * 96])
    agg = bls.aggregate_signatures(sigs)
    pubs = [key.pub_key().bytes_() for key in keys]
    assert not bls.aggregate_verify([INF_G1, pubs[1]], [b"m", b"m"], agg)
    assert not bls.aggregate_verify(pubs, [b"m", b"m"], INF_G2)


def test_aggregate_rejects_cancelled_pubkey_group():
    """pk and -pk signing the same message sum to infinity — the group
    contributes nothing and must be rejected, not trivially accepted."""
    key = k(b"cancel")
    pk_aff = o.bls_g1_decompress(key.pub_key().bytes_())
    neg_pk = o.bls_g1_compress((pk_aff[0], o.BLS_P - pk_aff[1]))
    # craft an "aggregate" for the cancelled pair: any subgroup point
    sig = key.sign(b"m")
    assert not o.bls_aggregate_verify(
        [key.pub_key().bytes_(), neg_pk], [b"m", b"m"], sig, DST)


# --------------------------------------------------------- batch verifier


def test_cpu_batch_verifier_mask_and_pinpoint():
    keys = [k(b"bv-%d" % i) for i in range(3)]
    bv = bls.CPUBatchVerifier()
    sigs = [key.sign(b"msg-%d" % i) for i, key in enumerate(keys)]
    for i, key in enumerate(keys):
        bv.add(key.pub_key(), b"msg-%d" % i, sigs[i])
    ok, mask = bv.verify()
    assert ok and mask == [True, True, True]
    bv2 = bls.CPUBatchVerifier()
    bv2.add(keys[0].pub_key(), b"msg-0", sigs[0])
    bv2.add(keys[1].pub_key(), b"msg-X", sigs[1])  # wrong message
    bv2.add(keys[2].pub_key(), b"msg-2", sigs[2])
    ok, mask = bv2.verify()
    assert not ok and mask == [True, False, True]


def test_batch_verifier_rejects_foreign_keys_and_bad_lengths():
    from cometbft_tpu.crypto import ed25519

    bv = bls.CPUBatchVerifier()
    with pytest.raises(crypto.ErrInvalidKey):
        bv.add(ed25519.gen_priv_key().pub_key(), b"m", bytes(96))
    with pytest.raises(crypto.ErrInvalidSignature):
        bv.add(k(b"l").pub_key(), b"m", bytes(64))


# ------------------------------------------------- registration / config


def test_pub_key_proto_roundtrip():
    from cometbft_tpu.types.validator import (pub_key_from_proto,
                                              pub_key_to_proto)

    pub = k(b"proto").pub_key()
    back = pub_key_from_proto(pub_key_to_proto(pub))
    assert back.type_() == "bls12381" and back.bytes_() == pub.bytes_()


def test_scheduled_verifier_accepts_96_byte_bls_sigs():
    from cometbft_tpu.crypto import batch as crypto_batch

    v = crypto_batch.ScheduledBatchVerifier()
    key = k(b"sz")
    v.add(key.pub_key(), b"m", key.sign(b"m"))
    assert v.count() == 1
    with pytest.raises(crypto.ErrInvalidSignature):
        v.add(key.pub_key(), b"m", bytes(64))


def test_bls_disabled_is_loud_not_silent():
    """Satellite: a BLS key with crypto.bls_enabled off must raise a
    helpful error at every batch seam — never fall back silently."""
    from cometbft_tpu.crypto import batch as crypto_batch

    key = k(b"loud").pub_key()
    bls.set_enabled(False)
    try:
        with pytest.raises(crypto.ErrInvalidKey, match="bls_enabled"):
            crypto_batch.supports_batch_verifier(key)
        sv = crypto_batch.ScheduledBatchVerifier()
        with pytest.raises(crypto.ErrInvalidKey, match="bls_enabled"):
            sv.add(key, b"m", bytes(96))
    finally:
        bls.set_enabled(True)
    assert crypto_batch.supports_batch_verifier(key)


def test_config_knob_round_trips_and_applies():
    from cometbft_tpu.config.config import CryptoConfig

    cfg = CryptoConfig()
    assert cfg.bls_enabled is True
    cfg.bls_enabled = False
    cfg.validate_basic()
    try:
        from cometbft_tpu.crypto import batch as crypto_batch

        crypto_batch.configure(cfg)
        assert not bls.enabled()
    finally:
        bls.set_enabled(True)


def test_privkey_structural_checks():
    with pytest.raises(crypto.ErrInvalidKey):
        bls.PrivKey(b"short")
    with pytest.raises(crypto.ErrInvalidKey):
        bls.PubKey(b"short")
    key = k(b"addr")
    assert len(key.pub_key().address()) == 20
