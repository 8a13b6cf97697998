"""Device-side unpacking of wire-format bytes into limbs / window digits.

Signatures, scalars and point encodings travel host->device as packed
uint32 words — (8, B) per 32-byte item, batch on the minor (lane) axis —
and are expanded to radix-2^13 limbs or 4-bit window digits ON DEVICE with
shift/mask ops. Rationale: bytes over the host link are paid per batch;
shipping a 10k-signature commit as bit-arrays was 25 MB, as packed words
it is ~1 MB. The reference
has no analog (its verifier consumes Go byte slices in-core,
crypto/ed25519/ed25519.go:208-241); this is the TPU-native wire layout.

Host-side packing counterpart: limbs.bytes_to_words.
"""

from __future__ import annotations

import jax.numpy as jnp

from cometbft_tpu.ops import limbs as L

WORDS = 8  # 32 bytes = 8 little-endian uint32 words


def words_to_y_limbs(w: jnp.ndarray) -> jnp.ndarray:
    """(8, B) uint32 point encodings -> (20, B) int32 y limbs (low 255
    bits, canonical 13-bit limbs; limb 19 is 8 bits). The sign bit (bit
    255) is excluded — see words_sign."""
    out = []
    for i in range(L.NLIMBS):
        bit = L.RADIX * i
        wi, off = bit // 32, bit % 32
        v = w[wi] >> off if off else w[wi]
        if off > 32 - L.RADIX and wi + 1 < WORDS:
            v = v | (w[wi + 1] << (32 - off))
        mask = 0xFF if i == L.NLIMBS - 1 else L.MASK  # limb 19: bits 247..254
        out.append((v & mask).astype(jnp.int32))
    return jnp.stack(out, axis=0)


def words_sign(w: jnp.ndarray) -> jnp.ndarray:
    """(8, B) uint32 -> (B,) int32 sign bit (bit 255)."""
    return (w[WORDS - 1] >> 31).astype(jnp.int32)


# Scalars are < L < 2^253: digit 50 covers bits 250..254, of which bits
# 253/254 are always zero, so its raw value is <= 7 and even with a ripple
# carry (+1) stays < 16 — the signed recoding never carries out of digit 50.
# Hence 51 digits, not ceil(256/5) + 1 = 53: each digit trimmed deletes a
# full ladder window (5 doublings + 2 adds = ~51 field muls per signature).
NDIGITS5 = 51


def words_to_digits5_signed(w: jnp.ndarray) -> jnp.ndarray:
    """(8, B) uint32 scalar words -> (51, B) int32 SIGNED 5-bit window
    digits in [-16, 15], little-endian: scalar = sum d_j * 32^j. Standard
    signed recoding (d >= 16 -> d - 32, carry 1 up) shortens the ladder to
    51 windows of 5 doublings and, because -d selects as a lane-local
    negation, keeps the table at 17 entries. The carry ripple is a 51-step
    scan over (B,) rows — noise next to one field mul."""
    raw = []
    for j in range(NDIGITS5):
        bit = 5 * j
        wi, off = bit // 32, bit % 32
        if wi >= WORDS:
            v = jnp.zeros_like(w[0])
        else:
            v = w[wi] >> off if off else w[wi]
            if off > 27 and wi + 1 < WORDS:
                v = v | (w[wi + 1] << (32 - off))
        raw.append((v & 31).astype(jnp.int32))
    digits = jnp.stack(raw, axis=0)  # (51, B) in [0, 31]

    import jax

    # The carry ripple c_{j+1} = (v_j + c_j >= 16) is a generate/propagate
    # chain (generate: v_j >= 16; propagate the incoming carry: v_j == 15),
    # exactly an adder carry-lookahead — solved with a log-depth
    # associative scan (6 levels for 51 digits) instead of a 51-step
    # sequential lax.scan.
    g = (digits >= 16)
    p = (digits == 15)

    def op(a, b):
        ga, pa = a
        gb, pb = b
        return ga & pb | gb, pa & pb

    gacc, _ = jax.lax.associative_scan(op, (g, p), axis=0)
    carry_in = jnp.concatenate(
        [jnp.zeros_like(gacc[:1]), gacc[:-1]], axis=0).astype(jnp.int32)
    d = digits + carry_in
    signed = d - 32 * (d >= 16).astype(jnp.int32)
    # the carry out of the top digit is provably zero for scalars < 2^253
    # (see the NDIGITS5 comment: digit 50's post-carry value is <= 8 < 16);
    # callers enforce s, k < L < 2^253 host-side (ed25519_kernel.stage_batch
    # rejects s >= L, k is reduced mod L).
    return signed
