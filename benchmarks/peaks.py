"""The table of peaks and the count of the work in one verification.

Peaks are the published ones, keyed by the `device_kind` JAX reports; a
kind that is not here is an error, never a default. The work of one
signature verification is counted from a stated textbook algorithm, NOT
read from the kernel, so that the count stays what it is whatever
implements it.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM at 819 GB/s, per chip
    "TPU v5 lite": {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peak for device_kind {device_kind!r}: "
                       "add it to benchmarks/peaks.py with its source")
    return PEAKS[device_kind]


# One multiplication in GF(2^255 - 19), schoolbook over 32 byte limbs:
# 32 x 32 products and as many additions.
FIELD_MUL_INT_OPS = 2 * 32 * 32

# Extended twisted Edwards coordinates (Hisil et al. 2008), a = -1:
# doubling 4M + 4S, unified addition 8M + 1 by 2d. A square root or an
# inverse square root by the exponent (p - 5) / 8 or (p - 2): ~252
# squarings and ~12 multiplications.
_DOUBLE = 8
_ADD = 9
_POWER = 264
_BITS = 253
# [s]B - [k]A by Straus/Shamir's simultaneous ladder over 253-bit scalars:
# one doubling a bit and an addition for three bit pairs in four
_DOUBLE_SCALAR = _BITS * _DOUBLE + (3 * _BITS // 4) * _ADD

FIELD_MULS_PER_VERIFY = {
    # decompress A and R (one root each), the ladder, subtract R, clear the
    # cofactor 8, compare with the identity
    "ed25519": 2 * _POWER + _DOUBLE_SCALAR + _ADD + 3 * _DOUBLE,
    # ristretto-decode A and R (one inverse root each), the ladder,
    # subtract R, clear the cofactor 4
    "sr25519": 2 * _POWER + _DOUBLE_SCALAR + _ADD + 2 * _DOUBLE,
}


def verify_int_ops(sigs_by_scheme: dict[str, float]) -> float:
    """Integer operations the textbook algorithm needs for these
    verifications."""
    return sum(n * FIELD_MULS_PER_VERIFY[scheme] * FIELD_MUL_INT_OPS
               for scheme, n in sigs_by_scheme.items())


def roofline_seconds(device_kind: str, sigs_by_scheme: dict[str, float],
                     wire_bytes: float) -> tuple[float, str]:
    """The least time one chip could take, and which peak bounds it."""
    p = peak(device_kind)
    by_ops = verify_int_ops(sigs_by_scheme) / p["int8_ops_per_s"]
    by_bytes = wire_bytes / p["hbm_bytes_per_s"]
    return (by_ops, "operations") if by_ops >= by_bytes else (by_bytes, "bytes")
