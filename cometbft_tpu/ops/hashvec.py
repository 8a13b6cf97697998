"""Lane-vectorized batch hashing for the host staging fast path.

BENCH_r05 put the 10k-row mixed mega-commit at ~2 ms of device compute
under ~48 ms of host staging — ~5.4 us/row of per-row hashing (SHA-512
challenges for ed25519, Merlin/STROBE transcripts for sr25519). This
module turns that per-row work into batch-axis work:

  sha512_many / sha512_rows   N digests per call, inputs grouped by padded
                              block count (commit sign-bytes are near-
                              uniform length, so one group dominates)
  keccak_f1600_many           N Keccak states advanced under ONE
                              permutation call — the engine behind the
                              batch STROBE transcript in
                              crypto/sr25519_math.py
  reduce512_mod_l             vectorized Barrett reduction of N 512-bit
                              digests mod the ed25519 group order L,
                              emitting the (N, 8) uint32 word layout the
                              device kernels consume (no per-row
                              int.from_bytes/%/to_bytes round trip)

Rung ladder (per core, measured on the dev box, selected per call):

  native   8-lane SIMD C (native/hashvec.c, GCC vector extensions,
           ISA picked from /proc/cpuinfo): 92 ns/row/permutation,
           166 ns/row for a 2-block SHA-512 — the production rung.
  numpy    the batch-axis numpy uint64 implementation in this file —
           bit-for-bit equal, always available. For Keccak it is ~40x
           the pure-Python per-row path (the no-toolchain rung); for
           SHA-512 OpenSSL's serial hashlib outruns it on small hosts,
           so auto mode prefers serial there.
  serial   per-row hashlib / Strobe128 — ragged stragglers and tiny
           groups, and the reference the equality tests pin against.

CBFT_HASHVEC=auto|native|numpy|serial forces a rung (tests use this to
pin the numpy reference); auto is measurement-driven as above. Every
rung is bit-for-bit identical — tests/test_hashvec.py fuzzes all three
against hashlib.sha512 and the serial Keccak over randomized lengths and
batch sizes.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import threading

import numpy as np

# below this many rows a group takes the serial rung: per-row native hash
# calls beat numpy/ctypes call overhead for a handful of stragglers
VEC_MIN_ROWS = 8

_M64 = np.uint64(0xFFFFFFFFFFFFFFFF)

# ---------------------------------------------------------------- native rung


def _isa_cflags() -> tuple:
    """Compiler-flag ladder for native/hashvec.c, widest ISA first. The
    ISA is read from /proc/cpuinfo (not -march=native: virtualized hosts
    hide the model and gcc then picks a narrow baseline)."""
    flags = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    flags = line
                    break
    except OSError:
        pass
    ladder = []
    if " avx512f" in flags and " avx512dq" in flags:
        ladder.append(("-O3", "-mavx512f", "-mavx512dq"))
    if " avx2" in flags:
        ladder.append(("-O3", "-mavx2"))
    ladder.append(("-O3",))
    return tuple(ladder)


def _load_native():
    from cometbft_tpu import native

    lib = native.load("hashvec", cflags_ladder=_isa_cflags())
    if lib is None:
        return None
    try:
        lib.keccak_many.argtypes = [ctypes.c_void_p, ctypes.c_long]
        lib.sha512_many.argtypes = [
            ctypes.c_void_p, ctypes.c_long, ctypes.c_long, ctypes.c_void_p]
        lib.reduce512_mod_l_many.argtypes = [
            ctypes.c_void_p, ctypes.c_long, ctypes.c_void_p]
    except AttributeError:
        return None
    return lib


_NATIVE = _load_native()

# ------------------------------------------------------------- rung selection

_VALID_MODES = ("auto", "native", "numpy", "serial")


def _mode() -> str:
    m = os.environ.get("CBFT_HASHVEC", "auto")
    return m if m in _VALID_MODES else "auto"


# path-taken counters (the tier-1 smoke asserts the vectorized path is
# actually taken for a uniform-length commit; microbench reads them too)
_stats_lock = threading.Lock()
_stats: dict[str, int] = {}


def _count(core: str, rung: str, rows: int) -> None:
    with _stats_lock:
        key = f"{core}_{rung}_rows"
        _stats[key] = _stats.get(key, 0) + rows


def stats() -> dict[str, int]:
    with _stats_lock:
        return dict(_stats)


def reset_stats() -> None:
    with _stats_lock:
        _stats.clear()


def native_available() -> bool:
    return _NATIVE is not None


def active_rung() -> str:
    """The rung the next batch hash will ride given the forced mode and
    what loaded: "native" (SIMD C core), "numpy" (batch-axis), or
    "serial" (hashlib/strobe stragglers). Stamped onto staging trace
    spans (libs/trace.py) so a trace shows WHICH hash ladder produced a
    given stage_us."""
    m = _mode()
    if m == "auto":
        return "native" if _NATIVE is not None else "numpy"
    if m == "native" and _NATIVE is None:
        return "numpy"
    return m


# ---------------------------------------------------------------- keccak rung
#
# State layout matches crypto/sr25519_math.keccak_f1600: lane i = x + 5*y,
# little-endian uint64 lanes, as an (N, 25) uint64 array (one row per
# independent sponge).

_KECCAK_RC = np.array([
    0x0000000000000001, 0x0000000000008082, 0x800000000000808A,
    0x8000000080008000, 0x000000000000808B, 0x0000000080000001,
    0x8000000080008081, 0x8000000000008009, 0x000000000000008A,
    0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
    0x000000008000808B, 0x800000000000008B, 0x8000000000008089,
    0x8000000000008003, 0x8000000000008002, 0x8000000000000080,
    0x000000000000800A, 0x800000008000000A, 0x8000000080008081,
    0x8000000000008080, 0x0000000080000001, 0x8000000080008008,
], dtype=np.uint64)

_ROTC = [[0, 36, 3, 41, 18], [1, 44, 10, 45, 2], [62, 6, 43, 15, 61],
         [28, 55, 25, 21, 56], [27, 20, 39, 8, 14]]

# rho+pi fused as one gather + one vector rotate: out[j] = rotl(in[SRC[j]])
_PI_SRC = np.zeros(25, dtype=np.intp)
_RHO = np.zeros((25, 1), dtype=np.uint64)
for _x in range(5):
    for _y in range(5):
        _PI_SRC[_y + 5 * ((2 * _x + 3 * _y) % 5)] = _x + 5 * _y
        _RHO[_y + 5 * ((2 * _x + 3 * _y) % 5), 0] = _ROTC[_x][_y]
# (64 - r) & 63 keeps the r == 0 lane shift-safe: t<<0 | t>>0 == t
_RHO_INV = (np.uint64(64) - _RHO) & np.uint64(63)
_CHI1 = np.array([(i % 5 + 1) % 5 + 5 * (i // 5) for i in range(25)],
                 dtype=np.intp)
_CHI2 = np.array([(i % 5 + 2) % 5 + 5 * (i // 5) for i in range(25)],
                 dtype=np.intp)
_D_IDX = np.array([i % 5 for i in range(25)], dtype=np.intp)
_C_L = np.array([(x - 1) % 5 for x in range(5)], dtype=np.intp)
_C_R = np.array([(x + 1) % 5 for x in range(5)], dtype=np.intp)
_U1 = np.uint64(1)
_U63 = np.uint64(63)


def _keccak_batch_numpy(states: np.ndarray) -> None:
    """In-place Keccak-f[1600] over (N, 25) uint64 states — the batch-axis
    numpy rung (all N sponges advance under one permutation)."""
    a = states.T.copy()  # (25, N): lane-major for whole-lane vector ops
    for r in range(24):
        c = np.bitwise_xor.reduce(a.reshape(5, 5, -1), axis=0)  # theta: (5,N)
        cr = c[_C_R]
        d = c[_C_L] ^ ((cr << _U1) | (cr >> _U63))
        a ^= d[_D_IDX]
        t = a[_PI_SRC]  # rho + pi
        t = (t << _RHO) | (t >> _RHO_INV)
        a = t ^ (~t[_CHI1] & t[_CHI2])  # chi
        a[0] ^= _KECCAK_RC[r]  # iota
    states[:] = a.T


def keccak_f1600_many(states: np.ndarray) -> None:
    """Advance N independent Keccak-f[1600] states (one (N, 25) uint64
    array, modified in place) under one permutation call — native SIMD
    when available, else the numpy batch rung. Bit-for-bit equal to the
    serial crypto/sr25519_math.keccak_f1600 on every state."""
    assert states.dtype == np.uint64 and states.ndim == 2 \
        and states.shape[1] == 25
    n = states.shape[0]
    if n == 0:
        return
    mode = _mode()
    if _NATIVE is not None and mode in ("auto", "native"):
        buf = np.ascontiguousarray(states)
        _NATIVE.keccak_many(buf.ctypes.data, n)
        if buf is not states:
            states[:] = buf
        _count("keccak", "native", n)
        return
    _keccak_batch_numpy(states)
    _count("keccak", "numpy", n)


# --------------------------------------------------------------- SHA-512 rung

_SHA_K = np.array([
    0x428a2f98d728ae22, 0x7137449123ef65cd, 0xb5c0fbcfec4d3b2f,
    0xe9b5dba58189dbbc, 0x3956c25bf348b538, 0x59f111f1b605d019,
    0x923f82a4af194f9b, 0xab1c5ed5da6d8118, 0xd807aa98a3030242,
    0x12835b0145706fbe, 0x243185be4ee4b28c, 0x550c7dc3d5ffb4e2,
    0x72be5d74f27b896f, 0x80deb1fe3b1696b1, 0x9bdc06a725c71235,
    0xc19bf174cf692694, 0xe49b69c19ef14ad2, 0xefbe4786384f25e3,
    0x0fc19dc68b8cd5b5, 0x240ca1cc77ac9c65, 0x2de92c6f592b0275,
    0x4a7484aa6ea6e483, 0x5cb0a9dcbd41fbd4, 0x76f988da831153b5,
    0x983e5152ee66dfab, 0xa831c66d2db43210, 0xb00327c898fb213f,
    0xbf597fc7beef0ee4, 0xc6e00bf33da88fc2, 0xd5a79147930aa725,
    0x06ca6351e003826f, 0x142929670a0e6e70, 0x27b70a8546d22ffc,
    0x2e1b21385c26c926, 0x4d2c6dfc5ac42aed, 0x53380d139d95b3df,
    0x650a73548baf63de, 0x766a0abb3c77b2a8, 0x81c2c92e47edaee6,
    0x92722c851482353b, 0xa2bfe8a14cf10364, 0xa81a664bbc423001,
    0xc24b8b70d0f89791, 0xc76c51a30654be30, 0xd192e819d6ef5218,
    0xd69906245565a910, 0xf40e35855771202a, 0x106aa07032bbd1b8,
    0x19a4c116b8d2d0c8, 0x1e376c085141ab53, 0x2748774cdf8eeb99,
    0x34b0bcb5e19b48a8, 0x391c0cb3c5c95a63, 0x4ed8aa4ae3418acb,
    0x5b9cca4f7763e373, 0x682e6ff3d6b2b8a3, 0x748f82ee5defb2fc,
    0x78a5636f43172f60, 0x84c87814a1f0ab72, 0x8cc702081a6439ec,
    0x90befffa23631e28, 0xa4506cebde82bde9, 0xbef9a3f7b2c67915,
    0xc67178f2e372532b, 0xca273eceea26619c, 0xd186b8c721c0c207,
    0xeada7dd6cde0eb1e, 0xf57d4f7fee6ed178, 0x06f067aa72176fba,
    0x0a637dc5a2c898a6, 0x113f9804bef90dae, 0x1b710b35131c471b,
    0x28db77f523047d84, 0x32caab7b40c72493, 0x3c9ebe0a15c9bebc,
    0x431d67c49c100d4c, 0x4cc5d4becb3e42b6, 0x597f299cfc657e2a,
    0x5fcb6fab3ad6faec, 0x6c44198c4a475817], dtype=np.uint64)

_SHA_H0 = np.array([
    0x6a09e667f3bcc908, 0xbb67ae8584caa73b, 0x3c6ef372fe94f82b,
    0xa54ff53a5f1d36f1, 0x510e527fade682d1, 0x9b05688c2b3e6c1f,
    0x1f83d9abfb41bd6b, 0x5be0cd19137e2179], dtype=np.uint64)


def _rotr(x: np.ndarray, n: int) -> np.ndarray:
    n = np.uint64(n)
    return (x >> n) | (x << (np.uint64(64) - n))


def _sha512_blocks_numpy(w_in: np.ndarray) -> np.ndarray:
    """(N, nb, 16) uint64 big-endian message words -> (N, 8) uint64 final
    state — the batch-axis numpy compression (FIPS 180-4, all N messages
    through each round together)."""
    n, nb, _ = w_in.shape
    h = [np.full(n, _SHA_H0[i], dtype=np.uint64) for i in range(8)]
    for bi in range(nb):
        w = [w_in[:, bi, t].copy() for t in range(16)]
        a, b, c, d, e, f, g, hh = h
        for t in range(80):
            if t >= 16:
                w15 = w[(t - 15) % 16]
                w2 = w[(t - 2) % 16]
                s0 = _rotr(w15, 1) ^ _rotr(w15, 8) ^ (w15 >> np.uint64(7))
                s1 = _rotr(w2, 19) ^ _rotr(w2, 61) ^ (w2 >> np.uint64(6))
                w[t % 16] = w[t % 16] + s0 + w[(t - 7) % 16] + s1
            s1e = _rotr(e, 14) ^ _rotr(e, 18) ^ _rotr(e, 41)
            ch = g ^ (e & (f ^ g))
            t1 = hh + s1e + ch + _SHA_K[t] + w[t % 16]
            s0a = _rotr(a, 28) ^ _rotr(a, 34) ^ _rotr(a, 39)
            mj = (a & (b | c)) | (b & c)
            t2 = s0a + mj
            hh = g; g = f; f = e; e = d + t1  # noqa: E702 - round rotation
            d = c; c = b; b = a; a = t1 + t2  # noqa: E702
        h = [h[0] + a, h[1] + b, h[2] + c, h[3] + d,
             h[4] + e, h[5] + f, h[6] + g, h[7] + hh]
    return np.stack(h, axis=1)


def _sha512_pad(rows: np.ndarray) -> tuple[np.ndarray, int]:
    """(N, L) uint8 same-length messages -> ((N, nb*128) padded buffer,
    nb). FIPS 180-4 padding vectorized across the batch."""
    n, ln = rows.shape
    nb = (ln + 17 + 127) // 128
    buf = np.zeros((n, nb * 128), dtype=np.uint8)
    buf[:, :ln] = rows
    buf[:, ln] = 0x80
    buf[:, -16:] = np.frombuffer((ln * 8).to_bytes(16, "big"), dtype=np.uint8)
    return buf, nb


def _batch_sha512_active() -> bool:
    """Is a batch compression rung (native SIMD or forced numpy) in play?
    In auto mode without the native library, serial OpenSSL is the fastest
    correct rung (the un-fused numpy compression loses to a native serial
    core on memory-traffic amplification — measured on the dev box), so
    batch grouping is skipped entirely."""
    mode = _mode()
    if mode == "native":
        return _NATIVE is not None
    if mode == "numpy":
        return True
    if mode == "serial":
        return False
    return _NATIVE is not None


def _sha512_compress(buf: np.ndarray, nb: int) -> np.ndarray:
    """Padded (N, nb*128) buffer -> (N, 64) uint8 digests via a batch
    rung: native SIMD when available (and not overridden), else the numpy
    batch-axis compression. Callers gate on _batch_sha512_active()."""
    n = buf.shape[0]
    if _NATIVE is not None and _mode() != "numpy":
        buf = np.ascontiguousarray(buf)
        out = np.empty((n, 64), dtype=np.uint8)
        _NATIVE.sha512_many(buf.ctypes.data, n, nb, out.ctypes.data)
        _count("sha512", "native", n)
        return out
    w = buf.reshape(n, nb, 16, 8).view(">u8")[..., 0].astype(np.uint64)
    h = _sha512_blocks_numpy(w)
    _count("sha512", "numpy", n)
    return np.ascontiguousarray(h).astype(">u8").view(np.uint8).reshape(n, 64)


def _sha512_serial(datas, out: np.ndarray, idxs) -> None:
    for i in idxs:
        out[i] = np.frombuffer(
            hashlib.sha512(datas[i]).digest(), dtype=np.uint8)
    _count("sha512", "serial", len(idxs))


def assemble_prefixed_rows(msgs, mlen: int) -> np.ndarray:
    """Reassemble uniform-length messages on the batch axis into an
    (N, mlen) uint8 matrix — the staging-side consumer of the
    shared-prefix wire protocol (libs/prefixrows.py). Runs of
    PrefixedMsg rows sharing the SAME prefix object write the prefix
    ONCE as a broadcast column block and join only their short
    suffixes; plain bytes rows join as before. For a vote flush this
    cuts the host copy from ~122 B/row to ~17 B/row of suffix plus one
    ~105-byte prefix per commit."""
    from cometbft_tpu.libs.prefixrows import MsgBlock, PrefixedMsg

    if isinstance(msgs, MsgBlock):
        # rows that are columns already: a front a class, no row walked
        return msgs.matrix(mlen)
    n = len(msgs)
    out = np.empty((n, mlen), dtype=np.uint8)
    i = 0
    while i < n:
        m = msgs[i]
        if isinstance(m, PrefixedMsg):
            p = m.prefix
            j = i
            while (j < n and isinstance(msgs[j], PrefixedMsg)
                   and msgs[j].prefix is p):
                j += 1
            plen = len(p)
            out[i:j, :plen] = np.frombuffer(p, dtype=np.uint8)
            sfx = b"".join(msgs[k].suffix for k in range(i, j))
            out[i:j, plen:] = np.frombuffer(
                sfx, dtype=np.uint8).reshape(j - i, mlen - plen)
        else:
            j = i
            while j < n and not isinstance(msgs[j], PrefixedMsg):
                j += 1
            blob = b"".join(msgs[i:j])
            out[i:j] = np.frombuffer(
                blob, dtype=np.uint8).reshape(j - i, mlen)
        i = j
    return out


def sha512_rows(rows: np.ndarray) -> np.ndarray:
    """(N, L) uint8 same-length messages -> (N, 64) uint8 digests,
    bit-for-bit hashlib.sha512. The uniform-length fast entry used by the
    staging paths (vote sign-bytes within a commit share one length)."""
    n = rows.shape[0]
    if n == 0:
        return np.zeros((0, 64), dtype=np.uint8)
    if not _batch_sha512_active() or n < VEC_MIN_ROWS:
        out = np.empty((n, 64), dtype=np.uint8)
        blob = np.ascontiguousarray(rows).tobytes()
        ln = rows.shape[1]
        for i in range(n):
            out[i] = np.frombuffer(
                hashlib.sha512(blob[i * ln:(i + 1) * ln]).digest(),
                dtype=np.uint8)
        _count("sha512", "serial", n)
        return out
    buf, nb = _sha512_pad(rows)
    return _sha512_compress(buf, nb)


def sha512_many(datas: list[bytes]) -> np.ndarray:
    """N messages of any lengths -> (N, 64) uint8 digests. Rows are
    grouped by padded block count and each group compressed in one
    batch call; groups below VEC_MIN_ROWS (ragged stragglers) take the
    serial hashlib rung."""
    n = len(datas)
    out = np.empty((n, 64), dtype=np.uint8)
    if n == 0:
        return out
    if not _batch_sha512_active():
        _sha512_serial(datas, out, range(n))
        return out
    lens = set(map(len, datas))
    if len(lens) == 1:  # the dominant commit shape: skip per-row grouping
        ln = lens.pop()
        rows = np.frombuffer(b"".join(datas), dtype=np.uint8)
        return sha512_rows(rows.reshape(n, ln) if ln else
                           np.zeros((n, 0), dtype=np.uint8))
    by_nb: dict[int, dict[int, list[int]]] = {}
    for i, d in enumerate(datas):
        nb = (len(d) + 17 + 127) // 128
        by_nb.setdefault(nb, {}).setdefault(len(d), []).append(i)
    for nb, by_len in by_nb.items():
        group_rows = sum(len(v) for v in by_len.values())
        if group_rows < VEC_MIN_ROWS:
            for idxs in by_len.values():
                _sha512_serial(datas, out, idxs)
            continue
        bufs, order = [], []
        for ln, idxs in by_len.items():
            flat = np.frombuffer(
                b"".join(datas[i] for i in idxs), dtype=np.uint8)
            buf, _ = _sha512_pad(flat.reshape(len(idxs), ln))
            bufs.append(buf)
            order.extend(idxs)
        digests = _sha512_compress(
            bufs[0] if len(bufs) == 1 else np.concatenate(bufs), nb)
        out[np.asarray(order, dtype=np.intp)] = digests
    return out


# --------------------------------------------------- Barrett reduction mod L
#
# k = digest mod L for N 512-bit little-endian digests at once, emitting
# the packed (N, 8) uint32 little-endian word layout the device kernels
# consume. Base-2^16 limbs in uint64 (products < 2^32, 17-term
# accumulations < 2^37 — no overflow), HAC Algorithm 14.42 with k = 16
# limbs: q3 = floor(floor(x / b^15) * mu / b^17), r = x - q3*L mod b^17,
# then at most two conditional subtractions of L.

from cometbft_tpu.crypto.ed25519_math import L as L_ED25519  # noqa: E402

_BARRETT_MU = (1 << 512) // L_ED25519  # 261 bits -> 17 base-2^16 limbs


def _to_limbs16(x: int, n: int) -> np.ndarray:
    return np.array([(x >> (16 * i)) & 0xFFFF for i in range(n)],
                    dtype=np.uint64)


_MU17 = _to_limbs16(_BARRETT_MU, 17)
_L17 = _to_limbs16(L_ED25519, 17)
_U16MASK = np.uint64(0xFFFF)
_U16 = np.uint64(16)
_U63SIGN = np.uint64(63)


def _carry16(acc: np.ndarray) -> np.ndarray:
    """Propagate base-2^16 carries along the limb axis of a limb-major
    (limbs, N) accumulator (values < 2^48 per limb on entry; canonical
    < 2^16 limbs on exit; overflow off the top limb dropped — callers
    size the array so it cannot occur or want mod-b^n semantics)."""
    c = np.zeros(acc.shape[1], dtype=np.uint64)
    for j in range(acc.shape[0]):
        t = acc[j] + c
        acc[j] = t & _U16MASK
        c = t >> _U16
    return acc


def _reduce512_mod_l_numpy(digests: np.ndarray) -> np.ndarray:
    """The batch-axis numpy Barrett rung (limb-major (17, N) layout so
    every per-limb op runs on a contiguous row)."""
    n = digests.shape[0]
    x = np.ascontiguousarray(digests).view("<u2").astype(np.uint64).T  # (32,N)
    q1 = x[15:]  # floor(x / b^15): 17 limbs
    q2 = np.zeros((34, n), dtype=np.uint64)
    for i in range(17):
        q2[i:i + 17] += q1 * _MU17[i]
    _carry16(q2)
    q3 = q2[17:]  # floor(q2 / b^17): 17 limbs
    r2 = np.zeros((17, n), dtype=np.uint64)  # q3*L mod b^17
    for i in range(17):
        if _L17[i]:
            r2[i:] += q3[:17 - i] * _L17[i]
    _carry16(r2)
    # r = x - r2 mod b^17 (limb-wise borrow chain, top borrow dropped);
    # the uint64 sign bit flags a wrapped (negative) limb difference
    r = np.zeros((17, n), dtype=np.uint64)
    borrow = np.zeros(n, dtype=np.uint64)
    for j in range(17):
        t = x[j] - r2[j] - borrow
        r[j] = t & _U16MASK
        borrow = t >> _U63SIGN
    # Barrett guarantees r < 3L: at most two conditional subtractions
    for _ in range(2):
        t = np.zeros_like(r)
        borrow = np.zeros(n, dtype=np.uint64)
        for j in range(17):
            d = r[j] - _L17[j] - borrow
            t[j] = d & _U16MASK
            borrow = d >> _U63SIGN
        ge = borrow == 0  # no final borrow: r >= L, take the difference
        r[:, ge] = t[:, ge]
    return np.ascontiguousarray(
        r[:16].T.astype(np.uint16)).view("<u4").reshape(n, 8)


def reduce512_mod_l(digests: np.ndarray) -> np.ndarray:
    """(N, 64) uint8 little-endian 512-bit values -> (N, 8) uint32
    little-endian words of (value mod L), bit-for-bit equal to
    int.from_bytes(d, "little") % L. Barrett reduction: native __int128
    rung when available, else the vectorized numpy rung."""
    n = digests.shape[0]
    if n == 0:
        return np.zeros((0, 8), dtype=np.uint32)
    if _NATIVE is not None and _mode() in ("auto", "native"):
        buf = np.ascontiguousarray(digests)
        out = np.empty((n, 8), dtype=np.uint32)
        _NATIVE.reduce512_mod_l_many(buf.ctypes.data, n, out.ctypes.data)
        return out
    return _reduce512_mod_l_numpy(digests)


def sha512_mod_l_words(datas: list[bytes]) -> np.ndarray:
    """SHA-512 digests reduced mod L as packed device words: the whole
    ed25519 challenge pipeline (hash -> wide reduction -> wire words) in
    three batch calls."""
    return reduce512_mod_l(sha512_many(datas))


# ------------------------------------------------------------- SHA-256 rung
#
# The BLS hash-to-curve pipeline (ops/bls12381/htc.py expand_message_xmd)
# hashes with SHA-256. Today the only rung is serial hashlib — SHA-256's
# host cost is a rounding error next to the pairing math it feeds, and
# each expand_message round is already batched ACROSS messages by the
# caller (9 sha256_many calls per batch instead of 9*N hashlib calls).
# When profiling ever shows this on a flush's critical path, the
# batch-axis rung follows _sha512_blocks_numpy with 32-bit words and
# K-constants — the structure above is the template.


def sha256_many(datas: list[bytes]) -> np.ndarray:
    """N messages -> (N, 32) uint8 digests, bit-for-bit hashlib.sha256;
    counted on the shared rung-stats surface like the sha512 cores."""
    n = len(datas)
    out = np.empty((n, 32), dtype=np.uint8)
    for i, d in enumerate(datas):
        out[i] = np.frombuffer(hashlib.sha256(d).digest(), dtype=np.uint8)
    if n:
        _count("sha256", "serial", n)
    return out
