"""sr25519 (schnorrkel) host-side oracle: ristretto255 group, Merlin
transcripts (STROBE-128 over Keccak-f[1600]), signing context, sign/verify.

Reference: crypto/sr25519/{privkey,pubkey,batch}.go, which delegate to
curve25519-voi's primitives/sr25519 with an empty signing context. The
protocol re-implemented here from the public schnorrkel/merlin/STROBE
specifications:

  sign:  t = SigningContext("")          (merlin transcript "SigningContext"
                                          + appended context bytes)
         t.append_message("sign-bytes", msg)
         t.proto_name("Schnorr-sig"); append pk, R
         k = t.challenge_scalar("sign:c")   (64-byte wide reduction mod L)
         s = k*secret + r  mod L
         signature = R_ristretto(32) || s(32) with bit 255 SET (the
         schnorrkel "v0.1.1 format" marker, cleared before use)

  verify: recompute k, accept iff  [4](sB - kA - R) == identity  — the
         cofactor-4 coset check IS ristretto equality (two edwards points
         encode to the same ristretto string iff they differ by E[4]).

Field/curve arithmetic reuses the ed25519 oracle (same edwards25519 curve
under the ristretto quotient).

COMPATIBILITY NOTE: byte-for-byte schnorrkel interop is validated against
the ristretto255 draft test vectors (generator multiples) and
self-consistency (sign<->verify, tamper rejection, torsion-offset
acceptance); no external schnorrkel implementation exists in this image to
cross-check transcript bytes end-to-end.
"""

from __future__ import annotations

import hashlib
import os
import secrets as _secrets

from cometbft_tpu.crypto import ed25519_math as ed

P = ed.P
L = ed.L
D = ed.D


# ---------------------------------------------------------------------------
# Keccak-f[1600]
# ---------------------------------------------------------------------------

_KECCAK_RC = [
    0x0000000000000001, 0x0000000000008082, 0x800000000000808A, 0x8000000080008000,
    0x000000000000808B, 0x0000000080000001, 0x8000000080008081, 0x8000000000008009,
    0x000000000000008A, 0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
    0x000000008000808B, 0x800000000000008B, 0x8000000000008089, 0x8000000000008003,
    0x8000000000008002, 0x8000000000000080, 0x000000000000800A, 0x800000008000000A,
    0x8000000080008081, 0x8000000000008080, 0x0000000080000001, 0x8000000080008008,
]
_ROTC = [
    [0, 36, 3, 41, 18],
    [1, 44, 10, 45, 2],
    [62, 6, 43, 15, 61],
    [28, 55, 25, 21, 56],
    [27, 20, 39, 8, 14],
]
_M64 = (1 << 64) - 1


def _rotl(x: int, n: int) -> int:
    n %= 64
    return ((x << n) | (x >> (64 - n))) & _M64


def keccak_f1600(state: bytearray) -> None:
    """In-place permutation of the 200-byte state."""
    a = [[int.from_bytes(state[8 * (x + 5 * y): 8 * (x + 5 * y) + 8], "little")
          for y in range(5)] for x in range(5)]
    for rc in _KECCAK_RC:
        # theta
        c = [a[x][0] ^ a[x][1] ^ a[x][2] ^ a[x][3] ^ a[x][4] for x in range(5)]
        d = [c[(x - 1) % 5] ^ _rotl(c[(x + 1) % 5], 1) for x in range(5)]
        for x in range(5):
            for y in range(5):
                a[x][y] ^= d[x]
        # rho + pi
        b = [[0] * 5 for _ in range(5)]
        for x in range(5):
            for y in range(5):
                b[y][(2 * x + 3 * y) % 5] = _rotl(a[x][y], _ROTC[x][y])
        # chi
        for x in range(5):
            for y in range(5):
                a[x][y] = b[x][y] ^ ((~b[(x + 1) % 5][y]) & b[(x + 2) % 5][y] & _M64)
        # iota
        a[0][0] ^= rc
    for x in range(5):
        for y in range(5):
            state[8 * (x + 5 * y): 8 * (x + 5 * y) + 8] = a[x][y].to_bytes(8, "little")


# ---------------------------------------------------------------------------
# STROBE-128 (the subset merlin uses: meta-AD, AD, PRF), per the STROBE v1.0.2
# spec and merlin's strobe128.rs.
# ---------------------------------------------------------------------------

_STROBE_R = 166  # 1600/8 - (2*128)/8 - 2

_FLAG_I = 1
_FLAG_A = 1 << 1
_FLAG_C = 1 << 2
_FLAG_T = 1 << 3
_FLAG_M = 1 << 4
_FLAG_K = 1 << 5


def _load_native_strobe():
    """ctypes handle to native/strobe.c, or None (pure-Python fallback).
    Byte-equivalence with the Python implementation is asserted by
    tests/test_sr25519.py."""
    from cometbft_tpu import native

    return native.load("strobe")


_NATIVE = _load_native_strobe()


class _NativeStrobe128:
    """Same surface as Strobe128, state in a packed 203-byte C buffer."""

    __slots__ = ("_buf",)

    def __init__(self, protocol_label: bytes):
        import ctypes

        self._buf = ctypes.create_string_buffer(203)
        _NATIVE.strobe_new(self._buf, protocol_label, len(protocol_label))

    def meta_ad(self, data: bytes, more: bool) -> None:
        _NATIVE.strobe_meta_ad(self._buf, data, len(data), int(more))

    def ad(self, data: bytes, more: bool) -> None:
        _NATIVE.strobe_ad(self._buf, data, len(data), int(more))

    def prf(self, n: int, more: bool = False) -> bytes:
        import ctypes

        out = ctypes.create_string_buffer(n)
        _NATIVE.strobe_prf(self._buf, out, n, int(more))
        return out.raw

    def key(self, data: bytes, more: bool = False) -> None:
        _NATIVE.strobe_key(self._buf, data, len(data), int(more))


class Strobe128:
    def __new__(cls, protocol_label: bytes = b""):
        # default arg keeps copy.deepcopy (Transcript.clone in the pure-
        # Python fallback) working: deepcopy reconstructs via __new__(cls)
        if cls is Strobe128 and _NATIVE is not None:
            return _NativeStrobe128(protocol_label)
        return super().__new__(cls)

    def __init__(self, protocol_label: bytes):
        self.state = bytearray(200)
        seed = b"\x01" + bytes([_STROBE_R + 2]) + b"\x01\x00\x01\x60" + b"STROBEv1.0.2"
        self.state[: len(seed)] = seed
        keccak_f1600(self.state)
        self.pos = 0
        self.pos_begin = 0
        self.cur_flags = 0
        self.meta_ad(protocol_label, False)

    # --- duplex plumbing (merlin strobe128.rs)

    def _run_f(self) -> None:
        self.state[self.pos] ^= self.pos_begin
        self.state[self.pos + 1] ^= 0x04
        self.state[_STROBE_R + 1] ^= 0x80
        keccak_f1600(self.state)
        self.pos = 0
        self.pos_begin = 0

    def _absorb(self, data: bytes) -> None:
        for byte in data:
            self.state[self.pos] ^= byte
            self.pos += 1
            if self.pos == _STROBE_R:
                self._run_f()

    def _squeeze(self, n: int) -> bytes:
        out = bytearray()
        for _ in range(n):
            out.append(self.state[self.pos])
            self.state[self.pos] = 0
            self.pos += 1
            if self.pos == _STROBE_R:
                self._run_f()
        return bytes(out)

    def _begin_op(self, flags: int, more: bool) -> None:
        if more:
            assert self.cur_flags == flags, "STROBE: inconsistent `more` flags"
            return
        assert not (flags & _FLAG_T), "STROBE: T flag not implemented (no transport)"
        old_begin = self.pos_begin
        self.pos_begin = self.pos + 1
        self.cur_flags = flags
        self._absorb(bytes([old_begin, flags]))
        force_f = bool(flags & (_FLAG_C | _FLAG_K))
        if force_f and self.pos != 0:
            self._run_f()

    # --- merlin's three ops

    def meta_ad(self, data: bytes, more: bool) -> None:
        self._begin_op(_FLAG_M | _FLAG_A, more)
        self._absorb(data)

    def ad(self, data: bytes, more: bool) -> None:
        self._begin_op(_FLAG_A, more)
        self._absorb(data)

    def prf(self, n: int, more: bool = False) -> bytes:
        self._begin_op(_FLAG_I | _FLAG_A | _FLAG_C, more)
        return self._squeeze(n)

    def key(self, data: bytes, more: bool = False) -> None:
        self._begin_op(_FLAG_A | _FLAG_C, more)
        # KEY overwrites (duplex override), per strobe128.rs overwrite
        for byte in data:
            self.state[self.pos] = byte
            self.pos += 1
            if self.pos == _STROBE_R:
                self._run_f()


class BatchStrobe128:
    """N STROBE-128 sponges advancing in lockstep — the batch-axis analog
    of Strobe128 for transcripts whose OP SEQUENCE is identical across
    rows (every verification challenge of a commit runs the same Merlin
    ops; only the absorbed bytes differ per row). State is an (N, 200)
    uint8 array whose (N, 25)-uint64 view advances under ONE batched
    Keccak-f[1600] permutation (ops/hashvec.py: native SIMD when
    available, else the numpy batch rung). pos/pos_begin/cur_flags stay
    scalars because the op sequence — and therefore every duplex
    position — is shared by construction.

    Bit-for-bit equal to Strobe128 on every row (tests/test_hashvec.py
    fuzzes arbitrary op sequences against the serial class)."""

    __slots__ = ("n", "state", "pos", "pos_begin", "cur_flags")

    def __init__(self, n: int, protocol_label: bytes):
        import numpy as np

        self.n = n
        self.state = np.zeros((n, 200), dtype=np.uint8)
        seed = (b"\x01" + bytes([_STROBE_R + 2]) + b"\x01\x00\x01\x60"
                + b"STROBEv1.0.2")
        self.state[:, :len(seed)] = np.frombuffer(seed, dtype=np.uint8)
        self._perm()
        self.pos = 0
        self.pos_begin = 0
        self.cur_flags = 0
        self.meta_ad(protocol_label, False)

    @classmethod
    def from_snapshot(cls, n: int, snap: tuple) -> "BatchStrobe128":
        """Broadcast a single-row snapshot (shared transcript prefix) to
        N lockstep rows."""
        import numpy as np

        bs = cls.__new__(cls)
        state_row, bs.pos, bs.pos_begin, bs.cur_flags = snap
        bs.n = n
        bs.state = np.broadcast_to(state_row, (n, 200)).copy()
        return bs

    def snapshot(self) -> tuple:
        """Row-0 state + duplex position (rows are identical until
        row-dependent data is absorbed)."""
        return (self.state[0].copy(), self.pos, self.pos_begin,
                self.cur_flags)

    # --- duplex plumbing (mirrors Strobe128 exactly)

    def _perm(self) -> None:
        from cometbft_tpu.ops import hashvec

        hashvec.keccak_f1600_many(self.state.view("<u8"))

    def _run_f(self) -> None:
        self.state[:, self.pos] ^= self.pos_begin
        self.state[:, self.pos + 1] ^= 0x04
        self.state[:, _STROBE_R + 1] ^= 0x80
        self._perm()
        self.pos = 0
        self.pos_begin = 0

    def _chunks(self, m: int):
        """Yield (offset, count) absorb/squeeze spans between permutation
        boundaries — the batched replacement for the per-byte loop."""
        off = 0
        while off < m:
            c = min(_STROBE_R - self.pos, m - off)
            yield off, c
            self.pos += c
            off += c
            if self.pos == _STROBE_R:
                self._run_f()

    def _as_rows(self, data):
        """bytes (broadcast to all rows) or (N, m) uint8 array."""
        import numpy as np

        if isinstance(data, (bytes, bytearray)):
            return np.frombuffer(bytes(data), dtype=np.uint8)[None, :], len(data)
        assert data.shape[0] == self.n and data.dtype == np.uint8
        return data, data.shape[1]

    def _absorb(self, data) -> None:
        rows, m = self._as_rows(data)
        for off, c in self._chunks(m):
            self.state[:, self.pos:self.pos + c] ^= rows[:, off:off + c]

    def _begin_op(self, flags: int, more: bool) -> None:
        if more:
            assert self.cur_flags == flags, "STROBE: inconsistent `more` flags"
            return
        assert not (flags & _FLAG_T), "STROBE: T flag not implemented"
        old_begin = self.pos_begin
        self.pos_begin = self.pos + 1
        self.cur_flags = flags
        self._absorb(bytes([old_begin, flags]))
        if (flags & (_FLAG_C | _FLAG_K)) and self.pos != 0:
            self._run_f()

    # --- merlin's ops

    def meta_ad(self, data, more: bool) -> None:
        self._begin_op(_FLAG_M | _FLAG_A, more)
        self._absorb(data)

    def ad(self, data, more: bool) -> None:
        self._begin_op(_FLAG_A, more)
        self._absorb(data)

    def prf(self, n: int, more: bool = False):
        import numpy as np

        self._begin_op(_FLAG_I | _FLAG_A | _FLAG_C, more)
        out = np.empty((self.n, n), dtype=np.uint8)
        for off, c in self._chunks(n):
            out[:, off:off + c] = self.state[:, self.pos:self.pos + c]
            self.state[:, self.pos:self.pos + c] = 0
        return out

    def key(self, data, more: bool = False) -> None:
        self._begin_op(_FLAG_A | _FLAG_C, more)
        rows, m = self._as_rows(data)
        for off, c in self._chunks(m):
            self.state[:, self.pos:self.pos + c] = rows[:, off:off + c]


class Transcript:
    """merlin::Transcript."""

    MERLIN_LABEL = b"Merlin v1.0"

    def __init__(self, label: bytes):
        self.strobe = Strobe128(self.MERLIN_LABEL)
        self.append_message(b"dom-sep", label)

    def append_message(self, label: bytes, message: bytes) -> None:
        self.strobe.meta_ad(label, False)
        self.strobe.meta_ad(len(message).to_bytes(4, "little"), True)
        self.strobe.ad(message, False)

    def append_u64(self, label: bytes, v: int) -> None:
        self.append_message(label, v.to_bytes(8, "little"))

    def challenge_bytes(self, label: bytes, n: int) -> bytes:
        self.strobe.meta_ad(label, False)
        self.strobe.meta_ad(n.to_bytes(4, "little"), True)
        return self.strobe.prf(n)

    def clone(self) -> "Transcript":
        import copy

        t = Transcript.__new__(Transcript)
        t.strobe = copy.deepcopy(self.strobe)
        return t

    # --- schnorrkel extensions (schnorrkel/src/context.rs)

    def proto_name(self, label: bytes) -> None:
        self.append_message(b"proto-name", label)

    def append_point(self, label: bytes, point_bytes: bytes) -> None:
        self.append_message(label, point_bytes)

    def challenge_scalar(self, label: bytes) -> int:
        return int.from_bytes(self.challenge_bytes(label, 64), "little") % L

    def witness_scalar(self, label: bytes, nonce_seed: bytes) -> int:
        """schnorrkel witness_scalar: fork the transcript via STROBE rekey
        with the nonce seed + RNG. Deterministic-with-randomness in
        schnorrkel; deterministic here (witness hygiene does not affect
        verifier compat)."""
        import copy

        s = copy.deepcopy(self.strobe)
        s.meta_ad(b"", False)
        s.meta_ad(label, True)
        s.key(nonce_seed, False)
        s.key(_secrets.token_bytes(32), False)
        s.meta_ad((64).to_bytes(4, "little"), False)
        return int.from_bytes(s.prf(64), "little") % L


# ---------------------------------------------------------------------------
# ristretto255 encode/decode over the ed25519 oracle's extended coordinates
# ---------------------------------------------------------------------------

SQRT_M1 = pow(2, (P - 1) // 4, P)


def _sqrt_ratio_m1(u: int, v: int) -> tuple[bool, int]:
    """(was_square, sqrt(u/v) or sqrt(i*u/v)), nonnegative root
    (ristretto255 spec SQRT_RATIO_M1)."""
    v3 = v * v % P * v % P
    v7 = v3 * v3 % P * v % P
    r = u * v3 % P * pow(u * v7 % P, (P - 5) // 8, P) % P
    check = v * r % P * r % P
    correct = check == u % P
    flipped = check == (-u) % P
    flipped_i = check == (-u) % P * SQRT_M1 % P
    if flipped or flipped_i:
        r = r * SQRT_M1 % P
    was_square = correct or flipped
    if r % 2 == 1:  # CT_ABS: take the nonnegative (even) root
        r = (-r) % P
    return was_square, r


# invsqrt(a - d), a = -1: the nonnegative root of 1/(a-d)
INVSQRT_A_MINUS_D = _sqrt_ratio_m1(1, (-1 - D) % P)[1]


def ristretto_decode(b: bytes) -> tuple[int, int, int, int] | None:
    """32 bytes -> extended point, or None (spec DECODE)."""
    if len(b) != 32:
        return None
    s = int.from_bytes(b, "little")
    if s >= P or s % 2 == 1:  # canonical and nonnegative
        return None
    ss = s * s % P
    u1 = (1 - ss) % P
    u2 = (1 + ss) % P
    u2_sqr = u2 * u2 % P
    v = (-(D * u1 % P * u1) - u2_sqr) % P
    was_square, invsqrt = _sqrt_ratio_m1(1, v * u2_sqr % P)
    den_x = invsqrt * u2 % P
    den_y = invsqrt * den_x % P * v % P
    x = 2 * s % P * den_x % P
    if x % 2 == 1:
        x = (-x) % P
    y = u1 * den_y % P
    t = x * y % P
    if not was_square or t % 2 == 1 or y == 0:
        return None
    return (x, y, 1, t)


def ristretto_encode(pt: tuple[int, int, int, int]) -> bytes:
    """Extended point -> canonical 32 bytes (spec ENCODE)."""
    x0, y0, z0, t0 = pt
    u1 = (z0 + y0) * (z0 - y0) % P
    u2 = x0 * y0 % P
    _, invsqrt = _sqrt_ratio_m1(1, u1 * u2 % P * u2 % P)
    den1 = invsqrt * u1 % P
    den2 = invsqrt * u2 % P
    z_inv = den1 * den2 % P * t0 % P
    ix0 = x0 * SQRT_M1 % P
    iy0 = y0 * SQRT_M1 % P
    enchanted_denominator = den1 * INVSQRT_A_MINUS_D % P
    rotate = (t0 * z_inv % P) % 2 == 1
    if rotate:
        x, y = iy0, ix0
        den_inv = enchanted_denominator
    else:
        x, y = x0, y0
        den_inv = den2
    if (x * z_inv % P) % 2 == 1:
        y = (-y) % P
    s = (z0 - y) * den_inv % P
    if s % 2 == 1:
        s = (-s) % P
    return s.to_bytes(32, "little")


def ristretto_basepoint_table():
    return ed.B_POINT


# ---------------------------------------------------------------------------
# schnorrkel keys + sign/verify (signing context = b"" as the reference,
# privkey.go:17 signingCtx = sr25519.NewSigningContext([]byte{}))
# ---------------------------------------------------------------------------

SIGNING_CTX = b"substrate"  # NOTE: reference uses empty ctx; see make_transcript


def make_signing_transcript(msg: bytes, ctx: bytes = b"") -> Transcript:
    """sr25519.NewSigningContext(ctx).NewTranscriptBytes(msg)
    (schnorrkel signing_context(ctx).bytes(msg))."""
    t = Transcript(b"SigningContext")
    t.append_message(b"", ctx)
    t.append_message(b"sign-bytes", msg)
    return t


def expand_ed25519(mini: bytes) -> tuple[int, bytes]:
    """MiniSecretKey.ExpandEd25519: scalar = clamp(sha512(mini)[:32]) >> 3
    ('divided by cofactor' — schnorrkel keeps the ed25519 bit layout
    compatible), nonce = sha512(mini)[32:]."""
    h = hashlib.sha512(mini).digest()
    key = bytearray(h[:32])
    key[0] &= 248
    key[31] &= 63
    key[31] |= 64
    scalar = int.from_bytes(bytes(key), "little") >> 3
    return scalar % L, h[32:]


def keypair_from_mini(mini: bytes) -> tuple[int, bytes, bytes]:
    """-> (secret scalar, nonce, public ristretto bytes)."""
    scalar, nonce = expand_ed25519(mini)
    pub = ristretto_encode(ed.scalar_mult(scalar, ed.B_POINT))
    return scalar, nonce, pub


def sign(mini_or_pair, msg: bytes) -> bytes:
    """64-byte schnorrkel signature: R(32) || s(32) with bit 255 set."""
    if isinstance(mini_or_pair, bytes):
        scalar, nonce, pub = keypair_from_mini(mini_or_pair)
    else:
        scalar, nonce, pub = mini_or_pair
    t = make_signing_transcript(msg)
    t.proto_name(b"Schnorr-sig")
    t.append_point(b"sign:pk", pub)
    r = t.witness_scalar(b"signing", nonce)
    r_point = ed.scalar_mult(r, ed.B_POINT)
    r_bytes = ristretto_encode(r_point)
    t.append_point(b"sign:R", r_bytes)
    k = t.challenge_scalar(b"sign:c")
    s = (k * scalar + r) % L
    sig = bytearray(r_bytes + s.to_bytes(32, "little"))
    sig[63] |= 128  # schnorrkel "not-ed25519" marker
    return bytes(sig)


def parse_signature(sig: bytes) -> tuple[bytes, int] | None:
    """-> (R bytes, s) or None. The marker bit must be set (schnorrkel
    rejects unmarked signatures) and s must be canonical."""
    if len(sig) != 64 or not sig[63] & 128:
        return None
    s_bytes = bytearray(sig[32:])
    s_bytes[31] &= 127
    s = int.from_bytes(bytes(s_bytes), "little")
    if s >= L:
        return None
    return sig[:32], s


def compute_challenge(pub: bytes, r_bytes: bytes, msg: bytes) -> int:
    t = make_signing_transcript(msg)
    t.proto_name(b"Schnorr-sig")
    t.append_point(b"sign:pk", pub)
    t.append_point(b"sign:R", r_bytes)
    return t.challenge_scalar(b"sign:c")


# shared transcript prefix per message length: everything up to (and
# including) the "sign-bytes" length header is row-independent, so it runs
# once on a 1-row batch sponge and broadcasts (bounded cache; commit
# sign-bytes lengths are few per chain)
_PREFIX_CACHE: dict[int, tuple] = {}


def _signing_prefix(mlen: int) -> tuple:
    snap = _PREFIX_CACHE.get(mlen)
    if snap is None:
        bs = BatchStrobe128(1, Transcript.MERLIN_LABEL)
        for label, msg in ((b"dom-sep", b"SigningContext"), (b"", b"")):
            bs.meta_ad(label, False)
            bs.meta_ad(len(msg).to_bytes(4, "little"), True)
            bs.ad(msg, False)
        bs.meta_ad(b"sign-bytes", False)
        bs.meta_ad(mlen.to_bytes(4, "little"), True)
        snap = bs.snapshot()
        if len(_PREFIX_CACHE) >= 256:
            _PREFIX_CACHE.pop(next(iter(_PREFIX_CACHE)))
        _PREFIX_CACHE[mlen] = snap
    return snap


def _batch_challenge_digests(pub_rows, r_rows, msg_rows):
    """(N, 32)/(N, 32)/(N, mlen) uint8 rows -> (N, 64) uint8 challenge
    bytes: the whole Merlin verification transcript advanced in lockstep,
    two batched permutations per row instead of a per-row sponge."""
    n = pub_rows.shape[0]
    bs = BatchStrobe128.from_snapshot(n, _signing_prefix(msg_rows.shape[1]))
    bs.ad(msg_rows, False)
    for label, msg in ((b"proto-name", b"Schnorr-sig"),):
        bs.meta_ad(label, False)
        bs.meta_ad(len(msg).to_bytes(4, "little"), True)
        bs.ad(msg, False)
    for label, rows in ((b"sign:pk", pub_rows), (b"sign:R", r_rows)):
        bs.meta_ad(label, False)
        bs.meta_ad((32).to_bytes(4, "little"), True)
        bs.ad(rows, False)
    bs.meta_ad(b"sign:c", False)
    bs.meta_ad((64).to_bytes(4, "little"), True)
    return bs.prf(64)


def batch_challenge_words(
    pubs: list[bytes], r_list: list[bytes], msgs: list[bytes]
):
    """All N verification challenges as packed (N, 8) uint32 device words
    (k mod L, little-endian) — the staging fast path. Rows group by
    message length; each group of VEC_MIN_ROWS+ advances under the batch
    STROBE transcript (one permutation call per duplex boundary for the
    WHOLE group); ragged stragglers fall back to the serial rung
    (native strobe.c batch, else per-row Python). Bit-for-bit equal to
    compute_challenge on every row."""
    import numpy as np

    n = len(pubs)
    r_rows = (np.frombuffer(b"".join(r_list), dtype=np.uint8).reshape(n, 32)
              if n else np.zeros((0, 32), dtype=np.uint8))
    return batch_challenge_words_rows(pubs, r_rows, msgs)


def batch_challenge_words_rows(pubs: list[bytes], r_rows, msgs: list[bytes]):
    """Array-native batch_challenge_words: R as the staged (N, 32) uint8
    signature halves (no per-row bytes round trip — sr25519_kernel's
    staging path feeds signature rows straight in)."""
    import numpy as np

    from cometbft_tpu.ops import hashvec

    n = len(pubs)
    out = np.zeros((n, 8), dtype=np.uint32)
    if n == 0:
        return out
    by_len: dict[int, list[int]] = {}
    for i, m in enumerate(msgs):
        by_len.setdefault(len(m), []).append(i)
    for mlen, idxs in by_len.items():
        if (len(idxs) < hashvec.VEC_MIN_ROWS
                or os.environ.get("CBFT_HASHVEC") == "serial"):
            ks = _serial_compute_challenges(
                [pubs[i] for i in idxs], [r_rows[i].tobytes() for i in idxs],
                [msgs[i] for i in idxs])
            blob = b"".join(k.to_bytes(32, "little") for k in ks)
            out[np.asarray(idxs, dtype=np.intp)] = np.frombuffer(
                blob, dtype=np.uint8).reshape(len(idxs), 32).view("<u4")
            continue
        sel = np.asarray(idxs, dtype=np.intp)
        pub_rows = np.frombuffer(
            b"".join(pubs[i] for i in idxs), dtype=np.uint8).reshape(-1, 32)
        msg_rows = np.frombuffer(
            b"".join(msgs[i] for i in idxs), dtype=np.uint8).reshape(-1, mlen)
        digests = _batch_challenge_digests(
            pub_rows, np.ascontiguousarray(r_rows[sel]), msg_rows)
        out[sel] = hashvec.reduce512_mod_l(digests)
    return out


def batch_challenge_words_block(pub_rows, r_rows, msgs):
    """batch_challenge_words_rows over columns: the keys as their (N, 32)
    uint8 matrix and the messages as a libs/prefixrows.MsgBlock, one
    message matrix a message length (MsgBlock.matrix) and no row cut out
    for the rows that batch; the same words, row for row."""
    import numpy as np

    from cometbft_tpu.ops import hashvec

    n = len(msgs)
    out = np.zeros((n, 8), dtype=np.uint32)
    if n == 0:
        return out
    lens = msgs.lengths()
    loose = []
    for mlen in np.unique(lens).tolist():
        sel = np.flatnonzero(lens == mlen)
        if (len(sel) < hashvec.VEC_MIN_ROWS
                or os.environ.get("CBFT_HASHVEC") == "serial"):
            loose.append(sel)
            continue
        digests = _batch_challenge_digests(
            np.ascontiguousarray(pub_rows[sel]),
            np.ascontiguousarray(r_rows[sel]),
            msgs.take(sel).matrix(mlen))
        out[sel] = hashvec.reduce512_mod_l(digests)
    if loose:
        sel = np.concatenate(loose)
        out[sel] = batch_challenge_words_rows(
            [pub_rows[i].tobytes() for i in sel],
            np.ascontiguousarray(r_rows[sel]), msgs.take(sel).tolist())
    return out


def batch_compute_challenges(
    pubs: list[bytes], r_list: list[bytes], msgs: list[bytes]
) -> list[int]:
    """All N verification challenges as ints. Routed through the batch
    STROBE transcript (batch_challenge_words) for uniform-length groups;
    serial rung otherwise. Equivalence with compute_challenge is asserted
    by tests/test_sr25519.py and tests/test_hashvec.py."""
    n = len(pubs)
    if n == 0:
        return []
    blob = batch_challenge_words(pubs, r_list, msgs).tobytes()
    return [int.from_bytes(blob[32 * i: 32 * i + 32], "little")
            for i in range(n)]


def _serial_compute_challenges(
    pubs: list[bytes], r_list: list[bytes], msgs: list[bytes]
) -> list[int]:
    """The serial rung: one native call for the whole batch (strobe.c
    sr25519_batch_challenge — the whole Merlin transcript per row runs in
    C, so the per-row cost is keccak-bound, not ctypes-bound), else the
    per-row Python path."""
    n = len(pubs)
    if n == 0:
        return []
    if _NATIVE is None or not hasattr(_NATIVE, "sr25519_batch_challenge"):
        return [compute_challenge(p, r, m)
                for p, r, m in zip(pubs, r_list, msgs)]
    import ctypes

    import numpy as np

    msg_buf = b"".join(msgs)
    offs = np.zeros(n + 1, dtype=np.int64)
    np.cumsum([len(m) for m in msgs], out=offs[1:])
    pub_buf = b"".join(pubs)
    r_buf = b"".join(r_list)
    out = ctypes.create_string_buffer(64 * n)
    offs_p = offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))

    def run_range(start: int, count: int) -> None:
        # ctypes releases the GIL for the duration of the C call, so
        # chunks keccak in parallel on real cores
        _NATIVE.sr25519_batch_challenge(
            pub_buf[32 * start:], r_buf[32 * start:], msg_buf,
            ctypes.cast(ctypes.byref(offs_p.contents, 8 * start),
                        ctypes.POINTER(ctypes.c_int64)),
            count, ctypes.cast(ctypes.byref(out, 64 * start),
                               ctypes.POINTER(ctypes.c_char)))

    workers = min(4, max(1, n // 512))
    if workers > 1:
        import concurrent.futures

        step = (n + workers - 1) // workers
        with concurrent.futures.ThreadPoolExecutor(workers) as ex:
            list(ex.map(lambda s: run_range(s, min(step, n - s)),
                        range(0, n, step)))
    else:
        run_range(0, n)
    raw = out.raw
    return [int.from_bytes(raw[64 * i: 64 * i + 64], "little") % L
            for i in range(n)]


def verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    parsed = parse_signature(sig)
    if parsed is None:
        return False
    r_bytes, s = parsed
    a_pt = ristretto_decode(pub)
    r_pt = ristretto_decode(r_bytes)
    if a_pt is None or r_pt is None:
        return False
    k = compute_challenge(pub, r_bytes, msg)
    # [4](sB - kA - R) == O  <=>  ristretto equality sB - kA == R
    sb = ed.scalar_mult(s, ed.B_POINT)
    ka = ed.scalar_mult(k, a_pt)
    diff = ed.point_add(sb, ed.point_neg(ka))
    diff = ed.point_add(diff, ed.point_neg(r_pt))
    quad = ed.point_double(ed.point_double(diff))
    return ed.is_identity(quad)


def gen_mini() -> bytes:
    return _secrets.token_bytes(32)
