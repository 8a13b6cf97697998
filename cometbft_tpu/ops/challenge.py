"""Device-side ed25519 challenge derivation: k = SHA-512(R||A||M) mod L
computed on the chip, so only signature material crosses the wire.

Of the ~98 B/sig the PR 10 reduced-send steady state shipped, 32 B was
the challenge scalar k — host-computed from bytes the device already has
(A is resident in the PR 10 validator tables, M's prefix is shared per
(height,round,chain) vote flush). This module is the device twin of the
host challenge pipeline in ops/hashvec.py:

  lane-parallel SHA-512     32-bit lane-pair message schedule and
                            compression over the batch axis (the VPU is
                            int32-native; every 64-bit word lives as an
                            (hi, lo) uint32 pair, carries recovered from
                            the wrapped low sum)
  device Barrett mod L      base-2^16 limbs in uint32 (16x16 products
                            are exact in 32 bits), HAC 14.42 with the
                            same mu/L limb tables as the numpy rung,
                            emitting the packed (8, N) challenge words
                            the verify grid consumes
  prefix/tail table         a 256-row device-resident table of
                            prefix||tail byte rows, content-keyed, so a
                            vote lane's message descriptor is a 2-byte
                            (flag|prefix-id) plus only the ~10-24
                            variable suffix bytes. A new row (a new
                            height) rides the batch's own upload: the
                            wire block carries up to CARRY_ROWS dirty
                            rows at its tail, the derive program sets
                            them and hands the new table back; the
                            batch's checksum covers them, and the table
                            adopts the result once that batch has
                            resolved intact. Only first use and an
                            overflow take the awaited scatter
                            (PrefixTable.sync)

Both cores are oracled bit-for-bit against hashvec.sha512_rows /
reduce512_mod_l (tests/test_challenge.py fuzzes every rung); the wire
integration lives in ops/ed25519_kernel.py behind
`crypto.wire_device_challenge`, with a degradation ladder (table miss,
ragged/oversize message, non-resident A, chaos/breaker) that falls back
per-lane or per-batch to the host-computed k — never a verdict change.

Wire layout (one flat uint32 block, ed25519_kernel stages it):

  words[0      : 8b ]   R encoding words, (8, b) word-major
  words[8b     : 16b]   s scalar words, (8, b) word-major
  words[16b    : W  ]   descriptor stream: 2*b bytes of per-lane uint16
                        LE descriptors (bit15 = device-derive flag, low
                        15 bits = prefix-table row), then b lanes of
                        `var` variable suffix bytes, lane-contiguous
  words[W      : W+C]   C = CARRY_WORDS: the prefix table's carried rows,
                        CARRY_ROWS row indices, then as many rows of
                        PREFIX_CAP bytes as LE words

giving 64 + 2 + var wire bytes per signature (plus the 2-byte residency
index) — ~66-82 B/sig against the 98 of host-computed challenges — and
CARRY_BYTES a batch for the table's rows.
"""

from __future__ import annotations

import functools
import threading

import numpy as np

from cometbft_tpu.ops import hashvec as _hv

# chaos/supervisor site for the derive seam (libs/chaos.py,
# ops/dispatch.py): failures here degrade to the host-challenge path
# under this site's own breaker — the main "device" breaker never trips
# on a challenge-plane fault
SITE = "ed25519.challenge"

TABLE_ROWS = 256  # prefix/tail rows resident per put_key
PREFIX_CAP = 160  # prefix+tail bytes per row (vote prefixes are ~105)
MAX_VAR = 24      # variable suffix bytes shipped per lane; 2 + var must
                  # stay under the 32 B of k it replaces for a wire win
MAX_MLEN = 192    # message bytes (prefix+var+tail): 64+192 pads to <= 3
                  # SHA-512 blocks, the static compile ladder's ceiling
MIN_LANES = 4     # below this the classic path's fixed cost wins
CARRY_ROWS = 8    # dirty table rows ONE derive call carries: a catch-up
                  # window's new heights (blocksync VERIFY_WINDOW). One
                  # static width, so the derive family stays as it was;
                  # more dirty rows than this take the awaited sync()
CARRY_WORDS = CARRY_ROWS * (1 + PREFIX_CAP // 4)  # indices, then rows
CARRY_BYTES = 4 * CARRY_WORDS
MIN_ELIGIBLE_FRAC = 0.5  # mostly-fallback batches take the classic path

# ------------------------------------------------------------------ config

_cfg = {"enabled": True}


def configure(enabled: bool | None = None) -> None:
    if enabled is not None:
        _cfg["enabled"] = bool(enabled)


def enabled() -> bool:
    return _cfg["enabled"]


# ------------------------------------------------------------------- stats

_stats_lock = threading.Lock()
_stats: dict[str, int] = {}


def count(key: str, n: int = 1) -> None:
    with _stats_lock:
        _stats[key] = _stats.get(key, 0) + n


def stats() -> dict[str, int]:
    with _stats_lock:
        return dict(_stats)


def reset_stats() -> None:
    with _stats_lock:
        _stats.clear()


# ------------------------------------------------- 64-bit lane-pair helpers
#
# The TPU VPU has no int64 lanes: every SHA-512 word is an (hi, lo)
# uint32 pair. Shift amounts are static Python ints so the rotations
# trace to plain vector shifts (no shift-by-32 hazards, no dtype
# promotion — Python scalars stay weakly typed against uint32).


def _add64(ah, al, bh, bl):
    import jax.numpy as jnp

    s = al + bl  # uint32 wraps; wrapped sum below an addend flags carry
    carry = (s < al).astype(jnp.uint32)
    return ah + bh + carry, s


def _rotr64(h, l, n: int):  # noqa: E741 - l is the low word
    if n == 32:
        return l, h
    if n < 32:
        return ((h >> n) | (l << (32 - n)), (l >> n) | (h << (32 - n)))
    m = n - 32
    return ((l >> m) | (h << (32 - m)), (h >> m) | (l << (32 - m)))


def _shr64(h, l, n: int):  # noqa: E741 - n < 32 only (sigma shifts 6, 7)
    return h >> n, (l >> n) | (h << (32 - n))


def _xor3(p, q, r):
    return p[0] ^ q[0] ^ r[0], p[1] ^ q[1] ^ r[1]


# --------------------------------------------------------- SHA-512 (device)

_K_HI_NP = (_hv._SHA_K >> np.uint64(32)).astype(np.uint32)
_K_LO_NP = (_hv._SHA_K & np.uint64(0xFFFFFFFF)).astype(np.uint32)
_H0_HI = tuple(int(x) >> 32 for x in _hv._SHA_H0)
_H0_LO = tuple(int(x) & 0xFFFFFFFF for x in _hv._SHA_H0)


def _pairs_from_be_bytes(buf):
    """(N, nb*128) uint8 padded buffer -> ((N, nb, 16), (N, nb, 16))
    uint32 big-endian message word pairs."""
    import jax.numpy as jnp

    b = buf.reshape(buf.shape[0], -1, 16, 8).astype(jnp.uint32)
    hi = (b[..., 0] << 24) | (b[..., 1] << 16) | (b[..., 2] << 8) | b[..., 3]
    lo = (b[..., 4] << 24) | (b[..., 5] << 16) | (b[..., 6] << 8) | b[..., 7]
    return hi, lo


def _compress_pairs(whi, wlo):
    """(N, nb, 16) uint32 BE word pairs -> 16-tuple of (N,) uint32 state
    arrays [h0hi, h0lo, ..., h7hi, h7lo] — FIPS 180-4 compression, all N
    lanes through each round together (the device twin of
    hashvec._sha512_blocks_numpy)."""
    import jax
    import jax.numpy as jnp

    n, nb, _ = whi.shape
    khi = jnp.asarray(_K_HI_NP)
    klo = jnp.asarray(_K_LO_NP)
    state = []
    for i in range(8):
        state.append(jnp.full((n,), _H0_HI[i], dtype=jnp.uint32))
        state.append(jnp.full((n,), _H0_LO[i], dtype=jnp.uint32))
    for bi in range(nb):  # nb is static (<= 3): the block loop unrolls
        wh = jnp.zeros((80, n), dtype=jnp.uint32).at[:16].set(whi[:, bi, :].T)
        wl = jnp.zeros((80, n), dtype=jnp.uint32).at[:16].set(wlo[:, bi, :].T)

        def _sched(t, wp):
            wh, wl = wp
            w15 = (wh[t - 15], wl[t - 15])
            w2 = (wh[t - 2], wl[t - 2])
            s0 = _xor3(_rotr64(*w15, 1), _rotr64(*w15, 8), _shr64(*w15, 7))
            s1 = _xor3(_rotr64(*w2, 19), _rotr64(*w2, 61), _shr64(*w2, 6))
            ah, al = _add64(wh[t - 16], wl[t - 16], *s0)
            ah, al = _add64(ah, al, wh[t - 7], wl[t - 7])
            ah, al = _add64(ah, al, *s1)
            return wh.at[t].set(ah), wl.at[t].set(al)

        with jax.named_scope("sha512_schedule"):
            wh, wl = jax.lax.fori_loop(16, 80, _sched, (wh, wl))

        def _round(t, st):
            (ah, al, bh, bl, ch, cl, dh, dl,
             eh, el, fh, fl, gh, gl, hh, hl) = st
            s1 = _xor3(_rotr64(eh, el, 14), _rotr64(eh, el, 18),
                       _rotr64(eh, el, 41))
            chh = gh ^ (eh & (fh ^ gh))
            chl = gl ^ (el & (fl ^ gl))
            t1h, t1l = _add64(hh, hl, *s1)
            t1h, t1l = _add64(t1h, t1l, chh, chl)
            t1h, t1l = _add64(t1h, t1l, khi[t], klo[t])
            t1h, t1l = _add64(t1h, t1l, wh[t], wl[t])
            s0 = _xor3(_rotr64(ah, al, 28), _rotr64(ah, al, 34),
                       _rotr64(ah, al, 39))
            mjh = (ah & (bh | ch)) | (bh & ch)
            mjl = (al & (bl | cl)) | (bl & cl)
            t2h, t2l = _add64(*s0, mjh, mjl)
            neh, nel = _add64(dh, dl, t1h, t1l)
            nah, nal = _add64(t1h, t1l, t2h, t2l)
            return (nah, nal, ah, al, bh, bl, ch, cl,
                    neh, nel, eh, el, fh, fl, gh, gl)

        with jax.named_scope("sha512_rounds"):
            st = jax.lax.fori_loop(0, 80, _round, tuple(state))
        nxt = []
        for i in range(8):
            sh, sl = _add64(state[2 * i], state[2 * i + 1],
                            st[2 * i], st[2 * i + 1])
            nxt.append(sh)
            nxt.append(sl)
        state = nxt
    return tuple(state)


# ----------------------------------------- Barrett reduction mod L (device)
#
# Same HAC 14.42 shape as hashvec._reduce512_mod_l_numpy, re-limbed for
# uint32 lanes: base-2^16 limbs so every 16x16 product is exact in 32
# bits, split into (lo, hi) contributions whose accumulators stay under
# 2^22 before one carry sweep. Borrows ride the uint32 sign bit (every
# operand is < 2^16, so a wrapped difference always sets bit 31).

_MU17_PY = tuple(int(x) for x in _hv._MU17)
_L17_PY = tuple(int(x) for x in _hv._L17)


def _bswap32(x):
    return (((x >> 24) & 0xFF) | ((x >> 8) & 0xFF00)
            | ((x << 8) & 0xFF0000) | (x << 24))


def _state_to_limbs(state):
    """16-tuple of (N,) uint32 BE state pairs -> 32 (N,) uint32 base-2^16
    limbs of the little-endian 512-bit digest value (the digest byte
    stream is the BE serialization of the eight 64-bit state words)."""
    limbs = []
    for i in range(8):
        wh = _bswap32(state[2 * i])
        wl = _bswap32(state[2 * i + 1])
        limbs += [wh & 0xFFFF, wh >> 16, wl & 0xFFFF, wl >> 16]
    return limbs


def _carry16(acc):
    """One base-2^16 carry sweep along a list of (N,) uint32 limb
    accumulators (values < 2^22 on entry; canonical limbs on exit;
    overflow off the top limb dropped — mod b^len semantics)."""
    out = []
    c = None
    for a in acc:
        t = a if c is None else a + c
        out.append(t & 0xFFFF)
        c = t >> 16
    return out


def _barrett_mod_l(x):
    """32 (N,) uint32 base-2^16 limbs -> 16 limbs of (x mod L), the
    bit-for-bit device twin of hashvec._reduce512_mod_l_numpy."""
    import jax.numpy as jnp

    zeros = jnp.zeros_like(x[0])
    q1 = x[15:]  # floor(x / b^15): 17 limbs
    q2 = [zeros] * 34
    for i in range(17):
        mu = _MU17_PY[i]
        if mu == 0:
            continue
        for j in range(17):
            p = q1[j] * mu  # < 2^32: exact
            q2[i + j] = q2[i + j] + (p & 0xFFFF)
            q2[i + j + 1] = q2[i + j + 1] + (p >> 16)
    q2 = _carry16(q2)
    q3 = q2[17:]  # floor(q2 / b^17): 17 limbs
    r2 = [zeros] * 17  # q3*L mod b^17
    for i in range(17):
        li = _L17_PY[i]
        if li == 0:
            continue
        for j in range(17 - i):
            p = q3[j] * li
            r2[i + j] = r2[i + j] + (p & 0xFFFF)
            if i + j + 1 < 17:
                r2[i + j + 1] = r2[i + j + 1] + (p >> 16)
    r2 = _carry16(r2)
    r = []
    borrow = zeros
    for j in range(17):
        t = x[j] - r2[j] - borrow
        r.append(t & 0xFFFF)
        borrow = t >> 31
    # Barrett guarantees r < 3L: at most two conditional subtractions
    for _ in range(2):
        d = []
        borrow = zeros
        for j in range(17):
            t = r[j] - _L17_PY[j] - borrow
            d.append(t & 0xFFFF)
            borrow = t >> 31
        ge = borrow == 0  # no final borrow: r >= L, take the difference
        r = [jnp.where(ge, d[j], r[j]) for j in range(17)]
    return r[:16]


def _limbs_to_words(r):
    """16 (N,) uint32 base-2^16 limbs -> (8, N) uint32 packed LE words
    (the k layout the verify grid consumes, batch-minor)."""
    import jax.numpy as jnp

    return jnp.stack([r[2 * w] | (r[2 * w + 1] << 16) for w in range(8)])


# -------------------------------------------------- test oracle entry points
#
# Standalone device pipelines over host arrays — what
# tests/test_challenge.py fuzzes bit-for-bit against the hashvec twins.
# The production path (the derive program below) never leaves the device.


@functools.lru_cache(maxsize=8)
def _digest_fn(nb: int):
    import jax
    import jax.numpy as jnp

    def sha512_digest(buf):
        st = _compress_pairs(*_pairs_from_be_bytes(buf))
        return jnp.stack(st, axis=1)  # (N, 16): h0hi, h0lo, ...

    return jax.jit(sha512_digest)


def sha512_rows_device(rows: np.ndarray) -> np.ndarray:
    """(N, L) uint8 same-length rows -> (N, 64) uint8 digests via the
    device lane-pair compression — bit-for-bit hashvec.sha512_rows."""
    n = rows.shape[0]
    if n == 0:
        return np.zeros((0, 64), dtype=np.uint8)
    buf, nb = _hv._sha512_pad(np.ascontiguousarray(rows))
    st = np.asarray(_digest_fn(nb)(buf))  # (N, 16) uint32
    return np.ascontiguousarray(st).astype(">u4").view(np.uint8).reshape(n, 64)


@functools.lru_cache(maxsize=2)
def _reduce_fn():
    import jax
    import jax.numpy as jnp

    def reduce_mod_l(w):  # (N, 16) uint32 LE digest words
        limbs = []
        for i in range(16):
            limbs += [w[:, i] & 0xFFFF, w[:, i] >> 16]
        with jax.named_scope("barrett_mod_l"):
            return jnp.transpose(_limbs_to_words(_barrett_mod_l(limbs)))

    return jax.jit(reduce_mod_l)


def reduce512_mod_l_device(digests: np.ndarray) -> np.ndarray:
    """(N, 64) uint8 little-endian digests -> (N, 8) uint32 words of
    (value mod L) via the device Barrett rung — bit-for-bit
    hashvec.reduce512_mod_l."""
    n = digests.shape[0]
    if n == 0:
        return np.zeros((0, 8), dtype=np.uint32)
    w = np.ascontiguousarray(digests).view("<u4").reshape(n, 16)
    return np.asarray(_reduce_fn()(w))


# ------------------------------------------------------ prefix/tail table
#
# The device-resident message dictionary: each row is prefix||tail bytes
# (a vote flush's shared sign-bytes prefix plus the batch-common suffix
# tail — chain-id trailer etc.), content-keyed host-side, LRU-evicted.
# Rows the device has not confirmed yet are DIRTY. plan_batch captures,
# AT PLAN TIME, the last confirmed device snapshot and a copy of the
# dirty rows (PrefixTable.carry); they go up at the tail of the batch's
# wire block, the derive program sets them and returns the new table,
# and the batch's checksum covers them. Scatters are functional and the
# plan's copies are its own, so an in-flight batch keeps its immutable
# table even if later plans evict its rows. The table adopts a derive's
# output (and cleans the rows) only once that batch resolved with its
# integrity intact; until then every plan carries them again. The
# awaited checksummed scatter (sync) serves first use and an overflow.

_CHK_MULT = np.uint32(2654435761)  # Knuth multiplicative; position-weighted


def _host_tab_chk(idx: np.ndarray, vals: np.ndarray) -> int:
    w = (np.arange(vals.size, dtype=np.uint32) * _CHK_MULT
         + np.uint32(1))
    chk = np.sum(vals.reshape(-1).astype(np.uint32) * w, dtype=np.uint32)
    chk += np.sum(idx.astype(np.uint32), dtype=np.uint32)
    return int(chk)


@functools.lru_cache(maxsize=8)
def _tab_scatter_fn(db: int):
    import jax
    import jax.numpy as jnp

    def prefix_table_scatter(tab, idx, vals):
        new = tab.at[idx].set(vals)
        w = (jnp.arange(vals.size, dtype=jnp.uint32) * _CHK_MULT
             + jnp.uint32(1))
        chk = jnp.sum(vals.reshape(-1).astype(jnp.uint32) * w,
                      dtype=jnp.uint32)
        chk = chk + jnp.sum(idx.astype(jnp.uint32), dtype=jnp.uint32)
        return new, chk

    return jax.jit(prefix_table_scatter)


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class PrefixTable:
    """One put_key's device prefix/tail dictionary: TABLE_ROWS rows of
    PREFIX_CAP bytes, host mirror + dirty-row scatter sync."""

    def __init__(self, put_key: str = "", device=None) -> None:
        self.put_key = put_key
        self._device = device
        self._lock = threading.Lock()
        self._rows: dict[tuple[bytes, bytes], int] = {}  # content -> row
        self._row_key: dict[int, tuple[bytes, bytes]] = {}
        self._lru: dict[tuple[bytes, bytes], None] = {}  # dict order = LRU
        self._host = np.zeros((TABLE_ROWS, PREFIX_CAP), dtype=np.uint8)
        # row -> the version of its insert: what the device has not
        # confirmed (a row re-used since a plan carried it stays dirty)
        self._dirty: dict[int, int] = {}
        self._tab = None  # the last CONFIRMED device snapshot
        self.version = 0
        self.counters = {"inserts": 0, "hits": 0, "evictions": 0,
                         "upload_failures": 0, "syncs": 0, "adoptions": 0}

    def ensure(self, prefix: bytes, tail: bytes,
               protect: set[int] | None = None,
               lanes: int = 1) -> int | None:
        """Row index for (prefix, tail), inserting (and evicting LRU) as
        needed. None when the content cannot be resident: over CAP, or
        every evictable row is protected by the in-flight plan. `lanes`
        says for how many lanes of a batch the caller asks at once (the
        `hits` counter counts lanes)."""
        if len(prefix) + len(tail) > PREFIX_CAP:
            return None
        key = (bytes(prefix), bytes(tail))
        with self._lock:
            row = self._rows.get(key)
            if row is not None:
                self.counters["hits"] += lanes
                self._lru.pop(key, None)
                self._lru[key] = None  # refresh recency
                return row
            if len(self._rows) < TABLE_ROWS:
                row = len(self._rows)
            else:
                victim = None
                for k in self._lru:  # oldest first
                    r = self._rows[k]
                    if protect is None or r not in protect:
                        victim = k
                        break
                if victim is None:
                    return None
                row = self._rows.pop(victim)
                self._lru.pop(victim, None)
                self._row_key.pop(row, None)
                self.counters["evictions"] += 1
            self._rows[key] = row
            self._row_key[row] = key
            self._lru[key] = None
            self._host[row] = 0
            body = key[0] + key[1]
            self._host[row, :len(body)] = np.frombuffer(body, dtype=np.uint8)
            self.version += 1
            self._dirty[row] = self.version
            self.counters["inserts"] += 1
            self.counters["hits"] += lanes - 1
            return row

    def _empty(self):
        """The all-zero table, on this table's device (a mesh chip's
        replica) or JAX's default one."""
        import jax
        import jax.numpy as jnp

        zeros = jnp.zeros((TABLE_ROWS, PREFIX_CAP), dtype=jnp.uint8)
        if self._device is None:
            return zeros
        return jax.device_put(zeros, self._device)

    def sync(self):
        """Upload dirty rows (checksummed scatter, one retry), AWAITED,
        and return the device table snapshot, or None when the upload
        cannot be trusted (rows stay dirty; the batch takes the host
        path). The scatter runs where its committed argument, the table,
        lies. First use and overflow only: see carry()."""
        with self._lock:
            return self._sync_locked()

    def _sync_locked(self):
        dirty = sorted(self._dirty)
        if not dirty and self._tab is not None:
            return self._tab
        if not dirty:  # empty table, first use
            self._tab = self._empty()
            return self._tab
        db = _pow2(len(dirty))
        idx = np.full(db, dirty[-1], dtype=np.int32)
        idx[:len(dirty)] = dirty
        vals = self._host[idx]  # padding repeats the last row: idempotent
        base = self._tab
        if base is None:
            base = self._empty()
        want = _host_tab_chk(idx, vals)
        fn = _tab_scatter_fn(db)
        from cometbft_tpu.ops import residency as _residency

        count("table_rows_awaited", len(dirty))
        for _ in range(2):  # one retry on checksum mismatch
            new, chk = fn(base, idx, vals)
            _residency.record_send("delta", vals.nbytes + idx.nbytes)
            _residency.count_trip(programs=1, waits=1)  # int(chk) blocks
            if int(chk) == want:
                self._tab = new
                self._dirty.clear()
                self.counters["syncs"] += 1
                return self._tab
        self.counters["upload_failures"] += 1
        return None

    def carry(self, pad_row: int):
        """What a plan's wire block takes of the table, or None when no
        snapshot can be trusted: (snapshot, words, gens). snapshot is the
        last confirmed device table; words (CARRY_WORDS,) uint32 is a
        HOST copy of the dirty rows, CARRY_ROWS indices and then the rows'
        bytes as LE words, padded by repeating a row — the in-program
        scatter is idempotent; with nothing dirty, `pad_row` (a row the
        plan reads, clean, so the snapshot holds the same bytes) fills
        them. gens is {row: insert version} of the rows really carried,
        for adopt(). No wait and no program: only a table's first use,
        and more dirty rows than one block carries, go through sync()."""
        with self._lock:
            if self._tab is None or len(self._dirty) > CARRY_ROWS:
                if self._sync_locked() is None:
                    return None
            gens = dict(self._dirty)
            rows = sorted(gens) or [pad_row]
            words = np.empty(CARRY_WORDS, dtype=np.uint32)
            idx = words[:CARRY_ROWS]
            idx[:] = rows[-1]
            idx[:len(rows)] = rows
            words[CARRY_ROWS:] = self._host[idx].view(np.uint32).ravel()
            return self._tab, words, gens

    def adopt(self, snapshot, new_tab, gens: dict[int, int]) -> bool:
        """A batch that carried `gens` over `snapshot` resolved with its
        integrity intact: its derive's output is the confirmed table now,
        and the carried rows are clean unless re-used since. Not when the
        confirmed table has moved on meanwhile (another batch's adoption,
        a sync): the other snapshot may hold rows this output lacks, and
        whatever stays dirty is carried again."""
        with self._lock:
            if self._tab is not snapshot:
                return False
            self._tab = new_tab
            for row, gen in gens.items():
                if self._dirty.get(row) == gen:
                    del self._dirty[row]
            self.counters["adoptions"] += 1
            return True

    def stats(self) -> dict:
        with self._lock:
            return dict(self.counters, rows=len(self._rows),
                        capacity=TABLE_ROWS, version=self.version,
                        dirty=len(self._dirty),
                        # where the snapshot lies (None until synced)
                        devices=(sorted(str(d) for d in self._tab.devices())
                                 if self._tab is not None else None))


_tables_lock = threading.Lock()
_tables: dict[str, PrefixTable] = {}


def table(put_key: str = "", device=None) -> PrefixTable:
    with _tables_lock:
        t = _tables.get(put_key)
        if t is None:
            t = PrefixTable(put_key, device=device)
            _tables[put_key] = t
        return t


def table_stats() -> dict:
    with _tables_lock:
        return {k or "default": t.stats() for k, t in _tables.items()}


def invalidate(put_key: str) -> None:
    """Forget one replica (a mesh chip readmitted after a fault must not
    serve a snapshot from before it): the next plan builds it anew."""
    with _tables_lock:
        _tables.pop(put_key, None)


def reset() -> None:
    """Forget every table and counter (tests)."""
    with _tables_lock:
        _tables.clear()
    reset_stats()


# ------------------------------------------------------------ batch planning


class Plan:
    """One batch's device-challenge shape, frozen at plan time: the
    static message geometry the derive program compiles against, the
    per-lane descriptor assignment, and the table the in-flight batch
    gathers from: the immutable confirmed snapshot `dev_tab` with the
    plan's own copy of the dirty rows (`carry`, the block's tail;
    `n_carried` of the CARRY_ROWS are real, the rest padding) set over it
    in-program."""

    __slots__ = ("plen", "tlen", "var", "slen", "pids", "eligible",
                 "vbytes", "dev_tab", "carry", "n_carried", "n",
                 "n_eligible", "n_fallback", "put_key", "_table", "_gens")

    def __init__(self, *, plen, tlen, var, slen, pids, eligible, vbytes,
                 table, carried, n, n_eligible, n_fallback, put_key):
        self.plen = plen
        self.tlen = tlen
        self.var = var
        self.slen = slen
        self.pids = pids
        self.eligible = eligible
        self.vbytes = vbytes
        self._table = table
        self.dev_tab, self.carry, self._gens = carried
        self.n_carried = len(self._gens)
        self.n = n
        self.n_eligible = n_eligible
        self.n_fallback = n_fallback
        self.put_key = put_key

    def adopt(self, new_tab) -> bool:
        """The batch resolved with its integrity intact (its checksum
        covered the block, `carry` in it): hand the derive's table to the
        PrefixTable. Nothing to do for a plan that carried padding."""
        if not self._gens:
            return False
        return self._table.adopt(self.dev_tab, new_tab, self._gens)


def plan_batch(msgs, pre_ok, put_key: str = "", device=None) -> Plan | None:
    """Decide the degradation rung for one batch: a Plan when device
    challenge derivation wins (dominant (prefix-len, suffix-len) combo
    covers most live lanes, messages fit the static compile ladder, the
    challenge breaker admits, the table has a snapshot), else None — the
    caller stays on the bit-identical host-challenge path. Lanes outside the
    dominant combo or missing a table row become per-lane host
    fallbacks inside the Plan, never verdict changes."""
    n = len(msgs)
    if not _cfg["enabled"]:
        count("plan_disabled")
        return None
    if n < MIN_LANES:
        count("plan_small")
        return None
    from cometbft_tpu.ops import dispatch as _dispatch

    if not _dispatch.supervisor(SITE).breaker.peek():
        count("plan_breaker_open")
        return None
    from cometbft_tpu.libs.prefixrows import MsgBlock

    pre_ok = np.asarray(pre_ok, dtype=bool)
    # the messages as columns (libs/prefixrows.MsgBlock: a class of rows a
    # (prefix, suffix width); a list is turned into one by its lane loop,
    # a commit's rows arrive so): what follows reads index vectors and
    # matrices, and walks classes, never lanes
    msgs = MsgBlock.of(msgs)
    live = msgs.take(np.flatnonzero(pre_ok)).present()
    combos: dict[tuple[int, int], int] = {}
    for c, lanes in live:
        key = (len(msgs.fronts[c]), msgs.bodies[c].shape[1])
        combos[key] = combos.get(key, 0) + len(lanes)
    if not combos:
        count("plan_no_ok_lanes")
        return None
    (plen, slen), nc = max(combos.items(), key=lambda kv: kv[1])
    n_ok = int(pre_ok.sum())
    if plen + slen > MAX_MLEN or plen > PREFIX_CAP:
        count("plan_oversize")
        return None
    if nc < MIN_LANES or nc < MIN_ELIGIBLE_FRAC * n_ok:
        count("plan_low_eligibility")
        return None
    # the classes of the dominant geometry, and their live lanes
    conforming = [c for c, _ in live
                  if (len(msgs.fronts[c]), msgs.bodies[c].shape[1])
                  == (plen, slen)]
    conf = pre_ok & np.isin(msgs.cls, conforming)
    cidx = np.flatnonzero(conf)
    sfx = np.empty((len(cidx), slen), dtype=np.uint8)
    ccls = msgs.cls[cidx]
    for c in conforming:
        at = np.flatnonzero(ccls == c)
        sfx[at] = msgs.bodies[c][msgs.pos[cidx[at]]]
    if slen:
        # the batch-common trailing run (vote rows: the chain-id trailer
        # after the per-lane timestamp) rides the table row, not the wire
        eqcols = (sfx == sfx[0]).all(axis=0)
        tlen = 0
        for j in range(slen - 1, -1, -1):
            if not eqcols[j]:
                break
            tlen += 1
    else:
        tlen = 0
    tlen = min(tlen, PREFIX_CAP - plen)
    var = slen - tlen
    if var > MAX_VAR:
        count("plan_oversize_var")
        return None
    tail = sfx[0, slen - tlen:].tobytes() if tlen else b""
    tab = table(put_key, device=device)
    pids = np.full(n, -1, dtype=np.int32)
    protect: set[int] = set()
    misses = 0
    for c in conforming:  # in the order of each class's first live lane
        at = cidx[ccls == c]
        pid = tab.ensure(msgs.fronts[c], tail, protect=protect,
                         lanes=len(at))
        if pid is None:
            misses += len(at)
            continue
        protect.add(pid)
        pids[at] = pid
    if misses:
        count("lane_table_miss", misses)
    eligible = pids >= 0
    ne = int(eligible.sum())
    if ne < MIN_LANES or ne < MIN_ELIGIBLE_FRAC * n_ok:
        count("plan_low_eligibility")
        return None
    # no upload and no wait here: the dirty rows ride the wire block
    carried = tab.carry(int(pids[eligible][0]))
    if carried is None:
        count("plan_upload_failed")
        return None
    vbytes = np.zeros((n, var), dtype=np.uint8)
    if var:
        vbytes[cidx] = sfx[:, :var]
    count("plans")
    count("lanes_device", ne)
    count("lanes_host_fallback", n_ok - ne)
    return Plan(plen=plen, tlen=tlen, var=var, slen=slen, pids=pids,
                eligible=eligible, vbytes=vbytes, table=tab, carried=carried,
                n=n, n_eligible=ne, n_fallback=n_ok - ne, put_key=put_key)


# ------------------------------------------------------------- wire packing


def stream_words(bucket: int, var: int) -> int:
    """uint32 words of descriptor stream for a bucket: 2 descriptor
    bytes per lane plus `var` lane-contiguous suffix bytes per lane."""
    return (2 * bucket + var * bucket + 3) // 4


def block_words(bucket: int, var: int) -> int:
    """Total uint32 words of one flat device-challenge staging block:
    R words, s words, descriptor stream, the table's carried rows."""
    return 16 * bucket + stream_words(bucket, var) + CARRY_WORDS


def fill_stream(block: np.ndarray, bucket: int, plan: Plan) -> None:
    """Pack the descriptor stream of a leased flat block in place:
    per-lane uint16 LE descriptors (bit15 = derive-on-device, low 15
    bits = prefix-table row; 0 for padding/fallback lanes), then the
    lane-contiguous variable suffix bytes; and behind it the plan's
    carried table rows."""
    sw = stream_words(bucket, plan.var)
    block[16 * bucket + sw:] = plan.carry
    sb = block[16 * bucket:16 * bucket + sw].view(np.uint8)
    sb[:] = 0
    n = plan.n
    desc = sb[:2 * bucket].view("<u2")
    vals = np.zeros(n, dtype=np.uint16)
    el = plan.eligible
    vals[el] = (0x8000 | plan.pids[el]).astype(np.uint16)
    desc[:n] = vals
    if plan.var:
        v = sb[2 * bucket:2 * bucket + bucket * plan.var]
        v.reshape(bucket, plan.var)[:n] = plan.vbytes


# ----------------------------------------------------- the derive program


def _le_bytes(w):
    """(n,) uint32 words -> (4n,) uint8, their little-endian bytes."""
    import jax.numpy as jnp

    return jnp.stack([(w >> (8 * k)) & 0xFF for k in range(4)],
                     axis=-1).reshape(-1).astype(jnp.uint8)


def _words_to_bytes(w):
    """(8, B) uint32 LE words -> (B, 32) uint8 encodings (the inverse of
    limbs.bytes_to_words, on device)."""
    import jax.numpy as jnp

    wt = jnp.transpose(w)  # (B, 8)
    parts = jnp.stack([(wt >> (8 * k)) & 0xFF for k in range(4)], axis=-1)
    return parts.reshape(wt.shape[0], 32).astype(jnp.uint8)


@functools.lru_cache(maxsize=32)
def derive_fn(bucket: int, var: int, plen: int, tlen: int, fb: int):
    """Compiled derive program for one batch geometry: the first of the
    two programs of a batch's trip (ed25519_kernel.verify_batch_async),
    and the one its upload rides in on. Signature:

      run(flat, idx, tx, ty, tz, tt, te, ptab[, fkw, fidx])
          -> (rw, sw, kw, chk, ax, ay, az, at, ntab)

    flat   (block_words,) uint32 — the staged wire block (R words, s
           words, descriptor stream, the prefix table's carried rows),
           handed over as the HOST array: the call uploads it, un-awaited.
     idx   (bucket,) uint16 — the lanes' rows in the resident key table
           (residency.KeyTable.index), host array too.
    tx..tt (20, cap) int32 resident A-coordinate planes, te (8, cap)
           uint32 the resident pubkey-encoding words: the table's device
           snapshot. The lanes' rows are gathered here: encodings into
           the challenge preimage, coordinates out as ax..at.
    ptab   (TABLE_ROWS, PREFIX_CAP) uint8 — the Plan's confirmed table
           snapshot (never donated: batches in flight share it). The
           block's carried rows (Plan.carry: the dirty rows the device
           has not confirmed, a new height's) are set over it HERE, the
           lanes' prefixes are gathered from the result, and the result
           goes out as ntab, which the PrefixTable adopts once the batch
           resolved intact. A pure hit carries padding (a clean row's
           own bytes): ntab equals ptab.
     fkw   (8, fb) uint32 host-computed challenge words for fallback
           lanes, fidx (fb,) int32 their lane indices (padded with a
           repeated real index — the scatter is idempotent). fb == 0
           omits both.

    Everything whose shape follows the geometry ends HERE, so that the
    verify program behind it (the ladder: a minute of tracing and
    compiling a shape) is built per bucket and not per geometry: rw, sw
    are the block's (8, bucket) R and s planes, and chk is the
    position-weighted checksum of all this call uploaded (flat, fkw,
    fidx), which the verify program holds against the host's value
    (ed25519_kernel._integrity_parts_chk_expr): no lane is derived from
    a table row whose bytes no checksum covered.

    kw is zero for padding/fallback/ineligible lanes before the fkw
    scatter: padded lanes carry identity R / s=0 / k=0, which the verify
    grid accepts — preserving the all-ok happy-path header."""
    import jax
    import jax.numpy as jnp

    from cometbft_tpu.ops import ed25519_kernel as EK

    count("derive_programs")  # lru miss: a program for a new geometry
    tot = 64 + plen + var + tlen
    nb = (tot + 17 + 127) // 128
    padlen = nb * 128 - tot
    pad_np = np.zeros(padlen, dtype=np.uint8)
    pad_np[0] = 0x80
    pad_np[-16:] = np.frombuffer((tot * 8).to_bytes(16, "big"),
                                 dtype=np.uint8)
    sw = stream_words(bucket, var)

    def derive_challenge(flat, idx, tx, ty, tz, tt, te, ptab, *fk):
        with jax.named_scope("prefix_rows"):
            carry = flat[16 * bucket + sw:]
            ptab = ptab.at[carry[:CARRY_ROWS].astype(jnp.int32)].set(
                _le_bytes(carry[CARRY_ROWS:]).reshape(CARRY_ROWS, PREFIX_CAP))
        rows = idx.astype(jnp.int32)
        a_dev = tuple(jnp.take(c, rows, axis=1) for c in (tx, ty, tz, tt))
        aw = jnp.take(te, rows, axis=1)
        stream = flat[16 * bucket:16 * bucket + sw]
        sb = _le_bytes(stream)
        dlo = sb[0:2 * bucket:2].astype(jnp.uint32)
        dhi = sb[1:2 * bucket:2].astype(jnp.uint32)
        desc = dlo | (dhi << 8)
        use_dev = (desc >> 15).astype(jnp.uint32)
        pid = (desc & 0x7FFF).astype(jnp.int32)
        rw = flat[:8 * bucket].reshape(8, bucket)
        parts = [_words_to_bytes(rw), _words_to_bytes(aw)]
        if plen or tlen:
            row = ptab[pid]  # (bucket, PREFIX_CAP) gather off the snapshot
        if plen:
            parts.append(row[:, :plen])
        if var:
            offs = (2 * bucket
                    + jnp.arange(bucket, dtype=jnp.int32)[:, None] * var
                    + jnp.arange(var, dtype=jnp.int32)[None, :])
            parts.append(sb[offs])
        if tlen:
            parts.append(row[:, plen:plen + tlen])
        if padlen:
            parts.append(jnp.broadcast_to(jnp.asarray(pad_np),
                                          (bucket, padlen)))
        msg = jnp.concatenate(parts, axis=1)  # (bucket, nb*128)
        st = _compress_pairs(*_pairs_from_be_bytes(msg))
        with jax.named_scope("barrett_mod_l"):
            kw = _limbs_to_words(_barrett_mod_l(_state_to_limbs(st)))
        kw = kw * use_dev
        if fb:
            fkw, fidx = fk
            kw = kw.at[:, fidx].set(fkw)
        s_w = flat[8 * bucket:16 * bucket].reshape(8, bucket)
        with jax.named_scope("integrity"):
            chk = EK._device_checksum_expr((flat,) + fk)
        return (rw, s_w, kw, chk) + a_dev + (ptab,)

    return jax.jit(derive_challenge)
