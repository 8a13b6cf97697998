"""Batched Ed25519 ZIP-215 verification — the jitted device entry points.

Two kernels, split so decompressed validator pubkeys can be cached across
calls (the device-resident analog of the reference's LRU expanded-key cache,
crypto/ed25519/ed25519.go:44,63-69 — a validator set re-verifies every
height, but its keys decompress once):

  decompress(words)                  -> (ok, X, Y, Z, T)
  verify(A-coords, rW, sW, kW)       -> per-lane validity mask

verify computes, per lane:  [8]([s]B - [k]A - R) == O   (cofactored,
ZIP-215), via a signed 5-bit windowed double-scalar ladder (curve.py), one
add of -R, three doublings, and a projective identity test. The mask
pinpoints bad signatures directly; the few lanes it rejects are
double-checked against the host oracle before being reported (see
_recheck_failed_lanes — the narrow analog of the reference's
fallback-to-serial re-verify, types/validation.go:266).

Wire layout (the perf-critical design point): R / s / k cross the host link
as packed (8, B) uint32 words — 96 B per signature — and are unpacked to
limbs/digits on device (ops/unpack.py). Validator pubkey coordinates live
in a device-resident batch cache keyed by the pubkey-set digest, so the
steady-state commit-verification path transfers ~1 MB per 10k-signature
batch instead of ~25 MB.

Batch sizes are bucketed to powers of two (min 8) to bound recompilation;
padding lanes carry the identity encoding (y=1) with zero scalars, which
verify as valid and are sliced off.
"""

from __future__ import annotations

import functools
import hashlib
import threading
import time as _time

import jax
import jax.numpy as jnp
import numpy as np

from cometbft_tpu.crypto import ed25519_math as oracle
from cometbft_tpu.libs import linkmodel as _linkmodel
from cometbft_tpu.libs import trace as _trace
from cometbft_tpu.ops import curve
from cometbft_tpu.ops import limbs as L
from cometbft_tpu.ops import residency as _residency
from cometbft_tpu.ops import unpack as U

MIN_BUCKET = 8
MAX_BUCKET_LOG2 = 17  # 128k lanes


def _staging_rung() -> str:
    """hashvec rung label for staging trace spans (never raises)."""
    try:
        from cometbft_tpu.ops import hashvec

        return hashvec.active_rung()
    except Exception:  # noqa: BLE001 - tracing must never break staging
        return "unknown"

_ID_ENC32 = (1).to_bytes(32, "little")  # y=1: the identity point encoding


def warmup_rows(b: int) -> tuple[list, list, list]:
    """(pubs, msgs, sigs) of b identity-point rows for a warm-up batch:
    pub = the identity encoding, s = 0 — structurally valid, decompress
    trivially, verify cheap (the scheduler's and the mesh's warm-ups)."""
    return ([_ID_ENC32] * b, [b"sched-warmup"] * b,
            [_ID_ENC32 + b"\x00" * 32] * b)

_default_dev_id: int | None = None


def default_device_index() -> int:
    """Index of the chip the single-chip dispatch path targets — stamped
    on dispatch trace spans so a flight-recorder tree names its fault
    domain even off the mesh path (the mesh stamps its own shard index)."""
    global _default_dev_id
    if _default_dev_id is None:
        try:
            _default_dev_id = int(jax.devices()[0].id)
        except Exception:  # noqa: BLE001 - tracing must never break dispatch
            _default_dev_id = 0
    return _default_dev_id


class Target:
    """Where a batch's trip runs. The default: JAX's first device, the
    process-wide key and prefix tables, the "device" supervisor, and every
    device fault resolved on the host oracle inside the thunk. A mesh shard
    (parallel/mesh.py) aims the same trip at one chip: that chip's device,
    its replicas of the tables (put_key "devN"), its own supervisor and
    in-flight gate, its chaos site beside the scheme's, and `strict`:
    device trouble is raised to the mesh, which redispatches the shard
    over the surviving chips."""

    __slots__ = ("device", "index", "put_key", "supervisor", "strict")

    def __init__(self, device=None, index: int | None = None,
                 put_key: str = "", supervisor: str = "device",
                 strict: bool = False):
        self.device = device
        self.index = default_device_index() if index is None else index
        self.put_key = put_key
        self.supervisor = supervisor
        self.strict = strict


_POW2_CAP = 2048  # above this, buckets are multiples of _POW2_CAP


def bucket_size(n: int) -> int:
    """Power-of-two buckets up to 2048, then multiples of 2048: bounds the
    number of compiled shapes (9 + 63) while capping padding waste at 20%
    for large batches (a 10240-sig mega-commit runs at exactly 10240 lanes,
    not 16384)."""
    if n > (1 << MAX_BUCKET_LOG2):
        raise ValueError(f"batch of {n} exceeds max bucket {1 << MAX_BUCKET_LOG2}")
    b = MIN_BUCKET
    while b < n and b < _POW2_CAP:
        b *= 2
    if b >= n:
        return b
    return (n + _POW2_CAP - 1) // _POW2_CAP * _POW2_CAP


@jax.jit
def _decompress_kernel(words: jnp.ndarray):
    """(8, B) uint32 packed encodings -> (ok, X, Y, Z, T) each (20, B)."""
    with jax.named_scope("decompress"):
        y = U.words_to_y_limbs(words)
        sign = U.words_sign(words)
        ok, p = curve.decompress_zip215(y, sign)
    return ok, p.x, p.y, p.z, p.t


def verify_math(ax, ay, az, at, r_words, s_words, k_words) -> jnp.ndarray:
    """The per-chip verify program (also the shard_map body, parallel/mesh).
    A-coords (20, B) int32; r/s/k packed (8, B) uint32. Lanes whose pubkey
    failed decompression produce garbage — the caller masks with ok_a."""
    y_r = U.words_to_y_limbs(r_words)
    sign_r = U.words_sign(r_words)
    ok_r, r = curve.decompress_zip215(y_r, sign_r)
    neg_a = curve.neg(curve.Point(ax, ay, az, at))
    sb_ka = curve.windowed_double_scalar_signed(
        U.words_to_digits5_signed(s_words), U.words_to_digits5_signed(k_words), neg_a
    )
    diff = curve.add(sb_ka, curve.neg(r))
    valid = curve.is_identity(curve.mul_by_cofactor(diff))
    return valid & ok_r


_verify_kernel = jax.jit(verify_math)


def verify_math_ok(ax, ay, az, at, r_words, s_words, k_words):
    """verify_math plus the device-side all-ok reduction the reduced-fetch
    header rides on (padding lanes carry the identity encoding and verify
    valid, so all() over the padded batch equals all() over the live
    lanes). XLA counterpart of pallas_verify.verify_pallas_ok."""
    mask = verify_math(ax, ay, az, at, r_words, s_words, k_words)
    return mask, mask.all()


# Pallas path: the fused-VMEM ladder (pallas_verify.py) is ~2.5x the
# XLA-compiled program on real TPU (HBM-bound vs VMEM-resident). Enabled
# for TPU backends on lane-aligned buckets; CPU (tests) and small buckets
# use the XLA program. CBFT_NO_PALLAS=1 forces the XLA path.
_use_pallas: bool | None = None


def _pallas_available() -> bool:
    global _use_pallas
    if _use_pallas is None:
        import os

        _use_pallas = (
            os.environ.get("CBFT_NO_PALLAS") != "1"
            and jax.devices()[0].platform == "tpu"
        )
    return _use_pallas


# Serializes jit dispatch (and therefore tracing) across ALL curve kernels
# and threads — see ops/dispatch.py for why the Pallas constant swap makes
# this mandatory.
from cometbft_tpu.ops.dispatch import KERNEL_DISPATCH_LOCK as _dispatch_lock

# ---------------------------------------------------------------------------
# Transfer integrity. The reference trusts in-process memory
# (types/validation.go:235); a device on the far side of a host link
# must earn that trust explicitly:
#   host->device: a position-weighted checksum of the staged r/s/k words is
#     recomputed ON DEVICE and compared to the host's value; the verdict
#     rides back inside the verify payload (no extra round trip).
#   device->host: the mask travels twice (mask + bitwise complement); an
#     echo mismatch flags fetch-path corruption.
# A failed check is counted, logged, retried once with a fresh transfer,
# and — if still failing — the batch falls back to the exact host oracle,
# so corruption is *detected and contained*, never silently tolerated.
# ---------------------------------------------------------------------------

_CHK_MULT = np.uint64(2654435761)  # Knuth multiplicative-hash odd constant


def _host_checksum(*arrs: np.ndarray) -> int:
    """Position-weighted sum mod 2^32 over the arrays' uint32 views, in
    ravel order — bit-identical to _device_checksum."""
    acc = 0
    off = 0
    for a in arrs:
        flat = np.ascontiguousarray(a).view(np.uint32).ravel().astype(np.uint64)
        idx = np.arange(off, off + flat.size, dtype=np.uint64)
        w = (idx * _CHK_MULT + 1) & 0xFFFFFFFF
        acc = (acc + int(((flat * w) & 0xFFFFFFFF).sum() & 0xFFFFFFFF)) & 0xFFFFFFFF
        off += flat.size
    return acc


def _device_checksum_expr(arrs) -> jnp.ndarray:
    """The device-side mirror of _host_checksum (traced inside the payload
    jit)."""
    acc = jnp.uint32(0)
    off = 0
    for a in arrs:
        if a.dtype == jnp.int32:
            flat = jax.lax.bitcast_convert_type(a, jnp.uint32).ravel()
        else:
            flat = a.astype(jnp.uint32).ravel()
        idx = jax.lax.iota(jnp.uint32, flat.size) + jnp.uint32(off)
        w = idx * jnp.uint32(2654435761) + jnp.uint32(1)
        acc = acc + (flat * w).sum(dtype=jnp.uint32)
        off += flat.size
    return acc


_device_checksum = jax.jit(_device_checksum_expr)


# ---------------------------------------------------------------------------
# Reduced-fetch protocol. The happy-path mask fetch used to pull the full
# (2B+1,) payload — ~20 KB and a full link RTT for bytes that are almost
# always all-true. The kernels now additionally emit a (2,) uint32 HEADER
# folding the all-ok verdict into the staging checksum:
#
#   token = device_checksum ^ (OK_MAGIC if every lane verified AND the
#           staged bytes checksummed else BAD_MAGIC);   header = [token, ~token]
#
# The host knows the expected checksum, so 8 fetched bytes prove "staged
# bytes arrived intact and every lane verified" — the full per-lane payload
# is pulled only when the header says otherwise (a failing lane, a staging
# checksum mismatch, or a mangled header fetch, each distinguished by
# decode_header). The complement echo gives the header the same
# corruption-detection plane as the full mask fetch; a corrupted header
# degrades to the full fetch, never to a wrong verdict.
# ---------------------------------------------------------------------------

OK_MAGIC = np.uint32(0x600DFA57)
_BAD_MAGIC = np.uint32(~0x600DFA57 & 0xFFFFFFFF)


def _integrity_parts_chk_expr(mask, allok, chk, expected):
    """-> ((2,) uint32 reduced-fetch header, (2B+1,) bool full payload
    [mask, ~mask (echo), staging-checksum ok]) from the device's checksum
    of the staged words and the host's."""
    with jax.named_scope("integrity"):
        ok = chk == expected.astype(jnp.uint32)
        payload = jnp.concatenate([mask, ~mask, ok[None]])
        tok = chk ^ jnp.where(allok & ok, OK_MAGIC, _BAD_MAGIC)
        return jnp.stack([tok, ~tok]), payload


def _integrity_parts_arrs_expr(mask, allok, expected, *arrs):
    """_integrity_parts_chk_expr with the checksum taken here, over an
    arbitrary array set: three r/s/k planes, or the device-challenge
    wire's flat block (+ the fallback-k scatter arrays). On the
    device-challenge path the two halves run in the batch's two programs
    (the checksum where the geometry's shapes end, challenge.derive_fn;
    header and payload with the ladder, _verify_programs)."""
    with jax.named_scope("integrity"):
        chk = _device_checksum_expr(arrs)
    return _integrity_parts_chk_expr(mask, allok, chk, expected)


def _integrity_parts_expr(mask, allok, rw, sw, kw, expected):
    return _integrity_parts_arrs_expr(mask, allok, expected, rw, sw, kw)


# NOT donated: the header/payload outputs are tiny (2 words + 2B+1
# bools), so no donated staged-word buffer could ever be reused for an
# output — XLA would warn "donated buffers were not usable" on every
# batch and copy anyway. Device-buffer recycling comes instead from the
# staged block dying with the dispatch closure (one (3,8,B) array per
# in-flight batch, freed at resolution) and the host-side StagingPool
# reuse underneath it.
_integrity_parts = jax.jit(_integrity_parts_expr)


class _LateExpected:
    """Host staging checksum resolved ON THE TRANSFER POOL: the
    device-challenge dispatch closure picks its degradation rung (device
    derive vs host-batch k) inside the closure, and each rung checksums a
    different array set — so the expected value decode_header compares
    against is a cell the closure fills before the header can be fetched
    (the same late-binding contract as _LateOkA). int(cell) is what
    decode_header and resolve_batches consume."""

    __slots__ = ("value",)

    def __init__(self, value: int):
        self.value = value

    def __int__(self) -> int:
        return int(self.value)


def decode_header(header: np.ndarray, expected) -> str:
    """Header verdicts: "happy" (staging intact, every lane valid — the
    per-lane mask need not cross the link), "full" (device and staging
    fine, some lane failed: pull the mask), "chk_mismatch" (the device saw
    different staged bytes than the host sent), "echo_corrupt" (the header
    itself was mangled on the fetch — its complement disagreed)."""
    h0, h1 = int(header[0]), int(header[1])
    if h1 != (~h0 & 0xFFFFFFFF):
        return "echo_corrupt"
    exp = int(expected)
    if h0 == exp ^ int(OK_MAGIC):
        return "happy"
    if h0 == exp ^ int(_BAD_MAGIC):
        return "full"
    return "chk_mismatch"


_INTACT = ("happy", "full")  # verdicts of a header whose checksum matched


# happy/full fetch accounting (bench emits fetch_bytes_happy_path from
# this; crypto_health surfaces it next to the hashvec rung counters)
_fetch_lock = threading.Lock()
_fetch_stats = {"happy_fetches": 0, "full_fetches": 0,
                "happy_bytes": 0, "full_bytes": 0}


def _count_fetch(happy: bool, nbytes: int) -> None:
    key = "happy" if happy else "full"
    with _fetch_lock:
        _fetch_stats[key + "_fetches"] += 1
        _fetch_stats[key + "_bytes"] += nbytes
    try:
        from cometbft_tpu.libs import metrics as _metrics

        cm = _metrics.crypto_metrics()
        cm.verify_fetches.labels(key).inc()
        cm.verify_fetch_bytes.labels(key).inc(nbytes)
    except Exception:  # noqa: BLE001 - metrics must never break verification
        pass


def fetch_stats() -> dict:
    with _fetch_lock:
        return dict(_fetch_stats)


def reset_fetch_stats() -> None:
    with _fetch_lock:
        for k in _fetch_stats:
            _fetch_stats[k] = 0


def _row_bytes(row):
    """A signature a host oracle reads: the bytes of a matrix's row (rows
    kept as columns, libs/rowblock.py), anything else as it is."""
    return row.tobytes() if isinstance(row, np.ndarray) else row


def host_oracle_mask(n, pre_ok, ok_a, rows, info) -> np.ndarray:
    """The CPU rung of the verify ladder: the scheme's exact host oracle
    over the batch rows. Counts the lanes as fallback verifies."""
    from cometbft_tpu.libs.prefixrows import as_bytes

    verify_fn = info[0]
    ok_a = _ok_arr(ok_a)  # may be a _LateOkA cell (pooled pubkey staging)
    pubs, msgs, sigs = rows
    with _trace.span("host_oracle", cat="compute", scheme=info[1], rows=n):
        host = np.fromiter(
            (verify_fn(p, as_bytes(m), _row_bytes(s))
             for p, m, s in zip(pubs, msgs, sigs)),
            dtype=bool, count=n)
    _count_fallback(info[1], n)
    return host & pre_ok & ok_a


def decode_payload(payload: np.ndarray, n, pre_ok, ok_a, rows, info,
                   redo=None) -> np.ndarray:
    """Validate the integrity payload and produce the final (N,) mask.
    On checksum/echo failure: count, log, retry once with a fresh transfer
    (redo), then fall back to the exact host oracle for the whole batch."""
    ok_a = _ok_arr(ok_a)  # may be a _LateOkA cell (pooled pubkey staging)
    b = (payload.shape[0] - 1) // 2
    mask = payload[:b].copy()
    echo = payload[b:2 * b]
    chk_ok = bool(payload[2 * b])
    echo_ok = bool((mask != echo).all())  # echo is the complement
    if not (chk_ok and echo_ok):
        from cometbft_tpu.libs import log as _log

        _count_integrity(
            "transfer_checksum_mismatch" if not chk_ok else "mask_echo_mismatch")
        _log.default().error(
            "device transfer integrity check failed",
            scheme=info[1], staging_checksum_ok=str(chk_ok),
            mask_echo_ok=str(echo_ok),
            action="retry" if redo is not None else "host-oracle fallback")
        if redo is not None:
            try:
                fresh = np.asarray(redo())
            except Exception:  # noqa: BLE001 - device died during the retry
                fresh = None
            if fresh is not None:
                return decode_payload(
                    fresh, n, pre_ok, ok_a, rows, info, redo=None)
        return host_oracle_mask(n, pre_ok, ok_a, rows, info)
    mask = mask[:n] & pre_ok & ok_a
    return apply_recheck(mask, pre_ok & ok_a, rows, info)


def _count_integrity(kind: str, n: int = 1) -> None:
    try:
        from cometbft_tpu.libs import metrics as _metrics

        getattr(_metrics.crypto_metrics(), kind).inc(n)
    except Exception:  # noqa: BLE001 - metrics must never break verification
        pass


def _count_fallback(scheme: str, n: int) -> None:
    """Count lanes that fell off the device onto the CPU ladder."""
    try:
        from cometbft_tpu.libs import metrics as _metrics

        _metrics.crypto_metrics().fallback_verifies.labels(scheme).inc(n)
    except Exception:  # noqa: BLE001
        pass


def _count_device_batch(scheme: str, lanes: int) -> None:
    """Count a successfully dispatched device batch (the TPU-path-is-alive
    signal the chaos tests assert on)."""
    try:
        from cometbft_tpu.libs import metrics as _metrics

        cm = _metrics.crypto_metrics()
        cm.device_batches.labels(scheme).inc()
        cm.device_lanes.labels(scheme).inc(lanes)
    except Exception:  # noqa: BLE001
        pass


from cometbft_tpu.ops import dispatch as _dispatch
from cometbft_tpu.ops.dispatch import PallasGate

_pallas_gate = PallasGate("pallas.ed25519")


# Device trace-count instrumentation: every lane count dispatched this
# process is a shape XLA/Pallas compiled a program for. The scheduler's
# bucket soak asserts len(dispatched_shapes()) stays <= the bucket-ladder
# length — continuous batching must bound compilation, not multiply it.
_dispatched_shapes: set[int] = set()


def dispatched_shapes() -> list[int]:
    return sorted(_dispatched_shapes)


def reset_shape_log() -> None:
    _dispatched_shapes.clear()


@functools.lru_cache(maxsize=8)
def _verify_programs(hostk: bool, ladder=None):
    """The verify program of a batch's trip as the PallasGate's
    (pallas_fn, xla_fn) couple: the ladder and the integrity header and
    payload in ONE program, -> ((2,) header, (2B+1,) payload), built per
    bucket (and instantiated per device the trip is aimed at). The Pallas
    one keeps `verify_pallas` in its name (the benchmark's roofline reader
    finds the module by it). `ladder` stands in for the curve math of
    both rungs, (ax, ay, az, at, rw, sw, kw) -> (mask, allok): the mesh
    tests' seam (VerifyMesh._scheme_ops()["kernel"]).

    hostk=False, after a device derive (challenge.derive_fn), all
    arguments its outputs but the host's checksum:
      (ax, ay, az, at, rw, sw, kw, chk, expected).
    hostk=True, the one program of a host-challenge batch, so the gather
    from the key table and the checksum are in it too:
      (idx, tx, ty, tz, tt, words, expected), words the (3, 8, B) r/s/k
      block."""
    from cometbft_tpu.ops import pallas_verify as PV

    def build(ladder, rung: str):
        def derived(ax, ay, az, at, rw, sw, kw, chk, expected):
            mask, allok = ladder(ax, ay, az, at, rw, sw, kw)
            return _integrity_parts_chk_expr(mask, allok, chk, expected)

        def hostk_(idx, tx, ty, tz, tt, words, expected):
            a = tuple(jnp.take(c, idx, axis=1) for c in (tx, ty, tz, tt))
            mask, allok = ladder(*a, words[0], words[1], words[2])
            return _integrity_parts_arrs_expr(mask, allok, expected, words)

        fn = hostk_ if hostk else derived
        fn.__name__ = f"verify_{rung}_{'hostk' if hostk else 'derived'}"
        return jax.jit(fn)

    # both ladders un-jitted: no program of the trip nests a jit
    return (build(ladder or PV.verify_pallas_ok_traced, "pallas"),
            build(ladder or verify_math_ok, "xla"))


def _dispatch_verify(hostk: bool, args: tuple, lanes: int, ladder=None):
    """-> ((2,) header, (2B+1,) payload), both device-resident, on the
    device the committed arrays among args lie on. Host arrays among args
    are uploaded by the call, un-awaited."""
    pallas_fn, xla_fn = _verify_programs(hostk, ladder)
    _dispatched_shapes.add(lanes)
    with _dispatch_lock:
        parts = _pallas_gate.run(pallas_fn, xla_fn, args, lanes)
    _residency.count_trip(programs=1)
    return parts


def _dispatch_hostk(idx, planes, words, expected, path: str, sigs: int,
                    target: Target, ladder=None):
    """The ONE program of a host-challenge batch: the index vector and the
    whole (3, 8, B) r/s/k block go up as its arguments, un-awaited;
    gather, ladder, checksum and integrity run inside it. Nothing waits
    for the upload: the program is ordered behind it on the device; a
    leased block stays leased until the batch resolves, and the runtime
    holds its own reference to a host argument while it reads it."""
    b = words.shape[2]
    with _trace.span("ed25519.dispatch", cat="compute", lanes=b,
                     device=target.index) as sp:
        parts = _dispatch_verify(True, (idx, *planes, words, expected), b,
                                 ladder)
        nbytes = idx.nbytes + words.nbytes
        sp.add_bytes(tx=nbytes)
    _residency.record_send(path, nbytes, sigs=sigs)
    return parts


def decompress_points(enc: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(N, 32) uint8 encodings -> (ok (N,) bool, coords (N, 4, 20) int32),
    padding internally to a bucket. Host-facing; used to fill the pubkey
    cache and by tests. Device arrays are limb-axis-first (20, B); the host
    cache keeps batch-major (N, 4, 20) for cheap per-key gathers."""
    n = enc.shape[0]
    b = bucket_size(n)
    words = L.bytes_to_words(enc)
    if b > n:
        pad = np.zeros((b - n, 8), dtype=np.uint32)
        pad[:, 0] = 1  # y = 1: the identity point, always decompressible
        words = np.concatenate([words, pad])
    with _dispatch_lock:
        ok, x, yy, z, t = _decompress_kernel(jnp.asarray(words.T))
    coords = np.stack(
        [np.asarray(x).T, np.asarray(yy).T, np.asarray(z).T, np.asarray(t).T], axis=1
    )
    return np.asarray(ok)[:n], coords[:n]


def pad_coords_batch_minor(coords: np.ndarray, bucket: int) -> tuple:
    """(N, 4, 20) int32 coords -> identity-padded, batch-minor
    (ax, ay, az, at) host arrays, each (20, bucket). THE one place the
    identity-point pad encoding (Y=1, Z=1) and the device layout
    transpose live — PubKeyCache.stage and the mesh's direct staging
    path share it."""
    pad = bucket - coords.shape[0]
    if pad:
        id_coords = np.zeros((pad, 4, L.NLIMBS), dtype=np.int32)
        id_coords[:, 1, 0] = 1  # Y = 1
        id_coords[:, 2, 0] = 1  # Z = 1
        coords = np.concatenate([coords, id_coords])
    return tuple(np.ascontiguousarray(coords[:, i].T) for i in range(4))


class PubKeyCache:
    """Two-level decompressed-pubkey cache.

    Host level: pubkey bytes -> (ok, (4, 20) int32 coords), bounded FIFO —
    absorbs validator-set churn and partial overlap between batches.
    Device level: digest of the padded pubkey batch -> coords already
    resident on device as (20, B) arrays — the steady-state hit for commit
    verification, where the same validator set re-verifies every height and
    the A-coordinate upload (3.3 MB at 10k lanes) drops to zero.
    """

    # subclasses (sr25519) swap in their scheme's device decompressor;
    # staticmethod so instances share one slot
    _decompress = staticmethod(lambda enc: decompress_points(enc))
    # scheme tag consumed by the reduced-send residency layer
    # (ops/residency.py) to key device validator tables per scheme
    scheme = "ed25519"

    def __init__(self, capacity: int = 65536, device_slots: int = 8):
        self.capacity = capacity
        self.device_slots = device_slots
        # reentrant (stage -> lookup_or_decompress): the cache is shared
        # by scheduler inline drains, blocksync staging threads, and mesh
        # shard workers — a concurrent FIFO eviction racing a reader must
        # not KeyError an honest batch onto the fallback ladder
        self._tlock = threading.RLock()
        self._map: dict[bytes, tuple[bool, np.ndarray]] = {}
        self._dev: dict[bytes, tuple] = {}
        # hit/miss/eviction counters per level (host bytes->coords FIFO vs
        # device-resident digest slots), mirrored onto /metrics
        # (crypto_pubkey_cache_events) and the crypto_health RPC section
        self.counters = {
            "host_hits": 0, "host_misses": 0, "host_evictions": 0,
            "device_hits": 0, "device_misses": 0, "device_evictions": 0,
        }

    def _count(self, level: str, event: str, n: int = 1) -> None:
        self.counters[f"{level}_{event}"] += n
        try:
            from cometbft_tpu.libs import metrics as _metrics

            _metrics.crypto_metrics().pubkey_cache_events.labels(
                level, event).inc(n)
        except Exception:  # noqa: BLE001 - metrics must never break staging
            pass

    def stats(self) -> dict:
        return dict(self.counters,
                    host_entries=len(self._map), device_slots=len(self._dev))

    def lookup_or_decompress(self, pubs: list[bytes]) -> tuple[np.ndarray, np.ndarray]:
        """Host-level: (ok (N,) bool, coords (N, 4, 20) int32)."""
        with self._tlock:
            return self._lookup_locked(pubs)

    def _lookup_locked(self, pubs: list[bytes]) -> tuple[np.ndarray, np.ndarray]:
        uniq = dict.fromkeys(pubs)
        missing = [p for p in uniq if p not in self._map]
        self._count("host", "misses", len(missing))
        self._count("host", "hits", len(uniq) - len(missing))
        if missing:
            enc = np.frombuffer(b"".join(missing), dtype=np.uint8).reshape(-1, 32)
            ok, coords = self._decompress(enc)
            evict = min(len(self._map), len(self._map) + len(missing) - self.capacity)
            for _ in range(max(0, evict)):
                self._map.pop(next(iter(self._map)))
            if evict > 0:
                self._count("host", "evictions", evict)
            for i, p in enumerate(missing):
                self._map[p] = (bool(ok[i]), coords[i])
        oks = np.empty(len(pubs), dtype=bool)
        coords = np.empty((len(pubs), 4, L.NLIMBS), dtype=np.int32)
        for i, p in enumerate(pubs):
            o, c = self._map[p]
            oks[i] = o
            coords[i] = c
        return oks, coords

    def stage(
        self, pubs: list[bytes], bucket: int, put=None, put_key: str = ""
    ) -> tuple[np.ndarray, tuple]:
        """(ok_a (N,) host bool, (ax, ay, az, at) device arrays (20, bucket)).
        `put` overrides jax.device_put (the mesh path passes a sharded put;
        put_key disambiguates cache entries across shardings/meshes).
        Serialized on the cache lock: a device-level miss pays its
        checksummed upload under it, which is the price of never caching a
        half-written entry a concurrent stager could read."""
        with self._tlock:
            return self._stage_locked(pubs, bucket, put, put_key)

    def _stage_locked(self, pubs, bucket, put, put_key):
        digest = hashlib.sha256(put_key.encode() + b"".join(pubs)).digest() + bytes(
            [bucket.bit_length()]
        )
        hit = self._dev.get(digest)
        if hit is not None:
            self._count("device", "hits")
            return hit[0], hit[1]
        self._count("device", "misses")
        ok_a, coords = self.lookup_or_decompress(pubs)
        put = put or jax.device_put
        host_arrs = pad_coords_batch_minor(coords, bucket)
        expected = _host_checksum(*host_arrs)
        dev = None
        for attempt in (1, 2):
            t0 = _time.perf_counter()
            dev = tuple(put(a) for a in host_arrs)
            # block before t1 (async dispatch would record enqueue time,
            # not wire time); the checksum read below forces residency
            # immediately after anyway
            jax.block_until_ready(dev)
            # coordinate-table upload bytes (per attempt: a retry really
            # re-crosses the wire) against the enclosing transfer span
            nbytes = sum(a.nbytes for a in host_arrs)
            _linkmodel.link().observe_transfer(
                nbytes, _time.perf_counter() - t0)
            _trace.add_bytes(tx=nbytes)
            # full-key-path wire accounting: the coordinate-table upload
            # the reduced-send residency exists to amortize away
            _residency.record_send("full", nbytes)
            # upload-time integrity check: a corrupted coordinate table
            # would poison EVERY batch against this valset until eviction,
            # so the one extra round trip per cache miss is paid here
            got = int(np.asarray(_device_checksum(dev)))
            _residency.count_trip(programs=1, waits=2)
            if got == expected:
                break
            _count_integrity("transfer_checksum_mismatch")
            from cometbft_tpu.libs import log as _log

            _log.default().error(
                "pubkey coordinate upload failed integrity check",
                attempt=str(attempt))
            if attempt == 2:
                raise RuntimeError(
                    "pubkey coordinate upload corrupted twice; refusing to "
                    "cache a poisoned table")
        if len(self._dev) >= self.device_slots:
            self._dev.pop(next(iter(self._dev)))
            self._count("device", "evictions")
        self._dev[digest] = (ok_a, dev)
        return ok_a, dev


@jax.jit
def _gather_coords(dev_u, idx):
    """Device-side gather: unique-pubkey coordinate table (20, U) -> per-lane
    A-coordinates (20, B). Runs as a plain XLA op enqueued before the verify
    kernel — no host round trip."""
    return tuple(jnp.take(c, idx, axis=1) for c in dev_u)


def _full_key_index(cache: "PubKeyCache", pubs: list[bytes], bucket: int,
                    put_key: str = "", device=None) -> tuple:
    """The full-key path's table and index: (ok_a (N,), idx (bucket,)
    int32, (ax, ay, az, at) device planes of the batch's UNIQUE keys). A
    batch that repeats a validator set W times (the coalesced blocksync
    window) uploads ONE copy of the coordinates (digest-cached across
    windows, since the unique set is stable even when window composition
    changes) plus a 4-byte/lane index vector — not W copies keyed on the
    exact concatenation."""
    uniq = list(dict.fromkeys(pubs))
    # an identity pad slot is needed only when padding lanes exist; when the
    # batch fills its bucket exactly (n == bucket == cap is legal) the +1
    # would overflow the lane cap
    need_pad = bucket > len(pubs)
    bu = bucket_size(len(uniq) + 1 if need_pad else len(uniq))
    put = None
    if device is not None:
        put = functools.partial(jax.device_put, device=device)
    ok_u, dev_u = cache.stage(uniq, bu, put=put, put_key=put_key)
    pos = {p: i for i, p in enumerate(uniq)}
    idx = np.full(bucket, len(uniq), dtype=np.int32)  # padding -> identity
    idx[: len(pubs)] = [pos[p] for p in pubs]
    return np.asarray(ok_u)[idx[: len(pubs)]], idx, dev_u


def _stage_index(cache: "PubKeyCache", pubs: list[bytes],
                 bucket: int, put_key: str = "", device=None) -> tuple:
    """Pubkey staging for a batch whose program gathers for itself:
    (ok_a (N,), idx host index vector (bucket,), (tx, ty, tz, tt)
    device coordinate planes to gather from, te the resident
    pubkey-encoding words or None, send path). Nothing is uploaded and
    no program runs here: idx rides the batch's first program as an
    argument.

    Indexed path first (ops/residency.py): when the batch's keys fit the
    device-resident validator table, the wire carries a 2-byte uint16
    row index per lane (unseen keys delta-insert, counted separately) —
    the reduced-send steady state. path="indexed". Only the residency
    tables keep raw key bytes on device; a None te is one of the
    device-challenge degradation rungs (non-resident A).

    Full-key path otherwise (_full_key_index). path="full".

    put_key / device: the chip's own replica of the table where the trip
    is aimed at one (Target)."""
    got = _residency.index(cache, pubs, bucket, put_key=put_key,
                           device=device)
    if got is not None:
        ok_a, idx, dev = got
        return ok_a, idx, dev[:4], dev[4], "indexed"
    ok_a, idx, dev_u = _full_key_index(cache, pubs, bucket, put_key, device)
    return ok_a, idx, dev_u, None, "full"


def _stage_gather(cache: "PubKeyCache", pubs: list[bytes], bucket: int,
                  put_key: str = "", device=None) -> tuple:
    """_stage_index with the gather as a program of its own, for the
    callers whose verify program takes gathered coordinates (sr25519,
    the mesh's shards): (ok_a (N,), (ax, ay, az, at) device arrays
    (20, bucket), send path, pubkey-staging wire bytes). The index
    vector goes up un-awaited; the gather is ordered behind it.

    `device` targets a specific chip (the mesh path stages each shard's
    coordinate table on its own fault domain; put_key must then carry the
    chip index so cache/table entries never alias across devices)."""
    got = _residency.stage(cache, pubs, bucket, put_key=put_key,
                           device=device)
    if got is not None:
        ok_a, a_dev, staging_tx = got
        return ok_a, a_dev, "indexed", staging_tx
    ok_a, idx, dev_u = _full_key_index(cache, pubs, bucket, put_key, device)
    idx_dev = (jax.device_put(idx) if device is None
               else jax.device_put(idx, device))
    _trace.add_bytes(tx=idx.nbytes)
    a_dev = _gather_coords(dev_u, idx_dev)
    _residency.count_trip(programs=1)
    return ok_a, a_dev, "full", idx.nbytes


_default_cache = PubKeyCache()


def cache_stats() -> dict:
    """Default PubKeyCache counters per scheme — the crypto_health RPC's
    pubkey_cache section (next to verify_sched)."""
    out = {"ed25519": _default_cache.stats()}
    try:
        from cometbft_tpu.ops import sr25519_kernel as SRK

        out["sr25519"] = SRK._default_cache.stats()
    except Exception:  # noqa: BLE001 - sr kernel may be unimportable (deps)
        pass
    return out


def compute_challenges(pubs: list[bytes], msgs: list[bytes], sigs: list[bytes]) -> list[int]:
    """k_i = SHA-512(R_i || A_i || M_i) mod L — host-side (SHA-512 is 64-bit
    word arithmetic, hostile to the TPU VPU). Batch-vectorized via
    ops/hashvec (lane-SIMD native core / batch-axis numpy / hashlib rung
    ladder, bit-for-bit hashlib); this list[int] entry is the compat shim —
    the staging path consumes packed words directly (stage_batch)."""
    from cometbft_tpu.ops import hashvec

    words = hashvec.sha512_mod_l_words(
        [sig[:32] + pub + msg for pub, msg, sig in zip(pubs, msgs, sigs)])
    blob = words.tobytes()
    return [int.from_bytes(blob[32 * i: 32 * i + 32], "little")
            for i in range(len(sigs))]


# L as 4 little-endian 64-bit words, most significant last — the vectorized
# s < L comparison reads these
_L_WORDS64 = np.frombuffer(oracle.L.to_bytes(32, "little"), dtype="<u8")


def scalars_lt_l(s_rows: np.ndarray) -> np.ndarray:
    """(N, 32) uint8 little-endian scalars -> (N,) bool of (s < L),
    vectorized lexicographic compare over the four 64-bit words from the
    most significant down — replaces the per-row int.from_bytes round trip
    in staging."""
    w = np.ascontiguousarray(s_rows).view("<u8")
    lt = np.zeros(w.shape[0], dtype=bool)
    decided = np.zeros(w.shape[0], dtype=bool)
    for i in (3, 2, 1, 0):
        lt |= ~decided & (w[:, i] < _L_WORDS64[i])
        decided |= w[:, i] != _L_WORDS64[i]
    return lt


_ID_ROW32 = np.frombuffer(_ID_ENC32, dtype=np.uint8)


def _challenge_words(r_rows, pub_rows, msgs, mlens, pre_ok) -> np.ndarray:
    """(N, 8) uint32 packed challenge words k = SHA-512(R||A||M) mod L.
    Uniform-length messages (every commit: sign-bytes share one length)
    hash as ONE (N, 64+mlen) batch call; ragged messages group inside
    sha512_many, or, where they come as columns (a prefixrows.MsgBlock),
    hash as one such call a message length with no row cut out. Rows with
    pre_ok False get k = 0 (their placeholder R/A content is hashed but
    discarded)."""
    from cometbft_tpu.libs.prefixrows import MsgBlock, as_bytes
    from cometbft_tpu.ops import hashvec

    n = r_rows.shape[0]
    if isinstance(msgs, MsgBlock) and n and not (mlens == mlens[0]).all():
        digests = np.empty((n, 64), dtype=np.uint8)
        for mlen in np.unique(mlens).tolist():
            at = np.flatnonzero(mlens == mlen)
            digests[at] = hashvec.sha512_rows(np.concatenate(
                [r_rows[at], pub_rows[at], msgs.take(at).matrix(mlen)],
                axis=1))
    elif n and (mlens == mlens[0]).all():
        # batch-axis reassembly: shared-prefix vote rows broadcast their
        # per-commit prefix once instead of joining N full copies
        msg_rows = hashvec.assemble_prefixed_rows(msgs, int(mlens[0]))
        data = np.concatenate([r_rows, pub_rows, msg_rows], axis=1)
        digests = hashvec.sha512_rows(data)
    else:
        r_blob, p_blob = r_rows.tobytes(), pub_rows.tobytes()
        digests = hashvec.sha512_many(
            [r_blob[32 * i:32 * i + 32] + p_blob[32 * i:32 * i + 32]
             + as_bytes(m) for i, m in enumerate(msgs)])
    k_words = hashvec.reduce512_mod_l(digests)
    k_words[~pre_ok] = 0
    return k_words


def _byte_rows(items, width: int) -> tuple[np.ndarray, np.ndarray | None]:
    """A column of byte strings that should each have `width` bytes:
    (which do (N,) bool, the (N, width) uint8 matrix if all do, else
    None). A column that arrives as a matrix already (libs/rowblock.py:
    a commit's signatures and keys) is returned as it is."""
    if isinstance(items, np.ndarray):
        return np.ones(items.shape[0], dtype=bool), items
    n = len(items)
    ok_len = np.fromiter(map(len, items), np.int64, n) == width
    if not ok_len.all():
        return ok_len, None
    return ok_len, np.frombuffer(
        b"".join(items), dtype=np.uint8).reshape(n, width)


def _structural_stage(
    pubs: list[bytes], sigs, pub_rows: np.ndarray | None = None,
) -> tuple[np.ndarray, list[bytes], np.ndarray, np.ndarray]:
    """The host-side structural checks every staging path shares (lengths,
    s < L — never reach the device), with placeholder substitution for the
    failing rows. Returns (pre_ok, safe_pubs, sig_rows, pub_rows) — the
    row matrices feed challenge computation (host or the device fallback
    lanes) and the word packing. sigs: a list of bytes or the (N, 64)
    matrix; pub_rows: the keys as their (N, 32) matrix where the caller
    has it (pubs stays the list the residency lookup reads). Neither
    matrix is written to."""
    n = len(sigs)
    ok_sig, sig_rows = _byte_rows(sigs, 64)
    ok_pub, pub_rows = _byte_rows(pubs if pub_rows is None else pub_rows, 32)
    ok_len = ok_sig & ok_pub
    safe_pubs = pubs
    if sig_rows is None or pub_rows is None:
        # ragged stragglers: per-row placeholder substitution
        sig_rows = np.zeros((n, 64), dtype=np.uint8)
        pub_rows = np.zeros((n, 32), dtype=np.uint8)
        sig_rows[:, :32] = _ID_ROW32
        pub_rows[:] = _ID_ROW32
        safe_pubs = [_ID_ENC32] * n
        for i in np.flatnonzero(ok_len):
            sig_rows[i] = np.frombuffer(sigs[i], dtype=np.uint8)
            pub_rows[i] = np.frombuffer(pubs[i], dtype=np.uint8)
            safe_pubs[i] = pubs[i]
    pre_ok = ok_len & scalars_lt_l(sig_rows[:, 32:])
    bad = np.flatnonzero(ok_len & ~pre_ok)  # s >= L rows need placeholders
    if bad.size:
        sig_rows = sig_rows.copy()  # the caller's matrix, or read-only
        sig_rows[bad, :32] = _ID_ROW32
        sig_rows[bad, 32:] = 0
        safe_pubs = [p if pre_ok[i] else _ID_ENC32
                     for i, p in enumerate(safe_pubs)]
    return pre_ok, safe_pubs, sig_rows, pub_rows


def stage_batch(
    pubs: list[bytes], msgs: list[bytes], sigs: list[bytes], bucket: int,
    out: np.ndarray | None = None,
) -> tuple[np.ndarray, list[bytes], np.ndarray, np.ndarray, np.ndarray]:
    """Host staging shared by the single-chip and mesh paths: structural
    checks (lengths, s < L — never reach the device), SHA-512 challenges,
    packed-word arrays padded to `bucket`, batch-minor (8, bucket) uint32.
    Returns (pre_ok, safe_pubs, r_words, s_words, k_words).

    All batch-axis numpy: vectorized length/s<L checks, one hashvec batch
    call for the challenges, r/s/k packed in place into `out` — a leased
    (3, 8, bucket) StagingPool block (limbs.POOL) — when given, else fresh
    arrays (mesh/bench callers that keep the words). This is the
    host-challenge path; the device-challenge twin (verify_batch_async's
    ops/challenge.py branch) stages the same structural rows but ships
    descriptors instead of k words."""
    pre_ok, safe_pubs, sig_rows, pub_rows = _structural_stage(pubs, sigs)
    r_words, s_words, k_words = _pack_host_words(
        pre_ok, sig_rows, pub_rows, msgs, bucket, out=out)
    return pre_ok, safe_pubs, r_words, s_words, k_words


def _pack_host_words(pre_ok, sig_rows, pub_rows, msgs, bucket,
                     out=None) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-challenge word packing: SHA-512 challenges plus the r/s/k
    planes, identity-padded to `bucket`."""
    from cometbft_tpu.libs.prefixrows import msg_lengths

    n = sig_rows.shape[0]
    k_rows = _challenge_words(
        sig_rows[:, :32], pub_rows, msgs, msg_lengths(msgs), pre_ok)

    sig_u4 = sig_rows.view("<u4")  # (n, 16): words 0-7 = R, 8-15 = s
    if out is None:
        out = np.empty((3, 8, bucket), dtype=np.uint32)
    r_words, s_words, k_words = out[0], out[1], out[2]
    r_words[:, :n] = sig_u4[:, :8].T
    s_words[:, :n] = sig_u4[:, 8:].T
    k_words[:, :n] = k_rows.T
    if bucket > n:  # identity encoding + zero scalars: verifies valid
        r_words[:, n:] = 0
        r_words[0, n:] = 1
        s_words[:, n:] = 0
        k_words[:, n:] = 0
    return r_words, s_words, k_words


def _pack_device_block(sig_rows: np.ndarray, bucket: int, plan,
                       block: np.ndarray) -> None:
    """Pack a leased FLAT block for the device-challenge wire: R words,
    s words (word-major (8, bucket) planes, identity-padded), then the
    descriptor stream (challenge.fill_stream). No k words — that is the
    point."""
    n = sig_rows.shape[0]
    sig_u4 = sig_rows.view("<u4")
    rw = block[:8 * bucket].reshape(8, bucket)
    sw = block[8 * bucket:16 * bucket].reshape(8, bucket)
    rw[:, :n] = sig_u4[:, :8].T
    sw[:, :n] = sig_u4[:, 8:].T
    if bucket > n:
        rw[:, n:] = 0
        rw[0, n:] = 1
        sw[:, n:] = 0
    from cometbft_tpu.ops import challenge as _challenge

    _challenge.fill_stream(block, bucket, plan)


def verify_batch(
    pubs: list[bytes],
    msgs: list[bytes],
    sigs: list[bytes],
    cache: PubKeyCache | None = None,
) -> tuple[bool, list[bool]]:
    """ZIP-215 batch verification with per-signature mask. Agrees with
    oracle.verify_zip215 on every input (tested bit-for-bit)."""
    mask = verify_batch_async(pubs, msgs, sigs, cache=cache)()
    return bool(mask.all()), mask.tolist()


# Failed lanes are re-verified on host with the exact ZIP-215 oracle before
# being reported invalid (bounded count — a batch with many failures is
# genuinely bad). The reference batch verifier falls back to serial
# re-verify on failure too (types/validation.go:266); here the motivation
# is also defensive: a host link can produce isolated
# single-lane corruption under load, and an honest signature must never be
# condemned by a flipped transfer bit.
_RECHECK_MAX = 32


def recheck_failed_lanes(mask, eligible, pubs, msgs, sigs,
                         verify_fn, scheme: str):
    """eligible: lanes that passed the host-side structural checks — a
    pre-failed lane carries a placeholder encoding (the identity, which
    being small-order validly signs ANYTHING under ZIP-215) and must never
    be flipped back to valid. Shared by the ed25519 and sr25519 paths;
    verify_fn is the scheme's exact host oracle."""
    import numpy as _np

    from cometbft_tpu.libs.prefixrows import as_bytes

    bad = _np.flatnonzero(~mask & eligible)
    if len(bad) == 0 or len(bad) > _RECHECK_MAX:
        return mask
    flipped = []
    for i in bad:
        if verify_fn(pubs[i], as_bytes(msgs[i]), _row_bytes(sigs[i])):
            mask[i] = True
            flipped.append(int(i))
    if flipped:
        from cometbft_tpu.libs import log as _log

        _count_integrity("mask_oracle_disagreement", len(flipped))
        _log.default().error(
            "device verify mask disagreed with host oracle; honoring host",
            scheme=scheme, lanes=str(flipped))
    return mask


def _recheck_failed_lanes(mask, eligible, pubs, msgs, sigs):
    return recheck_failed_lanes(
        mask, eligible, pubs, msgs, sigs, oracle.verify_zip215, "ed25519")


def apply_recheck(mask, eligible, rows, info):
    """Host-oracle recheck with optional per-group budgets: info is
    (verify_fn, scheme, groups). A coalesced window passes its per-commit
    row boundaries as groups so each commit keeps its own _RECHECK_MAX
    budget — one genuinely-bad commit must not suppress the
    transfer-corruption recheck for its window-mates."""
    verify_fn, scheme, groups = info
    pubs, msgs, sigs = rows
    if not groups:
        return recheck_failed_lanes(
            mask, eligible, pubs, msgs, sigs, verify_fn, scheme)
    for a, b in groups:
        mask[a:b] = recheck_failed_lanes(
            mask[a:b], eligible[a:b], pubs[a:b], msgs[a:b], sigs[a:b],
            verify_fn, scheme)
    return mask


def make_host_thunk(n, pre_ok, rows, info):
    """A verify thunk that never touches the device — the CPU rung of the
    ladder, used when the breaker has sidelined the device or staging
    failed. Same thunk contract as verify_batch_async (device_parts with a
    None payload acquirer and n > 0 routes resolve_batches here too)."""
    ones = np.ones(n, dtype=bool)
    cached: dict = {}

    def result() -> np.ndarray:
        if "m" not in cached:
            cached["m"] = host_oracle_mask(n, pre_ok, ones, rows, info)
        return cached["m"]

    result.device_parts = lambda: (None, n, pre_ok, ones, rows, info, None)
    return result


class _LateOkA:
    """Pubkey-validity mask resolved ON THE TRANSFER POOL: the
    reduced-send pipeline moved pubkey staging (residency/index upload)
    off the caller thread into the dispatch closure, so batch N+1's
    host staging overlaps batch N's pubkey RTT instead of serializing
    behind it. The cell is set by the closure before dispatch returns;
    a read before that only happens on ladder paths that already failed
    device dispatch — there the host oracle is ground truth and needs
    no device decompress mask, so the all-eligible default is exact."""

    __slots__ = ("n", "value")

    def __init__(self, n: int):
        self.n = n
        self.value = None

    def resolve(self) -> np.ndarray:
        v = self.value
        return v if v is not None else np.ones(self.n, dtype=bool)


def _ok_arr(ok_a) -> np.ndarray:
    return ok_a.resolve() if isinstance(ok_a, _LateOkA) else ok_a


def _to_host(dev_arr) -> np.ndarray:
    """THE device->host fetch of the verify trip (header, payload): the
    host blocks here until the batch's programs have run."""
    _residency.count_trip(waits=1)
    return np.asarray(dev_arr)


def supervised_device_thunk(scheme: str, sup, submit_fn, fetch_site: str,
                            n, pre_ok, ok_a, rows, info,
                            expected=0, lease=None, strict: bool = False,
                            on_intact=None):
    """The shared thunk shape for a supervised device batch (ed25519 and
    sr25519 build their dispatch closure, this builds the rest): dispatch
    runs on the transfer pool under the supervisor; fetches are
    watchdog-bounded; every failure drops the batch onto the host oracle
    instead of raising into the verify seam.

    submit_fn returns (header_dev, payload_dev) — the reduced-fetch pair
    from _integrity_parts. The thunk fetches the 8-byte header first and
    pulls the full per-lane payload only on a non-happy verdict. `expected`
    is the host staging checksum the header is decoded against; `lease` is
    the StagingPool block backing the staged words, returned to the pool
    once the batch resolves (the _redo retry re-reads it, so release waits
    for resolution, not dispatch — and the batch's upload is un-awaited, so
    until the header has been read a transfer may still be reading the
    block). The DoubleBuffer in-flight slot is NOT released here: the
    dispatch closure scopes it (acquire before the first program's call,
    release in a finally after the verify dispatch), so an abandoned thunk
    — a caller that takes device_parts() and never resolves, exactly like
    an unreleased pool block — can never leak a slot and wedge the gate.

    strict (a mesh shard, Target.strict): a failed dispatch or fetch is
    raised (DeviceOpFailed / DeviceUnavailable, recorded) instead of
    resolved on the host oracle: the mesh has other chips to ask first.
    A payload that fails its integrity checks still retries and then
    resolves on the host oracle, as everywhere.

    on_intact: called once a header has shown that the device checksummed
    the very bytes the host staged (verdict happy or full), here or in
    resolve_batches: what else rode the upload under that checksum (the
    prefix table's rows) is confirmed then."""
    # wrap_ctx carries the caller's trace context onto the pool thread so
    # the dispatch's transfer/compute spans land inside this batch's tree
    fut = _xfer_pool().submit(_trace.wrap_ctx(sup.run), submit_fn)
    _lease = [lease]

    def _release() -> None:
        blk, _lease[0] = _lease[0], None
        if blk is not None:
            L.POOL.release(blk)

    def _acquire():
        """Block until dispatch completes; returns the device-resident
        (header, payload) pair. Raises DeviceOpFailed/DeviceUnavailable
        (recorded)."""
        try:
            return fut.result(timeout=_dispatch.watchdog_timeout())
        except (_dispatch.DeviceOpFailed, _dispatch.DeviceUnavailable):
            raise
        except Exception as exc:  # noqa: BLE001 - watchdog timeout etc.
            sup.record_op_failure(exc)
            raise _dispatch.DeviceOpFailed(f"{scheme} dispatch wait") from exc

    _acquire.expected = expected  # resolve_batches decodes headers itself
    _acquire.on_intact = on_intact

    def _fetch_np(dev_arr, pure_transfer: bool = False) -> np.ndarray:
        """Device->host fetch (header or full payload): chaos site +
        watchdog + injected lane corruption (the integrity echo plane must
        catch it). Only a `pure_transfer` fetch feeds the link model: the
        FIRST fetch of a batch blocks until the kernel finishes, so its
        wall time is compute + wire — feeding that into the link
        estimator would inflate RTT by the kernel time. Once the header
        has been read the device result is materialized, and the payload
        fetch is pure wire."""
        from cometbft_tpu.libs import chaos

        with _trace.span(f"{scheme}.d2h", cat="fetch") as sp:
            try:
                chaos.fire(fetch_site)
                t0 = _time.perf_counter()
                out = _fetch_pool().submit(_to_host, dev_arr).result(
                    timeout=_dispatch.watchdog_timeout())
                if pure_transfer:
                    _linkmodel.link().observe_transfer(
                        out.nbytes, _time.perf_counter() - t0)
            except Exception as exc:  # noqa: BLE001
                sup.record_op_failure(exc)
                raise _dispatch.DeviceOpFailed(
                    f"{scheme} payload fetch") from exc
            sp.add_bytes(rx=out.nbytes)
        return chaos.corrupt_mask(fetch_site, out)

    def _redo():
        """Integrity-retry path: full fresh transfer+dispatch+fetch of the
        FULL payload (the header already said unhappy), supervised AND
        watchdog-bounded like every other device wait — a device that
        hangs during the retry must not stall the verify seam
        (decode_payload catches and falls to the host oracle), and the
        hang/failure is recorded so the breaker and crypto_health see it."""
        try:
            return _fetch_pool().submit(
                lambda: _to_host(sup.run(submit_fn)[1])).result(
                    timeout=_dispatch.watchdog_timeout())
        except (_dispatch.DeviceOpFailed, _dispatch.DeviceUnavailable):
            raise  # sup.run already recorded it
        except Exception as exc:  # noqa: BLE001 - watchdog timeout etc.
            sup.record_op_failure(exc)
            raise

    def result() -> np.ndarray:
        try:
            header_dev, payload_dev = _acquire()
            header = _fetch_np(header_dev)
        except (_dispatch.DeviceOpFailed, _dispatch.DeviceUnavailable):
            _release()
            if strict:
                raise
            return host_oracle_mask(n, pre_ok, _ok_arr(ok_a), rows, info)
        ok = _ok_arr(ok_a)  # staging completed: the cell is resolved
        verdict = decode_header(header, expected)
        if on_intact is not None and verdict in _INTACT:
            on_intact()
        if verdict == "happy":
            _count_fetch(True, header.nbytes)
            _release()
            return pre_ok & ok  # no failed lanes -> nothing to recheck
        if verdict == "echo_corrupt":
            _count_integrity("mask_echo_mismatch")
            from cometbft_tpu.libs import log as _log

            _log.default().error(
                "reduced-fetch header failed its complement echo; pulling "
                "the full payload", scheme=info[1])
        try:
            payload = _fetch_np(payload_dev, pure_transfer=True)
        except (_dispatch.DeviceOpFailed, _dispatch.DeviceUnavailable):
            _release()
            if strict:
                raise
            return host_oracle_mask(n, pre_ok, ok, rows, info)
        _count_fetch(False, header.nbytes + payload.nbytes)
        try:
            with _trace.span(f"{scheme}.decode", cat="resolve", rows=n):
                return decode_payload(
                    payload, n, pre_ok, ok, rows, info, redo=_redo)
        finally:
            _release()

    result.device_parts = lambda: (
        _acquire, n, pre_ok, ok_a, rows, info, _redo)
    result.release_staging = _release
    # where the batch's programs ran (after a result: the dispatch is done)
    result.placed = lambda: {str(d) for a in _acquire() for d in a.devices()}
    return result


def verify_batch_async(
    pubs: list[bytes],
    msgs,
    sigs,
    cache: PubKeyCache | None = None,
    recheck_groups: list[tuple[int, int]] | None = None,
    pub_rows: np.ndarray | None = None,
    target: Target | None = None,
    ladder=None,
):
    """Stage + dispatch without blocking on the device: returns a thunk that
    materializes the (N,) bool mask. Lets callers (blocksync streaming,
    VoteSet flush) overlap host staging of batch N+1 with device compute of
    batch N. recheck_groups: per-commit row boundaries of a coalesced
    window (see apply_recheck).

    The rows as lists of bytes, or as the scheduler has them since PR 31
    (libs/rowblock.SigColumns): msgs a prefixrows.MsgBlock, sigs the
    (N, 64) uint8 matrix, pub_rows the (N, 32) key matrix beside the key
    list. The columns are staged as they are and kept, unread and
    uncopied, for the host oracle.

    target: the chip the trip is aimed at (Target; a mesh shard), by
    default JAX's first device. Everything the trip keeps on a device is
    that chip's: its replica of the key table and of the prefix table, the
    un-awaited upload, both programs, the in-flight gate, the fetch.
    ladder: see _verify_programs.

    Device faults never escape the thunk: dispatch runs under the "device"
    supervisor (transient retry + breaker, ops/dispatch.py), fetches are
    watchdog-bounded, and any failure resolves the batch on the exact host
    oracle — a hung or dead device costs latency, not a consensus round.
    Only a strict target's thunk raises them (Target)."""
    n = len(sigs)
    assert len(pubs) == n and len(msgs) == n
    if n == 0:
        empty = lambda: np.zeros(0, dtype=bool)  # noqa: E731
        empty.device_parts = lambda: (
            None, 0, np.zeros(0, bool), np.zeros(0, bool), ([], [], []),
            (oracle.verify_zip215, "ed25519", None), None)
        return empty
    cache = cache or _default_cache
    target = target or Target()
    from cometbft_tpu.libs.prefixrows import MsgBlock

    b = bucket_size(n)
    sup = _dispatch.supervisor(target.supervisor)
    allowed = sup.breaker.peek()
    if target.strict and not allowed:
        raise _dispatch.DeviceUnavailable(sup.name)
    # sig_rows: THE attribution row-counting site for this batch (one
    # stage span per dispatched batch; everything else is informational)
    with _trace.span("ed25519.stage", cat="stage", sig_rows=n, lanes=b,
                     hash_rung=_staging_rung(), device=target.index):
        pre_ok, safe_pubs, sig_rows, pub_rows = _structural_stage(
            pubs, sigs, pub_rows)
        # the messages as columns from here on: a list's one lane loop
        msgs = MsgBlock.of(msgs)
        plan = None
        if allowed:
            try:
                from cometbft_tpu.ops import challenge as _challenge

                plan = _challenge.plan_batch(
                    msgs, pre_ok, put_key=target.put_key,
                    device=target.device)
            except Exception:  # noqa: BLE001 - planning never breaks staging
                plan = None
        if plan is None:
            block = L.POOL.lease(b)
            r_words, s_words, k_words = _pack_host_words(
                pre_ok, sig_rows, pub_rows, msgs, b, out=block)
        else:
            from cometbft_tpu.ops import challenge as _challenge

            block = L.POOL.lease_flat(_challenge.block_words(b, plan.var))
            _pack_device_block(sig_rows, b, plan, block)
    rows = (safe_pubs, msgs, sigs)
    info = (oracle.verify_zip215, "ed25519", recheck_groups)

    if not allowed:
        L.POOL.release(block)
        return make_host_thunk(n, pre_ok, rows, info)
    ok_cell = _LateOkA(n)
    gate = f"dev{target.index}"

    def _fire_dispatch_sites() -> None:
        from cometbft_tpu.libs import chaos

        chaos.fire("ed25519.dispatch")
        if target.put_key:  # a mesh chip's own site: one fault domain
            chaos.fire(f"ed25519.dispatch.{target.put_key}")

    if plan is None:
        expected = np.uint32(_host_checksum(r_words, s_words, k_words))

        def _transfer_and_dispatch():
            _fire_dispatch_sites()
            # pubkey staging rides the transfer pool too (reduced-send
            # pipeline): the caller thread never waits on the residency
            # lookup (or a delta upload), so host staging of batch N+1
            # overlaps it. A staging failure here feeds the
            # supervisor/breaker exactly like a dispatch failure (the
            # batch lands on the host oracle).
            with _trace.span("ed25519.stage_pubkeys", cat="transfer",
                             lanes=b, device=target.index):
                ok_a, idx, planes, _enc, path = _stage_index(
                    cache, safe_pubs, b, target.put_key, target.device)
            ok_cell.value = ok_a
            # in-flight slot, scoped to the verify dispatch (a _redo
            # retry or an abandoned thunk can never leak it): batch N's
            # upload overlaps batch N-1's compute, batch N+1 queues
            # until a slot frees
            with _trace.span("ed25519.slot", cat="queue", lanes=b):
                rel = _dispatch.doublebuffer(gate).acquire()
            try:
                parts = _dispatch_hostk(idx, planes, block, expected, path,
                                        n, target, ladder)
            finally:
                rel()
            _count_device_batch("ed25519", b)
            _residency.count_trip(batches=1)
            return parts

        # Residency lookup and dispatch run on a small pool: the caller
        # can stage batch i+1 while batch i is on its way.
        return supervised_device_thunk(
            "ed25519", sup, _transfer_and_dispatch, "ed25519.fetch",
            n, pre_ok, ok_cell, rows, info, expected=expected, lease=block,
            strict=target.strict)

    # ---- device-challenge path: the wire carries R/s + descriptors; k is
    # derived on-chip (ops/challenge.py) with per-lane host fallbacks for
    # the Plan's ineligible lanes, and a whole-batch host-k rung when the
    # derive itself fails or A is not table-resident.
    fb_lanes = np.flatnonzero(pre_ok & ~plan.eligible)
    fb = 0
    fkw = fidx = None
    if fb_lanes.size:
        with _trace.span("ed25519.challenge", cat="challenge",
                         lanes=int(fb_lanes.size), rung="lane_fallback"):
            msgs_fb = msgs.take(fb_lanes)
            k_fb = _challenge_words(
                np.ascontiguousarray(sig_rows[fb_lanes, :32]),
                np.ascontiguousarray(pub_rows[fb_lanes]),
                msgs_fb, msgs_fb.lengths(),
                np.ones(fb_lanes.size, dtype=bool))
            fb = bucket_size(int(fb_lanes.size))
            # pad by repeating the last real lane: the device scatter is
            # idempotent, so the repeated index just rewrites the same
            # value
            fidx = np.full(fb, int(fb_lanes[-1]), dtype=np.int32)
            fidx[:fb_lanes.size] = fb_lanes
            fkw = np.tile(k_fb[-1:].T, (1, fb)).astype(np.uint32)
            fkw[:, :fb_lanes.size] = k_fb.T
    fk = (fkw, fidx) if fb else ()
    # the block holds the prefix table's dirty rows too (a new height's
    # row: challenge.fill_stream), so this checksum covers them
    expected_dc = _host_checksum(block, *fk)
    expected_cell = _LateExpected(expected_dc)
    new_tab = [None]  # the derive's table, while a derive served the batch

    def _intact() -> None:
        """The header said the device saw the bytes the host sent, the
        carried rows among them: the prefix table may adopt them."""
        if new_tab[0] is not None:
            plan.adopt(new_tab[0])

    def _transfer_and_dispatch_dc():
        _fire_dispatch_sites()
        with _trace.span("ed25519.stage_pubkeys", cat="transfer", lanes=b,
                         device=target.index):
            ok_a, idx, planes, enc, path = _stage_index(
                cache, safe_pubs, b, target.put_key, target.device)
        ok_cell.value = ok_a
        with _trace.span("ed25519.slot", cat="queue", lanes=b):
            rel = _dispatch.doublebuffer(gate).acquire()
        try:
            return _challenge_rungs_and_dispatch(idx, planes, enc, path)
        finally:
            rel()

    def _challenge_rungs_and_dispatch(idx, planes, enc, path):
        from cometbft_tpu.libs import chaos
        from cometbft_tpu.ops import challenge as _challenge

        derived = None
        if enc is not None:
            sup_ch = _dispatch.supervisor(_challenge.SITE)

            def _derive():
                chaos.fire(_challenge.SITE)
                run = _challenge.derive_fn(
                    b, plan.var, plan.plen, plan.tlen, fb)
                # the batch's ONE upload: block (the prefix table's
                # dirty rows at its tail), index vector and the
                # fallback-k arrays are this call's host arguments,
                # un-awaited (the block stays leased until the batch
                # resolves)
                with _trace.span("ed25519.challenge", cat="challenge",
                                 lanes=b, device=target.index) as sp:
                    with _dispatch_lock:
                        out = run(block, idx, *planes, enc, plan.dev_tab,
                                  *fk)
                    nbytes = (block.nbytes + idx.nbytes
                              + sum(a.nbytes for a in fk))
                    sp.add_bytes(tx=nbytes)
                _residency.count_trip(programs=1)
                # the block's table rows are table maintenance on the
                # wire's books (padding too: it went up all the same),
                # the rest is what the signatures cost
                carried = _challenge.CARRY_BYTES
                _residency.record_send(path, nbytes - carried, sigs=n)
                _residency.record_send("delta", carried)
                if plan.n_carried:
                    _challenge.count("table_rows_carried", plan.n_carried)
                return out

            try:
                derived = sup_ch.run(_derive)
                if chaos.should_corrupt(_challenge.SITE):
                    # perturbed device k: the failing lane must be caught
                    # by the recheck plane, never reported as invalid
                    rw, sw, kw, *rest = derived
                    derived = (rw, sw, kw.at[0, 0].add(np.uint32(1)), *rest)
                    _residency.count_trip(programs=1)
            except (_dispatch.DeviceUnavailable, _dispatch.DeviceOpFailed):
                _challenge.count("derive_failed")
        else:
            _challenge.count("enc_not_resident")
        new_tab[0] = None
        if derived is None:
            # whole-batch host-k rung: compute k here on the transfer
            # pool and send the block's R and s planes with it as the
            # (3, 8, B) words of the host-challenge program (which
            # gathers for itself); the descriptor stream stays home
            with _trace.span("ed25519.challenge", cat="challenge", lanes=b,
                             rung="host_fallback"):
                k_rows = _challenge_words(
                    sig_rows[:, :32], pub_rows, msgs, msgs.lengths(),
                    pre_ok)
                words = np.zeros((3, 8, b), dtype=np.uint32)
                words[:2] = block[:16 * b].reshape(2, 8, b)
                words[2, :, :n] = k_rows.T
            expected_cell.value = _host_checksum(words)
            _challenge.count("batch_host_fallback")
            parts = _dispatch_hostk(
                idx, planes, words, np.uint32(expected_cell.value), path, n,
                target, ladder)
        else:
            expected_cell.value = expected_dc  # a _redo after a fallback
            rw, sw, kw, chk, *a_dev, new_tab[0] = derived
            with _trace.span("ed25519.dispatch", cat="compute", lanes=b,
                             device=target.index):
                parts = _dispatch_verify(
                    False, (*a_dev, rw, sw, kw, chk,
                            np.uint32(expected_dc)), b, ladder)
        _count_device_batch("ed25519", b)
        _residency.count_trip(batches=1)
        return parts

    return supervised_device_thunk(
        "ed25519", sup, _transfer_and_dispatch_dc, "ed25519.fetch",
        n, pre_ok, ok_cell, rows, info, expected=expected_cell, lease=block,
        strict=target.strict, on_intact=_intact)


def resolve_batches(thunks) -> list[np.ndarray]:
    """Materialize many verify_batch_async results with a two-phase
    reduced fetch (device-side concat): phase 1 pulls every batch's 8-byte
    header in ONE device->host fetch — every fetch pays a link round
    trip, so a happy window (the steady state) costs one tiny transfer
    instead of the full masks; phase 2 pulls the full
    per-lane payloads, again concatenated into one fetch, only for batches
    whose header said unhappy. Thunks may mix schemes (the mixed
    mega-commit resolves its ed25519 and sr25519 sub-batches together) —
    each carries its own host re-check oracle.

    Device-fault behavior: a batch whose dispatch failed (or that was
    staged host-side because the breaker was open) resolves on the host
    oracle; a failed/hung combined fetch (watchdog) drops every device
    batch still depending on it onto the host oracle. The function never
    raises on device trouble — blocksync's pool routine awaits it from an
    executor."""
    parts = [t.device_parts() for t in thunks]
    pairs: list = []  # per thunk: (header_dev, payload_dev) | None | False
    for p in parts:
        acquire = p[0]
        if acquire is None:
            pairs.append(None)
            continue
        try:
            pairs.append(acquire())
        except Exception:  # noqa: BLE001 - recorded by the thunk's supervisor
            pairs.append(False)
    live = [pr for pr in pairs if pr is not None and pr is not False]

    def _pull(arrs):
        from cometbft_tpu.libs import chaos

        chaos.fire("mixed.resolve")
        if len(arrs) == 1:  # nothing to join: no program, just the fetch
            return _to_host(arrs[0])
        _residency.count_trip(programs=1)
        return _to_host(jnp.concatenate(arrs))

    headers = None
    if live:
        sup = _dispatch.supervisor("device")
        try:
            with _trace.span("resolve.header_fetch", cat="fetch",
                             batches=len(live)) as sp:
                # NOT fed to the link model: this fetch blocks until every
                # batch's kernel finishes, so its wall time is compute-
                # entangled (the post-header payload pull below is pure)
                headers = _fetch_pool().submit(
                    _pull, [h for h, _ in live]).result(
                        timeout=_dispatch.watchdog_timeout())
                sp.add_bytes(rx=headers.nbytes)
        except Exception as exc:  # noqa: BLE001 - window falls to the CPU rung
            sup.record_op_failure(exc)
    verdicts: list[str | None] = []  # parallel to pairs; None = host oracle
    need_payload = []
    li = 0
    for pr, p in zip(pairs, parts):
        if pr is None or pr is False or headers is None:
            verdicts.append(None)
            continue
        v = decode_header(headers[2 * li:2 * li + 2], p[0].expected)
        li += 1
        if v in _INTACT and p[0].on_intact is not None:
            p[0].on_intact()
        if v == "echo_corrupt":
            _count_integrity("mask_echo_mismatch")
        if v != "happy":
            need_payload.append(pr[1])
        verdicts.append(v)
    flat = None
    if need_payload:
        sup = _dispatch.supervisor("device")
        try:
            t0 = _time.perf_counter()
            flat = _fetch_pool().submit(_pull, need_payload).result(
                timeout=_dispatch.watchdog_timeout())
            _linkmodel.link().observe_transfer(
                flat.nbytes, _time.perf_counter() - t0)
        except Exception as exc:  # noqa: BLE001 - those batches go host-side
            sup.record_op_failure(exc)
    if headers is not None:
        if not need_payload:
            _count_fetch(True, headers.nbytes)
        else:
            _count_fetch(False, headers.nbytes
                         + (flat.nbytes if flat is not None else 0))
    out = []
    off = 0
    for pr, p, v in zip(pairs, parts, verdicts):
        acquire, n, pre_ok, ok_a, rows, info, redo = p
        ok_a = _ok_arr(ok_a)  # late cell: resolved once dispatch ran
        if pr is None and acquire is None and n == 0:
            out.append(np.zeros(0, dtype=bool))
        elif pr is None or pr is False or v is None:
            out.append(host_oracle_mask(n, pre_ok, ok_a, rows, info))
        elif v == "happy":
            out.append(pre_ok & ok_a)
        elif flat is None:
            out.append(host_oracle_mask(n, pre_ok, ok_a, rows, info))
        else:
            b = pr[1].shape[0]
            out.append(decode_payload(
                flat[off:off + b], n, pre_ok, ok_a, rows, info, redo=redo))
            off += b
    for t in thunks:
        rel = getattr(t, "release_staging", None)
        if rel is not None:
            rel()
    return out


_pool = None
_fpool = None


def _xfer_pool():
    global _pool
    if _pool is None:
        import concurrent.futures

        _pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=4, thread_name_prefix="ed25519-xfer"
        )
    return _pool


def _fetch_pool():
    """Separate pool for watchdog-bounded device->host fetches: a fetch
    abandoned by the watchdog keeps its thread until jax gives up, and it
    must not starve the dispatch pool. If a hung device clogs both workers,
    subsequent fetches time out too — which is the truth — and the breaker
    stops new device batches after the threshold."""
    global _fpool
    if _fpool is None:
        import concurrent.futures

        _fpool = concurrent.futures.ThreadPoolExecutor(
            max_workers=2, thread_name_prefix="device-fetch"
        )
    return _fpool
