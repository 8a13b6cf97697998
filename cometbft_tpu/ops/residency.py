"""Device-resident validator sets — the reduced-send wire protocol.

PR 5 shrank the FETCH side to 8 B/batch; this module is the SEND-side
twin. The dominant recurring send over the host<->device wire
is key material that barely changes: the same validator set re-verifies
every height, yet the digest-keyed PubKeyCache re-uploads its whole
decompressed coordinate table whenever the exact unique-key
concatenation of a batch changes — which under the scheduler's
continuous batching (mempool riders coalesced into consensus flushes)
is nearly every flush. The FPGA verification-engine literature
(PAPERS.md, arXiv:2112.02229) makes the same move this module does:
keep the slowly-changing key material resident on the accelerator and
stream only the per-item deltas.

Design:

  KeyTable     one per (scheme, placement key): a fixed-capacity
               (20, cap) x 4 coordinate table resident on ONE device,
               plus a host-side key->row map. Rows are CONTENT-keyed
               (exact pubkey bytes), so a row can never serve stale
               coordinates — correctness never depends on the epoch
               bookkeeping below.
  indexed send a batch whose keys are all resident ships a 2-byte
               uint16 row index per lane instead of a 32-byte key (or a
               320-byte decompressed-coordinate row); the device
               gathers per-lane A-coordinates from the table with no
               host round trip.
  delta update unseen keys (validator-set churn, mempool riders) are
               decompressed host-side and scattered into free/LRU rows
               — the wire carries only the NEW rows, never the table,
               in blocks of DELTA_ROWS columns through ONE compiled
               program whatever their number (no compile per key count).
               Scatters are FUNCTIONAL (jnp .at[].set returns a fresh
               array): an in-flight batch keeps gathering from its own
               immutable snapshot, so concurrent churn can never
               corrupt a dispatched batch.
  epoch pins   validation.py announces the active validator set(s)
               (keyed by ValidatorSet.hash()); tables pin those rows so
               rider churn can never evict the hot set, and a new epoch
               re-pins by shipping only the evict/insert delta. An
               announced hash whose key content changed (set-hash
               mismatch) drops the pin and re-uploads the set in full —
               counted, and never a wrong verdict, because rows were
               content-keyed all along.
  replicas     placement keys carry the chip index on the multi-chip
               mesh ("dev3"), so each fault domain holds its own
               replica; invalidate_device() drops exactly one chip's
               replicas (mesh readmission re-seeds only the healed
               chip).
  degradation  anything the table cannot serve (capacity overflow, a
               poisoned delta upload, the module disabled) returns the
               batch to the classic full-key path
               (ed25519_kernel._stage_gather's digest cache) — the
               reduced-send protocol is an optimization layer, never a
               correctness dependency.

Send accounting: every host->device staging transfer is recorded under
a path label — "indexed" (steady state: index vector + staged r/s/k
words), "delta" (churn row uploads), "full" (full-key fallback:
coordinate-table uploads + 4-byte indices + staged words) — mirrored to
the crypto_verify_send_bytes{path} Prometheus counters and the
crypto_health staging.wire section, next to PR 5's fetch-side
verify_fetch_bytes{path}.
"""

from __future__ import annotations

import functools
import hashlib
import threading
import time as _time

import numpy as np

# ---------------------------------------------------------------- config

_cfg = {
    "enabled": True,
    # per-table row capacity: bounds device memory (320 B/row) and the
    # uint16 index width. One row is reserved for the identity padding
    # encoding.
    "rows": 16384,
}

_cfg_lock = threading.Lock()


def configure(enabled: bool | None = None, rows: int | None = None) -> None:
    """Apply config.crypto wire knobs (wire_indexed_sends,
    wire_table_rows). A capacity change applies to tables built after
    the call; live tables keep their allocation (a process-lifetime
    device buffer is not resized under in-flight batches)."""
    with _cfg_lock:
        if enabled is not None:
            _cfg["enabled"] = bool(enabled)
        if rows is not None:
            if not 64 <= rows <= 65536:
                raise ValueError("wire_table_rows must be in [64, 65536]")
            _cfg["rows"] = int(rows)


def enabled() -> bool:
    return _cfg["enabled"]


# ------------------------------------------------------- send accounting

_send_lock = threading.Lock()
_PATHS = ("indexed", "full", "delta")
_send_stats: dict[str, dict] = {
    p: {"sends": 0, "bytes": 0, "sigs": 0} for p in _PATHS
}


def record_send(path: str, nbytes: int, sigs: int = 0) -> None:
    """Account a host->device verify staging transfer under its send
    path. `sigs` counts live signature rows ONLY for the batch-carrying
    transfer (the staged-words + index send), so bytes/sig divides by
    real rows, not padding or table maintenance."""
    with _send_lock:
        s = _send_stats[path]
        s["sends"] += 1
        s["bytes"] += nbytes
        s["sigs"] += sigs
    try:
        from cometbft_tpu.libs import metrics as _metrics

        cm = _metrics.crypto_metrics()
        cm.verify_sends.labels(path).inc()
        cm.verify_send_bytes.labels(path).inc(nbytes)
    except Exception:  # noqa: BLE001 - metrics must never break staging
        pass


def send_stats() -> dict:
    """The crypto_health staging `wire` subsection and the scheduler's
    live bytes-per-sig planning source. steady_state_bytes_per_sig is
    the indexed path's measured rate — what one more signature costs on
    the wire once the validator set is resident."""
    with _send_lock:
        out = {p: dict(v) for p, v in _send_stats.items()}
    idx = out["indexed"]
    out["steady_state_bytes_per_sig"] = (
        round(idx["bytes"] / idx["sigs"], 2) if idx["sigs"] else None)
    full = out["full"]
    out["full_path_bytes_per_sig"] = (
        round(full["bytes"] / full["sigs"], 2) if full["sigs"] else None)
    return out


def measured_bytes_per_sig() -> float | None:
    """Live wire cost of one signature on the dominant send path: the
    indexed rate when the reduced-send path carries traffic, else the
    full-key rate. None until any batch has been sent."""
    stats = send_stats()
    return (stats["steady_state_bytes_per_sig"]
            or stats["full_path_bytes_per_sig"])


def reset_send_stats() -> None:
    with _send_lock:
        for p in _PATHS:
            _send_stats[p] = {"sends": 0, "bytes": 0, "sigs": 0}
        for k in _trip:
            _trip[k] = 0


# The trip of a batch to the device and back, counted where it happens:
# `device_programs` at every call of a compiled program on the batch's
# way (table maintenance included), `blocking_waits` wherever the host
# blocks on the device (awaited uploads, header and payload fetches),
# `batches` once a batch has been dispatched. A happy ed25519 batch over
# a resident validator set is 2 programs and 1 wait (the header fetch).
_trip = {"batches": 0, "device_programs": 0, "blocking_waits": 0}


def count_trip(batches: int = 0, programs: int = 0, waits: int = 0) -> None:
    with _send_lock:
        _trip["batches"] += batches
        _trip["device_programs"] += programs
        _trip["blocking_waits"] += waits


def trip_stats() -> dict:
    """The crypto_health staging `trip` subsection."""
    with _send_lock:
        return dict(_trip)


# ------------------------------------------------------- device programs


def _jax():
    import jax

    return jax


def _jnp():
    import jax.numpy as jnp

    return jnp


@functools.lru_cache(maxsize=1)
def _init_table_fn():
    jax = _jax()
    jnp = _jnp()

    @functools.partial(jax.jit, static_argnums=(0,))
    def init(cap: int):
        """Fresh (20, cap) x 4 coordinate table plus the (8, cap)
        compressed-encoding plane, built ON DEVICE (no wire bytes):
        every row the extended identity (X=0, Y=1, Z=1, T=0) — the
        padding encoding for BOTH schemes (ed25519's y=1 point and the
        ristretto identity decode to the same extended coords). The enc
        plane holds each row's 32 raw key bytes as 8 LE uint32 words
        (identity: y=1 -> word0=1) — the A half of the on-device
        challenge preimage SHA-512(R||A||M), so the device-challenge
        path (ops/challenge.py) never re-ships key bytes it already has
        resident as coordinates."""
        zero = jnp.zeros((20, cap), jnp.int32)
        one = zero.at[0, :].set(1)
        enc = jnp.zeros((8, cap), jnp.uint32).at[0, :].set(1)
        return zero, one, one, zero, enc

    return init


# columns a delta program takes: ONE compiled geometry a table, whatever
# the number of new keys. A light client's drifting sets bring anything
# from 8 to 500 a hop, and a geometry per size would compile on a caller's
# path again and again. 128 is one lane tile: a delta of 8 keys ships one
# block of 45.6 kB (2.9 kB of it keys), a whole set of 500 ships four.
DELTA_ROWS = 128
# a block's planes: the X, Y, Z, T limbs (4 x 20), the 8 encoding words
# and the table row each column goes to
_DELTA_PLANES = 4 * 20 + 8 + 1


@functools.lru_cache(maxsize=1)
def _delta_fn():
    jax = _jax()
    jnp = _jnp()
    from cometbft_tpu.ops import ed25519_kernel as EK

    @jax.jit
    def key_table_delta(tx, ty, tz, tt, te, block, acc):
        """One (_DELTA_PLANES, DELTA_ROWS) int32 block scattered into
        the table, and the block's checksum added to `acc`: a delta of
        several blocks chains through one accumulator, so the host
        awaits one word for all of them."""
        vals = block[:80].reshape(4, 20, DELTA_ROWS)
        enc = jax.lax.bitcast_convert_type(block[80:88], jnp.uint32)
        i = block[88]
        with jax.named_scope("integrity"):
            acc = acc + EK._device_checksum_expr((block,))
        return (tx.at[:, i].set(vals[0]), ty.at[:, i].set(vals[1]),
                tz.at[:, i].set(vals[2]), tt.at[:, i].set(vals[3]),
                te.at[:, i].set(enc), acc)

    return key_table_delta


class _NoRoom(Exception):
    """The table cannot serve this batch/set — caller degrades to the
    full-key path."""


# -------------------------------------------------------------- KeyTable


class KeyTable:
    """One device-resident validator table (see module docstring). All
    public methods are serialized on the table lock; device arrays are
    replaced functionally, so readers that captured a snapshot stay
    consistent."""

    def __init__(self, scheme: str, cache, rows: int, put_key: str = "",
                 device=None):
        self.scheme = scheme
        self.cache = cache  # the scheme's PubKeyCache (host decompressor)
        self.cap = int(rows)
        self.id_row = self.cap - 1  # identity encoding for padding lanes
        self.put_key = put_key
        self.device = device
        self._lock = threading.RLock()
        self._rows: dict[bytes, int] = {}  # key -> row (dict order = LRU)
        self._ok: dict[bytes, bool] = {}
        self._free: list[int] = list(range(self.cap - 1))
        # pinned epoch sets: set_hash -> (content_digest, tuple(keys));
        # bounded — interleaved valsets (light-client bisection across
        # churn epochs) must not thrash each other's pins
        self._pinned_sets: dict[bytes, tuple[bytes, tuple]] = {}
        self._pin_count: dict[bytes, int] = {}  # key -> pinning sets
        self._dev: tuple | None = None
        self.counters = {
            "indexed_batches": 0, "delta_updates": 0, "delta_rows": 0,
            "full_set_uploads": 0, "evictions": 0, "hash_mismatches": 0,
            "checksum_retries": 0,
        }

    _MAX_PINNED_SETS = 4

    # ------------------------------------------------------------ device

    def _build(self):
        if self._dev is None:
            jax = _jax()
            init = _init_table_fn()
            if self.device is not None:
                with jax.default_device(self.device):
                    self._dev = tuple(init(self.cap))
            else:
                self._dev = tuple(init(self.cap))
        return self._dev

    def _put(self, arr: np.ndarray):
        jax = _jax()
        return (jax.device_put(arr) if self.device is None
                else jax.device_put(arr, self.device))

    # ---------------------------------------------------------- eviction

    def _evict_one(self, protect: frozenset = frozenset()) -> int:
        """Free the least-recently-used unpinned row outside `protect`
        (the current batch's resident keys — room-making for a delta
        must never evict a row the very batch is about to index).
        Raises _NoRoom when nothing is evictable."""
        for key in self._rows:  # dict order: oldest first
            if self._pin_count.get(key, 0) == 0 and key not in protect:
                row = self._rows.pop(key)
                self._ok.pop(key, None)
                self.counters["evictions"] += 1
                return row
        raise _NoRoom("all resident rows pinned or staged by this batch")

    def _alloc_rows(self, n: int,
                    protect: frozenset = frozenset()) -> list[int]:
        """Take n free rows (evicting LRU unpinned keys as needed). On
        _NoRoom the partially-allocated rows return to the free list —
        an aborted allocation must not leak capacity."""
        out: list[int] = []
        try:
            while len(out) < n:
                if self._free:
                    out.append(self._free.pop())
                else:
                    out.append(self._evict_one(protect))
        except _NoRoom:
            self._free.extend(out)
            raise
        return out

    # ------------------------------------------------------------ deltas

    def _insert_keys(self, missing: list[bytes], path: str = "delta",
                     protect: frozenset = frozenset()) -> int:
        """Decompress + scatter `missing` keys into free/LRU rows.
        Returns the wire bytes shipped. The delta upload is integrity-
        checked like the full-table path (a corrupted row would poison
        one validator until eviction): checksum mismatch retries once
        with a fresh transfer, then raises — the caller degrades to the
        full-key path rather than caching a poisoned row."""
        if not missing:
            return 0
        if len(missing) > self.cap - 1:
            raise _NoRoom(f"{len(missing)} keys exceed table capacity")
        ok, coords = self.cache.lookup_or_decompress(missing)
        rows = self._alloc_rows(len(missing), protect=protect)
        try:
            return self._upload_rows(missing, ok, coords, rows, path)
        except Exception:
            # a failed upload (double checksum mismatch, device death)
            # must hand its allocated rows back: repeated failures would
            # otherwise permanently drain the table's capacity
            self._free.extend(rows)
            raise

    def _upload_rows(self, missing, ok, coords, rows, path) -> int:
        from cometbft_tpu.libs import linkmodel as _linkmodel
        from cometbft_tpu.libs import trace as _trace
        from cometbft_tpu.ops import ed25519_kernel as EK

        n = len(missing)
        lanes = -(-n // DELTA_ROWS) * DELTA_ROWS
        # identity-padded: a padding column rewrites the identity row
        # with the identity's own coordinates and encoding — a deliberate
        # idempotent no-op that keeps the delta at its one geometry
        flat = np.zeros((_DELTA_PLANES, lanes), dtype=np.int32)
        flat[20] = flat[40] = flat[80] = 1  # Y = 1, Z = 1, word0 = 1
        flat[88] = self.id_row
        flat[:80, :n] = coords.transpose(1, 2, 0).reshape(80, n)
        # the compressed-encoding plane rides the same delta: the rows'
        # raw 32 key bytes as 8 LE words
        flat.view(np.uint32)[80:88, :n] = np.frombuffer(
            b"".join(missing), dtype=np.uint8).reshape(-1, 32).view("<u4").T
        flat[88, :n] = rows
        blocks = [np.ascontiguousarray(flat[:, at:at + DELTA_ROWS])
                  for at in range(0, lanes, DELTA_ROWS)]
        expected = sum(EK._host_checksum(b) for b in blocks) & 0xFFFFFFFF
        nbytes = flat.nbytes
        delta = _delta_fn()
        for attempt in (1, 2):
            t0 = _time.perf_counter()
            blocks_dev = [self._put(b) for b in blocks]
            acc = self._put(np.zeros((), dtype=np.uint32))
            _jax().block_until_ready(blocks_dev)
            _linkmodel.link().observe_transfer(
                nbytes, _time.perf_counter() - t0)
            _trace.add_bytes(tx=nbytes)
            # functional: the table is adopted only once every block's
            # checksum has come back right
            dev = self._build()
            for block in blocks_dev:
                *dev, acc = delta(*dev, block, acc)
            got = int(np.asarray(acc))
            count_trip(programs=len(blocks), waits=2)
            if got == expected:
                break
            self.counters["checksum_retries"] += 1
            EK._count_integrity("transfer_checksum_mismatch")
            if attempt == 2:
                raise RuntimeError(
                    "validator-table delta upload corrupted twice; "
                    "refusing to cache a poisoned row")
        self._dev = tuple(dev)
        for i, key in enumerate(missing):
            self._rows[key] = rows[i]
            self._ok[key] = bool(ok[i])
        self.counters["delta_updates"] += 1
        self.counters["delta_rows"] += n
        record_send(path, nbytes)
        return nbytes

    # --------------------------------------------------------- epoch pins

    def _sync_sets(self, announced: dict[bytes, tuple[bytes, tuple]]) -> None:
        """Reconcile the table's pinned sets with the announced epoch
        sets: new hashes delta-insert and pin, content mismatches under
        a known hash re-upload the set in full (counted), vanished
        hashes unpin (rows stay resident as plain LRU entries)."""
        for h in list(self._pinned_sets):
            if h not in announced:
                self._unpin(h)
        for h, (digest, keys) in announced.items():
            cur = self._pinned_sets.get(h)
            if cur is not None:
                if cur[0] == digest:
                    continue
                # set-hash mismatch: the epoch key no longer names the
                # content we pinned. Rows are content-keyed so no wrong
                # verdict is possible — but the pin bookkeeping is void:
                # drop it and re-upload the set in full.
                self.counters["hash_mismatches"] += 1
                self._unpin(h)
                missing = [k for k in dict.fromkeys(keys)
                           if k not in self._rows]
                self._insert_keys(missing, path="full")
                self.counters["full_set_uploads"] += 1
                self._pin(h, digest, keys)
                continue
            uniq = list(dict.fromkeys(keys))
            if len(uniq) > self.cap - 1:
                continue  # set larger than the table: serve unpinned
            while (len(self._pinned_sets) >= self._MAX_PINNED_SETS
                   or sum(len(v[1]) for v in self._pinned_sets.values())
                   + len(uniq) > self.cap - 1):
                if not self._pinned_sets:
                    break
                self._unpin(next(iter(self._pinned_sets)))
            missing = [k for k in uniq if k not in self._rows]
            self._insert_keys(missing)
            self._pin(h, digest, uniq)

    def _pin(self, set_hash: bytes, digest: bytes, keys) -> None:
        keys = tuple(dict.fromkeys(keys))
        self._pinned_sets[set_hash] = (digest, keys)
        for k in keys:
            self._pin_count[k] = self._pin_count.get(k, 0) + 1

    def _unpin(self, set_hash: bytes) -> None:
        _, keys = self._pinned_sets.pop(set_hash)
        for k in keys:
            c = self._pin_count.get(k, 0) - 1
            if c <= 0:
                self._pin_count.pop(k, None)
            else:
                self._pin_count[k] = c

    # ------------------------------------------------------------ staging

    def index(self, pubs: list[bytes], bucket: int,
              announced: dict | None = None):
        """The indexed send, host half: (ok_a (N,), idx (bucket,) uint16
        row indices with padding lanes on the identity row, the table's
        device snapshot (tx, ty, tz, tt, te)). The caller hands idx and
        the snapshot to the program that gathers (one upload with the
        batch's own words, no round trip of its own). Unseen keys
        delta-insert first (counted separately); raises _NoRoom when the
        batch cannot fit, which returns the caller to the full-key
        path."""
        with self._lock:
            if announced:
                self._sync_sets(announced)
            uniq = dict.fromkeys(pubs)
            if len(uniq) > self.cap - 1:
                raise _NoRoom(f"{len(uniq)} unique keys exceed table")
            missing = [k for k in uniq if k not in self._rows]
            # LRU-touch the batch's RESIDENT keys, and PROTECT them from
            # room-making eviction: the delta insert must never evict a
            # row this very batch is about to index (a crowded table
            # degrades via _NoRoom to the full-key path instead)
            for k in uniq:
                row = self._rows.pop(k, None)
                if row is not None:
                    self._rows[k] = row
            self._insert_keys(missing, protect=frozenset(uniq))
            idx = np.full(bucket, self.id_row, dtype=np.uint16)
            idx[:len(pubs)] = [self._rows[p] for p in pubs]
            ok_a = np.fromiter((self._ok[p] for p in pubs), dtype=bool,
                               count=len(pubs))
            dev = self._build()
            self.counters["indexed_batches"] += 1
        return ok_a, idx, dev

    def stage(self, pubs: list[bytes], bucket: int,
              announced: dict | None = None):
        """index() plus the gather as a program of its own: (ok_a (N,),
        (ax, ay, az, at) device arrays (20, bucket), index-vector wire
        bytes). The 2 B/lane index vector goes up un-awaited: the gather
        is ordered behind it on the device, and no caller needs it
        sooner."""
        from cometbft_tpu.libs import trace as _trace
        from cometbft_tpu.ops import ed25519_kernel as EK

        ok_a, idx, dev = self.index(pubs, bucket, announced=announced)
        coords = EK._gather_coords(dev[:4], self._put(idx))
        count_trip(programs=1)
        _trace.add_bytes(tx=idx.nbytes)
        return ok_a, coords, idx.nbytes

    def stats(self) -> dict:
        with self._lock:
            return dict(
                self.counters, rows=len(self._rows), capacity=self.cap,
                pinned_sets=len(self._pinned_sets),
                pinned_rows=len(self._pin_count),
                free_rows=len(self._free),
                # where the resident arrays live (None until built)
                devices=(sorted(str(d) for d in self._dev[0].devices())
                         if self._dev is not None else None),
            )


# ------------------------------------------------------ process registry

_reg_lock = threading.Lock()
_tables: dict[tuple[str, str], KeyTable] = {}
# announced epoch sets per scheme: set_hash -> (content_digest, keys),
# bounded (interleaved valsets across light-client churn epochs)
_announced: dict[str, dict[bytes, tuple[bytes, tuple]]] = {}
_MAX_ANNOUNCED = 4
_last_announced_hash: bytes | None = None


def announce_validator_set(vals) -> None:
    """Register the active validator set for epoch-keyed residency
    (validation.py calls this on every commit verification). Never
    raises — residency is an optimization layer. A per-object stamp
    makes repeat announcements of the same ValidatorSet object free; one
    without it is asked for its root, which a set keeps once computed
    (ValidatorSet.hash()). A set mutated after stamping just pins one epoch
    late, which costs delta bytes, never correctness (content-keyed rows)."""
    global _last_announced_hash
    try:
        if getattr(vals, "_wire_announced", False):
            return
        from cometbft_tpu.libs import trace as _trace

        # stage `header` (Merkle roots on the host), not `transfer`,
        # which stays the wire's time: the root of a set object not seen
        # yet is host hashing, though the reduced-send protocol asks for it
        with _trace.span("residency.announce", cat="header",
                         validators=len(vals.validators)):
            h = vals.hash()
        if h == _last_announced_hash:
            # another object of the set announced last (a copy of it a
            # height): stamp this one too, or every call hashes it again
            vals._wire_announced = True
            return
        by_scheme: dict[str, list[bytes]] = {}
        for v in vals.validators:
            by_scheme.setdefault(v.pub_key.type_(), []).append(
                v.pub_key.bytes_())
        with _reg_lock:
            for scheme, keys in by_scheme.items():
                if scheme not in ("ed25519", "sr25519"):
                    continue
                sets = _announced.setdefault(scheme, {})
                if h in sets:
                    continue
                digest = hashlib.sha256(b"".join(keys)).digest()
                while len(sets) >= _MAX_ANNOUNCED:
                    sets.pop(next(iter(sets)))
                sets[h] = (digest, tuple(keys))
            _last_announced_hash = h
        try:
            vals._wire_announced = True
        except Exception:  # noqa: BLE001 - slotted/frozen sets re-hash
            pass
    except Exception:  # noqa: BLE001 - residency must never break verify
        pass


def register_set(scheme: str, set_hash: bytes, keys: list[bytes]) -> None:
    """Direct epoch registration (tests, callers that know the set hash
    without a ValidatorSet object)."""
    global _last_announced_hash
    with _reg_lock:
        sets = _announced.setdefault(scheme, {})
        digest = hashlib.sha256(b"".join(keys)).digest()
        sets.pop(set_hash, None)
        while len(sets) >= _MAX_ANNOUNCED:
            sets.pop(next(iter(sets)))
        sets[set_hash] = (digest, tuple(keys))
        _last_announced_hash = None


def table_for(cache, put_key: str = "", device=None) -> KeyTable | None:
    """The (scheme, placement-key) replica, built lazily. None when the
    cache carries no scheme tag (a custom cache from tests)."""
    scheme = getattr(cache, "scheme", None)
    if scheme is None:
        return None
    with _reg_lock:
        tbl = _tables.get((scheme, put_key))
        if tbl is None:
            tbl = KeyTable(scheme, cache, _cfg["rows"], put_key=put_key,
                           device=device)
            _tables[(scheme, put_key)] = tbl
        return tbl


def _indexed(method, cache, pubs, bucket, put_key, device):
    """KeyTable.index or KeyTable.stage (`method`) for a batch on its
    (scheme, placement-key) replica, or None when the full-key path must
    serve (disabled,
    untagged cache, capacity overflow, or a failed delta upload)."""
    if not _cfg["enabled"]:
        return None
    tbl = table_for(cache, put_key=put_key, device=device)
    if tbl is None:
        return None
    scheme = tbl.scheme
    with _reg_lock:
        announced = dict(_announced.get(scheme, {}))
    try:
        return method(tbl, pubs, bucket, announced=announced)
    except _NoRoom:
        return None
    except Exception:  # noqa: BLE001 - degraded, never a wrong verdict
        from cometbft_tpu.libs import log as _log

        try:
            _log.default().error(
                "reduced-send residency failed; falling back to the "
                "full-key path", scheme=scheme, put_key=put_key)
        except Exception:  # noqa: BLE001
            pass
        return None


def index(cache, pubs: list[bytes], bucket: int, put_key: str = "",
          device=None):
    """Try the reduced-send indexed path for a batch whose program
    gathers for itself: (ok_a, idx, (tx, ty, tz, tt, te)) or None."""
    return _indexed(KeyTable.index, cache, pubs, bucket, put_key, device)


def stage(cache, pubs: list[bytes], bucket: int, put_key: str = "",
          device=None):
    """Try the reduced-send indexed path for a batch, gathered:
    (ok_a, a_dev, index_bytes) or None."""
    return _indexed(KeyTable.stage, cache, pubs, bucket, put_key, device)


def invalidate_device(index: int) -> int:
    """Drop every replica placed on mesh fault domain `index` (put_key
    "devN"): called on chip readmission so exactly that chip's tables
    re-seed on the next shard — a healed device must not serve arrays
    from before its fault. Returns the number of tables dropped."""
    key = f"dev{index}"
    with _reg_lock:
        drop = [k for k in _tables if k[1] == key]
        for k in drop:
            del _tables[k]
    return len(drop)


def stats() -> dict:
    """The crypto_health staging `wire` subsection: send-path
    accounting plus per-replica table counters."""
    with _reg_lock:
        tables = {f"{s}/{pk}" if pk else s: t.stats()
                  for (s, pk), t in _tables.items()}
    out = send_stats()
    out["enabled"] = _cfg["enabled"]
    out["tables"] = tables
    return out


def reset() -> None:
    """Forget every table, announcement, and send counter (tests)."""
    global _last_announced_hash
    with _reg_lock:
        _tables.clear()
        _announced.clear()
        _last_announced_hash = None
    reset_send_stats()
