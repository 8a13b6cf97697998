"""Host-side numpy packing between wire bytes and device limb arrays.

Field elements travel to the device as (B, 20) int32 arrays of radix-2^13
limbs (little-endian); scalars travel as (B, 253) int32 bit arrays consumed
by the Straus ladder. Packing is vectorized numpy so a 10k-signature commit
stages in well under a millisecond of host time.
"""

from __future__ import annotations

import threading

import numpy as np

RADIX = 13
NLIMBS = 20  # 20 * 13 = 260 bits >= 255
MASK = (1 << RADIX) - 1
SCALAR_BITS = 253  # ZIP-215 enforces s < L < 2^253; k = H mod L < 2^253

_POW2 = (1 << np.arange(RADIX, dtype=np.int64)).astype(np.int64)


def int_to_limbs(x: int) -> np.ndarray:
    """Single Python int -> (20,) int32 limb array."""
    out = np.zeros(NLIMBS, dtype=np.int32)
    for i in range(NLIMBS):
        out[i] = x & MASK
        x >>= RADIX
    assert x == 0, "value exceeds 260 bits"
    return out


def limbs_to_int(limbs: np.ndarray) -> int:
    """(..., 20) limb array -> Python int (single element only)."""
    acc = 0
    for i in reversed(range(NLIMBS)):
        acc = (acc << RADIX) + int(limbs[..., i])
    return acc


def bytes32_to_bits(data: np.ndarray) -> np.ndarray:
    """(B, 32) uint8 -> (B, 256) uint8 bits, little-endian bit order."""
    return np.unpackbits(data, axis=-1, bitorder="little")


def bits_to_limbs(bits: np.ndarray) -> np.ndarray:
    """(B, <=260) bit array -> (B, 20) int32 limbs."""
    b = bits.shape[0]
    padded = np.zeros((b, NLIMBS * RADIX), dtype=np.int64)
    padded[:, : bits.shape[1]] = bits
    return (padded.reshape(b, NLIMBS, RADIX) * _POW2).sum(axis=-1).astype(np.int32)


def encodings_to_point_inputs(enc: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(B, 32) uint8 compressed-point encodings -> (y_limbs (B,20) int32,
    sign (B,) int32). The y candidate is the low 255 bits, NOT reduced — the
    device field ops are mod-p semantically, so non-canonical y (ZIP-215)
    needs no host handling."""
    bits = bytes32_to_bits(enc)
    sign = bits[:, 255].astype(np.int32)
    y_limbs = bits_to_limbs(bits[:, :255])
    return y_limbs, sign


def scalars_to_bits(scalars: list[int]) -> np.ndarray:
    """List of B ints (< 2^253) -> (B, 253) int32 bit array."""
    raw = np.frombuffer(
        b"".join(s.to_bytes(32, "little") for s in scalars), dtype=np.uint8
    ).reshape(len(scalars), 32)
    return bytes32_to_bits(raw)[:, :SCALAR_BITS].astype(np.int32)


def bytes_to_words(raw: np.ndarray) -> np.ndarray:
    """(B, 32) uint8 -> (B, 8) uint32 little-endian words — the packed
    host->device wire layout consumed by ops.unpack on device."""
    return np.ascontiguousarray(raw).view("<u4").reshape(raw.shape[0], 8)


def scalars_to_words(scalars) -> np.ndarray:
    """B scalars (< 2^256) -> (B, 8) uint32 word array. Accepts a list of
    ints, a bytes blob of B concatenated little-endian 32-byte values, or
    a (B, 32) uint8 array — the bytes/array forms are the staging fast
    path: no per-row int round trip, one view."""
    if isinstance(scalars, (bytes, bytearray, memoryview)):
        raw = np.frombuffer(bytes(scalars), dtype=np.uint8).reshape(-1, 32)
        return bytes_to_words(raw)
    if isinstance(scalars, np.ndarray):
        assert scalars.dtype == np.uint8 and scalars.shape[1] == 32
        return bytes_to_words(scalars)
    raw = np.frombuffer(
        b"".join(s.to_bytes(32, "little") for s in scalars), dtype=np.uint8
    ).reshape(len(scalars), 32)
    return bytes_to_words(raw)


class StagingPool:
    """Per-bucket pool of (3, 8, bucket) uint32 staging blocks — the r/s/k
    word arrays of one device batch, batch-minor, preallocated. The
    stagers (ed25519_kernel.stage_batch / sr25519_kernel.stage_rows_sr)
    pack rows in place into a leased block instead of allocating, joining
    and transposing fresh arrays per batch; the verify thunk releases the
    block once its batch resolves. A block that is never released (error
    paths, bench callers that keep the arrays) is simply garbage-collected
    — the pool is a bounded free list, not a ledger. Leased blocks are
    dirty: stagers overwrite every word, padding lanes included.

    Double-buffer contract (reduced-send protocol): a block is ONE
    contiguous array, so the whole r/s/k payload crosses the link as a
    single transfer (a host argument of the batch's first program in the
    ed25519 dispatch closures, un-awaited; `jnp.asarray(block)` in
    sr25519's), and a block stays leased for its batch's full flight —
    the transfer may read it until the batch resolves — so the steady
    state holds two blocks per bucket (batch N in transfer/compute while
    batch N+1 stages), which is why warm() preallocates pairs and
    MAX_FREE_PER_SHAPE is sized above 2. The dispatch-side half of the
    contract is ops/dispatch.DoubleBuffer: two in-flight slots per fault
    domain, so batch N's h2d overlaps batch N-1's compute.

    The free list is keyed by full block shape: the classic path leases
    (3, 8, bucket) r/s/k planes, the device-challenge path
    (ops/challenge.py) leases flat 1-D word blocks via lease_flat —
    release() routes either kind home by its shape."""

    MAX_FREE_PER_SHAPE = 4

    def __init__(self) -> None:
        self._free: dict[tuple, list[np.ndarray]] = {}
        self._lock = threading.Lock()
        self.leases = 0
        self.reuses = 0

    def _lease_shape(self, shape: tuple) -> np.ndarray:
        with self._lock:
            self.leases += 1
            free = self._free.get(shape)
            if free:
                self.reuses += 1
                return free.pop()
        return np.empty(shape, dtype=np.uint32)

    def lease(self, bucket: int) -> np.ndarray:
        return self._lease_shape((3, 8, bucket))

    def lease_flat(self, nwords: int) -> np.ndarray:
        """A flat (nwords,) uint32 block — the device-challenge wire
        layout (R words, s words, descriptor stream)."""
        return self._lease_shape((nwords,))

    def release(self, block: np.ndarray | None) -> None:
        if block is None:
            return
        with self._lock:
            free = self._free.setdefault(block.shape, [])
            if len(free) < self.MAX_FREE_PER_SHAPE:
                free.append(block)

    def _warm_shape(self, shape: tuple, pairs: int) -> None:
        with self._lock:
            free = self._free.setdefault(shape, [])
            while len(free) < min(pairs, self.MAX_FREE_PER_SHAPE):
                free.append(np.empty(shape, dtype=np.uint32))

    def warm(self, bucket: int, pairs: int = 2) -> None:
        """Preallocate `pairs` blocks for a bucket so the first flushes
        of the double-buffered steady state never allocate on the hot
        path (scheduler warmup calls this along the bucket ladder)."""
        self._warm_shape((3, 8, bucket), pairs)

    def warm_flat(self, nwords: int, pairs: int = 2) -> None:
        """warm() for the device-challenge flat blocks."""
        self._warm_shape((nwords,), pairs)

    def stats(self) -> dict:
        with self._lock:
            return {"leases": self.leases, "reuses": self.reuses,
                    "free_blocks": sum(len(v) for v in self._free.values())}


POOL = StagingPool()
