"""Shared-prefix byte rows — the host half of the reduced-send protocol.

The canonical sign-bytes of the votes in one commit differ only in the
timestamp field (and the NIL votes' block_id omission): ~105 of ~122
bytes per row are one shared per-(height, round, chain) prefix. The old
row builder materialized every row in full, so a 10k-validator commit
copied ~1.2 MB of identical prefix bytes per verification — and the
staging fast path then joined them AGAIN into the hash-input matrix.

These types carry the factored form end to end:

  SharedPrefixRows   the commit-level row container (built by
                     types/commit.vote_sign_bytes_all): one prefix,
                     per-row suffixes, and a small exceptions map for
                     rows that cannot share (NIL heads, an off-length
                     timestamp encoding). Indexing materializes real
                     bytes, so every legacy consumer sees the exact
                     rows it always did.
  PrefixedMsg        one row in factored form. Flows through the verify
                     plane (scheduler groups, kernel staging) without
                     materializing; ops/hashvec.assemble_prefixed_rows
                     reassembles whole runs on the batch axis with ONE
                     broadcast of the shared prefix. bytes(m) gives the
                     exact row for host oracles.

  MsgBlock           a batch's messages as COLUMNS (PR 31): a few classes
                     of rows, each one front (bytes) and one uint8
                     matrix of bodies, and two index vectors that say
                     which class and which matrix row every message is.
                     A commit of 10,240 votes is two or three classes;
                     selecting, splitting by scheme and concatenating
                     are index arithmetic, and no per-row object exists
                     between the commit's array pass and the hash-input
                     matrix. Indexing and iteration give exact bytes.

Layering: libs so both types/ (row construction) and ops/ (staging
reassembly) can import it.
"""

from __future__ import annotations

from collections.abc import Sequence

import numpy as np


class PrefixedMsg:
    """One message in (shared prefix, per-row suffix) factored form.
    len() is O(1); bytes() materializes the exact row. Staging groups
    consecutive rows whose `prefix` is the SAME OBJECT into one
    batch-axis broadcast, so builders must reuse one prefix object per
    run (SharedPrefixRows does)."""

    __slots__ = ("prefix", "suffix")

    def __init__(self, prefix: bytes, suffix: bytes):
        self.prefix = prefix
        self.suffix = suffix

    def __len__(self) -> int:
        return len(self.prefix) + len(self.suffix)

    def __bytes__(self) -> bytes:
        return self.prefix + self.suffix

    def tobytes(self) -> bytes:
        return self.prefix + self.suffix

    def __eq__(self, other) -> bool:
        if isinstance(other, PrefixedMsg):
            return (self.prefix == other.prefix
                    and self.suffix == other.suffix) or \
                bytes(self) == bytes(other)
        if isinstance(other, (bytes, bytearray)):
            return bytes(self) == bytes(other)
        return NotImplemented

    def __hash__(self) -> int:
        return hash(bytes(self))

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"PrefixedMsg({len(self.prefix)}B prefix + "
                f"{len(self.suffix)}B suffix)")


def as_bytes(msg) -> bytes:
    """Materialize a message that may be a PrefixedMsg (host-oracle and
    serial-verifier boundaries)."""
    return bytes(msg) if isinstance(msg, PrefixedMsg) else msg


def msg_lengths(msgs) -> np.ndarray:
    """(N,) int64 message lengths of a MsgBlock or a list of rows."""
    if isinstance(msgs, MsgBlock):
        return msgs.lengths()
    return np.fromiter(map(len, msgs), np.int64, len(msgs))


_NO_ROWS = np.zeros(0, dtype=np.intp)


class MsgBlock(Sequence):
    """N messages as columns: message i is `fronts[cls[i]]` followed by
    row `pos[i]` of the uint8 matrix `bodies[cls[i]]`. take() and
    concat() move index vectors only and share the matrices, so a class
    may have rows no message points at; a class no message points at is
    skipped wherever classes are walked (present())."""

    __slots__ = ("fronts", "bodies", "cls", "pos")

    def __init__(self, fronts: list, bodies: list, cls: np.ndarray,
                 pos: np.ndarray):
        self.fronts = fronts
        self.bodies = bodies
        self.cls = cls
        self.pos = pos

    @classmethod
    def of(cls, msgs) -> "MsgBlock":
        return msgs if isinstance(msgs, MsgBlock) else cls.from_list(msgs)

    @classmethod
    def from_list(cls, msgs) -> "MsgBlock":
        """The one lane loop that turns rows (bytes-like or PrefixedMsg)
        into columns: a class a (prefix, suffix length), plain rows with
        the empty prefix, classes in the order their first row comes."""
        n = len(msgs)
        klass = np.empty(n, dtype=np.intp)
        pos = np.empty(n, dtype=np.intp)
        ids: dict = {}
        parts: list[list] = []
        for i, m in enumerate(msgs):
            if type(m) is PrefixedMsg:
                key, part = (m.prefix, len(m.suffix)), m.suffix
            else:
                part = m if type(m) is bytes else bytes(m)
                key = (b"", len(part))
            c = ids.get(key)
            if c is None:
                c = ids[key] = len(parts)
                parts.append([])
            members = parts[c]
            klass[i] = c
            pos[i] = len(members)
            members.append(part)
        fronts = [key[0] for key in ids]
        bodies = [
            np.frombuffer(b"".join(members), dtype=np.uint8).reshape(
                len(members), key[1])
            for key, members in zip(ids, parts)]
        return cls(fronts, bodies, klass, pos)

    @classmethod
    def concat(cls, blocks: list) -> "MsgBlock":
        if len(blocks) == 1:
            return blocks[0]
        fronts: list = []
        bodies: list = []
        klass = []
        for b in blocks:
            klass.append(b.cls + len(fronts))
            fronts += b.fronts
            bodies += b.bodies
        return cls(fronts, bodies, np.concatenate(klass),
                   np.concatenate([b.pos for b in blocks]))

    def __len__(self) -> int:
        return len(self.cls)

    def take(self, idxs) -> "MsgBlock":
        return MsgBlock(self.fronts, self.bodies, self.cls[idxs],
                        self.pos[idxs])

    def __getitem__(self, i):
        if isinstance(i, slice):
            return self.take(i)
        c = self.cls[i]
        return self.fronts[c] + self.bodies[c][self.pos[i]].tobytes()

    def __iter__(self):
        return iter(self.tolist())

    def present(self) -> list[tuple[int, np.ndarray]]:
        """(class, the lanes in it, ascending) for every class that has
        a message, in the order of each class's first lane."""
        if len(self.fronts) == 1:
            return [(0, np.arange(len(self.cls)))] if len(self.cls) else []
        order = np.argsort(self.cls, kind="stable")
        cuts = np.flatnonzero(np.diff(self.cls[order])) + 1
        runs = np.split(order, cuts) if len(order) else []
        runs.sort(key=lambda lanes: lanes[0])
        return [(int(self.cls[lanes[0]]), lanes) for lanes in runs]

    def lengths(self) -> np.ndarray:
        per_class = np.fromiter(
            (len(f) + b.shape[1] for f, b in zip(self.fronts, self.bodies)),
            np.int64, len(self.fronts))
        return per_class[self.cls]

    def matrix(self, mlen: int) -> np.ndarray:
        """The (N, mlen) uint8 matrix of messages that all have mlen
        bytes: a class's front written once as a column block."""
        out = np.empty((len(self.cls), mlen), dtype=np.uint8)
        for c, lanes in self.present():
            front = self.fronts[c]
            out[lanes, :len(front)] = np.frombuffer(front, dtype=np.uint8)
            out[lanes, len(front):] = self.bodies[c][self.pos[lanes]]
        return out

    def tolist(self) -> list[bytes]:
        """Every message as bytes (host oracles, the mesh, BLS, legacy
        readers): the one place a block is cut into N objects again."""
        out: list = [None] * len(self.cls)
        for c, lanes in self.present():
            front = self.fronts[c]
            w = self.bodies[c].shape[1]
            blob = self.bodies[c][self.pos[lanes]].tobytes()
            for j, i in enumerate(lanes.tolist()):
                out[i] = front + blob[j * w:(j + 1) * w]
        return out


class SharedPrefixRows(Sequence):
    """An immutable sequence of byte rows where row[i] is either
    `prefix + suffixes[i]` or an explicit exception row. Indexing and
    iteration yield real bytes (drop-in for the old list); rows_for()
    yields the factored PrefixedMsg form for the staging pipeline.

    Two builders fill it (types/commit.py): the Writer loop hands the
    suffix list and the exceptions map themselves; the array pass hands
    a MsgBlock (`block`, class 0 the rows that share `prefix`), from
    which the list and the map are cut only if a legacy reader asks."""

    __slots__ = ("prefix", "block", "_suffixes", "_exceptions")

    def __init__(self, prefix: bytes, suffixes: list | None = None,
                 exceptions: dict[int, bytes] | None = None,
                 block: MsgBlock | None = None):
        self.prefix = prefix
        self.block = block
        self._suffixes = suffixes
        if block is None and exceptions is None:
            exceptions = {}
        self._exceptions = exceptions

    def _cut(self) -> None:
        rows = self.block.tolist()
        shared = (self.block.cls == 0).tolist()
        plen = len(self.prefix)
        self._suffixes = [r[plen:] if s else None
                          for r, s in zip(rows, shared)]
        self._exceptions = {i: r for i, (r, s)
                            in enumerate(zip(rows, shared)) if not s}

    @property
    def suffixes(self) -> list:
        if self._suffixes is None:
            self._cut()
        return self._suffixes

    @property
    def exceptions(self) -> dict[int, bytes]:
        if self._exceptions is None:
            self._cut()
        return self._exceptions

    def __len__(self) -> int:
        if self.block is not None:
            return len(self.block)
        return len(self._suffixes)

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self[j] for j in range(*i.indices(len(self)))]
        if i < 0:
            i += len(self)
        if self._suffixes is None:
            return self.block[i]
        exc = self.exceptions.get(i)
        if exc is not None:
            return exc
        return self.prefix + self.suffixes[i]

    def rows_for(self, idxs) -> list:
        """The factored rows for the selected indices: PrefixedMsg for
        shared rows (all referencing THE one prefix object, so staging
        batches them as a single run), exact bytes for exceptions."""
        out = []
        exceptions, suffixes = self.exceptions, self.suffixes
        for i in idxs:
            exc = exceptions.get(i)
            out.append(exc if exc is not None
                       else PrefixedMsg(self.prefix, suffixes[i]))
        return out

    def take(self, idxs) -> MsgBlock:
        """The selected rows as columns, no object a row: index vectors
        over the block the array pass made, or (rows of the Writer loop)
        the lane loop of MsgBlock.from_list over rows_for()."""
        if self.block is not None:
            return self.block.take(np.asarray(idxs, dtype=np.intp))
        return MsgBlock.from_list(self.rows_for(idxs))

    def shared_fraction(self) -> float:
        """How much of the container actually shares the prefix (tests,
        telemetry)."""
        n = len(self)
        if not n:
            return 0.0
        if self.block is not None:
            return int((self.block.cls == 0).sum()) / n
        return (n - len(self._exceptions)) / n
