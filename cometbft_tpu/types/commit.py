"""Commit and CommitSig (reference: types/block.go:574-900).

A Commit is the +2/3 precommit aggregate persisted in every block's
LastCommit; each CommitSig records one validator's precommit (or absence).
Commit.vote_sign_bytes reconstructs the exact canonical bytes each validator
signed — the input rows of the TPU verification batch.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from cometbft_tpu import crypto
from cometbft_tpu.crypto import merkle
from cometbft_tpu.libs import trace
from cometbft_tpu.types.basic import BlockID, BlockIDFlag, SignedMsgType
from cometbft_tpu.types.vote import Vote
from cometbft_tpu.utils import cmttime
from cometbft_tpu.utils import protobuf as pb

MAX_COMMIT_OVERHEAD_BYTES = 94
MAX_COMMIT_SIG_BYTES = 109

# Rows from which the array pass builds a commit's sign-rows faster than
# one Writer a signature: where it first won on the chip's host (32 rows:
# 156 us against 169; 36: 171 against 165; PERF.md section 6, PR 29, has
# the readings from 1 to 10,240 rows).
VECTOR_SIGN_ROWS_MIN = 36

# Rows from which types/validation._commit_rows selects, tallies and cuts a
# commit's rows with index vectors (the block path) and no longer walks
# its signatures: between the last size at which the lane path still won
# on the chip's host and the first at which the block path did, with one
# key type (150 rows: 162.6 us a call against 166.2; 200: 193.0 against
# 189.1; with two key types the block path wins from 150 on; PERF.md
# section 6, PR 31, has tools/row_block_crossover.py's table from 16 to
# 10,240 rows). Never below VECTOR_SIGN_ROWS_MIN: the block path takes the
# columns the array pass made.
ROW_BLOCK_MIN = 192

_TS_TAG = 5 << 3 | 2  # CanonicalVote field 5, wire 2: the timestamp message


@dataclass
class CommitSig:
    """types/block.go:586-600."""

    block_id_flag: BlockIDFlag
    validator_address: bytes = b""
    timestamp: cmttime.Timestamp = field(default_factory=cmttime.Timestamp.zero)
    signature: bytes = b""

    @classmethod
    def absent(cls) -> "CommitSig":
        return cls(block_id_flag=BlockIDFlag.ABSENT)

    def for_block(self) -> bool:
        return self.block_id_flag == BlockIDFlag.COMMIT

    def block_id(self, commit_block_id: BlockID) -> BlockID:
        """types/block.go:632-645."""
        if self.block_id_flag == BlockIDFlag.COMMIT:
            return commit_block_id
        return BlockID()

    def validate_basic(self) -> None:
        if self.block_id_flag not in (BlockIDFlag.ABSENT, BlockIDFlag.COMMIT, BlockIDFlag.NIL):
            raise ValueError(f"unknown BlockIDFlag: {self.block_id_flag}")
        if self.block_id_flag == BlockIDFlag.ABSENT:
            if self.validator_address:
                raise ValueError("validator address is present for absent CommitSig")
            if not self.timestamp.is_zero():
                raise ValueError("time is present for absent CommitSig")
            if self.signature:
                raise ValueError("signature is present for absent CommitSig")
        else:
            if len(self.validator_address) != crypto.ADDRESS_SIZE:
                raise ValueError("expected ValidatorAddress size to be 20 bytes")
            if not self.signature:
                raise ValueError("signature is missing")
            if len(self.signature) > crypto.MAX_SIGNATURE_SIZE:
                raise ValueError("signature is too big")

    def to_proto(self) -> bytes:
        w = pb.Writer()
        w.uvarint(1, int(self.block_id_flag))
        w.bytes(2, self.validator_address)
        w.message(3, pb.timestamp_bytes(self.timestamp.seconds, self.timestamp.nanos), always=True)
        w.bytes(4, self.signature)
        return w.output()

    @classmethod
    def from_proto(cls, data: bytes) -> "CommitSig":
        r = pb.Reader(data)
        cs = cls(block_id_flag=BlockIDFlag.ABSENT)
        while not r.at_end():
            f, w = r.read_tag()
            if f == 1:
                cs.block_id_flag = BlockIDFlag(r.read_uvarint())
            elif f == 2:
                cs.validator_address = r.read_bytes()
            elif f == 3:
                secs, nanos = r.read_timestamp()
                cs.timestamp = cmttime.Timestamp(secs, nanos)
            elif f == 4:
                cs.signature = r.read_bytes()
            else:
                r.skip(w)
        return cs


@dataclass
class Commit:
    """types/block.go:700-760."""

    height: int
    round_: int
    block_id: BlockID
    signatures: list[CommitSig]
    _hash: bytes | None = field(default=None, repr=False, compare=False)
    # chain_id -> rows; a dict (not a single-slot tuple) so alternating-
    # chain callers (light-client cross-chain paths, tests) don't silently
    # degrade to zero cache hits (ADVICE round-5). Bounded: a Commit is
    # only ever verified against a handful of chain ids.
    _sign_rows: dict | None = field(default=None, repr=False, compare=False)

    _MAX_SIGN_ROW_CHAINS = 4

    def size(self) -> int:
        return len(self.signatures)

    def get_vote(self, val_idx: int) -> Vote:
        """Reconstruct the precommit Vote for signature val_idx
        (types/block.go:857-869)."""
        cs = self.signatures[val_idx]
        return Vote(
            type_=SignedMsgType.PRECOMMIT,
            height=self.height,
            round_=self.round_,
            block_id=cs.block_id(self.block_id),
            timestamp=cs.timestamp,
            validator_address=cs.validator_address,
            validator_index=val_idx,
            signature=cs.signature,
        )

    def vote_sign_bytes(self, chain_id: str, val_idx: int) -> bytes:
        """types/block.go:880-883 — the batch-verification row builder."""
        return self.get_vote(val_idx).sign_bytes(chain_id)

    def vote_sign_bytes_all(self, chain_id: str):
        """All signatures' canonical sign-bytes in one pass, as a
        SharedPrefixRows container (libs/prefixrows.py) — indexing is
        byte-identical to vote_sign_bytes(chain_id, i) per index
        (asserted by tests). The CanonicalVote rows of one commit differ
        only in the timestamp field and the NIL-vote block_id omission,
        so the length varint + type/height/round/block_id head is built
        ONCE and kept FACTORED: COMMIT rows whose timestamp encodes to
        the commit's modal length store only their ~17-byte suffix
        (timestamp + chain tail); NIL votes and odd-length timestamps
        materialize as exception rows. The factored form flows through
        validation into kernel staging, where the whole run reassembles
        on the batch axis with one prefix broadcast instead of N row
        copies (the reduced-send protocol's host half).

        Two builders make the same three parts, chosen by the row count
        alone: from VECTOR_SIGN_ROWS_MIN rows on, _sign_row_parts_vector
        encodes every timestamp and cuts every suffix in one array pass;
        below it numpy's fixed cost loses to _sign_row_parts_scalar's one
        Writer a signature, which also takes a commit whose stamps do not
        fit int64. The span says which ran (`path`) and how many rows it
        built (`rows`); a hit of the per-object memo says `cached`."""
        if self._sign_rows is None:
            self._sign_rows = {}
        rows = self._sign_rows.get(chain_id)
        with trace.span("commit.sign_bytes", cat="signbytes",
                        cached=rows is not None) as sp:
            if rows is None:
                rows, path = self._build_sign_rows(chain_id)
                sp.set(rows=len(rows), path=path)
        return rows

    def _build_sign_rows(self, chain_id: str):
        from cometbft_tpu.libs.prefixrows import SharedPrefixRows
        from cometbft_tpu.types import canonical

        w = pb.Writer()
        w.uvarint(1, int(SignedMsgType.PRECOMMIT))
        w.sfixed64(2, self.height)
        w.sfixed64(3, self.round_)
        head_nil = w.output()  # NIL votes: block_id field omitted
        w.message(4, canonical.canonical_block_id_bytes(self.block_id))
        head_commit = w.output()
        tail = pb.Writer().string(6, chain_id).output()
        args = (self.signatures, head_commit, head_nil, tail)
        path, parts = "scalar", None
        if len(self.signatures) >= VECTOR_SIGN_ROWS_MIN:
            try:
                path, parts = "vector", _sign_row_parts_vector(*args)
            except OverflowError:
                pass  # a stamp past int64: the Writer masks it to 64 bits
        if parts is None:
            parts = _sign_row_parts_scalar(*args)
        rows = SharedPrefixRows(*parts)
        if len(self._sign_rows) >= self._MAX_SIGN_ROW_CHAINS:
            self._sign_rows.pop(next(iter(self._sign_rows)))
        self._sign_rows[chain_id] = rows
        return rows, path

    def hash(self) -> bytes:
        """Merkle root over CommitSig protos (types/block.go Commit.Hash)."""
        if self._hash is None:
            self._hash = merkle.hash_from_byte_slices(
                [cs.to_proto() for cs in self.signatures]
            )
        return self._hash

    def validate_basic(self) -> None:
        if self.height < 0:
            raise ValueError("negative Height")
        if self.round_ < 0:
            raise ValueError("negative Round")
        if self.height >= 1:
            if self.block_id.is_nil():
                raise ValueError("commit cannot be for nil block")
            if not self.signatures:
                raise ValueError("no signatures in commit")
            for cs in self.signatures:
                cs.validate_basic()

    def to_proto(self) -> bytes:
        w = pb.Writer()
        w.varint_i64(1, self.height)
        w.varint_i64(2, self.round_)
        w.message(3, self.block_id.to_proto(), always=True)
        for cs in self.signatures:
            w.message(4, cs.to_proto(), always=True)
        return w.output()

    @classmethod
    def from_proto(cls, data: bytes) -> "Commit":
        r = pb.Reader(data)
        c = cls(height=0, round_=0, block_id=BlockID(), signatures=[])
        while not r.at_end():
            f, w = r.read_tag()
            if f == 1:
                c.height = r.read_varint_i64()
            elif f == 2:
                c.round_ = r.read_varint_i64()
            elif f == 3:
                c.block_id = BlockID.from_proto(r.read_bytes())
            elif f == 4:
                c.signatures.append(CommitSig.from_proto(r.read_bytes()))
            else:
                r.skip(w)
        return c


# The two builders of Commit.vote_sign_bytes_all's rows. Both return what
# SharedPrefixRows takes: the prefix of the COMMIT rows whose timestamp has
# the commit's modal encoded length (the length varint in front of the
# body pins the total row length, so an off-length timestamp cannot share
# it), then either a suffix `ts_tag | len | timestamp | tail` for each of
# those rows, None for the others and the others whole by index (the
# Writer loop), or all of them as one MsgBlock (the array pass).


def _sign_row_parts_scalar(signatures, head_commit: bytes, head_nil: bytes,
                           tail: bytes):
    """One Writer a signature: the builder of small commits, of stamps
    that do not fit int64, and the tests' oracle for the array pass."""
    from collections import Counter

    ts_tag = bytes([_TS_TAG])
    ts_all = [pb.timestamp_bytes(cs.timestamp.seconds, cs.timestamp.nanos)
              for cs in signatures]
    commit_lens = Counter(
        len(ts) for ts, cs in zip(ts_all, signatures)
        if cs.block_id_flag == BlockIDFlag.COMMIT)
    modal_ts_len = commit_lens.most_common(1)[0][0] if commit_lens else 0
    prefix = _shared_prefix(head_commit, modal_ts_len, tail)
    suffixes: list = []
    exceptions: dict[int, bytes] = {}
    for i, (ts, cs) in enumerate(zip(ts_all, signatures)):
        if (cs.block_id_flag == BlockIDFlag.COMMIT
                and len(ts) == modal_ts_len):
            suffixes.append(ts_tag + pb.encode_uvarint(len(ts)) + ts + tail)
            continue
        head = (head_commit if cs.block_id_flag == BlockIDFlag.COMMIT
                else head_nil)
        body = head + ts_tag + pb.encode_uvarint(len(ts)) + ts + tail
        suffixes.append(None)
        exceptions[i] = pb.encode_uvarint(len(body)) + body
    return prefix, suffixes, exceptions


def _sign_row_parts_vector(signatures, head_commit: bytes, head_nil: bytes,
                           tail: bytes):
    """The same rows in one array pass, and left as columns: the
    timestamps of all rows as one ragged matrix (pb.timestamp_rows) set
    between the constant columns, then one compaction a CLASS of rows.
    Rows that are for the block or not alike and whose timestamps are as
    long have one front (length varint + head) and suffixes of one width:
    a commit is two or three such classes, class 0 the one that shares
    the prefix, and no row is cut out as an object (MsgBlock;
    SharedPrefixRows cuts them for a reader that asks). OverflowError
    where a stamp does not fit int64."""
    from cometbft_tpu.libs.prefixrows import MsgBlock

    n = len(signatures)
    seconds = np.array([cs.timestamp.seconds for cs in signatures],
                       dtype=np.int64)
    nanos = np.array([cs.timestamp.nanos for cs in signatures],
                     dtype=np.int64)
    for_block = np.array([cs.block_id_flag == BlockIDFlag.COMMIT
                          for cs in signatures], dtype=bool)
    ts_cells, ts_keep, ts_len = pb.timestamp_rows(seconds, nanos)
    # a timestamp is 22 bytes at most: its length varint is one byte
    ts_end = 2 + ts_cells.shape[1]
    cells = np.empty((n, ts_end + len(tail)), dtype=np.uint8)
    cells[:, 0] = _TS_TAG
    cells[:, 1] = ts_len
    cells[:, 2:ts_end] = ts_cells
    cells[:, ts_end:] = np.frombuffer(tail, dtype=np.uint8)
    keep = np.ones(cells.shape, dtype=bool)
    keep[:, 2:ts_end] = ts_keep

    commit_lens = ts_len[for_block]
    modal_ts_len = 0
    if len(commit_lens):
        counts = np.bincount(commit_lens)
        top = np.flatnonzero(counts == counts.max())
        if len(top) > 1:  # Counter.most_common's choice: the one met first
            top = commit_lens[np.isin(commit_lens, top)]
        modal_ts_len = int(top[0])
    prefix = _shared_prefix(head_commit, modal_ts_len, tail)

    # a row's class key: (timestamp length, for the block?)
    keys = ts_len.astype(np.intp) * 2 + for_block
    shared = 2 * modal_ts_len + 1
    fronts, bodies = [prefix], [None]
    cls = np.zeros(n, dtype=np.intp)
    pos = np.empty(n, dtype=np.intp)
    for key in [shared] + [k for k in np.unique(keys).tolist()
                           if k != shared]:
        members = np.flatnonzero(keys == key)
        width = 2 + key // 2 + len(tail)
        body = cells[members][keep[members]].reshape(len(members), width)
        pos[members] = np.arange(len(members))
        if key == shared:
            bodies[0] = body
            continue
        head = head_commit if key % 2 else head_nil
        cls[members] = len(fronts)
        fronts.append(pb.encode_uvarint(len(head) + width) + head)
        bodies.append(body)
    return prefix, None, None, MsgBlock(fronts, bodies, cls, pos)


def _shared_prefix(head_commit: bytes, modal_ts_len: int,
                   tail: bytes) -> bytes:
    modal_body = (len(head_commit) + 1
                  + len(pb.encode_uvarint(modal_ts_len)) + modal_ts_len
                  + len(tail))
    return pb.encode_uvarint(modal_body) + head_commit


@dataclass
class ExtendedCommitSig:
    """CommitSig + vote-extension data (types/block.go:741-800, ABCI 2.0)."""

    commit_sig: CommitSig
    extension: bytes = b""
    extension_signature: bytes = b""

    def validate_basic(self) -> None:
        self.commit_sig.validate_basic()
        if self.commit_sig.block_id_flag == BlockIDFlag.COMMIT:
            return
        if self.extension:
            raise ValueError("vote extension is present for non-commit CommitSig")
        if self.extension_signature:
            raise ValueError("vote extension signature is present for non-commit CommitSig")


@dataclass
class ExtendedCommit:
    """types/block.go:708-856: a commit carrying vote extensions, stored for
    the latest height to rebuild LastCommit precommits (for PrepareProposal)."""

    height: int
    round_: int
    block_id: BlockID
    extended_signatures: list[ExtendedCommitSig]

    def to_commit(self) -> Commit:
        return Commit(
            height=self.height,
            round_=self.round_,
            block_id=self.block_id,
            signatures=[e.commit_sig for e in self.extended_signatures],
        )

    def size(self) -> int:
        return len(self.extended_signatures)

    def get_extended_vote(self, val_idx: int) -> Vote:
        e = self.extended_signatures[val_idx]
        v = self.to_commit().get_vote(val_idx)
        v.extension = e.extension
        v.extension_signature = e.extension_signature
        return v

    def ensure_extensions(self, required: bool) -> None:
        """types/block.go:765-785."""
        for e in self.extended_signatures:
            cs = e.commit_sig
            if required and cs.block_id_flag == BlockIDFlag.COMMIT and not e.extension_signature:
                raise ValueError("vote extension signature is missing")
            if cs.block_id_flag != BlockIDFlag.COMMIT and (e.extension or e.extension_signature):
                raise ValueError("non-commit vote carries extension data")
