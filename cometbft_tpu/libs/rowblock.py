"""A batch's signature rows as columns — the form in which rows cross
from the commit path through crypto/batch and the verify scheduler into
kernel staging (PR 31).

Before, a 10,240-signature commit went down as 10,240 (PubKey, msg, sig)
tuples that every layer walked a lane at a time: the row builder, the
verifier's add(), the scheduler's grouping, the planner, the assembler.
Here the rows of one producer group are a RowBlock: for each key type in
it one SigColumns (keys, messages as a prefixrows.MsgBlock, signatures as
an (n, 64) matrix) and the vector of the lanes those rows have in the
group. A commit's block is made with index vectors from the validator
set's cached columns (types/validator.SetColumns) and the commit's
sign-rows; rows that arrive as tuples (vote flushes, mempool admission,
evidence, small commits) are turned into the same block by one lane loop,
from_rows(), so that everything downstream is written once.

Layering: libs, beside prefixrows — types/, crypto/, sched/ and ops/ all
read it.
"""

from __future__ import annotations

import numpy as np

from cometbft_tpu.libs.prefixrows import MsgBlock

SIG_WIDTH = 64  # the signature size of the schemes whose rows go as a matrix


def sig_column(sigs: list):
    """The signatures as an (n, 64) uint8 matrix if each has 64 bytes,
    else the list (a ragged or a BLS column: the kernels' structural
    checks and crypto/batch's width rule read it a row at a time)."""
    if set(map(len, sigs)) == {SIG_WIDTH}:
        return np.frombuffer(b"".join(sigs), dtype=np.uint8).reshape(
            len(sigs), SIG_WIDTH)
    return list(map(bytes, sigs))


class SigColumns:
    """The rows of ONE key type.

      keys      list of the PubKey objects (the scheduler's host rung)
      pub_rows  (n, 32) uint8 key matrix, or None where the block was
                made from tuples or a key has another size
      msgs      prefixrows.MsgBlock
      sigs      (n, 64) uint8 matrix, or a list of bytes where a
                signature has another size (see sig_column)"""

    __slots__ = ("keys", "_pubs", "pub_rows", "msgs", "sigs")

    def __init__(self, keys: list, msgs: MsgBlock, sigs,
                 pubs: list | None = None, pub_rows=None):
        self.keys = keys
        self._pubs = pubs
        self.pub_rows = pub_rows
        self.msgs = msgs
        self.sigs = sigs

    def __len__(self) -> int:
        return len(self.keys)

    @property
    def pubs(self) -> list[bytes]:
        """The keys as bytes (residency lookup, host oracles)."""
        if self._pubs is None:
            self._pubs = [k.bytes_() for k in self.keys]
        return self._pubs

    def sig_list(self) -> list[bytes]:
        if isinstance(self.sigs, list):
            return self.sigs
        blob = self.sigs.tobytes()
        return [blob[i:i + SIG_WIDTH]
                for i in range(0, len(blob), SIG_WIDTH)]

    def sig_widths(self) -> set[int]:
        if isinstance(self.sigs, list):
            return set(map(len, self.sigs))
        return {self.sigs.shape[1]} if len(self.sigs) else set()

    @classmethod
    def concat(cls, parts: list) -> "SigColumns":
        """The sub-batch of one key type from its rows in several groups,
        in the groups' order."""
        if len(parts) == 1:
            return parts[0]
        keys: list = []
        for p in parts:
            keys += p.keys
        # the keys' bytes where every part has them already (a commit's
        # come from its set's columns); else they are made if asked for
        pubs = None
        if all(p._pubs is not None for p in parts):
            pubs = [b for p in parts for b in p._pubs]
        pub_rows = None
        if all(p.pub_rows is not None for p in parts):
            pub_rows = np.concatenate([p.pub_rows for p in parts])
        if any(isinstance(p.sigs, list) for p in parts):
            sigs: list = []
            for p in parts:
                sigs += p.sig_list()
        else:
            sigs = np.concatenate([p.sigs for p in parts])
        return cls(keys, MsgBlock.concat([p.msgs for p in parts]), sigs,
                   pubs=pubs, pub_rows=pub_rows)


class RowBlock:
    """The rows of one producer group: `parts` maps a key type to (the
    lanes its rows have in the group, ascending; its SigColumns), in the
    order of each type's first lane."""

    __slots__ = ("n", "parts")

    def __init__(self, n: int, parts: dict):
        self.n = n
        self.parts = parts

    def __len__(self) -> int:
        return self.n

    @classmethod
    def from_rows(cls, keys, msgs, sigs) -> "RowBlock":
        """The lane loop: rows as three sequences (PubKey objects; bytes
        or PrefixedMsg rows, or a MsgBlock; signatures) into columns.
        Every producer that has its rows a lane at a time ends here,
        once."""
        n = len(keys)
        types = [k.type_() for k in keys]
        schemes = dict.fromkeys(types)
        if len(schemes) <= 1:
            if not n:
                return cls(0, {})
            return cls(n, {types[0]: (np.arange(n), SigColumns(
                list(keys), MsgBlock.of(msgs), sig_column(sigs)))})
        lanes_of: dict = {s: [] for s in schemes}
        for i, t in enumerate(types):
            lanes_of[t].append(i)
        block = isinstance(msgs, MsgBlock)
        parts = {}
        for scheme, lanes in lanes_of.items():
            at = np.asarray(lanes, dtype=np.intp)
            parts[scheme] = (at, SigColumns(
                [keys[i] for i in lanes],
                msgs.take(at) if block
                else MsgBlock.from_list([msgs[i] for i in lanes]),
                sig_column([sigs[i] for i in lanes])))
        return cls(n, parts)

    @classmethod
    def from_set(cls, cols, idxs: np.ndarray, msgs: MsgBlock, sigs: list,
                 msg_idxs: np.ndarray | None = None) -> "RowBlock":
        """No lane loop: the rows of the validators `idxs` of a set whose
        keys are columns already (types/validator.SetColumns), with
        `msgs` the sign-rows of ALL the commit's signatures and `sigs`
        the signatures of the chosen ones. Keys and messages are taken
        with index vectors, a key type at a time. A commit of the set
        itself has a validator's vote at the validator's index; where the
        commit is another set's (the trusting check), `msg_idxs` says at
        which signature each chosen validator's vote stands."""
        if msg_idxs is None:
            msg_idxs = idxs
        if len(cols.schemes) == 1:
            split = [(0, np.arange(len(idxs)))]
        else:
            code = cols.code[idxs]
            split = [(s, np.flatnonzero(code == s))
                     for s in range(len(cols.schemes))]
            # in the order of each type's first lane, as a lane loop meets them
            split = sorted((x for x in split if len(x[1])),
                           key=lambda x: x[1][0])
        sigs = sig_column(sigs)
        parts = {}
        for s, lanes in split:
            at = idxs[lanes]
            parts[cols.schemes[s]] = (lanes, SigColumns(
                cols.keys[at].tolist(), msgs.take(msg_idxs[lanes]),
                sigs[lanes] if isinstance(sigs, np.ndarray)
                else sig_column([sigs[i] for i in lanes.tolist()]),
                pubs=cols.key_bytes[at].tolist(),
                pub_rows=(cols.key_rows[at]
                          if (cols.key_sizes[at] == 32).all() else None)))
        return cls(len(idxs), parts)

    @classmethod
    def from_tuples(cls, rows) -> "RowBlock":
        """from_rows for a list of (PubKey, msg, sig)."""
        if not rows:
            return cls(0, {})
        return cls.from_rows(*zip(*rows))

    def lists(self) -> tuple[list, list, list]:
        """(PubKey objects, messages as bytes, signatures as bytes) in the
        group's lane order: the serial routes (an unbatchable key type,
        the BLS aggregate) read rows so."""
        keys: list = [None] * self.n
        msgs: list = [None] * self.n
        sigs: list = [None] * self.n
        for lanes, cols in self.parts.values():
            for col, out in ((cols.keys, keys), (cols.msgs.tolist(), msgs),
                             (cols.sig_list(), sigs)):
                for i, v in zip(lanes.tolist(), col):
                    out[i] = v
        return keys, msgs, sigs
