"""Validator and ValidatorSet with proposer-priority rotation.

Reference: types/validator.go, types/validator_set.go. The rotation
algorithm (a-priori deterministic weighted round-robin with priority
centering and rescaling) is consensus-critical: every node must compute the
identical proposer for (height, round), so the arithmetic here mirrors the
reference exactly — including int64 clipping semantics
(validator_set.go:114-250) — implemented over Python ints with explicit
clamps.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace

import numpy as np

from cometbft_tpu import crypto
from cometbft_tpu.crypto import merkle
from cometbft_tpu.libs import trace
from cometbft_tpu.utils import protobuf as pb

INT64_MAX = (1 << 63) - 1
INT64_MIN = -(1 << 63)
# reference: types/validator_set.go:25
MAX_TOTAL_VOTING_POWER = INT64_MAX // 8
# reference: types/validator_set.go:30
PRIORITY_WINDOW_SIZE_FACTOR = 2


def _clip(v: int) -> int:
    return max(INT64_MIN, min(INT64_MAX, v))


@dataclass
class Validator:
    """types/validator.go:13-20."""

    address: bytes
    pub_key: crypto.PubKey
    voting_power: int
    proposer_priority: int = 0

    @classmethod
    def new(cls, pub_key: crypto.PubKey, voting_power: int) -> "Validator":
        return cls(
            address=pub_key.address(),
            pub_key=pub_key,
            voting_power=voting_power,
            proposer_priority=0,
        )

    def copy(self) -> "Validator":
        return replace(self)

    def validate_basic(self) -> None:
        if self.pub_key is None:
            raise ValueError("validator does not have a public key")
        if self.voting_power < 0:
            raise ValueError("validator has negative voting power")
        if len(self.address) != crypto.ADDRESS_SIZE:
            raise ValueError("validator address is the wrong size")

    def compare_proposer_priority(self, other: "Validator") -> int:
        """Higher priority wins; tie-break by lower address
        (validator_set.go CompareProposerPriority)."""
        if self.proposer_priority > other.proposer_priority:
            return -1
        if self.proposer_priority < other.proposer_priority:
            return 1
        if self.address < other.address:
            return -1
        if self.address > other.address:
            return 1
        raise ValueError("cannot compare identical validators")

    def bytes_(self) -> bytes:
        """SimpleValidator proto: pub_key=1 (crypto.PublicKey oneof),
        voting_power=2 — the valset-hash leaf (types/validator.go:117-133)."""
        pk = pub_key_to_proto(self.pub_key)
        w = pb.Writer()
        w.message(1, pk)
        w.varint_i64(2, self.voting_power)
        return w.output()

    def to_proto(self) -> bytes:
        """tendermint.types.Validator: address=1, pub_key=2, voting_power=3,
        proposer_priority=4 (types/validator.go ToProto)."""
        w = pb.Writer()
        w.bytes(1, self.address)
        w.message(2, pub_key_to_proto(self.pub_key), always=True)
        w.varint_i64(3, self.voting_power)
        w.varint_i64(4, self.proposer_priority)
        return w.output()

    @classmethod
    def from_proto(cls, data: bytes) -> "Validator":
        r = pb.Reader(data)
        address = b""
        pub_key = None
        power = 0
        priority = 0
        while not r.at_end():
            f, w = r.read_tag()
            if f == 1:
                address = r.read_bytes()
            elif f == 2:
                pub_key = pub_key_from_proto(r.read_bytes())
            elif f == 3:
                power = r.read_varint_i64()
            elif f == 4:
                priority = r.read_varint_i64()
            else:
                r.skip(w)
        if pub_key is None:
            raise ValueError("Validator proto missing pub_key")
        return cls(
            address=address or pub_key.address(),
            pub_key=pub_key,
            voting_power=power,
            proposer_priority=priority,
        )


# crypto.PublicKey oneof (proto/tendermint/crypto/keys.proto): key type ->
# field number
_KEY_FIELDS = {"ed25519": 1, "secp256k1": 2, "sr25519": 3, "bls12381": 4}


def pub_key_to_proto(pub_key: crypto.PubKey) -> bytes:
    """crypto.PublicKey oneof: ed25519=1 bytes, secp256k1=2 bytes
    (proto/tendermint/crypto/keys.proto)."""
    field_num = _KEY_FIELDS.get(pub_key.type_())
    if field_num is None:
        raise ValueError(f"unsupported pubkey type {pub_key.type_()}")
    return pb.Writer().bytes(field_num, pub_key.bytes_(), always=True).output()


def pub_key_from_proto(data: bytes) -> crypto.PubKey:
    from cometbft_tpu.crypto import ed25519

    r = pb.Reader(data)
    while not r.at_end():
        f, w = r.read_tag()
        if f == 1:
            return ed25519.PubKey(r.read_bytes())
        if f == 2:
            from cometbft_tpu.crypto import secp256k1

            return secp256k1.PubKey(r.read_bytes())
        if f == 3:
            from cometbft_tpu.crypto import sr25519

            return sr25519.PubKey(r.read_bytes())
        if f == 4:
            from cometbft_tpu.crypto import bls12381

            return bls12381.PubKey(r.read_bytes())
        r.skip(w)
    raise ValueError("empty/unsupported PublicKey proto")


def _leaf_head(field_num: int, key_size: int) -> bytes:
    """What a SimpleValidator leaf has before its key's bytes: tag and
    length of pub_key = 1, then tag and length of the oneof's field."""
    inner = pb.encode_uvarint(field_num << 3 | 2) + pb.encode_uvarint(key_size)
    return b"\x0a" + pb.encode_uvarint(len(inner) + key_size) + inner


def _leaves(validators: list[Validator]) -> list[bytes]:
    """Validator.bytes_() of every validator, byte for byte, in one loop:
    a key type of the PublicKey oneof has a fixed head for its key size,
    then come the key and, unless the power is 0, 0x10 and its varint.
    Any other key type goes through bytes_() (which refuses it)."""
    heads: dict[tuple[str, int], bytes] = {}
    tails: dict[int, bytes] = {0: b""}
    leaves = []
    for v in validators:
        pub_key = v.pub_key
        key = pub_key.bytes_()
        kind = (pub_key.type_(), len(key))
        head = heads.get(kind)
        if head is None:
            field_num = _KEY_FIELDS.get(kind[0])
            if field_num is None:
                leaves.append(v.bytes_())
                continue
            head = heads[kind] = _leaf_head(field_num, kind[1])
        power = v.voting_power
        tail = tails.get(power)
        if tail is None:
            tail = tails[power] = b"\x10" + pb.encode_varint_i64(power)
        leaves.append(head + key + tail)
    return leaves


class SetColumns:
    """A validator set's keys and powers as columns, made once a set and
    not once a commit (ValidatorSet.columns): what a commit's row block
    (libs/rowblock.py) selects from with index vectors.

      schemes    the key types in the set, in the order first met
      code       (N,) the index into `schemes` of every validator
      keys       (N,) object array of the PubKey objects (the host rung)
      key_bytes  (N,) object array of their bytes (the residency lookup)
      key_rows   (N, 32) uint8, the 32-byte keys as a matrix; zero rows
                 where a key has another size (BLS: 48)
      key_sizes  (N,) the key sizes
      powers     (N,) int64 voting powers

    `src` is the list the columns were read from: a set whose list was
    replaced or changed in length reads them anew."""

    __slots__ = ("src", "n", "schemes", "code", "keys", "key_bytes",
                 "key_rows", "key_sizes", "powers")

    def __init__(self, validators: list):
        self.src = validators
        self.n = n = len(validators)
        keys = [v.pub_key for v in validators]
        types = [k.type_() for k in keys]
        self.schemes = tuple(dict.fromkeys(types))
        lookup = {t: i for i, t in enumerate(self.schemes)}
        self.code = np.fromiter((lookup[t] for t in types), np.intp, n)
        raw = [k.bytes_() for k in keys]
        self.keys = np.empty(n, dtype=object)
        self.keys[:] = keys
        self.key_bytes = np.empty(n, dtype=object)
        self.key_bytes[:] = raw
        self.key_sizes = np.fromiter(map(len, raw), np.intp, n)
        self.key_rows = np.zeros((n, 32), dtype=np.uint8)
        fits = self.key_sizes == 32
        if fits.all():
            self.key_rows[:] = np.frombuffer(
                b"".join(raw), dtype=np.uint8).reshape(n, 32)
        else:
            for i in np.flatnonzero(fits).tolist():
                self.key_rows[i] = np.frombuffer(raw[i], dtype=np.uint8)
        self.powers = np.fromiter(
            (v.voting_power for v in validators), np.int64, n)

    def rebound(self, validators: list) -> "SetColumns":
        """The same columns for a copy of the set (its list holds copies
        of the same validators)."""
        new = SetColumns.__new__(SetColumns)
        for name in self.__slots__:
            setattr(new, name, getattr(self, name))
        new.src = validators
        return new


class ValidatorSet:
    """types/validator_set.go:55-66. Validators sorted by address; proposer
    tracked explicitly and rotated by priority."""

    # the set's keys and powers as columns (columns()); a class default so
    # that a set made with __new__ (copy, from_proto, the stores) has it
    _columns: SetColumns | None = None
    # (the list it was read from, its length, {address: index}): see
    # address_index()
    _addr_index: tuple | None = None
    # (the list it was computed from, its length, the Merkle root): see
    # hash(). Written by hash() and copy() alone, never from the wire
    _merkle_root: tuple | None = None

    def __init__(self, validators: list[Validator]):
        self.validators: list[Validator] = sorted(
            (v.copy() for v in validators), key=lambda v: v.address
        )
        self.proposer: Validator | None = None
        self._total_voting_power: int | None = None
        if self.validators:
            self._update_total_voting_power()
            self.increment_proposer_priority(1)

    # ---------------------------------------------------------------- basics

    def is_nil_or_empty(self) -> bool:
        return not self.validators

    def __len__(self) -> int:
        return len(self.validators)

    def copy(self) -> "ValidatorSet":
        new = ValidatorSet.__new__(ValidatorSet)
        new.validators = [v.copy() for v in self.validators]
        new.proposer = self.proposer.copy() if self.proposer else None
        new._total_voting_power = self._total_voting_power
        if self._columns is not None and self._columns.src is self.validators:
            new._columns = self._columns.rebound(new.validators)
        if self._addr_index is not None:
            # the copies have the addresses and the order of the originals
            new._addr_index = (new.validators, len(new.validators),
                               self.address_index())
        root = self._kept_root()
        if root is not None:
            # the copies have the keys and the powers of the originals
            new._merkle_root = (new.validators, len(new.validators), root)
        return new

    def columns(self) -> SetColumns:
        """Keys, key types and powers as columns, read once from the set
        and kept until update_with_change_set changes a key or a power
        (priority moves touch neither; copy() carries them over). Nothing
        else in the repo writes a Validator's key or power in place, or
        puts another Validator into the list of a set: the Merkle root
        (hash()) is kept on the same terms, and a writer that does not
        drop it would leave a root of validators the set no longer has."""
        cols = self._columns
        if (cols is None or cols.src is not self.validators
                or cols.n != len(self.validators)):
            cols = self._columns = SetColumns(self.validators)
        return cols

    def _update_total_voting_power(self) -> None:
        total = 0
        for v in self.validators:
            total += v.voting_power
            if total > MAX_TOTAL_VOTING_POWER:
                raise ValueError(
                    f"total voting power cannot exceed {MAX_TOTAL_VOTING_POWER}"
                )
        self._total_voting_power = total

    def total_voting_power(self) -> int:
        if self._total_voting_power is None:
            self._update_total_voting_power()
        return self._total_voting_power

    def address_index(self) -> dict[bytes, int]:
        """{address: index of the first validator that has it}, made once
        a set and kept as columns() are: until the list is replaced or
        changes in length (update_with_change_set drops it; copy() hands
        it on). What get_by_address looks up, and what the trusting
        check joins a commit's addresses to (types/validation.py)."""
        kept = self._addr_index
        if (kept is None or kept[0] is not self.validators
                or kept[1] != len(self.validators)):
            index: dict[bytes, int] = {}
            for i, v in enumerate(self.validators):
                index.setdefault(v.address, i)
            kept = self._addr_index = (
                self.validators, len(self.validators), index)
        return kept[2]

    def has_address(self, address: bytes) -> bool:
        return address in self.address_index()

    def get_by_address(self, address: bytes) -> tuple[int, Validator | None]:
        i = self.address_index().get(address)
        if i is None:
            return -1, None
        return i, self.validators[i].copy()

    def get_by_index(self, index: int) -> tuple[bytes, Validator | None]:
        if index < 0 or index >= len(self.validators):
            return b"", None
        v = self.validators[index]
        return v.address, v.copy()

    # ------------------------------------------------------------- proposer

    def get_proposer(self) -> Validator | None:
        if not self.validators:
            return None
        if self.proposer is None:
            self.proposer = self._find_proposer()
        return self.proposer.copy()

    def _find_proposer(self) -> Validator:
        best = None
        for v in self.validators:
            if best is None or v.compare_proposer_priority(best) < 0:
                best = v
        return best

    def increment_proposer_priority(self, times: int) -> None:
        """validator_set.go:114-136."""
        if self.is_nil_or_empty():
            raise ValueError("empty validator set")
        if times <= 0:
            raise ValueError("cannot call IncrementProposerPriority with non-positive times")
        diff_max = PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power()
        self.rescale_priorities(diff_max)
        self._shift_by_avg_proposer_priority()
        proposer = None
        for _ in range(times):
            proposer = self._increment_proposer_priority()
        self.proposer = proposer

    def rescale_priorities(self, diff_max: int) -> None:
        """validator_set.go:141-162: divide by ceil(diff/diffMax) when the
        priority span exceeds diffMax. Go integer division truncates toward
        zero — mirror that, not Python floor."""
        if diff_max <= 0:
            return
        diff = self._max_min_priority_diff()
        ratio = (diff + diff_max - 1) // diff_max
        if diff > diff_max:
            for v in self.validators:
                q = abs(v.proposer_priority) // ratio
                v.proposer_priority = q if v.proposer_priority >= 0 else -q

    def _max_min_priority_diff(self) -> int:
        prios = [v.proposer_priority for v in self.validators]
        return abs(max(prios) - min(prios))

    def _increment_proposer_priority(self) -> Validator:
        for v in self.validators:
            v.proposer_priority = _clip(v.proposer_priority + v.voting_power)
        mostest = self._find_proposer()
        mostest.proposer_priority = _clip(
            mostest.proposer_priority - self.total_voting_power()
        )
        return mostest

    def _shift_by_avg_proposer_priority(self) -> None:
        n = len(self.validators)
        # Go big.Int Div: Euclidean-style? No — big.Int.Div with positive
        # divisor floors toward -inf for negative dividends, same as Python.
        avg = sum(v.proposer_priority for v in self.validators) // n
        for v in self.validators:
            v.proposer_priority = _clip(v.proposer_priority - avg)

    # ---------------------------------------------------------------- hash

    def hash(self) -> bytes:
        """Merkle root of SimpleValidator leaves (validator_set.go:347-353),
        computed once a set and kept as columns() are: until the list is
        replaced or changes in length (update_with_change_set drops it;
        copy() hands it on). Proposer priority is not in a leaf, so the
        rotation keeps it."""
        root = self._kept_root()
        if root is not None:
            trace.count("valset", "kept")
            return root
        trace.count("valset", "hashes")
        validators = self.validators
        root = merkle.hash_from_byte_slices(_leaves(validators))
        self._merkle_root = (validators, len(validators), root)
        return root

    def _kept_root(self) -> bytes | None:
        kept = self._merkle_root
        if (kept is None or kept[0] is not self.validators
                or kept[1] != len(self.validators)):
            return None
        return kept[2]

    # -------------------------------------------------------------- updates

    def update_with_change_set(self, changes: list[Validator]) -> None:
        """Apply ABCI ValidatorUpdates (validator_set.go:502-576 semantics):
        power 0 = removal; new addresses added; existing updated. Priorities
        of new validators start at -1.125 * total power (so they don't
        immediately propose); then recenter/rescale."""
        if not changes:
            return
        seen: set[bytes] = set()
        for c in changes:
            if c.address in seen:
                raise ValueError(f"duplicate entry {c.address.hex()} in changes")
            seen.add(c.address)
            if c.voting_power < 0:
                raise ValueError("voting power can't be negative")

        removals = {c.address for c in changes if c.voting_power == 0}
        updates = [c for c in changes if c.voting_power > 0]

        for addr in removals:
            if not self.has_address(addr):
                raise ValueError(f"failed to find validator {addr.hex()} to remove")

        by_addr = {v.address: v for v in self.validators}
        # Total voting power after updates but BEFORE removals — the base
        # for both the cap check and new-validator priorities
        # (validator_set.go:490,618-624 tvpAfterUpdatesBeforeRemovals;
        # excluding removals here would permanently diverge proposer
        # rotation from the reference for mixed add+remove change sets).
        upd_by_addr = {u.address: u for u in updates}
        new_total = 0
        for v in self.validators:
            upd = upd_by_addr.get(v.address)
            new_total += upd.voting_power if upd else v.voting_power
        for u in updates:
            if u.address not in by_addr:
                new_total += u.voting_power
        if new_total > MAX_TOTAL_VOTING_POWER:
            raise ValueError("total voting power would exceed maximum")

        self._columns = None  # keys and powers change in place from here
        self._addr_index = None
        self._merkle_root = None
        for u in updates:
            existing = by_addr.get(u.address)
            if existing is not None:
                existing.voting_power = u.voting_power
                existing.pub_key = u.pub_key
            else:
                nv = u.copy()
                # validator_set.go:316: new validators get -(total + total/8)
                nv.proposer_priority = -(new_total + (new_total >> 3))
                self.validators.append(nv)
        self.validators = [v for v in self.validators if v.address not in removals]
        self.validators.sort(key=lambda v: v.address)
        self._total_voting_power = None
        self._update_total_voting_power()
        if self.validators:
            self.rescale_priorities(PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power())
            self._shift_by_avg_proposer_priority()
            self.proposer = self._find_proposer()

    def validate_basic(self) -> None:
        if self.is_nil_or_empty():
            raise ValueError("validator set is nil or empty")
        for v in self.validators:
            v.validate_basic()
        if self.proposer is not None:
            self.proposer.validate_basic()
            if not self.has_address(self.proposer.address):
                raise ValueError("proposer not in validator set")

    def __iter__(self):
        return iter(self.validators)

    # ---------------------------------------------------------------- wire

    def to_proto(self) -> bytes:
        """tendermint.types.ValidatorSet: validators=1, proposer=2,
        total_voting_power=3 (types/validator_set.go ToProto)."""
        w = pb.Writer()
        for v in self.validators:
            w.message(1, v.to_proto(), always=True)
        if self.proposer is not None:
            w.message(2, self.proposer.to_proto(), always=True)
        w.varint_i64(3, self.total_voting_power())
        return w.output()

    @classmethod
    def from_proto(cls, data: bytes) -> "ValidatorSet":
        r = pb.Reader(data)
        vals: list[Validator] = []
        proposer: Validator | None = None
        while not r.at_end():
            f, w = r.read_tag()
            if f == 1:
                vals.append(Validator.from_proto(r.read_bytes()))
            elif f == 2:
                proposer = Validator.from_proto(r.read_bytes())
            else:
                r.skip(w)
        vs = cls.__new__(cls)
        vs.validators = sorted(vals, key=lambda v: v.address)
        vs.proposer = proposer
        vs._total_voting_power = None
        if vs.validators:
            vs._update_total_voting_power()
        return vs
