"""A validator set's Merkle root (ValidatorSet.hash, PR 36): computed from
flat leaves and level by level, kept on the set object as columns() are.

Parity: byte for byte the root of `[v.bytes_() ...]` through the recursion
the repo had before (kept here as the oracle) and, where it knows the key
types, the benchmark's plain reference. Invalidation: no path that changes
a key, a power or the membership of a set leaves a kept root behind."""

from __future__ import annotations

import hashlib
import os
import pathlib
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import commit_ref, light_ref  # noqa: E402
from cometbft_tpu.crypto import (bls12381, ed25519, merkle,  # noqa: E402
                                 secp256k1, sr25519)
from cometbft_tpu.libs import trace  # noqa: E402
from cometbft_tpu.state import store as state_store  # noqa: E402
from cometbft_tpu.state.state import State  # noqa: E402
from cometbft_tpu.types import validator as validator_mod  # noqa: E402
from cometbft_tpu.types.validator import (MAX_TOTAL_VOTING_POWER,  # noqa: E402
                                          Validator, ValidatorSet)


@pytest.fixture(autouse=True)
def _tracer():
    trace.reset()
    yield
    trace.reset()


def recursion_root(items: list[bytes]) -> bytes:
    """crypto/merkle.hash_from_byte_slices as it was: RFC 6962's split at
    the largest power of two below n, over list slices."""
    n = len(items)
    if n == 0:
        return merkle.empty_hash()
    if n == 1:
        return merkle.leaf_hash(items[0])
    k = merkle.get_split_point(n)
    return merkle.inner_hash(recursion_root(items[:k]),
                             recursion_root(items[k:]))


def oracle_root(vals: ValidatorSet) -> bytes:
    return recursion_root([v.bytes_() for v in vals.validators])


def reference_root(vals: ValidatorSet) -> bytes:
    """The benchmark's plain reference (it knows ed25519 and sr25519)."""
    return light_ref.valset_hash(commit_ref.ValsetSpec(
        chain_id="c",
        schemes=tuple(v.pub_key.type_() for v in vals.validators),
        pubs=tuple(v.pub_key.bytes_() for v in vals.validators),
        powers=tuple(v.voting_power for v in vals.validators)))


def _seed(tag: str, i: int, size: int) -> bytes:
    return hashlib.sha512(f"{tag}-{i}".encode()).digest()[:size]


# the bytes need not be points of their curves: a leaf holds them as they are
KEYS = {
    "ed25519": lambda i: ed25519.PubKey(_seed("ed", i, 32)),
    "sr25519": lambda i: sr25519.PubKey(_seed("sr", i, 32)),
    "secp256k1": lambda i: secp256k1.PubKey(b"\x02" + _seed("secp", i, 32)),
    "bls12381": lambda i: bls12381.PubKey(_seed("bls", i, 48)),
}
KEYS["mixed"] = lambda i: KEYS[("ed25519", "sr25519", "secp256k1",
                                "bls12381")[i % 4]](i)

POWERS = {"0": lambda n: 0, "1": lambda n: 1, "127": lambda n: 127,
          "128": lambda n: 128, "2pow31": lambda n: 1 << 31,
          "max_over_n": lambda n: MAX_TOTAL_VOTING_POWER // n}


def make_set(n: int, keys: str = "ed25519", power: int = 10) -> ValidatorSet:
    return ValidatorSet([Validator.new(KEYS[keys](i), power)
                         for i in range(n)])


def counts() -> dict:
    return trace.attribution()["valset"]


# ------------------------------------------------------------------- parity


@pytest.mark.parametrize("power", POWERS)
@pytest.mark.parametrize("keys", KEYS)
@pytest.mark.parametrize("n", [1, 2, 3, 37, 150, 500, 513])
def test_root_is_the_old_recursion_over_bytes(n, keys, power):
    vals = make_set(n, keys, POWERS[power](n))
    assert len(vals) == n
    leaves = [v.bytes_() for v in vals.validators]
    assert validator_mod._leaves(vals.validators) == leaves
    root = vals.hash()
    assert root == recursion_root(leaves)
    if keys in light_ref.KEY_FIELD:
        assert root == reference_root(vals)
    # and again, from the root the set keeps
    assert vals.hash() == root


def test_root_of_ed25519_and_sr25519_mixed_is_the_reference_s():
    vals = ValidatorSet([Validator.new(KEYS[("ed25519", "sr25519")[i % 2]](i),
                                       1 + i) for i in range(37)])
    assert vals.hash() == oracle_root(vals) == reference_root(vals)


def test_empty_set_hashes_to_the_empty_tree():
    assert ValidatorSet([]).hash() == merkle.empty_hash()


def test_a_key_type_outside_the_oneof_is_refused_as_bytes_refuses_it():
    class Other(ed25519.PubKey):
        def type_(self) -> str:
            return "other"

    vals = make_set(3)
    vals.validators[1].pub_key = Other(_seed("o", 1, 32))
    with pytest.raises(ValueError, match="unsupported pubkey type other"):
        vals.validators[1].bytes_()
    with pytest.raises(ValueError, match="unsupported pubkey type other"):
        ValidatorSet.hash(vals)
    assert vals._merkle_root is None


@pytest.mark.parametrize("n", range(71))
def test_level_by_level_tree_is_the_recursion_and_its_proofs_verify(n):
    items = [_seed("leaf", i, i % 7 * 9) for i in range(n)]
    root = merkle.hash_from_byte_slices(items)
    assert root == recursion_root(items)
    proof_root, proofs = merkle.proofs_from_byte_slices(items)
    assert proof_root == root
    assert len(proofs) == n
    for item, proof in zip(items, proofs):
        assert proof.verify(root, item)
        assert not proof.verify(root, item + b"x")


# ------------------------------------------------------------- invalidation


def _change(vals: ValidatorSet, kind: str) -> list[Validator]:
    target = vals.validators[len(vals) // 2]
    if kind == "power":
        return [Validator(target.address, target.pub_key,
                          target.voting_power + 5)]
    if kind == "key":
        return [Validator(target.address, KEYS["ed25519"](10_000),
                          target.voting_power)]
    if kind == "addition":
        return [Validator.new(KEYS["ed25519"](10_001), 7)]
    assert kind == "removal"
    return [Validator(target.address, target.pub_key, 0)]


@pytest.mark.parametrize("n", [4, 37])
@pytest.mark.parametrize("kind", ["power", "key", "addition", "removal"])
def test_a_change_set_leaves_no_kept_root_behind(kind, n):
    vals = make_set(n)
    before = vals.hash()
    vals.update_with_change_set(_change(vals, kind))
    assert vals._kept_root() is None
    trace.configure(enabled=True)
    after = vals.hash()
    assert counts() == {"hashes": 1, "kept": 0}
    assert after != before
    assert after == oracle_root(vals)
    rebuilt = ValidatorSet.__new__(ValidatorSet)
    rebuilt.validators = [v.copy() for v in vals.validators]
    assert after == rebuilt.hash()
    assert len(vals) == n + {"addition": 1, "removal": -1}.get(kind, 0)


@pytest.mark.parametrize("kind", ["power", "key", "addition", "removal"])
def test_a_copy_answers_from_the_kept_root_and_the_two_part_ways(kind):
    vals = make_set(37)
    root = vals.hash()
    trace.configure(enabled=True)
    copy = vals.copy()
    assert copy.hash() == root
    assert counts() == {"hashes": 0, "kept": 1}
    # a change to the copy leaves the original's root alone
    copy.update_with_change_set(_change(copy, kind))
    assert copy.hash() == oracle_root(copy) != root
    assert vals.hash() == root == oracle_root(vals)
    assert counts() == {"hashes": 1, "kept": 2}
    # and the other way round
    other = vals.copy()
    vals.update_with_change_set(_change(vals, kind))
    assert vals.hash() == oracle_root(vals) != root
    assert other.hash() == root == oracle_root(other)
    assert counts() == {"hashes": 2, "kept": 3}


def test_a_copy_of_a_set_never_hashed_computes_its_own_root():
    vals = make_set(8)
    copy = vals.copy()
    trace.configure(enabled=True)
    assert copy.hash() == oracle_root(vals)
    assert counts() == {"hashes": 1, "kept": 0}
    assert vals._merkle_root is None


@pytest.mark.parametrize("times", [1, 3, 200])
def test_the_rotation_keeps_the_root_and_it_is_still_right(times):
    vals = ValidatorSet([Validator.new(KEYS["ed25519"](i), 1 + i * i)
                         for i in range(9)])
    root = vals.hash()
    before = [v.proposer_priority for v in vals.validators]
    vals.increment_proposer_priority(times)
    assert [v.proposer_priority for v in vals.validators] != before
    trace.configure(enabled=True)
    assert vals.hash() == root == oracle_root(vals)
    assert counts() == {"hashes": 0, "kept": 1}


def _from_proto(vals):
    return ValidatorSet.from_proto(vals.to_proto())


def _from_store(vals):
    return state_store._valset_from_bytes(state_store._valset_bytes(vals))


def _from_state(vals):
    state = State(chain_id="c", initial_height=1, validators=vals,
                  next_validators=vals.copy(),
                  last_validators=ValidatorSet([]))
    return State.from_bytes(state.to_bytes()).validators


@pytest.mark.parametrize("loader", [_from_proto, _from_store, _from_state],
                         ids=["from_proto", "state_store", "state_state"])
@pytest.mark.parametrize("keys", ["ed25519", "mixed"])
def test_a_set_rebuilt_by_a_loader_computes_its_root_and_is_right(loader,
                                                                  keys):
    vals = make_set(37, keys, 11)
    root = vals.hash()  # a root on the source: nothing of it rides the bytes
    rebuilt = loader(vals)
    assert rebuilt is not vals and rebuilt._merkle_root is None
    trace.configure(enabled=True)
    assert rebuilt.hash() == root == oracle_root(rebuilt)
    assert counts() == {"hashes": 1, "kept": 0}


def test_a_validator_appended_to_the_list_after_a_hash_is_seen():
    vals = make_set(5)
    root = vals.hash()
    vals.validators.append(Validator.new(KEYS["ed25519"](77), 3))
    assert vals.hash() == oracle_root(vals) != root
    # a copy taken while the kept root is stale does not carry it
    vals.validators.append(Validator.new(KEYS["ed25519"](78), 3))
    copy = vals.copy()
    assert copy._merkle_root is None
    assert copy.hash() == oracle_root(vals)


def test_a_list_put_in_the_place_of_the_hashed_one_is_seen():
    vals = make_set(5)
    root = vals.hash()
    vals.validators = [v.copy() for v in vals.validators[:-1]] + [
        Validator.new(KEYS["ed25519"](79), 3)]
    assert vals.hash() == oracle_root(vals) != root


def test_the_counters_tell_roots_computed_from_roots_kept():
    vals = make_set(8)
    trace.configure(enabled=True)
    for _ in range(4):
        vals.hash()
    assert counts() == {"hashes": 1, "kept": 3}
    assert trace.COUNTS["valset"] == ("hashes", "kept")


def test_nothing_outside_the_class_names_the_kept_root():
    """A root is only ever what a set computed from its own validators:
    no caller, codec or store reads or writes the attribute."""
    named = [str(path.relative_to(ROOT))
             for top in ("cometbft_tpu", "benchmarks")
             for path in sorted(pathlib.Path(ROOT, top).rglob("*.py"))
             if "_merkle_root" in path.read_text()]
    assert named == [os.path.join("cometbft_tpu", "types", "validator.py")]
