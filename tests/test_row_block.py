"""A commit's rows as columns (PR 31): the block path of
types/validation._commit_rows against the lane path, which is its oracle.

Same verdict, same exception type and same message from verify_commit,
verify_commit_light, the trusting check and the staged entries whichever
path selects the rows; the scheduler's grouping, bounds and mask slicing
over blocks against the same groups as lists of tuples; what kernel staging
makes of a block against what it makes of the lists; the validator set's
cached columns; and the count of collector-tracked objects a 10,240-row
call keeps alive, which is what the block path is for.
"""

from __future__ import annotations

import gc
import hashlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from cometbft_tpu import sched
from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.crypto import ed25519, sr25519
from cometbft_tpu.libs import trace
from cometbft_tpu.libs.prefixrows import (MsgBlock, PrefixedMsg,
                                         SharedPrefixRows)
from cometbft_tpu.libs.rowblock import RowBlock, SigColumns
from cometbft_tpu.ops import challenge
from cometbft_tpu.ops import ed25519_kernel as EK
from cometbft_tpu.ops import sr25519_kernel as SRK
from cometbft_tpu.sched.scheduler import VerifyScheduler
from cometbft_tpu.types import commit as commit_mod
from cometbft_tpu.types import validation
from cometbft_tpu.types.basic import BlockID, BlockIDFlag, PartSetHeader
from cometbft_tpu.types.commit import Commit, CommitSig
from cometbft_tpu.types.validation import Fraction
from cometbft_tpu.types.validator import Validator, ValidatorSet
from cometbft_tpu.utils import cmttime

CHAIN = "row-block-chain"
HEIGHT = 77
BLOCK_ID = BlockID(hash=b"\x11" * 32,
                   part_set_header=PartSetHeader(total=1, hash=b"\x22" * 32))
PATHS = {"lane": 1 << 62, "block": 0}  # ROW_BLOCK_MIN that forces each
COMMIT, NIL, ABSENT = BlockIDFlag.COMMIT, BlockIDFlag.NIL, BlockIDFlag.ABSENT


@pytest.fixture(autouse=True)
def _cpu_backend():
    prev = crypto_batch.get_backend()
    crypto_batch.set_backend("cpu")
    sched.reset()
    yield
    sched.reset()
    crypto_batch.set_backend(prev)


@pytest.fixture
def array_pass_always(monkeypatch):
    """Sign-rows by the array pass at every size, so that the block path
    can be forced on commits under VECTOR_SIGN_ROWS_MIN rows too."""
    monkeypatch.setattr(commit_mod, "VECTOR_SIGN_ROWS_MIN", 0)


# ------------------------------------------------------------- committees


class Committee:
    """Signers (sorted as the set sorts them), their set, and commits."""

    def __init__(self, n: int, schemes: int = 1, power=10, seed: int = 1):
        kinds = (ed25519, sr25519)[:schemes]
        privs = [kinds[i % schemes].gen_priv_key_from_secret(
            b"row-block-%d-%d" % (seed, i)) for i in range(n)]
        powers = power if isinstance(power, list) else [power] * n
        self.vals = ValidatorSet([Validator.new(p.pub_key(), w)
                                  for p, w in zip(privs, powers)])
        by_addr = {p.pub_key().address(): p for p in privs}
        self.privs = [by_addr[v.address] for v in self.vals.validators]
        self.n = n

    def commit(self, flags=None, nanos=None) -> Commit:
        """A signed commit: flags[i] (COMMIT unless given) and a stamp a
        row on the benchmark's grid, every third one short a byte so that
        off-length rows (MsgBlock's other classes) are among them."""
        flags = flags or [COMMIT] * self.n
        sigs = []
        for i, (v, flag) in enumerate(zip(self.vals.validators, flags)):
            if flag == ABSENT:
                sigs.append(CommitSig.absent())
                continue
            ns = nanos[i] if nanos else (
                1_000_000 if i % 3 == 1 else 300_000_000 + i * 1_000_000)
            sigs.append(CommitSig(flag, v.address,
                                  cmttime.Timestamp(1_790_000_000, ns), b""))
        commit = Commit(height=HEIGHT, round_=0, block_id=BLOCK_ID,
                        signatures=sigs)
        for i, cs in enumerate(sigs):
            if cs.block_id_flag != ABSENT:
                cs.signature = self.privs[i].sign(
                    commit.vote_sign_bytes(CHAIN, i))
        return commit


def fresh(commit: Commit, edit=None) -> Commit:
    """A new Commit over copies of the signatures (no memo rides along),
    edited by edit(signatures) if given."""
    sigs = [CommitSig(cs.block_id_flag, cs.validator_address, cs.timestamp,
                      cs.signature) for cs in commit.signatures]
    if edit is not None:
        edit(sigs)
    return Commit(height=commit.height, round_=commit.round_,
                  block_id=commit.block_id, signatures=sigs)


def flip(*lanes):
    def edit(sigs):
        for lane in lanes:
            s = sigs[lane].signature
            sigs[lane].signature = bytes([s[0] ^ 1]) + s[1:]
    return edit


def outcome(call):
    try:
        call()
    except Exception as exc:  # noqa: BLE001 - the exception is the result
        return type(exc).__name__, str(exc)
    return "ok", ""


def both_paths(monkeypatch, call_of):
    """{path: outcome} of call_of() (a fresh call a path) on either path,
    with the paths that really ran."""
    out = {}
    for name, forced in PATHS.items():
        monkeypatch.setattr(validation, "ROW_BLOCK_MIN", forced)
        ran = []
        real = validation._select_block

        def spy(*a, _real=real, _ran=ran, **kw):
            _ran.append("block")
            return _real(*a, **kw)

        monkeypatch.setattr(validation, "_select_block", spy)
        out[name] = outcome(call_of())
        monkeypatch.setattr(validation, "_select_block", real)
        out[name + "_ran"] = bool(ran)
    return out


def verify_full(vals, commit):
    return lambda: validation.verify_commit(
        CHAIN, vals, commit.block_id, commit.height, commit)


def verify_light(vals, commit):
    return lambda: validation.verify_commit_light(
        CHAIN, vals, commit.block_id, commit.height, commit)


def verify_staged(vals, commit):
    def call():
        s = validation.stage_verify_commit(
            CHAIN, vals, commit.block_id, commit.height, commit)
        validation.prefetch_staged([s])
        s.finish()
    return call


ENTRIES = {"full": verify_full, "light": verify_light,
           "staged": verify_staged}


@pytest.fixture(scope="module")
def one_scheme():
    c = Committee(40, schemes=1)
    return c, c.commit()


@pytest.fixture(scope="module")
def two_schemes():
    c = Committee(40, schemes=2, seed=2)
    return c, c.commit()


def _lanes(c: Committee, scheme: str) -> list[int]:
    return [i for i, v in enumerate(c.vals.validators)
            if v.pub_key.type_() == scheme]


# ------------------------------------------- verdicts, block against lane


def _bad_signature_cases():
    """(key types in the committee, name, ((key type, which of its lanes),
    ...))."""
    cases = []
    for schemes in (1, 2):
        kinds = ("ed25519", "sr25519")[:schemes]
        cases.append((schemes, "good", ()))
        for where, at in (("first", 0), ("middle", 0.5), ("last", -1)):
            for scheme in kinds:
                cases.append((schemes, f"flip-{scheme}-{where}",
                              ((scheme, at),)))
        cases.append((schemes, "two-bad",
                      (("ed25519", -1), ("ed25519", 0.5))))
    cases.append((2, "two-bad-two-schemes",
                  (("sr25519", -1), ("ed25519", 1))))
    return cases


@pytest.mark.parametrize("entry", list(ENTRIES))
@pytest.mark.parametrize("schemes,name,bad", _bad_signature_cases(),
                         ids=[f"{c[0]}-{c[1]}"
                              for c in _bad_signature_cases()])
def test_block_path_answers_as_the_lane_path(
        monkeypatch, array_pass_always, one_scheme, two_schemes, schemes,
        name, bad, entry):
    c, commit = one_scheme if schemes == 1 else two_schemes
    lanes = []
    for scheme, at in bad:
        own = _lanes(c, scheme)
        lanes.append(own[int(at * len(own)) if isinstance(at, float) else at])
    got = both_paths(monkeypatch, lambda: ENTRIES[entry](
        c.vals, fresh(commit, flip(*lanes))))
    assert got["block_ran"] and not got["lane_ran"]
    assert got["block"] == got["lane"]
    if not lanes:
        assert got["block"] == ("ok", "")
    elif entry != "light" or min(lanes) <= 2 * c.n // 3:
        # the lower index is named (the light check stops at +2/3 and may
        # never reach a later one)
        kind, text = got["block"]
        assert kind == "ErrInvalidCommitSignature"
        assert text.startswith(f"wrong signature (#{min(lanes)}): ")


@pytest.mark.parametrize("entry", list(ENTRIES))
@pytest.mark.parametrize("lane", [0, 20, -1])
def test_a_63_byte_signature_is_named_by_the_serial_fallback(
        monkeypatch, array_pass_always, two_schemes, lane, entry):
    c, commit = two_schemes

    def cut(sigs):
        sigs[lane].signature = sigs[lane].signature[:63]

    got = both_paths(monkeypatch, lambda: ENTRIES[entry](
        c.vals, fresh(commit, cut)))
    assert got["block"] == got["lane"]
    if entry == "light" and lane == -1:
        assert got["block"] == ("ok", "")  # past +2/3: never taken
    else:
        assert got["block"][0] in ("ErrInvalidCommitSignature",
                                   "ErrInvalidSignature")


@pytest.mark.parametrize("entry", list(ENTRIES))
@pytest.mark.parametrize("flag", [ABSENT, NIL], ids=["absent", "nil"])
@pytest.mark.parametrize("where", ["first", "middle", "last", "spread"])
@pytest.mark.parametrize("schemes", [1, 2])
def test_absent_and_nil_rows(monkeypatch, array_pass_always, schemes, where,
                             flag, entry):
    c = Committee(40, schemes=schemes, seed=3)
    flags = [COMMIT] * c.n
    for i in {"first": [0], "middle": [19, 20], "last": [c.n - 1],
              "spread": list(range(0, c.n, 4))}[where]:
        flags[i] = flag
    commit = c.commit(flags)
    got = both_paths(monkeypatch, lambda: ENTRIES[entry](
        c.vals, fresh(commit)))
    assert got["block"] == got["lane"] == ("ok", "")
    # and with a bad signature behind the hole
    got = both_paths(monkeypatch, lambda: ENTRIES[entry](
        c.vals, fresh(commit, flip(21))))
    assert got["block"] == got["lane"]
    assert got["block"][0] == "ErrInvalidCommitSignature"


@pytest.mark.parametrize("entry", list(ENTRIES))
def test_all_absent(monkeypatch, array_pass_always, entry):
    c = Committee(40)
    commit = c.commit([ABSENT] * c.n)
    got = both_paths(monkeypatch, lambda: ENTRIES[entry](
        c.vals, fresh(commit)))
    assert got["block"] == got["lane"]
    assert got["block"] == (
        "ErrNotEnoughVotingPowerSigned",
        "invalid commit -- insufficient voting power: got 0, needed more "
        f"than {c.vals.total_voting_power() * 2 // 3}")


@pytest.mark.parametrize("entry", list(ENTRIES))
@pytest.mark.parametrize("signed,want", [(26, "ErrNotEnoughVotingPowerSigned"),
                                         (27, "ok")],
                         ids=["exactly-two-thirds", "one-over"])
def test_power_at_the_threshold(monkeypatch, array_pass_always, entry,
                                signed, want):
    """39 validators of power 1: needed 26; NIL votes are checked by the
    full path and tallied by none."""
    c = Committee(39, power=1, seed=4)
    flags = [COMMIT] * signed + [NIL] * 3 + [ABSENT] * (c.n - signed - 3)
    commit = c.commit(flags)
    got = both_paths(monkeypatch, lambda: ENTRIES[entry](
        c.vals, fresh(commit)))
    assert got["block"] == got["lane"]
    assert got["block"][0] == want
    if want != "ok":
        assert got["block"][1].endswith(f"got {signed}, needed more than 26")


def _rows_of(block: RowBlock):
    keys, msgs, sigs = block.lists()
    return [k.bytes_() for k in keys], msgs, sigs


@pytest.mark.parametrize("powers", ["equal", "uneven", "heavy-first"])
@pytest.mark.parametrize("schemes", [1, 2])
def test_the_early_stop_takes_the_rows_of_the_loops_break(
        monkeypatch, array_pass_always, schemes, powers):
    n = 40
    power = {"equal": 10, "uneven": [1 + (7 * i) % 13 for i in range(n)],
             "heavy-first": [500] + [1] * (n - 1)}[powers]
    c = Committee(n, schemes=schemes, power=power, seed=5)
    flags = [COMMIT] * n
    flags[1] = ABSENT
    flags[5] = NIL
    commit = c.commit(flags)
    needed = c.vals.total_voting_power() * 2 // 3
    taken = {}
    for name, forced in PATHS.items():
        monkeypatch.setattr(validation, "ROW_BLOCK_MIN", forced)
        block, idxs = validation._commit_rows(
            CHAIN, c.vals, fresh(commit), needed, commit_only=True,
            count_all_signatures=False, lookup_by_index=True)
        taken[name] = ([int(i) for i in idxs], list(block.parts),
                       *_rows_of(block))
    assert taken["block"] == taken["lane"]
    assert len(taken["block"][0]) < n - 2  # it did stop early
    assert 1 not in taken["block"][0] and 5 not in taken["block"][0]


@pytest.mark.parametrize("n", [1, 2, 150, commit_mod.ROW_BLOCK_MIN - 1,
                               commit_mod.ROW_BLOCK_MIN, 256])
def test_sizes_around_the_crossover_take_the_path_the_constant_says(
        monkeypatch, n):
    """Nothing forced: the row count alone chooses, and both sides of the
    constant give the verdicts of the lane path."""
    c = Committee(n, seed=6)
    commit = c.commit()
    ran = []
    real = validation._select_block
    monkeypatch.setattr(validation, "_select_block", lambda *a, **kw: (
        ran.append(1), real(*a, **kw))[1])
    assert outcome(verify_full(c.vals, fresh(commit))) == ("ok", "")
    assert bool(ran) == (n >= commit_mod.ROW_BLOCK_MIN)
    bad = n // 2
    want = outcome(lambda: validation._verify_commit_single(
        CHAIN, c.vals, fresh(commit, flip(bad)), 0, False, True, True))
    assert want[0] == "ErrInvalidCommitSignature"
    assert outcome(verify_full(c.vals, fresh(commit, flip(bad)))) == want
    assert commit_mod.ROW_BLOCK_MIN >= commit_mod.VECTOR_SIGN_ROWS_MIN


class _FakeScheme:
    """A stand-in for signature verification at 10,240 rows: a signature
    is valid iff it is sha512(key || message). It holds the scheduler's
    host rung to the ALIGNMENT of keys, messages and signatures through
    the block, which is what the block path could get wrong."""

    @staticmethod
    def sign(pub: bytes, msg: bytes) -> bytes:
        return hashlib.sha512(pub + msg).digest()

    @staticmethod
    def host_mask(scheme: str, cols: SigColumns) -> np.ndarray:
        return np.fromiter(
            (hashlib.sha512(p + m).digest() == s for p, m, s in zip(
                cols.pubs, cols.msgs.tolist(), cols.sig_list())),
            dtype=bool, count=len(cols))


@pytest.fixture(scope="module")
def mega():
    """10,240 validators of two key types (random keys: _FakeScheme signs)
    and their full commit."""
    from tools.row_block_crossover import committee

    vals, commit = committee(10_240, 2, seed=41)
    for i, (cs, v) in enumerate(zip(commit.signatures, vals.validators)):
        cs.signature = _FakeScheme.sign(
            v.pub_key.bytes_(), commit.vote_sign_bytes(
                "committee-10k", i))
    return vals, commit


@pytest.mark.parametrize("bad", [(), (0,), (5_000,), (10_239,),
                                 (9_000, 4_001)],
                         ids=["good", "first", "middle", "last", "two"])
def test_10240_rows_of_two_key_types(monkeypatch, mega, bad):
    vals, commit = mega
    monkeypatch.setattr(VerifyScheduler, "_host_mask",
                        staticmethod(_FakeScheme.host_mask))
    got = both_paths(monkeypatch, lambda: (lambda c: lambda: (
        validation.verify_commit("committee-10k", vals, c.block_id,
                                 c.height, c)))(fresh(commit, flip(*bad))))
    assert got["block_ran"] and not got["lane_ran"]
    assert got["block"] == got["lane"]
    if bad:
        assert got["block"][1].startswith(f"wrong signature (#{min(bad)}): ")
    else:
        assert got["block"] == ("ok", "")


def test_the_trusting_check_joins_over_columns_with_the_loops_double_vote_error(
        monkeypatch, array_pass_always, one_scheme):
    c, commit = one_scheme

    def twice(sigs):
        sigs[7] = CommitSig(COMMIT, sigs[3].validator_address,
                            sigs[3].timestamp, sigs[3].signature)

    level = Fraction(1, 3)
    got = both_paths(monkeypatch, lambda: (
        lambda cm: lambda: validation.verify_commit_light_trusting(
            CHAIN, c.vals, cm, level))(fresh(commit, twice)))
    # since PR 35 the address lookups run over columns too (path `join`:
    # tests/test_light_cell.py holds it to the loop row for row)
    assert got["block_ran"] and not got["lane_ran"]
    assert got["block"] == got["lane"]
    addr = c.vals.validators[3].address.hex()
    assert got["block"] == ("ValueError",
                            f"double vote from {addr} (3 and 7)")
    got = both_paths(monkeypatch, lambda: (
        lambda cm: lambda: validation.verify_commit_light_trusting(
            CHAIN, c.vals, cm, level))(fresh(commit)))
    assert got["block"] == got["lane"] == ("ok", "")


def test_a_signature_set_after_the_sign_rows_were_built_is_the_one_checked(
        monkeypatch, array_pass_always, one_scheme):
    """Callers sign over vote_sign_bytes_all's rows and then set the
    signatures on the same Commit: the block path reads them each call."""
    c, _ = one_scheme
    monkeypatch.setattr(validation, "ROW_BLOCK_MIN", 0)
    commit = c.commit()
    for cs in commit.signatures:
        cs.signature = b"\x00" * 64
    rows = commit.vote_sign_bytes_all(CHAIN)
    assert outcome(verify_full(c.vals, commit))[0] == \
        "ErrInvalidCommitSignature"
    for i, cs in enumerate(commit.signatures):
        cs.signature = c.privs[i].sign(rows[i])
    assert outcome(verify_full(c.vals, commit)) == ("ok", "")


# ------------------------------------------------------------ the scheduler


def _capture_kernels(monkeypatch):
    """Backend "tpu" with both kernels' entries replaced by host thunks
    that record what the scheduler handed them."""
    seen = []

    def entry(scheme, verify_fn):
        def verify_batch_async(pubs, msgs, sigs, cache=None,
                               recheck_groups=None, pub_rows=None):
            seen.append({"scheme": scheme, "bounds": recheck_groups,
                         "pubs": list(pubs), "msgs": list(msgs),
                         "sigs": [EK._row_bytes(s) for s in sigs],
                         "columns": isinstance(sigs, np.ndarray)})
            n = len(sigs)
            return EK.make_host_thunk(
                n, np.ones(n, dtype=bool), (pubs, msgs, sigs),
                (verify_fn, scheme, recheck_groups))
        return verify_batch_async

    from cometbft_tpu.crypto import ed25519_math as oracle
    from cometbft_tpu.crypto import sr25519_math as srm

    crypto_batch.set_backend("tpu")
    monkeypatch.setattr(EK, "verify_batch_async",
                        entry("ed25519", oracle.verify_zip215))
    monkeypatch.setattr(SRK, "verify_batch_async",
                        entry("sr25519", srm.verify))
    from cometbft_tpu.parallel import mesh as verify_mesh

    monkeypatch.setattr(verify_mesh, "active", lambda: None)
    return seen


_rider_sigs: dict = {}


def _rider_rows(c: Committee, lanes, bad=()):
    """Rows signed once a (key, message): sr25519 signs with a fresh nonce
    every time, and the two forms of a test are compared row for row."""
    rows = []
    for i in lanes:
        msg = b"rider-%d" % i
        pub = c.vals.validators[i].pub_key
        sig = _rider_sigs.setdefault((pub.bytes_(), msg),
                                     c.privs[i].sign(msg))
        if i in bad:
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        rows.append((pub, msg, sig))
    return rows


_handed: dict = {}  # form -> what the kernels were handed, to compare them


@pytest.mark.parametrize("form", ["block", "tuples"])
def test_a_block_group_rides_with_list_riders_of_both_schemes(
        monkeypatch, array_pass_always, two_schemes, form):
    """One drain: riders queued as tuples (a bad one among them), then two
    commits' groups. Every group gets its own mask, and the kernels get
    the same rows and the same recheck bounds whether the commits' rows
    came as blocks or as lists of tuples."""
    c, commit = two_schemes
    seen = _capture_kernels(monkeypatch)
    monkeypatch.setattr(validation, "ROW_BLOCK_MIN", 0)
    needed = c.vals.total_voting_power() * 2 // 3
    groups = []
    for edit in (None, flip(9)):
        block, _ = validation._commit_rows(
            CHAIN, c.vals, fresh(commit, edit), needed, False, True, True)
        groups.append(block if form == "block"
                      else list(zip(*block.lists())))
    s = VerifyScheduler()
    riders = _rider_rows(c, [0, 1, 2, 3, 4], bad={3})
    futures = s.submit(riders, klass=sched.MEMPOOL, deadline=1e9)
    masks = s.verify_many(groups, sched.CONSENSUS)
    s.stop()
    assert [bool(f.result(0)) for f in futures] == [
        True, True, True, False, True]
    assert masks[0].all() and len(masks[0]) == c.n
    assert np.flatnonzero(~masks[1]).tolist() == [9]
    by_scheme = {d["scheme"]: d for d in seen}
    assert set(by_scheme) == {"ed25519", "sr25519"}
    n_ed = len(_lanes(c, "ed25519"))
    r_ed = sum(1 for r in riders if r[0].type_() == "ed25519")
    assert by_scheme["ed25519"]["bounds"] == [
        (0, n_ed), (n_ed, 2 * n_ed), (2 * n_ed, 2 * n_ed + r_ed)]
    _handed[form] = {
        k: {f: d[f] for f in ("bounds", "pubs", "msgs", "sigs")}
        for k, d in by_scheme.items()}
    if len(_handed) == 2:
        assert _handed["block"] == _handed["tuples"]


@pytest.mark.parametrize("bad_commit", [None, 0, 2])
def test_a_window_of_blocks_through_verify_many(
        monkeypatch, array_pass_always, two_schemes, bad_commit):
    c, commit = two_schemes
    got = {}
    for name, forced in PATHS.items():
        monkeypatch.setattr(validation, "ROW_BLOCK_MIN", forced)
        window = [fresh(commit, flip(11) if k == bad_commit else None)
                  for k in range(3)]
        staged = [validation.stage_verify_commit(
            CHAIN, c.vals, cm.block_id, cm.height, cm) for cm in window]
        assert all(isinstance(s._rows, RowBlock) for s in staged)
        validation.prefetch_staged(staged)
        got[name] = [outcome(s.finish) for s in staged]
    assert got["block"] == got["lane"]
    for k, (kind, text) in enumerate(got["block"]):
        if k == bad_commit:
            assert text.startswith("wrong signature (#11): ")
        else:
            assert kind == "ok"


def test_verify_now_takes_an_empty_block_and_a_block_is_not_copied():
    s = VerifyScheduler()
    assert len(s.verify_now(RowBlock(0, {}))) == 0
    c = Committee(4, seed=8)
    block = RowBlock.from_tuples(_rider_rows(c, [0, 1, 2, 3]))
    kept = []
    real = s._dispatch
    s._dispatch = lambda groups: (kept.extend(groups), real(groups))[1]
    assert s.verify_now(block).all()
    assert kept[0].rows is block


# ------------------------------------------------------------ kernel staging


def _staging_inputs(scheme_mod, n=48):
    """One key type's rows twice over: as the block path hands them (a
    SigColumns off a commit's block) and as the lists the kernels always
    took (keys, PrefixedMsg / bytes rows, signatures), with off-length
    stamps, two NIL rows and one s >= L signature among them."""
    c = Committee(n, schemes=1, seed=9)
    if scheme_mod is sr25519:
        c = Committee(2 * n, schemes=2, seed=9)
    flags = [COMMIT] * c.n
    flags[4] = flags[5] = NIL
    commit = c.commit(flags)
    big_s = (EK.oracle.L + 5).to_bytes(32, "little")
    for lane in (6, 7):
        commit.signatures[lane].signature = \
            commit.signatures[lane].signature[:32] + big_s
    was = validation.ROW_BLOCK_MIN
    validation.ROW_BLOCK_MIN = 0
    try:
        block, idxs = validation._commit_rows(
            CHAIN, c.vals, commit, 0, False, True, True)
    finally:
        validation.ROW_BLOCK_MIN = was
    scheme = "sr25519" if scheme_mod is sr25519 else "ed25519"
    lanes, cols = block.parts[scheme]
    at = [int(idxs[i]) for i in lanes]
    legacy = SharedPrefixRows(*commit_mod._sign_row_parts_scalar(
        commit.signatures, *_heads(commit)))
    msgs = legacy.rows_for(at)
    assert any(isinstance(m, PrefixedMsg) for m in msgs)
    assert any(isinstance(m, bytes) for m in msgs)
    pubs = [c.vals.validators[i].pub_key.bytes_() for i in at]
    sigs = [commit.signatures[i].signature for i in at]
    assert isinstance(cols.sigs, np.ndarray) and cols.pub_rows is not None
    return cols, (pubs, msgs, sigs)


def _heads(commit: Commit):
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.basic import SignedMsgType
    from cometbft_tpu.utils import protobuf as pb

    w = pb.Writer()
    w.uvarint(1, int(SignedMsgType.PRECOMMIT))
    w.sfixed64(2, commit.height)
    w.sfixed64(3, commit.round_)
    head_nil = w.output()
    w.message(4, canonical.canonical_block_id_bytes(commit.block_id))
    return w.output(), head_nil, pb.Writer().string(6, CHAIN).output()


PLAN_FIELDS = ("plen", "tlen", "var", "slen", "n", "n_eligible", "n_fallback")


def test_the_device_challenge_plan_and_block_from_columns_equal_the_lists():
    cols, (pubs, msgs, sigs) = _staging_inputs(ed25519)
    made = {}
    for name, (p, m, s, rows) in {
            "columns": (cols.pubs, cols.msgs, cols.sigs, cols.pub_rows),
            "lists": (pubs, msgs, sigs, None)}.items():
        challenge.reset()
        pre_ok, safe, sig_rows, pub_rows = EK._structural_stage(p, s, rows)
        plan = challenge.plan_batch(m, pre_ok, put_key="rowblock")
        assert plan is not None
        b = EK.bucket_size(len(s))
        block = np.zeros(challenge.block_words(b, plan.var), dtype=np.uint32)
        EK._pack_device_block(sig_rows, b, plan, block)
        made[name] = (pre_ok, safe, sig_rows, pub_rows, plan, block,
                      EK._host_checksum(block))
    a, b = made["columns"], made["lists"]
    assert not a[0].all() and a[0].sum() == len(a[0]) - 2  # the s >= L rows
    for x, y in zip(a[:4], b[:4]):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    for f in PLAN_FIELDS:
        assert getattr(a[4], f) == getattr(b[4], f), f
    for f in ("pids", "eligible", "vbytes"):
        assert np.array_equal(getattr(a[4], f), getattr(b[4], f)), f
    assert 0 < a[4].n_fallback < a[4].n  # off-length and NIL rows
    assert np.array_equal(a[5], b[5])
    assert a[6] == b[6]


def test_the_host_k_words_from_columns_equal_the_lists():
    cols, (pubs, msgs, sigs) = _staging_inputs(ed25519)
    made = []
    for p, m, s, rows in ((cols.pubs, cols.msgs, cols.sigs, cols.pub_rows),
                          (pubs, msgs, sigs, None)):
        pre_ok, _safe, sig_rows, pub_rows = EK._structural_stage(p, s, rows)
        words = np.stack(EK._pack_host_words(
            pre_ok, sig_rows, pub_rows, m, EK.bucket_size(len(s))))
        made.append((words, EK._host_checksum(*words)))
    assert np.array_equal(made[0][0], made[1][0])
    assert made[0][1] == made[1][1]
    # and the uniform-length branch: the rows of one class alone
    shared = np.flatnonzero(cols.msgs.cls == 0)
    sub = cols.msgs.take(shared)
    assert np.array_equal(
        EK._challenge_words(cols.sigs[shared, :32], cols.pub_rows[shared],
                            sub, sub.lengths(), np.ones(len(shared), bool)),
        EK._challenge_words(cols.sigs[shared, :32], cols.pub_rows[shared],
                            [msgs[i] for i in shared], sub.lengths(),
                            np.ones(len(shared), bool)))


def test_sr25519_staging_from_columns_equals_the_lists():
    cols, (pubs, msgs, sigs) = _staging_inputs(sr25519)
    b = SRK.bucket_size(len(sigs))
    a = SRK.stage_rows_sr(cols.pubs, cols.msgs, cols.sigs, b,
                          pub_rows=cols.pub_rows)
    want = SRK.stage_rows_sr(pubs, msgs, sigs, b)
    assert not want[0].all()  # the s >= L rows
    assert np.array_equal(a[0], want[0])
    assert list(a[1]) == list(want[1])
    for x, y in zip(a[2:], want[2:]):
        assert np.array_equal(x, y)
    assert EK._host_checksum(*a[2:]) == EK._host_checksum(*want[2:])
    # the caller's matrix was not written to
    assert cols.sig_list() == sigs


@pytest.mark.parametrize("take", ["all", "evens", "reversed", "few"])
def test_msg_block_is_the_rows_it_was_made_from(take):
    prefix = b"\x08\x02" + b"H" * 60
    rows = [PrefixedMsg(prefix, b"%03d-suffix" % i) if i % 3
            else b"plain-row-%04d" % i if i % 2
            else PrefixedMsg(b"other" * 4, b"%02d" % i) for i in range(50)]
    exact = [bytes(r) for r in rows]
    block = MsgBlock.from_list(rows)
    assert block.tolist() == exact == list(block)
    assert [block[i] for i in range(50)] == exact
    assert block.lengths().tolist() == [len(r) for r in exact]
    sel = {"all": list(range(50)), "evens": list(range(0, 50, 2)),
           "reversed": list(range(49, -1, -1)), "few": [7, 7, 3]}[take]
    sub = block.take(np.asarray(sel))
    assert sub.tolist() == [exact[i] for i in sel]
    assert block[10:20].tolist() == exact[10:20]
    joined = MsgBlock.concat([sub, block, MsgBlock.from_list([])])
    assert joined.tolist() == [exact[i] for i in sel] + exact
    for mlen in set(sub.lengths().tolist()):
        at = np.flatnonzero(sub.lengths() == mlen)
        got = sub.take(at).matrix(mlen)
        assert [r.tobytes() for r in got] == [exact[sel[i]] for i in at]
    firsts = [int(lanes[0]) for _c, lanes in sub.present()]
    assert firsts == sorted(firsts)


# ------------------------------------------------ the set's cached columns


def test_a_validator_set_change_drops_the_cached_columns():
    c = Committee(6, schemes=2, seed=10)
    vals = c.vals
    cols = vals.columns()
    assert vals.columns() is cols  # read once
    assert cols.schemes == tuple(dict.fromkeys(
        v.pub_key.type_() for v in vals.validators))
    assert [k.bytes_() for k in cols.keys] == [
        v.pub_key.bytes_() for v in vals.validators]
    assert cols.key_rows.tobytes() == b"".join(cols.key_bytes.tolist())
    assert cols.powers.tolist() == [10] * 6
    # priority moves and a copy keep them
    vals.increment_proposer_priority(3)
    assert vals.columns() is cols
    twin = vals.copy()
    assert twin.columns().keys is cols.keys
    assert twin.columns().src is twin.validators
    # a change of a power, a key, or the membership reads them anew
    v0 = vals.validators[0]
    vals.update_with_change_set([Validator(v0.address, v0.pub_key, 33)])
    assert vals.columns() is not cols
    assert vals.columns().powers.tolist().count(33) == 1
    assert twin.columns().powers.tolist() == [10] * 6  # the copy is its own
    newcomer = Validator.new(
        ed25519.gen_priv_key_from_secret(b"newcomer").pub_key(), 5)
    before = vals.columns()
    vals.update_with_change_set([newcomer])
    assert vals.columns() is not before and vals.columns().n == 7
    vals.update_with_change_set([Validator(v0.address, v0.pub_key, 0)])
    assert vals.columns().n == 6
    assert v0.pub_key.bytes_() not in vals.columns().key_bytes.tolist()


def test_a_block_after_a_set_change_carries_the_new_set(
        monkeypatch, array_pass_always):
    monkeypatch.setattr(validation, "ROW_BLOCK_MIN", 0)
    c = Committee(8, seed=11)
    commit = c.commit()
    assert outcome(verify_full(c.vals, fresh(commit))) == ("ok", "")
    v2 = c.vals.validators[2]
    other = ed25519.gen_priv_key_from_secret(b"rotated-key")
    c.vals.update_with_change_set(
        [Validator(v2.address, other.pub_key(), v2.voting_power)])
    kind, text = outcome(verify_full(c.vals, fresh(commit)))
    assert kind == "ErrInvalidCommitSignature"
    assert text.startswith("wrong signature (#2): ")


# ------------------------------------------- what a call leaves on the heap


def test_a_10240_row_call_keeps_under_1000_tracked_objects_alive(
        monkeypatch, mega):
    """S4's half of the block path: while a 10,240-row verify_commit is at
    its deepest (the scheduler's host rung, here a stub that answers all
    true), fewer than 1,000 collector-tracked objects exist that did not
    before the call. Before PR 31 ~20,000 did (a PrefixedMsg and two tuples
    a lane): promoted to the oldest generation a call at a time, they are
    what brought the mixed cell its full collections."""
    vals, commit = mega
    alive = {}

    def rung(scheme, cols):
        alive.setdefault("at", len(gc.get_objects()))
        return np.ones(len(cols), dtype=bool)

    monkeypatch.setattr(VerifyScheduler, "_host_mask", staticmethod(rung))
    counts = {}
    for name, forced in PATHS.items():
        monkeypatch.setattr(validation, "ROW_BLOCK_MIN", forced)
        cm = fresh(commit)
        validation.verify_commit("committee-10k", vals, cm.block_id,
                                 cm.height, fresh(commit))  # warm: caches
        alive.clear()
        gc.collect()
        gc.disable()
        try:
            before = len(gc.get_objects())
            validation.verify_commit("committee-10k", vals, cm.block_id,
                                     cm.height, cm)
        finally:
            gc.enable()
        counts[name] = alive["at"] - before
    # (the lane path hands a block over since PR 31 too: its loop makes
    # lists of untracked bytes and ints, and no object a lane either)
    assert counts["block"] < 1_000 and counts["lane"] < 1_000, counts


# ------------------------------------------- the counter and its metric


METRIC = "commit_rows_block_pct.commit"


@pytest.fixture
def tracer():
    trace.reset()
    trace.configure(enabled=True, capacity=256, slow_ms=-1.0)
    yield
    trace.configure(enabled=False)
    trace.reset()


def test_the_commit_rows_span_says_its_path_and_the_tracer_sums_them(
        monkeypatch, tracer, one_scheme):
    from cometbft_tpu.ops import dispatch

    c, commit = one_scheme  # 40 rows: the array pass built its sign-rows
    for name, forced in PATHS.items():
        monkeypatch.setattr(validation, "ROW_BLOCK_MIN", forced)
        assert outcome(verify_full(c.vals, fresh(commit))) == ("ok", "")
        said = [s["attrs"] for s in trace.snapshot()
                if s["name"] == "commit.rows" and "path" in s["attrs"]]
        assert said[-1] == {"path": name, "rows": c.n}
    assert outcome(verify_light(c.vals, fresh(commit))) == ("ok", "")
    stopped = 2 * c.n // 3 + 1
    assert trace.attribution()["commit_rows"] == {
        "block": c.n + stopped, "lane": c.n}
    # a window's selection and the verifier's add are `commit.rows` spans
    # too, and count on neither side
    assert sum(s["name"] == "commit.rows" for s in trace.snapshot()) > 3
    assert dispatch.health_snapshot()["attribution"]["commit_rows"] == {
        "block": c.n + stopped, "lane": c.n}
    trace.reset_attribution()
    assert trace.attribution()["commit_rows"] == {"block": 0, "lane": 0}


@pytest.mark.parametrize("block,lane,want", [
    (102_400, 0, 100.0), (1_500, 500, 75.0), (0, 150, 0.0),
    (0, 0, None),        # the tracer was off
    (None, None, None),  # a parent: its tracer has no such counter
])
def test_the_reader_of_commit_rows_block_pct(block, lane, want):
    from benchmarks import readers

    counters = {"attribution.rows": 102_400}
    if block is not None:
        counters["attribution.commit_rows.block"] = block
        counters["attribution.commit_rows.lane"] = lane
    reading = readers.read_metric(
        os.path.join(ROOT, "benchmarks", "metrics"), METRIC,
        {"counters": counters})
    if want is None:
        assert reading is None
    else:
        assert reading == {"value": pytest.approx(want), "unit": "%"}


def test_benchmark_json_lists_the_metric_for_both_cells_at_the_end():
    """Appended behind PR 29's metric, as a PR's entries go at the end of
    their list (later PRs' come behind it), with its reader's unit; its
    `workloads` hold the two cells it was written for."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    names = [m["name"] for m in bench["per_layer"]]
    assert names.count(METRIC) == 1
    assert names.index(METRIC) == names.index(
        "sign_rows_vector_pct.commit") + 1
    entry = dict(bench["per_layer"][names.index(METRIC)])
    cells = entry.pop("workloads")
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "node path",
        "moves": "commit_verify_ms"}
    assert cells[:2] == ["hub-150.commit", "committee-10k-mixed.commit"]
