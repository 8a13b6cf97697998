"""The light client's cell (`light-500.bisect`, PR 35) at small sizes on the
CPU: the program's hop (light.verifier.verify through
benchmarks/program_light.py) against the plain reference
(benchmarks/reference/light_ref.py) over drifting validator sets, every
answer of the vocabulary met; the trusting check's selection over columns
(path `block`, lookup `address`) against the lane loop, row for row; light.Client's
_verify_skipping visiting exactly the hops the cell's driver lists; the
spans and counters the cell's metrics read."""

from __future__ import annotations

import asyncio
import dataclasses
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import program_light  # noqa: E402
from benchmarks.drivers import light_bisect  # noqa: E402
from benchmarks.reference import commit_ref, light_ref  # noqa: E402
from cometbft_tpu import light, sched  # noqa: E402
from cometbft_tpu.crypto import batch as crypto_batch  # noqa: E402
from cometbft_tpu.crypto import ed25519  # noqa: E402
from cometbft_tpu.libs import trace  # noqa: E402
from cometbft_tpu.light import client as light_client  # noqa: E402
from cometbft_tpu.light import verifier  # noqa: E402
from cometbft_tpu.light.provider import Provider  # noqa: E402
from cometbft_tpu.light.store import LightStore  # noqa: E402
from cometbft_tpu.store.db import MemDB  # noqa: E402
from cometbft_tpu.types import commit as commit_mod  # noqa: E402
from cometbft_tpu.types import validation  # noqa: E402
from cometbft_tpu.types.basic import (BlockID, BlockIDFlag,  # noqa: E402
                                      PartSetHeader)
from cometbft_tpu.types.commit import Commit, CommitSig  # noqa: E402
from cometbft_tpu.types.validation import Fraction  # noqa: E402
from cometbft_tpu.types.validator import Validator, ValidatorSet  # noqa: E402
from cometbft_tpu.utils import cmttime  # noqa: E402


@pytest.fixture(autouse=True)
def _cpu_backend():
    prev = crypto_batch.get_backend()
    crypto_batch.set_backend("cpu")
    sched.reset()
    trace.reset()
    yield
    trace.reset()
    sched.reset()
    crypto_batch.set_backend(prev)


def _config(validators: int, heights: int, epoch_heights: int,
            rotated: int) -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "light-500.json")) as fh:
        config = json.load(fh)
    config["validators"] = {"ed25519": validators}
    config["heights"] = heights
    config["drift"].update(epoch_heights=epoch_heights,
                           rotated_per_epoch=rotated)
    return config


# ---------------------------------------------- the hop against light_ref

SIZES = {4: _config(4, 400, 8, 1), 37: _config(37, 1000, 8, 2),
         200: _config(200, 2000, 16, 4)}
_chains: dict = {}
_lane_memo: dict = {}


def _lane_ok(lane) -> bool:
    if lane not in _lane_memo:
        _lane_memo[lane] = commit_ref.verify_lane(lane)
    return _lane_memo[lane]


@pytest.mark.parametrize("seed", [35, 2**31 + 35])
def test_the_rings_targets_are_independent_uniform_draws(seed, capsys):
    """make_data's ring: the hops of bisection after bisection, each to a
    target drawn by itself, uniform over root + 1 .. heights, cut at
    `ring_hops`; the set-up line says how many heights the ring holds."""
    import random
    import types

    config = dict(SIZES[37], ring_hops=40)
    cell = types.SimpleNamespace(config=config, traffic={"corrupt_every": 24})
    light_bisect.make_data(cell, seed)
    draws = random.Random(seed ^ 0xB15EC7)
    want: list = []
    targets = []
    while len(want) < 40:
        targets.append(draws.randint(2, 1000))
        want += cell.chain.bisect(targets[-1], 1)
    assert cell.hops == want[:40]
    assert len(set(targets)) == len(targets) >= 3
    verified = {h.new for h in cell.hops if h.verdict == "accept"}
    assert (f"{len(verified)} of them verified on the device: within the "
            "prefix table's 256 rows") in capsys.readouterr().out


def _chain(n: int) -> light_bisect.Chain:
    if n not in _chains:
        _chains[n] = light_bisect.Chain(SIZES[n], 35_000 + n)
    return _chains[n]


def _signed(chain, height: int) -> light_ref.LightBlockSpec:
    if not chain.block(height).commit.sigs:
        chain.sign([height])
    return chain.block(height)


def _first_hop(chain, verdict: str | None, start: int = 3) -> int:
    """The first height from `start` whose hop from the trust root the
    reference answers `verdict` before any signature (None: takes rows)."""
    for height in range(start, chain.heights + 1):
        if light_ref.hop_rows(chain.block(1), chain.block(height),
                              chain.params)[2] == verdict:
            return height
    raise AssertionError(f"no hop answered {verdict}")


def _flip(spec, lane: int):
    return dataclasses.replace(spec, commit=spec.commit.with_flipped(lane))


def _case(n: int, name: str):
    """(trusted spec, new spec, params, the answer the case is there for)."""
    chain, params = _chain(n), _chain(n).params
    root = chain.block(1)
    near = _first_hop(chain, None)
    first, second, _ = light_ref.hop_rows(root, chain.block(near), params)
    new = _signed(chain, near)
    taken = {row[2] for row in first + second}
    if name == "accept":
        return root, new, params, "accept"
    if name == "accept_adjacent":
        return root, _signed(chain, 2), params, "accept"
    if name == "untrusted":
        far = _first_hop(chain, "reject:untrusted")
        return root, _signed(chain, far), params, "reject:untrusted"
    if name == "power":
        # as many absent as leave the new set no 2/3 and the old its 1/3
        present = len(new.vals.pubs) * 2 // 3
        return root, dataclasses.replace(new, absent=frozenset(
            range(present, len(new.vals.pubs)))), params, "reject:power"
    if name == "wrong_signature_in_both_checks":
        lane = min(row[2] for row in first)
        return root, _flip(new, lane), params, f"reject#{lane}"
    if name == "wrong_signature_in_the_new_sets_rows":
        lane = max(row[2] for row in second)
        return root, _flip(new, lane), params, f"reject#{lane}"
    if name == "wrong_signature_outside_the_rows_taken":
        lane = max(set(range(len(new.vals.pubs))) - taken)
        return root, _flip(new, lane), params, "accept"
    if name == "double_vote":
        a, b = sorted(row[2] for row in first)[:2]
        addresses = list(new.addresses)
        addresses[b] = addresses[a]
        return root, dataclasses.replace(
            new, addresses=tuple(addresses)), params, "reject:double-vote"
    if name == "wrong_validators_hash":
        header = dataclasses.replace(
            new.header, validators_hash=chain.set_hash(len(chain.sets) - 1))
        return root, dataclasses.replace(
            new, header=header, commit=dataclasses.replace(
                new.commit, block_hash=light_ref.header_hash(header))
        ), params, "reject:header"
    if name == "commit_for_another_header":
        return root, dataclasses.replace(new, commit=dataclasses.replace(
            new.commit, block_hash=b"\x07" * 32)), params, "reject:header"
    if name == "adjacent_wrong_next_validators_hash":
        wrong = dataclasses.replace(root, header=dataclasses.replace(
            root.header, next_validators_hash=b"\x09" * 32))
        return wrong, _signed(chain, 2), params, "reject:header"
    if name == "expired":
        return root, new, dataclasses.replace(
            params, trusting_period_ns=10**9), "reject:expired"
    raise AssertionError(name)


CASES = ("accept", "accept_adjacent", "untrusted", "power",
         "wrong_signature_in_both_checks",
         "wrong_signature_in_the_new_sets_rows",
         "wrong_signature_outside_the_rows_taken", "double_vote",
         "wrong_validators_hash", "commit_for_another_header",
         "adjacent_wrong_next_validators_hash", "expired")


@pytest.mark.parametrize("name", CASES)
@pytest.mark.parametrize("n", sorted(SIZES))
def test_the_programs_hop_answers_as_the_reference(n, name):
    trusted, new, params, answer = _case(n, name)
    assert light_ref.verify(trusted, new, params, _lane_ok) == answer
    t = program_light.build_light_block(trusted)
    b = program_light.build_light_block(new)
    header, vals = program_light.fresh_block(b, b.validator_set)
    assert program_light.verdict_of(lambda: verifier.verify(
        t.signed_header, t.validator_set, header, vals,
        *program_light.verify_args(params))) == answer


@pytest.mark.parametrize("n", sorted(SIZES))
def test_the_control_accepts_what_the_guarantee_refuses(n):
    chain = _chain(n)
    far = _first_hop(chain, "reject:untrusted")
    t = program_light.build_light_block(chain.block(1))
    b = program_light.build_light_block(_signed(chain, far))
    args = (t.signed_header, t.validator_set, b.signed_header,
            b.validator_set, *program_light.verify_args(chain.params))
    assert program_light.verdict_of(
        lambda: program_light.entries()["verify"](*args)
    ) == "reject:untrusted"
    assert program_light.verdict_of(
        lambda: program_light.control_entries()["verify"](*args)
    ) == "accept"


# ------------------------------------- the driver's hops are the client's


class _ChainProvider(Provider):
    """Light blocks of a light_bisect.Chain, made and signed when asked."""

    def __init__(self, chain):
        self.chain, self.asked, self._made = chain, [], {}

    def light_block_now(self, height: int):
        if height not in self._made:
            self._made[height] = program_light.build_light_block(
                _signed(self.chain, height))
        return self._made[height]

    async def light_block(self, height: int):
        self.asked.append(height)
        return self.light_block_now(height)

    async def report_evidence(self, ev) -> None:
        raise AssertionError("no evidence in this chain")


@pytest.mark.parametrize("target", [2, 3, 40, 333, 1000])
def test_verify_skipping_visits_the_hops_the_driver_lists(monkeypatch, target):
    chain = _chain(37)
    hops = chain.bisect(target, 1)
    provider = _ChainProvider(chain)
    root = provider.light_block_now(1)
    period, now, drift, level = program_light.verify_args(chain.params)
    client = light.Client(
        chain.chain_id, light.TrustOptions(
            period_ns=period, height=1, hash_=root.hash()),
        provider, [], LightStore(MemDB()), trust_level=level,
        max_clock_drift_ns=drift)
    client.checkpoint_source = lambda height: None  # no cache between hops
    visited = []
    real = verifier.verify

    def spy(trusted_header, trusted_vals, header, vals, *rest):
        try:
            real(trusted_header, trusted_vals, header, vals, *rest)
        except light.ErrNewValSetCantBeTrusted:
            visited.append((trusted_header.height, header.height,
                            "reject:untrusted"))
            raise
        visited.append((trusted_header.height, header.height, "accept"))

    monkeypatch.setattr(light_client.verifier, "verify", spy)
    got = asyncio.run(client._verify_skipping(
        provider, root, provider.light_block_now(target), now))
    assert visited == [(h.trusted, h.new, h.verdict) for h in hops]
    assert [lb.height for lb in got] == [1] + [
        h.new for h in hops if h.verdict == "accept"]
    # a pivot is fetched once, when the hop above it could not be trusted
    assert provider.asked == list(dict.fromkeys(
        h.new for h in hops if h.new != target))
    if target > 300:
        assert sum(h.verdict != "accept" for h in hops) >= 2


# ------------------------------------------------ the join against the loop

CHAIN = "light-join-chain"
BLOCK_ID = BlockID(hash=b"\x11" * 32,
                   part_set_header=PartSetHeader(total=1, hash=b"\x22" * 32))
_keys: list = []


def _pub(i: int):
    while len(_keys) <= i:
        _keys.append(ed25519.gen_priv_key_from_secret(
            b"light-join-%d" % len(_keys)).pub_key())
    return _keys[i]


def _sets(n: int, known: float = 0.55):
    """(the trusted set, the commit's own set): n validators each, `known`
    of the commit's also in the trusted one."""
    shared = int(n * known)
    trusted = ValidatorSet([Validator.new(_pub(i), 10 + i % 3)
                            for i in range(n)])
    own = ValidatorSet([Validator.new(_pub(i), 10)
                        for i in range(n - shared, 2 * n - shared)])
    return trusted, own


def _commit(own: ValidatorSet, nanos=lambda i: i * 1000, flags=None) -> Commit:
    flags = flags or {}
    sigs = []
    for i, v in enumerate(own.validators):
        flag = flags.get(i, BlockIDFlag.COMMIT)
        if flag == BlockIDFlag.ABSENT:
            sigs.append(CommitSig.absent())
        else:
            sigs.append(CommitSig(flag, v.address,
                                  cmttime.Timestamp(1_790_000_000, nanos(i)),
                                  bytes([i % 251, i // 251]) * 32))
    return Commit(height=77, round_=0, block_id=BLOCK_ID, signatures=sigs)


def _outcome(select):
    try:
        block, idxs = select()
    except Exception as exc:  # noqa: BLE001 - the outcome is the error
        return type(exc).__name__, str(exc)
    keys, msgs, sigs = block.lists()
    return ([k.bytes_() for k in keys], [bytes(m) for m in msgs],
            [bytes(s) for s in sigs], [int(i) for i in idxs],
            {scheme: lanes.tolist() for scheme, (lanes, _c)
             in block.parts.items()})


def _both(trusted, commit, level=Fraction(1, 3), count_all=False):
    needed = (trusted.total_voting_power() * level.numerator
              // level.denominator)
    rows = commit.vote_sign_bytes_all(CHAIN)
    assert rows.block is not None
    return (_outcome(lambda: validation._select_block(
                trusted, commit, rows, needed, True, count_all, False)),
            _outcome(lambda: validation._select_lanes(
                trusted, commit, rows, needed, True, count_all, False)))


def _double(commit, first: int, second: int):
    src = commit.signatures[first]
    commit.signatures[second] = CommitSig(
        BlockIDFlag.COMMIT, src.validator_address, src.timestamp,
        src.signature)
    return commit


def _at(trusted, own, known: bool) -> list[int]:
    """The commit's signatures whose validator the trusted set has (or
    has not)."""
    return [i for i, v in enumerate(own.validators)
            if trusted.has_address(v.address) == known]


SHAPES = {
    "plain": lambda trusted, own: _commit(own),
    "nil_and_absent_votes": lambda trusted, own: _commit(own, flags={
        **{i: BlockIDFlag.NIL for i in range(0, len(own), 7)},
        **{i: BlockIDFlag.ABSENT for i in range(3, len(own), 11)}}),
    "double_vote_before_the_threshold": lambda trusted, own: _double(
        _commit(own), *_at(trusted, own, True)[1:4:2]),
    "double_vote_after_the_threshold": lambda trusted, own: _double(
        _commit(own), *_at(trusted, own, True)[-2:]),
    "double_vote_of_an_unknown_validator": lambda trusted, own: _double(
        _commit(own), *_at(trusted, own, False)[:2]),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("n", [191, 192, 500])
def test_the_join_selects_the_loops_rows(n, shape):
    trusted, own = _sets(n)
    joined, looped = _both(trusted, SHAPES[shape](trusted, own))
    assert joined == looped
    if shape == "double_vote_before_the_threshold":
        a, b = _at(trusted, own, True)[1:4:2]
        assert joined == ("ValueError", "double vote from "
                          f"{own.validators[a].address.hex()} ({a} and {b})")
    else:
        assert isinstance(joined[0], list) and joined[0]


@pytest.mark.parametrize("level,known,count_all", [
    (Fraction(1, 3), 0.2, False), (Fraction(2, 3), 0.55, False),
    (Fraction(1, 1), 1.0, False), (Fraction(1, 3), 0.55, True),
    (Fraction(1, 3), 0.0, False)],
    ids=["too_few_known", "two_thirds_not_there", "the_whole_set_never_over",
         "every_signature_counted", "no_validator_known"])
def test_the_join_raises_and_tallies_as_the_loop(level, known, count_all):
    trusted, own = _sets(200, known)
    joined, looped = _both(trusted, _commit(own), level, count_all)
    assert joined == looped
    if not count_all:
        assert joined[0] == "ErrNotEnoughVotingPowerSigned"


@pytest.mark.parametrize("n,path", [(191, "lane"), (192, "block"),
                                    (500, "block")])
def test_commit_rows_takes_the_join_from_row_block_min_on(n, path):
    assert commit_mod.ROW_BLOCK_MIN == 192
    trusted, own = _sets(n)
    trace.configure(enabled=True)
    trace.reset_attribution()
    staged = validation.stage_verify_commit_light_trusting(
        CHAIN, trusted, _commit(own), Fraction(1, 3))
    span, = [s for s in trace.snapshot() if s["name"] == "commit.rows"
             and "path" in s["attrs"]]
    assert span["attrs"] == {"path": path, "rows": len(staged._rows),
                             "lookup": "address"}
    att = trace.attribution()
    rows = len(staged._rows)
    assert att["trusting_rows"] == {
        "joined": rows if path == "block" else 0,
        "scanned": rows if path == "lane" else 0}
    assert att["commit_rows"] == {"block": rows if path == "block" else 0,
                                  "lane": rows if path == "lane" else 0}


def test_stamps_past_int64_walk_the_lanes_at_any_size():
    trusted, own = _sets(200)
    commit = _commit(own)
    commit.signatures[5].timestamp = cmttime.Timestamp(1 << 63, 0)
    assert commit.vote_sign_bytes_all(CHAIN).block is None
    trace.configure(enabled=True)
    validation.stage_verify_commit_light_trusting(
        CHAIN, trusted, commit, Fraction(1, 3))
    span, = [s for s in trace.snapshot() if s["name"] == "commit.rows"
             and "path" in s["attrs"]]
    assert span["attrs"]["path"] == "lane"


@pytest.mark.parametrize("n", [4, 200])
def test_get_by_address_keeps_its_copy_and_its_misses(n):
    vals, other = _sets(n, 0.5)
    index = vals.address_index()
    assert index is vals.address_index()        # made once a set
    for i, v in enumerate(vals.validators):
        at, got = vals.get_by_address(v.address)
        assert at == i and got == v and got is not v
        assert vals.has_address(v.address)
    missing = [v for v in other.validators if v.address not in index]
    assert missing and vals.get_by_address(missing[0].address) == (-1, None)
    assert not vals.has_address(missing[0].address)
    copy = vals.copy()
    assert copy.address_index() is index        # the same addresses, in order
    gone = vals.validators[0]
    copy.update_with_change_set([Validator(
        address=gone.address, pub_key=gone.pub_key, voting_power=0)])
    assert copy.get_by_address(gone.address) == (-1, None)
    assert vals.get_by_address(gone.address)[0] == 0
    assert copy.get_by_address(vals.validators[1].address)[0] == 0


# ------------------------------------------------------ spans and counters


def test_a_hops_spans_and_counters():
    chain = _chain(37)
    near, far = _first_hop(chain, None), _first_hop(chain, "reject:untrusted")
    t = program_light.build_light_block(chain.block(1))
    args = program_light.verify_args(chain.params)
    offered = [(near, None, "accept"), (far, None, "reject:untrusted"),
               (near, 0, "reject#0"), (2, None, "accept"),
               (far, None, "reject:untrusted")]
    trace.configure(enabled=True)
    trace.reset_attribution()
    for height, lane, answer in offered:
        b = program_light.build_light_block(_signed(chain, height))
        header, vals = program_light.fresh_block(b, b.validator_set, lane)
        assert program_light.verdict_of(lambda: verifier.verify(
            t.signed_header, t.validator_set, header, vals, *args)) == answer
    att = trace.attribution()
    assert att["light"] == {"hops": 5, "hops_untrusted": 2}
    spans = trace.snapshot()
    hops = [s for s in spans if s["name"] == "light.verify"]
    # the counters add up with the spans' answers
    answers = [s["attrs"]["answer"] for s in hops]
    assert att["light"]["hops"] == sum(
        answers.count(a) for a in ("accepted", "untrusted", "rejected"))
    assert att["light"]["hops_untrusted"] == answers.count("untrusted")
    assert [(s["attrs"]["height"], s["attrs"]["answer"], s["attrs"]["adjacent"],
             s["attrs"]["trusted_height"]) for s in hops] == [
        (near, "accepted", False, 1), (far, "untrusted", False, 1),
        (near, "rejected", False, 1), (2, "accepted", True, 1),
        (far, "untrusted", False, 1)]
    assert all(s["cat"] == "node" and s["parent_id"] is None for s in hops)
    by_id = {s["id"]: s for s in spans}
    headers = [s for s in spans if s["name"] == "light.header"]
    assert len(headers) == 5
    assert all(s["cat"] == "header"
               and by_id[s["parent_id"]]["name"] == "light.verify"
               for s in headers)
    assert att["stage_us"]["header"] > 0
    # a hop's roots below the hop: the two staged checks and their one
    # prefetch (a hop to the next height: the one light check)
    under = [s["name"] for s in spans if s["parent_id"] == hops[0]["id"]]
    assert under.count("commit.stage_verify") == 2
    assert under.count("commit.prefetch") == 1
    # Merkle roots of validator sets: a set object is announced where it
    # carries no stamp (the trusted set once, the new set of every hop that
    # reaches its own check). Computed: the five header checks' and the
    # trusted set's one announce; the new sets' three announces find the
    # root their header check left on the set
    announced = [s for s in spans if s["name"] == "residency.announce"]
    assert all(s["cat"] == "header" and s["attrs"]["validators"] == 37
               for s in announced)
    assert len(announced) == 1 + 3
    assert att["valset"] == {"hashes": 5 + 1, "kept": 3}
    # the trusting check at 37 rows walks the lanes (under ROW_BLOCK_MIN)
    assert att["trusting_rows"]["joined"] == 0
    assert att["trusting_rows"]["scanned"] == 2 * len(
        light_ref.hop_rows(chain.block(1), chain.block(near),
                           chain.params)[0])


def test_nothing_is_counted_while_the_tracer_is_off():
    trace.configure(enabled=True)
    trace.reset_attribution()
    trace.configure(enabled=False)
    trusted, _own = _sets(8)
    trusted.hash()
    trace.count("light", "hops")
    trace.configure(enabled=True)
    att = trace.attribution()
    assert att["valset"] == {"hashes": 0, "kept": 0}
    assert att["light"]["hops"] == 0
    # a set object of its own: `trusted` keeps the root it computed above,
    # and a copy() of it would too
    ValidatorSet(trusted.validators).hash()
    trusted.hash()
    assert trace.attribution()["valset"] == {"hashes": 1, "kept": 1}
