"""The two builders of a commit's sign-rows (types/commit.py, ISSUE 29): the
array pass that commits of VECTOR_SIGN_ROWS_MIN rows and more take, and the
one-Writer-a-signature loop that stays for the smaller ones, for stamps past
int64, and as the oracle here. Every row of `vote_sign_bytes_all` is held
byte for byte to `vote_sign_bytes(chain_id, i)`, the three parts handed to
SharedPrefixRows to the scalar builder's, and the `commit.sign_bytes` span
and the tracer's `sign_rows` counters to the path that ran."""

from __future__ import annotations

import json
import os
import random
import sys

import numpy as np
import pytest

from cometbft_tpu.libs import trace
from cometbft_tpu.libs.prefixrows import PrefixedMsg, as_bytes
from cometbft_tpu.types import commit as commit_mod
from cometbft_tpu.types.basic import BlockID, BlockIDFlag, PartSetHeader
from cometbft_tpu.types.commit import Commit, CommitSig
from cometbft_tpu.utils import cmttime
from cometbft_tpu.utils import protobuf as pb

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import readers  # noqa: E402

CHAIN = "committee-10k"
MIN = commit_mod.VECTOR_SIGN_ROWS_MIN
SECOND = 1_790_000_000
NANOS_EDGES = (0, 1, 127, 128, 16_383, 16_384, 2**21 - 1, 2**21, 2**28 - 1,
               2**28, 999_999_999)
SECONDS_EDGES = (0, 1, 2**31, 2**35, -1_000_000_007, 2**63 + 12_345)
METRIC = "sign_rows_vector_pct.commit"
METRICS_DIR = os.path.join(ROOT, "benchmarks", "metrics")


@pytest.fixture(autouse=True)
def _tracer_off():
    trace.reset()
    yield
    trace.reset()


def _sig(seconds: int, nanos: int,
         flag: BlockIDFlag = BlockIDFlag.COMMIT, lane: int = 0) -> CommitSig:
    if flag == BlockIDFlag.ABSENT:
        return CommitSig.absent()
    return CommitSig(flag, lane.to_bytes(20, "big"),
                     cmttime.Timestamp(seconds, nanos), bytes(64))


def _commit(stamps, flags=None) -> Commit:
    """A commit of one signature a stamp; `flags` maps index -> flag for
    the rows that are not for the block."""
    flags = flags or {}
    sigs = [_sig(s, n, flags.get(i, BlockIDFlag.COMMIT), i)
            for i, (s, n) in enumerate(stamps)]
    block_id = BlockID(hash=b"\x01" * 32,
                       part_set_header=PartSetHeader(total=1,
                                                     hash=b"\x02" * 32))
    return Commit(height=1_000_000, round_=0, block_id=block_id,
                  signatures=sigs)


def _grid(n: int, seed: int = 29) -> list[tuple[int, int]]:
    """The benchmark's stamp shape (benchmarks/datagen.make_ring): the
    same milliseconds spread evenly over one second, shuffled."""
    millis = [j * 1000 // n for j in range(n)]
    random.Random(seed).shuffle(millis)
    return [(SECOND, ms * 1_000_000) for ms in millis]


def _parts(commit: Commit, builder, chain_id: str = CHAIN):
    """(prefix, suffixes, exceptions) of one builder over the commit,
    through Commit._build_sign_rows with the crossover forced."""
    was = commit_mod.VECTOR_SIGN_ROWS_MIN
    commit_mod.VECTOR_SIGN_ROWS_MIN = {"vector": 0, "scalar": 1 << 62}[builder]
    try:
        commit._sign_rows = {}
        rows, path = commit._build_sign_rows(chain_id)
    finally:
        commit_mod.VECTOR_SIGN_ROWS_MIN = was
        commit._sign_rows = None
    return rows, path


def _hold_to_per_index(commit: Commit, chain_id: str = CHAIN,
                       path: str | None = None) -> None:
    """Both builders against vote_sign_bytes, index for index, and against
    each other part for part."""
    n = len(commit.signatures)
    want = [commit.vote_sign_bytes(chain_id, i) for i in range(n)]
    scalar, said = _parts(commit, "scalar", chain_id)
    assert said == "scalar"
    vector, said = _parts(commit, "vector", chain_id)
    assert said == (path or "vector")
    for rows in (scalar, vector, commit.vote_sign_bytes_all(chain_id)):
        assert len(rows) == n
        assert [rows[i] for i in range(n)] == want
        factored = rows.rows_for(range(n))
        assert [as_bytes(m) for m in factored] == want
        shared = [m for m in factored if isinstance(m, PrefixedMsg)]
        assert all(m.prefix is rows.prefix for m in shared)
        assert len(shared) == n - len(rows.exceptions)
    assert vector.prefix == scalar.prefix
    assert vector.suffixes == scalar.suffixes
    assert vector.exceptions == scalar.exceptions
    assert all(type(s) is bytes for s in vector.suffixes if s is not None)
    assert all(type(r) is bytes for r in vector.exceptions.values())


# ------------------------------------------------------------ the encoder


def test_timestamp_rows_equal_timestamp_bytes_on_10000_random_stamps():
    rng = random.Random(2929)
    seconds = [rng.choice((0, 1, -1, SECOND, rng.randrange(-2**63, 2**63),
                           rng.randrange(2**40)))
               for _ in range(10_000)]
    nanos = [rng.choice((0, rng.choice(NANOS_EDGES),
                         rng.randrange(1_000_000_000)))
             for _ in range(10_000)]
    cells, keep, lens = pb.timestamp_rows(np.array(seconds, dtype=np.int64),
                                          np.array(nanos, dtype=np.int64))
    assert cells.dtype == np.uint8 and keep.dtype == bool
    for i, (s, n) in enumerate(zip(seconds, nanos)):
        want = pb.timestamp_bytes(s, n)
        assert cells[i][keep[i]].tobytes() == want, (s, n)
        assert lens[i] == len(want)


def test_timestamp_rows_of_no_stamp():
    cells, keep, lens = pb.timestamp_rows(np.array([], dtype=np.int64),
                                          np.array([], dtype=np.int64))
    assert cells.shape[0] == keep.shape[0] == len(lens) == 0


# ------------------------------------------------------- sizes and stamps


@pytest.mark.parametrize("n", [1, MIN - 1, MIN, 150, 10_240])
def test_every_row_equals_vote_sign_bytes_at_size(n):
    commit = _commit(_grid(n))
    _hold_to_per_index(commit)
    # the path is chosen from the row count alone
    commit._sign_rows = {}
    _rows, path = commit._build_sign_rows(CHAIN)
    assert path == ("vector" if n >= MIN else "scalar")


@pytest.mark.parametrize("nanos", NANOS_EDGES)
def test_nanos_at_a_varint_edge(nanos):
    # among the grid's lengths (an exception row or a modal one), and alone
    stamps = _grid(3 * MIN)
    for i in (0, MIN, 3 * MIN - 1):
        stamps[i] = (SECOND, nanos)
    _hold_to_per_index(_commit(stamps))
    _hold_to_per_index(_commit([(SECOND, nanos)] * (MIN + 3)))


@pytest.mark.parametrize("seconds", SECONDS_EDGES)
def test_seconds_at_an_edge(seconds):
    """A second over int64 sends the whole commit to the scalar builder,
    which masks it to 64 bits as Vote.sign_bytes does."""
    path = "scalar" if seconds >= 2**63 else "vector"
    stamps = _grid(2 * MIN)
    stamps[0] = stamps[MIN] = (seconds, 5_000_000)
    stamps[-1] = (seconds, 0)
    _hold_to_per_index(_commit(stamps), path=path)
    _hold_to_per_index(_commit([(seconds, 7)] * (MIN + 1)), path=path)


@pytest.mark.parametrize("flag", [BlockIDFlag.NIL, BlockIDFlag.ABSENT])
@pytest.mark.parametrize("where", ["first", "middle", "last"])
def test_a_row_not_for_the_block(flag, where):
    n = 2 * MIN + 1
    i = {"first": 0, "middle": MIN, "last": n - 1}[where]
    commit = _commit(_grid(n), {i: flag})
    _hold_to_per_index(commit)
    rows, _ = _parts(commit, "vector")
    assert i in rows.exceptions and rows.suffixes[i] is None


def test_all_rows_absent():
    n = MIN + 2
    commit = _commit([(0, 0)] * n,
                     {i: BlockIDFlag.ABSENT for i in range(n)})
    _hold_to_per_index(commit)
    rows, _ = _parts(commit, "vector")
    assert sorted(rows.exceptions) == list(range(n))
    assert rows.shared_fraction() == 0.0


def test_an_empty_commit():
    commit = _commit([])
    for builder in ("scalar", "vector"):
        rows, _ = _parts(commit, builder)
        assert len(rows) == 0 and rows.exceptions == {}
    assert _parts(commit, "vector")[0].prefix == \
        _parts(commit, "scalar")[0].prefix
    assert len(commit.vote_sign_bytes_all(CHAIN)) == 0


@pytest.mark.parametrize("chain_id", ["c", "a-chain-id-of-forty-four-bytes-to-be-exact!"])
def test_chain_ids_of_two_lengths(chain_id):
    _hold_to_per_index(_commit(_grid(150)), chain_id)


def test_the_modal_length_may_be_the_shorter_one():
    # three stamps in four under 2^28 ns: the longer encoding is the
    # exception, and a tie goes to the length met first, as Counter's does
    n = 4 * MIN
    stamps = [(SECOND, (200 if i % 4 else 900) * 1_000_000 + i)
              for i in range(n)]
    commit = _commit(stamps)
    _hold_to_per_index(commit)
    rows, _ = _parts(commit, "vector")
    assert sorted(rows.exceptions) == list(range(0, n, 4))
    for first in ((SECOND, 900_000_000), (SECOND, 200_000_000)):
        tied = [first if i % 2 == 0 else
                (SECOND, 1_100_000_000 - first[1]) for i in range(n)]
        commit = _commit(tied)
        _hold_to_per_index(commit)
        rows, _ = _parts(commit, "vector")
        assert sorted(rows.exceptions) == list(range(1, n, 2))


@pytest.mark.parametrize("n", [150, 10_240])
def test_the_split_equals_the_scalar_builders_on_the_benchmarks_stamps(n):
    """A millisecond grid inside one second: the same rows share the
    prefix, so the same lanes are factored and the same fall back."""
    commit = _commit(_grid(n, seed=681593078))
    scalar, _ = _parts(commit, "scalar")
    vector, _ = _parts(commit, "vector")
    assert sorted(vector.exceptions) == sorted(scalar.exceptions)
    assert vector.shared_fraction() == scalar.shared_fraction()
    # stamps under 2^28 ns are a byte shorter: about 27% of the lanes
    assert 0.70 < vector.shared_fraction() < 0.75


# ------------------------------------------------- the span and the counter


def test_span_attributes_and_counters_either_side_of_the_crossover():
    small, large = _commit(_grid(MIN - 1)), _commit(_grid(MIN))
    trace.configure(enabled=True, capacity=256, slow_ms=-1.0)
    small.vote_sign_bytes_all(CHAIN)
    large.vote_sign_bytes_all(CHAIN)
    large.vote_sign_bytes_all(CHAIN)  # the memo: counts on neither path
    spans = [r for r in trace.snapshot() if r["name"] == "commit.sign_bytes"]
    assert [r["attrs"] for r in spans] == [
        {"cached": False, "rows": MIN - 1, "path": "scalar"},
        {"cached": False, "rows": MIN, "path": "vector"},
        {"cached": True}]
    assert all(r["cat"] == "signbytes" for r in spans)
    want = {"vector": MIN, "scalar": MIN - 1}
    assert trace.attribution()["sign_rows"] == want
    assert trace.attribution_of(trace.snapshot())["sign_rows"] == want
    # a stamp past int64 is counted where it was built
    _commit([(2**63, 1)] * (MIN + 4)).vote_sign_bytes_all(CHAIN)
    assert trace.attribution()["sign_rows"] == {
        "vector": MIN, "scalar": 2 * MIN + 3}
    trace.reset_attribution()
    assert trace.attribution()["sign_rows"] == {"vector": 0, "scalar": 0}


def test_the_health_snapshot_carries_both_counters():
    from cometbft_tpu.ops import dispatch

    trace.configure(enabled=True, capacity=64, slow_ms=-1.0)
    _commit(_grid(150)).vote_sign_bytes_all(CHAIN)
    assert dispatch.health_snapshot()["attribution"]["sign_rows"] == {
        "vector": 150, "scalar": 0}


# ------------------------------------------------------- the benchmark's


def _obs(vector, scalar) -> dict:
    counters = {"attribution.rows": 102_400,
                "attribution.stage_us.signbytes": 220_000.0}
    if vector is not None:
        counters["attribution.sign_rows.vector"] = vector
        counters["attribution.sign_rows.scalar"] = scalar
    return {"counters": counters}


@pytest.mark.parametrize("vector,scalar,want", [
    (102_400, 0, 100.0), (1_500, 500, 75.0), (0, 64, 0.0),
    (0, 0, None),        # the tracer was off, or every call hit the memo
    (None, None, None),  # a parent: its tracer has no such counter
])
def test_the_reader_of_sign_rows_vector_pct(vector, scalar, want):
    reading = readers.read_metric(METRICS_DIR, METRIC, _obs(vector, scalar))
    if want is None:
        assert reading is None
    else:
        assert reading == {"value": pytest.approx(want), "unit": "%"}


def test_benchmark_json_lists_the_metric_for_both_cells():
    """The entry is there under its name, with its reader's unit, and its
    `workloads` hold the two cells it was written for; later cells may be
    appended behind them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == METRIC]
    cells = entry.pop("workloads")
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "node path",
        "moves": "commit_verify_ms"}
    assert cells[:2] == ["hub-150.commit", "committee-10k-mixed.commit"]
    assert set(cells) <= {w["name"] for w in bench["workloads"]}