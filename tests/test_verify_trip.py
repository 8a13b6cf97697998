"""The trip of one ed25519 batch to the device and back (ISSUE 27): one
un-awaited upload, two compiled programs (derive, verify + integrity) and
one blocking wait (the 8-byte header fetch); the host-challenge branch is
one program and one wait; a failing lane adds the payload pull and nothing
else. The spies sit on the program callables, on jax.block_until_ready, on
the fetch and on JAX's eager dispatch (where the four plane slices of the
old trip ran); `crypto_health`'s staging.trip has to agree with them.

One batch shape throughout (150 rows, 256 lanes, a commit's geometry):
every further shape is a further trace of the ladder.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519
from cometbft_tpu.libs.prefixrows import PrefixedMsg, as_bytes
from cometbft_tpu.ops import challenge, residency
from cometbft_tpu.ops import dispatch as D
from cometbft_tpu.ops import ed25519_kernel as K
from cometbft_tpu.ops import limbs as L

N = 150
BUCKET = 256
BAD_LANE = 97


@pytest.fixture(autouse=True)
def _fresh_planes():
    residency.reset()
    challenge.reset()
    challenge.configure(enabled=True)
    yield
    residency.reset()
    challenge.reset()
    challenge.configure(enabled=True)


@pytest.fixture(scope="module")
def keys():
    return [ed25519.gen_priv_key() for _ in range(N)]


def _signed(keys, prefixes: list[bytes]):
    """150 vote-shaped rows (a shared prefix, 8 varying bytes, a common
    trailer), lane i under prefixes[i % len(prefixes)]: one prefix a
    commit's rows, eight a catch-up window's."""
    pubs, msgs, sigs = [], [], []
    for i, key in enumerate(keys):
        msg = PrefixedMsg(prefixes[i % len(prefixes)],
                          b"%08d" % i + b"|trip-chain")
        pubs.append(key.pub_key().bytes_())
        msgs.append(msg)
        sigs.append(key.sign(as_bytes(msg)))
    return pubs, msgs, sigs


def _with_a_bad_lane(sigs: list) -> list:
    bad = list(sigs)
    bad[BAD_LANE] = bad[BAD_LANE][:32] + bad[BAD_LANE + 1][32:]
    return bad


def _height(k: int) -> bytes:
    """Another height's shared prefix, of the first one's length: the
    same derive geometry, a row the prefix table has not seen."""
    return b"trip-vote-prefix|" + b"%071d" % k


@pytest.fixture(scope="module")
def rows(keys):
    """One height's rows, and the same with one signature that does not
    verify."""
    pubs, msgs, sigs = _signed(keys, [b"trip-vote-prefix|" + b"h" * 71])
    return pubs, msgs, sigs, _with_a_bad_lane(sigs)


class _Spies:
    """What a trip calls, counted at the seams it goes through."""

    def __init__(self, monkeypatch):
        self.programs: list[str] = []
        self.eager: list[str] = []
        self.awaited = 0
        self.fetches = 0
        self.concatenates = 0

        def counting(fn, name):
            def call(*args, **kwargs):
                self.programs.append(name)
                return fn(*args, **kwargs)
            return call

        derive_fn = challenge.derive_fn
        monkeypatch.setattr(challenge, "derive_fn", lambda *a: counting(
            derive_fn(*a), "derive_challenge"))
        verify_programs = K._verify_programs
        monkeypatch.setattr(K, "_verify_programs", lambda *a: tuple(
            counting(fn, fn.__name__) for fn in verify_programs(*a)))
        for name in ("_gather_coords", "_integrity_parts",
                     "_device_checksum"):
            monkeypatch.setattr(K, name, counting(getattr(K, name), name))

        block_until_ready = jax.block_until_ready

        def awaited(x):
            self.awaited += 1
            return block_until_ready(x)

        monkeypatch.setattr(jax, "block_until_ready", awaited)
        to_host = K._to_host

        def fetch(arr):
            self.fetches += 1
            return to_host(arr)

        monkeypatch.setattr(K, "_to_host", fetch)
        concatenate = jnp.concatenate

        def concat(*args, **kwargs):
            self.concatenates += 1
            return concatenate(*args, **kwargs)

        monkeypatch.setattr(jnp, "concatenate", concat)
        # an op on a device array outside any jit (x[a:b], .reshape, .at)
        # is a compiled program of its own, dispatched from here
        from jax._src import dispatch as jax_dispatch

        apply_primitive = jax_dispatch.apply_primitive

        def eager(prim, *args, **params):
            self.eager.append(str(prim))
            return apply_primitive(prim, *args, **params)

        monkeypatch.setattr(jax_dispatch, "apply_primitive", eager)


def _resolve(how: str, pubs, msgs, sigs) -> np.ndarray:
    thunk = K.verify_batch_async(pubs, msgs, sigs)
    if how == "thunk":
        return thunk()
    return K.resolve_batches([thunk])[0]


def _health_trip() -> dict:
    return D.health_snapshot()["staging"]["trip"]


@pytest.mark.parametrize("how", ["thunk", "resolve_batches"])
def test_happy_batch_is_two_programs_and_one_wait(rows, how, monkeypatch):
    pubs, msgs, sigs, _bad = rows
    assert K.verify_batch(pubs, msgs, sigs)[0]  # tables resident, warm
    residency.reset_send_stats()
    challenge.reset_stats()
    spies = _Spies(monkeypatch)

    mask = _resolve(how, pubs, msgs, sigs)

    assert mask.all() and mask.shape == (N,)
    assert spies.programs == ["derive_challenge", "verify_xla_derived"]
    assert spies.eager == []
    assert spies.awaited == 0
    assert spies.fetches == 1
    assert spies.concatenates == 0
    trip = {"batches": 1, "device_programs": 2, "blocking_waits": 1}
    assert residency.trip_stats() == trip
    assert _health_trip() == trip
    assert challenge.stats().get("lanes_device") == N
    # the same bytes as ever: the wire block and the 2 B/lane index
    sends = residency.send_stats()["indexed"]
    assert sends["sends"] == 1 and sends["sigs"] == N
    assert sends["bytes"] + challenge.CARRY_BYTES == (
        4 * challenge.block_words(BUCKET, 8) + 2 * BUCKET)


@pytest.mark.parametrize("how", ["thunk", "resolve_batches"])
def test_host_challenge_batch_is_one_program_and_one_wait(
        rows, how, monkeypatch):
    """The branch a flush under MIN_LANES or with the plane off takes."""
    pubs, msgs, sigs, _bad = rows
    challenge.configure(enabled=False)
    assert K.verify_batch(pubs, msgs, sigs)[0]
    residency.reset_send_stats()
    spies = _Spies(monkeypatch)

    mask = _resolve(how, pubs, msgs, sigs)

    assert mask.all()
    assert spies.programs == ["verify_xla_hostk"]
    assert spies.eager == []
    assert (spies.awaited, spies.fetches, spies.concatenates) == (0, 1, 0)
    trip = {"batches": 1, "device_programs": 1, "blocking_waits": 1}
    assert residency.trip_stats() == trip == _health_trip()
    sends = residency.send_stats()["indexed"]
    assert sends["bytes"] == 96 * BUCKET + 2 * BUCKET


@pytest.mark.parametrize("how", ["thunk", "resolve_batches"])
def test_failing_lane_same_programs_two_waits_right_lane(
        rows, how, monkeypatch):
    pubs, msgs, _sigs, bad = rows
    assert not K.verify_batch(pubs, msgs, bad)[0]
    residency.reset_send_stats()
    spies = _Spies(monkeypatch)

    mask = _resolve(how, pubs, msgs, bad)

    assert np.flatnonzero(~mask).tolist() == [BAD_LANE]
    assert spies.programs == ["derive_challenge", "verify_xla_derived"]
    assert spies.eager == []
    assert (spies.awaited, spies.fetches, spies.concatenates) == (0, 2, 0)
    trip = {"batches": 1, "device_programs": 2, "blocking_waits": 2}
    assert residency.trip_stats() == trip == _health_trip()


def _prefix_table(put_key: str = "default") -> dict:
    return D.health_snapshot()["staging"]["challenge"]["tables"][put_key]


@pytest.mark.parametrize("how", ["thunk", "resolve_batches"])
@pytest.mark.parametrize("new_rows", [1, 8])
def test_prefix_miss_rides_the_upload_two_programs_and_one_wait(
        keys, rows, how, new_rows, monkeypatch):
    """A new height (a commit: one new prefix; a catch-up window: eight)
    misses the prefix table, and its rows ride the derive call: no
    program and no wait of the table's own (ISSUE 34)."""
    pubs, msgs, sigs, _bad = rows
    assert K.verify_batch(pubs, msgs, sigs)[0]  # tables resident, warm
    assert _prefix_table()["syncs"] == 1  # the table's first use
    pubs, msgs, sigs = _signed(
        keys, [_height(100 + k) for k in range(new_rows)])
    residency.reset_send_stats()
    challenge.reset_stats()
    spies = _Spies(monkeypatch)

    mask = _resolve(how, pubs, msgs, sigs)

    assert mask.all() and mask.shape == (N,)
    assert spies.programs == ["derive_challenge", "verify_xla_derived"]
    assert spies.eager == []
    assert (spies.awaited, spies.fetches, spies.concatenates) == (0, 1, 0)
    trip = {"batches": 1, "device_programs": 2, "blocking_waits": 1}
    assert residency.trip_stats() == trip == _health_trip()
    counters = D.health_snapshot()["staging"]["challenge"]["counters"]
    assert counters["table_rows_carried"] == new_rows
    assert "table_rows_awaited" not in counters
    assert counters["lanes_device"] == N
    # the batch resolved intact: the table took the derive's output
    table = _prefix_table()
    assert (table["syncs"], table["adoptions"], table["dirty"]) == (1, 1, 0)
    assert table["rows"] == 1 + new_rows
    # on the wire: the block and the index as ever; the table's rows are
    # the block's tail, counted as table maintenance
    sends = residency.send_stats()
    assert sends["indexed"]["sends"] == 1 and sends["indexed"]["sigs"] == N
    assert sends["indexed"]["bytes"] + challenge.CARRY_BYTES == (
        4 * challenge.block_words(BUCKET, 8) + 2 * BUCKET)
    assert sends["delta"] == {"sends": 1, "sigs": 0,
                              "bytes": challenge.CARRY_BYTES}
    assert challenge.CARRY_BYTES == 8 * 160 + 8 * 4
    # and the next batch of the same height is a pure hit
    assert _resolve(how, pubs, msgs, sigs).all()
    assert _prefix_table()["adoptions"] == 1
    assert challenge.stats()["table_rows_carried"] == new_rows


def test_nine_new_prefixes_overflow_to_the_awaited_sync(
        keys, rows, monkeypatch):
    """More new rows than one derive call carries: the awaited scatter
    takes them all (a third program, a second wait), counted apart, and
    the verdicts are the oracle's."""
    from cometbft_tpu.crypto.ed25519_math import verify_zip215

    pubs, msgs, sigs, _bad = rows
    assert K.verify_batch(pubs, msgs, sigs)[0]
    pubs, msgs, sigs = _signed(keys, [_height(300 + k) for k in range(9)])
    sigs = _with_a_bad_lane(sigs)
    want = [verify_zip215(p, as_bytes(m), s)
            for p, m, s in zip(pubs, msgs, sigs)]
    assert want.count(False) == 1
    residency.reset_send_stats()
    challenge.reset_stats()
    spies = _Spies(monkeypatch)

    mask = _resolve("thunk", pubs, msgs, sigs)

    assert mask.tolist() == want
    assert spies.programs == ["derive_challenge", "verify_xla_derived"]
    assert spies.eager == [] and spies.fetches == 2  # header, payload
    assert residency.trip_stats() == {  # + the scatter and its wait
        "batches": 1, "device_programs": 3, "blocking_waits": 3}
    counters = challenge.stats()
    assert counters["table_rows_awaited"] == 9
    assert "table_rows_carried" not in counters
    table = _prefix_table()
    assert (table["syncs"], table["adoptions"], table["dirty"]) == (2, 0, 0)


@pytest.mark.parametrize("how", ["thunk", "resolve_batches"])
def test_corrupted_carried_row_takes_the_integrity_ladder(
        keys, rows, how, monkeypatch):
    """A carried row that arrives other than it left (a bit flipped
    between the host's checksum and the device: the seam of
    test_in_program_integrity_equals_the_expression, on the live path):
    the checksum the derive takes covers it, so the header refuses, the
    payload is pulled, the batch retries and the mask is the oracle's.
    The table does not adopt the refused snapshot, the row stays dirty
    and the next batch carries it again."""
    from cometbft_tpu.libs import metrics

    pubs, msgs, sigs, _bad = rows
    assert K.verify_batch(pubs, msgs, sigs)[0]
    pubs, msgs, sigs = _signed(keys, [_height(200)])
    derive_fn = challenge.derive_fn
    flips = [True]

    def flipping(*geometry):
        run = derive_fn(*geometry)

        def call(flat, *args):
            if flips and flips.pop():
                flat = flat.copy()  # the block's tail: indices, then rows
                flat[-challenge.CARRY_WORDS + challenge.CARRY_ROWS + 3] ^= (
                    np.uint32(1 << 5))  # a byte of the new row's prefix
            return run(flat, *args)
        return call

    monkeypatch.setattr(challenge, "derive_fn", flipping)
    challenge.reset_stats()
    mismatches = metrics.crypto_metrics().transfer_checksum_mismatch
    before = mismatches.total()

    mask = _resolve(how, pubs, msgs, sigs)

    assert mask.all() and not flips  # every signature is good
    assert mismatches.total() - before == 1
    table = _prefix_table()
    assert (table["adoptions"], table["dirty"]) == (0, 1)
    # the first try and the integrity retry both carried the row
    assert challenge.stats()["table_rows_carried"] == 2
    assert "table_rows_awaited" not in challenge.stats()
    residency.reset_send_stats()

    assert _resolve(how, pubs, msgs, sigs).all()

    assert residency.trip_stats() == {
        "batches": 1, "device_programs": 2, "blocking_waits": 1}
    assert challenge.stats()["table_rows_carried"] == 3
    table = _prefix_table()
    assert (table["adoptions"], table["dirty"]) == (1, 0)


def test_failed_derive_leaves_the_rows_dirty_and_host_k_answers(keys, rows):
    from cometbft_tpu.libs import chaos

    pubs, msgs, sigs, _bad = rows
    assert K.verify_batch(pubs, msgs, sigs)[0]
    pubs, msgs, sigs = _signed(keys, [_height(400)])
    challenge.reset_stats()
    chaos.arm(challenge.SITE, "permanent", 1)
    try:
        mask = _resolve("thunk", pubs, msgs, sigs)
    finally:
        chaos.reset()
        D.reset_supervision()  # the challenge breaker opened

    assert mask.all()
    counters = challenge.stats()
    assert counters["derive_failed"] == counters["batch_host_fallback"] == 1
    assert "table_rows_carried" not in counters
    table = _prefix_table()
    assert (table["adoptions"], table["dirty"]) == (0, 1)

    assert _resolve("thunk", pubs, msgs, sigs).all()

    assert challenge.stats()["table_rows_carried"] == 1
    table = _prefix_table()
    assert (table["adoptions"], table["dirty"]) == (1, 0)


def test_a_mesh_targets_prefix_table_stays_on_its_chip(keys, rows):
    """A mesh shard's trip is this trip aimed at a chip (Target): that
    chip's replica of the prefix table takes the carried rows, on that
    chip, and the process-wide table is not touched."""
    chip = jax.devices("cpu")[2]  # tests/conftest.py forces 8 host devices
    target = K.Target(device=chip, index=2, put_key="dev2")

    def ladder(ax, ay, az, at, rw, sw, kw):  # the mesh tests' seam
        mask = jnp.ones(rw.shape[1], dtype=bool)
        return mask, mask.all()

    def verify(pubs, msgs, sigs):
        thunk = K.verify_batch_async(pubs, msgs, sigs, target=target,
                                     ladder=ladder)
        mask = thunk()
        assert thunk.placed() == {str(chip)}
        return mask

    pubs, msgs, sigs, _bad = rows
    assert verify(pubs, msgs, sigs).all()  # first use: the awaited sync
    assert _prefix_table("dev2")["syncs"] == 1
    challenge.reset_stats()
    assert verify(*_signed(keys, [_height(500)])).all()
    assert challenge.stats()["table_rows_carried"] == 1
    table = _prefix_table("dev2")
    assert (table["syncs"], table["adoptions"], table["dirty"]) == (1, 1, 0)
    assert table["devices"] == [str(chip)]  # the adopted snapshot's place
    assert list(challenge.table_stats()) == ["dev2"]
    # a readmitted chip starts its replica anew (parallel/mesh.py)
    challenge.invalidate("dev2")
    assert verify(pubs, msgs, sigs).all()
    table = _prefix_table("dev2")
    assert (table["syncs"], table["adoptions"], table["rows"]) == (1, 0, 1)


def test_derive_failure_falls_to_one_host_k_program(rows, monkeypatch):
    """The whole-batch host-k rung behind the derive: the one program of
    the host-challenge branch, with R, s, the host's k and the index."""
    from cometbft_tpu.libs import chaos

    pubs, msgs, sigs, _bad = rows

    def with_a_failing_derive() -> np.ndarray:
        chaos.arm(challenge.SITE, "permanent", 1)
        try:
            return _resolve("thunk", pubs, msgs, sigs)
        finally:
            chaos.reset()
            D.reset_supervision()  # the challenge breaker opened

    assert with_a_failing_derive().all()  # tables resident, rung traced
    residency.reset_send_stats()
    challenge.reset_stats()
    spies = _Spies(monkeypatch)

    mask = with_a_failing_derive()

    assert mask.all()
    assert spies.programs == ["verify_xla_hostk"]
    assert (spies.awaited, spies.fetches) == (0, 1)
    assert challenge.stats().get("batch_host_fallback") == 1
    sends = residency.send_stats()["indexed"]
    assert sends["sends"] == 1 and sends["sigs"] == N
    assert sends["bytes"] == 96 * BUCKET + 2 * BUCKET  # no stream


@pytest.mark.parametrize("device_challenge", [True, False])
def test_staged_block_stays_leased_until_the_batch_resolves(
        rows, device_challenge, monkeypatch):
    """The upload is not awaited, so the host block must not be back in
    the pool (where the next batch would overwrite it) before the header
    has been read: until then a transfer may still be reading it."""
    pubs, msgs, sigs, _bad = rows
    challenge.configure(enabled=device_challenge)
    assert K.verify_batch(pubs, msgs, sigs)[0]
    leased = []
    for name in ("lease", "lease_flat"):
        lease = getattr(L.POOL, name)
        monkeypatch.setattr(L.POOL, name, lambda n, lease=lease: (
            leased.append(lease(n)) or leased[-1]))

    def pooled(block) -> bool:
        with L.POOL._lock:
            return any(b is block for b in L.POOL._free.get(block.shape, []))

    thunk = K.verify_batch_async(pubs, msgs, sigs)
    assert len(leased) == 1
    block = leased[0]
    staged = block.copy()
    header_dev, _payload_dev = thunk.device_parts()[0]()  # dispatched
    assert not pooled(block)
    np.asarray(header_dev)  # both programs have run
    assert not pooled(block)
    assert np.array_equal(block, staged)
    assert thunk().all()
    assert pooled(block)


# ------------------------------------------- integrity, traced in-program


@pytest.fixture(scope="module")
def staged(rows):
    """A failing-lane batch staged by hand as the dispatch closure stages
    it, and the s words that make the failing lane good again (its R, and
    so its k, are the good signature's)."""
    pubs, msgs, sigs, bad = rows
    residency.reset()
    challenge.reset()
    pre_ok, safe_pubs, sig_rows, _pub_rows = K._structural_stage(pubs, bad)
    plan = challenge.plan_batch(msgs, pre_ok)
    assert plan is not None and plan.n_fallback == 0
    flat = np.empty(challenge.block_words(BUCKET, plan.var), np.uint32)
    K._pack_device_block(sig_rows, BUCKET, plan, flat)
    ok_a, idx, planes, enc, _path = K._stage_index(
        K._default_cache, safe_pubs, BUCKET)
    assert ok_a.all()
    run = challenge.derive_fn(BUCKET, plan.var, plan.plen, plan.tlen, 0)
    table = (idx, *planes, enc, plan.dev_tab)
    residency.reset()
    challenge.reset()
    good_s = np.frombuffer(sigs[BAD_LANE][32:], np.uint32)
    return flat, run, table, good_s, plan.var


@pytest.mark.parametrize("case", ["good", "flipped_word", "failing_lane"])
@pytest.mark.parametrize("hostk", [False, True])
def test_in_program_integrity_equals_the_expression(staged, case, hostk):
    """Header and payload as the batch's programs make them against
    _integrity_parts_arrs_expr on the lanes' verdicts and the words that
    arrived: a block that arrived whole, one that did not (a bit of a
    padding lane, which moves no verdict), one with a failing lane."""
    flat, run, table, good_s, var = staged
    flat = flat.copy()
    pad_lane = BUCKET - 3
    verdicts = np.ones(BUCKET, dtype=bool)
    if case == "failing_lane":
        verdicts[BAD_LANE] = False
    else:
        flat[8 * BUCKET:16 * BUCKET].reshape(8, BUCKET)[:, BAD_LANE] = good_s
    if hostk:
        words = np.empty((3, 8, BUCKET), np.uint32)
        words[:2] = flat[:16 * BUCKET].reshape(2, 8, BUCKET)
        words[2] = np.asarray(run(flat, *table)[2])
        expected = np.uint32(K._host_checksum(words))
        if case == "flipped_word":  # k of a lane whose A is the identity
            words[2, 0, pad_lane] ^= np.uint32(1 << 9)
        header, payload = K._verify_programs(True)[1](
            *table[:5], words, expected)
        arrived = words
    else:
        expected = np.uint32(K._host_checksum(flat))
        if case == "flipped_word":  # suffix bytes of a lane not derived
            flat[16 * BUCKET + (2 * BUCKET + pad_lane * var) // 4] ^= (
                np.uint32(1 << 9))
        rw, sw, kw, chk, *a_dev, _ntab = run(flat, *table)
        header, payload = K._verify_programs(False)[1](
            *a_dev, rw, sw, kw, chk, expected)
        arrived = flat
    want_header, want_payload = K._integrity_parts_arrs_expr(
        jnp.asarray(verdicts), jnp.asarray(verdicts.all()),
        jnp.asarray(expected), jnp.asarray(arrived))
    assert np.array_equal(np.asarray(header), np.asarray(want_header))
    assert np.array_equal(np.asarray(payload), np.asarray(want_payload))
    verdict = {"good": "happy", "flipped_word": "chk_mismatch",
               "failing_lane": "full"}[case]
    assert K.decode_header(np.asarray(header), expected) == verdict
    assert bool(np.asarray(payload)[2 * BUCKET]) == (case != "flipped_word")


def test_resolve_batches_joins_only_when_there_is_something_to_join(
        rows, monkeypatch):
    """Two batches: one concatenate for the two headers (a program), one
    fetch; the one unhappy payload is pulled as it is."""
    pubs, msgs, sigs, bad = rows
    assert K.verify_batch(pubs, msgs, sigs)[0]
    assert not K.verify_batch(pubs, msgs, bad)[0]
    residency.reset_send_stats()
    spies = _Spies(monkeypatch)

    good_mask, bad_mask = K.resolve_batches([
        K.verify_batch_async(pubs, msgs, sigs),
        K.verify_batch_async(pubs, msgs, bad)])

    assert good_mask.all()
    assert np.flatnonzero(~bad_mask).tolist() == [BAD_LANE]
    assert spies.concatenates == 1 and spies.fetches == 2
    assert spies.eager == []
    assert residency.trip_stats() == {
        "batches": 2, "device_programs": 5, "blocking_waits": 2}


# ------------------------------------- the benchmark's reading of the trip


def test_benchmark_reads_programs_per_batch_and_nothing_on_a_parent(rows):
    """benchmarks/metrics/device_programs_per_batch.json over the
    flattened crypto_health snapshot, differenced as a run differences
    it: 2.0 for happy batches; None (and no error) on a program whose
    snapshot has no staging.trip, as the parent's has not."""
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from benchmarks import program, readers

    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        entry = next(m for m in json.load(fh)["per_layer"]
                     if m["name"] == "device_programs_per_batch.commit")
    cells = entry.pop("workloads")  # the two it was written for first
    assert cells[:2] == ["hub-150.commit", "committee-10k-mixed.commit"]
    assert entry == {
        "name": "device_programs_per_batch.commit",
        "unit": "programs/batch", "better": "lower",
        "source": "program_counter", "layer": "residency and wire",
        "moves": "commit_verify_ms"}
    metrics_dir = os.path.join(root, "benchmarks", "metrics")

    def flat() -> dict:
        out: dict = {}
        program._flatten(D.health_snapshot(), "", out)
        return out

    pubs, msgs, sigs, _bad = rows
    assert K.verify_batch(pubs, msgs, sigs)[0]
    before = flat()
    for _ in range(3):
        assert K.verify_batch(pubs, msgs, sigs)[0]
    counters = program.Counters.diff(before, flat())
    reading = readers.read_metric(metrics_dir, entry["name"],
                                  {"counters": counters})
    assert reading == {"value": 2.0, "unit": "programs/batch"}
    parents = {k: v for k, v in counters.items()
               if not k.startswith("staging.trip.")}
    assert readers.read_metric(metrics_dir, entry["name"],
                               {"counters": parents}) is None


def test_benchmark_reads_the_share_of_prefix_rows_carried(keys, rows):
    """benchmarks/metrics/prefix_rows_carried_pct.json over the flattened
    crypto_health snapshot, differenced as a run differences it: 100 for a
    window whose new heights all rode their derive calls, a share where
    the awaited sync() took some (an overflow), nothing (and no error) on
    a program without the counters, as the parent is, and in a window
    with no new row."""
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from benchmarks import program, readers

    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        per_layer = json.load(fh)["per_layer"]
    for family, moves, cell in (
            ("commit", "commit_verify_ms", "hub-150.commit"),
            ("catchup", "catchup_blocks_per_s", "hub-150.catchup")):
        entry = dict(next(m for m in per_layer if m["name"]
                          == f"prefix_rows_carried_pct.{family}"))
        assert entry.pop("workloads")[0] == cell  # written for it first
        assert entry == {
            "name": f"prefix_rows_carried_pct.{family}", "unit": "%",
            "better": "higher", "source": "program_counter",
            "layer": "residency and wire", "moves": moves}
    metrics_dir = os.path.join(root, "benchmarks", "metrics")
    name = "prefix_rows_carried_pct.commit"

    def flat() -> dict:
        out: dict = {}
        program._flatten(D.health_snapshot(), "", out)
        return out

    def read(counters):
        return readers.read_metric(metrics_dir, name, {"counters": counters})

    pubs, msgs, sigs, _bad = rows
    assert K.verify_batch(pubs, msgs, sigs)[0]  # first use: awaited
    before = flat()
    assert K.verify_batch(pubs, msgs, sigs)[0]  # a hit: no new row
    assert read(program.Counters.diff(before, flat())) is None
    for k in range(3):  # three new heights, a call each
        assert K.verify_batch(*_signed(keys, [_height(600 + k)]))[0]
    counters = program.Counters.diff(before, flat())
    assert read(counters) == {"value": 100.0, "unit": "%"}
    # an overflow: nine new rows at once take the awaited scatter
    assert K.verify_batch(
        *_signed(keys, [_height(700 + k) for k in range(9)]))[0]
    counters = program.Counters.diff(before, flat())
    assert read(counters) == {"value": 100.0 * 3 / (3 + 9), "unit": "%"}
    parents = {k: v for k, v in counters.items()
               if ".table_rows_" not in k}
    assert parents and read(parents) is None
