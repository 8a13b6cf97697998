"""Device-challenge equality tests: the lane-pair device SHA-512 and the
device Barrett reduction (ops/challenge.py) must be bit-for-bit identical
to the hashvec host twins — RFC 8032 challenge inputs, every padded
block-count group, ragged/boundary lengths, and a randomized 10k-row
sweep — plus the prefix/tail table contract (content keying, LRU + plan
protection, checksummed sync, snapshot immutability) and the planner's
degradation ladder. Tier-1-safe: JAX_PLATFORMS=cpu runs everything on
the forced-host platform; on real hardware the same programs ride the
TPU/XLA rungs unchanged."""

import hashlib

import numpy as np
import pytest

from cometbft_tpu.libs.prefixrows import PrefixedMsg
from cometbft_tpu.ops import challenge, hashvec
from cometbft_tpu.ops import limbs as _limbs

_RFC8032 = [
    (  # TEST 1: empty message
        "d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a",
        "",
        "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e0652249015"
        "55fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b",
    ),
    (  # TEST 2: one byte
        "3d4017c3e843895a92b70aa74d1b7ebc9c982ccf2ec4968cc0cd55f12af4660c",
        "72",
        "92a009a9f0d4cab8720e820b5f642540a2b27b5416503f8fb3762223ebdb69d"
        "a085ac1e43e15996e458f3613d0f11d8c387b2eaeb4302aeeb00d291612bb0c00",
    ),
    (  # TEST 3: two bytes
        "fc51cd8e6218a1a38da47ed00230f0580816ed13ba3303ac5deb911548908025",
        "af82",
        "6291d657deec24024827e69c3abe01a30ce548a284743a445e3680d7db5ac3a"
        "c18ff9b538d16f290ae67f760984dc6594a7c15e9716ed28dc027beceea1ec40a",
    ),
]


def _rows(datas: list[bytes]) -> np.ndarray:
    ln = len(datas[0])
    return np.frombuffer(b"".join(datas), dtype=np.uint8).reshape(
        len(datas), ln) if ln else np.zeros((len(datas), 0), dtype=np.uint8)


def test_rfc8032_challenge_inputs_device():
    ell = hashvec.L_ED25519
    for pub, m, sig in _RFC8032:
        d = bytes.fromhex(sig)[:32] + bytes.fromhex(pub) + bytes.fromhex(m)
        datas = [d] * 9  # one padded-block group per vector
        got = challenge.sha512_rows_device(_rows(datas))
        want = hashlib.sha512(d).digest()
        for i in range(9):
            assert got[i].tobytes() == want
        words = challenge.reduce512_mod_l_device(got)
        k = int.from_bytes(want, "little") % ell
        for i in range(9):
            assert words[i].tobytes() == k.to_bytes(32, "little")


def test_sha512_device_block_boundaries():
    """Padding edges: every padded-block-count group (1/2/3 blocks) and
    the lengths straddling the 1->2 and 2->3 boundaries."""
    for ln in (0, 1, 63, 111, 112, 113, 127, 128, 129, 239, 240, 241):
        rows = np.arange(16 * max(ln, 1), dtype=np.uint64).astype(
            np.uint8).reshape(16, -1)[:, :ln]
        rows = np.ascontiguousarray(rows)
        got = challenge.sha512_rows_device(rows)
        host = hashvec.sha512_rows(rows)
        assert got.tobytes() == host.tobytes(), ln
        for i in range(16):
            assert got[i].tobytes() == \
                hashlib.sha512(rows[i].tobytes()).digest(), ln


def test_reduce512_mod_l_device_edges():
    ell = hashvec.L_ED25519
    edge_vals = [0, 1, ell - 1, ell, ell + 1, 2 * ell, 3 * ell - 1,
                 (1 << 252), (1 << 512) - 1, (ell << 256) + ell - 1]
    rng = np.random.default_rng(0xBA44E77)
    vals = edge_vals + [int.from_bytes(rng.bytes(64), "little")
                        for _ in range(64)]
    digests = np.frombuffer(
        b"".join(v.to_bytes(64, "little") for v in vals),
        dtype=np.uint8).reshape(len(vals), 64)
    words = challenge.reduce512_mod_l_device(digests)
    host = hashvec.reduce512_mod_l(digests)
    assert words.tobytes() == host.tobytes()
    for i, v in enumerate(vals):
        assert words[i].tobytes() == (v % ell).to_bytes(32, "little"), i


def test_sha512_device_randomized_sweep():
    """10k-row bit-for-bit sweep against the host ladder, one compile
    per block group (uniform row length per group — the commit shape)."""
    rng = np.random.default_rng(0xD5A512)
    total = 0
    for ln in (96, 122, 180, 230):
        n = 2500
        rows = rng.integers(0, 256, size=(n, ln), dtype=np.uint8)
        got = challenge.sha512_rows_device(rows)
        host = hashvec.sha512_rows(rows)
        assert got.tobytes() == host.tobytes(), ln
        kd = challenge.reduce512_mod_l_device(got)
        kh = hashvec.reduce512_mod_l(host)
        assert kd.tobytes() == kh.tobytes(), ln
        total += n
    assert total == 10000


# ------------------------------------------------------- prefix/tail table


def test_prefix_table_content_keying_and_eviction():
    tab = challenge.PrefixTable("t0")
    r0 = tab.ensure(b"prefix-a", b"tail")
    assert tab.ensure(b"prefix-a", b"tail") == r0  # content hit
    r1 = tab.ensure(b"prefix-b", b"tail")
    assert r1 != r0
    assert tab.ensure(b"x" * (challenge.PREFIX_CAP + 1), b"") is None
    st = tab.stats()
    assert st["inserts"] == 2 and st["hits"] == 1 and st["rows"] == 2


def test_prefix_table_lru_eviction_respects_plan_protection():
    tab = challenge.PrefixTable("t1")
    rows = {}
    for i in range(challenge.TABLE_ROWS):
        rows[i] = tab.ensure(b"p%06d" % i, b"")
    assert tab.stats()["rows"] == challenge.TABLE_ROWS
    # protecting every row starves eviction: the new content must miss
    assert tab.ensure(b"fresh", b"", protect=set(rows.values())) is None
    # unprotected: the LRU row (the oldest insert) is evicted
    r = tab.ensure(b"fresh", b"", protect={rows[i] for i in range(1, 8)})
    assert r == rows[0]
    assert tab.stats()["evictions"] == 1


def test_prefix_table_sync_snapshot_is_immutable():
    tab = challenge.PrefixTable("t2")
    tab.ensure(b"alpha", b"T")
    snap1 = tab.sync()
    assert snap1 is not None
    got = np.asarray(snap1)[0, :6].tobytes()
    assert got == b"alphaT"
    # a later insert + sync must not mutate the captured snapshot
    tab.ensure(b"beta-longer", b"T")
    snap2 = tab.sync()
    assert np.asarray(snap1)[1].sum() == 0
    assert np.asarray(snap2)[1, :12].tobytes() == b"beta-longerT"


# ---------------------------------------------------------------- planning


def _vote_batch(n: int, var_ts: bool = True):
    """A vote-flush-shaped batch: one shared prefix object, per-lane
    timestamp-ish variable bytes, a common chain-id tail."""
    prefix = b"\x08\x02\x11" + b"H" * 100  # ~103 B shared sign-bytes head
    tail = b"\x32\x09chain-xyz"
    msgs = []
    for i in range(n):
        ts = b"\x2a\x0c" + i.to_bytes(6, "big") + b"\x00\x00\x00\x00"
        msgs.append(PrefixedMsg(prefix, ts + tail))
    return msgs


def test_plan_batch_vote_shape_and_fill_stream():
    challenge.reset()
    msgs = _vote_batch(32)
    pre_ok = np.ones(32, dtype=bool)
    plan = challenge.plan_batch(msgs, pre_ok, put_key="plantest")
    assert plan is not None
    assert plan.n_eligible == 32 and plan.n_fallback == 0
    # the common chain-id trailer factored into the table row, off the wire
    assert plan.tlen >= len(b"\x32\x09chain-xyz")
    assert plan.var <= challenge.MAX_VAR
    assert plan.plen == 103
    bucket = 32
    block = np.zeros(challenge.block_words(bucket, plan.var),
                     dtype=np.uint32)
    challenge.fill_stream(block, bucket, plan)
    sb = block[16 * bucket:].view(np.uint8)
    desc = sb[:2 * bucket].view("<u2")
    assert all(int(d) & 0x8000 for d in desc[:32])
    vb = sb[2 * bucket:2 * bucket + bucket * plan.var].reshape(
        bucket, plan.var)
    for i in range(32):
        suffix = msgs[i].suffix
        assert vb[i].tobytes() == suffix[:plan.var]


def test_plan_batch_degradation_reasons():
    challenge.reset()
    msgs = _vote_batch(16)
    ok = np.ones(16, dtype=bool)
    challenge.configure(enabled=False)
    try:
        assert challenge.plan_batch(msgs, ok) is None
    finally:
        challenge.configure(enabled=True)
    # too-small batches stay on the classic path
    assert challenge.plan_batch(msgs[:2], ok[:2]) is None
    # fully-divergent suffixes blow MAX_VAR: no plan
    rng = np.random.default_rng(3)
    ragged = [PrefixedMsg(b"P" * 40, rng.bytes(60)) for _ in range(16)]
    assert challenge.plan_batch(ragged, ok) is None
    # oversize messages: no plan
    big = [PrefixedMsg(b"P" * 300, b"s" * 8) for _ in range(16)]
    assert challenge.plan_batch(big, ok) is None
    st = challenge.stats()
    assert st.get("plan_disabled") and st.get("plan_small")
    assert st.get("plan_oversize_var") and st.get("plan_oversize")


def test_plan_batch_breaker_open_degrades():
    from cometbft_tpu.ops import dispatch

    dispatch.reset_supervision()
    challenge.reset()
    try:
        sup = dispatch.supervisor(challenge.SITE)
        sup.breaker.record_failure(dispatch.PERMANENT)
        assert not sup.breaker.peek()
        assert challenge.plan_batch(
            _vote_batch(16), np.ones(16, dtype=bool)) is None
        assert challenge.stats().get("plan_breaker_open")
    finally:
        dispatch.reset_supervision()


def test_plan_batch_mixed_lanes_fall_back_per_lane():
    challenge.reset()
    msgs = _vote_batch(24)
    msgs[5] = PrefixedMsg(b"other-prefix!", b"odd-suffix-here")  # nonconform
    msgs[9] = b"a plain bytes message....."
    pre_ok = np.ones(24, dtype=bool)
    pre_ok[11] = False  # structurally bad lane: neither device nor fallback
    plan = challenge.plan_batch(msgs, pre_ok, put_key="mixed")
    assert plan is not None
    assert not plan.eligible[5] and not plan.eligible[9]
    assert not plan.eligible[11]
    assert plan.n_eligible == 21
    assert plan.n_fallback == 2  # lanes 5 and 9 (live but nonconforming)


# --------------------------------------------- the derive program end-to-end


def test_derive_fn_matches_host_challenges():
    """The full device pipeline — descriptor decode, table gather,
    message assembly, SHA-512, Barrett — against host challenge words,
    with per-lane fallback scatter and padding lanes zeroed."""
    challenge.reset()
    import jax.numpy as jnp

    n, bucket = 24, 32
    rng = np.random.default_rng(0xDE51)
    msgs = _vote_batch(n)
    msgs[7] = PrefixedMsg(b"weird", b"nonconforming-suffix-length")
    pre_ok = np.ones(n, dtype=bool)
    plan = challenge.plan_batch(msgs, pre_ok, put_key="derive")
    assert plan is not None and plan.n_fallback == 1
    sigs = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)  # R encodings
    pubs = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
    block = np.zeros(challenge.block_words(bucket, plan.var),
                     dtype=np.uint32)
    rw = _limbs.bytes_to_words(sigs)  # (n, 8)
    block[:8 * bucket].reshape(8, bucket)[:, :n] = rw.T
    challenge.fill_stream(block, bucket, plan)
    aw = np.zeros((8, bucket), dtype=np.uint32)
    aw[:, :n] = _limbs.bytes_to_words(pubs).T
    # host fallback words for the nonconforming lane, padded to 2
    fb_lanes = np.flatnonzero(pre_ok & ~plan.eligible)
    fkw_rows = hashvec.sha512_mod_l_words(
        [sigs[i].tobytes() + pubs[i].tobytes() + bytes(msgs[i])
         for i in fb_lanes])
    fb = 2
    fidx = np.full(fb, fb_lanes[-1], dtype=np.int32)
    fidx[:len(fb_lanes)] = fb_lanes
    fkw = np.tile(fkw_rows[-1:].T, (1, fb)).astype(np.uint32)
    fkw[:, :len(fb_lanes)] = fkw_rows.T
    run = challenge.derive_fn(bucket, plan.var, plan.plen, plan.tlen, fb)
    # a bucket-row key table read in REVERSE lane order: lane i's key is
    # row bucket-1-i, so the gather is in the program under test
    idx = np.arange(bucket - 1, -1, -1, dtype=np.uint16)
    coords = tuple(jnp.asarray(rng.integers(
        0, 1 << 13, size=(20, bucket), dtype=np.int32)) for _ in range(4))
    rw_dev, sw_dev, kw, chk, *a_dev, _ntab = run(
        block, idx, *coords, jnp.asarray(aw[:, ::-1]), plan.dev_tab,
        fkw, fidx)
    planes = block[:16 * bucket].reshape(2, 8, bucket)
    assert np.array_equal(np.asarray(rw_dev), planes[0])
    assert np.array_equal(np.asarray(sw_dev), planes[1])
    for got, table in zip(a_dev, coords):
        assert np.array_equal(np.asarray(got), np.asarray(table)[:, idx])
    from cometbft_tpu.ops import ed25519_kernel as EK

    assert int(chk) == EK._host_checksum(block, fkw, fidx)
    kw = np.asarray(kw)  # (8, bucket)
    want = hashvec.sha512_mod_l_words(
        [sigs[i].tobytes() + pubs[i].tobytes() + bytes(msgs[i])
         for i in range(n)])
    for i in range(n):
        assert kw[:, i].tobytes() == want[i].tobytes(), i
    for i in range(n, bucket):  # padding lanes stay zero (happy header)
        assert not kw[:, i].any(), i


# ------------------------------------- the table's rows ride the derive call


def _derive_by_hand(plan, msgs, n, bucket, seed):
    """Run the plan's derive program as the dispatch closure does (no
    fallback lanes) -> (kw (8, bucket), chk, ntab, host's k words (n, 8),
    the host's checksum of all the call uploaded)."""
    import jax.numpy as jnp

    from cometbft_tpu.ops import ed25519_kernel as EK

    rng = np.random.default_rng(seed)
    sigs = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
    pubs = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
    block = np.zeros(challenge.block_words(bucket, plan.var),
                     dtype=np.uint32)
    block[:8 * bucket].reshape(8, bucket)[:, :n] = (
        _limbs.bytes_to_words(sigs).T)
    challenge.fill_stream(block, bucket, plan)
    aw = np.zeros((8, bucket), dtype=np.uint32)
    aw[:, :n] = _limbs.bytes_to_words(pubs).T
    coords = (jnp.zeros((20, bucket), jnp.int32),) * 4
    run = challenge.derive_fn(bucket, plan.var, plan.plen, plan.tlen, 0)
    _rw, _sw, kw, chk, *_a, ntab = run(
        block, np.arange(bucket, dtype=np.uint16), *coords, jnp.asarray(aw),
        plan.dev_tab)
    want = hashvec.sha512_mod_l_words(
        [sigs[i].tobytes() + pubs[i].tobytes() + bytes(msgs[i])
         for i in range(n)])
    assert np.array_equal(block[-challenge.CARRY_WORDS:], plan.carry)
    return np.asarray(kw), int(chk), ntab, want, EK._host_checksum(block)


def _height_batch(n: int, height: int):
    """_vote_batch under another shared prefix of the same length: a new
    height's votes, the same derive geometry."""
    return [PrefixedMsg(b"\x08\x02\x11" + b"%0100d" % height, m.suffix)
            for m in _vote_batch(n)]


@pytest.mark.parametrize("case", ["miss", "hit", "evicted_since"])
def test_derive_with_carried_rows_matches_host_challenges(case):
    """A new height's row is not on the device when the plan is made: it
    rides the derive call, which sets it over the snapshot, derives from
    the result and hands the new table back (`miss`). A pure hit carries
    padding, a clean row's own bytes, and returns the table it got, and
    the challenges today's program returns (`hit`). The plan's copies are
    its own: rows evicted and re-used by later plans do not change what
    the batch in flight reads (`evicted_since`)."""
    challenge.reset()
    n = bucket = 32
    ok = np.ones(n, dtype=bool)
    first = challenge.plan_batch(_vote_batch(n), ok, put_key="carry")
    assert first is not None and first.n_carried == 0  # first use: sync()
    assert challenge.stats()["table_rows_awaited"] == 1
    tab = challenge.table("carry")
    msgs = _vote_batch(n) if case == "hit" else _height_batch(n, 7)
    plan = challenge.plan_batch(msgs, ok, put_key="carry")
    assert plan is not None and plan.dev_tab is first.dev_tab
    assert plan.carry.shape == (challenge.CARRY_WORDS,)
    assert plan.carry.dtype == np.uint32
    assert plan.carry.nbytes == challenge.CARRY_BYTES == 8 * (4 + 160)
    assert challenge.block_words(bucket, plan.var) == (
        16 * bucket + challenge.stream_words(bucket, plan.var)
        + challenge.CARRY_WORDS)
    assert challenge.stats()["table_rows_awaited"] == 1  # no second sync
    didx = set(plan.carry[:challenge.CARRY_ROWS])
    if case == "hit":
        assert plan.n_carried == 0 and tab.stats()["dirty"] == 0
        assert didx == {0}  # padding: the row the lanes read
    else:
        assert plan.n_carried == 1 and tab.stats()["dirty"] == 1
        assert didx == {1}  # one real row, repeated
        assert not np.asarray(plan.dev_tab)[1].any()  # not on the device
    if case == "evicted_since":
        for i in range(challenge.TABLE_ROWS):  # every row re-used
            tab.ensure(b"later-%06d" % i, b"")
        assert tab.stats()["evictions"] >= 2
        assert not tab._host[1].tobytes().startswith(b"\x08\x02\x11")
    kw, chk, ntab, want, host_chk = _derive_by_hand(plan, msgs, n, bucket, 34)
    assert chk == host_chk  # the carried rows are under the checksum
    for i in range(n):
        assert kw[:, i].tobytes() == want[i].tobytes(), i
    # the row: the front, its byte 0 less the suffix width (what every
    # stamp width of a height shares), then the common tail
    prefix, suffix = msgs[0].prefix, msgs[0].suffix
    body = (bytes([(prefix[0] - len(suffix)) & 0xFF]) + prefix[1:]
            + suffix[plan.var:])
    row = int(plan.pids[0])
    assert np.asarray(ntab)[row, :len(body)].tobytes() == body
    if case == "hit":
        assert np.array_equal(np.asarray(ntab), np.asarray(plan.dev_tab))
        assert not plan.adopt(ntab)  # nothing to confirm
    # the confirmed snapshot itself is never written (not donated)
    assert np.array_equal(np.asarray(first.dev_tab)[0],
                          np.asarray(plan.dev_tab)[0])


def test_table_adopts_only_what_a_batch_confirmed():
    """adopt(): the derive's output becomes the confirmed snapshot and
    the carried rows clean; a row re-used since stays dirty; an output
    over a snapshot that is no longer the confirmed one is not taken; more
    dirty rows than a call carries go through the awaited sync()."""
    import jax.numpy as jnp

    challenge.reset()
    tab = challenge.PrefixTable("adopt")
    rows = challenge.CARRY_ROWS
    r0 = tab.ensure(b"h0", b"T")
    base, words, gens = tab.carry(r0)  # first use: awaited
    assert gens == {} and tab.stats()["syncs"] == 1
    r1 = tab.ensure(b"h1", b"T")
    base1, words, gens = tab.carry(r1)
    assert base1 is base and list(gens) == [r1]
    assert set(words[:rows]) == {r1}
    assert words[rows:].view(np.uint8)[:3].tobytes() == b"h1T"
    # a second plan before the first resolved carries the row again
    r2 = tab.ensure(b"h2", b"T")
    _b, words2, gens2 = tab.carry(r2)
    assert sorted(gens2) == [r1, r2] and words2[:2].tolist() == [r1, r2]
    out1 = jnp.asarray(tab._host.copy())  # what the first derive returns
    assert tab.adopt(base, out1, gens)
    assert tab.stats()["dirty"] == 1 and tab.stats()["adoptions"] == 1
    # the second plan's output was made over the OLD snapshot: not taken,
    # its row stays dirty and is carried by the next plan
    assert not tab.adopt(base, out1, gens2)
    base3, _w, gens3 = tab.carry(r2)
    assert base3 is out1 and list(gens3) == [r2]
    # r2 re-used (evicted, inserted anew) while its batch is in flight:
    # adopting that batch's output must not clean the new content
    tab._rows.pop((b"h2", b"T"))
    tab._lru.pop((b"h2", b"T"))
    tab._rows[(b"h2'", b"T")] = r2
    tab._lru[(b"h2'", b"T")] = None
    tab.version += 1
    tab._dirty[r2] = tab.version
    assert tab.adopt(base3, jnp.asarray(tab._host.copy()), gens3)
    assert tab.stats()["dirty"] == 1
    # overflow: nine dirty rows take the awaited scatter, all at once
    for i in range(challenge.CARRY_ROWS):
        tab.ensure(b"more-%d" % i, b"T")
    assert tab.stats()["dirty"] == challenge.CARRY_ROWS + 1
    before = challenge.stats().get("table_rows_awaited", 0)
    _b, words, gens = tab.carry(r0)
    assert gens == {} and set(words[:rows]) == {r0}
    assert tab.stats()["dirty"] == 0 and tab.stats()["syncs"] == 2
    assert challenge.stats()["table_rows_awaited"] - before == (
        challenge.CARRY_ROWS + 1)


def test_table_stays_true_under_concurrent_plans_and_adoptions():
    """Plans (ensure, carry) and resolutions (adopt, or none: a batch that
    failed) from more threads than cores, rows evicted and re-used all the
    while, overflows taking the awaited sync(): whatever the order, a row
    that is not dirty reads on the confirmed snapshot what the host mirror
    holds (a lost or wrongly cleaned row would derive from stale bytes)."""
    import sys
    import threading

    challenge.reset()
    tab = challenge.PrefixTable("stress")
    rows_n, cap = challenge.CARRY_ROWS, challenge.PREFIX_CAP
    errors: list = []

    def clean_rows_are_true() -> int:
        with tab._lock:
            confirmed = np.asarray(tab._tab)
            clean = [r for r in range(challenge.TABLE_ROWS)
                     if r not in tab._dirty]
            assert np.array_equal(confirmed[clean], tab._host[clean])
            return len(clean)

    def worker(seed: int) -> None:
        rng = np.random.default_rng(seed)
        try:
            for _ in range(100):
                pid = tab.ensure(b"height-%04d" % rng.integers(400), b"T")
                snapshot, words, gens = tab.carry(pid)
                new = np.asarray(snapshot).copy()  # what the derive does
                new[words[:rows_n]] = words[rows_n:].view(np.uint8).reshape(
                    rows_n, cap)
                if rng.random() < 0.7:  # else: the batch did not resolve
                    tab.adopt(snapshot, new, gens)
                clean_rows_are_true()
        except Exception as exc:  # noqa: BLE001 - the test reports it
            errors.append(exc)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert errors == []
    assert clean_rows_are_true() >= challenge.TABLE_ROWS - rows_n - 16
    assert tab.counters["adoptions"] > 0 and tab.counters["evictions"] > 0


# ------------------------------------------ every stamp width on the chip


def _commit_rows(n: int, nanos, chain_id: str = "cosmoshub-4",
                 height: int = 1_000_000, nil=(), lane_loop: bool = False):
    """A commit's sign-rows as the verify path gets them (an index-vector
    take of Commit.vote_sign_bytes_all: columns from 36 rows on, the
    lane loop's exception bytes below, or with `lane_loop` at any size),
    lane i stamped nanos[i] inside the height's second; the lanes in
    `nil` voted NIL."""
    from cometbft_tpu.libs.prefixrows import MsgBlock
    from cometbft_tpu.types.basic import BlockID, BlockIDFlag, PartSetHeader
    from cometbft_tpu.types.commit import Commit, CommitSig
    from cometbft_tpu.utils import cmttime

    rng = np.random.default_rng(height)
    bid = BlockID(hash=rng.bytes(32),
                  part_set_header=PartSetHeader(total=1, hash=rng.bytes(32)))
    sigs = [CommitSig(
        BlockIDFlag.NIL if i in nil else BlockIDFlag.COMMIT, bytes(20),
        cmttime.Timestamp(1_700_000_000 + 6 * height, int(ns)), bytes(64))
        for i, ns in enumerate(nanos)]
    commit = Commit(height=height, round_=0, block_id=bid, signatures=sigs)
    rows = commit.vote_sign_bytes_all(chain_id)
    if lane_loop:
        return MsgBlock.from_list(rows.rows_for(range(n)))
    return rows.take(np.arange(n))


def _ms_spread(n: int, seed: int = 7) -> list[int]:
    """The benchmark's stamps (benchmarks/datagen.make_ring): n
    milliseconds spread evenly over the second, in a seeded order."""
    spread = [j * 1000 // n * 1_000_000 for j in range(n)]
    np.random.default_rng(seed).shuffle(spread)
    return spread


# nanos 0 (the field omitted) and every width of its varint, 1 to 5 bytes
EVERY_WIDTH = [0, 7, 300, 1_000_000, 2_000_000, 100_000_000, 268_435_455,
               268_435_456, 999_000_000]


def _nanos_widths(nanos) -> set[int]:
    return {0 if ns == 0 else (int(ns).bit_length() + 6) // 7 for ns in nanos}


def _derive_against_host(plan, msgs, n: int, bucket: int, seed: int):
    """Run the plan's derive program on a hand-staged block -> (its k
    words (bucket, 8), the host's _challenge_words (n, 8))."""
    import jax.numpy as jnp

    from cometbft_tpu.ops import ed25519_kernel as EK

    rng = np.random.default_rng(seed)
    sigs = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
    pubs = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
    block = np.zeros(challenge.block_words(bucket, plan.var),
                     dtype=np.uint32)
    block[:8 * bucket].reshape(8, bucket)[:, :n] = (
        _limbs.bytes_to_words(sigs).T)
    challenge.fill_stream(block, bucket, plan)
    aw = np.zeros((8, bucket), dtype=np.uint32)
    aw[:, :n] = _limbs.bytes_to_words(pubs).T
    coords = (jnp.zeros((20, bucket), jnp.int32),) * 4
    run = challenge.derive_fn(bucket, plan.var, plan.plen, plan.tlen, 0)
    kw = run(block, np.arange(bucket, dtype=np.uint16), *coords,
             jnp.asarray(aw), plan.dev_tab)[2]
    want = EK._challenge_words(sigs, pubs, msgs, msgs.lengths(),
                               np.ones(n, dtype=bool))
    return np.asarray(kw).T, want


def _chain_across_a_block() -> str:
    """A chain id whose vote rows' widest and narrowest stamps pad to
    different SHA-512 block counts (64 + message + 17 bytes either side
    of 256)."""
    for k in range(1, 120):
        lens = _commit_rows(48, EVERY_WIDTH * 6, "c" * k).lengths()
        if ((64 + int(lens.max()) + 17 + 127) // 128
                != (64 + int(lens.min()) + 17 + 127) // 128):
            return "c" * k
    raise AssertionError("no chain id crosses a block")


@pytest.mark.parametrize("blocks", ["one_block_count", "across_a_block"])
def test_derive_is_bit_identical_for_every_stamp_width(blocks):
    """A full commit whose stamps take every width of the timestamp
    (nanos omitted, its varint 1 to 5 bytes, and the benchmark's ms
    0-999), 48 rows in a 64-lane bucket: every live lane derives on the
    chip, and k is _challenge_words' bit for bit, padding lanes zero;
    where the widths straddle a SHA-512 block, each lane reads the state
    after its own last block."""
    challenge.reset()
    n, bucket = 48, 64
    nanos = EVERY_WIDTH + _ms_spread(n - len(EVERY_WIDTH))
    assert _nanos_widths(nanos) == {0, 1, 2, 3, 4, 5}
    chain = "cosmoshub-4" if blocks == "one_block_count" else (
        _chain_across_a_block())
    msgs = _commit_rows(n, nanos, chain)
    assert len(set(msgs.lengths().tolist())) == 6
    plan = challenge.plan_batch(msgs, np.ones(n, dtype=bool),
                                put_key="widths-" + blocks)
    assert plan is not None
    assert (plan.n_eligible, plan.n_fallback) == (n, 0)
    assert plan.n_joined == int((plan.short > 0).sum()) > 0
    assert int(plan.short.max()) == 6  # nanos omitted: 6 bytes short
    assert challenge.stats()["lanes_width_joined"] == plan.n_joined
    kw, want = _derive_against_host(plan, msgs, n, bucket, 0x51A)
    assert kw[:n].tobytes() == want.tobytes()
    assert not kw[n:].any()


@pytest.mark.parametrize("n", [24, 150, 2048])
def test_a_commits_plan_derives_every_lane_from_one_row(n):
    """A benchmark-shaped commit (ms spread over the second: ~27% of
    its stamps shorter than the modal one) plans every lane onto the
    chip, from ONE prefix-table row for the height, whichever builder
    made its rows (24: the lane loop's exception bytes; 150 and 2,048:
    columns)."""
    challenge.reset()
    msgs = _commit_rows(n, _ms_spread(n))
    plan = challenge.plan_batch(msgs, np.ones(n, dtype=bool), put_key="one")
    assert plan is not None
    assert (plan.n_eligible, plan.n_fallback) == (n, 0)
    assert len(set(plan.pids.tolist())) == 1
    assert challenge.table("one").stats()["rows"] == 1
    modal = np.bincount(msgs.lengths()).max()
    assert plan.n_joined == n - modal
    if n >= 150:
        assert 0.2 < plan.n_joined / n < 0.35
    counters = challenge.stats()
    assert counters["lanes_device"] == n
    assert counters["lanes_host_fallback"] == 0
    assert counters["lanes_width_joined"] == plan.n_joined


def test_a_catchup_window_carries_one_row_a_height():
    """Eight heights of 150 votes in one batch (blocksync's window):
    eight new rows, at most CARRY_ROWS, ride the derive call; the table's
    awaited scatter runs only at its first use."""
    challenge.reset()
    from cometbft_tpu.libs.prefixrows import MsgBlock

    first = _commit_rows(150, _ms_spread(150), height=999)
    assert challenge.plan_batch(first, np.ones(150, bool),
                                put_key="window") is not None
    assert challenge.stats()["table_rows_awaited"] == 1  # first use
    window = MsgBlock.concat([_commit_rows(150, _ms_spread(150, h), height=h)
                              for h in range(1000, 1008)])
    n = len(window)
    plan = challenge.plan_batch(window, np.ones(n, bool), put_key="window")
    assert plan is not None
    assert (plan.n_eligible, plan.n_fallback) == (n, 0)
    assert plan.n_joined > 0
    assert plan.n_carried == 8 <= challenge.CARRY_ROWS
    assert len(set(plan.pids.tolist())) == 8
    assert challenge.stats()["table_rows_awaited"] == 1  # no second sync
    assert challenge.table("window").stats()["syncs"] == 1


def test_two_width_mixes_build_one_derive_program():
    """Batches whose stamps hold different widths in different
    shares plan the same geometry: the derive family does not grow with
    the widths a batch holds (the `derive_programs` counter), and both
    derive k bit for bit."""
    challenge.reset()
    n, bucket = 40, 64
    chain = "width-mix-chain"
    every = EVERY_WIDTH + _ms_spread(n - len(EVERY_WIDTH))
    # another mix: mostly 5-byte varints, a few ms-0 and 4-byte stamps
    other = [(300 + 17 * i) * 1_000_000 for i in range(n - 6)]
    other += [0, 0, 0, 50_000_000, 100_000_000, 200_000_000]
    assert _nanos_widths(other) == {0, 4, 5} != _nanos_widths(every)
    geometries = set()
    for i, nanos in enumerate((every, other)):
        msgs = _commit_rows(n, nanos, chain, height=500 + i)
        plan = challenge.plan_batch(msgs, np.ones(n, bool), put_key="mix")
        assert plan is not None and plan.n_fallback == 0
        geometries.add((plan.var, plan.plen, plan.tlen))
        kw, want = _derive_against_host(plan, msgs, n, bucket, i)
        assert kw[:n].tobytes() == want.tobytes()
    assert len(geometries) == 1
    assert challenge.stats()["derive_programs"] == 1


@pytest.mark.parametrize("rows", ["columns", "lane_loop"])
def test_nil_and_foreign_lanes_still_fall_back_per_lane(rows):
    """A NIL vote's shorter head and a foreign message stay with the
    per-lane host rung, whether the commit's rows come as columns or as
    the lane loop's PrefixedMsg and exception bytes; every other width
    joins the chip."""
    from cometbft_tpu.libs.prefixrows import MsgBlock

    challenge.reset()
    n = 48
    nanos = EVERY_WIDTH + _ms_spread(n - len(EVERY_WIDTH))
    commit = _commit_rows(n, nanos, nil=(3, 11),
                          lane_loop=rows == "lane_loop")
    if rows == "lane_loop":  # off-length and NIL rows as whole bytes
        assert sum(not f for f in commit.fronts) > 1
    foreign = MsgBlock.from_list([b"\x70" + b"a foreign message" * 6])
    msgs = MsgBlock.concat([commit, foreign])
    plan = challenge.plan_batch(msgs, np.ones(n + 1, bool), put_key="nil")
    assert plan is not None
    assert plan.n_fallback == 3
    assert np.flatnonzero(~plan.eligible).tolist() == [3, 11, n]
    assert plan.n_joined > 0
    assert len(set(plan.pids[plan.eligible].tolist())) == 1
