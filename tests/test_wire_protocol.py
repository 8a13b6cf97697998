"""Reduced-send wire protocol (ISSUE 10): device-resident validator
sets, indexed sends, epoch delta updates, shared vote prefixes, and the
send-side accounting plane.

Correctness contract under test: the indexed and full-key send paths
produce BIT-IDENTICAL verify verdicts (including bad-lane masks) across
validator-set churn, and every degradation (capacity overflow, set-hash
mismatch, poisoned delta) falls back to the full-key path — never to a
wrong verdict. Churn shape mirrors the bench light-client harness
(50% replacement per epoch, "churn every 12500" scaled down).
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519
from cometbft_tpu.libs.prefixrows import PrefixedMsg, SharedPrefixRows, as_bytes
from cometbft_tpu.ops import ed25519_kernel as K
from cometbft_tpu.ops import residency


@pytest.fixture(autouse=True)
def _fresh_residency():
    """Small tables, clean counters per test; restore defaults after."""
    residency.reset()
    residency.configure(enabled=True, rows=256)
    yield
    residency.reset()
    residency.configure(enabled=True, rows=16384)


def _sign_n(n, tag=b"wp", keys=None):
    keys = keys or [ed25519.gen_priv_key() for _ in range(n)]
    pubs, msgs, sigs = [], [], []
    for i in range(n):
        p = keys[i % len(keys)]
        m = tag + b"-%d" % i
        pubs.append(p.pub_key().bytes_())
        msgs.append(m)
        sigs.append(p.sign(m))
    return pubs, msgs, sigs


# ------------------------------------------------------------ bit identity


def test_indexed_vs_full_bit_identical_with_bad_lanes():
    """The reduced-send (indexed) path and the full-key path must agree
    bit-for-bit on every lane: valid rows, a corrupted signature, an
    undecodable pubkey, an s >= L scalar, and a ragged-length row."""
    pubs, msgs, sigs = _sign_n(24)
    sigs[3] = sigs[3][:32] + sigs[4][32:]          # wrong s for this R
    pubs[7] = b"\xff" * 32                          # undecodable pubkey
    sigs[9] = sigs[9][:32] + b"\xff" * 32           # s >= L
    sigs[11] = b"\x01" * 63                         # ragged length

    ok_i, mask_indexed = K.verify_batch(pubs, msgs, sigs)
    stats = residency.send_stats()
    assert stats["indexed"]["sigs"] == 24  # the batch rode the new path

    residency.configure(enabled=False)
    ok_f, mask_full = K.verify_batch(pubs, msgs, sigs)
    residency.configure(enabled=True)

    assert mask_indexed == mask_full
    assert [i for i, b in enumerate(mask_indexed) if not b] == [3, 7, 9, 11]
    assert ok_i == ok_f is False


def test_indexed_path_steady_state_bytes_per_sig():
    """Steady state (warm table), host-challenge wire format: one uint16
    index per lane + the staged r/s/k words. For a full 32-lane bucket
    that is 96 + 2 = 98 B/sig — and the delta path carries zero bytes
    once the set is resident."""
    from cometbft_tpu.ops import challenge

    challenge.configure(enabled=False)
    try:
        pubs, msgs, sigs = _sign_n(32)
        K.verify_batch(pubs, msgs, sigs)  # seeds the table (delta)
        residency.reset_send_stats()
        K.verify_batch(pubs, msgs, sigs)
        s = residency.send_stats()
        assert s["delta"]["sends"] == 0
        assert s["indexed"]["sigs"] == 32
        assert s["steady_state_bytes_per_sig"] == pytest.approx(98.0)
    finally:
        challenge.configure(enabled=True)


def test_device_challenge_steady_state_bytes_per_sig_bound():
    """Device-side challenge derivation (default): k words never cross
    the wire — each lane ships a 2-byte descriptor plus only the var
    suffix bytes not covered by the resident prefix table. For vote-shaped
    rows (shared prefix, short unique run, common chain-id trailer) the
    steady state must land at or under the 82 B/sig wire bound."""
    from cometbft_tpu.ops import challenge

    challenge.reset()
    challenge.reset_stats()
    keys = [ed25519.gen_priv_key() for _ in range(32)]
    prefix = b"dc-vote-prefix|" + b"h" * 73  # shared across the batch
    pubs, msgs, sigs = [], [], []
    for i, p in enumerate(keys):
        sfx = b"%08d" % i + b"|dc-chain"  # unique run + common trailer
        m = PrefixedMsg(prefix, sfx)
        pubs.append(p.pub_key().bytes_())
        msgs.append(m)
        sigs.append(p.sign(as_bytes(m)))

    ok, mask = K.verify_batch(pubs, msgs, sigs)  # seeds pubkey + prefix tables
    assert ok and all(mask)
    residency.reset_send_stats()
    ok, mask = K.verify_batch(pubs, msgs, sigs)
    assert ok and all(mask)

    st = challenge.stats()
    assert st["lanes_device"] >= 32  # the steady batch derived k on device
    s = residency.send_stats()
    assert s["indexed"]["sigs"] == 32
    assert s["steady_state_bytes_per_sig"] <= 82.0


def test_resolve_batches_rides_indexed_path():
    pubs, msgs, sigs = _sign_n(16)
    K.verify_batch(pubs, msgs, sigs)  # warm
    thunks = [K.verify_batch_async(pubs, msgs, sigs) for _ in range(3)]
    for mask in K.resolve_batches(thunks):
        assert mask.all()
    assert residency.send_stats()["indexed"]["sends"] >= 4


# ------------------------------------------------------------ epoch churn


def test_epoch_delta_update_ships_only_churned_rows():
    """The bench light-client churn shape (50% of the set replaced per
    epoch): registering the next epoch's set hash must delta-upload
    exactly the new keys — never the whole table."""
    pool = [ed25519.gen_priv_key() for _ in range(48)]
    epoch_a = pool[:32]
    epoch_b = pool[16:48]  # 16 carried over, 16 new
    keys_a = [p.pub_key().bytes_() for p in epoch_a]
    keys_b = [p.pub_key().bytes_() for p in epoch_b]

    residency.register_set("ed25519", b"epoch-a" + bytes(25), keys_a)
    pubs, msgs, sigs = _sign_n(32, keys=epoch_a)
    K.verify_batch(pubs, msgs, sigs)
    tbl = residency.stats()["tables"]["ed25519"]
    assert tbl["delta_rows"] == 32 and tbl["pinned_rows"] == 32

    residency.register_set("ed25519", b"epoch-b" + bytes(25), keys_b)
    pubs, msgs, sigs = _sign_n(32, keys=epoch_b)
    K.verify_batch(pubs, msgs, sigs)
    tbl = residency.stats()["tables"]["ed25519"]
    assert tbl["delta_rows"] == 48  # +16, not +32: the overlap stayed
    assert tbl["full_set_uploads"] == 0
    assert set(keys_b) <= set(
        residency._tables[("ed25519", "")]._rows)


def test_set_hash_mismatch_falls_back_to_full_upload():
    """The same epoch hash announcing DIFFERENT key content voids the
    pin and re-uploads the set in full — counted, and never a wrong
    verdict (rows are content-keyed throughout)."""
    keys_a = [ed25519.gen_priv_key() for _ in range(8)]
    keys_b = [ed25519.gen_priv_key() for _ in range(8)]
    h = b"same-hash" + bytes(23)
    residency.register_set("ed25519", h, [p.pub_key().bytes_() for p in keys_a])
    pubs, msgs, sigs = _sign_n(8, keys=keys_a)
    K.verify_batch(pubs, msgs, sigs)

    residency.register_set("ed25519", h, [p.pub_key().bytes_() for p in keys_b])
    pubs, msgs, sigs = _sign_n(8, keys=keys_b)
    sigs[2] = sigs[2][:32] + sigs[3][32:]
    ok, mask = K.verify_batch(pubs, msgs, sigs)
    tbl = residency.stats()["tables"]["ed25519"]
    assert tbl["hash_mismatches"] == 1
    assert tbl["full_set_uploads"] == 1
    assert [i for i, b in enumerate(mask) if not b] == [2]


def test_capacity_overflow_serves_from_full_key_path():
    """A batch whose unique keys exceed the table falls back to the
    full-key digest path — correct verdicts, counted under path=full."""
    residency.configure(rows=64)
    residency.reset()
    pubs, msgs, sigs = _sign_n(100)
    sigs[50] = sigs[50][:32] + sigs[51][32:]
    ok, mask = K.verify_batch(pubs, msgs, sigs)
    assert [i for i, b in enumerate(mask) if not b] == [50]
    s = residency.send_stats()
    assert s["indexed"]["sends"] == 0
    assert s["full"]["sigs"] == 100


@pytest.mark.parametrize("new_keys", [5, 128, 200])
def test_a_delta_of_any_size_runs_one_compiled_geometry(new_keys):
    """New keys reach the table in blocks of DELTA_ROWS columns through
    ONE program, whatever their number (a light client's drifting sets
    bring 8 to 500 a hop: no compile per key count), one awaited checksum
    for all the blocks; the rows gather as the host decompressed them."""
    delta = residency._delta_fn()
    # the program is process-global: a table of another size (the default
    # 16,384 rows, where another file's tests ran first) is another entry
    delta.clear_cache()
    residency.reset_send_stats()
    tbl = residency.table_for(K._default_cache)
    tbl.stage(_sign_n(3)[0], 8)            # the table's first delta
    compiled = delta._cache_size()
    trips = residency.trip_stats()
    pubs = _sign_n(new_keys)[0]
    ok_a, coords, _nbytes = tbl.stage(pubs, 256)
    blocks = -(-new_keys // residency.DELTA_ROWS)
    assert delta._cache_size() == compiled == 1
    now = residency.trip_stats()
    assert now["device_programs"] - trips["device_programs"] == blocks + 1
    assert now["blocking_waits"] - trips["blocking_waits"] == 2
    assert residency.send_stats()["delta"]["bytes"] == (
        (1 + blocks) * residency._DELTA_PLANES * residency.DELTA_ROWS * 4)
    assert ok_a.all() and tbl.stats()["delta_rows"] == 3 + new_keys
    _ok, want = K._default_cache.lookup_or_decompress(pubs)
    got = np.stack([np.asarray(c) for c in coords])[:, :, :new_keys]
    assert (got == want.transpose(1, 2, 0)).all()
    # padding lanes gather the identity row, untouched by any block
    pad = np.stack([np.asarray(c) for c in coords])[:, :, new_keys:]
    assert (pad[[0, 3]] == 0).all() and (pad[[1, 2], 0] == 1).all()


def test_poisoned_delta_upload_degrades_not_wrong(monkeypatch):
    """A delta upload whose device checksum fails twice must abandon the
    indexed path for that batch (full-key fallback), never cache the
    poisoned row."""
    delta = residency._delta_fn()

    def poisoned(*args):
        *table, acc = delta(*args)
        return (*table, acc + 1)

    monkeypatch.setattr(residency, "_delta_fn", lambda: poisoned)
    pubs, msgs, sigs = _sign_n(8)
    ok, mask = K.verify_batch(pubs, msgs, sigs)
    assert ok and all(mask)  # served correctly by the fallback ladder
    assert residency.send_stats()["indexed"]["sends"] == 0
    tbl = residency.stats()["tables"].get("ed25519")
    assert tbl is None or tbl["rows"] == 0  # nothing poisoned got cached


def test_mesh_readmission_reseeds_exactly_one_replica():
    """invalidate_device must drop the healed chip's replicas and leave
    its mesh-mates' resident sets untouched (per-chip fault domains)."""
    cache = K._default_cache
    pubs, _, _ = _sign_n(8)
    for put_key in ("dev0", "dev1"):
        tbl = residency.table_for(cache, put_key=put_key)
        tbl.stage(pubs, 8)
    assert set(k[1] for k in residency._tables) >= {"dev0", "dev1"}
    dropped = residency.invalidate_device(0)
    assert dropped == 1
    keys = set(k[1] for k in residency._tables)
    assert "dev0" not in keys and "dev1" in keys
    assert residency._tables[("ed25519", "dev1")].stats()["rows"] == 8


def test_crowded_table_protects_batch_keys_from_eviction():
    """Room-making eviction for a delta must never evict a row the
    current batch is about to index: when pinned rows crowd the table
    and the only evictable rows belong to this batch, the batch
    degrades cleanly to the full-key path (no KeyError, no error-path
    churn) and the resident rows stay resident."""
    residency.configure(rows=64)  # 63 usable rows
    residency.reset()
    pinned = [ed25519.gen_priv_key() for _ in range(40)]
    residency.register_set(
        "ed25519", b"crowd" + bytes(27),
        [p.pub_key().bytes_() for p in pinned])
    keys_a = [ed25519.gen_priv_key() for _ in range(10)]
    pubs, msgs, sigs = _sign_n(10, keys=keys_a)
    K.verify_batch(pubs, msgs, sigs)  # 40 pinned + 10 resident, 13 free
    tbl = residency._tables[("ed25519", "")]
    assert tbl.stats()["rows"] == 50
    # batch B: the 10 resident keys + 20 unseen -> needs 7 evictions,
    # but the only unpinned residents are batch B's own keys
    keys_b = keys_a + [ed25519.gen_priv_key() for _ in range(20)]
    pubs, msgs, sigs = _sign_n(30, keys=keys_b)
    sigs[15] = sigs[15][:32] + sigs[16][32:]
    ok, mask = K.verify_batch(pubs, msgs, sigs)
    assert [i for i, b in enumerate(mask) if not b] == [15]
    s = residency.send_stats()
    assert s["full"]["sigs"] == 30  # clean full-key degradation
    assert tbl.stats()["rows"] == 50  # nothing of batch A was evicted


def test_disabled_residency_never_engages():
    residency.configure(enabled=False)
    pubs, msgs, sigs = _sign_n(8)
    ok, mask = K.verify_batch(pubs, msgs, sigs)
    assert ok
    s = residency.send_stats()
    assert s["indexed"]["sends"] == 0 and s["full"]["sigs"] == 8


# -------------------------------------------------------- shared prefixes


def _commit_fixture(n=12):
    from cometbft_tpu.types.basic import (BlockID, PartSetHeader,
                                          SignedMsgType)
    from cometbft_tpu.types.validator import Validator, ValidatorSet
    from cometbft_tpu.types.vote import Vote
    from cometbft_tpu.types.vote_set import VoteSet
    from cometbft_tpu.utils import cmttime

    privs = [ed25519.gen_priv_key() for _ in range(n)]
    vs = ValidatorSet([Validator.new(p.pub_key(), 10) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    privs = [by_addr[v.address] for v in vs.validators]
    bid = BlockID(hash=b"\x01" * 32,
                  part_set_header=PartSetHeader(total=1, hash=b"\x02" * 32))
    vote_set = VoteSet("wp-chain", 9, 0, SignedMsgType.PRECOMMIT, vs)
    for i, p in enumerate(privs):
        v = Vote(type_=SignedMsgType.PRECOMMIT, height=9, round_=0,
                 block_id=bid, timestamp=cmttime.canonical_now_ms(),
                 validator_address=p.pub_key().address(), validator_index=i)
        v.signature = p.sign(v.sign_bytes("wp-chain"))
        vote_set.add_vote(v)
    return vs, privs, bid, vote_set.make_commit()


def test_vote_sign_rows_factored_form():
    """vote_sign_bytes_all returns a SharedPrefixRows whose factored
    rows (rows_for) share ONE prefix object per commit and materialize
    byte-identically — NIL votes become exception rows."""
    from cometbft_tpu.types.basic import BlockIDFlag

    _, _, _, commit = _commit_fixture(8)
    commit.signatures[5].block_id_flag = BlockIDFlag.NIL
    commit._sign_rows = None
    rows = commit.vote_sign_bytes_all("wp-chain")
    assert isinstance(rows, SharedPrefixRows)
    for i in range(8):
        assert rows[i] == commit.vote_sign_bytes("wp-chain", i), i
    factored = rows.rows_for(range(8))
    shared = [m for m in factored if isinstance(m, PrefixedMsg)]
    assert len(shared) >= 6  # NIL row (and any odd timestamp) excepted
    assert all(m.prefix is shared[0].prefix for m in shared)
    assert isinstance(factored[5], bytes)  # the NIL exception row
    for i, m in enumerate(factored):
        assert as_bytes(m) == rows[i]


def test_assemble_prefixed_rows_matches_join():
    from cometbft_tpu.ops import hashvec

    prefix = b"P" * 90
    msgs = [PrefixedMsg(prefix, b"s%02d" % i + b"T" * 29) for i in range(6)]
    msgs.insert(3, b"X" * 122)  # a materialized exception mid-run
    msgs.append(b"Y" * 122)
    got = hashvec.assemble_prefixed_rows(msgs, 122)
    want = np.frombuffer(b"".join(as_bytes(m) for m in msgs),
                         dtype=np.uint8).reshape(len(msgs), 122)
    assert np.array_equal(got, want)


def test_stage_batch_factored_rows_bit_identical():
    """Challenges (k words) computed from factored rows must equal the
    materialized-bytes computation bit for bit."""
    pubs, msgs, sigs = _sign_n(8, tag=b"Q" * 40)
    prefix = msgs[0][:32]
    factored = [PrefixedMsg(prefix, m[32:]) for m in msgs]
    b = K.bucket_size(8)
    pre1, sp1, r1, s1, k1 = K.stage_batch(pubs, msgs, sigs, b)
    pre2, sp2, r2, s2, k2 = K.stage_batch(pubs, factored, sigs, b)
    assert np.array_equal(k1, k2)
    assert np.array_equal(pre1, pre2)


def test_commit_verification_factored_through_scheduler():
    """The default path end to end: _commit_rows emits factored rows,
    the scheduler keeps them factored, staging reassembles, and a bad
    signature is still pinpointed by index."""
    from cometbft_tpu.types import validation

    vs, privs, bid, commit = _commit_fixture(12)
    validation.verify_commit("wp-chain", vs, bid, 9, commit)
    commit.signatures[4].signature = commit.signatures[5].signature
    commit._sign_rows = None
    with pytest.raises(validation.ErrInvalidCommitSignature, match=r"#4"):
        validation.verify_commit("wp-chain", vs, bid, 9, commit)


def test_announce_pins_validator_set():
    from cometbft_tpu.types import validation

    vs, privs, bid, commit = _commit_fixture(8)
    validation.verify_commit("wp-chain", vs, bid, 9, commit)
    sets = residency._announced.get("ed25519", {})
    assert vs.hash() in sets


# --------------------------------------------------------- planning/health


def test_scheduler_plans_from_measured_bytes_per_sig():
    from cometbft_tpu import sched

    residency.reset_send_stats()
    link = sched.get().health()["link"]
    assert "full_flush_wire_ms_at_measured_bytes_per_sig" in link
    assert "full_flush_wire_ms_at_96B_per_sig" not in link
    assert link["planning_bytes_per_sig"] == 96.0  # cold-start fallback
    residency.record_send("indexed", 980, sigs=10)
    assert sched.get().health()["link"]["planning_bytes_per_sig"] == 98.0


def test_crypto_health_staging_wire_section():
    from cometbft_tpu.ops import dispatch

    residency.record_send("indexed", 980, sigs=10)
    residency.record_send("delta", 500)
    snap = dispatch.health_snapshot()
    wire = snap["staging"]["wire"]
    assert wire["steady_state_bytes_per_sig"] == 98.0
    assert wire["delta"]["bytes"] == 500
    assert wire["enabled"] is True


def test_send_metrics_exposed():
    from cometbft_tpu.libs import metrics

    residency.record_send("indexed", 100, sigs=1)
    residency.record_send("full", 200)
    out = metrics.global_registry().render()
    assert 'cometbft_crypto_verify_send_bytes{path="indexed"}' in out
    assert 'cometbft_crypto_verify_sends{path="full"}' in out


def test_config_wire_knobs_validate_and_apply():
    from cometbft_tpu.config.config import CryptoConfig
    from cometbft_tpu.crypto import batch as crypto_batch

    cfg = CryptoConfig(backend="cpu", wire_indexed_sends=False,
                       wire_table_rows=128)
    cfg.validate_basic()
    crypto_batch.configure(cfg)
    try:
        assert residency.enabled() is False
        assert residency._cfg["rows"] == 128
    finally:
        crypto_batch.configure(CryptoConfig(backend="cpu"))
        crypto_batch.set_backend("auto")
    with pytest.raises(ValueError, match="wire_table_rows"):
        CryptoConfig(wire_table_rows=32).validate_basic()
    with pytest.raises(ValueError, match="wire_table_rows"):
        CryptoConfig(wire_table_rows=1 << 17).validate_basic()


def test_config_toml_roundtrip_keeps_wire_fields(tmp_path):
    from cometbft_tpu.config import Config

    cfg = Config(home=str(tmp_path))
    cfg.crypto.wire_indexed_sends = False
    cfg.crypto.wire_table_rows = 4096
    cfg.save()
    loaded = Config.load(str(tmp_path))
    assert loaded.crypto.wire_indexed_sends is False
    assert loaded.crypto.wire_table_rows == 4096


# ------------------------------------------------------------ bench --out


def test_bench_out_file_preferred_over_truncated_snapshot(tmp_path):
    import sys

    sys.path.insert(0, "/root/repo")
    import bench
    from tools import bench_compare

    record = {"metric": "ed25519_verify_throughput", "value": 123.0,
              "unit": "sigs/sec", "vs_baseline": 2.0,
              "detail": {"wire_bytes_per_sig": 98.0}}
    out_path = str(tmp_path / "BENCH_r09.out.json")
    bench._write_out(record, out_path)
    # driver snapshot with a front-truncated tail and parsed null — the
    # BENCH_r05 failure shape
    snap_path = str(tmp_path / "BENCH_r09.json")
    with open(snap_path, "w") as f:
        json.dump({"n": 9, "cmd": "python bench.py --out BENCH_r09.out.json",
                   "rc": 0, "tail": '"value": 1.0}}', "parsed": None}, f)
    got = bench_compare.load_snapshot(snap_path)
    assert got == record  # the out-file won, not the tail scrape
    # explicit "out" key wins too
    with open(snap_path, "w") as f:
        json.dump({"parsed": None, "tail": "", "out": out_path}, f)
    assert bench_compare.load_snapshot(snap_path) == record
    # ...but a GOOD parsed record is never shadowed by a stale
    # convention-named sibling (only the explicit "out" key outranks it)
    fresh = {"metric": "ed25519_verify_throughput", "value": 456.0,
             "detail": {"wire_bytes_per_sig": 66.0}}
    with open(snap_path, "w") as f:
        json.dump({"n": 9, "cmd": "python bench.py", "rc": 0,
                   "tail": "", "parsed": fresh}, f)
    assert bench_compare.load_snapshot(snap_path) == fresh
    # raw records (no driver wrapper) load as before
    with open(snap_path, "w") as f:
        json.dump(record, f)
    assert bench_compare.load_snapshot(snap_path) == record


def test_wire_bytes_per_sig_enforced_lower_better():
    from tools import bench_compare

    old = {"metric": "m", "value": 100.0,
           "detail": {"wire_bytes_per_sig": 98.0,
                      "stream_sigs_per_s": 200000.0}}
    new = json.loads(json.dumps(old))
    new["detail"]["wire_bytes_per_sig"] = 150.0  # +53%: a send regression
    new["detail"]["stream_sigs_per_s"] = 50000.0  # -75%: also enforced now
    verdict = bench_compare.compare(old, new)
    assert "wire_bytes_per_sig" in verdict["regressions"]
    # stream_sigs_per_s graduated from wire-bound-informational once the
    # device-challenge rung made the stream compute-bound
    assert "stream_sigs_per_s" in verdict["regressions"]
    assert verdict["metrics"]["stream_sigs_per_s"]["verdict"] == "fail"
    # an improvement always passes
    better = json.loads(json.dumps(old))
    better["detail"]["wire_bytes_per_sig"] = 34.0
    assert bench_compare.compare(old, better)["verdict"] == "pass"
