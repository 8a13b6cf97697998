"""Global verify scheduler (cometbft_tpu/sched) — continuous batching of
all signature work.

Covers the tentpole contract end to end: inline consensus drains that
coalesce queued filler, per-item futures with deadline flushing, priority
ordering and mempool backpressure, the starvation guard, bucketed dispatch
shapes (at most one compiled program per ladder rung), the scheduler's own
chaos site degrading to fragmented dispatch, metrics/health surfaces, and
a live 4-validator net whose vote flushes all route through the scheduler.
"""

from __future__ import annotations

import asyncio
import time

import numpy as np
import pytest

from cometbft_tpu import sched
from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.crypto import ed25519, sr25519
from cometbft_tpu.libs import chaos
from cometbft_tpu.sched.scheduler import CONSENSUS, MEMPOOL, SYNC, VerifyScheduler


@pytest.fixture(autouse=True)
def _fresh_scheduler():
    """Each case gets a fresh scheduler (and leaves none behind)."""
    sched.reset()
    chaos.reset()
    yield
    chaos.reset()
    sched.reset()
    sched.configure(max_lanes=16384, sync_deadline=0.002,
                    mempool_deadline=0.010, queue_limit=16384,
                    starvation_limit=0.25)


def _rows(n: int, bad: set[int] = frozenset(), scheme: str = "ed25519"):
    mod = ed25519 if scheme == "ed25519" else sr25519
    out = []
    for i in range(n):
        priv = mod.gen_priv_key()
        msg = b"sched-%d" % i
        sig = priv.sign(msg if i not in bad else b"WRONG")
        out.append((priv.pub_key(), msg, sig))
    return out


# ----------------------------------------------------------------- core


class TestVerifyNow:
    def test_masks_and_order(self):
        rows = _rows(6, bad={1, 4})
        mask = sched.get().verify_now(rows, CONSENSUS)
        assert mask.tolist() == [True, False, True, True, False, True]

    def test_verify_many_per_group_masks(self):
        g1 = _rows(3)
        g2 = _rows(2, bad={0})
        m1, m2 = sched.get().verify_many([g1, g2], SYNC)
        assert m1.tolist() == [True, True, True]
        assert m2.tolist() == [False, True]

    def test_mixed_schemes_one_batch(self):
        rows = _rows(2) + _rows(2, scheme="sr25519") + _rows(1, bad={0})
        mask = sched.get().verify_now(rows, CONSENSUS)
        assert mask.tolist() == [True, True, True, True, False]
        assert sched.get().batches == 1  # one coalesced dispatch

    def test_empty(self):
        assert sched.get().verify_many([[]], CONSENSUS)[0].tolist() == []


class TestFillerCoalescing:
    def test_queued_mempool_rides_consensus_flush(self):
        s = sched.get()
        # explicit far deadline: the worker must not race the inline
        # drain we are asserting on
        futs = s.submit(_rows(3), klass=MEMPOOL,
                        deadline=time.monotonic() + 30)
        assert not any(f.done() for f in futs)
        mask = s.verify_now(_rows(2), CONSENSUS)
        assert mask.tolist() == [True, True]
        # the riders resolved in the SAME batch, not a separate one
        assert s.batches == 1
        assert [f.result(timeout=1.0) for f in futs] == [True] * 3
        assert s.health()["fill_ratio_mean"] > s.health()[
            "fragmented_fill_ratio_mean"]

    def test_rider_bigger_than_bucket_space_stays_queued(self):
        s = sched.get()
        s.max_lanes = 8
        futs = s.submit(_rows(8), klass=MEMPOOL,  # never fits beside 2 rows
                        deadline=time.monotonic() + 30)
        s.verify_now(_rows(2), CONSENSUS)
        assert not any(f.done() for f in futs)
        s.flush()
        assert all(f.result(timeout=1.0) for f in futs)


class TestDeadlineWorker:
    def test_mempool_flushes_within_deadline(self):
        sched.configure(mempool_deadline=0.02)
        futs = sched.get().submit(_rows(2), klass=MEMPOOL)
        t0 = time.monotonic()
        assert [f.result(timeout=2.0) for f in futs] == [True, True]
        assert time.monotonic() - t0 < 1.0
        assert sched.get().worker_flushes >= 1

    def test_explicit_deadline_honored(self):
        s = sched.get()
        fut = s.submit(_rows(1), klass=SYNC,
                       deadline=time.monotonic() + 0.01)[0]
        assert fut.result(timeout=2.0) is True


class TestBackpressure:
    def test_mempool_rejected_when_queue_full(self):
        sched.configure(queue_limit=4)
        s = sched.get()
        s.submit(_rows(4), klass=MEMPOOL, deadline=time.monotonic() + 30)
        with pytest.raises(sched.SchedulerSaturated):
            s.submit(_rows(1), klass=MEMPOOL, deadline=time.monotonic() + 30)
        assert s.health()["rejected"] == 1
        s.flush()

    def test_mempool_rejected_when_consensus_saturated(self):
        sched.configure(queue_limit=4)
        s = sched.get()
        # consensus backlog alone fills the next buckets: admission sheds
        s.submit(_rows(4), klass=CONSENSUS, deadline=time.monotonic() + 30)
        with pytest.raises(sched.SchedulerSaturated):
            s.submit(_rows(1), klass=MEMPOOL)
        s.flush()

    def test_consensus_never_rejected(self):
        sched.configure(queue_limit=1)
        s = sched.get()
        s.submit(_rows(3), klass=SYNC, deadline=time.monotonic() + 30)
        s.submit(_rows(3), klass=CONSENSUS, deadline=time.monotonic() + 30)
        assert s.flush() == 6


class TestStarvationGuard:
    def test_overdue_mempool_promoted_over_fresh_sync(self):
        clock = [0.0]
        s = VerifyScheduler(max_lanes=8, starvation_limit=0.1,
                            clock=lambda: clock[0])
        old = s.submit(_rows(4), klass=MEMPOOL, deadline=1e9)
        clock[0] = 1.0  # far past the starvation limit
        fresh = s.submit(_rows(4), klass=SYNC, deadline=1e9)
        # inline drain has room for only ONE 4-row rider beside 4 own
        # rows at max_lanes=8... bucket_lanes(8+?)=8 -> space=4: the
        # overdue mempool group must win over the fresh sync group
        s.verify_now(_rows(4), CONSENSUS)
        assert all(f.done() for f in old)
        assert not any(f.done() for f in fresh)
        s.flush()
        s.stop()


class TestBucketShapes:
    def test_randomized_sizes_bounded_shapes(self, sched_rng):
        s = sched.get()
        for _ in range(40):
            n = sched_rng.randint(1, 40)
            s.verify_now(_rows(n), CONSENSUS)
        snap = s.health()
        ladder = set(s.bucket_ladder())
        assert set(snap["dispatch_shapes"]) <= ladder
        assert len(snap["dispatch_shapes"]) <= snap["bucket_ladder_len"]

    def test_bucket_ladder_matches_kernel(self):
        from cometbft_tpu.ops import ed25519_kernel as EK

        s = sched.get()
        for b in s.bucket_ladder(4096):
            assert EK.bucket_size(b) == b
        assert s.bucket_lanes(3) == 8
        assert s.bucket_lanes(129) == 256
        assert s.bucket_lanes(2049) == 4096

    def test_warmup_noop_on_cpu_backend(self):
        assert crypto_batch.resolve_backend() == "cpu"
        assert sched.get().warmup() == []


@pytest.mark.slow
class TestSchedulerSoak:
    def test_offered_load_soak_shape_bound(self, sched_rng):
        """Randomized offered load (consensus flush sizes, sync windows,
        mempool singles) for many rounds: the set of dispatched shapes
        stays within the bucket ladder — at most one compiled program
        per rung, never one per unique batch size."""
        s = sched.get()
        sizes = set()
        for _ in range(300):
            kind = sched_rng.random()
            if kind < 0.5:
                n = sched_rng.randint(1, 200)
                sizes.add(n)
                s.verify_now(_rows(min(n, 24)) * ((n // 24) + 1), CONSENSUS)
            elif kind < 0.8:
                w = [_rows(sched_rng.randint(1, 8)) for _ in range(3)]
                s.verify_many(w, SYNC)
            else:
                try:
                    s.submit(_rows(1), klass=MEMPOOL)
                except sched.SchedulerSaturated:
                    pass
        s.flush()
        snap = s.health()
        assert len(snap["dispatch_shapes"]) <= snap["bucket_ladder_len"]
        assert set(snap["dispatch_shapes"]) <= set(s.bucket_ladder())
        # pre-PR architecture would have paid one shape per unique size
        assert len(snap["dispatch_shapes"]) < len(sizes)


class TestPartialDispatchFailure:
    def test_failing_chunk_never_strands_other_chunks(self, monkeypatch):
        """A dispatch split into chunks must fail ONLY the failing
        chunk's futures; later chunks still dispatch and resolve — a
        stranded future would wedge a mempool admission await forever."""
        s = sched.get()
        s.max_lanes = 8  # 6+6 rows cannot share a chunk
        f1 = s.submit(_rows(6), klass=MEMPOOL,
                      deadline=time.monotonic() + 30)
        f2 = s.submit(_rows(6), klass=MEMPOOL,
                      deadline=time.monotonic() + 30)
        calls = {"n": 0}
        orig = VerifyScheduler._run_batch

        def flaky(self, groups):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("device went away")
            return orig(self, groups)

        monkeypatch.setattr(VerifyScheduler, "_run_batch", flaky)
        with pytest.raises(RuntimeError):
            s.flush()
        assert all(f.done() for f in f1 + f2)  # none stranded
        with pytest.raises(RuntimeError):
            f1[0].result(0)
        assert all(f.result(0) for f in f2)


# ----------------------------------------------------------------- chaos


class TestSchedChaos:
    def test_flush_fault_degrades_to_fragmented(self):
        chaos.arm("sched.flush", "transient", count=1)
        s = sched.get()
        futs = s.submit(_rows(2), klass=MEMPOOL)
        mask = s.verify_now(_rows(2, bad={1}), CONSENSUS)
        # verification correct despite the injected scheduler fault
        assert mask.tolist() == [True, False]
        assert [f.result(timeout=1.0) for f in futs] == [True, True]
        assert s.chaos_fallbacks == 1
        # next flush is healthy again
        assert s.verify_now(_rows(1), CONSENSUS).tolist() == [True]
        assert s.chaos_fallbacks == 1

    def test_permanent_flush_fault_still_verifies(self):
        chaos.arm("sched.flush", "permanent")
        mask = sched.get().verify_now(_rows(3, bad={0}), CONSENSUS)
        assert mask.tolist() == [False, True, True]
        assert sched.get().chaos_fallbacks >= 1


# ------------------------------------------------------- verifier routing


class TestRouting:
    def test_create_batch_verifier_routes_to_scheduler(self):
        bv = crypto_batch.create_batch_verifier(ed25519.gen_priv_key().pub_key())
        assert type(bv).__name__ == "ScheduledBatchVerifier"
        bv2 = crypto_batch.create_mixed_batch_verifier()
        assert type(bv2).__name__ == "ScheduledBatchVerifier"

    def test_ambient_work_class(self):
        assert sched.current_class() == CONSENSUS
        with sched.work_class(SYNC):
            assert sched.current_class() == SYNC
            bv = crypto_batch.create_batch_verifier(
                ed25519.gen_priv_key().pub_key())
            assert bv._klass == SYNC
        assert sched.current_class() == CONSENSUS

    def test_unbatchable_key_raises(self):
        from cometbft_tpu.crypto import secp256k1

        bv = crypto_batch.create_mixed_batch_verifier()
        priv = secp256k1.gen_priv_key()
        with pytest.raises(Exception):
            bv.add(priv.pub_key(), b"m", priv.sign(b"m"))

    def test_staged_commit_window_via_scheduler(self):
        """validation.prefetch_staged routes the window through the
        scheduler on the CPU backend too (pre-PR it was a TPU-only
        coalesce): one batch for the whole window."""
        import sys
        from pathlib import Path

        sys.path.insert(0, str(Path(__file__).parent))
        from light_harness import LightChain

        from cometbft_tpu.types import validation

        chain = LightChain("sched-window", 4, n_vals=4)
        vals = chain.valsets[1]
        staged = []
        for h in (1, 2, 3):
            lb = chain.blocks[h]
            staged.append(validation.stage_verify_commit(
                "sched-window", vals, lb.commit.block_id, h, lb.commit))
        before = sched.get().batches
        validation.prefetch_staged(staged, klass="sync")
        for s in staged:
            s.finish()
        assert sched.get().batches == before + 1
        assert sched.get().health()["class_rows"]["sync"] == 12


# ------------------------------------------------- one door to the device


def _signed_commits(chain_id: str, schemes: list[str], heights: int):
    """(vals, [(block_id, Commit)]): a validator set with one key a
    scheme name given (in CometBFT's order, by address) and `heights`
    full commits it signed."""
    import hashlib

    from cometbft_tpu.libs.prefixrows import as_bytes
    from cometbft_tpu.types.basic import BlockID, BlockIDFlag, PartSetHeader
    from cometbft_tpu.types.commit import Commit, CommitSig
    from cometbft_tpu.types.validator import Validator, ValidatorSet
    from cometbft_tpu.utils import cmttime

    mods = {"ed25519": ed25519, "sr25519": sr25519}
    privs = [mods[k].gen_priv_key_from_secret(b"one-door-%d" % i)
             for i, k in enumerate(schemes)]
    vals = ValidatorSet([Validator.new(p.pub_key(), 10) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    privs = [by_addr[v.address] for v in vals.validators]
    out = []
    for h in range(1, heights + 1):
        block_id = BlockID(hash=hashlib.sha256(b"door-%d" % h).digest(),
                           part_set_header=PartSetHeader(1, b"\x33" * 32))
        commit = Commit(height=h, round_=0, block_id=block_id, signatures=[
            CommitSig(block_id_flag=BlockIDFlag.COMMIT,
                      validator_address=v.address,
                      timestamp=cmttime.Timestamp(1_700_000_000 + h, i * 1000))
            for i, v in enumerate(vals.validators)])
        rows = commit.vote_sign_bytes_all(chain_id)
        for i, cs in enumerate(commit.signatures):
            cs.signature = privs[i].sign(as_bytes(rows.rows_for([i])[0]))
        out.append((block_id, commit))
    return vals, out


def _flipped(commit, idx: int):
    """A new Commit object (the sign-bytes cache rides the object) with
    one signature's first byte flipped."""
    import dataclasses

    from cometbft_tpu.types.commit import Commit

    sigs = list(commit.signatures)
    sig = sigs[idx].signature
    sigs[idx] = dataclasses.replace(
        sigs[idx], signature=bytes([sig[0] ^ 1]) + sig[1:])
    return Commit(height=commit.height, round_=commit.round_,
                  block_id=commit.block_id, signatures=sigs)


_KEY_MIXES = {
    "ed25519": ["ed25519"] * 4,
    "sr25519": ["sr25519"] * 4,
    "interleaved": ["ed25519", "sr25519"] * 3,
}
_DOOR_CHAIN = "one-door"
_DOOR_BAD = 1  # inside the rows even the 1/3-trusting path verifies


@pytest.fixture(scope="module")
def door_commits():
    """key mix -> (vals, three signed commits); signing is the slow part
    (sr25519 in Python integers), so once a module."""
    made = {mix: _signed_commits(_DOOR_CHAIN, schemes, 3)
            for mix, schemes in _KEY_MIXES.items()}
    kinds = [v.pub_key.type_() for v in made["interleaved"][0].validators]
    assert kinds != sorted(kinds) and kinds != sorted(kinds, reverse=True)
    return made


def _door_entries():
    """name -> (commits the entry takes, call(vals, [(block_id, commit)]));
    a window's second commit carries the flipped signature."""
    from cometbft_tpu.types import validation as V

    def stage(vals, pair):
        block_id, commit = pair
        return V.stage_verify_commit(
            _DOOR_CHAIN, vals, block_id, commit.height, commit)

    def window(vals, pairs):
        staged = [stage(vals, p) for p in pairs]
        V.prefetch_staged(staged, klass="sync")
        first_exc = None
        for s in staged:
            try:
                s.finish()  # per-commit error isolation stays with the caller
            except V.ErrInvalidCommitSignature as exc:
                first_exc = first_exc or exc
        if first_exc is not None:
            raise first_exc

    return {
        "verify_commit": (1, lambda vals, ps: V.verify_commit(
            _DOOR_CHAIN, vals, ps[0][0], ps[0][1].height, ps[0][1])),
        "verify_commit_light": (1, lambda vals, ps: V.verify_commit_light(
            _DOOR_CHAIN, vals, ps[0][0], ps[0][1].height, ps[0][1])),
        "verify_commit_light_trusting": (
            1, lambda vals, ps: V.verify_commit_light_trusting(
                _DOOR_CHAIN, vals, ps[0][1], V.Fraction(1, 3))),
        "stage_then_solo_finish": (
            1, lambda vals, ps: stage(vals, ps[0]).finish()),
        "window_of_three": (3, window),
        "resolve_staged": (2, lambda vals, ps: V.resolve_staged(
            [stage(vals, p) for p in ps])),
    }


class TestOneDoor:
    def test_old_scheduler_key_in_config_toml_is_ignored(self, tmp_path):
        """A node whose config.toml still carries the removed
        `crypto.scheduler` switch starts as before, on the scheduler."""
        from cometbft_tpu.config import Config

        cfg = Config(home=str(tmp_path))
        cfg.crypto.backend = "cpu"
        path = cfg.save()
        with open(path) as fh:
            text = fh.read()
        assert "\nscheduler =" not in text
        with open(path, "w") as fh:
            fh.write(text.replace("[crypto]\n", "[crypto]\nscheduler = false\n"))
        loaded = Config.load(str(tmp_path))
        assert not hasattr(loaded.crypto, "scheduler")
        assert loaded.crypto.backend == "cpu"
        crypto_batch.configure(loaded.crypto)
        bv = crypto_batch.create_batch_verifier(
            ed25519.gen_priv_key().pub_key())
        assert isinstance(bv, crypto_batch.ScheduledBatchVerifier)

    @pytest.mark.parametrize("package", [
        "types", "light", "blocksync", "state", "evidence", "consensus",
        "mempool", "crypto"])
    def test_upper_layers_import_no_kernel_module(self, package):
        """Which kernel serves a batch is the scheduler's decision alone:
        nothing above it reaches the kernel drivers."""
        import ast
        from pathlib import Path

        import cometbft_tpu

        banned = {"cometbft_tpu.ops." + m for m in (
            "ed25519_kernel", "sr25519_kernel", "batch_verifier")}
        files = sorted((Path(cometbft_tpu.__file__).parent / package)
                       .rglob("*.py"))
        assert files
        found = []
        for path in files:
            for node in ast.walk(ast.parse(path.read_text())):
                if isinstance(node, ast.Import):
                    names = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom):
                    names = [node.module or ""] + [
                        f"{node.module}.{a.name}" for a in node.names]
                else:
                    continue
                found += [f"{path.name}:{node.lineno} {n}" for n in names
                          if n in banned]
        assert not found, found

    @pytest.mark.parametrize("mix", list(_KEY_MIXES))
    @pytest.mark.parametrize("entry", list(_door_entries()))
    def test_every_entry_is_one_scheduler_batch(self, door_commits, entry,
                                                mix):
        """Each call or window into types/validation costs exactly one
        scheduler batch, passes a clean commit and names a flipped
        signature, whatever the key mix (cpu backend)."""
        from cometbft_tpu.types import validation as V

        n, call = _door_entries()[entry]
        vals, pairs = door_commits[mix]
        pairs = pairs[:n]

        before = sched.get().batches
        call(vals, pairs)
        assert sched.get().batches == before + 1

        bad_at = n // 2  # the middle commit of a window
        bad = [(bid, _flipped(c, _DOOR_BAD) if k == bad_at else c)
               for k, (bid, c) in enumerate(pairs)]
        before = sched.get().batches
        with pytest.raises(V.ErrInvalidCommitSignature,
                           match=rf"\(#{_DOOR_BAD}\)"):
            call(vals, bad)
        assert sched.get().batches == before + 1


# ------------------------------------------------------------- surfaces


class TestSurfaces:
    def test_crypto_health_has_verify_sched(self):
        from cometbft_tpu.ops import dispatch

        snap = dispatch.health_snapshot()
        vs = snap["verify_sched"]
        assert vs["enabled"] is True
        assert "fill_ratio_mean" in vs and "queue_depth" in vs

    def test_metrics_render_on_global_registry(self):
        from cometbft_tpu.libs import metrics as cmtmetrics

        cmtmetrics.sched_metrics()
        sched.get().verify_now(_rows(2), CONSENSUS)
        body = cmtmetrics.global_registry().render()
        for name in ("verify_sched_batch_lanes", "verify_sched_fill_ratio",
                     "verify_sched_queue_depth",
                     "verify_sched_flush_deadline_misses",
                     "verify_sched_flush_latency_seconds"):
            assert f"cometbft_{name}" in body, name

    def test_deadline_miss_counted(self):
        s = sched.get()
        # deadline already long past when the flush happens; either the
        # worker or the explicit flush dispatches it — futures resolve
        # strictly after miss accounting, so waiting removes the race
        futs = s.submit(_rows(1), klass=MEMPOOL,
                        deadline=time.monotonic() - 1.0)
        s.flush()
        assert futs[0].result(timeout=2.0) is True
        assert s.deadline_misses >= 1


# ----------------------------------------------------- live consensus net


class TestSchedulerThroughDeviceDeath:
    def test_net_commits_through_device_death_via_scheduler(self):
        """The chaos-matrix acceptance criterion verbatim: device faults
        armed (permanent dispatch death), a 4-validator net keeps
        committing with ALL verification routed via the scheduler — the
        scheduler's dispatches ride the supervisor/breaker ladder down to
        the CPU oracle, and the routing is asserted, not assumed."""
        from net_harness import make_net

        from cometbft_tpu.consensus.config import test_consensus_config
        from cometbft_tpu.libs import metrics as cmtmetrics
        from cometbft_tpu.ops import dispatch as D

        crypto_batch.set_backend("tpu")
        D.reset_supervision()
        D.configure(failure_threshold=1, retry_base=0.0, retry_cap=0.0)
        chaos.arm("ed25519.dispatch", "permanent")
        chaos.arm("sr25519.dispatch", "permanent")
        chaos.arm("pallas.trace", "permanent")
        fb0 = cmtmetrics.crypto_metrics().fallback_verifies.value("ed25519")

        async def run():
            cfg = test_consensus_config()
            cfg.batch_vote_verification = True
            net = await make_net(4, config=cfg, chain_id="sched-death")
            await net.start()
            try:
                await net.wait_for_height(4, timeout=90.0)
            finally:
                await net.stop()
            return net

        try:
            net = asyncio.run(run())
        finally:
            crypto_batch.set_backend("cpu")
            D.reset_supervision()
            D.configure(failure_threshold=3, retry_base=0.05, retry_cap=1.0)
        for node in net.nodes:
            assert node.block_store.height() >= 4
        snap = sched.get().health()
        assert snap["class_rows"]["consensus"] > 0  # flushes went via sched
        assert snap["batches"] > 0
        # the dead device dropped those scheduler batches onto the ladder
        assert cmtmetrics.crypto_metrics().fallback_verifies.value(
            "ed25519") > fb0


class TestSchedulerOnLiveNet:
    def test_four_validator_net_routes_votes_through_scheduler(self):
        """The chaos-matrix acceptance shape: a live 4-validator net with
        batched vote verification commits heights with EVERY flush routed
        through the scheduler (consensus-class rows observed), while
        mempool-class admission work runs concurrently as filler."""
        from net_harness import make_net

        from cometbft_tpu.consensus.config import test_consensus_config

        async def run():
            cfg = test_consensus_config()
            cfg.batch_vote_verification = True
            net = await make_net(4, config=cfg, chain_id="sched-net")
            await net.start()
            try:
                # concurrent mempool-class offered load
                rows = _rows(1)

                async def pump():
                    for _ in range(20):
                        try:
                            sched.get().submit(rows, klass=MEMPOOL)
                        except sched.SchedulerSaturated:
                            pass
                        await asyncio.sleep(0.01)

                pump_task = asyncio.create_task(pump())
                deadline = time.monotonic() + 60
                while time.monotonic() < deadline:
                    if min(n.block_store.height() for n in net.nodes) >= 3:
                        break
                    await asyncio.sleep(0.02)
                await pump_task
            finally:
                await net.stop()
            return min(n.block_store.height() for n in net.nodes)

        h = asyncio.run(run())
        assert h >= 3, f"net only reached height {h}"
        snap = sched.get().health()
        assert snap["class_rows"]["consensus"] > 0
        assert snap["class_rows"]["mempool"] > 0
        assert snap["batches"] > 0
