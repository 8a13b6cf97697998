"""The flight recorder above the scheduler and below the host: the spans of
a commit's path from types/validation's entries down (ISSUE 26), and the
names the profiler finds the device programs by.

One `verify_commit` of a 150-validator ed25519 commit through the global
scheduler, and the staged window path, on the CPU backend, where an inline
drain runs on the caller's thread alone: the spans are stamped with that
thread's CPU clock (the tracer's clock is injectable), so a share of a
call's time is decided by the work done and not by which test worker had
the core between two spans.
"""

from __future__ import annotations

import gc
import time

import pytest

import chip_smoke
from cometbft_tpu import sched
from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.libs import trace
from cometbft_tpu.types import validation

CHAIN_ID = chip_smoke.CHAIN_ID
ENTRY_STAGES = ("signbytes", "collect")


@pytest.fixture(autouse=True)
def _fresh_tracer_and_scheduler():
    trace.reset()
    crypto_batch.set_backend("cpu")
    sched.reset()
    yield
    trace.reset()
    sched.reset()


@pytest.fixture(scope="module")
def hub():
    """(vals, block_id, commit) of a 150-validator ed25519 committee."""
    return chip_smoke.make_commit(150, 0, seed=26)


def _arm() -> None:
    trace.configure(enabled=True, capacity=4096, slow_ms=-1.0,
                    clock=time.thread_time_ns)


def _tree(spans: list[dict], root: dict) -> list[dict]:
    return [r for r in spans if r["trace_id"] == root["trace_id"]]


def _roots(spans: list[dict], name: str) -> list[dict]:
    return [r for r in spans if r["name"] == name
            and r["parent_id"] is None]


def _self_share(spans: list[dict], *roots: dict) -> float:
    """The roots' SELF time (what no stage span below them covers, by the
    tracer's own model) over their duration, taken together."""
    self_ns = sum(
        trace.attribution_of(_tree(spans, r))["stage_us"]["node"] * 1e3
        for r in roots)
    return self_ns / sum(r["dur_ns"] for r in roots)


class TestVerifyCommitRoot:
    def test_finer_spans_cover_90pct_and_share_the_roots_trace_id(self, hub):
        vals, block_id, commit = hub
        # once untraced: imports and first-use set-up are no call's time
        validation.verify_commit(CHAIN_ID, vals, block_id, commit.height,
                                 chip_smoke.fresh(commit))
        _arm()
        validation.verify_commit(CHAIN_ID, vals, block_id, commit.height,
                                 chip_smoke.fresh(commit))
        spans = trace.snapshot()
        (root,) = _roots(spans, "commit.verify")
        assert root["cat"] == "node"
        assert root["attrs"] == {"path": "full", "height": commit.height,
                                 "sigs": 150}
        # every span of the call is in the root's tree: nothing else ran
        assert {r["trace_id"] for r in spans} == {root["trace_id"]}
        names = {r["name"] for r in spans}
        assert {"commit.sign_bytes", "commit.rows", "commit.verdict",
                "sched.verify", "sched.flush", "sched.group_rows",
                "sched.host_verify"} <= names
        by_id = {r["id"]: r for r in spans}
        sign = next(r for r in spans if r["name"] == "commit.sign_bytes")
        assert sign["cat"] == "signbytes"
        assert sign["attrs"]["cached"] is False
        assert by_id[sign["parent_id"]]["name"] == "commit.rows"
        sched_verify = next(r for r in spans if r["name"] == "sched.verify")
        assert sched_verify["parent_id"] == root["id"]
        share = _self_share(spans, root)
        assert share <= 0.10, (
            f"{share:.3f} of commit.verify is covered by no finer span")
        # and the rolling attribution reads the same call
        att = trace.attribution()
        assert att["rows"] == 150
        assert att["stage_us"]["node"] * 1e3 <= 0.10 * root["dur_ns"]
        assert all(att["stage_us"][s] > 0 for s in ENTRY_STAGES)

    @pytest.mark.parametrize("entry,path", [
        (lambda v, b, c: validation.verify_commit_light(
            CHAIN_ID, v, b, c.height, c), "light"),
        (lambda v, b, c: validation.verify_commit_light_trusting(
            CHAIN_ID, v, c, validation.Fraction(1, 3)), "trusting"),
    ])
    def test_light_entries_open_the_same_root(self, hub, entry, path):
        vals, block_id, commit = hub
        _arm()
        entry(vals, block_id, chip_smoke.fresh(commit))
        spans = trace.snapshot()
        (root,) = _roots(spans, "commit.verify")
        assert root["attrs"]["path"] == path
        assert {r["trace_id"] for r in spans} == {root["trace_id"]}

    def test_second_call_over_one_commit_reads_cached_sign_bytes(self, hub):
        vals, block_id, commit = hub
        fresh = chip_smoke.fresh(commit)
        _arm()
        for _ in range(2):
            validation.verify_commit(CHAIN_ID, vals, block_id, fresh.height,
                                     fresh)
        first, second = [r for r in trace.snapshot()
                         if r["name"] == "commit.sign_bytes"]
        assert first["attrs"]["cached"] is False
        assert second["attrs"]["cached"] is True
        assert second["dur_ns"] * 20 < first["dur_ns"]

    def test_wrong_signature_is_named_under_a_verdict_span(self, hub):
        vals, block_id, commit = hub
        _arm()
        with pytest.raises(validation.ErrInvalidCommitSignature,
                           match=r"\(#17\)"):
            validation.verify_commit(CHAIN_ID, vals, block_id, commit.height,
                                     chip_smoke.fresh(commit, 17))
        spans = trace.snapshot()
        (root,) = _roots(spans, "commit.verify")
        verdicts = [r for r in spans if r["name"] == "commit.verdict"]
        assert len(verdicts) == 2  # mask -> list, then the first bad lane
        assert all(r["cat"] == "collect" and r["parent_id"] == root["id"]
                   for r in verdicts)

    def test_serial_path_has_one_span_around_its_loop(self):
        """A one-validator commit is below the batch threshold: the loop
        encodes per index (no all-rows pass, nothing cached on the commit)
        under one `commit.sign_bytes` span, not one a signature."""
        vals, block_id, commit = chip_smoke.make_commit(1, 0, seed=27)
        _arm()
        validation.verify_commit(CHAIN_ID, vals, block_id, commit.height,
                                 commit)
        loop, root = trace.snapshot()
        assert (loop["name"], loop["cat"]) == ("commit.sign_bytes",
                                               "signbytes")
        assert loop["attrs"] == {"serial": True}
        assert root["name"] == "commit.verify"
        assert loop["parent_id"] == root["id"]
        assert not commit._sign_rows


class TestStagedWindowRoots:
    WINDOW = 8  # blocksync's

    def test_stage_prefetch_resolve_each_covered_and_one_trace_each(
            self, hub):
        vals, block_id, commit = hub
        window = [chip_smoke.fresh(commit) for _ in range(self.WINDOW)]

        def run():
            staged = [validation.stage_verify_commit(
                CHAIN_ID, vals, block_id, c.height, c) for c in window]
            validation.prefetch_staged(staged)
            validation.resolve_staged(staged)
            return staged

        run()
        window = [chip_smoke.fresh(commit) for _ in range(self.WINDOW)]
        _arm()
        staged = run()
        assert all(s._passed for s in staged)
        spans = trace.snapshot()
        stage_roots = _roots(spans, "commit.stage_verify")
        assert len(stage_roots) == self.WINDOW
        (prefetch,) = _roots(spans, "commit.prefetch")
        (resolve,) = _roots(spans, "commit.resolve")
        assert prefetch["attrs"]["commits"] == self.WINDOW
        # every span belongs to one of the calls' trees
        roots = stage_roots + [prefetch, resolve]
        assert all(r["cat"] == "node" for r in roots)
        assert {r["trace_id"] for r in spans} == {
            r["trace_id"] for r in roots}
        # a stage call is under a millisecond of CPU with ~35 us of glue
        # of its own: one young collection there would decide a single
        # call's share, so the window's stage calls are held together
        for name, share in (
                ("commit.stage_verify", _self_share(spans, *stage_roots)),
                ("commit.prefetch", _self_share(spans, prefetch))):
            assert share <= 0.10, (
                f"{share:.3f} of {name} is covered by no finer span")
        names = {r["name"] for r in _tree(spans, prefetch)}
        assert {"commit.rows", "sched.verify", "sched.flush"} <= names
        # resolve_staged found the window prefetched: its tree is the
        # second prefetch (nothing left to send) and one finish a commit
        inner = [r for r in _tree(spans, resolve) if r is not resolve]
        assert sorted(r["name"] for r in inner) == sorted(
            ["commit.prefetch", "commit.rows"]
            + ["commit.resolve", "commit.verdict"] * self.WINDOW)

    def test_finish_alone_is_a_root_and_a_passed_one_makes_no_span(
            self, hub):
        vals, block_id, commit = hub
        staged = validation.stage_verify_commit(
            CHAIN_ID, vals, block_id, commit.height, chip_smoke.fresh(commit))
        _arm()
        staged.finish()
        staged.finish()
        roots = _roots(trace.snapshot(), "commit.resolve")
        assert len(roots) == 1
        assert roots[0]["attrs"]["path"] == "finish"


class TestTracerOffAtTheNewCallSites:
    def test_every_new_site_gets_the_shared_noop_and_nothing_is_hooked(
            self, hub, monkeypatch):
        import jax.profiler

        vals, block_id, commit = hub
        validation.verify_commit(CHAIN_ID, vals, block_id, commit.height,
                                 chip_smoke.fresh(commit))
        made: list = []
        real_span = trace.span

        def counting_span(name, *a, **kw):
            sp = real_span(name, *a, **kw)
            made.append((name, sp))
            return sp

        class Refused:
            def __init__(self, *_a, **_kw):
                raise AssertionError("TraceAnnotation made with tracer off")

        monkeypatch.setattr(trace, "span", counting_span)
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Refused)
        hooks = list(gc.callbacks)
        assert not trace.enabled()
        t0 = time.perf_counter()
        validation.verify_commit(CHAIN_ID, vals, block_id, commit.height,
                                 chip_smoke.fresh(commit))
        t_call = time.perf_counter() - t0
        assert gc.callbacks == hooks
        assert trace.snapshot() == []
        names = [name for name, _ in made]
        assert {"commit.verify", "commit.sign_bytes", "commit.rows",
                "commit.verdict"} <= set(names)
        assert all(sp is trace._NOP for _, sp in made)
        # per call, never per signature
        assert len(names) <= 16, names
        monkeypatch.setattr(trace, "span", real_span)

        def touches():
            for name in names:
                with trace.span(name, cat="node", sigs=150) as sp:
                    sp.set(cached=False)

        t0 = time.perf_counter()
        for _ in range(100):
            touches()
        t_touches = (time.perf_counter() - t0) / 100
        assert t_touches < 0.03 * t_call, (
            f"{len(names)} disabled spans cost {t_touches * 1e6:.1f} us "
            f"against 3% of a {t_call * 1e3:.2f} ms verify_commit")


class TestProgramNames:
    def test_derive_program_is_named_and_scoped(self):
        import jax
        import jax.numpy as jnp

        from cometbft_tpu.ops import challenge

        bucket, var, plen, tlen = 8, 5, 60, 14
        run = challenge.derive_fn(bucket, var, plen, tlen, 0)
        lowered = run.lower(
            jax.ShapeDtypeStruct((challenge.block_words(bucket, var),),
                                 jnp.uint32),
            jax.ShapeDtypeStruct((bucket,), jnp.uint16),
            *[jax.ShapeDtypeStruct((20, 64), jnp.int32)] * 4,
            jax.ShapeDtypeStruct((8, 64), jnp.uint32),
            jax.ShapeDtypeStruct(
                (challenge.TABLE_ROWS, challenge.PREFIX_CAP), jnp.uint8))
        text = lowered.as_text(debug_info=True)
        assert "module @jit_derive_challenge" in text
        for scope in ("prefix_rows", "sha512_schedule", "sha512_rounds",
                      "barrett_mod_l"):
            assert f"jit(derive_challenge)/{scope}/" in text, scope

    @pytest.mark.parametrize("builder,name", [
        (lambda ch: ch._digest_fn(1), "sha512_digest"),
        (lambda ch: ch._reduce_fn(), "reduce_mod_l"),
        (lambda ch: ch._tab_scatter_fn(1), "prefix_table_scatter"),
        (lambda ch: ch.derive_fn(8, 5, 60, 14, 0), "derive_challenge"),
    ])
    def test_no_program_of_the_challenge_module_is_called_f(
            self, builder, name):
        from cometbft_tpu.ops import challenge

        assert builder(challenge).__name__ == name

    def test_decompress_and_integrity_are_scoped(self):
        import jax
        import jax.numpy as jnp

        from cometbft_tpu.ops import ed25519_kernel as EK

        words = jax.ShapeDtypeStruct((8, 8), jnp.uint32)
        text = EK._decompress_kernel.lower(words).as_text(debug_info=True)
        assert "jit(_decompress_kernel)/decompress/" in text
        mask = jax.ShapeDtypeStruct((8,), jnp.bool_)
        scalar = jax.ShapeDtypeStruct((), jnp.bool_)
        expected = jax.ShapeDtypeStruct((), jnp.uint32)
        text = EK._integrity_parts.lower(
            mask, scalar, words, words, words, expected).as_text(
                debug_info=True)
        assert "module @jit__integrity_parts_expr" in text
        assert "/integrity/" in text

    def test_the_rooflines_module_keeps_its_name(self):
        """verify_kernel_roofline.commit finds the kernel by the module
        pattern `verify_pallas` (benchmarks/metrics)."""
        import re

        from cometbft_tpu.ops import ed25519_kernel as EK
        from cometbft_tpu.ops import pallas_verify as PV

        assert PV._verify_pallas_bench.__name__ == "_verify_pallas_bench"
        # the ed25519 trip's verify programs (ladder + integrity in one):
        # the Pallas rung matches the reader's pattern, the XLA rung must
        # not be counted as the kernel
        for hostk in (False, True):
            pallas_fn, xla_fn = EK._verify_programs(hostk)
            assert re.search("verify_pallas", pallas_fn.__name__)
            assert not re.search("verify_pallas", xla_fn.__name__)

    @pytest.mark.parametrize("scheme", ["ed25519", "sr25519"])
    def test_pallas_call_is_named_by_scheme(self, scheme, monkeypatch):
        """The un-jitted entry with pallas_call recorded in place of
        traced: the kernel body's ladder is ~25 s of tracing a shape."""
        import jax.numpy as jnp

        from cometbft_tpu.ops import field as F
        from cometbft_tpu.ops import pallas_verify as PV

        seen: dict = {}

        def recording_pallas_call(kernel, **kw):
            seen.update(kw)
            return lambda *args: tuple(
                jnp.zeros(s.shape, s.dtype) for s in kw["out_shape"])

        monkeypatch.setattr(PV.pl, "pallas_call", recording_pallas_call)
        coords = jnp.zeros((F.NLIMBS, PV.LANES), jnp.int32)
        words = jnp.zeros((8, PV.LANES), jnp.uint32)
        mask, allok = PV._verify_pallas_bench.__wrapped__(
            coords, coords, coords, coords, words, words, words,
            interpret=True, scheme=scheme)
        assert seen["name"] == f"verify_ladder_{scheme}"
        assert seen["interpret"] is True
        assert mask.shape == (PV.LANES,)
