"""BLS12-381 consensus integration: commit verification through
types/validation.py's aggregate path and the 4-validator in-process net
with BLS validator keys.

The commit-level tests are tier-1-safe (oracle-rung aggregate, a few
hundred ms per check). The live nets are `slow` — BLS signing/verifying
on the pure-Python oracle costs ~0.1-0.3 s per vote, so a few heights
take tens of seconds (no device compile involved: the CPU backend stays
on the oracle rung)."""

from __future__ import annotations

import asyncio

import pytest

from cometbft_tpu.crypto import bls12381 as bls
from cometbft_tpu.crypto import ed25519
from cometbft_tpu.types.validation import (verify_commit,
                                           stage_verify_commit,
                                           ErrInvalidCommitSignature)

from net_harness import make_net


def _commit_fixture(schemes):
    """Build a real commit by running a tiny in-proc net and pulling a
    committed (valset, commit, block) out of it."""
    async def main():
        net = await make_net(len(schemes), key_schemes=list(schemes),
                             chain_id="bls-commit-fixture")
        await net.start()
        try:
            await net.wait_for_height(2, timeout=120.0)
        finally:
            await net.stop()
        node = net.nodes[0]
        commit = (node.block_store.load_seen_commit(1)
                  or node.block_store.load_block_commit(1))
        # height 1 was signed by the genesis validator set
        from cometbft_tpu.types.validator import Validator, ValidatorSet

        vals = ValidatorSet([Validator.new(p.pub_key(), 10)
                             for p in net.privs])
        return "bls-commit-fixture", vals, commit

    return asyncio.run(main())


@pytest.mark.slow
def test_four_validator_bls_net_commits_fork_free():
    """Acceptance: a 4-val in-proc net with BLS validator keys commits
    fork-free; every commit verified through the aggregate path."""
    async def main():
        net = await make_net(4, key_scheme="bls12381",
                             chain_id="bls-net-chain")
        await net.start()
        try:
            await net.wait_for_height(3, timeout=300.0)
        finally:
            await net.stop()
        for n in net.nodes:
            assert n.block_store.height() >= 3
        h2 = {n.block_store.load_block(2).hash() for n in net.nodes}
        assert len(h2) == 1, "fork detected"

    asyncio.run(main())


@pytest.mark.slow
def test_mixed_scheme_net_commits_and_verifies_per_lane():
    """Acceptance: a mixed-scheme commit (BLS + ed25519 validators)
    verifies through the scheduler with correct per-lane attribution —
    the net only advances if every commit (mixed sub-batches, one per
    scheme) verifies on every node."""
    async def main():
        net = await make_net(
            4, key_schemes=["bls12381", "ed25519", "ed25519", "bls12381"],
            chain_id="mixed-net-chain")
        await net.start()
        try:
            await net.wait_for_height(2, timeout=300.0)
        finally:
            await net.stop()
        h1 = {n.block_store.load_block(1).hash() for n in net.nodes}
        assert len(h1) == 1

    asyncio.run(main())


@pytest.mark.slow
def test_commit_verify_uses_aggregate_and_pinpoints_failures():
    """verify_commit on an all-BLS commit takes the one-pairing-product
    path; a corrupted signature still raises the per-signature error
    (the aggregate fails, the per-lane pass pinpoints)."""
    chain_id, vals, commit = _commit_fixture(["bls12381"] * 4)
    # the aggregate path accepts the honest commit
    verify_commit(chain_id, vals, commit.block_id, commit.height, commit)
    # staged (blocksync/light window) flavor resolves the same way
    staged = stage_verify_commit(
        chain_id, vals, commit.block_id, commit.height, commit)
    assert staged._bls_rows, "BLS commit must stage aggregate"
    staged.finish()
    # corrupt one signature: aggregate fails, per-lane pass pinpoints it
    k = bls.gen_priv_key_from_secret(b"intruder")
    bad = commit.signatures[1]
    orig = bad.signature
    bad.signature = k.sign(b"forged vote bytes")
    try:
        with pytest.raises(ErrInvalidCommitSignature):
            verify_commit(chain_id, vals, commit.block_id, commit.height,
                          commit)
        staged = stage_verify_commit(
            chain_id, vals, commit.block_id, commit.height, commit)
        with pytest.raises(ErrInvalidCommitSignature):
            staged.finish()
    finally:
        bad.signature = orig


def test_bls_disabled_commit_fails_loudly():
    """Satellite (validation side): an all-BLS validator set with the
    scheme disabled errors loudly instead of silently degrading."""
    from cometbft_tpu import crypto as _crypto
    from cometbft_tpu.types import validation as V

    class _FakePub:
        def type_(self):
            return "bls12381"

    bls.set_enabled(False)
    try:
        with pytest.raises(_crypto.ErrInvalidKey, match="bls_enabled"):
            V._bls_aggregate_ok([_FakePub()], [b"m"], [b"s"])
    finally:
        bls.set_enabled(True)
