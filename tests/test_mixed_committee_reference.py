"""The `committee-10k-mixed` configuration against the benchmark's plain
reference, small, on the CPU: benchmarks/configs/committee-10k-mixed.json
cut to 6 ed25519 + 6 sr25519 validators (the file's shapes otherwise: full
commits, equal power, its chain id and stamps), keys and presigned commits
made by benchmarks/datagen from a seed, verified through the program's
normal path (types.validation.verify_commit -> crypto/batch -> the
scheduler -> both schemes' device kernels, here XLA on the host CPU) and
through benchmarks/reference/commit_ref, which imports nothing of the
program. On the chip the same comparison, at 5,120 + 5,120, decides the
cell's `correct` (benchmarks/check.py)."""

from __future__ import annotations

import json
import os
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import datagen, program  # noqa: E402
from benchmarks.reference import commit_ref  # noqa: E402

from cometbft_tpu import sched  # noqa: E402
from cometbft_tpu.crypto import batch as crypto_batch  # noqa: E402
from cometbft_tpu.libs import chaos, linkmodel  # noqa: E402
from cometbft_tpu.ops import challenge, dispatch, residency  # noqa: E402
from cometbft_tpu.ops import ed25519_kernel as EK  # noqa: E402
from cometbft_tpu.types import validation  # noqa: E402

SEED = 2_147_489_028  # over 2**31, as the driver's are
PER_SCHEME = 6


def _committee(validators: dict):
    """(ValsetSpec, ring of CommitSpec, the program's ValidatorSet, the
    program's (block_id, Commit) for each ring entry)."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "committee-10k-mixed.json")) as fh:
        config = json.load(fh)
    assert config["validators"] == {"ed25519": 5120, "sr25519": 5120}
    config["validators"] = validators
    vals_spec, signers = datagen.make_validators(config, SEED)
    ring = datagen.make_ring(config, vals_spec, signers, SEED)
    assert len(ring) == config["ring_heights"] == 2
    vals = program.build_validator_set(vals_spec)
    commits = [program.build_commit(vals, spec) for spec in ring]
    return vals_spec, ring, vals, commits


@pytest.fixture(scope="module")
def committee():
    return _committee({"ed25519": PER_SCHEME, "sr25519": PER_SCHEME})


@pytest.fixture(scope="module")
def ed25519_committee():
    """The configuration's ed25519 half alone: the commits a staged solo
    finish() used to hand to the kernel itself, around the scheduler."""
    return _committee({"ed25519": PER_SCHEME})


@pytest.fixture(scope="module")
def device_plane():
    """Backend "tpu" through the node's own callable, one device (the
    tests' 8 forced host devices would switch the mesh on), and the
    cell's counters as benchmarks/program reads them."""
    from cometbft_tpu.config import Config
    from cometbft_tpu.libs import log as cmtlog
    from cometbft_tpu.node import node as node_mod
    from cometbft_tpu.parallel import mesh as verify_mesh

    def clean():
        chaos.reset()
        sched.reset()
        dispatch.reset_supervision()
        residency.reset()
        challenge.reset()
        linkmodel.reset()
        EK.reset_shape_log()
        verify_mesh.reset()

    clean()
    verify_mesh._set_for_testing(
        verify_mesh.VerifyMesh(devices=jax.devices()[:1]))
    prev = crypto_batch.get_backend()
    cfg = Config(home="")
    cfg.crypto.backend = "tpu"
    node_mod.configure_device_plane(
        cfg.crypto, cmtlog.Logger(level=cmtlog.parse_level("error")))
    counters = program.Counters()
    with program.warmup_watchdog():  # a cold XLA ladder outlasts 120 s
        yield counters
    counters.close()
    clean()
    crypto_batch.set_backend(prev)


def _lanes_of(vals_spec, scheme: str) -> list[int]:
    return [i for i, s in enumerate(vals_spec.schemes) if s == scheme]


def _cases():
    """(name, ring index, lanes to corrupt as (scheme, which of its
    lanes))."""
    return [("clean-0", 0, ()), ("clean-1", 1, ()),
            ("ed25519-first", 0, (("ed25519", 0),)),
            ("ed25519-last", 1, (("ed25519", -1),)),
            ("sr25519-first", 1, (("sr25519", 0),)),
            ("sr25519-last", 0, (("sr25519", -1),)),
            ("both-schemes", 0, (("sr25519", 2), ("ed25519", 3)))]


@pytest.fixture(params=["lane", "block"])
def row_path(request, monkeypatch):
    """Both ways types/validation._commit_rows selects a commit's rows
    (PR 31): walking the signatures, as a commit of 12 rows is by itself,
    and over columns, as the cell's 10,240 rows are, here forced. The
    kernels get the same batches either way."""
    from cometbft_tpu.types import commit as commit_mod

    if request.param == "block":
        monkeypatch.setattr(commit_mod, "VECTOR_SIGN_ROWS_MIN", 0)
        monkeypatch.setattr(validation, "ROW_BLOCK_MIN", 0)
    ran = []
    real = validation._select_block
    monkeypatch.setattr(validation, "_select_block", lambda *a, **kw: (
        ran.append(1), real(*a, **kw))[1])
    yield
    assert bool(ran) == (request.param == "block")


@pytest.mark.parametrize("name,ring_idx,corrupt", _cases(),
                         ids=[c[0] for c in _cases()])
def test_verify_commit_answers_as_the_reference(committee, device_plane,
                                                row_path, name, ring_idx,
                                                corrupt):
    vals_spec, ring, vals, commits = committee
    lanes = [_lanes_of(vals_spec, scheme)[which] for scheme, which in corrupt]
    spec = ring[ring_idx]
    block_id, commit = commits[ring_idx]
    commit = program.fresh(commit)
    for lane in lanes:
        spec = spec.with_flipped(lane)
        commit = program.fresh(commit, lane)

    want = commit_ref.verdict(vals_spec, spec, commit_ref.verify_lane)
    assert want == (f"reject#{min(lanes)}" if lanes else "accept")

    before = device_plane.read()
    got = program.verdict_of(lambda: validation.verify_commit(
        vals_spec.chain_id, vals, block_id, commit.height, commit))
    moved = program.Counters.diff(before, device_plane.read())

    assert got == want
    # the verdict is the device's own: nothing overturned, nothing served
    # by the host oracle
    assert moved["metrics.mask_oracle_disagreement"] == 0
    assert moved["metrics.fallback_verifies"] == 0
    assert moved["metrics.transfer_checksum_mismatch"] == 0
    # the cell's grouping path: one call's rows leave the scheduler as one
    # batch a scheme, each on the device
    assert moved["metrics.device_batches.ed25519"] == 1
    assert moved["metrics.device_batches.sr25519"] == 1
    assert moved["verify_sched.rows_total"] == 2 * PER_SCHEME
    assert moved["verify_sched.lanes_total"] == 2 * EK.bucket_size(PER_SCHEME)
    assert moved["staging.trip.batches"] == 2


@pytest.mark.parametrize("which", ["ed25519_committee", "committee"])
def test_solo_finish_enters_through_the_scheduler(request, device_plane,
                                                  row_path, which):
    """stage_verify_commit + finish() with no window prefetch, backend
    "tpu": one scheduler batch, one device batch a scheme, and a flipped
    signature named, as through verify_commit."""
    vals_spec, _ring, vals, commits = request.getfixturevalue(which)
    schemes = sorted(set(vals_spec.schemes))
    block_id, commit = commits[0]

    def solo(commit):
        return lambda: validation.stage_verify_commit(
            vals_spec.chain_id, vals, block_id, commit.height,
            commit).finish()

    for lane, want in ((None, "accept"), (3, "reject#3")):
        before = device_plane.read()
        got = program.verdict_of(solo(program.fresh(commit, lane)))
        moved = program.Counters.diff(before, device_plane.read())
        assert got == want
        assert moved["verify_sched.batches"] == 1
        assert moved["verify_sched.rows_total"] == len(vals_spec.schemes)
        assert moved["staging.trip.batches"] == len(schemes)
        for scheme in ("ed25519", "sr25519"):
            assert moved[f"metrics.device_batches.{scheme}"] == (
                scheme in schemes)
        assert moved["metrics.mask_oracle_disagreement"] == 0
        assert moved["metrics.fallback_verifies"] == 0


def test_the_committee_is_the_configurations(committee):
    """Mixed lane for lane in CometBFT's order (by address), equal power,
    every validator signing: what the cell runs at 5,120 + 5,120."""
    vals_spec, ring, vals, _commits = committee
    assert sorted(vals_spec.schemes) == (["ed25519"] * PER_SCHEME
                                         + ["sr25519"] * PER_SCHEME)
    assert len(set(vals_spec.schemes[:PER_SCHEME])) == 2  # interleaved
    assert set(vals_spec.powers) == {10}
    assert vals_spec.chain_id == "committee-10k"
    assert all(len(c.sigs) == 2 * PER_SCHEME for c in ring)
    assert [v.pub_key.type_() for v in vals.validators] == list(
        vals_spec.schemes)
