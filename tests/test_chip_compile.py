"""The main path's device programs, compiled for a v5e that is described
and not attached (on-chip-measurement guide, section 2), plus the Pallas
kernel's first correctness test (interpret mode, against the host oracle).

These guard every later PR at no chip time: what Mosaic or the TPU
compiler refuses here it would refuse on the chip. Nothing runs, so they
say nothing about results or times. The Pallas compiles take about half a
minute each (the verify ladder is a large program), not the guide's two
seconds. The XLA ladder the sub-128-lane buckets ride compiles too (65 s
at 8 lanes, by hand, PR 22) but is left out: with it the file ran past its
~200 s budget under the driver's six workers.

Rules this file keeps (the driver runs the suite with several workers, and
only one process at a time may load libtpu): the topology is described
inside a module-scoped fixture that skips when it cannot be; nothing
touches it at import time; everything is in THIS one file; no child
process; the persistent compilation cache is off around the compiles (a
described-device entry is written but cannot be read back without a chip).
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cometbft_tpu.ops.dispatch import KERNEL_DISPATCH_LOCK

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _verify_args(lanes: int, sharding):
    coords = jax.ShapeDtypeStruct((20, lanes), jnp.int32, sharding=sharding)
    words = jax.ShapeDtypeStruct((8, lanes), jnp.uint32, sharding=sharding)
    return (coords,) * 4 + (words,) * 3


@pytest.mark.parametrize("scheme,lanes", [("ed25519", 10240),
                                          ("sr25519", 6144)])
def test_pallas_verify_compiles_for_v5e(one_chip, no_persistent_cache,
                                        scheme, lanes):
    """The one Pallas kernel at the smoke's two big buckets: Mosaic
    accepts it and the program really carries the custom call."""
    from cometbft_tpu.ops import pallas_verify as PV

    # each scheme through the program its dispatch runs at that bucket:
    # sr25519 has one of its own name (PERF.md section 3, PR 28)
    args = _verify_args(lanes, one_chip)
    with KERNEL_DISPATCH_LOCK:  # the kernel's trace swaps module constants
        lowered = (PV.verify_pallas_sr_ok.lower(*args) if scheme == "sr25519"
                   else PV._verify_pallas_bench.lower(*args, scheme=scheme))
        compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_challenge_derive_compiles_for_v5e_vote_shape(one_chip,
                                                      no_persistent_cache):
    """The device SHA-512 + Barrett challenge program (with the gather
    from the 16,384-row key table in front and the block's checksum
    behind) at the geometry a 150-validator commit
    plans: bucket 256, the votes' shared prefix/tail, ms-grained
    timestamps with their off-length lanes as host fallbacks."""
    import chip_smoke
    from cometbft_tpu.ops import challenge
    from cometbft_tpu.ops import ed25519_kernel as EK

    vals, _bid, commit = chip_smoke.make_commit(150, 0, seed=3)
    _pubs, msgs, _sigs = chip_smoke.commit_rows(vals, commit)
    try:
        plan = challenge.plan_batch(msgs, np.ones(len(msgs), dtype=bool))
    finally:
        challenge.reset()
    assert plan is not None and plan.n_eligible > 0
    b = EK.bucket_size(len(msgs))
    assert b == 256
    fb = EK.bucket_size(plan.n_fallback) if plan.n_fallback else 0
    run = challenge.derive_fn(b, plan.var, plan.plen, plan.tlen, fb)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = 16384  # residency's default table (crypto.wire_table_rows)
    args = [arg((challenge.block_words(b, plan.var),), jnp.uint32),
            arg((b,), jnp.uint16),
            *[arg((20, rows), jnp.int32)] * 4,
            arg((8, rows), jnp.uint32),
            arg((challenge.TABLE_ROWS, challenge.PREFIX_CAP), jnp.uint8)]
    if fb:
        args += [arg((8, fb), jnp.uint32), arg((fb,), jnp.int32)]
    compiled = run.lower(*args).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("hostk", [False, True])
def test_trip_verify_program_compiles_for_v5e_commit_shape(
        one_chip, no_persistent_cache, hostk):
    """The second program of an ed25519 batch's trip at a 150-validator
    commit's 256 lanes: the Pallas ladder and the integrity header and
    payload in one module (and, host-challenge rung, the gather from the
    16,384-row key table and the checksum before them)."""
    from cometbft_tpu.ops import ed25519_kernel as EK

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    b, rows = 256, 16384
    words, scalar = arg((8, b), jnp.uint32), arg((), jnp.uint32)
    if hostk:
        args = [arg((b,), jnp.uint16), *[arg((20, rows), jnp.int32)] * 4,
                arg((3, 8, b), jnp.uint32), scalar]
    else:
        args = [*[arg((20, b), jnp.int32)] * 4, words, words, words,
                scalar, scalar]
    pallas_fn, _xla_fn = EK._verify_programs(hostk)
    with KERNEL_DISPATCH_LOCK:
        compiled = pallas_fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_key_table_delta_compiles_for_v5e(one_chip, no_persistent_cache):
    """The key table's one delta program (ops/residency.py) at the
    default 16,384-row table: a block of DELTA_ROWS columns scattered,
    its checksum chained."""
    from cometbft_tpu.ops import residency

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = 16384
    args = [*[arg((20, rows), jnp.int32)] * 4, arg((8, rows), jnp.uint32),
            arg((residency._DELTA_PLANES, residency.DELTA_ROWS), jnp.int32),
            arg((), jnp.uint32)]
    compiled = residency._delta_fn().lower(*args).compile()
    assert compiled.memory_analysis() is not None


def test_pallas_verify_interpret_matches_host_oracle():
    """pallas_verify.verify_pallas_ok in interpret mode at one 128-lane
    block: every lane agrees with the exact ZIP-215 host oracle, the one
    corrupted lane and only it is rejected, and the fused all-ok scalar
    follows the mask."""
    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.crypto import ed25519_math as oracle
    from cometbft_tpu.ops import ed25519_kernel as EK
    from cometbft_tpu.ops import pallas_verify as PV

    n, bad = PV.LANES, 77
    privs = [ed25519.gen_priv_key_from_secret(b"pallas-interpret-%d" % i)
             for i in range(n)]
    pubs = [p.pub_key().bytes_() for p in privs]
    msgs = [b"pallas interpret lane %d" % i for i in range(n)]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]
    sigs[bad] = sigs[bad][:40] + bytes([sigs[bad][40] ^ 1]) + sigs[bad][41:]
    # lanes 1..5: the valid signatures the kernels condemned until
    # canonicalize was made exact (tests/test_canonical_exact.py has them
    # through the XLA ladder); the kernel body traces the same function
    with open(os.path.join(ROOT, "benchmarks", "tests",
                           "condemned_valid_signatures.json")) as fh:
        for lane, case in enumerate(json.load(fh)["cases"], start=1):
            pubs[lane], msgs[lane], sigs[lane] = (
                bytes.fromhex(case[k]) for k in ("pub", "msg", "sig"))
    pre_ok, safe_pubs, rw, sw, kw = EK.stage_batch(pubs, msgs, sigs, n)
    enc = np.frombuffer(b"".join(safe_pubs), dtype=np.uint8).reshape(n, 32)
    ok_a, coords = EK.decompress_points(enc)
    a = EK.pad_coords_batch_minor(coords, n)
    with KERNEL_DISPATCH_LOCK:
        mask, allok = PV.verify_pallas_ok(
            *(jnp.asarray(x) for x in (*a, rw, sw, kw)), interpret=True)
    got = np.asarray(mask) & pre_ok & ok_a
    want = np.array([oracle.verify_zip215(p, m, s)
                     for p, m, s in zip(pubs, msgs, sigs)])
    assert want.sum() == n - 1 and not want[bad]
    assert (got == want).all()
    assert not bool(allok)
