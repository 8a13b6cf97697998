"""Batched BLS12-381 verification — staging/dispatch/reduced-fetch glue
shaped like ed25519_kernel.py, so the VerifyScheduler, the supervisor/
breaker ladder, resolve_batches' two-phase reduced fetch, and the
VerifyMesh's per-chip fault domains all carry the scheme untouched.

Two verify modes:

  verify_batch_async      batched SINGLE-verify (mempool admission,
                          evidence checks, mixed-scheme commits): per
                          lane i the pairing-product check
                          e(-g1, sig_i) * e(pk_i, H(m_i)) == 1, with the
                          two Miller loops of every lane batched into one
                          2B-wide loop and the final exponentiations
                          vectorized across lanes.
  aggregate_verify        one-pairing-product COMMIT verify: signatures
                          sum to one G2 point, pubkeys aggregate per
                          distinct sign-bytes (PoP semantics — identical
                          vote bytes aggregate their signers), and the
                          whole commit decides with D+1 Miller lanes and
                          ONE final exponentiation, any committee size.

Device layout: the staged block is (7, 35, bucket) int32 raw limb planes
[pk_x, sig_x0, sig_x1, u00, u01, u10, u11] plus a (3, bucket) flag plane
(pk sign, sig sign, lane-is-padding); SHA-256 message expansion is host
work (ops/hashvec.sha256_many), everything downstream — decompression,
subgroup checks, SvdW mapping, cofactor clearing, Miller loops, final
exponentiation — runs on the batch axis (ops/bls12381/).

The device program is a HOST-COMPOSED pipeline of jitted pieces (shared
exp/scan programs) rather than one monolithic jit: the monolithic form
compiled ~3x slower for zero runtime gain, and piece reuse means the
single-verify and aggregate paths share most of their compiled code.
Staged blocks do not ride limbs.StagingPool — its (3, 8, B) r/s/k block
shape is ed25519's wire format; BLS blocks are 7 limb planes and get
fresh arrays (pooling them is a later perf PR if profiles ever show it).

Degradation: identical to the other schemes — TPU (or XLA-on-CPU) device
path under the DeviceSupervisor, host-oracle fallback
(crypto/fallback.bls_verify) on any device fault, breaker-open routing,
reduced-fetch happy path of 8 B/batch via the shared header protocol.
"""

from __future__ import annotations

import time as _time

import jax
import jax.numpy as jnp
import numpy as np

from cometbft_tpu.crypto import fallback as _oracle
from cometbft_tpu.libs import linkmodel as _linkmodel
from cometbft_tpu.libs import trace as _trace
from cometbft_tpu.ops import dispatch as _dispatch
from cometbft_tpu.ops import ed25519_kernel as EK
from cometbft_tpu.ops.dispatch import KERNEL_DISPATCH_LOCK
from cometbft_tpu.ops.ed25519_kernel import bucket_size

SCHEME = "bls12381"
PUB_KEY_SIZE = 48
SIGNATURE_SIZE = 96


def _dst() -> bytes:
    from cometbft_tpu.crypto import bls12381

    return bls12381.DST


def oracle_verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """The exact host oracle behind the recheck/fallback ladder."""
    return _oracle.bls_verify(pub, msg, sig, _dst())


# generator encodings: the structural-reject / padding placeholder rows
# (decompressable, in-subgroup; their verify verdict is masked anyway)
_G1_GEN_ENC = _oracle.bls_g1_compress(_oracle.BLS_G1)
_G2_GEN_ENC = _oracle.bls_g2_compress(_oracle.BLS_G2)

_NEG_G1_LIMBS: tuple | None = None  # memoized (35,1) Montgomery -g1


def _neg_g1_coords(b: int):
    """(-g1) affine coordinates broadcast to b lanes (Montgomery)."""
    global _NEG_G1_LIMBS
    from cometbft_tpu.ops.bls12381 import fp

    if _NEG_G1_LIMBS is None:
        x, y = _oracle._NEG_G1
        _NEG_G1_LIMBS = (fp._const(x * fp.R_MOD_P % fp.P_INT),
                         fp._const(y * fp.R_MOD_P % fp.P_INT))
    xs, ys = _NEG_G1_LIMBS
    shape = (fp.NLIMBS, b)
    return (jnp.broadcast_to(xs, shape).astype(jnp.int32),
            jnp.broadcast_to(ys, shape).astype(jnp.int32))


# ------------------------------------------------------------------ staging


def _structural_check(pubs, sigs, n):
    """Host structural pass: lengths, compression flags, infinity
    rejection, x < p canonicality — everything the oracle rejects before
    field math. Returns (pre_ok, pk_rows (n, 48) uint8, sig_rows
    (n, 96) uint8) with placeholder substitution on bad rows."""
    pre_ok = np.ones(n, dtype=bool)
    pk_rows = np.empty((n, PUB_KEY_SIZE), dtype=np.uint8)
    sig_rows = np.empty((n, SIGNATURE_SIZE), dtype=np.uint8)
    p = _oracle.BLS_P
    for i in range(n):
        pk, sg = pubs[i], sigs[i]
        ok = len(pk) == PUB_KEY_SIZE and len(sg) == SIGNATURE_SIZE
        if ok:
            ok = bool(pk[0] & 0x80) and not (pk[0] & 0x40)
            ok = ok and bool(sg[0] & 0x80) and not (sg[0] & 0x40)
        if ok:
            ok = int.from_bytes(bytes([pk[0] & 0x1F]) + pk[1:], "big") < p
            ok = (ok
                  and int.from_bytes(bytes([sg[0] & 0x1F]) + sg[1:48],
                                     "big") < p
                  and int.from_bytes(sg[48:], "big") < p)
        pre_ok[i] = ok
        pk_rows[i] = np.frombuffer(pk if ok else _G1_GEN_ENC, dtype=np.uint8)
        sig_rows[i] = np.frombuffer(sg if ok else _G2_GEN_ENC, dtype=np.uint8)
    return pre_ok, pk_rows, sig_rows


def stage_batch_bls(pubs, msgs, sigs, bucket: int):
    """Host staging: structural checks, SHA-256 message expansion
    (hashvec rung), limb packing. Returns (pre_ok (n,), block
    (7, 35, bucket) int32, flags (3, bucket) int32) — flags rows are
    [pk sign, sig sign, is_pad]. msgs=None zero-fills the u-planes
    (3..6): the aggregate path hashes only the DISTINCT messages in
    their own small bucket, so per-lane hash-to-field here would be
    O(n) dead work on the path whose point is committee-size-independent
    cost."""
    from cometbft_tpu.libs.prefixrows import as_bytes
    from cometbft_tpu.ops.bls12381 import fp
    from cometbft_tpu.ops.bls12381 import htc

    n = len(sigs)
    pre_ok, pk_rows, sig_rows = _structural_check(pubs, sigs, n)
    pad = bucket - n
    if pad:
        pk_rows = np.concatenate([pk_rows, np.broadcast_to(
            np.frombuffer(_G1_GEN_ENC, np.uint8), (pad, 48))])
        sig_rows = np.concatenate([sig_rows, np.broadcast_to(
            np.frombuffer(_G2_GEN_ENC, np.uint8), (pad, 96))])
    flags = np.zeros((3, bucket), dtype=np.int32)
    flags[0] = (pk_rows[:, 0] & 0x20) != 0
    flags[1] = (sig_rows[:, 0] & 0x20) != 0
    flags[2, n:] = 1
    pk_x = pk_rows.copy()
    pk_x[:, 0] &= 0x1F
    sg_x = sig_rows.copy()
    sg_x[:, 0] &= 0x1F
    block = np.empty((7, fp.NLIMBS, bucket), dtype=np.int32)
    block[0] = fp.bytes_be_to_limbs(pk_x)
    # G2 wire order is x_c1 || x_c0 — plane 1 is c0, plane 2 is c1
    block[1] = fp.bytes_be_to_limbs(np.ascontiguousarray(sg_x[:, 48:]))
    block[2] = fp.bytes_be_to_limbs(np.ascontiguousarray(sg_x[:, :48]))
    if msgs is None:
        block[3:] = 0
    else:
        msg_bytes = [as_bytes(m) for m in msgs]
        if pad:
            msg_bytes = msg_bytes + [b""] * pad
        u00, u01, u10, u11 = htc.hash_to_field_limbs(msg_bytes, _dst())
        block[3], block[4], block[5], block[6] = u00, u01, u10, u11
    return pre_ok, block, flags


# ------------------------------------------------------------ device pieces
#
# Host-composed jitted pipeline. Each piece is compiled once per bucket
# shape and shared by the single-verify, aggregate and mesh paths.


@jax.jit
def _jit_decompress(block, flags):
    """-> (ok_pk, ok_sig, pk Point coords, sig Point coords) — curve
    membership falls out of the sqrt existence check."""
    from cometbft_tpu.ops.bls12381 import points as pts

    okp, pk = pts.g1_decompress(block[0], flags[0])
    oks, sig = pts.g2_decompress(block[1], block[2], flags[1])
    return okp, oks, tuple(pk), tuple(sig)


@jax.jit
def _jit_subgroup_g1(x, y, z):
    from cometbft_tpu.ops.bls12381 import points as pts

    return pts.in_subgroup(pts.G1Field, pts.Point(x, y, z))


@jax.jit
def _jit_subgroup_g2(p):
    from cometbft_tpu.ops.bls12381 import points as pts

    return pts.in_subgroup(pts.G2Field, pts.Point(*p))


@jax.jit
def _jit_hash_msgs(u00, u01, u10, u11):
    """Raw hash_to_field limb planes -> G2 points (projective), then
    affine for the Miller input."""
    from cometbft_tpu.ops.bls12381 import fp
    from cometbft_tpu.ops.bls12381 import htc
    from cometbft_tpu.ops.bls12381 import points as pts
    from cometbft_tpu.ops.bls12381.fp2 import Fp2

    u0 = Fp2(fp.to_mont(u00), fp.to_mont(u01))
    u1 = Fp2(fp.to_mont(u10), fp.to_mont(u11))
    h = htc.map_to_g2(u0, u1)
    hx, hy, _hid = pts.to_affine(pts.G2Field, h)
    return tuple(hx), tuple(hy)


@jax.jit
def _jit_miller(px, py, qxa, qxb, qya, qyb):
    from cometbft_tpu.ops.bls12381 import pairing
    from cometbft_tpu.ops.bls12381.fp2 import Fp2

    return pairing.miller_loop(px, py, Fp2(qxa, qxb), Fp2(qya, qyb))


@jax.jit
def _jit_pair_halves(f):
    """(2B,) Miller lanes -> per-lane product of halves (B,)."""
    from cometbft_tpu.ops.bls12381 import tower

    lo = jax.tree_util.tree_map(lambda a: a[..., : a.shape[-1] // 2], f)
    hi = jax.tree_util.tree_map(lambda a: a[..., a.shape[-1] // 2:], f)
    return tower.f12_mul(lo, hi)


@jax.jit
def _jit_eq_one(f):
    from cometbft_tpu.ops.bls12381 import tower

    return tower.f12_eq_one(f)


@jax.jit
def _jit_mask_header(mask, pad, block, flags, expected):
    """Final per-lane mask (padding lanes forced valid so the all-ok
    reduction mirrors the identity-padding of the other kernels) plus
    the reduced-fetch header/payload pair (shared protocol)."""
    mask = mask | (pad != 0)
    allok = mask.all()
    chk = EK._device_checksum_expr((block, flags))
    ok = chk == expected.astype(jnp.uint32)
    payload = jnp.concatenate([mask, ~mask, ok[None]])
    tok = chk ^ jnp.where(allok & ok, EK.OK_MAGIC, EK._BAD_MAGIC)
    return jnp.stack([tok, ~tok]), payload


def _affine_points(block_dev, flags_dev):
    """Shared front half: decompress + subgroup-validate + hash msgs.
    Returns (eligible (B,), pk affine coords, sig affine coords,
    H(m) affine coords) — all device-resident."""
    okp, oks, pk, sig = _jit_decompress(block_dev, flags_dev)
    sub1 = _jit_subgroup_g1(*pk)
    sub2 = _jit_subgroup_g2(sig)
    hx, hy = _jit_hash_msgs(block_dev[3], block_dev[4],
                            block_dev[5], block_dev[6])
    eligible = okp & oks & sub1 & sub2
    return eligible, pk, sig, (hx, hy)


def _concat_lanes(arrs):
    return jnp.concatenate(arrs, axis=-1)


def _verify_device(block_dev, flags_dev, expected):
    """The full single-verify pipeline -> (header, payload) devices."""
    from cometbft_tpu.ops.bls12381 import pairing
    from cometbft_tpu.ops.bls12381.fp2 import Fp2

    b = block_dev.shape[-1]
    eligible, pk, sig, (hx, hy) = _affine_points(block_dev, flags_dev)
    ng1x, ng1y = _neg_g1_coords(b)
    # one 2B-wide Miller loop: lanes [0, B) = e(-g1, sig),
    # lanes [B, 2B) = e(pk, H(m))
    px = _concat_lanes([ng1x, pk[0]])
    py = _concat_lanes([ng1y, pk[1]])
    qxa = _concat_lanes([sig[0].a, jnp.asarray(hx[0])])
    qxb = _concat_lanes([sig[0].b, hx[1]])
    qya = _concat_lanes([sig[1].a, hy[0]])
    qyb = _concat_lanes([sig[1].b, hy[1]])
    f = _jit_miller(px, py, qxa, qxb, qya, qyb)
    f = _jit_pair_halves(f)
    e = pairing.final_exp_composed(f)
    mask = _jit_eq_one(e) & eligible
    return _jit_mask_header(mask, flags_dev[2], block_dev, flags_dev,
                            expected)


# ------------------------------------------------------- batched single-verify


def verify_batch_async(pubs, msgs, sigs, cache=None,
                       recheck_groups=None):
    """Stage + dispatch without blocking (mirror of
    sr25519_kernel.verify_batch_async): returns a thunk with
    .device_parts for the shared single-fetch resolver
    (ed25519_kernel.resolve_batches) — a mixed ed25519+sr25519+BLS
    window still pays ONE device round trip. Device faults degrade to
    the exact host oracle under the supervisor/breaker, identically to
    the other schemes."""
    del cache  # BLS has no decompressed-pubkey device cache yet
    n = len(sigs)
    assert len(pubs) == n and len(msgs) == n
    if n == 0:
        empty = lambda: np.zeros(0, dtype=bool)  # noqa: E731
        empty.device_parts = lambda: (
            None, 0, np.zeros(0, bool), np.zeros(0, bool), ([], [], []),
            (oracle_verify, SCHEME, None), None)
        return empty

    rows = (list(pubs), list(msgs), list(sigs))
    info = (oracle_verify, SCHEME, recheck_groups)
    sup = _dispatch.supervisor("device")
    b = bucket_size(n)

    staged = None
    stage_counted = False
    if _dispatch.device_allowed():
        try:
            with _trace.span("bls12381.stage", cat="stage", sig_rows=n,
                             lanes=b, hash_rung=EK._staging_rung()):
                stage_counted = True
                staged = stage_batch_bls(pubs, msgs, sigs, b)
        except Exception as exc:  # noqa: BLE001 - staging died: host rung
            sup.record_op_failure(exc)
    if staged is None:
        with _trace.span("bls12381.host_precheck", cat="stage",
                         sig_rows=0 if stage_counted else n):
            pre_ok, _, _ = _structural_check(pubs, sigs, n)
        return EK.make_host_thunk(n, pre_ok, rows, info)
    pre_ok, block, flags = staged
    expected = np.uint32(EK._host_checksum(block, flags))

    def _transfer_and_dispatch():
        from cometbft_tpu.libs import chaos

        chaos.fire("bls12381.dispatch")
        with _trace.span("bls12381.h2d", cat="transfer", lanes=b) as sp:
            t0 = _time.perf_counter()
            block_dev = jnp.asarray(block)
            flags_dev = jnp.asarray(flags)
            jax.block_until_ready((block_dev, flags_dev))
            nbytes = block.nbytes + flags.nbytes
            _linkmodel.link().observe_transfer(
                nbytes, _time.perf_counter() - t0)
            sp.add_bytes(tx=nbytes)
        try:
            from cometbft_tpu.ops import residency as _residency

            _residency.record_send("full", nbytes, sigs=n)
        except Exception:  # noqa: BLE001 - accounting never breaks verify
            pass
        with _trace.span("bls12381.dispatch", cat="compute", lanes=b,
                         device=EK.default_device_index()):
            with KERNEL_DISPATCH_LOCK:
                parts = _verify_device(
                    block_dev, flags_dev, np.uint32(expected))
        EK._count_device_batch(SCHEME, b)
        return parts

    return EK.supervised_device_thunk(
        SCHEME, sup, _transfer_and_dispatch, "bls12381.fetch",
        n, pre_ok, np.ones(n, dtype=bool), rows, info, expected=expected)


def verify_batch(pubs, msgs, sigs, cache=None):
    """Batched single-verify with a per-signature mask."""
    if len(sigs) == 0:
        return True, []
    mask = verify_batch_async(pubs, msgs, sigs, cache=cache)()
    return bool(mask.all()), mask.tolist()


# ------------------------------------------------------------ aggregate path


def aggregate_verify(pubs, msgs, sigs) -> bool:
    """The one-pairing-product commit check over per-vote rows: every
    signature subgroup-validated and summed, pubkeys aggregated per
    distinct sign-bytes, D+1 Miller lanes, ONE final exponentiation —
    commit verify cost ~independent of committee size. Device path when
    the ladder allows it; the exact oracle otherwise (bit-consistent
    semantics either way, tested on every rung)."""
    n = len(sigs)
    if n == 0 or len(pubs) != n or len(msgs) != n:
        return False
    from cometbft_tpu.crypto import batch as crypto_batch

    if (crypto_batch.resolve_backend() != "tpu"
            or not _dispatch.device_allowed()):
        return _oracle_aggregate(pubs, msgs, sigs)
    sup = _dispatch.supervisor("device")
    try:
        return sup.run(lambda: _aggregate_device(pubs, msgs, sigs))
    except Exception:  # noqa: BLE001 - device fault: exact host oracle
        EK._count_fallback(SCHEME, n)
        return _oracle_aggregate(pubs, msgs, sigs)


def aggregate_signatures(sigs) -> bytes:
    """Sum per-vote G2 signature points into the one 96 B aggregate a
    CommitCertificate carries. Host-side point adds (production runs
    once per commit; the pairing work all lives on the verify side).
    Raises ValueError on undecodable/infinity inputs."""
    return _oracle.bls_aggregate([bytes(s) for s in sigs])


def aggregate_verify_agg(pubs, msgs, agg_sig) -> bool:
    """The certificate-verify entry: the same one-pairing-product check
    as aggregate_verify, but the G2 side arrives ALREADY aggregated (a
    CommitCertificate's signature) so the per-vote summing stage is
    skipped. Device path when the ladder allows it; exact oracle
    otherwise — bit-consistent semantics either way."""
    n = len(pubs)
    if n == 0 or len(msgs) != n or len(agg_sig) != SIGNATURE_SIZE:
        return False
    from cometbft_tpu.crypto import batch as crypto_batch

    if (crypto_batch.resolve_backend() != "tpu"
            or not _dispatch.device_allowed()):
        return _oracle_aggregate_agg(pubs, msgs, agg_sig)
    sup = _dispatch.supervisor("device")
    try:
        # every staged lane carries the same aggregate so structural and
        # decompress checks run unchanged; the device path slices lane 0
        # instead of summing
        return sup.run(lambda: _aggregate_device(
            pubs, msgs, [bytes(agg_sig)] * n, presummed_sig=True))
    except Exception:  # noqa: BLE001 - device fault: exact host oracle
        EK._count_fallback(SCHEME, n)
        return _oracle_aggregate_agg(pubs, msgs, agg_sig)


def _oracle_aggregate_agg(pubs, msgs, agg_sig) -> bool:
    from cometbft_tpu.libs.prefixrows import as_bytes

    return _oracle.bls_aggregate_verify(
        [bytes(p) for p in pubs], [as_bytes(m) for m in msgs],
        bytes(agg_sig), _dst())


# validator-set subgroup-check cache: sha256(pk bytes) -> (N,) bool.
# A validator set re-verifies every height; its KeyValidate subgroup
# scans run once per set, not once per commit (the BLS analog of the
# ed25519 decompressed-pubkey cache). Bounded FIFO.
_VALSET_OK: dict[bytes, np.ndarray] = {}
_VALSET_CAP = 64


def _valset_subgroup_ok(pubs, pk_points) -> np.ndarray:
    import hashlib

    key = hashlib.sha256(b"".join(bytes(p) for p in pubs)).digest()
    hit = _VALSET_OK.get(key)
    if hit is not None:
        return hit
    ok = np.asarray(_jit_subgroup_g1(*pk_points))
    if len(_VALSET_OK) >= _VALSET_CAP:
        _VALSET_OK.pop(next(iter(_VALSET_OK)))
    _VALSET_OK[key] = ok
    return ok


def _oracle_aggregate(pubs, msgs, sigs) -> bool:
    from cometbft_tpu.libs.prefixrows import as_bytes

    try:
        agg = _oracle.bls_aggregate([bytes(s) for s in sigs])
    except ValueError:
        return False
    return _oracle.bls_aggregate_verify(
        [bytes(p) for p in pubs], [as_bytes(m) for m in msgs], agg, _dst())


def _aggregate_device(pubs, msgs, sigs, presummed_sig: bool = False) -> bool:
    from cometbft_tpu.libs.prefixrows import as_bytes
    from cometbft_tpu.ops.bls12381 import pairing
    from cometbft_tpu.ops.bls12381 import points as pts

    n = len(sigs)
    b = bucket_size(n)
    with _trace.span("bls12381.stage", cat="stage", sig_rows=n, lanes=b,
                     hash_rung=EK._staging_rung()):
        # distinct-message grouping (PoP: identical vote bytes
        # aggregate); the staged block's u-planes hash the DISTINCT
        # messages padded to their own small bucket
        msg_b = [as_bytes(m) for m in msgs]
        distinct = list(dict.fromkeys(msg_b))
        group_of = {m: i for i, m in enumerate(distinct)}
        lane_group = np.asarray([group_of[m] for m in msg_b],
                                dtype=np.int64)
        pre_ok, block, flags = stage_batch_bls(
            pubs, None, sigs, b)  # u-planes unused on this path
        if not pre_ok.all():
            return False
    chaos_ok = True
    try:
        from cometbft_tpu.libs import chaos

        chaos.fire("bls12381.dispatch")
    except Exception:  # noqa: BLE001 - injected fault: oracle rung
        chaos_ok = False
    if not chaos_ok:
        raise _dispatch.DeviceOpFailed("bls12381 aggregate chaos")
    with _trace.span("bls12381.h2d", cat="transfer", lanes=b) as sp:
        t0 = _time.perf_counter()
        block_dev = jnp.asarray(block)
        flags_dev = jnp.asarray(flags)
        jax.block_until_ready((block_dev, flags_dev))
        _linkmodel.link().observe_transfer(
            block.nbytes, _time.perf_counter() - t0)
        sp.add_bytes(tx=block.nbytes + flags.nbytes)
    try:
        from cometbft_tpu.ops import residency as _residency

        _residency.record_send("full", block.nbytes + flags.nbytes, sigs=n)
    except Exception:  # noqa: BLE001
        pass
    with _trace.span("bls12381.dispatch", cat="compute", lanes=b,
                     device=EK.default_device_index()):
        with KERNEL_DISPATCH_LOCK:
            okp, oks, pk, sig = _jit_decompress(block_dev, flags_dev)
            # per-pubkey KeyValidate subgroup scans are CACHED by
            # validator-set content (a valset re-verifies every height);
            # per-signature subgroup membership is NOT re-checked here —
            # only the SUM enters the pairing equation and the sum is
            # subgroup-checked below (single-verify admission covers
            # individuals), which is what keeps the aggregate path free
            # of n scalar-mul scans per commit
            pk_sub = _valset_subgroup_ok(pubs, pk)
            ok_rows = (np.asarray(okp) & np.asarray(oks))[:n] \
                & pk_sub[:n]
            if not ok_rows.all():
                return False
            # signature sum (padding lanes hold the generator — slice
            # the live lanes and pad with identity instead)
            sig_pts = pts.Point(*sig)
            if presummed_sig:
                # certificate path: every lane holds the SAME
                # pre-aggregated signature — lane 0 IS the sum (summing
                # would scale the point by n)
                sig_sum = jax.tree_util.tree_map(
                    lambda a: a[..., :1], sig_pts)
            else:
                live = jax.tree_util.tree_map(
                    lambda a: a[..., :n], sig_pts)
                sig_sum = pts.sum_tree(pts.G2Field, live, n)
            # per-group pubkey sums (group masks padded to the bucket)
            pk_pts = pts.Point(*pk)
            pk_sums = []
            for gi in range(len(distinct)):
                sel_np = np.zeros(b, dtype=bool)
                sel_np[:n] = lane_group == gi
                sel = jnp.asarray(sel_np)
                ident = pts.identity_like(pts.G1Field, pk_pts.y)
                masked = jax.tree_util.tree_map(
                    lambda a, i: jnp.where(sel[None, :], a, i),
                    pk_pts, ident)
                pk_sums.append(pts.sum_tree(pts.G1Field, masked, n))
            # hash the distinct messages (their own small bucket)
            from cometbft_tpu.ops.bls12381 import htc

            d = len(distinct)
            db = bucket_size(d)
            u00, u01, u10, u11 = htc.hash_to_field_limbs(
                distinct + [b""] * (db - d), _dst())
            hx, hy = _jit_hash_msgs(
                jnp.asarray(u00), jnp.asarray(u01),
                jnp.asarray(u10), jnp.asarray(u11))
            # reject cancelled pubkey groups / infinity signature sum
            # (oracle semantics) and assemble the D+1 Miller lanes
            if not bool(np.asarray(_jit_subgroup_g2(tuple(sig_sum)))[0]):
                return False
            sig_aff = pts.to_affine(pts.G2Field, sig_sum)
            if bool(np.asarray(sig_aff[2])[0]):
                return False
            pk_affs = [pts.to_affine(pts.G1Field, s) for s in pk_sums]
            if any(bool(np.asarray(a[2])[0]) for a in pk_affs):
                return False
            mb = bucket_size(d + 1)
            ng1x, ng1y = _neg_g1_coords(1)
            px = _concat_lanes(
                [a[0] for a in pk_affs] + [ng1x]
                + [ng1x] * (mb - d - 1))
            py = _concat_lanes(
                [a[1] for a in pk_affs] + [ng1y]
                + [ng1y] * (mb - d - 1))
            qxa = _concat_lanes(
                [hx[0][:, gi:gi + 1] for gi in range(d)]
                + [sig_aff[0].a]
                + [sig_aff[0].a] * (mb - d - 1))
            qxb = _concat_lanes(
                [hx[1][:, gi:gi + 1] for gi in range(d)]
                + [sig_aff[0].b] + [sig_aff[0].b] * (mb - d - 1))
            qya = _concat_lanes(
                [hy[0][:, gi:gi + 1] for gi in range(d)]
                + [sig_aff[1].a] + [sig_aff[1].a] * (mb - d - 1))
            qyb = _concat_lanes(
                [hy[1][:, gi:gi + 1] for gi in range(d)]
                + [sig_aff[1].b] + [sig_aff[1].b] * (mb - d - 1))
            f = _jit_miller(px, py, qxa, qxb, qya, qyb)
            # mask the pad lanes to one, multiply down, one final exp
            pad_mask = np.zeros(mb, dtype=bool)
            pad_mask[d + 1:] = True
            from cometbft_tpu.ops.bls12381 import tower

            f = tower.f12_select(
                jnp.asarray(pad_mask),
                tower.f12_one((_oracle_nlimbs(), mb)), f)
            f = pairing.product_lanes(f)
            e = pairing.final_exp_composed(f)
            ok = bool(np.asarray(_jit_eq_one(e))[0])
    EK._count_device_batch(SCHEME, b)
    return ok


def _oracle_nlimbs() -> int:
    from cometbft_tpu.ops.bls12381 import fp

    return fp.NLIMBS


# ----------------------------------------------------------- mesh shard seam


def mesh_shard_verify(chip_device, pubs, msgs, sigs):
    """One mesh chip's BLS shard (parallel/mesh.py ops["shard_verify"]):
    stage host-side, place the block on the chip, run the shared pieces,
    fetch the mask. Returns (mask (n,), eligible (n,))."""
    n = len(sigs)
    b = bucket_size(n)
    pre_ok, block, flags = stage_batch_bls(pubs, msgs, sigs, b)
    expected = np.uint32(EK._host_checksum(block, flags))

    def _round() -> np.ndarray:
        t0 = _time.perf_counter()
        block_dev = jax.device_put(block, chip_device)
        flags_dev = jax.device_put(flags, chip_device)
        jax.block_until_ready((block_dev, flags_dev))
        _linkmodel.link().observe_transfer(
            block.nbytes + flags.nbytes, _time.perf_counter() - t0)
        with KERNEL_DISPATCH_LOCK:
            _header, payload = _verify_device(
                block_dev, flags_dev, expected)
        return np.asarray(payload)

    # same transfer-integrity contract as the single-chip resolver
    # (ed25519_kernel.decode_payload): checksum + mask/echo complement,
    # one fresh-transfer retry, then the shard FAILS so the mesh
    # redispatches it across surviving fault domains — a flipped bit in
    # the link must never become an accepted signature
    for _attempt in range(2):
        payload_np = _round()
        mask = payload_np[:b]
        echo = payload_np[b:2 * b]
        chk_ok = bool(payload_np[2 * b])
        if chk_ok and bool((mask != echo).all()):
            return mask[:n] & pre_ok, pre_ok.copy()
        EK._count_integrity(
            "transfer_checksum_mismatch" if not chk_ok
            else "mask_echo_mismatch")
    raise _dispatch.DeviceOpFailed(
        "bls12381 mesh shard transfer integrity check failed twice")
