"""End-to-end TPU-kernel batch verification vs the ZIP-215 oracle and the
CPU (OpenSSL) path. Runs on the virtual CPU mesh; the same jitted program is
what the driver benches on real TPU."""

import secrets

from cometbft_tpu.crypto import ed25519_math as oracle
from cometbft_tpu.ops import ed25519_kernel as K


def _sign_n(n, msg_prefix=b"vote-"):
    items = []
    for i in range(n):
        seed = secrets.token_bytes(32)
        pub = oracle.public_key_from_seed(seed)
        msg = msg_prefix + i.to_bytes(4, "big") + secrets.token_bytes(16)
        sig = oracle.sign(seed, msg)
        items.append((pub, msg, sig))
    return items


def test_all_valid_batch():
    items = _sign_n(6)
    pubs, msgs, sigs = map(list, zip(*items))
    ok, mask = K.verify_batch(pubs, msgs, sigs)
    assert ok and mask == [True] * 6


def test_mask_pinpoints_bad_signatures():
    items = _sign_n(8)
    pubs, msgs, sigs = map(list, zip(*items))
    # corrupt 2: flip a message, swap a signature
    msgs[2] = msgs[2] + b"x"
    sigs[5] = sigs[4]
    ok, mask = K.verify_batch(pubs, msgs, sigs)
    assert not ok
    want = [True] * 8
    want[2] = want[5] = False
    assert mask == want


def test_structural_rejects():
    items = _sign_n(4)
    pubs, msgs, sigs = map(list, zip(*items))
    sigs[0] = sigs[0][:32] + (oracle.L).to_bytes(32, "little")  # s >= L
    sigs[1] = b"\x00" * 63  # bad length
    pubs[2] = b"\x00" * 31  # bad length
    ok, mask = K.verify_batch(pubs, msgs, sigs)
    assert not ok
    assert mask == [False, False, False, True]


def test_adversarial_encodings_match_oracle():
    """Non-canonical / small-order encodings: ZIP-215's raison d'etre.
    Kernel must agree with the oracle on each, whatever the verdict."""
    items = _sign_n(2)
    pubs, msgs, sigs = map(list, zip(*items))
    # Non-canonical R (y = p+1 encodes identity-ish y=1) and garbage R
    cases = [
        (pubs[0], msgs[0], (oracle.P + 1).to_bytes(32, "little") + sigs[0][32:]),
        (pubs[1], msgs[1], bytes(31) + b"\x12" + sigs[1][32:]),
        # small-order pubkey (identity): sig over anything
        ((1).to_bytes(32, "little"), b"m", sigs[0]),
    ]
    pubs2 = [c[0] for c in cases]
    msgs2 = [c[1] for c in cases]
    sigs2 = [c[2] for c in cases]
    _, mask = K.verify_batch(pubs2, msgs2, sigs2)
    for i in range(len(cases)):
        assert mask[i] == oracle.verify_zip215(pubs2[i], msgs2[i], sigs2[i]), f"case {i}"


def test_pubkey_cache_reuse():
    cache = K.PubKeyCache()
    items = _sign_n(3)
    pubs, msgs, sigs = map(list, zip(*items))
    ok, _ = K.verify_batch(pubs, msgs, sigs, cache=cache)
    assert ok
    n_cached = len(cache._map)
    # same validators verified again (next height): cache must not grow
    ok2, _ = K.verify_batch(pubs, msgs, sigs, cache=cache)
    assert ok2 and len(cache._map) == n_cached


# ------------------------------------------------ transfer integrity


def test_checksum_host_device_agree():
    import numpy as np

    rng = np.random.default_rng(7)
    a = rng.integers(0, 1 << 32, size=(8, 16), dtype=np.uint32)
    b = rng.integers(-(1 << 31), 1 << 31, size=(20, 16), dtype=np.int32)
    import jax.numpy as jnp

    host = K._host_checksum(a, b)
    dev = int(np.asarray(K._device_checksum((jnp.asarray(a), jnp.asarray(b)))))
    assert host == dev
    # order and position sensitivity
    assert K._host_checksum(b, a) != host
    a2 = a.copy()
    a2[3, 5] ^= 1
    assert K._host_checksum(a2, b) != host


def test_happy_path_header_fetch_is_tiny():
    """An all-valid batch must resolve from the 8-byte reduced-fetch
    header alone — the full per-lane mask never crosses the link."""
    import numpy as np

    items = _sign_n(5)
    pubs, msgs, sigs = map(list, zip(*items))
    thunk = K.verify_batch_async(pubs, msgs, sigs)
    acquire, n, pre_ok, ok_a, rows, info, _redo = thunk.device_parts()
    header_dev, _payload_dev = acquire()
    header = np.asarray(header_dev)
    assert header.nbytes == 8 < 128
    assert K.decode_header(header, acquire.expected) == "happy"
    K.reset_fetch_stats()
    assert thunk().tolist() == [True] * 5
    st = K.fetch_stats()
    assert st["happy_fetches"] == 1 and st["full_fetches"] == 0
    assert st["happy_bytes"] == 8


def test_failing_lane_pulls_full_mask():
    """A batch with a bad lane must take the full-payload path and still
    pinpoint the lane."""
    items = _sign_n(5)
    pubs, msgs, sigs = map(list, zip(*items))
    sigs[1] = sigs[2]
    thunk = K.verify_batch_async(pubs, msgs, sigs)
    acquire, *_ = thunk.device_parts()
    import numpy as np

    header_dev, _ = acquire()
    assert K.decode_header(np.asarray(header_dev), acquire.expected) == "full"
    K.reset_fetch_stats()
    assert thunk().tolist() == [True, False, True, True, True]
    st = K.fetch_stats()
    assert st["full_fetches"] == 1 and st["happy_fetches"] == 0


def test_injected_mask_echo_corruption_detected():
    """A flipped bit on the device->host mask fetch must be detected by the
    redundant echo and resolved by the host oracle, not silently accepted."""
    import numpy as np

    from cometbft_tpu.libs import metrics

    items = _sign_n(5)
    pubs, msgs, sigs = map(list, zip(*items))
    thunk = K.verify_batch_async(pubs, msgs, sigs)
    acquire, n, pre_ok, ok_a, rows, info, _redo = thunk.device_parts()
    payload = np.asarray(acquire()[1]).copy()
    payload[2] = not payload[2]  # corrupt one mask lane; echo now disagrees
    mask = K.decode_payload(payload, n, pre_ok, ok_a, rows, info, redo=None)
    assert mask.tolist() == [True] * 5  # host oracle restored the truth
    reg_out = metrics.global_registry().render()
    assert "mask_echo_mismatch" in reg_out


def test_corrupted_header_degrades_to_full_fetch():
    """A mangled header (complement echo disagrees) must never produce a
    verdict — the full echo-protected payload decides instead."""
    import numpy as np

    items = _sign_n(4)
    pubs, msgs, sigs = map(list, zip(*items))
    thunk = K.verify_batch_async(pubs, msgs, sigs)
    acquire, *_ = thunk.device_parts()
    header = np.asarray(acquire()[0]).copy()
    header[0] ^= np.uint32(1 << 7)
    assert K.decode_header(header, acquire.expected) == "echo_corrupt"
    # a header claiming happy for DIFFERENT staged bytes is a checksum
    # mismatch, not happy
    wrong = np.uint32(int(acquire.expected) ^ 0xDEAD ^ int(K.OK_MAGIC))
    fake = np.array([wrong, ~wrong], dtype=np.uint32)
    assert K.decode_header(fake, acquire.expected) == "chk_mismatch"


def test_injected_staging_corruption_retries_then_recovers():
    """A staging-checksum failure retries with a fresh transfer (redo)."""
    import numpy as np

    items = _sign_n(4)
    pubs, msgs, sigs = map(list, zip(*items))
    thunk = K.verify_batch_async(pubs, msgs, sigs)
    acquire, n, pre_ok, ok_a, rows, info, redo = thunk.device_parts()
    bad = np.asarray(acquire()[1]).copy()
    bad[-1] = False  # device says the staged bytes didn't checksum
    calls = {"n": 0}

    def counting_redo():
        calls["n"] += 1
        return redo()

    mask = K.decode_payload(bad, n, pre_ok, ok_a, rows, info, redo=counting_redo)
    assert calls["n"] == 1  # one fresh transfer+dispatch
    assert mask.tolist() == [True] * 4


def test_corrupted_coordinate_upload_refused(monkeypatch):
    """A pubkey-table upload that fails its checksum twice must raise, not
    poison the device cache."""
    import pytest

    items = _sign_n(3)
    pubs = [p for p, _, _ in items]
    cache = K.PubKeyCache()
    monkeypatch.setattr(K, "_device_checksum", lambda dev: __import__("numpy").uint32(1))
    with pytest.raises(RuntimeError, match="corrupted twice"):
        cache.stage(pubs, K.bucket_size(len(pubs)))
    assert not cache._dev  # nothing cached
