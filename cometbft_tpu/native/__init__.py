"""Native (C) runtime components, built on demand with the system cc.

The reference leans on native code for its byte-crunching hot paths (Go
with assembly fast paths in curve25519-voi, merlin in Rust under
schnorrkel). This package holds the framework's equivalents: small C
libraries compiled once into the package directory and loaded via ctypes,
each with a pure-Python fallback so a missing toolchain degrades to slow,
never to broken.

Currently: strobe.c — the STROBE-128 duplex behind Merlin transcripts
(sr25519 signing/verification challenges) — and hashvec.c — the 8-lane
SIMD batch SHA-512 / Keccak-f[1600] / Barrett-mod-L cores behind the
staging fast path (ops/hashvec.py).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import tempfile

_DIR = os.path.dirname(os.path.abspath(__file__))
_loaded: dict[str, ctypes.CDLL] = {}
# lib -> "built" (cc ran in this process) | "reused" (a fresh-enough .so
# was already in the package directory) | "failed: <why>" — the smoke
# prints it, so a run that leaned on a stray .so or lost its cc says so
_status: dict[str, str] = {}


def status() -> dict[str, str]:
    return dict(_status)


def load(name: str, cflags_ladder: tuple = (("-O2",),)) -> ctypes.CDLL | None:
    """Compile (if stale) and load lib `name` (from {name}.c). Returns None
    when no working C toolchain is available — callers keep their Python
    fallback.

    cflags_ladder: candidate optimization-flag tuples tried in order (the
    SIMD hash cores pass an ISA ladder like -mavx512f > -mavx2 > none and
    degrade gracefully on a compiler too old for the wider flags). A
    non-default ladder is part of the artifact's cache name: the ladder is
    derived from the RUNNING host's /proc/cpuinfo, so a .so baked into an
    image on a wider-ISA build host is never loaded on a narrower machine
    (which would SIGILL instead of degrading) — the narrower host sees a
    different name and rebuilds, or falls back to pure Python."""
    if name in _loaded:
        return _loaded[name]
    if _status.get(name, "").startswith("failed"):
        return None
    src = os.path.join(_DIR, f"{name}.c")
    suffix = ""
    if cflags_ladder != (("-O2",),):
        import hashlib

        suffix = "." + hashlib.sha256(
            repr(cflags_ladder).encode()).hexdigest()[:8]
    so = os.path.join(_DIR, f"_{name}{suffix}.so")
    try:
        _status[name] = "reused"
        if (not os.path.exists(so)
                or os.path.getmtime(so) < os.path.getmtime(src)):
            _status[name] = "built"
            fd, tmp = tempfile.mkstemp(suffix=".so", dir=_DIR)
            os.close(fd)
            try:
                built = None
                for flags in cflags_ladder:
                    try:
                        subprocess.run(
                            ["cc", *flags, "-shared", "-fPIC", "-o", tmp, src],
                            check=True, capture_output=True, timeout=120)
                        built = flags
                        break
                    except subprocess.CalledProcessError:
                        continue
                if built is None:
                    raise RuntimeError(f"no cflags candidate built {name}")
                os.replace(tmp, so)  # atomic vs concurrent builders
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
        lib = ctypes.CDLL(so)
    except Exception as exc:  # noqa: BLE001 - no cc / sandboxed fs: fall back
        _status[name] = f"failed: {type(exc).__name__}: {exc}"
        return None
    _loaded[name] = lib
    return lib
