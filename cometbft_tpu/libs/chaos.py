"""Device-fault injection registry (the accelerator analog of libs/fail.py).

fail.py kills the PROCESS at indexed call sites to test WAL recovery;
chaos.py breaks the DEVICE at named call sites to test the verify ladder's
degradation paths (ops/dispatch.py supervisor: retry -> breaker -> CPU
fallback -> re-probe). Sites live on the device-dispatch seams:

  ed25519.dispatch   the ed25519 transfer+kernel dispatch worker
  ed25519.fetch      the ed25519 device->host payload fetch
  ed25519.challenge  the on-device challenge derivation (ops/challenge.py
                     derive program): a fault degrades the batch to
                     host-computed k, `corrupt` perturbs one device-derived
                     k word so the recheck plane must flip the lane back —
                     counted, never a verdict change
  dispatch.doublebuf the two-slot in-flight gate (ops/dispatch.DoubleBuffer)
                     acquired before each batch's h2d: a fault degrades the
                     fault domain to serialized single-buffer dispatch until
                     its breaker re-closes — overlap lost, verdicts untouched
  sr25519.dispatch   the sr25519 transfer+kernel dispatch worker
  sr25519.fetch      the sr25519 device->host payload fetch
  pallas.trace       inside the Pallas gate, before the fused-kernel call
  mixed.resolve      the coalesced multi-batch fetch (resolve_batches)
  sched.flush        the verify scheduler's batch-formation seam
                     (sched/scheduler.py _dispatch): an injected fault
                     degrades to per-group fragmented dispatch, never
                     failed verification

plus the per-chip mesh shard sites (parallel/mesh.py — one fault domain
per device, indices 0..MESH_CHAOS_DEVICES-1):

  ed25519.dispatch.devN / sr25519.dispatch.devN
                     one chip's shard dispatch inside the multi-chip
                     verify mesh; killing dev3 evicts exactly that fault
                     domain while the mesh re-shards over the survivors

plus the transport seams (the network plane's deterministic faults; the
probabilistic link faults — latency/drop/dup/reorder/partitions — live in
p2p/netchaos.py):

  net.dial           p2p outbound TCP dial (transport.dial)
  net.accept         p2p inbound connection intake (before upgrade)
  net.handshake      the secret-connection + node-info upgrade

plus the light-client provider seam (light/rpc_provider.py):

  light.fetch        one light_block RPC attempt against a provider; a
                     transient/timeout fault here exercises the capped
                     backoff+jitter retry instead of failing the whole
                     bisection on one flaky witness hop

Arming, via env (`CBFT_CHAOS`) or `arm()`/`arm_spec()`:

  CBFT_CHAOS="ed25519.dispatch=transient:3,pallas.trace=permanent"

`kind[:count]` per site — `count` firings (default: unlimited), then the
site heals. Kinds:

  timeout     raise ChaosTimeout (a hung fetch; the watchdog's TimeoutError)
  transient   raise ChaosTransientError (XlaRuntimeError-style, retryable)
  permanent   raise ChaosPermanentError (Mosaic compile death, not retryable)
  corrupt     leave the call alive but flip lane 0 of the fetched mask
              (exercises the transfer-integrity echo plane)

Every fault is deterministic: no randomness, a plain per-site counter, so a
chaos schedule is a reproducible test fixture. Thread-safe: sites fire from
the kernel transfer pool as well as the event loop.
"""

from __future__ import annotations

import os
import threading

# per-device mesh shard sites ("ed25519.dispatch.dev3"): the multi-chip
# verify mesh (parallel/mesh.py) fires BOTH the plain scheme site and the
# chip-indexed site inside every shard dispatch, so a schedule can kill or
# flap exactly one mesh fault domain while the other chips keep serving —
# the deterministic fixture behind the shrink/grow test matrix
MESH_CHAOS_DEVICES = 8

_MESH_SITES = tuple(
    f"{scheme}.dispatch.dev{i}"
    for scheme in ("ed25519", "sr25519")
    for i in range(MESH_CHAOS_DEVICES)
)

SITES = (
    "ed25519.dispatch",
    "ed25519.fetch",
    "ed25519.challenge",
    "dispatch.doublebuf",
    "sr25519.dispatch",
    "sr25519.fetch",
    "pallas.trace",
    "mixed.resolve",
    "sched.flush",
    "net.dial",
    "net.accept",
    "net.handshake",
    "light.fetch",
) + _MESH_SITES

KINDS = ("timeout", "transient", "permanent", "corrupt")

_ENV = "CBFT_CHAOS"


class ChaosTimeout(Exception):
    """Injected hung-device timeout."""


class ChaosTransientError(Exception):
    """Injected retryable device failure (XlaRuntimeError-style)."""


class ChaosPermanentError(Exception):
    """Injected permanent device failure (Mosaic compile death)."""


class _Site:
    __slots__ = ("kind", "remaining", "fired")

    def __init__(self, kind: str, remaining: int | None):
        self.kind = kind
        self.remaining = remaining  # None = unlimited
        self.fired = 0


_lock = threading.Lock()
_sites: dict[str, _Site] = {}
_env_loaded = False


def parse_spec(spec: str) -> list[tuple[str, str, int | None]]:
    """Parse a schedule string into (site, kind, count) triples, raising
    ValueError on any malformed part — config validation uses this so a
    typo'd schedule fails at boot, not inside a device dispatch."""
    out: list[tuple[str, str, int | None]] = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        site, _, fault = part.partition("=")
        kind, _, count = fault.partition(":")
        site, kind = site.strip(), kind.strip()
        if site not in SITES:
            raise ValueError(f"unknown chaos site {site!r} (sites: {SITES})")
        if kind not in KINDS:
            raise ValueError(f"unknown chaos kind {kind!r} (kinds: {KINDS})")
        if count:
            try:
                n = int(count)
            except ValueError:
                raise ValueError(
                    f"bad chaos count {count!r} in {part!r}") from None
            if n < 0:
                raise ValueError(f"negative chaos count in {part!r}")
        else:
            n = None
        out.append((site, kind, n))
    return out


def _load_env_locked() -> None:
    global _env_loaded
    if _env_loaded:
        return
    _env_loaded = True
    spec = os.environ.get(_ENV, "")
    if not spec:
        return
    try:
        _arm_spec_locked(spec)
    except ValueError as e:
        # a malformed env schedule must fail LOUDLY, not surface later as
        # a phantom "device failure" inside a dispatch worker — but this
        # loads lazily at the first fire(), where raising would be
        # classified as a device fault; log-and-ignore is the safe floor
        from cometbft_tpu.libs import log as _log

        _log.default().error(
            "ignoring malformed CBFT_CHAOS schedule", spec=spec, err=str(e))


def _arm_spec_locked(spec: str) -> None:
    for site, kind, count in parse_spec(spec):
        _arm_locked(site, kind, count)


def _arm_locked(site: str, kind: str, count: int | None) -> None:
    if site not in SITES:
        raise ValueError(f"unknown chaos site {site!r} (sites: {SITES})")
    if kind not in KINDS:
        raise ValueError(f"unknown chaos kind {kind!r} (kinds: {KINDS})")
    _sites[site] = _Site(kind, count)


def arm(site: str, kind: str, count: int | None = None) -> None:
    """Arm `site` to fail `count` times (None = until disarmed)."""
    with _lock:
        _load_env_locked()
        _arm_locked(site, kind, count)


def arm_spec(spec: str) -> None:
    """Arm from a CBFT_CHAOS-syntax schedule string."""
    with _lock:
        _load_env_locked()
        _arm_spec_locked(spec)


def disarm(site: str) -> None:
    with _lock:
        _sites.pop(site, None)


def reset() -> None:
    """Disarm everything and forget the env (tests re-arm per case)."""
    global _env_loaded
    with _lock:
        _sites.clear()
        _env_loaded = True  # a reset() overrides the process env schedule


def armed(site: str) -> str | None:
    """The site's live fault kind, or None."""
    with _lock:
        _load_env_locked()
        s = _sites.get(site)
        return s.kind if s is not None and s.remaining != 0 else None


def fired(site: str) -> int:
    """How many times the site has fired (armed or not: 0)."""
    with _lock:
        s = _sites.get(site)
        return s.fired if s is not None else 0


def _take(site: str, want_corrupt: bool) -> str | None:
    """Consume one firing if armed; returns the kind or None."""
    with _lock:
        _load_env_locked()
        s = _sites.get(site)
        if s is None or s.remaining == 0:
            return None
        if (s.kind == "corrupt") != want_corrupt:
            return None
        if s.remaining is not None:
            s.remaining -= 1
        s.fired += 1
        return s.kind


def fire(site: str) -> None:
    """Call at a dispatch/fetch site: raises the armed fault, if any.
    `corrupt` never raises here — it applies at corrupt_mask()."""
    kind = _take(site, want_corrupt=False)
    if kind is None:
        return
    if kind == "timeout":
        raise ChaosTimeout(f"chaos: injected device hang at {site}")
    if kind == "transient":
        raise ChaosTransientError(
            f"chaos: injected transient device failure at {site} "
            "(RESOURCE_EXHAUSTED)")
    raise ChaosPermanentError(
        f"chaos: injected permanent Mosaic failure at {site}")


def should_corrupt(site: str) -> bool:
    """Consume one `corrupt` firing at a value-perturbation site (e.g. the
    device-derived challenge words at ed25519.challenge, where there is no
    fetched mask to flip — the caller perturbs its own payload). True when
    the site was armed with `corrupt` and a firing was consumed."""
    return _take(site, want_corrupt=True) is not None


def corrupt_mask(site: str, payload):
    """Flip lane 0 of a fetched integrity payload when the site is armed
    with `corrupt` — simulates single-lane link corruption, which the
    mask-echo check must detect (the echo half is left intact)."""
    if _take(site, want_corrupt=True) is None:
        return payload
    out = payload.copy()
    out[0] = ~out[0] if out.dtype != bool else not out[0]
    return out


def snapshot() -> dict:
    """Armed sites + fire counts (surfaced in the crypto-health RPC)."""
    with _lock:
        _load_env_locked()
        return {
            site: {"kind": s.kind, "remaining": s.remaining, "fired": s.fired}
            for site, s in _sites.items()
        }
