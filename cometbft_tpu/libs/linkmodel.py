"""Live link estimation: EWMA bandwidth/RTT models for the wires the node
actually runs on.

Two links bound what this framework can do, and both are measured live,
never assumed:

  the device link     h2d staging transfers and d2h result fetches
                      cross the host<->accelerator link. The kernels
                      report here the transfers whose wall time is wire
                      time and that the host awaits anyway: the payload
                      pull after a batch's header has been read
                      (ops/ed25519_kernel.py thunk and resolve_batches),
                      key-table delta rows and coordinate-table uploads
                      (ops/residency.py, PubKeyCache), the block uploads
                      of sr25519, BLS and the mesh's shards. An ed25519
                      batch's own upload rides its first program
                      un-awaited and is NOT sampled: a caller does not
                      pay a round trip for a gauge. So `link()`
                      converges with table churn and unhappy batches,
                      not with every batch — crypto_health exposes it
                      and the scheduler's health view reads it; nothing
                      routes or plans by it.
  peer links          MConnection ping RTTs and flowrate throughput feed
                      per-peer models (owned by the MConnection) plus the
                      process-wide `p2p()` aggregate that net_telemetry
                      reports.

Estimation model (shared by both): a transfer of n bytes costs
rtt_share + n/bandwidth. Small transfers (below `rtt_bytes`) are
latency-dominated and update the RTT estimate; large ones (above
`bw_bytes`) update bandwidth after subtracting the current RTT estimate
from the measured wall time. Both estimates are exponentially weighted
moving averages, so the model tracks a link whose quality drifts (a
contended link, a healing partition) instead of averaging history
forever. `observe_rtt()` feeds pure round-trip measurements (p2p pings,
header-only fetches) without a byte count.

Everything is thread-safe and allocation-free on the observe path — these
sites sit inside verify batches and send routines.
"""

from __future__ import annotations

import threading


class LinkModel:
    """EWMA bandwidth/RTT estimator for one link."""

    def __init__(self, alpha: float = 0.2, rtt_bytes: int = 4096,
                 bw_bytes: int = 65536):
        if not 0.0 < alpha <= 1.0:
            raise ValueError("alpha must be in (0, 1]")
        self.alpha = alpha
        self.rtt_bytes = rtt_bytes
        self.bw_bytes = bw_bytes
        self._lock = threading.Lock()
        self._bw = 0.0  # bytes/sec EWMA (0 = no estimate yet)
        self._rtt = 0.0  # seconds EWMA (0 = no estimate yet)
        self._bw_samples = 0
        self._rtt_samples = 0
        self._bytes_total = 0
        self._seconds_total = 0.0

    # ---------------------------------------------------------- observing

    def observe_rtt(self, seconds: float) -> None:
        """A pure round-trip measurement (ping/pong, header-only fetch)."""
        if seconds <= 0:
            return
        with self._lock:
            self._rtt_samples += 1
            self._rtt = (seconds if self._rtt == 0.0
                         else self._rtt + self.alpha * (seconds - self._rtt))

    def observe_transfer(self, nbytes: int, seconds: float) -> None:
        """A measured transfer of nbytes taking seconds of wall time.
        Small transfers refine RTT; large ones refine bandwidth (with the
        RTT share subtracted, so a latency-heavy link doesn't read as
        slow bandwidth)."""
        if seconds <= 0 or nbytes < 0:
            return
        with self._lock:
            self._bytes_total += nbytes
            self._seconds_total += seconds
            if nbytes <= self.rtt_bytes:
                self._rtt_samples += 1
                self._rtt = (seconds if self._rtt == 0.0
                             else self._rtt + self.alpha * (seconds - self._rtt))
                return
            if nbytes < self.bw_bytes:
                return  # mid-size: ambiguous between rtt and bandwidth
            wire = seconds - self._rtt
            if wire <= 0:
                # faster than the RTT floor says is possible: the link got
                # quicker — bleed the RTT estimate down and use raw time
                self._rtt *= 1.0 - self.alpha
                wire = seconds
            sample = nbytes / wire
            self._bw_samples += 1
            self._bw = (sample if self._bw == 0.0
                        else self._bw + self.alpha * (sample - self._bw))

    # ------------------------------------------------------------ reading

    def bandwidth_bps(self) -> float:
        """Estimated link bandwidth in bytes/sec (0.0 = no estimate)."""
        with self._lock:
            return self._bw

    def rtt_seconds(self) -> float:
        """Estimated round-trip time in seconds (0.0 = no estimate)."""
        with self._lock:
            return self._rtt

    def transfer_seconds(self, nbytes: int) -> float | None:
        """Predicted wall time for an nbytes transfer (None until both
        estimates exist) — the scheduler/reduced-send planning primitive."""
        with self._lock:
            if self._bw == 0.0:
                return None
            return self._rtt + nbytes / self._bw

    def converged(self, min_samples: int = 3) -> bool:
        with self._lock:
            return self._bw_samples >= min_samples and self._rtt_samples >= 1

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "bandwidth_bytes_per_s": round(self._bw, 1),
                "bandwidth_mb_per_s": round(self._bw / 1e6, 3),
                "rtt_ms": round(self._rtt * 1e3, 3),
                "bandwidth_samples": self._bw_samples,
                "rtt_samples": self._rtt_samples,
                "bytes_observed": self._bytes_total,
                "seconds_observed": round(self._seconds_total, 3),
                "converged": (self._bw_samples >= 3
                              and self._rtt_samples >= 1),
            }

    def reset(self) -> None:
        with self._lock:
            self._bw = self._rtt = 0.0
            self._bw_samples = self._rtt_samples = 0
            self._bytes_total = 0
            self._seconds_total = 0.0


class SkewEstimator:
    """Per-peer wall-clock offset model (peer_clock - local_clock, ms).

    Two sample sources, kept as separate EWMAs so they cross-check each
    other:

      ping      the pong packet carries the responder's wall clock
                (p2p/conn/connection.py); with the send stamped at wall
                t0 and a measured RTT, ``offset = remote_wall -
                (t0 + rtt/2)``. Exact up to path asymmetry, so the
                per-sample error is bounded by rtt/2 plus jitter.
      vote      a received vote's signing timestamp against the local
                arrival clock, credited rtt/2 of flight time. Network
                delay is at least rtt/2, so vote samples are a LOWER
                bound on the true offset — they serve as the
                cross-check, not the estimate.

    ``offset_ms()`` prefers the ping EWMA and falls back to votes.  The
    documented error bound (asserted by tests/test_skew.py) is::

        |estimate - true| <= max(2 ms, rtt/2 * 1e3 + 3 * dev_ms)

    after ~50 samples, where dev_ms is the EWMA of absolute residuals —
    i.e. the estimator converges to within half the round trip plus
    three deviations of the observed jitter.  Thread-safe: samples
    arrive from per-connection recv tasks, reads from the RPC thread.
    """

    def __init__(self, alpha: float = 0.1):
        if not 0.0 < alpha <= 1.0:
            raise ValueError("alpha must be in (0, 1]")
        self.alpha = alpha
        self._lock = threading.Lock()
        self._peers: dict[str, dict] = {}

    def _peer(self, peer: str) -> dict:
        p = self._peers.get(peer)
        if p is None:
            p = {"ping_off": None, "ping_dev": 0.0, "ping_n": 0,
                 "vote_off": None, "vote_n": 0, "rtt_s": 0.0}
            self._peers[peer] = p
        return p

    def observe_ping(self, peer: str, remote_wall_ns: int,
                     midpoint_wall_ns: int, rtt_s: float) -> None:
        """A pong that carried the responder's wall clock; midpoint is
        the sender's wall clock at t0 + rtt/2."""
        sample = (remote_wall_ns - midpoint_wall_ns) / 1e6
        with self._lock:
            p = self._peer(peer)
            p["ping_n"] += 1
            if rtt_s > 0:
                p["rtt_s"] = (rtt_s if p["rtt_s"] == 0.0
                              else p["rtt_s"] + self.alpha * (rtt_s - p["rtt_s"]))
            if p["ping_off"] is None:
                p["ping_off"] = sample
                return
            resid = abs(sample - p["ping_off"])
            p["ping_dev"] += self.alpha * (resid - p["ping_dev"])
            p["ping_off"] += self.alpha * (sample - p["ping_off"])

    def observe_vote(self, peer: str, vote_wall_ns: int,
                     arrival_wall_ns: int, rtt_s: float = 0.0) -> None:
        """Vote-timestamp delta cross-check (lower bound on the offset:
        gossip delay exceeds rtt/2, pulling the sample down)."""
        sample = (vote_wall_ns - arrival_wall_ns) / 1e6 + rtt_s * 500.0
        with self._lock:
            p = self._peer(peer)
            p["vote_n"] += 1
            if p["vote_off"] is None:
                p["vote_off"] = sample
            else:
                p["vote_off"] += self.alpha * (sample - p["vote_off"])

    def offset_ms(self, peer: str) -> float | None:
        """Best offset estimate for peer (peer clock minus local), ms."""
        with self._lock:
            p = self._peers.get(peer)
            if p is None:
                return None
            if p["ping_off"] is not None:
                return p["ping_off"]
            return p["vote_off"]

    def error_bound_ms(self, peer: str) -> float | None:
        with self._lock:
            p = self._peers.get(peer)
            if p is None or p["ping_off"] is None:
                return None
            return max(2.0, p["rtt_s"] * 500.0 + 3.0 * p["ping_dev"])

    def snapshot(self) -> dict:
        """Per-peer skew table for consensus_timeline / net_telemetry."""
        out = {}
        with self._lock:
            for peer, p in self._peers.items():
                off = p["ping_off"] if p["ping_off"] is not None else p["vote_off"]
                ent = {
                    "offset_ms": None if off is None else round(off, 3),
                    "source": ("ping" if p["ping_off"] is not None
                               else "vote" if p["vote_off"] is not None
                               else "none"),
                    "ping_samples": p["ping_n"],
                    "vote_samples": p["vote_n"],
                    "rtt_ms": round(p["rtt_s"] * 1e3, 3),
                }
                if p["ping_off"] is not None:
                    ent["error_bound_ms"] = round(
                        max(2.0, p["rtt_s"] * 500.0 + 3.0 * p["ping_dev"]), 3)
                    ent["dev_ms"] = round(p["ping_dev"], 3)
                if p["ping_off"] is not None and p["vote_off"] is not None:
                    # votes lower-bound the offset; a vote EWMA far ABOVE
                    # the ping estimate means one of the clocks lies
                    ent["cross_check_ms"] = round(
                        p["vote_off"] - p["ping_off"], 3)
                out[peer] = ent
        return out

    def reset(self) -> None:
        with self._lock:
            self._peers.clear()


# ---------------------------------------------------------------------------
# process-global links. The device link is a process-global resource
# (like the device supervisors); the p2p aggregate pools every peer's ping
# RTTs and flow rates into one "how is my network" view for net_telemetry.
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_link: LinkModel | None = None
_p2p: LinkModel | None = None
_skew: SkewEstimator | None = None


def link() -> LinkModel:
    """The host<->device link (fed by the kernels' measured h2d/d2h
    transfers — ops/ed25519_kernel.py, ops/sr25519_kernel.py)."""
    global _link
    if _link is None:
        with _lock:
            if _link is None:
                # thresholds sized to the kernels' real transfer mix: the
                # 4 B/lane index uploads (<=2 KB at small buckets) probe
                # RTT; staged-word uploads start at 24 KB for a 256-lane
                # flush, so 16 KB+ counts toward bandwidth
                _link = LinkModel(alpha=0.2, rtt_bytes=2048,
                                    bw_bytes=16384)
    return _link


def p2p() -> LinkModel:
    """The aggregate peer-link view (fed by MConnection ping RTTs)."""
    global _p2p
    if _p2p is None:
        with _lock:
            if _p2p is None:
                _p2p = LinkModel(alpha=0.1, rtt_bytes=4096, bw_bytes=16384)
    return _p2p


def skew() -> SkewEstimator:
    """The per-peer clock-skew table (fed by MConnection pong wall stamps
    and the consensus reactor's vote-timestamp deltas; read by the
    heightline aggregator to project node clocks onto one fleet axis)."""
    global _skew
    if _skew is None:
        with _lock:
            if _skew is None:
                _skew = SkewEstimator(alpha=0.1)
    return _skew


def reset() -> None:
    """Forget the process links and the skew table (tests)."""
    global _link, _p2p, _skew
    with _lock:
        _link = None
        _p2p = None
        _skew = None
