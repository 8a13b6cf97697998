"""Wire-plane telemetry tests (ISSUE 8): per-peer/per-channel network
accounting on MConnection, the bounded-cardinality peer metric labels,
the live link model (incl. convergence against a netchaos-injected link
profile), and the net_telemetry RPC route schema.
"""

from __future__ import annotations

import asyncio
import time

import pytest

from cometbft_tpu.libs import linkmodel
from cometbft_tpu.libs import metrics as cmtmetrics
from cometbft_tpu.libs.flowrate import Monitor
from cometbft_tpu.p2p import netchaos
from cometbft_tpu.p2p.conn.connection import (
    ChannelDescriptor,
    MConnConfig,
    MConnection,
)


@pytest.fixture(autouse=True)
def _clean_links():
    linkmodel.reset()
    netchaos.reset()
    yield
    linkmodel.reset()
    netchaos.reset()


# --------------------------------------------------------------- harness


class _PipeEnd:
    """One direction-aware end of an in-memory duplex pipe with byte
    counters at the conn seam — the 'actual socket traffic' oracle the
    accounting is asserted against."""

    def __init__(self):
        self._buf = bytearray()
        self._data = asyncio.Event()
        self.peer: "_PipeEnd" = None
        self.bytes_written = 0
        self.bytes_read = 0
        self.closed = False

    async def write(self, data: bytes) -> None:
        self.bytes_written += len(data)
        self.peer._buf += data
        self.peer._data.set()

    async def readexactly(self, n: int) -> bytes:
        while len(self._buf) < n:
            if self.closed:
                raise ConnectionResetError("pipe closed")
            self._data.clear()
            await self._data.wait()
        out = bytes(self._buf[:n])
        del self._buf[:n]
        self.bytes_read += len(out)
        return out

    def close(self) -> None:
        self.closed = True
        self._data.set()


def _pipe_pair() -> tuple[_PipeEnd, _PipeEnd]:
    a, b = _PipeEnd(), _PipeEnd()
    a.peer, b.peer = b, a
    return a, b


async def _mconn_pair(config: MConnConfig | None = None, metrics=None,
                      labels=("pa", "pb")):
    """Two MConnections talking over the in-memory pipe, channels 0x01
    (hi prio) and 0x20."""
    chans = [ChannelDescriptor(id=0x01, priority=5),
             ChannelDescriptor(id=0x20, priority=1)]
    a_conn, b_conn = _pipe_pair()
    got_a: list = []
    got_b: list = []
    ev_a, ev_b = asyncio.Event(), asyncio.Event()

    async def recv_a(cid, msg):
        got_a.append((cid, msg))
        ev_a.set()

    async def recv_b(cid, msg):
        got_b.append((cid, msg))
        ev_b.set()

    async def err(e):
        pass

    cfg = config or MConnConfig(send_rate=0, recv_rate=0, ping_interval=30.0)
    ma = MConnection(a_conn, chans, recv_a, err, config=cfg,
                     metrics=metrics, peer_label=labels[0])
    mb = MConnection(b_conn, chans, recv_b, err, config=cfg,
                     metrics=metrics, peer_label=labels[1])
    ma.start()
    mb.start()
    return ma, mb, a_conn, b_conn, (got_a, ev_a), (got_b, ev_b)


async def _drain(cond, timeout=5.0):
    async def poll():
        while not cond():
            await asyncio.sleep(0.01)

    await asyncio.wait_for(poll(), timeout)


# ------------------------------------------------- per-channel accounting


class TestMConnAccounting:
    def test_per_channel_counters_match_seam_traffic(self):
        """Send a known message mix both directions; per-channel counters
        must be message-exact, and byte totals must sit within 5% of the
        bytes actually crossing the conn seam (the acceptance bound)."""
        async def main():
            ma, mb, a_conn, b_conn, _, (got_b, _) = await _mconn_pair()
            try:
                msgs_01 = [b"vote-%d" % i * 20 for i in range(10)]
                msgs_20 = [b"tx-%d" % i * 500 for i in range(5)]  # multi-packet
                for m in msgs_01:
                    assert await ma.send(0x01, m)
                for m in msgs_20:
                    assert await ma.send(0x20, m)
                await mb.send(0x01, b"reply")
                await _drain(lambda: len(got_b) == len(msgs_01) + len(msgs_20))
                st_a = ma.status()
                st_b = mb.status()

                # message counts are exact, per channel, both directions
                assert st_a["channels"]["0x1"]["send_msgs"] == len(msgs_01)
                assert st_a["channels"]["0x20"]["send_msgs"] == len(msgs_20)
                assert st_b["channels"]["0x1"]["recv_msgs"] == len(msgs_01)
                assert st_b["channels"]["0x20"]["recv_msgs"] == len(msgs_20)
                assert st_a["channels"]["0x1"]["recv_msgs"] == 1
                # a >1024-byte message fragments into multiple packets
                assert (st_a["channels"]["0x20"]["send_packets"]
                        > len(msgs_20))

                # monitor totals == bytes at the conn seam, EXACTLY, both
                # directions (recv counts the varint length prefix too,
                # matching the sender's encoded-packet accounting) — well
                # inside the 5% acceptance bound
                assert st_a["send"]["bytes_total"] == a_conn.bytes_written
                assert st_b["recv"]["bytes_total"] == b_conn.bytes_read
                # per-channel send bytes sum to the monitor total (no
                # pings were exchanged in this window)
                ch_sum = sum(c["send_bytes"]
                             for c in st_a["channels"].values())
                assert ch_sum == st_a["send"]["bytes_total"]
            finally:
                await ma.stop()
                await mb.stop()

        asyncio.run(main())

    def test_accounting_without_throttling(self):
        """Satellite: rate_limit=0 must keep the monitors measuring (never
        throttling) and status() must carry bytes_total/avg rate."""
        m = Monitor(rate_limit=0)
        assert m.update(10_000) == 0.0
        assert m.update(10_000) == 0.0
        assert m.bytes_total == 20_000
        st = m.stats()
        assert st["bytes_total"] == 20_000
        assert st["updates_total"] == 2
        assert st["rate_limit"] == 0
        assert st["lifetime_rate_bytes_per_s"] > 0

        async def main():
            cfg = MConnConfig(send_rate=0, recv_rate=0, ping_interval=30.0)
            ma, mb, _, _, _, (got_b, ev_b) = await _mconn_pair(cfg)
            try:
                await ma.send(0x01, b"unthrottled")
                await asyncio.wait_for(ev_b.wait(), 5)
                st = ma.status()
                assert st["send"]["bytes_total"] > 0
                assert "rate_bytes_per_s" in st["send"]
                assert mb.status()["recv"]["bytes_total"] > 0
            finally:
                await ma.stop()
                await mb.stop()

        asyncio.run(main())

    def test_queue_high_water_and_stall(self):
        async def main():
            ma, mb, _, _, _, (got_b, _) = await _mconn_pair()
            try:
                for i in range(8):
                    assert await ma.send(0x01, b"x" * 64)
                await _drain(lambda: len(got_b) == 8)
                st = ma.status()
                assert st["channels"]["0x1"]["queue_hwm"] >= 1
                assert st["send_stall_seconds"] >= 0
                assert set(st["send_stall_split_seconds"]) == {
                    "rate_limit", "socket_write"}
            finally:
                await ma.stop()
                await mb.stop()

        asyncio.run(main())

    def test_ping_rtt_ewma_feeds_p2p_link(self):
        async def main():
            cfg = MConnConfig(send_rate=0, recv_rate=0,
                              ping_interval=0.05, pong_timeout=5.0)
            ma, mb, _, _, _, _ = await _mconn_pair(cfg)
            try:
                await _drain(lambda: ma.status()["ping_samples"] >= 2,
                             timeout=5.0)
                st = ma.status()
                assert st["ping_rtt_ms"] > 0
                assert st["ping_rtt_last_ms"] > 0
                # the process-wide p2p link aggregate saw the samples
                assert linkmodel.p2p().rtt_seconds() > 0
            finally:
                await ma.stop()
                await mb.stop()

        asyncio.run(main())


# ------------------------------------------------ peer label cardinality


class TestPeerLabelCardinality:
    def test_cap_folds_overflow_into_other(self):
        reg = cmtmetrics.Registry()
        m = cmtmetrics.P2PMetrics(reg, peer_cap=3)
        ids = [f"{i:02d}" * 20 for i in range(10)]
        labels = [m.peer_label(i) for i in ids]
        own = [lb for lb in labels if lb != "other"]
        assert len(own) == 3
        assert labels[3:] == ["other"] * 7
        # stable: the same peer always maps to the same label
        assert [m.peer_label(i) for i in ids] == labels
        assert m.peer_label("") == "other"

    def test_exposition_series_bounded(self):
        reg = cmtmetrics.Registry()
        m = cmtmetrics.P2PMetrics(reg, peer_cap=2)
        for i in range(50):
            label = m.peer_label(f"{i:02d}" * 20)
            m.record_conn_traffic(label, {0x01: (100, 1)}, send=True)
        text = reg.render()
        series = [ln for ln in text.splitlines()
                  if ln.startswith("cometbft_p2p_peer_send_bytes_total{")]
        # 2 capped peers + the "other" bucket, one channel each
        assert len(series) == 3, series
        other = [ln for ln in series if 'peer="other"' in ln]
        assert len(other) == 1
        assert float(other[0].rsplit(" ", 1)[1]) == 48 * 100

    def test_churn_storm_past_cap_stays_bounded(self):
        """ISSUE 12 satellite: a churn storm cycling hundreds of peers
        through a capped ledger must not grow the label maps OR the
        exposition without bound — late peers fold into "other" even as
        slots keep turning over."""
        reg = cmtmetrics.Registry()
        m = cmtmetrics.P2PMetrics(reg, peer_cap=4)
        for i in range(300):  # connect -> traffic -> disconnect, rolling
            nid = f"{i:02d}"[:2] * 20
            label = m.peer_label(nid)
            m.record_conn_traffic(label, {0x22: (10, 1)}, send=True)
            m.release_peer(nid)
        stats = m.peer_label_stats()
        assert stats["owners"] == 0
        assert stats["released"] <= 4
        assert stats["minted"] <= stats["mint_cap"] == 8
        series = [ln for ln in reg.render().splitlines()
                  if ln.startswith("cometbft_p2p_peer_send_bytes_total{")]
        # at most mint_cap labeled series + one "other" bucket
        assert len(series) <= 8 + 1
        other = [ln for ln in series if 'peer="other"' in ln]
        assert other and float(other[0].rsplit(" ", 1)[1]) > 0

    def test_released_label_reclaimed_after_ban_expiry(self):
        """A banned peer's slot frees for others; when the ban expires
        and it redials, it gets its ORIGINAL label back — its series
        continues instead of minting a new one."""
        reg = cmtmetrics.Registry()
        m = cmtmetrics.P2PMetrics(reg, peer_cap=2)
        a, b, c = ("aa" * 20, "bb" * 20, "cc" * 20)
        la = m.peer_label(a)
        lb = m.peer_label(b)
        assert m.peer_label(c) == "other"  # cap full
        m.release_peer(a)  # banned
        # the freed slot admits the next NEW peer (mint cap permitting)
        lc = m.peer_label(c)
        assert lc == c[:10]
        # ban expired: a returns and re-claims its original label even
        # though owners are momentarily past the live cap
        assert m.peer_label(a) == la
        assert m.peer_label(b) == lb
        stats = m.peer_label_stats()
        assert stats["minted"] == 3 <= stats["mint_cap"]

    def test_mint_cap_holds_under_release_churn(self):
        """Past the mint cap, freed slots must NOT mint new labels —
        persisted series of released peers already occupy the
        exposition budget."""
        reg = cmtmetrics.Registry()
        m = cmtmetrics.P2PMetrics(reg, peer_cap=2)
        ids = [f"{i}{i}" * 20 for i in range(10)]
        minted = 0
        for nid in ids:
            if m.peer_label(nid) != "other":
                minted += 1
            m.release_peer(nid)
        assert minted == m.mint_cap == 4
        # everything after folds into other, forever
        assert m.peer_label("ff" * 20) == "other"
        # but an OLD released peer still re-claims its own label
        assert m.peer_label(ids[3]) == ids[3][:10]

    def test_switch_releases_label_on_peer_stop(self):
        """The Switch frees the slot when a peer stops: stop a live
        peer, its slot turns over."""
        from test_p2p import make_switch_pair, wait_until

        async def main():
            s1, s2, _, _, addr2 = await make_switch_pair()
            reg = cmtmetrics.Registry()
            s1.metrics = cmtmetrics.P2PMetrics(reg, peer_cap=4)
            try:
                await s1.dial_peers_async([addr2])
                await wait_until(lambda: s1.n_peers() and s2.n_peers())
                peer = next(iter(s1.peers.values()))
                s1.metrics.peer_label(peer.id)
                assert s1.metrics.peer_label_stats()["owners"] == 1
                await s1.stop_peer_for_error(peer, "test stop", score=0.0)
                st = s1.metrics.peer_label_stats()
                assert st["owners"] == 0 and st["released"] == 1
            finally:
                await s1.stop()
                await s2.stop()

        asyncio.run(main())

    def test_record_conn_traffic_directions(self):
        reg = cmtmetrics.Registry()
        m = cmtmetrics.P2PMetrics(reg, peer_cap=4)
        m.record_conn_traffic("p1", {0x01: (500, 2)}, send=True)
        m.record_conn_traffic("p1", {0x01: (300, 1)}, send=False)
        assert m.peer_send_bytes.value("p1", "0x1") == 500
        assert m.peer_receive_bytes.value("p1", "0x1") == 300
        assert m.peer_send_msgs.value("p1", "0x1") == 2
        assert m.peer_receive_msgs.value("p1", "0x1") == 1
        # the per-channel (unlabeled-by-peer) rollups advance too
        assert m.message_send_bytes.value("0x1") == 500
        assert m.message_receive_bytes.value("0x1") == 300


# ---------------------------------------------------------- link model


class TestLinkModel:
    def test_converges_on_synthetic_link(self):
        """Pure-unit convergence: a 2 MB/s / 50 ms link described by its
        own cost model must be recovered within 25%."""
        bw, rtt = 2_000_000.0, 0.050
        lm = linkmodel.LinkModel(alpha=0.3)
        for _ in range(12):
            lm.observe_transfer(256, rtt + 256 / bw)          # rtt probe
            lm.observe_transfer(500_000, rtt + 500_000 / bw)  # bw sample
        assert lm.converged()
        assert abs(lm.bandwidth_bps() - bw) / bw < 0.25, lm.snapshot()
        assert abs(lm.rtt_seconds() - rtt) / rtt < 0.25, lm.snapshot()
        est = lm.transfer_seconds(1_000_000)
        assert est is not None and abs(est - (rtt + 0.5)) < 0.2

    def test_converges_against_netchaos_link(self):
        """Acceptance: the estimator fed by transfers through a
        netchaos-shaped wire (bandwidth cap + latency) must land within
        25% of the injected profile."""
        inj_bw, inj_lat = 400_000, 0.02
        netchaos.arm(netchaos.NetChaosConfig(bandwidth=inj_bw,
                                             latency=inj_lat))

        class _Sink:
            async def write(self, data):
                pass

            def close(self):
                pass

        conn = netchaos.wrap(_Sink(), "nodeA", "nodeB")
        lm = linkmodel.LinkModel(alpha=0.3)

        async def main():
            for _ in range(4):
                t0 = time.perf_counter()
                await conn.write(b"\x00" * 256)  # latency-dominated
                lm.observe_transfer(256, time.perf_counter() - t0)
                t0 = time.perf_counter()
                await conn.write(b"\x00" * 65536)  # bandwidth-dominated
                lm.observe_transfer(65536, time.perf_counter() - t0)

        asyncio.run(main())
        assert lm.converged()
        got_bw, got_rtt = lm.bandwidth_bps(), lm.rtt_seconds()
        assert abs(got_bw - inj_bw) / inj_bw < 0.25, lm.snapshot()
        assert abs(got_rtt - inj_lat) / inj_lat < 0.25, lm.snapshot()

    def test_tracks_drifting_link(self):
        lm = linkmodel.LinkModel(alpha=0.3)
        for _ in range(10):
            lm.observe_transfer(500_000, 0.01 + 0.25)  # 2 MB/s
        for _ in range(20):
            lm.observe_transfer(500_000, 0.01 + 1.0)   # drops to 0.5 MB/s
        assert abs(lm.bandwidth_bps() - 500_000) / 500_000 < 0.25

    def test_link_exposed_in_crypto_health(self):
        from cometbft_tpu.ops import dispatch

        linkmodel.link().observe_transfer(1_000_000, 0.1)
        linkmodel.link().observe_rtt(0.05)
        snap = dispatch.health_snapshot()
        assert "link" in snap
        assert snap["link"]["bytes_observed"] == 1_000_000
        assert snap["link"]["rtt_ms"] == 50.0
        assert "converged" in snap["link"]
        # the scheduler's health view reads the same link live
        from cometbft_tpu import sched

        link = sched.get().health()["link"]
        assert link["rtt_ms"] == 50.0


# ------------------------------------------------- net_telemetry route


class _NodeShim:
    """The minimal node surface Environment.net_telemetry reads."""

    def __init__(self, switch, node_key, moniker="shim", laddr="x:1"):
        self.switch = switch
        self.node_key = node_key

        class _Info:
            pass

        self.node_info = _Info()
        self.node_info.moniker = moniker
        self.node_info.listen_addr = laddr


class TestNetTelemetryRoute:
    def test_route_registered_and_documented(self):
        from cometbft_tpu.rpc.core import Environment

        env = Environment.__new__(Environment)
        env.node = None
        assert "net_telemetry" in Environment._routes_table(env)
        import os

        spec = open(os.path.join(os.path.dirname(__file__), "..",
                                 "cometbft_tpu", "rpc",
                                 "openapi.yaml")).read()
        assert "/net_telemetry:" in spec

    def test_schema_over_live_switch_pair(self):
        """Two switches over real TCP; the route must report per-peer
        per-channel accounting that matches what crossed the wire, plus
        the link-model and chaos sections."""
        from test_p2p import make_switch_pair, wait_until

        from cometbft_tpu.rpc.core import Environment

        async def main():
            s1, s2, r1, r2, addr2 = await make_switch_pair()
            reg = cmtmetrics.Registry()
            s1.metrics = cmtmetrics.P2PMetrics(reg, peer_cap=8)
            try:
                await s1.dial_peers_async([addr2])
                await wait_until(lambda: s1.n_peers() and s2.n_peers())
                peer = next(iter(s1.peers.values()))
                payload = b"m" * 5000
                assert await peer.send(0x01, payload)
                await asyncio.wait_for(r2.got_msg.wait(), 5)

                env = Environment(_NodeShim(s1, s1.transport.node_key))
                tel = await env.net_telemetry({})
                assert tel["node_id"] == s1.transport.node_key.id()
                assert tel["n_peers"] == 1
                p = tel["peers"][0]
                assert p["id"] == peer.id
                ch = p["connection_status"]["channels"]["0x1"]
                assert ch["send_msgs"] == 1
                assert ch["send_bytes"] > len(payload)  # + framing
                assert ch["send_bytes"] < len(payload) * 1.05
                # rollups + link models + chaos snapshot present
                assert tel["channels"]["0x1"]["send_bytes"] == ch["send_bytes"]
                assert tel["totals"]["send_bytes"] >= ch["send_bytes"]
                for key in ("link", "p2p_link", "net_chaos",
                            "peer_scores"):
                    assert key in tel
                assert "bandwidth_bytes_per_s" in tel["link"]
            finally:
                await s1.stop()
                await s2.stop()

        asyncio.run(main())

    def test_accounting_vs_seam_on_4val_consensus_net(self):
        """Acceptance: on a 4-val in-proc TCP net committing real heights,
        every node's net_telemetry byte totals must sit within 5% of the
        traffic measured at the conn seam (netchaos.wrap monkeypatched to
        count)."""
        from tcp_net_harness import make_tcp_net

        counters: list = []
        orig_wrap = netchaos.wrap

        def counting_wrap(conn, local_id, remote_id):
            wrapped = orig_wrap(conn, local_id, remote_id)

            class _Counting:
                def __init__(self):
                    self.sent = 0
                    self.read = 0

                async def write(self, data):
                    self.sent += len(data)
                    await wrapped.write(data)

                async def readexactly(self, n):
                    out = await wrapped.readexactly(n)
                    self.read += len(out)
                    return out

                def close(self):
                    wrapped.close()

                def __getattr__(self, name):
                    return getattr(wrapped, name)

            c = _Counting()
            counters.append((local_id, c))
            return c

        async def main():
            from cometbft_tpu.p2p import switch as switch_mod

            switch_mod.netchaos.wrap = counting_wrap
            try:
                net = await make_tcp_net(4, chain_id="wire-telemetry")
                await net.start()
                try:
                    await net.wait_for_height(3, timeout=60)
                    for node in net.nodes:
                        tel = node.switch.net_telemetry()
                        assert tel["n_peers"] >= 3
                        me = node.node_key.id()
                        seam_sent = sum(c.sent for nid, c in counters
                                        if nid == me)
                        seam_read = sum(c.read for nid, c in counters
                                        if nid == me)
                        acc_sent = sum(
                            p["connection_status"]["send"]["bytes_total"]
                            for p in tel["peers"])
                        acc_read = sum(
                            p["connection_status"]["recv"]["bytes_total"]
                            for p in tel["peers"])
                        # seam counters may include conns that were torn
                        # down (dup tie-breaks), so seam >= accounted;
                        # live-conn accounting must still be within 5%
                        assert acc_sent <= seam_sent * 1.001
                        assert acc_sent >= seam_sent * 0.95, (
                            me, acc_sent, seam_sent)
                        assert acc_read <= seam_read * 1.001
                        assert acc_read >= seam_read * 0.95, (
                            me, acc_read, seam_read)
                        # consensus traffic landed on the vote/state chans
                        assert tel["totals"]["send_msgs"] > 0
                finally:
                    await net.stop()
            finally:
                switch_mod.netchaos.wrap = orig_wrap

        asyncio.run(main())
