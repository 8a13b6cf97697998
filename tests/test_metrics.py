"""Metrics: primitive semantics, Prometheus text rendering, and the
/metrics scrape endpoint on a live node (reference: each subsystem's
metrics.go + config.instrumentation)."""

import asyncio

from cometbft_tpu.libs.metrics import ConsensusMetrics, Registry


class TestPrimitives:
    def test_counter_gauge(self):
        reg = Registry(namespace="t")
        c = reg.counter("sub", "hits", "Hits")
        g = reg.gauge("sub", "depth", "Depth")
        c.inc()
        c.inc(2)
        g.set(5)
        g.dec()
        out = reg.render()
        assert "t_sub_hits 3" in out
        assert "t_sub_depth 4" in out
        assert "# TYPE t_sub_hits counter" in out

    def test_labels(self):
        reg = Registry(namespace="t")
        c = reg.counter("sub", "msgs", "Messages", labels=("chID",))
        c.labels("0x20").inc(7)
        c.labels("0x21").inc(1)
        out = reg.render()
        assert 't_sub_msgs{chID="0x20"} 7' in out
        assert 't_sub_msgs{chID="0x21"} 1' in out

    def test_histogram_buckets(self):
        reg = Registry(namespace="t")
        h = reg.histogram("sub", "lat", "Latency", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        out = reg.render()
        assert 't_sub_lat_bucket{le="0.1"} 1' in out
        assert 't_sub_lat_bucket{le="1"} 2' in out
        assert 't_sub_lat_bucket{le="+Inf"} 3' in out
        assert "t_sub_lat_count 3" in out

    def test_consensus_struct_renders(self):
        reg = Registry()
        m = ConsensusMetrics(reg)
        m.height.set(42)
        m.vote_extension_received.labels("accepted").inc()
        out = reg.render()
        assert "cometbft_consensus_height 42" in out
        assert 'cometbft_consensus_vote_extensions_received{status="accepted"} 1' in out


class TestExpositionRoundTrip:
    """ISSUE 6 exposition hardening: the rendered text must survive a
    strict parse — escaped label values decode back to the original
    strings, and each histogram label set renders in the order scrapers
    require (cumulative buckets ascending, the mandatory le="+Inf", then
    _sum, then _count)."""

    @staticmethod
    def _parse_labels(inner: str) -> dict:
        """A deliberately strict exposition label parser: name="value"
        pairs with \\\\ , \\" and \\n escapes — anything malformed
        raises."""
        out = {}
        i = 0
        while i < len(inner):
            eq = inner.index("=", i)
            name = inner[i:eq]
            assert inner[eq + 1] == '"'
            j = eq + 2
            val = []
            while inner[j] != '"':
                if inner[j] == "\\":
                    nxt = inner[j + 1]
                    val.append({"\\": "\\", '"': '"', "n": "\n"}[nxt])
                    j += 2
                else:
                    val.append(inner[j])
                    j += 1
            out[name] = "".join(val)
            i = j + 1
            if i < len(inner):
                assert inner[i] == ","
                i += 1
        return out

    def test_label_value_escaping_round_trip(self):
        reg = Registry(namespace="t")
        c = reg.counter("sub", "evil", "Evil labels", labels=("spec",))
        nasty = 'quote " backslash \\ newline \n done'
        c.labels(nasty).inc(3)
        line = next(l for l in reg.render().splitlines()
                    if l.startswith("t_sub_evil{"))
        assert "\n" not in line  # raw newline would split the series line
        inner = line[line.index("{") + 1:line.rindex("}")]
        assert self._parse_labels(inner) == {"spec": nasty}
        assert line.rsplit(" ", 1)[1] == "3"

    def test_help_escaping(self):
        reg = Registry(namespace="t")
        reg.counter("sub", "h", "line one\nline two \\ slash")
        out = reg.render()
        assert "# HELP t_sub_h line one\\nline two \\\\ slash" in out

    def test_histogram_series_order_and_escaping(self):
        reg = Registry(namespace="t")
        h = reg.histogram("sub", "lat", "Latency", labels=("klass",),
                          buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 0.5, 5.0):
            h.labels('a"b').observe(v)
        lines = [l for l in reg.render().splitlines()
                 if l.startswith("t_sub_lat")]
        # exact per-label-set order: buckets ascending, +Inf, _sum, _count
        kinds = [l.split("{")[0].rsplit(" ", 1)[0] for l in lines]
        assert kinds == ["t_sub_lat_bucket", "t_sub_lat_bucket",
                         "t_sub_lat_bucket", "t_sub_lat_sum",
                         "t_sub_lat_count"]
        les, counts = [], []
        for line in lines[:3]:
            inner = line[line.index("{") + 1:line.rindex("}")]
            labels = self._parse_labels(inner)
            assert labels["klass"] == 'a"b'
            les.append(labels["le"])
            counts.append(int(line.rsplit(" ", 1)[1]))
        assert les == ["0.1", "1", "+Inf"]
        # cumulative and consistent with _count / _sum
        assert counts == sorted(counts) and counts[-1] == 4
        assert float(lines[3].rsplit(" ", 1)[1]) == 6.05
        assert int(lines[4].rsplit(" ", 1)[1]) == 4
        # accessor pair used by bench/tests
        assert h.sum_value('a"b') == 6.05
        assert h.count_value('a"b') == 4


def test_node_metrics_endpoint(tmp_path):
    """A live node serves Prometheus text at /metrics with consensus
    heights advancing."""
    from cometbft_tpu.node.node import Node, init_files

    async def main():
        cfg = init_files(str(tmp_path), chain_id="metrics-chain")
        cfg.consensus.timeout_commit = 0.05
        cfg.rpc.laddr = "tcp://127.0.0.1:0"
        cfg.p2p.laddr = "tcp://127.0.0.1:0"
        node = Node(cfg)
        await node.start()
        try:
            deadline = asyncio.get_running_loop().time() + 20
            while node.block_store.height() < 2:
                assert asyncio.get_running_loop().time() < deadline
                await asyncio.sleep(0.05)
            # the block store is written before the gauge is set: the
            # gauge may trail the store by a step of the commit path
            target = node.block_store.height()
            host, port = node.rpc_server.bound_addr.rsplit(":", 1)
            while True:
                reader, writer = await asyncio.open_connection(host, int(port))
                writer.write(b"GET /metrics HTTP/1.1\r\nHost: x\r\nConnection: close\r\n\r\n")
                await writer.drain()
                raw = await reader.read()
                writer.close()
                text = raw.decode()
                assert "200 OK" in text and "text/plain" in text
                assert "cometbft_consensus_height" in text
                # the gauge tracks the actual chain
                line = next(l for l in text.splitlines()
                            if l.startswith("cometbft_consensus_height "))
                if float(line.split()[-1]) >= target:
                    break
                assert asyncio.get_running_loop().time() < deadline, line
                await asyncio.sleep(0.05)
            assert target >= 2
            assert "cometbft_mempool_size" in text
            assert "cometbft_p2p_peers" in text
        finally:
            await node.stop()

    asyncio.run(main())
