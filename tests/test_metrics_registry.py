"""Metrics registry: labels, gauges, Prometheus exposition, and
concurrency (observe/snapshot under threads with the sort moved
outside the lock)."""

from __future__ import annotations

import re
import threading

from bftkv_tpu.metrics import Metrics


def test_counters_gauges_labels_flatten_in_snapshot():
    m = Metrics()
    m.incr("plain")
    m.incr("rpc", 2, labels={"cmd": "write", "side": "client"})
    m.gauge("depth", 7.5)
    m.gauge("occ", 0.25, labels={"name": "dispatch"})
    snap = m.snapshot()
    assert snap["plain"] == 1
    # labels flatten sorted by key
    assert snap["rpc{cmd=write,side=client}"] == 2
    assert snap["depth"] == 7.5
    assert snap["occ{name=dispatch}"] == 0.25


def test_gauge_last_write_wins():
    m = Metrics()
    m.gauge("g", 1.0)
    m.gauge("g", 3.0)
    assert m.snapshot()["g"] == 3.0
    assert "bftkv_g 3.0" in m.prometheus()


def test_observe_series_snapshot_keys_unchanged():
    """The historical flat keys (.count/.sum/.p50/.p99) survive the
    label-aware restructure — existing consumers read them."""
    m = Metrics()
    for v in (1.0, 2.0, 3.0, 4.0):
        m.observe("lat", v)
    snap = m.snapshot()
    assert snap["lat.count"] == 4
    assert snap["lat.sum"] == 10.0
    assert "lat.p50" in snap and "lat.p99" in snap
    assert m.percentile("lat", 0.5) == 3.0


_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"  # metric name
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})?'
    r" -?[0-9.eE+-]+(e[+-]?[0-9]+)?$"
)


def test_prometheus_exposition_is_scrapable():
    m = Metrics()
    m.incr("server.write.ok", 3)
    m.incr("transport.rpcs", 5, labels={"cmd": "sign", "transport": "loop"})
    m.gauge("dispatch.throughput", 0.5)
    m.observe("client.write.latency", 0.01)
    m.observe("client.write.latency", 0.02)
    text = m.prometheus()
    assert text.endswith("\n")
    sample_lines = []
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            assert re.match(r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* "
                            r"(counter|gauge|histogram)$", line), line
            continue
        assert _PROM_LINE.match(line), f"unscrapable line: {line!r}"
        sample_lines.append(line)
    # counters end in _total
    assert any(
        ln.startswith("bftkv_server_write_ok_total 3") for ln in sample_lines
    )
    assert any(
        ln.startswith("bftkv_transport_rpcs_total{") and ' 5' in ln
        for ln in sample_lines
    )
    # every TYPE counter name ends _total
    for line in text.splitlines():
        mobj = re.match(r"^# TYPE (\S+) counter$", line)
        if mobj:
            assert mobj.group(1).endswith("_total"), line
    # observe() series expose fixed-bucket histograms + _sum/_count
    assert "# TYPE bftkv_client_write_latency histogram" in text
    assert 'bftkv_client_write_latency_bucket{le="0.01"} 1' in text
    assert 'bftkv_client_write_latency_bucket{le="0.025"} 2' in text
    assert 'bftkv_client_write_latency_bucket{le="+Inf"} 2' in text
    assert "bftkv_client_write_latency_sum" in text
    assert "bftkv_client_write_latency_count 2" in text
    # gauges typed as gauge
    assert "# TYPE bftkv_dispatch_throughput gauge" in text


def test_prometheus_label_escaping():
    m = Metrics()
    m.incr("weird", labels={"v": 'a"b\\c\nd'})
    text = m.prometheus()
    assert '\\"' in text and "\\\\" in text and "\\n" in text
    # still one line per sample
    for line in text.splitlines():
        assert "\n" not in line


def test_concurrent_observe_snapshot_percentile():
    """observe() from many threads while snapshot()/percentile() run
    concurrently: totals must come out exact and nothing deadlocks
    (the sort happens outside the lock)."""
    m = Metrics()
    n_threads, per_thread = 4, 3000
    stop = threading.Event()

    def writer(k: int):
        for i in range(per_thread):
            m.observe("lat", float(i))
            m.incr("ops", labels={"t": str(k % 2)})

    def reader():
        while not stop.is_set():
            m.snapshot()
            m.percentile("lat", 0.99)

    readers = [threading.Thread(target=reader) for _ in range(2)]
    writers = [threading.Thread(target=writer, args=(k,))
               for k in range(n_threads)]
    for t in readers + writers:
        t.start()
    for t in writers:
        t.join()
    stop.set()
    for t in readers:
        t.join()
    snap = m.snapshot()
    assert snap["lat.count"] == n_threads * per_thread
    assert (
        snap["ops{t=0}"] + snap["ops{t=1}"] == n_threads * per_thread
    )


def test_reset_clears_everything():
    m = Metrics()
    m.incr("a")
    m.gauge("b", 1)
    m.observe("c", 1.0)
    m.reset()
    assert m.snapshot() == {}
    assert m.prometheus() == "\n"


def test_histograms_merge_across_instances():
    """The fixed-ladder contract the fleet collector leans on: two
    registries' bucket vectors sum element-wise and the merged quantile
    estimate is computable from the sum alone (per-daemon summary
    quantiles can't do this — DESIGN.md §11)."""
    from bftkv_tpu.metrics import BUCKETS, histogram_quantile

    a, b = Metrics(), Metrics()
    for v in (0.002, 0.002, 0.02):
        a.observe("lat", v, labels={"shard": 0})
    for v in (0.2, 0.2, 0.2, 7.0):
        b.observe("lat", v, labels={"shard": 0})
    ha = a.histograms()["lat{shard=0}"]
    hb = b.histograms()["lat{shard=0}"]
    assert len(ha["buckets"]) == len(BUCKETS) + 1
    merged = [x + y for x, y in zip(ha["buckets"], hb["buckets"])]
    assert sum(merged) == 7
    assert ha["count"] + hb["count"] == 7
    # 4 of 7 samples are <= 0.25 -> the p50 bucket is le=0.25
    assert histogram_quantile(0.5, merged) == 0.25
    assert histogram_quantile(0.99, merged) == 10.0
    assert histogram_quantile(0.5, [0] * (len(BUCKETS) + 1)) is None
    # snapshot carries the same counts as flat .bucket{le=} keys
    snap = a.snapshot()
    assert snap["lat.bucket{shard=0,le=0.0025}"] == 2
    assert snap["lat.bucket{shard=0,le=0.025}"] == 1
