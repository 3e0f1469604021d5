"""End-to-end protocol rounds over the real HTTP transport with the
cross-request verify dispatcher installed.

The reference's whole tier-3 suite runs over HTTP loopback
(reference: protocol/test_utils.go:24-82); this is the analog, plus the
in-situ proof that concurrent server handlers share device launches
(dispatch batch occupancy > 1 under concurrent writes).
"""

import threading

import pytest

from bftkv_tpu.errors import Error
from bftkv_tpu.metrics import registry as metrics
from bftkv_tpu.ops import dispatch
from bftkv_tpu.transport.http import TrHTTP
from tests.cluster_utils import start_cluster

KEY_BITS = 1024  # keygen speed; the session/protocol path is bits-agnostic


@pytest.fixture(scope="module")
def http_cluster():
    # 4 quorum + 4 rw nodes: the READ-complement clique needs >= 4 nodes
    # for f >= 1 (wotqs.go:55-66), else the READ quorum is empty.
    cluster = start_cluster(4, 3, 4, bits=KEY_BITS, transport="http")
    yield cluster
    cluster.stop()


def test_http_write_read_roundtrip(http_cluster):
    c = http_cluster.clients[0]
    c.write(b"http/x", b"over the wire")
    assert c.read(b"http/x") == b"over the wire"
    # A second client sees the committed value through its own ports.
    assert http_cluster.clients[1].read(b"http/x") == b"over the wire"


def test_http_missing_variable_reads_none(http_cluster):
    assert http_cluster.clients[0].read(b"http/never-written") is None


def test_http_error_tunnel(http_cluster):
    """Interned errors survive the x-error header round trip
    (reference: transport/http/http.go:59-66): a hostile body fails
    session-layer decryption server-side and the client re-raises the
    *same interned error object*, not a generic HTTP failure."""
    addr = http_cluster.universe.servers[0].cert.address
    tr = http_cluster.clients[0].tr
    with pytest.raises(Error) as ei:
        tr.post(addr + "/bftkv/v1/sign", b"\xde\xad\xbe\xef" * 8)
    import bftkv_tpu.errors as errors

    assert errors.error_from_string(ei.value.message) is type(ei.value)


def test_http_concurrent_writes_share_device_batches(http_cluster, monkeypatch):
    """N clients writing concurrently through real sockets: all writes
    land, and the dispatcher coalesces verify calls from concurrent
    handler threads into shared launches (mean batch > 1).

    Calibration and the verify memo are disabled for the duration:
    both would (correctly) keep verifies away from the dispatcher on a
    CPU backend, and this test exists to observe the coalescing
    machinery itself."""
    from bftkv_tpu.crypto import vcache

    monkeypatch.setattr(vcache, "_ENABLED", False)
    metrics.reset()
    dispatch.install(
        dispatch.VerifyDispatcher(max_batch=256, max_wait=0.01, calibrate=False)
    )
    try:
        errors: list = []

        def run(ci, client):
            try:
                for i in range(3):
                    client.write(b"http/c%d/%d" % (ci, i), b"v%d-%d" % (ci, i))
            except Exception as e:  # pragma: no cover - surfaced below
                errors.append(e)

        threads = [
            threading.Thread(target=run, args=(ci, c))
            for ci, c in enumerate(http_cluster.clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        for ci in range(len(http_cluster.clients)):
            assert http_cluster.clients[0].read(b"http/c%d/2" % ci) == b"v%d-2" % ci

        snap = metrics.snapshot()
        assert snap.get("dispatch.flushes", 0) >= 1
        mean = snap["dispatch.verifies"] / snap["dispatch.flushes"]
        assert mean > 1.0, f"no cross-request coalescing observed: {snap}"
    finally:
        dispatch.uninstall()


def test_http_connections_are_reused(http_cluster):
    """The per-peer keep-alive pool carries repeat RPCs on existing
    sockets: after a warm first write, further writes mostly reuse
    (transport.conn.reused grows much faster than .dialed)."""
    c = http_cluster.clients[0]
    c.write(b"http/pool-warm", b"w")  # dials + pools the quorum links
    metrics.reset()
    for i in range(3):
        c.write(b"http/pool/%d" % i, b"v%d" % i)
    snap = metrics.snapshot()
    reused = snap.get("transport.conn.reused", 0)
    dialed = snap.get("transport.conn.dialed", 0)
    assert reused > 0, f"no connection reuse observed: {snap}"
    # A write is ~12 RPCs; with warm pools nearly all should reuse.
    assert reused >= 3 * dialed, (reused, dialed)


def test_http_transport_is_really_used(http_cluster):
    """Guard against the fixture silently falling back to loopback."""
    assert isinstance(http_cluster.clients[0].tr, TrHTTP)
    assert http_cluster.universe.servers[0].cert.address.startswith("http://127.0.0.1:")


# -- a small answer does not wait for a delayed ACK ---------------------------


class _Echo:
    """A transport server whose answer is ``size`` bytes."""

    def __init__(self, size: int):
        self.size = size

    def handler(self, cmd, data):
        return bytes([len(data) % 251]) * self.size


@pytest.mark.parametrize("size", [0, 900, 70_000, 300_000])
def test_an_answer_of_any_size_comes_back_whole_and_at_once(size):
    """The server's sockets are TCP_NODELAY: a write-write-read
    exchange under Nagle's algorithm holds the second write of an
    answer under one segment (64 KB on loopback) until the client's
    delayed ACK, ~40 ms an RPC — what every post of a small answer
    took before PR 34.  The median of 21 posts is held to 20 ms,
    which one stall in a post fails and a busy sandbox does not."""
    import statistics
    import time

    from bftkv_tpu import transport as tp

    srv = TrHTTP(None)
    srv.start(_Echo(size), "127.0.0.1:0")
    try:
        port = srv._server.server_address[1]
        url = f"http://127.0.0.1:{port}{tp.PREFIX}time"
        cli = TrHTTP(None)
        took = []
        for i in range(21):
            t0 = time.perf_counter()
            got = cli.post(url, b"q" * (100 + i))
            took.append(time.perf_counter() - t0)
            assert got == bytes([(100 + i) % 251]) * size
        assert statistics.median(took) < 0.020, sorted(took)
    finally:
        srv.stop()
