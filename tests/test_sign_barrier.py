"""BATCH_SIGN's records share ONE durability barrier a frame
(DESIGN.md §19.2): each admitted item is appended — readable at once,
by the frame's next check and by every concurrent handler — and the
frame waits out one barrier before ``issue_many``, so no share leaves
the server before every record it depends on is fsynced."""

from __future__ import annotations

import itertools
import threading
import time

import pytest

from bftkv_tpu import packet as pkt
from bftkv_tpu import transport as tp
from bftkv_tpu.errors import ERR_EQUIVOCATION, error_from_string
from bftkv_tpu.faults import failpoint as fp
from bftkv_tpu.metrics import registry as metrics
from bftkv_tpu.storage.logkv import LogStorage
from bftkv_tpu.storage.memkv import MemStorage
from tests.cluster_utils import start_cluster

BITS = 1024  # keygen speed; the barrier is width-agnostic
N = 16


def _count(name: str) -> float:
    return metrics.snapshot().get(name, 0)


@pytest.fixture
def log_cluster(tmp_path):
    """The daemon's engine as `--storage log` builds it: fsync on, the
    default group-commit linger."""
    ids = itertools.count()
    c = start_cluster(
        4, 1, 0, bits=BITS,
        storage_factory=lambda: LogStorage(str(tmp_path / f"db{next(ids)}")),
    )
    yield c
    c.stop()
    for s in c.all_servers:
        s.storage.close()


@pytest.fixture
def failpoints():
    reg = fp.registry.arm(0)
    yield reg
    fp.disarm()


def _frame(client, items) -> bytes:
    """A BATCH_SIGN payload as ``write_many`` builds it: ``items`` are
    ``(variable, value, t)``, each signed by the client."""
    tbs = [pkt.serialize(v, val, t, nfields=3) for v, val, t in items]
    sigs = client.crypt.signer.issue_many(tbs, include_cert=False)
    return pkt.serialize_list(
        [pkt.serialize(v, val, t, s, None)
         for (v, val, t), s in zip(items, sigs)]
    )


def _sign(srv, client, items) -> list[tuple[str | None, bytes]]:
    return pkt.parse_results(srv._batch_sign(_frame(client, items), None, None))


def test_a_frame_takes_one_barrier(log_cluster):
    srv, c = log_cluster.servers[0], log_cluster.clients[0]
    items = [(b"sb/x%02d" % i, b"v%d" % i, 1) for i in range(N)]
    before = {k: _count(k) for k in (
        "storage.log.fsync", "server.batch_sign.deferred",
        "server.batch_sign.direct", "server.batch_sign.barrier.count")}
    out = _sign(srv, c, items)
    assert [e for e, _ in out] == [None] * N
    assert all(pkt.parse_signature(share) for _e, share in out)
    assert _count("storage.log.fsync") - before["storage.log.fsync"] == 1
    assert (_count("server.batch_sign.deferred")
            - before["server.batch_sign.deferred"]) == N
    assert _count("server.batch_sign.direct") == before["server.batch_sign.direct"]
    assert (_count("server.batch_sign.barrier.count")
            - before["server.batch_sign.barrier.count"]) == 1
    for v, val, t in items:
        p = pkt.parse(srv.storage.read(v, 0))
        assert (p.value, p.t, p.ss) == (val, t, None)


def test_a_failed_barrier_issues_no_share(log_cluster, failpoints, monkeypatch):
    """An fsync that fails answers the frame with an error, through the
    transport, and ``issue_many`` never runs."""
    srv, c = log_cluster.servers[0], log_cluster.clients[0]
    issued = []
    real = srv.crypt.signer.issue_many
    monkeypatch.setattr(
        srv.crypt.signer, "issue_many",
        lambda *a, **k: issued.append(1) or real(*a, **k),
    )
    failpoints.add("storage.fsync", "io_error")
    ok_before = _count("server.sign.ok")
    node = c.crypt.keyring.get(log_cluster.universe.servers[0].id)
    got = []
    c.tr.multicast(
        tp.BATCH_SIGN, [node],
        _frame(c, [(b"sb/eio%d" % i, b"v", 1) for i in range(N)]),
        lambda res: got.append(res) or True,
    )
    assert len(got) == 1 and got[0].err is not None and got[0].data is None
    assert issued == []
    assert _count("server.sign.ok") == ok_before


def test_a_stalled_barrier_holds_the_answer(log_cluster, failpoints):
    srv, c = log_cluster.servers[0], log_cluster.clients[0]
    failpoints.add("storage.fsync", "stall", seconds=0.4, times=1)
    t0 = time.monotonic()
    out = _sign(srv, c, [(b"sb/stall%d" % i, b"v", 1) for i in range(N)])
    assert time.monotonic() - t0 >= 0.4
    assert [e for e, _ in out] == [None] * N


def test_an_equivocation_inside_a_frame(log_cluster):
    """``(x, t, v)`` then ``(x, t, v')`` in one frame: the second check
    reads the first record before any barrier and refuses it by the
    single path's equivocation error."""
    srv, c = log_cluster.servers[0], log_cluster.clients[0]
    out = _sign(srv, c, [(b"sb/eq", b"v", 1), (b"sb/eq", b"v-prime", 1)])
    assert out[0][0] is None and pkt.parse_signature(out[0][1])
    assert error_from_string(out[1][0]) is ERR_EQUIVOCATION
    assert pkt.parse(srv.storage.read(b"sb/eq", 0)).value == b"v"


def test_concurrent_frames_cannot_both_sign_one_slot(log_cluster, failpoints):
    """Frame A appends ``(x, t, v)`` and waits out a stalled barrier;
    frame B, naming ``(x, t, v')`` meanwhile, sees A's record before it
    is durable, is refused, and answers without waiting for A."""
    srv, c = log_cluster.servers[0], log_cluster.clients[0]
    failpoints.add("storage.fsync", "stall", seconds=1.0, times=1)
    frame_a = [(b"sb/race", b"v", 1)] + [
        (b"sb/race-pad%d" % i, b"p", 1) for i in range(N - 1)]
    got = {}
    a = threading.Thread(target=lambda: got.update(a=_sign(srv, c, frame_a)))
    a.start()
    deadline = time.monotonic() + 10
    while 1 not in srv.storage.versions(b"sb/race"):
        assert time.monotonic() < deadline
        time.sleep(0.005)
    out_b = _sign(srv, c, [(b"sb/race", b"v-prime", 1)])
    assert a.is_alive()  # B did not wait for A's barrier
    a.join(10)
    assert error_from_string(out_b[0][0]) is ERR_EQUIVOCATION
    assert got["a"][0][0] is None and pkt.parse_signature(got["a"][0][1])
    assert pkt.parse(srv.storage.read(b"sb/race", 0)).value == b"v"


def test_a_backend_without_the_split_persists_item_by_item():
    c = start_cluster(4, 1, 0, bits=BITS, storage_factory=MemStorage)
    try:
        srv = c.servers[0]
        assert getattr(srv.storage, "append", None) is None
        deferred = _count("server.batch_sign.deferred")
        direct = _count("server.batch_sign.direct")
        out = _sign(srv, c.clients[0],
                    [(b"sb/mem%d" % i, b"v", 1) for i in range(N)])
        assert [e for e, _ in out] == [None] * N
        assert _count("server.batch_sign.direct") - direct == N
        assert _count("server.batch_sign.deferred") == deferred
    finally:
        c.stop()


def test_the_chaos_recorder_sees_every_appended_record(tmp_path):
    """The nemesis wraps a replica's storage in ``RecordingStorage``:
    the split surfaces through it only where the engine has it, and an
    appended record is recorded as a persist, as a written one is."""
    from bftkv_tpu.faults.checker import HistoryRecorder, RecordingStorage

    rec = HistoryRecorder()
    assert getattr(RecordingStorage(MemStorage(), "m", rec), "append", None) is None
    st = RecordingStorage(LogStorage(str(tmp_path / "db")), "a01", rec)
    st.barrier(st.append(b"k", 1, b"not-a-record"))
    assert st.read(b"k") == b"not-a-record"
    (ev,) = rec.events("persist")
    assert (ev.fields["node"], ev.fields["variable"], ev.fields["t"]) == (
        "a01", b"k", 1)
    st.close()


@pytest.mark.parametrize("name,after,want", [
    ("sign_deferred_share",
     {"server.batch_sign.deferred": 512, "server.batch_sign.direct": 0}, 100.0),
    ("sign_deferred_share",
     {"server.batch_sign.deferred": 0, "server.batch_sign.direct": 256}, 0.0),
    ("daemon_sign_barrier_ms_per_call",
     {"server.batch_sign.barrier.sum": 0.008,
      "server.batch_sign.handler.count": 2}, 4.0),
])
def test_the_per_layer_metrics_read_the_daemons_counters(name, after, want):
    """Two data files on the harness's readers, listed for the three
    load cells; a daemon without the counters (the parent) reports
    nothing."""
    import importlib

    from benchmarks import run as runmod

    spec = runmod.load_json("benchmarks", "layer_metrics", name + ".json")
    (entry,) = [m for m in runmod.load_manifest()["per_layer"]
                if m["name"] == name]
    assert (entry["layer"], entry["moves"], entry["workloads"]) == (
        "replica daemons", "committed_ops_per_s",
        ["q4-rsa2048.load", "q10-rsa2048.load", "q4-rsa3072.load"])
    reader = importlib.import_module("benchmarks.readers." + spec["reader"])

    def read(snap):
        counters = runmod.Counters({"sidecar": {}, "daemons": {}},
                                   {"sidecar": {}, "daemons": {"a01": snap}})
        return reader.read({"ops": 1000, "window_s": 50.0,
                            "counters": counters}, spec["args"])

    assert read(after) == pytest.approx(want)
    assert read({"server.batch_sign.handler.count": 2,
                 "server.batch_sign.handler.sum": 2.0}) is None
