"""The waits inside a sidecar round trip, each timed where it happens:
a pool's queue (``dispatch.queue``), the tenant's wait for its one
connection (``sidecar.channel_wait``), the sidecar's service time of a
request (``sidecar.serve``) and the time admission holds requests out
(``sidecar.backlog.seconds``); and the per-layer metrics that read them
(docs/DESIGN.md §7.1).

The dispatchers and the sidecar are real: pools with a slow stand-in
verifier, and a sidecar on a unix socket whose verify pool can be held.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
import time
import types

import numpy as np
import pytest

from benchmarks.readers import counter_per_window
from benchmarks.run import Counters
from bftkv_tpu import admission, trace
from bftkv_tpu.cmd import verify_sidecar as vs
from bftkv_tpu.crypto import remote_verify, rsa
from bftkv_tpu.metrics import registry as metrics
from bftkv_tpu.ops import dispatch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TIMERS = ("dispatch.queue", "sidecar.channel_wait", "sidecar.serve")


class _SlowVerifier:
    """A verifier whose batch takes ``seconds``: a flush long enough
    for submits behind it to queue."""

    def __init__(self, seconds: float):
        self.seconds = seconds

    def verify_batch(self, items):
        time.sleep(self.seconds)
        return np.ones(len(items), dtype=bool)


def _recording(monkeypatch) -> list:
    """(thread, name, seconds, labels) of every ``observe``, in order;
    the registry still records them."""
    seen = []
    real = metrics.observe

    def observe(name, value, labels=None):
        seen.append((threading.get_ident(), name, value, labels))
        return real(name, value, labels=labels)

    monkeypatch.setattr(metrics, "observe", observe)
    return seen


# -- a pool's queue ---------------------------------------------------------


@pytest.mark.parametrize("pipeline", [1, 2])
def test_queue_is_observed_once_a_submit_and_within_its_wait(
    monkeypatch, pipeline
):
    flush_s = 0.05
    d = dispatch.VerifyDispatcher(
        _SlowVerifier(flush_s), max_batch=4, max_wait=0.001,
        calibrate=False, pipeline=pipeline,
    ).start()
    metrics.reset()
    seen = _recording(monkeypatch)
    submits = 6
    try:
        # a full batch a submit: each flush carries one, the rest queue
        threads = [threading.Thread(target=d.submit, args=([b"x"] * 4,))
                   for _ in range(submits)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
    finally:
        d.stop()
    snap = metrics.snapshot()
    assert snap["dispatch.queue.count{op=verify}"] == submits
    assert snap["dispatch.wait.count"] == submits
    by_thread: dict = {}
    for tid, name, value, labels in seen:
        if name in ("dispatch.wait", "dispatch.queue"):
            by_thread.setdefault(tid, []).append((name, value, labels))
    queued = []
    for obs in by_thread.values():
        # on the caller's thread: the wait, then the queued part of it
        (wname, wait, _), (qname, queue, qlabels) = obs
        assert (wname, qname, qlabels) == (
            "dispatch.wait", "dispatch.queue", {"op": "verify"})
        assert 0 <= queue <= wait
        queued.append(queue)
    assert len(queued) == submits
    # the last of six one-batch submits waited out the flushes ahead of
    # it (at least two even with two flushes in flight)
    assert max(queued) >= 2 * flush_s * 0.9


def test_a_submit_that_never_queued_observes_no_queue():
    d = dispatch.VerifyDispatcher(_SlowVerifier(0.0), calibrate=False)
    metrics.reset()
    assert list(d.submit([b"x"] * 3)) == [True] * 3  # not started: inline
    assert not [k for k in metrics.snapshot() if k.startswith("dispatch.queue")]


def test_a_flush_stamps_every_entry_it_carries():
    d = dispatch.VerifyDispatcher(_SlowVerifier(0.0), calibrate=False)
    batch = [dispatch._Pending([b"x"]), dispatch._Pending([b"y", b"z"])]
    before = time.perf_counter()
    d._flush(batch)
    assert all(p.event.is_set() for p in batch)
    stamps = {p.flushed for p in batch}
    assert len(stamps) == 1 and before <= stamps.pop() <= time.perf_counter()


# -- the channel lock and the sidecar's service time ------------------------


@pytest.fixture(scope="module")
def sidecar():
    sock = os.path.join(tempfile.mkdtemp(prefix="bftkv-rt-"), "s.sock")
    metrics.reset()
    srv, _t = vs.serve("unix:" + sock, max_batch=64)
    # built at 0, so that a run without a backlog reads 0, not nothing
    assert metrics.snapshot()["sidecar.backlog.seconds"] == 0
    try:
        yield srv, "unix:" + sock
    finally:
        srv.shutdown()
        srv.server_close()
        srv.service.stop()


@pytest.fixture(scope="module")
def verify_items():
    key = rsa.generate(1024)
    sig = rsa.sign(b"round trip", key)
    return [(b"round trip", sig, key.public)] * 3


def _held(monkeypatch, srv):
    """Hold the sidecar's first verify until ``release`` is set;
    ``entered`` says the first request is inside the sidecar."""
    entered, release = threading.Event(), threading.Event()
    real = srv.dispatcher.verify
    first = [True]

    def verify(items):
        if first[0]:
            first[0] = False
            entered.set()
            release.wait(30)
        return real(items)

    monkeypatch.setattr(srv.dispatcher, "verify", verify)
    return entered, release


def test_a_request_behind_another_waits_for_the_channel_lock(
    monkeypatch, sidecar, verify_items
):
    srv, addr = sidecar
    entered, release = _held(monkeypatch, srv)
    channel = remote_verify.SidecarChannel(addr)
    body = vs.encode_request(verify_items)
    answers, t_second = [], []

    def call():
        answers.append(channel.request(vs.OP_VERIFY, body))

    def second():
        t_second.append(time.perf_counter())
        call()

    metrics.reset()
    try:
        one = threading.Thread(target=call)
        one.start()
        assert entered.wait(30)
        two = threading.Thread(target=second)
        two.start()
        time.sleep(0.3)
        released = time.perf_counter()
        release.set()
        one.join(30)
        two.join(30)
    finally:
        release.set()
        channel.close()
    assert [a[0] for a in answers] == [vs.ST_OK, vs.ST_OK]
    snap = metrics.snapshot()
    waits = snap["sidecar.channel_wait.count{op=verify}"]
    assert waits == snap["sidecar.call.count{op=verify}"] == 2
    # the first found the lock free; the second waited out the hold
    assert snap["sidecar.channel_wait.sum{op=verify}"] >= (
        released - t_second[0]) - 0.01


def test_serve_counts_every_request_and_lies_inside_the_tenants_call(
    sidecar, verify_items
):
    _srv, addr = sidecar
    requests = 3
    metrics.reset()
    domain = remote_verify.RemoteVerifierDomain(addr)
    try:
        for _ in range(requests):
            assert list(domain.verify_batch(verify_items)) == [True] * 3
    finally:
        domain.channel.close()
    snap = metrics.snapshot()
    assert snap["sidecar.serve.count{op=verify}"] == requests
    assert snap["sidecar.call.count{op=verify}"] == requests
    assert 0 < snap["sidecar.serve.sum{op=verify}"] <= snap[
        "sidecar.call.sum{op=verify}"]
    # the counter it replaces is gone
    assert not [k for k in snap if k.startswith("sidecar.ops")]


def test_a_shed_is_served_and_a_control_frame_is_not(sidecar):
    srv, addr = sidecar
    q = srv.service.admission
    saved = q.max_inflight, q.max_queue
    channel = remote_verify.SidecarChannel(addr)
    metrics.reset()
    try:
        q.max_inflight, q.max_queue = 0, 0  # every request is shed
        out = channel.request(vs.OP_VERIFY, vs.encode_request([]))
        assert out is not None and out[0] == vs.ST_SHED
        assert channel.stats() is not None
    finally:
        q.max_inflight, q.max_queue = saved
        channel.close()
    snap = metrics.snapshot()
    assert snap["sidecar.serve.count{op=verify}"] == 1
    assert not [k for k in snap if k.startswith("sidecar.serve")
                and "op=verify" not in k]
    assert snap["sidecar.channel_wait.count{op=control}"] == 1


# -- the time admission holds requests out ----------------------------------


def test_backlog_seconds_grow_only_while_a_request_waits(monkeypatch):
    now = [100.0]
    monkeypatch.setattr(
        admission, "time", types.SimpleNamespace(monotonic=lambda: now[0]))
    metrics.reset()
    q = admission.AdmissionQueue(max_inflight=1, max_queue=4, max_wait=5.0,
                                 metric="sidecar.shed")
    grown = lambda: metrics.snapshot().get("sidecar.backlog.seconds", 0)  # noqa: E731
    now[0] += 2.0                      # empty: nobody waits
    assert q.acquire("verify")
    now[0] += 3.0                      # every slot held, nobody waits
    assert grown() == 0
    waiter = threading.Thread(target=q.acquire, args=("verify",))
    waiter.start()
    until = time.monotonic() + 5
    while q.depth() != (1, 1) and time.monotonic() < until:
        time.sleep(0.001)
    assert q.depth() == (1, 1)         # 0 -> 1 waiting opens the interval
    now[0] += 4.0
    assert grown() == 0                # counted when it closes
    q.release()                        # the waiter is admitted: 1 -> 0
    waiter.join(5)
    assert not waiter.is_alive() and q.depth() == (1, 0)
    assert grown() == pytest.approx(4.0)
    now[0] += 1.5                      # busy again, nobody waits
    q.release()
    assert grown() == pytest.approx(4.0)
    assert q.acquire("sign")
    q.release()
    assert grown() == pytest.approx(4.0)


def test_another_tier_records_no_backlog(monkeypatch):
    metrics.reset()
    q = admission.AdmissionQueue(max_inflight=1, max_queue=4, max_wait=0.01,
                                 metric="gateway.shed")
    assert q.acquire("read")
    assert not q.acquire("read")       # waited 10 ms, then shed
    q.release()
    assert not [k for k in metrics.snapshot() if "backlog" in k]


# -- the names ---------------------------------------------------------------


@pytest.mark.parametrize("name", TIMERS)
def test_the_timers_are_declared_and_not_bridged(name):
    assert name not in trace.BRIDGED
    assert name in trace.SPAN_PHASES
    assert trace.phase_of(name) == trace.SPAN_PHASES[name] != "other"
    assert trace.phase_of(name) in trace.PHASES


@pytest.mark.parametrize("name", TIMERS[1:])
def test_the_request_timers_are_ring_spans_off_the_profiler(name):
    entered = []
    trace.set_bridge(lambda n, **a: entered.append(n))
    trace.tracer.reset()
    metrics.reset()
    try:
        with trace.leaf(name, "verify"):
            pass
    finally:
        trace.set_bridge(None)
    assert entered == []
    assert [s["name"] for s in trace.tracer.export()["spans"]] == [name]
    assert metrics.snapshot()[f"{name}.count{{op=verify}}"] == 1


# -- the per-layer metrics that read them ------------------------------------


METRICS = {
    # name: (its workloads, the value the scrape below gives)
    "daemon_pool_queue_ms_per_request": ("all", 1000.0 * 6.0 / 300),
    "sidecar_channel_wait_ms_per_call": ("all", 1000.0 * 1.5 / 100),
    "sidecar_serve_ms_per_call": ("all", 1000.0 * 4.0 / 200),
    "sidecar_queue_ms_per_request": ("all", 1000.0 * 2.0 / 250),
    "sidecar_backlog_share": ("load", 100.0 * 12.5 / 50.0),
}
BEFORE = {"sidecar": {"sidecar.serve.sum{op=verify}": 1.0,
                      "sidecar.serve.count{op=verify}": 100,
                      "dispatch.queue.sum{op=verify}": 1.0,
                      "dispatch.queue.count{op=verify}": 50,
                      "sidecar.backlog.seconds": 2.5},
          "daemons": {"a01": {"dispatch.queue.sum{op=sign}": 1.0,
                              "dispatch.queue.count{op=sign}": 100,
                              "sidecar.channel_wait.sum{op=sign}": 0.5,
                              "sidecar.channel_wait.count{op=sign}": 50}}}
AFTER = {"sidecar": {"sidecar.serve.sum{op=verify}": 3.0,
                     "sidecar.serve.count{op=verify}": 200,
                     "sidecar.serve.sum{op=sign}": 2.0,
                     "sidecar.serve.count{op=sign}": 100,
                     "dispatch.queue.sum{op=verify}": 2.0,
                     "dispatch.queue.count{op=verify}": 150,
                     "dispatch.queue.sum{op=sign}": 1.0,
                     "dispatch.queue.count{op=sign}": 150,
                     "sidecar.backlog.seconds": 15.0},
         "daemons": {"a01": {"dispatch.queue.sum{op=sign}": 5.0,
                             "dispatch.queue.count{op=sign}": 300,
                             "sidecar.channel_wait.sum{op=sign}": 1.5,
                             "sidecar.channel_wait.count{op=sign}": 120},
                     "a02": {"dispatch.queue.sum{op=verify}": 2.0,
                             "dispatch.queue.count{op=verify}": 100,
                             "sidecar.channel_wait.sum{op=verify}": 0.5,
                             "sidecar.channel_wait.count{op=verify}": 30}}}


def _spec(name: str) -> dict:
    path = os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".json")
    with open(path) as f:
        return json.load(f)


def _ctx(before, after) -> dict:
    return {"ops": 1000, "window_s": 50.0,
            "counters": Counters(before, after), "trace": None}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_each_metric_reads_its_counters_over_the_window(name):
    spec = _spec(name)
    assert spec["name"] == name and spec["reader"] == "counter_per_window"
    value = counter_per_window.read(_ctx(BEFORE, AFTER), spec["args"])
    assert value == pytest.approx(METRICS[name][1])


@pytest.mark.parametrize("name", sorted(METRICS))
def test_each_metric_reads_nothing_where_the_program_lacks_the_timer(name):
    # the parent: pools, calls and launches grow, the new names do not
    parent = {"sidecar": {"verify.device": 5, "dispatch.wait.count": 9},
              "daemons": {"a01": {"sidecar.call.sum{op=sign}": 1.0,
                                  "sidecar.call.count{op=sign}": 9}}}
    empty = {"sidecar": {}, "daemons": {}}
    assert counter_per_window.read(_ctx(empty, parent),
                                   _spec(name)["args"]) is None


def test_the_manifest_lists_each_metric_for_its_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = [w["name"] for w in manifest["workloads"]]
    load = [c for c in cells if c.endswith(".load")]
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name, (which, _v) in METRICS.items():
        m = entries[name]
        assert m["workloads"] == (cells if which == "all" else load), name
        assert (m["source"], m["better"], m["moves"]) == (
            "program_counter", "lower", "committed_ops_per_s")
