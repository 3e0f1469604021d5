"""The phases of a sidecar launch: spans on the profiler's clock, the
histograms an untraced run prints, the interval in which the sidecar
holds no request, and kernels that say which chain they are
(docs/DESIGN.md §7, "Launch phases").

The capture is real: one verify pool and one sign pool launch on the
CPU backend (crossover forced down, a 1024-bit key, one bucket each —
two compiles), a sidecar answers two requests, and the file the
profiler wrote is read back with the benchmark's own reduction
(``benchmarks/reduce/xplane.py``), which is what names the idle gaps of
a chip run.
"""

from __future__ import annotations

import os
import re
import socket
import tempfile
import threading
import time
import types
import urllib.request

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from benchmarks.reduce import xplane  # noqa: E402
from bftkv_tpu import admission, ops, trace  # noqa: E402
from bftkv_tpu.cmd import verify_sidecar as vs  # noqa: E402
from bftkv_tpu.crypto import remote_verify, rsa  # noqa: E402
from bftkv_tpu.metrics import registry as metrics  # noqa: E402
from bftkv_tpu.ops import dispatch, ec_rns, pallas_rns, rns  # noqa: E402

FLUSH_PHASES = ("flush.stage", "flush.launch", "flush.fetch", "flush.unpack")
PHASES = ("dispatch.linger", *FLUSH_PHASES, "flush.scatter",
          "sidecar.decode", "sidecar.reply")
VERIFY_FLUSHES = 2


def _phase_counts(snap: dict) -> dict:
    """{(phase, op): count} of every phase histogram in a snapshot."""
    out = {}
    for key, v in snap.items():
        m = re.fullmatch(r"([a-z.]+)\.count\{(.*)\}", key)
        if m and m.group(1) in PHASES + ("sidecar.call",):
            assert m.group(2).startswith("op=") and "," not in m.group(2), key
            out[m.group(1), m.group(2)[3:]] = v
    return out


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """One profiler capture (``python_tracer_level`` 0, as the benchmark
    starts it) over two verify flushes and one sign flush through the
    real dispatchers, then two requests to a sidecar on a unix socket."""
    key = rsa.generate(1024)
    msg = b"phases"
    sig = rsa.sign(msg, key)
    forged = sig[:-1] + bytes([sig[-1] ^ 1])
    verify = dispatch.VerifyDispatcher(
        max_batch=64, max_wait=0.001, calibrate=False, pipeline=1
    ).start()
    sign = dispatch.SignDispatcher(
        max_batch=32, max_wait=0.001, calibrate=False, pipeline=1
    ).start()
    # the crossover a device would calibrate: these flushes launch
    verify.verifier.host_threshold = 1
    sign.signer.host_threshold = 1
    out = str(tmp_path_factory.mktemp("capture"))
    sock = os.path.join(tempfile.mkdtemp(prefix="bftkv-ph-"), "s.sock")
    metrics.reset()
    trace.tracer.reset()
    trace.set_bridge(jax.profiler.TraceAnnotation)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    srv = None
    try:
        items = [(msg, sig, key.public)] * 20 + [(msg, forged, key.public)]
        for _ in range(VERIFY_FLUSHES):
            with trace.span("client.write"):  # so that dispatch.wait exists
                ok = verify.submit(items)
            assert list(ok) == [True] * 20 + [False]
        assert sign.submit([(msg, key)] * 17) == [sig] * 17
        srv, _t = vs.serve("unix:" + sock, max_batch=64)
        domain = remote_verify.RemoteVerifierDomain("unix:" + sock)
        for _ in range(2):
            assert list(domain.verify_batch(items)) == [True] * 20 + [False]
    finally:
        jax.profiler.stop_trace()
        trace.set_bridge(None)
        verify.stop()
        sign.stop()
        if srv is not None:
            srv.shutdown()
            srv.server_close()
            srv.service.stop()
    return {
        "planes": xplane.load(xplane.find_xplane(out)),
        "snap": metrics.snapshot(),
        "ring": [s["name"] for s in trace.tracer.export()["spans"]],
    }


def _host(planes) -> list[tuple[str, int, int]]:
    return [(n, s, d) for p in planes if p["name"].startswith("/host:")
            for line in p["lines"] for n, s, d in line["events"]]


def test_every_phase_is_on_the_host_plane_and_no_wrapper_is(capture):
    names = {n for n, _s, _d in _host(capture["planes"])}
    assert set(PHASES) | {"sidecar.empty"} <= names
    # the wrappers and the parked threads were spans (the ring has
    # them), and stayed off the profiler's clock
    assert {"dispatch.flush", "signdispatch.flush", "dispatch.wait"} <= set(
        capture["ring"])
    assert not names & {"dispatch.flush", "signdispatch.flush",
                        "dispatch.wait", "dispatch.launch", "client.write"}
    assert "sidecar.empty" not in capture["ring"]  # an interval, no span


def test_a_gap_inside_a_stage_interval_is_named_after_it(capture):
    host = xplane.host_events(capture["planes"])
    stages = [(s, e) for n, s, e in host if n == "flush.stage"]
    assert stages
    s, e = max(stages, key=lambda se: se[1] - se[0])
    quarter = (e - s) // 4
    assert xplane.host_label(host, s + quarter, e - quarter) == "host:flush.stage"


def test_histogram_counts_follow_launches_and_requests(capture):
    snap = capture["snap"]
    counts = _phase_counts(snap)
    v = snap["verify.device_batch.count"]
    s = snap["sign.device_batch.count"]
    assert (v, s) == (VERIFY_FLUSHES, 1)
    for phase in FLUSH_PHASES[1:]:
        assert counts[phase, "verify"] == v, phase
    # staging is two intervals: the per-item tier split, the operands
    assert counts["flush.stage", "verify"] == 2 * v
    # linger and scatter belong to every flush of a pool, the sidecar's
    # two host-tier flushes (a CPU backend pins host) among them
    assert snap["dispatch.flushes"] == v + 2
    assert counts["dispatch.linger", "verify"] == v + 2
    assert counts["flush.scatter", "verify"] == v + 2
    # a sign launch is two launches (the pow, and the fault check that
    # verifies what it made) behind one linger and one scatter; its
    # staging starts with the per-item encodings (a third interval) and
    # the pow's unpack ends with the CRT recombination (a second one)
    assert counts["dispatch.linger", "sign"] == s
    assert counts["flush.scatter", "sign"] == s
    assert counts["flush.launch", "sign"] == 2 * s
    assert counts["flush.fetch", "sign"] == 2 * s
    assert counts["flush.stage", "sign"] == 3 * s
    assert counts["flush.unpack", "sign"] == 2 * s
    # the sidecar's two ends, per request; the reply is two intervals
    # (encode inside the admission slot, authenticate + send after it)
    requests = snap["sidecar.serve.count{op=verify}"]
    assert requests == 2
    assert counts["sidecar.decode", "verify"] == requests
    assert counts["sidecar.reply", "verify"] == 2 * requests
    assert counts["sidecar.call", "verify"] == requests
    # nothing else is labelled: ``op`` is the only key (checked in
    # _phase_counts), verify / sign the only values seen here
    assert {op for _p, op in counts} == {"verify", "sign"}


def test_phases_add_up_to_the_flush_seen_from_outside(capture):
    snap = capture["snap"]
    inside = sum(v for k, v in snap.items()
                 if k.startswith(tuple(p + ".sum" for p in FLUSH_PHASES)))
    outside = (snap["dispatch.flush.seconds.sum"]
               + snap["signdispatch.flush.seconds.sum"])
    assert 0.9 * outside <= inside <= outside


# -- the interval in which the sidecar holds no request ---------------------


def test_empty_seconds_grow_only_while_nothing_is_admitted_or_waiting(
    monkeypatch,
):
    now = [100.0]
    # the queue's own view of the clock, not the process's
    monkeypatch.setattr(
        admission, "time", types.SimpleNamespace(monotonic=lambda: now[0]))
    metrics.reset()
    q = admission.AdmissionQueue(max_inflight=1, max_queue=4, max_wait=5.0,
                                 metric="sidecar.shed")
    grown = lambda: metrics.snapshot().get("sidecar.empty.seconds", 0)  # noqa: E731
    now[0] += 2.0                      # empty since construction
    assert q.acquire("verify")         # 0 -> 1 closes the interval
    assert grown() == pytest.approx(2.0)
    now[0] += 3.0                      # busy: nothing grows
    waiter = threading.Thread(target=q.acquire, args=("verify",))
    waiter.start()
    until = time.monotonic() + 5
    while q.depth() != (1, 1) and time.monotonic() < until:
        time.sleep(0.001)
    assert q.depth() == (1, 1)
    now[0] += 4.0
    q.release()                        # 1 in flight + 1 waiting -> the waiter
    waiter.join(5)
    assert not waiter.is_alive() and q.depth() == (1, 0)
    assert grown() == pytest.approx(2.0)
    q.release()                        # 1 -> 0 opens the next interval
    now[0] += 1.5
    assert grown() == pytest.approx(2.0)  # counted when it closes
    assert q.acquire("sign")
    assert grown() == pytest.approx(3.5)
    q.release()


def test_another_tier_records_no_empty_interval(monkeypatch):
    calls = []
    monkeypatch.setattr(trace, "annotate", lambda *a, **k: calls.append(a))
    metrics.reset()
    q = admission.AdmissionQueue(metric="gateway.shed")
    for _ in range(3):
        assert q.acquire("read")
        q.release()
    assert not calls
    assert not [k for k in metrics.snapshot() if "empty" in k]


# -- the hook unset, and tracing off ----------------------------------------


class _Bridge:
    def __init__(self):
        self.entered, self.left = [], []

    def __call__(self, name, **attrs):
        bridge = self

        class _Annotation:
            def __enter__(self):
                bridge.entered.append((name, attrs))

            def __exit__(self, *exc):
                bridge.left.append(name)

        return _Annotation()


@pytest.fixture
def bridge():
    b = _Bridge()
    trace.set_bridge(b)
    yield b
    trace.set_bridge(None)


def test_only_leaf_phases_cross_the_bridge(bridge):
    with trace.span("dispatch.flush", phase="dispatch"):
        with trace.span("dispatch.wait"):
            with trace.leaf("flush.stage", "verify", items=3, bucket=256):
                pass
    assert bridge.entered == [
        ("flush.stage", {"items": 3, "bucket": 256, "op": "verify"})]
    assert bridge.left == ["flush.stage"]
    ann = trace.annotate("sidecar.empty")
    assert ann is not None and trace.annotate("dispatch.wait") is None
    ann.__exit__(None, None, None)
    assert bridge.left[-1] == "sidecar.empty"


def test_without_a_bridge_a_span_calls_nothing():
    trace.set_bridge(None)
    assert not trace.bridged()
    trace.tracer.reset()
    with trace.leaf("flush.stage", "verify"):
        pass
    assert trace.annotate("sidecar.empty") is None
    assert [s["name"] for s in trace.tracer.export()["spans"]] == ["flush.stage"]


def test_with_tracing_off_a_span_records_nothing_and_calls_nothing(
    bridge, monkeypatch,
):
    monkeypatch.setattr(trace.tracer, "enabled", False)
    trace.tracer.reset()
    metrics.reset()
    with trace.leaf("flush.stage", "verify", items=3) as sp:
        sp.attrs["bucket"] = 256  # call sites never branch on enablement
    with trace.span("flush.launch"):
        pass
    assert trace.annotate("sidecar.empty") is None
    assert not bridge.entered and not bridge.left
    assert trace.tracer.export()["spans"] == []
    # the histogram is a counter of the registry, not tracing: an
    # untraced, BFTKV_TRACE=off run still prints the split
    assert metrics.snapshot()["flush.stage.count{op=verify}"] == 1


# -- kernels say which chain they are ---------------------------------------


def _module_name(jitted, *args) -> str:
    if args == (None,):
        return "jit_" + jitted.__wrapped__.__name__
    return re.search(r"module @(\S+)", jitted.lower(*args).as_text()).group(1)


def _shapes(tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype), tree)


def _served_path_modules():
    """(expected module name, jitted function, operands) of every
    program the served path can launch, lowered at a small shape."""
    t = 8  # rows: a multiple of the 8-device test mesh
    u8 = lambda *shape: jax.ShapeDtypeStruct(shape, np.uint8)  # noqa: E731
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.float32)  # noqa: E731
    idx = jax.ShapeDtypeStruct((t,), np.int32)
    n2048 = (1 << 2047) + 973
    key = _shapes(rns.stack_key_rows([rns.context().key_rows(n2048)] * 2))
    rows = _shapes(rns.stack_key_rows([rns.context().key_rows(n2048)] * t))
    p512 = (1 << 511) + 187
    key512 = _shapes(rns.stack_key_rows(
        [rns.context(32, 512).key_rows(p512)] * 2))
    d = rns.DIGITS
    verify = (u8(t, 2 * d), u8(t, 2 * d), idx, key)
    pow512 = (u8(t, 64), u8(128, t), idx, key512)
    yield "jit_rns_verify", rns._jitted_verify(), (
        f32(t, 2 * d), f32(t, 2 * d), rows)
    yield "jit_rns_verify_gather", rns._jitted_verify_gather(), verify
    yield "jit_rns_pow_512", rns._jitted_pow(32, 512, False), pow512
    yield ("jit_rns_verify_gather_sharded",
           rns._jitted_verify_gather_sharded(), verify)
    yield "jit_rns_pow_512_sharded", rns._jitted_pow_sharded(32, 512), pow512
    # tracing the P-256 ladder takes 100 s on this backend: the name
    # jit gives the module is the wrapped function's, read from there
    yield "jit_ec_rns_scalar_mult", ec_rns._scalar_mult_fn(), None
    yield "jit_dispatch_rtt_probe", jax.jit(dispatch.dispatch_rtt_probe), (
        jax.ShapeDtypeStruct((256, 128), np.uint32),)
    pc = pallas_rns._pad_consts(32, 512)
    yield "jit_rns_pow_prep", pallas_rns._pow_prep(pc.k, pc.kpad), (
        idx, key512)
    pcv = pallas_rns._pad_consts(d, 2048)
    yield "jit_rns_verify_prep", pallas_rns._verify_prep(pcv.k, pcv.kpad), (
        idx, key)


def test_no_program_of_the_served_path_is_called_g():
    seen = []
    for want, jitted, args in _served_path_modules():
        name = _module_name(jitted, *(args or (None,)))
        assert name == want
        assert not re.match(r"jit_g\b", name) and "lambda" not in name
        seen.append(name)
    assert len(set(seen)) == len(seen)  # each tells its chain from the others


def test_pallas_chains_carry_their_names():
    src = open(pallas_rns.__file__).read()
    for name in ("rns_pow_chain", "rns_verify_chain"):
        assert f'name="{name}"' in src
    for fn in (pallas_rns._pow_call(32, 512, 8, True),
               pallas_rns._verify_call(rns.DIGITS, 2048, 8, True)):
        assert fn.__name__ in ("rns_pow_pallas", "rns_verify_pallas")


# -- the trace hook inside the sidecar --------------------------------------


@pytest.mark.parametrize(
    "query,dirname",
    [
        ("seconds=0&name=smoke", "smoke"),
        ("seconds=0&name=../../etc/passwd", ".._.._etc_passwd"),
        ("seconds=0&name=..", "trace"),
        ("seconds=0&name=", "trace"),
        ("seconds=nan", "trace"),
        ("seconds=1e9&name=" + "x" * 200, "x" * 64),
    ],
)
def test_profile_capture_is_confined_to_its_root(monkeypatch, query, dirname):
    started, slept = [], []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: started.append((d, kw)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr("time.sleep", slept.append)
    root = os.path.join(tempfile.gettempdir(), "bftkv-profile")
    outdir = ops.capture_profile("/debug/profile?" + query)
    assert outdir == os.path.join(root, dirname)
    assert os.path.dirname(os.path.normpath(outdir)) == root
    assert started[0][0] == outdir and 0.0 <= slept[0] <= 30.0


def test_sidecar_stats_port_serves_the_profile_hook(tmp_path):
    sock = os.path.join(tempfile.mkdtemp(prefix="bftkv-ph-"), "s.sock")
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()
    srv, _t = vs.serve("unix:" + sock, max_batch=64,
                       stats=f"127.0.0.1:{port}")
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/profile?seconds=0.1&name=../ph",
            timeout=120,
        ) as res:
            body = res.read().decode()
        outdir = os.path.join(tempfile.gettempdir(), "bftkv-profile", ".._ph")
        assert body == f"trace captured to {outdir}\n"
        assert xplane.find_xplane(outdir).endswith(".xplane.pb")
    finally:
        srv.stats_httpd.shutdown()
        srv.shutdown()
        srv.server_close()
        srv.service.stop()
