"""Device-resident crypto plane (ISSUE 19): persistent staging rings
(ops/devbuf), async mega-batch dispatch, and online recalibration.

All tier-1 tests here run on stub kernels — the real CPU-XLA RNS pow
compile costs ~23 s per shape and belongs to the slow tier.  The stub
DECODES the staged device operands (base halves, exponent nibbles,
CRT-reconstructed moduli) and answers from host ``pow``, so a staging
bug — wrong live rows, wrong pad broadcast, a slot reused while a
flush is in flight — shows up as a bit-for-bit mismatch against the
independently computed expected values.
"""

from __future__ import annotations

import json
import threading
import urllib.request

import numpy as np
import pytest

pytest.importorskip("jax")

from bftkv_tpu.metrics import registry as metrics  # noqa: E402
from bftkv_tpu.ops import devbuf, dispatch  # noqa: E402
from bftkv_tpu.ops import rns  # noqa: E402

M512 = (1 << 511) + 187  # odd pseudo-moduli, two limb-width classes
M768 = (1 << 767) + 183


# -- buffer ring ownership --------------------------------------------------


def test_ring_never_hands_out_inflight_slot():
    ring = devbuf.BufferRing(
        "t:ring", lambda: {"a": np.zeros(4)}, slots=2, width="t"
    )
    s1 = ring.acquire()
    s2 = ring.acquire()
    assert s1 is not None and s2 is not None and s1 is not s2
    assert s1.in_flight and s2.in_flight
    # Saturated: acquire must NOT block liveness — None tells the
    # caller to allocate fresh, and the overflow is counted.
    assert ring.acquire() is None
    assert ring.overflows == 1
    f = ring.fresh()
    assert f.in_flight and f is not s1 and f is not s2
    ring.release(f)  # unpooled: no-op, never re-enters the ring
    assert ring.acquire() is None
    ring.release(s1)
    s3 = ring.acquire()
    assert s3 is s1 and s3.seq == 2  # recycled only after release
    with pytest.raises(AssertionError):
        ring.release(s2)
        ring.release(s2)  # double release is a detected bug, not silent


def test_ring_acquire_waits_for_release():
    ring = devbuf.BufferRing(
        "t:wait", lambda: {"a": np.zeros(1)}, slots=1, width="t"
    )
    s = ring.acquire()
    t = threading.Timer(0.05, ring.release, args=(s,))
    t.start()
    try:
        got = ring.acquire(timeout=2.0)
        assert got is s  # the release woke the waiter within timeout
    finally:
        t.cancel()
        ring.release(got)


# -- stub device kernel -----------------------------------------------------


def _crt_int(ctx, residues) -> int:
    """Rebuild the modulus from its staged base-prime residues."""
    m = 0
    for r, p in zip(residues, ctx.pb):
        mi = ctx.M // p
        m += ((int(r) * pow(mi % p, -1, p)) % p) * mi
    return m % ctx.M


def _stub_jitted_pow(seen: list, crash_bases: frozenset = frozenset()):
    """A drop-in for ``rns._jitted_pow`` that decodes the STAGED
    buffers (not the caller's lists) and answers from host ``pow`` —
    staging corruption cannot cancel out."""

    def fake(digits, n_bits, donate=False):
        ctx = rns.context(digits, n_bits)
        k = ctx.k

        def g(bh, nt, ix, ukey):
            seen.append(
                {
                    "digits": digits,
                    "rings": devbuf.stats(),
                }
            )
            mods_u = [_crt_int(ctx, row[:k]) for row in np.asarray(ukey[0])]
            out = np.empty((bh.shape[0], k), dtype=np.float32)
            for j in range(bh.shape[0]):
                b = int.from_bytes(bh[j].tobytes(), "little")
                if b in crash_bases:
                    raise RuntimeError("injected kernel crash")
                e = 0
                for nib in nt[:, j]:
                    e = (e << 4) | int(nib)
                m = mods_u[int(ix[j])]
                v = pow(b, e, m)
                for i, p in enumerate(ctx.pb):
                    mi = ctx.M // p
                    out[j, i] = (v % p) * pow(mi % p, -1, p) % p
            return out

        return g

    return fake


@pytest.fixture()
def stub_kernel(monkeypatch):
    seen: list = []
    monkeypatch.setattr(rns, "_jitted_pow", _stub_jitted_pow(seen))
    monkeypatch.setattr(rns, "_shardable", lambda _batch: False)
    devbuf.reset()
    metrics.reset()
    yield seen
    devbuf.reset()
    metrics.reset()


# -- staged parity: two width classes, interleaved tenants ------------------


def test_interleaved_widths_scatter_back_bit_for_bit(stub_kernel):
    """Two tenants interleave RSA-512- and RSA-768-class items through
    the dispatcher; every scattered result must equal host
    ``pow`` exactly, and no staging slot may be reused while its
    launch is in flight."""
    d = dispatch.ModexpDispatcher(
        max_batch=256, max_wait=0.02, calibrate=False, device_threshold=2
    ).start()
    results: dict[int, list[int]] = {}
    try:

        def tenant(tid: int) -> None:
            items = [
                (3 + tid * 100 + i, 65537, M512 if i % 2 else M768)
                for i in range(8)
            ]
            results[tid] = (d.submit(items), items)

        threads = [
            threading.Thread(target=tenant, args=(t,)) for t in range(2)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        d.stop()
    for got, items in results.values():
        assert list(got) == [pow(b, e, m) for b, e, m in items]
    # Both width classes launched through the device tier...
    assert {s["digits"] for s in stub_kernel} == {32, 48}
    # ...each with its staging slot held in flight DURING the kernel
    # call (the stub snapshots ring state from inside the launch).
    for s in stub_kernel:
        busy = [r for r in s["rings"].values() if r["in_flight"] > 0]
        assert busy, "kernel ran without an in-flight staging slot"
    # All slots returned to their rings once the flushes completed.
    for r in devbuf.stats().values():
        assert r["in_flight"] == 0 and r["acquires"] >= 1
    snap = metrics.snapshot()
    assert snap.get("modexp.device", 0) == 16
    # A pow launch is compute, not a round trip: the EWMA that prices
    # the verify and sign crossover is not this pool's to feed.
    assert "dispatch.launch_rtt" not in snap


def test_kernel_crash_mid_flush_releases_slot_and_falls_back(monkeypatch):
    """A launch that dies mid-flush (device fault, tenant-poisoned
    batch) must release its staging slot — not leak it in flight — and
    the flush still answers every caller via the host tier."""
    seen: list = []
    sentinel = 424243  # base staged for the doomed 512-class launch
    monkeypatch.setattr(
        rns, "_jitted_pow", _stub_jitted_pow(seen, frozenset({sentinel}))
    )
    monkeypatch.setattr(rns, "_shardable", lambda _batch: False)
    devbuf.reset()
    metrics.reset()
    d = dispatch.ModexpDispatcher(
        max_batch=256, max_wait=0.01, calibrate=False, device_threshold=2
    ).start()
    try:
        items = [(sentinel, 65537, M512), (5, 65537, M512), (7, 3, M768)]
        got = d.submit(items)
        assert list(got) == [pow(b, e, m) for b, e, m in items]
        # The crashed width group fell back to host; the healthy one
        # (768-class) still answered from the stub device tier.
        snap = metrics.snapshot()
        assert snap.get("modexp.host", 0) >= 2
        assert snap.get("modexp.device", 0) == 1
        for r in devbuf.stats().values():
            assert r["in_flight"] == 0  # the crash released the slot
        # The ring is healthy: the next flush reuses it and succeeds.
        ok = d.submit([(11, 65537, M512), (13, 65537, M512)])
        assert list(ok) == [pow(11, 65537, M512), pow(13, 65537, M512)]
    finally:
        d.stop()
        devbuf.reset()
        metrics.reset()


def test_power_mod_rns_devbuf_off_matches_on(stub_kernel, monkeypatch):
    """BFTKV_DISPATCH_DEVBUF=off: throwaway staging arrays, identical
    results — the ring is an optimization, never a semantic."""
    bases, exps, mods = [9, 10, 11], [65537, 3, 17], [M512] * 3
    want = [pow(b, e, m) for b, e, m in zip(bases, exps, mods)]
    assert rns.power_mod_rns(bases, exps, mods, n_bits=512) == want
    assert devbuf.stats()  # ring path engaged
    devbuf.reset()
    monkeypatch.setenv("BFTKV_DISPATCH_DEVBUF", "off")
    assert rns.power_mod_rns(bases, exps, mods, n_bits=512) == want
    assert devbuf.stats() == {}  # no ring was minted


# -- async dispatch layer ---------------------------------------------------


class _FakeAsyncDispatcher(dispatch._BatchDispatcher):
    """Deterministic async subclass: launches record order, block on
    per-launch events, and can be told to raise at completion."""

    name = "modexpdispatch"  # registered metric prefix

    def __init__(self, **kw):
        super().__init__(**kw)
        self.launched: list = []
        self.finalized: list = []
        self.gates: dict = {}
        self.fail = set()

    def _run_batch(self, items):
        return [("sync", it) for it in items]

    def _launch_batch(self, items):
        tag = items[0]
        self.launched.append(tag)
        gate = self.gates.get(tag)

        def complete():
            if gate is not None:
                assert gate.wait(10)
            if tag in self.fail:
                raise RuntimeError(f"completion failed: {tag}")
            self.finalized.append(tag)
            return [("async", it) for it in items]

        return complete


def test_async_flushes_finalize_fifo_and_overlap():
    """Flush N+1 must launch while flush N's completion is still
    pending (the overlap the async plane exists for), and completions
    scatter FIFO so callers observe synchronous-path ordering."""
    d = _FakeAsyncDispatcher(
        max_batch=8, max_wait=0.005, calibrate=False, pipeline=1
    )
    assert d._async  # BFTKV_DISPATCH_ASYNC defaults on
    d.start()
    assert d._drain is not None
    g1, g2 = threading.Event(), threading.Event()
    d.gates.update({"a1": g1, "b1": g2})
    out: dict = {}
    try:
        t1 = threading.Thread(
            target=lambda: out.update(r1=d.submit(["a1", "a2"]))
        )
        t1.start()
        # Wait for launch 1 to be dispatched (completion gated open).
        deadline = threading.Event()
        for _ in range(200):
            if d.launched:
                break
            deadline.wait(0.01)
        assert d.launched == ["a1"]
        t2 = threading.Thread(
            target=lambda: out.update(r2=d.submit(["b1"]))
        )
        t2.start()
        # The second flush launches while the first is still gated:
        # host assembly of N+1 overlapped device execution of N.
        for _ in range(200):
            if len(d.launched) == 2:
                break
            deadline.wait(0.01)
        assert d.launched == ["a1", "b1"]
        assert not d.finalized
        g2.set()  # completion 2 ready FIRST...
        deadline.wait(0.05)
        assert d.finalized == []  # ...but FIFO holds it behind 1
        g1.set()
        t1.join(10)
        t2.join(10)
        assert d.finalized == ["a1", "b1"]
        assert out["r1"] == [("async", "a1"), ("async", "a2")]
        assert out["r2"] == [("async", "b1")]
    finally:
        g1.set()
        g2.set()
        d.stop()
    assert d._drain is None  # stop() drained the completion thread


def test_async_completion_error_reaches_callers_only_of_that_flush():
    d = _FakeAsyncDispatcher(
        max_batch=4, max_wait=0.002, calibrate=False, pipeline=1
    ).start()
    d.fail.add("bad")
    try:
        with pytest.raises(RuntimeError, match="completion failed"):
            d.submit(["bad"])
        assert d.submit(["fine"]) == [("async", "fine")]
    finally:
        d.stop()


def test_async_off_restores_synchronous_flush(monkeypatch):
    """BFTKV_DISPATCH_ASYNC=off: no drain thread, _launch_batch never
    consulted — the pre-r11 synchronous flush, byte for byte."""
    monkeypatch.setenv("BFTKV_DISPATCH_ASYNC", "off")

    class _NeverAsync(_FakeAsyncDispatcher):
        def _launch_batch(self, items):
            pytest.fail("_launch_batch called with ASYNC=off")

    d = _NeverAsync(max_batch=4, max_wait=0.002, calibrate=False).start()
    try:
        assert not d._async and d._drain is None
        assert d.submit(["x", "y"]) == [("sync", "x"), ("sync", "y")]
    finally:
        d.stop()


# -- calibration lifecycle --------------------------------------------------


def test_crossover_override_and_recalibrate(monkeypatch):
    try:
        monkeypatch.setenv("BFTKV_DISPATCH_CROSSOVER", "48")
        cal = dispatch.calibration(force=True)
        assert cal["source"] == "override"
        assert cal["verify_crossover"] == 48
        assert cal["prefer_host"] is False
        # <= 0 pins always-host regardless of backend.
        monkeypatch.setenv("BFTKV_DISPATCH_CROSSOVER", "0")
        cal = dispatch.calibration(force=True)
        assert cal["prefer_host"] is True
        assert cal["verify_crossover"] == dispatch.ALWAYS_HOST
        # recalibrate() re-applies the fresh verdict to installed
        # dispatchers without restarting them.
        monkeypatch.setenv("BFTKV_DISPATCH_CROSSOVER", "33")
        d = dispatch.install(
            dispatch.VerifyDispatcher(max_batch=8, max_wait=0.001)
        )
        try:
            cal = dispatch.recalibrate()
            assert cal["verify_crossover"] == 33
            assert d.verifier.host_threshold == 33
        finally:
            dispatch.uninstall()
    finally:
        # Un-cache the override so later tests see a real probe.
        monkeypatch.delenv("BFTKV_DISPATCH_CROSSOVER", raising=False)
        dispatch.calibration(force=True)


def test_launch_rtt_ewma_feeds_observed_calibration(monkeypatch):
    monkeypatch.setattr(dispatch, "_LAUNCH_RTT_EWMA", None)
    dispatch.note_launch_rtt(0.100)
    dispatch.note_launch_rtt(0.200)
    rtt = dispatch.observed_launch_rtt()
    assert rtt == pytest.approx(0.8 * 0.100 + 0.2 * 0.200)
    # CPU backends stay pinned no matter what the EWMA says — the
    # CPU-XLA kernels lose at every batch size (the r05 regression).
    cal = dispatch.calibration(force=True)
    assert cal["backend"] != "cpu" or cal["prefer_host"] is True


# -- sidecar: /recalibrate hook + device_plane stats ------------------------


def test_sidecar_recalibrate_hook_and_device_plane_stats(tmp_path):
    from bftkv_tpu.cmd import verify_sidecar as vs

    addr = f"unix:{tmp_path}/devplane.sock"
    stats = "127.0.0.1:19731"
    srv, _t = vs.serve(addr, stats=stats)
    try:
        metrics.reset()
        with urllib.request.urlopen(
            f"http://{stats}/recalibrate", timeout=10
        ) as r:
            cal = json.loads(r.read())
        assert cal["source"] in ("probe", "observed", "override")
        assert "verify_crossover" in cal
        with urllib.request.urlopen(
            f"http://{stats}/info", timeout=10
        ) as r:
            info = json.loads(r.read())
        plane = info["sidecar"]["device_plane"]
        assert plane["calibration"]["backend"] == cal["backend"]
        assert plane["recalibrations"] >= 1
        assert isinstance(plane["buffer_rings"], dict)
        # POST works too (the devtools-hook convention).
        req = urllib.request.Request(
            f"http://{stats}/recalibrate", data=b"", method="POST"
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            assert json.loads(r.read())["backend"] == cal["backend"]
    finally:
        srv.service.stop()
        srv.shutdown()
        srv.server_close()
        metrics.reset()


def test_sidecar_first_launch_triggers_recalibration(tmp_path, monkeypatch):
    """The first accelerator-backed launch (observed_launch_rtt turns
    non-None) re-prices the crossover within the short wake interval,
    not after the full BFTKV_DISPATCH_RECAL_S period."""
    from bftkv_tpu.cmd import verify_sidecar as vs

    monkeypatch.setenv("BFTKV_DISPATCH_RECAL_S", "3600")
    monkeypatch.setattr(
        vs.SidecarService, "_RECAL_TICK", 0.05, raising=False
    )
    addr = f"unix:{tmp_path}/firstlaunch.sock"
    srv, _t = vs.serve(addr)
    try:
        metrics.reset()
        dispatch.note_launch_rtt(0.010)  # "a launch completed"
        deadline = threading.Event()
        # Wait on THIS service's first-launch latch, not the bare
        # counter: a predecessor test's recal thread can outlive its
        # stop() join timeout and bump the global counter after our
        # metrics.reset(), satisfying a counter-only wait early.
        for _ in range(200):
            if (srv.service._recal_seen_rtt
                    and metrics.snapshot().get(
                        "sidecar.recalibrations", 0) >= 1):
                break
            deadline.wait(0.05)
        assert metrics.snapshot().get("sidecar.recalibrations", 0) >= 1
        assert srv.service._recal_seen_rtt is True
    finally:
        srv.service.stop()
        srv.shutdown()
        srv.server_close()
        metrics.reset()


# -- capacity plane wiring --------------------------------------------------


def test_capacity_rows_carry_launch_rtt_and_ring_saturation():
    from bftkv_tpu.obs import capacity

    metrics.reset()
    try:
        metrics.incr("modexpdispatch.flushes", 4)
        metrics.incr("modexpdispatch.items", 64)
        metrics.observe("modexpdispatch.batch", 16)
        metrics.gauge("dispatch.launch_rtt", 0.042)
        metrics.gauge(
            "devbuf.saturation", 0.75, labels={"width": "32"}
        )
        metrics.gauge(
            "devbuf.saturation", 0.25, labels={"width": "ec"}
        )
        idx = capacity._index(metrics.snapshot())
        row = capacity.compute_member(idx, {}, 1.0)["dispatch"]
        assert row["launch_rtt_s"] == pytest.approx(0.042)
        assert row["buffer_rings"] == {"32": 0.75, "ec": 0.25}
        assert row["saturation"] >= 0.75  # ring pressure surfaces
    finally:
        metrics.reset()


# -- real-kernel parity (slow tier) -----------------------------------------


@pytest.mark.slow  # ~23 s/shape CPU-XLA compile: tier-2 only
def test_staged_parity_real_kernel():
    devbuf.reset()
    bases, exps = [3, 5, 7], [65537, 65537, 3]
    mods = [M512, M512, M512]
    want = [pow(b, e, m) for b, e, m in zip(bases, exps, mods)]
    assert rns.power_mod_rns(bases, exps, mods, n_bits=512) == want
    deferred = rns.power_mod_rns(bases, exps, mods, n_bits=512, defer=True)
    assert deferred.wait() == want
    for r in devbuf.stats().values():
        assert r["in_flight"] == 0
