"""The sidecar is warm before it listens (cmd/verify_sidecar.py).

On a device backend, constructing the service launches one full batch
of every bucket shape the dispatchers can emit — through the
dispatchers — and ``serve()`` binds its socket only afterwards.  The
bucket arithmetic and the failure behaviour are checked here against
stand-in dispatchers that answer from the host (the real launches are
``chip_smoke.py``'s business; the programs' acceptance by the chip's
compiler is tests/test_chip_compile.py's).
"""

import os
import types

import numpy as np
import pytest

from bftkv_tpu.cmd import verify_sidecar as vs
from bftkv_tpu.crypto import rsa
from bftkv_tpu.metrics import registry as metrics
from bftkv_tpu.ops import dispatch


class _HostDispatcher:
    """submit() answered by host crypto, one computation per distinct
    item (warm-up batches repeat one item thousands of times)."""

    def __init__(self, fn, max_batch, **attrs):
        self.fn, self.max_batch = fn, max_batch
        self.batches: list[int] = []
        self.__dict__.update(attrs)

    def submit(self, items):
        self.batches.append(len(items))
        memo: dict = {}
        return [
            memo[id(it)] if id(it) in memo
            else memo.setdefault(id(it), self.fn(*it))
            for it in items
        ]


def _service(*, max_batch=4096, verify_crossover=16, sign_threshold=16,
             verify_fn=rsa.verify_host):
    svc = vs.SidecarService.__new__(vs.SidecarService)
    svc._cal = {"prefer_host": False}
    svc.verify = _HostDispatcher(
        verify_fn, max_batch,
        verifier=types.SimpleNamespace(host_threshold=verify_crossover),
    )
    svc.verify.submit = (
        lambda items, f=svc.verify.submit: np.asarray(f(items), dtype=bool)
    )
    svc.sign = _HostDispatcher(
        rsa.sign, max_batch,
        signer=types.SimpleNamespace(host_threshold=sign_threshold),
    )
    svc.modexp = _HostDispatcher(
        pow, max_batch, device_threshold=max(16, verify_crossover),
    )
    return svc


def _warmed(svc, role):
    return [s["items"] for s in svc.warmup["shapes"] if s["role"] == role]


@pytest.mark.parametrize(
    "max_batch,crossover,verify,sign",
    [
        # The defaults on a local chip: every power-of-two bucket a
        # flush can pad to — verify 256…4096, sign rows 64…8192.
        (4096, 16, [256, 512, 1024, 2048, 4096],
         [32, 64, 128, 256, 512, 1024, 2048, 4096]),
        # A slow link prices the crossover high: buckets nobody can
        # reach are not compiled.
        (4096, 600, [600, 1200, 2400, 4096],
         [32, 64, 128, 256, 512, 1024, 2048, 4096]),
        # A small service (tests, --max-batch): one floor bucket each.
        (64, 16, [64], [32, 64]),
    ],
)
def test_warmup_covers_every_launchable_bucket(
    max_batch, crossover, verify, sign
):
    svc = _service(max_batch=max_batch, verify_crossover=crossover)
    dispatch.note_launch_rtt(12.0)  # "a compile-laden round trip"
    metrics.incr("verify.device", 7)
    svc.warmup = svc._warm()
    assert _warmed(svc, "verify") == verify == svc.verify.batches
    assert _warmed(svc, "sign") == sign == svc.sign.batches
    assert _warmed(svc, "modexp") == svc.modexp.batches
    assert len(svc.modexp.batches) == 1
    # Warm-up is not traffic: neither its round trips nor its items
    # survive into what tenants and the crossover see.
    assert dispatch.observed_launch_rtt() is None
    assert metrics.snapshot().get("verify.device", 0) == 0
    cache = svc.warmup["compile_cache"]
    assert cache["dir"] and cache["warm"] is False  # nothing was loaded


def test_warmup_splits_each_shape_by_what_jax_reported(monkeypatch):
    """trace / lower / load / compile come from ``jax.monitoring``'s
    duration events while the shape ran, run from the time its flushes
    blocked on the device; a kind with no event reads 0."""
    import jax

    svc = _service(max_batch=64)
    inner = svc.verify.submit
    events = {
        "/jax/core/compile/jaxpr_trace_duration": 0.25,
        "/jax/core/compile/jaxpr_to_mlir_module_duration": 0.5,
        "/jax/core/compile/backend_compile_duration": 2.0,
        "/jax/compilation_cache/cache_retrieval_time_sec": 1.5,
        "/jax/some/other/duration": 64.0,
    }

    def submit(items):
        for name, seconds in events.items():
            jax.monitoring.record_event_duration_secs(name, seconds)
        metrics.observe("flush.fetch", 0.125, labels={"op": "verify"})
        return inner(items)

    svc.verify.submit = submit
    try:
        shapes = svc._warm()["shapes"]
    finally:
        jax.monitoring.unregister_event_listener(svc._on_jax_event)
        jax.monitoring.unregister_event_duration_listener(svc._on_jax_duration)
        vs.trace.set_bridge(None)
    split = ("trace_s", "lower_s", "load_s", "compile_s", "run_s")
    by_role = {s["role"]: [s[k] for k in split] for s in shapes}
    assert by_role["verify"] == [0.25, 0.5, 1.5, 0.5, 0.125]
    assert by_role["sign"] == by_role["modexp"] == [0.0] * 5
    assert all(s["seconds"] >= 0 for s in shapes)


def _counting_device_rows(svc):
    """The stand-in's launches count as a dispatcher's would."""
    inner = svc.modexp.submit

    def submit(items):
        metrics.incr("modexp.device", len(items))
        return inner(items)

    svc.modexp.submit = submit


def test_a_declared_ca_builds_its_fragment_class(monkeypatch):
    """``BFTKV_CA_BITS=2048``: the two buckets of 2,048-bit rows under
    the longer exponent class, after everything the identities need;
    undeclared, no such program and no such class in ``warm_rows``."""
    svc = _service(max_batch=64)
    svc.warmup = svc._warm()
    assert _warmed(svc, "fragment") == []
    assert svc.modexp.warm_rows == frozenset({1024})
    assert svc.warmup["ca_bits"] == svc.warmup["fragment_rows"] == []

    monkeypatch.setenv("BFTKV_CA_BITS", "2048")
    svc = _service(max_batch=64)
    _counting_device_rows(svc)
    svc.warmup = svc._warm()
    assert _warmed(svc, "fragment") == [64, 128]
    assert svc.modexp.batches[-2:] == [64, 128]
    assert {s["bits"] for s in svc.warmup["shapes"]
            if s["role"] == "fragment"} == {2048}
    assert svc.modexp.warm_rows == frozenset({1024, (2048, 4160)})
    assert svc.sign.signer.warm_rows == frozenset({1024})
    assert svc.warmup["ca_bits"] == [2048]
    assert svc.warmup["fragment_rows"] == [(2048, 4160)]


def test_a_declared_4096_bit_ca_builds_the_wide_fragment_class(monkeypatch):
    """``BFTKV_CA_BITS=4096``: the wide chain's class (4096, 8256),
    warmed and checked like the 2,048-bit class's at its one bucket — a
    launch is one 64-row tile there (``rns.long_exp_rows``)."""
    monkeypatch.setenv("BFTKV_CA_BITS", "4096")
    svc = _service(max_batch=64)
    _counting_device_rows(svc)
    svc.warmup = svc._warm()
    assert _warmed(svc, "fragment") == [64]
    assert {s["bits"] for s in svc.warmup["shapes"]
            if s["role"] == "fragment"} == {4096}
    assert svc.modexp.warm_rows == frozenset({1024, (4096, 8256)})
    assert svc.warmup["ca_bits"] == [4096]
    assert svc.warmup["fragment_rows"] == [(4096, 8256)]


def test_a_fragment_launch_that_misses_the_device_refuses_to_start(
    monkeypatch,
):
    monkeypatch.setenv("BFTKV_CA_BITS", "2048")
    svc = _service(max_batch=64)  # answers right, counts no device row
    with pytest.raises(RuntimeError, match="did not ride the device"):
        svc._warm()
    monkeypatch.setenv("BFTKV_CA_BITS", "a-width")
    with pytest.raises(ValueError, match="BFTKV_CA_BITS"):
        _service(max_batch=64)._warm()


def test_wrong_result_in_warmup_refuses_to_start():
    svc = _service(max_batch=64, verify_fn=lambda m, s, k: True)
    with pytest.raises(RuntimeError, match="wrong verdicts"):
        svc._warm()


def test_cpu_backend_has_nothing_to_warm_and_binds_after_construction(
    tmp_path, monkeypatch
):
    path = tmp_path / "warm.sock"
    seen = {}
    real_init = vs.SidecarService.__init__

    def init(self, **kw):
        seen["socket_exists_during_construction"] = os.path.exists(path)
        real_init(self, **kw)

    monkeypatch.setattr(vs.SidecarService, "__init__", init)
    srv, _t = vs.serve(f"unix:{path}")
    try:
        assert seen == {"socket_exists_during_construction": False}
        assert os.path.exists(path)
        assert srv.service.warmup == {"shapes": [], "seconds": 0.0}
        plane = srv.service.stats()["device_plane"]
        assert plane["device"]["platform"] == "cpu"
        assert plane["kernels"]["verify"] == "xla"
        assert plane["launched"]["verify"]["items"] == 0
    finally:
        srv.service.stop()
        srv.shutdown()
        srv.server_close()
