"""The reduction from a trace to busy / idle / per-module time, on a
small recorded structure with known answers, and the op and byte
functions against hand counts."""

import json
import os

import pytest

from benchmarks.reduce import rns_counts, xplane

US = 1_000

#: Two devices' worth of a tiny trace.  Device 0: three operations, two
#: of them overlapping -> busy 30+50 = 80 us; gaps of 20 us and 890 us.
TRACE = [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ("jit_g(1)", 0, 60 * US), ("jit_h(2)", 80 * US, 50 * US)]},
        {"name": "XLA Ops", "events": [
            ("fusion.1", 0, 20 * US), ("fusion.2", 10 * US, 20 * US),
            ("dot.3", 50 * US, 10 * US), ("fusion.4", 80 * US, 40 * US)]},
    ]},
    {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": []}]},
    {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ("flush_worker(stage)", 121 * US, 700 * US), ("tiny", 0, 1 * US)]}]},
]


def test_union_merges_overlaps():
    assert xplane.union([(0, 20), (10, 30), (50, 60), (60, 61), (5, 5)]) == [
        (0, 30), (50, 61)]


def test_known_busy_idle_and_modules():
    s = xplane.summarize(TRACE, window_s=1e-3)
    assert s["device_planes"] == 2 and s["devices_used"] == 1
    assert s["busy_s"] == pytest.approx(80e-6)
    assert s["module_s"] == pytest.approx(110e-6)
    assert s["modules"][0] == ("jit_g(1)", pytest.approx(60e-6))
    assert s["op_events"] == 4 and s["module_events"] == 2
    assert [g[1] for g in s["idle_gaps"]] == [pytest.approx(20e-6)] * 2


def test_gap_is_named_by_the_host_span_that_covers_it():
    trace = json.loads(json.dumps(TRACE))
    trace[0]["lines"][1]["events"].append(["late", 1000 * US, 10 * US])
    s = xplane.summarize(trace, window_s=2e-3)
    assert s["idle_gaps"][0][0] == "host:flush_worker"
    assert s["idle_gaps"][0][1] == pytest.approx(880e-6)
    assert s["idle_gaps"][1][0] == "host:unattributed"


def test_idle_share_reader_returns_nothing_without_device_time():
    from benchmarks.readers import device_idle_share, rns_roofline, window_mfu

    empty = {"busy_s": 0.0, "window_s": 1.0, "module_s": 0.0,
             "verify_items": 0, "sign_rows": 0, "devices_used": 0}
    ctx = {"trace": empty, "device": {"kind": "TPU v5 lite"}}
    assert device_idle_share.read(ctx, {}) is None
    assert rns_roofline.read(ctx, {}) is None
    assert window_mfu.read(ctx, {}) is None
    s = xplane.summarize(TRACE, window_s=1e-3)
    s.update(verify_items=1000, sign_rows=100)
    ctx["trace"] = s
    assert device_idle_share.read(ctx, {}) == pytest.approx(92.0)
    # 1000 verifies + 100 rows need 45.2 GFLOP: 229.4 us at 197 TFLOP/s
    assert rns_roofline.read(ctx, {}) == pytest.approx(100 * 229.446e-6 / 110e-6, rel=1e-4)
    assert s["roofline_bound"] == "flops"


def test_load_reads_an_xplane_file(tmp_path):
    """``load`` on a real (tiny) XSpace, made from its text form."""
    from jax.profiler import ProfileData

    text = """
    planes {
      name: "/device:TPU:0"
      lines { name: "XLA Ops" timestamp_ns: 1000
        events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
        events { metadata_id: 1 offset_ps: 50000000 duration_ps: 10000000 } }
      event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
    }
    planes { name: "/host:CPU" }
    planes { name: "Task Environment" }
    """
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    planes = xplane.load(str(path))
    assert [p["name"] for p in planes] == ["/device:TPU:0", "/host:CPU"]
    events = planes[0]["lines"][0]["events"]
    assert [(n, d) for n, _s, d in events] == [("fusion.1", 20000), ("fusion.1", 10000)]
    assert xplane.summarize(planes, 1e-3)["busy_s"] == pytest.approx(30e-6)


def test_counts_against_hand_counts():
    assert rns_counts.channels(2048) == 188
    assert rns_counts.channels(1024) == 94
    # one Montgomery product: 12 dots x 2 x k x (k+1)
    assert rns_counts.mont_flops(188) == 12 * 2 * 188 * 189 == 852_768
    # verify: 19 products + digit conversion (2 operands, 6 dots, 256 x 377)
    assert rns_counts.verify_flops() == 19 * 852_768 + 1_158_144 == 17_360_736
    assert rns_counts.sign_row_flops() == 1299 * 12 * 2 * 94 * 95 == 278_401_680
    assert rns_counts.verify_bytes() == 2 * 256 + 5
    assert rns_counts.sign_row_bytes() == 128 + 256 + 4 + 128


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        rns_counts.load_peaks("TPU v9 imaginary")
    peaks = rns_counts.load_peaks("TPU v5 lite")
    assert peaks["flops_per_s"] == 197e12 and peaks["bytes_per_s"] == 819e9
    assert os.path.exists(os.path.join(os.path.dirname(rns_counts.__file__), "peaks.json"))
