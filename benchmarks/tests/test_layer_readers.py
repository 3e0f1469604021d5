"""The readers this PR's per-layer metrics use, on small made-up
scrapes and gap lists with known answers: what they return where the
program has the counter or span, and that they return nothing (and do
not raise) where it has not, as on the parent commit."""

import json
import os

import pytest

from benchmarks.readers import counter_per_window, counter_ratio, idle_gap_share
from benchmarks.run import Counters

HERE = os.path.dirname(os.path.abspath(__file__))
BEFORE = {"sidecar": {"sidecar.empty.seconds": 10.0,
                      "flush.stage.sum{op=verify}": 1.0,
                      "flush.stage.sum{op=sign}": 0.5,
                      "verify.device": 1000, "sign.device": 100},
          "daemons": {"a01": {"sidecar.call.sum{op=verify}": 2.0,
                              "sidecar.call.count{op=verify}": 100}}}
AFTER = {"sidecar": {"sidecar.empty.seconds": 22.5,
                     "flush.stage.sum{op=verify}": 3.0,
                     "flush.stage.sum{op=sign}": 1.5,
                     "verify.device": 3000, "sign.device": 1100},
         "daemons": {"a01": {"sidecar.call.sum{op=verify}": 5.0,
                             "sidecar.call.count{op=verify}": 300},
                     "a02": {"sidecar.call.sum{op=sign}": 1.0,
                             "sidecar.call.count{op=sign}": 100}}}
GAPS = [["host:sidecar.empty", 0.3], ["host:flush.stage", 0.2],
        ["host:unattributed", 0.1], ["host:dispatch.linger", 0.2],
        ["host:sidecar.empty", 0.2]]


def spec(name: str) -> dict:
    with open(os.path.join(HERE, "..", "layer_metrics", name + ".json")) as f:
        return json.load(f)


def ctx(before=BEFORE, after=AFTER, gaps=GAPS) -> dict:
    return {"ops": 1000, "window_s": 50.0,
            "counters": Counters(before, after),
            "trace": None if gaps is None else {"idle_gaps": gaps}}


def test_empty_share_is_counter_growth_over_the_window():
    args = spec("sidecar_empty_share")["args"]
    assert counter_per_window.read(ctx(), args) == pytest.approx(25.0)


def test_a_counter_the_program_lacks_reads_nothing():
    parent = {"sidecar": {"verify.device": 5}, "daemons": {}}
    c = ctx(parent, parent)
    assert counter_per_window.read(c, spec("sidecar_empty_share")["args"]) is None
    # the parent launches and has items (the denominators grow): a
    # phase it does not time is left out, not read as 0 ms
    parent["sidecar"].update({"verify.device_batch.count": 7})
    c = ctx({"sidecar": {}, "daemons": {}}, parent)
    for name in ("linger_ms_per_launch", "host_stage_ms_per_kitem",
                 "host_unpack_ms_per_kitem", "launch_fetch_ms_per_launch"):
        assert spec(name)["reader"] == "counter_per_window"
        assert counter_per_window.read(c, spec(name)["args"]) is None
    assert spec("sidecar_rtt_mean_ms")["reader"] == "counter_ratio"
    assert counter_ratio.read(c, spec("sidecar_rtt_mean_ms")["args"]) is None
    assert counter_per_window.read({**c, "window_s": 0.0},
                                   spec("sidecar_empty_share")["args"]) is None


def test_phase_metrics_sum_over_the_op_label_and_the_daemons():
    c = ctx()
    # (2.0 + 1.0) s of staging over 2,000 + 1,000 items, in ms per 1,000
    assert counter_per_window.read(
        c, spec("host_stage_ms_per_kitem")["args"]) == pytest.approx(1000.0)
    # a phase that is timed and did not run in the window reads 0
    still = {"sidecar": dict(AFTER["sidecar"]), "daemons": {}}
    assert counter_per_window.read(
        ctx(still, {"sidecar": {**still["sidecar"], "verify.device": 4000},
                    "daemons": {}}),
        spec("host_stage_ms_per_kitem")["args"]) == 0.0
    # (3.0 + 1.0) s over 200 + 100 calls of two daemons
    assert counter_ratio.read(
        c, spec("sidecar_rtt_mean_ms")["args"]) == pytest.approx(4000.0 / 300)


@pytest.mark.parametrize("name,share", [("idle_named_share", 90.0),
                                        ("idle_empty_share", 50.0)])
def test_gap_shares_weigh_gaps_by_their_seconds(name, share):
    s = spec(name)
    assert s["reader"] == "idle_gap_share"
    assert idle_gap_share.read(ctx(), s["args"]) == pytest.approx(share)


@pytest.mark.parametrize("gaps", [None, []])
def test_no_trace_or_no_gap_reads_nothing(gaps):
    for name in ("idle_named_share", "idle_empty_share"):
        assert idle_gap_share.read(ctx(gaps=gaps), spec(name)["args"]) is None


def test_a_parent_trace_reads_no_empty_gap_and_does_not_raise():
    gaps = [["host:unattributed", 0.3], ["host:PjitFunction", 0.1]]
    assert idle_gap_share.read(
        ctx(gaps=gaps), spec("idle_empty_share")["args"]) == 0.0
    assert idle_gap_share.read(
        ctx(gaps=gaps), spec("idle_named_share")["args"]) == pytest.approx(25.0)
