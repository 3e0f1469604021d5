"""The cell ``q4-rsa3072.load`` is in the committed manifest with every
file it names; its readers give known answers on made-up scrapes and
return nothing where the program lacks the counter (the parent); the
width-general FLOP count equals ``rns_counts`` at 1024 bits; and the
rehearsed walk of the cell on the CPU ends correct."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmarks import run as runmod
from benchmarks.harness import ROOT
from benchmarks.readers import (counter_growth, counter_per_window,
                                counter_ratio, rns_pow_width)
from benchmarks.reduce import rns_counts
from benchmarks.run import Counters

CELL = "q4-rsa3072.load"
NEW = ("verify_local_share", "daemon_verify_ms_per_kitem",
       "unwarmed_width_items", "rns_roofline_w3072", "window_mfu_w3072")
KIND = "TPU v5 lite"


def spec(name: str) -> dict:
    return runmod.load_json("benchmarks", "layer_metrics", name + ".json")


def test_the_cell_and_everything_it_names_are_committed_files():
    m = runmod.load_manifest()
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell == m["workloads"][-1] and cell["chips"] == 1
    assert (cell["config"], cell["traffic"]) == ("q4-rsa3072", "load")
    entry = next(c for c in m["configs"] if c["name"] == "q4-rsa3072")
    cfg = runmod.load_json(entry["file"])
    old = runmod.load_json("benchmarks", "configs", "q4-rsa2048.json")
    assert cfg["key_bits"] == 3072 and entry["reduced"] == cfg["reduced"] == []
    assert cfg["environment"] == {"BFTKV_IDENTITY_BITS": "3072"}
    # the same deployment and the same guarantees, another width
    for k in ("quorum_servers", "storage_nodes", "users", "f", "key_alg",
              "value_bytes", "storage", "sidecar", "guarantees"):
        assert cfg[k] == old[k], k
    assert set(old["assumed"]) < set(cfg["assumed"])
    mix = runmod.load_json("benchmarks", "traffic", cell["traffic"] + ".json")
    assert mix["callers"] == 8 and mix["batch"] == 256 and "tenant" in mix
    per_layer = {e["name"]: e for e in m["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
        reader = importlib.import_module(
            "benchmarks.readers." + spec(name)["reader"])
        assert callable(reader.read)
    # the accepted shares of RSA-2048's counts stay with RSA-2048's cells
    for name in ("rns_roofline", "window_mfu"):
        assert CELL not in per_layer[name]["workloads"]
    listed = [e["name"] for e in m["per_layer"]
              if CELL in e.get("workloads", [CELL])]
    assert len(listed) >= 25


def test_row_counts_at_1024_bits_are_rns_counts_own():
    assert rns_pow_width.row_flops(1024) == rns_counts.sign_row_flops()
    assert rns_pow_width.row_bytes(1024) == rns_counts.sign_row_bytes()
    k = rns_counts.channels(1536)
    assert k == 140
    # 384 windows of five products, and the 19 of table and framing
    assert rns_pow_width.row_flops(1536) == 1939 * 12 * 2 * k * (k + 1)
    assert rns_pow_width.row_flops(1536) / rns_counts.sign_row_flops() == (
        pytest.approx(3.3, abs=0.1))


def trace(rows, modules, window_s=2.0):
    return {"sign_rows": rows, "verify_items": 0, "modules": modules,
            "module_s": sum(s for _n, s in modules), "window_s": window_s,
            "devices_used": 1}


def test_roofline_and_window_share_of_the_1536_bit_chain():
    roof, win = spec("rns_roofline_w3072")["args"], spec("window_mfu_w3072")["args"]
    assert roof == {"row_bits": 1536, "share": "roofline"}
    flops = 6000 * rns_pow_width.row_flops(1536)
    least = flops / 197e12
    tr = trace(6000, [["jit_rns_pow_1536(123)", 0.25],
                      ["jit_dispatch_rtt_probe", 0.5]])
    c = {"trace": tr, "device": {"kind": KIND}}
    # FLOPs-bound; only the module of that width is its time
    assert rns_pow_width.read(c, roof) == pytest.approx(100 * least / 0.25)
    assert rns_pow_width.read(c, win) == pytest.approx(100 * flops / (2.0 * 197e12))
    assert 0 < rns_pow_width.read(c, roof) < 100
    # the parent of the width: other modules, or no rows, or no trace
    other = {"trace": trace(6000, [["jit_rns_pow_1024(9)", 0.25]]),
             "device": {"kind": KIND}}
    for args in (roof, win):
        assert rns_pow_width.read(other, args) is None
        assert rns_pow_width.read({"trace": trace(0, tr["modules"]),
                                   "device": {"kind": KIND}}, args) is None
        assert rns_pow_width.read({"trace": None}, args) is None


def ctx(before, after):
    return {"ops": 1000, "window_s": 50.0, "counters": Counters(before, after)}


def test_the_counter_metrics_on_a_made_up_scrape():
    before = {"sidecar": {"sidecar.unwarmed_width": 0}, "daemons": {"a01": {}}}
    after = {"sidecar": {"sidecar.unwarmed_width": 0, "sign.device": 3000},
             "daemons": {
                 "a01": {"verify.local_wide": 9000, "verify.remote": 0,
                         "host.batch.native{op=verify}": 10000,
                         "host.batch.native{op=sign}": 50,
                         "host.batch.seconds.sum{op=verify}": 1.5,
                         "host.batch.seconds.sum{op=sign}": 7.0},
                 "rw01": {"verify.local_wide": 1000,
                          "host.batch.native{op=verify}": 2000,
                          "host.batch.seconds.sum{op=verify}": 0.3}}}
    c = ctx(before, after)
    assert counter_ratio.read(c, spec("verify_local_share")["args"]) == 100.0
    assert counter_per_window.read(
        c, spec("daemon_verify_ms_per_kitem")["args"]) == pytest.approx(150.0)
    assert counter_growth.read(c, spec("unwarmed_width_items")["args"]) == 0
    after["sidecar"]["sidecar.unwarmed_width"] = 7
    after["daemons"]["a01"]["verify.remote"] = 10000
    c = ctx(before, after)
    assert counter_growth.read(c, spec("unwarmed_width_items")["args"]) == 7
    assert counter_ratio.read(c, spec("verify_local_share")["args"]) == 50.0


def test_a_program_without_the_counters_reads_nothing():
    parent = {"sidecar": {"sign.device": 5}, "daemons": {"a01": {"verify.remote": 3}}}
    c = ctx({"sidecar": {}, "daemons": {}}, parent)
    assert counter_growth.read(c, spec("unwarmed_width_items")["args"]) is None
    assert counter_per_window.read(
        c, spec("daemon_verify_ms_per_kitem")["args"]) is None
    # its verifies all crossed the wire: a share of 0, not a fault
    assert counter_ratio.read(c, spec("verify_local_share")["args"]) == 0.0


def test_the_rehearsed_walk_of_the_cell_ends_correct():
    """1024-bit keys by the harness's own rule; the declaration in the
    configuration's environment reaches the children and harms nothing
    on the CPU, where nothing is warmed."""
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL,
         "--seed", "2147483999", "--seconds", "4", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    r = json.loads(p.stdout.splitlines()[-1])
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["compared"]["compiled_in_window"][0] == 0
    assert r["compared"]["forged_checked"][0] >= 1
    assert {"unwarmed_width_items", "verify_local_share",
            "daemon_verify_ms_per_kitem", "host_native_share"} <= set(r["metrics"])
    assert r["metrics"]["unwarmed_width_items"]["value"] == 0
    # 1024-bit rehearsal keys ride the chains: nothing is kept local
    assert r["metrics"]["verify_local_share"]["value"] == 0.0
    assert not {"rns_roofline_w3072", "window_mfu_w3072", "rns_roofline",
                "window_mfu"} & set(r["metrics"])
