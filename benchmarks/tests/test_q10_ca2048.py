"""The cell ``q10-ca2048.issue`` (PR 33): what it names is committed,
its kind judges every certificate, its reader counts what
``rns_counts`` would and reads nothing on a program without the class,
and a rehearsed walk ends correct — with the kind's plant, not.
"""

import importlib
import json
import os
import random
import subprocess
import sys

import pytest

from benchmarks import generator, kinds
from benchmarks import run as runmod
from benchmarks.harness import ROOT
from benchmarks.kinds import ca_issue, ca_issue_reference as reference
from benchmarks.readers import counter_ratio, rns_modexp_class
from benchmarks.reduce import rns_counts
from benchmarks.run import Counters

CELL = "q10-ca2048.issue"
NEW = ("ca_sign_ms_per_call", "daemon_dist_sign_handler_ms_per_call",
       "daemon_share_load_ms_per_call", "modexp_remote_share",
       "modexp_device_share", "modexp_rows_per_launch",
       "rns_roofline_ca2048", "window_mfu_ca2048")
KIND = "TPU v5 lite"


def spec(name: str) -> dict:
    return runmod.load_json("benchmarks", "layer_metrics", name + ".json")


def test_the_cell_and_everything_it_names_are_committed_files():
    m = runmod.load_manifest()
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "q10-ca2048", "ca-issue", 1)
    entry = next(c for c in m["configs"] if c["name"] == "q10-ca2048")
    cfg = runmod.load_json(entry["file"])
    old = runmod.load_json("benchmarks", "configs", "q10-rsa2048.json")
    assert entry["reduced"] == cfg["reduced"] == old["reduced"]
    # q10-rsa2048's deployment and write guarantees, and a CA dealt to it
    for k in ("quorum_servers", "storage_nodes", "users", "f", "key_alg",
              "key_bits", "value_bytes", "storage"):
        assert cfg[k] == old[k], k
    # one admission slot a tenant daemon: rows of requests that wait
    # outside admission cannot share a launch
    assert {k: v for k, v in cfg["sidecar"].items() if k != "admission"} == {
        k: v for k, v in old["sidecar"].items() if k != "admission"}
    assert cfg["environment"]["BFTKV_SIDECAR_MAX_INFLIGHT"] == str(
        cfg["quorum_servers"] + cfg["storage_nodes"])
    assert old["guarantees"].items() <= cfg["guarantees"].items()
    assert set(cfg["guarantees"]) - set(old["guarantees"]) == {
        "certificate", "threshold"}
    ca = cfg["threshold_ca"]
    assert (ca["algo"], ca["key_bits"], ca["k"], ca["n"], ca["hash"]) == (
        "rsa", 2048, 7, 10, "sha256")
    assert ca["k"] == 2 * cfg["f"] + 1 and ca["n"] == cfg["quorum_servers"]
    assert cfg["environment"]["BFTKV_CA_BITS"] == "2048"
    mix = runmod.load_json("benchmarks", "traffic", "ca-issue.json")
    assert (mix["callers"], mix["batch"], mix["ops"]) == (
        16, 1, {"ca_issue": 1.0})
    assert mix["tenant"] == runmod.load_json(
        "benchmarks", "traffic", "load-c4.json")["tenant"]
    assert "ca_bent_signature" in mix["controls"]
    per_layer = {e["name"]: e for e in m["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "committed_ops_per_s"
        reader = importlib.import_module(
            "benchmarks.readers." + spec(name)["reader"])
        assert callable(reader.read)
    assert [e["name"] for e in m["per_layer"][-8:]] == list(NEW)


# -- the kind -----------------------------------------------------------------


def judged(sigs):
    key = reference.rsa_keygen(random.Random("t"), 512)
    calls = []
    for i, bend in enumerate(sigs):
        tbs = b"tbs-%d" % i
        sig = reference.rsa_sign(tbs, key)
        if bend:
            sig = sig[:-1] + bytes([sig[-1] ^ 1])
        c = generator.Call("ca_issue", 0, [i], [], 0.0, 0.1, [None])
        c.values = [(tbs, sig)]
        calls.append(c)
    refused = generator.Call("ca_issue", 0, [99], [], 0.0, 0.1, ["refused"])
    return ca_issue.judge(calls + [refused], {"key": key}, {})


def test_every_certificate_is_judged_and_a_bent_one_is_bad():
    assert judged([False] * 5) == {"ca_certs_bad": 0, "ca_certs_checked": 5}
    assert judged([False, True, False, True]) == {
        "ca_certs_bad": 2, "ca_certs_checked": 4}
    assert judged([]) == {"ca_certs_bad": 0, "ca_certs_checked": 0}
    loaded = kinds.load(["ca_issue"])["ca_issue"]
    assert loaded.limits == [("ca_certs_bad", "<=", 0),
                             ("ca_certs_checked", ">=", 1)]
    assert list(loaded.plants) == ["ca_bent_signature"]


def test_the_plant_bends_every_nth_signature_and_nothing_else():
    class Api:
        def sign(self, *a):
            return b"\x00" * 8

        def write(self, *a):
            return "passed"

    bent = ca_issue.BentSignature(Api(), 3)
    got = [bent.sign("ca", b"t", 1, "sha256") for _ in range(6)]
    assert [g != b"\x00" * 8 for g in got] == [False, False, True] * 2
    assert bent.write(b"k", b"v") == "passed"


# -- the reader ---------------------------------------------------------------


def test_row_counts_follow_rns_counts_rule():
    k = rns_counts.channels(2048)
    assert rns_modexp_class.row_flops(1024, 256) == rns_counts.sign_row_flops()
    assert rns_modexp_class.row_flops(2048, 1026) == (
        (5 * 1026 + 19) * 12 * 2 * k * (k + 1))
    assert rns_modexp_class.row_flops(2048, 1026) == pytest.approx(
        4.4e9, rel=0.05)
    assert rns_modexp_class.row_bytes(2048, 1026) == 256 + 1026 + 4 + 256


def ctx(tr, sidecar):
    before = {"sidecar": dict.fromkeys(sidecar, 0), "daemons": {}}
    return {"trace": tr, "device": {"kind": KIND}, "window_s": 50.0,
            "counters": Counters(before, {"sidecar": sidecar, "daemons": {}})}


def trace(modules, window_s=2.0, **more):
    return {"modules": modules, "window_s": window_s, "devices_used": 1,
            "verify_items": 0, "sign_rows": 0, **more}


def test_roofline_and_window_share_of_the_fragment_class():
    roof = spec("rns_roofline_ca2048")["args"]
    win = spec("window_mfu_ca2048")["args"]
    assert roof == {"mod_bits": 2048, "exp_windows": 1026,
                    "module": "rns_pow_2048_e4160", "share": "roofline"}
    mods = [["jit_rns_pow_2048_e4160(7)", 1.5], ["jit_rns_pow_1024(9)", 0.1]]
    row = rns_modexp_class.row_flops(2048, 1026)
    counters = {"modexp.device": 50000, "sign.device": 25000}
    # exact where run.py brackets the rows itself
    c = ctx(trace(mods, modexp_rows=900), counters)
    assert rns_modexp_class.read(c, roof) == pytest.approx(
        100 * 900 * row / 197e12 / 1.5)
    # else the bracketed signs x the operation's own ratio: 400 rows are
    # 200 signs, two fragment rows a sign in this window
    c = ctx(trace(mods, sign_rows=400, verify_items=1000), counters)
    assert rns_modexp_class.read(c, roof) == pytest.approx(
        100 * 400 * row / 197e12 / 1.5)
    whole = (400 * row + 400 * rns_counts.sign_row_flops()
             + 1000 * rns_counts.verify_flops())
    assert rns_modexp_class.read(c, win) == pytest.approx(
        100 * whole / (2.0 * 197e12))
    # no sign rode the device in the traced window: the window's rows by
    # the traced share of its seconds
    c = ctx(trace(mods), counters)
    assert rns_modexp_class.read(c, roof) == pytest.approx(
        100 * 2000 * row / 197e12 / 1.5)
    for args in (roof, win):
        assert 0 < rns_modexp_class.read(c, args) < 100


def test_a_program_without_the_class_reads_nothing():
    roof = spec("rns_roofline_ca2048")["args"]
    win = spec("window_mfu_ca2048")["args"]
    parent = ctx(trace([["jit_rns_pow_1024(9)", 0.3],
                        ["jit_rns_verify_gather(2)", 0.1]], sign_rows=400),
                 {"sign.device": 25000})
    no_rows = ctx(trace([["jit_rns_pow_2048_e4160(7)", 1.5]]),
                  {"modexp.device": 0})
    for args in (roof, win):
        assert rns_modexp_class.read(parent, args) is None
        assert rns_modexp_class.read(no_rows, args) is None
        assert rns_modexp_class.read({"trace": None}, args) is None
    c = {"ops": 100, "window_s": 50.0, "counters": Counters(
        {"sidecar": {}, "daemons": {}},
        {"sidecar": {"sign.device": 5}, "daemons": {"a01": {"x": 1}}})}
    for name in ("daemon_dist_sign_handler_ms_per_call",
                 "daemon_share_load_ms_per_call", "modexp_remote_share",
                 "modexp_device_share", "modexp_rows_per_launch"):
        assert counter_ratio.read(c, spec(name)["args"]) is None


def test_the_counter_metrics_on_a_made_up_scrape():
    after = {"sidecar": {"modexp.device": 9000, "modexp.host": 1000,
                         "modexp.host.class{bits=8192}": 1000,
                         "modexp.device_batch.count": 100},
             "daemons": {"a01": {"modexp.remote": 990,
                                 "modexp.remote_fallback": 10,
                                 "modexp.remote_shed": 10,
                                 "server.dist_sign.handler.sum": 200.0,
                                 "server.dist_sign.handler.count": 1000,
                                 "server.dist_sign.share.sum": 2.0,
                                 "threshold.rsa.parse.sum": 4.0}}}
    c = {"ops": 1000, "window_s": 50.0,
         "counters": Counters({"sidecar": {}, "daemons": {}}, after)}
    read = lambda name: counter_ratio.read(c, spec(name)["args"])  # noqa: E731
    assert read("modexp_device_share") == 90.0
    assert read("modexp_remote_share") == 99.0
    assert read("modexp_rows_per_launch") == 90.0
    assert read("daemon_dist_sign_handler_ms_per_call") == 200.0
    assert read("daemon_share_load_ms_per_call") == 6.0


# -- the walk -----------------------------------------------------------------


def walk(seed: int, *more):
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL,
         "--seed", str(seed), "--seconds", "5", "--rehearse", *more],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=400)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.splitlines()[-1])


def test_the_rehearsed_walk_ends_correct_and_with_the_plant_it_does_not():
    """A 512-bit CA key dealt (7,10) over ten CPU daemons; the sidecar's
    host tier answers the modexps the daemons send it."""
    r = walk(2147483801, "--trace", "1")
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    names = list(r["compared"])
    assert names[-2:] == ["ca_certs_bad", "ca_certs_checked"]
    assert r["compared"]["ca_certs_bad"] == [0, "<=", 0]
    # every acknowledged certificate, the callers' warm ones too
    assert r["compared"]["ca_certs_checked"][0] >= r["attempted"]
    assert r["compared"]["compiled_in_window"][0] == 0
    assert r["metrics"]["modexp_remote_share"]["value"] == 100.0
    assert r["metrics"]["modexp_device_share"]["value"] == 0.0   # a CPU
    assert {"ca_sign_ms_per_call", "daemon_dist_sign_handler_ms_per_call",
            "daemon_share_load_ms_per_call"} <= set(r["metrics"])
    assert not {"rns_roofline_ca2048", "window_mfu_ca2048",
                "modexp_rows_per_launch"} & set(r["metrics"])
    r = walk(2147483802, "--plant", "ca_bent_signature")
    assert r["correct"] is False and r["compared"]["ca_certs_bad"][0] >= 1
