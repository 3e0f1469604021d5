"""The cell ``q10-ca4096.issue``: q10-ca2048's deployment with an
RSA-4096 CA key.  Its configuration differs from q10-ca2048's where it
says and nowhere else, its mix from ``ca-issue`` only by the kind and
its plant, and its reader counts the wide chain's rows by the rule of
``reduce/rns_wide_counts.py`` — whose channel count is the program's —
and reads nothing on a program without the class.
"""

import random

import pytest

from benchmarks import generator
from benchmarks import run as runmod
from benchmarks.kinds import ca_issue_reference as reference
from benchmarks.kinds import ca_issue_w4096
from benchmarks.readers import rns_modexp_wide
from benchmarks.reduce import rns_counts, rns_wide_counts
from benchmarks.run import Counters
from bftkv_tpu.ops import rns

CELL = "q10-ca4096.issue"
KIND = "TPU v5 lite"


def spec(name: str) -> dict:
    return runmod.load_json("benchmarks", "layer_metrics", name + ".json")


def test_the_configuration_is_q10_ca2048s_with_an_rsa_4096_key():
    m = runmod.load_manifest()
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "q10-ca4096", "ca-issue-w4096", 1)
    entry = next(c for c in m["configs"] if c["name"] == "q10-ca4096")
    new = runmod.load_json(entry["file"])
    old = runmod.load_json("benchmarks", "configs", "q10-ca2048.json")
    assert entry["reduced"] == new["reduced"] == old["reduced"]
    assert new["guarantees"] == old["guarantees"]
    changed = {k for k in old if old[k] != new[k]}
    assert changed == {"name", "source", "threshold_ca", "environment",
                       "assumed"}
    assert {k for k in old["threshold_ca"]
            if old["threshold_ca"][k] != new["threshold_ca"][k]} == {
        "key_bits", "what"}
    assert new["threshold_ca"]["key_bits"] == 4096
    assert new["threshold_ca"]["rehearse_key_bits"] == 512
    assert new["key_bits"] == 2048  # the identities: setup.sh's universe
    assert new["environment"] == {**old["environment"], "BFTKV_CA_BITS": "4096"}
    mix = runmod.load_json("benchmarks", "traffic", "ca-issue-w4096.json")
    was = runmod.load_json("benchmarks", "traffic", "ca-issue.json")
    assert mix["ops"] == {"ca_issue_w4096": 1.0}
    assert set(mix["controls"]) == {
        "ca_bent_signature_w4096", *set(was["controls"]) - {"ca_bent_signature"}}
    for k in ("callers", "batch", "loop", "keys", "record", "warm_calls",
              "check_sample", "tenant", "preload_records"):
        assert mix[k] == was[k], k


def test_the_reader_counts_the_wide_chains_channels():
    # the benchmark's count and the program's chain agree on k
    assert rns_wide_counts.channels(4096) == rns.pow_context(4096).k == 340
    assert rns_wide_counts.channels(3072) == rns.pow_context(3072).k
    with pytest.raises(ValueError):
        rns_counts.channels(4096)  # the 12-bit rule has no answer here
    k = 340
    assert rns_wide_counts.row_flops(4096, 2050) == (
        (5 * 2050 + 19) * 12 * 2 * k * (k + 1))
    assert rns_wide_counts.row_bytes(4096, 2050) == 512 + 2050 + 4 + 512
    # a first-level fragment of a 4,096-bit key dealt (., 10)
    assert 4 * 2050 >= 2 * 4096 + (10 - 1).bit_length() + 1


def ctx(tr, sidecar):
    before = {"sidecar": dict.fromkeys(sidecar, 0), "daemons": {}}
    return {"trace": tr, "device": {"kind": KIND}, "window_s": 50.0,
            "counters": Counters(before, {"sidecar": sidecar, "daemons": {}})}


def trace(modules, window_s=2.0, **more):
    return {"modules": modules, "window_s": window_s, "devices_used": 1,
            "verify_items": 0, "sign_rows": 0, **more}


def test_roofline_and_window_share_of_the_wide_class():
    roof = spec("rns_roofline_ca4096")["args"]
    win = spec("window_mfu_ca4096")["args"]
    assert roof == {"mod_bits": 4096, "exp_windows": 2050,
                    "module": "rns_pow_4096_e8256", "share": "roofline"}
    assert win == {**roof, "share": "window"}
    mods = [["jit_rns_pow_4096_e8256(7)", 1.5], ["jit_rns_pow_1024(9)", 0.1]]
    row = rns_wide_counts.row_flops(4096, 2050)
    c = ctx(trace(mods, modexp_rows=90), {"modexp.device": 5000})
    assert rns_modexp_wide.read(c, roof) == pytest.approx(
        100 * 90 * row / 197e12 / 1.5)
    c = ctx(trace(mods, sign_rows=40, verify_items=1000),
            {"modexp.device": 5000, "sign.device": 2500})
    whole = (40 * row + 40 * rns_counts.sign_row_flops()
             + 1000 * rns_counts.verify_flops())
    assert rns_modexp_wide.read(c, win) == pytest.approx(
        100 * whole / (2.0 * 197e12))
    for args in (roof, win):
        assert 0 < rns_modexp_wide.read(c, args) < 100


def test_a_program_without_the_wide_class_reads_nothing():
    roof = spec("rns_roofline_ca4096")["args"]
    win = spec("window_mfu_ca4096")["args"]
    parent = ctx(trace([["jit_rns_pow_1024(9)", 0.3],
                        ["jit_rns_pow_2048_e4160(7)", 0.9]], sign_rows=400),
                 {"sign.device": 25000, "modexp.device": 3000})
    for args in (roof, win):
        assert rns_modexp_wide.read(parent, args) is None
        assert rns_modexp_wide.read({"trace": None}, args) is None


def judged(sigs, seed=7):
    key = reference.rsa_keygen(random.Random("t"), 512)
    calls = []
    for i, bend in enumerate(sigs):
        tbs = b"tbs-%d" % i
        sig = reference.rsa_sign(tbs, key)
        if bend == "bit":
            sig = sig[:-1] + bytes([sig[-1] ^ 1])
        elif bend == "plus_n":  # the same residue, not below the modulus
            sig = (int.from_bytes(sig, "big") + key.n).to_bytes(
                len(sig) + 1, "big")
        c = generator.Call("ca_issue_w4096", 0, [i], [], 0.0, 0.1, [None])
        c.values = [(tbs, sig)]
        calls.append(c)
    return ca_issue_w4096.judge(calls, {"key": key, "seed": seed}, {})


def test_the_judge_takes_every_certificate_and_resigns_a_sample():
    assert judged([None] * 5) == {"ca4096_certs_bad": 0,
                                  "ca4096_certs_checked": 5,
                                  "ca4096_certs_resigned": 5}
    got = judged([None, "bit", None, "plus_n"])
    assert got["ca4096_certs_bad"] == 2 and got["ca4096_certs_checked"] == 4
    many = judged([None] * (ca_issue_w4096.SAMPLE + 40))
    assert many == {"ca4096_certs_bad": 0,
                    "ca4096_certs_checked": ca_issue_w4096.SAMPLE + 40,
                    "ca4096_certs_resigned": ca_issue_w4096.SAMPLE}
