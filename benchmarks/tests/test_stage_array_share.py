"""``stage_array_share`` (PR 29): the share of the sidecar's chain-bound
verify items staged by the array route.  Data files only — the accepted
``counter_ratio`` reads it; it is listed for the two RSA-2048 cells; on
a made-up scrape it gives the known answer, on the program's own
counters after a staged flush (no launch: the chain is replaced, as
``plants.py`` replaces it) the share the flush had, and on a program
without the counters (the parent) or with nothing chain-bound
(``q4-rsa3072.load``) nothing."""

import numpy as np

from benchmarks import run as runmod
from benchmarks.readers import counter_ratio
from benchmarks.run import Counters

NAME = "stage_array_share"
CELLS = ["q4-rsa2048.load", "q10-rsa2048.load"]


def spec() -> dict:
    return runmod.load_json("benchmarks", "layer_metrics", NAME + ".json")


def ctx(before: dict, after: dict) -> dict:
    return {"ops": 1000, "window_s": 50.0,
            "counters": Counters({"sidecar": before, "daemons": {}},
                                 {"sidecar": after, "daemons": {}})}


def test_the_entry_names_the_cells_that_stage_verifies():
    m = runmod.load_manifest()
    # by name: the contract appends later PRs' entries after it
    entry = next(e for e in m["per_layer"] if e["name"] == NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "sidecar dispatch",
                     "moves": "committed_ops_per_s", "workloads": CELLS}
    s = spec()
    assert s["name"] == NAME and s["reader"] == "counter_ratio"
    assert s["args"]["num"] == ["sidecar:verify.stage.array"]
    assert s["args"]["den"] == ["sidecar:verify.stage.array",
                                "sidecar:verify.stage.item"]


def test_known_answers_on_a_made_up_scrape():
    args = spec()["args"]
    before = {"verify.stage.array": 1000, "verify.stage.item": 10}
    after = {"verify.stage.array": 1000 + 19800, "verify.stage.item": 10 + 200,
             "verify.device": 20000, "flush.stage.sum{op=verify}": 1.0}
    assert counter_ratio.read(ctx(before, after), args) == 99.0
    # everything pulled aside is a share of 0, not a missing metric
    after = {"verify.stage.array": 1000, "verify.stage.item": 60}
    assert counter_ratio.read(ctx(before, after), args) == 0.0


def test_nothing_to_read_is_nothing_reported():
    args = spec()["args"]
    # the parent: no such counters
    parent = {"verify.device": 20000, "flush.stage.sum{op=verify}": 1.0}
    assert counter_ratio.read(ctx({}, parent), args) is None
    # q4-rsa3072.load: registered at 0, and nothing chain-bound staged
    wide = {"verify.stage.array": 0, "verify.stage.item": 0, "verify.host": 9}
    assert counter_ratio.read(ctx(dict(wide), wide), args) is None


def test_the_programs_own_counters_after_a_staged_flush(monkeypatch):
    from bftkv_tpu.crypto import rsa
    from bftkv_tpu.metrics import registry as metrics
    from bftkv_tpu.ops import rns

    monkeypatch.setattr(
        rns, "verify_e65537_rns_indexed",
        lambda *staged: np.zeros(len(staged[2]), dtype=bool))
    # an odd modulus the context builds rows for stands for a key
    n = next(n for n in range((1 << 1023) + 1, 1 << 1024, 2)
             if rns.context().key_rows(n) is not None)
    key = rsa.PublicKey(n)
    sound = (b"m", (n - 2).to_bytes(128, "big"), key)
    short = (b"m", b"\x07" * 127, key)       # counted as the integer
    over = (b"m", n.to_bytes(128, "big"), key)  # s >= n: the host tier's
    metrics.reset()
    vd = rsa.VerifierDomain(host_threshold=0)
    before = metrics.snapshot()
    assert before["verify.stage.array"] == before["verify.stage.item"] == 0
    vd.verify_batch([sound] * 198 + [short, over])
    after = metrics.snapshot()
    metrics.reset()
    assert counter_ratio.read(ctx(before, after), spec()["args"]) == 99.0
