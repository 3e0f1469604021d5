"""The manifest and the data files agree, and a cell, a configuration, a
mix and a per-layer metric can each be added as new files plus entries —
no file that is there is edited."""

import argparse
import importlib
import json
import os

import pytest

from benchmarks import run as runmod
from benchmarks.harness import ROOT
from benchmarks.generator import Call

B = os.path.join(ROOT, "benchmarks")


@pytest.fixture(scope="module")
def manifest():
    return runmod.load_manifest()


def test_every_entry_has_its_files(manifest):
    for c in manifest["configs"]:
        cfg = runmod.load_json(c["file"])
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert {"min_replicas", "suff"} <= set(cfg["guarantees"])
    for w in manifest["workloads"]:
        assert w["config"] in {c["name"] for c in manifest["configs"]}
        mix = runmod.load_json("benchmarks", "traffic", w["traffic"] + ".json")
        assert abs(sum(mix["ops"].values()) - 1.0) < 1e-9
        assert mix["loop"] == "closed" and mix["callers"] >= 1


def test_every_layer_metric_names_a_reader_and_a_reported_metric(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = [w["name"] for w in manifest["workloads"]]
    assert "setup_s" in e2e
    for m in manifest["per_layer"]:
        spec = runmod.load_json("benchmarks", "layer_metrics", m["name"] + ".json")
        reader = importlib.import_module("benchmarks.readers." + spec["reader"])
        assert callable(reader.read), m["name"]
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in moved.get("workloads", cells), (
                f"{m['name']} moves {m['moves']}, which {cell} does not report")
    files = {f[:-5] for f in os.listdir(os.path.join(B, "layer_metrics"))}
    assert files == {m["name"] for m in manifest["per_layer"]}


def test_names_and_sizes_fit_the_contract(manifest):
    import re

    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in manifest[group]:
            assert name.match(e["name"]), e["name"]
    for w in manifest["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in manifest["configs"]:
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_a_cell_a_config_a_mix_and_a_metric_are_added_as_files(tmp_path, manifest):
    """New files + new entries in a manifest; the harness finds them by
    name and the new metric's reader is read."""
    added = {
        os.path.join(B, "configs", "zz-test-q7.json"): dict(
            runmod.load_json("benchmarks", "configs", "q4-rsa2048.json"),
            name="zz-test-q7", quorum_servers=7),
        os.path.join(B, "traffic", "zz-test-mix.json"): dict(
            runmod.load_json("benchmarks", "traffic", "load.json"),
            name="zz-test-mix", callers=3),
        os.path.join(B, "layer_metrics", "zz_test_metric.json"): {
            "name": "zz_test_metric", "reader": "zz_test_reader", "args": {"k": 2}},
    }
    reader = os.path.join(B, "readers", "zz_test_reader.py")
    try:
        for path, obj in added.items():
            assert not os.path.exists(path)
            with open(path, "w") as f:
                json.dump(obj, f)
        with open(reader, "w") as f:
            f.write("def read(ctx, args):\n    return args['k'] * ctx['ops']\n")
        m = json.loads(json.dumps(manifest))
        m["configs"].append({"name": "zz-test-q7", "source": "test",
                             "file": "benchmarks/configs/zz-test-q7.json",
                             "reduced": [], "why": "test"})
        m["workloads"].append({"name": "zz-test-q7.mix", "config": "zz-test-q7",
                               "traffic": "zz-test-mix", "chips": 1, "why": "test"})
        m["per_layer"].append({"name": "zz_test_metric", "unit": "count",
                               "better": "higher", "source": "program_counter",
                               "layer": "client", "moves": "committed_ops_per_s",
                               "workloads": ["zz-test-q7.mix"]})
        path = tmp_path / "BENCHMARK.json"
        path.write_text(json.dumps(m))
        args = argparse.Namespace(workload="zz-test-q7.mix", seed=1, rehearse=False,
                                  manifest=str(path))
        run = runmod.Run(args)
        assert run.cluster.n_quorum == 7 and run.mix["callers"] == 3
        call = Call("insert", 0, [0, 1], [1, 1], 0.0, 0.5, [None, None])
        w = {"ops": 2, "span_s": 0.5, "window": [call],
             "counters": runmod.Counters({"sidecar": {}, "daemons": {}},
                                         {"sidecar": {"sidecar.shed{op=sign}": 1},
                                          "daemons": {}}),
             "cpu_s": {"client": 0.1, "daemons": 0.2, "sidecar": 0.3}}
        layers = run.per_layer(w, None)
        assert layers["zz_test_metric"] == (4, "count")
        assert layers["sheds_per_kop"][0] == 500.0
        assert layers["client_cpu_ms_per_op"][0] == pytest.approx(50.0)
        assert "device_idle_share" not in layers   # no trace: nothing to read
    finally:
        for path in [*added, reader]:
            if os.path.exists(path):
                os.remove(path)
