"""Whole runs on the CPU (``--rehearse``: tiny, no device metric): a
sound run is correct; a planted lost write, a planted wrong read, a
sidecar that accepts every signature and a dead child each make the run
say so, legibly; a host that stands still for seconds fails no operation
(and does under the program's adaptive RPC deadline); without a TPU, and in a
directory without the program, the command fails and prints no result.

Slow (about 25 s a run): ``python3 -m pytest benchmarks/tests -q``.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.harness import ROOT

CELL = "q4-rsa2048.load"


def bench(*argv, cwd=ROOT, timeout=300, **environ):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **environ)
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL, *argv],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


def result(p):
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.splitlines()[-1])


def test_sound_run_is_correct_and_prints_the_contract_keys():
    p = bench("--seed", "2147483777", "--seconds", "4", "--trace", "1", "--rehearse")
    r = result(p)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["compared"]["forged_checked"][0] >= 1   # the tenant's forgeries
    assert {"sheds_per_kop", "fallback_share", "admission_wait_p99_ms",
            "host_tier_share"} <= set(r["metrics"])
    # a CPU run never prints a device metric
    assert not {"rns_roofline", "device_idle_share", "window_mfu"} & set(r["metrics"])
    assert "busy_s" not in r["device"] and "breakdown" not in r
    assert p.stderr.strip().splitlines()[-1].startswith("compared: bad_reads=0<=0")
    assert all(v[0] <= v[2] if v[1] == "<=" else v[0] >= v[2]
               for v in r["compared"].values())


@pytest.mark.parametrize("plant,number", [
    ("lost_write", "under_replicated"), ("wrong_read", "bad_reads"),
    ("accept_all", "forged_accepted")])
def test_planted_fault_comes_out_incorrect(plant, number):
    p = bench("--seed", "2147483778", "--seconds", "4", "--trace", "0",
              "--rehearse", "--plant", plant)
    r = result(p)
    assert r["correct"] is False
    assert r["compared"][number][0] >= 1
    assert f"{number}=" in p.stderr.splitlines()[-1]


def test_controls_in_one_process_come_out_incorrect():
    p = bench("--seed", "2147483779", "--seconds", "3", "--trace", "0",
              "--rehearse", "--control-runs", "1", "--control-seconds", "2")
    assert result(p)["correct"] is True
    controls = [json.loads(l) for l in p.stdout.splitlines() if '"control"' in l]
    assert {c["control"] for c in controls} == {
        "lost_write", "wrong_read", "accept_all"}
    assert all(c["correct"] is False for c in controls)


def test_dead_child_exits_legibly():
    p = bench("--seed", "2147483780", "--seconds", "6", "--trace", "0",
              "--rehearse", "--plant", "dead_child")
    assert p.returncode != 0
    assert not p.stdout.strip() or '"correct"' not in p.stdout.splitlines()[-1]
    assert "FAILED: " in p.stderr and "died" in p.stderr
    assert "tail of cluster.log" in p.stderr
    note = os.path.join(ROOT, "benchmarks", ".run", f"{CELL}-2147483780", "failure.txt")
    with open(note) as f:
        assert f.read().startswith("FAILED: ")
    left = subprocess.run(["pgrep", "-f", "[2]147483780"], capture_output=True)
    assert left.returncode != 0, "a failed run left processes behind"


@pytest.mark.parametrize("plant,deadline,fails", [
    ("stall", "", False), ("stall_storage", "", False),
    ("stall_storage", "on", True)])   # every call meets a stopped storage node
def test_a_host_that_stands_still_fails_no_operation(plant, deadline, fails):
    """Every process of the run (``stall``), or the storage nodes alone
    (``stall_storage``: a disk that holds an fsync back), stops for 2.5 s
    mid-window.  Under the harness's fixed RPC deadline the calls in flight
    come back late and whole; under the program's adaptive one (from 1 s
    up), which the harness switches off, they fail with "rpc timeout":
    the control."""
    environ = {"BFTKV_ADAPTIVE_TIMEOUT": deadline} if deadline else {}
    p = bench("--seed", "2147483782", "--seconds", "6", "--trace", "0",
              "--rehearse", "--plant", plant, "--stall-seconds", "2.5",
              **environ)
    r = result(p)
    assert r["correct"] is True and r["attempted"] > 0
    assert (r["failed"] > 0) is fails
    notes = [json.loads(l) for l in p.stdout.splitlines()[:-1]]
    slowest = next(n for n in notes if "slowest_calls_ms" in n)
    if not fails:   # the wait is counted
        assert slowest["slowest_calls_ms"][0] >= 2000.0
    errors = next(n for n in notes if "errors" in n)["errors"]
    assert any("rpc timeout" in k for k in errors) is fails


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    p = bench("--seed", "2147483781", "--seconds", "2", "--trace", "0")
    assert p.returncode != 0 and '"correct"' not in p.stdout
    assert "FAILED: no TPU" in p.stderr


def test_unknown_cell_and_bare_directory_fail(tmp_path):
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", "nope", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True)
    assert p.returncode != 0 and "no cell 'nope'" in p.stderr
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns(".run", "__pycache__"))
    p = bench("--seed", "5", "--seconds", "2", "--trace", "0", "--rehearse",
              cwd=str(tmp_path))
    assert p.returncode != 0 and '"correct"' not in p.stdout
    assert "FAILED: " in p.stderr
