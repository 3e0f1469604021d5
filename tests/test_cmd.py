"""Operator-surface smoke test: genkeys → run_cluster (real OS
processes) → bftrw write/read → daemon client API.

This is the deployment shape of the reference — one process per replica
on localhost HTTP (scripts/run.sh + cmd/bftkv + cmd/bftrw) — which the
in-process cluster tests cannot cover.
"""

import os
import subprocess
import sys
import time
import urllib.parse
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASE = 17001
RW_BASE = 17101
API_BASE = 17501

ENV = dict(
    os.environ,
    PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
    JAX_PLATFORMS="cpu",  # daemons must not fight over the single TPU chip
)


def run_cmd(args, **kw):
    return subprocess.run(
        [sys.executable, "-m"] + args,
        env=ENV, cwd=REPO, capture_output=True, timeout=180, **kw
    )


def wait_port(port: int, timeout: float = 180.0) -> None:
    # Generous: a co-scheduled test suite or bench run can stretch 9
    # daemons' jax imports well past a minute on a shared CPU box.
    import socket

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        with socket.socket() as s:
            s.settimeout(1.0)
            try:
                s.connect(("127.0.0.1", port))
                return
            except OSError:
                time.sleep(0.3)
    raise TimeoutError(f"port {port} never came up")


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    from bftkv_tpu.cmd import run_cluster

    tmp = tmp_path_factory.mktemp("cmd")
    keys = str(tmp / "keys")
    dbs = str(tmp / "dbs")
    gen = run_cmd([
        "bftkv_tpu.cmd.genkeys", "--out", keys,
        "--servers", "4", "--rw", "4", "--users", "1", "--bits", "1024",
        "--base-port", str(BASE), "--rw-base-port", str(RW_BASE),
    ])
    assert gen.returncode == 0, gen.stderr.decode()

    homes = run_cluster.server_homes(keys)
    assert len(homes) == 8
    # The client APIs act as the user identity: server identities
    # under-collect collective signatures (their AUTH|PEER quorum
    # excludes self) and cannot reach the rw nodes in trust distance —
    # same property as the reference topology.
    procs = run_cluster.spawn(
        homes, dbs, storage="native", api_base=API_BASE,
        client_home=os.path.join(keys, "u01"), extra_env=ENV,
        # The whole fleet verifies through one shared sidecar process —
        # every cmd test below then exercises the sidecar path too.
        verify_sidecar=f"auto:127.0.0.1:{API_BASE + 99}",
    )
    try:
        for port in (*range(BASE, BASE + 4), *range(RW_BASE, RW_BASE + 4)):
            wait_port(port)
        wait_port(API_BASE)
        yield {"keys": keys, "dbs": dbs, "procs": procs}
    finally:
        run_cluster.shutdown(procs)


def test_bftrw_write_read_across_processes(cluster):
    home = os.path.join(cluster["keys"], "u01")
    w = run_cmd(["bftkv_tpu.cmd.bftrw", "--home", home, "write", "smoke/x",
                 "hello from bftrw"])
    assert w.returncode == 0, w.stderr.decode()
    r = run_cmd(["bftkv_tpu.cmd.bftrw", "--home", home, "read", "smoke/x"])
    assert r.returncode == 0, r.stderr.decode()
    assert r.stdout == b"hello from bftrw"


def test_bftrw_writemany_readmany(cluster):
    home = os.path.join(cluster["keys"], "u01")
    lines = b"\n".join(b"bulk/%d=value-%d" % (i, i) for i in range(5))
    w = run_cmd(
        ["bftkv_tpu.cmd.bftrw", "--home", home, "writemany"], input=lines
    )
    assert w.returncode == 0, w.stderr.decode()
    assert b"5/5 written" in w.stderr
    r = run_cmd(
        ["bftkv_tpu.cmd.bftrw", "--home", home, "readmany"]
        + ["bulk/%d" % i for i in range(5)]
    )
    assert r.returncode == 0, r.stderr.decode()
    for i in range(5):
        assert b"bulk/%d=value-%d" % (i, i) in r.stdout


def test_daemon_client_api(cluster):
    # The daemon's own client writes through the quorum...
    req = urllib.request.Request(
        f"http://127.0.0.1:{API_BASE}/write/smoke/api", data=b"via api",
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=60) as res:
        assert res.status == 200
    # ...and any other replica's API reads it back.
    with urllib.request.urlopen(
        f"http://127.0.0.1:{API_BASE + 1}/read/smoke/api", timeout=60
    ) as res:
        assert res.read() == b"via api"


def test_daemon_show_and_metrics(cluster):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{API_BASE}/show", timeout=30
    ) as res:
        body = res.read().decode()
    assert "self: a01" in body and "peer:" in body
    with urllib.request.urlopen(
        f"http://127.0.0.1:{API_BASE}/metrics", timeout=30
    ) as res:
        import json

        assert res.headers.get("content-type", "").startswith(
            "application/json"
        )
        snap = json.loads(res.read())
    assert isinstance(snap, dict)
    # Content negotiation: a Prometheus scraper's Accept header gets
    # text exposition from the same endpoint.
    req = urllib.request.Request(
        f"http://127.0.0.1:{API_BASE}/metrics",
        headers={"accept": "text/plain"},
    )
    with urllib.request.urlopen(req, timeout=30) as res:
        assert res.headers.get("content-type", "").startswith("text/plain")
        prom = res.read().decode()
    assert "# TYPE" in prom
    assert "_total" in prom  # counters end in _total
    # ?format=prometheus works without the header (curl-friendly)
    with urllib.request.urlopen(
        f"http://127.0.0.1:{API_BASE}/metrics?format=prometheus", timeout=30
    ) as res:
        assert res.read().decode().startswith("# TYPE")


def test_daemon_info_endpoint(cluster):
    import json

    with urllib.request.urlopen(
        f"http://127.0.0.1:{API_BASE}/info", timeout=30
    ) as res:
        info = json.loads(res.read())
    assert info["name"] == "a01"
    # the clique thresholds the fleet collector aggregates against,
    # straight from the wotqs b-masking math (n=4 -> f=1, 2f+1=3)
    assert info["clique"]["n"] == 4
    assert info["clique"]["f"] == 1
    assert info["clique"]["threshold"] == 3
    assert info["role"] == "clique"
    assert set(info["clique"]["members"]) == {"a01", "a02", "a03", "a04"}


def test_daemon_trace_export_cursor(cluster):
    import json

    with urllib.request.urlopen(
        f"http://127.0.0.1:{API_BASE}/trace?since=0", timeout=30
    ) as res:
        doc = json.loads(res.read())
    assert {"cursor", "dropped", "spans", "slow"} <= set(doc)
    cur = doc["cursor"]
    # draining again from the returned cursor yields nothing new
    # (no traffic between the two calls except other tests' residue;
    # allow spans but require the cursor to be monotonic)
    with urllib.request.urlopen(
        f"http://127.0.0.1:{API_BASE}/trace?since={cur}", timeout=30
    ) as res:
        doc2 = json.loads(res.read())
    assert doc2["cursor"] >= cur
    assert isinstance(doc2["spans"], list)


def test_daemon_trace_endpoint(cluster):
    import json

    # Drive one write through the daemon's client so a trace exists.
    req = urllib.request.Request(
        f"http://127.0.0.1:{API_BASE}/write/smoke/traced", data=b"t",
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=60) as res:
        assert res.status == 200
    # Straggler fan-out workers may still be recording rpc spans right
    # after the write returns; poll until the trace settles.
    deadline = time.monotonic() + 30
    names: list = []
    while time.monotonic() < deadline:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{API_BASE}/trace?limit=50", timeout=30
        ) as res:
            doc = json.loads(res.read())
        assert set(doc) == {"slow_threshold_s", "slow", "recent"}
        roots = [t for t in doc["recent"] if t["root"] == "client.write"]
        if roots:
            names = [s["name"] for s in roots[-1]["spans"]]
            if (
                "quorum.select" in names
                and sum(1 for n in names if n.startswith("rpc.")) >= 3
            ):
                break
        time.sleep(0.5)
    assert "quorum.select" in names, names
    assert sum(1 for n in names if n.startswith("rpc.")) >= 3, names


@pytest.mark.slow  # tier-2: heavy on a small-CPU tier-1 box (see pytest.ini)
def test_daemon_profile_endpoint(cluster):
    """The jax-profiler trace endpoint (pprof analog,
    reference: cmd/bftkv/main.go:20,253) captures a trace directory
    confined under the fixed profile root."""
    import tempfile

    outdir = os.path.join(tempfile.gettempdir(), "bftkv-profile", "smoke")
    with urllib.request.urlopen(
        f"http://127.0.0.1:{API_BASE}/debug/profile?seconds=0.2&name=smoke",
        timeout=90,
    ) as res:
        assert b"trace captured" in res.read()
    found = []
    for root, _dirs, files in os.walk(outdir):
        found += [f for f in files if f.endswith(".trace.json.gz")
                  or "xplane" in f or f.endswith(".pb")]
    assert found, f"no trace artifacts under {outdir}"


def test_daemon_api_missing_variable(cluster):
    import urllib.error

    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(
            f"http://127.0.0.1:{API_BASE}/read/smoke/none", timeout=60
        )
    assert ei.value.code == 404


def test_only_the_sidecar_may_see_the_chip(monkeypatch, tmp_path):
    """A chip belongs to one process: ``spawn`` hands the sidecar the
    caller's environment and pins every other child — daemons,
    gateways, collector, autopilot — to the CPU backend, so that no
    calibration probe or profiler endpoint races the sidecar for it."""
    from bftkv_tpu.cmd import run_cluster

    started = []

    class FakePopen:
        def __init__(self, cmd, env=None, **kw):
            self.args = cmd
            started.append((cmd[2], env))

    monkeypatch.setattr(run_cluster.subprocess, "Popen", FakePopen)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    homes = [str(tmp_path / n) for n in ("a01", "a02", "rw01")]
    run_cluster.spawn(
        homes, str(tmp_path / "dbs"), api_base=17900, sidecar="auto",
        fleet=17990, autopilot=True, gw_homes=[str(tmp_path / "gw01")],
        extra_env={"BFTKV_TEST_MARK": "1"},
    )
    by_module: dict = {}
    for module, env in started:
        by_module.setdefault(module, []).append(env)
    assert sorted(by_module) == [
        "bftkv_tpu.autopilot", "bftkv_tpu.cmd.bftkv", "bftkv_tpu.cmd.fleet",
        "bftkv_tpu.cmd.run_gateway", "bftkv_tpu.cmd.verify_sidecar",
    ]
    (sidecar_env,) = by_module.pop("bftkv_tpu.cmd.verify_sidecar")
    assert "JAX_PLATFORMS" not in sidecar_env  # the caller's: the chip
    assert sidecar_env["BFTKV_TEST_MARK"] == "1"
    assert len(by_module["bftkv_tpu.cmd.bftkv"]) == 3
    for envs in by_module.values():
        for env in envs:
            assert env["JAX_PLATFORMS"] == "cpu"
            assert env["BFTKV_TEST_MARK"] == "1"
    # The legacy verify-only sidecar owns the chip the same way.
    started.clear()
    run_cluster.spawn(homes, str(tmp_path / "dbs"), verify_sidecar="auto")
    assert "JAX_PLATFORMS" not in dict(started)[
        "bftkv_tpu.cmd.verify_sidecar"
    ]
    assert dict(started)["bftkv_tpu.cmd.bftkv"]["JAX_PLATFORMS"] == "cpu"


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_has_one_place(monkeypatch, tmp_path, from_env):
    """``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and the
    program touches nothing.  Unset: one fixed directory inside the
    checkout — never under ``~``, ``/tmp``, a pid or a time."""
    import jax

    from bftkv_tpu import ops

    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            ops.enable_compile_cache()
            assert jax.config.jax_compilation_cache_dir is None
            assert ops.compile_cache_dir() == str(tmp_path)
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            ops.enable_compile_cache()
            fixed = os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == fixed
            assert ops.compile_cache_dir() == ops.COMPILE_CACHE_DIR == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
