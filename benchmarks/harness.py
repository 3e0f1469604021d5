"""Bring the deployment up and down: one chip-owning sidecar, the
program's own ``genkeys`` and ``run_cluster``, every child in a session
of its own, every failure named before the exit.

Nothing here imports JAX or the program: the harness process stays off
the chip, and the children are the program's command lines.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLK_TCK = os.sysconf("SC_CLK_TCK")
LOG_TAIL = 1500


class BenchFailure(Exception):
    """A run that cannot give a result; the message is its one line."""


def http_json(url: str, timeout: float = 10.0):
    req = urllib.request.Request(url, headers={"accept": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def free_port_block(n: int) -> int:
    """First of ``n`` consecutive free ports.  The kernel is asked for
    one free port; the block starts there if its neighbours bind too."""
    for _ in range(200):
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        base = probe.getsockname()[1]
        probe.close()
        if base + n >= 65535:
            continue
        socks = []
        try:
            for port in range(base, base + n):
                s = socket.socket()
                socks.append(s)
                s.bind(("127.0.0.1", port))
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise BenchFailure("no block of free ports: the kernel offered none "
                       f"with {n} free neighbours in 200 tries")


def cpu_seconds(pid: int) -> float:
    """User + system CPU seconds of one process (its threads included,
    its children not), from ``/proc/<pid>/stat``."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            parts = f.read().rsplit(")", 1)[1].split()
    except OSError:
        return 0.0
    return (int(parts[11]) + int(parts[12])) / CLK_TCK


def children_of(pid: int) -> list[dict]:
    out = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
            if ppid != pid:
                continue
            with open(f"/proc/{entry}/cmdline", "rb") as f:
                argv = [a.decode(errors="replace") for a in f.read().split(b"\0")]
            with open(f"/proc/{entry}/environ", "rb") as f:
                env = f.read().split(b"\0")
        except (OSError, IndexError, ValueError):
            continue
        home = next((argv[i + 1] for i, a in enumerate(argv[:-1])
                     if a == "--home"), "")
        platforms = next((kv.split(b"=", 1)[1].decode() for kv in env
                          if kv.startswith(b"JAX_PLATFORMS=")), None)
        out.append({"pid": int(entry), "name": os.path.basename(home),
                    "JAX_PLATFORMS": platforms})
    return sorted(out, key=lambda c: c["name"])


def tail(path: str, n: int = LOG_TAIL) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")
    except OSError:
        return ""


class Cluster:
    """One deployment in one run directory."""

    def __init__(self, run_dir: str, config: dict, *, rehearse: bool,
                 chip_platforms: str | None = None):
        self.chip_platforms = chip_platforms
        self.run_dir = run_dir
        self.config = config
        self.rehearse = rehearse
        self.keys = os.path.join(run_dir, "keys")
        self.dbs = os.path.join(run_dir, "dbs")
        self.ctl = os.path.join(run_dir, "ctl")
        self.logs = {
            name: os.path.join(run_dir, name + ".log")
            for name in ("sidecar", "genkeys", "cluster")
        }
        self.procs: dict[str, subprocess.Popen] = {}
        self._files = []
        self._n_cmd = 0
        self._sock_dir = ""
        self.phases: dict[str, float] = {}
        self.n_quorum = int(config["quorum_servers"])
        self.n_storage = int(config["storage_nodes"])
        self.n_daemons = self.n_quorum + self.n_storage
        self.daemons: list[dict] = []
        self.sidecar_info: dict = {}
        self.device: dict = {}

    # -- environment --------------------------------------------------------

    def env(self, *, owns_chip: bool) -> dict:
        env = dict(os.environ)
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        env.setdefault("TPU_LOG_DIR", "disabled")
        if self.rehearse or not owns_chip:
            env["JAX_PLATFORMS"] = "cpu"
        elif self.chip_platforms is None:
            env.pop("JAX_PLATFORMS", None)  # the harness pinned itself
        else:
            env["JAX_PLATFORMS"] = self.chip_platforms
        for k, v in self.config.get("environment", {}).items():
            env[k] = str(v)
        return env

    def _spawn(self, name: str, argv: list[str], *, owns_chip: bool = False):
        log = open(self.logs[name], "ab")
        self._files.append(log)
        p = subprocess.Popen(
            [sys.executable, "-m", *argv], cwd=ROOT,
            env=self.env(owns_chip=owns_chip),
            stdout=log, stderr=subprocess.STDOUT, start_new_session=True,
        )
        self.procs[name] = p
        return p

    # -- bring-up -----------------------------------------------------------

    def prepare(self) -> None:
        shutil.rmtree(self.run_dir, ignore_errors=True)
        for d in (self.run_dir, self.dbs, self.ctl):
            os.makedirs(d)
        base = free_port_block(2 * self.n_daemons + 1)
        self.base_port = base
        self.rw_base_port = base + self.n_quorum
        self.api_base = base + self.n_daemons
        self.stats = f"http://127.0.0.1:{self.api_base + self.n_daemons}"
        self.sock = os.path.join(self.dbs, "sidecar.sock")
        if len(self.sock) > 100:  # AF_UNIX path limit (108)
            self._sock_dir = tempfile.mkdtemp(prefix="bftkv-bench-")
            self.sock = os.path.join(self._sock_dir, "sidecar.sock")

    def start_sidecar(self) -> None:
        sc = self.config["sidecar"]
        argv = ["benchmarks.sidecar_main", "--control", self.ctl]
        if self.rehearse:
            argv.append("--rehearse")
        argv += ["--", "--listen", "unix:" + self.sock,
                 "--max-batch", str(sc["max_batch"]),
                 "--stats", self.stats.split("//")[1]]
        self.t_sidecar = time.monotonic()
        self._spawn("sidecar", argv, owns_chip=True)

    def genkeys(self) -> None:
        t0 = time.monotonic()
        bits = 1024 if self.rehearse else int(self.config["key_bits"])
        p = self._spawn("genkeys", [
            "bftkv_tpu.cmd.genkeys", "--out", self.keys,
            "--servers", str(self.n_quorum), "--rw", str(self.n_storage),
            "--users", str(self.config["users"]), "--bits", str(bits),
            "--alg", self.config["key_alg"],
            "--base-port", str(self.base_port),
            "--rw-base-port", str(self.rw_base_port),
        ])
        rc = p.wait(timeout=600)
        if rc != 0:
            raise BenchFailure(f"genkeys exited {rc}")
        self.phases["genkeys"] = time.monotonic() - t0

    def check_alive(self) -> None:
        for name in ("sidecar", "cluster"):
            p = self.procs.get(name)
            if p is not None and p.poll() is not None:
                if name == "sidecar" and p.returncode == 3:
                    raise BenchFailure(
                        "no TPU: the sidecar's JAX found no accelerator "
                        "(use --rehearse for a CPU walk-through)")
                raise BenchFailure(f"child '{name}' died with exit code "
                                   f"{p.returncode}")

    def wait_sidecar(self, timeout: float) -> None:
        """Until the stats endpoint answers: the sidecar binds only after
        every launchable program is compiled or loaded."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            self.check_alive()
            try:
                self.sidecar_info = http_json(self.stats + "/info", 2.0)["sidecar"]
                break
            except (OSError, ValueError, KeyError):
                time.sleep(0.25)
        else:
            raise BenchFailure(f"ready-wait ran out: the sidecar did not "
                               f"answer in {timeout:.0f} s")
        self.phases["sidecar_ready"] = time.monotonic() - self.t_sidecar
        self.device = self.sidecar_info["device_plane"]["device"]

    def start_daemons(self) -> None:
        self._spawn("cluster", [
            "bftkv_tpu.cmd.run_cluster", "--keys", self.keys,
            "--db-root", self.dbs, "--storage", self.config["storage"],
            "--api-base", str(self.api_base),
            "--sidecar", "unix:" + self.sock,
        ])

    def wait_daemons(self, timeout: float) -> None:
        t0 = time.monotonic()
        pending = list(range(self.n_daemons))
        while pending and time.monotonic() - t0 < timeout:
            self.check_alive()
            still = []
            for i in pending:
                try:
                    http_json(f"http://127.0.0.1:{self.api_base + i}/info", 2.0)
                except (OSError, ValueError):
                    still.append(i)
            pending = still
            if pending:
                time.sleep(0.25)
        if pending:
            raise BenchFailure(f"ready-wait ran out: {len(pending)} of "
                               f"{self.n_daemons} daemons did not answer "
                               f"in {timeout:.0f} s")
        kids = [c for c in children_of(self.procs["cluster"].pid) if c["name"]]
        if len(kids) != self.n_daemons:
            raise BenchFailure(f"run_cluster has {len(kids)} daemon children, "
                               f"the configuration has {self.n_daemons}")
        for i, c in enumerate(kids):  # run_cluster's order: sorted homes
            c["api"] = f"http://127.0.0.1:{self.api_base + i}"
        self.daemons = kids
        self.phases["daemons_up"] = time.monotonic() - t0

    # -- the sidecar's control file ----------------------------------------

    def control(self, op: str, timeout: float = 60.0, **kw) -> dict:
        n, self._n_cmd = self._n_cmd, self._n_cmd + 1
        tmp = os.path.join(self.ctl, f".cmd-{n}.tmp")
        with open(tmp, "w") as f:
            json.dump({"op": op, **kw}, f)
        os.replace(tmp, os.path.join(self.ctl, f"cmd-{n}.json"))
        ack = os.path.join(self.ctl, f"ack-{n}.json")
        deadline = time.monotonic() + timeout
        while not os.path.exists(ack):
            self.check_alive()
            if time.monotonic() > deadline:
                raise BenchFailure(f"the sidecar did not answer control "
                                   f"operation '{op}' in {timeout:.0f} s")
            time.sleep(0.01)
        with open(ack) as f:
            reply = json.load(f)
        if not reply.get("ok"):
            raise BenchFailure(f"control operation '{op}' failed in the "
                               f"sidecar: {reply.get('error')}")
        return reply

    # -- counters -----------------------------------------------------------

    def scrape(self) -> dict:
        """Every process's counters and CPU seconds, at one instant (as
        near as sequential HTTP gets: a few ms per process)."""
        snap = {"t": time.monotonic(), "daemons": {}, "cpu": {}}
        for d in self.daemons:
            snap["daemons"][d["name"]] = http_json(d["api"] + "/metrics")
            snap["cpu"][d["name"]] = cpu_seconds(d["pid"])
        snap["sidecar"] = http_json(self.stats + "/metrics")
        snap["cpu"]["sidecar"] = cpu_seconds(self.procs["sidecar"].pid)
        return snap

    # -- teardown -----------------------------------------------------------

    def stop(self) -> None:
        """SIGTERM the launchers (run_cluster stops its daemons, the log
        engine closes), then SIGKILL whatever is left of each session."""
        live = [p for p in self.procs.values() if p.poll() is None]
        for p in live:
            try:
                p.send_signal(signal.SIGTERM)
            except OSError:
                pass
        deadline = time.monotonic() + 20
        for p in live:
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                pass
        for p in self.procs.values():
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except OSError:
                pass
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
        for f in self._files:
            f.close()
        self._files = []
        if self._sock_dir:
            shutil.rmtree(self._sock_dir, ignore_errors=True)

    def log_tails(self) -> str:
        return "\n".join(
            f"--- tail of {name}.log ---\n{tail(path)}"
            for name, path in self.logs.items() if os.path.exists(path)
        )
