#!/usr/bin/env python3
"""chip_smoke.py — does the served path still start, and use, the chip?

One run of the system's main path on one directly attached TPU, through
the entry points a user calls:

    client (bftrw) → HTTP transport → 8 replica daemons → dispatcher →
    ONE sidecar process that owns the chip → RNS kernels

on upstream's documented local deployment (``scripts/run.sh``: 4 quorum
servers + 4 storage nodes + 1 user) with RSA-2048 identities and
on-disk log storage.  It loads 4096 keys × 1 KB, reads every one back,
checks the store's guarantees on a few single operations, then reads
every process's counters and FAILS unless the device did the crypto and
nothing retreated to a host path on the way.  No rates: this is a
smoke.  Lines before the last are JSON records of what ran; the last
line is the verdict the driver reads.

    python chip_smoke.py                # one chip (what the driver runs)
    python chip_smoke.py --chips 4      # sharded verify/sign vs single
                                        # device, one process, no cluster
    python chip_smoke.py --rehearse     # same flow, tiny, on the CPU

This process never imports JAX: a chip belongs to one process at a
time, and every phase that needs it is a child that runs alone.
"""

from __future__ import annotations

import argparse
import base64
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
SERVERS, STORAGE = 4, 4
TRACEBACK = "Traceback (most recent call last)"


class SmokeFailure(Exception):
    pass


def emit(**record) -> None:
    print(json.dumps(record, sort_keys=True), flush=True)


# ---------------------------------------------------------------------------
# Child roles — each runs alone in its own process and may import JAX.
# ---------------------------------------------------------------------------


def _device() -> dict:
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def _timed(fn, repeats: int = 3):
    """(result, first-call seconds, median seconds of ``repeats`` more)."""
    t0 = time.perf_counter()
    out = fn()
    first = time.perf_counter() - t0
    later = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        later.append(time.perf_counter() - t0)
    return out, first, sorted(later)[len(later) // 2]


def _signed_items(rng: random.Random, rows: int, n_keys: int = 2):
    """``rows`` (message, signature, public key) triples under a few
    fresh RSA-2048 keys, every 7th signature forged; plus the expected
    verdicts by host ``pow``."""
    from bftkv_tpu.crypto import rsa

    keys = [rsa.generate(2048) for _ in range(n_keys)]
    items, want = [], []
    for i in range(rows):
        key = keys[i % n_keys]
        msg = rng.randbytes(64)
        sig = rsa.sign(msg, key)
        if i % 7 == 3:
            sig = sig[:-1] + bytes([sig[-1] ^ 1])
        items.append((msg, sig, key.public))
    t0 = time.perf_counter()
    want = [rsa.verify_host(m, s, k) for m, s, k in items]
    return keys, items, want, time.perf_counter() - t0


def role_kernels(args) -> int:
    """Forced ``pallas`` vs forced ``xla`` vs host ``pow`` on identical
    operands: one RSA-2048 verify batch with forged rows and one
    1024-bit pow batch, through the domains a flush uses.  Verdicts and
    residues must be equal; a Pallas retreat to XLA is fatal."""
    dev = _device()
    emit(phase="device", **dev)
    if not args.rehearse and dev["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 2
    from bftkv_tpu import ops
    from bftkv_tpu.crypto import rsa
    from bftkv_tpu.ops import rns

    ops.enable_compile_cache()
    rng = random.Random(args.seed)
    v_rows, p_rows = (8, 8) if args.rehearse else (256, 64)
    keys, items, want, host_verify_s = _signed_items(rng, v_rows)
    mods = [k.p for k in keys] + [k.q for k in keys]
    pw = [
        (rng.getrandbits(1000), rng.getrandbits(1024), mods[i % len(mods)])
        for i in range(p_rows)
    ]
    t0 = time.perf_counter()
    pw_want = [pow(b, e, m) for b, e, m in pw]
    host_pow_s = time.perf_counter() - t0

    record: dict = {
        "verify": {"rows": v_rows, "forged": want.count(False),
                   "host_pow_s": host_verify_s},
        "pow": {"rows": p_rows, "bits": 1024, "host_pow_s": host_pow_s},
    }
    bad = []
    for backend in ("pallas", "xla"):
        os.environ["BFTKV_RNS_VERIFY_BACKEND"] = backend
        os.environ["BFTKV_RNS_POW_BACKEND"] = backend
        dom = rsa.VerifierDomain(host_threshold=0)
        got, first, steady = _timed(lambda: list(dom.verify_batch(items)))
        record["verify"][backend] = {
            "first_call_s": first, "call_s": steady,
            "equal": [bool(g) for g in got] == want,
        }
        got, first, steady = _timed(
            lambda: rns.power_mod_rns(
                [b for b, _, _ in pw], [e for _, e, _ in pw],
                [m for _, _, m in pw], n_bits=1024,
            )
        )
        record["pow"][backend] = {
            "first_call_s": first, "call_s": steady,
            "equal": got == pw_want,
        }
        for op in ("verify", "pow"):
            if not record[op][backend]["equal"]:
                bad.append(f"{op} via {backend} disagrees with host pow")
    status = rns.pallas_status()
    record["pallas_status"] = status
    for which, st in status.items():
        if st != "ok":
            bad.append(f"forced pallas {which} chain did not run: {st}")
    emit(phase="kernels", **record)
    for msg in bad:
        print(f"chip_smoke: {msg}", file=sys.stderr)
    return 1 if bad else 0


def role_mesh(args) -> int:
    """One process, every local device: the sharded verify and sign
    entry points a multi-device sidecar flush takes, against the
    single-device ones (``BFTKV_SHARD=off``) and the host."""
    dev = _device()
    emit(phase="device", **dev)
    if not args.rehearse and (dev["platform"] != "tpu" or dev["count"] != 4):
        print("chip_smoke: --chips 4 needs four TPU devices, found "
              f"{dev['count']} x {dev['platform']}", file=sys.stderr)
        return 2
    import jax
    import numpy as np

    from bftkv_tpu import ops
    from bftkv_tpu.crypto import rsa
    from bftkv_tpu.ops import limb, rns

    ops.enable_compile_cache()
    rng = random.Random(args.seed)
    v_rows, s_rows = (256, 32) if args.rehearse else (4096, 1024)
    keys, items, want, _ = _signed_items(rng, v_rows, n_keys=4)
    to_sign = [(rng.randbytes(64), keys[i % 4]) for i in range(s_rows)]
    sig_want = [rsa.sign(m, k) for m, k in to_sign]

    def shard_mode(mode: str) -> None:
        os.environ["BFTKV_SHARD"] = mode
        rns._mesh.cache_clear()

    shard_mode("auto")
    mesh = rns._mesh()
    if mesh is None or mesh.devices.size != dev["count"]:
        print("chip_smoke: the sharding seam built no mesh over the "
              "local devices", file=sys.stderr)
        return 1

    # Where the operands of the two sharded programs live: the compiled
    # programs' own input/output shardings, and the result array of a
    # real sharded verify call — "everything on device 0" would show.
    def layout(name, compiled, batch_operands) -> None:
        # jit prunes unused key rows, so only the leading batch
        # operands line up with their shapes; the rest are key rows.
        ins = jax.tree_util.tree_leaves(compiled.input_shardings[0])
        n = len(batch_operands)
        emit(
            phase="sharding", program=name,
            batch_operands=[
                {"shape": list(a.shape), "sharding": str(s),
                 "per_device": list(s.shard_shape(a.shape))}
                for a, s in zip(batch_operands, ins)
            ],
            key_rows=sorted({str(s) for s in ins[n:]}),
            outputs=[str(s) for s in jax.tree_util.tree_leaves(
                compiled.output_shardings)],
        )

    ctx = rns.context()
    urows = [ctx.key_rows(k.n) for k in keys]
    urows += [urows[0]] * (64 - len(urows))
    sig_d = np.stack([
        limb.int_to_limbs(int.from_bytes(s, "big"), 128) for _, s, _ in items
    ])
    em_d = np.stack([
        limb.int_to_limbs(rsa.emsa_pkcs1v15_sha256(m, 256), 128)
        for m, _, _ in items
    ])
    idx = np.asarray([i % 4 for i in range(v_rows)], dtype=np.int32)
    v_args = (
        rns.digits_to_halves_u8(sig_d), rns.digits_to_halves_u8(em_d),
        idx, rns.stack_key_rows(urows),
    )
    layout("verify", rns._jitted_verify_gather_sharded()
           .lower(*v_args).compile(), v_args[:3])
    out = rns.verify_e65537_rns_indexed(sig_d, em_d, idx, v_args[3])
    emit(
        phase="sharding", program="verify.result",
        sharding=str(out.sharding),
        shards={str(s.device): list(s.data.shape)
                for s in out.addressable_shards},
    )
    direct_ok = [bool(b) for b in np.asarray(out)] == want
    pctx = rns.context(64, 1024)
    rows = 2 * s_rows
    p_args = (
        np.zeros((rows, 128), np.uint8), np.zeros((256, rows), np.uint8),
        np.zeros((rows,), np.int32),
        rns.stack_key_rows([pctx.key_rows(keys[0].p)] * 64),
    )
    layout("sign", rns._jitted_pow_sharded(64, 1024)
           .lower(*p_args).compile(), p_args[:3])

    record: dict = {"verify_rows": v_rows, "sign_rows": s_rows,
                    "forged": want.count(False),
                    "result_array_equal": direct_ok}
    bad = [] if direct_ok else ["sharded verify result array is wrong"]
    results = {}
    for mode in ("auto", "off"):
        shard_mode(mode)
        name = "sharded" if mode == "auto" else "single"
        vd = rsa.VerifierDomain(host_threshold=0)
        sd = rsa.SignerDomain(host_threshold=0)
        ok, v_first, v_s = _timed(lambda: list(vd.verify_batch(items)), 1)
        sigs, s_first, s_s = _timed(lambda: sd.sign_batch(to_sign), 1)
        results[name] = ([bool(b) for b in ok], sigs)
        record[name] = {
            "devices": dev["count"] if mode == "auto" else 1,
            "verify_first_call_s": v_first, "verify_call_s": v_s,
            "sign_first_call_s": s_first, "sign_call_s": s_s,
        }
    record["verdicts_identical"] = (
        results["sharded"][0] == results["single"][0] == want
    )
    record["signatures_identical"] = (
        results["sharded"][1] == results["single"][1] == sig_want
    )
    if not record["verdicts_identical"]:
        bad.append("sharded, single-device and host verdicts differ")
    if not record["signatures_identical"]:
        bad.append("sharded, single-device and host signatures differ")
    emit(phase="mesh", **record)
    for msg in bad:
        print(f"chip_smoke: {msg}", file=sys.stderr)
    return 1 if bad else 0


def role_tiers(args) -> int:
    """Build (on demand, as any process does) and load the three native
    host tiers; a tier that has a compiler and did not load is a
    failure, not a quiet Python fallback."""
    from bftkv_tpu import packet
    from bftkv_tpu.crypto import rsa

    tiers = {
        "_montmodexp": rsa._MM is not None,
        "_packetcodec": packet._C is not None,
    }
    try:
        from bftkv_tpu.storage import native

        native._load()
        tiers["libbftkvstore"] = True
    except Exception:
        tiers["libbftkvstore"] = False
    cc = bool(shutil.which("make")) and bool(
        shutil.which(os.environ.get("CC", "gcc"))
    )
    cxx = cc and bool(shutil.which(os.environ.get("CXX", "g++")))
    emit(phase="native_tiers", loaded=tiers,
         compiler={"cc": cc, "cxx": cxx})
    missing = [
        name for name, ok in tiers.items()
        if not ok and (cxx if name == "libbftkvstore" else cc)
    ]
    for name in missing:
        print(f"chip_smoke: native tier {name} did not build/load",
              file=sys.stderr)
    return 1 if missing else 0


ROLES = {"kernels": role_kernels, "mesh": role_mesh, "tiers": role_tiers}


# ---------------------------------------------------------------------------
# The parent: starts children, drives the cluster, reads the counters.
# ---------------------------------------------------------------------------


def child_env(args, *, owns_chip: bool, extra: dict | None = None) -> dict:
    """The sidecar and the kernel phases get the caller's environment
    (so JAX picks the chip); every other child is pinned to the CPU."""
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TPU_LOG_DIR", "disabled")
    if args.rehearse or not owns_chip:
        env["JAX_PLATFORMS"] = "cpu"
    env.update(extra or {})
    return env


def run_role(args, role: str, *, owns_chip: bool, extra=None) -> list[dict]:
    """Run one child role to its end; relay and return its records."""
    cmd = [sys.executable, os.path.abspath(__file__), "--role", role,
           "--seed", str(args.seed)]
    if args.rehearse:
        cmd.append("--rehearse")
    t0 = time.monotonic()
    p = subprocess.run(
        cmd, cwd=ROOT, env=child_env(args, owns_chip=owns_chip, extra=extra),
        stdout=subprocess.PIPE, text=True, timeout=args.phase_timeout,
    )
    records = []
    for line in p.stdout.splitlines():
        try:
            records.append(json.loads(line))
            print(line, flush=True)
        except ValueError:
            print(line, file=sys.stderr)
    emit(phase=f"{role}.done", seconds=time.monotonic() - t0,
         exit_code=p.returncode)
    if p.returncode != 0:
        raise SmokeFailure(f"{role} phase exited {p.returncode}")
    return records


def free_port_block(n: int) -> int:
    """First port of ``n`` consecutive ports nobody is bound to (asked
    of the kernel by binding them all), searched from a base that
    differs per process so that concurrent runs rarely collide."""
    lo, span = 22100, 9900  # above the tests' blocks, below ephemeral
    start = (os.getpid() * 64) % span
    for off in range(start, start + span, n):
        base = lo + off % (span - n)
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
    raise SmokeFailure("no free port block")


def http_json(url: str, timeout: float = 10.0):
    req = urllib.request.Request(url, headers={"accept": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def children_of(pid: int) -> list[dict]:
    """Direct children of ``pid`` with their command and the
    JAX_PLATFORMS each was started with (Linux /proc)."""
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
                argv = f.read().split(b"\0")
            with open(f"/proc/{entry}/environ", "rb") as f:
                env = dict(
                    kv.split(b"=", 1) for kv in f.read().split(b"\0")
                    if b"=" in kv
                )
        except (OSError, IndexError, ValueError):
            continue
        module = next(
            (a.decode() for a in argv if a.startswith(b"bftkv_tpu.")), "?"
        )
        home = next(
            (argv[i + 1].decode() for i, a in enumerate(argv[:-1])
             if a == b"--home"), "",
        )
        out.append({
            "pid": int(entry), "module": module,
            "name": os.path.basename(home) or module.rsplit(".", 1)[-1],
            "JAX_PLATFORMS": env.get(b"JAX_PLATFORMS", b"").decode() or None,
        })
    return sorted(out, key=lambda c: c["pid"])


class Cluster:
    """genkeys + run_cluster --sidecar auto, through the CLIs."""

    def __init__(self, args, work: str):
        self.args = args
        self.work = work
        self.keys = os.path.join(work, "keys")
        self.dbs = os.path.join(work, "dbs")
        self.log_path = os.path.join(work, "cluster.log")
        self.proc: subprocess.Popen | None = None
        self.sidecar_pid: int | None = None
        base = free_port_block(SERVERS + STORAGE + SERVERS + STORAGE + 1)
        self.base_port = base
        self.rw_base_port = base + SERVERS
        self.api_base = base + SERVERS + STORAGE
        self.n_daemons = SERVERS + STORAGE
        self.stats = f"http://127.0.0.1:{self.api_base + self.n_daemons}"
        self.sock = os.path.join(self.dbs, "sidecar.sock")
        if len(self.sock) > 100:  # AF_UNIX path limit (108)
            self.sock = os.path.join(
                tempfile.mkdtemp(prefix="bftkv-smoke-"), "sidecar.sock"
            )
        self.user_home = os.path.join(self.keys, "u01")

    def module(self, name: str, *argv: str, **kw):
        """One CLI of the program, run to its end on the CPU backend."""
        return subprocess.run(
            [sys.executable, "-m", f"bftkv_tpu.cmd.{name}", *argv],
            cwd=ROOT, env=child_env(self.args, owns_chip=False), **kw,
        )

    def genkeys(self) -> None:
        t0 = time.monotonic()
        p = self.module(
            "genkeys", "--out", self.keys,
            "--servers", str(SERVERS), "--rw", str(STORAGE), "--users", "1",
            "--bits", "2048",
            "--base-port", str(self.base_port),
            "--rw-base-port", str(self.rw_base_port),
            stdout=subprocess.DEVNULL,
        )
        if p.returncode != 0:
            raise SmokeFailure(f"genkeys exited {p.returncode}")
        emit(phase="genkeys", identities="RSA-2048",
             homes=sorted(os.listdir(self.keys)),
             seconds=time.monotonic() - t0)

    def start(self) -> None:
        self.t_start = time.monotonic()
        self.log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [
                sys.executable, "-m", "bftkv_tpu.cmd.run_cluster",
                "--keys", self.keys, "--db-root", self.dbs,
                "--storage", "log",
                "--api-base", str(self.api_base),
                "--sidecar", f"auto:unix:{self.sock}",
            ],
            cwd=ROOT,
            # run_cluster hands the sidecar this environment and pins
            # every daemon to the CPU itself.
            env=child_env(self.args, owns_chip=True),
            stdout=self.log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )

    def wait_ready(self) -> dict:
        """Until the sidecar's stats endpoint answers (it binds only
        after every launchable program is compiled) and every daemon's
        API does."""
        deadline = time.monotonic() + self.args.ready_timeout
        info = None
        pending = [f"http://127.0.0.1:{self.api_base + i}"
                   for i in range(self.n_daemons)]
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"run_cluster exited {self.proc.returncode} at start-up"
                )
            try:
                if info is None:
                    info = http_json(self.stats + "/info", 2.0)["sidecar"]
                    self.ready_s = time.monotonic() - self.t_start
                pending = [u for u in pending if not self._up(u)]
                if not pending:
                    break
            except (OSError, ValueError):
                if info is None and self.sidecar() is None and (
                    time.monotonic() - self.t_start > 5
                ):
                    raise SmokeFailure("the sidecar died during start-up")
            time.sleep(0.5)
        else:
            raise SmokeFailure(
                "cluster not ready in %ds (sidecar %s, daemons pending %d)"
                % (self.args.ready_timeout,
                   "ready" if info else "NOT ready", len(pending))
            )
        sc = self.sidecar()
        self.sidecar_pid = sc["pid"] if sc else None
        return info

    @staticmethod
    def _up(url: str) -> bool:
        try:
            http_json(url + "/info", 2.0)
            return True
        except (OSError, ValueError):
            return False

    def sidecar(self) -> dict | None:
        for c in children_of(self.proc.pid):
            if c["module"].endswith("verify_sidecar"):
                return c
        return None

    def bftrw(self, *argv: str, stdin: bytes | None = None):
        return self.module(
            "bftrw", "--home", self.user_home, *argv,
            input=stdin, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            timeout=self.args.phase_timeout,
        )

    def stop(self) -> None:
        if self.proc is None:
            return
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass
        try:  # whatever survived its launcher
            os.killpg(self.proc.pid, signal.SIGKILL)
        except OSError:
            pass
        self.proc.wait()
        self.log.close()
        if os.path.dirname(self.sock) != self.dbs:
            shutil.rmtree(os.path.dirname(self.sock), ignore_errors=True)

    def log_text(self) -> str:
        with open(self.log_path, "rb") as f:
            return f.read().decode(errors="replace")


def make_records(seed: int, n: int) -> list[tuple[str, str]]:
    """``n`` keys × 1 KB printable values, from the seed alone."""
    rng = random.Random(seed)
    return [
        (f"smoke-{seed}-{i:05d}",
         base64.b64encode(rng.randbytes(768)).decode())
        for i in range(n)
    ]


def phase_bulk(cl: Cluster, records, batch: int, failures: list) -> None:
    for name, verb in (("load", "writemany"), ("readback", "readmany")):
        t0 = time.monotonic()
        done = wrong = 0
        for off in range(0, len(records), batch):
            chunk = records[off : off + batch]
            if verb == "writemany":
                p = cl.bftrw("writemany", stdin="".join(
                    f"{k}={v}\n" for k, v in chunk).encode())
                got = len(chunk) if p.returncode == 0 else 0
            else:
                p = cl.bftrw("readmany", *[k for k, _ in chunk])
                back = dict(
                    line.split("=", 1)
                    for line in p.stdout.decode().splitlines() if "=" in line
                )
                got = sum(back.get(k) == v for k, v in chunk)
                wrong += sum(k in back and back[k] != v for k, v in chunk)
            done += got
            if got != len(chunk):
                failures.append(
                    f"{verb} batch at {off}: {got}/{len(chunk)} "
                    f"(exit {p.returncode}): "
                    + p.stderr.decode(errors="replace")[-400:]
                )
                break
        emit(phase=name, ops=done, of=len(records), batch=batch,
             value_bytes=len(records[0][1]), wrong_values=wrong,
             seconds=time.monotonic() - t0)


def phase_singles(cl: Cluster, seed: int, failures: list) -> None:
    """The guarantees, as far as a run can show them: a write is read
    back, an overwrite (a higher timestamp) wins, a write-once key
    refuses its second write and keeps its first value."""
    t0 = time.monotonic()
    ops = 0

    def expect(what: str, p, *, ok: bool = True, stdout: bytes | None = None):
        nonlocal ops
        ops += 1
        if (p.returncode == 0) != ok or (
            stdout is not None and p.stdout != stdout
        ):
            failures.append(
                f"{what}: exit {p.returncode}, stdout {p.stdout[:80]!r}: "
                + p.stderr.decode(errors="replace")[-300:]
            )

    k, w = f"smoke-{seed}-single", f"smoke-{seed}-once"
    expect("write", cl.bftrw("write", k, "first"))
    expect("read", cl.bftrw("read", k), stdout=b"first")
    expect("overwrite", cl.bftrw("write", k, "second"))
    expect("read after overwrite", cl.bftrw("read", k), stdout=b"second")
    expect("writeonce", cl.bftrw("writeonce", w, "kept"))
    expect("second writeonce must be refused",
           cl.bftrw("writeonce", w, "clobbered"), ok=False)
    expect("read write-once key", cl.bftrw("read", w), stdout=b"kept")
    emit(phase="singles", ops=ops, seconds=time.monotonic() - t0,
         overwrite_visible=not any("overwrite" in f for f in failures),
         writeonce_rewrite_refused=not any("once" in f for f in failures))


#: Counters that must be zero in every process: each one is a way of
#: finishing "green" without the chip.
MUST_BE_ZERO = (
    "verify.remote_fallback", "sign.remote_fallback",
    "modexp.remote_fallback", "sign.rns_fallback", "rns.pallas_fallback",
    "sidecar.shed", "verify.remote_shed", "sign.remote_shed",
    "verify.remote_breaker_open", "crypto.sidecar.dishonest",
)


def total(snap: dict, name: str) -> float:
    """A counter summed over its label sets (``name`` and ``name{…}``)."""
    return sum(
        v for k, v in snap.items()
        if k == name or k.startswith(name + "{")
    )


def phase_counters(cl: Cluster, procs: list[dict], batch: int,
                   failures: list) -> None:
    try:
        side = http_json(cl.stats + "/info")["sidecar"]
        snaps = {"sidecar": http_json(cl.stats + "/metrics")}
    except (OSError, ValueError) as e:
        failures.append(f"the sidecar's stats endpoint is gone: {e}")
        return
    daemons = sorted(  # run_cluster's order: API port = api_base + index
        (c for c in procs if c["module"].endswith("cmd.bftkv")),
        key=lambda c: c["name"],
    )
    for i, c in enumerate(daemons):
        try:
            snaps[c["name"]] = http_json(
                f"http://127.0.0.1:{cl.api_base + i}/metrics"
            )
        except (OSError, ValueError) as e:
            failures.append(f"daemon {c['name']}: /metrics unreachable: {e}")
    nonzero = {
        f"{proc}:{name}": total(snap, name)
        for proc, snap in snaps.items() for name in MUST_BE_ZERO
        if total(snap, name)
    }
    plane = side["device_plane"]
    launched = plane["launched"]
    tenants = [s for p, s in snaps.items() if p != "sidecar"]
    emit(
        phase="counters",
        launched=launched,
        # Flush sizes as the dispatchers saw them (counts, no rates).
        flushes={
            role: {k: b[k] for k in
                   ("flushes", "items", "occupancy_per_launch", "batch_p50")}
            for role, b in side["batch"].items()
        },
        kernels=plane["kernels"],
        compiled_since_warmup=plane["compiled_since_warmup"],
        launch_rtt_s=plane["launch_rtt_s"],
        buffer_rings=plane["buffer_rings"],
        queue=side["queue"],
        tenants={
            name: sum(total(s, name) for s in tenants)
            for name in ("verify.remote", "sign.remote", "verify.cache.hits",
                         "verify.cache.misses", "verify.host", "sign.host")
        },
        must_be_zero={
            n: sum(total(s, n) for s in snaps.values()) for n in MUST_BE_ZERO
        },
    )
    for key, v in nonzero.items():
        failures.append(f"{key} = {v:g} (must be 0)")
    if side["queue"]["shed"]:
        failures.append(f"sidecar shed {side['queue']['shed']} requests")
    for role, st in plane["kernels"]["pallas_status"].items():
        if st.startswith("fallback"):
            failures.append(f"pallas {role} chain: {st}")
    if plane["compiled_since_warmup"]:
        failures.append(
            f"{plane['compiled_since_warmup']} program(s) compiled inside "
            "the request path: the sidecar's warm-up missed a shape"
        )
    if cl.args.rehearse:
        return  # calibration pins host on the CPU: printed, not required
    need = min(256, batch)
    for role in ("verify", "sign"):
        got = launched[role]
        if got["items"] <= 0:
            failures.append(f"{role}.device = 0: no {role} ran on the chip")
        elif got["max_items_per_launch"] < need:
            failures.append(
                f"largest {role} launch carried "
                f"{got['max_items_per_launch']} items (< {need}); "
                f"calibration: {plane['calibration']}"
            )


def run_cluster_phase(args, work: str, failures: list) -> dict:
    """→ the device the sidecar reports."""
    cl = Cluster(args, work)
    cl.genkeys()
    cl.start()
    try:
        info = cl.wait_ready()
        plane = info["device_plane"]
        procs = children_of(cl.proc.pid)
        emit(phase="children", processes=[
            {k: c[k] for k in ("name", "module", "JAX_PLATFORMS")}
            for c in procs
        ])
        for c in procs:
            if (not c["module"].endswith("verify_sidecar")
                    and c["JAX_PLATFORMS"] != "cpu"):
                failures.append(
                    f"{c['name']} was started with JAX_PLATFORMS="
                    f"{c['JAX_PLATFORMS']}: only the sidecar may see the chip"
                )
        warm = plane["warmup"]
        emit(
            phase="sidecar_ready",
            seconds_from_start=cl.ready_s,
            cluster_ready_seconds=time.monotonic() - cl.t_start,
            warmup_seconds=warm["seconds"],
            compile_cache=warm.get("compile_cache"),
            warmed=[(s["role"], s["items"], s["seconds"])
                    for s in warm["shapes"]],
            device=plane["device"],
            calibration=plane["calibration"],
        )
        dev = plane["device"]
        if not args.rehearse:
            if dev["platform"] != "tpu":
                raise SmokeFailure(
                    f"the sidecar runs on {dev['platform']}, not on a TPU"
                )
            if plane["calibration"]["verify_crossover"] > min(256, args.batch):
                raise SmokeFailure(
                    "calibration puts the crossover above the smoke's "
                    f"batches: {plane['calibration']}"
                )
        if args.inject == "unlink-socket":
            os.unlink(cl.sock)
        records = make_records(args.seed, args.keys)
        phase_bulk(cl, records, args.batch, failures)
        if args.inject == "kill-sidecar":
            os.kill(cl.sidecar_pid, signal.SIGKILL)
        phase_singles(cl, args.seed, failures)
        phase_counters(cl, procs, args.batch, failures)
        alive = cl.sidecar()
        if alive is None or alive["pid"] != cl.sidecar_pid:
            failures.append("the sidecar process died during the run")
        if cl.proc.poll() is not None:
            failures.append(
                f"run_cluster exited {cl.proc.returncode} during the run"
            )
        return dev
    finally:
        cl.stop()
        text = cl.log_text()
        if TRACEBACK in text:
            failures.append("a cluster process printed a traceback")
        if failures or TRACEBACK in text:
            sys.stderr.write(
                "---- cluster log (tail) ----\n" + text[-6000:] + "\n"
            )


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--seed", type=int, default=22,
                    help="key names, values and kernel operands come "
                         "from it (identities are fresh every run)")
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="4: only the sharded-vs-single-device phase, "
                         "in one process on four chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="the same flow at a tiny size on the CPU "
                         "backend; never prints the chip's verdict")
    ap.add_argument("--keys", type=int, default=None,
                    help="records to load (default 4096; 64 rehearsing)")
    ap.add_argument("--batch", type=int, default=None,
                    help="records per writemany/readmany call "
                         "(default 256; 32 rehearsing)")
    ap.add_argument("--phases", default="tiers,kernels,cluster",
                    help="comma list of tiers,kernels,cluster")
    ap.add_argument("--ready-timeout", type=int, default=600,
                    help="seconds to wait for sidecar and daemons")
    ap.add_argument("--phase-timeout", type=int, default=600,
                    help="seconds any one child may take")
    ap.add_argument("--keep", action="store_true",
                    help="keep the run's directory (keys, stores, logs)")
    # Test hooks (tests/test_chip_smoke.py): break the run on purpose.
    ap.add_argument("--inject", default="", help=argparse.SUPPRESS,
                    choices=["", "kill-sidecar", "unlink-socket"])
    ap.add_argument("--role", choices=sorted(ROLES), help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.role:
        return ROLES[args.role](args)
    args.keys = args.keys or (64 if args.rehearse else 4096)
    args.batch = args.batch or (32 if args.rehearse else 256)
    phases = ["mesh"] if args.chips == 4 else args.phases.split(",")

    t0 = time.monotonic()
    failures: list[str] = []
    device = None
    os.makedirs(os.path.join(ROOT, ".chip_smoke"), exist_ok=True)
    work = tempfile.mkdtemp(prefix="r", dir=os.path.join(ROOT, ".chip_smoke"))
    emit(phase="start", mode="rehearsal on the CPU backend" if args.rehearse
         else f"{args.chips} chip(s)", phases=phases, seed=args.seed,
         keys=args.keys, batch=args.batch)
    try:
        if "tiers" in phases:
            run_role(args, "tiers", owns_chip=False)
        if "mesh" in phases:
            flags = os.environ.get("XLA_FLAGS", "")
            extra = {"XLA_FLAGS": flags + " --xla_force_host_platform"
                     "_device_count=4"} if args.rehearse else None
            device = run_role(args, "mesh", owns_chip=True, extra=extra)[0]
        if "kernels" in phases:
            device = run_role(args, "kernels", owns_chip=True)[0]
        if "cluster" in phases:
            device = run_cluster_phase(args, work, failures)
    except (SmokeFailure, subprocess.TimeoutExpired) as e:
        failures.append(str(e))
    finally:
        if args.keep:
            print(f"chip_smoke: kept {work}", file=sys.stderr)
        else:
            shutil.rmtree(work, ignore_errors=True)
    if "jax" in sys.modules:
        failures.append("the parent imported jax")
    emit(phase="end", seconds=time.monotonic() - t0, failures=len(failures),
         parent_imported_jax="jax" in sys.modules)
    if failures or device is None:
        for f in failures or ["no phase reported a device"]:
            print(f"chip_smoke: FAIL: {f}", file=sys.stderr)
        return 1
    device = {k: device[k] for k in ("platform", "kind", "count")}
    if args.rehearse:
        print(json.dumps({"ok": True, "rehearsal": True, "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
