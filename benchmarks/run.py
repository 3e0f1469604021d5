"""One run of one cell: bring the deployment up, warm it, drive the
window through the client facade, read the layers, judge, tear down.

    python3 -m benchmarks.run --workload W --seed N --seconds S --trace 0|1

The last line of standard output is the result the driver reads (keys
``correct, attempted, failed, metrics, device`` and, traced,
``breakdown``, then ``compared``).  Everything else — timing, the
admission histogram, the trace summary — goes on earlier lines and into
``benchmarks/.run/<workload>-<seed>/``.  Every path to a non-zero exit
first writes one line that names it (``FAILED: ...``) with the tails of
the children's logs, to standard error and to ``failure.txt`` there.

Without a TPU the command fails; ``--rehearse`` walks the same flow tiny
on the CPU and prints no device metric.  ``--plant`` and
``--control-runs`` are for the controls that must come out incorrect
(``--plant stall``, a host that stands still, for the one that must not).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

T_PROCESS = time.monotonic()
CALLER_PLATFORMS = os.environ.get("JAX_PLATFORMS")  # the sidecar's, untouched
os.environ["JAX_PLATFORMS"] = "cpu"  # this process never holds the chip
# The host of a chip machine stands still now and then, for seconds, or its
# disk holds an fsync back, and every process of a run is on it.  The
# program's adaptive RPC deadline (8 x a peer's recent p99 + 0.1 s, from 1 s
# up: 1.1-2.7 s for a storage node of q4-rsa2048.load) then runs out on a
# replica that answers late, and a whole call fails with "rpc timeout" (the
# driver's check of PR 32: 512 of 448,000 operations in one set, 0 in the
# other).  So every process of a run keeps the fixed deadline, upstream's
# 10 s (http.go:39-50): a late answer is late, and the call's time counts
# the wait.  The caller's environment can say otherwise (the controls do:
# `--plant stall`, `stall_storage`); a configuration's `environment`
# reaches the children alone, and the deadline that matters is the client's.
os.environ.setdefault("BFTKV_ADAPTIVE_TIMEOUT", "off")

from benchmarks import generator, harness, judge, kinds, plants, tenant  # noqa: E402
from benchmarks.harness import ROOT, BenchFailure  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
READY_TIMEOUT_S = 900.0   # the sidecar's cold warm start compiles for minutes
WARM_TIMEOUT_S = 240.0
DRAIN_TIMEOUT_S = 90.0    # for a caller's last call after the window closed
# The traced span is one whole call cycle (every phase of a write_many:
# verifies, signs, verifies), read from the calls the window has finished
# by then; stop_trace costs about 13 s + 1.4 s per MB of trace (20 s of
# it per traced second of these cells), which is what the ceiling allows
# inside a 360 s run.
TRACE_AT = 0.3            # share of the window gone when tracing starts
TRACE_MIN_S, TRACE_MAX_S = 2.0, 4.0
STOP_TRACE_TIMEOUT_S = 200.0
STALLS = ("stall", "stall_storage")   # --plant: what must fail no operation


def load_manifest(path: str = "") -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


class Counters:
    """Growth of every counter between two scrapes of all processes."""

    def __init__(self, before: dict, after: dict):
        self._growth = {"sidecar": self._diff(before["sidecar"], after["sidecar"]),
                        "daemons": {}}
        for name, snap in after["daemons"].items():
            for k, v in self._diff(before["daemons"].get(name, {}), snap).items():
                d = self._growth["daemons"]
                d[k] = d.get(k, 0.0) + v

    @staticmethod
    def _diff(a: dict, b: dict) -> dict:
        return {k: v - a.get(k, 0) for k, v in b.items()
                if isinstance(v, (int, float))}

    def items(self, scope: str):
        return self._growth[scope].items()

    def total(self, scope: str, name: str) -> float:
        return sum(v for k, v in self._growth[scope].items()
                   if k == name or k.startswith(name + "{"))


class Run:
    def __init__(self, args):
        self.args = args
        self.manifest = load_manifest(args.manifest)
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if args.workload not in cells:
            raise BenchFailure(f"no cell '{args.workload}' in BENCHMARK.json "
                               f"(cells: {sorted(cells)})")
        self.cell = cells[args.workload]
        cfg = next(c for c in self.manifest["configs"]
                   if c["name"] == self.cell["config"])
        self.config = load_json(cfg["file"])
        self.mix = load_json("benchmarks", "traffic", self.cell["traffic"] + ".json")
        if args.rehearse:
            self.mix.update(self.mix.get("rehearse", {}))
        self.check_mix()
        self.run_dir = os.path.join(HERE, ".run", f"{args.workload}-{args.seed}")
        self.cluster = harness.Cluster(self.run_dir, self.config,
                                       rehearse=args.rehearse,
                                       chip_platforms=CALLER_PLATFORMS)
        self.timing: dict = {}
        self.apis: list = []   # one client per user of the configuration
        self.api = None        # u01's: the preload and the write-once pair
        self.pool = None
        self._down = False

    def check_mix(self) -> None:
        """What a mix says about its operations and its callers, checked
        before any child starts: kinds that live in files are loaded
        (their limits and plants with them), the names of ``--plant`` and
        of the mix's ``controls`` are known ones, there is a user to drive
        the callers and, where the mix updates or reads, keys to draw."""
        mix, name = self.mix, self.cell["traffic"]
        self.kinds = kinds.load(k for k, share in mix["ops"].items() if share)
        self.limits = [lim for k in self.kinds.values() for lim in k.limits]
        self.plants = dict(plants.PLANTS)
        for k in self.kinds.values():
            self.plants.update(k.plants)
        known = {"dead_child", *STALLS, *self.plants, *plants.SIDECAR_PLANTS}
        for plant in (getattr(self.args, "plant", ""),
                      *mix.get("controls", {})):
            if plant and plant not in known:
                raise BenchFailure(f"no plant '{plant}' (--plant, or the "
                                   f"controls of mix '{name}'): known are "
                                   f"{sorted(known)}")
        if int(self.config["users"]) < 1:
            raise BenchFailure(f"configuration '{self.cell['config']}' has no "
                               "user: users is the number of clients that "
                               "drive the callers, one at least")
        if (any(mix["ops"].get(k) for k in ("update", "read"))
                and int(mix.get("preload_records", 0)) < 1):
            raise BenchFailure(f"mix '{name}' updates or reads and preloads "
                               "no record: preload_records is 0")

    def kind_ctx(self) -> dict:
        return {"clients": self.apis, "config": self.config, "mix": self.mix,
                "seed": self.args.seed, "rehearse": bool(self.args.rehearse)}

    # -- output -------------------------------------------------------------

    def note(self, **record) -> None:
        line = json.dumps(record, sort_keys=True, default=str)
        print(line, flush=True)
        with open(os.path.join(self.run_dir, "notes.jsonl"), "a") as f:
            f.write(line + "\n")

    # -- set-up -------------------------------------------------------------

    def set_up(self) -> None:
        cl, a = self.cluster, self.args
        cl.prepare()
        cl.start_sidecar()   # first: its warm start is most of set-up
        if "tenant" in self.mix:  # beside it: the tenant's key and pool
            self.pool = tenant.Pool(
                a.seed, self.mix["tenant"],
                1024 if a.rehearse else int(self.config["key_bits"]))
        cl.genkeys()         # beside it too
        cl.wait_sidecar(READY_TIMEOUT_S)
        cl.start_daemons()
        cl.wait_daemons(120)
        off_cpu = [c["name"] for c in cl.daemons
                   if c["JAX_PLATFORMS"] != "cpu"]
        if off_cpu:
            raise BenchFailure(f"daemons not pinned to the CPU backend: {off_cpu}")
        if cl.device.get("count", 0) < self.cell["chips"] and not a.rehearse:
            raise BenchFailure(f"the cell asks for {self.cell['chips']} chips, "
                               f"JAX found {cl.device}")
        t0 = time.monotonic()
        try:
            from bftkv_tpu.api import open_client
        except ImportError as e:
            raise BenchFailure(f"the program is not in this checkout: {e}")
        # One user, one client: the callers are threads of one process
        # sharing it, as the daemon's own client API does.  (Several
        # clients of one identity fight over the one session a server
        # keeps per peer: "unknown transport session".)  Every user of
        # the configuration is opened, u01 ... uNN; caller i drives the
        # client of user i mod users (``generator.assign``).
        for n in range(1, int(self.config["users"]) + 1):
            home = os.path.join(cl.keys, f"u{n:02d}")
            try:
                self.apis.append(open_client(home))
            except Exception as e:
                raise BenchFailure(f"user home '{home}' does not open: {e!r}")
        self.api = self.apis[0]
        self.timing["clients_open"] = time.monotonic() - t0
        if self.pool is not None:
            self.pool.wait(120)
        if self.kinds:
            t0 = time.monotonic()
            for kind in self.kinds.values():
                kind.prepare(self.kind_ctx())
            self.timing["kinds_prepare"] = time.monotonic() - t0

    # -- one window ---------------------------------------------------------

    def every(self, plant: str) -> int:
        return int(self.mix.get("controls", {}).get(plant, {}).get("every", 7))

    def window(self, seed: int, seconds: float, plant: str, *, trace: bool,
               preload: bool) -> dict:
        in_sidecar = plant in plants.SIDECAR_PLANTS
        if in_sidecar:
            self.cluster.control("plant", name=plant,
                                 host_tier=bool(self.args.rehearse))
        try:
            return self._window(seed, seconds, plant, trace, preload)
        finally:
            if in_sidecar:
                try:
                    self.cluster.control("unplant")
                except BenchFailure:
                    pass  # the sidecar is gone; the run is failing already

    def _window(self, seed: int, seconds: float, plant: str, trace: bool,
                preload: bool) -> dict:
        cl, mix = self.cluster, self.mix
        calls: list = []
        t0 = time.monotonic()
        if preload and int(mix.get("preload_records", 0)):
            calls += generator.preload(self.api, mix, seed)
            self.timing["preload"] = time.monotonic() - t0
        keys = generator.KeySpace(int(mix.get("preload_records", 0)))
        gate = generator.Gate(int(mix["callers"]))
        planted = [plants.plant(plant if plant in self.plants else "", api,
                                self.every(plant), self.plants)
                   for api in self.apis]
        facades = generator.assign(planted, int(mix["callers"]))
        forger = None
        if self.pool is not None:
            forger = tenant.Tenant("unix:" + cl.sock, self.pool, seed, gate)
            forger.start()
        callers = [
            generator.Caller(i, facades[i], mix, seed, keys, gate, self.kinds)
            for i in range(int(mix["callers"]))
        ]
        t0 = time.monotonic()
        for c in callers:
            c.start()
        if not gate.wait_warm(WARM_TIMEOUT_S):
            raise BenchFailure("the callers' warm calls did not finish in "
                               f"{WARM_TIMEOUT_S:.0f} s")
        self.timing.setdefault("warm", time.monotonic() - t0)
        cl.check_alive()
        before = cl.scrape()
        cpu0 = time.process_time()
        tracer = None
        trace_out: dict = {}
        t_open = gate.open(seconds)
        if plant == "dead_child":  # a replica daemon dies mid-window
            victim = cl.daemons[-1]["pid"]
            threading.Timer(seconds / 2, os.kill, (victim, signal.SIGKILL)).start()
        if plant in STALLS:        # the host, or its disk, stands still
            n = self.args.stalls
            for k in range(1, n + 1):
                threading.Timer(seconds * k / (n + 1), self.stall,
                                (self.args.stall_seconds,
                                 plant == "stall_storage")).start()
        if trace:
            tracer = threading.Thread(
                target=self._trace, args=(t_open, seconds, callers, trace_out),
                daemon=True)
            tracer.start()
        for c in callers:
            c.join(seconds + DRAIN_TIMEOUT_S)
            if c.is_alive():
                raise BenchFailure(f"{c.name} did not return "
                                   f"{DRAIN_TIMEOUT_S:.0f} s after the "
                                   "window closed")
            if c.error is not None:
                raise BenchFailure(f"{c.name} raised {c.error!r}")
        t_close = time.monotonic()
        cpu1 = time.process_time()
        cl.check_alive()
        after = cl.scrape()
        requests: list = []
        if forger is not None:
            # its open request is waited for; one that never returns
            # counts as unanswered
            forger.join(2 * tenant.REPLY_TIMEOUT_S + 10)
            if forger.error is not None:
                raise BenchFailure(f"the tenant raised {forger.error!r}")
            requests = list(forger.requests)
            if forger.is_alive():
                requests.append(tenant.Request([], 0.0))
        if tracer is not None:
            tracer.join(STOP_TRACE_TIMEOUT_S + 30)
            if tracer.is_alive():
                raise BenchFailure("stop_trace did not return in "
                                   f"{STOP_TRACE_TIMEOUT_S:.0f} s")
            if "error" in trace_out:
                raise BenchFailure(f"tracing failed: {trace_out['error']}")
        for c in callers:
            calls += c.calls
        win = [c for c in calls if c.phase == "window"]
        cpu = {"client": cpu1 - cpu0,
               "sidecar": after["cpu"]["sidecar"] - before["cpu"]["sidecar"],
               "daemons": sum(after["cpu"][d["name"]] - before["cpu"][d["name"]]
                              for d in cl.daemons)}
        return {"seed": seed, "plant": plant, "calls": calls, "window": win,
                "t_open": t_open, "t_close": t_close,
                "counters": Counters(before, after), "cpu_s": cpu,
                "trace": trace_out, "after": after, "tenant": requests}

    def stall(self, seconds: float, storage_only: bool) -> None:
        """Every process of the run stands still for ``seconds``, this one
        included, as the host of a chip machine does now and then — or the
        storage nodes alone, as under a disk that holds an fsync back: a
        helper outside them all stops them and lets them go on."""
        cl = self.cluster
        if storage_only:
            pids = [d["pid"] for d in cl.daemons[cl.n_quorum:]]
        else:
            pids = [os.getpid(), cl.procs["sidecar"].pid,
                    *(d["pid"] for d in cl.daemons)]
        pids = " ".join(map(str, pids))
        subprocess.Popen(
            ["sh", "-c", f"kill -STOP {pids}; sleep {seconds}; kill -CONT {pids}"],
            start_new_session=True).wait()

    def _trace(self, t_open: float, seconds: float, callers: list,
               out: dict) -> None:
        """Trace one call cycle of the steady part of the window."""
        try:
            time.sleep(max(0.0, t_open + TRACE_AT * seconds - time.monotonic()))
            done = [c for caller in callers for c in list(caller.calls)
                    if c.t_done and c.acked() == len(c.keynums)]
            lat = sorted(c.t_done - c.t_send for c in
                         ([c for c in done if c.phase == "window"] or done))
            cycle = lat[len(lat) // 2] if lat else TRACE_MIN_S
            span = min(max(TRACE_MIN_S, cycle), TRACE_MAX_S, seconds / 2)
            d = os.path.join(self.run_dir, "trace")
            start = self.cluster.control("trace_start", dir=d)
            time.sleep(max(0.0, start["t_started"] + span - time.monotonic()))
            stop = self.cluster.control("trace_stop",
                                        timeout=STOP_TRACE_TIMEOUT_S)
            out.update(dir=d, start=start, stop=stop, call_cycle_s=cycle)
        except Exception as e:
            out["error"] = repr(e)

    # -- reduction ----------------------------------------------------------

    def end_to_end(self, w: dict) -> dict:
        # a draw counts once: a built-in call that a kind in a file issued
        # beside its own is judged as any other and not counted again
        win = w["counted"] = [c for c in w["window"] if not c.beside]
        ops = sum(c.acked() for c in win)
        span = max(c.t_done for c in win) - min(c.t_send for c in win)
        m = {"committed_ops_per_s": (ops / span, "ops/s")}
        for kind, name in (("read", "read_p95_ms"), ("update", "update_p95_ms")):
            lat = [1000.0 * (c.t_done - c.t_send) if c.acked() == len(c.keynums)
                   else float("inf") for c in win if c.kind == kind]
            if lat and len(win[0].keynums) == 1:
                m[name] = (generator.percentile(lat, 0.95), "ms")
        w.update(ops=ops, span_s=span,
                 attempted=sum(len(c.keynums) for c in win),
                 failed=sum(len(c.keynums) - c.acked() for c in win))
        return m

    def trace_summary(self, w: dict) -> dict | None:
        tr = w["trace"]
        if not tr:
            return None
        from benchmarks.reduce import xplane

        path = xplane.find_xplane(tr["dir"])
        window_s = tr["stop"]["t_stop_called"] - tr["start"]["t_started"]
        summary = xplane.summarize(xplane.load(path), window_s)
        m0 = tr["start"]["before"]["metrics"]
        m1 = tr["stop"]["after"]["metrics"]
        grow = lambda k: m1.get(k, 0) - m0.get(k, 0)  # noqa: E731
        summary.update(
            verify_items=grow("verify.device"),
            sign_rows=2 * grow("sign.device"),
            verify_launches=grow("verify.device_batch.count"),
            sign_launches=grow("sign.device_batch.count"),
            xplane_bytes=os.path.getsize(path),
            stop_trace_s=tr["stop"]["t_stopped"] - tr["stop"]["t_stop_called"],
            call_cycle_s=tr["call_cycle_s"],
        )
        shutil.rmtree(tr["dir"], ignore_errors=True)  # tens of MB a run
        return summary

    def per_layer(self, w: dict, summary: dict | None) -> dict:
        ctx = {"ops": w["ops"], "window_s": w["span_s"], "calls": w["window"],
               "counters": w["counters"], "cpu_s": w["cpu_s"],
               "trace": summary, "device": self.cluster.device}
        out = {}
        for m in self.manifest["per_layer"]:
            if "workloads" in m and self.cell["name"] not in m["workloads"]:
                continue
            spec = load_json("benchmarks", "layer_metrics", m["name"] + ".json")
            needs_trace = m["source"] == "device_trace"
            if needs_trace and (summary is None or self.args.rehearse):
                continue  # no CPU number under a device metric's name
            reader = importlib.import_module(
                "benchmarks.readers." + spec["reader"])
            value = reader.read(ctx, spec.get("args", {}))
            if value is not None:
                out[m["name"]] = (value, m["unit"])
        return out

    # -- judging ------------------------------------------------------------

    def judge_live(self, w: dict) -> dict:
        """What needs the cluster alive: read-back through the client."""
        h = judge.History(w["seed"], self.mix["record"], w["calls"])
        sample = h.sample(int(self.mix["check_sample"]))
        # a wrong read is planted where reads happen: here too
        apis = [plants.plant(w["plant"] if w["plant"] == "wrong_read" else "",
                             api, self.every("wrong_read"))
                for api in self.apis]
        nums = judge.check_reads(h)
        for k, v in judge.readback(h, apis, sample).items():
            nums[k] = nums[k] + v if k in nums else v
        if self.pool is not None:
            nums.update(tenant.judge(w["tenant"], self.pool.key))
        for kind in self.kinds.values():  # their own calls, their own numbers
            nums.update(kind.judge(w["calls"], self.kind_ctx()))
        w.update(history=h, sample=sample)
        return nums

    def judge_disks(self, w: dict) -> dict:
        return judge.inspect_disks(
            w["history"], w["sample"], self.cluster.keys, self.cluster.dbs,
            self.config["guarantees"],
            bool(self.mix["acknowledged_with_collective_signature"]))

    # -- teardown -----------------------------------------------------------

    def tear_down(self) -> None:
        if self._down:
            return
        self._down = True
        for api in self.apis:
            try:
                api.tr.stop()
            except Exception:
                pass
        self.cluster.stop()

    def fail(self, reason: str) -> None:
        text = f"FAILED: {reason}\n{self.cluster.log_tails()}\n"
        sys.stderr.write(text)
        sys.stderr.flush()
        try:
            os.makedirs(self.run_dir, exist_ok=True)
            with open(os.path.join(self.run_dir, "failure.txt"), "w") as f:
                f.write(text)
        except OSError:
            pass


def error_counts(calls: list) -> dict:
    """What the failed operations said, most frequent first."""
    counts: dict[str, int] = {}
    for c in calls:
        for e in c.errors:
            if e is not None:
                key = f"{c.phase}/{c.kind}: {e[:160]}"
                counts[key] = counts.get(key, 0) + 1
    return dict(sorted(counts.items(), key=lambda kv: -kv[1])[:8])


def client_transport() -> dict:
    """What the client's transport says of its peers at the end of a run,
    from this process's own registry: the deadline it gives each peer's
    next RPC (adaptive: 8 x the peer's recent p99 + 0.1 s, between 1 s and
    the fixed 10 s), its retries and its peers flagged slow."""
    try:
        from bftkv_tpu.metrics import registry
    except ImportError:
        return {}
    return {k: v for k, v in sorted(registry.snapshot().items())
            if k.startswith(("transport.peer.deadline_ms", "transport.retries",
                             "transport.peer.slow"))}


def metrics_json(m: dict) -> dict:
    return {k: {"value": v, "unit": u} for k, (v, u) in m.items()}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny, on the CPU; prints no device metric")
    ap.add_argument("--plant", default="",
                    help="control: a fault planted under the timed path "
                         f"(dead_child, {', '.join(STALLS)}, "
                         f"{', '.join(plants.PLANTS)}, "
                         f"{', '.join(plants.SIDECAR_PLANTS)}, or a plant of "
                         "one of the mix's kinds)")
    ap.add_argument("--stall-seconds", type=float, default=4.0,
                    help="how long --plant stall stops every process of the "
                         "run, this one included (stall_storage: the storage "
                         "nodes alone)")
    ap.add_argument("--stalls", type=int, default=1,
                    help="how many times, evenly spaced inside the window "
                         "(one: at half of it)")
    ap.add_argument("--control-runs", type=int, default=0,
                    help="after the window, this many short planted windows "
                         "per plant of the mix's 'controls', on seeds seed+i")
    ap.add_argument("--control-seconds", type=float, default=8.0)
    ap.add_argument("--manifest", default="",
                    help="another manifest than BENCHMARK.json (tests, and "
                         "trying a cell before it is entered)")
    args = ap.parse_args(argv)

    try:
        run = Run(args)
    except (BenchFailure, OSError, ValueError, KeyError, StopIteration) as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 2
    if args.seconds is None:
        args.seconds = float(run.manifest["run_seconds"])

    def on_signal(signum, _frame):
        run.fail(f"signal {signum} before the run finished")
        run.tear_down()
        os._exit(128 + signum)

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)
    try:
        return measure(run)
    except BenchFailure as e:
        run.fail(str(e))
        return 1
    except Exception as e:
        import traceback

        run.fail(f"the harness raised {e!r}\n{traceback.format_exc()}")
        return 1
    finally:
        run.tear_down()


def measure(run: Run) -> int:
    args, cl = run.args, run.cluster
    run.set_up()
    w = run.window(args.seed, args.seconds, args.plant, trace=bool(args.trace),
                   preload=True)
    setup_s = w["t_open"] - T_PROCESS
    t_check = time.monotonic()
    e2e = run.end_to_end(w)
    e2e["setup_s"] = (setup_s, "s")
    mem = cl.control("memstats")["devices"]
    peak = max((d["stats"].get("peak_bytes_in_use", 0) for d in mem), default=0)
    info = harness.http_json(cl.stats + "/info")["sidecar"]
    numbers = run.judge_live(w)
    numbers.update(judge.writeonce(run.api, args.seed))
    numbers["dishonest_verdicts"] = int(
        w["counters"].total("daemons", "crypto.sidecar.dishonest"))
    numbers["compiled_in_window"] = int(
        info["device_plane"]["compiled_since_warmup"])
    numbers["committed_ops"] = w["ops"]
    controls = []
    for i in range(1, args.control_runs + 1):
        for j, name in enumerate(run.mix.get("controls", [])):
            # a seed of its own: no control meets another's records
            cw = run.window(args.seed + 16 * i + j, args.control_seconds, name,
                            trace=False, preload=False)
            run.end_to_end(cw)
            cn = run.judge_live(cw)
            cn["committed_ops"] = cw["ops"]
            controls.append((cw, cn))
    run.timing["check_live"] = time.monotonic() - t_check
    t0 = time.monotonic()
    run.tear_down()  # the stores are closed before the disks are read
    run.timing["teardown"] = time.monotonic() - t0
    t0 = time.monotonic()
    disks = run.judge_disks(w)
    numbers.update(disks)
    correct, compared = judge.verdict(numbers, run.limits)
    for cw, cn in controls:
        cn.update(run.judge_disks(cw))
        ok, ccompared = judge.verdict(cn, run.limits)
        run.note(control=cw["plant"], seed=cw["seed"], correct=ok,
                 ops=cw["ops"], compared=ccompared)
    run.timing["check_disks"] = time.monotonic() - t0
    summary = run.trace_summary(w)
    if args.trace and not args.rehearse and (not summary or summary["busy_s"] <= 0):
        raise BenchFailure("the traced window holds no device operation")
    layers = run.per_layer(w, summary)

    device = {"platform": cl.device["platform"], "kind": cl.device["kind"],
              "count": cl.device["count"], "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": w["attempted"],
              "failed": w["failed"]}
    if args.trace:
        result["metrics"] = metrics_json(layers)
        if summary and not args.rehearse:
            device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
            result["breakdown"] = {
                "device_ops": [[n, s] for n, s in summary["modules"]],
                "idle_gaps": summary["idle_gaps"],
            }
    else:
        result["metrics"] = metrics_json(e2e)
    result["device"] = device
    if args.rehearse:
        result["rehearsal"] = "CPU walk-through: no number here is a measurement"
    result["compared"] = compared

    warmup = cl.sidecar_info["device_plane"].get("warmup", {})
    run.timing.update(cl.phases, setup_s=setup_s,
                      total_s=time.monotonic() - T_PROCESS,
                      warmup=warmup.get("seconds"),
                      compile_cache=warmup.get("compile_cache"))
    half = w["t_open"] + args.seconds / 2
    run.note(timing=run.timing)
    run.note(window={"ops": w["ops"], "span_s": w["span_s"],
                     "calls": len(w["window"]),
                     "first_half_ops": sum(c.acked() for c in w["counted"] if c.t_done <= half),
                     "cpu_s": w["cpu_s"]},
             errors=error_counts(w["calls"]))
    run.note(client_transport=client_transport(),
             slowest_calls_ms=sorted(
                 (round(1000.0 * (c.t_done - c.t_send), 1) for c in w["counted"]),
                 reverse=True)[:8])
    run.note(admission={k: v for k, v in w["counters"].items("sidecar")
                        if k.startswith(("admission.wait.bucket",
                                         "admission.wait.count", "sidecar.shed"))},
             tenant_fallbacks={k: v for k, v in w["counters"].items("daemons")
                               if "remote_fallback" in k or "remote_shed" in k})
    run.note(disks=disks, bad_read_samples=numbers.get("bad_read_samples", []),
             tenant={k: numbers[k] for k in (
                 "tenant_requests", "tenant_shed", "valid_checked",
                 "forged_checked", "forged_accepted_kinds") if k in numbers})
    run.note(launched=info["device_plane"]["launched"],
             calibration=info["device_plane"]["calibration"],
             queue=info["queue"])
    if summary:
        run.note(trace_summary={k: v for k, v in summary.items()})
    if not args.trace:
        run.note(per_layer_untraced=metrics_json(layers))
    else:
        run.note(end_to_end_traced_run=metrics_json(e2e))
    shutil.rmtree(cl.dbs, ignore_errors=True)  # ~0.5 GB a run
    shutil.rmtree(cl.keys, ignore_errors=True)
    line = json.dumps(result)
    with open(os.path.join(run.run_dir, "result.json"), "w") as f:
        f.write(line + "\n")
    sys.stderr.write(judge.compared_line(compared) + "\n")
    sys.stderr.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
