"""The one process that touches the chip: the program's sidecar,
unchanged, with a control thread beside it.

    python -m benchmarks.sidecar_main --control DIR [--rehearse] -- \
        --listen unix:/path/sock --max-batch 4096 --stats 127.0.0.1:PORT

Everything after ``--`` goes to ``bftkv_tpu.cmd.verify_sidecar.main``
as it is.  The harness drops ``DIR/cmd-<n>.json`` (``{"op": ...}``);
the control thread answers with ``DIR/ack-<n>.json``.  Operations:

- ``snapshot``     the sidecar's whole metrics registry and the clock
- ``trace_start``  ``jax.profiler.start_trace(dir)``, then snapshot
- ``trace_stop``   snapshot, then ``stop_trace()``
- ``memstats``     ``memory_stats()`` of every device
- ``plant`` / ``unplant``  a control's fault where the verdict is
  produced (``plants.sidecar_plant``); never in a driver's run

Only the process that holds the chip can trace it or read its memory,
which is why this file exists (a trace hook inside the sidecar is on
PERF.md's list for the ``tracing`` issue).  ``time.monotonic()`` is the
system's CLOCK_MONOTONIC: the harness's clock too.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import traceback

POLL_S = 0.02


def _snapshot() -> dict:
    from bftkv_tpu.metrics import registry

    return {"t": time.monotonic(), "metrics": registry.snapshot()}


def _handle(cmd: dict) -> dict:
    import jax

    op = cmd.get("op")
    if op == "snapshot":
        return _snapshot()
    if op == "trace_start":
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(cmd["dir"], profiler_options=opts)
        # the counters at the instant tracing is on (starting takes a while)
        before = _snapshot()
        return {"before": before, "t_started": before["t"]}
    if op == "trace_stop":
        # the counters before stopping: writing the trace out takes tens
        # of seconds, during which the sidecar keeps serving
        after = _snapshot()
        jax.profiler.stop_trace()
        return {"t_stop_called": after["t"], "t_stopped": time.monotonic(),
                "after": after}
    if op == "memstats":
        return {"devices": [
            {"id": d.id, "stats": d.memory_stats() or {}} for d in jax.devices()
        ]}
    if op == "plant":
        from benchmarks import plants

        plants.sidecar_plant(cmd["name"], bool(cmd.get("host_tier")))
        return {"planted": cmd["name"]}
    if op == "unplant":
        from benchmarks import plants

        plants.sidecar_unplant()
        return {}
    raise ValueError(f"unknown control operation {op!r}")


def control_loop(ctl: str, stop: threading.Event) -> None:
    n = 0
    while not stop.is_set():
        path = os.path.join(ctl, f"cmd-{n}.json")
        if not os.path.exists(path):
            time.sleep(POLL_S)
            continue
        try:
            with open(path) as f:
                reply = {"ok": True, **_handle(json.load(f))}
        except Exception as e:  # the sidecar must keep serving
            reply = {"ok": False, "error": repr(e),
                     "traceback": traceback.format_exc()}
        tmp = os.path.join(ctl, f".ack-{n}.tmp")
        with open(tmp, "w") as f:
            json.dump(reply, f)
        os.replace(tmp, os.path.join(ctl, f"ack-{n}.json"))
        n += 1


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    rest = argv[argv.index("--") + 1:] if "--" in argv else []
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--control", required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="accept a CPU backend (never a measurement)")
    args = ap.parse_args(argv[: argv.index("--")] if "--" in argv else argv)

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    with open(os.path.join(args.control, "device.json"), "w") as f:
        json.dump(device, f)
    if device["platform"] != "tpu" and not args.rehearse:
        print(f"benchmarks.sidecar_main: JAX found no TPU ({device})",
              file=sys.stderr, flush=True)
        return 3
    stop = threading.Event()
    threading.Thread(
        target=control_loop, args=(args.control, stop), daemon=True
    ).start()
    from bftkv_tpu.cmd import verify_sidecar

    try:
        return verify_sidecar.main(rest)
    finally:
        stop.set()


if __name__ == "__main__":
    sys.exit(main())
