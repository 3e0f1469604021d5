"""From a profiler trace (``*.xplane.pb``) to device busy time, idle
gaps and time per XLA module.

The arithmetic works on a plain structure, so that it can be checked on
a small recorded trace (``tests/test_reduce.py``):

    planes = [{"name": str, "lines": [{"name": str,
               "events": [(name, start_ns, duration_ns), ...]}]}]

``load`` makes that structure from a file with ``jax.profiler.
ProfileData`` (JAX only; imported when called, by a process pinned to
the CPU backend — reading a trace needs no chip).

On a TPU the device planes are ``/device:TPU:<i>``; their line ``XLA
Ops`` carries one event per executed operation and ``XLA Modules`` one
per executed program.  Busy time is the union of the operation
intervals (the modules' where a plane has no operation line); a
device's idle share is 1 - busy / traced window.
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not hits:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return hits[-1]


def load(path: str, prefixes=(DEVICE_PREFIX, "/host:")) -> list[dict]:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(tuple(prefixes)):
            continue
        lines = []
        for line in plane.lines:
            events = [
                (ev.name, int(ev.start_ns), int(ev.duration_ns))
                for ev in line.events
            ]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Sorted, disjoint cover of ``(start, end)`` intervals."""
    out: list[tuple[int, int]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _line(plane: dict, name: str) -> list[tuple]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def device_planes(planes: list[dict]) -> list[dict]:
    return [p for p in planes if p["name"].startswith(DEVICE_PREFIX)]


def busy_intervals(plane: dict) -> list[tuple[int, int]]:
    events = _line(plane, OPS_LINE) or _line(plane, MODULES_LINE)
    return union([(s, s + d) for _n, s, d in events])


def summarize(planes: list[dict], window_s: float, top: int = 10) -> dict:
    """Busy seconds (mean over the device planes that ran anything —
    the chips used), time per module summed over the devices, and the
    longest idle gaps of the busiest device with what the host ran in
    each."""
    devs = device_planes(planes)
    per_dev = []
    for p in devs:
        cover = busy_intervals(p)
        per_dev.append((sum(e - s for s, e in cover) / 1e9, cover, p))
    used = [d for d in per_dev if d[0] > 0]
    modules: dict[str, float] = {}
    ops = 0
    for _busy, _cover, p in used:
        for name, _s, d in _line(p, MODULES_LINE):
            modules[name] = modules.get(name, 0.0) + d / 1e9
        ops += len(_line(p, OPS_LINE))
    out = {
        "device_planes": len(devs),
        "devices_used": len(used),
        "busy_s": sum(d[0] for d in used) / len(used) if used else 0.0,
        "window_s": window_s,
        "module_s": sum(modules.values()),
        "modules": sorted(modules.items(), key=lambda kv: -kv[1])[:top],
        "module_events": sum(
            len(_line(p, MODULES_LINE)) for _b, _c, p in used
        ),
        "op_events": ops,
        "idle_gaps": [],
    }
    if used:
        _busy, cover, _p = max(used, key=lambda d: d[0])
        gaps = sorted(
            ((cover[i + 1][0] - cover[i][1], cover[i][1], cover[i + 1][0])
             for i in range(len(cover) - 1)),
            reverse=True,
        )[:top]
        host = host_events(planes)
        out["idle_gaps"] = [
            [host_label(host, s, e), dur / 1e9] for dur, s, e in gaps
        ]
        out["first_event_s"] = cover[0][0] / 1e9
        out["last_event_s"] = cover[-1][1] / 1e9
    return out


def host_events(planes: list[dict]) -> list[tuple[str, int, int]]:
    """Host spans that can name an idle gap: every event of a host
    plane that lasts 50 us or more (shorter ones are bookkeeping)."""
    out = []
    for p in planes:
        if not p["name"].startswith("/host:"):
            continue
        for line in p["lines"]:
            out += [(n, s, s + d) for n, s, d in line["events"] if d >= 50_000]
    return out


def host_label(host: list[tuple[str, int, int]], start: int, end: int) -> str:
    """The host span that covers most of the gap, as ``host:<name>``;
    ``host:unattributed`` where none covers a tenth of it."""
    best, best_cov = "", 0
    for name, s, e in host:
        cov = min(e, end) - max(s, start)
        if cov > best_cov:
            best, best_cov = name, cov
    if best_cov * 10 < end - start:
        return "host:unattributed"
    return "host:" + best.split("(")[0].strip()[:48]
