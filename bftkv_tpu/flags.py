"""Declarative registry of every ``BFTKV_*`` environment flag.

The framework grew ~50 tuning and kill-switch flags across ten PRs,
each read ad hoc via ``os.environ.get`` next to the code it steers —
and the documentation drifted to cover a third of them.  This module
is the single source of truth: every flag is declared ONCE here with
its default, value kind and one doc line, and

- every runtime read goes through the seam below (:func:`raw`,
  :func:`get`, :func:`enabled`, :func:`get_int`, :func:`get_float`) —
  reading an undeclared ``BFTKV_*`` name raises immediately, so a new
  flag cannot ship undocumented;
- the README "Environment flags" table is GENERATED from this registry
  (``python -m bftkv_tpu.flags --readme``) and ``tools/bftlint``
  diff-checks it, so the docs cannot drift again;
- ``tools/bftlint``'s ``env-flag`` rule statically rejects any direct
  ``os.environ`` read of a ``BFTKV_*`` literal outside this module.

The seam deliberately does NOT cache: flags keep their original
read-at-call-site (often import-time) timing, so test monkeypatching
and per-process overrides behave exactly as before.

Value kinds: ``switch`` flags use the project-wide convention — any
value whose lowercase form is not ``off``/``0``/``false`` counts as
on (:func:`enabled`); ``str``/``int``/``float`` flags parse their raw
value at the call site's discretion.  A default of ``None`` means
"unset": the call site supplies a context-dependent fallback (the
``doc`` line says what that is).
"""

from __future__ import annotations

import os
from typing import NamedTuple

__all__ = [
    "Flag",
    "FLAGS",
    "declared",
    "enabled",
    "get",
    "get_float",
    "get_int",
    "raw",
    "readme_table",
]


class Flag(NamedTuple):
    name: str
    default: str | None  # None = unset (site-specific fallback)
    kind: str  # "switch" | "str" | "int" | "float"
    doc: str
    section: str


FLAGS: dict[str, Flag] = {}


def _flag(name: str, default: str | None, kind: str, doc: str) -> None:
    assert name.startswith("BFTKV_") and name not in FLAGS, name
    FLAGS[name] = Flag(name, default, kind, doc, _section)


_section = ""


def _begin(section: str) -> None:
    global _section
    _section = section


# ---------------------------------------------------------------------------
# The registry.  Grouped by subsystem; order is the README table order.
# ---------------------------------------------------------------------------

_begin("Write path & protocol")
_flag("BFTKV_PIGGYBACK", "on", "switch",
      "Round-collapsed writes: one WRITE_SIGN fan-out with signature "
      "shares riding the acks; `off` restores classic time/sign/write "
      "rounds (DESIGN.md §12).")
_flag("BFTKV_PRESESSION", "on", "switch",
      "Background session pump + per-client timestamp leases (skips "
      "the TIME round on steady-state writes).")
_flag("BFTKV_SIGN_FANOUT", "staged", "str",
      "`staged` asks a minimal sufficient prefix first and expands on "
      "shortfall; `full` restores the ask-everyone fan-out.")
_flag("BFTKV_WRITE_PIPELINE", "2", "int",
      "write_many: chunk write-rounds in flight behind the caller's "
      "time+sign rounds (1 disables pipelining).")
_flag("BFTKV_WRITE_CHUNK", "256", "int",
      "write_many chunk floor — batches at or below this stay "
      "monolithic so device launches amortize.")

_begin("Recovery & self-healing")
_flag("BFTKV_REPAIR", "on", "switch",
      "Pending-residue repair plane: each replica certifies-or-demotes "
      "its own commit-pending residue (DESIGN.md §13).")
_flag("BFTKV_REPAIR_AFTER", "5", "float",
      "Grace window in seconds before a pending record becomes "
      "repair-eligible.")
_flag("BFTKV_ADAPTIVE_TIMEOUT", "on", "switch",
      "Per-peer EWMA/p99 RPC deadlines in place of the fixed "
      "BFTKV_RPC_TIMEOUT (which stays the ceiling).")
_flag("BFTKV_ADAPTIVE_FLOOR", "1.0", "float",
      "Lower bound in seconds on an adaptive per-peer deadline.")
_flag("BFTKV_HEDGE", "on", "switch",
      "Hedged staged fan-outs: a stalled wave launches the next wave "
      "early after a p99-derived delay.")
_flag("BFTKV_HEDGE_MIN", "0.02", "float",
      "Lower clamp in seconds on the hedge delay.")
_flag("BFTKV_HEDGE_CAP", "0.5", "float",
      "Upper clamp in seconds on the hedge delay.")

_begin("Topology & sharding")
_flag("BFTKV_AUTOPILOT", "on", "switch",
      "Automatic topology decisions (hot-shard split, clique "
      "retirement); `off` disables deciding only — forced executes "
      "stay available (DESIGN.md §15).")
_flag("BFTKV_SHARD", "auto", "str",
      "Device-mesh sharding of sign/verify flushes over local "
      "accelerator devices; `off` pins single-device.")

_begin("Transport")
_flag("BFTKV_RPC_TIMEOUT", None, "float",
      "Fixed per-RPC response deadline ceiling in seconds (unset: "
      "falls back to BFTKV_HTTP_TIMEOUT, then 10).")
_flag("BFTKV_HTTP_TIMEOUT", None, "float",
      "Legacy alias for BFTKV_RPC_TIMEOUT, read only when that is "
      "unset.")
_flag("BFTKV_RPC_RETRIES", "0", "int",
      "Bounded jittered-backoff retries on transient transport errors "
      "(0 disables).")
_flag("BFTKV_RPC_BACKOFF", "0.05", "float",
      "Base backoff in seconds between transport retries.")
_flag("BFTKV_PEER_CB", "", "switch",
      "Per-peer circuit breaker in multicast (`1` enables; default "
      "off).")
_flag("BFTKV_PEER_CB_THRESHOLD", "3", "int",
      "Consecutive failures before a peer's breaker opens.")
_flag("BFTKV_PEER_CB_OPEN_SECS", "5", "float",
      "Seconds an open breaker skips a peer before the half-open "
      "probe.")
_flag("BFTKV_HTTP_POOL", "4", "int",
      "Idle keep-alive connections kept per (host, port).")
_flag("BFTKV_FANOUT_WORKERS", "256", "int",
      "Bound on the shared multicast fan-out worker pool.")
_flag("BFTKV_INLINE_FANOUT", "auto", "str",
      "`auto` runs loopback multicast inline when calibration says "
      "all-host; `off`/`on` force the threaded/inline path.")

_begin("Multi-region WAN")
_flag("BFTKV_REGION", None, "str",
      "This process's own region label, overriding the installed "
      "region map (a gateway box pinned to its serving region; "
      "unset: the identity's label from the universe's regions "
      "file).")
_flag("BFTKV_REGION_RANK", "on", "switch",
      "Locality-aware quorum staging: staged waves order candidates "
      "same-region-first (then by RTT matrix distance) so the minimal "
      "sufficient prefix is the near one and cross-region members are "
      "hedges, not the first ask.  Never changes which sets satisfy "
      "is_threshold/is_sufficient (DESIGN.md §21).")
_flag("BFTKV_REGION_LEASE_S", "0", "float",
      "Gateway freshness lease in seconds: while the last sync-"
      "invalidation round completed this recently, TTL-expired cache "
      "entries may still be served same-region (staleness bounded by "
      "lease + poll interval; 0 disables — DESIGN.md §21).")
_flag("BFTKV_WAN_RTT_MATRIX", None, "str",
      "Named geo-topology (wan2, wan3) or raw ms spec (e.g. "
      "20/80/150) compiled onto the link plane as quiet background "
      "delay rules — the deterministic WAN environment for benches "
      "and chaos soaks.")
_flag("BFTKV_WAN_JITTER", "0", "float",
      "Fractional jitter on WAN link delays: each one-way delay "
      "stretches uniformly (seeded per-rule draw) up to "
      "delay x (1 + jitter).")

_begin("Crypto & verification")
_flag("BFTKV_VERIFY_CACHE", "1", "switch",
      "Process-global verified-signature memo (`0` disables).")
_flag("BFTKV_VERIFY_CACHE_MAX", "65536", "int",
      "Bound on the verified-signature memo (entries).")
_flag("BFTKV_NATIVE_MODEXP", "auto", "str",
      "GIL-free Montgomery CRT modexp via native/montmodexp.c; `off` "
      "falls back to pow().")
_flag("BFTKV_NATIVE_CODEC", "auto", "str",
      "Native packet codec built on import; `off` keeps the pure-"
      "Python codec.")
_flag("BFTKV_OS_RNG", "", "switch",
      "`1` restores os.urandom for every secret draw (default: "
      "per-thread SHA-256 hash-DRBG reseeded from os.urandom).")
_flag("BFTKV_HOST_SIGN_THRESHOLD", None, "int",
      "Batch size below which signs stay on host (unset: measured "
      "crossover from dispatcher calibration).")
_flag("BFTKV_HOST_VERIFY_THRESHOLD", None, "int",
      "Batch size below which verifies stay on host (unset: measured "
      "crossover from dispatcher calibration).")
_flag("BFTKV_EC_BACKEND", "auto", "str",
      "EC scalar-mul backend: `limb` (`ops/ec.py`), `rns` "
      "(`ops/ec_rns.py`), or `auto`: `rns` on a TPU backend, `limb` "
      "elsewhere.")
_flag("BFTKV_EC_SIGN_THRESHOLD", None, "int",
      "EC sign host/device crossover batch size (unset: built-in "
      "crossover constant).")
_flag("BFTKV_EC_VERIFY_THRESHOLD", None, "int",
      "EC verify host/device crossover batch size (unset: built-in "
      "crossover constant).")

_begin("Shared crypto sidecar")
_flag("BFTKV_SIDECAR_SIGN", "on", "switch",
      "Clients remote their RSA signing to the shared sidecar when the "
      "channel can carry keys (unix socket or HMAC secret); `off` keeps "
      "signing in-process (verification still remotes).")
_flag("BFTKV_SIDECAR_SPOT_RATE", "0.05", "float",
      "Fraction of remote verify batches whose verdicts are re-checked "
      "locally on one sampled item; a mismatch opens the sidecar "
      "breaker and raises the sidecar_dishonest anomaly (DESIGN.md "
      "§17.3).")
_flag("BFTKV_SIDECAR_BREAKER", "30", "float",
      "Seconds the sidecar breaker skips the service after a transport "
      "failure or a dishonest result before retrying.")
_flag("BFTKV_SIDECAR_MAX_INFLIGHT", "4", "int",
      "Sidecar admission: crypto batches served concurrently; more "
      "wait, then shed (sidecar.shed).")
_flag("BFTKV_SIDECAR_MAX_QUEUE", "64", "int",
      "Sidecar admission: batches allowed to WAIT for a service slot "
      "before instant shedding.")
_flag("BFTKV_SIDECAR_MAX_WAIT", "0.5", "float",
      "Sidecar admission: longest a batch may wait for a service slot "
      "before it is shed.")
_flag("BFTKV_SIDECAR_MAX_KEYS", "64", "int",
      "Sign-key handles one sidecar connection may register (bounds "
      "hostile registration floods).")
_flag("BFTKV_IDENTITY_BITS", "2048", "str",
      "The deployment's RSA identity widths, comma-separated (`3072`, "
      "`2048,3072`): the sidecar builds the device programs of these "
      "widths before it listens; a sign or modexp at any other width is "
      "served from the host tier, never compiled inside a request. "
      "Chooses no tier: calibration does.")
_flag("BFTKV_CA_BITS", None, "str",
      "The key widths of the threshold CAs this deployment deals to its "
      "quorum, comma-separated (`2048`): the sidecar then also builds the "
      "pow programs a first-level threshold fragment rides (a whole "
      "modulus of that width under an exponent of twice that width + 64 "
      "bits, buckets 64 and 128 rows; 64 alone on the wide chain, widths "
      "past 2,130 bits up to 4096) before it listens. Unset: no such "
      "program is built and a fragment row is served from the host tier, "
      "counted (`sidecar.unwarmed_width`).")

_begin("Device kernels & dispatch")
_flag("BFTKV_DISPATCH_CALIBRATE", "1", "switch",
      "Install-time host-vs-device crossover calibration (`0` "
      "disables; CPU backends then still pin always-host).")
_flag("BFTKV_DISPATCH_PIPELINE", None, "int",
      "Flushes in flight at once in the batching dispatcher (unset: "
      "backend-dependent default).")
_flag("BFTKV_DISPATCH_ASYNC", "on", "switch",
      "Async mega-batch dispatch: flush workers hand non-blocking "
      "device launches to a completion-drain thread, so flush N+1's "
      "host assembly overlaps flush N's device execution; `off` "
      "restores fully synchronous flushes (the pre-r11 behavior).")
_flag("BFTKV_DISPATCH_CROSSOVER", None, "int",
      "Operator override for the host/device verify crossover batch "
      "size (0 or negative pins always-host; unset: measured by "
      "dispatch calibration and re-measured online from launch RTTs).")
_flag("BFTKV_DISPATCH_RECAL_S", "60", "float",
      "Sidecar online-recalibration period in seconds: the boot-time "
      "crossover pin is re-measured from observed launch RTTs, so an "
      "attached accelerator engages without a restart (0 disables).")
_flag("BFTKV_DISPATCH_DEVBUF", "on", "switch",
      "Persistent per-limb-width staging buffer rings for device "
      "launches: flushes write batches into pre-allocated slot arrays "
      "(pad rows broadcast, never re-converted); `off` re-allocates "
      "per launch.")
_flag("BFTKV_DISPATCH_DEVBUF_RING", "4", "int",
      "Slots per width-class buffer ring; with every slot in flight "
      "the next flush allocates fresh arrays (devbuf.overflow) "
      "instead of blocking behind the device.")
_flag("BFTKV_TPU_MIN_MODEXP_BATCH", "4", "int",
      "Smallest batch worth a device modexp launch.")
_flag("BFTKV_RNS_POW_BACKEND", "auto", "str",
      "`pallas` forces the fused Pallas RNS pow chain, `xla` the "
      "lowered one; `auto` picks by platform and device count "
      "(today: `xla`, but the longer exponent class on one TPU chip, "
      "which rides the fused chain).")
_flag("BFTKV_RNS_VERIFY_BACKEND", "auto", "str",
      "Same switch for the RNS verify kernel.")
_flag("BFTKV_PALLAS_TILE_POW", "256", "int",
      "Pallas pow kernel batch tile (power of two ≥ 8).")
_flag("BFTKV_PALLAS_TILE_VERIFY", "128", "int",
      "Pallas verify kernel batch tile (power of two ≥ 8).")

_begin("Storage")
_flag("BFTKV_PLAIN_FSYNC", None, "switch",
      "Per-write fsync pair (file + directory) in PlainStorage; "
      "unset: library off / daemon on (durability is a deployment "
      "policy).")
_flag("BFTKV_PLAIN_CACHE", "1024", "int",
      "PlainStorage write-through record cache (entries; 0 disables).")
_flag("BFTKV_STORAGE", None, "str",
      "Default `--storage` engine for the daemon/cluster CLIs "
      "(plain|log|native|mem; unset: plain for the daemon, log for "
      "run_cluster).")
_flag("BFTKV_LOG_SEGMENT_MB", "64", "int",
      "LogStorage segment size: the active segment seals past this "
      "and becomes a shippable snapshot unit (DESIGN.md §19).")
_flag("BFTKV_LOG_GROUP_COMMIT_MS", "2", "float",
      "LogStorage group-commit linger: how long the fsync leader "
      "waits for concurrent writers to join its barrier (0 = fsync "
      "immediately, still shared by the losers of the leader race).")
_flag("BFTKV_LOG_COMPACT_TRIGGER", "0.5", "float",
      "LogStorage background compaction trigger: sealed dead-byte "
      "ratio past which a compaction pass starts (0 disables).")
_flag("BFTKV_LOG_COMPACT_MBPS", None, "float",
      "Compaction IO governor: sustained copy-rate cap in MB/s "
      "(token-bucket sleep between record copies; unset/0 = "
      "ungoverned).  Throttle time surfaces as compact_io saturation "
      "in the capacity plane.")

_begin("Observability & tooling")
_flag("BFTKV_TRACE", "on", "switch",
      "Trace-id/span plane; `off` disables tracing entirely.")
_flag("BFTKV_SLOW_TRACE_SECONDS", "1.0", "float",
      "Slow-trace threshold: requests above it land in the slow ring "
      "and the one-JSON-line slow log.")
_flag("BFTKV_LOCKWATCH", "", "switch",
      "Opt-in runtime lock sanitizer: records the lock acquisition-"
      "order graph, reports lock-order cycles and blocking calls "
      "under storage/metrics/route locks (DESIGN.md §16).")
_flag("BFTKV_PROFILE", "", "switch",
      "Opt-in continuous wall-clock sampling profiler (collapsed "
      "flamegraph stacks served on /profile; DESIGN.md §18).  Off = "
      "no sampler thread, zero overhead.")
_flag("BFTKV_PROFILE_HZ", "67", "int",
      "Sampling rate of the continuous profiler (prime default so the "
      "comb never phase-locks to periodic work).")
_flag("BFTKV_SLO_WRITE_P99", None, "float",
      "Write-latency SLO in seconds: a shard whose per-scrape write "
      "p99 exceeds it for BFTKV_SLO_BURN_SCRAPES consecutive scrapes "
      "raises the slo_burn anomaly (unset: disabled).")
_flag("BFTKV_SLO_BURN_SCRAPES", "3", "int",
      "Consecutive breaching scrapes before slo_burn fires — the "
      "hysteresis that keeps one slow scrape from paging anyone.")
_flag("BFTKV_FLIGHT_RECORDER", "", "switch",
      "Arm the flight recorder in the chaos nemesis: every fault "
      "window must yield exactly one black-box bundle naming the "
      "detected anomaly, enforced via the nemesis exit code.")
_flag("BFTKV_RECORDER_DIR", None, "str",
      "Flight-recorder bundle directory (unset: <tmpdir>/"
      "bftkv-blackbox).")
_flag("BFTKV_RECORDER_MIN_INTERVAL", "5", "float",
      "Seconds within which anomaly events coalesce into (amend) the "
      "previous bundle instead of minting a new one — the flapping-"
      "anomaly disk bound.")
_flag("BFTKV_RECORDER_MAX_MB", "64", "int",
      "Total on-disk cap across flight-recorder bundles; oldest "
      "bundles are evicted first.")
_flag("BFTKV_SAT_THRESHOLD", "0.8", "float",
      "Capacity plane: per-resource saturation at or above this for "
      "BFTKV_SAT_SCRAPES consecutive traffic-bearing scrapes raises "
      "the resource_saturated anomaly (0 disables).")
_flag("BFTKV_SAT_SCRAPES", "3", "int",
      "Consecutive saturated scrapes before resource_saturated fires "
      "— same hysteresis contract as slo_burn (one episode, one "
      "anomaly; a clean scrape re-arms).")
_flag("BFTKV_SAT_WAIT_REF", "0.25", "float",
      "Capacity plane: queue-wait p99 (seconds) that maps to "
      "saturation 1.0 for wait-derived resources (admission, "
      "dispatch; the log commit path uses max(4x linger, this)).")
_flag("BFTKV_GIL_SAMPLER", "1", "switch",
      "GIL-pressure estimate (runnable-thread gauge) riding the "
      "profiler tick; costs nothing while the profiler is disarmed.")

_begin("Workload engine")
_flag("BFTKV_WORKLOAD", None, "str",
      "Workload spec `preset[,k=v,...]` (bftkv_tpu/workload/spec.py) "
      "for spec-shaped traffic: the chaos nemesis `--workload` default "
      "(unset: coverage traffic only).")
_flag("BFTKV_WORKLOAD_SEED", None, "int",
      "Seed override for workload-driven bench sections; one seed "
      "replays one op stream bit-for-bit (unset: section default).")
_flag("BFTKV_WORKLOAD_RATE", None, "float",
      "Offered-load override in ops/s for bench cluster_workload and "
      "cluster_shards (unset: section defaults).")
_flag("BFTKV_WORKLOAD_DURATION", None, "float",
      "Per-preset schedule duration override in seconds for bench "
      "cluster_workload (unset: section default).")
_flag("BFTKV_WORKLOAD_PROCS", None, "int",
      "Worker-process count for the multi-process driver pair in bench "
      "cluster_workload (unset: 2).")

# ---------------------------------------------------------------------------
# The read seam.
# ---------------------------------------------------------------------------


def _check(name: str) -> Flag:
    f = FLAGS.get(name)
    if f is None:
        raise KeyError(
            f"undeclared BFTKV flag {name!r}: declare it in "
            "bftkv_tpu/flags.py (default + doc line) before reading it"
        )
    return f


def declared() -> dict[str, Flag]:
    """Name → :class:`Flag` for every declared flag (insertion order)."""
    return dict(FLAGS)


def raw(name: str, default: str | None = None) -> str | None:
    """The raw environment value, or ``default`` when unset.

    This is the compatibility seam: it keeps each call site's exact
    historical semantics (site-specific defaults, ``== "1"`` vs
    ``!= "0"`` comparisons) while enforcing that the name is declared.
    New call sites should prefer the typed helpers below."""
    _check(name)
    v = os.environ.get(name)
    return default if v is None else v


def get(name: str) -> str | None:
    """Environment value, falling back to the registry default."""
    f = _check(name)
    v = os.environ.get(name)
    return f.default if v is None else v


def enabled(name: str, default: str | None = None) -> bool:
    """Project-wide switch semantics, exactly as every historical
    switch site implemented them: a SET value is on unless it
    lowercases to ``off``/``0``/``false`` (so an explicitly-set empty
    string counts as on, matching the established
    ``.lower() not in ("off", "0", "false")`` convention).  An UNSET
    flag falls back to the registry default, where empty/``None``
    means off (a default-off switch like ``BFTKV_LOCKWATCH``)."""
    f = _check(name)
    v = os.environ.get(name)
    if v is None:
        v = default if default is not None else (f.default or "")
        if v == "":
            return False
    return v.lower() not in ("off", "0", "false")


def get_int(name: str, default: int | None = None) -> int | None:
    f = _check(name)
    v = os.environ.get(name)
    if v is None or v == "":
        if default is not None:
            return default
        return int(f.default) if f.default is not None else None
    return int(v)


def get_float(name: str, default: float | None = None) -> float | None:
    f = _check(name)
    v = os.environ.get(name)
    if v is None or v == "":
        if default is not None:
            return default
        return float(f.default) if f.default is not None else None
    return float(v)


# ---------------------------------------------------------------------------
# README table generation (diff-checked by tools/bftlint).
# ---------------------------------------------------------------------------

README_BEGIN = (
    "<!-- flags-table:begin (generated by "
    "python -m bftkv_tpu.flags --readme; do not edit) -->"
)
README_END = "<!-- flags-table:end -->"


def readme_table() -> str:
    """The generated README section between the flags-table markers."""
    lines = [README_BEGIN, ""]
    section = None
    for f in FLAGS.values():
        if f.section != section:
            section = f.section
            lines.append(f"**{section}**")
            lines.append("")
            lines.append("| Flag | Default | Meaning |")
            lines.append("| --- | --- | --- |")
        default = "_(unset)_" if f.default is None else f"`{f.default}`"
        if f.default == "":
            default = "_(off)_"
        doc = " ".join(f.doc.split())
        lines.append(f"| `{f.name}` | {default} | {doc} |")
    lines.append("")
    lines.append(README_END)
    # Blank line between a table's last row and the next section header.
    out: list[str] = []
    for ln in lines:
        if ln.startswith("**") and out and out[-1].startswith("|"):
            out.append("")
        out.append(ln)
    return "\n".join(out)


def main(argv: list[str] | None = None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        prog="python -m bftkv_tpu.flags",
        description="BFTKV_* environment-flag registry",
    )
    p.add_argument(
        "--readme", action="store_true",
        help="print the generated README flags section",
    )
    args = p.parse_args(argv)
    if args.readme:
        print(readme_table())
    else:
        for f in FLAGS.values():
            d = "(unset)" if f.default is None else repr(f.default)
            print(f"{f.name:32s} {f.kind:7s} default={d}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
