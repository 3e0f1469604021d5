"""Process-wide metrics registry: counters, gauges, latency histograms.

The reference has no metrics beyond ``log.Printf`` (SURVEY.md §5); the
TPU framework needs them to steer batching — sig-verifies/sec, device
batch occupancy, quorum latencies are the signals the dispatcher and
the benchmark harness read.  Deliberately dependency-free and cheap:
one lock, plain dicts, snapshot on demand.

Every instrument takes optional ``labels`` (a small dict of low-
cardinality dimensions — command names, transport kind, shard indices,
never variables or peer addresses; cardinality rules in
docs/DESIGN.md §7).  Two export surfaces:

- :meth:`Metrics.snapshot` — the historical flat JSON dict; labeled
  series flatten to ``name{k=v,...}`` keys, unlabeled keys are
  unchanged so existing consumers keep working.  Each ``observe()``
  series additionally exports its fixed-bucket counts as
  ``name.bucket{le=...}`` keys;
- :meth:`Metrics.prometheus` — Prometheus text exposition (0.0.4):
  counters as ``bftkv_<name>_total``, gauges as ``bftkv_<name>``,
  ``observe()`` series as **histograms** (``_bucket{le=...}`` +
  ``_count``/``_sum``).

Histograms, not summaries: every daemon uses the same fixed bucket
bounds (:data:`BUCKETS`), so a fleet collector can sum bucket counts
across processes and compute fleet-wide quantile estimates — per-daemon
summary quantiles cannot be merged at all (the p99 of a set of p99s is
meaningless).  The in-process percentile()/snapshot p50/p99 keys stay
sample-exact for single-process consumers (bench.py).
"""

from __future__ import annotations

import re
import threading
import time
from collections import defaultdict
from bftkv_tpu.devtools.lockwatch import named_lock

__all__ = [
    "BUCKETS", "LABEL_KEYS", "Metrics", "histogram_quantile", "registry",
]

#: The CLOSED enum of label keys any instrument may carry.  Labels are
#: low-cardinality dimensions only (DESIGN.md §7); the key vocabulary
#: itself is fixed here so ``tools/bftlint``'s ``label-enum`` rule can
#: statically reject a call site inventing a new dimension (the
#: runtime cardinality tests bound the VALUES, this bounds the keys).
#: Adding a key is a deliberate schema change: extend this tuple and
#: document the dimension in DESIGN.md §7.
LABEL_KEYS = (
    "transport",  # backend: http / loop / visual / ws
    "side",       # client / server
    "cmd",        # protocol command name (closed command enum)
    "shard",      # shard index (int, < shard count)
    "op",         # gateway op (read/write) / sidecar op (verify/sign/modexp)
    "point",      # failpoint name (closed hook-site enum)
    "action",     # failpoint action kind
    "endpoint",   # daemon API endpoint (closed set + "other")
    "peer",       # normalized link name (bounded by fleet size)
    "event",      # visual/ws event type
    "kind",       # autopilot plan kind: split / retire
    "resource",   # capacity-plane resource (closed capacity.RESOURCES enum)
    "width",      # device batch limb-width group (bounded: few limb sizes + "ec")
    "bits",       # RSA identity width class: 1024/2048/3072/4096/other
    "le",         # histogram bucket bound (fixed BUCKETS ladder)
)

#: Fixed histogram bucket upper bounds, IDENTICAL in every process so
#: bucket counts sum across daemons.  The low end covers RPC/crypto
#: latencies (seconds), the high end covers the other observe() users
#: (batch sizes, items/s) coarsely — a count landing past 60 falls into
#: the wide tail buckets and the +Inf overflow.  Changing these bounds
#: is a fleet-wide flag day: collector merges require equal ladders.
BUCKETS: tuple = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 1000.0, 100000.0,
)


def histogram_quantile(q: float, buckets: list[int] | tuple) -> float | None:
    """Quantile estimate from per-bucket counts (len(BUCKETS)+1, the
    last being +Inf overflow): the upper bound of the bucket holding
    the q-th sample.  None on an empty histogram.  This is the merge
    side of the fixed-ladder design — sum per-daemon bucket vectors,
    then call this."""
    total = sum(buckets)
    if total <= 0:
        return None
    rank = q * total
    acc = 0
    for i, c in enumerate(buckets):
        acc += c
        if acc > rank or acc >= total:
            return BUCKETS[i] if i < len(BUCKETS) else float("inf")
    return float("inf")  # pragma: no cover


def _bucket_index(value: float) -> int:
    lo, hi = 0, len(BUCKETS)
    while lo < hi:
        mid = (lo + hi) // 2
        if value <= BUCKETS[mid]:
            hi = mid
        else:
            lo = mid + 1
    return lo  # == len(BUCKETS) -> +Inf overflow


#: Label sets are stored as sorted (key, value) tuples; () = unlabeled.
_NO_LABELS: tuple = ()


def _key(name: str, labels: dict | None) -> tuple[str, tuple]:
    if not labels:
        return (name, _NO_LABELS)
    return (name, tuple(sorted(labels.items())))


def _flat(name: str, labels: tuple) -> str:
    """Flat JSON-snapshot key: ``name`` or ``name{k=v,...}``."""
    if not labels:
        return name
    return name + "{" + ",".join(f"{k}={v}" for k, v in labels) + "}"


def _prom_name(name: str) -> str:
    return "bftkv_" + re.sub(r"[^a-zA-Z0-9_:]", "_", name)


def _prom_labels(labels: tuple, extra: tuple = ()) -> str:
    items = tuple(labels) + tuple(extra)
    if not items:
        return ""

    def esc(v) -> str:
        return (
            str(v)
            .replace("\\", "\\\\")
            .replace('"', '\\"')
            .replace("\n", "\\n")
        )

    return "{" + ",".join(f'{k}="{esc(v)}"' for k, v in items) + "}"


def _prom_value(v) -> str:
    return repr(v) if isinstance(v, float) else str(v)


class Metrics:
    def __init__(self):
        self._lock = named_lock("metrics")
        # Counters are sharded PER THREAD: ``incr`` is the hottest call
        # in the process (several per RPC from every handler, fan-out
        # worker and writer thread), and a single shared lock made each
        # contended acquire a blocking GIL round trip — profiled at
        # ~14 ms per blocked incr on the cluster_4 bench.  Each thread
        # mutates only its own dict (GIL-atomic for str/tuple keys);
        # readers sum the shards.  Totals are exact at read time.
        # Shards of finished threads stay in the list (their counts
        # must keep counting); growth is bounded by the process's peak
        # thread count, and the fan-out pool reuses threads.
        self._tl = threading.local()
        self._counter_shards: list[dict] = []
        self._gauges: dict[tuple, float] = {}
        self._counts: dict[tuple, int] = defaultdict(int)
        self._sums: dict[tuple, float] = defaultdict(float)
        self._samples: dict[tuple, list[float]] = defaultdict(list)
        # Fixed-bucket counts per observe() series (len(BUCKETS)+1; the
        # last slot is the +Inf overflow).  Unlike the sample ring these
        # cover the WHOLE run and merge across processes by summation.
        self._buckets: dict[tuple, list[int]] = defaultdict(
            lambda: [0] * (len(BUCKETS) + 1)
        )
        # Ring-buffer write cursors: the histogram must keep admitting
        # values forever.  The old append-until-full behavior froze each
        # series at its first 65536 samples, so a daemon's p50/p99
        # reported startup behavior for the rest of its life.
        self._sample_pos: dict[tuple, int] = defaultdict(int)
        self._max_samples = 65536

    def _local_counters(self) -> dict:
        d = getattr(self._tl, "counters", None)
        if d is None:
            d = self._tl.counters = defaultdict(int)
            with self._lock:
                self._counter_shards.append(d)
        return d

    def _counter_totals(self) -> dict:
        totals: dict[tuple, int] = defaultdict(int)
        with self._lock:
            shards = list(self._counter_shards)
        for d in shards:
            # dict.copy() is a single C-level operation under the GIL,
            # so a concurrently-incrementing owner thread cannot tear it.
            for k, v in d.copy().items():
                totals[k] += v
        return dict(totals)

    def incr(
        self, name: str, n: int | float = 1, labels: dict | None = None
    ) -> None:
        self._local_counters()[_key(name, labels)] += n

    def gauge(
        self, name: str, value: float, labels: dict | None = None
    ) -> None:
        """Last-write-wins instantaneous value (queue depth, occupancy,
        throughput of the latest flush)."""
        with self._lock:
            self._gauges[_key(name, labels)] = value

    def observe(
        self, name: str, value: float, labels: dict | None = None
    ) -> None:
        """Record one sample (latency seconds, batch size, ...).

        Samples land in a per-series ring buffer: totals (`.count` /
        `.sum`) cover the whole run while percentiles reflect the most
        recent ``_max_samples`` window."""
        k = _key(name, labels)
        with self._lock:
            self._counts[k] += 1
            self._sums[k] += value
            self._buckets[k][_bucket_index(value)] += 1
            s = self._samples[k]
            if len(s) < self._max_samples:
                s.append(value)
            else:
                s[self._sample_pos[k]] = value
                self._sample_pos[k] = (
                    self._sample_pos[k] + 1
                ) % self._max_samples

    class _Timer:
        def __init__(self, m: "Metrics", name: str):
            self.m, self.name = m, name

        def __enter__(self):
            self.t0 = time.perf_counter()
            return self

        def __exit__(self, *exc):
            self.m.observe(self.name, time.perf_counter() - self.t0)
            return False

    def timer(self, name: str) -> "Metrics._Timer":
        return Metrics._Timer(self, name)

    def percentile(
        self, name: str, q: float, labels: dict | None = None
    ) -> float | None:
        # Copy under the lock, sort outside: sorting up to 65536
        # samples while holding the lock stalled every concurrent
        # observe() for the duration of the sort.
        with self._lock:
            s = list(self._samples.get(_key(name, labels), ()))
        if not s:
            return None
        s.sort()
        i = min(len(s) - 1, int(q * len(s)))
        return s[i]

    def snapshot(self) -> dict:
        counters = self._counter_totals()
        with self._lock:
            # Copy everything under the lock — concurrent incr/observe
            # of a *new* name would otherwise mutate dicts
            # mid-iteration — but sort OUTSIDE it (see percentile()).
            gauges = dict(self._gauges)
            counts = dict(self._counts)
            sums = dict(self._sums)
            series = {k: list(s) for k, s in self._samples.items() if s}
            buckets = {k: list(b) for k, b in self._buckets.items()}
        out: dict = {}
        for (name, labels), v in counters.items():
            out[_flat(name, labels)] = v
        for (name, labels), v in gauges.items():
            out[_flat(name, labels)] = v
        for (name, labels), v in counts.items():
            out[_flat(name + ".count", labels)] = v
        for (name, labels), v in sums.items():
            out[_flat(name + ".sum", labels)] = v
        for (name, labels), s in series.items():
            s.sort()
            for q, tag in ((0.5, "p50"), (0.99, "p99")):
                out[_flat(f"{name}.{tag}", labels)] = s[
                    min(len(s) - 1, int(q * len(s)))
                ]
        # Fixed-bucket counts, one flat key per non-empty bucket (the
        # collector's merge input; empty buckets are elided to keep the
        # snapshot small).  ``le`` joins the series' own labels so the
        # key parses with the same name{k=v,...} grammar.
        for (name, labels), b in buckets.items():
            for i, c in enumerate(b):
                if not c:
                    continue
                le = BUCKETS[i] if i < len(BUCKETS) else "+Inf"
                out[
                    _flat(f"{name}.bucket", labels + (("le", le),))
                ] = c
        return out

    def histograms(self) -> dict:
        """Structured fixed-bucket export: flat series key →
        ``{"count", "sum", "buckets"}`` with ``buckets`` the raw
        per-bucket counts (len(BUCKETS)+1, last = +Inf overflow).
        In-process convenience view; the fleet collector itself merges
        from the snapshot's ``name.bucket{le=}`` flat keys, since that
        is the only form that crosses the daemon ``/metrics`` wire."""
        with self._lock:
            counts = dict(self._counts)
            sums = dict(self._sums)
            buckets = {k: list(b) for k, b in self._buckets.items()}
        return {
            _flat(name, labels): {
                "count": counts.get((name, labels), 0),
                "sum": sums.get((name, labels), 0.0),
                "buckets": b,
            }
            for (name, labels), b in buckets.items()
        }

    def prometheus(self) -> str:
        """Prometheus text exposition, format 0.0.4.

        Counter names end in ``_total``; ``observe()`` series render as
        fixed-bucket HISTOGRAMS (cumulative ``_bucket{le="..."}`` +
        ``_sum``/``_count`` over the whole run) so any scraper — and
        the fleet collector — can aggregate latency across daemons;
        gauges are plain.  (Summaries were the original exposition;
        per-daemon quantiles cannot be merged, DESIGN.md §11.)"""
        counters = self._counter_totals()
        with self._lock:
            gauges = dict(self._gauges)
            counts = dict(self._counts)
            sums = dict(self._sums)
            series = {k: list(b) for k, b in self._buckets.items()}

        lines: list[str] = []

        def by_name(d: dict) -> dict[str, list]:
            g: dict[str, list] = {}
            for (name, labels), v in d.items():
                g.setdefault(name, []).append((labels, v))
            return g

        for name, rows in sorted(by_name(counters).items()):
            pn = _prom_name(name) + "_total"
            lines.append(f"# TYPE {pn} counter")
            for labels, v in sorted(rows):
                lines.append(f"{pn}{_prom_labels(labels)} {_prom_value(v)}")

        for name, rows in sorted(by_name(gauges).items()):
            pn = _prom_name(name)
            lines.append(f"# TYPE {pn} gauge")
            for labels, v in sorted(rows):
                lines.append(f"{pn}{_prom_labels(labels)} {_prom_value(v)}")

        for name, rows in sorted(by_name(series).items()):
            pn = _prom_name(name)
            lines.append(f"# TYPE {pn} histogram")
            for labels, b in sorted(rows):
                acc = 0
                for i, c in enumerate(b):
                    acc += c
                    le = BUCKETS[i] if i < len(BUCKETS) else "+Inf"
                    lines.append(
                        f"{pn}_bucket{_prom_labels(labels, (('le', le),))}"
                        f" {acc}"
                    )
                key = (name, labels)
                lines.append(
                    f"{pn}_sum{_prom_labels(labels)}"
                    f" {_prom_value(sums.get(key, 0.0))}"
                )
                lines.append(
                    f"{pn}_count{_prom_labels(labels)}"
                    f" {_prom_value(counts.get(key, 0))}"
                )

        return "\n".join(lines) + "\n"

    def reset(self) -> None:
        with self._lock:
            shards = list(self._counter_shards)
            self._gauges.clear()
            self._counts.clear()
            self._sums.clear()
            self._samples.clear()
            self._sample_pos.clear()
            self._buckets.clear()
        for d in shards:
            d.clear()


registry = Metrics()
