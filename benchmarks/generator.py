"""The one general traffic generator: closed-loop callers driven by a
mix file (``traffic/<mix>.json``).

A mix names the operations and their shares, the batch size, the number
of callers, the key distribution and the record shape.  A caller waits
for each reply before its next call.  Operations:

- ``insert``  ``batch`` new records (``write_many``; ``write`` at batch 1)
- ``update``  a new version of ``batch`` existing records
- ``read``    ``batch`` existing records (``read_many``; ``read`` at 1)

A share of any other name is a kind that lives in a file,
``benchmarks/kinds/<kind>.py`` (its docstring says what a kind provides):
the caller hands the draw to it.  The harness opens one client per user
of the configuration (``users``); caller *i* drives the client of user
*i mod users*.

Every draw comes from ``--seed``; every seed issues the same kinds and
sizes of work.  The callers keep a history of what they sent and what
came back — the judge's only input besides the replicas' disks.
"""

from __future__ import annotations

import random
import threading
import time
from dataclasses import dataclass, field

from benchmarks import ycsb

KINDS = ("insert", "update", "read")   # the built-ins; the rest are files


def assign(clients: list, callers: int) -> list:
    """Whose client each caller drives: caller *i* the client of user
    *i mod users*.  Callers of one user share its ONE client object (a
    server keeps one session per peer); with one user that is all of them."""
    return [clients[i % len(clients)] for i in range(callers)]


@dataclass
class Call:
    kind: str
    caller: int
    keynums: list[int]
    versions: list[int]          # written versions (insert/update)
    t_send: float
    t_done: float = 0.0
    errors: list = field(default_factory=list)   # per item: None or str
    values: list = field(default_factory=list)   # per item, reads only
    phase: str = "window"
    beside: str = ""             # the kind in a file whose draw issued this
                                 # built-in call beside its own: judged as
                                 # any other, counted with the kind's call

    def acked(self) -> int:
        return sum(e is None for e in self.errors)


class KeySpace:
    """Record numbers: 0..loaded-1 exist after the preload; inserts take
    fresh blocks above them."""

    def __init__(self, loaded: int):
        self._lock = threading.Lock()
        self.loaded = loaded
        self._next = loaded

    def fresh(self, n: int) -> list[int]:
        with self._lock:
            first, self._next = self._next, self._next + n
        return list(range(first, first + n))


class Caller(threading.Thread):
    def __init__(self, idx: int, api, mix: dict, seed: int, keys: KeySpace,
                 gate: "Gate", kinds: dict | None = None):
        super().__init__(name=f"caller-{idx}", daemon=True)
        self.idx, self.api, self.mix, self.seed = idx, api, mix, seed
        self.keys, self.gate = keys, gate
        self.kinds = kinds or {}   # name -> kinds.Kind, for shares in files
        self.rng = random.Random(f"{seed}|caller|{idx}")
        self.batch = int(mix["batch"])
        self.shares = [(k, float(mix["ops"].get(k, 0.0))) for k in KINDS]
        self.shares += [(k, float(mix["ops"][k])) for k in self.kinds]
        self.record = mix["record"]
        self.chooser = None   # of existing keys: where the mix preloads some
        if keys.loaded > 0 and mix["keys"]["distribution"] != "new":
            self.chooser = ycsb.KeyChooser(
                mix["keys"]["distribution"], keys.loaded,
                float(mix["keys"].get("theta", 0.99)),
            )
        self._version = 0
        self.calls: list[Call] = []
        self.error: BaseException | None = None

    # -- one call -----------------------------------------------------------

    def _kind(self) -> str:
        u, acc = self.rng.random() * sum(s for _k, s in self.shares), 0.0
        for kind, share in self.shares:
            acc += share
            if u < acc:
                return kind
        return self.shares[-1][0]

    def _existing(self) -> list[int]:
        if self.chooser is None:
            raise RuntimeError(
                "an update or a read in a mix with no existing keys: give it "
                "preload_records and a keys.distribution to draw them by")
        out: list[int] = []
        while len(out) < self.batch:
            k = self.chooser.draw(self.rng)
            if k not in out:
                out.append(k)
        return out

    def _value(self, keynum: int, version: int) -> bytes:
        return ycsb.record(self.seed, keynum, version,
                           self.record["fields"], self.record["field_bytes"])

    def new_call(self, kind: str, keynums: list[int], versions: list[int],
                 phase: str) -> Call:
        """A call stamped as sent now (for the kinds that live in files)."""
        return Call(kind, self.idx, keynums, versions, time.monotonic(),
                    phase=phase)

    def one_call(self, phase: str) -> list[Call]:
        """Draw a kind and issue it: one call, or the several that a kind
        in a file returned."""
        kind = self._kind()
        if kind in self.kinds:
            calls = self.kinds[kind].one_call(self, phase)
            now = time.monotonic()
            for c in calls:
                c.t_done = c.t_done or now
        else:
            calls = [self.builtin(kind, phase)]
        self.calls += calls
        return calls

    def builtin(self, kind: str, phase: str) -> Call:
        """One ``insert`` / ``update`` / ``read`` as the mix sizes it, not
        yet filed under ``calls`` (``one_call`` files what it returns)."""
        keynums = (self.keys.fresh(self.batch) if kind == "insert"
                   else self._existing())
        names = [ycsb.key_name(self.seed, k) for k in keynums]
        versions: list[int] = []
        if kind != "read":
            for _ in keynums:
                self._version += 1
                # unique over callers; version 0 is the preload's
                versions.append(self._version * 4096 + self.idx + 1)
            items = [(n, self._value(k, v))
                     for n, k, v in zip(names, keynums, versions)]
        call = Call(kind, self.idx, keynums, versions, time.monotonic(),
                    phase=phase)
        try:
            if kind == "read":
                if self.batch == 1:
                    got = [self.api.read(names[0])]
                else:
                    got = self.api.read_many(names)
                call.values = [g if isinstance(g, (bytes, type(None))) else None
                               for g in got]
                call.errors = [None if isinstance(g, (bytes, type(None)))
                               else repr(g) for g in got]
            elif self.batch == 1:
                self.api.write(*items[0])
                call.errors = [None]
            else:
                call.errors = [None if e is None else repr(e)
                               for e in self.api.write_many(items)]
        except Exception as e:  # an operation the system refused or lost
            call.errors = [repr(e)] * len(keynums)
            call.values = [None] * len(keynums) if kind == "read" else []
        call.t_done = time.monotonic()
        return call

    # -- the thread ---------------------------------------------------------

    def run(self) -> None:
        try:
            # Warm until ``warm_calls`` calls came back whole: a
            # deployment's first calls can time out (sessions, key
            # registration with the sidecar), and a window must not start
            # on a caller that never got through.
            want, tries = int(self.mix["warm_calls"]), 0
            while want > 0:
                calls = self.one_call("warm")
                tries += 1
                want -= all(c.acked() == len(c.keynums) for c in calls)
                if tries >= int(self.mix["warm_calls"]) + 4 and want > 0:
                    said = [e for c in calls for e in c.errors if e is not None]
                    raise RuntimeError(
                        f"warm calls keep failing: {said[0] if said else calls}")
            self.gate.warm_done()
            deadline = self.gate.wait_start()
            while time.monotonic() < deadline:
                self.one_call("window")
        except BaseException as e:  # surfaced by the harness after join
            self.error = e
            self.gate.abort()


class Gate:
    """All callers finish warming, then start the window together."""

    def __init__(self, callers: int):
        self._cv = threading.Condition()
        self._warm_left = callers
        self._deadline: float | None = None
        self.aborted = False

    def warm_done(self) -> None:
        with self._cv:
            self._warm_left -= 1
            self._cv.notify_all()

    def abort(self) -> None:
        with self._cv:
            self.aborted = True
            self._warm_left = 0
            if self._deadline is None:
                self._deadline = 0.0
            self._cv.notify_all()

    def wait_warm(self, timeout: float) -> bool:
        with self._cv:
            return self._cv.wait_for(lambda: self._warm_left <= 0, timeout)

    def open(self, seconds: float) -> float:
        with self._cv:
            t0 = time.monotonic()
            self._deadline = t0 + seconds
            self._cv.notify_all()
        return t0

    def wait_start(self) -> float:
        with self._cv:
            self._cv.wait_for(lambda: self._deadline is not None)
            return self._deadline


def preload(api, mix: dict, seed: int, chunk: int = 256) -> list[Call]:
    """Load ``preload_records`` records (version 0) in one pass."""
    n = int(mix.get("preload_records", 0))
    rec = mix["record"]
    calls = []
    for off in range(0, n, chunk):
        keynums = list(range(off, min(n, off + chunk)))
        items = [
            (ycsb.key_name(seed, k),
             ycsb.record(seed, k, 0, rec["fields"], rec["field_bytes"]))
            for k in keynums
        ]
        call = Call("insert", -1, keynums, [0] * len(keynums),
                    time.monotonic(), phase="preload")
        call.errors = [None if e is None else repr(e)
                       for e in api.write_many(items)]
        call.t_done = time.monotonic()
        calls.append(call)
    return calls


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile; ``inf`` entries (failed operations) sort
    last, so a tail that reaches them reads ``inf``."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, int(-(-q * len(s) // 1)) - 1))]
