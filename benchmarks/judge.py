"""The comparison that decides ``correct``.

What the timed path produced — the calls of the window, at the window's
own batch, callers and record size — is held against the plain
reference: a dict of what was written (rebuilt from the seed), and the
replicas' disks read and verified by ``reference.py``.  Every number
compared is exact: its limit is 0 (or a floor of 1 for "something was
checked"); none depends on the load.

    bad_reads             a value read that no write of that key carried,
                          or that a later acknowledged write had replaced
    lost_acked_writes     an acknowledged write whose key reads back as
                          absent, or as an older version
    unanswered_checks     a read-back that returned an error, one retry on
    under_replicated      an acknowledged key (every one, not a sample)
                          held with a value the history allows by fewer
                          replicas than the configuration promises; for
                          the sampled keys a holder also needs a valid
                          writer signature and (where the mix's path
                          promises it on acknowledgement) a sufficient
                          set of valid quorum signatures
    bad_writer_signatures a stored record of a sampled key whose writer
                          signature fails under ``pow``
    bad_quorum_signatures a quorum member's signature carried by a stored
                          record of a sampled key that fails under ``pow``
                          or names an unknown signer
    writeonce_violations  a second write-once of a key accepted, or its
                          first value lost
    dishonest_verdicts    tenants' spot checks that caught the sidecar
                          returning a wrong verdict or signature
    compiled_in_window    programs the sidecar compiled after its warm-up
    forged_accepted       a forged item of ``tenant.py`` that the sidecar
                          answered True (``reference.rsa_verify``: False)
    valid_rejected        a valid item of the tenant answered False
    tenant_unanswered     a tenant request that got no answer (a shed is
                          an answer: overload, reported, not compared)
    readback_checked / records_verified / committed_ops / forged_checked
                          floors of 1: something was checked

A kind that lives in a file (``benchmarks/kinds/``) brings numbers of its
own, each with a limit of 0 or a floor of 1; ``verdict`` holds them beside
these, after them.  The history knows the built-in kinds only: a call of
any other kind is the kind's own to judge.
"""

from __future__ import annotations

import os
import random
import time

from benchmarks import reference, ycsb
from benchmarks.generator import KINDS, Call


class History:
    """Per key: every write sent (version, t_send, t_done, acked)."""

    def __init__(self, seed: int, record: dict, calls: list[Call]):
        self.seed, self.record = seed, record
        self.writes: dict[int, list[tuple[int, float, float, bool]]] = {}
        self.reads: list[tuple[int, bytes | None, float, float]] = []
        self.acked_keys: list[int] = []
        for c in calls:
            if c.kind not in KINDS:
                continue  # a kind in a file judges its own calls
            for i, k in enumerate(c.keynums):
                err = c.errors[i] if i < len(c.errors) else "no answer"
                if c.kind == "read":
                    if err is None:
                        self.reads.append((k, c.values[i], c.t_send, c.t_done))
                else:
                    self.writes.setdefault(k, []).append(
                        (c.versions[i], c.t_send, c.t_done, err is None))
                    if err is None:
                        self.acked_keys.append(k)

    def value(self, keynum: int, version: int) -> bytes:
        return ycsb.record(self.seed, keynum, version,
                           self.record["fields"], self.record["field_bytes"])

    def candidates(self, keynum: int, t_from: float, t_to: float) -> list[int]:
        """Versions a read over ``[t_from, t_to]`` may return: every write
        sent before the read ended, but for those a later acknowledged
        write (sent after they were done, done before the read began)
        has replaced."""
        ws = [w for w in self.writes.get(keynum, []) if w[1] <= t_to]
        # replaced = done before the newest such write was even sent
        newest = max((s for _v, s, d, ok in ws if ok and d < t_from),
                     default=float("-inf"))
        return [v for v, _s, d, _ok in ws if not newest > d]

    def must_exist(self, keynum: int, t_from: float) -> bool:
        return any(ok and d < t_from for _v, _s, d, ok in self.writes.get(keynum, []))

    def sample(self, n: int) -> list[int]:
        """A seeded sample of acknowledged keys, the last call's in it."""
        keys = sorted(set(self.acked_keys))
        if len(keys) <= n:
            return keys
        rng = random.Random(f"{self.seed}|sample")
        tail = sorted(set(self.acked_keys[-min(32, n // 4):]))
        rest = [k for k in keys if k not in set(tail)]
        return sorted(tail + rng.sample(rest, n - len(tail)))


def explain(h: History, keynum: int, value, t0: float, t1: float) -> dict:
    """A bad read, legibly: which version came back (if any we sent),
    and the key's writes around it, times relative to the read's start."""
    got = next((v for v, *_ in h.writes.get(keynum, [])
                if h.value(keynum, v) == value), None)
    return {
        "key": keynum, "returned_version": got if value is not None else "absent",
        "read_s": round(t1 - t0, 4),
        "allowed": h.candidates(keynum, t0, t1),
        "writes": [(v, round(s - t0, 4), round(d - t0, 4), ok)
                   for v, s, d, ok in h.writes.get(keynum, [])][-12:],
    }


def check_reads(h: History) -> dict:
    bad, samples = 0, []
    for k, value, t0, t1 in h.reads:
        allowed = {h.value(k, v) for v in h.candidates(k, t0, t1)}
        wrong = (h.must_exist(k, t0) if value is None
                 else value not in allowed)
        bad += wrong
        if wrong and len(samples) < 5:
            samples.append(explain(h, k, value, t0, t1))
    return {"bad_reads": bad, "window_reads_checked": len(h.reads),
            "bad_read_samples": samples}


def readback(h: History, apis: list, keys: list[int], chunk: int = 256) -> dict:
    """Read the sample back through the client path, once the window
    has closed; wait for each answer (one retry on an error).  ``apis``
    are the clients of the configuration's users: the chunks go round
    them, so every user reads some of what all wrote."""
    out = {"bad_reads": 0, "lost_acked_writes": 0, "unanswered_checks": 0,
           "readback_checked": 0, "bad_read_samples": []}
    for off in range(0, len(keys), chunk):
        api = apis[(off // chunk) % len(apis)]
        part = keys[off : off + chunk]
        names = [ycsb.key_name(h.seed, k) for k in part]
        t0 = time.monotonic()
        got = api.read_many(names)
        for k, name, g in zip(part, names, got):
            if isinstance(g, Exception):
                try:
                    g = api.read(name)
                except Exception:
                    out["unanswered_checks"] += 1
                    continue
            out["readback_checked"] += 1
            allowed = {h.value(k, v)
                       for v in h.candidates(k, t0, time.monotonic())}
            if g is None:
                out["lost_acked_writes"] += 1
            elif g not in allowed:
                # an acknowledged write replaced by nothing we sent, or
                # an older version back
                out["bad_reads"] += 1
            else:
                continue
            if len(out["bad_read_samples"]) < 5:
                out["bad_read_samples"].append(
                    explain(h, k, g, t0, time.monotonic()))
    return out


def _verify_record(pkt: bytes, name: bytes, ring: dict, suff: int,
                   collective: bool, out: dict) -> tuple[bool, bytes]:
    """``(sound, value)`` of one stored packet, counted into ``out``."""
    try:
        rec = reference.parse_record(pkt)
    except reference.Malformed:
        return False, b""
    w_ok = bool(reference.valid_signers(rec.tbs, rec.writer, ring))
    signers = {sid for sid, _s in rec.quorum.entries} if rec.quorum else set()
    q_valid = len(reference.valid_signers(rec.tbss, rec.quorum, ring))
    out["records_verified"] += 1
    out["bad_writer_signatures"] += not w_ok
    # Every quorum signature a stored record carries has to verify,
    # sufficient or not (a quorum server keeps the record with its own
    # share; a pending one has none).
    out["bad_quorum_signatures"] += len(signers) - q_valid
    # Where the path promises the collective signature on acknowledgement,
    # a holder carries a sufficient one; where it is back-filled later,
    # the writer's counts.
    q_ok = q_valid >= suff if collective else True
    return w_ok and q_ok and rec.key == name, rec.value


def inspect_disks(h: History, sample: list[int], cluster_keys: str, dbs: str,
                  guarantees: dict, collective: bool) -> dict:
    """Read every replica's log store.  EVERY acknowledged key is looked
    up on every replica and counted as held where the stored packet
    parses, names the key and carries a value the history allows; for
    the keys of ``sample`` the holder's signatures have to verify under
    ``pow`` as well."""
    ring = reference.load_ring(cluster_keys)
    every = sorted(set(h.acked_keys) | set(sample))
    sampled = set(sample)
    names = {ycsb.key_name(h.seed, k): k for k in every}
    now = time.monotonic()
    allowed = {
        k: {h.value(k, v) for v in h.candidates(k, now, now)} for k in every
    }
    out = {"under_replicated": 0, "bad_writer_signatures": 0,
           "bad_quorum_signatures": 0, "records_verified": 0, "replicas": 0,
           "keys_counted": len(every)}
    holders = dict.fromkeys(every, 0)
    verdicts: dict[bytes, tuple[bool, bytes]] = {}  # packet -> (sound, value)
    for replica in sorted(os.listdir(dbs)):
        root = os.path.join(dbs, replica)
        if not os.path.isdir(root):
            continue
        out["replicas"] += 1
        store = reference.read_store(root, set(names))
        for name, versions in store.items():
            k = names[name]
            held = False
            for pkt in versions.values():
                if k in sampled:
                    if pkt not in verdicts:
                        verdicts[pkt] = _verify_record(
                            pkt, name, ring, guarantees["suff"], collective, out)
                    sound, value = verdicts[pkt]
                else:
                    sound, value = _parse_only(
                        pkt, name, guarantees["suff"] if collective else 0)
                held |= sound and value in allowed[k]
            holders[k] += held
    short = [k for k, n in holders.items() if n < guarantees["min_replicas"]]
    out["under_replicated"] = len(short)
    out["under_replicated_sample"] = [
        (k, holders[k]) for k in short[:5]]
    out["min_holders"] = min(holders.values()) if holders else 0
    return out


def _parse_only(pkt: bytes, name: bytes, suff: int) -> tuple[bool, bytes]:
    """``(held, value)`` without a ``pow``: the packet parses, names the
    key, carries a writer signature and ``suff`` distinct signers."""
    try:
        rec = reference.parse_record(pkt)
    except reference.Malformed:
        return False, b""
    signers = {sid for sid, _s in rec.quorum.entries} if rec.quorum else set()
    return (rec.key == name and rec.writer is not None
            and len(signers) >= suff), rec.value


def writeonce(api, seed: int) -> dict:
    """Write-once honoured: the second write is refused, the first kept.
    Both are the same client's: another user's second write is refused
    as no write of the owner's (TOFU), whether write-once holds or not."""
    name = b"once%d" % seed
    first, second = b"kept-%d" % seed, b"clobbered-%d" % seed
    violations = 0
    try:
        api.write_once(name, first)
    except Exception:
        violations += 1
    try:
        api.write_once(name, second)
        violations += 1  # accepted twice
    except Exception:
        pass
    try:
        violations += api.read(name) != first
    except Exception:
        violations += 1
    return {"writeonce_violations": violations}


LIMITS = [
    ("bad_reads", "<=", 0), ("lost_acked_writes", "<=", 0),
    ("unanswered_checks", "<=", 0), ("under_replicated", "<=", 0),
    ("bad_writer_signatures", "<=", 0), ("bad_quorum_signatures", "<=", 0),
    ("writeonce_violations", "<=", 0), ("dishonest_verdicts", "<=", 0),
    ("compiled_in_window", "<=", 0), ("forged_accepted", "<=", 0),
    ("valid_rejected", "<=", 0), ("tenant_unanswered", "<=", 0),
    ("readback_checked", ">=", 1), ("records_verified", ">=", 1),
    ("committed_ops", ">=", 1), ("forged_checked", ">=", 1),
]


def verdict(numbers: dict, more_limits=()) -> tuple[bool, dict]:
    """``(correct, compared)``: each number beside its limit.
    ``more_limits`` are those of the mix's kinds in files, held after the
    built-in ones (``kinds.load`` has checked that each is exact)."""
    compared, ok = {}, True
    for name, op, limit in [*LIMITS, *more_limits]:
        if name not in numbers:
            continue
        v = numbers[name]
        compared[name] = [v, op, limit]
        ok &= (v <= limit) if op == "<=" else (v >= limit)
    return ok, compared


def compared_line(compared: dict) -> str:
    return "compared: " + " ".join(
        f"{k}={v[0]}{v[1]}{v[2]}" for k, v in compared.items()
    )
