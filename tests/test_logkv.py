"""Log-structured engine (DESIGN.md §19): group commit, residue-
preserving compaction, the O(changed) digest tree, storage-served
repair cursors, snapshot shipping, and the fill-scaling p50 bound the
issue's acceptance gate names (1M-key p50 within 1.3x of 10k)."""

import threading
import time

import pytest

from bftkv_tpu import packet as pkt
from bftkv_tpu.storage import segment as seg
from bftkv_tpu.storage.logkv import LogStorage


def _record(variable: bytes, t: int, *, completed: bool, value: bytes = b"v"):
    """A minimal protocol record: parsable, carries a collective
    signature whose ``completed`` bit drives the §12/§19.3 keep rules."""
    sig = pkt.SignaturePacket(
        type=1, version=0, completed=True, data=b"s", cert=b"c"
    )
    ss = pkt.SignaturePacket(
        type=1, version=0, completed=completed, data=b"ss", cert=None
    )
    return pkt.serialize(variable, value, t, sig, ss)


# -- group commit ------------------------------------------------------------


def test_write_batch_one_fsync(tmp_path, monkeypatch):
    """The group-commit contract: a coalesced batch shares ONE
    durability barrier, however many records it carries."""
    import os as os_mod

    calls = []
    real = os_mod.fsync
    monkeypatch.setattr(
        os_mod, "fsync", lambda fd: (calls.append(fd), real(fd))[1]
    )
    s = LogStorage(str(tmp_path / "db"), fsync=True, group_commit_s=0)
    calls.clear()
    s.write_batch([(b"k%03d" % i, 1, b"v%d" % i) for i in range(50)])
    assert len(calls) == 1
    for i in range(50):
        assert s.read(b"k%03d" % i) == b"v%d" % i
    s.close()


def test_single_writes_durable_and_concurrent(tmp_path, monkeypatch):
    """Single writes stay durable-by-default (fsync unless opted out),
    and concurrent writers never fsync MORE than once per write —
    losers of the leader race piggyback on the leader's barrier."""
    import os as os_mod

    count = [0]
    real = os_mod.fsync

    def counting(fd):
        count[0] += 1
        return real(fd)

    monkeypatch.setattr(os_mod, "fsync", counting)
    s = LogStorage(str(tmp_path / "db"), group_commit_s=0)
    assert s.fsync is True  # durable by default, unlike PlainStorage
    count[0] = 0
    errs = []

    def worker(w):
        try:
            for i in range(10):
                s.write(b"w%d-%d" % (w, i), 1, b"x")
        except Exception as e:  # pragma: no cover - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(w,)) for w in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert 1 <= count[0] <= 40
    for w in range(4):
        for i in range(10):
            assert s.read(b"w%d-%d" % (w, i)) == b"x"
    s.close()


def _counting_fsync(monkeypatch) -> list:
    import os as os_mod

    calls = []
    real = os_mod.fsync
    monkeypatch.setattr(
        os_mod, "fsync", lambda fd: (calls.append(fd), real(fd))[1]
    )
    return calls


def test_append_is_readable_before_its_barrier(tmp_path, monkeypatch):
    calls = _counting_fsync(monkeypatch)
    s = LogStorage(str(tmp_path / "db"), group_commit_s=0)
    calls.clear()
    pos = s.append(b"k", 1, b"v")
    assert s.read(b"k") == b"v" and s.versions(b"k") == [1]
    assert calls == []  # nothing durable yet
    s.barrier(pos)
    assert len(calls) == 1
    s.close()


def test_one_barrier_covers_every_earlier_append(tmp_path, monkeypatch):
    """Barriers are positions in one file: a barrier at the last
    position covers what other threads appended before it, and a later
    barrier at one of their positions costs no fsync."""
    calls = _counting_fsync(monkeypatch)
    s = LogStorage(str(tmp_path / "db"), group_commit_s=0)
    theirs = []
    threads = [
        threading.Thread(
            target=lambda w=w: theirs.extend(
                s.append(b"t%d-%d" % (w, i), 1, b"x") for i in range(8)))
        for w in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    calls.clear()
    s.barrier(s.append(b"mine", 1, b"y"))
    assert len(calls) == 1
    for pos in theirs:
        s.barrier(pos)
    assert len(calls) == 1
    s.close()


@pytest.mark.parametrize("form", ["write", "append+barrier"])
def test_write_is_append_then_barrier(tmp_path, monkeypatch, form):
    """Both forms leave the same bytes on disk, one fsync a record."""
    calls = _counting_fsync(monkeypatch)
    s = LogStorage(str(tmp_path / "db"), group_commit_s=0)
    calls.clear()
    for i in range(3):
        rec = (b"k%d" % i, i + 1, b"v%d" % i)
        if form == "write":
            s.write(*rec)
        else:
            s.barrier(s.append(*rec))
    assert len(calls) == 3
    s.close()
    (seg_file,) = (tmp_path / "db").iterdir()
    assert seg_file.read_bytes() == b"".join(
        seg.encode_record(b"k%d" % i, i + 1, b"v%d" % i) for i in range(3))


# -- compaction --------------------------------------------------------------


def test_compaction_residue_semantics(tmp_path):
    """§19.3 keep rules on real records: a pending version below a
    newer certified one compacts away; certified history, uncertified
    LATEST residue, and unparsable bytes all survive — before and
    after a crash-restart replay of the compacted segment."""
    s = LogStorage(str(tmp_path / "db"), fsync=False, compact_trigger=0)
    # a: pending@1 (reclaimable), certified@2, pending@3 (latest residue)
    s.write(b"a", 1, _record(b"a", 1, completed=False, value=b"a1"))
    s.write(b"a", 2, _record(b"a", 2, completed=True, value=b"a2"))
    s.write(b"a", 3, _record(b"a", 3, completed=False, value=b"a3"))
    # b: certified history — every version stays readable
    s.write(b"b", 1, _record(b"b", 1, completed=True, value=b"b1"))
    s.write(b"b", 2, _record(b"b", 2, completed=True, value=b"b2"))
    # c: unparsable bytes below a certified latest — never dropped
    s.write(b"c", 1, b"\x00not-a-record")
    s.write(b"c", 2, _record(b"c", 2, completed=True, value=b"c2"))

    s.seal_active()
    stats = s.compact()
    assert stats["dropped"] == 1  # exactly a@1
    assert stats["kept"] == 6

    def check(store):
        assert store.versions(b"a") == [2, 3]
        assert pkt.parse(store.read(b"a", 2)).value == b"a2"
        assert pkt.parse(store.read(b"a", 3)).value == b"a3"
        assert store.versions(b"b") == [1, 2]
        assert store.versions(b"c") == [1, 2]
        assert store.read(b"c", 1) == b"\x00not-a-record"

    check(s)
    s.reopen()  # replay the compacted segment from disk
    check(s)
    s.close()


def test_compaction_trigger_reclaims_dead_bytes(tmp_path):
    """Overwriting the same (variable, t) accumulates dead bytes in
    sealed segments; the background trigger compacts them away and the
    store keeps serving the live copies."""
    s = LogStorage(
        str(tmp_path / "db"),
        fsync=False,
        segment_bytes=2048,
        compact_trigger=0.3,
    )
    payload = bytes(128)
    for round_ in range(6):
        for i in range(20):
            s.write(b"k%02d" % i, 1, payload + b"%d" % round_)
    deadline = time.time() + 5.0
    while time.time() < deadline:
        if s.compactions and s.dead_ratio() < 0.3:
            break
        time.sleep(0.02)
    assert s.compactions >= 1
    for i in range(20):
        assert s.read(b"k%02d" % i) == payload + b"5"
    s.close()


# -- O(changed) digests ------------------------------------------------------


class _CountingStorage:
    """Storage proxy counting read()/versions() calls — the probe the
    O(changed) assertions use."""

    def __init__(self, inner):
        self.inner = inner
        self.reads = 0
        self.version_calls = 0

    def read(self, variable, t=0):
        self.reads += 1
        return self.inner.read(variable, t)

    def versions(self, variable):
        self.version_calls += 1
        return self.inner.versions(variable)

    def __getattr__(self, name):
        return getattr(self.inner, name)


def test_digest_tree_reads_o_changed(tmp_path):
    """After the initial build, a digest round re-reads ONLY dirty
    variables: 100 changed records out of 3000 cost ~100 reads, not a
    keyspace sweep."""
    from bftkv_tpu.sync.digest import DigestTree

    s = LogStorage(str(tmp_path / "db"), fsync=False)
    n = 3000
    for i in range(n):
        var = b"key-%05d" % i
        s.write(var, 1, _record(var, 1, completed=True))
    probe = _CountingStorage(s)
    tree = DigestTree(probe)
    tree.buckets()  # full build: O(keyspace), once
    base = tree.root()

    probe.reads = 0
    probe.version_calls = 0
    changed = [b"key-%05d" % i for i in range(0, 1000, 10)]  # 100 vars
    for var in changed:
        s.write(var, 2, _record(var, 2, completed=True))
        tree.mark(var)
    tree.buckets()
    assert tree.root() != base
    # Bounded by the CHANGED set (small constant per variable), far
    # under the 3000-key keyspace.
    assert probe.reads <= 4 * len(changed)
    assert probe.version_calls <= 4 * len(changed)

    probe.reads = 0
    probe.version_calls = 0
    tree.buckets()  # nothing dirty: free
    assert probe.reads == 0 and probe.version_calls == 0
    s.close()


# -- repair-scan cursor ------------------------------------------------------


def test_pending_variables_storage_served_cursor(tmp_path):
    """``pending_variables`` on a §19 store pages through the keyspace
    via the storage-served sorted_keys cursor: each window reads only
    window-many records, finds exactly the pending residue, and the
    cursor walk terminates."""
    from bftkv_tpu.protocol.server import Server

    s = LogStorage(str(tmp_path / "db"), fsync=False)
    pending_vars = set()
    for i in range(40):
        var = b"key-%03d" % i
        completed = i % 8 != 0
        if not completed:
            pending_vars.add(var)
        s.write(var, 1, _record(var, 1, completed=completed))

    class _Stub:
        storage = s

    stub = _Stub()
    probe = _CountingStorage(s)
    stub.storage = probe

    found = set()
    cursor = None
    rounds = 0
    while True:
        probe.reads = 0
        got, cursor = Server.pending_variables(
            stub, after=cursor, scan_window=7
        )
        rounds += 1
        assert probe.reads <= 7  # the window bounds the record reads
        found.update(v for v, _t, _raw, _p in got)
        if cursor is None:
            break
        assert rounds <= 40
    assert found == pending_vars
    assert rounds == 6  # ceil(40 / 7) windows, not a full-store parse
    s.close()


def test_sorted_keys_window(tmp_path):
    s = LogStorage(str(tmp_path / "db"), fsync=False)
    import random

    keys = [b"k%03d" % i for i in range(50)]
    for k in random.Random(7).sample(keys, len(keys)):
        s.write(k, 1, b"v")
    assert s.sorted_keys() == keys
    assert s.sorted_keys(after=b"k010", limit=5) == keys[11:16]
    assert s.sorted_keys(after=keys[-1]) == []
    # The cached sort survives same-key updates and extends on new keys.
    s.write(b"k000", 2, b"v2")
    s.write(b"zzz", 1, b"v")
    assert s.sorted_keys() == keys + [b"zzz"]
    s.close()


# -- snapshot shipping -------------------------------------------------------


def test_snapshot_records_live_only(tmp_path):
    """snapshot_records seals the active segment and streams exactly
    the LIVE records (superseded same-(variable, t) copies stay dead),
    honoring the predicate."""
    s = LogStorage(str(tmp_path / "db"), fsync=False)
    s.write(b"x", 1, b"old")
    s.write(b"x", 1, b"new")  # supersedes the first copy
    s.write(b"x", 2, b"x2")
    s.write(b"y", 1, b"y1")
    got = sorted(s.snapshot_records())
    assert got == [(b"x", 1, b"new"), (b"x", 2, b"x2"), (b"y", 1, b"y1")]
    only_y = list(s.snapshot_records(lambda v: v == b"y"))
    assert only_y == [(b"y", 1, b"y1")]
    assert s.sealed_segment_paths()  # the active segment was sealed
    s.close()


# -- fill-scaling p50 --------------------------------------------------------


def _fill_p50(path: str, n: int, samples: int = 2000) -> float:
    """Median append latency measured AFTER ``n`` resident keys."""
    s = LogStorage(path, fsync=False)
    payload = b"p" * 64
    for i in range(n):
        s.write(b"fill-%07d" % i, 1, payload)
    lat = []
    for i in range(samples):
        t0 = time.perf_counter()
        s.write(b"probe-%07d" % i, 1, payload)
        lat.append(time.perf_counter() - t0)
    s.close()
    lat.sort()
    return lat[len(lat) // 2]


def test_fill_p50_flat_10k_vs_100k(tmp_path):
    """Append cost must not scale with resident keyspace: p50 at 100k
    keys within 1.3x of p50 at 10k (plus a scheduler-noise epsilon)."""
    p10k = _fill_p50(str(tmp_path / "s10k"), 10_000)
    p100k = _fill_p50(str(tmp_path / "s100k"), 100_000)
    assert p100k <= 1.3 * p10k + 5e-6, (p10k, p100k)


@pytest.mark.slow
def test_fill_p50_flat_10k_vs_1m(tmp_path):
    """The acceptance-gate form: 1M resident keys, p50 within 1.3x of
    the 10k-key fill."""
    p10k = _fill_p50(str(tmp_path / "s10k"), 10_000)
    p1m = _fill_p50(str(tmp_path / "s1m"), 1_000_000)
    assert p1m <= 1.3 * p10k + 5e-6, (p10k, p1m)
