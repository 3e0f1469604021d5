"""Operation kinds and caller identities are files and data (PR 32).

Cluster-free, well under a second each: the built-ins issue what the
parent's generator issued (a digest taken from commit ``3ad0bdb``'s
``generator.py`` before it was edited); a kind written into
``benchmarks/kinds/`` by the test has its prepare / call / judge / plant
reached through fake clients; what is wrong with a kind or a mix fails
by name before any child starts; the history ignores a foreign kind; a
draw counts once; the configuration's users hand out the clients.

Then one ``--rehearse`` walk on the CPU (about 30 s each way) of a
temporary manifest whose mix is half ``insert``, half the test kind, on a
configuration of two users (a client each, a caller each): sound it ends
correct, with the kind's plant not.
"""

import argparse
import hashlib
import json
import os
import subprocess
import sys

import pytest

from benchmarks import generator, judge, kinds, plants
from benchmarks import run as runmod
from benchmarks.harness import ROOT, BenchFailure

B = os.path.join(ROOT, "benchmarks")
KIND = "zz_test_kind"
SEED = 2147483659

KIND_SOURCE = '''
"""Test kind: a write-once under a name of its own, read back at once,
and one built-in insert beside it."""
from benchmarks.plants import _Planted

LIMITS = [("zzk_wrong_values", "<=", 0), ("zzk_checked", ">=", 1),
          ("zzk_marker_kept", ">=", 1)]


def prepare(ctx):
    name, value = b"zzk-marker-%d" % ctx["seed"], b"dealt-%d" % ctx["seed"]
    ctx["clients"][0].write_once(name, value)
    return {"marker": (name, value)}


def one_call(caller, state, phase):
    n = caller.rng.getrandbits(48)
    name = b"zzk-%d-%d-%d" % (caller.seed, caller.idx, n)
    value = b"v%d" % caller.rng.getrandbits(200)
    call = caller.new_call("zz_test_kind", [n], [], phase)
    try:
        caller.api.write_once(name, value)
        call.values = [(value, caller.api.read(name))]
        call.errors = [None]
    except Exception as e:
        call.errors = [repr(e)]
    return [call, caller.builtin("insert", phase)]


def judge(calls, state, ctx):
    name, value = state["marker"]
    acked = [c for c in calls if c.errors == [None]]
    return {"zzk_wrong_values": sum(c.values[0][0] != c.values[0][1]
                                    for c in acked),
            "zzk_checked": len(acked),
            "zzk_marker_kept": int(ctx["clients"][-1].read(name) == value)}


class Bent(_Planted):
    """Every ``every``-th write-once stores a value with one bit changed."""

    def write_once(self, variable, value):
        if self._hit():
            value = value[:-1] + bytes([value[-1] ^ 1])
        self._api.write_once(variable, value)


PLANTS = {"zzk_bent": Bent}
'''


class FakeClient:
    """A dict behind the client facade; records what it was sent."""

    def __init__(self):
        self.store: dict = {}
        self.log: list = []

    def write(self, name, value, password=""):
        self.log.append(("write", name, value))
        self.store[name] = value

    def write_once(self, name, value):
        self.log.append(("write_once", name, value))
        if name in self.store:
            raise RuntimeError("write-once: exists")
        self.store[name] = value

    def write_many(self, items):
        self.log.append(("write_many", tuple(items)))
        self.store.update(items)
        return [None] * len(items)

    def read(self, name, password=""):
        self.log.append(("read", name))
        return self.store.get(name, b"v")

    def read_many(self, names):
        self.log.append(("read_many", tuple(names)))
        return [self.store.get(n, b"v") for n in names]


def mix_of(name: str, rehearse: bool = False) -> dict:
    mix = runmod.load_json("benchmarks", "traffic", name + ".json")
    if rehearse:
        mix.update(mix.get("rehearse", {}))
    return mix


def drive(mix: dict, seed: int, rounds: int, clients=None, loaded=None):
    """Callers driven in turn, no threads: ``(callers, clients)``."""
    n = int(mix["callers"])
    keys = generator.KeySpace(int(mix.get("preload_records", 0)))
    gate = generator.Gate(n)
    clients = clients or [FakeClient() for _ in range(n)]
    facades = generator.assign(clients, n)
    callers = [generator.Caller(i, facades[i], mix, seed, keys, gate, loaded)
               for i in range(n)]
    for _ in range(rounds):
        for c in callers:
            c.one_call("window")
    return callers, clients


# -- the built-ins do not move ------------------------------------------------

# Taken from the parent's generator.py (commit 3ad0bdb) by this very drive,
# before the file was edited: sha256 over, per caller and call,
# (caller, kind, keynums, versions, (method, names and values sent)).
PARENT_DIGESTS = [
    ("load", False, 3, 24,
     "db65172c822451e705344c6a51fb5e1a623b85b4d448b00e651c7cb56dba279c"),
    ("load-c4", False, 3, 12,
     "a208781f2eeaf26ae073016d2873fde5b7ae5d289661d4a7c9f0ee475e4acfbe"),
    ("ycsb-a", False, 25, 800,
     "a35ae88f40d9f9345b0947455b2a63b6c161f42b63cbeda3a80cd2a0e2142d1e"),
    ("load", True, 5, 10,
     "350cadc35da479bac51184a69d19b9d08f1e0e62b92da57ee43ccc9c95b11b14"),
]


@pytest.mark.parametrize("name,rehearse,rounds,n_calls,want", PARENT_DIGESTS)
def test_the_same_seed_issues_the_parents_calls(name, rehearse, rounds,
                                                n_calls, want):
    callers, clients = drive(mix_of(name, rehearse), SEED, rounds)
    h, n = hashlib.sha256(), 0
    for c, client in zip(callers, clients):
        assert len(c.calls) == len(client.log) == rounds
        for call, sent in zip(c.calls, client.log):
            h.update(repr((c.idx, call.kind, call.keynums, call.versions,
                           sent)).encode())
            n += 1
    assert (n, h.hexdigest()) == (n_calls, want)


def test_the_committed_mixes_open_nothing_new():
    m = runmod.load_manifest()
    for w in m["workloads"]:
        run = runmod.Run(argparse.Namespace(
            workload=w["name"], seed=1, rehearse=False, manifest=""))
        assert run.kinds == {} and run.limits == []
        assert run.plants == plants.PLANTS
        assert run.config["users"] == 1     # one client of u01 for all callers


# -- a kind in a file ---------------------------------------------------------


@pytest.fixture
def kind_file():
    path = os.path.join(B, "kinds", KIND + ".py")
    assert not os.path.exists(path)
    with open(path, "w") as f:
        f.write(KIND_SOURCE)
    sys.modules.pop("benchmarks.kinds." + KIND, None)
    try:
        yield path
    finally:
        os.remove(path)
        sys.modules.pop("benchmarks.kinds." + KIND, None)


def half_mix(**more) -> dict:
    mix = mix_of("load", rehearse=True)
    mix.update(ops={"insert": 0.5, KIND: 0.5}, callers=2, batch=4, **more)
    return mix


def walk(plant: str = ""):
    """prepare, calls, judge and verdict with fake clients: what run.py
    does with a kind, without a cluster."""
    mix = half_mix()
    loaded = kinds.load(mix["ops"])
    table = {**plants.PLANTS, **loaded[KIND].plants}
    clients = [FakeClient(), FakeClient()]
    clients[1].store = clients[0].store        # two users, one cluster
    ctx = {"clients": clients, "config": {}, "mix": mix, "seed": SEED,
           "rehearse": True}
    loaded[KIND].prepare(ctx)
    planted = [plants.plant(plant, c, 2, table) for c in clients]
    callers, _ = drive(mix, SEED, 12, clients=planted, loaded=loaded)
    calls = [c for caller in callers for c in caller.calls]
    numbers = {"bad_reads": 0, "committed_ops": sum(c.acked() for c in calls)}
    numbers.update(loaded[KIND].judge(calls, ctx))
    limits = loaded[KIND].limits
    return calls, clients, judge.verdict(numbers, limits)


def test_prepare_call_judge_are_reached_and_compared(kind_file):
    calls, clients, (ok, compared) = walk()
    assert ("write_once", b"zzk-marker-%d" % SEED, b"dealt-%d" % SEED) \
        in clients[0].log                       # prepare, through a client
    own = [c for c in calls if c.kind == KIND]
    assert own and all(c.t_done >= c.t_send > 0 for c in own)
    # the kind returned a built-in call beside each of its own
    assert sum(c.kind == "insert" for c in calls) >= len(own)
    assert ok is True
    assert list(compared) == ["bad_reads", "committed_ops", "zzk_wrong_values",
                              "zzk_checked", "zzk_marker_kept"]
    assert compared["zzk_wrong_values"] == [0, "<=", 0]
    assert compared["zzk_checked"] == [len(own), ">=", 1]
    assert compared["zzk_marker_kept"] == [1, ">=", 1]


def test_the_kinds_plant_comes_out_incorrect(kind_file):
    _calls, _clients, (ok, compared) = walk("zzk_bent")
    assert ok is False and compared["zzk_wrong_values"][0] >= 1


def test_same_seed_same_kind_calls(kind_file):
    a = [(c.kind, c.keynums, c.versions) for c in walk()[0]]
    b = [(c.kind, c.keynums, c.versions) for c in walk()[0]]
    assert a == b and {k for k, *_ in a} == {"insert", KIND}


def test_history_ignores_a_foreign_kinds_call(kind_file):
    calls, _clients, _v = walk()
    h = judge.History(SEED, half_mix()["record"], calls)
    inserted = {k for c in calls if c.kind == "insert" for k in c.keynums}
    assert set(h.writes) == inserted == set(h.acked_keys)
    foreign = {k for c in calls if c.kind == KIND for k in c.keynums}
    assert foreign and not foreign & set(h.writes) and not h.reads


def write_kind(path: str, **changed) -> None:
    src = KIND_SOURCE
    for old, new in changed.values():
        assert old in src
        src = src.replace(old, new)
    with open(path, "w") as f:
        f.write(src)
    sys.modules.pop("benchmarks.kinds." + KIND, None)


@pytest.mark.parametrize("change,said", [
    ({"limit": ('"<=", 0)', '"<=", 0.05)')}, "only <= 0 or >= 1"),
    ({"limit": ('"zzk_checked", ">=", 1', '"zzk_checked", ">=", 2')},
     "only <= 0 or >= 1"),
    ({"limit": ('"zzk_wrong_values", "<=", 0', '"zzk_wrong_values", "<", 0')},
     "only <= 0 or >= 1"),
    ({"name": ("zzk_wrong_values", "bad_reads")}, "'bad_reads' is taken"),
    ({"plant": ('"zzk_bent": Bent', '"lost_write": Bent')},
     "plant 'lost_write' is taken"),
    ({"judge": ("def judge(", "def judged(")}, "has no judge()"),
    ({"limits": ("LIMITS = [", "NOT_LIMITS = [")}, "declares no LIMITS"),
])
def test_a_kind_that_is_wrong_fails_by_name_when_loaded(kind_file, change, said):
    write_kind(kind_file, **change)
    with pytest.raises(BenchFailure) as e:
        kinds.load([KIND])
    assert f"kind '{KIND}'" in str(e.value) and said in str(e.value)


def test_an_unknown_kind_fails_by_name():
    with pytest.raises(BenchFailure) as e:
        kinds.load(["insert", "zz_no_such_kind"])
    assert "unknown operation kind 'zz_no_such_kind'" in str(e.value)
    assert "benchmarks/kinds/zz_no_such_kind.py" in str(e.value)
    with pytest.raises(BenchFailure):
        kinds.load(["../generator"])


def test_a_prepare_that_raises_and_a_judge_that_forgets_fail_by_name(kind_file):
    write_kind(kind_file, prepare=('    name, value = b"zzk-marker',
                                   '    raise KeyError("no CA key")\n'
                                   '    name, value = b"zzk-marker'))
    loaded = kinds.load([KIND])
    with pytest.raises(BenchFailure) as e:
        loaded[KIND].prepare({"clients": [FakeClient()], "seed": 1})
    assert f"kind '{KIND}': prepare raised KeyError('no CA key')" in str(e.value)
    write_kind(kind_file, judge=('"zzk_checked": len(acked),', ''))
    loaded = kinds.load([KIND])
    loaded[KIND].state = {"marker": (b"n", b"v")}
    with pytest.raises(BenchFailure) as e:
        loaded[KIND].judge([], {"clients": [FakeClient()]})
    assert "judge returned no ['zzk_checked']" in str(e.value)


@pytest.mark.parametrize("change,said", [
    ({"call": ('caller.new_call("zz_test_kind"', 'caller.new_call("zz_other"')},
     "returned a call of kind 'zz_other'"),
    ({"errors": ("call.errors = [None]", "call.errors = [None, None]")},
     "a call with 2 errors for 1 items"),
    ({"own": ('return [call, caller.builtin("insert", phase)]',
              'return [caller.builtin("insert", phase)]')},
     "returned no call of its own"),
    ({"update": ('caller.builtin("insert", phase)',
                 'caller.builtin("update", phase)')},
     "a mix with no existing keys"),
])
def test_a_call_the_harness_cannot_count_is_refused(kind_file, change, said):
    write_kind(kind_file, **change)
    mix = half_mix() | {"ops": {KIND: 1.0}}
    with pytest.raises(RuntimeError) as e:
        drive(mix, SEED, 1, loaded=kinds.load(mix["ops"]))
    assert f"kind '{KIND}'" in str(e.value) and said in str(e.value)


def test_a_kind_may_read_what_the_mix_preloads(kind_file):
    """No read share in the mix: the chooser is there all the same."""
    write_kind(kind_file, read=('caller.builtin("insert", phase)',
                                'caller.builtin("read", phase)'))
    mix = mix_of("ycsb-a", rehearse=True) | {"ops": {KIND: 1.0}, "callers": 2}
    callers, _ = drive(mix, SEED, 3, loaded=kinds.load(mix["ops"]))
    reads = [c for caller in callers for c in caller.calls if c.kind == "read"]
    assert len(reads) == 6 and all(c.beside == KIND for c in reads)
    assert all(0 <= k < mix["preload_records"] for c in reads for k in c.keynums)


def test_a_draw_counts_once(kind_file):
    """The kind's own items count; the insert beside them is judged (the
    history has it) and not counted again."""
    calls, _clients, _v = walk()
    own = [c for c in calls if c.kind == KIND]
    beside = [c for c in calls if c.beside]
    drawn = [c for c in calls if c.kind == "insert" and not c.beside]
    assert len(beside) == len(own) and {c.beside for c in beside} == {KIND}
    assert all(c.kind == "insert" for c in beside) and drawn
    run = runmod.Run.__new__(runmod.Run)
    w = {"window": calls}
    m = run.end_to_end(w)
    assert w["attempted"] == len(own) + 4 * len(drawn)    # batch 4
    assert w["ops"] == w["attempted"] and w["failed"] == 0
    assert sorted(map(id, w["counted"])) == sorted(map(id, own + drawn))
    assert m["committed_ops_per_s"][0] == w["ops"] / w["span_s"]
    h = judge.History(SEED, half_mix()["record"], calls)
    assert len(h.acked_keys) == 4 * (len(beside) + len(drawn))


# -- identities ---------------------------------------------------------------


def test_the_users_hand_out_the_clients():
    users = [FakeClient(), FakeClient(), FakeClient()]
    several = generator.assign(users, 8)
    assert all(several[i] is users[i % 3] for i in range(8))
    one = generator.assign(users[:1], 8)           # the accepted cells
    assert all(c is users[0] for c in one)
    callers, _ = drive(mix_of("load", True) | {"callers": 5}, SEED, 1,
                       clients=users)
    assert [c.api for c in callers] == [users[i % 3] for i in range(5)]
    # each client saw its own callers' calls and nothing else
    assert [len(u.log) for u in users] == [2, 2, 1]


def test_readback_goes_round_the_clients_and_writeonce_is_one_clients():
    users = [FakeClient(), FakeClient()]
    users[1].store = users[0].store
    calls = [generator.Call("insert", 0, list(range(6)), [4097] * 6, 0.0, 0.1,
                            [None] * 6)]
    h = judge.History(SEED, mix_of("load")["record"], calls)
    from benchmarks import ycsb

    for k in range(6):
        users[0].store[ycsb.key_name(SEED, k)] = h.value(k, 4097)
    out = judge.readback(h, users, list(range(6)), chunk=2)
    assert out["readback_checked"] == 6 and out["bad_reads"] == 0
    assert [sum(e[0] == "read_many" for e in u.log) for u in users] == [2, 1]
    # the owner's own second write: the only one write-once alone refuses
    assert judge.writeonce(users[0], 5) == {"writeonce_violations": 0}
    assert [e[0] for e in users[0].log if e[0] == "write_once"] == \
        ["write_once", "write_once"]
    assert not [e for e in users[1].log if e[0] == "write_once"]

    class Clobbers(FakeClient):                    # write-once broken
        def write_once(self, name, value):
            self.store[name] = value

    assert judge.writeonce(Clobbers(), 6)["writeonce_violations"] >= 1


# -- a mix that is wrong fails before any child starts ------------------------


def temp_manifest(tmp_path, mix: dict, users: int = 2):
    """A manifest with one more configuration (``users`` users) and cell;
    the files it names are the caller's to remove."""
    cfg = dict(runmod.load_json("benchmarks", "configs", "q4-rsa2048.json"),
               name="zz-test-u2", users=users)
    paths = {os.path.join(B, "configs", "zz-test-u2.json"): cfg,
             os.path.join(B, "traffic", "zz-test-half.json"): mix}
    for path, obj in paths.items():
        assert not os.path.exists(path)
        with open(path, "w") as f:
            json.dump(obj, f)
    m = runmod.load_manifest()
    m["configs"].append({"name": "zz-test-u2", "source": "test",
                         "file": "benchmarks/configs/zz-test-u2.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "zz-test-u2.half", "config": "zz-test-u2",
                           "traffic": "zz-test-half", "chips": 1, "why": "test"})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(m))
    return str(path), list(paths)


def bench(manifest: str, *argv, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload",
         "zz-test-u2.half", "--manifest", manifest, "--rehearse", *argv],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("change,users,argv,said", [
    ({"ops": {"insert": 0.5, "zz_no_such_kind": 0.5}}, 2, [],
     "FAILED: unknown operation kind 'zz_no_such_kind'"),
    ({}, 0, [], "configuration 'zz-test-u2' has no user"),
    ({"ops": {"insert": 0.5, "update": 0.5}}, 2, [],
     "updates or reads and preloads no record"),
    ({}, 2, ["--plant", "zz_no_such_plant"], "no plant 'zz_no_such_plant'"),
    ({"controls": {"zz_no_such_plant": {}}}, 2, [],
     "no plant 'zz_no_such_plant'"),
])
def test_a_mix_that_is_wrong_is_a_failed_line_and_no_child(
        tmp_path, change, users, argv, said):
    mix = dict(mix_of("load"), name="zz-test-half")
    mix.update(change)
    mix["rehearse"] = {**mix["rehearse"], **change}   # --rehearse reads these
    manifest, made = temp_manifest(tmp_path, mix, users)
    try:
        p = bench(manifest, *argv, timeout=60)
    finally:
        for path in made:
            os.remove(path)
    assert p.returncode != 0 and '"correct"' not in p.stdout
    assert said in p.stderr and p.stderr.startswith("FAILED: ")
    assert not os.path.exists(os.path.join(B, ".run", "zz-test-u2.half-1"))


# -- the walk: half insert, half the kind, a client per caller ---------------


@pytest.fixture
def half_cell(tmp_path, kind_file):
    mix = dict(mix_of("load"), name="zz-test-half",
               ops={"insert": 0.5, KIND: 0.5})
    for part in (mix, mix["rehearse"]):
        part["callers"] = 2          # of two users: caller 1 drives u02
        part["check_sample"] = 1 << 20   # every acknowledged key, under pow
        part["controls"] = dict(part["controls"], zzk_bent={"every": 3})
    manifest, made = temp_manifest(tmp_path, mix, users=2)
    try:
        yield manifest
    finally:
        for path in made:
            os.remove(path)


def result(p):
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.splitlines()[-1])


def test_the_walk_is_correct_and_with_the_kinds_plant_it_is_not(half_cell):
    p = bench(half_cell, "--seed", "2147483791", "--seconds", "5",
              "--trace", "0")
    r = result(p)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    names = list(r["compared"])
    assert names[-3:] == ["zzk_wrong_values", "zzk_checked", "zzk_marker_kept"]
    assert names[0] == "bad_reads" and "under_replicated" in names
    assert r["compared"]["zzk_wrong_values"] == [0, "<=", 0]
    assert r["compared"]["zzk_checked"][0] >= 1
    assert r["compared"]["zzk_checked"][1:] == [">=", 1]
    assert all(v[1:] in (["<=", 0], [">=", 1]) for v in r["compared"].values())
    assert p.stderr.strip().splitlines()[-1].startswith("compared: bad_reads=0<=0")
    assert " zzk_wrong_values=0<=0 " in p.stderr.splitlines()[-1]
    notes = [json.loads(l) for l in p.stdout.splitlines()[:-1]
             if l.startswith("{")]
    timing = next(n["timing"] for n in notes if "timing" in n)
    assert timing["kinds_prepare"] > 0       # inside set-up
    # Both users wrote, and u02's records are among those verified on the
    # disks: every draw of this mix inserts 16 records (the kind's own
    # insert beside its call), caller 1 drives u02's client and its warm
    # draw came back whole, so 16 keys at least are u02's; the sample holds
    # EVERY acknowledged key, so each was looked up on the replicas and its
    # writer's signature verified under the ring's key of its writer.
    disks = next(n["disks"] for n in notes if "disks" in n)
    assert disks["keys_counted"] >= 2 * 16
    assert disks["records_verified"] >= disks["keys_counted"]
    assert r["compared"]["bad_writer_signatures"] == [0, "<=", 0]
    assert r["compared"]["under_replicated"] == [0, "<=", 0]
    p = bench(half_cell, "--seed", "2147483792", "--seconds", "5",
              "--trace", "0", "--plant", "zzk_bent")
    r = result(p)
    assert r["correct"] is False
    assert r["compared"]["zzk_wrong_values"][0] >= 1
    assert "zzk_wrong_values=" in p.stderr.splitlines()[-1]
