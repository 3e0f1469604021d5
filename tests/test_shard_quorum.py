"""Keyed quorum routing: disjoint-clique shards, HRW bucket routing,
ownership, caches, and the choose_quorum generation guard.

All graph-level (FakeNode) tests — no crypto, so the whole file runs in
well under a second.  Topology: two 4-cliques of quorum servers
(a01-a04, b01-b04), eight storage-only rw nodes, and a user u01 who
signs every server and rw node.
"""

import threading

import pytest

from bftkv_tpu import quorum as q
from bftkv_tpu.graph import Graph
from bftkv_tpu.quorum.wotqs import ROUTE_BUCKETS, WotQS
from tests.test_graph_quorum import FakeNode


def mk_shard_universe(n_per_clique=4, n_rw=8, cliques=("a", "b")):
    nodes = {}
    nid = iter(range(1, 1000))

    def add(name, address="", uid=""):
        n = FakeNode(next(nid), name, address=address, uid=uid)
        nodes[name] = n
        return n

    for grp in cliques:
        for i in range(1, n_per_clique + 1):
            add(f"{grp}{i:02d}", address=f"http://{grp}{i:02d}")
    for i in range(1, n_rw + 1):
        add(f"rw{i:02d}", address=f"http://rw{i:02d}")
    add("u01", uid="u01@example.test")

    def sign(signer, signee):
        nodes[signee].signer_ids.add(nodes[signer].id)

    for grp in cliques:
        names = [f"{grp}{i:02d}" for i in range(1, n_per_clique + 1)]
        for s1 in names:
            for s2 in names:
                if s1 != s2:
                    sign(s1, s2)
    for grp in cliques:
        for i in range(1, n_per_clique + 1):
            sign("u01", f"{grp}{i:02d}")
    for i in range(1, n_rw + 1):
        sign("u01", f"rw{i:02d}")
        for grp in cliques:
            for j in range(1, n_per_clique + 1):
                sign(f"rw{i:02d}", f"{grp}{j:02d}")
    return nodes


def build(nodes, self_name, order=None):
    g = Graph()
    ordered = (
        [nodes[n] for n in order] if order else list(nodes.values())
    )
    g.add_nodes(ordered)
    g.set_self_nodes([nodes[self_name]])
    return g


@pytest.fixture()
def universe():
    return mk_shard_universe()


def shard_names(qs, universe):
    byid = {n.id: name for name, n in universe.items()}
    topo = qs._topology()
    return [sorted(byid[n.id] for n in c.nodes) for c in topo.shards]


# -- enumeration ----------------------------------------------------------


def test_two_cliques_enumerated(universe):
    qs = WotQS(build(universe, "u01"))
    groups = shard_names(qs, universe)
    assert sorted(map(tuple, groups)) == [
        tuple(f"a{i:02d}" for i in range(1, 5)),
        tuple(f"b{i:02d}" for i in range(1, 5)),
    ]
    assert qs.shard_count() == 2


def test_users_never_form_shards(universe):
    # u01 <-> nothing bidirectionally except... give u01 mutual edges
    # with a whole clique: still no shard membership (no address).
    for i in range(1, 5):
        universe["u01"].signer_ids.add(universe[f"a{i:02d}"].id)
    qs = WotQS(build(universe, "u01"))
    for grp in shard_names(qs, universe):
        assert "u01" not in grp


def test_single_clique_degenerates(universe):
    solo = {
        name: n
        for name, n in universe.items()
        if not name.startswith("b")
    }
    qs = WotQS(build(solo, "u01"))
    assert qs.shard_count() == 1
    assert qs.shard_of(b"x") is None
    assert qs.owns(b"anything")
    assert qs.owned_buckets() is None
    assert qs.shard_buckets() == [ROUTE_BUCKETS]
    # Bit-for-bit: the keyed API returns the SAME memoized object the
    # unkeyed call returns.
    qa = qs.choose_quorum(q.AUTH)
    assert qs.choose_quorum_for(b"x", q.AUTH) is qa


def test_local_trust_edges_do_not_shape_shards(universe):
    """server_trust_rw-style local edges exist in ONE view only; letting
    them into clique enumeration would give that view a different route
    table than the rest of the fleet.  a01's local a01->rw edges +
    rw->a01 certificate edges look bidirectional in a01's graph — the
    enumeration must still produce the pure server cliques."""
    g = build(universe, "a01")
    baseline = [sorted(n.id for n in c.nodes)
                for c in g.get_disjoint_cliques()]
    g.add_local_edges(
        universe["a01"].id,
        [universe[f"rw{i:02d}"].id for i in range(1, 9)],
    )
    got = [sorted(n.id for n in c.nodes) for c in g.get_disjoint_cliques()]
    assert got == baseline
    # An operator redundantly listing a CLIQUE-MATE in localtrust must
    # not demote the certificate-borne edge either: the clique survives.
    g.add_local_edges(universe["a01"].id, [universe["a02"].id])
    got = [sorted(n.id for n in c.nodes) for c in g.get_disjoint_cliques()]
    assert got == baseline


# -- routing --------------------------------------------------------------


def test_route_table_covers_every_bucket(universe):
    qs = WotQS(build(universe, "u01"))
    counts = qs.shard_buckets()
    assert sum(counts) == ROUTE_BUCKETS
    assert len(counts) == 2
    assert all(c > 0 for c in counts)
    # HRW over 256 buckets / 2 cliques: grossly unbalanced would mean a
    # broken hash, not bad luck.
    assert max(counts) / min(counts) < 2.0


def test_routing_agrees_across_views_and_orders(universe):
    names = list(universe)
    qs1 = WotQS(build(universe, "u01", order=names))
    qs2 = WotQS(build(universe, "a01", order=list(reversed(names))))
    qs3 = WotQS(build(universe, "rw01", order=sorted(names)))
    for i in range(64):
        x = b"var/%d" % i
        assert qs1.shard_of(x) == qs2.shard_of(x) == qs3.shard_of(x)


def test_ownership_matches_route(universe):
    qs_a = WotQS(build(universe, "a01"))
    qs_b = WotQS(build(universe, "b01"))
    a_idx = qs_a.my_shard()
    b_idx = qs_b.my_shard()
    assert a_idx is not None and b_idx is not None and a_idx != b_idx
    hits = {True: 0, False: 0}
    for i in range(64):
        x = b"own/%d" % i
        owner = qs_a.shard_of(x)
        assert qs_a.owns(x) == (owner == a_idx)
        assert qs_b.owns(x) == (owner == b_idx)
        hits[qs_a.owns(x)] += 1
    assert hits[True] and hits[False]  # both outcomes actually exercised


def test_complement_partition_balanced(universe):
    qs = WotQS(build(universe, "rw01"))
    topo = qs._topology()
    per_shard = [0, 0]
    for nid, idx in topo.assign.items():
        per_shard[idx] += 1
    assert per_shard == [4, 4]
    # every rw node got an assignment, no clique member did
    assert set(topo.assign) & set(topo.member) == set()
    mine = qs.my_shard()
    owned = qs.owned_buckets()
    assert owned is not None
    assert owned == {
        b for b in range(ROUTE_BUCKETS) if topo.table[b] == mine
    }


def test_keyed_quorum_stays_inside_shard(universe):
    qs = WotQS(build(universe, "u01"))
    topo = qs._topology()
    for i in range(16):
        x = b"q/%d" % i
        idx = qs.shard_of(x)
        allowed = {n.id for n in topo.shards[idx].nodes} | {
            nid for nid, a in topo.assign.items() if a == idx
        }
        for rw in (q.READ | q.AUTH, q.AUTH | q.PEER, q.WRITE, q.READ):
            quorum = qs.choose_quorum_for(x, rw)
            got = {n.id for qc in quorum.qcs for n in qc.nodes}
            assert got, (i, rw)
            assert got <= allowed, (i, rw, got - allowed)


def test_keyed_cache_and_generation(universe):
    g = build(universe, "u01")
    qs = WotQS(g)
    x = b"cache/1"
    q1 = qs.choose_quorum_for(x, q.WRITE)
    assert qs.choose_quorum_for(x, q.WRITE) is q1  # memoized
    g.remove_nodes([universe["rw08"]])  # bumps generation
    q2 = qs.choose_quorum_for(x, q.WRITE)
    assert q2 is not q1
    assert universe["rw08"].id not in {
        n.id for qc in q2.qcs for n in qc.nodes
    }


def test_route_metric_closed_enum(universe):
    from bftkv_tpu.metrics import registry as metrics

    # the registry is the worker's: an earlier file's wider universe
    # (48 shards) must not be counted against this one's two
    metrics.reset()
    qs = WotQS(build(universe, "u01"))
    for i in range(32):
        qs.choose_quorum_for(b"m/%d" % i, q.READ)
    snap = metrics.snapshot()
    labels = [
        k
        for k in snap
        if k.startswith("quorum.route.shard{")
    ]
    assert labels and len(labels) <= qs.shard_count()


# -- the choose_quorum generation-guard race (wotqs.py:207-235) -----------


def test_choose_quorum_generation_race():
    """A quorum built from the pre-mutation graph must never be served
    under the post-mutation generation: the clique walk completes on
    the old graph, membership mutates before the builder can memoize,
    and the guarded store has to DROP the stale result (wotqs.py's
    choose_quorum store guard — implemented but previously untested)."""
    # 6-node clique: still a valid quorum (f=1) after one node leaves,
    # so the post-mutation rebuild is a real quorum, not a degenerate
    # empty one.
    nodes = mk_shard_universe(n_per_clique=6, n_rw=8, cliques=("a",))
    g = build(nodes, "a01")
    qs = WotQS(g)
    started = threading.Event()
    proceed = threading.Event()
    real = g.get_cliques

    def stale_get_cliques(sid, distance):
        # Snapshot the PRE-mutation cliques, then let the mutation land
        # before returning — the builder finishes its construction from
        # a world that no longer exists.
        res = real(sid, distance)
        started.set()
        assert proceed.wait(5), "mutator never released the builder"
        return res

    g.get_cliques = stale_get_cliques
    box = {}

    def build_quorum():
        box["q"] = qs.choose_quorum(q.AUTH)

    t = threading.Thread(target=build_quorum)
    t.start()
    assert started.wait(5)
    # Membership mutation lands while the builder holds the old clique
    # list: a02 leaves, generation bumps.
    g.remove_nodes([nodes["a02"]])
    proceed.set()
    t.join(5)
    g.get_cliques = real
    stale = box["q"]
    assert nodes["a02"].id in {
        n.id for qc in stale.qcs for n in qc.nodes
    }, "builder should have constructed from the pre-mutation graph"
    # The next call must rebuild from the mutated graph — serving the
    # stale quorum out of the memo would resurrect a02 post-removal.
    fresh = qs.choose_quorum(q.AUTH)
    assert fresh is not stale
    assert fresh.qcs, "5-node clique must still form a quorum"
    assert nodes["a02"].id not in {
        n.id for qc in fresh.qcs for n in qc.nodes
    }


def test_live_generation_churn_under_writers(universe):
    """The autopilot's steady state: graph generations keep bumping
    (spare admission, revocations) WHILE writer threads select keyed
    quorums.  No quorum may ever be served under the wrong generation:
    whenever a writer observes a quiescent generation around its call
    (same before and after), the returned quorum must reflect exactly
    that generation's membership — here, whether rw08 exists."""
    g = build(universe, "u01")
    qs = WotQS(g)
    rw08 = universe["rw08"]
    # The route table derives from the CLIQUES alone, so it is stable
    # under rw (complement) churn; rw08's seat is its round-robin slot,
    # also stable whenever it is present.  Keys routed to that shard
    # must include rw08 in their WRITE complement exactly when the
    # generation they were served under had rw08 in the graph.
    rw_idx = qs.shard_index_of(rw08.id)
    assert rw_idx is not None
    keys = []
    i = 0
    while len(keys) < 8:
        x = b"churn/%d" % i
        i += 1
        if qs.shard_of(x) == rw_idx:
            keys.append(x)
    stop = threading.Event()
    present = {}  # generation -> rw08 in the graph at that generation
    lock = threading.Lock()
    violations: list = []

    def record(gen: int, has: bool) -> None:
        with lock:
            present[gen] = has

    record(g.generation, True)

    def churn():
        for _ in range(60):
            g.remove_nodes([rw08])
            record(g.generation, False)
            g.add_peers([rw08])
            record(g.generation, True)
        stop.set()

    def writer(wi: int):
        i = 0
        while not stop.is_set():
            i += 1
            x = keys[(wi + i) % len(keys)]
            gen_before = g.generation
            quorum = qs.choose_quorum_for(x, q.WRITE)
            topo_n = qs.shard_count()
            gen_after = g.generation
            if gen_before != gen_after:
                continue  # mutation mid-call: nothing to assert
            with lock:
                expect = present.get(gen_before)
            if expect is None:
                continue
            got = any(
                n.id == rw08.id
                for qc in quorum.qcs
                for n in qc.nodes
            )
            if got != expect:
                violations.append(
                    (wi, gen_before, expect, got)
                )
            if topo_n != 2:
                violations.append((wi, gen_before, "shards", topo_n))

    threads = [
        threading.Thread(target=writer, args=(wi,), daemon=True)
        for wi in range(4)
    ]
    churner = threading.Thread(target=churn, daemon=True)
    for t in threads:
        t.start()
    churner.start()
    churner.join(30)
    stop.set()
    for t in threads:
        t.join(10)
    assert not violations, violations[:5]
    # and the memos settled on the FINAL generation's world
    final = qs.choose_quorum_for(keys[0], q.WRITE)
    assert any(
        n.id == rw08.id for qc in final.qcs for n in qc.nodes
    )


def test_keyed_topology_generation_race(universe):
    """Same guard discipline for the shard topology memo: a routing
    table computed from the pre-mutation graph must not survive the
    mutation, or keys would keep routing to a dissolved clique."""
    g = build(universe, "u01")
    qs = WotQS(g)
    started = threading.Event()
    proceed = threading.Event()
    real = g.get_disjoint_cliques

    def stale_disjoint(min_size=4):
        res = real(min_size)
        started.set()
        assert proceed.wait(5)
        return res

    g.get_disjoint_cliques = stale_disjoint
    box = {}
    t = threading.Thread(
        target=lambda: box.setdefault("n", qs.shard_count())
    )
    t.start()
    assert started.wait(5)
    for name in ("b01", "b02", "b03", "b04"):
        g.remove_nodes([universe[name]])  # the b-clique dissolves
    proceed.set()
    t.join(5)
    g.get_disjoint_cliques = real
    assert box["n"] == 2  # the racer built from the old world...
    assert qs.shard_count() == 1  # ...but the memo did not keep it
