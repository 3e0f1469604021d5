"""The yardstick's traffic, guarded in tier-1.

The cluster-free cases of ``benchmarks/tests/test_kinds.py`` (which only
a builder runs) stand here under their names, so that the driver counts
them and the same-seed digests of the built-in mixes run in every
check: a generator that issues other calls for a seed is another
benchmark.  They are the same functions — imported, not copied, so that
the two files cannot drift apart — but for one, which the first
committed kind outdated: the three load cells still open nothing new,
and the cell that names the CA kind opens exactly it.

Two more: the calls of ``ca-issue`` for a seed, under a recording fake
client, pinned by digest; and the kind's refusal, by name, of a program
that cannot send a replica's modexps to the sidecar.
"""

import argparse
import hashlib
import os
import random
import sys

import pytest

# ``benchmarks.run`` gives every process of a RUN the fixed RPC deadline
# by a default in ``os.environ``, set when it is imported.  Every xdist
# worker imports this file to collect it: the other files' tests keep the
# program's own default.
_ADAPTIVE = os.environ.get("BFTKV_ADAPTIVE_TIMEOUT")

from benchmarks import kinds, plants
from benchmarks import run as runmod
from benchmarks.harness import BenchFailure
from benchmarks.tests.test_kinds import (  # noqa: F401  (collected here)
    SEED,
    FakeClient,
    drive,
    kind_file,
    mix_of,
    test_a_call_the_harness_cannot_count_is_refused,
    test_a_draw_counts_once,
    test_a_kind_may_read_what_the_mix_preloads,
    test_a_kind_that_is_wrong_fails_by_name_when_loaded,
    test_a_mix_that_is_wrong_is_a_failed_line_and_no_child,
    test_a_prepare_that_raises_and_a_judge_that_forgets_fail_by_name,
    test_an_unknown_kind_fails_by_name,
    test_history_ignores_a_foreign_kinds_call,
    test_prepare_call_judge_are_reached_and_compared,
    test_readback_goes_round_the_clients_and_writeonce_is_one_clients,
    test_same_seed_same_kind_calls,
    test_the_kinds_plant_comes_out_incorrect,
    test_the_same_seed_issues_the_parents_calls,
    test_the_users_hand_out_the_clients,
)

if _ADAPTIVE is None:
    os.environ.pop("BFTKV_ADAPTIVE_TIMEOUT", None)

LOAD_CELLS = ("q4-rsa2048.load", "q10-rsa2048.load", "q4-rsa3072.load")


def opened(workload: str):
    return runmod.Run(argparse.Namespace(
        workload=workload, seed=1, rehearse=False, manifest=""))


def test_the_committed_mixes_open_nothing_new():
    """The three load cells: no kind, no limit, no plant beyond the
    built-ins, one client of ``u01`` for all callers."""
    for name in LOAD_CELLS:
        run = opened(name)
        assert run.kinds == {} and run.limits == []
        assert run.plants == plants.PLANTS
        assert run.config["users"] == 1
    cells = {w["name"] for w in runmod.load_manifest()["workloads"]}
    assert cells == {*LOAD_CELLS, "q10-ca2048.issue", "q10-ca4096.issue"}
    run = opened("q10-ca2048.issue")
    assert list(run.kinds) == ["ca_issue"] and run.config["users"] == 1
    assert run.limits == [("ca_certs_bad", "<=", 0),
                          ("ca_certs_checked", ">=", 1)]
    assert set(run.plants) == {*plants.PLANTS, "ca_bent_signature"}
    run = opened("q10-ca4096.issue")
    assert list(run.kinds) == ["ca_issue_w4096"] and run.config["users"] == 1
    assert run.limits == [("ca4096_certs_bad", "<=", 0),
                          ("ca4096_certs_checked", ">=", 1),
                          ("ca4096_certs_resigned", ">=", 1)]
    assert set(run.plants) == {*plants.PLANTS, "ca_bent_signature_w4096"}


class FakeCA(FakeClient):
    """The client facade of a deployment with a threshold CA: records the
    deal and every TBS, and signs with nothing."""

    def distribute(self, caname, key):
        self.log.append(("distribute", caname, key.n))

    def sign(self, caname, tbs, algo, hash_name):
        self.log.append(("sign", caname, tbs, int(algo), hash_name))
        return hashlib.sha256(tbs).digest()


# sha256 over every (caller, kind, keynums, versions, beside) and over what
# the client was sent, in order: taken by this very drive when the kind was
# entered (PR 33, refused for its yardstick; PR 34).
PINNED = "07b971840e121d669815ceeeb91df04a711e88d01a56048761472cce7297c993"


def test_the_same_seed_issues_the_same_certificates():
    mix = mix_of("ca-issue")
    config = runmod.load_json("benchmarks", "configs", "q10-ca2048.json")
    loaded = kinds.load(mix["ops"])
    clients = [FakeCA()]
    loaded["ca_issue"].prepare({"clients": clients, "config": config,
                                "mix": mix, "seed": SEED, "rehearse": True})
    assert [e[:2] for e in clients[0].log] == [("distribute", f"ca-{SEED}")]
    callers, _ = drive(mix, SEED, 2, clients=clients, loaded=loaded)
    h, n = hashlib.sha256(), 0
    for c in callers:
        assert [x.kind for x in c.calls] == ["ca_issue", "insert"] * 2
        for call in c.calls:
            assert call.errors == [None] and len(call.keynums) == 1
            h.update(repr((c.idx, call.kind, call.keynums, call.versions,
                           call.beside)).encode())
            n += 1
    for sent in clients[0].log:
        h.update(repr(sent).encode())
    # one client for all sixteen callers: the deal, then a sign and a
    # write a draw
    assert (n, len(clients[0].log)) == (64, 1 + 64)
    assert h.hexdigest() == PINNED
    # every certificate a call got back is the judge's
    calls = [x for c in callers for x in c.calls]
    numbers = loaded["ca_issue"].judge(calls, {})
    assert numbers == {"ca_certs_bad": 32, "ca_certs_checked": 32}
    tbs = [x.values[0][0] for x in calls if x.kind == "ca_issue"]
    assert len(set(tbs)) == 32 and all(len(t) == 600 for t in tbs)
    rng = random.Random(f"{SEED}|caller|0")
    rng.random()  # the draw of the kind
    assert tbs[0] == rng.randbytes(600)



@pytest.mark.parametrize("stub", ["no_function", "no_chain"])
def test_the_kind_refuses_a_program_without_the_route(monkeypatch, capsys, stub):
    """A program that cannot send a replica's modexps to the sidecar (the
    parent of the PR that entered the cell: no ``remote_route``), or has
    no device chain for the fragment class, would run the cell on ten
    daemons' host ``pow``.  The kind refuses it by name when it is
    loaded: a ``FAILED:`` line, exit 2, no run directory, no child."""
    from bftkv_tpu.ops import modexp

    if stub == "no_function":
        monkeypatch.delattr(modexp, "remote_route")
    else:
        monkeypatch.setattr(modexp, "remote_route", lambda bits, exp_bits: False)
    monkeypatch.delitem(sys.modules, "benchmarks.kinds.ca_issue", raising=False)
    with pytest.raises(BenchFailure) as refused:
        kinds.load(["ca_issue"])
    assert "kind 'ca_issue'" in str(refused.value)
    assert "in the replica, on the host" in str(refused.value)
    started = []
    monkeypatch.setattr(runmod.harness, "Cluster",
                        lambda *a, **kw: started.append(a))
    monkeypatch.delitem(sys.modules, "benchmarks.kinds.ca_issue", raising=False)
    rc = runmod.main(["--workload", "q10-ca2048.issue", "--seed", "3000000019",
                      "--rehearse"])
    assert rc == 2 and started == []
    assert capsys.readouterr().err.startswith("FAILED: kind 'ca_issue' ")
    # the load cells name no kind and ask nothing
    assert opened("q10-rsa2048.load").kinds == {}


def test_the_4096_bit_kind_issues_the_calls_of_ca_issue():
    """``ca_issue_w4096`` draws exactly what ``ca_issue`` draws for a seed
    — the same TBS, the same stored record — under its own name, and its
    judge takes every certificate and re-signs a seeded sample."""
    config = runmod.load_json("benchmarks", "configs", "q10-ca4096.json")
    assert config["threshold_ca"]["key_bits"] == 4096
    assert config["environment"]["BFTKV_CA_BITS"] == "4096"
    logs, calls = {}, {}
    for traffic, kind in (("ca-issue", "ca_issue"),
                          ("ca-issue-w4096", "ca_issue_w4096")):
        mix = mix_of(traffic)
        loaded = kinds.load(mix["ops"])
        clients = [FakeCA()]
        loaded[kind].prepare({"clients": clients, "config": config,
                              "mix": mix, "seed": SEED, "rehearse": True})
        callers, _ = drive(mix, SEED, 2, clients=clients, loaded=loaded)
        logs[kind] = clients[0].log
        calls[kind] = [(c.idx, x.kind, x.keynums, x.beside)
                       for c in callers for x in c.calls]
        judged = [x for c in callers for x in c.calls]
        numbers = loaded[kind].judge(judged, {})
    assert logs["ca_issue"] == logs["ca_issue_w4096"]
    rename = {"ca_issue": "ca_issue_w4096"}
    assert [(i, rename.get(k, k), n, rename.get(b, b))
            for i, k, n, b in calls["ca_issue"]] == calls["ca_issue_w4096"]
    # the fake signs with nothing: every certificate is bad
    assert numbers == {"ca4096_certs_bad": 32, "ca4096_certs_checked": 32,
                       "ca4096_certs_resigned": 32}


@pytest.mark.parametrize("stub", ["no_function", "no_wide_chain"])
def test_the_4096_bit_kind_refuses_a_program_without_the_route(
        monkeypatch, capsys, stub):
    """The parent of the PR that entered ``q10-ca4096``: its
    ``remote_route`` holds a 2,048-bit fragment and no 4,096-bit one.  The
    kind refuses such a program by name when it is loaded: a ``FAILED:``
    line, exit 2, no child."""
    from bftkv_tpu.ops import modexp

    if stub == "no_function":
        monkeypatch.delattr(modexp, "remote_route")
    else:
        monkeypatch.setattr(modexp, "remote_route",
                            lambda bits, exp_bits: bits <= 2048)
    for name in ("ca_issue", "ca_issue_w4096"):
        monkeypatch.delitem(sys.modules, "benchmarks.kinds." + name,
                            raising=False)
    with pytest.raises(BenchFailure) as refused:
        kinds.load(["ca_issue_w4096"])
    assert "kind 'ca_issue_w4096'" in str(refused.value)
    assert "4,096-bit CA key in the replica" in str(refused.value)
    started = []
    monkeypatch.setattr(runmod.harness, "Cluster",
                        lambda *a, **kw: started.append(a))
    for name in ("ca_issue", "ca_issue_w4096"):
        monkeypatch.delitem(sys.modules, "benchmarks.kinds." + name,
                            raising=False)
    rc = runmod.main(["--workload", "q10-ca4096.issue", "--seed",
                      "3000000019", "--rehearse"])
    assert rc == 2 and started == []
    assert capsys.readouterr().err.startswith("FAILED: kind 'ca_issue_w4096' ")
