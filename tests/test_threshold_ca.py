"""The threshold CA on the served path (``q10-ca2048``'s tier-1 guard).

What the deployment needs, at small sizes on the CPU, held to the plain
reference (``benchmarks/kinds/ca_issue_reference.py``: nothing of the
program imported there):

- a threshold signature equals the PKCS#1 v1.5 signature of the undealt
  key byte for byte — with the servers' modexps in-process and with the
  modexp domain installed against a sidecar service, all servers up and
  one stopped (two rounds);
- the pow chain with an exponent class of its own equals ``pow``, and
  answers None one bit past the class;
- no first-level fragment of a 2,048-bit key exceeds the class the
  sidecar builds for a declared CA;
- a daemon's concurrent DISTSIGN handlers leave as ONE sidecar request,
  and fragment exponents never leave on a channel that carries no keys;
- the sidecar's dispatcher groups by (modulus class, exponent class),
  counts its crossover in work, and never compiles an undeclared class.
"""

from __future__ import annotations

import random
import threading
import time

import pytest

from benchmarks.kinds import ca_issue_reference as reference
from bftkv_tpu.cmd import verify_sidecar as vs
from bftkv_tpu.crypto import rsa
from bftkv_tpu.crypto.remote_verify import RemoteModexpDomain
from bftkv_tpu.crypto.threshold import ThresholdAlgo
from bftkv_tpu.crypto.threshold import rsa as trsa
from bftkv_tpu.metrics import registry as metrics
from bftkv_tpu.ops import dispatch, rns
from bftkv_tpu.ops.modexp import BatchModExp

from cluster_utils import modexp_route, start_cluster

SEED = 2147483659
CA_BITS = 512


def counter(name: str) -> float:
    return metrics.snapshot().get(name, 0)


def ca_key(seed: int = SEED):
    key = reference.rsa_keygen(random.Random(f"{seed}|ca"), CA_BITS)
    return key, rsa.PrivateKey(n=key.n, e=key.e, d=key.d, p=key.p, q=key.q)


def tbs_of(i: int) -> bytes:
    return random.Random(f"{SEED}|tbs|{i}").randbytes(600)


# -- (1) signature == reference, both routes ---------------------------------


@pytest.fixture(scope="module", params=["local", "sidecar"])
def dealt(request, tmp_path_factory):
    """4 quorum servers (f = 1: a (3,4) deal) with a seeded 512-bit CA
    key dealt to them, the servers' modexps on ``request.param``."""
    with modexp_route(request.param, tmp_path_factory.mktemp("sc")) as domain:
        cluster = start_cluster(n_servers=4, n_users=1, n_rw=4, bits=1024)
        try:
            key, program_key = ca_key()
            cluster.clients[0].distribute("ca-t1", program_key)
            yield cluster, key, domain
        finally:
            cluster.stop()


@pytest.mark.parametrize("i", range(8))
def test_certificate_equals_the_undealt_keys_signature(dealt, i):
    cluster, key, domain = dealt
    sent = counter("modexp.remote")
    rounds = counter("client.dist_sign.round.count")
    sig = cluster.clients[0].dist_sign(
        "ca-t1", tbs_of(i), ThresholdAlgo.RSA, "sha256"
    )
    assert sig == reference.rsa_sign(tbs_of(i), key)
    assert reference.rsa_verify(tbs_of(i), sig, key.n, key.e)
    # all four answer: ONE round, one first-level fragment a server —
    # through the sidecar where the domain is installed (the client's
    # multicast returns at the third answer: the fourth may still run)
    assert counter("client.dist_sign.round.count") - rounds == 1
    if domain is not None:
        assert counter("modexp.remote") - sent >= 3
        assert counter("modexp.local_secret") == 0


# -- (2) one server stopped: two rounds, same bytes --------------------------


@pytest.mark.parametrize("route", ["local", "sidecar"])
def test_one_server_down_takes_two_rounds_and_signs_the_same(route, tmp_path):
    with modexp_route(route, tmp_path):
        cluster = start_cluster(n_servers=4, n_users=1, n_rw=4, bits=1024)
        try:
            key, program_key = ca_key(SEED + 1)
            cli = cluster.clients[0]
            cli.distribute("ca-t2", program_key)
            cluster.servers[2].tr.stop()
            rounds = counter("client.dist_sign.round.count")
            sig = cli.dist_sign("ca-t2", tbs_of(9), ThresholdAlgo.RSA, "sha256")
            assert counter("client.dist_sign.round.count") - rounds == 2
            assert sig == reference.rsa_sign(tbs_of(9), key)
        finally:
            cluster.stop()


def test_the_reference_dealer_pins_the_wire_semantics():
    """The plain dealer and combiner agree with the program's tree: any
    k servers sign, fewer do not, and a server's share holds the indices
    the program's holds."""
    rng = random.Random(SEED)
    key, _ = ca_key()
    shares = reference.deal(key.d, 3, 4, rng)
    em = trsa.emsa_encode(b"\x30", b"\x01" * 40, 64)
    want = pow(em, key.d, key.n)
    for up in ({0, 1, 2, 3}, {0, 1, 3}, {1, 2, 3}, {0, 2, 3}):
        assert reference.combine(em, key.n, shares, up, 4, 3) == want
    with pytest.raises(ValueError):
        reference.combine(em, key.n, shares, {0, 3}, 4, 3)
    tree = trsa.make_key_tree(key.d, 0, 4, 3, random.Random(1).randrange)
    for i in range(4):
        held: dict = {}
        trsa.collect_keys(tree, i, held)
        assert sorted(held) == sorted(shares[i])


# -- (3) the pow chain's exponent class --------------------------------------


def seeded_rows(bits: int, exp_bits: int, rows: int = 64):
    rng = random.Random(f"{SEED}|pow|{bits}|{exp_bits}")
    mods = [rng.getrandbits(bits) | (1 << (bits - 1)) | 1 for _ in range(3)]
    mods = [mods[i % 3] for i in range(rows)]
    bases = [rng.getrandbits(bits) for _ in mods]
    exps = [rng.getrandbits(exp_bits) | (1 << (exp_bits - 1)) for _ in mods]
    return bases, exps, mods


@pytest.mark.parametrize("bits", [512, 1024, 2048])
@pytest.mark.parametrize("twice", [False, True], ids=["1x", "2x+4"])
def test_pow_chain_with_an_exponent_class_of_its_own(bits, twice):
    exp_bits = 2 * bits + 4 if twice else bits
    cls = rns.exp_class(bits, exp_bits)
    assert cls == (rns.long_exp_bits(bits) if twice else bits)
    assert rns.chains(bits, exp_bits).pow
    bases, exps, mods = seeded_rows(bits, exp_bits)
    got = rns.power_mod_rns(bases, exps, mods, n_bits=bits, exp_bits=cls)
    assert got == [pow(b, e, m) for b, e, m in zip(bases, exps, mods)]


@pytest.mark.parametrize("bits", [512, 1024, 2048])
def test_an_exponent_one_bit_past_the_class_returns_none(bits):
    top = rns.long_exp_bits(bits)
    assert top == 2 * bits + 64
    assert rns.exp_class(bits, top) == top
    assert rns.exp_class(bits, top + 1) is None
    assert not rns.chains(bits, top + 1).pow
    for cls, exp_bits in ((bits, bits + 1), (top, top + 1)):
        bases, exps, mods = seeded_rows(bits, exp_bits, rows=2)
        assert rns.power_mod_rns(
            bases, exps, mods, n_bits=bits, exp_bits=cls
        ) is None
    # no class of that size at this width
    assert rns.power_mod_rns([2], [3], [7], n_bits=bits, exp_bits=top + 8) \
        is None


def test_the_program_is_named_after_both_classes():
    assert rns._jitted_pow(32, 512, False).__wrapped__.__name__ == \
        "rns_pow_512"
    assert rns._jitted_pow(32, 512, False, 1088).__wrapped__.__name__ == \
        "rns_pow_512_e1088"


@pytest.mark.parametrize("rows", [5, 70], ids=["bucket64", "bucket128"])
def test_the_fused_chain_takes_the_longer_class(monkeypatch, rows):
    """What one TPU chip runs for the longer class
    (``_auto_backend``'s ``long_exp``): the whole chain as one Pallas kernel under the class's program
    name, its step count the staged window array's — interpreted here,
    at a small width and two buckets (two programs, one cached call
    each)."""
    monkeypatch.setenv("BFTKV_RNS_POW_BACKEND", "pallas")
    monkeypatch.setattr(
        rns, "_PALLAS_STATUS", {"pow": "unused", "verify": "unused"}
    )
    bits = 256
    top = rns.long_exp_bits(bits)
    bases, exps, mods = seeded_rows(bits, top - 3, rows=rows)
    got = rns.power_mod_rns(bases, exps, mods, n_bits=bits, exp_bits=top)
    assert got == [pow(b, e, m) for b, e, m in zip(bases, exps, mods)]
    assert rns.pallas_status()["pow"] == "ok"  # no quiet retreat


@pytest.mark.parametrize(
    "mode,platform,n_devices,want",
    [
        ("auto", "tpu", 1, True),    # measured: PERF.md §6, PR 33
        ("auto", "tpu", 4, False),   # the sharded XLA chain's
        ("auto", "cpu", 1, False),   # interpret mode is no backend
        ("xla", "tpu", 1, False),
        ("pallas", "cpu", 1, True),
    ],
)
def test_which_chain_the_longer_class_rides(
    monkeypatch, mode, platform, n_devices, want
):
    monkeypatch.setenv("BFTKV_RNS_POW_BACKEND", mode)
    monkeypatch.setattr(rns.jax, "default_backend", lambda: platform)
    monkeypatch.setattr(rns.jax, "devices", lambda: ["chip"] * n_devices)
    assert rns._use_pallas("BFTKV_RNS_POW_BACKEND", long_exp=True) is want
    # the rows' own class keeps the rule it had
    assert rns._use_pallas("BFTKV_RNS_POW_BACKEND") is (mode == "pallas")


# -- (4) no first-level fragment exceeds the declared class ------------------


@pytest.mark.parametrize("part", range(4))
def test_first_level_fragments_of_a_2048_bit_key_fit_the_class(part):
    """50 seeded (., 10) deals a case, 200 in all: the ten first-level
    fragments of a full-width 2,048-bit d."""
    rng = random.Random(f"{SEED}|deal|{part}")
    top = rns.long_exp_bits(2048)
    widest = 0
    for _ in range(50):
        d = rng.getrandbits(2048) | (1 << 2047)
        frags = trsa._split_key(d, 10, rng.randrange)
        assert sum(frags) == d
        widest = max(widest, max(abs(f).bit_length() for f in frags))
    assert 4090 <= widest <= 2 * 2048 + 4 + 1 <= top == 4160


# -- (5) one request a daemon; no keys on a key-free channel ----------------


class FakeChannel:
    """Where ``SidecarChannel`` stands: records the requests and answers
    them with ``pow``."""

    def __init__(self, carries_keys: bool):
        self.carries_keys = carries_keys
        self.requests: list[list] = []

    def tripped(self) -> bool:
        return False

    def trip(self) -> None:
        raise AssertionError("an honest channel was tripped")

    def request(self, op: int, payload: bytes):
        assert op == vs.OP_MODEXP
        items = vs.decode_modexp_request(payload)
        self.requests.append(items)
        out = b"".join(
            len(v).to_bytes(8, "big") + v
            for v in (vs._int_bytes(pow(b, e, m)) for b, e, m in items)
        )
        return vs.ST_OK, out


@pytest.mark.parametrize("carries_keys", [True, False])
def test_eight_handlers_leave_as_one_request_or_not_at_all(carries_keys):
    key, _ = ca_key()
    chan = FakeChannel(carries_keys)
    dispatch.install_modexp(
        dispatch.ModexpDispatcher(
            remote=RemoteModexpDomain(channel=chan, spot_rate=0),
            calibrate=False,
            max_wait=0.25,
        )
    )
    kept = counter("modexp.local_secret")
    rng = random.Random(SEED)
    pairs = [(rng.getrandbits(500), rng.getrandbits(1030)) for _ in range(8)]
    got: list = [None] * 8
    gate = threading.Barrier(8)

    def handler(i: int) -> None:
        gate.wait()
        # what RSAThreshold.sign does with the one fragment a healthy
        # request holds: under min_batch, and still no host pow here
        got[i] = BatchModExp.shared().modexp([pairs[i]], key.n)

    try:
        threads = [threading.Thread(target=handler, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
    finally:
        dispatch.uninstall_modexp()
    assert got == [[pow(b, e, key.n)] for b, e in pairs]
    if carries_keys:
        assert [len(r) for r in chan.requests] == [8]
        assert sorted(chan.requests[0]) == sorted(
            (b, e, key.n) for b, e in pairs
        )
        assert counter("modexp.local_secret") == kept
    else:
        assert chan.requests == []
        assert counter("modexp.local_secret") - kept == 8


# -- (6) the sidecar's dispatcher: classes, work, nothing compiled ----------


def fragment_items(n: int, exp_bits: int = 4100, bits: int = 2048):
    bases, exps, mods = seeded_rows(bits, exp_bits, rows=n)
    return list(zip(bases, exps, mods))


@pytest.fixture
def device_dispatcher(monkeypatch):
    """A ``ModexpDispatcher`` as a sidecar on a device has it (crossover
    18 verify items), its launches recorded and answered by ``pow``."""
    launched: list[tuple] = []

    def launch(bases, exps, mods, *, n_bits, exp_bits, defer=False, **_k):
        launched.append((n_bits, exp_bits, len(mods)))
        vals = [pow(b, e, m) for b, e, m in zip(bases, exps, mods)]
        return rns.DeferredModexp(lambda: vals) if defer else vals

    monkeypatch.setattr(rns, "power_mod_rns", launch)
    d = dispatch.ModexpDispatcher(calibrate=False, device_threshold=18)
    return d, launched


def test_an_undeclared_class_is_host_tier_and_counted_never_compiled(
    device_dispatcher,
):
    d, launched = device_dispatcher
    d.warm_rows = frozenset({1024})  # the identities' CRT halves alone
    items = fragment_items(3)
    unwarmed, host = counter("sidecar.unwarmed_width"), counter("modexp.host")
    assert d._run_batch(items) == [pow(*it) for it in items]
    assert launched == []
    assert counter("sidecar.unwarmed_width") - unwarmed == 3
    assert counter("modexp.host") - host == 3


def test_a_declared_class_rides_one_launch_a_class(device_dispatcher):
    d, launched = device_dispatcher
    d.warm_rows = frozenset({1024, (2048, 4160)})
    crt = fragment_items(4, exp_bits=1024, bits=1024)
    items = fragment_items(5) + crt
    dev, batches = counter("modexp.device"), counter("modexp.device_batch.count")
    assert d._run_batch(items) == [pow(*it) for it in items]
    assert sorted(launched) == [(1024, 1024, 4), (2048, 4160, 5)]
    assert counter("modexp.device") - dev == 9
    assert counter("modexp.device_batch.count") - batches == 2


def test_the_crossover_counts_work_not_items(device_dispatcher):
    d, launched = device_dispatcher
    # one first-level row costs ~270 verify items, one CRT-half row ~17
    assert 250 < dispatch.modexp_work(2048, 4160) < 300
    assert 15 < dispatch.modexp_work(1024, 1024) < 20
    d._run_batch(fragment_items(1))                       # 1 x 275 >= 18
    d._run_batch(fragment_items(1, exp_bits=1024, bits=1024))  # 17 < 18
    assert launched == [(2048, 4160, 1)]
    d.device_threshold = dispatch.ALWAYS_HOST             # a CPU backend
    d._run_batch(fragment_items(64))
    assert launched == [(2048, 4160, 1)]


def test_a_longer_class_is_cut_to_its_largest_bucket(device_dispatcher):
    d, launched = device_dispatcher
    few = fragment_items(8)
    items = [few[i % 8] for i in range(600)]
    assert d._run_batch(items) == [pow(*few[i % 8]) for i in range(600)]
    assert [n for _b, _e, n in launched] == [128, 128, 128, 128, 88]


def test_an_exponent_past_the_classes_is_host_tier_counted_by_class(
    device_dispatcher,
):
    d, launched = device_dispatcher
    item = fragment_items(1, exp_bits=8190)[0]  # a second-level fragment
    key = "modexp.host.class{bits=8192}"
    before = counter(key)
    assert d._run_batch([item]) == [pow(*item)]
    assert launched == [] and counter(key) - before == 1


def test_rows_that_arrive_during_a_launch_ride_the_next_one_together(
    monkeypatch,
):
    """A pow launch is long against the linger: while one of the pool's
    is out nothing is popped (the pool flushes on its collector's own
    thread), so what arrived meanwhile is ONE launch and not one a
    request."""
    launched: list[int] = []
    gate = threading.Event()

    def launch(bases, exps, mods, *, n_bits, exp_bits, defer=False, **_k):
        launched.append(len(mods))
        vals = [pow(b, e, m) for b, e, m in zip(bases, exps, mods)]
        first = len(launched) == 1

        def finish():
            if first:
                assert gate.wait(30)  # the device is busy with this one
            return vals

        return rns.DeferredModexp(finish) if defer else finish()

    monkeypatch.setattr(rns, "power_mod_rns", launch)
    d = dispatch.ModexpDispatcher(
        calibrate=False, device_threshold=18, max_wait=0.002
    ).start()
    items = fragment_items(4)
    got: dict = {}
    try:
        threads = [
            threading.Thread(
                target=lambda i=i: got.update({i: d.submit([items[i]])})
            )
            for i in range(4)
        ]
        threads[0].start()
        deadline = time.monotonic() + 10
        while not launched and time.monotonic() < deadline:
            time.sleep(0.005)
        assert launched == [1]
        for t in threads[1:]:
            t.start()
            time.sleep(0.02)  # ten lingers apart: a launch each, unheld
        assert launched == [1]
        gate.set()
        for t in threads:
            t.join(30)
    finally:
        gate.set()
        d.stop()
    assert launched == [1, 3]
    assert got == {i: [pow(*items[i])] for i in range(4)}


def test_an_idle_pool_launches_each_request_at_once(monkeypatch):
    """No launch of the pool is out: a request is held for the linger
    and no longer, whatever the pool launched before — a lone caller's
    rows never wait for company."""
    launched: list[int] = []

    def launch(bases, exps, mods, *, n_bits, exp_bits, defer=False, **_k):
        launched.append(len(mods))
        vals = [pow(b, e, m) for b, e, m in zip(bases, exps, mods)]
        return rns.DeferredModexp(lambda: vals) if defer else vals

    monkeypatch.setattr(rns, "power_mod_rns", launch)
    d = dispatch.ModexpDispatcher(
        calibrate=False, device_threshold=18, max_wait=0.002
    ).start()
    few = fragment_items(4)
    try:
        t0 = time.perf_counter()
        for i in range(4):
            assert d.submit([few[i]]) == [pow(*few[i])]
        assert launched == [1, 1, 1, 1]
        assert time.perf_counter() - t0 < 2.0  # four lingers and change
    finally:
        d.stop()
