"""A sign launch's operands are built in whole-array steps, on the flush
worker's own thread, with a key's constants computed once a key
(ISSUE 31; docs/DESIGN.md §7.1, "Launch phases").

The oracle is what this replaced, kept HERE: ``power_mod_rns``'s
staging block — an integer and two little arrays a row, ``np.stack``,
the nibble split of every exponent, a key table stacked and padded a
launch — and ``_sigma_to_ints`` with its float64 ``@`` and a byte
string a row.  Every case holds the new route to it bit for bit, pad
region included.  The operand half launches nothing: the jitted chain
is replaced by a recorder that answers from host ``pow``.  The
end-to-end half launches on the CPU backend at the buckets
``tests/test_key_width.py`` and ``tests/test_flush_phases.py`` compile
too (64 rows; 256 x 64 for the fault check).
"""

from __future__ import annotations

import math
import random
import time

import numpy as np
import pytest

from bftkv_tpu.crypto import rsa
from bftkv_tpu.metrics import registry as metrics
from bftkv_tpu.ops import devbuf, limb, rns


@pytest.fixture(autouse=True)
def fresh(monkeypatch):
    """No counter, staging slot or placed prime from another test."""
    metrics.reset()
    devbuf.reset()
    for bits in (512, 1024, 1536):
        ctx = rns.pow_context(bits)
        monkeypatch.setattr(ctx, "pow_keys", rns._PowKeyTable(ctx))
    yield
    devbuf.reset()
    metrics.reset()


# -- the oracle: yesterday's loops, copied ----------------------------------


def oracle_pow_operands(bases, exps, mods, n_bits):
    """``power_mod_rns``'s staging block as it was."""
    ctx = rns.pow_context(n_bits)
    digits = ctx.digits
    t = len(mods)
    unique: dict[int, int] = {}
    urows: list = []
    idxs: list[int] = []
    for m in mods:
        u = unique.get(m)
        if u is None:
            u = unique[m] = len(urows)
            urows.append(ctx.key_rows(m))
        idxs.append(u)
    padded = max(64, 1 << (t - 1).bit_length())
    kpad = max(64, 1 << (len(urows) - 1).bit_length())
    urows += [urows[0]] * (kpad - len(urows))
    ukey = rns.stack_key_rows(urows)
    bh = np.empty((padded, 2 * digits), dtype=np.uint8)
    nt = np.empty((4 * digits, padded), dtype=np.uint8)
    ix = np.empty((padded,), dtype=np.int32)
    base_digits = np.stack(
        [limb.int_to_limbs(b % m, digits) for b, m in zip(bases, mods)]
    )
    bh[:t, 0::2] = base_digits & 0xFF
    bh[:t, 1::2] = base_digits >> 8
    ed = np.stack([limb.int_to_limbs(e, digits) for e in exps])
    nib = np.empty((t, digits * 4), dtype=np.uint8)
    nib[:, 0::4] = ed & 0xF
    nib[:, 1::4] = (ed >> 4) & 0xF
    nib[:, 2::4] = (ed >> 8) & 0xF
    nib[:, 3::4] = (ed >> 12) & 0xF
    nt[:, :t] = nib[:, ::-1].T
    ix[:t] = np.asarray(idxs, dtype=np.int32)
    if padded > t:
        bh[t:] = bh[0:1]
        nt[:, t:] = nt[:, 0:1]
        ix[t:] = 0
    return bh, nt, ix, ukey


def oracle_sigma_to_ints(ctx, sigma):
    """``_sigma_to_ints`` as it was (the ``@`` is BLAS's), over the
    16-bit digit planes of M / p_i it had."""
    width = (ctx.M.bit_length() + rns.PR_BITS + 15) // 16 + 1
    m = np.zeros((ctx.k, width), dtype=np.float64)
    for i, p in enumerate(ctx.pb):
        m[i] = limb.int_to_limbs(ctx.M // p, width)
    acc = (sigma.astype(np.float64) @ m).astype(np.int64)
    carry = np.zeros(acc.shape[0], dtype=np.int64)
    out = np.empty_like(acc, dtype=np.uint16)
    for d in range(acc.shape[1]):
        s = acc[:, d] + carry
        out[:, d] = (s & 0xFFFF).astype(np.uint16)
        carry = s >> 16
    vals = [int.from_bytes(row.tobytes(), "little") for row in out]
    return [v % ctx.M for v in vals]


def assert_same_operands(got, want) -> None:
    for g, w in zip(got[:3], want[:3]):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(g, w)
    assert len(got[3]) == len(want[3]) == 6
    for g, w in zip(got[3], want[3]):
        g = np.asarray(g)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(g, w)


# -- inputs, and a recorder where the chain stands ---------------------------


def modulus(rng: random.Random, bits: int) -> int:
    """An odd number of exactly ``bits`` bits that the pow context of
    its width builds rows for.  Staging needs no prime."""
    ctx = rns.pow_context(bits)
    while True:
        n = rng.getrandbits(bits) | (1 << (bits - 1)) | 1
        if ctx.key_rows(n) is not None:
            return n


def sigma_of(ctx, values) -> np.ndarray:
    """The CRT coefficients the chain hands back for ``values``."""
    inv = [pow((ctx.M // p) % p, -1, p) for p in ctx.pb]
    return np.asarray(
        [[(v % p) * i % p for p, i in zip(ctx.pb, inv)] for v in values],
        dtype=np.float32,
    )


class PowRecorder:
    """Stands where ``_jitted_pow`` stands; keeps a copy of every
    launch's operands and answers each live row from host ``pow`` of
    the integers the caller meant (the operands are held to the oracle
    separately), pad rows with zeros."""

    def __init__(self, monkeypatch):
        self.calls: list[tuple] = []
        self.ukeys: list[tuple] = []  # as handed over, not copied
        self.answers: list[int] = []
        monkeypatch.setattr(rns, "_jitted_pow", self._jitted)
        monkeypatch.setattr(rns, "_shardable", lambda _batch: False)

    def _jitted(self, digits, n_bits, donate=False):
        ctx = rns.context(digits, n_bits)

        def launch(bh, nt, ix, ukey):
            self.ukeys.append(ukey)
            self.calls.append(
                (bh.copy(), nt.copy(), ix.copy(),
                 tuple(np.asarray(a) for a in ukey))
            )
            out = np.zeros((bh.shape[0], ctx.k), dtype=np.float32)
            out[: len(self.answers)] = sigma_of(ctx, self.answers)
            return out

        return launch


def rows_case(rng, bits, t, nkeys):
    """``t`` rows over ``nkeys`` moduli of ``bits`` bits, mixed."""
    mods_u = [modulus(rng, bits) for _ in range(nkeys)]
    mods = [mods_u[rng.randrange(nkeys)] for _ in range(t)]
    bases = [rng.getrandbits(2 * bits) for _ in range(t)]
    exps = [rng.getrandbits(rng.choice((bits, bits - 9, 17, 1))) for _ in mods]
    return bases, exps, mods


# -- the modexp entry: an exponent a row --------------------------------------


@pytest.mark.parametrize("bits", [1024, 1536])
@pytest.mark.parametrize("t,nkeys", [(1, 1), (37, 5), (64, 2), (70, 70)])
def test_per_row_exponents_equal_the_per_row_loop(monkeypatch, bits, t, nkeys):
    rng = random.Random(bits * 1000 + t)
    bases, exps, mods = rows_case(rng, bits, t, nkeys)
    exps[0] = 0x0F << 40  # leading zero nibbles, and a zero low end
    rec = PowRecorder(monkeypatch)
    rec.answers = want = [pow(b, e, m) for b, e, m in zip(bases, exps, mods)]
    assert rns.power_mod_rns(bases, exps, mods, n_bits=bits) == want
    assert_same_operands(
        rec.calls[0], oracle_pow_operands(bases, exps, mods, bits)
    )


@pytest.mark.parametrize("what", ["negative", "over_wide", "even", "rowless",
                                  "zero_modulus", "wide_modulus"])
def test_what_the_chain_cannot_take_still_answers_none(monkeypatch, what):
    rng = random.Random(7)
    rec = PowRecorder(monkeypatch)
    m = modulus(rng, 1024)
    bases, exps, mods = [5, 6], [3, 65537], [m, m]
    if what == "negative":
        exps[1] = -1
    elif what == "over_wide":
        exps[1] = 1 << 1024
    elif what == "even":
        mods[1] = m + 1
    elif what == "rowless":
        mods[1] = rns.pow_context(1024).pb[0] * 3
    elif what == "zero_modulus":
        mods[1] = 0
    else:
        mods[1] = (1 << 1100) + 1
    assert rns.power_mod_rns(bases, exps, mods, n_bits=1024) is None
    assert rec.calls == []
    assert metrics.snapshot().get("pow.keytable.upload", 0) == 0
    # nothing half-placed: the next launch over the sound modulus works
    rec.answers = [pow(5, 3, m)]
    assert rns.power_mod_rns([5], [3], [m], n_bits=1024) == rec.answers


# -- the signer's entry: a key's exponents, computed once ---------------------


class FakeKey:
    """p, q and d of no particular arithmetic meaning: staging reads
    their bytes, not their primality."""

    def __init__(self, rng, bits):
        self.p = self.q = modulus(rng, bits)
        while math.gcd(self.p, self.q) != 1:  # qinv exists
            self.q = modulus(rng, bits)
        self.n, self.e = self.p * self.q, rsa.F4
        self.d = rng.getrandbits(2 * bits - 3)
        self.size_bytes = (self.n.bit_length() + 7) // 8


def lane_of(rng, keys, bits, t):
    """A sign lane as ``sign_batch`` fills it, and the lists the old
    ``_sign_group_rns`` handed ``power_mod_rns`` for the same items."""
    recs = [rsa._SignKey(k, bits, rns.pow_context(bits).digits) for k in keys]
    lane = rsa._SignLane(bits)
    lane.keys = recs
    bases, exps, mods = [], [], []
    for i in range(t):
        k = rng.randrange(len(keys))
        key, r = keys[k], recs[k]
        em = rng.getrandbits(8 * key.size_bytes - 15)
        lane.idx.append(i)
        lane.ksel.append(k)
        lane.base.append((em % r.p).to_bytes(r.row, "little"))
        lane.base.append((em % r.q).to_bytes(r.row, "little"))
        bases += [em, em]
        exps += [key.d % (key.p - 1), key.d % (key.q - 1)]
        mods += [key.p, key.q]
    lane.close()
    return lane, bases, exps, mods


def launch_lane(lane):
    return rns.pow_rows_rns(
        lane.bits,
        [m for r in lane.keys for m in (r.p, r.q)],
        lane.row_mod, lane.base_bytes,
        np.concatenate([r.nib for r in lane.keys]), lane.row_mod,
        op="sign",
    )


@pytest.mark.parametrize("bits", [1024, 1536])
@pytest.mark.parametrize("t,nkeys", [(1, 1), (19, 1), (45, 3), (128, 7)])
def test_cached_columns_equal_the_per_row_loop(monkeypatch, bits, t, nkeys):
    rng = random.Random(bits * 1000 + t)
    keys = [FakeKey(rng, bits) for _ in range(nkeys)]
    keys[0].d = (keys[0].p - 1) * 5 + 0xABC  # dp of three nibbles
    lane, bases, exps, mods = lane_of(rng, keys, bits, t)
    # a key nobody names in this flush still has its place in the lane;
    # the oracle's table holds only what the rows name, in their order
    named = list(dict.fromkeys(lane.ksel))
    lane.keys = [lane.keys[k] for k in named]
    lane.ksel = [named.index(k) for k in lane.ksel]
    lane.close()
    rec = PowRecorder(monkeypatch)
    rec.answers = want = [pow(b, e, m) for b, e, m in zip(bases, exps, mods)]
    assert launch_lane(lane) == want
    assert_same_operands(
        rec.calls[0], oracle_pow_operands(bases, exps, mods, bits)
    )


def test_a_second_flush_of_the_same_keys_uploads_no_key_table(monkeypatch):
    rng = random.Random(3)
    keys = [FakeKey(rng, 1024) for _ in range(4)]
    rec = PowRecorder(monkeypatch)
    uploads = lambda: metrics.snapshot().get("pow.keytable.upload", 0)
    for flush in range(3):
        # another order of first appearance each flush: slots are the
        # table's, not the launch's
        lane, bases, exps, mods = lane_of(rng, keys[flush:] + keys[:flush], 1024, 23)
        rec.answers = [pow(b, e, m) for b, e, m in zip(bases, exps, mods)]
        assert launch_lane(lane) == rec.answers
        bh, nt, ix, ukey = rec.calls[-1]
        want = oracle_pow_operands(bases, exps, mods, 1024)
        assert np.array_equal(bh, want[0]) and np.array_equal(nt, want[1])
        # each row gathers the key rows the per-launch table gave it
        for g, w in zip(ukey, want[3]):
            assert np.array_equal(g[ix], w[want[2]])
    assert uploads() == 1
    assert rec.ukeys[0] is rec.ukeys[1] is rec.ukeys[2]
    # a new prime enters: one upload, the placed primes keep their slots
    newcomer = FakeKey(rng, 1024)
    lane, bases, exps, mods = lane_of(rng, [newcomer, keys[2]], 1024, 9)
    rec.answers = [pow(b, e, m) for b, e, m in zip(bases, exps, mods)]
    assert launch_lane(lane) == rec.answers
    assert uploads() == 2
    slots = rns.pow_context(1024).pow_keys._slots
    assert [slots[m] for k in keys[:1] for m in (k.p, k.q)] == [0, 1]
    assert len(slots) == 10


def test_primes_beyond_the_table_restart_it_or_ride_their_own(monkeypatch):
    rng = random.Random(5)
    rec = PowRecorder(monkeypatch)
    uploads = lambda: metrics.snapshot().get("pow.keytable.upload", 0)

    def launch(mods):
        rec.answers = [pow(3, 5, m) for m in mods]
        assert rns.power_mod_rns([3] * len(mods), [5] * len(mods), mods,
                                 n_bits=512) == rec.answers
        return rec.calls[-1]

    first = [modulus(rng, 512) for _ in range(40)]
    launch(first)
    # 40 placed + 30 new > 64: the table restarts from this launch
    second = [modulus(rng, 512) for _ in range(30)]
    _bh, _nt, ix, ukey = launch(second)
    assert ukey[0].shape[0] == 64 and ix[:30].tolist() == list(range(30))
    assert uploads() == 2
    launch(second[::-1])
    assert uploads() == 2
    # more than 64 distinct primes in ONE launch: a table of its own,
    # as ever — a power of two of rows, not kept
    many = [modulus(rng, 512) for _ in range(70)]
    got = launch(many)
    assert_same_operands(got, oracle_pow_operands([3] * 70, [5] * 70, many, 512))
    assert got[3][0].shape[0] == 128 and uploads() == 3
    launch(second)
    assert uploads() == 3  # the kept table was not disturbed


# -- residues to integers ---------------------------------------------------


@pytest.mark.parametrize("bits", [1024, 1536])
@pytest.mark.parametrize("kind", ["random", "zeros", "top", "one_row",
                                  "transposed"])
def test_sigma_to_ints_equals_python_integers(bits, kind):
    ctx = rns.pow_context(bits)
    rng = np.random.default_rng(bits)
    t = 1 if kind == "one_row" else 77
    if kind == "zeros":
        sigma = np.zeros((t, ctx.k), dtype=np.float32)
    elif kind == "top":
        sigma = np.tile(np.asarray(ctx.pb, dtype=np.float32) - 1, (t, 1))
    else:
        sigma = np.stack(
            [rng.integers(0, p, size=t) for p in ctx.pb], axis=1
        ).astype(np.float32)
    if kind == "transposed":  # as ops/ec_rns.py hands it over
        sigma = np.ascontiguousarray(sigma.T).T
    want = [
        sum(int(s) * (ctx.M // p) for s, p in zip(row, ctx.pb)) % ctx.M
        for row in sigma
    ]
    assert rns._sigma_to_ints(ctx, sigma) == want
    assert oracle_sigma_to_ints(ctx, sigma) == want


def test_sigma_to_ints_wakes_no_pool():
    """The BLAS pool's workers spin on every core after a product of
    this size (100x the call's own time and more, measured); the
    route that replaced it runs on the caller's thread alone.  OpenBLAS
    starts its threads at import, so counting threads shows nothing:
    the CPU the process burns does."""
    ctx = rns.pow_context(1024)
    rng = np.random.default_rng(1)
    sigma = np.stack(
        [rng.integers(0, p, size=512) for p in ctx.pb], axis=1
    ).astype(np.float32)
    rns._sigma_to_ints(ctx, sigma)  # the matrix, caches
    for _ in range(50):
        # a pool that another test's oracle woke spins on for a while:
        # wait until the process is quiet
        quiet = time.process_time()
        time.sleep(0.1)
        if time.process_time() - quiet < 0.005:
            break
    wall = 0.0
    cpu0, own0 = time.process_time(), time.thread_time()
    for _ in range(20):
        t0 = time.perf_counter()
        rns._sigma_to_ints(ctx, sigma)
        wall += time.perf_counter() - t0
        time.sleep(0.05)
    cpu, own = time.process_time() - cpu0, time.thread_time() - own0
    assert cpu <= 10 * wall, (cpu, wall)
    # sharper, where a throttled pool stretches the calls' wall time
    # too: threads other than the caller's burnt next to nothing
    assert cpu - own <= own + 0.1, (cpu, own)


# -- end to end, on the CPU backend -----------------------------------------


@pytest.fixture(scope="module")
def keys():
    return {bits: [rsa.generate(bits) for _ in range(2)] for bits in (2048, 3072)}


needs_native = pytest.mark.skipif(
    rsa._MM is None, reason="native modexp extension not built"
)


@needs_native
@pytest.mark.parametrize("bits", [2048, 3072])
def test_sign_batch_equals_sign_many_byte_for_byte(keys, bits):
    ks = keys[bits]
    items = [(b"sb-%d" % i * (1 + i % 3), ks[i % 3 == 0]) for i in range(29)]
    sd = rsa.SignerDomain(host_threshold=0)
    snap = metrics.snapshot()
    assert snap["sign.stage.array"] == snap["sign.stage.item"] == 0
    assert snap["pow.keytable.upload"] == 0
    assert sd.sign_batch(items) == rsa.sign_many(items)
    assert sd.sign_batch(items[::-1]) == rsa.sign_many(items[::-1])
    snap = metrics.snapshot()
    assert snap["sign.stage.array"] == snap["sign.device"] == 58
    assert snap["sign.stage.item"] == 0 and "sign.host" not in snap
    assert "sign.fault" not in snap and "sign.rns_fallback" not in snap
    assert snap["pow.keytable.upload"] == 1
    # once a key: the record is the cache's, not the flush's
    assert len(sd._crt) == 2
    assert all(r.bits == bits // 2 for r in sd._crt.values())


def test_items_that_leave_the_arrays_are_counted_and_signed_on_the_host(
    monkeypatch,
):
    rec = PowRecorder(monkeypatch)
    good = rsa.generate(1024)
    c = rns.pow_context(512).pb[0]
    p = c * ((good.p // c - 2) | 1)  # odd, no rows: a channel prime divides it
    rowless = rsa.PrivateKey(n=p * good.q, e=good.e, d=good.d, p=p, q=good.q)
    cold = rsa.generate(2048)  # 1,024-bit rows: nobody built that program
    items = [(b"x%d" % i, (good, rowless, cold, good)[i % 4]) for i in range(20)]
    sd = rsa.SignerDomain(host_threshold=0)
    sd.warm_rows = frozenset({512})
    monkeypatch.setattr(
        rsa.SignerDomain, "_fault_check",
        staticmethod(lambda keys, *_a: [True] * len(keys)),
    )
    rec.answers = [
        pow(rsa.emsa_pkcs1v15_sha256(m, k.size_bytes), k.d % (f - 1), f)
        for m, k in items if k is good for f in (k.p, k.q)
    ]
    out = sd.sign_batch(items)
    assert out == rsa.sign_many(items)
    snap = metrics.snapshot()
    assert (snap["sign.stage.array"], snap["sign.stage.item"]) == (10, 10)
    assert snap["sign.device"] == 10 and snap["sign.host"] == 10
    assert snap["sidecar.unwarmed_width"] == 5
    assert len(rec.calls) == 1
    # below the crossover nothing was bound for the device: not counted
    sd.host_threshold = 64
    sd.sign_batch(items)
    snap = metrics.snapshot()
    assert (snap["sign.stage.array"], snap["sign.stage.item"]) == (10, 10)


def test_the_per_layer_metric_reads_the_two_counters():
    """``sign_stage_array_share`` is a data file for the harness's
    ``counter_ratio``: the sidecar's two counters and nothing else, so
    that a process that lacks them (the parent) reports nothing."""
    import json
    import pathlib

    root = pathlib.Path(__file__).resolve().parent.parent
    spec = json.loads(
        (root / "benchmarks/layer_metrics/sign_stage_array_share.json").read_text()
    )
    assert spec["name"] == "sign_stage_array_share"
    assert spec["reader"] == "counter_ratio"
    assert spec["args"]["num"] == ["sidecar:sign.stage.array"]
    assert spec["args"]["den"] == ["sidecar:sign.stage.array",
                                   "sidecar:sign.stage.item"]
    assert spec["args"]["scale"] == 100.0
    entry = [
        m for m in json.loads((root / "BENCHMARK.json").read_text())["per_layer"]
        if m["name"] == spec["name"]
    ]
    assert len(entry) == 1 and entry[0]["layer"] == "sidecar dispatch"
    assert entry[0]["moves"] == "committed_ops_per_s"
    assert entry[0]["workloads"] == [
        "q4-rsa2048.load", "q10-rsa2048.load", "q4-rsa3072.load",
    ]
    # what the reader divides: the names a SignerDomain registers
    rsa.SignerDomain()
    snap = metrics.snapshot()
    for spec_name in spec["args"]["den"]:
        assert snap[spec_name.partition(":")[2]] == 0
