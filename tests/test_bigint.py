"""Kernel-vs-oracle property tests for the batched big-integer ops.

The oracle is Python's arbitrary-precision int — the analog of the
reference's Tier-1 math tests (rsa_test.go:31-53, dsa_test.go:47-215).
"""

import random

import numpy as np
import pytest

from bftkv_tpu.ops import bigint, limb

rng = random.Random(1234)


def rand_ints(n, bits):
    return [rng.getrandbits(bits) for _ in range(n)]


def rand_odd(bits):
    n = rng.getrandbits(bits) | (1 << (bits - 1)) | 1
    return n


@pytest.mark.parametrize("bits", [64, 256, 1024])
def test_carry_resolve(bits):
    nl = limb.nlimbs_for_bits(bits)
    # Random lane values up to 2^26 (the worst case the kernels produce).
    raw = np.array(
        [[rng.getrandbits(26) for _ in range(nl)] for _ in range(8)], dtype=np.uint32
    )
    out = np.asarray(bigint.carry_resolve(raw, nl + 2))
    for row_raw, row_out in zip(raw, out):
        want = sum(int(v) << (16 * i) for i, v in enumerate(row_raw))
        assert limb.limbs_to_int(row_out) == want


@pytest.mark.parametrize("bits", [64, 256, 2048])
def test_mul(bits):
    nl = limb.nlimbs_for_bits(bits)
    xs = rand_ints(6, bits)
    ys = rand_ints(6, bits)
    a = limb.ints_to_limbs(xs, nl)
    b = limb.ints_to_limbs(ys, nl)
    out = np.asarray(bigint.mul(a, b))
    for x, y, row in zip(xs, ys, out):
        assert limb.limbs_to_int(row) == x * y


def test_add_sub_geq():
    nl = 16
    xs = rand_ints(8, 250)
    ys = rand_ints(8, 250)
    a = limb.ints_to_limbs(xs, nl)
    b = limb.ints_to_limbs(ys, nl)
    s = np.asarray(bigint.add(a, b, nl + 1))
    for x, y, row in zip(xs, ys, s):
        assert limb.limbs_to_int(row) == x + y
    d = np.asarray(bigint.sub_mod_r(a, b))
    r = 1 << (16 * nl)
    for x, y, row in zip(xs, ys, d):
        assert limb.limbs_to_int(row) == (x - y) % r
    ge = np.asarray(bigint.geq(a, b))
    for x, y, g in zip(xs, ys, ge):
        assert bool(g) == (x >= y)
    # equality edge
    assert bool(np.asarray(bigint.geq(a, a)).all())


@pytest.mark.parametrize("bits", [256, 2048])
def test_mont_mul(bits):
    n = rand_odd(bits)
    dom = bigint.MontgomeryDomain(n)
    xs = [rng.randrange(n) for _ in range(5)]
    ys = [rng.randrange(n) for _ in range(5)]
    am = dom.encode(xs)
    bm = dom.encode(ys)
    out = np.asarray(bigint.mont_mul(am, bm, dom.n, dom.n_prime))
    got = dom.decode(out)
    for x, y, g in zip(xs, ys, got):
        assert g == (x * y) % n


def test_mont_roundtrip():
    n = rand_odd(256)
    dom = bigint.MontgomeryDomain(n)
    xs = [rng.randrange(n) for _ in range(4)]
    plain = limb.ints_to_limbs(xs, dom.nlimbs)
    m = bigint.to_mont(plain, dom.r2, dom.n, dom.n_prime)
    back = np.asarray(bigint.from_mont(m, dom.n, dom.n_prime))
    assert limb.limbs_to_ints(back) == xs


@pytest.mark.parametrize("e", [3, 17, 65537])
def test_mont_pow_static(e):
    n = rand_odd(512)
    dom = bigint.MontgomeryDomain(n)
    xs = [rng.randrange(n) for _ in range(4)]
    am = dom.encode(xs)
    out = np.asarray(bigint.mont_pow_static(am, e, dom.n, dom.n_prime))
    got = dom.decode(out)
    for x, g in zip(xs, got):
        assert g == pow(x, e, n)


@pytest.mark.parametrize("bits,ebits", [(256, 256), (512, 64)])
def test_mont_exp(bits, ebits):
    n = rand_odd(bits)
    dom = bigint.MontgomeryDomain(n)
    xs = [rng.randrange(n) for _ in range(4)]
    es = [rng.getrandbits(ebits) | 1 for _ in range(4)]
    am = dom.encode(xs)
    e = limb.ints_to_limbs(es, limb.nlimbs_for_bits(ebits))
    one = np.broadcast_to(dom.one_mont, am.shape)
    out = np.asarray(bigint.mont_exp(am, e, dom.n, dom.n_prime, one))
    got = dom.decode(out)
    for x, ei, g in zip(xs, es, got):
        assert g == pow(x, ei, n)


@pytest.mark.parametrize("ebits", [2113, 4096])
def test_modexp_engine_takes_exponents_no_rns_context_holds(
    ebits, monkeypatch
):
    """The use that keeps ``ops.modexp.power_batch``: exponents past
    both classes of their modulus's RNS rows (a 512-bit modulus rides
    1,024-bit rows: up to 2 x 1,024 + 64 = 2,112 bits) stay on the
    device through the limb Montgomery engine up to 4,096 bits, and
    equal ``pow``."""
    from bftkv_tpu.ops import modexp, rns

    rng = random.Random(ebits)
    n = rng.getrandbits(512) | (1 << 511) | 1
    pairs = [
        (rng.getrandbits(500), rng.getrandbits(ebits) | (1 << (ebits - 1)))
        for _ in range(4)
    ]
    monkeypatch.setattr(
        rns, "power_mod_rns",
        lambda *_a, **_k: pytest.fail("no RNS width class holds these"),
    )
    launches = []
    real = modexp.power_batch
    monkeypatch.setattr(
        modexp, "power_batch",
        lambda *a: launches.append(a[1].shape) or real(*a),
    )
    got = modexp.BatchModExp(min_batch=1).modexp(pairs, n)
    assert got == [pow(b, e, n) for b, e in pairs]
    assert launches == [(4, 256)]  # one launch, the 4,096-bit bucket


def test_mont_exp_shared_exponent():
    # Exponent broadcast from a single shared vector (e.g. fixed e).
    n = rand_odd(256)
    dom = bigint.MontgomeryDomain(n)
    xs = [rng.randrange(n) for _ in range(3)]
    am = dom.encode(xs)
    e_int = 65537
    e = limb.int_to_limbs(e_int, 2)
    one = np.broadcast_to(dom.one_mont, am.shape)
    out = np.asarray(bigint.mont_exp(am, e, dom.n, dom.n_prime, one))
    assert dom.decode(out) == [pow(x, e_int, n) for x in xs]


def test_per_element_moduli():
    # Batched moduli: each element has its own n (threshold-signing case).
    ns = [rand_odd(256) for _ in range(3)]
    doms = [bigint.MontgomeryDomain(n, 16) for n in ns]
    xs = [rng.randrange(n) for n in ns]
    ys = [rng.randrange(n) for n in ns]
    am = np.stack([d.encode([x])[0] for d, x in zip(doms, xs)])
    bm = np.stack([d.encode([y])[0] for d, y in zip(doms, ys)])
    nn = np.stack([d.n for d in doms])
    npr = np.stack([d.n_prime for d in doms])
    out = np.asarray(bigint.mont_mul(am, bm, nn, npr))
    for d, x, y, row, n in zip(doms, xs, ys, out, ns):
        assert d.decode(row[None])[0] == (x * y) % n


def test_mul_extremes():
    nl = 16
    m = (1 << (16 * nl)) - 1  # all-0xFFFF digits: worst-case carry chains
    a = limb.ints_to_limbs([m, m, 0, 1], nl)
    b = limb.ints_to_limbs([m, 1, m, m], nl)
    out = np.asarray(bigint.mul(a, b))
    want = [m * m, m, 0, m]
    assert limb.limbs_to_ints(out) == want
