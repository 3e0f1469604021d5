"""RSA sign/verify: host primitives vs the cryptography-library oracle,
and the batched TPU verify kernel vs both."""

import numpy as np
import pytest

from bftkv_tpu.crypto import rsa

KEY_BITS = 1024  # keygen speed; kernel is width-generic (128-limb padded)


@pytest.fixture(scope="module")
def keys():
    return [rsa.generate(KEY_BITS) for _ in range(3)]


def test_sign_verify_host(keys):
    key = keys[0]
    sig = rsa.sign(b"hello bftkv", key)
    assert rsa.verify_host(b"hello bftkv", sig, key.public)
    assert not rsa.verify_host(b"hello bftkV", sig, key.public)
    assert not rsa.verify_host(b"hello bftkv", sig, keys[1].public)


def test_sign_matches_cryptography_oracle(keys):
    pytest.importorskip("cryptography")  # oracle cross-check needs the host lib
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import padding, rsa as crsa

    key = keys[0]
    # Rebuild the same key in the oracle library and cross-check both ways.
    pub = crsa.RSAPublicNumbers(key.e, key.n).public_key()
    sig = rsa.sign(b"cross-check", key)
    pub.verify(sig, b"cross-check", padding.PKCS1v15(), hashes.SHA256())

    priv = crsa.RSAPrivateNumbers(
        p=key.p,
        q=key.q,
        d=key.d,
        dmp1=key.d % (key.p - 1),
        dmq1=key.d % (key.q - 1),
        iqmp=pow(key.q, -1, key.p),
        public_numbers=crsa.RSAPublicNumbers(key.e, key.n),
    ).private_key()
    their_sig = priv.sign(b"cross-check", padding.PKCS1v15(), hashes.SHA256())
    assert their_sig == sig  # PKCS#1 v1.5 is deterministic


def test_verify_batch_tpu(keys):
    # host_threshold=0 forces the device kernel even for a small batch —
    # this test also covers the power-of-two padding path (8 → 256 rows).
    dom = rsa.VerifierDomain(host_threshold=0)
    msgs = [f"msg-{i}".encode() for i in range(6)]
    items = []
    for i, m in enumerate(msgs):
        key = keys[i % len(keys)]
        items.append((m, rsa.sign(m, key), key.public))
    # Corrupt two entries: wrong message, wrong key.
    items.append((b"tampered", items[0][1], keys[0].public))
    items.append((msgs[1], items[1][1], keys[2].public))
    ok = dom.verify_batch(items)
    want = np.array([True] * 6 + [False, False])
    assert (ok == want).all()


def test_verify_batch_oversize_sig(keys):
    dom = rsa.VerifierDomain(host_threshold=0)
    key = keys[0]
    bad_sig = (key.n + 1).to_bytes(key.size_bytes + 1, "big")
    ok = dom.verify_batch([(b"m", bad_sig, key.public)])
    assert not ok[0]


def test_verify_batch_empty():
    assert rsa.VerifierDomain().verify_batch([]).shape == (0,)


def test_sign_batch_device_matches_host(keys):
    """Batched CRT signing on device is bit-identical to host signing
    (PKCS#1 v1.5 is deterministic), across mixed key sizes."""
    dom = rsa.SignerDomain(host_threshold=0)
    big = rsa.generate(2048)
    items = [(f"m{i}".encode(), keys[i % len(keys)]) for i in range(5)]
    items.append((b"wide", big))
    sigs = dom.sign_batch(items)
    for (m, k), s in zip(items, sigs):
        assert s == rsa.sign(m, k)
        assert rsa.verify_host(m, s, k.public)


def test_sign_batch_host_crossover(keys):
    dom = rsa.SignerDomain(host_threshold=64)
    items = [(b"a", keys[0]), (b"b", keys[1])]
    assert dom.sign_batch(items) == [rsa.sign(b"a", keys[0]), rsa.sign(b"b", keys[1])]


def oracle_sign(message: bytes, key: rsa.PrivateKey) -> bytes:
    em = rsa.emsa_pkcs1v15_sha256(message, key.size_bytes)
    return pow(em, key.d, key.n).to_bytes(key.size_bytes, "big")


@pytest.mark.parametrize("failure", ["returns_none", "raises"])
def test_group_the_pow_chain_cannot_serve_is_signed_on_the_host(
    keys, monkeypatch, failure
):
    """The one fallback of the one sign chain: the host tier, counted
    and never a limb program."""
    from bftkv_tpu.metrics import registry as metrics
    from bftkv_tpu.ops import bigint, rns

    def pow_rows_rns(*_a, **_k):
        if failure == "raises":
            raise RuntimeError("planted kernel failure")
        return None

    monkeypatch.setattr(rns, "pow_rows_rns", pow_rows_rns)
    monkeypatch.setattr(
        bigint, "mont_exp", lambda *_a: pytest.fail("limb program traced")
    )
    items = [(b"fb-%d" % i, keys[i % len(keys)]) for i in range(5)]
    metrics.reset()
    sigs = rsa.SignerDomain(host_threshold=0).sign_batch(items)
    assert sigs == [oracle_sign(m, k) for m, k in items]
    m = metrics.snapshot()
    assert m["sign.rns_fallback"] == 1  # one width group, one launch lost
    assert m["sign.host"] == m["sign.host.bits{bits=1024}"] == 5
    assert "sign.device" not in m


@pytest.mark.parametrize("kind", ["even", "channel_prime_factor"])
def test_one_malformed_key_costs_its_own_items_the_device(
    keys, monkeypatch, kind
):
    """A tenant may REGISTER any p * q = n.  A "prime" the pow chain
    has no rows for sends that key's items to the host tier; the sound
    keys of its width group still ride one launch."""
    from bftkv_tpu.metrics import registry as metrics
    from bftkv_tpu.ops import rns

    good = keys[0]
    ctx = rns.pow_context(512)
    if kind == "even":
        p = good.p + 1
    else:
        c = ctx.pb[0]
        p = c * ((good.p // c - 2) | 1)  # odd, below good.p, same width
    assert p.bit_length() == good.p.bit_length()
    assert ctx.key_rows(p) is None and ctx.key_rows(good.q) is not None
    bad = rsa.PrivateKey(n=p * good.q, e=good.e, d=good.d, p=p, q=good.q)
    items = [(b"mk-%d" % i, keys[i % len(keys)]) for i in range(7)]
    items.insert(4, (b"mk-bad", bad))

    launches = []
    real = rns.pow_rows_rns

    def spy(n_bits, umods, row_mod, *a, **kw):
        launches.append(len(row_mod))
        return real(n_bits, umods, row_mod, *a, **kw)

    monkeypatch.setattr(rns, "pow_rows_rns", spy)
    metrics.reset()
    sd = rsa.SignerDomain(host_threshold=0)
    sigs = sd.sign_batch(items)
    assert sigs.pop(4) == rsa.sign_many([items.pop(4)])[0]
    assert sigs == [oracle_sign(m, k) for m, k in items]
    assert launches == [14]  # both CRT halves of the seven sound items
    m = metrics.snapshot()
    assert m["sign.device"] == 7 and m["sign.host"] == 1
    assert "sign.rns_fallback" not in m
    # asked once a key: the answer is kept beside the CRT constants
    assert sd._crt[bad.n] is None and len(sd._crt) == 1 + len(keys)


@pytest.mark.parametrize("op", ["VERIFY", "SIGN"])
def test_backend_flags_are_undeclared(op, monkeypatch):
    """One chain an operation: a stale environment that still names a
    backend is read by nobody, and a read of the name raises."""
    from bftkv_tpu import flags

    name = f"BFTKV_{op}_BACKEND"  # spelled out nowhere in the tree
    monkeypatch.setenv(name, "limb")
    with pytest.raises(KeyError):
        flags.raw(name)
    rsa.VerifierDomain()
    rsa.SignerDomain()


def test_sign_dispatcher_end_to_end(keys):
    from bftkv_tpu.ops import dispatch

    d = dispatch.SignDispatcher(
        rsa.SignerDomain(host_threshold=0), max_batch=64, max_wait=0.005
    ).start()
    try:
        import threading

        out: dict = {}

        def go(i):
            out[i] = d.sign(b"msg-%d" % i, keys[i % len(keys)])

        ts = [threading.Thread(target=go, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        for i in range(8):
            assert rsa.verify_host(b"msg-%d" % i, out[i], keys[i % len(keys)].public)
    finally:
        d.stop()


def test_verify_batch_host_crossover(keys):
    """Small batches route to the host oracle (device launches only pay
    off past a few hundred items); results are identical either way."""
    dom = rsa.VerifierDomain(host_threshold=64)
    key = keys[0]
    sig = rsa.sign(b"m", key)
    ok = dom.verify_batch([(b"m", sig, key.public), (b"x", sig, key.public)])
    assert ok[0] and not ok[1]


# -- native Montgomery modexp (native/montmodexp.c) -------------------------


def test_native_modexp_matches_pow_oracle():
    """The CIOS Montgomery extension is pinned to pow() across widths,
    edge bases, and exponent shapes; the pure path stays the oracle."""
    import random

    if rsa._MM is None:
        pytest.skip("native modexp not built")
    rng = random.Random(1234)
    for bits in (512, 1024, 2048):
        for _ in range(10):
            mod = rng.getrandbits(bits) | (1 << (bits - 1)) | 1
            params = rsa._mont_params(mod)
            for base in (
                0,
                1,
                2,
                mod - 1,
                rng.getrandbits(bits) % mod,
            ):
                for exp in (1, 2, 65537, rng.getrandbits(bits)):
                    assert rsa._native_powmod(base, exp, params) == pow(
                        base, exp, mod
                    ), (bits, base, exp)


def _rows(width, rows):
    """``powmod_many``'s operands for ``[(base, exp, mod)]`` at one width."""
    ewidth = max(1, max((e.bit_length() + 7) // 8 for _b, e, _m in rows))
    return (
        width,
        ewidth,
        b"".join(b.to_bytes(width, "big") for b, _e, _m in rows),
        b"".join(e.to_bytes(ewidth, "big") for _b, e, _m in rows),
        b"".join(rsa._mont_params(m)[0] for _b, _e, m in rows),
    )


@pytest.mark.parametrize("entry", ["powmod_many", "powmod_many_cios"])
@pytest.mark.parametrize("bits", [1024, 1536, 2048, 3072, 4096])
def test_both_engines_match_pow_at_every_identity_width(bits, entry):
    """Every width an identity, a CRT half or a CA fragment has, through
    the batch entry the loader trusts (libcrypto where the process has
    it) and through the CIOS loop it falls back to: the exponents a
    row can carry (0, 1, a public one, full length, and a CA fragment's,
    twice the modulus and more) on the edge bases, in one call."""
    import random

    if rsa._MM is None:
        pytest.skip("native modexp not built")
    rng = random.Random(bits)
    mod = rng.getrandbits(bits) | (1 << (bits - 1)) | 1
    full = rng.getrandbits(bits) | (1 << (bits - 1))
    fragment = rng.getrandbits(2 * bits + 70) | (1 << (2 * bits + 69))
    rows = [
        (base, exp, mod)
        for base in (0, 1, mod - 1, rng.getrandbits(bits) % mod)
        for exp in (0, 1, rsa.F4, full, fragment)
    ]
    width = rsa._mont_params(mod)[1]
    got = getattr(rsa._MM, entry)(*_rows(width, rows))
    assert got == b"".join(
        pow(b, e, m).to_bytes(width, "big") for b, e, m in rows
    )


def test_native_sign_matches_pure_python(keys, monkeypatch):
    """One signature, both engines, byte-identical — so an engine flip
    (or BFTKV_NATIVE_MODEXP=off) can never change the wire."""
    if rsa._MM is None:
        pytest.skip("native modexp not built")
    key = keys[0]
    native = rsa.sign(b"engine parity", key)
    monkeypatch.setattr(rsa, "_MM", None)
    assert rsa.sign(b"engine parity", key) == native


def test_crt_pow_d_roundtrips_encrypt(keys):
    key = keys[0]
    m = 0x123456789ABCDEF
    c = pow(m, key.e, key.n)
    assert rsa.crt_pow_d(c, key) == m
