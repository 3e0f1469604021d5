"""Key width is something the served path observes (ISSUE 28).

One rule — ``ops.rns.chains`` — says which device chains can take a
width; the verifier, the fault check, the signer, the tenant channel
and the sidecar's warm-up ask it.  RSA-2048 rides both chains; an
RSA-3072 identity signs on the device (CRT halves of 1,536 bits) and
verifies on the native host tier, as a tier.  Every answer here is
held to Python ``pow`` and ``emsa_pkcs1v15_sha256``.

The keys are made once for the file, and every launch at 1,536 bits
pads to the same 64-row bucket: one compile (~20 s on the CPU backend).
"""

from __future__ import annotations

import pytest

from bftkv_tpu.cmd import verify_sidecar as vs
from bftkv_tpu.crypto import remote_verify, rsa
from bftkv_tpu.metrics import registry as metrics
from bftkv_tpu.ops import dispatch, rns

needs_native = pytest.mark.skipif(
    rsa._MM is None, reason="native modexp not built"
)


@pytest.fixture(scope="module")
def keys():
    return {2048: rsa.generate(2048), 3072: rsa.generate(3072)}


@pytest.fixture(autouse=True)
def fresh_metrics():
    metrics.reset()
    yield
    metrics.reset()


def oracle_sign(message: bytes, key: rsa.PrivateKey) -> bytes:
    em = rsa.emsa_pkcs1v15_sha256(message, key.size_bytes)
    return pow(em, key.d, key.n).to_bytes(key.size_bytes, "big")


def oracle_verify(message: bytes, sig: bytes, key: rsa.PublicKey) -> bool:
    s = int.from_bytes(sig, "big")
    if s >= key.n:
        return False
    em = rsa.emsa_pkcs1v15_sha256(message, key.size_bytes)
    return pow(s, key.e, key.n) == em


def mixed_items(keys) -> list:
    """Valid, forged, truncated and ``s >= n`` items of both widths,
    interleaved, eight of each kind and width."""
    items = []
    for i in range(8):
        for bits, key in keys.items():
            msg = b"width-%d-%d" % (bits, i)
            sig = oracle_sign(msg, key)
            forged = sig[:-1] + bytes([sig[-1] ^ 1])
            over = (key.n + 5).to_bytes(key.size_bytes, "big")
            items += [
                (msg, sig, key.public),
                (msg, forged, key.public),
                (msg, sig[:-3], key.public),
                (msg, over, key.public),
            ]
    return items


# -- the rule ----------------------------------------------------------------


@pytest.mark.parametrize("bits", [1024, 1536, 2048, 3072])
def test_the_rule_agrees_with_the_bases(bits):
    """``chains(bits).pow`` is whether ``RNSContext`` can be built at
    that width — on 12-bit channels to ~2,130 bits, on the wide chain's
    13-bit ones past that, to 4,096; the verify chain takes whole
    moduli up to 2048 bits."""
    try:
        ctx = rns.RNSContext(max(32, bits // 16), bits)
        built = True
    except ValueError:
        built = False
    assert rns.chains(bits).pow is built
    assert built is (bits <= rns.WIDE_MAX_BITS)
    assert not built or ctx.wide is (bits > 2048)
    assert rns.chains(bits).verify is (bits <= 2048)


def test_the_rule_at_the_edges():
    assert rns.chains(0) == rns.chains(-7) == (False, False)
    # the CRT halves of RSA-3072 and RSA-4096 ride the pow chain, the
    # moduli themselves do not ride the verify chain; whole moduli to
    # 4,096 bits ride the wide pow chain, and nothing wider does
    assert rns.chains(1536).pow and rns.chains(2048).pow
    assert not rns.chains(2049).verify and not rns.chains(4096).verify
    assert rns.chains(4096).pow and not rns.chains(4112).pow
    assert rsa.bits_class(1 << 3071) == 3072
    assert rsa.bits_class(1 << 2047) == 2048
    assert rsa.bits_class(1 << 5000) == "other"


# -- verify ------------------------------------------------------------------


@needs_native
def test_verify_batch_tiers_by_width_and_answers_as_the_oracle(keys):
    items = mixed_items(keys)
    want = [oracle_verify(*it) for it in items]
    assert sum(want) == 16
    vd = rsa.VerifierDomain(host_threshold=0)  # everything the chain takes
    got = vd.verify_batch(items)
    assert [bool(g) for g in got] == want
    m = metrics.snapshot()
    # every 3072-bit item on the native host tier, named and counted
    # with its width; none through the odd-exponent oracle branch
    assert m["verify.host.bits{bits=3072}"] == 32
    assert m.get("host.batch.python{op=verify}", 0) == 0
    # 2048-bit items: the chain, except s >= n (host, failing closed)
    assert m["verify.device"] == m["verify.device.bits{bits=2048}"] == 24
    assert m["verify.host.bits{bits=2048}"] == 8
    assert m["verify.host"] == 40


def test_an_odd_exponent_on_a_wide_key_is_still_the_oracles(keys):
    key = keys[3072]
    odd = rsa.PublicKey(n=key.n, e=3)
    msg = b"odd"
    vd = rsa.VerifierDomain(host_threshold=0)
    assert not vd.verify_batch([(msg, oracle_sign(msg, key), odd)])[0]
    assert "verify.host" not in metrics.snapshot()


@needs_native
def test_the_tenant_channel_keeps_wide_items_local(keys, tmp_path, monkeypatch):
    """Against an in-process sidecar: the 2048-bit items cross the
    wire, the 3072-bit ones never reach ``SidecarChannel.request``."""
    addr = f"unix:{tmp_path}/w.sock"
    srv, _t = vs.serve(addr)
    try:
        metrics.reset()
        rv = remote_verify.RemoteVerifierDomain(addr, spot_rate=0)
        sent: list = []
        real = rv.channel.request

        def request(op, payload):
            sent.append((op, vs.decode_request(payload)))
            return real(op, payload)

        monkeypatch.setattr(rv.channel, "request", request)
        items = mixed_items(keys)
        got = rv.verify_batch(items)
        assert [bool(g) for g in got] == [oracle_verify(*it) for it in items]
        assert len(sent) == 1 and sent[0][0] == vs.OP_VERIFY
        crossed = sent[0][1]
        assert len(crossed) == 32
        assert {k.n for _m, _s, k in crossed} == {keys[2048].n}
        m = metrics.snapshot()
        assert m["verify.local_wide"] == 32
        assert m["verify.remote"] == 32
        assert m.get("verify.remote_fallback", 0) == 0
    finally:
        srv.service.stop()
        srv.shutdown()
        srv.server_close()


# -- sign --------------------------------------------------------------------


def test_pow_chain_at_1536_bits_equals_pow(keys):
    key = keys[3072]
    dp = key.d % (key.p - 1)
    bases = [pow(7, i + 1, key.p) for i in range(64)]
    got = rns.power_mod_rns(
        bases, [dp] * 64, [key.p] * 64, n_bits=1536
    )
    assert got == [pow(b, dp, key.p) for b in bases]


@needs_native
def test_sign_batch_at_3072_bits_is_device_rows_fault_checked(keys):
    key = keys[3072]
    items = [(b"sign-%d" % i, key) for i in range(24)]
    sd = rsa.SignerDomain(host_threshold=0)
    assert sd.sign_batch(items) == [oracle_sign(m, k) for m, k in items]
    m = metrics.snapshot()
    assert m["sign.device"] == m["sign.device.bits{bits=3072}"] == 24
    assert "sign.host" not in m and "sign.rns_fallback" not in m
    # every signature checked, in native rows, none through pow
    assert m["host.batch.native{op=verify}"] == 24
    assert m.get("host.batch.python{op=verify}", 0) == 0


def fault_check(checks):
    """``SignerDomain._fault_check`` of ``[(key, s, em)]``: its maker
    hands it the integers and their bytes, made once."""
    keys, ss, ems = (list(c) for c in zip(*checks))
    return rsa.SignerDomain._fault_check(
        keys, ss, ems,
        [s.to_bytes(k.size_bytes, "big") for k, s in zip(keys, ss)],
        [em.to_bytes(k.size_bytes, "big") for k, em in zip(keys, ems)],
    )


@needs_native
def test_fault_check_catches_a_wrong_signature_at_either_width(keys):
    checks = []
    for i, (bits, key) in enumerate(sorted(keys.items()) * 2):
        em = rsa.emsa_pkcs1v15_sha256(b"fc-%d" % i, key.size_bytes)
        s = pow(em, key.d, key.n)
        if i >= 2:
            s ^= 1 << 9  # one faulted CRT half would look like this
        checks.append((key, s, em))
    assert fault_check(checks) == [True, True, False, False]


def test_rows_no_base_can_hold_sign_on_the_host(monkeypatch):
    """The pow chain stops where the prime supply does: such a key is
    the host tier's by the rule, not by a kernel error."""
    wide = rsa.generate(1024)
    sd = rsa.SignerDomain(host_threshold=0)
    monkeypatch.setattr(rns, "chains", lambda bits: rns.Chains(False, False))
    assert sd.sign_batch([(b"h", wide)]) == [oracle_sign(b"h", wide)]
    m = metrics.snapshot()
    assert m["sign.host"] == m["sign.host.bits{bits=1024}"] == 1
    assert "sign.rns_fallback" not in m and "sign.device" not in m


# -- the sidecar's warm-up ---------------------------------------------------


@needs_native
def test_warm_up_by_declared_width_never_compiles_in_a_request(
    keys, monkeypatch
):
    """A deployment that declares 3072: the sign buckets at 1,536-bit
    rows are built before the service exists, no verify bucket is
    (no chain holds the modulus); a 3072-bit sign flush then compiles
    nothing, and a 2048-bit one — undeclared — is counted and served
    from the host."""
    monkeypatch.setenv("BFTKV_IDENTITY_BITS", "3072")
    cal = {
        "backend": "test", "host_verify_s": 1e-4, "device_rtt_s": 1e-3,
        "verify_crossover": 16, "sign_crossover": None,
        "prefer_host": False, "source": "test",
    }
    monkeypatch.setattr(dispatch, "calibration", lambda force=False: cal)
    monkeypatch.setattr(rsa, "generate", lambda bits: keys[bits])
    svc = vs.SidecarService(max_batch=32)
    try:
        shapes = svc.warmup["shapes"]
        assert [(s["role"], s["items"], s["bits"]) for s in shapes] == [
            ("sign", 32, 3072), ("modexp", 32, 3072),
        ]
        assert svc.warmup["identity_bits"] == [3072]
        assert svc.warmup["pow_rows"] == [1536]
        assert svc.warmup["verify_chain"] is False
        assert svc.sign.signer.warm_rows == svc.modexp.warm_rows == {1536}
        assert svc.verify.verifier.chain_warm is False
        plane = svc.stats()["device_plane"]
        assert plane["compiled_since_warmup"] == 0
        assert metrics.snapshot()["sidecar.unwarmed_width"] == 0

        wide = [(b"w-%d" % i, keys[3072]) for i in range(24)]
        assert svc.sign.submit(wide) == [oracle_sign(m, k) for m, k in wide]
        m = metrics.snapshot()
        assert m["sign.device.bits{bits=3072}"] == 24
        assert m["sidecar.unwarmed_width"] == 0

        narrow = [(b"n-%d" % i, keys[2048]) for i in range(20)]
        assert svc.sign.submit(narrow) == [
            oracle_sign(m, k) for m, k in narrow
        ]
        k2 = keys[2048]
        rows = [(i + 2, k2.d % (k2.p - 1), k2.p) for i in range(20)]
        assert svc.modexp.submit(rows) == [pow(*r) for r in rows]
        # and a verify the chain could take, had it been built
        sig = oracle_sign(b"v", k2)
        ok = svc.verify.submit([(b"v", sig, k2.public)] * 20)
        assert all(ok)
        m = metrics.snapshot()
        assert m["sidecar.unwarmed_width"] == 60
        assert m["sign.host.bits{bits=2048}"] == 20
        assert m["modexp.host"] == 20
        assert m["verify.host.bits{bits=2048}"] == 20
        assert "verify.device" not in m
        assert svc.stats()["device_plane"]["compiled_since_warmup"] == 0
    finally:
        svc.stop()


@pytest.mark.parametrize("raw", ["", "rsa", "2048,x", "128", "99999"])
def test_a_declaration_that_is_no_width_fails_the_start(raw, monkeypatch):
    monkeypatch.setenv("BFTKV_IDENTITY_BITS", raw)
    with pytest.raises(ValueError, match="BFTKV_IDENTITY_BITS"):
        vs.identity_bits()


def test_the_default_declaration_is_todays_warm_up(monkeypatch):
    monkeypatch.delenv("BFTKV_IDENTITY_BITS", raising=False)
    assert vs.identity_bits() == [2048]
    monkeypatch.setenv("BFTKV_IDENTITY_BITS", "3072, 2048,3072")
    assert vs.identity_bits() == [2048, 3072]
