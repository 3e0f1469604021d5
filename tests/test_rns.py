"""RNS (residue number system) RSA verifier vs the host oracle.

Covers the Bajard/Shenoy base-extension math on real signatures, mixed
key sizes, adversarial inputs (bit flips, wrong keys, sig >= n, hostile
moduli sharing a factor with a channel prime), and the device chain
against the host oracle through VerifierDomain.
"""

import numpy as np
import pytest

from bftkv_tpu.crypto import rsa
from bftkv_tpu.ops import limb, rns


@pytest.fixture(scope="module")
def keys():
    return [rsa.generate(1024), rsa.generate(2048)]


def _verify_rns_direct(items):
    ctx = rns.context()
    rows, sig_d, em_d = [], [], []
    for message, sig_bytes, key in items:
        rows.append(ctx.key_rows(key.n))
        sig_d.append(limb.int_to_limbs(int.from_bytes(sig_bytes, "big"), 128))
        em_d.append(
            limb.int_to_limbs(
                rsa.emsa_pkcs1v15_sha256(message, key.size_bytes), 128
            )
        )
    key_rows = rns.stack_key_rows(rows)
    return np.asarray(
        rns.verify_e65537_rns(np.stack(sig_d), np.stack(em_d), key_rows)
    )


@pytest.mark.slow  # tier-2: heavy on a small-CPU tier-1 box (see pytest.ini)
def test_rns_matches_oracle_mixed_keys(keys):
    items = []
    want = []
    for i in range(6):
        key = keys[i % 2]
        m = b"rns-oracle-%d" % i
        sig = rsa.sign(m, key)
        if i == 2:
            sig = sig[:-1] + bytes([sig[-1] ^ 1])  # flipped bit
        if i == 4:
            m = b"tampered"
            # signature stays for the original message
            sig = rsa.sign(b"rns-oracle-4", key)
        items.append((m, sig, key.public))
        want.append(rsa.verify_host(m, sig, key.public))
    got = _verify_rns_direct(items)
    assert list(got) == want
    assert want == [True, True, False, True, False, True]


@pytest.mark.slow  # tier-2: heavy on a small-CPU tier-1 box (see pytest.ini)
def test_rns_wrong_key_rejected(keys):
    m = b"cross"
    sig = rsa.sign(m, keys[0])
    got = _verify_rns_direct([(m, sig, keys[1].public)] * 2)
    assert not got.any()


def test_verifier_domain_device_chain_agrees_with_host_oracle(keys):
    """The device chain and the host oracle (``verify_host_many``)
    return identical verdicts on the same adversarial batch."""
    key = keys[0]
    sig = rsa.sign(b"m", key)
    s = int.from_bytes(sig, "big")
    items = [
        (b"m", sig, key.public),
        (b"x", sig, key.public),
        (b"m", sig, keys[1].public),
        (b"m", (key.n + 5).to_bytes(key.size_bytes + 1, "big"), key.public),
        (b"m", sig[:-1] + bytes([sig[-1] ^ 1]), key.public),  # forged
        (b"m", sig[1:], key.public),  # short
        (b"m", b"\x00\x00" + sig, key.public),  # over-long, same integer
        (b"m", (s + key.n).to_bytes(key.size_bytes + 1, "big"), key.public),
        (b"m", key.n.to_bytes(key.size_bytes, "big"), key.public),  # s = n
    ]
    dom = rsa.VerifierDomain(host_threshold=0)
    got = list(dom.verify_batch(items))
    assert got == rsa.verify_host_many(items)
    assert got == [True, False, False, False, False, False, True, False, False]


def test_backend_name_validated():
    """``backend`` is a pinned keyword that selects nothing: the one
    chain's name or None pass, anything else is refused."""
    for name in ("rsn", "limb", "pallas"):
        with pytest.raises(ValueError):
            rsa.VerifierDomain(backend=name)
    rsa.VerifierDomain(backend="rns")
    rsa.VerifierDomain(backend=None)


def test_hostile_modulus_falls_back(keys):
    """A modulus sharing a factor with a channel prime cannot ride the
    RNS path; the verifier must fall back per item, not crash."""
    ctx = rns.context()
    p0 = ctx.pb[0]
    hostile_n = p0 * ((1 << 2000) // p0 + 1)  # divisible by a channel prime
    if hostile_n % 2 == 0:
        hostile_n += p0
    assert ctx.key_rows(hostile_n) is None
    dom = rsa.VerifierDomain(host_threshold=0, backend="rns")
    key = keys[0]
    sig = rsa.sign(b"m", key)
    items = [
        (b"m", sig, key.public),
        (b"m", sig, rsa.PublicKey(n=hostile_n)),
    ]
    ok = dom.verify_batch(items)
    assert ok[0] and not ok[1]


def test_rns_padding_rows_never_verify(keys):
    """Bucket padding uses sig=0 rows; a batch of 1 real item padded to
    256 must return exactly one True."""
    dom = rsa.VerifierDomain(host_threshold=0, backend="rns")
    key = keys[1]
    sig = rsa.sign(b"solo", key)
    ok = dom.verify_batch([(b"solo", sig, key.public)])
    assert ok.shape == (1,) and ok[0]


@pytest.mark.parametrize(
    "platform,n_devices",
    [("tpu", 1), ("tpu", 4), ("cpu", 1)],
)
def test_auto_backend_goes_by_platform_and_device_count(
    monkeypatch, platform, n_devices
):
    """``auto`` resolves from what the process observes — platform and
    device count — and from nothing outside the checkout.  Until a
    chip measurement judges the fused chains (ROADMAP S4) every case
    resolves to the XLA chains, what a fresh machine always ran: off
    TPU the fused chains would be interpreted, on several chips the
    sharded XLA path spreads the batch."""
    seen = []
    real = rns._auto_backend

    def spy(p, n, long_exp=False):
        seen.append((p, n))
        return real(p, n, long_exp)

    monkeypatch.setattr(rns.jax, "default_backend", lambda: platform)
    monkeypatch.setattr(rns.jax, "devices", lambda: ["chip"] * n_devices)
    monkeypatch.setattr(rns, "_auto_backend", spy)
    monkeypatch.delenv("BFTKV_RNS_POW_BACKEND", raising=False)
    monkeypatch.delenv("BFTKV_RNS_VERIFY_BACKEND", raising=False)
    assert rns._use_pallas("BFTKV_RNS_POW_BACKEND") is False
    assert rns._use_pallas("BFTKV_RNS_VERIFY_BACKEND") is False
    assert seen == [(platform, n_devices)] * 2


@pytest.mark.parametrize(
    "mode,want", [("pallas", True), ("xla", False)]
)
def test_forced_backend_never_consults_auto(monkeypatch, mode, want):
    monkeypatch.setattr(
        rns, "_auto_backend",
        lambda *a: pytest.fail("auto consulted for a forced backend"),
    )
    for env in ("BFTKV_RNS_POW_BACKEND", "BFTKV_RNS_VERIFY_BACKEND"):
        monkeypatch.setenv(env, mode)
        assert rns._use_pallas(env) is want


def test_pallas_retreat_is_counted_and_named(monkeypatch):
    """A fused chain that raises falls back to the XLA chain with the
    right answer — and a status string and a counter that the smoke
    reads as fatal."""
    from bftkv_tpu.metrics import registry as metrics
    from bftkv_tpu.ops import pallas_rns

    def boom(*a, **kw):
        raise RuntimeError("mosaic says no")

    monkeypatch.setattr(pallas_rns, "pow_pallas", boom)
    monkeypatch.setattr(
        rns, "_PALLAS_STATUS", {"pow": "unused", "verify": "unused"}
    )
    monkeypatch.setenv("BFTKV_RNS_POW_BACKEND", "pallas")
    before = metrics.snapshot().get("rns.pallas_fallback", 0)
    m = (1 << 511) + 111
    assert rns.power_mod_rns([5], [65537], [m], n_bits=512) == [
        pow(5, 65537, m)
    ]
    assert rns.pallas_status()["pow"] == "fallback: RuntimeError"
    assert metrics.snapshot()["rns.pallas_fallback"] == before + 1
