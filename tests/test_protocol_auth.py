"""TPA roaming + threshold-CA through live servers
(reference: protocol/roaming_test.go:15-29, dist_test.go:29-105)."""

from __future__ import annotations

import hashlib

import pytest

from bftkv_tpu.crypto import rsa
from bftkv_tpu.crypto.threshold import ThresholdAlgo
from bftkv_tpu.errors import Error

from cluster_utils import modexp_route, start_cluster

BITS = 2048


@pytest.fixture(scope="module")
def cluster():
    c = start_cluster(n_servers=4, n_users=2, n_rw=4, bits=BITS)
    yield c
    c.stop()


@pytest.fixture(params=["local", "sidecar"])
def modexp_domain(request, tmp_path):
    """The servers' modexps in-process, and with the modexp domain a
    ``--sidecar`` daemon installs (None / the ``RemoteModexpDomain``)."""
    with modexp_route(request.param, tmp_path) as domain:
        yield domain


def _remote_items() -> float:
    from bftkv_tpu.metrics import registry

    return registry.snapshot().get("modexp.remote", 0)


def test_tpa_roundtrip(cluster):
    """First authenticate sets up the shared secret; a later one (the
    'roaming' device) recovers the same cipher key
    (reference: roaming_test.go:15-29)."""
    cli = cluster.clients[0]
    proof, key = cli.authenticate(b"tpa_var", b"correct horse")
    assert proof is not None and key
    proof2, key2 = cli.authenticate(b"tpa_var", b"correct horse")
    assert key2 == key


def test_tpa_wrong_password(cluster):
    cli = cluster.clients[0]
    cli.authenticate(b"tpa_wp", b"right password")
    with pytest.raises(Error):
        cli.authenticate(b"tpa_wp", b"wrong password")


def test_tpa_protected_write_read(cluster):
    """The proof gates reads on servers that hold the auth params —
    the quorum servers, which stored them at setAuth/sign time
    (reference: server.go:181-185; full value secrecy additionally
    comes from API-layer symmetric encryption, api.go:149-163)."""
    from bftkv_tpu import packet as pkt
    from bftkv_tpu.errors import ERR_AUTHENTICATION_FAILURE

    cli = cluster.clients[0]
    proof, _key = cli.authenticate(b"tpa_rw", b"pw1")
    cli.write(b"tpa_rw", b"secret-value", proof=proof)
    assert cli.read(b"tpa_rw", proof=proof) == b"secret-value"
    # A quorum server holds the auth params (stored at setAuth/sign
    # time) and refuses any read of the protected variable without the
    # proof; with the proof it answers (with no completed version —
    # W = U − {Ci} + R keeps completed writes off the clique servers,
    # reference: wotqs.go:108-110).
    srv = cluster.servers[0]
    with pytest.raises(ERR_AUTHENTICATION_FAILURE):
        srv._read(pkt.serialize(b"tpa_rw", None, 0, None, None), None, None)
    raw = srv._read(pkt.serialize(b"tpa_rw", None, 0, None, proof), None, None)
    # The clique never holds a COMPLETED version (W = U − {Ci} + R);
    # since the round collapse it may serve its commit-pending copy —
    # uncertified, so a reader accepts it only through the resolve
    # path.  Either way: no certified record here.
    if raw is not None:
        p = pkt.parse(raw)
        assert p.ss is not None and not p.ss.completed


def test_threshold_rsa_ca(cluster, modexp_domain):
    """Distribute an RSA CA key, threshold-sign, verify against the
    public key (reference: dist_test.go:29-105)."""
    cli = cluster.clients[0]
    key = rsa.generate(2048)
    cli.distribute("ca-rsa", key)
    tbs = b"an X.509 to-be-signed blob"
    sent = _remote_items()
    sig = cli.dist_sign("ca-rsa", tbs, ThresholdAlgo.RSA, "sha256")
    assert rsa.verify_host(tbs, sig, key.public)
    assert sig == rsa.sign(tbs, key)  # the undealt key's, byte for byte
    # a 2,048-bit CA's first-level fragments went the installed way
    assert (_remote_items() - sent >= 3) is (modexp_domain is not None)


def test_threshold_dsa_ca(cluster):
    from bftkv_tpu.crypto.threshold import dsa as tdsa

    cli = cluster.clients[0]
    key = tdsa.generate(1024)
    cli.distribute("ca-dsa", key)
    tbs = b"dsa signing payload"
    sig = cli.dist_sign("ca-dsa", tbs, ThresholdAlgo.DSA, "sha256")
    # standard DSA verify: v = (g^u1 · y^u2 mod p) mod q == r
    size = (key.q.bit_length() + 7) // 8
    r = int.from_bytes(sig[:size], "big")
    s = int.from_bytes(sig[size:], "big")
    assert 0 < r < key.q and 0 < s < key.q
    ops = tdsa._DSAGroupOps(key.p, key.q, key.g)
    m = ops.os2i(hashlib.sha256(tbs).digest())
    w = pow(s, -1, key.q)
    v = (
        pow(key.g, m * w % key.q, key.p)
        * pow(key.y, r * w % key.q, key.p)
    ) % key.p % key.q
    assert v == r


def test_threshold_ecdsa_ca(cluster):
    pytest.importorskip("cryptography")  # oracle cross-check needs the host lib
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec as cec
    from cryptography.hazmat.primitives.asymmetric.utils import (
        encode_dss_signature,
    )

    from bftkv_tpu.crypto import ec
    from bftkv_tpu.crypto.threshold import ecdsa as tec

    cli = cluster.clients[0]
    key = tec.generate(ec.P256)
    cli.distribute("ca-ec", key)
    tbs = b"ecdsa signing payload"
    sig = cli.dist_sign("ca-ec", tbs, ThresholdAlgo.ECDSA, "sha256")
    r = int.from_bytes(sig[:32], "big")
    s = int.from_bytes(sig[32:], "big")
    pub = key.curve.scalar_base_mult(key.d)
    pubkey = cec.EllipticCurvePublicNumbers(
        pub[0], pub[1], cec.SECP256R1()
    ).public_key()
    pubkey.verify(encode_dss_signature(r, s), tbs, cec.ECDSA(hashes.SHA256()))


@pytest.mark.slow  # tier-2: heavy on a small-CPU tier-1 box (see pytest.ini)
def test_threshold_repeated_rounds_5_of_9():
    """Repeated dist_sign rounds at (t,n)=(5,9): regression for the
    session-reordering race — a second signing round's server-to-server
    share envelopes (relayed through the client, no transport retry
    channel) must stay decryptable even when the recipient never saw
    the dealer's earlier session bootstrap."""
    from bftkv_tpu.crypto.threshold import ecdsa as tec
    from bftkv_tpu.crypto import ec

    c = start_cluster(n_servers=9, n_users=1, n_rw=4, bits=1024)
    try:
        cli = c.clients[0]
        key = rsa.generate(1024)
        cli.distribute("rrca-rsa", key)
        eckey = tec.generate(ec.P256)
        cli.distribute("rrca-ec", eckey)
        for i in range(2):
            sig = cli.dist_sign(
                "rrca-rsa", b"round-%d" % i, ThresholdAlgo.RSA, "sha256"
            )
            assert rsa.verify_host(b"round-%d" % i, sig, key.public)
        for i in range(2):
            sig = cli.dist_sign(
                "rrca-ec", b"ec-round-%d" % i, ThresholdAlgo.ECDSA, "sha256"
            )
            assert len(sig) == 64
    finally:
        c.stop()


def test_threshold_x509_issuance(cluster, modexp_domain):
    """The threshold CA issues a real X.509 certificate: template TBS
    threshold-signed, certificate reassembled, verifiable with the
    standard library against the CA public key
    (reference: cmd/bftrw/bftrw.go:216-302)."""
    import datetime

    pytest.importorskip("cryptography")  # X.509 interop needs the host lib
    from cryptography import x509
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import (
        padding as cpadding,
        rsa as crsa,
    )

    from bftkv_tpu.cmd.bftrw import threshold_sign_x509

    cli = cluster.clients[0]
    ca_key = rsa.generate(2048)
    cli.distribute("x509-ca", ca_key)

    # Build a template: self-signed leaf with a SubjectKeyId.
    leaf = crsa.generate_private_key(public_exponent=65537, key_size=2048)
    name = x509.Name([x509.NameAttribute(x509.NameOID.COMMON_NAME, "leaf")])
    now = datetime.datetime(2026, 1, 1)
    template = (
        x509.CertificateBuilder()
        .subject_name(name)
        .issuer_name(name)
        .public_key(leaf.public_key())
        .serial_number(x509.random_serial_number())
        .not_valid_before(now)
        .not_valid_after(now + datetime.timedelta(days=365))
        .add_extension(
            x509.SubjectKeyIdentifier.from_public_key(leaf.public_key()),
            critical=False,
        )
        .sign(leaf, hashes.SHA256())
    )

    class _Api:  # the slice of api.API threshold_sign_x509 needs
        def sign(self, caname, tbs, algo, hash_name):
            return cli.dist_sign(caname, tbs, algo, hash_name)

    out_der = threshold_sign_x509(_Api(), "x509-ca", template.public_bytes(
        serialization.Encoding.DER))
    issued = x509.load_der_x509_certificate(out_der)
    assert issued.tbs_certificate_bytes == template.tbs_certificate_bytes
    ca_pub = crsa.RSAPublicNumbers(ca_key.e, ca_key.n).public_key()
    ca_pub.verify(
        issued.signature,
        issued.tbs_certificate_bytes,
        cpadding.PKCS1v15(),
        hashes.SHA256(),
    )
