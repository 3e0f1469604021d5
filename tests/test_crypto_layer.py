"""Crypto layer: certs, keyring, signatures, collective sigs, messages.

Mirrors the reference's crypto behavior (crypto/pgp/crypto_pgp.go):
cert parse/sign/merge, detached sign/verify, collective combine until
sufficient, sign-then-encrypt with nonce echo, symmetric data encryption.
"""


import pytest

from bftkv_tpu.crypto import cert as certmod
from bftkv_tpu.crypto import dataenc, keyring, message, new_crypto, rsa, signature
from bftkv_tpu.errors import (
    ERR_DECRYPTION_FAILURE,
    ERR_INSUFFICIENT_NUMBER_OF_SIGNATURES,
    ERR_INVALID_SIGNATURE,
)

KEY_BITS = 1024  # small keys keep the suite fast; kernels are width-generic


@pytest.fixture(scope="module")
def identities():
    out = []
    for i in range(5):
        key = rsa.generate(KEY_BITS)
        c = certmod.Certificate(
            n=key.n,
            e=key.e,
            name=f"node{i}",
            address=f"http://127.0.0.1:{6000 + i}",
            uid=f"node{i}@example.test",
        )
        out.append((key, c))
    return out


class FixedQuorum:
    """Duck-typed quorum: sufficient once >= k distinct nodes."""

    def __init__(self, k):
        self.k = k

    def is_sufficient(self, nodes):
        return len({n.id for n in nodes}) >= self.k


def test_cert_roundtrip_and_id(identities):
    key, c = identities[0]
    blob = c.serialize()
    [parsed] = certmod.parse(blob)
    assert parsed.id == c.id
    assert parsed.name == "node0"
    assert parsed.address.endswith(":6000")
    assert parsed.uid == "node0@example.test"
    assert parsed.n == key.n


def test_cert_sign_merge_signers(identities):
    _, c = identities[0]
    c = certmod.parse(c.serialize())[0]  # fresh copy
    for key, signer_cert in identities[1:3]:
        certmod.sign_certificate(c, key)
    assert set(c.signers()) == {identities[1][1].id, identities[2][1].id}
    assert c.verify_signature(identities[1][1])
    assert not c.verify_signature(identities[3][1])
    # merge unions signature sets
    c2 = certmod.parse(c.serialize())[0]
    certmod.sign_certificate(c2, identities[3][0])
    c.merge(c2)
    assert set(c.signers()) == {
        identities[1][1].id,
        identities[2][1].id,
        identities[3][1].id,
    }


def test_parse_many(identities):
    blob = certmod.serialize_many([c for _, c in identities])
    parsed = certmod.parse(blob)
    assert [p.id for p in parsed] == [c.id for _, c in identities]


def test_keyring_register_merge_persist(identities, tmp_path):
    ring = keyring.Keyring()
    key0, c0 = identities[0]
    ring.register([c0], priv=key0)
    assert ring.lookup(c0.id) is c0
    assert ring.private_key(c0.id).d == key0.d
    # merging via re-register
    copy = certmod.parse(c0.serialize())[0]
    certmod.sign_certificate(copy, identities[1][0])
    ring.register([copy])
    assert identities[1][1].id in ring.lookup(c0.id).signers()
    # persistence
    ring.save_pubring(str(tmp_path / "pubring"))
    ring.save_secring(str(tmp_path / "secring"))
    ring2 = keyring.Keyring()
    ring2.load_pubring(str(tmp_path / "pubring"))
    ring2.load_secring(str(tmp_path / "secring"))
    assert ring2.lookup(c0.id).id == c0.id
    assert ring2.private_key(c0.id).d == key0.d


def test_detached_signature(identities):
    key, c = identities[0]
    s = signature.Signer(key, c)
    pkt = s.issue(b"hello world")
    assert signature.signers(pkt) == [c.id]
    signature.verify_with_certificate(b"hello world", pkt, c)
    with pytest.raises(ERR_INVALID_SIGNATURE):
        signature.verify_with_certificate(b"tampered", pkt, c)
    # issuer resolution from the embedded cert, no keyring
    got = signature.issuer(pkt, None)
    assert got.id == c.id


def test_collective_combine_and_verify(identities):
    tbss = b"<x,v,t,sig>"
    ring = keyring.Keyring()
    for _, c in identities:
        ring.register([c])
    cs = signature.CollectiveSignature(rsa.VerifierDomain())
    q = FixedQuorum(3)
    ss = None
    done = False
    for i, (key, c) in enumerate(identities[:3]):
        share = cs.sign(signature.Signer(key, c), tbss)
        ss, done = cs.combine(ss, share, q, ring)
        assert done == (i == 2)
    assert ss.completed
    cs.verify(tbss, ss, q, ring)
    # not sufficient for a larger quorum
    with pytest.raises(ERR_INSUFFICIENT_NUMBER_OF_SIGNATURES):
        cs.verify(tbss, ss, FixedQuorum(4), ring)
    # tampered message fails
    with pytest.raises(ERR_INSUFFICIENT_NUMBER_OF_SIGNATURES):
        cs.verify(b"other", ss, q, ring)


def test_collective_verify_without_keyring_uses_embedded_certs(identities):
    tbss = b"payload"
    cs = signature.CollectiveSignature(rsa.VerifierDomain())
    q = FixedQuorum(2)
    ss = None
    for key, c in identities[:2]:
        share = cs.sign(signature.Signer(key, c), tbss)
        ss, _ = cs.combine(ss, share, q, None)
    empty = keyring.Keyring()
    cs.verify(tbss, ss, q, empty)


def test_duplicate_signer_counted_once(identities):
    tbss = b"dup"
    cs = signature.CollectiveSignature(rsa.VerifierDomain())
    key, c = identities[0]
    q = FixedQuorum(2)
    ss = None
    for _ in range(3):
        share = cs.sign(signature.Signer(key, c), tbss)
        ss, done = cs.combine(ss, share, q, None)
    assert not done
    with pytest.raises(ERR_INSUFFICIENT_NUMBER_OF_SIGNATURES):
        cs.verify(tbss, ss, q, keyring.Keyring())


def test_message_security_roundtrip(identities):
    skey, scert = identities[0]
    rkey, rcert = identities[1]
    sender = message.MessageSecurity(skey, scert)
    recipient = message.MessageSecurity(rkey, rcert)
    blob = sender.encrypt([rcert, identities[2][1]], b"secret payload", b"nonce42")
    pt, peer, nonce = recipient.decrypt(blob)
    assert pt == b"secret payload"
    assert peer.id == scert.id
    assert nonce == b"nonce42"
    # third recipient can also decrypt
    third = message.MessageSecurity(identities[2][0], identities[2][1])
    pt2, _, _ = third.decrypt(blob)
    assert pt2 == b"secret payload"
    # non-recipient cannot
    outsider = message.MessageSecurity(identities[3][0], identities[3][1])
    with pytest.raises(ERR_DECRYPTION_FAILURE):
        outsider.decrypt(blob)


def test_message_tamper_detected(identities):
    skey, scert = identities[0]
    rkey, rcert = identities[1]
    sender = message.MessageSecurity(skey, scert)
    recipient = message.MessageSecurity(rkey, rcert)
    blob = bytearray(sender.encrypt([rcert], b"payload", b"n"))
    blob[-1] ^= 0xFF
    with pytest.raises(ERR_DECRYPTION_FAILURE):
        recipient.decrypt(bytes(blob))


def test_dataenc_roundtrip():
    key = b"some derived key material"
    ct = dataenc.encrypt(b"hello", key)
    assert dataenc.decrypt(ct, key) == b"hello"
    with pytest.raises(ERR_DECRYPTION_FAILURE):
        dataenc.decrypt(ct, b"wrong key")


def test_crypto_bundle(identities):
    key, c = identities[0]
    cr = new_crypto(key, c)
    assert cr.signer.cert.id == c.id
    assert cr.keyring.lookup(c.id) is c
    pkt = cr.signer.issue(b"m")
    signature.verify_with_certificate(b"m", pkt, c)
