"""The plain reference: what a replica's disk must hold, read and
checked with nothing of the program.

A straightforward implementation of the same semantics as the store
under test, independent of it (nothing here imports ``bftkv_tpu``):

- the *model* of the store is a dict (``judge.py`` keeps it);
- this file reads what each replica daemon left on disk — the log
  engine's segment files — and checks every signature in a stored
  record with Python's ``pow`` and ``hashlib``:

      segment   crc32 | key_len u32 | t u64 | value_len u32 | key | value
      value     the write packet  chunk(x) chunk(v) t u64 sig ss [auth]
      chunk     u64 big-endian length | bytes
      sig / ss  type u8 | version u32 | completed u8 | chunk(data) | chunk(cert)
      data      repeated  signer_id u64 | chunk(signature)
      cert      "BCR1" chunk(n) e u32 chunk(name) chunk(address) chunk(uid)
                nsigs u16, nsigs x (signer_id u64 | chunk(sig))

  The writer signs the packet up to and including ``t``; the quorum
  members sign the packet up to and including the writer's signature.
  RSA signatures are PKCS#1 v1.5 over SHA-256.

Layouts as documented in the program's ``storage/segment.py``,
``packet.py`` and ``crypto/cert.py``; a later PR that changes one of
them on disk has to say so to this file's tests.
"""

from __future__ import annotations

import hashlib
import os
import re
import struct
import zlib
from dataclasses import dataclass

_SEG_HEADER = struct.Struct(">IIQI")
_SEG_NAME = re.compile(r"^seg-(\d{12})(?:-(\d{12})\.c(\d+))?\.log$")
_SHA256_PREFIX = bytes.fromhex("3031300d060960864801650304020105000420")
WRITE_ONCE_T = 2**64 - 1


class Malformed(ValueError):
    pass


# -- segment files ----------------------------------------------------------


def iter_segment(path: str):
    """``(key, t, value)`` of every intact record, in append order; stops
    at the first record whose checksum fails (a torn tail)."""
    with open(path, "rb") as f:
        data = f.read()
    off, n = 0, len(data)
    while off + _SEG_HEADER.size <= n:
        crc, klen, t, vlen = _SEG_HEADER.unpack_from(data, off)
        end = off + _SEG_HEADER.size + klen + vlen
        if end > n or zlib.crc32(data[off + 4 : end]) != crc:
            return
        body = off + _SEG_HEADER.size
        yield data[body : body + klen], t, data[body + klen : end]
        off = end


def read_store(root: str, wanted: set[bytes] | None = None
               ) -> dict[bytes, dict[int, bytes]]:
    """One replica's log store: ``key -> {t: stored packet}``, the last
    append of a ``(key, t)`` winning, as the engine replays it.
    ``wanted`` keeps only those keys (a run writes more than fits)."""
    segs = []
    for name in os.listdir(root) if os.path.isdir(root) else []:
        m = _SEG_NAME.match(name)
        if m:
            segs.append((int(m.group(1)), int(m.group(3) or 0), name))
    out: dict[bytes, dict[int, bytes]] = {}
    for _first, _gen, name in sorted(segs):
        for key, t, value in iter_segment(os.path.join(root, name)):
            if wanted is None or key in wanted:
                out.setdefault(key, {})[t] = value
    return out


# -- packets, signatures, certificates -------------------------------------


def _chunk(b: bytes, off: int) -> tuple[bytes, int]:
    if off + 8 > len(b):
        raise Malformed("torn chunk header")
    (ln,) = struct.unpack_from(">Q", b, off)
    off += 8
    if ln > len(b) - off:
        raise Malformed("chunk runs past the end")
    return b[off : off + ln], off + ln


@dataclass
class Sig:
    entries: list[tuple[int, bytes]]  # (signer id, signature)
    cert: bytes


def _signature(b: bytes, off: int) -> tuple[Sig | None, int]:
    if off + 6 > len(b):
        raise Malformed("torn signature header")
    typ = b[off]
    off += 6
    data, off = _chunk(b, off)
    cert, off = _chunk(b, off)
    if typ == 0:
        return None, off
    entries, p = [], 0
    while p < len(data):
        if p + 8 > len(data):
            raise Malformed("torn signer id")
        (sid,) = struct.unpack_from(">Q", data, p)
        s, p = _chunk(data, p + 8)
        entries.append((sid, s))
    return Sig(entries, cert), off


@dataclass
class Record:
    key: bytes
    value: bytes
    t: int
    writer: Sig | None
    quorum: Sig | None
    tbs: bytes   # what the writer signed
    tbss: bytes  # what the quorum members signed


def parse_record(pkt: bytes) -> Record:
    key, off = _chunk(pkt, 0)
    value, off = _chunk(pkt, off)
    if off + 8 > len(pkt):
        raise Malformed("no timestamp")
    (t,) = struct.unpack_from(">Q", pkt, off)
    off += 8
    tbs_end = off
    writer, off = _signature(pkt, off)
    tbss_end = off
    quorum = None
    if off < len(pkt):
        quorum, off = _signature(pkt, off)
    return Record(key, value, t, writer, quorum, pkt[:tbs_end], pkt[:tbss_end])


@dataclass
class Cert:
    id: int
    n: int
    e: int
    name: str


def parse_certs(data: bytes) -> list[Cert]:
    """The RSA certificates of a ring file (a concatenation)."""
    out, off = [], 0
    while off < len(data):
        magic = data[off : off + 4]
        if magic != b"BCR1":
            raise Malformed(f"certificate magic {magic!r}")
        nb, off = _chunk(data, off + 4)
        (e,) = struct.unpack_from(">I", data, off)
        off += 4
        name, off = _chunk(data, off)
        _addr, off = _chunk(data, off)
        _uid, off = _chunk(data, off)
        (nsigs,) = struct.unpack_from(">H", data, off)
        off += 2
        for _ in range(nsigs):
            _s, off = _chunk(data, off + 8)
        n = int.from_bytes(nb, "big")
        kid = hashlib.sha256(
            n.to_bytes((n.bit_length() + 7) // 8, "big") + struct.pack(">I", e)
        ).digest()[:8]
        out.append(Cert(int.from_bytes(kid, "big"), n, e, name.decode()))
    return out


def load_ring(keys_dir: str) -> dict[int, Cert]:
    """Every identity's public key, from the ``pubring`` files genkeys
    wrote (the deployment's identities; no result of a run)."""
    ring: dict[int, Cert] = {}
    for home in sorted(os.listdir(keys_dir)):
        path = os.path.join(keys_dir, home, "pubring")
        if os.path.isfile(path):
            with open(path, "rb") as f:
                for c in parse_certs(f.read()):
                    ring.setdefault(c.id, c)
    return ring


def rsa_verify(message: bytes, sig: bytes, n: int, e: int) -> bool:
    """RSASSA-PKCS1-v1_5 / SHA-256, by ``pow``."""
    k = (n.bit_length() + 7) // 8
    if len(sig) > k:
        return False
    t = _SHA256_PREFIX + hashlib.sha256(message).digest()
    if k < len(t) + 11:
        return False
    em = b"\x00\x01" + b"\xff" * (k - len(t) - 3) + b"\x00" + t
    return pow(int.from_bytes(sig, "big"), e, n) == int.from_bytes(em, "big")


def valid_signers(message: bytes, sig: Sig | None, ring: dict[int, Cert]) -> set[int]:
    """Distinct known signers whose signature over ``message`` holds."""
    good: set[int] = set()
    for sid, s in sig.entries if sig else []:
        c = ring.get(sid)
        if c is not None and sid not in good and rsa_verify(message, s, c.n, c.e):
            good.add(sid)
    return good


# -- plain RSA, for the tenant's own items ----------------------------------

_SMALL_PRIMES = [p for p in range(3, 2000, 2)
                 if all(p % q for q in range(3, int(p**0.5) + 1, 2))]


def _is_prime(n: int, rng) -> bool:
    if any(n % p == 0 for p in _SMALL_PRIMES):
        return n in _SMALL_PRIMES
    d, r = n - 1, 0
    while d % 2 == 0:
        d, r = d // 2, r + 1
    for _ in range(24):  # Miller-Rabin
        x = pow(rng.randrange(2, n - 1), d, n)
        if x in (1, n - 1):
            continue
        for _ in range(r - 1):
            x = x * x % n
            if x == n - 1:
                break
        else:
            return False
    return True


@dataclass
class RsaKey:
    n: int
    e: int
    p: int
    q: int
    d: int


def rsa_keygen(rng, bits: int = 2048, e: int = 65537) -> RsaKey:
    """An RSA key drawn from ``rng`` (a ``random.Random``)."""
    def prime(b: int) -> int:
        while True:
            c = rng.getrandbits(b) | (3 << (b - 2)) | 1
            if c % e != 1 and _is_prime(c, rng):
                return c

    p = prime(bits // 2)
    q = prime(bits - bits // 2)
    while q == p:
        q = prime(bits - bits // 2)
    return RsaKey(p * q, e, p, q, pow(e, -1, (p - 1) * (q - 1)))


def rsa_sign(message: bytes, key: RsaKey) -> bytes:
    """RSASSA-PKCS1-v1_5 / SHA-256 by ``pow`` (CRT halves)."""
    k = (key.n.bit_length() + 7) // 8
    t = _SHA256_PREFIX + hashlib.sha256(message).digest()
    em = int.from_bytes(b"\x00\x01" + b"\xff" * (k - len(t) - 3) + b"\x00" + t,
                        "big")
    sp = pow(em, key.d % (key.p - 1), key.p)
    sq = pow(em, key.d % (key.q - 1), key.q)
    s = sq + key.q * ((sp - sq) * pow(key.q, -1, key.p) % key.p)
    return s.to_bytes(k, "big")
