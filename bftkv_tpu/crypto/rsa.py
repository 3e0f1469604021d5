"""Host-side RSA primitives: key generation, PKCS#1 v1.5 encoding, signing.

Single-item client-side operations (a writer signs its own packet once per
write — reference: protocol/client.go:134) stay on host; *verification*,
the O(n²) per-write cluster cost, is batched on TPU via the RNS chains
of ``bftkv_tpu.ops.rns``, which alone decides what a chain can take
(``rns.chains``). The EMSA-PKCS1-v1_5 encoding mirrors what the
reference gets from Go's crypto/rsa (crypto/threshold/rsa/rsa.go:345-378).
"""

from __future__ import annotations

import contextlib
import functools
import hashlib
import logging
import os
import time
from collections import OrderedDict
from dataclasses import dataclass
from itertools import repeat

import numpy as np

from bftkv_tpu import trace
from bftkv_tpu.errors import ERR_INVALID_SIGNATURE
from bftkv_tpu.metrics import registry as metrics
from bftkv_tpu.ops import limb
from bftkv_tpu import flags
from bftkv_tpu.devtools.lockwatch import named_lock

log = logging.getLogger("bftkv_tpu.crypto.rsa")

# DigestInfo prefix for SHA-256 (RFC 8017 §9.2 note 1).
_SHA256_PREFIX = bytes.fromhex("3031300d060960864801650304020105000420")

F4 = 65537


@dataclass
class PublicKey:
    n: int
    e: int = F4

    @property
    def size_bytes(self) -> int:
        return (self.n.bit_length() + 7) // 8


@dataclass
class PrivateKey:
    n: int
    e: int
    d: int
    p: int
    q: int

    @property
    def public(self) -> PublicKey:
        return PublicKey(n=self.n, e=self.e)

    @property
    def size_bytes(self) -> int:
        return (self.n.bit_length() + 7) // 8

    def crt_params(self) -> tuple:
        """Cached CRT + Montgomery material: ``(dp, dq, qinv, pp, qp)``
        — one-time per key, consumed by :func:`_crt_pow_many`.  ``pp``
        and ``qp`` are :func:`_mont_params` of the primes, or None for
        a key the native modexp cannot take (an even or oversized
        "prime": ``pow`` then does the work)."""
        cached = self.__dict__.get("_crt")
        if cached is None:
            native = _native_ok(self.p) and _native_ok(self.q)
            cached = (
                self.d % (self.p - 1),
                self.d % (self.q - 1),
                pow(self.q, -1, self.p),
                _mont_params(self.p) if native else None,
                _mont_params(self.q) if native else None,
            )
            self.__dict__["_crt"] = cached
        return cached


def generate(bits: int = 2048) -> PrivateKey:
    """Generate an RSA key (host-side setup path).

    Provider chain: the host ``cryptography`` library when installed,
    the ``openssl`` CLI otherwise (the jax_graft image bakes in the
    binary but not the Python package), and a pure-Python
    Miller–Rabin generator as the last resort — setup-path only, never
    on a hot path."""
    try:
        from cryptography.hazmat.primitives.asymmetric import rsa as _rsa
    except Exception:
        try:
            return _generate_openssl(bits)
        except Exception:
            return _generate_py(bits)
    key = _rsa.generate_private_key(public_exponent=F4, key_size=bits)
    pn = key.private_numbers()
    return PrivateKey(
        n=pn.public_numbers.n,
        e=pn.public_numbers.e,
        d=pn.d,
        p=pn.p,
        q=pn.q,
    )


# -- dependency-free key generation (fallback providers) -------------------


def _der_ints(data: bytes) -> list[int]:
    """INTEGERs of one DER SEQUENCE (flat walk; enough for PKCS#1
    RSAPrivateKey and PKCS#8 unwrapping below)."""
    if not data or data[0] != 0x30:
        raise ValueError("der: not a SEQUENCE")
    body, _ = _der_tlv(data, 0)
    out: list[int] = []
    off = 0
    while off < len(body):
        tag = body[off]
        val, off = _der_tlv(body, off)
        if tag == 0x02:
            out.append(int.from_bytes(val, "big"))
    return out


def _der_tlv(data: bytes, off: int) -> tuple[bytes, int]:
    """Value bytes of the TLV at ``off`` plus the offset just past it."""
    if off + 2 > len(data):
        raise ValueError("der: truncated")
    length = data[off + 1]
    off += 2
    if length & 0x80:
        nlen = length & 0x7F
        if nlen == 0 or off + nlen > len(data):
            raise ValueError("der: bad length")
        length = int.from_bytes(data[off : off + nlen], "big")
        off += nlen
    if off + length > len(data):
        raise ValueError("der: truncated value")
    return data[off : off + length], off + length


def _pem_der(pem: bytes, marker: bytes) -> bytes:
    import base64

    start = pem.index(b"-----BEGIN " + marker + b"-----")
    end = pem.index(b"-----END " + marker + b"-----")
    b64 = b"".join(pem[start:end].splitlines()[1:])
    return base64.b64decode(b64)


def _generate_openssl(bits: int) -> PrivateKey:
    import subprocess

    pem = subprocess.run(
        ["openssl", "genrsa", str(bits)],
        capture_output=True,
        check=True,
        timeout=120,
    ).stdout
    if b"BEGIN RSA PRIVATE KEY" in pem:  # PKCS#1 (openssl 1.x)
        der = _pem_der(pem, b"RSA PRIVATE KEY")
    else:  # PKCS#8 (openssl 3.x): the key rides in an OCTET STRING
        der = _pem_der(pem, b"PRIVATE KEY")
        body, _ = _der_tlv(der, 0)
        off = 0
        while off < len(body):
            tag = body[off]
            val, off = _der_tlv(body, off)
            if tag == 0x04:
                der = val
                break
        else:
            raise ValueError("pkcs8: no key octet string")
    # RSAPrivateKey ::= SEQUENCE { version, n, e, d, p, q, dP, dQ, qInv }
    ints = _der_ints(der)
    if len(ints) < 6:
        raise ValueError("pkcs1: short key")
    _v, n, e, d, p, q = ints[:6]
    return PrivateKey(n=n, e=e, d=d, p=p, q=q)


def _is_probable_prime(n: int, rounds: int = 40) -> bool:
    import secrets

    if n < 2:
        return False
    for sp in (2, 3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37, 41, 43, 47):
        if n % sp == 0:
            return n == sp
    d, r = n - 1, 0
    while d % 2 == 0:
        d //= 2
        r += 1
    for _ in range(rounds):
        a = secrets.randbelow(n - 3) + 2
        x = pow(a, d, n)
        if x in (1, n - 1):
            continue
        for _ in range(r - 1):
            x = x * x % n
            if x == n - 1:
                break
        else:
            return False
    return True


def _gen_prime(bits: int, avoid: int = 0) -> int:
    import secrets

    while True:
        p = secrets.randbits(bits) | (1 << (bits - 1)) | (1 << (bits - 2)) | 1
        if p != avoid and p % F4 != 1 and _is_probable_prime(p):
            return p


def _generate_py(bits: int) -> PrivateKey:
    while True:
        p = _gen_prime(bits // 2)
        q = _gen_prime(bits - bits // 2, avoid=p)
        n = p * q
        if n.bit_length() != bits:
            continue
        phi = (p - 1) * (q - 1)
        try:
            d = pow(F4, -1, phi)
        except ValueError:
            continue
        return PrivateKey(n=n, e=F4, d=d, p=p, q=q)


_DIGEST_BYTES = hashlib.sha256().digest_size


def _emsa_head(em_len: int) -> bytes:
    """An EMSA-PKCS1-v1_5 encoding of ``em_len`` bytes up to its SHA-256
    digest: ``00 01 ff..ff 00 DigestInfo``, the same for every message."""
    ps_len = em_len - len(_SHA256_PREFIX) - _DIGEST_BYTES - 3
    if ps_len < 8:
        raise ERR_INVALID_SIGNATURE
    return b"\x00\x01" + b"\xff" * ps_len + b"\x00" + _SHA256_PREFIX


def emsa_pkcs1v15_sha256(message: bytes, em_len: int) -> int:
    """EMSA-PKCS1-v1_5 encoding of SHA-256(message), as an integer."""
    return int.from_bytes(
        _emsa_head(em_len) + hashlib.sha256(message).digest(), "big"
    )


# -- native Montgomery modexp (the RSA floor of the write path) -------------
# One RSA-2048 sign is two 1024-bit modexps; CPython's pow() runs them
# at ~4 ms each and holds the GIL throughout, capping a 4-signs-per-
# write protocol near 25 writes/s/core regardless of round structure.
# native/montmodexp.c takes a batch of rows in one call with the GIL
# released, each row on libcrypto's Montgomery exponentiation (the
# constant-time routine for a private exponent), or on its own CIOS
# loop where libcrypto is absent; ``_MM.engine`` says which.  pow()
# stays as the fallback AND the semantics oracle (differential tests
# in tests/test_rsa.py, tests/test_host_batch.py).  Disable with
# BFTKV_NATIVE_MODEXP=off.

_NATIVE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "native")
)


def _load_native_modexp(nd: str = _NATIVE_DIR):
    import importlib.util
    import subprocess
    import sysconfig

    if flags.raw("BFTKV_NATIVE_MODEXP", "auto") == "off":
        return None
    try:
        import fcntl

        inc = sysconfig.get_paths()["include"]
        suffix = sysconfig.get_config_var("EXT_SUFFIX")
        so_path = os.path.join(nd, f"_montmodexp{suffix}")
        src = os.path.join(nd, "montmodexp.c")
        # Check, build, AND load under the build lock: a concurrent
        # process's cc mid-write must never be exec_module()d as a
        # torn ELF (the silent-fallback except below would hide it as
        # a lifetime of slow pure-pow signing).
        with open(os.path.join(nd, ".mont.lock"), "w") as lk:
            fcntl.flock(lk, fcntl.LOCK_EX)
            if _stale_native(so_path, src):
                # -B: make's own mtime rule would keep a .so that is
                # newer than the source and still lacks the newest entry
                subprocess.run(
                    [
                        "make", "-s", "-B", "mont",
                        f"PY_INC={inc}", f"EXT_SUFFIX={suffix}",
                    ],
                    cwd=nd, check=True, capture_output=True,
                )
            spec = importlib.util.spec_from_file_location(
                "bftkv_tpu._montmodexp", so_path
            )
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
        return mod if _self_check(mod) else None
    except Exception:
        return None


#: The self-check's wide rows: a 1,024-bit odd modulus and a full-length
#: exponent, fixed bytes (any odd modulus will do for a modexp).
_CHECK_MOD = (
    int.from_bytes(hashlib.shake_256(b"montmodexp check m").digest(128), "big")
    | (1 << 1023) | 1
)
_CHECK_EXP = int.from_bytes(
    hashlib.shake_256(b"montmodexp check e").digest(128), "big"
) | (1 << 1023)


def _self_check(mod) -> bool:
    """Trust an extension for real signatures only where its rows equal
    ``pow``: a miscompiled or misbehaving engine must fall back, not
    corrupt the crypto plane.  Both routes an engine has — a public
    exponent and a full-length one — at 127 bits and at 1,024, on the
    edge bases, in one call a width."""
    if getattr(mod, "engine", None) not in _ENGINES:
        return False
    m127, m = (1 << 127) - 1, _CHECK_MOD
    bases = (0, 1, m - 1, _CHECK_EXP % m)
    for m, rows in (
        (m127, [(0xABCDEF123456789, F4), (m127 - 2, m127 - 2), (1, 0)]),
        (m, [(x, y) for x in bases for y in (F4, _CHECK_EXP)]),
    ):
        key_row, width = _mont_params(m)
        got = mod.powmod_many(
            width,
            width,
            b"".join(x.to_bytes(width, "big") for x, _ in rows),
            b"".join(y.to_bytes(width, "big") for _, y in rows),
            key_row * len(rows),
        )
        if got != b"".join(
            pow(x, y, m).to_bytes(width, "big") for x, y in rows
        ):
            return False
    return True


def _stale_native(so_path: str, src: str) -> bool:
    """Rebuild where the extension is missing, older than its source,
    or built from an older source, without the CIOS-only entry that
    came with the libcrypto engine (a ``.so`` carried over from an
    older tree): decided from the file, before anything
    is loaded — an extension module cannot be loaded twice."""
    if not os.path.exists(so_path) or (
        os.path.getmtime(so_path) < os.path.getmtime(src)
    ):
        return True
    with open(so_path, "rb") as f:
        return b"powmod_many_cios" not in f.read()


def _mont_params(mod: int) -> tuple:
    """``(key_row, width)`` for one odd modulus: the row is ``mod ||
    r2 || n0inv`` (``width`` + ``width`` + 8 bytes, big-endian), the
    form ``powmod_many`` takes one of per row."""
    width = (mod.bit_length() + 63) // 64 * 8
    r2 = pow(2, 2 * 8 * width, mod)
    n0 = (-pow(mod, -1, 1 << 64)) & ((1 << 64) - 1)
    return (
        mod.to_bytes(width, "big")
        + r2.to_bytes(width, "big")
        + n0.to_bytes(8, "big"),
        width,
    )


#: What ``_MM.engine`` may name: the rows' engine, and the name of the
#: counter of rows it ran (``host.modexp.<engine>``).
_ENGINES = ("libcrypto", "cios")

_MM = _load_native_modexp()
if _MM is not None:
    for _engine in _ENGINES:
        metrics.incr("host.modexp." + _engine, 0)  # a ratio has its denominator

#: Widest modulus the extension takes (native/montmodexp.c MAX_LIMBS).
_NATIVE_MAX_BITS = 4096


# -- the batched host tier ---------------------------------------------------
# Every RSA operation this process does on the host — the client's own
# signs and its check of every share, a daemon's self-check of what the
# sidecar signed, the local fallback after a shed — is rows of
# ``base ^ exp mod m``.  They cross into C once per chunk, not once per
# item (the int<->bytes hop of a per-item call holds the GIL), and a
# batch long enough to share is spread over one process-wide pool as
# wide as the cores the process may use.  A batch that makes a single
# chunk runs on the caller's thread: single operations never hop.

#: Rows below which a chunk is not worth a hop to the pool, by the
#: length of the exponent: ~2-5 ms of modexp either way (a public
#: exponent costs ~50 us a row at 2048 bits, a CRT half ~0.5 ms).
_CHUNK_ROWS_SHORT_EXP = 64
_CHUNK_ROWS_LONG_EXP = 8

_pool = None
_pool_lock = named_lock("crypto.rsa.host_pool")


def _pool_width() -> int:
    try:
        return max(1, len(os.sched_getaffinity(0)))
    except AttributeError:  # not on Linux
        return max(1, os.cpu_count() or 1)


def _host_pool():
    global _pool
    with _pool_lock:
        if _pool is None:
            from concurrent.futures import ThreadPoolExecutor

            _pool = ThreadPoolExecutor(
                max_workers=_pool_width(),
                thread_name_prefix="bftkv-hostrsa",
            )
        return _pool


def _powmod_chunk(width: int, rows: list) -> bytes:
    """One native call: rows of one width, ``(base, exp, (key_row, width))``."""
    ewidth = max(1, max((e.bit_length() + 7) // 8 for _b, e, _p in rows))
    return _MM.powmod_many(
        width,
        ewidth,
        b"".join([b.to_bytes(width, "big") for b, _e, _p in rows]),
        b"".join([e.to_bytes(ewidth, "big") for _b, e, _p in rows]),
        b"".join([p[0] for _b, _e, p in rows]),
    )


def _powmod_rows(rows: list) -> list[int]:
    """``[(base, exp, (key_row, width))]`` → ``[base^exp mod m]`` through
    the native batch entry; ``0 <= base < m`` and ``exp >= 0`` are the
    caller's.  Rows are grouped by width (one call takes one width),
    cut into chunks by the batch's length and, where that makes more
    than one, spread over the pool; the caller's thread takes a chunk
    itself."""
    out: list = [None] * len(rows)
    by_width: dict[int, list[int]] = {}
    for i, (_b, _e, (_k, width)) in enumerate(rows):
        by_width.setdefault(width, []).append(i)
    jobs: list[tuple[int, list[int]]] = []
    for width, idx in by_width.items():
        short = all(rows[i][1] < (1 << 32) for i in idx)
        per = _CHUNK_ROWS_SHORT_EXP if short else _CHUNK_ROWS_LONG_EXP
        # up to two chunks a core: the cores are shared with other
        # processes, and the slowest chunk sets the batch's time
        n = max(1, min(2 * _pool_width(), len(idx) // per))
        step = -(-len(idx) // n)
        jobs += [(width, idx[o : o + step]) for o in range(0, len(idx), step)]

    def run(job):
        width, idx = job
        return _powmod_chunk(width, [rows[i] for i in idx])

    if rows:
        metrics.incr("host.modexp." + _MM.engine, len(rows))
    futures = [_host_pool().submit(run, job) for job in jobs[1:]]
    results = [run(jobs[0])] if jobs else []
    results += [f.result() for f in futures]
    for (width, idx), res in zip(jobs, results):
        for j, i in enumerate(idx):
            out[i] = int.from_bytes(res[j * width : (j + 1) * width], "big")
    return out


def _native_ok(mod: int) -> bool:
    return mod & 1 == 1 and 2 < mod and mod.bit_length() <= _NATIVE_MAX_BITS


def _native_powmod(base: int, exp: int, params: tuple) -> int:
    return _powmod_rows([(base, exp, params)])[0]


#: modulus → ``_mont_params`` for PUBLIC moduli (a certificate builds a
#: fresh ``PublicKey`` on every access).  LRU-bounded: moduli arrive in
#: attacker-embedded certificates.  Private keys cache theirs on the
#: key object (``PrivateKey.crt_params``).
_PUB_PARAMS: "OrderedDict[int, tuple]" = OrderedDict()
_PUB_PARAMS_MAX = 4096
_pub_params_lock = named_lock("crypto.rsa.pub_params")


def _pub_params(n: int) -> tuple:
    with _pub_params_lock:
        p = _PUB_PARAMS.get(n)
        if p is not None:
            _PUB_PARAMS.move_to_end(n)
            return p
    p = _mont_params(n)
    with _pub_params_lock:
        _PUB_PARAMS[n] = p
        if len(_PUB_PARAMS) > _PUB_PARAMS_MAX:
            _PUB_PARAMS.popitem(last=False)
    return p


def powmod_host_many(items: list) -> list[int]:
    """``[(base, exp, mod)]`` → ``[base^exp mod mod]`` on this process's
    host tier: one native batch (:func:`_powmod_rows`) for the rows the
    extension takes, ``pow`` for the rest (an even or oversized
    modulus, a negative exponent, no extension)."""
    out: list = [None] * len(items)
    rows, at = [], []
    for i, (b, e, m) in enumerate(items):
        if _MM is not None and e >= 0 and _native_ok(m):
            rows.append((b % m, e, _pub_params(m)))
            at.append(i)
        else:
            out[i] = pow(b, e, m)
    for i, v in zip(at, _powmod_rows(rows)):
        out[i] = v
    return out


def _count_host_batch(op: str, native: int, python: int, t0: float) -> None:
    if native:
        metrics.incr("host.batch.native", native, labels={"op": op})
    if python:
        metrics.incr("host.batch.python", python, labels={"op": op})
    if native + python > 1:
        metrics.observe(
            "host.batch.seconds", time.perf_counter() - t0, labels={"op": op}
        )


def _crt_pow_many(pairs: list, op: str) -> list[int]:
    """``[(c, key)]`` → ``[c^d mod n]`` via CRT: both halves of every
    item are rows of one native batch; a key the extension cannot take
    (or no extension) goes through ``pow``."""
    t0 = time.perf_counter()
    out: list = [None] * len(pairs)
    rows: list = []
    native: list[tuple[int, int]] = []  # (item index, qinv)
    for i, (c, key) in enumerate(pairs):
        dp, dq, qinv, pp, qp = key.crt_params()
        if pp is not None and _MM is not None:
            rows += [(c % key.p, dp, pp), (c % key.q, dq, qp)]
            native.append((i, qinv))
        else:
            m1, m2 = pow(c, dp, key.p), pow(c, dq, key.q)
            out[i] = m2 + (qinv * (m1 - m2)) % key.p * key.q
    vals = _powmod_rows(rows)
    for j, (i, qinv) in enumerate(native):
        key = pairs[i][1]
        m1, m2 = vals[2 * j], vals[2 * j + 1]
        out[i] = m2 + (qinv * (m1 - m2)) % key.p * key.q
    _count_host_batch(op, len(native), len(pairs) - len(native), t0)
    return out


def crt_pow_d(c: int, key: PrivateKey) -> int:
    """``c^d mod n`` via CRT — the shared private-key primitive behind
    signing and OAEP unwrap, native-accelerated when the Montgomery
    extension is built."""
    return _crt_pow_many([(c, key)], "unwrap")[0]


def sign_many(items: list[tuple[bytes, PrivateKey]]) -> list[bytes]:
    """``[(message, key)]`` → PKCS#1 v1.5 signatures over
    SHA-256(message), CRT-accelerated: the host tier's batch form.
    Deterministic, so the bytes are those of any other correct signer."""
    pairs = [
        (emsa_pkcs1v15_sha256(message, key.size_bytes), key)
        for message, key in items
    ]
    return [
        s.to_bytes(key.size_bytes, "big")
        for s, (_m, key) in zip(_crt_pow_many(pairs, "sign"), pairs)
    ]


def sign(message: bytes, key: PrivateKey) -> bytes:
    """One PKCS#1 v1.5 signature: :func:`sign_many` of one item."""
    return sign_many([(message, key)])[0]


def _verify_oracle(message: bytes, sig: bytes, key: PublicKey) -> bool:
    """Python ``pow``: the semantics every other verify tier is held to."""
    s = int.from_bytes(sig, "big")
    if s >= key.n:
        return False
    return pow(s, key.e, key.n) == emsa_pkcs1v15_sha256(message, key.size_bytes)


def _sound_f4(key: PublicKey) -> bool:
    """e = 65537 on a modulus a verify tier can work with: odd, wide
    enough for the PKCS#1 encoding (512 bits) and no wider than the
    native extension takes.  Everything else is the oracle's."""
    return (
        key.e == F4 and key.n.bit_length() >= 512 and _native_ok(key.n)
    )


def _rows_match(triples: list) -> list[bool]:
    """``[(s, em, key)]`` → ``[s^65537 mod n == em]`` as rows of one
    native batch; ``s < n`` on a :func:`_sound_f4` key is the caller's."""
    params: dict[int, tuple] = {}  # a batch repeats a handful of keys
    rows: list = []
    for s, _em, key in triples:
        p = params.get(key.n)
        if p is None:
            p = params[key.n] = _pub_params(key.n)
        rows.append((s, F4, p))
    return [
        got == em for got, (_s, em, _k) in zip(_powmod_rows(rows), triples)
    ]


def _verify_rows(items: list, strict: bool) -> list[bool]:
    t0 = time.perf_counter()
    out = [False] * len(items)
    native: list[int] = []  # item indices
    triples: list = []
    python = 0
    for i, (message, sig, key) in enumerate(items):
        # e = 65537 on a sound modulus goes native; an odd exponent or
        # a junk key keeps the oracle's verdict, failing closed.
        if _MM is not None and _sound_f4(key):
            s = int.from_bytes(sig, "big")
            if s < key.n:
                native.append(i)
                triples.append(
                    (s, emsa_pkcs1v15_sha256(message, key.size_bytes), key)
                )
            continue
        python += 1
        try:
            out[i] = _verify_oracle(message, sig, key)
        except Exception:
            if strict:
                raise
    for i, ok in zip(native, _rows_match(triples)):
        out[i] = ok
    _count_host_batch("verify", len(items) - python, python, t0)
    return out


#: The widths a ``bits`` label may read: identity widths in use, and
#: ``other`` for whatever an attacker's certificate carries (labels
#: stay low-cardinality, DESIGN.md section 7).
_BITS_CLASSES = (1024, 2048, 3072, 4096)


def bits_class(n: int):
    """The ``bits`` label of modulus ``n``: the identity width it
    belongs to (the next of 1024 / 2048 / 3072 / 4096), else ``other``."""
    b = n.bit_length()
    return next((c for c in _BITS_CLASSES if b <= c), "other")


def count_tier(name: str, moduli, counts=None) -> None:
    """Count items on a tier (``verify.device``, ``verify.host``,
    ``sign.device``, ``sign.host``): the plain total every reader of
    the name has, and beside it ``<name>.bits{bits=...}`` by key width.
    One modulus an item, or distinct moduli with how many items each."""
    by_bits: dict = {}
    for n, k in zip(moduli, counts if counts is not None else repeat(1)):
        c = bits_class(n)
        by_bits[c] = by_bits.get(c, 0) + int(k)
    for c, k in by_bits.items():
        metrics.incr(name, k)
        metrics.incr(name + ".bits", k, labels={"bits": c})


def verify_host_many(
    items: list[tuple[bytes, bytes, PublicKey]]
) -> list[bool]:
    """``[(message, sig, key)]`` → verdicts: the host tier's batch form.
    Never raises for a key or a signature: junk fails closed."""
    if verify_host is not _verify_host:
        # The one-item form was replaced (a fault plant, a test
        # double): the batch form answers as it would.
        return [bool(verify_host(m, s, k)) for m, s, k in items]
    return _verify_rows(items, strict=False)


def verify_host(message: bytes, sig: bytes, key: PublicKey) -> bool:
    """Host verify of one item: :func:`verify_host_many` of one, except
    that a key no encoding fits raises as the oracle does."""
    return _verify_rows([(message, sig, key)], strict=True)[0]


_verify_host = verify_host


class SignerDomain:
    """Batched PKCS#1 v1.5 signing on device via CRT.

    Each signature is two half-width modexps (mod p and mod q), batched
    across concurrent requests into one RNS pow launch a row width
    (``ops.rns.pow_rows_rns``) — both halves of every signature ride
    in the *same* launch — plus a host-side CRT recombination and a
    fault check of every output before release.  An item rides when
    ``ops.rns`` takes its key: rows of a width the pow chain holds
    (``rns.chains``) and has a built program for
    (``rns.pow_rows_warm``), primes it has key rows for.  Every other
    item, and every batch below ``host_threshold`` items, is the host
    tier's (:func:`sign_many`): a launch costs tens of ms whatever its
    size.
    """

    HOST_CROSSOVER = 16

    def __init__(self, host_threshold: int | None = None):
        from bftkv_tpu import ops

        ops.enable_compile_cache()
        if host_threshold is None:
            host_threshold = int(
                flags.raw("BFTKV_HOST_SIGN_THRESHOLD", self.HOST_CROSSOVER)
            )
        self.host_threshold = host_threshold
        # key.n -> _SignKey, or None for a key the pow chain has no
        # rows for: one server signs every share with one key, so what
        # is a constant of the key is computed once a key.
        self._crt: "OrderedDict[int, _SignKey | None]" = OrderedDict()
        self._crt_lock = named_lock("crypto.rsa.montgomery")
        #: Row widths (bits) whose pow programs are built.  None:
        #: nobody said, a launch compiles on first use.  The sidecar
        #: says after its warm-up; a sign at another width then goes to
        #: the host tier and never compiles inside a request.
        self.warm_rows: frozenset | None = None
        # exist from the start (a scrape finds them; 0 reads as 0): the
        # fault check's, this domain's, its launches' key table's
        _count_staged(0, 0)
        _count_staged(0, 0, "sign")
        metrics.incr("pow.keytable.upload", 0)

    _CACHE_MAX = 1024  # distinct private keys in one trust domain: few

    def _sign_key(self, key: "PrivateKey") -> "_SignKey | None":
        """The constants of ``key`` that a sign launch needs
        (:class:`_SignKey`); None for a key whose CRT halves cannot
        ride the pow chain: rows the bases cannot hold
        (``rns.chains``), or a "prime" without rows at that width
        (even, or sharing a factor with a channel prime: a tenant may
        REGISTER any p * q = n).  Asked once a key, so that such a key
        costs its own items the device and not its width group:
        ``pow_rows_rns`` answers None for a whole launch when one
        modulus has no rows."""
        with self._crt_lock:
            rec = self._crt.get(key.n, False)
            if rec is not False:
                self._crt.move_to_end(key.n)
                return rec
        from bftkv_tpu.ops import rns as rns_ops

        bits = 16 * limb.nlimbs_for_bits(
            max(key.p.bit_length(), key.q.bit_length())
        )
        rec = None
        if rns_ops.chains(bits).pow:
            ctx = rns_ops.pow_context(bits)
            if (
                ctx.key_rows(key.p) is not None
                and ctx.key_rows(key.q) is not None
            ):
                rec = _SignKey(key, bits, ctx.digits)
        with self._crt_lock:
            self._crt[key.n] = rec
            if len(self._crt) > self._CACHE_MAX:
                self._crt.popitem(last=False)
        return rec

    def _sign_lane_rns(self, lane: "_SignLane", out: list) -> bool:
        """One RNS modexp launch for a width group: both CRT halves of
        every signature ride as rows with per-row modulus and secret
        exponent.  Returns False (leaving ``out`` untouched) when the
        launch cannot serve the group — the caller signs it on the
        host tier."""
        from bftkv_tpu.ops import rns as rns_ops

        t = len(lane.idx)
        vals = None
        try:
            vals = rns_ops.pow_rows_rns(
                lane.bits,
                [m for rec in lane.keys for m in (rec.p, rec.q)],
                lane.row_mod, lane.base_bytes,
                np.concatenate([rec.nib for rec in lane.keys]),
                lane.row_mod,  # dp rides with p, dq with q
                op="sign",
            )
        except Exception:
            log.exception("RNS sign launch failed")
        if vals is None:
            # sign_batch asked everything pow_rows_rns refuses a
            # launch by (row width, rows of every prime), so None is
            # as unexpected as a kernel failure.  Degrade, but loudly:
            # a silently broken RNS backend would misattribute every
            # bench number.
            metrics.incr("sign.rns_fallback")
            log.error(
                "RNS sign path served no launch of %d signs at %d-bit "
                "rows; signing them on the host tier", t, lane.bits,
            )
            return False
        count_tier(
            "sign.device", (rec.n for rec in lane.keys),
            np.bincount(lane.ksel, minlength=len(lane.keys)),
        )
        metrics.observe("sign.device_batch", t)
        ss: list[int] = []
        s_bytes: list[bytes] = []  # made once: the check's rows, out[i]
        with trace.leaf("flush.unpack", "sign", items=t):
            halves = iter(vals)
            for k, m1, m2 in zip(lane.ksel, halves, halves):
                rec = lane.keys[k]
                s = m2 + ((rec.qinv * (m1 - m2)) % rec.p) * rec.q
                ss.append(s)
                s_bytes.append(s.to_bytes(rec.size, "big"))
        # Fault check (Boneh–DeMillo–Lipton): one silently wrong CRT
        # half would let any observer factor the modulus via
        # gcd(s^e − em, n).  Verify every output before release — one
        # cheap e=65537 batch (17 modmuls) against the 1280-modmul
        # sign — and re-sign faulted items on the host.
        ok = self._fault_check(
            lane.item_keys, ss, lane.ems, s_bytes, lane.em_bytes
        )
        for i, key, em, sig, good in zip(
            lane.idx, lane.item_keys, lane.ems, s_bytes, ok
        ):
            if good:
                out[i] = sig
            else:
                metrics.incr("sign.fault")
                log.error(
                    "RNS sign fault check failed for one signature; "
                    "re-signing on host"
                )
                # Straight pow, no CRT: after a fault, produce the
                # signature by the most fault-immune route available.
                out[i] = pow(em, key.d, key.n).to_bytes(
                    key.size_bytes, "big"
                )
        return True

    @staticmethod
    def _fault_check(
        keys: list, ss: list[int], ems: list[int],
        s_bytes: list[bytes], em_bytes: list[bytes],
    ) -> list[bool]:
        """s^65537 ≡ em (mod n) for every produced signature: one RNS
        verify launch for the moduli the verify chain can take
        (``ops.rns.chains``), one native host batch for the sound
        moduli it cannot (RSA-3072 and wider), ``pow`` for the rest.
        Item for item: the key, the signature and the encoded message
        as integers, and the same two as the big-endian byte strings
        of the key's size that their maker already holds."""
        from bftkv_tpu.ops import rns as rns_ops

        ctx = None
        groups: dict = {}  # (n, e) -> _KeyGroup: asked once a key
        lanes: dict[int, _Lane] = {}  # key size in bytes -> its rows
        urows: list = []
        idxs: list[int] = []
        device_pos: list[int] = []
        host_pos: list[int] = []
        pulled = 0
        ok = [False] * len(ss)
        # The check is a verify launch of its own — stage, launch,
        # fetch, unpack — under the op of the sign it polices.
        with trace.leaf("flush.stage", "sign", items=len(ss)) as stage:
            for pos, key in enumerate(keys):
                g = groups.get((key.n, key.e))
                if g is None:
                    rows = None
                    chain = (
                        key.e == F4
                        and rns_ops.chains(key.n.bit_length()).verify
                    )
                    if chain:
                        ctx = ctx or rns_ops.context()
                        rows = ctx.key_rows(key.n)
                    g = groups[key.n, key.e] = _KeyGroup(
                        host_pos if rows is None else device_pos, chain, key.n
                    )
                    g.rows = rows
                    if rows is not None:
                        # the integers as whole rows, encodings included
                        g.lane = lanes.get(g.size)
                        if g.lane is None:
                            g.lane = lanes[g.size] = _Lane(g.size, g.size)
                g.idx.append(pos)
                if g.rows is None:
                    pulled += g.chain
                    continue
                if g.slot < 0:
                    g.slot = len(urows)
                    urows.append(g.rows)
                lane = g.lane
                lane.pos.append(len(idxs))
                lane.sigs.append(s_bytes[pos])
                lane.tails.append(em_bytes[pos])
                idxs.append(g.slot)
            _count_staged(len(device_pos), pulled)
            if device_pos:
                k = len(device_pos)
                bits = 16 * ctx.digits
                staged = _stage_verify_operands(
                    ctx, list(lanes.values()), idxs, urows
                )
                padded = len(staged[2])
                stage.attrs.update(bucket=padded, bits=bits)
        if host_pos:
            # Every one is checked, in one call: the native rows of the
            # host tier for a sound key (no ``pow`` per item under the
            # GIL), the oracle's ``pow`` for an odd exponent.  Host
            # work between the fetch and the answers: flush.unpack.
            with trace.leaf("flush.unpack", "sign", items=len(host_pos)):
                t0 = time.perf_counter()
                native: list[int] = []
                for pos in host_pos:
                    key, s = keys[pos], ss[pos]
                    if _MM is not None and _sound_f4(key) and s < key.n:
                        native.append(pos)
                    else:
                        ok[pos] = pow(s, key.e, key.n) == ems[pos]
                for pos, good in zip(native, _rows_match(
                    [(ss[pos], ems[pos], keys[pos]) for pos in native]
                )):
                    ok[pos] = good
                _count_host_batch(
                    "verify", len(native), len(host_pos) - len(native), t0
                )
        if device_pos:
            with trace.leaf(
                "flush.launch", "sign", items=k, bucket=padded, bits=bits
            ):
                dev = rns_ops.verify_e65537_rns_indexed(*staged)
            with trace.leaf("flush.fetch", "sign", items=k, bits=bits):
                good = np.asarray(dev)[:k]
            for pos, g in zip(device_pos, good):
                ok[pos] = bool(g)
            # The device check shares MXU/VPU machinery with the sign it
            # polices; a systematic device defect could correlate across
            # both.  Spot-check one random item per batch on the host —
            # over many batches a correlated defect cannot stay hidden
            # (ADVICE r3 low 3).
            import secrets as _secrets

            spot = device_pos[_secrets.randbelow(len(device_pos))]
            skey = keys[spot]
            host_ok = pow(ss[spot], skey.e, skey.n) == ems[spot]
            if host_ok != ok[spot]:
                metrics.incr("sign.fault_check_divergence")
                log.error(
                    "device fault check diverged from host spot check; "
                    "trusting the host verdict"
                )
                ok[spot] = ok[spot] and host_ok
        return ok

    def sign_batch(self, items: list[tuple[bytes, "PrivateKey"]]) -> list[bytes]:
        """[(message, key)] → [signature bytes], batched on device."""
        out: list[bytes | None] = [None] * len(items)
        # Device-eligible halves ride one launch a row width (p and q
        # of one key always share a width; different key sizes go in
        # separate launches so shapes stay uniform).
        lanes: dict[int, _SignLane] = {}
        host_idx: list[int] = []
        if len(items) < self.host_threshold:
            host_idx = list(range(len(items)))
        else:
            # Encodings and reduced bases: staging of the launches
            # below, the first interval of their flush.stage.  A flush
            # repeats a handful of keys hundreds of times: what a key
            # decides is asked once a distinct key, an item costs a
            # lookup of its key, SHA-256 and two reductions.
            with trace.leaf("flush.stage", "sign", items=len(items)):
                from bftkv_tpu.ops import rns as rns_ops

                groups: dict[int, _SignGroup] = {}  # key.n -> its items'
                owners: list[_SignGroup] = []  # beside items
                for _message, key in items:
                    g = groups.get(key.n)
                    if g is None:
                        g = groups[key.n] = _SignGroup(self._sign_key(key))
                    g.count += 1
                    owners.append(g)
                for g in groups.values():
                    # rows the bases cannot hold or a "prime" without
                    # rows (no record), a program nobody built: the
                    # host tier
                    rec = g.rec
                    if rec is None or not rns_ops.pow_rows_warm(
                        rec.bits, self.warm_rows, g.count
                    ):
                        continue
                    lane = lanes.get(rec.bits)
                    if lane is None:
                        lane = lanes[rec.bits] = _SignLane(rec.bits)
                    g.lane, g.k = lane, len(lane.keys)
                    lane.keys.append(rec)
                sha256 = hashlib.sha256
                for i, ((message, key), g) in enumerate(zip(items, owners)):
                    lane = g.lane
                    if lane is None:
                        host_idx.append(i)
                        continue
                    rec = g.rec
                    em_b = rec.head + sha256(message).digest()
                    em = int.from_bytes(em_b, "big")
                    lane.idx.append(i)
                    lane.item_keys.append(key)
                    lane.ksel.append(g.k)
                    lane.em_bytes.append(em_b)
                    lane.ems.append(em)
                    lane.base.append((em % rec.p).to_bytes(rec.row, "little"))
                    lane.base.append((em % rec.q).to_bytes(rec.row, "little"))
                for lane in lanes.values():
                    lane.close()
                _count_staged(
                    len(items) - len(host_idx), len(host_idx), "sign"
                )
        for lane in lanes.values():
            if not self._sign_lane_rns(lane, out):
                host_idx += lane.idx
        if host_idx:
            for i, sig in zip(
                host_idx, sign_many([items[i] for i in host_idx])
            ):
                out[i] = sig
            count_tier("sign.host", (items[i][1].n for i in host_idx))
        return out  # type: ignore[return-value]


class _SignKey:
    """What a sign launch needs of one private key, computed once a
    key and kept on ``SignerDomain._crt``: the CRT constants, the row
    width its halves ride at, and the two secret exponents as the
    pow chain scans them (``rns.exp_nibbles``: dp, then dq)."""

    __slots__ = (
        "bits", "row", "n", "p", "q", "qinv", "size", "head", "nib",
    )

    def __init__(self, key: "PrivateKey", bits: int, digits: int):
        from bftkv_tpu.ops import rns as rns_ops

        self.bits = bits  # of a row: p, q and the exponents fit
        self.row = 2 * digits  # a row's bytes
        self.n, self.p, self.q = key.n, key.p, key.q
        self.qinv = pow(key.q, -1, key.p)
        self.size = key.size_bytes  # a signature's length
        self.head = _emsa_head(self.size)
        self.nib = rns_ops.exp_nibbles(
            [key.d % (key.p - 1), key.d % (key.q - 1)], digits
        )


class _SignGroup:
    """One distinct key of a sign flush: its record, how many items
    name it, and — when its rows ride — the lane of its width and its
    place among that lane's keys."""

    __slots__ = ("rec", "count", "lane", "k")

    def __init__(self, rec: "_SignKey | None"):
        self.rec = rec
        self.count = 0
        self.lane: _SignLane | None = None
        self.k = -1


class _SignLane:
    """The rows of one sign launch — one row width: per item its place
    in the flush, its key (as the item names it, and among the lane's
    distinct ``keys``), its encoded message as bytes and as an
    integer; per row (two an item: mod p, mod q) the reduced base as
    ``row`` little-endian bytes."""

    __slots__ = (
        "bits", "keys", "idx", "item_keys", "ksel", "em_bytes", "ems",
        "base", "base_bytes", "row_mod",
    )

    def __init__(self, bits: int):
        self.bits = bits
        self.keys: list[_SignKey] = []
        self.idx: list[int] = []
        self.item_keys: list = []
        self.ksel: list[int] = []
        self.em_bytes: list[bytes] = []
        self.ems: list[int] = []
        self.base: list[bytes] = []
        self.base_bytes = b""
        self.row_mod: np.ndarray | None = None

    def close(self) -> None:
        """The per-item lists as what ``rns.pow_rows_rns`` takes: row
        2j is item j's half mod p (the lane's modulus 2k), row 2j + 1
        its half mod q (2k + 1), k the item's key among ``keys``."""
        self.base_bytes = b"".join(self.base)
        self.row_mod = (
            2 * np.asarray(self.ksel, dtype=np.intp)[:, None] + (0, 1)
        ).ravel()


class VerifierDomain:
    """Batched RSA e = 65537 verify of ``[(message, sig, key)]``.

    Heterogeneous batches mix keys freely: the RNS verify chain
    (``ops.rns.verify_e65537_rns_indexed``) gathers each row's key
    constants on device.  Three tiers, chosen per item by what the
    code can observe of the key: the device chain, for e = 65537 on a
    sound modulus the chain can take (``ops.rns.chains``); the native
    host tier, for e = 65537 on a sound modulus it cannot — RSA-3072 and
    wider identities — and for batches under the crossover; the host
    oracle, for a non-65537 exponent or a hostile modulus (even / zero
    / absurdly wide, reachable from attacker-embedded certificates),
    which fails closed.  Nothing raises out of the verification path.
    """

    #: Below this many items a batch verifies on host: a device launch
    #: costs ~tens of ms regardless of size, while a host e=65537 verify
    #: is ~0.2 ms — the device only wins past a few hundred items. 0
    #: forces everything through the kernel (tests, profiling).
    HOST_CROSSOVER = 192

    def __init__(
        self,
        host_threshold: int | None = None,
        backend: str | None = None,
    ):
        from bftkv_tpu import ops

        ops.enable_compile_cache()
        if host_threshold is None:
            host_threshold = flags.raw("BFTKV_HOST_VERIFY_THRESHOLD")
        #: Nobody chose the crossover (no argument, no flag, and no
        #: dispatcher has calibrated this domain since): the built-in
        #: default holds only where a device is behind it — see
        #: _stay_on_host.  Assigning ``host_threshold`` is a choice.
        self._builtin_threshold = host_threshold is None
        self._host_threshold = (
            self.HOST_CROSSOVER if host_threshold is None
            else int(host_threshold)
        )
        # ``backend`` selects nothing and reads no flag: one device
        # chain is left.  The keyword is pinned by
        # benchmarks/tests/test_stage_array_share.py, which passes
        # "rns" and which only a ``benchmark`` issue may edit
        # (ROADMAP D15).
        if backend not in (None, "rns"):
            raise ValueError(f"unknown verify backend {backend!r}")
        #: Whether the verify chain's programs are built.  None: nobody
        #: said, a launch compiles on first use.  The sidecar says after
        #: its warm-up (False where no declared identity width has a
        #: verify chain): a request then never compiles one.
        self.chain_warm: bool | None = None
        _count_staged(0, 0)  # exist from the start: 0 reads as 0

    @property
    def host_threshold(self) -> int:
        return self._host_threshold

    @host_threshold.setter
    def host_threshold(self, value: int) -> None:
        self._host_threshold = value
        self._builtin_threshold = False

    def _stay_on_host(self, n: int) -> bool:
        if n < self.host_threshold:
            return True
        if self._builtin_threshold:
            # First batch to reach the built-in crossover in a process
            # where nothing calibrated it — a plain client (bftrw, a
            # workload worker).  On the CPU backend the XLA kernels
            # lose to host ``pow`` at every batch size (the verdict
            # dispatch.calibration() reaches): 768 collective-signature
            # verifies cost ~14 s through CPU-XLA against ~0.2 s here.
            import jax

            # Decide first, mark decided last: the first call here
            # initialises the backend (seconds), and a second caller
            # arriving meanwhile must wait for the same verdict, not
            # find the mark already set and launch through CPU-XLA.
            on_cpu = jax.default_backend() == "cpu"
            if on_cpu:
                self._host_threshold = 1 << 30
            self._builtin_threshold = False
            return on_cpu
        return False

    def verify_batch(self, items: list[tuple[bytes, bytes, PublicKey]]) -> np.ndarray:
        """Batched TPU verify of [(message, sig, key)] → (batch,) bool."""
        from bftkv_tpu.crypto import cert as certmod  # lazy: cert imports rsa
        from bftkv_tpu.ops import rns

        out = np.zeros((len(items),), dtype=bool)
        device_idx: list[int] = []
        device_grp: list[_KeyGroup] = []  # beside device_idx
        ec_idx: list[int] = []
        wide_idx: list[int] = []
        odd_idx: list[int] = []
        unwarmed_idx: list[int] = []
        takes: dict[int, bool] = {}  # modulus bits -> the chain takes it
        # A flush repeats a handful of cluster keys thousands of times:
        # the tier rule is asked once a distinct key, an item costs a
        # lookup of its key.
        groups: dict = {}  # (n, e) -> _KeyGroup
        # The tier split is the first interval of the launch's
        # flush.stage where the batch is bound for the RNS chain (the
        # second, in _verify_rns, builds the operands).
        to_device = not self._stay_on_host(len(items))
        with (
            trace.leaf("flush.stage", "verify", items=len(items))
            if to_device else contextlib.nullcontext()
        ):
            for i, item in enumerate(items):
                key = item[2]
                try:
                    kid = (key.n, key.e)
                except AttributeError:
                    if not certmod.is_ec(key):
                        raise
                    # ECDSA P-256 identity keys: batched device verify
                    # via ops.ec (two scalar mults per item, one launch).
                    ec_idx.append(i)
                    continue
                g = groups.get(kid)
                if g is None:
                    if not _sound_f4(key):
                        # Host oracle for odd exponents; fails closed
                        # on junk keys.
                        g = _KeyGroup(odd_idx)
                    else:
                        bits = key.n.bit_length()
                        t = takes.get(bits)
                        if t is None:
                            t = takes[bits] = rns.chains(bits).verify
                        if t and self.chain_warm is False:
                            g = _KeyGroup(unwarmed_idx)
                        elif t:
                            g = _KeyGroup(device_idx, True, key.n)
                        else:
                            # A sound key the chain cannot hold
                            # (RSA-3072 and wider): the native host
                            # tier, as a tier.
                            g = _KeyGroup(wide_idx)
                    groups[kid] = g
                g.idx.append(i)
                if g.chain:
                    device_grp.append(g)
        unwarmed = len(unwarmed_idx)
        wide_idx += unwarmed_idx
        if unwarmed:
            rns.note_unwarmed("verifies", max(takes), unwarmed)
        if ec_idx:
            from bftkv_tpu.crypto import ecdsa as _ecdsa

            metrics.incr("verify.ec", len(ec_idx))
            out[np.asarray(ec_idx)] = np.asarray(
                _ecdsa.verify_batch([items[i] for i in ec_idx]), dtype=bool
            )
        if odd_idx:
            out[np.asarray(odd_idx)] = verify_host_many(
                [items[i] for i in odd_idx]
            )
        if device_idx and self._stay_on_host(len(device_idx)):
            # under the crossover: the host tier's too
            wide_idx += device_idx
            device_idx = []
        if wide_idx:
            wide = [items[i] for i in wide_idx]
            count_tier("verify.host", (k.n for _m, _s, k in wide))
            out[np.asarray(wide_idx)] = verify_host_many(wide)
        if device_idx:
            self._verify_rns(
                items,
                device_idx,
                device_grp,
                [g for g in groups.values() if g.chain],
                out,
            )
        return out

    def _verify_rns(
        self, items, device_idx, device_grp, chain_groups, out
    ) -> None:
        """RNS device path: the chain-bound items ``device_idx`` of
        ``items`` (``device_grp`` beside them, ``chain_groups`` their
        distinct keys), staged by :func:`_stage_verify_operands`, with
        a per-item route for what the arrays cannot take.

        Key rows are deduplicated host-side and gathered on device: a
        protocol flush repeats a handful of cluster keys thousands of
        times, and per-row key tensors (~12 KB each) would dominate
        the host→device bytes.
        """
        from bftkv_tpu.ops import rns

        ctx = rns.context()
        lanes: dict[int, _Lane] = {}  # key size in bytes -> its rows
        urows: list = []
        moduli: list[int] = []  # beside urows
        idxs: list[int] = []
        keep_idx: list[int] = []
        host_idx: list[int] = []
        pulled = 0
        sha256 = hashlib.sha256
        with trace.leaf(
            "flush.stage", "verify", items=len(device_idx)
        ) as sp:
            # Once a distinct key: its rows, its modulus as the bytes a
            # signature is compared with, the lane of its width.
            for g in chain_groups:
                g.rows = ctx.key_rows(g.n)
                if g.rows is None:
                    # No rows for this modulus (a factor shared with a
                    # channel prime).  No length matches: every item of
                    # the key takes the item route, to the host tier.
                    g.size = -1
                    continue
                g.n_bytes = g.n.to_bytes(g.size, "big")
                g.lane = lanes.get(g.size)
                if g.lane is None:
                    g.lane = lanes[g.size] = _Lane(g.size, _DIGEST_BYTES)
            for i, g in zip(device_idx, device_grp):
                message, sig, _key = items[i]
                if (
                    type(sig) is not bytes
                    or len(sig) != g.size
                    or sig >= g.n_bytes
                ):
                    # The item route, today as ever: a signature that
                    # is not the key's length in bytes (leading zeros,
                    # short, over-long) counts as the integer it
                    # denotes; s >= n and a rowless key are the host
                    # tier's, failing closed on junk.
                    pulled += 1
                    s = int.from_bytes(sig, "big")
                    if g.rows is None or s >= g.n:
                        host_idx.append(i)
                        continue
                    sig = s.to_bytes(g.size, "big")
                if g.slot < 0:
                    g.slot = len(urows)
                    urows.append(g.rows)
                    moduli.append(g.n)
                lane = g.lane
                lane.pos.append(len(idxs))
                lane.sigs.append(sig)
                lane.tails.append(sha256(message).digest())
                idxs.append(g.slot)
                keep_idx.append(i)
            _count_staged(len(device_idx) - pulled, pulled)
            if host_idx:
                host_items = [items[i] for i in host_idx]
                count_tier("verify.host", (k.n for _m, _s, k in host_items))
                out[np.asarray(host_idx)] = verify_host_many(host_items)
            if not idxs:
                return
            k = len(idxs)
            bits = 16 * ctx.digits
            staged = _stage_verify_operands(
                ctx, list(lanes.values()), idxs, urows
            )
            padded = len(staged[2])
            sp.attrs.update(bucket=padded, bits=bits)
            count_tier("verify.device", moduli, np.bincount(staged[2][:k]))
            metrics.observe("verify.device_batch", k)
        with trace.leaf(
            "flush.launch", "verify", items=k, bucket=padded, bits=bits
        ):
            dev = rns.verify_e65537_rns_indexed(*staged)
        with trace.leaf("flush.fetch", "verify", items=k, bits=bits):
            ok = np.asarray(dev)[:k]
        with trace.leaf("flush.unpack", "verify", items=k, bits=bits):
            out[np.asarray(keep_idx)] = ok


class _KeyGroup:
    """One distinct key of a flush: what it is asked once — the tier
    its items go to (``idx``, that tier's list of item indices) and,
    for a key bound for the RNS verify chain (``chain``), the material
    of :func:`_stage_verify_operands`' array route."""

    __slots__ = (
        "idx", "chain", "n", "size", "n_bytes", "rows", "slot", "lane",
    )

    def __init__(self, idx: list, chain: bool = False, n: int = 0):
        self.idx = idx
        self.chain = chain
        self.n = n
        self.size = (n.bit_length() + 7) // 8  # a signature's length
        self.n_bytes = b""  # n, big-endian in ``size`` bytes
        self.rows = None  # ``RNSContext.key_rows(n)``
        self.slot = -1  # its row among the launch's unique key rows
        self.lane: _Lane | None = None


class _Lane:
    """The rows of one verify launch whose numbers arrive as byte
    strings of one length: per row the signature, big-endian in
    ``size`` bytes, and the last ``tail`` bytes of its encoded message
    (the SHA-256 digest; or all ``size`` bytes, where the caller holds
    the encoding whole).  ``pos`` is each row's place in the launch."""

    __slots__ = ("size", "tail", "pos", "sigs", "tails")

    def __init__(self, size: int, tail: int):
        self.size = size
        self.tail = tail
        self.pos: list[int] = []
        self.sigs: list[bytes] = []
        self.tails: list[bytes] = []


@functools.lru_cache(maxsize=512)
def _em_template(size: int, width: int) -> np.ndarray:
    """The EMSA-PKCS1-v1_5 encoding of ``size`` bytes with a zero
    digest, as a row of ``width`` little-endian bytes: what every
    message under a key of that size shares."""
    row = np.zeros(width, dtype=np.uint8)
    head = _emsa_head(size)
    row[_DIGEST_BYTES:size] = np.frombuffer(head, dtype=np.uint8)[::-1]
    row.flags.writeable = False
    return row


def _count_staged(array: int, item: int, op: str = "verify") -> None:
    """Items of a device-bound flush staged by the array route, and
    those that left it.  ``verify``: chain-bound verify and fault-check
    items, and the ones pulled aside to the per-item route.  ``sign``:
    sign items whose pow rows were staged, and the ones sent to the
    host tier (a key without rows, rows no base holds, an unwarmed
    width)."""
    metrics.incr(op + ".stage.array", array)
    metrics.incr(op + ".stage.item", item)


def _stage_verify_operands(ctx, lanes: list, idxs: list, urows: list) -> tuple:
    """The operands of one verify launch, built from the rows' byte
    strings in whole-array steps: no integer and no array per row.

    The contract: ``rns.verify_e65537_rns_indexed`` takes a number as
    ``2 * ctx.digits`` uint8 halves of its 16-bit little-endian digits
    (``rns.digits_to_halves_u8``), which IS the number's little-endian
    byte string.  So a signature row is the signature's bytes reversed
    (zeros above its length), and an encoded-message row is its
    width's template (:func:`_em_template`) with the reversed SHA-256
    digest in bytes ``[0, 32)``.  Each lane's strings are joined, read
    as one ``(rows, size)`` array and reversed along the row into
    preallocated ``(padded, 2 * digits)`` arrays; the bucket is a power
    of two (floor 256), pad rows being signature 0 — 0^e never equals a
    PKCS#1 encoding — against row 0's encoding and key.  ``idxs`` maps
    each row to its key among ``urows``, whose axis is padded to a
    fixed floor of 64 (64 rows ≈ 800 KB of transfer — noise) so that
    the shape pair is a function of the bucket alone in any realistic
    cluster; more distinct keys escalate to the next power of two and
    pay one recompile.  Returns the arrays as the device is handed them
    (uint8 halves twice, int32 key index, stacked unique key rows), so
    that the call itself is the launch and nothing else.

    What cannot come this way is the callers' to pull aside, per item:
    a signature whose length is not its key's, or not below the
    modulus as bytes compare, and a key without rows.
    """
    from bftkv_tpu.ops import rns

    k = len(idxs)
    padded = max(256, 1 << (k - 1).bit_length())
    width = 2 * ctx.digits
    sig = np.zeros((padded, width), dtype=np.uint8)
    em = np.zeros((padded, width), dtype=np.uint8)
    for lane in lanes:
        if not lane.pos:
            continue
        rows = slice(0, k) if len(lane.pos) == k else np.asarray(lane.pos)
        if lane.tail < lane.size:
            em[rows] = _em_template(lane.size, width)
        for dst, size, strings in (
            (sig, lane.size, lane.sigs), (em, lane.tail, lane.tails)
        ):
            dst[rows, :size] = np.frombuffer(
                b"".join(strings), dtype=np.uint8
            ).reshape(len(strings), size)[:, ::-1]
    em[k:] = em[0]
    idx = np.zeros(padded, dtype=np.int32)
    idx[:k] = idxs
    kpad = max(64, 1 << (len(urows) - 1).bit_length())
    return sig, em, idx, rns.stack_key_rows(urows, pad_to=kpad)
