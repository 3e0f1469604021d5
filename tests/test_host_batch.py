"""The batched host tier (crypto/rsa.py ``sign_many`` /
``verify_host_many`` over native ``powmod_many``): same answers as the
Python ``pow`` oracle on every kind of item, the same bytes as
``rsa.sign``, inline below the chunk floor and pooled above it, the
Python fallback counted, right under concurrent callers, and the call
sites that moved onto it (daemon self-check, ``issue_many``) still
doing what they did."""

from __future__ import annotations

import os
import shutil
import sys
import threading

import pytest

from bftkv_tpu.crypto import cert as certmod
from bftkv_tpu.crypto import rsa, vcache
from bftkv_tpu.crypto.signature import Signer, verify_with_certificate
from bftkv_tpu.metrics import registry as metrics

needs_native = pytest.mark.skipif(
    rsa._MM is None, reason="native modexp not built"
)


@pytest.fixture(scope="module")
def keys():
    """One key per width the issue names, and a second 1024-bit one."""
    return {
        "a1024": rsa.generate(1024),
        "b1024": rsa.generate(1024),
        "k2048": rsa.generate(2048),
        "k3072": rsa.generate(3072),
    }


@pytest.fixture(autouse=True)
def fresh_metrics():
    metrics.reset()
    yield
    metrics.reset()


def oracle_sign(message: bytes, key: rsa.PrivateKey) -> bytes:
    """Straight ``pow``, no CRT, nothing native."""
    em = rsa.emsa_pkcs1v15_sha256(message, key.size_bytes)
    return pow(em, key.d, key.n).to_bytes(key.size_bytes, "big")


def oracle_verify(message: bytes, sig: bytes, key: rsa.PublicKey) -> bool:
    try:
        return rsa._verify_oracle(message, sig, key)
    except Exception:
        return False


def pool_threads() -> list[str]:
    return [
        t.name for t in threading.enumerate()
        if t.name.startswith("bftkv-hostrsa")
    ]


def counts(op: str) -> tuple[int, int]:
    """(native, python) items so far.  The registry is the worker
    process's: a thread an earlier test file left behind may add to
    it, so the tests below assert floors, and 0 only for the path
    nothing can take."""
    snap = metrics.snapshot()
    return (
        snap.get("host.batch.native{op=%s}" % op, 0),
        snap.get("host.batch.python{op=%s}" % op, 0),
    )


def hostile_items(keys) -> list:
    """Valid, forged, truncated, oversized, odd-exponent, junk-key and
    mixed-width items, with what each kind is."""
    out = []
    for name, key in keys.items():
        pub = key.public
        msg = b"msg-" + name.encode()
        sig = oracle_sign(msg, key)
        out += [
            ("valid", msg, sig, pub),
            ("wrong message", msg + b"!", sig, pub),
            ("flipped bit", msg, sig[:-1] + bytes([sig[-1] ^ 1]), pub),
            ("truncated", msg, sig[:-1], pub),
            ("empty", msg, b"", pub),
            ("leading zeros", msg, b"\x00\x00" + sig, pub),
            ("s = n", msg, key.n.to_bytes(key.size_bytes, "big"), pub),
            ("s > n", msg, b"\xff" * (key.size_bytes + 1), pub),
            ("other key", msg, sig, keys["b1024"].public
             if name != "b1024" else keys["a1024"].public),
        ]
    k = keys["a1024"]
    # e = 3: a signature made for that exponent verifies on the oracle
    phi = (k.p - 1) * (k.q - 1)
    if phi % 3:
        d3 = pow(3, -1, phi)
        em = rsa.emsa_pkcs1v15_sha256(b"e3", k.size_bytes)
        s3 = pow(em, d3, k.n).to_bytes(k.size_bytes, "big")
        out.append(("e = 3 valid", b"e3", s3, rsa.PublicKey(n=k.n, e=3)))
    sig = oracle_sign(b"junk", k)
    out += [
        ("e = 3 forged", b"junk", sig, rsa.PublicKey(n=k.n, e=3)),
        ("e = 1", b"junk", sig, rsa.PublicKey(n=k.n, e=1)),
        ("even modulus", b"junk", sig, rsa.PublicKey(n=k.n + 1)),
        ("n = 0", b"junk", sig, rsa.PublicKey(n=0)),
        ("n < 0", b"junk", sig, rsa.PublicKey(n=-k.n)),
        ("sub-512-bit key", b"junk", b"\x01" * 32,
         rsa.PublicKey(n=(1 << 255) | 1)),
        ("tiny key", b"junk", b"\x01", rsa.PublicKey(n=35)),
        ("over 4096 bits", b"junk", sig, rsa.PublicKey(n=(1 << 4200) | 1)),
    ]
    return out


def test_verify_many_matches_the_pow_oracle_on_hostile_items(keys):
    items = hostile_items(keys)
    got = rsa.verify_host_many([(m, s, k) for _w, m, s, k in items])
    want = [oracle_verify(m, s, k) for _w, m, s, k in items]
    assert got == want, [w for (w, *_), g, o in zip(items, got, want) if g != o]
    kinds = {w for (w, *_), g in zip(items, got) if g}
    assert kinds <= {"valid", "leading zeros", "e = 3 valid"}
    assert "valid" in kinds and "leading zeros" in kinds
    # the one-item form is the same code
    for (_w, m, s, k), o in zip(items, want):
        try:
            assert rsa.verify_host(m, s, k) == o
        except Exception:
            assert not o  # a key no encoding fits raises, as it did


@pytest.mark.parametrize("name", ["a1024", "k2048", "k3072"])
def test_sign_many_is_byte_equal_to_the_oracle_and_to_sign(keys, name):
    key = keys[name]
    msgs = [b"w-%d" % i for i in range(20)]
    sigs = rsa.sign_many([(m, key) for m in msgs])
    assert sigs == [oracle_sign(m, key) for m in msgs]
    assert sigs[:3] == [rsa.sign(m, key) for m in msgs[:3]]
    assert all(len(s) == key.size_bytes for s in sigs)


def test_sign_many_takes_mixed_keys_in_one_batch(keys):
    items = [
        (b"mix-%d" % i, list(keys.values())[i % len(keys)])
        for i in range(24)
    ]
    sigs = rsa.sign_many(items)
    assert sigs == [oracle_sign(m, k) for m, k in items]
    assert rsa.verify_host_many(
        [(m, s, k.public) for (m, k), s in zip(items, sigs)]
    ) == [True] * len(items)


def test_empty_and_single_batches_run_inline(keys):
    """No pool thread for a batch that makes one chunk: single
    operations (and YCSB-A's batches of 1-4) never hop."""
    key = keys["a1024"]
    before = set(pool_threads())
    tids = []
    orig = rsa._powmod_chunk

    def spy(width, rows):
        tids.append(threading.get_ident())
        return orig(width, rows)

    rsa._powmod_chunk = spy
    try:
        assert rsa.sign_many([]) == []
        assert rsa.verify_host_many([]) == []
        sig = rsa.sign_many([(b"one", key)])[0]
        assert rsa.verify_host_many([(b"one", sig, key.public)]) == [True]
        four = rsa.sign_many([(b"f-%d" % i, key) for i in range(3)])
        assert rsa.verify_host_many(
            [(b"f-%d" % i, s, key.public) for i, s in enumerate(four)]
        ) == [True] * 3
    finally:
        rsa._powmod_chunk = orig
    if rsa._MM is not None:
        assert tids and set(tids) == {threading.get_ident()}
    assert set(pool_threads()) == before
    # a batch of one is not a batch: only the batches of 3 were timed
    snap = metrics.snapshot()
    assert 1 <= snap["host.batch.seconds.count{op=sign}"] < 3
    assert 1 <= snap["host.batch.seconds.count{op=verify}"] < 3


@needs_native
def test_a_long_batch_is_spread_over_the_pool(keys):
    key = keys["a1024"]
    msgs = [b"p-%d" % i for i in range(64)]
    tids = set()
    orig = rsa._powmod_chunk

    def spy(width, rows):
        tids.add(threading.get_ident())
        return orig(width, rows)

    rsa._powmod_chunk = spy
    try:
        sigs = rsa.sign_many([(m, key) for m in msgs])
    finally:
        rsa._powmod_chunk = orig
    assert sigs == [oracle_sign(m, key) for m in msgs]
    assert threading.get_ident() in tids  # the caller takes a chunk
    if rsa._pool_width() > 1:
        assert len(tids) > 1 and pool_threads()
    assert len(pool_threads()) <= rsa._pool_width()
    native, python = counts("sign")
    assert native >= 64 and python == 0
    snap = metrics.snapshot()
    assert snap["host.batch.seconds.count{op=sign}"] >= 1
    assert snap["host.batch.seconds.sum{op=sign}"] > 0


def test_pool_width_is_the_cores_the_process_may_use():
    assert rsa._pool_width() == len(os.sched_getaffinity(0))


def test_native_off_gives_the_same_answers_and_counts_python(keys, monkeypatch):
    """BFTKV_NATIVE_MODEXP=off (or a failed build) leaves ``_MM`` None:
    the same entry points run the ``pow`` loop and say so."""
    monkeypatch.setenv("BFTKV_NATIVE_MODEXP", "off")
    assert rsa._load_native_modexp() is None
    items = hostile_items(keys)
    with_native = rsa.verify_host_many([(m, s, k) for _w, m, s, k in items])
    key = keys["k2048"]
    sigs = rsa.sign_many([(b"off-%d" % i, key) for i in range(5)])
    metrics.reset()
    monkeypatch.setattr(rsa, "_MM", None)
    assert rsa.verify_host_many(
        [(m, s, k) for _w, m, s, k in items]
    ) == with_native
    assert rsa.sign_many([(b"off-%d" % i, key) for i in range(5)]) == sigs
    native, python = counts("verify")
    assert native == 0 and python >= len(items)
    native, python = counts("sign")
    assert native == 0 and python >= 5


@needs_native
def test_eight_callers_at_once_are_right_and_all_native(keys):
    """8 threads x 768 items (a q4 ``write_many``'s share checks, all
    callers at once), a fifth of them forged."""
    key_list = [keys["a1024"], keys["b1024"], keys["k2048"]]
    pool = []
    for i in range(48):
        k = key_list[i % 3]
        m = b"c-%d" % i
        pool.append((m, rsa.sign(m, k), k.public))
    forged = [(m + b"x", s, k) for m, s, k in pool[:12]]
    batch = [(pool + forged)[i % 60] for i in range(768)]
    want = [i % 60 < 48 for i in range(768)]
    metrics.reset()
    results: dict[int, list] = {}
    errors: list = []

    def caller(j: int):
        try:
            results[j] = rsa.verify_host_many(batch[j:] + batch[:j])
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=caller, args=(j,)) for j in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert not errors
    for j in range(8):
        assert results[j] == want[j:] + want[:j]
    native, python = counts("verify")
    assert native >= 8 * 768 and python == 0
    assert metrics.snapshot()["host.batch.seconds.count{op=verify}"] >= 8


def test_a_replaced_one_item_oracle_governs_the_batch_form(keys, monkeypatch):
    """benchmarks/plants.py plants ``accept_all`` on the host tier by
    replacing ``rsa.verify_host``; the batch form must answer as the
    replacement would."""
    key = keys["a1024"]
    items = [(b"x", b"\x01" * 128, key.public)] * 3
    assert rsa.verify_host_many(items) == [False] * 3
    monkeypatch.setattr(rsa, "verify_host", lambda *_a, **_k: True)
    assert rsa.verify_host_many(items) == [True] * 3


# -- the engine ------------------------------------------------------------


def operands(rows: list) -> tuple:
    """``powmod_many``'s operands for ``[(base, exp, mod)]``, one width."""
    width = rsa._mont_params(rows[0][2])[1]
    ewidth = max(1, max((e.bit_length() + 7) // 8 for _b, e, _m in rows))
    return (
        width,
        ewidth,
        b"".join(b.to_bytes(width, "big") for b, _e, _m in rows),
        b"".join(e.to_bytes(ewidth, "big") for _b, e, _m in rows),
        b"".join(rsa._mont_params(m)[0] for _b, _e, m in rows),
    )


@needs_native
@pytest.mark.parametrize("entry", ["powmod_many", "powmod_many_cios"])
def test_one_call_of_many_moduli_in_any_order_matches_pow(entry):
    """More distinct moduli than the engine keeps Montgomery contexts
    for, each met again after others (a client's CRT halves alternate
    p, q, p, q), at both exponent forms: every row is ``pow``'s."""
    import random

    rng = random.Random(20)
    mods = [rng.getrandbits(1024) | (1 << 1023) | 1 for _ in range(20)]
    rows = []
    for i in range(60):
        m = mods[(i * 7) % len(mods)] if i % 3 else mods[i % 2]
        e = rsa.F4 if i % 2 else rng.getrandbits(1024)
        rows.append((rng.getrandbits(1024) % m, e, m))
    width = 128
    got = getattr(rsa._MM, entry)(*operands(rows))
    assert got == b"".join(
        pow(b, e, m).to_bytes(width, "big") for b, e, m in rows
    )


@needs_native
def test_mixed_widths_across_pool_chunks_match_pow():
    """One host-tier batch of three widths and both exponent forms,
    long enough to be cut into several chunks a width."""
    import random

    rng = random.Random(21)
    rows, want = [], []
    for bits in (1024, 1536, 2048):
        mods = [rng.getrandbits(bits) | (1 << (bits - 1)) | 1 for _ in range(3)]
        for i in range(24):
            m = mods[i % 3]
            e = rng.getrandbits(bits // 2) if i % 2 else rsa.F4
            b = rng.getrandbits(bits) % m
            rows.append((b, e, rsa._mont_params(m)))
            want.append(pow(b, e, m))
    order = list(range(len(rows)))
    rng.shuffle(order)
    chunks = []
    orig = rsa._powmod_chunk

    def spy(width, chunk):
        chunks.append(width)
        return orig(width, chunk)

    rsa._powmod_chunk = spy
    try:
        got = rsa._powmod_rows([rows[i] for i in order])
    finally:
        rsa._powmod_chunk = orig
    assert got == [want[i] for i in order]
    assert set(chunks) == {128, 192, 256} and len(chunks) > 3


@needs_native
def test_sign_many_of_rsa3072_keys_is_the_pure_paths(keys, monkeypatch):
    """The engine's CRT halves at 1,536 bits give the bytes the ``pow``
    path gives, key by key in one batch."""
    k3072 = keys["k3072"]
    items = [(b"w3072-%d" % i, k3072) for i in range(12)]
    items += [(b"w2048-%d" % i, keys["k2048"]) for i in range(4)]
    native = rsa.sign_many(items)
    monkeypatch.setattr(rsa, "_MM", None)
    assert rsa.sign_many(items) == native


@needs_native
def test_the_engine_counters_count_rows_under_the_reported_engine(keys):
    """``host.modexp.<engine>`` grows by rows — two a CRT sign, one a
    verify — under the name the extension reports; the other engine's
    counter does not move."""
    engine = rsa._MM.engine
    assert engine in rsa._ENGINES
    name = "host.modexp." + engine
    other = ["host.modexp." + e for e in rsa._ENGINES if e != engine][0]
    key = keys["k2048"]
    before = metrics.snapshot().get(name, 0)
    sigs = rsa.sign_many([(b"n-%d" % i, key) for i in range(5)])
    mid = metrics.snapshot().get(name, 0)
    assert mid - before >= 10
    assert rsa.verify_host_many(
        [(b"n-%d" % i, s, key.public) for i, s in enumerate(sigs)]
    ) == [True] * 5
    assert metrics.snapshot().get(name, 0) - mid >= 5
    assert metrics.snapshot().get(other, 0) == 0


class WrongRow:
    """The real extension, with one row of one route made wrong: the
    first row of ``width`` bytes whose exponent is public (``route``
    "public") or longer than 4 bytes ("secret")."""

    def __init__(self, route: str, width: int):
        self.engine = rsa._MM.engine
        self.route, self.width = route, width

    def powmod_many(self, width, ewidth, bases, exps, keys):
        out = bytearray(rsa._MM.powmod_many(width, ewidth, bases, exps, keys))
        if width != self.width:
            return bytes(out)
        for r in range(len(bases) // width):
            e = exps[r * ewidth : (r + 1) * ewidth].lstrip(b"\0")
            if (len(e) > 4) == (self.route == "secret"):
                out[(r + 1) * width - 1] ^= 1
                break
        return bytes(out)


@needs_native
@pytest.mark.parametrize("width", [16, 128])
@pytest.mark.parametrize("route", ["public", "secret"])
def test_the_self_check_refuses_an_engine_with_one_wrong_row(route, width):
    assert rsa._self_check(rsa._MM)
    assert not rsa._self_check(WrongRow(route, width))


@needs_native
def test_the_self_check_refuses_an_engine_it_does_not_know():
    fake = WrongRow("public", 0)  # every row right
    assert rsa._self_check(fake)
    fake.engine = "gmp"
    assert not rsa._self_check(fake)


# -- the call sites ---------------------------------------------------------


def test_self_check_trips_on_one_forged_signature_in_256(tmp_path, keys):
    """The daemon still checks EVERY signature the sidecar returns: one
    forged among 256 opens the breaker and the batch is re-signed."""
    from bftkv_tpu.cmd import verify_sidecar as vs
    from bftkv_tpu.crypto.remote_verify import RemoteSignerDomain

    key = keys["a1024"]
    srv, _t = vs.serve(f"unix:{tmp_path}/crypto.sock")
    try:
        items = [(b"sc-%d" % i, key) for i in range(256)]

        def one_forged(batch):
            sigs = rsa.sign_many(batch)
            sigs[137] = sigs[136]
            return sigs

        srv.service.sign.submit = one_forged
        metrics.reset()
        sd = RemoteSignerDomain(f"unix:{tmp_path}/crypto.sock")
        sigs = sd.sign_batch(items)
        assert sigs == [oracle_sign(m, k) for m, k in items]
        snap = metrics.snapshot()
        assert snap.get("crypto.sidecar.dishonest", 0) >= 1
        assert snap.get("sign.remote_fallback", 0) >= 256
        assert sd.channel.tripped()
        # the honest service passes the same check
        srv.service.sign.submit = rsa.sign_many
        metrics.reset()
        sd2 = RemoteSignerDomain(f"unix:{tmp_path}/crypto.sock")
        assert sd2.sign_batch(items) == sigs
        snap = metrics.snapshot()
        assert snap.get("crypto.sidecar.dishonest", 0) == 0
        assert snap.get("host.batch.native{op=verify}", 0) + snap.get(
            "host.batch.python{op=verify}", 0
        ) >= 256
    finally:
        srv.service.stop()
        srv.shutdown()
        srv.server_close()


def test_callers_arriving_together_all_stay_on_host(monkeypatch):
    """A plain client's first big verify decides host-or-device by
    asking JAX for its backend, which takes seconds the first time.
    Callers that arrive meanwhile (all eight do, now that their signs
    take 0.1 s) must get the same verdict — one of them used to find
    the question marked as answered and launch through CPU-XLA (31 s
    in one warm call of chip run ``b4c4``, PR 27)."""
    import time

    import jax

    dom = rsa.VerifierDomain()
    assert dom._builtin_threshold
    asked = threading.Event()

    def slow_backend():
        asked.set()
        time.sleep(0.3)
        return "cpu"

    monkeypatch.setattr(jax, "default_backend", slow_backend)
    verdicts: list[bool] = []
    first = threading.Thread(
        target=lambda: verdicts.append(dom._stay_on_host(768))
    )
    first.start()
    assert asked.wait(10)
    verdicts.append(dom._stay_on_host(768))  # arrives mid-question
    first.join(timeout=10)
    assert not first.is_alive()
    assert verdicts == [True, True]
    assert dom._stay_on_host(768) and not dom._builtin_threshold


def test_issue_many_still_seeds_the_verify_memo(keys):
    key = keys["a1024"]
    cert = certmod.Certificate(n=key.n, e=key.e, name="hb", uid="hb")
    vcache.reset()
    try:
        msgs = [b"seed-%d" % i for i in range(12)]
        pkts = Signer(key, cert).issue_many(msgs)
        assert metrics.snapshot().get("verify.cache.seeded", 0) >= 12
        calls = []
        orig = certmod.verify_detached
        certmod.verify_detached = lambda *a: calls.append(a) or orig(*a)
        try:
            for m, p in zip(msgs, pkts):
                verify_with_certificate(m, p, cert)
        finally:
            certmod.verify_detached = orig
        assert calls == [], "a seeded signature was verified again"
    finally:
        vcache.reset()


# -- the build --------------------------------------------------------------


@needs_native
@pytest.mark.parametrize("stale", ["older than the source", "no batch entry"])
def test_a_stale_extension_is_rebuilt_not_loaded(tmp_path, stale):
    """A ``.so`` left by an older tree — older than ``montmodexp.c``, or
    newer but built before ``powmod_many`` existed — is rebuilt under
    the lock, and what loads passes the self-check of both entries."""
    nd = tmp_path / "native"
    nd.mkdir()
    for name in ("Makefile", "montmodexp.c"):
        shutil.copy(os.path.join(rsa._NATIVE_DIR, name), nd / name)
    so = nd / os.path.basename(rsa._MM.__file__)
    # what a tree without the batch entry left behind: not loadable as
    # this extension, and without the entry's name in it
    so.write_bytes(b"\x7fELF stale build of _montmodexp: powmod only")
    src = nd / "montmodexp.c"
    if stale == "older than the source":
        os.utime(so, (1, 1))
    else:
        os.utime(src, (1, 1))
        assert os.path.getmtime(so) > os.path.getmtime(src)
    assert rsa._stale_native(str(so), str(src))
    mod = rsa._load_native_modexp(str(nd))
    assert mod is not None and hasattr(mod, "powmod_many")
    assert mod.__file__ == str(so) and so.stat().st_size > 4096
    assert not rsa._stale_native(str(so), str(src))
    m = (1 << 127) - 1
    row, width = rsa._mont_params(m)
    got = mod.powmod_many(
        width, 3, (5).to_bytes(width, "big"), (65537).to_bytes(3, "big"), row
    )
    assert int.from_bytes(got, "big") == pow(5, 65537, m)
