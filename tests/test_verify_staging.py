"""A verify launch's operands are built from the items' byte strings in
whole-array steps (ISSUE 29; docs/DESIGN.md §7.1, "Launch phases").

The oracle is the per-item loop this replaced, kept HERE: the tier
split, an integer and two little arrays an item, ``np.stack``, the
uint32 digits split back into bytes.  Every case holds the new route to
it bit for bit, pads included, and every input class to the verdict and
the tier counter it had.  The operand half launches nothing: the chain
is replaced by a recorder (as ``benchmarks/plants.py`` replaces it, by
attribute).  The end-to-end half launches on the CPU backend at the one
bucket ``tests/test_flush_phases.py`` compiles too (256 rows, 64 keys).
"""

from __future__ import annotations

import hashlib
import random

import numpy as np
import pytest

from bftkv_tpu.crypto import cert as certmod
from bftkv_tpu.crypto import ecdsa, rsa
from bftkv_tpu.metrics import registry as metrics
from bftkv_tpu.ops import limb, rns


@pytest.fixture(autouse=True)
def fresh_metrics():
    metrics.reset()
    yield
    metrics.reset()


# -- the oracle: yesterday's loops, copied ----------------------------------


def oracle_split(items) -> dict:
    """``VerifierDomain.verify_batch``'s per-item tier split (RNS
    backend, chain warm or unsaid)."""
    tiers = {"ec": [], "odd": [], "wide": [], "device": []}
    for i, (_message, _sig, key) in enumerate(items):
        if certmod.is_ec(key):
            tiers["ec"].append(i)
        elif not rsa._sound_f4(key):
            tiers["odd"].append(i)
        elif rns.chains(key.n.bit_length()).verify:
            tiers["device"].append(i)
        else:
            tiers["wide"].append(i)
    return tiers


def oracle_stage(items, device_idx):
    """``VerifierDomain._verify_rns``'s staging as it was: returns the
    staged operands (None where nothing is kept), the items pulled to
    the host tier and the items kept, by index."""
    ctx = rns.context()
    unique: dict[int, int] = {}
    urows: list = []
    idxs, digit_rows, em_rows, keep_idx, host_idx = [], [], [], [], []
    for j in device_idx:
        message, sig_bytes, key = items[j]
        kr = ctx.key_rows(key.n)
        s = int.from_bytes(sig_bytes, "big")
        if kr is None or s >= key.n:
            host_idx.append(j)
            continue
        u = unique.get(key.n)
        if u is None:
            u = unique[key.n] = len(urows)
            urows.append(kr)
        idxs.append(u)
        digit_rows.append(limb.int_to_limbs(s, ctx.digits))
        em_rows.append(
            limb.int_to_limbs(
                rsa.emsa_pkcs1v15_sha256(message, key.size_bytes), ctx.digits
            )
        )
        keep_idx.append(j)
    if not idxs:
        return None, host_idx, keep_idx
    k = len(idxs)
    padded = max(256, 1 << (k - 1).bit_length())
    for _ in range(padded - k):
        idxs.append(0)
        digit_rows.append(np.zeros(ctx.digits, dtype=np.uint32))
        em_rows.append(em_rows[0])
    kpad = max(64, 1 << (len(urows) - 1).bit_length())
    urows += [urows[0]] * (kpad - len(urows))
    staged = (
        rns.digits_to_halves_u8(np.stack(digit_rows)),
        rns.digits_to_halves_u8(np.stack(em_rows)),
        np.asarray(idxs, dtype=np.int32),
        rns.stack_key_rows(urows),
    )
    return staged, host_idx, keep_idx


def assert_same_operands(got, want) -> None:
    for g, w in zip(got[:3], want[:3]):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(g, w)
    assert len(got[3]) == len(want[3]) == 6
    for g, w in zip(got[3], want[3]):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(g, w)


# -- inputs -------------------------------------------------------------------


def modulus(rng: random.Random, bits: int) -> int:
    """An odd number of exactly ``bits`` bits that the context builds
    rows for.  The operand half needs no private key."""
    ctx = rns.context()
    while True:
        n = rng.getrandbits(bits) | (1 << (bits - 1)) | 1
        if ctx.key_rows(n) is not None:
            return n


def rowless_modulus(rng: random.Random, bits: int) -> int:
    """Sound for the tier rule (odd, e = 65537's), and sharing a factor
    with a channel prime: ``key_rows`` gives None."""
    p = rns.context().pb[0]
    while True:
        n = (rng.getrandbits(bits) | (1 << (bits - 1)) | 1) // p * p
        if n % 2 and n.bit_length() == bits:
            assert rns.context().key_rows(n) is None
            return n


def below(rng: random.Random, n: int, size: int) -> bytes:
    return rng.randrange(1, n).to_bytes(size, "big")


def flush(rng, keys, k, message=lambda i: b"m-%d" % i) -> list:
    """``k`` well-formed items over ``keys``, in a seeded order."""
    out = []
    for i in range(k):
        key = keys[rng.randrange(len(keys))]
        out.append((message(i), below(rng, key.n, key.size_bytes), key))
    return out


@pytest.fixture(scope="module")
def keys2048():
    rng = random.Random(2048)
    return [rsa.PublicKey(modulus(rng, 2048)) for _ in range(70)]


@pytest.fixture(scope="module")
def keys1024():
    rng = random.Random(1024)
    return [rsa.PublicKey(modulus(rng, 1024)) for _ in range(3)]


class Recorder:
    """Stands where the chain stands; remembers what it was handed and
    answers row ``r`` with ``r`` even, so that the scatter shows which
    row went where."""

    def __init__(self, monkeypatch):
        self.calls: list[tuple] = []
        monkeypatch.setattr(rns, "verify_e65537_rns_indexed", self)

    def __call__(self, *staged):
        self.calls.append(staged)
        return np.arange(len(staged[2])) % 2 == 0


def run_against_oracle(monkeypatch, items) -> dict:
    """One ``verify_batch`` of ``items`` with the chain recorded; holds
    operands, scatter, host-tier verdicts and counters to the oracle's.
    Returns the metrics snapshot."""
    rec = Recorder(monkeypatch)
    tiers = oracle_split(items)
    want, host_idx, keep_idx = oracle_stage(items, tiers["device"])
    vd = rsa.VerifierDomain(host_threshold=0, backend="rns")
    out = vd.verify_batch(items)
    if want is None:
        assert rec.calls == []
    else:
        assert len(rec.calls) == 1
        assert_same_operands(rec.calls[0], want)
    # every kept item got its own row's answer, in the oracle's order
    assert [bool(out[j]) for j in keep_idx] == [
        r % 2 == 0 for r in range(len(keep_idx))
    ]
    # everything else got the host tier's answer
    for name in ("odd", "wide"):
        host_idx = host_idx + tiers[name]
    assert [bool(out[j]) for j in host_idx] == rsa.verify_host_many(
        [items[j] for j in host_idx]
    )
    assert [bool(out[j]) for j in tiers["ec"]] == [
        ecdsa.verify_host(*items[j]) for j in tiers["ec"]
    ]
    snap = metrics.snapshot()
    assert snap.get("verify.device", 0) == len(keep_idx)
    assert snap.get("verify.host", 0) == len(host_idx) - len(tiers["odd"])
    assert snap.get("verify.ec", 0) == len(tiers["ec"])
    assert snap["verify.stage.array"] + snap["verify.stage.item"] == len(
        tiers["device"]
    )
    return snap


# -- the operand half ---------------------------------------------------------


@pytest.mark.parametrize("k", [1, 255, 256, 257, 650])
@pytest.mark.parametrize("n_keys", [1, 16, 70])
def test_operands_equal_the_per_item_loops(
    monkeypatch, keys2048, n_keys, k
):
    rng = random.Random(1000 * n_keys + k)
    items = flush(rng, keys2048[:n_keys], k)
    snap = run_against_oracle(monkeypatch, items)
    assert snap["verify.stage.array"] == k and snap["verify.stage.item"] == 0
    if min(n_keys, k) > 64:  # the unique-key axis escalated
        assert len({id(key) for _m, _s, key in items}) > 64


@pytest.mark.parametrize("size", [0, 5 * 1024])
def test_empty_and_long_messages(monkeypatch, keys2048, size):
    rng = random.Random(size)
    items = flush(
        rng, keys2048[:4], 40, message=lambda i: bytes([i]) * size
    )
    run_against_oracle(monkeypatch, items)


def test_two_widths_in_one_flush(monkeypatch, keys1024, keys2048):
    rng = random.Random(7)
    items = flush(rng, keys1024 + keys2048[:5], 300)
    assert {key.size_bytes for _m, _s, key in items} == {128, 256}
    snap = run_against_oracle(monkeypatch, items)
    assert snap["verify.device.bits{bits=1024}"] + snap[
        "verify.device.bits{bits=2048}"
    ] == 300


def edge_signatures(n: int, size: int, rng: random.Random) -> dict:
    """Signature byte strings of every class the item route exists for,
    and the canonical ones at the edges of the array route."""
    small = rng.randrange(1, 1 << (8 * (size - 1)))  # top byte zero
    return {
        "leading_zero": small.to_bytes(size, "big"),
        "one_short": small.to_bytes(size - 1, "big"),
        "one_long": b"\x00" + below(rng, n, size),
        "n_minus_1": (n - 1).to_bytes(size, "big"),
        "n": n.to_bytes(size, "big"),
        "above_n": (n + 2).to_bytes(size, "big"),
        "above_n_long": b"\x01" + below(rng, n, size),
        "all_zero": bytes(size),
        "empty": b"",
        "not_bytes": bytearray(below(rng, n, size)),
    }


#: class -> (staged by the array route, ends on the host tier)
EDGE_ROUTES = {
    "leading_zero": (True, False),
    "one_short": (False, False),
    "one_long": (False, False),
    "n_minus_1": (True, False),
    "n": (False, True),
    "above_n": (False, True),
    "above_n_long": (False, True),
    "all_zero": (True, False),
    "empty": (False, False),
    "not_bytes": (False, False),
}


@pytest.mark.parametrize("kind", sorted(EDGE_ROUTES))
@pytest.mark.parametrize("bits", [1024, 2048])
def test_one_edge_signature_among_sound_ones(
    monkeypatch, keys1024, keys2048, bits, kind
):
    rng = random.Random(f"{bits}-{kind}")
    keys = (keys1024 if bits == 1024 else keys2048)[:3]
    items = flush(rng, keys, 30)
    key = keys[1]
    edge = edge_signatures(key.n, key.size_bytes, rng)[kind]
    items.insert(11, (b"edge", edge, key))
    snap = run_against_oracle(monkeypatch, items)
    array, host = EDGE_ROUTES[kind]
    assert snap["verify.stage.item"] == (0 if array else 1)
    assert snap.get("verify.host", 0) == (1 if host else 0)


def test_every_class_in_one_flush(monkeypatch, keys1024, keys2048):
    """Edge signatures of both widths, a rowless key, an odd exponent,
    a junk modulus and an EC key between sound items."""
    rng = random.Random(29)
    sound = keys1024[:2] + keys2048[:6]
    items = flush(rng, sound, 120)
    for key in (keys1024[0], keys2048[0]):
        for sig in edge_signatures(key.n, key.size_bytes, rng).values():
            items.insert(rng.randrange(len(items)), (b"edge", sig, key))
    rowless = rsa.PublicKey(rowless_modulus(rng, 2048))
    odd = rsa.PublicKey(keys2048[1].n, e=3)
    junk = rsa.PublicKey(keys2048[2].n + 1)  # even
    ec_key = ecdsa.generate()
    ec_sig = ecdsa.sign(b"ec", ec_key)
    assert ecdsa.verify_host(b"ec", ec_sig, ec_key.public)
    extra = [
        (b"rowless", below(rng, rowless.n, 256), rowless),
        (b"rowless", b"", rowless),
        (b"odd", below(rng, odd.n, 256), odd),
        (b"junk", below(rng, junk.n, 256), junk),
        (b"ec", ec_sig, ec_key.public),
        (b"not ec", ec_sig, ec_key.public),
    ]
    for item in extra:
        items.insert(rng.randrange(len(items)), item)
    snap = run_against_oracle(monkeypatch, items)
    assert snap["verify.ec"] == 2
    # chain-bound, and pulled aside: the rowless key's two items, and
    # of either width every class the arrays do not take
    aside = sum(1 for array, _host in EDGE_ROUTES.values() if not array)
    assert snap["verify.stage.item"] == 2 + 2 * aside


def test_a_flush_of_nothing_the_arrays_can_take(monkeypatch, keys2048):
    key = keys2048[0]
    items = [(b"x", key.n.to_bytes(256, "big"), key)] * 5
    snap = run_against_oracle(monkeypatch, items)
    assert snap["verify.stage.item"] == snap["verify.host"] == 5
    assert snap["verify.stage.array"] == 0


def test_the_template_is_the_encoding_with_a_zero_digest():
    for size in (64, 128, 256):
        row = rsa._em_template(size, 256)
        digest = hashlib.sha256(b"t").digest()
        em = rsa.emsa_pkcs1v15_sha256(b"t", size)
        full = np.array(row)
        full[:32] = np.frombuffer(digest, dtype=np.uint8)[::-1]
        assert full.tobytes() == em.to_bytes(256, "little")
        assert not row.flags.writeable
    with pytest.raises(rsa.ERR_INVALID_SIGNATURE):
        rsa._em_template(61, 256)


def test_the_halves_are_the_little_endian_bytes():
    """The contract the array route stands on."""
    rng = random.Random(5)
    xs = [rng.getrandbits(2048) for _ in range(4)] + [0, 1, (1 << 2048) - 1]
    halves = rns.digits_to_halves_u8(limb.ints_to_limbs(xs, 128))
    assert [bytes(r) for r in halves] == [x.to_bytes(256, "little") for x in xs]


def test_both_counters_exist_from_the_start():
    metrics.reset()
    rsa.VerifierDomain()
    snap = metrics.snapshot()
    assert snap["verify.stage.array"] == snap["verify.stage.item"] == 0
    metrics.reset()
    rsa.SignerDomain()
    snap = metrics.snapshot()
    assert snap["verify.stage.array"] == snap["verify.stage.item"] == 0


# -- the fault check stages through the same builder -----------------------


def fault_check(checks):
    """``SignerDomain._fault_check`` of ``[(key, s, em)]``: its maker
    hands it the integers and their bytes, made once."""
    keys, ss, ems = (list(c) for c in zip(*checks))
    return rsa.SignerDomain._fault_check(
        keys, ss, ems,
        [s.to_bytes(k.size_bytes, "big") for k, s in zip(keys, ss)],
        [em.to_bytes(k.size_bytes, "big") for k, em in zip(keys, ems)],
    )

def test_fault_check_operands_equal_the_per_item_loop(monkeypatch, keys2048):
    rng = random.Random(31)
    rec = Recorder(monkeypatch)
    ctx = rns.context()
    checks, dig_s, dig_em, idxs, urows, unique = [], [], [], [], [], {}
    for i in range(37):
        key = keys2048[rng.randrange(3)]
        s, em = rng.randrange(key.n), rng.randrange(key.n)
        checks.append((key, s, em))
        if key.n not in unique:
            unique[key.n] = len(urows)
            urows.append(ctx.key_rows(key.n))
        idxs.append(unique[key.n])
        dig_s.append(limb.int_to_limbs(s, ctx.digits))
        dig_em.append(limb.int_to_limbs(em, ctx.digits))
    pad = 256 - len(idxs)
    want = (
        rns.digits_to_halves_u8(
            np.stack(dig_s + [np.zeros(ctx.digits, dtype=np.uint32)] * pad)
        ),
        rns.digits_to_halves_u8(np.stack(dig_em + [dig_em[0]] * pad)),
        np.asarray(idxs + [0] * pad, dtype=np.int32),
        rns.stack_key_rows(urows + [urows[0]] * (64 - len(urows))),
    )
    ok = fault_check(checks)
    assert_same_operands(rec.calls[0], want)
    # the recorder's answers, except where the host's spot check of one
    # random item overruled a True
    want_ok = [r % 2 == 0 for r in range(37)]
    assert sum(a != b for a, b in zip(ok, want_ok)) <= 1
    assert all(b or not a for a, b in zip(ok, want_ok))
    snap = metrics.snapshot()
    assert (snap["verify.stage.array"], snap["verify.stage.item"]) == (37, 0)


# -- end to end, on the CPU backend -----------------------------------------


@pytest.fixture(scope="module")
def real_keys():
    return [rsa.generate(1024) for _ in range(3)]


def test_verdicts_equal_the_host_tier_item_for_item(real_keys):
    rng = random.Random(41)
    items = []
    for i in range(200):
        key = real_keys[rng.randrange(3)]
        msg = b"e2e-%d" % i
        sig = rsa.sign(msg, key)
        if i % 4 == 0:  # a quarter forged, one way or another
            forged = [
                sig[:-1] + bytes([sig[-1] ^ 1]),
                rsa.sign(b"other", key),
                sig[1:],
                (key.n + 1).to_bytes(key.size_bytes, "big"),
            ]
            sig = forged[(i // 4) % 4]
        items.append((msg, sig, key.public))
    # one valid signature in a non-canonical length keeps its True
    msg, sig, pub = items[1]
    items[1] = (msg, b"\x00\x00" + sig, pub)
    want = rsa.verify_host_many(items)
    assert want[1] and 40 < sum(want) < 160
    metrics.reset()
    vd = rsa.VerifierDomain(backend="rns")
    vd.host_threshold = 1  # the crossover a device would calibrate
    got = vd.verify_batch(items)
    assert [bool(g) for g in got] == want
    snap = metrics.snapshot()
    assert snap["verify.stage.array"] + snap["verify.stage.item"] == 200
    assert snap["verify.device"] + snap["verify.host"] == 200
    # s >= n went to the host tier; the short and the long ones did not
    assert snap["verify.host"] == sum(1 for i in range(0, 200, 4)
                                      if (i // 4) % 4 == 3)
    assert snap["verify.stage.item"] == snap["verify.host"] + 1 + sum(
        1 for i in range(0, 200, 4) if (i // 4) % 4 == 2 and i != 1
    )


def test_fault_check_catches_a_planted_wrong_crt_half(real_keys):
    checks = []
    for i in range(12):
        key = real_keys[i % 3]
        em = rsa.emsa_pkcs1v15_sha256(b"fc-%d" % i, key.size_bytes)
        s = pow(em, key.d, key.n)
        if i in (4, 9):
            # a faulted half mod p: right mod q, wrong mod p
            s = (s + key.q * 12345) % key.n
        checks.append((key, s, em))
    ok = fault_check(checks)
    assert ok == [i not in (4, 9) for i in range(12)]
    snap = metrics.snapshot()
    assert (snap["verify.stage.array"], snap["verify.stage.item"]) == (12, 0)
    assert snap["flush.launch.count{op=sign}"] == 1
