"""The wide pow chain (ops/rns.py ``WIDE_BITS``): rows past the 12-bit
prime supply, up to 4,096 bits — ``q10-ca4096``'s tier-1 guard.

- every class that rode before it keeps its bases, its programs and its
  module names: the channel constants and the traced programs are pinned
  by digest (taken on the tree before the wide chain existed);
- ``chains`` / ``remote_route`` answer for the new width range;
- the channel constants keep every f32 partial sum of the split dots
  below 2^24 (the bound docs/DESIGN.md argues), computed from the
  constants themselves;
- the chain equals ``pow`` on seeded operands: a 4,096-bit modulus
  under a short exponent, a wider-than-2,130-bit modulus under its long
  class, and the fused chain (interpreted) on the same channel math;
- the sidecar's dispatcher groups the (4096, 8256) class and counts it.
"""

from __future__ import annotations

import hashlib
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bftkv_tpu.metrics import registry as metrics
from bftkv_tpu.ops import dispatch, modexp, pallas_rns, rns

SEED = 3000000037
S = jax.ShapeDtypeStruct


def _h(b) -> str:
    return hashlib.sha256(b if isinstance(b, bytes) else b.encode()).hexdigest()[:16]


def _keys(ctx, kpad: int = 64):
    k = ctx.k
    return tuple(S((kpad, w), jnp.float32) for w in (2 * k, 1, k, 2 * k, 2 * k, 1))


# -- (1) what rode before keeps its bases, programs and names ----------------

#: sha256[:16] of each class's channel constants (bases, matrices) and of
#: the jaxpr of each program at a launch's shape — taken on the tree
#: before the wide chain, and unchanged by it.
PINNED_CONTEXTS = {
    (64, 1024): "d144d64744a73929",
    (96, 1536): "74b1c506f5a41f68",
    (128, 2048): "ef8d0bd0d5923e49",
}


@pytest.mark.parametrize("digits,bits", sorted(PINNED_CONTEXTS))
def test_every_class_that_rode_keeps_its_bases(digits, bits):
    ctx = rns.context(digits, bits)
    assert not ctx.wide and ctx.split == rns.SPLIT
    assert max(ctx.pb + ctx.pq) < rns.PR
    blob = b"".join(np.ascontiguousarray(a).tobytes() for a in (
        np.asarray(ctx.pb), np.asarray(ctx.pq), ctx.p_all,
        ctx._E1[0], ctx._E1[1], ctx._E2[0], ctx._E2[1], ctx._D[0], ctx._D[1]))
    assert _h(blob) == PINNED_CONTEXTS[(digits, bits)]


def _xla_pow(digits, bits, exp_bits, windows):
    ctx = rns.context(digits, bits)
    f = rns._jitted_pow(digits, bits, False, exp_bits) if exp_bits else \
        rns._jitted_pow(digits, bits, False)
    args = (S((64, 2 * digits), jnp.uint8), S((windows, 64), jnp.uint8),
            S((64,), jnp.int32), _keys(ctx))
    return f, args


def _fused_fragment(rows):
    ctx = rns.context(128, 2048)
    f = pallas_rns.jitted_pow(128, 2048, 1040, rows, rns._pow_name(2048, 4160))
    return f, (S((rows, 256), jnp.uint8), S((1040, rows), jnp.uint8),
               S((rows,), jnp.int32), _keys(ctx))


def _pallas_sign():
    pc = pallas_rns._pad_consts(64, 1024)
    r = lambda w: S((512, w), jnp.float32)
    return pallas_rns._pow_call(64, 1024, pallas_rns.TILE_POW, False), (
        r(128), S((256, 512), jnp.float32), r(pc.kpad), r(pc.kpad), r(1),
        r(pc.kpad), r(pc.kpad), r(pc.kpad), r(1))


def _pallas_verify():
    pc = pallas_rns._pad_consts(128, 2048)
    r = lambda w: S((1024, w), jnp.float32)
    return pallas_rns._verify_call(128, 2048, pallas_rns.TILE_VERIFY, False), (
        r(256), r(256), r(pc.kpad), r(pc.kpad), r(1), r(pc.kpad), r(pc.kpad),
        r(pc.kpad), r(pc.kpad), r(pc.kpad), r(1))


PROGRAMS = {
    "verify": (lambda: (rns._jitted_verify_gather(), (
        S((256, 256), jnp.uint8), S((256, 256), jnp.uint8),
        S((256,), jnp.int32), _keys(rns.context()))), "8243b10b64939450",
        "rns_verify_gather"),
    "pow1024": (lambda: _xla_pow(64, 1024, None, 256), "c8c997c5aeec8ce9",
                "rns_pow_1024"),
    "pow1536": (lambda: _xla_pow(96, 1536, None, 384), "f92bc9619120a144",
                "rns_pow_1536"),
    "pow2048_e4160": (lambda: _xla_pow(128, 2048, 4160, 1040),
                      "b35849f5c618c686", "rns_pow_2048_e4160"),
    "fused_fragment64": (lambda: _fused_fragment(64), "84f5ceae80c97f48",
                         "rns_pow_2048_e4160"),
    "fused_fragment128": (lambda: _fused_fragment(128), "026a3c1ea2252025",
                          "rns_pow_2048_e4160"),
    "fused_sign": (_pallas_sign, "38e0e39873a3054c", "rns_pow_pallas"),
    "fused_verify": (_pallas_verify, "d42c94a715847339", "rns_verify_pallas"),
}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_every_program_that_rode_is_unchanged(program):
    build, digest, name = PROGRAMS[program]
    fn, args = build()
    assert fn.__name__ == name
    assert _h(str(jax.make_jaxpr(fn)(*args))) == digest


# -- (2) the capability rule ---------------------------------------------------


@pytest.mark.parametrize(
    "bits,exp_bits,verify,pow_",
    [
        (1024, None, True, True),
        (2048, 4101, True, True),
        (2130, None, False, True),     # the 12-bit supply's last widths
        (2176, 4416, False, True),     # the wide chain's first
        (3072, None, False, True),
        (3072, 2 * 3072 + 5, False, True),
        (4096, None, False, True),
        (4096, 8197, False, True),     # a first-level fragment of RSA-4096
        (4096, 8256, False, True),
        (4096, 8257, False, False),    # one bit past the long class
        (4112, None, False, False),    # past WIDE_MAX_BITS
        (8192, None, False, False),
    ],
)
def test_chains_answer_for_the_wide_range(bits, exp_bits, verify, pow_):
    assert rns.chains(bits, exp_bits) == (verify, pow_)
    assert rns._channel_bits(bits) == (
        None if not pow_ and exp_bits is None else
        rns.PR_BITS if bits <= 2130 else rns.WIDE_BITS)


@pytest.mark.parametrize(
    "bits,exp_bits,held",
    [(2048, 4101, True), (4096, 8197, True), (4096, 8258, False),
     (3072, 6149, True), (8192, 16389, False)],
)
def test_remote_route_answers_the_fragment_classes(bits, exp_bits, held):
    assert modexp.remote_route(bits, exp_bits) is held


# -- (3) the exactness bound, from the constants themselves -------------------


@pytest.mark.parametrize("bits", [2176, 3072, 4096])
def test_every_split_dot_of_the_wide_chain_stays_below_2_24(bits):
    ctx = rns.pow_context(bits)
    assert ctx.wide and ctx.split == rns.WIDE_SPLIT
    ps = ctx.pb + ctx.pq
    assert len(set(ps)) == len(ps) and ctx.k == len(ctx.pb) == len(ctx.pq)
    assert all(1 << 10 <= p < 1 << rns.WIDE_BITS for p in ps)
    assert min(ctx.M, ctx.Mq).bit_length() > bits + 64
    s = ctx.split
    lo_max, hi_max = (1 << s) - 1, ((1 << rns.WIDE_BITS) - 1) >> s
    for (mlo, mhi), x_hi in ((ctx._E1, hi_max), (ctx._E2, hi_max),
                             (ctx._D, 255 >> s)):
        assert mlo.max() <= lo_max and mhi.max() <= hi_max
        col_lo, col_hi = mlo.sum(axis=0), mhi.sum(axis=0)
        worst = max((lo_max * col_lo).max(),
                    (lo_max * col_hi + x_hi * col_lo).max(),
                    (x_hi * col_hi).max())
        assert worst < 1 << 24
    # channel products in 7-bit halves: each partial, and their sum
    assert (1 << rns.WIDE_BITS) * (1 << s) * 2 <= 1 << 21
    # the CRT sums on the host: exact in float64
    assert ctx.k * (1 << rns.WIDE_BITS) * (1 << 8 * rns._crt_digit_bytes(ctx)) \
        < 1 << 53
    assert bits <= rns.WIDE_MAX_BITS


# -- (4) the chain equals pow ---------------------------------------------------


def _rows(bits: int, exp_bits: int, t: int):
    rng = random.Random(f"{SEED}|{bits}|{exp_bits}")
    ctx = rns.pow_context(bits)
    mods = []
    while len(mods) < 3:
        m = rng.getrandbits(bits) | (1 << (bits - 1)) | 1
        if ctx.key_rows(m) is not None:
            mods.append(m)
    return [(rng.getrandbits(bits), rng.getrandbits(exp_bits), mods[i % 3])
            for i in range(t)]


def test_the_wide_chain_equals_pow_at_4096_bits():
    """A 4,096-bit modulus under a short exponent: eight windows staged
    (the window count is the staged array's), five rows in a bucket."""
    ctx = rns.pow_context(4096)
    assert (ctx.k, ctx.digits) == (340, 256)
    items = _rows(4096, 32, 5)
    mods = sorted({m for _, _, m in items})
    got = rns.pow_rows_rns(
        4096, mods, np.array([mods.index(m) for _, _, m in items]),
        b"".join((b % m).to_bytes(2 * ctx.digits, "little")
                 for b, _, m in items),
        rns.exp_nibbles([e for _, e, _ in items], ctx.digits, 8), None)
    assert got == [pow(*it) for it in items]


def test_the_wide_chain_takes_its_long_class_past_2130_bits():
    bits = 2176
    top = rns.long_exp_bits(bits)
    items = _rows(bits, top - 7, 3)
    got = rns.power_mod_rns([b for b, _, _ in items], [e for _, e, _ in items],
                            [m for _, _, m in items], n_bits=bits,
                            exp_bits=top)
    assert got == [pow(*it) for it in items]
    assert rns.power_mod_rns([2], [1 << top], [items[0][2]], n_bits=bits,
                             exp_bits=top) is None


def test_the_fused_wide_chain_equals_pow_interpreted():
    """The Pallas chain on the wide channels (what one TPU chip runs for
    the long class), interpreted at 2,176 bits under four windows."""
    bits, windows, t = 2176, 4, 8
    ctx = rns.pow_context(bits)
    items = _rows(bits, 4 * windows, t)
    mods = sorted({m for _, _, m in items})
    ukey = tuple(jnp.asarray(a) for a in rns.stack_key_rows(
        [ctx.key_rows(m) for m in mods], pad_to=64))
    base = np.frombuffer(b"".join(
        (b % m).to_bytes(2 * ctx.digits, "little") for b, _, m in items),
        np.uint8).reshape(t, -1)
    nib = np.ascontiguousarray(
        rns.exp_nibbles([e for _, e, _ in items], ctx.digits, windows).T)
    idx = np.array([mods.index(m) for _, _, m in items], np.int32)
    fn = pallas_rns.jitted_pow(ctx.digits, bits, windows, t,
                               rns._pow_name(bits, 4 * windows), True)
    sigma = np.asarray(fn(base, nib, idx, ukey))
    vals = rns._sigma_to_ints(ctx, sigma)
    assert [v % m for v, (_, _, m) in zip(vals, items)] == \
        [pow(*it) for it in items]
    assert pallas_rns._pow_tile(384) == 64  # 4,096-bit rows: kpad 384


def test_the_4096_bit_long_class_is_named_for_readers():
    assert rns.long_exp_bits(4096) == 8256
    assert rns._pow_name(4096, 8256) == "rns_pow_4096_e8256"
    fn = rns._jitted_pow(256, 4096, False, 8256)
    assert fn.__wrapped__.__name__ == "rns_pow_4096_e8256"


# -- (5) the sidecar's dispatcher groups the class ---------------------------


@pytest.fixture
def device_dispatcher(monkeypatch):
    """A ``ModexpDispatcher`` as a sidecar on a device has it, its
    launches recorded and answered by ``pow`` (once a distinct row)."""
    launched: list[tuple] = []
    memo: dict = {}

    def launch(bases, exps, mods, *, n_bits, exp_bits, defer=False, **_k):
        launched.append((n_bits, exp_bits, len(mods)))
        vals = [memo[it] if it in memo else memo.setdefault(it, pow(*it))
                for it in zip(bases, exps, mods)]
        return rns.DeferredModexp(lambda: vals) if defer else vals

    monkeypatch.setattr(rns, "power_mod_rns", launch)
    d = dispatch.ModexpDispatcher(calibrate=False, device_threshold=18)
    return d, launched, memo


def test_the_dispatcher_groups_the_4096_bit_fragment_class(device_dispatcher):
    d, launched, memo = device_dispatcher
    d.warm_rows = frozenset({1024, (4096, 8256)})
    few = _rows(4096, 8197, 2)
    items = [few[i % 2] for i in range(200)]
    key = "modexp.device.class{bits=4096}"
    before, dev = metrics.snapshot().get(key, 0), metrics.snapshot().get(
        "modexp.device", 0)
    assert d._run_batch(items) == [memo[few[i % 2]] for i in range(200)]
    # one class, cut at its one bucket: a 64-row tile of the fused chain
    assert rns.long_exp_rows(4096) == 64 and rns.long_exp_rows(2048) == 128
    assert launched == [(4096, 8256, 64)] * 3 + [(4096, 8256, 8)]
    snap = metrics.snapshot()
    assert snap.get(key, 0) - before == 200
    assert snap.get("modexp.device", 0) - dev == 200
    # one row of it costs ~2,176 verify items: over any crossover
    assert 2000 < dispatch.modexp_work(4096, 8256) < 2300


def test_an_undeclared_4096_bit_class_is_host_tier(device_dispatcher):
    d, launched, _memo = device_dispatcher
    d.warm_rows = frozenset({1024, (2048, 4160)})
    items = _rows(4096, 8197, 2)
    unwarmed = metrics.snapshot().get("sidecar.unwarmed_width", 0)
    assert d._run_batch(items) == [pow(*it) for it in items]
    assert launched == []
    assert metrics.snapshot().get("sidecar.unwarmed_width", 0) - unwarmed == 2
