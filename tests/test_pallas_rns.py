"""Fused RNS Pallas chains (ops/pallas_rns) vs host oracles and the XLA
RNS kernels — interpret mode on the CPU lane (the kernel body lowers to
ordinary XLA ops; Mosaic compilation is exercised on real TPU runs).
"""

from __future__ import annotations

import secrets

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from bftkv_tpu.crypto import rsa
from bftkv_tpu.ops import limb, pallas_rns, rns


def _pow_operands(ctx, digits, T, n_top_bits):
    mods = []
    while len(mods) < 3:
        m = secrets.randbits(n_top_bits) | 1
        if ctx.key_rows(m) is not None:
            mods.append(m)
    mods = [mods[i % 3] for i in range(T)]
    bases = [secrets.randbits(n_top_bits - 8) for _ in range(T)]
    exps = [secrets.randbits(n_top_bits - 40) for _ in range(T)]
    unique, urows, idxs = {}, [], []
    for m in mods:
        if m not in unique:
            unique[m] = len(urows)
            urows.append(ctx.key_rows(m))
        idxs.append(unique[m])
    urows += [urows[0]] * (64 - len(urows))
    ukey = tuple(jnp.asarray(a) for a in rns.stack_key_rows(urows))
    base_digits = np.stack(
        [limb.int_to_limbs(b % m, digits) for b, m in zip(bases, mods)]
    )
    ed = np.stack([limb.int_to_limbs(e, digits) for e in exps])
    nib = np.empty((T, digits * 4), dtype=np.uint8)
    nib[:, 0::4] = ed & 0xF
    nib[:, 1::4] = (ed >> 4) & 0xF
    nib[:, 2::4] = (ed >> 8) & 0xF
    nib[:, 3::4] = (ed >> 12) & 0xF
    nib = nib[:, ::-1]
    return mods, bases, exps, ukey, base_digits, nib, idxs


@pytest.mark.slow  # tier-2: heavy on a small-CPU tier-1 box (see pytest.ini)
def test_pow_pallas_matches_host_pow():
    digits, n_bits = 16, 256
    ctx = rns.context(digits, n_bits)
    T = 8
    mods, bases, exps, ukey, base_digits, nib, idxs = _pow_operands(
        ctx, digits, T, 250
    )
    sigma = np.asarray(
        pallas_rns.pow_pallas(
            rns.digits_to_halves_u8(base_digits),
            np.ascontiguousarray(nib.T),
            np.asarray(idxs, dtype=np.int32),
            ukey,
            digits=digits,
            n_bits=n_bits,
            interpret=True,
        )
    )
    vals = rns._sigma_to_ints(ctx, sigma)
    for v, b, e, m in zip(vals, bases, exps, mods):
        assert v % m == pow(b, e, m)


@pytest.mark.slow  # tier-2: heavy on a small-CPU tier-1 box (see pytest.ini)
def test_power_mod_rns_pallas_backend(monkeypatch):
    # The integrated seam: power_mod_rns routes through the fused
    # kernel when forced, and the result matches the host oracle.
    monkeypatch.setenv("BFTKV_RNS_POW_BACKEND", "pallas")
    mods, bases, exps = [], [], []
    ctx = rns.context(32, 512)
    while len(mods) < 5:
        m = secrets.randbits(500) | 1
        if ctx.key_rows(m) is not None:
            mods.append(m)
            bases.append(secrets.randbits(490))
            exps.append(secrets.randbits(480))
    got = rns.power_mod_rns(bases, exps, mods, n_bits=512)
    assert got == [pow(b, e, m) for b, e, m in zip(bases, exps, mods)]


@pytest.mark.slow  # tier-2: heavy on a small-CPU tier-1 box (see pytest.ini)
def test_verify_pallas_matches_reference():
    key1, key2 = rsa.generate(2048), rsa.generate(2048)
    ctx = rns.context()
    items = []
    for i, k in enumerate([key1, key2] * 4):
        msg = b"pv-%d" % i
        s = int.from_bytes(rsa.sign(msg, k), "big")
        em = rsa.emsa_pkcs1v15_sha256(msg, k.size_bytes)
        items.append((s, em, k))
    s3, em3, k3 = items[3]
    items[3] = (s3 ^ (1 << 17), em3, k3)  # bit-flipped signature
    sig_d = np.stack([limb.int_to_limbs(s, 128) for s, _, _ in items])
    em_d = np.stack([limb.int_to_limbs(e, 128) for _, e, _ in items])
    idx = np.array([i % 2 for i in range(8)], dtype=np.int32)
    urows = [ctx.key_rows(key1.n), ctx.key_rows(key2.n)]
    ukey = tuple(jnp.asarray(a) for a in rns.stack_key_rows(urows))
    ok = np.asarray(
        pallas_rns.verify_pallas(
            rns.digits_to_halves_u8(sig_d),
            rns.digits_to_halves_u8(em_d),
            idx,
            ukey,
            interpret=True,
        )
    )
    assert ok.tolist() == [True, True, True, False] + [True] * 4

    # Same inputs through the XLA RNS kernel must agree exactly.
    xla = np.asarray(
        rns.verify_e65537_rns_indexed(sig_d, em_d, idx, ukey)
    )
    assert ok.tolist() == xla.tolist()


@pytest.mark.slow  # tier-2: heavy on a small-CPU tier-1 box (see pytest.ini)
def test_verify_rns_indexed_pallas_backend(monkeypatch):
    # Env-forced fused backend through the public indexed entry point
    # (what the dispatcher and sidecar call).
    monkeypatch.setenv("BFTKV_RNS_VERIFY_BACKEND", "pallas")
    key = rsa.generate(2048)
    ctx = rns.context()
    msgs = [b"ix-%d" % i for i in range(4)]
    sigs = [int.from_bytes(rsa.sign(m, key), "big") for m in msgs]
    ems = [rsa.emsa_pkcs1v15_sha256(m, key.size_bytes) for m in msgs]
    sigs[2] ^= 2
    sig_d = np.stack([limb.int_to_limbs(s, 128) for s in sigs])
    em_d = np.stack([limb.int_to_limbs(e, 128) for e in ems])
    ukey = tuple(
        jnp.asarray(a) for a in rns.stack_key_rows([ctx.key_rows(key.n)])
    )
    ok = np.asarray(
        rns.verify_e65537_rns_indexed(
            sig_d, em_d, np.zeros(4, dtype=np.int32), ukey
        )
    )
    assert ok.tolist() == [True, True, False, True]


def test_mosaic_lowering_for_tpu_target():
    """The fused chains LOWER to Mosaic for a TPU target (jax.export
    runs the pallas→Mosaic MLIR lowering on the host, no device
    needed).  Interpret-mode tests cannot catch unsupported-op or
    layout errors in that lowering; this pins the class of failure
    that would otherwise only surface as the loud XLA fallback during
    a live run.  (tests/test_chip_compile.py goes one step further
    and hands the chains to the chip's own compiler.)"""
    from jax import export as jax_export

    # Verify chain at the production tile (2048-bit context).
    tv = pallas_rns.TILE_VERIFY
    pc = pallas_rns._pad_consts(128, 2048)
    run = pallas_rns._verify_call(128, 2048, tv, False)
    z = lambda w: jnp.zeros((tv, w), jnp.float32)
    exp = jax_export.export(run, platforms=("tpu",))(
        z(256), z(256),
        z(pc.kpad), z(pc.kpad), z(1), z(pc.kpad),
        z(pc.kpad), z(pc.kpad), z(pc.kpad), z(pc.kpad), z(1),
    )
    assert len(exp.mlir_module_serialized) > 0

    # Sign (pow) chain at the production tile (1024-bit CRT context).
    tp = pallas_rns.TILE_POW
    pc2 = pallas_rns._pad_consts(64, 1024)
    run2 = pallas_rns._pow_call(64, 1024, tp, False)
    zp = lambda w: jnp.zeros((tp, w), jnp.float32)
    exp2 = jax_export.export(run2, platforms=("tpu",))(
        zp(128),                               # base halves
        jnp.zeros((256, tp), jnp.float32),     # nibbles (W, T)
        zp(pc2.kpad), zp(pc2.kpad), zp(1), zp(pc2.kpad),
        zp(pc2.kpad), zp(pc2.kpad), zp(1),
    )
    assert len(exp2.mlir_module_serialized) > 0
