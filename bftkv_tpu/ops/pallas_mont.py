"""Pallas TPU kernel: the full RSA-2048 e=65537 verify chain in VMEM.

The XLA verify kernel (:mod:`bftkv_tpu.ops.rsa`) is HBM-bound: its
gather-based digit product materializes a ``(batch, 128, 256)``
intermediate (~0.5 GB at batch 4096) for every Montgomery product, and
19 products round-trip that traffic per verify. Here one
``pallas_call`` runs the *entire* chain — to-Montgomery, 17 products
for e = 65537, from-Montgomery, compare — on a VMEM-resident batch
tile, so the only HBM traffic is the operands once each way.

Representation inside the kernel: 16-bit digits in u32 lanes, one
number per sublane row, 128 digit lanes (exactly one lane tile).
Digit products are accumulated with per-limb broadcast and dynamic
lane shifts (``x`` padded into a doubled buffer + ``lax.dynamic_slice``
— no gathers), and carries resolve in log time via a Kogge–Stone
generate/propagate pass, mirroring :func:`bftkv_tpu.ops.bigint.carry_resolve`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["verify_e65537", "TILE"]

L = 128  # limbs (2048 bits / 16-bit digits)
M16 = 0xFFFF  # python int: jnp scalars would be captured consts in the kernel
TILE = 256  # batch rows per grid step


def _up_dyn(x: jnp.ndarray, s) -> jnp.ndarray:
    """Shift lanes up by (possibly traced) ``s``: out[k] = x[k-s], 0-fill.

    ``pltpu.roll`` supports traced shifts; lanes that wrapped around are
    masked off. Shifts may legitimately reach W (the phi half-product of
    the top limb in mod-R space): the mask then zeroes everything.
    """
    rolled = pltpu.roll(x, s, axis=1)
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane >= s, rolled, 0)


def _limb(a: jnp.ndarray, i) -> jnp.ndarray:
    """a[:, i] as (T, 1) for a traced ``i`` (no dynamic_slice in Mosaic):
    rotate lane i down to lane 0, then statically slice."""
    w = a.shape[1]
    return pltpu.roll(a, w - i, axis=1)[:, :1]


def _up1(x: jnp.ndarray, s: int) -> jnp.ndarray:
    """Static lane shift up (for carry resolution)."""
    if s == 0:
        return x
    t, w = x.shape
    return jnp.pad(x, ((0, 0), (s, 0)))[:, :w]


def _resolve(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Lane values (< 2^26) → canonical 16-bit digits + carry-out.

    Two local passes bound outstanding carries to one bit, then a
    Kogge–Stone generate/propagate scan finishes in log2(W) steps.
    """
    w = x.shape[1]
    c1 = x >> 16
    e = (x & M16) + _up1(c1, 1)
    cout = c1[:, w - 1 :]
    c2 = e >> 16
    t = (e & M16) + _up1(c2, 1)
    cout = cout + c2[:, w - 1 :]
    r = t & M16
    g = t >> 16  # 0/1
    p = (r == M16).astype(jnp.uint32)
    s = 1
    while s < w:
        g = g | (p & _up1(g, s))
        p = p & _up1(p, s)
        s *= 2
    digits = (r + _up1(g, 1)) & M16
    cout = cout + g[:, w - 1 :]
    return digits, cout


def _mul_cols(a: jnp.ndarray, b2: jnp.ndarray) -> jnp.ndarray:
    """Unresolved digit-product column sums.

    ``a`` is (T, 128); ``b2`` is (T, W) with the second operand in the
    low 128 lanes (W = 256 for a full product, 128 for a mod-R
    product — lanes shifted past W simply drop, which *is* mod R).
    Each step broadcasts one limb of ``a`` and shifts ``b2``'s digit
    products into place; lane sums stay < 2^25.
    """
    acc = jnp.zeros_like(b2)

    def body(i, acc):
        ai = _limb(a, i)
        prod = ai * b2
        plo = prod & M16
        phi = prod >> 16
        return acc + _up_dyn(plo, i) + _up_dyn(phi, i + 1)

    return lax.fori_loop(0, L, body, acc)


def _make_mont_mul(n, nprime, n2):
    """mont_mul closure over the (per-tile) modulus arrays.

    ``n``/``nprime`` are (T, 128); ``n2`` is n padded to (T, 256).
    """

    def mont_mul(a, b2):
        """REDC: a·b·R⁻¹ mod n.  ``a`` (T,128) digits, ``b2`` (T,256)
        with digits in the low half.  Returns (T,128) digits < n."""
        t_cols = _mul_cols(a, b2)  # (T,256) unresolved
        t_lo, _ = _resolve(t_cols[:, :L])
        m_cols = _mul_cols(t_lo, nprime)  # (T,128): product mod R
        m, _ = _resolve(m_cols)
        mn_cols = _mul_cols(m, n2)  # (T,256)
        s_digits, cout = _resolve(t_cols + mn_cols)
        hi = s_digits[:, L:]
        # Conditional subtract: value = cout·R + hi; reduce below n.
        comp = M16 - n
        sub = hi + comp
        one0 = (
            lax.broadcasted_iota(jnp.int32, hi.shape, 1) == 0
        ).astype(jnp.uint32)
        sub_digits, sub_cout = _resolve(sub + one0)
        need = (cout + sub_cout) > 0  # hi >= n  or overflow bit set
        return jnp.where(need, sub_digits, hi)

    return mont_mul


def _pad256(x):
    return jnp.concatenate([x, jnp.zeros_like(x)], axis=1)


def _verify_kernel(sig_ref, em_ref, n_ref, np_ref, r2_ref, out_ref):
    n = n_ref[:]
    nprime = np_ref[:]
    n2 = _pad256(n)
    mont_mul = _make_mont_mul(n, nprime, n2)

    s_m = mont_mul(sig_ref[:], _pad256(r2_ref[:]))  # to Montgomery form
    s_m2 = _pad256(s_m)

    def sq(_, acc):
        return mont_mul(acc, _pad256(acc))

    acc = lax.fori_loop(0, 16, sq, s_m)  # s^(2^16)
    acc = mont_mul(acc, s_m2)  # s^65537 (Montgomery)
    one = (
        lax.broadcasted_iota(jnp.int32, n.shape, 1) == 0
    ).astype(jnp.uint32)
    v = mont_mul(acc, _pad256(one))  # from Montgomery form
    out_ref[:] = v ^ em_ref[:]


@functools.partial(jax.jit, static_argnames=("interpret",))
def verify_e65537(sig, em, n, nprime, r2, *, interpret: bool = False):
    """sig^65537 mod n == em over the batch; Pallas chain kernel.

    Operands are (batch, 128) uint32 16-bit-digit arrays with batch a
    multiple of TILE (the caller pads). Returns (batch,) bool.
    """
    batch = sig.shape[0]
    grid = batch // TILE
    spec = pl.BlockSpec((TILE, L), lambda i: (i, 0), memory_space=pltpu.VMEM)
    diff = pl.pallas_call(
        _verify_kernel,
        name="mont_verify_chain",
        out_shape=jax.ShapeDtypeStruct((batch, L), jnp.uint32),
        grid=(grid,),
        in_specs=[spec] * 5,
        out_specs=spec,
        interpret=interpret,
    )(sig, em, n, nprime, r2)
    return jnp.all(diff == 0, axis=-1)
