"""Pallas TPU kernels: fused RNS Montgomery chains, VMEM-resident.

The XLA RNS kernels (:mod:`bftkv_tpu.ops.rns`) put the base-extension
matmuls on the MXU, but every elementwise Barrett link between matmuls
is its own XLA loop fusion reading and writing HBM: a windowed-modexp
sign chain is 256 scan steps x 5 Montgomery products x ~25 channel
arrays of traffic, so the chain is bandwidth-bound, not compute-bound
(docs/PERFORMANCE.md "Known ceilings"; reference sign hot loop:
crypto/pgp/crypto_pgp.go:346-371).  Here one ``pallas_call`` runs the
*entire* chain per batch tile — digit→residue conversion, the full
4-bit-window scan (or the 18-product e=65537 verify chain), and the
CRT/consistency epilogue — with the accumulator, window table, and
base-extension matrices VMEM-resident throughout.  HBM traffic drops
to the operands once each way; the dots still ride the MXU (6-bit
split operands as exact bf16 matmuls, f32 accumulate).

Channel geometry: the RNS bases have k channels (94 for the 1024-bit
sign context, 188 for 2048-bit verify); everything is padded to a
lane-aligned ``kpad`` (multiple of 128) with dummy channels p = 1
whose residues are identically zero — Barrett with p = 1 maps any
integral value to 0, and all padded matrix rows/columns are zero, so
the padding is inert end to end.  The 2^12 redundant channel rides as
(T, 1) arrays with power-of-two Barrett (exact), as in ops/rns.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bftkv_tpu.ops import rns

__all__ = [
    "pow_pallas", "jitted_pow", "verify_pallas", "TILE_POW", "TILE_VERIFY",
]

from bftkv_tpu import flags

#: Batch rows per grid step.  Budgeted against ~16 MB VMEM/core:
#: the pow chain (kpad=128) holds its 16-entry window table (~4 MB at
#: tile 256) plus ~5 MB of key rows/consts/temps — comfortable at 256.
#: The verify chain has no table but its kpad is 256 (k=188 channels)
#: and it streams ELEVEN row-blocked inputs, each double-buffered by
#: the Mosaic pipeline (~7 MB at tile 256 for inputs alone, plus ~4 MB
#: consts and the live temporaries) — tight enough that tile 128 is
#: the safe default; the first live-hardware measurement can raise it
#: via env (BFTKV_PALLAS_TILE_VERIFY / _POW).
def _tile_env(name: str, default: str) -> int:
    """Validated tile size: a power of two ≥ 8 (TPU sublane multiple;
    power-of-two so the callers' padded batches always divide it).
    Fail fast at import — a bad knob must not surface as a deep Mosaic
    error or a silent per-flush XLA fallback."""
    raw = flags.raw(name, default)
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(f"{name} must be an integer, got {raw!r}") from None
    if v < 8 or (v & (v - 1)):
        raise ValueError(f"{name} must be a power of two >= 8, got {v}")
    return v


TILE_POW = _tile_env("BFTKV_PALLAS_TILE_POW", "256")


def _pow_tile(kpad: int) -> int:
    """``TILE_POW`` is budgeted at kpad = 128 (rows to 1,024 bits);
    wider rows take as many fewer a tile (2,048-bit rows: kpad 256,
    tile 128 — 256 passes the scoped 16 MB by 0.5 MB on a v5e), down
    to a power of two (4,096-bit rows: kpad 384, tile 64), so that a
    padded batch is whole tiles."""
    return max(8, 1 << (TILE_POW * 128 // kpad).bit_length() - 1)


TILE_VERIFY = _tile_env("BFTKV_PALLAS_TILE_VERIFY", "128")
PR = rns.PR
_PRF = np.float32(PR)
_INV_PRF = np.float32(1.0 / PR)


# ---------------------------------------------------------------------------
# Padded, lane-aligned constants (host side, cached per context)
# ---------------------------------------------------------------------------


class _PadConsts:
    """ops/rns constants re-laid-out for the fused kernel: channel axis
    padded to a multiple of 128, the redundant-channel column split out
    of the extension matrices (it becomes a VPU row-reduce), matrices
    pre-split into 6-bit bf16-exact planes."""

    def __init__(self, ctx: rns.RNSContext):
        k, digits = ctx.k, ctx.digits
        kpad = -(-k // 128) * 128
        self.k, self.kpad, self.digits = k, kpad, digits
        self.wide, sp = ctx.wide, ctx.split

        def padv(v, fill=0.0):
            out = np.full((1, kpad), fill, dtype=np.float32)
            out[0, :k] = v
            return out

        self.pb = padv(ctx.p_all[:k], fill=1.0)
        self.pq = padv(ctx.p_all[k:], fill=1.0)
        self.ib = (np.float32(1.0) / self.pb)
        self.iq = (np.float32(1.0) / self.pq)
        self.invMi_b = padv(ctx.invMi_b)
        self.invMi_q = padv(ctx.invMi_q)
        self.Mq_mod_b = padv(ctx.Mq_mod_b)
        self.invM_q = padv(ctx.invM_q)
        self.invMq_pr = float(ctx.invMq_pr)
        self.invM_pr = float(ctx.invM_pr)

        # Rebuild integer matrices from the stored exact planes.
        sh = float(1 << sp)
        E1 = (ctx._E1[0] + sh * ctx._E1[1]).astype(np.int64)  # (k, k+1)
        E2 = (ctx._E2[0] + sh * ctx._E2[1]).astype(np.int64)
        D = (ctx._D[0] + sh * ctx._D[1]).astype(np.int64)  # (2d, 2k+1)

        def padm(m, rows, cols):
            out = np.zeros((rows, cols), dtype=np.int64)
            out[: m.shape[0], : m.shape[1]] = m
            return out

        split = lambda m: (
            (m & ((1 << sp) - 1)).astype(np.float32),
            (m >> sp).astype(np.float32),
        )
        self.E1q = split(padm(E1[:, :k], kpad, kpad))
        self.E1r = split(padm(E1[:, k:].T, 1, kpad))  # (1, kpad)
        self.E2b = split(padm(E2[:, :k], kpad, kpad))
        self.E2r = split(padm(E2[:, k:].T, 1, kpad))
        self.Db = split(padm(D[:, :k], 2 * digits, kpad))
        self.Dq = split(padm(D[:, k : 2 * k], 2 * digits, kpad))
        self.Dr = split(padm(D[:, 2 * k :].T, 1, 2 * digits))

    def arrays(self) -> tuple:
        """Operand order for the pallas_call const inputs."""
        return (
            self.pb, self.ib, self.pq, self.iq,
            self.invMi_b, self.invMi_q, self.Mq_mod_b, self.invM_q,
            *self.E1q, *self.E1r, *self.E2b, *self.E2r,
            *self.Db, *self.Dq, *self.Dr,
        )


@functools.lru_cache(maxsize=8)
def _pad_consts(digits: int, n_bits: int) -> _PadConsts:
    return _PadConsts(rns.context(digits, n_bits))


# ---------------------------------------------------------------------------
# Kernel math (jnp ops on VMEM-resident values; shared by pow & verify)
# ---------------------------------------------------------------------------


def _barrett(x, inv_p, p):
    q = jnp.floor(x * inv_p)
    r = x - q * p
    r = jnp.where(r < 0, r + p, r)
    r = jnp.where(r < 0, r + p, r)
    r = jnp.where(r >= p, r - p, r)
    r = jnp.where(r >= p, r - p, r)
    return r


def _mulmod(a, b, inv_p, p):
    return _barrett(a * b, inv_p, p)


def _mulmod_wide(a, b, inv_p, p):
    """``rns._mulmod_wide``: 13-bit channels, ``b`` in 7-bit halves."""
    bh = jnp.floor(b * np.float32(1 / 128))
    bl = b - bh * 128.0
    return _barrett(a * bl + _barrett(a * bh, inv_p, p) * 128.0, inv_p, p)


def _addmod(a, b, p):
    s = a + b
    return jnp.where(s >= p, s - p, s)


def _submod(a, b, p):
    d = a - b
    return jnp.where(d < 0, d + p, d)


def _mod_r(x):
    return x - jnp.floor(x * _INV_PRF) * _PRF


def _split(x, s: int = 6):
    hi = jnp.floor(x * np.float32(1.0 / (1 << s)))
    return x - hi * float(1 << s), hi


def _dot(a, b):
    return lax.dot_general(
        a.astype(jnp.bfloat16),
        b.astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _dot6(x, mlo, mhi, s: int = 6):
    """Exact x @ M for 12-bit integral operands via 6-bit bf16 planes.
    Returns the (ll, mid, hh) partial planes (each < 2^22).  ``s`` 7:
    13-bit operands in 7 + 6-bit planes (the wide chain; < 2^23)."""
    xlo, xhi = _split(x, s)
    return _dot(xlo, mlo), _dot(xlo, mhi) + _dot(xhi, mlo), _dot(xhi, mhi)


def _red6(x, rlo, rhi, s: int = 6):
    """Row-reduce variant for the redundant channel: Σ_i x[:,i]·r[i]
    as exact partial planes, (T, 1) each."""
    xlo, xhi = _split(x, s)
    rsum = lambda v: jnp.sum(v, axis=1, keepdims=True)
    return (
        rsum(xlo * rlo),
        rsum(xlo * rhi) + rsum(xhi * rlo),
        rsum(xhi * rhi),
    )


def _combine(sll, smid, shh, inv_p, p, s: int = 6):
    sh = float(1 << s)
    a = _barrett(sll, inv_p, p)
    b = _barrett(smid, inv_p, p)
    d = _barrett(shh, inv_p, p)
    b6 = _barrett(b * sh, inv_p, p)
    d12 = _barrett(_barrett(d * sh, inv_p, p) * sh, inv_p, p)
    return _addmod(_addmod(a, b6, p), d12, p)


def _combine_r(sll, smid, shh, s: int = 6):
    sh = float(1 << s)
    return _mod_r(
        _mod_r(sll) + _mod_r(smid * sh) + _mod_r(_mod_r(shh * sh) * sh)
    )


class _Ctx:
    """Constants loaded from refs once per kernel invocation."""

    def __init__(self, refs, invMq_pr, invM_pr, wide: bool = False):
        # the wide chain's 13-bit channels: 7 + 6-bit planes and split
        # channel products (``rns.WIDE_BITS``)
        self.s = rns.WIDE_SPLIT if wide else rns.SPLIT
        self.mul = _mulmod_wide if wide else _mulmod
        (
            self.pb, self.ib, self.pq, self.iq,
            self.invMi_b, self.invMi_q, self.Mq_mod_b, self.invM_q,
            e1q_lo, e1q_hi, e1r_lo, e1r_hi,
            e2b_lo, e2b_hi, e2r_lo, e2r_hi,
            db_lo, db_hi, dq_lo, dq_hi, dr_lo, dr_hi,
        ) = [r[:] for r in refs]
        self.E1q = (e1q_lo, e1q_hi)
        self.E1r = (e1r_lo, e1r_hi)
        self.E2b = (e2b_lo, e2b_hi)
        self.E2r = (e2r_lo, e2r_hi)
        self.Db = (db_lo, db_hi)
        self.Dq = (dq_lo, dq_hi)
        self.Dr = (dr_lo, dr_hi)
        self.invMq_pr = np.float32(invMq_pr)
        self.invM_pr = np.float32(invM_pr)

    # -- the Montgomery product (Bajard AMM + Shenoy), fully in VMEM --
    def mont_mul(self, a, b, key):
        ab, aq, ar = a
        bb, bq, br = b
        nb, nq, nr, ninvb = key[:4]
        s, mul = self.s, self.mul
        db = mul(ab, bb, self.ib, self.pb)
        dq = mul(aq, bq, self.iq, self.pq)
        dr = _mod_r(ar * br)

        qb = mul(db, ninvb, self.ib, self.pb)
        sigma = mul(qb, self.invMi_b, self.ib, self.pb)
        sll, smid, shh = _dot6(sigma, *self.E1q, s)
        qhat_q = _combine(sll, smid, shh, self.iq, self.pq, s)
        rll, rmid, rhh = _red6(sigma, *self.E1r, s)
        qhat_r = _combine_r(rll, rmid, rhh, s)

        t = mul(qhat_q, nq, self.iq, self.pq)
        rq = mul(_addmod(dq, t, self.pq), self.invM_q, self.iq, self.pq)
        rr = _mod_r(_mod_r(dr + _mod_r(qhat_r * nr)) * self.invM_pr)

        sigma2 = mul(rq, self.invMi_q, self.iq, self.pq)
        zll, zmid, zhh = _dot6(sigma2, *self.E2b, s)
        ext_b = _combine(zll, zmid, zhh, self.ib, self.pb, s)
        wll, wmid, whh = _red6(sigma2, *self.E2r, s)
        ext_r = _combine_r(wll, wmid, whh, s)
        alpha = _mod_r(_mod_r(ext_r - rr + _PRF) * self.invMq_pr)
        corr = mul(alpha, self.Mq_mod_b, self.ib, self.pb)
        rb = _submod(ext_b, corr, self.pb)
        return rb, rq, rr

    def to_residues(self, halves):
        """(T, 2·digits) 8-bit halves → residue triplet."""
        s = self.s
        sll, smid, shh = _dot6(halves, *self.Db, s)
        xb = _combine(sll, smid, shh, self.ib, self.pb, s)
        tll, tmid, thh = _dot6(halves, *self.Dq, s)
        xq = _combine(tll, tmid, thh, self.iq, self.pq, s)
        rll, rmid, rhh = _red6(halves, *self.Dr, s)
        xr = _combine_r(rll, rmid, rhh, s)
        return xb, xq, xr

    def ones_like(self, x):
        return (
            jnp.ones_like(x[0]),
            jnp.ones_like(x[1]),
            jnp.ones_like(x[2]),
        )


# ---------------------------------------------------------------------------
# Fused windowed modexp (the sign chain)
# ---------------------------------------------------------------------------


def _pow_body(invMq_pr, invM_pr, w_steps, wide, *refs):
    (base_ref, nib_ref, nb_ref, nq_ref, nr_ref, ninvb_ref,
     m2b_ref, m2q_ref, m2r_ref, *const_refs) = refs[:-1]
    out_ref = refs[-1]
    cx = _Ctx(const_refs, invMq_pr, invM_pr, wide)

    key = (nb_ref[:], nq_ref[:], nr_ref[:], ninvb_ref[:])
    m2 = (m2b_ref[:], m2q_ref[:], m2r_ref[:])
    base = cx.to_residues(base_ref[:])
    ones = cx.ones_like(base)
    base_m = cx.mont_mul(base, m2, key)
    one_m = cx.mont_mul(m2, ones, key)

    # 16-entry window table (Montgomery form), VMEM-resident.
    tab = [one_m, base_m]
    for _ in range(14):
        tab.append(cx.mont_mul(tab[-1], base_m, key))
    tb = jnp.concatenate([t[0] for t in tab], axis=1)  # (T, 16·kpad)
    tq = jnp.concatenate([t[1] for t in tab], axis=1)
    tr = jnp.concatenate([t[2] for t in tab], axis=1)  # (T, 16)
    kpad = base[0].shape[1]

    def step(i, acc):
        for _ in range(4):
            acc = cx.mont_mul(acc, acc, key)
        nib = jnp.transpose(nib_ref[pl.ds(i, 1), :])  # (T, 1) f32
        sel_b = jnp.zeros_like(acc[0])
        sel_q = jnp.zeros_like(acc[1])
        sel_r = jnp.zeros_like(acc[2])
        for w in range(16):
            m = (nib == np.float32(w)).astype(jnp.float32)
            sel_b = sel_b + m * tb[:, w * kpad : (w + 1) * kpad]
            sel_q = sel_q + m * tq[:, w * kpad : (w + 1) * kpad]
            sel_r = sel_r + m * tr[:, w : w + 1]
        return cx.mont_mul(acc, (sel_b, sel_q, sel_r), key)

    acc = lax.fori_loop(0, w_steps, step, one_m)
    vb, _vq, _vr = cx.mont_mul(acc, ones, key)  # out of Montgomery form
    out_ref[:] = cx.mul(vb, cx.invMi_b, cx.ib, cx.pb)  # CRT σ over B


@functools.lru_cache(maxsize=8)
def _pow_prep(k: int, kpad: int):
    """Jitted gather/pad prologue, built once per (k, kpad).

    Hoisted out of pow_pallas so the hot sign path doesn't re-trace the
    prologue on every dispatcher flush (ADVICE r4 #4) — the pallas_call
    is cached by _pow_call; this keeps prep cached symmetrically.
    """

    @jax.jit
    def rns_pow_prep(idx, ukey):
        n_all, n_r, neg_ninv_b, _ninv, m2_all, m2_r = tuple(
            u[idx] for u in ukey
        )
        pad = lambda x: jnp.pad(x, ((0, 0), (0, kpad - k)))
        return (
            pad(n_all[:, :k]), pad(n_all[:, k:]), n_r,
            pad(neg_ninv_b),
            pad(m2_all[:, :k]), pad(m2_all[:, k:]), m2_r,
        )

    return rns_pow_prep


@functools.lru_cache(maxsize=8)
def _verify_prep(k: int, kpad: int):
    """Jitted gather/pad prologue for the verify chain (see _pow_prep)."""

    @jax.jit
    def rns_verify_prep(idx, ukey):
        n_all, n_r, neg_ninv_b, ninv_all, m2_all, m2_r = tuple(
            u[idx] for u in ukey
        )
        pad = lambda x: jnp.pad(x, ((0, 0), (0, kpad - k)))
        return (
            pad(n_all[:, :k]), pad(n_all[:, k:]), n_r,
            pad(neg_ninv_b),
            pad(ninv_all[:, :k]), pad(ninv_all[:, k:]),
            pad(m2_all[:, :k]), pad(m2_all[:, k:]), m2_r,
        )

    return rns_verify_prep


@functools.lru_cache(maxsize=8)
def _pow_call(
    digits: int, n_bits: int, tile: int, interpret: bool,
    windows: int | None = None,
):
    pc = _pad_consts(digits, n_bits)
    kpad, w_steps = pc.kpad, windows or digits * 4
    consts = tuple(jnp.asarray(a) for a in pc.arrays())
    kernel = functools.partial(
        _pow_body, pc.invMq_pr, pc.invM_pr, w_steps, pc.wide
    )

    @jax.jit
    def rns_pow_pallas(base_h, nib_t, nb, nq, nr, ninvb, m2b, m2q, m2r):
        batch = base_h.shape[0]
        grid = batch // tile
        row = lambda width: pl.BlockSpec(
            (tile, width), lambda i: (i, 0), memory_space=pltpu.VMEM
        )
        full = lambda a: pl.BlockSpec(
            a.shape, lambda i: (0, 0), memory_space=pltpu.VMEM
        )
        return pl.pallas_call(
            kernel,
            name="rns_pow_chain",
            out_shape=jax.ShapeDtypeStruct((batch, kpad), jnp.float32),
            grid=(grid,),
            in_specs=[
                row(2 * digits),
                pl.BlockSpec(  # nibbles ride (W, T): blocked on axis 1
                    (w_steps, tile), lambda i: (0, i),
                    memory_space=pltpu.VMEM,
                ),
                row(kpad), row(kpad), row(1), row(kpad),
                row(kpad), row(kpad), row(1),
                *[full(c) for c in consts],
            ],
            out_specs=row(kpad),
            interpret=interpret,
        )(base_h, nib_t, nb, nq, nr, ninvb, m2b, m2q, m2r, *consts)

    return rns_pow_pallas


@functools.lru_cache(maxsize=8)
def jitted_pow(
    digits: int, n_bits: int, windows: int, rows: int, name: str,
    interpret: bool = False,
):
    """The fused chain as ONE program under ``rns._jitted_pow``'s
    signature and result — uint8 operands, the moduli's rows gathered
    on the device, (rows, k) σ — so that a launch of it is dispatched
    and fetched as a launch of the XLA chain is.  ``name`` is the
    program's (``rns._pow_name``): a device trace shows one module a
    launch and a kernel a tile, not a fusion a Barrett link."""
    pc = _pad_consts(digits, n_bits)
    k, kpad = pc.k, pc.kpad
    # built here, outside any trace: the call's constants are arrays
    run = _pow_call(
        digits, n_bits, min(_pow_tile(kpad), rows), interpret, windows
    )
    prep = _pow_prep(k, kpad)

    def rns_pow(base_halves_u8, exp_nibbles_t_u8, idx, ukey):
        return run(
            base_halves_u8.astype(jnp.float32),
            exp_nibbles_t_u8.astype(jnp.float32),
            *prep(idx, ukey),
        )[:, :k]

    rns_pow.__name__ = name
    return jax.jit(rns_pow)


def pow_pallas(
    base_halves_u8: np.ndarray,  # (T, 2·digits) uint8
    exp_nibbles_t_u8: np.ndarray,  # (W, T) uint8, MS nibble first
    idx: np.ndarray,  # (T,) int32 into ukey
    ukey: tuple,  # stacked unique key rows (rns.stack_key_rows)
    *,
    digits: int,
    n_bits: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Drop-in for the XLA ``_jitted_pow`` path: returns (T, kpad) σ
    whose first k columns match ``rns._pow_kernel``'s output.  The
    step count is the staged window array's."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t = base_halves_u8.shape[0]
    windows = exp_nibbles_t_u8.shape[0]
    tile = min(_pow_tile(_pad_consts(digits, n_bits).kpad), t)
    if t % tile:
        # grid = t // tile would silently drop the tail rows; in-repo
        # callers pad to powers of two, but this is a documented
        # drop-in for arbitrary batches — refuse loudly instead.
        raise ValueError(f"batch {t} not a multiple of tile {tile}")
    pc = _pad_consts(digits, n_bits)
    k, kpad = pc.k, pc.kpad
    run = _pow_call(digits, n_bits, tile, interpret, windows)

    # Gather + pad per-row key tensors on device (XLA, outside pallas).
    nb, nq, nr, ninvb, m2b, m2q, m2r = _pow_prep(k, kpad)(
        jnp.asarray(idx), tuple(jnp.asarray(u) for u in ukey)
    )
    return run(
        jnp.asarray(base_halves_u8).astype(jnp.float32),
        jnp.asarray(exp_nibbles_t_u8).astype(jnp.float32),
        nb, nq, nr, ninvb, m2b, m2q, m2r,
    )[:, :k]


# ---------------------------------------------------------------------------
# Fused e=65537 verify chain
# ---------------------------------------------------------------------------


def _verify_body(invMq_pr, invM_pr, k, *refs):
    (sig_ref, em_ref, nb_ref, nq_ref, nr_ref, ninvb_ref,
     ninv_b_ref, ninv_q_ref, m2b_ref, m2q_ref, m2r_ref, *const_refs) = refs[:-1]
    out_ref = refs[-1]
    cx = _Ctx(const_refs, invMq_pr, invM_pr)

    key = (nb_ref[:], nq_ref[:], nr_ref[:], ninvb_ref[:])
    s = cx.to_residues(sig_ref[:])
    em_b, em_q, _em_r = cx.to_residues(em_ref[:])
    m2 = (m2b_ref[:], m2q_ref[:], m2r_ref[:])
    sm = cx.mont_mul(s, m2, key)
    acc = sm
    for _ in range(16):
        acc = cx.mont_mul(acc, acc, key)
    acc = cx.mont_mul(acc, sm, key)
    ones = cx.ones_like(sm)
    vb, vq, _vr = cx.mont_mul(acc, ones, key)

    delta_b = _mulmod(
        _submod(vb, em_b, cx.pb), ninv_b_ref[:], cx.ib, cx.pb
    )
    delta_q = _mulmod(
        _submod(vq, em_q, cx.pq), ninv_q_ref[:], cx.iq, cx.pq
    )
    alpha = delta_b[:, :1]
    lane = lax.broadcasted_iota(jnp.int32, delta_b.shape, 1)
    okb = jnp.all((delta_b == alpha) | (lane >= k), axis=1, keepdims=True)
    okq = jnp.all((delta_q == alpha) | (lane >= k), axis=1, keepdims=True)
    out_ref[:] = (
        okb & okq & (alpha <= np.float32(k + 1))
    ).astype(jnp.float32)


@functools.lru_cache(maxsize=8)
def _verify_call(digits: int, n_bits: int, tile: int, interpret: bool):
    pc = _pad_consts(digits, n_bits)
    kpad = pc.kpad
    consts = tuple(jnp.asarray(a) for a in pc.arrays())
    kernel = functools.partial(
        _verify_body, pc.invMq_pr, pc.invM_pr, pc.k
    )

    @jax.jit
    def rns_verify_pallas(
        sig_h, em_h, nb, nq, nr, ninvb, ninv_b, ninv_q, m2b, m2q, m2r
    ):
        batch = sig_h.shape[0]
        grid = batch // tile
        row = lambda width: pl.BlockSpec(
            (tile, width), lambda i: (i, 0), memory_space=pltpu.VMEM
        )
        full = lambda a: pl.BlockSpec(
            a.shape, lambda i: (0, 0), memory_space=pltpu.VMEM
        )
        out = pl.pallas_call(
            kernel,
            name="rns_verify_chain",
            out_shape=jax.ShapeDtypeStruct((batch, 1), jnp.float32),
            grid=(grid,),
            in_specs=[
                row(2 * digits), row(2 * digits),
                row(kpad), row(kpad), row(1), row(kpad),
                row(kpad), row(kpad),
                row(kpad), row(kpad), row(1),
                *[full(c) for c in consts],
            ],
            out_specs=row(1),
            interpret=interpret,
        )(sig_h, em_h, nb, nq, nr, ninvb, ninv_b, ninv_q, m2b, m2q, m2r, *consts)
        return out[:, 0] > 0

    return rns_verify_pallas


def verify_pallas(
    sig_halves_u8: np.ndarray,
    em_halves_u8: np.ndarray,
    idx: np.ndarray,
    ukey: tuple,
    *,
    digits: int = rns.DIGITS,
    n_bits: int = 2048,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused-chain equivalent of ``rns.verify_e65537_rns_indexed``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t = sig_halves_u8.shape[0]
    tile = min(TILE_VERIFY, t)
    if t % tile:
        # Unwritten tail rows would be *uninitialized verdicts* — a
        # fail-open hazard.  Refuse; callers pad (rsa._verify_rns does).
        raise ValueError(f"batch {t} not a multiple of tile {tile}")
    pc = _pad_consts(digits, n_bits)
    k, kpad = pc.k, pc.kpad
    run = _verify_call(digits, n_bits, tile, interpret)

    args = _verify_prep(k, kpad)(
        jnp.asarray(idx), tuple(jnp.asarray(u) for u in ukey)
    )
    return run(
        jnp.asarray(sig_halves_u8).astype(jnp.float32),
        jnp.asarray(em_halves_u8).astype(jnp.float32),
        *args,
    )
