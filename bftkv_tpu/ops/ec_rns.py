"""P-256 scalar multiplication on the RNS/MXU field core.

The limb-based P-256 kernel (:mod:`bftkv_tpu.ops.ec`) pays the same
tax the limb RSA kernels did: every field multiply is a 16-step digit
convolution of *emulated* integer multiplies on the VPU (556 scalar
mults/s at batch 64 — the weakest kernel in the round-3 record).  This
module applies the RNS playbook that made RSA fast
(:mod:`bftkv_tpu.ops.rns`) to the P-256 field:

- field elements live as residues over ~54 primes of ~12 bits (two
  bases + a 2^12 redundant channel), so a field multiply is one
  channelwise f32 Barrett pass plus two base extensions that run as
  exact bf16 MXU matmuls — no emulated integer arithmetic anywhere;
- the modulus is FIXED (the P-256 prime), so all Montgomery/extension
  constants are compile-time and broadcast — zero per-row key traffic;
- **channel-major layout**: tensors are ``(k, T)`` — batch rides the
  lane (minor) axis, channels ride sublanes.  P-256's k is only 27
  per base; channels-minor would lane-pad 27 → 128 (4.7× VPU waste on
  every Barrett op), while batch-minor keeps all 128 lanes busy and
  pads sublanes just 27 → 32.  (The RSA contexts sit at k = 94/188
  where channels-minor padding is mild; here layout is the difference
  between a VPU-bound and a balanced kernel.)  Base extensions become
  ``Eᵀ @ x`` matmuls — same exact 6-bit-split bf16 MXU scheme;
- values are kept in redundant AMM form (< c·p for a tracked
  coefficient c); adds and subtracts are channelwise and *don't*
  reduce — only the Montgomery product does (every ``fmul`` output is
  < (k+2)·p ≈ 30·p).  Subtraction adds a fixed multiple of p to stay
  positive; the group-law formulas stack at most two subtractions, so
  a two-level slack policy (2^14·p, then 2^16·p) keeps every value
  positive and every product far inside the ~64 bits of headroom the
  bases carry over p (worst pairing ≈ 2^34 ≪ 2^64);
- "is zero (mod p)" — needed by the unified group law for the
  identity/doubling lanes — uses the α-consistency trick from RSA
  verify: v < c·p is a multiple of p iff w_j = v_j·(p⁻¹ mod p_j)
  agrees across every channel (then v = w_0·p exactly, because
  |v − w_0·p| < M).  Exact provided c < min channel prime (~3833), so
  the law only tests *fresh* values: differences of ``fmul`` outputs
  with the small slack (bound 62·p) and the Z coordinate, which is
  kept eligible by construction — ``jac_double`` computes
  Z3 = 2·Y1·Z1 (a mult, not the (Y+Z)²−γ−δ trick), which also keeps
  the identity's Z an *exact* integer 0 through every operation;
- scalar mult is fixed 4-bit windows over 64 steps: 4 doublings + a
  one-hot table select + one unified add per window — constant-time,
  uniform across the batch (reference hot loop this accelerates:
  crypto/threshold/ecdsa/ecdsa.go:31-59, plus identity-cert ECDSA).

Selection: ``ops.ec.scalar_mult_hosts`` routes here per
``BFTKV_EC_BACKEND`` (limb | rns | auto); ``crypto/ec.py`` remains the
host correctness oracle either way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bftkv_tpu.crypto.ec import P256
from bftkv_tpu.ops import limb, rns

__all__ = ["scalar_mult_hosts", "scalar_base_mult_hosts"]

_DIGITS = 16  # 256 bits / 16-bit digits
_WINDOW = 4
_NWIN = 256 // _WINDOW

# fsub slack multiples of p.  SMALL: for differences of fmul outputs
# (< 30p) that must stay is_zero-eligible.  L1: subtrahend is an fmul
# output or a short add-chain of them (< 2^12·p).  L2: subtrahend is
# itself an L1 fsub output (< 2^14.1·p).
_S_SMALL = 32
_S_L1 = 1 << 14
_S_L2 = 1 << 16

_PRF = np.float32(rns.PR)
_INV_PRF = np.float32(1.0 / rns.PR)
_I64 = np.float32(1.0 / 64.0)


# -- channel-major field primitives (tensors (k, T); constants (k, 1)) --


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


def _addmod(a, b, p):
    s = a + b
    return jnp.where(s >= p, s - p, s)


def _submod(a, b, p):
    d = a - b
    return jnp.where(d < 0, d + p, d)


def _mod_r(x):
    return x - jnp.floor(x * _INV_PRF) * _PRF


def _split6(x):
    hi = jnp.floor(x * _I64)
    return x - hi * 64.0, hi


def _dot(m, x):
    return lax.dot_general(
        m.astype(jnp.bfloat16),
        x.astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _dot6(mlo, mhi, x):
    """Exact M @ x for 12-bit integral operands via 6-bit bf16 planes:
    M is pre-split (rows = output channels), x is (k, T)."""
    xlo, xhi = _split6(x)
    return (
        _dot(mlo, xlo),
        _dot(mlo, xhi) + _dot(mhi, xlo),
        _dot(mhi, xhi),
    )


def _red6(rlo, rhi, x):
    """Redundant-channel row-reduce: Σ_i r[i]·x[i, :] → (1, T) planes."""
    xlo, xhi = _split6(x)
    s = lambda v: jnp.sum(v, axis=0, keepdims=True)
    return (
        s(rlo * xlo),
        s(rlo * xhi) + s(rhi * xlo),
        s(rhi * xhi),
    )


def _combine(sll, smid, shh, inv_p, p):
    a = _barrett(sll, inv_p, p)
    b = _barrett(smid, inv_p, p)
    d = _barrett(shh, inv_p, p)
    b6 = _barrett(b * 64.0, inv_p, p)
    d12 = _barrett(_barrett(d * 64.0, inv_p, p) * 64.0, inv_p, p)
    return _addmod(_addmod(a, b6, p), d12, p)


def _combine_r(sll, smid, shh):
    return _mod_r(
        _mod_r(sll) + _mod_r(smid * 64.0) + _mod_r(_mod_r(shh * 64.0) * 64.0)
    )


class _P256RNS:
    """Fixed-modulus RNS field context, channel-major device constants."""

    def __init__(self):
        ctx = rns.context(_DIGITS, 256)
        self.ctx = ctx
        self.k = k = ctx.k
        p = P256.p
        f32 = lambda xs: np.asarray(xs, dtype=np.float32)
        col = lambda xs: jnp.asarray(f32(xs)[:, None])  # (k, 1)

        self.pb = col(ctx.p_all[:k])
        self.pq = col(ctx.p_all[k:])
        self.ib = col(1.0 / ctx.p_all[:k])
        self.iq = col(1.0 / ctx.p_all[k:])
        self.invMi_b = col(ctx.invMi_b)
        self.invMi_q = col(ctx.invMi_q)
        self.Mq_mod_b = col(ctx.Mq_mod_b)
        self.invM_q = col(ctx.invM_q)
        self.invMq_pr = np.float32(ctx.invMq_pr)
        self.invM_pr = np.float32(ctx.invM_pr)
        nrow = ctx.key_rows(p)
        n_all = np.asarray(nrow[0])
        self.nb = col(n_all[:k])
        self.nq = col(n_all[k:])
        self.nr = jnp.asarray(np.full((1, 1), float(nrow[1]), np.float32))
        self.neg_ninv_b = col(np.asarray(nrow[2]))

        # Extension matrices, pre-transposed for Eᵀ @ x and pre-split.
        E1 = (ctx._E1[0] + 64.0 * ctx._E1[1]).astype(np.int64)  # (k, k+1)
        E2 = (ctx._E2[0] + 64.0 * ctx._E2[1]).astype(np.int64)
        split = lambda m: (
            jnp.asarray((m & 63).astype(np.float32)),
            jnp.asarray((m >> 6).astype(np.float32)),
        )
        self.E1qT = split(E1[:, :k].T)  # (k_q, k_b)
        self.E1r = split(E1[:, k:])  # (k_b, 1) column, used as reduce
        self.E2bT = split(E2[:, :k].T)
        self.E2r = split(E2[:, k:])

        self.pinv_b = col([pow(p % q, -1, q) for q in ctx.pb])

        def const_of(v: int):
            return (
                col([v % q for q in ctx.pb]),
                col([v % q for q in ctx.pq]),
                jnp.asarray(np.full((1, 1), v % rns.PR, np.float32)),
            )

        self.sp = {
            _S_SMALL: const_of(_S_SMALL * p),
            _S_L1: const_of(_S_L1 * p),
            _S_L2: const_of(_S_L2 * p),
        }
        self.one_m = const_of(ctx.M % p)
        self.zero = const_of(0)

    # -- field ops (triplets (xb (k,T), xq (k,T), xr (1,T))) -----------

    def fmul(self, a, b):
        """RNS Montgomery product (Bajard AMM + Shenoy), channel-major."""
        ab, aq, ar = a
        bb, bq, br = b
        db = _mulmod(ab, bb, self.ib, self.pb)
        dq = _mulmod(aq, bq, self.iq, self.pq)
        dr = _mod_r(ar * br)

        qb = _mulmod(db, self.neg_ninv_b, self.ib, self.pb)
        sigma = _mulmod(qb, self.invMi_b, self.ib, self.pb)
        sll, smid, shh = _dot6(*self.E1qT, sigma)
        qhat_q = _combine(sll, smid, shh, self.iq, self.pq)
        rll, rmid, rhh = _red6(*self.E1r, sigma)
        qhat_r = _combine_r(rll, rmid, rhh)

        t = _mulmod(qhat_q, self.nq, self.iq, self.pq)
        rq = _mulmod(_addmod(dq, t, self.pq), self.invM_q, self.iq, self.pq)
        rr = _mod_r(_mod_r(dr + _mod_r(qhat_r * self.nr)) * self.invM_pr)

        sigma2 = _mulmod(rq, self.invMi_q, self.iq, self.pq)
        zll, zmid, zhh = _dot6(*self.E2bT, sigma2)
        ext_b = _combine(zll, zmid, zhh, self.ib, self.pb)
        wll, wmid, whh = _red6(*self.E2r, sigma2)
        ext_r = _combine_r(wll, wmid, whh)
        alpha = _mod_r(_mod_r(ext_r - rr + _PRF) * self.invMq_pr)
        corr = _barrett(alpha * self.Mq_mod_b, self.ib, self.pb)
        rb = _submod(ext_b, corr, self.pb)
        return rb, rq, rr

    def fadd(self, a, b):
        return (
            _addmod(a[0], b[0], self.pb),
            _addmod(a[1], b[1], self.pq),
            _mod_r(a[2] + b[2]),
        )

    def fsub(self, a, b, s: int = _S_L1):
        """a − b + s·p (s·p ≡ 0 mod p keeps the residue class; s must
        exceed b's bound coefficient so the value stays positive)."""
        sp = self.sp[s]
        return (
            _addmod(_submod(a[0], b[0], self.pb), sp[0], self.pb),
            _addmod(_submod(a[1], b[1], self.pq), sp[1], self.pq),
            _mod_r(a[2] - b[2] + sp[2] + _PRF),
        )

    def fdbl(self, a):
        return self.fadd(a, a)

    def is_zero(self, v):
        """(T,) bool: v ≡ 0 (mod p), exact for v < (min prime)·p."""
        w = _mulmod(v[0], self.pinv_b, self.ib, self.pb)
        alpha = w[:1, :]
        return jnp.all(w == alpha, axis=0) & (
            alpha[0, :] <= np.float32(2 * _S_SMALL)
        )

    def select(self, cond, a, b):
        """Per-lane triplet select; cond is (T,)."""
        c = cond[None, :]
        return tuple(jnp.where(c, x, y) for x, y in zip(a, b))

    # -- group law (Jacobian, unified / branch-free) -------------------

    def jac_double(self, X1, Y1, Z1):
        """dbl-2001-b shape for a = −3, except Z3 = 2·Y1·Z1: a mult
        keeps Z3 < 60p (is_zero-eligible) and maps the identity's
        exact-0 Z to exact 0 (0 is absorbing through fmul/fadd)."""
        delta = self.fmul(Z1, Z1)
        gamma = self.fmul(Y1, Y1)
        beta = self.fmul(X1, gamma)
        t0 = self.fsub(X1, delta, _S_L1)
        t1 = self.fadd(X1, delta)
        alpha = self.fmul(t0, self.fadd(self.fdbl(t1), t1))
        beta4 = self.fdbl(self.fdbl(beta))  # < 120p
        X3 = self.fsub(self.fmul(alpha, alpha), self.fdbl(beta4), _S_L1)
        Z3 = self.fdbl(self.fmul(Y1, Z1))
        g2 = self.fmul(gamma, gamma)
        Y3 = self.fsub(
            self.fmul(alpha, self.fsub(beta4, X3, _S_L2)),
            self.fdbl(self.fdbl(self.fdbl(g2))),
            _S_L1,
        )
        return X3, Y3, Z3

    def jac_add(self, P1, P2):
        X1, Y1, Z1 = P1
        X2, Y2, Z2 = P2
        Z1Z1 = self.fmul(Z1, Z1)
        Z2Z2 = self.fmul(Z2, Z2)
        U1 = self.fmul(X1, Z2Z2)
        U2 = self.fmul(X2, Z1Z1)
        S1 = self.fmul(self.fmul(Y1, Z2), Z2Z2)
        S2 = self.fmul(self.fmul(Y2, Z1), Z1Z1)
        # H/R: differences of fmul outputs with the SMALL slack — the
        # only values (besides Z) the is_zero test ever sees.
        H = self.fsub(U2, U1, _S_SMALL)
        R = self.fsub(S2, S1, _S_SMALL)
        H2 = self.fmul(H, H)
        H3 = self.fmul(H2, H)
        U1H2 = self.fmul(U1, H2)
        X3 = self.fsub(
            self.fsub(self.fmul(R, R), H3, _S_L1), self.fdbl(U1H2), _S_L1
        )
        Y3 = self.fsub(
            self.fmul(R, self.fsub(U1H2, X3, _S_L2)),
            self.fmul(S1, H3),
            _S_L1,
        )
        Z3 = self.fmul(self.fmul(Z1, Z2), H)

        dX, dY, dZ = self.jac_double(X1, Y1, Z1)

        inf1 = self.is_zero(Z1)
        inf2 = self.is_zero(Z2)
        same_x = self.is_zero(H) & ~inf1 & ~inf2
        same_y = self.is_zero(R)
        is_dbl = same_x & same_y
        to_inf = same_x & ~same_y  # P + (−P) = O

        X = self.select(is_dbl, dX, X3)
        Y = self.select(is_dbl, dY, Y3)
        Z = self.select(is_dbl, dZ, Z3)
        Z = self.select(to_inf, tuple(jnp.zeros_like(c) for c in Z), Z)
        X = self.select(inf1, X2, self.select(inf2, X1, X))
        Y = self.select(inf1, Y2, self.select(inf2, Y1, Y))
        Z = self.select(inf1, Z2, self.select(inf2, Z1, Z))
        return X, Y, Z

    # -- host codecs ---------------------------------------------------

    def encode_points(self, pts: list):
        """Affine host points (None = identity) → Montgomery RNS batch."""
        p = P256.p
        M = self.ctx.M
        one = M % p
        xs, ys, zs = [], [], []
        for pt in pts:
            if pt is None:
                xs.append(one)  # placeholder; Z = 0 marks identity
                ys.append(one)
                zs.append(0)
            else:
                xs.append((pt[0] * M) % p)
                ys.append((pt[1] * M) % p)
                zs.append(one)
        return tuple(self._ints_to_res(v) for v in (xs, ys, zs))

    def _ints_to_res(self, vals: list[int]):
        ctx = self.ctx
        t = len(vals)
        out_b = np.empty((self.k, t), dtype=np.float32)
        out_q = np.empty((self.k, t), dtype=np.float32)
        out_r = np.empty((1, t), dtype=np.float32)
        for i, v in enumerate(vals):
            out_b[:, i] = [v % q for q in ctx.pb]
            out_q[:, i] = [v % q for q in ctx.pq]
            out_r[0, i] = v % rns.PR
        return (jnp.asarray(out_b), jnp.asarray(out_q), jnp.asarray(out_r))

    def encode_points_into(self, pts: list, res: np.ndarray) -> None:
        """:meth:`encode_points`, but written into columns of a
        persistent staging block ``res`` of shape ``(3, 2k+1, T)`` —
        X/Y/Z on the leading axis, the b/q/r channel rows stacked on
        the middle one.  Same encoding, zero fresh allocation."""
        ctx = self.ctx
        p = P256.p
        one = ctx.M % p
        for i, pt in enumerate(pts):
            if pt is None:
                vals = (one, one, 0)  # Z = 0 marks identity
            else:
                vals = ((pt[0] * ctx.M) % p, (pt[1] * ctx.M) % p, one)
            for comp, v in zip(res, vals):
                comp[: self.k, i] = [v % q for q in ctx.pb]
                comp[self.k : 2 * self.k, i] = [v % q for q in ctx.pq]
                comp[2 * self.k, i] = v % rns.PR

    def decode_points(self, X, Y, Z) -> list:
        """Jacobian Montgomery RNS batch → affine host points.  The
        final Z inversion is host-side ``pow`` (one ~µs op per point —
        not worth a device Fermat chain)."""
        ctx = self.ctx
        p = P256.p
        ones = tuple(jnp.ones_like(c) for c in X)
        outs = []
        for comp in (X, Y, Z):
            plain = self.fmul(comp, ones)  # strip the Montgomery factor
            sigma = _mulmod(plain[0], self.invMi_b, self.ib, self.pb)
            vals = rns._sigma_to_ints(ctx, np.asarray(sigma).T)
            outs.append([v % p for v in vals])
        xs, ys, zs = outs
        pts = []
        for x, y, z in zip(xs, ys, zs):
            if z == 0:
                pts.append(None)
                continue
            zi = pow(z, -1, p)
            zi2 = zi * zi % p
            pts.append((x * zi2 % p, y * zi2 * zi % p))
        return pts


@functools.lru_cache(maxsize=1)
def _engine() -> _P256RNS:
    return _P256RNS()


def _bcast(c, t: int):
    return tuple(jnp.broadcast_to(a, (a.shape[0], t)) for a in c)


@functools.lru_cache(maxsize=1)
def _scalar_mult_fn():
    eng = _engine()

    def ec_rns_scalar_mult(Xb, Xq, Xr, Yb, Yq, Yr, Zb, Zq, Zr, nibbles_t):
        P = ((Xb, Xq, Xr), (Yb, Yq, Yr), (Zb, Zq, Zr))
        t = Xb.shape[1]
        one_m = _bcast(eng.one_m, t)
        ident = (one_m, one_m, _bcast(eng.zero, t))
        # Window table t[j] = j·P (t[0] = identity), 15 unified adds.
        tab = [ident, P]
        for _ in range(14):
            tab.append(eng.jac_add(tab[-1], P))
        # Stack on a leading window axis for the one-hot select.
        cat = [
            [jnp.stack([w[i][j] for w in tab]) for j in range(3)]
            for i in range(3)
        ]

        def sel(mask16, i):
            # mask16: (16, 1, T) one-hot; reduce over the window axis.
            return tuple(
                jnp.sum(mask16 * cat[i][j], axis=0) for j in range(3)
            )

        def body(acc, nib):
            for _ in range(_WINDOW):
                acc = eng.jac_double(*acc)
            m16 = (
                nib[None, None, :]
                == jnp.arange(16, dtype=jnp.float32)[:, None, None]
            ).astype(jnp.float32)
            q = (sel(m16, 0), sel(m16, 1), sel(m16, 2))
            return eng.jac_add(acc, q), None

        acc, _ = lax.scan(body, ident, nibbles_t)
        return acc

    return jax.jit(ec_rns_scalar_mult)


def _nibbles(scalars: list[int]) -> np.ndarray:
    """(NWIN, T) f32 window values, most-significant first."""
    ks = [s % P256.n for s in scalars]
    ed = limb.ints_to_limbs(ks, _DIGITS)  # (T, 16) 16-bit digits
    nib = np.empty((len(ks), _NWIN), dtype=np.float32)
    nib[:, 0::4] = ed & 0xF
    nib[:, 1::4] = (ed >> 4) & 0xF
    nib[:, 2::4] = (ed >> 8) & 0xF
    nib[:, 3::4] = (ed >> 12) & 0xF
    nib = nib[:, ::-1]
    return np.ascontiguousarray(nib.T)


def _ec_staging(padded: int):
    """Persistent EC-identity staging slot for one padded batch size.

    One ring per padded width (``ec:8``, ``ec:16``, ...) under the
    shared :mod:`bftkv_tpu.ops.devbuf` pool — the third width class of
    the device plane next to the RSA-2048/3072 pow rings.  Each slot
    carries a ``pad_lo`` watermark: columns ``pad_lo:`` are known to
    hold the identity-point encoding from an earlier call, so the
    steady state re-encodes only live rows and never re-pays the
    Python residue loop for the pad region.
    """
    from bftkv_tpu.ops import devbuf

    k = _engine().k

    def make():
        return {
            "res": np.empty((3, 2 * k + 1, padded), dtype=np.float32),
            "nib": np.empty((_NWIN, padded), dtype=np.float32),
            "pad_lo": np.full(1, padded, dtype=np.int64),
        }

    if not devbuf.enabled():
        return None, devbuf.Slot(make())
    ring = devbuf.ring_for(f"ec:{padded}", make, width="ec")
    slot = ring.acquire()
    if slot is None:
        return None, ring.fresh()
    return ring, slot


def scalar_mult_hosts(points: list, scalars: list[int]) -> list:
    """Batched k·P on the RNS field core; same contract as
    :func:`bftkv_tpu.ops.ec.scalar_mult_hosts` (power-of-two padding,
    floor 8).  Operands stage through a persistent ``devbuf`` ring
    (width class ``ec``); pad columns hold the identity point exactly
    as the historical pad-with-None lists did, so results are
    bit-identical with staging on or off."""
    if not points:
        return []
    from bftkv_tpu import ops

    ops.enable_compile_cache()
    eng = _engine()
    k = eng.k
    t = len(points)
    padded = max(8, 1 << (t - 1).bit_length())
    ring, slot = _ec_staging(padded)
    try:
        res, nib = slot["res"], slot["nib"]
        eng.encode_points_into(points, res[:, :, :t])
        nib[:, :t] = _nibbles(scalars)
        # Identity-pad only the columns a previous (larger) batch
        # dirtied; columns past the slot's watermark are already the
        # identity encoding from an earlier call.
        pad_lo = int(slot["pad_lo"][0])
        if t < pad_lo:
            eng.encode_points_into(
                [None] * (pad_lo - t), res[:, :, t:pad_lo]
            )
            nib[:, t:pad_lo] = 0.0
        slot["pad_lo"][0] = t
        X, Y, Z = (
            (
                jnp.asarray(comp[:k]),
                jnp.asarray(comp[k : 2 * k]),
                jnp.asarray(comp[2 * k :]),
            )
            for comp in res
        )
        out = _scalar_mult_fn()(*X, *Y, *Z, jnp.asarray(nib))
        # decode_points materializes the outputs, which forces the
        # launch that read the staged buffers to completion — the slot
        # is safe to recycle once we return.  (On the exception path a
        # ghost launch may still *read* the slot after release; jit
        # never writes into numpy operands, and the ghost's outputs
        # are discarded, so the next acquirer is unaffected.)
        return eng.decode_points(*out)[:t]
    finally:
        if ring is not None:
            ring.release(slot)


def scalar_base_mult_hosts(scalars: list[int]) -> list:
    return scalar_mult_hosts([(P256.gx, P256.gy)] * len(scalars), scalars)
