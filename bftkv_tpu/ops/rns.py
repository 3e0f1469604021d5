"""RSA verification in a residue number system — MXU/f32-native bignum.

The limb Montgomery engine (:mod:`bftkv_tpu.ops.bigint`) is bound by
*emulated* 32-bit integer multiplies on the VPU — a 128-limb
Montgomery product is a 128-step convolution of digit products, and
every digit product pays the int32-mul emulation tax. This module
removes both the convolution and the integer arithmetic:

- numbers live as residues modulo ~2k primes of 11-12 bits (two RNS
  bases B, B' plus a 2^12 redundant channel), so multiplication is
  *channelwise*: one native f32 multiply per lane (products < 2^24 are
  exactly representable) plus a Barrett reduction — f32 reciprocal,
  floor, and ≤2 conditional fixups, all native VPU ops;
- Montgomery reduction (Bajard et al.) needs two base extensions per
  product; each is Σ_i σ_i·(M/p_i mod target) — a matrix product whose
  matrix depends only on the prime bases, NOT the data → it runs on
  the MXU as four *exact* f32 matmuls (operands split into 6-bit
  halves, so every partial sum stays < 2^24);
- the B→B' extension is approximate (off by α·M, α < k — harmless:
  the bases carry ~200 bits of slack over 2048-bit moduli), while the
  B'→B return extension is made *exact* with the Shenoy–Kumaresan
  correction through the 2^12 redundant channel, keeping the bases
  consistent;
- the final check needs no RNS→positional conversion: with
  v ≡ s^e (mod N) and v < (k+1)·N, Δ_j = (v_j − em_j)·N⁻¹ mod p_j is
  the same small integer α = (v − em)/N in *every* channel iff the
  signature is valid; ~2k independent channels cannot agree otherwise.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bftkv_tpu import trace
from bftkv_tpu.devtools.lockwatch import named_lock
from bftkv_tpu.ops import devbuf
from bftkv_tpu import flags
from bftkv_tpu.metrics import registry as metrics

__all__ = [
    "Chains",
    "chains",
    "exp_class",
    "long_exp_bits",
    "RNSContext",
    "context",
    "pow_context",
    "verify_e65537_rns",
    "flat_verify_fn",
    "stack_key_rows",
    "assemble_key_rows",
    "digits_to_halves",
]

PR_BITS = 12
PR = 1 << PR_BITS  # redundant modulus (power of two)
DIGITS = 128  # 16-bit digits per 2048-bit number
SPLIT = 6  # matmul operand split (values < 64: f32 partials stay exact)
#: The wide pow chain, for rows the 12-bit supply cannot hold: channels
#: are the primes of [2^10, 2^13), channel products are taken in 7-bit
#: halves of one operand and matmul operands split 7 + 6 bits, so that
#: every f32 intermediate stays an exact integer below 2^24
#: (docs/DESIGN.md, "The wide pow chain").  Its bound is checked for
#: rows up to ``WIDE_MAX_BITS``.
WIDE_BITS = 13
WIDE_SPLIT = 7
WIDE_MAX_BITS = 4096


def _gen_primes(lo: int, hi: int) -> list[int]:
    sieve = np.ones(hi - lo, dtype=bool)
    for p in range(2, int(hi**0.5) + 1):
        start = max(p * p, ((lo + p - 1) // p) * p)
        sieve[start - lo :: p] = False
    return [int(lo + i) for i in np.nonzero(sieve)[0]]


def _deal_bases(
    n_bits: int, top_bits: int = PR_BITS
) -> tuple[list[int], list[int]]:
    """The two bases for numbers of ``n_bits`` bits: all primes of
    [2^10, 2^top_bits), largest first, dealt alternately so both get
    ~equal bit mass, until each clears ``n_bits`` by a healthy margin
    (the AMM slack analysis needs M > (k+2)^2 N), then cut to equal
    channel counts.  The supply is finite — below 2^12, 392 primes,
    4,391 bits; below 2^13, 856 primes, 10,214 bits — so this is where
    a width the chains cannot hold is found out: ValueError.
    """
    need = n_bits + 64
    pb: list[int] = []
    pq: list[int] = []
    bits_b = bits_q = 0.0
    for p in _gen_primes(1 << 10, 1 << top_bits)[::-1]:
        if bits_b <= bits_q:
            pb.append(p)
            bits_b += np.log2(p)
        else:
            pq.append(p)
            bits_q += np.log2(p)
        if bits_b > need and bits_q > need:
            break
    else:
        raise ValueError("not enough sub-2^12 primes for the bases")
    # Equal channel counts keep the matmul shapes square-ish.
    k = min(len(pb), len(pq))
    pb, pq = pb[:k], pq[:k]
    if math.prod(pb) <= (1 << need) or math.prod(pq) <= (1 << need):
        raise ValueError("base bit mass too small")
    return pb, pq


@functools.lru_cache(maxsize=256)
def _bases_hold(n_bits: int, top_bits: int = PR_BITS) -> bool:
    try:
        _deal_bases(n_bits, top_bits)
    except ValueError:
        return False
    return True


def _channel_bits(n_bits: int) -> int | None:
    """The channel width of the pow chain at ``n_bits``-bit rows: 12
    where the sub-2^12 primes hold two bases (every class that rode
    before the wide chain keeps them), 13 for wider rows up to
    ``WIDE_MAX_BITS``, None past that."""
    if _bases_hold(n_bits):
        return PR_BITS
    if n_bits <= WIDE_MAX_BITS and _bases_hold(n_bits, WIDE_BITS):
        return WIDE_BITS
    return None


class Chains(NamedTuple):
    """Which device chains can take a number of some width."""

    verify: bool  # a modulus this wide rides the verify chain
    pow: bool  # a row (modulus and exponent) this wide rides the pow chain


def long_exp_bits(n_bits: int) -> int:
    """The longer of a modulus class's two exponent classes, in bits:
    ``2 * n_bits + 64``.  A threshold-RSA first-level fragment of an
    ``n_bits``-bit d dealt (., n) is d minus n - 1 random values below
    ``2^(2 * n_bits - 1)``: up to ``2 * n_bits + ceil(log2 n) + 1``
    bits (crypto/threshold/rsa.py:_split_key); the 64 is that for any
    n the tree can be dealt for, rounded up to whole bytes of 4-bit
    windows."""
    return 2 * n_bits + 64


def long_exp_rows(n_bits: int) -> int:
    """Rows one launch of the longer exponent class at ``n_bits``-bit
    rows holds at most: one tile of the fused chain — 128 on 12-bit
    channels (2,048-bit rows: kpad 256), 64 on the wide chain's (4,096
    bits: kpad 384; a 128-row launch of 64-row tiles would block its
    window array 64 lanes wide, which Mosaic refuses).  More rows are
    more launches, the device time of as many tiles."""
    return 64 if _channel_bits(n_bits) == WIDE_BITS else 128


def exp_class(n_bits: int, exp_bits: int) -> int | None:
    """The exponent class (bits) of the pow chain at ``n_bits``-bit
    rows that holds an exponent of ``exp_bits`` bits: ``n_bits`` itself
    (every sign row: dp, dq; whole-modulus rows whose exponents are no
    wider), :func:`long_exp_bits` beyond it, None past that."""
    if exp_bits <= n_bits:
        return n_bits
    if exp_bits <= long_exp_bits(n_bits):
        return long_exp_bits(n_bits)
    return None


@functools.lru_cache(maxsize=256)
def chains(bits: int, exp_bits: int | None = None) -> Chains:
    """THE capability rule: what the RNS chains can take at ``bits``
    (the modulus) and, for the pow chain, ``exp_bits`` (the exponent;
    None: no wider than the modulus).

    The pow chain is compiled per row width (``context(digits,
    n_bits)``), so it takes any modulus two bases can be built for:
    from the primes below 2^12 up to about 2,130 bits (the CRT halves
    of RSA-2048, -3072 and -4096, whole moduli up to 2048 bits), and
    past that, on the wide chain's 13-bit channels, up to 4,096 bits
    (``WIDE_MAX_BITS``: whole RSA-3072 and RSA-4096 moduli).  Its
    window count is a shape of its own: at each row width two exponent
    classes have programs, exponents up to the row width
    (``rns_pow_<bits>``) and exponents up to ``long_exp_bits(bits)`` =
    2 x bits + 64 (``rns_pow_<bits>_e<exp bits>``: 4,160 bits at
    2,048-bit rows, 8,256 at 4,096, what a first-level threshold-RSA
    fragment of a key of that width needs); a longer exponent rides no
    chain.  The verify chain works on whole moduli in the one context
    ``context()`` and takes what fits its digits.  A wider modulus is
    not hostile, it is beyond the chains, and belongs to the native
    host tier (``crypto/rsa.py:verify_host_many``).  Everyone who
    routes by width — verifier, fault check, signer, modexp
    dispatcher, tenant channel, the sidecar's warm-up — asks here.
    """
    if bits <= 0:
        return Chains(False, False)
    return Chains(
        verify=bits <= 16 * DIGITS and _bases_hold(16 * DIGITS),
        pow=_channel_bits(bits) is not None and (
            exp_bits is None or exp_class(bits, exp_bits) is not None
        ),
    )


_unwarmed_logged: set = set()


def note_unwarmed(what: str, bits: int, items: int) -> None:
    """Items of a width whose device program the owner did not build
    (the sidecar's warm-up, by the deployment's declared identity
    widths and CA width): they are served from the host tier — counted
    per item (``sidecar.unwarmed_width``), logged once a kind and width
    — and never compile inside a request."""
    metrics.incr("sidecar.unwarmed_width", items)
    if (what, bits) not in _unwarmed_logged and len(_unwarmed_logged) < 64:
        _unwarmed_logged.add((what, bits))
        logging.getLogger("bftkv_tpu.ops.rns").warning(
            "%s of %d bits arrived, and this deployment's declared "
            "widths (BFTKV_IDENTITY_BITS, BFTKV_CA_BITS) built no device "
            "program for them: served on the host tier", what, bits,
        )


def pow_rows_warm(
    n_bits: int, warm_rows, items: int = 1, exp_bits: int | None = None
) -> bool:
    """Whether a pow launch at ``n_bits``-bit rows (exponent class
    ``exp_bits``; None: the rows' own width) may go to the device now.
    ``warm_rows`` is the owner's word on which row classes have their
    programs built — ``n`` for rows whose exponents are no wider than
    they, ``(n, e)`` for a longer exponent class — or ``None``: nobody
    said, compile on first use."""
    cls = n_bits if exp_bits in (None, n_bits) else (n_bits, exp_bits)
    if warm_rows is None or cls in warm_rows:
        return True
    note_unwarmed("pow rows", n_bits, items)
    return False


class RNSContext:
    """Shared (key-independent) precomputation for one digit width."""

    def __init__(self, digits: int = DIGITS, n_bits: int = 2048):
        # past the wide chain's range this deals from the 12-bit supply
        # and raises, as any width beyond the chains does
        top = _channel_bits(n_bits) or PR_BITS
        self.wide = top == WIDE_BITS
        self.split = WIDE_SPLIT if self.wide else SPLIT
        self.pb, self.pq = _deal_bases(n_bits, top)
        k = self.k = len(self.pb)
        self.digits = digits
        self.M = math.prod(self.pb)
        self.Mq = math.prod(self.pq)

        f = lambda xs: np.asarray(xs, dtype=np.float32)
        self.p_all = f(self.pb + self.pq)
        self.inv_all = np.float32(1.0) / self.p_all  # Barrett reciprocals

        # --- extension B -> B' (+ redundant channel) ------------------
        Mi = [self.M // p for p in self.pb]
        self.invMi_b = f([pow(Mi[i] % p, -1, p) for i, p in enumerate(self.pb)])
        E1 = np.zeros((k, k + 1), dtype=np.int64)
        for i in range(k):
            for j, q in enumerate(self.pq):
                E1[i, j] = Mi[i] % q
            E1[i, k] = Mi[i] % PR
        self._E1 = self._split(E1)

        # --- extension B' -> B (+ redundant channel, Shenoy) ----------
        Mqj = [self.Mq // q for q in self.pq]
        self.invMi_q = f([pow(Mqj[j] % q, -1, q) for j, q in enumerate(self.pq)])
        E2 = np.zeros((k, k + 1), dtype=np.int64)
        for j in range(k):
            for i, p in enumerate(self.pb):
                E2[j, i] = Mqj[j] % p
            E2[j, k] = Mqj[j] % PR
        self._E2 = self._split(E2)
        self.Mq_mod_b = f([self.Mq % p for p in self.pb])
        self.invMq_pr = np.float32(pow(self.Mq % PR, -1, PR))
        self.invM_q = f([pow(self.M % q, -1, q) for q in self.pq])
        self.invM_pr = np.float32(pow(self.M % PR, -1, PR))

        # --- digit -> residue conversion ------------------------------
        # Digits are 16-bit; split each into two 8-bit halves so the
        # conversion matmul operands stay < 2^8 (f32 partial sums over
        # 256 half-digits < 256·255·2^12 ≈ 2^26 — too big; split the
        # *matrix* to 6 bits instead and the data to 8: partials
        # < 256·255·63 ≈ 2^22 — exact).
        D = np.zeros((2 * digits, 2 * k + 1), dtype=np.int64)
        for d in range(digits):
            w_lo = pow(1 << 16, d)
            w_hi = (w_lo << 8)
            for ch, p in enumerate(self.pb + self.pq):
                D[2 * d, ch] = w_lo % p
                D[2 * d + 1, ch] = w_hi % p
            D[2 * d, 2 * k] = w_lo % PR
            D[2 * d + 1, 2 * k] = w_hi % PR
        self._D = self._split(D)
        self.pow_keys = _PowKeyTable(self)

    def _split(self, m: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Channel-width entries → two f32 planes, the low ``split``
        bits and the rest: 6 + 6 on 12-bit channels, 7 + 6 on the wide
        chain's 13-bit ones."""
        s = self.split
        return (
            (m & ((1 << s) - 1)).astype(np.float32),
            (m >> s).astype(np.float32),
        )

    # -- per-key (per modulus N) data, host side ------------------------

    @functools.lru_cache(maxsize=4096)
    def key_rows(self, n: int):
        """Channel constants for one public modulus ``n`` (cached).

        Returns None for a modulus this context cannot build rows
        for: even, wider than its digits (callers route by width
        first — :func:`chains` — so that is a caller's slip, not a
        hostile key: an RSA-3072 modulus is merely beyond the bases),
        or sharing a factor with a channel prime — real RSA moduli
        never do, but certificates are attacker-supplied, so such
        keys must fall back, not raise.
        """
        if n <= 0 or n % 2 == 0 or n.bit_length() > 16 * self.digits:
            return None
        chans = self.pb + self.pq
        for p in chans:
            if n % p == 0:
                return None
        f = lambda xs: np.asarray(xs, dtype=np.float32)
        n_all = f([n % p for p in chans])
        n_r = np.float32(n % PR)
        neg_ninv_b = f([(-pow(n, -1, p)) % p for p in self.pb])
        ninv_all = f([pow(n % p, -1, p) for p in chans])
        m2 = (self.M * self.M) % n
        m2_all = f([m2 % p for p in chans])
        m2_r = np.float32(m2 % PR)
        return n_all, n_r, neg_ninv_b, ninv_all, m2_all, m2_r


@functools.lru_cache(maxsize=8)
def context(digits: int = DIGITS, n_bits: int = 2048) -> RNSContext:
    return RNSContext(digits, n_bits)


def pow_context(n_bits: int) -> RNSContext:
    """The context of the pow chain at ``n_bits``-bit rows: a modulus
    rides :func:`pow_rows_rns` there when this context has
    ``key_rows`` for it."""
    return context(max(32, (n_bits + 15) // 16), n_bits)


# ---------------------------------------------------------------------------
# Device side. All tensors are f32 holding exact integers < 2^24;
# channels ride the last axis. One number = (xb (T,k), xq (T,k), xr (T,1)).
# ---------------------------------------------------------------------------

_PRF = np.float32(PR)
_INV_PRF = np.float32(1.0 / PR)


def _barrett(x, inv_p, p):
    """x mod p for integral f32 x < 2^24; exact via reciprocal + fixups."""
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
    """a·b mod p on the wide chain's 13-bit channels, where a·b (up to
    2^26) is no exact f32: ``b`` in 7-bit halves, each partial product
    and their sum below 2^21."""
    bh = jnp.floor(b * np.float32(1 / 128))
    bl = b - bh * 128
    return _barrett(a * bl + _barrett(a * bh, inv_p, p) * 128, inv_p, p)


def _addmod(a, b, p):
    s = a + b
    return jnp.where(s >= p, s - p, s)


def _submod(a, b, p):
    d = a - b
    return jnp.where(d < 0, d + p, d)


def _mod_r(x):
    """x mod 2^12 for integral f32 x < 2^24 (exact)."""
    return x - jnp.floor(x * _INV_PRF) * _PRF


def _mulmod_r(a, b):
    return _mod_r(a * b)


def _matmul_f32(x, m_split, split: int = SPLIT):
    """Exact Σ_i x[i]·M[i,j] via bf16 MXU matmuls with f32 accumulate.

    ``x`` (T,rows) f32 integral < 2^12, split into 6-bit halves; the
    matrix is pre-split.  Every operand is < 64, which bf16 represents
    exactly (8 significant bits), and the MXU multiplies bf16 natively
    into an f32 accumulator — one systolic pass per dot instead of
    XLA's multi-pass f32 emulation.  Partial products < 2^12, summed
    over ≤ 400 rows < 2^21 — exact.  Returns (s_ll, s_mid, s_hh).
    On the wide chain (``split`` 7) ``x`` < 2^13 and the low planes
    hold 7 bits: partials < 2^14, summed over ≤ 512 rows < 2^23.
    """
    mlo, mhi = m_split
    s = 1 << split
    xlo = x - jnp.floor(x * np.float32(1 / s)) * s  # x & (s-1), f32-exact
    xhi = jnp.floor(x * np.float32(1 / s))
    dot = lambda a, b: jax.lax.dot_general(
        a.astype(jnp.bfloat16),
        b.astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    s_ll = dot(xlo, mlo)
    s_mid = dot(xlo, mhi) + dot(xhi, mlo)
    s_hh = dot(xhi, mhi)
    return s_ll, s_mid, s_hh


def _combine_mod(s_ll, s_mid, s_hh, inv_p, p, split: int = SPLIT):
    """(s_ll + 2^6·s_mid + 2^12·s_hh) mod p, channelwise, f32-exact.

    Partials < 2^22; reduce each below p (< 2^12) before shifting so
    every intermediate stays < 2^24.  (Wide chain: shifts of 2^7 and
    2^14, p < 2^13, intermediates < 2^20.)"""
    s = 1 << split
    a = _barrett(s_ll, inv_p, p)
    b = _barrett(s_mid, inv_p, p)
    d = _barrett(s_hh, inv_p, p)
    b6 = _barrett(b * s, inv_p, p)
    d12 = _barrett(_barrett(d * s, inv_p, p) * s, inv_p, p)
    return _addmod(_addmod(a, b6, p), d12, p)


def _combine_mod_r(s_ll, s_mid, s_hh, split: int = SPLIT):
    s = 1 << split
    return _mod_r(_mod_r(s_ll) + _mod_r(s_mid * s) + _mod_r(_mod_r(s_hh * s) * s))


class _Consts:
    """Device-resident context constants bundled for one jit call."""

    def __init__(self, ctx: RNSContext):
        self.k = ctx.k
        self.split = ctx.split
        self.mul = _mulmod_wide if ctx.wide else _mulmod
        j = jnp.asarray
        self.pb = j(ctx.p_all[: ctx.k])
        self.pq = j(ctx.p_all[ctx.k :])
        self.ib = j(ctx.inv_all[: ctx.k])
        self.iq = j(ctx.inv_all[ctx.k :])
        self.invMi_b = j(ctx.invMi_b)
        self.invMi_q = j(ctx.invMi_q)
        self.E1 = (j(ctx._E1[0]), j(ctx._E1[1]))
        self.E2 = (j(ctx._E2[0]), j(ctx._E2[1]))
        self.D = (j(ctx._D[0]), j(ctx._D[1]))
        self.Mq_mod_b = j(ctx.Mq_mod_b)
        self.invMq_pr = jnp.float32(ctx.invMq_pr)
        self.invM_q = j(ctx.invM_q)
        self.invM_pr = jnp.float32(ctx.invM_pr)


def _mont_mul(cn, a, b, key):
    """RNS Montgomery product (Bajard AMM + Shenoy return extension)."""
    ab, aq, ar = a
    bb, bq, br = b
    n_all, n_r, neg_ninv_b, _ninv, _m2, _m2r = key
    k, sp, mul = cn.k, cn.split, cn.mul
    nq = n_all[:, k:]

    db = mul(ab, bb, cn.ib, cn.pb)
    dq = mul(aq, bq, cn.iq, cn.pq)
    dr = _mulmod_r(ar, br)

    # q = d·(−N⁻¹) mod M, channelwise in B.
    qb = mul(db, neg_ninv_b, cn.ib, cn.pb)
    # Approximate extension of q̂ = Σ σ_i·M_i (= q + α₁M) to B' ∪ {2^12}.
    sigma = mul(qb, cn.invMi_b, cn.ib, cn.pb)
    s_ll, s_mid, s_hh = _matmul_f32(sigma, cn.E1, sp)
    qhat_q = _combine_mod(
        s_ll[:, :k], s_mid[:, :k], s_hh[:, :k], cn.iq, cn.pq, sp
    )
    qhat_r = _combine_mod_r(s_ll[:, k:], s_mid[:, k:], s_hh[:, k:], sp)

    # r = (d + q̂·N)/M in B' and the redundant channel.
    t = mul(qhat_q, nq, cn.iq, cn.pq)
    rq = mul(_addmod(dq, t, cn.pq), cn.invM_q, cn.iq, cn.pq)
    tr = _mulmod_r(qhat_r, n_r)
    rr = _mulmod_r(_mod_r(dr + tr), cn.invM_pr)

    # Exact extension of r from B' back to B (Shenoy via 2^12 channel).
    sigma2 = mul(rq, cn.invMi_q, cn.iq, cn.pq)
    z_ll, z_mid, z_hh = _matmul_f32(sigma2, cn.E2, sp)
    ext_b = _combine_mod(
        z_ll[:, :k], z_mid[:, :k], z_hh[:, :k], cn.ib, cn.pb, sp
    )
    ext_r = _combine_mod_r(z_ll[:, k:], z_mid[:, k:], z_hh[:, k:], sp)
    alpha = _mulmod_r(_mod_r(ext_r - rr + _PRF), cn.invMq_pr)
    corr = mul(
        jnp.broadcast_to(alpha, ext_b.shape),
        jnp.broadcast_to(cn.Mq_mod_b, ext_b.shape),
        cn.ib,
        cn.pb,
    )
    rb = _submod(ext_b, corr, cn.pb)
    return rb, rq, rr


def _to_residues(cn, digit_halves):
    """(T, 256) 8-bit digit halves → residues over [B | B' | 2^12]."""
    k, sp = cn.k, cn.split
    s_ll, s_mid, s_hh = _matmul_f32(digit_halves, cn.D, sp)
    xb = _combine_mod(
        s_ll[:, :k], s_mid[:, :k], s_hh[:, :k], cn.ib, cn.pb, sp
    )
    xq = _combine_mod(
        s_ll[:, k : 2 * k], s_mid[:, k : 2 * k], s_hh[:, k : 2 * k],
        cn.iq, cn.pq, sp,
    )
    xr = _combine_mod_r(
        s_ll[:, 2 * k :], s_mid[:, 2 * k :], s_hh[:, 2 * k :], sp
    )
    return xb, xq, xr


def _verify_kernel(cn: _Consts, sig_halves, em_halves, key):
    n_all, n_r, neg_ninv_b, ninv_all, m2_all, m2_r = key
    k = cn.k
    s = _to_residues(cn, sig_halves)
    em_b, em_q, _em_r = _to_residues(cn, em_halves)

    m2 = (m2_all[:, :k], m2_all[:, k:], m2_r)
    sm = _mont_mul(cn, s, m2, key)  # to Montgomery form

    acc = sm
    for _ in range(16):
        acc = _mont_mul(cn, acc, acc, key)
    acc = _mont_mul(cn, acc, sm, key)

    one = (jnp.ones_like(sm[0]), jnp.ones_like(sm[1]), jnp.ones_like(sm[2]))
    vb, vq, _vr = _mont_mul(cn, acc, one, key)  # v ≡ s^e (mod N), v < (k+1)N

    # Δ_j = (v_j − em_j)·N⁻¹ mod p_j: the same small α in every channel
    # iff v ≡ em (mod N).
    delta_b = _mulmod(_submod(vb, em_b, cn.pb), ninv_all[:, :k], cn.ib, cn.pb)
    delta_q = _mulmod(_submod(vq, em_q, cn.pq), ninv_all[:, k:], cn.iq, cn.pq)
    alpha = delta_b[:, :1]
    ok = jnp.all(delta_b == alpha, axis=1) & jnp.all(delta_q == alpha, axis=1)
    return ok & (alpha[:, 0] <= cn.k + 1)


def flat_verify_fn():
    """The verify step with a flat signature — the public jittable for
    drivers and benchmarks (the graft entry / shard_map wrap it):
    ``f(sig_h, em_h, n_all, n_r, neg_ninv_b, ninv_all, m2_all, m2_r)``.
    """
    cn = _Consts(context())

    def f(sig_h, em_h, n_all, n_r, neg_ninv_b, ninv_all, m2_all, m2_r):
        return _verify_kernel(
            cn, sig_h, em_h, (n_all, n_r, neg_ninv_b, ninv_all, m2_all, m2_r)
        )

    return f


@functools.lru_cache(maxsize=1)
def _jitted_verify():
    f = flat_verify_fn()

    @jax.jit
    def rns_verify(sig_halves, em_halves, key):
        return f(sig_halves, em_halves, *key)

    return rns_verify


@functools.lru_cache(maxsize=1)
def _jitted_verify_gather():
    """Verify with device-side key gather and uint8 operands.

    The per-row key tensors are ~12 KB each and a cluster flush
    repeats a handful of distinct keys thousands of times.  Shipping
    (K, ·) unique-key tensors plus a (T,) index and casting u8→f32 on
    device cuts the host→device bytes ~12x.
    """
    cn = _Consts(context())

    @jax.jit
    def rns_verify_gather(sig_halves_u8, em_halves_u8, idx, ukey):
        key = tuple(u[idx] for u in ukey)
        return _verify_kernel(
            cn,
            sig_halves_u8.astype(jnp.float32),
            em_halves_u8.astype(jnp.float32),
            key,
        )

    return rns_verify_gather


# ---------------------------------------------------------------------------
# General modexp in RNS — the signing hot path (CRT halves of RSA keys).
#
# Unlike verify (fixed e = 65537), exponents here are per-row secrets up
# to the modulus width.  Fixed 4-bit windows keep the schedule uniform
# across the batch: every step is 4 squarings plus one multiply by a
# table entry selected with a one-hot matvec (no data-dependent control
# flow, no gather) — constant-time by construction, SURVEY §7 hard
# part 3 applied to modexp.  The AMM invariant (inputs < (k+2)N keep
# outputs < (k+2)N when M > (k+2)²N) is iteration-stable, so a
# 256-step chain needs no extra slack over verify's 18-step chain.
# ---------------------------------------------------------------------------


def _pow_kernel(cn: _Consts, base_halves, exp_nibbles_t, key):
    """acc = base^exp mod N per row; returns CRT coefficients σ over B.

    ``exp_nibbles_t``: (W, T) f32 most-significant-nibble first.
    """
    k = cn.k
    m2 = (key[4][:, :k], key[4][:, k:], key[5])

    def one_like(x):
        return (
            jnp.ones_like(x[0]),
            jnp.ones_like(x[1]),
            jnp.ones_like(x[2]),
        )

    base = _to_residues(cn, base_halves)
    ones = one_like(base)
    base_m = _mont_mul(cn, base, m2, key)  # to Montgomery form
    one_m = _mont_mul(cn, m2, ones, key)  # M mod N, the Montgomery one

    # 16-entry window table in Montgomery form: t[w] = base^w.
    tab = [one_m, base_m]
    for _ in range(14):
        tab.append(_mont_mul(cn, tab[-1], base_m, key))
    tb = jnp.stack([t[0] for t in tab], axis=1)  # (T, 16, k)
    tq = jnp.stack([t[1] for t in tab], axis=1)
    tr = jnp.stack([t[2] for t in tab], axis=1)  # (T, 16, 1)

    def body(acc, nib):
        for _ in range(4):
            acc = _mont_mul(cn, acc, acc, key)
        oh = jax.nn.one_hot(nib.astype(jnp.int32), 16, dtype=jnp.float32)
        sel = (
            jnp.einsum("tw,twc->tc", oh, tb),
            jnp.einsum("tw,twc->tc", oh, tq),
            jnp.einsum("tw,twc->tc", oh, tr),
        )
        return _mont_mul(cn, acc, sel, key), None

    acc, _ = jax.lax.scan(body, one_m, exp_nibbles_t)
    vb, _vq, _vr = _mont_mul(cn, acc, ones, key)  # out of Montgomery form
    # CRT coefficients: σ_i = v_i·(M_i⁻¹ mod p_i); host side rebuilds
    # v = Σ σ_i·M_i (< M, no α ambiguity since v < (k+1)·N ≪ M).
    return cn.mul(vb, cn.invMi_b, cn.ib, cn.pb)


def _pow_name(n_bits: int, exp_bits: int | None) -> str:
    """A pow program's name: its row width and, where the exponent
    class is not the rows' own, that too (``rns_pow_2048_e4160``)."""
    if exp_bits in (None, n_bits):
        return f"rns_pow_{n_bits}"
    return f"rns_pow_{n_bits}_e{exp_bits}"


@functools.lru_cache(maxsize=8)
def _jitted_pow(
    digits: int, n_bits: int, donate: bool = False,
    exp_bits: int | None = None,
):
    """uint8 operands + device-side gather of the (few) unique moduli —
    same transfer-lean scheme as the verify path.

    ``donate=True`` (accelerator backends only) donates the per-batch
    operand buffers: XLA may alias the freshly-transferred arrays into
    the kernel instead of defensively copying them — the host-side
    staging slot (:mod:`bftkv_tpu.ops.devbuf`) stays owned by the host
    and is reused for the next flush.  CPU ignores donation with a
    warning, so callers gate it on the backend.

    The program is named after its width (``rns_pow_1024``: the CRT
    halves of RSA-2048) and, where the exponent class is the longer
    one (``exp_bits``), after that too (``rns_pow_2048_e4160``: a
    first-level threshold fragment), so a device trace tells the chains
    apart.  The window count is the staged array's: one function a
    class, one program a class and bucket."""
    cn = _Consts(context(digits, n_bits))

    def rns_pow(base_halves_u8, exp_nibbles_t_u8, idx, ukey):
        key = tuple(u[idx] for u in ukey)
        return _pow_kernel(
            cn,
            base_halves_u8.astype(jnp.float32),
            exp_nibbles_t_u8.astype(jnp.float32),
            key,
        )

    rns_pow.__name__ = _pow_name(n_bits, exp_bits)
    return jax.jit(rns_pow, donate_argnums=(0, 1, 2) if donate else ())


def _crt_digit_bytes(ctx: RNSContext) -> int:
    """Bytes of one digit plane of :func:`_crt_matrix`: 4, so that row
    sums Σ σ_i·M_i stay < k·2^12·2^32 < 2^53 (k < 512) — exact; on the
    wide chain's 13-bit channels (k·2^13·2^32 passes 2^53 at k = 340)
    2: sums < k·2^13·2^16."""
    return 2 if ctx.wide else 4


def _crt_matrix(ctx: RNSContext) -> np.ndarray:
    """(k, D) float64 digit planes of M_i = M/p_i, cached on ctx.
    Row sums Σ σ_i·M_i are exact (:func:`_crt_digit_bytes`)."""
    m = getattr(ctx, "_crt_digits", None)
    if m is None:
        b = _crt_digit_bytes(ctx)
        width = (ctx.M.bit_length() + PR_BITS + 8 * b - 1) // (8 * b) + 1
        if ctx.wide:
            width += 1  # σ_i < 2^13: one more bit of carry than 2^12
        m = np.stack([
            np.frombuffer(
                (ctx.M // p).to_bytes(b * width, "little"), f"<u{b}"
            )
            for p in ctx.pb
        ]).astype(np.float64)
        ctx._crt_digits = m
    return m


def _sigma_to_ints(ctx: RNSContext, sigma: np.ndarray) -> list[int]:
    """Batched RNS→integer: the digit sums Σ σ_i·M_i, one carry pass,
    one byte string for the whole batch.

    ``einsum`` and not ``@``: a float64 ``@`` of this size wakes the
    BLAS pool, whose workers then spin on every core of a host that
    the daemons' native RSA batches also want.  ``einsum`` runs on the
    caller's thread, releases the GIL, and its sums (integers below
    2^53) are exact in any order."""
    acc = np.einsum(
        "tk,kd->td", sigma.astype(np.float64), _crt_matrix(ctx)
    ).astype(np.int64)
    b = _crt_digit_bytes(ctx)
    out = np.empty(acc.shape, dtype=f"<u{b}")
    carry = np.zeros(acc.shape[0], dtype=np.int64)
    for d in range(acc.shape[1]):
        carry += acc[:, d]
        out[:, d] = carry  # the low 8·b bits
        carry >>= 8 * b
    raw = out.tobytes()
    w = b * acc.shape[1]
    big = ctx.M
    return [
        int.from_bytes(raw[o : o + w], "little") % big
        for o in range(0, len(raw), w)
    ]


class DeferredModexp:
    """Handle for a non-blocking :func:`pow_rows_rns` launch.

    The kernel is already on the device stream when this is returned;
    :meth:`wait` materializes the device result, rebuilds the integers,
    and releases the staging slot.  Exactly one waiter finalizes it
    (the dispatcher's completion-drain thread)."""

    __slots__ = ("_finish", "_value", "_done")

    def __init__(self, finish):
        self._finish = finish
        self._value = None
        self._done = False

    def wait(self) -> list[int]:
        if not self._done:
            self._done = True
            fin, self._finish = self._finish, None
            self._value = fin()
        return self._value


def _pow_staging(digits: int, n_bits: int, padded: int, windows: int):
    """One launch's operand arrays — a persistent devbuf slot when the
    rings are on (``None`` ring → plain throwaway arrays).  ``windows``
    is the exponent class in 4-bit windows: a ring a class."""
    shapes = {
        "base_halves": ((padded, 2 * digits), np.uint8),
        "nib_t": ((windows, padded), np.uint8),
        "idx": ((padded,), np.int32),
    }

    def make():
        return {k: np.empty(s, d) for k, (s, d) in shapes.items()}

    if not devbuf.enabled():
        return None, devbuf.Slot(make())
    name = f"pow:{digits}:{n_bits}:{padded}"
    if windows != 4 * digits:
        name += f":e{4 * windows}"
    ring = devbuf.ring_for(name, make, width=str(digits))
    slot = ring.acquire()
    if slot is None:
        return None, ring.fresh()  # ring saturated: unpooled fallback
    return ring, slot


class _PowKeyTable:
    """The pow chain's key rows as the device holds them, at one row
    width: a prime keeps its slot from launch to launch, so the
    stacked table is built and uploaded when a new prime enters — a
    handful of times in the life of a process that signs for 4–10
    keys — and not once a launch.  The jitted chain does not donate
    its key operand, so the same device arrays are handed to every
    launch until the table changes.

    The unique-modulus axis has a fixed floor of 64 rows: cross-request
    flushes mix many signers' p/q, and every fresh (T, K) pair would
    recompile the 256-step scan (~15-60 s); 64 padded key rows are
    < 1 MB.  A launch whose primes do not fit beside the ones placed
    restarts the table from its own; one with more than 64 distinct
    primes gets a table of its own, padded to a power of two and not
    kept."""

    FLOOR = 64

    def __init__(self, ctx: RNSContext):
        self._ctx = ctx
        self._lock = named_lock("ops.rns.keytable")
        self._slots: dict[int, int] = {}
        self._rows: list = []
        self._dev: tuple | None = None

    @staticmethod
    def _upload(rows: list, kpad: int) -> tuple:
        metrics.incr("pow.keytable.upload")
        return tuple(
            jnp.asarray(a) for a in stack_key_rows(rows, pad_to=kpad)
        )

    def place(self, umods: list[int]):
        """``(slots, ukey)`` for a launch over the moduli ``umods``:
        each one's row in the stacked device table ``ukey`` (an int32
        array beside ``umods``).  None when some modulus has no rows
        (``RNSContext.key_rows``): the caller falls back."""
        with self._lock:
            slots, rows = self._slots, self._rows
            new = dict.fromkeys(m for m in umods if m not in slots)
            if len(rows) + len(new) > self.FLOOR:
                slots, rows = {}, []
                new = dict.fromkeys(umods)
            fresh = [self._ctx.key_rows(m) for m in new]
            if any(r is None for r in fresh):
                return None
            for m, r in zip(new, fresh):
                slots[m] = len(rows)
                rows.append(r)
            at = np.fromiter(
                (slots[m] for m in umods), dtype=np.int32, count=len(umods)
            )
            if len(rows) > self.FLOOR:
                kpad = 1 << (len(rows) - 1).bit_length()
                return at, self._upload(rows, kpad)
            if new:
                self._slots, self._rows = slots, rows
                self._dev = self._upload(rows, self.FLOOR)
            return at, self._dev


def exp_nibbles(
    exps: list[int], digits: int, windows: int | None = None
) -> np.ndarray:
    """``(len(exps), windows)`` uint8: each exponent's 4-bit windows,
    most significant first, as the pow chain scans them — ``windows``
    (even) is the exponent class, ``4 * digits`` (exponents as wide as
    the rows) where none is given.  A number's little-endian bytes are
    its nibbles two by two, low one first."""
    if windows is None:
        windows = 4 * digits
    raw = np.frombuffer(
        b"".join(e.to_bytes(windows // 2, "little") for e in exps),
        dtype=np.uint8,
    ).reshape(len(exps), windows // 2)
    nib = np.empty((len(exps), windows), dtype=np.uint8)
    nib[:, 0::2] = raw & 0xF
    nib[:, 1::2] = raw >> 4
    return nib[:, ::-1]


def power_mod_rns(
    bases: list[int], exps: list[int], mods: list[int], *,
    n_bits: int = 1024, exp_bits: int | None = None,
    defer: bool = False, op: str = "modexp",
):
    """Batched x^e mod m with per-row (x, e, m) — the threshold-RSA
    workhorse.  Returns a list of ints, or None when any modulus or
    exponent cannot ride the RNS path (caller falls back).

    ``n_bits`` is the modulus class (the row width) and ``exp_bits``
    the exponent class, a shape of its own: ``n_bits`` where none is
    given, or :func:`long_exp_bits` of it (a first-level threshold
    fragment is about twice its modulus: rsa.go:97-117, 140-178); an
    exponent one bit past its class, or a class :func:`chains` does
    not hold, answers None.  The way in for callers whose exponents
    come a row: it turns the rows' integers into what
    :func:`pow_rows_rns` takes — the launch itself, ``defer`` and
    ``op`` are that function's.
    """
    if not mods:
        return []
    if exp_bits is None:
        exp_bits = n_bits
    if exp_class(n_bits, exp_bits) != exp_bits:
        return None
    for e in exps:
        if e < 0 or e.bit_length() > exp_bits:
            return None
    ctx = pow_context(n_bits)
    long_exp = {} if exp_bits == n_bits else {"exp_bits": exp_bits}
    with trace.leaf(
        "flush.stage", op, items=len(mods), bits=n_bits, **long_exp
    ):
        unique: dict[int, int] = {}
        row_mod = np.fromiter(
            (unique.setdefault(m, len(unique)) for m in mods),
            dtype=np.intp, count=len(mods),
        )
        umods = list(unique)
        if any(
            m <= 0 or m.bit_length() > 16 * ctx.digits for m in umods
        ):
            return None  # no rows for it: asked before its bytes are
        base_bytes = b"".join(
            (b % m).to_bytes(2 * ctx.digits, "little")
            for b, m in zip(bases, mods)
        )
        nib_cols = exp_nibbles(
            exps, ctx.digits,
            # the rows' own width: the context's digits, as ever
            None if exp_bits == n_bits else -(-exp_bits // 8) * 2,
        )
    return pow_rows_rns(
        n_bits, umods, row_mod, base_bytes, nib_cols, None,
        defer=defer, op=op,
    )


def pow_rows_rns(
    n_bits: int, umods: list[int], row_mod: np.ndarray, base_bytes: bytes,
    nib_cols: np.ndarray, row_col: np.ndarray | None, *,
    defer: bool = False, op: str = "modexp",
):
    """One launch of the pow chain at ``n_bits``-bit rows — the CRT
    signing workhorse (reference hot loop: crypto_pgp.go:346-371) —
    its operands built in whole-array steps from what the caller holds:

    - ``umods``: the launch's moduli, each once (a signer: p and q of
      each distinct key); ``row_mod`` (t,): each row's modulus among
      them;
    - ``base_bytes``: the rows' bases, reduced by their moduli, as
      joined ``2 * digits``-byte little-endian strings — the halves of
      a number's 16-bit little-endian digits, low half first, ARE its
      little-endian bytes (:func:`digits_to_halves_u8`);
    - ``nib_cols`` (c, windows) uint8 from :func:`exp_nibbles` and
      ``row_col`` (t,): each row's exponent among them — a signer's
      exponents are constants of its keys, computed once a key; None
      where the exponents come a row (c = t, in order).  Its second
      axis IS the launch's exponent class: ``4 * digits`` windows for
      exponents as wide as the rows (every sign), more for the longer
      class — another staging ring, another program
      (:func:`_jitted_pow`), the same kernel.

    Returns the rows' ``base^exp mod modulus`` as a list of ints, or
    None when a modulus has no key rows (caller falls back).

    ``defer=True`` returns a :class:`DeferredModexp` instead of a list:
    the launch is dispatched but NOT blocked on, so the caller (the
    async dispatcher) can stage further width groups while the device
    works.  The staging slot stays in flight until ``wait()``.

    ``op`` labels the launch's phase spans and histograms
    (``flush.stage`` / ``.launch`` / ``.fetch`` / ``.unpack``): the
    signer passes ``sign``.
    """
    ctx = pow_context(n_bits)
    digits = ctx.digits
    t = len(row_mod)
    windows = nib_cols.shape[1]
    # None for rows whose exponents are as wide as they: the programs,
    # rings and span attributes of every sign launch stay as they were.
    exp_bits = None if windows == 4 * digits else 4 * windows
    long_exp = {} if exp_bits is None else {"exp_bits": exp_bits}
    attrs = {"bits": n_bits, **long_exp}
    ring = slot = None
    released = False

    def _release():
        nonlocal released
        if not released:
            released = True
            if ring is not None:
                ring.release(slot)

    try:
        with trace.leaf("flush.stage", op, items=t, **attrs) as sp:
            placed = ctx.pow_keys.place(umods)
            if placed is None:
                return None
            at, ukey = placed
            # Pad the batch axis (floor 64) to power-of-two buckets so
            # only a handful of kernel shapes compile.
            padded = max(64, 1 << (t - 1).bit_length())
            sp.attrs["bucket"] = padded
            # Stage operands into a persistent slot (devbuf ring) or
            # throwaway arrays.  The pad region broadcasts row 0 in
            # place: its base, its exponent, its modulus.
            ring, slot = _pow_staging(digits, n_bits, padded, windows)
            bh, nt, ix = slot["base_halves"], slot["nib_t"], slot["idx"]
            bh[:t] = np.frombuffer(base_bytes, dtype=np.uint8).reshape(
                t, 2 * digits
            )
            nt[:, :t] = (
                nib_cols if row_col is None else nib_cols[row_col]
            ).T
            ix[:t] = at[row_mod]
            if padded > t:
                bh[t:] = bh[0:1]
                nt[:, t:] = nt[:, 0:1]
                ix[t:] = ix[0]
            pow_args = (bh, nt, ix, ukey)

        def unpack(sigma: np.ndarray) -> list[int]:
            with trace.leaf("flush.unpack", op, items=t, **attrs):
                vals = _sigma_to_ints(ctx, sigma)
                return [
                    v % umods[u] for v, u in zip(vals, row_mod.tolist())
                ]

        sigma = None
        # the rows' own class (every sign) rides the fused chain only
        # where it is forced: no measurement has judged it there yet
        if exp_bits is None and _use_pallas("BFTKV_RNS_POW_BACKEND"):
            try:
                from bftkv_tpu.ops import pallas_rns

                # the fused chain blocks on its result: launch and
                # fetch are one interval here
                with trace.leaf(
                    "flush.launch", op, items=t, bucket=padded, bits=n_bits
                ):
                    sigma = np.asarray(
                        pallas_rns.pow_pallas(
                            *pow_args, digits=digits, n_bits=n_bits
                        )
                    )[:t]
                _PALLAS_STATUS["pow"] = "ok"
            except Exception as e:
                _pallas_fell_back("pow", e)
        if sigma is not None:
            _release()
            res = unpack(sigma)
            return DeferredModexp(lambda: res) if defer else res

        def xla_chain():
            if _shardable(padded):
                return _jitted_pow_sharded(digits, n_bits, **long_exp)
            # Donation only pays (and only works) on real accelerators;
            # see _jitted_pow.
            return _jitted_pow(
                digits, n_bits,
                donate=jax.default_backend() in ("tpu", "gpu"),
                **long_exp,
            )

        with trace.leaf(
            "flush.launch", op, items=t, bucket=padded, **attrs
        ):
            # jax dispatch is async: ``dev`` is not a result yet
            dev = None
            if exp_bits is not None and _use_pallas(
                "BFTKV_RNS_POW_BACKEND", long_exp=True
            ):
                # the longer class on one chip: the fused chain as one
                # program, launched and fetched as the XLA chain is
                try:
                    from bftkv_tpu.ops import pallas_rns

                    dev = pallas_rns.jitted_pow(
                        digits, n_bits, windows, padded,
                        _pow_name(n_bits, exp_bits),
                        jax.default_backend() != "tpu",
                    )(*pow_args)
                    _PALLAS_STATUS["pow"] = "ok"
                except Exception as e:
                    _pallas_fell_back("pow", e)
            if dev is None:
                dev = xla_chain()(*pow_args)

        def finish() -> list[int]:
            try:
                with trace.leaf("flush.fetch", op, items=t, **attrs):
                    s = np.asarray(dev)[:t]
            finally:
                # Materialized (or launch failed): the device no longer
                # reads the staging arrays either way.
                _release()
            return unpack(s)

        if defer:
            # Slot ownership moves to the handle: finish() releases it.
            return DeferredModexp(finish)
        return finish()
    except BaseException:
        _release()
        raise


def digits_to_halves(digits_u32: np.ndarray) -> np.ndarray:
    """(T, 128) 16-bit digits → (T, 256) interleaved 8-bit halves (f32)."""
    t = digits_u32.shape[0]
    out = np.empty((t, 2 * digits_u32.shape[1]), dtype=np.float32)
    out[:, 0::2] = (digits_u32 & 0xFF).astype(np.float32)
    out[:, 1::2] = (digits_u32 >> 8).astype(np.float32)
    return out


def digits_to_halves_u8(digits_u32: np.ndarray) -> np.ndarray:
    """Same as :func:`digits_to_halves` but uint8 — 4x less wire for
    host→device transfer; the kernel casts to f32 on device.  The
    halves of a number's 16-bit little-endian digits, low half first,
    are the number's little-endian byte string: a caller that holds
    the bytes builds this array from them directly
    (``crypto/rsa.py:_stage_verify_operands``), and operands that are
    uint8 already pass through."""
    if digits_u32.dtype == np.uint8:
        return digits_u32
    t = digits_u32.shape[0]
    out = np.empty((t, 2 * digits_u32.shape[1]), dtype=np.uint8)
    out[:, 0::2] = (digits_u32 & 0xFF).astype(np.uint8)
    out[:, 1::2] = (digits_u32 >> 8).astype(np.uint8)
    return out


def verify_e65537_rns(sig_digits, em_digits, key_rows) -> jnp.ndarray:
    """Batched RSA e=65537 verify in RNS.

    ``sig_digits``/``em_digits``: (T, 128) uint32 16-bit digit arrays;
    ``key_rows``: stacked per-row key tensors from
    :meth:`RNSContext.key_rows` — (n_all (T,2k), n_r (T,1),
    neg_ninv_b (T,k), ninv_all (T,2k), m2_all (T,2k), m2_r (T,1)).
    """
    sig_h = digits_to_halves(np.asarray(sig_digits))
    em_h = digits_to_halves(np.asarray(em_digits))
    return _jitted_verify()(sig_h, em_h, key_rows)


#: Last outcome per fused-chain entry point in THIS process:
#: "unused" (never attempted), "ok" (a pallas call completed), or
#: "fallback: <Error>" (the loud XLA fallback fired).  A later "ok"
#: overwrites an earlier fallback, so the retreat is also counted
#: (``rns.pallas_fallback``): whoever reports a rate or checks a run
#: from outside the process reads the counter.
_PALLAS_STATUS = {"pow": "unused", "verify": "unused"}


def pallas_status() -> dict:
    return dict(_PALLAS_STATUS)


def _pallas_fell_back(which: str, e: Exception) -> None:
    """A Mosaic compile/runtime failure degrades to the XLA chain
    instead of sinking the crypto plane — but never quietly: status
    string, counter and a logged traceback."""
    _PALLAS_STATUS[which] = f"fallback: {type(e).__name__}"
    metrics.incr("rns.pallas_fallback")
    logging.getLogger("bftkv_tpu.ops.rns").exception(
        "pallas %s kernel failed; falling back to XLA", which
    )


def _auto_backend(
    platform: str, n_devices: int, long_exp: bool = False
) -> str:
    """What ``auto`` resolves to, from what the process can observe.

    Off TPU the fused chains would run in interpret mode, far slower
    than the XLA kernels; on a multi-chip host the sharded XLA path
    spreads the batch over every device (see :func:`_mesh`).  On one
    TPU chip the fused chains are the candidate.  The pow chain's
    LONGER exponent class (``long_exp``) has its measurement — 10.0 /
    17.3 ms a launch at 64 / 128 rows against the XLA chain's 37.7 /
    36.6 (PERF.md §6, PR 33) — and rides the fused chain there; no
    measurement has judged the verify chain and the signs' pow class
    yet (ROADMAP S4), so every other case resolves to ``xla`` — what a
    fresh machine has always run."""
    if long_exp and platform == "tpu" and n_devices == 1:
        return "pallas"
    return "xla"


def _use_pallas(env: str, long_exp: bool = False) -> bool:
    """Backend choice for the fused VMEM-resident Pallas chains
    (:mod:`bftkv_tpu.ops.pallas_rns`): ``pallas``/``xla`` force,
    ``auto`` (default) is :func:`_auto_backend`."""
    mode = flags.raw(env, "auto")
    if mode == "auto":
        mode = _auto_backend(
            jax.default_backend(), len(jax.devices()), long_exp
        )
    return mode == "pallas"


@functools.lru_cache(maxsize=1)
def _mesh():
    """1-D device mesh over every local device, or None when sharding
    is pointless (single device) or disabled (``BFTKV_SHARD=off``).

    This is the production counterpart of the driver's
    ``dryrun_multichip`` demo: verify/sign flushes are data-parallel
    over the batch axis, so the dispatcher's launches shard across the
    replica's whole accelerator pool via ``shard_map`` — collectives
    stay strictly inside one replica's trust domain (SURVEY §5)."""
    if flags.raw("BFTKV_SHARD", "auto") == "off":
        return None
    devs = jax.devices()
    if len(devs) < 2:
        return None
    return jax.sharding.Mesh(np.array(devs), ("batch",))


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs
    )


@functools.lru_cache(maxsize=1)
def _jitted_verify_gather_sharded():
    """The gather-verify kernel sharded over the batch axis of the
    local device mesh; key rows replicate (they are small and shared)."""
    from jax.sharding import PartitionSpec as P

    cn = _Consts(context())
    mesh = _mesh()

    def rns_verify_gather_sharded(sig_halves_u8, em_halves_u8, idx, ukey):
        key = tuple(u[idx] for u in ukey)
        return _verify_kernel(
            cn,
            sig_halves_u8.astype(jnp.float32),
            em_halves_u8.astype(jnp.float32),
            key,
        )

    b = P("batch")
    return jax.jit(
        _shard_map(
            rns_verify_gather_sharded, mesh,
            in_specs=(b, b, b, (P(),) * 6),
            out_specs=b,
        )
    )


@functools.lru_cache(maxsize=8)
def _jitted_pow_sharded(
    digits: int, n_bits: int, exp_bits: int | None = None
):
    from jax.sharding import PartitionSpec as P

    cn = _Consts(context(digits, n_bits))
    mesh = _mesh()

    def rns_pow_sharded(base_halves_u8, exp_nibbles_t_u8, idx, ukey):
        key = tuple(u[idx] for u in ukey)
        return _pow_kernel(
            cn,
            base_halves_u8.astype(jnp.float32),
            exp_nibbles_t_u8.astype(jnp.float32),
            key,
        )

    rns_pow_sharded.__name__ = _pow_name(n_bits, exp_bits) + "_sharded"
    b = P("batch")
    return jax.jit(
        _shard_map(
            rns_pow_sharded, mesh,
            # exponent nibbles ride (W, T): batch is axis 1 there.
            in_specs=(b, P(None, "batch"), b, (P(),) * 6),
            out_specs=b,
        )
    )


def _shardable(batch: int) -> bool:
    mesh = _mesh()
    return mesh is not None and batch % mesh.devices.size == 0


def verify_e65537_rns_indexed(
    sig_digits, em_digits, key_idx, unique_rows
) -> jnp.ndarray:
    """Transfer-lean verify: ``unique_rows`` are stacked rows for the
    *distinct* keys only (from :func:`stack_key_rows`), ``key_idx`` maps
    each item to its key row; the gather happens on device."""
    sig_h = digits_to_halves_u8(np.asarray(sig_digits))
    em_h = digits_to_halves_u8(np.asarray(em_digits))
    idx = np.asarray(key_idx, dtype=np.int32)
    if _use_pallas("BFTKV_RNS_VERIFY_BACKEND"):
        try:
            from bftkv_tpu.ops import pallas_rns

            # Materialize before returning: jit dispatch is async, so a
            # Mosaic failure would otherwise surface at the *caller's*
            # block_until_ready, past this fallback.  Callers convert
            # the verdict to numpy immediately anyway.
            out = jax.block_until_ready(
                pallas_rns.verify_pallas(sig_h, em_h, idx, unique_rows)
            )
            _PALLAS_STATUS["verify"] = "ok"
            return out
        except Exception as e:
            _pallas_fell_back("verify", e)
    if _shardable(sig_h.shape[0]):
        return _jitted_verify_gather_sharded()(sig_h, em_h, idx, unique_rows)
    return _jitted_verify_gather()(sig_h, em_h, idx, unique_rows)


def stack_key_rows(rows: list, pad_to: int = 0):
    """Stack per-key row tuples (from :meth:`RNSContext.key_rows`) into
    the batch tensors ``verify_e65537_rns`` takes. The (T, 1) reshape
    of the scalar redundant-channel entries lives here and only here.
    ``pad_to`` repeats row 0 up to that many rows (the unique-key axis
    of an indexed launch is padded to a few fixed sizes)."""
    t = max(len(rows), pad_to)

    def stack(i):
        real = np.stack([np.asarray(r[i]) for r in rows])
        if t == len(rows):
            return real
        out = np.empty((t,) + real.shape[1:], dtype=real.dtype)
        out[: len(rows)] = real
        out[len(rows) :] = real[0]
        return out

    return (
        stack(0),
        stack(1).reshape(t, 1),
        stack(2),
        stack(3),
        stack(4),
        stack(5).reshape(t, 1),
    )


def assemble_key_rows(ns: list[int]):
    """Stack cached per-key rows for a batch of moduli, or None if any
    modulus is RNS-incapable (caller falls back for those)."""
    ctx = context()
    rows = []
    for n in ns:
        r = ctx.key_rows(n)
        if r is None:
            return None
        rows.append(r)
    return stack_key_rows(rows)
