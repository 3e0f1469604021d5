"""Shared batched-modexp engine: route big-int exponentiations to the TPU.

Every distributed-crypto subsystem in the reference bottoms out in
``big.Int.Exp`` loops — TPA's DH rounds (crypto/auth/auth.go), threshold
RSA's per-fragment signing (crypto/threshold/rsa/rsa.go:140-178), and
threshold DSA's partial-R combination (crypto/threshold/dsa/dsa.go:33-52).
This engine replaces those per-item loops with one batch per request.

Where the process has a modexp domain installed
(``ops.dispatch.install_modexp``: a replica daemon started with
``--sidecar``), every request goes there first, whatever its size — a
healthy threshold-RSA request holds ONE fragment, and the batch is made
in the sidecar, across the daemon's handlers and across daemons.

Otherwise the launch is this process's own: the RNS pow chain
(``ops.rns.power_mod_rns``) for moduli up to 2,048 bits under
exponents up to twice the modulus class + 64 bits (``ops.rns.chains``:
a first-level threshold fragment of a 2,048-bit key, ~4,100 bits),
:func:`power_batch` (the limb Montgomery engine) for wider operands,
up to ``MAX_EXP_LIMBS``.  The wide chain's classes (moduli to 4,096
bits, ``ops.rns.WIDE_MAX_BITS``) are built in the sidecar that a
deployment declares them to (``BFTKV_CA_BITS``), not here.

Policy of the local path: batches below ``min_batch`` (default 4,
override with ``BFTKV_TPU_MIN_MODEXP_BATCH``) run as host ``pow`` — a
single modexp doesn't amortize a kernel launch. Per-modulus Montgomery
precomputation is LRU-bounded since moduli can be influenced by remote
peers.
"""

from __future__ import annotations

import logging
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from bftkv_tpu import flags
from bftkv_tpu.ops import bigint

__all__ = ["BatchModExp", "power_batch", "remote_route"]


def remote_route(bits: int, exp_bits: int) -> bool:
    """Whether ``x^e mod m`` (``m`` of ``bits`` bits, ``e`` of
    ``exp_bits``), asked for in a replica daemon started with
    ``--sidecar``, leaves for the sidecar and finds a device chain
    there.  The way out is :meth:`BatchModExp.modexp`'s first question
    (``ops.dispatch.install_modexp``), so what is left to answer is the
    class: ``ops.rns.chains``, THE capability rule.  A deployment that
    states one chip-owning sidecar for its quorum's modexps asks this
    before it starts a process (``benchmarks/kinds/ca_issue.py``); a
    program without the function runs them in the replica, on the
    host."""
    from bftkv_tpu.ops import rns

    return rns.chains(16 * -(-bits // 16), exp_bits).pow


@jax.jit
def power_batch(
    base: jnp.ndarray,
    e: jnp.ndarray,
    n: jnp.ndarray,
    n_prime: jnp.ndarray,
    r2: jnp.ndarray,
    one_mont: jnp.ndarray,
) -> jnp.ndarray:
    """base^e mod n with per-element full-width exponents, all operands
    ``(batch, L)`` digit arrays.

    The device path of operands wider than the RNS width classes:
    threshold-RSA fragment exponents grow past the key size per tree
    level (reference: crypto/threshold/rsa/rsa.go:97-117).
    """
    b_mont = bigint.to_mont(base, r2, n, n_prime)
    v_mont = bigint.mont_exp(
        b_mont, e, n, n_prime, jnp.broadcast_to(one_mont, b_mont.shape)
    )
    return bigint.from_mont(v_mont, n, n_prime)


class BatchModExp:
    _shared = None
    _DOM_CACHE_MAX = 64

    def __init__(self, min_batch: int | None = None):
        if min_batch is None:
            min_batch = int(flags.raw("BFTKV_TPU_MIN_MODEXP_BATCH", "4"))
        self.min_batch = min_batch
        self._domains: "OrderedDict[tuple[int, int], object]" = OrderedDict()

    @classmethod
    def shared(cls) -> "BatchModExp":
        if cls._shared is None:
            cls._shared = cls()
        return cls._shared

    def _domain(self, n: int, nlimbs: int):
        key = (n, nlimbs)
        dom = self._domains.get(key)
        if dom is None:
            dom = bigint.MontgomeryDomain(n, nlimbs)
            self._domains[key] = dom
            if len(self._domains) > self._DOM_CACHE_MAX:
                self._domains.popitem(last=False)
        else:
            self._domains.move_to_end(key)
        return dom

    # Exponents outgrow the modulus (threshold-RSA fragments double in
    # width per tree level — rsa.go:97-117: ~4,100 bits at the first
    # level of a 2,048-bit key, 8,192 / 16,384 / 32,768 below).  The
    # first level rides the RNS chain's longer exponent class; past
    # this limb width of the LIMB engine the window loop dominates and
    # host pow wins: cap its device path.
    MAX_EXP_LIMBS = 256  # 4096 bits

    def modexp(self, pairs: list[tuple[int, int]], n: int) -> list[int]:
        """[(base, exp)] → [base^exp mod n] — one kernel launch when the
        batch is big enough and ``n`` is odd (Montgomery-compatible)."""
        if not pairs:
            return []
        from bftkv_tpu.ops import dispatch

        installed = dispatch.get_modexp()
        if installed is not None and n > 1:
            # before min_batch: one remote-bound item is no host pow
            return installed.submit([(b % n, e, n) for b, e in pairs])
        if len(pairs) < self.min_batch or n % 2 == 0 or n <= 1:
            return [pow(b % n, e, n) for b, e in pairs]
        from bftkv_tpu.ops import limb

        nlimbs = limb.nlimbs_for_bits(n.bit_length())
        max_e = max(e for _, e in pairs)

        # Prefer the RNS windowed-modexp kernel: it covers moduli up
        # to 2,048 bits under either exponent class of their row width
        # (``rns.exp_class``: up to the width, or up to twice it + 64).
        # Wider moduli (the wide chain's classes are the sidecar's),
        # and the exponents of the second tree level and below
        # (rsa.go:97-117), stay on the limb path.
        # power_mod_rns stages operands through the persistent devbuf
        # ring for its class, so per-call marshalling here is just the
        # list splits below.
        from bftkv_tpu.ops import rns

        nb = next((w for w in (1024, 2048) if n.bit_length() <= w), None)
        eb = nb and rns.exp_class(nb, max_e.bit_length())
        if eb:
            from bftkv_tpu.metrics import registry as metrics

            try:
                vals = rns.power_mod_rns(
                    [b for b, _ in pairs],
                    [e for _, e in pairs],
                    [n] * len(pairs),
                    n_bits=nb,
                    exp_bits=eb,
                )
            except Exception:
                # power_mod_rns signals every *legitimately* incapable
                # input by returning None; an exception is an
                # unexpected defect — degrade, but loudly.
                metrics.incr("modexp.rns_error")
                logging.getLogger(__name__).exception(
                    "RNS modexp failed; falling back to limb kernel"
                )
                vals = None
            if vals is not None:
                metrics.incr("modexp.rns_staged", len(pairs))
                return vals
            # else: RNS-incapable modulus (None) or logged error —
            # fall through to the limb path either way.

        e_limbs = max(limb.nlimbs_for_bits(max_e.bit_length()), 1)
        if e_limbs > self.MAX_EXP_LIMBS:
            return [pow(b % n, e, n) for b, e in pairs]
        # Bucket the exponent width (64/128/256 limbs) so varying widths
        # reuse a handful of compiled programs instead of one each.
        for bucket in (64, 128, 256):
            if e_limbs <= bucket:
                e_limbs = bucket
                break
        dom = self._domain(n, nlimbs)
        base = limb.ints_to_limbs([b % n for b, _ in pairs], nlimbs)
        exp = limb.ints_to_limbs([e for _, e in pairs], e_limbs)
        out = power_batch(
            base,
            exp,
            np.broadcast_to(dom.n, base.shape),
            np.broadcast_to(dom.n_prime, base.shape),
            np.broadcast_to(dom.r2, base.shape),
            np.broadcast_to(dom.one_mont, base.shape),
        )
        return limb.limbs_to_ints(np.asarray(out))
