"""Operations and bytes of the WIDE pow chain's rows, from shapes alone.

The wide chain (``ops/rns.py``, ``WIDE_BITS``) takes whole-modulus rows
wider than the primes below 2^12 can hold two bases for — up to 4,096
bits — on channels that are the primes of [2^10, 2^13), dealt as the
12-bit ones are: largest first, alternately to two bases until both
carry ``n_bits + 64`` bits, cut to equal counts.  Its Montgomery
product keeps the dot structure of ``rns_counts.py`` (two base
extensions as split bf16 dots), so a product costs
``rns_counts.mont_flops(k)`` at this ``k``; only ``k`` differs (340 at
4,096 bits, where the 12-bit rule has no answer).  Nothing here imports
the program.
"""

from __future__ import annotations

import math

from benchmarks.reduce import rns_counts


def channels(n_bits: int) -> int:
    """``k`` of the wide chain for rows of ``n_bits`` bits."""
    lo, hi = 1 << 10, 1 << 13
    sieve = bytearray([1]) * (hi - lo)
    for p in range(2, int(hi**0.5) + 1):
        for m in range(max(p * p, -(-lo // p) * p), hi, p):
            sieve[m - lo] = 0
    primes = [lo + i for i in range(hi - lo) if sieve[i]][::-1]
    need = n_bits + 64
    nb = nq = 0
    bits_b = bits_q = 0.0
    for p in primes:
        if bits_b <= bits_q:
            nb, bits_b = nb + 1, bits_b + math.log2(p)
        else:
            nq, bits_q = nq + 1, bits_q + math.log2(p)
        if bits_b > need and bits_q > need:
            return min(nb, nq)
    raise ValueError(f"not enough primes below 2^13 for {n_bits} bits")


def row_flops(mod_bits: int, exp_windows: int) -> float:
    """MXU FLOPs of one row: five products a 4-bit window and the 19 of
    table and framing (``rns_counts.SIGN_PRODUCTS``'s rule)."""
    products = 5 * exp_windows + (rns_counts.SIGN_PRODUCTS - 5 * 256)
    return products * rns_counts.mont_flops(channels(mod_bits))


def row_bytes(mod_bits: int, exp_windows: int) -> float:
    """HBM bytes one row must move: base in (uint8 half digits), exponent
    windows in, key index in, residues out."""
    return mod_bits // 8 + exp_windows + 4 + mod_bits // 8
