"""Operations and bytes the RNS chains need per item, from shapes alone.

A verify item is one RSA-2048 signature check with e = 65537; a sign
row is one 1024-bit CRT half (a CRT signature is two rows).  The
arithmetic is copied from ``bench.py:_rns_verify_flops`` and
``_rns_sign_flops`` (which stay where they are; see PERF.md, Open
questions); ``k``, the number of residue channels per base, follows the
rule of ``ops/rns.py:RNSContext``: the primes below 2^12, largest
first, dealt alternately to two bases until both carry ``n_bits + 64``
bits.  Nothing here imports the program.
"""

from __future__ import annotations

import json
import math
import os

DIGITS = 128          # 16-bit digits of a 2048-bit number
MONT_DOTS = 12        # bf16 dots per Montgomery product (two base extensions, 6-bit split)
VERIFY_PRODUCTS = 19  # to-Mont + 17 for e=65537 + from-Mont
SIGN_PRODUCTS = 1299  # 256 steps x 5 + the 16-entry table + framing, per CRT half


def channels(n_bits: int) -> int:
    """``k`` for numbers of ``n_bits`` bits."""
    lo, hi = 1 << 10, 1 << 12
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
    raise ValueError(f"not enough primes below 2^12 for {n_bits} bits")


def mont_flops(k: int) -> float:
    """One Montgomery product of one row: 12 dots of (1,k)x(k,k+1)."""
    return MONT_DOTS * 2 * k * (k + 1)


def verify_flops() -> float:
    """MXU FLOPs of one RSA-2048 verify item."""
    k = channels(2048)
    conv = 2 * 6 * 2 * (2 * DIGITS) * (2 * k + 1) / 2  # digits -> residues, two operands
    return VERIFY_PRODUCTS * mont_flops(k) + conv


def sign_row_flops() -> float:
    """MXU FLOPs of one 1024-bit CRT-half row."""
    return SIGN_PRODUCTS * mont_flops(channels(1024))


def verify_bytes() -> float:
    """HBM bytes one verify item must move: signature and encoded
    message in (two uint8 half-digit rows of 2 x 128), its key-row index
    in, one verdict out.  Key rows are shared by a launch and not
    counted (a lower bound on bytes makes the least time a lower bound)."""
    return 2 * 2 * DIGITS + 4 + 1


def sign_row_bytes() -> float:
    """HBM bytes one CRT-half row must move: base (128 uint8 half
    digits), exponent windows (256 uint8), key index in; 128 bytes out."""
    return DIGITS + 256 + 4 + DIGITS


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peak numbers for device kind {device_kind!r} in reduce/peaks.json"
        )
    return table[device_kind]


def least_seconds(verify_items: float, sign_rows: float, device_kind: str) -> dict:
    """The least time the chip could take for that work, and which of
    the two bounds sets it."""
    peaks = load_peaks(device_kind)
    flops = verify_items * verify_flops() + sign_rows * sign_row_flops()
    nbytes = verify_items * verify_bytes() + sign_rows * sign_row_bytes()
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["bytes_per_s"]
    return {
        "seconds": max(t_flops, t_bytes),
        "bound": "flops" if t_flops >= t_bytes else "bytes",
        "flops": flops,
        "bytes": nbytes,
    }
