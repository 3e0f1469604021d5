"""The plain reference of the ``ca_issue`` kind: what a certificate of
the threshold CA has to be, with nothing of the program imported.

Deal-free: the threshold signature of a TBS equals the PKCS#1 v1.5 /
SHA-256 signature under the UNDEALT key byte for byte (the fragments
sum to d over the integers and the padding is deterministic), so the
judge needs the key it made from the seed and three functions of
``benchmarks/reference.py`` — ``rsa_keygen``, ``rsa_sign``,
``rsa_verify`` — which are general and stand here under their names.

For the tests alone, to pin the wire semantics: a plain (k, n) additive
tree dealer and combiner after ``docs/tex/method.tex`` (upstream
``crypto/threshold/rsa/rsa.go:75-117``).  The key is split additively
among the n servers; each part is split again among the servers NOT on
its path, down to depth n - k, so that the fragments any k servers hold
sum to d.  A node's index is ``parent * n + server + 1``; server *i*
holds, at every node where it is a child, that child's value, filed
under the PARENT's index; its partial signature of fragment ``idx`` is
filed by the client under ``idx * n + i + 1``.
"""

from __future__ import annotations

from benchmarks.reference import (  # noqa: F401  (the kind's judge)
    RsaKey,
    rsa_keygen,
    rsa_sign,
    rsa_verify,
)


def on_path(server: int, idx: int, n: int) -> bool:
    while idx:
        if server == (idx - 1) % n:
            return True
        idx = (idx - 1) // n
    return False


def depth(idx: int, n: int) -> int:
    d = 0
    while idx:
        idx, d = (idx - 1) // n, d + 1
    return d


def deal(d: int, k: int, n: int, rng) -> list[dict[int, int]]:
    """``shares[i] = {node index: fragment}`` of a (k, n) deal of ``d``;
    ``rng`` a ``random.Random``.  Fragments are signed integers of about
    twice the width of what they split."""
    shares: list[dict[int, int]] = [{} for _ in range(n)]

    def split(value: int, idx: int) -> None:
        servers = [i for i in range(n) if not on_path(i, idx, n)]
        parts = []
        for _ in servers[:-1]:
            x = rng.getrandbits(2 * value.bit_length())
            parts.append(-(x >> 1) if x & 1 else x >> 1)
        parts.append(value - sum(parts))
        for i, part in zip(servers, parts):
            shares[i][idx] = part
            if depth(idx, n) < n - k:
                split(part, idx * n + i + 1)

    split(d, 0)
    return shares


def combine(em: int, modulus: int, shares: list, up: set[int], n: int,
            k: int) -> int:
    """``em ^ d mod modulus`` from the servers in ``up`` alone: at every
    node, a child whose server answers gives its fragment's power, one
    whose server is silent is rebuilt from ITS children.  Raises where
    fewer than k servers are up."""

    def power(idx: int) -> int:
        acc = 1
        for i in range(n):
            if on_path(i, idx, n):
                continue
            if i in up:
                f = shares[i][idx]
                p = pow(em, abs(f), modulus)
                acc = acc * (pow(p, -1, modulus) if f < 0 else p) % modulus
            elif depth(idx, n) < n - k:
                acc = acc * power(idx * n + i + 1) % modulus
            else:
                raise ValueError("fewer than k servers answer")
        return acc

    return power(0)
