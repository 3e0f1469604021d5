"""YCSB's key chooser and record builder, seeded by ``--seed``.

The program sees only the operations.  The arithmetic follows YCSB's
``ZipfianGenerator`` (Gray et al.'s method, constant 0.99) and
``ScrambledZipfianGenerator`` (the rank is hashed with FNV-1a so that
the popular keys are spread over the key space), and the record shape
of its core workloads: 10 fields of 100 bytes.  Copied in from the
arithmetic of ``bftkv_tpu/workload/spec.py`` (seeded pure draws), which
this file does not import.
"""

from __future__ import annotations

import hashlib
import random

FNV_OFFSET = 0xCBF29CE484222325
FNV_PRIME = 0x100000001B3
MASK64 = (1 << 64) - 1


def fnv64(n: int) -> int:
    """FNV-1a over the 8 little-endian bytes of ``n`` (YCSB Utils.fnvhash64)."""
    h = FNV_OFFSET
    for _ in range(8):
        h = ((h ^ (n & 0xFF)) * FNV_PRIME) & MASK64
        n >>= 8
    return h


class Zipfian:
    """Ranks 0..items-1, rank 0 the most popular."""

    def __init__(self, items: int, theta: float = 0.99):
        if items < 2:
            raise ValueError("zipfian needs at least 2 items")
        self.items, self.theta = items, theta
        self.zetan = sum(1.0 / i**theta for i in range(1, items + 1))
        zeta2 = 1.0 + 0.5**theta
        self.alpha = 1.0 / (1.0 - theta)
        self.eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / self.zetan)

    def rank(self, u: float) -> int:
        uz = u * self.zetan
        if uz < 1.0:
            return 0
        if uz < 1.0 + 0.5**self.theta:
            return 1
        r = int(self.items * (self.eta * u - self.eta + 1.0) ** self.alpha)
        return min(r, self.items - 1)


class KeyChooser:
    """Draws a record number of an existing key space of ``records``."""

    def __init__(self, distribution: str, records: int, theta: float = 0.99):
        self.records = records
        self.distribution = distribution
        if distribution == "zipfian":
            self.zipf = Zipfian(records, theta)
        elif distribution != "uniform":
            raise ValueError(f"unknown key distribution {distribution!r}")

    def draw(self, rng: random.Random) -> int:
        if self.distribution == "uniform":
            return rng.randrange(self.records)
        return fnv64(self.zipf.rank(rng.random())) % self.records


def key_name(seed: int, keynum: int) -> bytes:
    """YCSB's ``user<hash>`` key, made distinct per seed so that no run
    meets another run's records."""
    return b"user%d-%d" % (seed, fnv64(keynum))


def record(seed: int, keynum: int, version: int, fields: int = 10,
           field_bytes: int = 100) -> bytes:
    """The ``fields`` x ``field_bytes`` value of one version of one key:
    a pure function of its arguments (so the judge can rebuild it)."""
    h = hashlib.sha256(b"%d|%d|%d" % (seed, keynum, version)).digest()
    return random.Random(h).randbytes(fields * field_bytes)
