"""The generators are reproducible from a seed, and zipfian is zipfian."""

import collections
import random

from benchmarks import ycsb


def test_same_seed_same_keys_and_records():
    a = ycsb.KeyChooser("zipfian", 8192)
    b = ycsb.KeyChooser("zipfian", 8192)
    ra, rb = random.Random("s|1"), random.Random("s|1")
    assert [a.draw(ra) for _ in range(500)] == [b.draw(rb) for _ in range(500)]
    assert ycsb.record(3_000_000_007, 5, 2) == ycsb.record(3_000_000_007, 5, 2)
    assert ycsb.record(1, 5, 2) != ycsb.record(1, 5, 3) != ycsb.record(2, 5, 3)
    assert len(ycsb.record(1, 0, 0)) == 1000
    assert ycsb.key_name(7, 1) != ycsb.key_name(8, 1)


def test_zipfian_099_is_skewed_and_scrambled():
    z = ycsb.Zipfian(8192, 0.99)
    rng = random.Random(5)
    ranks = collections.Counter(z.rank(rng.random()) for _ in range(50_000))
    assert ranks.most_common(1)[0][0] == 0
    assert 0.09 < ranks[0] / 50_000 < 0.12      # 1/zeta(8192, .99) = 0.104
    assert ranks[0] > ranks[1] > ranks[7]
    chooser = ycsb.KeyChooser("zipfian", 8192)
    hot = collections.Counter(chooser.draw(rng) for _ in range(50_000))
    assert hot.most_common(1)[0][0] == ycsb.fnv64(0) % 8192 != 0


def test_fnv64_matches_ycsb():
    # YCSB Utils.fnvhash64(0): offset basis xored and multiplied 8 times
    h = ycsb.FNV_OFFSET
    for _ in range(8):
        h = (h * ycsb.FNV_PRIME) & ycsb.MASK64
    assert ycsb.fnv64(0) == h
