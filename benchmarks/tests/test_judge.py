"""The plain reference and the judge, on records this file builds itself
(its own encoder, its own RSA key), with the faults planted one by one."""

import hashlib
import os
import struct
import zlib

import pytest

from benchmarks import judge, reference, ycsb
from benchmarks.generator import Call

P = 0xA1D49711B323FFBAD94CF2222935769730EFD1A0EA3F06DE9760C0C9683461CC017D87C3BB9753DF
Q = 0xFA68131C757646FB78D407ADA6527166C13B29ACFC08811127962EE8C812D85198D31DD03F5E0131
P2 = 0xDB951BECEC60C92040E02AA7BE1A172EE81EE4D78DB3C797D40CC986BB3A073F3CE6FF6FF4014BE7
Q2 = 0xE86F17A980DFE56B72F44409E5A4497AD8C800DC00EEE68350B16D141C1F96BB589990F607FA08DF
E = 65537
PREFIX = bytes.fromhex("3031300d060960864801650304020105000420")


class Key:
    def __init__(self, p, q, name):
        self.n, self.name = p * q, name
        self.d = pow(E, -1, (p - 1) * (q - 1))
        nb = self.n.to_bytes((self.n.bit_length() + 7) // 8, "big")
        self.id = int.from_bytes(
            hashlib.sha256(nb + struct.pack(">I", E)).digest()[:8], "big")

    def sign(self, msg: bytes) -> bytes:
        k = (self.n.bit_length() + 7) // 8
        t = PREFIX + hashlib.sha256(msg).digest()
        em = b"\x00\x01" + b"\xff" * (k - len(t) - 3) + b"\x00" + t
        return pow(int.from_bytes(em, "big"), self.d, self.n).to_bytes(k, "big")

    def cert(self) -> bytes:
        nb = self.n.to_bytes((self.n.bit_length() + 7) // 8, "big")
        return (b"BCR1" + chunk(nb) + struct.pack(">I", E) + chunk(self.name.encode())
                + chunk(b"http://x") + chunk(b"uid") + struct.pack(">H", 0))


def chunk(b: bytes) -> bytes:
    return struct.pack(">Q", len(b)) + b


def sig_packet(entries, cert=b"") -> bytes:
    data = b"".join(struct.pack(">Q", sid) + chunk(s) for sid, s in entries)
    return struct.pack(">BI?", 1, 1, True) + chunk(data) + chunk(cert)


WRITER, SERVER = Key(P, Q, "u01"), Key(P2, Q2, "a01")


def packet(key: bytes, value: bytes, t: int, *, shares: int = 1,
           break_writer=False, break_share=False) -> bytes:
    tbs = chunk(key) + chunk(value) + struct.pack(">Q", t)
    ws = WRITER.sign(tbs)
    if break_writer:
        ws = ws[:-1] + bytes([ws[-1] ^ 1])
    tbss = tbs + sig_packet([(WRITER.id, ws)], WRITER.cert())
    if not shares:
        return tbss
    ss = SERVER.sign(tbss)
    if break_share:
        ss = ss[:-1] + bytes([ss[-1] ^ 1])
    return tbss + sig_packet([(SERVER.id, ss)])


def segment(records) -> bytes:
    out = b""
    for key, t, value in records:
        tail = struct.pack(">IQI", len(key), t, len(value)) + key + value
        out += struct.pack(">I", zlib.crc32(tail)) + tail
    return out


@pytest.fixture
def deployment(tmp_path):
    keys, dbs = tmp_path / "keys", tmp_path / "dbs"
    for k in (WRITER, SERVER):
        (keys / k.name).mkdir(parents=True)
        (keys / k.name / "pubring").write_bytes(WRITER.cert() + SERVER.cert())
    return keys, dbs


SEED, RECORD = 77, {"fields": 10, "field_bytes": 100}
GUARANTEES = {"min_replicas": 3, "suff": 1}


def history(n=4):
    calls = [Call("insert", 0, list(range(n)), [1] * n, 1.0, 2.0, [None] * n)]
    return judge.History(SEED, RECORD, calls)


def write_replicas(dbs, n_replicas, make):
    for r in range(n_replicas):
        d = dbs / f"rw{r:02d}"
        d.mkdir(parents=True)
        recs = [(ycsb.key_name(SEED, k), 1, make(r, k)) for k in range(4)]
        (d / "seg-000000000001.log").write_bytes(segment(recs))


def good(_r, k, **kw):
    return packet(ycsb.key_name(SEED, k), ycsb.record(SEED, k, 1), 1, **kw)


def test_reference_round_trip():
    pkt = good(0, 2)
    rec = reference.parse_record(pkt)
    assert rec.key == ycsb.key_name(SEED, 2) and rec.t == 1
    ring = {c.id: c for c in reference.parse_certs(WRITER.cert() + SERVER.cert())}
    assert reference.valid_signers(rec.tbs, rec.writer, ring) == {WRITER.id}
    assert reference.valid_signers(rec.tbss, rec.quorum, ring) == {SERVER.id}
    assert not reference.rsa_verify(rec.tbs + b"x", rec.writer.entries[0][1],
                                    WRITER.n, E)


def test_torn_tail_is_dropped(tmp_path):
    seg = segment([(b"k1", 1, b"v1"), (b"k2", 1, b"v2")])
    (tmp_path / "seg-000000000001.log").write_bytes(seg[:-1])
    assert list(reference.read_store(str(tmp_path))) == [b"k1"]


def test_sound_disks_pass(deployment):
    keys, dbs = deployment
    write_replicas(dbs, 4, good)
    d = judge.inspect_disks(history(), [0, 1, 2, 3], str(keys), str(dbs),
                            GUARANTEES, True)
    assert d["under_replicated"] == 0 and d["min_holders"] == 4
    assert d["bad_writer_signatures"] == d["bad_quorum_signatures"] == 0
    assert d["records_verified"] == 4


@pytest.mark.parametrize("fault,number", [
    ("missing_on_two", "under_replicated"),
    ("wrong_value", "under_replicated"),
    ("no_collective", "under_replicated"),
    ("bad_writer", "bad_writer_signatures"),
    ("bad_share", "bad_quorum_signatures"),
])
def test_planted_disk_faults_fail(deployment, fault, number):
    keys, dbs = deployment

    def make(r, k):
        if k != 3:
            return good(r, k)
        if fault == "wrong_value":
            return packet(ycsb.key_name(SEED, k), b"other", 1)
        if fault == "no_collective":
            return good(r, k, shares=0)
        if fault == "bad_writer":
            return good(r, k, break_writer=True)
        if fault == "bad_share":
            return good(r, k, break_share=r == 0)
        return good(r, k)

    write_replicas(dbs, 4, make)
    if fault == "missing_on_two":
        for r in (0, 1):
            seg = dbs / f"rw{r:02d}" / "seg-000000000001.log"
            seg.write_bytes(segment(
                [(ycsb.key_name(SEED, k), 1, good(r, k)) for k in range(3)]))
    nums = judge.inspect_disks(history(), [0, 1, 2, 3], str(keys), str(dbs),
                               GUARANTEES, True)
    assert nums[number] >= 1
    nums.update(committed_ops=4, readback_checked=4)
    assert judge.verdict(nums)[0] is False


def test_every_acknowledged_key_is_counted_not_only_the_sample(deployment):
    """One key of four, outside the sample, missing on two replicas: the
    sample of one sees nothing, the count over every key does."""
    keys, dbs = deployment
    write_replicas(dbs, 4, good)
    for r in (0, 1):
        seg = dbs / f"rw{r:02d}" / "seg-000000000001.log"
        seg.write_bytes(segment(
            [(ycsb.key_name(SEED, k), 1, good(r, k)) for k in (0, 1, 3)]))
    d = judge.inspect_disks(history(), [0], str(keys), str(dbs), GUARANTEES, True)
    assert d["keys_counted"] == 4 and d["records_verified"] == 1
    assert d["under_replicated"] == 1 and d["under_replicated_sample"] == [(2, 2)]
    # a holder outside the sample still needs the value and suff signers
    write_replicas_again = lambda make: [  # noqa: E731
        (dbs / f"rw{r:02d}" / "seg-000000000001.log").write_bytes(segment(
            [(ycsb.key_name(SEED, k), 1, make(r, k)) for k in range(4)]))
        for r in range(4)]
    write_replicas_again(lambda r, k: good(r, k, shares=0 if k == 2 else 1))
    d = judge.inspect_disks(history(), [0], str(keys), str(dbs), GUARANTEES, True)
    assert d["under_replicated"] == 1
    write_replicas_again(lambda r, k: packet(ycsb.key_name(SEED, k), b"other", 1)
                         if k == 2 and r < 2 else good(r, k))
    d = judge.inspect_disks(history(), [0], str(keys), str(dbs), GUARANTEES, True)
    assert d["under_replicated"] == 1


def test_back_filled_path_counts_the_writer_signature(deployment):
    keys, dbs = deployment
    write_replicas(dbs, 4, lambda r, k: good(r, k, shares=0))
    args = (history(), [0, 1, 2, 3], str(keys), str(dbs), GUARANTEES)
    assert judge.inspect_disks(*args, True)["under_replicated"] == 4
    assert judge.inspect_disks(*args, False)["under_replicated"] == 0


class FakeApi:
    def __init__(self, h, bend=None, lose=None):
        self.h, self.bend, self.lose = h, bend, lose

    def read_many(self, names):
        out = []
        for k, _name in enumerate(names):
            v = self.h.value(k, 1)
            if k == self.lose:
                v = None
            elif k == self.bend:
                v = v[:-1] + bytes([v[-1] ^ 1])
            out.append(v)
        return out


def test_readback_counts_lost_and_wrong():
    h = history()
    assert judge.readback(h, [FakeApi(h)], [0, 1, 2, 3])["readback_checked"] == 4
    assert judge.readback(h, [FakeApi(h, lose=2)], [0, 1, 2, 3])["lost_acked_writes"] == 1
    assert judge.readback(h, [FakeApi(h, bend=1)], [0, 1, 2, 3])["bad_reads"] == 1


def test_stale_read_is_a_bad_read():
    calls = [
        Call("update", 0, [5], [10], 1.0, 2.0, [None]),
        Call("update", 1, [5], [20], 3.0, 4.0, [None]),   # after the first was done
        Call("update", 2, [5], [30], 5.5, 7.0, [None]),   # in flight during the reads
    ]
    val = lambda v: ycsb.record(SEED, 5, v, 10, 100)  # noqa: E731
    for version, bad in ((10, 1), (20, 0), (30, 0)):
        rd = Call("read", 3, [5], [], 5.0, 6.0, [None], [val(version)])
        h = judge.History(SEED, RECORD, calls + [rd])
        assert judge.check_reads(h)["bad_reads"] == bad
    rd = Call("read", 3, [5], [], 5.0, 6.0, [None], [None])
    assert judge.check_reads(judge.History(SEED, RECORD, calls + [rd]))["bad_reads"] == 1


def test_verdict_names_every_number_beside_its_limit():
    ok, compared = judge.verdict({"bad_reads": 0, "committed_ops": 5,
                                  "readback_checked": 3, "records_verified": 2})
    assert ok and compared["bad_reads"] == [0, "<=", 0]
    assert "committed_ops=5>=1" in judge.compared_line(compared)
    assert judge.verdict({"bad_reads": 1})[0] is False
    assert judge.verdict({"committed_ops": 0})[0] is False


# -- the tenant's forged items ----------------------------------------------


def tenant_requests(answer):
    """Two requests of the tenant's own making, answered by ``answer``."""
    import random

    from benchmarks import tenant

    key = reference.rsa_keygen(random.Random("k"), 1024)
    pairs = [(b"m%d" % i, reference.rsa_sign(b"m%d" % i, key)) for i in range(4)]
    items = [pairs[0], (pairs[1][0], tenant._flip(pairs[1][1], 9)),
             (tenant._flip(pairs[2][0], 3), pairs[2][1]), (pairs[3][0], pairs[0][1])]
    kinds = ["", "signature_bit", "message_bit", "swapped"]
    truth = bytes([1, 0, 0, 0])
    reqs = [tenant.Request(items, 0.0, 1.0, 0, answer(truth), forged=kinds),
            tenant.Request(items, 1.0, 2.0, 1, b"", forged=kinds)]  # one shed
    return tenant.judge(reqs, key), key


def test_plain_rsa_round_trip_and_forgeries():
    nums, key = tenant_requests(lambda truth: truth)
    assert nums["forged_accepted"] == nums["valid_rejected"] == 0
    assert nums["forged_checked"] == 3 and nums["valid_checked"] == 1
    assert nums["tenant_shed"] == 1 and nums["tenant_unanswered"] == 0
    nums.update(committed_ops=1, readback_checked=1, records_verified=1)
    assert judge.verdict(nums)[0] is True


@pytest.mark.parametrize("answer,number,reads", [
    (lambda t: bytes([1] * len(t)), "forged_accepted", 3),      # accept_all
    (lambda t: bytes([0] * len(t)), "valid_rejected", 1),
    (lambda t: t[1:] + t[:1], "forged_accepted", 1),           # verdicts misrouted
    (lambda t: t[:2], "tenant_unanswered", 1),                  # a short answer
])
def test_wrong_verdicts_fail(answer, number, reads):
    nums, _key = tenant_requests(answer)
    assert nums[number] == reads
    nums.update(committed_ops=1, readback_checked=1, records_verified=1)
    assert judge.verdict(nums)[0] is False


def test_all_shed_checks_nothing_and_fails():
    from benchmarks import tenant

    nums = tenant.judge([tenant.Request([], 0.0, 1.0, 1)],
                        reference.RsaKey(15, 3, 3, 5, 3))
    assert nums["forged_checked"] == 0 and judge.verdict(nums)[0] is False
