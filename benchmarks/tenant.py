"""One more tenant on the sidecar's socket, sending forged items.

The deployment's own traffic carries only valid signatures, so nothing
the daemons see tells a verify chain that works from one that answers
True.  This tenant joins the window on the same socket, through the
program's own tenant channel (``SidecarChannel``, ``OP_VERIFY``), at the
window's own load: its items ride the same cross-tenant launches as the
daemons'.  A seeded share of each request is forged — a bit bent in the
signature, a bit bent in the message, or another item's signature — and
every verdict that comes back is judged against ``reference.rsa_verify``:
a forged item has to be answered False, a valid one True.

The key and the pool of valid signatures are made from ``--seed`` by
``reference.py``'s plain RSA (nothing of the program) while the sidecar
warms up.  The mix's ``tenant`` group sets the load: ``items`` per
request, ``interval_s`` between a reply and the next request,
``forged_share``, ``pool``, ``message_bytes``.  A shed request is
overload, not an answer, and is reported beside the numbers; a request
that gets no answer counts as ``tenant_unanswered``.
"""

from __future__ import annotations

import random
import threading
import time
from dataclasses import dataclass, field

from benchmarks import reference

REPLY_TIMEOUT_S = 90.0  # a traced sidecar can stall for tens of seconds


class Pool:
    """The tenant's RSA key and its valid ``(message, signature)`` pairs,
    built on a thread of its own (set-up waits on the sidecar anyway)."""

    def __init__(self, seed: int, spec: dict, bits: int):
        self.spec = spec
        self._args = (seed, int(spec["pool"]), int(spec["message_bytes"]), bits)
        self.key: reference.RsaKey | None = None
        self.pairs: list[tuple[bytes, bytes]] = []
        self.error: BaseException | None = None
        self._thread = threading.Thread(target=self._build, daemon=True,
                                        name="tenant-pool")
        self._thread.start()

    def _build(self) -> None:
        try:
            seed, n, size, bits = self._args
            rng = random.Random(f"{seed}|tenant")
            self.key = reference.rsa_keygen(rng, bits)
            for i in range(n):
                msg = b"tenant|%d|%d|" % (seed, i) + rng.randbytes(size)
                self.pairs.append((msg, reference.rsa_sign(msg, self.key)))
        except BaseException as e:
            self.error = e

    def wait(self, timeout: float) -> None:
        self._thread.join(timeout)
        if self._thread.is_alive() or self.error is not None:
            raise RuntimeError(f"the tenant's key pool was not built: "
                               f"{self.error!r}")


@dataclass
class Request:
    items: list                      # (message, signature) as sent
    t_send: float
    t_done: float = 0.0
    status: int | None = None        # None: no answer came
    verdicts: bytes = b""
    phase: str = "window"
    forged: list = field(default_factory=list)  # what was bent, per item


def _flip(b: bytes, bit: int) -> bytes:
    i = len(b) - 1 - bit // 8
    return b[:i] + bytes([b[i] ^ (1 << (bit % 8))]) + b[i + 1:]


class Tenant(threading.Thread):
    def __init__(self, addr: str, pool: Pool, seed: int, gate):
        super().__init__(name="tenant", daemon=True)
        self.addr, self.pool, self.gate = addr, pool, gate
        self.rng = random.Random(f"{seed}|tenant-items")
        self.requests: list[Request] = []
        self.error: BaseException | None = None

    def _draw(self) -> tuple[list, list]:
        spec, pairs = self.pool.spec, self.pool.pairs
        items, forged = [], []
        for _ in range(int(spec["items"])):
            msg, sig = pairs[self.rng.randrange(len(pairs))]
            kind = ""
            if self.rng.random() < float(spec["forged_share"]):
                kind = self.rng.choice(("signature_bit", "message_bit", "swapped"))
                if kind == "signature_bit":
                    # below the top byte: the forgery stays under the modulus
                    sig = _flip(sig, self.rng.randrange(8 * (len(sig) - 1)))
                elif kind == "message_bit":
                    msg = _flip(msg, self.rng.randrange(8 * len(msg)))
                else:
                    sig = pairs[self.rng.randrange(len(pairs))][1]
            items.append((msg, sig))
            forged.append(kind)
        return items, forged

    def _one(self, channel, phase: str) -> None:
        from bftkv_tpu.cmd.verify_sidecar import OP_VERIFY, encode_request

        items, forged = self._draw()
        req = Request(items, time.monotonic(), phase=phase, forged=forged)
        key = self.pool.key
        reply = channel.request(
            OP_VERIFY, encode_request([(m, s, key) for m, s in items]))
        req.t_done = time.monotonic()
        if reply is None:
            channel.reset()  # its breaker is the daemons' business, not ours
        else:
            req.status, req.verdicts = reply[0], bytes(reply[1])
        self.requests.append(req)

    def run(self) -> None:
        try:
            from bftkv_tpu.crypto.remote_verify import SidecarChannel

            channel = SidecarChannel(self.addr, timeout=REPLY_TIMEOUT_S)
            try:
                self._one(channel, "warm")  # registers the key's rows
                deadline = self.gate.wait_start()
                while time.monotonic() < deadline:
                    self._one(channel, "window")
                    time.sleep(float(self.pool.spec["interval_s"]))
            finally:
                channel.close()
        except BaseException as e:  # surfaced by the harness after join
            self.error = e


def judge(requests: list[Request], key: reference.RsaKey) -> dict:
    """Every verdict beside the reference's (``ST_OK`` = 0, ``ST_SHED`` = 1
    in the sidecar's protocol)."""
    out = {"forged_accepted": 0, "valid_rejected": 0, "tenant_unanswered": 0,
           "forged_checked": 0, "valid_checked": 0, "tenant_shed": 0,
           "tenant_requests": len(requests), "forged_accepted_kinds": {}}
    for r in requests:
        if r.status == 1:
            out["tenant_shed"] += 1
            continue
        if r.status != 0 or len(r.verdicts) != len(r.items):
            out["tenant_unanswered"] += 1
            continue
        for (msg, sig), got, kind in zip(r.items, r.verdicts, r.forged):
            want = reference.rsa_verify(msg, sig, key.n, key.e)
            out["valid_checked" if want else "forged_checked"] += 1
            if got and not want:
                out["forged_accepted"] += 1
                kinds = out["forged_accepted_kinds"]
                kinds[kind] = kinds.get(kind, 0) + 1
            elif want and not got:
                out["valid_rejected"] += 1
    return out
