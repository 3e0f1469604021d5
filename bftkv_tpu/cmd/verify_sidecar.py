"""Shared threshold-crypto sidecar — one process owns the box's crypto.

SURVEY §5's deployment note: with several replica daemons (and edge
gateways) co-located on one accelerator host, per-process dispatchers
each pay their own device launches, XLA compilations, and transfer
overhead.  This sidecar is the Thetacrypt-shaped answer: ONE co-located
service multiplexes every tenant's crypto — verify, sign, and raw
modexp batches from all processes coalesce in its dispatchers into
shared launches (shard_map fan-out over every local device;
``native/montmodexp.c`` as the GIL-free host-fallback tier), and only
one process compiles/holds the kernels.

The service is **untrusted by construction** (2G2T's verifiable-
outsourcing framing): tenants self-check returned signatures with the
public exponent (cheap at e=65537) on EVERY item — a forged signature
can never leave a tenant — and spot-check verify/modexp verdicts
locally at a sampled rate, falling back to local crypto — with the
breaker open and a ``sidecar_dishonest`` fleet anomaly raised — on
any mismatch.  A lying service is therefore evicted within an
expected ``1/spot_rate`` batches; the sampled window is the tunable
trade, and ``BFTKV_SIDECAR_SPOT_RATE=1`` closes it (DESIGN.md §17.3).

Wire protocol (length-prefixed, one request per frame):

- **v1 (legacy verify)**: ``u32 count``, then per item ``chunk(msg)
  chunk(sig) chunk(n) u32 e``; response: count bytes of 0/1.  Kept
  bit-compatible for old clients.
- **v2 (op-tagged)**: ``u32 0xFFFFFFFF`` (impossible as a v1 count),
  ``u8 op``, payload.  Response: ``u8 status`` + payload.  Ops:
  VERIFY (v1 body), SIGN (``u32 count``, per item ``u32 handle``
  ``chunk(msg)``), REGISTER (``u32 count``, per key ``chunk(n) u32 e
  chunk(d) chunk(p) chunk(q)``), MODEXP (``u32 count``, per item
  ``chunk(base) chunk(exp) chunk(mod)``), STATS (empty → JSON stats
  frame).  Statuses: OK / SHED (admission declined — tenant falls
  back local WITHOUT opening its breaker) / ERR (internal failure —
  tenant falls back local and opens its breaker) / BAD_HANDLE (sign
  handle unknown, e.g. after a sidecar restart — tenant re-registers
  and retries once) / REFUSED (key registration declined for the
  connection's lifetime: a channel that must not carry keys, or the
  per-connection key budget spent — the client keeps signing locally
  and never asks again).

Sign keys are registered **per connection** as integer handles and are
accepted ONLY over the mode-0600 Unix socket or an HMAC-authenticated
channel — private material never crosses a squatter-able plain TCP
port (the client enforces the same policy and simply never remotes
signing there).

Backpressure: VERIFY/SIGN/MODEXP pass a bounded admission queue
(``bftkv_tpu.admission.AdmissionQueue``, the gateway's semantics) —
bounded inflight + bounded wait, instant shed past it with the
``sidecar.shed`` metric.  A shed tenant batch runs on the tenant's own
host crypto; the service degrades, it never queues unboundedly.

Failure semantics for v1 frames (deliberate, load-bearing):

- *Malformed frame* (attacker-controlled bytes): all-fail response of
  the claimed count — the client's accounting stays aligned and hostile
  input can never manufacture a "valid" verdict.
- *Internal error* (dispatcher/device failure): **zero-length
  response** — a count mismatch on the client side, which makes
  ``RemoteVerifierDomain`` fall back to local verification.  A broken
  accelerator must degrade to local verify, not masquerade as
  "all signatures invalid" (a cluster-wide liveness outage).

Trust boundary: results are checked by the tenants, but *liveness* and
key secrecy still require transport integrity, so the recommended
deployment is a **Unix domain socket** (``--listen unix:/path/sock``,
created mode 0600) — a TCP port can be squatted by any local user
after a sidecar crash.  For TCP, configure a shared secret
(``--secret-file``): every request and response carries an HMAC-SHA256
tag and the client fails closed (local crypto) on tag mismatch.

Run: ``python -m bftkv_tpu.cmd.verify_sidecar --listen
unix:/run/bftkv/crypto.sock --stats 127.0.0.1:7960``.  Daemons opt in
with ``bftkv --sidecar unix:/run/bftkv/crypto.sock`` (verify-only
legacy spelling: ``--verify-sidecar``); ``run_cluster --sidecar auto``
boots one beside the whole fleet and the FleetCollector scrapes the
``--stats`` endpoint as a ``role=sidecar`` member.
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import hmac
import io
import json
import logging
import os
import socket
import socketserver
import struct
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from bftkv_tpu import trace
from bftkv_tpu.admission import AdmissionQueue
from bftkv_tpu.metrics import registry as metrics
from bftkv_tpu.packet import read_chunk, write_chunk
from bftkv_tpu import flags

_log = logging.getLogger("bftkv_tpu.sidecar")

#: JAX's own persistent-cache events, as the sidecar counts them: a
#: program loaded from the cache, and a program that had to be compiled
#: (a "miss" is a compile long enough to be worth an entry —
#: jax_persistent_cache_min_compile_time_secs).
_CACHE_EVENT_NAMES = {
    "/jax/compilation_cache/cache_hits": "loaded",
    "/jax/compilation_cache/cache_misses": "compiled",
}

#: JAX's duration events (``jax.monitoring``, names as in JAX 0.9's
#: ``jax/_src/dispatch.py`` and ``compiler.py``) that split a warm-up
#: shape's seconds.  ``backend`` covers the whole of "compile or load
#: from the persistent cache"; ``load`` is the cache read inside it, so
#: what was compiled is their difference.
_DURATION_EVENT_NAMES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "load",
}

__all__ = [
    "serve",
    "main",
    "encode_request",
    "decode_request",
    "encode_op",
    "encode_sign_request",
    "decode_sign_request",
    "encode_register_request",
    "decode_register_request",
    "encode_modexp_request",
    "decode_modexp_request",
    "request_tag",
    "response_tag",
    "SidecarService",
    "TAG_LEN",
    "MAGIC",
    "OP_VERIFY",
    "OP_SIGN",
    "OP_REGISTER",
    "OP_MODEXP",
    "OP_STATS",
    "ST_OK",
    "ST_SHED",
    "ST_ERR",
    "ST_BAD_HANDLE",
    "ST_REFUSED",
]

TAG_LEN = 32  # HMAC-SHA256

#: v2 frame marker: impossible as a v1 item count (> any max_frame).
MAGIC = b"\xff\xff\xff\xff"

OP_VERIFY = 1
OP_SIGN = 2
OP_REGISTER = 3
OP_MODEXP = 4
OP_STATS = 5

ST_OK = 0
ST_SHED = 1
ST_ERR = 2
ST_BAD_HANDLE = 3
ST_REFUSED = 4

_OP_NAMES = {OP_VERIFY: "verify", OP_SIGN: "sign", OP_MODEXP: "modexp"}


def request_tag(secret: bytes, body: bytes) -> bytes:
    return hmac.new(secret, b"bftkv-sidecar-req" + body, hashlib.sha256).digest()


def response_tag(secret: bytes, req_body: bytes, out: bytes) -> bytes:
    """Tag binds the verdicts to the exact request they answer, so a
    recorded response for one batch cannot be replayed for another."""
    h = hashlib.sha256(req_body).digest()
    return hmac.new(secret, b"bftkv-sidecar-res" + h + out, hashlib.sha256).digest()


# -- codecs (shared by client and server) -----------------------------------


def _int_bytes(v: int) -> bytes:
    return v.to_bytes((v.bit_length() + 7) // 8 or 1, "big")


def encode_request(items: list) -> bytes:
    """[(message, sig_bytes, PublicKey)] → one VERIFY body (v1 shape)."""
    buf = io.BytesIO()
    buf.write(struct.pack(">I", len(items)))
    for message, sig, key in items:
        write_chunk(buf, message)
        write_chunk(buf, sig)
        write_chunk(buf, _int_bytes(key.n))
        buf.write(struct.pack(">I", key.e))
    return buf.getvalue()


def decode_request(body: bytes) -> list:
    from bftkv_tpu.crypto.rsa import PublicKey

    r = io.BytesIO(body)
    (count,) = struct.unpack(">I", r.read(4))
    if count > len(body):  # each item needs headers at minimum
        raise ValueError("bad count")
    items = []
    for _ in range(count):
        msg = read_chunk(r) or b""
        sig = read_chunk(r) or b""
        n = int.from_bytes(read_chunk(r) or b"", "big")
        (e,) = struct.unpack(">I", r.read(4))
        items.append((msg, sig, PublicKey(n=n, e=e)))
    return items


def encode_op(op: int, payload: bytes = b"") -> bytes:
    """One v2 body: magic + op byte + payload."""
    return MAGIC + bytes([op]) + payload


def encode_sign_request(items: list) -> bytes:
    """[(handle, message)] → SIGN payload."""
    buf = io.BytesIO()
    buf.write(struct.pack(">I", len(items)))
    for handle, message in items:
        buf.write(struct.pack(">I", handle))
        write_chunk(buf, message)
    return buf.getvalue()


def decode_sign_request(payload: bytes) -> list:
    r = io.BytesIO(payload)
    (count,) = struct.unpack(">I", r.read(4))
    if count > len(payload):
        raise ValueError("bad count")
    items = []
    for _ in range(count):
        (handle,) = struct.unpack(">I", r.read(4))
        items.append((handle, read_chunk(r) or b""))
    return items


def encode_register_request(keys: list) -> bytes:
    """[PrivateKey] → REGISTER payload (n, e, d, p, q per key)."""
    buf = io.BytesIO()
    buf.write(struct.pack(">I", len(keys)))
    for k in keys:
        write_chunk(buf, _int_bytes(k.n))
        buf.write(struct.pack(">I", k.e))
        write_chunk(buf, _int_bytes(k.d))
        write_chunk(buf, _int_bytes(k.p))
        write_chunk(buf, _int_bytes(k.q))
    return buf.getvalue()


def decode_register_request(payload: bytes) -> list:
    from bftkv_tpu.crypto.rsa import PrivateKey

    r = io.BytesIO(payload)
    (count,) = struct.unpack(">I", r.read(4))
    if count > len(payload):
        raise ValueError("bad count")
    keys = []
    for _ in range(count):
        n = int.from_bytes(read_chunk(r) or b"", "big")
        (e,) = struct.unpack(">I", r.read(4))
        d = int.from_bytes(read_chunk(r) or b"", "big")
        p = int.from_bytes(read_chunk(r) or b"", "big")
        q = int.from_bytes(read_chunk(r) or b"", "big")
        if not (1 < p < n and 1 < q < n and p * q == n and d > 0):
            raise ValueError("inconsistent private key")
        keys.append(PrivateKey(n=n, e=e, d=d, p=p, q=q))
    return keys


def wrap_keys(secret: bytes, payload: bytes) -> bytes:
    """AEAD-seal a REGISTER payload under the shared secret.

    The HMAC frame tags authenticate but do not HIDE: a squatter on a
    freed TCP port would otherwise read n/e/d/p/q out of the very first
    frame a reconnecting client sends — before any response proves the
    peer knows the secret.  Sealing makes captured key material
    worthless without the secret (the unix socket needs none of this:
    the kernel enforces mode 0600)."""
    from bftkv_tpu.crypto.aead import AESGCM
    from bftkv_tpu.crypto.rng import generate_random

    key = hashlib.sha256(b"bftkv-sidecar-keywrap" + secret).digest()
    nonce = generate_random(12)
    return nonce + AESGCM(key).encrypt(
        nonce, payload, b"bftkv-sidecar-register"
    )


def unwrap_keys(secret: bytes, wrapped: bytes) -> bytes:
    """Inverse of :func:`wrap_keys`; raises on tamper/garbage."""
    from bftkv_tpu.crypto.aead import AESGCM

    if len(wrapped) < 12:
        raise ValueError("short keywrap")
    key = hashlib.sha256(b"bftkv-sidecar-keywrap" + secret).digest()
    return AESGCM(key).decrypt(
        wrapped[:12], wrapped[12:], b"bftkv-sidecar-register"
    )


def encode_modexp_request(items: list) -> bytes:
    """[(base, exp, mod)] → MODEXP payload."""
    buf = io.BytesIO()
    buf.write(struct.pack(">I", len(items)))
    for b, e, m in items:
        write_chunk(buf, _int_bytes(b))
        write_chunk(buf, _int_bytes(e))
        write_chunk(buf, _int_bytes(m))
    return buf.getvalue()


def decode_modexp_request(payload: bytes) -> list:
    r = io.BytesIO(payload)
    (count,) = struct.unpack(">I", r.read(4))
    if count > len(payload):
        raise ValueError("bad count")
    items = []
    for _ in range(count):
        b = int.from_bytes(read_chunk(r) or b"", "big")
        e = int.from_bytes(read_chunk(r) or b"", "big")
        m = int.from_bytes(read_chunk(r) or b"", "big")
        if m <= 0:
            raise ValueError("bad modulus")
        items.append((b, e, m))
    return items


def _chunks(payload: bytes, count: int) -> list:
    """``count`` length-prefixed chunks (sign/modexp response bodies)."""
    r = io.BytesIO(payload)
    out = []
    for _ in range(count):
        out.append(read_chunk(r) or b"")
    if r.read(1):
        raise ValueError("trailing bytes")
    return out


# -- the service ------------------------------------------------------------


def _declared_widths(flag: str) -> list[int]:
    """RSA widths a deployment declared in ``flag``, ascending.  A set
    value that is no list of widths fails the start."""
    raw = flags.get(flag) or ""
    try:
        widths = sorted({int(w) for w in raw.split(",") if w.strip()})
    except ValueError:
        widths = []
    if not widths or widths[0] < 512 or widths[-1] > 16384:
        raise ValueError(
            f"{flag}={raw!r}: want RSA widths, such as '2048' or "
            "'2048,3072'"
        )
    return widths


def identity_bits() -> list[int]:
    """The deployment's RSA identity widths (``BFTKV_IDENTITY_BITS``)."""
    return _declared_widths("BFTKV_IDENTITY_BITS")


def ca_bits() -> list[int]:
    """The key widths of the threshold CAs the deployment deals to its
    quorum (``BFTKV_CA_BITS``); none where it declares none."""
    if not flags.get("BFTKV_CA_BITS"):
        return []
    return _declared_widths("BFTKV_CA_BITS")


def _phase_sum(snap: dict, name: str) -> float:
    """Seconds a phase histogram holds, over its ``op`` label sets."""
    return sum(
        v for k, v in snap.items() if k.startswith(name + ".sum")
    )


class SidecarService:
    """Dispatchers + admission + stats for one sidecar process.

    Cross-tenant coalescing happens HERE: every connection handler
    thread submits into these shared dispatchers, so batches from
    different replica/gateway processes ride the same launches.  The
    measured host/device crossover steers each flush's tier *inside*
    the launch (``dispatch.calibration()``: CPU backends pin
    always-host — the Montgomery native kernel — so the r05 CPU-XLA
    flush disaster cannot recur here either), while the dispatcher
    queue itself is never bypassed: occupancy must stay observable and
    tenants must keep coalescing even on a host-only box."""

    def __init__(
        self,
        *,
        max_batch: int = 4096,
        max_wait: float | None = None,
        admission: AdmissionQueue | None = None,
    ):
        from bftkv_tpu.ops import dispatch

        cal = dispatch.calibration()
        # Host tier (CPU-calibrated box): there is no launch overhead
        # to amortize, so a collection window only adds latency —
        # cross-tenant coalescing still happens through concurrency (a
        # flush in service queues every arrival behind it).  On an
        # accelerator the usual windows amortize the launch RTT.
        host_tier = cal["prefer_host"]
        if max_wait is None and host_tier:
            max_wait = 0.0005
        kw = {} if max_wait is None else {"max_wait": max_wait}
        self.verify = dispatch.VerifyDispatcher(
            max_batch=max_batch, calibrate=False, **kw
        ).start()
        sign_wait = 0.0005 if host_tier else None
        # Host-tier flush bounds: a host sign is ~2 ms/item with no
        # launch to amortize, so a flush merging several tenants'
        # batches makes EACH wait for ALL (fair-share latency, minus
        # nothing).  Bounding the flush keeps FIFO-at-request latency;
        # on an accelerator the big merges ARE the win and the bounds
        # stay wide.
        sign_flush = 16 if host_tier else max_batch
        if host_tier:
            self.verify.max_batch = min(self.verify.max_batch, 256)
        self.sign = dispatch.SignDispatcher(
            max_batch=sign_flush, calibrate=False, max_wait=sign_wait
        ).start()
        self.modexp = dispatch.ModexpDispatcher(
            max_batch=sign_flush,
            calibrate=False,
            **kw,
        ).start()
        self._cal: dict = {}
        self.apply_calibration(cal)
        self.max_keys = flags.get_int("BFTKV_SIDECAR_MAX_KEYS")
        self._t0 = time.monotonic()
        import jax

        devs = jax.devices()
        self._device = {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        }
        # On a device backend, starting MEANS compiling: every program
        # a flush can launch is built (or loaded from the compile
        # cache) here, before the caller binds a socket.  Runs before
        # the recalibration loop exists, so its compile-laden round
        # trips can never price the crossover.
        self.warmup = self._warm()
        # Exist from the start, so that 0 reads as 0 and not as a
        # program without the counter (the warm-up ended on a reset).
        for name in ("sidecar.unwarmed_width", "modexp.device",
                     "modexp.host", "sidecar.backlog.seconds"):
            metrics.incr(name, 0)
        # After the warm-up, which passes no admission: the queue's
        # "empty since" is then the moment the service can first serve.
        self.admission = admission or AdmissionQueue(
            max_inflight=flags.get_int("BFTKV_SIDECAR_MAX_INFLIGHT"),
            max_queue=flags.get_int("BFTKV_SIDECAR_MAX_QUEUE"),
            max_wait=flags.get_float("BFTKV_SIDECAR_MAX_WAIT"),
            metric="sidecar.shed",
        )
        # Online recalibration (ISSUE 19): the boot verdict above used
        # to be forever — nothing ever called calibration(force=True)
        # again, so an accelerator attached (or un-wedged) mid-run
        # could not flip ALWAYS_HOST without a restart.  The loop
        # re-measures every BFTKV_DISPATCH_RECAL_S seconds, and
        # immediately after the FIRST accelerator-backed launch
        # completes (observed_launch_rtt turns non-None).
        self._recal_stop = threading.Event()
        self._recal_seen_rtt = False
        self._recal_thread: threading.Thread | None = None
        period = flags.get_float("BFTKV_DISPATCH_RECAL_S")
        if period and period > 0:
            self._recal_thread = threading.Thread(
                target=self._recal_loop, args=(period,), daemon=True
            )
            self._recal_thread.start()

    def _warm(self) -> dict:
        """Launch one full batch of every bucket shape the dispatchers
        can emit, through the dispatchers themselves — collector, flush
        worker, staging ring, donated launch, async completion — and
        check each result against the host.

        The first launch of a shape compiles it (12–23 s each on a v5e
        host; a 30 s tenant channel timeout would turn that into silent
        host fallback), so a sidecar that is listening is a sidecar whose
        programs are built.  Which programs is the deployment's to say:
        its identity widths (``BFTKV_IDENTITY_BITS``, default 2048),
        each asked of ``ops.rns.chains``.  Per width: where the verify
        chain takes the modulus, the verify buckets — the powers of two
        from 256 to ``max_batch``, built once, one program serving
        every modulus the chain holds — which a sign's fault check
        rides too; where the pow chain takes the CRT halves, the sign
        buckets — a flush of n signatures is 2n rows, buckets 64 …
        2·``max_batch`` — and one modexp launch at that row width,
        which shares the sign programs.  And its threshold CAs' key
        widths (``BFTKV_CA_BITS``, default none): per width the
        programs a first-level fragment rides — a whole modulus of that
        width under the longer exponent class (``rns.long_exp_bits``),
        buckets 64 and 128 (64 alone at the wide chain's widths: a
        launch holds one fused-chain tile, ``rns.long_exp_rows``), each
        launch checked against the host.  A
        wrong result or a device error raises: a sidecar that cannot
        launch does not start.

        Afterwards the domains know what was built
        (``VerifierDomain.chain_warm``, ``warm_rows`` of the signer and
        of the modexp dispatcher): an item of an undeclared width is
        served from the host tier and counted
        (``sidecar.unwarmed_width``), so no request ever compiles.
        (One shape still can: a flush mixing more than 64 distinct
        moduli escalates the key axis.)

        Nothing to do on a CPU backend — calibration pins host there
        and no flush ever launches."""
        self._cache_events = {"loaded": 0, "compiled": 0}
        self._durations = dict.fromkeys(_DURATION_EVENT_NAMES.values(), 0.0)
        if self._cal["prefer_host"]:
            return {"shapes": [], "seconds": 0.0}
        from bftkv_tpu import ops
        from bftkv_tpu.crypto import rsa as rsamod
        from bftkv_tpu.ops import dispatch, rns

        import jax

        # Listening for the life of the service (stop() unregisters):
        # the counts restart after the warm-up, so a later compile is a
        # compile inside some tenant's request.
        jax.monitoring.register_event_listener(self._on_jax_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_jax_duration
        )
        # This process holds the device: its leaf phase spans go to the
        # profiler too, on the clock of the device's own events
        # (trace.BRIDGED; whoever captures — /debug/profile on the
        # stats port — then sees what the host did in each idle gap).
        trace.set_bridge(jax.profiler.TraceAnnotation)

        def buckets(lo: int, hi: int) -> list[int]:
            b, out = lo, []
            while b < hi:
                out.append(b)
                b *= 2
            return out + [hi]

        t_start = time.monotonic()
        msg = b"bftkv-sidecar-warmup"
        shapes: list[dict] = []
        fetched = _phase_sum(metrics.snapshot(), "flush.fetch")
        bits = 0  # the width being warmed

        def timed(role: str, n: int, fn) -> None:
            # The shapes run one at a time (submit blocks), so every
            # JAX duration event and phase observation between t0 and
            # the return is this shape's.  A kind with no event is 0.
            nonlocal fetched
            self._durations = dict.fromkeys(self._durations, 0.0)
            t0 = time.monotonic()
            fn(n)
            dt = round(time.monotonic() - t0, 3)
            d = self._durations
            before, fetched = fetched, _phase_sum(
                metrics.snapshot(), "flush.fetch"
            )
            shape = {
                "role": role, "items": n, "bits": bits, "seconds": dt,
                "trace_s": round(d["trace"], 3),
                "lower_s": round(d["lower"], 3),
                "load_s": round(d["load"], 3),
                "compile_s": round(max(0.0, d["backend"] - d["load"]), 3),
                # blocked on the device until the result was back
                "run_s": round(fetched - before, 3),
            }
            shapes.append(shape)
            _log.info(
                "warm-up: %s x%d at %d bits in %.1f s (trace %.1f, lower "
                "%.1f, load %.1f, compile %.1f, run %.1f)",
                role, n, bits, dt, shape["trace_s"], shape["lower_s"],
                shape["load_s"], shape["compile_s"], shape["run_s"],
            )

        def warm_verify(n: int) -> None:
            ok = self.verify.submit(
                [(msg, sig, key.public)] * (n - 1)
                + [(msg, forged, key.public)]
            )
            if not (all(ok[:-1]) and not ok[-1]):
                raise RuntimeError(
                    f"sidecar warm-up: verify batch of {n} returned "
                    "wrong verdicts"
                )

        def warm_sign(n: int) -> None:
            if self.sign.submit([(msg, key)] * n) != [sig] * n:
                raise RuntimeError(
                    f"sidecar warm-up: sign batch of {n} returned a "
                    "wrong signature"
                )

        def warm_modexp(n: int) -> None:
            items = [(i + 2, key.d % (key.p - 1), key.p) for i in range(n)]
            if self.modexp.submit(items) != [pow(*it) for it in items]:
                raise RuntimeError(
                    f"sidecar warm-up: modexp batch of {n} returned a "
                    "wrong residue"
                )

        # One key per declared width; the verify chain's one program
        # is built by the first width it takes, every sign bucket after
        # every verify bucket (a sign's fault check rides them).
        keys = {b: rsamod.generate(b) for b in identity_bits()}
        bits = next((b for b in keys if rns.chains(b).verify), 0)
        verify_warm = bool(bits)
        if verify_warm:
            key = keys[bits]
            sig = rsamod.sign(msg, key)
            forged = sig[:-1] + bytes([sig[-1] ^ 1])
            v_lo = max(256, self.verify.verifier.host_threshold)
            for n in buckets(min(v_lo, self.verify.max_batch),
                             self.verify.max_batch):
                timed("verify", n, warm_verify)
        # the CRT-half row width of each key the pow chain can take
        rows = {
            bits: 16 * -(-max(k.p.bit_length(), k.q.bit_length()) // 16)
            for bits, k in keys.items()
        }
        rows = {b: r for b, r in rows.items() if rns.chains(r).pow}
        for bits in rows:
            key = keys[bits]
            sig = rsamod.sign(msg, key)
            s_lo = max(32, self.sign.signer.host_threshold)
            for n in buckets(min(s_lo, self.sign.max_batch),
                             self.sign.max_batch):
                timed("sign", n, warm_sign)
        for bits in rows:
            key = keys[bits]
            m = min(max(64, self.modexp.device_threshold),
                    self.modexp.max_batch)
            timed("modexp", m, warm_modexp)
        self.verify.verifier.chain_warm = verify_warm
        self.sign.signer.warm_rows = frozenset(rows.values())
        # A declared CA's first-level fragments: whole-modulus rows
        # under the longer exponent class, entered in ``warm_rows``
        # before their buckets run, so that each warm launch passes the
        # dispatcher as a tenant's will.
        ca_rows: dict[int, tuple[int, int]] = {}
        for bits in ca_bits():
            n_bits = 16 * -(-bits // 16)
            cls = (n_bits, rns.long_exp_bits(n_bits))
            if rns.chains(*cls).pow:
                ca_rows[bits] = cls
        self.modexp.warm_rows = frozenset(rows.values()) | frozenset(
            ca_rows.values()
        )

        def fragment_rows() -> tuple[list, list]:
            # eight distinct rows and their residues: a first-level
            # fragment is d minus nine random values of 2 x bits - 1 bits
            mod = (1 << (bits - 1)) + 973
            ctx = rns.pow_context(ca_rows[bits][0])
            while ctx.key_rows(mod) is None:
                mod += 2  # the chain has rows for it
            few = [
                (i + 2, (1 << (2 * bits)) + (i + 1) * 0x9E3779B97F4A7C15, mod)
                for i in range(8)
            ]
            return few, [pow(*it) for it in few]

        def warm_fragments(n: int) -> None:
            items = [few[i % 8] for i in range(n)]
            on_device = metrics.snapshot().get("modexp.device", 0)
            if self.modexp.submit(items) != [want[i % 8] for i in range(n)]:
                raise RuntimeError(
                    f"sidecar warm-up: fragment batch of {n} at {bits} "
                    "bits returned a wrong residue"
                )
            if metrics.snapshot().get("modexp.device", 0) - on_device != n:
                raise RuntimeError(
                    f"sidecar warm-up: fragment batch of {n} at {bits} "
                    "bits did not ride the device"
                )

        for bits in ca_rows:
            few, want = fragment_rows()
            top = rns.long_exp_rows(ca_rows[bits][0])
            for n in buckets(min(64, top), top):
                timed("fragment", n, warm_fragments)
        # Warm-up is not traffic.  Its round trips included compilation
        # (or a cache load) and say nothing about what a launch costs;
        # its items are not any tenant's.  The observed-RTT series and
        # the registry restart from zero — no listener exists yet, so
        # nothing of a tenant's can be lost — and ``shapes`` above is
        # the record of what ran.
        dispatch.forget_launch_rtt()
        metrics.reset()
        counts = self._cache_events
        self._cache_events = dict.fromkeys(counts, 0)
        return {
            "shapes": shapes,
            "seconds": round(time.monotonic() - t_start, 3),
            # what was declared, and what that built
            "identity_bits": list(keys),
            "verify_chain": verify_warm,
            "pow_rows": sorted(set(rows.values())),
            "ca_bits": list(ca_rows),
            "fragment_rows": sorted(set(ca_rows.values())),
            # which chain those rode: "ok" the fused one, "unused" or
            # "fallback: <error>" the XLA one (rns.pallas_status)
            "fragment_chain": rns.pallas_status()["pow"],
            "compile_cache": {
                "dir": ops.compile_cache_dir(),
                **counts,
                # Warm = this start loaded its programs, compiled none.
                "warm": counts["compiled"] == 0 and counts["loaded"] > 0,
            },
        }

    def _on_jax_event(self, event: str, **_kw) -> None:
        name = _CACHE_EVENT_NAMES.get(event)
        if name is not None:
            self._cache_events[name] += 1

    def _on_jax_duration(self, event: str, duration: float, **_kw) -> None:
        name = _DURATION_EVENT_NAMES.get(event)
        if name is not None:
            self._durations[name] += duration

    def apply_calibration(self, cal: dict) -> None:
        """(Re-)point the dispatchers' host/device thresholds at a
        calibration verdict — boot and every recalibration.  The tier
        decision lives inside each launch, so no dispatcher restart
        (and no caller disruption) is needed when the verdict moves.
        Note the sidecar intentionally does NOT adopt ``prefer_host``
        inline bypass: tenants must keep coalescing through the queue
        even on a host-only box (occupancy stays observable)."""
        from bftkv_tpu.ops import dispatch

        if flags.raw("BFTKV_HOST_VERIFY_THRESHOLD") is None:
            self.verify.verifier.host_threshold = cal["verify_crossover"]
        if flags.raw("BFTKV_HOST_SIGN_THRESHOLD") is None:
            if cal["sign_crossover"] is not None:
                self.sign.signer.host_threshold = cal["sign_crossover"]
            elif self.sign._signer_default_threshold is not None:
                self.sign.signer.host_threshold = (
                    self.sign._signer_default_threshold
                )
        self.modexp.device_threshold = (
            dispatch.ALWAYS_HOST
            if cal["prefer_host"]
            else max(16, cal["verify_crossover"])
        )
        self._cal = cal

    def recalibrate(self) -> dict:
        """Force a fresh measurement and re-apply it (the
        ``/recalibrate`` devtools hook and the periodic loop)."""
        from bftkv_tpu.ops import dispatch

        cal = dispatch.calibration(force=True)
        self.apply_calibration(cal)
        metrics.incr("sidecar.recalibrations")
        return cal

    def _recal_loop(self, period: float) -> None:
        from bftkv_tpu.ops import dispatch

        next_at = time.monotonic() + period
        # Wake at min(period, 2 s): the periodic re-measure honors the
        # full period, but the first-successful-launch trigger should
        # not wait out a 60 s window to engage a device that just
        # proved itself.
        while not self._recal_stop.wait(timeout=min(period, 2.0)):
            rtt = dispatch.observed_launch_rtt()
            first_launch = rtt is not None and not self._recal_seen_rtt
            if first_launch:
                self._recal_seen_rtt = True
            if first_launch or time.monotonic() >= next_at:
                try:
                    self.recalibrate()
                except Exception:
                    metrics.incr("sidecar.recalibration_errors")
                next_at = time.monotonic() + period

    def stop(self) -> None:
        self._recal_stop.set()
        if self._recal_thread is not None:
            self._recal_thread.join(timeout=5)
            self._recal_thread = None
        self.verify.stop()
        self.sign.stop()
        self.modexp.stop()
        if self.warmup["shapes"]:
            import jax

            jax.monitoring.unregister_event_listener(self._on_jax_event)
            jax.monitoring.unregister_event_duration_listener(
                self._on_jax_duration
            )
            trace.set_bridge(None)

    def stats(self) -> dict:
        """The ``/metrics``-style stats frame (OP_STATS and the stats
        HTTP ``/info``): queue depth, per-dispatcher batch occupancy,
        shed, and per-op throughput counters."""
        snap = metrics.snapshot()
        inflight, waiting = self.admission.depth()

        def disp(name: str) -> dict:
            flushes = snap.get(f"{name}.flushes", 0)
            items = snap.get(f"{name}.items", 0)
            return {
                "flushes": flushes,
                "items": items,
                "occupancy_per_launch": round(items / flushes, 2)
                if flushes
                else None,
                "batch_p50": snap.get(f"{name}.batch.p50", 0),
                "throughput_items_per_s": round(
                    snap.get(f"{name}.throughput", 0), 1
                ),
            }

        from bftkv_tpu.ops import devbuf, dispatch
        from bftkv_tpu.ops import rns

        def launched(name: str) -> dict:
            # What actually rode a device launch (a flush below the
            # crossover runs on host inside the dispatcher).
            return {
                "items": snap.get(f"{name}.device", 0),
                "launches": snap.get(f"{name}.device_batch.count", 0),
                "max_items_per_launch": int(
                    metrics.percentile(f"{name}.device_batch", 1.0) or 0
                ),
                "host_items": snap.get(f"{name}.host", 0),
            }

        pallas = rns.pallas_status()
        rtt = dispatch.observed_launch_rtt()
        return {
            "uptime_s": round(time.monotonic() - self._t0, 1),
            "queue": {
                "inflight": inflight,
                "waiting": waiting,
                "max_inflight": self.admission.max_inflight,
                "shed": self.admission.shed,
            },
            "ops": {
                name: snap.get("sidecar.items{op=%s}" % name, 0)
                for name in _OP_NAMES.values()
            },
            "batch": {
                "verify": disp("dispatch"),
                "sign": disp("signdispatch"),
                "modexp": disp("modexpdispatch"),
            },
            "device_plane": {
                "device": self._device,
                "calibration": {
                    k: self._cal.get(k)
                    for k in (
                        "backend",
                        "host_verify_s",
                        "device_rtt_s",
                        "verify_crossover",
                        "prefer_host",
                        "source",
                    )
                },
                "launched": {
                    "verify": launched("verify"),
                    "sign": launched("sign"),
                },
                # Which RNS chain served each role: the fused Pallas
                # chain once one completed, else the XLA chain.  A
                # retreat from one to the other is never quiet.
                "kernels": {
                    "verify": "pallas" if pallas["verify"] == "ok" else "xla",
                    "sign": "pallas" if pallas["pow"] == "ok" else "xla",
                    "pallas_status": pallas,
                    "pallas_fallbacks": snap.get("rns.pallas_fallback", 0),
                    "sign_rns_fallbacks": snap.get("sign.rns_fallback", 0),
                },
                "warmup": self.warmup,
                # Programs compiled AFTER the warm-up, i.e. inside some
                # tenant's request: a shape the warm-up did not cover.
                "compiled_since_warmup": self._cache_events["compiled"],
                "launch_rtt_s": None if rtt is None else round(rtt, 6),
                "recalibrations": snap.get("sidecar.recalibrations", 0),
                "buffer_rings": devbuf.stats(),
            },
        }


class _Handler(socketserver.BaseRequestHandler):
    def handle(self) -> None:
        sock = self.request
        secret = self.server.secret
        # Per-CONNECTION sign-key handles: a reconnect starts empty, so
        # a client that reconnects after a sidecar restart re-registers
        # (and a crashed client's keys die with its connection).
        conn_keys: dict = {}
        next_handle = [1]
        try:
            while True:
                hdr = _recvall(sock, 4)
                if hdr is None:
                    return
                (ln,) = struct.unpack(">I", hdr)
                if ln > self.server.max_frame:
                    return  # oversized frame: drop the connection
                body = _recvall(sock, ln)
                if body is None:
                    return
                if secret is not None:
                    # Unauthenticated peer: drop the connection. No
                    # all-fail reply — an attacker must not be able to
                    # steer verdicts at all without the secret.
                    if len(body) < TAG_LEN or not hmac.compare_digest(
                        body[-TAG_LEN:], request_tag(secret, body[:-TAG_LEN])
                    ):
                        return
                    body = body[:-TAG_LEN]
                opname = None
                if body[:4] == MAGIC and len(body) >= 5:
                    opname = _OP_NAMES.get(body[4])
                # A VERIFY / SIGN / MODEXP frame's whole service time,
                # sheds included: admission, decode, dispatch and both
                # intervals of the reply.  Control frames, v1: no phase.
                with (trace.leaf("sidecar.serve", opname) if opname
                      else contextlib.nullcontext()):
                    if body[:4] == MAGIC and len(body) >= 5:
                        status, payload = self._handle_v2(
                            body[4], body[5:], conn_keys, next_handle
                        )
                        out = bytes([status]) + payload
                    else:
                        out = self._handle_v1(body)
                    if opname is None:
                        self._send(sock, secret, body, out)
                    else:
                        # second interval of sidecar.reply: authenticate
                        # and send (the first, encode, is in _handle_v2)
                        with trace.leaf(
                            "sidecar.reply", opname, bytes=len(out)
                        ):
                            self._send(sock, secret, body, out)
        except (ConnectionError, OSError):
            return

    @staticmethod
    def _send(sock, secret, body: bytes, out: bytes) -> None:
        tag = b"" if secret is None or not out else response_tag(
            secret, body, out
        )
        sock.sendall(struct.pack(">I", len(out) + len(tag)) + out + tag)

    def _handle_v1(self, body: bytes) -> bytes:
        """Legacy verify frames, bit-compatible with old clients."""
        claimed = struct.unpack(">I", body[:4])[0] if len(body) >= 4 else 0
        try:
            items = decode_request(body)
        except Exception:
            # Malformed frame: all-fail response of the claimed count
            # keeps the client's accounting aligned (a hostile count is
            # already bounded by the frame).
            return bytes(min(claimed, len(body)))
        try:
            ok = self.server.dispatcher.verify(items)
            return bytes(bool(b) for b in ok)
        except Exception:
            # Internal failure (dead/hung accelerator, bug): zero-
            # length reply = count mismatch = client falls back to
            # LOCAL verification.  Never fabricate "all invalid" for
            # well-formed input.
            return b""

    def _handle_v2(
        self, op: int, payload: bytes, conn_keys: dict, next_handle: list
    ) -> tuple[int, bytes]:
        svc: SidecarService = self.server.service
        if op == OP_STATS:
            try:
                return ST_OK, json.dumps(svc.stats()).encode()
            except Exception:
                return ST_ERR, b""
        if op == OP_REGISTER:
            if not self.server.keys_ok:
                # Key material must only cross the 0600 unix socket or
                # the HMAC channel; plain TCP is refusable by policy
                # (the client never sends keys there either).
                return ST_REFUSED, b""
            try:
                if self.server.secret is not None:
                    # Key material on the HMAC channel arrives sealed
                    # (wrap_keys): the frame tag authenticates, the
                    # AEAD hides — see the client's register path.
                    payload = unwrap_keys(self.server.secret, payload)
                keys = decode_register_request(payload)
            except Exception:
                return ST_ERR, b""
            if len(conn_keys) + len(keys) > svc.max_keys:
                # Per-connection key budget spent (handles are add-only
                # while the connection lives): REFUSED, not ERR — the
                # client's refused-path is terminal for the connection
                # (signing stays local, verify keeps remoting), whereas
                # ERR would trip the shared breaker and re-trip it on
                # every register retry — a permanent flap that benches
                # verify too and spams sidecar_down anomalies.
                return ST_REFUSED, b""
            handles = []
            for k in keys:
                h = next_handle[0]
                next_handle[0] += 1
                conn_keys[h] = k
                handles.append(h)
            return ST_OK, struct.pack(">I", len(handles)) + b"".join(
                struct.pack(">I", h) for h in handles
            )
        opname = _OP_NAMES.get(op)
        if opname is None:
            return ST_ERR, b""
        if not svc.admission.acquire(opname):
            return ST_SHED, b""
        try:
            # sidecar.decode runs inside the admission slot: where it
            # sits in a capture shows what that costs the queue.
            decode = trace.leaf("sidecar.decode", opname, bytes=len(payload))
            if op == OP_VERIFY:
                try:
                    with decode:
                        items = decode_request(payload)
                except Exception:
                    return ST_ERR, b""
                metrics.incr(
                    "sidecar.items", len(items), labels={"op": opname}
                )
                ok = self.server.dispatcher.verify(items)
                with trace.leaf("sidecar.reply", opname, items=len(items)):
                    return ST_OK, bytes(bool(b) for b in ok)
            if op == OP_SIGN:
                try:
                    with decode:
                        pairs = decode_sign_request(payload)
                except Exception:
                    return ST_ERR, b""
                if any(h not in conn_keys for h, _m in pairs):
                    # Unknown handle: the canonical cause is a client
                    # that outlived a sidecar restart — it re-registers
                    # on its (new) connection and retries.
                    return ST_BAD_HANDLE, b""
                metrics.incr(
                    "sidecar.items", len(pairs), labels={"op": opname}
                )
                sigs = svc.sign.submit(
                    [(m, conn_keys[h]) for h, m in pairs]
                )
                with trace.leaf("sidecar.reply", opname, items=len(pairs)):
                    buf = io.BytesIO()
                    for sig in sigs:
                        write_chunk(buf, sig)
                    return ST_OK, buf.getvalue()
            # OP_MODEXP
            try:
                with decode:
                    items = decode_modexp_request(payload)
            except Exception:
                return ST_ERR, b""
            metrics.incr(
                "sidecar.items", len(items), labels={"op": opname}
            )
            vals = svc.modexp.submit(items)
            with trace.leaf("sidecar.reply", opname, items=len(items)):
                buf = io.BytesIO()
                for v in vals:
                    write_chunk(buf, _int_bytes(v))
                return ST_OK, buf.getvalue()
        except Exception:
            # Internal failure: the status byte IS the signal — the
            # tenant falls back to local crypto and opens its breaker.
            return ST_ERR, b""
        finally:
            svc.admission.release()


def _recvall(sock, n: int) -> bytes | None:
    buf = b""
    while len(buf) < n:
        part = sock.recv(n - len(buf))
        if not part:
            return None
        buf += part
    return buf


class _Server(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True


class _UnixServer(socketserver.ThreadingUnixStreamServer):
    daemon_threads = True


# -- stats endpoint (FleetCollector scrape surface) -------------------------


class _StatsHandler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *a):
        pass

    def _reply(self, code: int, body: bytes, ctype="application/json"):
        self.send_response(code)
        self.send_header("content-type", ctype)
        self.send_header("content-length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        import urllib.parse

        path = self.path
        try:
            if path == "/info":
                doc = {
                    "name": self.server.sidecar_name,
                    "role": "sidecar",
                    "sidecar": self.server.service.stats(),
                }
                self._reply(200, json.dumps(doc, sort_keys=True).encode())
            elif path == "/metrics" or path.startswith("/metrics?"):
                q = urllib.parse.parse_qs(
                    urllib.parse.urlparse(path).query
                )
                accept = self.headers.get("accept") or ""
                want_prom = q.get("format", [""])[0] == "prometheus" or (
                    "application/json" not in accept
                    and ("text/plain" in accept or "openmetrics" in accept)
                )
                if want_prom:
                    self._reply(
                        200,
                        metrics.prometheus().encode(),
                        "text/plain; version=0.0.4; charset=utf-8",
                    )
                else:
                    self._reply(
                        200,
                        json.dumps(
                            metrics.snapshot(), sort_keys=True
                        ).encode(),
                    )
            elif path == "/trace" or path.startswith("/trace?"):
                from bftkv_tpu import trace as trmod

                q = urllib.parse.parse_qs(
                    urllib.parse.urlparse(path).query
                )
                try:
                    since = int(q.get("since", ["0"])[0])
                except ValueError:
                    since = 0
                doc = trmod.tracer.export(max(0, since))
                doc["slow"] = trmod.tracer.slow()
                self._reply(
                    200,
                    json.dumps(doc, sort_keys=True, default=str).encode(),
                )
            elif path.startswith("/debug/profile"):
                # The daemon API's endpoint (cmd/bftkv.py), where it can
                # see a device: in a --sidecar deployment only this
                # process holds the chip.  Same helper, same
                # confinement of the output directory.
                from bftkv_tpu import ops

                outdir = ops.capture_profile(path)
                self._reply(
                    200,
                    f"trace captured to {outdir}\n".encode(),
                    "text/plain",
                )
            elif path == "/recalibrate":
                # Devtools hook (ISSUE 19 satellite): force a fresh
                # host/device calibration and re-apply it live.  GET for
                # curl convenience; the stats port is loopback/operator
                # surface, and the action is idempotent re-measurement.
                cal = self.server.service.recalibrate()
                self._reply(
                    200, json.dumps(cal, sort_keys=True, default=str).encode()
                )
            else:
                self._reply(404, b'"unknown endpoint"')
        except Exception as e:  # operator surface: never kill the sidecar
            self._reply(500, json.dumps(str(e)).encode())

    def do_POST(self):
        # Drain any body so keep-alive framing survives the reply.
        ln = int(self.headers.get("content-length") or 0)
        if ln:
            self.rfile.read(min(ln, 1 << 16))
        if self.path == "/recalibrate":
            return self.do_GET()
        self._reply(404, b'"unknown endpoint"')


def serve(
    listen: str,
    *,
    max_batch: int = 4096,
    max_wait: float | None = None,
    max_frame: int = 1 << 26,
    secret: bytes | None = None,
    stats: str = "",
    name: str = "sidecar01",
    admission: AdmissionQueue | None = None,
):
    """Start the sidecar; returns (server, thread) for embedding.

    ``listen`` is ``host:port`` or ``unix:/path/to.sock`` (socket file
    created mode 0600 — only this uid's processes can reach the
    service).  ``stats`` optionally serves /info + /metrics + /trace
    on an HTTP port for the fleet collector (``role=sidecar``).
    """
    # The service comes first: on a device backend constructing it
    # compiles every launchable program (SidecarService._warm), and no
    # socket exists until that is done — a tenant that dials early is
    # refused at once and runs its own host crypto, instead of hanging
    # on a listener that cannot answer yet.
    service = SidecarService(
        max_batch=max_batch, max_wait=max_wait, admission=admission
    )
    if listen.startswith("unix:"):
        path = listen[len("unix:"):]
        try:
            os.unlink(path)
        except OSError:
            pass
        # umask, not post-bind chmod: the socket must never be
        # world-connectable, even for the bind→chmod window (a peer
        # that connects in that window keeps its connection).
        old_umask = os.umask(0o177)
        try:
            srv = _UnixServer(path, _Handler)
        finally:
            os.umask(old_umask)
        os.chmod(path, 0o600)
    else:
        host, _, port = listen.rpartition(":")
        srv = _Server((host or "127.0.0.1", int(port)), _Handler)
    srv.service = service
    #: Back-compat alias: v1 handling and existing embedders address
    #: the verify dispatcher as ``srv.dispatcher``.
    srv.dispatcher = srv.service.verify
    srv.max_frame = max_frame
    srv.secret = secret
    # Sign keys may only arrive over a channel a local squatter cannot
    # impersonate: the 0600 unix socket, or HMAC-authenticated frames.
    srv.keys_ok = listen.startswith("unix:") or secret is not None
    srv.stats_httpd = None
    if stats:
        host, _, port = stats.rpartition(":")
        httpd = ThreadingHTTPServer(
            (host or "127.0.0.1", int(port)), _StatsHandler
        )
        httpd.daemon_threads = True
        httpd.service = srv.service
        httpd.sidecar_name = name
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        srv.stats_httpd = httpd
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    return srv, t


def load_secret(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read().strip()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description="shared crypto sidecar")
    ap.add_argument("--listen", default="127.0.0.1:7900",
                    help="host:port, or unix:/path/to.sock (recommended: "
                         "a TCP port can be squatted after a crash, and "
                         "sign-key registration needs unix or --secret-"
                         "file)")
    ap.add_argument("--max-batch", type=int, default=4096)
    ap.add_argument("--secret-file", default="",
                    help="file holding a shared secret; frames are then "
                         "HMAC-authenticated both ways (use for TCP)")
    ap.add_argument("--stats", default="",
                    help="host:port for the /info + /metrics + /trace "
                         "stats endpoint the fleet collector scrapes "
                         "(role=sidecar member)")
    ap.add_argument("--name", default="sidecar01",
                    help="member name reported on the stats /info")
    args = ap.parse_args(argv)
    secret = load_secret(args.secret_file) if args.secret_file else None
    # Warm-up progress (one line per compiled shape) goes to stdout
    # beside the "listening" line.
    h = logging.StreamHandler(sys.stdout)
    h.setFormatter(logging.Formatter("crypto-sidecar: %(message)s"))
    _log.addHandler(h)
    _log.setLevel(logging.INFO)
    srv, t = serve(
        args.listen,
        max_batch=args.max_batch,
        secret=secret,
        stats=args.stats,
        name=args.name,
    )
    print(
        f"crypto-sidecar: listening on {args.listen}"
        + (f", stats @ {args.stats}" if args.stats else ""),
        flush=True,
    )
    try:
        t.join()
    except KeyboardInterrupt:
        srv.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
