"""Cross-request batching dispatchers — the TPU crypto sidecar.

The reference runs every RSA operation one at a time inside each request
handler (crypto_pgp.go:485-500 called from server.go:207,300; DetachSign
at crypto_pgp.go:346-371).  On TPU that wastes the device: a single
RSA-2048 e=65537 verify is ~17 modmuls over 128 limbs — three orders of
magnitude below a v5e's appetite — and host ``pow`` holds the GIL, so
per-handler signing also serializes the whole server.  The dispatchers
turn per-request crypto calls from *concurrent* threads into shared
device launches:

- callers submit their item batches and block on a future;
- a collector thread flushes when ``max_batch`` items are pending or
  ``max_wait`` elapsed since the first pending item (latency floor for
  low load — SURVEY §7 hard part 2);
- one batched kernel launch serves every caller in the flush; results
  are scattered back to the futures;
- up to ``pipeline`` flushes run concurrently (default 2): batch N+1's
  host assembly and transfer overlap batch N's device round trip (the
  device stream serializes the kernels; the device would otherwise
  idle through every flush's host phases).

Two instances exist: the **verify** dispatcher (collective-signature
verification, ``VerifierDomain.verify_batch``) and the **sign**
dispatcher (collective-signature share issuance,
``SignerDomain.sign_batch`` — batched CRT modexp).  Both fall back to
host crypto below their crossover batch size.

Deployment stance: replicas are mutually distrusting, so a dispatcher
serves exactly one replica's trust domain (or an in-process cluster in
tests/benchmarks, where the host is one trust domain by construction).
Batch-occupancy and latency are exported through
:mod:`bftkv_tpu.metrics` as ``<name>.batch`` / ``<name>.wait``.
"""

from __future__ import annotations

import queue
import threading
import time

import numpy as np

from bftkv_tpu import trace
from bftkv_tpu.faults import failpoint as fp
from bftkv_tpu.metrics import registry as metrics
from bftkv_tpu import flags
from bftkv_tpu.devtools.lockwatch import named_lock

__all__ = [
    "VerifyDispatcher",
    "SignDispatcher",
    "ModexpDispatcher",
    "install",
    "uninstall",
    "get",
    "install_signer",
    "uninstall_signer",
    "get_signer",
    "install_modexp",
    "uninstall_modexp",
    "get_modexp",
    "modexp_work",
    "uninstall_all",
    "note_launch_rtt",
    "observed_launch_rtt",
    "forget_launch_rtt",
    "recalibrate",
]


#: Sentinel crossover meaning "the device never wins for this backend".
ALWAYS_HOST = 1 << 30

_CALIBRATION: dict | None = None
_calibration_lock = named_lock("dispatch.calibration")
_LAUNCH_RTT_EWMA: float | None = None


def note_launch_rtt(seconds: float) -> None:
    """Feed one observed launch round trip into the online-recalibration
    EWMA (α = 0.2) and the ``dispatch.launch_rtt`` gauge.

    The boot-time calibration probes a trivial jitted op; real flushes
    measure the thing itself.  :func:`recalibrate` prefers this series
    over a fresh probe, so a device whose launch cost drifts (or one
    that appears mid-run) re-prices the crossover from what launches
    actually cost."""
    global _LAUNCH_RTT_EWMA
    with _calibration_lock:
        prev = _LAUNCH_RTT_EWMA
        _LAUNCH_RTT_EWMA = (
            seconds if prev is None else 0.8 * prev + 0.2 * seconds
        )
        metrics.gauge("dispatch.launch_rtt", _LAUNCH_RTT_EWMA)


def observed_launch_rtt() -> float | None:
    with _calibration_lock:
        return _LAUNCH_RTT_EWMA


def forget_launch_rtt() -> None:
    """Drop the observed series (the sidecar's warm-up: round trips
    that included compilation are not what a launch costs)."""
    global _LAUNCH_RTT_EWMA
    with _calibration_lock:
        _LAUNCH_RTT_EWMA = None


def dispatch_rtt_probe(x):
    """The trivial device program :func:`calibration` times (named for
    the device trace, like every program the sidecar can launch)."""
    return x * 2 + 1


def calibration(force: bool = False) -> dict:
    """Measured host-verify cost vs device launch RTT, once per process.

    The host/device crossover used to be a hard-coded constant
    (``VerifierDomain.HOST_CROSSOVER = 192``), which is wrong in both
    directions: on a locally-attached accelerator the launch RTT is a
    few ms, so protocol-sized batches (~24 items at cluster_4) should
    engage the device but never reached the constant; on a CPU backend
    the XLA kernels are slower than host ``pow`` at EVERY batch size
    (the RNS kernels are MXU-shaped), so the constant let 16-writer
    bursts cross it and sink whole seconds into CPU-XLA flushes
    (BENCH_r05: 1,126 device signs on the CPU fallback).

    Measures (a) per-item host e=65537 verify cost via raw ``pow`` on a
    fixed 2048-bit modulus and (b) the device launch round trip via a
    trivial jitted op on device-resident operands — a lower bound on
    any real kernel launch.  ``crossover ≈ rtt / host_per_item`` is the
    batch size where one launch starts beating the host loop.  On a CPU
    "device" the kernels themselves lose to host ``pow`` regardless of
    batch, so the crossover pins to :data:`ALWAYS_HOST`.
    """
    global _CALIBRATION
    with _calibration_lock:
        if _CALIBRATION is not None and not force:
            return _CALIBRATION
        import jax

        backend = jax.default_backend()
        env = flags.raw("BFTKV_DISPATCH_CROSSOVER")
        if env is not None:
            # Operator override: outranks every measurement.  ≤ 0 pins
            # always-host; a positive value is the verify crossover
            # batch size (and un-pins the backend regardless of what a
            # probe would say — the operator knows their accelerator).
            x = int(env)
            pinned = x <= 0
            cal = {
                "backend": backend,
                "host_verify_s": None,
                "device_rtt_s": _LAUNCH_RTT_EWMA,
                "verify_crossover": ALWAYS_HOST if pinned else x,
                "sign_crossover": ALWAYS_HOST if pinned else None,
                "prefer_host": pinned,
                "source": "override",
            }
            metrics.gauge(
                "dispatch.crossover", -1 if pinned else x
            )
            _CALIBRATION = cal
            return cal
        # Host per-item cost: raw pow on a fixed odd 2048-bit modulus —
        # the dominant term of a host verify, no keygen required.
        n = (1 << 2047) + 973  # odd, full-width; exactness is irrelevant
        s = (1 << 2040) // 7
        t0 = time.perf_counter()
        reps = 12
        for _ in range(reps):
            pow(s, 65537, n)
        host_s = (time.perf_counter() - t0) / reps
        if backend == "cpu":
            cal = {
                "backend": backend,
                "host_verify_s": host_s,
                "device_rtt_s": None,
                "verify_crossover": ALWAYS_HOST,
                "sign_crossover": ALWAYS_HOST,
                "prefer_host": True,
                "source": "probe",
            }
        else:
            # Online recalibration: once real flushes have measured
            # their own round trips (note_launch_rtt), the EWMA of the
            # thing itself outranks the trivial-op probe — the probe is
            # a lower bound, the EWMA is the price actually paid.
            rtt = _LAUNCH_RTT_EWMA
            source = "observed"
            if rtt is None:
                import jax.numpy as jnp

                f = jax.jit(dispatch_rtt_probe)
                x = jax.device_put(jnp.zeros((256, 128), jnp.uint32))
                jax.block_until_ready(f(x))  # compile outside the timing
                t0 = time.perf_counter()
                for _ in range(3):
                    jax.block_until_ready(f(x))
                rtt = (time.perf_counter() - t0) / 3
                source = "probe"
            cal = {
                "backend": backend,
                "host_verify_s": host_s,
                "device_rtt_s": rtt,
                # Floor of 16 so a noisy fast-RTT measurement cannot
                # push tiny batches onto the device.
                "verify_crossover": max(16, int(rtt / max(host_s, 1e-7))),
                # Sign launches are far heavier than the probe op;
                # keep the signer's proven default on real devices.
                "sign_crossover": None,
                "prefer_host": False,
                "source": source,
            }
        metrics.gauge(
            "dispatch.crossover",
            -1 if cal["verify_crossover"] == ALWAYS_HOST
            else cal["verify_crossover"],
        )
        _CALIBRATION = cal
        return cal


class _Pending:
    __slots__ = ("items", "event", "result", "error", "flushed")

    def __init__(self, items):
        self.items = items
        self.event = threading.Event()
        self.result = None
        self.error: Exception | None = None
        #: ``perf_counter`` when the flush that carries this entry began
        #: (stamped by :meth:`_BatchDispatcher._flush`, on the flushing
        #: thread): the end of its ``dispatch.queue`` interval.
        self.flushed: float | None = None


class _BatchDispatcher:
    """Accumulates per-thread requests into shared device batches."""

    #: metrics prefix; subclasses override.
    name = "dispatch"

    #: What this pool serves: the ``op`` label of its phase spans and
    #: histograms (``dispatch.linger``, ``flush.*``; DESIGN.md §7).
    op = "verify"

    #: Flushes in flight at once (``BFTKV_DISPATCH_PIPELINE`` overrides).
    #: A flush is [host assembly | device round trip | scatter]; with a
    #: single stream the device idles through both host phases.
    #: Two in-flight flushes let batch N+1 assemble and transfer while
    #: batch N computes — jax dispatch is async and the device stream
    #: serializes the actual kernels, so on an accelerator this is pure
    #: overlap.  On CPU the "device" is the host: a second flush worker
    #: contends with the kernel for cores instead of filling idle
    #: device time (measured ~14% slower on the 16-replica batched
    #: bench), so the default resolves per backend at start().  1
    #: forces strict serial flushing.
    DEFAULT_PIPELINE_TPU = 2

    def __init__(
        self,
        *,
        max_batch: int = 1024,
        max_wait: float = 0.002,
        pipeline: int | None = None,
        calibrate: bool | None = None,
    ):

        self.max_batch = max_batch
        self.max_wait = max_wait
        if calibrate is None:
            calibrate = flags.raw("BFTKV_DISPATCH_CALIBRATE", "1") != "0"
        self._calibrate = calibrate
        #: True once install-time calibration decides the host beats a
        #: device launch at ANY batch this backend can see — call sites
        #: (``Signer.issue_many``, :meth:`VerifyDispatcher.verify`) then
        #: skip the collector wait + flush queue and run host inline.
        self._prefer_host = False
        if pipeline is None:
            env = flags.raw("BFTKV_DISPATCH_PIPELINE")
            pipeline = int(env) if env else None
        self.pipeline = max(1, pipeline) if pipeline is not None else None
        self._inflight: threading.BoundedSemaphore | None = None
        self._work: "queue.SimpleQueue[list[_Pending] | None]" | None = None
        self._workers: list[threading.Thread] = []
        #: Async mega-batch dispatch (``BFTKV_DISPATCH_ASYNC``): flushes
        #: whose subclass implements :meth:`_launch_batch` hand the
        #: device a non-blocking launch and return immediately; a single
        #: completion-drain thread finalizes launches FIFO and scatters
        #: results, so flush N+1's host assembly overlaps flush N's
        #: device execution.  ``off`` restores the fully synchronous
        #: flush (pre-r11 behavior, byte for byte).
        self._async = flags.enabled("BFTKV_DISPATCH_ASYNC")
        self._completions: "queue.SimpleQueue | None" = None
        self._async_slots: threading.BoundedSemaphore | None = None
        self._drain: threading.Thread | None = None
        self._lock = named_lock("dispatch.batcher")
        self._cv = threading.Condition(self._lock)
        self._queue: list[_Pending] = []
        self._queued_items = 0
        self._running = False
        self._thread: threading.Thread | None = None

    # -- subclass hooks ---------------------------------------------------

    def _run_batch(self, items: list):
        """One batched launch; returns a sequence aligned with items."""
        raise NotImplementedError

    def _launch_batch(self, items: list):
        """Non-blocking launch hook for the async path: stage ``items``
        into (persistent) device buffers, hand the kernel launch to the
        device WITHOUT blocking on its result, and return a zero-arg
        completion callable that blocks on the device and returns a
        sequence aligned with ``items``.  Return ``None`` to decline —
        the flush then takes the synchronous :meth:`_run_batch` path
        (the default: only subclasses with a genuinely async device
        tier opt in)."""
        return None

    def prefer_host(self, n_items: int) -> bool:
        """True when calibration proved these items end on host either
        way, so the caller should skip the dispatcher round trip."""
        return self._prefer_host

    def _combine(self, chunks: list):
        return np.concatenate(chunks)

    def _empty(self):
        return np.zeros((0,), dtype=bool)

    # -- lifecycle --------------------------------------------------------

    def start(self):
        if self.pipeline is None:
            # Deferred so constructing a dispatcher never forces jax
            # backend init; by start() the process has long since chosen.
            import jax

            self.pipeline = (
                self.DEFAULT_PIPELINE_TPU
                if jax.default_backend() == "tpu"
                else 1
            )
        with self._lock:
            if self._running:
                return self
            self._running = True
        if self.pipeline > 1 and not self._workers:
            # Persistent flush workers (no per-flush thread churn; a
            # thread-creation failure surfaces HERE, before any caller
            # has a future at stake).  The semaphore bounds batches
            # handed off but not yet flushed, so the collector stalls
            # — and submits keep coalescing — when the pipeline is full.
            self._inflight = threading.BoundedSemaphore(self.pipeline)
            self._work = queue.SimpleQueue()
            self._workers = [
                threading.Thread(
                    target=self._flush_worker,
                    args=(self._work, self._inflight),
                    daemon=True,
                )
                for _ in range(self.pipeline)
            ]
            for w in self._workers:
                w.start()
        if self._async and self._drain is None:
            # One drain thread regardless of pipeline width: completions
            # finalize FIFO, so async callers observe the same wake
            # ordering the synchronous path gave them.  The semaphore
            # bounds launches dispatched but not yet finalized —
            # assembly of the next flush overlaps the device, but a slow
            # device cannot accumulate unbounded staged batches.
            self._completions = queue.SimpleQueue()
            self._async_slots = threading.BoundedSemaphore(
                (self.pipeline or 1) + 1
            )
            self._drain = threading.Thread(
                target=self._completion_drain,
                args=(self._completions,),
                daemon=True,
            )
            self._drain.start()
        self._thread = threading.Thread(target=self._collector, daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        with self._cv:
            self._running = False
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        # Drain the worker pool: queued batches flush first (FIFO),
        # then each worker eats one sentinel and exits.  Joining the
        # workers IS the no-caller-left-waiting guarantee; a worker
        # wedged past the timeout (hung device call) is abandoned as a
        # daemon thread — its callers are hung on the device either way.
        if self._workers:
            for _ in self._workers:
                self._work.put(None)
            for w in self._workers:
                w.join(timeout=5)
            self._workers = []
            self._work = None
            self._inflight = None
        # Drain the completion thread LAST: the collector and every
        # flush worker are joined above, so all async launches are
        # already enqueued ahead of this sentinel (FIFO) — no caller's
        # completion can arrive after it.
        if self._drain is not None:
            self._completions.put(None)
            self._drain.join(timeout=5)
            self._drain = None
            self._completions = None
            self._async_slots = None

    def _flush_worker(self, work, inflight) -> None:
        # Queue + semaphore ride in as locals: a worker abandoned by a
        # timed-out stop() join must keep releasing the OLD semaphore,
        # never a successor pool's (instance attrs are re-created on
        # restart).
        while True:
            batch = work.get()
            if batch is None:
                return
            try:
                self._flush(batch)
            finally:
                inflight.release()

    # -- caller side ------------------------------------------------------

    def submit(self, items: list):
        """Blocking batched call; safe from any thread."""
        if not items:
            return self._empty()
        p = _Pending(items)
        t0 = time.perf_counter()
        with self._cv:
            # _running is checked under the lock: a stop() racing with an
            # unlocked check could let the collector exit after the check
            # but before the append, stranding this entry forever.
            running = self._running
            if running:
                self._queue.append(p)
                self._queued_items += len(items)
                self._cv.notify_all()
        if not running:
            return self._run_batch(items)
        if trace.capture() is not None:
            # Inside a request trace: the queue wait is the "dispatch"
            # phase of that request's wall-clock budget (DESIGN.md §18).
            # No active trace (background flushers, bench drivers) —
            # skip the span rather than minting orphan roots.
            with trace.span(
                "dispatch.wait",
                attrs={"items": len(items), "pool": self.name},
            ):
                p.event.wait()
        else:
            p.event.wait()
        metrics.observe(f"{self.name}.wait", time.perf_counter() - t0)
        if p.flushed is not None:
            # The queued part of ``.wait`` (linger, a pipeline permit,
            # batches ahead): one observation a submit, on this thread.
            # The interval ends on the flushing thread, so no ring span.
            metrics.observe(
                "dispatch.queue", p.flushed - t0, labels={"op": self.op}
            )
        if p.error is not None:
            raise p.error
        return p.result

    # -- collector --------------------------------------------------------

    def _collector(self) -> None:
        # Local refs for the same reason as _flush_worker: a collector
        # that outlives a timed-out stop() join must finish against the
        # pool it started with.
        inflight, work = self._inflight, self._work
        while True:
            with self._cv:
                while self._running and not self._queue:
                    self._cv.wait()
                if not self._running and not self._queue:
                    return
                # Wait for more work up to max_wait after the first
                # pending item, unless the batch target is already met.
                # ``dispatch.linger`` is that window, first pending
                # entry seen to batch popped (the wait on an EMPTY
                # queue above is no phase of any launch).
                with trace.leaf("dispatch.linger", self.op) as sp:
                    deadline = time.monotonic() + self.max_wait
                    while (
                        self._running
                        and self._queued_items < self.max_batch
                        and (remaining := deadline - time.monotonic()) > 0
                    ):
                        self._cv.wait(timeout=remaining)
                    # Bounded pop: whole pending entries up to
                    # ``max_batch`` items (always at least one).
                    # Draining the queue unboundedly would merge every
                    # queued caller's batch into one flush and make EACH
                    # wait for ALL — the head-of-line latency no
                    # chunking inside the flush can undo (results
                    # scatter only when the whole flush returns).  The
                    # remainder flushes on the next loop iteration, so a
                    # burst still coalesces into max_batch-sized
                    # launches.
                    batch = []
                    taken = 0
                    while self._queue and (
                        not batch
                        or taken + len(self._queue[0].items)
                        <= self.max_batch
                    ):
                        p = self._queue.pop(0)
                        batch.append(p)
                        taken += len(p.items)
                    self._queued_items -= taken
                    sp.attrs["items"] = taken
            if self.pipeline == 1:
                self._flush(batch)
            else:
                # Bounded hand-off: at most ``pipeline`` batches past
                # this point.  With the permit held, the collector
                # stalls (stops draining the queue) whenever the
                # pipeline is full, so submits keep coalescing into
                # bigger batches — the same backpressure the serial
                # collector had.
                inflight.acquire()
                if not self._running:
                    # stop() began while we waited for a permit; the
                    # sentinels may already be queued ahead of this
                    # batch.  Flush inline so these callers are served,
                    # not stranded behind a drained pool.
                    try:
                        self._flush(batch)
                    finally:
                        inflight.release()
                else:
                    work.put(batch)

    def _flush(self, batch: list[_Pending]) -> None:
        began = time.perf_counter()
        for p in batch:
            p.flushed = began
        if fp.ARMED:
            # ``dispatch.flush`` failpoint: a stalled device launch —
            # every caller blocked on this flush waits it out, which is
            # exactly what a wedged accelerator round trip looks like.
            act = fp.fire("dispatch.flush", name=self.name)
            if act is not None and act.kind == "stall":
                time.sleep(fp.delay_seconds(act))
        flat = [it for p in batch for it in p.items]
        occupancy = len(flat) / self.max_batch
        metrics.observe(f"{self.name}.batch", len(flat))
        metrics.incr(f"{self.name}.flushes")
        metrics.incr(f"{self.name}.items", len(flat))
        # Device-occupancy: items-per-LAUNCH vs the calibrated max batch.
        # Distinct from the flush span's ``occupancy`` when an oversized
        # flush chunks into several launches — each launch is then
        # near-full even though flat/max_batch > 1 (capacity plane reads
        # this gauge).
        launches = max(1, -(-len(flat) // self.max_batch))
        metrics.incr(f"{self.name}.launches", launches)
        metrics.gauge(
            f"{self.name}.device_occupancy",
            len(flat) / (launches * self.max_batch),
            labels={"width": "all"},
        )
        t0 = time.perf_counter()
        if (
            self._async
            and self._completions is not None
            and len(flat) <= self.max_batch
        ):
            # Async path: ask the subclass for a non-blocking launch.
            # Semaphore + completion queue ride in as locals for the
            # same abandoned-worker reason as _flush_worker.
            slots, completions = self._async_slots, self._completions
            slots.acquire()
            completion = None
            try:
                with trace.span(
                    f"{self.name}.launch",
                    attrs={"batch_size": len(flat)},
                    phase="dispatch",
                ):
                    completion = self._launch_batch(flat)
            except Exception as e:
                slots.release()
                for p in batch:
                    p.error = e
                    p.event.set()
                return
            if completion is not None:
                completions.put((batch, len(flat), completion, t0, slots))
                return
            slots.release()
        # Each flush is its own (root) trace: device batches are shared
        # across requests, so they cannot belong to any one request's
        # trace — the span carries the batch shape and, once the launch
        # returns, the measured items/s the batch actually achieved.
        with trace.span(
            f"{self.name}.flush",
            attrs={
                "batch_size": len(flat),
                "occupancy": round(occupancy, 4),
            },
            # Dynamic name: declare the phase explicitly (the
            # span-phase lint cannot resolve f-strings with no
            # leading literal against trace.SPAN_PHASES).
            phase="dispatch",
        ) as sp:
            try:
                if len(flat) <= self.max_batch:
                    out = self._run_batch(flat)
                else:
                    # A burst can out-run the collector and drain as one
                    # oversized queue; chunk the device launches so padded
                    # batch shapes stay bounded by max_batch.
                    out = self._combine(
                        [
                            self._run_batch(flat[i : i + self.max_batch])
                            for i in range(0, len(flat), self.max_batch)
                        ]
                    )
            except Exception as e:
                # Swallow, never raise: the error reaches every caller
                # through its future, and raising here would kill the
                # collector / flush-worker thread for good.
                sp.attrs["error"] = repr(e)
                for p in batch:
                    p.error = e
                    p.event.set()
                return
            dt = time.perf_counter() - t0
            metrics.observe(f"{self.name}.flush.seconds", dt)
            if dt > 0:
                throughput = len(flat) / dt
                sp.attrs["items_per_s"] = round(throughput, 1)
                metrics.gauge(f"{self.name}.throughput", throughput)
        self._scatter(batch, out)

    def _scatter(self, batch: list[_Pending], out) -> None:
        """Results to the callers' futures."""
        with trace.leaf("flush.scatter", self.op, items=len(out)):
            off = 0
            for p in batch:
                p.result = out[off : off + len(p.items)]
                off += len(p.items)
                p.event.set()

    def _completion_drain(self, completions) -> None:
        # Finalizes async launches strictly FIFO: block on the device
        # result, scatter to futures, feed the observed round trip into
        # online recalibration.  A completion that raises reaches its
        # callers through their futures — the drain thread, like the
        # flush workers, must never die to an item error.
        while True:
            entry = completions.get()
            if entry is None:
                return
            batch, n_items, completion, t0, slots = entry
            try:
                out = completion()
            except Exception as e:
                for p in batch:
                    p.error = e
                    p.event.set()
                continue
            finally:
                slots.release()
            dt = time.perf_counter() - t0
            metrics.observe(f"{self.name}.flush.seconds", dt)
            if dt > 0:
                metrics.gauge(f"{self.name}.throughput", n_items / dt)
            note_launch_rtt(dt)
            self._scatter(batch, out)


class VerifyDispatcher(_BatchDispatcher):
    """Batched signature verification (items: (message, sig, PublicKey))."""

    name = "dispatch"  # historical metric names kept stable

    def __init__(
        self,
        verifier=None,
        *,
        max_batch: int = 1024,
        max_wait: float = 0.002,
        pipeline: int | None = None,
        calibrate: bool | None = None,
    ):
        super().__init__(
            max_batch=max_batch,
            max_wait=max_wait,
            pipeline=pipeline,
            calibrate=calibrate,
        )
        if verifier is None:
            from bftkv_tpu.crypto import rsa as rsamod

            verifier = rsamod.VerifierDomain()
        self.verifier = verifier

    def start(self):
        super().start()
        if self._calibrate:
            self.apply_calibration(calibration())
        return self

    def apply_calibration(self, cal: dict) -> None:
        """(Re-)apply a calibration verdict — called at start() and by
        :func:`recalibrate` when online measurement moves the pin."""
        # An explicit env threshold is the operator's word and
        # outranks the measurement.
        if flags.raw("BFTKV_HOST_VERIFY_THRESHOLD") is None:
            self.verifier.host_threshold = cal["verify_crossover"]
        self._prefer_host = cal["prefer_host"]

    def _run_batch(self, items: list):
        return self.verifier.verify_batch(items)

    def verify(self, items: list) -> np.ndarray:
        if self._prefer_host:
            # Calibrated all-host backend: the flush would run the same
            # host loop anyway; inline skips max_wait + queueing.
            metrics.incr("dispatch.verifies", len(items))
            return self.verifier.verify_batch(items)
        out = self.submit(items)
        metrics.incr("dispatch.verifies", len(items))
        return out


class SignDispatcher(_BatchDispatcher):
    """Batched signing (items: (message, PrivateKey) — RSA or EC P-256).

    The server-side hot loop this absorbs is collective-signature share
    issuance — one private op per server per sign request
    (reference: crypto_pgp.go:346-371 via server.go:264) — which
    otherwise serializes the whole process behind the GIL.  A flush
    partitions by algorithm: RSA items ride one CRT-modexp launch; EC
    items group by key and ride one nonce base-mult launch per key
    (ADVICE r4 #3: EC used to bypass the dispatcher, so concurrent
    writers' EC batches never coalesced across threads).
    """

    name = "signdispatch"
    op = "sign"

    #: A sign launch costs ~115 ms regardless of batch, so waiting
    #: 20 ms to fill it is cheap: measured at 16 replicas, 2 ms flushes
    #: give batch-p50 ~17 and ~2 writes/s; 20 ms gives ~41 and ~15.
    DEFAULT_MAX_WAIT = 0.02

    def __init__(
        self,
        signer=None,
        *,
        max_batch: int = 1024,
        max_wait: float | None = None,
        pipeline: int | None = None,
        calibrate: bool | None = None,
    ):
        super().__init__(
            max_batch=max_batch,
            max_wait=self.DEFAULT_MAX_WAIT if max_wait is None else max_wait,
            pipeline=pipeline,
            calibrate=calibrate,
        )
        if signer is None:
            from bftkv_tpu.crypto import rsa as rsamod

            signer = rsamod.SignerDomain()
        self.signer = signer
        # The signer's proven built-in crossover, captured before any
        # calibration pin touches it: a later recalibration that
        # un-pins the backend (accelerator appeared) restores this
        # rather than leaving the boot-time ALWAYS_HOST in place.
        self._signer_default_threshold = getattr(
            signer, "host_threshold", None
        )

    def start(self):
        super().start()
        if self._calibrate:
            self.apply_calibration(calibration())
        return self

    def apply_calibration(self, cal: dict) -> None:
        self._prefer_host = cal["prefer_host"]
        if flags.raw("BFTKV_HOST_SIGN_THRESHOLD") is not None:
            return
        if cal["sign_crossover"] is not None:
            # CPU backend: any flush that still lands here (e.g. a
            # caller ignoring prefer_host) must host-sign rather
            # than sink seconds into a CPU-XLA modexp launch.
            self.signer.host_threshold = cal["sign_crossover"]
        elif self._signer_default_threshold is not None:
            # Backend (re-)engaged: the pin above may still be in place
            # from an earlier all-host verdict — restore the signer's
            # proven default crossover.
            self.signer.host_threshold = self._signer_default_threshold

    def _run_batch(self, items: list):
        from bftkv_tpu.crypto import cert as certmod

        ec_pos = [i for i, (_, k) in enumerate(items) if certmod.is_ec(k)]
        if not ec_pos:
            return self.signer.sign_batch(items)
        from bftkv_tpu.crypto import ecdsa as _ecdsa

        out: list = [None] * len(items)
        ec_set = set(ec_pos)
        rsa_pos = [i for i in range(len(items)) if i not in ec_set]
        if rsa_pos:
            for i, sig in zip(
                rsa_pos, self.signer.sign_batch([items[i] for i in rsa_pos])
            ):
                out[i] = sig
        # Group EC items by key object so each key's messages share one
        # nonce base-mult launch (ecdsa.sign_batch signs for one key).
        groups: dict[int, tuple] = {}
        for i in ec_pos:
            msg, key = items[i]
            groups.setdefault(id(key), (key, []))[1].append((i, msg))
        for key, pairs in groups.values():
            # EC entry point occupancy: one nonce base-mult launch per
            # key group; fill is this group's share of the batch cap.
            metrics.gauge(
                "signdispatch.device_occupancy",
                min(1.0, len(pairs) / self.max_batch),
                labels={"width": "ec"},
            )
            for (i, _), sig in zip(
                pairs, _ecdsa.sign_batch([m for _, m in pairs], key)
            ):
                out[i] = sig
        return out

    def _combine(self, chunks: list):
        return [sig for chunk in chunks for sig in chunk]

    def _empty(self):
        return []

    def sign(self, message: bytes, key) -> bytes:
        return self.submit([(message, key)])[0]


def modexp_work(n_bits: int, exp_bits: int) -> float:
    """What one modexp row costs, in RSA-2048 e = 65537 verify items
    (19 Montgomery products of 2,048 bits, what the calibrated
    crossover counts): five products a 4-bit window and the 19 of
    table and framing, each ``(n_bits / 2048)^2`` of a verify's.  A
    1,024-bit CRT-half row is ~17, a first-level threshold fragment
    (2,048-bit modulus, ~4,100-bit exponent) ~270."""
    return (5 * exp_bits / 4 + 19) / 19 * (n_bits / 2048) ** 2


def _row_class(e: int, m: int) -> tuple[int, int | None]:
    """``(n_bits, exp_bits)`` of the pow rows that hold ``x^e mod m``:
    the modulus's row width and the exponent's class at that width
    (None: past both, ``ops.rns.exp_class``)."""
    from bftkv_tpu.ops import rns as rns_ops

    n_bits = 16 * -(-m.bit_length() // 16)
    return n_bits, rns_ops.exp_class(n_bits, e.bit_length())


class ModexpDispatcher(_BatchDispatcher):
    """Batched raw modular exponentiation (items: (base, exp, mod) ints).

    The sidecar's third op class: tenants outsource arbitrary modexps
    (a replica daemon's threshold-fragment exponentiations, TPA and
    threshold-DSA rounds, protocol experiments) and spot-check the
    answers themselves — the service is untrusted by construction, so
    correctness never depends on it (DESIGN.md §17.3).  Odd moduli
    go through the Montgomery native kernel (GIL-releasing host tier);
    everything else falls back to ``pow``.  A flush is grouped by row
    class — (modulus width, exponent class), ``ops.rns.chains`` — and a
    group whose WORK (:func:`modexp_work`, in verify items) reaches
    ``device_threshold`` rides one RNS device launch — on an
    accelerator that is the shard_map fan-out path the sign dispatcher
    already uses.  An exponent past its modulus's classes (a
    threshold fragment of the second tree level and below) is the host
    tier's, counted by class (``modexp.host.class{bits}``).

    With ``remote`` (a ``RemoteModexpDomain``) this is a replica
    daemon's collector instead: the concurrent handlers' items leave
    as ONE request on the daemon's one-at-a-time channel, and the
    batch is made in the sidecar, across daemons
    (:func:`install_modexp`).
    """

    name = "modexpdispatch"
    op = "modexp"

    def __init__(
        self,
        *,
        max_batch: int = 1024,
        max_wait: float = 0.002,
        calibrate: bool | None = None,
        device_threshold: int | None = None,
        remote=None,
    ):
        # One flush at a time, on the collector's own thread: a pow
        # launch (a sequential scan of its windows) or a daemon's
        # request on its one-at-a-time channel is long against the
        # linger, and what is popped while one is out could only queue
        # behind it.  So nothing is popped meanwhile — the rows that
        # arrive during a flush leave together as the next one.
        super().__init__(
            max_batch=max_batch,
            max_wait=max_wait,
            pipeline=1,
            calibrate=calibrate,
        )
        # The signer's crossover semantics, counted in work: a group
        # whose rows cost less than this many verify items runs on the
        # native host tier.  ALWAYS_HOST on CPU backends (set by the
        # sidecar from calibration()).
        self.device_threshold = (
            device_threshold
            if device_threshold is not None
            else ALWAYS_HOST
        )
        #: Row classes whose pow programs are built — ``n`` for rows
        #: whose exponents are no wider than they, ``(n, e)`` for a
        #: longer exponent class; None: nobody said
        #: (``ops.rns.pow_rows_warm`` has the rule).
        self.warm_rows: frozenset | None = None
        self.remote = remote
        for name in ("modexp.device", "modexp.host"):
            metrics.incr(name, 0)  # a ratio has its denominator

    def apply_calibration(self, cal: dict) -> None:
        self._prefer_host = cal["prefer_host"]
        self.device_threshold = (
            ALWAYS_HOST if cal["prefer_host"] else cal["verify_crossover"]
        )

    def _width_groups(self, items: list, device_idx: list[int]) -> list:
        """``[(n_bits, exp_bits, idxs)]``: one launch each.  A class the
        chains cannot hold, or whose rows together cost less than the
        crossover, gets none: its items are the host tier's (as are
        those of a class whose program nobody built:
        :meth:`_launch_group`)."""
        from bftkv_tpu.ops import rns as rns_ops

        by_class: dict[tuple[int, int], list[int]] = {}
        for i in device_idx:
            cls = _row_class(*items[i][1:])
            if cls[1] is not None:
                by_class.setdefault(cls, []).append(i)
        groups = []
        for (n_bits, exp_bits), idxs in by_class.items():
            if (
                len(idxs) * modexp_work(n_bits, exp_bits)
                < self.device_threshold
                or not rns_ops.chains(n_bits, exp_bits).pow
            ):
                continue
            # a longer class: launches of one fused-chain tile at most
            # (the sidecar builds those buckets and no more, so a larger
            # group is several launches, never a fresh program)
            step = (
                len(idxs) if exp_bits == n_bits
                else rns_ops.long_exp_rows(n_bits)
            )
            groups += [
                (n_bits, exp_bits, idxs[o : o + step])
                for o in range(0, len(idxs), step)
            ]
        return groups

    def _note_device_group(self, n_bits: int, idxs: list[int]) -> None:
        metrics.incr("modexp.device", len(idxs))
        # beside ``modexp.host.class``: which row width the device took
        metrics.incr(
            "modexp.device.class", len(idxs), labels={"bits": str(n_bits)}
        )
        metrics.observe("modexp.device_batch", len(idxs))
        # Per-limb-width device occupancy: widths are the handful of
        # deployed modulus sizes, so the label stays bounded (capacity
        # plane joins on `width`).
        metrics.gauge(
            "modexpdispatch.device_occupancy",
            min(1.0, len(idxs) / self.max_batch),
            labels={"width": str(n_bits // 16)},
        )

    @staticmethod
    def _device_idx(items: list) -> list[int]:
        return [
            i
            for i, (b, e, m) in enumerate(items)
            if m > 2 and m % 2 == 1 and e >= 0 and 0 <= b
        ]

    def _launch_group(self, items: list, group: tuple):
        """One group's launch, dispatched and not waited for (None: no
        program was built for its class, counted, or the chain has no
        rows for a modulus)."""
        from bftkv_tpu.ops import rns as rns_ops

        n_bits, exp_bits, idxs = group
        if not rns_ops.pow_rows_warm(
            n_bits, self.warm_rows, len(idxs), exp_bits
        ):
            return None
        return rns_ops.power_mod_rns(
            [items[i][0] for i in idxs],
            [items[i][1] for i in idxs],
            [items[i][2] for i in idxs],
            n_bits=n_bits,
            exp_bits=exp_bits,
            defer=True,
        )

    def _run_batch(self, items: list) -> list[int]:
        """EVERY group's launch is dispatched before ANY is waited for
        (row classes ride the device stream back to back), then the
        native host tier answers whatever no launch did: a group under
        the crossover, an exponent past the classes, an even modulus, a
        launch that failed."""
        if self.remote is not None:
            return self.remote.powmod_batch(items)
        groups = []
        if self.device_threshold < ALWAYS_HOST:  # else: nothing to ask
            groups = self._width_groups(items, self._device_idx(items))
        launches = []
        for group in groups:
            try:
                launches.append(self._launch_group(items, group))
            except Exception:
                launches.append(None)  # incapable/hostile moduli: host
        out: list[int | None] = [None] * len(items)
        for (n_bits, _e, idxs), d in zip(groups, launches):
            try:
                vals = None if d is None else d.wait()
            except Exception:
                vals = None  # device failure: host fallback
            if vals is not None:
                self._note_device_group(n_bits, idxs)
                for i, v in zip(idxs, vals):
                    out[i] = int(v)
        self._host_fill(items, out)
        return out  # type: ignore[return-value]

    def _host_fill(self, items: list, out: list) -> None:
        """Host tier for every item the device didn't answer: one
        native batch (``rsa.powmod_host_many``)."""
        from bftkv_tpu.crypto import rsa as rsamod

        left = [i for i, v in enumerate(out) if v is None]
        if not left:
            return
        for i in left:
            _b, e, m = items[i]
            if m <= 0:
                raise ValueError("modexp: modulus must be positive")
            if _row_class(e, m)[1] is None:
                # past both exponent classes of its modulus (a
                # threshold fragment of the second tree level and
                # below): no chain holds it, counted by class
                metrics.incr(
                    "modexp.host.class",
                    labels={
                        "bits": str(1 << (e.bit_length() - 1).bit_length())
                    },
                )
        for i, v in zip(
            left, rsamod.powmod_host_many([items[i] for i in left])
        ):
            out[i] = v
        metrics.incr("modexp.host", len(left))

    def _combine(self, chunks: list):
        return [v for chunk in chunks for v in chunk]

    def _empty(self):
        return []

    def powmod(self, base: int, exp: int, mod: int) -> int:
        return self.submit([(base, exp, mod)])[0]


_global: VerifyDispatcher | None = None
_global_signer: SignDispatcher | None = None
_global_lock = named_lock("dispatch.install")


def install(dispatcher: VerifyDispatcher | None = None) -> VerifyDispatcher:
    """Install (and start) the process-wide verify dispatcher;
    verification call sites (``CollectiveSignature.verify``) route
    through it."""
    global _global
    with _global_lock:
        if _global is not None:
            _global.stop()
        _global = (dispatcher or VerifyDispatcher()).start()
        return _global


def uninstall() -> None:
    global _global
    with _global_lock:
        if _global is not None:
            _global.stop()
            _global = None


def get() -> VerifyDispatcher | None:
    return _global


def install_signer(dispatcher: SignDispatcher | None = None) -> SignDispatcher:
    """Install (and start) the process-wide sign dispatcher; signing
    call sites (``Signer.issue``) route through it."""
    global _global_signer
    with _global_lock:
        if _global_signer is not None:
            _global_signer.stop()
        _global_signer = (dispatcher or SignDispatcher()).start()
        return _global_signer


def uninstall_signer() -> None:
    global _global_signer
    with _global_lock:
        if _global_signer is not None:
            _global_signer.stop()
            _global_signer = None


def get_signer() -> SignDispatcher | None:
    return _global_signer


_global_modexp: ModexpDispatcher | None = None


def install_modexp(dispatcher: ModexpDispatcher) -> ModexpDispatcher:
    """Install (and start) the process-wide modexp domain: whoever
    calls ``ops.modexp.BatchModExp`` (threshold RSA and DSA, TPA) is
    asked for here first.  A replica daemon started with ``--sidecar``
    installs a collector over its ``RemoteModexpDomain``
    (``ModexpDispatcher(remote=...)``)."""
    global _global_modexp
    with _global_lock:
        if _global_modexp is not None:
            _global_modexp.stop()
        _global_modexp = dispatcher.start()
        return _global_modexp


def uninstall_modexp() -> None:
    global _global_modexp
    with _global_lock:
        if _global_modexp is not None:
            _global_modexp.stop()
            _global_modexp = None


def get_modexp() -> ModexpDispatcher | None:
    return _global_modexp


def recalibrate() -> dict:
    """Force a fresh calibration and re-apply it to the installed
    dispatchers.

    This is the piece the boot-time pin was missing: ``calibration``
    always supported ``force=True`` but nothing ever called it after
    process start, so an accelerator attached (or un-wedged) mid-run
    could never flip the ``ALWAYS_HOST`` verdict.  Exposed to operators
    through the sidecar's ``/recalibrate`` devtools hook and run
    periodically by the sidecar (``BFTKV_DISPATCH_RECAL_S``)."""
    cal = calibration(force=True)
    with _global_lock:
        for d in (_global, _global_signer):
            if d is not None and d._calibrate:
                d.apply_calibration(cal)
    return cal


def uninstall_all() -> None:
    uninstall()
    uninstall_signer()
    uninstall_modexp()
