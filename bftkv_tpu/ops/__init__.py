"""bftkv_tpu.ops — batched TPU kernels for the crypto data plane.

The reference's hot loops (SURVEY.md §2 "hot crypto loops") are per-item
``math/big`` modexps and per-signature PGP verifies. Here they are
array programs: big integers are ``(batch, limbs)`` arrays of 16-bit
digits, and every sign/verify/combine is a batched, jit-compiled kernel.

Modules:
- ``limb``   — host-side codec between Python ints and limb arrays
- ``bigint`` — batched limb arithmetic: mul, Montgomery REDC, modexp
- ``ec``     — batched P-256 point arithmetic (Jacobian), scalar mult
- ``tally``  — vmapped quorum/graph boolean reductions
"""

import os as _os

from bftkv_tpu.ops import bigint, limb  # noqa: F401


#: The one place compiled programs are kept when the environment names
#: none: inside the checkout (git-ignored), so every process of a
#: deployment — and the next run on the same tree — shares it.  The
#: directory is part of JAX's cache key; it must never move.
COMPILE_CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__)
    ))),
    ".jax_cache",
)


def compile_cache_dir() -> str:
    """Where this process's compiled programs persist:
    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX
    reads that itself), else :data:`COMPILE_CACHE_DIR`."""
    return _os.environ.get("JAX_COMPILATION_CACHE_DIR") or COMPILE_CACHE_DIR


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache (idempotent).

    The RNS kernels compile in 12–23 s per bucket shape for a v5e; with
    the cache, sidecar restarts and repeat runs load them instead.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set nothing is touched;
    otherwise the cache lives in :data:`COMPILE_CACHE_DIR`.  Called
    lazily by every device entry point.
    """
    if _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    if jax.config.jax_compilation_cache_dir != COMPILE_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)


def capture_profile(path: str) -> str:
    """``/debug/profile?seconds=N&name=…`` — of the daemon API and of
    the sidecar's stats port: one jax profiler capture (TensorBoard /
    Perfetto / ``jax.profiler.ProfileData``) of the ``seconds`` asked
    for, at most 30.  Returns the directory it was written to, which is
    confined to ``<tmp>/bftkv-profile/<name>``: the port may be exposed
    beyond localhost, so a caller names a capture and never a path.

    Where the process bridges its own phase spans to the profiler (the
    sidecar: :func:`bftkv_tpu.trace.set_bridge`), Python's function
    tracer stays off: its events would outnumber, and out-cover, the
    spans that say what the host did while the device waited."""
    import re
    import tempfile
    import time
    import urllib.parse

    import jax

    from bftkv_tpu import trace

    q = urllib.parse.parse_qs(urllib.parse.urlparse(path).query)
    try:
        seconds = float(q.get("seconds", ["2"])[0])
    except ValueError:
        seconds = 2.0
    if not (seconds >= 0.0):  # also catches NaN
        seconds = 0.0
    seconds = min(seconds, 30.0)
    name = re.sub(r"[^A-Za-z0-9_.-]", "_", q.get("name", ["trace"])[0])[:64]
    # "", "." and ".." survive the character filter but escape (or
    # collapse into) the confinement root.
    if name in ("", ".", ".."):
        name = "trace"
    outdir = _os.path.join(tempfile.gettempdir(), "bftkv-profile", name)
    opts = jax.profiler.ProfileOptions()
    if trace.bridged():
        opts.python_tracer_level = 0
    jax.profiler.start_trace(outdir, profiler_options=opts)
    try:
        time.sleep(seconds)
    finally:
        jax.profiler.stop_trace()
    return outdir
