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
