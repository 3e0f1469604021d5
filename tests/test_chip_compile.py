"""Ask the chip's compiler before the chip.

Every program ``chip_smoke.py`` launches, at the shape it launches it,
handed to the TPU compiler for a *described* v5e — no chip attached
(on-chip-measurement guide §2, rehearsal 3).  What Mosaic or XLA:TPU
refuses here (a misaligned slice, too much VMEM, a program that cannot
be partitioned) would otherwise cost a chip run to find.  Nothing
executes: a compile that passes is not a chip run.

One batch size per kernel, not a sweep: the XLA chains take ~10 s
each, the fused Pallas chains 20-30 s.

The topology is described inside a module-scoped fixture — never at
import — so that every xdist worker collects the same tests and only
the worker that runs this file loads libtpu.
"""

import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from bftkv_tpu.ops import pallas_rns, rns


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    # A TPU executable compiled without a chip is written to the
    # persistent cache but cannot be read back: keep these out of it.
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _key_shapes(ctx, place, kpad: int = 64):
    """Shapes of ``rns.stack_key_rows`` for ``kpad`` unique moduli."""
    k = ctx.k
    return tuple(
        place((kpad, w)) for w in (2 * k, 1, k, 2 * k, 2 * k, 1)
    )


def _operands(kind: str, rows: int, place, place_t, place_key):
    """ShapeDtypeStructs for one flush of ``rows`` rows: what
    ``rsa._verify_rns`` / ``rns.power_mod_rns`` hand the jitted chain."""
    if kind == "verify":
        return (
            place((rows, 2 * rns.DIGITS), jnp.uint8),
            place((rows, 2 * rns.DIGITS), jnp.uint8),
            place((rows,), jnp.int32),
            _key_shapes(rns.context(), place_key),
        )
    # CRT halves: RSA-2048's are 64 digits with 1024-bit exponents (256
    # nibbles), RSA-3072's 96 digits with 1536-bit ones (384).  A
    # first-level threshold fragment of a 2,048-bit CA key: a whole
    # modulus (128 digits) under the longer exponent class (4,160 bits:
    # 1,040 nibbles).
    digits = {"pow": 64, "pow1536": 96, "fragment": 128}[kind]
    windows = (
        rns.long_exp_bits(16 * digits) // 4 if kind == "fragment"
        else 4 * digits
    )
    return (
        place((rows, 2 * digits), jnp.uint8),
        place_t((windows, rows), jnp.uint8),
        place((rows,), jnp.int32),
        _key_shapes(rns.context(digits, 16 * digits), place_key),
    )


def _on(sharding):
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding
    )


def _compiled_ok(compiled, *, kernel: bool = False) -> None:
    ma = compiled.memory_analysis()
    # One v5e chip holds 16 GB; these programs are nowhere near it.
    assert ma.temp_size_in_bytes + ma.argument_size_in_bytes < 2 << 30
    if kernel:
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "kind,rows",
    [
        ("verify", 256),   # the floor bucket: any flush of 16..256 items
        ("verify", 4096),  # the sidecar's max_batch, warm-up's largest
        ("pow", 512),      # 256 share signs = 512 CRT-half rows
        ("pow", 2048),     # four servers' 256-sign batches coalesced
        ("pow1536", 2048),  # the same flush on RSA-3072 identities
        ("fragment", 64),   # one caller's certificate over ten servers
        ("fragment", 256),  # sixteen callers': the class's largest bucket
    ],
)
def test_xla_chain_compiles_for_one_chip(topo, kind, rows):
    one = _on(SingleDeviceSharding(topo.devices[0]))
    fn = {  # the pow chains donated, as on a device
        "verify": rns._jitted_verify_gather,
        "pow": lambda: rns._jitted_pow(64, 1024, True),
        "pow1536": lambda: rns._jitted_pow(96, 1536, True),
        "fragment": lambda: rns._jitted_pow(128, 2048, True, 4160),
    }[kind]()
    with warnings.catch_warnings():
        # ``_jitted_pow`` donates uint8 operands that no f32 output
        # can alias; JAX says so once per lowering.
        warnings.filterwarnings("ignore", message="Some donated buffers")
        compiled = fn.lower(*_operands(kind, rows, one, one, one)).compile()
    _compiled_ok(compiled)


def test_pallas_verify_chain_compiles_at_its_tile(topo):
    one = _on(SingleDeviceSharding(topo.devices[0]))
    tile = pallas_rns.TILE_VERIFY
    pc = pallas_rns._pad_consts(rns.DIGITS, 2048)
    rows = 1024
    row = lambda w: one((rows, w))
    run = pallas_rns._verify_call(rns.DIGITS, 2048, tile, False)
    compiled = run.lower(
        row(2 * rns.DIGITS), row(2 * rns.DIGITS),
        row(pc.kpad), row(pc.kpad), row(1), row(pc.kpad),
        row(pc.kpad), row(pc.kpad),
        row(pc.kpad), row(pc.kpad), row(1),
    ).compile()
    _compiled_ok(compiled, kernel=True)


def test_pallas_pow_chain_compiles_at_its_tile(topo):
    one = _on(SingleDeviceSharding(topo.devices[0]))
    tile = pallas_rns.TILE_POW
    pc = pallas_rns._pad_consts(64, 1024)  # the RSA-2048 CRT context
    rows = 512
    row = lambda w: one((rows, w))
    run = pallas_rns._pow_call(64, 1024, tile, False)
    compiled = run.lower(
        row(128), one((256, rows)),
        row(pc.kpad), row(pc.kpad), row(1), row(pc.kpad),
        row(pc.kpad), row(pc.kpad), row(1),
    ).compile()
    _compiled_ok(compiled, kernel=True)


@pytest.mark.parametrize("rows", [64, 128])
def test_fused_fragment_chain_compiles_at_both_buckets(topo, rows):
    """What one chip launches for a first-level threshold fragment:
    the fused chain at 2,048-bit rows and 1,040 windows, one tile a
    bucket (128 rows is the most the scoped VMEM holds at kpad 256)."""
    one = _on(SingleDeviceSharding(topo.devices[0]))
    windows = rns.long_exp_bits(2048) // 4
    fn = pallas_rns.jitted_pow(
        128, 2048, windows, rows, rns._pow_name(2048, 4 * windows)
    )
    compiled = fn.lower(*_operands("fragment", rows, one, one, one)).compile()
    _compiled_ok(compiled, kernel=True)
    assert pallas_rns._pow_tile(256) == 128


def test_fused_wide_fragment_chain_compiles_at_its_bucket(topo):
    """What one chip launches for a first-level fragment of an RSA-4096
    CA key: the wide chain (13-bit channels, kpad 384) fused at 4,096-bit
    rows and 2,064 windows, its one bucket of 64 rows — one tile."""
    one = _on(SingleDeviceSharding(topo.devices[0]))
    rows, digits = 64, 256
    windows = rns.long_exp_bits(4096) // 4
    assert (rns.long_exp_rows(4096), windows) == (rows, 2064)
    fn = pallas_rns.jitted_pow(
        digits, 4096, windows, rows, rns._pow_name(4096, 4 * windows)
    )
    compiled = fn.lower(
        one((rows, 2 * digits), jnp.uint8),
        one((windows, rows), jnp.uint8),
        one((rows,), jnp.int32),
        _key_shapes(rns.pow_context(4096), one),
    ).compile()
    _compiled_ok(compiled, kernel=True)
    assert pallas_rns._pow_tile(384) == rows


@pytest.mark.parametrize(
    "kind,rows", [("verify", 4096), ("pow", 2048)]
)
def test_sharded_chain_compiles_for_four_chips(topo, monkeypatch, kind, rows):
    """``chip_smoke.py --chips 4``: the flush a multi-device sidecar
    launches, on a ``batch`` mesh over the four described chips.  The
    production builders read the mesh from ``jax.devices()`` (CPU
    here), so the test hands them the described one."""
    mesh = Mesh(np.array(topo.devices), ("batch",))
    assert mesh.devices.size == 4
    builder = (
        rns._jitted_verify_gather_sharded if kind == "verify"
        else rns._jitted_pow_sharded
    )
    monkeypatch.setattr(rns, "_mesh", lambda: mesh)
    builder.cache_clear()
    try:
        fn = builder() if kind == "verify" else builder(64, 1024)
        compiled = fn.lower(*_operands(
            kind, rows,
            _on(NamedSharding(mesh, P("batch"))),
            _on(NamedSharding(mesh, P(None, "batch"))),
            _on(NamedSharding(mesh, P())),
        )).compile()
    finally:
        builder.cache_clear()  # never leave a TPU-mesh program cached
    _compiled_ok(compiled)
    # Data-parallel over the batch: each chip gets a quarter, and
    # nothing crosses chips.
    (out,) = jax.tree_util.tree_leaves(compiled.output_shardings)
    assert out.shard_shape((rows, 1))[0] == rows // 4
    text = compiled.as_text()
    assert "all-reduce" not in text and "all-gather" not in text
