"""Main-path generated kernels compiled for a described TPU v5e chip.

Interpret mode cannot see the chip compiler's refusals: block shapes off
the (8, 128) tiling, scalar stores to VMEM, primitives Mosaic cannot
lower, blocks over the scoped-VMEM limit.  These tests build each
driver with ``interpret=False`` and compile its jitted program
(``driver.call``) for one chip of a ``v5e:2x2`` topology that is
described, not attached — at the serving widths (vocab 92,544, dh 128,
S 2048).  Nothing runs; a refusal raises here.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker
imports this file.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import repro.core.array as ga
from repro import runtime
from repro.core import backends, dispatch
from repro.core.reduction import ReductionKernel
from repro.core.scan import ScanKernel

VOCAB = 92544


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for the described chip land in no persistent cache: they
    # could not be read back without one
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _chip_spec(kernel):
    return dataclasses.replace(kernel.spec, interpret=False)


def _compile(driver, shapes, sharding):
    assert driver.interpret is False
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return driver.call.lower(*args).compile()


def _row_shapes(spec, brows, ncols, ragged):
    shape = {"scalar": (1, 1), "full": (brows, ncols), "row": (brows, 1),
             "col": (1, ncols)}
    lead = [((brows, 1), jnp.int32)] if ragged else []
    return lead + [(shape[k], d) for _, d, k in spec.arg_meta]


def _compile_rows(kernel, b, n, sharding, ragged):
    """Compile ``kernel``'s row-layout driver at the geometry the family
    picks for a ``(b, n)`` operand."""
    spec = _chip_spec(kernel)
    br = dispatch.default_batch_block(b)
    brows, ncols = dispatch.bucket_batch(b, br), dispatch.bucket_cols(n)
    be = backends.get_backend("pallas")
    if isinstance(kernel, ReductionKernel):
        drv = be.reduction_rows_driver(spec, brows=brows, ncols=ncols,
                                       block_rows=br, ragged=ragged)
        lead = [((brows, 1) if ragged else (1, 1), jnp.int32)]
        shapes = lead + _row_shapes(spec, brows, ncols, False)
    else:
        drv = be.elementwise_rows_driver(spec, brows=brows, ncols=ncols,
                                         block_rows=br, ragged=ragged)
        shapes = _row_shapes(spec, brows, ncols, ragged)
    return _compile(drv, shapes, sharding)


def _softmax_kernels(b, axis=-1):
    shape = (VOCAB, b) if axis == 0 else (b, VOCAB)
    x = ga.RTCGArray(jnp.zeros(shape, jnp.float32))
    sched = ga.plan_many([ga.softmax(x, stable=True, axis=axis)],
                         backend="pallas")
    assert len(sched.steps) == 1 and len(sched.epilogues) == 1
    return sched.steps[0].kernel(), sched.epilogues[0].kernel()


@pytest.mark.parametrize("ragged", [False, True], ids=["dense", "ragged"])
@pytest.mark.parametrize("b", [1, 8, 32])
def test_softmax_wave_and_epilogue_compile(one_chip, b, ragged):
    wave, epilogue = _softmax_kernels(b)
    _compile_rows(wave, b, VOCAB, one_chip, ragged)
    _compile_rows(epilogue, b, VOCAB, one_chip, ragged)


def test_axis0_softmax_compiles(one_chip):
    """Column softmax over a (92544, 8) operand: the wave runs the
    transposed domain (8 rows of 92544), the epilogue the storage
    layout (92544 rows of 8 columns)."""
    wave, epilogue = _softmax_kernels(8, axis=0)
    _compile_rows(wave, 8, VOCAB, one_chip, ragged=False)
    _compile_rows(epilogue, VOCAB, 8, one_chip, ragged=False)


@pytest.mark.parametrize("b", [1, 8, 32])
def test_sampler_cdf_flush_compiles(one_chip, b):
    wave, epilogue = runtime._ragged_kernels("softmax.cdf")
    _compile_rows(wave, b, VOCAB, one_chip, ragged=True)
    compiled = _compile_rows(epilogue, b, VOCAB, one_chip, ragged=True)
    assert "tpu_custom_call" in compiled.as_text()


def test_flat_reduction_compiles(one_chip):
    n = 1 << 20
    k = ReductionKernel(np.float32, "0", "a+b", "x[i]*x[i]", "float *x")
    br = dispatch.default_block_rows(n)
    bucket = dispatch.bucket_rows(n, br)
    drv = backends.get_backend("pallas").reduction_driver(
        _chip_spec(k), bucket=bucket, block_rows=br)
    _compile(drv, [((1, 1), jnp.int32), ((bucket, 128), jnp.float32)],
             one_chip)


@pytest.mark.parametrize("scan_expr,exclusive",
                         [("a+b", False), ("a+b", True), ("max(a,b)", False)])
def test_scan_compiles(one_chip, scan_expr, exclusive):
    n = 1 << 20
    k = ScanKernel(np.float32, scan_expr, exclusive=exclusive)
    grid = dispatch.next_pow2(-(-n // k.block_n))
    drv = backends.get_backend("pallas").scan_driver(
        _chip_spec(k), grid=grid, block_n=k.block_n)
    _compile(drv, [((grid, 1, k.block_n), jnp.float32)], one_chip)


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention.flash_attention import \
        pallas_flash_attention

    S, dh = 2048, 128
    q = jax.ShapeDtypeStruct((1, 16, S, dh), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8, S, dh), jnp.bfloat16, sharding=one_chip)
    fn = jax.jit(lambda q, k, v: pallas_flash_attention(
        q, k, v, causal=True, interpret=False))
    fn.lower(q, kv, kv).compile()
