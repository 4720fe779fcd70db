"""Brute-force exact nearest-neighbor search — the paper's Table 4 workload.

§6.4 (entropy of natural scenes): for each target patch, find the exact
Euclidean nearest neighbor in an exponentially growing neighbor set; the
GPU port parallelizes the brute-force distance scan.

TPU formulation: d^2(t, n) = |t|^2 - 2 t.n + |n|^2, so the scan is a
tiled MXU matmul with a running (min, argmin) carried in VMEM scratch
across the sequential neighbor-block grid axis.  Targets are tiled over
the parallel axis.  Tunables: block_t x block_n ("block sizes" in the
paper's tuning space).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.platform import interpret_mode
from repro.core.templates import KernelTemplate

NN_TMPL = KernelTemplate(
    "nn_kernel",
    '''
def {{ name }}(t_ref, n_ref, od_ref, oi_ref, bd_ref, bi_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        bd_ref[...] = jnp.full_like(bd_ref, 3.0e38)
        bi_ref[...] = jnp.zeros_like(bi_ref)

    t = t_ref[...].astype(jnp.float32)
    n = n_ref[...].astype(jnp.float32)
    d2 = (jnp.sum(t * t, axis=1, keepdims=True)
          - 2.0 * jax.lax.dot_general(t, n, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32)
          + jnp.sum(n * n, axis=1, keepdims=True).T)
    col = j * {{ bn }} + jax.lax.broadcasted_iota(jnp.int32, ({{ bt }}, {{ bn }}), 1)
{% if mask_cols %}
    d2 = jnp.where(col < {{ n_total }}, d2, 3.0e38)
{% endif %}
    blk_min = jnp.min(d2, axis=1, keepdims=True)
    # first-match argmin, computed with 2D-only ops (TPU-friendly)
    blk_arg = jnp.min(jnp.where(d2 == blk_min, col, 2147483647),
                      axis=1, keepdims=True)
    better = blk_min < bd_ref[...][:, :1]
    bd_ref[...] = jnp.broadcast_to(
        jnp.where(better, blk_min, bd_ref[...][:, :1]), bd_ref.shape)
    bi_ref[...] = jnp.broadcast_to(
        jnp.where(better, blk_arg, bi_ref[...][:, :1]), bi_ref.shape)

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        od_ref[...] = bd_ref[...]
        oi_ref[...] = bi_ref[...]
''',
)


@functools.lru_cache(maxsize=256)
def build_kernel(bt: int, bn: int, mask_cols: bool, n_total: int):
    return NN_TMPL.build(name="nn_kernel", bt=bt, bn=bn,
                         mask_cols=mask_cols, n_total=n_total)


def pallas_nn_search(targets, neighbors, *, block_t: int = 128, block_n: int = 512,
                     interpret: bool | None = None):
    """targets: (T, D); neighbors: (N, D) -> (min_dist2 (T,), argmin (T,))."""
    if interpret is None:
        interpret = interpret_mode()
    T, D = targets.shape
    N, D2 = neighbors.shape
    assert D == D2
    pt = -(-T // block_t) * block_t
    pn = -(-N // block_n) * block_n
    tp = jnp.pad(targets, ((0, pt - T), (0, 0)))
    np_ = jnp.pad(neighbors, ((0, pn - N), (0, 0)))
    kernel = build_kernel(block_t, block_n, pn != N, N)
    lanes = 128
    od, oi = pl.pallas_call(
        kernel,
        grid=(pt // block_t, pn // block_n),
        in_specs=[
            pl.BlockSpec((block_t, D), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, D), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, lanes), lambda i, j: (i, 0)),
            pl.BlockSpec((block_t, lanes), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((pt, lanes), jnp.float32),
            jax.ShapeDtypeStruct((pt, lanes), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_t, lanes), jnp.float32),
            pltpu.VMEM((block_t, lanes), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ) if not interpret else None,
        interpret=interpret,
    )(tp, np_)
    return od[:T, 0], oi[:T, 0]
