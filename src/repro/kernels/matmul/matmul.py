"""RTCG-generated tiled MXU matmul kernel.

The kernel source is *rendered at run time* from a Jinja template
(paper §5.3 strategy 2) specialized on block shape and epilogue — the
epilogue (bias add / activation) is hardcoded into the generated source
instead of being a runtime branch, which is exactly the paper's
"cost of flexibility" argument (§4.2).

Loop slicing (paper §2) on TPU: grid = (M/bm, N/bn, K/bk); the K axis is
innermost and sequential ("arbitrary" dimension semantics) so a VMEM
scratch accumulator carries partial sums; M/N axes are parallel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.platform import interpret_mode
from repro.core.templates import KernelTemplate

MATMUL_TMPL = KernelTemplate(
    "matmul_kernel",
    '''
def {{ name }}(x_ref, y_ref, {% if bias %}b_ref, {% endif %}o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], y_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        acc = acc_ref[...]
{% if bias %}
        acc = acc + b_ref[...].astype(jnp.float32)
{% endif %}
{% if activation == "relu" %}
        acc = jnp.maximum(acc, 0.0)
{% elif activation == "gelu" %}
        acc = jax.nn.gelu(acc)
{% elif activation == "silu" %}
        acc = acc * jax.nn.sigmoid(acc)
{% elif activation %}
        acc = {{ activation }}(acc)
{% endif %}
        o_ref[...] = acc.astype(o_ref.dtype)
''',
)


def render(block_m: int, block_n: int, block_k: int, activation: str | None = None,
           bias: bool = False, name: str = "matmul_kernel") -> str:
    return MATMUL_TMPL.render(name=name, activation=activation, bias=bias,
                              bm=block_m, bn=block_n, bk=block_k)


@functools.lru_cache(maxsize=512)
def build_kernel(block_m: int, block_n: int, block_k: int,
                 activation: str | None = None, bias: bool = False):
    """Render + load the kernel body (content-cached by parameters)."""
    fn = MATMUL_TMPL.build(name="matmul_kernel", activation=activation, bias=bias,
                           bm=block_m, bn=block_n, bk=block_k)
    return fn


def pallas_matmul(x, y, bias_arr=None, *, block_m: int = 128, block_n: int = 128,
                  block_k: int = 128, activation: str | None = None,
                  out_dtype=None, interpret: bool | None = None):
    """Tiled matmul: (M,K) @ (K,N) [+ bias (N,)] with fused epilogue.

    Pads every dim up to its block multiple, runs the generated kernel,
    slices the result back.
    """
    if interpret is None:
        interpret = interpret_mode()
    M, K = x.shape
    K2, N = y.shape
    assert K == K2, (x.shape, y.shape)
    out_dtype = out_dtype or x.dtype

    pm = -(-M // block_m) * block_m
    pn = -(-N // block_n) * block_n
    pk = -(-K // block_k) * block_k
    xp = jnp.pad(x, ((0, pm - M), (0, pk - K)))
    yp = jnp.pad(y, ((0, pk - K), (0, pn - N)))
    kernel = build_kernel(block_m, block_n, block_k, activation, bias_arr is not None)

    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
        pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
    ]
    inputs = [xp, yp]
    if bias_arr is not None:
        bp = jnp.pad(bias_arr, (0, pn - N)).reshape(1, pn)
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)))
        inputs.append(bp)

    scratch = [pltpu.VMEM((block_m, block_n), jnp.float32)]
    out = pl.pallas_call(
        kernel,
        grid=(pm // block_m, pn // block_n, pk // block_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((pm, pn), out_dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ) if not interpret else None,
        interpret=interpret,
    )(*inputs)
    return out[:M, :N]
