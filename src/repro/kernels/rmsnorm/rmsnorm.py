"""Fused RMSNorm Pallas kernel (row-blocked, optional fused residual add).

A one-pass fused normalize+scale that would otherwise be 4 HBM round
trips (square, mean, rsqrt-mul, weight-mul) — the ElementwiseKernel
argument (paper §5.2) applied to a row-wise reduction pattern.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.platform import interpret_mode
from repro.core.templates import KernelTemplate

RMSNORM_TMPL = KernelTemplate(
    "rmsnorm_kernel",
    '''
def {{ name }}(x_ref, w_ref, {% if residual %}r_ref, {% endif %}o_ref):
    x = x_ref[...].astype(jnp.float32)
{% if residual %}
    x = x + r_ref[...].astype(jnp.float32)
{% endif %}
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + {{ eps }})
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)
''',
)


@functools.lru_cache(maxsize=64)
def build_kernel(eps: float, residual: bool):
    return RMSNORM_TMPL.build(name="rmsnorm_kernel", eps=eps, residual=residual)


def pallas_rmsnorm(x, w, residual=None, *, eps: float = 1e-6,
                   block_rows: int = 128, interpret: bool | None = None):
    """x: (..., D) row-normalized; w: (D,). Optional fused residual add."""
    if interpret is None:
        interpret = interpret_mode()
    orig_shape = x.shape
    D = orig_shape[-1]
    R = int(x.size // D)
    x2 = x.reshape(R, D)
    pr = -(-R // block_rows) * block_rows
    xp = jnp.pad(x2, ((0, pr - R), (0, 0)))
    wp = w.reshape(1, D)
    inputs = [xp, wp]
    in_specs = [
        pl.BlockSpec((block_rows, D), lambda r: (r, 0)),
        pl.BlockSpec((1, D), lambda r: (0, 0)),
    ]
    if residual is not None:
        rp = jnp.pad(residual.reshape(R, D), ((0, pr - R), (0, 0)))
        inputs.append(rp)
        in_specs.append(pl.BlockSpec((block_rows, D), lambda r: (r, 0)))
    kernel = build_kernel(eps, residual is not None)
    out = pl.pallas_call(
        kernel,
        grid=(pr // block_rows,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, D), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((pr, D), x.dtype),
        interpret=interpret,
    )(*inputs)
    return out[:R].reshape(orig_shape)
