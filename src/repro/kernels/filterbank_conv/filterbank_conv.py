"""3D filter-bank convolution — the paper's Table 1 auto-tuning workload.

The paper (§6.2, computational visual neuroscience) auto-tunes a 3D
filter-bank convolution over "unique combinations of loop unrolling
depth, register spilling, block/grid dimensions, thread work size,
shared memory padding" and observes a different winning configuration
per input shape and per device.

TPU adaptation (DESIGN.md §2): the CUDA shared-memory/texture staging
becomes VMEM residency; thread-block decomposition becomes output-row
tiling; *loop unrolling* of the (fh, fw) filter taps happens at template
render time — each tap becomes a statically-sliced MXU matmul
(bh*w_out, C) x (C, F) accumulated in f32.  Tunables mirror the paper's:

  * block_h      — output rows per grid step ("thread work size")
  * unroll_w     — fully unroll the fw tap loop vs keep a fori_loop
                   ("loop unrolling depth")

Input (H, W, C) and the filterbank (F, fh, fw, C) stay fully VMEM
resident (they fit for all Table-1 shapes); only the output is tiled.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.platform import interpret_mode
from repro.core.templates import KernelTemplate

FILTERBANK_TMPL = KernelTemplate(
    "fbconv_kernel",
    '''
def {{ name }}(x_ref, f_ref, o_ref):
    y0 = pl.program_id(0) * {{ bh }}
    acc = jnp.zeros(({{ bh }} * {{ w_out }}, {{ F }}), jnp.float32)
{% for dy in range(fh) %}
{% if unroll_w %}
{% for dx in range(fw) %}
    rows = x_ref[pl.ds(y0 + {{ dy }}, {{ bh }}), {{ dx }}:{{ dx + w_out }}, :]
    acc += jax.lax.dot_general(
        rows.reshape({{ bh * w_out }}, {{ C }}), f_ref[:, {{ dy }}, {{ dx }}, :],
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
{% endfor %}
{% else %}
    def _tap_{{ dy }}(dx, acc):
        rows = x_ref[pl.ds(y0 + {{ dy }}, {{ bh }}), pl.ds(dx, {{ w_out }}), :]
        return acc + jax.lax.dot_general(
            rows.reshape({{ bh * w_out }}, {{ C }}), f_ref[:, {{ dy }}, dx, :],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    acc = jax.lax.fori_loop(0, {{ fw }}, _tap_{{ dy }}, acc)
{% endif %}
{% endfor %}
    o_ref[...] = acc.reshape({{ bh }}, {{ w_out }}, {{ F }}).astype(o_ref.dtype)
''',
)


@functools.lru_cache(maxsize=256)
def build_kernel(bh: int, w_out: int, F: int, C: int, fh: int, fw: int, unroll_w: bool):
    return FILTERBANK_TMPL.build(name="fbconv_kernel", bh=bh, w_out=w_out,
                                 F=F, C=C, fh=fh, fw=fw, unroll_w=unroll_w)


def pallas_filterbank_conv(x, filters, *, block_h: int = 8, unroll_w: bool = True,
                           interpret: bool | None = None):
    """x: (H, W, C) input; filters: (F, fh, fw, C). 'valid' convolution
    (cross-correlation, as in the paper's workload) -> (H-fh+1, W-fw+1, F)."""
    if interpret is None:
        interpret = interpret_mode()
    H, W, C = x.shape
    F, fh, fw, C2 = filters.shape
    assert C == C2
    h_out, w_out = H - fh + 1, W - fw + 1
    gh = -(-h_out // block_h)
    # pad input rows so every output block has its full halo available
    pad_rows = gh * block_h + fh - 1 - H
    xp = jnp.pad(x, ((0, max(0, pad_rows)), (0, 0), (0, 0)))
    kernel = build_kernel(block_h, w_out, F, C, fh, fw, unroll_w)
    out = pl.pallas_call(
        kernel,
        grid=(gh,),
        in_specs=[
            pl.BlockSpec(xp.shape, lambda y: (0, 0, 0)),       # full input in VMEM
            pl.BlockSpec(filters.shape, lambda y: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_h, w_out, F), lambda y: (y, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((gh * block_h, w_out, F), x.dtype),
        interpret=interpret,
    )(xp, filters)
    return out[:h_out]


def flops(x_shape, f_shape) -> float:
    H, W, C = x_shape
    F, fh, fw, _ = f_shape
    return 2.0 * (H - fh + 1) * (W - fw + 1) * F * fh * fw * C
