"""RTCG-generated WKV-6 recurrence kernel (the attention-free hot spot).

The paper's attention kernels are inapplicable to RWKV (DESIGN.md §4) —
so RTCG applies to its recurrence instead.  The XLA scan path writes the
(dh x dh) state and the k^T v outer product to HBM *every timestep*
(~17 GB/layer/pass at train_4k — the dominant roofline term for
rwkv6-7b).  This kernel keeps the state in VMEM scratch across the whole
sequence: grid = (B*H, T/chunk) with the time axis sequential, the
chunk body *unrolled at template-render time* (the paper's Fig. 5
unrolling, once more), HBM traffic = r/k/v/w reads + y writes only.

Recurrence per head (dh = head dim), all f32 in-register/VMEM:
    y_t = r_t (S + diag(u) k_t^T v_t)
    S   = diag(w_t) S + k_t^T v_t
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.platform import interpret_mode
from repro.core.templates import KernelTemplate

WKV_TMPL = KernelTemplate(
    "wkv6_kernel",
    '''
def {{ name }}(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    u = u_ref[0, :][:, None]                      # (dh, 1)
    S = s_ref[...]
{% for t in range(chunk) %}
    r_t = r_ref[0, {{ t }}, :][None, :].astype(jnp.float32)
    k_t = k_ref[0, {{ t }}, :][:, None].astype(jnp.float32)
    v_t = v_ref[0, {{ t }}, :][None, :].astype(jnp.float32)
    w_t = w_ref[0, {{ t }}, :][:, None]
    kv = k_t * v_t                                # (dh, dh)
    y = jnp.dot(r_t, S + u * kv, preferred_element_type=jnp.float32)
    o_ref[0, {{ t }}, :] = y[0].astype(o_ref.dtype)
    S = w_t * S + kv
{% endfor %}
    s_ref[...] = S
''',
)


@functools.lru_cache(maxsize=64)
def build_kernel(chunk: int):
    return WKV_TMPL.build(name="wkv6_kernel", chunk=chunk)


def pallas_wkv6(r, k, v, w, u, *, chunk: int = 16, interpret: bool | None = None):
    """r/k/v: (B, T, H, dh); w: (B, T, H, dh) decay in (0,1), f32;
    u: (H, dh) bonus, f32.  -> y (B, T, H, dh) f32."""
    if interpret is None:
        interpret = interpret_mode()
    B, T, H, dh = r.shape
    pt = -(-T // chunk) * chunk

    def flat(x, fill=0.0):
        x = jnp.moveaxis(x, 2, 1).reshape(B * H, T, dh)
        return jnp.pad(x, ((0, 0), (0, pt - T), (0, 0)),
                       constant_values=fill)

    rf, kf, vf = flat(r), flat(k), flat(v)
    wf = flat(w.astype(jnp.float32), fill=1.0)   # pad decay=1: state frozen
    kernel = build_kernel(chunk)

    blk = pl.BlockSpec((1, chunk, dh), lambda g, c: (g, c, 0))
    out = pl.pallas_call(
        kernel,
        grid=(B * H, pt // chunk),
        in_specs=[blk, blk, blk, blk,
                  pl.BlockSpec((1, dh), lambda g, c, H=H: (g % H, 0))],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((B * H, pt, dh), jnp.float32),
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ) if not interpret else None,
        interpret=interpret,
    )(rf, kf, vf, wf, u.astype(jnp.float32))
    return jnp.moveaxis(out[:, :T].reshape(B, H, T, dh), 1, 2)
