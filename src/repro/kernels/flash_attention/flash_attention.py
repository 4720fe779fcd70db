"""RTCG-generated flash attention (online-softmax) Pallas kernel.

TPU adaptation of the memory-bound attention hot spot: instead of CUDA
shared-memory staging, Q/K/V tiles are BlockSpec'd into VMEM; the KV
axis is the sequential innermost grid dimension carrying running
(max, denominator, accumulator) in VMEM scratch — the canonical TPU
flash-attention decomposition.

RTCG knobs baked into the *generated source* (paper §4.2 specialization):
  * block_q, block_kv     — loop slicing, autotunable
  * causal                — mask arithmetic only emitted when needed
  * skip_masked_blocks    — emit a pl.when guard that skips fully-masked
                            KV blocks (halves causal FLOPs); this is one
                            of the §Perf hillclimb levers
  * kv_len masking        — only emitted when the sequence needed padding
  * GQA                   — the kv head index map is computed host-side

Supports GQA via the K/V BlockSpec index map (q-head -> kv-head group),
so KV tiles are fetched once per group, never materialized per q-head.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.platform import interpret_mode
from repro.core.templates import KernelTemplate

NEG_INF = -1e30

FLASH_TMPL = KernelTemplate(
    "flash_kernel",
    '''
def {{ name }}(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, {{ neg_inf }})
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0, :, :]
        k = k_ref[0, :, :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * {{ scale }}
{% if causal or mask_cols %}
        col = j * {{ bkv }} + jax.lax.broadcasted_iota(jnp.int32, ({{ bq }}, {{ bkv }}), 1)
{% endif %}
{% if causal %}
        row = i * {{ bq }} + jax.lax.broadcasted_iota(jnp.int32, ({{ bq }}, {{ bkv }}), 0)
        s = jnp.where(row >= col, s, {{ neg_inf }})
{% endif %}
{% if mask_cols %}
        s = jnp.where(col < {{ kv_len }}, s, {{ neg_inf }})
{% endif %}
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, :1])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_cur
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, :, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

{% if causal and skip_masked_blocks %}
    # skip KV blocks strictly above the diagonal (no valid q >= k pair)
    pl.when(j * {{ bkv }} <= i * {{ bq }} + {{ bq }} - 1)(_compute)
{% else %}
    _compute()
{% endif %}

    @pl.when(j == pl.num_programs(2) - 1)
    def _done():
        l = l_ref[...][:, :1]
        l = jnp.where(l == 0.0, 1.0, l)   # fully-masked (padded) rows
        o_ref[0, :, :] = (acc_ref[...] / l).astype(o_ref.dtype)
''',
)


@functools.lru_cache(maxsize=512)
def build_kernel(bq: int, bkv: int, scale: float, causal: bool,
                 skip_masked_blocks: bool, mask_cols: bool, kv_len: int):
    return FLASH_TMPL.build(
        name="flash_kernel", bq=bq, bkv=bkv, scale=scale, causal=causal,
        skip_masked_blocks=skip_masked_blocks, mask_cols=mask_cols,
        kv_len=kv_len, neg_inf=NEG_INF)


def pallas_flash_attention(q, k, v, *, causal: bool = True,
                           block_q: int = 128, block_kv: int = 128,
                           scale: float | None = None,
                           skip_masked_blocks: bool = True,
                           interpret: bool | None = None):
    """q: (B, H, Sq, D); k, v: (B, Hk, Skv, D) with H % Hk == 0 (GQA)."""
    if interpret is None:
        interpret = interpret_mode()
    B, H, Sq, D = q.shape
    _, Hk, Skv, _ = k.shape
    assert H % Hk == 0, (H, Hk)
    group = H // Hk
    scale = (D ** -0.5) if scale is None else scale

    pq = -(-Sq // block_q) * block_q
    pk = -(-Skv // block_kv) * block_kv
    qf = jnp.pad(q, ((0, 0), (0, 0), (0, pq - Sq), (0, 0))).reshape(B * H, pq, D)
    kf = jnp.pad(k, ((0, 0), (0, 0), (0, pk - Skv), (0, 0))).reshape(B * Hk, pk, D)
    vf = jnp.pad(v, ((0, 0), (0, 0), (0, pk - Skv), (0, 0))).reshape(B * Hk, pk, D)

    kernel = build_kernel(block_q, block_kv, scale, causal,
                          skip_masked_blocks, pk != Skv, Skv)

    def kv_index(g, i, j):
        return ((g // H) * Hk + (g % H) // group, j, 0)

    grid = (B * H, pq // block_q, pk // block_kv)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_kv, D), kv_index),
            pl.BlockSpec((1, block_kv, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, pq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ) if not interpret else None,
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, pq, D)[:, :, :Sq, :]
