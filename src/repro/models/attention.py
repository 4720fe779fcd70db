"""Attention: chunked flash-style jnp path (dry-run/XLA), naive path
(smoke oracle), Pallas path (TPU), and the KV-cache decode path.

The naive and decode paths normalize scores through
`layers.fused_softmax`: concrete (outside-jit) score matrices of any
batch shape ride the axis-aware fusion planner — ONE row-segmented
reduction wave + ONE fused 2-D epilogue for the whole ``(B·H·S, Skv)``
batch — while traced values fall back to ``jax.nn.softmax``.

The jnp flash path is the FLOP-equivalent stand-in the dry-run compiles
(Pallas does not lower on the CPU host backend — DESIGN.md §6).  Causal
scheduling is selectable:

  * masked_full      — scan all KV chunks, mask above the diagonal
                       (baseline; 2x causal FLOP waste)
  * prefix_unrolled  — python-unrolled loop over q chunks, each slicing
                       exactly its causal KV prefix (halves attention
                       FLOPs in the compiled HLO; §Perf hillclimb lever)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models.layers import fused_softmax

NEG_INF = -1e30


def _gqa_expand(k, H):
    """(B, S, Hk, dh) -> (B, S, H, dh) by group repeat (jnp path only)."""
    B, S, Hk, dh = k.shape
    if Hk == H:
        return k
    return jnp.repeat(k, H // Hk, axis=2)


def naive_attention(q, k, v, *, causal: bool, scale: float):
    """q: (B, S, H, dh); k/v: (B, Skv, Hk, dh). Full score matrix."""
    H = q.shape[2]
    k, v = _gqa_expand(k, H), _gqa_expand(v, H)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        Sq, Skv = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((Sq, Skv), bool), k=Skv - Sq)
        s = jnp.where(mask, s, NEG_INF)
    p = fused_softmax(s)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


def _flash_q_chunk(q, k, v, *, q_start, kv_chunk, causal, scale, kv_len=None):
    """Online-softmax over KV chunks for one q chunk.
    q: (B, qc, H, dh); k/v: (B, Skv, Hk, dh) [already GQA-expanded]."""
    B, qc, H, dh = q.shape
    Skv = k.shape[1]
    nk = Skv // kv_chunk
    kc = k.reshape(B, nk, kv_chunk, H, dh)
    vc = v.reshape(B, nk, kv_chunk, H, dh)

    def step(carry, inp):
        m, l, acc = carry
        kb, vb, j = inp
        # bf16 operands + f32 MXU accumulation — casting q/k to f32 first
        # would double the head all-gather bytes and fall off the MXU.
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kb,
                       preferred_element_type=jnp.float32) * scale
        col = j * kv_chunk + lax.broadcasted_iota(jnp.int32, (qc, kv_chunk), 1)
        row = q_start + lax.broadcasted_iota(jnp.int32, (qc, kv_chunk), 0)
        if causal:
            s = jnp.where((row >= col)[None, None], s, NEG_INF)
        if kv_len is not None:
            s = jnp.where((col < kv_len)[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    init = (jnp.full((B, H, qc), NEG_INF, jnp.float32),
            jnp.zeros((B, H, qc), jnp.float32),
            jnp.zeros((B, H, qc, dh), jnp.float32))
    # checkpoint each KV step: backward recomputes the (qc, kc) score block
    # instead of storing it — the flash-attention backward memory property.
    (m, l, acc), _ = lax.scan(
        jax.checkpoint(step), init,
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), jnp.arange(nk)))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l[..., None]).astype(q.dtype)           # (B, H, qc, dh)
    return jnp.moveaxis(out, 1, 2)                       # (B, qc, H, dh)


def flash_attention_jnp(q, k, v, *, causal: bool, scale: float,
                        q_chunk: int, kv_chunk: int,
                        schedule: str = "masked_full"):
    """q: (B, S, H, dh); k/v: (B, Skv, Hk, dh)."""
    B, S, H, dh = q.shape
    Skv = k.shape[1]
    k, v = _gqa_expand(k, H), _gqa_expand(v, H)
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, Skv)
    if S % q_chunk or Skv % kv_chunk:
        # fall back to one-chunk (padding handled by callers at step level)
        return naive_attention(q, k, v, causal=causal, scale=scale)
    nq = S // q_chunk

    if schedule == "prefix_unrolled" and causal and S == Skv:
        outs = []
        for i in range(nq):
            prefix = (i + 1) * q_chunk
            # round the causal prefix up to a kv_chunk multiple
            pref = -(-prefix // kv_chunk) * kv_chunk
            outs.append(_flash_q_chunk(
                q[:, i * q_chunk:(i + 1) * q_chunk], k[:, :pref], v[:, :pref],
                q_start=i * q_chunk, kv_chunk=kv_chunk, causal=True, scale=scale))
        return jnp.concatenate(outs, axis=1)

    qs = q.reshape(B, nq, q_chunk, H, dh)

    def per_chunk(i, q_blk):
        return _flash_q_chunk(q_blk, k, v, q_start=i * q_chunk,
                              kv_chunk=kv_chunk, causal=causal, scale=scale)

    out = lax.map(lambda args: per_chunk(*args),
                  (jnp.arange(nq), jnp.moveaxis(qs, 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, dh)


def decode_attention(q, k_cache, v_cache, pos, *, scale: float):
    """Single-token grouped-query decode. q: (B, 1, H, dh); caches:
    (B, Smax, Hk, dh) with Hk dividing H; pos: () or (B,) int32 —
    number of valid cache entries minus one is pos; positions <= pos
    attend.  Query head h reads KV head h // (H/Hk) from the cache in
    its own dtype: no upcast or head-repeated copy of the cache, the
    products accumulate in float32 and the probabilities stay float32.
    Its operations carry the ``decode_attention`` scope in the compiled
    program's metadata."""
    with jax.named_scope("decode_attention"):
        B, _, H, dh = q.shape
        Smax, Hk = k_cache.shape[1], k_cache.shape[2]
        qg = q.reshape(B, Hk, H // Hk, dh)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                       preferred_element_type=jnp.float32) * scale
        col = jnp.arange(Smax)
        valid = col[None, :] <= jnp.reshape(pos, (-1, 1))  # (B or 1, Smax)
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)  # (B, Hk, g, Smax)
        p = fused_softmax(s)
        out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache,
                         precision=lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
        return out.reshape(B, 1, H, dh).astype(q.dtype)


def kv_sharded_decode_attention(cfg: ModelConfig, ctx, q, k_cache, v_cache,
                                k_new, v_new, pos):
    """Flash-decoding: the KV cache's SEQUENCE dim is sharded over the
    model axis (used when kv_heads doesn't divide it — MQA/GQA).  Each
    model shard computes attention over its local KV range; the online
    softmax is combined with pmax/psum.  The single-token cache update is
    routed to the owning shard with a masked dynamic-update-slice.
    Collective cost per token: two psums of (B, H, dh)-sized partials —
    versus GSPMD's all-gather of the whole cache.

    q: (B, 1, H, dh); caches: (B, Smax, Hk, dh) seq-sharded; k_new/v_new:
    (B, 1, Hk, dh). -> (out (B,1,H,dh), new_k_cache, new_v_cache).
    Its operations carry the ``decode_attention`` scope."""
    with jax.named_scope("decode_attention"):
        return _kv_sharded_decode_attention(cfg, ctx, q, k_cache, v_cache,
                                            k_new, v_new, pos)


def _kv_sharded_decode_attention(cfg: ModelConfig, ctx, q, k_cache, v_cache,
                                 k_new, v_new, pos):
    from jax.sharding import PartitionSpec as P

    B = q.shape[0]
    scale = cfg.dh ** -0.5
    baxes = ctx.batch_axes
    shard_batch = baxes and B % ctx.data_shards == 0
    bdim = (baxes if len(baxes) > 1 else baxes[0]) if shard_batch else None
    qspec = P(bdim, None, None, None)
    cspec = P(bdim, "model", None, None)

    def body(q_l, k_l, v_l, kn, vn, pos_):
        j = lax.axis_index("model")
        S_loc = k_l.shape[1]
        # --- masked single-position update on the owning shard
        owns = (pos_ >= j * S_loc) & (pos_ < (j + 1) * S_loc)
        lpos = jnp.clip(pos_ - j * S_loc, 0, S_loc - 1)
        k_upd = lax.dynamic_update_slice(k_l, kn.astype(k_l.dtype), (0, lpos, 0, 0))
        v_upd = lax.dynamic_update_slice(v_l, vn.astype(v_l.dtype), (0, lpos, 0, 0))
        k_l = jnp.where(owns, k_upd, k_l)
        v_l = jnp.where(owns, v_upd, v_l)
        # --- local attention over this shard's KV range (local batch!)
        b, _, H, dh = q_l.shape
        qf = q_l.reshape(b, H, dh).astype(jnp.float32)
        kf = _gqa_expand(k_l.astype(jnp.float32), H)
        s = jnp.einsum("bhd,bshd->bhs", qf, kf) * scale
        col = j * S_loc + jnp.arange(S_loc)
        s = jnp.where((col[None, None, :] <= pos_), s, NEG_INF)
        m_loc = jnp.max(s, axis=-1)                       # (b, H)
        m = lax.pmax(m_loc, "model")
        p = jnp.exp(s - m[..., None])
        l_loc = jnp.sum(p, axis=-1)
        o_loc = jnp.einsum("bhs,bshd->bhd", p,
                           _gqa_expand(v_l.astype(jnp.float32), H))
        l = lax.psum(l_loc, "model")
        o = lax.psum(o_loc, "model") / jnp.maximum(l, 1e-30)[..., None]
        return o.reshape(b, 1, H, dh).astype(q_l.dtype), k_l, v_l

    out, k_cache, v_cache = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(qspec, cspec, cspec, qspec, qspec, P()),
        out_specs=(qspec, cspec, cspec),
        check_vma=False,
    )(q, k_cache, v_cache, k_new, v_new, pos)
    return out, k_cache, v_cache


def use_kv_sharded_decode(cfg: ModelConfig, ctx, seq_len: int) -> bool:
    if ctx.mesh is None or ctx.model_axis is None:
        return False
    msize = ctx.axis_size("model")
    return (cfg.num_kv_heads % msize != 0) and (seq_len % msize == 0)


def attention(cfg: ModelConfig, q, k, v, *, causal: bool):
    """Training/prefill dispatch. q: (B,S,H,dh); k/v: (B,Skv,Hk,dh)."""
    scale = cfg.dh ** -0.5
    impl = cfg.attention_impl
    if impl == "pallas":
        from repro.kernels.flash_attention.ops import flash_attention
        # kernel layout is (B, H, S, D)
        o = flash_attention(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                            jnp.moveaxis(v, 2, 1), causal=causal)
        return jnp.moveaxis(o, 1, 2)
    if impl == "naive":
        return naive_attention(q, k, v, causal=causal, scale=scale)
    return flash_attention_jnp(q, k, v, causal=causal, scale=scale,
                               q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk,
                               schedule=cfg.causal_schedule)
