"""Decoder(-encoder) stack: scan-over-periods forward, training loss,
prefill, and KV/state-cache decode for every assigned architecture.

One compiled layer body per slot regardless of depth (`lax.scan` over
stacked per-period parameters); hybrid archs (jamba) unroll their
period-internal slot pattern inside the scanned body.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import ssm
from repro.models.layers import dense_mlp, norm, position_encode
from repro.models.moe import moe_ffn
from repro.models.schema import decoder_period, slot_plan
from repro.sharding.partition import MeshContext, NULL_CTX


# ------------------------------------------------------------ attention mixer
def _qkv(cfg: ModelConfig, p, x, positions, ctx, cross: bool = False, kv_src=None):
    B, S, d = x.shape
    H, Hk, dh = cfg.num_heads, cfg.num_kv_heads, cfg.dh
    pre = "x" if cross else ""
    kv_in = kv_src if kv_src is not None else x
    q = jnp.einsum("bsd,de->bse", x, p[pre + "wq"])
    k = jnp.einsum("bsd,de->bse", kv_in, p[pre + "wk"])
    v = jnp.einsum("bsd,de->bse", kv_in, p[pre + "wv"])
    if cfg.use_bias:
        q = q + p[pre + "bq"].astype(q.dtype)
        k = k + p[pre + "bk"].astype(k.dtype)
        v = v + p[pre + "bv"].astype(v.dtype)
    q = q.reshape(B, S, H, dh)
    k = k.reshape(B, kv_in.shape[1], Hk, dh)
    v = v.reshape(B, kv_in.shape[1], Hk, dh)
    if not cross and positions is not None:
        q = position_encode(cfg, q, positions)
        k = position_encode(cfg, k, positions)
    q = ctx.constrain(q, "batch", None, "heads", None)
    k = ctx.constrain(k, "batch", None, "kv_heads", None)
    v = ctx.constrain(v, "batch", None, "kv_heads", None)
    return q, k, v


def _attn_out(cfg: ModelConfig, p, y, cross: bool = False):
    B, S = y.shape[:2]
    pre = "x" if cross else ""
    out = jnp.einsum("bse,ed->bsd", y.reshape(B, S, -1), p[pre + "wo"])
    if cfg.use_bias:
        out = out + p[pre + "bo"].astype(out.dtype)
    return out


def attn_mixer(cfg: ModelConfig, p, x, positions, ctx, *, causal=True,
               cache=None, pos=None, mode="train"):
    """-> (out, new_cache)."""
    q, k, v = _qkv(cfg, p, x, positions, ctx)
    if mode == "decode":
        if attn_mod.use_kv_sharded_decode(cfg, ctx, cache["k"].shape[1]):
            y, k_cache, v_cache = attn_mod.kv_sharded_decode_attention(
                cfg, ctx, q, cache["k"], cache["v"], k, v, pos)
        else:
            with jax.named_scope("kv_write"):
                k_cache = lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0))
                v_cache = lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0))
            y = attn_mod.decode_attention(q, k_cache, v_cache, pos,
                                          scale=cfg.dh ** -0.5)
        new_cache = {**cache, "k": k_cache, "v": v_cache}
    else:
        y = attn_mod.attention(cfg, q, k, v, causal=causal)
        new_cache = cache
        if mode == "prefill" and cache is not None:
            new_cache = {**cache,
                         "k": lax.dynamic_update_slice(
                             cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
                         "v": lax.dynamic_update_slice(
                             cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))}
    return _attn_out(cfg, p, y), new_cache


def cross_attn(cfg: ModelConfig, p, x, ctx, *, enc_out=None, cache=None, mode="train"):
    if mode == "decode":
        xk, xv = cache["xk"], cache["xv"]
        H, dh = cfg.num_heads, cfg.dh
        q = jnp.einsum("bsd,de->bse", x, p["xwq"])
        if cfg.use_bias:
            q = q + p["xbq"].astype(q.dtype)
        q = q.reshape(x.shape[0], x.shape[1], H, dh)
        y = attn_mod.decode_attention(q, xk, xv, xk.shape[1] - 1, scale=cfg.dh ** -0.5)
        return _attn_out(cfg, p, y, cross=True), cache
    q, k, v = _qkv(cfg, p, x, None, ctx, cross=True, kv_src=enc_out)
    y = attn_mod.attention(cfg, q, k, v, causal=False)
    new_cache = cache
    if mode == "prefill" and cache is not None:
        new_cache = {**cache, "xk": k.astype(cache["xk"].dtype),
                     "xv": v.astype(cache["xv"].dtype)}
    return _attn_out(cfg, p, y, cross=True), new_cache


# ------------------------------------------------------------------ one slot
def apply_slot(cfg: ModelConfig, mixer: str, mlp: str, p: dict, x, positions,
               ctx: MeshContext, *, mode="train", cache=None, pos=None,
               enc_out=None, causal=True):
    """Residual block: norm -> mixer -> +res; [cross]; norm -> mlp -> +res.
    -> (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache = dict(cache) if cache is not None else None
    h = norm(cfg, p, "norm1", x)
    checkpointed = mode == "train" and cfg.remat != "none"

    if mixer == "attn":
        y, c = attn_mixer(cfg, p, h, positions, ctx, causal=causal,
                          cache=cache, pos=pos, mode=mode)
        if new_cache is not None and c is not None:
            new_cache.update({k2: c[k2] for k2 in ("k", "v") if k2 in c})
    elif mixer == "rwkv6":
        y, st, sh = ssm.rwkv6_time_mix(
            cfg, p, h,
            state=cache["wkv"] if mode == "decode" and cache else None,
            shift_last=cache["shift_tm"] if mode == "decode" and cache else None,
            chunk=cfg.scan_chunk, checkpoint=checkpointed, ctx=ctx)
        if new_cache is not None:
            new_cache["wkv"], new_cache["shift_tm"] = st, sh
    elif mixer == "mamba":
        y, st, cv = ssm.mamba_mix(
            cfg, p, h,
            state=cache["ssm"] if mode == "decode" and cache else None,
            conv_state=cache["conv"] if mode == "decode" and cache else None,
            chunk=cfg.scan_chunk, checkpoint=checkpointed, ctx=ctx)
        if new_cache is not None:
            new_cache["ssm"], new_cache["conv"] = st, cv.astype(new_cache["conv"].dtype)
    else:
        raise ValueError(mixer)
    x = x + y

    if cfg.is_encdec:
        h = norm(cfg, p, "normx", x)
        y, c = cross_attn(cfg, p, h, ctx, enc_out=enc_out, cache=cache, mode=mode)
        if new_cache is not None and c is not None:
            new_cache.update({k2: c[k2] for k2 in ("xk", "xv") if k2 in c})
        x = x + y

    h = norm(cfg, p, "norm2", x)
    if mlp == "moe":
        y, aux = moe_ffn(cfg, p, h, ctx)
    elif cfg.mlp_type == "rwkv":
        y, sh = ssm.rwkv_channel_mix(
            cfg, p, h,
            shift_last=cache["shift_cm"] if mode == "decode" and cache else None)
        if new_cache is not None:
            new_cache["shift_cm"] = sh
    else:
        y = dense_mlp(cfg, p, h, ctx)
    x = x + y
    # sequence parallelism (tp_sp_fsdp profile): residual stream sharded
    # over 'model' on the seq dim between layers; no-op in other profiles
    # ("seq_tp" resolves to an unsharded dim there). Train-only: the win
    # is the remat x-stack; prefill has no backward and the extra
    # gather churn hurts archs whose heads don't divide the model axis.
    if mode == "train":
        x = ctx.constrain(x, "batch", "seq_tp", None)
    return x, new_cache, aux


# ------------------------------------------------------------------ decoder
def _remat_wrap(cfg: ModelConfig, fn, mode: str):
    if mode != "train" or cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def decoder_apply(cfg: ModelConfig, dec_params: dict, x, positions,
                  ctx: MeshContext, *, mode="train", cache=None, pos=None,
                  enc_out=None):
    """Scan over periods. dec_params/cache leaves have leading num_periods.
    -> (x, new_cache, total_aux)."""
    plan = slot_plan(cfg)

    # nested remat: each slot is its own checkpoint region so the backward
    # of a multi-slot period (jamba: 8 layers) holds one slot's transients
    # at a time instead of all eight.
    slot_fns = {}
    for s, (mixer, mlp) in enumerate(plan):
        def slot_fn(x_carry, p_slot, c_slot, _mixer=mixer, _mlp=mlp):
            return apply_slot(cfg, _mixer, _mlp, p_slot, x_carry, positions, ctx,
                              mode=mode, cache=c_slot, pos=pos, enc_out=enc_out)
        if mode == "train" and cfg.remat != "none" and len(plan) > 1:
            slot_fn = jax.checkpoint(slot_fn)
        slot_fns[s] = slot_fn

    def period_body(x_carry, per_period):
        p_slots, c_slots = per_period
        new_c = {}
        aux_total = jnp.zeros((), jnp.float32)
        for s, (mixer, mlp) in enumerate(plan):
            c_slot = c_slots.get(f"slot_{s}") if c_slots is not None else None
            x_carry, nc, aux = slot_fns[s](x_carry, p_slots[f"slot_{s}"], c_slot)
            if nc is not None:
                new_c[f"slot_{s}"] = nc
            aux_total = aux_total + aux
        return x_carry, (new_c if new_c else None, aux_total)

    body = _remat_wrap(cfg, period_body, mode)
    if cache is None:
        # scan without cache: pass a dummy zero array per period
        def body_nocache(x_carry, p_slots):
            return body(x_carry, (p_slots, None))
        x, (nc, aux) = lax.scan(body_nocache, x, dec_params)
        return x, None, jnp.sum(aux)
    x, (new_cache, aux) = lax.scan(body, x, (dec_params, cache))
    return x, new_cache, jnp.sum(aux)


# --------------------------------------------------------------- embeddings
def embed_tokens(cfg: ModelConfig, params, tokens, ctx: MeshContext):
    x = jnp.take(params["embedding"], tokens, axis=0)
    return ctx.constrain(x, "batch", None, None)


def splice_vision(cfg: ModelConfig, x, vision_embeds):
    """VLM stub frontend: first `vision_tokens` positions come from the
    (precomputed) patch embeddings."""
    V = vision_embeds.shape[1]
    return jnp.concatenate([vision_embeds.astype(x.dtype), x[:, V:]], axis=1)


def _positions_for(cfg: ModelConfig, batch, B, S):
    if cfg.pos_type == "mrope" and "positions" in batch:
        return batch["positions"]
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))


def add_learned_pos(cfg: ModelConfig, params, x, offset=0):
    if cfg.pos_type != "learned":
        return x
    S = x.shape[1]
    tbl = lax.dynamic_slice_in_dim(params["pos_embedding"], offset, S, axis=0)
    return x + tbl.astype(x.dtype)[None]


# ------------------------------------------------------------------ encoder
def encoder_apply(cfg: ModelConfig, params, frame_embeds, ctx: MeshContext):
    """Whisper-style encoder over stub frame embeddings (B, T, d)."""
    x = frame_embeds.astype(jnp.dtype(cfg.dtype))
    x = x + params["enc_pos_embedding"].astype(x.dtype)[None, :x.shape[1]]
    ecfg = cfg.replace(ssm_type="", num_experts=0)

    def body(x_carry, p_slot):
        x_carry, _, _ = apply_slot(ecfg.replace(encoder_layers=0), "attn", "dense",
                                   p_slot, x_carry, None, ctx,
                                   mode="train", causal=False)
        return x_carry, None

    x, _ = lax.scan(_remat_wrap(cfg, body, "train"), x, params["encoder"]["slot_0"])
    return norm(cfg, params, "enc_final_norm", x)


# ------------------------------------------------------------------ forward
def forward(cfg: ModelConfig, params, batch: dict, ctx: MeshContext = NULL_CTX,
            *, mode: str = "train", cache=None, pos=None):
    """mode: train | prefill | decode.
    batch keys: tokens (B,S); optional labels, vision_embeds, frame_embeds,
    positions.  -> dict with x/logits/cache/aux."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed_tokens(cfg, params, tokens, ctx)
    if cfg.frontend == "vision" and "vision_embeds" in batch:
        x = splice_vision(cfg, x, batch["vision_embeds"])
    offset = pos if mode == "decode" else 0
    x = add_learned_pos(cfg, params, x, offset if mode == "decode" else 0)

    if mode == "decode":
        positions = jnp.full((B, S), pos, jnp.int32)
        if cfg.pos_type == "mrope":
            positions = jnp.broadcast_to(positions[None], (3, B, S))
    else:
        positions = _positions_for(cfg, batch, B, S)

    enc_out = None
    if cfg.is_encdec and mode != "decode":
        enc_out = encoder_apply(cfg, params, batch["frame_embeds"], ctx)

    x, new_cache, aux = decoder_apply(cfg, params["decoder"], x, positions, ctx,
                                      mode=mode, cache=cache, pos=pos,
                                      enc_out=enc_out)
    x = norm(cfg, params, "final_norm", x)
    return {"x": x, "cache": new_cache, "aux": aux}


def logits_from_hidden(cfg: ModelConfig, params, x, ctx: MeshContext):
    head = params["embedding"] if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,vd->bsv", x, head).astype(jnp.float32)
    return ctx.constrain(logits, "batch", None, "vocab")


def cross_entropy(cfg: ModelConfig, params, x, labels, ctx: MeshContext):
    """Mean next-token CE. Optionally chunked over the sequence axis so
    (B, chunk, V) logits are materialized instead of (B, S, V)."""
    head = params["embedding"] if cfg.tie_embeddings else params["lm_head"]
    B, S, d = x.shape

    def chunk_loss(xc, yc):
        logits = jnp.einsum("bsd,vd->bsv", xc, head).astype(jnp.float32)
        logits = ctx.constrain(logits, "batch", None, "vocab")
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        true = jnp.take_along_axis(logits, yc[..., None].astype(jnp.int32),
                                   axis=-1)[..., 0]
        return jnp.sum(logz - true)

    if cfg.loss_chunk and S % cfg.loss_chunk == 0 and S > cfg.loss_chunk:
        nc = S // cfg.loss_chunk
        xc = x.reshape(B, nc, cfg.loss_chunk, d)
        yc = labels.reshape(B, nc, cfg.loss_chunk)

        def body(tot, inp):
            xi, yi = inp
            return tot + chunk_loss(xi, yi), None

        total, _ = lax.scan(body, jnp.zeros((), jnp.float32),
                            (jnp.moveaxis(xc, 1, 0), jnp.moveaxis(yc, 1, 0)))
    else:
        total = chunk_loss(x, labels)
    return total / (B * S)


def forward_train(cfg: ModelConfig, params, batch, ctx: MeshContext = NULL_CTX):
    out = forward(cfg, params, batch, ctx, mode="train")
    loss = cross_entropy(cfg, params, out["x"], batch["labels"], ctx)
    total = loss + cfg.router_aux_coef * out["aux"]
    return total, {"loss": loss, "aux_loss": out["aux"]}


# ------------------------------------------------------------------- caches
def init_cache(cfg: ModelConfig, B: int, max_len: int, *, abstract=False):
    """Decode cache pytree; leaves stacked over periods per slot."""
    period = decoder_period(cfg)
    P_ = cfg.num_layers // period
    dt = jnp.dtype(cfg.dtype)
    H, Hk, dh, d = cfg.num_heads, cfg.num_kv_heads, cfg.dh, cfg.d_model

    def mk(shape, dtype):
        if abstract:
            return jax.ShapeDtypeStruct((P_,) + shape, dtype)
        return jnp.zeros((P_,) + shape, dtype)

    cache: dict = {}
    for s, (mixer, mlp) in enumerate(slot_plan(cfg)):
        slot: dict = {}
        if mixer == "attn":
            slot["k"] = mk((B, max_len, Hk, dh), dt)
            slot["v"] = mk((B, max_len, Hk, dh), dt)
        elif mixer == "rwkv6":
            rH = d // cfg.rwkv_head_dim
            slot["wkv"] = mk((B, rH, cfg.rwkv_head_dim, cfg.rwkv_head_dim), jnp.float32)
            slot["shift_tm"] = mk((B, d), dt)
            slot["shift_cm"] = mk((B, d), dt)
        elif mixer == "mamba":
            din = cfg.ssm_expand * d
            slot["ssm"] = mk((B, din, cfg.ssm_state_dim), jnp.float32)
            slot["conv"] = mk((B, cfg.ssm_conv_dim - 1, din), dt)
        if cfg.mlp_type == "rwkv" and mixer != "rwkv6":
            slot["shift_cm"] = mk((B, d), dt)
        if cfg.is_encdec:
            slot["xk"] = mk((B, cfg.encoder_positions, Hk, dh), dt)
            slot["xv"] = mk((B, cfg.encoder_positions, Hk, dh), dt)
        cache[f"slot_{s}"] = slot
    return cache


def cache_logical_axes(cfg: ModelConfig) -> dict:
    """Logical sharding axes for each cache leaf (mirrors init_cache)."""
    axes: dict = {}
    for s, (mixer, mlp) in enumerate(slot_plan(cfg)):
        slot: dict = {}
        if mixer == "attn":
            slot["k"] = ("layers", "batch", "seq", "kv_heads", None)
            slot["v"] = ("layers", "batch", "seq", "kv_heads", None)
        elif mixer == "rwkv6":
            slot["wkv"] = ("layers", "batch", "heads", None, None)
            slot["shift_tm"] = ("layers", "batch", "embed")
            slot["shift_cm"] = ("layers", "batch", "embed")
        elif mixer == "mamba":
            slot["ssm"] = ("layers", "batch", "mlp", None)
            slot["conv"] = ("layers", "batch", None, "mlp")
        if cfg.mlp_type == "rwkv" and mixer != "rwkv6":
            slot["shift_cm"] = ("layers", "batch", "embed")
        if cfg.is_encdec:
            slot["xk"] = ("layers", "batch", None, "kv_heads", None)
            slot["xv"] = ("layers", "batch", None, "kv_heads", None)
        axes[f"slot_{s}"] = slot
    return axes


def prefill(cfg: ModelConfig, params, batch, ctx: MeshContext = NULL_CTX,
            *, max_len: int | None = None):
    """Run the full prompt, return (last-token logits, filled cache)."""
    B, S = batch["tokens"].shape
    cache = init_cache(cfg, B, max_len or S)
    out = forward(cfg, params, batch, ctx, mode="prefill", cache=cache)
    logits = logits_from_hidden(cfg, params, out["x"][:, -1:, :], ctx)
    return logits[:, 0], out["cache"]


def decode_step(cfg: ModelConfig, params, cache, tokens, pos,
                ctx: MeshContext = NULL_CTX):
    """One decode step. tokens: (B, 1); pos: scalar int32 index of the
    slot being written. -> (logits (B, V), new_cache)."""
    batch = {"tokens": tokens}  # enc-dec: encoder output lives in cache (xk/xv)
    out = forward(cfg, params, batch, ctx, mode="decode", cache=cache, pos=pos)
    logits = logits_from_hidden(cfg, params, out["x"], ctx)
    return logits[:, 0], out["cache"]
