"""Mixture-of-Experts FFN with shard_map expert parallelism.

Pattern (DESIGN.md §5): tokens are sharded over the batch axes, experts
over the model axis.  Each (data, model) shard routes *its* tokens over
the full expert table (router weights replicated — negligible compute),
scatter-dispatches the subset assigned to its local experts into a
capacity-bounded (E_local, C, d) buffer, runs the expert SwiGLU as a
batched matmul, gathers back, and psums the combined output over the
model axis — the same collective volume as a tensor-parallel FFN.

Expert weights are additionally FSDP-sharded over the data axis on the
d_model dim and all-gathered per layer *inside* the shard_map (the
explicit ZeRO-3 gather; overlapped across scan iterations by the XLA
scheduler).  Capacity overflow drops tokens (standard practice; the
residual path carries them).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.sharding.partition import MeshContext


def _route(cfg: ModelConfig, router_w, x_flat):
    """x_flat: (G, d) -> (probs (G, k), idx (G, k) int32, aux_loss scalar)."""
    # bf16 dot (f32 MXU accumulation), f32 cast AFTER: keeps the x_flat
    # cotangent bf16 — preferred_element_type=f32 here would make every
    # backward activation all-reduce f32 (2x wire bytes; §Perf).
    logits = jnp.einsum("gd,de->ge", x_flat,
                        router_w.astype(x_flat.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = lax.top_k(probs, cfg.num_experts_per_tok)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # switch-style load balance loss: E * sum_e f_e * P_e
    E = cfg.num_experts
    f = jnp.zeros((E,), jnp.float32).at[top_i[:, 0]].add(1.0) / top_i.shape[0]
    pbar = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * pbar)
    return top_p.astype(x_flat.dtype), top_i.astype(jnp.int32), aux


def _expert_ffn(cfg: ModelConfig, w1, w3, w2, buf):
    """buf: (El, C, d) -> (El, C, d) batched SwiGLU (bf16 throughout —
    keeping silu in f32 would materialize f32 copies of the largest
    activation tensors; see EXPERIMENTS.md §Perf)."""
    h = jnp.einsum("ecd,edf->ecf", buf, w1)
    g = jnp.einsum("ecd,edf->ecf", buf, w3)
    h = jax.nn.silu(h) * g
    return jnp.einsum("ecf,efd->ecd", h, w2)


def _local_moe(cfg: ModelConfig, capacity: int, n_local: int, model_axis: str | None,
               fsdp_axis: str | None, x, router_w, we1, we3, we2,
               all_axes: tuple = ()):
    """Per-shard body. x: (b_loc, S, d); we*: (E_local, d_loc, f)."""
    b, S, d = x.shape
    G = b * S
    xf = x.reshape(G, d)
    if fsdp_axis is not None:
        # explicit ZeRO-3 all-gather of the layer's expert weights
        we1 = lax.all_gather(we1, fsdp_axis, axis=1, tiled=True)
        we3 = lax.all_gather(we3, fsdp_axis, axis=1, tiled=True)
        we2 = lax.all_gather(we2, fsdp_axis, axis=2, tiled=True)
    probs, idx, aux = _route(cfg, router_w, xf)          # (G,k)
    k = cfg.num_experts_per_tok
    E = cfg.num_experts
    e0 = (lax.axis_index(model_axis) if model_axis else 0) * n_local

    # position of each (token, slot) within its expert, via per-slot cumsum
    counts = jnp.zeros((E,), jnp.int32)
    positions = []
    for s in range(k):
        onehot = jax.nn.one_hot(idx[:, s], E, dtype=jnp.int32)      # (G, E)
        pos_in_e = jnp.cumsum(onehot, axis=0) - 1 + counts[None, :]
        positions.append(jnp.take_along_axis(pos_in_e, idx[:, s:s + 1], axis=1)[:, 0])
        counts = counts + jnp.sum(onehot, axis=0)
    pos = jnp.stack(positions, axis=1)                   # (G, k)

    local = (idx >= e0) & (idx < e0 + n_local) & (pos < capacity)
    e_loc = jnp.where(local, idx - e0, n_local)          # OOB row -> dropped
    p_loc = jnp.where(local, pos, capacity)

    buf = jnp.zeros((n_local, capacity, d), x.dtype)
    src = jnp.broadcast_to(xf[:, None, :], (G, k, d)).reshape(G * k, d)
    buf = buf.at[e_loc.reshape(-1), p_loc.reshape(-1)].set(
        src, mode="drop", unique_indices=True)

    out_buf = _expert_ffn(cfg, we1, we3, we2, buf)       # (El, C, d)

    gathered = out_buf.at[e_loc.reshape(-1), p_loc.reshape(-1)].get(
        mode="fill", fill_value=0)                        # (G*k, d)
    gathered = gathered.reshape(G, k, d) * probs[..., None]
    out = jnp.sum(gathered, axis=1).astype(x.dtype)      # (G, d) — cast
    # BEFORE the psum: halves collective bytes and keeps the residual bf16
    if model_axis is not None:
        out = lax.psum(out, model_axis)
    if all_axes:
        aux = lax.pmean(aux, all_axes)   # replicated aux across the mesh
    return out.reshape(b, S, d), aux


def moe_ffn(cfg: ModelConfig, p: dict, x, ctx: MeshContext):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar)."""
    B, S, d = x.shape
    msize = ctx.axis_size("model") if ctx.model_axis else 1
    assert cfg.num_experts % msize == 0, (cfg.num_experts, msize)
    n_local = cfg.num_experts // msize
    # replicate tokens over the batch axes when B does not divide them
    # (long_500k decode: B=1) — routing is then computed redundantly,
    # which is negligible at decode token counts.
    shard_batch = ctx.batch_axes and B % ctx.data_shards == 0
    G = (B // ctx.data_shards if shard_batch else B) * S
    capacity = max(4, int(cfg.capacity_factor * G * cfg.num_experts_per_tok
                          / cfg.num_experts))

    if ctx.mesh is not None and ctx.profile not in ("tp_fsdp", "tp_sp_fsdp"):
        raise ValueError(
            f"MoE archs require a profile with experts on 'model' "
            f"(tp_fsdp/tp_sp_fsdp); got {ctx.profile!r}")
    if ctx.mesh is None:
        out, aux = _local_moe(cfg, capacity, n_local, None, None,
                              x, p["router"], p["we1"], p["we3"], p["we2"])
    else:
        baxes = ctx.batch_axes
        bdim = (baxes if len(baxes) > 1 else baxes[0]) if shard_batch else None
        bspec = P(bdim, None, None)
        fsdp = "data" if "data" in ctx.mesh.axis_names else None
        # expert weights arrive (E/model, d/data, f) — gathered inside
        wspec13 = P("model", fsdp, None)
        wspec2 = P("model", None, fsdp)
        body = functools.partial(_local_moe, cfg, capacity, n_local,
                                 ctx.model_axis, fsdp,
                                 all_axes=tuple(ctx.mesh.axis_names))
        out, aux = jax.shard_map(
            body, mesh=ctx.mesh,
            in_specs=(bspec, P(None, None), wspec13, wspec13, wspec2),
            out_specs=(bspec, P()),
            check_vma=False,
        )(x, p["router"], p["we1"], p["we3"], p["we2"])

    if cfg.dense_residual_ffn:
        from repro.models.layers import dense_mlp
        out = out + dense_mlp(cfg, p, x, ctx)
    return out, aux
