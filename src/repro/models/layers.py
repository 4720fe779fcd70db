"""Shared model layers: norms, RoPE/M-RoPE, MLPs, checkpointed chunked scan."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig


def is_tracer(x) -> bool:
    """True when ``x`` is an abstract value inside a jax trace (so RTCG
    host paths must fall back to jax ops)."""
    return isinstance(x, jax.core.Tracer)


def norm(cfg: ModelConfig, p: dict, name: str, x, *, use_pallas: bool = False,
         use_rtcg: bool = False):
    w = p[name]
    if cfg.norm_type == "layernorm":
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * lax.rsqrt(var + cfg.norm_eps)
        return (y * w + p[name + "_b"]).astype(x.dtype)
    if use_rtcg and not is_tracer(x):
        return rtcg_rmsnorm(x, w, eps=cfg.norm_eps)
    if use_pallas:
        from repro.kernels.rmsnorm.ops import rmsnorm as pallas_rms
        return pallas_rms(x, w.astype(x.dtype), eps=cfg.norm_eps)
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(ms + cfg.norm_eps) * w).astype(x.dtype)


# ---------------------------------------------------------------- RoPE
def rope_freqs(dh: int, theta: float):
    return theta ** (-jnp.arange(0, dh // 2, dtype=jnp.float32) / (dh // 2))


def apply_rope(x, positions, theta: float):
    """x: (B, S, H, dh); positions: (B, S) int32."""
    dh = x.shape[-1]
    inv = rope_freqs(dh, theta)                        # (dh/2,)
    ang = positions[..., None].astype(jnp.float32) * inv   # (B, S, dh/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta: float, sections: tuple):
    """Qwen2-VL M-RoPE. x: (B, S, H, dh); positions3: (3, B, S) —
    temporal/height/width position streams; `sections` gives the half-dim
    split among them (sum(sections) == dh // 2)."""
    dh = x.shape[-1]
    half = dh // 2
    assert sum(sections) == half, (sections, half)
    inv = rope_freqs(dh, theta)                        # (half,)
    # pick the position stream per frequency section (static table)
    sec_id = jnp.asarray(np.repeat(np.arange(3), np.asarray(sections)), jnp.int32)
    pos = jnp.moveaxis(positions3, 0, -1).astype(jnp.float32)  # (B, S, 3)
    pos = jnp.take(pos, sec_id, axis=-1)               # (B, S, half)
    ang = pos * inv
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def position_encode(cfg: ModelConfig, x, positions):
    """q/k rotary application dispatch. positions: (B,S) or (3,B,S)."""
    if cfg.pos_type == "rope":
        return apply_rope(x, positions, cfg.rope_theta)
    if cfg.pos_type == "mrope":
        if positions.ndim == 2:  # text-only fallback: all streams equal
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        return apply_mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    return x


# ------------------------------------------------------------- softmax
def fused_softmax(x, *, stable: bool = True, backend: str | None = None):
    """Softmax dispatch with an RTCG fused host path — axis-aware.

    Concrete inputs of ANY batch shape (a logits row outside jit, the
    full ``(B, N)`` attention-score matrices of the naive and decode
    paths) route through the fusion planner's row-segmented schedule:
    ONE generated per-row reduction wave plus ONE fused 2-D epilogue —
    2 launches for the whole batch instead of ``3·B`` per-row launches
    or a jax fallback.  ``stable=True`` stays at 2 launches too: the row
    max and the shifted-exp sum share one wave (each row is complete
    inside its block, so the dependency resolves in-kernel).  Traced
    values fall back to ``jax.nn.softmax``; axis is always the last one.

    ``backend`` pins the execution backend per call (``"pallas"`` /
    ``"xla"``); by default the process-wide ``REPRO_BACKEND`` selection
    applies.  ``backend="auto"`` (PR 5) takes the serving-runtime path
    instead: the default `repro.runtime.ServingRuntime` picks the
    backend per shape bucket from latency telemetry and records the
    call into the warm-start manifest — see DESIGN.md §9.2.
    """
    if is_tracer(x):
        return jax.nn.softmax(x, axis=-1)
    if getattr(x, "ndim", 0) == 0:
        return jax.nn.softmax(x, axis=-1)
    from repro.core.backends import is_auto

    if is_auto(backend):
        from repro import runtime as _rt

        return _rt.default_runtime().softmax(x, stable=stable)
    from repro.core import array as ga

    rows = jnp.reshape(x, (-1, x.shape[-1]))
    out = ga.softmax(ga.RTCGArray(rows), stable=stable).evaluate(
        backend=backend).value
    return jnp.reshape(out, x.shape).astype(x.dtype)


def rtcg_rmsnorm(x, w, *, eps: float = 1e-6, backend: str | None = None):
    """Planner-backed RMSNorm: ``x / sqrt(mean(x^2, -1) + eps) * w``
    scheduled as ONE row-segmented reduction wave plus ONE fused 2-D
    epilogue (2 launches), with the ``(N,)`` weight broadcast per-col
    and the per-row ``mean`` re-entering the epilogue as a ``(B, 1)``
    broadcast arg — the axis-aware-fusion counterpart of the
    hand-written `repro.kernels.rmsnorm` Pallas kernel.  ``backend``
    pins the execution backend per call (default: ``REPRO_BACKEND``);
    ``backend="auto"`` routes through the serving runtime's latency
    router + warm-start manifest (DESIGN.md §9.2)."""
    from repro.core.backends import is_auto

    if is_auto(backend):
        from repro import runtime as _rt

        return _rt.default_runtime().rmsnorm(x, w, eps=eps)
    from repro.core import array as ga

    orig = x.shape
    X = ga.RTCGArray(jnp.reshape(x, (-1, orig[-1])).astype(jnp.float32))
    W = ga.RTCGArray(jnp.asarray(w).astype(jnp.float32))
    out = (X / (((X * X).mean(axis=-1) + eps).sqrt()) * W).evaluate(
        backend=backend).value
    return jnp.reshape(out, orig).astype(x.dtype)


# ---------------------------------------------------------------- MLPs
def dense_mlp(cfg: ModelConfig, p: dict, x, ctx):
    if cfg.mlp_type == "swiglu":
        h = jnp.einsum("bsd,df->bsf", x, p["w1"])
        g = jnp.einsum("bsd,df->bsf", x, p["w3"])
        h = jax.nn.silu(h) * g
        h = ctx.constrain(h, "batch", None, "mlp")
        return jnp.einsum("bsf,fd->bsd", h, p["w2"])
    h = jnp.einsum("bsd,df->bsf", x, p["wi"])
    if cfg.use_bias:
        h = h + p["bi"].astype(h.dtype)
    h = jax.nn.gelu(h)
    h = ctx.constrain(h, "batch", None, "mlp")
    out = jnp.einsum("bsf,fd->bsd", h, p["wo_mlp"])
    if cfg.use_bias:
        out = out + p["bo_mlp"].astype(out.dtype)
    return out


# ------------------------------------------------- chunked, checkpointed scan
def chunked_scan(step_fn, init_carry, xs, chunk: int, checkpoint: bool = True):
    """lax.scan over the leading (time) axis of `xs`, processed in chunks
    of `chunk` steps.  Each chunk body is optionally jax.checkpoint'ed so
    the backward pass stores only chunk-boundary carries (O(T/chunk)
    memory instead of O(T)) — required to train SSM/RWKV recurrences at
    4k-500k sequence lengths."""
    T = jax.tree.leaves(xs)[0].shape[0]
    chunk = min(chunk, T)
    main = (T // chunk) * chunk
    nchunks = main // chunk

    def chunk_body(carry, xc):
        return lax.scan(step_fn, carry, xc)

    if checkpoint:
        chunk_body = jax.checkpoint(chunk_body)

    xs_main = jax.tree.map(
        lambda a: a[:main].reshape((nchunks, chunk) + a.shape[1:]), xs)
    carry, ys_c = lax.scan(chunk_body, init_carry, xs_main)
    ys = jax.tree.map(lambda a: a.reshape((main,) + a.shape[2:]), ys_c)
    if main != T:  # remainder tail, scanned unchunked
        carry, ys_tail = lax.scan(step_fn, carry, jax.tree.map(lambda a: a[main:], xs))
        ys = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=0), ys, ys_tail)
    return carry, ys
