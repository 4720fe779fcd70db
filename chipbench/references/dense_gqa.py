"""Plain reference: a dense decoder-only transformer with grouped-query
attention, rotary positions and a SwiGLU MLP (the InternLM2 and DeepSeek
LLM layer, arXiv:2403.17297 and arXiv:2401.02954).

Straight ``jax.numpy`` in float32 with every matrix product at
``precision="highest"``; no cache, no batching, no kernel.  It imports
nothing of the program: the configuration's numbers come from its file
and the weights are read by name from the tree the benchmark made.

Per layer: ``x += Wo·attn(rope(Wq·n1(x)), rope(Wk·n1(x)), Wv·n1(x))``,
then ``x += W2·(silu(W1·n2(x)) * W3·n2(x))``; ``n`` is RMSNorm with a
weight; query head ``h`` reads key/value head ``h // (H / Hk)``; rotary
embedding rotates the two halves of each head (``x1 cos - x2 sin``,
``x1 sin + x2 cos``) with inverse frequencies ``theta^(-2i/dh)``.

``precision="int8"`` is the control: every weight matrix is rounded to
int8 with one scale per output channel (the step a later change might
take to save memory), the arithmetic stays float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = "highest"
Q_BLOCK = 256          # query rows per attention block
HEAD_BLOCK_BYTES = 256 << 20


def _int8(w, axis: int):
    """Symmetric int8 rounding with one scale per slice along ``axis``
    (the reduced axis), back in float32."""
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def _fp8(w, axis: int):
    """float8 (e4m3) rounding with one scale per slice along ``axis``,
    back in float32."""
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _w(w, precision: str, axis: int = 0):
    w = w.astype(jnp.float32)
    if precision == "int8":
        return _int8(w, axis)
    if precision == "fp8":
        return _fp8(w, axis)
    return w


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta: float):
    """x: (T, heads, dh) at positions 0..T-1."""
    T, _, dh = x.shape
    inv = theta ** (-jnp.arange(0, dh // 2, dtype=jnp.float32) / (dh // 2))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("m", "precision"))
def _layer(x, slot, i, m: tuple, precision: str):
    d, H, Hk, dh, eps, theta = m
    T = x.shape[0]
    w = {k: lax.dynamic_index_in_dim(v, i, keepdims=False)
         for k, v in slot.items()}
    h = _rms(x, w["norm1"].astype(jnp.float32), eps)
    q = jnp.einsum("td,de->te", h, _w(w["wq"], precision), precision=HI)
    k = jnp.einsum("td,de->te", h, _w(w["wk"], precision), precision=HI)
    v = jnp.einsum("td,de->te", h, _w(w["wv"], precision), precision=HI)
    q = _rope(q.reshape(T, H, dh), theta)
    k = jnp.repeat(_rope(k.reshape(T, Hk, dh), theta), H // Hk, axis=1)
    v = jnp.repeat(v.reshape(T, Hk, dh), H // Hk, axis=1)
    cols = jnp.arange(T)

    def block(start):
        qb = lax.dynamic_slice_in_dim(q, start, Q_BLOCK, axis=0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * dh ** -0.5
        rows = start + jnp.arange(Q_BLOCK)
        s = jnp.where(cols[None, None, :] <= rows[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    y = lax.map(block, jnp.arange(0, T, Q_BLOCK)).reshape(T, H * dh)
    x = x + jnp.einsum("te,ed->td", y, _w(w["wo"], precision), precision=HI)
    h = _rms(x, w["norm2"].astype(jnp.float32), eps)
    a = jnp.einsum("td,df->tf", h, _w(w["w1"], precision), precision=HI)
    b = jnp.einsum("td,df->tf", h, _w(w["w3"], precision), precision=HI)
    return x + jnp.einsum("tf,fd->td", jax.nn.silu(a) * b,
                          _w(w["w2"], precision), precision=HI)


@functools.partial(jax.jit, static_argnames=("precision",))
def _embed(table, tokens, precision: str):
    return _w(table[tokens], precision, axis=-1)


@functools.partial(jax.jit, static_argnames=("nb", "eps", "precision"))
def _head(x, norm_w, head, nb: int, eps: float, precision: str):
    """Logits of the rows ``x`` over the whole vocabulary, the (V, d)
    head taken in ``nb`` blocks of rows so its float32 copy stays small."""
    h = _rms(x, norm_w.astype(jnp.float32), eps)
    V, d = head.shape
    blocks = head.reshape(nb, V // nb, d)
    out = lax.map(lambda blk: jnp.einsum(
        "td,vd->tv", h, _w(blk, precision, axis=-1), precision=HI), blocks)
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], V)


def _head_blocks(V: int, d: int) -> int:
    nb = 1
    while V % nb or (V // nb) * d * 4 > HEAD_BLOCK_BYTES:
        nb += 1
    return nb


def logits(model: dict, params, tokens: np.ndarray, at: np.ndarray,
           precision: str = "float32") -> np.ndarray:
    """Logits at positions ``at`` of one causal pass over ``tokens``
    (length a multiple of 256; positions past the last of ``at`` change
    nothing before it) -> float32 array (len(at), vocab)."""
    d, H = model["d_model"], model["num_heads"]
    Hk = model["num_kv_heads"]
    dh = model.get("head_dim") or d // H
    m = (d, H, Hk, dh, float(model["norm_eps"]), float(model["rope_theta"]))
    if len(tokens) % Q_BLOCK:
        raise ValueError(f"context length {len(tokens)} is not a multiple "
                         f"of {Q_BLOCK}")
    x = _embed(params["embedding"], jnp.asarray(tokens, jnp.int32), precision)
    slot = params["decoder"]["slot_0"]
    for i in range(model["num_layers"]):
        x = _layer(x, slot, jnp.int32(i), m, precision)
    head = params["embedding" if model.get("tie_embeddings") else "lm_head"]
    nb = _head_blocks(*head.shape)
    out = _head(x[jnp.asarray(at, jnp.int32)], params["final_norm"], head,
                nb, float(model["norm_eps"]), precision)
    return np.asarray(out, np.float32)
