"""Operations and bytes each request needs, from the configuration's numbers.

Only the work a request needs counts, never padding: a prefill of an
``L``-token prompt counts ``L`` tokens (the program runs it padded to
``max_len``), and a decode row counts the cache columns its own prompt
and tokens fill.  A multiply-add is two operations.  Weights and cache
are counted at two bytes (bfloat16); activations are left out.
"""

from __future__ import annotations


def _dims(m: dict):
    d, H, Hk = m["d_model"], m["num_heads"], m["num_kv_heads"]
    dh = m.get("head_dim") or d // H
    return d, H, Hk, dh, m["d_ff"], m["vocab_size"], m["num_layers"]


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies with in one layer."""
    d, H, Hk, dh, f, _, _ = _dims(m)
    return d * H * dh + 2 * d * Hk * dh + H * dh * d + 3 * d * f


def weight_bytes(m: dict, itemsize: int = 2) -> int:
    """Every layer's matrices plus the output head (the embedding table
    is read one row per token)."""
    d, _, _, _, _, V, n = _dims(m)
    return itemsize * (n * layer_matmul_params(m) + V * d)


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    d, _, Hk, dh, _, _, n = _dims(m)
    return itemsize * 2 * Hk * dh * n


def prefill_flops(m: dict, L: int) -> int:
    """One ``L``-token prompt: the layers' products for every token,
    causal attention (``QK^T`` and ``PV`` over ``L(L+1)/2`` pairs), and
    the head for the last token."""
    d, H, _, dh, _, V, n = _dims(m)
    return (2 * L * n * layer_matmul_params(m)
            + 2 * 2 * n * H * dh * L * (L + 1) // 2
            + 2 * d * V)


def prefill_bytes(m: dict, L: int) -> int:
    return weight_bytes(m) + L * kv_bytes_per_token(m)


def decode_flops(m: dict, contexts: list) -> int:
    """One decode step over rows whose contexts hold ``contexts`` tokens
    (the new token included): layer products, attention over those
    columns, and the head, for each row."""
    d, H, _, dh, _, V, n = _dims(m)
    rows = len(contexts)
    return (2 * rows * n * layer_matmul_params(m)
            + 2 * 2 * n * H * dh * sum(contexts)
            + 2 * rows * d * V)


def decode_bytes(m: dict, contexts: list) -> int:
    """Weights once for the step, plus each row's cache columns."""
    return weight_bytes(m) + sum(contexts) * kv_bytes_per_token(m)


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """Roofline: the larger of compute time and memory time at peak."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
