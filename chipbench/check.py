"""The comparison that decides ``correct``.

Three numbers, each against a limit from the configuration's file:

* ``logit_gap`` (the model step: admission prefill and decode through the
  KV cache).  For a sample of finished requests drawn from the seed, with
  the longest in it, the reference runs once over the context the engine
  served: the left padding the engine put before the prompt (it ends the
  prompt at the shared position), the prompt, and the served tokens.  At
  each served position it takes the token the program put first (the
  served token where decoding is greedy, the argmax of the recorded
  logits row where it samples) and reads how far that token's reference
  logit lies below the reference's best.  The number is the widest gap.
* ``sampler_gap`` (the runtime sampler, sampled cells only).  For each
  recorded row the reference computes the float64 softmax of the row over
  the temperature, its cumulative sum, and the draw point from the same
  key; the number is the probability mass by which the draw point lies
  outside the served token's interval (0 when it lies inside).
* ``count_gap`` (the engine's bookkeeping).  Requests whose token count is
  not what they asked for, cut only where the shared position reached
  ``max_len`` (``min(max_new, max_len - admit_pos + 1)``), or is not one
  token per step they were live, or that never finished.  Limit 0.
"""

from __future__ import annotations

import numpy as np

PAD_ID = 0


def sample_requests(requests: list, seed: int, min_tokens: int,
                    max_requests: int) -> list:
    """The requests to compare, drawn from the seed before the window:
    the one asking the most tokens, then others in a seeded order until
    ``min_tokens`` asked tokens or ``max_requests`` requests."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    longest = max(requests, key=lambda r: (r.max_new, -r.index))
    chosen = [longest]
    for i in rng.permutation(len(requests)):
        if (sum(r.max_new for r in chosen) >= min_tokens
                or len(chosen) >= max_requests):
            break
        r = requests[int(i)]
        if r is not longest:
            chosen.append(r)
    return chosen


def context(r, max_len: int, block: int) -> "tuple[np.ndarray, np.ndarray]":
    """The served context of request ``r`` and the positions whose logits
    produced its tokens.  Padded at the end to a multiple of ``block``."""
    n = len(r.tokens)
    pad = r.admit_pos - r.prompt_len
    seq = np.concatenate([np.full(pad, PAD_ID, np.int32), r.prompt,
                          np.asarray(r.tokens[:-1], np.int32)])
    T = -(-max(len(seq), 1) // block) * block
    T = max(T, -(-max_len // block) * block)
    out = np.full(T, PAD_ID, np.int32)
    out[:len(seq)] = seq
    at = np.arange(r.admit_pos - 1, r.admit_pos - 1 + n)
    return out, at


def gap(ref_rows: np.ndarray, tokens: np.ndarray) -> float:
    """Widest gap between the reference's best logit and its logit of
    ``tokens`` (one per row)."""
    ref = np.asarray(ref_rows, np.float64)
    picked = ref[np.arange(len(tokens)), np.asarray(tokens, np.int64)]
    return float(np.max(ref.max(axis=-1) - picked))


def cdf(row: np.ndarray, temperature: float) -> np.ndarray:
    z = np.asarray(row, np.float64) / max(temperature, 1e-8)
    p = np.exp(z - z.max())
    return np.cumsum(p / p.sum())


def draw_point(key) -> float:
    import jax

    return float(jax.random.uniform(jax.numpy.asarray(key), ()))


def mass_gap(row, temperature: float, u: float, token: int) -> float:
    """Probability mass between ``u`` and the interval that ``token``
    holds in the float64 CDF of ``row / temperature``."""
    c = cdf(row, temperature)
    lo = c[token - 1] if token > 0 else 0.0
    hi = c[token]
    return float(max(lo - u, u - hi, 0.0))


def count_gap(requests: list, max_len: int) -> int:
    bad = 0
    for r in requests:
        if r.tokens is None or r.admit_step < 0:
            bad += 1
            continue
        n = len(r.tokens)
        due = min(r.max_new, max_len - r.admit_pos + 1)
        if n != due or n != r.finish_step - r.admit_step + 1:
            bad += 1
    return bad


def compare(reference, model: dict, params, served, chosen: list,
            recorded: "dict | None", max_len: int, block: int,
            controls: tuple = ()) -> dict:
    """Readings of every number.  With ``controls`` (lower precisions the
    reference knows), also the control's readings: ``logit_gap.<p>`` for
    the reference at precision ``p`` put in the program's place, and,
    where the cell samples, ``sampler_gap.bfloat16`` for the sampler
    computed in bfloat16."""
    gaps = {p: 0.0 for p in ("float32",) + tuple(controls)}
    samp = {"float32": 0.0, "bfloat16": 0.0}
    positions = 0
    for r in chosen:
        if r.tokens is None or len(r.tokens) == 0:
            continue
        toks, at = context(r, max_len, block)
        ref = reference.logits(model, params, toks, at, "float32")
        rows = None
        if recorded is not None:
            rows = recorded.get(r.rid, [])
            if len(rows) != len(r.tokens):
                raise RuntimeError(f"request {r.rid}: {len(rows)} recorded "
                                   f"rows for {len(r.tokens)} tokens")
            first = np.array([int(np.argmax(x)) for x, _, _ in rows])
        else:
            first = np.asarray(r.tokens)
        positions += len(at)
        gaps["float32"] = max(gaps["float32"], gap(ref, first))
        for p in controls:
            low = reference.logits(model, params, toks, at, p)
            gaps[p] = max(gaps[p], gap(ref, np.argmax(low, axis=-1)))
        if rows is not None:
            for (x, key, temp), tok in zip(rows, r.tokens):
                u = draw_point(key)
                samp["float32"] = max(samp["float32"],
                                      mass_gap(x, temp, u, int(tok)))
                if controls:
                    samp["bfloat16"] = max(samp["bfloat16"], mass_gap(
                        x, temp, u, control_draw(x, temp, u)))
    readings = {"logit_gap": gaps["float32"]}
    if recorded is not None:
        readings["sampler_gap"] = samp["float32"]
    for p in controls:
        readings[f"logit_gap.{p}"] = gaps[p]
    if controls and recorded is not None:
        readings["sampler_gap.bfloat16"] = samp["bfloat16"]
    readings["count_gap"] = float(count_gap(served.requests, max_len))
    readings["positions"] = positions
    return readings


def as_control(readings: dict, precision: str) -> dict:
    """The readings with the control in the program's place: the reference
    at ``precision`` for the model step and, where the cell samples, the
    bfloat16 sampler; the engine's counts stay the program's."""
    out = dict(readings, logit_gap=readings[f"logit_gap.{precision}"])
    if "sampler_gap" in readings:
        out["sampler_gap"] = readings["sampler_gap.bfloat16"]
    return out


def control_draw(row, temperature: float, u: float) -> int:
    """The sampler computed in bfloat16, the precision below the float32
    the runtime states: probabilities and their running sum in bfloat16."""
    import ml_dtypes

    z = np.asarray(row, np.float64) / max(temperature, 1e-8)
    p = np.exp(z - z.max())
    c = np.cumsum((p / p.sum()).astype(ml_dtypes.bfloat16))
    c = c.astype(np.float64)
    return min(int(np.searchsorted(c, u * c[-1], side="right")), len(c) - 1)
