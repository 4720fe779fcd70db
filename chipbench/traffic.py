"""The one traffic generator: a mix file's parameters and a seed -> requests.

A mix file (``traffic/<name>.json``) gives the number of closed-loop
sessions, the request rate the window is sized by, and the length
distributions.  Every seed serves the same (prompt length, output
length) pairs in the same order: the lengths sit at fixed quantiles of
the distributions, paired and ordered by one rule for every mix, the
first two permutations of ``numpy.random.default_rng(ORDER_SEED)``.
The run's seed draws the token ids (and, elsewhere, the weights, the
sampling keys and the check's sample).  The order is fixed because the
engine decodes every slot at one shared position: how many steps a
request list takes depends on the order of its prompt lengths, so an
order drawn per seed would change the work from seed to seed.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

ORDER_SEED = 0     # the one rule that pairs and orders every mix's lengths


@dataclass
class Request:
    """One request of the run and what the driver saw happen to it."""

    index: int                  # position in the seeded request list
    prompt: np.ndarray          # int32 token ids
    max_new: int
    rid: "int | None" = None    # the engine's request id
    session: int = -1
    t_submit: float = math.nan
    admit_step: int = -1        # the step that admitted it (first token)
    admit_pos: int = -1         # the engine's position after that step
    finish_step: int = -1
    tokens: "np.ndarray | None" = None
    status: str = "pending"     # ok | truncated | error | shed

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])


def quantile(dist: dict, q: float) -> int:
    """The ``q`` quantile of a length distribution, clipped and rounded."""
    kind = dist["dist"]
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(q))
    elif kind == "uniform":
        x = dist["min"] + q * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(min(max(round(x), dist["min"]), dist["max"]))


def request_count(mix: dict, seconds: float) -> int:
    """Requests a run serves: the mix's rate times the window's length."""
    return max(1, round(mix["requests_per_s"] * seconds))


def length_pairs(mix: dict, n: int) -> list:
    """The ``n`` (prompt length, output length) pairs every seed serves,
    in the order it serves them."""
    qs = [(i + 0.5) / n for i in range(n)]
    prompts = [quantile(mix["prompt_len"], q) for q in qs]
    outs = [quantile(mix["output_len"], q) for q in qs]
    rng = np.random.default_rng(ORDER_SEED)
    pairing, order = rng.permutation(n), rng.permutation(n)
    return [(prompts[int(i)], outs[int(pairing[i])]) for i in order]


def make_requests(mix: dict, n: int, seed: int, vocab: int) -> list:
    """The request list: the mix's pairs with token ids from ``seed``
    (never the pad id 0)."""
    rng = np.random.default_rng(seed)
    out = []
    for i, (L, m) in enumerate(length_pairs(mix, n)):
        prompt = rng.integers(1, vocab, L, dtype=np.int64).astype(np.int32)
        out.append(Request(index=i, prompt=prompt, max_new=int(m)))
    return out
