"""Build the system under test for one configuration file.

The benchmark makes the weights itself: one jitted program fills the
program's parameter tree (its leaf names and shapes, from
``schema.abstract_params``) from the seed, on the device, in the type
they are served in.  The reference reads the same weights by name; no
weight, scale or table comes from the program.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02            # every weight matrix: normal(0, INIT_STD)
SAMPLER_BACKEND = "pallas"  # the runtime's generated kernels, pinned


def prng_key(seed: int):
    """A key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def model_config(conf: dict):
    """The program's ``ModelConfig`` with the file's numbers."""
    from repro.configs.base import ModelConfig

    return ModelConfig(**conf["model"])


def make_weights(cfg, seed: int):
    """All weights from ``seed`` in one jitted call: norms are ones
    (float32), every matrix normal(0, ``INIT_STD``) in the served dtype."""
    from repro.models import schema

    abstract = schema.abstract_params(cfg)
    paths = jax.tree_util.tree_leaves_with_path(abstract)

    def init(key):
        leaves = []
        for path, leaf in paths:
            name = jax.tree_util.keystr(path)
            if "norm" in name:
                leaves.append(jnp.ones(leaf.shape, leaf.dtype))
                continue
            k = jax.random.fold_in(key, zlib.crc32(name.encode()))
            leaves.append((jax.random.normal(k, leaf.shape, jnp.float32)
                           * INIT_STD).astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(abstract), leaves)

    return jax.block_until_ready(jax.jit(init)(prng_key(seed)))


class RecordingRuntime:
    """The serving runtime as the engine's decode loop calls it
    (``submit_sample`` and ``flush``), with the logits rows and keys of
    chosen requests kept for the check.  Each such row starts an
    asynchronous copy to the host; `collect` (between steps, once the
    flush is done) keeps the host copy and drops the device buffer."""

    def __init__(self, runtime, engine_ref, watch: set):
        self._rt = runtime
        self._engine = engine_ref
        self.watch = watch          # engine request ids to record
        self.rows: dict = {}        # rid -> [(host row, key, temperature)]
        self._order: "list | None" = None
        self._k = 0
        self._inflight: list = []

    def submit_sample(self, logits_row, key, temperature=1.0, **kw):
        if self._order is None:
            # the engine samples its occupied slots in slot order
            self._order = self._engine().kv.live()
            self._k = 0
        rid = self._order[self._k]
        self._k += 1
        if rid in self.watch:
            logits_row.copy_to_host_async()
            self._inflight.append((rid, logits_row, key, temperature))
        return self._rt.submit_sample(logits_row, key, temperature, **kw)

    def flush(self, *a, **kw):
        self._order = None
        return self._rt.flush(*a, **kw)

    def collect(self) -> None:
        for rid, row, key, temp in self._inflight:
            self.rows.setdefault(rid, []).append(
                (np.asarray(row), np.asarray(key), float(temp)))
        self._inflight = []


def make_engine(cfg, params, serving: dict, sessions: int):
    """``ContinuousEngine`` with a ``ServingRuntime`` attached.  The
    runtime's flush window is longer than any step, so every step's rows
    flush together on the engine's own ``flush()`` and never split by
    timing; the pending queue holds every session, so nothing is shed."""
    from repro import runtime
    from repro.serving.engine import ContinuousEngine

    capacity = int(serving["capacity"])
    rt = runtime.ServingRuntime(backend=SAMPLER_BACKEND, window=3600.0,
                                max_batch=capacity)
    eng = ContinuousEngine(cfg, params, capacity=capacity,
                           max_len=int(serving["max_len"]), runtime=rt,
                           max_pending=max(64, sessions))
    return eng, rt


def warm_up(eng, temperature: float) -> None:
    """Serve ``capacity`` one-token prompts asking 1..capacity tokens:
    every admission, scatter and decode shape, and every sampler flush
    size from ``capacity`` rows down to one, compiles here and not in
    the window.  The batch is empty afterwards, as in a fresh engine."""
    cap = eng.capacity
    for i in range(cap):
        eng.submit(np.ones((1,), np.int32), max_new=i + 1)
    eng.run(temperature=temperature, max_steps=4 * cap + 4)
    if eng.stats()["pending"] or eng.kv.live():
        raise RuntimeError("warm-up did not drain the engine")
