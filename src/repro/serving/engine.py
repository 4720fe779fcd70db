"""Batched serving engine: prefill + stepwise decode with KV/state cache.

Static-batch engine with greedy/temperature sampling; the request queue
gives continuous-batching semantics at prompt granularity (finished
sequences are replaced at the next prefill boundary).  Per-slot position
decode (token-granular continuous batching) is scaffolded behind
`uniform_pos` — see DESIGN.md §5.

Runtime-routed sampling (PR 5, DESIGN.md §9): pass a
`repro.runtime.ServingRuntime` and temperature sampling computes its
softmax through the runtime — ONE fused 2-launch row schedule for the
whole logits block, backend picked per bucket by the latency router,
and the call recorded into the warm-start manifest.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import transformer
from repro.models.layers import is_tracer
from repro.runtime import observe
from repro.sharding.partition import MeshContext, NULL_CTX


@dataclass
class GenerationResult:
    tokens: np.ndarray          # (B, steps)
    steps: int
    prefill_len: int


class Engine:
    def __init__(self, cfg: ModelConfig, params, ctx: MeshContext = NULL_CTX,
                 max_len: int = 512, runtime=None):
        self.cfg = cfg
        self.params = params
        self.ctx = ctx
        self.max_len = max_len
        self.runtime = runtime  # optional repro.runtime.ServingRuntime
        self._prefill = jax.jit(
            lambda p, b: transformer.prefill(cfg, p, b, ctx, max_len=max_len))
        self._decode = jax.jit(
            lambda p, c, t, pos: transformer.decode_step(cfg, p, c, t, pos, ctx))

    def _sample(self, logits, key, temperature: float):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if self.runtime is not None and not is_tracer(logits):
            # runtime-routed path: RTCG softmax over the concrete logits
            # block (2 generated launches, auto-routed backend) + per-row
            # host-side categorical draw
            return self.runtime.sample(logits, key, temperature)
        return jax.random.categorical(key, logits / temperature, axis=-1).astype(jnp.int32)

    def generate(self, prompts: np.ndarray, steps: int, *, temperature: float = 0.0,
                 seed: int = 0, extra_batch: dict | None = None) -> GenerationResult:
        """prompts: (B, S) int32. Greedy/temperature decode for `steps`."""
        B, S = prompts.shape
        assert S + steps <= self.max_len, (S, steps, self.max_len)
        batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
        if extra_batch:
            batch.update(extra_batch)
        logits, cache = self._prefill(self.params, batch)
        key = jax.random.PRNGKey(seed)
        out = []
        tok = self._sample(logits, key, temperature)[:, None]
        out.append(tok)
        for i in range(steps - 1):
            key, sub = jax.random.split(key)
            logits, cache = self._decode(self.params, cache, tok, jnp.int32(S + i))
            tok = self._sample(logits, sub, temperature)[:, None]
            out.append(tok)
        return GenerationResult(np.concatenate([np.asarray(t) for t in out], axis=1),
                                steps, S)


@dataclass
class ServedResult:
    """One finished request, mapped back to its submitter.

    ``prompt`` is the *original* unpadded prompt (the engine left-pads a
    block to its longest member; that padding never leaks out here),
    ``tokens`` the generated continuation, ``padded_len`` the block
    width this request was actually served at.
    """

    request_id: int
    prompt: np.ndarray
    prompt_len: int
    tokens: np.ndarray
    padded_len: int = 0

    @property
    def sequence(self) -> np.ndarray:
        """Original prompt + generated tokens, padding stripped."""
        return np.concatenate([np.asarray(self.prompt, np.int32),
                               np.asarray(self.tokens, np.int32)])


@dataclass
class RequestQueue:
    """Prompt-granular continuous batching: keeps the static batch full by
    refilling finished slots from a pending queue between generate calls.

    Requests carry per-request ids and original prompt lengths through
    `run` (PR 5): ``done`` holds `ServedResult` records instead of bare
    padded rows in pop order, so a caller can map each result back to
    its submitter (`result_for`) and read padding-free sequences."""
    pending: list = field(default_factory=list)   # (request_id, prompt)
    done: list = field(default_factory=list)      # ServedResult
    _next_id: int = 0

    def submit(self, prompt: np.ndarray, request_id: "int | None" = None) -> int:
        """Queue one prompt; returns the id its result will carry."""
        if request_id is None:
            request_id = self._next_id
        self._next_id = max(self._next_id, request_id) + 1
        self.pending.append((request_id, np.asarray(prompt, np.int32)))
        return request_id

    def run(self, engine: Engine, batch_size: int, steps: int, pad_id: int = 0,
            temperature: float = 0.0, seed: int = 0):
        while self.pending:
            block = [self.pending.pop(0) for _ in range(min(batch_size, len(self.pending)))]
            S = max(len(p) for _, p in block)
            arr = np.full((len(block), S), pad_id, np.int32)
            for i, (_, p) in enumerate(block):
                arr[i, S - len(p):] = p   # left-pad
            res = engine.generate(arr, steps, temperature=temperature,
                                  seed=seed)
            for i, (rid, p) in enumerate(block):
                self.done.append(ServedResult(
                    request_id=rid, prompt=p, prompt_len=len(p),
                    tokens=np.asarray(res.tokens[i]), padded_len=S))
        return self.done

    def result_for(self, request_id: int) -> "ServedResult | None":
        """Look a finished request up by the id `submit` returned."""
        for r in self.done:
            if r.request_id == request_id:
                return r
        return None


class _LiveRequest:
    """Engine-side record of one slot lease (host bookkeeping only)."""

    __slots__ = ("request_id", "prompt", "max_new", "tokens")

    def __init__(self, request_id: int, prompt: np.ndarray, max_new: int):
        self.request_id = request_id
        self.prompt = prompt
        self.max_new = max_new
        self.tokens: list = []


class ContinuousEngine:
    """Token-granular continuous batching: requests join and leave the
    live decode batch *every step*, not at prefill boundaries.

    The device state is ONE fixed-shape batch cache
    (``transformer.init_cache(cfg, capacity, max_len)``); requests lease
    slots of it through a `repro.runtime.kvcache.RequestsCache` pool
    (admission, deadline eviction, `FleetOverloadError` shed).  The
    engine builds on the ``uniform_pos`` scaffold (DESIGN.md §5): every
    live slot shares one write position, so a step is ONE jitted
    ``decode_step`` over the whole batch.  A new request's prompt is
    prefilled as a single ``(1, max_len)`` row (left-padded so the
    prompt *ends* at the current position — one jit trace regardless of
    prompt length) and scattered into its leased slot; mixed prompt
    lengths therefore coexist in one batch without per-length retraces.

    Sampling flows through the serving runtime's *ragged* sampler
    micro-batch: each step's live logits rows submit as one
    ``softmax.cdf`` flush — 2 generated-kernel launches per step for
    the whole batch, with the inverse-CDF cumsum fused into the flush's
    epilogue launch (the per-request post-step is a single host
    ``searchsorted``).

    Attention-mixer architectures only: non-attention mixers (rwkv6 /
    mamba) carry running recurrent state, which full-width row prefill
    would corrupt for the co-resident slots' timeline.
    """

    def __init__(self, cfg: ModelConfig, params, ctx: MeshContext = NULL_CTX,
                 capacity: int = 4, max_len: int = 512, runtime=None,
                 pad_id: int = 0, eos_id: "int | None" = None,
                 max_pending: int = 64):
        from repro.runtime.fleet import FleetOverloadError
        from repro.runtime.kvcache import RequestsCache

        mixers = {m for m, _ in transformer.slot_plan(cfg)}
        if mixers - {"attn"}:
            raise ValueError(
                f"ContinuousEngine requires attention mixers only, got "
                f"{sorted(mixers)} (recurrent state cannot be re-prefilled "
                "per slot)")
        if cfg.is_encdec:
            raise ValueError("ContinuousEngine does not serve enc-dec models")
        self.cfg = cfg
        self.params = params
        self.ctx = ctx
        self.capacity = int(capacity)
        self.max_len = int(max_len)
        self.runtime = runtime
        self.pad_id = int(pad_id)
        self.eos_id = eos_id
        self.max_pending = int(max_pending)
        self._overload_error = FleetOverloadError

        self.kv = RequestsCache(self.capacity)
        self.cache = transformer.init_cache(cfg, self.capacity, self.max_len)
        self.pos = 0                      # uniform filled-column count
        self._slots: list = [None] * self.capacity   # slot -> _LiveRequest
        self._tok = np.full((self.capacity, 1), self.pad_id, np.int32)
        # (rid, prompt, max_new, deadline, submit time)
        self._pending: deque = deque()
        self._next_id = 0
        self._key = jax.random.PRNGKey(0)
        self._steps = 0
        self._generated = 0
        self._pending_shed = 0
        self.done: list = []              # ServedResult, completion order
        self.evicted_ids: list = []

        def admit_fn(p, tokens, last_index):
            cache = transformer.init_cache(cfg, 1, self.max_len)
            out = transformer.forward(cfg, p, {"tokens": tokens}, ctx,
                                      mode="prefill", cache=cache)
            x_last = lax.dynamic_slice_in_dim(out["x"], last_index, 1, axis=1)
            logits = transformer.logits_from_hidden(cfg, p, x_last, ctx)
            return logits[:, 0], out["cache"]

        def scatter_fn(full, row, slot):
            return jax.tree.map(
                lambda f, r: lax.dynamic_update_index_in_dim(
                    f, r[:, 0], slot, axis=1), full, row)

        self._admit = jax.jit(admit_fn)
        self._scatter = jax.jit(scatter_fn)
        self._decode = jax.jit(
            lambda p, c, t, pos: transformer.decode_step(cfg, p, c, t, pos,
                                                         ctx))

    # -- request intake ---------------------------------------------------
    def submit(self, prompt, max_new: int = 16,
               deadline: "float | None" = None,
               request_id: "int | None" = None) -> int:
        """Queue one prompt; returns its request id.  A full pending
        queue sheds the request with `FleetOverloadError` (the engine's
        bounded-admission contract — callers see backpressure, requests
        never queue unboundedly)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if not (1 <= prompt.shape[0] < self.max_len):
            raise ValueError(
                f"prompt length {prompt.shape[0]} outside [1, {self.max_len})")
        if len(self._pending) >= self.max_pending:
            self._pending_shed += 1
            raise self._overload_error(
                f"pending queue full ({self.max_pending}); request shed")
        if request_id is None:
            request_id = self._next_id
        self._next_id = max(self._next_id, request_id) + 1
        self._pending.append((request_id, prompt, int(max_new), deadline,
                              time.monotonic()))
        return request_id

    # -- the decode loop --------------------------------------------------
    def _live_slots(self) -> list:
        return [s for s in range(self.capacity) if self._slots[s] is not None]

    def _finish(self, slot: int, evicted: bool = False,
                expired: bool = False) -> None:
        req = self._slots[slot]
        self._slots[slot] = None
        self._tok[slot, 0] = self.pad_id
        if evicted:
            self.kv.evict(req.request_id, expired=expired)
            self.evicted_ids.append(req.request_id)
        else:
            self.kv.release(req.request_id)
        self.done.append(ServedResult(
            request_id=req.request_id, prompt=req.prompt,
            prompt_len=int(req.prompt.shape[0]),
            tokens=np.asarray(req.tokens, np.int32),
            padded_len=self.max_len))

    def _admit_pending(self, rows: dict) -> None:
        """FIFO admission: lease slots to queued prompts that fit the
        current uniform position (an empty batch re-anchors the position
        to the first prompt's length).  Each admission is one fixed-
        shape ``(1, max_len)`` prefill + one scatter (an ``admit`` span);
        its first-token logits row joins this step's sampler flush in
        ``rows``."""
        while self._pending and self.kv.has_free_slot():
            rid, prompt, max_new, deadline, t_submit = self._pending[0]
            L = int(prompt.shape[0])
            if not self._live_slots() and not rows:
                self.pos = L           # empty batch: re-anchor the clock
            elif L > self.pos:
                break                  # FIFO head waits for pos to grow
            if self.pos >= self.max_len:
                break                  # no room to decode even one token
            self._pending.popleft()
            if observe._MODE:
                observe.observe_hist("engine_queue_wait_seconds", (),
                                     time.monotonic() - t_submit)
            slot = self.kv.admit(rid, L, deadline=deadline)
            tok = observe.span_begin("admit", "engine")
            try:
                self._slots[slot] = _LiveRequest(rid, prompt, max_new)
                toks = np.full((1, self.max_len), self.pad_id, np.int32)
                toks[0, self.pos - L:self.pos] = prompt
                logits1, row_cache = self._admit(
                    self.params, jnp.asarray(toks), jnp.int32(self.pos - 1))
                self.cache = self._scatter(self.cache, row_cache,
                                           jnp.int32(slot))
                rows[slot] = logits1[0]
            finally:
                observe.span_end(tok, args=None if tok is None else
                                 {"prompt_len": L, "slot": slot})

    def _sample_rows(self, rows: dict, temperature: float) -> dict:
        """One token per live row — ONE ragged runtime flush when a
        runtime is attached and temperature > 0 (2 generated launches
        for the whole step), host argmax for greedy decoding.  A
        ``sample`` span, whose ``path`` names which."""
        if not rows:
            return {}
        path = "greedy" if temperature == 0.0 else \
            "runtime" if self.runtime is not None else "categorical"
        tok = observe.span_begin("sample", "engine")
        try:
            if path == "greedy":
                return {s: int(np.argmax(np.asarray(r)))
                        for s, r in rows.items()}
            subkeys = {}
            for s in sorted(rows):
                self._key, subkeys[s] = jax.random.split(self._key)
            if path == "runtime":
                futs = {s: self.runtime.submit_sample(rows[s], subkeys[s],
                                                      temperature)
                        for s in sorted(rows)}
                self.runtime.flush()
                return {s: int(f.result(timeout=60.0))
                        for s, f in futs.items()}
            return {s: int(jax.random.categorical(
                subkeys[s], jnp.asarray(rows[s]) / temperature))
                for s in sorted(rows)}
        finally:
            observe.span_end(tok, args=None if tok is None else
                             {"rows": len(rows), "path": path})

    def step(self, temperature: float = 0.0) -> int:
        """One uniform decode step: evict expired leases, advance every
        live slot by one token, admit queued requests into freed slots,
        sample all fresh logits rows in one flush.  Returns the number
        of live requests after the step.

        Each step is a ``step`` span (``engine.step`` in a profile) +
        latency observation (PR 10), holding one span per phase:
        ``decode``, ``admit`` (one per admission), ``sample`` (the
        sampler's ragged flush parents under it) and ``retire``."""
        tok = observe.span_begin("step", "engine")
        t0 = time.perf_counter()
        try:
            return self._step(temperature)
        finally:
            if observe._MODE:
                observe.observe_hist("decode_step_seconds", (),
                                     time.perf_counter() - t0)
            if tok is not None:
                observe.span_end(tok, args={"live": len(self._live_slots()),
                                            "step": self._steps})

    def _step(self, temperature: float = 0.0) -> int:
        for rid in self.kv.expired():
            slot = self.kv.slot_of(rid)
            if slot is not None:
                self._finish(slot, evicted=True, expired=True)
        rows: dict = {}
        live = self._live_slots()
        if live:
            tok = observe.span_begin("decode", "engine")
            try:
                logits, self.cache = self._decode(
                    self.params, self.cache, jnp.asarray(self._tok),
                    jnp.int32(self.pos))
                self.pos += 1
                for s in live:
                    rows[s] = logits[s]
            finally:
                observe.span_end(tok)
        self._admit_pending(rows)
        toks = self._sample_rows(rows, temperature)
        if observe._MODE:
            observe.observe_hist("engine_live_rows", (), float(len(toks)))
        tok = observe.span_begin("retire", "engine")
        try:
            self._steps += 1
            self._generated += len(toks)
            for s, t in toks.items():
                req = self._slots[s]
                req.tokens.append(t)
                self._tok[s, 0] = t
                if (len(req.tokens) >= req.max_new
                        or (self.eos_id is not None and t == self.eos_id)):
                    self._finish(s)
            if self.pos >= self.max_len:
                # cache exhausted: every survivor ends truncated at max_len
                for s in self._live_slots():
                    self._finish(s)
            return len(self._live_slots())
        finally:
            observe.span_end(tok)

    def run(self, temperature: float = 0.0, max_steps: int = 100000) -> list:
        """Step until the pending queue and the live batch drain; ->
        `ServedResult` list in completion order."""
        steps = 0
        while (self._pending or self._live_slots()) and steps < max_steps:
            self.step(temperature=temperature)
            steps += 1
        return self.done

    def result_for(self, request_id: int) -> "ServedResult | None":
        for r in self.done:
            if r.request_id == request_id:
                return r
        return None

    def stats(self) -> dict:
        return {
            "kv": self.kv.stats(),
            "pos": self.pos,
            "steps": self._steps,
            "tokens_generated": self._generated,
            "pending": len(self._pending),
            "pending_shed": self._pending_shed,
            "completed": len(self.done),
            "evicted": len(self.evicted_ids),
        }
