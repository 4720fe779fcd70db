"""Continuous-batching decode tests (PR 9) — the `RequestsCache` slot
pool, the token-granular `ContinuousEngine`, the executor's flush-window
drain, and the version-tolerant tracer shim.
"""

import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import runtime as rtm
from repro.configs.registry import get_config
from repro.core import dispatch
from repro.core.cache import DiskCache
from repro.models.layers import is_tracer
from repro.models.schema import init_params
from repro.runtime.kvcache import RequestsCache
from repro.serving.engine import ContinuousEngine, Engine

rng = np.random.default_rng(23)


# ------------------------------------------------------- RequestsCache
def test_kvcache_admit_release_cycle():
    kv = RequestsCache(2)
    s0 = kv.admit("a", 5)
    s1 = kv.admit("b", 9)
    assert {s0, s1} == {0, 1}
    with pytest.raises(rtm.FleetOverloadError):
        kv.admit("c", 3)
    assert kv.stats()["shed"] == 1
    assert kv.release("a") == s0
    # freed slot leases again
    assert kv.admit("c", 3) == s0
    assert kv.live() == sorted(["c", "b"], key=lambda r: kv.slot_of(r))
    st = kv.stats()
    assert st["admitted"] == 3 and st["released"] == 1 and st["live"] == 2


def test_kvcache_deadline_eviction():
    t = [100.0]
    kv = RequestsCache(2, clock=lambda: t[0])
    kv.admit("a", 4, deadline=5.0)
    kv.admit("b", 4)             # no deadline: never expires
    assert kv.expired() == []
    t[0] = 106.0
    assert kv.expired() == ["a"]
    kv.evict("a", expired=True)
    st = kv.stats()
    assert st["evicted"] == 1 and st["expired"] == 1
    assert kv.expired() == []    # reclaimed leases drop out
    with pytest.raises(KeyError):
        kv.release("a")


def test_kvcache_double_admit_rejected():
    kv = RequestsCache(2)
    kv.admit("a", 1)
    with pytest.raises(ValueError):
        kv.admit("a", 1)


# -------------------------------------------------- continuous engine
@pytest.fixture(scope="module")
def model():
    cfg = get_config("internlm2-1.8b", smoke=True).replace(
        dtype="float32", attention_impl="naive")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _prompt(cfg, L):
    return rng.integers(1, cfg.vocab_size, size=L).astype(np.int32)


def test_continuous_matches_static_greedy(model):
    """One request through the continuous engine decodes the exact same
    greedy tokens as the static-batch engine."""
    cfg, params = model
    prompt = _prompt(cfg, 6)
    ref = Engine(cfg, params, max_len=32).generate(prompt[None], 5)
    eng = ContinuousEngine(cfg, params, capacity=2, max_len=32)
    eng.submit(prompt, max_new=5)
    res = eng.run()
    assert np.array_equal(np.asarray(ref.tokens[0]), res[0].tokens)


def test_requests_join_and_leave_every_step(model):
    """More requests than capacity, mixed prompt lengths and mixed
    max_new: slots recycle mid-stream and every request completes with
    exactly its own token budget."""
    cfg, params = model
    eng = ContinuousEngine(cfg, params, capacity=2, max_len=48)
    lens = [5, 9, 3, 7, 2]
    budgets = [4, 2, 5, 3, 4]
    rids = [eng.submit(_prompt(cfg, L), max_new=m)
            for L, m in zip(lens, budgets)]
    res = eng.run()
    assert len(res) == len(rids)
    for rid, L, m in zip(rids, lens, budgets):
        r = eng.result_for(rid)
        assert r is not None and r.prompt_len == L
        assert r.tokens.shape == (m,)
    st = eng.stats()
    assert st["kv"]["admitted"] == 5 and st["kv"]["live"] == 0
    # continuous batching actually overlapped requests: fewer steps than
    # the sum of sequential budgets
    assert st["steps"] < sum(budgets)


def test_decode_step_is_two_launches_with_runtime(model, tmp_path):
    """The hard per-step launch budget: one uniform decode step over a
    live batch (decode jit + ONE ragged sampler flush) = 2 generated
    launches, regardless of how many requests are live."""
    cfg, params = model
    rt = rtm.ServingRuntime(
        backend="pallas", window=0.25, max_batch=8,
        router=rtm.BackendRouter(),
        manifest=rtm.WarmStartManifest(
            cache=DiskCache("decode_manifest", root=tmp_path)))
    try:
        eng = ContinuousEngine(cfg, params, capacity=3, max_len=48,
                               runtime=rt)
        for L in (5, 9, 3):
            eng.submit(_prompt(cfg, L), max_new=4)
        eng.step(temperature=0.7)   # admission step (pays jit + builds)
        with dispatch.count_launches() as c:
            eng.step(temperature=0.7)
        assert c.delta == 2, c.by_backend
        eng.run(temperature=0.7)
        assert len(eng.done) == 3
    finally:
        rt.close()


def test_deadline_evicts_mid_decode(model):
    cfg, params = model
    eng = ContinuousEngine(cfg, params, capacity=2, max_len=48)
    rid = eng.submit(_prompt(cfg, 4), max_new=1000, deadline=0.0)
    eng.step()                   # admits + samples one token
    time.sleep(0.01)
    eng.step()                   # deadline passed: evicted before decode
    assert rid in eng.evicted_ids
    r = eng.result_for(rid)
    assert r is not None and r.tokens.shape[0] >= 1
    assert eng.stats()["kv"]["expired"] == 1


def test_pending_queue_sheds(model):
    cfg, params = model
    eng = ContinuousEngine(cfg, params, capacity=1, max_len=32,
                           max_pending=2)
    eng.submit(_prompt(cfg, 3))
    eng.submit(_prompt(cfg, 3))
    with pytest.raises(rtm.FleetOverloadError):
        eng.submit(_prompt(cfg, 3))
    assert eng.stats()["pending_shed"] == 1


def test_engine_counters_queue_wait_and_live_rows(model):
    """Counters mode: one queue-wait observation per admitted request
    (submit to the admission that pops it) and one live-rows observation
    per step, summing to the tokens generated."""
    from repro.runtime import observe

    cfg, params = model
    prev = observe.set_mode("counters")
    observe.METRICS.clear()
    try:
        eng = ContinuousEngine(cfg, params, capacity=2, max_len=48)
        for L, m in ((5, 3), (4, 2), (6, 2)):
            eng.submit(_prompt(cfg, L), max_new=m)
        eng.run()
        hists = observe.METRICS.snapshot()["histograms"]
    finally:
        observe.set_mode(prev)
        observe.METRICS.clear()
    wait = hists["engine_queue_wait_seconds"][""]
    rows = hists["engine_live_rows"][""]
    assert wait["count"] == 3 and wait["sum"] >= 0.0
    st = eng.stats()
    assert rows["count"] == st["steps"]
    assert rows["sum"] == st["tokens_generated"] == 7
    text = observe.metrics_text({"histograms": hists})
    assert f"repro_engine_live_rows_count {st['steps']}" in text
    assert "# TYPE repro_engine_queue_wait_seconds histogram" in text


def test_named_scopes_leave_the_decode_program_unchanged(model, monkeypatch):
    """``decode_attention``/``kv_write`` are op metadata only: with the
    scopes made no-ops the compiled decode step is the same program,
    operation names included, once metadata is stripped."""
    import contextlib
    import re

    from repro.models import transformer

    cfg, params = model
    cache = transformer.init_cache(cfg, 2, 32)
    args = (params, cache, jnp.zeros((2, 1), jnp.int32), jnp.int32(4))

    def compiled():   # a fresh function: no trace cached from before
        text = jax.jit(lambda p, c, t, pos: transformer.decode_step(
            cfg, p, c, t, pos)).lower(*args).compile().as_text()
        # drop each instruction's metadata and the source-location tables
        bare = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
        return text, [line for line in bare.splitlines() if line.strip()
                      and not re.match(r"(FileNames|FunctionNames|"
                                       r"FileLocations|StackFrames|\d+ )",
                                       line)]

    scoped, plain = compiled()
    assert "/decode_attention/" in scoped and "/kv_write/" in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    unscoped, plain_too = compiled()
    assert "/decode_attention/" not in unscoped
    assert plain == plain_too


# ------------------------------------------- grouped-query decode attention
def _expanded_decode_attention(q, k_cache, v_cache, pos, scale):
    """The decode formula before grouped-query attention, kept as the
    oracle: float32 upcast, KV heads repeated H/Hk times, plain softmax.
    -> float32 (B, 1, H, dh)."""
    B, _, H, dh = q.shape
    rep = H // k_cache.shape[2]
    k = jnp.repeat(k_cache.astype(jnp.float32), rep, axis=2)
    v = jnp.repeat(v_cache.astype(jnp.float32), rep, axis=2)
    s = jnp.einsum("bhd,bshd->bhs",
                   q.reshape(B, H, dh).astype(jnp.float32), k) * scale
    valid = jnp.arange(k.shape[1])[None, :] <= jnp.reshape(pos, (-1, 1))
    s = jnp.where(valid[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bshd->bhd", p, v).reshape(B, 1, H, dh)


@pytest.mark.parametrize("jit", [True, False], ids=["jit", "concrete"])
@pytest.mark.parametrize("pos_kind", ["scalar", "per_row"])
@pytest.mark.parametrize("H,Hk", [(4, 4), (4, 2), (8, 1)])
def test_grouped_decode_attention_matches_expanded_formula(H, Hk, pos_kind,
                                                           jit):
    """Grouped-query decode over bfloat16 caches computes what the
    upcast-and-repeat formula computed: the bf16 products are exact in
    float32, so only the summation order differs."""
    from repro.models.attention import decode_attention

    B, Smax, dh = 3, 40, 16
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(10 * H + Hk), 3)
    q = jax.random.normal(kq, (B, 1, H, dh)).astype(jnp.bfloat16)
    k_cache = jax.random.normal(kk, (B, Smax, Hk, dh)).astype(jnp.bfloat16)
    v_cache = jax.random.normal(kv, (B, Smax, Hk, dh)).astype(jnp.bfloat16)
    pos = (jnp.int32(17) if pos_kind == "scalar"
           else jnp.array([0, 17, Smax - 1], jnp.int32))
    scale = dh ** -0.5

    def attend(q_):
        return decode_attention(q_, k_cache, v_cache, pos, scale=scale)

    fn = jax.jit(attend) if jit else attend
    ref = np.asarray(_expanded_decode_attention(q, k_cache, v_cache, pos,
                                                scale))
    # a float32 q holding the bf16 values: the result before the final cast
    out = fn(q.astype(jnp.float32))
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-6)
    # bf16 in, bf16 out: the same values rounded once
    out_bf16 = fn(q)
    assert out_bf16.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out_bf16, np.float32),
        np.asarray(jnp.asarray(ref).astype(jnp.bfloat16), np.float32),
        rtol=2 ** -7, atol=1e-6)


def _jaxpr_values(jaxpr):
    """Every value defined in a jaxpr and in the jaxprs its equations
    hold (jit, scan, cond bodies), their inputs included."""
    from jax.extend import core as jcore

    for eqn in jaxpr.eqns:
        yield from eqn.outvars
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (tuple, list))
                        else (param,)):
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jcore.Jaxpr):
                    yield from sub.invars
                    yield from _jaxpr_values(sub)


def test_decode_step_holds_no_float32_or_head_repeated_cache():
    """The jitted decode step of a GQA model reads its bf16 KV cache as
    it lies: no value has the head-repeated shape (B, Smax, H, dh), and
    none is a float32 (B, Smax, Hk, dh) copy of a cache."""
    from repro.models import transformer

    cfg = get_config("internlm2-1.8b", smoke=True)
    H, Hk, dh = cfg.num_heads, cfg.num_kv_heads, cfg.dh
    assert H > Hk and jnp.dtype(cfg.dtype) == jnp.bfloat16
    B, Smax = 2, 32
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = transformer.init_cache(cfg, B, Smax)
    step = jax.jit(lambda p, c, t, pos: transformer.decode_step(
        cfg, p, c, t, pos))
    closed = jax.make_jaxpr(step)(params, cache, jnp.zeros((B, 1), jnp.int32),
                                  jnp.int32(4))
    avals = [v.aval for v in _jaxpr_values(closed.jaxpr)]
    cache_shaped = [a for a in avals if a.shape == (B, Smax, Hk, dh)]
    assert cache_shaped, "the walk must reach the per-layer cache"
    assert [a for a in avals if a.shape == (B, Smax, H, dh)] == []
    assert [a for a in cache_shaped if a.dtype == jnp.float32] == []


def test_rejects_non_attention_archs(model):
    cfg = get_config("rwkv6-7b", smoke=True).replace(dtype="float32")
    with pytest.raises(ValueError):
        ContinuousEngine(cfg, params=None, capacity=1, max_len=8)


# --------------------------------------------- executor window drain
@pytest.fixture
def rt(tmp_path):
    r = rtm.ServingRuntime(
        backend="pallas", window=0.25, max_batch=4,
        router=rtm.BackendRouter(),
        manifest=rtm.WarmStartManifest(
            cache=DiskCache("drain_manifest", root=tmp_path)))
    yield r
    r.close()


def test_flush_classification_counters(rt):
    """stats() separates full flushes (hit max_batch) from window/flush
    flushes (timer or explicit flush drained a partial batch)."""
    N = 256
    rows = [rng.standard_normal(N).astype(np.float32) for _ in range(4)]
    futs = [rt.submit_softmax(r) for r in rows]       # max_batch=4: full
    [f.result(timeout=60) for f in futs]
    f = rt.submit_softmax(rows[0])                    # partial, forced
    rt.flush()
    f.result(timeout=60)
    ex = rt.executor.stats()
    assert ex["full_flushes"] == 1
    assert ex["window_flushes"] == 1
    assert ex["flushes"] == 2


def test_due_batch_drains_queued_rows(rt):
    """Satellite fix: rows arriving while an earlier batch flushes are
    pulled into their due batch at flush time (up to max_batch) instead
    of waiting out a fresh window."""
    ex = rt.executor
    N = 128
    row = rng.standard_normal(N).astype(np.float32)
    release = threading.Event()

    def slow_post(r):
        release.wait(timeout=60)
        return 0

    # batch A (slow post holds the worker inside its flush long enough
    # for B's stragglers to queue), batch B due at the same time
    fa = ex.submit("softmax", row, shared={"stable": True},
                   key_extra=(True,), post=slow_post)
    fb1 = ex.submit("softmax", rng.standard_normal(2 * N).astype(np.float32),
                    shared={"stable": True}, key_extra=(True,))
    rt.flush(wait=False)         # both batches go due now
    # worker is stuck in A's post; this row lands in a NEW forming batch
    # under B's key and must be drained into B when B flushes
    time.sleep(0.05)
    fb2 = ex.submit("softmax", rng.standard_normal(2 * N).astype(np.float32),
                    shared={"stable": True}, key_extra=(True,))
    release.set()
    assert fb1.result(timeout=60) is not None
    assert fb2.result(timeout=60) is not None
    st = ex.stats()
    assert st["drained_rows"] >= 1, st


# ------------------------------------------------------- tracer shim
def test_is_tracer_version_tolerant():
    assert not is_tracer(jnp.ones((2,)))
    assert not is_tracer(3.0)
    seen = {}

    def probe(x):
        seen["traced"] = is_tracer(x)
        return x * 2

    jax.jit(probe)(jnp.ones((2,)))
    assert seen["traced"] is True


def test_engine_sample_uses_shim(model, tmp_path):
    """Engine._sample falls back to jax sampling under trace and routes
    concrete logits through the runtime — via is_tracer, not a direct
    jax.core.Tracer reference."""
    import repro.serving.engine as engine_mod

    assert "jax.core.Tracer" not in open(engine_mod.__file__).read()
    cfg, params = model
    rt = rtm.ServingRuntime(
        backend="pallas", window=0.05, max_batch=4,
        router=rtm.BackendRouter(),
        manifest=rtm.WarmStartManifest(
            cache=DiskCache("shim_manifest", root=tmp_path)))
    try:
        eng = Engine(cfg, params, max_len=32, runtime=rt)
        res = eng.generate(_prompt(cfg, 4)[None], 3, temperature=0.8)
        assert res.tokens.shape == (1, 3)
    finally:
        rt.close()
