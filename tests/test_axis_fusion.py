"""Axis-aware fusion tests (planner v3) — row-wise reductions over 2-D
operands.

Covers: ``(B,)``-shaped lazy row reduces and their launch schedules
(batched softmax — stable included — is exactly 2 launches), same-wave
``_acc`` chaining, common-subexpression hoisting in generated sources,
broadcasting leaves of unequal length (``(B, 1)`` / ``(N,)`` / scalar)
inside one epilogue, int32/float64 dtype faithfulness, 2-D shape
bucketing (driver reuse across a size sweep, per-bucket-pair tuning),
the model-level `fused_softmax` batched path, and the planner-backed
`rtcg_rmsnorm` against the hand-written Pallas kernel — with
property-style sweeps across batch sizes and bucket-boundary row
lengths.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    from _hypothesis_stub import given, settings, st

import repro.core.array as ga
from repro.core import backends, dispatch

rng = np.random.default_rng(7)

# col-bucket boundary: ceil(N/128) lane groups, bucket flips at pow2 groups
BOUNDARY_NS = (1023, 1024, 1025)
BATCHES = (1, 7, 32)


@pytest.fixture(scope="module", params=["pallas", "xla"], autouse=True)
def rtcg_backend(request):
    """Run the whole axis-aware suite once per execution backend (PR 4):
    row-wave schedules, `_acc` chaining, broadcast-arg binding and
    bucket-reuse guarantees must hold identically on pallas and xla."""
    import os

    old = os.environ.get("REPRO_BACKEND")
    os.environ["REPRO_BACKEND"] = request.param
    yield request.param
    if old is None:
        os.environ.pop("REPRO_BACKEND", None)
    else:
        os.environ["REPRO_BACKEND"] = old


def _launches(fn):
    with dispatch.count_launches() as c:
        out = fn()
    return out, c.delta


# ------------------------------------------------- row-wise reductions
@pytest.mark.parametrize("B", BATCHES)
@pytest.mark.parametrize("n", BOUNDARY_NS)
def test_row_reduce_shapes_and_values(B, n):
    x = rng.standard_normal((B, n)).astype(np.float32)
    X = ga.to_gpu(x)
    s = X.sum(axis=-1)
    assert s.shape == (B,)
    got, delta = _launches(lambda: s.value)
    assert delta == 1
    np.testing.assert_allclose(np.asarray(got), x.sum(-1), atol=1e-2)
    mx, delta = _launches(lambda: X.max(axis=-1).value)
    assert delta == 1
    np.testing.assert_allclose(np.asarray(mx), x.max(-1), rtol=1e-6)


@pytest.mark.parametrize("B", BATCHES)
@pytest.mark.parametrize("n", BOUNDARY_NS)
def test_batched_softmax_exactly_two_launches(B, n):
    """The acceptance contract: a whole (B, N) batch through the planner
    is ONE row wave + ONE fused 2-D epilogue — for stable softmax too
    (max and shifted-exp sum share the wave via in-kernel chaining)."""
    x = (rng.standard_normal((B, n)) * 4).astype(np.float32)
    X = ga.to_gpu(x)
    ref = np.asarray(jax.nn.softmax(jnp.asarray(x), axis=-1))
    sm, delta = _launches(lambda: ga.softmax(X).value)
    assert delta == 2
    np.testing.assert_allclose(np.asarray(sm), ref, atol=1e-5)
    sm2, delta2 = _launches(lambda: ga.softmax(X, stable=True).value)
    assert delta2 == 2
    np.testing.assert_allclose(np.asarray(sm2), ref, atol=1e-5)


def test_stable_softmax_single_wave_structure():
    """max + shifted-exp-sum land in ONE wave (dependency resolved as an
    in-kernel _acc reference), not two dependent launches."""
    x = rng.standard_normal((4, 600)).astype(np.float32)
    X = ga.to_gpu(x)
    sm = ga.softmax(X, stable=True)
    sched = ga.plan_many([sm])
    assert len(sched.steps) == 1
    assert len(sched.steps[0].nodes) == 2         # max + shifted sum
    assert len(sched.epilogues) == 1
    assert sched.kernel_launches == 2
    snips = sched.steps[0].snippet
    assert any("_acc0" in s for s in snips)       # same-wave chaining


def test_row_mean_host_folds():
    """.mean(axis=-1) = row-sum wave + /n on the host: 1 launch, (B,)."""
    x = rng.standard_normal((5, 700)).astype(np.float32)
    X = ga.to_gpu(x)
    m = X.mean(axis=-1)
    assert m.shape == (5,)
    got, delta = _launches(lambda: m.value)
    assert delta == 1
    np.testing.assert_allclose(np.asarray(got), x.mean(-1), atol=1e-5)


def test_row_reduce_unfused_baseline():
    """fuse=False materializes the map first: 2 launches, same numbers."""
    x = rng.standard_normal((3, 500)).astype(np.float32)
    X = ga.to_gpu(x)
    got, delta = _launches(lambda: (X * 2 + 1).sum(axis=-1, fuse=False).value)
    assert delta == 2
    np.testing.assert_allclose(np.asarray(got), (x * 2 + 1).sum(-1), atol=1e-2)


# --------------------------------------------------- dtype faithfulness
@pytest.mark.parametrize("B", (1, 7))
@pytest.mark.parametrize("n", BOUNDARY_NS)
def test_int32_row_reductions_exact(B, n):
    xi = rng.integers(-1000, 1000, (B, n)).astype(np.int32)
    XI = ga.to_gpu(xi)
    s = XI.sum(axis=-1)
    assert jnp.dtype(s.dtype) == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(s.value), xi.astype(np.int64).sum(-1).astype(np.int32))
    np.testing.assert_array_equal(np.asarray(XI.max(axis=-1).value), xi.max(-1))
    np.testing.assert_array_equal(np.asarray(XI.min(axis=-1).value), xi.min(-1))


def test_float64_row_plans_canonicalize():
    """float64 leaves follow jax_enable_x64 (canonical dtype), and the
    row schedule stays correct either way."""
    x = rng.standard_normal((4, 300))
    X = ga.to_gpu(x)
    want = jnp.dtype(jax.dtypes.canonicalize_dtype(jnp.float64))
    assert jnp.dtype(X.dtype) == want
    got, delta = _launches(lambda: (X.exp() / X.exp().sum(axis=-1)).value)
    assert delta == 2
    ref = jax.nn.softmax(jnp.asarray(x).astype(want), axis=-1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


# --------------------------------------------- broadcasting leaves
def test_broadcast_leaves_in_one_epilogue():
    """(B,1)-vs-(B,N), (N,)-vs-(B,N) and 1-element leaves fuse into one
    kernel instead of raising on mismatched sizes."""
    B, N = 6, 400
    x = rng.standard_normal((B, N)).astype(np.float32)
    w = rng.standard_normal(N).astype(np.float32)
    c = rng.standard_normal((B, 1)).astype(np.float32)
    one = np.asarray([2.5], np.float32)
    X, W, C = ga.to_gpu(x), ga.to_gpu(w), ga.to_gpu(c)
    out, delta = _launches(lambda: (X * W + C - ga.to_gpu(one)).value)
    assert delta == 1                     # ONE fused row-layout kernel
    np.testing.assert_allclose(np.asarray(out), x * w + c - 2.5, atol=1e-5)


def test_broadcast_leaf_kind_classification():
    assert ga._leaf_kind(np.zeros((6, 400), np.float32), 6, 400) == "full"
    assert ga._leaf_kind(np.zeros((6, 1), np.float32), 6, 400) == "row"
    assert ga._leaf_kind(np.zeros((400,), np.float32), 6, 400) == "col"
    assert ga._leaf_kind(np.zeros((1, 400), np.float32), 6, 400) == "col"
    assert ga._leaf_kind(np.zeros((1,), np.float32), 6, 400) == "scalar"
    with pytest.raises(ValueError):
        ga._leaf_kind(np.zeros((3, 7), np.float32), 6, 400)


def test_reduce_free_broadcast_chain_plans_row_layout():
    """v1 plan() upgrades to the row layout when leaves broadcast."""
    x = rng.standard_normal((3, 200)).astype(np.float32)
    w = rng.standard_normal(200).astype(np.float32)
    p = ga.plan((ga.to_gpu(x) * ga.to_gpu(w))._expr)
    assert p.axis == -1 and p.geometry == (3, 200)
    np.testing.assert_allclose(np.asarray(p.launch()), x * w, rtol=1e-5)


# ------------------------------------------------- CSE in generated source
def test_cse_sibling_row_stats_share_one_chain():
    x = rng.standard_normal((4, 900)).astype(np.float32)
    X = ga.to_gpu(x)
    chain = X * 2 + 1
    sched = ga.plan_many([chain.min(axis=-1), chain.max(axis=-1),
                          chain.sum(axis=-1)])
    assert sched.kernel_launches == 1
    wave = sched.steps[0]
    assert len(wave.prelude) == 1         # the chain hoisted once
    assert wave.snippet == ["_t0"] * 3    # all accumulators reuse it
    (lo, hi, tot), delta = _launches(sched.launch)
    assert delta == 1
    ref = x * 2 + 1
    np.testing.assert_allclose(np.asarray(lo), ref.min(-1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(hi), ref.max(-1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(tot), ref.sum(-1), atol=1e-2)


def test_cse_across_epilogue_roots():
    """Structurally-equal subtrees built twice hoist into one temp."""
    x = rng.standard_normal(800).astype(np.float32)
    X = ga.to_gpu(x)
    sched = ga.plan_many([X.exp() * 2, X.exp() + 1])   # two distinct exp nodes
    epi = sched.epilogues[0]
    assert len(epi.prelude) == 1 and "expf" in epi.prelude[0]
    a, b = sched.launch()
    np.testing.assert_allclose(np.asarray(a), np.exp(x) * 2, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(b), np.exp(x) + 1, rtol=1e-5)


# --------------------------------------------------- 2-D bucketing
def test_row_driver_reuse_across_bucket():
    """An (B, N) sweep inside one (batch, row-length) bucket pair reuses
    ONE compiled driver per generated kernel — the 2-D bucketing bound."""
    X0 = ga.to_gpu(rng.standard_normal((8, 900)).astype(np.float32))
    (X0.tanh().sum(axis=-1)).value          # warm: compile wave driver
    c0 = dispatch.compile_count()
    for B, N in ((8, 899), (7, 950), (5, 1000), (8, 1024)):
        x = rng.standard_normal((B, N)).astype(np.float32)
        v = ga.to_gpu(x).tanh().sum(axis=-1).value
        np.testing.assert_allclose(np.asarray(v), np.tanh(x).sum(-1), atol=1e-3)
    assert dispatch.compile_count() == c0   # same bucket pair: zero rebuilds


def test_bucket_pair_helpers():
    assert dispatch.bucket_cols(1) == 128
    assert dispatch.bucket_cols(1024) == 1024
    assert dispatch.bucket_cols(1025) == 2048
    assert dispatch.rc_bucket(7, 900) == dispatch.rc_bucket(8, 1024)
    assert dispatch.rc_bucket(7, 900) != dispatch.rc_bucket(9, 900)
    assert dispatch.bucket_batch(1, 1) == 1
    assert dispatch.bucket_batch(7, 8) == 8
    # TPU block rule: a multiple of 8 rows, or the whole padded batch
    assert dispatch.batch_block(7, 4) == 8
    assert dispatch.batch_block(3, 1) == 4
    assert dispatch.batch_block(32, 4) == 8
    assert dispatch.default_batch_block(1) == 1
    assert dispatch.default_batch_block(4) == 4
    assert dispatch.default_batch_block(32) == 8
    with pytest.raises(ValueError, match="multiple of 8"):
        dispatch.bucket_batch(7, 4)


def test_row_reduction_autotune_per_bucket_pair(tmp_path):
    from repro.core.cache import DiskCache
    from repro.core.reduction import ReductionKernel

    rowsum = ReductionKernel(np.float32, "0", "a+b", "x[i]", "float *x",
                             name="tunerow", axis=-1)
    cache = DiskCache("tune", root=tmp_path)
    v = jnp.asarray(rng.standard_normal((16, 3000)).astype(np.float32))
    rep = rowsum.autotune(v, cache=cache, repeats=1, warmup=1)
    be = backends.get_backend().name
    assert rowsum._tuned[(be, dispatch.rc_bucket(16, 3000))] == rep.best["block_rows"]
    # same bucket pair, different exact shape -> cached, no re-timing
    v2 = jnp.asarray(rng.standard_normal((13, 2900)).astype(np.float32))
    rep2 = rowsum.autotune(v2, cache=cache, repeats=1, warmup=1)
    assert rep2.cached and rep2.best == rep.best
    np.testing.assert_allclose(np.asarray(rowsum(v2)),
                               np.asarray(v2).sum(-1), atol=1e-2)


# ------------------------------------------------ model-level wiring
def test_fused_softmax_batched_two_launches():
    from repro.models.layers import fused_softmax

    x = jnp.asarray((rng.standard_normal((16, 512)) * 6).astype(np.float32))
    with dispatch.count_launches() as c:
        out = fused_softmax(x)
    assert c.delta == 2
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jax.nn.softmax(x, axis=-1)),
                               atol=1e-5)
    # >2-D batches flatten to rows; traced inputs still fall back
    x4 = jnp.reshape(x, (2, 2, 4, 512))
    np.testing.assert_allclose(np.asarray(fused_softmax(x4)),
                               np.asarray(jax.nn.softmax(x4, axis=-1)),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(jax.jit(fused_softmax)(x)),
                               np.asarray(jax.nn.softmax(x, axis=-1)),
                               atol=1e-6)


def test_rtcg_rmsnorm_matches_reference_and_kernel():
    from repro.kernels.rmsnorm.ops import rmsnorm as pallas_rmsnorm
    from repro.models.layers import rtcg_rmsnorm

    B, D = 9, 768
    x = rng.standard_normal((B, D)).astype(np.float32)
    w = rng.standard_normal(D).astype(np.float32)
    xj, wj = jnp.asarray(x), jnp.asarray(w)
    with dispatch.count_launches() as c:
        got = rtcg_rmsnorm(xj, wj, eps=1e-6)
    assert c.delta == 2                    # row wave + fused 2-D epilogue
    ref = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * w
    np.testing.assert_allclose(np.asarray(got), ref, atol=1e-4)
    np.testing.assert_allclose(np.asarray(pallas_rmsnorm(xj, wj, eps=1e-6)),
                               ref, atol=1e-4)


# ------------------------------------------- property-style sweeps
@given(B=st.integers(1, 12), n=st.integers(450, 650), seed=st.integers(0, 50))
@settings(max_examples=6, deadline=None)
def test_batched_softmax_property(B, n, seed):
    r = np.random.default_rng(seed)
    x = r.standard_normal((B, n)).astype(np.float32)
    X = ga.to_gpu(x)
    sm, delta = _launches(lambda: ga.softmax(X, stable=True).value)
    assert delta == 2
    np.testing.assert_allclose(np.asarray(sm),
                               np.asarray(jax.nn.softmax(jnp.asarray(x), axis=-1)),
                               atol=1e-5)


@given(B=st.integers(1, 10), n=st.integers(100, 400), seed=st.integers(0, 50))
@settings(max_examples=6, deadline=None)
def test_row_variance_property(B, n, seed):
    r = np.random.default_rng(seed)
    x = r.standard_normal((B, n)).astype(np.float32)
    X = ga.to_gpu(x)
    v = (((X - X.mean(axis=-1)) ** 2).mean(axis=-1)).value
    np.testing.assert_allclose(np.asarray(v), x.var(-1), rtol=1e-3, atol=1e-5)
