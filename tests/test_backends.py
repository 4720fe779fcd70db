"""Backend abstraction tests (PR 4) — one RTCG pipeline, two targets.

Covers: registry/selection (explicit arg, instance passthrough,
``REPRO_BACKEND``), capability fingerprints and backend-sensitive
persistence fingerprints, backend-keyed driver caching (same rendered
source on two backends = two driver-cache entries, two compile counts),
per-backend launch counters (`count_launches().by_backend`), tuning
winners per (backend, bucket), XlaBackend numerics vs PallasBackend
across all three kernel families, and the planner/serving-layer
``backend=`` pass-through.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import repro.core.array as ga
from repro.core import backends, dispatch
from repro.core.backends import PallasBackend, XlaBackend, get_backend
from repro.core.cache import environment_fingerprint, fingerprint_token
from repro.core.elementwise import ElementwiseKernel
from repro.core.reduction import ReductionKernel
from repro.core.scan import ExclusiveScanKernel, InclusiveScanKernel

rng = np.random.default_rng(42)


# ------------------------------------------------------------ selection
def test_registry_and_selection(monkeypatch):
    assert set(backends.available_backends()) >= {"pallas", "xla"}
    assert isinstance(get_backend("pallas"), PallasBackend)
    assert isinstance(get_backend("xla"), XlaBackend)
    # instances are singletons and pass through get_backend
    be = get_backend("xla")
    assert get_backend("xla") is be
    assert get_backend(be) is be
    # default comes from REPRO_BACKEND (default pallas)
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    assert get_backend().name == "pallas"
    monkeypatch.setenv("REPRO_BACKEND", "xla")
    assert get_backend().name == "xla"
    with pytest.raises(ValueError, match="unknown RTCG backend"):
        get_backend("opencl")


def test_fingerprints_differ_across_backends(monkeypatch):
    fp = get_backend("pallas").fingerprint()
    fx = get_backend("xla").fingerprint()
    assert fp != fx and fp["backend"] == "pallas" and fx["backend"] == "xla"
    # persistence fingerprints (cache.py) carry the backend dimension:
    # a pallas-keyed disk entry can never be served to the xla target
    assert environment_fingerprint("pallas") != environment_fingerprint("xla")
    assert fingerprint_token("pallas") != fingerprint_token("xla")
    # the env-resolved form follows REPRO_BACKEND
    monkeypatch.setenv("REPRO_BACKEND", "xla")
    assert environment_fingerprint()["rtcg_backend"] == "xla"
    assert fingerprint_token() == fingerprint_token("xla")
    monkeypatch.setenv("REPRO_BACKEND", "pallas")
    assert fingerprint_token() == fingerprint_token("pallas")


# ------------------------------------------------- backend-keyed caches
def test_driver_cache_is_backend_keyed():
    """Same rendered source on two backends -> two driver-cache entries
    and one compile counted against each backend's tag."""
    k = ElementwiseKernel("float *z, float *x", "z[i] = 3*x[i] + 1",
                          name="bk_cache_probe")
    x = jnp.asarray(rng.standard_normal(4096).astype(np.float32))
    size0 = len(dispatch.driver_cache())
    cp0, cx0 = dispatch.compile_count("pallas"), dispatch.compile_count("xla")
    zp = k(x, x, backend="pallas")
    zx = k(x, x, backend="xla")
    assert len(dispatch.driver_cache()) == size0 + 2
    assert dispatch.compile_count("pallas") == cp0 + 1
    assert dispatch.compile_count("xla") == cx0 + 1
    np.testing.assert_allclose(np.asarray(zp), np.asarray(zx), rtol=1e-6)
    # re-calls on either backend are pure cache hits
    c0 = dispatch.compile_count()
    k(x, x, backend="pallas"); k(x, x, backend="xla")
    assert dispatch.compile_count() == c0


def test_launch_counters_tagged_by_backend():
    k = ElementwiseKernel("float *z, float *x", "z[i] = x[i] * x[i]",
                          name="bk_counter_probe")
    x = jnp.asarray(rng.standard_normal(2048).astype(np.float32))
    with dispatch.count_launches() as c:
        k(x, x, backend="pallas")
        k(x, x, backend="xla")
        k(x, x, backend="xla")
    assert c.delta == 3
    assert c.by_backend["pallas"] == 1 and c.by_backend["xla"] == 2
    assert "pallas" in dispatch.launch_counts()
    assert "xla" in dispatch.launch_counts()
    # stats() surfaces the per-backend maps benchmarks record
    s = dispatch.stats()
    assert s["launches_by_backend"]["xla"] >= 2


def test_tuning_winners_per_backend_bucket(tmp_path):
    from repro.core.cache import DiskCache

    k = ElementwiseKernel("float *o, float *v", "o[i] = 2*v[i] - 3",
                          name="bk_tune_probe")
    cache = DiskCache("tune", root=tmp_path)
    v = jnp.asarray(rng.standard_normal(50_000).astype(np.float32))
    rp = k.autotune(v, v, cache=cache, repeats=1, warmup=1, backend="pallas")
    rx = k.autotune(v, v, cache=cache, repeats=1, warmup=1, backend="xla")
    nb = dispatch.n_bucket(50_000)
    assert k._tuned[("pallas", nb)] == rp.best["block_rows"]
    assert k._tuned[("xla", nb)] == rx.best["block_rows"]
    # the tuning-cache keys differ per backend: the second tune must not
    # be a cache hit of the first
    assert not rx.cached


# ------------------------------------------------------ numerics parity
def test_xla_elementwise_matches_pallas_multi_statement():
    k = ElementwiseKernel(
        "float *x, float *y, float *z, float *w",
        "float t = x[i] * y[i]; z[i] = t + expf(-fabsf(t)); w[i] = z[i] * 0.5f",
        name="bk_multi")
    x = jnp.asarray(rng.standard_normal(3000).astype(np.float32))
    y = jnp.asarray(rng.standard_normal(3000).astype(np.float32))
    zp, wp = k(x, y, x, y, backend="pallas")
    zx, wx = k(x, y, x, y, backend="xla")
    np.testing.assert_allclose(np.asarray(zp), np.asarray(zx), atol=1e-6)
    np.testing.assert_allclose(np.asarray(wp), np.asarray(wx), atol=1e-6)


@pytest.mark.parametrize("n", (127, 128, 4097))
def test_xla_reduction_matches_pallas_multi_acc(n):
    stats = ReductionKernel(
        [np.float32] * 3, ["3.4e38", "-3.4e38", "0"],
        ["fminf(a,b)", "fmaxf(a,b)", "a+b"],
        ["x[i]", "x[i]", "x[i]"], "float *x", name="bk_stats")
    x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    got_p = [float(v) for v in stats(x, backend="pallas")]
    got_x = [float(v) for v in stats(x, backend="xla")]
    ref = [float(np.min(np.asarray(x))), float(np.max(np.asarray(x))),
           float(np.sum(np.asarray(x)))]
    np.testing.assert_allclose(got_p, ref, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(got_x, ref, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("B,n", [(1, 513), (5, 1024)])
def test_xla_row_reduction_matches_pallas(B, n):
    rowsum = ReductionKernel(np.float32, "0", "a+b", "x[i]", "float *x",
                             name="bk_rowsum", axis=-1)
    x = jnp.asarray(rng.standard_normal((B, n)).astype(np.float32))
    got_p = np.asarray(rowsum(x, backend="pallas"))
    got_x = np.asarray(rowsum(x, backend="xla"))
    ref = np.asarray(x).sum(-1)
    np.testing.assert_allclose(got_p, ref, atol=1e-3)
    np.testing.assert_allclose(got_x, ref, atol=1e-3)


@pytest.mark.parametrize("expr,ref_fn", [
    ("a+b", lambda v: np.cumsum(v)),
    ("fmaxf(a,b)", lambda v: np.maximum.accumulate(v)),
])
def test_xla_scan_matches_pallas(expr, ref_fn):
    k = InclusiveScanKernel(np.float32, expr, name=f"bk_scan_{expr[:4]}")
    x = jnp.asarray(rng.standard_normal(10_000).astype(np.float32))
    got_p = np.asarray(k(x, backend="pallas"))
    got_x = np.asarray(k(x, backend="xla"))
    ref = ref_fn(np.asarray(x))
    np.testing.assert_allclose(got_p, ref, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(got_x, ref, rtol=1e-4, atol=1e-3)


def test_xla_exclusive_scan_matches_pallas():
    k = ExclusiveScanKernel(np.float32, "a+b", "0", name="bk_exscan")
    x = jnp.asarray(rng.standard_normal(5000).astype(np.float32))
    got_p = np.asarray(k(x, backend="pallas"))
    got_x = np.asarray(k(x, backend="xla"))
    ref = np.concatenate([[0.0], np.cumsum(np.asarray(x))[:-1]])
    np.testing.assert_allclose(got_p, ref, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(got_x, ref, rtol=1e-4, atol=1e-3)


# -------------------------------------------------- planner pass-through
def test_planner_backend_pin_identical_schedule():
    """A pinned backend runs the exact same 2-launch schedule: one row
    wave + one epilogue, every launch tagged with the pinned backend."""
    x = rng.standard_normal((4, 700)).astype(np.float32)
    ref = np.asarray(jax.nn.softmax(jnp.asarray(x), axis=-1))
    outs = {}
    for be in ("pallas", "xla"):
        sm = ga.softmax(ga.RTCGArray(jnp.asarray(x)), stable=True)
        with dispatch.count_launches() as c:
            outs[be] = np.asarray(sm.evaluate(backend=be).value)
        assert c.delta == 2 and c.by_backend == {be: 2}
        np.testing.assert_allclose(outs[be], ref, atol=1e-5)
    np.testing.assert_allclose(outs["pallas"], outs["xla"], atol=1e-6)


def test_layers_backend_pass_through():
    from repro.models.layers import fused_softmax, rtcg_rmsnorm

    x = jnp.asarray(rng.standard_normal((3, 257)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal(257).astype(np.float32))
    sm_ref = np.asarray(jax.nn.softmax(x, axis=-1))
    rm_ref = (np.asarray(x) / np.sqrt((np.asarray(x) ** 2).mean(-1, keepdims=True)
                                      + 1e-6) * np.asarray(w))
    for be in ("pallas", "xla"):
        with dispatch.count_launches() as c:
            sm = fused_softmax(x, backend=be)
        assert c.by_backend == {be: 2}
        np.testing.assert_allclose(np.asarray(sm), sm_ref, atol=1e-5)
        with dispatch.count_launches() as c:
            rm = rtcg_rmsnorm(x, w, backend=be)
        assert c.by_backend == {be: 2}
        np.testing.assert_allclose(np.asarray(rm), rm_ref, atol=1e-4)


def test_env_selection_routes_generated_kernels(monkeypatch):
    monkeypatch.setenv("REPRO_BACKEND", "xla")
    k = ElementwiseKernel("float *z, float *x", "z[i] = x[i] + 1",
                          name="bk_env_probe")
    x = jnp.asarray(rng.standard_normal(1000).astype(np.float32))
    with dispatch.count_launches() as c:
        k(x, x)
    assert c.by_backend == {"xla": 1}
    # explicit arg overrides the env selection
    with dispatch.count_launches() as c:
        k(x, x, backend="pallas")
    assert c.by_backend == {"pallas": 1}


def test_pinned_and_env_plans_share_kernel_and_tuning(monkeypatch):
    """A plan pinned to backend="xla" and a backend=None plan evaluated
    under REPRO_BACKEND=xla must resolve the SAME kernel instance, so
    tuning winners recorded through either route apply to both."""
    x = ga.to_gpu(np.asarray(rng.standard_normal(3000), np.float32))
    ga.autotune((2 * x + 1).sum(), backend="xla", repeats=1, warmup=1)
    monkeypatch.setenv("REPRO_BACKEND", "xla")
    k_pin = ga.plan_many([(2 * x + 1).sum()], backend="xla").steps[0].kernel()
    k_env = ga.plan_many([(2 * x + 1).sum()]).steps[0].kernel()
    assert k_pin is k_env
    assert ("xla", dispatch.n_bucket(3000)) in k_env._tuned


def test_block_insensitive_backend_shares_driver_across_block_rows():
    """block_rows does not change the xla-generated code, so tuning
    candidates that pad to the same bucket share ONE compiled driver
    (pallas, whose BlockSpecs depend on it, compiles per block size)."""
    k = ElementwiseKernel("float *o, float *v", "o[i] = v[i] * 4",
                          name="bk_blockshare")
    v = jnp.asarray(rng.standard_normal(64 * 128).astype(np.float32))
    cx0 = dispatch.compile_count("xla")
    k(v, v, backend="xla", block_rows=8)
    k(v, v, backend="xla", block_rows=16)
    assert dispatch.compile_count("xla") == cx0 + 1
    cp0 = dispatch.compile_count("pallas")
    k(v, v, backend="pallas", block_rows=8)
    k(v, v, backend="pallas", block_rows=16)
    assert dispatch.compile_count("pallas") == cp0 + 2


def test_xla_backend_renders_source_without_pallas():
    """The xla lowering of an elementwise spec is plain jnp source — no
    refs, no program_id, no pallas import needed to execute it."""
    k = ElementwiseKernel("float *z, float *x", "z[i] = 2*x[i]",
                          name="bk_render_probe")
    src = k.render(8, backend="xla")
    assert "pl." not in src and "_ref" not in src
    psrc = k.render(8, backend="pallas")
    assert "pl.program_id" in psrc or "_ref" in psrc


# ------------------------------------------------- chip-facing helpers
def test_interpret_flag_has_no_default():
    """A spec must carry the interpret flag its family resolved: one
    built without it is an error, not a silent interpreter run."""
    from repro.core.backends.base import ReductionSpec
    from repro.core.platform import interpret_mode

    with pytest.raises(TypeError, match="interpret"):
        ReductionSpec(name="r", arg_meta=(), scalar_names=(),
                      loaded_vectors=(), prelude_lines=(), outs=(),
                      multi=False)
    k = ReductionKernel(np.float32, "0", "a+b", "x[i]", "float *x",
                        name="bk_interp_flag")
    assert k.spec.interpret is interpret_mode() is (jax.default_backend() != "tpu")
    k(jnp.ones(300, jnp.float32), backend="pallas")
    drivers = [dispatch.driver_cache().get(key)
               for key in dispatch.driver_cache().keys() if key[1] == "pallas"]
    assert drivers and all(d.interpret is interpret_mode() for d in drivers)
    assert all(callable(d.call) for d in drivers)


def test_device_peaks_table():
    from repro.core.platform import PEAKS, TARGET_DEVICE_KIND, device_peaks

    v5e = device_peaks("TPU v5 lite")
    assert (v5e["bf16_flops"], v5e["hbm_bytes_per_s"]) == (197e12, 819e9)
    # off the chip the analytic models score for the target chip
    assert device_peaks() is PEAKS[TARGET_DEVICE_KIND]
    with pytest.raises(KeyError, match="no peaks"):
        device_peaks("TPU v99")


def test_configure_compile_cache(monkeypatch):
    from repro.core.platform import REPO_ROOT, configure_compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert configure_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = configure_compile_cache()
        assert path == str(REPO_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_row_prefix_sum_output(backend):
    """``out[i] = cumsumf(e)``, the sampler's inverse-CDF epilogue: the
    Pallas kernel computes ``e`` (Mosaic has no cumsum lowering) and its
    driver prefix-sums the row inside the same launch."""
    from repro.core.platform import BroadcastArg, VectorArg

    k = ElementwiseKernel(
        [BroadcastArg(np.float32, "r0", "row"), VectorArg(np.float32, "x"),
         VectorArg(np.float32, "out")],
        "out[i] = cumsumf(x[i] / r0)", name="bk_row_cdf", layout="rows",
        backend=backend)
    if backend == "pallas":
        assert "cumsum" not in k.render(8, 1024, backend="pallas")
    x = np.abs(rng.standard_normal((3, 1000))).astype(np.float32)
    lens = [1000, 700, 5]
    r0 = jnp.asarray([x[i, :n].sum() for i, n in enumerate(lens)])
    k(r0, jnp.asarray(x), jnp.asarray(x), row_lens=lens)  # build
    with dispatch.count_launches() as c:
        out = np.asarray(k(r0, jnp.asarray(x), jnp.asarray(x), row_lens=lens))
    assert c.by_backend == {backend: 1}
    for i, n in enumerate(lens):
        np.testing.assert_allclose(out[i, :n], np.cumsum(x[i, :n]) / r0[i],
                                   rtol=1e-5, atol=1e-6)


def test_pallas_cumsumf_must_be_a_whole_row_output():
    from repro.core.backends.pallas import split_row_scans

    line = ("out = jnp.broadcast_to(jnp.asarray(cumsum_lanes(x / r0)), "
            "_BLK).astype(jnp.float32)")
    body, scans = split_row_scans([line], ["out"])
    assert scans == ["out"]
    assert body == ["out = jnp.broadcast_to(jnp.asarray(x / r0), "
                    "_BLK).astype(jnp.float32)"]
    partial = line.replace("cumsum_lanes(x / r0)", "cumsum_lanes(x) / r0")
    with pytest.raises(NotImplementedError, match="whole right-hand side"):
        split_row_scans([partial], ["out"])
    with pytest.raises(NotImplementedError, match="read or rewritten"):
        split_row_scans([line, "out = out * 2"], ["out"])
