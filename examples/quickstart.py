"""Quickstart: the paper's core ideas in 60 lines.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np
import jax.numpy as jnp

# Everything below runs under the __main__ guard: the supervised-fleet
# demo (section 6) spawns worker *processes*, and spawn children
# re-import this module — without the guard every worker would re-run
# the whole quickstart (including the autotuner) before serving.
if __name__ == "__main__":
    # the fleet demo (section 6) needs its workers off any chip this
    # process holds: fail now, not after sections 1-5
    from repro.runtime.fleet import check_one_process_per_chip

    check_one_process_per_chip()

    # 1. GPUArray-style device arrays with lazy RTCG fusion (paper Fig. 3b)
    import repro.core.array as ga

    a = ga.to_gpu(np.random.randn(4, 4).astype(np.float32))
    a_doubled = (2 * a).get()
    print("2*a ->\n", a_doubled)

    # 1b. Fusion planner v2: reductions as *interior* DAG nodes — softmax
    #     is ONE generated reduction + ONE fused epilogue kernel (2 launches)
    v = ga.to_gpu(np.random.randn(10000).astype(np.float32))
    sm = ga.softmax(v).value
    print("fused softmax sums to:", float(sm.sum()))
    print("variance (2 reduce launches, /n on host):",
          float(((v - v.mean()) ** 2).mean()))

    # 1c. Axis-aware fusion (planner v3): a whole (B, N) batch of rows is
    #     STILL 2 launches — one row-segmented reduction wave (one
    #     accumulator per row; stable softmax's max and shifted-exp sum
    #     share it) plus one fused 2-D epilogue.  Unequal-length leaves
    #     broadcast inside the fused kernel: (N,) weights per-col, per-row
    #     reduced values as (B, 1) args — batched rmsnorm rides the same
    #     schedule.
    scores = ga.to_gpu(np.random.randn(32, 1024).astype(np.float32))
    batched = ga.softmax(scores, stable=True).value   # (32, 1024), 2 launches
    print("batched softmax rows sum to 1:",
          bool(np.allclose(np.asarray(batched.sum(axis=-1)), 1.0, atol=1e-5)))
    w = ga.to_gpu(np.random.randn(1024).astype(np.float32))
    rms = (scores / (((scores * scores).mean(axis=-1) + 1e-6).sqrt()) * w).value
    print("fused batched rmsnorm:", rms.shape)        # also 2 launches

    # 1d. Execution backends (PR 4, the paper's PyCUDA/PyOpenCL pairing):
    #     the SAME pipeline — snippets, fusion planner, bucketing, caches,
    #     autotuner — lowers through pluggable backends.  "pallas" (the
    #     default) assembles pallas_call kernels; "xla" compiles the same
    #     snippets to plain jnp under jax.jit, no Pallas needed.  Pick one
    #     per call, or process-wide with REPRO_BACKEND=xla; drivers, tuning
    #     winners and counters are all keyed per backend.
    from repro.core import dispatch

    for be in ("pallas", "xla"):
        with dispatch.count_launches() as c:
            out = ga.softmax(scores, stable=True).evaluate(backend=be).value
        print(f"softmax on {be}: {c.delta} launches {c.by_backend}, "
              f"rows sum to 1: "
              f"{bool(np.allclose(np.asarray(out.sum(-1)), 1.0, atol=1e-5))}")
    # same numbers, same 2-launch schedule — only the compile target differs
    #   (run e.g.:  REPRO_BACKEND=xla PYTHONPATH=src python examples/quickstart.py)

    # 1e. Serving runtime (PR 5): backend="auto" stops pinning and lets the
    #     runtime's router pick pallas-vs-xla per call from measured latency
    #     (seeded by autotuner winners); single-row requests submitted from
    #     concurrent threads micro-batch into ONE 2-launch (K, N) schedule;
    #     and every served key lands in a warm-start manifest that
    #     runtime.warmup() replays at startup (zero cold-start compiles).
    from repro import runtime

    auto_sm = ga.softmax(scores, stable=True).evaluate(backend="auto").value
    from repro.models.layers import fused_softmax
    auto_layer = fused_softmax(np.random.randn(4, 256).astype(np.float32),
                               backend="auto")
    st = runtime.stats()
    print("runtime routes:", st["router"]["routes"],
          "| manifest entries:", st["manifest"]["entries"])

    # 1f. Kernel IR (PR 7, DESIGN.md §11): specs lower into a searchable
    #     IR — a tagged iteration domain + statements + argument access
    #     map — and pure transformations (tile, split, transpose_layout,
    #     fuse_epilogue) rewrite it before either backend renders it.
    #     Every plan is introspectable: dump the IR and its transformation
    #     log.  axis=0 column reductions are just `transpose_layout` —
    #     same 2-launch softmax schedule, columns instead of rows.
    from repro.core import ir

    spec = ga.plan(ga.exp(scores)._expr).kernel().spec
    kir = ir.tile(ir.lower_elementwise(spec, rows=32, lanes=1024,
                                       layout="rows"), "rows", 8)
    print("kernel IR:\n" + kir.describe())
    col_sm = ga.softmax(scores, stable=True, axis=0).value   # still 2 launches
    print("axis=0 softmax cols sum to 1:",
          bool(np.allclose(np.asarray(col_sm.sum(axis=0)), 1.0, atol=1e-5)))

    # 2. ElementwiseKernel: C-like snippet -> generated tiled Pallas kernel
    #    (paper Fig. 4a, verbatim API)
    from repro.core import ElementwiseKernel

    lin_comb = ElementwiseKernel(
        "float a, float *x, float b, float *y, float *z",
        "z[i] = a*x[i] + b*y[i]")
    x = jnp.asarray(np.random.randn(500000).astype(np.float32))
    y = jnp.asarray(np.random.randn(500000).astype(np.float32))
    z = lin_comb(5.0, x, 6.0, y, x)
    print("lin_comb max err:",
          float(jnp.max(jnp.abs(z - (5 * x + 6 * y)))))

    # 3. ReductionKernel (paper §5.2): fused map+reduce
    from repro.core import ReductionKernel

    dot = ReductionKernel(np.float32, neutral="0", reduce_expr="a+b",
                          map_expr="x[i]*y[i]", arguments="float *x, float *y")
    print("dot:", float(dot(x, y)), "ref:", float(x @ y))

    # 3b. The paper's Fig. 4a, near-verbatim (curandom + ElementwiseKernel)
    from repro.core import curandom as pycurandom

    xr = pycurandom.rand((500000,))
    yr = pycurandom.rand((500000,))
    zr = lin_comb(5, xr, 6, yr, xr)
    print("fig4a max err:", float(jnp.max(jnp.abs(zr - (5 * xr + 6 * yr)))))

    # 3c. ScanKernel (pycuda.scan): generated two-pass blocked prefix scan
    from repro.core import InclusiveScanKernel

    cumsum = InclusiveScanKernel(np.float32, "a+b")
    print("scan ok:", bool(jnp.allclose(cumsum(xr),
                                        jnp.cumsum(xr), rtol=1e-5)))

    # 4. Run-time specialization + autotuning (paper §4.1/§4.2):
    #    the same kernel template, tuned per input shape at run time
    from repro.kernels.filterbank_conv import ops as fb

    img = jnp.asarray(np.random.randn(64, 64, 8).astype(np.float32))
    filters = jnp.asarray(np.random.randn(16, 9, 9, 8).astype(np.float32))
    report = fb.tune_report(img, filters)
    print("autotuner winner for 64x64x8:", report.best)

    # 5. The Copperhead-style DSL (paper §6.3, Fig. 7)
    from repro.core.dsl import cu

    @cu
    def axpy(a, xs, ys):
        def triad(xi, yi):
            return a * xi + yi
        return map(triad, xs, ys)

    print("axpy ok:", np.allclose(axpy(np.float32(2.0), x, y), 2 * x + y,
                                  rtol=1e-5, atol=1e-5))
    print("generated source:\n", axpy.source)

    # 6. Supervised serving fleet (PR 8, DESIGN.md §12): N worker
    #    *processes* (each a full ServingRuntime on its own pipe) behind
    #    a bounded admission queue and a supervisor that heartbeats,
    #    restarts crashed workers with backoff, and re-dispatches their
    #    in-flight requests to survivors.  Here: a 4-worker fleet serves
    #    32 softmax requests while ONE worker is killed mid-traffic
    #    (deterministic worker.kill fault on its 2nd dispatch group) —
    #    every request still completes (availability 1.0), and restarted
    #    workers warm up compile-free from the shared manifest.
    import tempfile
    from repro.runtime import ServingFleet
    from repro.runtime.supervisor import BackoffPolicy

    rng = np.random.default_rng(7)
    rows = [rng.standard_normal(512).astype(np.float32) for _ in range(32)]
    with ServingFleet(
            workers=4, backend="xla", max_batch=8, group_max=1,
            max_outstanding=1, max_redispatch=5,
            backoff=BackoffPolicy(base=0.01, cap=0.2),
            chaos_rules=[{"site": "worker.kill", "index": 2, "times": 1}],
            chaos_incarnations=[1],   # only first incarnations carry the bomb
            cache_dir=tempfile.mkdtemp(prefix="quickstart-fleet-"),
    ) as fleet:
        fleet.wait_ready(timeout=300)
        futs = [fleet.submit_softmax(r, deadline=120) for r in rows]
        outs = [f.result(timeout=180) for f in futs]
        ok = sum(bool(np.allclose(np.asarray(o).sum(), 1.0, atol=1e-4))
                 for o in outs)
        fs = fleet.fleet_stats()
        print(f"fleet: {ok}/{len(rows)} served (availability "
              f"{ok / len(rows):.3f}) with {sum(fs['deaths'].values())} "
              f"worker death(s), {fs['redispatched']} re-dispatched, "
              f"{fs['starts'] - fs['workers']} restart(s)")

    # 7. Continuous-batching decode (PR 9, DESIGN.md §13): requests join
    #    and leave the live decode batch EVERY step.  Each request leases
    #    a slot of one fixed-shape device KV cache (RequestsCache pool:
    #    admit / evict / explicit shed), prompts of any length prefill as
    #    one (1, max_len) row scattered into the slot, and every step's
    #    mixed-length sampler rows coalesce into ONE *ragged*
    #    softmax.cdf flush — 2 generated launches per step, whatever the
    #    occupancy, with the inverse-CDF cumsum fused into the epilogue.
    import jax
    from pathlib import Path
    from repro.configs.registry import get_config
    from repro.core.cache import DiskCache
    from repro.models.schema import init_params
    from repro.serving.engine import ContinuousEngine

    cfg = get_config("internlm2-1.8b", smoke=True).replace(
        dtype="float32", attention_impl="naive")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rt = runtime.ServingRuntime(
        backend="auto", window=0.25, max_batch=8,
        router=runtime.BackendRouter(),
        manifest=runtime.WarmStartManifest(cache=DiskCache(
            "quickstart_decode",
            root=Path(tempfile.mkdtemp(prefix="quickstart-decode-")))))
    eng = ContinuousEngine(cfg, params, capacity=3, max_len=48, runtime=rt)
    for L, m in ((5, 6), (9, 4), (3, 5), (7, 3), (2, 4)):   # 5 requests, 3 slots
        eng.submit(rng.integers(1, cfg.vocab_size, size=L).astype(np.int32),
                   max_new=m)
    eng.step(temperature=0.7)            # admission step pays the builds
    with dispatch.count_launches() as c:
        eng.step(temperature=0.7)        # steady state: the ragged pair
    results = eng.run(temperature=0.7)   # slots recycle as requests finish
    st = eng.stats()
    print(f"continuous decode: {len(results)} requests "
          f"({st['tokens_generated']} tokens) through "
          f"{st['kv']['capacity']} KV slots "
          f"in {st['steps']} steps; {c.delta} launches/steady-step")
    rt.close()

    # 8. Flight recorder + metrics plane (PR 10, DESIGN.md §14): arm
    #    REPRO_TRACE=spans and a coalesced burst produces an end-to-end
    #    trace — per-request `request` roots with admit/queue/reply
    #    children pointing at the ONE `flush` that served them all —
    #    exportable as Chrome trace JSON (load in Perfetto), plus
    #    mergeable fixed-edge histograms behind a Prometheus /metrics
    #    endpoint (`repro.launch.serve --stats-port`).
    import threading
    from pathlib import Path
    from repro.runtime import observe

    observe.set_mode("spans")
    obs_rt = runtime.ServingRuntime(backend="xla", window=0.25, max_batch=8)
    burst = [rng.standard_normal(512).astype(np.float32) for _ in range(8)]
    futs = [None] * len(burst)

    def _sub(i):
        futs[i] = obs_rt.submit_softmax(burst[i])

    ts = [threading.Thread(target=_sub, args=(i,)) for i in range(len(burst))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    for f in futs:
        f.result(timeout=120)
    trace_path = Path(tempfile.mkdtemp(prefix="quickstart-obs-")) / \
        "trace.json"
    n_ev = runtime.export_trace(trace_path)
    lat = observe.latency_summary(observe.METRICS.snapshot())
    obs_rt.close()
    observe.set_mode("off")
    print(f"flight recorder: {len(burst)} requests -> {n_ev} spans "
          f"-> {trace_path}")
    print("cross-request latency:",
          {k: f"p50={v['p50_ms']:.2f}ms p95={v['p95_ms']:.2f}ms"
           for k, v in lat.items()})
    print("prometheus sample:", observe.metrics_text().splitlines()[0])
