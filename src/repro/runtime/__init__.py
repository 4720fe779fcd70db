"""Serving runtime — coalescing executor + backend auto-router + warm-start
manifest (PR 5; contract in DESIGN.md §9 and ROADMAP "Serving runtime").

The paper's claim is that run-time code generation plus aggressive
caching lets a scripting layer serve GPU work at hardware speed; this
package is the layer that makes that hold under *concurrent* serving
traffic.  It sits between the fusion planner (`repro.core.array`) and
the serving engine (`repro.serving.engine`) and owns three cooperating
pieces:

  * `CoalescingExecutor` — independent single-row requests (sampler
    softmax, per-request rmsnorm) micro-batch into ONE row-segmented
    ``(K, N)`` schedule: K requests, 2 launches instead of ``2·K``;
  * `BackendRouter` — ``backend="auto"``: per-(family, backend, shape
    bucket) latency EMAs (seeded from autotuner winners and `BlockCost`)
    pick pallas vs xla per call;
  * `WarmStartManifest` — every served (family, geometry, backend) key
    persists to a `DiskCache` namespace; `warmup()` replays them so a
    fresh process reaches zero cold-start compiles.

Typical serving use::

    from repro import runtime

    rt = runtime.ServingRuntime(backend="auto", max_batch=16)
    rt.warmup()                       # replay the persisted manifest
    futs = [rt.submit_softmax(row) for row in rows]   # from K threads
    probs = [f.result() for f in futs]                # one 2-launch flush
    rt.stats()                        # coalesce factor, route table, ...

`default_runtime()` is the process-wide instance the model layers and
the engine use when asked to route (``backend="auto"`` /
``Engine(runtime=...)``).
"""

from __future__ import annotations

import threading
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backends as _backends
from repro.core import dispatch
from repro.core.backends import is_auto as _is_auto
from repro.runtime import faults, observe
from repro.runtime.executor import CoalescingExecutor, RuntimeFuture
from repro.runtime.manifest import WarmStartManifest
from repro.runtime.router import (BackendRouter, CircuitBreaker, bucket_for,
                                  default_breaker, default_router,
                                  set_default_breaker, set_default_router)

# arm the process-lifetime chaos plan, if REPRO_CHAOS asks for one (the
# CI chaos leg; a no-op otherwise)
faults.install_env_plan()
# arm the observability knob, if REPRO_TRACE asks for one (PR 10,
# DESIGN.md §14; off by default — no observer installed, zero overhead)
observe.install_from_env()

_DEFAULT: "ServingRuntime | None" = None
_DEFAULT_LOCK = threading.Lock()


class ServingRuntime:
    """Facade wiring executor + router + manifest into one serving layer.

    ``backend`` is the default resolution policy: ``"auto"`` routes per
    call through the router; a concrete name (``"pallas"``/``"xla"``)
    pins every call (telemetry is still recorded, so a later switch to
    auto starts informed).  ``window``/``max_batch`` shape the
    executor's micro-batch flush policy.
    """

    def __init__(self, backend: str = "auto", window: float = 0.002,
                 max_batch: int = 64, router: "BackendRouter | None" = None,
                 manifest: "WarmStartManifest | None" = None):
        self.backend = backend
        self.router = router if router is not None else default_router()
        self.manifest = manifest if manifest is not None else WarmStartManifest()
        self.executor = CoalescingExecutor(self, window=window,
                                           max_batch=max_batch)
        self.manifest.start_listening()

    # -- the routed/timed core -------------------------------------------
    def _resolve(self, family: str, bucket: tuple,
                 backend: "str | None" = None) -> str:
        be = backend if backend is not None else self.backend
        if _is_auto(be):
            return self.router.choose(family, bucket)
        return _backends.get_backend(be).name

    def _timed(self, family: str, geometry: tuple, dtype: str, params: dict,
               run, backend: "str | None" = None, record: bool = True):
        bucket = bucket_for(geometry)
        if params.get("ragged"):
            # ragged flushes mask per-row lengths inside the kernel —
            # their latency profile (and tuned winners) must not share
            # EMA cells with the dense drivers of the same geometry
            bucket = bucket + ("R",)
        be = self._resolve(family, bucket, backend)
        # telemetry (PR 10): a "serve" span parenting the plan/launch
        # spans below it, and a latency observation labeled (family,
        # backend, bucket, rung).  Every hook here is behind the
        # REPRO_TRACE knob (or an active profiler session, for the
        # span) — off-mode adds one int check.
        tok = observe.span_begin("serve", "runtime")
        if observe._MODE:
            dispatch.take_last_rung()   # clear a stale rung on this thread
        d0 = dispatch.degradation_total()
        t0 = time.perf_counter()
        try:
            with dispatch.count_compiles() as cc:
                out = run(be)
                jax.block_until_ready(out)
        finally:
            if tok is not None:
                observe.span_end(tok, args={"family": family, "backend": be,
                                            "bucket": str(bucket)})
        dt = time.perf_counter() - t0
        if observe._MODE:
            rung = dispatch.take_last_rung() or (
                "none" if dispatch.degradation_total() == d0
                else "degraded")
            bstr = "x".join(str(d) for d in bucket)
            observe.observe_hist("request_latency_seconds",
                                 (family, be, bstr, rung), dt)
            observe.count("requests_total", family, be)
        if record:
            # cold calls pay one-off driver builds; folding that wall-clock
            # into the EMA would poison the route (compile cost is
            # amortized by the cache, launch cost is what repeats), so
            # only compile-free calls feed the latency telemetry.
            # Degraded calls (ladder rungs taken inside `run`, PR 6) are
            # excluded for the same reason — the measurement belongs to
            # a fallback path, not to the chosen backend — and are not
            # recorded in the manifest (a warm start should replay the
            # healthy configuration, not a broken one).
            clean = dispatch.degradation_total() == d0
            if cc.delta == 0 and clean:
                self.router.observe(family, be, bucket, dt)
            if clean:
                self.manifest.record(family, geometry, dtype, be, params)
        return out

    def _run_batch(self, family: str, X, shared: dict,
                   backend: "str | None" = None, record: bool = True,
                   row_lens=None):
        """Run one fused row schedule over a stacked ``(K, N)`` operand —
        the executor's flush target and the warmup replayer.  With
        ``row_lens`` (one int32 length per row) the schedule runs the
        *ragged* kernel pair: each row is masked to its own length
        inside the kernels, so mixed-length requests padded to the
        bucket max still flush as ONE 2-launch schedule."""
        import repro.core.array as ga

        b, n = int(X.shape[0]), int(X.shape[-1])
        if row_lens is not None:
            return self._run_ragged(family, X, shared, row_lens,
                                    backend=backend, record=record)
        if family == "softmax.cdf":
            raise ValueError("family 'softmax.cdf' is ragged-only "
                             "(pass row_lens=)")
        if family == "softmax":
            stable = bool(shared.get("stable", True))

            def run(be):
                # family= keys the ladder's breaker cells consistently
                # with router.choose ("softmax", not the structural hash)
                return ga.softmax(ga.RTCGArray(X), stable=stable).evaluate(
                    backend=be, family=family).value

            params = {"stable": stable}
        elif family == "softmax.axis0":
            stable = bool(shared.get("stable", True))

            def run(be):
                # column softmax: the kernel IR's transpose_layout domain
                return ga.softmax(ga.RTCGArray(X), stable=stable,
                                  axis=0).evaluate(
                    backend=be, family=family).value

            params = {"stable": stable}
        elif family == "rmsnorm":
            w = jnp.asarray(shared["w"]).astype(X.dtype)
            eps = float(shared.get("eps", 1e-6))

            def run(be):
                Xa, W = ga.RTCGArray(X), ga.RTCGArray(w)
                return (Xa / (((Xa * Xa).mean(axis=-1) + eps).sqrt())
                        * W).evaluate(backend=be, family=family).value

            params = {"eps": eps}
        else:
            raise ValueError(f"unknown runtime family {family!r} "
                             "(softmax | softmax.axis0 | rmsnorm)")
        return self._timed(family, (b, n), str(X.dtype), params, run,
                           backend=backend, record=record)

    def _run_ragged(self, family: str, X, shared: dict, row_lens,
                    backend: "str | None" = None, record: bool = True):
        """One *ragged* 2-launch flush: a row-segmented reduction wave
        whose first operand is the per-row ``(B,)`` int32 length vector,
        plus a fused 2-D epilogue masked to the same lengths.  Rows
        shorter than the bucket width contribute only their own
        elements; the padding columns come back zeroed.

        Families: ``softmax`` (probabilities), ``softmax.cdf`` (the
        sampler epilogue — the inverse-CDF cumulative sum fuses into
        the SAME epilogue launch via ``cumsumf``, so K sampler rows add
        zero launches over K softmax rows), ``rmsnorm`` (sum-of-squares
        wave normalized by each row's true length)."""
        b, n = int(X.shape[0]), int(X.shape[-1])
        X = jnp.asarray(X)
        lens = jnp.asarray(row_lens, jnp.int32).reshape(-1)
        if int(lens.shape[0]) != b:
            raise ValueError(f"row_lens has {int(lens.shape[0])} entries "
                             f"for {b} rows")
        if family in ("softmax", "softmax.cdf"):
            wave, epilogue = _ragged_kernels(family)
            X32 = X.astype(jnp.float32)

            def run(be):
                r0, r1 = wave(X32, backend=be, row_lens=lens)
                return epilogue(r0, r1, X32, X32, backend=be, row_lens=lens)

            params = {"ragged": True, "stable": True}
        elif family == "rmsnorm":
            wave, epilogue = _ragged_kernels("rmsnorm")
            w = jnp.asarray(shared["w"]).astype(jnp.float32).reshape(-1)
            eps = float(shared.get("eps", 1e-6))
            # bind the shared weight at the flush width: row i reads
            # w[:len_i] (columns align), and masked columns never read w
            if int(w.shape[0]) >= n:
                w = w[:n]
            else:
                w = jnp.pad(w, (0, n - int(w.shape[0])), constant_values=1.0)
            X32 = X.astype(jnp.float32)
            L = lens.astype(jnp.float32)  # true-length mean, not bucket mean

            def run(be):
                r0 = wave(X32, backend=be, row_lens=lens)
                return epilogue(r0, L, w, eps, X32, X32, backend=be,
                                row_lens=lens)

            params = {"ragged": True, "eps": eps}
        else:
            raise ValueError(f"unknown ragged family {family!r} "
                             "(softmax | softmax.cdf | rmsnorm)")
        return self._timed(family, (b, n), str(X.dtype), params, run,
                           backend=backend, record=record)

    # -- direct (already-batched) calls ----------------------------------
    def softmax(self, x, stable: bool = True,
                backend: "str | None" = None, axis: int = -1):
        """Routed softmax over a whole operand (any batch shape): ONE
        2-launch row schedule, with telemetry + manifest recording.
        ``axis=0`` normalizes the *columns* of a 2-D operand (the kernel
        IR's ``transpose_layout`` domain) — same 2-launch schedule,
        routed and recorded under the ``softmax.axis0`` family."""
        X = jnp.asarray(x)
        if axis in (0, -2) and X.ndim >= 2:
            if X.ndim != 2:
                raise ValueError("axis=0 softmax requires a 2-D operand")
            out = self._run_batch("softmax.axis0", X, {"stable": stable},
                                  backend=backend)
            return out.reshape(X.shape).astype(X.dtype)
        rows = X.reshape(-1, X.shape[-1]) if X.ndim >= 2 else X.reshape(1, -1)
        out = self._run_batch("softmax", rows, {"stable": stable},
                              backend=backend)
        return out.reshape(X.shape).astype(X.dtype)

    def rmsnorm(self, x, w, eps: float = 1e-6,
                backend: "str | None" = None):
        """Routed planner RMSNorm (float32 math, like
        `models.layers.rtcg_rmsnorm`)."""
        X = jnp.asarray(x)
        rows = jnp.reshape(X, (-1, X.shape[-1])).astype(jnp.float32)
        w32 = jnp.asarray(w).astype(jnp.float32)
        out = self._run_batch("rmsnorm", rows, {"w": w32, "eps": eps},
                              backend=backend)
        return out.reshape(X.shape).astype(X.dtype)

    def sample(self, logits, key, temperature: float = 1.0,
               backend: "str | None" = None):
        """Temperature sampling with the softmax routed through the
        runtime: probabilities come from ONE fused 2-launch schedule for
        the whole ``(B, V)`` block; the categorical draw is ONE device
        uniform draw plus a vectorized host-side inverse-CDF (zero
        extra generated-kernel launches, zero per-row round trips —
        this sits in the engine's decode hot path)."""
        L = jnp.asarray(logits)
        if temperature == 0.0:
            return jnp.argmax(L, axis=-1).astype(jnp.int32)
        probs = self.softmax(L / float(temperature), stable=True,
                             backend=backend)
        rows = np.asarray(probs, np.float64).reshape(-1, probs.shape[-1])
        cum = np.cumsum(rows, axis=-1)
        u = np.asarray(jax.random.uniform(key, (rows.shape[0],)),
                       np.float64) * cum[:, -1]   # residual-mass normalize
        toks = np.minimum((cum < u[:, None]).sum(axis=-1),
                          rows.shape[-1] - 1).astype(np.int32)
        return jnp.asarray(toks.reshape(L.shape[:-1]), jnp.int32)

    # -- coalescing single-row submissions -------------------------------
    def submit_softmax(self, row, stable: bool = True,
                       deadline: "float | None" = None,
                       ragged: bool = False) -> RuntimeFuture:
        """Queue one softmax row; same-bucket rows inside the window
        flush as ONE ``(K, N)`` 2-launch schedule.  ``deadline``
        (seconds) bounds this request's retry budget after a failed
        flush (PR 6 poison isolation).  With ``ragged=True`` the row
        coalesces with *any* length (rows pad to the flush max and the
        kernels mask per-row), so mixed-length traffic still batches."""
        return self.executor.submit("softmax", row,
                                    shared={"stable": stable},
                                    key_extra=(bool(stable),),
                                    deadline=deadline, ragged=ragged)

    def submit_rmsnorm(self, row, w, eps: float = 1e-6,
                       deadline: "float | None" = None,
                       ragged: bool = False) -> RuntimeFuture:
        """Queue one rmsnorm row; coalesces with rows sharing the SAME
        weight vector (identity) and eps."""
        return self.executor.submit(
            "rmsnorm", jnp.asarray(row).astype(jnp.float32),
            shared={"w": w, "eps": eps}, key_extra=(id(w), float(eps)),
            deadline=deadline, ragged=ragged)

    def submit_sample(self, logits_row, key, temperature: float = 1.0,
                      deadline: "float | None" = None) -> RuntimeFuture:
        """Queue one sampler request: the row joins the ragged
        ``softmax.cdf`` micro-batch (scaled by its temperature at
        submit so the batch stays homogeneous) — mixed vocab/logit
        lengths coalesce into ONE flush, and the inverse-CDF cumsum
        runs fused inside the flush's epilogue launch.  The per-request
        post-step is a single host ``searchsorted`` on this request's
        CDF row."""
        row = jnp.asarray(logits_row) / float(max(temperature, 1e-8))
        return self.executor.submit(
            "softmax.cdf", row, shared={}, key_extra=(True,),
            post=lambda cdf_row: int(_draw_cdf(np.asarray(cdf_row), key)),
            deadline=deadline, ragged=True)

    # -- lifecycle / introspection ---------------------------------------
    def warmup(self) -> dict:
        """Replay the persisted manifest: rebuild every recorded driver
        (on each entry's recorded backend) before live traffic, so
        traffic hitting recorded cells compiles nothing — see
        `WarmStartManifest.replay` for the report shape.

        Row entries are additionally replayed at every power-of-two
        batch size below the recorded one: executor flushes chunk by
        window timing (a quiet period flushes 5 rows, not 16), and a
        ``K'``-row flush uses exactly the driver of the
        ``next_pow2(K')`` batch bucket — so warming the pow2 ladder
        covers every partial-flush geometry live traffic can produce.

        Persisted transformation sequences load *first*, so replayed
        kernels build with the winning tiled/transposed schedules — the
        zero-compile-on-replay property covers the transformed drivers,
        not their untuned defaults.

        Fleet router telemetry (PR 8) imports first as well: cells this
        process has never measured adopt the fleet's merged EMAs, so a
        restarted worker routes like its predecessors from request one
        instead of re-learning pallas-vs-xla from priors."""
        adopted = self.router.import_state(self.manifest.load_router_state())
        self.manifest.load_sequences()

        def run_entry(entry):
            geometry = tuple(int(d) for d in entry["geometry"])
            dtype = entry["dtype"]
            params = entry.get("params", {})
            if entry["family"] == "rmsnorm":
                shared = {"w": jnp.ones((geometry[-1],), dtype),
                          "eps": params.get("eps", 1e-6)}
            else:
                shared = {"stable": params.get("stable", True)}
            batches = [geometry[0]]
            p = 1
            while p < geometry[0]:   # pow2 sub-bucket ladder
                batches.append(p)
                p *= 2
            ragged = bool(params.get("ragged"))
            for b in batches:
                if b * geometry[-1] <= 1:
                    continue  # a 1-element operand cannot plan a row
                    # reduction (it binds as a scalar leaf) — live
                    # traffic can't produce this driver either
                # ragged entries replay with synthetic full-length rows:
                # the driver is length-agnostic (lengths are a runtime
                # operand), so any mix warms the same compiled pair
                lens = (jnp.full((b,), geometry[-1], jnp.int32)
                        if ragged else None)
                self._run_batch(entry["family"],
                                jnp.zeros((b, geometry[-1]), dtype), shared,
                                backend=entry["backend"], record=False,
                                row_lens=lens)

        report = self.manifest.replay(run_entry)
        report["router_cells_adopted"] = adopted
        return report

    def sync_router(self) -> dict:
        """Two-way router-telemetry sync with the fleet manifest (PR 8):
        publish this process's measured EMAs (flock-merged,
        observation-weighted), then adopt merged cells this process has
        not measured itself.  Workers call this on the supervisor's
        ``sync`` control op and at drain; `close()` publishes one final
        time."""
        self.manifest.record_router_state(self.router.export_state())
        adopted = self.router.import_state(self.manifest.load_router_state())
        return {"adopted": adopted}

    def stats(self) -> dict:
        """One JSON-able snapshot across all three pieces + dispatch.

        PR 10 adds three keys: ``metrics`` (the process's labeled
        histogram/counter document — merged associatively by
        `merge_stats` so fleet percentiles are exact), ``kvcache`` (the
        aggregate over every live `RequestsCache` in this process, so
        fleet merges stop dropping slot/eviction/shed counts), and
        ``trace`` (recorder occupancy + the REPRO_TRACE mode)."""
        from repro.runtime import kvcache as _kvcache

        return {
            "backend": self.backend,
            "executor": self.executor.stats(),
            "router": self.router.stats(),
            "manifest": {"entries": len(self.manifest),
                         "sequences": len(self.manifest.sequences())},
            "dispatch": dispatch.stats(),
            "degradations": dispatch.degradation_counts(),
            "breaker": self.router.breaker.stats(),
            "faults": faults.stats(),
            "metrics": observe.METRICS.snapshot(),
            "kvcache": _kvcache.aggregate_stats(),
            "trace": {"mode": observe.mode(), **observe.RECORDER.stats()},
        }

    def stats_snapshot(self) -> dict:
        """Wire-safe `stats()` for cross-process aggregation (PR 8): the
        same document round-tripped through JSON so every leaf is a
        plain int/float/str — a fleet worker ships this over its pipe
        and the dispatcher folds N of them via `merge_stats`."""
        import json

        return json.loads(json.dumps(self.stats(), default=str))

    def flush(self, wait: bool = True) -> None:
        self.executor.flush(wait=wait)

    def close(self) -> None:
        self.executor.close()
        try:
            self.manifest.record_router_state(self.router.export_state())
        except Exception:
            pass  # telemetry publish must never block shutdown
        self.manifest.stop_listening()


_RAGGED_LOCK = threading.Lock()
_RAGGED_KERNELS: dict = {}


def _ragged_kernels(family: str):
    """Module-cached (wave, epilogue) kernel pair for one ragged family.

    Built once per process and shared by every runtime instance — the
    kernel objects only *describe* the computation; compiled drivers
    live in the process-wide dispatch LRU keyed per backend/bucket, so
    sharing the family objects costs nothing and keeps content keys
    stable across runtimes (one driver serves them all)."""
    from repro.core.elementwise import ElementwiseKernel
    from repro.core.platform import BroadcastArg, ScalarArg, VectorArg
    from repro.core.reduction import ReductionKernel

    with _RAGGED_LOCK:
        pair = _RAGGED_KERNELS.get(family)
        if pair is not None:
            return pair
        f32 = jnp.float32
        if family in ("softmax", "softmax.cdf"):
            wave = _RAGGED_KERNELS.get("_softmax_wave")
            if wave is None:
                # stable two-accumulator wave: row max + shifted exp sum
                wave = ReductionKernel(
                    [f32, f32], ["-3.4e38", "0"],
                    ["fmaxf(a, b)", "a + b"],
                    ["x[i]", "expf(x[i] - _acc0)"],
                    "float *x", axis=-1, name="ragged_softmax_wave")
                _RAGGED_KERNELS["_softmax_wave"] = wave
            op = ("out[i] = cumsumf(expf(x[i] - r0) / r1)"
                  if family == "softmax.cdf"
                  else "out[i] = expf(x[i] - r0) / r1")
            epilogue = ElementwiseKernel(
                [BroadcastArg(f32, "r0", "row"), BroadcastArg(f32, "r1", "row"),
                 VectorArg(f32, "x"), VectorArg(f32, "out")],
                op, name=f"ragged_{family.replace('.', '_')}_epi",
                layout="rows")
        elif family == "rmsnorm":
            wave = ReductionKernel(
                f32, "0", "a + b", "x[i] * x[i]",
                "float *x", axis=-1, name="ragged_rmsnorm_wave")
            epilogue = ElementwiseKernel(
                [BroadcastArg(f32, "r0", "row"), BroadcastArg(f32, "L", "row"),
                 BroadcastArg(f32, "w", "col"), ScalarArg(f32, "eps"),
                 VectorArg(f32, "x"), VectorArg(f32, "out")],
                "out[i] = x[i] / sqrtf(r0 / L + eps) * w[i]",
                name="ragged_rmsnorm_epi", layout="rows")
        else:
            raise ValueError(f"unknown ragged family {family!r}")
        pair = (wave, epilogue)
        _RAGGED_KERNELS[family] = pair
        return pair


def _draw_cdf(cdf_row: np.ndarray, key) -> int:
    """Categorical draw from one *cumulative* probability row (the
    fused ``softmax.cdf`` epilogue output): the cumsum already ran on
    device inside the flush, so the host post-step is a single
    ``searchsorted`` — no per-request ``np.cumsum`` over the vocab."""
    cum = np.asarray(cdf_row, np.float64)
    u = float(jax.random.uniform(key, ())) * cum[-1]
    return min(int(np.searchsorted(cum, u, side="right")),
               cum.shape[-1] - 1)


def _draw(probs_row: np.ndarray, key) -> int:
    """Inverse-CDF categorical draw from one probability row (host-side;
    normalizes residual fp mass so the draw is always in range)."""
    cum = np.cumsum(np.asarray(probs_row, np.float64))
    u = float(jax.random.uniform(key, ())) * cum[-1]
    return min(int(np.searchsorted(cum, u, side="right")),
               probs_row.shape[-1] - 1)


def default_runtime() -> ServingRuntime:
    """Process-wide runtime used by ``backend="auto"`` layer calls and
    `serving.engine.Engine` when none is passed explicitly."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = ServingRuntime()
        return _DEFAULT


def set_default_runtime(rt: "ServingRuntime | None") -> "ServingRuntime | None":
    """Swap (or reset with ``None``) the process default — tests and
    servers that configure their own window/backend.  Returns the
    previous instance (caller decides whether to close it)."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        prev, _DEFAULT = _DEFAULT, rt
        return prev


def warmup() -> dict:
    """Module-level convenience: ``runtime.warmup()`` on the default."""
    return default_runtime().warmup()


def stats() -> dict:
    """Module-level convenience: ``runtime.stats()`` on the default."""
    return default_runtime().stats()


def stats_snapshot(rt: "ServingRuntime | None" = None) -> dict:
    """JSON-safe per-process stats document (the default runtime's, or
    an explicit one) — the unit `merge_stats` aggregates."""
    return (rt if rt is not None else default_runtime()).stats_snapshot()


#: keys that are configuration or shared state, not per-process counters:
#: aggregate by max, never by sum
_MERGE_MAX_KEYS = frozenset({
    "max_coalesce", "maxsize", "entries", "sequences", "window_s",
    "max_batch", "threshold", "cooldown_s", "active_plans", "seed",
    "tracked_cells", "pending", "capacity",
})
#: router latency tables: merge by min (the best estimate any worker
#: measured), never by sum
_MERGE_MIN_TABLES = frozenset({"ema_ms", "priors_ms"})


def _fold_stats(dst: dict, src: dict) -> None:
    for k, v in src.items():
        if k == "metrics" and isinstance(v, dict):
            # the labeled histogram/counter document merges through its
            # own (associative, exact) fold — generic numeric folding
            # would sum histogram bucket *indices* into nonsense
            cur = dst.get(k)
            dst[k] = observe.merge_metrics(cur, v) if cur else \
                observe.merge_metrics(v)
            continue
        if isinstance(v, dict):
            sub = dst.setdefault(k, {})
            if not isinstance(sub, dict):
                continue
            if k in _MERGE_MIN_TABLES:
                for kk, vv in v.items():
                    cur = sub.get(kk)
                    sub[kk] = vv if cur is None else min(cur, vv)
            else:
                _fold_stats(sub, v)
        elif isinstance(v, bool):
            dst.setdefault(k, v)
        elif isinstance(v, (int, float)):
            if k in _MERGE_MAX_KEYS:
                dst[k] = max(dst.get(k, v), v)
            else:
                dst[k] = dst.get(k, 0) + v
        else:
            dst.setdefault(k, v)


def merge_stats(snapshots: "list[dict]") -> dict:
    """Aggregate per-worker `stats_snapshot()` documents into ONE
    fleet-level view (PR 8): counters (requests, flushes, launches,
    retries, degradations, failovers, route counts, fault injections)
    sum across workers; shared-state sizes (manifest entries) and
    configuration knobs take the max; router latency tables take the
    elementwise min (the best estimate any worker measured); realized
    ratios (coalesce factor, launches/request) are recomputed from the
    summed counters so the fleet view is self-consistent."""
    merged: dict = {}
    folded = 0
    for snap in snapshots:
        if not isinstance(snap, dict):
            continue
        _fold_stats(merged, snap)
        folded += 1
    ex = merged.get("executor")
    if isinstance(ex, dict):
        req, fl = ex.get("requests", 0), ex.get("flushes", 0)
        ex["coalesce_factor"] = (req / fl) if fl else 0.0
        ex["launches_per_request"] = \
            (ex.get("launches", 0) / req) if req else 0.0
    merged["workers_merged"] = folded
    if "metrics" in merged:
        # cross-worker percentile view straight off the merged
        # histograms: exact counts, percentiles within one bucket width
        merged["latency"] = observe.latency_summary(merged["metrics"])
    return merged


def export_trace(path, extra_events: "list[dict] | None" = None) -> int:
    """Export this process's flight recorder as Chrome trace-event JSON
    (Perfetto/chrome://tracing-loadable); returns the event count.
    `ServingFleet.export_trace` is the merged cross-worker form."""
    return observe.export_trace(path, extra_events)


def metrics_text(metrics_doc: "dict | None" = None) -> str:
    """Prometheus text exposition of the live metrics registry (or an
    explicit merged document) — what ``--stats-port`` serves."""
    return observe.metrics_text(metrics_doc)


from repro.runtime.fleet import FleetOverloadError, ServingFleet  # noqa: E402
from repro.runtime.kvcache import RequestsCache  # noqa: E402
from repro.runtime.supervisor import (BackoffPolicy,  # noqa: E402
                                      CrashLoopBreaker, Supervisor)

__all__ = [
    "ServingRuntime", "CoalescingExecutor", "RuntimeFuture",
    "BackendRouter", "CircuitBreaker", "WarmStartManifest", "bucket_for",
    "default_runtime", "set_default_runtime", "default_router",
    "set_default_router", "default_breaker", "set_default_breaker",
    "faults", "warmup", "stats", "stats_snapshot", "merge_stats",
    "ServingFleet", "FleetOverloadError", "RequestsCache", "BackoffPolicy",
    "CrashLoopBreaker", "Supervisor",
    "observe", "export_trace", "metrics_text",
]
