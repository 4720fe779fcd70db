"""Chip smoke test: the serving main path on a TPU, at full width.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip (data, model) = (1, 4) mesh path

One chip: internlm2-1.8b at its published widths (24 layers, d 2048,
vocab 92,544) with random weights from ``--seed`` serves 8 requests
through ``serving.ContinuousEngine`` at capacity 8 and ``max_len`` 2048,
sampling at temperature > 0 through ``runtime.ServingRuntime`` pinned
first to the generated Pallas kernels and then to the XLA backend.  It
checks that decode logits through the KV cache match a no-cache
forward, that the sampler's probabilities match ``jax.nn.softmax``,
that the Pallas phase launched compiled (not interpreted) Pallas
kernels, and that no degradation rung or failover ran.

Four chips: the same parameters sharded over a (1, 4) mesh with
``schema.param_specs``; greedy requests through ``ContinuousEngine``
with that ``MeshContext``; logits compared with a one-device run of the
same parameters.

Exits non-zero, before any work and without a result line, unless JAX's
first device is a TPU.  The last line of standard output is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Step times printed on the way are host-clock readings, informational
only.  JAX's persistent compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache/`` here.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import sys
import time
import warnings
from contextlib import contextmanager
from pathlib import Path

ARCH = "internlm2-1.8b"
CAPACITY = 8
MAX_LEN = 2048
N_REQUESTS = 8
PROMPT_RANGE = (64, 1024)
NEW_TOKENS = (16, 32)
TEMPERATURE = 0.8
# cache-vs-forward check: a prompt of PROMPT_LEN tokens, then
# DECODE_STEPS teacher-forced decode steps (PROMPT_LEN + DECODE_STEPS
# is one 1024-token attention chunk for the reference forward)
PROMPT_LEN, DECODE_STEPS = 1000, 24
# Logits distance is max|diff| over std(reference logits).  In float32
# (weights cast up, matmuls at "highest" precision) the cache path and
# the no-cache forward compute the same sums in another order, so they
# agree to rounding; a wrong cache position or mask moves logits by
# the whole spread.  LOGIT_TOL_F32 is 100x below what bf16 alone moves.
LOGIT_TOL_F32 = 1e-3
# In bfloat16 the two paths round in different places (cached K/V, f32
# decode attention vs chunked bf16 prefill attention), and random
# weights carry that through 24 layers to a tenth or more of the
# spread.  The bf16 cache path must stay as close to the float32
# reference as BF16_SLACK times the bf16 no-cache forward does.
BF16_SLACK = 2.0
# Sampler tolerances (f32): probabilities against jax.nn.softmax, and
# the CDF against a float64 cumulative sum of those probabilities (an
# f32 prefix sum over 92,544 probabilities that add up to 1: each of
# its ~17 levels of partial sums rounds within f32 eps of 1).
PROB_ATOL, PROB_RTOL = 1e-7, 1e-4
CDF_ATOL = 2e-5
# A flush that compiles a full-vocab kernel pair on a cold cache takes
# seconds; the runtime's default 30 s flush wait is for warm serving.
FLUSH_TIMEOUT = 180.0
STALL_EXIT_S = 1080.0


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (persistent
    cache reads included), accumulated per named phase; each phase's
    line also shows the first device's memory when the phase ends."""

    def __init__(self):
        import jax

        self.phase_name = None
        self.seconds: dict = {}
        self.backend_seconds: dict = {}
        self.cache_hits: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_):
        if self.phase_name is None or not event.startswith("/jax/core/compile/"):
            return
        self.seconds[self.phase_name] = self.seconds.get(self.phase_name, 0.0) + duration
        if event.endswith("backend_compile_duration"):
            self.backend_seconds[self.phase_name] = (
                self.backend_seconds.get(self.phase_name, 0.0) + duration)

    def _event(self, event: str, **_):
        if self.phase_name is not None and event == "/jax/compilation_cache/cache_hits":
            self.cache_hits[self.phase_name] = self.cache_hits.get(self.phase_name, 0) + 1

    @contextmanager
    def phase(self, name: str):
        self.phase_name = name
        try:
            yield
        finally:
            import jax

            self.phase_name = None
            mem = jax.devices()[0].memory_stats() or {}
            log(f"compile[{name}]: {self.seconds.get(name, 0.0):.3f} s total, "
                f"{self.backend_seconds.get(name, 0.0):.3f} s backend compile, "
                f"{self.cache_hits.get(name, 0)} persistent-cache hits; "
                f"bytes in use {mem.get('bytes_in_use', 'not reported')}, "
                f"peak so far {mem.get('peak_bytes_in_use', 'not reported')}")


def check_attention_chunks(cfg, seq_lens) -> None:
    """The smoke's sequence lengths must take the chunked flash path:
    `flash_attention_jnp` silently switches to the naive full-matrix
    path when a length does not divide its chunk."""
    for s in seq_lens:
        for chunk in (cfg.attn_q_chunk, cfg.attn_kv_chunk):
            c = min(chunk, s)
            if s % c:
                raise AssertionError(f"sequence length {s} does not divide "
                                     f"attention chunk {chunk}")


def make_requests(cfg, rng, n: int):
    """``n`` (prompt, max_new) pairs from ``rng``, longest prompt first:
    the engine decodes on one shared position, so a descending FIFO
    admits every request into the first step."""
    lo, hi = PROMPT_RANGE
    lens = sorted(rng.integers(lo, hi + 1, n).tolist(), reverse=True)
    return [(rng.integers(0, cfg.vocab_size, L).astype("int32"),
             int(rng.integers(NEW_TOKENS[0], NEW_TOKENS[1] + 1)))
            for L in lens]


def init_params(cfg, seed: int, clock):
    """Random weights from ``seed`` through ``schema.init_params``, as one
    jitted program (eager, it compiles each op per leaf shape: a minute
    more on a cold cache)."""
    import jax

    from repro.models import schema

    with clock.phase("init"):
        params = jax.jit(lambda k: schema.init_params(cfg, k))(
            jax.random.PRNGKey(seed))
        return jax.block_until_ready(params)


def cache_vs_forward(cfg, params, tokens, ctx=None):
    """Logits of the last prompt position and of each teacher-forced
    decode step through the KV cache (the engine's `transformer.prefill`
    / `decode_step`), and of the same positions from one no-cache
    `transformer.forward` -> (cached (K, V), reference (K, V))."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer
    from repro.sharding.partition import NULL_CTX

    ctx = ctx or NULL_CTX
    L = PROMPT_LEN
    toks = jnp.asarray(tokens[None, :])
    prefill = jax.jit(lambda p, b: transformer.prefill(cfg, p, b, ctx,
                                                       max_len=MAX_LEN))
    decode = jax.jit(lambda p, c, t, pos: transformer.decode_step(
        cfg, p, c, t, pos, ctx))

    def reference(p, t):
        out = transformer.forward(cfg, p, {"tokens": t}, ctx, mode="prefill")
        return transformer.logits_from_hidden(cfg, p, out["x"][:, L - 1:-1], ctx)

    logits, cache = prefill(params, {"tokens": toks[:, :L]})
    got = [logits[0]]
    for j in range(DECODE_STEPS - 1):
        logits, cache = decode(params, cache, toks[:, L + j:L + j + 1],
                               jnp.int32(L + j))
        got.append(logits[0])
    ref = jax.jit(reference)(params, toks)[0]
    return np.asarray(jnp.stack(got), np.float64), np.asarray(ref, np.float64)


def distance(got, ref) -> float:
    import numpy as np

    return float(np.max(np.abs(got - ref)) / np.std(ref))


def check_logits(name: str, got, ref, tol: float) -> None:
    import numpy as np

    err = distance(got, ref)
    agree = float(np.mean(np.argmax(got, -1) == np.argmax(ref, -1)))
    log(f"{name}: max|diff|/std(ref) = {err:.4g} (tolerance {tol:.4g}), "
        f"argmax agreement {agree:.3f} over {got.shape[0]} positions")
    if not np.isfinite(got).all() or err > tol:
        raise AssertionError(f"{name}: logits differ by {err:.4g} std > {tol:.4g}")


def as_f32(cfg, params):
    """``cfg`` and ``params`` in float32 (the bf16 weights cast up)."""
    import jax
    import jax.numpy as jnp

    return cfg.replace(dtype="float32"), jax.tree.map(
        lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x,
        params)


def check_cache_path(cfg, params, tokens, clock) -> None:
    """Cached decode logits against the no-cache forward: exact to
    rounding in float32, and in bf16 no further from the float32
    reference than `BF16_SLACK` times the bf16 forward."""
    import jax

    cfg32, p32 = as_f32(cfg, params)
    with clock.phase("cache-vs-forward f32"), \
            jax.default_matmul_precision("highest"):
        got32, ref32 = cache_vs_forward(cfg32, p32, tokens)
    del p32
    check_logits("f32 cache vs f32 forward", got32, ref32, LOGIT_TOL_F32)
    with clock.phase("cache-vs-forward bf16"):
        got16, ref16 = cache_vs_forward(cfg, params, tokens)
    fwd16 = distance(ref16, ref32)
    log(f"bf16 forward vs f32 forward: max|diff|/std(ref) = {fwd16:.4g}; "
        f"bf16 cache vs bf16 forward: {distance(got16, ref16):.4g}")
    check_logits("bf16 cache vs f32 forward", got16, ref32, BF16_SLACK * fwd16)


def flush(rt, what: str) -> None:
    """Flush ``rt``'s executor with a cold-compile allowance; report
    how long it took, or every thread's stack when it does not end."""
    t0 = time.perf_counter()
    try:
        rt.executor.flush(wait=True, timeout=FLUSH_TIMEOUT)
    except TimeoutError:
        faulthandler.dump_traceback(file=sys.stdout, all_threads=True)
        raise
    log(f"[{rt.backend}] {what} flush: {time.perf_counter() - t0:.3f} s")


def check_sampler(rt, vocab: int, rng) -> None:
    """Ragged sampler flush (the engine's ``softmax.cdf`` family) and the
    ragged softmax flush on mixed row lengths vs ``jax.nn.softmax``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    lens = [vocab, vocab - vocab // 8 - 1, vocab // 2 + 3, vocab // 100 + 5]
    rows = [jnp.asarray(rng.standard_normal(n).astype("float32") * 3.0)
            for n in lens]
    cdfs = [rt.executor.submit("softmax.cdf", r, key_extra=(True,), ragged=True)
            for r in rows]
    probs = [rt.submit_softmax(r, ragged=True) for r in rows]
    flush(rt, "ragged sampler check")
    worst_p = worst_c = 0.0
    for r, fc, fp in zip(rows, cdfs, probs):
        ref = np.asarray(jax.nn.softmax(r), np.float64)
        p = np.asarray(fp.result(timeout=FLUSH_TIMEOUT), np.float64)
        c = np.asarray(fc.result(timeout=FLUSH_TIMEOUT), np.float64)
        np.testing.assert_allclose(p, ref, rtol=PROB_RTOL, atol=PROB_ATOL)
        np.testing.assert_allclose(c, np.cumsum(ref), rtol=0, atol=CDF_ATOL)
        worst_p = max(worst_p, float(np.max(np.abs(p - ref))))
        worst_c = max(worst_c, float(np.max(np.abs(c - np.cumsum(ref)))))
    log(f"sampler[{rt.backend}]: probabilities max|diff| {worst_p:.3g} "
        f"(rtol {PROB_RTOL}, atol {PROB_ATOL}), CDF max|diff| {worst_c:.3g} "
        f"(atol {CDF_ATOL}) over row lengths {lens}")


def check_clean(rt, launches: dict) -> None:
    """No ladder rung, no failover, no flush the executor had to retry
    row by row, no interpreted Pallas driver, and launches only on the
    pinned backend."""
    from repro.core import dispatch
    from repro.runtime.router import default_breaker

    backend = rt.backend
    deg = dispatch.degradation_counts()
    failovers = default_breaker().stats()["failovers"]
    ex = rt.executor.stats()
    isolated = {k: ex[k] for k in ("batch_retries", "isolated_rows",
                                   "row_failures") if ex[k]}
    cache = dispatch.driver_cache()
    pallas = [cache.get(k) for k in cache.keys() if k[1] == "pallas"]
    interpreted = sum(getattr(d, "interpret", True) is not False for d in pallas)
    log(f"[{backend}] launches by backend {launches}; degradations {deg}; "
        f"failovers {failovers}; executor isolation {isolated}; "
        f"pallas drivers {len(pallas)}, interpreted {interpreted}")
    if deg or failovers or isolated:
        raise AssertionError(f"[{backend}] degraded: {deg}, failovers "
                             f"{failovers}, executor isolation {isolated}")
    if interpreted:
        raise AssertionError(f"[{backend}] {interpreted} Pallas drivers interpret")
    if set(launches) != {backend} or launches[backend] <= 0:
        raise AssertionError(f"[{backend}] launches {launches}")


def serve(cfg, params, backend: str, requests, clock, rng) -> None:
    """Serve ``requests`` through ContinuousEngine with the runtime
    pinned to ``backend``, temperature sampling (the ragged
    ``softmax.cdf`` flush every step)."""
    import jax
    import numpy as np

    from repro import runtime
    from repro.core import dispatch
    from repro.serving.engine import ContinuousEngine

    rt = runtime.ServingRuntime(backend=backend, max_batch=CAPACITY)
    try:
        with dispatch.count_launches() as lc:
            with clock.phase(f"{backend}:sampler-flush"):
                futs = [rt.submit_sample(
                    np.asarray(rng.standard_normal(cfg.vocab_size), "float32"),
                    jax.random.PRNGKey(i), TEMPERATURE) for i in range(CAPACITY)]
                flush(rt, "first sampler")
                [f.result(timeout=FLUSH_TIMEOUT) for f in futs]
            check_sampler(rt, cfg.vocab_size, rng)
            eng = ContinuousEngine(cfg, params, capacity=CAPACITY,
                                   max_len=MAX_LEN, runtime=rt)
            ids = {eng.submit(p, max_new=m): m for p, m in requests}
            times = []
            while len(eng.done) < len(ids):
                name = {0: "admit", 1: "decode"}.get(len(times))
                t0 = time.perf_counter()
                if name:
                    with clock.phase(f"{backend}:{name}"):
                        eng.step(temperature=TEMPERATURE)
                        jax.block_until_ready(eng.cache)
                else:
                    eng.step(temperature=TEMPERATURE)
                    jax.block_until_ready(eng.cache)
                times.append(time.perf_counter() - t0)
                if len(times) > 4 * MAX_LEN:
                    raise AssertionError("engine did not drain")
        st = eng.stats()
        short = [r.request_id for r in eng.done if len(r.tokens) != ids[r.request_id]]
        if short or eng.evicted_ids:
            raise AssertionError(f"[{backend}] truncated {short}, "
                                 f"evicted {eng.evicted_ids}")
        steady = sorted(times[2:])
        log(f"[{backend}] served {len(eng.done)} requests, "
            f"{st['tokens_generated']} tokens in {st['steps']} steps; "
            f"steady step (decode + sampler flush, host clock) median "
            f"{steady[len(steady) // 2] * 1e3:.2f} ms over {len(steady)} steps")
        check_clean(rt, lc.by_backend)
        del eng
    finally:
        rt.close()


def one_chip(args, clock) -> None:
    import jax
    import numpy as np

    from repro.configs.registry import get_config
    from repro.models import schema

    cfg = get_config(ARCH)
    check_attention_chunks(cfg, (PROMPT_LEN, PROMPT_LEN + DECODE_STEPS, MAX_LEN))
    params = init_params(cfg, args.seed, clock)
    log(f"{ARCH}: {schema.count_params(params) / 1e9:.3f} B parameters, "
        f"{sum(x.nbytes for x in jax.tree.leaves(params)) / 2**30:.2f} GiB")
    rng = np.random.default_rng(args.seed)
    tokens = rng.integers(0, cfg.vocab_size, PROMPT_LEN + DECODE_STEPS)
    check_cache_path(cfg, params, tokens, clock)
    requests = make_requests(cfg, rng, N_REQUESTS)
    log(f"requests: prompt lengths {[len(p) for p, _ in requests]}, "
        f"new tokens {[m for _, m in requests]}")
    for backend in ("pallas", "xla"):
        serve(cfg, params, backend, requests, clock, rng)


def four_chips(args, clock) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding

    from repro.configs.registry import get_config
    from repro.launch.mesh import make_mesh
    from repro.models import schema
    from repro.serving.engine import ContinuousEngine
    from repro.sharding.partition import MeshContext

    cfg = get_config(ARCH)
    check_attention_chunks(cfg, (PROMPT_LEN, PROMPT_LEN + DECODE_STEPS, MAX_LEN))
    mesh = make_mesh((1, 4), ("data", "model"))
    ctx = MeshContext(mesh, profile=cfg.parallelism_profile)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             schema.param_specs(cfg, mesh))
    params = init_params(cfg, args.seed, clock)

    # logits in float32, where sharding changes only summation order
    rng = np.random.default_rng(args.seed)
    tokens = rng.integers(0, cfg.vocab_size, PROMPT_LEN + DECODE_STEPS)
    cfg32, p32 = as_f32(cfg, params)
    with jax.default_matmul_precision("highest"):
        with clock.phase("one-device f32"):
            one, _ = cache_vs_forward(cfg32, p32, tokens)
        p32 = jax.device_put(p32, shardings)
        with clock.phase("four-device f32"):
            four, ref4 = cache_vs_forward(cfg32, p32, tokens, ctx)
    del p32
    check_logits("4-device f32 cache vs 4-device f32 forward", four, ref4,
                 LOGIT_TOL_F32)
    check_logits("4-device vs 1-device f32 logits", four, one, LOGIT_TOL_F32)

    sharded = jax.device_put(params, shardings)
    spans = {}
    for path, x in jax.tree_util.tree_leaves_with_path(sharded):
        if x.size >= 1 << 20:
            shard = x.addressable_shards[0].data.shape
            spans[jax.tree_util.keystr(path)] = (len(x.sharding.device_set),
                                                 tuple(shard), tuple(x.shape))
    for name, (n, shard, full) in spans.items():
        log(f"weight {name}: {full} over {n} devices, shard {shard}")
    if any(n != 4 or shard == full for n, shard, full in spans.values()):
        raise AssertionError("a large weight is not split over the 4 devices")

    requests = make_requests(cfg, rng, N_REQUESTS)
    served = {}
    for name, p, c in (("1-device", params, None), ("4-device", sharded, ctx)):
        kw = {"ctx": c} if c is not None else {}
        eng = ContinuousEngine(cfg, p, capacity=CAPACITY, max_len=MAX_LEN, **kw)
        for prompt, m in requests:
            eng.submit(prompt, max_new=m)
        with clock.phase(f"serve {name}"):
            done = eng.run(temperature=0.0)
        served[name] = {r.request_id: r.tokens for r in done}
        log(f"[{name}] served {len(done)} greedy requests, "
            f"{eng.stats()['tokens_generated']} tokens")
        del eng
    if len(served["4-device"]) != len(requests):
        raise AssertionError("4-device engine did not serve every request")
    same = np.mean([np.array_equal(served["1-device"][i], served["4-device"][i])
                    for i in served["1-device"]])
    log(f"greedy tokens identical across 1 and 4 devices for {same:.3f} "
        "of requests (informational: bf16 near-ties may flip)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r}); refusing to run on a CPU", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    # Retries absorb injected transient faults; on the chip a failure is
    # real and must surface at once, not after 6 x 6 recompiles.
    os.environ["REPRO_RETRY_MAX"] = "0"
    os.environ.pop("REPRO_CHAOS", None)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro.core.platform import configure_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is missing ({e})", file=sys.stderr)
        return 3
    cache_dir = configure_compile_cache()
    # a run that stalls prints every thread's stack and exits well
    # inside a 20-minute budget
    faulthandler.dump_traceback_later(STALL_EXIT_S, exit=True)
    # a failover warning means a pinned backend failed: make it fatal
    warnings.filterwarnings("error", message="RTCG backend", category=RuntimeWarning)
    log(f"jax {jax.__version__}; device {dev.device_kind} x {len(devices)}; "
        f"compile cache {cache_dir}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args, clock)
    else:
        one_chip(args, clock)
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}; "
        f"compile seconds total {sum(clock.seconds.values()):.3f}; "
        f"wall {time.perf_counter() - t0:.1f} s")
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
