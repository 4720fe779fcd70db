"""Serving launcher: batched generation demo with throughput report.

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --smoke

PR 5 adds the serving-runtime path (DESIGN.md §9):

  * ``--use-runtime`` routes temperature sampling through a
    `repro.runtime.ServingRuntime` — softmax over each logits block is
    ONE fused 2-launch schedule on the backend the latency router picks,
    every call lands in the warm-start manifest, and the report prints
    ``runtime.stats()`` (router routes, coalesce counters, manifest
    size);
  * ``--coalesce K`` demos cross-request micro-batching: K threads each
    submit one softmax row and the executor flushes them as a single
    ``(K, N)`` schedule — 2 launches total instead of ``2·K``.

PR 10 adds the telemetry plane (DESIGN.md §14):

  * ``--stats-port P`` serves live telemetry over stdlib HTTP while the
    demo runs: ``/metrics`` (Prometheus text exposition of the latency/
    size histograms and event counters), ``/stats`` (the runtime's JSON
    stats snapshot), ``/trace`` (the flight recorder as Chrome trace
    JSON).  Arm ``REPRO_TRACE=counters|spans`` to populate them; the
    one-shot viewer is ``python -m repro.runtime.observe --url ...``;
  * ``--trace-out PATH`` exports the recorder to a Perfetto-loadable
    Chrome trace file at exit (requires ``REPRO_TRACE=spans``).

PR 8 adds the supervised-fleet path (DESIGN.md §12):

  * ``--fleet N`` serves the sampling-softmax traffic through a
    `repro.runtime.ServingFleet` of N worker *processes* instead of the
    in-process runtime — bounded admission, heartbeat supervision,
    crash restart with backoff, at-most-once re-dispatch;
  * ``--fleet-kill`` additionally kills one worker mid-traffic (a
    deterministic ``worker.kill`` fault on its 2nd dispatch group) to
    demo that availability stays 1.0 through a process death.
"""

from __future__ import annotations

import argparse
import threading
import time

import jax
import numpy as np

from repro.configs.registry import get_config
from repro.core.platform import REPO_ROOT, configure_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.schema import init_params
from repro.serving.engine import Engine, RequestQueue
from repro.sharding.partition import MeshContext


# The fleet demo's warm-start store: a fixed path, so a second run warms
# up from the first (git-ignored).
FLEET_STORE = REPO_ROOT / ".fleet_store"


def coalesce_demo(runtime, k: int, n: int) -> None:
    """K concurrent single-row softmax requests -> one 2-launch flush."""
    from repro.core import dispatch

    rng = np.random.default_rng(0)
    rows = [rng.standard_normal(n).astype(np.float32) for _ in range(k)]
    futs: list = [None] * k

    def submit(i):
        futs[i] = runtime.submit_softmax(rows[i])

    with dispatch.count_launches() as c:
        threads = [threading.Thread(target=submit, args=(i,)) for i in range(k)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for f in futs:
            f.result(timeout=120)
    ex = runtime.executor.stats()
    print(f"coalesce demo: {k} requests x ({n},) rows -> "
          f"{c.delta} launches {c.by_backend} "
          f"(coalesce factor {ex['coalesce_factor']:.1f}, "
          f"{ex['launches_per_request']:.2f} launches/request)")


def fleet_demo(n_workers: int, k: int, n: int, kill: bool = False) -> None:
    """K softmax requests over an N-worker process fleet; optionally one
    injected worker death mid-traffic (availability must stay 1.0)."""
    from repro.runtime import ServingFleet
    from repro.runtime.supervisor import BackoffPolicy

    chaos = {}
    if kill:
        # every first-incarnation worker carries the bomb; restarted
        # incarnations are clean, so single-file dispatch + a fast
        # restart backoff keeps the re-dispatch budget comfortable
        chaos = dict(
            chaos_rules=[{"site": "worker.kill", "index": 2, "times": 1}],
            chaos_incarnations=[1], group_max=1, max_outstanding=1)
    rng = np.random.default_rng(0)
    rows = [rng.standard_normal(n).astype(np.float32) for _ in range(k)]
    with ServingFleet(workers=n_workers, backend="xla", max_batch=8,
                      max_redispatch=5,
                      backoff=BackoffPolicy(base=0.01, cap=0.2),
                      cache_dir=str(FLEET_STORE),
                      **chaos) as fleet:
        fleet.wait_ready(timeout=300)
        t0 = time.time()
        futs = [fleet.submit_softmax(r, deadline=120) for r in rows]
        ok = 0
        for r, f in zip(rows, futs):
            out = np.asarray(f.result(timeout=180))
            ok += bool(np.allclose(out.sum(), 1.0, atol=1e-4))
        dt = time.time() - t0
        fs = fleet.fleet_stats()
        print(f"fleet demo: {ok}/{k} served over {n_workers} workers "
              f"in {dt:.2f}s (availability {ok / k:.3f}); "
              f"{sum(fs['deaths'].values())} worker death(s), "
              f"{fs['redispatched']} re-dispatched, "
              f"{fs['starts'] - fs['workers']} restart(s), "
              f"{fs['shed']} shed")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--use-runtime", action="store_true",
                    help="route sampling softmax through the serving "
                         "runtime (backend auto-router + manifest)")
    ap.add_argument("--coalesce", type=int, default=0, metavar="K",
                    help="also run the K-request coalescing demo")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="also serve the request wave through an N-worker "
                         "supervised process fleet (DESIGN.md §12)")
    ap.add_argument("--fleet-kill", action="store_true",
                    help="with --fleet: kill one worker mid-traffic and "
                         "show availability staying 1.0")
    ap.add_argument("--stats-port", type=int, default=None, metavar="P",
                    help="serve live telemetry on 127.0.0.1:P while the "
                         "demo runs (/metrics, /stats, /trace); port 0 "
                         "picks a free one")
    ap.add_argument("--trace-out", default="", metavar="PATH",
                    help="export the flight recorder as Chrome trace "
                         "JSON at exit (arm REPRO_TRACE=spans)")
    args = ap.parse_args(argv)
    configure_compile_cache()
    if args.fleet:
        from repro.runtime.fleet import check_one_process_per_chip

        check_one_process_per_chip()

    stats_server = None
    if args.stats_port is not None:
        from repro.runtime import observe

        stats_server = observe.StatsServer(
            port=args.stats_port,
            stats_fn=lambda: (runtime.stats_snapshot()
                              if runtime is not None
                              else observe._default_stats()))
        print(f"stats server: {stats_server.url()} "
              f"(/metrics /stats /trace; REPRO_TRACE={observe.mode()})")

    runtime = None
    if args.use_runtime or args.coalesce:
        from repro import runtime as rtm

        # generous window: the demo's submitter threads must all land in
        # one flush (a real server tunes this against latency SLOs)
        runtime = rtm.ServingRuntime(backend="auto", window=0.1,
                                     max_batch=max(args.coalesce or 16, 2))
        warm = runtime.warmup()
        print(f"runtime warmup: {warm['replayed']}/{warm['entries']} manifest "
              f"entries replayed, {warm['compiles']} driver compiles")

    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = make_mesh((len(jax.devices()),), ("data",))
    ctx = MeshContext(mesh)
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = Engine(cfg, params, ctx,
                    max_len=args.prompt_len + args.steps + 8,
                    runtime=runtime if args.use_runtime else None)

    rng = np.random.default_rng(0)
    queue = RequestQueue()
    ids = [queue.submit(rng.integers(0, cfg.vocab_size,
                                     rng.integers(4, args.prompt_len))
                        .astype(np.int32))
           for _ in range(args.requests)]
    t0 = time.time()
    done = queue.run(engine, args.batch, args.steps,
                     temperature=args.temperature)
    dt = time.time() - t0
    total_tokens = sum(r.tokens.size for r in done)
    print(f"served {len(done)} requests, {total_tokens} tokens "
          f"in {dt:.2f}s -> {total_tokens/dt:.1f} tok/s")
    first = queue.result_for(ids[0])
    print(f"request {first.request_id}: prompt_len={first.prompt_len} "
          f"(padded to {first.padded_len}), sequence[:8]:",
          first.sequence[:8])

    if args.coalesce:
        coalesce_demo(runtime, args.coalesce, int(cfg.vocab_size))
    if args.fleet:
        fleet_demo(args.fleet, k=max(args.requests, 8),
                   n=min(int(cfg.vocab_size), 4096), kill=args.fleet_kill)
    if runtime is not None:
        st = runtime.stats()
        print("runtime.stats(): routes:", st["router"]["routes"],
              "| executor:", {k: st["executor"][k] for k in
                              ("requests", "flushes", "coalesce_factor")},
              "| manifest entries:", st["manifest"]["entries"])
        runtime.close()
    if args.trace_out:
        from repro import runtime as rtm

        n_ev = rtm.export_trace(args.trace_out)
        print(f"trace: {n_ev} events -> {args.trace_out} "
              "(load in Perfetto / chrome://tracing)")
    if stats_server is not None:
        stats_server.close()
    return len(done)


if __name__ == "__main__":
    main()
