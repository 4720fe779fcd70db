# One function per paper table. Print ``name,us_per_call,derived`` CSV
# and write machine-readable ``BENCH_<suite>.json`` next to it (one file
# per suite: rows + dispatch compile/launch deltas) so the perf
# trajectory is trackable across PRs.
#
#   table1  — bench_filterbank:  RTCG auto-tuned 3D filter-bank conv
#   table2/3 — bench_copperhead: DSL perf fraction + LOC vs hand-written
#   table4  — bench_nn:          brute-force nearest neighbor scaling
#   §5.2    — bench_elementwise: fused RTCG kernels vs eager temporaries,
#             plus DAG-level map-reduce fusion (1 launch vs 2)
#   softmax — bench_softmax:     flat + axis-aware batched softmax (2
#             launches for a whole (B, N) batch vs 3·B unfused)
#   rmsnorm — bench_rmsnorm:     planner-fused row norm vs hand-written
#             Pallas kernel vs eager baseline
#   serving — bench_serving:     PR 5 runtime — coalesced vs per-request
#             dispatch, auto vs pinned backend, cold vs warm start
#   chaos   — bench_chaos:       PR 6 fault tolerance — availability + p50
#             under injected faults, fault-free ladder overhead,
#             serving with one backend fully dead
#   fleet   — bench_fleet:       PR 8 supervised fleet — availability at
#             0/1 injected worker kills, zero-compile warm restart,
#             explicit shed under 2x overload
#   decode  — bench_decode:      PR 9 continuous batching — ragged vs
#             per-length-bucket sampler flush, Poisson decode tokens/s
#             at capacity 1/4/16, 2-launch step budget, warm restart
#   obs     — bench_obs:         PR 10 flight recorder — REPRO_TRACE
#             overhead vs off (counters <=2%, spans <=8%, hard-asserted)
#             plus trace-export schema check
#   §6.1    — bench_dgfem:       per-order tuned element-local linalg
#   model   — bench_model:       train-step throughput + attention sweep
#
# ``--compare DIR`` re-reads the committed ``BENCH_<suite>.json`` from
# DIR and fails (exit 1) when a fused row regressed by more than
# ``--compare-tol`` (default 20%).  Rows are matched by name; the metric
# is the row's ``speedup`` over its same-run unfused baseline when
# present (machine-portable), else ``us_per_call``.
#
# All numbers are CPU (interpret-mode Pallas / XLA-CPU) wall clock: they
# check counts (launches, compiles), never chip speed.  ``chip_smoke.py``
# at the repo root is what runs the serving path on a TPU.
import argparse
import json
import sys
import traceback
from pathlib import Path


def compare_rows(fresh: dict, committed: dict, tol: float = 0.20) -> list[str]:
    """Regressions in *gated* rows of ``fresh`` vs ``committed``.

    Rows gate the build when their name marks them as a fused path
    (``.fused`` / ``.fused_stable`` suffixes) OR they carry an explicit
    ``gate: true`` flag — how BENCH_serving.json's coalesced/auto rows
    opt in (PR 5) without the fusion naming convention.  Baselines move
    with the machine.  Rows present on one side only are skipped (a new
    suite size is not a regression).  Returns human-readable messages.
    """
    old = {r["name"]: r for r in committed.get("rows", [])}
    problems = []
    for row in fresh.get("rows", []):
        name = row["name"]
        if ".fused" not in name and not row.get("gate"):
            continue
        ref = old.get(name)
        if ref is None:
            continue
        # availability rows (the chaos suite, PR 6) gate on availability
        # ALONE, with zero tolerance — a committed 1.0 must stay 1.0 —
        # and never on wall clock (latency under injected faults is a
        # property of the fault plan, not a perf regression signal)
        if "availability" in row:
            if row["availability"] < ref.get("availability", 1.0):
                problems.append(
                    f"{name}: availability {row['availability']:.3f} < "
                    f"committed {ref.get('availability', 1.0):.3f}")
            continue
        # the launch schedule is the fusion contract and is noise-free:
        # a fused row that needs MORE launches always fails, whatever tol
        if ("kernels_launched" in row and "kernels_launched" in ref
                and row["kernels_launched"] > ref["kernels_launched"]):
            problems.append(
                f"{name}: {row['kernels_launched']} launches > committed "
                f"{ref['kernels_launched']} (fusion schedule regressed)")
            continue
        if "speedup" in row and "speedup" in ref:
            # machine-portable: fused-vs-unfused ratio within one run
            if row["speedup"] < ref["speedup"] * (1.0 - tol):
                problems.append(
                    f"{name}: speedup {row['speedup']:.2f}x < "
                    f"{(1 - tol):.0%} of committed {ref['speedup']:.2f}x")
        elif row["us_per_call"] > ref["us_per_call"] * (1.0 + tol):
            problems.append(
                f"{name}: {row['us_per_call']:.1f}us > "
                f"{(1 + tol):.0%} of committed {ref['us_per_call']:.1f}us")
    return problems


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma list: table1,table2,...")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--json-dir", default=".",
                    help="directory for BENCH_<suite>.json files")
    ap.add_argument("--sizes", default="",
                    help="comma list of element counts for the fusion/softmax "
                         "suites (smoke tests use small sizes)")
    ap.add_argument("--batches", default="",
                    help="comma list of BxN row shapes (e.g. 8x512,64x4096) "
                         "for the batched softmax / rmsnorm suites")
    ap.add_argument("--compare", default="",
                    help="directory holding committed BENCH_<suite>.json; "
                         "fail on >tol regression in fused rows")
    ap.add_argument("--compare-tol", type=float, default=0.20)
    ap.add_argument("--chaos", default="",
                    help="arm a process-lifetime transient fault plan, e.g. "
                         "compile:0.05,launch:0.05 (same spec as REPRO_CHAOS)")
    args = ap.parse_args()
    from repro.core.platform import configure_compile_cache

    configure_compile_cache()

    if args.chaos:
        from repro.runtime import faults
        faults.install_env_plan(args.chaos)

    from benchmarks import (bench_chaos, bench_copperhead, bench_decode,
                            bench_dgfem, bench_elementwise, bench_filterbank,
                            bench_fleet, bench_model, bench_nn, bench_obs,
                            bench_rmsnorm, bench_serving, bench_softmax)
    from benchmarks import common
    from benchmarks.common import header
    from repro.core import dispatch
    from repro.core.cache import environment_fingerprint

    fusion_kwargs = {}
    softmax_kwargs = {}
    rmsnorm_kwargs = {}
    serving_kwargs = {}
    if args.sizes:
        sizes = tuple(int(s) for s in args.sizes.split(","))
        fusion_kwargs["sizes"] = sizes
        softmax_kwargs["sizes"] = sizes
    if args.batches:
        shapes = tuple(tuple(int(d) for d in s.split("x"))
                       for s in args.batches.split(","))
        softmax_kwargs["batches"] = shapes
        rmsnorm_kwargs["shapes"] = shapes
        serving_kwargs["shapes"] = shapes   # K x N request waves

    suites = {
        "table1": bench_filterbank.run,
        "table2": bench_copperhead.run,
        "table4": bench_nn.run,
        "fusion": lambda repeats: bench_elementwise.run(repeats=repeats, **fusion_kwargs),
        "softmax": lambda repeats: bench_softmax.run(repeats=repeats, **softmax_kwargs),
        "rmsnorm": lambda repeats: bench_rmsnorm.run(repeats=repeats, **rmsnorm_kwargs),
        "serving": lambda repeats: bench_serving.run(repeats=repeats, **serving_kwargs),
        "chaos": lambda repeats: bench_chaos.run(repeats=repeats, **serving_kwargs),
        "fleet": lambda repeats: bench_fleet.run(repeats=repeats, **serving_kwargs),
        "decode": bench_decode.run,
        "obs": bench_obs.run,
        "dgfem": bench_dgfem.run,
        "model": bench_model.run,
    }
    chosen = args.only.split(",") if args.only else list(suites)
    json_dir = Path(args.json_dir)
    json_dir.mkdir(parents=True, exist_ok=True)
    header()
    failed = []
    regressions: list[str] = []
    for name in chosen:
        row_start = len(common.ROWS)
        compiles0, launches0 = dispatch.compile_counts(), dispatch.launch_counts()
        try:
            suites[name](repeats=args.repeats)
        except Exception:
            traceback.print_exc()
            failed.append(name)
            continue
        cache = dispatch.driver_cache()
        compiles1, launches1 = dispatch.compile_counts(), dispatch.launch_counts()
        payload = {
            "suite": name,
            "env": environment_fingerprint(),
            # per-suite deltas; driver_cache is end-of-suite *state* only
            # (its hit/miss counters are process-cumulative, so they would
            # read skewed next to the deltas)
            "compile_count": sum(compiles1.values()) - sum(compiles0.values()),
            "launch_count": sum(launches1.values()) - sum(launches0.values()),
            # the same deltas broken down by execution backend (PR 4):
            # the pallas-vs-xla split a suite exercised
            "compile_counts": {
                k: d for k in compiles1
                if (d := compiles1[k] - compiles0.get(k, 0)) > 0},
            "launch_counts": {
                k: d for k in launches1
                if (d := launches1[k] - launches0.get(k, 0)) > 0},
            "driver_cache": {"size": len(cache), "maxsize": cache.maxsize},
            "rows": common.ROWS[row_start:],
        }
        out = json_dir / f"BENCH_{name}.json"
        out.write_text(json.dumps(payload, indent=2, default=str))
        print(f"# wrote {out}", flush=True)
        if args.compare:
            committed = Path(args.compare) / f"BENCH_{name}.json"
            if committed.exists():
                probs = compare_rows(payload, json.loads(committed.read_text()),
                                     tol=args.compare_tol)
                regressions.extend(f"[{name}] {p}" for p in probs)
            else:
                print(f"# compare: no committed {committed}, skipping",
                      flush=True)
    if regressions:
        print("PERF REGRESSIONS (fused rows):", file=sys.stderr)
        for p in regressions:
            print(f"  {p}", file=sys.stderr)
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
    if failed or regressions:
        sys.exit(1)


if __name__ == "__main__":
    main()
