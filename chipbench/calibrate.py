"""Readings for setting the check's limits: many seeds in one process.

    python3 chipbench/calibrate.py --workload <cell> --seeds 11,12,13 \
        --seconds 20 [--controls fp8] [--fault state] \
        [--trace-events-out FILE]

For each seed, one run of the cell as ``run.py`` makes it, printing one
JSON line with the run's result, every reading of the check and, with
``--controls fp8``, each control judged by the run's own comparison: the
reference with float8 weights put in the program's place, and the
sampler computed in bfloat16 (``controls.<p>.correct``).  ``--fault``
plants one of ``faults.KINDS`` in the timed path of every run.
``--trace-events-out`` traces the first seed's window and writes a short
slice of its events (a test fixture for the trace reduction).  Not part
of the benchmark's own runs.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import CACHE, ROOT, configure  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--fault", default="")
    ap.add_argument("--trace-events-out", default="")
    args = ap.parse_args(argv)
    configure()
    from chipbench import check, faults
    from chipbench.harness import Bench, judge, run_cell
    from chipbench.tracing import slice_events

    controls = tuple(c for c in args.controls.split(",") if c)
    bench = Bench(ROOT)
    limits = bench.config(bench.cell(args.workload)["config"])["limits"]
    t_start = T_START
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        hooks, trace_dir = {}, None
        if args.fault:
            hooks["engine"] = faults.install(args.fault)
        if i == 0 and args.trace_events_out:
            trace_dir = str(CACHE / "trace")

            def dump(events, t0, t1):
                with open(args.trace_events_out, "w") as f:
                    json.dump(slice_events(events, t0, t1), f)
            hooks["events"] = dump
        result, lines, readings = run_cell(
            ROOT, args.workload, seed, args.seconds, trace_dir is not None,
            t_start=t_start, trace_dir=trace_dir, controls=controls,
            hooks=hooks)
        sampled = "sampler_gap" in readings
        judged = {}
        for p in controls:
            ok, checks = judge(check.as_control(readings, p), limits, sampled)
            judged[p] = {"correct": ok, "checks": checks}
        print(json.dumps({"seed": seed, "fault": args.fault or None,
                          "readings": readings, "controls": judged,
                          **result}), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
