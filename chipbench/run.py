"""Serving benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's configuration with weights from ``--seed``, warms up
every shape the cell's traffic uses, serves ``requests_per_s x seconds``
requests through ``serving.ContinuousEngine`` as a closed loop, compares
what it served with a plain reference, and prints one JSON object as the
last line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device`` and, traced, ``breakdown``; the numbers
compared with their limits come last, under ``checks``, and again as the
last lines of standard error.

Exits 2, with no result, where JAX finds no TPU or fewer chips than the
cell asks for, and 3 where the program under test is missing.  Compiled
programs are cached in ``chipbench/.cache/`` of this checkout.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
CACHE = ROOT / "chipbench" / ".cache"


def configure() -> None:
    """Caches inside this checkout at fixed paths, no retries or injected
    faults (a failure on the chip is real), and the import paths."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    os.environ["REPRO_CACHE_DIR"] = str(CACHE / "repro")
    os.environ["REPRO_RETRY_MAX"] = "0"
    os.environ.pop("REPRO_CHAOS", None)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    configure()
    from chipbench.harness import NoChip, log, run_cell

    try:
        import repro.serving.engine  # noqa: F401
    except ImportError as e:
        log(f"chipbench: the program under test is missing ({e})")
        return 3
    trace_dir = str(CACHE / "trace") if args.trace else None
    if trace_dir:
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        result, lines, _ = run_cell(ROOT, args.workload, args.seed,
                                    args.seconds, bool(args.trace),
                                    t_start=T_START, trace_dir=trace_dir)
    except NoChip as e:
        log(f"chipbench: {e}")
        return 2
    finally:
        if trace_dir:
            import shutil

            shutil.rmtree(trace_dir, ignore_errors=True)
    for line in lines:
        log(line)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
