"""Roofline reporting: the analytic dry-run table (the original
§Roofline table, from cost-model dry runs)::

    PYTHONPATH=src python -m benchmarks.roofline_report [--mesh 16x16]

Measured roofline shares come from the chip benchmark's traces
(``chipbench/metrics/prefill_roofline.py``, ``decode_roofline.py``).
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path


def render(path="results/dryrun.json", mesh="16x16") -> str:
    rs = [r for r in json.loads(Path(path).read_text())
          if r["mesh"] == mesh and r.get("ok")]
    out = [f"{'arch':22s} {'shape':12s} {'C ms':>8s} {'M ms':>8s} {'X ms':>8s} "
           f"{'dom':>5s} {'frac':>6s} {'useful':>6s} {'mem GiB':>8s}"]
    for r in sorted(rs, key=lambda r: (r["arch"], r["shape"])):
        ro = r["roofline"]
        m = r["memory"]
        out.append(
            f"{r['arch']:22s} {r['shape']:12s} {ro['compute_s']*1e3:8.1f} "
            f"{ro['memory_s']*1e3:8.1f} {ro['collective_s']*1e3:8.1f} "
            f"{ro['dominant'][:5]:>5s} {ro['roofline_fraction']:6.3f} "
            f"{ro['useful_flops_ratio']:6.2f} {m['total_per_dev']/2**30:8.2f}")
    return "\n".join(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--path", default="results/dryrun.json")
    ap.add_argument("--mesh", default="16x16")
    args = ap.parse_args()
    print(render(args.path, args.mesh))


if __name__ == "__main__":
    main()
