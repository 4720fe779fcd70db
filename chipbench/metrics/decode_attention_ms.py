"""Device time of decode attention per run of the decode program, in ms:
the leaf operations of ``jit__lambda`` whose ``op_name`` carries the
``decode_attention`` scope (`chipbench/spans.py`), over its runs.  None
where the compiled program has no such scope."""

from pathlib import Path

from chipbench import spans


def read(run):
    t = spans.load(run, Path(__file__).resolve().parents[1])
    if t is None or not t["runs"] or "decode_attention" not in t["scoped"]:
        return None
    return 1e3 * t["scoped"]["decode_attention"] / t["runs"]
