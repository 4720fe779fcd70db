"""Device-idle time inside the engine's sampling phase per engine step of
the window, in ms: the idle seconds that fall inside the union of the
program's ``engine.sample`` spans in the profile (`chipbench/spans.py`),
over the steps.  None where the program writes no such span."""

from pathlib import Path

from chipbench import spans


def read(run):
    t = spans.load(run, Path(__file__).resolve().parents[1])
    if t is None or not t["steps"] or "engine.sample" not in t["spans"]:
        return None
    return 1e3 * t["spans"]["engine.sample"]["idle_s"] / t["steps"]
