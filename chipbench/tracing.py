"""From a profiler trace to the numbers the per-layer metrics read.

Two stages, kept apart so the second can be checked on a small recorded
trace:

1. `events_from_xplane` reads the ``.xplane.pb`` the JAX profiler writes
   into plain event lists: device programs (the "XLA Modules" line of each
   TPU plane), device operations (its "XLA Ops" line) and host activity
   (every line of the host plane), each ``(name, start_s, end_s)`` on the
   profiler's common clock.
2. `reduce` clips them to the measured window and gives device busy time
   (the union of operation intervals, averaged over the chips), device
   seconds and runs per program, the operations that took most time
   (innermost operations only), and idle time summed by what the host
   was doing: at each idle instant, the shortest host event that spans it.
"""

from __future__ import annotations

import glob
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")


def events_from_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: dict = {"programs": [], "ops": [], "host": [], "chips": 0}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            out["chips"] += 1
            chip = plane.name.rsplit(":", 1)[-1]
            for line in plane.lines:
                key = {"XLA Modules": "programs", "XLA Ops": "ops"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    out[key].append((e.name, e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9, chip))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    out["host"].append((e.name, e.start_ns * 1e-9,
                                        (e.start_ns + e.duration_ns) * 1e-9,
                                        line.name))
    return out


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def program_name(name: str) -> str:
    """``jit_admit_fn(12)`` -> ``jit_admit_fn``."""
    return _SUFFIX.sub("", name.strip())


def op_name(name: str) -> str:
    """``%broadcast_in_dim.85 = f32[...] broadcast(...)`` ->
    ``broadcast_in_dim.85``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _leaves(ops: list) -> list:
    """The operations that hold no other operation of their chip (a
    ``while`` over the layers holds the layer's operations)."""
    out = []
    by_chip = defaultdict(list)
    for op in ops:
        by_chip[op[3]].append(op)
    for evs in by_chip.values():
        evs.sort(key=lambda o: (o[1], -o[2]))
        for i, op in enumerate(evs):
            nxt = evs[i + 1] if i + 1 < len(evs) else None
            if nxt is None or nxt[1] >= op[2] or nxt[2] > op[2]:
                out.append(op)
    return out


def _split(gap_s: float, gap_e: float, active: list, out: dict) -> None:
    """Add the gap's time to the host activity the host was deepest in:
    at each instant, the shortest host event that covers it."""
    cuts = sorted({gap_s, gap_e} | {t for _, s, e, _ in active
                                    for t in (s, e) if gap_s < t < gap_e})
    for a, b in zip(cuts, cuts[1:]):
        cover = [(e - s, n) for n, s, e, _ in active if s <= a and e >= b]
        out[min(cover)[1] if cover else "(no host activity)"] += b - a


def idle_by_host(gaps: list, host: list, skip: tuple = ()) -> dict:
    """Idle seconds by what the host was doing (``gaps`` in time order;
    host events named in ``skip`` wrap everything and name nothing)."""
    host = sorted((h for h in host if h[0] not in skip), key=lambda h: h[1])
    out: dict = defaultdict(float)
    active: list = []
    i = 0
    for gs, ge in gaps:
        while i < len(host) and host[i][1] < ge:
            active.append(host[i])
            i += 1
        active = [h for h in active if h[2] > gs]
        _split(gs, ge, active, out)
    return out


def reduce(events: dict, t0: float, t1: float, top: int = 10,
           skip: tuple = ()) -> dict:
    """Numbers of the window ``[t0, t1]`` (seconds on the trace's clock);
    host events named in ``skip`` name no idle time."""
    chips = max(1, events["chips"])

    def clip(evs):
        return [(n, max(s, t0), min(e, t1), c) for n, s, e, c in evs
                if e > t0 and s < t1]

    ops, progs, host = clip(events["ops"]), clip(events["programs"]), \
        clip(events["host"])
    by_chip = defaultdict(list)
    for _, s, e, c in (ops or progs):
        by_chip[c].append((s, e))
    busy = {c: _union(iv) for c, iv in by_chip.items()}
    busy_s = sum(e - s for iv in busy.values() for s, e in iv) / chips
    programs: dict = {}
    for n, s, e, _ in progs:
        p = programs.setdefault(program_name(n), [0.0, 0])
        p[0] += (e - s) / chips
        p[1] += 1
    op_time: dict = defaultdict(float)
    for n, s, e, _ in _leaves(ops):
        op_time[op_name(n)] += (e - s) / chips
    gaps = []
    first = sorted(busy)[0] if busy else None
    if first is not None:
        prev = t0
        for s, e in busy[first] + [[t1, t1]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
    idle = idle_by_host(gaps, host, skip)
    return {
        "window_s": t1 - t0,
        "busy_s": busy_s,
        "chips": chips,
        "programs": {k: {"seconds": v[0], "runs": v[1]}
                     for k, v in sorted(programs.items(),
                                        key=lambda kv: -kv[1][0])},
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[k, v] for k, v in sorted(idle.items(),
                                                key=lambda kv: -kv[1])[:top]],
        "gaps": len(gaps),
        "longest_gap_s": max((e - s for s, e in gaps), default=0.0),
    }


def window_bounds(events: dict, marker: str) -> "tuple[float, float]":
    """The span of the host annotation ``marker`` (the driver wraps the
    measured window in one)."""
    spans = [(s, e) for n, s, e, _ in events["host"] if n == marker]
    if not spans:
        raise ValueError(f"no host annotation {marker!r} in the trace")
    return spans[0]


def slice_events(events: dict, t0: float, t1: float, span: float = 0.25) -> dict:
    """``span`` seconds from the middle of the window ``[t0, t1]``, as a
    small JSON-able trace (the reduction's test fixture)."""
    mid = (t0 + t1) / 2
    a, b = mid - span / 2, mid + span / 2

    def within(evs):
        return [list(e) for e in evs if e[2] > a and e[1] < b]

    return {"chips": events["chips"], "t0": a, "t1": b,
            "programs": within(events["programs"]), "ops": within(events["ops"]),
            "host": within(events["host"])}
