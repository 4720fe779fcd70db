"""The program's own spans and scopes in a traced run's profile: what
``sample_idle_ms`` and ``decode_attention_ms`` read.

The serving program writes its spans into the profile as host
annotations named ``<category>.<name>`` (``engine.step``,
``engine.sample``, ``executor.flush``, ...), and tags the operations of
its decode program with ``jax.named_scope`` names (``decode_attention``,
``kv_write``), which the compiled program keeps as each operation's
``op_name`` metadata.  `tracing.reduce` keeps the ten largest names only,
so the readers here read the ``.xplane.pb`` again, once per run, from the
directory ``run.py`` writes it to (``chipbench/.cache/trace``), while the
harness still holds it:

* `span_table`: for each host span of the program in the window, its
  count, host seconds and the device-idle seconds inside the union of
  its intervals (a name that occurs twice over one interval, as the
  harness's and the program's ``engine.step`` do, counts it once);
* `scope_map` and `scoped_seconds`: the decode program's leaf-operation
  device seconds by scope, from the compiled program's text.  The text
  comes from compiling the decode step again at the run's shapes (JAX's
  compilation cache holds it); operation names are unique within one
  program only, so only operations inside that program's runs count.

On a host with no TPU plane (the tests on the CPU) the CPU client's
operation events, each tagged with its program, stand in for the device.
A program without these spans or scopes reads None, not zero.
"""

from __future__ import annotations

import re
from bisect import bisect_right
from collections import defaultdict
from pathlib import Path

from chipbench import harness, tracing

PREFIXES = ("engine.", "executor.", "runtime.")
DECODE_PROGRAM = "jit__lambda"
SCOPES = ("decode_attention", "kv_write")
UNSCOPED, UNMAPPED = "unscoped", "unmapped"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")

_loaded: dict = {}


# ------------------------------------------------------------ reading
def events(path: str) -> dict:
    """`tracing.events_from_xplane`, with the CPU client's operations as
    the device where the profile has no TPU plane."""
    ev = tracing.events_from_xplane(path)
    if ev["chips"] == 0:
        _cpu_operations(path, ev)
    return ev


def _cpu_operations(path: str, ev: dict) -> None:
    from jax.profiler import ProfileData

    runs: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "hlo_module" not in stats or "hlo_op" not in stats:
                    continue
                s, t = e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9
                ev["ops"].append((str(stats["hlo_op"]), s, t, "cpu"))
                key = (str(stats["hlo_module"]), stats.get("run_id"))
                a, b = runs.get(key, (s, t))
                runs[key] = (min(a, s), max(b, t))
    ev["programs"] += [(name, s, t, "cpu") for (name, _), (s, t)
                       in runs.items()]
    ev["chips"] = 1 if runs else 0


# --------------------------------------------------------- host spans
def idle_gaps(ev: dict, t0: float, t1: float) -> list:
    """The device's idle intervals in ``[t0, t1]``, on its first chip, as
    `tracing.reduce` takes them."""
    by_chip = defaultdict(list)
    for _, s, e, c in (ev["ops"] or ev["programs"]):
        if e > t0 and s < t1:
            by_chip[c].append((max(s, t0), min(e, t1)))
    if not by_chip:
        return []
    gaps, prev = [], t0
    for s, e in tracing._union(by_chip[sorted(by_chip)[0]]) + [[t1, t1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    return gaps


def _overlap(a: list, b: list) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def span_table(ev: dict, t0: float, t1: float,
               prefixes: tuple = PREFIXES) -> dict:
    """``{name: {"count", "seconds", "idle_s"}}`` for every host event of
    the window whose name starts with one of ``prefixes``: how many, the
    length of the union of their intervals, and the device-idle seconds
    inside that union."""
    spans = defaultdict(list)
    for n, s, e, _ in ev["host"]:
        if n.startswith(prefixes) and e > t0 and s < t1:
            spans[n].append((max(s, t0), min(e, t1)))
    gaps = idle_gaps(ev, t0, t1)
    out = {}
    for n, iv in sorted(spans.items()):
        u = tracing._union(iv)
        out[n] = {"count": len(iv), "seconds": sum(e - s for s, e in u),
                  "idle_s": _overlap(u, gaps)}
    return out


# ------------------------------------------------------------- scopes
def scope_of(op_name: str, scopes: tuple = SCOPES) -> str:
    """The innermost of ``scopes`` on an ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        if part in scopes:
            return part
    return UNSCOPED


def scope_map(hlo_text: str, scopes: tuple = SCOPES) -> dict:
    """Every instruction of a compiled program's text -> its scope.  An
    instruction without metadata (a fusion, say) takes the scope of the
    computation it calls: that of its root, else of its first
    instruction with metadata."""
    own, calls, comps = {}, {}, defaultdict(list)
    roots: dict = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m and " = " not in line:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        meta = _OP_NAME.search(line)
        own[name] = meta.group(1) if meta else None
        call = _CALLS.search(line)
        if call:
            calls[name] = call.group(1)
        if comp is not None:
            comps[comp].append(name)
            if line.lstrip().startswith("ROOT"):
                roots[comp] = name

    def named(name: str, depth: int = 0) -> "str | None":
        if own.get(name):
            return own[name]
        comp = calls.get(name)
        if comp is None or depth > 8:
            return None
        for inner in [roots.get(comp)] + comps.get(comp, []):
            if inner is not None and (found := named(inner, depth + 1)):
                return found
        return None

    return {n: scope_of(named(n) or "", scopes) for n in own}


def decode_program_text(model: dict, capacity: int, max_len: int) -> str:
    """The compiled text of the engine's decode step at the run's shapes
    (the jitted ``lambda`` that `ContinuousEngine` builds)."""
    import jax
    import jax.numpy as jnp

    from chipbench import build
    from repro.models import schema, transformer

    cfg = build.model_config({"model": model})
    params = schema.abstract_params(cfg)
    cache = jax.eval_shape(
        lambda: transformer.init_cache(cfg, capacity, max_len))
    step = jax.jit(lambda p, c, t, pos: transformer.decode_step(cfg, p, c, t,
                                                                pos))
    return step.lower(params, cache,
                      jax.ShapeDtypeStruct((capacity, 1), jnp.int32),
                      jax.ShapeDtypeStruct((), jnp.int32)).compile().as_text()


def _inside(ops: list, runs: list) -> list:
    """The operations that lie inside one of ``runs`` on their chip."""
    by_chip = defaultdict(list)
    for _, s, e, c in runs:
        by_chip[c].append((s, e))
    spans = {c: tracing._union(iv) for c, iv in by_chip.items()}
    starts = {c: [s for s, _ in u] for c, u in spans.items()}
    out = []
    for op in ops:
        k = bisect_right(starts.get(op[3], ()), op[1]) - 1
        if k >= 0 and op[2] <= spans[op[3]][k][1]:
            out.append(op)
    return out


def scoped_seconds(ev: dict, t0: float, t1: float, program: str,
                   scopes: dict) -> dict:
    """Device seconds of ``program``'s leaf operations in the window, by
    the scope ``scopes`` gives each operation name (``unmapped`` for a
    name it lacks), averaged over the chips like `tracing.reduce`."""
    chips = max(1, ev["chips"])

    def clip(evs):
        return [(n, max(s, t0), min(e, t1), c) for n, s, e, c in evs
                if e > t0 and s < t1]

    runs = [p for p in clip(ev["programs"])
            if tracing.program_name(p[0]) == program]
    out = dict.fromkeys(sorted(set(scopes.values())), 0.0)
    out[UNMAPPED] = 0.0
    for n, s, e, _ in _inside(tracing._leaves(clip(ev["ops"])), runs):
        out[scopes.get(tracing.op_name(n), UNMAPPED)] += (e - s) / chips
    return out


# ------------------------------------------------------------ the run
def load(run, bench_dir: Path) -> "dict | None":
    """Spans and decode scopes of a traced run, read once: ``{"steps",
    "runs", "spans", "scoped"}``, or None where the run has no trace."""
    trace_dir = Path(bench_dir) / ".cache" / "trace"
    if run.trace is None or not trace_dir.is_dir():
        return None
    try:
        path = tracing.find_xplane(str(trace_dir))
    except FileNotFoundError:
        return None
    key = (path, Path(path).stat().st_mtime_ns)
    if key not in _loaded:
        _loaded.clear()
        _loaded[key] = _read(run, path)
    return _loaded[key]


def _read(run, path: str) -> dict:
    ev = events(path)
    t0, t1 = tracing.window_bounds(ev, harness.WINDOW_MARK)
    spans = span_table(ev, t0, t1)
    for name, s in spans.items():
        harness.log(f"span {name}: {s['count']} events, {s['seconds']:.6f} s "
                    f"host, {s['idle_s']:.6f} s device idle")
    scopes = scope_map(decode_program_text(run.model, run.capacity,
                                           run.max_len))
    scoped = scoped_seconds(ev, t0, t1, DECODE_PROGRAM, scopes)
    total = sum(scoped.values())
    mapped = 1.0 - scoped[UNMAPPED] / total if total else 0.0
    harness.log(
        f"scope map: {len(scopes)} operations of {DECODE_PROGRAM}, "
        f"{mapped:.2%} of its {total:.6f} s leaf-operation device time "
        "mapped; " + ", ".join(f"{k} {v:.6f} s" for k, v in scoped.items()))
    runs = sum(1 for n, s, e, _ in ev["programs"] if e > t0 and s < t1
               and tracing.program_name(n) == DECODE_PROGRAM)
    return {"steps": len(run.served.step_end), "runs": runs, "spans": spans,
            "scoped": scoped}
