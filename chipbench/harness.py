"""One run of one cell: build, warm up, serve the window, check, report.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``chipbench/configs/<config>.json``: the model's numbers as run, the
  serving setting, the plain reference's module
  (``chipbench/references/<reference>.py``) and the check's limits;
* ``chipbench/traffic/<traffic>.json``: sessions, rate, length
  distributions, sampling and the size of the check's sample;
* ``chipbench/metrics/<metric>.py``: a ``read(run)`` that returns the
  metric's value, or None where the run holds nothing to read.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path

import numpy as np

WINDOW_MARK = "chipbench.window"
STEP_MARK = "engine.step"
BLOCK = 256            # the reference's context is padded to this multiple


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


class Bench:
    """``BENCHMARK.json`` under ``root`` and the files it names."""

    def __init__(self, root):
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self.dir = self.root / self.spec["paths"][0]

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def _json(self, kind: str, name: str) -> dict:
        return json.loads((self.dir / kind / f"{name}.json").read_text())

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def _module(self, path: Path):
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{path.parent.name}_{path.stem}".replace(".", "_")
            .replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def reader(self, metric: str):
        return self._module(self.dir / "metrics" / f"{metric}.py")

    def reference(self, name: str):
        return self._module(self.dir / "references" / f"{name}.py")

    def metrics_for(self, cell: str, traced: bool) -> list:
        """The cell's end-to-end metrics (untraced run) or per-layer
        metrics (traced run): those that list it, or list no cells."""
        group = self.spec["per_layer" if traced else "end_to_end"]
        return [m for m in group if cell in m.get("workloads", [cell])]

    def peaks(self, kind: str) -> dict:
        table = json.loads((self.dir / "peaks.json").read_text())
        if kind not in table:
            raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
        return table[kind]


class Run:
    """What a metric reader sees: the served requests with their stamps,
    the configuration, the peaks, and (traced runs) the reduced trace."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    @property
    def requests(self) -> list:
        return self.served.requests

    def decode_contexts(self) -> list:
        """Per step of the window, the context (prompt plus tokens fed)
        of each row the step decoded."""
        steps: list = [[] for _ in self.served.step_end]
        for r in self.requests:
            if r.admit_step < 0:
                continue
            for j in range(r.admit_step + 1, r.finish_step + 1):
                steps[j].append(r.prompt_len + j - r.admit_step)
        return steps

    def token_gaps_s(self) -> list:
        t = self.served.step_end
        return [t[j] - t[j - 1] for r in self.requests if r.admit_step >= 0
                for j in range(r.admit_step + 1, r.finish_step + 1)]

    def program_seconds(self, names) -> "tuple[float, int]":
        progs = self.trace["programs"]
        return (sum(progs.get(n, {}).get("seconds", 0.0) for n in names),
                sum(progs.get(n, {}).get("runs", 0) for n in names))


def percentile(values: list, q: float) -> "float | None":
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if values else None


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Backend compiles while it is open, counted by JAX's monitoring
    events."""

    def __init__(self):
        self.n = 0

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


class PauseCounter:
    """Python's garbage collections while it is open, and their time."""

    def __init__(self):
        self.n, self.seconds, self._t = 0, 0.0, 0.0

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.n += 1
            self.seconds += time.perf_counter() - self._t

    def __enter__(self):
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)


def slow_steps(served) -> str:
    """Where the window lost time: gaps between step ends over twice
    their median, their excess over it, and the three longest."""
    gaps = np.diff(np.asarray(served.step_end, np.float64))
    if gaps.size == 0:
        return "no step gaps"
    med = float(np.median(gaps))
    slow = np.flatnonzero(gaps > 2 * med)
    top = slow[np.argsort(gaps[slow])[::-1][:3]]
    return (f"step gaps median {med * 1e3:.2f} ms, {slow.size} over twice "
            f"it with {float(np.sum(gaps[slow] - med)):.3f} s excess, the "
            f"longest " + ", ".join(f"step {int(k) + 1} {gaps[k] * 1e3:.1f} ms"
                                    for k in top))


def judge(readings: dict, limits: dict, sampled: bool) -> "tuple[bool, dict]":
    """``correct`` and the numbers it compared, each with its limit."""
    numbers = ["logit_gap"] + (["sampler_gap"] if sampled else []) \
        + ["count_gap"]
    checks = {k: {"value": readings[k], "limit": limits[k]} for k in numbers}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


def run_cell(root, cell_name: str, seed: int, seconds: float, trace: bool,
             *, t_start: float, require_tpu: bool = True,
             trace_dir: "str | None" = None, controls: tuple = (),
             hooks: "dict | None" = None) -> "tuple[dict, list, dict]":
    """One run.  -> (the result line's object, the check's lines, every
    reading of the check, controls included)."""
    import jax

    bench = Bench(root)
    cell = bench.cell(cell_name)
    phases = {"start": time.perf_counter() - t_start}
    t = time.perf_counter()
    devices = jax.devices()
    phases["devices"] = time.perf_counter() - t
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell["chips"]):
        raise NoChip(f"cell {cell_name} needs {cell['chips']} TPU chip(s); "
                     f"JAX finds {len(devices)} {devices[0].platform} device(s)")
    from chipbench import build, check, driver, traffic

    conf = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    hooks = hooks or {}
    cfg = build.model_config(conf)
    model, serving = conf["model"], conf["serving"]
    temperature = float(mix["temperature"])
    sampled = temperature > 0
    n = traffic.request_count(mix, seconds)
    requests = traffic.make_requests(mix, n, seed, model["vocab_size"])
    chosen = check.sample_requests(requests, seed, mix["check"]["min_tokens"],
                                   mix["check"]["max_requests"])

    t = time.perf_counter()
    params = build.make_weights(cfg, seed)
    phases["weights"] = time.perf_counter() - t
    t = time.perf_counter()
    eng, rt = build.make_engine(cfg, params, serving, mix["sessions"])
    recorder = None
    if sampled:
        recorder = build.RecordingRuntime(rt, lambda: eng, set())
        eng.runtime = recorder
    if "engine" in hooks:
        hooks["engine"](eng)
    build.warm_up(eng, temperature)
    if recorder is not None:
        recorder.collect()
        recorder.rows.clear()
    # the engine takes no seed: its sampling key is set from the run's
    eng._key = jax.random.fold_in(build.prng_key(seed), 1)
    phases["warm_up"] = time.perf_counter() - t
    watch = {id(r) for r in chosen}

    def on_submit(r):
        if recorder is not None and id(r) in watch:
            recorder.watch.add(r.rid)

    step_mark: list = []

    def before_step(k):
        if "before_step" in hooks:
            hooks["before_step"](k)
        if trace:
            step_mark.append(jax.profiler.TraceAnnotation(STEP_MARK))
            step_mark[-1].__enter__()

    def after_step(k):
        if trace:
            step_mark.pop().__exit__(None, None, None)
        if recorder is not None:
            recorder.collect()
        if "after_step" in hooks:
            hooks["after_step"](k)

    if trace:
        # host activity from the runtime's own annotations; the Python
        # call tracer would slow the host the window measures
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t_open = time.perf_counter()
    setup_s = t_open - t_start
    with CompileCounter() as compiles, PauseCounter() as pauses, \
            jax.profiler.TraceAnnotation(WINDOW_MARK):
        served = driver.serve(eng, requests, mix["sessions"], temperature,
                              max_steps=n * (int(serving["max_len"]) + 2),
                              before_step=before_step,
                              after_step=after_step, on_submit=on_submit)
    if trace:
        jax.profiler.stop_trace()
    dev = devices[0]
    mem = dev.memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0))
    counts = served.status_counts()
    log(f"requests: attempted {n}, failed {served.failed}, by status "
        f"{counts}; window {served.window_s:.3f} s, "
        f"{len(served.step_end)} steps, {served.tokens} tokens")
    log(f"compiles in the window: {compiles.n}; garbage collections "
        f"{pauses.n}, {pauses.seconds:.3f} s; {slow_steps(served)}")
    log(f"setup {setup_s:.3f} s, by phase "
        f"{json.dumps({k: round(v, 3) for k, v in phases.items()})}")
    log(f"memory_peak_bytes {memory_peak}")

    rt.close()
    if recorder is not None:
        recorder._engine = None
    del eng, rt
    gc.collect()

    t = time.perf_counter()
    reference = bench.reference(conf["reference"])
    readings = check.compare(
        reference, model, params, served, chosen,
        None if recorder is None else recorder.rows,
        int(serving["max_len"]), BLOCK, tuple(controls))
    log(f"check: {len(chosen)} requests, {readings['positions']} served "
        f"positions compared with the reference in "
        f"{time.perf_counter() - t:.1f} s")

    reduced = None
    if trace:
        from chipbench import tracing

        t = time.perf_counter()
        events = tracing.events_from_xplane(tracing.find_xplane(trace_dir))
        t0, t1 = tracing.window_bounds(events, WINDOW_MARK)
        reduced = tracing.reduce(events, t0, t1, skip=(WINDOW_MARK,))
        if "events" in hooks:
            hooks["events"](events, t0, t1)
        log(f"trace read in {time.perf_counter() - t:.1f} s; "
            f"{reduced['gaps']} idle gaps, the longest "
            f"{reduced['longest_gap_s'] * 1e3:.3f} ms")
        for name, p in reduced["programs"].items():
            log(f"program {name}: {p['seconds']:.6f} s device, "
                f"{p['runs']} runs")

    run = Run(served=served, model=model, serving=serving, traffic=mix,
              capacity=int(serving["capacity"]),
              max_len=int(serving["max_len"]), setup_s=setup_s,
              peaks=bench.peaks(dev.device_kind) if require_tpu else
              bench.peaks("TPU v5 lite"), trace=reduced, chips=cell["chips"])
    metrics = {}
    for m in bench.metrics_for(cell_name, trace):
        value = bench.reader(m["name"]).read(run)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    correct, checks = judge(readings, conf["limits"], sampled)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": n, "failed": served.failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in reduced["device_ops"]],
            "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    lines = [f"{k} {c['value']!r} limit {c['limit']!r}"
             for k, c in checks.items()]
    return result, lines, readings
