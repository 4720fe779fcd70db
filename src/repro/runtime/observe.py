"""Flight recorder + metrics plane — end-to-end serving observability
(PR 10; contract in DESIGN.md §14).

The paper's run-time code generation loop *is* an observability loop:
generate, compile, time, pick the winner.  This module grows that idea
from "time one kernel" to "trace one request through the whole serving
stack" and owns two cooperating planes:

  * the **flight recorder** — per-request spans (fleet admit → queue
    wait → coalesced flush → compile/launch per backend → sampler →
    reply, plus ContinuousEngine steps and their phases) in a bounded ring
    buffer, exportable as Chrome trace-event JSON (`export_trace`,
    loadable in Perfetto / ``chrome://tracing``);
  * the **metrics plane** — fixed-bucket latency/size histograms with
    p50/p95/p99, labeled ``(family, backend, rc_bucket, rung)``, plus
    event counters.  Fixed bucket edges make the merge a plain
    elementwise count sum:
    associative, commutative, and exact, so `merge_metrics` folds N
    fleet workers into ONE coherent percentile view (accurate to one
    bucket width).

Everything is gated by one process-wide knob::

    REPRO_TRACE=off       # default: no hooks installed, zero overhead
    REPRO_TRACE=counters  # histograms + counters, no span records
    REPRO_TRACE=spans     # counters + the flight recorder

``off`` keeps the hot path allocation-free: every entry point is a
single module-int check and `dispatch.set_observer(None)` means the
core launch path never even calls back here.  The overhead bound is
benchmarked and gated in ``benchmarks/bench_obs.py``.

Whatever the knob, a scoped span (`span_begin`/`span_end`, `span`)
that opens while a JAX profiler session is active also becomes a
``jax.profiler.TraceAnnotation`` named ``<cat>.<name>``: it lands in
the profile's host plane on the device planes' clock, so device idle
time in a profile names the serving phase the host was in.  Spans the
recorder builds after the fact from stored timestamps stay
recorder-only.  JAX is imported on the first named span, not here.

The core never imports this module — `install()` injects a callback
through `dispatch.set_observer` (the PR 6 ``set_fault_hook`` pattern),
and everything else hooks runtime-layer seams (executor flush, fleet
dispatch, kvcache admit/evict).

One-shot CLI (the ``repro-top`` view)::

    PYTHONPATH=src python -m repro.runtime.observe --url http://127.0.0.1:9100

HTTP endpoints (`StatsServer`, wired to ``launch/serve.py
--stats-port``): ``/metrics`` (Prometheus text), ``/stats`` (JSON
stats snapshot), ``/trace`` (Chrome trace JSON).
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from bisect import bisect_left
from collections import deque
from typing import Any

MODE_OFF, MODE_COUNTERS, MODE_SPANS = 0, 1, 2
_MODE_NAMES = {"off": MODE_OFF, "counters": MODE_COUNTERS,
               "spans": MODE_SPANS}
#: the process-wide knob; module-level int so the off-path check is one
#: global load (hot paths read ``observe._MODE`` directly)
_MODE = MODE_OFF

TRACE_CAPACITY = 65536      # flight-recorder ring size (events)

# ---------------------------------------------------------------- histograms
#: fixed log2-spaced latency edges (seconds), 1µs .. ~33s.  FIXED edges
#: are the whole merge story: two histograms over the same edges merge
#: by elementwise count sum — associative/commutative/exact — and a
#: percentile read off merged counts is accurate to one bucket width.
LATENCY_EDGES_S = tuple(1e-6 * (2.0 ** k) for k in range(26))
#: pow2 size edges (rows per flush, batch occupancy), 1 .. 32768
SIZE_EDGES = tuple(float(2 ** k) for k in range(16))

#: metric name -> (label names, bucket edges).  Declared up front so
#: label cardinality is bounded by construction (families × backends ×
#: rc buckets × 5 rungs — see DESIGN.md §14) and the text exposition
#: knows its label names without shipping them per sample.
HIST_DEFS: dict = {
    "request_latency_seconds": (("family", "backend", "bucket", "rung"),
                                LATENCY_EDGES_S),
    "queue_wait_seconds": (("family",), LATENCY_EDGES_S),
    "flush_rows": (("family",), SIZE_EDGES),
    "launch_seconds": (("site", "backend"), LATENCY_EDGES_S),
    "decode_step_seconds": ((), LATENCY_EDGES_S),
    "engine_queue_wait_seconds": ((), LATENCY_EDGES_S),
    "engine_live_rows": ((), SIZE_EDGES),
}
COUNTER_DEFS: dict = {
    "requests_total": ("family", "backend"),
    "degradations_total": ("rung", "family"),
    "kvcache_events_total": ("event",),
    "fleet_events_total": ("event",),
}
_LSEP = "|"          # label-tuple join for snapshot keys ("softmax|xla")


class Histogram:
    """Fixed-edge histogram: ``counts[i]`` holds observations ``v <=
    edges[i]`` (Prometheus ``le`` semantics); the last slot is +Inf."""

    __slots__ = ("edges", "counts", "count", "sum")

    def __init__(self, edges: tuple = LATENCY_EDGES_S):
        self.edges = edges
        self.counts = [0] * (len(edges) + 1)
        self.count = 0
        self.sum = 0.0

    def observe(self, v: float) -> None:
        self.counts[bisect_left(self.edges, v)] += 1
        self.count += 1
        self.sum += v

    def percentile(self, p: float) -> float:
        """Upper edge of the bucket holding the p-quantile (``0<p<=1``)
        — an overestimate by at most one bucket width, which fixed log2
        edges bound at 2x.  0.0 when empty."""
        if not self.count:
            return 0.0
        rank = p * self.count
        cum = 0
        for i, c in enumerate(self.counts):
            cum += c
            if cum >= rank and c:
                return (self.edges[i] if i < len(self.edges)
                        else float("inf"))
        return float("inf")  # pragma: no cover - rank <= count always hits

    def snapshot(self) -> dict:
        """JSON-able sparse view (edges are implied by the metric def)."""
        return {"counts": {str(i): c for i, c in enumerate(self.counts)
                           if c},
                "count": self.count, "sum": self.sum}

    def merge_snapshot(self, snap: dict) -> None:
        for i, c in (snap.get("counts") or {}).items():
            self.counts[int(i)] += int(c)
        self.count += int(snap.get("count", 0))
        self.sum += float(snap.get("sum", 0.0))

    @classmethod
    def from_snapshot(cls, snap: dict, edges: tuple) -> "Histogram":
        h = cls(edges)
        h.merge_snapshot(snap)
        return h


class MetricsRegistry:
    """Thread-safe label-keyed histograms + counters.

    Keys are ``(metric, (label values...))``; label *names* live in
    `HIST_DEFS`/`COUNTER_DEFS`.  `snapshot()` is the JSON-able document
    that rides ``stats_snapshot()["metrics"]`` across fleet pipes and
    merges through `merge_metrics`."""

    def __init__(self):
        self._lock = threading.Lock()
        self._hists: dict = {}
        self._counters: dict = {}

    def observe(self, metric: str, labels: tuple, value: float) -> None:
        with self._lock:
            h = self._hists.get((metric, labels))
            if h is None:
                edges = HIST_DEFS.get(metric, ((), LATENCY_EDGES_S))[1]
                h = self._hists[(metric, labels)] = Histogram(edges)
            h.observe(value)

    def inc(self, metric: str, labels: tuple, n: int = 1) -> None:
        with self._lock:
            k = (metric, labels)
            self._counters[k] = self._counters.get(k, 0) + n

    def snapshot(self) -> dict:
        with self._lock:
            hists: dict = {}
            for (metric, labels), h in self._hists.items():
                hists.setdefault(metric, {})[
                    _LSEP.join(str(v) for v in labels)] = h.snapshot()
            counters: dict = {}
            for (metric, labels), n in self._counters.items():
                counters.setdefault(metric, {})[
                    _LSEP.join(str(v) for v in labels)] = n
        return {"histograms": hists, "counters": counters}

    def clear(self) -> None:
        with self._lock:
            self._hists.clear()
            self._counters.clear()


def merge_metrics(*docs: "dict | None") -> dict:
    """Merge metrics-snapshot documents: histogram counts and counters
    sum elementwise.  Associative and commutative (fixed edges; pure
    addition), so any merge order across the fleet yields the same
    document."""
    out: dict = {"histograms": {}, "counters": {}}
    for doc in docs:
        if not isinstance(doc, dict):
            continue
        for metric, series in (doc.get("histograms") or {}).items():
            dst_m = out["histograms"].setdefault(metric, {})
            for lkey, snap in series.items():
                dst = dst_m.get(lkey)
                if dst is None:
                    dst_m[lkey] = {
                        "counts": dict(snap.get("counts") or {}),
                        "count": snap.get("count", 0),
                        "sum": snap.get("sum", 0.0)}
                else:
                    for i, c in (snap.get("counts") or {}).items():
                        dst["counts"][i] = dst["counts"].get(i, 0) + c
                    dst["count"] += snap.get("count", 0)
                    dst["sum"] += snap.get("sum", 0.0)
        for metric, series in (doc.get("counters") or {}).items():
            dst_m = out["counters"].setdefault(metric, {})
            for lkey, n in series.items():
                dst_m[lkey] = dst_m.get(lkey, 0) + n
    return out


def percentiles(hist_snap: dict, edges: tuple = LATENCY_EDGES_S,
                ps: tuple = (0.5, 0.95, 0.99)) -> dict:
    """p50/p95/p99 (upper bucket edges) from one histogram snapshot."""
    h = Histogram.from_snapshot(hist_snap, edges)
    return {f"p{int(p * 100)}": h.percentile(p) for p in ps}


def latency_summary(metrics_doc: "dict | None") -> dict:
    """Cross-worker latency view from a (merged) metrics document:
    ``{"family|backend": {count, p50_ms, p95_ms, p99_ms}}`` — the
    request-latency histograms collapsed over (rc bucket, rung), which
    is an exact operation (count sums) thanks to fixed edges."""
    out: dict = {}
    series = ((metrics_doc or {}).get("histograms") or {}).get(
        "request_latency_seconds") or {}
    grouped: dict = {}
    for lkey, snap in series.items():
        parts = lkey.split(_LSEP)
        fb = _LSEP.join(parts[:2])   # family|backend
        g = grouped.setdefault(fb, Histogram(LATENCY_EDGES_S))
        g.merge_snapshot(snap)
    for fb, h in grouped.items():
        out[fb] = {"count": h.count,
                   "p50_ms": h.percentile(0.5) * 1e3,
                   "p95_ms": h.percentile(0.95) * 1e3,
                   "p99_ms": h.percentile(0.99) * 1e3}
    return out


# ----------------------------------------------------------- text exposition
def _esc(v: str) -> str:
    return str(v).replace("\\", r"\\").replace('"', r'\"').replace(
        "\n", r"\n")


def _label_str(names: tuple, lkey: str, extra: str = "") -> str:
    vals = lkey.split(_LSEP) if lkey else []
    pairs = [f'{n}="{_esc(v)}"' for n, v in zip(names, vals)]
    if extra:
        pairs.append(extra)
    return "{" + ",".join(pairs) + "}" if pairs else ""


def _fmt(x: float) -> str:
    return f"{x:.9g}"


def metrics_text(metrics_doc: "dict | None" = None,
                 prefix: str = "repro_") -> str:
    """Prometheus text exposition of a metrics document (default: this
    process's live registry) — what ``/metrics`` serves."""
    doc = metrics_doc if metrics_doc is not None else METRICS.snapshot()
    lines: list[str] = []
    for metric in sorted(doc.get("counters") or {}):
        names = COUNTER_DEFS.get(metric, ())
        lines.append(f"# TYPE {prefix}{metric} counter")
        for lkey in sorted(doc["counters"][metric]):
            lines.append(f"{prefix}{metric}{_label_str(names, lkey)} "
                         f"{doc['counters'][metric][lkey]}")
    for metric in sorted(doc.get("histograms") or {}):
        names, edges = HIST_DEFS.get(metric, ((), LATENCY_EDGES_S))
        lines.append(f"# TYPE {prefix}{metric} histogram")
        for lkey in sorted(doc["histograms"][metric]):
            snap = doc["histograms"][metric][lkey]
            counts = {int(i): c for i, c in
                      (snap.get("counts") or {}).items()}
            cum = 0
            for i, edge in enumerate(edges):
                cum += counts.get(i, 0)
                le = 'le="' + _fmt(edge) + '"'
                lines.append(f"{prefix}{metric}_bucket"
                             f"{_label_str(names, lkey, le)} {cum}")
            cum += counts.get(len(edges), 0)
            inf = 'le="+Inf"'
            lines.append(f"{prefix}{metric}_bucket"
                         f"{_label_str(names, lkey, inf)} {cum}")
            lines.append(f"{prefix}{metric}_sum{_label_str(names, lkey)} "
                         f"{_fmt(snap.get('sum', 0.0))}")
            lines.append(f"{prefix}{metric}_count{_label_str(names, lkey)} "
                         f"{snap.get('count', 0)}")
    return "\n".join(lines) + ("\n" if lines else "")


# ------------------------------------------------------------ flight recorder
class FlightRecorder:
    """Bounded ring buffer of Chrome trace events ("X" complete spans).

    Timestamps are ``time.monotonic()`` — on Linux that is
    CLOCK_MONOTONIC, which is system-wide, so spans recorded in spawned
    fleet workers land on the same timeline as the parent's and one
    merged trace lines up without clock translation.  Parentage rides
    ``args.sid`` / ``args.parent`` (the trace-event format has no
    native nesting across threads)."""

    def __init__(self, capacity: int = TRACE_CAPACITY):
        self._lock = threading.Lock()
        self._events: deque = deque(maxlen=max(16, int(capacity)))
        self._ids = itertools.count(1)
        self._dropped = 0

    def next_id(self) -> int:
        return next(self._ids)

    def add(self, name: str, cat: str, t0: float, t1: float,
            sid: "int | None" = None, parent: "int | None" = None,
            args: "dict | None" = None) -> int:
        """Record one complete span ``[t0, t1]`` (monotonic seconds);
        returns its span id (``sid``), for use as a later ``parent``."""
        if sid is None:
            sid = next(self._ids)
        a: dict = {"sid": sid}
        if parent is not None:
            a["parent"] = parent
        if args:
            a.update(args)
        ev = {"ph": "X", "name": name, "cat": cat,
              "ts": t0 * 1e6, "dur": max(0.0, (t1 - t0) * 1e6),
              "pid": os.getpid(), "tid": threading.get_ident(), "args": a}
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self._dropped += 1
            self._events.append(ev)
        return sid

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def drain(self) -> list[dict]:
        with self._lock:
            evs = list(self._events)
            self._events.clear()
            return evs

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._dropped = 0

    def stats(self) -> dict:
        with self._lock:
            return {"events": len(self._events),
                    "capacity": self._events.maxlen,
                    "dropped": self._dropped}


#: process-wide singletons: one recorder, one registry — hooks all over
#: the runtime write here, snapshots/exports read here
METRICS = MetricsRegistry()
RECORDER = FlightRecorder()

_ctx = threading.local()   # per-thread span parent stack


def current_parent() -> "int | None":
    stack = getattr(_ctx, "stack", None)
    return stack[-1] if stack else None


def _bind_profiler() -> bool:
    """First named span: bind the profiler's annotation lazily (JAX is
    not imported with this module) and answer for this call."""
    global _profiling, _Annotation
    from jax.profiler import TraceAnnotation

    _Annotation = TraceAnnotation
    _profiling = TraceAnnotation.is_enabled
    return _profiling()


#: is a JAX profiler session recording?  (rebound on first use)
_profiling = _bind_profiler
_Annotation: Any = None


def span_begin(name: "str | None" = None, cat: str = "",
               args: "dict | None" = None) -> "tuple | None":
    """Open a span; returns an opaque token for `span_end`.

    In spans mode the span is pushed as the current thread's parent and
    recorded at its end.  While a profiler session is active a named
    span is also a ``TraceAnnotation`` ``<cat>.<name>`` (with ``args`` as
    its metadata), closed by `span_end` on the same thread.  With
    neither on this returns None without allocating."""
    ann = None
    if name is not None and _profiling():
        ann = _Annotation(f"{cat}.{name}", **(args or {}))
        ann.__enter__()
    if _MODE < MODE_SPANS:
        return None if ann is None else (None, None, 0.0, name, cat, args,
                                         ann)
    sid = RECORDER.next_id()
    stack = getattr(_ctx, "stack", None)
    if stack is None:
        stack = _ctx.stack = []
    parent = stack[-1] if stack else None
    stack.append(sid)
    return (sid, parent, time.monotonic(), name, cat, args, ann)


def span_end(token: "tuple | None", name: "str | None" = None,
             cat: "str | None" = None,
             args: "dict | None" = None) -> "int | None":
    """Close a span opened by `span_begin` (no-op on a None token);
    ``name``/``cat`` default to the ones given at the begin, ``args``
    add to its.  Returns the recorded span id (spans mode).  Callers
    pair these in try/finally so an exception can't leak the parent
    stack or leave an annotation open."""
    if token is None:
        return None
    sid, parent, t0, name0, cat0, args0, ann = token
    if ann is not None:
        if args:
            ann.set_metadata(**args)
        ann.__exit__(None, None, None)
    if sid is None:
        return None
    stack = getattr(_ctx, "stack", None)
    if stack and stack[-1] == sid:
        stack.pop()
    if args0:
        args = {**args0, **args} if args else args0
    return RECORDER.add(name or name0 or "span", cat or cat0, t0,
                        time.monotonic(), sid=sid, parent=parent, args=args)


class span:
    """``with observe.span("flush", "executor", family=...):`` — the
    non-hot-path convenience over `span_begin`/`span_end`."""

    __slots__ = ("name", "cat", "args", "token", "sid")

    def __init__(self, name: str, cat: str, **args):
        self.name, self.cat, self.args = name, cat, args
        self.token = None
        self.sid: "int | None" = None

    def __enter__(self) -> "span":
        self.token = span_begin(self.name, self.cat, self.args or None)
        if self.token is not None:
            self.sid = self.token[0]
        return self

    def __exit__(self, *exc) -> bool:
        span_end(self.token)
        return False


# ------------------------------------------------- hot-path entry points
def count(metric: str, *labels, n: int = 1) -> None:
    """Bump one labeled counter (no-op when the knob is off)."""
    if _MODE:
        METRICS.inc(metric, labels, n)


def observe_hist(metric: str, labels: tuple, value: float) -> None:
    """Record one histogram observation (no-op when the knob is off)."""
    if _MODE:
        METRICS.observe(metric, labels, value)


# -------------------------------------------------- the dispatch observer
def _dispatch_event(event: str, site: "str | None" = None,
                    backend: "str | None" = None,
                    family: "str | None" = None,
                    bucket: "Any | None" = None,
                    t0: float = 0.0, t1: float = 0.0,
                    rung: "str | None" = None,
                    token: "Any | None" = None,
                    name: "str | None" = None) -> Any:
    """The callback `install` hands to `dispatch.set_observer`.  Events:

    * ``"site"`` — one timed `run_with_retries` attempt (site is
      ``compile``/``launch``): a ``launch_seconds`` observation, plus a
      span (parented to the caller's current span) when spans are on;
    * ``"degradation"`` — a ladder rung taken: a labeled counter;
    * ``"begin"``/``"end"`` — a core-side block (`dispatch.
      observe_block`, e.g. the planner's resilient evaluation) opening/
      closing a span that parents the launches inside it.
    """
    if event == "site":
        METRICS.observe("launch_seconds", (site or "?", backend or "?"),
                        t1 - t0)
        if _MODE >= MODE_SPANS:
            RECORDER.add(site or "launch", "kernel", t0, t1,
                         parent=current_parent(),
                         args={"backend": backend, "family": family,
                               "bucket": str(bucket)})
    elif event == "degradation":
        METRICS.inc("degradations_total", (rung or "?", family or "?"))
    elif event == "begin":
        return span_begin(name or "block", "plan",
                          {"family": family} if family else None)
    elif event == "end":
        span_end(token)
    return None


# ------------------------------------------------------- mode management
def mode() -> str:
    for name, m in _MODE_NAMES.items():
        if m == _MODE:
            return name
    return str(_MODE)  # pragma: no cover


def set_mode(new: str) -> str:
    """Switch the process-wide knob; installs/uninstalls the dispatch
    observer so ``off`` leaves the core launch path untouched.  Returns
    the previous mode name (so callers can restore)."""
    global _MODE
    if new not in _MODE_NAMES:
        raise ValueError(f"REPRO_TRACE mode {new!r} not in "
                         f"{sorted(_MODE_NAMES)}")
    prev = mode()
    _MODE = _MODE_NAMES[new]
    from repro.core import dispatch
    dispatch.set_observer(_dispatch_event if _MODE else None)
    return prev


def install_from_env() -> str:
    """Arm the knob from ``REPRO_TRACE`` (a no-op when unset/off) —
    called once on ``repro.runtime`` import, mirroring
    `faults.install_env_plan`."""
    m = os.environ.get("REPRO_TRACE", "").strip().lower()
    if m in _MODE_NAMES and m != "off":
        set_mode(m)
    return mode()


# ------------------------------------------------------------ trace export
def write_trace(path, events: "list[dict]") -> int:
    """Write Chrome trace-event JSON; returns the event count."""
    from pathlib import Path

    payload = {"traceEvents": list(events), "displayTimeUnit": "ms"}
    Path(path).write_text(json.dumps(payload))
    return len(events)


def export_trace(path, extra_events: "list[dict] | None" = None) -> int:
    """Export this process's recorder (plus any pre-collected worker
    events) as Chrome trace JSON — `runtime.export_trace` re-exports
    this; `ServingFleet.export_trace` feeds worker events in."""
    return write_trace(path, RECORDER.events() + list(extra_events or []))


# --------------------------------------------------------- HTTP telemetry
class StatsServer:
    """Stdlib-http live telemetry endpoint (no dependencies):

    * ``GET /metrics`` — Prometheus text exposition of the live registry
    * ``GET /stats``   — JSON: ``stats_fn()`` (e.g. a runtime snapshot)
    * ``GET /trace``   — Chrome trace JSON of the live recorder

    Serves on a daemon thread; ``port=0`` picks a free port (read it
    back from ``.port``)."""

    def __init__(self, port: int = 0, host: str = "127.0.0.1",
                 stats_fn=None):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        self._stats_fn = stats_fn or _default_stats
        server = self

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 - stdlib handler contract
                try:
                    if self.path.startswith("/metrics"):
                        body = metrics_text().encode()
                        ctype = "text/plain; version=0.0.4"
                    elif self.path.startswith("/stats"):
                        body = json.dumps(server._stats_fn(),
                                          default=str).encode()
                        ctype = "application/json"
                    elif self.path.startswith("/trace"):
                        body = json.dumps(
                            {"traceEvents": RECORDER.events(),
                             "displayTimeUnit": "ms"}).encode()
                        ctype = "application/json"
                    else:
                        self.send_error(404)
                        return
                except Exception as e:  # telemetry must answer, not die
                    self.send_error(500, str(e))
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):  # quiet: no stderr per request
                pass

        self._httpd = ThreadingHTTPServer((host, int(port)), Handler)
        self._httpd.daemon_threads = True
        self.host, self.port = self._httpd.server_address[:2]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="repro-stats-http",
            daemon=True)
        self._thread.start()

    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def close(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5.0)


def _default_stats() -> dict:
    """`StatsServer`'s fallback ``/stats`` document when no runtime is
    wired in: dispatch counters + the live metrics registry."""
    from repro.core import dispatch

    return {"dispatch": dispatch.stats_snapshot(),
            "metrics": METRICS.snapshot(),
            "recorder": RECORDER.stats(),
            "trace_mode": mode()}


# ------------------------------------------------------------ repro-top CLI
def top_view(stats_doc: dict) -> str:
    """One-shot ``repro-top`` text view of a stats document (a runtime
    `stats_snapshot`, a fleet ``merged`` doc, or `_default_stats`)."""
    doc = stats_doc or {}
    metrics_doc = doc.get("metrics") or {}
    lines = [f"{'family|backend':<24s} {'count':>8s} {'p50 ms':>9s} "
             f"{'p95 ms':>9s} {'p99 ms':>9s}"]
    lat = latency_summary(metrics_doc)
    for fb in sorted(lat):
        row = lat[fb]
        lines.append(f"{fb:<24s} {row['count']:>8d} {row['p50_ms']:>9.3f} "
                     f"{row['p95_ms']:>9.3f} {row['p99_ms']:>9.3f}")
    if not lat:
        lines.append("(no request-latency samples — is REPRO_TRACE on?)")
    ex = doc.get("executor") or {}
    if ex:
        lines.append(
            f"executor: {ex.get('requests', 0)} reqs / "
            f"{ex.get('flushes', 0)} flushes "
            f"(coalesce {ex.get('coalesce_factor', 0.0):.2f}, "
            f"{ex.get('launches_per_request', 0.0):.2f} launches/req)")
    deg = doc.get("degradations") or {}
    rungs = {k: v for k, v in deg.items() if ":" not in k}
    if rungs:
        lines.append("degradations: " + ", ".join(
            f"{k}={v}" for k, v in sorted(rungs.items())))
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="repro-top: one-shot serving telemetry view")
    ap.add_argument("--url", default="",
                    help="StatsServer base URL (e.g. http://127.0.0.1:9100)"
                         " — fetches /stats")
    ap.add_argument("--stats", default="",
                    help="path to a saved stats_snapshot JSON document")
    ap.add_argument("--metrics", action="store_true",
                    help="print the raw Prometheus exposition instead")
    args = ap.parse_args(argv)

    if args.url:
        from urllib.request import urlopen

        base = args.url.rstrip("/")
        if args.metrics:
            print(urlopen(base + "/metrics", timeout=10)
                  .read().decode(), end="")
            return 0
        doc = json.loads(urlopen(base + "/stats", timeout=10).read())
    elif args.stats:
        from pathlib import Path

        doc = json.loads(Path(args.stats).read_text())
    else:
        doc = _default_stats()
    if args.metrics:
        print(metrics_text(doc.get("metrics") or {}), end="")
        return 0
    print(top_view(doc))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
