"""Flight recorder + metrics plane (PR 10, DESIGN.md §14).

Four layers, bottom-up:

  * histogram/merge algebra — fixed-edge histograms merge by count sum
    (associative, commutative, exact); percentiles read off merged
    counts within one bucket width; Prometheus text exposition;
  * flight recorder — bounded ring, Chrome trace-event schema
    round-trip, ``sid``/``parent`` parentage;
  * hot-path contract — ``REPRO_TRACE=off`` allocates NOTHING in the
    observe module (tracemalloc-verified), `take_last_rung` is
    read-and-clear;
  * the serving stack — request spans reconstructed through a real
    coalesced flush (admit/queue/reply children, flush backref, serve
    under flush), `merge_stats` folding metrics + kvcache + executor
    counters, and the fleet acceptance run: K=8 over 4 workers with one
    injected worker kill exports ONE merged cross-process trace whose
    ``dispatch`` spans join worker ``serve_group`` spans by gid, and
    ``fleet.stats()`` carries cross-worker p50/p95 per (family,
    backend).
"""

import json
import os
import threading
import tracemalloc

import numpy as np
import pytest

from repro.runtime import observe

# exact-in-binary latencies: histogram ``sum`` fields stay bit-identical
# whatever the merge order, so associativity asserts with ==
V1, V2, V3 = 1.0 / 1024, 1.0 / 512, 1.0 / 256


@pytest.fixture(autouse=True)
def _reset_observe():
    """Leave each test with a clean registry/recorder and the mode the
    process was launched with (the CI obs-smoke leg runs the whole
    suite under REPRO_TRACE=spans — later tests must still see it)."""
    yield
    observe.set_mode("off")
    observe.METRICS.clear()
    observe.RECORDER.clear()
    observe.install_from_env()


# ---------------------------------------------------------------------------
# histogram / merge algebra
# ---------------------------------------------------------------------------

class TestHistogram:
    def test_percentile_within_one_bucket_width(self):
        h = observe.Histogram(observe.LATENCY_EDGES_S)
        for _ in range(50):
            h.observe(0.001)
        for _ in range(50):
            h.observe(0.004)
        assert h.count == 100
        # upper edge of the holding bucket: log2 edges bound the
        # overestimate at 2x
        assert 0.001 <= h.percentile(0.5) <= 0.002
        assert 0.004 <= h.percentile(0.99) <= 0.008
        assert h.percentile(0.5) <= h.percentile(0.95) <= h.percentile(0.99)

    def test_empty_and_snapshot_roundtrip(self):
        h = observe.Histogram(observe.SIZE_EDGES)
        assert h.percentile(0.5) == 0.0
        h.observe(3.0)
        h.observe(1e9)   # beyond the last edge: the +Inf slot
        snap = h.snapshot()
        h2 = observe.Histogram.from_snapshot(snap, observe.SIZE_EDGES)
        assert h2.count == 2 and h2.counts == h.counts
        assert h2.percentile(0.99) == float("inf")


def _doc(vals, n_req):
    r = observe.MetricsRegistry()
    for v in vals:
        r.observe("request_latency_seconds",
                  ("softmax", "xla", "16x16", "none"), v)
    r.inc("requests_total", ("softmax", "xla"), n_req)
    return r.snapshot()


def test_merge_metrics_associative_and_commutative():
    a = _doc([V1] * 3, 3)
    b = _doc([V2] * 5, 5)
    c = _doc([V3] * 7, 7)
    m1 = observe.merge_metrics(observe.merge_metrics(a, b), c)
    m2 = observe.merge_metrics(a, observe.merge_metrics(b, c))
    m3 = observe.merge_metrics(c, b, a)
    assert m1 == m2 == m3
    s = m1["histograms"]["request_latency_seconds"]["softmax|xla|16x16|none"]
    assert s["count"] == 15 and s["sum"] == 3 * V1 + 5 * V2 + 7 * V3
    assert m1["counters"]["requests_total"]["softmax|xla"] == 15
    assert set(m1) == {"histograms", "counters"}


def test_latency_summary_collapses_bucket_and_rung():
    r = observe.MetricsRegistry()
    r.observe("request_latency_seconds", ("softmax", "xla", "16x16", "none"),
              V1)
    r.observe("request_latency_seconds", ("softmax", "xla", "8x8",
                                          "degraded"), V3)
    summ = observe.latency_summary(r.snapshot())
    assert set(summ) == {"softmax|xla"}
    e = summ["softmax|xla"]
    assert e["count"] == 2
    assert 0 < e["p50_ms"] <= e["p95_ms"] <= e["p99_ms"]


def test_metrics_text_exposition():
    r = observe.MetricsRegistry()
    r.inc("requests_total", ("softmax", "xla"), 3)
    r.observe("queue_wait_seconds", ("softmax",), V1)
    text = observe.metrics_text(r.snapshot())
    assert "# TYPE repro_requests_total counter" in text
    assert 'repro_requests_total{family="softmax",backend="xla"} 3' in text
    assert "# TYPE repro_queue_wait_seconds histogram" in text
    assert 'repro_queue_wait_seconds_count{family="softmax"} 1' in text
    assert 'repro_queue_wait_seconds_bucket{family="softmax",le="+Inf"} 1' \
        in text
    # cumulative le buckets never decrease
    cums = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith("repro_queue_wait_seconds_bucket")]
    assert cums == sorted(cums) and cums[-1] == 1
    # empty document renders empty (scrape-friendly, not an error)
    assert observe.metrics_text(
        {"histograms": {}, "counters": {}, "profile": {}}) == ""


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_trace_export_schema_roundtrip(tmp_path):
    observe.RECORDER.clear()
    sid = observe.RECORDER.add("root", "test", 1.0, 2.0)
    observe.RECORDER.add("child", "test", 1.2, 1.5, parent=sid,
                         args={"k": "v"})
    path = tmp_path / "trace.json"
    n = observe.export_trace(path)
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert len(evs) == n == 2
    for e in evs:
        assert e["ph"] == "X"
        assert {"name", "cat", "ts", "dur", "pid", "tid", "args"} <= set(e)
    by = {e["name"]: e for e in evs}
    assert by["child"]["args"]["parent"] == by["root"]["args"]["sid"]
    assert by["child"]["args"]["k"] == "v"
    assert by["root"]["ts"] == 1.0e6 and by["root"]["dur"] == 1.0e6


def test_recorder_ring_is_bounded():
    rec = observe.FlightRecorder(capacity=16)
    for i in range(40):
        rec.add(f"e{i}", "t", 0.0, 0.0)
    st = rec.stats()
    assert st["events"] == 16 and st["capacity"] == 16
    assert st["dropped"] == 24
    # the ring keeps the newest events
    assert rec.events()[-1]["name"] == "e39"


# ---------------------------------------------------------------------------
# hot-path contract
# ---------------------------------------------------------------------------

def test_off_mode_allocates_nothing():
    observe.set_mode("off")
    labels = ("softmax",)

    def hot():
        tok = observe.span_begin()
        observe.span_end(tok, "x", "y")
        observe.count("requests_total", "softmax", "xla")
        observe.observe_hist("queue_wait_seconds", labels, V1)

    for _ in range(16):   # warm any lazy caches
        hot()
    tracemalloc.start()
    try:
        s0 = tracemalloc.take_snapshot()
        for _ in range(1000):
            hot()
        s1 = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    flt = [tracemalloc.Filter(True, observe.__file__)]
    diff = s1.filter_traces(flt).compare_to(s0.filter_traces(flt),
                                            "filename")
    leaked = sum(d.size_diff for d in diff)
    # any per-call allocation would show as >= 16KB over 1000 calls;
    # allow sub-1-byte/call slack for unrelated daemon-thread noise
    # (earlier tests leave supervisor/executor threads behind)
    assert leaked < 1000, \
        f"off-mode hot path allocated {leaked}B/1000 calls in observe"


def test_take_last_rung_is_read_and_clear():
    from repro.core import dispatch

    dispatch._tl_obs.rung = "retry"
    assert dispatch.take_last_rung() == "retry"
    assert dispatch.take_last_rung() is None


def test_observe_block_is_null_without_observer():
    from repro.core import dispatch

    observe.set_mode("off")   # uninstalls the dispatch observer
    blk = dispatch.observe_block("plan", family="softmax")
    assert blk is dispatch._NULL_BLOCK
    with blk:   # and it is a no-op context manager
        pass


def test_set_mode_installs_and_removes_observer():
    from repro.core import dispatch

    prev = observe.set_mode("counters")
    assert observe.mode() == "counters" and dispatch._observer is not None
    observe.set_mode("off")
    assert dispatch._observer is None
    observe.set_mode(prev)
    with pytest.raises(ValueError):
        observe.set_mode("verbose")


def _profile_host_events(path) -> list:
    """(name, start_ns, end_ns, thread line) of every host event of the
    newest profile under ``path``."""
    import glob

    from jax.profiler import ProfileData

    xplane = sorted(glob.glob(f"{path}/**/*.xplane.pb", recursive=True))[-1]
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, line.name)
            for plane in ProfileData.from_file(xplane).planes
            if plane.name == "/host:CPU" for line in plane.lines
            for e in line.events]


def test_spans_reach_a_profiler_session_with_the_recorder_off(tmp_path):
    """Named spans become ``<cat>.<name>`` annotations while a profiler
    session records, nested as opened, whatever the knob; outside a
    session they cost nothing and return no token."""
    import jax

    observe.set_mode("off")
    assert observe.span_begin("step", "engine") is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        outer = observe.span_begin("step", "engine")
        inner = observe.span_begin("sample", "engine")
        observe.span_end(inner, args={"rows": 3, "path": "greedy"})
        observe.span_end(outer)
        with observe.span("flush", "executor", family="softmax"):
            pass
        unnamed = observe.span_begin()
    finally:
        jax.profiler.stop_trace()
    assert unnamed is None
    assert observe.RECORDER.events() == []
    evs = {e[0]: e for e in _profile_host_events(tmp_path)}
    assert {"engine.step", "engine.sample", "executor.flush"} <= set(evs)
    step, sample = evs["engine.step"], evs["engine.sample"]
    assert step[1] <= sample[1] and sample[2] <= step[2]


def test_spans_mode_records_what_the_profiler_sees(tmp_path):
    import jax

    observe.set_mode("spans")
    observe.RECORDER.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        tok = observe.span_begin("admit", "engine", {"slot": 2})
        observe.span_end(tok, args={"prompt_len": 9})
    finally:
        jax.profiler.stop_trace()
    (rec,) = observe.RECORDER.events()
    assert (rec["name"], rec["cat"]) == ("admit", "engine")
    assert rec["args"]["slot"] == 2 and rec["args"]["prompt_len"] == 9
    assert "engine.admit" in {e[0] for e in _profile_host_events(tmp_path)}
    assert observe.current_parent() is None


def test_observe_imports_no_jax():
    """The module binds the profiler's annotation on first use; loading
    it alone pulls in no JAX."""
    import subprocess
    import sys

    code = ("import importlib.util, sys\n"
            f"spec = importlib.util.spec_from_file_location('o', "
            f"{observe.__file__!r})\n"
            "m = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(m)\n"
            "print('jax' in sys.modules)\n"
            "assert m.span_begin('x', 'y') is None\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, check=True).stdout
    assert out.strip() == "False"


def test_flush_span_closes_once_when_isolation_raises(tmp_path, monkeypatch):
    """A poisoned row sends the flush to per-row isolation; when that
    handler itself raises, the flush span (and its profiler annotation)
    still closes, once, on the flushing thread."""
    import jax

    from repro import runtime as rtm
    from repro.runtime.faults import FaultPlan, FaultRule

    observe.set_mode("spans")
    observe.RECORDER.clear()
    rt = rtm.ServingRuntime(backend="xla", window=3600.0, max_batch=8)
    ex = rt.executor
    try:
        rows = np.random.default_rng(0).standard_normal((3, 64))
        for r in rows.astype(np.float32):
            rt.submit_softmax(r)
        with ex._cv:
            _, batch = ex._batches.popitem()

        def broken(batch, err):
            raise RuntimeError("isolation failed")

        monkeypatch.setattr(ex, "_retry_rows", broken)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with FaultPlan([FaultRule(site="executor.row", family="softmax",
                                      index=1)]):
                with pytest.raises(RuntimeError, match="isolation failed"):
                    ex._flush_batch(batch)
        finally:
            jax.profiler.stop_trace()
    finally:
        rt.close()
    flushes = [e for e in observe.RECORDER.events() if e["name"] == "flush"]
    assert len(flushes) == 1 and flushes[0]["args"]["isolated"] is True
    assert observe.current_parent() is None
    names = [e[0] for e in _profile_host_events(tmp_path)]
    assert names.count("executor.flush") == 1


def test_stats_server_endpoints():
    from urllib.request import urlopen

    observe.set_mode("counters")
    observe.METRICS.clear()
    observe.count("requests_total", "softmax", "xla")
    srv = observe.StatsServer(port=0)
    try:
        base = srv.url()
        text = urlopen(base + "/metrics", timeout=10).read().decode()
        assert 'repro_requests_total{family="softmax",backend="xla"} 1' \
            in text
        stats = json.loads(urlopen(base + "/stats", timeout=10).read())
        assert "metrics" in stats
        trace = json.loads(urlopen(base + "/trace", timeout=10).read())
        assert "traceEvents" in trace
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# the serving stack
# ---------------------------------------------------------------------------

def test_request_span_parentage_through_flush():
    from repro import runtime as rtm

    observe.set_mode("spans")
    observe.RECORDER.clear()
    K, N = 4, 256
    rt = rtm.ServingRuntime(backend="xla", window=0.25, max_batch=K)
    try:
        rng = np.random.default_rng(0)
        rows = [rng.standard_normal(N).astype(np.float32) for _ in range(K)]
        futs: list = [None] * K

        def sub(i):
            futs[i] = rt.submit_softmax(rows[i])

        ts = [threading.Thread(target=sub, args=(i,)) for i in range(K)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        for f in futs:
            np.testing.assert_allclose(np.asarray(f.result(timeout=300)).sum(),
                                       1.0, atol=1e-4)
    finally:
        rt.close()
    evs = observe.RECORDER.events()
    by_sid = {e["args"]["sid"]: e for e in evs}
    assert {"request", "admit", "queue", "reply", "flush", "serve",
            "plan"} <= {e["name"] for e in evs}
    roots = [e for e in evs if e["name"] == "request"]
    assert len(roots) == K
    for r in roots:
        kids = {e["name"] for e in evs
                if e["args"].get("parent") == r["args"]["sid"]}
        assert {"admit", "queue", "reply"} <= kids
        # the backref onto the flush that actually served this request
        assert by_sid[r["args"]["flush"]]["name"] == "flush"
    # execution nesting on the flush thread: serve under flush, plan
    # under serve
    serves = [e for e in evs if e["name"] == "serve"]
    assert serves
    for s in serves:
        assert by_sid[s["args"]["parent"]]["name"] == "flush"
    plans = [e for e in evs if e["name"] == "plan"]
    assert plans
    assert all(by_sid[p["args"]["parent"]]["name"] == "serve" for p in plans)


def test_merge_stats_folds_metrics_kvcache_executor(tmp_path):
    from repro import runtime as rtm

    observe.set_mode("counters")
    observe.METRICS.clear()
    rt = rtm.ServingRuntime(backend="xla", window=0.05, max_batch=2)
    try:
        X = np.random.default_rng(0).standard_normal((2, 128)).astype(
            np.float32)
        rt.softmax(X, stable=True)
        snap = rt.stats_snapshot()
    finally:
        rt.close()
    n_req = snap["metrics"]["counters"]["requests_total"]["softmax|xla"]
    assert n_req >= 1
    merged = rtm.merge_stats([snap, snap])
    # metrics fold through the histogram merge, not generic numeric sum
    assert merged["metrics"]["counters"]["requests_total"]["softmax|xla"] \
        == 2 * n_req
    hist = merged["metrics"]["histograms"]["request_latency_seconds"]
    assert sum(s["count"] for s in hist.values()) == \
        2 * sum(s["count"] for s in
                snap["metrics"]["histograms"]
                ["request_latency_seconds"].values())
    # the PR 9/PR 10 merge-audit keys survive the fold
    assert "kvcache" in merged and "pools" in merged["kvcache"]
    assert merged["executor"]["requests"] == 2 * snap["executor"]["requests"]
    # and the merged doc grows the cross-worker percentile view
    assert merged["latency"]["softmax|xla"]["count"] == 2 * n_req


@pytest.mark.slow
def test_fleet_merged_trace_and_cross_worker_latency(tmp_path):
    """The PR 10 acceptance run: K=8 over 4 workers with one injected
    worker kill -> ONE merged Chrome trace with per-request
    admit/queue/dispatch/reply parentage, dispatcher ``dispatch`` spans
    joining worker ``serve_group`` spans (other pids) by gid, and
    cross-worker p50/p95 per (family, backend) in ``fleet.stats()``."""
    from repro.runtime.fleet import ServingFleet
    from repro.runtime.supervisor import BackoffPolicy

    observe.set_mode("spans")
    observe.RECORDER.clear()
    observe.METRICS.clear()
    K = 8
    rows = np.random.default_rng(0).standard_normal((K, 128)).astype(
        np.float32)
    fleet = ServingFleet(
        workers=4, backend="xla", max_batch=8,
        cache_dir=str(tmp_path / "fleet-cache"),
        env={"REPRO_TRACE": "spans"},
        chaos_rules=[{"site": "worker.kill", "index": 2, "times": 1}],
        chaos_incarnations=[1], group_max=1, max_outstanding=1,
        max_redispatch=5, backoff=BackoffPolicy(base=0.01, cap=0.1),
        supervisor_tick=0.05)
    try:
        fleet.wait_ready(timeout=300)
        futs = [fleet.submit_softmax(r, deadline=120) for r in rows]
        for f in futs:
            out = np.asarray(f.result(timeout=180))
            assert abs(float(out.sum()) - 1.0) < 1e-3
        st = fleet.stats()
        assert st["fleet"]["deaths"].get("crash", 0) >= 1   # the kill landed
        lat = st["latency"]
        assert "softmax|fleet" in lat, f"latency families: {sorted(lat)}"
        e = lat["softmax|fleet"]
        assert e["count"] == K
        assert 0 < e["p50_ms"] <= e["p95_ms"]
        path = tmp_path / "fleet-trace.json"
        n_ev = fleet.export_trace(path)
    finally:
        fleet.close()
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert len(evs) == n_ev > 0
    roots = [e for e in evs if e["name"] == "request"]
    assert len(roots) == K
    for r in roots:
        kids = {e["name"] for e in evs
                if e["args"].get("parent") == r["args"]["sid"]}
        assert {"admit", "queue", "dispatch", "reply"} <= kids
    # cross-process join: dispatcher dispatch spans resolve to worker
    # serve_group spans by gid.  Spans of the killed incarnations died
    # with their processes (the truthful picture), so not every gid
    # joins — but the surviving timeline must join somewhere.
    main_pid = os.getpid()
    sg = {e["args"].get("gid"): e for e in evs if e["name"] == "serve_group"}
    assert sg and all(e["pid"] != main_pid for e in sg.values())
    joined = [e for e in evs if e["name"] == "dispatch"
              and e["args"].get("gid") in sg]
    assert joined, "no dispatch span joined a worker serve_group span"
