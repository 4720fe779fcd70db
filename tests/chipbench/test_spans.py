"""The program's spans and scopes in a traced run, on the CPU at the SMOKE
sizes: the engine's phases nest inside its steps in the profile's host
plane and in the flight recorder alike, the sampler's flush holds its
reply loop, the span table and the scope map of `chipbench/spans.py`
read what they should, and the two metrics that read them are finite.
"""

from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

from test_chipbench import CHAT, DOC, make_root  # noqa: E402

from chipbench import harness, spans  # noqa: E402

PHASES = ("engine.decode", "engine.admit", "engine.sample", "engine.retire")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One profiled run of each SMOKE cell with the flight recorder on
    too: its result, the profile's events (the CPU's operations as the
    device) and window, and the recorder's spans."""
    from repro.runtime import observe

    root = make_root(tmp_path_factory.mktemp("spans"))
    trace_dir = root / "chipbench" / ".cache" / "trace"
    out = {}
    prev = observe.set_mode("spans")
    try:
        for cell in (CHAT, DOC):
            observe.RECORDER.clear()
            result, _, _ = harness.run_cell(
                root, cell, 2147483907, 5.0, True,
                t_start=time.perf_counter(), require_tpu=False,
                trace_dir=str(trace_dir))
            ev = spans.events(spans.tracing.find_xplane(str(trace_dir)))
            out[cell] = dict(result=result, recorded=observe.RECORDER.events(),
                             events=ev, window=spans.tracing.window_bounds(
                                 ev, harness.WINDOW_MARK))
            shutil.rmtree(trace_dir)
    finally:
        observe.set_mode(prev)
        observe.RECORDER.clear()
    return out


def _within(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2] and \
        inner[3] == outer[3]


@pytest.mark.parametrize("cell", [CHAT, DOC])
def test_host_plane_nests_engine_phases_in_steps(traced, cell):
    host = traced[cell]["events"]["host"]
    steps = [h for h in host if h[0] == "engine.step"]
    assert steps
    for name in PHASES:
        phase = [h for h in host if h[0] == name]
        assert phase, name
        assert all(any(_within(p, s) for s in steps) for p in phase), name


def test_worker_thread_nests_reply_in_flush(traced):
    host = traced[CHAT]["events"]["host"]
    flushes = [h for h in host if h[0] == "executor.flush"]
    replies = [h for h in host if h[0] == "executor.reply"]
    assert flushes and len(replies) == len(flushes)
    assert all(any(_within(r, f) for f in flushes) for r in replies)
    # the flush runs on the runtime's worker thread, not the engine's
    tids = {}
    for e in traced[CHAT]["recorded"]:
        tids.setdefault(f"{e['cat']}.{e['name']}", set()).add(e["tid"])
    assert tids["executor.flush"] == tids["executor.reply"]
    assert tids["executor.flush"].isdisjoint(tids["engine.step"])


@pytest.mark.parametrize("cell", [CHAT, DOC])
def test_recorder_parents_engine_phases_under_step(traced, cell):
    rec = traced[cell]["recorded"]
    by_sid = {e["args"]["sid"]: e for e in rec}
    steps = [e for e in rec if (e["cat"], e["name"]) == ("engine", "step")]
    assert steps and all({"live", "step"} <= set(e["args"]) for e in steps)
    for name in PHASES:
        phase = [e for e in rec if f"{e['cat']}.{e['name']}" == name]
        assert phase, name
        assert all(by_sid[e["args"]["parent"]]["name"] == "step"
                   for e in phase), name
    admits = [e for e in rec if (e["cat"], e["name"]) == ("engine", "admit")]
    assert all({"prompt_len", "slot"} <= set(e["args"]) for e in admits)
    samples = [e for e in rec if (e["cat"], e["name"]) == ("engine", "sample")]
    paths = {e["args"]["path"] for e in samples}
    assert paths == ({"runtime"} if cell == CHAT else {"greedy"})
    if cell == CHAT:
        replies = [e for e in rec if (e["cat"], e["name"]) ==
                   ("executor", "reply")]
        assert replies and all(by_sid[e["args"]["parent"]]["name"] == "flush"
                               for e in replies)


@pytest.mark.parametrize("cell", [CHAT, DOC])
def test_traced_cells_read_sample_idle_and_decode_attention(traced, cell):
    metrics = traced[cell]["result"]["metrics"]
    for name in ("sample_idle_ms", "decode_attention_ms"):
        assert name in metrics, name
        assert metrics[name]["value"] >= 0.0 and metrics[name]["unit"] == "ms"
    assert metrics["decode_attention_ms"]["value"] > 0.0


def test_scope_map_covers_the_decode_program(traced):
    """Every operation the decode program ran as in the profile has a
    scope, and both scopes hold operations."""
    c = json.loads((HERE / "smoke" / "smoke-dense.json").read_text())
    text = spans.decode_program_text(c["model"], c["serving"]["capacity"],
                                     c["serving"]["max_len"])
    scopes = spans.scope_map(text)
    assert {"decode_attention", "kv_write", "unscoped"} <= \
        set(scopes.values())
    ev = traced[DOC]["events"]
    runs = [p for p in ev["programs"]
            if spans.tracing.program_name(p[0]) == spans.DECODE_PROGRAM]
    ran = {op[0] for op in spans._inside(ev["ops"], runs)}
    assert ran and ran <= set(scopes)
    split = spans.scoped_seconds(ev, *traced[DOC]["window"],
                                 spans.DECODE_PROGRAM, scopes)
    assert split[spans.UNMAPPED] == 0.0 and split["decode_attention"] > 0


def test_span_table_by_hand():
    """Nesting, a name twice over one interval (counted once through the
    union), and device idle split by the window."""
    ev = {"chips": 1,
          "ops": [("a", 0.0, 1.0, "0"), ("b", 3.0, 4.0, "0"),
                  ("c", 6.0, 9.0, "0")],
          "programs": [],
          "host": [("engine.step", 0.5, 5.0, "python"),    # the harness's
                   ("engine.step", 0.6, 4.9, "python"),    # the program's
                   ("engine.sample", 1.5, 2.5, "python"),
                   ("engine.step", 5.5, 8.0, "python"),
                   ("engine.step", 5.6, 7.9, "python"),
                   ("engine.sample", 5.7, 6.5, "python"),
                   ("executor.flush", 1.6, 2.4, "worker"),
                   ("np.asarray", 1.7, 2.0, "python")]}
    t = spans.span_table(ev, 0.0, 7.0)
    assert set(t) == {"engine.step", "engine.sample", "executor.flush"}
    # idle in [0, 7]: [1, 3] and [4, 6]
    assert t["engine.step"]["count"] == 4
    assert t["engine.step"]["seconds"] == pytest.approx(4.5 + 1.5)
    assert t["engine.step"]["idle_s"] == pytest.approx(2.0 + 1.5)
    assert t["engine.sample"]["count"] == 2
    assert t["engine.sample"]["seconds"] == pytest.approx(1.0 + 0.8)
    assert t["engine.sample"]["idle_s"] == pytest.approx(1.0 + 0.3)
    assert t["executor.flush"]["idle_s"] == pytest.approx(0.8)


def test_scope_map_by_hand():
    """A fusion without metadata takes its fused computation's scope; an
    instruction's own metadata wins; the innermost scope on a path wins."""
    text = """HloModule jit__lambda, entry_computation_layout={()->f32[]}

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %exp.1 = f32[4]{0} exponential(%param_0), metadata={op_name="jit(_lambda)/while/body/decode_attention/exp"}
}

ENTRY %main.3 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %fusion.2 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation
  %dynamic-update-slice.4 = f32[4]{0} dynamic-update-slice(%fusion.2, %x), metadata={op_name="jit(_lambda)/decode_attention/kv_write/dus"}
  ROOT %add.5 = f32[4]{0} add(%fusion.2, %x), metadata={op_name="jit(_lambda)/add"}
}
"""
    m = spans.scope_map(text)
    assert m["fusion.2"] == "decode_attention"
    assert m["dynamic-update-slice.4"] == "kv_write"
    assert m["add.5"] == "unscoped" and m["x"] == "unscoped"


def test_readers_tell_a_missing_span_from_an_empty_one(tmp_path, monkeypatch):
    """None where the program wrote no ``engine.sample`` span or has no
    ``decode_attention`` scope, 0.0 where they are there and empty."""
    bench = harness.Bench(HERE.parents[1])
    run = harness.Run(trace={}, served=None)
    loaded = {"steps": 4, "runs": 3, "spans": {}, "scoped": {"unscoped": 1.0}}
    monkeypatch.setattr(spans, "load", lambda run, d: loaded)
    idle, attn = bench.reader("sample_idle_ms"), bench.reader(
        "decode_attention_ms")
    assert idle.read(run) is None and attn.read(run) is None
    loaded["spans"]["engine.sample"] = {"count": 4, "seconds": 0.1,
                                        "idle_s": 0.0}
    loaded["scoped"]["decode_attention"] = 0.0
    assert idle.read(run) == 0.0 and attn.read(run) == 0.0
    monkeypatch.undo()
    # no profile where run.py writes it: nothing to read
    assert spans.load(run, tmp_path) is None
