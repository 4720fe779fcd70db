"""The chip benchmark's harness on the CPU, at the SMOKE sizes.

Covers what a chip run cannot show cheaply: the closed-loop driver's
determinism, the trace reduction on a recorded trace, the counting
functions at hand-computed shapes, discovery of new cells by file name,
the shape of the result line, and that the check fails a broken timed
path and the lower-precision control.  Nothing here touches a TPU: runs
go through ``run_cell(require_tpu=False)``.
"""

from __future__ import annotations

import gzip
import hashlib
import json
import random
import shutil
import sys
import time
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
SMOKE = Path(__file__).resolve().parent / "smoke"
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from chipbench import (build, check, driver, faults, flops, harness,  # noqa: E402
                       tracing, traffic)

CHAT, DOC = "smoke-dense.smoke-chat", "smoke-dense.smoke-doc"


def make_root(dst: Path) -> Path:
    """A benchmark root holding the repository's harness data plus the
    SMOKE configuration and mixes, with two cells over them."""
    shutil.copytree(REPO / "chipbench", dst / "chipbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(SMOKE / "smoke-dense.json", dst / "chipbench" / "configs")
    for mix in ("smoke-chat", "smoke-doc"):
        shutil.copy(SMOKE / f"{mix}.json", dst / "chipbench" / "traffic")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["workloads"] = [
        {"name": CHAT, "config": "smoke-dense", "traffic": "smoke-chat",
         "chips": 1, "why": "sampled"},
        {"name": DOC, "config": "smoke-dense", "traffic": "smoke-doc",
         "chips": 1, "why": "greedy, long prompts"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            doc_only = all("longdoc" in w for w in m["workloads"])
            chat_only = all(w.endswith(".chat") for w in m["workloads"])
            m["workloads"] = ([DOC] if doc_only else [CHAT] if chat_only
                              else [CHAT, DOC])
    (dst / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return dst


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


def run(root, cell, seed=2147483901, seconds=5.0, trace=False, **kw):
    return harness.run_cell(root, cell, seed, seconds, trace,
                            t_start=time.perf_counter(), require_tpu=False,
                            **kw)


# ------------------------------------------------------------ determinism
def _serve(root, cell, seed, before=None, after=None):
    bench = harness.Bench(root)
    c = bench.cell(cell)
    conf, mix = bench.config(c["config"]), bench.traffic(c["traffic"])
    cfg = build.model_config(conf)
    params = build.make_weights(cfg, seed)
    eng, rt = build.make_engine(cfg, params, conf["serving"],
                                mix["sessions"])
    try:
        build.warm_up(eng, mix["temperature"])
        eng._key = build.prng_key(seed)
        reqs = traffic.make_requests(mix, traffic.request_count(mix, 6.0),
                                     seed, conf["model"]["vocab_size"])
        out = driver.serve(eng, reqs, mix["sessions"], mix["temperature"],
                           max_steps=10_000, before_step=before,
                           after_step=after)
    finally:
        rt.close()
    return out


@pytest.mark.parametrize("cell", [CHAT, DOC])
def test_closed_loop_counts_depend_on_the_seed_alone(root, cell):
    """Two runs of one seed, the second with random sleeps around every
    step, give the same attempted and failed counts, the same status and
    the same token count for every request, and the same admissions."""
    seed = 2147483911
    a = _serve(root, cell, seed)
    rnd = random.Random(7)

    def nap(_):
        time.sleep(rnd.random() * 0.004)

    b = _serve(root, cell, seed, before=nap, after=nap)
    sig = [[(r.status, len(r.tokens), r.admit_step, r.admit_pos,
             r.finish_step) for r in x.requests] for x in (a, b)]
    assert sig[0] == sig[1]
    assert (len(a.requests), a.failed) == (len(b.requests), b.failed)
    assert a.status_counts() == b.status_counts()
    if cell == DOC:
        # the long prompts reach max_len with the batch never empty: the
        # engine's truncation shows, the same way on both runs
        assert a.status_counts().get("truncated", 0) > 0


def test_every_seed_serves_the_same_lengths():
    mix = json.loads((REPO / "chipbench/traffic/chat.json").read_text())
    a = traffic.make_requests(mix, 18, 2147483001, 92544)
    b = traffic.make_requests(mix, 18, 5, 92544)
    assert [(r.prompt_len, r.max_new) for r in a] == \
        [(r.prompt_len, r.max_new) for r in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    lens = [r.prompt_len for r in a]
    assert min(lens) >= 64 and max(lens) <= 1024


# --------------------------------------------------------- trace reduction
def _naive_busy(ops, t0, t1, step=1e-6):
    """Busy time by sampling the window on a fine grid (independent of
    the interval union the reduction uses)."""
    grid = np.arange(t0, t1, step)
    busy = np.zeros(grid.shape, bool)
    for _, s, e, _c in ops:
        busy |= (grid >= s) & (grid < e)
    return busy.sum() * step


def test_trace_reduction_on_a_recorded_chip_trace():
    with gzip.open(REPO / "chipbench/testdata/trace_small.json.gz", "rt") as f:
        ev = json.load(f)
    t0, t1 = ev["t0"], ev["t1"]
    red = tracing.reduce(ev, t0, t1)
    assert red["window_s"] == pytest.approx(t1 - t0)
    assert red["busy_s"] == pytest.approx(_naive_busy(ev["ops"], t0, t1),
                                          abs=2e-5)
    assert 0 < red["busy_s"] < red["window_s"]
    # the decode program runs in the slice; its device time is the sum of
    # its clipped events
    dec = [(max(s, t0), min(e, t1)) for n, s, e, _ in ev["programs"]
           if tracing.program_name(n) == "jit__lambda" and e > t0 and s < t1]
    assert dec
    assert red["programs"]["jit__lambda"]["seconds"] == \
        pytest.approx(sum(e - s for s, e in dec))
    assert red["programs"]["jit__lambda"]["runs"] == len(dec)
    idle = sum(g for _, g in red["idle_gaps"])
    assert idle <= red["window_s"] - red["busy_s"] + 1e-9
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and n for n, _ in red["idle_gaps"])


def test_trace_reduction_by_hand():
    """Two chips, overlapping operations, a program split by the window."""
    ev = {"chips": 2,
          "ops": [("a", 0.0, 2.0, "0"), ("b", 1.0, 3.0, "0"),
                  ("a", 5.0, 6.0, "0"), ("a", 0.0, 4.0, "1")],
          "programs": [("jit_f(3)", 0.0, 3.0, "0"), ("jit_f(3)", 5.0, 6.0, "0"),
                       ("jit_g(4)", 0.0, 4.0, "1")],
          "host": [("wait", 2.5, 5.5, "python"), ("step", 0.0, 10.0, "python")]}
    red = tracing.reduce(ev, 1.0, 8.0)
    # chip 0 busy [1, 3] + [5, 6] = 3 s; chip 1 busy [1, 4] = 3 s
    assert red["busy_s"] == pytest.approx(3.0)
    assert red["programs"]["jit_f"] == {"seconds": pytest.approx(1.5),
                                        "runs": 2}
    # chip 0's idle gaps, longest first and in time order on a tie:
    # [3, 5] under "wait" (the shorter of two covering spans), [6, 8]
    # under "step" alone
    assert red["idle_gaps"] == [["wait", pytest.approx(2.0)],
                                ["step", pytest.approx(2.0)]]
    assert dict(red["device_ops"])["a"] == pytest.approx((1 + 1 + 3) / 2)


# ------------------------------------------------------ counting functions
M = {"d_model": 8, "num_heads": 2, "num_kv_heads": 1, "head_dim": 4,
     "d_ff": 16, "vocab_size": 10, "num_layers": 3}


def test_counting_functions_by_hand():
    # per layer: wq 8x8 + wk, wv 8x4 each + wo 8x8 + 3 x 8x16 = 576
    assert flops.layer_matmul_params(M) == 64 + 32 + 32 + 64 + 384
    # prefill of L = 5: 2*5*3*576 + 4*3*2*4*15 + 2*8*10
    assert flops.prefill_flops(M, 5) == 17280 + 1440 + 160
    # bytes: weights 2*(3*576 + 80) + 5 tokens x (2 bytes x 2 x 1 x 4 x 3)
    assert flops.prefill_bytes(M, 5) == 3616 + 5 * 48
    # decode of two rows with 7 and 3 columns
    assert flops.decode_flops(M, [7, 3]) == \
        2 * 2 * 3 * 576 + 4 * 3 * 2 * 4 * 10 + 2 * 2 * 8 * 10
    assert flops.decode_bytes(M, [7, 3]) == 3616 + 10 * 48
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_seconds(1000, 50, peaks) == 10.0
    assert flops.least_seconds(1000, 200, peaks) == 20.0


def test_prefill_counts_the_prompt_not_max_len(root):
    """The roofline reader counts each admitted prompt's own tokens: the
    same program time with a longer max_len gives the same share."""
    reader = harness.Bench(root).reader("prefill_roofline")
    reqs = [traffic.Request(index=i, prompt=np.ones(L, np.int32), max_new=4,
                            admit_step=0) for i, L in enumerate((5, 9))]
    peaks = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e9}
    shares = []
    for max_len in (16, 4096):
        r = harness.Run(served=driver.Served(requests=reqs), model=M,
                        peaks=peaks, max_len=max_len,
                        trace={"programs": {"jit_admit_fn": {"seconds": 1.0,
                                                             "runs": 2}}})
        shares.append(reader.read(r))
    want = sum(flops.least_seconds(flops.prefill_flops(M, L),
                                   flops.prefill_bytes(M, L), peaks)
               for L in (5, 9))
    assert shares == [pytest.approx(100 * want)] * 2


# ---------------------------------------------------------------- discovery
def _digest(d: Path) -> dict:
    return {str(p.relative_to(d)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(d.rglob("*")) if p.is_file()}


def test_new_config_traffic_and_metric_found_by_name(tmp_path):
    root = make_root(tmp_path)
    before = _digest(root / "chipbench")
    bench = root / "chipbench"
    conf = json.loads((SMOKE / "smoke-dense.json").read_text())
    conf["model"]["num_layers"] = 1
    (bench / "configs" / "smoke-one.json").write_text(json.dumps(conf))
    mix = json.loads((SMOKE / "smoke-doc.json").read_text())
    mix["sessions"] = 3
    (bench / "traffic" / "smoke-few.json").write_text(json.dumps(mix))
    (bench / "metrics" / "steps_per_request.py").write_text(
        "def read(run):\n"
        "    return len(run.served.step_end) / len(run.requests)\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "smoke-one.smoke-few",
                              "config": "smoke-one", "traffic": "smoke-few",
                              "chips": 1, "why": "added by files alone"})
    spec["per_layer"].append({"name": "steps_per_request", "unit": "steps",
                              "better": "lower", "source": "host_clock",
                              "layer": "engine", "moves": "tokens_per_s",
                              "workloads": ["smoke-one.smoke-few"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    after = _digest(bench)
    assert all(after[k] == v for k, v in before.items())
    result, _, _ = run(root, "smoke-one.smoke-few", trace=True,
                       trace_dir=str(tmp_path / "trace"))
    assert result["correct"]
    assert result["metrics"]["steps_per_request"]["value"] > 1
    assert set(result["metrics"]) == {"steps_per_request"}


# ---------------------------------------------------------- the last line
@pytest.mark.parametrize("traced", [False, True])
def test_result_line_keys(root, tmp_path, traced):
    result, lines, _ = run(root, CHAT, trace=traced,
                           trace_dir=str(tmp_path / "t") if traced else None)
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    keys += ["breakdown"] if traced else []
    assert list(result) == keys + ["checks"]
    dev = ["platform", "kind", "count", "memory_peak_bytes"]
    assert list(result["device"]) == dev + (["busy_s", "window_s"]
                                            if traced else [])
    assert result["correct"] is True
    assert result["attempted"] == traffic.request_count(
        json.loads((SMOKE / "smoke-chat.json").read_text()), 5.0)
    want = ["itl_p95_ms", "tokens_per_s", "setup_s"] if not traced else \
        ["batch_occupancy", "mfu"]
    assert set(want) <= set(result["metrics"])
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert set(result["checks"]) == {"logit_gap", "sampler_gap", "count_gap"}
    assert lines == [f"{k} {c['value']!r} limit {c['limit']!r}"
                     for k, c in result["checks"].items()]
    json.dumps(result)


# ------------------------------------------------- faults and the control
@pytest.mark.parametrize("cell", [CHAT, DOC])
@pytest.mark.parametrize("kind", faults.KINDS)
def test_broken_timed_path_is_not_correct(root, cell, kind):
    result, _, _ = run(root, cell, hooks={"engine": faults.install(kind)})
    assert result["correct"] is False, result["checks"]


@pytest.mark.parametrize("cell", [CHAT, DOC])
def test_lower_precision_control_is_not_correct(root, cell):
    """The reference with float8 weights put in the program's place (and,
    where the cell samples, the sampler computed in bfloat16), judged by
    the run's own comparison, is not correct where the program's run is."""
    result, _, readings = run(root, cell, controls=("fp8",))
    assert result["correct"] is True
    limits = json.loads((SMOKE / "smoke-dense.json").read_text())["limits"]
    ok, checks = harness.judge(check.as_control(readings, "fp8"), limits,
                               cell == CHAT)
    assert ok is False, checks
    assert checks["logit_gap"]["value"] > limits["logit_gap"], checks
    if cell == CHAT:
        assert checks["sampler_gap"]["value"] > limits["sampler_gap"]


def test_sample_holds_the_longest_request():
    reqs = [traffic.Request(index=i, prompt=np.ones(3, np.int32), max_new=m)
            for i, m in enumerate([5, 90, 7, 12, 3])]
    chosen = check.sample_requests(reqs, 2147483999, 100, 3)
    assert chosen[0].max_new == 90 and len(chosen) <= 3
    assert chosen == check.sample_requests(reqs, 2147483999, 100, 3)
