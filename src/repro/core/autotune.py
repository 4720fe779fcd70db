"""Automated tuning (paper §4.1, §6.2, Table 1).

"Retain as many variants as is practical ... choose the best one from a
reasonable-size pool of candidates in an automated fashion, guided by
some metric such as execution speed ... at run time, when complete
information is available."

The tuner takes a candidate list of config dicts and a ``builder``
returning a callable per config, measures each, and persists the winner
in the tuning cache keyed by (kernel name, candidate space, abstract
input signature, environment fingerprint) — so tuning cost is paid once
per relevant change, exactly like the paper's application-level cache.

Measurement backends (pluggable — see DESIGN.md §8.1):
  * ``wallclock`` — median-of-repeats timing (the paper's mode; used on
    real hardware and for CPU-executable generated code).
  * ``analytic``  — a TPU roofline/VMEM cost model over the config, for
    TPU-targeted kernels in a CPU-only container where wall-clock would
    measure the interpreter, not the hardware.
  * ``hybrid``    — the analytic model *pre-prunes* the candidate pool
    (keeping the ``prune_keep`` cheapest, default ~1/3), then only the
    survivors are wall-clock timed.  Tuning cost drops from
    O(candidates) timings to O(survivors) while the model only has to
    rank, not predict, absolute speed.

Per-bucket tuning: pass ``signature_fn=dispatch.bucketed_signature`` so
the cache key collapses exact array sizes to their power-of-two shape
bucket — a winner tuned once transfers to every size in the bucket
(kernels' ``.autotune()`` does this by default).
"""

from __future__ import annotations

import math
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import jax

from repro.core.cache import DiskCache, stable_hash, tuning_cache


# Winner hooks (PR 5, DESIGN.md §9.2): after a per-bucket tune resolves,
# every registered hook gets ``(name, backend, bucket, seconds, sequence)``
# for the winning config.  The serving runtime's backend router subscribes
# here so its per-(backend, bucket) latency priors are *seeded* by measured
# tuning results instead of starting blind, and the warm-start manifest
# records the winning transformation sequence for replay.
WINNER_HOOKS: list[Callable] = []


def notify_winner(name: str, backend: "str | None", bucket: Any,
                  seconds: float, sequence: "tuple | None" = None) -> None:
    """Fan a tuning winner's measured score (and, since the kernel-IR
    layer, its winning transformation sequence) out to the registered
    hooks (exceptions are swallowed — telemetry must never fail a tune).
    Legacy four-argument hooks are still called without the sequence."""
    for fn in list(WINNER_HOOKS):
        try:
            try:
                fn(name, backend, bucket, seconds, sequence)
            except TypeError:
                fn(name, backend, bucket, seconds)
        except Exception:  # pragma: no cover - observability only
            pass


# ----------------------------------------------------------------------
# Transformation-sequence store (kernel IR, DESIGN.md §11).  A tuning
# winner is not just a scalar block size: it is the IR transformation
# sequence (`repro.core.ir.TRANSFORMS` vocabulary) that produced the
# winning schedule — ``transpose_layout`` for column-segmented domains,
# ``tile(rows, block)`` / ``split(stream, inner)`` for the blocking.
# The store is keyed per ``(tune name, backend, bucket)`` so the kernel
# families can recover a tuned schedule for any shape in the bucket even
# on a *fresh kernel instance* (the per-instance ``_tuned`` dict only
# survives as long as the object), and the warm-start manifest persists
# it across processes.
# ----------------------------------------------------------------------
_SEQ_LOCK = threading.Lock()
SEQUENCE_STORE: dict = {}   # (name, backend, bucket) -> transformation seq


def _seq_bucket(bucket: Any) -> Any:
    return tuple(bucket) if isinstance(bucket, (list, tuple)) else bucket


def sequence_for(param: str, value: int, transposed: bool = False) -> tuple:
    """The IR transformation sequence a winning ``param`` value denotes.

    ``block_rows`` winners tile the ``rows`` axis (after a
    ``transpose_layout`` when the domain is column-segmented);
    ``block_n`` winners split the scan ``stream`` axis."""
    if param == "block_n":
        return (("split", {"axis": "stream", "inner": int(value)}),)
    seq = [("transpose_layout", {})] if transposed else []
    seq.append(("tile", {"axis": "rows", "block": int(value)}))
    return tuple(seq)


def record_sequence(name: str, backend: "str | None", bucket: Any,
                    sequence) -> None:
    """Record ``sequence`` as the winning transformation chain for
    ``(name, backend, bucket)`` (idempotent; thread-safe)."""
    seq = tuple((op, dict(params)) for op, params in sequence)
    with _SEQ_LOCK:
        SEQUENCE_STORE[(name, backend, _seq_bucket(bucket))] = seq


def tuned_sequence(name: str, backend: "str | None",
                   bucket: Any) -> "tuple | None":
    """The recorded winning transformation sequence, or None."""
    with _SEQ_LOCK:
        return SEQUENCE_STORE.get((name, backend, _seq_bucket(bucket)))


def sequence_param(name: str, backend: "str | None", bucket: Any,
                   param: str) -> "int | None":
    """Extract the scalar knob (``block_rows`` / ``block_n``) from a
    recorded transformation sequence — how the kernel families' fast
    paths consult the store without replaying the IR chain."""
    seq = tuned_sequence(name, backend, bucket)
    if not seq:
        return None
    for op, params in seq:
        if param == "block_n" and op == "split":
            return params.get("inner")
        if param == "block_rows" and op == "tile":
            return params.get("block")
    return None


def block_rows_candidates(n: int, lanes: int = 128) -> list[dict]:
    """Shared ``block_rows`` candidate pool for the row-blocked kernel
    families (elementwise, reduction): powers of two up to the padded
    (pow2-bucketed) row count — so the largest candidate is a single
    grid step over the bucket with zero extra padding, and every
    candidate keeps the grid divisible."""
    rows = -(-n // lanes)
    cap = 1 << (max(8, rows) - 1).bit_length()  # next_pow2, >= 8
    cands = [{"block_rows": b}
             for b in (8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192)
             if b <= cap]
    return cands or [{"block_rows": 8}]


def batch_block_candidates(b: int) -> list[dict]:
    """``block_rows`` candidate pool for *row-segmented* kernels, where
    the blocked dimension is the batch-row count of a ``(B, N)`` operand:
    the blocks a TPU accepts (`dispatch.batch_block`) — multiples of 8
    rows up to one grid step over the padded batch bucket, or, below 8
    rows, the whole padded batch as one block."""
    from repro.core.dispatch import batch_block

    return [{"block_rows": r}
            for r in sorted({batch_block(b, r)
                             for r in (8, 16, 32, 64, 128, 256)})]


def block_n_candidates(n: int) -> list[dict]:
    """``block_n`` candidate pool for the blocked scan: power-of-two
    block lengths no larger than the padded input (one block minimum)."""
    cap = max(1024, 1 << (max(1, n) - 1).bit_length())
    cands = [{"block_n": b} for b in (1024, 2048, 4096, 8192, 16384)
             if b <= cap]
    return cands or [{"block_n": 1024}]


def tune_per_bucket(name: str, builder: Callable, cost_fn: Callable,
                    candidates: Sequence[dict], args: Sequence[Any], n: int,
                    tuned: dict, param: str, *, measure: str = "hybrid",
                    cache: "DiskCache | None" = None, repeats: int = 3,
                    warmup: int = 1, prune_keep: int | None = None,
                    bucket_key: Any = None,
                    signature_fn: Callable | None = None,
                    backend: str | None = None) -> "TuneReport":
    """Shared per-bucket tuning path for the kernel families.

    Wires `Autotuner(signature_fn=dispatch.bucketed_signature)` (so the
    tuning-cache key collapses exact sizes to their shape bucket) and
    records the winner's ``param`` in ``tuned``, where the family's
    ``_pick_*`` lookup finds it on later plain calls.  Elementwise/
    Reduction tune ``block_rows``; Scan tunes ``block_n``.

    Row-segmented (axis-aware) kernels pass ``bucket_key=rc_bucket(b, n)``
    and ``signature_fn=dispatch.bucketed_signature_2d`` so the winner is
    recorded per (batch, row-length) bucket *pair* instead of per flat
    element-count bucket.

    The signature carries the *execution backend* (PR 4): with
    ``backend`` set, winners live in ``tuned[(backend, bucket)]`` and
    the persistent tuning-cache key includes the backend name, so a
    block size tuned on the pallas interpreter can never be served to
    the xla lowering (or vice versa) — the backend is a measured
    variable, like the CUDA-vs-OpenCL comparisons treat it.
    """
    from repro.core import dispatch

    nb = bucket_key if bucket_key is not None else dispatch.n_bucket(n)
    tuner = Autotuner(name, builder=builder, measure=measure, cost_fn=cost_fn,
                      cache=cache, repeats=repeats, warmup=warmup,
                      signature_fn=signature_fn or dispatch.bucketed_signature,
                      prune_keep=prune_keep)
    report = tuner.tune(candidates, args,
                        key_extra=("n_bucket",
                                   list(nb) if isinstance(nb, tuple) else nb,
                                   "backend", backend or ""))
    # winner key is ALWAYS the (backend, bucket) pair — the families'
    # _pick_* lookups read exactly this shape, so a caller omitting
    # ``backend`` still stores a readable (None, bucket) entry rather
    # than a bare-bucket key nothing ever consults
    tuned[(backend, nb)] = report.best[param]
    # the winner *is* a transformation sequence: record it per
    # (name, backend, bucket) so fresh kernel instances and the
    # warm-start manifest can replay the schedule, not just the scalar
    transposed = isinstance(nb, tuple) and len(nb) > 2
    sequence = sequence_for(param, report.best[param], transposed=transposed)
    record_sequence(name, backend, nb, sequence)
    viable = [r.score for r in report.results
              if r.ok and math.isfinite(r.score)]
    if viable:  # seed the serving runtime's router with the winner's score
        notify_winner(name, backend, nb, min(viable), sequence=sequence)
    return report


def signature_of(args: Sequence[Any]) -> list:
    sig = []
    for a in args:
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is not None:
            sig.append([list(shape), str(dtype)])
        else:
            sig.append([type(a).__name__])
    return sig


def measure_wallclock(fn: Callable, args: Sequence[Any], *, repeats: int = 5,
                      warmup: int = 2) -> float:
    """Median wall-clock seconds per call, post-warmup, synchronized."""

    def sync(res):
        jax.block_until_ready(res)

    for _ in range(warmup):
        sync(fn(*args))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        sync(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


# ----------------------------------------------------------------------
# Analytic TPU cost model: scores a blocked kernel config without running
# it.  Inputs are abstract: bytes moved per block, flops per block, grid
# size, vmem footprint.  Peaks come from `platform.device_peaks`.
# ----------------------------------------------------------------------
GRID_OVERHEAD_S = 1e-6  # per-grid-step dispatch overhead estimate
MXU_DIM = 128
SUBLANE = 8


@dataclass
class BlockCost:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    vmem_bytes: float = 0.0
    grid: int = 1
    # matmul tile dims for MXU alignment penalties (0 = not a matmul)
    tile_dims: tuple = ()

    def seconds(self) -> float:
        from repro.core.platform import device_peaks

        peaks = device_peaks()
        if self.vmem_bytes > peaks["vmem_bytes"]:
            return math.inf  # config does not fit VMEM: reject
        compute_t = self.flops / peaks["bf16_flops"]
        mem_t = self.hbm_bytes / peaks["hbm_bytes_per_s"]
        align = 1.0
        for d in self.tile_dims:
            if d % MXU_DIM:  # pay for padding to the systolic array
                align *= MXU_DIM / (d % MXU_DIM) if d < MXU_DIM else 1.1
        return max(compute_t, mem_t) * align + self.grid * GRID_OVERHEAD_S


@dataclass
class TuneResult:
    params: dict
    score: float
    ok: bool = True
    error: str = ""


@dataclass
class TuneReport:
    name: str
    best: dict
    results: list[TuneResult] = field(default_factory=list)
    cached: bool = False

    def table(self) -> str:
        rows = [f"{self.name}: best={self.best} cached={self.cached}"]
        for r in sorted(self.results, key=lambda r: r.score):
            rows.append(f"  {r.params}  score={r.score:.3e}  {'OK' if r.ok else r.error}")
        return "\n".join(rows)


class Autotuner:
    def __init__(self, name: str, builder: Callable[..., Callable],
                 measure: str = "wallclock",
                 cost_fn: Callable[[dict, Sequence[Any]], BlockCost] | None = None,
                 cache: DiskCache | None = None,
                 repeats: int = 5, warmup: int = 2,
                 signature_fn: Callable[[Sequence[Any]], list] | None = None,
                 prune_keep: int | None = None):
        self.name = name
        self.builder = builder
        self.measure = measure
        self.cost_fn = cost_fn
        self.cache = cache if cache is not None else tuning_cache
        self.repeats, self.warmup = repeats, warmup
        self.signature_fn = signature_fn or signature_of
        self.prune_keep = prune_keep
        if measure in ("analytic", "hybrid") and cost_fn is None:
            raise ValueError(f"{measure} measurement requires cost_fn")

    def _score(self, params: dict, args: Sequence[Any]) -> float:
        if self.measure == "analytic":
            return self.cost_fn(params, args).seconds()
        fn = self.builder(**params)
        return measure_wallclock(fn, args, repeats=self.repeats, warmup=self.warmup)

    def _hybrid_survivors(self, candidates: Sequence[dict], args: Sequence[Any]
                          ) -> tuple[list[dict], list[TuneResult]]:
        """Rank all candidates analytically; return (to-time, pruned-results)."""
        scored = []
        for params in candidates:
            try:
                scored.append((self.cost_fn(params, args).seconds(), params))
            except Exception as e:
                scored.append((math.inf, params))
        scored.sort(key=lambda t: t[0])
        keep = self.prune_keep or max(2, len(candidates) // 3)
        survivors = [p for s, p in scored[:keep] if math.isfinite(s)]
        pruned = [TuneResult(params=p, score=s, ok=False,
                             error="pruned by analytic model")
                  for s, p in scored[len(survivors):]]
        if not survivors:  # model rejected everything: fall back to timing all
            return list(candidates), []
        return survivors, pruned

    def tune(self, candidates: Sequence[dict], args: Sequence[Any],
             key_extra: Any = None, use_cache: bool = True) -> TuneReport:
        key = self.cache.make_key(self.name, list(candidates),
                                  self.signature_fn(args),
                                  self.measure, key_extra)
        if use_cache:
            hit = self.cache.get(key)
            if hit is not None:
                return TuneReport(self.name, best=hit["best"],
                                  results=[TuneResult(**r) for r in hit["results"]],
                                  cached=True)
        results: list[TuneResult] = []
        to_time: Sequence[dict] = candidates
        if self.measure == "hybrid":
            to_time, pruned = self._hybrid_survivors(candidates, args)
            results.extend(pruned)
        for params in to_time:
            try:
                score = self._score(params, args)
                results.append(TuneResult(params=params, score=score))
            except Exception as e:  # a failing variant is data, not an error
                results.append(TuneResult(params=params, score=math.inf,
                                          ok=False, error=f"{type(e).__name__}: {e}"[:200]))
        viable = [r for r in results if r.ok and math.isfinite(r.score)]
        if not viable:
            raise RuntimeError(f"autotune({self.name}): no viable candidate\n" +
                               "\n".join(f"{r.params}: {r.error}" for r in results))
        best = min(viable, key=lambda r: r.score).params
        self.cache.put(key, {"best": best,
                             "results": [r.__dict__ for r in results]})
        return TuneReport(self.name, best=best, results=results)

    def build_best(self, candidates: Sequence[dict], args: Sequence[Any],
                   **tune_kwargs) -> tuple[Callable, TuneReport]:
        report = self.tune(candidates, args, **tune_kwargs)
        return self.builder(**report.best), report
