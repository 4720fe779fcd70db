"""Dispatch engine — shape-bucketed drivers + a low-overhead launch path.

The paper's economics (Fig. 2) only work if a generated kernel is cheap
to *re-launch*: compilation is amortized by the semi-permanent cache,
so the steady state must be a dictionary lookup, not a re-trace.  The
seed violated this for shape churn — every distinct element count ``n``
built (template render + ``exec`` + ``jax.jit`` trace) a brand-new
driver.  This module makes launch cost independent of shape churn.

Bucketing math
--------------
An elementwise/reduction workload of ``n`` elements is laid out as
``(rows, LANES)`` with ``rows = ceil(n / LANES)``.  Instead of
compiling a driver for the exact ``rows``, we round up:

1. ``rows`` -> next multiple of ``block_rows``   (grid must divide)
2. that     -> next power of two                 (the *bucket*)

so one compiled driver serves every ``n`` whose padded row count lands
in the same bucket.  Correctness does not depend on the static bucket
shape: inputs are zero-padded up to the bucket and the *runtime* ``n``
(a traced scalar, not a static constant) masks or slices the result.
An ``n`` sweep over a ``2x`` range therefore compiles at most
``ceil(log2(range)) + 1`` drivers — the acceptance bound — and the
waste is bounded: a bucket at most doubles the padded rows, and padded
lanes cost only VPU time, never correctness.

Driver cache
------------
Compiled drivers are closures over jitted ``pallas_call``s — they
cannot go in the JSON `DiskCache`, so they live in a bounded in-memory
`LRUCache` (`driver_cache()`), *shared* across `ElementwiseKernel`,
`ReductionKernel` and `ScanKernel` instances.  Keys are
content-addressed on the rendered source hash (two instances producing
identical source share one driver).  Eviction merely costs a rebuild.

Counters
--------
``compile_count()`` / ``launch_count()`` count driver builds and driver
invocations process-wide, *tagged per backend* (PR 4): drivers compiled
by different execution backends never share a cache entry (keys carry
the backend name), and the counters keep the same separation so a
launch-count assertion can never silently mix backends.  The no-arg
forms return process totals; pass a backend name for one backend's
count, or read the full tag -> count maps via ``compile_counts()`` /
``launch_counts()``.  ``benchmarks/run.py`` records the per-backend
deltas per suite.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Sequence

from repro.core.cache import LRUCache
from repro.core.platform import LANES  # re-export: the bucketing lane width
from repro.core.platform import SUBLANES

_DEFAULT_CACHE_SIZE = int(os.environ.get("REPRO_DRIVER_CACHE_SIZE", "256"))

_driver_cache = LRUCache(maxsize=_DEFAULT_CACHE_SIZE)

_counter_lock = threading.Lock()
_UNTAGGED = "untagged"  # counter tag when a caller does not name a backend
_compile_counts: dict[str, int] = {}
_launch_counts: dict[str, int] = {}
_degradation_counts: dict[str, int] = {}

# Fault-injection probe (PR 6, DESIGN.md §10).  ``repro.runtime.faults``
# installs its `maybe_fail` here on import; until then — and whenever no
# `FaultPlan` is active — the compile/launch paths pay one ``is None``
# check.  The hook signature is ``(site, backend, family, bucket,
# index)`` and it *raises* (an ``InjectedFault``) to inject.
_fault_hook: "Callable | None" = None

# Observability probe (PR 10, DESIGN.md §14).  ``repro.runtime.observe``
# installs its event callback here when ``REPRO_TRACE`` is armed — the
# same core-never-imports-runtime seam as the fault hook.  Events:
# ``("site", site=, backend=, family=, bucket=, t0=, t1=)`` for a timed
# compile/launch attempt (monotonic seconds), ``("degradation", rung=,
# family=)`` per ladder step, and ``("begin", name=, family=)``/
# ``("end", token=, name=, family=)`` bracketing an `observe_block`.  With no observer the
# launch path pays one ``is None`` check and zero allocations.
_observer: "Callable | None" = None

# Last degradation rung taken on *this thread* — the serving layer reads
# (and clears) it per request to label latency histograms with the rung
# that actually served the request.  Thread-local because requests on
# different executor/fleet threads degrade independently.
_tl_obs = threading.local()

# Bounded-retry knobs for *transient* failures (an exception whose
# ``transient`` attribute is truthy — injected flakes, and any real
# error a backend marks recoverable).  Read per call so tests can
# monkeypatch the env.
_RETRY_BACKOFF_S = 0.0005
_RETRY_BACKOFF_CAP_S = 0.05


def set_fault_hook(fn: "Callable | None") -> None:
    """Install (or clear) the fault-injection probe — see
    `repro.runtime.faults`; core never imports the runtime layer."""
    global _fault_hook
    _fault_hook = fn


def set_observer(fn: "Callable | None") -> None:
    """Install (or clear) the observability probe — see
    `repro.runtime.observe`; core never imports the runtime layer.
    Observer exceptions are swallowed at every notification site:
    telemetry must never change execution."""
    global _observer
    _observer = fn


def _notify_site(site: str, backend: "str | None", family: "str | None",
                 bucket: "tuple | None", t0: float, t1: float) -> None:
    obs = _observer
    if obs is not None:
        try:
            obs("site", site=site, backend=backend, family=family,
                bucket=bucket, t0=t0, t1=t1)
        except Exception:  # pragma: no cover - telemetry never breaks launches
            pass


def take_last_rung() -> "str | None":
    """Read-and-clear the last degradation rung recorded on this thread
    (None when the preceding call served clean) — the latency-histogram
    ``rung`` label."""
    rung = getattr(_tl_obs, "rung", None)
    _tl_obs.rung = None
    return rung


class _NullBlock:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_BLOCK = _NullBlock()


class _ObserveBlock:
    __slots__ = ("name", "family", "token")

    def __init__(self, name: str, family: "str | None"):
        self.name, self.family, self.token = name, family, None

    def __enter__(self):
        obs = _observer
        if obs is not None:
            try:
                self.token = obs("begin", name=self.name,
                                 family=self.family)
            except Exception:  # pragma: no cover
                self.token = None
        return self

    def __exit__(self, *exc):
        obs = _observer
        if obs is not None and self.token is not None:
            try:
                obs("end", token=self.token, name=self.name,
                    family=self.family)
            except Exception:  # pragma: no cover
                pass
        return False


def observe_block(name: str, family: "str | None" = None):
    """Span a core-side block (e.g. the planner's resilient evaluation)
    in the flight recorder, parenting any launches inside it.  With no
    observer installed this returns a shared null context manager —
    no allocation on the unobserved path."""
    if _observer is None:
        return _NULL_BLOCK
    return _ObserveBlock(name, family)


def retry_max() -> int:
    """Max *retries* (attempts - 1) for transient failures at the
    compile/launch sites; ``REPRO_RETRY_MAX``, default 5 — deep enough
    that a 5% transient fault rate escapes a call with p ≈ 1.6e-8, so
    launch-count assertions stay exact under the CI chaos leg."""
    return max(0, int(os.environ.get("REPRO_RETRY_MAX", "5")))


def run_with_retries(fn: Callable[[], Any], *, site: str,
                     backend: "str | None" = None,
                     family: "str | None" = None,
                     bucket: "tuple | None" = None) -> Any:
    """Run ``fn`` behind the fault probe with bounded exponential-backoff
    retries for transient failures.  Non-transient exceptions propagate
    immediately (the degradation ladder and circuit breaker own those);
    with no hook and no observer installed this is a plain call.

    When the observer is armed, each *successful* attempt is timed with
    ``time.monotonic()`` (system-wide on Linux, so fleet-worker spans
    land on one timeline) and reported as a ``site`` event."""
    if _fault_hook is None and _observer is None:
        return fn()
    if _fault_hook is None:
        t0 = time.monotonic()
        out = fn()
        _notify_site(site, backend, family, bucket, t0, time.monotonic())
        return out
    attempts = retry_max() + 1
    for k in range(attempts):
        try:
            _fault_hook(site, backend, family, bucket, None)
            if _observer is None:
                return fn()
            t0 = time.monotonic()
            out = fn()
            _notify_site(site, backend, family, bucket, t0, time.monotonic())
            return out
        except Exception as e:  # noqa: BLE001 - classified below
            if not getattr(e, "transient", False) or k >= attempts - 1:
                raise
            time.sleep(min(_RETRY_BACKOFF_S * (2 ** k), _RETRY_BACKOFF_CAP_S))
    raise AssertionError("unreachable")  # pragma: no cover

# Compile listeners (PR 5, DESIGN.md §9.3): the serving runtime's
# warm-start manifest records every driver build it witnesses, so a
# fresh process can replay the same keys at startup.  Listeners get
# ``(key, backend)`` per build; exceptions are swallowed (observability
# must never break a compile).
_compile_listeners: list = []


# ----------------------------------------------------------------- buckets
def next_pow2(x: int) -> int:
    """Smallest power of two >= x (x >= 1)."""
    return 1 << (max(1, int(x)) - 1).bit_length()


def bucket_rows(n: int, block_rows: int, lanes: int = LANES) -> int:
    """Padded row count for ``n`` elements, rounded to its pow2 bucket.

    Result is a multiple of ``block_rows`` (the grid must divide) and a
    power of two whenever ``block_rows`` is one (it always is for the
    tuner's candidate set).
    """
    rows = -(-n // lanes)
    rows = -(-rows // block_rows) * block_rows
    bucket = next_pow2(rows)
    # block_rows not a power of two: keep divisibility over pow2-ness.
    return -(-bucket // block_rows) * block_rows


def n_bucket(n: int, lanes: int = LANES) -> int:
    """Shape bucket of an element count, independent of block_rows.

    Used as the per-bucket key for autotuning results: every ``n``
    mapping to the same ``n_bucket`` shares one tuned configuration.
    """
    return next_pow2(-(-n // lanes))


# ------------------------------------------- 2-D (row-segmented) buckets
def batch_block(b: int, block_rows: int) -> int:
    """The row-segmented block a TPU accepts for a ``b``-row batch:
    ``block_rows`` rounded up to a multiple of `SUBLANES`, capped at the
    whole pow2-padded batch (a block's row count must be a multiple of
    8 or equal the padded array's).  Every target uses the same rule,
    so interpret-mode runs exercise the layout the chip compiles."""
    whole = next_pow2(max(1, int(b)))
    return min(whole, -(-int(block_rows) // SUBLANES) * SUBLANES)


def bucket_batch(b: int, block_rows: int) -> int:
    """Padded batch-row count for a row-segmented kernel over ``(B, N)``
    operands: next multiple of ``block_rows`` (the grid must divide),
    then the next power of two — the same shape-churn bound as
    `bucket_rows`, applied to the *batch* dimension, so a batch-size
    sweep over a ``k×`` range compiles ≤ ``ceil(log2(k)) + 1`` drivers.
    ``block_rows`` must already satisfy `batch_block`'s rule.
    """
    if block_rows != batch_block(b, block_rows):
        raise ValueError(
            f"block_rows={block_rows} for a {b}-row batch is neither a "
            f"multiple of {SUBLANES} nor the whole padded batch "
            f"(use dispatch.batch_block)")
    rows = -(-max(1, int(b)) // block_rows) * block_rows
    bucket = next_pow2(rows)
    return -(-bucket // block_rows) * block_rows


def bucket_cols(n: int, lanes: int = LANES) -> int:
    """Padded row length for a row-segmented kernel: a power-of-two
    number of LANES-wide lane groups, so a row-length sweep also
    compiles log-many drivers.  The runtime row length masks padding
    lanes inside the kernel (reductions) or is sliced off (elementwise).
    """
    return next_pow2(-(-max(1, int(n)) // lanes)) * lanes


def rc_bucket(b: int, n: int, lanes: int = LANES,
              transposed: bool = False, ragged: bool = False) -> tuple:
    """(batch, row-length) bucket pair — the per-bucket tuning key for
    row-segmented kernels, independent of ``block_rows`` (analogue of
    `n_bucket` for the 2-D layout).

    ``transposed=True`` appends a layout marker: axis=0 column
    reductions run the segmented kernel over the transposed domain, so
    their winners must never collide with axis=-1 winners for the same
    geometry in the tuning store or breaker cells (a square (N, N)
    operand would otherwise share a key across both layouts).

    ``ragged=True`` appends an ``"R"`` marker: ragged row-segmented
    kernels carry a per-row length operand and mask differently from
    the dense form, so their tuning winners / router EMA cells /
    breaker cells must never collide with same-geometry dense ones."""
    pair = (next_pow2(max(1, int(b))), next_pow2(-(-max(1, int(n)) // lanes)))
    if transposed:
        pair = pair + ("T",)
    if ragged:
        pair = pair + ("R",)
    return pair


def default_batch_block(b: int, target_grid: int = 8,
                        max_rows: int = 256) -> int:
    """Bucket-derived default batch ``block_rows`` for row-segmented
    kernels: keep the sequential grid near ``target_grid`` steps.  A
    batch under 8 rows is one whole-batch block (the B=1 serving
    sampler pays no row-padding tax); larger batches use multiples of
    8 rows (`batch_block`)."""
    br = next_pow2(max(1, int(b))) // target_grid
    return batch_block(b, min(max_rows, max(br, 1)))


def default_block_rows(n: int, lanes: int = LANES, target_grid: int = 8,
                       min_rows: int = 8, max_rows: int = 512) -> int:
    """Bucket-derived default ``block_rows``: scale the block so the
    sequential grid stays ~``target_grid`` steps (8-row blocks on a
    100k-element reduction mean a 128-step grid — 5x slower than a
    right-sized block).  Derived from `n_bucket`, never exact ``n``, so
    every size in a bucket picks the same driver.  Explicit/instance/
    tuned ``block_rows`` always override this."""
    br = n_bucket(n, lanes) // target_grid
    return max(min_rows, min(max_rows, br or min_rows))


def bucketed_signature(args: Sequence[Any], lanes: int = LANES) -> list:
    """Abstract input signature with sizes collapsed to their buckets.

    Drop-in for `autotune.signature_of` as an Autotuner ``signature_fn``:
    two argument lists whose arrays share dtypes and size *buckets*
    produce the same tuning-cache key, so a winner tuned at ``n=5000``
    transfers to ``n=5100`` without re-timing.
    """
    sig = []
    for a in args:
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is not None:
            size = 1
            for d in shape:
                size *= int(d)
            sig.append(["bucket", n_bucket(max(1, size), lanes), str(dtype)])
        else:
            sig.append([type(a).__name__])
    return sig


def bucketed_signature_2d(args: Sequence[Any], lanes: int = LANES) -> list:
    """2-D counterpart of `bucketed_signature` for row-segmented kernels:
    the last dim buckets as a row length, the leading dims collapse to a
    batch-row bucket (`rc_bucket`), so a tuning winner transfers across
    a whole ``(B, N)`` sweep within one bucket pair."""
    sig = []
    for a in args:
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is not None and len(shape) >= 2:
            b = 1
            for d in shape[:-1]:
                b *= int(d)
            rb, cb = rc_bucket(b, int(shape[-1]), lanes)
            sig.append(["bucket2d", rb, cb, str(dtype)])
        elif shape is not None:
            size = 1
            for d in shape:
                size *= int(d)
            sig.append(["bucket", n_bucket(max(1, size), lanes), str(dtype)])
        else:
            sig.append([type(a).__name__])
    return sig


# ------------------------------------------------------------ driver cache
def driver_cache() -> LRUCache:
    return _driver_cache


def get_or_build(key: Any, builder: Callable[[], Callable],
                 backend: str | None = None, name: str | None = None,
                 bucket: "tuple | None" = None) -> Callable:
    """Shared-LRU lookup; on miss, build + count one driver compile
    against ``backend``'s tag.  Callers must put the backend name in
    ``key`` too — the tag only labels the counter.  ``name``/``bucket``
    identify the kernel to the fault probe (the ``compile`` site fires
    *before* the builder runs, so a failed build never half-counts);
    transient compile faults are absorbed by bounded retries.

    ``REPRO_IR_STRICT=1`` additionally asserts the builder went through
    the kernel-IR pipeline (`repro.core.ir.mark_rendered`) — the CI
    IR-parity leg's proof that no legacy string path builds drivers."""
    tag = backend or _UNTAGGED

    def build():
        strict = os.environ.get("REPRO_IR_STRICT", "") not in ("", "0")
        if strict:
            from repro.core import ir as _ir
            _ir.clear_rendered()
        drv = run_with_retries(builder, site="compile", backend=tag,
                               family=name, bucket=bucket)
        if strict:
            from repro.core import ir as _ir
            if not _ir.take_rendered():
                raise AssertionError(
                    f"REPRO_IR_STRICT: driver {key!r} was built without "
                    f"the kernel-IR pipeline (legacy string path)")
        return drv

    return _driver_cache.get_or_create(
        key, build, on_create=lambda: _record_compile(tag, key))


def add_compile_listener(fn: Callable[[Any, str], None]) -> None:
    """Register ``fn(key, backend)`` to run after every driver compile
    (the warm-start manifest's recording hook)."""
    if fn not in _compile_listeners:
        _compile_listeners.append(fn)


def remove_compile_listener(fn: Callable[[Any, str], None]) -> None:
    try:
        _compile_listeners.remove(fn)
    except ValueError:
        pass


def _record_compile(backend: str, key: Any = None) -> None:
    with _counter_lock:
        _compile_counts[backend] = _compile_counts.get(backend, 0) + 1
    for fn in list(_compile_listeners):
        try:
            fn(key, backend)
        except Exception:  # pragma: no cover - observability never breaks builds
            pass


def record_launch(backend: str | None = None) -> None:
    tag = backend or _UNTAGGED
    with _counter_lock:
        _launch_counts[tag] = _launch_counts.get(tag, 0) + 1


def record_degradation(rung: str, family: str | None = None) -> None:
    """Count one degradation-ladder step (PR 6): ``rung`` is one of
    ``unfused`` / ``backend_failover`` / ``breaker_skip`` / ``eager``.
    Counted here (not in the runtime layer) because the ladder lives in
    the core planner path; ``runtime.stats()["degradations"]`` reads it
    back so silent slow-paths stay observable."""
    with _counter_lock:
        _degradation_counts[rung] = _degradation_counts.get(rung, 0) + 1
        if family:
            k = f"{rung}:{family}"
            _degradation_counts[k] = _degradation_counts.get(k, 0) + 1
    _tl_obs.rung = rung
    obs = _observer
    if obs is not None:
        try:
            obs("degradation", rung=rung, family=family)
        except Exception:  # pragma: no cover - telemetry never breaks serving
            pass


def degradation_counts() -> dict[str, int]:
    """Snapshot of rung -> count (plus ``rung:family`` breakdowns)."""
    with _counter_lock:
        return dict(_degradation_counts)


def degradation_total() -> int:
    """Total ladder steps taken — routers/runtimes snapshot this around
    a timed call so degraded latency never poisons a backend's EMA."""
    with _counter_lock:
        return sum(n for k, n in _degradation_counts.items() if ":" not in k)


def compile_count(backend: str | None = None) -> int:
    """Driver compiles: process total, or one backend's when named."""
    with _counter_lock:
        if backend is not None:
            return _compile_counts.get(backend, 0)
        return sum(_compile_counts.values())


def launch_count(backend: str | None = None) -> int:
    """Driver launches: process total, or one backend's when named."""
    with _counter_lock:
        if backend is not None:
            return _launch_counts.get(backend, 0)
        return sum(_launch_counts.values())


def compile_counts() -> dict[str, int]:
    """Snapshot of the backend tag -> compile count map."""
    with _counter_lock:
        return dict(_compile_counts)


def launch_counts() -> dict[str, int]:
    """Snapshot of the backend tag -> launch count map."""
    with _counter_lock:
        return dict(_launch_counts)


class _LaunchCounter:
    """Context manager over the launch counter: ``delta`` after exit is
    the number of generated-kernel launches inside the block, and
    ``by_backend`` the nonzero per-backend deltas — so a test can assert
    both the schedule length and *which* backend executed it."""

    def __enter__(self):
        self._start = launch_counts()
        self.delta = 0
        self.by_backend: dict[str, int] = {}
        return self

    def __exit__(self, *exc):
        end = launch_counts()
        self.by_backend = {
            k: d for k in end
            if (d := end[k] - self._start.get(k, 0)) > 0}
        self.delta = sum(self.by_backend.values())
        return False


def count_launches() -> _LaunchCounter:
    """``with dispatch.count_launches() as c: ...; c.delta`` — the test/
    benchmark idiom for asserting launch schedules (e.g. fused softmax
    is a reduce + one epilogue: delta == 2).  ``c.by_backend`` breaks
    the delta down per backend tag."""
    return _LaunchCounter()


class _CompileCounter:
    """Context manager over the *compile* counter: ``delta`` after exit
    is the number of driver builds inside the block, ``by_backend`` the
    nonzero per-backend deltas.  The warm-start acceptance gate
    (DESIGN.md §9.3) is ``delta == 0`` around replayed traffic after
    ``runtime.warmup()``."""

    def __enter__(self):
        self._start = compile_counts()
        self.delta = 0
        self.by_backend: dict[str, int] = {}
        return self

    def __exit__(self, *exc):
        end = compile_counts()
        self.by_backend = {
            k: d for k in end
            if (d := end[k] - self._start.get(k, 0)) > 0}
        self.delta = sum(self.by_backend.values())
        return False


def count_compiles() -> _CompileCounter:
    """``with dispatch.count_compiles() as c: ...; c.delta`` — compile-
    side twin of `count_launches`, used by the serving runtime's
    warm-start tests and the CI warmup leg (zero cold-start compiles
    after a manifest replay)."""
    return _CompileCounter()


def reset_counters() -> None:
    """Zero the compile/launch counters (cache contents are kept)."""
    with _counter_lock:
        _compile_counts.clear()
        _launch_counts.clear()
        _degradation_counts.clear()


def clear() -> None:
    """Drop all cached drivers and zero counters (tests/benchmarks)."""
    _driver_cache.clear()
    reset_counters()


def stats() -> dict:
    s = _driver_cache.stats()
    s["compiles"] = compile_count()
    s["launches"] = launch_count()
    s["compiles_by_backend"] = compile_counts()
    s["launches_by_backend"] = launch_counts()
    s["degradations"] = degradation_counts()
    return s


def stats_snapshot() -> dict:
    """JSON-able `stats()` view for cross-process aggregation (PR 8):
    every value is a plain int or a str->int dict, so a fleet worker can
    ship it over a pipe and the dispatcher can `merge_stats` N of them
    into one fleet-level view."""
    return stats()


def merge_stats(snapshots: "list[dict]") -> dict:
    """Fold per-process `stats_snapshot()` dicts into one aggregate:
    counters (hits/misses/evictions/compiles/launches, the by-backend
    and degradation maps) sum across processes; ``size``/``maxsize``
    sum too — the fleet's total cached-driver footprint."""
    out: dict = {}
    for snap in snapshots:
        if not isinstance(snap, dict):
            continue
        for k, v in snap.items():
            if isinstance(v, dict):
                sub = out.setdefault(k, {})
                for kk, vv in v.items():
                    sub[kk] = sub.get(kk, 0) + vv
            elif isinstance(v, (int, float)) and not isinstance(v, bool):
                out[k] = out.get(k, 0) + v
    return out
