"""Request-coalescing executor — micro-batch concurrent single-row work.

The serving problem (DESIGN.md §9.1): request traffic arrives as
*independent* single-row calls — a sampler softmax over one logits row
per request — and the pre-runtime path paid one full generated-kernel
schedule per request: 2 launches each, ``2·K`` for K concurrent
requests.  The PR 3 axis-aware machinery already executes a whole
``(K, N)`` batch in the SAME 2 launches (one row-segmented reduction
wave + one fused 2-D epilogue); what was missing is batching *across
requests*.  This executor closes that gap:

  * `submit` enqueues a row into the micro-batch forming for its
    coalescing key — ``(family, row length, dtype, family params)`` —
    and returns a `RuntimeFuture`;
  * a batch **flushes** when it reaches ``max_batch`` rows or its
    ``window`` (seconds, measured from the batch's first row) expires,
    whichever is first;
  * a flush stacks the rows into one ``(K, N)`` operand and runs the
    family's fused row schedule ONCE through the owning
    `ServingRuntime` (which routes the backend, records telemetry and
    the warm-start manifest), then scatters row results back to their
    futures — K requests, 2 launches.

Coalesce-factor counters (`stats`): ``requests / flushes`` is the
realized micro-batch size; ``launches`` (via `dispatch.count_launches`)
proves the ``2`` vs ``2·K`` claim, and both feed
``benchmarks/bench_serving.py`` rows and the acceptance tests.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable

import jax.numpy as jnp

from repro.core import dispatch
from repro.runtime import observe


class RuntimeFuture:
    """Single-assignment result slot handed back by `submit`.

    First writer wins: once a result or error lands, later writes are
    ignored — so `close()` can fail a stuck request and a late worker
    completion is dropped instead of clobbering the reported error."""

    __slots__ = ("_event", "_value", "_error", "_family", "_n")

    def __init__(self, family: str = "?", n: int = 0):
        self._event = threading.Event()
        self._value: Any = None
        self._error: "BaseException | None" = None
        self._family = family
        self._n = n

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: "float | None" = None) -> Any:
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"runtime request still pending after {timeout}s "
                f"(family={self._family!r}, row_length={self._n})")
        if self._error is not None:
            raise self._error
        return self._value

    def _set(self, value: Any) -> None:
        if self._event.is_set():
            return
        self._value = value
        self._event.set()

    def _set_error(self, exc: BaseException) -> None:
        if self._event.is_set():
            return
        self._error = exc
        self._event.set()


class _Batch:
    __slots__ = ("family", "shared", "deadline", "rows", "posts", "futures",
                 "seqs", "deadlines", "budgets", "submits", "ragged")

    def __init__(self, family: str, shared: dict, deadline: float,
                 ragged: bool = False):
        self.family = family
        self.shared = shared
        self.deadline = deadline
        self.ragged = ragged            # rows may differ in length
        self.rows: list = []
        self.posts: list = []
        self.futures: list[RuntimeFuture] = []
        self.seqs: list[int] = []       # executor-wide request sequence ids
        self.deadlines: list = []       # per-request absolute deadlines
        self.budgets: list = []         # the raw deadline= seconds (report)
        self.submits: list = []         # submit timestamps (elapsed report)

    def absorb(self, other: "_Batch", limit: int) -> int:
        """Move up to ``limit`` queued requests from ``other`` into this
        batch (FIFO) — the flush-window drain.  Returns rows moved."""
        take = max(0, min(limit, len(other.rows)))
        for name in ("rows", "posts", "futures", "seqs", "deadlines",
                     "budgets", "submits"):
            src = getattr(other, name)
            getattr(self, name).extend(src[:take])
            del src[:take]
        return take


class CoalescingExecutor:
    """Micro-batch window over single-row requests (one worker thread).

    ``runtime`` is the owning `ServingRuntime` — flushes call its
    ``_run_batch`` so routing/telemetry/manifest recording ride along.
    ``window`` is the maximum seconds a request waits for co-travellers;
    ``max_batch`` flushes a batch early the moment it fills (the
    benchmarks set ``max_batch=K`` so a K-request wave flushes exactly
    once, with no timing dependence).
    """

    def __init__(self, runtime, window: float = 0.002, max_batch: int = 64):
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self._runtime = runtime
        self.window = float(window)
        self.max_batch = int(max_batch)
        self._cv = threading.Condition()
        self._batches: dict = {}      # coalescing key -> _Batch
        self._inflight = 0
        self._inflight_batches: list = []  # popped but not yet resolved
        self._closed = False
        self._thread: "threading.Thread | None" = None
        self._seq = 0                 # request sequence (fault-probe index)
        # counters (under _cv): the coalesce-factor bookkeeping
        self._requests = 0
        self._flushes = 0
        self._launches = 0
        self._max_coalesce = 0
        self._batch_retries = 0       # flushes that fell back to per-row
        self._isolated_rows = 0       # rows re-run individually
        self._row_retries = 0         # individual row attempts beyond first
        self._row_failures = 0        # futures failed after isolation
        self._window_flushes = 0      # flushed below max_batch (window/close)
        self._full_flushes = 0        # flushed at max_batch
        self._drained_rows = 0        # rows pulled into a due batch at flush

    # -- submission ------------------------------------------------------
    def submit(self, family: str, row, *, shared: "dict | None" = None,
               key_extra: tuple = (), post: "Callable | None" = None,
               deadline: "float | None" = None,
               ragged: bool = False) -> RuntimeFuture:
        """Queue one row for ``family``; rows sharing the coalescing key
        ``(family, len(row), dtype, *key_extra)`` inside one window
        flush as a single ``(K, N)`` schedule.  ``post(row_result)``
        runs on this request's slice of the batch output (the sampler's
        per-request categorical draw).  ``deadline`` (seconds from now)
        bounds this request's share of any per-row retry budget after a
        failed flush — it does not cancel a healthy in-flight batch.

        ``ragged`` drops the row *length* from the coalescing key: any
        mix of lengths forms ONE batch, padded at flush time to the
        longest row and executed through the runtime's ragged kernel
        pair (each row masked to its true length in-kernel); this
        request's future resolves with exactly its own ``len(row)``
        prefix of the output."""
        row = jnp.asarray(row)
        if row.ndim != 1:
            raise ValueError(
                f"submit coalesces single rows; got shape {row.shape} "
                "(batched operands go through the runtime directly)")
        fut = RuntimeFuture(family, int(row.shape[0]))
        lkey = "R" if ragged else int(row.shape[0])
        key = (family, lkey, str(row.dtype)) + tuple(key_extra)
        with self._cv:
            if self._closed:
                raise RuntimeError("executor is closed")
            batch = self._batches.get(key)
            if batch is None:
                batch = self._batches[key] = _Batch(
                    family, dict(shared or {}),
                    time.monotonic() + self.window, ragged=ragged)
            batch.rows.append(row)
            batch.posts.append(post)
            batch.futures.append(fut)
            batch.seqs.append(self._seq)
            now = time.monotonic()
            batch.deadlines.append(
                None if deadline is None else now + deadline)
            batch.budgets.append(deadline)
            batch.submits.append(now)
            self._seq += 1
            self._requests += 1
            self._ensure_thread()
            self._cv.notify_all()
        return fut

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._loop, name="repro-runtime-flusher", daemon=True)
            self._thread.start()

    # -- the flush loop --------------------------------------------------
    def _due(self, now: float) -> list:
        return [k for k, b in self._batches.items()
                if self._closed or b.deadline <= now
                or len(b.rows) >= self.max_batch]

    def _loop(self) -> None:
        while True:
            with self._cv:
                now = time.monotonic()
                due = self._due(now)
                if not due:
                    if self._closed:
                        return
                    timeout = None
                    if self._batches:
                        timeout = max(0.0, min(
                            b.deadline for b in self._batches.values()) - now)
                    self._cv.wait(timeout)
                    continue
                batches = [(k, self._batches.pop(k)) for k in due]
                self._inflight += len(batches)
                self._inflight_batches.extend(b for _, b in batches)
            try:
                for k, b in batches:
                    self._drain_into(k, b)
                    self._flush_batch(b)
            finally:
                with self._cv:
                    self._inflight -= len(batches)
                    for _, b in batches:
                        try:
                            self._inflight_batches.remove(b)
                        except ValueError:
                            pass
                    self._cv.notify_all()

    def _drain_into(self, key, batch: _Batch) -> None:
        """Flush-window fix: between this batch going due and its flush
        actually starting (earlier batches in the same due wave flush
        first), same-key rows keep arriving and used to wait out a whole
        fresh window.  Pull them into the due batch up to ``max_batch``
        so a continuous request stream rides the earliest flush."""
        with self._cv:
            queued = self._batches.get(key)
            if queued is None:
                return
            moved = batch.absorb(queued, self.max_batch - len(batch.rows))
            self._drained_rows += moved
            if not queued.rows:
                del self._batches[key]

    def _flush_batch(self, batch: _Batch) -> None:
        # telemetry (PR 10): the flush span is opened on this worker
        # thread BEFORE _run_batch so the runtime's "serve" span — and
        # the plan/launch spans below it — parent under this flush;
        # per-request spans are reconstructed post-hoc from the batch's
        # recorded submit timestamps (zero bookkeeping on submit).  It
        # closes exactly once, in the finally, whatever the flush raises:
        # with a profiler session on it is an annotation that must end
        # on this thread.
        ftok = observe.span_begin("flush", "executor")
        t_flush = time.monotonic()
        isolated = False
        try:
            try:
                self._probe_rows(batch)  # injected poison fails here
                lens = None
                if batch.ragged:
                    lens = [int(r.shape[0]) for r in batch.rows]
                    width = max(lens)
                    X = jnp.stack([
                        r if int(r.shape[0]) == width
                        else jnp.pad(r, (0, width - int(r.shape[0])))
                        for r in batch.rows])
                else:
                    X = jnp.stack(batch.rows)
                with dispatch.count_launches() as c:
                    out = self._runtime._run_batch(batch.family, X,
                                                   batch.shared,
                                                   row_lens=lens)
                with self._cv:
                    self._flushes += 1
                    if len(batch.rows) >= self.max_batch:
                        self._full_flushes += 1
                    else:
                        self._window_flushes += 1
                    self._launches += c.delta
                    self._max_coalesce = max(self._max_coalesce,
                                             len(batch.rows))
            except BaseException as e:  # noqa: BLE001 - batch failed
                # Poison-request isolation (DESIGN.md §10): one bad
                # request must not take down its K-1 co-travellers, so
                # the batch falls back to bounded per-row retries (whose
                # serve spans still parent under this flush span).
                isolated = True
                self._retry_rows(batch, e)
            else:
                t_out = time.monotonic()
                # scatter results; a failing per-request post step (e.g.
                # a bad sampler key) fails ONLY its own future, never
                # co-batched ones.  Ragged rows resolve with their true-
                # length prefix (the padding columns are masked to zero
                # in-kernel and carry no information).
                rtok = observe.span_begin("reply", "executor")
                try:
                    for i, (fut, post) in enumerate(zip(batch.futures,
                                                        batch.posts)):
                        try:
                            row_out = out[i] if lens is None \
                                else out[i][:lens[i]]
                            fut._set(post(row_out) if post is not None
                                     else row_out)
                        except BaseException as e:  # noqa: BLE001
                            fut._set_error(e)
                finally:
                    observe.span_end(rtok)
        finally:
            args = None
            if ftok is not None:
                args = {"family": batch.family, "rows": len(batch.rows)}
                if isolated:
                    args["isolated"] = True
            flush_sid = observe.span_end(ftok, args=args)
        self._note_flush(batch, t_flush)
        if flush_sid is not None and not isolated:
            self._record_request_spans(batch, t_flush, t_out, flush_sid)

    def _note_flush(self, batch: _Batch, t_flush: float) -> None:
        """Counters-mode flush telemetry: each request's queue wait
        (submit -> flush start) and the realized batch occupancy."""
        if not observe._MODE:
            return
        for t_sub in batch.submits:
            observe.observe_hist("queue_wait_seconds", (batch.family,),
                                 max(0.0, t_flush - t_sub))
        observe.observe_hist("flush_rows", (batch.family,),
                             float(len(batch.rows)))

    def _record_request_spans(self, batch: _Batch, t_flush: float,
                              t_out: float, flush_sid: int) -> None:
        """Spans-mode per-request reconstruction: one ``request`` root
        per row spanning submit -> reply, with ``admit``/``queue``/
        ``reply`` children; the root's ``flush`` arg names the shared
        flush span (which parents the serve/plan/launch spans), joining
        each request's timeline to the coalesced work that served it."""
        t_end = time.monotonic()
        rec = observe.RECORDER
        for i in range(len(batch.futures)):
            t_sub = batch.submits[i]
            rid = rec.add("request", "request", t_sub, t_end,
                          args={"family": batch.family,
                                "seq": batch.seqs[i], "flush": flush_sid})
            rec.add("admit", "request", t_sub, t_sub, parent=rid)
            rec.add("queue", "request", t_sub, t_flush, parent=rid)
            rec.add("reply", "request", t_out, t_end, parent=rid)

    def _probe_rows(self, batch: _Batch) -> None:
        """Fault-injection probe at the ``executor.row`` site, once per
        request in the batch (``index`` = the request's submit sequence
        number) — how tests plant a deterministic poison request."""
        from repro.runtime import faults

        for seq in batch.seqs:
            faults.maybe_fail("executor.row", family=batch.family, index=seq)

    def _deadline_error(self, batch: _Batch, i: int) -> TimeoutError:
        """Elapsed-vs-budget timeout report: the deadline bounds the
        request's TOTAL time since submit — flush wait + failed-flush
        time + every retry backoff — not just the retry loop."""
        elapsed = time.monotonic() - batch.submits[i]
        return TimeoutError(
            f"request deadline exceeded: {elapsed:.3f}s elapsed of "
            f"{batch.budgets[i]:.3f}s budget (family={batch.family!r}, "
            f"row_length={int(batch.rows[i].shape[0])})")

    def _retry_rows(self, batch: _Batch, batch_err: BaseException) -> None:
        """Re-run a failed flush one row at a time: ``retry_max`` + 1
        attempts per row with exponential backoff, each row's TOTAL
        budget (from submit) bounded by its own deadline — backoff
        sleeps are clipped so they can never overshoot it.  A row that
        never succeeds fails only its own future (seeded with the batch
        error if nothing more specific happened)."""
        from repro.runtime import faults

        with self._cv:
            self._batch_retries += 1
        attempts = dispatch.retry_max() + 1
        for i, fut in enumerate(batch.futures):
            if fut.done():
                continue
            with self._cv:
                self._isolated_rows += 1
            seq, dl, post = batch.seqs[i], batch.deadlines[i], batch.posts[i]
            last: BaseException = batch_err
            for k in range(attempts):
                now = time.monotonic()
                if dl is not None and now >= dl:
                    last = self._deadline_error(batch, i)
                    break
                if k:
                    with self._cv:
                        self._row_retries += 1
                    delay = min(0.0005 * (2 ** k), 0.05)
                    if dl is not None:
                        # never sleep past the deadline: the remaining
                        # budget caps the backoff, and an exhausted
                        # budget times out instead of attempting late
                        delay = min(delay, max(0.0, dl - now))
                    time.sleep(delay)
                    if dl is not None and time.monotonic() >= dl:
                        last = self._deadline_error(batch, i)
                        break
                try:
                    faults.maybe_fail("executor.row", family=batch.family,
                                      index=seq)
                    row = batch.rows[i].reshape(1, -1)
                    # a lone ragged row needs no padding: its true
                    # length IS the operand width
                    lens = [int(row.shape[-1])] if batch.ragged else None
                    with dispatch.count_launches() as c:
                        out = self._runtime._run_batch(
                            batch.family, row, batch.shared, row_lens=lens)
                    with self._cv:
                        self._launches += c.delta
                    fut._set(post(out[0]) if post is not None else out[0])
                    break
                except BaseException as e:  # noqa: BLE001
                    last = e
            if not fut.done():
                with self._cv:
                    self._row_failures += 1
                fut._set_error(last)

    # -- control ---------------------------------------------------------
    def flush(self, wait: bool = True, timeout: float = 30.0) -> None:
        """Force every forming batch to flush now; with ``wait`` block
        until the queue and in-flight work drain."""
        deadline = time.monotonic() + timeout
        with self._cv:
            for b in self._batches.values():
                b.deadline = 0.0
            if self._batches:
                self._ensure_thread()
            self._cv.notify_all()
            while wait and (self._batches or self._inflight):
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError("executor flush timed out")
                self._cv.wait(min(remaining, 0.1))

    def close(self, timeout: float = 30.0, drain: bool = True) -> None:
        """Stop the worker; no future is ever left unset.  With
        ``drain`` (default) queued batches still flush first; with
        ``drain=False`` they are failed immediately.  Whatever remains
        pending after ``timeout`` — including rows of a flush stuck
        inside a wedged backend — fails with
        ``RuntimeError("executor closed")`` (futures are first-writer-
        wins, so a late worker completion is dropped harmlessly)."""
        undrained: list = []
        with self._cv:
            self._closed = True
            if not drain:
                undrained = list(self._batches.values())
                self._batches.clear()
            self._cv.notify_all()
            thread = self._thread
        for b in undrained:
            for fut in b.futures:
                fut._set_error(RuntimeError("executor closed"))
        if thread is not None and thread.is_alive():
            thread.join(timeout=timeout)
        with self._cv:
            leftovers = list(self._batches.values()) + \
                list(self._inflight_batches)
            self._batches.clear()
        for b in leftovers:
            for fut in b.futures:
                fut._set_error(RuntimeError("executor closed"))

    def stats(self) -> dict:
        """Coalesce-factor counters: K requests per flush at 2 launches
        each is the whole value proposition, so it is measured.  The
        retry block reports the poison-isolation path (PR 6)."""
        with self._cv:
            return {
                "requests": self._requests,
                "flushes": self._flushes,
                "launches": self._launches,
                "pending": sum(len(b.rows) for b in self._batches.values()),
                "max_coalesce": self._max_coalesce,
                "coalesce_factor": (self._requests / self._flushes
                                    if self._flushes else 0.0),
                "launches_per_request": (self._launches / self._requests
                                         if self._requests else 0.0),
                "window_s": self.window,
                "max_batch": self.max_batch,
                "window_flushes": self._window_flushes,
                "full_flushes": self._full_flushes,
                "drained_rows": self._drained_rows,
                "batch_retries": self._batch_retries,
                "isolated_rows": self._isolated_rows,
                "row_retries": self._row_retries,
                "row_failures": self._row_failures,
            }
