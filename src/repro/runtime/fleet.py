"""Supervised serving fleet — process-level fault tolerance over the
serving runtime (PR 8; contract in DESIGN.md §12).

Everything below `ServingRuntime` survives *recoverable* failures: the
breaker reroutes a sick backend, the degradation ladder rebuilds a
kernel, the executor isolates a poison row.  None of it survives the
process itself dying — a segfaulting driver, an OOM kill, a wedged
runtime thread.  This module adds that last layer:

  * `ServingFleet` — the front-end dispatcher.  It owns a **bounded
    admission queue** (overflow requests shed immediately with
    `FleetOverloadError` — an explicit rejection under overload beats
    an unbounded latency cliff), coalesces same-key queued requests
    into groups, and fans the groups over N **worker processes**, each
    a full `ServingRuntime` in its own ``spawn``-ed interpreter talking
    over a `multiprocessing.Pipe`.
  * `supervisor.Supervisor` — health-checks workers via heartbeats,
    detects crashes (process death), hangs (heartbeat silence → kill),
    and startup stalls; restarts through `BackoffPolicy` (exponential)
    gated per slot by a `CrashLoopBreaker` (K rapid deaths → open →
    cooldown → half-open probe).
  * **Re-dispatch** — the in-flight requests of a dead worker re-enter
    the queue head and run on survivors, bounded per request by its
    ``deadline`` and a ``max_redispatch`` attempt budget (at-most-once
    beyond that: the future fails explicitly rather than retrying
    forever).  Futures are first-writer-wins, so a hedge or a late
    duplicate completion is harmless.
  * **Hedging** — groups in flight longer than ``hedge_after`` are
    cloned to a second worker; the first answer wins (straggler
    mitigation, exercised by the ``worker.slow`` fault site).
  * **Crash-safe warm restart** — workers are spawned (never forked:
    fork duplicates jax runtime state; spawn proves the cold-start
    claim on a genuinely fresh interpreter) and warm up from the shared
    `WarmStartManifest` before taking traffic: autotune sequences,
    replay entries, and the fleet's merged router EMAs (flock-merged in
    `DiskCache.update`) — so a restarted worker serves its first
    request with zero compiles and routes like its predecessors.

Workers probe the ``worker.*`` fault sites (`faults.worker_fault`) once
at startup (``index=0``) and once per received group (``index`` = the
incarnation's group ordinal, from 1) — so ``REPRO_CHAOS=
worker.kill:0.05`` kills real children probabilistically while tests
plant exact-index deterministic rules via ``chaos_rules``.

Typical use::

    from repro.runtime.fleet import ServingFleet

    fleet = ServingFleet(workers=4, backend="auto")
    fleet.wait_ready()
    futs = [fleet.submit_softmax(row) for row in rows]
    out = [f.result(timeout=30) for f in futs]
    fleet.stats()          # merged fleet-level view (merge_stats)
    fleet.close()
"""

from __future__ import annotations

import itertools
import multiprocessing as mp
import os
import threading
import time
from collections import deque

import numpy as np

from repro.runtime import observe
from repro.runtime.executor import RuntimeFuture
from repro.runtime.supervisor import (BackoffPolicy, CrashLoopBreaker,
                                      Supervisor)


class FleetOverloadError(RuntimeError):
    """Admission queue full: the request was shed, not queued."""


# ---------------------------------------------------------------------------
# worker child process
# ---------------------------------------------------------------------------

def _draw_seeded(probs_row, seed: int) -> int:
    """Deterministic inverse-CDF categorical draw from one probability
    row — seeded with a plain int so a hedged or re-dispatched sampler
    request draws the SAME token on every worker that serves it."""
    cum = np.cumsum(np.asarray(probs_row, np.float64))
    u = float(np.random.default_rng(seed).random()) * cum[-1]
    return min(int(np.searchsorted(cum, u, side="right")),
               int(cum.shape[-1]) - 1)


def _worker_main(conn, config: dict) -> None:
    """Worker process entry (spawn target): build a full
    `ServingRuntime`, warm it from the shared manifest, then serve
    groups off the pipe, interleaving heartbeats.

    Heartbeats are sent from the SAME loop that serves requests — a
    handler that wedges stops the heart, which is exactly what lets the
    supervisor tell "busy" (beating between groups) from "hung"."""
    os.environ.update({k: str(v) for k, v in (config.get("env") or {}).items()})

    import jax.numpy as jnp

    from repro import runtime
    from repro.core import dispatch
    from repro.runtime import faults

    incarnation = int(config.get("incarnation", 1))
    rules = [faults.FaultRule(**dict(r))
             for r in (config.get("chaos_rules") or [])]
    gate = config.get("chaos_incarnations")
    if rules and (gate is None or incarnation in set(gate)):
        faults.FaultPlan(rules, seed=int(config.get("chaos_seed", 0))
                         ).activate()

    rt = runtime.ServingRuntime(
        backend=config.get("backend", "auto"),
        window=float(config.get("window", 0.002)),
        max_batch=int(config.get("max_batch", 64)))
    warm: dict = {}
    if config.get("warmup", True):
        try:
            warm = rt.warmup()
        except Exception as e:  # a corrupt manifest must not crash-loop
            warm = {"error": f"{type(e).__name__}: {e}"}
    compile_baseline = dispatch.compile_count()
    faults.worker_fault(index=0)  # startup probe (traffic-free chaos)
    try:
        conn.send(("ready", os.getpid(), warm))
    except (OSError, EOFError, BrokenPipeError):
        return

    hb_interval = float(config.get("hb_interval", 0.2))
    groups = 0
    stopping = False
    while not stopping:
        try:
            if not conn.poll(hb_interval):
                conn.send(("hb", time.monotonic()))
                continue
            msg = conn.recv()
        except (OSError, EOFError, BrokenPipeError):
            break
        kind = msg[0]
        if kind == "grp":
            _, gid, family, rows, shared, metas = msg
            groups += 1
            # spans-mode: serve_group is the worker-side anchor a
            # dispatcher-side "dispatch" span joins on via the shared
            # gid (monotonic timestamps are system-wide, so the merged
            # trace lines up across pids without clock translation)
            stok = observe.span_begin("serve_group", "fleet")
            try:
                faults.worker_fault(family=family, index=groups)
                out = np.asarray(
                    rt._run_batch(family, jnp.asarray(rows), dict(shared)))
                payload = []
                for i, meta in enumerate(metas):
                    seed = (meta or {}).get("sample_seed")
                    payload.append(_draw_seeded(out[i], int(seed))
                                   if seed is not None else out[i])
                reply = ("res", gid, True, payload)
            except BaseException as e:  # noqa: BLE001 - reply, don't die
                reply = ("res", gid, False, f"{type(e).__name__}: {e}")
            finally:
                if stok is not None:
                    observe.span_end(stok, args={"gid": gid,
                                                 "family": family,
                                                 "rows": len(rows),
                                                 "ok": reply[2]})
            try:
                conn.send(reply)
            except (OSError, EOFError, BrokenPipeError):
                break
        elif kind == "ctl":
            _, cid, op = msg
            try:
                if op == "stats":
                    snap = rt.stats_snapshot()
                    snap["worker"] = {
                        "pid": os.getpid(), "incarnation": incarnation,
                        "groups": groups,
                        "serving_compiles":
                            dispatch.compile_count() - compile_baseline,
                        "warm": warm,
                    }
                    payload = snap
                elif op == "sync":
                    payload = rt.sync_router()
                elif op == "drain":
                    rt.flush()
                    payload = rt.sync_router()
                elif op == "trace":
                    # drain (don't just copy) so a long-lived worker's
                    # ring buffer never re-ships events across exports
                    payload = {"events": observe.RECORDER.drain(),
                               "pid": os.getpid(),
                               "mode": observe.mode()}
                elif op == "stop":
                    payload = {"groups": groups}
                    stopping = True
                else:
                    payload = {"error": f"unknown ctl op {op!r}"}
            except Exception as e:
                payload = {"error": f"{type(e).__name__}: {e}"}
            try:
                conn.send(("ctl_res", cid, payload))
                if stopping:
                    conn.send(("bye",))
            except (OSError, EOFError, BrokenPipeError):
                break
    try:
        rt.close()  # publishes final router telemetry to the manifest
    except Exception:
        pass


# ---------------------------------------------------------------------------
# parent-side bookkeeping
# ---------------------------------------------------------------------------

class _FleetRequest:
    __slots__ = ("fut", "family", "row", "shared", "key", "meta",
                 "deadline_abs", "submitted", "attempts", "in_queue", "solo")

    def __init__(self, fut, family, row, shared, key, meta, deadline_abs):
        self.fut = fut
        self.family = family
        self.row = row
        self.shared = shared
        self.key = key
        self.meta = meta
        self.deadline_abs = deadline_abs
        self.submitted = time.monotonic()
        self.attempts = 0          # dispatch attempts (redispatch budget)
        self.in_queue = False
        self.solo = False          # isolate after a group error reply


class _Group:
    __slots__ = ("gid", "reqs", "worker", "sent_at", "hedged", "is_hedge")

    def __init__(self, gid, reqs, worker, is_hedge=False):
        self.gid = gid
        self.reqs = reqs
        self.worker = worker
        self.sent_at = time.monotonic()
        self.hedged = is_hedge     # hedged groups are never re-hedged
        self.is_hedge = is_hedge


class _WorkerSlot:
    """Parent-side state for one worker position (survives restarts —
    the process and pipe are per-incarnation, the slot is not)."""

    def __init__(self, idx: int, breaker: CrashLoopBreaker):
        self.idx = idx
        self.lock = threading.Lock()
        self.breaker = breaker
        self.proc = None
        self.conn = None
        self.ready = False
        self.warm: dict = {}
        self.started_at = 0.0
        self.last_hb = 0.0
        self.incarnation = 0
        self.deaths = 0            # consecutive (backoff input)
        self.wants_restart = False
        self.restart_at = 0.0
        self.stopping = False      # expected exit in progress
        self.draining = False      # no new assignments (rolling restart)
        self.inflight: dict = {}   # gid -> _Group
        self.ctl_pending: dict = {}  # cid -> RuntimeFuture


def check_one_process_per_chip(env: "dict | None" = None) -> None:
    """Fail fast where fleet workers would need a TPU chip.

    A chip belongs to one process at a time.  Once this process has
    opened JAX on a TPU it holds the chip, and every spawned worker that
    imports JAX for the same platform would fail or hang at start-up.
    Workers pinned off the TPU (``JAX_PLATFORMS`` without ``tpu`` in
    ``env`` or the inherited environment) are fine.  Pinning one worker
    per chip is not implemented."""
    platforms = (env or {}).get("JAX_PLATFORMS",
                                os.environ.get("JAX_PLATFORMS", ""))
    if platforms and "tpu" not in platforms.split(","):
        return
    from repro.core.platform import on_tpu

    if on_tpu():
        raise RuntimeError(
            "ServingFleet needs one process per TPU chip: this process "
            "holds the chip, so worker processes could not open it (they "
            "would fail or hang at start-up).  Serve in-process through "
            "ServingRuntime, or run the workers with JAX_PLATFORMS=cpu.")


class ServingFleet:
    """N supervised `ServingRuntime` worker processes behind one bounded
    admission queue.  See the module docstring for the architecture;
    the knobs:

    ``workers``/``backend``/``window``/``max_batch`` size the fleet and
    configure each worker's runtime.  ``queue_depth`` bounds admission
    (overflow → `FleetOverloadError`).  ``group_max`` caps how many
    same-key queued requests ride one dispatch group;
    ``max_outstanding`` caps groups in flight per worker
    (backpressure).  ``max_redispatch`` bounds how many times a request
    may be re-dispatched after worker deaths/error replies;
    ``hedge_after`` (seconds, ``None`` = off) clones stragglers.
    ``hb_interval``/``hb_timeout``/``start_timeout`` drive health
    checks; ``backoff``/``breaker_factory`` override restart policy.
    ``chaos_rules`` (list of `FaultRule` kwargs) + ``chaos_incarnations``
    arm deterministic per-worker fault plans; ``env``/``cache_dir``
    pin worker environment (the shared manifest root).
    """

    def __init__(self, workers: int = 2, backend: str = "auto",
                 window: float = 0.002, max_batch: int = 16,
                 queue_depth: int = 256, group_max: "int | None" = None,
                 max_outstanding: int = 2, max_redispatch: int = 1,
                 hedge_after: "float | None" = None,
                 hb_interval: float = 0.2, hb_timeout: float = 10.0,
                 start_timeout: float = 120.0,
                 backoff: "BackoffPolicy | None" = None,
                 breaker_factory=None,
                 supervisor_tick: float = 0.05,
                 warmup: bool = True,
                 chaos_rules: "list[dict] | None" = None,
                 chaos_incarnations: "list[int] | None" = None,
                 chaos_seed: int = 0,
                 env: "dict | None" = None,
                 cache_dir: "str | None" = None,
                 start: bool = True):
        if workers < 1:
            raise ValueError("workers must be >= 1")
        check_one_process_per_chip(env)
        self.backend = backend
        self.window = float(window)
        self.max_batch = int(max_batch)
        self.queue_depth = int(queue_depth)
        self.group_max = int(group_max or max_batch)
        self.max_outstanding = int(max_outstanding)
        self.max_redispatch = int(max_redispatch)
        self.hedge_after = hedge_after
        self.hb_interval = float(hb_interval)
        self.hb_timeout = float(hb_timeout)
        self.start_timeout = float(start_timeout)
        self.backoff = backoff if backoff is not None else BackoffPolicy()
        self.warmup_workers = bool(warmup)
        self.chaos_rules = [dict(r) for r in (chaos_rules or [])]
        self.chaos_incarnations = (None if chaos_incarnations is None
                                   else [int(i) for i in chaos_incarnations])
        self.chaos_seed = int(chaos_seed)
        self.env = dict(env or {})
        if cache_dir is not None:
            self.env.setdefault("REPRO_CACHE_DIR", str(cache_dir))

        make_breaker = breaker_factory or CrashLoopBreaker
        self._slots = [_WorkerSlot(i, make_breaker())
                       for i in range(int(workers))]
        self._ctx = mp.get_context("spawn")
        self._cv = threading.Condition()
        self._queue: "deque[_FleetRequest]" = deque()
        self._closing = False
        self._dispatcher: "threading.Thread | None" = None
        self._gid = itertools.count(1)
        self._cid = itertools.count(1)
        self._rr = 0               # round-robin tiebreak cursor
        # counters (under _cv)
        self._submitted = 0
        self._completed = 0
        self._failed = 0
        self._shed = 0
        self._redispatched = 0
        self._redispatch_dropped = 0
        self._hedges = 0
        self._deaths_by_cause: dict = {}
        self._starts = 0
        self.supervisor = Supervisor(self, tick=supervisor_tick)
        if start:
            self.start()

    # -- lifecycle --------------------------------------------------------
    def start(self) -> "ServingFleet":
        for slot in self._slots:
            if slot.proc is None:
                self._start_worker(slot)
        with self._cv:
            if self._dispatcher is None or not self._dispatcher.is_alive():
                self._dispatcher = threading.Thread(
                    target=self._dispatch_loop, name="repro-fleet-dispatch",
                    daemon=True)
                self._dispatcher.start()
        self.supervisor.start()
        return self

    def wait_ready(self, timeout: float = 180.0,
                   count: "int | None" = None) -> list[dict]:
        """Block until ``count`` (default: all) workers are ready;
        returns their warm-start reports."""
        want = len(self._slots) if count is None else int(count)
        deadline = time.monotonic() + timeout
        with self._cv:
            while True:
                ready = [s for s in self._slots if s.ready]
                if len(ready) >= want:
                    return [dict(s.warm) for s in ready]
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"{len(ready)}/{want} workers ready after {timeout}s")
                self._cv.wait(min(remaining, 0.25))

    def _start_worker(self, slot: _WorkerSlot) -> None:
        now = time.monotonic()
        with slot.lock:
            slot.incarnation += 1
            inc = slot.incarnation
            slot.wants_restart = False
        config = {
            "backend": self.backend, "window": self.window,
            "max_batch": self.max_batch, "warmup": self.warmup_workers,
            "hb_interval": self.hb_interval, "incarnation": inc,
            "env": self.env, "chaos_rules": self.chaos_rules,
            "chaos_incarnations": self.chaos_incarnations,
            # distinct stream per (slot, incarnation) so probabilistic
            # rules don't fire in lockstep across the fleet
            "chaos_seed": self.chaos_seed + slot.idx * 1009 + inc,
        }
        parent_conn, child_conn = self._ctx.Pipe()
        proc = self._ctx.Process(
            target=_worker_main, args=(child_conn, config),
            name=f"repro-fleet-w{slot.idx}.{inc}", daemon=True)
        # spawn children inherit os.environ at start(): pin the worker
        # env (cache root, backend, chaos spec) around it, then restore
        saved = {k: os.environ.get(k) for k in self.env}
        os.environ.update({k: str(v) for k, v in self.env.items()})
        try:
            proc.start()
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        child_conn.close()
        with slot.lock:
            slot.proc = proc
            slot.conn = parent_conn
            slot.ready = False
            slot.started_at = now
            slot.last_hb = now
            slot.stopping = False
        slot.breaker.record_start(now)
        with self._cv:
            self._starts += 1
        threading.Thread(target=self._recv_loop,
                         args=(slot, parent_conn, inc),
                         name=f"repro-fleet-recv-w{slot.idx}.{inc}",
                         daemon=True).start()

    def _kill_worker(self, slot: _WorkerSlot) -> None:
        with slot.lock:
            proc = slot.proc
        if proc is not None and proc.is_alive():
            proc.kill()
            proc.join(timeout=5.0)

    def kill_worker(self, idx: int) -> None:
        """Hard-kill one worker process (bench/test hook: an external
        SIGKILL; the supervisor detects, re-dispatches, restarts)."""
        self._kill_worker(self._slots[idx])

    # -- receive path -----------------------------------------------------
    def _recv_loop(self, slot: _WorkerSlot, conn, inc: int) -> None:
        while True:
            try:
                msg = conn.recv()
            except (OSError, EOFError):
                return
            with slot.lock:
                if slot.incarnation != inc:
                    return  # stale pipe of a replaced incarnation
                slot.last_hb = time.monotonic()
            kind = msg[0]
            if kind == "ready":
                with slot.lock:
                    slot.ready = True
                    slot.warm = msg[2] if isinstance(msg[2], dict) else {}
                with self._cv:
                    self._cv.notify_all()
            elif kind == "hb":
                pass
            elif kind == "res":
                _, gid, ok, payload = msg
                with slot.lock:
                    group = slot.inflight.pop(gid, None)
                if group is None:
                    continue
                if ok:
                    done = 0
                    fresh = []
                    for req, val in zip(group.reqs, payload):
                        if not req.fut.done():
                            req.fut._set(val)
                            done += 1
                            fresh.append(req)
                    with self._cv:
                        self._completed += done
                        self._cv.notify_all()
                    if fresh and observe._MODE:
                        self._note_replies(group, fresh)
                else:
                    self._requeue_group(
                        group, RuntimeError(
                            f"worker {slot.idx} rejected group: {payload}"),
                        solo=True)
            elif kind == "ctl_res":
                _, cid, payload = msg
                with slot.lock:
                    fut = slot.ctl_pending.pop(cid, None)
                if fut is not None:
                    fut._set(payload)
            elif kind == "bye":
                return

    def _note_replies(self, group: "_Group", reqs) -> None:
        """Telemetry for requests whose futures this reply just resolved
        (PR 10): an end-to-end latency observation per request labeled
        with the pseudo-backend ``fleet`` (distinct from the worker-side
        per-flush histograms, which carry the real backend tag), and —
        in spans mode — the dispatcher half of each request's timeline:
        admit -> queue -> dispatch(gid) -> reply, where the ``gid`` arg
        joins the worker's ``serve_group`` span across process lines."""
        now = time.monotonic()
        rec = observe.RECORDER
        spans = observe._MODE >= observe.MODE_SPANS
        for req in reqs:
            observe.observe_hist(
                "request_latency_seconds",
                (req.family, "fleet", "-", "none"), now - req.submitted)
            if not spans:
                continue
            rid = rec.add("request", "request", req.submitted, now,
                          args={"family": req.family, "gid": group.gid,
                                "worker": group.worker})
            rec.add("admit", "request", req.submitted, req.submitted,
                    parent=rid)
            rec.add("queue", "request", req.submitted, group.sent_at,
                    parent=rid)
            rec.add("dispatch", "request", group.sent_at, now, parent=rid,
                    args={"gid": group.gid, "worker": group.worker,
                          "hedge": group.is_hedge})
            rec.add("reply", "request", now, now, parent=rid)

    # -- death / redispatch ----------------------------------------------
    def _handle_death(self, slot: _WorkerSlot, cause: str,
                      now: "float | None" = None) -> None:
        now = time.monotonic() if now is None else now
        with slot.lock:
            proc, conn = slot.proc, slot.conn
            if proc is None:
                return
            slot.proc = None
            slot.conn = None
            slot.ready = False
            inflight = list(slot.inflight.values())
            slot.inflight.clear()
            ctl = list(slot.ctl_pending.values())
            slot.ctl_pending.clear()
            graceful = slot.stopping and cause == "stop"
            slot.stopping = False
            slot.draining = False
        try:
            conn.close()
        except Exception:
            pass
        proc.join(timeout=2.0)
        for fut in ctl:
            fut._set_error(RuntimeError(
                f"fleet worker {slot.idx} died ({cause})"))
        if graceful:
            with slot.lock:
                slot.wants_restart = not self._closing
                slot.restart_at = now
        else:
            opened = slot.breaker.record_death(now)
            with slot.lock:
                slot.deaths += 1
                slot.wants_restart = not self._closing
                slot.restart_at = now + self.backoff.delay(slot.deaths)
            with self._cv:
                self._deaths_by_cause[cause] = \
                    self._deaths_by_cause.get(cause, 0) + 1
                if opened:
                    self._deaths_by_cause["breaker_opened"] = \
                        self._deaths_by_cause.get("breaker_opened", 0) + 1
            observe.count("fleet_events_total", f"death:{cause}")
        err = RuntimeError(f"fleet worker {slot.idx} died ({cause})")
        for group in inflight:
            self._requeue_group(group, err)
        with self._cv:
            self._cv.notify_all()

    def _requeue_group(self, group: _Group, err: BaseException,
                       solo: bool = False) -> None:
        """At-most-once-per-budget re-dispatch: each request of a dead
        or rejected group re-enters the queue HEAD (it already waited
        once) unless its deadline passed or its attempt budget
        (1 + ``max_redispatch`` dispatches) is exhausted — those fail
        explicitly, carrying the underlying error."""
        now = time.monotonic()
        with self._cv:
            for req in group.reqs:
                if req.fut.done() or req.in_queue:
                    continue
                if req.deadline_abs is not None and now >= req.deadline_abs:
                    elapsed = now - req.submitted
                    self._redispatch_dropped += 1
                    self._failed += 1
                    req.fut._set_error(TimeoutError(
                        f"request deadline exceeded during re-dispatch: "
                        f"{elapsed:.3f}s elapsed "
                        f"(family={req.family!r}); last error: {err}"))
                    continue
                if req.attempts > self.max_redispatch:
                    self._redispatch_dropped += 1
                    self._failed += 1
                    req.fut._set_error(RuntimeError(
                        f"request failed after {req.attempts} dispatch "
                        f"attempts (max_redispatch={self.max_redispatch}): "
                        f"{err}"))
                    continue
                if solo:
                    req.solo = True
                req.in_queue = True
                self._queue.appendleft(req)
                self._redispatched += 1
                observe.count("fleet_events_total", "redispatch")
            self._cv.notify_all()

    # -- dispatch path ----------------------------------------------------
    def _eligible_slots(self) -> list:
        out = []
        for slot in self._slots:
            with slot.lock:
                if (slot.proc is not None and slot.ready
                        and not slot.stopping and not slot.draining
                        and len(slot.inflight) < self.max_outstanding):
                    out.append((len(slot.inflight), slot))
        return out

    def _pick_slot(self, exclude: "int | None" = None):
        cands = [(n, s) for n, s in self._eligible_slots()
                 if s.idx != exclude]
        if not cands:
            return None
        least = min(n for n, _ in cands)
        tied = [s for n, s in cands if n == least]
        self._rr += 1
        return tied[self._rr % len(tied)]

    def _take_group(self) -> "list[_FleetRequest]":
        """Pop the head request plus up to ``group_max - 1`` same-key
        co-travellers (skipping over other keys, preserving their
        order).  Called under ``_cv``."""
        head = self._queue.popleft()
        head.in_queue = False
        if head.solo:
            return [head]
        reqs = [head]
        if len(self._queue) and self.group_max > 1:
            keep: list = []
            while self._queue and len(reqs) < self.group_max:
                r = self._queue.popleft()
                if r.key == head.key and not r.solo:
                    r.in_queue = False
                    reqs.append(r)
                else:
                    keep.append(r)
            for r in reversed(keep):
                self._queue.appendleft(r)
        return reqs

    def _dispatch_loop(self) -> None:
        while True:
            with self._cv:
                if self._closing and not self._queue:
                    alive = any(s.proc is not None for s in self._slots)
                    if not alive or not self._any_inflight():
                        return
                reqs = None
                slot = None
                if self._queue:
                    slot = self._pick_slot()
                    if slot is not None:
                        reqs = self._take_group()
                if reqs is None:
                    self._cv.wait(0.05)
                    continue
            self._send_group(slot, reqs)

    def _send_group(self, slot: _WorkerSlot, reqs, is_hedge=False) -> bool:
        gid = next(self._gid)
        group = _Group(gid, reqs, slot.idx, is_hedge=is_hedge)
        rows = np.stack([r.row for r in reqs])
        metas = [r.meta for r in reqs]
        family, shared = reqs[0].family, reqs[0].shared
        with slot.lock:
            conn = slot.conn
            if conn is None or slot.stopping:
                conn = None
            else:
                slot.inflight[gid] = group
                if not is_hedge:
                    for r in reqs:
                        r.attempts += 1
        if conn is None:
            if not is_hedge:
                self._requeue_group(group, RuntimeError(
                    f"worker {slot.idx} unavailable at dispatch"))
            return False
        try:
            # send OUTSIDE slot.lock: a full pipe blocks until the
            # worker drains it, and the receiver thread needs the lock
            # to keep heartbeat timestamps fresh meanwhile
            conn.send(("grp", gid, family, rows, shared, metas))
            observe.count("fleet_events_total", "dispatch")
            return True
        except (OSError, ValueError, BrokenPipeError):
            with slot.lock:
                slot.inflight.pop(gid, None)
            # a broken pipe IS a dead worker: mark it down now (the
            # requeued requests must not burn their budget bouncing off
            # this slot until the supervisor's next tick notices)
            self._handle_death(slot, cause="crash")
            if not is_hedge:
                self._requeue_group(group, RuntimeError(
                    f"worker {slot.idx} pipe broke at dispatch"))
            return False

    def _hedge_sweep(self, now: "float | None" = None) -> None:
        """Supervisor-tick hook: clone groups in flight longer than
        ``hedge_after`` to a second worker (once each); first answer
        wins on the shared futures."""
        if self.hedge_after is None:
            return
        now = time.monotonic() if now is None else now
        candidates = []
        for slot in self._slots:
            with slot.lock:
                for group in slot.inflight.values():
                    if (not group.hedged
                            and now - group.sent_at > self.hedge_after
                            and any(not r.fut.done() for r in group.reqs)):
                        group.hedged = True
                        candidates.append(group)
        for group in candidates:
            with self._cv:
                target = self._pick_slot(exclude=group.worker)
            if target is None:
                group.hedged = False  # retry next sweep
                continue
            if self._send_group(target, group.reqs, is_hedge=True):
                with self._cv:
                    self._hedges += 1
                observe.count("fleet_events_total", "hedge")

    def _any_inflight(self) -> bool:
        for slot in self._slots:
            with slot.lock:
                if slot.inflight:
                    return True
        return False

    # -- submission API ---------------------------------------------------
    def _submit(self, family: str, row, shared: dict, key_extra: tuple,
                meta: "dict | None" = None,
                deadline: "float | None" = None) -> RuntimeFuture:
        row = np.asarray(row)
        if row.ndim != 1:
            raise ValueError(
                f"fleet submits coalesce single rows; got shape {row.shape}")
        fut = RuntimeFuture(family, int(row.shape[0]))
        key = (family, int(row.shape[0]), str(row.dtype)) + tuple(key_extra)
        req = _FleetRequest(
            fut, family, row, dict(shared), key, dict(meta or {}),
            None if deadline is None else time.monotonic() + float(deadline))
        with self._cv:
            if self._closing:
                raise RuntimeError("fleet is closed")
            if len(self._queue) >= self.queue_depth:
                self._shed += 1
                observe.count("fleet_events_total", "shed")
                raise FleetOverloadError(
                    f"admission queue full ({self.queue_depth} queued); "
                    f"request shed (overload: reject beats unbounded "
                    f"latency)")
            req.in_queue = True
            self._queue.append(req)
            self._submitted += 1
            self._cv.notify_all()
        return fut

    def submit_softmax(self, row, stable: bool = True,
                       deadline: "float | None" = None) -> RuntimeFuture:
        return self._submit("softmax", row, {"stable": bool(stable)},
                            (bool(stable),), deadline=deadline)

    def submit_rmsnorm(self, row, w, eps: float = 1e-6,
                       deadline: "float | None" = None) -> RuntimeFuture:
        w = np.asarray(w, np.float32)
        return self._submit("rmsnorm", np.asarray(row, np.float32),
                            {"w": w, "eps": float(eps)},
                            (id(w), float(eps)), deadline=deadline)

    def submit_sample(self, logits_row, seed: int,
                      temperature: float = 1.0,
                      deadline: "float | None" = None) -> RuntimeFuture:
        """Sampler request: the row joins the stable-softmax batch
        (temperature folded in at submit); the categorical draw runs
        worker-side, seeded with the caller's plain-int ``seed`` so a
        hedged duplicate draws the identical token."""
        row = np.asarray(logits_row, np.float32) / max(float(temperature),
                                                       1e-8)
        return self._submit("softmax", row, {"stable": True}, (True,),
                            meta={"sample_seed": int(seed)},
                            deadline=deadline)

    # -- control / introspection ------------------------------------------
    def _ctl(self, slot: _WorkerSlot, op: str,
             timeout: float = 15.0):
        cid = next(self._cid)
        fut = RuntimeFuture(f"ctl:{op}", 0)
        with slot.lock:
            conn = slot.conn
            if conn is None:
                raise RuntimeError(f"worker {slot.idx} is down")
            slot.ctl_pending[cid] = fut
        try:
            conn.send(("ctl", cid, op))
        except (OSError, ValueError, BrokenPipeError) as e:
            with slot.lock:
                slot.ctl_pending.pop(cid, None)
            raise RuntimeError(f"worker {slot.idx} pipe broke: {e}") from e
        return fut.result(timeout=timeout)

    def worker_stats(self, timeout: float = 15.0) -> list:
        """One `stats_snapshot` per responsive worker (down workers are
        skipped, not raised)."""
        out = []
        for slot in self._slots:
            try:
                out.append(self._ctl(slot, "stats", timeout=timeout))
            except (RuntimeError, TimeoutError):
                continue
        return out

    def sync_workers(self, timeout: float = 15.0) -> list:
        """Ask every responsive worker to two-way-sync its router
        telemetry with the shared manifest."""
        out = []
        for slot in self._slots:
            try:
                out.append(self._ctl(slot, "sync", timeout=timeout))
            except (RuntimeError, TimeoutError):
                continue
        return out

    def fleet_stats(self) -> dict:
        """Dispatcher-level counters + per-slot supervision state (no
        worker round-trips — always answers, even mid-outage)."""
        with self._cv:
            counters = {
                "workers": len(self._slots),
                "submitted": self._submitted,
                "completed": self._completed,
                "failed": self._failed,
                "shed": self._shed,
                "queued": len(self._queue),
                "queue_depth": self.queue_depth,
                "redispatched": self._redispatched,
                "redispatch_dropped": self._redispatch_dropped,
                "hedges": self._hedges,
                "starts": self._starts,
                "deaths": dict(self._deaths_by_cause),
            }
        slots = []
        for s in self._slots:
            with s.lock:
                slots.append({
                    "idx": s.idx, "alive": s.proc is not None,
                    "ready": s.ready, "incarnation": s.incarnation,
                    "consecutive_deaths": s.deaths,
                    "inflight_groups": len(s.inflight),
                    "draining": s.draining,
                    "breaker": s.breaker.stats(),
                })
        counters["slots"] = slots
        return counters

    def stats(self, timeout: float = 15.0) -> dict:
        """The fleet-level view: dispatcher counters + every responsive
        worker's snapshot merged through `runtime.merge_stats` (satellite
        3: counters sum, latency tables min, shared sizes max).

        PR 10: the dispatcher's own metrics (fleet-labeled end-to-end
        latency, fleet event counters) fold into ``merged["metrics"]``
        via the associative histogram merge, and ``latency`` is the
        cross-worker p50/p95/p99 view per (family, backend) — percentile
        reads off exactly-summed bucket counts, accurate to one bucket
        width."""
        from repro import runtime as _runtime

        snaps = self.worker_stats(timeout=timeout)
        merged = _runtime.merge_stats(snaps)
        merged["metrics"] = observe.merge_metrics(
            merged.get("metrics"), observe.METRICS.snapshot())
        merged["latency"] = observe.latency_summary(merged["metrics"])
        return {"fleet": self.fleet_stats(),
                "merged": merged,
                "latency": merged["latency"],
                "workers": [s.get("worker", {}) for s in snaps]}

    def export_trace(self, path, timeout: float = 15.0) -> int:
        """ONE merged Chrome trace across process lines: every
        responsive worker's recorder is drained over its pipe (the
        ``trace`` control op) and written together with the
        dispatcher's own spans; returns the total event count.
        Monotonic timestamps are system-wide, so worker ``serve_group``
        spans line up against dispatcher ``dispatch`` spans on a shared
        timeline, joined by their ``gid`` args.  Spans of a killed
        worker die with its process — the surviving timeline shows the
        re-dispatch instead, which is the truthful picture."""
        events: list = []
        for slot in self._slots:
            try:
                payload = self._ctl(slot, "trace", timeout=timeout)
                events.extend((payload or {}).get("events") or [])
            except (RuntimeError, TimeoutError):
                continue
        return observe.export_trace(path, events)

    # -- drain / restart / shutdown ---------------------------------------
    def drain(self, timeout: float = 60.0) -> None:
        """Block until the queue and all in-flight groups are resolved
        (admission stays open — this is a quiesce point, not a stop)."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while self._queue or self._any_inflight():
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"fleet drain timed out ({len(self._queue)} queued)")
                self._cv.wait(min(remaining, 0.1))

    def rolling_restart(self, wait_timeout: float = 180.0) -> dict:
        """Zero-downtime restart: one slot at a time — stop assigning,
        wait its in-flight out, sync its router telemetry, stop it
        cleanly (no backoff, no breaker hit), wait for the fresh
        incarnation to come up warm, move on.  Survivors keep serving
        throughout."""
        rotated = []
        for slot in self._slots:
            with slot.lock:
                slot.draining = True
            deadline = time.monotonic() + wait_timeout
            with self._cv:
                while True:
                    with slot.lock:
                        busy = bool(slot.inflight)
                    if not busy:
                        break
                    if time.monotonic() >= deadline:
                        break  # stop anyway; death path re-dispatches
                    self._cv.wait(0.1)
            try:
                self._ctl(slot, "sync", timeout=15.0)
            except (RuntimeError, TimeoutError):
                pass
            with slot.lock:
                prev_inc = slot.incarnation
                slot.stopping = True
            try:
                self._ctl(slot, "stop", timeout=15.0)
            except (RuntimeError, TimeoutError):
                self._kill_worker(slot)
            # supervisor notices the (expected) exit and restarts with
            # no backoff; wait for the FRESH incarnation to warm up
            # (slot.ready alone is not enough — it stays set until the
            # old incarnation's exit is handled)
            t_end = time.monotonic() + wait_timeout
            with self._cv:
                while True:
                    with slot.lock:
                        if slot.incarnation > prev_inc and slot.ready:
                            break
                    if time.monotonic() >= t_end:
                        raise TimeoutError(
                            f"worker {slot.idx} did not come back ready")
                    self._cv.wait(0.25)
            with slot.lock:
                rotated.append(slot.incarnation)
        return {"rotated": len(rotated), "incarnations": rotated}

    def close(self, timeout: float = 30.0) -> None:
        """Graceful shutdown: stop admission, drain what's queued, stop
        workers cleanly (they publish router telemetry on the way out),
        fail anything still unresolved — no future is left hanging."""
        with self._cv:
            if self._closing:
                return
            self._closing = True
            self._cv.notify_all()
        try:
            self.drain(timeout=timeout)
        except TimeoutError:
            pass
        self.supervisor.stop()
        for slot in self._slots:
            with slot.lock:
                slot.stopping = True
                slot.wants_restart = False
                conn = slot.conn
            if conn is not None:
                try:
                    conn.send(("ctl", next(self._cid), "stop"))
                except Exception:
                    pass
        deadline = time.monotonic() + timeout
        for slot in self._slots:
            with slot.lock:
                proc = slot.proc
            if proc is not None:
                proc.join(timeout=max(0.1, deadline - time.monotonic()))
                if proc.is_alive():
                    proc.kill()
                    proc.join(timeout=5.0)
        # fail every unresolved future explicitly
        leftovers: list = []
        with self._cv:
            while self._queue:
                leftovers.append(self._queue.popleft())
        for slot in self._slots:
            with slot.lock:
                groups = list(slot.inflight.values())
                slot.inflight.clear()
                ctl = list(slot.ctl_pending.values())
                slot.ctl_pending.clear()
                slot.proc = None
                slot.conn = None
                slot.ready = False
            for g in groups:
                leftovers.extend(g.reqs)
            for fut in ctl:
                fut._set_error(RuntimeError("fleet closed"))
        for req in leftovers:
            req.fut._set_error(RuntimeError("fleet closed"))
        with self._cv:
            self._cv.notify_all()
        if self._dispatcher is not None:
            self._dispatcher.join(timeout=5.0)

    def __enter__(self) -> "ServingFleet":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
