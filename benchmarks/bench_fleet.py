"""Fleet benchmarks (PR 8) -> BENCH_fleet.json.

The supervised-fleet claims, measured (DESIGN.md §12):

  * **availability under worker kill** — K-request waves served by a
    3-worker fleet with 0 and 1 injected worker deaths (a deterministic
    ``worker.kill`` on the 2nd dispatch group).  Availability must be
    1.0 in BOTH legs (hard-asserted here AND gated zero-tolerance by
    ``run.py --compare``); the rows carry p50 request latency so the
    cost of re-dispatch stays visible across PRs.
  * **crash-safe warm restart** — after serving, every worker is rolled
    (fresh spawn, warm-up from the shared manifest: entries, sequences,
    merged router EMAs) and the SAME traffic replays; the restarted
    incarnations' serving compile count must be exactly 0
    (hard-asserted — the paper's compile-once claim, surviving process
    death).
  * **overload shed** — 2x the admission queue's capacity submitted at
    once against a deliberately slowed single worker: overflow must be
    shed *explicitly* (`FleetOverloadError`), every admitted request
    must still complete (availability of admitted == 1.0), and the shed
    rate is recorded.

``REPRO_FLEET_BACKEND`` pins the worker backend (default ``xla`` —
interpret-mode pallas makes spawn-heavy legs crawl; the CI fleet-smoke
job runs both).
"""

from __future__ import annotations

import os
import tempfile
import threading
import time
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.runtime.fleet import (FleetOverloadError, ServingFleet,
                                 check_one_process_per_chip)
from repro.runtime.supervisor import BackoffPolicy

DEFAULT_SHAPES = ((16, 512),)
WAVES = 2
BACKEND = os.environ.get("REPRO_FLEET_BACKEND", "xla")


def _fresh_fleet(**kw):
    kw.setdefault("workers", 3)
    kw.setdefault("backend", BACKEND)
    kw.setdefault("max_batch", 8)
    kw.setdefault("max_redispatch", 3)
    kw.setdefault("backoff", BackoffPolicy(base=0.01, cap=0.2))
    kw.setdefault("cache_dir",
                  str(Path(tempfile.mkdtemp(prefix="bench-fleet-"))))
    return ServingFleet(**kw)


def _wave(fleet, rows, ref, deadline=120.0):
    """One K-thread wave; each thread times its own request end-to-end
    (submit -> verified result)."""
    K = len(rows)
    ok = [0] * K
    lats = [0.0] * K

    def one(i):
        t0 = time.perf_counter()
        try:
            out = fleet.submit_softmax(rows[i], deadline=deadline).result(
                timeout=deadline + 60)
            np.testing.assert_allclose(np.asarray(out), ref[i], atol=1e-4)
            ok[i] = 1
        except Exception:
            ok[i] = 0
        lats[i] = time.perf_counter() - t0

    threads = [threading.Thread(target=one, args=(i,)) for i in range(K)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return sum(ok), K - sum(ok), lats


def _traffic(K: int, N: int, rng):
    rows = [rng.standard_normal(N).astype(np.float32) for _ in range(K)]
    ref = np.asarray(jax.nn.softmax(jnp.asarray(np.stack(rows)), axis=-1))
    return rows, ref


def _availability_and_restart_legs(K: int, N: int, rng) -> None:
    """kills0 + warm_restart share one fleet (and one manifest)."""
    rows, ref = _traffic(K, N, rng)
    fleet = _fresh_fleet()
    try:
        fleet.wait_ready(timeout=300)
        served = failed = 0
        lats: list = []
        for _ in range(WAVES):
            o, f, ls = _wave(fleet, rows, ref)
            served, failed = served + o, failed + f
            lats.extend(ls)
        availability = served / (served + failed)
        assert availability == 1.0, (
            f"fault-free fleet availability {availability:.3f} "
            f"({failed} failed)")
        emit(f"fleet.k{K}x{N}.kills0", float(np.percentile(lats, 50)),
             f"3 workers; availability {availability:.3f}; "
             f"{served} requests",
             gate=True, availability=availability, requests=served + failed,
             workers=3)

        # crash-safe warm restart: roll every worker, replay the SAME
        # traffic, and demand a compile-free fleet
        fleet.sync_workers()
        fleet.rolling_restart(wait_timeout=300)
        served = failed = 0
        lats = []
        for _ in range(WAVES):
            o, f, ls = _wave(fleet, rows, ref)
            served, failed = served + o, failed + f
            lats.extend(ls)
        availability = served / (served + failed)
        assert availability == 1.0, \
            f"post-restart availability {availability:.3f}"
        compiles = [w.get("serving_compiles")
                    for w in fleet.stats()["workers"]]
        restart_compiles = sum(int(c or 0) for c in compiles)
        # the headline acceptance: a restarted worker warms up from the
        # shared manifest and serves known traffic with ZERO compiles
        assert restart_compiles == 0, (
            f"restarted workers compiled during serving: {compiles}")
        emit(f"fleet.k{K}x{N}.warm_restart", float(np.percentile(lats, 50)),
             f"rolled 3 workers; serving compiles {restart_compiles}; "
             f"availability {availability:.3f}",
             gate=True, availability=availability,
             restart_compiles=restart_compiles)
    finally:
        fleet.close()


def _kill_leg(K: int, N: int, rng) -> None:
    """1 injected worker death mid-traffic (deterministic worker.kill on
    each first-incarnation worker's 2nd group)."""
    rows, ref = _traffic(K, N, rng)
    fleet = _fresh_fleet(
        group_max=1, max_outstanding=1,
        chaos_rules=[{"site": "worker.kill", "index": 2, "times": 1}],
        chaos_incarnations=[1])
    try:
        fleet.wait_ready(timeout=300)
        served = failed = 0
        lats: list = []
        for _ in range(WAVES):
            o, f, ls = _wave(fleet, rows, ref)
            served, failed = served + o, failed + f
            lats.extend(ls)
        availability = served / (served + failed)
        st = fleet.fleet_stats()
        kills = sum(st["deaths"].values())
        assert availability == 1.0, (
            f"availability {availability:.3f} with {kills} worker kills "
            f"({failed}/{served + failed} failed)")
        assert kills >= 1, "kill leg injected no worker death"
        emit(f"fleet.k{K}x{N}.kills1", float(np.percentile(lats, 50)),
             f"{kills} workers killed mid-traffic; availability "
             f"{availability:.3f}; {st['redispatched']} redispatched",
             gate=True, availability=availability, worker_kills=kills,
             redispatched=st["redispatched"])
    finally:
        fleet.close()


def _overload_leg(K: int, N: int, rng) -> None:
    """2x queue capacity at once against one slowed worker: overflow is
    shed explicitly, admitted requests all complete."""
    rows, ref = _traffic(2 * K, N, rng)
    fleet = _fresh_fleet(
        workers=1, queue_depth=K, group_max=1, max_outstanding=1,
        chaos_rules=[{"site": "worker.slow"}],   # every group stalls
        env={"REPRO_CHAOS_SLOW_S": "0.05"})
    try:
        fleet.wait_ready(timeout=300)
        futs = []
        shed = 0
        for r in rows:
            try:
                futs.append((r, fleet.submit_softmax(r, deadline=120)))
            except FleetOverloadError:
                shed += 1
        served = failed = 0
        lats: list = []
        for r, f in futs:
            t0 = time.perf_counter()
            try:
                out = f.result(timeout=180)
                np.testing.assert_allclose(
                    np.asarray(out),
                    np.asarray(jax.nn.softmax(jnp.asarray(r))), atol=1e-4)
                served += 1
            except Exception:
                failed += 1
            lats.append(time.perf_counter() - t0)
        availability = served / max(1, served + failed)
        shed_rate = shed / len(rows)
        assert shed >= 1, "2x overload shed nothing (queue never filled)"
        assert availability == 1.0, (
            f"admitted-request availability {availability:.3f} under "
            f"overload ({failed} failed)")
        assert fleet.fleet_stats()["shed"] == shed
        emit(f"fleet.k{K}x{N}.overload_shed", float(np.percentile(lats, 50)),
             f"2x overload: {shed}/{len(rows)} shed "
             f"({shed_rate:.0%}); admitted availability "
             f"{availability:.3f}",
             gate=True, availability=availability, shed=shed,
             shed_rate=shed_rate, offered=len(rows))
    finally:
        fleet.close()


def run(repeats: int = 3, shapes=DEFAULT_SHAPES) -> None:
    check_one_process_per_chip()
    rng = np.random.default_rng(31)
    for K, N in shapes:
        _availability_and_restart_legs(int(K), int(N), rng)
        _kill_leg(int(K), int(N), rng)
        _overload_leg(int(K), int(N), rng)
