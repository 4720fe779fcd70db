"""Supervised serving fleet (PR 8): supervisor state machines, worker
process fault tolerance, overload shed, and crash-safe warm restart.

The policy classes (`BackoffPolicy`, `CrashLoopBreaker`) are tested as
pure state machines on an injected clock; the process-level behaviours
(kill → re-dispatch, hang → heartbeat kill, crash-loop → breaker open,
rolling restart → zero-compile warm-up) run real ``spawn`` workers with
deterministic ``worker.*`` fault rules."""

import time

import numpy as np
import pytest

from repro.runtime.fleet import FleetOverloadError, ServingFleet
from repro.runtime.supervisor import BackoffPolicy, CrashLoopBreaker

BACKENDS = ["xla", "pallas"]


def _fleet(tmp_path, **kw):
    kw.setdefault("workers", 2)
    kw.setdefault("backend", "xla")
    kw.setdefault("max_batch", 8)
    kw.setdefault("cache_dir", str(tmp_path / "fleet-cache"))
    kw.setdefault("supervisor_tick", 0.05)
    return ServingFleet(**kw)


def _rows(k=6, n=64, seed=0):
    return np.random.default_rng(seed).standard_normal((k, n)).astype(
        np.float32)


# ---------------------------------------------------------------------------
# policy state machines (no processes, injected clock)
# ---------------------------------------------------------------------------

class TestBackoffPolicy:
    def test_schedule_doubles_to_cap(self):
        p = BackoffPolicy(base=0.05, cap=2.0)
        assert p.schedule(7) == [0.05, 0.1, 0.2, 0.4, 0.8, 1.6, 2.0]
        assert p.delay(100) == 2.0
        assert p.delay(0) == 0.0

    def test_rejects_bad_params(self):
        with pytest.raises(ValueError):
            BackoffPolicy(base=0.0)
        with pytest.raises(ValueError):
            BackoffPolicy(base=1.0, cap=0.5)


class TestCrashLoopBreaker:
    def make(self):
        return CrashLoopBreaker(threshold=3, min_uptime=1.0, cooldown=5.0)

    def test_opens_after_k_rapid_deaths(self):
        b = self.make()
        t = 0.0
        for i in range(2):
            b.record_start(t)
            opened = b.record_death(t + 0.1)  # rapid: uptime < 1.0
            assert opened is False and b.state == "closed"
            assert b.allow_restart(t + 0.2)
            t += 0.2
        b.record_start(t)
        assert b.record_death(t + 0.1) is True  # the 3rd rapid death opens
        assert b.state == "open"
        assert not b.allow_restart(t + 1.0)

    def test_slow_death_resets_rapid_run(self):
        b = self.make()
        for t in (0.0, 10.0):
            b.record_start(t)
            b.record_death(t + 0.1)
        b.record_start(20.0)
        assert b.record_death(25.0) is False  # healthy uptime: run broken
        assert b.rapid_deaths == 0 and b.state == "closed"

    def _drive_open(self, b, t0=0.0):
        t = t0
        for _ in range(b.threshold):
            b.record_start(t)
            b.record_death(t + 0.1)
            t += 0.2
        assert b.state == "open"
        return t

    def test_cooldown_admits_one_halfopen_probe(self):
        b = self.make()
        t = self._drive_open(b)
        assert not b.allow_restart(t + 1.0)       # inside cooldown
        assert b.allow_restart(t + 5.1)           # cooldown over: the probe
        assert b.state == "half_open"
        assert not b.allow_restart(t + 5.2)       # only ONE probe

    def test_probe_recovery_closes(self):
        b = self.make()
        t = self._drive_open(b)
        assert b.allow_restart(t + 5.1)
        b.record_start(t + 5.1)
        b.note_healthy(t + 7.0)
        assert b.state == "closed" and b.rapid_deaths == 0
        assert b.allow_restart(t + 7.1)

    def test_probe_rapid_death_reopens(self):
        b = self.make()
        t = self._drive_open(b)
        assert b.allow_restart(t + 5.1)
        b.record_start(t + 5.1)
        assert b.record_death(t + 5.2) is True
        assert b.state == "open"
        assert not b.allow_restart(t + 6.0)


# ---------------------------------------------------------------------------
# overload control (no worker processes needed: start=False)
# ---------------------------------------------------------------------------

def test_admission_queue_sheds_overflow(tmp_path):
    fleet = _fleet(tmp_path, workers=1, queue_depth=4, start=False)
    rows = _rows(5)
    futs = [fleet.submit_softmax(rows[i]) for i in range(4)]
    with pytest.raises(FleetOverloadError):
        fleet.submit_softmax(rows[4])
    assert fleet.fleet_stats()["shed"] == 1
    fleet.close(timeout=0.5)
    for f in futs:  # shutdown fails queued futures explicitly
        with pytest.raises(RuntimeError, match="fleet closed"):
            f.result(timeout=1)
    with pytest.raises(RuntimeError, match="closed"):
        fleet.submit_softmax(rows[0])


# ---------------------------------------------------------------------------
# live fleets
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fleet_serves_and_merges_stats(tmp_path):
    fleet = _fleet(tmp_path)
    try:
        fleet.wait_ready(timeout=180)
        rows = _rows(10)
        futs = [fleet.submit_softmax(r) for r in rows]
        out = [f.result(timeout=60) for f in futs]
        for o in out:
            assert abs(float(np.sum(o)) - 1.0) < 1e-3
        tok = fleet.submit_sample(rows[0], seed=7).result(timeout=60)
        assert 0 <= int(tok) < rows.shape[1]
        # identical seed => identical draw (hedge/redispatch safety)
        tok2 = fleet.submit_sample(rows[0], seed=7).result(timeout=60)
        assert int(tok) == int(tok2)
        st = fleet.stats()
        assert st["merged"]["workers_merged"] == 2
        assert st["fleet"]["completed"] == st["fleet"]["submitted"]
        assert st["fleet"]["failed"] == 0
        pids = {w.get("pid") for w in st["workers"]}
        assert len(pids) == 2  # genuinely separate processes
    finally:
        fleet.close()


@pytest.mark.slow
@pytest.mark.parametrize("backend", BACKENDS)
def test_worker_kill_redispatches_inflight(tmp_path, backend):
    # every first-incarnation worker dies serving its 2nd group; the
    # supervisor restarts them and the requests finish on survivors /
    # successors within their deadline
    fleet = _fleet(
        tmp_path, backend=backend, max_outstanding=1, max_redispatch=3,
        group_max=1,  # one request per group: the kill lands on group 2
        chaos_rules=[{"site": "worker.kill", "index": 2, "times": 1}],
        chaos_incarnations=[1],
        backoff=BackoffPolicy(base=0.01, cap=0.1))
    try:
        fleet.wait_ready(timeout=180)
        rows = _rows(8)
        futs = [fleet.submit_softmax(r, deadline=120) for r in rows]
        out = [f.result(timeout=120) for f in futs]
        for o in out:
            assert abs(float(np.sum(o)) - 1.0) < 1e-3
        st = fleet.fleet_stats()
        assert st["deaths"].get("crash", 0) >= 1
        assert st["redispatched"] >= 1
        assert st["completed"] == len(rows)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:  # supervisor restarts them
            if all(s["alive"] and s["ready"]
                   for s in fleet.fleet_stats()["slots"]):
                break
            time.sleep(0.1)
        assert all(s["alive"] for s in fleet.fleet_stats()["slots"])
    finally:
        fleet.close()


@pytest.mark.slow
def test_worker_hang_detected_via_heartbeat(tmp_path):
    # first group wedges the handler: heartbeats stop, the supervisor
    # kills the silent worker and the request re-dispatches
    fleet = _fleet(
        tmp_path, hb_interval=0.1, hb_timeout=1.0, max_redispatch=3,
        chaos_rules=[{"site": "worker.hang", "index": 1, "times": 1}],
        chaos_incarnations=[1],
        backoff=BackoffPolicy(base=0.01, cap=0.1))
    try:
        fleet.wait_ready(timeout=180)
        fut = fleet.submit_softmax(_rows(1)[0], deadline=120)
        out = fut.result(timeout=120)
        assert abs(float(np.sum(out)) - 1.0) < 1e-3
        st = fleet.fleet_stats()
        assert st["deaths"].get("hang", 0) >= 1
        assert st["redispatched"] >= 1
    finally:
        fleet.close()


@pytest.mark.slow
def test_startup_crash_loop_opens_breaker(tmp_path):
    # every incarnation dies at the startup probe (index=0): after
    # `threshold` rapid deaths the slot's breaker opens and restarts stop
    fleet = _fleet(
        tmp_path, workers=1, warmup=False,
        chaos_rules=[{"site": "worker.kill", "index": 0}],
        backoff=BackoffPolicy(base=0.01, cap=0.05),
        breaker_factory=lambda: CrashLoopBreaker(
            threshold=3, min_uptime=30.0, cooldown=300.0))
    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            slot = fleet.fleet_stats()["slots"][0]
            if slot["breaker"]["state"] == "open":
                break
            time.sleep(0.1)
        st = fleet.fleet_stats()
        slot = st["slots"][0]
        assert slot["breaker"]["state"] == "open"
        assert slot["breaker"]["total_deaths"] >= 3
        assert st["starts"] >= 3
        starts_at_open = st["starts"]
        time.sleep(0.5)  # breaker open: no further restart attempts
        assert fleet.fleet_stats()["starts"] == starts_at_open
    finally:
        fleet.close(timeout=5)


@pytest.mark.slow
def test_worker_reject_isolates_and_retries(tmp_path):
    # a sick-but-responsive worker error-replies its 1st group: requests
    # re-dispatch (solo) and succeed without any process death
    fleet = _fleet(
        tmp_path, max_redispatch=3,
        chaos_rules=[{"site": "worker.reject", "index": 1, "times": 1}],
        chaos_incarnations=[1])
    try:
        fleet.wait_ready(timeout=180)
        rows = _rows(4)
        futs = [fleet.submit_softmax(r, deadline=120) for r in rows]
        for f in futs:
            assert abs(float(np.sum(f.result(timeout=120))) - 1.0) < 1e-3
        st = fleet.fleet_stats()
        assert st["redispatched"] >= 1
        assert not st["deaths"]
    finally:
        fleet.close()


@pytest.mark.slow
def test_hedging_duplicates_stragglers_harmlessly(tmp_path):
    # a worker.slow straggler trips the hedge timer; the duplicate
    # completion is absorbed by first-writer-wins futures
    fleet = _fleet(
        tmp_path, hedge_after=0.25, max_outstanding=4,
        chaos_rules=[{"site": "worker.slow", "index": 1, "times": 1}],
        chaos_incarnations=[1],
        env={"REPRO_CHAOS_SLOW_S": "2.0"})
    try:
        fleet.wait_ready(timeout=180)
        fut = fleet.submit_softmax(_rows(1)[0])
        out = fut.result(timeout=120)
        assert abs(float(np.sum(out)) - 1.0) < 1e-3
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and \
                fleet.fleet_stats()["hedges"] < 1:
            time.sleep(0.05)
        st = fleet.fleet_stats()
        assert st["hedges"] >= 1
        assert st["failed"] == 0
        # both slow rules are spent: traffic is fast and exactly-once now
        t0 = time.monotonic()
        fleet.submit_softmax(_rows(1, seed=1)[0]).result(timeout=60)
        assert time.monotonic() - t0 < 1.5
    finally:
        fleet.close()


@pytest.mark.slow
def test_graceful_drain_and_rolling_restart_warm(tmp_path):
    # rolling restart rotates every slot with zero crashes; the fresh
    # incarnations warm from the shared manifest and serve the same
    # traffic with ZERO compiles (the crash-safe warm-restart claim)
    fleet = _fleet(tmp_path, max_redispatch=2)
    try:
        fleet.wait_ready(timeout=180)
        rows = _rows(8)
        futs = [fleet.submit_softmax(r) for r in rows]
        futs += [fleet.submit_rmsnorm(rows[0], np.ones(64, np.float32))]
        [f.result(timeout=60) for f in futs]
        fleet.drain(timeout=60)
        fleet.sync_workers()
        rep = fleet.rolling_restart(wait_timeout=180)
        assert rep["rotated"] == 2
        assert rep["incarnations"] == [2, 2]
        futs = [fleet.submit_softmax(r) for r in rows]
        futs += [fleet.submit_rmsnorm(rows[0], np.ones(64, np.float32))]
        [f.result(timeout=60) for f in futs]
        st = fleet.stats()
        assert not st["fleet"]["deaths"], "rolling restart must not crash"
        compiles = [w.get("serving_compiles") for w in st["workers"]]
        assert compiles and all(c == 0 for c in compiles), \
            f"restarted workers must serve compile-free, got {compiles}"
        assert st["fleet"]["failed"] == 0
    finally:
        fleet.close()


def test_fleet_refuses_workers_that_need_a_held_chip(monkeypatch):
    """On a TPU host the parent holds the chip: a fleet whose workers
    would open the same platform fails at once, naming the cause."""
    import repro.core.platform as platform
    from repro.runtime.fleet import check_one_process_per_chip

    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="one process per TPU chip"):
        ServingFleet(workers=2, start=False)
    # workers pinned off the chip are fine
    check_one_process_per_chip(env={"JAX_PLATFORMS": "cpu"})
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    check_one_process_per_chip()
