"""The closed-loop driver that serves every cell.

``sessions`` clients share one seeded request list.  A free session takes
the next request of the list; a session is free again once its request
has finished.  Submission happens between two ``engine.step()`` calls in
this thread, with no think time, so what is submitted, admitted and
counted is a function of the request list alone: nothing here reads the
clock to decide anything.  The clock only stamps what happened.

The window opens at the first submission and closes when the last
request finishes; every request of the list counts as attempted.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field


@dataclass
class Served:
    """What one closed-loop run produced, with the host clock's stamps."""

    requests: list
    step_start: list = field(default_factory=list)
    step_end: list = field(default_factory=list)
    t_open: float = 0.0

    @property
    def t_close(self) -> float:
        return self.step_end[-1] if self.step_end else self.t_open

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def status_counts(self) -> dict:
        out: dict = {}
        for r in self.requests:
            out[r.status] = out.get(r.status, 0) + 1
        return dict(sorted(out.items()))

    @property
    def failed(self) -> int:
        return sum(r.status != "ok" for r in self.requests)

    @property
    def tokens(self) -> int:
        return sum(0 if r.tokens is None else len(r.tokens)
                   for r in self.requests)


def serve(engine, requests: list, sessions: int, temperature: float, *,
          max_steps: int, before_step=None, after_step=None,
          on_submit=None, clock=time.perf_counter) -> Served:
    """Serve ``requests`` through ``engine`` as a closed loop of
    ``sessions`` clients.  ``before_step(k)`` / ``after_step(k)`` run
    around step ``k`` (tests inject delays there); ``on_submit(r)`` runs
    once ``r`` has its engine request id."""
    from repro.runtime.fleet import FleetOverloadError

    out = Served(requests=requests)
    todo = deque(requests)
    waiting: deque = deque()    # submitted, not yet admitted, FIFO
    live: dict = {}             # rid -> request
    seen_done = len(engine.done)

    def submit(session: int) -> None:
        if not todo:
            return
        r = todo.popleft()
        r.session = session
        r.t_submit = clock()
        try:
            r.rid = engine.submit(r.prompt, max_new=r.max_new)
        except FleetOverloadError:     # the engine's bounded queue sheds
            r.status = "shed"
            submit(session)
            return
        waiting.append(r)
        if on_submit is not None:
            on_submit(r)

    out.t_open = clock()
    for s in range(sessions):
        submit(s)
    k = 0
    while (waiting or live) and k < max_steps:
        if before_step is not None:
            before_step(k)
        out.step_start.append(clock())
        engine.step(temperature=temperature)
        out.step_end.append(clock())
        if after_step is not None:
            after_step(k)
        done = engine.done[seen_done:]
        seen_done = len(engine.done)
        finished = {res.request_id for res in done}
        # FIFO admission: the admitted requests are a prefix of `waiting`
        while waiting and (waiting[0].rid in finished
                           or engine.kv.slot_of(waiting[0].rid) is not None):
            r = waiting.popleft()
            r.admit_step = k
            r.admit_pos = int(engine.pos)
            live[r.rid] = r
        for res in done:
            r = live.pop(res.request_id)
            r.finish_step = k
            r.tokens = res.tokens
            r.status = "ok" if len(res.tokens) >= r.max_new else "truncated"
            submit(r.session)
        k += 1
    for r in list(waiting) + list(live.values()) + list(todo):
        r.status = "error"       # never finished within max_steps
    return out
