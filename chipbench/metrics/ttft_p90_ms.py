"""Time to first token, 90th percentile over every request of the window:
from its submission (host clock, between two engine steps) to the end of
the step that produced its first token."""


def read(run):
    from chipbench.harness import percentile

    t = run.served.step_end
    v = [(t[r.admit_step] - r.t_submit) * 1e3 for r in run.requests
         if r.admit_step >= 0]
    return percentile(v, 90)
