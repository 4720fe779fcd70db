"""Engine queue wait, 90th percentile over requests: submission to the
start of the step that admitted the request (host clock)."""


def read(run):
    from chipbench.harness import percentile

    t = run.served.step_start
    v = [(t[r.admit_step] - r.t_submit) * 1e3 for r in run.requests
         if r.admit_step >= 0]
    return percentile(v, 90)
