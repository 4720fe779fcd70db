"""Device time of one admission: the admission prefill program and the
scatter into the batch cache, from the trace, per admission."""

PROGRAMS = ("jit_admit_fn", "jit_scatter_fn")


def read(run):
    secs, _ = run.program_seconds(PROGRAMS)
    admitted = sum(r.admit_step >= 0 for r in run.requests)
    return 1e3 * secs / admitted if secs > 0 and admitted else None
