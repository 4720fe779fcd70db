"""Admission programs' share of their roofline, in %: the least time the
chip needs for every admitted prompt's own tokens (operations at peak or
bytes at peak bandwidth, whichever is longer) over the admission and
scatter programs' device time."""

from chipbench import flops

PROGRAMS = ("jit_admit_fn", "jit_scatter_fn")


def read(run):
    secs, _ = run.program_seconds(PROGRAMS)
    least = sum(flops.least_seconds(flops.prefill_flops(run.model, r.prompt_len),
                                    flops.prefill_bytes(run.model, r.prompt_len),
                                    run.peaks)
                for r in run.requests if r.admit_step >= 0)
    return 100.0 * least / secs if secs > 0 and least > 0 else None
