"""Decode program's share of its roofline, in %: per step, the least time
for the live rows' work (weights once, each row's own cache columns)
over the decode program's device time."""

from chipbench import flops

PROGRAMS = ("jit__lambda",)


def read(run):
    secs, _ = run.program_seconds(PROGRAMS)
    least = sum(flops.least_seconds(flops.decode_flops(run.model, c),
                                    flops.decode_bytes(run.model, c),
                                    run.peaks)
                for c in run.decode_contexts() if c)
    return 100.0 * least / secs if secs > 0 and least > 0 else None
