"""Device time per run of the decode program (the engine's jitted
``decode_step`` lambda), from the trace."""

PROGRAMS = ("jit__lambda",)


def read(run):
    secs, runs = run.program_seconds(PROGRAMS)
    return 1e3 * secs / runs if runs else None
