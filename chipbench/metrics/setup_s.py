"""Process start to the first submission: imports, device start-up,
weights, engine and runtime, warm-up (compiles or cache reads)."""


def read(run):
    return run.setup_s
