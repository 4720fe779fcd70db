"""Device time of the runtime sampler's programs per engine step: every
program in the window other than the model step's (admission, scatter,
decode), from the trace."""

MODEL_STEP = {"jit_admit_fn", "jit_scatter_fn", "jit__lambda"}


def read(run):
    steps = len(run.served.step_end)
    secs = sum(p["seconds"] for name, p in run.trace["programs"].items()
               if name not in MODEL_STEP)
    return 1e3 * secs / steps if steps and secs > 0 else None
