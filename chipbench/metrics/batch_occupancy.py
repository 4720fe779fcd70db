"""Mean live slots over capacity per engine step, in %: the rows each step
sampled (one token each) over steps times capacity."""


def read(run):
    steps = len(run.served.step_end)
    if not steps:
        return None
    return 100.0 * run.served.tokens / (steps * run.capacity)
