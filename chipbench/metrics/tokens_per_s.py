"""Generated tokens over the whole window, first submission to last
finish."""


def read(run):
    w = run.served.window_s
    return run.served.tokens / w if w > 0 else None
