"""Gap between consecutive tokens of a request, 95th percentile over every
gap of every request (host clock at the end of each engine step)."""


def read(run):
    from chipbench.harness import percentile

    v = run.token_gaps_s()
    return None if not v else percentile([g * 1e3 for g in v], 95)
