"""Faults planted in the timed path, under the harness, to show that the
check catches them: a step that returns its cache unchanged, half the
batch left out of the step, a token altered where it is produced.  Used
by the tests at the SMOKE size and by ``calibrate.py --fault`` at a
cell's own size; the benchmark's own runs plant none."""

KINDS = ("state", "half", "token")


def install(kind: str):
    """An ``engine`` hook for ``run_cell`` that plants ``kind``."""
    if kind not in KINDS:
        raise ValueError(f"unknown fault {kind!r}; one of {KINDS}")

    def hook(eng):
        decode, sample = eng._decode, eng._sample_rows
        if kind == "state":
            eng._decode = lambda p, c, t, pos: (decode(p, c, t, pos)[0], c)
        elif kind == "half":
            def half(p, c, t, pos):
                logits, new = decode(p, c, t, pos)
                h = logits.shape[0] // 2
                return logits.at[h:].set(logits[:h]), new
            eng._decode = half
        else:
            def altered(rows, temperature):
                out = sample(rows, temperature)
                return {s: (t + 1) % eng.cfg.vocab_size
                        for s, t in out.items()}
            eng._sample_rows = altered
    return hook
