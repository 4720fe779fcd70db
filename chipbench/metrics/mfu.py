"""Whole step's share of the chip's peak, in %: the model operations of
every prompt token and generated token served (prompts counted at their
own length, decode rows over their own context) over the window times
the peak of the chips used."""

from chipbench import flops


def read(run):
    w = run.served.window_s
    total = sum(flops.prefill_flops(run.model, r.prompt_len)
                for r in run.requests if r.admit_step >= 0)
    total += sum(flops.decode_flops(run.model, c)
                 for c in run.decode_contexts() if c)
    if w <= 0 or total <= 0:
        return None
    return 100.0 * total / (w * run.peaks["bf16_flops_per_s"] * run.chips)
