"""Decode step: model FLOPs of the steps run over their device time at
the chip's bf16 peak.  A step's FLOPs are 2 per weight per live row
(every layer and the head, not the embedding gather) plus 4 * heads *
head_dim per attended token per layer (``bench/work.py``), averaged
over the steps the host dispatched while tracing."""


def read(ctx):
    tr, w = ctx["trace"], ctx["work"]
    if not tr["step_runs"] or not w:
        return None
    flops = w["step_flops"] * tr["step_runs"]
    return 100.0 * flops / (tr["step_s"] * ctx["peak"]["bf16_flops_per_s"])
