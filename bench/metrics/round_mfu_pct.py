"""round_mfu_pct: the FLOPs the sub-model needs (``bench.flops.model_flops``:
forward and backward of the windowed projections, the LM head and causal
attention over the window's heads; recompute not counted) of every round
in the window, over window seconds x chips x the chip's bf16 peak.
Layer: the fed round step (``WindowFedAvg.round``, jitted)."""
from bench.flops import model_flops


def read(ctx):
    if not ctx.rounds or ctx.window_s <= 0:
        return None
    work = model_flops(ctx.cell.config, ctx.cell.mix) * ctx.rounds
    return 100.0 * work / (ctx.window_s * ctx.cell.chips
                           * ctx.peaks["bf16_flops_per_s"])
