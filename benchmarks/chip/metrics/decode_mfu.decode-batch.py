"""Model FLOPs per generated token (work/model_flops.decode_flops at the
traced ticks' slot positions) x this run's output tokens/s over the
chip's bf16 peak, in percent."""
import numpy as np


def read(ctx):
    pos = [p for tick in ctx.tick_positions for p in tick]
    rate = ctx.rates.get("output_tokens_per_s")
    if not pos or not rate:
        return None
    work = ctx.work("model_flops")
    per_token = np.mean([work.decode_flops(ctx.cell.config, p) for p in pos])
    return 100.0 * per_token * rate / (ctx.device["count"]
                                       * ctx.peaks["flops_bf16"])
