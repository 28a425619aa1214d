"""Model FLOPs per trained token (work/model_flops.train_flops_per_token:
forward and backward, no recompute) x this run's train tokens/s over chips
x the bf16 peak, in percent."""


def read(ctx):
    rate = ctx.rates.get("train_tokens_per_s")
    if not rate:
        return None
    c = ctx.cell.config
    per_token = ctx.work("model_flops").train_flops_per_token(
        c, ctx.cell.traffic["seq"])
    return 100.0 * per_token * rate / (ctx.device["count"]
                                       * ctx.peaks["flops_bf16"])
