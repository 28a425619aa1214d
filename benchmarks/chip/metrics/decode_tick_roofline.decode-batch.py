"""Least time for the decode tick's necessary work (work/decode_tick.py,
at the slot positions of each traced tick) over the device time per call
of the decode program, in percent."""
import numpy as np


def read(ctx):
    if ctx.reduced is None or not ctx.tick_positions:
        return None
    calls, secs = ctx.reduced.program(r"^jit_decode_all$")
    if not calls:
        return None
    work = ctx.work("decode_tick")
    bound = np.mean([work.necessary(ctx.cell.config, pos, ctx.peaks)
                     ["bound_s"] for pos in ctx.tick_positions])
    return 100.0 * bound / (secs / calls)
