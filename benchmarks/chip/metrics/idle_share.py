"""1 - (union of device-op intervals) / traced window, in percent, from
the profiler trace of the window's end (mean over the cell's chips)."""


def read(ctx):
    if ctx.reduced is None:
        return None
    return 100.0 * ctx.reduced.idle_share
