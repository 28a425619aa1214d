"""Device time per call of the engine's decode program (jit name
`decode_all`), from the profiler trace of the window's end."""
PROGRAM = r"^jit_decode_all$"


def read(ctx):
    if ctx.reduced is None:
        return None
    calls, secs = ctx.reduced.program(PROGRAM)
    return secs / calls * 1e3 if calls else None
