"""Device time of the engine's prefill programs (`prefill_one`, whole
prompts; `prefill_resume`, chunks) per 1024 prompt tokens dispatched
while the profiler ran."""
PROGRAMS = r"^jit_prefill_(one|resume)$"


def read(ctx):
    if ctx.reduced is None or not ctx.chunk_tokens_traced:
        return None
    calls, secs = ctx.reduced.program(PROGRAMS)
    if not calls:
        return None
    return secs * 1e3 / (ctx.chunk_tokens_traced / 1024)
