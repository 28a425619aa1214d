"""Share of slot-ticks that produced a token: decode tokens / (ticks x
slots), from the engine's counters over the window (decode tokens are the
tokens synced less the first tokens synced, which prefill produces)."""


def read(ctx):
    c = ctx.counters
    if not c.get("ticks"):
        return None
    return 100.0 * (c["tokens"] - c["firsts"]) / (c["ticks"] * c["slots"])
