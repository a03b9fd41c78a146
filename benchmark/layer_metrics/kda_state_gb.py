"""Cache manager: what the state slots hold on the device beside the pool,
in GB (1e9 bytes): ``state_bytes`` of ``engine.stats()`` at the window's
end, every slot's KDA matrices and convolution rows (and the counters'
few words), whatever the contexts: a KDA family's fixed cost a row. Nothing
where the program reports no such count (a checkout from before PR 52) or
the family keeps no state."""


def read(ctx):
    held = (ctx.get("stats_after") or {}).get("state_bytes")
    return held / 1e9 if held else None
