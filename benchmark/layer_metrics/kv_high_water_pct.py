"""Cache manager: the most blocks ever allocated at once, as a share of the
usable pool (``num_blocks`` less block 0, the garbage sink)."""


def read(ctx):
    usable = ctx["traffic"]["engine"]["num_blocks"] - 1
    return 100.0 * ctx["stats_after"]["kv_high_water_blocks"] / usable
