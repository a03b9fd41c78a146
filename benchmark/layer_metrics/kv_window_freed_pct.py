"""Cache manager: of the blocks the windowed (sliding) groups took in the
window, the share that went back BEHIND the window while their sequence
lived (``kv_window_blocks_freed`` over ``kv_window_blocks_taken`` of
``engine.stats()``, the window's end less its start). The rest went back
with their sequence. 0 where nothing is given back; nothing where the
program has no such counters or the groups took no block."""


def read(ctx):
    after = ctx.get("stats_after") or {}
    before = ctx.get("stats_before") or {}
    if "kv_window_blocks_taken" not in after:
        return None
    taken = after["kv_window_blocks_taken"] - before.get(
        "kv_window_blocks_taken", 0)
    if taken <= 0:
        return None
    freed = after["kv_window_blocks_freed"] - before.get(
        "kv_window_blocks_freed", 0)
    return 100.0 * freed / taken
