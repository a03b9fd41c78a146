"""Cache manager: of the rows the window's decode steps held, the share
whose context had passed the sliding window, so whose sliding layers
really slid and whose groups gave blocks back (``decode_rows_past_window``
over ``decode_rows`` of ``engine.stats()``, the window's end less its
start). Whether the traffic works the mechanism at all: under 30 the
window binds for too few rows, and the cell is an expert-layer cell.
Nothing where the program keeps no such counters (a program before them,
or a family without sliding layers)."""


def read(ctx):
    after = ctx.get("stats_after") or {}
    before = ctx.get("stats_before") or {}
    if "decode_rows" not in after:
        return None
    rows = after["decode_rows"] - before.get("decode_rows", 0)
    if rows <= 0:
        return None
    past = after["decode_rows_past_window"] - before.get(
        "decode_rows_past_window", 0)
    return 100.0 * past / rows
