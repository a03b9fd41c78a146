"""Cache manager: what a decode row attends under the two tables, as a
share of what one table holding every position would make it read: the
window's decode steps' ``(kv_tokens_window + kv_chunks) / kv_tokens``, each
summed over the flight recorder's decode records of the window (the same
numbers the ``executor.dispatch`` spans carry: ``kv_tokens`` a row's true
context in whole blocks, ``kv_tokens_window`` its rows of its own window,
``kv_chunks`` its summaries of closed windows). Nothing where the records
carry no ``kv_chunks`` (a program without composed tables)."""


def read(ctx):
    steps = [s for s in ctx.get("flight") or ()
             if s.get("kind") == "decode" and "kv_chunks" in s
             and s.get("kv_tokens")]
    if not steps:
        return None
    attended = sum(s["kv_tokens_window"] + s["kv_chunks"] for s in steps)
    return 100.0 * attended / sum(s["kv_tokens"] for s in steps)
