"""Scheduler: host time the emit pass costs a token it puts on a stream,
in us: the seconds of ``engine.emit`` over the step kinds of
``engine.stats()["phases"]`` (decode and the prefill kinds; a step that
ran nothing has no such phase), over ``stats()["host"]["emit_rows"]``, the
tokens those passes put on streams, both as the window's end less its
start. The pass also retires rows and flushes the block quarantine, so a
step's fixed cost is spread over its rows: 64 rows a step read lower than
8. A program that does not count the rows gives None."""
from benchmark import host_reduce, span_reduce

EMIT = "engine.emit"


def read(ctx):
    host = host_reduce.host_delta(ctx)
    if not host or not host.get("emit_rows"):
        return None
    kinds = (ctx.get("stats_after") or {}).get("phases") or {}
    seconds = sum((span_reduce.phase_totals(ctx, kind) or {})
                  .get(EMIT, [0, 0.0])[1] for kind in kinds)
    return 1e6 * seconds / host["emit_rows"]
