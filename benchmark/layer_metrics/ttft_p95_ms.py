"""Scheduler: p95 (nearest rank) of the time to first token, timed from
when each request was DUE, over the requests due in the window; a request
with no token counts at the whole time it was watched. A tail over ~135
requests: it swings by 15% between runs of one code (PR 23, chip), which is
why it is recorded here and the median is what a PR is held to."""
from benchmark import stats


def read(ctx):
    sample = stats.due_in_window(ctx["records"], ctx["t0"], ctx["t1"])
    if not sample:
        return None
    return stats.percentile(stats.ttft_ms(sample, ctx["t_seen_until"]), 0.95)
