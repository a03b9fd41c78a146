"""Scheduler: p95 (nearest rank) of the gaps between consecutive tokens at
the client, over the requests due in the window. The gaps are bimodal (a
decode step alone, or a decode step plus other requests' whole prefill),
and the p95 lies on the edge between the modes: it swings by 7% between
runs of one code (PR 23, chip), so it is recorded here, and the median and
the mean gap are what a PR is held to."""
from benchmark import stats


def read(ctx):
    sample = stats.due_in_window(ctx["records"], ctx["t0"], ctx["t1"])
    gaps = stats.gaps_ms(sample)
    return stats.percentile(gaps, 0.95) if gaps else None
