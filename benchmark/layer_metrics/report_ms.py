"""Trainer: median of the benchmark's own span around ``train.report`` in
the loop (host clock; one report per step)."""
from benchmark import stats


def read(ctx):
    spans = ctx["spans"].get("train.report")
    return stats.percentile(spans, 0.5) * 1e3 if spans else None
