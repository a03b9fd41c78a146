"""Kernels: of the picks the router made in the window's decode steps, the
share that met a ZERO-COMPUTE expert (``moe_zero_picks_decode`` over
``moe_pairs_decode`` of ``engine.stats()``, the window's end less its
start): an expert that returns its input, costs no product and is computed
where the token is. 33.3% under an even router over 512 real + 256 zero
outputs; the rest of a token's 12 picks are real experts, between 0 and
12 of them, which is how the compute a token varies. Nothing where the
program counts no zero picks."""
from benchmark import span_reduce


def zero_pick_pct(zero_picks: float, picks: float) -> float:
    return 100.0 * zero_picks / picks


def read(ctx):
    zero = span_reduce.counter_delta(ctx, "moe_zero_picks_decode")
    picks = span_reduce.counter_delta(ctx, "moe_pairs_decode")
    if zero is None or not picks:
        return None
    return zero_pick_pct(zero, picks)
