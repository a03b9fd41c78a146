"""``emit_row_us.sat``'s reader over a recorded run's ``stats()`` pair
(``emit_row_us_recorded.json``: ``engine.stats()`` at the two ends of the
30 s window of ``gpt2-serve-chat-saturated``, traced, on one TPU v5 lite in
PR 40, once with the program before that PR, which does not count the rows
its emit passes put on streams, and once with the program that does; only
the counters the reader takes are kept)."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "emit_row_us_recorded.json")) as f:
    RECORDED = json.load(f)
EMIT = "engine.emit"


def _read(ctx):
    return common.load_layer_metric("emit_row_us.sat").read(dict(ctx))


def _emit_seconds(ctx):
    before, after = ctx["stats_before"]["phases"], ctx["stats_after"]["phases"]
    return sum(table[EMIT][1] - before.get(kind, {}).get(EMIT, [0, 0.0])[1]
               for kind, table in after.items() if EMIT in table)


def test_emit_row_us_is_the_windows_emit_seconds_a_row():
    ctx = RECORDED["change"]
    rows = (ctx["stats_after"]["host"]["emit_rows"]
            - ctx["stats_before"]["host"]["emit_rows"])
    assert rows > 100_000  # a 30 s window of a cell that completes ~8k a s
    # decode steps and both prefill kinds emit in this cell
    assert {k for k, t in ctx["stats_after"]["phases"].items()
            if EMIT in t} >= {"decode", "prefill"}
    us = _read(ctx)
    assert us == pytest.approx(1e6 * _emit_seconds(ctx) / rows)
    assert 1.0 < us < 40.0


@pytest.mark.parametrize("what", ["parent", "no_host", "empty_window"])
def test_emit_row_us_reads_nothing_where_nothing_is_counted(what):
    if what == "parent":
        # the program before PR 40: the phases are there, the counter not
        ctx = RECORDED["parent"]
        assert "emit_rows" not in ctx["stats_after"]["host"]
        assert _emit_seconds(ctx) > 0
    elif what == "no_host":
        ctx = {"stats_before": {"phases": {}},
               "stats_after": {"phases": {"decode": {EMIT: [9, 0.01]}}}}
    else:
        ctx = dict(RECORDED["change"],
                   stats_after=RECORDED["change"]["stats_before"])
    assert _read(ctx) is None


def test_emit_row_us_counts_every_kind_that_emits():
    """A kind that appears only inside the window (no entry at its start)
    and a kind with no emit phase (``none``) are both taken as they are."""
    ctx = {
        "stats_before": {"phases": {"decode": {EMIT: [10, 1.0]}},
                         "host": {"emit_rows": 100}},
        "stats_after": {"phases": {"decode": {EMIT: [30, 1.5]},
                                   "prefill_chunk": {EMIT: [5, 0.25]},
                                   "none": {"engine.wait": [3, 9.0]}},
                        "host": {"emit_rows": 1100}},
    }
    assert _read(ctx) == pytest.approx(1e6 * 0.75 / 1000)
