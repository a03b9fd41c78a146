"""``prefill_fill_pct.*``'s reader over a recorded run's ``stats()`` pair
and prefill flight records (``prefill_fill_pct_recorded.json``: the 30 s
window of ``mistral7b-chat-saturated``, traced, on one TPU v5 lite in PR
42, once with the program before that PR, which pads every row of a
prefill step to the longest row's bucket and counts no slots, and once
with the program that fills a step by tokens and does; only what the
reader takes is kept)."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "prefill_fill_pct_recorded.json")) as f:
    RECORDED = json.load(f)


def _ctx(side):
    rec = RECORDED[side]
    return {"stats_before": rec["stats_before"],
            "stats_after": rec["stats_after"],
            "flight": [dict(zip(("kind", "bucket_b", "bucket_len", "tokens"),
                                s)) for s in rec["flight"]]}


def _read(ctx, name="prefill_fill_pct.sat"):
    return common.load_layer_metric(name).read(dict(ctx))


def _by_flight(ctx):
    return 100.0 * sum(s["tokens"] for s in ctx["flight"]) / sum(
        s["bucket_b"] * s["bucket_len"] for s in ctx["flight"])


def test_fill_is_the_windows_tokens_over_its_slots():
    ctx = _ctx("change")
    before, after = ctx["stats_before"], ctx["stats_after"]
    tokens = after["prefill_tokens_total"] - before["prefill_tokens_total"]
    slots = after["prefill_slots"] - before["prefill_slots"]
    assert tokens > 500_000 and after["prefill_steps_packed"] == \
        after["prefill_steps"]  # a 30 s window of ~25 prompts a second
    fill = _read(ctx)
    assert fill == pytest.approx(100.0 * tokens / slots)
    assert 85.0 < fill < 92.0
    # every packed step is rows of one q tile
    assert {s["bucket_len"] for s in ctx["flight"]} == {128}
    assert {s["kind"] for s in ctx["flight"]} == {"prefill_chunk"}
    # and the flight records say the same but for the window's edges
    assert _by_flight(ctx) == pytest.approx(fill, abs=0.5)
    # one reader serves both names
    assert _read(ctx, "prefill_fill_pct.steady") == fill


def test_a_program_that_counts_no_slots_is_read_from_its_flight_records():
    ctx = _ctx("parent")
    assert "prefill_slots" not in ctx["stats_after"]
    fill = _read(ctx)
    assert fill == pytest.approx(_by_flight(ctx))
    # ISSUE 42's derivation from the ledger: 1.64 slots a real token
    assert 55.0 < fill < 65.0
    # rows x the longest row's bucket: what the step shapes were
    assert {(s["bucket_b"], s["bucket_len"]) for s in ctx["flight"]} <= {
        (b, n) for b in (1, 2, 4) for n in (512, 1024, 2048)}


@pytest.mark.parametrize("what", [
    "no_stats", "empty_window", "no_prefill_steps", "records_without_shape"])
def test_fill_reads_nothing_where_nothing_was_launched(what):
    if what == "no_stats":
        ctx = {}
    elif what == "empty_window":
        ctx = dict(_ctx("change"))
        ctx["stats_after"] = ctx["stats_before"]
    elif what == "no_prefill_steps":
        ctx = {"stats_before": {}, "stats_after": {"decode_steps": 9},
               "flight": [{"kind": "decode", "batch": 4, "bucket_b": 4,
                           "bucket_len": 64, "tokens": 4}]}
    else:  # a drained prefill record carries no shape
        ctx = {"stats_after": {}, "flight": [{"kind": "prefill", "tokens": 0}]}
    assert _read(ctx) is None


def test_the_counters_are_taken_before_the_records():
    """Both kinds of step count: a packed step's rows x its q tile, a step
    of a request a row its batch bucket x its length bucket."""
    ctx = {
        "stats_before": {"prefill_tokens_total": 100, "prefill_slots": 128},
        "stats_after": {"prefill_tokens_total": 100 + 700 + 300,
                        "prefill_slots": 128 + 8 * 128 + 2 * 512},
        "flight": [{"kind": "prefill_chunk", "bucket_b": 1, "bucket_len": 128,
                    "tokens": 1}],
    }
    assert _read(ctx) == pytest.approx(100.0 * 1000 / 2048)
    del ctx["stats_after"]["prefill_slots"]
    assert _read(ctx) == pytest.approx(100.0 / 128)
