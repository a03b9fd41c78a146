"""``stage_kb.sat``'s reader over a recorded run's ``stats()`` pair
(``stage_kb_recorded.json``: ``engine.stats()`` at the two ends of the 30 s
window of ``gpt2-serve-chat-saturated``, traced, on one TPU v5 lite in PR
38, once with the program before that PR, which filled and moved an
all-ones allow-mask every launch, and once with the mask resting on the
device; only the counters the readers take are kept)."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "stage_kb_recorded.json")) as f:
    RECORDED = json.load(f)
WORDS = 50304 // 32  # GPT-2's padded vocabulary, a bit a token


def _read(name, ctx):
    return common.load_layer_metric(name).read(dict(ctx))


@pytest.mark.parametrize("side", ["parent", "change"])
def test_stage_kb_is_the_windows_bytes_a_launch(side):
    ctx = RECORDED[side]
    before, after = ctx["stats_before"], ctx["stats_after"]
    launches = (after["decode_steps"] - before["decode_steps"]
                + after["prefill_steps"] - before["prefill_steps"])
    moved = after["host"]["stage_bytes"] - before["host"]["stage_bytes"]
    kb = _read("stage_kb.sat", ctx)
    assert kb == pytest.approx(moved / launches / 1024)
    # the same window's arrays a launch, by the reader beside it
    arrays = _read("stage_transfers.sat", ctx)
    if side == "parent":
        # a mask a launch among ~7.7 arrays: a 64-row decode step's is
        # 393 KB, a prefill's rows fewer
        assert 7.0 < arrays < 8.5
        assert 0.6 * 64 * WORDS * 4 / 1024 < kb < 64 * WORDS * 4 / 1024
    else:
        # the mask rests on the device: an array fewer, and what is left
        # is the tables and the [B] arrays
        assert after["host"]["stage_masks"] == 0
        assert 6.0 < arrays < 7.5
        assert 4.0 < kb < 30.0


def test_stage_kb_reads_nothing_where_nothing_is_counted():
    ctx = RECORDED["change"]
    bare = {"stats_before": {"decode_steps": 1, "prefill_steps": 1},
            "stats_after": {"decode_steps": 9, "prefill_steps": 2}}
    assert _read("stage_kb.sat", bare) is None
    # a program that counts the arrays but not yet their bytes
    old = json.loads(json.dumps(ctx))
    for end in ("stats_before", "stats_after"):
        del old[end]["host"]["stage_bytes"]
    assert _read("stage_kb.sat", old) is None
    # an empty window
    still = dict(ctx, stats_after=ctx["stats_before"])
    assert _read("stage_kb.sat", still) is None
