"""``benchmark/scope_reduce.py`` on a chip trace recorded WITH its programs'
compiled texts (``tiny_scope_tpu.xplane.pb.gz`` and
``tiny_scope_texts.json.gz``, written by ``record_tiny_scope_trace.py`` on a
v5e, PR 50): two hand-stepped engines (a small GQA llama, the tiny
``lfm2_moe``) under one profiler session."""
import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import scope_reduce, trace_reduce  # noqa: E402

TRACE = os.path.join(HERE, "tiny_scope_tpu.xplane.pb.gz")
TEXTS = os.path.join(HERE, "tiny_scope_texts.json.gz")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """``(planes, window, programs)``: the trace's device events, its
    window marks, and every program's scope map from its recorded text."""
    from ray_tpu.serve.llm import obs

    path = tmp_path_factory.mktemp("scope") / "tiny_scope_tpu.xplane.pb"
    with gzip.open(TRACE, "rb") as src, open(path, "wb") as dst:
        dst.write(src.read())
    planes, window = scope_reduce.read_planes(str(path))
    with gzip.open(TEXTS, "rt") as f:
        texts = json.load(f)
    programs = {label: {"name": held["name"],
                        "scopes": obs.scope_map(held["text"])}
                for label, held in texts.items()}
    return planes, window, programs


def _total(out: dict) -> float:
    return sum(s for row in out["by"].values() for s in row.values())


def test_shares_add_up_to_busy_time_and_almost_all_of_it_is_named(recorded):
    from ray_tpu.serve.llm import obs

    planes, window, programs = recorded
    assert len(planes) == 1 and len(programs) == 10
    out = scope_reduce.attribute(planes, window, programs)
    # self times inside the marks are the busy time, as trace_reduce has it
    reduced = trace_reduce.reduce_planes(planes, window)
    assert out["busy_s"] == pytest.approx(reduced["busy_s"], rel=1e-12)
    assert _total(out) == pytest.approx(out["busy_s"], rel=1e-6)
    assert out["unmatched"] == {}
    # every module run found the ONE text of its program: eight ran
    assert len(out["programs"]) == 8
    for module, label in out["programs"].items():
        assert label.startswith(trace_reduce._ID.sub("", module) + " ")
    assert set(out["runs"]) == {"prefill", "decode", "other"}
    assert out["runs"]["prefill"] == 4 and out["runs"]["decode"] == 18
    ctx = {"scope_table": out}
    named = scope_reduce.named_pct(ctx)
    assert 99.0 < named < 100.0
    shares = {g: scope_reduce.share_pct(ctx, g) or 0.0
              for g in scope_reduce.GROUPS if g != "matmul"}
    assert sum(shares.values()) + (100.0 - named) == pytest.approx(100.0,
                                                                   abs=1e-6)
    # the groups are a partition of the vocabulary
    held = [s for g, scopes in scope_reduce.GROUPS.items() if g != "matmul"
            for s in scopes]
    assert sorted(held) == sorted(obs.SCOPES)
    # both families' parts are there: kernels, experts, the conv
    for scope in ("attn_kernel", "attn_cache", "moe_gmm", "moe_move",
                  "short_conv", "layer_stack", "head"):
        assert scope_reduce.seconds(out, (scope,)) > 0.0, scope
    # of the prefill programs alone
    prefill = sum(out["by"]["prefill"].values())
    assert scope_reduce.share_pct(ctx, "matmul", kind="prefill") \
        == pytest.approx(100.0 * scope_reduce.seconds(
            out, ("attn_proj", "ffn"), "prefill") / prefill)
    assert 0.0 < scope_reduce.share_pct(ctx, "experts", kind="prefill") < 50.0


def test_a_window_cut_run_counts_to_busy_time_only(recorded):
    planes, window, programs = recorded
    whole = scope_reduce.attribute(planes, window, programs)
    # close the window in the middle of the last decode run
    runs = sorted((m for m in planes[0]["modules"]
                   if "_decode_step" in m[0]), key=lambda m: m[1])
    name, s, e = runs[-1]
    cut = scope_reduce.attribute(planes, (window[0], 0.5 * (s + e)), programs)
    assert cut["runs"]["decode"] == whole["runs"]["decode"] - 1
    assert cut["runs"]["prefill"] == whole["runs"]["prefill"]
    # ... and its part inside the window is booked, to its own program
    assert _total(cut) == pytest.approx(cut["busy_s"], rel=1e-6)
    before = scope_reduce.attribute(planes, (window[0], s), programs)
    assert before["busy_s"] < cut["busy_s"] < whole["busy_s"]
    assert sum(cut["by"]["decode"].values()) \
        > sum(before["by"]["decode"].values())
    assert name in cut["programs"]


def test_an_unmatched_module_run_is_said_and_not_guessed(recorded, capsys):
    planes, window, programs = recorded
    whole = scope_reduce.attribute(planes, window, programs)
    module, label = next((m, lb) for m, lb in whole["programs"].items()
                         if "lfm2_moe_decode" in m)
    # without its text, the module's time is UNMATCHED, whatever the texts
    # of the same program at other shapes would say of such names
    fewer = {lb: p for lb, p in programs.items() if lb != label}
    out = scope_reduce.attribute(planes, window, fewer)
    assert set(out["unmatched"]) == {module}
    assert "no text of" in out["unmatched"][module]
    assert module not in out["programs"]
    lost = scope_reduce.seconds(out, (scope_reduce.UNMATCHED,))
    assert lost > 0.0
    assert _total(out) == pytest.approx(out["busy_s"], rel=1e-6)
    # what was named of it is named no more (a little of it never was)
    named, was = (scope_reduce.named_pct({"scope_table": t})
                  for t in (out, whole))
    assert was - 100.0 * lost / out["busy_s"] <= named + 1e-9 < was - 10.0
    out["map_s"] = {}
    scope_reduce.say_table(out)
    assert f"UNMATCHED {module}" in capsys.readouterr().out
    # under the floor no share by part is reported at all
    holes = scope_reduce.attribute(planes, window, {})
    assert scope_reduce.named_pct({"scope_table": holes}) == 0.0
    assert scope_reduce.share_pct({"scope_table": holes}, "attn") is None
    # two texts that hold a module's events and disagree on one: no guess
    events = {("fusion.1", "f32[4]")}
    a = {"fusion.1": ("ffn", "f32[4]", False)}
    b = {"fusion.1": ("head", "f32[4]", False)}
    assert scope_reduce.match_program(events, [("a", a), ("b", a)])[0] == "a"
    label, why = scope_reduce.match_program(events, [("a", a), ("b", b)])
    assert label is None and "disagree" in why
    assert scope_reduce.match_program(
        events, [("a", {"fusion.1": ("ffn", "f32[8]", False)})])[0] is None


def test_readers_leave_the_metric_out_where_there_is_nothing_to_read():
    """A checkout from before PR 50 (no record of programs), a rehearsal (no
    chip in the trace), an untraced run: every new reader returns None and
    raises nothing."""
    from benchmark import common

    for ctx in ({}, {"trace_run": None, "stats_before": {"waiting": 0},
                     "stats_after": {"waiting": 0}}):
        for name in ("scope_named_pct.sat", "scope_pct.attn.sat",
                     "scope_pct.head.steady", "prefill_scope_pct.matmul.sat",
                     "prefill_scope_pct.experts.sat", "setup_first_calls_s",
                     "setup_programs", "first_calls_in_window.sat"):
            assert common.load_layer_metric(name).read(dict(ctx)) is None
    programs = {"decode:4:4x8": {"name": "jit_x_decode_step",
                                 "first_call_s": 1.5, "calls": 2},
                "prefill:1x64:1x4": {"name": "jit_x_prefill",
                                     "first_call_s": 2.0, "calls": 1}}
    later = dict(programs, **{"decode:8:8x8": {
        "name": "jit_x_decode_step", "first_call_s": 0.25, "calls": 1}})
    ctx = {"stats_before": {"programs": programs},
           "stats_after": {"programs": later}}
    read = lambda name: common.load_layer_metric(name).read(ctx)  # noqa: E731
    assert read("setup_first_calls_s") == 3.5
    assert read("setup_programs") == 2
    assert read("first_calls_in_window.sat") == 1
    ctx["stats_after"] = {"programs": programs}
    assert read("first_calls_in_window.steady") == 0
