"""The reduction from trace to metrics: on plain data, and against a small
trace recorded on the chip (``tiny_tpu.xplane.pb``: six runs of a jitted
scan of three matmuls and three of a small reduction on one TPU v5 lite,
about 40 ms, recorded by PR 23 with the window marked as the runners
mark it)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tiny_tpu.xplane.pb")


def test_union_counts_an_overlap_once():
    assert trace_reduce.union_ns([(0, 10), (5, 15), (20, 30), (22, 25)]) == 25
    assert trace_reduce.union_ns([]) == 0


def test_self_time_leaves_out_children():
    # a while loop of 100 holds two fusions of 30 and 20
    events = [("while", 0, 100), ("fusion.1", 10, 40), ("fusion.2", 50, 70),
              ("copy", 120, 130)]
    assert trace_reduce.self_times(events) == {
        "while": [1, 50], "fusion.1": [1, 30], "fusion.2": [1, 20],
        "copy": [1, 10]}


def test_reduce_planes_busy_idle_modules_and_gaps():
    plane = {
        "ops": [("fusion", 0, 40), ("paged_attention", 40, 50),
                ("fusion", 100, 140), ("paged_attention", 140, 150),
                ("fusion", 300, 400)],
        "modules": [("jit_decode_step(11)", 0, 50),
                    ("jit_decode_step(12)", 100, 150),
                    ("jit_prefill(7)", 300, 400),
                    # cut by the window's end: busy, but not in the median
                    ("jit_decode_step(11)", 480, 560)],
    }
    plane["ops"].append(("fusion", 480, 560))
    r = trace_reduce.reduce_planes([plane], (0, 500))
    assert r["window_s"] == pytest.approx(500e-9)
    assert r["busy_s"] == pytest.approx((50 + 50 + 100 + 20) * 1e-9)
    assert r["modules"]["jit_decode_step"]["count"] == 2
    assert r["modules"]["jit_decode_step"]["median_ms"] == pytest.approx(50e-6)
    assert r["modules"]["jit_prefill"]["count"] == 1
    assert r["idle_gaps"] == {
        "after jit_decode_step before jit_decode_step": pytest.approx(50e-9),
        "after jit_decode_step before jit_prefill": pytest.approx(150e-9)}
    assert trace_reduce.module_median_ms(r, "decode_step") == \
        pytest.approx(50e-6)
    assert trace_reduce.module_median_ms(r, "verify") is None
    assert trace_reduce.ops_share_pct(r, "paged_attention") == \
        pytest.approx(100 * 20 / 220)
    b = trace_reduce.breakdown(r)
    assert b["device_ops"][0][0] == "fusion"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_two_chips_are_averaged():
    a = {"ops": [("f", 0, 100)], "modules": []}
    b = {"ops": [("f", 0, 50)], "modules": []}
    r = trace_reduce.reduce_planes([a, b], (0, 100))
    assert r["chips"] == 2 and r["busy_s"] == pytest.approx(75e-9)


def test_nothing_to_read_is_none():
    assert trace_reduce.module_median_ms(None, "decode_step") is None
    assert trace_reduce.ops_share_pct(None, "flash") is None


def test_recorded_tpu_trace():
    r = trace_reduce.reduce_file(RECORDED)
    assert r["chips"] == 1
    # the window is what lies between the two marks, on the trace's clock
    assert r["window_s"] == pytest.approx(0.022769329, abs=1e-9)
    assert r["busy_s"] == pytest.approx(3.9442e-05, abs=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    step, other = r["modules"]["jit_tiny_step"], r["modules"]["jit_tiny_other"]
    assert (step["count"], other["count"]) == (5, 3)
    assert step["median_ms"] == pytest.approx(0.006718, abs=1e-6)
    assert trace_reduce.module_median_ms(r, "tiny_step") == step["median_ms"]
    # operations are named by their HLO text; the scan's matmuls ran three
    # times a step inside the while loop, whose self time is what is left
    fusion = [v for k, v in r["ops"].items() if "convolution_tanh_fusion" in k]
    loop = [v for k, v in r["ops"].items() if k.startswith("%while = ")]
    assert fusion[0]["count"] == 15 and loop[0]["count"] == 5
    assert loop[0]["self_s"] < fusion[0]["self_s"] / 15
    assert sum(v["self_s"] for v in r["ops"].values()) == pytest.approx(
        r["busy_s"], rel=1e-6)
    assert trace_reduce.ops_share_pct(r, "convolution_tanh_fusion") == \
        pytest.approx(100 * 2.2223e-05 / 3.9442e-05, rel=1e-3)
    assert set(r["idle_gaps"]) == {
        "after jit_tiny_step before jit_tiny_other",
        "after jit_tiny_other before jit_tiny_step",
        "after jit_tiny_step before jit_tiny_step"}
    b = trace_reduce.breakdown(r)
    assert b["device_ops"][0][0] == "convolution_tanh_fusion.2 [fusion]"
    assert all(len(name) <= 120 for name, _ in b["device_ops"])


def test_a_trace_with_no_device_plane_reduces_to_nothing(tmp_path):
    # the CPU backend writes no device plane: a run that finds no
    # accelerator has no device numbers
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(str(tmp_path))
    assert path is not None
    assert trace_reduce.reduce_file(path) is None


def test_unnamed_programs_are_named_by_their_dispatch():
    # decode (id 1) runs most; a prefill (ids 2, 3) is always followed by a
    # decode. The first run's dispatch lies before the trace and the last
    # dispatch's run after it, and the device's clock runs ahead of the
    # host's, so time alone would pair every run with the NEXT dispatch
    kinds = "dddpdddqddpdd"
    ids = {"d": 1, "p": 2, "q": 3}
    fns = {"d": "decode_step", "p": "prefill", "q": "prefill"}
    modules = [(f"jit__unknown({ids[k]})", 100 * i + 7, 100 * i + 97)
               for i, k in enumerate(kinds)]
    dispatches = [(fns[k], 100 * i - 90) for i, k in enumerate(kinds)][1:]
    dispatches.append(("decode_step", 100 * len(kinds) - 90))
    named = trace_reduce.name_modules(modules + [("jit_named(9)", 5000, 5001)],
                                      dispatches + [("named", 4990)])
    assert [n for n, _, _ in named] == [
        f"jit_{fns[k]}({ids[k]})" for k in kinds] + ["jit_named(9)"]
    nested = [("PjitFunction(f)", 0, 10), ("PjitFunction(f)", 1, 9),
              ("PjitFunction(f)", 20, 30), ("PjitFunction(f)", 21, 29)]
    assert trace_reduce.outermost(nested) == [nested[0], nested[2]]
