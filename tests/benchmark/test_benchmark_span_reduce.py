"""The program's spans against the device's trace: idle time by phase and
the two kernels' roofline shares, on hand-made events and against a small
trace recorded on the chip WITH the engine's spans
(``tiny_serve_tpu.xplane.pb.gz``, written by ``record_tiny_serve_trace.py``
on one TPU v5 lite in PR 24: a small GQA llama serving five requests
through the Pallas decode kernel, then three flash-attention steps; 42 ms,
2.1 MB unpacked)."""
import gzip
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common, span_reduce, trace_reduce  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "tiny_serve_tpu.xplane.pb.gz")


def _span(name, start, end, **attrs):
    return {"name": name, "start": float(start), "end": float(end),
            "attrs": attrs}


# ------------------------------------------------------- idle attribution

def test_a_gap_under_nested_spans_goes_to_the_inner_one():
    spans = [("engine.batch", 0, 100), ("kv.reserve", 20, 60),
             ("executor.stage", 30, 40), ("engine.sync", 100, 150)]
    assert span_reduce.innermost(spans) == [
        ("engine.batch", 0, 20), ("kv.reserve", 20, 30),
        ("executor.stage", 30, 40), ("kv.reserve", 40, 60),
        ("engine.batch", 60, 100), ("engine.sync", 100, 150)]
    got = span_reduce.attribute([(25, 45), (90, 110)], spans)
    assert got == {"kv.reserve": 10, "executor.stage": 10,
                   "engine.batch": 10, "engine.sync": 10, "other": 0}


def test_a_gap_no_span_covers_is_other():
    spans = [("engine.emit", 10, 20), ("engine.account", 30, 40)]
    got = span_reduce.attribute([(0, 50), (100, 120)], spans)
    assert got == {"engine.emit": 10, "engine.account": 10, "other": 50}
    assert span_reduce.by_layer(got) == {
        "scheduler": 20, "kv": 0, "executor": 0, "sync": 0, "wait": 0,
        "other": 50}
    assert span_reduce.attribute([], spans) == {}
    # what no span covers, told by the spans on either side of it
    gaps = span_reduce.between(spans + [("kv.reserve", 32, 38)])
    assert gaps == [("engine.emit>engine.account", 20, 30)]
    assert span_reduce.attribute([(0, 50), (100, 120)], gaps) == {
        "engine.emit>engine.account": 10, "other": 60}


def test_complement_is_what_the_operations_leave_of_the_window():
    ops = [(-5, 10), (8, 20), (40, 50), (50, 60), (90, 130)]
    assert span_reduce.complement(ops, 0, 100) == [(20, 40), (60, 90)]
    assert span_reduce.complement([], 0, 100) == [(0, 100)]
    assert span_reduce.complement([(0, 100)], 10, 90) == []


US = 1000.0  # the hand-made slices count in microseconds, events in ns


def _serving_slice(offset: float, launch: float = 30 * US):
    """Six steps, ``p d d p d d``: a prefill syncs at once (the device
    idles until the next dispatch), a decode after a decode is dispatched
    while its predecessor runs. The device's clock reads ``offset`` more
    than the host's."""
    kinds = "pddpdd"
    host_t = 1000 * US
    device_free = 0.0
    spans, modules, ops = [], [], []
    for i, k in enumerate(kinds):
        kind = "prefill" if k == "p" else "decode"
        spans.append(_span("engine.batch", host_t, host_t + 40 * US))
        spans.append(_span("executor.stage", host_t + 40 * US,
                           host_t + 60 * US))
        spans.append(_span(
            "executor.dispatch", host_t + 60 * US, host_t + 100 * US,
            kind=kind, kv_tokens=64 * (i + 1)))
        start = max(host_t + 60 * US + launch, device_free)
        dur = (400 if k == "p" else 300) * US
        name = "jit_llama_prefill(7)" if k == "p" else \
            "jit_llama_decode_step(9)"
        modules.append((name, start + offset, start + dur + offset))
        ops.append(("%fusion.1 = fusion()", start + offset,
                    start + dur - 100 * US + offset))
        ops.append(('%paged_attention.3 = custom-call(), custom_call_target'
                    '="tpu_custom_call"', start + dur - 100 * US + offset,
                    start + dur + offset))
        device_free = start + dur
        if k == "p":
            # lag 0: the host waits for the prefill's tokens, then emits
            spans.append(_span("engine.sync", host_t + 100 * US,
                               device_free + 5 * US, lag=0))
            spans.append(_span("engine.emit", device_free + 5 * US,
                               device_free + 45 * US))
            host_t = device_free + 50 * US
        else:
            host_t = host_t + 110 * US
    return spans, modules, ops


@pytest.mark.parametrize("offset", [-1_200_000.0, 0.0, 350_000.0])
def test_a_known_clock_offset_is_recovered(offset):
    spans, modules, ops = _serving_slice(offset)
    # the trace began after the first dispatch and ended before the last
    # dispatch's run: six runs, five dispatches of them, one more dispatch
    dispatches = [s for s in spans if s["name"] == "executor.dispatch"]
    extra = _span("executor.dispatch", 5000 * US, 5040 * US, kind="decode")
    syncs = [s for s in spans if s["name"] == "engine.sync"]
    found = span_reduce.align(dispatches[1:] + [extra],
                              span_reduce.step_runs(modules), syncs)
    assert found["shift"] == -1 and len(found["pairs"]) == 5
    # run start - dispatch start is the offset plus the launch (30 us)
    # where the device waited for it; the one prefill paired was synced
    # 5 us after its run ended, which holds the offset from below
    assert found["offset_ns"] == pytest.approx(offset + 30 * US)
    assert found["offset_floor_ns"] == pytest.approx(offset - 5 * US)
    assert all(d is dispatches[i + 1] and r == modules[i + 1]
               for i, (d, r) in enumerate(found["pairs"]))


def test_where_the_names_fit_more_than_one_shift_nothing_is_paired():
    # nothing but decode steps: the names cannot tell the shifts apart,
    # and a wrong one would be off by a whole step
    runs = [("jit_gpt_decode_step(3)", 1000.0 * i + 130, 1000.0 * i + 930)
            for i in range(8)]
    dispatches = [_span("executor.dispatch", 1000.0 * i + 100,
                        1000.0 * i + 120, kind="decode") for i in range(8)]
    assert span_reduce.align(dispatches, runs) is None
    assert span_reduce.align([], runs) is None
    assert span_reduce.align(dispatches, []) is None
    # one prefill among them settles it ...
    runs[3] = ("jit_gpt_prefill(5)",) + runs[3][1:]
    dispatches[3]["attrs"]["kind"] = "prefill"
    assert span_reduce.align(dispatches, runs)["shift"] == 0
    # ... and one pair that disagrees under every shift leaves none
    dispatches[5]["attrs"]["kind"] = "verify"
    assert span_reduce.align(dispatches, runs) is None
    raw = {"window": (0.0, 8000.0), "spans": dispatches,
           "planes": [{"ops": [], "modules": runs}]}
    assert span_reduce.reduce_raw(raw) is None


def test_a_shift_that_a_lag_0_sync_contradicts_is_rejected():
    # p d p d p d: the names fit shifts 0, 2 and -2 alike. The sync after
    # each prefill's dispatch ends 10 after that prefill's run. Under
    # shift 2 the third dispatch is laid against the first run, under
    # shift -2 against the fifth: the first and the fifth launch took 300
    # longer than the others, so each wrong shift has a sync that ends
    # before the run it is said to have waited for
    kinds = ["prefill", "decode"] * 3
    launch = [300.0, 30.0, 30.0, 30.0, 330.0, 30.0]
    dispatches = [_span("executor.dispatch", 1000.0 * i, 1000.0 * i + 20,
                        kind=k) for i, k in enumerate(kinds)]
    runs = [("jit_gpt_prefill(5)" if k == "prefill"
             else "jit_gpt_decode_step(3)",
             1000.0 * i + launch[i], 1000.0 * i + launch[i] + 700)
            for i, k in enumerate(kinds)]
    syncs = [_span("engine.sync", 1000.0 * i + 20, runs[i][2] + 10, lag=0)
             for i in (0, 2, 4)]
    assert span_reduce.align(dispatches, runs) is None  # three shifts fit
    found = span_reduce.align(dispatches, runs, syncs)
    assert found["shift"] == 0
    assert (found["offset_ns"], found["offset_floor_ns"]) == (30.0, -10.0)
    # a lag-1 sync waits for the run before, and says nothing here
    late = [_span("engine.sync", 1020.0, 1025.0, lag=1)]
    assert span_reduce.align(dispatches[:2], runs[:2], late)[
        "offset_floor_ns"] is None


def test_the_six_shares_sum_to_the_idle_share():
    offset = -1_200_000.0
    spans, modules, ops = _serving_slice(offset)
    w0, w1 = 900 * US, 3400 * US
    raw = {"window": (w0 + offset, w1 + offset), "spans": spans,
           "planes": [{"ops": ops, "modules": modules}]}
    r = span_reduce.reduce_raw(raw)
    assert r["clock_offset_us"] == pytest.approx(offset / 1e3 + 30)
    assert r["shift"] == 0 and r["paired"] == 6
    assert r["clock_offset_floor_us"] == pytest.approx(offset / 1e3 - 5)
    busy = trace_reduce.reduce_planes(raw["planes"], raw["window"])["busy_s"]
    assert r["idle_s"] == pytest.approx(r["window_s"] - busy)
    assert r["idle_s"] == pytest.approx(500e-6)
    assert sum(r["idle_by_layer_s"].values()) == pytest.approx(r["idle_s"])
    assert set(r["idle_by_layer_s"]) == set(span_reduce.LAYERS)
    # between a prefill's end and the next decode's start the host emits
    # the first tokens (40 us), packs the batch (40), stages (20) and
    # launches; the offset found holds the fastest launch (30), so that
    # much of each gap moves from the dispatch span at its end to the span
    # at its start: the sync's last 5 us become 35
    by = {k: round(v * 1e6) for k, v in r["idle_by_span_s"].items()}
    assert by == {"engine.batch": 120, "executor.stage": 60,
                  "engine.emit": 80, "engine.sync": 70, "other": 170}
    assert {k: round(v * 1e6) for k, v in r["idle_by_layer_s"].items()} == {
        "scheduler": 200, "kv": 0, "executor": 60, "sync": 70, "wait": 0,
        "other": 170}
    # two runs (the first has no run before it) began on a device that
    # had finished the last, each the same launch after its dispatch
    assert r["launch_after_idle_us"] == {"count": 2, "median": 0.0,
                                         "max": 0.0}


def test_without_spans_or_device_there_is_nothing_to_read():
    spans, modules, ops = _serving_slice(0.0)
    plane = {"ops": ops, "modules": modules}
    assert span_reduce.reduce_raw(
        {"window": (0, 4000 * US), "spans": [], "planes": [plane]}) is None
    assert span_reduce.reduce_raw(
        {"window": (0, 4000 * US), "spans": spans, "planes": []}) is None
    # a run with no trace (a rehearsal's, an untraced one's) reads nothing
    ctx = {"trace_run": None}
    assert span_reduce.load(ctx) == (None, None)
    assert span_reduce.idle_pct(ctx, "scheduler") is None
    # the parent of the PR that brought the phases keeps no totals
    old = {"stats_before": {"waiting": 0}, "stats_after": {"waiting": 1}}
    assert span_reduce.phase_totals(old, "decode") is None
    assert span_reduce.counter_delta(old, "decode_steps") is None
    for name in ("decode_host_ms.sat", "stage_ms.sat",
                 "decode_steady_pct.steady", "idle_pct.other.sat",
                 "paged_attn_hbm_pct.sat", "flash_attn_mxu_pct"):
        assert common.load_layer_metric(name).read(dict(old, **ctx)) is None


# --------------------------------------------- counters and phase totals

def test_readers_of_the_phase_totals_and_the_timeline():
    before = {"decode_steps": 10, "decode_steps_steady": 4, "phases": {
        "decode": {"engine.batch": [20, 0.010], "engine.sync": [10, 0.400],
                   "executor.stage": [10, 0.005]}}}
    after = {"decode_steps": 110, "decode_steps_steady": 64, "phases": {
        "decode": {"engine.batch": [220, 0.110], "engine.sync": [110, 4.4],
                   "executor.stage": [110, 0.055], "kv.reserve": [100, 0.02]},
        "prefill": {"engine.batch": [5, 0.001]}}}
    records = [{"id": i, "due": 1.0 + i} for i in range(4)]
    timelines = {i: {"events": [
        {"event": "received", "ts": 100.0 + i},
        {"event": "submitted", "ts": 100.0 + i + 0.010 * (i + 1)},
        {"event": "admitted", "ts": 101.0 + i}]} for i in range(4)}
    ctx = {"stats_before": before, "stats_after": after, "records": records,
           "timelines": timelines, "t0": 0.0, "t1": 10.0}
    read = lambda name: common.load_layer_metric(name).read(ctx)  # noqa: E731
    # every phase but the sync, over the dispatches: (100 + 50 + 20) ms
    assert read("decode_host_ms.sat") == pytest.approx(1.7)
    assert read("decode_host_ms.steady") == pytest.approx(1.7)
    assert read("stage_ms.sat") == pytest.approx(0.5)
    assert read("decode_steady_pct.sat") == pytest.approx(60.0)
    assert read("submit_wait_p95_ms") == pytest.approx(40.0)
    assert span_reduce.phase_totals(ctx, "verify") is None


# ------------------------------------------------------------- rooflines

def test_roofline_functions_on_hand_computed_shapes():
    # Mistral's widths: a token of context is 2 x 8 x 128 x 2 B = 4 KiB a
    # layer; 64 rows of 784 tokens in six layers
    assert span_reduce.paged_attn_bytes(
        64 * 784, n_kv_head=8, head_dim=128, itemsize=2,
        n_layer=6) == 64 * 784 * 4096 * 6 == 1_233_125_376
    # GPT-2's training step: 24 x 12 heads x 1,024^2 x 64 x 2 forward,
    # 3.5 times that with the backward, in 12 layers
    assert span_reduce.flash_attn_flops(
        batch=24, heads=12, seq=1024, head_dim=64,
        n_layer=12) == pytest.approx(1.6235e12, rel=1e-4)
    spans, modules, ops = _serving_slice(0.0)
    raw = {"window": (900 * US, 3400 * US), "spans": spans,
           "planes": [{"ops": ops, "modules": modules}]}
    r = span_reduce.reduce_raw(raw)
    widths = {"n_kv_head": 2, "head_dim": 128, "itemsize": 2, "n_layer": 2}
    got = span_reduce.paged_attn_hbm_pct(r, ops, widths, hbm_gb_per_s=819.0)
    # the four decode steps read 64 x (2 + 3 + 5 + 6) tokens of 2 KiB in
    # 4 x 100 us of kernel
    assert got["steps"] == 4 and got["kernel_s"] == pytest.approx(400e-6)
    assert got["bytes"] == 64 * 16 * 2048
    assert got["pct"] == pytest.approx(
        100 * (64 * 16 * 2048 / 400e3) / 819.0)
    # a step the window cuts is left out
    r["steps"][1]["inside"] = False
    assert span_reduce.paged_attn_hbm_pct(r, ops, widths, 819.0)["steps"] == 3
    train = [("jit_step(1)", 0, 1000), ("jit_step(1)", 1000, 2000),
             ("jit_other(2)", 2000, 2100), ("jit_step(1)", 2900, 3900)]
    calls = [('%x = custom-call(), custom_call_target="tpu_custom_call"',
              s + 100, s + 300) for _, s, _ in train if s != 2000]
    got = span_reduce.flash_attn_mxu_pct(
        train, calls, (0, 3000), flops_per_step=2e5, bf16_tflops=197.0)
    assert got["steps"] == 2 and got["kernel_s"] == pytest.approx(400e-9)
    assert got["tflops"] == pytest.approx(1.0)
    assert span_reduce.flash_attn_mxu_pct(
        train, [], (0, 3000), 2e5, 197.0) is None


# ------------------------------------------- the trace recorded on the chip

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The recorded trace unpacked where ``trace_reduce.find_xplane`` looks
    for a run's trace: ``(trace directory, raw, reduced)``."""
    root = tmp_path_factory.mktemp("tiny_serve")
    folder = root / "plugins" / "profile" / "recorded"
    folder.mkdir(parents=True)
    with gzip.open(RECORDED) as src, \
            open(folder / "tiny.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    ctx = {"trace_run": {"dir": str(root)}}
    raw, reduced = span_reduce.load(ctx)
    return str(root), raw, reduced


def test_recorded_trace_spans_are_laid_against_the_device(recorded):
    _, raw, r = recorded
    assert {s["name"] for s in raw["spans"]} == set(span_reduce.PHASES) - {
        "engine.wait"}  # hand-stepped: no loop, no wait
    # eleven dispatches inside the session, eleven runs, every name agrees
    assert (r["shift"], r["paired"]) == (0, 11)
    assert [s["attrs"]["kind"] for s in r["steps"]] == \
        ["prefill"] + ["decode"] * 5 + ["prefill"] + ["decode"] * 4
    assert all(span_reduce.PROGRAM_OF[s["attrs"]["kind"]] in s["run"][0]
               and s["inside"] for s in r["steps"])
    # the device's clock read 0.76 ms less than the host's at most (the
    # fastest launch is in that), and 2.14 ms less at least: the first
    # prefill's sync returned that long, on these clocks, before its run
    assert r["clock_offset_us"] == pytest.approx(-757.181, abs=1e-3)
    assert r["clock_offset_floor_us"] == pytest.approx(-2143.965, abs=1e-3)
    # the spans carry what a reader has, and no more
    assert {frozenset(s["attrs"]) for s in raw["spans"]} == {
        frozenset(), frozenset({"kind"}), frozenset({"kind", "kv_tokens"}),
        frozenset({"lag"})}
    # with it, no run starts before its dispatch; the hand-stepped engine
    # leaves the device idle before every launch but the first
    assert all(s["run"][1] - r["clock_offset_us"] * 1e3 >=
               d["start"] for s, d in zip(r["steps"], sorted(
                   (x for x in raw["spans"]
                    if x["name"] == span_reduce.DISPATCH),
                   key=lambda x: x["start"])))
    assert r["launch_after_idle_us"]["count"] == 10
    assert r["launch_after_idle_us"]["max"] < 200
    # idle by phase adds up to what trace_reduce calls idle
    plain = trace_reduce.reduce_file(trace_reduce.find_xplane(recorded[0]))
    assert r["window_s"] == pytest.approx(plain["window_s"])
    assert r["idle_s"] == pytest.approx(plain["window_s"] - plain["busy_s"])
    assert sum(r["idle_by_layer_s"].values()) == pytest.approx(r["idle_s"])
    # a tiny model leaves the device idle 94% of the time, most of it
    # while nine small arrays are staged one by one
    assert r["idle_s"] / r["window_s"] == pytest.approx(0.9422, abs=1e-3)
    assert max(r["idle_by_span_s"], key=r["idle_by_span_s"].get) == \
        "executor.stage"
    assert r["idle_by_layer_s"]["other"] < 0.25 * r["idle_s"]
    assert {"jit_llama_prefill", "jit_llama_decode_step",
            "jit_tiny_train_step"} == set(plain["modules"])


def test_recorded_trace_rooflines_lie_between_0_and_100(recorded):
    _, raw, r = recorded
    plane = raw["planes"][0]
    widths = {"n_kv_head": 2, "head_dim": 128, "itemsize": 2, "n_layer": 2}
    hbm = span_reduce.paged_attn_hbm_pct(r, plane["ops"], widths, 819.0)
    # nine decode steps: 5 x 144 + 4 x 192 tokens of context in whole
    # blocks, 2 KiB a token a layer
    assert hbm["steps"] == 9
    assert hbm["bytes"] == (5 * 144 + 4 * 192) * 2 * 2 * 128 * 2 * 2
    assert hbm["kernel_s"] == pytest.approx(167.078e-6, rel=1e-4)
    assert hbm["pct"] == pytest.approx(2.227, abs=1e-3)
    flops = span_reduce.flash_attn_flops(2, 4, 512, 64, n_layer=1)
    assert flops == 3.5 * 2 * 2 * 4 * 512 * 512 * 64
    # the serving programs hold Pallas calls too (the paged kernel), so the
    # training step's runs are picked out as the train cell has them: alone
    train = [m for m in plane["modules"] if "tiny_train_step" in m[0]]
    mxu = span_reduce.flash_attn_mxu_pct(
        train, plane["ops"], raw["window"], flops, 197.0)
    assert mxu["steps"] == 3
    assert mxu["kernel_s"] == pytest.approx(87.575e-6, rel=1e-4)
    assert mxu["pct"] == pytest.approx(16.337, abs=1e-3)
    assert 0 < hbm["pct"] < 100 and 0 < mxu["pct"] < 100


def test_recorded_trace_every_trace_reader_gives_a_number(recorded):
    root, raw, r = recorded
    ctx = {
        "trace_run": {"dir": root}, "span_trace": (raw, r),
        "trace": trace_reduce.reduce_file(trace_reduce.find_xplane(root)),
        "config": {"keys": {"n_head": 8, "n_kv_head": 2, "d_model": 1024,
                            "n_layer": 2, "dtype": "bfloat16"}},
    }
    got = {name: common.load_layer_metric(name).read(ctx) for name in (
        "idle_pct.scheduler.sat", "idle_pct.kv.sat", "idle_pct.executor.sat",
        "idle_pct.sync.sat", "idle_pct.wait.sat", "idle_pct.other.sat",
        "prefill_device_share_pct.sat")}
    assert all(v is not None and v >= 0 for v in got.values()), got
    idle = sum(v for k, v in got.items() if k.startswith("idle_pct."))
    busy = ctx["trace"]["busy_s"] / ctx["trace"]["window_s"]
    assert idle == pytest.approx(100 * (1 - busy), abs=1e-6)
    assert got["prefill_device_share_pct.sat"] == pytest.approx(
        100 * 0.00076191 / 0.002444971, rel=1e-4)
