"""``benchmark/host_reduce.py``: what the stepping thread's time is made of
outside a step's phases, on hand-made events and against a small trace
recorded on the chip WITH the new spans (``tiny_host_tpu.xplane.pb.gz``,
written by ``record_tiny_host_trace.py`` on one TPU v5 lite in PR 36: the
small GQA llama of ``tiny_serve_tpu.xplane.pb.gz`` behind the engine's own
stepping thread, four clients, one lock held 20 ms and one forced
collection inside the marks)."""
import gzip
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common, host_reduce, span_reduce  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "tiny_host_tpu.xplane.pb.gz")
US = 1000.0  # the hand-made slices count in microseconds, events in ns


def _span(name, start, end, **attrs):
    return {"name": name, "start": float(start), "end": float(end),
            "attrs": attrs}


# ----------------------------------------------------------- interval sums

def test_union_and_subtract_are_one_pass_interval_sums():
    assert host_reduce.union([(5, 9), (0, 3), (2, 4), (9, 10), (7, 7)]) == [
        (0, 4), (5, 10)]
    left = host_reduce.subtract([(0, 10), (20, 30), (40, 50)],
                                [(-5, 2), (4, 6), (9, 22), (28, 45)])
    assert left == [(2, 4), (6, 9), (22, 28), (45, 50)]
    assert host_reduce.subtract([(0, 10)], []) == [(0, 10)]
    assert host_reduce.subtract([], [(0, 10)]) == []
    assert host_reduce.total(left) == 16


def test_the_edges_end_at_the_first_and_last_step_run():
    runs = [("jit_llama_decode_step", 120, 150),
            ("jit_llama_prefill", 200, 260), ("jit_llama_decode_step", 5, 9)]
    assert host_reduce.edges(runs, 100, 300) == [(100, 120), (260, 300)]
    # a run that straddles a mark leaves no edge there
    assert host_reduce.edges(runs, 130, 250) == []
    assert host_reduce.edges([], 100, 300) == [(100, 300)]


def test_lock_gc_edge_and_unspanned_add_up_to_other():
    idle = [(0, 100), (200, 260), (300, 420)]
    phases = [(10, 40), (205, 215), (400, 500)]
    cover = {
        # a collection on another thread, partly while the stepping thread
        # waited for the lock: counted once, as the collector's
        "gc": [(50, 70), (330, 335)],
        "lock": [(60, 90), (215, 230), (340, 350)],
        "edge": [(0, 45), (390, 420)],
    }
    got = host_reduce.split_other(idle, phases, cover)
    # other: 100 - 30, 60 - 10, 100 of (300, 400)
    assert got["other"] == 70 + 50 + 100
    assert got["gc"] == 20 + 5
    assert got["lock"] == 20 + 15 + 10
    # (0, 10) and (40, 45) of the opening edge; (390, 400) of the closing
    assert got["edge"] == 10 + 5 + 10
    assert got["unspanned"] == got["other"] - got["gc"] - got["lock"] \
        - got["edge"] == 125
    assert sum(got[p] for p in host_reduce.PARTS) == got["other"]
    # nothing named: all of it is unspanned
    bare = host_reduce.split_other(idle, phases, {})
    assert bare["unspanned"] == bare["other"] == 220
    # what ``span_reduce`` calls other is the same sum
    named = [("engine.batch", s, e) for s, e in phases]
    assert span_reduce.attribute(idle, named)["other"] == got["other"]


# ------------------------------------------------- the floor from every sync

def _steps(*runs):
    """``span_reduce``'s paired steps: launch ``seq`` with its run."""
    return [{"attrs": {"kind": kind, "seq": seq},
             "run": (f"jit_llama_{kind}", start, end), "inside": True}
            for seq, kind, start, end in runs]


def test_every_sync_is_a_floor_whatever_its_lag():
    OFFSET = 1300.0
    steps = _steps((7, "decode_step", 100 + OFFSET, 200 + OFFSET),
                   (8, "prefill", 200 + OFFSET, 600 + OFFSET),
                   (9, "decode_step", 600 + OFFSET, 700 + OFFSET))
    syncs = [
        # launch 7 synced behind launch 8 (lag 1): its run ended at 200,
        # the sync returned 40 later
        _span("engine.sync", 150, 240, lag=1, seq=7),
        # launch 8 synced behind TWO launches (lag 2), 5 after its run
        _span("engine.sync", 500, 605, lag=2, seq=8),
        # launch 9 synced with nothing behind it (lag 0), 25 after
        _span("engine.sync", 650, 725, lag=0, seq=9),
        # a sync whose launch lies before the trace finds no run
        _span("engine.sync", 90, 95, lag=1, seq=6),
    ]
    got = host_reduce.offset_floor_by_seq(steps, syncs)
    assert got == {"floor_ns": OFFSET - 5, "syncs": 3,
                   "syncs_by_lag": {1: 1, 2: 1, 0: 1}}
    # the lag-0 sync alone, as ``span_reduce`` has it, is a looser floor
    assert host_reduce.offset_floor_by_seq(steps, syncs[2:3])[
        "floor_ns"] == OFFSET - 25
    # spans without ``seq`` (the parent's) give nothing, and do not raise
    old = [_span("engine.sync", 150, 240, lag=1)]
    assert host_reduce.offset_floor_by_seq(steps, old) is None
    assert host_reduce.offset_floor_by_seq(
        [{"attrs": {"kind": "decode"}, "run": ("x", 0, 1)}], syncs) is None


# ------------------------------------------------------- the whole reduction

def _slice(offset: float = 1500 * US):
    """A prefill and three decode steps of a host-bound engine (a run takes
    100, the host's step 900), the device's clock ``offset`` ahead of the
    host's. Step 2 waits 300 for the lock, step 3's stage phase holds an id
    gather, a collection of ANOTHER thread lies over the end of step 3's
    last phase and 40 of the 50 of plain code that lie between a step's
    last phase and the next step's wait for the lock."""
    spans, host, modules, ops = [], [], [], []
    t = 1000 * US
    w0 = t - 100 * US
    for seq in range(1, 5):
        lock = 300 * US if seq == 2 else 10 * US
        host.append({"name": "engine.lock", "start": t, "end": t + lock,
                     "line": "llm-engine-step"})
        t += lock
        for name, dur in (("engine.schedule", 50), ("engine.batch", 200),
                          ("executor.stage", 400)):
            spans.append(_span(name, t, t + dur * US))
            if name == "executor.stage" and seq == 3:
                host.append({"name": "executor.feed", "start": t + 100 * US,
                             "end": t + 250 * US, "line": "llm-engine-step"})
            t += dur * US
        # one prefill among the decode steps, so that ONE shift pairs them
        kind, program = (("prefill", "prefill") if seq == 1
                         else ("decode", "decode_step"))
        spans.append(_span("executor.dispatch", t, t + 100 * US,
                           kind=kind, seq=seq, kv_tokens=256))
        run = (f"jit_llama_{program}({seq})", t + 20 * US + offset,
               t + 120 * US + offset)
        modules.append(run)
        ops.append(("fusion.1",) + run[1:])
        t += 100 * US
        if seq > 1:
            spans.append(_span("engine.sync", t, t + 30 * US, lag=1,
                               seq=seq - 1))
            t += 30 * US
        spans.append(_span("engine.account", t, t + 60 * US))
        t += 60 * US
        if seq == 3:
            host.append({"name": "host.gc", "start": t - 20 * US,
                         "end": t + 40 * US, "line": "client-3"})
        t += 50 * US  # ... and the loop's own code
    w1 = t + 200 * US
    raw = {"window": (w0 + offset, w1 + offset), "spans": spans,
           "planes": [{"ops": ops, "modules": modules}]}
    return raw, host


def test_the_reduction_splits_other_and_bounds_the_offset():
    raw, host = _slice()
    reduced = span_reduce.reduce_raw(raw)
    assert reduced is not None and reduced["shift"] == 0
    # the ceiling: the offset and the fastest launch
    assert reduced["clock_offset_us"] == pytest.approx(1520.0)
    assert reduced["clock_offset_floor_us"] is None  # no lag-0 sync
    got = host_reduce.reduce_raw(raw, reduced, host)
    other = got["other_s"]
    assert other["other"] == pytest.approx(
        reduced["idle_by_layer_s"]["other"])
    assert sum(other[p] for p in host_reduce.PARTS) == pytest.approx(
        other["other"])
    # the collection takes 40 of one gap of plain code (its 20 under a
    # phase are the phase's); the other two gaps and the rest of that one
    # are unspanned; the edges: 100 before the first wait and 200 + 50
    # after the last phase, less what the waits for the lock take
    assert other["gc"] == pytest.approx(40e-6)
    assert other["unspanned"] == pytest.approx((50 + 50 + 10) * 1e-6)
    assert other["lock"] == pytest.approx((10 + 300 + 10 + 10) * 1e-6)
    assert other["edge"] == pytest.approx((100 + 250) * 1e-6)
    assert got["spans"] == {"engine.lock": 4, "host.gc": 1,
                            "executor.feed": 1}
    assert got["gc_lines"] == 1
    # every sync names its launch, so each is a floor under the true
    # offset of 1500 (the ceiling lies 20 over it, the fastest launch); a
    # loose one here, where the device had long finished at every sync
    assert got["offset_floor_syncs"] == {1: 3}
    assert got["offset_floor_by_seq_us"] == pytest.approx(590.0)
    assert got["clock_offset_us"] - got["offset_floor_by_seq_us"] == \
        pytest.approx(930.0)
    # the nested gather is split from the stage phase around it, and the
    # lock is a span like the others when the new names count
    by_span = got["idle_by_span_s"]
    assert by_span["executor.feed"] == pytest.approx(150e-6)
    with_feed = by_span["executor.stage"] + by_span["executor.feed"]
    assert with_feed == pytest.approx(
        reduced["idle_by_span_s"]["executor.stage"])
    # the thread's OWN account of its slice, the device idle or not: the
    # 50 of plain code after each of the first three steps, nothing else
    assert got["thread_gaps_s"] == {
        "engine.account>engine.lock": [3, pytest.approx(150e-6)]}
    assert got["thread_spanned_s"] == pytest.approx(
        (330 + 4 * 810 + 3 * 30) * 1e-6)
    assert by_span["engine.lock"] == pytest.approx(other["lock"])
    assert by_span["other"] == pytest.approx(
        other["other"] - other["lock"], abs=1e-9)


def test_the_readers_give_nothing_for_a_program_without_the_spans():
    """The parent's trace and counters: every new reader returns None."""
    raw, _ = _slice()
    for s in raw["spans"]:
        s["attrs"].pop("seq", None)
    ctx = {"span_trace": (raw, span_reduce.reduce_raw(raw)),
           "trace_run": None, "flight": [{"kind": "decode", "dur_ms": 3.0}],
           "stats_before": {"decode_steps": 1}, "t0": 0.0, "t1": 30.0,
           "stats_after": {"decode_steps": 9, "phases": {}}}
    for name in ("idle_pct.lock.sat", "idle_pct.gc.sat", "idle_pct.edge.sat",
                 "idle_pct.unspanned.sat", "lock_wait_ms.sat",
                 "decode_host_cpu_ms.steady", "decode_wall_max_ms.sat",
                 "gc_step_max_ms.steady", "stage_transfers.sat",
                 "feed_ms.sat", "clock_offset_width_us.sat",
                 "decode_remapped_pct.sat"):
        assert common.load_layer_metric(name).read(dict(ctx)) is None, name


def test_the_counter_readers_difference_the_window():
    def stats(n):
        return {
            "decode_steps": 100 * n, "decode_steps_remapped": 40 * n,
            "prefill_steps": 10 * n,
            "phases": {"decode": {"executor.stage": [100 * n, 0.24 * n],
                                  "engine.sync": [100 * n, 0.5 * n]}},
            "host": {
                "spans": {"engine.lock": [110 * n, 0.011 * n, 0.001 * n],
                          "executor.feed": [40 * n, 0.008 * n, 0.006 * n]},
                "phase_cpu": {"decode": {"executor.stage": 0.2 * n,
                                         "engine.sync": 0.01 * n}},
                "gc": {"collections": [50 * n, 4 * n, n],
                       "seconds": [0.01 * n, 0.02 * n, 0.3 * n]},
                "stage_transfers": 880 * n, "stage_bytes": 70400 * n}}

    flight = [
        {"kind": "decode", "step": 5, "dur_ms": 5.5, "lock_ms": 0.1,
         "gc_ms": 0.0, "cpu_ms": 5.0},
        {"kind": "decode", "step": 6, "dur_ms": 61.0, "lock_ms": 50.2,
         "gc_ms": 0.4, "cpu_ms": 6.1, "sync_ms": 0.2},
        {"kind": "prefill_chunk", "step": 7, "dur_ms": 90.0, "lock_ms": 0.0,
         "gc_ms": 31.5, "cpu_ms": 9.0},
    ]
    ctx = {"stats_before": stats(1), "stats_after": stats(3), "t0": 10.0,
           "t1": 40.0, "flight": flight}
    delta = host_reduce.host_delta(ctx)
    assert delta["spans"]["engine.lock"] == pytest.approx([220, 0.022, 0.002])
    assert delta["gc"]["collections"] == [100, 8, 2]
    assert delta["stage_transfers"] == 1760

    def read(name):
        return common.load_layer_metric(name).read(ctx)

    assert read("lock_wait_ms.sat") == pytest.approx(0.1)
    # every phase but the sync: 0.4 s of CPU over 200 steps
    assert read("decode_host_cpu_ms.sat") == pytest.approx(2.0)
    assert read("stage_transfers.sat") == pytest.approx(1760 / 220)
    assert read("feed_ms.sat") == pytest.approx(0.2)
    assert read("decode_remapped_pct.sat") == pytest.approx(40.0)
    # the longest DECODE step, and the record with the most collector time
    assert read("decode_wall_max_ms.sat") == 61.0
    assert read("gc_step_max_ms.steady") == 31.5


# ------------------------------------------------- the trace from the chip

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    if not os.path.isfile(RECORDED):
        pytest.skip("no recorded trace")
    path = os.path.join(str(tmp_path_factory.mktemp("trace")),
                        "tiny_host_tpu.xplane.pb")
    with gzip.open(RECORDED, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    raw = span_reduce.read_file(path)
    reduced = span_reduce.reduce_raw(raw)
    return raw, reduced, host_reduce.read_file(path)


def test_recorded_trace_holds_the_new_spans(recorded):
    raw, reduced, host = recorded
    assert reduced is not None
    names = {s["name"] for s in host}
    assert names == set(host_reduce.HOST_SPANS)
    # the lock's spans lie on ONE line, the stepping thread's, and every
    # gather lies inside a stage phase of that thread
    assert len({s["line"] for s in host
                if s["name"] == host_reduce.LOCK}) == 1
    stages = [(s["start"], s["end"]) for s in raw["spans"]
              if s["name"] == "executor.stage"]
    for feed in (s for s in host if s["name"] == host_reduce.FEED):
        assert any(a <= feed["start"] and feed["end"] <= b
                   for a, b in stages)
    # the script held the lock 20 ms once, and collected once
    waits = sorted(s["end"] - s["start"] for s in host
                   if s["name"] == host_reduce.LOCK)
    assert waits[-1] >= 15e6 > waits[-2]
    assert sum(s["name"] == host_reduce.GC for s in host) == 1
    # every dispatch and every sync names its launch
    for s in raw["spans"]:
        if s["name"] in ("executor.dispatch", "engine.sync"):
            assert int(s["attrs"]["seq"]) > 0


def test_recorded_trace_splits_other_and_bounds_the_offset(recorded):
    raw, reduced, host = recorded
    got = host_reduce.reduce_raw(raw, reduced, host)
    other = got["other_s"]
    assert other["other"] == pytest.approx(
        reduced["idle_by_layer_s"]["other"], abs=1e-9)
    assert sum(other[p] for p in host_reduce.PARTS) == pytest.approx(
        other["other"], abs=1e-9)
    # the held lock cost the device 20 ms of idle time under no phase
    assert other["lock"] == pytest.approx(0.022558, abs=1e-5)
    assert other["edge"] == pytest.approx(0.002290, abs=1e-5)
    # the collection ran 94 ms on the MAIN thread while the stepping
    # thread stood inside ``executor.stage`` (the interpreter was the
    # collector's): the device's idle under it is that phase's, none of
    # it ``other``'s, and is told apart as ``idle_under_gc_s``
    assert other["gc"] == 0.0
    assert got["idle_under_gc_s"] == pytest.approx(0.0945, abs=1e-3)
    assert reduced["idle_by_span_s"]["executor.stage"] > 0.09
    # the thread's own account: most of what no span covers lies between
    # a step's last phase and the next step's asking for the lock
    gaps = got["thread_gaps_s"]
    assert max(gaps, key=lambda k: gaps[k][1]) == \
        "engine.account>engine.lock"
    assert gaps["engine.account>engine.lock"] == [
        18, pytest.approx(0.003327, abs=1e-5)]
    # every sync is a floor under the ceiling (a host-bound slice: the
    # device had long finished at every sync, so the floors are loose),
    # where ``span_reduce`` alone has none (no ``lag == 0`` sync)
    assert reduced["clock_offset_floor_us"] is None
    assert got["offset_floor_syncs"] == {1: 21}
    assert got["clock_offset_us"] == pytest.approx(-927.9, abs=0.1)
    assert got["offset_floor_by_seq_us"] == pytest.approx(-4825.3, abs=0.1)
