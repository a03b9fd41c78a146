"""The serving step's host phases and the names of its programs.

``obs.phase`` books every host phase of an engine step twice: as a span on
the profiler's clock while a profiler session is active, and into
``engine.stats()["phases"]`` always. The jitted step programs carry their
family's names, so that a trace's ``XLA Modules`` line reads
``jit_llama_decode_step`` and not ``jit__unknown``.
"""
from __future__ import annotations

import dataclasses
import glob
import os

import numpy as np
import pytest

from ray_tpu.serve.llm import obs

STEP_PHASES = {
    "engine.schedule", "engine.batch", "kv.reserve", "executor.stage",
    "executor.dispatch", "engine.sync", "engine.emit", "engine.account",
}
# the spans that are no phase of a step: the wait for the engine's lock
# before one, and the id gather INSIDE ``executor.stage``. They are booked
# under ``stats()["host"]["spans"]``, never under ``stats()["phases"]``
HOST_SPANS = {"engine.lock", "executor.feed"}


def _model_config(family: str = "llama"):
    import jax.numpy as jnp

    if family == "llama":
        from ray_tpu.models.llama import LlamaConfig as Config
    else:
        from ray_tpu.models.gpt import GPTConfig as Config
    return dataclasses.replace(Config.tiny(), dtype=jnp.float32,
                               attention="xla")


def _engine(family: str = "llama", **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    return LLMEngine(
        EngineConfig(model=family, model_config=_model_config(family), **kw),
        auto_step=False,
    )


def _run(eng, streams, limit: int = 200) -> None:
    for _ in range(limit):
        if all(s.done for s in streams):
            return
        eng.step()
    raise AssertionError("streams did not finish")


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps every span
    with its attributes and how deep it was opened."""

    def __init__(self):
        self.spans: list[dict] = []
        self.depth = 0

    def __call__(self, name, **attrs):
        recorder = self

        class Span:
            # a collection (``host.gc``, the process's, not the engine's)
            # may fall anywhere, inside whatever is open: not kept
            kept = name != "host.gc"

            def __enter__(self):
                self.rec = {"name": name, "attrs": dict(attrs),
                            "depth": recorder.depth, "closed": False}
                if self.kept:
                    recorder.spans.append(self.rec)
                    recorder.depth += 1
                return self

            def __exit__(self, *exc):
                recorder.depth -= self.kept
                self.rec["closed"] = True

        return Span()


@pytest.fixture
def recorder(monkeypatch):
    rec = _Recorder()
    monkeypatch.setattr(obs, "_annotation", rec)
    return rec


# ------------------------------------------------------------------ names

@pytest.mark.parametrize("program", ["prefill", "decode_step", "verify_step"])
@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_jitted_program_lowers_under_its_own_name(jax_cpu, family, program):
    eng = _engine(family, speculative_k=2, drafter="ngram")
    fns, cache, ex = eng.fns, eng.cache, eng.executor
    B, nb = 2, 2
    i32 = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
    sample = eng._sample_args_locked([], B)
    if program == "prefill":
        lowered = fns._prefill.lower(
            ex.params, cache.k, cache.v, i32(B, nb * eng.cfg.block_size),
            np.ones((B,), np.int32), i32(B, nb), sample=sample)
    elif program == "decode_step":
        lowered = fns._decode.lower(
            ex.params, cache.k, cache.v, i32(B), i32(B), i32(B, nb),
            sample=sample)
    else:
        W = eng.cfg.speculative_k + 1
        words = (eng.model_cfg.vocab_size + 31) // 32
        sample["mask"] = np.full((B, W, words), 0xFFFFFFFF, np.uint32)
        lowered = fns._verify.lower(
            ex.params, cache.k, cache.v, i32(B, W), i32(B), i32(B),
            i32(B, nb), sample=sample)
    text = lowered.as_text()
    assert f"module @jit_{family}_{program}" in text
    assert "jit__unknown" not in text
    eng.shutdown()


# ----------------------------------------------------------------- phases

@pytest.mark.timeout(120)
def test_phases_balance_and_never_overlap(jax_cpu, recorder):
    eng = _engine()
    streams = [eng.submit([i + 1, 2, 3], max_new_tokens=6) for i in range(3)]
    seen: list[dict] = []
    steps = records = 0
    last = {}
    for _ in range(100):
        if all(s.done for s in streams):
            break
        before = len(recorder.spans)
        assert eng.step()
        steps += 1
        mine = recorder.spans[before:]
        # every span of the step closed, none was opened inside another,
        # and every step PROGRAM is accounted once: a decode step where it
        # was launched, last; a prefill where its ids reached the host,
        # which is the step after it when another launch followed it
        assert recorder.depth == 0
        assert all(s["closed"] for s in mine)
        # the step asked for the lock first; the only span opened inside
        # another is the id gather, inside the stage phase
        assert mine[0]["name"] == "engine.lock"
        assert all(s["depth"] == (s["name"] == "executor.feed")
                   for s in mine)
        assert all(a["name"] == "executor.stage" for a, b in
                   zip(mine, mine[1:]) if b["name"] == "executor.feed")
        mine = [s for s in mine if s["name"] not in HOST_SPANS]
        seen += mine
        names = [s["name"] for s in mine]
        assert names[0] == "engine.schedule"
        kinds = [s["attrs"]["kind"] for s in mine
                 if s["name"] == "executor.dispatch"]
        assert len(kinds) <= 1
        if kinds == ["decode"] or not kinds:
            assert names[-1] == "engine.account"
        assert names.count("engine.emit") == names.count("engine.sync")
        ring = [r["kind"] for r in eng.debug_dump()["steps"]
                if r["kind"] != "compile"]
        assert names.count("engine.account") == len(ring) - records
        records = len(ring)
        # totals only ever grow
        now = {(k, n): tuple(rec) for k, table in
               eng.stats()["phases"].items() for n, rec in table.items()}
        for key, (count, seconds) in last.items():
            assert now[key][0] >= count and now[key][1] >= seconds
        last = now
    assert {s["name"] for s in seen} == STEP_PHASES
    phases = eng.stats()["phases"]
    assert set(phases) == {"prefill_chunk", "decode"}  # packed: the chunk kind
    # one account a step program launched, and one for the last, empty step
    launched = sum(s["name"] == "executor.dispatch" for s in seen)
    assert sum(t["engine.account"][0] for t in phases.values()) == \
        launched + 1 == steps
    # what the spans said is what the totals counted
    for name in STEP_PHASES:
        assert sum(t.get(name, [0])[0] for t in phases.values()) == sum(
            s["name"] == name for s in seen)
    # a span carries the attributes a reader has, and no other: the
    # dispatch its kind (and what the kernel must read), the sync its lag
    attrs = {name: [s["attrs"] for s in seen if s["name"] == name]
             for name in STEP_PHASES}
    # the prefill's ids are synced behind the first decode step's launch,
    # and only the drain syncs with nothing launched behind
    assert [a["lag"] for a in attrs["engine.sync"]][0] == 1
    assert [a["lag"] for a in attrs["engine.sync"]][-1] == 0
    assert {a["lag"] for a in attrs["engine.sync"]} == {0, 1}
    assert all(set(a) == {"lag", "seq"} for a in attrs["engine.sync"])
    # (PR 39: a prefill kind carries ``qk_pairs``, the positions its real
    # query tokens attend)
    assert all(set(a) == ({"kind", "seq", "qk_pairs"}
                          if a["kind"] == "prefill_chunk"
                          else {"kind", "seq", "kv_tokens"})
               for a in attrs["executor.dispatch"])
    # launches are numbered as they are made, every one is synced once,
    # oldest first, and a sync's lag is the launches made since its own
    launched_seq = [a["seq"] for a in attrs["executor.dispatch"]]
    assert launched_seq == list(range(1, launched + 1))
    assert [a["seq"] for a in attrs["engine.sync"]] == launched_seq
    assert all(a == {} for name in STEP_PHASES - {
        "engine.sync", "executor.dispatch"} for a in attrs[name])
    # the sync's histogram and the phase total are one reading
    assert eng.stats()["host_sync_seconds_total"] == pytest.approx(
        sum(t["engine.sync"][1] for t in phases.values()), abs=1e-6)
    # an idle step runs nothing and books its scheduling to no kind
    assert eng.step() is False
    assert eng.stats()["phases"]["none"]["engine.schedule"][0] == 1
    eng.shutdown()


@pytest.mark.timeout(120)
def test_phases_close_when_a_step_raises(jax_cpu, recorder, monkeypatch):
    eng = _engine()
    s = eng.submit([1, 2, 3], max_new_tokens=4)
    eng.step()  # the prefill
    monkeypatch.setattr(
        eng.fns, "_decode",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")))
    before = len(recorder.spans)
    with pytest.raises(RuntimeError, match="boom"):
        eng.step()
    mine = recorder.spans[before:]
    assert recorder.depth == 0 and all(x["closed"] for x in mine)
    assert [x["name"] for x in mine][-1] == "executor.dispatch"
    # what ran before the fault is booked, under the kind that was running
    decode = eng.stats()["phases"]["decode"]
    assert decode["engine.schedule"][0] == 1
    assert decode["executor.dispatch"][0] == 1
    assert "engine.account" not in decode
    assert not s.done
    eng.shutdown()


@pytest.mark.timeout(120)
def test_kv_tokens_is_the_sum_of_block_rounded_contexts(jax_cpu, recorder):
    eng = _engine(block_size=4)
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8], [9] * 9]
    streams = [eng.submit(p, max_new_tokens=5) for p in prompts]
    checked = 0
    for _ in range(100):
        if all(s.done for s in streams):
            break
        # what the coming decode step's kernel has to read: every row's
        # context with the token in flight, in whole blocks
        rows = [r for r in eng._running
                if len(r.generated) + r.inflight < r.sampling.max_new_tokens]
        before = len(recorder.spans)
        eng.step()
        span = [x["attrs"] for x in recorder.spans[before:]
                if x["name"] == "executor.dispatch"]
        if not span or span[0]["kind"] != "decode":
            continue
        record = eng.debug_dump()["steps"][-1]
        if record["steady"]:
            # the batch is the one in flight: its rows were known before
            want = sum(-(-(r.total_len + r.inflight - 1) // 4) * 4
                       for r in rows)
            assert span[0]["kv_tokens"] == want
            assert record["batch"] == len(rows)
            checked += 1
    assert checked >= 2
    flight = [r for r in eng.debug_dump()["steps"]
              if r["kind"] == "decode" and r.get("batch")]
    dispatched = [x["attrs"] for x in recorder.spans
                  if x["name"] == "executor.dispatch"
                  and x["attrs"]["kind"] == "decode"]
    assert [r["kv_tokens"] for r in flight] == [
        a["kv_tokens"] for a in dispatched]
    assert sum(r["steady"] for r in flight) == \
        eng.stats()["decode_steps_steady"]
    assert all(r["kv_tokens"] % 4 == 0 and r["kv_tokens"] >= 4 * r["batch"]
               for r in flight)
    eng.shutdown()


@pytest.mark.timeout(180)
@pytest.mark.parametrize("constrained", [False, True])
def test_steady_steps_are_counted(jax_cpu, constrained):
    eng = _engine()
    extra = {"structured": "json"} if constrained else {}
    streams = [eng.submit([3, 5, 7 + i], max_new_tokens=8, **extra)
               for i in range(2)]
    _run(eng, streams)
    st = eng.stats()
    assert st["decode_steps"] == st["phases"]["decode"][
        "executor.dispatch"][0] > 0
    assert st["decode_steps_steady"] <= st["decode_steps"]
    if constrained:
        # the allow-mask of step N+1 needs step N's token on the host:
        # a grammar-constrained batch never dispatches ahead
        assert st["decode_steps_steady"] == 0
    else:
        assert st["decode_steps_steady"] > 0
    eng.shutdown()


@pytest.mark.timeout(120)
def test_received_is_never_after_submitted(jax_cpu):
    eng = _engine()
    streams = [eng.submit([i + 1, 2], max_new_tokens=2) for i in range(4)]
    _run(eng, streams)
    for s in streams:
        events = eng.request_timeline(s.request_id)["events"]
        assert [e["event"] for e in events[:2]] == ["received", "submitted"]
        assert events[0]["ts"] <= events[1]["ts"]
    eng.shutdown()


# ------------------------------------------------- the profiler's own clock

@pytest.mark.timeout(180)
def test_profiler_session_returns_the_spans_with_attributes(jax_cpu,
                                                            tmp_path):
    import jax
    from jax.profiler import ProfileData

    eng = _engine()
    warm = [eng.submit([1, 2, 3], max_new_tokens=3)]
    _run(eng, warm)  # compiled before the session
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _run(eng, [eng.submit([4, 5, 6], max_new_tokens=4)])
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[0]
    spans: dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in STEP_PHASES:
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.duration_ns, dict(e.stats)))
    assert set(spans) == STEP_PHASES
    dispatch = [stats for _, _, stats in spans["executor.dispatch"]]
    assert [d["kind"] for d in dispatch][:2] == ["prefill_chunk", "decode"]
    decode = [d for d in dispatch if d["kind"] == "decode"]
    assert decode and all(d["kv_tokens"] >= 16 for d in decode)
    assert {s["lag"] for _, _, s in spans["engine.sync"]} == {0, 1}
    # one step's spans follow one another on the profiler's clock
    flat = sorted((s, s + d) for name in STEP_PHASES
                  for s, d, _ in spans[name])
    assert all(a[1] <= b[0] for a, b in zip(flat, flat[1:]))
    eng.shutdown()


# --------------------------------- the trace's reader on the widened order

def _widened_trace(honest: bool = True, clip: int = 0) -> dict:
    """A hand-made trace in the order the widened pipeline leaves: a step
    is launched behind the one in flight (a decode step over another batch
    behind its id gather, ``jit_feed_ids``), THEN the older one is synced,
    its sync labelled by the launches that sat between (1); one collapse
    syncs the step just launched (0). The device's clock runs ``OFFSET``
    ahead of the host's. ``honest=False`` labels a prefill's deferred sync
    0, as an engine that did not count would; ``clip`` drops the first
    dispatch spans, as a trace that opens in mid-stream does."""
    OFFSET, LAUNCH = 1.3e6, 0.05e6
    DUR = {"decode": 5e6, "prefill": 20e6}
    order = "D D P D D D P D P D D D P D D".split()
    spans, modules, ops = [], [], []
    t, free = 10e6, 0.0
    inflight = []  # (kind, run end on the host's clock, launches then)
    launched = 0

    def span(name, dur, **attrs):
        nonlocal t
        spans.append({"name": name, "start": t, "end": t + dur,
                      "attrs": attrs})
        t += dur

    def sync(lag):
        nonlocal t
        kind, end, _ = inflight.pop(0)
        if not honest and kind == "prefill":
            lag = 0
        span("engine.sync", max(0.0, end - t) + 0.05e6, lag=lag)
        span("engine.emit", 0.8e6)

    for i, k in enumerate(order):
        kind = "decode" if k == "D" else "prefill"
        span("engine.schedule", 0.3e6)
        span("engine.batch", 0.6e6)
        span("executor.stage", 0.4e6)
        if kind == "decode" and i and order[i - 1] == "P":
            start = max(free, t)  # rows joined: the ids are gathered
            modules.append(("jit_feed_ids", start + OFFSET,
                            start + 0.01e6 + OFFSET))
            ops.append(("gather.1",) + modules[-1][1:])
            free = start + 0.01e6
        attrs = {"kind": kind}
        if kind == "decode":
            attrs["kv_tokens"] = 4096
        span("executor.dispatch", 0.2e6, **attrs)
        start = max(free, t + LAUNCH)
        free = start + DUR[kind]
        name = f"jit_llama_{'decode_step' if kind == 'decode' else kind}"
        modules.append((name, start + OFFSET, free + OFFSET))
        ops.append(("fusion.1", start + OFFSET, free + OFFSET))
        launched += 1
        inflight.append((kind, free, launched))
        while len(inflight) > 1:
            sync(launched - inflight[0][2])
        if i == 9:  # a constrained row: everything is synced first
            sync(0)
        span("engine.account", 0.1e6)
    sync(0)
    clipped = 0
    kept = []
    for s in spans:
        if s["name"] == "executor.dispatch" and clipped < clip:
            clipped += 1
            continue
        kept.append(s)
    return {"window": (12e6 + OFFSET, free + OFFSET), "spans": kept,
            "planes": [{"ops": ops, "modules": modules}]}


@pytest.mark.parametrize("clip,shift", [(0, 0), (1, -1), (2, -2)])
def test_reader_pairs_every_step_of_the_widened_order(clip, shift):
    """``benchmark.span_reduce`` on the new order (decode N, prefill P,
    decode N+1 launched before P's sync): ONE shift, every step program
    paired with its dispatch by name and order, the id gather's runs left
    out of the pairing, the offset between the two clocks found."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark import span_reduce

    raw = _widened_trace(clip=clip)
    runs = span_reduce.step_runs(raw["planes"][0]["modules"])
    assert len(runs) == 15 and not any("feed_ids" in r[0] for r in runs)
    spans = sorted(raw["spans"], key=lambda s: s["start"])
    found = span_reduce.align(
        [s for s in spans if s["name"] == "executor.dispatch"], runs,
        [s for s in spans if s["name"] == "engine.sync"])
    assert found is not None and found["shift"] == shift
    assert len(found["pairs"]) == 15 - clip
    assert found["offset_floor_ns"] <= found["offset_ns"]
    reduced = span_reduce.reduce_raw(raw)
    assert reduced["shift"] == shift and reduced["paired"] == 15 - clip
    # the offset, the dispatch span and the fastest launch
    assert reduced["clock_offset_us"] == pytest.approx(1550.0)
    kinds = [s["attrs"]["kind"] for s in reduced["steps"]]
    assert kinds.count("prefill") == 4 - (clip > 2)
    # every launch found a step in flight but the one after the collapse:
    # the device stood idle for that step's host work alone
    assert 0.0 < reduced["idle_s"] < 0.004
    assert sum(reduced["idle_by_layer_s"].values()) == pytest.approx(
        reduced["idle_s"])


def test_reader_needs_the_true_lag():
    """A deferred prefill's sync labelled 0 would name the step launched
    BEHIND the prefill as the one it waits for: its run ends long after
    the sync, every shift's floor lies over its ceiling, and the reader
    gives nothing rather than a guess. So ``engine.sync``'s ``lag`` counts
    the launches that sat between (engine._reconcile_locked)."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark import span_reduce

    assert span_reduce.reduce_raw(_widened_trace(honest=False)) is None
