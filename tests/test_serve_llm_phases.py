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
            def __enter__(self):
                self.rec = {"name": name, "attrs": dict(attrs),
                            "depth": recorder.depth, "closed": False}
                recorder.spans.append(self.rec)
                recorder.depth += 1
                return self

            def __exit__(self, *exc):
                recorder.depth -= 1
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
    steps = 0
    last = {}
    for _ in range(100):
        if all(s.done for s in streams):
            break
        before = len(recorder.spans)
        assert eng.step()
        steps += 1
        mine = recorder.spans[before:]
        seen += mine
        # every span of the step closed, none was opened inside another,
        # and the step was accounted once, last
        assert recorder.depth == 0
        assert all(s["closed"] and s["depth"] == 0 for s in mine)
        names = [s["name"] for s in mine]
        assert names[0] == "engine.schedule"
        assert names.count("engine.account") == 1
        assert names[-1] == "engine.account"
        assert names.count("executor.dispatch") <= 1
        # totals only ever grow
        now = {(k, n): tuple(rec) for k, table in
               eng.stats()["phases"].items() for n, rec in table.items()}
        for key, (count, seconds) in last.items():
            assert now[key][0] >= count and now[key][1] >= seconds
        last = now
    assert {s["name"] for s in seen} == STEP_PHASES
    phases = eng.stats()["phases"]
    assert set(phases) == {"prefill", "decode"}
    assert sum(t["engine.account"][0] for t in phases.values()) == steps
    # what the spans said is what the totals counted
    for name in STEP_PHASES:
        assert sum(t.get(name, [0])[0] for t in phases.values()) == sum(
            s["name"] == name for s in seen)
    # a span carries the attributes a reader has, and no other: the
    # dispatch its kind (and what the kernel must read), the sync its lag
    attrs = {name: [s["attrs"] for s in seen if s["name"] == name]
             for name in STEP_PHASES}
    assert [a["lag"] for a in attrs["engine.sync"]][0] == 0
    assert {a["lag"] for a in attrs["engine.sync"]} == {0, 1}
    assert all(set(a) == {"lag"} for a in attrs["engine.sync"])
    assert all(set(a) == ({"kind"} if a["kind"] == "prefill"
                          else {"kind", "kv_tokens"})
               for a in attrs["executor.dispatch"])
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
    assert [d["kind"] for d in dispatch][:2] == ["prefill", "decode"]
    decode = [d for d in dispatch if d["kind"] == "decode"]
    assert decode and all(d["kv_tokens"] >= 16 for d in decode)
    assert {s["lag"] for _, _, s in spans["engine.sync"]} == {0, 1}
    # one step's spans follow one another on the profiler's clock
    flat = sorted((s, s + d) for name in STEP_PHASES
                  for s, d, _ in spans[name])
    assert all(a[1] <= b[0] for a, b in zip(flat, flat[1:]))
    eng.shutdown()
