"""Observability of the LLM serving stack (ISSUE 4): request-lifecycle
timelines, serving-latency histograms, the engine flight recorder, trace
propagation proxy -> handle -> replica -> engine, and the /debug/llm
endpoint.

Engine-level tests drive step() directly or via the background stepper;
cluster tests run a two-replica LLM app behind the HTTP proxy with a
chaos plan that fails one engine mid-stream — the dying replica must
leave a flight-recorder dump on disk and the resumed stream must stay in
ONE trace. Engine unit tests come first in this module: the cluster
fixture exports a chaos plan through the environment, and module order
keeps it from leaking into the unit-test engines.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import logging
import os
import time
import urllib.error
import urllib.request

import pytest

from conftest import serve_http_url, shutdown_if_setup_fails

from ray_tpu._private import chaos
from ray_tpu._private.chaos import Fault, FaultPlan
from ray_tpu.util import metrics, tracing


def _f32(cfg):
    import jax.numpy as jnp

    return dataclasses.replace(cfg, dtype=jnp.float32, attention="xla")


def _model_config():
    from ray_tpu.models.llama import LlamaConfig

    return _f32(LlamaConfig.tiny())


def _engine(*, auto_step=False, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    return LLMEngine(
        EngineConfig(model="llama", model_config=_model_config(), **kw),
        auto_step=auto_step,
    )


def _wait_for(predicate, timeout_s=30.0, interval=0.2):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


# ------------------------------------------------------------ timelines

@pytest.mark.timeout(120)
def test_request_timeline_phase_order(jax_cpu):
    eng = _engine()
    s = eng.submit([1, 2, 3], max_new_tokens=4)
    # live timeline is queryable mid-flight
    live = eng.request_timeline(s.request_id)
    assert live is not None
    # ``received`` is stamped before the scheduler's lock is taken,
    # ``submitted`` inside it
    assert [e["event"] for e in live["events"]] == ["received", "submitted"]
    assert live["events"][1]["prompt_tokens"] == 3
    assert live["finish_reason"] is None
    for _ in range(50):
        if s.done:
            break
        eng.step()
    assert len(list(s)) == 4
    # finished: archived timeline survives the request
    tl = eng.request_timeline(s.request_id)
    assert tl is not None and tl["finish_reason"] == "finished"
    events = [e["event"] for e in tl["events"]]
    assert events[:3] == ["received", "submitted", "admitted"]
    prefills = [e for e in tl["events"]
                if e["event"] in ("prefill", "prefill_chunk")]
    assert prefills, "timeline must show the prefill phase"
    assert all(e["dur_ms"] >= 0 for e in prefills)
    assert events.index("first_token") > events.index("admitted")
    assert events.count("token") == 3  # 4 generated, first is first_token
    assert events[-1] == "finished"
    assert tl["events"][-1]["tokens"] == 4
    # timestamps are monotone non-decreasing down the timeline
    ts = [e["ts"] for e in tl["events"]]
    assert ts == sorted(ts)
    assert eng.request_timeline("nope") is None
    eng.shutdown()


@pytest.mark.timeout(120)
def test_timeline_history_is_bounded(jax_cpu):
    eng = _engine(timeline_history=3)
    ids = []
    for i in range(5):
        s = eng.submit([i + 1, 2, 3], max_new_tokens=1)
        for _ in range(20):
            if s.done:
                break
            eng.step()
        list(s)
        ids.append(s.request_id)
    assert eng.request_timeline(ids[0]) is None, "oldest must be evicted"
    assert eng.request_timeline(ids[-1]) is not None
    eng.shutdown()


# ----------------------------------------------------------- histograms

@pytest.mark.timeout(120)
def test_latency_histograms_and_compile_events_exported(jax_cpu):
    before = metrics.collect(prefix="llm_")

    def count(key):
        return before.get(key, 0)

    eng = _engine()
    streams = [eng.submit([i + 1, 2, 3], max_new_tokens=4)
               for i in range(2)]
    for _ in range(60):
        if all(s.done for s in streams):
            break
        eng.step()
    for s in streams:
        assert len(list(s)) == 4
    after = metrics.collect(prefix="llm_")
    assert after["llm_ttft_seconds_count"] >= count(
        "llm_ttft_seconds_count") + 2
    assert after["llm_time_per_output_token_seconds_count"] >= count(
        "llm_time_per_output_token_seconds_count") + 6
    assert after["llm_queue_wait_seconds_count"] >= count(
        "llm_queue_wait_seconds_count") + 2
    # step-latency histogram is tagged by phase kind
    assert any(
        k.startswith("llm_engine_step_latency_seconds_count{kind=")
        for k in after
    )
    # compile events tagged by shape key, shapes drawn from the buckets
    shapes = [k for k in after
              if k.startswith("llm_compile_events_total{shape=")]
    assert shapes, "compile events must be tagged by shape"
    # the phase totals agree with the histogram: every step ends in one
    # ``engine.account``, which observes it once; and a step's phases lie
    # inside the interval the histogram timed, all but ``engine.schedule``
    # before it and ``engine.account`` after it (a prefill is timed up to
    # its sync: its ``engine.emit`` comes after, too)
    phases = eng.stats()["phases"]
    assert set(phases) >= {"prefill_chunk", "decode"}  # packed: the chunk kind
    for kind in ("prefill_chunk", "decode"):
        tag = f"{{kind={kind}}}"
        steps = (after[f"llm_engine_step_latency_seconds_count{tag}"]
                 - count(f"llm_engine_step_latency_seconds_count{tag}"))
        timed = (after[f"llm_engine_step_latency_seconds_sum{tag}"]
                 - count(f"llm_engine_step_latency_seconds_sum{tag}"))
        assert phases[kind]["engine.account"][0] == steps
        assert phases[kind]["engine.schedule"][0] == steps
        outside = {"engine.schedule", "engine.account"} | (
            {"engine.emit"} if kind == "prefill_chunk" else set())
        inside = sum(sec for name, (_, sec) in phases[kind].items()
                     if name not in outside)
        assert 0 < inside <= timed + 1e-6
    eng.shutdown()


def test_metric_redefinition_mismatch_raises(jax_cpu):
    # satellite: a second registration must either match exactly (same
    # object back) or fail loudly — never silently mislabel/misbucket
    c1 = metrics.counter("obs_test_counter", tag_keys=("a",))
    assert metrics.counter("obs_test_counter", tag_keys=("a",)) is c1
    with pytest.raises(ValueError, match="tag_keys"):
        metrics.counter("obs_test_counter", tag_keys=("b",))
    h1 = metrics.histogram("obs_test_hist", boundaries=(1.0, 2.0))
    assert metrics.histogram("obs_test_hist", boundaries=(1.0, 2.0)) is h1
    with pytest.raises(ValueError, match="boundaries"):
        metrics.histogram("obs_test_hist", boundaries=(1.0, 2.0, 3.0))
    with pytest.raises(ValueError, match="tag_keys"):
        metrics.histogram("obs_test_hist", boundaries=(1.0, 2.0),
                          tag_keys=("kind",))


# ------------------------------------------------------ flight recorder

@pytest.mark.timeout(120)
def test_flight_recorder_ring_is_bounded(jax_cpu):
    eng = _engine(flight_recorder_steps=8)
    s = eng.submit([1, 2, 3], max_new_tokens=20)
    for _ in range(60):
        if s.done:
            break
        eng.step()
    assert len(list(s)) == 20
    dump = eng.debug_dump()
    assert dump["reason"] == "debug"
    assert dump["capacity"] == 8
    assert len(dump["steps"]) == 8, "ring must hold exactly capacity"
    assert dump["steps_total"] > 8
    # records are the LAST N steps, consecutively numbered
    nums = [r["step"] for r in dump["steps"]]
    assert nums == list(range(dump["steps_total"] - 7,
                              dump["steps_total"] + 1))
    step_recs = [r for r in dump["steps"] if r["kind"] != "compile"]
    for r in step_recs:
        for key in ("kind", "ts", "dur_ms", "admitted", "expired", "cow",
                    "evicted_blocks", "kv_util", "waiting", "running"):
            assert key in r, f"flight record missing {key}: {r}"
    assert dump["stats"]["failed"] is False
    assert dump["cache"]["num_blocks"] == eng.cache.cfg.num_blocks
    # the process's event_stats ride along (the proxy's request timings;
    # this process has none), the engine's own host time comes as phases
    assert "event_stats" in dump
    assert dump["stats"]["phases"]["decode"]["engine.account"][0] > 8
    eng.shutdown()


@pytest.mark.timeout(60)
def test_flight_dump_dir_is_bounded(tmp_path, monkeypatch):
    """Auto-named dumps rotate: only the newest RAY_TPU_FLIGHT_KEEP
    survive repeated engine deaths (a crash-looping deployment must not
    fill the disk the postmortem needs). keep <= 0 disables rotation."""
    from ray_tpu.serve.llm import obs

    monkeypatch.setenv(obs.FLIGHT_KEEP_ENV, "3")
    paths = []
    for i in range(6):
        p = obs.write_dump({"reason": f"death-{i}"}, dir=str(tmp_path))
        assert p is not None
        paths.append(p)
        time.sleep(0.002)  # distinct auto-names + strict mtime order
    survivors = sorted(glob.glob(str(tmp_path / "llm_flight_*.json")))
    assert survivors == sorted(paths[-3:]), "must keep exactly the newest 3"
    # the survivors are whole, readable dumps
    assert json.loads(open(survivors[0]).read())["reason"] == "death-3"

    monkeypatch.setenv(obs.FLIGHT_KEEP_ENV, "0")
    for i in range(5):
        obs.write_dump({"reason": f"nocap-{i}"}, dir=str(tmp_path))
        time.sleep(0.002)
    assert len(glob.glob(str(tmp_path / "llm_flight_*.json"))) == 8, \
        "keep=0 must disable rotation entirely"


@pytest.mark.chaos
@pytest.mark.timeout(180)
def test_engine_death_writes_flight_dump(jax_cpu, chaos_plan, tmp_path):
    """Acceptance: kill the engine mid-stream (chaos raise on the 71st
    decode step) -> EngineDiedError AND a flight dump on disk with >= 64
    step records."""
    from ray_tpu.serve.llm import EngineDiedError

    chaos_plan(FaultPlan(faults=(
        Fault(point="engine.decode", action="raise", after=70, times=1),
    )))
    eng = _engine(auto_step=True, flight_recorder_dir=str(tmp_path))
    s = eng.submit([1, 2, 3], max_new_tokens=90)
    with pytest.raises(EngineDiedError):
        for _tok in s:
            pass
    files = glob.glob(str(tmp_path / "llm_flight_*.json"))
    assert len(files) == 1, f"expected exactly one dump, got {files}"
    dump = json.loads(open(files[0]).read())
    assert dump["reason"] == "engine_died"
    assert dump["steps_total"] >= 64
    assert len(dump["steps"]) >= 64
    kinds = {r["kind"] for r in dump["steps"]}
    assert "decode" in kinds
    assert dump["stats"]["failed"] is True
    # the failed request's timeline records the terminal reason
    tl = eng.request_timeline(s.request_id)
    assert tl is not None and tl["finish_reason"] == "failed"
    # a second failure path must not dump again (one post-mortem/engine)
    eng.shutdown()
    assert len(glob.glob(str(tmp_path / "llm_flight_*.json"))) == 1
    chaos.clear()


@pytest.mark.chaos
@pytest.mark.timeout(180)
def test_watchdog_timeout_writes_lock_free_dump(jax_cpu, chaos_plan,
                                                tmp_path):
    """The wedged-step watchdog dumps WITHOUT the scheduler lock (the
    wedged stepper still holds it): ring only, no stats section."""
    from ray_tpu.serve.llm import EngineDiedError

    chaos_plan(FaultPlan(faults=(
        Fault(point="engine.decode", action="delay", arg=3.0, after=2),
    )))
    eng = _engine(auto_step=True, step_timeout_s=0.3,
                  flight_recorder_dir=str(tmp_path))
    s = eng.submit([1, 2, 3], max_new_tokens=20)
    with pytest.raises(EngineDiedError):
        for _tok in s:
            pass
    files = glob.glob(str(tmp_path / "llm_flight_*.json"))
    assert len(files) == 1
    dump = json.loads(open(files[0]).read())
    assert dump["reason"] == "watchdog_timeout"
    assert dump["steps"], "ring snapshot must be present"
    assert "stats" not in dump, "lock-free dump must not take the lock"
    eng.shutdown()
    chaos.clear()


@pytest.mark.timeout(120)
def test_shutdown_dump_to_explicit_path(jax_cpu, tmp_path):
    eng = _engine()
    s = eng.submit([1, 2, 3], max_new_tokens=2)
    for _ in range(20):
        if s.done:
            break
        eng.step()
    list(s)
    path = str(tmp_path / "final.json")
    eng.shutdown(dump=path)
    dump = json.loads(open(path).read())
    assert dump["reason"] == "shutdown"
    assert dump["steps_total"] >= 1


# --------------------------------------------------------------- spans

@pytest.mark.timeout(180)
def test_engine_emits_request_spans_under_caller_trace(ray_start, jax_cpu):
    """Engine-level trace propagation: submit() inside a span -> the
    request's phase spans join the caller's trace, parented under one
    engine.request span, with per-chunk prefill and a first-token
    marker."""
    eng = _engine(auto_step=True)
    with tracing.span("client") as root:
        trace_id = root["trace_id"]
        s = eng.submit([1, 2, 3], max_new_tokens=4)
        assert len(list(s)) == 4
    assert _wait_for(
        lambda: len(tracing.get_trace(trace_id)) >= 5, timeout_s=20
    ), f"spans never landed: {tracing.get_trace(trace_id)}"
    spans = tracing.get_trace(trace_id)
    by_name: dict = {}
    for sp in spans:
        by_name.setdefault(sp["name"], []).append(sp)
    req = by_name["engine.request"][0]
    assert req["parent_span_id"] == root["span_id"]
    assert req["attrs"]["finish_reason"] == "finished"
    assert req["attrs"]["prompt_tokens"] == 3
    assert req["attrs"]["tokens"] == 4
    assert "engine.queued" in by_name
    # the request's and the queue's span start at ``submitted``, inside the
    # scheduler's lock, as they always have: ``received``, stamped before
    # the wait for the lock, is the timeline's alone
    events = {e["event"]: e["ts"]
              for e in eng.request_timeline(s.request_id)["events"]}
    assert events["received"] < events["submitted"]
    assert req["start"] == events["submitted"]
    assert by_name["engine.queued"][0]["start"] == events["submitted"]
    assert req["attrs"]["ttft_s"] == pytest.approx(
        events["first_token"] - events["submitted"], abs=1e-5)
    prefill_names = [n for n in by_name
                     if n in ("engine.prefill", "engine.prefill_chunk")]
    assert prefill_names, "per-chunk prefill spans missing"
    marker = by_name["engine.first_token"][0]
    assert marker["type"] == "marker"
    decode = by_name["engine.decode"][0]
    assert decode["attrs"]["tokens"] == 3
    # every phase span parents under the request span
    for name in ("engine.queued", "engine.first_token", "engine.decode",
                 prefill_names[0]):
        assert by_name[name][0]["parent_span_id"] == req["span_id"]
    eng.shutdown()


# ------------------------------------------------------------- cluster

@pytest.fixture(scope="module")
def obs_cluster(tmp_path_factory):
    """Two-replica LLM app behind the HTTP proxy, flight dumps routed to
    a temp dir through the environment, and a chaos plan that raises in
    one engine's 71st decode step — inherited by every replica worker."""
    flight_dir = str(tmp_path_factory.mktemp("flight"))
    prev_flight = os.environ.get("RAY_TPU_FLIGHT_DIR")
    os.environ["RAY_TPU_FLIGHT_DIR"] = flight_dir
    plan = FaultPlan(seed=11, faults=(
        Fault(point="engine.decode", action="raise", after=70, times=1),
    ))
    prev_plan = os.environ.get(chaos.ENV_VAR)
    os.environ[chaos.ENV_VAR] = plan.to_json()
    chaos.clear()

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import EngineConfig, build_llm_app

    try:
        ray_tpu.init(num_cpus=8)
        with shutdown_if_setup_fails():
            serve.start(http_options={"port": 0}, grpc_options=None)
            handle = serve.run(
                build_llm_app(
                    EngineConfig(model="llama",
                                 model_config=_model_config(), seed=0),
                    num_replicas=2,
                ),
                name="llm-obs", route_prefix="/llmobs", timeout_s=180,
            )
        yield serve, handle, flight_dir
        serve.shutdown()
        ray_tpu.shutdown()
    finally:
        # also where the set-up failed (a taken port): a plan left in the
        # environment kills the 71st decode step of every later test of
        # this worker
        chaos.clear()
        for var, prev in ((chaos.ENV_VAR, prev_plan),
                          ("RAY_TPU_FLIGHT_DIR", prev_flight)):
            if prev is None:
                os.environ.pop(var, None)
            else:
                os.environ[var] = prev


def _http_generate(payload: dict, *, traced: bool):
    headers = {"Content-Type": "application/json"}
    if traced:
        headers["x-ray-tpu-trace"] = "1"
    req = urllib.request.Request(
        serve_http_url("/llmobs"),
        data=json.dumps(payload).encode(), headers=headers,
    )
    resp = urllib.request.urlopen(req, timeout=120)
    body = resp.read().decode()
    chunks = [json.loads(line) for line in body.splitlines() if line]
    return resp, chunks


@pytest.mark.timeout(300)
def test_http_request_yields_one_trace_with_engine_spans(obs_cluster):
    """Acceptance: HTTP generate with the trace header -> ONE trace id,
    echoed on the response, whose spans cover proxy -> handle -> replica
    task -> engine phases (per-chunk prefill + first-token marker)."""
    resp, chunks = _http_generate(
        {"prompt": [1, 2, 3], "max_new_tokens": 6}, traced=True)
    trace_id = resp.headers.get("x-ray-tpu-trace-id")
    assert trace_id, "proxy must echo the assigned trace id"
    assert len(chunks) == 6
    assert all(c["trace_id"] == trace_id for c in chunks), \
        "every chunk must carry the request's ONE trace id"

    needed = {"http.request", "handle.dispatch", "engine.request",
              "engine.first_token", "engine.decode"}

    def landed():
        names = {s["name"] for s in tracing.get_trace(trace_id)}
        return needed <= names

    assert _wait_for(landed, timeout_s=30), (
        f"missing spans: "
        f"{needed - {s['name'] for s in tracing.get_trace(trace_id)}}"
    )
    spans = tracing.get_trace(trace_id)
    by_name = {s["name"]: s for s in spans}
    root = by_name["http.request"]
    assert root["parent_span_id"] is None
    assert by_name["handle.dispatch"]["parent_span_id"] == root["span_id"]
    # the replica task span bridges handle -> engine
    task_spans = [s for s in spans if s.get("type") == "task"]
    assert task_spans, "replica task execution must appear in the trace"
    assert any(n in by_name for n in ("engine.prefill",
                                      "engine.prefill_chunk"))
    req_span = by_name["engine.request"]
    assert req_span["attrs"]["finish_reason"] == "finished"
    assert by_name["engine.decode"]["parent_span_id"] == req_span["span_id"]
    # untraced requests pay nothing: no header, no per-chunk trace ids
    resp2, chunks2 = _http_generate(
        {"prompt": [1, 2, 3], "max_new_tokens": 2}, traced=False)
    assert resp2.headers.get("x-ray-tpu-trace-id") is None
    assert all("trace_id" not in c for c in chunks2)


@pytest.mark.timeout(300)
def test_debug_llm_endpoint(obs_cluster):
    resp = urllib.request.urlopen(
        serve_http_url("/debug/llm?app=llm-obs"), timeout=60)
    out = json.loads(resp.read())
    assert out["app"] == "llm-obs"
    dumps = [d for d in out["replicas"] if d]
    assert dumps, "at least one replica must answer debug_dump"
    for d in dumps:
        assert d["reason"] == "debug"
        assert "steps" in d and "stats" in d and "cache" in d
    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(
            serve_http_url("/debug/llm?app=nope"), timeout=60)
    assert err.value.code == 404


@pytest.mark.timeout(300)
def test_access_log_line_per_http_request(obs_cluster):
    records: list = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    h = Capture()
    logger = logging.getLogger("ray_tpu.serve.access")
    logger.addHandler(h)
    logger.setLevel(logging.INFO)
    try:
        _resp, chunks = _http_generate(
            {"prompt": [1, 2, 3], "max_new_tokens": 3,
             "request_id": "acc-req-1"}, traced=True)
        assert len(chunks) == 3
        assert _wait_for(lambda: any("acc-req-1" in r for r in records),
                         timeout_s=15)
    finally:
        logger.removeHandler(h)
    line = json.loads(next(r for r in records if "acc-req-1" in r))
    assert line["proxy"] == "http"
    assert line["path"] == "/llmobs"
    assert line["status"] == "200"
    assert line["tokens"] == 3
    assert line["trace_id"]
    assert line["ttft_ms"] is not None and line["ttft_ms"] >= 0
    assert line["duration_ms"] >= line["ttft_ms"]


@pytest.mark.chaos
@pytest.mark.timeout(300)
def test_killed_engine_dumps_flight_and_stream_keeps_one_trace(obs_cluster):
    """Acceptance: the chaos plan fails one replica's engine mid-stream.
    The dying engine leaves a flight dump on disk (>= 64 step records);
    the client's failover resume completes on the survivor and EVERY
    chunk — before and after the failover — carries the same trace id,
    with both replicas' engine.request spans in that one trace."""
    from ray_tpu.serve.llm import stream_tokens

    _serve, handle, flight_dir = obs_cluster
    with tracing.span("client.stream") as root:
        trace_id = root["trace_id"]
        gen = stream_tokens(handle, {
            "prompt": [1, 2, 3],
            "max_new_tokens": 90,
            "request_id": "obs-kill-1",
        })
        chunks = list(gen)
    assert gen.failovers >= 1, "the chaos fault should force a failover"
    assert [c["index"] for c in chunks] == list(range(90))
    assert all(c.get("trace_id") == trace_id for c in chunks), \
        "resumed stream must stay in the SAME trace"
    # the dying replica dumped its flight recorder before fanning out
    assert _wait_for(
        lambda: glob.glob(os.path.join(flight_dir, "llm_flight_*.json")),
        timeout_s=30,
    ), "no flight dump written by the killed engine"
    dumps = [json.loads(open(p).read())
             for p in glob.glob(os.path.join(flight_dir,
                                             "llm_flight_*.json"))]
    died = [d for d in dumps if d["reason"] == "engine_died"]
    assert died, f"expected an engine_died dump, got reasons: " \
                 f"{[d['reason'] for d in dumps]}"
    assert max(len(d["steps"]) for d in died) >= 64
    # both the failed and the finishing engine joined the one trace
    def two_requests():
        spans = tracing.get_trace(trace_id)
        reqs = [s for s in spans if s["name"] == "engine.request"]
        return len(reqs) >= 2

    assert _wait_for(two_requests, timeout_s=30), \
        "expected engine.request spans from BOTH replicas in one trace"
    reasons = sorted(
        s["attrs"]["finish_reason"]
        for s in tracing.get_trace(trace_id)
        if s["name"] == "engine.request"
    )
    assert "failed" in reasons and "finished" in reasons
