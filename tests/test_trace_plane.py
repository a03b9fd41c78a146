"""Fleet trace plane + SLO burn-rate monitor (ISSUE 19): tail-sampled
central span collection in the controller's TraceStore, deterministic
head sampling, full-lifecycle spans assembling across replica failover,
and the multi-window burn-rate math in serve/slo.py.

Unit tests drive the TraceStore / sampler / SLO evaluator as pure
objects; the cluster test runs a two-replica LLM app with a chaos plan
that fails one engine mid-stream and asserts the killed stream comes
back from the controller as ONE assembled trace — failover-retained,
with both replicas' engine spans and the router's resume span — while
the client stream stays byte-identical to an unfaulted run.
"""
from __future__ import annotations

import dataclasses
import time

import pytest

from conftest import shutdown_if_setup_fails

from ray_tpu._private import chaos
from ray_tpu._private.chaos import Fault, FaultPlan
from ray_tpu.serve.slo import SLOSpec, default_slos, evaluate
from ray_tpu.serve.trace_store import (
    RETENTION_FLAGS, TraceStore, sample_decision,
)
from ray_tpu.util import tracing

# byte-identity vector: the chaos fault raises in the serving engine's
# 71st decode step, mid-way through a 90-token stream
TRACE_PROMPT = [5, 6, 7]
TRACE_SAMPLING = dict(max_new_tokens=90, temperature=0.8, seed=42)


def _span(name, trace_id, span_id, parent=None, start=0.0, end=1.0,
          **attrs):
    return {"name": name, "kind": "span", "trace_id": trace_id,
            "span_id": span_id, "parent_span_id": parent,
            "start": start, "end": end, "attrs": attrs}


def _wait_for(predicate, timeout_s=30.0, interval=0.2):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


# ---------------------------------------------------- head/tail sampling

def test_sample_decision_is_deterministic_and_tracks_rate():
    ids = [f"trace-{i:04d}" for i in range(4000)]
    first = [sample_decision(t, 0.25) for t in ids]
    assert first == [sample_decision(t, 0.25) for t in ids], \
        "same id must always land on the same side of the rate"
    assert all(sample_decision(t, 1.0) for t in ids)
    assert not any(sample_decision(t, 0.0) for t in ids)
    rate = sum(first) / len(first)
    assert 0.20 < rate < 0.30, f"crc32 sample far off the rate: {rate}"
    # monotone in rate for a fixed id: once sampled at r, sampled at r' > r
    for t in ids[:200]:
        if sample_decision(t, 0.1):
            assert sample_decision(t, 0.5)


def test_head_sampler_is_seeded_and_tracks_rate():
    from ray_tpu.serve.proxy import head_sampler

    a = head_sampler("http:127.0.0.1:8000", 0.3)
    b = head_sampler("http:127.0.0.1:8000", 0.3)
    seq_a = [a() for _ in range(2000)]
    seq_b = [b() for _ in range(2000)]
    assert seq_a == seq_b, "same seed must reproduce the same decisions"
    rate = sum(seq_a) / len(seq_a)
    assert 0.25 < rate < 0.35, f"head sample far off the rate: {rate}"
    always = head_sampler("x", 1.0)
    never = head_sampler("x", 0.0)
    assert all(always() for _ in range(50))
    assert not any(never() for _ in range(50))
    other = head_sampler("grpc:127.0.0.1:9000", 0.3)
    assert [other() for _ in range(2000)] != seq_a, \
        "distinct proxies must not share a decision stream"


# -------------------------------------------------- TraceStore retention

@pytest.mark.parametrize("span,flag", [
    (_span("engine.request", "t", "s", finish_reason="failed"), "error"),
    (_span("engine.request", "t", "s", finish_reason="cancelled"), "error"),
    (_span("engine.request", "t", "s", finish_reason="shutdown"), "error"),
    (_span("engine.request", "t", "s", finish_reason="expired"),
     "deadline"),
    (_span("engine.request", "t", "s", finish_reason="finished",
           preempt_count=2), "preempted"),
    (_span("engine.preempted", "t", "s", parked_ms=12.5), "preempted"),
    (_span("handle.resume", "t", "s", failover=1), "failover"),
    (_span("handle.shed", "t", "s", priority="batch"), "shed"),
    (_span("handoff.seal", "t", "s", attempt=1), "handoff-retry"),
    (_span("handoff.fetch", "t", "s", attempt=2), "handoff-retry"),
])
def test_tail_retention_triggers(span, flag):
    assert flag in RETENTION_FLAGS
    store = TraceStore()
    store.ingest([span], source="replica:r1", stamp=1.0)
    assert store.list_traces(status=flag), \
        f"span {span['name']} should raise the {flag!r} flag"


def test_no_retention_flag_on_boring_spans():
    store = TraceStore()
    store.ingest([
        _span("engine.request", "t", "s1", finish_reason="finished",
              ttft_s=0.01),
        _span("handoff.seal", "t", "s2", attempt=0),
        _span("handle.dispatch", "t", "s3", deployment="app/llm"),
    ], source="replica:r1", stamp=1.0)
    (row,) = store.list_traces()
    assert row["status"] in (["slow"], ["sampled"])
    assert row["app"] == "app"
    assert row["ttft_s"] == 0.01


def test_two_engine_requests_flag_failover():
    store = TraceStore()
    store.ingest(
        [_span("engine.request", "t", "s1", finish_reason="failed")],
        source="replica:r1", stamp=1.0)
    store.ingest(
        [_span("engine.request", "t", "s2", finish_reason="finished")],
        source="replica:r2", stamp=2.0)
    (row,) = store.list_traces(status="failover")
    assert row["trace_id"] == "t"


def test_eviction_keeps_flagged_sampled_and_ttft_reservoir():
    store = TraceStore(max_traces=40, sample_rate=0.3, ttft_reservoir=2)
    boring = [f"boring-{i:03d}" for i in range(50)]
    for i, tid in enumerate(boring):
        store.ingest([_span("engine.request", tid, f"s{i}",
                            finish_reason="finished",
                            ttft_s=0.001 * (i + 1))],
                     source="replica:r1", stamp=float(i))
    flagged = [f"bad-{i}" for i in range(5)]
    for i, tid in enumerate(flagged):
        store.ingest([_span("engine.request", tid, f"f{i}",
                            finish_reason="failed")],
                     source="replica:r1", stamp=100.0 + i)
    assert len(store) == 40
    assert store.stats()["evicted_traces"] == 15
    for tid in flagged:
        assert tid in store, "flagged traces must ride out eviction"
    # the 2 slowest-TTFT traces survive regardless of the sample
    assert boring[-1] in store and boring[-2] in store
    # everything evicted failed the deterministic sample (and was not in
    # the reservoir) — tail retention never dropped an interesting trace
    for tid in boring:
        if tid not in store:
            assert not sample_decision(tid, 0.3)
    assert store.list_traces(status="slow")


def test_ingest_dedups_redelivered_spans_and_bounds_spans():
    store = TraceStore(max_spans_per_trace=3)
    spans = [_span("a", "t", "s1"), _span("b", "t", "s2", parent="s1")]
    assert store.ingest(spans, source="proxy:p1", stamp=1.0) == 2
    # a poll retry re-delivers the same drain: exactly-once by span id
    assert store.ingest(spans, source="proxy:p1", stamp=2.0) == 0
    assert store.ingest(
        [_span("c", "t", "s3"), _span("d", "t", "s4")],
        source="proxy:p1", stamp=3.0) == 1, "span cap must drop overflow"
    assert store.stats()["dropped_spans"] == 1
    # junk without ids is skipped, never raises (poll path stays alive)
    assert store.ingest([{"weird": 1}, {}], source="x", stamp=4.0) == 0


def test_assemble_nests_children_and_labels_sources():
    store = TraceStore()
    store.ingest([
        _span("http.request", "t", "root", start=0.0, end=5.0, app="demo"),
        _span("handle.dispatch", "t", "disp", parent="root",
              start=0.5, end=4.5),
    ], source="proxy:p1", stamp=1.0)
    store.ingest([
        _span("engine.request", "t", "eng", parent="disp",
              start=1.0, end=4.0, finish_reason="finished"),
    ], source="replica:r1", stamp=1.5)
    tree = store.assemble("t")
    assert tree["span_count"] == 3
    assert tree["sources"] == ["proxy:p1", "replica:r1"]
    (root,) = tree["tree"]
    assert root["name"] == "http.request"
    (disp,) = root["children"]
    assert disp["name"] == "handle.dispatch"
    assert disp["children"][0]["name"] == "engine.request"
    assert disp["children"][0]["source"] == "replica:r1"
    assert store.assemble("nope") is None
    # orphaned spans (parent sampled out elsewhere) surface as roots
    store.ingest([_span("x", "t2", "s9", parent="never-collected")],
                 source="replica:r1", stamp=2.0)
    assert store.assemble("t2")["tree"][0]["name"] == "x"


def test_exemplar_ids_by_flag_and_ttft():
    store = TraceStore()
    store.ingest([_span("handle.shed", "shed-old", "a")],
                 source="c", stamp=1.0)
    store.ingest([_span("handle.shed", "shed-new", "b")],
                 source="c", stamp=2.0)
    for i, tid in enumerate(("fast", "slow", "slower")):
        store.ingest([_span("engine.request", tid, f"t{i}",
                            finish_reason="finished",
                            ttft_s=0.1 * (i + 1))],
                     source="c", stamp=3.0 + i)
    assert store.exemplar_ids(flags=("shed",), n=1) == ["shed-new"]
    assert store.exemplar_ids(slowest_ttft=True, n=2) == ["slower", "slow"]


# --------------------------------------------------- burn-rate windows

def _ring(*points):
    return list(points)


def test_ratio_burn_rate_multi_window_math():
    spec = SLOSpec(name="avail", kind="ratio", objective=0.99,
                   bad_families=("llm_requests_rejected",),
                   total_families=("llm_requests_finished",))
    now = 1000.0
    # 10 bad / 100 total inside BOTH windows: bad_fraction 0.1 against a
    # 0.01 budget -> burn 10.0 in each window -> burning
    history = {
        "llm_requests_rejected_total{replica_id=r1}": _ring(
            (700.0, 0.0), (990.0, 10.0)),
        "llm_requests_finished_total{replica_id=r1}": _ring(
            (700.0, 0.0), (990.0, 90.0)),
    }
    (res,) = evaluate([spec], history, now)
    assert res["burning"] is True
    for w in ("60s", "300s"):
        assert res["windows"][w]["burn_rate"] == pytest.approx(10.0)
        assert res["windows"][w]["bad_fraction"] == pytest.approx(0.1)
        assert res["windows"][w]["events"] == pytest.approx(100.0)


def test_ratio_burn_requires_every_window():
    spec = SLOSpec(name="avail", kind="ratio", objective=0.99,
                   bad_families=("llm_requests_rejected",),
                   total_families=("llm_requests_finished",))
    now = 1000.0
    # all the bad events happened 2-5 minutes ago: the long window burns,
    # the short one is clean -> NOT burning (blip guard, inverted: the
    # incident is over)
    history = {
        "llm_requests_rejected_total{replica_id=r1}": _ring(
            (700.0, 0.0), (800.0, 10.0), (990.0, 10.0)),
        "llm_requests_finished_total{replica_id=r1}": _ring(
            (700.0, 0.0), (800.0, 40.0), (990.0, 90.0)),
    }
    (res,) = evaluate([spec], history, now)
    assert res["windows"]["300s"]["burn_rate"] > 1.0
    assert res["windows"]["60s"]["burn_rate"] == 0.0
    assert res["burning"] is False


def test_no_data_is_not_an_outage():
    (res,) = evaluate(
        [default_slos()[2]], {}, now=50.0)  # availability, empty history
    assert res["burning"] is False
    assert all(w["burn_rate"] == 0.0 for w in res["windows"].values())


def test_latency_burn_from_histogram_buckets():
    spec = SLOSpec(name="ttft", kind="latency", objective=0.9,
                   family="llm_ttft_seconds", threshold_s=0.5)
    now = 1000.0
    # 100 events in-window, 70 under the 0.5s threshold: bad 0.3 against
    # a 0.1 budget -> burn 3.0 everywhere -> burning
    history = {
        "llm_ttft_seconds_bucket{le=0.1,replica_id=r1}": _ring(
            (700.0, 0.0), (990.0, 40.0)),
        "llm_ttft_seconds_bucket{le=0.5,replica_id=r1}": _ring(
            (700.0, 0.0), (990.0, 70.0)),
        "llm_ttft_seconds_bucket{le=+Inf,replica_id=r1}": _ring(
            (700.0, 0.0), (990.0, 100.0)),
    }
    (res,) = evaluate([spec], history, now)
    assert res["burning"] is True
    for w in res["windows"].values():
        assert w["burn_rate"] == pytest.approx(3.0)
        assert w["events"] == pytest.approx(100.0)


def test_gauge_floor_burn():
    spec = SLOSpec(name="goodput", kind="gauge_floor", objective=0.99,
                   family="llm_goodput_tokens_per_sec",
                   label_filters=(("kind", "decode"),), floor=10.0)
    now = 100.0
    history = {
        # windowed average 5.0 against a floor of 10 -> bad 0.5
        "llm_goodput_tokens_per_sec{kind=decode,replica_id=r1}": _ring(
            (95.0, 4.0), (99.0, 6.0)),
        # wrong kind: filtered out, must not dilute the average
        "llm_goodput_tokens_per_sec{kind=prefill,replica_id=r1}": _ring(
            (95.0, 1000.0)),
    }
    (res,) = evaluate([spec], history, now)
    assert res["burning"] is True
    assert res["windows"]["60s"]["bad_fraction"] == pytest.approx(0.5)


def test_slospec_validation():
    with pytest.raises(ValueError, match="kind"):
        SLOSpec(name="x", kind="nope")
    with pytest.raises(ValueError, match="threshold_s"):
        SLOSpec(name="x", kind="latency")
    with pytest.raises(ValueError, match="bad_families"):
        SLOSpec(name="x", kind="ratio")
    with pytest.raises(ValueError, match="floor"):
        SLOSpec(name="x", kind="gauge_floor")
    assert {s.name for s in default_slos()} == {
        "ttft_p99", "tpot_p99", "availability", "goodput_floor"}


# ------------------------------------------------------- span plumbing

def test_span_buffer_drains_atomically():
    tracing.drain_buffered_spans()  # discard whatever earlier tests left
    with tracing.span("outer") as root:
        with tracing.span("inner"):
            pass
    spans = tracing.drain_buffered_spans()
    assert [s["name"] for s in spans] == ["inner", "outer"]
    assert all(s["trace_id"] == root["trace_id"] for s in spans)
    assert tracing.drain_buffered_spans() == [], "drain must clear"


def test_attach_context_reenters_stored_trace():
    with tracing.span("origin") as root:
        ctx = tracing.current_context()
    assert tracing.current_context() is None
    with tracing.attach_context(ctx):
        got = tracing.current_context()
        assert got["trace_id"] == root["trace_id"]
        assert got["parent_span_id"] == root["span_id"]
    assert tracing.current_context() is None
    with tracing.attach_context(None):  # no-op for untraced callers
        assert tracing.current_context() is None


# ------------------------------------------------------------- cluster

@pytest.fixture(scope="module")
def trace_cluster():
    """Two-replica LLM app, no proxies in the path (the driver IS the
    client), with a chaos plan that raises in one engine's 71st decode
    step — the traced stream below fails over mid-flight."""
    import os

    plan = FaultPlan(seed=19, faults=(
        Fault(point="engine.decode", action="raise", after=70, times=1),
    ))
    prev = os.environ.get(chaos.ENV_VAR)
    os.environ[chaos.ENV_VAR] = plan.to_json()
    chaos.clear()

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import EngineConfig, build_llm_app

    import jax.numpy as jnp

    mc = dataclasses.replace(
        LlamaConfig.tiny(), dtype=jnp.float32, attention="xla")
    try:
        ray_tpu.init(num_cpus=8)
        with shutdown_if_setup_fails():
            serve.start(http_options={"port": 18177}, grpc_options=None)
            handle = serve.run(
                build_llm_app(
                    EngineConfig(model="llama", model_config=mc, seed=0),
                    num_replicas=2,
                ),
                name="llm-trace", route_prefix="/llmtrace", timeout_s=180,
            )
        yield serve, handle, mc
        serve.shutdown()
        ray_tpu.shutdown()
    finally:
        # also where the set-up failed: a plan left in the environment
        # kills the 71st decode step of every later test of this worker
        chaos.clear()
        if prev is None:
            os.environ.pop(chaos.ENV_VAR, None)
        else:
            os.environ[chaos.ENV_VAR] = prev


@pytest.mark.chaos
@pytest.mark.timeout(300)
def test_failover_trace_assembles_in_fleet_store(trace_cluster, jax_cpu):
    """Acceptance: a traced stream whose serving replica's engine dies
    mid-flight assembles into ONE tree in the controller's TraceStore —
    the driver's root + dispatch/resume spans (pushed: the controller
    cannot poll the driver) joined with BOTH replicas' polled engine
    spans under the failover retention flag — while the client stream
    stays byte-identical to an unfaulted single-engine run."""
    import ray_tpu
    from ray_tpu.serve.controller import CONTROLLER_NAME
    from ray_tpu.serve.llm import EngineConfig, LLMEngine, stream_tokens

    _serve, handle, mc = trace_cluster
    with tracing.span("client.stream") as root:
        trace_id = root["trace_id"]
        gen = stream_tokens(handle, {
            "prompt": TRACE_PROMPT,
            "request_id": "trace-kill-1",
            **TRACE_SAMPLING,
        })
        chunks = list(gen)
    assert gen.failovers >= 1, "the chaos fault should force a failover"

    # byte-identity survives the failover (deterministic keyed sampling).
    # The reference engine runs in THIS process, which inherited the env
    # chaos plan — drop it here (the replicas read theirs at boot) or the
    # reference generate would trip the same decode fault.
    import os

    os.environ.pop(chaos.ENV_VAR, None)
    chaos.clear()
    reference = LLMEngine(
        EngineConfig(model="llama", model_config=mc, seed=0),
        auto_step=False,
    ).generate(TRACE_PROMPT, **TRACE_SAMPLING)
    assert [c["index"] for c in chunks] == list(
        range(TRACE_SAMPLING["max_new_tokens"]))
    assert [c["token"] for c in chunks] == reference
    assert all(c.get("trace_id") == trace_id for c in chunks)

    ctrl = ray_tpu.get_actor(CONTROLLER_NAME)
    pushed = ray_tpu.get(
        ctrl.trace_push.remote(tracing.drain_buffered_spans(), "client"),
        timeout=30)
    assert pushed > 0, "driver span push must land"

    def assembled():
        tree = ray_tpu.get(ctrl.trace_get.remote(trace_id), timeout=10)
        if tree is None:
            return False
        flat = ray_tpu.get(ctrl.trace_spans.remote(trace_id), timeout=10)
        reqs = [s for s in flat if s["name"] == "engine.request"]
        return len(reqs) >= 2

    assert _wait_for(assembled, timeout_s=60), \
        "both replicas' engine spans never reached the TraceStore"

    tree = ray_tpu.get(ctrl.trace_get.remote(trace_id), timeout=10)
    assert "failover" in tree["status"], \
        "tail retention must flag the failover trace"
    # spans from the driver AND both replica processes, ONE tree
    assert "client" in tree["sources"]
    assert len([s for s in tree["sources"]
                if s.startswith("replica:")]) >= 2
    flat = ray_tpu.get(ctrl.trace_spans.remote(trace_id), timeout=10)
    names = {s["name"] for s in flat}
    assert {"client.stream", "handle.dispatch", "handle.resume",
            "engine.request"} <= names
    reasons = sorted(s["attrs"]["finish_reason"] for s in flat
                     if s["name"] == "engine.request")
    assert "failed" in reasons and "finished" in reasons
    # the dispatch spans carry the routing decision
    dispatches = [s for s in flat if s["name"] == "handle.dispatch"]
    assert len(dispatches) >= 2, "initial dispatch + failover re-dispatch"
    for d in dispatches:
        assert d["attrs"]["strategy"] in ("single", "prefix", "p2c")
        assert d["attrs"]["replica"]
    resume = next(s for s in flat if s["name"] == "handle.resume")
    assert resume["attrs"]["failover"] >= 1
    assert resume["attrs"]["delivered_chunks"] >= 1
    # everything nests under the ONE client root
    (tree_root,) = tree["tree"]
    assert tree_root["name"] == "client.stream"
    # the trace rode in over the fleet endpoint's own summary listing too
    rows = ray_tpu.get(
        ctrl.trace_list.remote(status="failover"), timeout=10)
    assert any(r["trace_id"] == trace_id for r in rows)

    # the SLO monitor is live on the same controller tick
    slo = ray_tpu.get(ctrl.slo_status.remote(), timeout=10)
    assert {s["name"] for s in slo["specs"]} >= {
        "ttft_p99", "availability"}
    assert _wait_for(
        lambda: ray_tpu.get(ctrl.slo_status.remote(), timeout=10)[
            "results"],
        timeout_s=30), "SLO evaluation never produced results"
