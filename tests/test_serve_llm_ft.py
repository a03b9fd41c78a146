"""Failure semantics of the serving stack (ISSUE 2): admission control,
deadlines, cancellation, engine fail-closed (step exception + wedged-step
watchdog), and mid-stream replica failover with byte-identical resumed
streams — all driven by deterministic ray_tpu._private.chaos fault plans
rather than hand-rolled os._exit sprinkling.

Engine-level tests drive step() directly (auto_step=False) or a real
background stepper; cluster tests run two LLM replicas plus a
deliberately tiny-capacity app behind the HTTP/gRPC proxies and assert
the degradation surface (503 + Retry-After / RESOURCE_EXHAUSTED).
"""
from __future__ import annotations

import dataclasses
import json
import time
import urllib.error
import urllib.request

import pytest

from conftest import serve_http_url, shutdown_if_setup_fails

from ray_tpu._private import chaos
from ray_tpu._private.chaos import Fault, FaultPlan


# verified byte-identical resume vector: kill after 3 tokens of 8
KILL_PROMPT = [5, 6, 7]
KILL_SAMPLING = dict(max_new_tokens=8, temperature=0.8, seed=42)
KILL_AT_INDEX = 2  # chunk index after which the serving replica dies


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


def _pool_is_clean(eng) -> bool:
    return (
        len(eng.cache._free) == eng.cache.cfg.usable_blocks
        and eng.cache._reserved == 0
    )


# ------------------------------------------------------------ admission

@pytest.mark.timeout(120)
def test_overload_rejects_when_queue_full(jax_cpu):
    from ray_tpu.serve.llm import EngineOverloadedError
    from ray_tpu.util import metrics

    eng = _engine(max_waiting=2)
    streams = [eng.submit([1, 2, 3], max_new_tokens=4) for _ in range(2)]
    before = metrics.collect().get("llm_requests_rejected_total", 0)
    for _ in range(3):
        with pytest.raises(EngineOverloadedError):
            eng.submit([1, 2, 3], max_new_tokens=4)
    assert eng.stats()["rejected_total"] == 3
    assert metrics.collect()["llm_requests_rejected_total"] == before + 3
    # rejected requests left no state behind: the queued ones still run
    for _ in range(50):
        if all(s.done for s in streams):
            break
        eng.step()
    assert all(len(list(s)) == 4 for s in streams)
    assert _pool_is_clean(eng)


@pytest.mark.timeout(120)
def test_overload_rejects_on_block_budget(jax_cpu):
    from ray_tpu.serve.llm import EngineOverloadedError

    # each request needs ceil((3+13)/16) = 1 block of worst-case budget
    eng = _engine(max_waiting_blocks=2)
    eng.submit([1, 2, 3], max_new_tokens=13)
    eng.submit([1, 2, 3], max_new_tokens=13)
    with pytest.raises(EngineOverloadedError):
        eng.submit([1, 2, 3], max_new_tokens=13)
    # admission drains the budget: after a step the queue has capacity again
    eng.step()
    eng.submit([1, 2, 3], max_new_tokens=13)


# ------------------------------------------------------------ deadlines

@pytest.mark.timeout(120)
def test_deadline_expiry_mid_decode_frees_blocks(jax_cpu):
    from ray_tpu.serve.llm import DeadlineExceededError

    eng = _engine()
    # compiled first: the deadline is to lapse mid-generation, not inside
    # the first step's compile (the first token reaches the stream at the
    # prefill's reconcile, behind the first decode step's launch)
    eng.generate([1, 2, 3], max_new_tokens=3)
    while eng.step():
        pass
    s = eng.submit([1, 2, 3], max_new_tokens=50, deadline_s=0.15)
    eng.step()  # prefill
    eng.step()  # decode (emits first token)
    time.sleep(0.2)  # let the deadline lapse mid-generation
    eng.step()  # expiry sweep evicts the sequence
    got = []
    with pytest.raises(DeadlineExceededError):
        for tok in s:
            got.append(tok)
    assert 1 <= len(got) < 50, "should fail after SOME tokens, before all"
    assert _pool_is_clean(eng)
    assert eng.stats()["deadline_exceeded_total"] == 1


# ---------------------------------------------------------- cancellation

@pytest.mark.timeout(120)
def test_cancel_frees_every_reserved_block(jax_cpu):
    from ray_tpu.serve.llm import RequestCancelledError

    eng = _engine()
    s = eng.submit([1, 2, 3], max_new_tokens=40)
    eng.step()  # prefill: blocks allocated, worst case reserved
    assert not _pool_is_clean(eng)
    assert eng.cancel(s.request_id) is True
    # the prefill is still in flight with the row's first token (its sync
    # waits behind the next launch): the blocks go back exactly once, at
    # its reconcile, which the next step makes
    assert eng.stats()["decode_inflight"] == 1 and not _pool_is_clean(eng)
    assert eng.step() and not eng.step()
    assert _pool_is_clean(eng), "cancel must return allocation AND reservation"
    with pytest.raises(RequestCancelledError):
        list(s)
    assert eng.cancel(s.request_id) is False  # idempotent
    assert eng.stats()["cancelled_total"] == 1
    # a WAITING (never admitted) request cancels cleanly too
    w = eng.submit([4, 5, 6], max_new_tokens=40)
    assert eng.cancel(w.request_id) is True
    assert eng.stats()["waiting"] == 0
    assert _pool_is_clean(eng)


# ------------------------------------------------------------- shutdown

@pytest.mark.timeout(180)
def test_shutdown_is_leak_free_and_fails_pending_streams(jax_cpu):
    from ray_tpu.serve.llm import RequestCancelledError

    for _ in range(3):
        eng = _engine(auto_step=False)
        streams = [eng.submit([i + 1, 2, 3], max_new_tokens=30)
                   for i in range(3)]
        eng.step()  # some running, some possibly waiting
        eng.shutdown()
        assert _pool_is_clean(eng), "shutdown must return every KV block"
        for s in streams:
            with pytest.raises(RequestCancelledError):
                # drain any pre-shutdown tokens, then hit the error
                for _tok in s:
                    pass
        with pytest.raises(RuntimeError):
            eng.submit([1], max_new_tokens=1)
        eng.shutdown()  # idempotent


# ---------------------------------------------------- engine fail-closed

@pytest.mark.chaos
@pytest.mark.timeout(120)
def test_step_exception_fails_all_streams(jax_cpu, chaos_plan):
    from ray_tpu.serve.llm import EngineDiedError

    chaos_plan(FaultPlan(faults=(
        Fault(point="engine.decode", action="raise", after=2),
    )))
    eng = _engine(auto_step=True)
    s = eng.submit([1, 2, 3], max_new_tokens=20)
    with pytest.raises(EngineDiedError) as ei:
        for _tok in s:
            pass
    assert isinstance(ei.value.__cause__, chaos.ChaosFault)
    assert eng.failed and eng.stats()["failed"]
    assert _pool_is_clean(eng), "failure must reset the cache"
    with pytest.raises(EngineDiedError):
        eng.submit([1], max_new_tokens=1)
    eng.shutdown()


@pytest.mark.chaos
@pytest.mark.timeout(120)
def test_wedged_step_watchdog_fails_streams_without_the_lock(jax_cpu,
                                                             chaos_plan):
    """A decode that never returns (chaos delay >> step_timeout_s) holds
    the scheduler lock; the watchdog must still fail every in-flight
    stream — lock-free — instead of letting clients block forever."""
    from ray_tpu.serve.llm import EngineDiedError

    chaos_plan(FaultPlan(faults=(
        Fault(point="engine.decode", action="delay", arg=3.0, after=2),
    )))
    eng = _engine(auto_step=True, step_timeout_s=0.3)
    s = eng.submit([1, 2, 3], max_new_tokens=20)
    t0 = time.monotonic()
    with pytest.raises(EngineDiedError):
        for _tok in s:
            pass
    # the stream failed while the step was STILL wedged (3s sleep)
    assert time.monotonic() - t0 < 2.5
    assert eng.failed
    with pytest.raises(EngineDiedError):
        eng.submit([1], max_new_tokens=1)
    eng.shutdown()


# -------------------------------------------------- deterministic resume

@pytest.mark.timeout(120)
def test_engine_resume_is_byte_identical(jax_cpu):
    """The failover contract at the engine level: re-prefilling
    prompt + generated-so-far on a FRESH engine with start_index set
    reproduces the remaining tokens exactly (one RNG uniform per token)."""
    full = _engine().generate(KILL_PROMPT, **KILL_SAMPLING)
    assert len(full) == KILL_SAMPLING["max_new_tokens"]
    k = KILL_AT_INDEX + 1
    resumed = _engine().generate(
        KILL_PROMPT + full[:k],
        max_new_tokens=KILL_SAMPLING["max_new_tokens"] - k,
        temperature=KILL_SAMPLING["temperature"],
        seed=KILL_SAMPLING["seed"],
        start_index=k,
    )
    assert resumed == full[k:]


# ------------------------------------------------------------- cluster

@pytest.fixture(scope="module")
def ft_cluster():
    """Two-replica LLM app + a tiny-capacity app + a slow unary app, with
    a chaos plan exported through the environment so every replica worker
    inherits it: the tagged request's replica dies after chunk index 2,
    and every decode step is slightly delayed (gives the overload test a
    window while the hog request is running)."""
    import os

    plan = FaultPlan(seed=7, faults=(
        Fault(point="llm.token", action="kill",
              when={"tag": "killme", "index": KILL_AT_INDEX,
                    "resumed": False}),
        Fault(point="engine.decode", action="delay", arg=0.02, times=None),
    ))
    prev = os.environ.get(chaos.ENV_VAR)
    os.environ[chaos.ENV_VAR] = plan.to_json()
    chaos.clear()  # force re-read of the env plan in THIS process too

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import EngineConfig, build_llm_app

    ray_tpu.init(num_cpus=8)
    with shutdown_if_setup_fails():
        serve.start(http_options={"port": 0}, grpc_options={"port": 0})
        ft_handle = serve.run(
            build_llm_app(
                EngineConfig(model="llama", model_config=_model_config(), seed=0),
                num_replicas=2,
            ),
            name="llm-ft", route_prefix="/llmft", timeout_s=180,
        )
        tiny_handle = serve.run(
            build_llm_app(
                EngineConfig(
                    model="llama", model_config=_model_config(), seed=0,
                    max_batch_size=1, max_prefill_batch=1, max_waiting=1,
                ),
            ),
            name="llm-tiny", route_prefix="/tiny", timeout_s=180,
        )

        @serve.deployment
        class Slow:
            def __call__(self, payload):
                time.sleep(0.8)
                return "done"

        slow_handle = serve.run(Slow.bind(), name="slow", route_prefix="/slow",
                                timeout_s=180)
    yield serve, {"ft": ft_handle, "tiny": tiny_handle, "slow": slow_handle}
    serve.shutdown()
    ray_tpu.shutdown()
    chaos.clear()
    if prev is None:
        os.environ.pop(chaos.ENV_VAR, None)
    else:
        os.environ[chaos.ENV_VAR] = prev


def _tiny_stats(handle) -> dict:
    return handle.stats.remote().result(timeout=60)


def _wait_for(predicate, timeout_s=30.0, interval=0.1):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


@pytest.mark.chaos
@pytest.mark.timeout(300)
def test_replica_death_mid_stream_resumes_byte_identical(ft_cluster):
    """Acceptance: kill the serving replica after N streamed tokens; the
    client stream completes byte-identical to an uninterrupted run."""
    from ray_tpu.serve.llm import stream_tokens

    serve, handles = ft_cluster
    # uninterrupted reference from a local engine with the same config and
    # seed — replicas init params from the identical PRNG key
    reference = _engine().generate(KILL_PROMPT, **KILL_SAMPLING)

    gen = stream_tokens(handles["ft"], {
        "prompt": KILL_PROMPT,
        "request_id": "kill-req-1",
        "chaos_tag": "killme",
        **KILL_SAMPLING,
    })
    chunks = list(gen)
    assert gen.failovers >= 1, "the chaos kill should have forced a failover"
    assert [c["index"] for c in chunks] == list(
        range(KILL_SAMPLING["max_new_tokens"]))
    assert [c["token"] for c in chunks] == reference
    assert all(c["request_id"] == "kill-req-1" for c in chunks)
    # the surviving replica recorded the resume
    stats = [s for s in handles["ft"].broadcast("stats") if s]
    assert sum(s.get("requests_resumed", 0) for s in stats) >= 1


@pytest.mark.chaos
@pytest.mark.timeout(300)
def test_overload_degrades_to_503_and_resource_exhausted(ft_cluster):
    """Acceptance: drive the tiny engine past capacity -> HTTP 503 with
    Retry-After and gRPC RESOURCE_EXHAUSTED, llm_requests_rejected
    incrementing; cancelling the hog returns every KV block."""
    import grpc

    serve, handles = ft_cluster
    tiny = handles["tiny"]

    # occupy the single batch slot with a slow request (chaos delays every
    # decode step), then fill the 1-deep waiting queue
    hog = tiny.remote({"prompt": [1, 2, 3], "max_new_tokens": 100,
                       "request_id": "hog1"})
    first = next(iter(hog))
    assert first["index"] == 0
    queued = tiny.remote({"prompt": [4, 5, 6], "max_new_tokens": 4,
                          "request_id": "q1"})
    assert _wait_for(lambda: _tiny_stats(tiny)["waiting"] >= 1), \
        "queued request never reached the admission queue"

    # HTTP: overload -> 503 + Retry-After, decided BEFORE headers
    req = urllib.request.Request(
        serve_http_url("/tiny"),
        data=json.dumps({"prompt": "x", "max_new_tokens": 4}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with pytest.raises(urllib.error.HTTPError) as http_err:
        urllib.request.urlopen(req, timeout=60)
    assert http_err.value.code == 503
    # class-aware backoff (PR 17): an un-prioritized request is the
    # "default" class, whose Retry-After is 2 s
    assert http_err.value.headers["Retry-After"] == "2"

    # gRPC: overload -> RESOURCE_EXHAUSTED
    ch = grpc.insecure_channel(f"127.0.0.1:{serve.grpc_port()}")
    stream = ch.unary_stream(
        "/ray_tpu.serve.ServeAPI/Stream",
        request_serializer=lambda b: b,
        response_deserializer=lambda b: b,
    )
    with pytest.raises(grpc.RpcError) as grpc_err:
        list(stream(
            json.dumps({"prompt": "x", "max_new_tokens": 4}).encode(),
            metadata=(("application", "llm-tiny"),), timeout=60,
        ))
    ch.close()
    assert grpc_err.value.code() == grpc.StatusCode.RESOURCE_EXHAUSTED
    assert _tiny_stats(tiny)["rejected_total"] >= 2

    # cancel the hog (broadcast: routing may have hidden its replica) —
    # its stream fails and every reserved block returns to the pool
    assert any(tiny.broadcast("cancel", "hog1"))
    with pytest.raises(Exception, match="(?i)cancel"):
        for _chunk in hog:
            pass
    assert [c["index"] for c in queued] == list(range(4))  # queue drains
    assert _wait_for(lambda: (
        lambda s: s["running"] == 0 and s["waiting"] == 0
        and s["kv_used_blocks"] == 0
    )(_tiny_stats(tiny))), "cancellation must free every KV block"
    assert _tiny_stats(tiny)["cancelled_total"] >= 1


@pytest.mark.timeout(180)
def test_http_deadline_maps_to_504(ft_cluster):
    serve, _ = ft_cluster
    req = urllib.request.Request(
        serve_http_url("/tiny"),
        data=json.dumps({"prompt": "x", "max_new_tokens": 4,
                         "deadline_s": 0.0}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with pytest.raises(urllib.error.HTTPError) as http_err:
        urllib.request.urlopen(req, timeout=60)
    assert http_err.value.code == 504


@pytest.mark.timeout(180)
def test_router_sweep_reclaims_inflight_after_get_timeout(ft_cluster):
    """Satellite: the router's in-flight count survives a GetTimeoutError
    (the request IS still running) but is reclaimed by the sweep once the
    replica finishes — a timed-out replica must not look loaded forever."""
    from ray_tpu.exceptions import GetTimeoutError

    _, handles = ft_cluster
    handle = handles["slow"]
    router = handle._router
    resp = handle.remote(None)
    with pytest.raises(GetTimeoutError):
        resp.result(timeout=0.05)
    assert sum(router._inflight.values()) >= 1, \
        "timed-out call must still count as in-flight (it IS running)"

    def reclaimed():
        router._refresh(force=True)  # refresh runs the sweep
        return sum(router._inflight.values()) == 0

    assert _wait_for(reclaimed, timeout_s=30), \
        "sweep never reclaimed the in-flight count after completion"
