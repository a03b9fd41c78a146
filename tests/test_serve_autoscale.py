"""Engine-signal autoscaling, graceful replica drain, and cluster-wide
admission (shedding) — the robustness loop for serve.llm under real
traffic.

Pure-policy tests cover the signal thresholds (snapshot_is_hot/cold,
desired_from_signals, fleet_saturated) and the AutoscalingDecider's
debounce edge cases (direction flip restarts the streak, a settled tick
clears the pending direction, min==max never moves). Engine tests assert
the AutoscalingSnapshot surface and its gauges. Cluster tests run the
tier-1 deterministic chaos storyline: a seeded burst with a mid-stream
replica kill, fleet saturation shedding to HTTP 503 + Retry-After, a
signal-driven scale-up, and a graceful drain that hands an in-flight
stream to a survivor byte-identically.
"""
from __future__ import annotations

import dataclasses
import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from conftest import serve_http_url, shutdown_if_setup_fails

from ray_tpu._private import chaos
from ray_tpu._private.chaos import Fault, FaultPlan
from ray_tpu.serve.autoscaling_policy import (
    AutoscalingDecider,
    desired_from_signals,
    fleet_saturated,
    snapshot_is_cold,
    snapshot_is_hot,
)
from ray_tpu.serve.config import AutoscalingConfig


KILL_PROMPT = [5, 6, 7]
KILL_SAMPLING = dict(max_new_tokens=8, temperature=0.8, seed=42)
KILL_AT_INDEX = 2


# ---------------- pure policy (no cluster, no jax) ----------------

def _cfg(**kw):
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 10)
    return AutoscalingConfig(**kw)


def _snap(**kw):
    base = dict(
        queue_depth=0, queue_wait_p95_s=0.0, kv_pool_pressure=0.0,
        deadline_miss_rate=0.0, rejection_rate=0.0, running=0, prefilling=0,
    )
    base.update(kw)
    return base


def test_snapshot_hot_thresholds():
    cfg = _cfg(upscale_queue_wait_p95_s=0.25, upscale_kv_pressure=0.85)
    assert not snapshot_is_hot(cfg, _snap())
    assert snapshot_is_hot(cfg, _snap(queue_wait_p95_s=0.3))
    assert snapshot_is_hot(cfg, _snap(kv_pool_pressure=0.9))
    # default miss-rate threshold 0.0 means ANY miss is hot
    assert snapshot_is_hot(cfg, _snap(deadline_miss_rate=0.01))
    assert snapshot_is_hot(cfg, _snap(rejection_rate=0.5))
    # just below every threshold stays cold-ish
    assert not snapshot_is_hot(
        cfg, _snap(queue_wait_p95_s=0.2, kv_pool_pressure=0.5))


def test_snapshot_cold_requires_idle_and_low_pressure():
    cfg = _cfg(downscale_kv_pressure=0.5)
    assert snapshot_is_cold(cfg, _snap())
    assert not snapshot_is_cold(cfg, _snap(queue_depth=1))
    assert not snapshot_is_cold(cfg, _snap(running=1))
    assert not snapshot_is_cold(cfg, _snap(prefilling=1))
    assert not snapshot_is_cold(cfg, _snap(kv_pool_pressure=0.6))


def test_desired_from_signals():
    cfg = _cfg(min_replicas=1, max_replicas=4)
    # no snapshots -> hold
    assert desired_from_signals(cfg, [], 2) == 2
    # one hot replica -> +1 (single step; debounce sets the ramp rate)
    assert desired_from_signals(
        cfg, [_snap(), _snap(rejection_rate=1.0)], 2) == 3
    # all cold -> -1
    assert desired_from_signals(cfg, [_snap(), _snap()], 2) == 1
    # mixed (not all cold, none hot) -> hold
    assert desired_from_signals(cfg, [_snap(running=1), _snap()], 2) == 2
    # clamped at both ends
    assert desired_from_signals(cfg, [_snap(rejection_rate=1.0)], 4) == 4
    assert desired_from_signals(cfg, [_snap()], 1) == 1


def test_fleet_saturated_requires_max_hot_and_queueing():
    cfg = _cfg(min_replicas=1, max_replicas=2)
    hot_q = _snap(rejection_rate=1.0, queue_depth=3)
    # below max_replicas: scaling can still help -> never shed
    assert not fleet_saturated(cfg, [hot_q], 1)
    # at max but one replica merely hot without a backlog -> no shed
    assert not fleet_saturated(
        cfg, [hot_q, _snap(rejection_rate=1.0)], 2)
    # at max, every replica hot AND queueing -> shed
    assert fleet_saturated(cfg, [hot_q, hot_q], 2)
    # no snapshots -> fail open (never shed blind)
    assert not fleet_saturated(cfg, [], 2)


def test_decider_direction_flip_restarts_streak():
    cfg = _cfg(upscale_delay_periods=2, downscale_delay_periods=2,
               target_ongoing_requests=1,
               upscale_smoothing_factor=1.0, downscale_smoothing_factor=1.0)
    d = AutoscalingDecider(cfg)
    assert d.decide(10, 2) == 2          # up streak = 1
    assert d.decide(0, 2) == 2           # FLIP down: streak restarts at 1
    assert d._pending_direction == -1 and d._streak == 1
    assert d.decide(0, 2) < 2            # second down tick acts


def test_decider_settled_tick_clears_pending_direction():
    cfg = _cfg(upscale_delay_periods=2, downscale_delay_periods=2,
               target_ongoing_requests=1, upscale_smoothing_factor=1.0)
    d = AutoscalingDecider(cfg)
    assert d.decide(10, 2) == 2          # up streak = 1
    assert d.decide(2, 2) == 2           # at target: settled tick
    assert d._pending_direction == 0 and d._streak == 0
    # the next up tick must start a FRESH streak (not inherit the old one
    # and act immediately)
    assert d.decide(10, 2) == 2
    assert d.decide(10, 2) > 2


def test_decider_min_equals_max_never_moves():
    cfg = _cfg(min_replicas=2, max_replicas=2, upscale_delay_periods=1,
               downscale_delay_periods=1, target_ongoing_requests=1)
    d = AutoscalingDecider(cfg)
    for load in (100, 0, 50, 0, 100):
        assert d.decide(load, 2) == 2
    hot = [_snap(rejection_rate=1.0, queue_depth=1)] * 2
    cold = [_snap()] * 2
    for snaps in (hot, cold, hot):
        assert d.decide_from_signals(snaps, 2) == 2


def test_decider_signal_debounce_prevents_flapping():
    cfg = _cfg(min_replicas=1, max_replicas=4, upscale_delay_periods=2,
               downscale_delay_periods=2)
    d = AutoscalingDecider(cfg)
    hot = [_snap(rejection_rate=1.0)]
    cold = [_snap()]
    # alternating hot/cold ticks never reach the 2-period streak
    for snaps in (hot, cold, hot, cold, hot, cold):
        assert d.decide_from_signals(snaps, 2) == 2
    # two consecutive hot ticks act
    assert d.decide_from_signals(hot, 2) == 2
    assert d.decide_from_signals(hot, 2) == 3


# ---------------- chaos fault-plan round-trips ----------------

def test_fault_plan_round_trips_new_points():
    plan = FaultPlan(seed=13, faults=(
        Fault(point="replica_drain", action="delay", arg=0.05, times=3),
        Fault(point="controller_scale", action="raise",
              when={"deployment": "LLMDeployment", "target": 1}),
        Fault(point="llm.snapshot", action="delay", arg=0.2, times=None),
    ))
    back = FaultPlan.from_json(plan.to_json())
    assert back == plan
    assert json.loads(plan.to_json())["seed"] == 13


def test_delay_fault_jitter_is_seeded():
    """A repeating delay fault jitters its sleep from the PLAN seed, so
    two runs of the same plan produce the same schedule."""

    def sleeps(seed):
        plan = FaultPlan(seed=seed, faults=(
            Fault(point="llm.snapshot", action="delay", arg=0.01, times=None),
        ))
        chaos.install(plan)
        recorded = []

        class _FakeTime:
            sleep = staticmethod(recorded.append)

        real_time = chaos.time
        try:
            # swap the module REFERENCE, never mutate the real time module
            chaos.time = _FakeTime
            for _ in range(4):
                chaos.fire("llm.snapshot")
        finally:
            chaos.time = real_time
            chaos.clear()
        return recorded

    a, b, c = sleeps(3), sleeps(3), sleeps(4)
    assert a == b, "same seed must replay the same jitter schedule"
    assert a != c, "different seed must change the jitter schedule"
    assert all(0.005 <= s <= 0.015 for s in a), "jitter stays in [0.5x, 1.5x]"


# ---------------- engine snapshot surface ----------------

def _model_config():
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    return dataclasses.replace(
        LlamaConfig.tiny(), dtype=jnp.float32, attention="xla")


def _engine(**kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    return LLMEngine(
        EngineConfig(model="llama", model_config=_model_config(), **kw),
        auto_step=False,
    )


@pytest.mark.timeout(120)
def test_engine_autoscaling_snapshot_and_gauges(jax_cpu):
    from ray_tpu.serve.llm import EngineOverloadedError
    from ray_tpu.util import metrics

    eng = _engine(max_batch_size=1, max_prefill_batch=1, max_waiting=2)
    idle = eng.autoscaling_snapshot()
    assert idle["queue_depth"] == 0 and idle["running"] == 0
    assert 0.0 <= idle["kv_pool_pressure"] <= 1.0
    assert idle["rejection_rate"] == 0.0

    s1 = eng.submit([1, 2, 3], max_new_tokens=6)
    s2 = eng.submit([4, 5, 6], max_new_tokens=4)   # waits (batch slot = 1)
    with pytest.raises(EngineOverloadedError):
        eng.submit([7, 8, 9], max_new_tokens=4)    # queue full -> rejected
    eng.step()  # prefill s1 (admission records the queue wait)
    busy = eng.autoscaling_snapshot()
    assert busy["queue_depth"] == 1
    assert busy["rejection_rate"] > 0.0
    assert busy["kv_pool_pressure"] > idle["kv_pool_pressure"]
    collected = metrics.collect()
    assert collected["llm_queue_depth"] == 1
    assert collected["llm_kv_free_blocks"] == busy["kv_free_blocks"]
    assert collected["llm_kv_pool_pressure"] == busy["kv_pool_pressure"]

    for _ in range(200):
        if s1.done and s2.done:
            break
        eng.step()
    assert len(list(s1)) == 6 and len(list(s2)) == 4
    done = eng.autoscaling_snapshot()
    assert done["decode_step_p50_s"] > 0.0
    # the latest snapshot rides along in the debug dump / flight records
    dump = eng.debug_dump()
    assert dump["autoscaling_snapshot"]["queue_depth"] == 0
    assert any(r.get("kind") == "autoscale_snapshot"
               for r in dump["steps"])
    eng.shutdown()


# ---------------- cluster storyline (tier-1 deterministic) ----------------

def _wait_for(predicate, timeout_s=60.0, interval=0.1):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


@pytest.fixture(scope="module")
def as_cluster():
    """Two apps behind one controller, chaos plan exported via env:

    - ``llm-main``: 2 replicas (min==max==2, signal-capable) — the kill
      and shed phases. A tagged request's replica dies after chunk 2.
    - ``llm-as``: min=1/max=2 — the signal-driven upscale and the
      graceful-drain phases (short 2 s drain deadline so an in-flight
      stream outlives it and must hand off).
    """
    import os

    plan = FaultPlan(seed=7, faults=(
        Fault(point="llm.token", action="kill",
              when={"tag": "killme", "index": KILL_AT_INDEX,
                    "resumed": False}),
        # drain-phase streams are throttled ~20-60 ms/chunk (seeded
        # jitter) so they reliably outlive the 2 s drain deadline —
        # tiny-llama's max_seq_len caps streams at ~120 tokens, which
        # would otherwise finish before the deadline fires
        Fault(point="llm.token", action="delay", arg=0.04, times=None,
              when={"tag": "slowme"}),
    ))
    prev = os.environ.get(chaos.ENV_VAR)
    os.environ[chaos.ENV_VAR] = plan.to_json()
    chaos.clear()

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import EngineConfig, build_llm_app

    ray_tpu.init(num_cpus=8)
    with shutdown_if_setup_fails():
        serve.start(http_options={"port": 0})
        main_handle = serve.run(
            build_llm_app(
                # capacity 6 per replica (2 running + 4 queued): the 4-stream
                # kill burst always fits on the survivor, and the shed phase
                # overflows it with a 16-hog fleet
                EngineConfig(
                    model="llama", model_config=_model_config(), seed=0,
                    max_batch_size=2, max_prefill_batch=2, max_waiting=4,
                    block_size=16, num_blocks=256,
                ),
                autoscaling_config=dict(min_replicas=2, max_replicas=2),
            ),
            name="llm-main", route_prefix="/main", timeout_s=300,
        )
        as_handle = serve.run(
            build_llm_app(
                EngineConfig(
                    model="llama", model_config=_model_config(), seed=0,
                    max_batch_size=1, max_prefill_batch=1, max_waiting=1,
                    block_size=16, num_blocks=256,
                ),
                autoscaling_config=dict(
                    min_replicas=1, max_replicas=2,
                    upscale_delay_periods=1, downscale_delay_periods=10_000,
                    # hotness must come ONLY from rejections (probes we
                    # control): queue-wait samples from the drain hand-off
                    # must never re-trigger an upscale after the scale-down
                    upscale_queue_wait_p95_s=30.0,
                ),
                graceful_shutdown_timeout_s=2.0,
            ),
            name="llm-as", route_prefix="/as", timeout_s=300,
        )
        from ray_tpu.serve.controller import CONTROLLER_NAME

        ctrl = ray_tpu.get_actor(CONTROLLER_NAME)
    yield {"main": main_handle, "as": as_handle, "ctrl": ctrl,
           "serve": serve, "ray": ray_tpu}
    serve.shutdown()
    ray_tpu.shutdown()
    chaos.clear()
    if prev is None:
        os.environ.pop(chaos.ENV_VAR, None)
    else:
        os.environ[chaos.ENV_VAR] = prev


def _dep_status(ctrl, app):
    import ray_tpu

    st = ray_tpu.get(ctrl.status.remote(), timeout=30)
    return st.get(app, {}).get("LLMDeployment", {})


def _stream(handle, payload):
    from ray_tpu.serve.llm import stream_tokens

    return stream_tokens(handle, payload)


def _replica_pools_clean(handle) -> bool:
    stats = [s for s in handle.broadcast("stats") if s]
    return bool(stats) and all(
        s["running"] == 0 and s["waiting"] == 0 and s["kv_used_blocks"] == 0
        for s in stats
    )


@pytest.mark.chaos
@pytest.mark.timeout(300)
def test_burst_with_kill_resumes_byte_identical(as_cluster):
    """Seeded burst; the tagged stream's replica dies after chunk 2.
    Every accepted stream (including siblings displaced by the kill,
    whose resume may briefly race the overloaded survivor) completes
    byte-identical to an unfaulted local reference."""
    import numpy as np

    reference_engine = _engine(seed=0)
    rng = np.random.default_rng(7)
    payloads = []
    for i in range(4):
        n = int(rng.integers(3, 10))
        payloads.append({
            "prompt": [int(x) for x in rng.integers(1, 64, n)],
            "request_id": f"burst-{i}",
            "max_new_tokens": 8,
            "temperature": 0.8,
            "seed": 100 + i,
        })
    payloads[0]["chaos_tag"] = "killme"
    refs = [
        reference_engine.generate(
            p["prompt"], max_new_tokens=p["max_new_tokens"],
            temperature=p["temperature"], seed=p["seed"])
        for p in payloads
    ]
    reference_engine.shutdown()

    results: list[dict] = [None] * len(payloads)

    def run(i):
        gen = _stream(as_cluster["main"], payloads[i])
        chunks = list(gen)
        results[i] = {"chunks": chunks, "failovers": gen.failovers}

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(payloads))]
    for i, t in enumerate(threads):
        t.start()
        time.sleep(0.15)  # stagger so P2C spreads the burst
    for t in threads:
        t.join(timeout=240)
    assert all(r is not None for r in results), "a burst stream never finished"
    assert results[0]["failovers"] >= 1, "the chaos kill must force a failover"
    for i, r in enumerate(results):
        idxs = [c["index"] for c in r["chunks"]]
        toks = [c["token"] for c in r["chunks"]]
        assert idxs == list(range(8)), f"stream {i}: gap/dup in {idxs}"
        assert toks == refs[i], f"stream {i}: tokens diverged after failover"
    # the controller replaces the killed replica
    assert _wait_for(
        lambda: _dep_status(as_cluster["ctrl"], "llm-main")
        .get("running_replicas") == 2, timeout_s=120)
    assert _wait_for(lambda: _replica_pools_clean(as_cluster["main"]),
                     timeout_s=60), "burst must leave no KV blocks behind"


@pytest.mark.chaos
@pytest.mark.timeout(300)
def test_saturated_fleet_sheds_503_with_retry_after(as_cluster):
    """Both llm-main replicas hot (rejecting) with a backlog -> the
    controller flips the deployment to shed -> handles fail fast with
    EngineOverloadedError and the HTTP proxy answers 503 + Retry-After.
    Clearing the backlog clears the shed flag."""
    import itertools

    from ray_tpu.exceptions import EngineOverloadedError

    handle = as_cluster["main"]
    ctrl = as_cluster["ctrl"]
    # 16 feeder threads continuously re-dispatch ~120-token hog streams
    # against a fleet capacity of 12 (2 replicas x (2 running +
    # 4 queued)): each replica holds a backlog (queue-wait blows past the
    # 0.25 s hot threshold) and rejects the overflow — every replica hot
    # AND queueing on a max-sized fleet == fleet saturated -> shed
    stop_feeding = threading.Event()
    seq = itertools.count()

    def feeder():
        while not stop_feeding.is_set():
            try:
                for _ in _stream(handle, {
                    "prompt": [1, 2, 3],
                    "request_id": f"hog-{next(seq)}",
                    "max_new_tokens": 120, "temperature": 0.8, "seed": 7,
                }):
                    pass
            except Exception:  # noqa: BLE001 — rejection/shed IS the load
                time.sleep(0.05)

    feeders = [threading.Thread(target=feeder) for _ in range(16)]
    for t in feeders:
        t.start()
    try:
        assert _wait_for(
            lambda: _dep_status(ctrl, "llm-main").get("shedding") is True,
            timeout_s=90, interval=0.3), \
            "saturated fleet never flipped to shedding"

        # router: fresh data-plane dispatches now fail fast, PRE-dispatch.
        # Poll: the router's routing table lags status() by up to the
        # 0.25 s refresh TTL; the message match pins the router path (the
        # engine's own admission rejection words it differently)
        def router_sheds():
            try:
                next(_stream(handle, {"prompt": [8], "max_new_tokens": 2}))
            except EngineOverloadedError as e:
                return "shedding at admission" in str(e)
            except Exception:  # noqa: BLE001 — engine-side rejection
                return False
            return False

        from ray_tpu.util import metrics

        def shed_count():
            return sum(v for k, v in metrics.collect(
                "llm_requests_shed_total").items() if "app=llm-main" in k)

        shed_before = shed_count()
        assert _wait_for(router_sheds, timeout_s=30, interval=0.2), \
            "router never refused a fresh dispatch pre-dispatch"
        # a shed request is counted as shed, apart from the engine's own
        # rejections and from errors
        assert shed_count() > shed_before

        # HTTP proxy: 503 + Retry-After. Polled for the same reason —
        # shed can flicker off while the router refuses the feeders and
        # the admitted backlog drains, before load re-saturates it.
        retry_after = []

        def proxy_503():
            req = urllib.request.Request(
                serve_http_url("/main"),
                data=json.dumps(
                    {"prompt": "x", "max_new_tokens": 2}).encode(),
                headers={"Content-Type": "application/json"},
            )
            try:
                urllib.request.urlopen(req, timeout=60).read()
                return False
            except urllib.error.HTTPError as err:
                if err.code != 503:
                    return False
                retry_after.append(err.headers["Retry-After"])
                return True

        assert _wait_for(proxy_503, timeout_s=30, interval=0.2), \
            "HTTP proxy never returned 503 while the fleet shed"
        # class-aware backoff (PR 17): an un-prioritized request is the
        # "default" class, whose Retry-After is 2 s
        assert retry_after[-1] == "2"
    finally:
        stop_feeding.set()
    for t in feeders:
        t.join(timeout=180)
    assert not any(t.is_alive() for t in feeders), "a feeder thread is stuck"

    # load gone -> the backlog drains; queue_depth hitting 0 clears the
    # shed flag even though the 30 s rejection window is still warm
    assert _wait_for(
        lambda: _dep_status(ctrl, "llm-main").get("shedding") is False,
        timeout_s=90), "shed flag must clear once the backlog drains"
    assert _wait_for(lambda: _replica_pools_clean(handle), timeout_s=60)


@pytest.mark.chaos
@pytest.mark.timeout(300)
def test_signal_upscale_then_graceful_drain_hands_off_stream(as_cluster):
    """llm-as storyline: saturation signals scale 1 -> 2; then a
    scale_deployment drain back to 1 while both replicas hold an
    in-flight stream — the drained replica outlives its 2 s deadline,
    is killed, and its stream hands off to the survivor byte-identically."""
    import ray_tpu

    handle = as_cluster["as"]
    ctrl = as_cluster["ctrl"]
    assert _dep_status(ctrl, "llm-as").get("target_replicas") == 1

    # phase 1: saturate the single replica -> rejection signal -> upscale
    hog = _stream(handle, {"prompt": [1, 2, 3], "request_id": "as-hog",
                           "max_new_tokens": 120, "temperature": 0.8,
                           "seed": 3})
    next(hog)  # hog holds the single batch slot

    # probes pile into the 1-deep waiting queue behind the hog; the
    # overflow rejections are the saturation signal. Fire-and-forget
    # threads: an ADMITTED probe's first token blocks behind the hog,
    # which must not stall the polling loop.
    def _probe():
        try:
            for _ in _stream(handle, {"prompt": [9], "max_new_tokens": 2}):
                pass
        except Exception:  # noqa: BLE001 — rejection IS the signal
            pass

    def upscaled():
        threading.Thread(target=_probe, daemon=True).start()
        return _dep_status(ctrl, "llm-as").get("target_replicas") == 2

    assert _wait_for(upscaled, timeout_s=60, interval=0.3), \
        "engine signals never drove a scale-up"
    assert _wait_for(
        lambda: _dep_status(ctrl, "llm-as").get("running_replicas") == 2,
        timeout_s=120), "second replica never became RUNNING"
    handle.broadcast("cancel", "as-hog")
    try:  # cancelled mid-stream raises; a hog that already finished its
        for _ in hog:  # 120 tokens just completes — either is fine, the
            pass  # rejections it caused are what drove the upscale
    except Exception:  # noqa: BLE001
        pass
    assert _wait_for(lambda: _replica_pools_clean(handle), timeout_s=60)

    # cool-down: wait out the 30 s rejection-rate window so the phase-1
    # saturation signals can't re-upscale the fleet after the drain
    def fleet_cold():
        snaps = [s for s in handle.broadcast("autoscaling_snapshot") if s]
        return len(snaps) == 2 and all(
            s["rejection_rate"] == 0.0 and s["queue_depth"] == 0
            for s in snaps
        )

    assert _wait_for(fleet_cold, timeout_s=60, interval=1.0), \
        "rejection window never cooled"

    # phase 2: one long stream per replica (the second dispatch lands on
    # the idle replica because the first is still in flight)
    reference_engine = _engine(seed=0)
    # "slowme" throttles each chunk 20-60 ms (seeded chaos delay): a
    # 120-token stream lives ~5 s, comfortably past the 2 s drain
    # deadline, so the victim is reliably killed mid-stream
    payloads = [
        {"prompt": [11, 12, 13], "request_id": "drain-a",
         "max_new_tokens": 120, "temperature": 0.8, "seed": 21,
         "chaos_tag": "slowme"},
        {"prompt": [14, 15, 16], "request_id": "drain-b",
         "max_new_tokens": 120, "temperature": 0.8, "seed": 22,
         "chaos_tag": "slowme"},
    ]
    refs = [
        reference_engine.generate(
            p["prompt"], max_new_tokens=p["max_new_tokens"],
            temperature=p["temperature"], seed=p["seed"])
        for p in payloads
    ]
    reference_engine.shutdown()
    gens, firsts = [], []
    for p in payloads:
        g = _stream(handle, p)
        firsts.append(next(g))  # first chunk: the stream is live on its
        gens.append(g)  # replica, so P2C sends the next one elsewhere

    # phase 3: drain back to 1 — the victim still serves a stream, so it
    # exceeds the 2 s drain deadline and is killed mid-drain; its stream
    # must fail over and finish byte-identically
    assert ray_tpu.get(
        ctrl.scale_deployment.remote("llm-as", "LLMDeployment", 1),
        timeout=30)
    saw_draining = []

    def drained():
        d = _dep_status(ctrl, "llm-as")
        if d.get("draining_replicas", 0) > 0:
            saw_draining.append(True)
        return (d.get("running_replicas") == 1
                and d.get("draining_replicas", 0) == 0)

    assert _wait_for(drained, timeout_s=120), "drain never completed"
    assert saw_draining, "the scale-down must pass through DRAINING"

    results = []
    for first, g in zip(firsts, gens):
        chunks = [first] + [c for c in g]
        results.append({"chunks": chunks, "failovers": g.failovers})
    assert sum(r["failovers"] for r in results) >= 1, \
        "the mid-drain kill must force at least one hand-off"
    for r, ref, p in zip(results, refs, payloads):
        got = [c["token"] for c in r["chunks"]]
        idxs = [c["index"] for c in r["chunks"]]
        assert idxs == list(range(p["max_new_tokens"])), \
            f"{p['request_id']}: dropped/duplicated chunks"
        assert got == ref, f"{p['request_id']}: tokens diverged across drain"
    # min_replicas floor respected; survivor pool is leak-free
    assert _dep_status(ctrl, "llm-as").get("target_replicas") == 1
    assert _wait_for(lambda: _replica_pools_clean(handle), timeout_s=60)

    # a draining/gone replica never turns a FRESH request into a failure
    # loop: fresh dispatch after the drain just works
    tail = list(_stream(handle, {"prompt": [1], "max_new_tokens": 2,
                                 "temperature": 0.0}))
    assert len(tail) == 2

    # drain accounting: the EngineOverloadedError count for draining
    # replicas is visible on the controller gauge path via status()
    assert _dep_status(ctrl, "llm-as").get("shedding") is False
