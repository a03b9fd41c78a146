"""Priority-preemptive serving (ISSUE 17): pause-to-host-tier KV
preemption and per-class graceful degradation under overload.

The contract under test is LOSSLESSNESS THROUGH A PAUSE: a batch-class
stream preempted under pressure (KV chain demoted through the host-tier
funnel, request parked with zero device blocks) and resumed later is
byte-identical to an unpreempted run — greedy AND temperature/top-p, for
both model families, on the single-device AND tp/fsdp-sharded executor.
On top of that: exactly-once block accounting through cancel and
deadline expiry while parked, the starvation-aging floor (batch always
finishes, and a once-parked stream becomes non-preemptible), the
``preempt_exhausted`` latch and per-class snapshot fields the
class-aware shed policy keys on, the per-class proxy Retry-After map,
and a chaos storyline: the replica holding a parked stream dies at the
resume instant and the client's failover resume is still byte-identical.

Engine tests drive step() directly (auto_step=False); parity runs f32 +
XLA attention like the rest of the serving suite.
"""
from __future__ import annotations

import dataclasses
import time

import pytest

from conftest import shutdown_if_setup_fails

from ray_tpu._private import chaos
from ray_tpu._private.chaos import Fault, FaultPlan


# verified preemption vector: a 6-token batch prompt generating 16 under
# an interactive flood on a 24-block / block_size-4 pool
BATCH_PROMPT = [5, 6, 7, 8, 9, 11]
BATCH_NEW = 16
# aggressive thresholds so the tiny CPU engines preempt deterministically
PREEMPTION = dict(kv_pressure=0.5, queue_wait_s=0.05, resume_pressure=0.4)

SAMPLINGS = [
    dict(),                                     # greedy
    dict(temperature=0.8, top_p=0.9, seed=7),   # nucleus
]


def _f32(cfg):
    import jax.numpy as jnp

    return dataclasses.replace(cfg, dtype=jnp.float32, attention="xla")


def _model_config(family="llama"):
    if family == "gpt":
        from ray_tpu.models.gpt import GPTConfig

        return _f32(GPTConfig.tiny())
    from ray_tpu.models.llama import LlamaConfig

    return _f32(LlamaConfig.tiny())


def _engine(family="llama", mc=None, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 24)
    return LLMEngine(
        EngineConfig(
            model=family,
            model_config=mc if mc is not None else _model_config(family),
            **kw,
        ),
        auto_step=False,
    )


def _drain(eng, streams, steps=1200):
    for _ in range(steps):
        if all(s.done for s in streams):
            break
        if not eng.step():
            # idle with parked streams: only the resume-pressure /
            # aging clock is in the way — let it advance
            time.sleep(0.02)
    while eng.step():  # reconcile any in-flight step (lag-1 drain)
        pass


def _flood(eng, n, *, max_new=8, seed0=100):
    return [
        eng.submit([13 + i, 4, 5], max_new_tokens=max_new,
                   priority="interactive", temperature=0.8, seed=seed0 + i)
        for i in range(n)
    ]


def _step_until(eng, predicate, steps=400):
    for _ in range(steps):
        if predicate():
            return True
        eng.step()
        time.sleep(0.005)
    return predicate()


def _pool_is_clean(eng) -> bool:
    return (
        len(eng.cache._free) + len(eng.cache._lru)
        == eng.cache.cfg.usable_blocks
        and eng.cache._reserved == 0
    )


# --------------------------------------------- preempt/resume identity

@pytest.mark.timeout(240)
@pytest.mark.parametrize("family", ["gpt", "llama"])
@pytest.mark.parametrize("sampling", SAMPLINGS,
                         ids=["greedy", "nucleus"])
def test_preempt_resume_byte_identical(jax_cpu, family, sampling):
    """A batch stream paused under an interactive flood and resumed
    after it completes the same tokens as an unpreempted engine."""
    ref = _engine(family).generate(
        BATCH_PROMPT, max_new_tokens=BATCH_NEW, **sampling)

    eng = _engine(family, preemption=dict(PREEMPTION))
    batch = eng.submit(BATCH_PROMPT, max_new_tokens=BATCH_NEW,
                       priority="batch", **sampling)
    eng.step()  # prefill — batch is now RUNNING
    eng.step()  # a decode step: some tokens stream before the pause
    inter = _flood(eng, 6)
    time.sleep(PREEMPTION["queue_wait_s"] + 0.02)
    _drain(eng, [batch] + inter)

    assert eng.stats()["preemptions_total"] >= 1, \
        "the flood should have forced at least one preemption"
    assert eng.stats()["preempted"] == 0
    assert list(batch) == ref
    for s in inter:
        assert len(list(s)) == 8
    assert _pool_is_clean(eng), "exactly-once accounting through the pause"
    eng.shutdown()


@pytest.mark.timeout(240)
def test_preempt_resume_byte_identical_sharded(jax_cpu):
    """Same pause/resume identity through the GSPMD ShardedExecutor
    (tp=2/fsdp=2 on the 8-virtual-device CPU mesh), both samplings."""
    mc = _model_config("llama")
    for sampling in SAMPLINGS:
        ref = _engine("llama", mc).generate(
            BATCH_PROMPT, max_new_tokens=BATCH_NEW, **sampling)
        eng = _engine("llama", mc, tp=2, fsdp=2,
                      preemption=dict(PREEMPTION))
        assert eng.stats()["executor"]["executor"] == "sharded"
        batch = eng.submit(BATCH_PROMPT, max_new_tokens=BATCH_NEW,
                           priority="batch", **sampling)
        eng.step()
        eng.step()
        inter = _flood(eng, 6)
        time.sleep(PREEMPTION["queue_wait_s"] + 0.02)
        _drain(eng, [batch] + inter)
        assert eng.stats()["preemptions_total"] >= 1
        assert list(batch) == ref
        assert _pool_is_clean(eng)
        eng.shutdown()


@pytest.mark.timeout(240)
def test_preempt_composes_with_structured_output(jax_cpu):
    """A grammar-constrained batch stream parks with its FSM cursor
    intact and resumes byte-identical — and still valid JSON-mode."""
    from ray_tpu.serve.llm import structured

    # a seed whose JSON runs on inside a string: under seed 7 these
    # weights write `{}` and the stream has ended before the flood
    # arrives, so nothing is left to pause
    ref_eng = _engine("llama")
    ref = ref_eng.generate(BATCH_PROMPT, max_new_tokens=BATCH_NEW,
                           temperature=0.8, seed=10,
                           structured="json")
    assert len(ref) == BATCH_NEW, "the vector must outlive the flood"
    eng = _engine("llama", preemption=dict(PREEMPTION))
    batch = eng.submit(BATCH_PROMPT, max_new_tokens=BATCH_NEW,
                       priority="batch", temperature=0.8, seed=10,
                       structured="json")
    eng.step()
    eng.step()
    inter = _flood(eng, 6)
    time.sleep(PREEMPTION["queue_wait_s"] + 0.02)
    _drain(eng, [batch] + inter)
    assert eng.stats()["preemptions_total"] >= 1
    toks = list(batch)
    assert toks == ref
    dfa = structured.compile_grammar(
        structured.parse_response_format("json"),
        eng.model_cfg.vocab_size, eng.cfg.eos_id)
    cur = structured.FSMCursor(dfa)
    assert all(cur.advance(t) for t in toks if t != eng.cfg.eos_id)
    eng.shutdown()


@pytest.mark.timeout(240)
def test_preemption_collapses_the_lag(jax_cpu):
    """A pause syncs every step program in flight first (a decode step,
    and a prefill whose sync waited behind it): the victim's blocks go
    back with nothing queued, so none is quarantined, and the stream is
    parked with every token it was owed."""
    eng = _engine(preemption=dict(PREEMPTION))
    frees = {}
    free = eng.cache.free

    def watched(seq_id, **kw):
        frees.setdefault(seq_id, (eng.stats()["decode_inflight"],
                                  kw.get("quarantine")))
        return free(seq_id, **kw)

    eng.cache.free = watched
    batch, inter = _park_one(eng)
    assert eng.stats()["steps_inflight_high_water"] == 2
    assert frees[batch.request_id] == (0, False), frees
    assert eng.cache.debug_snapshot()["quarantined_blocks"] == 0
    _drain(eng, [batch] + inter)
    assert list(batch) == _engine().generate(BATCH_PROMPT,
                                             max_new_tokens=BATCH_NEW)
    assert _pool_is_clean(eng)
    eng.shutdown()


# --------------------------------------------- block hygiene while parked

def _park_one(eng, **sampling):
    """Submit a batch stream, get it running, then flood until the
    scheduler parks it. Returns (batch_stream, flood_streams)."""
    batch = eng.submit(BATCH_PROMPT, max_new_tokens=BATCH_NEW,
                       priority="batch", **sampling)
    eng.step()
    eng.step()
    inter = _flood(eng, 6)
    time.sleep(PREEMPTION["queue_wait_s"] + 0.02)
    assert _step_until(eng, lambda: eng.stats()["preempted"] == 1), \
        "batch stream never parked"
    return batch, inter


@pytest.mark.timeout(240)
def test_cancel_while_parked_is_exactly_once(jax_cpu):
    """Cancelling a PREEMPTED stream releases nothing twice: the park
    already freed every device block, eviction just unparks."""
    from ray_tpu.serve.llm import RequestCancelledError

    eng = _engine("llama", preemption=dict(PREEMPTION))
    batch, inter = _park_one(eng)
    assert eng.cancel(batch.request_id) is True
    assert eng.stats()["preempted"] == 0
    with pytest.raises(RequestCancelledError):
        list(batch)
    assert eng.cancel(batch.request_id) is False  # idempotent
    _drain(eng, inter)
    assert all(len(list(s)) == 8 for s in inter)
    assert _pool_is_clean(eng), \
        "cancel of a parked stream must not double-free its blocks"
    eng.shutdown()


@pytest.mark.timeout(240)
def test_deadline_expiry_while_parked(jax_cpu):
    """A parked stream's deadline still fires: the sweep reaches the
    preempted list and the stream fails with DeadlineExceededError."""
    from ray_tpu.serve.llm import DeadlineExceededError

    eng = _engine("llama", preemption=dict(PREEMPTION))
    batch, inter = _park_one(eng, deadline_s=0.5)
    time.sleep(0.55)  # lapse while parked
    eng.step()        # expiry sweep
    got = []
    with pytest.raises(DeadlineExceededError):
        for tok in batch:
            got.append(tok)
    assert len(got) < BATCH_NEW
    assert eng.stats()["preempted"] == 0
    assert eng.stats()["deadline_exceeded_total"] == 1
    _drain(eng, inter)
    assert _pool_is_clean(eng)
    eng.shutdown()


@pytest.mark.timeout(240)
def test_shutdown_with_parked_streams_is_leak_free(jax_cpu):
    """shutdown() fans out to parked streams too — they fail like every
    other pending stream instead of hanging their consumers forever."""
    from ray_tpu.serve.llm import RequestCancelledError

    eng = _engine("llama", preemption=dict(PREEMPTION))
    batch, inter = _park_one(eng)
    eng.shutdown()
    with pytest.raises(RequestCancelledError):
        list(batch)
    assert eng.stats()["preempted"] == 0


# ------------------------------------------------------ starvation floor

@pytest.mark.timeout(240)
def test_starvation_aging_floor(jax_cpu):
    """Under a sustained interactive flood, a parked batch stream ages
    past the floor, resumes REGARDLESS of pressure, is never preempted
    a second time (anti-thrash), and completes byte-identical."""
    ref = _engine("llama").generate(BATCH_PROMPT, max_new_tokens=BATCH_NEW)

    # resume_pressure below any pressure: nothing resumes but by its age.
    # (With every prefill shape made before traffic no step stalls for a
    # compile any more, and between two waves of this small flood the pool
    # stands empty for a step: a stream resumed by THAT is not yet aged.)
    pc = dict(PREEMPTION, aging_s=0.4, resume_pressure=-1.0)
    eng = _engine("llama", preemption=pc)
    batch = eng.submit(BATCH_PROMPT, max_new_tokens=BATCH_NEW,
                       priority="batch")
    eng.step()
    eng.step()
    inter = list(_flood(eng, 6))
    time.sleep(pc["queue_wait_s"] + 0.02)
    assert _step_until(eng, lambda: eng.stats()["preempted"] == 1)
    # keep interactive pressure on well past the aging floor: the batch
    # stream must come back and finish THROUGH the flood, not after it
    seed = 500
    deadline = time.monotonic() + 20.0
    while not batch.done and time.monotonic() < deadline:
        if eng.stats()["waiting"] < 2:
            inter.extend(_flood(eng, 2, seed0=seed))
            seed += 2
        eng.step()
    assert batch.done, "aged batch stream starved under the flood"
    assert batch._request.preempt_count == 1, \
        "a once-parked stream must not be preempted again"
    _drain(eng, inter)
    assert list(batch) == ref
    assert _pool_is_clean(eng)
    eng.shutdown()


# ------------------------------------- exhaustion latch & shed policy

@pytest.mark.timeout(240)
def test_preempt_exhausted_latch_and_class_snapshot(jax_cpu):
    """When pressure holds but no running stream is outranked by a
    waiter, the engine latches preempt_exhausted and exports the
    per-class queue depth — the inputs to class-aware shedding."""
    eng = _engine("llama", num_blocks=12, preemption=dict(PREEMPTION))
    # interactive hogs: fill the pool so the next interactive cannot fit
    hogs = [
        eng.submit([21 + i, 3, 4], max_new_tokens=24,
                   priority="interactive", temperature=0.8, seed=60 + i)
        for i in range(2)
    ]
    eng.step()
    waiter = eng.submit([31, 3, 4, 5], max_new_tokens=24,
                        priority="interactive", temperature=0.8, seed=70)
    time.sleep(PREEMPTION["queue_wait_s"] + 0.02)
    assert _step_until(
        eng, lambda: eng.stats()["preempt_exhausted"], steps=60)
    snap = eng.autoscaling_snapshot()
    assert snap["preempt_exhausted"] is True
    assert snap["preempted_streams"] == 0
    assert snap["queue_depth_by_class"]["interactive"] >= 1
    assert snap["queue_depth_by_class"]["batch"] == 0
    assert eng.stats()["preemptions_total"] == 0, \
        "equal-rank runners must never be preempted"
    _drain(eng, hogs + [waiter])
    eng.shutdown()


def test_shed_classes_policy_is_batch_first():
    """Pure-math unit: shed_classes() escalates batch -> +default ->
    everything, and stays empty while scaling can still help."""
    from ray_tpu.serve.autoscaling_policy import shed_classes
    from ray_tpu.serve.config import AutoscalingConfig

    cfg = AutoscalingConfig(min_replicas=1, max_replicas=2)
    # exhausted but NOT hot: preemption thresholds trip below the
    # upscale thresholds, so the graduated band exists
    exh = {
        "queue_wait_p95_s": 0.0, "kv_pool_pressure": 0.5,
        "queue_depth": 2, "preempt_exhausted": True,
        "queue_depth_by_class": {"interactive": 2, "default": 0,
                                 "batch": 1},
    }
    # below max_replicas: scaling helps, shed nothing
    assert shed_classes(cfg, [exh, exh], 1) == ()
    # at max, all exhausted, no default backlog: batch only
    assert shed_classes(cfg, [exh, exh], 2) == ("batch",)
    # default backlog on every replica joins default
    exh_d = dict(exh, queue_depth_by_class={"interactive": 1,
                                            "default": 2, "batch": 1})
    assert shed_classes(cfg, [exh_d, exh_d], 2) == ("batch", "default")
    # one replica not exhausted: preemption still has room somewhere
    assert shed_classes(cfg, [exh, dict(exh, preempt_exhausted=False)],
                        2) == ()
    # fleet_saturated (hot + queueing everywhere at max) sheds all
    # classes — it subsumes the graduated signal
    hot = dict(exh, queue_wait_p95_s=99.0, kv_pool_pressure=1.0)
    assert shed_classes(cfg, [hot, hot], 2) == (
        "batch", "default", "interactive")


def test_replica_with_parked_streams_is_not_cold():
    """A parked stream holds no blocks but IS pending work — the
    downscale policy must not read its replica as idle."""
    from ray_tpu.serve.autoscaling_policy import snapshot_is_cold
    from ray_tpu.serve.config import AutoscalingConfig

    cfg = AutoscalingConfig(min_replicas=1, max_replicas=2)
    idle = {"queue_depth": 0, "running": 0, "prefilling": 0,
            "kv_pool_pressure": 0.0}
    assert snapshot_is_cold(cfg, idle)
    assert not snapshot_is_cold(cfg, dict(idle, preempted_streams=1))


# -------------------------------------------------- proxy plumbing

def test_http_retry_after_is_class_aware():
    """The HTTP proxy's overload mapping backs batch off harder than
    interactive, and defaults sanely without a class."""
    from ray_tpu.exceptions import EngineOverloadedError
    from ray_tpu.serve.proxy import _status_for

    for prio, retry in (("interactive", "1"), ("default", "2"),
                        ("batch", "5"), (None, "2")):
        status, headers = _status_for(EngineOverloadedError("full"), prio)
        assert status == 503
        assert headers["Retry-After"] == retry


def test_priority_validation():
    from ray_tpu.serve.llm import SamplingParams

    for p in ("interactive", "default", "batch"):
        assert SamplingParams(priority=p).priority == p
    with pytest.raises(ValueError):
        SamplingParams(priority="bulk")


# ------------------------------------------------------ chaos storyline

@pytest.fixture(scope="module")
def priority_cluster():
    """Two preemption-enabled replicas behind the proxies, with a chaos
    plan every replica inherits: the first replica to RESUME a parked
    stream dies at that instant (the parked stream then fails over), and
    decode steps are slightly delayed so the interactive flood holds
    pressure long enough to force the park."""
    import os

    plan = FaultPlan(seed=7, faults=(
        Fault(point="llm.resume_preempted", action="kill"),
        Fault(point="engine.decode", action="delay", arg=0.04, times=None),
    ))
    prev = os.environ.get(chaos.ENV_VAR)
    os.environ[chaos.ENV_VAR] = plan.to_json()
    chaos.clear()

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import EngineConfig, build_llm_app

    ray_tpu.init(num_cpus=8)
    with shutdown_if_setup_fails():
        serve.start(http_options={"port": 0}, grpc_options={"port": 0})
        handle = serve.run(
            build_llm_app(
                EngineConfig(
                    model="llama", model_config=_model_config(), seed=0,
                    block_size=4, num_blocks=24,
                    preemption=dict(PREEMPTION),
                ),
                num_replicas=2,
            ),
            name="llm-prio", route_prefix="/prio", timeout_s=180,
        )
    yield serve, handle
    serve.shutdown()
    ray_tpu.shutdown()
    chaos.clear()
    if prev is None:
        os.environ.pop(chaos.ENV_VAR, None)
    else:
        os.environ[chaos.ENV_VAR] = prev


@pytest.mark.chaos
@pytest.mark.timeout(300)
def test_replica_killed_while_stream_parked_resumes_byte_identical(
        priority_cluster):
    """Acceptance: a batch stream is preempted under an interactive
    flood; the chaos plan kills its replica the moment the parked
    stream is resumed. The client's failover resume on the survivor
    still completes byte-identical to an unfaulted reference."""
    import threading

    from ray_tpu.serve.llm import stream_tokens

    serve, handle = priority_cluster
    # a much longer batch stream + slower decode than the engine-level
    # tests: the flood must land while the batch stream is still
    # mid-generation for the park (and therefore the resume-instant
    # kill) to happen, and stream dispatch latency under load is easily
    # a second or two. 64 new tokens keeps the chain at 18 of the 23
    # usable KV blocks — admissible alone, yet leaving so little
    # headroom that a couple of interactive arrivals force waiters.
    batch_new = 4 * BATCH_NEW
    sampling = dict(max_new_tokens=batch_new, temperature=0.8, seed=42)
    reference = _engine("llama").generate(BATCH_PROMPT, **sampling)

    flood_errors: list = []

    def flood_once(rid, i):
        try:
            list(stream_tokens(handle, {
                "prompt": [13 + (i % 100), 4, 5],
                "request_id": rid,
                "max_new_tokens": 16,
                "temperature": 0.8,
                "seed": 100 + i,
                "priority": "interactive",
            }, max_failovers=3))
        except Exception as e:  # noqa: BLE001 — collected for the assert
            flood_errors.append(e)

    def flood_burst(burst_no, seconds, nworkers=10):
        """Hold ~nworkers interactive streams in flight for `seconds`."""
        stop = threading.Event()

        def worker(k):
            seq = 0
            while not stop.is_set():
                flood_once(f"prio-flood-{burst_no}-{k}-{seq}",
                           burst_no * 1000 + k * 50 + seq)
                seq += 1

        workers = [
            threading.Thread(target=worker, args=(k,), daemon=True)
            for k in range(nworkers)
        ]
        for w in workers:
            w.start()
        time.sleep(seconds)
        stop.set()
        for w in workers:
            w.join(timeout=60)

    gen = stream_tokens(handle, {
        "prompt": BATCH_PROMPT,
        "request_id": "prio-batch-1",
        "priority": "batch",
        **sampling,
    }, max_failovers=3)
    it = iter(gen)
    first = next(it)  # batch stream is RUNNING before the flood lands

    # A background consumer keeps pulling the batch stream so the client
    # observes the kill (and fails over) while the main thread drives
    # load. Pressure is applied in bounded PULSES: each burst forces the
    # batch stream to park, and the quiet gap after it lets pressure
    # drain so the engine resumes the parked stream — the instant the
    # chaos plan's kill fires. Repeat until the stream's own failover
    # counter trips (a one-shot burst races the batch stream's runtime;
    # polling replica stats instead would queue behind the flood).
    chunks = [first]
    stream_done = threading.Event()

    def consume():
        try:
            chunks.extend(it)
        finally:
            stream_done.set()

    consumer = threading.Thread(target=consume, daemon=True)
    consumer.start()

    burst_no = 0
    deadline = time.monotonic() + 150
    while (gen.failovers < 1 and not stream_done.is_set()
           and time.monotonic() < deadline):
        flood_burst(burst_no, seconds=6.0)
        burst_no += 1
        for _ in range(40):  # drain window: resume fires, kill lands
            if gen.failovers >= 1 or stream_done.is_set():
                break
            time.sleep(0.2)
    assert stream_done.wait(timeout=120), "batch stream never completed"
    consumer.join(timeout=10)

    assert gen.failovers >= 1, \
        "the resume-instant kill should have forced a failover"
    assert [c["index"] for c in chunks] == list(range(batch_new))
    assert [c["token"] for c in chunks] == reference
    assert not flood_errors, f"interactive flood failed: {flood_errors[:3]}"
    # at least one engine recorded the preemption that armed the kill
    stats = [s for s in handle.broadcast("stats") if s]
    assert sum(s.get("requests_resumed", 0) for s in stats) >= 1
