"""Disaggregated prefill/decode with the KV-block handoff over the
object plane (docs/SERVING_LLM.md "Disaggregated prefill/decode").

Unit tests pin the wire format (versioned header, chain + content
digests, corruption/truncation/layout failures), the engine-level
export -> adopt round trip (byte-identical generation, leak-free pools,
idempotent adoption, chain verification against the WRONG prompt), the
per-pool autoscaling signal scoping (``AutoscalingConfig.signal_mode``),
and the seeded RESUME backoff schedule.

Cluster tests run the chaos storyline: a prefill replica killed at the
``llm.handoff.seal`` hook retries the seal on a survivor; a sealed KV
object deleted before the decode fetch falls back to decode-local
prefill — both streams byte-identical to a non-disaggregated local
reference, with no leaked KV blocks and no leaked sealed objects — and
the two pools scale on DISJOINT signals (admission saturation grows
only the prefill pool; the decode pool ignores it).
"""
from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np
import pytest

from conftest import shutdown_if_setup_fails

from ray_tpu._private import chaos
from ray_tpu._private.chaos import Fault, FaultPlan
from ray_tpu.serve.autoscaling_policy import snapshot_is_hot
from ray_tpu.serve.config import AutoscalingConfig


# ---------------- wire format (no jax, no cluster) ----------------

def _layout(**kw):
    from ray_tpu.serve.llm.kv_transfer import KVLayout

    base = dict(n_layer=2, block_size=4, n_kv_head=2, head_dim=8,
                dtype="float32")
    base.update(kw)
    return KVLayout(**base)


def _records(layout, n, seed=0):
    rng = np.random.default_rng(seed)
    shape = (layout.n_layer, layout.block_size, layout.n_kv_head,
             layout.head_dim)
    out = []
    for i in range(n):
        out.append((bytes([i]) * 16,
                    rng.standard_normal(shape).astype(np.float32),
                    rng.standard_normal(shape).astype(np.float32)))
    return out


def test_wire_roundtrip_bit_exact():
    from ray_tpu.serve.llm import kv_transfer as kt

    layout = _layout()
    records = _records(layout, 3)
    wire = kt.pack_blocks(layout, records, prefix_tokens=12)
    out_layout, prefix_tokens, out = kt.unpack_blocks(wire)
    assert out_layout == layout and prefix_tokens == 12
    assert len(out) == 3
    for (d1, k1, v1), (d2, k2, v2) in zip(records, out):
        assert d1 == d2
        assert np.array_equal(k1, k2) and np.array_equal(v1, v2)


@pytest.mark.parametrize("mutilate", ["payload", "magic", "version",
                                      "truncate", "header"])
def test_wire_rejects_corruption(mutilate):
    from ray_tpu.serve.llm import kv_transfer as kt

    layout = _layout()
    wire = bytearray(kt.pack_blocks(layout, _records(layout, 2),
                                    prefix_tokens=8))
    if mutilate == "payload":
        wire[-1] ^= 0xFF                      # content digest mismatch
    elif mutilate == "magic":
        wire[0] ^= 0xFF
    elif mutilate == "version":
        wire[4] ^= 0xFF
    elif mutilate == "truncate":
        wire = wire[:-7]
    elif mutilate == "header":
        wire[12] ^= 0xFF                      # garbage inside the JSON
    with pytest.raises(kt.KVTransferError):
        kt.unpack_blocks(bytes(wire))


def test_wire_layout_equality_is_strict():
    assert _layout() == _layout()
    assert _layout() != _layout(dtype="bfloat16")
    assert _layout() != _layout(n_kv_head=4)
    # block payload size tracks the layout
    assert _layout().block_bytes == 2 * 4 * 2 * 8 * 4


def test_handoff_object_id_deterministic():
    from ray_tpu._private.ids import ObjectID
    from ray_tpu.serve.llm.kv_transfer import handoff_object_id

    a = handoff_object_id("req-1", 0)
    assert isinstance(a, ObjectID)
    assert a == handoff_object_id("req-1", 0)
    assert a != handoff_object_id("req-1", 1)
    assert a != handoff_object_id("req-2", 0)


# ---------------- engine export -> adopt (jax, no cluster) ----------------

def _model_config():
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    return dataclasses.replace(
        LlamaConfig.tiny(), dtype=jnp.float32, attention="xla")


def _engine(auto_step=True, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("seed", 0)
    return LLMEngine(
        EngineConfig(model="llama", model_config=_model_config(), **kw),
        auto_step=auto_step,
    )


def _pool_is_clean(eng) -> bool:
    c = eng.cache
    return (
        len(c._free) + len(c._lru) == c.cfg.usable_blocks
        and c._reserved == 0
        and c.used_blocks == 0
    )


def _prompt(n, seed=7):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(1, 250, size=n)]


@pytest.mark.timeout(300)
def test_export_adopt_generates_byte_identical(jax_cpu):
    """The full handoff round trip at the engine level: prefill on one
    engine, pack/unpack through the wire format, adopt on a second
    engine — generation there is byte-identical to a cold reference,
    the adopted prefix serves as a prefix hit (almost no prefill
    recompute), and both pools end clean."""
    from ray_tpu.serve.llm import kv_transfer as kt

    prompt = _prompt(35)
    sampling = dict(max_new_tokens=8, temperature=0.8, seed=5)

    ref_eng = _engine()
    ref = ref_eng.generate(prompt, **sampling)
    ref_eng.shutdown()

    donor = _engine()
    donor.generate(prompt, max_new_tokens=1, seed=5)
    records = donor.export_prefix(prompt)
    assert len(records) == len(prompt) // 8  # every full block exported
    wire = kt.pack_blocks(donor.kv_layout(), records,
                          prefix_tokens=len(records) * 8)
    donor.shutdown()

    layout, _, unpacked = kt.unpack_blocks(wire)
    taker = _engine()
    assert layout == taker.kv_layout()
    landed = taker.adopt_prefix(prompt, unpacked)
    assert landed == len(records)
    assert taker.cache.stats.adopted_blocks == landed
    assert _pool_is_clean(taker), "adoption must not consume pool capacity"

    out = taker.generate(prompt, **sampling)
    assert out == ref, "adopted-KV generation diverged from cold reference"
    st = taker.stats()
    assert st["prefix_hit_tokens"] >= landed * 8
    # only the sub-block prompt tail was recomputed locally
    assert st["prefill_tokens_total"] <= len(prompt) - landed * 8
    assert _pool_is_clean(taker)
    taker.shutdown()


@pytest.mark.timeout(300)
def test_adopt_is_idempotent_and_chain_verified(jax_cpu):
    """Re-adopting the same records is a no-op (resident digests are
    skipped — the decode-survivor re-land path), and records offered for
    the WRONG prompt land zero blocks (the chain digest is recomputed
    from the prompt actually being served)."""
    prompt = _prompt(32)
    donor = _engine()
    donor.generate(prompt, max_new_tokens=1, seed=0)
    records = donor.export_prefix(prompt)
    assert len(records) == 4
    donor.shutdown()

    taker = _engine()
    first = taker.adopt_prefix(prompt, records)
    assert first == 4
    again = taker.adopt_prefix(prompt, records)
    assert again == 4, "resident blocks count as landed on re-adopt"
    assert taker.cache.stats.adopted_blocks == 4, "idempotent re-land"

    other = _prompt(32, seed=99)
    assert taker.adopt_prefix(other, records) == 0

    # a tampered chain digest stops the walk at the tamper point
    fresh = _engine()
    broken = list(records)
    broken[2] = (b"\x00" * 16, broken[2][1], broken[2][2])
    assert fresh.adopt_prefix(prompt, broken) == 2
    assert _pool_is_clean(fresh)
    fresh.shutdown()
    taker.shutdown()


@pytest.mark.timeout(300)
def test_adopt_degrades_when_pool_is_tight(jax_cpu):
    """Adoption never evicts live work: with most blocks referenced by a
    running stream, only the spare capacity is adopted and generation
    still completes byte-identically via partial prefix hit + local
    prefill for the rest."""
    prompt = _prompt(32)
    donor = _engine()
    ref = donor.generate(prompt, max_new_tokens=6, temperature=0.8, seed=9)
    records = donor.export_prefix(prompt)
    donor.shutdown()

    # usable pool of 8 blocks; the hog's prefill+decode reserves 6. Stepped
    # by hand: a tiny hog on a free-running engine can END before the
    # adoption gets the engine's lock, and leave the pool wide open
    taker = _engine(auto_step=False, num_blocks=9, max_batch_size=2,
                    max_prefill_batch=2)
    hog = taker.submit([1] * 5, max_new_tokens=43)
    taker.step()  # hog admitted + prefilled: its 6 blocks are reserved
    landed = taker.adopt_prefix(prompt, records)
    assert landed < len(records), "tight pool must not fully adopt"
    out = taker.generate(prompt, max_new_tokens=6, temperature=0.8, seed=9)
    assert out == ref
    while taker.step():
        pass
    assert len(list(hog)) == 43
    taker.shutdown()


# ---------------- autoscaling signal scoping (pure policy) ----------------

def _snap(**kw):
    base = dict(
        queue_depth=0, queue_wait_p95_s=0.0, kv_pool_pressure=0.0,
        deadline_miss_rate=0.0, rejection_rate=0.0, running=0, prefilling=0,
    )
    base.update(kw)
    return base


def test_signal_mode_scopes_hot_signals():
    def cfg(mode, **kw):
        kw.setdefault("min_replicas", 1)
        kw.setdefault("max_replicas", 4)
        kw.setdefault("upscale_queue_wait_p95_s", 0.25)
        kw.setdefault("upscale_kv_pressure", 0.85)
        return AutoscalingConfig(signal_mode=mode, **kw)

    admission_hot = _snap(queue_wait_p95_s=0.5, rejection_rate=1.0)
    generation_hot = _snap(kv_pool_pressure=0.95, deadline_miss_rate=0.1)

    # "all" (default): both families trip
    assert snapshot_is_hot(cfg("all"), admission_hot)
    assert snapshot_is_hot(cfg("all"), generation_hot)
    # prefill pool: admission-side only
    assert snapshot_is_hot(cfg("prefill"), admission_hot)
    assert not snapshot_is_hot(cfg("prefill"), generation_hot)
    # decode pool: generation-side only
    assert not snapshot_is_hot(cfg("decode"), admission_hot)
    assert snapshot_is_hot(cfg("decode"), generation_hot)
    # decode-step p50 (TPOT) bound is decode-scoped and off by default
    slow_decode = _snap(decode_step_p50_s=0.5)
    assert not snapshot_is_hot(cfg("decode"), slow_decode)
    assert snapshot_is_hot(
        cfg("decode", upscale_decode_step_p50_s=0.2), slow_decode)
    assert not snapshot_is_hot(
        cfg("prefill", upscale_decode_step_p50_s=0.2), slow_decode)


def test_signal_mode_validation():
    with pytest.raises(ValueError):
        AutoscalingConfig(signal_mode="both")
    with pytest.raises(ValueError):
        AutoscalingConfig(upscale_decode_step_p50_s=0.0)
    from ray_tpu.serve.config import DeploymentConfig

    with pytest.raises(ValueError):
        DeploymentConfig(pool_role="drafter")
    assert DeploymentConfig(pool_role="prefill").pool_role == "prefill"


# ---------------- RESUME backoff schedule (satellite) ----------------

def test_resume_backoff_is_seeded_exponential_with_jitter():
    from ray_tpu.serve.handle import resume_backoff_s

    base, cap = 0.05, 1.0
    sched = [resume_backoff_s(123, a, base=base, cap=cap) for a in range(10)]
    # deterministic per (seed, attempt)
    assert sched == [resume_backoff_s(123, a, base=base, cap=cap)
                     for a in range(10)]
    # every delay jitters within [span/2, span] of the doubling span
    for attempt, delay in enumerate(sched):
        span = min(cap, base * 2 ** attempt)
        assert span / 2 <= delay <= span, (attempt, delay, span)
    # capped: late attempts never exceed the ceiling
    assert all(d <= cap for d in sched)
    # the schedule actually grows toward the cap (not a fixed cadence)
    assert max(sched[5:]) > 4 * max(sched[:2])
    # different streams (seeds) land on different jitter
    other = [resume_backoff_s(456, a, base=base, cap=cap) for a in range(10)]
    assert other != sched


# ---------------- cluster storyline (tier-1 deterministic) ----------------

def _wait_for(predicate, timeout_s=60.0, interval=0.1):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


def _dep_status(ctrl, app, dep):
    import ray_tpu

    st = ray_tpu.get(ctrl.status.remote(), timeout=30)
    return st.get(app, {}).get(dep, {})


def _pools_clean(handle) -> bool:
    stats = [s for s in handle.broadcast("stats") if s]
    return bool(stats) and all(
        s["running"] == 0 and s["waiting"] == 0 and s["kv_used_blocks"] == 0
        for s in stats
    )


def _object_gone(oid_hex) -> bool:
    from ray_tpu._private.ids import ObjectID
    from ray_tpu._private.object_store import EVICTED
    from ray_tpu._private.worker import global_worker

    got = global_worker().store.get(
        ObjectID.from_hex(oid_hex), timeout_ms=0)
    return got is None or got is EVICTED


@pytest.fixture(scope="module")
def dg_cluster():
    """One controller, two disaggregated apps, chaos plan via env:

    - ``llm-dg``: 2 static prefill replicas + 1 decode replica — the
      handoff, kill-mid-seal, and evicted-object tests (2 prefill
      replicas so the seal retry has a survivor).
    - ``llm-dgs``: min=1/max=2 prefill pool on ``signal_mode="prefill"``
      and min=1/max=2 decode pool on ``signal_mode="decode"`` — the
      disjoint-signal scaling storyline.
    """
    import os

    plan = FaultPlan(seed=13, faults=(
        Fault(point="llm.handoff.seal", action="kill",
              when={"tag": "sealkill", "attempt": 0}),
    ))
    prev = os.environ.get(chaos.ENV_VAR)
    os.environ[chaos.ENV_VAR] = plan.to_json()
    chaos.clear()

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import EngineConfig, build_llm_app

    ecfg = EngineConfig(
        model="llama", model_config=_model_config(), seed=0,
        block_size=8, num_blocks=64,
    )
    ray_tpu.init(num_cpus=8)
    with shutdown_if_setup_fails():
        serve.start(http_options={"port": 0})
        dg_handle = serve.run(
            build_llm_app(
                ecfg,
                prefill_replicas=2,
                autoscaling_config=dict(min_replicas=1, max_replicas=1),
            ),
            name="llm-dg", route_prefix="/dg", timeout_s=300,
        )
        # tight admission on the scaling app: rejections are the ONLY
        # admission-side saturation probe the test drives
        scfg = dataclasses.replace(
            ecfg, max_batch_size=1, max_prefill_batch=1, max_waiting=1)
        dgs_handle = serve.run(
            build_llm_app(
                scfg,
                prefill_replicas=1,
                prefill_options=dict(autoscaling_config=dict(
                    min_replicas=1, max_replicas=2, signal_mode="prefill",
                    upscale_delay_periods=1, downscale_delay_periods=10_000,
                    upscale_queue_wait_p95_s=30.0,
                )),
                autoscaling_config=dict(
                    min_replicas=1, max_replicas=2, signal_mode="decode",
                    upscale_delay_periods=1, downscale_delay_periods=10_000,
                    upscale_queue_wait_p95_s=30.0,
                ),
            ),
            name="llm-dgs", route_prefix="/dgs", timeout_s=300,
        )
        from ray_tpu.serve.controller import CONTROLLER_NAME

        ctrl = ray_tpu.get_actor(CONTROLLER_NAME)
        prefill_handle = serve.get_deployment_handle("LLMPrefill", "llm-dg")
    yield {
        "decode": dg_handle, "prefill": prefill_handle,
        "dgs": dgs_handle, "ctrl": ctrl, "serve": serve,
    }
    serve.shutdown()
    ray_tpu.shutdown()
    chaos.clear()
    if prev is None:
        os.environ.pop(chaos.ENV_VAR, None)
    else:
        os.environ[chaos.ENV_VAR] = prev


def _reference(payloads):
    eng = _engine()
    refs = [
        eng.generate(p["prompt"], max_new_tokens=p["max_new_tokens"],
                     temperature=p["temperature"], seed=p["seed"])
        for p in payloads
    ]
    eng.shutdown()
    return refs


def _attempt_oids(request_id, retries=2):
    from ray_tpu.serve.llm.kv_transfer import handoff_object_id

    return [handoff_object_id(request_id, a).hex()
            for a in range(retries + 1)]


@pytest.mark.chaos
@pytest.mark.timeout(300)
def test_disagg_stream_byte_identical_and_swept(dg_cluster):
    """Happy path: the prompt prefills on the prefill pool, its KV
    blocks hand off through the object store, and the decode stream is
    byte-identical to a non-disaggregated local reference. When the
    stream ends every attempt object is gone from the store and both
    pools are clean."""
    from ray_tpu.serve.llm import stream_tokens

    payload = {
        "prompt": _prompt(35, seed=21), "request_id": "dg-happy",
        "max_new_tokens": 8, "temperature": 0.8, "seed": 31,
    }
    [ref] = _reference([payload])

    gen = stream_tokens(dg_cluster["decode"], payload,
                        prefill_handle=dg_cluster["prefill"])
    chunks = list(gen)
    assert [c["index"] for c in chunks] == list(range(8))
    assert [c["token"] for c in chunks] == ref, \
        "disaggregated stream diverged from the co-located reference"

    # the decode replica really landed handed-off blocks
    hs = [s for s in dg_cluster["decode"].broadcast("handoff_stats") if s]
    assert sum(s["landed_blocks"] for s in hs) >= len(payload["prompt"]) // 8
    # the prefill pool really sealed
    ps = [s for s in dg_cluster["prefill"].broadcast("handoff_stats") if s]
    assert sum(s["sealed_total"] for s in ps) >= 1

    # leak checks: every attempt object swept, both pools clean
    for oid_hex in _attempt_oids("dg-happy"):
        assert _wait_for(lambda o=oid_hex: _object_gone(o), timeout_s=30), \
            f"sealed handoff object {oid_hex} leaked"
    assert _wait_for(lambda: _pools_clean(dg_cluster["decode"]),
                     timeout_s=60)
    assert _wait_for(lambda: _pools_clean(dg_cluster["prefill"]),
                     timeout_s=60)


@pytest.mark.chaos
@pytest.mark.timeout(300)
def test_prefill_kill_mid_handoff_reruns_on_survivor(dg_cluster):
    """The canonical chaos test: the prefill replica serving attempt 0
    is killed AT the ``llm.handoff.seal`` hook — after prefill, before
    the object seals. The seal state machine excludes the dead replica
    and re-runs on the survivor (deterministic attempt-1 object id);
    the client stream is byte-identical, nothing leaks, and the
    controller replaces the dead prefill replica."""
    from ray_tpu.serve.llm import stream_tokens

    payload = {
        "prompt": _prompt(40, seed=22), "request_id": "dg-kill",
        "max_new_tokens": 8, "temperature": 0.8, "seed": 32,
        "chaos_tag": "sealkill",
    }
    [ref] = _reference([payload])

    gen = stream_tokens(dg_cluster["decode"], payload,
                        prefill_handle=dg_cluster["prefill"])
    chunks = list(gen)
    assert [c["index"] for c in chunks] == list(range(8))
    assert [c["token"] for c in chunks] == ref, \
        "stream diverged after the prefill replica was killed mid-handoff"

    # the handoff was re-run (attempt > 0 seals increment the retry
    # counter on the surviving prefill replica) and still landed
    def survivor_sealed():
        hs = [s for s in dg_cluster["prefill"].broadcast("handoff_stats")
              if s]
        return sum(s["sealed_total"] for s in hs) >= 1

    assert _wait_for(survivor_sealed, timeout_s=30), \
        "no prefill replica sealed after the kill"
    ds = [s for s in dg_cluster["decode"].broadcast("handoff_stats") if s]
    assert sum(s["landed_blocks"] for s in ds) >= len(payload["prompt"]) // 8

    # every attempt id — including the killed attempt 0's, which was
    # never sealed — is swept (delete tombstones unknown ids too)
    for oid_hex in _attempt_oids("dg-kill"):
        assert _wait_for(lambda o=oid_hex: _object_gone(o), timeout_s=30), \
            f"handoff attempt object {oid_hex} leaked"

    # the controller replaces the killed prefill replica
    assert _wait_for(
        lambda: _dep_status(dg_cluster["ctrl"], "llm-dg", "LLMPrefill")
        .get("running_replicas") == 2, timeout_s=120)
    assert _wait_for(lambda: _pools_clean(dg_cluster["prefill"]),
                     timeout_s=60)
    assert _wait_for(lambda: _pools_clean(dg_cluster["decode"]),
                     timeout_s=60)


@pytest.mark.chaos
@pytest.mark.timeout(300)
def test_evicted_handoff_object_falls_back_byte_identical(dg_cluster):
    """A sealed KV object lost between seal and fetch (deleted here;
    LRU eviction surfaces identically as EVICTED) must degrade to
    decode-local prefill — the stream completes byte-identically, it
    does NOT die and does NOT hang to the fetch deadline."""
    from ray_tpu.serve.llm import stream_tokens

    payload = {
        "prompt": _prompt(33, seed=23), "request_id": "dg-evict",
        "max_new_tokens": 8, "temperature": 0.8, "seed": 33,
    }
    [ref] = _reference([payload])

    # seal manually on the prefill pool, then lose the object. This raw
    # handle call bypasses _seal_handoff's exclude-and-retry machinery on
    # purpose (we need the manifest), so it must tolerate the previous
    # test's killed replica lingering in this driver's routing table
    # until the controller's replacement propagates.
    from ray_tpu.exceptions import ActorDiedError

    manifest = None
    deadline = time.monotonic() + 90
    while True:
        try:
            manifest = dg_cluster["prefill"].prefill_export.remote(
                dict(payload)).result(timeout=60)
            break
        except ActorDiedError:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.5)
    assert manifest is not None and manifest["num_blocks"] >= 4
    from ray_tpu._private.ids import ObjectID
    from ray_tpu._private.worker import global_worker

    global_worker().store.delete(ObjectID.from_hex(manifest["object_id"]))
    assert _object_gone(manifest["object_id"])

    before = [s for s in dg_cluster["decode"].broadcast("handoff_stats")
              if s]
    fallbacks_before = sum(s["fallbacks"] for s in before)

    dispatch = dict(payload, kv_handoff=manifest)
    t0 = time.monotonic()
    chunks = list(stream_tokens(dg_cluster["decode"], dispatch))
    elapsed = time.monotonic() - t0
    assert [c["index"] for c in chunks] == list(range(8))
    assert [c["token"] for c in chunks] == ref, \
        "stream diverged after falling back to decode-local prefill"
    # EVICTED surfaces promptly (daemon tombstone wakes the getter);
    # generous bound still far below the 10 s fetch deadline + decode
    assert elapsed < 9.0, f"fallback took {elapsed:.1f}s — fetch hung"

    after = [s for s in dg_cluster["decode"].broadcast("handoff_stats")
             if s]
    assert sum(s["fallbacks"] for s in after) > fallbacks_before, \
        "decode replica never recorded the handoff fallback"
    assert _wait_for(lambda: _pools_clean(dg_cluster["decode"]),
                     timeout_s=60)


@pytest.mark.chaos
@pytest.mark.timeout(300)
def test_pools_scale_on_disjoint_signals(dg_cluster):
    """llm-dgs storyline: admission saturation on the prefill pool
    (rejected prefill_export bursts) scales ONLY the prefill pool —
    the decode pool, on ``signal_mode="decode"``, holds at 1 even while
    its own admission rejects — proving the disjoint-signal split."""
    import ray_tpu
    from ray_tpu.serve import get_deployment_handle
    from ray_tpu.serve.llm import stream_tokens

    ctrl = dg_cluster["ctrl"]
    prefill = get_deployment_handle("LLMPrefill", "llm-dgs")
    assert _dep_status(ctrl, "llm-dgs", "LLMPrefill") \
        .get("target_replicas") == 1
    assert _dep_status(ctrl, "llm-dgs", "LLMDecode") \
        .get("target_replicas") == 1

    # phase 1: hammer the prefill pool with concurrent long exports —
    # max_batch=max_waiting=1, so overflow rejects (the prefill-pool
    # saturation signal)
    stop = threading.Event()

    def feeder(i):
        n = 0
        while not stop.is_set():
            try:
                prefill.prefill_export.remote({
                    "prompt": _prompt(48, seed=100 + i),
                    "request_id": f"dgs-feed-{i}-{n}",
                }).result(timeout=30)
            except Exception:  # noqa: BLE001 — rejection IS the signal
                time.sleep(0.02)
            n += 1

    feeders = [threading.Thread(target=feeder, args=(i,), daemon=True)
               for i in range(6)]
    for t in feeders:
        t.start()
    try:
        assert _wait_for(
            lambda: _dep_status(ctrl, "llm-dgs", "LLMPrefill")
            .get("target_replicas") == 2, timeout_s=90, interval=0.3), \
            "prefill saturation never scaled the prefill pool"
        # the decode pool must NOT have moved on admission signals
        assert _dep_status(ctrl, "llm-dgs", "LLMDecode") \
            .get("target_replicas") == 1, \
            "decode pool scaled on a prefill-side signal"
    finally:
        stop.set()
    for t in feeders:
        t.join(timeout=60)

    # phase 2: admission-saturate the DECODE pool the same way; its
    # signal_mode="decode" config ignores queue-wait/rejections, so it
    # must hold at 1 across several reconcile periods
    stop2 = threading.Event()

    def decode_feeder(i):
        n = 0
        while not stop2.is_set():
            try:
                for _ in stream_tokens(dg_cluster["dgs"], {
                    "prompt": [1 + i, 2, 3],
                    "request_id": f"dgs-dec-{i}-{n}",
                    "max_new_tokens": 24, "temperature": 0.8, "seed": 5,
                }):
                    pass
            except Exception:  # noqa: BLE001 — rejection IS the probe
                time.sleep(0.02)
            n += 1

    dec_feeders = [
        threading.Thread(target=decode_feeder, args=(i,), daemon=True)
        for i in range(4)
    ]
    for t in dec_feeders:
        t.start()
    try:

        def decode_rejecting():
            snaps = [s for s in dg_cluster["dgs"]
                     .broadcast("autoscaling_snapshot") if s]
            return any(s.get("rejection_rate", 0.0) > 0.0 for s in snaps)

        assert _wait_for(decode_rejecting, timeout_s=60, interval=0.3), \
            "decode pool never saw admission rejections"
        # several snapshot periods of sustained rejections: no upscale
        time.sleep(3.0)
        assert _dep_status(ctrl, "llm-dgs", "LLMDecode") \
            .get("target_replicas") == 1, \
            "decode pool scaled on an admission-side signal"
    finally:
        stop2.set()
    for t in dec_feeders:
        t.join(timeout=60)

    # gauge surface: the controller exports the prefill-pool size from
    # pool_role (value is checked via status; the metric lives in the
    # controller process)
    assert _dep_status(ctrl, "llm-dgs", "LLMPrefill") \
        .get("target_replicas") == 2
    assert _wait_for(lambda: _pools_clean(dg_cluster["dgs"]), timeout_s=90)
