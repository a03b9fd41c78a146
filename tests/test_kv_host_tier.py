"""Fleet-scale KV caching (ISSUE 15): pinned host-memory cache tier +
prefix-aware routing.

(a) HostKVTier — LRU byte-capacity arena of RTKV-packed blocks: put/get
    roundtrip, capacity eviction, oversize refusal
(b) PagedKVCache demote/promote — eviction demotes through the installed
    ``demote_fn``, host hits promote exactly-once through the staged
    ``take_pending_promotions`` drain, the unlanded-block guard never
    exports garbage device bytes, corrupt arena entries drop to
    recompute, ``release_all`` clears queue + tracking set + arena
(c) engine byte-identity — churn workloads that demote then promote must
    emit byte-identical streams with the tier on vs off (greedy AND
    temperature/top-p, single-device AND sharded executors), leak-free
    through cancel and with COW forks of promoted blocks
(d) observability — ``debug_snapshot()``, flight records, ``stats()``
    and the metrics registry carry the two-tier counters
(e) router — prefix-chain scoring, the load-skew escape hatch, and the
    digest-space mirror of ``api.encode_text``/``_block_key``
(f) chaos storyline — kill the serving replica mid-stream; the survivor
    resumes byte-identical, promoting the prompt's prefix from its OWN
    host tier

Parity tests run f32 + XLA attention (same rationale as
tests/test_serve_llm.py): the promoted path re-lands bytes the demoted
path captured, and token argmax/sampling must agree across cold,
cached, and promoted prefills.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import types

import numpy as np
import pytest

from conftest import shutdown_if_setup_fails

from ray_tpu._private import chaos
from ray_tpu._private.chaos import Fault, FaultPlan


# shared system prompt: 4 full blocks at block_size=8
PREFIX_TOKENS = 32
PREFIX_BLOCKS = 4

KILL_SAMPLING = dict(max_new_tokens=8, temperature=0.8, seed=42)
KILL_AT_INDEX = 2  # chunk index after which the serving replica dies


def _model_config():
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    return dataclasses.replace(
        LlamaConfig.tiny(), dtype=jnp.float32, attention="xla"
    )


def _engine(mc, *, auto_step=False, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    kw.setdefault("block_size", 8)
    # 16 usable blocks: a handful of filler prompts forces LRU eviction
    kw.setdefault("num_blocks", 17)
    return LLMEngine(
        EngineConfig(model="llama", model_config=mc, **kw), auto_step=auto_step
    )


def _pool_is_clean(eng) -> bool:
    c = eng.cache
    return (
        len(c._free) + len(c._lru) == c.cfg.usable_blocks
        and c._reserved == 0
        and c.used_blocks == 0
    )


def _shared_prefix(n=PREFIX_TOKENS):
    rng = np.random.default_rng(42)
    return [int(t) for t in rng.integers(1, 250, size=n)]


def _churn(eng, n=8, base=100):
    """Distinct filler prompts that run the 16-block pool dry, evicting
    (and, with the tier on, demoting) the previously cached prefix."""
    for i in range(n):
        eng.generate([base + i] * 17, max_new_tokens=4)


# ------------------------------------------------------ (a) HostKVTier

def _tiny_layout():
    from ray_tpu.serve.llm.kv_transfer import KVLayout

    return KVLayout(n_layer=1, block_size=2, n_kv_head=1, head_dim=2,
                    dtype="float32")


def _tier_block(fill):
    k = np.full((1, 2, 1, 2), float(fill), np.float32)
    return k, -k


def test_host_tier_put_get_roundtrip_and_lru_eviction():
    from ray_tpu.serve.llm.kv_cache import HostKVTier

    layout = _tiny_layout()
    d = [bytes([i]) * 16 for i in range(4)]
    probe = HostKVTier(1 << 20, layout)
    probe.put(d[0], *_tier_block(0))
    wire_len = probe.nbytes

    tier = HostKVTier(2 * wire_len, layout)  # room for exactly two
    assert tier.put(d[0], *_tier_block(10)) == (True, 0)
    assert tier.put(d[1], *_tier_block(11)) == (True, 0)
    # third entry evicts the LRU-oldest (d0)
    assert tier.put(d[2], *_tier_block(12)) == (True, 1)
    assert d[0] not in tier and tier.blocks == 2
    # get verifies + refreshes recency: d1 touched, so d3 evicts d2
    k, v = tier.get(d[1])
    assert float(k.flat[0]) == 11.0 and (v == -k).all()
    assert tier.put(d[3], *_tier_block(13)) == (True, 1)
    assert d[2] not in tier and d[1] in tier
    assert list(tier.digests()) == [d[3], d[1]]  # MRU first
    # re-putting a resident digest refreshes, never re-packs
    assert tier.put(d[1], *_tier_block(99)) == (True, 0)
    assert float(tier.get(d[1])[0].flat[0]) == 11.0
    # a payload larger than the whole cap is refused outright
    small = HostKVTier(wire_len - 1, layout)
    assert small.put(d[0], *_tier_block(1)) == (False, 0)
    assert small.blocks == 0 and small.nbytes == 0
    tier.clear()
    assert tier.blocks == 0 and tier.nbytes == 0


# ------------------------------- (b) cache-level demote/promote machine

def _cache(**kw):
    import jax.numpy as jnp

    from ray_tpu.serve.llm.kv_cache import KVCacheConfig, PagedKVCache

    kw.setdefault("host_cache_bytes", 1 << 20)
    return PagedKVCache(KVCacheConfig(
        n_layer=2, n_kv_head=2, head_dim=4, num_blocks=9, block_size=4,
        dtype=jnp.float32, **kw,
    ))


def _stub_demote(cache):
    """Stand-in for executor.export_blocks: fills each exported block
    with its own id so promotions are content-checkable."""
    calls: list[list[int]] = []

    def demote_fn(ids):
        calls.append(list(ids))
        k = np.zeros((2, len(ids), 4, 2, 4), np.float32)
        for j, b in enumerate(ids):
            k[:, j] = float(b)
        return k, -k

    cache.demote_fn = demote_fn
    return calls


def _warm_and_evict(cache, tokens):
    """Register ``tokens`` (2 full blocks) then churn the whole pool so
    both cached blocks demote into the host tier; pool left all-free."""
    cache.reserve(2)
    cache.allocate("warm")
    cache.ensure_capacity("warm", 8)
    cache.register_prefix("warm", tokens, 8)
    cache.free("warm")
    assert cache.cached_blocks == 2
    cache.reserve(8)
    cache.allocate("churn")
    cache.ensure_capacity("churn", 32)  # 8 blocks: evicts both cached
    cache.free("churn")


@pytest.mark.timeout(120)
def test_cache_demote_promote_roundtrip_exactly_once(jax_cpu):
    cache = _cache()
    calls = _stub_demote(cache)
    tokens = list(range(1, 9))
    _warm_and_evict(cache, tokens)

    evicted = [b for ids in calls for b in ids]
    assert len(evicted) == 2
    assert cache.stats.demoted_blocks == 2
    assert cache.host_tier.blocks == 2

    # both tiers count toward the servable prefix
    assert cache.peek_prefix(tokens) == 2

    cache.reserve(2)
    cache.allocate("c")
    assert cache.assign_prefix("c", tokens) == 8  # all 8 prompt tokens
    assert cache.stats.promoted_blocks == 2
    staged = cache.take_pending_promotions()
    assert len(staged) == 2
    # payloads carry the ORIGINAL demoted blocks' content
    assert sorted(int(k.flat[0]) for _, k, _ in staged) == sorted(evicted)
    for _, k, v in staged:
        assert (v == -k).all()
    # exactly-once: the queue drains at most once
    assert cache.take_pending_promotions() == []
    cache.promotions_landed([b for b, _, _ in staged])
    assert not cache._unlanded
    # the arena keeps its entries through promotion (provenance)
    assert cache.host_tier.blocks == 2
    # routing summary names both tiers, device-resident digests first
    summary = cache.prefix_digest_summary()
    assert len(summary) == 2 and len(set(summary)) == 2

    cache.free("c")
    assert cache.release_all() == 0
    assert len(cache._free) == cache.cfg.usable_blocks
    assert cache.host_tier.blocks == 0 and not cache._pending_promotions


@pytest.mark.timeout(120)
def test_unlanded_promoted_block_evicted_before_landing_never_exports(jax_cpu):
    """A block claimed for promotion whose payload has not landed holds
    garbage device bytes: evicting it must NOT call the demote funnel,
    the stale queue entry must drop at drain time, and the arena entry
    it came from must survive so a later request re-promotes it."""
    cache = _cache()
    calls = _stub_demote(cache)
    tokens = list(range(1, 9))
    _warm_and_evict(cache, tokens)
    assert cache.stats.demoted_blocks == 2

    cache.reserve(2)
    cache.allocate("c")
    assert cache.assign_prefix("c", tokens) == 8
    assert len(cache._unlanded) == 2
    cache.free("c")  # cancelled before the engine drained the queue

    # churn evicts both unlanded blocks: no export of garbage bytes
    n_exports = len(calls)
    cache.reserve(8)
    cache.allocate("d")
    cache.ensure_capacity("d", 32)
    assert len(calls) == n_exports, "unlanded block was demote-exported"
    assert cache.stats.demote_drops == 0  # arena still backs both
    assert cache.host_tier.blocks == 2
    assert not cache._unlanded

    # the stale queue records drop at the drain, counted
    assert cache.take_pending_promotions() == []
    assert cache.stats.promotion_drops == 2

    # and the content is still promotable from the arena
    cache.free("d")
    cache.reserve(2)
    cache.allocate("e")
    assert cache.assign_prefix("e", tokens) == 8
    assert cache.stats.promoted_blocks == 4
    staged = cache.take_pending_promotions()
    assert len(staged) == 2
    cache.promotions_landed([b for b, _, _ in staged])
    cache.free("e")
    cache.release_all()
    assert len(cache._free) == cache.cfg.usable_blocks


@pytest.mark.timeout(120)
def test_corrupt_host_entry_drops_to_recompute(jax_cpu):
    """Bit rot in the arena fails RTKV verification at promote time: the
    entry is discarded + counted and the chain walk stops — corrupt
    bytes never land in the device pool."""
    cache = _cache()
    _stub_demote(cache)
    tokens = list(range(1, 9))
    _warm_and_evict(cache, tokens)

    # flip one payload byte of the FIRST chain entry
    first = next(iter(cache.host_tier._wire))
    wire = bytearray(cache.host_tier._wire[first])
    wire[-1] ^= 0xFF
    cache.host_tier._wire[first] = bytes(wire)

    # peek is a pure lookup (no verification): the engine's over-sized
    # reservation is what makes the later shortfall safe
    assert cache.peek_prefix(tokens) == 2
    cache.reserve(2)
    cache.allocate("c")
    hit_tokens = cache.assign_prefix("c", tokens)
    assert cache.stats.host_corrupt_drops >= 1
    assert first not in cache.host_tier  # dropped, not retried forever
    # the walk stopped at the corrupt link; anything assigned is landable
    assert hit_tokens < 8
    staged = cache.take_pending_promotions()
    cache.promotions_landed([b for b, _, _ in staged])
    cache.release_reservation(2 - hit_tokens // 4)  # unconsumed units
    cache.free("c")
    cache.release_all()
    assert len(cache._free) == cache.cfg.usable_blocks


# ------------------------------------ (c) engine-level byte-identity

@pytest.mark.timeout(300)
@pytest.mark.parametrize("mesh_kw", [{}, {"tp": 2, "fsdp": 2}],
                         ids=["single", "sharded"])
def test_host_tier_byte_identity_through_demote_promote(jax_cpu, mesh_kw):
    """Churn demotes the shared prefix, the re-hit promotes it back:
    every token (greedy AND temperature/top-p) must match the
    tier-disabled engine byte-for-byte, on both executors."""
    mc = _model_config()
    prefix = _shared_prefix()

    def workload(eng):
        out = [eng.generate(prefix + [1, 2, 3], max_new_tokens=4)]
        _churn(eng)
        out.append(eng.generate(prefix + [9, 9, 9], max_new_tokens=4))
        out.append(eng.generate(prefix + [9, 9, 8], max_new_tokens=4,
                                temperature=0.9, top_p=0.8, seed=5))
        return out

    ref = workload(_engine(mc, host_cache_bytes=0, **mesh_kw))
    eng = _engine(mc, host_cache_bytes=1 << 22, **mesh_kw)
    got = workload(eng)
    assert got == ref, "host tier must never change emitted tokens"
    st = eng.stats()
    assert st["kv_demoted_blocks"] >= PREFIX_BLOCKS  # tier engaged
    assert st["kv_promoted_blocks"] >= PREFIX_BLOCKS  # re-hit was a promote
    assert _pool_is_clean(eng)
    assert not eng.cache._unlanded
    eng.shutdown()


@pytest.mark.timeout(300)
def test_promoted_prefix_rehit_cheaper_than_recompute(jax_cpu):
    """The point of the tier: a demoted-prefix re-hit computes only the
    uncached suffix, not the whole prompt again."""
    mc = _model_config()
    prefix = _shared_prefix()
    eng = _engine(mc, host_cache_bytes=1 << 22)
    eng.generate(prefix + [1, 2, 3], max_new_tokens=4)
    _churn(eng)
    assert eng.stats()["kv_demoted_blocks"] >= PREFIX_BLOCKS
    before = eng.stats()["prefill_tokens_total"]
    eng.generate(prefix + [9, 9, 9], max_new_tokens=4)
    computed = eng.stats()["prefill_tokens_total"] - before
    assert computed == 3, (
        f"promoted prefix must serve {PREFIX_TOKENS} tokens without "
        f"recompute; computed {computed}"
    )
    eng.shutdown()


@pytest.mark.timeout(300)
def test_cancel_and_release_all_with_promoted_blocks(jax_cpu):
    """Refcount hygiene through the promotion path: cancelling one of two
    requests sharing promoted blocks leaks nothing, and release_all
    clears the promotion queue, the unlanded set AND the arena."""
    mc = _model_config()
    prefix = _shared_prefix()
    eng = _engine(mc, host_cache_bytes=1 << 22)
    eng.generate(prefix + [1], max_new_tokens=2)
    _churn(eng)
    assert eng.stats()["kv_demoted_blocks"] >= PREFIX_BLOCKS

    a = eng.submit(prefix + [2], max_new_tokens=20)
    b = eng.submit(prefix + [3], max_new_tokens=20)
    eng.step()  # admit + prefill: a promotes, b shares the same blocks
    assert eng.stats()["kv_promoted_blocks"] >= PREFIX_BLOCKS
    assert eng.cancel(a.request_id) is True
    assert eng.cache.used_blocks > 0  # b still references the prefix
    for _ in range(200):
        if b.done:
            break
        eng.step()
    while eng.step():  # reconcile the dispatched-ahead tail
        pass
    assert len(list(b)) == 20
    assert _pool_is_clean(eng), "cancel+completion must return every block"
    assert not eng.cache._unlanded

    assert eng.cache.host_tier.blocks > 0
    eng.cache.release_all()
    assert eng.cache.host_tier.blocks == 0
    assert not eng.cache._pending_promotions and not eng.cache._unlanded
    assert len(eng.cache._free) == eng.cache.cfg.usable_blocks
    eng.shutdown()


@pytest.mark.timeout(300)
def test_cow_fork_of_promoted_block_diverges(jax_cpu):
    """A fully-resident-in-host-tier prompt: both concurrent requests
    promote/share the same blocks, then diverge through COW clones of
    the promoted tail block — landing is dispatched before the COW copy,
    so the forks must clone real content, byte-identical to tier-off."""
    mc = _model_config()
    rng = np.random.default_rng(42)
    prompt = [int(t) for t in rng.integers(1, 250, size=64)]  # 8 full blocks

    ref_eng = _engine(mc, host_cache_bytes=0)
    ref_greedy = ref_eng.generate(prompt, max_new_tokens=6)
    ref_s1 = ref_eng.generate(prompt, max_new_tokens=6, temperature=0.8,
                              seed=1)
    ref_s2 = ref_eng.generate(prompt, max_new_tokens=6, temperature=0.8,
                              seed=2)
    assert ref_s1 != ref_s2  # genuinely divergent continuations

    eng = _engine(mc, host_cache_bytes=1 << 22)
    assert eng.generate(prompt, max_new_tokens=6) == ref_greedy  # cold
    _churn(eng, base=200)  # all 8 prompt blocks demote
    assert eng.stats()["kv_demoted_blocks"] >= 8
    base_cow = eng.stats()["cow_blocks"]
    base_prom = eng.stats()["kv_promoted_blocks"]

    s1 = eng.submit(prompt, max_new_tokens=6, temperature=0.8, seed=1)
    s2 = eng.submit(prompt, max_new_tokens=6, temperature=0.8, seed=2)
    for _ in range(200):
        if s1.done and s2.done:
            break
        eng.step()
    while eng.step():
        pass
    assert list(s1) == ref_s1
    assert list(s2) == ref_s2
    st = eng.stats()
    assert st["kv_promoted_blocks"] - base_prom >= 8
    assert st["cow_blocks"] - base_cow >= 2
    assert _pool_is_clean(eng)
    eng.shutdown()


# --------------------------------------------- (d) observability surface

@pytest.mark.timeout(300)
def test_two_tier_observability_surface(jax_cpu):
    from ray_tpu.util import metrics

    mc = _model_config()
    prefix = _shared_prefix()
    eng = _engine(mc, host_cache_bytes=1 << 22)
    eng.generate(prefix + [1], max_new_tokens=2)
    _churn(eng)
    eng.generate(prefix + [2], max_new_tokens=2)

    snap = eng.cache.debug_snapshot()
    for key in ("host_blocks", "host_bytes", "demotions", "promotions",
                "host_evicted_blocks", "promotion_drops", "demote_drops",
                "host_corrupt_drops"):
        assert key in snap, f"debug_snapshot missing {key}"
    assert snap["demotions"] >= PREFIX_BLOCKS
    assert snap["promotions"] >= PREFIX_BLOCKS
    assert snap["host_blocks"] > 0 and snap["host_bytes"] > 0

    recs = [r for r in eng.debug_dump()["steps"] if r["kind"] != "compile"]
    assert recs
    for key in ("host_blocks", "host_bytes", "demotions", "promotions"):
        assert all(key in r for r in recs), f"flight record missing {key}"

    st = eng.stats()
    assert st["host_cache_blocks"] == snap["host_blocks"]
    assert st["kv_demoted_blocks"] == snap["demotions"]
    assert st["kv_promoted_blocks"] == snap["promotions"]

    m = metrics.collect(prefix="llm_")
    assert m.get("llm_kv_demoted_blocks_total", 0) >= PREFIX_BLOCKS
    assert m.get("llm_kv_promoted_blocks_total", 0) >= PREFIX_BLOCKS
    assert any(k.startswith("llm_host_cache_blocks") for k in m)

    # the two-tier autoscaling signal rides the snapshot
    auto = eng.autoscaling_snapshot()
    assert "kv_pressure_two_tier" in auto
    assert auto["kv_pressure_two_tier"] <= auto["kv_pool_pressure"]
    assert auto["kv_host_cached_blocks"] == snap["host_blocks"]
    assert auto["prefix_digests"], "routing summary must piggyback"
    eng.shutdown()


# --------------------------------------------------- (e) router scoring

def test_router_prefix_choice_scoring_and_escape_hatch():
    from ray_tpu.serve.handle import _PREFIX_MAX_SKEW, _Router
    from ray_tpu.serve.llm.kv_cache import _block_key

    r = _Router.__new__(_Router)
    r._lock = threading.Lock()
    r.app_name, r.deployment_name = "app", "dep"
    r._prefix_routing = True
    r._prefix_block_size = 4
    r._prefix_vocab_size = 256
    r._inflight = {}

    def rep(aid):
        return types.SimpleNamespace(
            _actor_id=types.SimpleNamespace(binary=lambda aid=aid: aid))

    a, b = rep(b"A"), rep(b"B")
    tokens = list(range(1, 13))  # 3 full blocks
    digest, chain = b"", []
    for i in range(3):
        digest = _block_key(digest, tokens[i * 4:(i + 1) * 4])
        chain.append(digest.hex())
    r._prefix_summaries = {b"A": frozenset(chain[:1]), b"B": frozenset(chain)}

    # longest LEADING match wins
    assert r._prefix_choice_locked([a, b], tuple(chain)) is b
    # a chain no replica holds -> fall back to power-of-two
    assert r._prefix_choice_locked([a, b], ("ff" * 16,)) is None
    # escape hatch: the winner's load skew must stay bounded
    r._inflight = {b"B": _PREFIX_MAX_SKEW + 1, b"A": 0}
    assert r._prefix_choice_locked([a, b], tuple(chain)) is None
    r._inflight = {b"B": _PREFIX_MAX_SKEW, b"A": 0}
    assert r._prefix_choice_locked([a, b], tuple(chain)) is b
    # exclude composes upstream: with only A left, A's 1-block match wins
    assert r._prefix_choice_locked([a], tuple(chain)) is a


def test_router_prompt_digests_mirror_engine_chain():
    from ray_tpu.serve.handle import (
        _PREFIX_MATCH_BLOCKS,
        _Router,
    )
    from ray_tpu.serve.llm.api import encode_text
    from ray_tpu.serve.llm.kv_cache import _block_key

    r = _Router.__new__(_Router)
    r._lock = threading.Lock()
    r.app_name, r.deployment_name = "app", "dep"
    r._prefix_routing = True
    r._prefix_block_size = 4
    r._prefix_vocab_size = 256
    r._inflight = {}
    r._prefix_summaries = {b"A": frozenset({"aa"})}

    def chain_of(tokens, bs=4):
        digest, out = b"", []
        for i in range(len(tokens) // bs):
            digest = _block_key(digest, tokens[i * bs:(i + 1) * bs])
            out.append(digest.hex())
        return tuple(out)

    tokens = list(range(1, 13))
    assert r._prompt_digests({"prompt": tokens}) == chain_of(tokens)
    # str prompts hash in the SAME token space as api.encode_text
    text = "the same system prompt every request shares"
    assert r._prompt_digests({"prompt": text}) == chain_of(
        encode_text(text, 256))
    # resumes keep today's dispatch path
    assert r._prompt_digests({"prompt": tokens, "prior_tokens": [1]}) is None
    # sub-block prompts have no routable chain
    assert r._prompt_digests({"prompt": [1, 2]}) is None
    # the walk is bounded
    long_tokens = list(range(4 * (_PREFIX_MATCH_BLOCKS + 4)))
    got = r._prompt_digests({"prompt": long_tokens})
    assert len(got) == _PREFIX_MATCH_BLOCKS
    # kill switch
    r._prefix_routing = False
    assert r._prompt_digests({"prompt": tokens}) is None
    r._prefix_routing = True
    # no advertised summaries -> nothing to steer toward
    r._prefix_summaries = {}
    assert r._prompt_digests({"prompt": tokens}) is None


# ------------------------------------------------- (f) chaos storyline

@pytest.fixture(scope="module")
def host_tier_cluster():
    """Two host-tier replicas behind the router, prefix routing OFF (the
    warm/churn phases must spread over BOTH replicas), with a chaos plan
    that kills the replica serving the tagged request mid-stream."""
    plan = FaultPlan(seed=7, faults=(
        Fault(point="llm.token", action="kill",
              when={"tag": "killme", "index": KILL_AT_INDEX,
                    "resumed": False}),
    ))
    prev_plan = os.environ.get(chaos.ENV_VAR)
    os.environ[chaos.ENV_VAR] = plan.to_json()
    prev_routing = os.environ.get("RAY_TPU_PREFIX_ROUTING")
    os.environ["RAY_TPU_PREFIX_ROUTING"] = "0"
    chaos.clear()

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import EngineConfig, build_llm_app

    ecfg = EngineConfig(
        model="llama", model_config=_model_config(), seed=0,
        block_size=8, num_blocks=17, host_cache_bytes=1 << 24,
    )
    ray_tpu.init(num_cpus=8)
    with shutdown_if_setup_fails():
        serve.start(http_options={"port": 0}, grpc_options={"port": 0})
        handle = serve.run(
            build_llm_app(ecfg, num_replicas=2),
            name="llm-host-tier", route_prefix="/hosttier", timeout_s=180,
        )
    yield serve, handle, ecfg
    serve.shutdown()
    ray_tpu.shutdown()
    chaos.clear()
    if prev_plan is None:
        os.environ.pop(chaos.ENV_VAR, None)
    else:
        os.environ[chaos.ENV_VAR] = prev_plan
    if prev_routing is None:
        os.environ.pop("RAY_TPU_PREFIX_ROUTING", None)
    else:
        os.environ["RAY_TPU_PREFIX_ROUTING"] = prev_routing


def _live_stats(handle):
    return [s for s in handle.broadcast("stats") if s]


def _run_stream(handle, payload):
    from ray_tpu.serve.llm import stream_tokens

    return list(stream_tokens(handle, payload))


@pytest.mark.chaos
@pytest.mark.timeout(600)
def test_kill_replica_survivor_promotes_from_own_host_tier(host_tier_cluster):
    """The fleet storyline: both replicas cache the shared prefix, churn
    demotes it into each replica's host tier, then the replica serving
    the tagged request is killed mid-stream. The survivor must resume
    byte-identical — serving the prompt's prefix by PROMOTING it from
    its own host tier, not recomputing it."""
    serve, handle, ecfg = host_tier_cluster
    from ray_tpu.serve.llm import LLMEngine, stream_tokens

    prefix = _shared_prefix()
    kill_prompt = prefix + [9, 8, 7]

    # (1) warm BOTH replicas: random placement reaches each within a few
    # sequential streams; the gate is per-replica cached-prefix state
    for i in range(30):
        _run_stream(handle, {"prompt": prefix + [3, 1],
                             "request_id": f"warm-{i}", "max_new_tokens": 4})
        stats = _live_stats(handle)
        if len(stats) >= 2 and all(
            s.get("prefix_cached_blocks", 0) >= PREFIX_BLOCKS for s in stats
        ):
            break
    else:
        pytest.fail("could not warm the prefix onto both replicas")

    # (2) churn both replicas dry: the warm prefix is each pool's
    # LRU-oldest content, so its blocks are the FIRST demotions
    for i in range(60):
        _run_stream(handle, {"prompt": [100 + i] * 17,
                             "request_id": f"churn-{i}", "max_new_tokens": 4})
        stats = _live_stats(handle)
        if len(stats) >= 2 and all(
            s.get("kv_demoted_blocks", 0) >= PREFIX_BLOCKS for s in stats
        ):
            break
    else:
        pytest.fail("churn did not demote the prefix on both replicas")
    assert all(s.get("kv_promoted_blocks", 0) == 0 for s in stats), (
        "no promotion may happen before the storyline request"
    )

    # (3) uninterrupted reference from a local engine with the replica
    # config — replicas init params from the identical PRNG key
    reference = LLMEngine(ecfg, auto_step=False).generate(
        kill_prompt, **KILL_SAMPLING)

    gen = stream_tokens(handle, {
        "prompt": kill_prompt,
        "request_id": "kill-req-1",
        "chaos_tag": "killme",
        **KILL_SAMPLING,
    })
    chunks = list(gen)
    assert gen.failovers >= 1, "the chaos kill should have forced a failover"
    assert [c["index"] for c in chunks] == list(
        range(KILL_SAMPLING["max_new_tokens"]))
    assert [c["token"] for c in chunks] == reference

    # (4) the survivor resumed the stream AND promoted the prefix from
    # its own host tier (the killed replica's counters died with it)
    stats = _live_stats(handle)
    resumed = [s for s in stats if s.get("requests_resumed", 0) >= 1]
    assert resumed, "no live replica recorded the resume"
    assert any(
        s.get("kv_promoted_blocks", 0) >= PREFIX_BLOCKS for s in resumed
    ), f"survivor served the resume without promoting: {stats}"
