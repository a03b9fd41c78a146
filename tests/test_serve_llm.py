"""Continuous-batching LLM engine (ray_tpu.serve.llm): paged-KV parity
with the full-sequence forward, continuous batching == solo decoding,
block reuse, bounded compile cache, metrics, and end-to-end streaming
through the Serve ingress paths.

Parity tests run f32 + XLA attention so the cached path and the
full-sequence reference share identical numerics (bf16 is the serving
default; the engine is dtype-agnostic).
"""
from __future__ import annotations

import dataclasses
import json
import urllib.request

import numpy as np
import pytest

from conftest import serve_http_url, shutdown_if_setup_fails


def _f32(cfg):
    import jax.numpy as jnp

    return dataclasses.replace(cfg, dtype=jnp.float32, attention="xla")


def _family_setup(family):
    if family == "gpt":
        from ray_tpu.models.gpt import GPTConfig, gpt_forward

        return _f32(GPTConfig.tiny()), gpt_forward
    from ray_tpu.models.llama import LlamaConfig, llama_forward

    # tiny() has n_kv_head=2 < n_head=4 — GQA exercised in the cached path
    return _f32(LlamaConfig.tiny()), llama_forward


def _engine(family, mc, *, auto_step=False, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 64)
    return LLMEngine(
        EngineConfig(model=family, model_config=mc, **kw), auto_step=auto_step
    )


# ------------------------------------------------------------------ (a)

@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_paged_decode_logits_match_full_forward(jax_cpu, family):
    """Prefill + per-token cached decode logits == full-sequence forward
    logits at the same position, for both model families."""
    import jax, jax.numpy as jnp
    from ray_tpu.serve.llm.decode import DecodeFns
    from ray_tpu.serve.llm.kv_cache import KVCacheConfig, PagedKVCache

    mc, forward = _family_setup(family)
    fns = DecodeFns(family, mc)
    params = fns.init(jax.random.PRNGKey(0), mc)
    bs = 8
    cache = PagedKVCache(KVCacheConfig(
        n_layer=mc.n_layer,
        n_kv_head=getattr(mc, "n_kv_head", mc.n_head),
        head_dim=mc.head_dim, num_blocks=32, block_size=bs, dtype=mc.dtype,
    ))

    prompt = [3, 141, 59, 26, 250, 7, 91]
    seq = list(prompt)
    cache.allocate("s")
    cache.ensure_capacity("s", len(prompt), reserved=False)
    tokens = np.zeros((1, 8), np.int32)
    tokens[0, : len(prompt)] = prompt
    logits, cache.k, cache.v, _ = fns.prefill(
        params, cache.k, cache.v,
        jnp.asarray(tokens), jnp.asarray([len(prompt)], np.int32),
        jnp.asarray(cache.block_table("s", 1)[None, :]),
    )
    full = forward(params, jnp.asarray([seq], jnp.int32), mc)[:, -1]
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full), atol=2e-4, rtol=2e-4
    )

    for _ in range(5):
        tok = int(np.argmax(np.asarray(logits)[0]))
        seq.append(tok)
        cache.ensure_capacity("s", len(seq), reserved=False)
        nb = -(-16 // bs)  # context bucket 16 for these lengths
        logits, cache.k, cache.v, _ = fns.decode(
            params, cache.k, cache.v,
            jnp.asarray([tok], np.int32),
            jnp.asarray([len(seq) - 1], np.int32),
            jnp.asarray(cache.block_table("s", nb)[None, :]),
        )
        full = forward(params, jnp.asarray([seq], jnp.int32), mc)[:, -1]
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full), atol=2e-4, rtol=2e-4
        )


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_engine_tokens_match_naive_full_forward_decode(jax_cpu, family):
    """Acceptance parity: greedy tokens through the paged-KV engine equal
    a naive recompute-everything argmax decode."""
    import jax.numpy as jnp

    mc, forward = _family_setup(family)
    eng = _engine(family, mc)
    prompt = [5, 9, 17, 3, 250, 33]
    toks = eng.generate(prompt, max_new_tokens=6)

    seq, naive = list(prompt), []
    for _ in range(6):
        logits = forward(eng.params, jnp.asarray([seq], jnp.int32), mc)
        t = int(np.argmax(np.asarray(logits)[0, -1]))
        naive.append(t)
        seq.append(t)
    assert toks == naive


# ------------------------------------------------------------------ (b)

def test_continuous_batching_matches_solo(jax_cpu):
    """Staggered mixed-length requests joining/leaving the running batch
    produce per-request outputs identical to solo runs."""
    mc, _ = _family_setup("llama")
    prompts = [[1, 2, 3], [7] * 11, [100, 200, 300, 400, 5], [250, 250]]

    solo = [
        _engine("llama", mc).generate(p, max_new_tokens=8) for p in prompts
    ]

    eng = _engine("llama", mc)
    streams = [eng.submit(prompts[0], max_new_tokens=8)]
    eng.step()  # prefill req0
    eng.step()  # decode — req0 alone
    streams.append(eng.submit(prompts[1], max_new_tokens=8))
    eng.step()  # prefill req1 joins
    streams.append(eng.submit(prompts[2], max_new_tokens=8))
    streams.append(eng.submit(prompts[3], max_new_tokens=8))
    for _ in range(200):
        if all(s.done for s in streams):
            break
        eng.step()
    assert [list(s) for s in streams] == solo


def test_sampling_deterministic_per_seed(jax_cpu):
    mc, _ = _family_setup("llama")
    eng = _engine("llama", mc)
    kw = dict(max_new_tokens=5, temperature=0.7, top_k=4, seed=123)
    a = eng.generate([3, 1, 4], **kw)
    b = eng.generate([3, 1, 4], **kw)
    assert a == b
    greedy = eng.generate([3, 1, 4], max_new_tokens=5)
    assert eng.generate([3, 1, 4], max_new_tokens=5, top_k=1,
                        temperature=0.5) == greedy


# ------------------------------------------------------------------ (c)

def test_kv_blocks_freed_and_reused(jax_cpu):
    """Blocks freed on completion are reused: the allocator high-water
    mark is set by CONCURRENT load, not total traffic."""
    mc, _ = _family_setup("llama")
    eng = _engine("llama", mc, num_blocks=17)  # 16 usable
    # each request needs ceil((5+8)/8)=2 blocks -> 8 fit concurrently
    streams = [eng.submit([i + 1] * 5, max_new_tokens=8) for i in range(12)]
    for _ in range(400):
        if all(s.done for s in streams):
            break
        eng.step()
    assert all(s.done for s in streams)
    st = eng.stats()
    assert st["kv_used_blocks"] == 0, "completion must free all blocks"
    assert st["kv_high_water_blocks"] <= 16
    assert eng.cache.stats.allocated_total == 24  # 2 per request
    assert eng.cache.stats.freed_total == 24
    # sequential load never needs more than one request's blocks live
    eng2 = _engine("llama", mc, num_blocks=17)
    for i in range(6):
        eng2.generate([i + 1] * 5, max_new_tokens=8)
    assert eng2.cache.stats.high_water_blocks <= 2


def test_admission_queues_when_pool_exhausted(jax_cpu):
    """Requests beyond the reservation capacity wait, then run to
    completion as finished sequences return their blocks."""
    mc, _ = _family_setup("llama")
    eng = _engine("llama", mc, num_blocks=5)  # 4 usable -> 2 concurrent
    streams = [eng.submit([9, 9, 9], max_new_tokens=8) for _ in range(5)]
    eng.step()
    assert eng.stats()["waiting"] == 3  # only 2 reservations fit
    for _ in range(400):
        if all(s.done for s in streams):
            break
        eng.step()
    outs = [list(s) for s in streams]
    assert all(len(o) == 8 for o in outs)
    assert len({tuple(o) for o in outs}) == 1  # same prompt -> same tokens


# ------------------------------------------- compile-count guard

def test_bounded_compiled_shapes(jax_cpu):
    """Staggered requests of many distinct lengths compile only a bounded
    set of (batch-bucket, length-bucket) shapes."""
    mc, _ = _family_setup("llama")
    eng = _engine(
        "llama", mc, block_size=8, max_batch_size=4,
        batch_buckets=(1, 2, 4), length_buckets=(8, 16, 32),
    )
    lengths = [1, 2, 3, 5, 7, 9, 11, 13, 17, 21]  # 10 distinct lengths
    streams = []
    for i, n in enumerate(lengths):
        streams.append(eng.submit([(i + 3)] * n, max_new_tokens=4))
        eng.step()  # stagger: varying running-batch sizes
    for _ in range(400):
        if all(s.done for s in streams):
            break
        eng.step()
    assert all(s.done for s in streams)
    # hard ceiling: decode's batch buckets * length buckets, and the
    # packed prefill ladder (rows of one piece, whatever the ten lengths)
    assert eng.num_compiled_shapes <= 3 * 3 + len(eng._piece_rows)
    sigs = eng.fns.signatures
    assert {s[1] for s in sigs if s[0] != "decode"} == {
        (rows, 32) for rows in (1, 2, 3, 4)}
    for kind, tok_shape, table_shape in sigs:
        if kind == "decode":
            assert tok_shape[0] in (1, 2, 4)  # every call hit a batch bucket


# ------------------------------------------- metrics

def test_engine_metrics_exported(jax_cpu):
    from ray_tpu.util import metrics

    mc, _ = _family_setup("llama")
    eng = _engine("llama", mc)
    eng.generate([1, 2, 3], max_new_tokens=4)
    snap = metrics.collect()
    assert snap.get("llm_engine_tokens_generated_total", 0) >= 4
    assert "llm_engine_queue_depth" in snap
    assert "llm_engine_kv_block_utilization" in snap
    prefill_count = snap.get(
        'llm_engine_step_latency_seconds_count{kind=prefill_chunk}', 0)
    decode_count = snap.get(
        'llm_engine_step_latency_seconds_count{kind=decode}', 0)
    assert prefill_count >= 1 and decode_count >= 3


def test_pad_to_bucket_shared_implementation():
    """Satellite: one padding rule for @serve.batch and the engine."""
    from ray_tpu.serve import pad_to_bucket as a
    from ray_tpu.serve.batching import pad_to_bucket as b
    from ray_tpu.serve._shapes import pad_to_bucket as c, pow2_buckets

    assert a is b is c
    assert a(3, (2, 4, 8)) == 4 and a(9, (2, 4, 8)) == 8
    assert pow2_buckets(8, 48) == (8, 16, 32, 48)
    assert pow2_buckets(1, 8) == (1, 2, 4, 8)


# ------------------------------------------------------------------ (d)

@pytest.fixture(scope="module")
def llm_cluster():
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import EngineConfig, build_llm_app

    ray_tpu.init(num_cpus=6)
    with shutdown_if_setup_fails():
        serve.start(http_options={"port": 0})
        handle = serve.run(
            build_llm_app(EngineConfig(model="llama", seed=0)),
            name="llm", route_prefix="/llm", timeout_s=180,
        )
    yield serve, handle
    serve.shutdown()
    ray_tpu.shutdown()


def test_streaming_through_handle(llm_cluster):
    from ray_tpu.serve import DeploymentResponseGenerator

    _, handle = llm_cluster
    resp = handle.remote({"prompt": "hi there", "max_new_tokens": 6})
    assert isinstance(resp, DeploymentResponseGenerator)
    chunks = list(resp)
    assert [c["index"] for c in chunks] == list(range(6))
    assert all(isinstance(c["token"], int) for c in chunks)
    # greedy: a second identical request reproduces the stream exactly
    again = [c["token"] for c in
             handle.remote({"prompt": "hi there", "max_new_tokens": 6})]
    assert again == [c["token"] for c in chunks]
    stats = handle.stats.remote().result(timeout=120)
    assert stats["num_compiled_shapes"] >= 2


def test_streaming_through_http_sse(llm_cluster):
    _, handle = llm_cluster
    expected = [c["token"] for c in
                handle.remote({"prompt": "hi there", "max_new_tokens": 6})]
    req = urllib.request.Request(
        serve_http_url("/llm"),
        data=json.dumps({"prompt": "hi there", "max_new_tokens": 6}).encode(),
        headers={"Content-Type": "application/json",
                 "Accept": "text/event-stream"},
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        assert resp.headers["Content-Type"].startswith("text/event-stream")
        events = [json.loads(line[len(b"data: "):])
                  for line in resp if line.startswith(b"data: ")]
    assert [e["token"] for e in events] == expected
