"""The EvaByte family on the CPU at a tiny size (hidden 64, 4 heads of 16,
a window of 32, chunks of 4, 2 output heads), seeded weights, float32: the
program against the plain reference (benchmark/reference/evabyte.py); the
serving path (prefill in chunks, then decode through the ring and the
summary table, composed into a step's table) against the reference's full
forward across two window closes; the WRONG models (a window or a chunk off
by one, a summary seen a window early, ``mu`` left out) refused by the same
tolerance; the cache manager's two kinds of table; rows at different
windows batched continuously; the chunk summaries' two backends; what the
engine refuses and why; the counters and the dispatch spans.

Program and reference in float32 compute the same mathematics and differ in
the order of sums: 1e-4 on logits of size ~2 (seen 2.4e-6); a wrong model
moves them by 0.08 or more.
"""
from __future__ import annotations

import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

W, C, BS = 32, 4, 4
TOL = 1e-3  # on the deficit of the engine's token under a reference


@pytest.fixture(scope="module")
def ref():
    from benchmark import common

    return common.load_named("reference", "evabyte")


@pytest.fixture(scope="module")
def tiny(jax_cpu):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.evabyte import EvaByteConfig, evabyte_init

    cfg = dataclasses.replace(EvaByteConfig.tiny(), dtype=jnp.float32)
    assert (cfg.window_size, cfg.chunk_size, cfg.num_pred_heads) == (W, C, 2)
    return cfg, evabyte_init(jax.random.PRNGKey(1), cfg)


def _engine(cfg, params, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    settings = dict(model="evabyte", model_config=cfg, block_size=BS,
                    num_blocks=257, max_batch_size=4, prefill_chunk_tokens=16,
                    length_buckets=(16, 160))
    settings.update(kw)
    return LLMEngine(EngineConfig(**settings), params=params,
                     auto_step=False)


def _prompts(lens, seed=0, vocab=320):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=n).tolist() for n in lens]


def _drive(engine, streams, limit=4000):
    for _ in range(limit):
        if all(s.done for s in streams):
            return
        engine.step()
    raise AssertionError("streams did not finish")


def _deficit(ref, params, cfg, prompt, out, **variant):
    """The largest gap between the reference's largest next-byte logit and
    its logit of the engine's token, over the generated positions."""
    import jax.numpy as jnp

    logits = np.asarray(ref.logits(
        params, jnp.asarray([prompt + out]), cfg, **variant))[0, :, 0]
    rows = logits[len(prompt) - 1: len(prompt) + len(out) - 1]
    return float((rows.max(-1) - rows[np.arange(len(out)), out]).max())


# ------------------------------------------------ (a) program == reference


@pytest.mark.parametrize("length", [3, W, W + 1, 3 * W + 5])
def test_forward_is_the_references_on_every_output_head(tiny, ref, length):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.evabyte import evabyte_forward

    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(length), (2, length), 0,
                                cfg.vocab_size)
    got = np.asarray(evabyte_forward(params, tokens, cfg))
    want = np.asarray(ref.logits(params, tokens, cfg))
    assert got.shape == want.shape == (2, length, 2, cfg.vocab_size)
    np.testing.assert_allclose(got, want, atol=1e-4)
    # the served step's head is output head 0: the next byte
    at = jnp.asarray([[0, length - 1]] * 2)
    np.testing.assert_allclose(
        np.asarray(ref.logits_at(params, tokens, at, cfg)),
        want[:, [0, length - 1], 0], atol=1e-5)


def test_the_whole_model_is_the_rows_six_and_a_half_billion(jax_cpu):
    import jax

    from ray_tpu.models.evabyte import EvaByteConfig, evabyte_init

    def count(cfg):
        shapes = jax.eval_shape(
            lambda: evabyte_init(jax.random.PRNGKey(0), cfg))
        return sum(a.size for a in jax.tree.leaves(shapes))

    assert abs(count(EvaByteConfig()) - 6.489e9) < 2e6
    assert abs(count(EvaByteConfig(n_layer=8)) - 1631e6) < 1e6
    head = jax.eval_shape(lambda: evabyte_init(
        jax.random.PRNGKey(0), EvaByteConfig(n_layer=1)))["lm_head"]
    assert head.shape == (4096, 8 * 320)  # the published shape
    with pytest.raises(ValueError, match="chunk_size"):
        EvaByteConfig(window_size=32, chunk_size=5)


# -------------------------------- (b) prefill + decode through the cache


# shorter than a chunk, not a multiple of C, exactly W, W + 1, 2W - 1, and
# a prompt of several prefill chunks past a window
PROMPTS = [3, 10, W, W + 1, 2 * W - 1, 50]


@pytest.fixture(scope="module")
def served(tiny):
    """Every prompt of PROMPTS served alone, 70 new tokens each: decoding
    crosses two window closes whatever the prompt."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    out = {}
    for n, prompt in zip(PROMPTS, _prompts(PROMPTS, seed=3)):
        stream = engine.submit(prompt, max_new_tokens=70, temperature=0.0)
        _drive(engine, [stream])
        out[n] = (prompt, list(stream))
        assert len(out[n][1]) == 70
        assert (n + 69) // W - (n - 1) // W >= 2  # windows closed in decode
    stats = engine.stats()
    engine.shutdown()
    return out, stats


@pytest.mark.parametrize("length", PROMPTS)
def test_engine_through_the_two_tables_is_the_references_forward(
        tiny, ref, served, length):
    cfg, params = tiny
    prompt, out = served[0][length]
    assert _deficit(ref, params, cfg, prompt, out) < TOL


def test_served_counters_and_programs(served):
    _, stats = served
    assert stats["kv_used_blocks"] == 0 and stats["prefix_reuse"] is False
    assert "ring as it stood" in stats["prefix_reuse_why_not"]
    total = sum(n + 70 for n in PROMPTS)
    # a request's last token is sampled and never written
    written = sum((n + 69) // C for n in PROMPTS)
    assert stats["eva_chunks_written_prefill"] \
        + stats["eva_chunks_written_decode"] == written
    assert stats["eva_chunks_written_prefill"] == sum(
        16 * (n // 16) // C + (n % 16) // C for n in PROMPTS)
    assert stats["eva_windows_closed_prefill"] == sum(
        n // W for n in PROMPTS)
    assert stats["eva_windows_closed_prefill"] \
        + stats["eva_windows_closed_decode"] == sum(
            (n + 69) // W for n in PROMPTS)
    ring, slots = stats["kv_groups"]
    assert (ring["kind"], ring["window"], ring["blocks"]) == ("ring", W, 0)
    assert (slots["kind"], slots["every"], slots["blocks"]) == (
        "slots", C, 0)
    assert ring["high_water_blocks"] == W // BS  # a window, never more
    assert slots["high_water_blocks"] == -(-(-(-(max(PROMPTS) + 69) // C))
                                           // BS)
    assert total > 0


# ------------------- (b, c) logits through the cache manager's two tables


def _logits_through_the_cache(cfg, params, tokens, prompt_len, steps):
    """The family's own step functions (logits out, no sampling; jitted
    once, ``steps``) over the REAL cache manager's composed tables: the
    prompt in chunks of 16, then one token a step, teacher-forced.
    {position: next-byte logits}."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.llm.kv_cache import KVCacheConfig, PagedKVCache

    evabyte_prefill, evabyte_decode_step = steps

    cache = PagedKVCache(KVCacheConfig(
        n_layer=cfg.n_layer, n_kv_head=cfg.n_kv_head, head_dim=cfg.head_dim,
        num_blocks=65, block_size=BS, dtype=jnp.float32, prefix_reuse=False,
        groups=cfg.kv_table_groups))
    cache.allocate(0)
    k, v = cache.k, cache.v
    width = cache.cfg.composed_blocks(max(PROMPTS) + 70)
    out = {}
    done = 0
    with jax.default_matmul_precision("highest"):
        while done < prompt_len:
            n = min(16, prompt_len - done)
            cache.ensure_capacity(0, done + n, reserved=False)
            chunk = np.zeros((1, 16), np.int32)
            chunk[0, :n] = tokens[done:done + n]
            fresh = done == 0 and n == prompt_len
            logits, k, v, _ = evabyte_prefill(
                params, k, v, jnp.asarray(chunk), jnp.asarray([n]),
                jnp.asarray(cache.block_table(0, width, done)[:, None]),
                start=None if fresh else jnp.asarray([done]))
            done += n
            out[done - 1] = np.asarray(logits)[0]
        for pos in range(prompt_len, len(tokens)):
            cache.ensure_capacity(0, pos + 1, reserved=False)
            logits, k, v, _ = evabyte_decode_step(
                params, k, v, jnp.asarray(tokens[pos:pos + 1]),
                jnp.asarray([pos]),
                jnp.asarray(cache.block_table(0, width, pos)[:, None]))
            out[pos] = np.asarray(logits)[0]
    assert cache.free(0) == cache.cfg.request_blocks(len(tokens))
    return out


@pytest.fixture(scope="module")
def through_the_cache(tiny):
    """{prompt length: (tokens, {position: logits})}, 70 tokens decoded
    after each prompt: two window closes inside every one."""
    import functools

    import jax

    from ray_tpu.models.evabyte import evabyte_decode_step, evabyte_prefill

    cfg, params = tiny
    steps = [jax.jit(functools.partial(fn, cfg=cfg))
             for fn in (evabyte_prefill, evabyte_decode_step)]
    out = {}
    for n in PROMPTS:
        tokens = np.asarray(jax.random.randint(
            jax.random.PRNGKey(n), (n + 70,), 1, cfg.vocab_size))
        out[n] = (tokens, _logits_through_the_cache(
            cfg, params, tokens, n, steps))
    return out


def _gap(ref, params, cfg, tokens, got, **variant):
    """Largest difference between the served logits and a reference's."""
    import jax.numpy as jnp

    want = np.asarray(ref.logits(
        params, jnp.asarray(tokens[None]), cfg, **variant))[0, :, 0]
    return max(float(np.abs(want[pos] - row).max())
               for pos, row in got.items())


@pytest.mark.parametrize("length", PROMPTS)
def test_cached_steps_over_composed_tables_match_the_reference_logits(
        tiny, ref, through_the_cache, length):
    cfg, params = tiny
    tokens, got = through_the_cache[length]
    assert len(got) >= 70 and max(got) == length + 69
    assert _gap(ref, params, cfg, tokens, got) < 1e-4


@pytest.mark.parametrize("variant", [
    {"window": W - 1}, {"window": W + 1}, {"chunk": C - 1}, {"chunk": C + 1},
    {"early": 1}, {"use_mu": False},
], ids=lambda v: "-".join(f"{k}={x}" for k, x in v.items()))
def test_a_wrong_model_is_refused_by_the_same_tolerance(
        tiny, ref, through_the_cache, variant):
    """The reference with a window or a chunk off by one, a summary
    visible one window early, or ``mu`` left out is another model: the
    served logits, within 1e-4 of the right reference's, stand two hundred
    times further from its (seen 0.08 to 1.4), for the short prompt and
    the long alike."""
    cfg, params = tiny
    for n in (3, 2 * W - 1):
        tokens, got = through_the_cache[n]
        assert _gap(ref, params, cfg, tokens, got, **variant) > 0.02, n


# ----------------------------------------------------- (d) the cache manager


def _cache(num_blocks=129, window=W, chunk=C, bs=BS):
    from ray_tpu.serve.llm.kv_cache import KVCacheConfig, PagedKVCache

    return PagedKVCache(KVCacheConfig(
        n_layer=2, n_kv_head=1, head_dim=4, num_blocks=num_blocks,
        block_size=bs, prefix_reuse=False,
        groups=((("ring", window), (0, 1)), (("slots", chunk), (0, 1)))))


@pytest.mark.parametrize("n,want", [
    (1, 1 + 1), (16, 4 + 1), (17, 5 + 2), (W, 8 + 2), (W + 1, 8 + 3),
    (10 * W, 8 + 20),
])
def test_request_blocks_is_a_window_and_a_slot_a_chunk(n, want):
    cfg = _cache().cfg
    assert cfg.request_blocks(n) == want == min(
        -(-n // BS), W // BS) + -(-(-(-n // C)) // BS)
    # the issue's formula at the published sizes
    from ray_tpu.serve.llm.kv_cache import KVCacheConfig

    real = KVCacheConfig(
        n_layer=8, n_kv_head=32, head_dim=128, num_blocks=4353,
        groups=((("ring", 2048), ()), (("slots", 16), ())))
    for tokens in (1, 300, 2048, 2049, 5973, 18432):
        assert real.request_blocks(tokens) == min(
            -(-tokens // 16), 128) + -(-tokens // 256)
    assert real.composed_blocks(18432) == 64 + 128
    assert real.composed_blocks(2048) == 128


def test_ring_is_reused_and_the_summary_table_grows():
    cache = _cache()
    need = cache.cfg.request_blocks(100)
    cache.reserve(need)
    cache.allocate("a")
    ring_at_a_window = None
    for tokens in range(1, 101):
        cache.ensure_capacity("a", tokens)
        ring, slots = cache._group_tables("a")
        assert len(ring) == min(-(-tokens // BS), W // BS)
        assert len(slots) == -(-(-(-tokens // C)) // BS)
        if tokens == W:
            ring_at_a_window = list(ring)
        if tokens > W:
            assert ring == ring_at_a_window  # reused in place, for ever
        # the composed table of a query at the last position
        pos = tokens - 1
        table = cache.block_table("a", 32, pos)
        closed = (W // C // BS) * (pos // W)
        assert table.shape == (2, 32)
        assert list(table[0, :closed]) == slots[:closed]
        assert list(table[0, closed: closed + len(ring)]) == ring
        assert not table[0, closed + len(ring):].any()
        assert list(table[1, : len(slots)]) == slots
        assert cache.table_epoch(pos) == pos // W
    assert cache.reserved_blocks == 0  # all of it was drawn, no more
    report = cache.group_report()
    assert [g["blocks"] for g in report] == [8, 7]
    assert cache.stats.high_water_blocks == 15 == cache.used_blocks
    with pytest.raises(ValueError, match="composes"):
        cache.block_table("a", 8, 99)
    assert cache.free("a") == 15
    assert sorted(cache._free) == list(range(1, 129))  # every block back
    assert [g["blocks"] for g in cache.group_report()] == [0, 0]
    assert [g["high_water_blocks"] for g in cache.group_report()] == [8, 7]
    assert cache.stats.window_blocks_taken == 0  # no sliding group here
    assert cache.free_behind("a", 50) == 0 if "a" in cache._tables else True


def test_a_block_size_that_cuts_a_window_or_its_chunks_is_refused():
    with pytest.raises(ValueError, match="block_size"):
        _cache(bs=16)  # the window's 8 chunks are half a block
    with pytest.raises(ValueError, match="block_size"):
        _cache(window=36, chunk=4, bs=8)
    from ray_tpu.serve.llm.kv_cache import KVCacheConfig

    with pytest.raises(ValueError, match="one ring and one slot table"):
        KVCacheConfig(n_layer=1, n_kv_head=1, head_dim=4,
                      groups=((("slots", 4), (0,)), (("ring", 32), (0,))))


# --------------------------------------- (e) rows at different windows


def test_continuous_batching_gives_the_single_rows_streams(tiny, served):
    """Four rows whose positions lie in different windows, joining and
    leaving one batch: each stream is the one its prompt gave alone."""
    cfg, params = tiny
    lens = [3, W + 1, 2 * W - 1, 50]
    engine = _engine(cfg, params)
    streams = []
    for n in lens:
        streams.append(engine.submit(
            served[0][n][0], max_new_tokens=70, temperature=0.0))
        for _ in range(3):  # the next one joins rows already decoding
            engine.step()
    _drive(engine, streams)
    for n, s in zip(lens, streams):
        assert list(s) == served[0][n][1], n
    assert {sig[0] for sig in engine.fns.signatures} >= {
        "prefill", "prefill_chunk", "decode"}
    # one table width whatever the context: that of the longest bucket
    widths = {sig[2] for sig in engine.fns.signatures if sig[0] != "prefill"}
    assert {w[0] for w in widths} == {2}
    assert {w[2] for w in widths} == {engine.cache.cfg.composed_blocks(160)}
    assert engine.stats()["kv_used_blocks"] == 0
    engine.shutdown()


def test_engine_on_the_kernel_path_is_the_references_forward(tiny, ref):
    """The Pallas backend (interpret mode): the composed table handed to
    the compute-block kernel, the summaries from the ``eva_summarize``
    kernel; chunked prefill past a window, then decode across a close."""
    cfg, params = tiny
    cfg = dataclasses.replace(cfg, attention_backend="pallas")
    engine = _engine(cfg, params)
    prompt = _prompts([W + 9], seed=5)[0]
    stream = engine.submit(prompt, max_new_tokens=30, temperature=0.0)
    _drive(engine, [stream])
    assert _deficit(ref, params, cfg, prompt, list(stream)) < TOL
    engine.shutdown()


@pytest.mark.parametrize("shape", [(5, C, 4, 16), (2, 3, 16, 32, 128)])
def test_chunk_summaries_backends_agree_with_the_reference(
        jax_cpu, ref, shape):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.eva import chunk_summaries

    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    k, v = (jax.random.normal(key, shape, jnp.float32) for key in keys[:2])
    phi, mu = (jax.random.normal(key, shape[-2:], jnp.float32)
               for key in keys[2:])
    xla = chunk_summaries(k, v, phi, mu, backend="xla")
    kernel = chunk_summaries(k, v, phi, mu, backend="pallas", interpret=True)
    n, chunk = int(np.prod(shape[:-3])), shape[-3]
    want = ref.summaries(k.reshape(n * chunk, *shape[-2:]),
                         v.reshape(n * chunk, *shape[-2:]), phi, mu, chunk)
    for got in (xla, kernel):
        assert got[0].shape == got[1].shape == shape[:-3] + shape[-2:]
        for g, w in zip(got, want):
            np.testing.assert_allclose(
                np.asarray(g).reshape(w.shape), np.asarray(w), atol=2e-5)


# --------------------------------------------------- (f) what is refused


@pytest.mark.parametrize("option,match", [
    ({"speculative_k": 2}, "speculative_k.*folded into"),
    ({"host_cache_bytes": 1 << 20}, "host_cache_bytes.*one block a digest"),
    ({"preemption": {}}, "preemption.*neither the ring"),
    ({"quantization": "int8"}, "quantization.*scale planes"),
    ({"tp": 2}, "tp/fsdp/mesh.*one table a step"),
])
def test_what_the_two_tables_cannot_carry_is_refused(tiny, option, match):
    cfg, params = tiny
    with pytest.raises(ValueError, match=match):
        _engine(cfg, params, **option)


@pytest.mark.parametrize("chunk", [None, 12, 24, 64, 2])
def test_a_prefill_chunk_that_does_not_tile_a_window_is_refused(tiny, chunk):
    """A prefill step lies inside one window and is whole chunks: 12 and 24
    do not divide 32, 64 spans two windows, 2 is half a chunk, and no
    chunking at all is a whole prompt a step."""
    cfg, params = tiny
    with pytest.raises(ValueError, match="prefill_chunk_tokens must divide"):
        _engine(cfg, params, prefill_chunk_tokens=chunk)


def test_handoff_is_refused_and_prefix_reuse_says_why_not(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompt = _prompts([20], seed=6)[0]
    with pytest.raises(ValueError, match="handoff.*last window"):
        engine.export_prefix(prompt)
    with pytest.raises(ValueError, match="handoff"):
        engine.adopt_prefix(prompt, [])
    # prefix caching is on by default and finds nothing, with its reason
    first = engine.submit(prompt, max_new_tokens=2, temperature=0.0)
    _drive(engine, [first])
    again = engine.submit(prompt, max_new_tokens=2, temperature=0.0)
    _drive(engine, [again])
    stats = engine.stats()
    assert list(first) == list(again)
    assert stats["prefix_hit_tokens"] == 0 and not stats["prefix_reuse"]
    assert "summary blocks are not content-addressed" in \
        stats["prefix_reuse_why_not"]
    engine.shutdown()
    from ray_tpu.serve.llm import LLMEngine

    assert LLMEngine._no_prefix_reuse(False, False, False) is None
    assert "state" in LLMEngine._no_prefix_reuse(True, False, False)
    assert "given back" in LLMEngine._no_prefix_reuse(False, True, False)


# ------------------------------------------------------ spans and records


def test_dispatch_spans_carry_what_the_composed_table_reads(
        tiny, monkeypatch):
    from ray_tpu.serve.llm import obs

    cfg, params = tiny
    engine = _engine(cfg, params)
    seen = []
    real = obs.phase

    def spy(table, name, **attrs):
        if name == "executor.dispatch":
            seen.append(attrs)
        return real(table, name, **attrs)

    monkeypatch.setattr(obs, "phase", spy)
    streams = [engine.submit(p, max_new_tokens=3, temperature=0.0)
               for p in _prompts([2 * W + 5], seed=7)]
    _drive(engine, streams)
    decodes = [a for a in seen if a["kind"] == "decode"]
    # the first decode step writes position 69, context 70: whole blocks
    # of 4; 6 rows of its own window, the 8 summaries of each closed one
    assert [a["kv_tokens"] for a in decodes] == [72, 72]
    assert [a["kv_tokens_window"] for a in decodes] == [69 % W + 1,
                                                        70 % W + 1]
    assert [a["kv_chunks"] for a in decodes] == [2 * (W // C)] * 2
    assert all(a["eva_chunks"] == 1 for a in decodes)  # the row's bucket
    chunks = [a for a in seen if a["kind"] == "prefill_chunk"]
    assert chunks and all(a["eva_chunks"] % (16 // C) == 0 for a in chunks)
    flight = engine.debug_dump()["steps"]
    assert any(s.get("kv_chunks") == 2 * (W // C) for s in flight
               if s["kind"] == "decode")
    engine.shutdown()


def test_widened_pipeline_matches_solo_runs(tiny):
    """ISSUE 33's schedule (conftest ``run_widened_schedule``): a step's
    composed table is made from its rows' positions, which the host knows
    before any sync, so a decode step is launched behind a chunk still in
    flight; the streams are the bytes of solo runs."""
    from conftest import run_widened_schedule

    cfg, params = tiny
    st = run_widened_schedule(lambda **kw: _engine(cfg, params, **kw),
                              cfg.vocab_size)
    assert st["eva_windows_closed_prefill"] + \
        st["eva_windows_closed_decode"] > 0
