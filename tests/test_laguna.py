"""The laguna family on the CPU at the tiny preset, seeded weights, float32:
the program against the plain reference (benchmark/reference/laguna.py), the
serving path (chunked prefill, then decode through the tables by group, the
context past the window and blocks given back behind it) against the
reference's full forward, the expert layer told which experts it holds, the
windowed kernel against the XLA path at GQA groups of 6 and 8, YaRN's
frequencies against transformers' own, what the engine refuses, and the
counters.

Program and reference in float32 compute the same mathematics and differ in
the order of sums: 1e-4 on logits of size ~4 (seen 2.2e-5).
"""
from __future__ import annotations

import dataclasses
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


@pytest.fixture(scope="module")
def ref():
    from benchmark import common

    return common.load_named("reference", "laguna")


@pytest.fixture(scope="module")
def tiny(jax_cpu):
    """(float32 config that holds experts 2-5 of 8, its seeded params)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.laguna import LagunaConfig, laguna_init

    cfg = dataclasses.replace(LagunaConfig.tiny(), dtype=jnp.float32,
                              experts_held=(2, 4))
    return cfg, laguna_init(jax.random.PRNGKey(1), cfg)


def _engine(cfg, params, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    settings = dict(model="laguna", model_config=cfg, block_size=4,
                    num_blocks=129, max_batch_size=4, prefill_chunk_tokens=16,
                    length_buckets=(16, 32, 64, 128))
    settings.update(kw)
    return LLMEngine(EngineConfig(**settings), params=params,
                     auto_step=False)


def _prompts(lens, seed=0, vocab=512):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=n).tolist() for n in lens]


def _drive(engine, streams, limit=4000):
    for _ in range(limit):
        if all(s.done for s in streams):
            return
        engine.step()
    raise AssertionError("streams did not finish")


# ------------------------------------------------------ the configuration


def test_layout_of_the_published_period(jax_cpu):
    from ray_tpu.models.laguna import LagunaConfig

    cfg = LagunaConfig(layer_types=(
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention") * 2)
    assert cfg.kv_table_groups == (
        (None, (0, 4)), (512, (1, 5)), (512, (2, 6)), (512, (3, 7)))
    assert cfg.n_kv_layer == 2 and cfg.n_layer == 8
    assert [slot for _, slot, _ in cfg.kv_layout] == [0, 0, 0, 0, 1, 1, 1, 1]
    # an uneven stack still gives every layer a slot of its own
    tiny = LagunaConfig.tiny()
    assert tiny.kv_table_groups == ((None, (0, 4)), (8, (1, 3)), (8, (2,)))
    assert len({(g, s) for g, s, _ in tiny.kv_layout}) == tiny.n_layer
    with pytest.raises(ValueError, match="experts_held"):
        LagunaConfig(experts_held=(250, 32))
    with pytest.raises(ValueError, match="layer_types"):
        LagunaConfig(layer_types=("conv",))


def test_the_whole_model_is_the_rows_33_billion(jax_cpu):
    """The row says 33.4B: every leaf of the 40 published layers, counted
    from shapes alone; and the benchmark's cut (8 layers, 32 experts)."""
    import jax

    from ray_tpu.models.laguna import LagunaConfig, laguna_init

    period = ("full_attention",) + ("sliding_attention",) * 3

    def count(cfg):
        shapes = jax.eval_shape(
            lambda: laguna_init(jax.random.PRNGKey(0), cfg))
        return sum(a.size for a in jax.tree.leaves(shapes))

    assert abs(count(LagunaConfig(layer_types=period * 10)) - 33.44e9) < 2e7
    cut = LagunaConfig(layer_types=period * 2, experts_held=(0, 32))
    assert abs(count(cut) - 1477.9e6) < 1e5


def test_yarn_inverse_frequencies_are_transformers_own(jax_cpu, ref):
    """ops/layers.py ``yarn_inv_freq`` and the reference's own formula on
    the row's ``rope_parameters.full_attention`` against transformers'
    ``_compute_yarn_parameters``."""
    pytest.importorskip("torch")
    rope_utils = pytest.importorskip("transformers.modeling_rope_utils")

    from ray_tpu.models.laguna import LagunaConfig
    from ray_tpu.ops.layers import yarn_inv_freq

    scaling = {"rope_type": "yarn", "factor": 64,
               "original_max_position_embeddings": 4096, "beta_slow": 1,
               "beta_fast": 64, "attention_factor": 1.4158883083359672}
    hf = types.SimpleNamespace(
        rope_theta=500000, partial_rotary_factor=0.5, head_dim=128,
        hidden_size=2048, num_attention_heads=48,
        max_position_embeddings=262144, rope_scaling=scaling)
    want, factor = rope_utils._compute_yarn_parameters(hf, "cpu")
    want = want.numpy()
    assert factor == pytest.approx(1.4158883083359672)
    got = yarn_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    assert got.shape == want.shape == (32,) and got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=2e-6)
    np.testing.assert_allclose(
        np.asarray(ref._yarn_inv_freq(LagunaConfig())), want, rtol=2e-6)
    # the blend is not the plain frequencies, nor those over the factor
    plain = 1.0 / 500000.0 ** (np.arange(0, 64, 2) / 64)
    assert not np.allclose(got, plain) and not np.allclose(got, plain / 64)
    assert got[0] == pytest.approx(plain[0])
    assert got[-1] == pytest.approx(plain[-1] / 64, rel=1e-5)


def test_rope_partial_rotates_the_first_part_only(jax_cpu):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.layers import rope, rope_partial

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 16))
    ang = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 4))
    out = rope_partial(x, jnp.cos(ang), jnp.sin(ang))
    np.testing.assert_array_equal(out[..., 8:], x[..., 8:])
    np.testing.assert_allclose(
        out[..., :8], rope(x[..., :8], jnp.cos(ang), jnp.sin(ang)))
    assert float(jnp.abs(out[..., :8] - x[..., :8]).max()) > 0.1
    whole = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 8))
    np.testing.assert_array_equal(
        rope_partial(x, jnp.cos(whole), jnp.sin(whole)),
        rope(x, jnp.cos(whole), jnp.sin(whole)))


# ------------------------------------------------- program == reference


def test_full_forward_matches_the_reference(tiny, ref):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.laguna import laguna_forward

    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 40), 1,
                                cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got = laguna_forward(params, tokens, cfg)
    want = ref.logits(params, tokens, cfg)
    assert want.shape == (2, 40, cfg.vocab_size)
    assert float(jnp.abs(got - want).max()) < 1e-4
    assert float(jnp.abs(want).max()) > 1.0


@pytest.mark.parametrize("change", ["window", "gate", "shared", "held"])
def test_the_reference_notices_each_mechanism(tiny, ref, change):
    """A window one wider, no gate, no shared expert, another share of the
    experts: each moves the logits far past the tolerance above."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.laguna import laguna_forward

    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 40), 1,
                                cfg.vocab_size)
    want = ref.logits(params, tokens, cfg)
    other_cfg, other = cfg, params
    if change == "window":
        other_cfg = dataclasses.replace(cfg, sliding_window=9)
    elif change == "held":
        other_cfg = dataclasses.replace(cfg, experts_held=(0, 4))
    else:
        leaf = {"gate": "attn_gate_w", "shared": "moe_shared_w_out"}[change]
        other = dict(params, layers=[
            {k: (jnp.zeros_like(v) if k == leaf else v)
             for k, v in lp.items()} for lp in params["layers"]])
    got = laguna_forward(other, tokens, other_cfg)
    assert float(jnp.abs(got - want).max()) > 0.05


# ------------------------------------------ the expert layer and its share


def _layer_inputs(T=24, D=32, E=16, F=8, k=3, seed=0):
    import jax

    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(keys[0], (T, D))
    router = jax.random.normal(keys[1], (D, E))
    w_in = jax.random.normal(keys[2], (E, D, 2 * F)) * D ** -0.5
    w_out = jax.random.normal(keys[3], (E, F, D)) * F ** -0.5
    return x, router, w_in, w_out, k


def _by_loop(x, weights, experts, w_in, w_out):
    """The layer as a loop over (token, choice): the plain meaning."""
    import jax
    import jax.numpy as jnp

    out = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for j in range(experts.shape[1]):
            e = int(experts[t, j])
            gate, up = jnp.split(x[t] @ w_in[e], 2)
            out[t] += float(weights[t, j]) * np.asarray(
                (jax.nn.silu(gate) * up) @ w_out[e])
    return out


def test_dropless_without_held_is_the_layer_it_was(jax_cpu):
    """``held=None``: every pair computed; and naming ALL the experts as
    held is the same layer."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.moe import moe_dropless, moe_route

    x, router, w_in, w_out, k = _layer_inputs()
    weights, experts = moe_route(x, router, None, k, scale=2.5)
    with jax.default_matmul_precision("highest"):
        y, sizes = moe_dropless(x, weights, experts, w_in, w_out,
                                dtype=jnp.float32)
        y_all, sizes_all = moe_dropless(x, weights, experts, w_in, w_out,
                                        dtype=jnp.float32, held=(0, 16))
        want = _by_loop(x, weights, experts, w_in, w_out)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(y_all), np.asarray(y), atol=1e-6)
    assert int(sizes.sum()) == x.shape[0] * k
    np.testing.assert_array_equal(np.asarray(sizes), np.asarray(sizes_all))


@pytest.mark.parametrize("valid", [False, True])
def test_the_eight_shares_add_up_to_the_uncut_layer(jax_cpu, valid):
    """The guide's test of shares (model-configs section 4): each of 8
    holders computes the routed part its own 2 of 16 experts give; the
    eight parts add up to the uncut layer's routed output, pair counts
    included, and a holder's part counts only its own pairs."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.moe import moe_dropless, moe_route

    x, router, w_in, w_out, k = _layer_inputs(seed=1)
    mask = (jnp.arange(x.shape[0]) % 5 != 0) if valid else None
    weights, experts = moe_route(x, router, None, k, scale=2.5)
    with jax.default_matmul_precision("highest"):
        whole, sizes = moe_dropless(x, weights, experts, w_in, w_out,
                                    dtype=jnp.float32, valid=mask)
        parts = [moe_dropless(
            x, weights, experts, w_in[f:f + 2], w_out[f:f + 2],
            dtype=jnp.float32, valid=mask, held=(f, 2))
            for f in range(0, 16, 2)]
    total = sum(np.asarray(y) for y, _ in parts)
    np.testing.assert_allclose(total, np.asarray(whole), atol=2e-5)
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(s) for _, s in parts]), np.asarray(sizes))
    assert all(s.shape == (2,) for _, s in parts)
    if valid:  # a padding row gets nothing from anyone
        assert float(np.abs(total[0]).max()) == 0.0
    # one holder alone is NOT the layer
    assert float(np.abs(np.asarray(parts[0][0]) - np.asarray(whole)).max()) \
        > 0.1
    with pytest.raises(ValueError, match="held names"):
        moe_dropless(x, weights, experts, w_in, w_out, dtype=jnp.float32,
                     held=(0, 2))


def test_shares_of_the_model_add_up_with_the_shared_expert_once(tiny, ref):
    """The whole feed-forward: two holders' routed parts plus the shared
    expert ONCE are the uncut layer's output (the reference's parts)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.laguna import laguna_init

    cfg, _ = tiny
    whole = dataclasses.replace(cfg, experts_held=None)
    params = laguna_init(jax.random.PRNGKey(5), whole)
    lp = params["layers"][2]
    h = jax.random.normal(jax.random.PRNGKey(6), (12, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want = ref.shared_part(h, lp) + ref.routed_part(h, lp, whole)
        got = ref.shared_part(h, lp)
        for first in (0, 4):
            share = dataclasses.replace(cfg, experts_held=(first, 4))
            mine = dict(lp, moe_gmm_w_in=lp["moe_gmm_w_in"][first:first + 4],
                        moe_gmm_w_out=lp["moe_gmm_w_out"][first:first + 4])
            got = got + ref.routed_part(h, mine, share)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(ref.shared_part(h, lp)).max()) > 1e-3


# ------------------------------- the windowed kernel == the XLA formulation


@pytest.mark.parametrize("kind", ["decode", "chunk"])
@pytest.mark.parametrize("group", [6, 8])
def test_windowed_kernel_matches_xla(jax_cpu, group, kind):
    """Interpret mode, pages of whole (8, 128) tiles (the compute-block
    kernel, the one laguna's pool takes), a GQA group of 6 and of 8, the
    whole pool read at a layer index. Table entries wholly behind the
    window are block 0, as the cache manager leaves them, and block 0 holds
    NaN for the kernel: it never copies such a page."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.kv_cache import paged_prefill_attention
    from ray_tpu.ops.paged_attention import paged_prefill_attention_pallas

    Hkv, hd, bs, W, NB, B = 8, 128, 16, 40, 12, 2
    S = 1 if kind == "decode" else 24
    keys = jax.random.split(jax.random.PRNGKey(group), 3)
    pool_k = jax.random.normal(keys[0], (2, 1 + B * NB, bs, Hkv, hd))
    pool_v = jax.random.normal(keys[1], (2, 1 + B * NB, bs, Hkv, hd))
    q = jax.random.normal(keys[2], (B, S, group * Hkv, hd))
    last = np.array([150, 97])  # each row's newest position
    pos = jnp.asarray(last[:, None] - (S - 1) + np.arange(S)[None, :],
                      jnp.int32)
    tables = 1 + np.arange(B * NB, dtype=np.int32).reshape(B, NB)
    for b in range(B):  # what free_behind gave back before this step
        tables[b, : max(0, (int(pos[b, 0]) - W + 1) // bs)] = 0
    assert (tables == 0).any()
    tables = jnp.asarray(tables)
    want = paged_prefill_attention(
        q, pool_k[1], pool_v[1], tables, pos, window=W)
    poisoned = (pool_k.at[:, 0].set(jnp.nan), pool_v.at[:, 0].set(jnp.nan))
    got = paged_prefill_attention_pallas(
        q, *poisoned, tables, pos, window=W, layer=1, interpret=True)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # and the window matters: full attention over the same pages differs
    full = paged_prefill_attention(q, pool_k[1], pool_v[1], tables, pos)
    assert float(jnp.abs(full - want).max()) > 1e-2


def test_windowed_calls_have_a_kernel_name_of_their_own(jax_cpu):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import (
        _kernel_name, paged_prefill_attention_pallas,
    )

    assert _kernel_name(None) == "paged_attention"
    assert _kernel_name(512) == "paged_attention_window"
    pool = jnp.zeros((1, 9, 16, 8, 128))
    q = jnp.zeros((1, 1, 8, 128))
    args = (q, pool, pool, jnp.zeros((1, 4), jnp.int32),
            jnp.zeros((1, 1), jnp.int32))
    for window, name in ((None, "paged_attention"),
                         (32, "paged_attention_window")):
        jaxpr = str(jax.make_jaxpr(lambda *a: paged_prefill_attention_pallas(
            *a, layer=0, window=window, interpret=True))(*args))
        assert f"name={name}\n" in jaxpr or f"name={name} " in jaxpr \
            or f"name={name}]" in jaxpr, jaxpr[-400:]


# ------------------------- the serving path == the reference's full forward


def test_cached_steps_match_the_reference_logits(tiny, ref):
    """The family's own step functions on hand-built tables by group: a
    prompt in chunks, then decode, the context three windows long, the
    sliding groups' entries behind the window block 0 — logits against the
    reference's at every step."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.laguna import (
        laguna_decode_step, laguna_init_state, laguna_prefill,
    )

    cfg, params = tiny
    bs, NB, W = 4, 12, cfg.sliding_window
    G = len(cfg.kv_table_groups)
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(7), (40,), 1, cfg.vocab_size))
    want = np.asarray(ref.logits(params, jnp.asarray(tokens[None]), cfg))[0]
    shape = (cfg.n_kv_layer, 1 + G * NB, bs, cfg.n_kv_head, cfg.head_dim)
    k, v = jnp.zeros(shape), jnp.zeros(shape)
    state = laguna_init_state(cfg, 2)
    slots = jnp.ones((1,), jnp.int32)
    blocks = 1 + np.arange(G * NB, dtype=np.int32).reshape(G, 1, NB)

    def tables_at(next_pos):
        t = blocks.copy()
        for g, (window, _) in enumerate(cfg.kv_table_groups):
            if window is not None:
                t[g, 0, : max(0, (next_pos - window + 1) // bs)] = 0
        return jnp.asarray(t)

    done = 0
    with jax.default_matmul_precision("highest"):
        for n in (16, 12):  # two chunks, the second past the window
            chunk = np.zeros((1, 16), np.int32)
            chunk[0, :n] = tokens[done:done + n]
            out, k, v, state = laguna_prefill(
                params, k, v, jnp.asarray(chunk), jnp.asarray([n]),
                tables_at(done), cfg,
                start=None if done == 0 else jnp.asarray([done]),
                state=state, slots=slots)
            done += n
            np.testing.assert_allclose(
                np.asarray(out)[0], want[done - 1], atol=1e-4)
        for pos in range(done, 40):
            out, k, v, state = laguna_decode_step(
                params, k, v, jnp.asarray(tokens[pos:pos + 1]),
                jnp.asarray([pos]), tables_at(pos), cfg, state=state,
                slots=slots)
            np.testing.assert_allclose(
                np.asarray(out)[0], want[pos], atol=1e-4)
    assert (np.asarray(tables_at(39))[1:] == 0).sum() >= 2 * (40 - W) // bs - 2


def test_engine_serves_through_the_grouped_cache(tiny, ref):
    """``EngineConfig(model="laguna")`` through the normal path: prompts
    shorter and longer than a chunk and the window, greedy tokens equal to
    the reference's argmax at every position, blocks given back behind the
    window, nothing held at the end."""
    import jax.numpy as jnp

    cfg, params = tiny
    engine = _engine(cfg, params)
    prompts = _prompts([5, 23, 40, 61], seed=3)
    streams = [engine.submit(p, max_new_tokens=20, temperature=0.0)
               for p in prompts]
    _drive(engine, streams)
    for p, s in zip(prompts, streams):
        out = list(s)
        assert len(out) == 20
        logits = np.asarray(ref.logits(params, jnp.asarray([p + out]), cfg))[0]
        rows = logits[len(p) - 1: len(p) + 19]
        deficit = rows.max(-1) - rows[np.arange(20), out]
        assert float(deficit.max()) < 1e-3, deficit
    st = engine.stats()
    assert "prefill_chunk" in {sig[0] for sig in engine.fns.signatures}
    groups = st["kv_groups"]
    assert [g["window"] for g in groups] == [None, 8, 8]
    assert [g["layers"] for g in groups] == [[0, 4], [1, 3], [2]]
    assert all(g["blocks"] == 0 for g in groups)
    # the full group held a whole request, a sliding one a chunk + window
    assert groups[0]["high_water_blocks"] > groups[1]["high_water_blocks"]
    assert 0 < st["kv_window_blocks_freed"] < st["kv_window_blocks_taken"]
    assert st["kv_used_blocks"] == 0 and st["prefix_reuse"] is False
    assert engine.cache.reserved_blocks == engine._kv_room == 2 * 4 * 4
    described = st["executor"]
    assert described["kv_layers"] == 2
    assert described["kv_groups"] == [
        {"kind": "full", "window": None, "layers": [0, 4]},
        {"kind": "sliding", "window": 8, "layers": [1, 3]},
        {"kind": "sliding", "window": 8, "layers": [2]}]
    engine.shutdown()


def test_counters_count_routed_and_held_pairs(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompts = _prompts([9, 30], seed=4)
    streams = [engine.submit(p, max_new_tokens=6, temperature=0.0)
               for p in prompts]
    _drive(engine, streams)
    st = engine.stats()
    layers, k = cfg.n_moe_layer, cfg.top_k
    assert st["moe_pairs_prefill"] == (9 + 30) * layers * k
    # the first new token comes out of prefill; each later one of a step
    assert st["moe_pairs_decode"] == 2 * 5 * layers * k
    assert 0 < st["moe_pairs_held_prefill"] < st["moe_pairs_prefill"]
    assert 0 < st["moe_pairs_held_decode"] < st["moe_pairs_decode"]
    assert len(st["moe_pairs_by_expert"]) == 4  # the held experts
    assert sum(st["moe_pairs_by_expert"]) == \
        st["moe_pairs_held_prefill"] + st["moe_pairs_held_decode"]
    assert 0 < st["moe_expert_reads_decode"] <= 5 * layers * 4
    engine.shutdown()


def test_dispatch_spans_carry_the_window_tokens(tiny, monkeypatch):
    from ray_tpu.serve.llm import obs

    cfg, params = tiny
    engine = _engine(cfg, params)
    seen = []
    real = obs.phase

    def spy(table, name, **attrs):
        if name == "executor.dispatch" and attrs.get("kind") == "decode":
            seen.append(attrs)
        return real(table, name, **attrs)

    monkeypatch.setattr(obs, "phase", spy)
    streams = [engine.submit(p, max_new_tokens=4, temperature=0.0)
               for p in _prompts([3, 13], seed=5)]
    _drive(engine, streams)
    assert seen and all("kv_tokens_window" in a for a in seen)
    first = seen[0]
    # contexts 4 and 14 at the first step: whole blocks of 4; the window 8
    assert first["kv_tokens"] == 4 + 16
    assert first["kv_tokens_window"] == 4 + 8
    engine.shutdown()


@pytest.mark.parametrize("option,match", [
    ({"speculative_k": 2}, "speculative_k"),
    ({"host_cache_bytes": 1 << 20}, "host_cache_bytes"),
    ({"preemption": {}}, "preemption"),
    ({"quantization": "int8"}, "quantization"),
    ({"tp": 2}, "tp/fsdp/mesh"),
])
def test_what_the_grouped_cache_cannot_carry_is_refused(tiny, option, match):
    cfg, params = tiny
    with pytest.raises(ValueError, match=match):
        _engine(cfg, params, **option)


def test_grouped_tables_are_refused_by_their_own_reason(jax_cpu):
    """The reasons of a family that is grouped but keeps no state."""
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    cfg = EngineConfig(model="laguna", speculative_k=2)
    with pytest.raises(ValueError, match="gave back"):
        LLMEngine._refuse_for_state(cfg, None, False, True)
    with pytest.raises(ValueError, match="rolled back"):
        LLMEngine._refuse_for_state(cfg, None, True, True)
    LLMEngine._refuse_for_state(cfg, None, False, False)  # nothing to refuse
    LLMEngine._refuse_for_state(EngineConfig(model="laguna"), None, True,
                                True)


def test_handoff_is_refused_and_a_small_pool_says_why(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompt = _prompts([20], seed=6)[0]
    with pytest.raises(ValueError, match="handoff"):
        engine.export_prefix(prompt)
    with pytest.raises(ValueError, match="handoff"):
        engine.adopt_prefix(prompt, [])
    engine.shutdown()
    # no chunking: the room is a whole prompt a sliding group a row
    with pytest.raises(ValueError, match="prefill_chunk_tokens"):
        _engine(cfg, params, prefill_chunk_tokens=None, num_blocks=65)


def test_engine_on_the_kernel_path_matches_the_reference(jax_cpu, ref):
    """The Pallas backend (interpret mode) at pages of whole (8, 128)
    tiles: the whole pool handed to the compute-block kernel at a layer
    index, windowed and not, GQA groups of 2 and 3 in one stack, through
    the engine's tables by group; chunked prefill, then decode."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.laguna import LagunaConfig, laguna_init

    cfg = dataclasses.replace(
        LagunaConfig.tiny(), dtype=jnp.float32, n_kv_head=8, head_dim=128,
        n_head_full=16, n_head_sliding=24, experts_held=(0, 4),
        layer_types=("full_attention", "sliding_attention",
                     "sliding_attention", "full_attention"))
    params = laguna_init(jax.random.PRNGKey(2), cfg)
    engine = _engine(cfg, params, attention_backend="pallas", block_size=16,
                     num_blocks=65, prefill_chunk_tokens=32,
                     length_buckets=(32, 64, 128))
    assert engine.cache.k.shape == (2, 65, 16, 1024)  # a token's heads a row
    prompts = _prompts([7, 45], seed=8)
    streams = [engine.submit(p, max_new_tokens=12, temperature=0.0)
               for p in prompts]
    _drive(engine, streams)
    for p, s in zip(prompts, streams):
        out = list(s)
        logits = np.asarray(ref.logits(params, jnp.asarray([p + out]), cfg))[0]
        rows = logits[len(p) - 1: len(p) + 11]
        deficit = rows.max(-1) - rows[np.arange(12), out]
        assert float(deficit.max()) < 1e-3, deficit
    assert engine.stats()["kv_window_blocks_freed"] > 0
    engine.shutdown()


def test_widened_pipeline_matches_solo_runs(tiny):
    """ISSUE 33's schedule (conftest ``run_widened_schedule``): blocks go
    back behind the window while the chunk that passed them, or a decode
    step, is still in flight (``free_behind`` rests on the device's
    order), and the streams are the bytes of solo runs."""
    from conftest import run_widened_schedule

    cfg, params = tiny
    st = run_widened_schedule(lambda **kw: _engine(cfg, params, **kw),
                              cfg.vocab_size)
    assert st["kv_window_blocks_freed"] > 0
