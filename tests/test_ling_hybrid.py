"""The ling_hybrid family on the CPU at the tiny preset (widths 64, four
layers kda / kda / latent / kda, 16 experts in 4 groups of which 2 stay, 2
a token), seeded weights: the program against the plain reference
(benchmark/reference/ling_hybrid.py), the serving path (the latent pool in
planes, the KDA matrices and convolution rows in a slot) against the full
forward, whole and chunked; the eight shares against the uncut layer; the
slots' lifetime; what the engine refuses for the first family that has
BOTH planes and state rows; its counters and its spans.

Tolerances, with their reason. Program and reference in float32 compute
the same mathematics and differ in the order of sums (the program carries
the KDA state chunk by chunk through a triangular solve where the
reference walks token by token; the program attends through the paged
planes, absorbed in decode, where the reference expands every key): 3e-4
on logits of size ~3 (seen 4e-5), PROVIDED both chose the same experts,
which in float32 they do unless the 2nd and 3rd biased scores of a token
tie to 1e-7 (none at these seeds). A KDA state kept in bfloat16 moves the
logits by 1e-2 and more (``test_a_state_in_bfloat16_is_noticed``), thirty
times the limit.
"""
from __future__ import annotations

import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

BS = 8


@pytest.fixture(scope="module")
def ref():
    from benchmark import common

    return common.load_named("reference", "ling_hybrid")


@pytest.fixture(scope="module")
def tiny(jax_cpu):
    """(float32 config, its seeded params)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.ling_hybrid import LingHybridConfig, ling_hybrid_init

    cfg = dataclasses.replace(LingHybridConfig.tiny(), dtype=jnp.float32)
    return cfg, ling_hybrid_init(jax.random.PRNGKey(1), cfg)


def _engine(cfg, params, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    settings = dict(model="ling_hybrid", model_config=cfg, num_blocks=129,
                    block_size=BS, max_batch_size=4)
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


def _pools(cfg, blocks):
    import jax.numpy as jnp

    # ONE plane (a row's parts side by side), no second pool
    return jnp.zeros((cfg.n_kv_layer, blocks, BS, sum(
        stored for _, _, stored in cfg.kv_planes)), cfg.dtype), None


def _serve_logits(cfg, params, prompt, new, chunk=None, slot=1, state=None,
                  nb=20, backend="xla", round_state=None):
    """Prefill (whole, or by chunks of ``chunk``) then ``new`` greedy decode
    steps through the planes and the state slot, on logits
    (``sample=None``): the logits that chose each generated token [new,
    V], the sequence, the last state."""
    import jax.numpy as jnp

    from ray_tpu.models.ling_hybrid import (
        ling_hybrid_decode_step, ling_hybrid_init_state, ling_hybrid_prefill,
    )

    cfg = dataclasses.replace(cfg, attention_backend=backend)
    blocks = 1 + 2 * nb
    k, v = _pools(cfg, blocks)
    if state is None:
        state = ling_hybrid_init_state(cfg, 3)
    first = 1 + nb * (slot - 1)
    table = jnp.asarray([list(range(first, first + nb))], jnp.int32)
    slots = jnp.asarray([slot], jnp.int32)
    n = len(prompt)

    def rounded(state):
        if round_state is None:
            return state
        return {**state, "kda": state["kda"].astype(round_state).astype(
            jnp.float32)}

    if chunk is None:
        logits, k, v, state = ling_hybrid_prefill(
            params, k, v, jnp.asarray([prompt], jnp.int32),
            jnp.asarray([n], jnp.int32), table, cfg, state=state,
            slots=slots)
    else:
        for s in range(0, n, chunk):
            part = prompt[s:s + chunk]
            toks = np.zeros((1, chunk), np.int32)
            toks[0, :len(part)] = part
            logits, k, v, state = ling_hybrid_prefill(
                params, k, v, jnp.asarray(toks),
                jnp.asarray([len(part)], jnp.int32), table, cfg,
                start=jnp.asarray([s], jnp.int32), state=rounded(state),
                slots=slots)
    seq, out = list(prompt), []
    for _ in range(new):
        out.append(np.asarray(logits[0]))
        seq.append(int(np.argmax(out[-1])))
        logits, k, v, state = ling_hybrid_decode_step(
            params, k, v, jnp.asarray([seq[-1]], jnp.int32),
            jnp.asarray([len(seq) - 1], jnp.int32), table, cfg,
            state=rounded(state), slots=slots)
    return np.stack(out), seq, state


# ------------------------------------------------- program == reference


def test_config_is_hashable_and_counts_its_layers(jax_cpu):
    from ray_tpu.models.ling_hybrid import LingHybridConfig, slot_state_bytes

    cfg = LingHybridConfig(
        vocab_size=19648, num_dense_layers=1, experts_held=[0, 64],
        layer_types=["kda"] * 4 + ["latent", "kda", "kda"])
    hash(cfg)
    assert (cfg.n_layer, cfg.n_kv_layer, cfg.n_kda_layer) == (7, 1, 6)
    assert (cfg.n_held, cfg.groups_held, cfg.conv_width) == (64, (0,), 12288)
    assert [p[1:] for p in cfg.kv_planes] == [(512, 512), (64, 128)]
    # 6 x (32 x 128 x 128 float32 + 3 x 12,288 bfloat16) = 13.03 MB a slot
    assert slot_state_bytes(cfg) == 6 * (2097152 + 73728) == 13025280
    assert dataclasses.replace(
        cfg, experts_held=(96, 64)).groups_held == (1, 2)
    with pytest.raises(ValueError, match="layer_types"):
        LingHybridConfig(layer_types=("conv",))
    with pytest.raises(ValueError, match="n_group"):
        LingHybridConfig(n_group=7)
    with pytest.raises(ValueError, match="top_k"):
        LingHybridConfig(num_experts=16, n_group=8, topk_group=1, top_k=8)


def test_a_nonzero_swiglu_limit_raises(jax_cpu):
    """The published lists hold 4, 5 and 7 from layer 34 on: a layer with a
    clamp is refused, not served with a guessed form."""
    from ray_tpu.models.ling_hybrid import LingHybridConfig

    types = ("kda", "latent")
    LingHybridConfig(layer_types=types, num_dense_layers=1,
                     swiglu_limits=[[0, 0], [0, 0]])
    with pytest.raises(ValueError, match="swiglu_limits.*clamp"):
        LingHybridConfig(layer_types=types, num_dense_layers=1,
                         swiglu_limits=[[0, 0], [4, 0]])
    with pytest.raises(ValueError, match="a pair a layer"):
        LingHybridConfig(layer_types=types, num_dense_layers=1,
                         swiglu_limits=[[0, 0]])


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("n,chunk", [
    (21, None),      # fresh, whole
    (100, None),     # fresh, whole: two KDA chunks of 64
    (100, 40),       # 3 chunks (40 + 40 + 20): state, convolution rows and
                     # the latent context carried over two seams
    (61, 24),        # 3 chunks ending mid-piece (24 = 16 + 8; 13 left)
    (5, 8),          # shorter than the convolution's history at its start
])
def test_prefill_then_decode_matches_reference_on_logits(
        tiny, ref, n, chunk, backend):
    import jax.numpy as jnp

    cfg, params = tiny
    prompt = _prompts([n], seed=5 + n)[0]
    got, seq, _ = _serve_logits(cfg, params, prompt, 6, chunk=chunk,
                                backend=backend)
    want = np.asarray(ref.logits(params, jnp.asarray([seq[:-1]]), cfg))[0]
    np.testing.assert_allclose(got, want[n - 1:], atol=3e-4)


def test_a_state_in_bfloat16_is_noticed(tiny, ref):
    """The limit 3e-4 is tight enough that a KDA state rounded to bfloat16
    between steps fails it."""
    import jax.numpy as jnp

    cfg, params = tiny
    prompt = _prompts([100], seed=105)[0]
    got, seq, _ = _serve_logits(cfg, params, prompt, 6, chunk=40,
                                round_state=jnp.bfloat16)
    want = np.asarray(ref.logits(params, jnp.asarray([seq[:-1]]), cfg))[0]
    assert np.abs(got - want[99:]).max() > 3e-3


def _softplus_gate(ref, monkeypatch):
    import jax
    import jax.numpy as jnp

    def gate(f, lp, cfg):
        x = (f + ref._f32(lp["kda_dt_bias"])).reshape(
            -1, cfg.kda_n_head, cfg.kda_head_dim)
        return -jnp.exp(ref._f32(lp["kda_a_log"]))[:, None] \
            * jax.nn.softplus(x)

    monkeypatch.setattr(ref, "kda_gate", gate)


def _norm_joined(ref, monkeypatch):
    def joined(o, scale, eps):
        S = o.shape[0]
        flat = o.reshape(S, -1)
        flat = flat * (flat.shape[1] ** 0.5 / (
            (flat ** 2).sum(-1, keepdims=True) + eps * flat.shape[1]) ** 0.5)
        return flat.reshape(o.shape) * ref._f32(scale)

    monkeypatch.setattr(ref, "output_norm", joined)


def _rotary_by_halves(ref, monkeypatch):
    import jax.numpy as jnp

    def by_halves(x, theta):
        S, _, R = x.shape
        inv = 1.0 / theta ** (jnp.arange(0, R, 2, dtype=jnp.float32) / R)
        ang = jnp.outer(jnp.arange(S, dtype=jnp.float32), inv)
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        a, b = x[..., : R // 2], x[..., R // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

    monkeypatch.setattr(ref, "rotate", by_halves)


@pytest.mark.parametrize("wrong", [
    "softplus_gate", "norm_joined", "rotary_by_halves", "no_latent_gate",
    "no_qk_norm", "no_bias", "no_groups", "no_conv"])
def test_the_reference_notices_each_reading(tiny, ref, monkeypatch, wrong):
    """Each assumed reading swapped for its OTHER in the reference moves the
    logits far past the limit the program is held to: the comparison sees
    every one of them."""
    import jax
    import jax.numpy as jnp

    cfg, params = tiny
    if wrong == "softplus_gate":
        _softplus_gate(ref, monkeypatch)
    elif wrong == "norm_joined":
        _norm_joined(ref, monkeypatch)
    elif wrong == "rotary_by_halves":
        _rotary_by_halves(ref, monkeypatch)
    elif wrong == "no_latent_gate":
        monkeypatch.setattr(
            ref, "gate_head_wise",
            lambda u, lp: jnp.ones((u.shape[0], cfg.n_head)))
    elif wrong == "no_qk_norm":
        monkeypatch.setattr(ref, "qk_norm", lambda x, eps: x)
    elif wrong == "no_bias":
        params = jax.tree.map(lambda a: a, params)
        for lp in params["layers"]:
            if "moe_route_bias" in lp:
                lp["moe_route_bias"] = jnp.zeros_like(lp["moe_route_bias"])
    elif wrong == "no_groups":
        cfg = dataclasses.replace(cfg, n_group=1, topk_group=1)
    elif wrong == "no_conv":
        monkeypatch.setattr(ref, "short_conv",
                            lambda x, w: jax.nn.silu(x * ref._f32(w)[-1]))
    good_cfg, good = tiny
    prompt = _prompts([70], seed=9)[0]
    got, seq, _ = _serve_logits(good_cfg, good, prompt, 4)
    want = np.asarray(ref.logits(params, jnp.asarray([seq[:-1]]), cfg))[0]
    assert np.abs(got - want[69:]).max() > 3e-3, wrong


# ----------------------------------------------------- the eight shares


def test_the_shares_add_up_to_the_uncut_layer(tiny, ref):
    """The four ``held`` groups' outputs with the shared expert counted once
    equal the uncut layer (the tiny preset has 4 groups of 4; the cell 8 of
    64): in the program's ``_ffn`` and in the reference's, and the two
    agree share by share."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import ling_hybrid as m

    cfg, params = tiny
    lp = params["layers"][1]
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(2, 9, cfg.d_model)), jnp.float32)
    valid = jnp.ones((2, 9), bool)
    whole, sizes, met = m._ffn(x, lp, cfg, valid)
    assert int(sizes.sum()) == 2 * 9 * cfg.top_k and int(met) == 18
    size = cfg.num_experts // cfg.n_group
    z = m.rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    shared = m.swiglu(z, lp["moe_shared_w_in"], lp["moe_shared_w_out"],
                      cfg.dtype)
    total, pairs, tokens = shared, 0, 0
    for g in range(cfg.n_group):
        held = (g * size, size)
        part_cfg = dataclasses.replace(cfg, experts_held=held)
        part_lp = {**lp,
                   "moe_gmm_w_in": lp["moe_gmm_w_in"][g * size:(g + 1) * size],
                   "moe_gmm_w_out": lp["moe_gmm_w_out"][
                       g * size:(g + 1) * size]}
        out, n, hit = m._ffn(x, part_lp, part_cfg, valid)
        total = total + (out - shared)
        pairs += int(n.sum())
        tokens += int(hit)
        with jax.default_matmul_precision("highest"):
            want = ref.routed_part(z.reshape(18, -1), part_lp, part_cfg)
        np.testing.assert_allclose((out - shared).reshape(18, -1), want,
                                   atol=2e-5)
    np.testing.assert_allclose(total, whole, atol=2e-5)
    # every pair is held by exactly one share; a token's 2 of 4 groups stay
    assert pairs == 2 * 9 * cfg.top_k
    assert tokens == 18 * cfg.topk_group


# -------------------------------------------------------------- engine


# what the engine below decoded over the pool in TWO planes (the tree PR 53
# started from; the interpreter's four are the first of each): one plane
# moves no token
_TWO_PLANES_DECODED = [
    [312, 449, 238, 320, 352, 342, 21, 213], [91, 47, 5, 10],
    [33, 229, 47, 156, 197, 110, 264, 400],
    [204, 112, 494, 343, 35, 473, 111, 344], [130, 111, 160],
    [84, 123, 268, 101, 60, 435],
]


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_engine_streams_match_the_reference_and_solo(tiny, ref, backend):
    """Rows that join and leave under continuous batching (six requests,
    four under the kernels' interpreter, through three slots: a slot is
    reused, and a reused slot starts from zeros in BOTH of its leaves): every stream is the reference's
    greedy continuation, together as alone, whole as in 3 chunks."""
    import jax.numpy as jnp

    cfg, params = tiny
    prompts = _prompts([5, 70, 100, 61, 33, 90], seed=0)
    news = [8, 4, 8, 8, 3, 6]
    if backend == "pallas":  # the interpreter is slow: four of the six
        prompts, news = prompts[:4], [4, 3, 4, 4]
    engine = _engine(cfg, params, attention_backend=backend,
                     max_batch_size=3)
    streams = [engine.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, news)]
    _drive(engine, streams)
    outs = [list(s) for s in streams]
    assert outs == [was[:n] for was, n in zip(_TWO_PLANES_DECODED, news)]
    for p, o in zip(prompts, outs):
        seq = p + o
        logits = np.asarray(ref.logits(params, jnp.asarray([seq[:-1]]),
                                       cfg))[0]
        assert logits[len(p) - 1:].argmax(-1).tolist() == o
    stats = engine.stats()
    assert stats["state_slots_high_water"] == 3
    chunky = _engine(cfg, params, attention_backend=backend,
                     prefill_chunk_tokens=40, max_batch_size=3)
    again = [chunky.submit(p, max_new_tokens=n)
             for p, n in zip(prompts, news)]
    _drive(chunky, again)
    assert [list(s) for s in again] == outs
    assert chunky.generate(prompts[2], max_new_tokens=news[2]) == outs[2]
    engine.shutdown()
    chunky.shutdown()


def test_a_reused_slot_starts_from_zeros(tiny):
    """A slot whose last sequence left a state behind (matrix and
    convolution rows both poisoned) serves the next as a clean one does."""
    import jax.numpy as jnp

    from ray_tpu.models.ling_hybrid import ling_hybrid_init_state

    cfg, params = tiny
    prompt = _prompts([30], seed=2)[0]
    clean, _, _ = _serve_logits(cfg, params, prompt, 4, chunk=16)
    dirty = ling_hybrid_init_state(cfg, 3)
    dirty = {**dirty, "kda": dirty["kda"] + 3.0, "conv": dirty["conv"] - 2.0}
    again, _, state = _serve_logits(cfg, params, prompt, 4, chunk=16,
                                    state=dirty)
    np.testing.assert_allclose(again, clean, atol=1e-6)
    fresh, _, _ = _serve_logits(cfg, params, prompt, 4, state=dirty)
    np.testing.assert_allclose(fresh, clean, atol=3e-4)
    # the other slot was left as it stood
    assert float(jnp.abs(state["kda"][:, 2] - 3.0).max()) == 0.0
    assert float(jnp.abs(state["conv"][:, 2] + 2.0).max()) == 0.0


def test_counters_stats_and_step_attrs(tiny):
    from ray_tpu.models.ling_hybrid import LingHybridConfig, step_attrs
    from ray_tpu.ops import kda

    cfg, params = tiny
    held = dataclasses.replace(cfg, experts_held=(4, 4))
    part = dict(params)
    part["layers"] = [
        {k: (v[4:8] if k.startswith("moe_gmm") else v) for k, v in lp.items()}
        for lp in params["layers"]]
    engine = _engine(held, part)
    prompts = _prompts([70, 20], seed=3)
    streams = [engine.submit(p, max_new_tokens=5) for p in prompts]
    _drive(engine, streams)
    stats = engine.stats()
    # 3 expert layers: (70 + 20) prompt tokens and 2 x 4 decode steps
    tokens = 3 * (90 + 8)
    assert stats["moe_tokens_routed"] == tokens
    assert stats["moe_pairs_prefill"] + stats["moe_pairs_decode"] \
        == tokens * cfg.top_k
    assert 0 < stats["moe_groups_held"] < tokens
    held_pairs = stats["moe_pairs_held_prefill"] + stats["moe_pairs_held_decode"]
    assert 0 < held_pairs < tokens * cfg.top_k
    assert sum(stats["moe_pairs_by_expert"]) == held_pairs
    # a token whose kept groups leave this device's out sends it nothing
    assert held_pairs <= stats["moe_groups_held"] * cfg.top_k
    desc = engine.executor.describe()
    assert desc["state"]["arrays"]["kda"] == [3, 5, 4, 16, 16]
    assert desc["state"]["arrays"]["conv"] == [3, 5, 3, 192]
    assert desc["kv_pool"]["kind"] == "latent" and desc["kv_layers"] == 1
    (plane,) = desc["kv_pool"]["planes"]
    assert [p["name"] for p in plane["parts"]] == ["latent", "rope"]
    assert plane["name"] == "latent+rope"
    assert desc["kv_pool"]["page_copies"] == 1
    assert stats["state_bytes"] == desc["state"]["bytes"] > 3 * 5 * 4 * 256 * 4
    assert stats["kv_pool"]["kind"] == "latent"
    assert set(stats["moe_gmm_form"].values()) == {"ragged"}
    decodes = [r for r in engine._flight.snapshot()
               if r["kind"] == "decode" and r["batch"]]
    assert sum(r["rows"] for r in decodes) == 8
    assert all(r["state_mb"] == round(
        r["rows"] * 3 * 4 * 256 * 4 * 2 / 1e6, 3) for r in decodes)
    engine.shutdown()
    big = LingHybridConfig(layer_types=["kda"] * 4 + ["latent", "kda", "kda"],
                           num_dense_layers=1)
    assert step_attrs(big, "decode", [(63, 1), (9000, 1)]) == {
        "rows": 2, "expanded_pairs": 0,
        "state_mb": round(2 * 6 * 2097152 * 2 / 1e6, 3)}
    assert step_attrs(big, "prefill", [(2048, 2048), (0, 20)]) == {
        "tokens": 2068, "kda_pieces": 2048 // kda.PIECE + 2,
        "expanded_pairs": 2048 * 2048 + 2048 * 2049 // 2 + 20 * 21 // 2,
        "prefix_blocks": 1}


# ----------------------------------------------------------- refusals


def test_a_sequence_is_not_split_over_rows(tiny):
    """Planes alone would be pages under one table (cells 8 and 10 pack
    pieces of prompts); state rows keep a row a request."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    why = engine.cache.cfg.why_not_split
    assert why is not None and "state rows" in why
    assert not engine.cache.cfg.one_table
    assert engine.cache.cfg.planes and engine.cache.cfg.state_slots == 5
    engine.shutdown()


@pytest.mark.parametrize("option,match", [
    ({"speculative_k": 2}, "per-sequence state.*speculative_k.*rolled back"),
    ({"host_cache_bytes": 1 << 20},
     "per-sequence state.*host_cache_bytes.*state at its"),
    ({"preemption": "swap"}, "per-sequence state.*preemption.*state slot"),
    ({"quantization": "int8"},
     "per-sequence state.*quantization.*quantized path"),
    ({"tp": 2}, "per-sequence state.*tp/fsdp/mesh.*state arrays"),
])
def test_unsupported_options_are_refused_by_name(tiny, option, match):
    """Both lists apply (state rows AND planes); the state's comes first
    and refuses every option the planes' does, and preemption besides."""
    cfg, params = tiny
    with pytest.raises(ValueError, match=match):
        _engine(cfg, params, **option)


def test_the_planes_list_refuses_the_same_options():
    """... and a family with planes alone would be refused the same four by
    the planes' list (no new refusal was needed for the union)."""
    from ray_tpu.serve.llm import EngineConfig
    from ray_tpu.serve.llm.engine import LLMEngine

    for option, match in [
            ({"speculative_k": 2}, "latent row.*speculative_k"),
            ({"host_cache_bytes": 1 << 20}, "latent row.*host_cache_bytes"),
            ({"tp": 2}, "latent row.*tp/fsdp/mesh")]:
        cfg = EngineConfig(model="ling_hybrid", **option)
        with pytest.raises(ValueError, match=match):
            LLMEngine._refuse_for_state(cfg, None, False, False, False, True)
    with pytest.raises(ValueError, match="latent row.*quantization"):
        LLMEngine._refuse_for_state(
            EngineConfig(model="ling_hybrid"), "int8", False, False, False,
            True)


def test_prefix_reuse_is_off_and_handoff_refused(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompt = _prompts([40], seed=4)[0]
    a = engine.generate(prompt, max_new_tokens=3)
    b = engine.generate(prompt, max_new_tokens=3)
    assert a == b
    assert engine.cache.peek_prefix(prompt) == 0
    st = engine.stats()
    assert st["prefix_reuse"] is False and st["prefix_hit_tokens"] == 0
    assert "per-sequence state" in st["prefix_reuse_why_not"]
    # the handoff's refusal comes from the PLANES (its record cannot say
    # them); the state rows would refuse it as well
    with pytest.raises(ValueError, match="planes.*KVLayout"):
        engine.export_prefix(prompt)
    with pytest.raises(ValueError, match="planes"):
        engine.adopt_prefix(prompt, [])
    engine.shutdown()


def test_scopes_name_the_kda_parts(tiny):
    """The five ``kda_*`` names are in the vocabulary and reach the
    programs: a decode program names conv, gate, step and out; a prefill
    program the chunked form."""
    from ray_tpu.serve.llm import obs

    for name in ("kda_conv", "kda_gate", "kda_step", "kda_chunk", "kda_out"):
        assert name in obs.SCOPES
    cfg, params = tiny
    engine = _engine(cfg, params)
    engine.generate(_prompts([20], seed=1)[0], max_new_tokens=3)
    scopes = engine.program_scopes()
    engine.shutdown()
    seen = {kind: set() for kind in ("prefill", "decode")}
    for label, program in scopes.items():
        kind = "decode" if "decode" in program["name"] else "prefill"
        seen[kind] |= {entry[0] for entry in program["scopes"].values()}
    assert {"kda_conv", "kda_gate", "kda_step", "kda_out", "moe_route",
            "attn_kernel"} <= seen["decode"]
    assert {"kda_conv", "kda_gate", "kda_chunk", "kda_out"} <= seen["prefill"]
