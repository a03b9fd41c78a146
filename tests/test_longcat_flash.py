"""The LongCat-Flash family on the CPU at the tiny preset (hidden 64, 4
heads, ``q_lora_rank`` 24, ``kv_lora_rank`` 16, nope 8 + rope 4, v 8, 2
DOUBLE layers = 4 latent sub-layers, 8 real + 4 zero-compute experts, 3 a
token, vocab 512 of which 64 held), seeded weights, float32: the program
against the plain reference (benchmark/reference/longcat_flash.py: the
EXPANDED form, so every comparison is also absorbed against expanded), the
serving path (prefill in chunks, then decode through a pool of 2 x layers
sub-layers) on both backends, the latent kernel at an ODD head count in the
Pallas interpreter against the XLA path with every other page poisoned, the
shortcut, what the engine refuses and what it counts. In the same file, as
the other expert families' tests are: ``moe_route(score="softmax")`` with
and without a bias against a hand-written route, ``moe_dropless(zero_from=)``
against a dense loop, and THE SHARE TEST: the parts that all holders give,
the zero-compute experts' part counted ONCE, are the uncut layer.

Program and reference in float32 compute the same mathematics and differ in
the order of sums (and in WHERE the up-projections enter): 1e-4 on logits
of size ~3.
"""
from __future__ import annotations

import dataclasses
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

VOCAB_HELD = 64  # of 512


@pytest.fixture(scope="module")
def ref():
    from benchmark import common

    return common.load_named("reference", "longcat_flash")


@pytest.fixture(scope="module")
def tiny(jax_cpu):
    """(float32 config that holds real experts 2-3 of 8 and 64 rows of the
    vocabulary's 512, its seeded params)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.longcat_flash import (
        LongCatFlashConfig, longcat_flash_init,
    )

    cfg = dataclasses.replace(
        LongCatFlashConfig.tiny(VOCAB_HELD), dtype=jnp.float32,
        experts_held=(2, 2))
    return cfg, longcat_flash_init(jax.random.PRNGKey(1), cfg)


def _engine(cfg, params, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    settings = dict(model="longcat_flash", model_config=cfg, block_size=4,
                    num_blocks=129, max_batch_size=4, prefill_chunk_tokens=16,
                    length_buckets=(16, 32, 64, 128))
    settings.update(kw)
    return LLMEngine(EngineConfig(**settings), params=params,
                     auto_step=False)


def _prompts(lens, seed=0, vocab=VOCAB_HELD):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=n).tolist() for n in lens]


def _drive(engine, streams, limit=4000):
    for _ in range(limit):
        if all(s.done for s in streams):
            break
        if not engine.step():
            time.sleep(0.01)
    while engine.step():
        pass
    assert all(s.done for s in streams)


# ------------------------------------------------- the model and its config


def test_tiny_preset_and_published_widths(jax_cpu):
    """The published configuration: 28 double layers = 56 cache layers, a
    row of 512 + 64 numbers (two parts of one plane), 768 router outputs of which 256
    compute nothing, the two rescalings 2 and 3.46."""
    from ray_tpu.models.longcat_flash import LongCatFlashConfig

    t = LongCatFlashConfig.tiny()
    assert (t.d_model, t.n_head, t.q_lora_rank, t.kv_lora_rank,
            t.qk_nope_head_dim, t.qk_rope_head_dim, t.v_head_dim) == (
                64, 4, 24, 16, 8, 4, 8)
    assert (t.n_layer, t.n_kv_layer, t.num_experts, t.num_zero_experts,
            t.top_k) == (2, 4, 8, 4, 3)
    pub = LongCatFlashConfig()
    assert (pub.n_layer, pub.n_kv_layer, pub.n_head) == (28, 56, 64)
    assert pub.kv_planes == (("latent", 512, 512), ("rope", 64, 128))
    assert abs(pub.softmax_scale - 192 ** -0.5) < 1e-12
    assert abs(pub.q_scale - 2.0) < 1e-12
    assert abs(pub.c_scale - 12 ** 0.5) < 1e-12
    assert (pub.num_experts, pub.num_zero_experts, pub.top_k) == (
        512, 256, 12)
    assert pub.norm_topk_prob is False and pub.routed_scaling_factor == 6.0
    assert pub.n_held == 512 and dataclasses.replace(
        pub, experts_held=(0, 16)).n_held == 16
    with pytest.raises(ValueError, match="experts_held"):
        dataclasses.replace(pub, experts_held=(500, 16))  # into the zeros
    off = dataclasses.replace(pub, mla_scale_q_lora=False,
                              mla_scale_kv_lora=False)
    assert off.q_scale is None and off.c_scale is None


def test_a_blocks_bytes_over_eight_sublayers(jax_cpu):
    """Reckoned, not allocated: the cell's 4 layers are 8 latent
    sub-layers, so a token is 8 x 1,152 B by the widths and 10,240 B as
    stored; 16,385 block ids of 16 tokens are 2.68 GB as stored."""
    import jax.numpy as jnp

    from ray_tpu.models.longcat_flash import LongCatFlashConfig
    from ray_tpu.serve.llm.kv_cache import KVCacheConfig

    cfg = dataclasses.replace(LongCatFlashConfig(), n_layer=4)
    kv = KVCacheConfig(n_layer=cfg.n_kv_layer, n_kv_head=1, head_dim=576,
                       num_blocks=16385, block_size=16, dtype=jnp.bfloat16,
                       planes=cfg.kv_planes)
    assert kv.n_layer == 8
    assert kv.block_bytes == 16 * 10240
    assert abs(kv.block_bytes * 16385 - 2.684e9) < 0.001e9


def test_full_forward_matches_the_reference(tiny, ref):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.longcat_flash import longcat_flash_forward

    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 37), 1,
                                cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got = longcat_flash_forward(params, tokens, cfg)
    want = ref.logits(params, tokens, cfg)
    assert got.shape == (2, 37, VOCAB_HELD)
    assert float(jnp.abs(want).max()) > 1.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_absorbed_attention_is_the_expanded_one_sublayer(tiny, ref):
    """One sub-layer's attention WITH the two rescalings: the absorbed form
    over the cached row (``c`` already rescaled) is the expanded form, in
    the program and against the reference's; and the rescalings are there
    (without them the result differs)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import longcat_flash as m
    from ray_tpu.models.parts import absorb, unabsorb

    cfg, params = tiny
    sp = params["layers"][1]["sub"][1]
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 29, cfg.d_model))
    pos = np.broadcast_to(np.arange(29, dtype=np.int32), (2, 29))

    def absorbed_attention(q_nope, q_rope, c, k_r):
        s = (jnp.einsum("bshc,btc->bhst", absorb(q_nope, sp, cfg), c)
             + jnp.einsum("bshr,btr->bhst", q_rope, k_r)) * cfg.softmax_scale
        t = jnp.arange(c.shape[1])
        p = jax.nn.softmax(
            jnp.where(t[None, :] <= t[:, None], s, -1e30), axis=-1)
        return unabsorb(jnp.einsum("bhst,btc->bshc", p, c), sp, cfg)

    with jax.default_matmul_precision("highest"):
        rot = m.rotary_at(pos, cfg)
        parts = m._rows(u, sp, *rot, cfg)
        plain = m.queries_and_row(u, sp, *rot, cfg)
        absorbed = absorbed_attention(*parts)
        expanded = m.expanded_attention(*parts, sp, cfg)
        unscaled = m.expanded_attention(*plain, sp, cfg)
        want = np.stack([np.asarray(ref.attention(u[b], sp, cfg))
                         for b in range(2)])
        through_o = np.asarray(absorbed @ sp["mla_w_o"])
    # the row as cached: c rescaled by (D / C) ** 0.5, k_r not; q by (D / Q)
    np.testing.assert_allclose(np.asarray(parts[2]),
                               np.asarray(plain[2]) * 2.0, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(parts[3]), np.asarray(plain[3]))
    np.testing.assert_allclose(np.asarray(parts[1]), np.asarray(plain[1])
                               * (64 / 24) ** 0.5, rtol=1e-4, atol=1e-6)
    assert float(np.abs(np.asarray(expanded)).max()) > 0.1
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=1e-5)
    np.testing.assert_allclose(through_o, want, atol=1e-5)
    assert float(jnp.abs(unscaled - expanded).max()) > 1e-2


def test_the_init_keeps_scores_neither_flat_nor_one_hot(jax_cpu):
    """Behind the two rescalings the scores ``s q . k`` of a seeded model
    have the std that models/pangu_ultra_moe.py's have (1.55 ** 2 = 2.4):
    the leaves a rescaled value feeds are drawn that much smaller."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import longcat_flash as m

    cfg = dataclasses.replace(
        m.LongCatFlashConfig.tiny(), dtype=jnp.float32, d_model=256,
        q_lora_rank=64, kv_lora_rank=32, qk_nope_head_dim=32,
        qk_rope_head_dim=16, n_layer=1)
    sp = m.longcat_flash_init(jax.random.PRNGKey(5), cfg)["layers"][0]["sub"][0]
    u = jax.random.normal(jax.random.PRNGKey(6), (1, 64, cfg.d_model))
    pos = jnp.arange(64, dtype=jnp.int32)[None]
    q_nope, q_rope, c, k_r = m._rows(u, sp, *m.rotary_at(pos, cfg), cfg)
    k_nope = (c @ sp["mla_w_uk"]).reshape(1, 64, cfg.n_head, -1)
    s = (jnp.einsum("bshn,bthn->bhst", q_nope, k_nope)
         + jnp.einsum("bshr,btr->bhst", q_rope, k_r)) * cfg.softmax_scale
    assert 1.8 < float(jnp.std(s)) < 3.0, float(jnp.std(s))
    v = c @ sp["mla_w_uv"]
    assert 0.7 < float(jnp.std(v)) < 1.3, float(jnp.std(v))


def test_rotary_pairs_are_by_halves(jax_cpu, ref):
    """The ONE rotary function (models/parts.py's, which pangu's file
    names too) and the reference's turn the same pairs (i, i + R / 2)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import longcat_flash as m
    from ray_tpu.models import pangu_ultra_moe as pangu
    from ray_tpu.models import parts

    for shared in ("cached_heads", "queries_and_row", "rotary_at",
                   "expanded_attention", "swiglu"):
        assert getattr(m, shared) is getattr(pangu, shared) \
            is getattr(parts, shared), shared
    cfg = m.LongCatFlashConfig.tiny()
    x = jax.random.normal(jax.random.PRNGKey(4), (9, 3, 4))
    pos = jnp.arange(9, dtype=jnp.int32)[None]
    got = parts.rotate(x[None], *m.rotary_at(pos, cfg))[0]
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref._rotate(x, cfg.rope_theta)),
                               atol=1e-6)


def test_the_shortcut_lands_behind_the_second_half(tiny, ref, monkeypatch):
    """Moving the routed branch's result behind the FIRST half (a plain
    layer, no shortcut) changes the logits far past the tolerance: the
    program agrees with the reference as described (1e-4) and stands as
    far from the reference without the shortcut as that one does."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.longcat_flash import longcat_flash_forward

    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(11), (1, 24), 1,
                                cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got = longcat_flash_forward(params, tokens, cfg)
    right = ref.logits(params, tokens, cfg)
    monkeypatch.setattr(ref, "SHORTCUT_BEHIND_FIRST_HALF", True)
    wrong = ref.logits(params, tokens, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(right), atol=1e-4)
    assert float(jnp.abs(right - wrong).max()) > 1e-2
    assert float(jnp.abs(got - wrong).max()) > 1e-2


@pytest.mark.parametrize("change", [
    "shortcut", "values", "zeros", "held", "bias", "q_scale", "route_scale"])
def test_the_reference_notices_each_mechanism(tiny, ref, change,
                                              monkeypatch):
    import jax
    import jax.numpy as jnp

    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, 24), 1,
                                cfg.vocab_size)
    want = ref.logits(params, tokens, cfg)
    other_cfg, other = cfg, params
    if change == "shortcut":
        monkeypatch.setattr(ref, "SHORTCUT_BEHIND_FIRST_HALF", True)
    elif change == "values":
        monkeypatch.setattr(ref, "VALUES_NOT_RESCALED", True)
    elif change == "zeros":
        monkeypatch.setattr(ref, "ZERO_EXPERTS_DROPPED", True)
    elif change == "held":
        other_cfg = dataclasses.replace(cfg, experts_held=(4, 2))
    elif change == "q_scale":
        other_cfg = dataclasses.replace(cfg, mla_scale_q_lora=False)
    elif change == "route_scale":
        other_cfg = dataclasses.replace(cfg, routed_scaling_factor=1.0)
    else:  # a learned selection bias changes WHICH experts a token meets
        bias = jnp.where(jnp.arange(12) >= 8, 1.0, 0.0)  # every zero first
        other = dict(params, layers=[
            dict(lp, moe_route_bias=bias) for lp in params["layers"]])
    got = ref.logits(other, tokens, other_cfg)
    assert float(jnp.abs(got - want).max()) > 1e-2, change


# ------------------------------------------- the expert functions (ops/moe.py)


def _hand_route(x, router, bias, k, scale):
    """softmax over ALL outputs, the k largest of p + bias, weights
    scale * p at the chosen: written out with numpy."""
    logits = np.asarray(x, np.float64) @ np.asarray(router, np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    by = p if bias is None else p + np.asarray(bias, np.float64)
    experts = np.argsort(-by, axis=-1, kind="stable")[:, :k]
    return scale * np.take_along_axis(p, experts, -1), experts


@pytest.mark.parametrize("with_bias", [False, True])
def test_softmax_route_against_a_hand_written_one(jax_cpu, with_bias):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.moe import moe_route

    x = jax.random.normal(jax.random.PRNGKey(20), (17, 32))
    router = jax.random.normal(jax.random.PRNGKey(21), (32, 12)) * 0.4
    bias = (jax.random.normal(jax.random.PRNGKey(22), (12,)) * 0.2
            if with_bias else None)
    weights, experts = moe_route(x, router, bias, 3, norm_topk=False,
                                 scale=6.0, score="softmax")
    want_w, want_e = _hand_route(x, router, bias, 3, 6.0)
    np.testing.assert_array_equal(np.asarray(experts), want_e)
    np.testing.assert_allclose(np.asarray(weights), want_w, rtol=1e-5)
    assert experts.dtype == jnp.int32 and weights.dtype == jnp.float32
    # not renormalised: the chosen weights do not sum to the scale
    assert float(jnp.abs(weights.sum(-1) - 6.0).min()) > 0.1
    if with_bias:  # the bias chooses, the UNBIASED score weighs
        plain, chosen = moe_route(x, router, None, 3, norm_topk=False,
                                  scale=6.0, score="softmax")
        assert (np.asarray(chosen) != np.asarray(experts)).any()
    normed, _ = moe_route(x, router, bias, 3, norm_topk=True, scale=6.0,
                          score="softmax")
    np.testing.assert_allclose(np.asarray(normed.sum(-1)), 6.0, rtol=1e-4)


def test_route_names_its_three_scores(jax_cpu):
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    assert moe.ROUTE_SCORES == ("sigmoid", "softmax", "softmax_topk")
    with pytest.raises(ValueError) as e:
        moe.moe_route(jnp.ones((2, 4)), jnp.ones((4, 3)), None, 2,
                      score="tanh")
    for name in moe.ROUTE_SCORES:
        assert name in str(e.value) and name in moe.moe_route.__doc__


def _dense_loop(x, weights, experts, w_in, w_out, zero_from, first=0):
    """Every (token, pick) by itself: a held real expert's SwiGLU, a
    zero-compute expert's ``x``, nothing for a real expert not held."""
    x = np.asarray(x, np.float64)
    out = np.zeros_like(x)
    E = w_in.shape[0]
    for t in range(x.shape[0]):
        for w, e in zip(np.asarray(weights)[t], np.asarray(experts)[t]):
            if e >= zero_from:
                out[t] += w * x[t]
            elif first <= e < first + E:
                h = x[t] @ np.asarray(w_in[e - first], np.float64)
                g, up = np.split(h, 2)
                out[t] += w * ((g / (1 + np.exp(-g)) * up)
                               @ np.asarray(w_out[e - first], np.float64))
    return out


@pytest.mark.parametrize("case", ["all", "held", "padded", "zeros_only"])
def test_zero_from_against_a_dense_loop(jax_cpu, case):
    """Ids ``>= zero_from`` add ``w * x`` and cost no product: they are in
    no group (``sizes`` counts the real pairs alone), whatever is held, and
    a padding row's zero picks add nothing."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.moe import moe_dropless

    T, D, F, E, Z, k = 11, 16, 8, 6, 3, 4
    ks = jax.random.split(jax.random.PRNGKey(30), 5)
    x = jax.random.normal(ks[0], (T, D))
    w_in = jax.random.normal(ks[1], (E, D, 2 * F)) * 0.3
    w_out = jax.random.normal(ks[2], (E, F, D)) * 0.3
    weights = jax.random.uniform(ks[3], (T, k))
    experts = jnp.argsort(
        jax.random.uniform(ks[4], (T, E + Z)), axis=-1)[:, :k].astype(
            jnp.int32)
    if case == "zeros_only":
        experts = jnp.maximum(experts, E)
    held, first = (None, 0) if case != "held" else ((2, 3), 2)
    valid = None
    if case == "padded":
        valid = jnp.arange(T) % 3 != 1
    with jax.default_matmul_precision("highest"):
        got, sizes = moe_dropless(
            x, weights, experts,
            w_in[first:first + 3] if held else w_in,
            w_out[first:first + 3] if held else w_out,
            dtype=jnp.float32, valid=valid, held=held, zero_from=E)
    want = _dense_loop(x, weights, experts,
                       w_in[first:first + 3] if held else w_in,
                       w_out[first:first + 3] if held else w_out, E, first)
    real = np.asarray(experts) < E
    if held:
        real &= (np.asarray(experts) >= 2) & (np.asarray(experts) < 5)
    if valid is not None:
        want[~np.asarray(valid)] = 0.0
        real &= np.asarray(valid)[:, None]
    assert int(sizes.sum()) == int(real.sum())
    if case == "zeros_only":
        assert int(sizes.sum()) == 0
    assert float(np.abs(want).max()) > 0.1
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


def test_without_zero_from_nothing_changed(jax_cpu):
    """``zero_from=None`` is the function the other four families call: an
    id past the weights is then a padding pair, computed nowhere."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.moe import moe_dropless

    ks = jax.random.split(jax.random.PRNGKey(31), 4)
    x = jax.random.normal(ks[0], (5, 8))
    w_in = jax.random.normal(ks[1], (3, 8, 8))
    w_out = jax.random.normal(ks[2], (3, 4, 8))
    weights = jnp.ones((5, 2))
    experts = jnp.asarray([[0, 1], [2, 0], [1, 2], [0, 2], [1, 0]], jnp.int32)
    jaxpr = str(jax.make_jaxpr(lambda *a: moe_dropless(
        *a, dtype=jnp.float32))(x, weights, experts, w_in, w_out))
    assert "moe_zero" not in jaxpr
    with_zero = str(jax.make_jaxpr(lambda *a: moe_dropless(
        *a, dtype=jnp.float32, zero_from=3))(x, weights, experts, w_in,
                                             w_out))
    assert len(with_zero) > len(jaxpr)


def test_the_four_holders_parts_add_up_to_the_uncut_layer(tiny, ref):
    """THE SHARE TEST. The routed branch's parts that the 4 holders of 2
    real experts give, with the zero-compute experts' part counted ONCE
    (it is computed where the token is, whatever that device holds), are
    the uncut reference's whole routed branch over all 8 + 4."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.longcat_flash import longcat_flash_init
    from ray_tpu.ops.moe import moe_dropless, moe_route

    cfg, _ = tiny
    whole = dataclasses.replace(cfg, experts_held=None)
    lp = longcat_flash_init(jax.random.PRNGKey(6), whole)["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(7), (12, cfg.d_model))
    ones = jnp.ones((12,), bool)
    with jax.default_matmul_precision("highest"):
        want = ref.routed_part(h, lp, whole)
        weights, experts = moe_route(
            h, lp["moe_route_w"], lp["moe_route_bias"], cfg.top_k,
            norm_topk=False, scale=cfg.routed_scaling_factor,
            score="softmax")
        got, pairs, with_zeros = 0.0, 0, []
        for first in (0, 2, 4, 6):
            mine = (lp["moe_gmm_w_in"][first:first + 2],
                    lp["moe_gmm_w_out"][first:first + 2])
            part, sizes = moe_dropless(
                h, weights, experts, *mine, dtype=jnp.float32, valid=ones,
                held=(first, 2))
            both, _ = moe_dropless(
                h, weights, experts, *mine, dtype=jnp.float32, valid=ones,
                held=(first, 2), zero_from=cfg.num_experts)
            got = got + part
            pairs += int(sizes.sum())
            with_zeros.append(both - part)
        # every holder computes the SAME zero part for a token: once
        for z in with_zeros[1:]:
            np.testing.assert_allclose(np.asarray(z),
                                       np.asarray(with_zeros[0]), atol=1e-6)
        got = got + with_zeros[0]
    zero_picks = int((experts >= cfg.num_experts).sum())
    assert 0 < zero_picks < 12 * cfg.top_k
    assert pairs == 12 * cfg.top_k - zero_picks  # each real pair ONE holder
    assert float(jnp.abs(with_zeros[0]).max()) > 1e-2
    assert float(jnp.abs(want).max()) > 1e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # and the program's layer under one holder is that holder's reference
    held = dataclasses.replace(cfg, experts_held=(2, 2))
    mine = dict(lp, moe_gmm_w_in=lp["moe_gmm_w_in"][2:4],
                moe_gmm_w_out=lp["moe_gmm_w_out"][2:4])
    from ray_tpu.models.longcat_flash import _routed

    with jax.default_matmul_precision("highest"):
        s, sizes, zero = _routed(h[None], mine, held, ones[None])
        np.testing.assert_allclose(
            np.asarray(s[0]), np.asarray(ref.routed_part(h, mine, held)),
            atol=1e-5)
    assert int(zero) == zero_picks and sizes.shape == (2,)


# --------------------------------------------------- the kernel at odd heads


def _latent_case(kind, seed=0, H=3, C=16, R=4, bs=4, NB=8, B=2):
    """q at an ODD head count, the pool (one plane, rows ``[c | k_rope]``
    at whole lanes) with every page OUTSIDE the tables poisoned, the tables and positions of a decode step or a chunk
    against a resident context."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import latent_row_width, plane_width

    S = {"decode": 1, "chunk": 8}[kind]
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    blocks = 1 + B * NB
    pool = jax.random.normal(
        ks[0], (4, blocks + 3, bs, latent_row_width(C, R)))
    Cp = plane_width(C)
    pool = pool.at[..., C:Cp].set(0.0).at[..., Cp + R:].set(0.0)
    tables = np.zeros((B, NB), np.int32)
    perm = np.random.default_rng(seed).permutation(np.arange(1, blocks))
    ctx = {"decode": [13, 30], "chunk": [21, 9]}[kind]
    pos = np.zeros((B, S), np.int32)
    for b in range(B):
        n = -(-(ctx[b] + S) // bs)
        tables[b, :n] = perm[b * NB: b * NB + n]
        pos[b] = ctx[b] + np.arange(S)
    poisoned = np.ones(blocks + 3, bool)
    poisoned[tables[tables > 0]] = False
    poison = jnp.asarray(poisoned)[None, :, None, None]
    lanes = jnp.arange(pool.shape[-1]) < Cp  # NaN in c's lanes, inf behind
    pool = jnp.where(poison, jnp.where(lanes, jnp.nan, jnp.inf), pool)
    q = jax.random.normal(ks[2], (B, S, H, C + R))
    return q, pool, jnp.asarray(tables), jnp.asarray(pos), C


@pytest.mark.parametrize("kind", ["decode", "chunk"])
@pytest.mark.parametrize("heads", [3, 5])
def test_latent_kernel_at_an_odd_head_count_with_pages_poisoned(
        jax_cpu, kind, heads):
    """The kernel in the Pallas interpreter == the XLA path at 3 and 5
    heads (a tile's rows are queries x heads: no multiple of 8), at pool
    layer 3 of 4 (a second sub-layer's), pages no table names hold NaN and
    inf."""
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import latent_attention

    q, pool, tables, pos, C = _latent_case(kind, H=heads)
    want = latent_attention(
        q, pool.at[:, 0].set(0.0), tables, pos,
        latent_dim=C, scale=0.3, backend="xla", layer=3)
    got = latent_attention(q, pool, tables, pos, latent_dim=C,
                           scale=0.3, backend="pallas", layer=3)
    assert got.shape == (*q.shape[:3], C)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ------------------------------------------------------- the cached steps


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_cached_steps_match_the_reference_logits(tiny, ref, backend):
    """The family's own step functions on a hand-built table: a fresh
    chunk, a chunk against the resident context, then decode through a
    pool of FOUR sub-layers for two layers: logits against the reference's
    (expanded, no cache) at every step, to 1e-4."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.longcat_flash import (
        longcat_flash_decode_step, longcat_flash_init_state,
        longcat_flash_prefill,
    )

    cfg, params = tiny
    cfg = dataclasses.replace(cfg, attention_backend=backend)
    bs, NB = 4, 12
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(10), (40,), 1, cfg.vocab_size))
    want = np.asarray(ref.logits(params, jnp.asarray(tokens[None]), cfg))[0]
    k, v = jnp.zeros((cfg.n_kv_layer, 1 + NB, bs, sum(
        stored for _, _, stored in cfg.kv_planes))), None
    assert k.shape[0] == 4 == 2 * cfg.n_layer
    state = longcat_flash_init_state(cfg, 2)
    slots = jnp.ones((1,), jnp.int32)
    tables = jnp.asarray(1 + np.arange(NB, dtype=np.int32)[None])
    done = 0
    with jax.default_matmul_precision("highest"):
        for n in (16, 11):
            chunk = np.zeros((1, 16), np.int32)
            chunk[0, :n] = tokens[done:done + n]
            out, k, v, state = longcat_flash_prefill(
                params, k, v, jnp.asarray(chunk), jnp.asarray([n]), tables,
                cfg, start=None if done == 0 else jnp.asarray([done]),
                state=state, slots=slots)
            done += n
            np.testing.assert_allclose(
                np.asarray(out)[0], want[done - 1], atol=1e-4)
        for pos in range(done, 40):
            out, k, v, state = longcat_flash_decode_step(
                params, k, v, jnp.asarray(tokens[pos:pos + 1]),
                jnp.asarray([pos]), tables, cfg, state=state, slots=slots)
            np.testing.assert_allclose(
                np.asarray(out)[0], want[pos], atol=1e-4)
    # EVERY one of the four sub-layers kept its own row of every token
    rows = np.asarray(k[:, 1:11, :, :16]).reshape(4, -1, 16)
    assert float(np.abs(rows).min()) > 0
    for a in range(4):
        for b in range(a + 1, 4):
            assert float(np.abs(rows[a] - rows[b]).max()) > 1e-2
    # one row ``[c | k_rope]`` a token, zeros in the padding, no second pool
    assert v is None and k.shape[-1] == 256
    assert float(jnp.abs(k[:, 1:11, :, 128:132]).min()) > 0
    assert float(jnp.abs(k[..., 16:128]).max()) == 0.0
    assert float(jnp.abs(k[..., 132:]).max()) == 0.0


# what the engine below decoded over the pool in TWO planes (the tree PR 53
# started from, both backends): one plane moves no token
_TWO_PLANES_DECODED = [
    [58, 58, 24, 46, 61, 46, 61, 58, 24, 46, 61, 58],
    [9, 43, 32, 44, 32, 55, 24, 19, 1, 23, 38, 25],
    [23, 1, 24, 2, 1, 10, 36, 26, 41, 45, 30, 29],
    [56, 53, 6, 12, 44, 15, 23, 26, 25, 30, 56, 36],
]


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_engine_serves_through_the_latent_pool(tiny, ref, backend):
    """``EngineConfig(model="longcat_flash")`` through the normal path:
    prompts shorter and longer than a chunk (prefill in chunks, then
    decode), greedy tokens the reference's own at every position (its
    logit within 1e-4 of the largest), the pool reported in planes over
    2 x layers, nothing held at the end."""
    import jax.numpy as jnp

    cfg, params = tiny
    engine = _engine(cfg, params, attention_backend=backend)
    prompts = _prompts([5, 23, 40, 61], seed=3)
    streams = [engine.submit(prompts[0], max_new_tokens=12, temperature=0.0)]
    engine.step()  # a whole prompt alone: one piece of a packed step
    streams += [engine.submit(p, max_new_tokens=12, temperature=0.0)
                for p in prompts[1:]]
    _drive(engine, streams)
    for p, s, was in zip(prompts, streams, _TWO_PLANES_DECODED):
        out = list(s)
        assert len(out) == 12 and max(out) < VOCAB_HELD
        assert out == was
        logits = np.asarray(ref.logits(params, jnp.asarray([p + out]), cfg))[0]
        rows = logits[len(p) - 1: len(p) + 11]
        deficit = rows.max(-1) - rows[np.arange(12), out]
        assert float(deficit.max()) < 1e-4, deficit
    # ISSUE 47: a latent pool's prefill steps are packed, cold prompt or
    # not: the chunk program over the ladder's rungs, no ``prefill`` kind
    kinds = {sig[0] for sig in engine.fns.signatures}
    assert kinds == {"prefill_chunk", "decode"}
    st = engine.stats()
    assert st["prefill_steps_packed"] == st["prefill_steps"] > 0
    assert st["kv_used_blocks"] == 0 and st["prefix_reuse"] is True
    assert st["kv_pool"]["kind"] == "latent"
    assert st["kv_pool"]["row_bytes"] == (16 + 4) * 4
    described = st["executor"]
    assert described["attention_backend"] == backend
    assert described["kv_layers"] == 4 == 2 * cfg.n_layer
    assert described["kv_pool"]["shapes"] == [[4, 129, 4, 256]]
    assert described["kv_pool"]["page_copies"] == 1
    assert len(described["kv_pool"]["planes"]) == 1
    assert engine.cache.v is None
    assert "kv_groups" not in described
    engine.shutdown()


def test_program_names_and_the_familys_entry(jax_cpu):
    from ray_tpu.models import longcat_flash as m
    from ray_tpu.serve.llm import decode

    assert m.longcat_flash_prefill.__name__ == "longcat_flash_prefill"
    assert m.longcat_flash_decode_step.__name__ == \
        "longcat_flash_decode_step"
    fam = decode.get_family("longcat_flash")
    assert fam.verify_step is None and fam.state_rows is False
    assert "longcat_flash" in decode.FAMILIES and len(decode.FAMILIES) == 12
    cfg = fam.default_config()
    axes, quant = fam.param_axes(cfg), fam.quant_axes(cfg)
    sub = quant["layers"][0]["sub"][1]
    assert (sub["mla_w_uk"], sub["dense_ffn_w_out"], sub["attn_norm"]) == (
        0, 0, -1)
    assert quant["layers"][1]["moe_gmm_w_in"] == 1
    assert quant["layers"][1]["moe_route_w"] == -1
    assert quant["layers"][1]["moe_route_bias"] == -1
    assert (quant["wte"], quant["lm_head"], quant["ln_f_scale"]) == (1, 0, -1)
    assert axes["layers"][0]["moe_gmm_w_out"] == ("expert", "mlp", None)
    assert axes["layers"][0]["sub"][0]["mla_kv_norm"] == ("embed",)


def test_counters_count_zero_picks_and_a_steps_held_pairs(tiny):
    """``stats()``: every pick is a routed pair; a pick met a held real
    expert, a zero-compute expert, or a real expert held elsewhere (counted
    nowhere here); and a decode step's held pairs land in one bucket of
    ``moe_step_pairs_decode``, so a window's largest and mean are read off
    the difference of two readings."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompts = _prompts([9, 30], seed=4)
    streams = [engine.submit(p, max_new_tokens=6, temperature=0.0)
               for p in prompts]
    _drive(engine, streams)
    st = engine.stats()
    layers, k = cfg.n_layer, cfg.top_k
    assert st["moe_pairs_prefill"] == (9 + 30) * layers * k
    assert st["moe_pairs_decode"] == 2 * 5 * layers * k
    for kind in ("prefill", "decode"):
        held = st[f"moe_pairs_held_{kind}"]
        zero = st[f"moe_zero_picks_{kind}"]
        assert 0 < held and 0 < zero
        assert held + zero < st[f"moe_pairs_{kind}"]  # the rest: elsewhere
    # 4 of 12 outputs are zeros: about a third of the picks
    share = st["moe_zero_picks_prefill"] / st["moe_pairs_prefill"]
    assert 0.15 < share < 0.55, share
    assert len(st["moe_pairs_by_expert"]) == 2  # the held REAL experts
    assert sum(st["moe_pairs_by_expert"]) == \
        st["moe_pairs_held_prefill"] + st["moe_pairs_held_decode"]
    hist = st["moe_step_pairs_decode"]
    steps = st["decode_steps"]
    assert sum(hist) == steps > 0
    assert sum(n * c for n, c in enumerate(hist)) == \
        st["moe_pairs_held_decode"]
    engine.shutdown()


@pytest.mark.parametrize("option,match", [
    ({"speculative_k": 2}, "no verify step"),
    ({"host_cache_bytes": 1 << 20}, "KVLayout"),
    ({"quantization": "int8"}, "latent row has no head"),
    ({"tp": 2}, "one shared row has none"),
])
def test_what_a_latent_pool_cannot_carry_is_refused(tiny, option, match):
    """Over TWO latent sub-layers a layer nothing new is refused: the
    latent family's list stands, by the same reasons."""
    cfg, params = tiny
    with pytest.raises(ValueError, match=match) as e:
        _engine(cfg, params, **option)
    assert "longcat_flash" in str(e.value)
    assert "latent row a token" in str(e.value)


def test_a_prefix_hit_gives_the_uninterrupted_tokens(tiny):
    """Counters alone stand in no prefix hit's way: a second request with
    the first's prompt reuses its blocks in all four sub-layers."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompt = _prompts([37], seed=8)[0]
    first = engine.submit(prompt, max_new_tokens=8, temperature=0.0)
    _drive(engine, [first])
    second = engine.submit(prompt, max_new_tokens=8, temperature=0.0)
    _drive(engine, [second])
    assert list(second) == list(first)
    assert engine.stats()["prefix_hit_tokens"] >= 32
    engine.shutdown()
