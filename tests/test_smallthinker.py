"""The smallthinker family on the CPU at the tiny preset, seeded weights,
float32: the program against the plain reference
(benchmark/reference/smallthinker.py), the serving path (chunked prefill,
then decode through the tables by group, the context past the window and
blocks given back behind it) against the reference's full forward on both
attention backends, the router (a softmax over the k largest logits, taken
from the layer's INPUT), ReLU-gated experts, the NoPE layers, the kernel at
an odd group of 7 over a lane-dense pool of 4 heads of 128, what the engine
refuses, and the counters.

Program and reference in float32 compute the same mathematics and differ in
the order of sums: 1e-4 on logits of size ~4 (seen 9e-6).
"""
from __future__ import annotations

import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


@pytest.fixture(scope="module")
def ref():
    from benchmark import common

    return common.load_named("reference", "smallthinker")


@pytest.fixture(scope="module")
def tiny(jax_cpu):
    """(float32 tiny config, its seeded params)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.smallthinker import (
        SmallThinkerConfig, smallthinker_init,
    )

    cfg = dataclasses.replace(SmallThinkerConfig.tiny(), dtype=jnp.float32)
    return cfg, smallthinker_init(jax.random.PRNGKey(1), cfg)


def _engine(cfg, params, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    settings = dict(model="smallthinker", model_config=cfg, block_size=4,
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
    from ray_tpu.models.smallthinker import SmallThinkerConfig

    cfg = SmallThinkerConfig(layer_types=(
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention") * 2)
    assert cfg.kv_table_groups == (
        (None, (0, 4)), (4096, (1, 5)), (4096, (2, 6)), (4096, (3, 7)))
    assert cfg.n_kv_layer == 2 and cfg.n_layer == 8
    assert [slot for _, slot, _ in cfg.kv_layout] == [0, 0, 0, 0, 1, 1, 1, 1]
    whole = SmallThinkerConfig()
    assert whole.n_layer == 52 and whole.n_kv_layer == 13
    assert whole.layer_types.count("full_attention") == 13
    tiny = SmallThinkerConfig.tiny()
    assert tiny.kv_table_groups == ((None, (0, 4)), (8, (1, 3)), (8, (2,)))
    assert tiny.n_head // tiny.n_kv_head == 7  # the odd group, kept
    with pytest.raises(ValueError, match="layer_types"):
        SmallThinkerConfig(layer_types=("conv",))
    with pytest.raises(ValueError, match="multiple of n_kv_head"):
        SmallThinkerConfig(n_head=30)


def test_the_whole_model_is_the_rows_21_billion(jax_cpu):
    """The row says 21B with 3B active: every leaf of the 52 published
    layers, counted from shapes alone; and the benchmark's cut (8 layers,
    every expert, the whole vocabulary)."""
    import jax

    from ray_tpu.models.smallthinker import (
        SmallThinkerConfig, smallthinker_init,
    )

    def count(cfg):
        shapes = jax.eval_shape(
            lambda: smallthinker_init(jax.random.PRNGKey(0), cfg))
        return sum(a.size for a in jax.tree.leaves(shapes))

    assert abs(count(SmallThinkerConfig()) - 21.51e9) < 1e7
    cut = SmallThinkerConfig(layer_types=SmallThinkerConfig().layer_types[:8])
    assert abs(count(cut) - 3966.9e6) < 1e5
    # active a token: attention, router, 6 of 64 experts, the head's row
    active = 52 * (20.97e6 + 0.164e6 + 6 * 5.898e6) + 2 * 151936 * 2560
    assert 2.9e9 < active < 3.8e9


# ----------------------------------------------------------- the router


def _layer_inputs(T=24, D=32, E=16, F=8, k=3, seed=0):
    import jax

    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(keys[0], (T, D))
    router = jax.random.normal(keys[1], (D, E))
    w_in = jax.random.normal(keys[2], (E, D, 2 * F)) * D ** -0.5
    w_out = jax.random.normal(keys[3], (E, F, D)) * F ** -0.5
    return x, router, w_in, w_out, k


def test_route_is_a_softmax_over_the_k_largest_logits(jax_cpu):
    """``score="softmax_topk"``: the k largest LOGITS, weighted by a softmax
    over those k alone AND, the same numbers, by the softmax over all
    experts renormalised over the chosen; without ``norm_topk`` the softmax
    over all at the chosen."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.moe import moe_route

    x, router, _, _, k = _layer_inputs()
    weights, experts = moe_route(x, router, None, k, score="softmax_topk")
    logits = np.asarray(jnp.dot(x, router,
                                precision=jax.lax.Precision.HIGHEST))
    order = np.argsort(-logits, axis=-1)[:, :k]
    np.testing.assert_array_equal(np.asarray(experts), order)
    chosen = np.take_along_axis(logits, order, axis=-1)
    over_k = np.asarray(jax.nn.softmax(jnp.asarray(chosen), axis=-1))
    np.testing.assert_allclose(np.asarray(weights), over_k, atol=1e-6)
    over_all = np.take_along_axis(
        np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1)), order, -1)
    np.testing.assert_allclose(
        np.asarray(weights), over_all / over_all.sum(-1, keepdims=True),
        atol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-6)
    plain, same = moe_route(x, router, None, k, norm_topk=False,
                            score="softmax_topk", scale=2.0)
    np.testing.assert_array_equal(np.asarray(same), order)
    np.testing.assert_allclose(np.asarray(plain), 2.0 * over_all, atol=1e-6)
    # it is not the sigmoid router's weights
    sig, _ = moe_route(x, router, None, k)
    assert float(np.abs(np.asarray(sig) - np.asarray(weights)).max()) > 0.05
    with pytest.raises(ValueError, match="selection bias"):
        moe_route(x, router, jnp.zeros(16), k, score="softmax_topk")
    with pytest.raises(ValueError, match="score must be"):
        moe_route(x, router, None, k, score="softmax_all")


def test_defaults_give_the_parents_arrays_bit_for_bit(jax_cpu):
    """``moe_route`` and ``moe_dropless`` without the new options are what
    they were: the router's arrays equal the parent's formula written out
    here bit for bit, the default activation is ``silu`` to the bit, and
    the traced programs of default and explicit calls are one text."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.moe import ROUTE_NORM_EPS, moe_dropless, moe_route

    x, router, w_in, w_out, k = _layer_inputs(seed=2)
    bias = jax.random.normal(jax.random.PRNGKey(9), (16,)) * 0.1

    def parents(x, router, bias, top_k, scale):
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        weights = weights / (
            jnp.sum(weights, axis=-1, keepdims=True) + ROUTE_NORM_EPS)
        return weights * scale, experts.astype(jnp.int32)

    got = moe_route(x, router, bias, k, scale=2.5)
    want = parents(x, router, bias, k, 2.5)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    y, sizes = moe_dropless(x, *got, w_in, w_out, dtype=jnp.float32)
    y_silu, sizes_silu = moe_dropless(x, *got, w_in, w_out,
                                      dtype=jnp.float32, act="silu")
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_silu))
    np.testing.assert_array_equal(np.asarray(sizes), np.asarray(sizes_silu))
    texts = [str(jax.make_jaxpr(fn)(x, router, w_in, w_out)) for fn in (
        lambda x, r, wi, wo: moe_dropless(
            x, *moe_route(x, r, None, k), wi, wo, dtype=jnp.float32),
        lambda x, r, wi, wo: moe_dropless(
            x, *moe_route(x, r, None, k, score="sigmoid"), wi, wo,
            dtype=jnp.float32, act="silu"))]
    assert texts[0] == texts[1] and "logistic" in texts[0]


def _by_loop(x, weights, experts, w_in, w_out, act):
    """The layer as a loop over (token, choice): the plain meaning."""
    import jax.numpy as jnp

    out = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for j in range(experts.shape[1]):
            e = int(experts[t, j])
            gate, up = jnp.split(x[t] @ w_in[e], 2)
            out[t] += float(weights[t, j]) * np.asarray(
                (act(gate) * up) @ w_out[e])
    return out


@pytest.mark.parametrize("valid", [False, True])
def test_dropless_relu_is_a_loop_over_the_experts(jax_cpu, valid):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.moe import moe_dropless, moe_route

    x, router, w_in, w_out, k = _layer_inputs(seed=3)
    mask = (jnp.arange(x.shape[0]) % 5 != 0) if valid else None
    weights, experts = moe_route(x, router, None, k, score="softmax_topk")
    with jax.default_matmul_precision("highest"):
        y, sizes = moe_dropless(x, weights, experts, w_in, w_out,
                                dtype=jnp.float32, valid=mask, act="relu")
        want = _by_loop(x, weights, experts, w_in, w_out, jax.nn.relu)
        silu = _by_loop(x, weights, experts, w_in, w_out, jax.nn.silu)
    if valid:
        want[::5] = 0.0
        assert float(np.abs(np.asarray(y)[0]).max()) == 0.0
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    assert int(sizes.sum()) == (int(mask.sum()) if valid else 24) * k
    assert float(np.abs(want - silu).max()) > 0.05  # relu is not silu
    with pytest.raises(KeyError):
        moe_dropless(x, weights, experts, w_in, w_out, dtype=jnp.float32,
                     act="gelu")


def test_the_route_reads_the_layers_input(tiny, monkeypatch):
    """The router of a layer reads what comes INTO the layer: with the
    attention weights of layer 0 changed, layer 0's route is the same to
    the bit, its output is not, and layer 1's route (whose input that
    output is) moves."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import smallthinker as m

    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 24), 1,
                                cfg.vocab_size)
    seen = []
    real = m.moe_route

    def spy(*args, **kw):
        out = real(*args, **kw)
        seen.append(tuple(np.asarray(a) for a in out))
        return out

    monkeypatch.setattr(m, "moe_route", spy)
    first = m.smallthinker_forward(params, tokens, cfg)
    other = dict(params, layers=[
        {k: (v * 1.5 if i == 0 and k in ("wq", "wv", "wo") else v)
         for k, v in lp.items()} for i, lp in enumerate(params["layers"])])
    second = m.smallthinker_forward(other, tokens, cfg)
    a, b = seen[:cfg.n_layer], seen[cfg.n_layer:]
    assert len(a) == len(b) == cfg.n_layer
    for x, y in zip(a[0], b[0]):
        np.testing.assert_array_equal(x, y)  # weights and experts, layer 0
    assert float(np.abs(a[1][0] - b[1][0]).max()) > 1e-4
    assert float(jnp.abs(first - second).max()) > 1e-3


def test_full_layers_carry_no_position_and_sliding_layers_do(tiny):
    """NoPE: a full layer's q and k do not depend on the positions at all
    (stretched to 2 p + 3 they are the same arrays), a sliding layer's do.
    A uniform SHIFT of all positions is no test of it: rotary embedding is
    relative, so a sliding layer's scores do not move under a shift either
    (held here too), only its q and k do."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import smallthinker as m

    cfg, params = tiny
    lp = params["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 12, cfg.d_model))
    pos = jnp.arange(12, dtype=jnp.int32)[None]
    at = {name: m.rotary_tables(p, cfg) for name, p in (
        ("plain", pos), ("stretched", 2 * pos + 3), ("shifted", pos + 40))}
    full = {n: m._qkv(h, lp, "full_attention", t, cfg) for n, t in at.items()}
    slid = {n: m._qkv(h, lp, "sliding_attention", t, cfg)
            for n, t in at.items()}
    for n in ("stretched", "shifted"):
        for a, b in zip(full["plain"], full[n]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert float(jnp.abs(slid["plain"][0] - slid[n][0]).max()) > 0.1
        # v is never rotated
        np.testing.assert_array_equal(np.asarray(slid["plain"][2]),
                                      np.asarray(slid[n][2]))

    def scores(qkv):
        q, k, _ = qkv
        return jnp.einsum("bshd,bthd->bhst", q, jnp.repeat(k, 7, axis=2))

    np.testing.assert_allclose(np.asarray(scores(slid["plain"])),
                               np.asarray(scores(slid["shifted"])),
                               atol=2e-4)
    assert float(jnp.abs(scores(slid["plain"])
                         - scores(slid["stretched"])).max()) > 0.05


# ------------------------------------------------- program == reference


def test_full_forward_matches_the_reference(tiny, ref):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.smallthinker import smallthinker_forward

    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 40), 1,
                                cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got = smallthinker_forward(params, tokens, cfg)
    want = ref.logits(params, tokens, cfg)
    assert want.shape == (2, 40, cfg.vocab_size)
    assert float(jnp.abs(got - want).max()) < 1e-4
    assert float(jnp.abs(want).max()) > 1.0


WRONG_MODELS = ["router_reads_post_attention", "router_reads_raw_input",
                "rotary_on_a_full_layer", "no_rotary_on_sliding_layers",
                "silu_for_relu", "window_one_short", "window_one_long",
                "sigmoid_router"]


def wrong_reference(ref, change):
    """The reference with ONE mechanism read wrongly: ``(patches, cfg
    change)``, the module attributes to set and the config fields to
    replace. benchmark's controls (PERF.md section 6) use the same."""
    import jax
    import jax.numpy as jnp

    def always_rotate(x, kind, cfg):
        return ref_positional(x, "sliding_attention", cfg)

    def never_rotate(x, kind, cfg):
        return x

    def silu_expert(g, w_in, w_out):
        gate, up = jnp.split(ref._mm(g, w_in), 2, axis=-1)
        return ref._mm(jax.nn.silu(gate) * up, w_out)

    def sigmoid_route(x, h, lp, cfg):
        s = jax.nn.sigmoid(
            ref._f32(ref.router_input(x, h)) @ ref._f32(lp["moe_route_w"]))
        kth = jnp.sort(s, axis=-1)[..., -cfg.top_k][..., None]
        w = jnp.where(s >= kth, s, 0.0)
        return w / jnp.sum(w, axis=-1, keepdims=True)

    def post_attention_hidden(params, tokens, cfg):
        x = ref._f32(params["wte"])[tokens]
        for lp, kind in zip(params["layers"], cfg.layer_types):
            h = ref._rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            x = x + ref._attention(h, lp, kind, cfg)
            g = ref._rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
            # the WRONG place: the stream the experts read
            x = x + ref.experts(g, ref.route(x, g, lp, cfg), lp)
        return ref._rms_norm(x, params["ln_f_scale"], cfg.norm_eps)

    ref_positional = ref.positional
    return {
        "router_reads_post_attention": (
            {"_hidden_one": post_attention_hidden}, {}),
        "router_reads_raw_input": ({"router_input": lambda x, h: x}, {}),
        "rotary_on_a_full_layer": ({"positional": always_rotate}, {}),
        "no_rotary_on_sliding_layers": ({"positional": never_rotate}, {}),
        "silu_for_relu": ({"expert": silu_expert}, {}),
        "window_one_short": ({}, {"sliding_window": -1}),
        "window_one_long": ({}, {"sliding_window": +1}),
        "sigmoid_router": ({"route": sigmoid_route}, {}),
    }[change]


@pytest.mark.parametrize("change", WRONG_MODELS)
def test_the_reference_notices_each_mechanism(tiny, ref, change, monkeypatch):
    """A router fed the post-attention stream or the raw input, rotary left
    on a full layer or off a sliding one, ``silu`` for ``relu``, a window
    of one key less or more, a sigmoid router: each moves the logits far
    past the 1e-4 the program is held to, in float32 where rounding cannot
    hide it."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.smallthinker import smallthinker_forward

    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 40), 1,
                                cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got = smallthinker_forward(params, tokens, cfg)
    patches, fields = wrong_reference(ref, change)
    for name, fn in patches.items():
        monkeypatch.setattr(ref, name, fn)
    wrong_cfg = dataclasses.replace(cfg, **{
        k: getattr(cfg, k) + d for k, d in fields.items()})
    wrong = ref.logits(params, tokens, wrong_cfg)
    assert float(jnp.abs(got - wrong).max()) > 0.02, change


# ----------------------------------- the kernel at an odd group, lane-dense


@pytest.mark.parametrize("window", [None, 40])
@pytest.mark.parametrize("kind", ["decode", "chunk"])
def test_kernel_at_a_group_of_seven_matches_xla(jax_cpu, kind, window):
    """Interpret mode at the published heads: 28 query heads on 4 K/V heads
    of 128, the pool lane-dense ``[2, blocks, 16, 512]`` as the cache
    manager stores it, read at a layer index, plain and windowed. Windowed:
    table entries wholly behind the window are block 0, as ``free_behind``
    leaves them, and block 0 holds NaN for the kernel: it never copies
    such a page."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.kv_cache import paged_prefill_attention
    from ray_tpu.ops.paged_attention import (
        paged_prefill_attention_pallas, pool_shape,
    )

    Hq, Hkv, hd, bs, NB, B = 28, 4, 128, 16, 12, 2
    S = 1 if kind == "decode" else 24
    shape = pool_shape(2, 1 + B * NB, bs, Hkv, hd)
    assert shape == (2, 1 + B * NB, bs, 512)
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    pool_k = jax.random.normal(keys[0], shape)
    pool_v = jax.random.normal(keys[1], shape)
    q = jax.random.normal(keys[2], (B, S, Hq, hd))
    last = np.array([150, 97])  # each row's newest position
    pos = jnp.asarray(last[:, None] - (S - 1) + np.arange(S)[None, :],
                      jnp.int32)
    tables = 1 + np.arange(B * NB, dtype=np.int32).reshape(B, NB)
    if window is not None:
        for b in range(B):  # what free_behind gave back before this step
            tables[b, : max(0, (int(pos[b, 0]) - window + 1) // bs)] = 0
        assert (tables == 0).any()
    tables = jnp.asarray(tables)
    want = paged_prefill_attention(
        q, pool_k[1], pool_v[1], tables, pos, window=window)
    poisoned = (pool_k.at[:, 0].set(jnp.nan), pool_v.at[:, 0].set(jnp.nan))
    got = paged_prefill_attention_pallas(
        q, *poisoned, tables, pos, window=window, layer=1, interpret=True)
    assert got.shape == (B, S, Hq, hd) and bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    if window is not None:  # and the window matters
        full = paged_prefill_attention(q, pool_k[1], pool_v[1], tables, pos)
        assert float(jnp.abs(full - want).max()) > 1e-2


# ------------------------- the serving path == the reference's full forward


def test_cached_steps_match_the_reference_logits(tiny, ref):
    """The family's own step functions on hand-built tables by group: a
    prompt in chunks, then decode, the context five windows long, the
    sliding groups' entries behind the window block 0 — logits against the
    reference's at every step."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.smallthinker import (
        smallthinker_decode_step, smallthinker_init_state,
        smallthinker_prefill,
    )
    from ray_tpu.ops.paged_attention import pool_shape

    cfg, params = tiny
    bs, NB = 4, 12
    G = len(cfg.kv_table_groups)
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(7), (40,), 1, cfg.vocab_size))
    want = np.asarray(ref.logits(params, jnp.asarray(tokens[None]), cfg))[0]
    shape = pool_shape(cfg.n_kv_layer, 1 + G * NB, bs, cfg.n_kv_head,
                       cfg.head_dim)
    k, v = jnp.zeros(shape), jnp.zeros(shape)
    state = smallthinker_init_state(cfg, 2)
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
            out, k, v, state = smallthinker_prefill(
                params, k, v, jnp.asarray(chunk), jnp.asarray([n]),
                tables_at(done), cfg,
                start=None if done == 0 else jnp.asarray([done]),
                state=state, slots=slots)
            done += n
            np.testing.assert_allclose(
                np.asarray(out)[0], want[done - 1], atol=1e-4)
        for pos in range(done, 40):
            out, k, v, state = smallthinker_decode_step(
                params, k, v, jnp.asarray(tokens[pos:pos + 1]),
                jnp.asarray([pos]), tables_at(pos), cfg, state=state,
                slots=slots)
            np.testing.assert_allclose(
                np.asarray(out)[0], want[pos], atol=1e-4)
    assert (np.asarray(tables_at(39))[1:] == 0).sum() >= 14


def _kernel_config():
    """The published heads (28 on 4 of 128: the lane-dense 512-lane row, a
    group of 7) in a stack of four small layers, for the interpreted
    kernel."""
    import jax.numpy as jnp

    from ray_tpu.models.smallthinker import SmallThinkerConfig

    return dataclasses.replace(
        SmallThinkerConfig.tiny(), dtype=jnp.float32, n_head=28, n_kv_head=4,
        head_dim=128,
        layer_types=("full_attention", "sliding_attention",
                     "sliding_attention", "full_attention"))


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_engine_serves_through_the_grouped_cache(tiny, ref, backend):
    """``EngineConfig(model="smallthinker")`` through the normal path on
    both attention backends: prompts shorter and longer than a chunk and
    the window, prefill by chunks then decode past the window, the
    reference's logit of every greedy token within 1e-4 of its largest (on
    the XLA backend; the interpreted kernel's base-2 softmax 1e-3), blocks
    given back behind the window, nothing held at the end."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.smallthinker import smallthinker_init

    if backend == "xla":
        cfg, params = tiny
        engine = _engine(cfg, params, attention_backend="xla")
        prompts, new, tol = _prompts([5, 23, 40, 61], seed=3), 20, 1e-4
        assert engine.cache.k.shape == (2, 129, 4, 32)
    else:
        cfg = _kernel_config()
        params = smallthinker_init(jax.random.PRNGKey(2), cfg)
        engine = _engine(cfg, params, attention_backend="pallas",
                         block_size=16, num_blocks=65,
                         prefill_chunk_tokens=32,
                         length_buckets=(32, 64, 128))
        assert engine.cache.k.shape == (2, 65, 16, 512)  # lane-dense
        prompts, new, tol = _prompts([7, 45], seed=8), 12, 1e-3
    streams = [engine.submit(p, max_new_tokens=new, temperature=0.0)
               for p in prompts]
    _drive(engine, streams)
    for p, s in zip(prompts, streams):
        out = list(s)
        assert len(out) == new
        logits = np.asarray(ref.logits(params, jnp.asarray([p + out]), cfg))[0]
        rows = logits[len(p) - 1: len(p) + new - 1]
        deficit = rows.max(-1) - rows[np.arange(new), out]
        assert float(deficit.max()) < tol, deficit
    st = engine.stats()
    assert "prefill_chunk" in {sig[0] for sig in engine.fns.signatures}
    assert {sig[0] for sig in engine.fns.signatures} <= {
        "prefill", "prefill_chunk", "decode"}
    groups = st["kv_groups"]
    assert [g["window"] for g in groups][0] is None
    assert all(g["window"] == 8 for g in groups[1:])
    assert all(g["blocks"] == 0 for g in groups)
    assert 0 < st["kv_window_blocks_freed"] < st["kv_window_blocks_taken"]
    assert st["kv_used_blocks"] == 0 and st["prefix_reuse"] is False
    assert "gave back" in st["prefix_reuse_why_not"] \
        or "given back" in st["prefix_reuse_why_not"]
    assert st["executor"]["kv_groups"][0]["kind"] == "full"
    assert {g["kind"] for g in st["executor"]["kv_groups"][1:]} == {"sliding"}
    engine.shutdown()


def test_counters_and_spans_count_rows_past_the_window(tiny, monkeypatch):
    """``stats()``: the routed pairs (every expert is held: ``top_k`` a
    token a layer), the experts a decode step read, and the decode rows
    whose context had passed the window; ``executor.dispatch`` carries
    ``kv_tokens``, ``kv_tokens_window`` and ``rows_past_window``."""
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
    streams = [engine.submit(p, max_new_tokens=6, temperature=0.0)
               for p in _prompts([3, 13], seed=5)]
    _drive(engine, streams)
    st = engine.stats()
    layers, k = cfg.n_layer, cfg.top_k
    assert st["moe_pairs_prefill"] == (3 + 13) * layers * k
    # the first new token comes out of prefill; each later one of a step
    assert st["moe_pairs_decode"] == 2 * 5 * layers * k
    assert len(st["moe_pairs_by_expert"]) == cfg.num_experts
    assert sum(st["moe_pairs_by_expert"]) == \
        st["moe_pairs_prefill"] + st["moe_pairs_decode"]
    assert 0 < st["moe_expert_reads_decode"] <= 5 * layers * 2 * k
    assert seen and all(
        {"kv_tokens", "kv_tokens_window", "rows_past_window"} <= set(a)
        for a in seen)
    first = seen[0]
    # contexts 4 and 14 at the first step: whole blocks of 4; the window 8
    assert first["kv_tokens"] == 4 + 16
    assert first["kv_tokens_window"] == 4 + 8
    assert first["rows_past_window"] == 1
    assert st["decode_rows"] == 2 * 5
    # the short row's context ends AT the window of 8, never past it
    assert st["decode_rows_past_window"] == sum(
        a["rows_past_window"] for a in seen) == 5
    engine.shutdown()


def test_a_family_without_windows_counts_no_rows_past_one(jax_cpu):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    engine = LLMEngine(EngineConfig(model="gpt", block_size=4, num_blocks=33,
                                    max_batch_size=2), auto_step=False)
    streams = [engine.submit([1, 2, 3], max_new_tokens=3, temperature=0.0)]
    _drive(engine, streams)
    st = engine.stats()
    assert st["decode_steps"] > 0
    assert st["decode_rows"] == st["decode_rows_past_window"] == 0
    engine.shutdown()


@pytest.mark.parametrize("option,match", [
    ({"speculative_k": 2}, "speculative_k.*gave back"),
    ({"host_cache_bytes": 1 << 20}, "host_cache_bytes.*one table"),
    ({"preemption": {}}, "preemption.*demoted from one table"),
    ({"quantization": "int8"}, "quantization.*not laid out by group"),
    ({"tp": 2}, "tp/fsdp/mesh.*one table a step"),
])
def test_what_the_grouped_cache_cannot_carry_is_refused(tiny, option, match):
    """Every refusal names the option and the grouped tables' own reason
    (the family keeps only counters beside the pool, no rows)."""
    cfg, params = tiny
    with pytest.raises(ValueError, match=match) as e:
        _engine(cfg, params, **option)
    assert "tables by group of layers" in str(e.value)


def test_handoff_is_refused_and_a_small_pool_says_why(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompt = _prompts([20], seed=6)[0]
    with pytest.raises(ValueError, match="handoff"):
        engine.export_prefix(prompt)
    with pytest.raises(ValueError, match="handoff"):
        engine.adopt_prefix(prompt, [])
    engine.shutdown()
    with pytest.raises(ValueError, match="prefill_chunk_tokens"):
        _engine(cfg, params, prefill_chunk_tokens=None, num_blocks=65)


def test_the_families_are_served_and_named(jax_cpu):
    from ray_tpu.serve.llm import decode

    assert sorted(decode.FAMILIES) == [
        "evabyte", "falcon_h1", "gpt", "laguna", "lfm2_moe", "ling_hybrid",
        "llama",
        "longcat_flash", "minicpm_sala", "pangu_ultra_moe", "sdar_moe",
        "smallthinker"]
    with pytest.raises(ValueError, match="smallthinker"):
        decode.get_family("smallthinker2")
    fam = decode.get_family("smallthinker")
    assert fam.verify_step is None and fam.state_rows is False
    assert fam.prefill.__name__ == "smallthinker_prefill"
    assert fam.decode_step.__name__ == "smallthinker_decode_step"


@pytest.mark.parametrize("tokens", [1, 300, 4096, 4128, 5869, 16384])
def test_request_blocks_at_a_window_of_4096(jax_cpu, tokens):
    """What admission reserves at the published window: every block of the
    full group, and of each of the three sliding groups a row's window and
    its slack, 258 blocks, however long the request grows."""
    import jax.numpy as jnp

    from ray_tpu.models.smallthinker import SmallThinkerConfig
    from ray_tpu.serve.llm.kv_cache import KVCacheConfig

    cfg = SmallThinkerConfig(layer_types=SmallThinkerConfig().layer_types[:8])
    kv = KVCacheConfig(n_layer=cfg.n_kv_layer, n_kv_head=4, head_dim=128,
                       num_blocks=65537, block_size=16, dtype=jnp.bfloat16,
                       groups=cfg.kv_table_groups)
    full = -(-tokens // 16)
    assert kv.request_blocks(tokens) == full + 3 * min(full, 258)
    assert kv.window_blocks(4096) == 258
    assert kv.prefill_room(4, 2048) == 4 * 3 * 128


def test_widened_pipeline_matches_solo_runs(tiny):
    """ISSUE 33's schedule (conftest ``run_widened_schedule``): blocks go
    back behind the window while the chunk that passed them, or a decode
    step, is still in flight, and the streams are the bytes of solo
    runs."""
    from conftest import run_widened_schedule

    cfg, params = tiny
    st = run_widened_schedule(lambda **kw: _engine(cfg, params, **kw),
                              cfg.vocab_size)
    assert st["kv_window_blocks_freed"] > 0
