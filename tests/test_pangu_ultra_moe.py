"""The openPangu-Ultra-MoE family on the CPU at the tiny preset (hidden 64, 4
heads, ``q_lora_rank`` 24, ``kv_lora_rank`` 16, nope 8 + rope 4, v 8, 1 dense
+ 2 expert layers, 8 experts of which 2 held, vocab 512 of which 64 held),
seeded weights, float32: the program against the plain reference
(benchmark/reference/pangu_ultra_moe.py: the EXPANDED form, so every
comparison is also absorbed against expanded), the serving path (prefill,
chunked prefill, then decode through the pool in planes) on both backends,
the latent kernel in the Pallas interpreter against the XLA path with every
page outside the tables poisoned, that a page is copied once, the holders'
parts and the sliced head, a prefix hit and a pause, what the engine
refuses, and what it reports.

Program and reference in float32 compute the same mathematics and differ in
the order of sums (and in WHERE the up-projections enter): 1e-4 on logits
of size ~3 (seen 3e-6).
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

    return common.load_named("reference", "pangu_ultra_moe")


@pytest.fixture(scope="module")
def tiny(jax_cpu):
    """(float32 config that holds experts 2-3 of 8 and 64 rows of the
    vocabulary's 512, its seeded params)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.pangu_ultra_moe import (
        PanguUltraMoEConfig, pangu_ultra_moe_init,
    )

    cfg = dataclasses.replace(
        PanguUltraMoEConfig.tiny(VOCAB_HELD), dtype=jnp.float32,
        experts_held=(2, 2))
    return cfg, pangu_ultra_moe_init(jax.random.PRNGKey(1), cfg)


def _engine(cfg, params, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    settings = dict(model="pangu_ultra_moe", model_config=cfg, block_size=4,
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
            time.sleep(0.01)  # parked streams wait for the resume clock
    while engine.step():
        pass
    assert all(s.done for s in streams)


# ------------------------------------------------- the model and its config


def test_tiny_preset_and_published_planes(jax_cpu):
    """The tiny preset is the issue's, and at the published widths a
    token's row is 512 + 64 numbers, two parts of ONE plane, the rotary
    one stored at a whole lane tile."""
    from ray_tpu.models.pangu_ultra_moe import PanguUltraMoEConfig

    t = PanguUltraMoEConfig.tiny()
    assert (t.d_model, t.n_head, t.q_lora_rank, t.kv_lora_rank,
            t.qk_nope_head_dim, t.qk_rope_head_dim, t.v_head_dim) == (
                64, 4, 24, 16, 8, 4, 8)
    assert (t.n_layer, t.num_dense_layers, t.num_experts) == (3, 1, 8)
    pub = PanguUltraMoEConfig()
    assert pub.kv_planes == (("latent", 512, 512), ("rope", 64, 128))
    assert abs(pub.softmax_scale - 192 ** -0.5) < 1e-12
    assert pub.n_held == 256 and dataclasses.replace(
        pub, experts_held=(0, 8)).n_held == 8
    with pytest.raises(ValueError, match="experts_held"):
        dataclasses.replace(pub, experts_held=(250, 8))


def test_a_blocks_bytes_at_the_published_widths(jax_cpu):
    """Reckoned, not allocated: 1,152 B a token a layer by the widths of
    what is cached, 92,160 B a block id over 5 layers; as STORED (the
    rotary part at 128 lanes) 1,280 B and 102,400 B, +11%: ONE plane of
    576 numbers stored as 640, a page one copy. By head the same token
    would be 81,920 B."""
    import jax.numpy as jnp

    from ray_tpu.models.pangu_ultra_moe import PanguUltraMoEConfig
    from ray_tpu.serve.llm.kv_cache import KVCacheConfig

    pub = PanguUltraMoEConfig()
    kv = KVCacheConfig(n_layer=5, n_kv_head=1, head_dim=576,
                       num_blocks=40961, block_size=16, dtype=jnp.bfloat16,
                       planes=pub.kv_planes)
    assert kv.row_bytes == 1152
    assert kv.block_size * kv.n_layer * kv.row_bytes == 92160
    assert kv.stored_row_bytes == 1280 and kv.block_bytes == 102400
    pool = kv.describe_pool()
    assert pool["kind"] == "latent" and pool["page_copies"] == 1
    assert pool["stored_row_bytes"] == 1280
    (plane,) = pool["planes"]
    assert (plane["name"], plane["width"], plane["stored_width"]) == (
        "latent+rope", 576, 640)
    assert [(p["name"], p["width"], p["stored_width"])
            for p in plane["parts"]] == [
                ("latent", 512, 512), ("rope", 64, 128)]
    by_head = KVCacheConfig(n_layer=5, n_kv_head=128, head_dim=160,
                            dtype=jnp.bfloat16)  # (192 + 128) / 2 a head
    assert by_head.row_bytes == 128 * (192 + 128) * 2 == 81920
    assert by_head.describe_pool() == {
        "kind": "heads", "row_bytes": 81920, "stored_row_bytes": 81920,
        "block_bytes": 16 * 5 * 81920, "page_copies": 2}
    with pytest.raises(ValueError, match="planes"):
        KVCacheConfig(n_layer=5, n_kv_head=1, head_dim=576,
                      planes=pub.kv_planes, quantization="int8")


def test_full_forward_matches_the_reference(tiny, ref):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.pangu_ultra_moe import pangu_ultra_moe_forward

    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 37), 1,
                                cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got = pangu_ultra_moe_forward(params, tokens, cfg)
    want = ref.logits(params, tokens, cfg)
    assert got.shape == (2, 37, VOCAB_HELD)
    assert float(jnp.abs(want).max()) > 1.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_absorbed_attention_is_the_expanded_one_layer(tiny, ref):
    """One layer's attention: the absorbed form (what the cached step
    computes against the pool) is the expanded form (keys and values by
    head), in the program and against the reference's."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import pangu_ultra_moe as m
    from ray_tpu.models.parts import absorb, unabsorb

    cfg, params = tiny
    lp = params["layers"][1]
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 29, cfg.d_model))
    pos = np.broadcast_to(np.arange(29, dtype=np.int32), (2, 29))
    def absorbed_attention(q_nope, q_rope, c, k_r):
        # what the cached step computes against the pool, written out:
        # W_uk absorbed into the query, ONE row a token as key and value,
        # W_uv after the sum
        s = (jnp.einsum("bshc,btc->bhst", absorb(q_nope, lp, cfg), c)
             + jnp.einsum("bshr,btr->bhst", q_rope, k_r)) * cfg.softmax_scale
        t = jnp.arange(c.shape[1])
        p = jax.nn.softmax(
            jnp.where(t[None, :] <= t[:, None], s, -1e30), axis=-1)
        return unabsorb(jnp.einsum("bhst,btc->bshc", p, c), lp, cfg)

    with jax.default_matmul_precision("highest"):
        parts = m.queries_and_row(u, lp, *m.rotary_at(pos, cfg), cfg)
        absorbed = absorbed_attention(*parts)
        expanded = m.expanded_attention(*parts, lp, cfg)
        want = np.stack([np.asarray(ref.attention(u[b], lp, cfg))
                         for b in range(2)])
        through_o = np.asarray(absorbed @ lp["mla_w_o"])
    assert float(np.abs(np.asarray(expanded)).max()) > 0.1
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=1e-5)
    np.testing.assert_allclose(through_o, want, atol=1e-5)


def test_rotary_pairs_are_by_halves(jax_cpu, ref):
    """The program's one rotary function and the reference's turn the
    same pairs (i, i + R / 2)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import parts
    from ray_tpu.models import pangu_ultra_moe as m

    cfg = m.PanguUltraMoEConfig.tiny()
    x = jax.random.normal(jax.random.PRNGKey(4), (9, 3, 4))
    pos = jnp.arange(9, dtype=jnp.int32)[None]
    got = parts.rotate(x[None], *parts.rotary_at(pos, cfg))[0]
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref._rotate(x, cfg.rope_theta)),
                               atol=1e-6)
    # position 0 turns nothing; a later one turns dimension 0 with 2
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(x[0]))
    assert abs(float(got[5, 0, 1]) - float(x[5, 0, 1])) > 1e-4


@pytest.mark.parametrize("change", ["post_norms", "whole_row", "held",
                                    "shared"])
def test_the_reference_notices_each_mechanism(tiny, ref, change,
                                              monkeypatch):
    import jax
    import jax.numpy as jnp

    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, 24), 1,
                                cfg.vocab_size)
    want = ref.logits(params, tokens, cfg)
    other_cfg, other = cfg, params
    if change == "post_norms":
        monkeypatch.setattr(ref, "NO_POST_NORMS", True)
    elif change == "whole_row":
        monkeypatch.setattr(ref, "VALUE_IS_WHOLE_ROW", True)
    elif change == "held":
        other_cfg = dataclasses.replace(cfg, experts_held=(4, 2))
    else:
        other = dict(params, layers=[
            {k: (jnp.zeros_like(v) if k == "moe_shared_w_out" else v)
             for k, v in lp.items()} for lp in params["layers"]])
    got = ref.logits(other, tokens, other_cfg)
    assert float(jnp.abs(got - want).max()) > 1e-2, change


def test_the_four_holders_parts_add_up_to_the_uncut_layer(tiny, ref):
    """The parts that the 4 holders of 2 experts give, the shared expert
    and everything else counted once, are the uncut reference layer: the
    program's expert layer told which experts it holds, against the
    reference's feed-forward over all 8."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.pangu_ultra_moe import pangu_ultra_moe_init
    from ray_tpu.ops.moe import moe_dropless, moe_route

    cfg, _ = tiny
    whole = dataclasses.replace(cfg, experts_held=None)
    lp = pangu_ultra_moe_init(jax.random.PRNGKey(6), whole)["layers"][2]
    z = jax.random.normal(jax.random.PRNGKey(7), (12, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want = ref.ffn(z, lp, whole)
        weights, experts = moe_route(
            z, lp["moe_route_w"], None, cfg.top_k, norm_topk=True,
            scale=cfg.routed_scaling_factor)
        got = ref.shared_part(z, lp)
        pairs = 0
        for first in (0, 2, 4, 6):
            part, sizes = moe_dropless(
                z, weights, experts, lp["moe_gmm_w_in"][first:first + 2],
                lp["moe_gmm_w_out"][first:first + 2], dtype=jnp.float32,
                valid=jnp.ones((12,), bool), held=(first, 2))
            got = got + part
            pairs += int(sizes.sum())
    assert pairs == 12 * cfg.top_k  # every routed pair met ONE holder
    assert float(jnp.abs(want).max()) > 1e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_the_sliced_heads_logits_are_the_whole_heads_first_rows(tiny, ref):
    """A model that holds 64 rows of the vocabulary gives, for ids of the
    slice, the logits the whole head gives on those rows."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.pangu_ultra_moe import (
        pangu_ultra_moe_forward, pangu_ultra_moe_init,
    )

    cfg, _ = tiny
    whole = dataclasses.replace(cfg, vocab_size=512)
    params = pangu_ultra_moe_init(jax.random.PRNGKey(8), whole)
    sliced = dict(params, wte=params["wte"][:VOCAB_HELD],
                  lm_head=params["lm_head"][:, :VOCAB_HELD])
    tokens = jax.random.randint(jax.random.PRNGKey(9), (1, 20), 1,
                                VOCAB_HELD)
    with jax.default_matmul_precision("highest"):
        all_rows = pangu_ultra_moe_forward(params, tokens, whole)
        mine = pangu_ultra_moe_forward(sliced, tokens, cfg)
    assert mine.shape[-1] == VOCAB_HELD and all_rows.shape[-1] == 512
    np.testing.assert_allclose(np.asarray(mine),
                               np.asarray(all_rows[..., :VOCAB_HELD]),
                               atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(ref.logits(sliced, tokens, cfg)), np.asarray(mine),
        atol=1e-4)


# --------------------------------------------- the pool in planes, the kernel


def test_write_kv_lands_a_token_as_one_row(jax_cpu):
    """``write_kv`` over the one plane: a token's row reads back as ``[c |
    k_rope | zeros]``, each part at its stored width (tiny: 16 as 128, 4 as
    128; published: 512 | 64 | 64 zeros), nothing else touched."""
    import jax.numpy as jnp

    from ray_tpu.ops.kv_cache import write_kv
    from ray_tpu.ops.paged_attention import (
        latent_parts, latent_row, latent_row_width,
    )

    assert latent_row_width(16, 4) == 256 and latent_row_width(512, 64) == 640
    pool = jnp.full((2, 5, 4, 256), -1.0)
    c = jnp.arange(2 * 3 * 16, dtype=jnp.float32).reshape(2, 3, 16) + 1
    k_r = jnp.arange(2 * 3 * 4, dtype=jnp.float32).reshape(2, 3, 4) + 100
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    pos = jnp.asarray([[3, 4, 5], [0, 1, 2]], jnp.int32)
    valid = jnp.asarray([[True, True, False], [True, True, True]])
    pool, none = write_kv(pool, None, c, k_r, pos, tables, valid=valid,
                          layer=1)
    assert none is None
    assert float(pool[0].max()) == -1.0  # the other layer is untouched
    row = np.asarray(pool[1, 3, 2])
    np.testing.assert_array_equal(row[:16], np.asarray(c[1, 2]))
    np.testing.assert_array_equal(row[128:132], np.asarray(k_r[1, 2]))
    assert not row[16:128].any() and not row[132:].any()  # the padding
    np.testing.assert_array_equal(row, np.asarray(latent_row(c, k_r)[1, 2]))
    got_c, got_r = latent_parts(pool[1, 1, 3], 16, 4)
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(c[0, 0]))
    np.testing.assert_array_equal(np.asarray(got_r), np.asarray(k_r[0, 0]))
    np.testing.assert_array_equal(
        np.asarray(pool[1, 2, 0, :16]), np.asarray(c[0, 1]))
    assert float(pool[1, 2, 1].max()) == -1.0  # the masked token went to 0
    # decode: one row a sequence
    pool, _ = write_kv(pool, None, c[:, 0], k_r[:, 0],
                       jnp.asarray([6, 3], jnp.int32), tables, layer=0)
    np.testing.assert_array_equal(np.asarray(pool[0, 2, 2, 128:132]),
                                  np.asarray(k_r[0, 0]))
    # at the published widths a row is 512 | 64 | 64 zeros
    wide = np.asarray(latent_row(jnp.ones((1, 512)), jnp.ones((1, 64))))
    assert wide.shape == (1, 640)
    assert wide[0, :576].all() and not wide[0, 576:].any()


def _latent_walk_case(ctx, S, H, C=16, R=4, bs=16, NB=None, seed=0):
    """q ``[B, S, H, C + R]``, the pool (ONE plane: rows ``[c | k_rope]``,
    each part at whole lanes), the tables and positions of one row a context of ``ctx`` tokens plus ``S`` queries (a decode step,
    a chunk against a resident context, a fresh prompt at 0). The pool has
    twice the pages the tables name; every page OUTSIDE them, block 0
    among them, is poisoned. ``NB`` None: tables as wide as the longest row
    needs, in whole 128s."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import latent_row_width, plane_width

    B = len(ctx)
    need = [-(-(c + S) // bs) for c in ctx]
    if NB is None:
        NB = -(-(max(need) + 2) // 128) * 128
    blocks = 2 * (1 + sum(need))
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    pool = jax.random.normal(ks[0], (2, blocks, bs, latent_row_width(C, R)))
    # the row's padding lanes hold zeros, as the pool's do
    Cp = plane_width(C)
    pool = pool.at[..., C:Cp].set(0.0).at[..., Cp + R:].set(0.0)
    tables = np.zeros((B, NB), np.int32)
    perm = np.random.default_rng(seed).permutation(np.arange(1, blocks))
    pos = np.zeros((B, S), np.int32)
    at = 0
    for b in range(B):
        tables[b, :need[b]] = perm[at: at + need[b]]
        at += need[b]
        pos[b] = ctx[b] + np.arange(S)
    poisoned = np.ones(blocks, bool)
    poisoned[tables[tables > 0]] = False
    poison = jnp.asarray(poisoned)[None, :, None, None]
    lanes = jnp.arange(pool.shape[-1]) < Cp  # NaN in c's lanes, inf behind
    pool = jnp.where(poison, jnp.where(lanes, jnp.nan, jnp.inf), pool)
    q = jax.random.normal(ks[2], (B, S, H, C + R))
    return q, pool, jnp.asarray(tables), jnp.asarray(pos), C


def _latent_case(kind):
    """A tiny case (pages of 4 tokens, 4 heads, tables of 8 entries): a
    decode step, a chunk against a resident context, or a fresh prompt."""
    S = {"decode": 1, "chunk": 8, "fresh": 11}[kind]
    ctx = {"decode": [13, 30], "chunk": [21, 9], "fresh": [0, 0]}[kind]
    return _latent_walk_case(ctx, S, H=4, bs=4, NB=8)


@pytest.mark.parametrize("kind", ["decode", "chunk", "fresh"])
def test_latent_kernel_matches_xla_with_every_other_page_poisoned(
        jax_cpu, kind):
    """The kernel in the Pallas interpreter == the XLA path through
    ``gather_kv``, both reading the whole pools at a layer index; pages no
    table names hold NaN and inf (block 0, which padding entries name, is
    poisoned too: the walk never copies past a row's frontier)."""
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import latent_attention

    q, pool, tables, pos, C = _latent_case(kind)
    # the XLA path gathers a table's padding entries (block 0) and masks
    # them: give IT a clean block 0; the kernel gets the poisoned one
    want = latent_attention(
        q, pool.at[:, 0].set(0.0), tables, pos,
        latent_dim=C, scale=0.3, backend="xla", layer=1)
    got = latent_attention(q, pool, tables, pos, latent_dim=C,
                           scale=0.3, backend="pallas", layer=1)
    assert got.shape == (*q.shape[:3], C)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# where a row's context ends, in blocks of T tokens and pages of 16: the
# frontier's block is then walked by the loop of the frontier's bound (a
# part of a block) or as a whole block, and is a tile's first, second or
# third block
_ENDS = {
    "below_one_block": lambda T: T // 4 + 3,
    "in_a_blocks_first_page": lambda T: T + 5,
    "in_a_blocks_last_page": lambda T: 2 * T - 3,
    "at_a_blocks_edge": lambda T: 2 * T - 1,
}


@pytest.mark.parametrize("ends", sorted(_ENDS))
@pytest.mark.parametrize("kind", ["decode", "chunk"])
@pytest.mark.parametrize("heads", [32, 64, 128])
def test_latent_kernel_walks_whole_and_partial_blocks(jax_cpu, heads, kind,
                                                      ends):
    """The latent kernel at BOTH block lengths it chooses by a tile's rows
    (a decode tile of ``heads`` rows: ``_latent_tokens`` few-row length; a
    chunk's tile of 8 x ``heads`` rows: the many-row length), at the three
    cells' head counts, == the XLA path, every page outside the tables
    poisoned. The batch holds the context under test between two others,
    so a tile's first block is started under the tile BEFORE it, whole or
    in part, and the slot it lands in alternates with that tile's count of
    blocks."""
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import (
        _LATENT_Q_BLOCK, _latent_tokens, latent_attention,
    )

    S = 1 if kind == "decode" else _LATENT_Q_BLOCK
    T = _latent_tokens(S * heads)
    assert T == (1024 if kind == "decode" else 256)
    ctx = [T + 17, _ENDS[ends](T), 2 * T]
    q, pool, tables, pos, C = _latent_walk_case(ctx, S, heads)
    want = latent_attention(
        q, pool.at[:, 0].set(0.0), tables, pos,
        latent_dim=C, scale=0.3, backend="xla", layer=1)
    got = latent_attention(q, pool, tables, pos, latent_dim=C,
                           scale=0.3, backend="pallas", layer=1)
    assert got.shape == (*q.shape[:3], C)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(want).max()) > 0.05
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("kind", ["decode", "chunk"])
@pytest.mark.parametrize("heads", [32, 64, 128])
def test_one_plane_kernel_equals_the_float32_reference(jax_cpu, heads, kind):
    """The one-plane kernel == ``ops/kv_cache.paged_latent_attention`` in
    float32 at the three cells' head counts, decode rows and a chunk tile,
    at the PUBLISHED row (512 | 64 | 64 zeros) in pages of 16, over a batch
    whose tables hold: a row whose last block the frontier cuts, a padding
    row (every entry 0, position 0: it reads block 0 and is dropped), and
    two rows that SHARE their first block (a common prefix; the second
    goes on in a block of its own)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.kv_cache import paged_latent_attention, write_kv
    from ray_tpu.ops.paged_attention import latent_attention, latent_row_width

    C, R, bs, NB = 512, 64, 16, 8
    S = 1 if kind == "decode" else 8
    ks = jax.random.split(jax.random.PRNGKey(heads), 4)
    tables = np.zeros((4, NB), np.int32)
    tables[0, :5] = [7, 3, 9, 2, 5]      # 70 tokens: the fifth block is cut
    tables[2, :2] = [4, 6]               # rows 2 and 3 share block 4
    tables[3, :2] = [4, 8]
    first = np.asarray([70 - S, 0, 30 - S, 24 - S])  # row 1: padding
    pool = jnp.zeros((2, 10, bs, latent_row_width(C, R)))
    assert pool.shape[-1] == 640
    for b, n in ((0, 70), (2, 30), (3, 24)):
        # row 3 rewrites the shared block's first 16 rows with row 2's own
        src = 2 if b == 3 else b
        c = jax.random.normal(jax.random.fold_in(ks[0], src), (1, 70, C))
        k_r = jax.random.normal(jax.random.fold_in(ks[1], src), (1, 70, R))
        at = jnp.arange(16 if b == 3 else n)[None]
        pool, _ = write_kv(pool, None, c[:, :at.shape[1]],
                           k_r[:, :at.shape[1]], at,
                           jnp.asarray(tables[b:b + 1]), layer=1)
        if b == 3:  # its own tokens behind the shared prefix
            c = jax.random.normal(jax.random.fold_in(ks[0], 3), (1, 8, C))
            k_r = jax.random.normal(jax.random.fold_in(ks[1], 3), (1, 8, R))
            pool, _ = write_kv(pool, None, c, k_r, 16 + jnp.arange(8)[None],
                               jnp.asarray(tables[3:]), layer=1)
    q = jax.random.normal(ks[2], (4, S, heads, C + R))
    pos = jnp.asarray(first[:, None] + np.arange(S)[None], jnp.int32)
    pos = pos.at[1].set(0)
    want = paged_latent_attention(
        q, pool[1], jnp.asarray(tables), pos, latent_dim=C, scale=0.07)
    got = latent_attention(q, pool, jnp.asarray(tables), pos, latent_dim=C,
                           scale=0.07, backend="pallas", layer=1)
    assert got.shape == (4, S, heads, C) and got.dtype == jnp.float32
    assert bool(jnp.isfinite(got).all())
    real = np.asarray([0, 2, 3])
    assert float(jnp.abs(want[real]).max()) > 0.1
    np.testing.assert_allclose(
        np.asarray(got)[real], np.asarray(want)[real], atol=3e-5)


def test_a_latent_chunk_of_many_tiles_is_served(jax_cpu):
    """A chunk of 20 queries is three tiles of 8 (the last padded): each
    tile has its own frontier, the grid's second axis, and the tile after
    a row's last is the NEXT row's first."""
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import latent_attention

    q, pool, tables, pos, C = _latent_walk_case(
        [250, 0, 300, 5], 20, 64)
    want = latent_attention(
        q, pool.at[:, 0].set(0.0), tables, pos,
        latent_dim=C, scale=0.3, backend="xla", layer=1)
    got = latent_attention(q, pool, tables, pos, latent_dim=C,
                           scale=0.3, backend="pallas", layer=1)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` of a traced function, the ones inside its
    nested jits too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                found += _pallas_calls(inner)
    return found


def _loop_bodies(jaxpr):
    """The bodies of every loop in a kernel's jaxpr, nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    if eqn.primitive.name in ("scan", "while"):
                        found.append(inner)
                    found += _loop_bodies(inner)
    return found


def test_a_page_is_copied_once_for_keys_and_values(jax_cpu):
    """The kernel's own text, counted: wherever a block's copies are
    started (the call's first block; a whole block, one traced page
    unrolled as it is lowered; a block the frontier cuts, by a loop of the
    frontier's bound) a page gets ONE copy (the pool is one plane: a page
    ``[c | k_rope]`` is contiguous); a whole block is awaited once, a cut
    block once a copy. The tile's latent lanes then feed two of the three
    products (``q~ . c`` and ``p . c``), its rotary lanes the third, and the
    block's compute is in the text ONCE. Nothing copies a page a second
    time for the values, and no program holds a second pool."""
    import functools

    import jax

    from ray_tpu.ops.paged_attention import (
        LATENT_KERNEL_NAME, paged_latent_attention_pallas,
    )

    q, pool, tables, pos, C = _latent_case("chunk")
    jaxpr = jax.make_jaxpr(functools.partial(
        paged_latent_attention_pallas, latent_dim=C, scale=0.3, layer=1,
        interpret=False))(q, pool, tables, pos)
    calls = _pallas_calls(jaxpr.jaxpr)
    assert len(calls) == 1
    assert LATENT_KERNEL_NAME in str(calls[0].params["name"]) \
        or LATENT_KERNEL_NAME in str(calls[0].params)
    kernel = calls[0].params["jaxpr"]
    body = str(kernel)
    # three stretches start copies, each a loop over pages: ONE copy a page
    starts = [str(loop).count("dma_start") for loop in _loop_bodies(kernel)
              if "dma_start" in str(loop) and "dot_general" not in str(loop)]
    assert starts == [1, 1, 1], starts
    assert body.count("dma_start") == 3, body.count("dma_start")
    assert body.count("dma_wait") == 2, body.count("dma_wait")
    # the call's operands past the three scalar-prefetch words and q and
    # its positions: ONE pool
    assert len(calls[0].invars) == 6, calls[0].invars
    assert body.count("dot_general") == 3, body.count("dot_general")


def test_latent_calls_have_a_kernel_name_of_their_own(jax_cpu):
    """``paged_attention_latent`` holds ``paged_attention``: the accepted
    share metric finds it, and a trace parts it from the by-head calls."""
    from ray_tpu.ops.paged_attention import LATENT_KERNEL_NAME, _kernel_name

    assert LATENT_KERNEL_NAME == "paged_attention_latent"
    assert _kernel_name(None) in LATENT_KERNEL_NAME
    assert LATENT_KERNEL_NAME not in (_kernel_name(None), _kernel_name(8))


# ------------------------------------------------------- the cached steps


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_cached_steps_match_the_reference_logits(tiny, ref, backend):
    """The family's own step functions on a hand-built table: a fresh
    chunk, a chunk against the resident context, then decode through the
    pool in planes: logits against the reference's (expanded, no cache)
    at every step, to 1e-4."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.pangu_ultra_moe import (
        pangu_ultra_moe_decode_step, pangu_ultra_moe_init_state,
        pangu_ultra_moe_prefill,
    )

    cfg, params = tiny
    cfg = dataclasses.replace(cfg, attention_backend=backend)
    bs, NB = 4, 12
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(10), (40,), 1, cfg.vocab_size))
    want = np.asarray(ref.logits(params, jnp.asarray(tokens[None]), cfg))[0]
    k, v = jnp.zeros((cfg.n_layer, 1 + NB, bs, sum(
        stored for _, _, stored in cfg.kv_planes))), None
    state = pangu_ultra_moe_init_state(cfg, 2)
    slots = jnp.ones((1,), jnp.int32)
    tables = jnp.asarray(1 + np.arange(NB, dtype=np.int32)[None])
    done = 0
    with jax.default_matmul_precision("highest"):
        for n in (16, 11):
            chunk = np.zeros((1, 16), np.int32)
            chunk[0, :n] = tokens[done:done + n]
            out, k, v, state = pangu_ultra_moe_prefill(
                params, k, v, jnp.asarray(chunk), jnp.asarray([n]), tables,
                cfg, start=None if done == 0 else jnp.asarray([done]),
                state=state, slots=slots)
            done += n
            np.testing.assert_allclose(
                np.asarray(out)[0], want[done - 1], atol=1e-4)
        for pos in range(done, 40):
            out, k, v, state = pangu_ultra_moe_decode_step(
                params, k, v, jnp.asarray(tokens[pos:pos + 1]),
                jnp.asarray([pos]), tables, cfg, state=state, slots=slots)
            np.testing.assert_allclose(
                np.asarray(out)[0], want[pos], atol=1e-4)
    # all a layer kept of a token: its ONE row ``[c | k_rope]``, zeros in
    # the padding, and no second pool
    assert v is None and k.shape[-1] == 256
    assert float(jnp.abs(k[:, 1:11, :, :16]).min()) > 0
    assert float(jnp.abs(k[:, 1:11, :, 128:132]).min()) > 0
    assert float(jnp.abs(k[..., 16:128]).max()) == 0.0
    assert float(jnp.abs(k[..., 132:]).max()) == 0.0


# what the engine below decoded over the pool in TWO planes (the tree PR 53
# started from, both backends): one plane moves no token
_TWO_PLANES_DECODED = [
    [25, 21, 12, 44, 7, 45, 26, 21, 9, 63, 4, 58],
    [57, 57, 56, 35, 32, 35, 48, 36, 61, 61, 17, 35],
    [35, 31, 34, 35, 36, 36, 48, 5, 50, 35, 15, 32],
    [20, 25, 35, 54, 32, 54, 32, 57, 31, 48, 25, 42],
]


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_engine_serves_through_the_latent_pool(tiny, ref, backend):
    """``EngineConfig(model="pangu_ultra_moe")`` through the normal path:
    prompts shorter and longer than a chunk, greedy tokens the reference's
    own at every position (its logit within 1e-4 of the largest) and the
    ones the two-plane pool decoded, the pool reported as one plane,
    nothing held at the end."""
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
    assert described["kv_pool_shape"] == [3, 129, 4, 256]
    assert described["kv_pool"]["shapes"] == [[3, 129, 4, 256]]
    assert described["kv_pool"]["page_copies"] == 1
    (plane,) = described["kv_pool"]["planes"]
    assert (plane["name"], plane["width"], plane["stored_width"]) == (
        "latent+rope", 20, 256)
    assert [p["name"] for p in plane["parts"]] == ["latent", "rope"]
    assert [p["width"] for p in plane["parts"]] == [16, 4]
    assert engine.cache.v is None
    assert described["kv_layers"] == 3 and "kv_groups" not in described
    engine.shutdown()


def test_program_names_are_the_familys(jax_cpu):
    from ray_tpu.models import pangu_ultra_moe as m
    from ray_tpu.serve.llm import decode

    assert m.pangu_ultra_moe_prefill.__name__ == "pangu_ultra_moe_prefill"
    assert m.pangu_ultra_moe_decode_step.__name__ == \
        "pangu_ultra_moe_decode_step"
    fam = decode.get_family("pangu_ultra_moe")
    assert fam.verify_step is None and fam.state_rows is False
    assert decode.get_family("lfm2_moe").state_rows is True


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
    assert len(st["moe_pairs_by_expert"]) == 2  # the held experts
    assert sum(st["moe_pairs_by_expert"]) == \
        st["moe_pairs_held_prefill"] + st["moe_pairs_held_decode"]
    assert 0 < st["moe_expert_reads_decode"] <= 5 * layers * 2
    engine.shutdown()


def test_dispatch_spans_carry_kv_tokens_and_qk_pairs(tiny, monkeypatch):
    """``executor.dispatch``: ``kv_tokens`` on decode (each row's context
    in whole blocks), ``qk_pairs`` on the prefill kinds (each real query
    token at position p attends p + 1 positions)."""
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
               for p in _prompts([3, 21], seed=5)]
    _drive(engine, streams)
    prefills = [a for a in seen if a["kind"].startswith("prefill")]
    decodes = [a for a in seen if a["kind"] == "decode"]
    assert all("qk_pairs" in a for a in prefills)
    assert all("kv_tokens" in a and "qk_pairs" not in a for a in decodes)
    tri = lambda n: n * (n + 1) // 2
    # prompts of 3 and 21 in chunks of 16: every (query, key) pair once
    assert sum(a["qk_pairs"] for a in prefills) == tri(3) + tri(21)
    chunked = [a for a in prefills if a["kind"] == "prefill_chunk"]
    assert chunked and chunked[-1]["qk_pairs"] == tri(21) - tri(16)
    # the short row decodes alone first (context 4: one block of 4), the
    # long one joins behind its second chunk (context 22 and more: 24)
    assert decodes[0]["kv_tokens"] == 4
    assert max(a["kv_tokens"] for a in decodes) >= 4 + 24
    engine.shutdown()


# ---------------------------------- prefix reuse, a pause, what is refused


def test_a_prefix_hit_gives_the_uninterrupted_tokens(tiny):
    """A second request over the same 24-token prefix maps its blocks (a
    block's bytes are all a hit needs) and streams what a cold engine
    streams."""
    cfg, params = tiny
    shared = _prompts([24], seed=6)[0]
    a, b = shared + [7, 9, 11], shared + [5, 3]
    cold = _engine(cfg, params, prefix_caching=False)
    want = [cold.generate(p, max_new_tokens=10, temperature=0.0)
            for p in (a, b)]
    cold.shutdown()
    engine = _engine(cfg, params)
    got = [engine.generate(p, max_new_tokens=10, temperature=0.0)
           for p in (a, b)]
    st = engine.stats()
    assert got == want
    assert st["prefix_hit_tokens"] >= 24 and st["prefix_reuse"] is True
    assert st["prefix_reuse_why_not"] is None
    engine.shutdown()


def test_a_preempted_and_resumed_row_streams_what_an_unpaused_one_does(tiny):
    """Paused under an interactive flood (its blocks content-addressed,
    its allocation released) and resumed: the same greedy tokens."""
    cfg, params = tiny
    pre = dict(kv_pressure=0.5, queue_wait_s=0.05, resume_pressure=0.4)
    prompt = [5, 6, 7, 8, 9, 11]
    plain = _engine(cfg, params, num_blocks=24)
    want = plain.generate(prompt, max_new_tokens=16, temperature=0.0)
    plain.shutdown()
    engine = _engine(cfg, params, num_blocks=24, preemption=pre)
    batch = engine.submit(prompt, max_new_tokens=16, priority="batch",
                          temperature=0.0)
    engine.step()
    engine.step()
    flood = [engine.submit([13 + i, 4, 5], max_new_tokens=8,
                           priority="interactive", temperature=0.0)
             for i in range(6)]
    time.sleep(pre["queue_wait_s"] + 0.02)
    _drive(engine, [batch] + flood)
    st = engine.stats()
    assert st["preemptions_total"] >= 1 and st["preempted"] == 0
    assert list(batch) == want
    assert st["kv_used_blocks"] == 0
    engine.shutdown()


@pytest.mark.parametrize("option,match", [
    ({"speculative_k": 2}, "speculative_k: the family has no verify step"),
    ({"host_cache_bytes": 1 << 20}, "host_cache_bytes: the host tier's "
                                    "record"),
    ({"quantization": "int8"}, "quantization: a quantized pool's scale "
                               "planes"),
    ({"tp": 2}, "tp/fsdp/mesh: ShardedExecutor splits the pool along its "
                "head axis"),
    ({"fsdp": 2}, "one shared row has none"),
])
def test_what_a_latent_pool_cannot_carry_is_refused(tiny, option, match):
    cfg, params = tiny
    with pytest.raises(ValueError, match=match) as err:
        _engine(cfg, params, **option)
    assert "in planes" in str(err.value)
    assert "pangu_ultra_moe" in str(err.value)


def test_the_handoff_is_refused_by_the_records_reason(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompt = _prompts([20], seed=7)[0]
    with pytest.raises(ValueError, match="cannot say planes"):
        engine.export_prefix(prompt)
    with pytest.raises(ValueError, match="cannot say planes"):
        engine.adopt_prefix(prompt, [])
    engine.shutdown()


def test_counters_alone_refuse_nothing_of_their_own(jax_cpu):
    """A family whose ``state`` holds only counters is not refused what a
    per-sequence state is: only what the planes cannot carry."""
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    ask = EngineConfig(model="pangu_ultra_moe", preemption={})
    LLMEngine._refuse_for_state(ask, None, False, False, False, True)
    with pytest.raises(ValueError, match="state slot"):
        LLMEngine._refuse_for_state(ask, None, True, False, False, True)
    with pytest.raises(ValueError, match="in planes"):
        LLMEngine._refuse_for_state(
            EngineConfig(model="pangu_ultra_moe", speculative_k=1), None,
            False, False, False, True)


def test_widened_pipeline_matches_solo_runs(tiny):
    """ISSUE 33's schedule (conftest ``run_widened_schedule``) over the
    pool in planes: joins, finishes and a cancel in flight, the streams
    the bytes of solo runs."""
    from conftest import run_widened_schedule

    cfg, params = tiny
    run_widened_schedule(lambda **kw: _engine(cfg, params, **kw),
                         cfg.vocab_size)
