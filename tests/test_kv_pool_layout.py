"""Where the K/V pool rests (ISSUE 31, ISSUE 43), at the engine: EVERY
pool is stored lane-dense, ``[n_layer, num_blocks, block_size, Hkv * hd]``
(8 heads of 128 and 32 of 128 too, since ISSUE 43: one stored layout), and
nothing outside the device sees it (CPU, float32, tiny widths with heads of
64; the Pallas backend in the interpreter).

Per family: greedy streams identical under both attention backends over
the lane-dense pool; a block exported from it is byte-identical on the RTKV
wire to the same block from a pool laid by heads, and lands in either;
``describe()`` reports the stored shape.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

BS, BLOCKS = 8, 33


def _config(family):
    import jax.numpy as jnp

    if family == "gpt":
        from ray_tpu.models.gpt import GPTConfig

        # 2 heads of 64, MHA: GPT-2's page in small
        return dataclasses.replace(
            GPTConfig.tiny(), n_head=2, d_model=128, dtype=jnp.float32,
            attention="xla")
    from ray_tpu.models.lfm2_moe import Lfm2MoeConfig

    # GQA: 4 query heads on 2 K/V heads of 64, two attention layers
    return dataclasses.replace(
        Lfm2MoeConfig.tiny(), n_head=4, n_kv_head=2, head_dim=64,
        layer_types=("conv", "full_attention", "conv", "full_attention"),
        dtype=jnp.float32)


def _engine(family, cfg, params, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    return LLMEngine(
        EngineConfig(model=family, model_config=cfg, block_size=BS,
                     num_blocks=BLOCKS, max_batch_size=4, **kw),
        params=params, auto_step=False)


def _by_heads(engine):
    """Re-lay an engine's (still empty) pools BY HEADS, the layout before
    ISSUE 31: every path takes such a pool as handed in."""
    cfg = engine.cache.cfg
    shape = engine.cache.k.shape[:3] + (cfg.n_kv_head, cfg.head_dim)
    engine.cache.k = engine.cache.k.reshape(shape)
    engine.cache.v = engine.cache.v.reshape(shape)
    return engine


@pytest.fixture(scope="module")
def served(jax_cpu):
    """family -> (config, seeded params)."""
    import jax

    from ray_tpu.serve.llm.decode import get_family

    out = {}
    for family in ("gpt", "lfm2_moe"):
        cfg = _config(family)
        out[family] = cfg, get_family(family).init(
            jax.random.PRNGKey(31), cfg)
    return out


PROMPTS = [[3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37], [2, 4, 6]]


@pytest.mark.parametrize("family", ["gpt", "lfm2_moe"])
def test_streams_identical_across_backends_over_a_lane_dense_pool(
        served, family):
    cfg, params = served[family]
    n_kv = getattr(cfg, "n_kv_head", cfg.n_head)
    n_layer = getattr(cfg, "n_kv_layer", cfg.n_layer)
    outs = {}
    for backend in ("xla", "pallas"):
        eng = _engine(family, cfg, params, attention_backend=backend,
                      prefill_chunk_tokens=8)
        stored = [n_layer, BLOCKS, BS, n_kv * 64]
        assert list(eng.cache.k.shape) == stored
        assert eng.executor.describe()["kv_pool_shape"] == stored
        assert eng.stats()["executor"]["kv_pool_shape"] == stored
        outs[backend] = [eng.generate(p, max_new_tokens=10) for p in PROMPTS]
        eng.shutdown()
    assert outs["pallas"] == outs["xla"], family
    # and the stream does not depend on where the pool rests
    eng = _by_heads(_engine(family, cfg, params, attention_backend="xla",
                            prefill_chunk_tokens=8))
    assert eng.executor.describe()["kv_pool_shape"] == [
        n_layer, BLOCKS, BS, n_kv, 64]
    assert [eng.generate(p, max_new_tokens=10) for p in PROMPTS] \
        == outs["xla"]
    eng.shutdown()


@pytest.mark.parametrize("family", ["gpt", "lfm2_moe"])
def test_exported_blocks_are_the_same_bytes_whatever_the_pool(
        served, family):
    """What leaves the pool is by heads: the RTKV wire payload of a block
    is byte-identical from a lane-dense pool and from one laid by heads,
    and a payload lands in either (a lane-dense pool's export into a pool
    by heads, and back), leaving both pools with the same K/V."""
    from ray_tpu.serve.llm import kv_transfer

    cfg, params = served[family]
    lane = _engine(family, cfg, params)
    heads = _by_heads(_engine(family, cfg, params))
    assert lane.cache.k.ndim == 4 and heads.cache.k.ndim == 5
    for eng in (lane, heads):
        eng.generate(PROMPTS[0], max_new_tokens=6)
    # the blocks the one request just wrote, and left (block 0 is the sink)
    written = np.abs(np.asarray(lane.cache.k)[0]).reshape(BLOCKS, -1).sum(1)
    ids = [int(b) for b in np.flatnonzero(written) if b]
    assert len(ids) == 2, ids  # 11 + 5 positions
    layout = lane.kv_layout()
    assert layout == heads.kv_layout()
    wires = []
    for eng in (lane, heads):
        k, v = eng.executor.export_blocks(ids)
        assert k.shape == (layout.n_layer, len(ids), BS, layout.n_kv_head,
                           layout.head_dim)
        assert np.abs(k).sum() > 0
        wires.append(kv_transfer.pack_blocks(
            layout,
            [(bytes([i]) * 16, k[:, i], v[:, i]) for i in range(len(ids))],
            prefix_tokens=len(ids) * BS))
    assert wires[0] == wires[1]
    # each side's export lands in the OTHER side's pool, at other blocks
    _, _, records = kv_transfer.unpack_blocks(wires[0], expect=layout)
    k_new = np.stack([k for _, k, _ in records], axis=1)
    v_new = np.stack([v for _, _, v in records], axis=1)
    dst = [5, 6]
    for eng in (lane, heads):
        eng.executor.land_blocks(dst, k_new, v_new)
        k_back, v_back = eng.executor.export_blocks(dst)
        np.testing.assert_array_equal(k_back, k_new)
        np.testing.assert_array_equal(v_back, v_new)
    np.testing.assert_array_equal(
        np.asarray(lane.cache.k)[:, dst].reshape(k_new.shape),
        np.asarray(heads.cache.k)[:, dst])
    assert not lane.executor.export_blocks([])[0].size
    assert lane.executor.export_blocks([])[0].shape[2:] == k_new.shape[2:]
    for eng in (lane, heads):
        eng.shutdown()


# every family the engine serves, with what keeps its tiny engine small
# (tests/test_serve_llm_packed_prefill.py ``ROWS``)
FAMILIES = {
    "gpt": dict(num_blocks=33),
    "llama": dict(num_blocks=33),
    "lfm2_moe": dict(num_blocks=65),
    "laguna": dict(block_size=4, num_blocks=129, prefill_chunk_tokens=16,
                   length_buckets=(16, 32, 64, 128)),
    "evabyte": dict(block_size=4, num_blocks=257, prefill_chunk_tokens=16,
                    length_buckets=(16, 160)),
    "smallthinker": dict(block_size=4, num_blocks=129,
                         prefill_chunk_tokens=16,
                         length_buckets=(16, 32, 64, 128)),
    "pangu_ultra_moe": dict(block_size=4, num_blocks=129,
                            prefill_chunk_tokens=16,
                            length_buckets=(16, 32, 64, 128)),
    # a page of the cache IS the selection's block (8 in the tiny preset)
    "minicpm_sala": dict(block_size=8, num_blocks=129,
                         prefill_chunk_tokens=16,
                         length_buckets=(16, 32, 64, 128)),
    # planes AND state rows: one latent layer of four writes the pool
    "ling_hybrid": dict(block_size=8, num_blocks=129,
                        prefill_chunk_tokens=16,
                        length_buckets=(16, 32, 64, 128)),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_familys_pool_is_lane_dense(jax_cpu, family):
    """One stored layout: a token's heads one row in every family's pool
    (a latent family's ONE plane holds its row's parts side by side, and
    there is no second pool), and ``describe()`` says so."""
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    eng = LLMEngine(EngineConfig(model=family, max_batch_size=4,
                                 **FAMILIES[family]), auto_step=False)
    cache = eng.cache.cfg
    described = eng.executor.describe()
    assert described["kv_pool_shape"] == list(eng.cache.k.shape)
    assert eng.cache.k.ndim == 4
    if cache.planes:
        assert eng.cache.v is None
        assert eng.cache.k.shape[3] == sum(at for _, _, at in cache.planes)
        pool = described["kv_pool"]
        assert pool["page_copies"] == 1 and len(pool["planes"]) == 1
        assert pool["shapes"] == [list(eng.cache.k.shape)]
    else:
        assert eng.cache.v.shape == eng.cache.k.shape == (
            cache.n_layer, cache.num_blocks, cache.block_size,
            cache.n_kv_head * cache.head_dim)
        assert described["kv_pool"]["page_copies"] == 2
    assert eng.stats()["executor"]["kv_pool_shape"] == list(eng.cache.k.shape)
    eng.shutdown()


# (K/V heads, head size): the benchmark's cells at published widths, then
# ``tp`` = 4 shards of two of them and wide MHA rows
@pytest.mark.parametrize("name,heads", [
    ("mistral", (8, 128)),
    ("gpt2", (12, 64)),
    ("lfm2", (8, 64)),
    ("laguna", (8, 128)),
    ("evabyte", (32, 128)),
    ("smallthinker", (4, 128)),
    ("mistral-tp4-shard", (2, 128)),
    ("gpt2-tp4-shard", (3, 64)),
    ("mha-x128-of-64", (128, 64)),
    ("mha-x160-of-64", (160, 64)),
])
def test_published_widths_store_one_row(name, heads):
    """``pool_shape`` has one answer, a token's heads a row, and the cache
    manager's pool is that shape."""
    from ray_tpu.ops.paged_attention import pool_shape
    from ray_tpu.serve.llm.kv_cache import KVCacheConfig, PagedKVCache

    n_kv, hd = heads
    assert pool_shape(6, 4097, 16, n_kv, hd) == (6, 4097, 16, n_kv * hd)
    cache = PagedKVCache(KVCacheConfig(
        n_layer=1, n_kv_head=n_kv, head_dim=hd, num_blocks=3, block_size=16))
    assert cache.pool_shape() == (1, 3, 16, n_kv * hd)
    assert cache.k.shape == cache.v.shape == cache.pool_shape()
