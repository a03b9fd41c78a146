"""Fused Pallas paged-attention kernels (ISSUE 8 decode, ISSUE 18 prefill).

Two layers of pinning on CPU (the kernels run in Pallas interpret mode —
real kernel code, HLO-interpreted):

- KERNEL: ``paged_attention_pallas`` vs the XLA ``paged_attention``
  formulation on one shared paged pool — contiguous and shuffled block
  tables, GQA ratios 1/2/4, ragged positions with block-0-padded
  tables, eager and jitted. ``paged_prefill_attention_pallas`` the same
  way against ``mha_reference`` (fresh prompts) and the XLA
  ``paged_prefill_attention`` (ragged chunk starts at true positions,
  verify-window per-column positions, q-block padding), plus
  sliding-window equivalence to a masked dense reference on all three
  implementations.
- ENGINE: ``attention_backend="pallas"`` produces byte-identical token
  streams to ``"xla"`` — greedy and temperature/top-p, gpt and llama,
  fresh prefill, chunked prefill and speculative verify,
  SingleDeviceExecutor and tp/fsdp ShardedExecutor — and the
  compile-kind contract is frozen across backends (same signature set,
  no new kinds).
"""
from __future__ import annotations

import dataclasses

import pytest


@pytest.fixture(autouse=True)
def _cpu(jax_cpu):
    return jax_cpu


def _f32(cfg):
    import jax.numpy as jnp

    return dataclasses.replace(cfg, dtype=jnp.float32, attention="xla")


def _model_config(family="llama"):
    if family == "gpt":
        from ray_tpu.models.gpt import GPTConfig

        return _f32(GPTConfig.tiny())
    from ray_tpu.models.llama import LlamaConfig

    return _f32(LlamaConfig.tiny())


def _engine(family, mc, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 64)
    return LLMEngine(
        EngineConfig(model=family, model_config=mc, **kw), auto_step=False
    )


def _pool(key, B, lengths, Hkv, hd, bs, NB, shuffle):
    """``_prefill_pool``'s paged pool for decode: returns (k_layer,
    v_layer, tables, positions) with positions = lengths - 1 (the decode
    query position)."""
    import jax.numpy as jnp

    k_layer, v_layer, tables, _, _ = _prefill_pool(
        key, lengths, Hkv, hd, bs, NB, shuffle
    )
    return k_layer, v_layer, tables, jnp.asarray(lengths, jnp.int32) - 1


# --------------------------------------------------- kernel vs XLA path


@pytest.mark.parametrize("gqa", [1, 2, 4])
@pytest.mark.parametrize("shuffle", [False, True])
def test_pallas_kernel_matches_xla(jax_cpu, gqa, shuffle):
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.kv_cache import paged_attention
    from ray_tpu.ops.paged_attention import paged_attention_pallas

    key = jax.random.PRNGKey(100 + gqa)
    lengths = [1, 6, 18, 32]
    Hkv, hd, bs, NB = 2, 32, 8, 4
    k_layer, v_layer, tables, positions = _pool(
        key, len(lengths), lengths, Hkv, hd, bs, NB, shuffle
    )
    q = jax.random.normal(
        jax.random.fold_in(key, 9), (len(lengths), Hkv * gqa, hd)
    )
    ref = paged_attention(q, k_layer, v_layer, tables, positions)
    out = paged_attention_pallas(q, k_layer, v_layer, tables, positions)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5, (gqa, shuffle)


def test_pallas_kernel_under_jit(jax_cpu):
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.kv_cache import paged_attention
    from ray_tpu.ops.paged_attention import decode_attention

    key = jax.random.PRNGKey(5)
    lengths = [9, 24]
    k_layer, v_layer, tables, positions = _pool(
        key, 2, lengths, 2, 16, 8, 4, shuffle=True
    )
    q = jax.random.normal(jax.random.fold_in(key, 9), (2, 4, 16))
    jitted = jax.jit(
        lambda *a: decode_attention(*a, backend="pallas")
    )
    out = jitted(q, k_layer, v_layer, tables, positions)
    ref = paged_attention(q, k_layer, v_layer, tables, positions)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_backend_resolution_and_validation(jax_cpu):
    from ray_tpu.ops.paged_attention import resolve_backend
    from ray_tpu.serve.config import ModelParallelConfig
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    # CPU under tier-1: "auto" is the XLA formulation (the kernel would
    # interpret — correct but slow; it is opted into explicitly)
    assert resolve_backend("auto") == "xla"
    assert resolve_backend("xla") == "xla"
    assert resolve_backend("pallas") == "pallas"
    with pytest.raises(ValueError):
        resolve_backend("cudnn")
    with pytest.raises(ValueError):
        ModelParallelConfig(attention_backend="cudnn")
    with pytest.raises(ValueError):
        LLMEngine(
            EngineConfig(
                model="llama",
                model_config=_model_config(),
                attention_backend="cudnn",
            ),
            auto_step=False,
        )


# ------------------------------------------- prefill kernel vs references


def _prefill_pool(key, lengths, Hkv, hd, bs, NB, shuffle, quant=None):
    """A paged pool with ragged sequences: noise-filled blocks (block 0 is
    the garbage sink), tables padded with 0 past each length, physical
    ids optionally shuffled. Returns (k_layer, v_layer, tables, kc, vc):
    the dense per-row contexts (kc, vc) are what was written into the
    paged layers, so tests can build dense references without
    re-gathering. ``quant`` makes the pool a ``QuantizedKV`` of
    that kind (noise data under noise scales; ``write_kv`` quantizes what
    it writes). A length of 0 is a padding row: an all-zero table. The
    layers are in the shape the cache manager STORES them in
    (``pool_shape``): by heads where ``[Hkv, hd]`` is whole (8, 128)
    tiles, lane-dense ``[num_blocks, bs, Hkv * hd]`` everywhere else:
    every case below that ran the one-page walk (2 heads of 16 or 32,
    heads of 64, 12 heads, a ``tp`` shard's 2) runs the compute-block
    kernel over a lane-dense pool."""
    import random as _random

    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.kv_cache import write_kv
    from ray_tpu.ops.paged_attention import pool_shape
    from ray_tpu.ops.quantization import QuantizedKV, quant_dtype

    B = len(lengths)
    num_blocks = 1 + B * NB
    ids = list(range(1, num_blocks))
    if shuffle:
        _random.Random(7).shuffle(ids)
    rows, nxt = [], 0
    for L in lengths:
        need = -(-L // bs)
        rows.append(ids[nxt:nxt + need] + [0] * (NB - need))
        nxt += need
    tables = jnp.asarray(rows, jnp.int32)
    T = NB * bs
    kc = jax.random.normal(jax.random.fold_in(key, 1), (B, T, Hkv, hd))
    vc = jax.random.normal(jax.random.fold_in(key, 2), (B, T, Hkv, hd))
    shape = (num_blocks, bs, Hkv, hd)
    k_layer = jax.random.normal(jax.random.fold_in(key, 3), shape)
    v_layer = jax.random.normal(jax.random.fold_in(key, 4), shape)
    stored = pool_shape(1, num_blocks, bs, Hkv, hd)[1:]
    if quant is not None:
        k_layer, v_layer = (
            QuantizedKV(
                (40.0 * x).astype(quant_dtype(quant)).reshape(stored),
                0.01 + jnp.abs(x[..., 0]),
            )
            for x in (k_layer, v_layer)
        )
    else:
        k_layer, v_layer = k_layer.reshape(stored), v_layer.reshape(stored)
    assert stored == (num_blocks, bs, Hkv * hd)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    valid = pos < jnp.asarray(lengths, jnp.int32)[:, None]
    k_layer, v_layer = write_kv(
        k_layer, v_layer, kc, vc, pos, tables, valid=valid
    )
    return k_layer, v_layer, tables, kc, vc


@pytest.mark.parametrize("gqa", [1, 2, 4])
@pytest.mark.parametrize("shuffle", [False, True])
def test_prefill_kernel_matches_mha_reference(jax_cpu, gqa, shuffle):
    """Fresh whole-prompt prefill (positions 0..S-1, everything cached)
    equals causal dense attention over the chunk."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import mha_reference
    from ray_tpu.ops.paged_attention import paged_prefill_attention_pallas

    key = jax.random.PRNGKey(200 + gqa)
    B, S, Hkv, hd, bs, NB = 2, 24, 2, 32, 8, 4
    k_layer, v_layer, tables, kc, vc = _prefill_pool(
        key, [S] * B, Hkv, hd, bs, NB, shuffle
    )
    q = jax.random.normal(jax.random.fold_in(key, 9), (B, S, Hkv * gqa, hd))
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    out = paged_prefill_attention_pallas(
        q, k_layer, v_layer, tables, positions
    )
    ref = mha_reference(
        q.transpose(0, 2, 1, 3),
        kc[:, :S].transpose(0, 2, 1, 3),
        vc[:, :S].transpose(0, 2, 1, 3),
        causal=True,
    ).transpose(0, 2, 1, 3)
    assert out.shape == ref.shape
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5, (gqa, shuffle)


@pytest.mark.parametrize("gqa", [1, 2, 4])
@pytest.mark.parametrize("shuffle", [False, True])
def test_prefill_kernel_ragged_starts_match_xla(jax_cpu, gqa, shuffle):
    """Chunked prefill: each row's chunk sits at a different TRUE start
    over a different amount of resident context."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.kv_cache import paged_prefill_attention
    from ray_tpu.ops.paged_attention import paged_prefill_attention_pallas

    key = jax.random.PRNGKey(300 + gqa)
    lengths = [6, 17, 29]
    S, Hkv, hd, bs, NB = 6, 2, 16, 8, 4
    k_layer, v_layer, tables, _, _ = _prefill_pool(
        key, lengths, Hkv, hd, bs, NB, shuffle
    )
    # the chunk is the LAST S cached positions of each row
    starts = jnp.asarray([L - S for L in lengths], jnp.int32)
    positions = starts[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    q = jax.random.normal(
        jax.random.fold_in(key, 9), (len(lengths), S, Hkv * gqa, hd)
    )
    ref = paged_prefill_attention(q, k_layer, v_layer, tables, positions)
    out = paged_prefill_attention_pallas(
        q, k_layer, v_layer, tables, positions
    )
    assert out.shape == ref.shape and out.dtype == ref.dtype
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5, (gqa, shuffle)


@pytest.mark.parametrize("window", [1, 4, 16])
def test_prefill_window_matches_masked_dense(jax_cpu, window, monkeypatch):
    """Sliding-window attention: all three implementations — the pallas
    kernel (skips kv-blocks below the window floor), the dense XLA path,
    and the streaming XLA path — equal a dense reference with the mask
    ``pos - window < t <= pos`` applied explicitly."""
    import jax
    import jax.numpy as jnp
    import ray_tpu.ops.kv_cache as kvc
    from ray_tpu.ops.paged_attention import paged_prefill_attention_pallas

    key = jax.random.PRNGKey(400 + window)
    lengths = [11, 30]
    S, Hkv, hd, bs, NB = 8, 2, 16, 8, 4
    k_layer, v_layer, tables, kc, vc = _prefill_pool(
        key, lengths, Hkv, hd, bs, NB, shuffle=True
    )
    starts = jnp.asarray([L - S for L in lengths], jnp.int32)
    positions = starts[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    B = len(lengths)
    q = jax.random.normal(jax.random.fold_in(key, 9), (B, S, Hkv * 2, hd))

    # dense reference over the raw contexts with the window mask explicit
    T = NB * bs
    scale = 1.0 / (hd ** 0.5)
    qg = q.reshape(B, S, Hkv, 2, hd)
    logits = jnp.einsum("bshgd,bthd->bshgt", qg, kc) * scale
    t = jnp.arange(T, dtype=jnp.int32)[None, None, :]
    mask = (t <= positions[:, :, None]) & (
        t > positions[:, :, None] - window
    )
    logits = jnp.where(mask[:, :, None, None, :], logits, -1e30)
    ref = jnp.einsum(
        "bshgt,bthd->bshgd", jax.nn.softmax(logits, axis=-1), vc
    ).reshape(B, S, Hkv * 2, hd)

    out_k = paged_prefill_attention_pallas(
        q, k_layer, v_layer, tables, positions, window=window
    )
    out_d = kvc.paged_prefill_attention(
        q, k_layer, v_layer, tables, positions, window=window
    )
    monkeypatch.setattr(kvc, "PREFILL_STREAM_MIN_T", 1)
    out_s = kvc.paged_prefill_attention(
        q, k_layer, v_layer, tables, positions, window=window
    )
    for name, out in (("pallas", out_k), ("dense", out_d), ("stream", out_s)):
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5, (name, window)


def test_prefill_verify_window_per_column_positions(jax_cpu):
    """Speculative verify windows: per-row starts AND per-column true
    positions, padding columns clamped to position 0 exactly as the
    models pass them."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.kv_cache import paged_prefill_attention
    from ray_tpu.ops.paged_attention import paged_prefill_attention_pallas

    key = jax.random.PRNGKey(500)
    W, Hkv, hd, bs, NB = 4, 2, 16, 8, 4
    starts = jnp.asarray([3, 11], jnp.int32)
    draft_len = jnp.asarray([1, 3], jnp.int32)
    lengths = [int(s) + int(d) + 1 for s, d in zip(starts, draft_len)]
    k_layer, v_layer, tables, _, _ = _prefill_pool(
        key, lengths, Hkv, hd, bs, NB, shuffle=True
    )
    pos = starts[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
    valid = jnp.arange(W, dtype=jnp.int32)[None, :] <= draft_len[:, None]
    positions = jnp.where(valid, pos, 0)
    q = jax.random.normal(jax.random.fold_in(key, 9), (2, W, Hkv * 2, hd))
    ref = paged_prefill_attention(q, k_layer, v_layer, tables, positions)
    out = paged_prefill_attention_pallas(
        q, k_layer, v_layer, tables, positions
    )
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_prefill_kernel_qblock_padding_and_jit(jax_cpu):
    """A q_block that does not divide S exercises the pad-and-slice path
    (multiple q-blocks, per-block frontiers), and the dispatcher stays
    jittable with the backend static."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.kv_cache import paged_prefill_attention
    from ray_tpu.ops.paged_attention import (
        paged_prefill_attention_pallas, prefill_attention,
    )

    key = jax.random.PRNGKey(600)
    lengths = [12, 27]
    S, Hkv, hd, bs, NB = 12, 2, 16, 8, 4
    k_layer, v_layer, tables, _, _ = _prefill_pool(
        key, lengths, Hkv, hd, bs, NB, shuffle=True
    )
    starts = jnp.asarray([L - S for L in lengths], jnp.int32)
    positions = starts[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    q = jax.random.normal(jax.random.fold_in(key, 9), (2, S, Hkv * 2, hd))
    ref = paged_prefill_attention(q, k_layer, v_layer, tables, positions)
    out = paged_prefill_attention_pallas(
        q, k_layer, v_layer, tables, positions, q_block=5
    )
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5
    jitted = jax.jit(lambda *a: prefill_attention(*a, backend="pallas"))
    out_j = jitted(q, k_layer, v_layer, tables, positions)
    assert float(jnp.max(jnp.abs(out_j - ref))) < 2e-5


# What a compute block of P pages adds to the walk (bs 8 and a table of 16
# or more entries give P = 16: 128 tokens a block; 8 heads of 128 is the
# page stored by heads, every other a lane-dense page, a head a lane slice
# of it; 3 heads of 64 and 2 of 32 are rows of no whole lanes, padded for
# the kernel). Each case:
# (lengths, chunk S, table width NB, pool kwargs, kernel kwargs); the chunk
# is the LAST S cached positions of each row, a length of 0 a padding row.
_BLOCK_EDGES = {
    # the last block holds 4 of 16 entries
    "table-not-multiple-of-P": ([140, 155, 9], 1, 20, {}, {}),
    # a context ending one short of, on, and one past a block boundary
    "context-ends-on-boundary": ([127, 128, 129, 256], 1, 32, {}, {}),
    "context-ends-inside-block": ([41, 200, 3], 1, 32, {}, {}),
    "shuffled-pages": ([140, 250, 33], 1, 32, {"shuffle": True}, {}),
    "padding-rows-zero-table": ([0, 150, 0, 12], 1, 32, {}, {}),
    # the floor of the earliest query lies in the middle of block 1
    "window-floor-inside-block": (
        [200, 255], 6, 32, {"shuffle": True}, {"window": 20}
    ),
    "window-of-one-page": ([200, 141], 1, 32, {}, {"window": 5}),
    # a chunk that starts at token 138 of 128-token blocks, in two q-blocks
    "chunk-start-inside-block": (
        [150, 250], 12, 32, {"shuffle": True}, {"q_block": 8}
    ),
    "int8-scale-planes": (
        [140, 9, 250], 1, 32, {"shuffle": True, "quant": "int8"}, {}
    ),
    "int8-chunk-not-multiple-of-P": (
        [150, 60], 5, 20, {"quant": "int8"}, {}
    ),
    "fp8-scale-planes": ([33, 180], 1, 32, {"quant": "fp8"}, {}),
    # a table narrower than a block: P = 1, and 2 with a third entry
    "P-forced-to-1": ([5, 8], 1, 1, {}, {}),
    "P-forced-to-2": ([5, 16, 19], 3, 3, {}, {}),
    # pages that are no whole tiles by heads: stored lane-dense
    "unaligned-heads-one-page-walk": (
        [140, 9], 1, 20, {"shuffle": True, "Hkv": 2, "hd": 32}, {}
    ),
    "unaligned-heads-int8-chunk": (
        [40, 150], 4, 20, {"quant": "int8", "Hkv": 2, "hd": 32}, {}
    ),
    # GPT-2's page (12 heads of 64: a row of 768 lanes), lfm2's (8 of 64)
    "heads-of-64-x12-decode": (
        [140, 9, 250], 1, 32, {"shuffle": True, "Hkv": 12, "hd": 64}, {}
    ),
    "heads-of-64-x12-chunk": (
        [150, 250], 12, 32, {"shuffle": True, "Hkv": 12, "hd": 64},
        {"q_block": 8}
    ),
    "heads-of-64-x8-window": (
        [200, 255], 6, 32, {"shuffle": True, "Hkv": 8, "hd": 64},
        {"window": 20}
    ),
    "heads-of-64-x8-int8": (
        [140, 9, 250], 1, 32, {"quant": "int8", "Hkv": 8, "hd": 64}, {}
    ),
    "heads-of-64-x12-fp8-chunk": (
        [150, 60], 5, 20, {"quant": "fp8", "Hkv": 12, "hd": 64}, {}
    ),
    # a tp shard's 2 heads of 128 (a row of 256 lanes) and 3 of 64 (192:
    # no whole lanes, the slab padded for the kernel)
    "tp-shard-2-heads-verify": (
        [40, 150], 4, 20, {"shuffle": True, "Hkv": 2, "hd": 128}, {}
    ),
    "tp-shard-3-heads-of-64": (
        [140, 250], 1, 32, {"shuffle": True, "Hkv": 3, "hd": 64}, {}
    ),
    "tp-shard-3-heads-of-64-int8": (
        [40, 150], 4, 20, {"quant": "int8", "Hkv": 3, "hd": 64}, {}
    ),
}


@pytest.mark.parametrize("edge", sorted(_BLOCK_EDGES))
def test_kernel_compute_block_edges_match_xla(jax_cpu, edge):
    """The edges a compute block of several pages creates, each against
    the XLA formulation on the same pool, at the same tolerance as the
    one-page cases above."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.kv_cache import paged_prefill_attention
    from ray_tpu.ops.paged_attention import (
        _block_tokens, _compute_block, paged_prefill_attention_pallas,
    )

    lengths, S, NB, pool_kw, kernel_kw = _BLOCK_EDGES[edge]
    pool_kw = dict(pool_kw)
    Hkv, hd = pool_kw.pop("Hkv", 8), pool_kw.pop("hd", 128)
    bs, G = 8, 2
    key = jax.random.PRNGKey(sum(map(ord, edge)))
    k_layer, v_layer, tables, _, _ = _prefill_pool(
        key, lengths, Hkv, hd, bs, NB, pool_kw.pop("shuffle", False),
        **pool_kw,
    )
    # the block the case was built for is the one the function derives:
    # the tokens a few-row tile aims at (128 over 8 heads of 128 in
    # float32, a row of 4 KB; 512 over a quantized KB) in pages of 8, or
    # what the table is wide enough for
    data = k_layer.data if "quant" in pool_kw else k_layer
    P, _ = _compute_block(
        data.shape[1:], Hkv, hd, S * G, NB, jnp.float32, data.dtype,
        "quant" in pool_kw,
    )
    row_bytes = Hkv * hd * data.dtype.itemsize
    aim = _block_tokens(S * G, row_bytes)
    # the most tokens, a power of two, whose K tile is within half a MiB
    most = 1 << ((512 * 1024 // row_bytes).bit_length() - 1)
    assert aim == min(512, max(128, most)), (edge, aim)
    assert P == min(aim // bs, 1 << (NB.bit_length() - 1)), (edge, P)
    starts = jnp.asarray([max(L - S, 0) for L in lengths], jnp.int32)
    positions = starts[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    positions = jnp.where(
        jnp.asarray(lengths)[:, None] > 0, positions, 0
    )
    q = jax.random.normal(
        jax.random.fold_in(key, 9), (len(lengths), S, Hkv * G, hd)
    )
    window = kernel_kw.get("window")
    ref = paged_prefill_attention(
        q, k_layer, v_layer, tables, positions, window=window
    )
    out = paged_prefill_attention_pallas(
        q, k_layer, v_layer, tables, positions, **kernel_kw
    )
    assert out.shape == ref.shape and out.dtype == ref.dtype
    live = jnp.asarray(lengths)[:, None, None, None] > 0
    assert float(jnp.max(jnp.abs(jnp.where(live, out - ref, 0.0)))) < 2e-5


@pytest.mark.parametrize("edge", [
    "shuffled-pages", "int8-scale-planes", "window-floor-inside-block",
    "chunk-start-inside-block", "unaligned-heads-one-page-walk",
    "unaligned-heads-int8-chunk", "heads-of-64-x12-chunk",
    "heads-of-64-x8-int8", "tp-shard-3-heads-of-64",
])
def test_kernel_reads_the_whole_pool_at_a_layer(jax_cpu, edge):
    """``(pool, layer)``: the kernel handed the WHOLE multi-layer pool, by
    heads or lane-dense, and a layer index (static, and traced under jit through the
    dispatchers) give what the XLA formulation gives on ``pool[layer]``,
    and bit for bit what the same kernel gives on that slab alone;
    ``write_kv`` at a layer writes that slab's rows and no other
    layer's."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.kv_cache import paged_prefill_attention, write_kv
    from ray_tpu.ops.paged_attention import (
        decode_attention, paged_prefill_attention_pallas, prefill_attention,
    )

    lengths, S, NB, pool_kw, kernel_kw = _BLOCK_EDGES[edge]
    pool_kw = dict(pool_kw)
    Hkv, hd = pool_kw.pop("Hkv", 8), pool_kw.pop("hd", 128)
    bs, G, L = 8, 2, 3
    key = jax.random.PRNGKey(sum(map(ord, edge)))
    slabs = [
        _prefill_pool(jax.random.fold_in(key, 50 + i), lengths, Hkv, hd, bs,
                      NB, pool_kw.get("shuffle", False),
                      quant=pool_kw.get("quant"))
        for i in range(L)]
    tables = slabs[0][2]
    stack = lambda *a: jnp.stack(a)
    k_pool = jax.tree.map(stack, *[s[0] for s in slabs])
    v_pool = jax.tree.map(stack, *[s[1] for s in slabs])
    starts = jnp.asarray([max(n - S, 0) for n in lengths], jnp.int32)
    positions = starts[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    q = jax.random.normal(
        jax.random.fold_in(key, 9), (len(lengths), S, Hkv * G, hd))
    window = kernel_kw.get("window")
    for layer in (0, L - 1):
        k_layer, v_layer = slabs[layer][:2]
        ref = paged_prefill_attention(
            q, k_layer, v_layer, tables, positions, window=window)
        out = paged_prefill_attention_pallas(
            q, k_pool, v_pool, tables, positions, layer=layer, **kernel_kw)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5, (edge, layer)
        alone = paged_prefill_attention_pallas(
            q, k_layer, v_layer, tables, positions, **kernel_kw)
        assert jnp.array_equal(out, alone), (edge, layer)
    # a traced layer, through the dispatchers the cached step calls
    jitted = jax.jit(lambda layer, backend: prefill_attention(
        q, k_pool, v_pool, tables, positions, window=window,
        backend=backend, layer=layer), static_argnums=1)
    for backend in ("pallas", "xla"):
        got = jitted(jnp.int32(1), backend)
        ref = paged_prefill_attention(
            q, *slabs[1][:2], tables, positions, window=window)
        assert float(jnp.max(jnp.abs(got - ref))) < 2e-5, (edge, backend)
    if window is None:
        got = jax.jit(lambda layer: decode_attention(
            q[:, -1], k_pool, v_pool, tables, positions[:, -1],
            backend="pallas", layer=layer))(jnp.int32(2))
        ref = paged_prefill_attention(
            q[:, -1:], *slabs[2][:2], tables, positions[:, -1:])[:, 0]
        assert float(jnp.max(jnp.abs(got - ref))) < 2e-5, edge
    # the scatter: rows at [layer, blk, slot], every other layer untouched
    rows = jax.random.normal(
        jax.random.fold_in(key, 10), (len(lengths), S, Hkv, hd))
    valid = jnp.asarray(lengths)[:, None] > jnp.zeros((1, S), jnp.int32)
    k_new, v_new = jax.jit(lambda layer: write_kv(
        k_pool, v_pool, rows, 2 * rows, positions, tables, valid=valid,
        layer=layer))(jnp.int32(1))
    k_slab, v_slab = jax.jit(lambda k, v: write_kv(
        k, v, rows, 2 * rows, positions, tables, valid=valid))(*slabs[1][:2])
    for pool, new, slab in ((k_pool, k_new, k_slab), (v_pool, v_new, v_slab)):
        for was, now, want in zip(*map(jax.tree.leaves, (pool, new, slab))):
            assert jnp.array_equal(now[1], want)
            assert jnp.array_equal(now[0], was[0])
            assert jnp.array_equal(now[2], was[2])


# The kernel over the ONE stored layout against the XLA formulation: a
# compute block is the lane-dense ``[tokens, Hkv * hd]`` tile and a head a
# static lane slice of it, a score and a value product a head, whatever the
# tile's rows: (Hkv, G, hd, chunk S, kernel kwargs, pool kwargs). The groups
# are the cells' (1, 4, 6, 7, 8), the heads 64 and 128 (and 32, 96: a head
# that is no whole lane tile, or half of one); every case has a ragged last
# block and a padding row.
_PRODUCTS = {
    "g1-x32-hd128-evabyte": (32, 1, 128, 1, {}, {}),
    "g4-x8-hd128-mistral": (8, 4, 128, 1, {}, {}),
    "g6-x8-hd128-laguna-full": (8, 6, 128, 1, {}, {}),
    "g7-x4-hd128-smallthinker": (4, 7, 128, 1, {}, {}),
    "g8-x8-hd128-laguna-window": (
        8, 8, 128, 1, {"window": 20}, {}),
    "g7-x4-hd128-window-verify": (
        4, 7, 128, 3, {"window": 37}, {"shuffle": True}),
    "g4-x8-hd128-int8": (8, 4, 128, 1, {}, {"quant": "int8"}),
    "g7-x4-hd128-fp8": (4, 7, 128, 1, {}, {"quant": "fp8"}),
    "g1-x12-hd64-gpt2": (12, 1, 64, 1, {}, {}),
    "g4-x8-hd64-lfm2": (8, 4, 64, 1, {}, {}),
    "g6-x8-hd64": (8, 6, 64, 1, {}, {"shuffle": True}),
    "g7-x4-hd64-window": (4, 7, 64, 1, {"window": 20}, {}),
    "g8-x8-hd64-window-verify": (
        8, 8, 64, 2, {"window": 37}, {"shuffle": True}),
    "g4-x8-hd64-int8": (8, 4, 64, 1, {}, {"quant": "int8"}),
    "g7-x4-hd64-fp8": (4, 7, 64, 1, {}, {"quant": "fp8"}),
    "g1-x12-hd64-int8-verify": (
        12, 1, 64, 5, {}, {"quant": "int8"}),
    "g2-x2-hd32-tiny": (2, 2, 32, 1, {}, {}),
    # all heads' rows together one MXU pass (8 x 16 = 128) and past it (136)
    "edge-128-rows": (8, 4, 64, 4, {}, {"shuffle": True}),
    "edge-136-rows": (8, 1, 64, 17, {}, {"shuffle": True}),
    "edge-136-rows-int8": (
        8, 1, 64, 17, {}, {"quant": "int8"}),
    # a q tile of a prefill chunk: many rows a head
    "g4-x8-hd64-chunk": (8, 4, 64, 40, {"q_block": 32}, {}),
    # two q tiles of a chunk, each with its own frontier
    "g2-x4-hd64-two-tiles": (4, 2, 64, 12, {"q_block": 8}, {}),
    # a head that does not tile a lane tile
    "hd-96-no-lane-tile": (2, 2, 96, 1, {}, {}),
}


@pytest.mark.parametrize("case", sorted(_PRODUCTS))
def test_lane_dense_products_match_xla(jax_cpu, case):
    """The kernel against the XLA formulation on the same lane-dense pool,
    and its text: two products a K/V head a compute block, at every count
    of rows."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.kv_cache import paged_prefill_attention
    from ray_tpu.ops.paged_attention import paged_prefill_attention_pallas

    Hkv, G, hd, S, kernel_kw, pool_kw = _PRODUCTS[case]
    pool_kw = dict(pool_kw)
    lengths, bs, NB = [140, 0, 250, 33], 8, 32
    key = jax.random.PRNGKey(sum(map(ord, case)))
    k_layer, v_layer, tables, _, _ = _prefill_pool(
        key, lengths, Hkv, hd, bs, NB, pool_kw.pop("shuffle", False),
        **pool_kw)
    starts = jnp.asarray([max(L - S, 0) for L in lengths], jnp.int32)
    positions = starts[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    positions = jnp.where(jnp.asarray(lengths)[:, None] > 0, positions, 0)
    q = jax.random.normal(
        jax.random.fold_in(key, 9), (len(lengths), S, Hkv * G, hd))

    def kernel(q):
        return paged_prefill_attention_pallas(
            q, k_layer, v_layer, tables, positions, **kernel_kw)

    text = str(jax.make_jaxpr(kernel)(q))
    body = text[text.index("pallas_call"):]
    assert body.count("dot_general") == 2 * Hkv
    ref = paged_prefill_attention(
        q, k_layer, v_layer, tables, positions,
        window=kernel_kw.get("window"))
    out = kernel(q)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    live = jnp.asarray(lengths)[:, None, None, None] > 0
    assert float(jnp.max(jnp.abs(jnp.where(live, out - ref, 0.0)))) < 2e-5


# (page, Hkv, hd, R, NB, pool dtype, quantized) -> pages a compute block
_BLOCK_CHOICES = {
    # the cells' shapes: 16-token pages, a few rows (decode: a K tile of
    # half a MiB a block, between 128 and 512 tokens) or a q tile of 128
    # queries x G (prefill: 256 tokens)
    "gqa-decode": (((16, 1024), 8, 128, 4, 160, "bfloat16", False), 16),
    "gqa-prefill": (((16, 1024), 8, 128, 512, 128, "bfloat16", False), 16),
    "gqa-verify": (((16, 1024), 8, 128, 16, 160, "bfloat16", False), 16),
    "f32-prefill": (((16, 1024), 8, 128, 128, 64, "float32", False), 16),
    "f32-decode": (((16, 1024), 8, 128, 4, 160, "float32", False), 8),
    "int8-decode": (((16, 1024), 8, 128, 4, 160, "int8", True), 32),
    # a table narrower than a block
    "one-entry-table": (((16, 1024), 8, 128, 4, 1, "bfloat16", False), 1),
    "five-entry-table": (((16, 1024), 8, 128, 4, 5, "bfloat16", False), 4),
    # a page that is a block already
    "page-of-128": (((128, 1024), 8, 128, 4, 64, "bfloat16", False), 2),
    "page-of-128-prefill": (
        ((128, 1024), 8, 128, 512, 64, "bfloat16", False), 2
    ),
    # a q tile whose own buffers leave no room: the floor
    "vmem-floor": (((16, 1024), 8, 128, 8192, 160, "float32", False), 1),
    # the other cells' rows: GPT-2's (16 x 768), lfm2's and SmallThinker's
    # (16 x 512), EvaByte's (16 x 4096: a lane tile of scores is the floor)
    "gpt2-decode": (((16, 768), 12, 64, 1, 64, "bfloat16", False), 16),
    "gpt2-prefill": (((16, 768), 12, 64, 128, 64, "bfloat16", False), 16),
    "lfm2-decode": (((16, 512), 8, 64, 4, 160, "bfloat16", False), 32),
    "lfm2-int8-prefill": (((16, 512), 8, 64, 512, 128, "int8", True), 16),
    "smallthinker-decode": (
        ((16, 512), 4, 128, 7, 1024, "bfloat16", False), 32),
    "evabyte-decode": (((16, 4096), 32, 128, 1, 192, "bfloat16", False), 8),
    # a tp shard's narrow row stops at 512 tokens
    "tp4-shard-decode": (((16, 256), 2, 128, 4, 160, "bfloat16", False), 32),
}


@pytest.mark.parametrize("shapes", sorted(_BLOCK_CHOICES))
def test_compute_block_is_derived_from_shapes(jax_cpu, shapes):
    import jax.numpy as jnp
    from ray_tpu.ops.paged_attention import _VMEM_CAP, _compute_block

    (page, Hkv, hd, R, NB, dtype, quantized), want = _BLOCK_CHOICES[shapes]
    P, vmem = _compute_block(
        page, Hkv, hd, R, NB, jnp.bfloat16, jnp.dtype(dtype), quantized
    )
    assert P == want
    assert P == 1 or vmem <= _VMEM_CAP


# ------------------------------------------------ engine stream parity


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_token_streams_identical_across_backends(jax_cpu, family):
    """Greedy AND sampled streams must be byte-identical: the kernel's
    flash-style softmax and the XLA softmax agree to well below the
    argmax/inverse-CDF decision boundaries at f32."""
    prompts = [[3, 5, 7, 11], [2, 4, 6]]
    outs = {}
    for backend in ("xla", "pallas"):
        eng = _engine(family, _model_config(family),
                      attention_backend=backend)
        outs[backend] = [
            eng.generate(prompts[0], max_new_tokens=12),
            eng.generate(prompts[1], max_new_tokens=10,
                         temperature=0.8, top_p=0.9, seed=17),
            eng.generate(prompts[1], max_new_tokens=8,
                         temperature=1.1, top_k=4, seed=3),
        ]
        assert eng.model_cfg.attention_backend == backend
        assert eng.executor.describe()["attention_backend"] == backend
        eng.shutdown()
    assert outs["pallas"] == outs["xla"]


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_sharded_streams_identical_across_backends(jax_cpu, family):
    """The kernel is head-count-agnostic: per-shard execution over the
    head-sharded pool (tp) under fsdp-sharded weights yields the same
    streams as XLA. Mesh tp=2/fsdp=2 — the same shape the sharded
    serving tests compile, so the xla arm rides the shared jit cache."""
    outs = {}
    for backend in ("xla", "pallas"):
        eng = _engine(family, _model_config(family),
                      attention_backend=backend, tp=2, fsdp=2)
        assert eng.executor.kind == "sharded"
        assert eng.executor.describe()["attention_backend"] == backend
        outs[backend] = [
            eng.generate([13, 17, 19], max_new_tokens=10),
            eng.generate([23, 29, 31], max_new_tokens=8,
                         temperature=0.9, top_p=0.8, seed=5),
        ]
        eng.shutdown()
    assert outs["pallas"] == outs["xla"], family


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_chunked_prefill_streams_identical_across_backends(jax_cpu, family):
    """Long prompts through ``prefill_chunk_tokens`` slices: every chunk
    after the first runs the TRUE-position paged path, so this pins the
    kernel's ragged-start masking end to end."""
    prompt = [3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37, 41, 43, 47,
              53, 59, 61, 67, 71, 73]
    outs = {}
    for backend in ("xla", "pallas"):
        eng = _engine(family, _model_config(family),
                      attention_backend=backend, prefill_chunk_tokens=8)
        outs[backend] = [
            eng.generate(prompt, max_new_tokens=10),
            eng.generate(prompt, max_new_tokens=8,
                         temperature=0.8, top_p=0.9, seed=17),
        ]
        assert any(
            s[0] == "prefill_chunk" for s in eng.fns.signatures
        ), backend
        eng.shutdown()
    assert outs["pallas"] == outs["xla"], family


class _OracleDrafter:
    """Drafts what an engine WITHOUT speculation generated for the same
    request (``streams``: (prompt, its tokens) pairs, told apart by what
    was generated so far). A random-init model does not repeat its
    prompt, so the n-gram drafter would propose nothing and no verify
    program would ever run; with the oracle every decode step of these
    requests is a verify step."""

    def __init__(self, streams):
        self._streams = [(list(p), list(out)) for p, out in streams]

    def propose(self, prompt, generated, k):
        done = len(generated)
        for p, out in self._streams:
            if p == list(prompt) and out[:done] == list(generated):
                return out[done:done + k]
        return []


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_spec_verify_streams_identical_across_backends(jax_cpu, family):
    """Speculative decoding's verify windows run the prefill kernel at
    per-column positions with padding columns clamped to 0 — the stream
    (committed tokens only) must still be byte-identical across
    backends, greedy and sampled, and to the stream without speculation."""
    motif = [435, 326, 262, 138, 158, 21, 39, 9]
    requests = [(motif * 3, dict()),
                (motif * 3, dict(temperature=0.9, top_p=0.8, seed=5))]
    eng = _engine(family, _model_config(family), attention_backend="xla")
    plain = [eng.generate(p, max_new_tokens=12, **kw) for p, kw in requests]
    eng.shutdown()
    drafter = _OracleDrafter(
        [(p, out) for (p, _), out in zip(requests, plain)])
    for backend in ("xla", "pallas"):
        eng = _engine(family, _model_config(family),
                      attention_backend=backend, speculative_k=2,
                      drafter=drafter)
        outs = [eng.generate(p, max_new_tokens=12, **kw)
                for p, kw in requests]
        assert any(s[0] == "verify" for s in eng.fns.signatures), backend
        assert eng.stats()["spec_accepted_tokens"] > 0, backend
        eng.shutdown()
        assert outs == plain, (family, backend)


def test_sharded_chunked_and_verify_streams_identical(jax_cpu):
    """tp=2/fsdp=2: chunked prefill and speculative verify per shard over
    the head-sharded pool — the prefill kernel is head-count-agnostic, so
    streams match XLA under GSPMD unchanged."""
    motif = [435, 326, 262, 138, 158, 21, 39, 9]
    requests = [(list(range(3, 43, 2)), dict()),
                (motif * 3, dict(temperature=0.9, top_p=0.8, seed=5))]
    sharded = dict(tp=2, fsdp=2, prefill_chunk_tokens=8)
    eng = _engine("llama", _model_config(), attention_backend="xla",
                  **sharded)
    plain = [eng.generate(p, max_new_tokens=10, **kw) for p, kw in requests]
    eng.shutdown()
    drafter = _OracleDrafter(
        [(p, out) for (p, _), out in zip(requests, plain)])
    for backend in ("xla", "pallas"):
        eng = _engine("llama", _model_config(), attention_backend=backend,
                      speculative_k=2, drafter=drafter, **sharded)
        assert eng.executor.kind == "sharded"
        outs = [eng.generate(p, max_new_tokens=10, **kw)
                for p, kw in requests]
        kinds = {s[0] for s in eng.fns.signatures}
        assert {"prefill_chunk", "verify"} <= kinds, (backend, kinds)
        eng.shutdown()
        assert outs == plain, backend


def test_backend_via_model_parallel_config(jax_cpu):
    """The mesh-object spelling threads too, and engine-level
    attention_backend wins over the mesh's."""
    from ray_tpu.serve.config import ModelParallelConfig

    eng = _engine(
        "llama", _model_config(),
        mesh=ModelParallelConfig(tp=2, attention_backend="pallas"),
    )
    assert eng.executor.describe()["attention_backend"] == "pallas"
    eng.shutdown()
    eng = _engine(
        "llama", _model_config(),
        mesh=ModelParallelConfig(tp=2, attention_backend="pallas"),
        attention_backend="xla",
    )
    assert eng.executor.describe()["attention_backend"] == "xla"
    eng.shutdown()


# ------------------------------------------------ compile-kind contract


def test_compile_contract_frozen_across_backends(jax_cpu):
    """Backend selection must not widen the jit surface: same kinds, same
    signature SET as an identically-driven xla engine, and further
    sampled traffic on the pallas engine compiles nothing new."""

    motif = [435, 326, 262, 138, 158, 21, 39, 9]

    def drive(eng):
        for kw in (dict(),
                   dict(temperature=0.7, top_p=0.9, seed=2)):
            eng.generate([3, 5, 7, 11], max_new_tokens=6, **kw)
        # long prompt -> prefill_chunk signatures; the motif prompt's
        # spec run -> verify signatures
        eng.generate(list(range(3, 43, 2)), max_new_tokens=4)
        eng.generate(motif * 3, max_new_tokens=6)
        return set(eng.fns.signatures)

    engs = {
        b: _engine("llama", _model_config(), attention_backend=b,
                   prefill_chunk_tokens=8, speculative_k=2)
        for b in ("xla", "pallas")
    }
    sigs = {b: drive(e) for b, e in engs.items()}
    assert {s[0] for s in sigs["pallas"]} <= {
        "prefill", "prefill_chunk", "decode", "verify"
    }
    assert sigs["pallas"] == sigs["xla"]

    before = len(engs["pallas"].fns.signatures)
    engs["pallas"].generate(
        [8, 9, 10], max_new_tokens=6, temperature=1.2, top_k=3, seed=11
    )
    assert len(engs["pallas"].fns.signatures) == before
    for e in engs.values():
        e.shutdown()
