"""Paged KV-cache array primitives: block-granular write / gather / attend /
copy (COW for the prefix cache).

The serving-side counterpart of ops/attention.py. A paged cache stores one
layer's keys/values as fixed-size physical blocks, a token's heads ONE
lane-dense row (ops/paged_attention.py ``pool_shape``; the cache manager
allocates it so for every family)

    k_layer, v_layer: [num_blocks, block_size, n_kv_head * head_dim]

Every function here also takes a pool laid BY HEADS, ``[num_blocks,
block_size, n_kv_head, head_dim]`` (a test's own array, a reference's): a
write flattens its rows to the pool's, a read splits the row into heads of
q's size after indexing. Each sequence owns a BLOCK TABLE — logical position p of sequence b
lives at (block_tables[b, p // block_size], p % block_size). Block tables
are dense int32 arrays padded with block 0, which is reserved as a garbage
sink: every out-of-range or padding write is redirected there, so the
scatter/gather ops below are mask-free and shape-static (XLA-friendly — no
dynamic shapes, bounded compile cache). Host-side block accounting (the
allocator, free lists, reuse) lives in serve/llm/kv_cache.py; these
functions are pure array ops so the model decode paths (models/gpt.py,
models/llama.py) can use them without depending on the serve layer.
``write_kv`` also takes the WHOLE pools ``[n_layer, num_blocks, ...]`` and
a ``layer=`` index, which is how the cached step writes (models/cached.py:
the pool is donated to the step programs and updated where it stands);
``copy_blocks`` and ``land_blocks`` donate the pools they are handed.

Attention here is the XLA formulation, the CPU default and reference
semantics: decode gathers blocks, masks and softmaxes; prefill does the
same below ``PREFILL_STREAM_MIN_T`` and switches to an online-softmax
scan over block slabs above it (the padded context never materializes at
long T). The block-parallel Pallas decode AND prefill kernels with the
same call signatures live in ops/paged_attention.py; model steps pick
between backends via the ``decode_attention`` / ``prefill_attention``
dispatchers' ``backend`` knob (threaded from
EngineConfig.attention_backend). GQA never materializes repeated KV heads
in any path: the queries regroup onto their shared KV head and the
einsums carry the group as a free axis.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ray_tpu.ops.quantization import QuantizedKV, quantize_kv

NEG_INF = -1e30


def physical_slots(
    positions: jax.Array, block_tables: jax.Array, block_size: int
) -> tuple[jax.Array, jax.Array]:
    """Logical positions -> (physical block id, slot within block).

    positions: [B] or [B, S] int32; block_tables: [B, NB] int32. Positions
    outside the table range are clamped onto block 0 by the caller's
    masking; here indices are clamped so gathers stay in bounds.
    """
    idx = positions // block_size
    slot = positions % block_size
    idx = jnp.clip(idx, 0, block_tables.shape[1] - 1)
    if positions.ndim == 1:
        blk = jnp.take_along_axis(block_tables, idx[:, None], axis=1)[:, 0]
    else:
        blk = jnp.take_along_axis(block_tables, idx, axis=1)
    return blk, slot


def write_kv(
    k_layer: jax.Array,
    v_layer: jax.Array,
    k: jax.Array,
    v: jax.Array,
    positions: jax.Array,
    block_tables: jax.Array,
    *,
    valid: jax.Array | None = None,
    layer: jax.Array | int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Scatter new keys/values into a layer's paged cache.

    k, v: [B, H_kv, hd] (decode: one token per sequence, positions [B]) or
    [B, S, H_kv, hd] (prefill: positions [B, S]). `valid` masks rows/tokens
    that are padding — their writes are redirected to the reserved garbage
    block 0, slot 0, keeping the scatter shape-static.

    ``layer`` given: ``k_layer`` / ``v_layer`` are the WHOLE pools
    ``[n_layer, num_blocks, ...]`` and the rows land at ``[layer, blk,
    slot]``. That is how the cached step calls it (models/cached.py): the
    pool is updated where it stands, B x S rows a layer.

    A ``QuantizedKV`` pool quantizes the incoming values at exactly this
    scatter's granularity — one amax per (token, kv-head) row — and lands
    data and scale with the same (blk, slot) indices, so incremental
    decode appends never touch (or re-quantize) previously written slots.
    """
    # [.., block_size, H_kv, hd], or lane-dense [.., block_size, H_kv * hd]:
    # a token's K/V then lands as ONE row
    lead = 1 if layer is None else 2
    block_size = k_layer.shape[lead]
    blk, slot = physical_slots(positions, block_tables, block_size)
    if valid is not None:
        blk = jnp.where(valid, blk, 0)
        slot = jnp.where(valid, slot, 0)
    at = (blk, slot) if layer is None else (layer, blk, slot)

    def rows(x):
        # in the pool's own row
        return x.reshape(*blk.shape, *k_layer.shape[lead + 1:])

    if v_layer is None:
        # a pool in ONE plane (latent attention): ``k`` is the token's
        # latent vector and ``v`` its key's rotary rest, one of each for
        # all heads, and its row ``[k | v]``, each part at whole lanes
        from ray_tpu.ops.paged_attention import latent_row

        return k_layer.at[at].set(
            rows(latent_row(k, v).astype(k_layer.dtype))), None
    if isinstance(k_layer, QuantizedKV):
        kind = "int8" if k_layer.data.dtype == jnp.int8 else "fp8"
        kq, ks = quantize_kv(k, kind)
        vq, vs = quantize_kv(v, kind)
        k_layer = QuantizedKV(
            k_layer.data.at[at].set(rows(kq)), k_layer.scale.at[at].set(ks))
        v_layer = QuantizedKV(
            v_layer.data.at[at].set(rows(vq)), v_layer.scale.at[at].set(vs))
        return k_layer, v_layer
    k_layer = k_layer.at[at].set(rows(k.astype(k_layer.dtype)))
    v_layer = v_layer.at[at].set(rows(v.astype(v_layer.dtype)))
    return k_layer, v_layer


def gather_kv(
    k_layer: jax.Array, v_layer: jax.Array, block_tables: jax.Array,
    *, head_dim: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Materialize each sequence's cached context in position order:
    [B, NB * block_size, H_kv, hd]. Unallocated table entries point at the
    garbage block; the caller masks those positions. ``head_dim`` (q's):
    what splits a lane-dense layer's rows into heads, after the indexing;
    None reads it off a layer stored by heads.

    For a ``QuantizedKV`` pool this is the sanctioned XLA-fallback dequant
    (f32 out): the gathered context is ONE sequence batch's working set,
    never the whole pool — the full-pool-dequant lint in
    tests/test_sanitizers.py allowlists exactly this function and the
    streaming slab path below."""
    B, NB = block_tables.shape
    Bs = k_layer.shape[1]

    def context(layer):
        # None: the layer's own last axis (one stored by heads)
        hd = layer.shape[-1] if head_dim is None else head_dim
        if not isinstance(layer, QuantizedKV):
            return layer[block_tables].reshape(B, NB * Bs, -1, hd)
        data = layer.data[block_tables].astype(jnp.float32)
        return (
            data.reshape(B, NB, Bs, -1, hd)
            * layer.scale[block_tables][..., None]
        ).reshape(B, NB * Bs, -1, hd)

    return context(k_layer), context(v_layer)


# Context length (NB * block_size) at and above which
# ``paged_prefill_attention`` switches from the dense one-shot formulation
# to the streaming (block-slab scan) one. The dense path keeps the full
# [B, S, Hkv, G, T] f32 score tensor live through softmax, an O(S*T) HBM
# spike that at the long contexts ROADMAP item 1 targets dwarfs the output;
# the streaming path peaks at one [B, S, Hkv, G, block_size] slab instead.
# Numerics differ at the last ulp (online vs one-shot softmax), so short
# contexts — everything the byte-identity tier-1 suite pins — keep the
# dense path bit-for-bit; tests monkeypatch this down to cover streaming.
PREFILL_STREAM_MIN_T = 2048


def _paged_prefill_streaming(
    qg: jax.Array,          # [B, S, Hkv, G, hd] regrouped queries
    k_layer: jax.Array,
    v_layer: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    *,
    scale: float,
    window: int | None,
) -> jax.Array:
    """Online-softmax scan over physical block slabs: gathers ONE
    [B, block_size, Hkv, hd] slab per step instead of the whole padded
    context, carrying flash-style running (max, sum, acc). The padded
    [B, T] context and the [.., T] score tensor never exist in HBM."""
    B, S, Hkv, G, hd = qg.shape
    bs = k_layer.shape[1]
    NB = block_tables.shape[1]

    def heads(x):
        # a lane-dense slab's rows split into heads; a no-op otherwise
        return x.reshape(B, bs, Hkv, hd)

    def _slab(carry, xs):
        m, l, acc = carry
        i, blk = xs
        if isinstance(k_layer, QuantizedKV):
            # per-slab dequant (one block's worth, in registers/VMEM —
            # never the whole pool); allowlisted by the dequant lint.
            kb, vb = k_layer[blk], v_layer[blk]
            keys = heads(kb.data.astype(jnp.float32)) * kb.scale[..., None]
            values = heads(vb.data.astype(jnp.float32)) * vb.scale[..., None]
        else:
            keys = heads(k_layer[blk])      # [B, bs, Hkv, hd]
            values = heads(v_layer[blk])
        s = jnp.einsum(
            "bshgd,bthd->bshgt", qg, keys,
            preferred_element_type=jnp.float32,
        ) * scale
        t = i * bs + jnp.arange(bs, dtype=positions.dtype)
        mask = t[None, None, :] <= positions[:, :, None]   # [B, S, bs]
        if window is not None:
            mask = jnp.logical_and(
                mask, t[None, None, :] > positions[:, :, None] - window
            )
        mask = mask[:, :, None, None, :]
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # p is explicitly zeroed where masked: for a fully-masked slab
        # m_new stays NEG_INF and exp(NEG_INF - NEG_INF) would be 1.
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bshgt,bthd->bshgd", p.astype(values.dtype), values,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    init = (
        jnp.full((B, S, Hkv, G), NEG_INF, jnp.float32),
        jnp.zeros((B, S, Hkv, G), jnp.float32),
        jnp.zeros((B, S, Hkv, G, hd), jnp.float32),
    )
    xs = (jnp.arange(NB, dtype=positions.dtype), block_tables.T)
    (_, l, acc), _ = jax.lax.scan(_slab, init, xs)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return acc / l_safe[..., None]


def paged_prefill_attention(
    q: jax.Array,
    k_layer: jax.Array,
    v_layer: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    *,
    scale: float | None = None,
    window: int | None = None,
) -> jax.Array:
    """Multi-token (chunked-prefill) attention over a paged cache.

    q: [B, S, H_q, hd] — a CHUNK of queries whose K/V were already written
    via ``write_kv`` (so each query's own position is in the cache), with
    ``positions`` [B, S] giving every query's TRUE logical position. Each
    query attends over the sequence's full gathered context with the mask
    ``t <= position`` — i.e. all previously-cached tokens (an earlier
    chunk, or blocks mapped from a prefix cache) plus the causal part of
    its own chunk. ``window=W`` additionally masks ``t <= position - W``
    (sliding-window attention). Padding queries attend at whatever clamped
    position the caller gave them; their outputs are garbage the caller
    discards. Returns [B, S, H_q, hd] in q.dtype; GQA as in
    ``paged_attention``.

    Contexts at/above ``PREFILL_STREAM_MIN_T`` take the streaming path
    (``_paged_prefill_streaming``): the padded [B, T] gather and the full
    score tensor are never materialized — memory peaks at one block slab.
    """
    B, S, Hq, hd = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    Hkv = math.prod(k_layer.shape[2:]) // hd  # by heads, or lane-dense
    # GQA without materializing rep x copies of K/V: queries regroup onto
    # their shared KV head ([B,S,Hq,hd] -> [B,S,Hkv,G,hd] — query head h
    # serves kv head h // G) and the einsums contract against the COMPACT
    # keys/values, carrying the group as a free axis.
    qg = q.reshape(B, S, Hkv, Hq // Hkv, hd)
    T = block_tables.shape[1] * k_layer.shape[1]
    if T >= PREFILL_STREAM_MIN_T:
        out = _paged_prefill_streaming(
            qg, k_layer, v_layer, block_tables, positions,
            scale=scale, window=window,
        )
        return out.reshape(B, S, Hq, hd).astype(q.dtype)
    keys, values = gather_kv(
        k_layer, v_layer, block_tables, head_dim=hd)  # [B, T, Hkv, hd]
    logits = jnp.einsum(
        "bshgd,bthd->bshgt", qg, keys, preferred_element_type=jnp.float32
    ) * scale
    mask = (
        jnp.arange(T, dtype=positions.dtype)[None, None, :]
        <= positions[:, :, None]
    )  # [B, S, T]
    if window is not None:
        mask = jnp.logical_and(
            mask,
            jnp.arange(T, dtype=positions.dtype)[None, None, :]
            > positions[:, :, None] - window,
        )
    logits = jnp.where(mask[:, :, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(values.dtype)
    out = jnp.einsum("bshgt,bthd->bshgd", probs, values)
    return out.reshape(B, S, Hq, hd).astype(q.dtype)


def _copy_blocks(
    cache_k: jax.Array, cache_v: jax.Array, src: jax.Array, dst: jax.Array
) -> tuple[jax.Array, jax.Array]:
    # cache_k/v: [n_layer, num_blocks, block_size, ...] (plain pools, by
    # heads or lane-dense) or QuantizedKV pytrees with their scale planes;
    # src/dst: [P]. The tree map moves every leaf — quantized COW clones
    # data AND scale planes in the same fused op, no dequant round-trip.
    def _cp(a):
        return a.at[:, dst].set(a[:, src])

    return jax.tree.map(_cp, cache_k), jax.tree.map(_cp, cache_v)


def _land_blocks(
    cache_k: jax.Array,
    cache_v: jax.Array,
    blocks: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    # cache_k/v: [n_layer, num_blocks, block_size, ...] pools (or
    # QuantizedKV pytrees); blocks: [P]; k_new/v_new: matching payloads
    # [n_layer, P, ...] per leaf, in the pool's own trailing shape. Quantized handoffs land the wire's
    # already-quantized data and scale planes verbatim — bit-exact with
    # the exporter's pool, which is what keeps disaggregated streams
    # byte-identical within a quantized config.
    def _land(a, n):
        return a.at[:, blocks].set(n.astype(a.dtype))

    return (
        jax.tree.map(_land, cache_k, k_new),
        jax.tree.map(_land, cache_v, v_new),
    )


# Disaggregated-handoff landing: scatter externally-produced KV blocks
# (fetched from the object store by a decode replica) into the paged pool
# across all layers in one fused op. Callers pad the block-id list to a
# pow2 bucket with id 0 (the garbage block) and zero payload rows, so the
# jitted shape set stays closed exactly like ``copy_blocks``. The pools are
# donated, as they are to the step programs (serve/llm/decode.py): the
# caller rebinds them from the result and the ones it passed are deleted.
land_blocks = jax.jit(_land_blocks, donate_argnums=(0, 1))


# Copy-on-write block duplication for the prefix cache: when a sequence
# must append into a block it shares with other sequences (or that is
# registered in the prefix-cache hash map), the host allocator points the
# sequence at a fresh block and this op clones the shared content into it,
# across all layers in one fused gather+scatter. Callers pad the (src,
# dst) id lists to a small bucket with (0, 0) identity pairs — copying
# the garbage block onto itself is a no-op — so the jitted shape set
# stays closed. Jitted once at module level: every engine in the process
# shares the compiled programs (same discipline as decode.py's _jit_cache).
# The pools are donated (see ``land_blocks``).
copy_blocks = jax.jit(_copy_blocks, donate_argnums=(0, 1))


def paged_attention(
    q: jax.Array,
    k_layer: jax.Array,
    v_layer: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    *,
    scale: float | None = None,
) -> jax.Array:
    """Single-token decode attention over a paged cache.

    q: [B, H_q, hd] (the current token's query, AFTER its own k/v were
    written, so the mask `t <= position` includes self-attention).
    Returns [B, H_q, hd] in q.dtype. GQA: H_q may be a multiple of the
    cache's H_kv; the query group attends against the compact KV heads
    (no repeat — grouped einsum).
    """
    B, Hq, hd = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    keys, values = gather_kv(
        k_layer, v_layer, block_tables, head_dim=hd)  # [B, T, Hkv, hd]
    Hkv = keys.shape[2]
    # GQA via grouped einsum over the compact KV heads (see
    # paged_prefill_attention) — no rep x K/V expansion in HBM.
    q = q.reshape(B, Hkv, Hq // Hkv, hd)
    logits = jnp.einsum(
        "bhgd,bthd->bhgt", q, keys, preferred_element_type=jnp.float32
    ) * scale
    T = keys.shape[1]
    mask = jnp.arange(T, dtype=positions.dtype)[None, :] <= positions[:, None]
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(values.dtype)
    out = jnp.einsum("bhgt,bthd->bhgd", probs, values)
    return out.reshape(B, Hq, hd).astype(q.dtype)


def paged_latent_attention(
    q: jax.Array,
    layer: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    *,
    latent_dim: int,
    scale: float,
) -> jax.Array:
    """Latent (absorbed multi-head latent) attention over a cache in ONE
    plane, the XLA formulation: every query head attends ONE row a token,
    whose key is ``[latent | rotary]`` and whose value is the latent part.

    q ``[B, S, H, C + R]`` (``[q~ | q_rope]``, the up-projection of the
    keys absorbed into the query; ``C = latent_dim``), ``layer``
    ``[num_blocks, block_size, latent_row_width(C, R)]`` (each part of a
    row is stored at whole lanes: what lies past its width is not read),
    ``positions`` ``[B, S]`` the queries' true positions, their own rows
    already written. Returns ``[B, S, H, C]`` in q's dtype: the
    probabilities' sum of latent rows, which the layer un-absorbs. The
    context is gathered once and feeds both products."""
    from ray_tpu.ops.paged_attention import latent_parts

    C = latent_dim
    R = q.shape[-1] - C
    B, NB = block_tables.shape
    latent, rope = latent_parts(
        layer[block_tables].reshape(B, NB * layer.shape[1], -1), C, R)
    logits = (
        jnp.einsum("bshc,btc->bsht", q[..., :C], latent,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bshr,btr->bsht", q[..., C:], rope,
                     preferred_element_type=jnp.float32)) * scale
    T = latent.shape[1]
    mask = (jnp.arange(T, dtype=positions.dtype)[None, None, :]
            <= positions[:, :, None])
    logits = jnp.where(mask[:, :, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(latent.dtype)
    return jnp.einsum("bsht,btc->bshc", probs, latent).astype(q.dtype)
