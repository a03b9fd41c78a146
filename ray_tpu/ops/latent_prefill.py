"""A latent layer's PREFILL attention in the EXPANDED form.

A latent family (models/pangu_ultra_moe.py, models/longcat_flash.py) keeps
one row a token for all heads, ``c`` (the normed latent, key and value)
and ``k_r`` (the key's rotary rest). A decode row wants the ABSORBED form
over that row (ops/paged_attention.py ``paged_attention_latent``: the
1,152 B are read once for every head, at ``2 H (C + R + C)`` operations a
pair). A prefill step's queries are many, and for them the expanded form
is the cheap one: keys ``[c W_uk,h | k_r]`` and values ``c W_uv,h`` by
head cost ``2 H (N + R + V)`` a pair, 3.4 x fewer at the published widths
(512 / 64 / 128 / 128), once a key's up-projection is shared by a few
hundred queries.

What a step does a layer, AFTER its rows were written to the pool:

1. **its own keys, in hand**: the step's ``T = B x S`` token slots
   flattened (a sequence's pieces are consecutive rows in position order:
   serve/llm/engine.py ``_prefill_chunk_locked``) give K ``[H, T, N + R]``
   and V ``[H, T, V]``: ONE ``flash_fwd`` call, causal over the flat index
   and same-sequence by a segment id a token (rows of one table are one
   sequence; padding carries a negative id and attends and is attended by
   nothing real);
2. **the resident prefix** of each sequence in the step (positions before
   its first token here): read from the pool through the table in blocks
   of ``PREFIX_BLOCK`` keys, each up-projected once and attended by that
   sequence's queries (the others' blocks are skipped by their ids), masked
   by the prefix's length alone, each call going on from the one before
   (its float32 output and log-sum-exp are the carried state: no pass
   between the calls).
   The loop's trip count is the REAL prefix's: a first chunk runs none. No
   context is ever expanded whole: one block's K and V beside the step's.

Same softmax as both kernels have had (base 2, ``exp2`` in bfloat16 on
bfloat16 inputs, float32 max, sum and accumulator), every pair kept. The
"xla" backend runs the same structure over a plain masked softmax."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import (
    LOG2E, NEG_INF, _flash_forward, pallas_interpret,
)
from ray_tpu.ops.paged_attention import (
    _as_pools, latent_parts, resolve_backend,
)

# keys of a resident prefix up-projected and attended at a time (about a
# chunk of cell 8: K and V of one block are 168 MB at 128 heads)
PREFIX_BLOCK = 2048
# The kernel's blocks (docs/MICROBENCHMARKS.md, PR 51: it is bound by what
# a grid step costs beside its products, so few large blocks win). The
# step's tokens are padded to whole tiles of ``_TILE`` (past half a tile),
# and up to ``_ONE_BLOCK`` of them are ONE square block of the causal call,
# whose upper right quarter is left out from ``_HALVE`` tokens up (below,
# the two updates cost what they save), and a prefix block's keys are ONE
# block under them (one block a call is also the least code: a kernel's
# text is ~2 MB a call a layer a program on the device); more tokens are
# blocks of ``_BLOCK`` both ways.
_TILE = 256
_ONE_BLOCK = 2048
_HALVE = 1536
_BLOCK = 1024


def _padded(T: int, tile: int, one: int, halve: int,
            block: int) -> tuple[int, int, bool]:
    """``(T padded, the causal call's square block, whether its diagonal
    block is halved)`` for a step of T token slots; a prefix call takes as
    many queries a block."""
    if T > one:
        return -(-T // block) * block, block, False
    Tp = T if T <= tile // 2 else -(-T // tile) * tile
    return Tp, Tp, Tp >= halve


def prefix_blocks(first, block: int | None = None):
    """Key blocks a sequence whose step starts at position ``first``
    attends of its resident prefix: the loop's trips for it, in the
    program (``first`` an array) and on the host (the ``prefix_blocks`` of
    a dispatch span) by the one expression."""
    return -(-first // (block or PREFIX_BLOCK))


def _segment_attention_xla(q, k, v, q_seg, k_seg, carry, *, causal, scale):
    """The serving kernel's contract in plain XLA (ops/attention.py
    ``_flash_forward`` with ids): ``(o [H, Tq, Dv] float32, base-2
    log-sum-exp [H, Tq, 1] float32)``, going on from ``carry``, such a
    pair over other keys."""
    s = jnp.einsum("hqd,hkd->hqk", q, k,
                   preferred_element_type=jnp.float32) * (scale * LOG2E)
    allowed = q_seg[:, None] == k_seg[None, :]
    if causal:
        t = jnp.arange(q.shape[1])
        allowed &= t[None, :] <= t[:, None]
    s = jnp.where(allowed[None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    if carry is not None:
        m = jnp.maximum(m, carry[1])
    p = jnp.exp2(s - jnp.where(m == NEG_INF, 0.0, m))
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("hqk,hkv->hqv", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    if carry is not None:
        w = jnp.exp2(carry[1] - m)  # the carried state: sum 1 at its max
        l, acc = l + w, acc + w * carry[0]
    l = jnp.where(l == 0.0, 1.0, l)
    return acc / l, m + jnp.log2(l)


def _segment_attention(q, k, v, q_seg, k_seg, carry=None, *, causal, scale,
                       blocks, pallas, interpret, halve=False):
    if not pallas:
        return _segment_attention_xla(
            q, k, v, q_seg, k_seg, carry, causal=causal, scale=scale)
    o, lse = _flash_forward(
        q[None], k[None], v[None], causal=causal, scale=scale,
        block_q=blocks[0], block_kv=blocks[1], interpret=interpret,
        save_lse=True, q_seg=q_seg, k_seg=k_seg, halve_diagonal=halve,
        carry=None if carry is None else (carry[0][None], carry[1][None]))
    return o[0], lse[0]


def _expand(c, k_r, w_uk, w_uv):
    """Keys ``[H, T, N + R]`` and values ``[H, T, V]`` by head of the rows
    ``c [T, C]``, ``k_r [T, R]``."""
    H = w_uk.shape[1]
    # a projection, whoever calls it (serve/llm/obs.py ``SCOPES``: the
    # innermost listed name is the operation's)
    with jax.named_scope("attn_proj"):
        k = jnp.concatenate([
            jnp.einsum("tc,chn->htn", c, w_uk),
            jnp.broadcast_to(k_r[None], (H, *k_r.shape)),
        ], axis=-1)
        return k, jnp.einsum("tc,chv->htv", c, w_uv)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block", "tiles", "pallas", "interpret"))
def _expanded_call(q, c, k_r, valid, start, pool, block_tables, layer, w_uk,
                   w_uv, *, scale, block, tiles, pallas, interpret):
    """``expanded_prefill_attention`` behind a jit of its own: an unrolled
    stack calls it once a latent layer with the same shapes, and the inner
    jit's cache makes it traced once a process (``_latent_call``)."""
    B, S, H, _ = q.shape
    C, R = c.shape[-1], k_r.shape[-1]
    T = B * S
    Tp, own, halve = _padded(T, *tiles)
    attend = functools.partial(
        _segment_attention, scale=scale, pallas=pallas, interpret=interpret)
    # rows of one table are one sequence; the ids count them in row order
    fresh = jnp.concatenate([
        jnp.ones((1,), bool),
        jnp.any(block_tables[1:] != block_tables[:-1], axis=1)])
    seq = jnp.cumsum(fresh, dtype=jnp.int32) - 1                  # [B]
    ids = jnp.broadcast_to(seq[:, None], (B, S))
    # padded BEFORE the layouts and products that follow, which then come
    # out whole tiles at no pass of their own
    flat = lambda x, fill=0: jnp.pad(
        x.reshape(T, *x.shape[2:]),
        ((0, Tp - T),) + ((0, 0),) * (x.ndim - 2), constant_values=fill)
    q_seg = flat(jnp.where(valid, ids, -2), -2)
    k_seg = flat(jnp.where(valid, ids, -1), -1)
    qh = flat(q).transpose(1, 0, 2)                               # [H, Tp, .]
    k, v = _expand(flat(c), flat(k_r), w_uk, w_uv)

    # the resident prefixes, block by block: trips[b] blocks for the
    # sequence that row b opens, none for its later rows
    bs = pool.shape[2]
    pages = block // bs
    trips = jnp.where(fresh, prefix_blocks(start, block), 0)
    ends = jnp.cumsum(trips)
    NB = block_tables.shape[1]
    tables = jnp.pad(block_tables, ((0, 0), (0, -NB % pages)))
    # under ONE block of queries a prefix block's keys are one block too
    prefix = (own, block if own == Tp else own)

    def one(i, carry):
        b = jnp.sum(ends <= i)
        j = i - (ends[b] - trips[b])
        with jax.named_scope("attn_cache"):
            page = jax.lax.dynamic_slice(tables[b], (j * pages,), (pages,))
            c_j, r_j = latent_parts(
                pool[layer[0], page].reshape(block, -1), C, R)
        at = j * block + jnp.arange(block, dtype=jnp.int32)
        return attend(
            qh, *_expand(c_j, r_j, w_uk, w_uv), q_seg,
            jnp.where(at < start[b], seq[b], -1), carry, causal=False,
            blocks=prefix)

    o, _ = jax.lax.fori_loop(
        0, ends[-1], one,
        attend(qh, k, v, q_seg, k_seg, causal=True, blocks=(own, own),
               halve=halve))
    return o[:, :T].astype(q.dtype).transpose(1, 0, 2).reshape(B, S, -1)


def expanded_prefill_attention(
    q: jax.Array,
    c: jax.Array,
    k_r: jax.Array,
    pool: jax.Array,
    block_tables: jax.Array,
    valid: jax.Array,
    start: jax.Array | None,
    w_uk: jax.Array,
    w_uv: jax.Array,
    *,
    scale: float,
    backend: str = "auto",
    layer: jax.Array | int | None = None,
) -> jax.Array:
    """The heads' outputs ``[B, S, H * V]`` of a prefill step's latent
    layer. ``q [B, S, H, N + R]`` (``[q_nope | q_rope]``, NOT absorbed),
    the step's own rows ``c [B, S, C]`` and ``k_r [B, S, R]`` (already
    written to the pool), ``valid [B, S]`` its real tokens, ``start
    [B]`` each row's true first position (None: 0, nothing resident),
    ``block_tables [B, NB]``, the pool ``[n_layer, num_blocks,
    block_size, latent_row_width(C, R)]`` with ``layer`` (one layer's
    without),
    ``w_uk [C, H, N]`` and ``w_uv [C, H, V]`` in q's dtype."""
    pallas = resolve_backend(backend) == "pallas"
    pool, _, layer = _as_pools(pool, None, layer)
    B, NB = block_tables.shape
    bs = pool.shape[2]
    # whole pages, and no more than the table holds
    block = min(PREFIX_BLOCK, NB * bs) // bs * bs
    if start is None:
        start = jnp.zeros((B,), jnp.int32)
    return _expanded_call(
        q, c, k_r, valid, start.astype(jnp.int32), pool,
        block_tables.astype(jnp.int32), layer.reshape(1), w_uk, w_uv,
        scale=float(scale), block=block,
        tiles=(_TILE, _ONE_BLOCK, _HALVE, _BLOCK), pallas=pallas,
        interpret=bool(pallas and pallas_interpret()))
