"""Attention that SELECTS its pages: InfLLM-V2's block top-k over a paged
cache (MiniCPM4, arXiv:2506.07900 section 2.2; arXiv:2509.24663), the
attention of a ``minicpm4`` layer (models/minicpm_sala.py).

A query at position ``t`` below ``dense_len`` attends every key ``<= t``.
At or past it, per K/V head (the same for the ``G`` query heads of its
group), with ``K = kernel_size``, ``s = kernel_stride``, ``Bs = block_size``:

1. compressed keys ``c_j = mean(k_{s j} .. k_{s j + K - 1})``, visible to
   ``t`` iff ``s j + K - 1 <= t``;
2. ``p^h = softmax_j(q_t^h . c_j * scale)`` over the visible ``j`` for each
   head of the group, EXACTLY (no coarser estimate of the log-sum-exp), and
   ``r_j = sum_h p^h_j``;
3. a block's score ``R_b = max r_j`` over the visible ``c_j`` whose tokens
   overlap block ``b`` (``ceil((Bs b - K + 1) / s) <= j <= floor((Bs b + Bs
   - 1) / s)``), ``-inf`` where none is visible;
4. forced to ``+inf``: the first ``init_blocks`` blocks and the
   ``window_size / Bs`` blocks that end with the query's own;
5. the ``topk`` blocks of largest ``R`` (ties to the lower ``b``; a block
   past the query's own is never chosen);
6. the tokens of those blocks at positions ``<= t``.

What is stored: ``K = 2 s`` (the family's 32 / 16), so ``c_j`` is the mean
of two adjacent SEGMENTS of ``s`` tokens, and the cache keeps one float32
row a segment, the SUM of its keys, ``Bs / s`` rows a block in a plane of
its own addressed by BLOCK ID like K and V (``[n_sparse_layer, num_blocks,
Bs / s, n_kv_head * hd]``: an eighth of K's bytes; a block's rows are
freed with the block because nothing but its id names them). A segment's
row is SET by its first token and added to by the rest, so a reused block
needs no clearing; ``c_j = (seg_j + seg_{j+1}) / K`` exists as soon as its
last token is written, whatever chunk, block or decode step wrote it.

The selection (``sparse_select`` scope: gather of a row's segments through
its table, scores, softmax, group sum, max pool, forcing, top-k) is XLA's.
Decode attends the chosen pages with a kernel of its own,
``paged_attention_sparse``: a walk over a LIST of pages a (row, K/V head)
that copies only that head's lanes of each page, so a row-step reads its
``topk`` blocks and nothing else of its context. A prefill chunk with a
query at or past ``dense_len`` chooses its blocks a tile of 64 queries at a
time and attends them with ``paged_attention_select``: the by-head paged
kernel's walk under one more mask, a (query, K/V head) attending a page
only where it was chosen, a compute block that no query of the tile chose
neither copied nor computed (the same mathematics as a kernel over the
UNION of a tile's blocks; what that would save is ROADMAP R10 d's). A chunk
wholly below ``dense_len`` takes the dense paged kernel. On the CPU
(backend "xla") both attentions are a gather and a mask.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import LOG2E, NEG_INF, pallas_interpret

SPARSE_KERNEL_NAME = "paged_attention_sparse"
SELECT_KERNEL_NAME = "paged_attention_select"
# queries a tile of a chunk's selection and of ``paged_attention_select``:
# one selection block, so a tile's queries share their own block and their
# forced ones (and, in XLA's masked form, [Hkv, tile * G, context] float32
# scores are the step's largest temporary)
PREFILL_Q_TILE = 64


class SparseConfig(NamedTuple):
    """The seven integers of the family's ``sparse_config``."""

    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192

    def check(self) -> "SparseConfig":
        if self.kernel_size != 2 * self.kernel_stride:
            raise ValueError(
                "the cache keeps one sum a segment of kernel_stride tokens "
                "and a compressed key is two of them: kernel_size must be "
                f"2 x kernel_stride, got {self.kernel_size} and "
                f"{self.kernel_stride}")
        if self.block_size % self.kernel_stride \
                or self.window_size % self.block_size:
            raise ValueError(
                "block_size must be whole segments and window_size whole "
                f"blocks, got {self}")
        if self.topk < self.init_blocks + self.window_blocks:
            raise ValueError(f"topk cannot hold the forced blocks: {self}")
        if self.dense_len < (self.topk + 1) * self.block_size:
            raise ValueError(
                "a sparse query must have more than topk blocks to choose "
                f"from: dense_len >= (topk + 1) x block_size, got {self}")
        return self

    @property
    def segments(self) -> int:
        """Segment rows a block."""
        return self.block_size // self.kernel_stride

    @property
    def window_blocks(self) -> int:
        return self.window_size // self.block_size

    @property
    def list_width(self) -> int:
        """Entries of a decode row's page list: ``topk`` chosen, or every
        block of a row still below ``dense_len``."""
        return max(self.topk, -(-self.dense_len // self.block_size))


@dataclass(frozen=True)
class Selection:
    """What a ``minicpm4`` layer hands the cache side of its step
    (models/cached.py ``attend(q, k, v, select=...)``): in decode the
    chosen ``pages`` [B, Hkv, W] and ``vpos`` [B, Hkv] of
    ``select_decode``; in prefill the rows' segment sums ``seg_rows`` [B,
    NS, Hkv * hd], from which each tile of queries chooses its own."""

    cfg: SparseConfig
    pages: jax.Array | None = None
    vpos: jax.Array | None = None
    seg_rows: jax.Array | None = None


# ------------------------------------------------- the plane of segments


def write_segments(segs: jax.Array, layer: int, k: jax.Array,
                   pos: jax.Array, tables: jax.Array, cfg: SparseConfig,
                   valid: jax.Array | None = None) -> jax.Array:
    """``segs`` ``[n_layer, num_blocks, segments, row]`` float32 with the
    sums of the segments that ``k`` touches brought up to date.

    Decode (``k`` [B, row], ``pos`` [B]): the token's segment is set where
    the token opens it, else added to. A chunk (``k`` [B, S, row], ``pos``
    [B, S] with ``pos[:, 0]`` a multiple of ``kernel_stride``, ``valid``
    [B, S] its real tokens, right-padded): every segment the chunk's real
    tokens touch is SET to their sum (a partial last segment too: the
    decode steps that follow add to it)."""
    s, Bs = cfg.kernel_stride, cfg.block_size
    NB = tables.shape[1]
    with jax.named_scope("sparse_select"):
        if k.ndim == 2:
            blk = jnp.take_along_axis(
                tables, (pos // Bs)[:, None], axis=1)[:, 0]
            si = (pos % Bs) // s
            old = jnp.where((pos % s == 0)[:, None], 0.0,
                            segs[layer, blk, si])
            return segs.at[layer, blk, si].set(old + k.astype(jnp.float32))
        B, S, row = k.shape
        pad = -S % s
        k32 = jnp.where(valid[..., None], k, 0).astype(jnp.float32)
        if pad:
            k32 = jnp.pad(k32, ((0, 0), (0, pad), (0, 0)))
            valid = jnp.pad(valid, ((0, 0), (0, pad)))
        n = (S + pad) // s
        sums = k32.reshape(B, n, s, row).sum(axis=2)
        first = pos[:, :1] + s * jnp.arange(n, dtype=pos.dtype)[None, :]
        touched = valid.reshape(B, n, s)[:, :, 0]
        blk = jnp.take_along_axis(
            tables, jnp.minimum(first // Bs, NB - 1), axis=1)
        blk = jnp.where(touched, blk, 0)
        si = jnp.where(touched, (first % Bs) // s, 0)
        return segs.at[layer, blk, si].set(sums)


def gather_segments(segs: jax.Array, layer: int,
                    tables: jax.Array) -> jax.Array:
    """A row's segment sums through its table: [B, NB * segments, row]."""
    B, NB = tables.shape
    with jax.named_scope("sparse_select"):
        return segs[layer, tables].reshape(B, NB * segs.shape[2], -1)


# ------------------------------------------------------------ selection


def block_scores(q: jax.Array, seg_rows: jax.Array, pos: jax.Array,
                 cfg: SparseConfig, n_kv_head: int, scale: float):
    """``R`` [B, S, Hkv, NB] float32 for queries ``q`` [B, S, Hq, hd] at
    ``pos`` [B, S] over a row's segment sums ``seg_rows`` [B, NS, Hkv *
    hd]: steps 1-4 above (``-inf`` for a block past the query's own)."""
    B, S, Hq, hd = q.shape
    NS = seg_rows.shape[1]
    per, K, s, Bs = cfg.segments, cfg.kernel_size, cfg.kernel_stride, \
        cfg.block_size
    NB = NS // per
    G = Hq // n_kv_head
    # q . c_j = (q . seg_j + q . seg_{j+1}) / K: the products are taken
    # against the SEGMENTS as gathered (a head's keys a lane slice of the
    # row) and the shift by one segment is made on the scores, which are a
    # hundredth of the keys' bytes; the last j has no second half yet
    qg = q.reshape(B, S, n_kv_head, G, hd)
    segs = seg_rows.astype(q.dtype)
    by_seg = jnp.stack([
        jnp.einsum("bsgd,bjd->bsgj", qg[:, :, h],
                   segs[..., h * hd:(h + 1) * hd],
                   preferred_element_type=jnp.float32)
        for h in range(n_kv_head)], axis=2)               # [B,S,Hkv,G,NS]
    sc = (by_seg + jnp.pad(by_seg[..., 1:], ((0, 0),) * 4 + ((0, 1),))) * (
        scale / K)
    j = jnp.arange(NS, dtype=jnp.int32)
    visible = (s * j + K - 1)[None, None, :] <= pos[..., None]   # [B, S, NS]
    sc = jnp.where(visible[:, :, None, None], sc, -jnp.inf)
    top = jnp.max(sc, axis=-1, keepdims=True)
    e = jnp.exp(sc - jnp.where(jnp.isfinite(top), top, 0.0))
    den = jnp.sum(e, axis=-1, keepdims=True)
    r = jnp.sum(e / jnp.where(den > 0, den, 1.0), axis=3)    # [B,S,Hkv,NS]
    r = jnp.where(visible[:, :, None], r, -jnp.inf)
    # the compressed keys that overlap block b: ``back`` before its first
    back = (K - 1) // s
    r = jnp.pad(r, ((0, 0),) * 3 + ((back, 0),), constant_values=-jnp.inf)
    R = lax.reduce_window(
        r, -jnp.inf, lax.max, (1, 1, 1, per + back), (1, 1, 1, per),
        "VALID")                                             # [B,S,Hkv,NB]
    b = jnp.arange(NB, dtype=jnp.int32)
    own = (pos // Bs)[..., None]                               # [B, S, 1]
    forced = (b < cfg.init_blocks) | (
        (b > own - cfg.window_blocks) & (b <= own))
    R = jnp.where(forced[:, :, None], jnp.inf, R)
    return jnp.where((b <= own)[:, :, None], R, -jnp.inf)


def chosen_mask(R: jax.Array, pos: jax.Array, cfg: SparseConfig) -> jax.Array:
    """Step 5 as a MASK [B, S, Hkv, NB] over the blocks, without a sort of
    the scores (a sort of 832 scores a (query, K/V head) was 10.5 of a
    chunk's 15 ms of selection a layer on the chip): a query below
    ``dense_len`` gets every block up to its own; one at or past it the
    ``topk`` of largest ``R``. ``R`` is >= 0 or +-inf, so its bits order
    as integers: the ``topk``-th largest is found a bit at a time (31
    counts), and the blocks that TIE with it are taken from the lower
    index up. The ONE place the top-k and its tie rule live: a chunk
    attends under the mask, a decode row under its list
    (``choose_blocks``)."""
    NB = R.shape[-1]
    k = min(cfg.topk, NB)
    key = jnp.where(R == -jnp.inf, -1,
                    lax.bitcast_convert_type(R.astype(jnp.float32),
                                             jnp.int32))
    kth = jnp.zeros(R.shape[:-1] + (1,), jnp.int32)
    for bit in range(30, -1, -1):
        higher = kth | (1 << bit)
        enough = jnp.sum(key >= higher, axis=-1, keepdims=True) >= k
        kth = jnp.where(enough, higher, kth)
    above = key > kth
    tied = key == kth
    left = k - jnp.sum(above, axis=-1, keepdims=True)
    sparse = above | (tied & (jnp.cumsum(tied, axis=-1) <= left))
    b = jnp.arange(NB, dtype=jnp.int32)
    own = (pos // cfg.block_size)[..., None, None]
    dense = (pos < cfg.dense_len)[..., None, None]
    return jnp.where(dense, b <= own, sparse)


def choose_blocks(R: jax.Array, pos: jax.Array, cfg: SparseConfig):
    """``chosen_mask`` as LISTS: ``(blocks, n)``, per (row, query, K/V
    head) the chosen blocks' LOGICAL indices ``[.., W]`` int32 ascending
    (``W = list_width``; what is past ``n`` is padding, ``NB``) and their
    count ``n [..]``. The query's own block is always the last of the
    list. For a decode step's one query a row: the sort is of block
    indices, a (row, K/V head)."""
    NB = R.shape[-1]
    W = cfg.list_width
    mask = chosen_mask(R, pos, cfg)
    b = jnp.arange(NB, dtype=jnp.int32)
    blocks = jnp.sort(jnp.where(mask, b, NB), axis=-1)
    blocks = jnp.pad(blocks, ((0, 0),) * 3 + ((0, max(W - NB, 0)),),
                     constant_values=NB)[..., :W]
    return blocks, jnp.sum(blocks < NB, axis=-1).astype(jnp.int32)


def select_decode(q: jax.Array, seg_rows: jax.Array, pos: jax.Array,
                  tables: jax.Array, cfg: SparseConfig, n_kv_head: int,
                  scale: float):
    """One query a row: ``q`` [B, Hq, hd] at ``pos`` [B]. Returns
    ``(pages [B, Hkv, W], vpos [B, Hkv], counts)``: the chosen blocks'
    PHYSICAL ids in ascending logical order (block 0 past the list's end),
    the query's position in the list's own token order (the own block is
    last: ``(n - 1) * Bs + pos % Bs``), and ``counts`` ``{"attended",
    "visible", "sparse"}`` [B]: blocks attended and blocks up to the own
    one, summed over K/V heads, and whether the row is past ``dense_len``."""
    NB = tables.shape[1]
    with jax.named_scope("sparse_select"):
        R = block_scores(q[:, None], seg_rows, pos[:, None], cfg, n_kv_head,
                         scale)
        blocks, n = choose_blocks(R, pos[:, None], cfg)
        blocks, n = blocks[:, 0], n[:, 0]                 # [B, Hkv, W]
        pages = jnp.take_along_axis(
            tables[:, None, :], jnp.minimum(blocks, NB - 1), axis=2)
        pages = jnp.where(blocks < NB, pages, 0)
        vpos = (n - 1) * cfg.block_size + (pos % cfg.block_size)[:, None]
        counts = {
            "attended": jnp.sum(n, axis=1),
            "visible": n_kv_head * (pos // cfg.block_size + 1),
            "sparse": pos >= cfg.dense_len,
        }
        return pages.astype(jnp.int32), vpos.astype(jnp.int32), counts


# ------------------------------------------ attention over chosen pages


def _lanes(pool: jax.Array, hd: int):
    """A pool as handed in, lane-dense ``[L, NBLK, Bs, Hkv * hd]``."""
    if pool.ndim == 5:
        pool = pool.reshape(*pool.shape[:3], -1)
    return pool, pool.shape[-1] // hd


def sparse_decode_attention_xla(q, k_pool, v_pool, pages, vpos, layer, *,
                                scale=None):
    """The semantics of ``paged_attention_sparse`` as a gather: q [B, Hq,
    hd]; ``pages`` [B, Hkv, W] physical ids, ``vpos`` [B, Hkv] the last
    attended token in the list's order. Returns [B, Hq, hd]."""
    B, Hq, hd = q.shape
    k_pool, Hkv = _lanes(k_pool, hd)
    v_pool, _ = _lanes(v_pool, hd)
    Bs = k_pool.shape[2]
    W = pages.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    def head_rows(pool):
        x = pool[layer, pages]                       # [B, Hkv, W, Bs, row]
        x = x.reshape(B, Hkv, W * Bs, Hkv, hd)
        h = jnp.arange(Hkv)
        return x[:, h, :, h].transpose(1, 0, 2, 3)   # [B, Hkv, W * Bs, hd]

    k, v = head_rows(k_pool), head_rows(v_pool)
    qg = q.reshape(B, Hkv, Hq // Hkv, hd)
    s = jnp.einsum("bkgd,bktd->bkgt", qg, k,
                   preferred_element_type=jnp.float32) * scale
    t = jnp.arange(W * Bs, dtype=jnp.int32)
    seen = t[None, None, :] <= vpos[..., None]
    p = jax.nn.softmax(jnp.where(seen[:, :, None], s, NEG_INF), axis=-1)
    o = jnp.einsum("bkgt,bktd->bkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Hq, hd).astype(q.dtype)


def _sparse_decode_kernel(
    pages_ref,    # scalar prefetch: [B * Hkv, W] int32 chosen pages
    vpos_ref,     # scalar prefetch: [B * Hkv] int32 last attended token
    layer_ref,    # scalar prefetch: [1] int32, the pool's layer
    q_ref,        # [1, Hkv, G, hd], pre-scaled (scale * log2 e)
    k_hbm,        # the whole pool in HBM: a page [Bs, Hkv * hd] at
    v_hbm,        # [layer, id]
    o_ref,        # [1, Hkv, G, hd]
    k_buf,        # [2, P * Bs, hd]: two slots of ONE head's lanes
    v_buf,
    sems,         # DMA semaphores, one a slot
    m_scr,        # [G, 1]
    l_scr,        # [G, 1]
    acc_scr,      # [G, hd]
    *,
    block_size: int,
    pages: int,
):
    """One row: each K/V head in turn walks ITS list of pages, ``pages`` a
    compute block, copying only that head's ``hd`` lanes of each page (the
    next block's while this one computes), under a causal mask in the
    list's own token order. The running softmax is the by-head kernel's
    (ops/paged_attention.py ``_paged_attention_kernel``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    layer = layer_ref[0]
    n_head, G, hd = q_ref.shape[1:]
    bs, T = block_size, pages * block_size
    div, lmin = lax.div, lax.min

    for h in range(n_head):
        row = b * n_head + h
        vpos = vpos_ref[row]
        last = div(vpos, bs)          # the list's last attended entry
        hi = div(last, pages) + 1     # compute blocks

        def copies(i, op, row=row, last=last, h=h):
            slot = lax.rem(i, 2)
            base = i * pages

            def page(p, carry):
                src = pages_ref[row, base + p]
                dst = pl.ds(pl.multiple_of(p * bs, bs), bs)
                for pool, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                    copy = pltpu.make_async_copy(
                        pool.at[layer, src, :, pl.ds(h * hd, hd)],
                        buf.at[slot, dst], sems.at[slot])
                    copy.start() if op == "start" else copy.wait()
                return carry

            lax.fori_loop(0, lmin(last + 1 - base, pages), page, 0)

        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        # a page of a visited block that no copy wrote: 0 x V must stay 0
        v_buf[...] = jnp.zeros_like(v_buf)

        def block(i, vpos=vpos, copies=copies, h=h):
            slot = lax.rem(i, 2)
            copies(i, "wait")
            t = i * T + lax.broadcasted_iota(jnp.int32, (G, T), 1)
            s = lax.dot_general(
                q_ref[0, h], k_buf[slot], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)            # [G, T]
            s = jnp.where(t <= vpos, s, NEG_INF)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            if q_ref.dtype == jnp.bfloat16:
                p = jnp.exp2((s - m_new).astype(jnp.bfloat16))
            else:
                p = jnp.exp2(s - m_new)
            alpha = jnp.exp2(m_prev - m_new)
            l_scr[...] = alpha * l_scr[...] + jnp.sum(
                p, axis=1, keepdims=True, dtype=jnp.float32)
            acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
                p.astype(v_buf.dtype), v_buf[slot],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[...] = m_new

        def step(i, carry, hi=hi, copies=copies, block=block):
            pl.when(i < hi)(functools.partial(copies, i, "start"))
            pl.when(i > 0)(functools.partial(block, i - 1))
            return carry

        lax.fori_loop(0, hi + 1, step, 0)
        l = l_scr[...]
        o_ref[0, h] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def sparse_decode_attention_pallas(q, k_pool, v_pool, pages, vpos, layer, *,
                                   scale=None, interpret=None):
    """``paged_attention_sparse``: q [B, Hq, hd] over the pages ``pages``
    [B, Hkv, W] (physical ids) of the WHOLE pools at ``layer``, each (row,
    K/V head) up to token ``vpos`` [B, Hkv] of its list. Returns [B, Hq,
    hd]. Reads ``ceil((vpos + 1) / Bs)`` pages of ONE head's lanes a (row,
    head) and nothing else of the pools."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = pallas_interpret()
    B, Hq, hd = q.shape
    k_pool, Hkv = _lanes(k_pool, hd)
    v_pool, _ = _lanes(v_pool, hd)
    Bs = k_pool.shape[2]
    W = pages.shape[-1]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    # pages a compute block: 512 tokens where the list is wide enough (the
    # by-head kernel's few-row rule, ``_BLOCK_TOKENS_MOST``)
    P = 1
    while 2 * P * Bs <= 512 and 2 * P <= W:
        P *= 2
    Wp = -(-W // P) * P
    pages = jnp.pad(pages.astype(jnp.int32),
                    ((0, 0), (0, 0), (0, Wp - W))).reshape(B * Hkv, Wp)
    qf = (q * jnp.asarray(scale * LOG2E, q.dtype)).reshape(B, Hkv, G, hd)
    q_map = lambda b, *refs: (b, 0, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, Hkv, G, hd), q_map),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, Hkv, G, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, P * Bs, hd), k_pool.dtype),
            pltpu.VMEM((2, P * Bs, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_sparse_decode_kernel, block_size=Bs, pages=P),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name=SPARSE_KERNEL_NAME,
        interpret=interpret,
    )(pages, vpos.astype(jnp.int32).reshape(B * Hkv),
      jnp.asarray(layer, jnp.int32).reshape(1), qf, k_pool, v_pool)
    return out.reshape(B, Hq, hd)


def sparse_decode_attention(q, k_pool, v_pool, pages, vpos, layer, *,
                            backend: str = "auto", scale=None):
    """The decode step's selected-page attention, by backend (the
    ``attention_backend`` knob, as ``decode_attention``)."""
    from ray_tpu.ops.paged_attention import resolve_backend

    if resolve_backend(backend) == "pallas":
        return sparse_decode_attention_pallas(
            q, k_pool, v_pool, pages, vpos, layer, scale=scale)
    return sparse_decode_attention_xla(
        q, k_pool, v_pool, pages, vpos, layer, scale=scale)


def _select_prefill_kernel(
    tables_ref,   # scalar prefetch: [B, NBp] int32 block tables
    qmax_ref,     # scalar prefetch: [B, nqb] int32 frontier a q tile
    live_ref,     # scalar prefetch: [B * nqb * ncb] int32: a query of the
                  # tile chose a page of compute block i
    layer_ref,    # scalar prefetch: [1] int32, the pool's layer
    q_ref,        # [1, Hkv, R, hd], pre-scaled; GROUP-MAJOR rows: row r is
                  # group member r // qb of the tile's query r % qb
    pos_ref,      # [1, qb, 1] int32 true position of each query
    sel_ref,      # [1, 1, ncb, Hkv, P, qb]: 1 where query q chose page p of
                  # compute block i for K/V head h
    k_hbm,        # the whole pools in HBM: a page [Bs, Hkv * hd] at
    v_hbm,        # [layer, id]
    o_ref,        # [1, Hkv, R, hd]
    k_buf,        # [2, P * Bs, Hkv * hd]
    v_buf,
    sems,
    m_scr,        # [Hkv, R, 1]
    l_scr,
    acc_scr,      # [Hkv, R, hd]
    *,
    block_size: int,
    pages: int,
    group: int,
):
    """The by-head paged kernel's walk and running softmax
    (ops/paged_attention.py ``_paged_attention_kernel``) under one more
    mask: a (query, K/V head) attends a page only where its selection
    chose it. A compute block of ``pages`` pages that no query of the tile
    chose is neither copied nor computed. Inside a block the mask is built
    a QUERY at a time, [qb, T]: the [query, page] choices spread over a
    page's tokens by one small product, and the causal compare; a tile's
    rows are group-major, so the scores [G x qb, T] are viewed [G, qb, T]
    and the mask is broadcast over the group (built a ROW at a time, by
    products over [G x qb] rows, it was three MXU passes a block beside the
    attention's four)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    j = pl.program_id(1)
    layer = layer_ref[0]
    n_head, rows, hd = q_ref.shape[1:]
    ncb, qb = sel_ref.shape[2], sel_ref.shape[5]
    n_entries = tables_ref.shape[1]
    bs, T = block_size, pages * block_size
    div, lmin = lax.div, lax.min
    last = lmin(div(qmax_ref[b, j], bs), n_entries - 1)
    hi = div(last, pages) + 1
    tile = (b * pl.num_programs(1) + j) * ncb

    def live(i):
        return live_ref[tile + lmin(i, ncb - 1)] > 0

    def copies(i, op):
        slot = lax.rem(i, 2)
        base = i * pages

        def page(p, carry):
            src = tables_ref[b, base + p]
            dst = pl.ds(pl.multiple_of(p * bs, bs), bs)
            for pool, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                copy = pltpu.make_async_copy(
                    pool.at[layer, src], buf.at[slot, dst], sems.at[slot])
                copy.start() if op == "start" else copy.wait()
            return carry

        lax.fori_loop(0, lmin(last + 1 - base, pages), page, 0)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    v_buf[...] = jnp.zeros_like(v_buf)
    # token t of a block lies in page t // bs
    of_page = (div(lax.broadcasted_iota(jnp.int32, (pages, T), 1), bs)
               == lax.broadcasted_iota(jnp.int32, (pages, T), 0)
               ).astype(jnp.bfloat16)

    def block(i):
        slot = lax.rem(i, 2)
        copies(i, "wait")
        t = i * T + lax.broadcasted_iota(jnp.int32, (qb, T), 1)
        causal = t <= pos_ref[0]                           # [qb, T]
        chose = sel_ref[0, 0, i]                           # [Hkv, P, qb]
        masks, scores, values = [], [], []
        for h in range(n_head):
            by_token = lax.dot_general(
                chose[h].astype(jnp.bfloat16), of_page,
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # [qb, T]
            masks.append(jnp.logical_and(causal, by_token > 0.5))
            k = k_buf[slot, :, h * hd:(h + 1) * hd]
            values.append(v_buf[slot, :, h * hd:(h + 1) * hd])
            scores.append(lax.dot_general(
                q_ref[0, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))
        # [Hkv, G, qb, T] under a mask [Hkv, 1, qb, T]
        seen = jnp.stack(masks)[:, None]
        s = jnp.where(seen, jnp.stack(scores).reshape(
            n_head, group, qb, T), NEG_INF).reshape(n_head, rows, T)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        if q_ref.dtype == jnp.bfloat16:
            p = jnp.exp2((s - m_new).astype(jnp.bfloat16))
        else:
            p = jnp.exp2(s - m_new)
        # a row with nothing seen yet has m_new = NEG_INF and p = 1 at its
        # masked tokens: they must count for nothing
        p = jnp.where(seen, p.reshape(n_head, group, qb, T),
                      jnp.zeros((), p.dtype)).reshape(n_head, rows, T)
        alpha = jnp.exp2(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(
            p, axis=2, keepdims=True, dtype=jnp.float32)
        p = p.astype(values[0].dtype)
        acc_scr[...] = acc_scr[...] * alpha + jnp.stack([
            lax.dot_general(
                lax.index_in_dim(p, h, 0, keepdims=False), values[h],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            for h in range(n_head)])
        m_scr[...] = m_new

    def step(i, carry):
        pl.when(jnp.logical_and(i < hi, live(i)))(
            functools.partial(copies, i, "start"))
        pl.when(jnp.logical_and(i > 0, live(i - 1)))(
            functools.partial(block, i - 1))
        return carry

    lax.fori_loop(0, hi + 1, step, 0)
    l = l_scr[...]
    o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(
        o_ref.dtype)


def select_prefill_attention_pallas(q, k_pool, v_pool, tables, pos, chosen,
                                    layer, *, scale=None,
                                    q_block: int = PREFILL_Q_TILE,
                                    interpret=None):
    """``paged_attention_select``: a chunk's queries ``q`` [B, S, Hq, hd]
    at ``pos`` [B, S] over the WHOLE pools at ``layer``, each (query, K/V
    head) attending the table entries ``chosen`` [B, S, Hkv, NB] marks (and
    of those the tokens at or below its position). Returns [B, S, Hq, hd].
    Copies the pages some query of a tile chose, up to the tile's frontier:
    a call costs what it attends, not the table's width."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.ops.paged_attention import _frontiers, _q_tiles

    if interpret is None:
        interpret = pallas_interpret()
    B, S, Hq, hd = q.shape
    k_pool, Hkv = _lanes(k_pool, hd)
    v_pool, _ = _lanes(v_pool, hd)
    Bs, row = k_pool.shape[2:]
    NB = tables.shape[1]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    q, pos, qb, nqb = _q_tiles(q, pos, min(q_block, S))
    Sp = nqb * qb
    R = qb * G
    P = 1
    while 2 * P * Bs <= 256 and 2 * P <= NB:
        P *= 2
    ncb = -(-NB // P)
    NBp = ncb * P
    tables = jnp.pad(tables.astype(jnp.int32), ((0, 0), (0, NBp - NB)))
    chosen = jnp.pad(chosen, ((0, 0), (0, Sp - S), (0, 0), (0, NBp - NB)))
    chose = chosen.reshape(B, nqb, qb, Hkv, ncb, P)
    live = jnp.any(chose, axis=(2, 3, 5)).astype(jnp.int32).reshape(-1)
    sel = chose.transpose(0, 1, 4, 3, 5, 2).astype(jnp.bfloat16)
    # a tile's rows GROUP-MAJOR: [B, Hkv, tile, G, qb, hd]
    qf = (q * jnp.asarray(scale * LOG2E, q.dtype)).reshape(
        B, nqb, qb, Hkv, G, hd).transpose(0, 3, 1, 4, 2, 5).reshape(
            B, Hkv, Sp * G, hd)
    pos_rows = pos.reshape(B, Sp, 1)
    qmax, _ = _frontiers(pos, nqb)
    q_map = lambda b, j, *refs: (b, 0, j, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, nqb),
        in_specs=[
            pl.BlockSpec((1, Hkv, R, hd), q_map),
            pl.BlockSpec((1, qb, 1), lambda b, j, *refs: (b, j, 0)),
            pl.BlockSpec((1, 1, ncb, Hkv, P, qb),
                         lambda b, j, *refs: (b, j, 0, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hkv, R, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, P * Bs, row), k_pool.dtype),
            pltpu.VMEM((2, P * Bs, row), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((Hkv, R, 1), jnp.float32),
            pltpu.VMEM((Hkv, R, 1), jnp.float32),
            pltpu.VMEM((Hkv, R, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_select_prefill_kernel, block_size=Bs, pages=P,
                          group=G),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, Sp * G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=64 * 1024 * 1024),
        name=SELECT_KERNEL_NAME,
        interpret=interpret,
    )(tables, qmax, live, jnp.asarray(layer, jnp.int32).reshape(1), qf,
      pos_rows, sel, k_pool, v_pool)
    out = out.reshape(B, Hkv, nqb, G, qb, hd).transpose(0, 2, 4, 1, 3, 5)
    return out.reshape(B, Sp, Hq, hd)[:, :S]


def sparse_prefill_attention(q, k_pool, v_pool, tables, pos, valid,
                             seg_rows, layer, cfg: SparseConfig, *,
                             backend: str = "auto", scale=None):
    """A chunk's queries ``q`` [B, S, Hq, hd] at ``pos`` [B, S] (``valid``
    [B, S] the real ones) over the pools at ``layer``, the chunk's own K/V
    and segments already written. A chunk with no query at or past
    ``dense_len`` takes the dense paged path (``prefill_attention``). Any
    other: a tile of ``PREFILL_Q_TILE`` queries at a time chooses its blocks
    (queries below ``dense_len`` among them: every block up to their own),
    and the chunk attends what was chosen: under the Pallas backend with
    ``paged_attention_select`` over the pool where it stands, else (the
    CPU's formulation, the same mathematics) a tile at a time against the
    rows' gathered context under the per-(query, K/V head, block) mask.
    Returns [B, S, Hq, hd]."""
    from ray_tpu.ops.paged_attention import prefill_attention, resolve_backend

    B, S, Hq, hd = q.shape
    k_pool, Hkv = _lanes(k_pool, hd)
    v_pool, _ = _lanes(v_pool, hd)
    Bs = k_pool.shape[2]
    NB = tables.shape[1]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    safe = jnp.where(valid, pos, 0)
    kernel = resolve_backend(backend) == "pallas"

    def dense(_):
        return prefill_attention(q, k_pool, v_pool, tables, safe,
                                 scale=scale, backend=backend, layer=layer)

    def sparse(_):
        tq = min(PREFILL_Q_TILE, S)
        n = -(-S // tq)
        pad = n * tq - S
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pp = jnp.pad(safe, ((0, 0), (0, pad)))

        def choose(qt, pt):
            with jax.named_scope("sparse_select"):
                return chosen_mask(
                    block_scores(qt, seg_rows, pt, cfg, Hkv, scale), pt, cfg)

        tiles = (qp.reshape(B, n, tq, Hq, hd).transpose(1, 0, 2, 3, 4),
                 pp.reshape(B, n, tq).transpose(1, 0, 2))
        if kernel:
            chosen = lax.map(lambda a: choose(*a), tiles)  # [n,B,tq,Hkv,NB]
            chosen = chosen.transpose(1, 0, 2, 3, 4).reshape(
                B, n * tq, Hkv, NB)[:, :S]
            return select_prefill_attention_pallas(
                q, k_pool, v_pool, tables, safe, chosen, layer, scale=scale)
        k = k_pool[layer, tables].reshape(B, NB * Bs, Hkv, hd)
        v = v_pool[layer, tables].reshape(B, NB * Bs, Hkv, hd)
        t = jnp.arange(NB * Bs, dtype=jnp.int32)

        def tile(args):
            qt, pt = args                      # [B, tq, Hq, hd]; [B, tq]
            chosen = choose(qt, pt)            # [B, tq, Hkv, NB]
            with jax.named_scope("sparse_prefill_attention"):
                seen = jnp.repeat(chosen, Bs, axis=-1) & (
                    t <= pt[..., None])[:, :, None]
                s = jnp.einsum(
                    "bqkgd,btkd->bkqgt", qt.reshape(B, tq, Hkv, G, hd), k,
                    preferred_element_type=jnp.float32) * scale
                seen = seen.transpose(0, 2, 1, 3)[:, :, :, None]
                p = jax.nn.softmax(jnp.where(seen, s, NEG_INF), axis=-1)
                o = jnp.einsum("bkqgt,btkd->bqkgd", p.astype(v.dtype), v,
                               preferred_element_type=jnp.float32)
            return o.reshape(B, tq, Hq, hd).astype(q.dtype)

        out = lax.map(tile, tiles)
        return out.transpose(1, 0, 2, 3, 4).reshape(B, n * tq, Hq, hd)[:, :S]

    return lax.cond(jnp.max(safe) >= cfg.dense_len, sparse, dense, None)
