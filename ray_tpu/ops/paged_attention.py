"""Fused Pallas paged-attention kernel (block-table-aware, GQA-compact).

The TPU counterpart of ``ops/kv_cache.py``'s ``paged_attention`` and
``paged_prefill_attention``. The XLA formulation gathers every sequence's
ENTIRE padded context (``gather_kv`` → ``[B, NB*block_size, H_kv, hd]``
in HBM) before a masked softmax. This kernel is the vLLM PagedAttention
shape instead: one ``pallas_call`` that walks each sequence's block
table and DMAs K/V **directly from the paged pool**
(``[num_blocks, block_size, n_kv_head * hd]``), several pages at a time,
into VMEM.
Nothing is ever materialized at the padded context length, no head is ever
repeated.

ONE kernel serves decode, fresh prefill, chunked prefill at true-position
offsets and the verify windows of every pool of K and V by head: decode is
the S = 1 case of the multi-token kernel (``paged_attention_pallas``
reshapes and calls ``paged_prefill_attention_pallas``). A pool in PLANES
(latent attention: one row a token that every head reads, as key and as
value) has a kernel body of its own at the end of this file
(``_latent_attention_kernel``, PR 46): the same table walk and running
softmax, but ONE head of 64 to 1,024 rows a tile, whose cost on the chip
was starting its many small page copies in turn with the products.

THE POOL IS READ WHERE IT STANDS: the cached step (models/cached.py) hands
every entry point here the WHOLE pools and ``layer=``, an int32 scalar that
reaches the kernel as one more scalar-prefetch word; a page is
``pool[layer, id]`` and no ``pool[layer]`` exists outside the kernel. That
holds for every pool the cache manager allocates, because there is ONE
stored layout (``pool_shape``): lane-dense, a token's heads one row,
``[n_layer, num_blocks, block_size, n_kv_head * hd]``, whatever the count
and size of the heads (8 heads of 128: ``[.., 1024]``; 32 of 128: ``[..,
4096]``; 12 of 64: ``[.., 768]``; 4 of 128: ``[.., 512]``; a ``tp`` shard's
contiguous heads of the row). A page is then whole (8, 128) tiles, and a
compute block of pages IS the ``[tokens, n_kv_head * hd]`` tile the
products take, a head's keys a static lane slice of it. Up to PR 42 pools
of 8k heads of 128 were stored BY HEADS (``[.., n_kv_head, hd]``, whole
tiles too); a head's ``[tokens, hd]`` tile out of such a block is a strided
read of single rows, and timed alone on the chip that read cost three times
what the block's products and softmax cost (docs/MICROBENCHMARKS.md, PR 43).
Without ``layer=`` the pool is one layer's ``[num_blocks, block_size, ...]``.

Design (same playbook as ``ops/attention.py``'s flash kernels):

- TILING THE CHIP'S COMPILER ACCEPTS: Mosaic requires the last two
  dimensions of every block to be multiples of (8, 128) or to equal the
  array's, and slices a ref that stays in HBM only at whole such tiles;
  and the TPU runtime rests an array whose minor pair is not whole tiles
  (``[12, 64]``, ``[8, 64]``) in another order than the one written, so
  that XLA relays K and V around every call that wants them as written
  (PERF.md, PR 27, 29 and 31). Hence the stored shape above: a page is
  fetched whole, ALL its KV heads at once, by the kernel's own copy; the
  head loop runs inside the kernel (static, unrolled), and head ``h``'s
  ``[tokens, hd]`` tile is the static lane slice ``[:, h * hd:(h + 1) *
  hd]`` of the block (``Hkv`` and ``hd`` are read off q's shape and the
  pool's row). Two shapes fall outside: a pool handed in BY HEADS (a
  test's own array; the cache manager allocates none) is viewed
  lane-dense, on the chip a relayout of it; and a row that is not whole
  lanes (an odd count of heads of 64: GPT-2's 12 over ``tp`` = 4) Mosaic
  refuses to slice ("Slice shape
  along dimension 3 must be aligned to tiling (128), but is 192"): that
  layer's slab is padded to whole lanes for the call. The quantized pools'
  data planes (int8 / fp8) go the same way as the plain ones; their
  ``[num_blocks, block_size, H_kv]`` scale planes have no whole tile a
  page, so each row's scales are
  gathered through its table by XLA (``B x NB x block_size x H_kv``
  floats) and ride in as a lane-dense ``[H_kv, tokens]`` tile a block,
  applied to the SCORES and the probabilities: ``q . (k * s_t) = (q . k)
  * s_t``. The q/out tiles are ``(1, H_kv, q_block*G, hd)`` with
  ``q_block*G`` a multiple of 8 (or the whole chunk), and the per-row
  positions arrive as a ``[B, S*G, 1]`` column so the causal mask is a
  lane broadcast.
- BLOCK-TABLE WALK BY THE KERNEL'S OWN COPIES: grid ``(B, q_blocks)``; the
  K and V pools stay in HBM (``memory_space=pl.ANY``). The block table
  and the per-(b, q-block) causal frontier / window floor (``qmax`` /
  ``qmin``, reduced from the per-row positions) ride in as
  ``PrefetchScalarGridSpec`` scalar operands. A grid step loops over the
  row's COMPUTE BLOCKS of P consecutive table entries (``P * block_size``
  tokens, 128 where the table is wide enough; ``_compute_block`` derives
  P from the shapes and the VMEM they need, down to 1): it starts one
  async copy per attended page of the block, ``pool[tables[b, e]]`` into a
  two-slot VMEM scratch ``[2, P, *page]``, all in flight together, and
  starts block i+1's copies before it computes block i. Entries past the
  frontier, or — windowed — below the floor, start no copy, and the loop
  runs only from the floor's block to the frontier's: a call costs the
  pages it attends, not the table's width, and a chunk of C queries
  against a T-token context costs O(C·T_attended). Per head the block is
  ONE ``[P * block_size, hd]`` tile and one score matmul; the
  running-softmax update is made once a block for all heads together, on
  ``[H_kv, R, P * block_size]`` scores.
- A PRODUCT A HEAD AT EVERY COUNT OF ROWS, ONE PATH: ISSUE 43 asked for ONE
  product an operand for ALL heads where a q tile has few rows (decode: a
  block-diagonal query ``[H_kv * R, H_kv * hd]`` against the whole tile).
  Timed on a v5e over the same lane-dense tile it made the call 2% to 11%
  LONGER at heads of 128 (its value product computes every head's columns
  for every row and keeps an ``H_kv``-th) and 14% to 18% shorter at heads
  of 64 (two heads share each stationary tile), and end to end it did not
  clear the rule set for a second path (PERF.md, PR 43; ROADMAP S5(b) keeps
  the heads-of-64 reading). A prefill tile (128 x G rows) could never take
  it: block-diagonal, its zeros would multiply the MXU's real work by
  ``H_kv``, and hundreds of rows already pay for a head's stationary tile.
- GQA COMPACTION: queries reshape ``[B, S, H_q, hd] → [B, H_kv, S*G, hd]``
  (``G = H_q // H_kv``; met so far: 1, 4, 6, 7, 8, 128); a head step takes
  the whole query group against the SHARED KV tile with one dot, so GQA is a
  free extra row dimension (7 rows pad to 8) instead of a ``rep``× KV copy.
- FLASH RUNNING SOFTMAX: per-(kv-head, row) running max / sum /
  accumulator live in VMEM scratch across the loop over blocks (the max
  and sum as a column a head, ``[H_kv, R, 1]``); the
  softmax is base-2 with ``scale * log2(e)`` folded into q once (exp2
  instead of exp, no rescale pass), bf16 inputs run the exp2 at half
  precision. A static ``window=`` adds the sliding-window variant that
  also skips pages below the window floor.

THE KERNEL'S TEXT IS PAID FOR AT EVERY START: each process traces and
lowers it anew for each of its ~50 step programs, cache hit or not, and
the engine's warm start is an end-to-end metric (``setup_s``). So the page
copies are a loop and not P unrolled branches, the walk's scalars use
``lax.div / max / min`` (``//`` and ``jnp.clip`` lower through ``sign``),
and only the two matmuls are unrolled per head (PERF.md, PR 27).

SHARDING: a compiled kernel is an opaque custom call that GSPMD cannot
partition. ``ShardedExecutor`` (serve/llm/executor.py) splits the pool's
KV-head axis (a lane-dense pool's row, into contiguous heads a device) over
``tp`` and runs its steps under ``jax.set_mesh``; the dispatchers below see
that mesh and wrap the kernel in ``shard_map`` over the head axis, so each
device runs the identical program over its local heads and its local share
of the pool.

``decode_attention`` / ``prefill_attention`` are the dispatchers the model
steps call: the ``backend`` knob ("auto" | "xla" | "pallas") threads down
from ``EngineConfig.attention_backend`` via the model config, with "auto"
resolving to the Pallas kernel on TPU and the XLA formulation elsewhere.
The kernel is always compiled; only the test hook
``ops.attention.pallas_interpret`` runs it in the Pallas interpreter.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.attention import LOG2E, NEG_INF, pallas_interpret
from ray_tpu.ops.quantization import QuantizedKV

BACKENDS = ("auto", "xla", "pallas")


def resolve_backend(backend: str) -> str:
    """Normalize the attention_backend knob to a concrete backend."""
    if backend == "auto":
        return "pallas" if jax.devices()[0].platform == "tpu" else "xla"
    if backend not in ("xla", "pallas"):
        raise ValueError(
            f"attention_backend must be one of {BACKENDS}, got {backend!r}"
        )
    return backend


def _vmem_bytes(shape, dtype) -> int:
    """Bytes an array takes in VMEM: its last dimension padded to 128
    lanes, the one before it to the dtype's sublane tile (8 rows of 32
    bits; 16-bit types pack 16 rows a tile, 8-bit types 32)."""
    itemsize = jnp.dtype(dtype).itemsize
    sub = 8 * max(1, 4 // itemsize)
    *lead, rows, lanes = shape
    return (
        math.prod(lead) * -(-rows // sub) * sub * -(-lanes // 128) * 128
        * itemsize
    )


# What a compute block aims at. A tile of FEW rows (decode, verify) pays a
# fixed ~550 cycles a block for its chain (products -> max -> exp2 -> sum ->
# products -> accumulator), whatever the block holds, and each grid step's
# first block is copied with nothing to compute beside it: measured on a
# v5e over seven shapes (PERF.md, PR 43), the call is shortest where a
# block's K tile (V's the same) is about half a MiB: 512 tokens of a
# 1 KB row, 256 of a 2 KB row, and never fewer than a whole lane tile of
# scores, 128 (a 8 KB row). A tile of many rows (a whole MXU pass or more:
# prefill) pays for rescaling its [R, hd] accumulator once a block and keeps
# the two lane tiles of scores a block it has had since PR 27.
_BLOCK_TOKENS = 128
_BLOCK_TOKENS_MOST = 512
_BLOCK_BYTES = 512 * 1024
_MANY_ROWS = 128
# What a call may count on in VMEM (a v5e core has 128 MiB; the limit
# handed to the compiler is twice the count, for what it spills); its
# scoped default is 16 MiB, raised only as far as a call's shapes need.
_VMEM_CAP = 48 * 1024 * 1024
_VMEM_DEFAULT = 16 * 1024 * 1024


def pool_shape(n_layer: int, num_blocks: int, block_size: int, Hkv: int,
               hd: int) -> tuple[int, ...]:
    """The shape a K or V pool is STORED in (serve/llm/kv_cache.py
    allocates it): a token's heads ONE lane-dense row, ``[n_layer,
    num_blocks, block_size, Hkv * hd]``, for every count and size of heads.
    A page is then whole (8, 128) tiles wherever the row is whole lanes, rests
    in the order written and is copied where it stands (a minor pair ``[12,
    64]`` or ``[4, 128]`` would rest in another order and be relaid around
    every kernel call: PERF.md, PR 27, 29), and a compute block of it is the
    ``[tokens, Hkv * hd]`` tile the kernel multiplies as it lies, a head's
    keys a static lane slice of it (PR 43: a head's tile out of a block
    stored BY HEADS, ``[tokens, Hkv, hd]``, was a strided read that cost
    three times the products). A ``tp`` mesh splits the row into contiguous
    heads a device."""
    return (n_layer, num_blocks, block_size, Hkv * hd)


def _block_tokens(R: int, row_bytes: int) -> int:
    """The tokens a compute block aims at, for a q tile of ``R`` rows a
    head over K (or V) rows of ``row_bytes`` a token (see ``_BLOCK_BYTES``).
    A pool in planes has its own rule, ``_latent_tokens``."""
    if R >= _MANY_ROWS:
        return 2 * _BLOCK_TOKENS
    tokens = _BLOCK_TOKENS
    while (tokens < _BLOCK_TOKENS_MOST
           and 2 * tokens * row_bytes <= _BLOCK_BYTES):
        tokens *= 2
    return tokens


def _fit_pages(need, bs: int, tokens: int, NB: int):
    """``(P, need(P))``: the largest power of two of pages whose ``P *
    bs`` tokens stay within the ``tokens`` a block aims at, that a table of
    ``NB`` entries is wide enough for, and whose ``need(P)`` bytes of VMEM
    fit; a page a block is the floor."""
    P = 1
    while (
        2 * P * bs <= tokens and 2 * P <= NB and need(2 * P) <= _VMEM_CAP
    ):
        P *= 2
    return P, need(P)


def _compute_block(page, Hkv, hd, R, NB, q_dtype, kv_dtype, quantized):
    """``(P, vmem_bytes)``: the pages of one compute block and what the
    call then keeps in VMEM, from the shapes alone (``page`` is a page's
    shape in the pool, ``[bs, row]``). P is the largest power of two whose
    ``P * bs`` tokens stay within what a block aims at, that the table is
    wide enough for, and whose two-slot K/V scratch fits beside the q and
    out tiles, the positions, the accumulators and a block's scores; a
    page a block (P = 1) is the floor."""
    bs, row = page
    fixed = (
        # q and out tiles, double-buffered by the pipeline; positions
        4 * _vmem_bytes((Hkv, R, hd), q_dtype)
        + 2 * _vmem_bytes((R, 1), jnp.int32)
        # running max and sum, accumulator
        + 2 * _vmem_bytes((Hkv, R, 1), jnp.float32)
        + _vmem_bytes((Hkv, R, hd), jnp.float32)
    )
    if quantized:
        # a row's K and V scales, double-buffered: one lane a token
        fixed += 4 * _vmem_bytes((Hkv, NB * bs), jnp.float32)

    def need(p):
        # two slots of K and V pages; a block's scores and probabilities
        # for every head, its K and V tiles and its value product as values
        return fixed + 4 * p * _vmem_bytes(page, kv_dtype) + 2 * (
            _vmem_bytes((Hkv, R, p * bs), jnp.float32)
            + _vmem_bytes((p * bs, row), jnp.float32)
        ) + _vmem_bytes((Hkv, R, hd), jnp.float32)

    row_bytes = row * jnp.dtype(kv_dtype).itemsize
    return _fit_pages(need, bs, _block_tokens(R, row_bytes), NB)


def _kernel_name(window: int | None) -> str:
    """The name a call has in compiler dumps and device traces: the
    windowed calls have one of their own, so that a trace parts the
    sliding layers' time from the full layers'."""
    return "paged_attention" if window is None else "paged_attention_window"


# the latent calls' own name (``paged_latent_attention_pallas``): a trace
# then parts the time of a pool in planes from that of K and V by head
LATENT_KERNEL_NAME = "paged_attention_latent"


def _paged_attention_kernel(
    tables_ref,   # scalar prefetch: [B, NB] int32 block tables
    qmax_ref,     # scalar prefetch: [B, nqb] int32 frontier per q-block
    qmin_ref,     # scalar prefetch: [B, nqb] int32 floor per q-block
    layer_ref,    # scalar prefetch: [1] int32, the pool's layer
    q_ref,        # [1, Hkv, R, hd] — this (b, q-block)'s rows for every kv
                  # head, pre-scaled; row r = query (r // G) of the block,
                  # group member (r % G); R = q_block * G
    pos_ref,      # [1, rows, 1] int32 — true position of each row's query
    *rest,        # quantized: (ks_ref, vs_ref, k_hbm, v_hbm, o_ref, ...) —
                  # ks/vs [1, n_blocks, Hkv, T] f32, this row's per-(head,
                  # token) scales, gathered through its table; else (k_hbm,
                  # v_hbm, o_ref, ...). Then the scratch: (k_buf, v_buf,
                  # sems, m, l, acc).
                  # k_hbm / v_hbm: the whole pool, every layer, in HBM,
                  # a page [bs, Hkv * hd] at [layer, id]; k_buf / v_buf: the
                  # two-slot VMEM scratch of a block, [2, P * bs, row]
    block_size: int,
    pages: int,
    window: int | None,
    quantized: bool,
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if quantized:
        ks_ref, vs_ref, *rest = rest
    k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, m_scr, l_scr, acc_scr = rest

    b = pl.program_id(0)
    j = pl.program_id(1)
    layer = layer_ref[0]
    n_head, rows, hd = q_ref.shape[1:]
    n_entries = tables_ref.shape[1]
    bs, T = block_size, pages * block_size

    # The table entries this q-block attends: up to the page of its causal
    # frontier, and — windowed — from the page of the window floor of its
    # EARLIEST query. Entries outside start no copy; a compute block with
    # none is never visited, so the walk costs the pages attended and not
    # the table's width.
    # (lax.div / lax.max on these non-negative scalars, not ``//`` and
    # ``jnp.clip``: their sign handling is a tenth of what lowering this
    # kernel costs, and every process lowers it for each step program.)
    div, lmax, lmin = lax.div, lax.max, lax.min
    last = lmin(div(qmax_ref[b, j], bs), n_entries - 1)
    first = jnp.int32(0)
    if window is not None:
        first = div(lmax(qmin_ref[b, j] - (window - 1), 0), bs)
    lo, hi = div(first, pages), div(last, pages) + 1

    def copies(i, op):
        # block i's attended pages, each page one copy of K and one of V
        # into slot i % 2. A loop over the pages and not P unrolled
        # branches: every process traces and lowers the kernel's text anew
        # for each of its step programs, so it stays a one-page kernel's.
        slot = lax.rem(i, 2)
        base = i * pages

        def page(p, carry):
            src = tables_ref[b, base + p]
            dst = pl.ds(pl.multiple_of(p * bs, bs), bs)
            for pool, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                copy = pltpu.make_async_copy(
                    pool.at[layer, src], buf.at[slot, dst], sems.at[slot]
                )
                copy.start() if op == "start" else copy.wait()
            return carry

        lax.fori_loop(
            lmax(first - base, 0), lmin(last + 1 - base, pages), page, 0
        )

    def head(buf, slot, h):
        # head h's [T, hd] out of the block's [T, Hkv * hd] tile as it
        # lies: a static lane slice
        x = buf[slot, :, h * hd:(h + 1) * hd]
        return x.astype(jnp.float32) if quantized else x

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    # A page of a visited block that no copy wrote holds an older block's
    # page, or at a call's start nothing yet; the mask zeroes its
    # probabilities, and 0 x V must stay 0.
    v_buf[...] = jnp.zeros_like(v_buf)

    def step(i, carry):
        # block i's pages fly while block i - 1 computes
        pl.when(i < hi)(functools.partial(copies, i, "start"))
        pl.when(i > lo)(functools.partial(block, i - 1))
        return carry

    def block(i):
        slot = lax.rem(i, 2)
        copies(i, "wait")
        # per-ROW causal mask, shared by every head of the block
        pos_rows = pos_ref[0]                              # [rows, 1]
        t = i * T + lax.broadcasted_iota(jnp.int32, (rows, T), 1)
        mask = t <= pos_rows
        if window is not None:
            mask = jnp.logical_and(mask, t > pos_rows - window)
        # Only the two matmuls run head by head (the loop is unrolled: a
        # head's tile is a static slice); the running softmax is updated
        # for all heads at once on [Hkv, R, T]. What a head adds to the
        # kernel's text is what every process pays again, for each of its
        # step programs, to trace and lower it.
        k_v = [
            (head(k_buf, slot, h), head(v_buf, slot, h))
            for h in range(n_head)
        ]

        def q_head(h):
            # [R, hd], pre-scaled
            if quantized:
                return lax.convert_element_type(q_ref[0, h], jnp.float32)
            return q_ref[0, h]

        s = jnp.stack([
            lax.dot_general(
                q_head(h), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            for h, (k, _) in enumerate(k_v)
        ])                             # [Hkv, R, T]
        if quantized:
            # dequantize the SCORES: q . (k * scale_t) is
            # (q . k) * scale_t, a [1, T] row against R x T products
            # where scaling K would take T x hd
            s = s * ks_ref[0, i][:, None, :]
        s = jnp.where(mask[None], s, NEG_INF)
        m_prev = m_scr[...]                                # [Hkv, R, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        # bf16 inputs: exp2 at half precision, matching the flash
        # forward; f32 inputs keep a fully-f32 softmax
        if q_ref.dtype == jnp.bfloat16 and not quantized:
            p = jnp.exp2((s - m_new).astype(jnp.bfloat16))
        else:
            p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(
            p, axis=2, keepdims=True, dtype=jnp.float32
        )
        if quantized:
            # and V's scales ride the probabilities the same way
            p = p * vs_ref[0, i][:, None, :]
        p = p.astype(k_v[0][1].dtype)
        acc_scr[...] = acc_scr[...] * alpha + jnp.stack([
            lax.dot_general(
                lax.index_in_dim(p, h, 0, keepdims=False), v,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            for h, (_, v) in enumerate(k_v)
        ])
        m_scr[...] = m_new

    lax.fori_loop(lo, hi + 1, step, 0)
    l = l_scr[...]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def _q_tiles(q, positions, q_block):
    """``q`` ``[B, S, ..]`` and its ``positions`` cut into tiles of
    ``q_block`` queries along S: ``(q, pos, q_block, tiles)``. A tile that
    is not the whole chunk has 8k queries; S is padded up to whole tiles
    with position-0 rows, which the caller slices off."""
    S = q.shape[1]
    qb = q_block
    if qb < S:
        # a q tile that is not the whole chunk must have 8k rows
        qb = -(-qb // 8) * 8
    nqb = -(-S // qb)
    Sp = nqb * qb
    pos = positions.astype(jnp.int32)
    if Sp != S:
        q = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
        pos = jnp.pad(pos, ((0, 0), (0, Sp - S)))
    return q, pos, qb, nqb


def _frontiers(pos, nqb):
    """``(qmax, qmin)`` ``[B, nqb]``: the causal frontier and the window
    floor per (b, q-block), the scalars that bound the kernel's walk.
    Padding rows sit at position 0, so they never extend the frontier (and
    only make the floor conservative, never wrong)."""
    posb = pos.reshape(pos.shape[0], nqb, -1)
    return (jnp.max(posb, axis=2).astype(jnp.int32),
            jnp.min(posb, axis=2).astype(jnp.int32))


def _as_pools(k_layer, v_layer, layer):
    """``(k_pool, v_pool, layer)``, the layer an int32 scalar: one layer's
    slab (``layer`` None) is a pool of that one layer, read at 0."""
    if layer is None:
        k_layer, v_layer = jax.tree.map(lambda a: a[None], (k_layer, v_layer))
        layer = 0
    return k_layer, v_layer, jnp.asarray(layer, jnp.int32)


def paged_prefill_attention_pallas(
    q: jax.Array,
    k_layer: jax.Array,
    v_layer: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    *,
    scale: float | None = None,
    window: int | None = None,
    layer: jax.Array | int | None = None,
    q_block: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Multi-token (prefill / chunked-prefill / verify-window) attention
    straight off the paged KV pool.

    Same contract as ``ops/kv_cache.paged_prefill_attention``: q
    ``[B, S, H_q, hd]`` is a CHUNK of queries whose own K/V were already
    written via ``write_kv``, ``positions`` ``[B, S]`` int32 gives every
    query's TRUE logical position (callers zero padding columns — their
    outputs are garbage the caller discards), pool layers
    ``[num_blocks, block_size, H_kv, hd]`` or lane-dense ``[num_blocks,
    block_size, H_kv * hd]`` (``pool_shape``), ``block_tables`` ``[B, NB]``
    int32 padded with the garbage block 0. Returns ``[B, S, H_q, hd]``
    in q.dtype.

    ``layer`` given (an int32 scalar, traced or not): ``k_layer`` /
    ``v_layer`` are the WHOLE pools ``[n_layer, num_blocks, ...]`` and the
    kernel reads its pages at that layer: the index is one more scalar
    word to the kernel, and no ``pool[layer]`` exists outside it. That is
    how the cached step calls it. Without it the pool is one layer's.

    The grid is ``(B, q_blocks)``: per (b, q-block) the flash running
    softmax loops over the compute blocks of the sequence's block table
    that the q-block attends, copying each block's pages out of the pool
    itself, the next block's while it computes this one, and looping the
    heads in-kernel (see the module docstring for why).
    ``window=W`` (static) additionally masks ``t <= pos - W`` and skips
    pages wholly below the window floor — sliding-window attention at
    O(S·W) cost.

    ``q_block`` tiles the chunk axis (default: whole chunk up to 128
    queries; a chunk split into several q-blocks rounds the block up to a
    multiple of 8 queries, S is padded up to a multiple with position-0
    rows and the pad is sliced off). ``interpret=None`` means compiled,
    unless the test hook ``ops.attention.pallas_interpret`` is set.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = pallas_interpret()
    k_layer, v_layer, layer = _as_pools(k_layer, v_layer, layer)
    layer = layer.reshape(1)
    quantized = isinstance(k_layer, QuantizedKV)
    if quantized:
        k_data, k_scale = k_layer.data, k_layer.scale
        v_data, v_scale = v_layer.data, v_layer.scale
    else:
        k_data, v_data = k_layer, v_layer
    B, S, Hq, hd = q.shape
    bs = k_data.shape[2]
    # the heads are read off the operands: q's head size and the pool's
    # row, ``[.., Hkv, hd]`` or lane-dense ``[.., Hkv * hd]``
    Hkv = math.prod(k_data.shape[3:]) // hd
    if k_data.ndim == 5:
        # The cache manager stores every pool lane-dense (``pool_shape``);
        # one handed in by heads (a test's own array) is VIEWED so, which
        # on the chip is a relayout of it.
        k_data, v_data = (
            a.reshape(*a.shape[:3], Hkv * hd) for a in (k_data, v_data))
    page_layer = layer
    if (Hkv * hd) % 128:
        # Mosaic copies a row out of HBM only at whole lanes: a row that is not
        # (an odd count of heads of 64: GPT-2's 12 over tp = 4; a test's
        # small heads) is padded to them, ONE layer's slab a call (what the
        # compiler said: "Slice shape along dimension 3 must be aligned to
        # tiling (128), but is 192"). No benchmark cell stores such a row.
        k_data, v_data = (
            jnp.pad(lax.dynamic_index_in_dim(a, layer[0], 0),
                    ((0, 0),) * 3 + ((0, -(Hkv * hd) % 128),))
            for a in (k_data, v_data))
        page_layer = jnp.zeros_like(layer)
    if Hq % Hkv:
        raise ValueError(
            f"query heads ({Hq}) must be a multiple of KV heads ({Hkv})"
        )
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    G = Hq // Hkv
    NB = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    q, pos, qb, nqb = _q_tiles(
        q, positions, q_block if q_block is not None else min(S, Q_TILE))
    Sp = nqb * qb
    R = qb * G
    # fold softmax scale AND log2(e) into q once — base-2 softmax
    # in-kernel. [B, S, Hq, hd] -> [B, Hkv, S*G, hd]: query head h serves
    # kv head h // G (the jnp.repeat head mapping, compacted), and the
    # (query, group) rows flatten s-major so a q tile is G-contiguous.
    qf = (q * jnp.asarray(scale * LOG2E, q.dtype)).reshape(
        B, Sp, Hkv, G, hd
    ).transpose(0, 2, 1, 3, 4).reshape(B, Hkv, Sp * G, hd)
    tables = block_tables.astype(jnp.int32)
    # one position per ROW, as a column: the kernel's mask is then a lane
    # broadcast against the [R, block_size] scores
    pos_rows = jnp.broadcast_to(
        pos[:, :, None], (B, Sp, G)
    ).reshape(B, Sp * G, 1)
    qmax, qmin = _frontiers(pos, nqb)

    page = k_data.shape[2:]
    pages, vmem = _compute_block(
        page, Hkv, hd, R, NB, q.dtype, k_data.dtype, quantized
    )
    n_blocks = -(-NB // pages)

    def q_map(b, j, *refs):
        return (b, 0, j, 0)

    def pos_map(b, j, *refs):
        return (b, j, 0)

    def scale_map(b, j, *refs):
        return (b, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, Hkv, R, hd), q_map),
        pl.BlockSpec((1, R, 1), pos_map),
    ]
    operands = [tables, qmax, qmin, page_layer, qf, pos_rows]
    if quantized:
        # The scale planes' pages ([bs, Hkv] f32: no whole tile) cannot be
        # copied out of HBM by the kernel: each row's scales are gathered
        # through its table here — B x NB x bs x Hkv floats, against a
        # pool's num_blocks x bs x Hkv x hd — one lane-dense [Hkv, T] tile
        # a compute block.
        padded = jnp.pad(tables, ((0, 0), (0, n_blocks * pages - NB)))

        def row_scales(scale):
            return scale[layer[0], padded].reshape(
                B, n_blocks, pages * bs, Hkv
            ).transpose(0, 1, 3, 2)

        spec = pl.BlockSpec((1, n_blocks, Hkv, pages * bs), scale_map)
        in_specs += [spec, spec]
        operands += [row_scales(k_scale), row_scales(v_scale)]
    # the pools stay in HBM: the kernel copies the pages it attends itself
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
    operands += [k_data, v_data]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, nqb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hkv, R, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, pages * bs, page[1]), k_data.dtype),
            pltpu.VMEM((2, pages * bs, page[1]), v_data.dtype),
            # one DMA semaphore a slot, shared by the slot's copies
            pltpu.SemaphoreType.DMA((2,)),
            # running max and sum: a column a head
            pltpu.VMEM((Hkv, R, 1), jnp.float32),
            pltpu.VMEM((Hkv, R, 1), jnp.float32),
            pltpu.VMEM((Hkv, R, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_attention_kernel, block_size=bs, pages=pages,
            window=window, quantized=quantized,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, Sp * G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=max(2 * vmem, _VMEM_DEFAULT),
        ),
        name=_kernel_name(window),
        interpret=interpret,
    )(*operands)
    out = out.reshape(B, Hkv, Sp, G, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, Sp, Hq, hd)[:, :S]


def paged_attention_pallas(
    q: jax.Array,
    k_layer: jax.Array,
    v_layer: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    *,
    scale: float | None = None,
    layer: jax.Array | int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Single-token decode attention straight off the paged KV pool: the
    S = 1 case of ``paged_prefill_attention_pallas``.

    Same contract as ``ops/kv_cache.paged_attention``: q ``[B, H_q, hd]``
    (the current token's query, AFTER its own k/v were written, so the
    ``t <= position`` mask includes self), ``positions`` ``[B]`` int32.
    Returns ``[B, H_q, hd]`` in q.dtype.
    """
    return paged_prefill_attention_pallas(
        q[:, None], k_layer, v_layer, block_tables, positions[:, None],
        scale=scale, layer=layer, interpret=interpret,
    )[:, 0]


def _over_heads(kernel, q, k_pool, v_pool, block_tables, positions, layer):
    """Run ``kernel`` on q ``[B, S, H_q, hd]`` and the whole pools at
    ``layer``; under a mesh whose ``tp`` axis is wider than 1
    (``ShardedExecutor`` sets it around its steps), inside a ``shard_map``
    over the head axis — GSPMD cannot partition the compiled kernel, and
    would all-gather the pool to run it whole."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.shape.get("tp", 1) == 1:
        return kernel(q, k_pool, v_pool, block_tables, positions, layer)
    heads = P(None, None, "tp", None)
    # axis 3 is a pool's heads, a lane-dense pool's row of them (contiguous
    # heads a device) and a scale plane's heads alike
    pool = jax.tree.map(
        lambda a: P(None, None, None, "tp", *[None] * (a.ndim - 4)), k_pool)
    return jax.shard_map(
        kernel,
        in_specs=(heads, pool, pool, P(), P(), P()),
        out_specs=heads,
        check_vma=False,
    )(q, k_pool, v_pool, block_tables, positions, layer)


def _at_layer(k_pool, v_pool, layer):
    """The XLA formulations (the CPU default and the reference semantics;
    no benchmark cell runs them) index the layer first."""
    if layer is None:
        return k_pool, v_pool
    return jax.tree.map(lambda a: a[layer], (k_pool, v_pool))


def decode_attention(
    q: jax.Array,
    k_layer: jax.Array,
    v_layer: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    *,
    scale: float | None = None,
    backend: str = "auto",
    window: int | None = None,
    layer: jax.Array | int | None = None,
) -> jax.Array:
    """Backend dispatcher for decode attention — the one entry point the
    model decode steps call. ``backend`` is the ``attention_backend`` knob
    threaded from ``EngineConfig`` through the model config; "auto" picks
    the Pallas kernel on TPU and the XLA formulation elsewhere. Both
    backends share the exact call signature and numerics contract
    (tests/test_paged_attention.py). ``window`` and ``layer``: see
    ``prefill_attention`` (a windowed decode is its S = 1 case under
    either backend)."""
    if resolve_backend(backend) == "pallas" or window is not None:
        return prefill_attention(
            q[:, None], k_layer, v_layer, block_tables, positions[:, None],
            scale=scale, backend=backend, window=window, layer=layer,
        )[:, 0]
    from ray_tpu.ops.kv_cache import paged_attention as _xla_paged_attention

    return _xla_paged_attention(
        q, *_at_layer(k_layer, v_layer, layer), block_tables, positions,
        scale=scale,
    )


def prefill_attention(
    q: jax.Array,
    k_layer: jax.Array,
    v_layer: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    *,
    scale: float | None = None,
    backend: str = "auto",
    window: int | None = None,
    layer: jax.Array | int | None = None,
) -> jax.Array:
    """Backend dispatcher for multi-token paged attention — the one entry
    point the model prefill, chunked-prefill, and verify paths call.
    ``backend`` is the same ``attention_backend`` knob as
    ``decode_attention`` (static in the traced step, part of the engine's
    jit-cache key, zero new compile kinds); both backends share the exact
    call signature and numerics contract (tests/test_paged_attention.py).
    ``window`` selects sliding-window attention (see
    ``paged_prefill_attention_pallas``). ``layer`` given: ``k_layer`` /
    ``v_layer`` are the whole pools ``[n_layer, num_blocks, ...]``, read at
    that layer; the kernel takes the index as data and never sees a
    ``pool[layer]``."""
    if resolve_backend(backend) == "pallas":
        k_pool, v_pool, layer = _as_pools(k_layer, v_layer, layer)
        return _over_heads(
            lambda q, k, v, tables, pos, layer: paged_prefill_attention_pallas(
                q, k, v, tables, pos, scale=scale, window=window,
                layer=layer),
            q, k_pool, v_pool, block_tables, positions, layer,
        )
    from ray_tpu.ops.kv_cache import (
        paged_prefill_attention as _xla_paged_prefill,
    )

    return _xla_paged_prefill(
        q, *_at_layer(k_layer, v_layer, layer), block_tables, positions,
        scale=scale, window=window,
    )


# ----------------------------------------------------------------------------
# A pool in ONE PLANE: latent attention (models/pangu_ultra_moe.py,
# models/longcat_flash.py, models/ling_hybrid.py). A token's row is one
# latent vector that every head reads, as key (with its rotary rest beside
# it) and as value; the ONE pool the step carries holds the row ``[c |
# k_rope]``, each part stored at whole lanes (``latent_row``). The call has
# a kernel body of its OWN (PR 46, ``_latent_attention_kernel``): the table
# walk is the by-head kernel's (a page is copied once, none past a row's
# frontier), but a tile is ONE head of 32 to 1,024 rows (the query heads of
# its queries; decode is S = 1) whose key is its value, and timed alone on
# the chip the call was bound by neither its products nor its bytes:
# STARTING a copy holds the kernel's one instruction stream ~35 cycles, in
# turn with the products (docs/MICROBENCHMARKS.md, PR 46). So a block is
# long, its copies are started in straight-line text and awaited once, no
# tile waits for its first block, and a page is ONE copy: the latent and
# the rotary part rested in two arrays until PR 53 (two copies a page:
# 2,125 -> 1,620 us a call at cell 8's shape, docs/MICROBENCHMARKS.md).
# ----------------------------------------------------------------------------

# queries a tile of a chunk: with H heads each, ``q_block * H`` rows share
# every page the tile copies
_LATENT_Q_BLOCK = 8
# the tokens a FEW-row tile's block aims at (a decode tile: the heads of one
# query, 64 or 128 rows): the block's chain costs ~750 cycles whatever it
# holds, and each block's waits and loop turn come on top: 1,024 tokens
# measured 30% faster than 256 and 6% faster than 512 at both head counts
_LATENT_BLOCK_TOKENS = 1024


def plane_width(width: int) -> int:
    """What a part of ``width`` numbers of a token's row is STORED at:
    whole lanes of 128, so that a page is whole tiles, rests as written,
    is copied where it stands and is read by lane-aligned views (the
    latent 512 as it is; the rotary 64 as 128)."""
    return -(-width // 128) * 128


def latent_row_width(latent: int, rope: int) -> int:
    """The stored width of a latent family's row ``[c | k_rope]``: each
    part at whole lanes (512 + 64 numbers as 640)."""
    return plane_width(latent) + plane_width(rope)


def latent_row(c: jax.Array, k_rope: jax.Array) -> jax.Array:
    """A token's row as the plane stores it, ``[c | k_rope]`` along the
    last axis with zeros behind each part up to whole lanes."""
    def stored(x):
        return jnp.pad(x, ((0, 0),) * (x.ndim - 1)
                       + ((0, plane_width(x.shape[-1]) - x.shape[-1]),))

    return jnp.concatenate([stored(c), stored(k_rope)], axis=-1)


def latent_parts(rows: jax.Array, latent: int, rope: int):
    """``(c, k_rope)`` of stored rows: ``latent_row`` read back."""
    at = plane_width(latent)
    return rows[..., :latent], rows[..., at:at + rope]


def _latent_tokens(rows: int) -> int:
    """The tokens a latent compute block aims at, by the tile's rows: a
    tile of many rows (a chunk's 8 queries x the heads) reuses every
    latched context tile and pays for rescaling its ``[rows, 512]``
    accumulator a block: it keeps the two lane tiles of scores a block it
    has had since PR 39."""
    return _LATENT_BLOCK_TOKENS if rows <= _MANY_ROWS else 2 * _BLOCK_TOKENS


def _latent_block(bs, W, Cp, R, NB, q_dtype, kv_dtype):
    """``(P, vmem_bytes)`` as ``_compute_block``, for pages ``[bs, W]`` of
    the one plane (``Cp`` of them the latent part) and a tile of R rows."""
    fixed = (
        2 * _vmem_bytes((R, W), q_dtype)            # q, double-buffered
        + 2 * _vmem_bytes((R, Cp), q_dtype)         # out
        + 2 * _vmem_bytes((R, 1), jnp.int32)
        + 2 * _vmem_bytes((R, 1), jnp.float32)      # running max and sum
        + _vmem_bytes((R, Cp), jnp.float32)         # accumulator
    )

    def need(p):
        return (fixed + 2 * _vmem_bytes((p * bs, W), kv_dtype)
                + 2 * _vmem_bytes((R, p * bs), jnp.float32)
                + _vmem_bytes((R, Cp), jnp.float32))

    return _fit_pages(need, bs, _latent_tokens(R), NB)


def _latent_attention_kernel(
    tables_ref,   # scalar prefetch: [B, NB] int32 block tables
    qmax_ref,     # scalar prefetch: [B, nqb] int32 frontier per q-block
    layer_ref,    # scalar prefetch: [1] int32, the pools' layer
    q_ref,        # [1, R, W]: this (b, q-block)'s rows ``[q~ | q_rope]``,
                  # pre-scaled; row r = query (r // H), head (r % H)
    pos_ref,      # [1, R, 1] int32: true position of each row's query
    kv_hbm,       # the plane, every layer, in HBM: a page [bs, W] of rows
                  # ``[c (Cp) | k_rope, zeros behind]``
    o_ref,        # [1, R, Cp]
    kv_buf,       # [2, P * bs, W]: the two-slot scratch of a block
    sems,         # one DMA semaphore a slot, shared by the slot's copies
    m_scr, l_scr,  # [R, 1] float32 running max and sum
    acc_scr,      # [R, Cp] float32
    base_ref,     # SMEM [1] int32: the slot this tile's block 0 is in
    *,
    block_size: int,
    pages: int,
):
    """One (b, q-block) tile: the rows' running softmax over the blocks of
    P pages its frontier reaches. The grid runs IN ORDER (both axes
    "arbitrary") and the scratch lives across it: a tile's block 0 was
    started under the LAST block of the tile before it (the call's first
    tile starts its own), so no tile waits for a copy with nothing to
    compute beside it, and the slots alternate across tiles
    (``base_ref``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, j = pl.program_id(0), pl.program_id(1)
    nb, nj = pl.num_programs(0), pl.num_programs(1)
    layer = layer_ref[0]
    rows, C = acc_scr.shape
    n_entries = tables_ref.shape[1]
    bs, T = block_size, pages * block_size
    div, lmin, rem = lax.div, lax.min, lax.rem

    def frontier(bb, jj):
        # the last table entry a tile attends (the page of its frontier:
        # see ``_paged_attention_kernel``), and its count of blocks
        last = lmin(div(qmax_ref[bb, jj], bs), n_entries - 1)
        return last, div(last, pages) + 1

    last, hi = frontier(b, j)
    # the tile after this one, in the grid's order
    more_j = j + 1 < nj
    b2 = jnp.where(more_j, b, lmin(b + 1, nb - 1))
    j2 = jnp.where(more_j, j + 1, 0)
    final = jnp.logical_and(b == nb - 1, j == nj - 1)
    last2, _ = frontier(b2, j2)

    def page_copy(bb, i, p, slot):
        # page p of a row's block i: ONE copy, the page as it rests
        dst = pl.ds(pl.multiple_of(p * bs, bs), bs)
        src = tables_ref[bb, i * pages + p]
        return pltpu.make_async_copy(
            kv_hbm.at[layer, src], kv_buf.at[slot, dst], sems.at[slot])

    def is_whole(ll, i):
        # every page of block i lies at or under the last attended one
        return ll + 1 - i * pages >= pages

    def each_page(bb, i, slot, op, n, unroll=False):
        # ``op`` ("start" | "wait") on the copies of block i's pages
        # 0 .. n - 1
        def page(p, carry):
            getattr(page_copy(bb, i, p, slot), op)()
            return carry

        lax.fori_loop(0, n, page, 0, unroll=unroll)

    def cut(bb, ll, i, slot, op):
        # a block the frontier cuts (a tile has one, its last): its
        # attended pages alone, by a loop of the frontier's bound
        each_page(bb, i, slot, op, lmin(ll + 1 - i * pages, pages))

    def start(bb, ll, i, slot):
        whole = is_whole(ll, i)
        # straight-line text (one traced page, unrolled as it is lowered):
        # a rolled loop's turn costs each copy ~4 cycles more
        pl.when(whole)(lambda: each_page(
            bb, i, slot, "start", pages, unroll=True))
        pl.when(jnp.logical_not(whole))(
            lambda: cut(bb, ll, i, slot, "start"))

    def wait(ll, i, slot):
        whole = is_whole(ll, i)

        @pl.when(whole)
        def _():
            # ONE wait for all P pages: a semaphore counts bytes, and a
            # slot's own size is its P pages'
            pltpu.make_async_copy(
                kv_buf.at[slot], kv_buf.at[slot], sems.at[slot]).wait()

        pl.when(jnp.logical_not(whole))(
            lambda: cut(b, ll, i, slot, "wait"))

    def compute(i, slot):
        # ONE [T, W] tile a block for all the rows, read by whole lanes:
        # its first C are key and value, the rest the key's rotary part:
        # the scores are q~ . c + q_rope . k_r
        c = kv_buf[slot, :, :C]
        nt = (((1,), (1,)), ((), ()))
        s = lax.dot_general(
            q_ref[0, :, :C], c, nt, preferred_element_type=jnp.float32,
        ) + lax.dot_general(
            q_ref[0, :, C:], kv_buf[slot, :, C:], nt,
            preferred_element_type=jnp.float32,
        )                                                  # [R, T]
        t = i * T + lax.broadcasted_iota(jnp.int32, (rows, T), 1)
        s = jnp.where(t <= pos_ref[0], s, NEG_INF)
        m_prev = m_scr[...]                                # [R, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # bf16 inputs: exp2 at half precision, matching the flash forward
        if q_ref.dtype == jnp.bfloat16:
            p = jnp.exp2((s - m_new).astype(jnp.bfloat16))
        else:
            p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(
            p, axis=1, keepdims=True, dtype=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
            p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(jnp.logical_and(b == 0, j == 0))
    def _():
        # The rows of a slot that no copy of the block wrote hold an older
        # block's pages, or at a call's start nothing yet; the mask zeroes
        # their probabilities, and 0 x the latent tile (the value) must
        # stay 0: zeros once, and after that only pages the tables name.
        kv_buf[...] = jnp.zeros_like(kv_buf)
        base_ref[0] = 0
        cut(b, last, 0, 0, "start")

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    base = base_ref[0]

    def block(i, carry):
        slot = rem(base + i, 2)
        wait(last, i, slot)
        # the block after this one flies while this one computes: this
        # tile's next, or block 0 of the tile after it
        more = i + 1 < hi
        pl.when(jnp.logical_or(more, jnp.logical_not(final)))(
            lambda: start(
                jnp.where(more, b, b2), jnp.where(more, last, last2),
                jnp.where(more, i + 1, 0), 1 - slot))
        compute(i, slot)
        return carry

    lax.fori_loop(0, hi, block, 0)
    base_ref[0] = rem(base + hi, 2)
    l = l_scr[...]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("latent_dim", "scale", "q_block", "interpret"))
def _latent_call(q, pool, block_tables, positions, layer,
                 *, latent_dim, scale, q_block, interpret):
    """``paged_latent_attention_pallas`` behind a jit of its own: a step
    program calls it once a latent layer with the same shapes, and the
    inner jit's cache makes the kernel's text traced once a process and
    lowered once a program, not once a call (a program still holds one
    ``paged_attention_latent`` custom call a layer)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, D = q.shape
    C, R = latent_dim, D - latent_dim
    bs, W = pool.shape[2:]
    Cp = plane_width(C)
    NB = block_tables.shape[1]
    q, pos, qb, nqb = _q_tiles(q, positions, q_block)
    Sp = nqb * qb
    rows = qb * H
    q = q * jnp.asarray(scale * LOG2E, q.dtype)
    # each part of a row padded as the plane's row is (nothing at the
    # latent 512; the rotary 64 to 128, against the row's zeros)
    qf = latent_row(q[..., :C], q[..., C:]).reshape(B, Sp * H, W)
    pos_rows = jnp.broadcast_to(
        pos[:, :, None], (B, Sp, H)).reshape(B, Sp * H, 1)
    qmax, _ = _frontiers(pos, nqb)
    pages, vmem = _latent_block(bs, W, Cp, rows, NB, q.dtype, pool.dtype)

    def q_map(b, j, *refs):
        return (b, j, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nqb),
        in_specs=[
            pl.BlockSpec((1, rows, W), q_map),
            pl.BlockSpec((1, rows, 1), q_map),
            # the plane stays in HBM: the kernel copies the pages itself
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, rows, Cp), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, pages * bs, W), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, Cp), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _latent_attention_kernel, block_size=bs, pages=pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sp * H, Cp), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # in order, on one core: a tile starts the next tile's copies
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(2 * vmem, _VMEM_DEFAULT),
        ),
        name=LATENT_KERNEL_NAME,
        interpret=interpret,
    )(block_tables.astype(jnp.int32), qmax, layer, qf, pos_rows, pool)
    return out.reshape(B, Sp, H, Cp)[:, :S, :, :C]


def paged_latent_attention_pallas(
    q: jax.Array,
    pool: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    *,
    latent_dim: int,
    scale: float,
    layer: jax.Array | int | None = None,
    q_block: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Latent attention straight off a pool in one plane: same contract as
    ``ops/kv_cache.paged_latent_attention``. q ``[B, S, H, C + R]``
    (``[q~ | q_rope]``, ``C = latent_dim``), the pool ``[n_layer,
    num_blocks, block_size, latent_row_width(C, R)]`` with ``layer`` (one
    layer's without), ``positions`` ``[B, S]``. Returns ``[B, S, H, C]``
    in q's dtype.

    The kernel is ``_latent_attention_kernel`` under the name
    ``paged_attention_latent``: grid ``(B, q_blocks)``, a tile of
    ``q_block`` queries x H heads as ROWS over the one shared row a token;
    per compute block ONE copy a page, the tile's latent lanes then feed
    the scores (``q~ . c``, the rotary lanes' ``q_rope . k_r`` added) and
    the values (``p . c``). So a page is read once for keys and values, at
    ``2 H (C + R + C)`` flop a token. A chunk of queries against a
    resident context runs the same kernel: nothing of the context is ever
    expanded by head in HBM."""
    if interpret is None:
        interpret = pallas_interpret()
    pool, _, layer = _as_pools(pool, None, layer)
    B, S = q.shape[:2]
    call = functools.partial(
        _latent_call, layer=layer.reshape(1), latent_dim=latent_dim,
        scale=float(scale),
        q_block=q_block if q_block is not None else min(S, _LATENT_Q_BLOCK),
        interpret=bool(interpret))
    # the rows' tables ride in scalar memory: a batch whose tables pass
    # what fits there goes in groups of rows, a call a group
    rows = _latent_rows_a_call(B, block_tables.shape[1])
    if rows == B:
        return call(q, pool, block_tables, positions)
    return jnp.concatenate([
        call(q[i:i + rows], pool, block_tables[i:i + rows],
             positions[i:i + rows])
        for i in range(0, B, rows)])


def latent_attention(
    q: jax.Array,
    pool: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    *,
    latent_dim: int,
    scale: float,
    backend: str = "auto",
    layer: jax.Array | int | None = None,
) -> jax.Array:
    """Backend dispatcher for latent attention over a pool in one plane,
    the one entry point of the cached step's latent layers, every kind of
    step: q ``[B, S, H, C + R]`` at true ``positions`` ``[B, S]`` (decode
    is S = 1), ``[B, S, H, C]`` back. "pallas": the kernel above; "xla":
    ``ops/kv_cache.paged_latent_attention`` through ``gather_kv``."""
    if resolve_backend(backend) == "pallas":
        return paged_latent_attention_pallas(
            q, pool, block_tables, positions,
            latent_dim=latent_dim, scale=scale, layer=layer)
    from ray_tpu.ops.kv_cache import paged_latent_attention

    return paged_latent_attention(
        q, pool if layer is None else pool[layer], block_tables,
        positions, latent_dim=latent_dim, scale=scale)


# The most queries ``paged_prefill_attention_pallas`` gives one q tile: a
# chunk of more is walked in tiles of this many, each its own grid step
# with its own causal frontier. So Q_TILE tokens of a prompt cost the
# kernel the same as a row of their own as they do as a tile of a longer
# row, which is what lets the scheduler fill a prefill step with PIECES of
# prompts (serve/llm/engine.py ``_prefill_chunk_locked``). Defined down
# here so that no line of the kernel's call chain moves: a compiled step
# program is found in the persistent cache by its callers' line numbers.
Q_TILE = 128


# Bytes of block tables one call of the latent kernel prefetches into
# scalar memory (1 MB on a v5e, of which the compiler keeps some): 128
# rows of 768 entries (cells 8 and 10) are 393 KB; 128 rows of 2,560
# (models/ling_hybrid.py's cell: contexts to 40,960 tokens in pages of 16)
# would be 1.31 MB and are refused by the compiler, so they go as two
# calls of 64 rows.
_LATENT_TABLE_SMEM = 768 * 1024


def _latent_rows_a_call(B: int, NB: int) -> int:
    """Rows of a batch one call of the latent kernel takes: all of them
    where their tables fit scalar memory, else the largest divisor of
    ``B`` whose tables do."""
    rows = B
    while rows > 1 and rows * NB * 4 > _LATENT_TABLE_SMEM:
        rows = next(r for r in range(rows - 1, 0, -1) if B % r == 0)
    return rows
