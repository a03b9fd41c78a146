"""Attention ops: Pallas flash attention (TPU) + XLA reference.

New capability relative to the reference, which has no native attention or
sequence-parallel kernels at all (SURVEY.md §5.7 — long-context support in
the reference is delegated to DeepSpeed/FSDP integrations). Design per the
Pallas TPU guide, with three TPU-specific twists that fell out of profiling
on a v5e (these kernels are VPU- and grid-overhead-bound, not MXU-bound —
attention matmul FLOPs are ~1% of a GPT step but were ~40% of its time):

- GROUPED GRID: each grid step processes `group` (batch*head) slices at
  once via batched dot_generals, dividing the per-step overhead (~3-5 us
  of pipeline/DMA bookkeeping) by the group size. Grid is
  (bh/group, q_blocks, kv_blocks), innermost axis varies fastest so VMEM
  scratch accumulators persist across the reduction axis.
- BASE-2 SOFTMAX: log2(e) folds into the softmax scale (which itself folds
  into q once, O(S*D)), so the per-element transcendental is a bare exp2
  instead of exp's mul+exp2, and no [bq,bkv]-sized rescale pass exists.
- HALF-PRECISION EXP: when the inputs are bf16, the exp2/subtract run in
  bf16 (2x VPU lanes); the running max, log-sum-exp and output
  accumulation stay f32. Probabilities are bf16-quantized (~0.4% rel)
  — the same precision the output is stored at anyway. f32 inputs get a
  fully-f32 softmax (tests compare against the XLA reference at 1e-5).

Backward is a two-pass Pallas flash backward (dk/dv pass with q innermost,
dq pass with kv innermost) that recomputes score blocks against the
forward-saved logsumexp — O(S) residuals and no O(S^2) HBM temps (the
XLA-recompute backward it replaced materialized four [b,h,S,S] f32 tensors
per layer, the v5e OOM + bandwidth bottleneck at bs16/seq1024). The causal
mask is only computed on diagonal-crossing blocks; blocks fully below the
diagonal skip the iota/select entirely and blocks above are not executed.

The kernels are always compiled for the device; the CPU tests run them in
the Pallas interpreter through the one test hook ``pallas_interpret``.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp

NEG_INF = -1e30
LOG2E = math.log2(math.e)
LN2 = math.log(2.0)


def pallas_interpret() -> bool:
    """The one test hook for the Pallas interpreter: every kernel in
    ``ray_tpu.ops`` called with ``interpret=None`` is compiled unless
    ``RAY_TPU_PALLAS_INTERPRET=1`` is in the environment (tests/conftest.py
    sets it, so worker processes inherit it). Never derived from the
    platform: a kernel that cannot compile for the device must fail."""
    return os.environ.get("RAY_TPU_PALLAS_INTERPRET") == "1"


def mha_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
) -> jax.Array:
    """Plain XLA attention. q,k,v: [B, H, S, D] (kv may have fewer heads =
    grouped-query; heads must divide)."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    q_heads, kv_heads = q.shape[1], k.shape[1]
    if q_heads != kv_heads:
        rep = q_heads // kv_heads
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if causal:
        s_q, s_k = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _pick_group(bh: int, block_q: int, block_kv: int) -> int:
    """Largest group size whose f32 score temps stay well inside VMEM
    (~48 MB for ~3 [g,bq,bkv] f32 live values) and that divides bh."""
    budget = 48 * 1024 * 1024
    per = block_q * block_kv * 4 * 3
    g = min(max(1, budget // per), 8)  # cap BEFORE the divisibility walk
    while g > 1 and bh % g:
        g -= 1
    return g



def _clamp_block(block: int, seq_len: int) -> int:
    """Largest block <= `block` that divides seq_len (halving as needed, so
    e.g. S=1536 with a 1024 default lands on 512 instead of erroring)."""
    block = min(block, seq_len)
    while block > 1 and seq_len % block:
        block //= 2
    return block


def _causal_regimes(q_idx, kv_idx, block_q, block_kv):
    """(executed, fully_below): block-level causal classification."""
    executed = kv_idx * block_kv <= q_idx * block_q + (block_q - 1)
    fully_below = kv_idx * block_kv + (block_kv - 1) <= q_idx * block_q
    return executed, fully_below


def _mask_scores(s, q_idx, kv_idx, block_q, block_kv):
    g, bq, bkv = s.shape
    q_pos = q_idx * block_q + jax.lax.broadcasted_iota(jnp.int32, (g, bq, bkv), 1)
    k_pos = kv_idx * block_kv + jax.lax.broadcasted_iota(jnp.int32, (g, bq, bkv), 2)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _bdot(a, b, contract, batch=((0,), (0,)), out=jnp.float32):
    """Batched dot over leading group axis: a [g,M,*], b [g,N,*]."""
    return jax.lax.dot_general(
        a, b, ((contract), (batch)), preferred_element_type=out
    )


# ----------------------------------------------------------------------------
# Pallas forward kernel
# ----------------------------------------------------------------------------


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref,  # [g, block_q, D], [g, block_kv, D], [g, block_kv, Dv]
    o_ref,                # [g, block_q, Dv]
    *rest,                # optional lse_ref [g, block_q, 128], then scratch
    causal: bool,
    block_q: int,
    block_kv: int,
    save_lse: bool,
    seg=None,             # (q_seg_ref, k_seg_ref, q_span_ref, k_span_ref)
    carry=None,           # (o_ref [g, block_q, Dv], lse_ref [g, block_q, 128])
    halve_diagonal=False,
):
    from jax.experimental import pallas as pl

    if save_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        lse_ref, (m_scr, l_scr, acc_scr) = None, rest

    q_idx = pl.program_id(1)
    kv_idx = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(kv_idx == 0)
    def _init():
        if carry is not None:
            # go on from an earlier call over OTHER keys: its normalised
            # output and log-sum-exp are a state (max, sum 1, accumulator)
            m_scr[:] = carry[1][:]
            l_scr[:] = jnp.ones_like(l_scr)
            acc_scr[:] = carry[0][:].astype(acc_scr.dtype)
            return
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute(masked: bool, part=None):
        # storage-dtype matmul operands: bf16 x bf16 -> f32 runs the MXU at
        # full rate. q arrives pre-scaled by softmax_scale * log2(e), so
        # the softmax is base-2 and needs no per-element rescale.
        # ``part``: (first row, rows, first key, keys) of the block, static:
        # the update is made over that corner of it alone
        r0, nr, k0, nk = part or (0, block_q, 0, block_kv)
        rows = slice(None) if part is None else slice(r0, r0 + nr)
        keys = slice(None) if part is None else slice(k0, k0 + nk)
        q = q_ref[:, rows]                         # [g, bq, D]
        k = k_ref[:, keys]                         # [g, bkv, D]
        v = v_ref[:, keys]                         # [g, bkv, Dv]
        s = _bdot(q, k, ((2,), (2,)))              # [g, bq, bkv] f32
        if masked and seg is not None:
            # a pair is allowed where both tokens carry one segment id
            # (padding carries a negative one, a query's unlike a key's);
            # ONE [bq, bkv] mask for the group, the diagonal in it
            allowed = seg[0][rows] == seg[1][:, keys]
            if causal:
                at = lambda first, axis: (
                    first + jax.lax.broadcasted_iota(
                        jnp.int32, allowed.shape, axis))
                allowed &= (at(q_idx * block_q + r0, 0)
                            >= at(kv_idx * block_kv + k0, 1))
            s = jnp.where(allowed[None], s, NEG_INF)
        elif masked:
            s = _mask_scores(s, q_idx, kv_idx, block_q, block_kv)

        m_prev = m_scr[:, rows, :1]                # [g, bq, 1]
        m_cur = jnp.max(s, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # a row that has met no allowed key yet stands at NEG_INF: its
        # masked scores must come out as probability 0, not exp2(0)
        m_ref = m_new if seg is None else jnp.where(
            m_new == NEG_INF, 0.0, m_new)
        # bf16 inputs: run the exp2 at half precision (2x VPU throughput);
        # the probabilities feed a bf16 matmul + an f32 row sum either way
        if q.dtype == jnp.bfloat16:
            p = jnp.exp2((s - m_ref).astype(jnp.bfloat16))
        else:
            p = jnp.exp2(s - m_ref)
        alpha = jnp.exp2(m_prev - m_new)           # [g, bq, 1]
        l_new = alpha * l_scr[:, rows, :1] + jnp.sum(
            p, axis=2, keepdims=True, dtype=jnp.float32
        )
        acc_scr[:, rows] = acc_scr[:, rows] * alpha + _bdot(
            p.astype(v.dtype), v, ((2,), (1,))
        )
        m_scr[:, rows] = jnp.broadcast_to(m_new, (*m_new.shape[:2], 128))
        l_scr[:, rows] = jnp.broadcast_to(l_new, (*l_new.shape[:2], 128))

    if seg is not None:
        # by block, from the blocks' spans of segment ids (lowest, padding
        # included; highest): nothing where no id can be shared. An
        # executed block is always masked: the ONE mask a group costs no
        # time that shows, and a second, unmasked body doubles the
        # kernel's text, which rests on the device a call a layer a
        # program (docs/MICROBENCHMARKS.md, PR 51)
        q_lo, q_hi = seg[2][q_idx, 0], seg[2][q_idx, 1]
        k_lo, k_hi = seg[3][kv_idx, 0], seg[3][kv_idx, 1]
        executed = (k_hi >= jnp.maximum(q_lo, 0)) & (
            q_hi >= jnp.maximum(k_lo, 0))
        if causal:
            executed &= _causal_regimes(q_idx, kv_idx, block_q, block_kv)[0]

        @pl.when(executed)
        def _():
            if not halve_diagonal:
                return _compute(masked=True)
            # the call's ONE block stands on the diagonal: its upper right
            # quarter holds no allowed pair, so the block is two updates,
            # every row over the first half of the keys and the later half
            # of the rows over the second, three quarters of the products
            # (worth it from blocks of ~1,536 up)
            half = block_q // 2
            _compute(True, (0, block_q, 0, half))
            _compute(True, (half, half, half, half))
    elif causal:
        executed, fully_below = _causal_regimes(q_idx, kv_idx, block_q, block_kv)

        @pl.when(executed & jnp.logical_not(fully_below))
        def _():
            _compute(masked=True)

        @pl.when(fully_below)
        def _():
            _compute(masked=False)
    else:
        _compute(masked=False)

    @pl.when(kv_idx == n_kv - 1)
    def _finalize():
        l = l_scr[:, :, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[:] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        if save_lse:
            # base-2 logsumexp per query row, lane-broadcast to the
            # (8,128)-tiled output layout (m/l already hold 128 copies)
            lse_ref[:] = m_scr[:] + jnp.log2(
                jnp.where(l_scr[:] == 0.0, 1.0, l_scr[:])
            )


def _flash_fwd_segmented(q_span, k_span, q_ref, k_ref, v_ref, q_seg, k_seg,
                         *rest, carried, **static):
    """``_flash_fwd_kernel`` as a call with segment ids hands it its
    references: the blocks' spans (scalar prefetch) lead, the ids follow
    the values, then an earlier call's output and log-sum-exp."""
    carry, rest = (rest[:2], rest[2:]) if carried else (None, rest)
    _flash_fwd_kernel(q_ref, k_ref, v_ref, *rest, carry=carry,
                      seg=(q_seg, k_seg, q_span, k_span), **static)


def _seg_spans(seg, block):
    """[blocks, 2] int32: each block's lowest and highest segment id."""
    by_block = seg.reshape(-1, block)
    return jnp.stack([by_block.min(axis=1), by_block.max(axis=1)], axis=1)


def _flash_forward(
    q, k, v, *, causal, scale, block_q, block_kv, interpret, save_lse=False,
    q_seg=None, k_seg=None, carry=None, halve_diagonal=False,
):
    """q ``[B, H, S, D]`` against k ``[B, H, Sk, D]`` and v ``[B, H, Sk,
    Dv]`` (a value may be narrower than a key; ``Sk != S`` without
    ``causal``): ``[B, H, S, Dv]`` in q's dtype, with ``save_lse`` the
    base-2 log-sum-exp ``[B, H, S]`` float32 too.

    The serving call gives ``q_seg [S]`` / ``k_seg [Sk]`` int32, one for
    every (batch, head): a query attends the keys that carry ITS id (and,
    under ``causal``, stand at or before its index); a negative id, a
    query's unlike a key's, marks padding, which attends and is attended
    by nothing: such a query's output is 0 and its log-sum-exp
    ``NEG_INF``. Blocks whose ids cannot meet are skipped. Such a call
    returns ``(o float32, the log-sum-exp as the kernel keeps it, [B, H,
    S, 128] lane-broadcast)`` and takes the same pair as ``carry``: an
    earlier call's result over OTHER keys, which this one goes on from
    (its buffers are reused), so a context is attended block by block
    with no pass between the calls. ``halve_diagonal`` (a causal call
    of ONE block; on the chip of whole 256s, so that a half is whole
    lanes): the block leaves its upper right quarter out."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, heads, seq_len, head_dim = q.shape
    kv_len, v_dim = k.shape[2], v.shape[3]
    assert kv_len == seq_len or not causal, (seq_len, kv_len)
    block_q = _clamp_block(block_q, seq_len)
    block_kv = _clamp_block(block_kv, kv_len)
    bh = batch * heads
    g = _pick_group(bh, block_q, block_kv)
    # fold softmax scale AND log2(e) into q once (O(S*D)) — the kernels
    # compute a base-2 softmax with no per-score rescale pass
    qf = (q * jnp.asarray(scale * LOG2E, q.dtype)).reshape(bh, seq_len, head_dim)
    kf = k.reshape(bh, kv_len, head_dim)
    vf = v.reshape(bh, kv_len, v_dim)

    grid = (bh // g, seq_len // block_q, kv_len // block_kv)
    kernel = functools.partial(
        _flash_fwd_kernel,
        causal=causal,
        block_q=block_q,
        block_kv=block_kv,
        save_lse=save_lse,
    )
    serving = q_seg is not None
    row = lambda width: pl.BlockSpec(
        (g, block_q, width), lambda b, i, j, *_: (b, i, 0))
    out_specs = [row(v_dim)]
    out_shapes = [jax.ShapeDtypeStruct(
        (bh, seq_len, v_dim), jnp.float32 if serving else q.dtype)]
    if save_lse:
        # lane-broadcast [bh, S, 128] rather than [bh, S]: a 2D output
        # violates Mosaic's (8,128) output-tile constraint; 128 copies of
        # a f32 scalar per row is ~64 bytes/token of extra HBM — noise
        out_specs.append(row(128))
        out_shapes.append(jax.ShapeDtypeStruct((bh, seq_len, 128), jnp.float32))
    in_specs = [
        row(head_dim),
        pl.BlockSpec((g, block_kv, head_dim), lambda b, i, j, *_: (b, j, 0)),
        pl.BlockSpec((g, block_kv, v_dim), lambda b, i, j, *_: (b, j, 0)),
    ]
    operands, spans, aliases = [qf, kf, vf], [], {}
    if serving:
        assert save_lse
        assert not halve_diagonal or (
            causal and grid[1:] == (1, 1) and block_q % 2 == 0)
        kernel = functools.partial(
            _flash_fwd_segmented, carried=carry is not None,
            halve_diagonal=halve_diagonal, **kernel.keywords)
        in_specs += [
            pl.BlockSpec((block_q, 1), lambda b, i, j, *_: (i, 0)),
            pl.BlockSpec((1, block_kv), lambda b, i, j, *_: (0, j)),
        ]
        operands += [q_seg.reshape(seq_len, 1), k_seg.reshape(1, kv_len)]
        spans = [_seg_spans(q_seg, block_q), _seg_spans(k_seg, block_kv)]
        if carry is not None:
            in_specs += [row(v_dim), row(128)]
            operands += [carry[0].reshape(bh, seq_len, v_dim),
                         carry[1].reshape(bh, seq_len, 128)]
            aliases = {len(spans) + 5: 0, len(spans) + 6: 1}
    result = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(spans),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((g, block_q, 128), jnp.float32),
                pltpu.VMEM((g, block_q, 128), jnp.float32),
                pltpu.VMEM((g, block_q, v_dim), jnp.float32),
            ],
        ),
        out_shape=out_shapes,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024,
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        name="flash_fwd",
        interpret=interpret,
    )(*spans, *operands)
    out, lse = (result[0], result[1]) if save_lse else (result[0], None)
    out = out.reshape(batch, heads, seq_len, v_dim)
    if serving:
        return out, lse.reshape(batch, heads, seq_len, 128)
    if save_lse:
        return out, lse.reshape(batch, heads, seq_len, 128)[..., 0]
    return out


# ----------------------------------------------------------------------------
# Pallas backward kernels (two-pass flash backward)
#
# Pass 1 (dk, dv): grid (bh/g, kv_blocks, q_blocks) — q innermost so the
# dk/dv accumulators live in VMEM scratch across q steps.
# Pass 2 (dq):     grid (bh/g, q_blocks, kv_blocks) — kv innermost, ditto.
# Both recompute the score block from (q, k) and renormalize with the
# base-2 lse saved by the forward; delta = sum(do*o, -1) is precomputed in
# XLA. Nothing O(S^2) ever touches HBM.
# ----------------------------------------------------------------------------


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,  # blocks, see specs
    dk_ref, dv_ref,                                   # [g, block_kv, D]
    *rest,  # fused mode: dq_ref [g, 1, block_q, D] f32; then scratch x2
    causal: bool,
    block_q: int,
    block_kv: int,
    fused_dq: bool = False,
):
    from jax.experimental import pallas as pl

    if fused_dq:
        dq_ref, dk_scr, dv_scr = rest
    else:
        dq_ref, (dk_scr, dv_scr) = None, rest

    kv_idx = pl.program_id(1)
    q_idx = pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(q_idx == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute(masked: bool):
        q = q_ref[:]                                  # [g, bq, D] pre-scaled
        k = k_ref[:]                                  # [g, bkv, D]
        v = v_ref[:]                                  # [g, bkv, D]
        do = do_ref[:]                                # [g, bq, D]
        lse = lse_ref[:, :, :1]                       # [g, bq, 1] f32, base-2
        delta = delta_ref[:, :, :1]                   # [g, bq, 1] f32

        s = _bdot(q, k, ((2,), (2,)))                 # [g, bq, bkv] f32
        if masked:
            s = _mask_scores(s, q_idx, kv_idx, block_q, block_kv)
        if q.dtype == jnp.bfloat16:
            p = jnp.exp2((s - lse).astype(jnp.bfloat16))
        else:
            p = jnp.exp2(s - lse)                     # normalized probs
        # dv += p^T @ do
        dv_scr[:] = dv_scr[:] + _bdot(
            p.astype(do.dtype), do, ((1,), (1,))
        )
        # dp = do @ v^T ; ds = ln2 * p * (dp - delta): the softmax is
        # base-2 (p = exp2(s2 - lse2) with s2 = log2e-scaled logits), so
        # dL/ds2 carries a ln2 from d exp2. With q pre-scaled by
        # scale*log2e, dk = ds^T @ q_scaled is then exact, and dq needs
        # one scale*log2e rescale in the wrapper (ln2 * log2e = 1).
        dp = _bdot(do, v, ((2,), (2,)))
        ds = p.astype(jnp.float32) * (dp - delta) * LN2
        dk_scr[:] = dk_scr[:] + _bdot(
            ds.astype(q.dtype), q, ((1,), (1,))
        )
        if dq_ref is not None:
            # fused single-sweep: the score block and dp are already in
            # VMEM, so the dq contribution of THIS kv block costs one
            # extra matmul — eliminating the entire second recompute pass
            # (3 of 7 matmul sweeps + its exp2/mask/DMA traffic)
            dq_ref[:, 0] = _bdot(ds.astype(k.dtype), k, ((2,), (1,)))

    if causal:
        executed, fully_below = _causal_regimes(q_idx, kv_idx, block_q, block_kv)

        if dq_ref is not None:
            # skipped blocks must still define their dq partial slot
            @pl.when(jnp.logical_not(executed))
            def _zero_dq():
                dq_ref[:, 0] = jnp.zeros_like(dq_ref[:, 0])

        @pl.when(executed & jnp.logical_not(fully_below))
        def _():
            _compute(masked=True)

        @pl.when(fully_below)
        def _():
            _compute(masked=False)
    else:
        _compute(masked=False)

    @pl.when(q_idx == n_q - 1)
    def _finalize():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref,                                           # [g, block_q, D]
    dq_scr,                                           # VMEM [g, block_q, D] f32
    *,
    causal: bool,
    block_q: int,
    block_kv: int,
):
    from jax.experimental import pallas as pl

    q_idx = pl.program_id(1)
    kv_idx = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(kv_idx == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute(masked: bool):
        q = q_ref[:]
        k = k_ref[:]
        v = v_ref[:]
        do = do_ref[:]
        lse = lse_ref[:, :, :1]
        delta = delta_ref[:, :, :1]

        s = _bdot(q, k, ((2,), (2,)))
        if masked:
            s = _mask_scores(s, q_idx, kv_idx, block_q, block_kv)
        if q.dtype == jnp.bfloat16:
            p = jnp.exp2((s - lse).astype(jnp.bfloat16))
        else:
            p = jnp.exp2(s - lse)
        dp = _bdot(do, v, ((2,), (2,)))
        ds = p.astype(jnp.float32) * (dp - delta) * LN2  # see dkv kernel
        dq_scr[:] = dq_scr[:] + _bdot(
            ds.astype(k.dtype), k, ((2,), (1,))
        )

    if causal:
        executed, fully_below = _causal_regimes(q_idx, kv_idx, block_q, block_kv)

        @pl.when(executed & jnp.logical_not(fully_below))
        def _():
            _compute(masked=True)

        @pl.when(fully_below)
        def _():
            _compute(masked=False)
    else:
        _compute(masked=False)

    @pl.when(kv_idx == n_kv - 1)
    def _finalize():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _flash_backward(
    q, k, v, out, lse, do, *, causal, scale, block_q, block_kv, interpret
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, heads, seq_len, head_dim = q.shape
    block_q = _clamp_block(block_q, seq_len)
    block_kv = _clamp_block(block_kv, seq_len)
    bh = batch * heads
    g = _pick_group(bh, block_q, block_kv)
    # kernels compute grads w.r.t. the pre-scaled q (matching the forward's
    # folded scale*log2e); the chain rule back to q multiplies dq by the
    # same factor. For k and v no correction is needed: d s2/dk carries the
    # scaled q itself, and the ln2 from d exp2 cancels the folded log2(e)
    # in the ds -> (dk, dq) contractions' normalization (worked out so the
    # returned grads match the natural-base reference exactly).
    scale2 = scale * LOG2E
    qf = (q * jnp.asarray(scale2, q.dtype)).reshape(bh, seq_len, head_dim)
    kf = k.reshape(bh, seq_len, head_dim)
    vf = v.reshape(bh, seq_len, head_dim)
    dof = do.reshape(bh, seq_len, head_dim)

    # delta_i = dO_i . O_i (row dot), lane-broadcast alongside lse to the
    # (8,128)-tiled layout the kernels read; O(S*D) traffic, transient
    delta = jnp.sum(
        dof.astype(jnp.float32)
        * out.reshape(bh, seq_len, head_dim).astype(jnp.float32),
        axis=-1, keepdims=True,
    )                                                   # [bh, S, 1]
    delta_b = jnp.broadcast_to(delta, (bh, seq_len, 128))
    lse_b = jnp.broadcast_to(
        lse.reshape(bh, seq_len, 1), (bh, seq_len, 128)
    ).astype(jnp.float32)

    # pass 1: dk, dv — kv blocks outer, q blocks inner (b, j, i) grid order
    dkv_specs = [
        pl.BlockSpec((g, block_q, head_dim), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((g, block_kv, head_dim), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((g, block_kv, head_dim), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((g, block_q, head_dim), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((g, block_q, 128), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((g, block_q, 128), lambda b, j, i: (b, i, 0)),
    ]
    n_kv = seq_len // block_kv
    # Fused single sweep when the kv-block count is small: the dk/dv pass
    # already has the score block, dp, and k in VMEM, so each grid step
    # emits its dq partial (one extra matmul) into a per-kv-block slot and
    # XLA sums the n_kv slots — the entire dq recompute pass (3 of 7
    # matmul sweeps + its exp2/mask/DMA) disappears. Partials cost
    # bh*n_kv*S*hd f32 of HBM, so long sequences fall back to two-pass.
    fused = n_kv <= 4
    dkv_out_specs = [
        pl.BlockSpec((g, block_kv, head_dim), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((g, block_kv, head_dim), lambda b, j, i: (b, j, 0)),
    ]
    dkv_out_shapes = [
        jax.ShapeDtypeStruct((bh, seq_len, head_dim), k.dtype),
        jax.ShapeDtypeStruct((bh, seq_len, head_dim), v.dtype),
    ]
    if fused:
        dkv_out_specs.append(pl.BlockSpec(
            (g, 1, block_q, head_dim), lambda b, j, i: (b, j, i, 0)
        ))
        dkv_out_shapes.append(jax.ShapeDtypeStruct(
            (bh, n_kv, seq_len, head_dim), jnp.float32
        ))
    result = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, causal=causal,
            block_q=block_q, block_kv=block_kv, fused_dq=fused,
        ),
        grid=(bh // g, n_kv, seq_len // block_q),
        in_specs=dkv_specs,
        out_specs=dkv_out_specs,
        out_shape=dkv_out_shapes,
        scratch_shapes=[
            pltpu.VMEM((g, block_kv, head_dim), jnp.float32),
            pltpu.VMEM((g, block_kv, head_dim), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024,
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        name="flash_bwd_dkv",
        interpret=interpret,
    )(qf, kf, vf, dof, lse_b, delta_b)

    shape = (batch, heads, seq_len, head_dim)
    if fused:
        dk, dv, dq_parts = result
        dq = jnp.sum(dq_parts, axis=1).astype(q.dtype)
        dq = (dq * jnp.asarray(scale2, dq.dtype)).reshape(shape)
        return dq, dk.reshape(shape), dv.reshape(shape)
    dk, dv = result

    # pass 2: dq — q blocks outer, kv inner
    row_specs = [
        pl.BlockSpec((g, block_q, head_dim), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((g, block_kv, head_dim), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((g, block_kv, head_dim), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((g, block_q, head_dim), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((g, block_q, 128), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((g, block_q, 128), lambda b, i, j: (b, i, 0)),
    ]
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, causal=causal,
            block_q=block_q, block_kv=block_kv,
        ),
        grid=(bh // g, seq_len // block_q, seq_len // block_kv),
        in_specs=row_specs,
        out_specs=pl.BlockSpec(
            (g, block_q, head_dim), lambda b, i, j: (b, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((bh, seq_len, head_dim), q.dtype),
        scratch_shapes=[pltpu.VMEM((g, block_q, head_dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024,
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        name="flash_bwd_dq",
        interpret=interpret,
    )(qf, kf, vf, dof, lse_b, delta_b)

    dq = (dq * jnp.asarray(scale2, dq.dtype)).reshape(shape)
    return dq, dk.reshape(shape), dv.reshape(shape)


# ----------------------------------------------------------------------------
# custom VJP: pallas forward, pallas two-pass backward
# ----------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def _flash_attention(q, k, v, causal, scale, block_q, block_kv, interpret):
    return _flash_forward(
        q, k, v, causal=causal, scale=scale,
        block_q=block_q, block_kv=block_kv, interpret=interpret,
    )


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_kv, interpret):
    out, lse = _flash_forward(
        q, k, v, causal=causal, scale=scale,
        block_q=block_q, block_kv=block_kv, interpret=interpret,
        save_lse=True,
    )
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, scale, block_q, block_kv, interpret, res, do):
    q, k, v, out, lse = res
    return _flash_backward(
        q, k, v, out, lse, do, causal=causal, scale=scale,
        block_q=block_q, block_kv=block_kv, interpret=interpret,
    )


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 1024,
    block_kv: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    """Flash attention. q,k,v: [B, H, S, D]; returns [B, H, S, D].

    Grouped-query attention is handled by repeating kv heads up front
    (cheap relative to attention itself; a head-aware kernel is a later
    optimization). `interpret=None` means compiled, unless the test hook
    ``pallas_interpret`` is set. Default 1024 blocks: these kernels are
    grid-overhead-bound, so fewer/bigger blocks win on TPU (measured on
    v5e); long sequences clamp to the VMEM-driven group sizing.
    """
    if interpret is None:
        interpret = pallas_interpret()
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if q.shape[1] != k.shape[1]:
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    return _flash_attention(q, k, v, causal, scale, block_q, block_kv, interpret)
