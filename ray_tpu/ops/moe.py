"""Mixture-of-Experts layers: two of them, for two paths.

**The served layer is the dropless one** (``moe_route`` + ``moe_dropless``,
at the end of this file): a router (sigmoid scores or a softmax over all
outputs, each with a selection bias, or a softmax over the chosen logits),
top-k, the (token, expert) pairs sorted by expert, one grouped matrix
product over the experts, gated experts (SwiGLU, or ReLU-gated;
ZERO-COMPUTE experts return their input and cost no product), weighted
combine. The grouped product has TWO FORMS, chosen by ``gmm_form`` from
the shapes a step is traced with (no option, no model's name):
``few_rows``, the repo's Pallas kernel ``moe_gmm_few_rows`` (a group's rows
stand still, 128 at a time counted from the group's first, and its expert
streams past them once, gate | up | activation | down in one call: every
decode step and every prefill step of the seven expert cells), and
``ragged``, XLA's
``jax.lax.ragged_dot`` twice (widths that are no whole lane tiles, and
steps wider than any that was timed: ``_FEW_PAIRS_AN_EXPERT``). XLA's
kernel picks its row tile from m = T x k and pays a whole tile's pass a
group, rows of no group included: 36% of HBM's peak at m = 1,024 where the
same groups at m = 128 read 65% (docs/MICROBENCHMARKS.md, PR 49).
Every routed pair is computed whatever the routing, so a token's output
depends on its own row only (batched == solo) and the layer can be checked
against a plain reference. ``models/lfm2_moe.py`` serves through it.

**The capacity layer** (``moe_forward``) is the one the llama TRAINING path
uses (``models/llama.py`` with ``num_experts > 0``), built for expert
parallelism over the ``ep`` mesh axis: softmax router, top-k gating with
capacity-bounded one-hot dispatch einsums (static shapes, no ragged
gather), GELU experts sharded on ``ep`` so that the dispatch/combine
einsums lower to all-to-alls, and the Switch load-balancing loss. Its
capacity is a share of the BATCH (``int(cf * k * T / E)``) and overflow
tokens are dropped, so a token's output depends on its batch-mates: right
for training at a fixed batch, not servable. The reference has NO MoE /
expert parallelism (SURVEY.md section 2.4 EP row: absent).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import pallas_interpret
from ray_tpu.ops.layers import gelu
from ray_tpu.ops.paged_attention import _VMEM_DEFAULT


@dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_hidden: int
    num_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_coeff: float = 0.01
    dtype: Any = jnp.bfloat16


def moe_init(key: jax.Array, cfg: MoEConfig) -> dict:
    kr, k1, k2 = jax.random.split(key, 3)
    E, D, H = cfg.num_experts, cfg.d_model, cfg.d_hidden
    return {
        "router": jax.random.normal(kr, (D, E), jnp.float32) * 0.02,
        "w_in": jax.random.normal(k1, (E, D, H), jnp.float32) * (D**-0.5),
        "w_out": jax.random.normal(k2, (E, H, D), jnp.float32) * (H**-0.5),
    }


def moe_logical_axes() -> dict:
    """Logical axis names per param (for ray_tpu.parallel.sharding rules:
    'expert' maps to the ep mesh axis)."""
    return {
        "router": (None, None),
        "w_in": ("expert", None, "mlp"),
        "w_out": ("expert", "mlp", None),
    }


def moe_forward(params: dict, x: jax.Array, cfg: MoEConfig):
    """x: [tokens, d_model] -> (y, aux_loss).

    Dispatch/combine are dense one-hot einsums over a capacity-bounded
    buffer [E, C, D]; with w_in/w_out sharded on the expert axis XLA turns
    the [E, C, D] intermediates into all-to-alls across ep.
    """
    T, D = x.shape
    E, k = cfg.num_experts, cfg.top_k
    capacity = max(1, int(cfg.capacity_factor * k * T / E))

    router_logits = (x.astype(jnp.float32) @ params["router"])  # [T, E] f32
    probs = jax.nn.softmax(router_logits, axis=-1)

    # top-k expert choice per token
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # [T, k]
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # position of each (token, choice) within its expert's capacity buffer
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)  # [T, k, E]
    flat = onehot.reshape(T * k, E)
    pos_in_expert = (jnp.cumsum(flat, axis=0) - flat).reshape(T, k, E)
    pos = jnp.sum(pos_in_expert * onehot, axis=-1)  # [T, k]
    keep = pos < capacity  # overflow tokens drop (standard Switch behavior)
    gate_vals = gate_vals * keep.astype(gate_vals.dtype)

    # dispatch tensor [T, k, E, C] — one-hot over (expert, slot)
    slot_onehot = jax.nn.one_hot(pos, capacity, dtype=cfg.dtype)  # [T, k, C]
    dispatch = (
        onehot.astype(cfg.dtype)[..., None] * slot_onehot[..., None, :]
    ) * keep.astype(cfg.dtype)[..., None, None]  # [T, k, E, C]
    combine = dispatch * gate_vals.astype(cfg.dtype)[..., None, None]

    xb = x.astype(cfg.dtype)
    expert_in = jnp.einsum("td,tkec->ecd", xb, dispatch)  # [E, C, D]
    h = gelu(jnp.einsum("ecd,edh->ech", expert_in, params["w_in"].astype(cfg.dtype)))
    expert_out = jnp.einsum("ech,ehd->ecd", h, params["w_out"].astype(cfg.dtype))
    y = jnp.einsum("ecd,tkec->td", expert_out, combine).astype(x.dtype)

    # load-balance aux loss (Switch eq. 4): E * sum_e f_e * P_e
    me = jnp.mean(probs, axis=0)  # mean router prob per expert
    # fraction of tokens whose top-1 choice is each expert
    ce = jnp.sum(
        jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32), axis=0
    ) / T
    aux = cfg.aux_loss_coeff * E * jnp.sum(me * ce)
    return y, aux


def moe_reference_dense(params: dict, x: jax.Array, cfg: MoEConfig) -> jax.Array:
    """Every token through every chosen expert WITHOUT capacity limits —
    correctness oracle for tests (top-k gating, no drops)."""
    T, D = x.shape
    probs = jax.nn.softmax(x.astype(jnp.float32) @ params["router"], axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, cfg.top_k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    xb = x.astype(cfg.dtype)
    # [E, T, D]: run all tokens through all experts, then select
    h = gelu(jnp.einsum("td,edh->eth", xb, params["w_in"].astype(cfg.dtype)))
    all_out = jnp.einsum("eth,ehd->etd", h, params["w_out"].astype(cfg.dtype))
    out = jnp.zeros_like(xb)
    for j in range(cfg.top_k):
        sel = jnp.take_along_axis(
            all_out, expert_idx[None, :, j, None], axis=0
        )[0]  # [T, D]
        out = out + sel * gate_vals[:, j, None].astype(cfg.dtype)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------------
# The served layer: dropless; sigmoid- or softmax-routed; gated experts.
# ----------------------------------------------------------------------------

# what the published LFM2-MoE code adds to the sum of the chosen scores
# before dividing by it (``norm_topk_prob``)
ROUTE_NORM_EPS = 1e-6
ROUTE_SCORES = ("sigmoid", "softmax", "softmax_topk")
EXPERT_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def moe_route(x: jax.Array, router: jax.Array, bias: jax.Array | None,
              top_k: int, *, norm_topk: bool = True, scale: float = 1.0,
              score: str = "sigmoid"):
    """x [T, D] -> (weights [T, k] f32, experts [T, k] int32).

    ``score="sigmoid"``: ``s = sigmoid(x @ router)``; the ``top_k`` experts
    are chosen by ``s + bias`` (the stored selection bias), but weighted by
    the UNBIASED ``s``, divided by their sum when ``norm_topk``, times
    ``scale``. ``score="softmax"``: the same with ``s = softmax(x @
    router)`` over ALL the router's outputs (models/longcat_flash.py: 768,
    of which 256 name zero-compute experts; not renormalised there).
    ``score="softmax_topk"`` (no bias): the ``top_k`` largest
    LOGITS, weighted by a softmax over those k alone, which is the softmax
    over all experts renormalised over the chosen (``norm_topk``; without
    it, the softmax over all at the chosen), times ``scale``. All of
    it in float32 at the highest matmul precision, whatever ``x`` is: a
    near-tie between the k-th and the (k+1)-th score is the one place where
    rounding changes WHICH weights a token meets."""
    return _route(x, router, bias, top_k, norm_topk, scale, score, None)[:2]


def moe_route_grouped(x: jax.Array, router: jax.Array,
                      bias: jax.Array | None, top_k: int,
                      groups: tuple[int, int], *, norm_topk: bool = True,
                      scale: float = 1.0):
    """``moe_route``'s sigmoid scores under DeepSeek-V3's group-limited
    selection (models/ling_hybrid.py) -> (weights [T, k] f32, experts
    [T, k] int32, the groups that stayed [T, n_group] bool).
    ``groups = (n_group, topk_group)``: the experts are ``n_group`` runs of
    equal length, a group's score is the sum of its two largest
    ``s + bias``, the ``topk_group`` best groups stay and the ``top_k`` are
    taken among theirs; the weights as ``moe_route``'s."""
    return _route(x, router, bias, top_k, norm_topk, scale, "sigmoid", groups)


def _route(x, router, bias, top_k, norm_topk, scale, score, groups):
    """(weights, experts, the groups that stayed or None)."""
    if score not in ROUTE_SCORES:
        raise ValueError(f"score must be one of {ROUTE_SCORES}, got {score!r}")
    with jax.named_scope("moe_route"):
        logits = jnp.dot(
            x.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        if score == "softmax_topk":
            if bias is not None:
                raise ValueError("a softmax_topk router has no selection bias")
            chosen, experts = jax.lax.top_k(logits, top_k)
            over = chosen if norm_topk else logits
            weights = jnp.exp(chosen - jax.nn.logsumexp(
                over, axis=-1, keepdims=True))
            return weights * scale, experts.astype(jnp.int32), None
        scores = (jax.nn.softmax(logits, axis=-1) if score == "softmax"
                  else jax.nn.sigmoid(logits))
        chosen_by = scores if bias is None else scores + bias.astype(
            jnp.float32)
        stays = None
        if groups is not None:
            chosen_by, stays = _keep_groups(chosen_by, *groups)
        _, experts = jax.lax.top_k(chosen_by, top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        if norm_topk:
            weights = weights / (
                jnp.sum(weights, axis=-1, keepdims=True) + ROUTE_NORM_EPS)
        return weights * scale, experts.astype(jnp.int32), stays


def _keep_groups(chosen_by: jax.Array, n_group: int, topk_group: int):
    """(``chosen_by`` [T, E] with the experts of every group but a
    token's ``topk_group`` best at -inf, those groups [T, n_group] bool);
    a group's score: the sum of its two largest entries."""
    T, E = chosen_by.shape
    if score_groups_bad(E, n_group, topk_group):
        raise ValueError(
            f"groups ({n_group}, {topk_group}) do not cut {E} experts into "
            "equal groups of two or more, of which some stay")
    by_group = chosen_by.reshape(T, n_group, E // n_group)
    group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
    _, kept = jax.lax.top_k(group_score, topk_group)          # [T, kept]
    stays = jnp.any(
        kept[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1)
    return jnp.where(
        stays[:, :, None], by_group, -jnp.inf).reshape(T, E), stays


def score_groups_bad(experts: int, n_group: int, topk_group: int) -> bool:
    """Whether ``(n_group, topk_group)`` is no grouping of ``experts``."""
    return (n_group < 1 or experts % n_group or experts // n_group < 2
            or not 0 < topk_group <= n_group)


# ----------------------------------------------------------------------------
# The grouped product with the rows standing still: ``few_rows``.
# ----------------------------------------------------------------------------

GMM_KERNEL_NAME = "moe_gmm_few_rows"
GMM_FORMS = ("ragged", "few_rows")
_FEW_ROWS_TILE = 128
# a window of sorted rows starts on a whole tile of the output's rows (8 of
# float32; HBM tiles bf16 by 8 rows too): the chip's copies take no other
_FEW_ROWS_ALIGN = 8
_FEW_WEIGHT_TILE_BYTES = 2 * 1024 * 1024
# the widest step timed: pairs (held or not) an expert the weights hold
_FEW_PAIRS_AN_EXPERT = 2048


def _tile_rows(rows: int, row_bytes: int, target: int) -> int:
    """Rows of a weight tile: the largest multiple of 128 that divides
    ``rows`` and keeps the tile within ``target`` bytes (128 where none
    does: a tile is never less than a lane tile deep)."""
    best = 128
    for n in range(128, rows + 1, 128):
        if rows % n == 0 and n * row_bytes <= target:
            best = n
    return best


def few_rows_items(sizes, tm: int = _FEW_ROWS_TILE):
    """How many work items ``moe_gmm_few_rows`` makes of groups of
    ``sizes`` rows (numpy or jax, [E]): ``ceil(size / tm)`` a group, which
    is how many times the kernel streams that group's expert. One wherever
    a group has at most ``tm`` rows, whatever row it starts at."""
    return (-(-sizes // tm)).sum()


def few_rows_items_bound(experts: int, m: int, tm: int = _FEW_ROWS_TILE) -> int:
    """The static length of the kernel's lists over ``m`` sorted rows: no
    sizes that sum to ``m`` or less make more items (a group with rows
    makes one, and one more for every whole ``tm`` rows it holds)."""
    return min(experts, m) + m // tm


def _work_items(sizes, tm: int, items: int, align: int, last_win: int):
    """The kernel's lists, from the groups' sizes [E] int32: for every group
    with rows, one item for each ``tm`` rows OF THE GROUP, counted from its
    first row (``few_rows_items``), in order: ``(group, window, first row,
    end)`` each [items] int32, and how many items there are. An item's
    rows are ``first .. end`` (at most ``tm``, the group's next); its
    window is the ``tm + align`` sorted rows from ``window * align``, the
    tile of ``align`` rows that holds its first row (or ``last_win``, the
    last window that ends inside the buffer). ``items`` is the lists'
    length (``few_rows_items_bound``); past the count they hold group 0 and
    no rows, and the grid does not go there. Sums over masks, no scan,
    search or gather: eight small operations on the device where the
    plain form (two ``cumsum``, a ``searchsorted``, four gathers) compiled
    to twenty, 15 us of laguna's 288 us call (docs/MICROBENCHMARKS.md,
    PR 49)."""
    E = sizes.shape[0]
    g = jnp.arange(E, dtype=jnp.int32)
    upto = g[None, :] <= g[:, None]  # [E, E]: group j stands at or before g
    ends = jnp.sum(jnp.where(upto, sizes[None, :], 0), axis=1)
    starts = ends - sizes
    reach = -(-sizes // tm)
    item_end = jnp.sum(jnp.where(upto, reach[None, :], 0), axis=1)
    item_start = item_end - reach
    w = jnp.arange(items, dtype=jnp.int32)
    # [items, E]: item w is one of group g's (at most one g a row)
    mine = (item_start[None, :] <= w[:, None]) & (w[:, None] < item_end[None, :])

    def pick(v):
        return jnp.sum(jnp.where(mine, v, 0), axis=1)

    lo = pick(starts[None, :] + (w[:, None] - item_start[None, :]) * tm)
    hi = jnp.minimum(lo + tm, pick(ends[None, :]))
    win = jnp.minimum(lo // align, last_win)
    return pick(g[None, :]), win, lo, hi, item_end[-1]


def _moe_gmm_few_rows_kernel(grp, win, lo, hi, tot, x_ref, w_in_ref,
                             w_out_ref, y_hbm, h_scr, g_scr, res, carry,
                             sent, sem, *, n_in, n_out, rows, align, F, tf,
                             act):
    """One work item (an expert x the next rows of its group, at most
    ``tm``) a step of grid axis 0, over a WINDOW of ``rows`` = ``tm +
    align`` sorted rows that starts on a tile of ``align`` and holds the
    item's; axis 1 walks the expert's matrices: ``n_in`` row tiles of
    ``w_in`` [td, 2F] into ``h_scr`` (the gate and up products, float32),
    the activation once into ``g_scr`` with the rows that are not the
    item's zeroed, then ``n_out`` row tiles of ``w_out`` [tf, D] into
    ``res``. The output stays in HBM and the kernel copies ``res`` there
    itself, a whole tile of ``align`` rows once: the tile an item ends
    inside is CARRIED to the next item (whose rows begin there) and added
    to the head of its window; an item sends the tiles from its first row's
    to the one its end falls in (the last item that one too), in pieces of
    1, 2, 4 .. tiles, the binary digits of their count."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    w, t = pl.program_id(0), pl.program_id(1)
    real = tot[0] > 0  # a grid has one step even where no group has a row
    tiles = rows // align

    def pieces(n, src, dst):
        """(whether, the copy) for each binary digit of ``n`` tiles sent
        from tile ``src`` of ``res`` to tile ``dst`` of the output."""
        for j in range(tiles.bit_length()):
            before = (n >> (j + 1)) << (j + 1)

            def at(tile):
                return pl.ds(pl.multiple_of((tile + before) * align, align),
                             align << j)
            yield (n >> j) & 1 == 1, pltpu.make_async_copy(
                res.at[at(src)], y_hbm.at[at(dst)], sem.at[0])

    def wait_sent():
        for sending, copy in pieces(sent[0], 0, 0):  # sizes alone matter
            pl.when(sending)(copy.wait)

    @pl.when((w == 0) & (t == 0))
    def _():
        carry[...] = jnp.zeros_like(carry)

    @pl.when(real & (t < n_in))
    def _():
        prod = jnp.dot(x_ref[...], w_in_ref[0],
                       preferred_element_type=jnp.float32)

        @pl.when(t == 0)
        def _():
            h_scr[...] = prod

        @pl.when(t > 0)
        def _():
            h_scr[...] += prod

    @pl.when(real & (t == n_in - 1))
    def _():
        h = h_scr[...]
        row = win[w] * align + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0)
        mine = (row >= lo[w]) & (row < hi[w])
        gated = jnp.where(
            mine, EXPERT_ACTS[act](h[:, :F]) * h[:, F:], 0.0
        ).astype(g_scr.dtype)
        for j in range(n_out):
            g_scr[j] = gated[:, j * tf:(j + 1) * tf]

    @pl.when(real & (t >= n_in))
    def _():
        j = t - n_in
        prod = jnp.dot(g_scr[j], w_out_ref[0],
                       preferred_element_type=jnp.float32)

        @pl.when(j == 0)
        def _():
            # the item before's tiles have left ``res`` (they had this
            # item's whole first product to go)
            pl.when(w > 0)(wait_sent)
            res[...] = prod

        @pl.when(j > 0)
        def _():
            res[...] += prod

    @pl.when(real & (t == n_in + n_out - 1))
    def _():
        first = lo[w] // align - win[w]  # tiles, from the window's start
        end = hi[w] // align - win[w]
        open_ = hi[w] % align > 0  # the item ends inside a tile
        last = w == tot[0] - 1
        head = pl.ds(pl.multiple_of(first * align, align), align)
        res[head, :] += carry[...]
        tail = pl.ds(pl.multiple_of(
            jnp.minimum(end, tiles - 1) * align, align), align)
        carry[...] = jnp.where(open_, res[tail, :], 0.0)
        sent[0] = end - first + (last & open_).astype(jnp.int32)
        for sending, copy in pieces(sent[0], first, win[w] + first):
            pl.when(sending)(copy.start)

        pl.when(last)(wait_sent)


@functools.partial(
    jax.jit, static_argnames=("act", "tm", "tile_bytes", "interpret"))
def _moe_gmm_few_rows_call(xs, w_in, w_out, sizes, *, act, tm, tile_bytes,
                           interpret):
    """``moe_gmm_few_rows`` behind a jit of its own: a step program calls it
    once an expert layer with the same shapes, and the inner jit's cache
    makes the kernel's text traced once a process and lowered once a
    program, not once a layer (ops/paged_attention.py ``_latent_call``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, D = xs.shape
    E, F = w_out.shape[:2]
    itemsize = jnp.dtype(xs.dtype).itemsize
    td = _tile_rows(D, 2 * F * itemsize, tile_bytes)
    tf = _tile_rows(F, D * itemsize, tile_bytes)
    n_in, n_out = D // td, F // tf
    align = _FEW_ROWS_ALIGN
    rows = tm + align  # a window: an item's rows, from a tile's first row
    # whole tiles and one window at the least: nothing to pad where a step
    # sorts a multiple of 8 pairs, 136 or more
    m_pad = max(-(-m // align) * align, rows)
    xs = jnp.pad(xs, ((0, m_pad - m), (0, 0)))
    grp, win, lo, hi, total = _work_items(
        sizes, tm, few_rows_items_bound(E, m, tm), align,
        (m_pad - rows) // align)

    def x_map(w, t, grp, win, lo, hi, tot):
        return (win[w] * align, jnp.minimum(t, n_in - 1) * td)

    def w_in_map(w, t, grp, win, lo, hi, tot):
        return (grp[w], jnp.minimum(t, n_in - 1), 0)

    def w_out_map(w, t, grp, win, lo, hi, tot):
        # while an item's ``w_in`` streams, the item BEFORE's last tile
        # stays: the first tile of this item's ``w_out`` is then copied
        # under the last ``w_in`` product, not beside the first
        ahead = (t < n_in) & (w > 0)
        return (jnp.where(ahead, grp[jnp.maximum(w - 1, 0)], grp[w]),
                jnp.where(ahead, n_out - 1, jnp.maximum(t - n_in, 0)), 0)

    vmem = 2 * (rows * td + td * 2 * F + tf * D) * itemsize \
        + (rows + align) * D * 4 + 2 * rows * 2 * F * 4 + rows * F * itemsize
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        # as many items as there are: the bound is the lists' length only
        grid=(jnp.maximum(total, 1), n_in + n_out),
        in_specs=[
            # a window starts at a ROW (of whole tiles), not at a block
            pl.BlockSpec((pl.Element(rows), pl.Element(td)), x_map),
            pl.BlockSpec((1, td, 2 * F), w_in_map),
            pl.BlockSpec((1, tf, D), w_out_map),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((rows, 2 * F), jnp.float32),
            pltpu.VMEM((n_out, rows, tf), xs.dtype),
            pltpu.VMEM((rows, D), jnp.float32),
            pltpu.VMEM((align, D), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SemaphoreType.DMA((1,)),
        ],
    )
    ys = pl.pallas_call(
        functools.partial(
            _moe_gmm_few_rows_kernel, n_in=n_in, n_out=n_out, rows=rows,
            align=align, F=F, tf=tf, act=act),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # in order, on one core: an item takes the tile the one before
            # ended inside
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(2 * vmem, _VMEM_DEFAULT),
        ),
        name=GMM_KERNEL_NAME,
        interpret=interpret,
    )(grp, win, lo, hi, total.reshape(1), xs, w_in, w_out)
    return ys[:m]


def moe_gmm_few_rows(xs: jax.Array, w_in: jax.Array, w_out: jax.Array,
                     sizes: jax.Array, *, act: str = "silu",
                     interpret: bool | None = None) -> jax.Array:
    """The two grouped products and the activation between them as ONE
    Pallas kernel, made for groups of few rows (a group of many costs a
    read of its expert a 128 rows): xs [m, D] sorted by group (group
    g owns rows ``sum(sizes[:g]) .. + sizes[g]``; rows past the last group
    belong to none), ``w_in`` [E, D, 2F], ``w_out`` [E, F, D], all of one
    dtype -> ys [m, D] float32, a group's rows through its expert; the rows
    of no group hold whatever was there (the caller masks them, as after
    ``ragged_dot``).

    The rows stand still and the weights stream: a work item is a group's
    next ``_FEW_ROWS_TILE`` rows, counted from the GROUP's first row, so an
    expert with n rows is read ``ceil(n / 128)`` times (``few_rows_items``):
    once wherever n <= 128, whatever sorted row the group starts at (until
    PR 57 an item was an aligned tile of sorted rows, and a group across a
    tile's edge read its expert twice: 175 reads for 128 experts a folded
    pass of cell 13). The weights come in tiles of whole rows of the stored
    matrices (``_FEW_WEIGHT_TILE_BYTES``: each one contiguous copy), the
    next tile, the next expert's first included, copied under the current
    one's product by the pipeline; an expert with no row is never in the
    list. An item's rows reach the kernel as a window of 128 + 8 sorted
    rows from the tile of 8 that holds its first (the chip copies rows by
    whole tiles), ``h`` (gate | up, float32), the activation and the
    window's output stay in fast memory, and every tile of 8 output rows
    goes to HBM once: the one an item ends inside rides to the next item."""
    if interpret is None:
        interpret = pallas_interpret()
    return _moe_gmm_few_rows_call(
        xs, w_in, w_out, sizes.astype(jnp.int32), act=act,
        tm=_FEW_ROWS_TILE, tile_bytes=_FEW_WEIGHT_TILE_BYTES,
        interpret=bool(interpret))


def gmm_form(pairs: int, experts: int, d_model: int, d_expert: int) -> str:
    """Which form ``moe_dropless`` gives its grouped product, from the
    shapes it is traced with: ``pairs`` = T x k rows, the ``experts`` the
    weights hold, their widths. ``few_rows`` wherever the kernel can tile
    the widths (whole lane tiles) and the step lies inside what was timed
    (docs/MICROBENCHMARKS.md, PR 49: the layer alone on a v5e, ``ragged``
    -> ``few_rows`` in us, with the list of then, an item an aligned tile
    of 128 sorted rows; PR 57's list, an item a group's next 128 rows, is
    timed there beside it): the five cells' decode steps 2,496 -> 1,695
    (lfm2, 4 pairs an expert), 567 -> 286 (laguna, 16 of which 2 held),
    2,548 -> 1,187 (openPangu, 128 of which 5 held), 1,431 -> 1,133
    (smallthinker, 4.5), 1,422 -> 1,211 (LongCat, 72 of which 1.6 held),
    and their prefill steps up to 2,048 pairs an expert (openPangu's
    2,048-token chunk 5,979 -> 4,316; 512 held rows an expert a tie, -5%;
    768 the one loss, +2%). Past that nothing was timed and XLA's kernel
    stays."""
    if d_model % 128 or d_expert % 128:
        return "ragged"
    return ("few_rows" if pairs <= _FEW_PAIRS_AN_EXPERT * experts
            else "ragged")


def step_gmm_form(cfg, rows: int) -> str:
    """The form the grouped product of a step program of ``rows`` tokens
    takes under an expert family's config (``top_k``, ``d_model``,
    ``d_expert``, ``n_held`` or ``num_experts``): what ``moe_dropless``
    decides when that program is traced, for ``stats()["moe_gmm_form"]``
    and the decode flight record (decode.py ``Family.gmm_form``)."""
    return gmm_form(rows * cfg.top_k,
                    getattr(cfg, "n_held", cfg.num_experts), cfg.d_model,
                    cfg.d_expert)


def moe_dropless(x: jax.Array, weights: jax.Array, experts: jax.Array,
                 w_in: jax.Array, w_out: jax.Array, *, dtype,
                 valid: jax.Array | None = None,
                 held: tuple[int, int] | None = None, act: str = "silu",
                 zero_from: int | None = None):
    """The expert layer proper: x [T, D] -> (y [T, D], pairs_by_expert [E]
    int32).

    ``w_in`` [E, D, 2F] packs each expert's gate and up projections (gate
    first), ``w_out`` [E, F, D]: an expert is ``(act(x gate) * (x up))
    down``, ``act`` ``silu`` (SwiGLU) or ``relu``. The T * k (token,
    expert) pairs are sorted by expert and met by ONE grouped product each way
    (``gmm_form``: the kernel ``moe_gmm_few_rows``, or ``jax.lax.ragged_dot``
    twice, with the per-expert counts as group sizes; either way products
    in ``dtype``, float32 accumulation), so any routing is
    computed whole, all tokens on one expert included: no capacity, no
    drop. ``valid`` [T] bool marks the real rows of a bucketed batch:
    pairs of padding rows sort behind every expert's, belong to no group,
    are not computed and not counted, and their output is zero.

    ``held = (first, count)``: this device holds experts ``first ..
    first + count - 1`` of the ones the router scores (``w_in`` and
    ``w_out`` lead with ``count``, ``experts`` keeps the router's ids).
    A pair routed to an expert that is not held sorts behind every group
    with the padding rows, is not computed and not counted; ``y`` is then
    the part of the layer's output that the held experts give, and the
    parts of all the holders add up to the whole layer's. No exchange of
    rows is made here: that is the caller's, across devices.

    ``zero_from``: ids ``>= zero_from`` name ZERO-COMPUTE experts, which
    return their input. Such a pair sorts behind every group with the
    padding rows and costs no product; it adds ``w * x`` in float32, here,
    whatever is held: a token's zero picks are computed where the token
    is, so among the holders of one layer they are counted ONCE (by the
    token's own device), not once a holder. ``held`` keeps its meaning for
    the ids below ``zero_from``, which are the only ones counted."""
    T, D = x.shape
    k = experts.shape[1]
    E = w_in.shape[0]
    if zero_from is not None and held is None:
        held = (0, E)  # every real expert is held: the zero ids are in no group
    # ``moe_move``: what stands between the router and the grouped product
    # and behind it (the sort, the rows' gather, the way back, the weighted
    # combine); ``moe_gmm``: the grouped product alone
    with jax.named_scope("moe_move"):
        flat = experts.reshape(T * k)
        kept = None  # [T * k]: the pairs some group computes; None: all
        if held is not None:
            first, count = held
            if count != E:
                raise ValueError(
                    f"held names {count} experts, the weights hold {E}")
            kept = (flat >= first) & (flat < first + count)
            if valid is not None:
                kept = kept & jnp.repeat(valid, k)
            flat = jnp.where(kept, flat - first, E)
        elif valid is not None:
            flat = jnp.where(jnp.repeat(valid, k), flat, E)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.bincount(flat, length=E + 1)[:E].astype(jnp.int32)
        xs = x.astype(dtype)[order // k]
    with jax.named_scope("moe_gmm"):
        if gmm_form(T * k, E, D, w_out.shape[1]) == "few_rows":
            ys = moe_gmm_few_rows(
                xs, w_in.astype(dtype), w_out.astype(dtype), sizes, act=act)
        else:
            h = jax.lax.ragged_dot(
                xs, w_in.astype(dtype), sizes,
                preferred_element_type=jnp.float32,
            )
            gate, up = jnp.split(h, 2, axis=-1)
            gated = (EXPERT_ACTS[act](gate) * up).astype(dtype)
            ys = jax.lax.ragged_dot(
                gated, w_out.astype(dtype), sizes,
                preferred_element_type=jnp.float32,
            )
    with jax.named_scope("moe_move"):
        # back to (token, choice) order, then the weighted sum over choices
        back = jnp.zeros((T * k,), jnp.int32).at[order].set(
            jnp.arange(T * k, dtype=jnp.int32))
        yk = ys[back].reshape(T, k, D)
        w = weights.astype(jnp.float32)
        if kept is not None:
            kept = kept.reshape(T, k)
            yk = jnp.where(kept[:, :, None], yk, 0.0)
            w = jnp.where(kept, w, 0.0)
        elif valid is not None:
            # rows past the last group hold whatever the product left there
            yk = jnp.where(valid[:, None, None], yk, 0.0)
            w = jnp.where(valid[:, None], w, 0.0)
        y = jnp.einsum("tkd,tk->td", yk, w,
                       precision=jax.lax.Precision.HIGHEST)
        if zero_from is None:
            return y.astype(x.dtype), sizes
    with jax.named_scope("moe_zero"):
        w = jnp.where(experts >= zero_from, weights.astype(jnp.float32), 0.0)
        if valid is not None:
            w = jnp.where(valid[:, None], w, 0.0)
        y = y + jnp.sum(w, axis=-1, keepdims=True) * x.astype(jnp.float32)
    return y.astype(x.dtype), sizes
