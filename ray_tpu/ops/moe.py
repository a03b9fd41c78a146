"""Mixture-of-Experts layers: two of them, for two paths.

**The served layer is the dropless one** (``moe_route`` + ``moe_dropless``,
at the end of this file): a router (sigmoid scores or a softmax over all
outputs, each with a selection bias, or a softmax over the chosen logits),
top-k, the (token, expert) pairs sorted by expert, one grouped matrix
product over the experts (``jax.lax.ragged_dot``), gated experts (SwiGLU,
or ReLU-gated; ZERO-COMPUTE experts return their input and cost no
product), weighted combine.
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

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.ops.layers import gelu


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
            return weights * scale, experts.astype(jnp.int32)
        scores = (jax.nn.softmax(logits, axis=-1) if score == "softmax"
                  else jax.nn.sigmoid(logits))
        chosen_by = scores if bias is None else scores + bias.astype(
            jnp.float32)
        _, experts = jax.lax.top_k(chosen_by, top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        if norm_topk:
            weights = weights / (
                jnp.sum(weights, axis=-1, keepdims=True) + ROUTE_NORM_EPS)
        return weights * scale, experts.astype(jnp.int32)


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
    (``jax.lax.ragged_dot`` with the per-expert counts as group sizes:
    products in ``dtype``, float32 accumulation), so any routing is
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
    with jax.named_scope("moe_gmm"):
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
