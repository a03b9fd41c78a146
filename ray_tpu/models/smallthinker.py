"""SmallThinker family: one full-attention layer WITHOUT positional encoding
to three rotary layers that slide over a window, an odd group of query heads
over few K/V heads, and ReLU-gated experts whose router reads the layer's
INPUT, before attention, for serving.

Follows the public ``smallthinker`` configuration (PowerInfer
SmallThinker-21BA3B-Instruct's ``config.json``). For layer ``l`` with input
``x`` and ``h = RMSNorm(x; eps)``::

    logits = router_input(x, h) W_r            # [E], float32, highest
    e_1..e_k = the k largest logits;  w = softmax over those k
    q = h Wq [Hq, hd]   k = h Wk [Hkv, hd]   v = h Wv [Hkv, hd]   (no bias)
    rope_layout[l] = 1: q, k = rotary(q, k, pos), all hd dimensions, theta;
                        keys t with pos - W < t <= pos (``window_keys``)
    rope_layout[l] = 0: NO positional encoding; every key t <= pos
    x' = x + softmax(q k^T / sqrt(hd) + mask) v Wo
    g = RMSNorm(x')
    out = x' + sum_j w_j (relu(g Wgate_ej) * (g Wup_ej)) Wdown_ej

``layer_types`` names ``rope_layout`` / ``sliding_window_layout`` (they are
one list in the published file): ``full_attention`` is 0 / 0,
``sliding_attention`` 1 / 1. With ``norm_topk_prob`` the softmax over the
chosen logits equals the softmax over all experts renormalised over the
chosen (``moe_primary_router_apply_softmax`` with ``norm_topk_prob``): one
number, two ways to say it (tests/test_smallthinker.py holds both). Final
RMSNorm, an untied head. No norm over a head, no shared expert, no gate.

What the configuration does not say and this file reads by convention is
listed in benchmark/configs/smallthinker-21b-a3b-8l.json ``assumed``, each
reading ONE function here and one in benchmark/reference/smallthinker.py:

- ``router_input``: the router reads the NORMED input ``h`` (the other
  reading: the raw stream ``x``);
- ``window_keys``: a query at ``p`` sees the ``W`` keys ``p - W + 1 .. p``,
  itself included: the repo's ``window=`` (as ``laguna``), which is also
  transformers' ``sliding_window`` mask (the other reading: ``W`` keys
  BEHIND it, ``W + 1`` in all);
- "primary + secondary experts" of the family's description has no key in
  the configuration: the 64 primary experts are all there is.

Same conventions as models/laguna.py (a LIST of per-layer trees, float32
masters, activations in ``cfg.dtype``, the prefill / decode-step contract
of models/cached.py, K/V by GROUP of layers: ``kv_layout``,
``kv_table_groups``), with models/parts.py's final norm, head, working
state and plain windowed attention, and what this family forces:

- The route is taken BEFORE ``attend`` and carried past it to the expert
  layer (``_cached_layer``: ``route -> attend -> experts``): no other
  family here routes from anything but the tensor its experts read.
- 28 query heads over 4 K/V heads of 128: a group of SEVEN over the
  lane-dense pool every family has, here rows of 512,
  ``[n_kv_layer, num_blocks, block_size, 512]``
  (ops/paged_attention.py ``pool_shape``).
- ``state`` holds no per-sequence rows, only the expert layers' counters
  (``pairs`` ``[2, E, 2]``, ``reads`` ``[2]``, each a (low, high) pair of
  uint32 words; models/lfm2_moe.py), added to inside the program.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models import cached
from ray_tpu.models.laguna import LagunaConfig
from ray_tpu.models.lfm2_moe import lfm2_moe_counters as smallthinker_counters
from ray_tpu.models.parts import (
    count_pairs,
    final_norm,
    head_untied,
    leaf_tree,
    open_experts,
    rotary_tables,
    windowed_attention,
)
from ray_tpu.ops.layers import rms_norm, rope_partial
from ray_tpu.ops.moe import moe_dropless, moe_route, step_gmm_form

LAYER_KINDS = ("full_attention", "sliding_attention")
QK_GAIN = 1.4  # ``smallthinker_init``: wq and wk against fan_in ** -0.5


@dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936
    max_seq_len: int = 16384
    d_model: int = 2560
    n_head: int = 28
    n_kv_head: int = 4
    head_dim: int = 128
    # rope_layout / sliding_window_layout 0: full_attention; 1: sliding
    layer_types: tuple[str, ...] = (
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention") * 13
    sliding_window: int = 4096
    num_experts: int = 64
    top_k: int = 6
    d_expert: int = 768             # each expert's gated width
    norm_topk_prob: bool = True
    rope_theta: float = 1500000.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # decode attention backend / serving quantization: see models/gpt.py
    # GPTConfig. The engine refuses ``quantization`` for this family.
    attention_backend: str = "auto"
    quantization: str | None = None

    def __post_init__(self):
        # JSON lists arrive here: the config is a jit-cache key
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = sorted(set(self.layer_types) - set(LAYER_KINDS))
        if bad:
            raise ValueError(
                f"layer_types holds {bad}; this family has {LAYER_KINDS}")
        if self.n_head % self.n_kv_head:
            raise ValueError("query heads must be a multiple of n_kv_head")
        if self.top_k > self.num_experts:
            raise ValueError("top_k exceeds num_experts")
        if self.sliding_window < 1:
            raise ValueError("sliding_window must be >= 1")

    @staticmethod
    def tiny(vocab_size: int = 512) -> "SmallThinkerConfig":
        return SmallThinkerConfig(
            vocab_size=vocab_size, max_seq_len=128, d_model=64, n_head=14,
            n_kv_head=2, head_dim=16,
            layer_types=("full_attention", "sliding_attention",
                         "sliding_attention", "sliding_attention",
                         "full_attention"),
            sliding_window=8, num_experts=8, top_k=3, d_expert=32,
            rope_theta=10000.0,
        )

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    # Where a layer's K/V lives (serve/llm/kv_cache.py groups) is the
    # laguna family's rule, written over ``layer_types`` and
    # ``sliding_window`` alone: the full layers group 0, the sliding layers
    # of a period one group each, a group's layers one a period.
    kv_layout = LagunaConfig.kv_layout
    kv_table_groups = LagunaConfig.kv_table_groups
    n_kv_layer = LagunaConfig.n_kv_layer


def smallthinker_init(key: jax.Array, cfg: SmallThinkerConfig) -> dict:
    """Float32 masters, normal from ``key``, each matmul leaf with std
    ``fan_in ** -0.5`` and the projections back into the residual stream a
    further ``(2 L) ** -0.5`` smaller (models/lfm2_moe.py ``lfm2_moe_init``
    and its reasons). ``wq`` and ``wk`` are 1.4 x larger, as
    models/laguna.py's and for its reason: with no norm over a head a
    unit-variance ``q . k / sqrt(hd)`` has std 1, and a softmax over the
    thousands of keys a NoPE layer sees is then nearly flat: a layer whose
    output hardly depends on WHICH keys it saw would let a freed block, a
    window one key off or a rotary embedding left on pass the reference
    check. At 1.4 the scores' std is 2 on every layer (rotary keeps a
    vector's length): over 8,192 keys the largest weighs ~4% and some 150
    carry a row; over a window of 4,096, some 75. Larger (std 4: about one
    key a row) costs agreement with the float32 reference, because
    rounding of the scores grows with their std (PERF.md, PR 30)."""
    D, hd, Hq, Hkv = cfg.d_model, cfg.head_dim, cfg.n_head, cfg.n_kv_head
    E, F = cfg.num_experts, cfg.d_expert
    back = (2 * cfg.n_layer) ** -0.5

    def norm(key, *shape, fan_in, gain=1.0):
        return jax.random.normal(key, shape, jnp.float32) * (
            gain * fan_in ** -0.5)

    keys = jax.random.split(key, cfg.n_layer + 2)
    layers = []
    for i in range(cfg.n_layer):
        k = iter(jax.random.split(keys[i], 8))
        layers.append({
            "attn_norm": jnp.ones((D,), jnp.float32),
            "ffn_norm": jnp.ones((D,), jnp.float32),
            "wq": norm(next(k), D, Hq * hd, fan_in=D, gain=QK_GAIN),
            "wk": norm(next(k), D, Hkv * hd, fan_in=D, gain=QK_GAIN),
            "wv": norm(next(k), D, Hkv * hd, fan_in=D),
            "wo": norm(next(k), Hq * hd, D, fan_in=Hq * hd, gain=back),
            "moe_route_w": norm(next(k), D, E, fan_in=D),
            "moe_gmm_w_in": norm(next(k), E, D, 2 * F, fan_in=D),  # gate, up
            "moe_gmm_w_out": norm(next(k), E, F, D, fan_in=F, gain=back),
        })
    return {
        "wte": norm(keys[-2], cfg.vocab_size, D, fan_in=D),
        "layers": layers,
        "ln_f_scale": jnp.ones((D,), jnp.float32),
        "lm_head": norm(keys[-1], D, cfg.vocab_size, fan_in=D),
    }


_LEAF_AXES = {
    "attn_norm": ("embed",), "ffn_norm": ("embed",),
    "wq": ("embed", "mlp"), "wk": ("embed", "mlp"), "wv": ("embed", "mlp"),
    "wo": ("mlp", "embed"), "moe_route_w": (None, None),
    "moe_gmm_w_in": ("expert", None, "mlp"),
    "moe_gmm_w_out": ("expert", "mlp", None),
    "wte": ("vocab", "embed"), "ln_f_scale": ("embed",),
    "lm_head": ("embed", "vocab"),
}
# the contraction axis of each matmul weight; -1: kept as given (norm
# scales, and the router, which is read in float32)
_LEAF_QUANT = {"wq": 0, "wk": 0, "wv": 0, "wo": 0,
               "moe_gmm_w_in": 1, "moe_gmm_w_out": 1, "wte": 1, "lm_head": 0}


def smallthinker_param_axes(cfg: SmallThinkerConfig) -> dict:
    """Logical axis names per leaf; the experts get an axis of their own."""
    return leaf_tree(smallthinker_init, cfg, _LEAF_AXES.__getitem__)


def smallthinker_quant_axes(cfg: SmallThinkerConfig) -> dict:
    """Per leaf, the contraction axis of a matmul weight (>= 0: the
    executor stores it in ``cfg.dtype``, experts included) or -1."""
    return leaf_tree(smallthinker_init, cfg,
                     lambda name: _LEAF_QUANT.get(name, -1))


# ------------------------------------------------------------------ state


def smallthinker_init_state(cfg: SmallThinkerConfig, slots: int) -> dict:
    """The counters the step programs keep (no per-sequence rows: ``slots``
    only says which rows are padding, slot 0): every expert is held, so
    they are models/lfm2_moe.py's (``smallthinker_counters`` reads them)."""
    del slots
    return {"pairs": jnp.zeros((2, cfg.num_experts, 2), jnp.uint32),
            "reads": jnp.zeros((2,), jnp.uint32)}


# ----------------------------------------------------------------- layers


def router_input(x, h):
    """What the router reads, of the layer's input ``x`` and its normed
    form ``h = RMSNorm(x)``: the NORMED input (assumed; the other reading
    is the raw stream ``x``: a correction is this function and the
    reference's)."""
    del x
    return h


def window_keys(cfg: SmallThinkerConfig) -> int:
    """The keys a sliding layer's query at ``p`` sees, itself included: ``p
    - window_keys + 1 .. p`` (assumed: ``sliding_window`` of them; the
    other reading is ``sliding_window`` keys behind it, one more). The
    cache's groups hold what ``cfg.sliding_window`` says: the other reading
    is that key of the configuration, one more."""
    return cfg.sliding_window


def _route(x, h, lp, cfg: SmallThinkerConfig):
    """The layer's route, from its INPUT: (weights [T, k] f32, experts [T,
    k] int32) over the flattened tokens, taken before attention."""
    flat = router_input(x, h).reshape(-1, x.shape[-1])
    return moe_route(flat, lp["moe_route_w"], None, cfg.top_k,
                     norm_topk=cfg.norm_topk_prob, score="softmax_topk")


def _qkv(h, lp, kind: str, tables, cfg: SmallThinkerConfig):
    """Projections, and on a sliding layer the rotary embedding (a full
    layer's q and k carry no position at all). q [B, S, Hq, hd]; k, v [B,
    S, Hkv, hd] (the compact GQA heads, as the cache stores them)."""
    B, S, _ = h.shape
    Hq, Hkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    q = (h @ lp["wq"].astype(cfg.dtype)).reshape(B, S, Hq, hd)
    k = (h @ lp["wk"].astype(cfg.dtype)).reshape(B, S, Hkv, hd)
    v = (h @ lp["wv"].astype(cfg.dtype)).reshape(B, S, Hkv, hd)
    if kind == "sliding_attention":
        q, k = rope_partial(q, *tables), rope_partial(k, *tables)
    return q, k, v


def _experts(x, lp, route, cfg: SmallThinkerConfig, valid):
    """RMSNorm + the routed ReLU-gated experts + residual on x [B, S, D],
    under the ``route`` taken from the layer's input. ``valid`` [B, S]
    marks the real tokens. Returns (x', routed pairs by expert [E])."""
    B, S, D = x.shape
    g = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    y, sizes = moe_dropless(
        g.reshape(B * S, D), *route, lp["moe_gmm_w_in"], lp["moe_gmm_w_out"],
        dtype=cfg.dtype, valid=valid.reshape(B * S), act="relu")
    return x + y.reshape(B, S, D), sizes


def smallthinker_forward(params: dict, tokens: jax.Array,
                         cfg: SmallThinkerConfig) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32: the whole sequence at
    once, no cache (the program's own full forward)."""
    B, S = tokens.shape
    x = params["wte"].astype(cfg.dtype)[tokens]
    tables = rotary_tables(
        jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S)), cfg)
    valid = jnp.ones((B, S), bool)
    for lp, kind in zip(params["layers"], cfg.layer_types):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        route = _route(x, h, lp, cfg)
        q, k, v = _qkv(h, lp, kind, tables, cfg)
        attn = windowed_attention(
            q, k, v,
            window_keys(cfg) if kind == "sliding_attention" else None)
        x = x + attn @ lp["wo"].astype(cfg.dtype)
        x, _ = _experts(x, lp, route, cfg, valid)
    return head_untied(params, final_norm(params, x, cfg), cfg)


# ----------------------------------------------------------------------------
# Cached inference paths (serve/llm engine): what models/cached.py's one
# step needs of this family. The pool is lane-dense [n_kv_layer, num_blocks,
# block_size, n_kv_head * head_dim] at the published heads and the step's
# block tables are [n_group, B, NB]. Rows in slot 0 are padding: routed
# nowhere, counted nowhere.
# ----------------------------------------------------------------------------


def _cached_embed(params, tokens, step, cfg: SmallThinkerConfig):
    x = step.take(params["wte"].astype(cfg.dtype), tokens)
    return x, rotary_tables(step.pos, cfg)


def _cached_layer(x, lp, attend, step, work: dict, cfg: SmallThinkerConfig):
    i = work["layer"]
    kind = cfg.layer_types[i]
    group, slot, window = cfg.kv_layout[i]
    with jax.named_scope("attn_proj"):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        route = _route(x, h, lp, cfg)  # before attention, from the input
        q, k, v = _qkv(h, lp, kind, step.aux, cfg)
        attn = attend(q, k, v, group=group, slot=slot,
                      window=None if window is None else window_keys(cfg))
        x = x + attn @ lp["wo"].astype(cfg.dtype)
    with jax.named_scope("ffn"):
        x, sizes = _experts(x, lp, route, cfg, work["routed"])
    return x, {**work, "layer": i + 1, "sizes": [*work["sizes"], sizes]}


def _close_state(state: dict, work: dict, step, cfg: SmallThinkerConfig):
    return {**state, **count_pairs(
        state, work["sizes"], int(step.kind == "decode"))}


FAMILY = cached.CachedFamily(
    "smallthinker", SmallThinkerConfig, "layers", _cached_embed,
    _cached_layer, final_norm, head_untied, open_state=open_experts,
    close_state=_close_state,
    no_verify="a rejected window may reach behind freed blocks (the engine "
              "refuses speculation over grouped tables)",
    state_rows=False, gmm_form=step_gmm_form)
smallthinker_prefill, smallthinker_decode_step, _ = cached.steps(FAMILY)
