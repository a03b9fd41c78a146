"""Laguna family: full and sliding-window attention layers with different
head counts in one stack, a gate a head, a shared expert beside the routed
ones, and one device's share of the experts, for serving.

Follows the public ``laguna`` configuration (poolside Laguna-XS.2's
``config.json``). With ``h = RMSNorm(x)``, layer ``l`` of type ``t_l`` with
``H_l`` query heads is::

    q = h Wq [H_l, hd]   k = h Wk [Hkv, hd]   v = h Wv [Hkv, hd]
    q, k = rotary_t(q, k, pos)
    a = softmax(q k^T / sqrt(hd) + causal [sliding: keys t > pos - W]) v
    y = x + (sigmoid(h Wg)[H_l] * a) Wo            # one gate a head
    z = RMSNorm(y)
    dense:   out = y + SwiGLU(z)
    sparse:  out = y + SwiGLU_shared(z) + sum_j w_j SwiGLU_{e_j}(z)

``full_attention`` layers rotate the first ``partial_rotary_full`` of a
head's dimensions with YaRN-scaled frequencies (cos and sin times the
attention factor); ``sliding_attention`` layers rotate the whole head with
plain frequencies and see the last ``sliding_window`` positions. The router
is ``moe_route`` without a selection bias (sigmoid scores, the chosen ones
divided by their sum, times ``routed_scaling_factor``). Final RMSNorm, an
untied head. What the configuration does not say and this file reads by
convention is listed in benchmark/configs/laguna-xs.2-ep8-8l.json
``assumed``; the gate is ONE function here (``_head_gate``).

Same conventions as models/lfm2_moe.py (a LIST of per-layer trees, float32
masters, activations in ``cfg.dtype``, the prefill / decode-step contract
of models/cached.py) with what this family forces:

- The K/V of a layer lives in a GROUP's blocks: the full layers are one
  group, the sliding layers ``n_sliding_group`` more, every group with
  ``n_kv_layer`` layers, so that one block id names ``n_kv_layer`` slots
  of every kind and the pool is ``[n_kv_layer, num_blocks, ...]``
  (``kv_layout``). A layer tells the cached step its group, its slot and
  its window (``attend(q, k, v, group=, slot=, window=)``); the cache
  manager (serve/llm/kv_cache.py) keeps one table a group and gives a
  sliding group's blocks back behind the window.
- ``experts_held = (first, count)``: the layer routes over ``num_experts``
  and computes the part of the result its own ``count`` experts give
  (``moe_dropless(held=)``); the expert leaves lead with ``count``. The
  shared expert is computed for every token. None: all of them.
- ``state`` holds no per-sequence rows, only the expert layers' counters,
  added to inside the program: ``pairs`` ``[2, held, 2]`` (pairs that met a
  HELD expert, by expert, prefill and decode apart), ``routed`` ``[2, 2]``
  (all routed pairs of real tokens: ``top_k`` a token an expert layer) and
  ``reads`` ``[2]`` (held experts that got a token, summed over decode
  steps and expert layers), each a (low, high) pair of uint32 words.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models import cached
from ray_tpu.models.parts import (
    close_experts,
    count_value,
    final_norm,
    head_untied,
    leaf_tree,
    open_experts,
    swiglu,
    windowed_attention,
)
from ray_tpu.ops.layers import rms_norm, rope_partial, yarn_inv_freq
from ray_tpu.ops.moe import moe_dropless, moe_route, step_gmm_form

LAYER_KINDS = ("full_attention", "sliding_attention")
QK_GAIN = 1.4  # ``laguna_init``: wq and wk against fan_in ** -0.5


@dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    max_seq_len: int = 262144
    d_model: int = 2048
    n_head_full: int = 48           # query heads of a full_attention layer
    n_head_sliding: int = 64        # ... of a sliding_attention layer
    n_kv_head: int = 8
    head_dim: int = 128
    layer_types: tuple[str, ...] = (
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention")
    sliding_window: int = 512
    num_dense_layers: int = 1
    d_mlp: int = 8192               # dense SwiGLU width
    num_experts: int = 256          # what the router scores
    top_k: int = 8
    d_expert: int = 512             # each routed expert's SwiGLU width
    d_shared: int = 512             # the shared expert's
    experts_held: tuple[int, int] | None = None  # (first, count); None: all
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    rope_theta_full: float = 500000.0
    rope_theta_sliding: float = 10000.0
    partial_rotary_full: float = 0.5
    yarn_factor: float = 64.0
    yarn_original_max: int = 4096
    yarn_beta_fast: float = 64.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.4158883083359672
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # decode attention backend / serving quantization: see models/gpt.py
    # GPTConfig. The engine refuses ``quantization`` for this family.
    attention_backend: str = "auto"
    quantization: str | None = None

    def __post_init__(self):
        # JSON lists arrive here: the config is a jit-cache key
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.experts_held is not None:
            object.__setattr__(
                self, "experts_held", tuple(int(n) for n in self.experts_held))
            first, count = self.experts_held
            if not (0 <= first and 0 < count
                    and first + count <= self.num_experts):
                raise ValueError(
                    f"experts_held {self.experts_held} is not a range of the "
                    f"{self.num_experts} experts")
        bad = sorted(set(self.layer_types) - set(LAYER_KINDS))
        if bad:
            raise ValueError(
                f"layer_types holds {bad}; this family has {LAYER_KINDS}")
        for n in (self.n_head_full, self.n_head_sliding):
            if n % self.n_kv_head:
                raise ValueError("query heads must be a multiple of n_kv_head")
        if not 0 <= self.num_dense_layers <= len(self.layer_types):
            raise ValueError("num_dense_layers exceeds the layer count")
        if self.top_k > self.num_experts:
            raise ValueError("top_k exceeds num_experts")
        if self.sliding_window < 1:
            raise ValueError("sliding_window must be >= 1")

    @staticmethod
    def tiny(vocab_size: int = 512) -> "LagunaConfig":
        return LagunaConfig(
            vocab_size=vocab_size, max_seq_len=128, d_model=64,
            n_head_full=4, n_head_sliding=6, n_kv_head=2, head_dim=16,
            layer_types=("full_attention", "sliding_attention",
                         "sliding_attention", "sliding_attention",
                         "full_attention"),
            sliding_window=8, num_dense_layers=1, d_mlp=128, num_experts=8,
            top_k=2, d_expert=32, d_shared=32, yarn_original_max=16,
            yarn_factor=8.0, yarn_beta_fast=4.0,
        )

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def n_moe_layer(self) -> int:
        return self.n_layer - self.num_dense_layers

    @property
    def n_held(self) -> int:
        """Experts whose weights this device holds."""
        return (self.num_experts if self.experts_held is None
                else self.experts_held[1])

    def n_head_of(self, kind: str) -> int:
        return (self.n_head_full if kind == "full_attention"
                else self.n_head_sliding)

    # ---- where a layer's K/V lives (serve/llm/kv_cache.py groups) ----

    @property
    def kv_layout(self) -> tuple[tuple[int, int, int | None], ...]:
        """Per layer ``(group, slot, window)``. The full layers are group
        0; sliding layer ``j`` (counted among the sliding ones) is in
        group ``1 + j % n_sliding_group`` at slot ``j // n_sliding_group``:
        with the published period (full, sliding x 3) the sliding layers
        of one period fall into three groups and a group's layers are one
        a period, as the full group's are."""
        n_full = self.layer_types.count("full_attention")
        n_sliding = self.n_layer - n_full
        groups = -(-n_sliding // n_full) if n_full and n_sliding else 1
        first = 1 if n_full else 0  # no full layer: the sliding are group 0
        out, full, sliding = [], 0, 0
        for kind in self.layer_types:
            if kind == "full_attention":
                out.append((0, full, None))
                full += 1
            else:
                out.append((first + sliding % groups, sliding // groups,
                            self.sliding_window))
                sliding += 1
        return tuple(out)

    @property
    def kv_table_groups(self) -> tuple[tuple[int | None, tuple[int, ...]], ...]:
        """What the cache manager is told of each group, ``(window,
        layers)``: its window (None: every token is kept) and the layers
        whose K/V it holds (``KVCacheConfig.groups``)."""
        groups: dict[int, tuple] = {}
        for i, (g, _, window) in enumerate(self.kv_layout):
            groups[g] = (window, groups.get(g, (window, ()))[1] + (i,))
        return tuple(groups[g] for g in sorted(groups))

    @property
    def n_kv_layer(self) -> int:
        """Slots of the pool's layer axis: the most layers a group has."""
        return max(slot for _, slot, _ in self.kv_layout) + 1


def laguna_init(key: jax.Array, cfg: LagunaConfig) -> dict:
    """Float32 masters, normal from ``key``, each matmul leaf with std
    ``fan_in ** -0.5`` and the projections back into the residual stream a
    further ``(2 L) ** -0.5`` smaller (models/lfm2_moe.py ``lfm2_moe_init``
    and its reasons). ``wq`` and ``wk`` are 1.4 x larger: with no norm over
    a head (assumed) a unit-variance q . k / sqrt(hd) has std 1, a softmax
    that is nearly flat over 512 keys and flatter over thousands, and a
    layer whose output hardly depends on WHICH keys it saw would let a
    freed block pass the reference check. At 1.4 the scores' std is 2 on a
    sliding layer and about 3 on a full one (YaRN's attention factor
    squared is 2 on the rotated half): some ten keys carry a row. At 2 x
    (std 4 and 6, nearly one key a row) the served bfloat16 program
    agreed with the float32 reference's choice at 35% of positions on the
    chip: rounding of the scores grows with their std (PERF.md, PR 30).
    The gate's leaf is drawn at unit scale, so that the gates spread over
    (0, 1)."""
    D, hd, Hkv = cfg.d_model, cfg.head_dim, cfg.n_kv_head
    E, F, M, Fs = cfg.n_held, cfg.d_expert, cfg.d_mlp, cfg.d_shared
    back = (2 * cfg.n_layer) ** -0.5

    def norm(key, *shape, fan_in, gain=1.0):
        return jax.random.normal(key, shape, jnp.float32) * (
            gain * fan_in ** -0.5)

    keys = jax.random.split(key, cfg.n_layer + 2)
    layers = []
    for i, kind in enumerate(cfg.layer_types):
        k = iter(jax.random.split(keys[i], 12))
        Hq = cfg.n_head_of(kind)
        lp: dict = {
            "attn_norm": jnp.ones((D,), jnp.float32),
            "ffn_norm": jnp.ones((D,), jnp.float32),
            "wq": norm(next(k), D, Hq * hd, fan_in=D, gain=QK_GAIN),
            "wk": norm(next(k), D, Hkv * hd, fan_in=D, gain=QK_GAIN),
            "wv": norm(next(k), D, Hkv * hd, fan_in=D),
            "wo": norm(next(k), Hq * hd, D, fan_in=Hq * hd, gain=back),
            "attn_gate_w": norm(next(k), D, Hq, fan_in=D),
        }
        if i < cfg.num_dense_layers:
            lp["mlp_in"] = norm(next(k), D, 2 * M, fan_in=D)  # gate, up
            lp["mlp_out"] = norm(next(k), M, D, fan_in=M, gain=back)
        else:
            lp["moe_route_w"] = norm(next(k), D, cfg.num_experts, fan_in=D)
            lp["moe_gmm_w_in"] = norm(next(k), E, D, 2 * F, fan_in=D)
            lp["moe_gmm_w_out"] = norm(next(k), E, F, D, fan_in=F,
                                       gain=back)
            lp["moe_shared_w_in"] = norm(next(k), D, 2 * Fs, fan_in=D)
            lp["moe_shared_w_out"] = norm(next(k), Fs, D, fan_in=Fs,
                                          gain=back)
        layers.append(lp)
    return {
        "wte": norm(keys[-2], cfg.vocab_size, D, fan_in=D),
        "layers": layers,
        "ln_f_scale": jnp.ones((D,), jnp.float32),
        "lm_head": norm(keys[-1], D, cfg.vocab_size, fan_in=D),
    }


_LEAF_AXES = {
    "attn_norm": ("embed",), "ffn_norm": ("embed",),
    "wq": ("embed", "mlp"), "wk": ("embed", "mlp"), "wv": ("embed", "mlp"),
    "wo": ("mlp", "embed"), "attn_gate_w": ("embed", None),
    "mlp_in": ("embed", "mlp"), "mlp_out": ("mlp", "embed"),
    "moe_route_w": (None, None),
    "moe_gmm_w_in": ("expert", None, "mlp"),
    "moe_gmm_w_out": ("expert", "mlp", None),
    "moe_shared_w_in": ("embed", "mlp"), "moe_shared_w_out": ("mlp", "embed"),
    "wte": ("vocab", "embed"), "ln_f_scale": ("embed",),
    "lm_head": ("embed", "vocab"),
}
# the contraction axis of each matmul weight; -1: kept as given (norm
# scales, and the router and the gate, which are read in float32)
_LEAF_QUANT = {
    "wq": 0, "wk": 0, "wv": 0, "wo": 0, "mlp_in": 0, "mlp_out": 0,
    "moe_gmm_w_in": 1, "moe_gmm_w_out": 1,
    "moe_shared_w_in": 0, "moe_shared_w_out": 0, "wte": 1, "lm_head": 0,
}


def laguna_param_axes(cfg: LagunaConfig) -> dict:
    """Logical axis names per leaf; the experts get an axis of their own."""
    return leaf_tree(laguna_init, cfg, _LEAF_AXES.__getitem__)


def laguna_quant_axes(cfg: LagunaConfig) -> dict:
    """Per leaf, the contraction axis of a matmul weight (>= 0: the
    executor stores it in ``cfg.dtype``, experts included) or -1."""
    return leaf_tree(laguna_init, cfg, lambda name: _LEAF_QUANT.get(name, -1))


# ------------------------------------------------------------------ state


def laguna_init_state(cfg: LagunaConfig, slots: int) -> dict:
    """The counters the step programs keep (no per-sequence rows: ``slots``
    only says which rows are padding, slot 0)."""
    del slots
    return {
        "pairs": jnp.zeros((2, cfg.n_held, 2), jnp.uint32),
        "routed": jnp.zeros((2, 2), jnp.uint32),
        "reads": jnp.zeros((2,), jnp.uint32),
    }


def laguna_counters(state: dict) -> dict:
    """``state``'s counters as plain integers (a device->host read)."""
    pairs = count_value(state["pairs"])    # [2, held]: prefill, decode
    routed = count_value(state["routed"])  # [2]
    return {
        "moe_pairs_prefill": int(routed[0]),
        "moe_pairs_decode": int(routed[1]),
        "moe_pairs_held_prefill": int(pairs[0].sum()),
        "moe_pairs_held_decode": int(pairs[1].sum()),
        "moe_expert_reads_decode": int(count_value(state["reads"])),
        "moe_pairs_by_expert": [int(n) for n in pairs.sum(axis=0)],
    }


# ----------------------------------------------------------------- layers


def _head_gate(h, lp, cfg: LagunaConfig):
    """``gating``: one sigmoid gate a head from the layer's normed input,
    [B, S, Hq] float32 (assumed; the other reading is a gate an element,
    ``[D, Hq * hd]``: a correction is this function and the reference's)."""
    return jax.nn.sigmoid(jnp.einsum(
        "bsd,dh->bsh", h.astype(jnp.float32),
        lp["attn_gate_w"].astype(jnp.float32)))


def _rotary_tables(pos, cfg: LagunaConfig) -> dict:
    """``{kind: (cos, sin)}`` at the true positions ``pos`` [B, S]: the
    full layers' ``[B, S, rot // 2]`` over the rotated part of the head,
    YaRN's frequencies, times the attention factor; the sliding layers'
    over the whole head."""
    out = {}
    p = pos.astype(jnp.float32)[..., None]
    if "full_attention" in cfg.layer_types:
        rot = int(cfg.head_dim * cfg.partial_rotary_full)
        ang = p * jnp.asarray(yarn_inv_freq(
            rot, cfg.rope_theta_full, cfg.yarn_factor, cfg.yarn_original_max,
            cfg.yarn_beta_fast, cfg.yarn_beta_slow))
        out["full_attention"] = (
            jnp.cos(ang) * cfg.yarn_attention_factor,
            jnp.sin(ang) * cfg.yarn_attention_factor)
    if "sliding_attention" in cfg.layer_types:
        hd = cfg.head_dim
        ang = p / (cfg.rope_theta_sliding ** (
            jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
        out["sliding_attention"] = (jnp.cos(ang), jnp.sin(ang))
    return out


def _qkv(h, lp, kind: str, tables: dict, cfg: LagunaConfig):
    """Projections and the kind's rotary embedding. q [B, S, Hq, hd]; k, v
    [B, S, Hkv, hd] (the compact GQA heads, as the cache stores them)."""
    B, S, _ = h.shape
    Hq, Hkv, hd = cfg.n_head_of(kind), cfg.n_kv_head, cfg.head_dim
    q = (h @ lp["wq"].astype(cfg.dtype)).reshape(B, S, Hq, hd)
    k = (h @ lp["wk"].astype(cfg.dtype)).reshape(B, S, Hkv, hd)
    v = (h @ lp["wv"].astype(cfg.dtype)).reshape(B, S, Hkv, hd)
    cos, sin = tables[kind]
    return rope_partial(q, cos, sin), rope_partial(k, cos, sin), v


def _attn_out(x, h, attn, lp, kind: str, cfg: LagunaConfig):
    """The gated attention output [B, S, Hq * hd] through ``wo``, added."""
    B, S, _ = x.shape
    Hq, hd = cfg.n_head_of(kind), cfg.head_dim
    gated = attn.reshape(B, S, Hq, hd) * _head_gate(h, lp, cfg)[
        ..., None].astype(attn.dtype)
    return x + gated.reshape(B, S, Hq * hd) @ lp["wo"].astype(cfg.dtype)


def _ffn(x, lp, cfg: LagunaConfig, valid):
    """RMSNorm + (SwiGLU | shared expert + held routed experts) + residual
    on x [B, S, D]. ``valid`` [B, S] marks the real tokens. Returns (x',
    the held experts' pairs by expert [held] int32 or None for a dense
    layer)."""
    B, S, D = x.shape
    h = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    if "mlp_in" in lp:
        return x + swiglu(h, lp["mlp_in"], lp["mlp_out"], cfg.dtype), None
    flat = h.reshape(B * S, D)
    weights, experts = moe_route(
        flat, lp["moe_route_w"], None, cfg.top_k,
        norm_topk=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor)
    y, sizes = moe_dropless(
        flat, weights, experts, lp["moe_gmm_w_in"], lp["moe_gmm_w_out"],
        dtype=cfg.dtype, valid=valid.reshape(B * S), held=cfg.experts_held)
    with jax.named_scope("moe_shared"):
        shared = swiglu(h, lp["moe_shared_w_in"], lp["moe_shared_w_out"],
                        cfg.dtype)
    return x + shared + y.reshape(B, S, D), sizes


def laguna_forward(params: dict, tokens: jax.Array,
                   cfg: LagunaConfig) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32: the whole sequence at
    once, no cache (the program's own full forward)."""
    B, S = tokens.shape
    x = params["wte"].astype(cfg.dtype)[tokens]
    tables = _rotary_tables(
        jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S)), cfg)
    valid = jnp.ones((B, S), bool)
    for lp, kind in zip(params["layers"], cfg.layer_types):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(h, lp, kind, tables, cfg)
        attn = windowed_attention(
            q, k, v,
            cfg.sliding_window if kind == "sliding_attention" else None)
        x = _attn_out(x, h, attn, lp, kind, cfg)
        x, _ = _ffn(x, lp, cfg, valid)
    return head_untied(params, final_norm(params, x, cfg), cfg)


# ----------------------------------------------------------------------------
# Cached inference paths (serve/llm engine): what models/cached.py's one
# step needs of this family. The pool is [n_kv_layer, num_blocks,
# block_size, n_kv_head * head_dim] and the step's block tables are
# [n_group, B, NB] (a family whose layers are all of one kind has one
# group and still names it). Rows in slot 0 are padding: routed nowhere,
# counted nowhere.
# ----------------------------------------------------------------------------


def _cached_embed(params, tokens, step, cfg: LagunaConfig):
    x = step.take(params["wte"].astype(cfg.dtype), tokens)
    return x, _rotary_tables(step.pos, cfg)


def _cached_layer(x, lp, attend, step, work: dict, cfg: LagunaConfig):
    i = work["layer"]
    kind = cfg.layer_types[i]
    group, slot, window = cfg.kv_layout[i]
    with jax.named_scope("attn_proj"):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(h, lp, kind, step.aux, cfg)
        attn = attend(q, k, v, group=group, slot=slot, window=window)
        x = _attn_out(x, h, attn, lp, kind, cfg)
    with jax.named_scope("ffn"):
        x, sizes = _ffn(x, lp, cfg, work["routed"])
    work = {**work, "layer": i + 1}
    if sizes is not None:
        work["sizes"] = [*work["sizes"], sizes]
    return x, work


FAMILY = cached.CachedFamily(
    "laguna", LagunaConfig, "layers", _cached_embed, _cached_layer,
    final_norm, head_untied, open_state=open_experts,
    close_state=close_experts,
    no_verify="a rejected window may reach behind freed blocks (the engine "
              "refuses speculation over grouped tables)",
    gmm_form=step_gmm_form)
laguna_prefill, laguna_decode_step, _ = cached.steps(FAMILY)
