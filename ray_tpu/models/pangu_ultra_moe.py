"""openPangu-Ultra-MoE family: multi-head LATENT attention (one 576-wide
row a token for all 128 heads), sandwich norms, a shared expert beside one
device's share of 256 sigmoid-routed ones, for serving.

Follows the public ``pangu_ultra_moe`` configuration
(FreedomIntelligence/openPangu-Ultra-MoE-718B ``config.json``). With ``u =
RMSNorm(x)``, ``s = (N + R) ** -0.5`` (N = ``qk_nope_head_dim``, R =
``qk_rope_head_dim``, C = ``kv_lora_rank``, V = ``v_head_dim``), one layer
at position ``t`` is::

    c_q = RMSNorm(u W_dq)                 [q_nope,h | q_rope,h] = c_q W_uq
    [c | k_r] = u W_dkv     c = RMSNorm(c)     q_rope,h, k_r rotated at t
    expanded:  k_h(m) = [c(m) W_uk,h | k_r(m)]      v_h(m) = c(m) W_uv,h
               o_h = sum_{m<=t} softmax_m(s q_h . k_h(m)) v_h(m)
    absorbed:  q~_h = q_nope,h W_uk,h^T   score s (q~_h . c(m) + q_rope,h . k_r(m))
               o~_h = sum_m p_m c(m)      o_h = o~_h W_uv,h
    y = x + RMSNorm(concat_h(o_h) W_o)    # sandwich: a norm AFTER ...
    z = RMSNorm(y)
    x' = y + RMSNorm(ffn(z))              # ... each sub-layer too

``[c | k_r]`` (C + R numbers) is ALL a layer caches of a token, one row
for every head. The cached step computes the form its KIND wants
(models/parts.py ``cached_heads``). A DECODE step the ABSORBED form:
attention of the heads over one shared row whose key is the row and whose
value is its first C numbers (models/cached.py ``attend(..., latent=s)``,
ops/paged_attention.py ``latent_attention``; ``absorb`` before it,
``unabsorb`` after). A PREFILL step the EXPANDED form (``attend(...,
latent=s, up=(W_uk, W_uv))``, ops/latent_prefill.py: a key's
up-projection is shared by the step's many queries, at 3.4 x fewer
operations a pair), as ``pangu_ultra_moe_forward`` (no cache) does; the
two are the same numbers (tests/test_pangu_ultra_moe.py). ``ffn``: SwiGLU ``d_mlp`` on the first
``num_dense_layers`` layers, then the shared expert plus ``moe_route``
without a selection bias (sigmoid scores, the ``top_k`` largest divided by
their sum, times ``routed_scaling_factor``) over ``moe_dropless``. Final
RMSNorm, an untied head. The multi-token-prediction module behind the last
layer drafts and is no part of the next-token forward pass: not held.
What the configuration does not say and this file reads by convention is
listed in benchmark/configs/openpangu-ultra-moe-ep32-5l.json ``assumed``;
the rotary form is ONE function (models/parts.py ``rotate``: by halves).

Same conventions as models/laguna.py (a LIST of per-layer trees, float32
masters, activations in ``cfg.dtype``, ``experts_held``, the counters in
``state``: models/parts.py ``open_experts`` / ``close_experts`` over
laguna's counters) with what this family forces:

- THE POOL IS ONE PLANE (``kv_planes``): ``cache_k`` holds a token's row
  ``[c | k_rope]`` for all heads, ``[n_layer, num_blocks, block_size,
  640]``, each part stored at whole lanes (ops/paged_attention.py
  ``latent_row``: the latent 512, the rotary 64 as 128, its rest zeros),
  so a page is one contiguous copy; ``cache_v`` is None. The cache
  manager, the executor's report and the refusals read ``kv_planes``;
  nothing here is ``(n_kv_head, head_dim)``.
- ``vocab_size`` is what THIS device holds of the vocabulary: embedding,
  head, logits and sampling are over it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models import cached
from ray_tpu.models.laguna import (
    laguna_counters as pangu_ultra_moe_counters,
    laguna_init_state as pangu_ultra_moe_init_state,
)
from ray_tpu.models.parts import (
    cached_heads,
    close_experts,
    expanded_attention,
    final_norm,
    head_untied,
    latent_step_attrs,
    leaf_tree,
    open_experts,
    queries_and_row,
    rotary_at,
    swiglu,
)
from ray_tpu.ops.layers import rms_norm
from ray_tpu.ops.moe import moe_dropless, moe_route, step_gmm_form
from ray_tpu.ops.paged_attention import plane_width

# ``pangu_ultra_moe_init``: W_uq, and on the key's side W_uk and the rotary
# columns of W_dkv, against fan_in ** -0.5
QK_GAIN = 1.55


@dataclass(frozen=True)
class PanguUltraMoEConfig:
    vocab_size: int = 153600        # rows of the vocabulary HELD here
    max_seq_len: int = 131072
    d_model: int = 7680
    n_head: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512         # C: the latent row
    qk_nope_head_dim: int = 128     # N
    qk_rope_head_dim: int = 64      # R: the row's rotary rest
    v_head_dim: int = 128           # V
    n_layer: int = 61
    num_dense_layers: int = 3       # ``first_k_dense_replace``
    d_mlp: int = 18432              # dense SwiGLU width
    num_experts: int = 256          # what the router scores
    top_k: int = 8
    d_expert: int = 2048            # each routed expert's SwiGLU width
    d_shared: int = 2048            # the shared expert's
    experts_held: tuple[int, int] | None = None  # (first, count); None: all
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    rope_theta: float = 25600000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # decode attention backend / serving quantization: see models/gpt.py
    # GPTConfig. The engine refuses ``quantization`` for this family.
    attention_backend: str = "auto"
    quantization: str | None = None

    def __post_init__(self):
        if self.experts_held is not None:
            object.__setattr__(
                self, "experts_held", tuple(int(n) for n in self.experts_held))
            first, count = self.experts_held
            if not (0 <= first and 0 < count
                    and first + count <= self.num_experts):
                raise ValueError(
                    f"experts_held {self.experts_held} is not a range of the "
                    f"{self.num_experts} experts")
        if not 0 <= self.num_dense_layers <= self.n_layer:
            raise ValueError("num_dense_layers exceeds the layer count")
        if self.top_k > self.num_experts:
            raise ValueError("top_k exceeds num_experts")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")

    @staticmethod
    def tiny(vocab_size: int = 512) -> "PanguUltraMoEConfig":
        return PanguUltraMoEConfig(
            vocab_size=vocab_size, max_seq_len=256, d_model=64, n_head=4,
            q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, n_layer=3, num_dense_layers=1,
            d_mlp=128, num_experts=8, top_k=2, d_expert=32, d_shared=32,
            rope_theta=10000.0,
        )

    @property
    def n_moe_layer(self) -> int:
        return self.n_layer - self.num_dense_layers

    @property
    def n_held(self) -> int:
        """Experts whose weights this device holds."""
        return (self.num_experts if self.experts_held is None
                else self.experts_held[1])

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    # ---- what a token's row in the pool is (serve/llm/kv_cache.py) ----

    @property
    def kv_planes(self) -> tuple[tuple[str, int, int], ...]:
        """THE description of what this family caches of a token in a
        layer: ``(name, width, stored width)`` a PART of its row, in the
        row's order. ONE row for all heads in the one pool: the latent
        vector (key and value) and the key's rotary rest beside it."""
        return (
            ("latent", self.kv_lora_rank, plane_width(self.kv_lora_rank)),
            ("rope", self.qk_rope_head_dim,
             plane_width(self.qk_rope_head_dim)),
        )


def pangu_ultra_moe_init(key: jax.Array, cfg: PanguUltraMoEConfig) -> dict:
    """Float32 masters, normal from ``key``, each matmul leaf with std
    ``fan_in ** -0.5`` and the projections back into the residual stream a
    further ``(2 L) ** -0.5`` smaller (models/lfm2_moe.py ``lfm2_moe_init``
    and its reasons). With both RMSNorms in front of them a unit-scale
    ``W_uq``, ``W_uk`` and rotary ``W_dkv`` give q and k components of unit
    variance and scores ``s q . k`` of std 1: a softmax nearly flat over
    the thousands of keys this family exists for, so that a layer's
    output would hardly depend on WHICH rows it read. ``QK_GAIN`` 1.55 on
    the query's side (``W_uq``) and on the key's (``W_uk``, and the rotary
    columns of ``W_dkv``; its latent columns are normed away) makes the
    scores' std 1.55 ** 2 = 2.4: of ``n`` keys about ``n exp(-2.4 ** 2)``
    carry a row, some ten of 3,000 (models/laguna.py ``laguna_init``: at
    std 4 and above the served bfloat16 program no longer agreed with the
    float32 reference). Norm scales are ones."""
    D, H = cfg.d_model, cfg.n_head
    Q, C = cfg.q_lora_rank, cfg.kv_lora_rank
    N, R, V = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    E, F, M, Fs = cfg.n_held, cfg.d_expert, cfg.d_mlp, cfg.d_shared
    back = (2 * cfg.n_layer) ** -0.5

    def norm(key, *shape, fan_in, gain=1.0):
        return jax.random.normal(key, shape, jnp.float32) * (
            gain * fan_in ** -0.5)

    keys = jax.random.split(key, cfg.n_layer + 2)
    layers = []
    for i in range(cfg.n_layer):
        k = iter(jax.random.split(keys[i], 12))
        ones = lambda n: jnp.ones((n,), jnp.float32)
        w_dkv = norm(next(k), D, C + R, fan_in=D)
        lp: dict = {
            "attn_norm": ones(D), "attn_post_norm": ones(D),
            "ffn_norm": ones(D), "ffn_post_norm": ones(D),
            "mla_q_norm": ones(Q), "mla_kv_norm": ones(C),
            "mla_w_dq": norm(next(k), D, Q, fan_in=D),
            "mla_w_uq": norm(next(k), Q, H * (N + R), fan_in=Q,
                             gain=QK_GAIN),
            "mla_w_dkv": w_dkv.at[:, C:].multiply(QK_GAIN),
            "mla_w_uk": norm(next(k), C, H * N, fan_in=C, gain=QK_GAIN),
            "mla_w_uv": norm(next(k), C, H * V, fan_in=C),
            "mla_w_o": norm(next(k), H * V, D, fan_in=H * V, gain=back),
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


_NORMS = ("attn_norm", "attn_post_norm", "ffn_norm", "ffn_post_norm",
          "mla_q_norm", "mla_kv_norm")
_LEAF_AXES = {
    **{name: ("embed",) for name in _NORMS},
    "mla_w_dq": ("embed", None), "mla_w_uq": (None, "mlp"),
    "mla_w_dkv": ("embed", None), "mla_w_uk": (None, "mlp"),
    "mla_w_uv": (None, "mlp"), "mla_w_o": ("mlp", "embed"),
    "mlp_in": ("embed", "mlp"), "mlp_out": ("mlp", "embed"),
    "moe_route_w": (None, None),
    "moe_gmm_w_in": ("expert", None, "mlp"),
    "moe_gmm_w_out": ("expert", "mlp", None),
    "moe_shared_w_in": ("embed", "mlp"), "moe_shared_w_out": ("mlp", "embed"),
    "wte": ("vocab", "embed"), "ln_f_scale": ("embed",),
    "lm_head": ("embed", "vocab"),
}
# the contraction axis of each matmul weight; -1: kept as given (norm
# scales, and the router, which is read in float32)
_LEAF_QUANT = {
    "mla_w_dq": 0, "mla_w_uq": 0, "mla_w_dkv": 0, "mla_w_uk": 0,
    "mla_w_uv": 0, "mla_w_o": 0, "mlp_in": 0, "mlp_out": 0,
    "moe_gmm_w_in": 1, "moe_gmm_w_out": 1,
    "moe_shared_w_in": 0, "moe_shared_w_out": 0, "wte": 1, "lm_head": 0,
}


def pangu_ultra_moe_param_axes(cfg: PanguUltraMoEConfig) -> dict:
    """Logical axis names per leaf; the experts get an axis of their own."""
    return leaf_tree(pangu_ultra_moe_init, cfg, _LEAF_AXES.__getitem__)


def pangu_ultra_moe_quant_axes(cfg: PanguUltraMoEConfig) -> dict:
    """Per leaf, the contraction axis of a matmul weight (>= 0: the
    executor stores it in ``cfg.dtype``, experts included) or -1."""
    return leaf_tree(pangu_ultra_moe_init, cfg,
                     lambda name: _LEAF_QUANT.get(name, -1))


# ----------------------------------------------------------------- layers


def _attn_out(x, heads, lp, cfg: PanguUltraMoEConfig):
    """The heads' outputs [B, S, H * V] through ``W_o``, normed, added."""
    a = heads @ lp["mla_w_o"].astype(cfg.dtype)
    return x + rms_norm(a, lp["attn_post_norm"], cfg.norm_eps)


def _ffn(x, lp, cfg: PanguUltraMoEConfig, valid):
    """``x + RMSNorm(ffn(RMSNorm(x)))`` on x [B, S, D], ``ffn`` a SwiGLU
    or the shared expert + the held routed experts. ``valid`` [B, S] marks
    the real tokens. Returns (x', the held experts' pairs by expert [held]
    int32 or None for a dense layer)."""
    B, S, D = x.shape
    z = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    if "mlp_in" in lp:
        out, sizes = swiglu(z, lp["mlp_in"], lp["mlp_out"], cfg.dtype), None
    else:
        flat = z.reshape(B * S, D)
        weights, experts = moe_route(
            flat, lp["moe_route_w"], None, cfg.top_k,
            norm_topk=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor)
        y, sizes = moe_dropless(
            flat, weights, experts, lp["moe_gmm_w_in"], lp["moe_gmm_w_out"],
            dtype=cfg.dtype, valid=valid.reshape(B * S),
            held=cfg.experts_held)
        with jax.named_scope("moe_shared"):
            shared = swiglu(z, lp["moe_shared_w_in"],
                            lp["moe_shared_w_out"], cfg.dtype)
        out = shared + y.reshape(B, S, D)
    return x + rms_norm(out, lp["ffn_post_norm"], cfg.norm_eps), sizes


def pangu_ultra_moe_forward(params: dict, tokens: jax.Array,
                            cfg: PanguUltraMoEConfig) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32: the whole sequence at
    once, no cache, attention in the expanded form."""
    B, S = tokens.shape
    x = params["wte"].astype(cfg.dtype)[tokens]
    cos, sin = rotary_at(
        jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S)), cfg)
    valid = jnp.ones((B, S), bool)
    for lp in params["layers"]:
        u = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        heads = expanded_attention(
            *queries_and_row(u, lp, cos, sin, cfg), lp, cfg)
        x, _ = _ffn(_attn_out(x, heads, lp, cfg), lp, cfg, valid)
    return head_untied(params, final_norm(params, x, cfg), cfg)


# ----------------------------------------------------------------------------
# Cached inference paths (serve/llm engine): what models/cached.py's one
# step needs of this family. The pool is one plane of latent rows
# (``kv_planes``), one table for all layers. Rows in slot 0 are padding:
# routed nowhere, counted nowhere (``state`` holds only the counters).
# ----------------------------------------------------------------------------


def _cached_embed(params, tokens, step, cfg: PanguUltraMoEConfig):
    x = step.take(params["wte"].astype(cfg.dtype), tokens)
    return x, rotary_at(step.pos, cfg)


def _cached_layer(x, lp, attend, step, work: dict,
                  cfg: PanguUltraMoEConfig):
    with jax.named_scope("attn_proj"):
        u = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        heads = cached_heads(
            *queries_and_row(u, lp, *step.aux, cfg), lp, attend, step, cfg)
        x = _attn_out(x, heads, lp, cfg)
    with jax.named_scope("ffn"):
        x, sizes = _ffn(x, lp, cfg, work["routed"])
    work = {**work, "layer": work["layer"] + 1}
    if sizes is not None:
        work["sizes"] = [*work["sizes"], sizes]
    return x, work


FAMILY = cached.CachedFamily(
    "pangu_ultra_moe", PanguUltraMoEConfig, "layers", _cached_embed,
    _cached_layer, final_norm, head_untied, open_state=open_experts,
    close_state=close_experts,
    no_verify="nothing drafts (the prediction module is not held)",
    state_rows=False, step_attrs=latent_step_attrs, gmm_form=step_gmm_form)
pangu_ultra_moe_prefill, pangu_ultra_moe_decode_step, _ = cached.steps(FAMILY)
