"""LongCat-Flash family (the language model of LongCat-Flash-Omni): a
layer is a DOUBLE layer, two latent-attention sub-layers and two dense
SwiGLUs with ONE routed branch across them, whose router is a softmax over
real experts AND zero-compute experts that return their input; for
serving. The audio and vision encoders and the codec decoder of the Omni
model have no key in the language model's configuration and are not served.

Follows the public configuration (meituan-longcat/LongCat-Flash-Omni
``config.json``). ``N`` = RMSNorm at ``rms_norm_eps`` with its own scale
each time; for layer ``l`` with input ``x``::

    a1 = x  + MLA_1(N(x))            h1 = N(a1)
    s  = MoE(h1)                     # the routed branch, from the first half
    b1 = a1 + SwiGLU_1(h1)           # dense, ``d_mlp``
    a2 = b1 + MLA_2(N(b1))           h2 = N(a2)
    out = a2 + SwiGLU_2(h2) + s      # the shortcut lands behind the second half

``MLA(u)`` (D = ``d_model``, Q = ``q_lora_rank``, C = ``kv_lora_rank``, N =
``qk_nope_head_dim``, R = ``qk_rope_head_dim``, V = ``v_head_dim``)::

    c_q = N(u W_dq)      [q_nope,h | q_rope,h] = (c_q W_uq) * (D / Q) ** 0.5
    [c | k_r] = u W_dkv  c = N(c) * (D / C) ** 0.5
    k_h = [c W_uk,h | k_r]   v_h = c W_uv,h   rotary on q_rope,h and the ONE k_r
    causal softmax at (N + R) ** -0.5, then W_o  (H V -> D)

The two rescalings (``mla_scale_q_lora``, ``mla_scale_kv_lora``) multiply
``q`` (both parts) and the NORMED ``c`` (so the keys' nope part and the
values, not ``k_r``). The pool caches ``[c | k_r]`` with ``c`` ALREADY
rescaled and the cached step computes the form its kind wants (models/
parts.py ``cached_heads``: a decode step the ABSORBED form, ``absorb``
before ``attend(..., latent=scale)``, ``unabsorb`` after; a prefill step
the EXPANDED one, ``attend(..., up=)``; there is one copy, pangu's too);
``longcat_flash_forward`` (no cache) computes the EXPANDED form.

``MoE(h)``: ``p = softmax(h W_r)`` over ALL ``num_experts +
num_zero_experts`` outputs (512 + 256), float32 at the highest precision;
the ``top_k`` (12) largest of ``p + bias``; weights
``routed_scaling_factor * p`` (6 p) at the chosen, NOT renormalised; an
expert ``e < num_experts`` gives ``(silu(h W_g,e) * (h W_u,e)) W_d,e``, an
expert ``e >= num_experts`` gives ``h`` (ops/moe.py ``moe_route(score=
"softmax")``, ``moe_dropless(zero_from=)``). A token therefore meets
between 0 and 12 real experts. A token's zero picks are computed where the
token is: a device that holds ``experts_held`` of the real experts computes
ALL of its tokens' zero picks and its share of their real ones. Final
RMSNorm, an untied head. What the configuration does not say and this file
reads by convention is listed in
benchmark/configs/longcat-flash-omni-ep32-4l.json ``assumed``.

Same conventions as models/pangu_ultra_moe.py (a LIST of per-layer trees,
float32 masters, activations in ``cfg.dtype``, ``experts_held``, the pool
in planes ``kv_planes``, ``vocab_size`` what this device holds) with what
this family forces:

- THE POOL SPANS ``2 * n_layer`` LATENT SUB-LAYERS (``n_kv_layer``): a
  layer calls ``attend`` twice and models/cached.py counts attending calls,
  so sub-layer ``j`` of layer ``l`` is pool layer ``2 l + j``.
- a layer's tree holds its two halves under ``sub`` (each: two norms, one
  MLA's leaves under pangu's names, one dense SwiGLU ``dense_ffn_w_in`` /
  ``_out``) and the routed branch beside them (``moe_route_w``,
  ``moe_route_bias``, ``moe_gmm_w_in`` / ``_out``).
- the counters in ``state`` are laguna's plus the picks that met a
  zero-compute expert and, a decode step, how many held real pairs it
  computed (a histogram: a window's largest and mean are read from it).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models import cached
from ray_tpu.models.laguna import laguna_counters, laguna_init_state
from ray_tpu.models.pangu_ultra_moe import QK_GAIN
from ray_tpu.models.parts import (
    cached_heads,
    close_experts,
    count_add,
    count_value,
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

# ``state["step_pairs"]``: decode steps by the held real pairs they computed
# (all expert layers of the step together), one bucket a count, the last
# bucket every count past it
STEP_PAIRS_BUCKETS = 1024


@dataclass(frozen=True)
class LongCatFlashConfig:
    vocab_size: int = 131072        # rows of the vocabulary HELD here
    max_seq_len: int = 131072
    d_model: int = 6144
    n_head: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512         # C: the latent row
    qk_nope_head_dim: int = 128     # N
    qk_rope_head_dim: int = 64      # R: the row's rotary rest
    v_head_dim: int = 128           # V
    n_layer: int = 28               # DOUBLE layers: 2 latent sub-layers each
    d_mlp: int = 12288              # each of a layer's two dense SwiGLUs
    num_experts: int = 512          # real experts the router scores
    num_zero_experts: int = 256     # ... and those that return their input
    top_k: int = 12
    d_expert: int = 2048
    experts_held: tuple[int, int] | None = None  # (first, count); None: all
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 6.0
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    rope_theta: float = 10000000.0
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
                    f"{self.num_experts} real experts")
        if self.top_k > self.num_experts + self.num_zero_experts:
            raise ValueError("top_k exceeds the router's outputs")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")

    @staticmethod
    def tiny(vocab_size: int = 512) -> "LongCatFlashConfig":
        return LongCatFlashConfig(
            vocab_size=vocab_size, max_seq_len=256, d_model=64, n_head=4,
            q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, n_layer=2, d_mlp=128,
            num_experts=8, num_zero_experts=4, top_k=3, d_expert=32,
            rope_theta=10000.0,
        )

    @property
    def n_kv_layer(self) -> int:
        """The pool's layers: the latent SUB-layers, two a layer."""
        return 2 * self.n_layer

    @property
    def n_held(self) -> int:
        """Real experts whose weights this device holds."""
        return (self.num_experts if self.experts_held is None
                else self.experts_held[1])

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @property
    def q_scale(self) -> float | None:
        return ((self.d_model / self.q_lora_rank) ** 0.5
                if self.mla_scale_q_lora else None)

    @property
    def c_scale(self) -> float | None:
        return ((self.d_model / self.kv_lora_rank) ** 0.5
                if self.mla_scale_kv_lora else None)

    @property
    def kv_planes(self) -> tuple[tuple[str, int, int], ...]:
        """What a latent sub-layer caches of a token (models/
        pangu_ultra_moe.py ``kv_planes``): the latent vector, rescaled, and
        the key's rotary rest."""
        return (
            ("latent", self.kv_lora_rank, plane_width(self.kv_lora_rank)),
            ("rope", self.qk_rope_head_dim,
             plane_width(self.qk_rope_head_dim)),
        )


def longcat_flash_init(key: jax.Array, cfg: LongCatFlashConfig) -> dict:
    """Float32 masters, normal from ``key``, each matmul leaf with std
    ``fan_in ** -0.5`` and the projections back into the residual stream a
    further ``(5 L) ** -0.5`` smaller (five branches a layer add to it).
    The two rescalings multiply what ``W_uq``, ``W_uk`` and ``W_uv`` read
    by 2 and 3.46 at the published widths; unit-scale leaves would then
    give scores ``s q . k`` of std 5.8, a softmax that is one-hot. So the
    leaves a rescaled value feeds are drawn that much SMALLER: ``W_uq`` at
    ``QK_GAIN / q_scale``, ``W_uk`` at ``QK_GAIN / c_scale``, ``W_uv`` at
    ``1 / c_scale``, the rotary columns of ``W_dkv`` at ``QK_GAIN``: behind
    the rescalings q and k are what models/pangu_ultra_moe.py's are (scores
    of std 1.55 ** 2 = 2.4, its reasons) and the values of unit variance.
    The rescalings themselves stay in the forward pass, program and
    reference alike. The selection bias is zeros (an even router). Norm
    scales are ones."""
    D, H = cfg.d_model, cfg.n_head
    Q, C = cfg.q_lora_rank, cfg.kv_lora_rank
    N, R, V = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    E, F, M = cfg.n_held, cfg.d_expert, cfg.d_mlp
    routed = cfg.num_experts + cfg.num_zero_experts
    back = (5 * cfg.n_layer) ** -0.5
    q_gain = QK_GAIN / (cfg.q_scale or 1.0)
    c_gain = 1.0 / (cfg.c_scale or 1.0)

    def norm(key, *shape, fan_in, gain=1.0):
        return jax.random.normal(key, shape, jnp.float32) * (
            gain * fan_in ** -0.5)

    ones = lambda n: jnp.ones((n,), jnp.float32)

    def half(key):
        k = iter(jax.random.split(key, 8))
        w_dkv = norm(next(k), D, C + R, fan_in=D)
        return {
            "attn_norm": ones(D), "ffn_norm": ones(D),
            "mla_q_norm": ones(Q), "mla_kv_norm": ones(C),
            "mla_w_dq": norm(next(k), D, Q, fan_in=D),
            "mla_w_uq": norm(next(k), Q, H * (N + R), fan_in=Q, gain=q_gain),
            "mla_w_dkv": w_dkv.at[:, C:].multiply(QK_GAIN),
            "mla_w_uk": norm(next(k), C, H * N, fan_in=C,
                             gain=QK_GAIN * c_gain),
            "mla_w_uv": norm(next(k), C, H * V, fan_in=C, gain=c_gain),
            "mla_w_o": norm(next(k), H * V, D, fan_in=H * V, gain=back),
            "dense_ffn_w_in": norm(next(k), D, 2 * M, fan_in=D),  # gate, up
            "dense_ffn_w_out": norm(next(k), M, D, fan_in=M, gain=back),
        }

    keys = jax.random.split(key, cfg.n_layer + 2)
    layers = []
    for i in range(cfg.n_layer):
        k = iter(jax.random.split(keys[i], 5))
        layers.append({
            "sub": [half(next(k)), half(next(k))],
            "moe_route_w": norm(next(k), D, routed, fan_in=D),
            "moe_route_bias": jnp.zeros((routed,), jnp.float32),
            "moe_gmm_w_in": norm(next(k), E, D, 2 * F, fan_in=D),
            "moe_gmm_w_out": norm(next(k), E, F, D, fan_in=F, gain=back),
        })
    return {
        "wte": norm(keys[-2], cfg.vocab_size, D, fan_in=D),
        "layers": layers,
        "ln_f_scale": jnp.ones((D,), jnp.float32),
        "lm_head": norm(keys[-1], D, cfg.vocab_size, fan_in=D),
    }


_LEAF_AXES = {
    "mla_w_dq": ("embed", None), "mla_w_uq": (None, "mlp"),
    "mla_w_dkv": ("embed", None), "mla_w_uk": (None, "mlp"),
    "mla_w_uv": (None, "mlp"), "mla_w_o": ("mlp", "embed"),
    "dense_ffn_w_in": ("embed", "mlp"), "dense_ffn_w_out": ("mlp", "embed"),
    "moe_route_w": (None, None), "moe_route_bias": (None,),
    "moe_gmm_w_in": ("expert", None, "mlp"),
    "moe_gmm_w_out": ("expert", "mlp", None),
    "wte": ("vocab", "embed"), "lm_head": ("embed", "vocab"),
}
# the contraction axis of each matmul weight; the others (norm scales, and
# the router and its bias, which are read in float32) are kept as given
_LEAF_QUANT = {
    "mla_w_dq": 0, "mla_w_uq": 0, "mla_w_dkv": 0, "mla_w_uk": 0,
    "mla_w_uv": 0, "mla_w_o": 0, "dense_ffn_w_in": 0, "dense_ffn_w_out": 0,
    "moe_gmm_w_in": 1, "moe_gmm_w_out": 1, "wte": 1, "lm_head": 0,
}


def longcat_flash_param_axes(cfg: LongCatFlashConfig) -> dict:
    """Logical axis names per leaf; the experts get an axis of their own,
    every norm scale is ``("embed",)``."""
    return leaf_tree(longcat_flash_init, cfg,
                     lambda n: _LEAF_AXES.get(n, ("embed",)))


def longcat_flash_quant_axes(cfg: LongCatFlashConfig) -> dict:
    """Per leaf, the contraction axis of a matmul weight (>= 0: the
    executor stores it in ``cfg.dtype``, experts included) or -1."""
    return leaf_tree(longcat_flash_init, cfg,
                     lambda n: _LEAF_QUANT.get(n, -1))


# ------------------------------------------------------------------ state


def longcat_flash_init_state(cfg: LongCatFlashConfig, slots: int) -> dict:
    """The counters the step programs keep (no per-sequence rows): laguna's
    over the HELD REAL experts, the zero picks by kind of step, and the
    decode steps by their held real pairs (plain uint32 words: steps)."""
    return {
        **laguna_init_state(cfg, slots),
        "zero": jnp.zeros((2, 2), jnp.uint32),
        "step_pairs": jnp.zeros((STEP_PAIRS_BUCKETS,), jnp.uint32),
    }


def longcat_flash_counters(state: dict) -> dict:
    """``state``'s counters as plain integers (a device->host read):
    ``moe_pairs_*`` count every pick (zero picks too), ``moe_pairs_held_*``
    the picks that met a held real expert."""
    import numpy as np

    zero = count_value(state["zero"])  # [2]: prefill, decode
    return {
        **laguna_counters(state),
        "moe_zero_picks_prefill": int(zero[0]),
        "moe_zero_picks_decode": int(zero[1]),
        "moe_step_pairs_decode": [
            int(n) for n in np.asarray(state["step_pairs"])],
    }


# ----------------------------------------------------------------- layers


def _rows(u, sp, cos, sin, cfg: LongCatFlashConfig):
    return queries_and_row(u, sp, cos, sin, cfg, q_scale=cfg.q_scale,
                           c_scale=cfg.c_scale)


def _dense_ffn(h, sp, cfg: LongCatFlashConfig):
    with jax.named_scope("dense_ffn"):
        return swiglu(h, sp["dense_ffn_w_in"], sp["dense_ffn_w_out"],
                      cfg.dtype)


def _routed(h, lp, cfg: LongCatFlashConfig, valid):
    """The routed branch of h [B, S, D], the first half's normed output:
    ``(s [B, S, D], the held real experts' pairs by expert [held] int32,
    the picks that met a zero-compute expert, a scalar)``. ``valid`` [B, S]
    marks the real tokens."""
    B, S, D = h.shape
    flat = h.reshape(B * S, D)
    real = valid.reshape(B * S)
    weights, experts = moe_route(
        flat, lp["moe_route_w"], lp["moe_route_bias"], cfg.top_k,
        norm_topk=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor,
        score="softmax")
    y, sizes = moe_dropless(
        flat, weights, experts, lp["moe_gmm_w_in"], lp["moe_gmm_w_out"],
        dtype=cfg.dtype, valid=real, held=cfg.experts_held,
        zero_from=cfg.num_experts)
    zero = jnp.sum((experts >= cfg.num_experts) & real[:, None])
    return y.reshape(B, S, D), sizes, zero


def _layer(x, lp, attention, cfg: LongCatFlashConfig, valid):
    """One double layer on x [B, S, D]. ``attention(u, sp)`` is the latent
    attention of a sub-layer over its normed input, through ``W_o``: the
    cached step's or the plain forward's. The routed branch reads the
    FIRST half's normed output and lands behind the second half (the
    shortcut). Returns (x', sizes, zero) as ``_routed``."""
    first, second = lp["sub"]
    with jax.named_scope("attn_proj"):
        x = x + attention(
            rms_norm(x, first["attn_norm"], cfg.norm_eps), first)
    with jax.named_scope("ffn"):
        h = rms_norm(x, first["ffn_norm"], cfg.norm_eps)
        s, sizes, zero = _routed(h, lp, cfg, valid)
        x = x + _dense_ffn(h, first, cfg)
    with jax.named_scope("attn_proj"):
        x = x + attention(
            rms_norm(x, second["attn_norm"], cfg.norm_eps), second)
    with jax.named_scope("ffn"):
        h = rms_norm(x, second["ffn_norm"], cfg.norm_eps)
        return x + _dense_ffn(h, second, cfg) + s, sizes, zero


def longcat_flash_forward(params: dict, tokens: jax.Array,
                          cfg: LongCatFlashConfig) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32: the whole sequence at
    once, no cache, attention in the expanded form."""
    B, S = tokens.shape
    x = params["wte"].astype(cfg.dtype)[tokens]
    cos, sin = rotary_at(
        jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S)), cfg)
    valid = jnp.ones((B, S), bool)

    def attention(u, sp):
        heads = expanded_attention(*_rows(u, sp, cos, sin, cfg), sp, cfg)
        return heads @ sp["mla_w_o"].astype(cfg.dtype)

    for lp in params["layers"]:
        x, _, _ = _layer(x, lp, attention, cfg, valid)
    return head_untied(params, final_norm(params, x, cfg), cfg)


# ----------------------------------------------------------------------------
# Cached inference paths (serve/llm engine): what models/cached.py's one
# step needs of this family. The pool is one plane of latent rows
# (``kv_planes``) over ``2 * n_layer`` sub-layers, one table for all. Rows
# in slot 0 are padding: routed nowhere, counted nowhere.
# ----------------------------------------------------------------------------


def _cached_embed(params, tokens, step, cfg: LongCatFlashConfig):
    x = step.take(params["wte"].astype(cfg.dtype), tokens)
    return x, rotary_at(step.pos, cfg)


def _open_state(state: dict, step, cfg: LongCatFlashConfig) -> dict:
    return {**open_experts(state, step, cfg), "zero": []}


def _cached_layer(x, lp, attend, step, work: dict, cfg: LongCatFlashConfig):
    def attention(u, sp):
        # the pool's layer is the attending call's ordinal: 2 l + j
        heads = cached_heads(
            *_rows(u, sp, *step.aux, cfg), sp, attend, step, cfg)
        return heads @ sp["mla_w_o"].astype(cfg.dtype)

    x, sizes, zero = _layer(x, lp, attention, cfg, work["routed"])
    return x, {**work, "layer": work["layer"] + 1,
               "sizes": [*work["sizes"], sizes],
               "zero": [*work["zero"], zero]}


def _close_state(state: dict, work: dict, step, cfg: LongCatFlashConfig):
    kind = int(step.kind == "decode")
    out = close_experts(state, work, step, cfg)
    out["zero"] = state["zero"].at[kind].set(
        count_add(state["zero"][kind], sum(work["zero"])))
    if kind:
        held = jnp.sum(sum(work["sizes"]))
        out["step_pairs"] = state["step_pairs"].at[
            jnp.minimum(held, STEP_PAIRS_BUCKETS - 1)].add(1)
    return out


FAMILY = cached.CachedFamily(
    "longcat_flash", LongCatFlashConfig, "layers", _cached_embed,
    _cached_layer, final_norm, head_untied, open_state=_open_state,
    close_state=_close_state,
    no_verify="nothing drafts; the latent family's refusals apply",
    state_rows=False, step_attrs=latent_step_attrs, gmm_form=step_gmm_form)
longcat_flash_prefill, longcat_flash_decode_step, _ = cached.steps(FAMILY)
