"""LFM2-MoE family: short-convolution and attention layers in one stack,
dense and sparse-expert feed-forward layers, for serving.

Follows the public ``lfm2_moe`` formulation (LiquidAI LFM2-24B-A2B's
``config.json`` and the family's published modelling code). A block is::

    h = x + Op(RMSNorm_op(x));   y = h + FFN(RMSNorm_ffn(h))

``Op`` is, by ``layer_types[i]``, either the gated short convolution
(``conv``; ops/short_conv.py) or grouped-query attention with an RMSNorm
over each head's q and k BEFORE the rotary embedding (``full_attention``).
``FFN`` is a dense SwiGLU in the first ``num_dense_layers`` layers and, in
the rest, a dropless expert layer: sigmoid router with a stored selection
bias, top-k, weights from the unbiased scores divided by their sum
(ops/moe.py ``moe_route`` / ``moe_dropless``). Final RMSNorm; the output
head is the embedding, tied.

Same conventions as models/llama.py (pure param pytrees, float32 masters,
activations in ``cfg.dtype``, the same prefill / decode-step contract) with
three differences this family forces:

- The layers are not one ``lax.scan``: they differ in kind, so the stack is
  a Python loop and ``params["layers"]`` a list with one dict per layer
  (no leaf is stacked over layers; one layer's experts are 1.2 GB in bf16).
- Two kinds of per-sequence state. The paged K/V pool spans the ATTENTION
  layers only (``cfg.n_kv_layer``). Each conv layer keeps the last
  ``conv_L_cache - 1`` rows of its gated input per sequence, in a SLOT of
  the ``state["conv"]`` array ``[n_conv_layer, slots, K-1, D]`` (slot 0 is
  the garbage sink, as block 0 is); the step functions take ``state`` and
  the rows' ``slots`` by keyword and return the next ``state``
  (models/cached.py).
- ``state`` also carries the expert layers' counters, added to inside the
  program so that no step hands the host anything but its tokens:
  ``pairs`` ``[2, E, 2]`` (routed token-expert pairs by expert, prefill and
  decode apart, padding rows excluded) and ``reads`` ``[2]`` (experts that
  got at least one token, summed over decode steps and expert layers),
  each a (low, high) pair of uint32 words (models/parts.py
  ``count_value``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models import cached
from ray_tpu.models.parts import (
    count_pairs,
    count_value,
    final_norm,
    head_tied,
    leaf_tree,
    routed_mask,
    swiglu,
)
from ray_tpu.ops.attention import mha_reference
from ray_tpu.ops.layers import rms_norm, rope
from ray_tpu.ops.moe import moe_dropless, moe_route, step_gmm_form
from ray_tpu.ops.short_conv import short_conv_decode, short_conv_prefill

LAYER_KINDS = ("conv", "full_attention")


@dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    max_seq_len: int = 128000
    d_model: int = 2048
    n_head: int = 32
    n_kv_head: int = 8
    head_dim: int = 64              # a key of its own: not d_model // n_head
    layer_types: tuple[str, ...] = ("conv", "conv", "full_attention", "conv")
    num_dense_layers: int = 2
    d_mlp: int = 11776              # dense SwiGLU width
    num_experts: int = 64
    top_k: int = 4
    d_expert: int = 1536            # each expert's SwiGLU width
    conv_L_cache: int = 3           # taps of the short convolution
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    dtype: Any = jnp.bfloat16
    # decode attention backend / serving quantization: see models/gpt.py
    # GPTConfig. The engine refuses ``quantization`` for this family.
    attention_backend: str = "auto"
    quantization: str | None = None

    def __post_init__(self):
        # a JSON list arrives here: the config is a jit-cache key
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = sorted(set(self.layer_types) - set(LAYER_KINDS))
        if bad:
            raise ValueError(
                f"layer_types holds {bad}; this family has {LAYER_KINDS}")
        if self.n_head % self.n_kv_head:
            raise ValueError("n_head must be a multiple of n_kv_head")
        if not 0 <= self.num_dense_layers <= len(self.layer_types):
            raise ValueError("num_dense_layers exceeds the layer count")
        if self.top_k > self.num_experts:
            raise ValueError("top_k exceeds num_experts")

    @staticmethod
    def tiny(vocab_size: int = 512) -> "Lfm2MoeConfig":
        return Lfm2MoeConfig(
            vocab_size=vocab_size, max_seq_len=128, d_model=64, n_head=2,
            n_kv_head=2, head_dim=16,
            layer_types=("conv", "full_attention", "conv", "conv"),
            num_dense_layers=1, d_mlp=128, num_experts=8, top_k=2,
            d_expert=32,
        )

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def n_kv_layer(self) -> int:
        """Layers that cache K/V: what the paged pool spans."""
        return self.layer_types.count("full_attention")

    @property
    def n_conv_layer(self) -> int:
        return self.layer_types.count("conv")

    @property
    def n_moe_layer(self) -> int:
        return self.n_layer - self.num_dense_layers


def lfm2_moe_init(key: jax.Array, cfg: Lfm2MoeConfig) -> dict:
    """Float32 masters, normal from ``key``, each matmul leaf with std
    ``fan_in ** -0.5`` (0.022 at the published hidden size) and the
    projections back into the residual stream a further ``(2 L) ** -0.5``
    smaller, so that at ANY width, the tiny test preset included, every
    layer moves the output and the router's scores spread. The embedding
    has std ``d_model ** -0.5`` (tied head: logits of about unit spread).
    ``moe_route_bias`` is drawn with std 0.02: small against the spread of
    an expert's scores over tokens (about 0.2), so that the experts stay
    about evenly loaded, as a trained checkpoint's bias is there to keep
    them, and yet of the size of the gap between the k-th and the
    (k+1)-th score, so that the biased selection differs from the unbiased
    one for about a quarter of the routed pairs (zeros would leave the
    mechanism untested; at std 0.1 some experts take twice their share
    and a 64-row decode step meets 46 of 64 experts, not 62)."""
    D, hd = cfg.d_model, cfg.head_dim
    Hq, Hkv, K = cfg.n_head, cfg.n_kv_head, cfg.conv_L_cache
    E, F, M = cfg.num_experts, cfg.d_expert, cfg.d_mlp
    back = (2 * cfg.n_layer) ** -0.5

    def norm(key, *shape, fan_in, gain=1.0):
        return jax.random.normal(key, shape, jnp.float32) * (
            gain * fan_in ** -0.5)

    keys = jax.random.split(key, cfg.n_layer + 1)
    layers = []
    for i, kind in enumerate(cfg.layer_types):
        k = iter(jax.random.split(keys[i], 8))
        lp: dict = {"op_norm": jnp.ones((D,), jnp.float32),
                    "ffn_norm": jnp.ones((D,), jnp.float32)}
        if kind == "conv":
            lp["short_conv_in"] = norm(next(k), D, 3 * D, fan_in=D)  # B C u
            lp["short_conv_w"] = norm(next(k), K, D, fan_in=K)
            lp["short_conv_out"] = norm(next(k), D, D, fan_in=D, gain=back)
        else:
            # 3 x larger: the per-head norm takes the scale out again, and
            # WITHOUT the norm the softmax would be 9 x sharper (a trained
            # q / k has no reason to be of unit size either)
            lp["wq"] = norm(next(k), D, Hq * hd, fan_in=D, gain=3.0)
            lp["wk"] = norm(next(k), D, Hkv * hd, fan_in=D, gain=3.0)
            lp["wv"] = norm(next(k), D, Hkv * hd, fan_in=D)
            lp["wo"] = norm(next(k), Hq * hd, D, fan_in=Hq * hd, gain=back)
            lp["q_norm"] = jnp.ones((hd,), jnp.float32)
            lp["k_norm"] = jnp.ones((hd,), jnp.float32)
        if i < cfg.num_dense_layers:
            lp["mlp_in"] = norm(next(k), D, 2 * M, fan_in=D)  # gate, up
            lp["mlp_out"] = norm(next(k), M, D, fan_in=M, gain=back)
        else:
            lp["moe_route_w"] = norm(next(k), D, E, fan_in=D)
            lp["moe_route_bias"] = norm(next(k), E, fan_in=1, gain=0.02)
            lp["moe_gmm_w_in"] = norm(next(k), E, D, 2 * F, fan_in=D)
            lp["moe_gmm_w_out"] = norm(next(k), E, F, D, fan_in=F,
                                       gain=back)
        layers.append(lp)
    return {
        "wte": norm(keys[-1], cfg.vocab_size, D, fan_in=D),
        "layers": layers,
        "ln_f_scale": jnp.ones((D,), jnp.float32),
    }


_LEAF_AXES = {
    "op_norm": ("embed",), "ffn_norm": ("embed",),
    "short_conv_in": ("embed", "mlp"), "short_conv_w": (None, "mlp"),
    "short_conv_out": ("mlp", "embed"),
    "wq": ("embed", "mlp"), "wk": ("embed", "mlp"), "wv": ("embed", "mlp"),
    "wo": ("mlp", "embed"), "q_norm": (None,), "k_norm": (None,),
    "mlp_in": ("embed", "mlp"), "mlp_out": ("mlp", "embed"),
    "moe_route_w": (None, None), "moe_route_bias": (None,),
    "moe_gmm_w_in": ("expert", None, "mlp"),
    "moe_gmm_w_out": ("expert", "mlp", None),
    "wte": ("vocab", "embed"), "ln_f_scale": ("embed",),
}
# the contraction axis of each matmul weight; -1: kept as given (norm
# scales, the conv filter, and the router, which is read in float32)
_LEAF_QUANT = {
    "short_conv_in": 0, "short_conv_out": 0, "wq": 0, "wk": 0, "wv": 0,
    "wo": 0, "mlp_in": 0, "mlp_out": 0,
    "moe_gmm_w_in": 1, "moe_gmm_w_out": 1, "wte": 1,
}


def lfm2_moe_param_axes(cfg: Lfm2MoeConfig) -> dict:
    """Logical axis names per leaf; the experts get an axis of their own."""
    return leaf_tree(lfm2_moe_init, cfg, _LEAF_AXES.__getitem__)


def lfm2_moe_quant_axes(cfg: Lfm2MoeConfig) -> dict:
    """Per leaf, the contraction axis of a matmul weight (>= 0: the
    executor stores it in ``cfg.dtype``, experts included) or -1."""
    return leaf_tree(lfm2_moe_init, cfg,
                     lambda name: _LEAF_QUANT.get(name, -1))


# ------------------------------------------------------------------ state


def lfm2_moe_init_state(cfg: Lfm2MoeConfig, slots: int) -> dict:
    """The per-sequence state beside the paged pool, zeroed: ``slots``
    counts slot 0, the garbage sink of padding rows."""
    return {
        "conv": jnp.zeros(
            (cfg.n_conv_layer, slots, cfg.conv_L_cache - 1, cfg.d_model),
            cfg.dtype),
        "pairs": jnp.zeros((2, cfg.num_experts, 2), jnp.uint32),
        "reads": jnp.zeros((2,), jnp.uint32),
    }


def lfm2_moe_counters(state: dict) -> dict:
    """``state``'s counters as plain integers (a device->host read)."""
    pairs = count_value(state["pairs"])  # [2, E]: prefill, decode
    return {
        "moe_pairs_prefill": int(pairs[0].sum()),
        "moe_pairs_decode": int(pairs[1].sum()),
        "moe_expert_reads_decode": int(count_value(state["reads"])),
        "moe_pairs_by_expert": [int(n) for n in pairs.sum(axis=0)],
    }


# ----------------------------------------------------------------- layers


def _ffn(x, lp, cfg: Lfm2MoeConfig, valid):
    """RMSNorm + (SwiGLU | experts) + residual on x [B, S, D]. ``valid``
    [B, S] marks the real tokens. Returns (x', the expert layer's routed
    pairs by expert [E] int32, or None for a dense layer)."""
    B, S, D = x.shape
    h = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    if "mlp_in" in lp:
        return x + swiglu(h, lp["mlp_in"], lp["mlp_out"], cfg.dtype), None
    flat = h.reshape(B * S, D)
    weights, experts = moe_route(
        flat, lp["moe_route_w"],
        lp["moe_route_bias"] if cfg.use_expert_bias else None, cfg.top_k,
        norm_topk=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor,
    )
    y, sizes = moe_dropless(
        flat, weights, experts, lp["moe_gmm_w_in"], lp["moe_gmm_w_out"],
        dtype=cfg.dtype, valid=valid.reshape(B * S),
    )
    return x + y.reshape(B, S, D), sizes


def _rope_at(pos, cfg: Lfm2MoeConfig):
    """(cos, sin) [B, S, head_dim // 2] at the true positions ``pos``
    [B, S]: the angles themselves, not a table of ``max_seq_len`` rows."""
    hd = cfg.head_dim
    inv_freq = 1.0 / (
        cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang), jnp.sin(ang)


def _qkv(h, lp, cos, sin, cfg: Lfm2MoeConfig):
    """Projections, the per-head RMSNorm on q and k, then the rotary
    embedding. q [B, S, Hq, hd]; k, v [B, S, Hkv, hd] (the compact GQA
    heads, as the cache stores them)."""
    B, S, _ = h.shape
    Hq, Hkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    q = (h @ lp["wq"].astype(cfg.dtype)).reshape(B, S, Hq, hd)
    k = (h @ lp["wk"].astype(cfg.dtype)).reshape(B, S, Hkv, hd)
    v = (h @ lp["wv"].astype(cfg.dtype)).reshape(B, S, Hkv, hd)
    q = rope(rms_norm(q, lp["q_norm"], cfg.norm_eps), cos, sin)
    k = rope(rms_norm(k, lp["k_norm"], cfg.norm_eps), cos, sin)
    return q, k, v


def lfm2_moe_forward(params: dict, tokens: jax.Array,
                     cfg: Lfm2MoeConfig) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32: the whole sequence at
    once, no cache and no state (the program's own full forward)."""
    B, S = tokens.shape
    x = params["wte"].astype(cfg.dtype)[tokens]
    cos, sin = _rope_at(
        jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S)), cfg)
    valid = jnp.ones((B, S), bool)
    lengths = jnp.full((B,), S, jnp.int32)
    for lp, kind in zip(params["layers"], cfg.layer_types):
        h = rms_norm(x, lp["op_norm"], cfg.norm_eps)
        if kind == "conv":
            b, c, u = jnp.split(h @ lp["short_conv_in"].astype(cfg.dtype),
                                3, axis=-1)
            y, _ = short_conv_prefill(b * u, c, lp["short_conv_w"], None,
                                      lengths)
            x = x + y @ lp["short_conv_out"].astype(cfg.dtype)
        else:
            q, k, v = _qkv(h, lp, cos, sin, cfg)
            attn = mha_reference(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), causal=True,
            ).transpose(0, 2, 1, 3).reshape(B, S, -1)
            x = x + attn @ lp["wo"].astype(cfg.dtype)
        x, _ = _ffn(x, lp, cfg, valid)
    return head_tied(params, final_norm(params, x, cfg), cfg)


# ----------------------------------------------------------------------------
# Cached inference paths (serve/llm engine): what models/cached.py's one
# step needs of this family. The pool is [n_kv_layer, num_blocks,
# block_size, n_kv_head, head_dim]; ``state`` and the rows' ``slots`` [B]
# reach the steps by keyword.
#
# A fresh prompt's convolutions begin from zeros whatever the slot held (a
# reused slot is cleared by its first use); a chunk's continue from the
# slot's rows (zeros where ``start[b]`` is 0); a decode step reads its
# slot's rows and writes the next. Rows in slot 0 are padding: routed
# nowhere, counted nowhere.
# ----------------------------------------------------------------------------


def _cached_embed(params, tokens, step, cfg: Lfm2MoeConfig):
    x = step.take(params["wte"].astype(cfg.dtype), tokens)
    return x, _rope_at(step.pos, cfg)


def _open_state(state: dict, step, cfg: Lfm2MoeConfig) -> dict:
    """The step's working state: the conv rows as the layers so far left
    them and the ordinal of the next conv layer, each expert layer's
    routed pairs, and the mask of the tokens that are routed."""
    return {"conv": state["conv"], "conv_done": 0, "sizes": [],
            "routed": routed_mask(step)}


def _mixer(x, lp, attend, step, work: dict, cfg: Lfm2MoeConfig):
    """The layer's first half on x [B, S, D]: norm, the gated short
    convolution over the slot's rows or attention over the pool, the
    residual. Returns (x', work')."""
    h = rms_norm(x, lp["op_norm"], cfg.norm_eps)
    if "short_conv_in" not in lp:
        q, k, v = _qkv(h, lp, *step.aux, cfg)
        return x + attend(q, k, v) @ lp["wo"].astype(cfg.dtype), work
    conv, ci, slots = work["conv"], work["conv_done"], step.slots
    decode = step.kind == "decode"  # one row a sequence: [B, D]
    b, c, u = jnp.split(
        (h[:, 0] if decode else h) @ lp["short_conv_in"].astype(cfg.dtype),
        3, axis=-1)
    if decode:
        y, after = short_conv_decode(b * u, c, lp["short_conv_w"],
                                     conv[ci, slots])
    else:
        before = None
        if step.kind != "fresh":
            before = jnp.where((step.start > 0)[:, None, None],
                               conv[ci, slots], 0)
        y, after = short_conv_prefill(b * u, c, lp["short_conv_w"],
                                      before, step.rows)
    work = {**work, "conv_done": ci + 1,
            "conv": conv.at[ci, slots].set(after.astype(conv.dtype))}
    y = y @ lp["short_conv_out"].astype(cfg.dtype)
    return x + (y[:, None] if decode else y), work


def _cached_layer(x, lp, attend, step, work: dict, cfg: Lfm2MoeConfig):
    # ``attn_proj``: the layer's mixer half, whichever mixer it is (the
    # convolution itself is ``short_conv``, the cache side ``attn_*``)
    with jax.named_scope("attn_proj"):
        x, work = _mixer(x, lp, attend, step, work, cfg)
    with jax.named_scope("ffn"):
        x, sizes = _ffn(x, lp, cfg, work["routed"])
    if sizes is not None:
        work = {**work, "sizes": [*work["sizes"], sizes]}
    return x, work


def _close_state(state: dict, work: dict, step, cfg: Lfm2MoeConfig) -> dict:
    """The next ``state``: the conv rows as the step left them, and the
    step's routed pairs added to the counters of its kind."""
    return {"conv": work["conv"], "reads": state["reads"], **count_pairs(
        state, work["sizes"], int(step.kind == "decode"))}


FAMILY = cached.CachedFamily(
    "lfm2_moe", Lfm2MoeConfig, "layers", _cached_embed, _cached_layer,
    final_norm, head_tied, open_state=_open_state, close_state=_close_state,
    no_verify="rejected drafts would need the conv state rolled back",
    gmm_form=step_gmm_form)
lfm2_moe_prefill, lfm2_moe_decode_step, _ = cached.steps(FAMILY)
