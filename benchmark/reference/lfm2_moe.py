"""Plain reference of the LFM2-MoE family's forward pass: straightforward
``jax.numpy`` in float32 at the highest matmul precision, a Python loop over
the layers, no kernel, no cache, no state, no sort and no grouped product.

Follows the public ``lfm2_moe`` formulation (LiquidAI LFM2-24B-A2B
``config.json``; the family's published modelling code). Token embedding;
per layer ``h = x + Op(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; final
RMSNorm; the head is the embedding, tied.

- ``Op`` = gated short convolution (``layer_types[i] == "conv"``):
  ``[B, C, u] = split3(x W_in)`` in that order, ``v = B * u``,
  ``z_t = sum_j w[j] * v[t - (K-1) + j]`` (depthwise, causal, zeros before
  position 0; written as K shifted multiplies), ``Op = (C * z) W_out``.
- ``Op`` = attention (``"full_attention"``): q / k / v projections without
  bias, RMSNorm over each head's ``head_dim`` on q and on k BEFORE the
  rotary embedding (rotate-half form), grouped-query causal softmax at
  scale ``head_dim ** -0.5`` written as a masked softmax, output projection.
- ``FFN``, the first ``num_dense_layers`` layers: SwiGLU (gate and up packed
  in one ``[D, 2M]`` matrix, gate first). The others: ``s = sigmoid(x W_r)``;
  the ``top_k`` experts by ``s + expert_bias``; weights the unbiased ``s`` of
  the chosen, divided by their sum, times ``routed_scaling_factor``; the
  layer is a loop (a ``lax.scan``) over ALL experts, each computing every
  token and entering the sum under a weight that is 0 where the token did
  not choose it.

Departures from the published code, noted: (1) it adds 1e-6 to the sum of
the chosen scores before dividing (as recalled — this sandbox has no
network to check it); so do the program (ops/moe.py ``ROUTE_NORM_EPS``)
and this file, each with a constant of its own. (2) The head is tied to the
embedding by the family's convention (``tie_embedding``; the key is not in
the catalog's row). (3) The checkpoint stores the conv filter as
``[D, 1, K]``; the program's tree holds it as ``[K, D]``, tap j of which
multiplies position ``t - (K-1) + j``.

Reads the program's parameter tree (``models/lfm2_moe.py lfm2_moe_init``)
and nothing else of the program. Each weight is cast to float32 where it is
used, one expert at a time: one layer's experts are 2.4 GB in float32 and
the engine's 8 GB of weights are alive beside the reference on the chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ENGINE_MODEL = "lfm2_moe"
ROUTE_NORM_EPS = 1e-6
# what ``init_fn`` rounds once to the published checkpoint's dtype: every
# leaf with two or more axes (the matmul weights, the router and the conv
# filter among them); norm scales and the router's bias stay float32
PUBLISHED_DTYPE = jnp.bfloat16


def config_class():
    from ray_tpu.models.lfm2_moe import Lfm2MoeConfig

    return Lfm2MoeConfig


def init_fn():
    """The program's own initialiser, its matrix leaves rounded ONCE to
    bfloat16 inside the same jitted call (the checkpoint is published in
    bfloat16). The benchmark then holds one copy of the weights: the
    executor stores a leaf that is already in the compute dtype as it is,
    and the reference reads the same rounded values. With float32 masters
    alive beside the stored tree the configuration would not fit the chip."""
    from ray_tpu.models.lfm2_moe import lfm2_moe_init

    def init(key, cfg):
        return jax.tree.map(
            lambda a: a.astype(PUBLISHED_DTYPE) if a.ndim >= 2 else a,
            lfm2_moe_init(key, cfg))

    return init


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(scale)


def _rope(x, theta):
    """x [B, S, H, hd]: rotate the two halves of each head by the angle of
    its position."""
    S, hd = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.outer(jnp.arange(S, dtype=jnp.float32), inv_freq)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _short_conv(h, lp):
    b, c, u = jnp.split(h @ _f32(lp["short_conv_in"]), 3, axis=-1)
    v = b * u
    w = _f32(lp["short_conv_w"])
    K, S = w.shape[0], v.shape[1]
    padded = jnp.pad(v, ((0, 0), (K - 1, 0), (0, 0)))
    z = sum(padded[:, j:j + S] * w[j] for j in range(K))
    return (c * z) @ _f32(lp["short_conv_out"])


def _attention(h, lp, cfg, causal):
    B, S, _ = h.shape
    Hq, Hkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    q = (h @ _f32(lp["wq"])).reshape(B, S, Hq, hd)
    k = (h @ _f32(lp["wk"])).reshape(B, S, Hkv, hd)
    v = (h @ _f32(lp["wv"])).reshape(B, S, Hkv, hd)
    q = _rope(_rms_norm(q, lp["q_norm"], cfg.norm_eps), cfg.rope_theta)
    k = _rope(_rms_norm(k, lp["k_norm"], cfg.norm_eps), cfg.rope_theta)
    k = jnp.repeat(k, Hq // Hkv, axis=2)
    v = jnp.repeat(v, Hq // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(hd))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return a.reshape(B, S, Hq * hd) @ _f32(lp["wo"])


def _swiglu(h, w_in, w_out):
    gate, up = jnp.split(h @ _f32(w_in), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ _f32(w_out)


def route(h, lp, cfg):
    """h [..., D] -> the [..., E] weight of every expert for every token:
    0 where the token did not choose the expert."""
    scores = jax.nn.sigmoid(h @ _f32(lp["moe_route_w"]))
    chosen_by = scores
    if cfg.use_expert_bias:
        chosen_by = scores + _f32(lp["moe_route_bias"])
    kth = jnp.sort(chosen_by, axis=-1)[..., -cfg.top_k][..., None]
    chosen = chosen_by >= kth
    weights = jnp.where(chosen, scores, 0.0)
    if cfg.norm_topk_prob:
        weights = weights / (
            jnp.sum(weights, axis=-1, keepdims=True) + ROUTE_NORM_EPS)
    return weights * cfg.routed_scaling_factor


def _experts(h, lp, cfg):
    weights = route(h, lp, cfg)

    def one_expert(out, e):
        w_in, w_out, weight = e  # this expert's matrices, cast where used
        return out + weight[..., None] * _swiglu(h, w_in, w_out), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (lp["moe_gmm_w_in"], lp["moe_gmm_w_out"],
         jnp.moveaxis(weights, -1, 0)))
    return out


def hidden(params: dict, tokens, cfg):
    """tokens [B, S] -> final hidden states [B, S, D], float32."""
    with jax.default_matmul_precision("highest"):
        S = tokens.shape[1]
        x = _f32(params["wte"])[tokens]
        causal = jnp.tril(jnp.ones((S, S), bool))
        for i, (lp, kind) in enumerate(zip(params["layers"],
                                           cfg.layer_types)):
            h = _rms_norm(x, lp["op_norm"], cfg.norm_eps)
            if kind == "conv":
                x = x + _short_conv(h, lp)
            else:
                x = x + _attention(h, lp, cfg, causal)
            h = _rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
            if i < cfg.num_dense_layers:
                x = x + _swiglu(h, lp["mlp_in"], lp["mlp_out"])
            else:
                x = x + _experts(h, lp, cfg)
        return _rms_norm(x, params["ln_f_scale"], cfg.norm_eps)


def logits_at(params: dict, tokens, positions, cfg):
    """Float32 logits [B, P, V] at ``positions`` [B, P] of ``tokens``
    [B, S]: only the rows that are asked for meet the output head."""
    with jax.default_matmul_precision("highest"):
        x = hidden(params, tokens, cfg)
        rows = jnp.take_along_axis(x, positions[..., None], axis=1)
        return rows @ _f32(params["wte"]).T


def logits(params: dict, tokens, cfg):
    """Float32 logits [B, S, V] at every position."""
    with jax.default_matmul_precision("highest"):
        return hidden(params, tokens, cfg) @ _f32(params["wte"]).T
