"""Plain reference of the LongCat-Flash family's forward pass (the language
model of LongCat-Flash-Omni): straightforward ``jax.numpy`` in float32 at
the highest matmul precision, a Python loop over the layers, no kernel, no
cache, no absorption, no sort, no grouped product and no batching: one
sequence at a time (``lax.map`` over the requests), and a sub-layer's
attention one block of queries at a time, so that the check's prompts fit
beside the engine on the chip.

Follows the public configuration (meituan-longcat/LongCat-Flash-Omni
``config.json``; the audio and vision encoders and the codec decoder have no
key in it and are no part of this pass). Token embedding; then 28 DOUBLE
layers (here ``cfg.n_layer``), with ``N`` = RMSNorm at ``rms_norm_eps``
under its own scale each time::

    a1 = x  + MLA_1(N(x))            h1 = N(a1)
    s  = MoE(h1)                     # the routed branch, from the first half
    b1 = a1 + SwiGLU_1(h1)           # dense, 12,288
    a2 = b1 + MLA_2(N(b1))           h2 = N(a2)
    out = a2 + SwiGLU_2(h2) + s      # the shortcut lands behind the second half

final RMSNorm; an untied head.

- ``MLA(u)``, multi-head latent attention in its EXPANDED form: ``c_q = N(u
  W_dq)`` (1,536); ``[q_nope,h | q_rope,h] = (c_q W_uq) * (6144 / 1536) **
  0.5`` for each of 64 heads (128 + 64); ``[c | k_r] = u W_dkv`` (512 + 64);
  ``c = N(c) * (6144 / 512) ** 0.5``; ``q_rope,h`` and the ONE ``k_r``
  rotated at the token's position (``_rotate``: pairs by halves, plain
  ``theta ** (-2i / R)``, theta 1e7, no scaling); keys ``k_h = [c W_uk,h |
  k_r]`` and values ``v_h = c W_uv,h`` BY HEAD, written out for the whole
  sequence; causal softmax at ``(128 + 64) ** -0.5`` written as a masked
  softmax; the heads' outputs through ``W_o`` (8,192 -> 6,144). The program
  caches only ``[c | k_r]`` (``c`` already rescaled) and computes the
  absorbed form: every comparison with this file is also absorbed against
  expanded.
- ``MoE(h)``: ``p = softmax(h W_r)`` over ALL 768 outputs; the 12 largest of
  ``p + bias``; weights ``6 p`` at the chosen, not renormalised; a loop
  (``lax.scan``) over the REAL experts this device holds
  (``cfg.experts_held``: the same share the program is given), each
  computing every token and entering the sum under a weight that is 0 where
  the token did not choose it; the zero-compute experts (ids >= 512) as a
  plain ``sum(w_zero) * h``, ALL of them here: a token's zero picks are
  computed where the token is, whatever that device holds. A pair routed
  to a real expert that is not held adds nothing, here as in the program.
- The head holds ``cfg.vocab_size`` rows, the slice of the vocabulary this
  device holds, as the program's does.

Departures from the published description, each a reading of what the
configuration does not say (benchmark/configs/longcat-flash-omni-ep32-4l.json
``assumed`` gives the other reading of each): (1) the experts and the dense
layers gate with silu; (2) the two rescalings multiply q (both parts) and
the normed latent (keys' nope part and values, not ``k_r``); (3) rotary
pairs by halves; (4) the selection bias is zeros; (5) the chosen scores are
not renormalised.

Reads the program's parameter tree (``models/longcat_flash.py
longcat_flash_init``) and of its config only numbers. Each weight is cast to
float32 where it is used.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ENGINE_MODEL = "longcat_flash"
Q_BLOCK = 128  # queries a block of attention: [H, 128, S] float32 scores
# what ``init_fn`` rounds once to the published checkpoint's dtype: every
# leaf with two or more axes; norm scales and the selection bias stay float32
PUBLISHED_DTYPE = jnp.bfloat16
# None: float32 at the highest precision. A control sets a narrower dtype
# (``jnp.float8_e4m3fn``): both operands of every matrix product are then
# cut to it first, which is how "the reference computed one precision
# lower" is read for the limit of ``reference_check``.
ROUND_TO = None
# Controls of ``reference_check``'s limit, each a WRONG model that the limit
# must refuse (False: the model as described): the routed branch added
# behind the FIRST half (no shortcut across the layer); the values not
# rescaled (``mla_scale_kv_lora`` on the keys' side alone); the zero-compute
# experts dropped (their picks add nothing).
SHORTCUT_BEHIND_FIRST_HALF = False
VALUES_NOT_RESCALED = False
ZERO_EXPERTS_DROPPED = False


def config_class():
    from ray_tpu.models.longcat_flash import LongCatFlashConfig

    return LongCatFlashConfig


def init_fn():
    """The program's own initialiser, its matrix leaves rounded ONCE to
    bfloat16 inside the same jitted call (benchmark/reference/lfm2_moe.py
    ``init_fn`` and its reasons: one copy of the weights is alive, the
    executor stores them as they are, the reference reads the same
    values)."""
    from ray_tpu.models.longcat_flash import longcat_flash_init

    def init(key, cfg):
        return jax.tree.map(
            lambda a: a.astype(PUBLISHED_DTYPE) if a.ndim >= 2 else a,
            longcat_flash_init(key, cfg))

    return init


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _cut(a):
    a = _f32(a)
    return a if ROUND_TO is None else a.astype(ROUND_TO).astype(jnp.float32)


def _mm(x, w):
    return _cut(x) @ _cut(w)


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(scale)


def _rotate(x, theta):
    """x [S, heads, R]: each pair (i, i + R / 2) of a head turned by the
    angle of the token's position, ``pos theta ** (-2i / R)`` (pairs by
    halves: assumed; the other reading pairs (2i, 2i + 1))."""
    S, _, R = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    ang = jnp.outer(jnp.arange(S, dtype=jnp.float32), inv_freq)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : R // 2], x[..., R // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(u, sp, cfg):
    """u [S, D], a sub-layer's normed input -> the heads' outputs through
    ``W_o`` [S, D]: the expanded form, keys and values by head, with the
    two rescalings."""
    S = u.shape[0]
    H, N, R = cfg.n_head, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    C, V, D = cfg.kv_lora_rank, cfg.v_head_dim, cfg.d_model
    q_scale = (D / cfg.q_lora_rank) ** 0.5 if cfg.mla_scale_q_lora else 1.0
    c_scale = (D / C) ** 0.5 if cfg.mla_scale_kv_lora else 1.0
    c_q = _rms_norm(_mm(u, sp["mla_w_dq"]), sp["mla_q_norm"], cfg.norm_eps)
    q = _mm(c_q, sp["mla_w_uq"]).reshape(S, H, N + R) * q_scale
    q = jnp.concatenate(
        [q[..., :N], _rotate(q[..., N:], cfg.rope_theta)], axis=-1)
    kv = _mm(u, sp["mla_w_dkv"])
    c_normed = _rms_norm(kv[:, :C], sp["mla_kv_norm"], cfg.norm_eps)
    c = c_normed * c_scale
    k_r = _rotate(kv[:, None, C:], cfg.rope_theta)            # [S, 1, R]
    k = jnp.concatenate(
        [_mm(c, sp["mla_w_uk"]).reshape(S, H, N),
         jnp.broadcast_to(k_r, (S, H, R))], axis=-1)          # [S, H, N + R]
    v = _mm(c_normed if VALUES_NOT_RESCALED else c,
            sp["mla_w_uv"]).reshape(S, H, V)
    qb = min(Q_BLOCK, S)
    blocks = -(-S // qb)
    q = jnp.pad(q, ((0, blocks * qb - S), (0, 0), (0, 0)))
    t = jnp.arange(S)
    scale = (N + R) ** -0.5

    def one_block(j):
        pos = j * qb + jnp.arange(qb)
        qs = jax.lax.dynamic_slice_in_dim(q, j * qb, qb)
        s = jnp.einsum("qhd,khd->hqk", _cut(qs), _cut(k)) * scale
        seen = t[None, :] <= pos[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _cut(p), _cut(v))

    a = jax.lax.map(one_block, jnp.arange(blocks)).reshape(-1, H, V)[:S]
    return _mm(a.reshape(S, H * V), sp["mla_w_o"])


def _swiglu(h, w_in, w_out):
    g, up = jnp.split(_mm(h, w_in), 2, axis=-1)
    return _mm(jax.nn.silu(g) * up, w_out)


def route(h, lp, cfg):
    """h [..., D] -> the [..., 768] weight of every output the router
    scores, real and zero-compute, for every token: 0 where the token did
    not choose it."""
    p = jax.nn.softmax(_f32(h) @ _f32(lp["moe_route_w"]), axis=-1)
    by = p + _f32(lp["moe_route_bias"])
    kth = jnp.sort(by, axis=-1)[..., -cfg.top_k][..., None]
    weights = jnp.where(by >= kth, p, 0.0)
    if cfg.norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights * cfg.routed_scaling_factor


def routed_part(h, lp, cfg):
    """What the routed branch adds for h [S, D] on this device: the real
    experts it holds, and every zero-compute pick of its tokens."""
    weights = route(h, lp, cfg)
    first, count = cfg.experts_held or (0, cfg.num_experts)
    mine = weights[..., first: first + count]

    def one_expert(out, e):
        w_in, w_out, weight = e  # this expert's matrices, cast where used
        return out + weight[..., None] * _swiglu(h, w_in, w_out), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (lp["moe_gmm_w_in"], lp["moe_gmm_w_out"], jnp.moveaxis(mine, -1, 0)))
    if ZERO_EXPERTS_DROPPED:
        return out
    w_zero = weights[..., cfg.num_experts:]
    return out + jnp.sum(w_zero, axis=-1, keepdims=True) * h


def layer(x, lp, cfg):
    """One double layer on x [S, D]."""
    first, second = lp["sub"]
    a1 = x + attention(
        _rms_norm(x, first["attn_norm"], cfg.norm_eps), first, cfg)
    h1 = _rms_norm(a1, first["ffn_norm"], cfg.norm_eps)
    s = routed_part(h1, lp, cfg)
    b1 = a1 + _swiglu(h1, first["dense_ffn_w_in"], first["dense_ffn_w_out"])
    if SHORTCUT_BEHIND_FIRST_HALF:
        b1, s = b1 + s, 0.0
    a2 = b1 + attention(
        _rms_norm(b1, second["attn_norm"], cfg.norm_eps), second, cfg)
    h2 = _rms_norm(a2, second["ffn_norm"], cfg.norm_eps)
    return a2 + _swiglu(
        h2, second["dense_ffn_w_in"], second["dense_ffn_w_out"]) + s


def _hidden_one(params: dict, tokens, cfg):
    """tokens [S] -> final hidden states [S, D], float32."""
    x = _f32(params["wte"])[tokens]
    for lp in params["layers"]:
        x = layer(x, lp, cfg)
    return _rms_norm(x, params["ln_f_scale"], cfg.norm_eps)


def hidden(params: dict, tokens, cfg):
    """tokens [B, S] -> final hidden states [B, S, D], float32, one
    sequence at a time."""
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(lambda t: _hidden_one(params, t, cfg), tokens)


def logits_at(params: dict, tokens, positions, cfg):
    """Float32 logits [B, P, V] at ``positions`` [B, P] of ``tokens``
    [B, S]: only the rows that are asked for meet the output head."""
    with jax.default_matmul_precision("highest"):
        def one(args):
            t, pos = args
            return _mm(_hidden_one(params, t, cfg)[pos], params["lm_head"])

        return jax.lax.map(one, (tokens, positions))


def logits(params: dict, tokens, cfg):
    """Float32 logits [B, S, V] at every position."""
    with jax.default_matmul_precision("highest"):
        return _mm(hidden(params, tokens, cfg), params["lm_head"])
