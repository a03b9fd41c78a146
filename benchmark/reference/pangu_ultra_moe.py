"""Plain reference of the openPangu-Ultra-MoE family's forward pass:
straightforward ``jax.numpy`` in float32 at the highest matmul precision, a
Python loop over the layers, no kernel, no cache, no absorption, no sort, no
grouped product and no batching: one sequence at a time (``lax.map`` over
the requests), and a layer's attention one block of queries at a time, so
that sixteen prompts of thousands of tokens fit beside the engine on the
chip.

Follows the public ``pangu_ultra_moe`` configuration
(FreedomIntelligence/openPangu-Ultra-MoE-718B ``config.json``). Token
embedding; per layer, with SANDWICH norms (``sandwich_norm: true``: four
RMSNorm scales a layer), ``y = x + RMSNorm(Attn(RMSNorm(x)))``, ``x' = y +
RMSNorm(FFN(RMSNorm(y)))``; final RMSNorm; an untied head.

- ``Attn``, multi-head latent attention in its EXPANDED form, with ``u`` the
  normed input: ``c_q = RMSNorm(u W_dq)`` (``q_lora_rank``); ``[q_nope,h |
  q_rope,h] = c_q W_uq`` for each of ``n_head`` heads; ``[c | k_r] = u
  W_dkv`` (``kv_lora_rank`` + ``qk_rope_head_dim``), ``c = RMSNorm(c)``;
  ``q_rope,h`` and the ONE ``k_r`` rotated at the token's position
  (``_rotate``: pairs by halves, plain ``theta ** (-2i / R)``, no scaling);
  keys ``k_h = [c W_uk,h | k_r]`` and values ``v_h = c W_uv,h`` BY HEAD,
  written out for the whole sequence; causal softmax at scale
  ``(qk_nope_head_dim + qk_rope_head_dim) ** -0.5`` written as a masked
  softmax; the heads' outputs through ``W_o``. The program caches only ``[c
  | k_r]`` and computes the absorbed form (``q_nope,h W_uk,h^T`` against
  ``c``, ``W_uv,h`` after the sum): every comparison with this file is also
  absorbed against expanded.
- ``FFN``, the first ``num_dense_layers`` layers: SwiGLU (gate and up packed
  in one ``[D, 2M]`` matrix, gate first). The others: the shared expert's
  SwiGLU for every token, plus the routed part: ``g = sigmoid(z W_r)`` over
  ALL ``num_experts``; the ``top_k`` by ``g``; weights ``g`` of the chosen
  divided by their sum, times ``routed_scaling_factor``; a loop
  (``lax.scan``) over the experts THIS DEVICE HOLDS (``cfg.experts_held``:
  the same share the program is given), each computing every token and
  entering the sum under a weight that is 0 where the token did not choose
  it. A pair routed to an expert that is not held adds nothing, here as in
  the program: the 32 holders' parts add up to the whole layer.
- The head holds ``cfg.vocab_size`` rows, the slice of the vocabulary this
  device holds, as the program's does.

Departures from the published description, each a reading of what the
configuration does not say (benchmark/configs/openpangu-ultra-moe-ep32-5l.json
``assumed`` gives the other reading of each): (1) the router scores with a
sigmoid, has no selection bias and adds 1e-6 to the sum of the chosen
scores before dividing; (2) the four norms of a layer sit before and after
each sub-layer, the second INSIDE the residual branch; (3) rotary pairs by
halves; (4) no extra softmax scale. The multi-token-prediction module
(``num_nextn_predict_layers``) drafts and is no part of this pass.

Reads the program's parameter tree (``models/pangu_ultra_moe.py
pangu_ultra_moe_init``) and of its config only numbers. Each weight is cast
to float32 where it is used.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ENGINE_MODEL = "pangu_ultra_moe"
ROUTE_NORM_EPS = 1e-6
Q_BLOCK = 128  # queries a block of attention: [H, 128, S] float32 scores
# what ``init_fn`` rounds once to the published checkpoint's dtype: every
# leaf with two or more axes; norm scales stay float32
PUBLISHED_DTYPE = jnp.bfloat16
# None: float32 at the highest precision. A control sets a narrower dtype
# (``jnp.float8_e4m3fn``): both operands of every matrix product are then
# cut to it first, which is how "the reference computed one precision
# lower" is read for the limit of ``reference_check``.
ROUND_TO = None
# Controls of ``reference_check``'s limit, each a WRONG model that the limit
# must refuse (False: the model as described): the value taken as the whole
# row ``[c | k_r]`` through a ``W_uv`` widened with the key's rotary
# columns; and no norm after a sub-layer.
VALUE_IS_WHOLE_ROW = False
NO_POST_NORMS = False


def config_class():
    from ray_tpu.models.pangu_ultra_moe import PanguUltraMoEConfig

    return PanguUltraMoEConfig


def init_fn():
    """The program's own initialiser, its matrix leaves rounded ONCE to
    bfloat16 inside the same jitted call (benchmark/reference/lfm2_moe.py
    ``init_fn`` and its reasons: one copy of the weights is alive, the
    executor stores them as they are, the reference reads the same
    values)."""
    from ray_tpu.models.pangu_ultra_moe import pangu_ultra_moe_init

    def init(key, cfg):
        return jax.tree.map(
            lambda a: a.astype(PUBLISHED_DTYPE) if a.ndim >= 2 else a,
            pangu_ultra_moe_init(key, cfg))

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


def attention(u, lp, cfg):
    """u [S, D], the layer's normed input -> the heads' outputs through
    ``W_o`` [S, D]: the expanded form, keys and values by head."""
    S = u.shape[0]
    H, N, R = cfg.n_head, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    C, V = cfg.kv_lora_rank, cfg.v_head_dim
    c_q = _rms_norm(_mm(u, lp["mla_w_dq"]), lp["mla_q_norm"], cfg.norm_eps)
    q = _mm(c_q, lp["mla_w_uq"]).reshape(S, H, N + R)
    q = jnp.concatenate(
        [q[..., :N], _rotate(q[..., N:], cfg.rope_theta)], axis=-1)
    kv = _mm(u, lp["mla_w_dkv"])
    c = _rms_norm(kv[:, :C], lp["mla_kv_norm"], cfg.norm_eps)
    k_r = _rotate(kv[:, None, C:], cfg.rope_theta)            # [S, 1, R]
    k = jnp.concatenate(
        [_mm(c, lp["mla_w_uk"]).reshape(S, H, N),
         jnp.broadcast_to(k_r, (S, H, R))], axis=-1)          # [S, H, N + R]
    v = _mm(c, lp["mla_w_uv"]).reshape(S, H, V)
    if VALUE_IS_WHOLE_ROW:
        # the control: what a kernel that takes the 576-wide row for the
        # value computes, the rotary part entering through W_uv's first R
        # latent rows
        v = v + _mm(k_r[:, 0], _f32(lp["mla_w_uv"])[:R]).reshape(S, H, V)
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
    return _mm(a.reshape(S, H * V), lp["mla_w_o"])


def _swiglu(h, w_in, w_out):
    g, up = jnp.split(_mm(h, w_in), 2, axis=-1)
    return _mm(jax.nn.silu(g) * up, w_out)


def route(h, lp, cfg):
    """h [..., D] -> the [..., E] weight of every expert the router scores
    for every token: 0 where the token did not choose the expert."""
    scores = jax.nn.sigmoid(_f32(h) @ _f32(lp["moe_route_w"]))
    kth = jnp.sort(scores, axis=-1)[..., -cfg.top_k][..., None]
    weights = jnp.where(scores >= kth, scores, 0.0)
    if cfg.norm_topk_prob:
        weights = weights / (
            jnp.sum(weights, axis=-1, keepdims=True) + ROUTE_NORM_EPS)
    return weights * cfg.routed_scaling_factor


def routed_part(h, lp, cfg):
    """What the experts this device holds add for h [S, D]."""
    weights = route(h, lp, cfg)
    first, count = cfg.experts_held or (0, cfg.num_experts)
    mine = weights[..., first: first + count]

    def one_expert(out, e):
        w_in, w_out, weight = e  # this expert's matrices, cast where used
        return out + weight[..., None] * _swiglu(h, w_in, w_out), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (lp["moe_gmm_w_in"], lp["moe_gmm_w_out"], jnp.moveaxis(mine, -1, 0)))
    return out


def shared_part(h, lp):
    return _swiglu(h, lp["moe_shared_w_in"], lp["moe_shared_w_out"])


def ffn(z, lp, cfg):
    """z [S, D], the sub-layer's normed input -> what it adds before its
    post-norm: a SwiGLU, or the shared expert and the held routed ones."""
    if "mlp_in" in lp:
        return _swiglu(z, lp["mlp_in"], lp["mlp_out"])
    return shared_part(z, lp) + routed_part(z, lp, cfg)


def layer(x, lp, cfg):
    """One layer on x [S, D] with its sandwich norms."""
    def post(a, scale):
        return a if NO_POST_NORMS else _rms_norm(a, scale, cfg.norm_eps)

    a = attention(_rms_norm(x, lp["attn_norm"], cfg.norm_eps), lp, cfg)
    y = x + post(a, lp["attn_post_norm"])
    f = ffn(_rms_norm(y, lp["ffn_norm"], cfg.norm_eps), lp, cfg)
    return y + post(f, lp["ffn_post_norm"])


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
