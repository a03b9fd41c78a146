"""Plain reference of the Ling-3.0 hybrid family's forward pass
(``bailing_hybrid``): straightforward ``jax.numpy`` in float32 at the
highest matmul precision, a Python loop over the layers, no kernel, no
cache, no chunks and no batching: one sequence at a time (``lax.map`` over
the requests), latent attention a block of query rows at a time.

Follows the public configuration (inclusionAI Ling-3.0-flash
``config.json``): Kimi Delta Attention (Kimi Linear, arXiv:2510.26692
section 3), DeepSeek-V2's latent attention, DeepSeek-V3's group-limited
sigmoid router. ``x0 = E[token]``; ``h = x + Mixer(RMSNorm(x))``, ``x' = h
+ FFN(RMSNorm(h))``; logits ``= W_head RMSNorm(x_L)``.

- ``kda``: ``[q~ | k~ | v~] = u W_qkv``; a causal depthwise convolution of
  4 taps over time on every channel (zeros before the sequence), SiLU; by
  head ``q = q' / |q'| * K^-0.5``, ``k = k' / |k'|`` (``qk_norm``); ``log a
  = kda_lower_bound * sigmoid(exp(A_log) (u W_f + dt_bias))`` a channel
  (``kda_gate``), ``beta = sigmoid(u w_beta)`` a head; per head THE
  RECURRENCE, token by token under a ``lax.scan``: ``S~ = Diag(a_t) S``,
  ``S = S~ + beta_t k_t (v_t - S~^T k_t)^T``, ``o_t = S^T q_t``; ``y =
  W_o(output_norm(o) * sigmoid(u W_g))``.
- ``latent``: ``q = u W_q`` by head ``[q_nope | q_rope]``; ``[c | k_r] = u
  W_dkv``, ``c`` normed; the rotary parts turned over INTERLEAVED pairs
  (``rotate``); the EXPANDED form, keys ``[c W_uk,h | k_r]`` and values ``c
  W_uv,h`` by head, a causal softmax at scale ``192^-0.5``; ``y = W_o
  concat_h(o_h * gate_head_wise(u)[h])``.
- ``ffn``: a SwiGLU, or the shared expert and ALL the held experts as
  dense products under the router's weights (``route``: sigmoid scores,
  the stored bias added for the CHOICE only, a group's score the sum of
  its two largest biased scores, the ``topk_group`` best groups stay, the
  ``top_k`` largest biased scores among theirs, weights ``scale * s_e /
  (sum of the chosen s + 1e-6)``), ``held`` as the program's.

The six readings the configuration leaves open
(benchmark/configs/ling-3.0-flash-ep8-7l.json ``assumed``), ONE function
each here: ``kda_gate`` (the other: ``-exp(A_log) softplus(f + dt_bias)``,
unbounded below), ``kda_projections`` (full rank; the other: through 128),
``gate_head_wise`` (the latent layers' gate a head; the other: the key
names KDA's output gate, one number a head in place of 128), ``kda_rope``
(none; the other: rotary over the first 64 of each head), ``qk_norm``
(KDA's L2 norm only; no norm a head on the latent keys), ``output_norm``
(a head at a time; the other: over the 4,096 joined).

Reads the program's parameter tree (``models/ling_hybrid.py
ling_hybrid_init``) and of its config only numbers. Each weight is cast to
float32 where it is used.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ENGINE_MODEL = "ling_hybrid"
ROUTE_NORM_EPS = 1e-6
Q_BLOCK = 128  # queries a block of attention: [H, 128, S] float32 scores
# what ``init_fn`` rounds once to the published checkpoint's dtype: every
# leaf with two or more axes; norm scales, biases and ``A_log`` stay float32
PUBLISHED_DTYPE = jnp.bfloat16
# None: float32 at the highest precision. A control sets a narrower dtype:
# both operands of every matrix product are then cut to it first
# (``reference_check``'s "one precision lower").
ROUND_TO = None
# ... and the KDA state alone rounded to it after every token (a control:
# "the state kept in bfloat16")
STATE_ROUND_TO = None


def config_class():
    from ray_tpu.models.ling_hybrid import LingHybridConfig

    return LingHybridConfig


def init_fn():
    """The program's own initialiser, its matrix leaves rounded ONCE to
    bfloat16 inside the same jitted call (benchmark/reference/lfm2_moe.py
    ``init_fn`` and its reasons)."""
    from ray_tpu.models.ling_hybrid import ling_hybrid_init

    def init(key, cfg):
        return jax.tree.map(
            lambda a: a.astype(PUBLISHED_DTYPE) if a.ndim >= 2 else a,
            ling_hybrid_init(key, cfg))

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


# ------------------------------------------------------------------- kda


def short_conv(x, w):
    """x [S, D] through the causal depthwise filter ``w`` [taps, D] (tap
    ``j`` meets position ``t - (taps - 1) + j``; zeros before the
    sequence), then SiLU (``linear_silu``)."""
    taps, S = w.shape[0], x.shape[0]
    ext = jnp.concatenate(
        [jnp.zeros((taps - 1, x.shape[1]), x.dtype), x], axis=0)
    return jax.nn.silu(sum(ext[j:j + S] * _f32(w)[j] for j in range(taps)))


def qk_norm(x, eps):
    """The L2 norm over each head's numbers, eps under the root."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def kda_projections(u, lp):
    """``(f, g)``: the decay's and the output gate's inputs, full rank."""
    return _mm(u, lp["kda_w_f"]), _mm(u, lp["kda_w_g"])


def kda_gate(f, lp, cfg):
    """``log a`` [S, H, K] in ``(kda_lower_bound, 0)``."""
    H, K = cfg.kda_n_head, cfg.kda_head_dim
    x = (f + _f32(lp["kda_dt_bias"])).reshape(-1, H, K)
    return cfg.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(_f32(lp["kda_a_log"]))[:, None] * x)


def kda_rope(q, k):
    """No positional encoding in a KDA layer."""
    return q, k


def output_norm(o, scale, eps):
    """RMSNorm over each head's 128, o [S, H, V], one ``[V]`` weight."""
    return _rms_norm(o, scale, eps)


def kda(u, lp, cfg):
    """u [S, D], the layer's normed input -> ``Mixer(u)`` [S, D]."""
    S = u.shape[0]
    H, K = cfg.kda_n_head, cfg.kda_head_dim
    qkv = short_conv(_mm(u, lp["kda_w_qkv"]), lp["kda_conv_w"])
    q, k, v = (a.reshape(S, H, K) for a in jnp.split(qkv, 3, axis=-1))
    q = qk_norm(q, cfg.norm_eps) * K ** -0.5
    k = qk_norm(k, cfg.norm_eps)
    q, k = kda_rope(q, k)
    f, g = kda_projections(u, lp)
    log_a = kda_gate(f, lp, cfg)
    beta = jax.nn.sigmoid(_f32(u) @ _f32(lp["kda_w_beta"]))       # [S, H]

    def token(state, xs):
        qt, kt, vt, lt, bt = xs                   # [H, K] x 4, [H]
        state = jnp.exp(lt)[..., None] * state
        err = vt - jnp.einsum("hk,hkv->hv", kt, state)
        state = state + (bt[:, None] * kt)[..., None] * err[:, None, :]
        if STATE_ROUND_TO is not None:
            # ``reduce_precision``, not a pair of casts: the compiler may
            # keep the excess precision of a float32 -> bfloat16 -> float32
            # round trip (on the chip it did: the control read 0.0000)
            kind = jnp.finfo(STATE_ROUND_TO)
            state = jax.lax.reduce_precision(state, kind.nexp, kind.nmant)
        return state, jnp.einsum("hk,hkv->hv", qt, state)

    _, o = jax.lax.scan(token, jnp.zeros((H, K, K), jnp.float32),
                        (q, k, v, log_a, beta))
    o = output_norm(o, lp["kda_out_norm"], cfg.norm_eps).reshape(S, H * K)
    return _mm(o * jax.nn.sigmoid(g), lp["kda_w_o"])


# ---------------------------------------------------------------- latent


def rotate(x, theta):
    """x [S, heads, R]: each INTERLEAVED pair (2i, 2i + 1) of a head
    turned by the angle of the token's position, ``pos theta ** (-2i /
    R)`` (``rope_interleave``)."""
    S, _, R = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    ang = jnp.outer(jnp.arange(S, dtype=jnp.float32), inv_freq)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def gate_head_wise(u, lp):
    """The latent layers' sigmoid gate, one number a head: [S, H]."""
    return jax.nn.sigmoid(_f32(u) @ _f32(lp["mla_w_g"]))


def latent(u, lp, cfg):
    """u [S, D] -> ``Mixer(u)`` [S, D]: the expanded form."""
    S = u.shape[0]
    H, N, R = cfg.n_head, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    C, V = cfg.kv_lora_rank, cfg.v_head_dim
    q = _mm(u, lp["mla_w_q"]).reshape(S, H, N + R)
    q = jnp.concatenate(
        [q[..., :N], rotate(q[..., N:], cfg.rope_theta)], axis=-1)
    kv = _mm(u, lp["mla_w_dkv"])
    c = _rms_norm(kv[:, :C], lp["mla_kv_norm"], cfg.norm_eps)
    k_r = rotate(kv[:, None, C:], cfg.rope_theta)              # [S, 1, R]
    k = jnp.concatenate(
        [_mm(c, lp["mla_w_uk"]).reshape(S, H, N),
         jnp.broadcast_to(k_r, (S, H, R))], axis=-1)
    v = _mm(c, lp["mla_w_uv"]).reshape(S, H, V)
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
    a = a * gate_head_wise(u, lp)[..., None]
    return _mm(a.reshape(S, H * V), lp["mla_w_o"])


# ------------------------------------------------------------------- ffn


def _swiglu(h, w_in, w_out):
    g, up = jnp.split(_mm(h, w_in), 2, axis=-1)
    return _mm(jax.nn.silu(g) * up, w_out)


def route(h, lp, cfg):
    """h [S, D] -> the [S, E] weight of every expert the router scores for
    every token: 0 where the token did not choose the expert."""
    E, G = cfg.num_experts, cfg.n_group
    scores = jax.nn.sigmoid(_f32(h) @ _f32(lp["moe_route_w"]))
    biased = scores + _f32(lp["moe_route_bias"])
    by_group = biased.reshape(-1, G, E // G)
    group_score = jnp.sum(jnp.sort(by_group, axis=-1)[..., -2:], axis=-1)
    kth_group = jnp.sort(group_score, axis=-1)[..., -cfg.topk_group][..., None]
    among = jnp.where((group_score >= kth_group)[..., None], by_group,
                      -jnp.inf).reshape(-1, E)
    kth = jnp.sort(among, axis=-1)[..., -cfg.top_k][..., None]
    weights = jnp.where(among >= kth, scores, 0.0)
    if cfg.norm_topk_prob:
        weights = weights / (
            jnp.sum(weights, axis=-1, keepdims=True) + ROUTE_NORM_EPS)
    return weights * cfg.routed_scaling_factor


def routed_part(h, lp, cfg, held=None):
    """What the experts ``held = (first, count)`` (None: the config's) add
    for h [S, D]: every held expert a dense product under its weight."""
    weights = route(h, lp, cfg)
    first, count = held or cfg.experts_held or (0, cfg.num_experts)
    mine = weights[..., first: first + count]

    def one_expert(out, e):
        w_in, w_out, weight = e
        return out + weight[..., None] * _swiglu(h, w_in, w_out), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (lp["moe_gmm_w_in"], lp["moe_gmm_w_out"], jnp.moveaxis(mine, -1, 0)))
    return out


def shared_part(h, lp):
    return _swiglu(h, lp["moe_shared_w_in"], lp["moe_shared_w_out"])


def ffn(z, lp, cfg):
    if "mlp_in" in lp:
        return _swiglu(z, lp["mlp_in"], lp["mlp_out"])
    return shared_part(z, lp) + routed_part(z, lp, cfg)


def layer(x, lp, cfg):
    """One layer on x [S, D]: plain pre-norm residuals."""
    u = _rms_norm(x, lp["mixer_norm"], cfg.norm_eps)
    mixer = kda if "kda_w_qkv" in lp else latent
    h = x + mixer(u, lp, cfg)
    return h + ffn(_rms_norm(h, lp["ffn_norm"], cfg.norm_eps), lp, cfg)


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
