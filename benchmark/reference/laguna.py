"""Plain reference of the Laguna family's forward pass: straightforward
``jax.numpy`` in float32 at the highest matmul precision, a Python loop over
the layers, no kernel, no cache, no sort, no grouped product and no
batching: one sequence at a time (``lax.map`` over the requests), and a
layer's attention one block of queries at a time, so that sixteen prompts
of thousands of tokens fit beside the engine on the chip.

Follows the public ``laguna`` configuration (poolside Laguna-XS.2
``config.json``). Token embedding; per layer ``y = x + Attn(RMSNorm(x))``,
``out = y + FFN(RMSNorm(y))``; final RMSNorm; an untied head.

- ``Attn``: q / k / v projections without bias; ``H_l`` query heads, 48 on
  ``full_attention`` layers and 64 on ``sliding_attention`` ones, over 8
  key/value heads. Rotary embedding, rotate-half form: a full layer rotates
  the first ``partial_rotary_full`` of the head's dimensions with YaRN's
  inverse frequencies (``_yarn_inv_freq``: the formula of transformers
  4.57 ``modeling_rope_utils._compute_yarn_parameters``, written out here
  from the config's own numbers) and multiplies cos and sin by the
  attention factor; a sliding layer rotates the whole head with plain
  ``theta ** (-2i / hd)``. Causal softmax at scale ``head_dim ** -0.5``
  written as a masked softmax; a sliding layer also masks keys at or below
  ``pos - sliding_window``. ``gate`` multiplies each head's output by one
  sigmoid of the layer's normed input; output projection.
- ``FFN``, the first ``num_dense_layers`` layers: SwiGLU (gate and up
  packed in one ``[D, 2M]`` matrix, gate first). The others: the shared
  expert's SwiGLU for every token, plus the routed part: ``s = sigmoid(z
  W_r)`` over ALL ``num_experts``; the ``top_k`` by ``s``; weights ``s`` of
  the chosen divided by their sum, times ``routed_scaling_factor``; a loop
  (``lax.scan``) over the experts THIS DEVICE HOLDS (``cfg.experts_held``:
  the same share the program is given), each computing every token and
  entering the sum under a weight that is 0 where the token did not choose
  it. A pair routed to an expert that is not held adds nothing, here as in
  the program: the eight holders' parts add up to the whole layer.

Departures from the published description, each a reading of what the
configuration does not say (benchmark/configs/laguna-xs.2-ep8-8l.json
``assumed`` gives the other reading of each): (1) ``gating: true`` is read
as ONE gate a head (``gate``; a gate an element would be 0.63 B more
parameters than the row's 33.4 B); (2) the router has no selection bias
and adds 1e-6 to the sum of the chosen scores before dividing; (3) no norm
over q or k heads and none after a sub-layer.

Reads the program's parameter tree (``models/laguna.py laguna_init``) and
of its config only numbers. Each weight is cast to float32 where it is
used.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ENGINE_MODEL = "laguna"
ROUTE_NORM_EPS = 1e-6
Q_BLOCK = 256  # queries a block of attention: [H, 256, S] float32 scores
# what ``init_fn`` rounds once to the published checkpoint's dtype: every
# leaf with two or more axes; norm scales stay float32
PUBLISHED_DTYPE = jnp.bfloat16
# None: float32 at the highest precision. A control sets a narrower dtype
# (``jnp.float8_e4m3fn``): both operands of every matrix product are then
# cut to it first, which is how "the reference computed one precision
# lower" is read for the limit of ``reference_check``.
ROUND_TO = None


def config_class():
    from ray_tpu.models.laguna import LagunaConfig

    return LagunaConfig


def init_fn():
    """The program's own initialiser, its matrix leaves rounded ONCE to
    bfloat16 inside the same jitted call (benchmark/reference/lfm2_moe.py
    ``init_fn`` and its reasons: one copy of the weights is alive, the
    executor stores them as they are, the reference reads the same
    values)."""
    from ray_tpu.models.laguna import laguna_init

    def init(key, cfg):
        return jax.tree.map(
            lambda a: a.astype(PUBLISHED_DTYPE) if a.ndim >= 2 else a,
            laguna_init(key, cfg))

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


def _yarn_inv_freq(cfg):
    """Inverse frequencies of a full layer's rotated dimensions."""
    dim = int(cfg.head_dim * cfg.partial_rotary_full)
    base, factor = cfg.rope_theta_full, cfg.yarn_factor

    def correction_dim(rotations):
        return dim * math.log(
            cfg.yarn_original_max / (rotations * 2 * math.pi)) / (
                2 * math.log(base))

    low = max(math.floor(correction_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    plain = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    # ramp 0: the dimension turns often enough, keep it; 1: divide by factor
    return plain / factor * ramp + plain * (1 - ramp)


def _rotate(x, inv_freq, times):
    """x [S, H, hd]: rotate the two halves of the first ``2 *
    len(inv_freq)`` dimensions of each head by the angle of its position;
    cos and sin times ``times``; the other dimensions pass."""
    rot = 2 * inv_freq.shape[0]
    ang = jnp.outer(jnp.arange(x.shape[0], dtype=jnp.float32), inv_freq)
    cos, sin = jnp.cos(ang)[:, None] * times, jnp.sin(ang)[:, None] * times
    x1, x2 = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., rot:]], -1)


def gate(h, lp):
    """``gating``: one sigmoid gate a head, [S, H], from the normed input
    (assumed; a gate an element is the other reading)."""
    return jax.nn.sigmoid(_mm(h, lp["attn_gate_w"]))


def _attention(h, lp, kind, cfg):
    """h [S, D] -> the layer's attention output [S, D]."""
    S = h.shape[0]
    sliding = kind == "sliding_attention"
    Hq = cfg.n_head_sliding if sliding else cfg.n_head_full
    Hkv, hd = cfg.n_kv_head, cfg.head_dim
    q = _mm(h, lp["wq"]).reshape(S, Hq, hd)
    k = _mm(h, lp["wk"]).reshape(S, Hkv, hd)
    v = _mm(h, lp["wv"]).reshape(S, Hkv, hd)
    if sliding:
        inv_freq = 1.0 / cfg.rope_theta_sliding ** (
            jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        times = 1.0
    else:
        inv_freq, times = _yarn_inv_freq(cfg), cfg.yarn_attention_factor
    q, k = _rotate(q, inv_freq, times), _rotate(k, inv_freq, times)
    k = jnp.repeat(k, Hq // Hkv, axis=1)
    v = jnp.repeat(v, Hq // Hkv, axis=1)
    qb = min(Q_BLOCK, S)
    blocks = -(-S // qb)
    q = jnp.pad(q, ((0, blocks * qb - S), (0, 0), (0, 0)))
    t = jnp.arange(S)

    def one_block(j):
        pos = j * qb + jnp.arange(qb)
        qs = jax.lax.dynamic_slice_in_dim(q, j * qb, qb)
        s = jnp.einsum("qhd,khd->hqk", _cut(qs), _cut(k)) / math.sqrt(hd)
        seen = t[None, :] <= pos[:, None]
        if sliding:
            seen = seen & (t[None, :] > pos[:, None] - cfg.sliding_window)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _cut(p), _cut(v))

    a = jax.lax.map(one_block, jnp.arange(blocks)).reshape(-1, Hq, hd)[:S]
    a = a * gate(h, lp)[..., None]
    return _mm(a.reshape(S, Hq * hd), lp["wo"])


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


def _hidden_one(params: dict, tokens, cfg):
    """tokens [S] -> final hidden states [S, D], float32."""
    x = _f32(params["wte"])[tokens]
    for i, (lp, kind) in enumerate(zip(params["layers"], cfg.layer_types)):
        h = _rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        x = x + _attention(h, lp, kind, cfg)
        h = _rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
        if i < cfg.num_dense_layers:
            x = x + _swiglu(h, lp["mlp_in"], lp["mlp_out"])
        else:
            x = x + shared_part(h, lp) + routed_part(h, lp, cfg)
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
