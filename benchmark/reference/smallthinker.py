"""Plain reference of the SmallThinker family's forward pass: straightforward
``jax.numpy`` in float32 at the highest matmul precision, a Python loop over
the layers, no kernel, no cache, no sort, no grouped product and no
batching: one sequence at a time (``lax.map`` over the requests), and a
layer's attention one block of queries at a time, so that sixteen prompts
of thousands of tokens fit beside the engine on the chip.

Follows the public ``smallthinker`` configuration (PowerInfer
SmallThinker-21BA3B-Instruct ``config.json``). Token embedding; per layer,
with ``h = RMSNorm(x)``: the ROUTE from the layer's input, ``y = x +
Attn(h)``, ``out = y + Experts(RMSNorm(y); route)``; final RMSNorm; an
untied head.

- ``route``: ``logits = router_input(x, h) W_r`` over all ``num_experts``;
  the ``top_k`` largest; weights a softmax over those alone (with
  ``norm_topk_prob``; without it the softmax over all, at the chosen). It
  is computed BEFORE attention, from the layer's input, and used behind it.
- ``Attn``: q / k / v projections without bias, 28 query heads over 4
  key/value heads of 128, no norm over a head. ``positional``: a
  ``sliding_attention`` layer (``rope_layout`` 1) rotates the whole head,
  rotate-half form, plain ``theta ** (-2i / hd)``; a ``full_attention``
  layer (0) applies NOTHING. Causal softmax at scale ``head_dim ** -0.5``
  written as a masked softmax; ``sees``: a sliding layer also masks keys at
  or below ``pos - sliding_window``. Output projection.
- ``Experts``: a loop (``lax.scan``) over all the experts, each computing
  every token as ``(relu(g W_gate) * (g W_up)) W_down`` (gate and up packed
  in one ``[D, 2F]`` matrix, gate first) and entering the sum under a
  weight that is 0 where the token did not choose it.

Departures from the published description, each a reading of what the
configuration does not say (benchmark/configs/smallthinker-21b-a3b-8l.json
``assumed`` gives the other reading of each), each ONE function here:
(1) the router reads the NORMED input (``router_input``); (2) the window's
edge: a query sees ``sliding_window`` keys, itself included (``sees``);
(3) "primary + secondary experts" has no key: the 64 primary experts are
all there is.

Reads the program's parameter tree (``models/smallthinker.py
smallthinker_init``) and of its config only numbers. Each weight is cast to
float32 where it is used.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ENGINE_MODEL = "smallthinker"
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
    from ray_tpu.models.smallthinker import SmallThinkerConfig

    return SmallThinkerConfig


def init_fn():
    """The program's own initialiser, its matrix leaves rounded ONCE to
    bfloat16 inside the same jitted call (benchmark/reference/lfm2_moe.py
    ``init_fn`` and its reasons: one copy of the weights is alive, the
    executor stores them as they are, the reference reads the same
    values)."""
    from ray_tpu.models.smallthinker import smallthinker_init

    def init(key, cfg):
        return jax.tree.map(
            lambda a: a.astype(PUBLISHED_DTYPE) if a.ndim >= 2 else a,
            smallthinker_init(key, cfg))

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


def router_input(x, h):
    """What the router reads: the layer's NORMED input (assumed; the raw
    stream ``x`` is the other reading)."""
    del x
    return h


def route(x, h, lp, cfg):
    """The [S, E] weight of every expert for every token, from the layer's
    INPUT: 0 where the token did not choose the expert."""
    logits = _f32(router_input(x, h)) @ _f32(lp["moe_route_w"])
    kth = jnp.sort(logits, axis=-1)[..., -cfg.top_k][..., None]
    chosen = logits >= kth
    over = chosen if cfg.norm_topk_prob else jnp.ones_like(chosen)
    e = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    return jnp.where(chosen, e, 0.0) / jnp.sum(
        jnp.where(over, e, 0.0), axis=-1, keepdims=True)


def positional(x, kind, cfg):
    """x [S, H, hd] with its positions written in: on a sliding layer the
    two halves of each head rotated by the angle of the token's position;
    on a full layer NOTHING (no positional encoding at all)."""
    if kind != "sliding_attention":
        return x
    hd = cfg.head_dim
    inv_freq = 1.0 / cfg.rope_theta ** (
        jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.outer(jnp.arange(x.shape[0], dtype=jnp.float32), inv_freq)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def sees(pos, t, kind, cfg):
    """Whether the query at ``pos`` sees the key at ``t``: every earlier
    position and itself; on a sliding layer the last ``sliding_window`` of
    them, itself included (assumed: the window's edge)."""
    seen = t <= pos
    if kind == "sliding_attention":
        seen = seen & (t > pos - cfg.sliding_window)
    return seen


def _attention(h, lp, kind, cfg):
    """h [S, D] -> the layer's attention output [S, D]."""
    S = h.shape[0]
    Hq, Hkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    q = positional(_mm(h, lp["wq"]).reshape(S, Hq, hd), kind, cfg)
    k = positional(_mm(h, lp["wk"]).reshape(S, Hkv, hd), kind, cfg)
    v = _mm(h, lp["wv"]).reshape(S, Hkv, hd)
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
        seen = sees(pos[:, None], t[None, :], kind, cfg)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _cut(p), _cut(v))

    a = jax.lax.map(one_block, jnp.arange(blocks)).reshape(-1, Hq, hd)[:S]
    return _mm(a.reshape(S, Hq * hd), lp["wo"])


def expert(g, w_in, w_out):
    """One expert on g [S, D]: ReLU-gated, gate first in ``w_in``."""
    gate, up = jnp.split(_mm(g, w_in), 2, axis=-1)
    return _mm(jax.nn.relu(gate) * up, w_out)


def experts(g, weights, lp):
    """What the experts add for g [S, D] under ``weights`` [S, E]."""
    def one_expert(out, e):
        w_in, w_out, weight = e  # this expert's matrices, cast where used
        return out + weight[..., None] * expert(g, w_in, w_out), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(g),
        (lp["moe_gmm_w_in"], lp["moe_gmm_w_out"],
         jnp.moveaxis(weights, -1, 0)))
    return out


def _hidden_one(params: dict, tokens, cfg):
    """tokens [S] -> final hidden states [S, D], float32."""
    x = _f32(params["wte"])[tokens]
    for lp, kind in zip(params["layers"], cfg.layer_types):
        h = _rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        weights = route(x, h, lp, cfg)  # from the INPUT, before attention
        x = x + _attention(h, lp, kind, cfg)
        g = _rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
        x = x + experts(g, weights, lp)
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
