"""Plain reference of the EvaByte family's forward pass: straightforward
``jax.numpy`` in float32 at the highest matmul precision, a ``lax.scan``
over the like layers, no kernel, no cache, no blocks, no tables: masks
built from positions alone. One sequence at a time (``lax.map`` over the
requests), a layer's attention one block of queries at a time and its
feed-forward one block of rows at a time, so that sixteen prompts of
thousands of bytes fit beside the engine on the chip.

Follows the public ``EvaByte/EvaByte`` configuration (``config.json``) and
EVA's published estimator (Zheng et al., ICLR 2023, arXiv:2302.04542). With
``W = window_size``, ``C = chunk_size``, ``s = head_dim ** -0.5``, one
layer, head ``h``:

- ``u = rmsnorm(x) * (1 + g)`` (``norm_add_unit_offset``); ``q, k, v = u
  Wq, u Wk, u Wv`` without bias; rotary embedding (theta 1e5, the whole
  head) on q and k at the true positions;
- chunk ``c`` is positions ``[cC, cC + C)``. With two learned vectors a
  head, ``phi_h`` and ``mu_h``: ``a_m = softmax over m in c of (s k_m .
  phi_h)``, the summary's value ``V_c = sum a_m v_m`` and key ``K_c = mean
  k_m + mu_h``;
- a query at ``t`` sees the exact keys of its own window, ``{m : m // W = t
  // W, m <= t}``, and the summary of every chunk whose last position lies
  in a window before ``t // W``, under ONE softmax at scale ``s``;
- ``y = x + o Wo``; ``x' = y + swiglu(rmsnorm(y) * (1 + g2))`` (gate and up
  packed in one ``[D, 2M]`` matrix, gate first); a final norm of the same
  form; the head ``[D, num_pred_heads x vocab]``, output head ``j``
  predicting byte ``t + 1 + j``.

Departures from the published description, each a reading of what the
configuration does not say (benchmark/configs/evabyte-6.5b-8l.json
``assumed`` gives the other reading of each): (1) the summary's weights are
``softmax(s k . phi)`` with no ``-|k|^2 / 2`` term, and its key the plain
mean of the chunk's keys plus ``mu`` (not the weighted mean); (2) the
rotary embedding is the rotate-half form; (3) the head's columns are laid
``[head 0's vocab | head 1's | ...]``, head 0 the next byte. What is cached
and what a query sees is EvaByte's as published: windows that tile the
sequence, summaries that become visible when their window closes.

``window``, ``chunk``, ``early`` and ``use_mu`` make the WRONG models the
tests hold the engine apart from (a window or a chunk off by one, a
summary visible ``early`` windows too soon, ``mu`` left out).

Reads the program's parameter tree (``models/evabyte.py evabyte_init``)
and of its config only numbers. Each weight is cast to float32 where it is
used.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ENGINE_MODEL = "evabyte"
Q_BLOCK = 128     # queries a block of attention: [H, 128, keys] scores
ROW_BLOCK = 1024  # rows a block of the feed-forward: [1024, 2M] float32
# what ``init_fn`` rounds once to the published checkpoint's dtype: every
# matrix leaf (``eva_phi`` / ``eva_mu`` among them); the norms' offsets stay
# float32
PUBLISHED_DTYPE = jnp.bfloat16
# None: float32 at the highest precision. A control sets a narrower dtype
# (``jnp.float8_e4m3fn``): both operands of every matrix product are then
# cut to it first, which is how "the reference computed one precision
# lower" is read for the limit of ``reference_check``.
ROUND_TO = None


def config_class():
    from ray_tpu.models.evabyte import EvaByteConfig

    return EvaByteConfig


def init_fn():
    """The program's own initialiser, its matrix leaves rounded ONCE to
    bfloat16 inside the same jitted call (benchmark/reference/lfm2_moe.py
    ``init_fn`` and its reasons: one copy of the weights is alive, the
    executor stores them as they are, the reference reads the same
    values)."""
    from ray_tpu.models.evabyte import evabyte_init

    def init(key, cfg):
        # a leaf of the stacked layers leads with the layer axis: its
        # matrices have three axes, its norm offsets ``[L, D]`` two
        def rounded(tree, axes):
            return jax.tree.map(
                lambda a: a.astype(PUBLISHED_DTYPE) if a.ndim >= axes else a,
                tree)

        params = evabyte_init(key, cfg)
        blocks = params.pop("blocks")
        return dict(rounded(params, 2), blocks=rounded(blocks, 3))

    return init


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _cut(a):
    a = _f32(a)
    return a if ROUND_TO is None else a.astype(ROUND_TO).astype(jnp.float32)


def _mm(x, w):
    return _cut(x) @ _cut(w)


def _norm(x, g, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + _f32(g))


def _rotate(x, theta):
    """x [S, H, hd]: rotate the two halves of each head by the angle of
    its position (rotate-half)."""
    hd = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.outer(jnp.arange(x.shape[0], dtype=jnp.float32), inv_freq)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def summaries(k, v, phi, mu, C):
    """k, v [S, H, hd] -> the summaries of the sequence's whole chunks,
    (K [n, H, hd], V [n, H, hd]), n = S // C."""
    S, H, hd = k.shape
    n = S // C
    kc, vc = k[:n * C].reshape(n, C, H, hd), v[:n * C].reshape(n, C, H, hd)
    a = jax.nn.softmax(
        jnp.einsum("nchd,hd->nch", _cut(kc), _cut(phi)) / math.sqrt(hd),
        axis=1)
    return (jnp.mean(kc, axis=1) + _f32(mu),
            jnp.einsum("nch,nchd->nhd", _cut(a), _cut(vc)))


def _attention(u, lp, cfg, W, C, early, use_mu):
    """u [S, D], the layer's normed input -> its attention output [S, D]."""
    S = u.shape[0]
    H, hd = cfg.n_head, cfg.head_dim
    q = _rotate(_mm(u, lp["wq"]).reshape(S, H, hd), cfg.rope_theta)
    k = _rotate(_mm(u, lp["wk"]).reshape(S, H, hd), cfg.rope_theta)
    v = _mm(u, lp["wv"]).reshape(S, H, hd)
    mu = lp["eva_mu"] if use_mu else jnp.zeros_like(lp["eva_mu"])
    k_c, v_c = summaries(k, v, lp["eva_phi"], mu, C)
    keys = jnp.concatenate([k_c, k], axis=0)
    values = jnp.concatenate([v_c, v], axis=0)
    m = jnp.arange(S)
    last = jnp.arange(k_c.shape[0]) * C + C - 1  # a chunk's last position
    qb = min(Q_BLOCK, S)
    blocks = -(-S // qb)
    q = jnp.pad(q, ((0, blocks * qb - S), (0, 0), (0, 0)))

    def one_block(j):
        t = (j * qb + jnp.arange(qb))[:, None]
        qs = jax.lax.dynamic_slice_in_dim(q, j * qb, qb)
        s = jnp.einsum("qhd,khd->hqk", _cut(qs), _cut(keys)) / math.sqrt(hd)
        local = (m[None, :] // W == t // W) & (m[None, :] <= t)
        remote = (last[None, :] // W < t // W + early) & (last[None, :] <= t)
        seen = jnp.concatenate([remote, local], axis=1)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _cut(p), _cut(values))

    a = jax.lax.map(one_block, jnp.arange(blocks)).reshape(-1, H * hd)[:S]
    return _mm(a, lp["wo"])


def _swiglu(u, w_in, w_out):
    """u [S, D] -> [S, D], a block of rows at a time (``w_in`` is ``[D,
    2M]``, the gate's M columns first)."""
    S = u.shape[0]
    rb = min(ROW_BLOCK, S)
    blocks = -(-S // rb)
    u = jnp.pad(u, ((0, blocks * rb - S), (0, 0)))

    def one_block(rows):
        g, up = jnp.split(_mm(rows, w_in), 2, axis=-1)
        return _mm(jax.nn.silu(g) * up, w_out)

    return jax.lax.map(one_block, u.reshape(blocks, rb, -1)).reshape(
        blocks * rb, -1)[:S]


def _hidden_one(params: dict, tokens, cfg, window=None, chunk=None,
                early=0, use_mu=True):
    """tokens [S] -> final hidden states [S, D], float32."""
    W = cfg.window_size if window is None else window
    C = cfg.chunk_size if chunk is None else chunk
    def layer(x, lp):
        x = x + _attention(_norm(x, lp["ln1_g"], cfg.norm_eps), lp, cfg,
                           W, C, early, use_mu)
        return x + _swiglu(_norm(x, lp["ln2_g"], cfg.norm_eps),
                           lp["mlp_in"], lp["mlp_out"]), None

    # the layers' leaves lead with the layer axis: a scan takes one
    # layer's slice at a time (a Python loop's eight slices do not depend
    # on the request, and the compiler keeps all of them: the weights twice)
    x, _ = jax.lax.scan(layer, _f32(params["wte"])[tokens], params["blocks"])
    return _norm(x, params["ln_f_g"], cfg.norm_eps)


def logits_at(params: dict, tokens, positions, cfg):
    """Float32 logits [B, P, V] of the NEXT byte (output head 0) at
    ``positions`` [B, P] of ``tokens`` [B, S]: only the rows that are asked
    for meet the output head."""
    with jax.default_matmul_precision("highest"):
        def one(args):
            t, pos = args
            return _mm(_hidden_one(params, t, cfg)[pos],
                       params["lm_head"][:, :cfg.vocab_size])

        return jax.lax.map(one, (tokens, positions))


def logits(params: dict, tokens, cfg, **variant):
    """Float32 logits [B, S, num_pred_heads, V] at every position: output
    head ``j`` predicts byte ``t + 1 + j``."""
    with jax.default_matmul_precision("highest"):
        h = jax.lax.map(
            lambda t: _hidden_one(params, t, cfg, **variant), tokens)
        out = _mm(h, params["lm_head"])
    return out.reshape(*tokens.shape, cfg.num_pred_heads, cfg.vocab_size)
