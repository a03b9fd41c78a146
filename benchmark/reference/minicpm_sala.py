"""Plain reference of the MiniCPM-SALA family's forward pass: straightforward
``jax.numpy`` in float32 at the highest matmul precision, a Python loop over
the layers, no kernel, no cache, no recurrent state and no batching: one
sequence at a time (``lax.map`` over the requests), a mixer a block of
query rows at a time and the SwiGLU a block of rows at a time, so that
sixteen sequences of 16k tokens fit beside the engine on the chip.

Follows the public ``minicpm_sala`` configuration (openbmb MiniCPM-SALA
``config.json``; ``mixer_types`` names the attention of MiniCPM4, InfLLM-V2:
arXiv:2506.07900 section 2.2 and arXiv:2509.24663, and Lightning Attention:
arXiv:2401.04658 as MiniMax-01, arXiv:2501.08313, builds it).

``x0 = E[token] * scale_emb``; with ``a = scale_depth / sqrt(32)`` (the
PUBLISHED depth): ``h <- h + a * Mixer(RMSNorm(h))``, ``h <- h + a *
SwiGLU(RMSNorm(h))``; logits ``= W_head . (RMSNorm(h) / (hidden_size /
dim_model_base))``.

- ``lightning``: q, k, v, g projections; ``qk_norm`` (RMSNorm over each
  head with one learned weight); rotary over the whole head, rotate-half
  form, at the true position; the ``O(n^2)`` masked-decay product
  ``o_i = sum_{j <= i} lam^(i - j) (q_i . k_j) v_j / sqrt(hd)`` (``decay``:
  ``lam_h = exp(-s_h)``, ``s_h = 2^(-8 (h + 1) / H) * (1 - l / 31 + 1e-5)``
  with ``l`` the layer's PUBLISHED index); ``y = Wo(RMSNorm(o) *
  sigmoid(g))``, the norm over the heads joined (``output_norm``).
- ``minicpm4``: 32 query heads over 2 K/V heads, ``qk_norm``, NO positional
  encoding; a softmax attention under a dense ``[n, n]`` mask built from
  the six steps of the selection (``chosen_blocks``): compressed keys as
  the MEAN of 32 keys every 16, each group head's softmax over the visible
  ones, the group's sum, a max over the five that overlap a block, the
  first block and the last 32 forced, the 64 largest with ties to the lower
  block; every key below ``dense_len``. ``y = Wo(Attn * sigmoid(g))``.

Two departures from the public kernels, both in
benchmark/configs/minicpm-sala-8l.json ``assumed``: (a) step 2's softmax
over the compressed keys is computed EXACTLY (the public kernels estimate
its log-sum-exp from a coarser pooling, kernel 128 / stride 64, to save a
pass: an approximation of this definition); (b) dense or sparse is decided
by the QUERY's position (``t < dense_len``), not by the length of the
sequence it arrived in, so that a position's output does not depend on
what follows it. The other reading of each assumed size is ONE function
or constant here (``decay``, ``output_norm``, ``qk_norm``).

Reads the program's parameter tree (``models/minicpm_sala.py
minicpm_sala_init``) and of its config only numbers. Each weight is cast to
float32 where it is used.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

ENGINE_MODEL = "minicpm_sala"
Q_BLOCK = 128   # queries a block of a mixer: [H, 128, S] float32 scores
ROW_BLOCK = 2048  # rows a block of the SwiGLU
# what ``init_fn`` rounds once to the published checkpoint's dtype: every
# leaf with two or more axes; norm scales stay float32
PUBLISHED_DTYPE = jnp.bfloat16
# None: float32 at the highest precision. A control sets a narrower dtype
# (``jnp.float8_e4m3fn``): both operands of every matrix product are then
# cut to it first, which is how "the reference computed one precision
# lower" is read for the limit of ``reference_check``.
ROUND_TO = None


def config_class():
    from ray_tpu.models.minicpm_sala import MiniCPMSALAConfig

    return MiniCPMSALAConfig


def init_fn():
    """The program's own initialiser, its matrix leaves rounded ONCE to
    bfloat16 inside the same jitted call (benchmark/reference/lfm2_moe.py
    ``init_fn`` and its reasons: one copy of the weights is alive, the
    executor stores them as they are, the reference reads the same
    values)."""
    from ray_tpu.models.minicpm_sala import minicpm_sala_init

    def init(key, cfg):
        return jax.tree.map(
            lambda a: a.astype(PUBLISHED_DTYPE) if a.ndim >= 2 else a,
            minicpm_sala_init(key, cfg))

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


def _blocks_of(x, size):
    """x [S, ...] as [n, size, ...], zero rows behind."""
    n = -(-x.shape[0] // size)
    pad = [(0, n * size - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad).reshape(n, size, *x.shape[1:])


def qk_norm(x, scale, cfg):
    """RMSNorm over each head's ``hd`` numbers, one learned weight [hd]
    (assumed for BOTH mixers; the other reading: the lightning layers
    only)."""
    return _rms_norm(x, scale, cfg.norm_eps)


def rotary(x, cfg):
    """x [S, H, hd] rotated at positions 0..S-1: the two halves of the
    whole head, plain ``theta ** (-2i / hd)``."""
    hd = x.shape[-1]
    inv_freq = 1.0 / cfg.rope_theta ** (
        jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.outer(jnp.arange(x.shape[0], dtype=jnp.float32), inv_freq)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def decay(layer: int, cfg):
    """The heads' slopes ``s_h`` [H] of the layer with PUBLISHED index
    ``layer`` (assumed: Lightning Attention's slopes as MiniMax-01 builds
    them; the other reading has no layer factor)."""
    H = cfg.lightning_n_head
    base = 2.0 ** (-8.0 * np.arange(1, H + 1, dtype=np.float64) / H)
    return jnp.asarray(
        base * (1 - layer / (cfg.n_layer_published - 1) + 1e-5), jnp.float32)


def output_norm(o, scale, cfg):
    """RMSNorm over the ``H x hd`` numbers of all heads joined (assumed:
    MiniMax-01's form; the other reading: a head at a time)."""
    return _rms_norm(o, scale, cfg.norm_eps)


def _lightning(h, lp, layer: int, cfg):
    """h [S, D] -> the lightning layer's output [S, D]: the masked-decay
    product, a block of queries at a time."""
    S = h.shape[0]
    H, hd = cfg.lightning_n_head, cfg.lightning_head_dim
    q = _mm(h, lp["lightning_wq"]).reshape(S, H, hd)
    k = _mm(h, lp["lightning_wk"]).reshape(S, H, hd)
    v = _mm(h, lp["lightning_wv"]).reshape(S, H, hd)
    g = _mm(h, lp["lightning_wg"])
    q = rotary(qk_norm(q, lp["q_norm"], cfg), cfg)
    k = rotary(qk_norm(k, lp["k_norm"], cfg), cfg)
    slopes = decay(layer, cfg)
    qb = min(Q_BLOCK, S)
    t = jnp.arange(S)

    def one_block(args):
        j, qs = args
        pos = j * qb + jnp.arange(qb)
        gap = pos[:, None] - t[None, :]                        # [qb, S]
        lam = jnp.where(
            gap >= 0,
            jnp.exp(-slopes[:, None, None] * jnp.maximum(gap, 0)), 0.0)
        s = jnp.einsum("qhd,khd->hqk", _cut(qs), _cut(k)) * lam
        return jnp.einsum("hqk,khd->qhd", _cut(s), _cut(v))

    blocks = _blocks_of(q, qb)
    o = jax.lax.map(one_block, (jnp.arange(blocks.shape[0]), blocks))
    o = o.reshape(-1, H * hd)[:S] / math.sqrt(hd)
    o = output_norm(o, lp["lightning_out_norm"], cfg)
    return _mm(o * jax.nn.sigmoid(g), lp["lightning_wo"])


def compressed_keys(k, cfg):
    """k [S, Hkv, hd] -> c [NJ, Hkv, hd]: ``c_j`` the mean of keys ``16 j
    .. 16 j + 31``, for every ``j`` whose 32 keys exist."""
    S = k.shape[0]
    K, s = cfg.kernel_size, cfg.kernel_stride
    nj = max((S - K) // s + 1, 0)
    idx = s * np.arange(nj)[:, None] + np.arange(K)[None, :]
    return jnp.mean(k[idx], axis=1)


def chosen_blocks(q, c, pos, n_blocks: int, cfg):
    """Which blocks each query attends: q [Q, Hq, hd] at ``pos`` [Q], the
    compressed keys ``c`` [NJ, Hkv, hd] -> [Hkv, Q, n_blocks] bool. A query
    below ``dense_len``: every block up to its own. At or past it: steps
    2-5 of the selection."""
    K, s, Bs = cfg.kernel_size, cfg.kernel_stride, cfg.sparse_block_size
    Hq, hd = q.shape[1:]
    Hkv = c.shape[1]
    G = Hq // Hkv
    nj = c.shape[0]
    b = jnp.arange(n_blocks)
    own = pos // Bs
    upto = b[None, :] <= own[:, None]                           # [Q, NB]
    if nj == 0:
        return jnp.broadcast_to(upto[None], (Hkv, *upto.shape))
    j = jnp.arange(nj)
    visible = (s * j + K - 1)[None, :] <= pos[:, None]          # [Q, NJ]
    sc = jnp.einsum("qkgd,jkd->kgqj", _cut(q.reshape(-1, Hkv, G, hd)),
                    _cut(c)) / math.sqrt(hd)
    sc = jnp.where(visible[None, None], sc, -jnp.inf)
    top = jnp.max(sc, axis=-1, keepdims=True)
    e = jnp.exp(sc - jnp.where(jnp.isfinite(top), top, 0.0))
    den = jnp.sum(e, axis=-1, keepdims=True)
    r = jnp.sum(e / jnp.where(den > 0, den, 1.0), axis=1)       # [Hkv,Q,NJ]
    r = jnp.where(visible[None], r, -jnp.inf)
    # the compressed keys whose 32 tokens overlap block b
    lo = -((K - 1) // s)
    offsets = np.arange(lo, Bs // s)
    js = (Bs // s) * np.arange(n_blocks)[:, None] + offsets[None, :]
    inside = (js >= 0) & (js < nj)
    picked = jnp.where(inside[None, None],
                       r[:, :, np.clip(js, 0, nj - 1)], -jnp.inf)
    R = jnp.max(picked, axis=-1)                               # [Hkv,Q,NB]
    forced = (b[None, :] < cfg.init_blocks) | (
        (b[None, :] > own[:, None] - cfg.window_size // Bs) & upto)
    R = jnp.where(forced[None], jnp.inf, R)
    R = jnp.where(upto[None], R, -jnp.inf)
    # the topk largest, ties to the lower block: a stable descending sort
    order = jnp.argsort(-R, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    sparse = (rank < cfg.topk) & (R > -jnp.inf)
    dense = (pos < cfg.dense_len)[None, :, None]
    return jnp.where(dense, upto[None], sparse)


def _sparse(h, lp, cfg):
    """h [S, D] -> the minicpm4 layer's output [S, D]: softmax attention
    under the dense mask of the chosen blocks, a block of queries at a
    time."""
    S = h.shape[0]
    Hq, Hkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    Bs = cfg.sparse_block_size
    G = Hq // Hkv
    q = qk_norm(_mm(h, lp["wq"]).reshape(S, Hq, hd), lp["q_norm"], cfg)
    k = qk_norm(_mm(h, lp["wk"]).reshape(S, Hkv, hd), lp["k_norm"], cfg)
    v = _mm(h, lp["wv"]).reshape(S, Hkv, hd)
    g = _mm(h, lp["wg"])
    c = compressed_keys(k, cfg)
    n_blocks = -(-S // Bs)
    qb = min(Q_BLOCK, S)
    t = jnp.arange(S)

    def one_block(args):
        j, qs = args
        pos = j * qb + jnp.arange(qb)
        chosen = chosen_blocks(qs, c, pos, n_blocks, cfg)   # [Hkv, qb, NB]
        seen = jnp.repeat(chosen, Bs, axis=-1)[..., :S] & (
            t[None, :] <= pos[:, None])[None]
        s = jnp.einsum("qkgd,tkd->kgqt", _cut(qs.reshape(qb, Hkv, G, hd)),
                       _cut(k)) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(seen[:, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", _cut(p), _cut(v))

    blocks = _blocks_of(q, qb)
    a = jax.lax.map(one_block, (jnp.arange(blocks.shape[0]), blocks))
    a = a.reshape(-1, Hq * hd)[:S]
    return _mm(a * jax.nn.sigmoid(g), lp["wo"])


def _swiglu(h, lp):
    """h [S, D] -> [S, D], a block of rows at a time."""
    def one_block(rows):
        gate, up = jnp.split(_mm(rows, lp["mlp_in"]), 2, axis=-1)
        return _mm(jax.nn.silu(gate) * up, lp["mlp_out"])

    S = h.shape[0]
    out = jax.lax.map(one_block, _blocks_of(h, min(ROW_BLOCK, S)))
    return out.reshape(-1, h.shape[-1])[:S]


def _hidden_one(params: dict, tokens, cfg):
    """tokens [S] -> what the head reads [S, D], float32."""
    a = cfg.scale_depth / math.sqrt(cfg.n_layer_published)
    x = _f32(params["wte"])[tokens] * cfg.scale_emb
    for lp, kind, layer in zip(params["layers"], cfg.mixer_types,
                               cfg.layer_index):
        h = _rms_norm(x, lp["mixer_norm"], cfg.norm_eps)
        if kind == "lightning-attn":
            x = x + a * _lightning(h, lp, layer, cfg)
        else:
            x = x + a * _sparse(h, lp, cfg)
        h = _rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
        x = x + a * _swiglu(h, lp)
    h = _rms_norm(x, params["ln_f_scale"], cfg.norm_eps)
    return h / (cfg.d_model / cfg.dim_model_base)


def hidden(params: dict, tokens, cfg):
    """tokens [B, S] -> what the head reads [B, S, D], float32, one
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
