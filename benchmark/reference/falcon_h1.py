"""Plain reference of the Falcon-H1 family's forward pass (``falcon_h1``):
straightforward ``jax.numpy`` in float32 at the highest matmul precision, no
kernel, no cache, no chunks and no batching: one sequence at a time
(``lax.map`` over the requests), the state recurrence as a ``lax.scan`` over
TOKENS (the definition, not the chunked form), attention as a masked softmax.

Follows the public configuration (tiiuae Falcon-H1-34B-Instruct
``config.json``) and Mamba-2 as published (Dao and Gu, arXiv:2405.21060).
``x0 = E[token] * embedding_multiplier``; a layer, with ``u = RMSNorm(x)``
feeding BOTH mixers::

    p = ((u * ssm_in_multiplier) W_in) * mup          [z | xBC | dt]
    xBC = silu(conv_causal(xBC) + b_c)                -> xs [H, P], B, C [G, N]
    dt = softplus(dt + dt_bias);  a = exp(dt A),  A = -exp(A_log)
    S_t = a_t S_{t-1} + dt_t (xs_t outer B_t);   y_t = S_t C_t + D xs_t
    m = (gated_norm(y, z) W_out) * ssm_out_multiplier
    q = v_in W_q, k = (v_in W_k) * key_multiplier, v = v_in W_v,
        v_in = u * attention_in_multiplier; rotary by halves over the head
    a = softmax_causal(q k^T / sqrt(hd)) v W_o * attention_out_multiplier
    x = x + m + a
    x = x + (silu((g W_gate) * mlp_multipliers[0]) * (g W_up)) W_down
            * mlp_multipliers[1],   g = RMSNorm(x)

and ``logits = (RMSNorm(x_L) W_head) * lm_head_multiplier``.

The readings the configuration leaves open
(benchmark/configs/falcon-h1-34b-instruct-5l.json ``assumed``) that are code,
ONE function each here: ``gated_norm`` (the gate first, the norm over each
GROUP's channels; the other: over all of them) and ``mup`` (a vector over
``W_in``'s columns, applied in the step).

Departures from the published description, none of which changes a number a
test could see (tests/test_falcon_h1.py holds the blocked ``logits_at`` to
the unblocked pass): the work is done IN BLOCKS so that it fits beside the
serving engine at published widths. One layer's matrices are cast to float32
at a time, and the feed-forward half ``FFN_BLOCKS`` column blocks at a time
(a layer is 1.72 GB in float32, 1.32 GB of it the SwiGLU); attention one K/V
head's group of query heads and ``Q_BLOCK`` query rows at a time; the
embedding is gathered before it is cast; the head meets the asked positions
only, over ``HEAD_BLOCKS`` blocks of the vocabulary (a float32 head is 5.35
GB).

Reads the program's parameter tree (``models/falcon_h1.py falcon_h1_init``)
and of its config only numbers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ENGINE_MODEL = "falcon_h1"
Q_BLOCK = 256       # queries a block of attention: [g, 256, S] float32 scores
FFN_BLOCKS = 8      # column blocks of the SwiGLU (21,504 = 8 x 2,688)
HEAD_BLOCKS = 15    # blocks of the vocabulary (261,120 = 15 x 17,408)
# what ``init_fn`` asks the program's initialiser for: matrices in the
# published checkpoint's dtype; vectors stay float32
PUBLISHED_DTYPE = jnp.bfloat16
# None: float32 at the highest precision. A control sets a narrower dtype:
# both operands of every matrix product are then cut to it first
# (``reference_check``'s "one precision lower").
ROUND_TO = None
# ... the head's product alone cut to it (a control: "a bfloat16 head")
HEAD_ROUND_TO = None
# ... and the SSM state alone rounded to it after every token (a control:
# "the state kept in bfloat16")
STATE_ROUND_TO = None


def config_class():
    from ray_tpu.models.falcon_h1 import FalconH1Config

    return FalconH1Config


def init_fn():
    """The program's own initialiser, asked for matrix leaves in bfloat16:
    each is rounded ONCE, as it is drawn, inside the one jitted call
    (benchmark/reference/lfm2_moe.py ``init_fn`` and its reasons). The
    embedding and the head are drawn in blocks of rows
    (models/falcon_h1.py ``_normal``): a float32 draw of either would be
    5.35 GB beside the tree."""
    from ray_tpu.models.falcon_h1 import falcon_h1_init

    def init(key, cfg):
        return falcon_h1_init(key, cfg, dtype=PUBLISHED_DTYPE)

    return init


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _cut(a, to=None):
    a = _f32(a)
    to = ROUND_TO if to is None else to
    return a if to is None else a.astype(to).astype(jnp.float32)


def _mm(x, w, to=None):
    return _cut(x, to) @ _cut(w, to)


def _blocks(n: int, want: int) -> int:
    """``want`` blocks where they divide ``n``, else one."""
    return want if n % want == 0 else 1


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(scale)


# ------------------------------------------------------------- state space


def mup(cfg):
    """The five ``ssm_multipliers`` as a vector over ``W_in``'s columns ``[z
    | x | B | C | dt]``."""
    import numpy as np

    S, GN = cfg.d_ssm, cfg.ssm_n_group * cfg.ssm_d_state
    return np.repeat(np.asarray(cfg.ssm_multipliers, np.float32),
                     [S, S, GN, GN, cfg.ssm_n_head])


def short_conv(x, w, b):
    """x [S, C] through the causal depthwise filter ``w`` [taps, C] (tap
    ``j`` meets position ``t - (taps - 1) + j``; zeros before the sequence)
    plus the bias ``b`` [C] (``mamba_conv_bias``), then SiLU."""
    taps, S = w.shape[0], x.shape[0]
    ext = jnp.concatenate(
        [jnp.zeros((taps - 1, x.shape[1]), x.dtype), x], axis=0)
    return jax.nn.silu(
        sum(ext[j:j + S] * _f32(w)[j] for j in range(taps)) + _f32(b))


def gated_norm(y, z, scale, cfg):
    """``RMSNorm(y * silu(z))``, y [S, d_ssm]: the gate FIRST
    (``mamba_norm_before_gate`` false), the norm over each of the G groups'
    channels, one ``[d_ssm]`` weight."""
    S = y.shape[0]
    g = (y * jax.nn.silu(z)).reshape(S, cfg.ssm_n_group, -1)
    g = g * jax.lax.rsqrt(
        jnp.mean(jnp.square(g), axis=-1, keepdims=True) + cfg.norm_eps)
    return g.reshape(S, -1) * _f32(scale)


def ssm(u, lp, cfg):
    """u [S, D], the layer's normed input -> the state-space branch [S, D]."""
    S = u.shape[0]
    H, P, N, G = (cfg.ssm_n_head, cfg.ssm_head_dim, cfg.ssm_d_state,
                  cfg.ssm_n_group)
    d = cfg.d_ssm
    p = _mm(u * cfg.ssm_in_multiplier, lp["ssm_w_in"]) * mup(cfg)
    z, xBC, dt = p[:, :d], p[:, d:d + cfg.conv_width], p[:, d + cfg.conv_width:]
    xBC = short_conv(xBC, lp["ssm_conv_w"], lp["ssm_conv_b"])
    xs = xBC[:, :d].reshape(S, H, P)
    Bm = xBC[:, d:d + G * N].reshape(S, G, N)
    Cm = xBC[:, d + G * N:].reshape(S, G, N)
    dt = jax.nn.softplus(dt + _f32(lp["ssm_dt_bias"]))          # [S, H]
    A = -jnp.exp(_f32(lp["ssm_a_log"]))
    D = _f32(lp["ssm_d"])
    by_head = lambda a: jnp.repeat(a, H // G, axis=0)  # noqa: E731

    def token(state, xs_t):
        x, dt_t, B, C = xs_t                  # [H, P], [H], [G, N] x 2
        state = jnp.exp(dt_t * A)[:, None, None] * state + (
            dt_t[:, None] * x)[..., None] * by_head(B)[:, None, :]
        if STATE_ROUND_TO is not None:
            # ``reduce_precision``, not a pair of casts, which the compiler
            # may drop (benchmark/reference/ling_hybrid.py)
            kind = jnp.finfo(STATE_ROUND_TO)
            state = jax.lax.reduce_precision(state, kind.nexp, kind.nmant)
        return state, jnp.einsum("hpn,hn->hp", state, by_head(C)) \
            + D[:, None] * x

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), jnp.float32),
                        (xs, dt, Bm, Cm))
    y = gated_norm(y.reshape(S, d), z, lp["ssm_norm"], cfg)
    return _mm(y, lp["ssm_w_out"]) * cfg.ssm_out_multiplier


# --------------------------------------------------------------- attention


def rotate(x, theta):
    """x [S, heads, hd]: rotate-half over the whole head at the token's
    position, angles ``pos theta ** (-2i / hd)`` in float32, no scaling."""
    S, _, hd = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.outer(jnp.arange(S, dtype=jnp.float32), inv_freq)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def attention(u, lp, cfg):
    """u [S, D] -> the attention branch [S, D]: a causal softmax, one K/V
    head's query heads and ``Q_BLOCK`` query rows at a time."""
    S = u.shape[0]
    Hq, Hkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    g = Hq // Hkv
    v_in = u * cfg.attention_in_multiplier
    q = rotate(_mm(v_in, lp["wq"]).reshape(S, Hq, hd), cfg.rope_theta)
    k = rotate((_mm(v_in, lp["wk"]) * cfg.key_multiplier).reshape(
        S, Hkv, hd), cfg.rope_theta)
    v = _mm(v_in, lp["wv"]).reshape(S, Hkv, hd)
    qb = min(Q_BLOCK, S)
    blocks = -(-S // qb)
    q = jnp.pad(q, ((0, blocks * qb - S), (0, 0), (0, 0)))
    q = q.reshape(blocks * qb, Hkv, g, hd)
    t = jnp.arange(S)

    def one(args):
        j, h = args
        pos = j * qb + jnp.arange(qb)
        qs = jax.lax.dynamic_slice_in_dim(q, j * qb, qb)[:, h]  # [qb, g, hd]
        s = jnp.einsum("qgd,kd->gqk", _cut(qs), _cut(k[:, h])) * hd ** -0.5
        seen = t[None, :] <= pos[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->qgd", _cut(p), _cut(v[:, h]))

    js, hs = jnp.meshgrid(jnp.arange(blocks), jnp.arange(Hkv), indexing="ij")
    a = jax.lax.map(one, (js.reshape(-1), hs.reshape(-1)))  # [blocks*Hkv,..]
    a = a.reshape(blocks, Hkv, qb, g, hd).transpose(0, 2, 1, 3, 4).reshape(
        blocks * qb, Hq * hd)[:S]
    return _mm(a, lp["wo"]) * cfg.attention_out_multiplier


# ------------------------------------------------------------------- ffn


def ffn(g, lp, cfg, blocks: int = FFN_BLOCKS):
    """g [S, D], the normed input -> the feed-forward half [S, D]; the
    SwiGLU ``blocks`` column blocks at a time (a sum of the blocks' parts:
    the same products in another order of addition)."""
    F = cfg.d_mlp
    gate_mul, down_mul = cfg.mlp_multipliers
    n = _blocks(F, blocks)
    f = F // n
    cols = jax.lax.dynamic_slice_in_dim   # a block where the leaf stands

    def block(out, j):
        gate = _mm(g, cols(lp["mlp_in"], j * f, f, axis=1)) * gate_mul
        up = _mm(g, cols(lp["mlp_in"], F + j * f, f, axis=1))
        return out + _mm(jax.nn.silu(gate) * up,
                         cols(lp["mlp_out"], j * f, f, axis=0)), None

    out, _ = jax.lax.scan(block, jnp.zeros_like(g), jnp.arange(n))
    return out * down_mul


def layer(x, lp, cfg, ffn_blocks: int = FFN_BLOCKS):
    """One layer on x [S, D]: ONE norm feeds both mixers, whose outputs are
    summed into the residual; then the feed-forward half."""
    u = _rms_norm(x, lp["input_norm"], cfg.norm_eps)
    x = x + ssm(u, lp, cfg) + attention(u, lp, cfg)
    return x + ffn(_rms_norm(x, lp["ffn_norm"], cfg.norm_eps), lp, cfg,
                   ffn_blocks)


def _hidden_one(params: dict, tokens, cfg, ffn_blocks: int = FFN_BLOCKS):
    """tokens [S] -> final hidden states [S, D], float32."""
    x = _f32(params["wte"][tokens]) * cfg.embedding_multiplier
    for lp in params["layers"]:
        x = layer(x, lp, cfg, ffn_blocks)
    return _rms_norm(x, params["ln_f_scale"], cfg.norm_eps)


def head(params: dict, h, cfg, blocks: int = HEAD_BLOCKS):
    """h [P, D] -> float32 logits [P, V], ``blocks`` blocks of the
    vocabulary at a time."""
    V = cfg.vocab_size
    n = _blocks(V, blocks)

    def block(j):
        return _mm(h, jax.lax.dynamic_slice_in_dim(
            params["lm_head"], j * (V // n), V // n, axis=1), HEAD_ROUND_TO)

    out = jax.lax.map(block, jnp.arange(n))                 # [n, P, V / n]
    return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], V) \
        * cfg.lm_head_multiplier


def logits_at(params: dict, tokens, positions, cfg):
    """Float32 logits [B, P, V] at ``positions`` [B, P] of ``tokens``
    [B, S]: only the rows that are asked for meet the output head."""
    with jax.default_matmul_precision("highest"):
        def one(args):
            t, pos = args
            return head(params, _hidden_one(params, t, cfg)[pos], cfg)

        return jax.lax.map(one, (tokens, positions))


def logits(params: dict, tokens, cfg):
    """Float32 logits [B, S, V] at every position, UNBLOCKED: every
    product whole (a small size's: the tests hold ``logits_at`` to it)."""
    with jax.default_matmul_precision("highest"):
        def one(t):
            return head(params, _hidden_one(params, t, cfg, 1), cfg, 1)

        return jax.lax.map(one, tokens)
