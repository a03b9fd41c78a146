"""Falcon-H1 family (``falcon_h1``): a Mamba-2 state-space mixer AND grouped-
query attention side by side in EVERY layer, summed into one residual, for
serving.

Follows the public ``falcon_h1`` configuration (tiiuae Falcon-H1-34B-Instruct
``config.json``), whose two mixers are published layers: Mamba-2 (Dao and Gu,
arXiv:2405.21060; ops/ssd.py) and rotary grouped-query attention. With ``u =
RMSNorm(x)`` ONE norm feeds both mixers::

    x  = x + SSM(u) + Attention(u)
    x  = x + FFN(RMSNorm(x))

``SSM`` (H heads of P channels, a state ``[P, N]`` a head, G groups): ``p =
((u * ssm_in_multiplier) W_in) * mup`` with ``mup`` the five
``ssm_multipliers`` over the columns ``[z | x | B | C | dt]``; a causal
depthwise convolution of ``conv_kernel`` taps WITH BIAS over ``[x | B | C]``
and SiLU (ops/short_conv.py, the plain form); ``dt = softplus(dt + dt_bias)``,
``A = -exp(A_log)``; the recurrence of ops/ssd.py with the ``D`` skip; ``y =
RMSNorm_by_group(y * silu(z))`` (``_gated_norm``: the gate FIRST,
``mamba_norm_before_gate`` false); ``(y W_out) * ssm_out_multiplier``.

``Attention``: ``v_in = u * attention_in_multiplier``; ``q = v_in W_q``, ``k =
(v_in W_k) * key_multiplier``, ``v = v_in W_v`` (no bias); rotary over the
whole head by halves, no scaling; the paged cache (models/cached.py
``attend``); ``(a W_o) * attention_out_multiplier``.

``FFN``: ``(silu((g W_gate) * mlp_multipliers[0]) * (g W_up)) W_down *
mlp_multipliers[1]``. The embedding is ``E[token] * embedding_multiplier``,
the logits ``(RMSNorm(x_L) W_head) * lm_head_multiplier`` (untied).

What the configuration leaves open is listed in
benchmark/configs/falcon-h1-34b-instruct-5l.json ``assumed``, each with its
other reading; the two that are code are ONE function here (``_gated_norm``,
``_mup``) and one in the reference. The multipliers are scalars IN THE STEP:
no leaf is rescaled at load.

Like layers, held as a LIST of per-layer trees (the conventions of
models/ling_hybrid.py: float32 masters, activations in ``cfg.dtype``,
``state`` rows a slot, donated to the step programs): the first family whose
pool's layers and state's layers are the SAME layers. ``state`` holds ``ssd``
``[n_layer, slots, H, P, N]`` float32 and ``conv`` ``[n_layer, slots, taps -
1, conv_width]`` (the convolution's history; slot 0 the garbage sink; a row
whose chunk starts its sequence begins from zeros in BOTH whatever the slot
held). No counters.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models import cached
from ray_tpu.models.parts import (
    final_norm, head_untied, leaf_tree, rotary_tables)
from ray_tpu.ops import ssd
from ray_tpu.ops.layers import rms_norm, rope
from ray_tpu.ops.paged_attention import resolve_backend
from ray_tpu.ops.short_conv import short_conv_decode, short_conv_prefill

# ``falcon_h1_init``: W_q and W_k against what makes q and k of unit scale
# (models/pangu_ultra_moe.py ``QK_GAIN`` and its reason: scores of std 2.4)
QK_GAIN = 1.55

@dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 261120
    max_seq_len: int = 262144
    d_model: int = 5120
    n_layer: int = 72
    n_head: int = 20
    n_kv_head: int = 4
    head_dim: int = 128
    d_mlp: int = 21504
    ssm_n_head: int = 32            # H (``mamba_n_heads``)
    ssm_head_dim: int = 128         # P (``mamba_d_head``)
    ssm_d_state: int = 256          # N (``mamba_d_state``)
    ssm_n_group: int = 2            # G (``mamba_n_groups``)
    conv_kernel: int = 4            # ``mamba_d_conv``
    ssm_chunk: int = 128            # ``mamba_chunk_size``
    rope_theta: float = 1e11
    norm_eps: float = 1e-5
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    # over the in-projection's columns [z | x | B | C | dt]
    ssm_multipliers: tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)
    # on the gate's product, on the layer's output
    mlp_multipliers: tuple[float, ...] = (
        0.1767766952966369, 0.011160714285714284)
    dtype: Any = jnp.bfloat16
    # decode attention backend / serving quantization: see models/gpt.py
    # GPTConfig. The engine refuses ``quantization`` for this family.
    attention_backend: str = "auto"
    quantization: str | None = None

    def __post_init__(self):
        for name, n in (("ssm_multipliers", 5), ("mlp_multipliers", 2)):
            value = tuple(float(x) for x in getattr(self, name))
            if len(value) != n:
                raise ValueError(f"{name} holds {n} numbers, not {value}")
            object.__setattr__(self, name, value)
        if self.ssm_n_head % self.ssm_n_group:
            raise ValueError("ssm_n_group must divide ssm_n_head")
        if self.n_head % self.n_kv_head or self.head_dim % 2:
            raise ValueError("n_kv_head must divide n_head; head_dim even")

    @staticmethod
    def tiny(vocab_size: int = 512) -> "FalconH1Config":
        """Two groups, heads of 16, state 32; every multiplier off 1 so that
        each one's place shows."""
        return FalconH1Config(
            vocab_size=vocab_size, max_seq_len=256, d_model=64, n_layer=2,
            n_head=4, n_kv_head=2, head_dim=16, d_mlp=128, ssm_n_head=4,
            ssm_head_dim=16, ssm_d_state=32, ssm_n_group=2, ssm_chunk=16,
            rope_theta=10000.0, attention_in_multiplier=0.5)

    @property
    def d_ssm(self) -> int:
        """``mamba_d_ssm``: the state-space branch's inner width."""
        return self.ssm_n_head * self.ssm_head_dim

    @property
    def conv_width(self) -> int:
        """Channels the short convolution runs over: ``[x | B | C]``."""
        return self.d_ssm + 2 * self.ssm_n_group * self.ssm_d_state

    @property
    def d_in_proj(self) -> int:
        """Columns of ``W_in``: ``[z | x | B | C | dt]`` (no inner MLP)."""
        return self.d_ssm + self.conv_width + self.ssm_n_head


def _mup(cfg: FalconH1Config):
    """The ``ssm_multipliers`` as a vector over ``W_in``'s columns
    (numpy: a constant of the traced program). The other reading: folded
    into the leaf at load."""
    import numpy as np

    widths = (cfg.d_ssm, cfg.d_ssm, cfg.ssm_n_group * cfg.ssm_d_state,
              cfg.ssm_n_group * cfg.ssm_d_state, cfg.ssm_n_head)
    return np.repeat(np.asarray(cfg.ssm_multipliers, np.float32), widths)


# rows a block of a leaf's draw may hold at most: a block's float32 normals
# rest beside the tree, never a whole leaf's (the embedding and the head are
# 1.34 G elements: 5.35 GB in float32)
_DRAW_ROWS = 16384


def _normal(key, shape, std, dtype):
    """``N(0, std^2)`` of ``shape`` in ``dtype``; ``std`` a number or a
    vector over the LAST axis. A leaf of more than ``_DRAW_ROWS`` rows is
    drawn in blocks of rows (the largest divisor of the rows under it),
    each rounded to ``dtype`` as it is drawn."""
    std = jnp.asarray(std, jnp.float32)
    rows = shape[0]
    per = next(d for d in range(min(rows, _DRAW_ROWS), 0, -1)
               if rows % d == 0)
    if len(shape) != 2 or per == rows:
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(
            dtype)

    def block(k):
        return (jax.random.normal(k, (per, shape[1]), jnp.float32)
                * std).astype(dtype)

    return jax.lax.map(block, jax.random.split(key, rows // per)).reshape(
        shape)


def falcon_h1_init(key: jax.Array, cfg: FalconH1Config,
                   dtype=jnp.float32) -> dict:
    """Masters in ``dtype`` (float32; the benchmark's ``init_fn`` asks for
    the checkpoint's bfloat16 matrices), normal from ``key``. The published
    multipliers are small (``lm_head_multiplier`` 1/128, ``mlp_multipliers
    [1]`` 0.011): with every matrix at ``fan_in ** -0.5`` the signal behind
    each would vanish and no comparison would see the multiplier. So each
    matrix leaf has the std that makes the signal BEHIND its multiplier of
    unit scale: ``fan_in ** -0.5`` over the multipliers that stand between
    the leaf's input and its output (``W_in`` by column, over
    ``ssm_in_multiplier * mup``), the projections back into the residual
    stream a further ``(3 L) ** -0.5`` smaller (three branches a layer),
    ``W_q`` and ``W_k`` times ``QK_GAIN`` (scores of std 2.4, so that a row's
    output depends on WHICH rows it read), the embedding ``1 /
    embedding_multiplier``. ``A_log`` and ``dt_bias`` by Mamba-2's own
    initialiser: ``A`` uniform in 1..16, ``dt`` log-uniform in 1e-3..1e-1
    (``dt_bias`` its inverse softplus), so that a head's memory spans a few
    tokens to a few thousand: a state both remembers and forgets inside a
    6 k context, and one lost at a chunk's seam is noticed. The filter's
    taps have std ``taps ** -0.5`` and its bias 0.5; ``D`` and the norm
    scales are ones. Vectors stay float32 whatever ``dtype``."""
    D, F, V = cfg.d_model, cfg.d_mlp, cfg.vocab_size
    Hq, Hkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    H, S = cfg.ssm_n_head, cfg.d_ssm
    back = (3 * cfg.n_layer) ** -0.5
    f32 = jnp.float32
    a_in, k_mul = cfg.attention_in_multiplier, cfg.key_multiplier
    gate_mul, down_mul = cfg.mlp_multipliers

    def norm(key, *shape, std):
        return _normal(key, shape, std, dtype)

    ones = lambda n: jnp.ones((n,), f32)  # noqa: E731
    keys = jax.random.split(key, cfg.n_layer + 2)
    layers = []
    for i in range(cfg.n_layer):
        k = iter(jax.random.split(keys[i], 16))
        dt = jnp.exp(jax.random.uniform(
            next(k), (H,), f32, math.log(1e-3), math.log(1e-1)))
        layers.append({
            "input_norm": ones(D), "ffn_norm": ones(D),
            "ssm_w_in": norm(next(k), D, cfg.d_in_proj, std=D ** -0.5 / (
                cfg.ssm_in_multiplier * jnp.asarray(_mup(cfg)))),
            "ssm_conv_w": jax.random.normal(
                next(k), (cfg.conv_kernel, cfg.conv_width), f32)
            * cfg.conv_kernel ** -0.5,
            "ssm_conv_b": 0.5 * jax.random.normal(
                next(k), (cfg.conv_width,), f32),
            "ssm_a_log": jnp.log(jax.random.uniform(
                next(k), (H,), f32, 1.0, 16.0)),
            "ssm_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "ssm_d": ones(H), "ssm_norm": ones(S),
            "ssm_w_out": norm(next(k), S, D, std=S ** -0.5 * back
                              / cfg.ssm_out_multiplier),
            "wq": norm(next(k), D, Hq * hd, std=D ** -0.5 * QK_GAIN / a_in),
            "wk": norm(next(k), D, Hkv * hd,
                       std=D ** -0.5 * QK_GAIN / (a_in * k_mul)),
            "wv": norm(next(k), D, Hkv * hd, std=D ** -0.5 / a_in),
            "wo": norm(next(k), Hq * hd, D, std=(Hq * hd) ** -0.5 * back
                       / cfg.attention_out_multiplier),
            # [gate | up]
            "mlp_in": norm(next(k), D, 2 * F, std=D ** -0.5 / jnp.repeat(
                jnp.asarray([gate_mul, 1.0], f32), F)),
            "mlp_out": norm(next(k), F, D, std=F ** -0.5 * back / down_mul),
        })
    return {
        "wte": norm(keys[-2], V, D, std=1.0 / cfg.embedding_multiplier),
        "layers": layers,
        "ln_f_scale": ones(D),
        # drawn by ROWS of the vocabulary (blocks), stored [D, V]
        "lm_head": norm(keys[-1], V, D,
                        std=D ** -0.5 / cfg.lm_head_multiplier).T,
    }


_LEAF_AXES = {
    "input_norm": ("embed",), "ffn_norm": ("embed",),
    "ssm_w_in": ("embed", "mlp"), "ssm_conv_w": (None, "mlp"),
    "ssm_conv_b": ("mlp",), "ssm_a_log": (None,), "ssm_dt_bias": (None,),
    "ssm_d": (None,), "ssm_norm": ("mlp",), "ssm_w_out": ("mlp", "embed"),
    "wq": ("embed", "heads"), "wk": ("embed", "kv_heads"),
    "wv": ("embed", "kv_heads"), "wo": ("heads", "embed"),
    "mlp_in": ("embed", "mlp"), "mlp_out": ("mlp", "embed"),
    "wte": ("vocab", "embed"), "ln_f_scale": ("embed",),
    "lm_head": ("embed", "vocab"),
}
# the contraction axis of each matmul weight; -1: kept as given
_LEAF_QUANT = {
    "ssm_w_in": 0, "ssm_w_out": 0, "wq": 0, "wk": 0, "wv": 0, "wo": 0,
    "mlp_in": 0, "mlp_out": 0, "wte": 1, "lm_head": 0,
}


def falcon_h1_param_axes(cfg: FalconH1Config) -> dict:
    """Logical axis names per leaf."""
    return leaf_tree(falcon_h1_init, cfg, _LEAF_AXES.__getitem__)


def falcon_h1_quant_axes(cfg: FalconH1Config) -> dict:
    """Per leaf, the contraction axis of a matmul weight (>= 0: the
    executor stores it in ``cfg.dtype``) or -1."""
    return leaf_tree(falcon_h1_init, cfg,
                     lambda name: _LEAF_QUANT.get(name, -1))


# ------------------------------------------------------------------ state


def falcon_h1_init_state(cfg: FalconH1Config, slots: int) -> dict:
    """What the family keeps beside the pool, zeroed: ``slots`` counts slot
    0 (the garbage sink of padding rows)."""
    return {
        "ssd": jnp.zeros((cfg.n_layer, slots, cfg.ssm_n_head,
                          cfg.ssm_head_dim, cfg.ssm_d_state), jnp.float32),
        "conv": jnp.zeros(
            (cfg.n_layer, slots, cfg.conv_kernel - 1, cfg.conv_width),
            cfg.dtype),
    }


def step_attrs(cfg: FalconH1Config, kind: str, rows: list) -> dict:
    """What a step's ``executor.dispatch`` span says of the state-space
    mixers (decode.py ``Family.step_attrs``; ``rows`` ``[(first position,
    tokens)]`` a request). A decode step: its ``rows`` and ``state_mb``, the
    megabytes of matrix state its layers move (each row's, once each way).
    A prefill step: its real ``tokens`` and the ``ssd_pieces`` of
    ``ssm_chunk`` tokens its rows are cut into."""
    if kind == "decode":
        return {"rows": len(rows), "state_mb": round(
            len(rows) * cfg.n_layer * cfg.ssm_n_head * cfg.ssm_head_dim
            * cfg.ssm_d_state * 4 * 2 / 1e6, 3)}
    return {"tokens": sum(n for _, n in rows),
            "ssd_pieces": sum(-(-n // cfg.ssm_chunk) for _, n in rows)}


# ----------------------------------------------------------------- layers


def _gated_norm(y, z, lp, cfg: FalconH1Config):
    """``RMSNorm(y * silu(z))`` over each GROUP's channels (``d_ssm / G``),
    one learned ``[d_ssm]`` weight: the gate FIRST (``mamba_norm_before_gate``
    false). The other reading of the norm: over all ``d_ssm`` channels."""
    G = cfg.ssm_n_group
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    g = g.reshape(*g.shape[:-1], G, -1)
    g = g * jax.lax.rsqrt(
        jnp.mean(jnp.square(g), axis=-1, keepdims=True) + cfg.norm_eps)
    return (g.reshape(*y.shape) * lp["ssm_norm"].astype(jnp.float32)).astype(
        cfg.dtype)


def _cached_embed(params, tokens, step, cfg: FalconH1Config):
    x = step.take(params["wte"].astype(cfg.dtype), tokens)
    x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x, rotary_tables(step.pos, cfg)


def _head(params, h, cfg: FalconH1Config):
    """[..., D] -> float32 logits through the untied head, times
    ``lm_head_multiplier``."""
    return head_untied(params, h, cfg) * cfg.lm_head_multiplier


def _open_state(state: dict, step, cfg: FalconH1Config) -> dict:
    """The step's working state: the two arrays of rows as the layers so
    far left them, and the ordinal of the next layer."""
    del step, cfg
    return {"ssd": state["ssd"], "conv": state["conv"], "layer": 0}


def _close_state(state: dict, work: dict, step, cfg: FalconH1Config):
    del state, step, cfg
    return {"ssd": work["ssd"], "conv": work["conv"]}


def _begins(step, rows):
    """``rows`` [B, ...] of a slot's state as a prefill step starts from
    them: zeros for a row whose chunk starts its sequence, whatever the
    slot held."""
    if step.kind == "fresh":
        return jnp.zeros_like(rows)
    keep = (step.start > 0).reshape((-1,) + (1,) * (rows.ndim - 1))
    return jnp.where(keep, rows, jnp.zeros_like(rows))


def _ssm_in(u, lp, cfg: FalconH1Config):
    """The in-projection of the layer's normed input: ``(z [.., d_ssm],
    xBC [.., conv_width])`` in ``cfg.dtype`` and ``dt`` [.., H] float32
    BEFORE its bias and softplus. The product comes out in float32: ``dt``
    enters an exponent summed over thousands of tokens."""
    S = cfg.d_ssm
    with jax.named_scope("ssd_proj"):
        p = jnp.einsum(
            "...d,df->...f", u * jnp.asarray(cfg.ssm_in_multiplier, u.dtype),
            lp["ssm_w_in"].astype(cfg.dtype),
            preferred_element_type=jnp.float32) * _mup(cfg)
        return (p[..., :S].astype(cfg.dtype),
                p[..., S:S + cfg.conv_width].astype(cfg.dtype),
                p[..., S + cfg.conv_width:])


def _ssm_mixer(z, xBC, dt, lp, step, work: dict, cfg: FalconH1Config):
    """The state-space branch behind its in-projection, on rows ``[B, ..]``
    (decode) or ``[B, S, ..]``: the convolution over the slot's rows, the
    recurrence over the slot's state, the gated norm. Returns (y [.., d_ssm]
    before ``W_out``, the working state with this layer's rows of both
    arrays updated)."""
    H, P, N, G = (cfg.ssm_n_head, cfg.ssm_head_dim, cfg.ssm_d_state,
                  cfg.ssm_n_group)
    states, conv = work["ssd"], work["conv"]
    li, slots = work["layer"], step.slots
    decode = step.kind == "decode"
    pallas = resolve_backend(cfg.attention_backend) == "pallas"
    with jax.named_scope("attn_cache"):  # the convolution's rows, read
        history = conv[li, slots]
    if decode:
        xBC, history = short_conv_decode(
            xBC, None, lp["ssm_conv_w"], history, act=jax.nn.silu,
            scope="ssd_conv", bias=lp["ssm_conv_b"])
    else:
        xBC, history = short_conv_prefill(
            xBC, None, lp["ssm_conv_w"], _begins(step, history), step.rows,
            act=jax.nn.silu, scope="ssd_conv", bias=lp["ssm_conv_b"])
    with jax.named_scope("attn_cache"):  # ... and written back
        conv = conv.at[li, slots].set(history.astype(conv.dtype))
    with jax.named_scope("ssd_conv"):
        lead = xBC.shape[:-1]
        xs = xBC[..., :cfg.d_ssm].reshape(*lead, H, P)
        Bm = xBC[..., cfg.d_ssm:cfg.d_ssm + G * N].reshape(*lead, G, N)
        Cm = xBC[..., cfg.d_ssm + G * N:].reshape(*lead, G, N)
        dt = jax.nn.softplus(dt + lp["ssm_dt_bias"])
        A = -jnp.exp(lp["ssm_a_log"])
    if decode and pallas:
        # the rows' states are updated where they stand
        y, states = ssd.ssd_step_pallas(
            xs, dt, A, Bm, Cm, lp["ssm_d"], states, li, slots)
    else:
        with jax.named_scope("attn_cache"):
            before = states[li, slots]
        if decode:
            y, after = ssd.ssd_step(xs, dt, A, Bm, Cm, lp["ssm_d"], before)
        else:
            y, after = ssd.ssd_chunk(
                xs, dt, A, Bm, Cm, lp["ssm_d"], _begins(step, before),
                step.valid, piece=cfg.ssm_chunk)
        with jax.named_scope("attn_cache"):
            states = states.at[li, slots].set(after)
    with jax.named_scope("ssd_out"):
        y = _gated_norm(y.reshape(*lead, cfg.d_ssm), z, lp, cfg)
    return y, {**work, "layer": li + 1, "ssd": states, "conv": conv}


def _qkv(u, lp, cos, sin, cfg: FalconH1Config):
    """Attention's projections and the rotary embedding. q [B, S, Hq, hd];
    k, v [B, S, Hkv, hd] (the compact GQA heads, as the cache stores
    them)."""
    B, S, _ = u.shape
    Hq, Hkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    dtype = cfg.dtype
    v_in = u * jnp.asarray(cfg.attention_in_multiplier, dtype)
    q = (v_in @ lp["wq"].astype(dtype)).reshape(B, S, Hq, hd)
    k = ((v_in @ lp["wk"].astype(dtype)) * jnp.asarray(
        cfg.key_multiplier, dtype)).reshape(B, S, Hkv, hd)
    v = (v_in @ lp["wv"].astype(dtype)).reshape(B, S, Hkv, hd)
    return rope(q, cos, sin), rope(k, cos, sin), v


def _ffn(x, lp, cfg: FalconH1Config):
    dtype = cfg.dtype
    gate_mul, down_mul = cfg.mlp_multipliers
    g = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    gate, up = jnp.split(g @ lp["mlp_in"].astype(dtype), 2, axis=-1)
    h = jax.nn.silu(gate * jnp.asarray(gate_mul, dtype)) * up
    return (h @ lp["mlp_out"].astype(dtype)) * jnp.asarray(down_mul, dtype)


def _cached_layer(x, lp, attend, step, work: dict, cfg: FalconH1Config):
    """One layer over the chunk: ONE norm, both mixers' projections of it,
    then the two sequence operators (each over what the row keeps: pages,
    a slot), their out-projections summed into the residual; the
    feed-forward half."""
    dtype = cfg.dtype
    decode = step.kind == "decode"
    with jax.named_scope("attn_proj"):
        u = rms_norm(x, lp["input_norm"], cfg.norm_eps)
        q, k, v = _qkv(u, lp, *step.aux, cfg)
        z, xBC, dt = _ssm_in(u[:, 0] if decode else u, lp, cfg)
        a = attend(q, k, v)
        y, work = _ssm_mixer(z, xBC, dt, lp, step, work, cfg)
        with jax.named_scope("ssd_proj"):
            m = (y @ lp["ssm_w_out"].astype(dtype)) * jnp.asarray(
                cfg.ssm_out_multiplier, dtype)
        a = (a @ lp["wo"].astype(dtype)) * jnp.asarray(
            cfg.attention_out_multiplier, dtype)
        x = x + (m[:, None] if decode else m) + a
    with jax.named_scope("ffn"):
        x = x + _ffn(x, lp, cfg)
    return x, work


FAMILY = cached.CachedFamily(
    "falcon_h1", FalconH1Config, "layers", _cached_embed, _cached_layer,
    final_norm, _head, open_state=_open_state, close_state=_close_state,
    no_verify="rejected drafts would need the SSM state (a matrix a head a "
              "sequence) and the convolution's rows rolled back",
    step_attrs=step_attrs, donated_state_counters=())
falcon_h1_prefill, falcon_h1_decode_step, _ = cached.steps(FAMILY)
