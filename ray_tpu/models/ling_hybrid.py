"""Ling-3.0 hybrid family (``bailing_hybrid``): delta-rule linear-attention
layers (KDA) that keep a matrix of state a head a sequence, one LATENT
attention layer in six, and one device's share of 512 small experts chosen
by groups, for serving.

Follows the public ``bailing_hybrid`` configuration (inclusionAI
Ling-3.0-flash ``config.json``), whose mixers are two published layers:
Kimi Delta Attention (Kimi Linear, arXiv:2510.26692 section 3;
ops/kda.py) and DeepSeek-V2's multi-head latent attention
(models/pangu_ultra_moe.py serves it; the absorbed and expanded forms are
models/parts.py's). With ``u = RMSNorm(x)`` a layer is ``h = x + Mixer(u)``,
``x' = h + FFN(RMSNorm(h))``: plain pre-norm residuals, no sandwich.

``kda`` (H heads of K = V = ``kda_head_dim``): ``[q~ | k~ | v~] = u [W_q |
W_k | W_v]``, a causal depthwise convolution of ``conv_kernel`` taps over
time on every channel and SiLU (ops/short_conv.py, the plain form); by
head ``q = q' / |q'| * K^-0.5``, ``k = k' / |k'|`` (the L2 norm, eps under
the root); ``log a = kda_lower_bound * sigmoid(exp(A_log[h]) * (u W_f +
dt_bias))`` a CHANNEL of the key (``_kda_gate``), ``beta = sigmoid(u
w_beta)`` a head; the delta rule over a float32 state ``[K, V]`` a head
(ops/kda.py); ``y = W_o(RMSNorm_head(o) * sigmoid(u W_g))``, the norm over
each head's V with one learned ``[V]`` weight. No positional encoding.

``latent``: as models/pangu_ultra_moe.py with what differs: no query
latent (``q = u W_q`` straight to H x (N + R)), INTERLEAVED rotary pairs
``(2i, 2i + 1)`` (``_rotate``), a sigmoid gate a head on the heads' outputs
(``y = W_o concat_h(o_h * sigmoid(u w_g)[h])``), no norm behind ``W_o``.

``ffn``: SwiGLU ``d_mlp`` on the first ``num_dense_layers`` layers, then
one shared expert beside ``moe_route`` with a stored selection bias and
GROUP-LIMITED selection (``n_group`` groups, the ``topk_group`` best by
the sum of their two largest biased scores stay, ``top_k`` among theirs;
weights from the unbiased sigmoid scores, normalised, times
``routed_scaling_factor``) over ``moe_dropless(held=experts_held)``. With
one group a device, a token whose kept groups leave this device's out
sends it nothing. ``swiglu_limits`` (a limit a layer; 0: no clamp) raises
on a nonzero one: the configuration does not say the clamp's form.

What the configuration leaves open (six entries) is listed in
benchmark/configs/ling-3.0-flash-ep8-7l.json ``assumed``, each with its
other reading; each is ONE function here (``_kda_gate``,
``_kda_projections``, ``_latent_gate``, ``_kda_positions``, ``_qk_norm``,
``_kda_out_norm``) and one in the reference.

Same conventions as models/pangu_ultra_moe.py (a LIST of per-layer trees,
float32 masters, activations in ``cfg.dtype``, ``experts_held``, the pool
IN PLANES spanning the latent layers only: ``n_kv_layer``) and
models/minicpm_sala.py (``state`` rows a slot, donated to the step
programs) at once: the first family with BOTH ``kv_planes`` and state
rows. ``state`` holds ``kda`` ``[n_kda, slots, H, K, V]`` float32 and
``conv`` ``[n_kda, slots, taps - 1, 3 H K]`` (the convolution's history;
slot 0 the garbage sink; a row whose chunk starts its sequence begins from
zeros in BOTH whatever the slot held), and the counters (``pairs``,
``routed``, ``reads`` as models/laguna.py's, and ``groups``: the tokens
routed and those of them whose kept groups hold this device's).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models import cached
from ray_tpu.models.laguna import laguna_counters
from ray_tpu.models.pangu_ultra_moe import QK_GAIN
from ray_tpu.models.parts import (
    cached_heads,
    close_experts,
    count_add,
    count_value,
    final_norm,
    head_untied,
    latent_step_attrs,
    leaf_tree,
    open_experts,
    rotary_at,
    swiglu,
)
from ray_tpu.ops import kda
from ray_tpu.ops.layers import rms_norm
from ray_tpu.ops.moe import (
    moe_dropless, moe_route_grouped, score_groups_bad, step_gmm_form)
from ray_tpu.ops.paged_attention import plane_width, resolve_backend
from ray_tpu.ops.short_conv import short_conv_decode, short_conv_prefill

LAYER_KINDS = ("kda", "latent")


@dataclass(frozen=True)
class LingHybridConfig:
    vocab_size: int = 157184        # rows of the vocabulary HELD here
    max_seq_len: int = 262144
    d_model: int = 2560
    # each layer's mixer, in order (``layer_group_size``: layer l of the
    # published 42 is latent iff (l + 1) % 6 == 0)
    layer_types: tuple[str, ...] = (
        "kda", "kda", "kda", "kda", "kda", "latent")
    kda_n_head: int = 32
    kda_head_dim: int = 128         # K = V
    conv_kernel: int = 4            # ``short_conv_kernel_size``
    kda_lower_bound: float = -5.0
    n_head: int = 32                # latent attention's heads
    kv_lora_rank: int = 512         # C: the latent row
    qk_nope_head_dim: int = 128     # N
    qk_rope_head_dim: int = 64      # R: the row's rotary rest
    v_head_dim: int = 128           # V
    num_dense_layers: int = 2       # ``first_k_dense_replace``
    d_mlp: int = 6144               # dense SwiGLU width
    num_experts: int = 512          # what the router scores
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    d_expert: int = 768
    d_shared: int = 768
    experts_held: tuple[int, int] | None = None  # (first, count); None: all
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    # a clamp a layer on the experts' and the shared expert's SwiGLU
    # (``expert_swiglu_limit_list`` / ``share_expert_swiglu_limit_list`` of
    # the layers held); None: zeros. A nonzero one is refused.
    swiglu_limits: tuple[tuple[float, float], ...] | None = None
    rope_theta: float = 6000000.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # decode attention backend / serving quantization: see models/gpt.py
    # GPTConfig. The engine refuses ``quantization`` for this family.
    attention_backend: str = "auto"
    quantization: str | None = None

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = sorted(set(self.layer_types) - set(LAYER_KINDS))
        if bad:
            raise ValueError(
                f"layer_types holds {bad}; this family has {LAYER_KINDS}")
        if self.experts_held is not None:
            object.__setattr__(
                self, "experts_held", tuple(int(n) for n in self.experts_held))
            first, count = self.experts_held
            if not (0 <= first and 0 < count
                    and first + count <= self.num_experts):
                raise ValueError(
                    f"experts_held {self.experts_held} is not a range of the "
                    f"{self.num_experts} experts")
        if not 0 <= self.num_dense_layers <= self.n_layer:
            raise ValueError("num_dense_layers exceeds the layer count")
        if score_groups_bad(self.num_experts, self.n_group, self.topk_group) \
                or self.top_k > self.topk_group * (
                    self.num_experts // self.n_group):
            raise ValueError(
                f"n_group {self.n_group} / topk_group {self.topk_group} do "
                f"not leave top_k {self.top_k} of {self.num_experts} experts "
                "to choose among")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")
        limits = self.swiglu_limits
        if limits is None:
            limits = ((0.0, 0.0),) * self.n_layer
        limits = tuple(tuple(float(x) for x in pair) for pair in limits)
        object.__setattr__(self, "swiglu_limits", limits)
        if len(limits) != self.n_layer:
            raise ValueError("swiglu_limits names a pair a layer")
        if any(x != 0.0 for pair in limits for x in pair):
            raise ValueError(
                f"swiglu_limits {limits} asks for a clamp on a SwiGLU, and "
                "the configuration does not say its form (on the gate, on "
                "both halves, before or after the activation): a layer with "
                "a nonzero limit is not served rather than guessed")

    @staticmethod
    def tiny(vocab_size: int = 512) -> "LingHybridConfig":
        return LingHybridConfig(
            vocab_size=vocab_size, max_seq_len=256, d_model=64,
            layer_types=("kda", "kda", "latent", "kda"), kda_n_head=4,
            kda_head_dim=16, n_head=4, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, num_dense_layers=1, d_mlp=128,
            num_experts=16, top_k=2, n_group=4, topk_group=2, d_expert=32,
            d_shared=32, rope_theta=10000.0,
        )

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def n_kv_layer(self) -> int:
        """Layers that cache a latent row: what the paged pool spans."""
        return self.layer_types.count("latent")

    @property
    def n_kda_layer(self) -> int:
        return self.layer_types.count("kda")

    @property
    def n_moe_layer(self) -> int:
        return self.n_layer - self.num_dense_layers

    @property
    def n_held(self) -> int:
        """Experts whose weights this device holds."""
        return (self.num_experts if self.experts_held is None
                else self.experts_held[1])

    @property
    def groups_held(self) -> tuple[int, ...]:
        """The routing groups that hold one of this device's experts."""
        size = self.num_experts // self.n_group
        first, count = self.experts_held or (0, self.num_experts)
        return tuple(range(first // size, (first + count - 1) // size + 1))

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @property
    def conv_width(self) -> int:
        """Channels the short convolution runs over: ``[q | k | v]``."""
        return 3 * self.kda_n_head * self.kda_head_dim

    @property
    def kv_planes(self) -> tuple[tuple[str, int, int], ...]:
        """What a latent layer caches of a token: ``(name, width, stored
        width)`` a part of its row (models/pangu_ultra_moe.py
        ``kv_planes``)."""
        return (
            ("latent", self.kv_lora_rank, plane_width(self.kv_lora_rank)),
            ("rope", self.qk_rope_head_dim,
             plane_width(self.qk_rope_head_dim)),
        )


def slot_state_bytes(cfg: LingHybridConfig) -> int:
    """Bytes a slot of ``state`` holds: a float32 matrix a head and the
    convolution's history, a KDA layer."""
    H, K = cfg.kda_n_head, cfg.kda_head_dim
    return cfg.n_kda_layer * (
        H * K * K * 4
        + (cfg.conv_kernel - 1) * cfg.conv_width * jnp.dtype(
            cfg.dtype).itemsize)


def ling_hybrid_init(key: jax.Array, cfg: LingHybridConfig) -> dict:
    """Float32 masters, normal from ``key``, each matmul leaf with std
    ``fan_in ** -0.5`` and the projections back into the residual stream a
    further ``(2 L) ** -0.5`` smaller (models/lfm2_moe.py ``lfm2_moe_init``
    and its reasons); the latent layers' ``W_q`` and key side times
    ``QK_GAIN`` (models/pangu_ultra_moe.py: scores of std 2.4, so that a
    row's output depends on WHICH rows it read). The router's selection
    bias is drawn with std 0.05, NOT zeros, so that "chosen by ``s + b``,
    weighted by ``s``" is exercised by every comparison. A KDA layer's
    ``A_log`` is ``log U(1, 4)`` a head and ``dt_bias`` ``N(-6, 1.5)`` a
    channel: under the bounded gate the channels' memories then spread
    from a few tokens to the whole context (``log a`` from about -0.5 to
    -1e-6), so that a state carried over thousands of tokens still moves
    the output and a state lost at a chunk's seam is noticed. The filter's
    taps have std ``taps ** -0.5``. Norm scales are ones."""
    D = cfg.d_model
    H, K = cfg.kda_n_head, cfg.kda_head_dim
    LH, C = cfg.n_head, cfg.kv_lora_rank
    N, R, V = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    E, F, M, Fs = cfg.n_held, cfg.d_expert, cfg.d_mlp, cfg.d_shared
    back = (2 * cfg.n_layer) ** -0.5

    def norm(key, *shape, fan_in, gain=1.0):
        return jax.random.normal(key, shape, jnp.float32) * (
            gain * fan_in ** -0.5)

    ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
    keys = jax.random.split(key, cfg.n_layer + 2)
    layers = []
    for i, kind in enumerate(cfg.layer_types):
        k = iter(jax.random.split(keys[i], 16))
        lp: dict = {"mixer_norm": ones(D), "ffn_norm": ones(D)}
        if kind == "kda":
            lp["kda_w_qkv"] = norm(next(k), D, 3 * H * K, fan_in=D)
            lp["kda_conv_w"] = norm(next(k), cfg.conv_kernel, 3 * H * K,
                                    fan_in=cfg.conv_kernel)
            lp["kda_w_f"] = norm(next(k), D, H * K, fan_in=D)
            lp["kda_a_log"] = jnp.log(jax.random.uniform(
                next(k), (H,), jnp.float32, 1.0, 4.0))
            lp["kda_dt_bias"] = -6.0 + 1.5 * jax.random.normal(
                next(k), (H * K,), jnp.float32)
            lp["kda_w_beta"] = norm(next(k), D, H, fan_in=D)
            lp["kda_w_g"] = norm(next(k), D, H * K, fan_in=D)
            lp["kda_out_norm"] = ones(K)
            lp["kda_w_o"] = norm(next(k), H * K, D, fan_in=H * K, gain=back)
        else:
            w_dkv = norm(next(k), D, C + R, fan_in=D)
            lp["mla_w_q"] = norm(next(k), D, LH * (N + R), fan_in=D,
                                 gain=QK_GAIN)
            lp["mla_w_dkv"] = w_dkv.at[:, C:].multiply(QK_GAIN)
            lp["mla_kv_norm"] = ones(C)
            lp["mla_w_uk"] = norm(next(k), C, LH * N, fan_in=C, gain=QK_GAIN)
            lp["mla_w_uv"] = norm(next(k), C, LH * V, fan_in=C)
            lp["mla_w_g"] = norm(next(k), D, LH, fan_in=D)
            lp["mla_w_o"] = norm(next(k), LH * V, D, fan_in=LH * V, gain=back)
        if i < cfg.num_dense_layers:
            lp["mlp_in"] = norm(next(k), D, 2 * M, fan_in=D)  # gate, up
            lp["mlp_out"] = norm(next(k), M, D, fan_in=M, gain=back)
        else:
            lp["moe_route_w"] = norm(next(k), D, cfg.num_experts, fan_in=D)
            lp["moe_route_bias"] = 0.05 * jax.random.normal(
                next(k), (cfg.num_experts,), jnp.float32)
            lp["moe_gmm_w_in"] = norm(next(k), E, D, 2 * F, fan_in=D)
            lp["moe_gmm_w_out"] = norm(next(k), E, F, D, fan_in=F, gain=back)
            lp["moe_shared_w_in"] = norm(next(k), D, 2 * Fs, fan_in=D)
            lp["moe_shared_w_out"] = norm(next(k), Fs, D, fan_in=Fs,
                                          gain=back)
        layers.append(lp)
    return {
        "wte": norm(keys[-2], cfg.vocab_size, D, fan_in=D),
        "layers": layers,
        "ln_f_scale": jnp.ones((D,), jnp.float32),
        "lm_head": norm(keys[-1], D, cfg.vocab_size, fan_in=D),
    }


_LEAF_AXES = {
    "mixer_norm": ("embed",), "ffn_norm": ("embed",),
    "kda_w_qkv": ("embed", "mlp"), "kda_conv_w": (None, "mlp"),
    "kda_w_f": ("embed", "mlp"), "kda_a_log": (None,),
    "kda_dt_bias": (None,), "kda_w_beta": ("embed", None),
    "kda_w_g": ("embed", "mlp"), "kda_out_norm": (None,),
    "kda_w_o": ("mlp", "embed"),
    "mla_w_q": ("embed", "mlp"), "mla_w_dkv": ("embed", None),
    "mla_kv_norm": (None,), "mla_w_uk": (None, "mlp"),
    "mla_w_uv": (None, "mlp"), "mla_w_g": ("embed", None),
    "mla_w_o": ("mlp", "embed"),
    "mlp_in": ("embed", "mlp"), "mlp_out": ("mlp", "embed"),
    "moe_route_w": (None, None), "moe_route_bias": (None,),
    "moe_gmm_w_in": ("expert", None, "mlp"),
    "moe_gmm_w_out": ("expert", "mlp", None),
    "moe_shared_w_in": ("embed", "mlp"), "moe_shared_w_out": ("mlp", "embed"),
    "wte": ("vocab", "embed"), "ln_f_scale": ("embed",),
    "lm_head": ("embed", "vocab"),
}
# the contraction axis of each matmul weight; -1: kept as given (norm
# scales, the filter, the gates' small leaves, and the router, which is
# read in float32)
_LEAF_QUANT = {
    "kda_w_qkv": 0, "kda_w_f": 0, "kda_w_g": 0, "kda_w_o": 0,
    "mla_w_q": 0, "mla_w_dkv": 0, "mla_w_uk": 0, "mla_w_uv": 0,
    "mla_w_o": 0, "mlp_in": 0, "mlp_out": 0,
    "moe_gmm_w_in": 1, "moe_gmm_w_out": 1,
    "moe_shared_w_in": 0, "moe_shared_w_out": 0, "wte": 1, "lm_head": 0,
}


def ling_hybrid_param_axes(cfg: LingHybridConfig) -> dict:
    """Logical axis names per leaf; the experts get an axis of their own."""
    return leaf_tree(ling_hybrid_init, cfg, _LEAF_AXES.__getitem__)


def ling_hybrid_quant_axes(cfg: LingHybridConfig) -> dict:
    """Per leaf, the contraction axis of a matmul weight (>= 0: the
    executor stores it in ``cfg.dtype``, experts included) or -1."""
    return leaf_tree(ling_hybrid_init, cfg,
                     lambda name: _LEAF_QUANT.get(name, -1))


# ------------------------------------------------------------------ state

# counters[which]: (low, high) uint32 words
COUNTER_LEAVES = ("pairs", "routed", "reads", "groups")


def ling_hybrid_init_state(cfg: LingHybridConfig, slots: int) -> dict:
    """What the family keeps beside the pool, zeroed: ``slots`` counts slot
    0 (the garbage sink of padding rows)."""
    H, K = cfg.kda_n_head, cfg.kda_head_dim
    return {
        "kda": jnp.zeros((cfg.n_kda_layer, slots, H, K, K), jnp.float32),
        "conv": jnp.zeros(
            (cfg.n_kda_layer, slots, cfg.conv_kernel - 1, cfg.conv_width),
            cfg.dtype),
        "pairs": jnp.zeros((2, cfg.n_held, 2), jnp.uint32),
        "routed": jnp.zeros((2, 2), jnp.uint32),
        "reads": jnp.zeros((2,), jnp.uint32),
        "groups": jnp.zeros((2, 2, 2), jnp.uint32),
    }


def ling_hybrid_counters(state: dict) -> dict:
    """``state``'s counters as plain integers (a device->host read): those
    of models/laguna.py, and ``moe_tokens_routed`` (the (token, expert
    layer)s routed) with ``moe_groups_held`` (those of them whose kept
    groups hold one of this device's)."""
    groups = count_value(state["groups"])  # [2, 2]: kind x (routed, held)
    return {**laguna_counters(state),
            "moe_tokens_routed": int(groups[:, 0].sum()),
            "moe_groups_held": int(groups[:, 1].sum())}


def step_attrs(cfg: LingHybridConfig, kind: str, rows: list) -> dict:
    """What a step's ``executor.dispatch`` span says of the family's
    mixers (decode.py ``Family.step_attrs``; ``rows`` ``[(first position,
    tokens)]`` a request). A decode step: its ``rows`` and ``state_mb``,
    the megabytes of matrix state its KDA layers move (each row's, once
    each way). A prefill step: its real ``tokens``, the ``kda_pieces`` of
    ``ops.kda.PIECE`` tokens its rows are cut into, and the latent layer's
    ``expanded_pairs`` / ``prefix_blocks`` as models/pangu_ultra_moe.py's."""
    H, K = cfg.kda_n_head, cfg.kda_head_dim
    latent = latent_step_attrs(cfg, kind, rows)
    if kind == "decode":
        return {"rows": len(rows), **latent,
                "state_mb": round(
                    len(rows) * cfg.n_kda_layer * H * K * K * 4 * 2 / 1e6, 3)}
    return {"tokens": sum(n for _, n in rows),
            "kda_pieces": sum(-(-n // kda.PIECE) for _, n in rows), **latent}


# ----------------------------------------------------------------- layers
# The six readings the configuration leaves open, one function each
# (benchmark/configs/ling-3.0-flash-ep8-7l.json ``assumed``).


def _kda_gate(f, lp, cfg: LingHybridConfig):
    """``log a`` [.., H, K] float32 in ``(kda_lower_bound, 0)`` from ``f =
    u W_f`` [.., H * K]: the BOUNDED gate (``kda_safe_gate``). The other
    reading: Kimi Linear's ``-exp(A_log) softplus(f + dt_bias)``."""
    H, K = cfg.kda_n_head, cfg.kda_head_dim
    x = (f.astype(jnp.float32) + lp["kda_dt_bias"]).reshape(
        *f.shape[:-1], H, K)
    return cfg.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(lp["kda_a_log"])[:, None] * x)


def _kda_projections(u, lp, cfg: LingHybridConfig):
    """``(f, g)`` [.., H * K] each: ``W_f`` and ``W_g`` at FULL rank
    (``no_kda_lora``). The other reading: low rank through ``head_dim``.
    ``f`` comes out in float32: it enters an exponent (``_kda_gate``), where
    a bfloat16's step at ``|f + dt_bias|`` ~ 6 (0.03) times ``exp(A_log)``
    would be 12% of a slow channel's decay rate."""
    f = jnp.einsum("...d,df->...f", u, lp["kda_w_f"].astype(cfg.dtype),
                   preferred_element_type=jnp.float32)
    return f, u @ lp["kda_w_g"].astype(cfg.dtype)


def _kda_positions(q, k, step):
    """No positional encoding in a KDA layer. The other reading: rotary
    over the first ``rotary_dim`` of each head (``partial_rotary_factor``)."""
    del step
    return q, k


def _qk_norm(x, scale: float, eps: float):
    """KDA's L2 norm a head (``use_qk_norm``), float32: ``x / sqrt(sum x^2
    + eps) * scale``. The latent layers' keys get NO norm a head (a norm on
    ``k_nope`` would make ``k_h`` non-linear in ``c``, and the absorbed
    form would not exist)."""
    x = x.astype(jnp.float32)
    return x * (jax.lax.rsqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + eps) * scale)


def _kda_out_norm(o, lp, cfg: LingHybridConfig):
    """RMSNorm over EACH head's V (``group_norm_size 1``), one ``[V]``
    weight. The other reading: over the heads joined."""
    return rms_norm(o, lp["kda_out_norm"], cfg.norm_eps)


def _latent_gate(u, lp):
    """The latent layers' gate, one number a HEAD [.., H] float32
    (``gated_attention_proj_granularity_type head_wise``). The other
    reading: the key names KDA's output gate."""
    return jax.nn.sigmoid(jnp.einsum(
        "...d,dh->...h", u.astype(jnp.float32),
        lp["mla_w_g"].astype(jnp.float32)))


def _rotate(x, cos, sin):
    """The rotary embedding of x ``[B, S, heads, R]`` over INTERLEAVED
    pairs ``(2i, 2i + 1)`` (``rope_interleave``)."""
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.stack([a * c - b * s, a * s + b * c], axis=-1).reshape(
        x.shape).astype(x.dtype)


def _cached_embed(params, tokens, step, cfg: LingHybridConfig):
    x = step.take(params["wte"].astype(cfg.dtype), tokens)
    return x, rotary_at(step.pos, cfg)


def _open_state(state: dict, step, cfg: LingHybridConfig) -> dict:
    """The step's working state: the two arrays of rows as the layers so
    far left them, the ordinals of the next layer and the next KDA layer,
    each expert layer's held pairs and held-group tokens, and the mask of
    the tokens that are routed."""
    return {**open_experts(state, step, cfg), "kda": state["kda"],
            "conv": state["conv"], "kda_done": 0, "groups": []}


def _begins(step, rows):
    """``rows`` [B, ...] of a slot's state as a prefill step starts from
    them: zeros for a row whose chunk starts its sequence, whatever the
    slot held."""
    if step.kind == "fresh":
        return jnp.zeros_like(rows)
    keep = (step.start > 0).reshape((-1,) + (1,) * (rows.ndim - 1))
    return jnp.where(keep, rows, jnp.zeros_like(rows))


def _kda_mixer(u, lp, step, work: dict, cfg: LingHybridConfig):
    """``Mixer(u)`` of a KDA layer on u [B, S, D] and the working state
    with this layer's rows of both arrays updated."""
    B, S, _ = u.shape
    H, K = cfg.kda_n_head, cfg.kda_head_dim
    dtype = cfg.dtype
    states, conv = work["kda"], work["conv"]
    li, slots = work["kda_done"], step.slots
    decode = step.kind == "decode"
    pallas = resolve_backend(cfg.attention_backend) == "pallas"
    x = u[:, 0] if decode else u
    qkv = x @ lp["kda_w_qkv"].astype(dtype)
    f, g = _kda_projections(x, lp, cfg)
    beta_in = jnp.einsum("...d,dh->...h", x.astype(jnp.float32),
                         lp["kda_w_beta"].astype(jnp.float32))
    with jax.named_scope("attn_cache"):  # the convolution's rows, read
        history = conv[li, slots]
    if decode:
        qkv, history = short_conv_decode(
            qkv, None, lp["kda_conv_w"], history, act=jax.nn.silu,
            scope="kda_conv")
    else:
        qkv, history = short_conv_prefill(
            qkv, None, lp["kda_conv_w"], _begins(step, history), step.rows,
            act=jax.nn.silu, scope="kda_conv")
    with jax.named_scope("attn_cache"):  # ... and written back
        conv = conv.at[li, slots].set(history.astype(conv.dtype))
    with jax.named_scope("kda_conv"):
        q, k, v = (a.reshape(*a.shape[:-1], H, K)
                   for a in jnp.split(qkv, 3, axis=-1))
        q = _qk_norm(q, K ** -0.5, cfg.norm_eps).astype(dtype)
        k = _qk_norm(k, 1.0, cfg.norm_eps).astype(dtype)
        q, k = _kda_positions(q, k, step)
    with jax.named_scope("kda_gate"):
        log_a = _kda_gate(f, lp, cfg)
        beta = jax.nn.sigmoid(beta_in)
    if decode and pallas:
        # the rows' states are updated where they stand
        o, states = kda.kda_step_pallas(q, k, v, log_a, beta, states, li,
                                        slots)
    else:
        with jax.named_scope("attn_cache"):
            before = states[li, slots]
        if decode:
            o, after = kda.kda_step(q, k, v, log_a, beta, before)
        else:
            o, after = kda.kda_chunk(q, k, v, log_a, beta,
                                     _begins(step, before), step.valid)
        with jax.named_scope("attn_cache"):
            states = states.at[li, slots].set(after)
    with jax.named_scope("kda_out"):
        o = _kda_out_norm(o, lp, cfg).reshape(*o.shape[:-2], H * K)
        o = o * jax.nn.sigmoid(g.astype(jnp.float32)).astype(dtype)
    y = o @ lp["kda_w_o"].astype(dtype)
    work = {**work, "kda_done": li + 1, "kda": states, "conv": conv}
    return (y[:, None] if decode else y), work


def _latent_mixer(u, lp, attend, step, cfg: LingHybridConfig):
    """``Mixer(u)`` of a latent layer: the projections, the row written
    and attended through the cache in the form the kind of step wants
    (models/parts.py ``cached_heads``), the gate a head."""
    B, S, _ = u.shape
    H, N, R, C, V = (cfg.n_head, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.kv_lora_rank, cfg.v_head_dim)
    cos, sin = step.aux
    q = (u @ lp["mla_w_q"].astype(cfg.dtype)).reshape(B, S, H, N + R)
    kv = u @ lp["mla_w_dkv"].astype(cfg.dtype)
    c = rms_norm(kv[..., :C], lp["mla_kv_norm"], cfg.norm_eps)
    k_r = _rotate(kv[..., None, C:], cos, sin)[:, :, 0]
    heads = cached_heads(q[..., :N], _rotate(q[..., N:], cos, sin), c, k_r,
                         lp, attend, step, cfg)
    gate = _latent_gate(u, lp).astype(cfg.dtype)            # [B, S, H]
    heads = (heads.reshape(B, S, H, V) * gate[..., None]).reshape(B, S, -1)
    return heads @ lp["mla_w_o"].astype(cfg.dtype)


def _ffn(x, lp, cfg: LingHybridConfig, valid):
    """``ffn(RMSNorm(x))`` on x [B, S, D]: a SwiGLU, or the shared expert +
    the held routed experts under the grouped route. ``valid`` [B, S]
    marks the real tokens. Returns (the sub-layer's output, the held
    experts' pairs by expert [held] int32, the count of real tokens whose
    kept groups hold one of this device's), the last two None for a dense
    layer."""
    B, S, D = x.shape
    z = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    if "mlp_in" in lp:
        return swiglu(z, lp["mlp_in"], lp["mlp_out"], cfg.dtype), None, None
    flat = z.reshape(B * S, D)
    weights, experts, stays = moe_route_grouped(
        flat, lp["moe_route_w"], lp["moe_route_bias"], cfg.top_k,
        (cfg.n_group, cfg.topk_group), norm_topk=cfg.norm_topk_prob,
        scale=cfg.routed_scaling_factor)
    y, sizes = moe_dropless(
        flat, weights, experts, lp["moe_gmm_w_in"], lp["moe_gmm_w_out"],
        dtype=cfg.dtype, valid=valid.reshape(B * S), held=cfg.experts_held)
    with jax.named_scope("counters"):
        mine = jnp.any(stays[:, jnp.asarray(cfg.groups_held)], axis=-1)
        met = jnp.sum(mine & valid.reshape(B * S))
    with jax.named_scope("moe_shared"):
        shared = swiglu(z, lp["moe_shared_w_in"], lp["moe_shared_w_out"],
                        cfg.dtype)
    return shared + y.reshape(B, S, D), sizes, met


def _cached_layer(x, lp, attend, step, work: dict, cfg: LingHybridConfig):
    with jax.named_scope("attn_proj"):
        u = rms_norm(x, lp["mixer_norm"], cfg.norm_eps)
        if "kda_w_qkv" in lp:
            y, work = _kda_mixer(u, lp, step, work, cfg)
        else:
            y = _latent_mixer(u, lp, attend, step, cfg)
        x = x + y
    with jax.named_scope("ffn"):
        out, sizes, met = _ffn(x, lp, cfg, work["routed"])
        x = x + out
    work = {**work, "layer": work["layer"] + 1}
    if sizes is not None:
        work["sizes"] = [*work["sizes"], sizes]
        work["groups"] = [*work["groups"], met]
    return x, work


def _close_state(state: dict, work: dict, step, cfg: LingHybridConfig):
    """The next ``state``: the rows as the step left them, and the step's
    routed tokens, held pairs and held-group tokens added to the counters."""
    out = {**close_experts(state, work, step, cfg),
           "kda": work["kda"], "conv": work["conv"]}
    if work["sizes"]:
        kind = int(step.kind == "decode")
        tokens = jnp.sum(work["routed"]) * len(work["sizes"])
        out["groups"] = state["groups"].at[kind].set(count_add(
            state["groups"][kind], jnp.stack([tokens, sum(work["groups"])])))
    return out


FAMILY = cached.CachedFamily(
    "ling_hybrid", LingHybridConfig, "layers", _cached_embed, _cached_layer,
    final_norm, head_untied, open_state=_open_state,
    close_state=_close_state,
    no_verify="rejected drafts would need the KDA state (a matrix a head a "
              "sequence, and the convolution's rows) rolled back; the "
              "prediction module that would draft is not held",
    step_attrs=step_attrs, gmm_form=step_gmm_form,
    donated_state_counters=COUNTER_LEAVES)
ling_hybrid_prefill, ling_hybrid_decode_step, _ = cached.steps(FAMILY)
