"""SDAR-MoE family: a decoder of like layers (grouped-query attention with a
norm over each head, routed SwiGLU experts) that GENERATES by diffusion over
blocks: an answer grows a block of ``B = block_length`` tokens at a time,
each block denoised from all-MASK in a few passes of the same forward, for
serving.

Follows the public ``sdar_moe`` configuration (JetLM SDAR-30B-A3B-Chat's
``config.json``). Layer ``l``, stream ``x``::

    h = RMSNorm(x)
    q = h Wq [Hq, hd]   k = h Wk [Hkv, hd]   v = h Wv [Hkv, hd]   (no bias)
    q = RMSNorm_hd(q; q_norm)   k = RMSNorm_hd(k; k_norm)       (``qk_norm``)
    q, k = rotary(q, k, pos), all hd dimensions, rotate-half, theta
    x' = x + softmax_j(q_i k_j / sqrt(hd)) v Wo  over j: block(j) <= block(i)
    g = RMSNorm(x')
    p = softmax(g W_r) over all E; the k largest, renormalised over them
    out = x' + sum_e p_e (silu(g Wgate_e) * (g Wup_e)) Wdown_e

then ``RMSNorm`` and an untied head. ``block(p) = p // B``: attention is FULL
inside a block and causal from block to block, prompt and answer alike
(``sees``). That mask is all that "diffusion over blocks" does to the forward
pass; the rest is the decoding loop (serve/llm/engine.py
``_decode_blocks_locked``; docs/SERVING_LLM.md "Block diffusion"):

    prefill the whole prompt blocks; emit nothing
    a block's ids X: the prompt's tail (first block only), MASK elsewhere
      a PASS: logits of the block's B positions given the committed K/V and
        X; ``fill`` still-masked positions get their argmax (the logits AT
        a masked position choose THAT position's token: ``logit_position``)
      when no position is masked: one more pass over X leaves the block's
        K/V in the cache for good (the COMMIT); its tokens go to the client

A pass rewrites the K/V rows of what it carries past the committed
frontier, whatever its ids: they are provisional until a pass has run over
the block's FINISHED ids. That pass is not one of its own where another
block is due: the finished block FOLDS into the next block's first pass, a
row carrying ``[finished block | next block, all MASK]``, 2B positions
under ``sees`` (the finished block's queries see the cache and their
block, the fresh block's see both: one pass computes what a commit and a
first denoising pass compute, from the same inputs). Only a request's
last block is committed by a pass of its own that chooses nothing. The
family has ONE decode program, the row's block ``[rows, B + 1]`` in and
out (ids and masked bits), traced ``[rows, 2B]``; each row's phase is data
(models/cached.py ``_block_step``: a row folds where no bit is set and
its schedule fills on).

Which positions a pass fills (``remasking``; ops/sampling.py
``unmask_tokens``): ``sequential`` the first ``n`` masked, left to right;
``low_confidence_static`` the ``n`` whose largest softmax probability is
highest; ``low_confidence_dynamic`` every one whose largest probability
passes ``confidence_threshold`` (the configuration's, a constant of the
program), at least ``n``. ``n`` a pass is ops/sampling.py ``fill_counts``:
``B // T`` for ``T = denoising_steps``, a remainder to the first passes.
Whether a position is masked is the caller's knowledge of its schedule (a
bit a position), never a comparison of ids with ``mask_token_id``
(``masked_is_positional``: the id may be drawn as a token).

What the configuration does not give and this file reads by convention is
listed in benchmark/configs/sdar-30b-a3b-chat-6l.json ``assumed``, each
reading ONE function here and one in benchmark/reference/sdar_moe.py
(``qk_norm``, ``sees``, ``fill_counts``, ``logit_position``).

Same conventions as models/smallthinker.py (a LIST of per-layer trees,
float32 masters, activations in ``cfg.dtype``, the cached step of
models/cached.py, ``state`` the expert layers' counters alone), with
``CachedFamily(block_steps=True)``: a prompt chunk and a block pass are the
chunk step under the block mask; a pass runs the head on the ``B``
positions of a row that choose (a folding row's fresh block, else the
row's block).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models import cached
from ray_tpu.models.lfm2_moe import lfm2_moe_counters as sdar_moe_counters
from ray_tpu.models.parts import (
    count_pairs,
    final_norm,
    head_untied,
    leaf_tree,
    open_experts,
    rotary_tables,
)
from ray_tpu.ops.layers import rms_norm, rope_partial
from ray_tpu.ops.moe import moe_dropless, moe_route, step_gmm_form
from ray_tpu.ops.sampling import REMASKING

# the two head norms' scales at init: the scores' std is then 2 on every
# layer, as laguna's and smallthinker's ``wq`` / ``wk`` gain makes it and
# for their reason (a softmax over thousands of keys at std 1 is nearly
# flat, and a wrong mask would pass the reference check)
QK_GAIN = 1.4


@dataclass(frozen=True)
class SdarMoeConfig:
    vocab_size: int = 151936
    max_seq_len: int = 32768
    d_model: int = 2048
    n_head: int = 32
    n_kv_head: int = 4
    head_dim: int = 128
    n_layer: int = 48
    num_experts: int = 128
    top_k: int = 8
    d_expert: int = 768             # each expert's gated width
    norm_topk_prob: bool = True
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # ---- generation by diffusion over blocks (the family's routine) ----
    block_length: int = 4
    # passes that fill a block; None: as many as the block is long
    denoising_steps: int | None = None
    remasking: str = "low_confidence_dynamic"
    # the largest probability past which ``low_confidence_dynamic`` fills a
    # position early
    confidence_threshold: float = 0.9
    mask_token_id: int = 151669
    # decode attention backend / serving quantization: see models/gpt.py
    # GPTConfig. The engine refuses ``quantization`` for this family.
    attention_backend: str = "auto"
    quantization: str | None = None

    def __post_init__(self):
        if self.n_head % self.n_kv_head:
            raise ValueError("query heads must be a multiple of n_kv_head")
        if self.top_k > self.num_experts:
            raise ValueError("top_k exceeds num_experts")
        if not 1 <= self.block_length <= 30:
            raise ValueError(
                "block_length must be 1..30 (a block's masked positions "
                "ride one int32 word)")
        if self.remasking not in REMASKING:
            raise ValueError(
                f"remasking must be one of {REMASKING}, got "
                f"{self.remasking!r}")
        if self.denoising_steps is not None and self.denoising_steps < 1:
            raise ValueError("denoising_steps must be >= 1")
        if not 0.0 <= self.confidence_threshold <= 1.0:
            raise ValueError("confidence_threshold is a probability, 0..1")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError("mask_token_id lies outside the vocabulary")

    @staticmethod
    def tiny(vocab_size: int = 512) -> "SdarMoeConfig":
        return SdarMoeConfig(
            vocab_size=vocab_size, max_seq_len=128, d_model=64, n_head=8,
            n_kv_head=2, head_dim=16, n_layer=3, num_experts=8, top_k=3,
            d_expert=32, rope_theta=10000.0, mask_token_id=vocab_size - 3,
        )


def sdar_moe_init(key: jax.Array, cfg: SdarMoeConfig) -> dict:
    """Float32 masters, normal from ``key``, each matmul leaf with std
    ``fan_in ** -0.5`` and the projections back into the residual stream a
    further ``(2 L) ** -0.5`` smaller (models/lfm2_moe.py ``lfm2_moe_init``
    and its reasons). Norm scales are ones, but the two head norms'
    ``QK_GAIN``."""
    D, hd, Hq, Hkv = cfg.d_model, cfg.head_dim, cfg.n_head, cfg.n_kv_head
    E, F = cfg.num_experts, cfg.d_expert
    back = (2 * cfg.n_layer) ** -0.5

    def norm(key, *shape, fan_in, gain=1.0):
        return jax.random.normal(key, shape, jnp.float32) * (
            gain * fan_in ** -0.5)

    keys = jax.random.split(key, cfg.n_layer + 2)
    layers = []
    for i in range(cfg.n_layer):
        k = iter(jax.random.split(keys[i], 8))
        layers.append({
            "attn_norm": jnp.ones((D,), jnp.float32),
            "ffn_norm": jnp.ones((D,), jnp.float32),
            "q_norm": jnp.full((hd,), QK_GAIN, jnp.float32),
            "k_norm": jnp.full((hd,), QK_GAIN, jnp.float32),
            "wq": norm(next(k), D, Hq * hd, fan_in=D),
            "wk": norm(next(k), D, Hkv * hd, fan_in=D),
            "wv": norm(next(k), D, Hkv * hd, fan_in=D),
            "wo": norm(next(k), Hq * hd, D, fan_in=Hq * hd, gain=back),
            "moe_route_w": norm(next(k), D, E, fan_in=D),
            "moe_gmm_w_in": norm(next(k), E, D, 2 * F, fan_in=D),  # gate, up
            "moe_gmm_w_out": norm(next(k), E, F, D, fan_in=F, gain=back),
        })
    return {
        "wte": norm(keys[-2], cfg.vocab_size, D, fan_in=D),
        "layers": layers,
        "ln_f_scale": jnp.ones((D,), jnp.float32),
        "lm_head": norm(keys[-1], D, cfg.vocab_size, fan_in=D),
    }


_LEAF_AXES = {
    "attn_norm": ("embed",), "ffn_norm": ("embed",),
    "q_norm": (None,), "k_norm": (None,),
    "wq": ("embed", "mlp"), "wk": ("embed", "mlp"), "wv": ("embed", "mlp"),
    "wo": ("mlp", "embed"), "moe_route_w": (None, None),
    "moe_gmm_w_in": ("expert", None, "mlp"),
    "moe_gmm_w_out": ("expert", "mlp", None),
    "wte": ("vocab", "embed"), "ln_f_scale": ("embed",),
    "lm_head": ("embed", "vocab"),
}
# the contraction axis of each matmul weight; -1: kept as given (norm
# scales, and the router, which is read in float32)
_LEAF_QUANT = {"wq": 0, "wk": 0, "wv": 0, "wo": 0,
               "moe_gmm_w_in": 1, "moe_gmm_w_out": 1, "wte": 1, "lm_head": 0}


def sdar_moe_param_axes(cfg: SdarMoeConfig) -> dict:
    """Logical axis names per leaf; the experts get an axis of their own."""
    return leaf_tree(sdar_moe_init, cfg, _LEAF_AXES.__getitem__)


def sdar_moe_quant_axes(cfg: SdarMoeConfig) -> dict:
    """Per leaf, the contraction axis of a matmul weight (>= 0: the
    executor stores it in ``cfg.dtype``, experts included) or -1."""
    return leaf_tree(sdar_moe_init, cfg,
                     lambda name: _LEAF_QUANT.get(name, -1))


# ------------------------------------------------------------------ state


def sdar_moe_init_state(cfg: SdarMoeConfig, slots: int) -> dict:
    """The counters the step programs keep (no per-sequence rows: ``slots``
    only says which rows are padding, slot 0): models/lfm2_moe.py's
    (``sdar_moe_counters`` reads them)."""
    del slots
    return {"pairs": jnp.zeros((2, cfg.num_experts, 2), jnp.uint32),
            "reads": jnp.zeros((2,), jnp.uint32)}


# ----------------------------------------------------------------- layers


def qk_norm(x, scale, cfg: SdarMoeConfig):
    """The norm over each head of q and of k, before the rotary embedding
    (assumed: no key of the configuration names it; ``sdar_moe`` descends
    from a family whose attention has it. The other reading: none; a
    correction is this function and the reference's)."""
    return rms_norm(x, scale, cfg.norm_eps)


def sees(pos, t, cfg: SdarMoeConfig):
    """Whether the query at ``pos`` sees the key at ``t``: every position
    up to the END of its own block (full inside a block, causal from block
    to block; ``prompt_mask``: a prompt's tokens under the same rule)."""
    return t // cfg.block_length <= pos // cfg.block_length


def _qkv(h, lp, tables, cfg: SdarMoeConfig):
    """Projections, the norm a head, the rotary embedding. q [B, S, Hq,
    hd]; k, v [B, S, Hkv, hd] (the compact GQA heads, as the cache stores
    them)."""
    B, S, _ = h.shape
    Hq, Hkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    q = (h @ lp["wq"].astype(cfg.dtype)).reshape(B, S, Hq, hd)
    k = (h @ lp["wk"].astype(cfg.dtype)).reshape(B, S, Hkv, hd)
    v = (h @ lp["wv"].astype(cfg.dtype)).reshape(B, S, Hkv, hd)
    q = rope_partial(qk_norm(q, lp["q_norm"], cfg), *tables)
    k = rope_partial(qk_norm(k, lp["k_norm"], cfg), *tables)
    return q, k, v


def _experts(x, lp, cfg: SdarMoeConfig, valid):
    """RMSNorm + the routed SwiGLU experts + residual on x [B, S, D].
    ``valid`` [B, S] marks the real tokens. Returns (x', routed pairs by
    expert [E])."""
    B, S, D = x.shape
    g = rms_norm(x, lp["ffn_norm"], cfg.norm_eps).reshape(B * S, D)
    route = moe_route(g, lp["moe_route_w"], None, cfg.top_k,
                      norm_topk=cfg.norm_topk_prob, score="softmax_topk")
    y, sizes = moe_dropless(
        g, *route, lp["moe_gmm_w_in"], lp["moe_gmm_w_out"], dtype=cfg.dtype,
        valid=valid.reshape(B * S))
    return x + y.reshape(B, S, D), sizes


def _block_attention(q, k, v, cfg: SdarMoeConfig):
    """Plain attention over a whole sequence under the block mask, q [B,
    S, Hq, hd], GQA by regrouping the queries: [B, S, Hq * hd]."""
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, S, Hkv, Hq // Hkv, hd)
    s = jnp.einsum("bshgd,bthd->bhgst", qg, k,
                   preferred_element_type=jnp.float32) * hd ** -0.5
    t = jnp.arange(S)
    mask = sees(t[:, None], t[None, :], cfg)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1).astype(q.dtype)
    return jnp.einsum("bhgst,bthd->bshgd", p, v).reshape(B, S, Hq * hd)


def sdar_moe_forward(params: dict, tokens: jax.Array,
                     cfg: SdarMoeConfig) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32: the whole sequence at
    once under the block mask, no cache (the program's own full forward)."""
    B, S = tokens.shape
    x = params["wte"].astype(cfg.dtype)[tokens]
    tables = rotary_tables(
        jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S)), cfg)
    valid = jnp.ones((B, S), bool)
    for lp in params["layers"]:
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(h, lp, tables, cfg)
        x = x + _block_attention(q, k, v, cfg) @ lp["wo"].astype(cfg.dtype)
        x, _ = _experts(x, lp, cfg, valid)
    return head_untied(params, final_norm(params, x, cfg), cfg)


# ----------------------------------------------------------------------------
# Cached inference paths (serve/llm engine): what models/cached.py's block
# steps need of this family. The pool is lane-dense [n_layer, num_blocks,
# block_size, n_kv_head * head_dim] under ONE table. Rows in slot 0 are
# padding: routed nowhere, counted nowhere.
# ----------------------------------------------------------------------------


def _cached_embed(params, tokens, step, cfg: SdarMoeConfig):
    x = step.take(params["wte"].astype(cfg.dtype), tokens)
    return x, rotary_tables(step.pos, cfg)


def _cached_layer(x, lp, attend, step, work: dict, cfg: SdarMoeConfig):
    with jax.named_scope("attn_proj"):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(h, lp, step.aux, cfg)
        x = x + attend(q, k, v) @ lp["wo"].astype(cfg.dtype)
    with jax.named_scope("ffn"):
        x, sizes = _experts(x, lp, cfg, work["routed"])
    return x, {**work, "layer": work["layer"] + 1,
               "sizes": [*work["sizes"], sizes]}


def _close_state(state: dict, work: dict, step, cfg: SdarMoeConfig):
    # a block pass is this family's decode step
    return {**state, **count_pairs(
        state, work["sizes"], int(step.kind == "block"))}


FAMILY = cached.CachedFamily(
    "sdar_moe", SdarMoeConfig, "layers", _cached_embed, _cached_layer,
    final_norm, head_untied, open_state=open_experts,
    close_state=_close_state,
    no_verify="there is nothing to draft for (a pass fills a block's "
              "positions in any order; no next-token distribution is left "
              "to check)",
    state_rows=False, gmm_form=step_gmm_form, block_steps=True)
sdar_moe_prefill, sdar_moe_decode_step, _ = cached.steps(FAMILY)
