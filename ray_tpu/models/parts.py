"""The parts served families share, written once.

A family's file (models/laguna.py, pangu_ultra_moe.py, ...) holds what is
the family's own; what two or more of them compute the same way lives
here, beside the cached step they all run (models/cached.py), and a
family's file imports it from here, never from another family's file. A
change to a function of this file is a change to every family that names
it. By group:

- the tail of a stack: ``final_norm``, ``head_tied`` / ``head_untied``;
- ``swiglu``, the dense gated feed-forward;
- the 64-bit counters a step program keeps in ``state``: ``count_add``
  on the device, ``count_value`` on the host;
- an expert layer's bookkeeping: ``routed_mask``, ``open_experts`` /
  ``close_experts`` (the working state and the counters of a family that
  holds a RANGE of the experts and counts what was routed too),
  ``count_pairs`` (a family that holds them all: pairs and reads);
- ``leaf_tree``: a tree of the parameters' shape by leaf name, for a
  family's ``<name>_param_axes`` / ``<name>_quant_axes``;
- latent attention (one row a token for all heads): ``rotary_at``,
  ``rotate``, ``queries_and_row``, ``absorb`` / ``unabsorb``,
  ``cached_heads``, ``expanded_attention``, ``latent_step_attrs``;
- the whole-head rotary angles ``rotary_tables`` and the plain
  ``windowed_attention`` of the families whose layers slide over a window.

Leaf names (``ln_f_scale``, ``lm_head``, ``mla_w_uk``, ...) and
``jax.named_scope`` names are part of what the benchmark's readers find
operations by: they are the families' and stay as they are.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import NEG_INF
from ray_tpu.ops.latent_prefill import prefix_blocks
from ray_tpu.ops.layers import rms_norm, rope

# ------------------------------------------------------- the tail of a stack


def final_norm(params, x, cfg):
    return rms_norm(x, params["ln_f_scale"], cfg.norm_eps)


def head_tied(params, h, cfg):
    """[..., D] -> float32 logits over the tied embedding."""
    return jnp.einsum(
        "...d,vd->...v", h.astype(cfg.dtype), params["wte"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    )


def head_untied(params, h, cfg):
    """[..., D] -> float32 logits through the untied head (over the rows
    of the vocabulary THIS device holds, where a family says so)."""
    return jnp.einsum(
        "...d,dv->...v", h.astype(cfg.dtype),
        params["lm_head"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    )


def swiglu(h, w_in, w_out, dtype):
    gate, up = jnp.split(h @ w_in.astype(dtype), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_out.astype(dtype)


# ----------------------------------------------------------------- counters


def count_add(acc: jax.Array, n: jax.Array) -> jax.Array:
    """``acc`` [..., 2] uint32 (low, high words) plus ``n`` [...] >= 0."""
    low = acc[..., 0] + n.astype(jnp.uint32)
    high = acc[..., 1] + (low < acc[..., 0]).astype(jnp.uint32)
    return jnp.stack([low, high], axis=-1)


def count_value(acc) -> Any:
    """Host side: the integers a (low, high) counter array holds."""
    import numpy as np

    a = np.asarray(acc).astype(np.uint64)
    return (a[..., 1] << np.uint64(32)) + a[..., 0]


# ------------------------------------------- an expert layer's bookkeeping


def routed_mask(step):
    """The tokens of a step that are routed, ``[B, 1]`` or ``[B, S]``: the
    real tokens of the rows that are no padding (slot 0)."""
    routed = (step.slots > 0)[:, None]
    if step.valid is not None:
        routed = step.valid & routed
    return routed


def count_pairs(state: dict, sizes: list, kind: int) -> dict:
    """The counters of ``state`` that a step's expert layers move, as a
    dict of their next values: ``sizes`` (one ``[experts]`` int32 a layer:
    the pairs each expert took) added to ``pairs[kind]`` (``kind`` 0 a
    prefill step, 1 a decode step) and, for a decode step, the experts
    that got a token to ``reads``."""
    out = {"pairs": state["pairs"].at[kind].set(
        count_add(state["pairs"][kind], sum(sizes)))}
    if kind:
        out["reads"] = count_add(
            state["reads"], sum(jnp.sum(s > 0) for s in sizes))
    return out


def open_experts(state: dict, step, cfg) -> dict:
    """The step's working state: the index of the next layer, each expert
    layer's held pairs, and the mask of the tokens that are routed."""
    del state, cfg
    return {"layer": 0, "sizes": [],
            "routed": jnp.broadcast_to(routed_mask(step), step.pos.shape)}


def close_experts(state: dict, work: dict, step, cfg) -> dict:
    """``state`` with the step's counters added, for a family that holds a
    range of the experts and so also counts what was ROUTED, all the picks
    of the step's routed tokens, ``top_k`` a token a layer (``pairs``
    ``[2, held, 2]``, ``routed`` ``[2, 2]``, ``reads`` ``[2]``:
    models/laguna.py ``laguna_init_state``). Its own body and not
    ``count_pairs`` with a middle: the step programs' recorded texts hold
    the order pairs, routed, reads."""
    kind = int(step.kind == "decode")
    sizes = work["sizes"]
    out = dict(state)
    if not sizes:
        return out
    out["pairs"] = state["pairs"].at[kind].set(
        count_add(state["pairs"][kind], sum(sizes)))
    out["routed"] = state["routed"].at[kind].set(count_add(
        state["routed"][kind],
        jnp.sum(work["routed"]) * (cfg.top_k * len(sizes))))
    if kind:
        out["reads"] = count_add(
            state["reads"], sum(jnp.sum(s > 0) for s in sizes))
    return out


# ------------------------------------------------------------ the axis trees


def leaf_tree(init, cfg, of) -> dict:
    """``of(name)`` for every leaf of ``init(key, cfg)``'s tree, by the
    leaf's own name (the last key of its path)."""
    shape = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))
    return jax.tree_util.tree_map_with_path(
        lambda path, _: of(path[-1].key), shape)


# --------------------------------------------------------- latent attention
# models/pangu_ultra_moe.py says the layer; ``cfg`` names ``n_head``,
# ``kv_lora_rank`` (C), ``qk_nope_head_dim`` (N), ``qk_rope_head_dim`` (R),
# ``v_head_dim`` (V), ``softmax_scale``; a layer's leaves are ``mla_*``.


def rotary_at(pos, cfg):
    """(cos, sin) ``[B, S, R // 2]`` at the true positions ``pos`` [B, S];
    no scaling of the frequencies (the config has no ``rope_scaling``)."""
    R = cfg.qk_rope_head_dim
    inv_freq = 1.0 / (
        cfg.rope_theta ** (jnp.arange(0, R, 2, dtype=jnp.float32) / R))
    ang = pos.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang), jnp.sin(ang)


def rotate(x, cos, sin):
    """The rotary embedding of x ``[B, S, heads, R]``, pairs BY HALVES
    (dimension i with i + R / 2: assumed; the other reading, interleaved
    pairs, is this function and the reference's ``_rotate``)."""
    return rope(x, cos, sin)


def queries_and_row(u, lp, cos, sin, cfg, *, q_scale: float | None = None,
                    c_scale: float | None = None):
    """The projections of the layer's normed input ``u`` [B, S, D]:
    ``(q_nope [B, S, H, N], q_rope [B, S, H, R], c [B, S, C], k_r [B, S,
    R])``, the last two the token's row as the pool keeps it. ``q_scale``
    multiplies both parts of every head's query and ``c_scale`` the normed
    latent (so keys' nope part and values, not ``k_r``):
    models/longcat_flash.py's two rescalings; None: none."""
    B, S, _ = u.shape
    H, N, R, C = (cfg.n_head, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                  cfg.kv_lora_rank)
    c_q = rms_norm(u @ lp["mla_w_dq"].astype(cfg.dtype), lp["mla_q_norm"],
                   cfg.norm_eps)
    q = (c_q @ lp["mla_w_uq"].astype(cfg.dtype)).reshape(B, S, H, N + R)
    kv = u @ lp["mla_w_dkv"].astype(cfg.dtype)
    c = rms_norm(kv[..., :C], lp["mla_kv_norm"], cfg.norm_eps)
    if q_scale is not None:
        q = q * jnp.asarray(q_scale, q.dtype)
    if c_scale is not None:
        c = c * jnp.asarray(c_scale, c.dtype)
    k_r = rotate(kv[..., None, C:], cos, sin)[:, :, 0]
    return q[..., :N], rotate(q[..., N:], cos, sin), c, k_r


def absorb(q_nope, lp, cfg):
    """``q~_h = q_nope,h W_uk,h^T``: [B, S, H, N] -> [B, S, H, C]."""
    w = lp["mla_w_uk"].astype(cfg.dtype).reshape(
        cfg.kv_lora_rank, cfg.n_head, cfg.qk_nope_head_dim)
    return jnp.einsum("bshn,chn->bshc", q_nope, w)


def unabsorb(o, lp, cfg):
    """``o_h = o~_h W_uv,h``: [B, S, H, C] -> [B, S, H * V]."""
    B, S = o.shape[:2]
    w = lp["mla_w_uv"].astype(cfg.dtype).reshape(
        cfg.kv_lora_rank, cfg.n_head, cfg.v_head_dim)
    return jnp.einsum("bshc,chv->bshv", o, w).reshape(B, S, -1)


def cached_heads(q_nope, q_rope, c, k_r, lp, attend, step, cfg):
    """The heads' outputs ``[B, S, H * V]`` through the cache, in the form
    the KIND of step wants (models/cached.py ``_attend_latent``). A decode
    row reads one shared row a token for all heads: the ABSORBED form
    (``W_uk`` into the query, ``W_uv`` out of the result). A prefill
    step's many queries share each key's up-projection: the EXPANDED form,
    the queries as projected and the two matrices by head handed on."""
    C, H = cfg.kv_lora_rank, cfg.n_head
    if step.kind == "decode":
        q = jnp.concatenate([absorb(q_nope, lp, cfg), q_rope], axis=-1)
        o = attend(q, c, k_r, latent=cfg.softmax_scale)  # [B, S, H * C]
        return unabsorb(o.reshape(*o.shape[:2], H, C), lp, cfg)
    return attend(
        jnp.concatenate([q_nope, q_rope], axis=-1), c, k_r,
        latent=cfg.softmax_scale,
        up=tuple(lp[w].astype(cfg.dtype).reshape(C, H, -1)
                 for w in ("mla_w_uk", "mla_w_uv")))


def expanded_attention(q_nope, q_rope, c, k_r, lp, cfg):
    """The EXPANDED form over a whole sequence, no cache: keys ``[c W_uk,h
    | k_r]`` and values ``c W_uv,h`` by head, a causal softmax. [B, S, H *
    V] in q's dtype."""
    B, S, H, N = q_nope.shape
    C, V = cfg.kv_lora_rank, cfg.v_head_dim
    k_nope = (c @ lp["mla_w_uk"].astype(cfg.dtype)).reshape(B, S, H, N)
    v = (c @ lp["mla_w_uv"].astype(cfg.dtype)).reshape(B, S, H, V)
    s = (jnp.einsum("bshn,bthn->bhst", q_nope, k_nope,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bshr,btr->bhst", q_rope, k_r,
                      preferred_element_type=jnp.float32)
         ) * cfg.softmax_scale
    t = jnp.arange(S)
    p = jax.nn.softmax(
        jnp.where(t[None, :] <= t[:, None], s, NEG_INF), axis=-1
    ).astype(q_nope.dtype)
    return jnp.einsum("bhst,bthv->bshv", p, v).reshape(B, S, H * V)


def latent_step_attrs(cfg, kind: str, rows: list) -> dict:
    """What a step's ``executor.dispatch`` span says of the form its
    latent layers attended in (``CachedFamily.step_attrs``; ``rows``
    ``[(first position, tokens)]`` a request): ``expanded_pairs``, the
    (query, key) pairs that went through the expanded form (every pair of
    a prefill step, none of a decode step), and a prefill step's
    ``prefix_blocks``, the key blocks of resident prefixes it up-projected
    a layer."""
    if kind == "decode":
        return {"expanded_pairs": 0}
    return {"expanded_pairs": sum(n * first + n * (n + 1) // 2
                                  for first, n in rows),
            "prefix_blocks": sum(prefix_blocks(first) for first, _ in rows)}


# ------------------------------------------- layers that slide over a window


def rotary_tables(pos, cfg):
    """(cos, sin) ``[B, S, hd // 2]`` at the true positions ``pos`` [B, S]:
    a rotary embedding over the whole head."""
    hd = cfg.head_dim
    ang = pos.astype(jnp.float32)[..., None] / (cfg.rope_theta ** (
        jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    return jnp.cos(ang), jnp.sin(ang)


def windowed_attention(q, k, v, window: int | None):
    """Plain attention over a whole sequence, q [B, S, Hq, hd], GQA by
    regrouping the queries: [B, S, Hq * hd] in q's dtype."""
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, S, Hkv, Hq // Hkv, hd)
    s = jnp.einsum("bshgd,bthd->bhgst", qg, k,
                   preferred_element_type=jnp.float32) * hd ** -0.5
    t = jnp.arange(S)
    mask = t[None, :] <= t[:, None]
    if window is not None:
        mask = mask & (t[None, :] > t[:, None] - window)
    p = jax.nn.softmax(jnp.where(mask, s, NEG_INF), axis=-1).astype(q.dtype)
    return jnp.einsum("bhgst,bthd->bshgd", p, v).reshape(B, S, Hq * hd)
