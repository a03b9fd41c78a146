"""EvaByte: a byte-level decoder whose attention is exact inside a window
and summarised behind it (EVA, Zheng et al., ICLR 2023, arXiv:2302.04542;
the public ``EvaByte/EvaByte`` configuration: 32 layers, hidden 4096, 32
heads of 128, SwiGLU 11008, a vocabulary of 320 bytes, 8 output heads,
``window_size`` 2048, ``chunk_size`` 16).

One layer, head ``h``, with ``W = window_size``, ``C = chunk_size``, ``s =
head_dim ** -0.5`` and ``w(t) = t // W``:

- ``u = rmsnorm(x) * (1 + g)``; ``q, k, v = u Wq, u Wk, u Wv`` (no bias),
  rotary (rotate-half, the whole head) on q and k at the TRUE positions;
- chunk ``c`` is positions ``[cC, cC + C)``; its summary ``(K_c, V_c)`` is
  ops/eva.py ``chunk_summaries`` with the layer's ``eva_phi`` / ``eva_mu``;
- a query at ``t`` sees, under ONE softmax, the exact keys of its own
  window ``{m : w(m) = w(t), m <= t}`` and the summaries of every chunk
  that lies in a window before ``w(t)`` (chunks ``0 .. (W/C) w(t) - 1``);
- ``y = x + o Wo``; ``x' = y + swiglu(rmsnorm(y) * (1 + g2))``, the residual
  stream and its adds in float32; the head is ``[D, P * V]``, output head
  ``j`` predicting byte ``t + 1 + j``, logits in float32.

``evabyte_forward`` is the plain full-sequence form (masks, no cache). The
served step is models/cached.py's, over TWO tables a sequence that every
layer reads (serve/llm/kv_cache.py): a window table, a ring of ``W /
block_size`` blocks written at ``t mod W``, and a summary table with one
slot a chunk. The step's table 0 is COMPOSED of them, ``[the summary blocks
of closed windows | the window's blocks]``, so that a token's K/V is
written and masked at ``place(t) = (W/C) w(t) + t mod W`` and the attention
ops run as they are, causal in table coordinates; table 1 is the summary
table, where the step that fills a chunk's last slot writes its summary
(ops/eva.py ``write_prefill_summaries`` / ``write_decode_summaries``). A
summary becomes visible only through ``place``: when its window closes.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models import cached
from ray_tpu.ops.eva import (
    chunk_summaries,
    write_decode_summaries,
    write_prefill_summaries,
)
from ray_tpu.ops.layers import rms_norm, rope, rope_cache


@dataclass(frozen=True)
class EvaByteConfig:
    vocab_size: int = 320
    max_seq_len: int = 32768
    n_layer: int = 32
    n_head: int = 32
    d_model: int = 4096
    d_mlp: int = 11008
    num_pred_heads: int = 8
    window_size: int = 2048
    chunk_size: int = 16
    rope_theta: float = 100000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # decode attention backend and serving quantization: models/gpt.py
    attention_backend: str = "auto"
    quantization: str | None = None

    @staticmethod
    def tiny(vocab_size: int = 320) -> "EvaByteConfig":
        return EvaByteConfig(
            vocab_size=vocab_size, max_seq_len=256, n_layer=2, n_head=4,
            d_model=64, d_mlp=128, num_pred_heads=2, window_size=32,
            chunk_size=4)

    def __post_init__(self):
        if self.d_model % self.n_head:
            raise ValueError("d_model must be a multiple of n_head")
        if self.window_size % self.chunk_size:
            raise ValueError(
                f"chunk_size {self.chunk_size} does not divide window_size "
                f"{self.window_size}: chunks tile a window")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @property
    def n_kv_head(self) -> int:
        return self.n_head  # a K/V head a query head, as published

    @property
    def chunks_per_window(self) -> int:
        return self.window_size // self.chunk_size

    @property
    def kv_table_groups(self) -> tuple:
        """What the cache manager is told (``KVCacheConfig.groups``): two
        tables that EVERY layer reads, the ring of a window's exact K/V
        and the slot-a-chunk table of summaries."""
        layers = tuple(range(self.n_layer))
        return ((("ring", self.window_size), layers),
                (("slots", self.chunk_size), layers))


def evabyte_init(key: jax.Array, cfg: EvaByteConfig) -> dict:
    """Float32 masters, normal from ``key``: each matmul leaf with std
    ``fan_in ** -0.5``, the projections back into the residual stream a
    further ``(2 L) ** -0.5`` smaller, ``wq`` and ``wk`` 1.4 x larger so
    that a row's scores have std 2 and some ten keys carry it (models/
    laguna.py ``laguna_init`` and its reason: a softmax that is flat over
    a window would not tell WHICH keys a query saw). ``eva_phi`` and
    ``eva_mu`` with std ``head_dim ** -0.5`` (assumed: no checkpoint
    here); the norms' offsets ``g`` zero."""
    ks = iter(jax.random.split(key, 12))
    L, D, M, V = cfg.n_layer, cfg.d_model, cfg.d_mlp, cfg.vocab_size
    H, hd, P = cfg.n_head, cfg.head_dim, cfg.num_pred_heads
    back = (2 * L) ** -0.5

    def norm(*shape, std):
        return jax.random.normal(next(ks), shape, jnp.float32) * std

    return {
        "wte": norm(V, D, std=1.0),
        "blocks": {
            "ln1_g": jnp.zeros((L, D), jnp.float32),
            "wq": norm(L, D, H * hd, std=1.4 * D ** -0.5),
            "wk": norm(L, D, H * hd, std=1.4 * D ** -0.5),
            "wv": norm(L, D, H * hd, std=D ** -0.5),
            "wo": norm(L, H * hd, D, std=back * (H * hd) ** -0.5),
            "eva_phi": norm(L, H, hd, std=hd ** -0.5),
            "eva_mu": norm(L, H, hd, std=hd ** -0.5),
            "ln2_g": jnp.zeros((L, D), jnp.float32),
            # SwiGLU packs gate and up into one [D, 2M] matrix, gate first
            "mlp_in": norm(L, D, 2 * M, std=D ** -0.5),
            "mlp_out": norm(L, M, D, std=back * M ** -0.5),
        },
        "ln_f_g": jnp.zeros((D,), jnp.float32),
        # the published shape: P heads of V outputs, head 0 the next byte
        "lm_head": norm(D, P * V, std=D ** -0.5),
    }


def evabyte_param_axes(cfg: EvaByteConfig) -> dict:
    return {
        "wte": ("vocab", "embed"),
        "blocks": {
            "ln1_g": (None, "embed"),
            "wq": (None, "embed", "mlp"), "wk": (None, "embed", "mlp"),
            "wv": (None, "embed", "mlp"), "wo": (None, "mlp", "embed"),
            "eva_phi": (None, None, None), "eva_mu": (None, None, None),
            "ln2_g": (None, "embed"),
            "mlp_in": (None, "embed", "mlp"),
            "mlp_out": (None, "mlp", "embed"),
        },
        "ln_f_g": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def evabyte_quant_axes(cfg: EvaByteConfig) -> dict:
    """Which leaves are matmul weights (stored in the compute dtype by the
    executor; -1: kept as given): see models/llama.py."""
    return {
        "wte": 1,
        "blocks": {"ln1_g": -1, "wq": 1, "wk": 1, "wv": 1, "wo": 1,
                   "eva_phi": -1, "eva_mu": -1, "ln2_g": -1, "mlp_in": 1,
                   "mlp_out": 1},
        "ln_f_g": -1,
        "lm_head": 0,
    }


def _normed(x, g, cfg: EvaByteConfig):
    """``rmsnorm(x) * (1 + g)`` of the float32 stream, in the compute
    dtype for the product that follows."""
    return rms_norm(x, 1.0 + g, cfg.norm_eps).astype(cfg.dtype)


def _qkv(x, bp, cos, sin, cfg: EvaByteConfig, positions=None):
    B, S, _ = x.shape
    H, hd = cfg.n_head, cfg.head_dim
    u = _normed(x, bp["ln1_g"], cfg)
    q = (u @ bp["wq"].astype(cfg.dtype)).reshape(B, S, H, hd)
    k = (u @ bp["wk"].astype(cfg.dtype)).reshape(B, S, H, hd)
    v = (u @ bp["wv"].astype(cfg.dtype)).reshape(B, S, H, hd)
    return rope(q, cos, sin, positions), rope(k, cos, sin, positions), v


def _ffn(y, bp, cfg: EvaByteConfig):
    u = _normed(y, bp["ln2_g"], cfg)
    gate, up = jnp.split(u @ bp["mlp_in"].astype(cfg.dtype), 2, axis=-1)
    out = (jax.nn.silu(gate) * up) @ bp["mlp_out"].astype(cfg.dtype)
    return y + out.astype(jnp.float32)


def _eva_attention(q, k, v, phi, mu, cfg: EvaByteConfig):
    """The layer's attention over a whole sequence from position 0, by
    masks: q, k, v ``[B, S, H, hd]`` -> ``[B, S, H * hd]``. The keys are
    the summaries of the sequence's whole chunks, then its tokens."""
    B, S, H, hd = q.shape
    W, C = cfg.window_size, cfg.chunk_size
    n = S // C
    k_c, v_c = chunk_summaries(
        k[:, :n * C].reshape(B, n, C, H, hd),
        v[:, :n * C].reshape(B, n, C, H, hd), phi, mu, backend="xla")
    keys = jnp.concatenate([k_c, k], axis=1).astype(jnp.float32)
    values = jnp.concatenate([v_c, v], axis=1).astype(jnp.float32)
    t = jnp.arange(S)[:, None]
    m = jnp.arange(S)[None, :]
    local = (m // W == t // W) & (m <= t)
    remote = jnp.arange(n)[None, :] < (t // W) * (W // C)
    mask = jnp.concatenate([remote, local], axis=1)  # [S, n + S]
    scores = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                        keys) * hd ** -0.5
    probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, values)
    return out.reshape(B, S, H * hd).astype(q.dtype)


def evabyte_forward(params: dict, tokens: jax.Array, cfg: EvaByteConfig):
    """tokens ``[B, S]`` int32 -> logits ``[B, S, num_pred_heads, vocab]``
    float32: output head ``j`` at position ``t`` predicts byte ``t + 1 +
    j``. No cache, no blocks: one pass over the whole sequence."""
    B, S = tokens.shape
    x = params["wte"].astype(cfg.dtype)[tokens].astype(jnp.float32)
    cos, sin = rope_cache(S, cfg.head_dim, cfg.rope_theta)

    def body(x, bp):
        q, k, v = _qkv(x, bp, cos, sin, cfg)
        attn = _eva_attention(q, k, v, bp["eva_phi"], bp["eva_mu"], cfg)
        y = x + (attn @ bp["wo"].astype(cfg.dtype)).astype(jnp.float32)
        return _ffn(y, bp, cfg), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    h = _final_norm(params, x, cfg)
    logits = jnp.einsum(
        "bsd,dv->bsv", h, params["lm_head"].astype(cfg.dtype),
        preferred_element_type=jnp.float32)
    return logits.reshape(B, S, cfg.num_pred_heads, cfg.vocab_size)


# ----------------------------------------------------------------------------
# The served step (models/cached.py): what is this family's own.
# ----------------------------------------------------------------------------


def _place(pos, cfg: EvaByteConfig):
    """A token's index in the step's composed table (table 0): behind the
    ``W / C`` summaries of each closed window, its offset in its own
    window. Where its K/V is written and where the causal mask stands; the
    rotary embedding keeps the true position."""
    W = cfg.window_size
    return (pos // W) * cfg.chunks_per_window + pos % W


def _cached_embed(params, tokens, step, cfg: EvaByteConfig):
    x = step.take(params["wte"].astype(cfg.dtype), tokens)
    if step.kind == "fresh":
        rows, at = tokens.shape[1], None
    else:
        rows, at = cfg.max_seq_len, step.table_pos(cfg.max_seq_len)
    return x.astype(jnp.float32), (
        *rope_cache(rows, cfg.head_dim, cfg.rope_theta), at)


def _cached_layer(x, bp, attend, step, state, cfg: EvaByteConfig):
    cos, sin, at = step.aux
    with jax.named_scope("attn_proj"):
        q, k, v = _qkv(x, bp, cos, sin, cfg, positions=at)

    def summarise(cache_k, cache_v, layer):
        """The summaries of the chunks this step completes, into the
        summary table (the step's table 1), slot ``t // C``: a prompt
        chunk's whole chunks from the fresh k / v, a decode row's chunk
        read back from the ring (ops/eva.py)."""
        ring, summaries = step.block_tables[0], step.block_tables[1]
        common = dict(layer=layer, summaries=summaries,
                      backend=cfg.attention_backend)
        if step.kind == "decode":
            return write_decode_summaries(
                cache_k, cache_v, bp["eva_phi"], bp["eva_mu"], t=step.rows,
                at=step.at[:, 0], ring=ring, chunk=cfg.chunk_size, **common)
        return write_prefill_summaries(
            cache_k, cache_v, k, v, bp["eva_phi"], bp["eva_mu"],
            start=step.pos[:, 0], lengths=step.rows, chunk=cfg.chunk_size,
            **common)

    with jax.named_scope("attn_proj"):
        attn = attend(q, k, v, group=0, then=summarise)
        y = x + (attn @ bp["wo"].astype(cfg.dtype)).astype(jnp.float32)
    with jax.named_scope("ffn"):
        return _ffn(y, bp, cfg), state


def _final_norm(params, x, cfg: EvaByteConfig):
    return _normed(x, params["ln_f_g"], cfg)


def _head(params, h, cfg: EvaByteConfig):
    """The served step samples the NEXT byte: output head 0's columns."""
    return jnp.einsum(
        "...d,dv->...v", h,
        params["lm_head"][:, :cfg.vocab_size].astype(cfg.dtype),
        preferred_element_type=jnp.float32)


FAMILY = cached.CachedFamily(
    "evabyte", EvaByteConfig, "blocks", _cached_embed, _cached_layer,
    _final_norm, _head, place=_place,
    no_verify="a rejected draft would already be in its chunk's sum")
evabyte_prefill, evabyte_decode_step, _ = cached.steps(FAMILY)
