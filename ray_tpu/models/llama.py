"""LLaMA-family transformer: RMSNorm + RoPE + GQA + SwiGLU, optional MoE.

Second flagship model family (modern-decoder architecture; the reference
ships no model zoo of its own — its Train/Serve layers wrap torch models —
so this follows the public LLaMA/Mixtral formulation). Same conventions as
models/gpt.py: pure param pytrees, a parallel tree of logical axis names,
`lax.scan` over stacked blocks, params f32 / activations bf16.

GQA: n_kv_head < n_head shares each KV head across n_head//n_kv_head query
heads (KV repeated before the attention kernel — keeps flash/ring kernels
head-uniform). MoE: num_experts > 0 swaps the SwiGLU MLP for a Mixtral-style
top-k expert MLP (ops/moe.py) with the load-balance aux loss summed over
layers.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models import cached
from ray_tpu.ops.attention import flash_attention, mha_reference
from ray_tpu.ops.layers import rms_norm, rope, rope_cache
from ray_tpu.ops.moe import MoEConfig, moe_forward
from ray_tpu.parallel.sharding import ShardingRules, with_logical_constraint


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_seq_len: int = 2048
    n_layer: int = 8
    n_head: int = 8
    n_kv_head: int = 4
    d_model: int = 512
    d_mlp: int = 1408  # ~8/3 * d_model rounded to 128 (SwiGLU sizing)
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    attention: str = "flash"  # flash | xla | ring (training/full-seq path)
    # decode attention backend (serve/llm): auto | xla | pallas — see
    # models/gpt.py GPTConfig.attention_backend.
    attention_backend: str = "auto"
    # serving quantization ("int8" | "fp8" | None) — see models/gpt.py
    # GPTConfig.quantization. Threaded from EngineConfig.quantization.
    quantization: str | None = None
    remat: bool = False
    scan_layers: bool = True  # lax.scan over blocks vs unrolled loop (see
                              # models/gpt.py: unrolling dodges the
                              # backward's scan-carry tax; benches unroll,
                              # pipeline meshes keep the scan)
    fused_loss: bool = True   # chunked lm-head+CE on the single-device
                              # path — no [B,S,V] logits (ops/loss.py)
    # MoE (0 = dense SwiGLU)
    num_experts: int = 0
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_coeff: float = 0.01

    @staticmethod
    def tiny(vocab_size: int = 512) -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=vocab_size, max_seq_len=128, n_layer=2, n_head=4,
            n_kv_head=2, d_model=64, d_mlp=128,
        )

    @staticmethod
    def tiny_moe(vocab_size: int = 512) -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=vocab_size, max_seq_len=128, n_layer=2, n_head=4,
            n_kv_head=2, d_model=64, d_mlp=128, num_experts=4, top_k=2,
        )

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @property
    def kv_groups(self) -> int:
        return self.n_head // self.n_kv_head

    def __post_init__(self):
        if self.n_head % self.n_kv_head:
            raise ValueError("n_head must be a multiple of n_kv_head")


def llama_init(key: jax.Array, cfg: LlamaConfig) -> dict:
    k = iter(jax.random.split(key, 16))
    L, D, M, V = cfg.n_layer, cfg.d_model, cfg.d_mlp, cfg.vocab_size
    hd, Hq, Hkv = cfg.head_dim, cfg.n_head, cfg.n_kv_head
    std = 0.02

    def norm(key, *shape, scale=std):
        return jax.random.normal(key, shape, jnp.float32) * scale

    blocks: dict = {
        "ln1_scale": jnp.ones((L, D), jnp.float32),
        "wq": norm(next(k), L, D, Hq * hd),
        "wk": norm(next(k), L, D, Hkv * hd),
        "wv": norm(next(k), L, D, Hkv * hd),
        "wo": norm(next(k), L, Hq * hd, D, scale=std / (2 * L) ** 0.5),
        "ln2_scale": jnp.ones((L, D), jnp.float32),
    }
    if cfg.num_experts:
        E = cfg.num_experts
        blocks.update(
            {
                "moe_router": norm(next(k), L, D, E),
                # experts use the GELU MLP form of ops/moe.moe_forward
                "moe_w_in": norm(next(k), L, E, D, M, scale=D**-0.5),
                "moe_w_out": norm(next(k), L, E, M, D, scale=M**-0.5),
            }
        )
    else:
        blocks.update(
            {
                # SwiGLU packs gate+up into one [D, 2M] matmul
                "mlp_in": norm(next(k), L, D, 2 * M),
                "mlp_out": norm(next(k), L, M, D, scale=std / (2 * L) ** 0.5),
            }
        )
    return {
        "wte": norm(next(k), V, D),
        "blocks": blocks,
        "ln_f_scale": jnp.ones((D,), jnp.float32),
        "lm_head": norm(next(k), D, V),
    }


def llama_param_axes(cfg: LlamaConfig) -> dict:
    blocks: dict = {
        "ln1_scale": (None, "embed"),
        "wq": (None, "embed", "mlp"),
        "wk": (None, "embed", "mlp"),
        "wv": (None, "embed", "mlp"),
        "wo": (None, "mlp", "embed"),
        "ln2_scale": (None, "embed"),
    }
    if cfg.num_experts:
        blocks.update(
            {
                "moe_router": (None, None, None),
                "moe_w_in": (None, "expert", None, "mlp"),
                "moe_w_out": (None, "expert", "mlp", None),
            }
        )
    else:
        blocks.update(
            {
                "mlp_in": (None, "embed", "mlp"),
                "mlp_out": (None, "mlp", "embed"),
            }
        )
    return {
        "wte": ("vocab", "embed"),
        "blocks": blocks,
        "ln_f_scale": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def llama_quant_axes(cfg: LlamaConfig) -> dict:
    """Per-leaf amax reduction axis for serving weight quantization (see
    models/gpt.py gpt_quant_axes): the contraction axis of each matmul so
    scales are per-output-channel; -1 keeps the leaf in full precision.
    RMSNorm scales stay f32 (tiny, numerically load-bearing); MoE expert
    weights stay f32 because ``moe_forward`` consumes the raw params
    without the ``astype`` dequant seam."""
    blocks: dict = {
        "ln1_scale": -1,
        "wq": 1,
        "wk": 1,
        "wv": 1,
        "wo": 1,
        "ln2_scale": -1,
    }
    if cfg.num_experts:
        blocks.update(
            {"moe_router": -1, "moe_w_in": -1, "moe_w_out": -1}
        )
    else:
        blocks.update({"mlp_in": 1, "mlp_out": 1})
    return {
        "wte": 1,
        "blocks": blocks,
        "ln_f_scale": -1,
        "lm_head": 0,
    }


def _swiglu(x, w_in, w_out, dtype):
    gate_up = x @ w_in.astype(dtype)
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_out.astype(dtype)


def _moe_cfg(cfg: LlamaConfig) -> MoEConfig:
    return MoEConfig(
        d_model=cfg.d_model, d_hidden=cfg.d_mlp, num_experts=cfg.num_experts,
        top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
        aux_loss_coeff=cfg.aux_loss_coeff, dtype=cfg.dtype,
    )


def _attn_qkv(x, bp, cos, sin, cfg: LlamaConfig, positions=None):
    """rms_norm + Q/K/V projections with RoPE applied at the true position
    (``positions`` [B, S] indexes the cos/sin tables; None = 0..S-1).
    Returns q [B, S, Hq, hd] and k, v [B, S, Hkv, hd] — kv heads NOT yet
    repeated, so the KV-cached path (serve/llm) stores the compact GQA
    heads. Shared by the full-sequence block and prefill/decode."""
    B, S, _ = x.shape
    Hq, Hkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    h = rms_norm(x, bp["ln1_scale"])
    q = (h @ bp["wq"].astype(cfg.dtype)).reshape(B, S, Hq, hd)
    kk = (h @ bp["wk"].astype(cfg.dtype)).reshape(B, S, Hkv, hd)
    vv = (h @ bp["wv"].astype(cfg.dtype)).reshape(B, S, Hkv, hd)
    q = rope(q, cos, sin, positions)
    kk = rope(kk, cos, sin, positions)
    return q, kk, vv


def _ffn_residual(x, bp, cfg: LlamaConfig, constrain=None):
    """ln2 + (SwiGLU | MoE) + residual. Returns (x, aux_loss)."""
    B, S, D = x.shape
    h = rms_norm(x, bp["ln2_scale"])
    if cfg.num_experts:
        flat = h.reshape(B * S, D)
        moe_params = {
            "router": bp["moe_router"],
            "w_in": bp["moe_w_in"],
            "w_out": bp["moe_w_out"],
        }
        out, aux = moe_forward(moe_params, flat, _moe_cfg(cfg))
        return x + out.reshape(B, S, D), aux
    h2 = _swiglu(h, bp["mlp_in"], bp["mlp_out"], cfg.dtype)
    if constrain is not None:
        h2 = constrain(h2, ("batch", "seq", "embed"))
    return x + h2, jnp.zeros((), jnp.float32)


def _block(x, bp, cos, sin, cfg: LlamaConfig, rules, mesh):
    B, S, D = x.shape
    Hq, hd, g = cfg.n_head, cfg.head_dim, cfg.kv_groups

    def constrain(t, axes):
        if mesh is None:
            return t
        return with_logical_constraint(t, axes, rules, mesh)

    q, kk, vv = _attn_qkv(x, bp, cos, sin, cfg)
    # GQA: repeat KV heads to match query heads (kernel stays head-uniform)
    if g > 1:
        kk = jnp.repeat(kk, g, axis=2)
        vv = jnp.repeat(vv, g, axis=2)
    q = q.transpose(0, 2, 1, 3)
    kk = kk.transpose(0, 2, 1, 3)
    vv = vv.transpose(0, 2, 1, 3)
    q = constrain(q, ("batch", "heads", None, None))

    if cfg.attention == "flash":
        attn = flash_attention(q, kk, vv, causal=True)
    elif cfg.attention == "ring":
        from ray_tpu.ops.ring_attention import ring_attention_sharded

        attn = ring_attention_sharded(q, kk, vv, mesh, causal=True)
    else:
        attn = mha_reference(q, kk, vv, causal=True)
    attn = attn.transpose(0, 2, 1, 3).reshape(B, S, Hq * hd)
    x = x + attn @ bp["wo"].astype(cfg.dtype)

    x, aux = _ffn_residual(x, bp, cfg, constrain)
    return constrain(x, ("batch", "seq", "embed")), aux


def llama_hidden(
    params: dict,
    tokens: jax.Array,
    cfg: LlamaConfig,
    *,
    rules: ShardingRules | None = None,
    mesh=None,
):
    """tokens [B, S] int32 → (final hidden [B, S, D] after rms_norm,
    summed MoE aux loss)."""
    B, S = tokens.shape
    wte = params["wte"].astype(cfg.dtype)
    if mesh is not None:
        # replicate the table for the token gather (see gpt.py: a gather
        # from a vocab/embed-sharded table triggers SPMD's involuntary full
        # rematerialization fallback every step)
        wte = with_logical_constraint(wte, (None, None), rules, mesh)
    x = wte[tokens]
    if mesh is not None:
        x = with_logical_constraint(x, ("batch", "seq", "embed"), rules, mesh)
    cos, sin = rope_cache(S, cfg.head_dim, cfg.rope_theta)

    def body(carry, bp):
        x, aux_sum = carry
        out, aux = _block(x, bp, cos, sin, cfg, rules, mesh)
        return (out, aux_sum + aux), None

    if cfg.remat:
        body = jax.checkpoint(body)
    init = (x, jnp.zeros((), jnp.float32))
    if cfg.scan_layers:
        (x, aux_sum), _ = jax.lax.scan(body, init, params["blocks"])
    else:
        carry = init
        for i in range(cfg.n_layer):
            carry, _ = body(carry, jax.tree.map(lambda a: a[i],
                                                params["blocks"]))
        x, aux_sum = carry
    return rms_norm(x, params["ln_f_scale"]), aux_sum


def llama_forward(
    params: dict,
    tokens: jax.Array,
    cfg: LlamaConfig,
    *,
    rules: ShardingRules | None = None,
    mesh=None,
    return_aux: bool = False,
):
    """tokens [B, S] int32 → logits [B, S, vocab] f32 (+ total MoE aux loss)."""
    x, aux_sum = llama_hidden(params, tokens, cfg, rules=rules, mesh=mesh)
    # bf16 operands keep the vocab matmul on the MXU's fast path;
    # accumulation and the returned logits are f32 for a stable softmax
    logits = jnp.einsum(
        "bsd,dv->bsv", x.astype(cfg.dtype),
        params["lm_head"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    )
    if return_aux:
        return logits, aux_sum
    return logits


def llama_loss(
    params: dict,
    batch: dict,
    cfg: LlamaConfig,
    *,
    rules: ShardingRules | None = None,
    mesh=None,
) -> jax.Array:
    mask = batch.get("mask")
    if "tokens" in batch:
        inputs = batch["tokens"][:, :-1]
        targets = batch["tokens"][:, 1:]
        # a [B, S+1] token-aligned mask must shift with the targets; a
        # [B, S] mask is already target-aligned
        if mask is not None and mask.shape[-1] == batch["tokens"].shape[-1]:
            mask = mask[:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
    if cfg.fused_loss and mesh is None:
        # single-device path: chunked lm-head + CE (ops/loss.py) — the
        # [B,S,V] logits tensor never exists. lm_head is [D, V]; the
        # transpose folds into the chunk matmuls' dimension numbers.
        from ray_tpu.ops.loss import fused_lm_head_loss

        x, aux = llama_hidden(params, inputs, cfg, rules=rules, mesh=mesh)
        B, S, D = x.shape
        ce = fused_lm_head_loss(
            x.reshape(B * S, D),
            params["lm_head"].T,
            targets.reshape(B * S).astype(jnp.int32),
            None if mask is None else mask.reshape(B * S).astype(jnp.float32),
        )
        return ce + aux
    logits, aux = llama_forward(
        params, inputs, cfg, rules=rules, mesh=mesh, return_aux=True
    )
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is not None:
        ce = -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1)
    else:
        ce = -jnp.mean(ll)
    return ce + aux


# ----------------------------------------------------------------------------
# KV-cached inference paths (serve/llm engine): what models/cached.py's one
# step needs of this family. GQA: the cache stores the compact n_kv_head
# heads; repetition to n_head happens inside the attention ops. Cache layout
# [n_layer, num_blocks, block_size, n_kv_head, head_dim] (ops/kv_cache.py).
# ----------------------------------------------------------------------------


def _cached_embed(params, tokens, step, cfg: LlamaConfig):
    """The embedding, and the rotary table with the rows of it each token
    takes: a fresh prompt slices the table to its 0..S-1 (None: no
    gather), every other kind indexes the true positions."""
    x = step.take(params["wte"].astype(cfg.dtype), tokens)
    if step.kind == "fresh":
        rows, at = tokens.shape[1], None
    else:
        rows, at = cfg.max_seq_len, step.table_pos(cfg.max_seq_len)
    return x, (*rope_cache(rows, cfg.head_dim, cfg.rope_theta), at)


def _cached_layer(x, bp, attend, step, state, cfg: LlamaConfig):
    cos, sin, at = step.aux
    with jax.named_scope("attn_proj"):
        q, kk, vv = _attn_qkv(x, bp, cos, sin, cfg, positions=at)
        x = x + attend(q, kk, vv) @ bp["wo"].astype(cfg.dtype)
    with jax.named_scope("ffn"):
        x, _ = _ffn_residual(x, bp, cfg)
    return x, state


def _final_norm(params, x, cfg: LlamaConfig):
    return rms_norm(x, params["ln_f_scale"])


def _head(params, h, cfg: LlamaConfig):
    return jnp.einsum(
        "...d,dv->...v", h.astype(cfg.dtype),
        params["lm_head"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    )


FAMILY = cached.CachedFamily(
    "llama", LlamaConfig, "blocks", _cached_embed, _cached_layer,
    _final_norm, _head)
llama_prefill, llama_decode_step, llama_verify_step = cached.steps(FAMILY)


def llama_num_params(cfg: LlamaConfig) -> int:
    p = llama_init(jax.random.PRNGKey(0), cfg)
    return sum(x.size for x in jax.tree.leaves(p))
