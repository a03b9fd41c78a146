"""GPT-2 family — the flagship transformer, mesh-parallel from the ground up.

Model config matches GPT-2 125M (BASELINE.json config 3: "JaxTrainer GPT-2
125M data-parallel"). Written as pure-JAX param pytrees with a parallel
tree of *logical axis names* so every parallelism strategy in
ray_tpu/parallel (dp/fsdp/tp/sp) is a rules-table change, not a model
change. Transformer blocks are stacked and iterated with `lax.scan` —
one compiled block body regardless of depth (XLA-friendly control flow).

Dtype policy: params f32, activations bf16, loss/softmax f32.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models import cached
from ray_tpu.ops.attention import flash_attention, mha_reference
from ray_tpu.ops.layers import gelu, layer_norm
from ray_tpu.parallel.sharding import ShardingRules, with_logical_constraint


@dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304  # 50257 padded to a multiple of 128 for the MXU
    max_seq_len: int = 1024
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    d_mlp: int = 3072
    dropout: float = 0.0  # dropout-free by default (modern practice)
    dtype: Any = jnp.bfloat16
    attention: str = "flash"  # flash | xla | ring (training/full-seq path)
    # decode attention backend (serve/llm): auto | xla | pallas — "auto"
    # picks the Pallas paged-attention kernel (ops/paged_attention.py) on
    # TPU and the XLA gather formulation elsewhere. Static in the jitted
    # decode step; threaded from EngineConfig.attention_backend.
    attention_backend: str = "auto"
    # serving quantization ("int8" | "fp8" | None): weights quantized
    # per-channel by the executor (ops/quantization.py) and the paged KV
    # pool stored quantized with per-(token, head) scales. Static in the
    # jitted steps (part of the decode jit-cache key); threaded from
    # EngineConfig.quantization. Training paths ignore it.
    quantization: str | None = None
    remat: bool = False       # jax.checkpoint each block (long-context)
    scan_layers: bool = True  # lax.scan over blocks (one compiled body) vs a
                              # fully unrolled Python loop. Unrolling lets XLA
                              # schedule/fuse across layer boundaries instead
                              # of round-tripping the scan carry: measured
                              # 33%→43% MFU on GPT-2-small bs16/seq1024 on a
                              # v5e — the backward pays the scan tax. Cost:
                              # ~3x compile time; meshes with pipeline
                              # parallelism need the scan form.
    fused_loss: bool = True   # chunked lm-head+CE, no [B,S,V] logits
                              # (single-device path; meshes use the einsum
                              # head so tp can shard the vocab matmul)

    @staticmethod
    def gpt2_small() -> "GPTConfig":
        return GPTConfig()

    @staticmethod
    def gpt2_medium() -> "GPTConfig":
        return GPTConfig(n_layer=24, n_head=16, d_model=1024, d_mlp=4096)

    @staticmethod
    def tiny(vocab_size: int = 512) -> "GPTConfig":
        """Test-size config for CPU meshes."""
        return GPTConfig(
            vocab_size=vocab_size, max_seq_len=128, n_layer=2, n_head=4,
            d_model=64, d_mlp=256,
        )

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head


def gpt_init(key: jax.Array, cfg: GPTConfig) -> dict:
    """Initialize params. Block weights carry a leading n_layer axis (for
    lax.scan); GPT-2 init: normal(0.02), residual projections scaled by
    1/sqrt(2*n_layer)."""
    k = iter(jax.random.split(key, 16))
    std = 0.02
    resid_std = std / math.sqrt(2 * cfg.n_layer)
    L, D, H, M, V, S = (
        cfg.n_layer, cfg.d_model, cfg.n_head, cfg.d_mlp,
        cfg.vocab_size, cfg.max_seq_len,
    )

    def norm(key, *shape, scale=std):
        return (jax.random.normal(key, shape, jnp.float32) * scale)

    return {
        "wte": norm(next(k), V, D),
        "wpe": norm(next(k), S, D, scale=std / 2),
        "blocks": {
            "ln1_scale": jnp.ones((L, D), jnp.float32),
            "ln1_bias": jnp.zeros((L, D), jnp.float32),
            "qkv_w": norm(next(k), L, D, 3 * D),
            "qkv_b": jnp.zeros((L, 3 * D), jnp.float32),
            "proj_w": norm(next(k), L, D, D, scale=resid_std),
            "proj_b": jnp.zeros((L, D), jnp.float32),
            "ln2_scale": jnp.ones((L, D), jnp.float32),
            "ln2_bias": jnp.zeros((L, D), jnp.float32),
            "mlp_in_w": norm(next(k), L, D, M),
            "mlp_in_b": jnp.zeros((L, M), jnp.float32),
            "mlp_out_w": norm(next(k), L, M, D, scale=resid_std),
            "mlp_out_b": jnp.zeros((L, D), jnp.float32),
        },
        "ln_f_scale": jnp.ones((D,), jnp.float32),
        "ln_f_bias": jnp.zeros((D,), jnp.float32),
    }


def gpt_param_axes(cfg: GPTConfig | None = None) -> dict:
    """Logical axis names, same tree structure as gpt_init's output.

    "embed" maps to fsdp (ZeRO-3 sharding), "mlp"/"heads"/"vocab" to tp —
    see parallel/sharding.py DEFAULT_RULES. "layer" is never sharded.
    """
    return {
        "wte": ("vocab", "embed"),
        "wpe": (None, "embed"),
        "blocks": {
            "ln1_scale": (None, "embed"),
            "ln1_bias": (None, "embed"),
            "qkv_w": (None, "embed", "mlp"),
            "qkv_b": (None, "mlp"),
            "proj_w": (None, "mlp", "embed"),
            "proj_b": (None, "embed"),
            "ln2_scale": (None, "embed"),
            "ln2_bias": (None, "embed"),
            "mlp_in_w": (None, "embed", "mlp"),
            "mlp_in_b": (None, "mlp"),
            "mlp_out_w": (None, "mlp", "embed"),
            "mlp_out_b": (None, "embed"),
        },
        "ln_f_scale": ("embed",),
        "ln_f_bias": ("embed",),
    }


def gpt_quant_axes(cfg: GPTConfig | None = None) -> dict:
    """Per-leaf amax reduction axis for serving weight quantization, same
    tree structure as gpt_init's output (``ops/quantization.py
    quantize_params``). The axis is each matmul's CONTRACTION axis so the
    scale is per-output-channel; -1 keeps the leaf in full precision
    (biases, layer norms — tiny and numerically load-bearing). ``wte``
    reduces over embed: per-vocab-row scales serve both the gather and
    the tied lm head (which contracts embed per vocab row)."""
    return {
        "wte": 1,
        "wpe": 1,
        "blocks": {
            "ln1_scale": -1,
            "ln1_bias": -1,
            "qkv_w": 1,
            "qkv_b": -1,
            "proj_w": 1,
            "proj_b": -1,
            "ln2_scale": -1,
            "ln2_bias": -1,
            "mlp_in_w": 1,
            "mlp_in_b": -1,
            "mlp_out_w": 1,
            "mlp_out_b": -1,
        },
        "ln_f_scale": -1,
        "ln_f_bias": -1,
    }


def _attn_qkv(x, bp, cfg: GPTConfig):
    """ln1 + fused QKV projection. x: [B, S, D] -> q, k, v [B, S, H, hd].
    Shared by the full-sequence block and the KV-cached prefill/decode
    paths (serve/llm) so the projection math exists exactly once."""
    B, S, _ = x.shape
    H, hd = cfg.n_head, cfg.head_dim
    h = layer_norm(x, bp["ln1_scale"], bp["ln1_bias"])
    qkv = (h @ bp["qkv_w"].astype(cfg.dtype)) + bp["qkv_b"].astype(cfg.dtype)
    q, kk, vv = jnp.split(qkv, 3, axis=-1)
    return (
        q.reshape(B, S, H, hd),
        kk.reshape(B, S, H, hd),
        vv.reshape(B, S, H, hd),
    )


def _attn_residual(x, attn, bp, cfg: GPTConfig):
    """Output projection + residual. attn: [B, S, D] (heads merged)."""
    return x + (attn @ bp["proj_w"].astype(cfg.dtype)) + bp["proj_b"].astype(
        cfg.dtype
    )


def _mlp_residual(x, bp, cfg: GPTConfig, constrain=None):
    h = layer_norm(x, bp["ln2_scale"], bp["ln2_bias"])
    h = gelu((h @ bp["mlp_in_w"].astype(cfg.dtype)) + bp["mlp_in_b"].astype(cfg.dtype))
    if constrain is not None:
        h = constrain(h, ("batch", "seq", "mlp"))
    return x + (h @ bp["mlp_out_w"].astype(cfg.dtype)) + bp["mlp_out_b"].astype(
        cfg.dtype
    )


def _block(x, bp, cfg: GPTConfig, rules: ShardingRules | None, mesh):
    """One transformer block. x: [B, S, D] in cfg.dtype."""
    B, S, D = x.shape

    def constrain(t, axes):
        if mesh is None:
            return t
        return with_logical_constraint(t, axes, rules, mesh)

    q, kk, vv = _attn_qkv(x, bp, cfg)
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

    attn = attn.transpose(0, 2, 1, 3).reshape(B, S, D)
    x = _attn_residual(x, attn, bp, cfg)
    x = _mlp_residual(x, bp, cfg, constrain)
    return constrain(x, ("batch", "seq", "embed"))


def gpt_hidden(
    params: dict,
    tokens: jax.Array,
    cfg: GPTConfig,
    *,
    rules: ShardingRules | None = None,
    mesh=None,
) -> jax.Array:
    """tokens [B, S] int32 → final hidden states [B, S, D] (cfg.dtype),
    after the final layer norm (everything but the lm-head)."""
    B, S = tokens.shape
    wte = params["wte"].astype(cfg.dtype)
    if mesh is not None:
        # Gather from a vocab/embed-sharded table forces SPMD's last-resort
        # full rematerialization (replicate + repartition per step). The
        # lookup wants the table replicated anyway — say so EXPLICITLY, so
        # the all-gather happens once where the partitioner can place it,
        # and the gather itself partitions trivially along batch.
        wte = with_logical_constraint(wte, (None, None), rules, mesh)
    x = wte[tokens] + params["wpe"].astype(cfg.dtype)[:S]
    if mesh is not None:
        x = with_logical_constraint(x, ("batch", "seq", "embed"), rules, mesh)

    blocks = params["blocks"]
    body = lambda x, bp: _block(x, bp, cfg, rules, mesh)
    if cfg.remat:
        body = jax.checkpoint(body)
    if cfg.scan_layers:
        x, _ = jax.lax.scan(lambda c, bp: (body(c, bp), None), x, blocks)
    else:
        for i in range(cfg.n_layer):
            x = body(x, jax.tree.map(lambda a: a[i], blocks))

    return layer_norm(x, params["ln_f_scale"], params["ln_f_bias"])


def gpt_forward(
    params: dict,
    tokens: jax.Array,
    cfg: GPTConfig,
    *,
    rules: ShardingRules | None = None,
    mesh=None,
) -> jax.Array:
    """tokens [B, S] int32 → logits [B, S, vocab] (f32)."""
    x = gpt_hidden(params, tokens, cfg, rules=rules, mesh=mesh)
    wte = params["wte"].astype(cfg.dtype)
    if mesh is not None:
        wte = with_logical_constraint(wte, (None, None), rules, mesh)
    # tied embeddings (GPT-2): output projection = wte^T. Inputs stay bf16
    # so the MXU runs at bf16 rate (the lm-head is ~25% of model FLOPs);
    # accumulation and the returned logits are f32 for a stable softmax.
    logits = jnp.einsum(
        "bsd,vd->bsv", x.astype(cfg.dtype), wte,
        preferred_element_type=jnp.float32,
    )
    return logits


def gpt_loss(
    params: dict,
    batch: dict,
    cfg: GPTConfig,
    *,
    rules: ShardingRules | None = None,
    mesh=None,
) -> jax.Array:
    """Next-token cross-entropy. batch: {"tokens": [B, S+1]} or
    {"inputs": [B,S], "targets": [B,S]}."""
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
        # single-device path: chunked lm-head + CE with closed-form grads
        # (ops/loss.py) — the [B,S,V] logits tensor never exists, which is
        # what lets bs16-32/seq1024 GPT-2 fit a single v5e chip
        from ray_tpu.ops.loss import fused_lm_head_loss

        x = gpt_hidden(params, inputs, cfg, rules=rules, mesh=mesh)
        B, S, D = x.shape
        return fused_lm_head_loss(
            x.reshape(B * S, D),
            params["wte"],
            targets.reshape(B * S).astype(jnp.int32),
            None if mask is None else mask.reshape(B * S).astype(jnp.float32),
        )
    logits = gpt_forward(params, inputs, cfg, rules=rules, mesh=mesh)
    # target log-prob without materializing a [B,S,V] log_softmax: the
    # gather and the logsumexp reduction fuse into the logits producer
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    ll = picked - lse
    if mask is not None:
        return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1)
    return -jnp.mean(ll)


# ----------------------------------------------------------------------------
# KV-cached inference paths (serve/llm engine): what models/cached.py's one
# step needs of this family. Cache layout: [n_layer, num_blocks, block_size,
# n_head, head_dim] (ops/kv_cache.py; block 0 is the garbage sink).
# ----------------------------------------------------------------------------


def _cached_embed(params, tokens, step, cfg: GPTConfig):
    """Learned positional embeddings at the true positions."""
    x = step.take(params["wte"].astype(cfg.dtype), tokens)
    wpe = params["wpe"].astype(cfg.dtype)
    if step.kind == "fresh":
        return x + wpe[: tokens.shape[1]], None
    return x + step.take(wpe, step.table_pos(cfg.max_seq_len)), None


def _cached_layer(x, bp, attend, step, state, cfg: GPTConfig):
    with jax.named_scope("attn_proj"):
        x = _attn_residual(x, attend(*_attn_qkv(x, bp, cfg)), bp, cfg)
    with jax.named_scope("ffn"):
        return _mlp_residual(x, bp, cfg), state


def _final_norm(params, x, cfg: GPTConfig):
    return layer_norm(x, params["ln_f_scale"], params["ln_f_bias"])


def _head(params, h, cfg: GPTConfig):
    # tied embeddings, as in gpt_forward
    return jnp.einsum(
        "...d,vd->...v", h.astype(cfg.dtype), params["wte"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    )


FAMILY = cached.CachedFamily(
    "gpt", GPTConfig, "blocks", _cached_embed, _cached_layer, _final_norm,
    _head)
gpt_prefill, gpt_decode_step, gpt_verify_step = cached.steps(FAMILY)


def gpt_num_params(cfg: GPTConfig) -> int:
    p = gpt_init(jax.random.PRNGKey(0), cfg)
    return sum(x.size for x in jax.tree.leaves(p))
