"""Plain reference of the llama family's forward pass: straightforward
``jax.numpy`` in float32, no kernel, no cache, no batching tricks.

Follows the public LLaMA / Mistral formulation: token embedding; per layer
RMSNorm, Q/K/V projections, rotary embedding in the "rotate half" form,
grouped-query causal softmax attention, output projection, residual;
RMSNorm, SwiGLU (gate and up packed in one ``[D, 2M]`` matrix, gate first),
residual; final RMSNorm; an untied output head. Dense layers only.

Departure, noted: RMSNorm's epsilon is a parameter of this function and is
given the program's 1e-6 (Mistral publishes 1e-5; the program has no
setting for it, and the reference has to compute what the program claims
to compute).

Reads the program's parameter tree (``models/llama.py llama_init``) and
nothing else of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ENGINE_MODEL = "llama"


def config_class():
    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig


def init_fn():
    from ray_tpu.models.llama import llama_init

    return llama_init


def loss_fn():
    """The program's own loss, for a training cell of this family."""
    from ray_tpu.models.llama import llama_loss

    return llama_loss


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """x [B, S, H, hd]: rotate the two halves of each head by the angle of
    its position."""
    S, hd = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.outer(jnp.arange(S, dtype=jnp.float32), inv_freq)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def hidden(params: dict, tokens, cfg, eps: float = 1e-6):
    """tokens [B, S] -> final hidden states [B, S, D], float32."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: jnp.asarray(a, jnp.float32)
        B, S = tokens.shape
        Hq, Hkv, hd = cfg.n_head, cfg.n_kv_head, cfg.d_model // cfg.n_head
        x = f32(params["wte"])[tokens]
        causal = jnp.tril(jnp.ones((S, S), bool))
        blocks = params["blocks"]
        for i in range(cfg.n_layer):
            bp = {k: f32(v[i]) for k, v in blocks.items()}
            h = _rms_norm(x, bp["ln1_scale"], eps)
            q = _rope((h @ bp["wq"]).reshape(B, S, Hq, hd), cfg.rope_theta)
            k = _rope((h @ bp["wk"]).reshape(B, S, Hkv, hd), cfg.rope_theta)
            v = (h @ bp["wv"]).reshape(B, S, Hkv, hd)
            k = jnp.repeat(k, Hq // Hkv, axis=2)
            v = jnp.repeat(v, Hq // Hkv, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(hd))
            s = jnp.where(causal[None, None], s, -jnp.inf)
            a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
            x = x + a.reshape(B, S, Hq * hd) @ bp["wo"]
            h = _rms_norm(x, bp["ln2_scale"], eps)
            gate, up = jnp.split(h @ bp["mlp_in"], 2, axis=-1)
            x = x + (jax.nn.silu(gate) * up) @ bp["mlp_out"]
        return _rms_norm(x, f32(params["ln_f_scale"]), eps)


def logits_at(params: dict, tokens, positions, cfg):
    """Float32 logits [B, P, V] at ``positions`` [B, P] of ``tokens``
    [B, S]: only the rows that are asked for meet the output head."""
    with jax.default_matmul_precision("highest"):
        x = hidden(params, tokens, cfg)
        rows = jnp.take_along_axis(x, positions[..., None], axis=1)
        return rows @ jnp.asarray(params["lm_head"], jnp.float32)


def logits(params: dict, tokens, cfg):
    """Float32 logits [B, S, V] at every position."""
    with jax.default_matmul_precision("highest"):
        return hidden(params, tokens, cfg) @ jnp.asarray(
            params["lm_head"], jnp.float32)
