"""Plain reference of the GPT-2 family's forward pass and loss:
straightforward ``jax.numpy`` in float32, no kernel, no cache, no fused
loss.

Follows the published GPT-2: token plus learned position embedding; per
layer LayerNorm (eps 1e-5), one fused QKV projection with bias, causal
softmax attention over ``n_head`` heads, output projection with bias,
residual; LayerNorm, MLP with the tanh approximation of GELU, residual;
final LayerNorm; the output head tied to the token embedding. The loss is
the mean next-token cross-entropy.

Reads the program's parameter tree (``models/gpt.py gpt_init``) and nothing
else of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ENGINE_MODEL = "gpt"


def config_class():
    from ray_tpu.models.gpt import GPTConfig

    return GPTConfig


def init_fn():
    from ray_tpu.models.gpt import gpt_init

    return gpt_init


def loss_fn():
    """The program's own loss, which the training cell differentiates."""
    from ray_tpu.models.gpt import gpt_loss

    return gpt_loss


def _layer_norm(x, scale, bias, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def hidden(params: dict, tokens, cfg):
    """tokens [B, S] -> final hidden states [B, S, D], float32."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: jnp.asarray(a, jnp.float32)
        B, S = tokens.shape
        H, hd = cfg.n_head, cfg.d_model // cfg.n_head
        x = f32(params["wte"])[tokens] + f32(params["wpe"])[:S]
        causal = jnp.tril(jnp.ones((S, S), bool))
        blocks = params["blocks"]
        for i in range(cfg.n_layer):
            bp = {k: f32(v[i]) for k, v in blocks.items()}
            h = _layer_norm(x, bp["ln1_scale"], bp["ln1_bias"])
            q, k, v = jnp.split(h @ bp["qkv_w"] + bp["qkv_b"], 3, axis=-1)
            q, k, v = (t.reshape(B, S, H, hd) for t in (q, k, v))
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(hd))
            s = jnp.where(causal[None, None], s, -jnp.inf)
            a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
            x = x + a.reshape(B, S, H * hd) @ bp["proj_w"] + bp["proj_b"]
            h = _layer_norm(x, bp["ln2_scale"], bp["ln2_bias"])
            h = _gelu_tanh(h @ bp["mlp_in_w"] + bp["mlp_in_b"])
            x = x + h @ bp["mlp_out_w"] + bp["mlp_out_b"]
        return _layer_norm(x, f32(params["ln_f_scale"]),
                           f32(params["ln_f_bias"]))


def logits_at(params: dict, tokens, positions, cfg):
    """Float32 logits [B, P, V] at ``positions`` [B, P] of ``tokens``."""
    with jax.default_matmul_precision("highest"):
        x = hidden(params, tokens, cfg)
        rows = jnp.take_along_axis(x, positions[..., None], axis=1)
        return rows @ jnp.asarray(params["wte"], jnp.float32).T


def logits(params: dict, tokens, cfg):
    with jax.default_matmul_precision("highest"):
        return hidden(params, tokens, cfg) @ jnp.asarray(
            params["wte"], jnp.float32).T


def loss(params: dict, tokens, cfg):
    """Mean next-token cross-entropy of ``tokens`` [B, S+1], float32."""
    lg = logits(params, tokens[:, :-1], cfg)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)
