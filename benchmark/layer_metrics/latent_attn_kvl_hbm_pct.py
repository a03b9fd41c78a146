"""Kernels: the latent attention kernel's share of the HBM roofline in
decode, for a family whose pool spans only SOME of its layers
(models/ling_hybrid.py: one latent layer of seven). The bytes and the time
are ``latent_attn_hbm_pct``'s (``kv_tokens`` of each decode step's
``executor.dispatch`` span x (``kv_lora_rank`` + ``qk_rope_head_dim``)
numbers a row x the item size, over the time the ``paged_attention_latent``
operations took inside those steps' runs, over the chip's published
bandwidth), but the LAYERS are asked of the configuration: the program's
config class built from ``keys`` says how many layers cache a latent row
(``n_kv_layer``), where ``latent_attn_hbm_pct`` multiplies by ``n_layer``
and would read seven times too high here. One reader for every latent
cell is a later ``benchmark`` issue's (PERF.md section 7 z). Nothing where
the trace holds no latent call or the configuration names no such count."""
import jax
import jax.numpy as jnp

from benchmark import common, span_reduce

NEEDLE = "paged_attention_latent"


def latent_attn_bytes(kv_tokens: int, kv_lora_rank: int,
                      qk_rope_head_dim: int, itemsize: int,
                      n_kv_layer: int) -> int:
    """Bytes one decode step's latent attention must read: ONE row a token
    of context in every layer THAT CACHES ONE."""
    return kv_tokens * (kv_lora_rank + qk_rope_head_dim) * itemsize \
        * n_kv_layer


def widths_of(keys: dict, model_config) -> dict:
    return {"kv_lora_rank": keys["kv_lora_rank"],
            "qk_rope_head_dim": keys["qk_rope_head_dim"],
            "itemsize": jnp.dtype(keys["dtype"]).itemsize,
            "n_kv_layer": int(model_config.n_kv_layer)}


def read(ctx):
    raw, reduced = span_reduce.load(ctx)
    keys = ctx["config"]["keys"]
    cfg = ctx.get("model_config")
    if not reduced or "kv_lora_rank" not in keys \
            or not hasattr(cfg, "n_kv_layer"):
        return None
    widths = widths_of(keys, cfg)
    total_tokens, total_ns, steps = common.load_named(
        "layer_metrics", "kda_state_hbm_pct").decode_kernel_time(
            raw, reduced, NEEDLE, "kv_tokens")
    if not steps:
        return None
    peak = common.peaks_for(jax.devices()[0].device_kind)["hbm_gb_per_s"]
    gb_per_s = latent_attn_bytes(total_tokens, **widths) / total_ns
    common.say(f"latent attention against HBM over {widths['n_kv_layer']} "
               f"pool layer(s): {steps} decode runs, "
               f"{total_tokens / steps:.0f} rows of context a step, "
               f"{total_ns / steps / 1e3:.1f} us a step in the kernel, "
               f"{gb_per_s:.1f} GB/s")
    return 100.0 * gb_per_s / peak
