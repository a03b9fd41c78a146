"""Kernels: the latent attention kernel's share of the HBM roofline in
decode. Bytes the decode steps of the traced slice had to read of latent
rows (``kv_tokens`` of each ``executor.dispatch`` span: the rows' contexts
in whole blocks, x (``kv_lora_rank`` + ``qk_rope_head_dim``) numbers a row x
the item size x layers, widths from the configuration's ``keys``) over the
time the ``paged_attention_latent`` operations took inside those steps'
runs, over the chip's published bandwidth (``peaks.json``). The bytes are
what the layer's mathematics must read, 1,152 B a token a layer at the
published widths, whatever implements or stores it: a pool that pads a row
(the rotary plane at whole lanes, +11%) reads as a LOWER share, never a
higher. Every head reads the same row, so at 128 heads the kernel does 2 x
128 x (576 + 512) flop for each 1,152 B: 242 flop/B against the v5e's
ridge of 240; the same time against the chip's matrix peak reads within
0.5% of this share (the reader SAYS both): one metric, not two. Nothing
where the trace holds no latent call."""
import jax
import jax.numpy as jnp

from benchmark import common, span_reduce

NEEDLE = "paged_attention_latent"


def latent_attn_bytes(kv_tokens: int, kv_lora_rank: int,
                      qk_rope_head_dim: int, itemsize: int,
                      n_layer: int) -> int:
    """Bytes one decode step's latent attention must read: ONE row a token
    of context, the latent vector and the key's rotary rest, in every
    layer, whatever the number of heads."""
    return kv_tokens * (kv_lora_rank + qk_rope_head_dim) * itemsize * n_layer


def latent_attn_flops(kv_tokens: int, n_head: int, kv_lora_rank: int,
                      qk_rope_head_dim: int, n_layer: int) -> int:
    """... and the products it cannot do without in the absorbed form:
    every head's score over the whole row and its sum of latent parts."""
    return 2 * kv_tokens * n_head * (
        2 * kv_lora_rank + qk_rope_head_dim) * n_layer


def widths_of(keys: dict) -> dict:
    return {"kv_lora_rank": keys["kv_lora_rank"],
            "qk_rope_head_dim": keys["qk_rope_head_dim"],
            "itemsize": jnp.dtype(keys["dtype"]).itemsize,
            "n_layer": keys["n_layer"]}


def read(ctx):
    raw, reduced = span_reduce.load(ctx)
    keys = ctx["config"]["keys"]
    if not reduced or "kv_lora_rank" not in keys:
        return None
    widths = widths_of(keys)
    calls = span_reduce.kernel_calls(raw["planes"][0]["ops"], NEEDLE)
    total_tokens, total_ns, steps = 0, 0.0, 0
    for step in reduced["steps"]:
        a = step["attrs"]
        if a.get("kind") != "decode" or not step["inside"] \
                or "kv_tokens" not in a \
                or span_reduce.PROGRAM_OF["decode"] not in step["run"][0]:
            continue
        ns = span_reduce.time_inside(calls, step["run"][1], step["run"][2])
        if ns <= 0:
            continue
        total_tokens += int(a["kv_tokens"])
        total_ns += ns
        steps += 1
    if not steps:
        return None
    peaks = common.peaks_for(jax.devices()[0].device_kind)
    gb_per_s = latent_attn_bytes(total_tokens, **widths) / total_ns
    tflops = latent_attn_flops(
        total_tokens, keys["n_head"], keys["kv_lora_rank"],
        keys["qk_rope_head_dim"], keys["n_layer"]) / total_ns / 1e3
    common.say(f"latent attention against HBM: {steps} decode runs, "
               f"{total_tokens / steps:.0f} rows of context a step, "
               f"{total_ns / 1e9:.4f}s, {gb_per_s:.1f} GB/s "
               f"({100.0 * gb_per_s / peaks['hbm_gb_per_s']:.2f}% of the "
               f"bandwidth); the same time against the matrix peak: "
               f"{tflops:.1f} TFLOP/s "
               f"({100.0 * tflops / peaks['bf16_tflops']:.2f}%)")
    return 100.0 * gb_per_s / peaks["hbm_gb_per_s"]
