"""Kernels: the paged kernel's share of the HBM roofline where a decode
step carries a BLOCK a row (generation by diffusion over blocks): all W
queries of a row share one frontier, the block's end, so the call is the
decode kernel's at W x the query rows a K/V head (32 at W = 4 over groups
of 8) and reads a row's pages once a K/V head. Bytes the kernel had to
read in the slice's decode steps: each ``executor.dispatch`` span's
``kv_tokens`` (the rows' contexts to their block's END, in whole pages) x
(K, V) x KV heads x head size x the pool's item size x layers
(``block_attn_bytes``; serve/llm/engine.py counts the same tokens, tested
equal), over the time the ``paged_attention`` calls took inside those
steps' runs, over the chip's published bandwidth (``peaks.json``). Only the
spans that say ``block_len`` are read: nothing for an autoregressive
family, whose share is ``paged_attn_hbm_pct``."""
import jax
import jax.numpy as jnp

from benchmark import common, span_reduce

KERNEL = "paged_attention"


def block_attn_bytes(kv_tokens: int, n_kv_head: int, head_dim: int,
                     itemsize: int, n_layer: int) -> int:
    """Bytes the attention calls of a decode step must read: K and V of
    every context token, every layer."""
    return kv_tokens * 2 * n_kv_head * head_dim * itemsize * n_layer


def read(ctx):
    raw, reduced = span_reduce.load(ctx)
    keys = ctx["config"]["keys"]
    if not reduced or "block_length" not in keys:
        return None
    widths = {"n_kv_head": keys["n_kv_head"], "head_dim": keys["head_dim"],
              "itemsize": jnp.dtype(keys["dtype"]).itemsize,
              "n_layer": keys["n_layer"]}
    calls = span_reduce.kernel_calls(raw["planes"][0]["ops"], KERNEL)
    tokens, total_ns, steps = 0, 0.0, 0
    for step in reduced["steps"]:
        a = step["attrs"]
        if a.get("kind") != "decode" or not step["inside"] \
                or "block_len" not in a or "kv_tokens" not in a \
                or span_reduce.PROGRAM_OF["decode"] not in step["run"][0]:
            continue
        ns = span_reduce.time_inside(calls, step["run"][1], step["run"][2])
        if ns <= 0:
            continue
        tokens += int(a["kv_tokens"])
        total_ns += ns
        steps += 1
    if not steps:
        return None
    peak = common.peaks_for(jax.devices()[0].device_kind)["hbm_gb_per_s"]
    gb_per_s = block_attn_bytes(tokens, **widths) / total_ns
    common.say(f"block attention against HBM: {steps} decode runs, "
               f"{tokens / steps:.0f} context tokens a step, "
               f"{total_ns / steps / 1e3:.1f} us a step in the kernel, "
               f"{gb_per_s:.1f} GB/s with {widths}")
    return 100.0 * gb_per_s / peak
