"""Kernels: both paged-attention kernels' share of the HBM roofline in
decode, at a model whose layers do not all read the same context. Bytes
the kernels had to read in the slice's decode steps (each
``executor.dispatch`` span's ``kv_tokens`` x the layers that keep every
token + its ``kv_tokens_window`` x the sliding layers, x K and V x KV
heads x head size x the pool's item size; the layer counts and the widths
from the configuration's keys) over the time the ``paged_attention`` and
``paged_attention_window`` operations took inside those steps' runs, over
the chip's published bandwidth (``peaks.json``). ``kv_tokens`` counts a
row's context in whole blocks and ``kv_tokens_window`` the tokens a
sliding layer attends, ``min(context, window)``, which is less than the
pages its kernel copies (the window's first and last page are partly
outside it), so the share errs low. Nothing where the dispatch spans
carry no ``kv_tokens_window`` (a program without windowed layers)."""
import jax
import jax.numpy as jnp

from benchmark import common, span_reduce


def attn_kv_bytes(kv_tokens: int, kv_tokens_window: int, n_full: int,
                  n_sliding: int, n_kv_head: int, head_dim: int,
                  itemsize: int) -> int:
    """Bytes the two kernels must read for one decode step: K and V of
    every row's whole context in each full layer, and of the window's
    tokens in each sliding layer."""
    per_token = 2 * n_kv_head * head_dim * itemsize
    return (kv_tokens * n_full + kv_tokens_window * n_sliding) * per_token


def read(ctx):
    raw, reduced = span_reduce.load(ctx)
    if not reduced:
        return None
    keys = ctx["config"]["keys"]
    kinds = list(keys.get("layer_types", ()))
    widths = {
        "n_full": kinds.count("full_attention"),
        "n_sliding": kinds.count("sliding_attention"),
        "n_kv_head": keys.get("n_kv_head"), "head_dim": keys.get("head_dim"),
        "itemsize": jnp.dtype(keys["dtype"]).itemsize,
    }
    # both kernels' names hold the first's
    calls = span_reduce.kernel_calls(raw["planes"][0]["ops"],
                                     "paged_attention")
    total_bytes, total_ns, steps = 0, 0.0, 0
    for step in reduced["steps"]:
        a = step["attrs"]
        if a.get("kind") != "decode" or not step["inside"] \
                or "kv_tokens_window" not in a \
                or span_reduce.PROGRAM_OF["decode"] not in step["run"][0]:
            continue
        ns = span_reduce.time_inside(calls, step["run"][1], step["run"][2])
        if ns <= 0:
            continue
        total_bytes += attn_kv_bytes(
            int(a["kv_tokens"]), int(a["kv_tokens_window"]), **widths)
        total_ns += ns
        steps += 1
    if not steps:
        return None
    gb_per_s = total_bytes / total_ns
    peak = common.peaks_for(jax.devices()[0].device_kind)["hbm_gb_per_s"]
    common.say(f"both attention kernels against HBM: {steps} decode runs, "
               f"{total_bytes / steps / 1e9:.3f} GB a step, "
               f"{total_ns / 1e9:.4f}s, {gb_per_s:.1f} GB/s with {widths}")
    return 100.0 * gb_per_s / peak
