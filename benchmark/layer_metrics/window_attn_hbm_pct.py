"""Kernels: the WINDOWED paged-attention kernel alone against the HBM
roofline in decode. Bytes the sliding layers' calls had to read in the
slice's decode steps (each ``executor.dispatch`` span's
``kv_tokens_window``, a row's ``min(context, window)``, x the sliding
layers x K and V x KV heads x head size x the pool's item size; the layer
count and the widths from the configuration's keys) over the time the
``paged_attention_window`` operations took inside those steps' runs, over
the chip's published bandwidth (``peaks.json``). ``attn_kv_hbm_pct`` reads
both kernels together; where the sliding layers read most of attention's
bytes this is the kernel that sets the step, read alone. The tokens a
window attends are fewer than the pages its kernel copies (the window's
first and last page are partly outside it), so the share errs low. Nothing
where the dispatch spans carry no ``kv_tokens_window`` or no windowed call
ran."""
import jax
import jax.numpy as jnp

from benchmark import common, span_reduce

NEEDLE = "paged_attention_window"


def window_attn_bytes(kv_tokens_window: int, n_sliding: int, n_kv_head: int,
                      head_dim: int, itemsize: int) -> int:
    """Bytes the windowed kernel must read for one decode step: K and V of
    the window's tokens of every row, in each sliding layer."""
    return kv_tokens_window * n_sliding * 2 * n_kv_head * head_dim * itemsize


def read(ctx):
    raw, reduced = span_reduce.load(ctx)
    if not reduced:
        return None
    keys = ctx["config"]["keys"]
    widths = {
        "n_sliding": list(keys.get("layer_types", ())).count(
            "sliding_attention"),
        "n_kv_head": keys.get("n_kv_head"), "head_dim": keys.get("head_dim"),
        "itemsize": jnp.dtype(keys["dtype"]).itemsize,
    }
    calls = span_reduce.kernel_calls(raw["planes"][0]["ops"], NEEDLE)
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
        total_bytes += window_attn_bytes(int(a["kv_tokens_window"]), **widths)
        total_ns += ns
        steps += 1
    if not steps:
        return None
    gb_per_s = total_bytes / total_ns
    peak = common.peaks_for(jax.devices()[0].device_kind)["hbm_gb_per_s"]
    common.say(f"the windowed attention kernel against HBM: {steps} decode "
               f"runs, {total_bytes / steps / 1e9:.3f} GB a step, "
               f"{total_ns / 1e9:.4f}s, {gb_per_s:.1f} GB/s with {widths}")
    return 100.0 * gb_per_s / peak
