"""Kernels: the paged-attention kernel's share of the HBM roofline in
decode, on what a COMPOSED table makes it read (models/evabyte.py): each
row's exact K/V of its own window (``kv_tokens_window`` of an
``executor.dispatch`` span: the sum over rows of ``t mod W + 1``) and one
summary a chunk of every window closed before it (``kv_chunks``: the sum of
``(W / C) (t // W)``), a summary having a token's shape, x K and V x heads x
head size x the pool's item size x layers (widths from the configuration's
keys), over the time the ``paged_attention`` operations took inside those
steps' runs, over the chip's published bandwidth (``peaks.json``). The
bytes are what the layer's mathematics must read, whatever implements it:
the pages the kernel copies are whole blocks, so the share errs low by up
to a block a row. Nothing where the dispatch spans carry no ``kv_chunks``
(a program without composed tables)."""
import jax
import jax.numpy as jnp

from benchmark import common, span_reduce


def eva_attn_bytes(kv_tokens_window: int, kv_chunks: int, n_head: int,
                   head_dim: int, itemsize: int, n_layer: int) -> int:
    """Bytes one decode step's attention must read: K and V of every
    row's window rows and of every summary it sees, in every layer."""
    return (kv_tokens_window + kv_chunks) * 2 * n_head * head_dim \
        * itemsize * n_layer


def widths_of(keys: dict) -> dict:
    return {"n_head": keys["n_head"],
            "head_dim": keys["d_model"] // keys["n_head"],
            "itemsize": jnp.dtype(keys["dtype"]).itemsize,
            "n_layer": keys["n_layer"]}


def read(ctx):
    raw, reduced = span_reduce.load(ctx)
    if not reduced:
        return None
    widths = widths_of(ctx["config"]["keys"])
    calls = span_reduce.kernel_calls(raw["planes"][0]["ops"],
                                     "paged_attention")
    total_bytes, total_ns, steps = 0, 0.0, 0
    for step in reduced["steps"]:
        a = step["attrs"]
        if a.get("kind") != "decode" or not step["inside"] \
                or "kv_chunks" not in a \
                or span_reduce.PROGRAM_OF["decode"] not in step["run"][0]:
            continue
        ns = span_reduce.time_inside(calls, step["run"][1], step["run"][2])
        if ns <= 0:
            continue
        total_bytes += eva_attn_bytes(
            int(a["kv_tokens_window"]), int(a["kv_chunks"]), **widths)
        total_ns += ns
        steps += 1
    if not steps:
        return None
    gb_per_s = total_bytes / total_ns
    peak = common.peaks_for(jax.devices()[0].device_kind)["hbm_gb_per_s"]
    common.say(f"composed-table attention against HBM: {steps} decode "
               f"runs, {total_bytes / steps / 1e9:.3f} GB a step, "
               f"{total_ns / 1e9:.4f}s, {gb_per_s:.1f} GB/s with {widths}")
    return 100.0 * gb_per_s / peak
