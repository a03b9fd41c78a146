"""Kernels: the selected-page attention's share of the HBM roofline in
decode (``paged_attention_sparse``, ops/sparse_select.py). Bytes the decode
steps of the traced slice had to read of K and V: each ``executor.dispatch``
span's ``sel_blocks`` (summed over the step's rows, the blocks ONE K/V head
of one selecting layer attends: ``topk`` = 64 for a row at or past
``dense_len``, every block up to its own for a row below it, which the same
kernel serves) x the block's 64 tokens x ``head_dim`` x 2 (K and V) x the
item size x K/V heads x selecting layers, over the time the kernel's calls
took inside those steps' runs, over the chip's published bandwidth
(``peaks.json``). 4.19 MB a sparse row-step a layer at the published widths
(64 blocks x 64 x 2 heads x 128 x 2 x 2 B), whatever the row's context: the
NEEDED bytes, whatever the layout makes the kernel fetch (it copies one
head's 128 lanes of a page, so it fetches what it needs; the last block's
tokens past the query are fetched and masked: the share errs low). At a
group of 16 query heads the kernel does 2 x 16 x 2 x 128 flop for each 2 x
128 x 2 B: 16 flop/B, far left of the v5e's ridge (240): bandwidth is its
roofline. Nothing where the trace holds no such call or the spans no
``sel_blocks`` (the parent of the PR that brought them)."""
import jax
import jax.numpy as jnp

from benchmark import common, span_reduce

NEEDLE = "paged_attention_sparse"


def sparse_attn_bytes(sel_blocks: int, block_size: int, n_kv_head: int,
                      head_dim: int, itemsize: int, n_layer: int) -> int:
    """Bytes one decode step's selected-page attention must read:
    ``sel_blocks`` blocks a K/V head a layer, K and V of each."""
    return (sel_blocks * block_size * n_kv_head * head_dim * 2 * itemsize
            * n_layer)


def widths_of(keys: dict) -> dict:
    return {"block_size": keys["sparse_block_size"],
            "n_kv_head": keys["n_kv_head"], "head_dim": keys["head_dim"],
            "itemsize": jnp.dtype(keys["dtype"]).itemsize,
            "n_layer": list(keys["mixer_types"]).count("minicpm4")}


def read(ctx):
    raw, reduced = span_reduce.load(ctx)
    keys = ctx["config"]["keys"]
    if not reduced or "sparse_block_size" not in keys:
        return None
    widths = widths_of(keys)
    calls = span_reduce.kernel_calls(raw["planes"][0]["ops"], NEEDLE)
    blocks, total_ns, steps = 0, 0.0, 0
    for step in reduced["steps"]:
        a = step["attrs"]
        if a.get("kind") != "decode" or not step["inside"] \
                or "sel_blocks" not in a \
                or span_reduce.PROGRAM_OF["decode"] not in step["run"][0]:
            continue
        ns = span_reduce.time_inside(calls, step["run"][1], step["run"][2])
        if ns <= 0:
            continue
        blocks += int(a["sel_blocks"])
        total_ns += ns
        steps += 1
    if not steps:
        return None
    peak = common.peaks_for(jax.devices()[0].device_kind)["hbm_gb_per_s"]
    gb_per_s = sparse_attn_bytes(blocks, **widths) / total_ns
    common.say(f"selected-page attention against HBM: {steps} decode runs, "
               f"{blocks / steps:.0f} blocks a K/V head a layer a step, "
               f"{total_ns / steps / 1e3:.1f} us a step in the kernel, "
               f"{gb_per_s:.1f} GB/s with {widths}")
    return 100.0 * gb_per_s / peak
