"""Kernels: device time in the chunk summaries' own operations, as a share
of busy time: the ``eva_summarize`` kernel's calls (ops/eva.py: the
program names its kernel so, because inside a scanned stack no operation
carries a leaf's name), and any operation whose text holds the ``eva_phi``
/ ``eva_mu`` leaves (what a plain-XLA formulation outside a scan would
read). The gather that reads a decode row's chunk back and the scatter that
lands a summary read no such name: the share errs low by what they take.
Nothing where no such operation took time.

The kernel has NO roofline metric, and this is why: its operands are the
step's fresh K/V or the gather's result, which the compiler rests in fast
memory (``S(1)`` in the operation's text), so HBM's bandwidth does not
bound it: over the bytes of ``eva_summarize_bytes`` it read 1,247 GB/s,
152% of the chip's 819 (my chip run, PR 32, call 1). What it moves is said
here as information, where the dispatch spans carry ``eva_chunks``."""
from benchmark import common, span_reduce, trace_reduce

KERNEL = "eva_summarize"
NEEDLES = ("eva_summar", "eva_phi", "eva_mu")


def eva_summarize_bytes(chunks: int, chunk_size: int, n_head: int,
                        head_dim: int, itemsize: int, n_layer: int) -> int:
    """Bytes the kernel moves for ``chunks`` chunks a layer: K and V of a
    chunk's positions in, one key and one value out."""
    return chunks * 2 * (chunk_size + 1) * n_head * head_dim * itemsize \
        * n_layer


def say_what_it_moves(ctx) -> None:
    """The chunks the kernel was handed in the slice's steps
    (``eva_chunks`` of each ``executor.dispatch`` span: a decode step's
    rows, a chunk each; a prompt chunk's rows x chunks), their bytes and
    the kernel's time inside those runs."""
    import jax.numpy as jnp

    raw, reduced = span_reduce.load(ctx)
    keys = ctx["config"]["keys"]
    if not reduced or "chunk_size" not in keys:
        return
    widths = {"chunk_size": keys["chunk_size"], "n_head": keys["n_head"],
              "head_dim": keys["d_model"] // keys["n_head"],
              "itemsize": jnp.dtype(keys["dtype"]).itemsize,
              "n_layer": keys["n_layer"]}
    calls = span_reduce.kernel_calls(raw["planes"][0]["ops"], KERNEL)
    total_bytes, total_ns = 0, 0.0
    for step in reduced["steps"]:
        if step["inside"] and "eva_chunks" in step["attrs"]:
            total_bytes += eva_summarize_bytes(
                int(step["attrs"]["eva_chunks"]), **widths)
            total_ns += span_reduce.time_inside(
                calls, step["run"][1], step["run"][2])
    if total_ns > 0:
        common.say(f"chunk summaries: {total_bytes / 1e9:.3f} GB in "
                   f"{total_ns / 1e9:.4f}s, {total_bytes / total_ns:.1f} "
                   f"GB/s out of fast memory (no HBM roofline)")


def read(ctx):
    reduced = ctx.get("trace")
    if not reduced or not any(
            v["self_s"] > 0 and any(n in name for n in NEEDLES)
            for name, v in reduced["ops"].items()):
        return None
    say_what_it_moves(ctx)
    return trace_reduce.ops_share_pct(reduced, *NEEDLES)
