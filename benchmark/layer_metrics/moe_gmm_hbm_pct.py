"""Kernels: the grouped expert product's share of the HBM roofline in
decode. Bytes the decode steps of the traced slice had to read of expert
weights (the window's mean ``moe_expert_reads_decode`` per decode step —
the experts that got at least one token, summed over the expert layers —
times each expert's three matrices, times the slice's decode runs) over
the time the ``moe_gmm`` operations took inside those runs, over the
chip's published bandwidth (``peaks.json``). Decode at a full batch meets
a few rows an expert, so the weights' bytes are the floor; the rows read
and written are a hundredth of them and left out, so the share errs low."""
import jax
import jax.numpy as jnp

from benchmark import common, span_reduce

NEEDLES = ("moe_gmm", "ragged-dot")


def moe_gmm_bytes(expert_reads: float, d_model: int, d_expert: int,
                  itemsize: int) -> float:
    """Bytes of expert weights one decode step must read: for each expert
    that got a token, gate and up ``[D, 2F]`` and down ``[F, D]``."""
    return expert_reads * 3 * d_model * d_expert * itemsize


def read(ctx):
    reads = span_reduce.counter_delta(ctx, "moe_expert_reads_decode")
    steps = span_reduce.counter_delta(ctx, "decode_steps")
    if not reads or not steps:
        return None
    raw, reduced = span_reduce.load(ctx)
    if not reduced:
        return None
    calls = [(s, e) for name, s, e in raw["planes"][0]["ops"]
             if any(n in name for n in NEEDLES)]
    runs, total_ns = 0, 0.0
    for step in reduced["steps"]:
        if step["attrs"].get("kind") != "decode" or not step["inside"] \
                or span_reduce.PROGRAM_OF["decode"] not in step["run"][0]:
            continue
        ns = span_reduce.time_inside(calls, step["run"][1], step["run"][2])
        if ns > 0:
            total_ns += ns
            runs += 1
    if not runs:
        return None
    keys = ctx["config"]["keys"]
    per_step = moe_gmm_bytes(reads / steps, keys["d_model"], keys["d_expert"],
                             jnp.dtype(keys["dtype"]).itemsize)
    gb_per_s = per_step * runs / total_ns
    peak = common.peaks_for(jax.devices()[0].device_kind)["hbm_gb_per_s"]
    common.say(f"grouped expert product against HBM: {runs} decode runs, "
               f"{reads / steps:.1f} experts read a step, "
               f"{per_step / 1e9:.3f} GB a step, {total_ns / 1e9:.4f}s, "
               f"{gb_per_s:.1f} GB/s")
    return 100.0 * gb_per_s / peak
