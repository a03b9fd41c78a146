"""Kernels: the KDA layers' one-token update against the HBM roofline in
decode (the kernel ``kda_step``, ops/kda.py). Bytes the decode steps of the
traced slice had to move: each ``executor.dispatch`` span's ``rows`` x
``kda_n_head`` x (the float32 state ``[K, V]`` read and written whole,
whatever the row's context: 2.10 MB each way a row a layer at the
published widths; and the row's float32 columns, the decay, ``beta k``,
``k`` and ``q``, its value row and its output) x KDA layers, over the time
the kernel's calls took inside those steps' runs, over the chip's
published bandwidth (``peaks.json``). The update does ~8 flop for each 8 B
it moves: bandwidth is its roofline. The convolution, the gate and the
norms around the kernel are other operations (``scope_pct.kda`` holds
them) and are in neither the bytes nor the time. XLA's formulation (the
``xla`` backend: gather, update, scatter) has no name in a trace and reads
nothing here. Nothing where the trace holds no such call or the spans
carry no ``rows``."""
import jax

from benchmark import common, span_reduce

KERNEL = "kda_step"


def kda_step_bytes(rows: int, n_head: int, head_dim: int,
                   n_layer: int) -> int:
    """Bytes one decode step's KDA layers must move: every row's float32
    state once each way, four float32 columns of K, a float32 value row and
    the output row in bfloat16 (ops/kda.py ``step_bytes``, written out so
    that the yardstick does not move with the program)."""
    k = v = head_dim
    return rows * n_head * (2 * k * v * 4 + 4 * k * 4 + v * 4 + v * 2) \
        * n_layer


def widths_of(keys: dict) -> dict:
    return {"n_head": keys["kda_n_head"], "head_dim": keys["kda_head_dim"],
            "n_layer": list(keys["layer_types"]).count("kda")}


def decode_kernel_time(raw, reduced, needle: str, attr: str):
    """``(sum of the spans' attr, ns in the kernel, steps)`` over the decode
    steps of the slice that lie inside it, carry ``attr`` on their
    ``executor.dispatch`` span and whose run holds a call named
    ``*needle*`` (``latent_attn_kvl_hbm_pct`` reads through it too)."""
    calls = span_reduce.kernel_calls(raw["planes"][0]["ops"], needle)
    total, total_ns, steps = 0, 0.0, 0
    for step in reduced["steps"]:
        a = step["attrs"]
        if a.get("kind") != "decode" or not step["inside"] \
                or attr not in a \
                or span_reduce.PROGRAM_OF["decode"] not in step["run"][0]:
            continue
        ns = span_reduce.time_inside(calls, step["run"][1], step["run"][2])
        if ns <= 0:
            continue
        total += int(a[attr])
        total_ns += ns
        steps += 1
    return total, total_ns, steps


def read(ctx):
    raw, reduced = span_reduce.load(ctx)
    keys = ctx["config"]["keys"]
    if not reduced or "kda_n_head" not in keys:
        return None
    widths = widths_of(keys)
    rows, total_ns, steps = decode_kernel_time(raw, reduced, KERNEL, "rows")
    if not steps:
        return None
    peak = common.peaks_for(jax.devices()[0].device_kind)["hbm_gb_per_s"]
    gb_per_s = kda_step_bytes(rows, **widths) / total_ns
    common.say(f"kda state against HBM: {steps} decode runs, "
               f"{rows / steps:.1f} rows a step, "
               f"{total_ns / steps / 1e3:.1f} us a step in the kernel, "
               f"{gb_per_s:.1f} GB/s with {widths}")
    return 100.0 * gb_per_s / peak
