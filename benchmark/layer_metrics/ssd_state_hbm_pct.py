"""Kernels: the Mamba-2 mixers' one-token update against the HBM roofline in
decode (the kernel ``ssd_step``, ops/ssd.py). Bytes the decode steps of the
traced slice had to move: each ``executor.dispatch`` span's ``rows`` x
(``ssm_n_head`` x (the float32 state ``[P, N]`` read and written whole,
whatever the row's context: 4.19 MB each way a row a layer at the published
widths; and the row's ``x`` columns, ``dt`` and output) + ``ssm_n_group`` x
the float32 B and C vectors, fetched once a group) x layers, over the time
the kernel's calls took inside those steps' runs, over the chip's published
bandwidth (``peaks.json``). The update does ~6 flop for each 8 B it moves:
bandwidth is its roofline. The convolution, the softplus and the gated norm
around the kernel are other operations (``scope_pct.ssd`` holds them) and are
in neither the bytes nor the time; the rows the glue hands the kernel in
float32 (``dt x``, ``D x``, the decay) are the program's choice and not
counted: the share errs low by 0.3%. XLA's formulation (the ``xla`` backend:
gather, update, scatter) has no name in a trace and reads nothing here.
Nothing where the trace holds no such call, the spans carry no ``rows`` or
the configuration has no ``ssm_n_head`` (a checkout from before PR 58 cannot
run the cell at all)."""
import jax

from benchmark import common

KERNEL = "ssd_step"


def ssd_step_bytes(rows: int, n_head: int, head_dim: int, d_state: int,
                   n_group: int, n_layer: int) -> int:
    """Bytes one decode step's SSM mixers must move: every row's float32
    state once each way, its ``x`` columns (2 B), ``dt`` (4 B a head) and
    output (2 B), and B and C in float32 once a group (ops/ssd.py
    ``step_bytes``, written out so that the yardstick does not move with the
    program)."""
    return rows * (n_head * (2 * head_dim * d_state * 4 + head_dim * 2 + 4
                             + head_dim * 2)
                   + n_group * 2 * d_state * 4) * n_layer


def widths_of(keys: dict) -> dict:
    return {"n_head": keys["ssm_n_head"], "head_dim": keys["ssm_head_dim"],
            "d_state": keys["ssm_d_state"], "n_group": keys["ssm_n_group"],
            "n_layer": keys["n_layer"]}


def read(ctx):
    from benchmark import span_reduce

    raw, reduced = span_reduce.load(ctx)
    keys = ctx["config"]["keys"]
    if not reduced or "ssm_n_head" not in keys:
        return None
    widths = widths_of(keys)
    # the one walk over decode steps that hold a named kernel's calls
    walk = common.load_layer_metric("kda_state_hbm_pct").decode_kernel_time
    rows, total_ns, steps = walk(raw, reduced, KERNEL, "rows")
    if not steps:
        return None
    peak = common.peaks_for(jax.devices()[0].device_kind)["hbm_gb_per_s"]
    gb_per_s = ssd_step_bytes(rows, **widths) / total_ns
    common.say(f"ssd state against HBM: {steps} decode runs, "
               f"{rows / steps:.1f} rows a step, "
               f"{total_ns / steps / 1e3:.1f} us a step in the kernel, "
               f"{gb_per_s:.1f} GB/s with {widths}")
    return 100.0 * gb_per_s / peak
