"""Kernels: the lightning layers' one-token update against the HBM roofline
in decode (``lightning_step``, ops/lightning.py). Bytes the decode steps of
the traced slice had to move of recurrent state: each ``executor.dispatch``
span's ``rows`` x ``lightning_n_head`` x ``head_dim`` x ``head_dim`` float32
x 2 (a row's state is read and written whole, whatever its context: 2.10 MB
each way a row a layer at the published widths) x lightning layers, over
the time the update's operations took inside those steps' runs, over the
chip's published bandwidth (``peaks.json``). The update does 4 flop for
each 8 B it moves: bandwidth is its roofline.

Which operations: a Pallas body is named ``lightning_step``; XLA's
formulation has no name of its own in a trace (an operation is named by
its HLO text, and a ``jax.named_scope`` is not part of it), so its
operations are found by what they hold: the state's printed type,
``f32[<rows>,<heads>,<hd>,<hd>]``, as result or operand (the slots' gather,
the decay-and-add, the product with the query, the scatter back). The q / k
/ v projections and norms before it hold no such type and are left out: the
share errs high by their absence from the time, never past what the bytes
allow. Nothing where no such operation took time or the spans carry no
``rows``."""
import re

import jax

from benchmark import common, span_reduce

KERNEL = "lightning_step"


def lightning_state_bytes(rows: int, n_head: int, head_dim: int,
                          n_layer: int) -> int:
    """Bytes one decode step's lightning layers must move: every row's
    float32 state, read and written."""
    return rows * n_head * head_dim * head_dim * 4 * 2 * n_layer


def widths_of(keys: dict) -> dict:
    return {"n_head": keys["lightning_n_head"],
            "head_dim": keys["lightning_head_dim"],
            "n_layer": list(keys["mixer_types"]).count("lightning-attn")}


def state_type(keys: dict) -> "re.Pattern":
    """The printed type of some rows' states, any count of leading axes."""
    h, d = keys["lightning_n_head"], keys["lightning_head_dim"]
    return re.compile(rf"f32\[(\d+,)+{h},{d},{d}\]")


def state_calls(ops: list[tuple], keys: dict) -> list[tuple]:
    """``(start, end)`` of the operations that are the update: the named
    kernel's calls, else the operations that hold the state's type."""
    named = span_reduce.kernel_calls(ops, KERNEL)
    if named:
        return named
    typed = state_type(keys)
    return [(s, e) for name, s, e in ops
            if typed.search(name) and " while(" not in name
            and " conditional(" not in name]


def read(ctx):
    raw, reduced = span_reduce.load(ctx)
    keys = ctx["config"]["keys"]
    if not reduced or "lightning_n_head" not in keys:
        return None
    widths = widths_of(keys)
    calls = state_calls(raw["planes"][0]["ops"], keys)
    rows, total_ns, steps = 0, 0.0, 0
    for step in reduced["steps"]:
        a = step["attrs"]
        if a.get("kind") != "decode" or not step["inside"] \
                or "rows" not in a \
                or span_reduce.PROGRAM_OF["decode"] not in step["run"][0]:
            continue
        ns = span_reduce.time_inside(calls, step["run"][1], step["run"][2])
        if ns <= 0:
            continue
        rows += int(a["rows"])
        total_ns += ns
        steps += 1
    if not steps:
        return None
    peak = common.peaks_for(jax.devices()[0].device_kind)["hbm_gb_per_s"]
    gb_per_s = lightning_state_bytes(rows, **widths) / total_ns
    common.say(f"lightning state against HBM: {steps} decode runs, "
               f"{rows / steps:.1f} rows a step, "
               f"{total_ns / steps / 1e3:.1f} us a step in the update, "
               f"{gb_per_s:.1f} GB/s with {widths}")
    return 100.0 * gb_per_s / peak
