"""Kernels: the Mamba-2 mixers' chunked form against the chip's matrix peak
in prefill (``ssd_chunk``, ops/ssd.py). Operations the prefill steps of the
traced slice could not do without BY THE CHUNKED FORM at the piece ``Q`` the
configuration states (``ssm_chunk``: ``mamba_chunk_size`` 128): each
``executor.dispatch`` span's ``tokens`` x 2 x (the table ``C . B`` a GROUP:
``Q N``; a head's table against ``x``: ``Q P``; the carried state's two
products a head, the read ``S C`` and the update: ``2 P N``) x layers, over
the time the form took inside prefill programs, over the published bf16 peak
(``peaks.json``). The products with the state run in float32 at the highest
precision (six passes of the matrix unit each): the share says how far the
form as written is from the peak, not how busy the unit is; and most of the
form's time is elementwise (the decay table ``exp(c_i - c_j)`` a head): it is
informational, as ``kda_prefill_mxu_pct``. Padding tokens of a chunk are work
the form does and the count leaves out: the share errs low.

Which time: the form is XLA's, its operations carry the named scope
``ssd_chunk`` in the compiled programs, and ``benchmark/scope_reduce.py``
books every event of the slice to its scope: the seconds of that scope in
programs of the ``prefill`` kind (a Pallas body of that name would be found
by its calls first). Nothing where no such operation took time, the spans
carry no ``tokens`` or the configuration has no ``ssm_n_head``."""
import jax

from benchmark import common

KERNEL = "ssd_chunk"
KINDS = ("prefill", "prefill_chunk")


def ssd_chunk_flops(tokens: int, n_head: int, head_dim: int, d_state: int,
                    n_group: int, piece: int, n_layer: int) -> int:
    """The chunked form's operations over ``tokens`` tokens (ops/ssd.py
    ``chunk_flops``, written out so that the yardstick does not move with
    the program)."""
    per_token = (n_group * piece * d_state
                 + n_head * (piece * head_dim + 2 * head_dim * d_state))
    return 2 * tokens * per_token * n_layer


def widths_of(keys: dict) -> dict:
    return {"n_head": keys["ssm_n_head"], "head_dim": keys["ssm_head_dim"],
            "d_state": keys["ssm_d_state"], "n_group": keys["ssm_n_group"],
            "piece": keys["ssm_chunk"], "n_layer": keys["n_layer"]}


def read(ctx):
    from benchmark import scope_reduce, span_reduce

    raw, reduced = span_reduce.load(ctx)
    keys = ctx["config"]["keys"]
    if not reduced or "ssm_n_head" not in keys:
        return None
    steps = [s for s in reduced["steps"]
             if s["attrs"].get("kind") in KINDS and s["inside"]
             and "tokens" in s["attrs"]
             and span_reduce.PROGRAM_OF["prefill"] in s["run"][0]]
    if not steps:
        return None
    tokens = sum(int(s["attrs"]["tokens"]) for s in steps)
    calls = span_reduce.kernel_calls(raw["planes"][0]["ops"], KERNEL)
    if calls:
        total_s = sum(span_reduce.time_inside(calls, s["run"][1], s["run"][2])
                      for s in steps) / 1e9
    else:
        out = scope_reduce.table(ctx)
        total_s = out and scope_reduce.seconds(out, (KERNEL,), "prefill")
    if not total_s:
        return None
    widths = widths_of(keys)
    tflops = ssd_chunk_flops(tokens, **widths) / total_s / 1e12
    peak = common.peaks_for(jax.devices()[0].device_kind)["bf16_tflops"]
    common.say(f"ssd chunked form against the matrix peak: {len(steps)} "
               f"prefill runs, {tokens / len(steps):.0f} tokens a step, "
               f"{1e3 * total_s / len(steps):.2f} ms a step in the form, "
               f"{tflops:.2f} TFLOP/s with {widths}")
    return 100.0 * tflops / peak
