"""Kernels: the KDA layers' chunked form against the chip's matrix peak in
prefill (``kda_chunk``, ops/kda.py). Operations the prefill steps of the
traced slice could not do without BY THE CHUNKED FORM at the chunk ``C`` the
program uses (``ops.kda.CHUNK`` = 64 tokens): each ``executor.dispatch``
span's ``tokens`` x ``kda_n_head`` x 2 x (the two tables against the keys,
``A`` and the query-key table: ``2 C K``; the triangular solve counted as
the product it replaces: ``C C``; its inverse against ``[beta K e^G | beta
V]``: ``C (K + V)``; the carried state's three products, ``W S``, ``q S`` and
the update: ``3 K V``; the table against the pseudo-values: ``C V``) x KDA
layers, over the time the form took inside prefill programs, over the
published bf16 peak (``peaks.json``). The products with the state and the
solve run in float32 at the highest precision (six passes of the matrix
unit each): the share says how far the form as written is from the peak,
not how busy the unit is. Padding tokens of a chunk are work the form does
and the count leaves out: the share errs low.

Which time: a Pallas body would be named ``kda_chunk`` in the trace; while
the form is XLA's, its operations carry the named scope ``kda_chunk`` in
the compiled programs, and ``benchmark/scope_reduce.py`` books every
event of the slice to its scope: the seconds of that scope in programs of
the ``prefill`` kind. The table books a run that the window cuts with its
part inside, where the spans count whole steps: of ~100 runs a slice, two.
Nothing where no such operation took time or the spans carry no
``tokens``."""
import jax

from benchmark import common, scope_reduce, span_reduce

KERNEL = "kda_chunk"
CHUNK = 64
KINDS = ("prefill", "prefill_chunk")


def kda_chunk_flops(tokens: int, n_head: int, head_dim: int, chunk: int,
                    n_layer: int) -> int:
    """The chunked form's operations over ``tokens`` tokens (ops/kda.py
    ``chunk_flops``, written out so that the yardstick does not move with
    the program)."""
    k = v = head_dim
    per_token = (2 * chunk * k + chunk * chunk + chunk * (k + v)
                 + 3 * k * v + chunk * v)
    return 2 * tokens * n_head * per_token * n_layer


def widths_of(keys: dict) -> dict:
    return {"n_head": keys["kda_n_head"], "head_dim": keys["kda_head_dim"],
            "chunk": CHUNK,
            "n_layer": list(keys["layer_types"]).count("kda")}


def read(ctx):
    raw, reduced = span_reduce.load(ctx)
    keys = ctx["config"]["keys"]
    if not reduced or "kda_n_head" not in keys:
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
    tflops = kda_chunk_flops(tokens, **widths) / total_s / 1e12
    peak = common.peaks_for(jax.devices()[0].device_kind)["bf16_tflops"]
    common.say(f"kda chunked form against the matrix peak: {len(steps)} "
               f"prefill runs, {tokens / len(steps):.0f} tokens a step, "
               f"{1e3 * total_s / len(steps):.2f} ms a step in the form, "
               f"{tflops:.2f} TFLOP/s with {widths}")
    return 100.0 * tflops / peak
