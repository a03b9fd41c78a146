"""Kernels: the lightning layers' chunked scan against the chip's matrix
peak in prefill (``lightning_chunk``, ops/lightning.py). Operations the
prefill steps of the traced slice could not do without by the chunked form
at the piece ``C`` the program uses (``ops.lightning.PIECE`` = 128 tokens):
each ``executor.dispatch`` span's ``tokens`` x ``lightning_n_head`` x 4 x
``head_dim`` x (``C`` + ``head_dim``) (a token's scores against its piece
and their sum of values, ``4 hd C``; its product with the carried state and
its own outer product into it, ``4 hd hd``) x lightning layers, over the
time the scan took inside those steps' runs, over the published bf16 peak
(``peaks.json``). Padding tokens of a chunk are work the scan does and the
count leaves out: the share errs low.

Which operations: a Pallas body is named ``lightning_chunk``; XLA's
formulation is a ``lax.scan`` over the pieces, which a trace shows as a
``while`` whose carried tuple holds the rows' states, ``f32[<rows>,<heads>,
<hd>,<hd>]``, with the pieces' operations inside its event: the whole of
each such loop is counted. Nothing where no such operation took time or the
spans carry no ``tokens``."""
import jax

from benchmark import common, span_reduce

KERNEL = "lightning_chunk"
PIECE = 128
KINDS = ("prefill", "prefill_chunk")


def lightning_chunk_flops(tokens: int, n_head: int, head_dim: int,
                          piece: int, n_layer: int) -> int:
    """The chunked form's operations over ``tokens`` tokens: ``4 hd (C +
    hd)`` a token a head a layer."""
    return tokens * n_head * 4 * head_dim * (piece + head_dim) * n_layer


def widths_of(keys: dict) -> dict:
    return {"n_head": keys["lightning_n_head"],
            "head_dim": keys["lightning_head_dim"], "piece": PIECE,
            "n_layer": list(keys["mixer_types"]).count("lightning-attn")}


def scan_calls(ops: list[tuple], keys: dict) -> list[tuple]:
    """``(start, end)`` of the scans: the named kernel's calls, else the
    ``while`` loops that carry the state's type."""
    named = span_reduce.kernel_calls(ops, KERNEL)
    if named:
        return named
    typed = common.load_named(
        "layer_metrics", "lightning_state_hbm_pct").state_type(keys)
    return [(s, e) for name, s, e in ops
            if " while(" in name and typed.search(name)]


def read(ctx):
    raw, reduced = span_reduce.load(ctx)
    keys = ctx["config"]["keys"]
    if not reduced or "lightning_n_head" not in keys:
        return None
    widths = widths_of(keys)
    calls = scan_calls(raw["planes"][0]["ops"], keys)
    tokens, total_ns, steps = 0, 0.0, 0
    for step in reduced["steps"]:
        a = step["attrs"]
        if a.get("kind") not in KINDS or not step["inside"] \
                or "tokens" not in a \
                or span_reduce.PROGRAM_OF["prefill"] not in step["run"][0]:
            continue
        ns = span_reduce.time_inside(calls, step["run"][1], step["run"][2])
        if ns <= 0:
            continue
        tokens += int(a["tokens"])
        total_ns += ns
        steps += 1
    if not steps:
        return None
    tflops = lightning_chunk_flops(tokens, **widths) / total_ns / 1e3
    peak = common.peaks_for(jax.devices()[0].device_kind)["bf16_tflops"]
    common.say(f"lightning chunk scan against the matrix peak: {steps} "
               f"prefill runs, {tokens / steps:.0f} tokens a step, "
               f"{total_ns / steps / 1e6:.2f} ms a step in the scans, "
               f"{tflops:.2f} TFLOP/s with {widths}")
    return 100.0 * tflops / peak
