"""Kernels: a latent family's prefill attention against the chip's matrix
peak. Operations the prefill steps of the traced slice could not do
without (``qk_pairs`` of each ``executor.dispatch`` span of a prefill kind:
the sum over the step's real query tokens of the positions each attends, x
2 x heads x (``qk_nope_head_dim`` + ``qk_rope_head_dim`` + ``v_head_dim``) x
layers: the EXPANDED form's two products, the fewest the mathematics needs)
over the time the attention's named kernels (``paged_attention_latent``,
``flash_fwd``) took inside those steps' runs, over the published bf16 peak
(``peaks.json``). A trace names a kernel by its name and a fusion only by
its operands, so a prefill's attention is found by those names. The
absorbed kernel, which never builds a context's K and V by head, does 2 x
heads x (2 x 512 + 64) a pair, 3.4 x these operations, and can read at
most 29; a later path that expands a chunk is judged on the same
yardstick. Padding queries and masked keys are work the kernel does and
the count leaves out: the share errs low. Nothing where the trace holds no
such call or the spans no ``qk_pairs``."""
import jax

from benchmark import common, span_reduce

NEEDLES = ("paged_attention_latent", "flash_fwd")
KINDS = ("prefill", "prefill_chunk")


def latent_prefill_flops(qk_pairs: int, n_head: int, qk_nope_head_dim: int,
                         qk_rope_head_dim: int, v_head_dim: int,
                         n_layer: int) -> int:
    """The expanded form's operations over ``qk_pairs`` (query, key)
    pairs: a score over the 192-wide key and a sum of 128-wide values, for
    every head, in every layer."""
    return 2 * qk_pairs * n_head * (
        qk_nope_head_dim + qk_rope_head_dim + v_head_dim) * n_layer


def widths_of(keys: dict) -> dict:
    return {k: keys[k] for k in ("n_head", "qk_nope_head_dim",
                                 "qk_rope_head_dim", "v_head_dim", "n_layer")}


def read(ctx):
    raw, reduced = span_reduce.load(ctx)
    keys = ctx["config"]["keys"]
    if not reduced or "qk_nope_head_dim" not in keys:
        return None
    ops = raw["planes"][0]["ops"]
    calls = [c for n in NEEDLES for c in span_reduce.kernel_calls(ops, n)]
    pairs, total_ns, steps = 0, 0.0, 0
    for step in reduced["steps"]:
        a = step["attrs"]
        if a.get("kind") not in KINDS or not step["inside"] \
                or "qk_pairs" not in a \
                or span_reduce.PROGRAM_OF["prefill"] not in step["run"][0]:
            continue
        ns = span_reduce.time_inside(calls, step["run"][1], step["run"][2])
        if ns <= 0:
            continue
        pairs += int(a["qk_pairs"])
        total_ns += ns
        steps += 1
    if not steps:
        return None
    tflops = latent_prefill_flops(pairs, **widths_of(keys)) / total_ns / 1e3
    peak = common.peaks_for(jax.devices()[0].device_kind)["bf16_tflops"]
    common.say(f"latent prefill attention against the matrix peak: {steps} "
               f"prefill runs, {pairs / steps / 1e6:.2f} M (query, key) "
               f"pairs a step, {total_ns / 1e9:.4f}s, {tflops:.1f} TFLOP/s "
               f"of the expanded form's operations")
    return 100.0 * tflops / peak
