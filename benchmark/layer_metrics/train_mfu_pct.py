"""Trainer: model FLOP/s utilization of the traced run: tokens/s x 6 N over
the chip's published bf16 peak (``peaks.json``). N counts every parameter;
attention's own operations are left out (as ``benchmarks/gpt_mfu.py``
does), so this understates the work and can be compared with that file's
records. A rehearsal has no peak and reports nothing."""
from benchmark import common


def read(ctx):
    if ctx["device"]["platform"] != "tpu":
        return None
    peak = common.peaks_for(ctx["device"]["kind"])["bf16_tflops"] * 1e12
    rate = ctx["end_to_end"]["train_tokens_per_s"]
    return 100.0 * rate * 6.0 * ctx["n_params"] / (
        peak * ctx["cell"]["chips"])
