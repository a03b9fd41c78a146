"""Kernels: device time in the flash-attention forward and backward calls,
as a share of busy time. The trace names them after the transformation
that made them (``%jvp___``, ``%transpose_jvp___``), not after the kernel,
so they are found by what they are: the train step's Pallas calls
(``custom_call_target="tpu_custom_call"``), of which the flash kernels are
the only ones (the fused loss of ``ops/loss.py`` is plain XLA)."""
from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.ops_share_pct(ctx.get("trace"), "tpu_custom_call")
