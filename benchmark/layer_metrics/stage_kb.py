"""Executor: KB of host arrays a launch moves to the device:
``engine.stats()["host"]["stage_bytes"]`` over the decode and prefill
dispatches, the window's end less its start, / 1024. A grammar allow-mask
that is filled on the host and moved is among them ([64, vocabulary / 32]
uint32: 393 KB at GPT-2's vocabulary); one that rests on the device is
not, as an input already on the device never is."""
from benchmark import host_reduce, span_reduce


def read(ctx):
    host = host_reduce.host_delta(ctx)
    launches = (span_reduce.counter_delta(ctx, "decode_steps") or 0) + (
        span_reduce.counter_delta(ctx, "prefill_steps") or 0)
    if not host or not launches or "stage_bytes" not in host:
        return None
    return host["stage_bytes"] / launches / 1024
