"""Executor: host arrays ``executor.stage`` moves to the device a launch:
``engine.stats()["host"]["stage_transfers"]`` over the decode and prefill
dispatches, the window's end less its start (an input already on the
device, the ids of the step in flight, moves nothing)."""
from benchmark import host_reduce, span_reduce


def read(ctx):
    host = host_reduce.host_delta(ctx)
    launches = (span_reduce.counter_delta(ctx, "decode_steps") or 0) + (
        span_reduce.counter_delta(ctx, "prefill_steps") or 0)
    if not host or not launches:
        return None
    return host["stage_transfers"] / launches
