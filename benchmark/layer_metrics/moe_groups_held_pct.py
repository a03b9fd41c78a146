"""Kernels: of the (token, expert layer)s the group-limited router routed
in the window, the share whose KEPT groups hold one of this device's
experts (``moe_groups_held`` over ``moe_tokens_routed`` of
``engine.stats()``, the window's end less its start, prefill and decode
together; models/ling_hybrid.py counts both in ``state``). With one
routing group a device and 4 of 8 groups kept, 50% under an even router:
the other half of the tokens send this device nothing, whatever their
experts. Nothing where the program keeps no such counters."""
from benchmark import span_reduce


def read(ctx):
    held = span_reduce.counter_delta(ctx, "moe_groups_held")
    routed = span_reduce.counter_delta(ctx, "moe_tokens_routed")
    if held is None or not routed:
        return None
    return 100.0 * held / routed
