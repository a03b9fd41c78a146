"""Executor: device time of the prefill programs' runs in the traced
slice, as a share of the device's busy time: what decoding rows lose to
other requests' prompts."""


def read(ctx):
    reduced = ctx.get("trace")
    if not reduced or not reduced["busy_s"] or not reduced["modules"]:
        return None
    prefill_s = sum(m["total_s"] for name, m in reduced["modules"].items()
                    if "_prefill" in name)
    return 100.0 * prefill_s / reduced["busy_s"]
