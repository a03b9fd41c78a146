"""Scheduler: CPU time the stepping thread itself ran of a decode step's
host phases, in ms a step: ``engine.stats()["host"]["phase_cpu"]["decode"]``
of every phase but ``engine.sync``, the window's end less its start, over
the decode dispatches. Beside ``decode_host_ms.*`` (the same phases' wall
time) it says how much of the host's step the thread was on a core; the
rest it stood off it: waiting for the interpreter, or for the machine. (The
program reads the CPU clock on one step in eight and takes that share for
all; where the kernel counts CPU time in ticks the sum is right, one
reading coarse.) The reader SAYS the split phase by phase."""
from benchmark import common, host_reduce, span_reduce


def read(ctx):
    host = host_reduce.host_delta(ctx)
    steps = span_reduce.counter_delta(ctx, "decode_steps")
    if not host or not steps or "decode" not in host["phase_cpu"]:
        return None
    cpu = host["phase_cpu"]["decode"]
    wall = span_reduce.phase_totals(ctx, "decode") or {}
    common.say("decode step's phases, ms a step wall / own CPU: " + ", ".join(
        f"{name} {1e3 * wall.get(name, [0, 0.0])[1] / steps:.3f} / "
        f"{1e3 * cpu[name] / steps:.3f}" for name in sorted(cpu)))
    return 1e3 * sum(v for name, v in cpu.items()
                     if name != "engine.sync") / steps
