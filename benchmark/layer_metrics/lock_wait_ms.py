"""Scheduler: what the stepping thread waits for the engine's own lock, in
ms a step: seconds of the ``engine.lock`` span over its count
(``engine.stats()["host"]["spans"]``, the window's end less its start).
The reader also SAYS what the thread's window was made of: the phases'
seconds, the lock's, and what neither covers."""
from benchmark import common, host_reduce, span_reduce


def read(ctx):
    host = host_reduce.host_delta(ctx)
    if not host or not host["spans"].get("engine.lock", [0])[0]:
        return None
    count, seconds, cpu = host["spans"]["engine.lock"]
    window = ctx["t1"] - ctx["t0"]
    kinds = (ctx["stats_after"].get("phases") or {})
    phases = {k: span_reduce.phase_totals(ctx, k) for k in kinds}
    booked = sum(sec for table in phases.values()
                 for _, sec in table.values())
    ran = sum(v for table in host["phase_cpu"].values()
              for v in table.values())
    # the records' ``cpu_ms`` add up to the CPU the thread ran in all
    thread_cpu = 1e-3 * sum(
        s["cpu_ms"] for s in host_reduce.window_records(ctx, "cpu_ms"))
    common.say(
        f"the stepping thread's window of {window:.3f} s (it ran "
        f"{thread_cpu:.3f} s of CPU): phases {booked:.4f} s (ran "
        f"{ran:.4f}), waits for the lock {seconds:.4f} s in {count} (ran "
        f"{cpu:.4f}), under no span {window - booked - seconds:.4f} s (ran "
        f"{thread_cpu - ran - cpu:.4f}); collector {host['gc']}; staged "
        f"{host['stage_transfers']} arrays, {host['stage_bytes']} bytes")
    return 1e3 * seconds / count
