"""From the profiler's ``.xplane.pb`` to numbers: device busy and idle time,
time by XLA module and by operation, and the idle gaps between modules.

Read with ``jax.profiler.ProfileData`` and nothing else. What a TPU trace
holds (looked at by hand, PR 23): one plane per chip named
``/device:TPU:<n>``; on it a line ``XLA Modules`` with one event per run of
a compiled program, named ``jit_<function>(<id>)``, and a line ``XLA Ops``
with one event per HLO operation that ran, named by the operation's whole
HLO text (``%fusion.12 = f32[...] fusion(...), kind=kLoop, ...``; a Pallas
call is a ``custom-call`` whose target is ``tpu_custom_call``). Events
carry ``start_ns`` and ``duration_ns``. Operations of a ``while`` loop lie
inside the loop's own event, so times by operation are SELF times: an
event's duration less its children's.

The CPU backend writes no device plane. In a rehearsal the host threads
that run XLA's CPU programs stand in for one, so that the same code runs;
a rehearsal's numbers are never a device's.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CPU_STAND_IN = "tf_XLAPjRtCpuClient"
# the runners mark the traced slice on the profiler's own clock with two
# empty host annotations; device events are clipped to what lies between
MARK_OPEN = "benchmark.window.open"
MARK_CLOSE = "benchmark.window.close"
_ID = re.compile(r"\(\d+\)$")
# the host's dispatch of a jitted function, on the python thread's line
DISPATCH = re.compile(r"^PjitFunction\((.+)\)$")


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def union_ns(intervals: list[tuple[float, float]]) -> float:
    """Total length covered by ``(start, end)`` intervals, overlaps once."""
    total = 0.0
    end = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def self_times(events: list[tuple[str, float, float]]) -> dict[str, list]:
    """``{name: [count, self_ns]}`` for ``(name, start, end)`` events that
    may nest: a parent's self time leaves out what its children cover."""
    out: dict[str, list] = {}
    stack: list[list] = []  # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            rec = out.setdefault(name, [0, 0.0])
            rec[0] += 1
            rec[1] += own

    for name, s, e in sorted(events, key=lambda t: (t[1], -(t[2] - t[1]))):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return out


def _median(xs: list[float]) -> float:
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def name_modules(modules: list[tuple], dispatches: list[tuple]) -> list:
    """Give the runs of programs that the trace calls ``jit__unknown(<id>)``
    the name of the function that was dispatched for them.

    The program jits ``functools.partial`` objects, which have no name, so
    every serving program is ``jit__unknown``; what tells decode from
    prefill is the host's ``PjitFunction(<function>)`` event at dispatch.
    One device runs its programs in the order they were dispatched, so the
    k-th run belongs to the (k + d)-th dispatch, where the offset ``d`` is
    small and unknown: a dispatch or a run at either edge may lie outside
    the trace, and the two clocks are not aligned well enough to settle it
    by time (tried on the chip, PR 23: a first-in first-out match by time
    was off by one and called every prefill a decode). A program id stands
    for ONE function, so the right offset is the one under which the runs
    of each id agree most on their function. Each id is then named by the
    majority of its runs. ``dispatches`` is ``[(function, start)]``."""
    from collections import Counter

    runs = sorted(modules, key=lambda m: m[1])
    calls = [fn for fn, _ in sorted(dispatches, key=lambda d: d[1])]
    best = None
    for d in (0, 1, -1, 2, -2, 3, -3):
        votes: dict[str, Counter] = {}
        for k, (name, _, _) in enumerate(runs):
            if 0 <= k + d < len(calls):
                votes.setdefault(name, Counter())[calls[k + d]] += 1
        agree = sum(c.most_common(1)[0][1] for c in votes.values())
        total = sum(sum(c.values()) for c in votes.values())
        score = agree / total if total else 0.0
        if best is None or score > best[0] + 1e-12:
            best = (score, votes)
    votes = best[1] if best else {}
    out = []
    for name, s, e in modules:
        if "_unknown" in name and name in votes:
            ident = _ID.search(name)
            name = (f"jit_{votes[name].most_common(1)[0][0]}"
                    f"{ident.group(0) if ident else ''}")
        out.append((name, s, e))
    return out


def outermost(events: list[tuple]) -> list[tuple]:
    """Drop an event that lies inside the one before it under the same name
    (the profiler records each dispatch twice, one inside the other)."""
    kept: list[tuple] = []
    for ev in sorted(events, key=lambda t: (t[1], -t[2])):
        if kept and kept[-1][0] == ev[0] and ev[2] <= kept[-1][2]:
            continue
        kept.append(ev)
    return kept


def clip(events: list[tuple], w0: float, w1: float) -> list[tuple]:
    """The part of each ``(name, start, end)`` event inside [w0, w1]."""
    return [(n, max(s, w0), min(e, w1)) for n, s, e in events
            if e > w0 and s < w1]


def reduce_planes(planes: list[dict], window_ns: tuple[float, float]) -> dict:
    """The reduction proper, on plain data: ``planes`` is a list of
    ``{"ops": [(name, start, end)], "modules": [(name, start, end)]}``,
    one per chip; events are clipped to ``window_ns``. A program's run that
    the window cuts counts towards busy time, not towards its median."""
    w0, w1 = window_ns
    planes = [{"ops": clip(p["ops"], w0, w1),
               "modules": [m for m in p["modules"]
                           if m[1] >= w0 and m[2] <= w1]} for p in planes]
    busy = [union_ns([(s, e) for _, s, e in p["ops"]]) for p in planes]
    ops: dict[str, list] = {}
    modules: dict[str, dict] = {}
    gaps: dict[str, float] = {}
    for p in planes:
        for name, (count, own) in self_times(p["ops"]).items():
            rec = ops.setdefault(name, [0, 0.0])
            rec[0] += count
            rec[1] += own
        prev = None
        for name, s, e in sorted(p["modules"], key=lambda t: t[1]):
            name = _ID.sub("", name)
            m = modules.setdefault(name, {"durations_ns": []})
            m["durations_ns"].append(e - s)
            if prev is not None and s > prev[1]:
                key = f"after {prev[0]} before {name}"
                gaps[key] = gaps.get(key, 0.0) + (s - prev[1])
            prev = (name, e)
    n = max(1, len(planes))
    for m in modules.values():
        d = m.pop("durations_ns")
        m.update(count=len(d), total_s=sum(d) / 1e9 / n,
                 median_ms=_median(d) / 1e6)
    return {
        "chips": len(planes),
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / 1e9 / n,
        "modules": modules,
        "ops": {k: {"count": c, "self_s": t / 1e9 / n}
                for k, (c, t) in ops.items()},
        "idle_gaps": {k: v / 1e9 / n for k, v in gaps.items()},
    }


def reduce_file(path: str, stand_in_cpu: bool = False) -> dict | None:
    """Reduce one ``.xplane.pb``. None where it holds no device plane (and
    no stand-in was asked for)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    lo, hi = float("inf"), 0.0
    marks: dict[str, float] = {}
    dispatched: list[tuple] = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        host = plane.name == "/host:CPU"
        if not (device or host):
            continue
        ops, modules = [], []
        for line in plane.lines:
            events = [(e.name, float(e.start_ns),
                       float(e.start_ns + e.duration_ns))
                      for e in line.events]
            for name, s, e in events:
                lo, hi = min(lo, s), max(hi, e)
                if name in (MARK_OPEN, MARK_CLOSE):
                    marks[name] = s
                elif host and DISPATCH.match(name):
                    dispatched.append((DISPATCH.match(name).group(1), s, e))
            if device and line.name == OPS_LINE:
                ops += events
            elif device and line.name == MODULES_LINE:
                modules += events
            elif host and stand_in_cpu and line.name.startswith(CPU_STAND_IN):
                ops += [ev for ev in events if ev[2] > ev[1]]
        if device or (stand_in_cpu and ops):
            planes.append({"ops": ops, "modules": modules})
    if MARK_OPEN in marks and MARK_CLOSE in marks:
        lo, hi = marks[MARK_OPEN], marks[MARK_CLOSE]
    if not planes or hi <= lo:
        return None
    dispatches = [(fn, s) for fn, s, _ in outermost(dispatched)]
    for p in planes:
        p["modules"] = name_modules(p["modules"], dispatches)
    return reduce_planes(planes, (lo, hi))


def top(table: dict[str, float], n: int = 10) -> list[list]:
    """``[[name, seconds], ...]``, the ``n`` largest first."""
    return [[k, v] for k, v in
            sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def short_name(op: str) -> str:
    """An operation's event is named by its whole HLO text (``%fusion.18 =
    (f32[...]) fusion(...), kind=kLoop, ...``); the result line wants the
    instruction's name and what kind it is."""
    name, _, rest = op.partition(" = ")
    name = name.lstrip("%")
    if not rest:
        return name[:120]
    if "tpu_custom_call" in rest:
        return name + " [pallas]"
    m = re.search(r"\b([a-z][a-z\-]*)\(", rest)
    return f"{name} [{m.group(1)}]" if m else name


def breakdown(reduced: dict) -> dict:
    """The result line's ``breakdown``: the device operations that took
    most time (self time, under the names the trace prints) and the idle
    gaps between programs, named by the programs on either side — what the
    host was doing in them needs host spans on the profiler's clock."""
    by_short: dict[str, float] = {}
    for op, v in reduced["ops"].items():
        # one instruction name in several programs (the decode kernel at
        # two batch sizes) is one line
        by_short[short_name(op)] = by_short.get(short_name(op), 0.0) \
            + v["self_s"]
    return {
        "device_ops": top(by_short),
        "idle_gaps": top(reduced["idle_gaps"]),
    }


def module_median_ms(reduced: dict | None, *needles: str) -> float | None:
    """Median device duration, in ms, of the runs of every program whose
    name holds one of ``needles``; None where there is none."""
    if not reduced:
        return None
    hits = [(m["median_ms"], m["count"])
            for name, m in reduced["modules"].items()
            if any(n in name for n in needles)]
    if not hits:
        return None
    # several programs (one per shape) match: the median of the one that
    # ran most is the step the window mostly took
    return max(hits, key=lambda h: h[1])[0]


def ops_share_pct(reduced: dict | None, *needles: str) -> float | None:
    """Self time of the operations whose name holds one of ``needles``, as
    a share of the device's busy time, in percent."""
    if not reduced or not reduced["busy_s"]:
        return None
    t = sum(v["self_s"] for name, v in reduced["ops"].items()
            if any(n in name for n in needles))
    return 100.0 * t / reduced["busy_s"]
