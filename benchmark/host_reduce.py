"""What the stepping thread's time is made of OUTSIDE a step's phases, laid
against the device's idle time and read from the program's own counters.

``span_reduce`` attributes the device's idle time to the phases of an engine
step (its ``PHASES``) and calls what no phase covers ``other``. The program
names more than its phases (``serve/llm/obs.py``): the stepping thread's
wait for the engine's lock before a step (``engine.lock``), a collection of
the process's collector on whichever thread ran it (``host.gc``, generation
2 only), and the id gather inside the stage phase (``executor.feed``); and
every ``executor.dispatch`` and ``engine.sync`` span carries the launch
number ``seq`` of the step it launched or synced. This module reads those
from the same ``.xplane.pb``, takes the window, the offset between the two
clocks and the pairing of launches with runs from ``span_reduce.load``, and

1. splits ``other`` four ways, in this order of precedence: under a
   collection (it stops every thread, the holder of a lock too; one that
   falls while the stepping thread is INSIDE a phase is that phase's idle
   time, not ``other``'s, and is told as ``idle_under_gc_s``); under the
   wait for the lock; at the slice's EDGES, between a window mark and the
   first or last step program's run inside it (where a span that was open
   when the session began is missing from the trace); and what is left,
   under no span at all. The four add up to ``other``;
2. puts a floor under the clock offset from EVERY sync: a sync of any lag
   cannot end before the run of the launch it names does, so ``run end -
   sync end`` is a floor, and ``span_reduce``'s ceiling less the highest
   floor is the width the offset is known to;
3. differences ``engine.stats()["host"]`` over the window.

The reductions work on plain lists, so that tests feed them hand-made
events; only ``read_file`` touches ``jax.profiler.ProfileData``. A program
that writes no such spans or counters (the parent of the PR that brought
them) gives None everywhere, and raises nowhere.
"""
from __future__ import annotations

from benchmark import common, span_reduce, trace_reduce

LOCK, GC, FEED = "engine.lock", "host.gc", "executor.feed"
HOST_SPANS = (LOCK, GC, FEED)
# ``other``'s parts, in the order of precedence where two cover one instant
PARTS = ("gc", "lock", "edge", "unspanned")


# --------------------------------------------------------- plain reductions


def union(intervals: list[tuple]) -> list[tuple]:
    """``(start, end)`` intervals as sorted, disjoint ones."""
    out: list[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return [(s, e) for s, e in out]


def subtract(intervals: list[tuple], cover: list[tuple]) -> list[tuple]:
    """What is left of sorted disjoint ``intervals`` outside the sorted
    disjoint ``cover``: one pass over both."""
    out = []
    j = 0
    for s, e in intervals:
        at = s
        while j < len(cover) and cover[j][1] <= at:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            if cover[k][0] > at:
                out.append((at, cover[k][0]))
            at = max(at, cover[k][1])
            k += 1
        if at < e:
            out.append((at, e))
    return out


def total(intervals: list[tuple]) -> float:
    return sum(e - s for s, e in intervals)


def edges(runs: list[tuple], w0: float, w1: float) -> list[tuple]:
    """The slice's two edges: from the opening mark to the start of the
    first step program's run that reaches into the slice, and from the end
    of the last one to the closing mark. ``runs`` are ``(name, start, end)``
    on the marks' clock; a run that straddles a mark leaves no edge there,
    and a slice with no run is all edge."""
    inside = sorted((s, e) for _, s, e in runs if e > w0 and s < w1)
    if not inside:
        return [(w0, w1)]
    out = []
    if inside[0][0] > w0:
        out.append((w0, inside[0][0]))
    last = max(e for _, e in inside)
    if last < w1:
        out.append((last, w1))
    return out


def split_other(idle: list[tuple], phases: list[tuple],
                cover: dict[str, list]) -> dict[str, float]:
    """``{"other", "gc", "lock", "edge", "unspanned"}`` in the intervals'
    unit: the ``(start, end)`` idle intervals less the ``phases`` (what
    ``span_reduce`` counts as ``other``), then what each of ``cover
    ["gc" | "lock" | "edge"]`` takes of the rest in turn, and the rest."""
    rest = subtract(union(idle), union(phases))
    out = {"other": total(rest)}
    for part in PARTS[:-1]:
        left = subtract(rest, union(cover.get(part, [])))
        out[part] = total(rest) - total(left)
        rest = left
    out["unspanned"] = total(rest)
    return out


def thread_gaps(spans: list[tuple], w0: float, w1: float) -> dict:
    """Where ONE thread's time lies that none of its ``(name, start, end)``
    spans covers, inside ``[w0, w1]``: ``{"covered": t, "gaps": {"<ended
    last>><starts next>": [count, t]}}``. Unlike ``span_reduce.between``
    laid under the device's idle time, this is the thread's own account:
    every stretch between two spans, whether the device waited or not."""
    pieces = [p for p in span_reduce.innermost(spans)
              if p[2] > w0 and p[1] < w1]
    gaps: dict[str, list] = {}
    for a, b in zip(pieces, pieces[1:]):
        if b[1] > a[2]:
            rec = gaps.setdefault(f"{a[0]}>{b[0]}", [0, 0.0])
            rec[0] += 1
            rec[1] += b[1] - a[2]
    return {"covered": sum(min(e, w1) - max(s, w0) for _, s, e in pieces),
            "gaps": gaps}


def offset_floor_by_seq(steps: list[dict], syncs: list[dict]) -> dict | None:
    """The floor under the clock offset (device clock less host clock) from
    every sync that names its launch: ``steps`` are ``span_reduce``'s paired
    dispatches (``attrs`` with ``seq``, ``run``), ``syncs`` the
    ``engine.sync`` spans. None where no sync finds its run."""
    run_of = {int(s["attrs"]["seq"]): s["run"] for s in steps
              if "seq" in s["attrs"]}
    found = [(sync, run_of[int(sync["attrs"]["seq"])]) for sync in syncs
             if int(sync["attrs"].get("seq", -1)) in run_of]
    if not found:
        return None
    by_lag: dict[int, int] = {}
    for sync, _ in found:
        lag = int(sync["attrs"].get("lag", -1))
        by_lag[lag] = by_lag.get(lag, 0) + 1
    return {"floor_ns": max(run[2] - sync["end"] for sync, run in found),
            "syncs": len(found), "syncs_by_lag": by_lag}


def reduce_raw(raw: dict, reduced: dict, host: list[dict]) -> dict:
    """The reduction proper. ``raw`` and ``reduced`` are ``span_reduce``'s
    (its ``read_file`` and ``reduce_raw``), ``host`` the spans of
    ``HOST_SPANS`` as ``{"name", "start", "end", "line"}``."""
    w0, w1 = raw["window"]
    offset = reduced["clock_offset_us"] * 1e3
    plane = raw["planes"][0]
    idle = [(s - offset, e - offset) for s, e in span_reduce.complement(
        [(s, e) for _, s, e in plane["ops"]], w0, w1)]
    phases = [(s["start"], s["end"]) for s in raw["spans"]]
    runs = [(n, s - offset, e - offset)
            for n, s, e in span_reduce.step_runs(plane["modules"])]
    by_name = {name: [(s["start"], s["end"]) for s in host
                      if s["name"] == name] for name in HOST_SPANS}
    parts = split_other(idle, phases, {
        "gc": by_name[GC], "lock": by_name[LOCK],
        "edge": edges(runs, w0 - offset, w1 - offset)})
    # the stepping thread's spans, nested ones split by the innermost: what
    # the device's idle time lies under when the new names count too
    stepping = {s["line"] for s in host if s["name"] == LOCK}
    named = [(s["name"], s["start"], s["end"]) for s in raw["spans"]] + [
        (s["name"], s["start"], s["end"]) for s in host
        if s["name"] != GC and s["line"] in stepping]
    by_span = span_reduce.attribute(idle, named)
    own = thread_gaps(named, w0 - offset, w1 - offset)
    floor = offset_floor_by_seq(
        reduced["steps"],
        [s for s in raw["spans"] if s["name"] == "engine.sync"])
    return {
        "window_s": (w1 - w0) / 1e9,
        "other_s": {k: v / 1e9 for k, v in parts.items()},
        "idle_by_span_s": {k: v / 1e9 for k, v in by_span.items()},
        # the stepping thread's own slice: under a span, and the ten
        # largest stretches under none, [count, seconds] by the spans on
        # either side
        "thread_spanned_s": own["covered"] / 1e9,
        "thread_gaps_s": {k: [n, t / 1e9] for k, (n, t) in sorted(
            own["gaps"].items(), key=lambda kv: -kv[1][1])[:10]},
        "spans": {name: len(v) for name, v in by_name.items()},
        "gc_lines": len({s["line"] for s in host if s["name"] == GC}),
        # a collection on ANOTHER thread holds the stepping thread inside
        # whatever phase it is in, and the device's idle time is then that
        # phase's: all the idle under a collection, whoever it is booked to
        "idle_under_gc_s": (total(union(idle)) - total(
            subtract(union(idle), union(by_name[GC])))) / 1e9,
        "clock_offset_us": reduced["clock_offset_us"],
        "offset_floor_by_seq_us": floor and floor["floor_ns"] / 1e3,
        "offset_floor_syncs": floor and floor["syncs_by_lag"],
    }


# ------------------------------------------------------- the trace's file


def read_file(path: str) -> list[dict]:
    """The spans of ``HOST_SPANS`` on the host's plane, each with the line
    (thread) it lies on."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        # a thread's line carries the process's name, not its own: told
        # apart by where it stands among the plane's lines
        for index, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in HOST_SPANS:
                    out.append({
                        "name": e.name, "start": float(e.start_ns),
                        "end": float(e.start_ns + e.duration_ns),
                        "line": index})
    return out


def load(ctx: dict) -> dict | None:
    """The run's trace reduced, read once a run and kept in ``ctx``; said
    once. None without a trace, where ``span_reduce`` could not lay the
    spans against the device, or where the program wrote no ``engine.lock``
    span (then ``other`` cannot be split, and nothing is guessed)."""
    if "host_trace" not in ctx:
        ctx["host_trace"] = None
        raw, reduced = span_reduce.load(ctx)
        run = ctx.get("trace_run") or {}
        path = run.get("dir") and trace_reduce.find_xplane(run["dir"])
        if raw and reduced and path:
            host = read_file(path)
            if any(s["name"] == LOCK for s in host):
                ctx["host_trace"] = reduce_raw(raw, reduced, host)
                common.say(f"the rest of the stepping thread against the "
                           f"device: {ctx['host_trace']}")
    return ctx["host_trace"]


def other_pct(ctx: dict, part: str) -> float | None:
    """One part of ``idle_pct.other.sat``'s idle, as a share of the slice,
    in percent."""
    found = load(ctx)
    if not found:
        return None
    return 100.0 * found["other_s"][part] / found["window_s"]


# --------------------------------------------------- the program's counters


def host_delta(ctx: dict) -> dict | None:
    """``engine.stats()["host"]`` at the window's end less at its start:
    ``{"spans": {name: [count, seconds, cpu_seconds]}, "phase_cpu": {kind:
    {phase: cpu_seconds}}, "gc": {"collections", "seconds"} by generation,
    "stage_transfers", "stage_bytes"}``. None where the program keeps no
    such counters."""
    after = (ctx.get("stats_after") or {}).get("host")
    if after is None:
        return None
    before = (ctx.get("stats_before") or {}).get("host") or {}

    def less(a, b):
        if isinstance(a, dict):
            return {k: less(v, (b or {}).get(k)) for k, v in a.items()}
        if isinstance(a, list):
            return [x - y for x, y in zip(a, b or [0] * len(a))]
        return a - (b or 0)

    return less(after, before)


def window_records(ctx: dict, field: str) -> list[dict]:
    """The window's flight records that carry ``field``."""
    return [s for s in ctx.get("flight") or () if field in s]


def say_slow_step(what: str, record: dict) -> None:
    """One record's own account of where its time went."""
    shown = {k: record.get(k) for k in (
        "kind", "step", "dur_ms", "lock_ms", "gc_ms", "cpu_ms", "sync_ms",
        "sync_lag", "batch", "steady", "remapped") if k in record}
    common.say(f"{what}: {shown}")
