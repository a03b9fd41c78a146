"""From the profiler's trace to the program's own spans: the device's idle
time by the host phase that covers it, and two kernels' shares of their
rooflines.

The engine writes its host phases into the profiler's trace
(``serve/llm/obs.py::phase``: ``engine.schedule``, ``engine.batch``,
``kv.reserve``, ``executor.stage``, ``executor.dispatch``, ``engine.sync``,
``engine.emit``, ``engine.account``, ``engine.wait``), each with attributes;
the device writes its programs' runs and operations (``trace_reduce``'s
module docstring says how). The two lie on planes whose clocks differ by a
millisecond or so, which is as long as the gaps in question, so the offset
is settled first:

1. Every ``executor.dispatch`` span names its program (``kind``), and so
   does every run on the ``XLA Modules`` line. One device runs what it was
   handed in order, so run ``k`` belongs to dispatch ``k + d``; ``d`` is
   small and not 0, because runs at the trace's start were dispatched before
   it. The right ``d`` is the one under which the two NAME sequences agree
   everywhere; where more than one does, nothing is attributed (``align``).
2. ``run start - dispatch start`` is the offset plus the time the launch
   took. It is least where the device stood idle waiting for that launch
   (queued behind another run it is a whole step more), so the smallest
   difference over all pairs is the offset plus the fastest launch.
   Device times less this offset are on the host's
   clock, where a run starts no earlier than its dispatch.
3. From the other side, an ``engine.sync`` span with ``lag == 0`` cannot end
   before the run it waits for: ``run end - sync end`` is a floor under the
   offset (``clock_offset_floor_us``), and a shift whose floor lies over
   its ceiling is wrong.

An idle interval of the device is then split among the innermost spans that
cover it; what no span covers is ``other``, and is also told by the spans
on either side of it (``between``). The reduction works on plain
lists, so that tests feed it hand-made events; only ``read_file`` touches
``jax.profiler.ProfileData``.

The operations and bytes of a roofline share are the algorithm's, computed
here from shapes and from the ``kv_tokens`` the engine attaches to each
decode dispatch — never by the program.
"""
from __future__ import annotations

from benchmark import common, trace_reduce

# span name -> the layer its idle time is reported under (idle_pct.<layer>)
PHASES = {
    "engine.schedule": "scheduler", "engine.batch": "scheduler",
    "engine.emit": "scheduler", "engine.account": "scheduler",
    "kv.reserve": "kv",
    "executor.stage": "executor", "executor.dispatch": "executor",
    "engine.sync": "sync",
    "engine.wait": "wait",
}
LAYERS = ("scheduler", "kv", "executor", "sync", "wait", "other")
DISPATCH = "executor.dispatch"
# an ``executor.dispatch`` span's ``kind`` -> what its program's name holds
PROGRAM_OF = {"prefill": "_prefill", "prefill_chunk": "_prefill",
              "decode": "_decode_step", "verify": "_verify_step"}
# run ``k`` belongs to dispatch ``k + d``: lag-1 dispatch keeps at most two
# runs in flight, so at most two at the trace's start lack their dispatch
# span, and the host's and the device's tracers start a run or two apart
SHIFTS = (0, -1, 1, -2, 2)


# --------------------------------------------------------- plain reductions


def complement(intervals: list[tuple], w0: float, w1: float) -> list[tuple]:
    """What ``(start, end)`` intervals leave uncovered of ``[w0, w1]``."""
    out = []
    at = w0
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, w1)))
        at = max(at, e)
        if at >= w1:
            break
    if at < w1:
        out.append((at, w1))
    return [(s, e) for s, e in out if e > s]


def step_runs(modules: list[tuple]) -> list[tuple]:
    """The runs of the engine's step programs, in the order they ran."""
    needles = set(PROGRAM_OF.values())
    return sorted((m for m in modules if any(n in m[0] for n in needles)),
                  key=lambda m: m[1])


def align(dispatches: list[dict], runs: list[tuple],
          syncs: list[dict] = ()) -> dict | None:
    """Pair run ``k`` with dispatch ``k + d``. ``dispatches`` are the
    ``executor.dispatch`` spans (``{"start", "end", "attrs"}``) by start,
    ``runs`` the step programs' ``(name, start, end)`` by start, ``syncs``
    the ``engine.sync`` spans. ``d`` is the ONE shift under which every
    pair agrees on the program's name and which no sync contradicts: a
    sync with ``lag == 0`` waits for the run of the dispatch before it, so
    it cannot end before that run does, and ``run end - sync end`` is a
    floor under the offset that ``run start - dispatch start`` is a
    ceiling over. None where no shift passes or more than one does (a
    slice of nothing but decode steps fits every shift, and a wrong one
    would lay every idle gap against the spans of another step): the
    metrics are then left out, not guessed."""
    passed = []
    for d in SHIFTS:
        pairs = [(dispatches[k + d], run) for k, run in enumerate(runs)
                 if 0 <= k + d < len(dispatches)]
        if not pairs or not all(
                PROGRAM_OF.get(disp["attrs"].get("kind"), "?") in run[0]
                for disp, run in pairs):
            continue
        offset = min(run[1] - disp["start"] for disp, run in pairs)
        floor = None
        for sync in syncs:
            if int(sync["attrs"].get("lag", -1)) != 0:
                continue
            before = [run for disp, run in pairs
                      if disp["start"] <= sync["start"]]
            if before and (floor is None
                           or before[-1][2] - sync["end"] > floor):
                floor = before[-1][2] - sync["end"]
        if floor is None or floor <= offset:
            passed.append({"shift": d, "pairs": pairs, "offset_ns": offset,
                           "offset_floor_ns": floor})
    return passed[0] if len(passed) == 1 else None


def innermost(spans: list[tuple]) -> list[tuple]:
    """``(name, start, end)`` spans that may nest, as the flat sequence of
    ``(name, start, end)`` pieces in which ``name`` is the innermost span
    open."""
    out: list[tuple] = []
    stack: list[tuple] = []  # (name, end)
    at = None

    def emit(upto: float) -> None:
        nonlocal at
        if stack and upto > at:
            out.append((stack[-1][0], at, upto))
        at = upto

    for name, s, e in sorted(spans, key=lambda t: (t[1], -t[2])):
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            stack.pop()
        emit(s)
        stack.append((name, e))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def attribute(idle: list[tuple], spans: list[tuple]) -> dict[str, float]:
    """Split the ``(start, end)`` idle intervals among the innermost
    ``(name, start, end)`` spans that cover them: ``{name: ns}``; what no
    span covers is ``other``. Both on one clock."""
    out: dict[str, float] = {}
    pieces = innermost(spans)
    i = 0
    for s, e in sorted(idle):
        covered = 0.0
        while i < len(pieces) and pieces[i][2] <= s:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][1] < e:
            name, ps, pe = pieces[j]
            part = min(e, pe) - max(s, ps)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                covered += part
            j += 1
        out["other"] = out.get("other", 0.0) + (e - s) - covered
    return out


def by_layer(by_span: dict[str, float]) -> dict[str, float]:
    out = dict.fromkeys(LAYERS, 0.0)
    for name, v in by_span.items():
        out[PHASES.get(name, "other")] += v
    return out


def between(spans: list[tuple]) -> list[tuple]:
    """The stretches no ``(name, start, end)`` span covers, each named for
    the spans on either side of it, ``"<ended last>><starts next>"``: laid
    under ``attribute`` they say where ``other`` lies."""
    pieces = innermost(spans)
    return [(f"{a[0]}>{b[0]}", a[2], b[1])
            for a, b in zip(pieces, pieces[1:]) if b[1] > a[2]]


def kernel_calls(ops: list[tuple], needle: str) -> list[tuple]:
    """``(start, end)`` of the operations named ``*needle*``. Kernel calls
    are leaves, so their durations are self times."""
    return [(s, e) for name, s, e in ops if needle in name]


def time_inside(calls: list[tuple], s: float, e: float) -> float:
    return sum(ce - cs for cs, ce in calls if cs >= s and ce <= e)


def reduce_raw(raw: dict) -> dict | None:
    """The reduction proper. ``raw`` is ``{"window": (w0, w1), "spans":
    [{"name", "start", "end", "attrs"}], "planes": [{"ops", "modules"}]}``;
    the first plane is the one the spans are laid against (one engine
    drives one chip, or all chips in step). None where the trace holds no
    phase span or no device plane."""
    spans = sorted(raw["spans"], key=lambda s: s["start"])
    if not spans or not raw["planes"]:
        return None
    w0, w1 = raw["window"]
    plane = raw["planes"][0]
    dispatches = [s for s in spans if s["name"] == DISPATCH]
    found = align(dispatches, step_runs(plane["modules"]),
                  [s for s in spans if s["name"] == "engine.sync"])
    if found is None:
        return None
    offset = found["offset_ns"]
    # idle on the device's own clock, clipped as ``trace_reduce`` clips
    # busy time, so that the two add up to the window; then moved onto the
    # host's clock for the spans
    idle = complement([(s, e) for _, s, e in plane["ops"]], w0, w1)
    on_host = [(s - offset, e - offset) for s, e in idle]
    named = [(s["name"], s["start"], s["end"]) for s in spans]
    by_span = attribute(on_host, named)
    by_gap = attribute(on_host, between(named))
    del by_gap["other"]  # here: what a span does cover
    # launches the device stood waiting for: its last run had ended (on
    # the host's clock) before this dispatch began
    pairs = found["pairs"]
    launches = sorted(
        run[1] - offset - disp["start"]
        for (disp, run), (_, last) in zip(pairs[1:], pairs)
        if last[2] - offset <= disp["start"])
    floor = found["offset_floor_ns"]
    return {
        "window_s": (w1 - w0) / 1e9,
        "idle_s": sum(e - s for s, e in idle) / 1e9,
        "clock_offset_us": offset / 1e3,
        "clock_offset_floor_us": None if floor is None else floor / 1e3,
        "shift": found["shift"], "paired": len(pairs),
        "launch_after_idle_us": {
            "count": len(launches),
            "median": launches[len(launches) // 2] / 1e3 if launches else None,
            "max": launches[-1] / 1e3 if launches else None},
        "idle_by_span_s": {k: v / 1e9 for k, v in by_span.items()},
        "idle_by_layer_s": {k: v / 1e9 for k, v in by_layer(by_span).items()},
        # ``other`` by the spans on either side of the uncovered stretch
        "other_between_s": {k: v / 1e9 for k, v in by_gap.items()},
        # each paired dispatch with its run, for the kernels' rooflines
        "steps": [{"attrs": disp["attrs"], "run": run,
                   "inside": run[1] >= w0 and run[2] <= w1}
                  for disp, run in pairs],
    }


# ----------------------------------------------------- operations and bytes


def paged_attn_bytes(kv_tokens: int, n_kv_head: int, head_dim: int,
                     itemsize: int, n_layer: int) -> int:
    """Bytes the decode kernel must read for one step: K and V of every
    row's context (``kv_tokens``: the contexts rounded up to whole blocks,
    summed over rows) in every layer. The query and the output are a
    thousandth of that and left out, so the share errs low."""
    return kv_tokens * 2 * n_kv_head * head_dim * itemsize * n_layer


def flash_attn_flops(batch: int, heads: int, seq: int, head_dim: int,
                     n_layer: int) -> float:
    """Operations of causal attention in one training step: the forward's
    two matrix products over the lower triangle (2 x B x H x S^2 x hd),
    the backward's five (2.5 times that; the recomputed scores are among
    the five the algorithm needs), in every layer."""
    return 3.5 * 2.0 * batch * heads * seq * seq * head_dim * n_layer


def paged_attn_hbm_pct(reduced: dict, ops: list[tuple], widths: dict,
                       hbm_gb_per_s: float) -> dict | None:
    """The decode kernel's share of the HBM roofline over the decode steps
    that ran whole inside the window: bytes they had to read over the time
    their ``paged_attention`` calls took, over the peak."""
    total_bytes = 0
    total_ns = 0.0
    steps = 0
    calls = kernel_calls(ops, "paged_attention")
    for step in reduced["steps"]:
        a = step["attrs"]
        if a.get("kind") != "decode" or not step["inside"] \
                or PROGRAM_OF["decode"] not in step["run"][0]:
            continue
        ns = time_inside(calls, step["run"][1], step["run"][2])
        if ns <= 0:
            continue
        total_bytes += paged_attn_bytes(int(a["kv_tokens"]), **widths)
        total_ns += ns
        steps += 1
    if not steps:
        return None
    gb_per_s = total_bytes / total_ns
    return {"steps": steps, "bytes": total_bytes, "kernel_s": total_ns / 1e9,
            "gb_per_s": gb_per_s, "pct": 100.0 * gb_per_s / hbm_gb_per_s}


def flash_attn_mxu_pct(modules: list[tuple], ops: list[tuple],
                       window: tuple, flops_per_step: float,
                       bf16_tflops: float) -> dict | None:
    """The flash kernels' share of the MXU peak over the training steps
    that ran whole inside the window: a step is a program's run that holds
    Pallas calls (``tpu_custom_call``)."""
    w0, w1 = window
    total_ns = 0.0
    steps = 0
    calls = kernel_calls(ops, "tpu_custom_call")
    for _, s, e in modules:
        if s < w0 or e > w1:
            continue
        ns = time_inside(calls, s, e)
        if ns > 0:
            total_ns += ns
            steps += 1
    if not steps:
        return None
    tflops = flops_per_step * steps / total_ns / 1e3
    return {"steps": steps, "flops": flops_per_step * steps,
            "kernel_s": total_ns / 1e9, "tflops": tflops,
            "pct": 100.0 * tflops / bf16_tflops}


# ------------------------------------------------------- the trace's file


def read_file(path: str) -> dict | None:
    """The ``.xplane.pb`` as plain data for ``reduce_raw``: the window's
    two marks, the program's phase spans with their attributes, and each
    chip's operations and program runs. None without the marks."""
    from jax.profiler import ProfileData

    spans, planes = [], []
    marks: dict[str, float] = {}
    wanted = set(PHASES)
    for plane in ProfileData.from_file(path).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            planes.append({
                key: [(e.name, float(e.start_ns),
                       float(e.start_ns + e.duration_ns))
                      for e in lines[name].events] if name in lines else []
                for key, name in (("ops", trace_reduce.OPS_LINE),
                                  ("modules", trace_reduce.MODULES_LINE))})
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        spans.append({
                            "name": e.name, "start": float(e.start_ns),
                            "end": float(e.start_ns + e.duration_ns),
                            "attrs": dict(e.stats)})
                    elif e.name in (trace_reduce.MARK_OPEN,
                                    trace_reduce.MARK_CLOSE):
                        marks[e.name] = float(e.start_ns)
    if len(marks) != 2:
        return None
    return {"window": (marks[trace_reduce.MARK_OPEN],
                       marks[trace_reduce.MARK_CLOSE]),
            "spans": spans, "planes": planes}


def load(ctx: dict) -> tuple[dict | None, dict | None]:
    """``(raw, reduced)`` of the run's trace, read once a run and kept in
    ``ctx``; the table of idle time by span is said once. ``(raw, None)``
    where the program wrote no spans (the parent of the PR that brought
    them), ``(None, None)`` without a trace or a device plane."""
    if "span_trace" not in ctx:
        run = ctx.get("trace_run") or {}
        path = run.get("dir") and trace_reduce.find_xplane(run["dir"])
        raw = read_file(path) if path else None
        if raw is not None and not raw["planes"]:
            raw = None
        reduced = reduce_raw(raw) if raw else None
        if reduced:
            shown = {k: v for k, v in reduced.items() if k != "steps"}
            common.say(f"spans against the device: {shown}")
        ctx["span_trace"] = (raw, reduced)
    return ctx["span_trace"]


def idle_pct(ctx: dict, layer: str) -> float | None:
    """Device idle inside the marks under the spans of ``layer``, as a
    share of the slice, in percent."""
    _, reduced = load(ctx)
    if not reduced:
        return None
    return 100.0 * reduced["idle_by_layer_s"][layer] / reduced["window_s"]


def phase_totals(ctx: dict, kind: str) -> dict | None:
    """``{phase: [count, seconds]}`` of the steps of ``kind`` inside the
    window: ``stats_after - stats_before`` of ``engine.stats()["phases"]``.
    None where the program keeps no such totals."""
    after = (ctx.get("stats_after") or {}).get("phases")
    if after is None or kind not in after:
        return None
    before = ctx["stats_before"]["phases"].get(kind, {})
    return {name: [count - before.get(name, [0, 0.0])[0],
                   seconds - before.get(name, [0, 0.0])[1]]
            for name, (count, seconds) in after[kind].items()}


def counter_delta(ctx: dict, key: str) -> float | None:
    after = (ctx.get("stats_after") or {}).get(key)
    if after is None:
        return None
    return after - ctx["stats_before"][key]
