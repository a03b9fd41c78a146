"""Device time by the program's own names: every ``XLA Ops`` event of the
traced slice booked to (the kind of step whose program ran it, the part of
a layer the program says it belongs to).

A device trace cannot say this alone: an event is named by its
instruction's HLO text (``%fusion.161 = f32[4,2048,8,128]{...} fusion(...),
kind=kOutput, calls=...``) and carries no metadata, so the scope a program
gave the operation (``jax.named_scope``; the vocabulary is
``ray_tpu.serve.llm.obs.SCOPES``) is not in it. It IS in the program:
``ray_tpu.serve.llm.decode.program_scopes()`` lowers every step program the
process has run from its first call's abstract arguments and maps each
instruction's NAME to its scope and its result type (``obs.scope_map``).
What joins the two (settled on the chip, PR 50): an event's name is the
compiled line with the operands' types written out and without ``metadata``
and ``backend_config``, so the texts differ and the instruction's name and
result type are the key; instruction names repeat from program to program
(every prefill rung has a ``%fusion.161``), so a module run ``jit_x(<id>)``
is first matched to the ONE compiled text of a program named ``jit_x`` that
holds all of its events' names under their types. A run whose program has
no such text is SAID and booked as ``unmatched``, never guessed; a program
that is no step's (the engine's id gather) is ``other``.

Times are SELF times (an event's duration less its children's: a ``while``
holds its body's events) inside the window marks, as ``trace_reduce`` takes
them, so the table's cells add up to the device's busy time. A run that the
window cuts is booked with the part inside, and is not counted as a run.

Nothing here is called unless a metric of ``benchmark/layer_metrics/``
asks; the table is built once a traced run (``ctx["scope_table"]``), AFTER
the window has closed, and says what building it took.
"""
from __future__ import annotations

import bisect
import time

from benchmark import trace_reduce
from benchmark.common import say

# the metrics' groups of scopes; with ``unnamed`` they cover the table
GROUPS = {
    "attn_proj": ("attn_proj",),
    "attn": ("attn_kernel", "attn_cache"),
    "ffn": ("ffn", "dense_ffn", "moe_shared"),
    "experts": ("moe_gmm",),
    "moe_move": ("moe_route", "moe_move", "moe_zero"),
    "mixer": ("short_conv", "lightning_step", "lightning_chunk",
              "sparse_select", "sparse_prefill_attention", "eva_summarize"),
    "head": ("head", "sample", "counters", "embed", "layer_stack"),
    # of the prefill programs alone
    "matmul": ("attn_proj", "ffn"),
}
UNNAMED = "unnamed"       # the program's text is known and names no scope
UNMATCHED = "unmatched"   # a step program's run with no compiled text
OTHER = "other"           # a program that is no step's
# under this share of busy time named, no share by part is reported
NAMED_FLOOR_PCT = 90.0
KINDS = (("_prefill", "prefill"), ("_decode_step", "decode"),
         ("_verify_step", "verify"))


def kind_of(program: str) -> str:
    """The kind of step a program's name says (``jit_llama_prefill`` ->
    ``prefill``); ``OTHER`` for a program that is no step's."""
    for tail, kind in KINDS:
        if program.endswith(tail):
            return kind
    return OTHER


def match_program(events: dict, candidates: list) -> tuple:
    """The compiled text a module's runs belong to. ``events``: ``{(name,
    result type)}`` of the instructions that ran; ``candidates``: ``[(label,
    {name: (scope, type, mixed)})]``, the texts of the programs under the
    module's name. ``(label, scopes)`` of the one that holds every event
    under its type; several may (two rungs of one ladder compile to texts
    that agree where these events are): then they have to agree on every
    event's scope. ``(None, why)`` otherwise."""
    full = [(label, scopes) for label, scopes in candidates
            if all(name in scopes and scopes[name][1] == kind
                   for name, kind in events)]
    if not full:
        best = max(
            ((sum(name in scopes and scopes[name][1] == kind
                  for name, kind in events), label)
             for label, scopes in candidates), default=(0, None))
        return None, (f"no text of {len(candidates)} holds all "
                      f"{len(events)} instructions (the best, {best[1]}, "
                      f"{best[0]})")
    label, scopes = full[0]
    for other, theirs in full[1:]:
        if any(theirs[name][0] != scopes[name][0] for name, _ in events):
            return None, f"{label} and {other} both hold them and disagree"
    return label, scopes


def attribute(planes: list, window_ns: tuple, programs: dict) -> dict:
    """The table proper, on plain data. ``planes``: ``[{"ops": [(name,
    start, end)], "modules": [(name(id), start, end)]}]`` a chip;
    ``programs``: ``{label: {"name": "jit_x", "scopes": {...}}}``. Returns
    ``{"busy_s", "by": {kind: {scope: seconds}}, "runs": {kind: n},
    "mixed_s", "unmatched": {module: why}, "programs": {module: label}}``,
    seconds a chip (the planes' mean, as ``trace_reduce.reduce_planes``)."""
    from ray_tpu.serve.llm import obs

    w0, w1 = window_ns
    n = max(1, len(planes))
    by: dict = {}
    runs: dict = {}
    unmatched: dict = {}
    matched: dict = {}
    busy = mixed = 0.0
    for plane in planes:
        ops = trace_reduce.clip(plane["ops"], w0, w1)
        busy += trace_reduce.union_ns([(s, e) for _, s, e in ops])
        mods = sorted(trace_reduce.clip(plane["modules"], w0, w1),
                      key=lambda m: m[1])
        whole = {(name, s) for name, s, e in plane["modules"]
                 if s >= w0 and e <= w1}
        starts = [m[1] for m in mods]
        groups: dict = {}  # module(id) -> its events; None: in no run
        for ev in ops:
            k = bisect.bisect_right(starts, ev[1]) - 1
            inside = k >= 0 and ev[1] < mods[k][2]
            groups.setdefault(mods[k][0] if inside else None, []).append(ev)
        for name, s, _ in mods:
            if (name, s) in whole:
                kind = kind_of(trace_reduce._ID.sub("", name))
                runs[kind] = runs.get(kind, 0) + 1
        for module, events in groups.items():
            program = (None if module is None
                       else trace_reduce._ID.sub("", module))
            kind = OTHER if module is None else kind_of(program)
            own = trace_reduce.self_times(events)
            key_of = {name: obs.instruction_key(name) for name in own}
            scopes = None
            if kind != OTHER:
                label, scopes = match_program(
                    set(key_of.values()) - {None},
                    [(lb, p["scopes"]) for lb, p in programs.items()
                     if p["name"] == program])
                if label is None:
                    unmatched[module], scopes = scopes, None
                else:
                    matched[module] = label
            row = by.setdefault(kind, {})
            for name, (_, ns) in own.items():
                key = key_of[name]
                if kind == OTHER:
                    scope = OTHER
                elif scopes is None:
                    scope = UNMATCHED
                elif key is None or key[0] not in scopes:
                    scope = UNNAMED
                else:
                    scope = scopes[key[0]][0]
                    if scopes[key[0]][2]:
                        mixed += ns
                row[scope] = row.get(scope, 0.0) + ns
    return {
        "busy_s": busy / 1e9 / n,
        "by": {kind: {scope: ns / 1e9 / n for scope, ns in row.items()}
               for kind, row in by.items()},
        "runs": runs, "mixed_s": mixed / 1e9 / n,
        "unmatched": unmatched, "programs": matched,
    }


def read_planes(path: str):
    """``(planes, (w0, w1))`` of one ``.xplane.pb``: every chip's ``XLA
    Ops`` and ``XLA Modules`` events, the modules named as
    ``trace_reduce.reduce_file`` names them, and the window marks (the
    whole trace where there are none). None where no chip is in it."""
    from jax.profiler import ProfileData

    planes, marks, dispatched = [], {}, []
    lo, hi = float("inf"), 0.0
    for plane in ProfileData.from_file(path).planes:
        device = bool(trace_reduce.DEVICE_PLANE.match(plane.name))
        if not (device or plane.name == "/host:CPU"):
            continue
        ops, modules = [], []
        for line in plane.lines:
            events = [(e.name, float(e.start_ns),
                       float(e.start_ns + e.duration_ns))
                      for e in line.events]
            for name, s, e in events:
                lo, hi = min(lo, s), max(hi, e)
                if name in (trace_reduce.MARK_OPEN, trace_reduce.MARK_CLOSE):
                    marks[name] = s
                elif not device and trace_reduce.DISPATCH.match(name):
                    dispatched.append(
                        (trace_reduce.DISPATCH.match(name).group(1), s, e))
            if device and line.name == trace_reduce.OPS_LINE:
                ops = events
            elif device and line.name == trace_reduce.MODULES_LINE:
                modules = events
        if device:
            planes.append({"ops": ops, "modules": modules})
    if len(marks) == 2:
        lo, hi = marks[trace_reduce.MARK_OPEN], marks[trace_reduce.MARK_CLOSE]
    if not planes or hi <= lo:
        return None
    dispatches = [(fn, s) for fn, s, _ in trace_reduce.outermost(dispatched)]
    for p in planes:
        p["modules"] = trace_reduce.name_modules(p["modules"], dispatches)
    return planes, (lo, hi)


def table(ctx: dict) -> dict | None:
    """The traced run's table, built once (``ctx["scope_table"]``) and
    said. None where there is nothing to build it from: no traced run, no
    chip in the trace (a rehearsal's), or a program that keeps no record
    of its programs (a checkout from before PR 50)."""
    if "scope_table" in ctx:
        return ctx["scope_table"]
    ctx["scope_table"] = None
    run = ctx.get("trace_run")
    path = run and trace_reduce.find_xplane(run["dir"])
    if not path:
        return None
    from ray_tpu.serve.llm import decode

    if not hasattr(decode, "program_scopes"):
        say("scope table: this program keeps no record of its programs")
        return None
    t0 = time.perf_counter()
    read = read_planes(path)
    if read is None:
        return None
    planes, window = read
    ran = {trace_reduce._ID.sub("", m[0]) for p in planes
           for m in p["modules"]}
    t1 = time.perf_counter()
    programs = decode.program_scopes(only=ran)
    t2 = time.perf_counter()
    out = attribute(planes, window, programs)
    out["map_s"] = {"read_trace": t1 - t0, "program_scopes": t2 - t1,
                    "attribute": time.perf_counter() - t2,
                    "programs_read": len(programs)}
    ctx["scope_table"] = out
    say_table(out)
    return out


def say_table(out: dict) -> None:
    busy = out["busy_s"] or float("nan")
    say(f"scope table: built after the window in {out['map_s']}; busy "
        f"{out['busy_s']:.4f}s, whole runs by kind {out['runs']}, "
        f"{100.0 * out['mixed_s'] / busy:.1f}% of busy in fusions that mix "
        f"scopes (booked whole to the one they carry)")
    for kind, row in sorted(out["by"].items()):
        total = sum(row.values())
        cells = ", ".join(
            f"{scope} {s:.4f}s {100.0 * s / busy:.2f}%"
            for scope, s in sorted(row.items(), key=lambda kv: -kv[1]))
        say(f"scope table: {kind} {total:.4f}s "
            f"{100.0 * total / busy:.2f}% of busy: {cells}")
    for module, why in sorted(out["unmatched"].items()):
        say(f"scope table: UNMATCHED {module}: {why}")
    say(f"scope table: module -> program text {out['programs']}")


def seconds(out: dict, scopes, kind: str | None = None) -> float:
    return sum(s for k, row in out["by"].items() if kind in (None, k)
               for scope, s in row.items() if scope in scopes)


def named_pct(ctx: dict) -> float | None:
    """Busy time whose operation resolved to a name of ``obs.SCOPES``."""
    out = table(ctx)
    if not out or not out["busy_s"]:
        return None
    from ray_tpu.serve.llm import obs

    return 100.0 * seconds(out, obs.SCOPES) / out["busy_s"]


def share_pct(ctx: dict, group: str, kind: str | None = None):
    """The scopes of ``GROUPS[group]`` as a share of busy time (``kind``
    None) or of the device time of the programs of one ``kind`` of step.
    None where they took no time, or where less than ``NAMED_FLOOR_PCT`` of
    busy time resolved to a name at all: a share of a table that is a
    tenth holes ranks nothing."""
    named = named_pct(ctx)
    if named is None:
        return None
    if named < NAMED_FLOOR_PCT:
        say(f"scope share {group}: {named:.1f}% of busy time is named, "
            f"under {NAMED_FLOOR_PCT}: not reported")
        return None
    out = table(ctx)
    of = out["busy_s"] if kind is None else sum(
        out["by"].get(kind, {}).values())
    part = seconds(out, GROUPS[group], kind)
    if not part or not of:
        return None
    return 100.0 * part / of


def programs_of(ctx: dict, when: str):
    """``stats()["programs"]`` at one end of the window; None where the
    program keeps none (a checkout from before PR 50)."""
    return (ctx.get(when) or {}).get("programs")
