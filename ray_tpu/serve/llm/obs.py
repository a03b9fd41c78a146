"""Instrumentation plane for serve/llm: one clock, serving-latency
histograms, and the engine flight recorder.

Design constraints (ISSUE 4 / docs/OBSERVABILITY.md):

- **One clock.** Every duration the engine records — step latency
  histograms, flight-recorder records, phase totals — flows through
  ``clock()`` (monotonic), and every absolute timestamp (timelines,
  spans, chrome export) through ``wall()``; what a thread itself ran of
  a duration through ``thread_cpu()``. tests/test_sanitizers.py lints
  serve/llm for stray ``time.time()`` / ``time.perf_counter()`` /
  ``time.thread_time()`` calls outside this module, so the records can
  never disagree about what was measured.
- **Zero device syncs.** Nothing here touches jax values; the engine's
  single device->host sync point (``_host_tokens``) is unchanged.
- **O(1) per step.** The flight recorder is a ``deque(maxlen=N)`` ring:
  one dict append per step, old records drop off the far end. Dumping is
  a read-only snapshot, safe from the lock-free watchdog thread (a
  ``list(deque)`` copy is atomic under the GIL) — the whole point is
  explaining a step that wedged while holding the scheduler lock.
"""
from __future__ import annotations

import gc
import json
import logging
import os
import re
import tempfile
import threading
import time
from collections import deque

from ray_tpu._private import event_stats
from ray_tpu.util import metrics

logger = logging.getLogger("ray_tpu.serve.llm")

# THE two clocks: monotonic for durations, wall for timestamps that must
# line up across processes (timelines, spans, chrome export).
clock = time.perf_counter
wall = time.time
# ... and the CPU seconds the CALLING thread has run: beside a duration on
# ``clock()`` it says how much of it the thread was on a core. The rest it
# waited: for the interpreter, for a lock, for the device, for the machine.
# A kernel that accounts CPU time by its timer's ticks (the TPU host's: 10
# ms) moves this clock a tick at a time: ONE reading is then that coarse,
# and a sum over many phases right in expectation (a tick falls into a
# phase as often as the thread runs there). There a reading is a system
# call of ~6 us, a hundred times ``clock()``'s, which is why the phases of
# only one step in ``CPU_EVERY`` read it (``PhaseTable.cpu``).
thread_cpu = time.thread_time
CPU_EVERY = 8

# The profiler's host-span class, imported at first use: this module (and
# engine.py) import no jax at module level.
_annotation = None


def _span_class():
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation


class PhaseTable(dict):
    """Where phases are booked: ``{name: [count, seconds, cpu_seconds,
    cpu_measured_seconds]}``. ``cpu`` says whether a phase booked NOW reads
    the thread's CPU clock beside the wall clock: the engine turns it on
    for one step in ``CPU_EVERY``, and the last two numbers are the CPU
    seconds and the wall seconds of those phases alone."""

    cpu = True


def cpu_estimate(seconds: float, cpu_seconds: float,
                 measured_seconds: float) -> float:
    """CPU seconds of ALL the phases behind ``seconds``, from the ones
    whose CPU was read: their share on the core, taken for all. 0.0 where
    none was read yet."""
    if measured_seconds <= 0.0:
        return 0.0
    return cpu_seconds * seconds / measured_seconds


class phase:
    """One host phase of an engine step, as a context manager, booked twice:

    - as a ``jax.profiler.TraceAnnotation(name, **attrs)``: a span on the
      profiler's own clock, beside the device's operations, while a
      profiler session is active — and a flag test while none is. The
      profiler session is the only switch there is.
    - into ``totals`` (a ``PhaseTable``, the engine's), always: count and
      seconds on ``clock()``, what ``stats()["phases"]`` reports; and,
      while the table's ``cpu`` is on, the CPU seconds the thread ran of
      the phase on ``thread_cpu()`` (with the phase's seconds once more,
      as their base): what ``stats()["host"]`` estimates from.

    ``seconds`` holds the duration once the phase has closed, for a caller
    that feeds another record from the same reading. Phases of one step
    follow one another and never overlap, so their seconds add up; a span
    that lies INSIDE a phase (``executor.feed``) is booked into a table of
    its own. Never open one inside a per-row loop: one span around the loop.
    """

    __slots__ = ("_totals", "_name", "_span", "_t0", "_cpu0", "seconds")

    def __init__(self, totals: dict, name: str, **attrs):
        self._totals = totals
        self._name = name
        self._span = (_annotation or _span_class())(name, **attrs)

    def __enter__(self) -> "phase":
        self._span.__enter__()
        # (a plain dict as the table: every phase reads the CPU clock)
        self._cpu0 = (thread_cpu() if getattr(self._totals, "cpu", True)
                      else None)
        self._t0 = clock()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = clock() - self._t0
        cpu = None if self._cpu0 is None else thread_cpu() - self._cpu0
        self._span.__exit__(*exc)
        rec = self._totals.get(self._name)
        if rec is None:
            rec = self._totals[self._name] = [0, 0.0, 0.0, 0.0]
        rec[0] += 1
        rec[1] += self.seconds
        if cpu is not None:
            rec[2] += cpu
            rec[3] += self.seconds


class GcWatch:
    """The collector's pauses, for the whole process: ONE ``gc.callbacks``
    entry, in while any engine of the process lives (``acquire`` where an
    engine is built, ``release`` where it shuts down). A collection stops
    every Python thread, the step thread among them, so it is counted
    here and not by an engine: collections and seconds by generation,
    always, and a ``host.gc`` span (``generation``) on the profiler's
    clock for a generation-2 collection, the only kind long enough to
    show against a device step. The callback runs inside the collector:
    it never raises and touches no jax value."""

    def __init__(self):
        self.collections = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        self._users = 0
        self._guard = threading.Lock()
        self._t0 = None
        self._span = None

    def acquire(self) -> None:
        _span_class()  # imported here, never inside the collector
        with self._guard:
            self._users += 1
            if self._users == 1:
                gc.callbacks.append(self._on_gc)

    def release(self) -> None:
        with self._guard:
            self._users -= 1
            if self._users == 0 and self._on_gc in gc.callbacks:
                gc.callbacks.remove(self._on_gc)

    @property
    def installed(self) -> bool:
        return self._on_gc in gc.callbacks

    def total_seconds(self) -> float:
        return self.seconds[0] + self.seconds[1] + self.seconds[2]

    def totals(self) -> dict:
        return {"collections": list(self.collections),
                "seconds": list(self.seconds)}

    def _on_gc(self, when: str, info: dict) -> None:
        # collections do not nest and a collection starts and stops on
        # one thread, so one pending start is all there is to keep
        try:
            gen = info["generation"]
            if when == "start":
                if gen == 2 and _annotation is not None:
                    self._span = _annotation("host.gc", generation=gen)
                    self._span.__enter__()
                self._t0 = clock()
            elif self._t0 is not None:
                self.seconds[gen] += clock() - self._t0
                self.collections[gen] += 1
                self._t0 = None
                if self._span is not None:
                    span, self._span = self._span, None
                    span.__exit__(None, None, None)
        except Exception:  # noqa: BLE001 — an observer inside the collector
            self._t0 = self._span = None


# the process's one watch (``gc.callbacks`` is the process's own list)
gc_watch = GcWatch()


# Serving-appropriate buckets: TTFT spans "prefix-hit tiny model" (ms) to
# "cold 70B prefill" (tens of seconds); per-output-token tracks decode
# step cadence; queue wait tracks admission backpressure.
TTFT_BUCKETS = (
    0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
)
TPOT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 1.0)
QUEUE_WAIT_BUCKETS = (0.0005, 0.002, 0.01, 0.05, 0.2, 1.0, 5.0, 20.0)
# The single O(batch) device->host sync (engine._host_tokens): sub-ms on
# the pipelined steady state, device-step-sized when the lag collapses.
HOST_SYNC_BUCKETS = (
    0.00005, 0.0002, 0.001, 0.005, 0.02, 0.1, 0.5, 2.0,
)


def ttft_histogram() -> metrics.Histogram:
    return metrics.histogram(
        "llm_ttft_seconds",
        "Time from submit() to the first generated token",
        boundaries=TTFT_BUCKETS,
    )


def tpot_histogram() -> metrics.Histogram:
    return metrics.histogram(
        "llm_time_per_output_token_seconds",
        "Gap between consecutive generated tokens of one request",
        boundaries=TPOT_BUCKETS,
    )


def queue_wait_histogram() -> metrics.Histogram:
    return metrics.histogram(
        "llm_queue_wait_seconds",
        "Time a request waited for admission (submit -> admitted)",
        boundaries=QUEUE_WAIT_BUCKETS,
    )


def host_sync_histogram() -> metrics.Histogram:
    return metrics.histogram(
        "llm_host_sync_seconds",
        "Time blocked in the engine's single device->host token sync",
        boundaries=HOST_SYNC_BUCKETS,
    )


def sync_bytes_counter() -> metrics.Counter:
    return metrics.counter(
        "llm_sync_bytes",
        "Bytes crossed device->host at the engine's token sync point "
        "(O(batch) int32 per step under fused sampling)",
    )


def goodput_gauge() -> metrics.Gauge:
    return metrics.gauge(
        "llm_goodput_tokens_per_sec",
        "Windowed serving goodput: tokens retired per second of "
        "attributed device time, by step kind",
        tag_keys=("kind",),
    )


def mfu_gauge() -> metrics.Gauge:
    return metrics.gauge(
        "llm_serving_mfu",
        "Windowed serving model-FLOPs utilization: goodput x 2*n_params "
        "FLOPs/token over the executor's peak FLOP rate, by step kind",
        tag_keys=("kind",),
    )


def compile_counter() -> metrics.Counter:
    return metrics.counter(
        "llm_compile_events",
        "New jit signatures seen by this engine's DecodeFns, by shape key",
        tag_keys=("shape",),
    )


def shape_key(sig: tuple) -> str:
    """Stable label for one (kind, tokens_shape, tables_shape) signature,
    e.g. ``prefill_chunk:4x32:4x8`` — bounded cardinality because shapes
    are drawn from the closed bucket ladders."""
    kind, tok, tbl = sig
    return (
        f"{kind}:{'x'.join(str(d) for d in tok)}:"
        f"{'x'.join(str(d) for d in tbl)}"
    )


# ---- the program's own names for the parts of a step ------------------
#
# THE vocabulary: every ``jax.named_scope`` of a step program (models/
# cached.py, the families' ``layer``, ops/) takes its name from here, and
# nothing else reads as a part. A scope is metadata of the compiled
# program (``op_name="jit(llama_prefill)/.../attn_proj/dot_general"``): it
# costs a trace its string and a served step nothing, and it is NOT in a
# profiler trace (an ``XLA Ops`` event is the instruction's text without
# its metadata), which is why ``DecodeFns.program_scopes()`` maps
# instruction names to scopes from the compiled text. Scopes nest, and an
# instruction belongs to the INNERMOST one that is listed here:
#
# - ``embed``: the token (and position) embedding, and what a step
#   prepares per token before its layers: positions, masks, rotary rows,
#   the working form of ``state``;
# - ``layer_stack``: a scanned stack's own loop: a layer's weights sliced
#   out of the stacked tree, the loop's counter (the layers' own
#   operations lie deeper, under the names below);
# - ``attn_proj``: an attention layer around its cache side: the norm
#   before it, the q / k / v / o products, rotary, QK-norm, a gate a
#   head, a latent layer's down / up and absorb products, the residual;
# - ``attn_cache``: the scatter of the step's new rows into the pool;
# - ``attn_kernel``: the paged / latent / window / sparse attention call
#   and the relayout around it. Inside it ``sparse_select`` (the
#   selection of a selecting layer's pages) and
#   ``sparse_prefill_attention`` (its chunk attention) keep their names;
# - ``eva_summarize``: a composed family's chunk summaries (ops/eva.py),
#   written between ``attn_cache`` and ``attn_kernel``;
# - ``short_conv``, ``lightning_step``, ``lightning_chunk``: the mixers
#   that stand where attention would (their in / out products lie under
#   ``attn_proj``); a KDA layer's parts (models/ling_hybrid.py):
#   ``kda_conv`` (the convolution over ``[q | k | v]``, SiLU, the L2
#   norms), ``kda_gate`` (the decay a channel, ``beta``), ``kda_step`` /
#   ``kda_chunk`` (the delta rule, ops/kda.py), ``kda_out`` (the norm a
#   head and the output gate); a Mamba-2 branch's parts
#   (models/falcon_h1.py, beside attention in the same layer): ``ssd_proj``
#   (its in / out products and the multipliers on them), ``ssd_conv`` (the
#   convolution over ``[x | B | C]`` with bias, SiLU, ``dt``'s softplus),
#   ``ssd_step`` / ``ssd_chunk`` (the recurrence, ops/ssd.py), ``ssd_out``
#   (the gate and the norm a group);
# - ``ffn``: the feed-forward half of a layer: its norm, the dense MLP,
#   the residual. Inside it ``dense_ffn`` (a double layer's SwiGLUs),
#   ``moe_route`` (router product, scores, top-k), ``moe_move`` (what
#   stands between router and grouped product and behind it: the sort,
#   the gathers, the weighted combine), ``moe_gmm`` (the grouped expert
#   product), ``moe_zero`` (zero-compute experts) and ``moe_shared`` (the
#   shared expert);
# - ``head``: the final norm, the gather of the rows that reach the head,
#   the head's product; ``sample``: the sampling / verify epilogue;
# - ``counters``: the counter words a family's programs keep in ``state``.
SCOPES = (
    "embed", "layer_stack", "attn_proj", "attn_cache", "attn_kernel",
    "sparse_select", "sparse_prefill_attention", "eva_summarize",
    "short_conv", "lightning_step", "lightning_chunk", "kda_conv",
    "kda_gate", "kda_step", "kda_chunk", "kda_out", "ssd_proj", "ssd_conv",
    "ssd_step", "ssd_chunk", "ssd_out", "ffn", "dense_ffn",
    "moe_route", "moe_move", "moe_gmm", "moe_zero", "moe_shared", "head",
    "sample", "counters",
)
# an instruction under none of them
UNNAMED = "unnamed"

_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
# a printed type's layouts and the ``/*index=5*/`` marks of a long tuple
_LAYOUT = re.compile(r"\{[^{}]*\}|/\*[^*]*\*/")
_CALLED = re.compile(r"\b(calls|to_apply)=%([\w.\-]+)")
_REF = re.compile(r"%[\w.\-]+")


def scope_of(op_name: str) -> str:
    """The innermost component of an instruction's ``op_name`` that is a
    name of ``SCOPES``; ``UNNAMED`` where none is."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return UNNAMED


def instruction_key(line: str) -> tuple[str, str] | None:
    """``(name, result type)`` of one instruction's text: a line of a
    compiled program (``%fusion.3 = bf16[64,4096]{1,0:T(8,128)} fusion(
    %p), kind=kLoop, ..., metadata={...}``) or a device event's name in a
    profiler trace, which is that line with the operands' types written
    out and no metadata. The type is taken without its layouts (``bf16[64,
    4096]``; a tuple's in its parentheses): the two printers agree on
    shapes. None where the text is no instruction."""
    line = line.strip()
    if line.startswith("ROOT "):
        line = line[5:]
    name, eq, rest = line.partition(" = ")
    if not eq or not name.startswith("%"):
        return None
    if rest.startswith("("):  # a tuple: up to the parenthesis that closes it
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        kind = rest[:i + 1]
    else:
        kind = rest.split(" ", 1)[0]
    return name[1:], _LAYOUT.sub("", kind)


def hlo_computations(text: str) -> dict[str, list[str]]:
    """``{computation name: its instructions' lines}`` of a compiled
    program's text."""
    bodies: dict[str, list[str]] = {}
    current = None
    for line in text.splitlines():
        if current is None:
            # "%name (params) -> type {" or "ENTRY %name (...) -> ... {"
            if line.endswith("{") and "->" in line and (
                    line.startswith("%") or line.startswith("ENTRY ")):
                head = line.removeprefix("ENTRY ")
                current = bodies.setdefault(head.split(" ", 1)[0][1:], [])
        elif line.startswith("}"):
            current = None
        else:
            current.append(line)
    return bodies


def scope_map(text: str) -> dict[str, tuple[str, str, bool]]:
    """``{instruction name: (scope, result type, mixed)}`` of one compiled
    program's text (``compiled.as_text()``), one entry for every
    instruction that can run as a device event of its own: those of the
    entry computation, of ``while`` bodies and conditions and of
    ``conditional`` branches. What a fusion holds runs as the ONE fusion,
    so a fused computation's instructions have no entry; the fusion is
    named by its own metadata (the compiler gives it its root's), or, where
    that names no scope, by the one scope its fused instructions agree on;
    ``mixed`` says that they name more than one (a norm fused into the
    product behind it: the fusion's time is then booked whole to the scope
    it carries). An instruction the COMPILER added carries no ``op_name``
    of the program's (none at all: a copy that relays an operand out, a
    prefetch's ``copy-start`` / ``copy-done``; or a bare ``reduce_window_sum``
    with no ``jit(...)/`` path before it): it takes the scope its users in the computation
    agree on, else the one its operands agree on; one that has an
    ``op_name`` under no scope stays ``UNNAMED``: the program did not name
    it. Instruction names are unique in a module. A pure function: no
    jax, no device."""
    bodies = hlo_computations(text)
    fused: set[str] = set()
    for lines in bodies.values():
        for line in lines:
            for how, callee in _CALLED.findall(line):
                if how == "to_apply" or " fusion(" in line:
                    fused.add(callee)

    def own(line: str) -> str:
        m = _OP_NAME.search(line)
        return scope_of(m.group(1)) if m else UNNAMED

    out: dict[str, tuple[str, str, bool]] = {}
    for comp, lines in bodies.items():
        if comp in fused:
            continue
        named: dict[str, list] = {}    # name -> [scope, type, mixed]
        added: dict[str, list] = {}    # the compiler's own -> operands
        for line in lines:
            key = instruction_key(line)
            if key is None:
                continue
            scope, mixed = own(line), False
            callee = _CALLED.search(line) if " fusion(" in line else None
            if callee is not None:
                inside = {own(ln) for ln in bodies.get(callee.group(2), ())
                          if "metadata={" in ln} - {UNNAMED}
                mixed = len(inside | ({scope} - {UNNAMED})) > 1
                if scope == UNNAMED and len(inside) == 1:
                    scope = next(iter(inside))
            named[key[0]] = [scope, key[1], mixed]
            op_name = _OP_NAME.search(line)
            if scope == UNNAMED and not (
                    op_name and op_name.group(1).startswith("jit(")):
                added[key[0]] = [
                    n[1:] for n in _REF.findall(line.split(" = ", 1)[1])]
        users: dict[str, list] = {}
        for line in lines if added else ():
            key = instruction_key(line)
            if key is not None:
                for n in _REF.findall(line.split(" = ", 1)[1]):
                    if n[1:] in added:
                        users.setdefault(n[1:], []).append(key[0])
        for _ in range(3 if added else 0):  # a copy of a copy of a ...
            for name, operands in added.items():
                if named[name][0] != UNNAMED:
                    continue
                for around in (users.get(name, ()), operands):
                    agreed = {named[n][0] for n in around
                              if n in named} - {UNNAMED}
                    if len(agreed) == 1:
                        named[name][0] = next(iter(agreed))
                        break
        out.update((k, tuple(v)) for k, v in named.items())
    return out


class FlightRecorder:
    """Bounded ring of per-step records for post-mortem debugging.

    ``record()`` appends one dict (phase, bucket shape, admission/eviction
    counts, duration, KV utilization — built by the engine under its
    lock); ``dump()`` packages the ring plus the process's event_stats
    into one JSON-safe dict. Dumped on ``EngineDiedError``, watchdog
    timeout, ``shutdown(dump=...)``, ``engine.debug_dump()`` and the
    proxy's ``/debug/llm`` endpoint.
    """

    def __init__(self, capacity: int = 256):
        self.capacity = max(1, int(capacity))
        self._ring: deque[dict] = deque(maxlen=self.capacity)
        self._steps = 0

    def __len__(self) -> int:
        return len(self._ring)

    def record(self, rec: dict) -> None:
        """O(1): one append; the ring evicts from the far end."""
        self._steps += 1
        rec["step"] = self._steps
        self._ring.append(rec)

    def snapshot(self) -> list[dict]:
        # list(deque) is a GIL-atomic copy — safe without the engine lock
        # (the watchdog dumps while the wedged stepper still holds it)
        return list(self._ring)

    def dump(self, reason: str, extra: dict | None = None) -> dict:
        out = {
            "reason": reason,
            "ts": wall(),
            "pid": os.getpid(),
            "steps_total": self._steps,
            "capacity": self.capacity,
            "steps": self.snapshot(),
            "event_stats": event_stats.snapshot(),
        }
        if extra:
            out.update(extra)
        return out


def dump_dir(explicit: str | None = None) -> str:
    """Where flight-recorder JSON lands: the engine's configured dir, else
    ``RAY_TPU_FLIGHT_DIR``, else ``<tmp>/ray_tpu_flight``."""
    return (
        explicit
        or os.environ.get("RAY_TPU_FLIGHT_DIR")
        or os.path.join(tempfile.gettempdir(), "ray_tpu_flight")
    )


# dump-directory bound: keep the newest N auto-named dumps. Repeated
# engine deaths (e.g. a crash-looping deployment respawning through a
# controller outage) write one dump per death — unbounded, that fills
# the disk the incident responder needs for the postmortem itself.
FLIGHT_KEEP_ENV = "RAY_TPU_FLIGHT_KEEP"
_FLIGHT_KEEP_DEFAULT = 20


def _prune_dumps(d: str) -> None:
    """Rotate auto-named flight dumps in ``d``: keep the newest N
    (RAY_TPU_FLIGHT_KEEP, default 20; <= 0 disables rotation).
    Best-effort like the writes — pruning must never raise."""
    try:
        keep = int(os.environ.get(FLIGHT_KEEP_ENV, _FLIGHT_KEEP_DEFAULT))
    except ValueError:
        keep = _FLIGHT_KEEP_DEFAULT
    if keep <= 0:
        return
    try:
        names = [
            n
            for n in os.listdir(d)
            if n.startswith("llm_flight_") and n.endswith(".json")
        ]
        if len(names) <= keep:
            return
        # auto-generated names embed wall-clock ms, but concurrent pids
        # interleave — mtime is the honest recency order
        paths = sorted(
            (os.path.join(d, n) for n in names),
            key=lambda p: os.stat(p).st_mtime,
        )
        for p in paths[:-keep]:
            os.unlink(p)
    except OSError as e:
        logger.warning("flight-recorder dir prune failed: %r", e)


def write_dump(
    dump: dict, *, dir: str | None = None, path: str | None = None
) -> str | None:
    """Serialize one flight-recorder dump to disk. Best-effort by
    contract: the dump happens while the engine is dying, and
    observability must never turn a clean failure fan-out into a crash —
    returns the path, or None when the write failed. Auto-named dumps
    rotate (newest RAY_TPU_FLIGHT_KEEP kept); an explicit ``path`` is
    the caller's to manage."""
    auto = path is None
    try:
        if path is None:
            d = dump_dir(dir)
            os.makedirs(d, exist_ok=True)
            path = os.path.join(
                d,
                f"llm_flight_{os.getpid()}_{int(wall() * 1000)}.json",
            )
        with open(path, "w") as f:
            json.dump(dump, f, indent=1, default=str)
        if auto:
            _prune_dumps(os.path.dirname(path))
        return path
    except Exception as e:  # noqa: BLE001 — never fail the failure path
        logger.warning("flight-recorder dump failed: %r", e)
        return None
