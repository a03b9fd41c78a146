"""The stepping thread's whole cycle has a name.

Beside the phases of a step (``stats()["phases"]``, whose names and shape
are FROZEN here: two accepted benchmark readers sum and unpack them) the
engine says what else the thread's time is made of, under ``stats()
["host"]``: the wait for its own lock (``engine.lock``), the id gather
inside the stage phase (``executor.feed``), the CPU seconds the thread
itself ran of each phase, the collector's pauses in the process
(``host.gc``) and what a step stages. Every flight record carries the
three that explain a slow step: ``lock_ms``, ``gc_ms``, ``cpu_ms``.
"""
from __future__ import annotations

import dataclasses
import gc
import threading
import time

import pytest

from ray_tpu.serve.llm import obs

# THE names ``stats()["phases"]`` may hold, each ``[count, seconds]``:
# ``decode_host_ms.*`` sums every one of a kind but ``engine.sync``, and
# ``stage_ms.sat`` unpacks a pair. A new span goes under ``stats()["host"]``.
FROZEN_PHASES = {
    "engine.schedule", "engine.batch", "kv.reserve", "executor.stage",
    "executor.dispatch", "engine.sync", "engine.emit", "engine.account",
    "engine.wait",
}


def _engine(auto_step: bool = False, model: dict | None = None, **kw):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32,
                              attention="xla", **(model or {}))
    return LLMEngine(EngineConfig(model="llama", model_config=cfg, **kw),
                     auto_step=auto_step)


def _run(eng, streams, limit: int = 400) -> None:
    for _ in range(limit):
        if all(s.done for s in streams):
            return
        eng.step()
    raise AssertionError("streams did not finish")


def _records(eng) -> list[dict]:
    return [r for r in eng.debug_dump()["steps"] if r["kind"] != "compile"]


@pytest.mark.timeout(120)
def test_phase_names_and_shape_are_frozen(jax_cpu):
    eng = _engine(auto_step=True)
    for s in [eng.submit([i + 1, 2, 3], max_new_tokens=6) for i in range(3)]:
        assert len(list(s)) == 6
    time.sleep(0.12)  # an idle loop books ``engine.wait``
    st = eng.stats()
    eng.shutdown()
    phases = st["phases"]
    # a dense family's prompts are packed: the chunk kind, cold or not
    assert set(phases) == {"prefill_chunk", "decode", "none"}
    assert {n for table in phases.values() for n in table} == FROZEN_PHASES
    for table in phases.values():
        for rec in table.values():
            assert isinstance(rec, list) and len(rec) == 2
            assert isinstance(rec[0], int) and isinstance(rec[1], float)
    # everything new lives under ONE key, and mirrors the phases' keys
    host = st["host"]
    assert set(host) == {"spans", "phase_cpu", "gc", "stage_transfers",
                         "stage_bytes", "stage_masks", "emit_rows"}
    assert {k: set(v) for k, v in host["phase_cpu"].items()} == {
        k: set(v) for k, v in phases.items()}
    assert set(host["spans"]) <= {"engine.lock", "executor.feed"}
    for count, seconds, cpu in host["spans"].values():
        assert count > 0 and seconds >= 0.0 and cpu >= 0.0
    # a thread cannot run more CPU than wall time passes (clock grain)
    for kind, table in host["phase_cpu"].items():
        for name, cpu in table.items():
            assert 0.0 <= cpu <= phases[kind][name][1] + 0.02
    # waiting for work is not running
    assert host["phase_cpu"]["none"]["engine.wait"] < \
        0.5 * phases["none"]["engine.wait"][1]


@pytest.mark.timeout(120)
def test_a_held_lock_shows_in_the_span_and_the_next_record(jax_cpu):
    eng = _engine()
    streams = [eng.submit([1, 2, 3], max_new_tokens=8)]
    for _ in range(3):
        eng.step()
    before = eng.stats()["host"]["spans"]["engine.lock"]
    quiet = _records(eng)[-1]
    assert quiet["lock_ms"] < 20.0
    # a record counts the thread's CPU from the record before it; the
    # first one has no such base (and a thread's CPU clock is its own)
    assert _records(eng)[0]["cpu_ms"] == 0.0 < quiet["cpu_ms"] < 5000.0
    holding, let_go = threading.Event(), threading.Event()

    def hold():
        with eng._lock:
            holding.set()
            let_go.wait(timeout=30)

    t = threading.Thread(target=hold)
    t.start()
    assert holding.wait(timeout=30)
    threading.Timer(0.05, let_go.set).start()
    assert eng.step()  # asks for the lock while the other thread has it
    t.join(timeout=30)
    assert not t.is_alive()
    after = eng.stats()["host"]["spans"]["engine.lock"]
    assert after[0] == before[0] + 1
    assert after[1] - before[1] >= 0.045
    # the thread waited: it did not run
    assert after[2] - before[2] < 0.02
    held = _records(eng)[-1]
    assert held["lock_ms"] >= 45.0
    # and the wait is said once: the record after it is quiet again
    assert eng.step()
    assert _records(eng)[-1]["lock_ms"] < 20.0
    _run(eng, streams)
    eng.shutdown()


@pytest.mark.timeout(120)
def test_a_collection_shows_in_host_gc_and_the_next_record(jax_cpu):
    gc.collect()
    users = obs.gc_watch._users
    eng = _engine()
    assert obs.gc_watch.installed
    streams = [eng.submit([1, 2, 3], max_new_tokens=8)]
    for _ in range(3):
        eng.step()
    before = eng.stats()["host"]["gc"]
    junk = [[i] for i in range(200_000)]  # work for the collector
    junk.append(junk)
    gc.collect()
    del junk
    assert eng.step()
    after = eng.stats()["host"]["gc"]
    assert len(after["collections"]) == len(after["seconds"]) == 3
    assert after["collections"][2] >= before["collections"][2] + 1
    took = after["seconds"][2] - before["seconds"][2]
    assert took > 0.0
    record = _records(eng)[-1]
    assert record["gc_ms"] >= 1000.0 * took - 0.01 > 0.0
    assert eng.step()
    assert _records(eng)[-1]["gc_ms"] <= record["gc_ms"]
    _run(eng, streams)
    eng.shutdown()
    eng.shutdown()  # a second shutdown gives nothing back twice
    assert obs.gc_watch._users == users
    assert obs.gc_watch.installed == (users > 0)
    if not users:
        assert not any(getattr(cb, "__self__", None) is obs.gc_watch
                       for cb in gc.callbacks)


def test_the_collector_callback_never_raises():
    watch = obs.GcWatch()
    watch._on_gc("stop", {"generation": 0})  # a stop with no start
    assert watch.totals() == {"collections": [0, 0, 0],
                              "seconds": [0.0, 0.0, 0.0]}
    watch._on_gc("start", {})  # not what the collector hands over
    watch._on_gc("start", {"generation": 1})
    watch._on_gc("stop", {"generation": 1})
    assert watch.collections == [0, 1, 0] and watch.seconds[1] > 0.0
    assert watch.total_seconds() == watch.seconds[1]
    assert not watch.installed


@pytest.mark.timeout(120)
def test_a_generation_two_collection_is_a_span(jax_cpu, monkeypatch):
    seen = []

    class Span:
        def __init__(self, name, **attrs):
            self.rec = {"name": name, "attrs": attrs, "closed": False}

        def __enter__(self):
            seen.append(self.rec)

        def __exit__(self, *exc):
            self.rec["closed"] = True

    monkeypatch.setattr(obs, "_annotation", Span)
    watch = obs.GcWatch()
    watch.acquire()
    try:
        gc.collect(0)
        gc.collect(1)
        assert not [s for s in seen if s["name"] == "host.gc"]
        gc.collect()
    finally:
        watch.release()
    spans = [s for s in seen if s["name"] == "host.gc"]
    # (the process's own watch says the same where an engine of another
    # test lives on in this process)
    assert spans == [{"name": "host.gc", "attrs": {"generation": 2},
                      "closed": True}] * (1 + obs.gc_watch.installed)
    assert watch.collections[2] == 1 and not watch.installed


@pytest.mark.timeout(180)
def test_spans_cover_the_stepping_thread(jax_cpu):
    """Over 200 steps of the engine's own thread, the phases, the waits
    for the lock and the waits for work add up to the thread's wall time
    within 5%: nothing the loop does at length is outside a span. The
    model is wide enough for its step to be the longer part of a cycle:
    on a machine whose one core the stepping thread shares with the
    "device", the thread is put off the core wherever it runs, between
    two spans as well, in proportion to its own work there."""
    eng = _engine(auto_step=True, model=dict(
        n_layer=4, d_model=512, d_mlp=2048, n_head=8, n_kv_head=2))

    def wave() -> None:
        streams = [eng.submit([i + 1, 5, 9, 4], max_new_tokens=40)
                   for i in range(3)]
        # polled, not read token by token: a reader woken at every token
        # takes the interpreter from the stepping thread between spans
        deadline = time.monotonic() + 120
        while not all(s.done for s in streams):
            assert time.monotonic() < deadline
            time.sleep(0.02)

    wave()  # every step program and id gather compiled, the thread up

    def booked(st) -> float:
        return sum(rec[1] for table in st["phases"].values()
                   for rec in table.values()) \
            + st["host"]["spans"]["engine.lock"][1]

    def steps(st) -> int:
        return st["decode_steps"] + st["prefill_steps"]

    def window() -> tuple[float, float]:
        st0, t0 = eng.stats(), obs.clock()
        while True:
            wave()
            st1, t1 = eng.stats(), obs.clock()
            if steps(st1) - steps(st0) >= 200:
                return booked(st1) - booked(st0), t1 - t0

    # the best of three windows: on a loaded machine the thread is put off
    # the core between two spans too, which is the machine's doing
    seen = []
    for _ in range(3):
        covered, wall = window()
        seen.append((covered / wall, covered, wall))
        if covered >= 0.95 * wall:
            break
    eng.shutdown()
    share, covered, wall = max(seen)
    # ``stats()`` reads under the lock: a span may close a moment after
    # the clock was read
    assert 0.95 * wall <= covered <= wall + 0.05, seen


@pytest.mark.timeout(120)
def test_stage_counts_what_it_moves(jax_cpu):
    eng = _engine()
    streams = [eng.submit([i + 1, 2, 3], max_new_tokens=4 + 3 * i)
               for i in range(3)]
    _run(eng, streams)
    st = eng.stats()
    host = st["host"]
    launches = st["decode_steps"] + st["prefill_steps"]
    assert launches == st["phases"]["decode"]["executor.dispatch"][0] + \
        st["phases"]["prefill_chunk"]["executor.dispatch"][0]
    # a prefill moves tokens, lengths, tables and four sampling leaves; a
    # decode step positions, tables and the four, and its ids or the
    # indices to gather them by unless the batch is the one in flight.
    # The fifth leaf, the allow-mask, rests on the device where no row is
    # constrained: no launch moved one
    assert 6 * launches <= host["stage_transfers"] <= 8 * launches
    assert host["stage_bytes"] >= 4 * host["stage_transfers"]
    assert host["stage_masks"] == 0
    # rows left the batch between steps: their ids were gathered, under
    # a span of its own inside the stage phase
    assert st["decode_steps_remapped"] > 0
    feed = host["spans"]["executor.feed"]
    assert feed[0] == st["decode_steps_remapped"]
    assert 0.0 < feed[1] < st["phases"]["decode"]["executor.stage"][1]
    assert "executor.feed" not in st["phases"]["decode"]
    # under a grammar a mask IS moved, by the launches that hold a
    # constrained row and by no other: counted where the engine sees the
    # batch, beside the executor's own count
    constrained = []
    sample_args = eng._sample_args_locked

    def seen(batch, *args, **kw):
        constrained.append(any(r.fsm is not None for r in batch))
        return sample_args(batch, *args, **kw)

    eng._sample_args_locked = seen
    words = (eng.model_cfg.vocab_size + 31) // 32
    streams = [
        eng.submit([1, 2, 3], max_new_tokens=3,
                   structured={"type": "regex", "pattern": "(yes|no)"}),
        eng.submit([4, 5, 6], max_new_tokens=12),
    ]
    _run(eng, streams)
    st = eng.stats()
    after = st["host"]
    assert len(constrained) == \
        st["decode_steps"] + st["prefill_steps"] - launches
    assert 0 < sum(constrained) < len(constrained)
    assert after["stage_masks"] == sum(constrained)
    moved = after["stage_transfers"] - host["stage_transfers"]
    assert 6 * len(constrained) + sum(constrained) <= moved \
        <= 8 * len(constrained) + sum(constrained)
    assert after["stage_bytes"] - host["stage_bytes"] >= \
        4 * words * sum(constrained)
    eng.shutdown()


@pytest.mark.timeout(120)
def test_goodput_window_sums_are_the_samples(jax_cpu, monkeypatch):
    """The two gauges' sums are kept as samples enter and leave the
    window; they are what summing the window gives, whichever way a
    sample left it (too many, or too old)."""
    from ray_tpu.serve.llm import engine as engine_mod

    eng = _engine()
    now = [1000.0]
    monkeypatch.setattr(obs, "clock", lambda: now[0])
    monkeypatch.setattr(engine_mod, "_GOODPUT_WINDOW_STEPS", 8)
    for i in range(40):
        now[0] += 0.5 if i != 25 else 60.0  # one gap empties the window
        eng._goodput_record_locked("decode", 0.004 + 0.001 * (i % 5),
                                   3 + i % 7)
        samples, dev_s, toks = eng._goodput_windows["decode"]
        assert len(samples) <= 8
        assert all(s[0] >= now[0] - 30.0 for s in samples)
        assert toks == sum(s[2] for s in samples)
        assert dev_s == pytest.approx(sum(s[1] for s in samples), rel=1e-9)
        last = eng.stats()["goodput"]["decode"]
        assert last["window_steps"] == len(samples)
        assert last["window_tokens"] == toks
        assert last["tokens_per_sec"] == pytest.approx(
            toks / sum(s[1] for s in samples), abs=1e-3)
        if i == 25:
            assert len(samples) == 1
    eng.shutdown()


@pytest.mark.timeout(120)
def test_phases_read_the_cpu_clock_on_one_step_in_eight(jax_cpu, monkeypatch):
    """A reading of the thread's CPU clock is a system call (6 us on the
    TPU's host, a hundred times the wall clock's): the phases of one step
    in ``obs.CPU_EVERY`` take it, and ``stats()["host"]["phase_cpu"]`` is
    their share on the core taken for all."""
    assert obs.cpu_estimate(10.0, 1.0, 2.0) == 5.0
    assert obs.cpu_estimate(10.0, 0.0, 0.0) == 0.0
    eng = _engine()
    streams = [eng.submit([1, 2, 3], max_new_tokens=40)]
    calls = [0]
    real = obs.thread_cpu

    def counted():
        calls[0] += 1
        return real()

    monkeypatch.setattr(obs, "thread_cpu", counted)
    per_step = []
    for _ in range(2 * obs.CPU_EVERY):
        before = calls[0]
        assert eng.step()
        per_step.append(calls[0] - before)
    # always: the lock's span (2 readings) and the flight record's (1),
    # a gather's span where there was one (2: the decode step behind a
    # packed prefill takes its id from the last piece's row, and books
    # that prefill's record too, 1); beside them every phase's two on the
    # steps that are measured, the first and the ninth
    measured = [i for i, n in enumerate(per_step) if n > 6]
    assert measured == [0, obs.CPU_EVERY]
    # (a prefill launched with its sync put off: five phases, no record)
    assert all(per_step[i] >= 2 + 2 * 5 for i in measured)
    st = eng.stats()
    cpu = st["host"]["phase_cpu"]["decode"]
    assert set(cpu) == set(st["phases"]["decode"])
    assert 0.0 < cpu["executor.stage"] <= \
        st["phases"]["decode"]["executor.stage"][1] * 1.5
    _run(eng, streams)
    eng.shutdown()
