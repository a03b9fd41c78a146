"""A step hands its tokens over in one cheap pass.

A request's stream is the interpreter's own C queue (``queue.SimpleQueue``)
and a reconciled step's rows are booked together: one reading of the clocks
for all of them, the TTFT / TPOT histograms and the tokens counter touched
once a step. What a reader receives does not change: every token in order
and exactly once, an exception raised where it was put and the stream ended
behind it, a blocked reader ended by cancel, deadline, shutdown and the
engine's death. ``stats()["host"]["emit_rows"]`` counts the tokens the emit
passes put on streams.
"""
from __future__ import annotations

import dataclasses
import queue
import sys
import threading
import time

import pytest

from ray_tpu._private.chaos import Fault, FaultPlan
from ray_tpu.util import metrics

ROWS = 64
NEW = 9


def _engine(auto_step: bool = False, **kw):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32,
                              attention="xla")
    kw.setdefault("max_batch_size", ROWS)
    kw.setdefault("num_blocks", 1024)
    return LLMEngine(EngineConfig(model="llama", model_config=cfg, **kw),
                     auto_step=auto_step)


def _prompt(i: int) -> list[int]:
    return [i % 200 + 1, 2, 3 + i % 5, 4][: 2 + i % 3]


def _sampling(i: int, how: str) -> dict:
    if how == "greedy":
        return {"temperature": 0.0}
    return {"temperature": 0.9, "top_p": 0.95, "seed": 1000 + i}


def _submit_all(eng, n: int, how: str, **kw) -> list:
    return [eng.submit(_prompt(i), max_new_tokens=NEW + i % 4,
                       **_sampling(i, how), **kw) for i in range(n)]


def _step_until_done(eng, streams, limit: int = 2000) -> None:
    for _ in range(limit):
        if all(s.done for s in streams):
            return
        eng.step()
    raise AssertionError("streams did not finish")


class _Reader(threading.Thread):
    """One client: blocks in the stream's iterator, keeps what it got and
    what ended it."""

    def __init__(self, stream):
        super().__init__(daemon=True)
        self.stream = stream
        self.got: list[int] = []
        self.error: BaseException | None = None
        self.ended = False
        self.start()

    def run(self) -> None:
        try:
            for tok in self.stream:
                self.got.append(tok)
        except BaseException as e:  # noqa: BLE001 — the test reads it
            self.error = e
        self.ended = True


def _join(readers, timeout: float = 30.0) -> None:
    deadline = time.monotonic() + timeout
    for r in readers:
        r.join(max(0.0, deadline - time.monotonic()))
    assert all(r.ended for r in readers), "a reader is still blocked"


# ------------------------------------------------ what a reader receives


@pytest.mark.timeout(300)
@pytest.mark.parametrize("how", ["greedy", "seeded"])
@pytest.mark.parametrize("readers", [1, ROWS])
def test_readers_get_every_token_in_order_exactly_once(jax_cpu, readers,
                                                       how):
    """``readers`` threads block on their streams while the main thread
    steps the engine; what each receives is, byte for byte, what the same
    requests give with nobody reading until the end."""
    alone = _engine()
    streams = _submit_all(alone, readers, how)
    _step_until_done(alone, streams)
    reference = [list(s) for s in streams]
    alone.shutdown()
    assert [len(t) for t in reference] == [NEW + i % 4
                                           for i in range(readers)]

    eng = _engine()
    streams = _submit_all(eng, readers, how)
    # the interpreter changes hands as often as it can: a lost or doubled
    # token would show
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        clients = [_Reader(s) for s in streams]
        _step_until_done(eng, streams)
        _join(clients)
    finally:
        sys.setswitchinterval(interval)
    eng.shutdown()
    assert [c.error for c in clients] == [None] * readers
    assert [c.got for c in clients] == reference
    # ended once: nothing is left on a finished stream, for any reader
    assert all(s._request.out.empty() for s in streams)


def test_a_streams_queue_is_the_interpreters_own(jax_cpu):
    eng = _engine()
    s = eng.submit([1, 2, 3], max_new_tokens=2)
    assert type(s._request.out) is queue.SimpleQueue
    _step_until_done(eng, [s])
    assert len(list(s)) == 2
    eng.shutdown()


@pytest.mark.timeout(120)
def test_a_resumed_streams_numbering_is_untouched(jax_cpu):
    eng = _engine()
    s = eng.submit([1, 2, 3, 4, 5], max_new_tokens=4, start_index=2)
    _step_until_done(eng, [s])
    assert len(list(s)) == 4
    events = eng.request_timeline(s.request_id)["events"]
    eng.shutdown()
    assert [e["index"] for e in events
            if e["event"] in ("first_token", "token")] == [2, 3, 4, 5]


# ------------------------------------------------- what ends a reader


def _cancel(eng, stream):
    assert eng.cancel(stream.request_id) is True


def _shutdown(eng, stream):
    eng.shutdown()


def _die(eng, stream):
    from ray_tpu.serve.llm import EngineDiedError

    eng._fail_engine(EngineDiedError("the replica died"))


def _step_past_deadline(eng, stream):
    time.sleep(0.3)
    eng.step()  # the expiry sweep


@pytest.mark.timeout(120)
@pytest.mark.parametrize("how", ["cancel", "deadline", "shutdown",
                                 "failover"])
def test_a_blocked_reader_is_ended_with_the_error(jax_cpu, how):
    """A reader that blocks between tokens gets the exception where it was
    put: after every token that went before it, raised once, the stream
    ended behind it."""
    from ray_tpu.serve.llm import (DeadlineExceededError, EngineDiedError,
                                   RequestCancelledError)

    end, error = {
        "cancel": (_cancel, RequestCancelledError),
        "deadline": (_step_past_deadline, DeadlineExceededError),
        "shutdown": (_shutdown, RequestCancelledError),
        "failover": (_die, EngineDiedError),
    }[how]
    # the same two requests run to their end, nobody reading meanwhile
    alone = _engine()
    whole = [alone.submit(p, max_new_tokens=60)
             for p in ([1, 2, 3], [4, 5, 6])]
    _step_until_done(alone, whole)
    reference = list(whole[0])
    alone.shutdown()

    eng = _engine()
    # compiled first, so that a deadline lapses between tokens and not
    # inside the first step's compile
    warm = eng.submit([1, 2, 3], max_new_tokens=3)
    _step_until_done(eng, [warm])
    kw = {"deadline_s": 0.25} if how == "deadline" else {}
    s = eng.submit([1, 2, 3], max_new_tokens=60, **kw)
    other = eng.submit([4, 5, 6], max_new_tokens=60)
    client = _Reader(s)
    for _ in range(4):
        eng.step()
    deadline = time.monotonic() + 10
    while len(client.got) < 2 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert 2 <= len(client.got) < 60 and not client.ended  # blocked
    end(eng, s)
    _join([client])
    assert isinstance(client.error, error), client.error
    assert s.done
    # the tokens before the error are the run's own, in order, and as
    # many as the engine says it put on the stream
    assert client.got == reference[:len(client.got)]
    timeline = eng.request_timeline(s.request_id)
    assert timeline["finish_reason"] != "finished"
    assert len(client.got) == sum(
        e["event"] in ("first_token", "token") for e in timeline["events"])
    # ended behind the error: a second pass over the stream gets nothing
    assert list(s) == []
    if how in ("cancel", "deadline"):
        # the other stream lives on, and ends as it would have
        _step_until_done(eng, [other])
        assert len(list(other)) == 60
    eng.shutdown()


@pytest.mark.chaos
@pytest.mark.timeout(120)
def test_a_step_that_raises_ends_every_blocked_reader(jax_cpu, chaos_plan):
    from ray_tpu.serve.llm import EngineDiedError

    chaos_plan(FaultPlan(faults=(
        Fault(point="engine.decode", action="raise", after=3),
    )))
    eng = _engine(auto_step=True)
    with eng._lock:  # every request is in before the loop's first step
        clients = [_Reader(s) for s in _submit_all(eng, 8, "greedy")]
    _join(clients)
    assert all(isinstance(c.error, EngineDiedError) for c in clients)
    assert all(len(c.got) < NEW + 4 for c in clients)
    assert all(list(c.stream) == [] for c in clients)
    eng.shutdown()


def test_an_exception_on_a_stream_is_raised_where_it_was_put(jax_cpu):
    """The iterator's own contract, on a stream filled by hand: tokens in
    order, the exception at its place, the end behind it."""
    from ray_tpu.serve.llm import engine as E

    req = E._Request("r", [1], E.SamplingParams(max_new_tokens=4))
    for item in (7, 8, ValueError("boom"), E._DONE):
        req.out.put(item)
    stream = E.TokenStream(req)
    got = []
    with pytest.raises(ValueError, match="boom"):
        for tok in stream:
            got.append(tok)
    assert got == [7, 8]
    assert list(stream) == []


# ----------------------------------------- the rows of a step, booked together


def _token_events(eng, streams) -> list[list[dict]]:
    return [[e for e in eng.request_timeline(s.request_id)["events"]
             if e["event"] in ("first_token", "token")] for s in streams]


@pytest.mark.timeout(300)
@pytest.mark.parametrize("family", ["plain", "stops", "grammar", "eos"])
def test_a_steps_rows_carry_one_timestamp_and_every_token_is_counted(
        jax_cpu, family):
    """The rows of one reconciled step carry ONE ``ts``; the timeline's
    ``token`` / ``first_token`` events, the TTFT and TPOT histograms'
    counts, ``llm_engine_tokens_generated`` and ``host.emit_rows`` all
    equal the tokens the streams got, whatever checks a request brings
    (stop sequences, a grammar, an end-of-sequence id)."""
    n = 16
    eng_kw, sub_kw = {}, {}
    if family == "grammar":
        eng_kw["eos_id"] = 0  # NUL: in no grammar's text
    if family == "eos":
        # some rows end early on it
        eng_kw["eos_id"] = _an_id_the_model_reaches()
    eng = _engine(**eng_kw)
    if family == "stops":
        first = eng.submit(_prompt(0), max_new_tokens=NEW, temperature=0.0)
        _step_until_done(eng, [first])
        sub_kw["stop"] = [list(first)[4:6]]
    if family == "grammar":
        sub_kw["structured"] = {"type": "regex", "pattern": "[0-9a-f]{3,6}"}
    before = metrics.collect(prefix="llm_")
    rows_before = eng.stats()["host"]["emit_rows"]
    streams = _submit_all(eng, n, "greedy", **sub_kw)
    _step_until_done(eng, streams)
    got = [list(s) for s in streams]
    tokens = sum(len(t) for t in got)
    assert tokens >= n
    if family in ("stops", "eos"):
        assert any(len(t) < NEW for t in got), "no row ended early"
    events = _token_events(eng, streams)
    stats = eng.stats()
    steps = [r for r in eng.debug_dump()["steps"] if r["kind"] != "compile"]
    after = metrics.collect(prefix="llm_")
    eng.shutdown()

    def grew(key):
        return after[key] - before.get(key, 0)

    # every token once in the timeline, numbered from the stream's start
    assert [len(e) for e in events] == [len(t) for t in got]
    for evs in events:
        assert [e["event"] for e in evs] == (
            ["first_token"] + ["token"] * (len(evs) - 1))
        assert [e["index"] for e in evs] == list(range(len(evs)))
        # a request's tokens come a step apart
        stamps = [e["ts"] for e in evs]
        assert stamps == sorted(set(stamps))
    # one timestamp a reconciled step: the distinct stamps are no more
    # than the steps made, and the rows of a step share theirs
    stamps = [e["ts"] for evs in events for e in evs]
    assert len(set(stamps)) <= len(steps)
    assert len(set(stamps)) * 2 < tokens
    assert max(stamps.count(ts) for ts in set(stamps)) >= n // 2
    # the histograms and the counters saw every one of them
    assert grew("llm_ttft_seconds_count") == n
    assert grew("llm_time_per_output_token_seconds_count") == tokens - n
    assert grew("llm_engine_tokens_generated_total") == tokens
    assert grew("llm_ttft_seconds_sum") > 0
    assert stats["host"]["emit_rows"] - rows_before == tokens


def _an_id_the_model_reaches() -> int:
    probe = _engine()
    s = probe.submit(_prompt(0), max_new_tokens=NEW, temperature=0.0)
    _step_until_done(probe, [s])
    probe.shutdown()
    return list(s)[3]


@pytest.mark.timeout(120)
def test_emit_rows_leaves_out_a_finished_rows_wasted_token(jax_cpu):
    """A row that ended on its end-of-sequence id with its next step in
    flight has that step's token dropped at the reconcile: it is put on no
    stream, counted by no counter."""
    eng = _engine(eos_id=_an_id_the_model_reaches())
    streams = _submit_all(eng, 4, "greedy")
    _step_until_done(eng, streams)
    while eng.step():
        pass
    st = eng.stats()
    decodes = [r for r in eng.debug_dump()["steps"] if r["kind"] == "decode"]
    eng.shutdown()
    got = [list(s) for s in streams]
    tokens = sum(len(t) for t in got)
    assert len(got[0]) == 4 and got[0][-1] == eng.cfg.eos_id
    assert st["host"]["emit_rows"] == tokens
    # decode steps emitted all but each row's first token (a prefill's) ...
    emitted = sum(r["tokens"] for r in decodes)
    assert emitted == tokens - len(streams)
    # ... and ran a row past its end (launched before its last token was
    # on the host), whose id went nowhere
    assert sum(r["batch"] for r in decodes) > emitted


@pytest.mark.timeout(120)
def test_observe_many_is_observe_for_each(jax_cpu):
    bounds = (0.001, 0.01, 0.1, 1.0)
    one = metrics.histogram("handoff_test_one", boundaries=bounds)
    many = metrics.histogram("handoff_test_many", boundaries=bounds)
    values = [0.0, 0.0005, 0.001, 0.0011, 0.05, 0.1, 0.5, 1.0, 7.0, 0.05]
    for v in values:
        one.observe(v)
    many.observe_many(values)
    many.observe_many([])
    got = metrics.collect(prefix="handoff_test_")
    ones = {k.replace("handoff_test_one", ""): v for k, v in got.items()
            if k.startswith("handoff_test_one") and "_created" not in k}
    manys = {k.replace("handoff_test_many", ""): v for k, v in got.items()
             if k.startswith("handoff_test_many") and "_created" not in k}
    assert ones == pytest.approx(manys) and ones["_count"] == len(values)
    # with tags, as the step-latency histogram has them
    tagged = metrics.histogram("handoff_test_tagged", boundaries=bounds,
                               tag_keys=("kind",))
    tagged.observe_many([0.05, 5.0], tags={"kind": "decode"})
    got = metrics.collect(prefix="handoff_test_tagged")
    assert got["handoff_test_tagged_count{kind=decode}"] == 2
    assert got["handoff_test_tagged_bucket{kind=decode,le=0.1}"] == 1
