"""Grammar-constrained decoding (ISSUE 16): the structured-output
subsystem end to end.

The contract under test: a request carrying ``structured=`` (JSON mode,
a JSON-Schema subset, or a regex) streams ONLY tokens its token-level
DFA accepts — property-tested over seeded spec corpora — while
everything that made the engine deterministic stays intact:

* the compile-kind set is IDENTICAL to an unconstrained engine (the
  allow-mask is data in the sample pytree, not signature), so mixed
  constrained/unconstrained batches share one decode program;
* unconstrained streams in a mixed batch are byte-identical to a solo
  run (the all-ones mask is a bitwise identity);
* mid-stream failover resume is byte-identical — greedy AND
  temperature/top-p, gpt AND llama, single-device AND tp/fsdp-sharded —
  because FSM cursors rebuild from the replayed prefix alone;
* speculation stays lossless: spec-on == spec-off byte-identical for
  constrained streams (drafts are DFA-filtered, never trusted);
* an invalid or unsatisfiable grammar fails at SUBMIT with
  GrammarError -> HTTP 400 / gRPC INVALID_ARGUMENT, never a 500.

Compiler unit tests cross-check the regex-subset DFA against
``re.fullmatch`` on seeded corpora of accepted walks and mutations.

Parity tests run f32 + XLA attention, like the rest of the serving
suite; tiny configs keep vocab >= 256 so token t < 256 is byte t.
"""
from __future__ import annotations

import dataclasses
import json
import random
import re
import time

import pytest

from conftest import shutdown_if_setup_fails

from ray_tpu._private import chaos
from ray_tpu._private.chaos import Fault, FaultPlan


VOCAB = 512  # tiny-config vocab: tokens < 256 are bytes, verbatim
EOS = 0      # NUL never appears in grammar text, so the bit is unambiguous

# regex corpus: each entry exercises a distinct construct family
REGEXES = [
    r"[0-9]{1,3}(\.[0-9]{1,3}){3}",          # bounded reps + groups
    r"(yes|no|maybe)",                        # alternation
    r"-?(0|[1-9][0-9]*)(\.[0-9]+)?",          # optional + star
    r"[a-f]+x?",                              # plus + optional tail
    r'"(a|b)*"',                              # quoted star
]

SCHEMAS = [
    {"type": "object", "properties": {"ok": {"type": "boolean"}}},
    {"type": "object", "properties": {
        "n": {"type": "integer"},
        "tag": {"enum": ["x", "y"]},
    }},
    {"type": "array", "items": {"type": "integer"},
     "minItems": 1, "maxItems": 3},
    {"const": "done"},
    {"anyOf": [{"type": "integer"}, {"type": "boolean"}]},
]


def _f32(cfg):
    import jax.numpy as jnp

    return dataclasses.replace(cfg, dtype=jnp.float32, attention="xla")


def _model_config(family="llama"):
    if family == "gpt":
        from ray_tpu.models.gpt import GPTConfig

        return _f32(GPTConfig.tiny())
    from ray_tpu.models.llama import LlamaConfig

    return _f32(LlamaConfig.tiny())


def _engine(family="llama", mc=None, **kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("eos_id", EOS)
    return LLMEngine(
        EngineConfig(model=family, model_config=mc or _model_config(family),
                     **kw),
        auto_step=False,
    )


def _drain(eng, streams, steps=800):
    for _ in range(steps):
        if all(s.done for s in streams):
            break
        eng.step()
    while eng.step():  # reconcile any in-flight step (lag-1 drain)
        pass


def _dfa(spec, vocab=VOCAB, eos=EOS):
    from ray_tpu.serve.llm import structured

    return structured.compile_grammar(
        structured.parse_response_format(spec), vocab, eos)


def _assert_stream_grammar_valid(spec, toks, max_new_tokens):
    """Replay an emitted stream through a FRESH cursor: every token must
    be DFA-accepted, and a stream that completed before its budget must
    sit at a match (it stopped via must_stop or the EOS bit, both of
    which require an accepting state)."""
    from ray_tpu.serve.llm import structured

    cur = structured.FSMCursor(_dfa(spec))
    body = [t for t in toks if t != EOS]
    for t in body:
        assert cur.advance(t), (
            f"token {t} rejected at state {cur.state} in stream {toks}")
    if len(toks) < max_new_tokens:
        assert cur.accepting, (
            f"completed stream is not a full match: {bytes(body)!r}")
    return bytes(body)


# =================================================== compiler unit tests


def test_parse_response_format_variants():
    from ray_tpu.serve.llm.structured import (
        GrammarError, GrammarSpec, parse_response_format,
    )

    assert parse_response_format(None) is None
    assert parse_response_format("json").kind == "json"
    assert parse_response_format("json_object").kind == "json"
    assert parse_response_format({"type": "json_object"}).kind == "json"
    spec = parse_response_format({"type": "regex", "pattern": "ab*"})
    assert (spec.kind, spec.text) == ("regex", "ab*")
    sch = {"type": "integer"}
    direct = parse_response_format({"type": "json_schema", "schema": sch})
    openai = parse_response_format(
        {"type": "json_schema", "json_schema": {"schema": sch}})
    assert direct == openai and direct.kind == "json_schema"
    # passthrough of an already-parsed spec
    assert parse_response_format(spec) is spec
    for bad in (42, "yaml", {"type": "ebnf"}, {"type": "regex"},
                {"type": "json_schema"}, {}, []):
        with pytest.raises(GrammarError):
            parse_response_format(bad)


def test_regex_dfa_agrees_with_re_fullmatch():
    """Property: over seeded corpora of accepted walks and byte-level
    mutations, DFA acceptance == re.fullmatch for every regex in the
    supported subset."""
    rng = random.Random(1609)
    for pattern in REGEXES:
        dfa = _dfa({"type": "regex", "pattern": pattern})
        compiled = re.compile(pattern.encode())

        def walk():
            """Random accepted string via the DFA itself."""
            s, out = 0, bytearray()
            for _ in range(64):
                nxt = [b for b in range(256) if dfa.trans[s][b] >= 0]
                if bool(dfa.accept[s]) and (not nxt or rng.random() < 0.3):
                    return bytes(out)
                if not nxt:
                    return bytes(out)
                b = rng.choice(nxt)
                out.append(b)
                s = int(dfa.trans[s][b])
            return None  # unbounded walk: skip

        def dfa_accepts(bs):
            s = 0
            for b in bs:
                s = int(dfa.trans[s][b])
                if s < 0:
                    return False
            return bool(dfa.accept[s])

        for _ in range(40):
            w = walk()
            if w is None:
                continue
            assert compiled.fullmatch(w), (pattern, w)
            # mutations: flip / drop / append a byte, then cross-check
            for _ in range(4):
                m = bytearray(w)
                op = rng.randrange(3)
                if op == 0 and m:
                    m[rng.randrange(len(m))] = rng.randrange(256)
                elif op == 1 and m:
                    del m[rng.randrange(len(m))]
                else:
                    m.append(rng.randrange(256))
                got = dfa_accepts(bytes(m))
                want = compiled.fullmatch(bytes(m)) is not None
                assert got == want, (pattern, bytes(m))


def test_unsatisfiable_and_invalid_grammars_raise():
    from ray_tpu.serve.llm import structured
    from ray_tpu.serve.llm.structured import GrammarError

    # vocab 16 has no token for byte 'A' (65): DFA is born dead
    with pytest.raises(GrammarError):
        _dfa({"type": "regex", "pattern": "A"}, vocab=16)
    for bad in ("(", "a{5,2}", "^a$", r"(?=x)", "[z-a]"):
        with pytest.raises(GrammarError):
            _dfa({"type": "regex", "pattern": bad})
    # schema: unsupported type / bad key bytes / malformed schema text
    for bad in ({"type": "frobnicate"},
                {"type": "object", "properties": {"\x00": {}}}):
        with pytest.raises(GrammarError):
            _dfa({"type": "json_schema", "schema": bad})
    with pytest.raises(GrammarError):
        structured.compile_grammar(
            structured.GrammarSpec("json_schema", "{not json"), VOCAB, EOS)


def test_json_mode_dfa_shape_and_eos_bit():
    import numpy as np

    dfa = _dfa("json")
    bits = (dfa.mask[:, :, None] >> np.arange(32, dtype=np.uint32)) & 1
    allow = bits.reshape(dfa.n_states, -1)[:, :dfa.vocab_size] != 0
    # the opening byte of JSON mode is exactly '{'
    assert list(np.nonzero(allow[0])[0]) == [ord("{")]
    # every accepting state grants the EOS bit; no rejecting state does
    assert (allow[:, EOS] == dfa.accept).all()
    # tokens >= 256 (non-byte ids in the tiny vocab) are never allowed
    assert not allow[:, 256:].any()


def test_grammar_cache_hits_and_keying():
    from ray_tpu.serve.llm import structured

    structured.clear_cache()
    spec = structured.parse_response_format(
        {"type": "regex", "pattern": "(a|b)c"})
    d1 = structured.compile_grammar(spec, VOCAB, EOS)
    before = structured.cache_stats()
    d2 = structured.compile_grammar(spec, VOCAB, EOS)
    after = structured.cache_stats()
    assert d2 is d1, "same (kind, text, vocab, eos) must hit the cache"
    assert after["hits"] == before["hits"] + 1
    # vocab and eos are part of the key
    d3 = structured.compile_grammar(spec, 300, EOS)
    d4 = structured.compile_grammar(spec, VOCAB, None)
    assert d3 is not d1 and d4 is not d1
    assert structured.cache_stats()["size"] == 3


def test_fsm_cursor_advance_draft_filter_and_verify_masks():
    import numpy as np

    from ray_tpu.serve.llm import structured

    dfa = _dfa({"type": "regex", "pattern": "ab"})
    cur = structured.FSMCursor(dfa)
    assert cur.advance(ord("a")) and not cur.dead
    assert not cur.advance(ord("z")) and cur.dead
    assert not cur.advance(ord("b")), "a dead cursor stays dead"

    # filter_draft truncates at the first disallowed token and before
    # EOS, without moving the cursor
    cur = structured.FSMCursor(dfa)
    assert cur.filter_draft([ord("a"), ord("b")]) == [ord("a"), ord("b")]
    assert cur.filter_draft([ord("a"), ord("z"), ord("b")]) == [ord("a")]
    assert cur.filter_draft([ord("a"), EOS, ord("b")]) == [ord("a")]
    assert cur.filter_draft([ord("z")]) == []
    assert cur.state == 0, "filter_draft must not advance the cursor"

    # stage_verify_masks: column 0 = current state's mask, column s =
    # state after draft[:s]; the last state holds past the draft length
    W, words = 4, dfa.words
    out = np.zeros((W, words), dtype=np.uint32)
    cur.stage_verify_masks(out, [ord("a"), ord("b")])
    assert (out[0] == dfa.mask[0]).all()
    s1 = int(dfa.trans[0][ord("a")])
    s2 = int(dfa.trans[s1][ord("b")])
    assert (out[1] == dfa.mask[s1]).all()
    assert (out[2] == dfa.mask[s2]).all()
    assert (out[3] == dfa.mask[s2]).all(), "held past the draft length"


def test_schema_corpus_walks_parse_as_json():
    """Property: random DFA-accepted walks for every corpus schema are
    valid JSON (json.loads) of the right top-level shape."""
    rng = random.Random(77)
    shapes = [dict, dict, list, str, (int, bool)]
    for schema, shape in zip(SCHEMAS, shapes):
        dfa = _dfa({"type": "json_schema", "schema": schema})
        for _ in range(25):
            s, out = 0, bytearray()
            for _ in range(128):
                nxt = [b for b in range(256) if dfa.trans[s][b] >= 0]
                if bool(dfa.accept[s]) and (not nxt or rng.random() < 0.4):
                    break
                if not nxt:
                    break
                b = rng.choice(nxt)
                out.append(b)
                s = int(dfa.trans[s][b])
            assert bool(dfa.accept[s]), (schema, bytes(out))
            val = json.loads(bytes(out))
            assert isinstance(val, shape), (schema, val)


# ======================================== SamplingParams hardening


def test_sampling_params_validation():
    from ray_tpu.serve.llm import SamplingParams

    for kw in (dict(max_new_tokens=0), dict(max_new_tokens=1 << 21),
               dict(start_index=-1), dict(temperature=float("nan")),
               dict(temperature=-0.5), dict(top_k=-2),
               dict(top_p=0.0), dict(top_p=1.5)):
        with pytest.raises(ValueError):
            SamplingParams(**kw)
    # stop normalization: a bare int becomes a 1-token sequence, strings
    # of ints become tuples; empty sequences are rejected
    sp = SamplingParams(stop=(5, [6, 7]))
    assert sp.stop == ((5,), (6, 7))
    with pytest.raises(ValueError):
        SamplingParams(stop=((),))


# ============================================== engine: grammar property


@pytest.mark.timeout(300)
@pytest.mark.parametrize("sampling", [
    dict(),
    dict(temperature=0.9, top_p=0.95, seed=11),
], ids=["greedy", "nucleus"])
def test_constrained_streams_obey_grammar_property(jax_cpu, sampling):
    """Acceptance: 100% of tokens streamed for constrained requests are
    grammar-accepted, across the seeded regex AND schema corpora, for
    greedy and temperature/top-p sampling; streams that complete within
    budget decode to a full match."""
    eng = _engine()
    specs = (
        [{"type": "regex", "pattern": p} for p in REGEXES]
        + [{"type": "json_schema", "schema": s} for s in SCHEMAS]
        + ["json"]
    )
    streams = [
        eng.submit([3, 5, 7 + i], max_new_tokens=48, structured=spec,
                   **dict(sampling, seed=sampling.get("seed", 0) + i))
        if sampling else
        eng.submit([3, 5, 7 + i], max_new_tokens=48, structured=spec)
        for i, spec in enumerate(specs)
    ]
    _drain(eng, streams, steps=2000)
    for spec, s in zip(specs, streams):
        toks = list(s)
        assert toks, f"no tokens for {spec}"
        body = _assert_stream_grammar_valid(spec, toks, 48)
        if len(toks) < 48:
            if isinstance(spec, dict) and spec.get("type") == "regex":
                assert re.fullmatch(spec["pattern"].encode(), body)
            else:
                json.loads(body)


@pytest.mark.timeout(180)
def test_json_mode_greedy_emits_parseable_object(jax_cpu):
    toks = _engine().generate([9, 8, 7], max_new_tokens=96,
                              structured="json")
    body = _assert_stream_grammar_valid("json", toks, 96)
    if len(toks) < 96:
        assert isinstance(json.loads(body), dict)


# =========================================== compile-kind / mixed batch


@pytest.mark.timeout(240)
def test_mixed_batch_shares_programs_and_preserves_unconstrained_bytes(
        jax_cpu):
    """The mask is DATA: a constrained+unconstrained mixed batch compiles
    the exact kind set of an unconstrained engine, and the unconstrained
    stream is byte-identical to a solo run (all-ones mask is a bitwise
    identity)."""
    mc = _model_config()
    base = _engine(mc=mc)
    solo = base.generate([4, 5, 6], max_new_tokens=12,
                         temperature=0.7, seed=3)
    base_kinds = {s[0] for s in base.fns.signatures}

    eng = _engine(mc=mc)
    spec = {"type": "regex", "pattern": r"[0-9]{1,3}(\.[0-9]{1,3}){3}"}
    streams = [
        eng.submit([4, 5, 6], max_new_tokens=12, temperature=0.7, seed=3),
        eng.submit([1, 2, 3], max_new_tokens=16, structured=spec),
        eng.submit([2, 2, 2], max_new_tokens=16, structured="json"),
    ]
    _drain(eng, streams)
    assert list(streams[0]) == solo
    kinds = {s[0] for s in eng.fns.signatures}
    assert kinds == base_kinds, (
        f"constrained traffic changed the compile-kind set: "
        f"{kinds} != {base_kinds}")
    _assert_stream_grammar_valid(spec, list(streams[1]), 16)
    _assert_stream_grammar_valid("json", list(streams[2]), 16)


@pytest.mark.timeout(180)
def test_chunked_prefill_constrained_stream_is_valid(jax_cpu):
    """Chunked prefill flows through the same masked sample path: a long
    prompt prefilled in 8-token slices still yields a grammar-clean
    stream, byte-identical to the monolithic-prefill engine."""
    mc = _model_config()
    spec = {"type": "regex", "pattern": "(yes|no|maybe)"}
    prompt = list(range(1, 38))
    mono = _engine(mc=mc).generate(prompt, max_new_tokens=12,
                                   structured=spec)
    chunked = _engine(mc=mc, prefill_chunk_tokens=8).generate(
        prompt, max_new_tokens=12, structured=spec)
    assert chunked == mono
    _assert_stream_grammar_valid(spec, chunked, 12)


# ========================================================= stop sequences


@pytest.mark.timeout(180)
def test_stop_sequence_truncates_and_spans_resume_boundary(jax_cpu):
    mc = _model_config()
    base = _engine(mc=mc).generate([5, 6, 7], max_new_tokens=10,
                                   temperature=0.8, seed=42)
    assert len(base) == 10
    # stop at the first occurrence of base[2:4]: stream includes the
    # stop sequence itself, then completes
    stopped = _engine(mc=mc).generate([5, 6, 7], max_new_tokens=10,
                                      temperature=0.8, seed=42,
                                      stop=(base[2:4],))
    assert stopped == base[:4]
    # resume boundary: stop = (base[2], base[3]), resume at k=3 — the
    # match spans the replayed prompt tail and the first resumed token
    resumed = _engine(mc=mc).generate(
        [5, 6, 7] + base[:3], max_new_tokens=7, temperature=0.8,
        seed=42, start_index=3, stop=((base[2], base[3]),))
    assert resumed == [base[3]], (
        "stop spanning the resume boundary must fire on the first token")


# ==================================================== failover resume


@pytest.mark.timeout(300)
@pytest.mark.parametrize("family", ["gpt", "llama"])
@pytest.mark.parametrize("sampling", [
    dict(),
    dict(temperature=0.8, top_p=0.9, seed=21),
], ids=["greedy", "nucleus"])
def test_constrained_resume_is_byte_identical(jax_cpu, family, sampling):
    """The failover contract with a grammar attached: re-prefilling
    prompt + delivered on a FRESH engine (FSM rebuilt by replaying just
    the delivered tokens) reproduces the remaining stream exactly."""
    spec = {"type": "regex", "pattern": r"[0-9]{1,3}(\.[0-9]{1,3}){3}"}
    mc = _model_config(family)
    full = _engine(family, mc).generate([7, 7, 7], max_new_tokens=15,
                                        structured=spec, **sampling)
    assert len(full) >= 8, full
    k = 3
    resumed = _engine(family, mc).generate(
        [7, 7, 7] + full[:k], max_new_tokens=15 - k, structured=spec,
        start_index=k, **sampling)
    assert resumed == full[k:]
    _assert_stream_grammar_valid(spec, full, 15)


@pytest.mark.timeout(300)
@pytest.mark.parametrize("sampling", [
    dict(),
    dict(temperature=0.8, top_p=0.9, seed=21),
], ids=["greedy", "nucleus"])
def test_constrained_resume_sharded_matches_single_device(jax_cpu,
                                                          sampling):
    """Same resume contract through the GSPMD ShardedExecutor (tp=2 /
    fsdp=2 on the 8-virtual-device mesh), cross-checked against the
    single-device stream."""
    spec = {"type": "json_schema",
            "schema": {"type": "object",
                       "properties": {"n": {"type": "integer"}}}}
    mc = _model_config()
    single = _engine(mc=mc).generate([9, 9, 9], max_new_tokens=14,
                                     structured=spec, **sampling)
    eng = _engine(mc=mc, tp=2, fsdp=2)
    assert eng.stats()["executor"]["executor"] == "sharded"
    full = eng.generate([9, 9, 9], max_new_tokens=14, structured=spec,
                        **sampling)
    assert full == single, "sharded stream diverged from single-device"
    k = 4
    resumed = _engine(mc=mc, tp=2, fsdp=2).generate(
        [9, 9, 9] + full[:k], max_new_tokens=14 - k, structured=spec,
        start_index=k, **sampling)
    assert resumed == full[k:]


@pytest.mark.timeout(180)
def test_resumed_prefix_rejected_by_grammar_raises(jax_cpu):
    """A resume whose delivered tokens do not replay through the DFA is
    a client error at submit, not a poisoned stream."""
    from ray_tpu.serve.llm.structured import GrammarError

    eng = _engine()
    with pytest.raises(GrammarError):
        eng.submit([1, 2, 3, ord("z"), ord("z")], max_new_tokens=4,
                   structured={"type": "regex", "pattern": "ab*"},
                   start_index=2)


# ======================================================== speculation


@pytest.mark.timeout(300)
@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_spec_on_equals_spec_off_constrained(jax_cpu, family):
    """Losslessness survives the grammar: with drafts DFA-filtered and
    the per-state verify mask staged, spec-on commits the identical
    stream to spec-off — greedy and nucleus, json and regex."""
    mc = _model_config(family)
    cases = [
        (dict(), "json"),
        (dict(temperature=0.9, top_p=0.9, seed=5),
         {"type": "regex", "pattern": r"-?(0|[1-9][0-9]*)(\.[0-9]+)?"}),
    ]
    for sampling, spec in cases:
        off = _engine(family, mc).generate(
            [6, 4, 2], max_new_tokens=16, structured=spec, **sampling)
        on = _engine(family, mc, speculative_k=3).generate(
            [6, 4, 2], max_new_tokens=16, structured=spec, **sampling)
        assert on == off, (family, spec, sampling)


# =========================================== degradation + observability


def test_grammar_error_maps_to_client_fault_statuses():
    import grpc

    from ray_tpu.serve.grpc_proxy import _code_for
    from ray_tpu.serve.llm.structured import GrammarError
    from ray_tpu.serve.proxy import _status_for

    status, headers = _status_for(GrammarError("unsatisfiable"))
    assert status == 400 and "Retry-After" not in headers
    assert _code_for(GrammarError("unsatisfiable")) == (
        grpc.StatusCode.INVALID_ARGUMENT)


@pytest.mark.timeout(180)
def test_structured_stats_and_metrics(jax_cpu):
    from ray_tpu.serve.llm import structured
    from ray_tpu.util import metrics

    structured.clear_cache()
    before = metrics.collect().get("llm_structured_requests_total", 0)
    eng = _engine()
    s = eng.submit([1, 2, 3], max_new_tokens=6, structured="json")
    eng.step()
    st = eng.stats()
    assert st["structured_running"] == 1
    assert st["grammar_cache"]["size"] >= 1
    _drain(eng, [s])
    list(s)
    assert metrics.collect()["llm_structured_requests_total"] == before + 1
    assert eng.stats()["structured_running"] == 0


# ============================================== cluster: chaos failover


@pytest.fixture(scope="module")
def structured_cluster():
    """Two LLM replicas with a chaos plan that kills the replica serving
    the tagged CONSTRAINED request after its third streamed chunk."""
    import os

    plan = FaultPlan(seed=3, faults=(
        Fault(point="llm.token", action="kill",
              when={"tag": "gkill", "index": 2, "resumed": False}),
    ))
    prev = os.environ.get(chaos.ENV_VAR)
    os.environ[chaos.ENV_VAR] = plan.to_json()
    chaos.clear()

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import EngineConfig, build_llm_app

    ray_tpu.init(num_cpus=8)
    with shutdown_if_setup_fails():
        serve.start(http_options={"port": 0}, grpc_options={"port": 0})
        handle = serve.run(
            build_llm_app(
                EngineConfig(model="llama", model_config=_model_config(),
                             seed=0, eos_id=EOS, block_size=8, num_blocks=64),
                num_replicas=2,
            ),
            name="llm-structured", route_prefix="/llmstructured",
            timeout_s=180,
        )
    yield handle
    serve.shutdown()
    ray_tpu.shutdown()
    chaos.clear()
    if prev is None:
        os.environ.pop(chaos.ENV_VAR, None)
    else:
        os.environ[chaos.ENV_VAR] = prev


@pytest.mark.chaos
@pytest.mark.timeout(300)
def test_replica_death_mid_constrained_stream_resumes_byte_identical(
        jax_cpu, structured_cluster):
    """Acceptance: kill the serving replica at token N of a constrained
    stream; the client stream completes byte-identical to an
    uninterrupted run AND every emitted prefix stays grammar-valid."""
    from ray_tpu.serve.llm import stream_tokens, structured

    spec = {"type": "regex", "pattern": r"[0-9]{1,3}(\.[0-9]{1,3}){3}"}
    sampling = dict(max_new_tokens=15, temperature=0.8, seed=42)
    reference = _engine().generate([5, 6, 7], structured=spec, **sampling)
    assert len(reference) >= 8

    gen = stream_tokens(structured_cluster, {
        "prompt": [5, 6, 7],
        "request_id": "gkill-req-1",
        "chaos_tag": "gkill",
        "response_format": spec,
        **sampling,
    })
    chunks, cur = [], structured.FSMCursor(_dfa(spec))
    for c in gen:
        chunks.append(c)
        if c["token"] != EOS:
            assert cur.advance(c["token"]), (
                f"mid-failover prefix broke the grammar at {chunks}")
    assert gen.failovers >= 1, "the chaos kill should have forced failover"
    assert [c["index"] for c in chunks] == list(range(len(reference)))
    assert [c["token"] for c in chunks] == reference
    stats = [s for s in structured_cluster.broadcast("stats") if s]
    assert sum(s.get("requests_resumed", 0) for s in stats) >= 1
