"""The step programs name their parts (``obs.SCOPES``), ``DecodeFns`` keeps
what it takes to read the names back from a compiled program
(``program_scopes``), and a first call says what it cost
(``stats()["programs"]``): ISSUE 50."""
from __future__ import annotations

import dataclasses
import re

import pytest

# the engine settings a family's tiny preset needs beside the defaults
SETTINGS = {
    "laguna": dict(block_size=4, num_blocks=129, max_batch_size=4,
                   prefill_chunk_tokens=16, length_buckets=(16, 32, 64, 128)),
    "smallthinker": dict(block_size=4, num_blocks=129, max_batch_size=4,
                         prefill_chunk_tokens=16,
                         length_buckets=(16, 32, 64, 128)),
    "evabyte": dict(block_size=4, num_blocks=257, max_batch_size=4,
                    prefill_chunk_tokens=16, length_buckets=(16, 160)),
    "minicpm_sala": dict(block_size=8, num_blocks=129, max_batch_size=4),
    "ling_hybrid": dict(block_size=8, num_blocks=129, max_batch_size=4),
    "sdar_moe": dict(block_size=8, num_blocks=129, max_batch_size=4),
    "falcon_h1": dict(block_size=8, num_blocks=129, max_batch_size=4),
}
FAMILIES = ("gpt", "llama", "lfm2_moe", "laguna", "evabyte",
            "pangu_ultra_moe", "smallthinker", "longcat_flash",
            "minicpm_sala", "ling_hybrid", "sdar_moe", "falcon_h1")
HEAVY = re.compile(r" (dot|convolution|ragged-dot|custom-call)\(")


def _engine(family: str, **more):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    return LLMEngine(
        EngineConfig(model=family, **{**SETTINGS.get(family, {}), **more}),
        auto_step=False)


def _serve(engine, prompt_len: int = 40, new: int = 3) -> None:
    stream = engine.submit(list(range(1, prompt_len + 1)), max_new_tokens=new)
    for _ in range(1000):
        if stream.done:
            return
        engine.step()
    raise AssertionError("the request did not finish")


def test_the_vocabulary_is_closed(jax_cpu):
    """Every ``jax.named_scope`` of the step programs' code takes its name
    from ``obs.SCOPES``, and the families registered are the ones tested."""
    import glob
    import os

    from ray_tpu.serve.llm import decode, obs

    assert set(FAMILIES) == set(decode.FAMILIES)
    assert len(set(obs.SCOPES)) == len(obs.SCOPES)
    assert obs.UNNAMED not in obs.SCOPES
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    used = set()
    for path in (glob.glob(os.path.join(root, "ray_tpu/models/*.py"))
                 + glob.glob(os.path.join(root, "ray_tpu/ops/*.py"))):
        with open(path) as f:
            used |= set(re.findall(r'named_scope\("([^"]+)"\)', f.read()))
    assert used and used <= set(obs.SCOPES), used - set(obs.SCOPES)
    # the kernel's name is the one scope given by a constant
    from ray_tpu.ops.eva import KERNEL_NAME

    assert KERNEL_NAME in obs.SCOPES
    assert set(obs.SCOPES) - used == {KERNEL_NAME}


@pytest.mark.parametrize("family", FAMILIES)
def test_every_product_of_a_step_program_has_a_name(jax_cpu, family):
    """The compiled prefill and decode programs of every served family at
    its tiny preset: each instruction that can run as an event of its own
    and holds a ``dot``, ``convolution``, ``ragged-dot`` or ``custom-call``
    (itself, or inside the fusion it is) resolves to a name of
    ``obs.SCOPES``. The CPU's compiler rewrites some products into
    instructions with no ``op_name`` at all; those take the scope their
    users or operands agree on (``obs.scope_map``), so none may be left."""
    from ray_tpu.serve.llm import decode, obs

    before = decode.lowerings
    engine = _engine(family)
    _serve(engine)
    programs = engine.stats()["programs"]
    kinds = {key.split(":")[0] for key in programs}
    assert "decode" in kinds and kinds & {"prefill", "prefill_chunk"}
    assert decode.lowerings == before  # serving lowered nothing again
    # one program a kind: the rungs of a ladder differ in their rows alone
    records = {}
    for sig, rec in engine.fns._signatures.items():
        records.setdefault(sig[0], rec)
    engine.shutdown()
    for kind, rec in records.items():
        # (as ``program_scopes()`` reads it: the test cache may hold an
        # executable that a tree from before the names wrote)
        text = decode._compiled_text(rec)
        scopes = obs.scope_map(text)
        bodies = obs.hlo_computations(text)
        heavy = 0
        for lines in bodies.values():
            for line in lines:
                key = obs.instruction_key(line)
                if key is None or key[0] not in scopes:
                    continue
                held = [line]
                callee = re.search(r"calls=%([\w.\-]+)", line)
                if " fusion(" in line and callee:
                    held += bodies.get(callee.group(1), [])
                if not any(HEAVY.search(ln) for ln in held):
                    continue
                heavy += 1
                assert scopes[key[0]][0] in obs.SCOPES, (
                    family, kind, line.strip()[:300])
        assert heavy >= 4, (family, kind, heavy)
        named = {s for s, _, _ in scopes.values()}
        assert {"embed", "attn_proj", "ffn"} <= named, named
        if family == "sdar_moe" and kind != "decode":
            # a block family's prompt chunk chooses no token: no head
            assert not {"head", "sample"} & named
            continue
        assert "head" in named
        if kind != "verify":
            assert "sample" in named


RECORDED = """\
HloModule jit_llama_decode_step, is_scheduled=true

%fused_computation.1 (p0: bf16[64,4096], p1: f32[4096]) -> bf16[64,4096] {
  %p0 = bf16[64,4096]{1,0} parameter(0)
  %p1 = f32[4096]{0} parameter(1)
  %m.1 = f32[64,4096]{1,0} multiply(%p0, %p1), metadata={op_name="jit(llama_decode_step)/layer_stack/while/body/closed_call/ffn/mul" stack_frame_id=4}
  ROOT %d.1 = bf16[64,4096]{1,0} dot(%m.1, %p0), metadata={op_name="jit(llama_decode_step)/layer_stack/while/body/closed_call/attn_proj/dot_general" stack_frame_id=5}
}

%fused_computation.2 (p0: bf16[64,4096]) -> bf16[64,4096] {
  %p0.1 = bf16[64,4096]{1,0} parameter(0)
  ROOT %n.1 = bf16[64,4096]{1,0} negate(%p0.1), metadata={op_name="jit(llama_decode_step)/layer_stack/while/body/closed_call/ffn/neg" stack_frame_id=6}
}

%region_1.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0), metadata={op_name="reduce_sum"}
  %b = f32[] parameter(1), metadata={op_name="reduce_sum"}
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="jit(llama_decode_step)/head/reduce_sum"}
}

%body.3 (arg: (s32[], bf16[64,4096])) -> (s32[], bf16[64,4096]) {
  %arg = (s32[]{:T(128)}, bf16[64,4096]{1,0:T(8,128)(2,1)}) parameter(0)
  %gte.1 = bf16[64,4096]{1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=1
  %slice.4 = f32[4096]{0:T(1024)} fusion(%gte.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(llama_decode_step)/layer_stack/while/body/dynamic_slice" stack_frame_id=2}
  %copy.7 = bf16[64,4096]{0,1:T(8,128)(2,1)} copy(%gte.1)
  %paged_attention.10 = bf16[64,4096]{1,0:T(8,128)(2,1)} custom-call(%copy.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(llama_decode_step)/layer_stack/while/body/closed_call/attn_proj/attn_kernel/paged_attention/pallas_call" stack_frame_id=9}
  %fusion.161 = bf16[64,4096]{1,0:T(8,128)(2,1)} fusion(%paged_attention.10, %slice.4), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(llama_decode_step)/layer_stack/while/body/closed_call/attn_proj/dot_general" stack_frame_id=5}
  %fusion.162 = bf16[64,4096]{1,0:T(8,128)(2,1)} fusion(%fusion.161), kind=kLoop, calls=%fused_computation.2
  %stray.1 = bf16[64,4096]{1,0:T(8,128)(2,1)} negate(%fusion.162), metadata={op_name="jit(llama_decode_step)/neg" stack_frame_id=11}
  ROOT %tuple.2 = (s32[]{:T(128)}, /*index=1*/bf16[64,4096]{1,0:T(8,128)(2,1)}) tuple(%gte.1, %stray.1)
}

ENTRY %main.19 (x: bf16[64,4096]) -> f32[64] {
  %x = bf16[64,4096]{1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="tokens"}
  %while.12 = (s32[]{:T(128)}, bf16[64,4096]{1,0:T(8,128)(2,1)}) while(%x), condition=%cond.2, body=%body.3, metadata={op_name="jit(llama_decode_step)/layer_stack/while" stack_frame_id=1}
  ROOT %reduce.5 = f32[64]{0:T(128)} reduce(%while.12), dimensions={1}, to_apply=%region_1.1, metadata={op_name="jit(llama_decode_step)/head/reduce_sum" stack_frame_id=12}
}
"""


def test_scope_map_on_a_recorded_text():
    """``obs.scope_map`` on a text in the compiled form (a cut of Mistral's
    decode program for a v5e): the innermost listed scope wins, a fusion is
    named by its own metadata and flagged where its fused instructions name
    more, one without metadata takes what they agree on, the compiler's own
    copy takes its user's scope, a fused computation's and a reduction's
    instructions have no entry, and what the program named under no scope
    stays unnamed."""
    from ray_tpu.serve.llm import obs

    got = obs.scope_map(RECORDED)
    assert got["paged_attention.10"] == (
        "attn_kernel", "bf16[64,4096]", False)
    assert got["fusion.161"] == ("attn_proj", "bf16[64,4096]", True)
    assert got["fusion.162"] == ("ffn", "bf16[64,4096]", False)
    assert got["slice.4"][0] == "layer_stack"
    assert got["copy.7"][0] == "attn_kernel"  # its one user's
    assert got["while.12"][0] == "layer_stack"
    assert got["while.12"][1] == "(s32[], bf16[64,4096])"
    assert got["tuple.2"][1] == "(s32[], bf16[64,4096])"
    assert got["reduce.5"] == ("head", "f32[64]", False)
    assert got["stray.1"][0] == obs.UNNAMED  # named by the program, no scope
    for fused in ("m.1", "d.1", "n.1", "add.9", "a"):
        assert fused not in got
    # a device event's name is the line with the operands' types written
    # out and no metadata: the same key
    event = ("%fusion.161 = bf16[64,4096]{1,0:T(8,128)(2,1)} fusion("
             "bf16[64,4096]{1,0:T(8,128)(2,1)} %paged_attention.10, "
             "f32[4096]{0:T(1024)} %slice.4), kind=kOutput, "
             "calls=%fused_computation.1")
    assert obs.instruction_key(event) == ("fusion.161", "bf16[64,4096]")
    assert obs.instruction_key("not an instruction") is None
    assert obs.scope_of("jit(f)/ffn/moe_gmm/custom_call") == "moe_gmm"
    assert obs.scope_of("jit(f)/while/body/add") == obs.UNNAMED


def test_first_calls_are_timed_and_nothing_is_lowered_unasked(jax_cpu):
    """``stats()["programs"]``: a ``first_call_s`` a signature, ``calls``
    counting the FIRST calls (one for every engine that reaches the
    signature); the compile flight record says its ``ms``; and
    ``program_scopes()`` lowers only when called, once a program."""
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import decode, obs

    # a configuration of this test's own: the registry is the process's
    cfg = dataclasses.replace(LlamaConfig.tiny(), d_mlp=96)
    before = decode.lowerings
    first = _engine("llama", model_config=cfg)
    _serve(first)
    programs = first.stats()["programs"]
    assert set(programs) == {
        obs.shape_key(sig) for sig in first.fns.signatures}
    assert sorted(programs) == first.debug_dump()["compiled_shapes"]
    assert first.num_compiled_shapes == len(programs)
    for held in programs.values():
        assert held["name"] in ("jit_llama_prefill", "jit_llama_decode_step")
        assert held["first_call_s"] > 0.0 and held["calls"] == 1
    compiles = [r for r in first.debug_dump()["steps"]
                if r["kind"] == "compile"]
    assert {r["shape"] for r in compiles} == set(programs)
    assert all(r["ms"] > 0.0 for r in compiles)
    # a second engine over the same programs: its first calls are counted,
    # what the process's first call cost stays
    second = _engine("llama", model_config=cfg)
    assert second.num_compiled_shapes == 0
    assert second.stats()["programs"] == programs  # the process's
    _serve(second)
    again = second.stats()["programs"]
    assert set(again) == set(programs)
    reached = {obs.shape_key(sig) for sig in second.fns.signatures}
    for key, held in again.items():
        assert held["first_call_s"] == programs[key]["first_call_s"]
        assert held["calls"] == (2 if key in reached else 1)
    assert decode.lowerings == before  # two engines served, none lowered
    maps = second.program_scopes()
    assert set(maps) == set(programs)
    assert decode.lowerings == before + len(programs)
    for key, held in maps.items():
        assert held["name"] == programs[key]["name"]
        scopes = {scope for scope, _, _ in held["scopes"].values()}
        assert {"attn_proj", "ffn", "head", "embed"} <= scopes
    first.shutdown()
    second.shutdown()
    # it answers after shutdown, from what it kept, and lowers nothing twice
    assert second.program_scopes(only={"jit_llama_decode_step"}).keys() == {
        key for key in programs if key.startswith("decode")}
    whole = decode.program_scopes(only={"jit_llama_prefill"})
    mine = [held for held in whole.values() if any(
        held["scopes"] is kept["scopes"] for kept in maps.values())]
    assert len(mine) == sum(key.startswith("prefill") for key in programs)
    now = decode.lowerings  # (another test's llama programs may be in it)
    assert second.program_scopes().keys() == maps.keys()
    assert decode.lowerings == now
    # the kept arguments hold no device buffer
    import jax

    for rec in second.fns._programs.values():
        for leaf in jax.tree.leaves(rec["args"]):
            assert not isinstance(leaf, jax.Array)


def test_a_stale_compile_cache_is_compiled_again_under_its_names(
        jax_cpu, tmp_path, monkeypatch):
    """JAX's persistent-cache key leaves metadata out, so an executable
    that a checkout from before the names wrote is found under this one's
    key, with its own ``op_name``s. ``program_scopes()`` sees a text that
    names no scope, compiles the program once more under a key that holds
    the metadata, and reads the names there."""
    import contextlib

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import obs

    # this test's own programs; ``remat`` is the trainer's and changes no
    # step program, so the two configurations trace apart (a jitted
    # ``functools.partial`` is found again by its keywords' VALUES) and
    # compile to one text
    old = dataclasses.replace(LlamaConfig.tiny(), d_mlp=80, remat=False)
    cfg = dataclasses.replace(old, remat=True)
    settings = {"jax_compilation_cache_dir": str(tmp_path),
                "jax_persistent_cache_min_compile_time_secs": 0.0,
                "jax_persistent_cache_min_entry_size_bytes": 0}
    was = {name: getattr(jax.config, name) for name in settings}

    def configure(values):
        for name, value in values.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()

    def served(cfg):
        engine = _engine("llama", model_config=cfg)
        _serve(engine)
        engine.shutdown()
        return engine

    configure(settings)
    try:
        with monkeypatch.context() as m:  # a tree that names nothing
            m.setattr(jax, "named_scope",
                      lambda name: contextlib.nullcontext())
            served(old)
        wrote = len(list(tmp_path.iterdir()))
        assert wrote >= 2
        engine = served(cfg)  # this tree: the same programs, the same keys
        assert len(list(tmp_path.iterdir())) == wrote  # read, not compiled
        maps = engine.program_scopes()
        for held in maps.values():
            named = {scope for scope, _, _ in held["scopes"].values()}
            assert {"attn_proj", "ffn", "head"} <= named - {obs.UNNAMED}
        # one more entry a program, under the key that holds its metadata
        assert len(list(tmp_path.iterdir())) == wrote + len(maps)
    finally:
        configure(was)


def test_nothing_in_the_program_calls_program_scopes():
    """``program_scopes()`` is an operator's and a benchmark's call: under
    ``ray_tpu/`` the only calls of it (and of what lowers, ``_scopes_of``)
    are the three definitions handing on to one another."""
    import ast
    import glob
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    calls = set()
    for path in glob.glob(os.path.join(root, "ray_tpu/**/*.py"),
                          recursive=True):
        with open(path) as f:
            tree = ast.parse(f.read())
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    name = getattr(node.func, "attr",
                                   getattr(node.func, "id", None))
                    if name in ("program_scopes", "_scopes_of"):
                        calls.add((os.path.relpath(path, root), fn.name,
                                   name))
    assert calls == {
        ("ray_tpu/serve/llm/engine.py", "program_scopes", "program_scopes"),
        ("ray_tpu/serve/llm/decode.py", "program_scopes", "_scopes_of"),
    }, calls
