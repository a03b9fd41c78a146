"""Records ``tiny_scope_tpu.xplane.pb`` and ``tiny_scope_texts.json``, the
small trace WITH its programs' compiled texts that
``test_benchmark_scope_reduce.py`` reads: a hand-stepped engine over the
tiny ``lfm2_moe`` preset (attention, short-convolution and expert layers in
a Python loop of unlike layers) and one over a small GQA llama (a scanned
stack, packed prefill, the Pallas kernels), each serving a few requests
under one profiler session marked as the runners mark theirs; then every
program's compiled text, as ``LLMEngine.program_scopes()`` reads it.

On the chip, from the root of the checkout (PR 50 recorded it so):

    chiprun -- python3 tests/benchmark/record_tiny_scope_trace.py

writes both under ``chiprun_out/``; gzip them beside this file. It also
SAYS what the reader's join rests on (PR 50's step 0): whether an event's
name equals its compiled line less ``metadata`` (it does not: the event
writes the operands' types out), and that every event's instruction name
and result type are in exactly the texts of its program.
``BENCHMARK_REHEARSAL=1`` with ``JAX_PLATFORMS=cpu
RAY_TPU_PALLAS_INTERPRET=1`` rehearses the script on the CPU, whose trace
has no device plane.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common, scope_reduce, trace_reduce  # noqa: E402


def main() -> int:
    import jax
    import numpy as np

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import EngineConfig, LLMEngine, decode, obs

    if not common.rehearsal() and jax.devices()[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {jax.devices()}")
    backend = "xla" if common.rehearsal() else "pallas"
    llama = LlamaConfig(
        vocab_size=2048, max_seq_len=512, n_layer=2, n_head=8, n_kv_head=2,
        d_model=1024, d_mlp=2048, attention_backend=backend)
    engines = [
        LLMEngine(EngineConfig(model="llama", model_config=llama,
                               block_size=16, num_blocks=129,
                               max_batch_size=4,
                               length_buckets=(64, 128, 256)),
                  auto_step=False),
        LLMEngine(EngineConfig(model="lfm2_moe", max_batch_size=4),
                  auto_step=False),
    ]
    rng = np.random.default_rng(50)

    def serve(eng, lengths: list[int], new: int) -> None:
        vocab = eng.model_cfg.vocab_size
        streams = [eng.submit(rng.integers(1, vocab, size=n).tolist(),
                              max_new_tokens=new) for n in lengths]
        for _ in range(1000):
            if all(s.done for s in streams):
                return
            eng.step()
        raise SystemExit("the requests did not finish")

    for eng in engines:  # every shape once, outside the trace
        serve(eng, [40, 90], new=6)
        serve(eng, [40, 90, 33], new=6)
    out = os.path.join(ROOT, "chiprun_out", "tiny_scope_trace")
    shutil.rmtree(out, ignore_errors=True)
    tracing = common.Tracing(out)
    tracing.start()
    for eng in engines:
        serve(eng, [40, 90], new=6)
        serve(eng, [52, 70, 33], new=5)
    print(tracing.stop())
    print("programs", {eng.cfg.model: eng.stats()["programs"]
                       for eng in engines})
    for eng in engines:
        eng.shutdown()
    assert decode.lowerings == 0, "a served request lowered a program again"

    # the programs' texts, as program_scopes() reads them (after shutdown)
    t0 = time.perf_counter()
    texts = {}
    for registry in decode._programs.values():
        for sig, rec in registry.items():
            args, kwargs = rec["args"]
            texts[f"{rec['name']} {obs.shape_key(sig)}"] = {
                "name": rec["name"],
                "text": rec["fn"].lower(*args, **kwargs).compile().as_text()}
    print(f"{len(texts)} programs lowered and compiled again in "
          f"{time.perf_counter() - t0:.2f}s")
    t0 = time.perf_counter()
    maps = decode.program_scopes()
    print(f"program_scopes(): {len(maps)} programs in "
          f"{time.perf_counter() - t0:.2f}s, lowerings {decode.lowerings}")
    path = trace_reduce.find_xplane(out)
    kept = os.path.join(ROOT, "chiprun_out", "tiny_scope_tpu.xplane.pb")
    shutil.copyfile(path, kept)
    with open(os.path.join(ROOT, "chiprun_out", "tiny_scope_texts.json"),
              "w") as f:
        # less each line's ``backend_config``: two thirds of a text, and
        # nothing the map reads
        json.dump({label: {**held, "text": re.sub(
            r", backend_config=\{.*$", "", held["text"], flags=re.M)}
            for label, held in texts.items()}, f)
    print(kept, os.path.getsize(kept), "bytes;", len(texts), "texts",
          sum(len(t["text"]) for t in texts.values()), "characters")

    # step 0: what joins an event to its compiled line
    read = scope_reduce.read_planes(kept)
    if read is None:
        print("no device plane in the trace (a rehearsal's)")
        return 0
    planes, window = read
    lines = {}  # compiled lines less metadata and backend_config, by program
    for label, held in texts.items():
        lines[label] = {
            re.sub(r", (metadata|backend_config)=\{.*$", "",
                   ln.strip().removeprefix("ROOT ")): 1
            for ln in held["text"].splitlines()}
    events = {name for p in planes for name, _, _ in p["ops"]}
    whole = sum(any(name in held for held in lines.values())
                for name in events)
    print(f"step 0: {whole} of {len(events)} distinct event names equal a "
          f"compiled line less its metadata and backend_config")
    for name in sorted(events)[:3]:
        key = obs.instruction_key(name)
        print("  event:", name[:300])
        for label, held in texts.items():
            for ln in held["text"].splitlines():
                if obs.instruction_key(ln) == key:
                    print("  line :", ln.strip()[:300], "|", label)
                    break
    programs = {label: {"name": held["name"],
                        "scopes": obs.scope_map(held["text"])}
                for label, held in texts.items()}
    table = scope_reduce.attribute(planes, window, programs)
    table["map_s"] = {}
    scope_reduce.say_table(table)
    return 0


if __name__ == "__main__":
    sys.exit(main())
