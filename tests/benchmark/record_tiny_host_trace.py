"""Records ``tiny_host_tpu.xplane.pb``, the small trace that
``test_benchmark_host_reduce.py`` reads: the small GQA llama of
``record_tiny_serve_trace.py`` (2 layers, 8 query / 2 KV heads of 128, the
Pallas decode kernel) behind the engine's OWN stepping thread, four closed-
loop clients of unequal lengths (rows join and leave, so ids are gathered:
``executor.feed``), under one profiler session marked as the runners mark
theirs. Inside the marks one thread holds the engine's lock for 20 ms
(``engine.lock``) and one forces a collection (``host.gc``).

On the chip, from the root of the checkout (PR 36 recorded it so):

    chiprun -- python3 tests/benchmark/record_tiny_host_trace.py

writes ``chiprun_out/tiny_host_tpu.xplane.pb``; gzip it beside this file.
``BENCHMARK_REHEARSAL=1`` with ``JAX_PLATFORMS=cpu
RAY_TPU_PALLAS_INTERPRET=1`` rehearses the script on the CPU, whose trace
has no device plane.
"""
from __future__ import annotations

import gc
import os
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common, trace_reduce  # noqa: E402


def main() -> int:
    import jax
    import numpy as np

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    if not common.rehearsal() and jax.devices()[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {jax.devices()}")
    cfg = LlamaConfig(
        vocab_size=2048, max_seq_len=512, n_layer=2, n_head=8, n_kv_head=2,
        d_model=1024, d_mlp=2048,
        attention_backend="xla" if common.rehearsal() else "pallas")
    settings = dict(model="llama", model_config=cfg, block_size=16,
                    num_blocks=129, max_batch_size=4,
                    length_buckets=(64, 128, 256), batch_buckets=(1, 2, 4))
    # every shape once on a hand-stepped engine, outside the trace: the
    # programs are the process's, so the serving engine finds them compiled
    warm = LLMEngine(EngineConfig(**settings), auto_step=False)
    rng = np.random.default_rng(36)
    for rows in (1, 2, 4):
        for n in (40, 90, 200):
            streams = [warm.submit(rng.integers(1, 2048, size=n).tolist(),
                                   max_new_tokens=56 if n == 200 else 8)
                       for _ in range(rows)]
            for _ in range(2000):
                if all(s.done for s in streams):
                    break
                warm.step()
            else:
                raise SystemExit("the warm-up did not finish")
    warm.shutdown()

    eng = LLMEngine(EngineConfig(**settings), auto_step=True)
    stop = threading.Event()

    def client(i: int) -> None:
        mine = np.random.default_rng(100 + i)
        while not stop.is_set():
            n = int(mine.integers(20, 120))
            list(eng.submit(mine.integers(1, 2048, size=n).tolist(),
                            max_new_tokens=int(mine.integers(4, 24))))

    clients = [threading.Thread(target=client, args=(i,)) for i in range(4)]
    for t in clients:
        t.start()
    time.sleep(0.3)  # the ramp, and whatever the warm-up missed

    def hold() -> None:
        with eng._lock:
            time.sleep(0.02)

    out = os.path.join(ROOT, "chiprun_out", "tiny_host_trace")
    shutil.rmtree(out, ignore_errors=True)
    tracing = common.Tracing(out)
    tracing.start()
    time.sleep(0.03)
    holder = threading.Thread(target=hold)
    holder.start()
    holder.join(timeout=30)
    time.sleep(0.02)
    gc.collect()
    time.sleep(0.03)
    print(tracing.stop(), eng.stats()["host"])
    stop.set()
    for t in clients:
        t.join(timeout=60)
    print("compile records in the ring:", sum(
        r["kind"] == "compile" for r in eng.debug_dump()["steps"]))
    eng.shutdown()
    path = trace_reduce.find_xplane(out)
    kept = os.path.join(ROOT, "chiprun_out", "tiny_host_tpu.xplane.pb")
    shutil.copyfile(path, kept)
    print(kept, os.path.getsize(kept), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
