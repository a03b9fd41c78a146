"""Records ``tiny_serve_tpu.xplane.pb``, the small trace that
``test_benchmark_span_reduce.py`` reads: a hand-stepped engine over a small
GQA llama (2 layers, 8 query / 2 KV heads of 128, the Pallas decode kernel)
serving five requests, then three runs of a jitted flash-attention forward
and backward, under one profiler session marked as the runners mark theirs.
The engine's phase spans, its named programs and both kernels are in it.

On the chip, from the root of the checkout (PR 24 recorded it so):

    chiprun -- python3 tests/benchmark/record_tiny_serve_trace.py

writes ``chiprun_out/tiny_serve_tpu.xplane.pb``; copy it beside this file
and correct the numbers the test pins. ``BENCHMARK_REHEARSAL=1`` with
``JAX_PLATFORMS=cpu RAY_TPU_PALLAS_INTERPRET=1`` rehearses the script on
the CPU, whose trace has no device plane.
"""
from __future__ import annotations

import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common, trace_reduce  # noqa: E402

FLASH_SHAPE = (2, 4, 512, 64)  # batch, heads, sequence, head size


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.ops.attention import flash_attention
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    if not common.rehearsal() and jax.devices()[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {jax.devices()}")
    cfg = LlamaConfig(
        vocab_size=2048, max_seq_len=512, n_layer=2, n_head=8, n_kv_head=2,
        d_model=1024, d_mlp=2048,
        attention_backend="xla" if common.rehearsal() else "pallas")
    eng = LLMEngine(
        EngineConfig(model="llama", model_config=cfg, block_size=16,
                     num_blocks=129, max_batch_size=4,
                     length_buckets=(64, 128, 256)),
        auto_step=False)
    rng = np.random.default_rng(24)

    def serve(lengths: list[int], new: int) -> None:
        streams = [eng.submit(rng.integers(1, 2048, size=n).tolist(),
                              max_new_tokens=new) for n in lengths]
        for _ in range(1000):
            if all(s.done for s in streams):
                return
            eng.step()
        raise SystemExit("the requests did not finish")

    def tiny_train_step(q, k, v):
        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v).astype(jnp.float32))

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    train = jax.jit(tiny_train_step)
    x = jnp.ones(FLASH_SHAPE, jnp.bfloat16)
    # every shape once, outside the trace
    serve([40, 90], new=6)
    serve([40, 90, 33], new=6)
    jax.block_until_ready(train(x, x, x))

    out = os.path.join(ROOT, "chiprun_out", "tiny_serve_trace")
    shutil.rmtree(out, ignore_errors=True)
    tracing = common.Tracing(out)
    tracing.start()
    serve([40, 90], new=6)
    serve([52, 70, 33], new=5)
    for _ in range(3):
        jax.block_until_ready(train(x, x, x))
    print(tracing.stop(), eng.stats()["phases"])
    eng.shutdown()
    path = trace_reduce.find_xplane(out)
    kept = os.path.join(ROOT, "chiprun_out", "tiny_serve_tpu.xplane.pb")
    shutil.copyfile(path, kept)
    print(kept, os.path.getsize(kept), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
