"""Records ``tiny_latent_tpu.xplane.pb``, the small trace that
``test_benchmark_pangu_ultra_moe.py`` reads with the three latent readers:
a hand-stepped engine over a small openPangu-Ultra-MoE (3 layers, 8 heads
over one 128 + 64 row a token in two planes, 8 experts of which 2 held,
the Pallas latent kernel) serving a few requests, a prompt longer than a
chunk among them, under one profiler session marked as the runners mark
theirs. The engine's phase spans with ``kv_tokens`` and ``qk_pairs``, its
named programs and ``paged_attention_latent`` are in it.

On the chip, from the root of the checkout (PR 39 recorded it so):

    chiprun -- python3 tests/benchmark/record_tiny_latent_trace.py

writes ``chiprun_out/tiny_latent_tpu.xplane.pb``; gzip it beside this file
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

KEYS = dict(vocab_size=2048, max_seq_len=1024, d_model=512, n_head=8,
            q_lora_rank=256, kv_lora_rank=128, qk_nope_head_dim=64,
            qk_rope_head_dim=64, v_head_dim=64, n_layer=3, num_dense_layers=1,
            d_mlp=1024, num_experts=8, top_k=2, d_expert=256, d_shared=256,
            experts_held=(0, 2))


def main() -> int:
    import jax
    import numpy as np

    from ray_tpu.models.pangu_ultra_moe import PanguUltraMoEConfig
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    if not common.rehearsal() and jax.devices()[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {jax.devices()}")
    cfg = PanguUltraMoEConfig(
        **KEYS, attention_backend="xla" if common.rehearsal() else "pallas")
    eng = LLMEngine(
        EngineConfig(model="pangu_ultra_moe", model_config=cfg, block_size=16,
                     num_blocks=129, max_batch_size=4, max_prefill_batch=1,
                     prefill_chunk_tokens=128, length_buckets=(128, 512),
                     batch_buckets=(1, 4)),
        auto_step=False)
    rng = np.random.default_rng(39)

    def serve(lengths: list[int], new: int) -> None:
        streams = [eng.submit(rng.integers(1, 2048, size=n).tolist(),
                              max_new_tokens=new) for n in lengths]
        for _ in range(1000):
            if all(s.done for s in streams):
                return
            eng.step()
        raise SystemExit("the requests did not finish")

    # every shape once, outside the trace
    serve([40], new=4)
    serve([300, 90, 33], new=4)

    out = os.path.join(ROOT, "chiprun_out", "tiny_latent_trace")
    shutil.rmtree(out, ignore_errors=True)
    tracing = common.Tracing(out)
    tracing.start()
    serve([50], new=5)
    serve([280, 70, 33], new=6)
    print(tracing.stop(), eng.stats()["phases"])
    eng.shutdown()
    path = trace_reduce.find_xplane(out)
    kept = os.path.join(ROOT, "chiprun_out", "tiny_latent_tpu.xplane.pb")
    shutil.copyfile(path, kept)
    print(kept, os.path.getsize(kept), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
