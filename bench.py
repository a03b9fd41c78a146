"""Benchmark: GPT-2 (125M) training throughput on one TPU chip, plus
ResNet-50 as the secondary entry.

One process that measures and prints ONE JSON line:
{"metric", "value", "unit", "vs_baseline", "mfu", ..., "device", "resnet"}.
It fails — nonzero exit, no JSON line — when JAX finds no TPU, when the
chip is not in the peak table (ray_tpu/benchmarks/gpt_mfu.py), or when
any measurement raises. There is no CPU retry, no cached result and no
watchdog: a run either measured the device or it did not.

Headline: tokens/sec + MFU for a jitted GPT-2 125M train step (flash
attention, bf16, donated buffers) — see ray_tpu/benchmarks/gpt_mfu.py. The
reference publishes no transformer/TPU number (BASELINE.md), so the bar is
self-set: 35% MFU; vs_baseline = mfu / 0.35. The secondary "resnet" entry
compares images/sec with the reference's published 40.7 img/s 1-GPU
TorchTrainer (doc/source/train/benchmarks.rst:33-37).

Shapes are overridable with BENCH_GPT_BS / BENCH_GPT_SEQ / BENCH_GPT_STEPS
/ BENCH_GPT_CONFIG / BENCH_GPT_REMAT (gpt_mfu.gpt_env_kwargs) and
BENCH_BATCH_SIZE / BENCH_STEPS / BENCH_IMAGE_SIZE; BENCH_SKIP_RESNET=1
drops the secondary entry.
"""
from __future__ import annotations

import json
import os
import time
from functools import partial

from ray_tpu.benchmarks.gpt_mfu import (
    chip_peak_tflops, gpt_env_kwargs, run_gpt_bench,
)

BASELINE_IMG_PER_SEC = 40.7  # reference 1-GPU TorchTrainer (BASELINE.md)

# ResNet-50 @224: ~4.09 GFLOPs forward per image; train step (fwd+bwd) ~3x.
RESNET50_TRAIN_GFLOPS_PER_IMG_224 = 3.0 * 4.09


def _make_resnet_result(images_per_sec: float, platform: str, image_size: int,
                        peak_tflops: float, tag: str = "") -> dict:
    # Scale FLOPs quadratically with resolution relative to 224 (convs dominate).
    gflops_img = RESNET50_TRAIN_GFLOPS_PER_IMG_224 * (image_size / 224.0) ** 2
    achieved_tflops = images_per_sec * gflops_img / 1e3
    return {
        "metric": f"resnet50_train_images_per_sec_per_chip_{platform}{tag}",
        "value": round(images_per_sec, 2),
        "unit": "images/sec",
        "vs_baseline": round(images_per_sec / BASELINE_IMG_PER_SEC, 2),
        "mfu": round(achieved_tflops / peak_tflops, 4) if peak_tflops else 0.0,
        "achieved_tflops": round(achieved_tflops, 1),
        "chip_peak_tflops": peak_tflops,
    }


def run_resnet_bench(batch_size: int = 256, steps: int = 30, warmup: int = 5,
                     image_size: int = 224, tag: str = "") -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.resnet import ResNet50, resnet_init, resnet_loss

    dev = jax.devices()[0]
    platform = dev.platform
    peak = chip_peak_tflops(dev)
    dtype = jnp.bfloat16
    model = ResNet50(num_classes=1000, dtype=dtype)
    params, batch_stats = resnet_init(jax.random.PRNGKey(0), model, image_size)

    tx = optax.sgd(0.1, momentum=0.9, nesterov=True)
    opt_state = tx.init(params)

    # donation: params/stats/opt_state buffers are consumed and rewritten
    # in place, halving HBM traffic for the weight update
    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(params, batch_stats, opt_state, batch):
        (loss, (new_stats, acc)), grads = jax.value_and_grad(
            resnet_loss, has_aux=True
        )(params, batch_stats, model, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_stats, opt_state, loss

    # synthetic data, device-resident (input-pipeline throughput is measured
    # separately by the data layer; this is the compute ceiling, matching how
    # the reference's GPU benchmark feeds preloaded tensors)
    key = jax.random.PRNGKey(1)
    batch = {
        "image": jax.random.normal(
            key, (batch_size, image_size, image_size, 3), dtype
        ),
        "label": jax.random.randint(key, (batch_size,), 0, 1000),
    }

    for _ in range(warmup):
        params, batch_stats, opt_state, loss = train_step(
            params, batch_stats, opt_state, batch
        )
    jax.block_until_ready(loss)

    t0 = time.perf_counter()
    for _ in range(steps):
        params, batch_stats, opt_state, loss = train_step(
            params, batch_stats, opt_state, batch
        )
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    return _make_resnet_result(batch_size * steps / dt, platform, image_size,
                               peak, tag)


def main() -> None:
    from ray_tpu._private.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU; JAX found platform {dev.platform!r}"
        )
    # bs24/seq1024 is the headline shape (BENCH_r05.json)
    result = run_gpt_bench(
        **{"batch_size": 24, "seq_len": 1024, **gpt_env_kwargs()}
    )
    result["device"] = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    if not os.environ.get("BENCH_SKIP_RESNET"):
        kwargs = {}
        for name, k in (("BENCH_BATCH_SIZE", "batch_size"),
                        ("BENCH_STEPS", "steps"),
                        ("BENCH_IMAGE_SIZE", "image_size")):
            if os.environ.get(name):
                kwargs[k] = int(os.environ[name])
        result["resnet"] = run_resnet_bench(**kwargs)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
