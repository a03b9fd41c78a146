"""GPT-2 (125M) single-chip train-step benchmark — the headline metric.

Transformers are the workload TPUs are bought for; this measures a jitted
next-token training step (flash-attention Pallas kernel, bf16 activations,
donated buffers) and reports tokens/sec + MFU.

MFU convention: model FLOPs = 6 * n_params * tokens per train step (PaLM
appendix-B style, attention excluded — conservative), divided by the chip's
peak bf16 rate. The reference publishes no MFU (or any TPU number) for its
trainers (doc/source/train/benchmarks.rst), so the bar here is the absolute
one this repo sets for itself: >= 0.35 on a single chip.

Runnable standalone: `python -m ray_tpu.benchmarks.gpt_mfu` prints one JSON
line (used by bench.py as the headline entry).
"""
from __future__ import annotations

import json
import os
import time
from functools import partial


def run_gpt_bench(
    batch_size: int = 16,
    seq_len: int = 1024,
    steps: int = 40,
    warmup: int = 4,
    peak_tflops: float | None = None,
    config: str = "gpt2_small",
    remat: bool = False,
) -> dict:
    """Measure jitted GPT train-step throughput. ``peak_tflops`` defaults
    to the device's entry in ``CHIP_PEAK_TFLOPS``; a device that has none
    (the CPU included) is an error unless the caller hands in a peak."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig, gpt_num_params

    dev = jax.devices()[0]
    platform = dev.platform
    if peak_tflops is None:
        peak_tflops = chip_peak_tflops(dev)

    cfg = getattr(GPTConfig, config)() if config != "tiny" else GPTConfig.tiny()
    # the bench runs the unrolled layer loop: XLA schedules across layer
    # boundaries instead of paying the scan-carry tax in the backward
    # (33%→43% MFU on v5e bs16/seq1024; see docs/MICROBENCHMARKS.md)
    cfg = dataclasses.replace(cfg, scan_layers=env_bool("BENCH_GPT_SCAN"))
    if remat:
        # last-rung fallback for smaller-HBM chips: per-block
        # rematerialization trades ~1 extra forward for dropping the
        # saved per-layer residuals (scan or unrolled alike)
        cfg = dataclasses.replace(cfg, remat=True)
    if seq_len > cfg.max_seq_len:
        # long-context bench shapes: grow the positional table (a shorter
        # context slices down free)
        cfg = dataclasses.replace(cfg, max_seq_len=seq_len)
    n_params = gpt_num_params(cfg)
    model_label = _model_label(config, n_params)
    train_step, params, opt_state = make_train_step(cfg)

    key = jax.random.PRNGKey(1)
    batch = {
        "tokens": jax.random.randint(
            key, (batch_size, seq_len + 1), 0, cfg.vocab_size, jnp.int32
        ),
    }
    tokens_per_step = batch_size * seq_len

    for _ in range(warmup):
        params, opt_state, loss = train_step(params, opt_state, batch)
    jax.block_until_ready(loss)

    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = train_step(params, opt_state, batch)
    jax.block_until_ready(loss)
    tps = tokens_per_step * steps / (time.perf_counter() - t0)

    achieved = tps * 6.0 * n_params / 1e12
    mfu = achieved / peak_tflops
    return {
        "metric": f"{model_label}_train_tokens_per_sec_per_chip_{platform}",
        "value": round(tps, 1),
        "unit": "tokens/sec",
        # no reference GPT/MFU number exists (BASELINE.md) — the bar is
        # the self-set 35% MFU target, so vs_baseline = mfu / 0.35
        "vs_baseline": round(mfu / 0.35, 3),
        "mfu": round(mfu, 4),
        "achieved_tflops": round(achieved, 1),
        "chip_peak_tflops": peak_tflops,
        "n_params": n_params,
        "batch_size": batch_size,
        "seq_len": seq_len,
        "remat": remat,
    }


def make_train_step(cfg):
    """The jitted GPT train step this benchmark times (adamw, donated
    params and optimizer state) with freshly initialised state: returns
    ``(train_step, params, opt_state)``; ``train_step(params, opt_state,
    batch) -> (params, opt_state, loss)``. chip_smoke.py's train phase
    runs the same step inside a JaxTrainer worker."""
    import jax
    import optax

    from ray_tpu.models.gpt import gpt_init, gpt_loss

    params = gpt_init(jax.random.PRNGKey(0), cfg)
    tx = optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1)
    opt_state = tx.init(params)

    @partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(gpt_loss)(params, batch, cfg)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return train_step, params, opt_state


def _model_label(config: str, n_params: int) -> str:
    """Metric label derived from the ACTUAL benched config, never hardcoded:
    a tiny-config fallback run must not be labeled as the 125M headline."""
    canonical = {"gpt2_small": "gpt2_125m", "gpt2_medium": "gpt2_350m"}
    if config in canonical:
        return canonical[config]
    if n_params >= 1e6:
        return f"gpt2_{config}_{n_params / 1e6:.0f}m"
    return f"gpt2_{config}_{n_params / 1e3:.0f}k"


# Published per-chip peak bf16 TFLOP/s by device_kind substring (Google
# Cloud TPU documentation, one page per generation; shared with bench.py
# and the serving MFU gauge; ordering matters — first substring match
# wins). A device that is not listed is an error, never a default.
CHIP_PEAK_TFLOPS = [
    ("v6", 918.0),
    ("v5p", 459.0),
    ("v5 lite", 197.0),
    ("v5e", 197.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
]


def env_bool(name: str) -> bool:
    """Shared falsy-string parse so 'False'/'no'/'off'/'0' all disable."""
    return os.environ.get(name, "").strip().lower() not in (
        "", "0", "false", "no", "off"
    )


def gpt_env_kwargs() -> dict:
    """BENCH_GPT_* env overrides as run_gpt_bench kwargs — the one parser
    both entry points (bench.py and this module's main) share. A falsy
    BENCH_GPT_REMAT contributes nothing, so it cannot make the kwargs
    truthy and suppress bench.py's OOM fallback ladder."""
    kwargs: dict = {}
    for name, key in (("BENCH_GPT_BS", "batch_size"),
                      ("BENCH_GPT_SEQ", "seq_len"),
                      ("BENCH_GPT_STEPS", "steps")):
        if os.environ.get(name):
            kwargs[key] = int(os.environ[name])
    if os.environ.get("BENCH_GPT_CONFIG"):
        kwargs["config"] = os.environ["BENCH_GPT_CONFIG"]
    if env_bool("BENCH_GPT_REMAT"):
        kwargs["remat"] = True
    return kwargs


def chip_peak_tflops(device) -> float:
    kind = getattr(device, "device_kind", "").lower()
    for sub, peak in CHIP_PEAK_TFLOPS:
        if sub in kind:
            return peak
    raise ValueError(
        f"no published peak for device kind {kind!r} (platform "
        f"{device.platform!r}); add it to CHIP_PEAK_TFLOPS with its source"
    )


def main() -> None:
    print(json.dumps(run_gpt_bench(**gpt_env_kwargs())), flush=True)


if __name__ == "__main__":
    main()
