"""The readings ``reference_check.tolerance_why`` of
``falcon-h1-34b-instruct-5l`` and PERF.md (PR 58) quote, re-runnable from the
tree: the harness's own comparison (``serve_engine.reference_check``) at
published widths, the sound program's streams judged under the exact
reference and under references with ONE thing changed each, in this process:

- ``fp8``: both operands of every matrix product of the reference cut to
  e4m3, the nearest precision below the configuration's bfloat16 (the second
  reading a limit is set from: it has to come out as NOT correct);
- a fault that a serving stack could plant, MIRRORED into the reference (the
  comparison sees the difference between program and reference whichever
  side holds it, and a reference is recompiled in a minute where the
  engine's programs take five): a multiplier dropped (``no_<name>``), the
  head's product in bfloat16 (``head_bf16``), the gate BEHIND the norm
  (``gate_behind_norm``), the norm over all 4,096 channels
  (``norm_over_all``), the convolution's bias left out (``no_conv_bias``),
  the SSM state kept in bfloat16 (``state_bf16``).

On the chip, from the root of the checkout (PR 58 ran it so):

    chiprun --timeout 2400 -- python3 tests/benchmark/control_falcon_h1_readings.py

prints one line a (seed, reading) and writes
``chiprun_out/falcon_h1_readings.json``. ``CONTROL_SEEDS`` (default one)
names the seeds, ``CONTROL_READINGS`` a comma list of the readings (default:
all). ``BENCHMARK_REHEARSAL=1`` with ``JAX_PLATFORMS=cpu
RAY_TPU_PALLAS_INTERPRET=1`` rehearses the script on the CPU at the
configuration's rehearsal size.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import os
import sys
import time
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common  # noqa: E402
from benchmark.runners import serve_engine  # noqa: E402

CELL = "falconh1-worked-answers-saturated"
DROPPED = {"no_key_multiplier": ("key_multiplier", None),
           "no_attention_out_multiplier": ("attention_out_multiplier", None),
           "no_ssm_multiplier_dt": ("ssm_multipliers", 4),
           "no_mlp_multiplier_gate": ("mlp_multipliers", 0),
           "no_lm_head_multiplier": ("lm_head_multiplier", None)}
READINGS = ("exact", "fp8", *DROPPED, "head_bf16", "gate_behind_norm",
            "norm_over_all", "no_conv_bias", "state_bf16")


def _without(cfg, field, index):
    if index is None:
        return dataclasses.replace(cfg, **{field: 1.0})
    value = list(getattr(cfg, field))
    value[index] = 1.0
    return dataclasses.replace(cfg, **{field: tuple(value)})


def changed(ref, reading: str):
    """A context in which the reference module computes ``reading``."""
    import jax
    import jax.numpy as jnp

    patch = lambda name, value: mock.patch.object(ref, name, value)  # noqa: E731
    if reading == "exact":
        return contextlib.nullcontext()
    if reading == "fp8":
        return patch("ROUND_TO", jnp.float8_e4m3fn)
    if reading in DROPPED:
        sound = ref.logits_at
        return patch("logits_at", lambda pr, t, pos, cfg: sound(
            pr, t, pos, _without(cfg, *DROPPED[reading])))
    if reading == "head_bf16":
        return patch("HEAD_ROUND_TO", jnp.bfloat16)
    if reading == "state_bf16":
        return patch("STATE_ROUND_TO", jnp.bfloat16)
    if reading == "no_conv_bias":
        sound_conv = ref.short_conv
        return patch("short_conv", lambda x, w, b: sound_conv(x, w, 0.0 * b))
    rms = lambda g, eps: g * jax.lax.rsqrt(  # noqa: E731
        jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)
    if reading == "gate_behind_norm":
        def wrong(y, z, scale, cfg):
            S = y.shape[0]
            g = rms(y.reshape(S, cfg.ssm_n_group, -1), cfg.norm_eps)
            return g.reshape(S, -1) * jnp.asarray(
                scale, jnp.float32) * jax.nn.silu(z)
        return patch("gated_norm", wrong)
    if reading == "norm_over_all":
        return patch("gated_norm", lambda y, z, scale, cfg: rms(
            y * jax.nn.silu(z), cfg.norm_eps) * jnp.asarray(
                scale, jnp.float32))
    raise ValueError(f"no reading {reading!r}; there are {READINGS}")


def main() -> int:
    import jax

    from ray_tpu._private.compile_cache import enable_compile_cache

    enable_compile_cache()
    spec = common.resolve_cell(common.load_manifest(), CELL)
    common.device_report(1)
    cfg = common.model_config(spec["config"])
    ref = common.load_named("reference", spec["config"]["family"])
    seeds = [int(s) for s in os.environ.get(
        "CONTROL_SEEDS", "2900000011").split(",")]
    readings = os.environ.get("CONTROL_READINGS", ",".join(READINGS)).split(
        ",")
    out = []
    for seed in seeds:
        # one seed's weights at a time: two would not fit the chip
        params = jax.block_until_ready(
            serve_engine.make_params(spec, cfg, seed))
        engine = serve_engine.make_engine(spec, cfg, params, auto_step=False)
        for reading in readings:
            t = time.time()
            with changed(ref, reading):
                chk = serve_engine.reference_check(engine, spec, cfg, seed)
            out.append({"seed": seed, "reading": reading, **chk,
                        "seconds": round(time.time() - t, 1)})
            print("CONTROL", json.dumps(out[-1]), flush=True)
        engine.shutdown()
        del engine, params
        gc.collect()
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/falcon_h1_readings.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
