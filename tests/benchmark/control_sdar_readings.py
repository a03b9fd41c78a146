"""The readings ``reference_check.tolerance_why`` of
``sdar-30b-a3b-chat-6l`` quotes, re-runnable from the tree: the harness's
own comparison (``serve_engine.reference_check``) at published widths on the
sound program over several seeds, and, on the engine's own sequences of the
first seeds, what a WRONG or a COARSER reference would have chosen, judged
the same way (the right reference's logit of that choice against its
largest):

- ``fp8``: the reference computed with both operands of every matrix
  product cut to e4m3, the nearest precision below bfloat16: must FAIL;
- ``bf16``: the same with bfloat16 operands, the engine's own kind of
  noise: must PASS;
- ``causal``: a causal mask for the block mask; ``no_qk_norm``: the norm
  over a head dropped; ``not_renormalised``: expert weights not
  renormalised over the chosen 8.

On the chip, from the root of the checkout (PR 54 ran it so):

    chiprun --timeout 2400 -- python3 tests/benchmark/control_sdar_readings.py

prints one line a reading and appends them to
``chiprun_out/sdar_readings.jsonl``. ``CONTROL_SEEDS`` names the seeds (the
controls run on the first ``CONTROL_WRONG`` of them, default 1). ON THE CHIP
GIVE ONE SEED A PROCESS (``for s in ..; do CONTROL_SEEDS=$s python3 ..``): a
finished engine's 4.8 GB pool and its weights stay reachable from this
process's closures, and a second seed's weights do not fit beside them (PR
54's first call died so at its second seed). ``BENCHMARK_REHEARSAL=1`` with
``JAX_PLATFORMS=cpu RAY_TPU_PALLAS_INTERPRET=1`` rehearses the script on the
CPU at the configuration's rehearsal size.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common  # noqa: E402
from benchmark.runners import serve_engine  # noqa: E402

CELL = "sdar-blockdiff-chat-saturated"


def variants(ref, cfg):
    """name -> (module attributes to set, the config the reference gets)."""
    import jax.numpy as jnp

    return {
        "fp8": ({"ROUND_TO": jnp.float8_e4m3fn}, cfg),
        "bf16": ({"ROUND_TO": jnp.bfloat16}, cfg),
        "causal": ({"sees": lambda pos, t, cfg: t <= pos}, cfg),
        "no_qk_norm": ({"qk_norm": lambda x, scale, cfg: x}, cfg),
        "not_renormalised": (
            {}, dataclasses.replace(cfg, norm_topk_prob=False)),
    }


def main() -> int:
    import jax
    import numpy as np

    from ray_tpu._private.compile_cache import enable_compile_cache

    enable_compile_cache()
    spec = common.resolve_cell(common.load_manifest(), CELL)
    common.device_report(1)
    cfg = common.model_config(spec["config"])
    ref = common.load_named("reference", spec["config"]["family"])
    seeds = [int(s) for s in os.environ.get(
        "CONTROL_SEEDS", "54").split(",")]
    wrong_on = int(os.environ.get("CONTROL_WRONG", "1"))
    out = []

    def say(rec):
        out.append(rec)
        print("CONTROL", json.dumps(rec), flush=True)

    for n, seed in enumerate(seeds):
        # one seed's weights at a time: two would not fit the chip
        params = jax.block_until_ready(
            serve_engine.make_params(spec, cfg, seed))
        engine = serve_engine.make_engine(spec, cfg, params, auto_step=False)
        asked = []
        submit = engine.submit

        def keep(prompt, **kw):
            asked.append((list(prompt), submit(prompt, **kw)))
            return asked[-1][1]

        engine.submit = keep
        t = time.time()
        chk = serve_engine.reference_check(engine, spec, cfg, seed)
        say({"seed": seed, "reading": "engine", **chk,
             "seconds": round(time.time() - t, 1)})
        weights = engine.params
        engine.shutdown()
        del engine
        gc.collect()
        if n >= wrong_on:
            del params, weights
            gc.collect()
            continue
        # the engine's own sequences, laid out as the harness lays them
        chk_cfg = spec["config"]["reference_check"]
        new = chk_cfg["new_tokens"]
        tokens = np.zeros((len(asked), chk_cfg["pad_to"]), np.int32)
        positions = np.zeros((len(asked), new), np.int32)
        for i, (prompt, stream) in enumerate(asked):
            seq = prompt + list(stream._request.generated)
            tokens[i, :len(seq)] = seq
            positions[i] = len(prompt) + np.arange(new) - 1

        def logits(c):
            got = jax.jit(lambda p, t, pos: ref.logits_at(p, t, pos, c))(
                weights, tokens, positions)
            return np.asarray(jax.block_until_ready(got), np.float32)

        right = logits(cfg)
        top = right.max(axis=-1)
        for name, (attrs, wrong_cfg) in variants(ref, cfg).items():
            was = {k: getattr(ref, k) for k in attrs}
            for k, v in attrs.items():
                setattr(ref, k, v)
            t = time.time()
            try:
                chosen = logits(wrong_cfg).argmax(axis=-1)
            finally:
                for k, v in was.items():
                    setattr(ref, k, v)
            deficit = top - np.take_along_axis(
                right, chosen[..., None], axis=-1)[..., 0]
            say({"seed": seed, "reading": name,
                 "max_deficit": float(deficit.max()),
                 "median_deficit": float(np.median(deficit)),
                 "same_token": int((chosen == right.argmax(-1)).sum()),
                 "checked": int(deficit.size),
                 "seconds": round(time.time() - t, 1)})
        del params, weights, right
        gc.collect()
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/sdar_readings.jsonl", "a") as f:
        f.writelines(json.dumps(rec) + "\n" for rec in out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
