"""The control ``reference_check.tolerance_why`` of
``ling-3.0-flash-ep8-7l`` quotes, re-runnable from the tree: the harness's
own comparison (``serve_engine.reference_check``) at published widths, on
the sound PROGRAM and on the program with ONE fault planted in this process
(the KDA state rounded to bfloat16 wherever it is stored: the step kernel's
write and the chunked form's carried state; the reference untouched), under
the committed ``reference_check`` and under longer decodes given as DATA
alone (``new_tokens`` / ``every`` / ``pad_to`` / ``requests``), so that a
state rounded at every decode step has hundreds to thousands of steps to
show in.

On the chip, from the root of the checkout (PR 52 ran it so):

    chiprun --timeout 1500 -- python3 tests/benchmark/control_ling_state_bf16.py

prints one line a (seed, program, setting) and writes
``chiprun_out/ling_state_control.json``. ``CONTROL_SEEDS`` (default two)
names the seeds. ``BENCHMARK_REHEARSAL=1`` with ``JAX_PLATFORMS=cpu
RAY_TPU_PALLAS_INTERPRET=1`` rehearses the script on the CPU at the
configuration's rehearsal size.
"""
from __future__ import annotations

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

CELL = "ling-reason-long-saturated"
# the committed check, then the same rows decoding 1,024 tokens, then four
# short prompts decoding 3,072 (the latent layer's context then made by
# decode steps almost alone)
SETTINGS = {
    "committed": {},
    "decode_1024": {"new_tokens": 1024, "every": 2, "pad_to": 7168},
    "decode_3072": {"requests": 4, "new_tokens": 3072, "every": 4,
                    "pad_to": 4096},
}
if common.rehearsal():
    SETTINGS = {"committed": {},
                "decode_24": {"new_tokens": 24, "every": 2, "pad_to": 128}}


def plant_bf16_state():
    """The program's stored KDA state through bfloat16; returns the undo."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import kda

    real_chunk, real_kernel = kda.kda_chunk, kda._kda_step_kernel

    def chunk(*a, **k):
        o, state = real_chunk(*a, **k)
        return o, jax.lax.reduce_precision(state, 8, 7)

    def kernel(*refs, **k):
        real_kernel(*refs, **k)
        s_out = refs[-1]
        s_out[...] = s_out[...].astype(jnp.bfloat16).astype(jnp.float32)

    kda.kda_chunk, kda._kda_step_kernel = chunk, kernel

    def undo():
        kda.kda_chunk, kda._kda_step_kernel = real_chunk, real_kernel
    return undo


def main() -> int:
    import jax

    from ray_tpu._private.compile_cache import enable_compile_cache
    from ray_tpu.serve.llm import decode

    enable_compile_cache()
    spec = common.resolve_cell(common.load_manifest(), CELL)
    common.device_report(1)
    cfg = common.model_config(spec["config"])
    committed = dict(spec["config"]["reference_check"])
    seeds = [int(s) for s in os.environ.get(
        "CONTROL_SEEDS", "404,2900000011").split(",")]
    out = []
    for program in ("sound", "state_bf16"):
        undo = plant_bf16_state() if program == "state_bf16" else None
        decode._jit_cache.clear()
        jax.clear_caches()
        for seed in seeds:
            # one seed's weights at a time: two would not fit the chip
            params = jax.block_until_ready(
                serve_engine.make_params(spec, cfg, seed))
            engine = serve_engine.make_engine(
                spec, cfg, params, auto_step=False)
            for name, over in SETTINGS.items():
                spec["config"]["reference_check"] = {**committed, **over}
                t = time.time()
                chk = serve_engine.reference_check(engine, spec, cfg, seed)
                out.append({"seed": seed, "program": program,
                            "setting": name, **chk,
                            "seconds": round(time.time() - t, 1)})
                print("CONTROL", json.dumps(out[-1]), flush=True)
            engine.shutdown()
            del engine, params
            gc.collect()
        if undo:
            undo()
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ling_state_control.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
