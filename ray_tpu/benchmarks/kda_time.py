"""The KDA layer's two forms timed alone on the chip, at the shapes of the
Ling-3.0-flash cell (docs/MICROBENCHMARKS.md, PERF.md PR 52):

    chiprun -- python3 ray_tpu/benchmarks/kda_time.py [part,...]

A part is

- ``step``: the kernel ``kda_step`` (ops/kda.py ``kda_step_pallas``) over
  the slots' array ``[6, 129, 32, 128, 128]`` float32 at ROWS rows (128:
  the cell's decode step; ``ROWS=16,128``), the state aliased in and out;
- ``step_xla``: XLA's form of the same step (gather, update, scatter);
- ``chunk``: ``kda_chunk`` over one row of TOKENS tokens (2,048: the cell's
  prefill chunk; ``TOKENS=512,2048``) from a carried state;
- ``chunk_bf16``: the same with the products against the state at the
  operands' dtype (one pass of the matrix unit where ``chunk`` takes six):
  what the float32 state products cost.

One JSON line a (part, size): microseconds a call (the host's clock around
ONE program that makes REPS calls in a row, each fed the state the one
before left, so that the host's ~240 us a dispatch is not in it), and the
call against its roofline: ``step`` GB/s over ``ops.kda.step_bytes`` and the
share of 819 GB/s, ``chunk`` TFLOP/s over ``ops.kda.chunk_flops`` and the
share of 197. Off a TPU the script refuses; ``REHEARSE=1`` runs tiny shapes
(the kernel through the Pallas interpreter) to show that the script runs,
and prints NO time."""
import json
import os
import statistics
import sys
import time
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
rehearse = bool(os.environ.get("REHEARSE"))
if rehearse:
    os.environ["RAY_TPU_PALLAS_INTERPRET"] = "1"
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import kda  # noqa: E402

device = jax.devices()[0]
if device.platform != "tpu" and not rehearse:
    sys.exit(f"kda_time: {device.platform} is no TPU: a time from it would "
             "mean nothing (REHEARSE=1 checks the script alone)")

parts = (sys.argv[1] if len(sys.argv) > 1
         else "step,step_xla,chunk,chunk_bf16").split(",")
LAYERS, SLOTS, H, K = (2, 5, 4, 16) if rehearse else (6, 129, 32, 128)
ROWS = [int(n) for n in os.environ.get(
    "ROWS", "4" if rehearse else "128").split(",")]
TOKENS = [int(n) for n in os.environ.get(
    "TOKENS", "48" if rehearse else "2048").split(",")]
REPS = 1 if rehearse else 16
HBM_GB_S, MXU_TFLOPS = 819.0, 197.0


def timed(loop, *args):
    """Seconds a call of the REPS inside ``loop`` (jitted; its first
    argument donated and handed back)."""
    out = jax.block_until_ready(loop(*args))
    times = []
    for _ in range(1 if rehearse else 5):
        t0 = time.perf_counter()
        out = jax.block_until_ready(loop(out, *args[1:]))
        times.append((time.perf_counter() - t0) / REPS)
    return statistics.median(times)


def inputs(key, *lead):
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (*lead, H, K), jnp.bfloat16) * K ** -0.5
    k = jax.random.normal(ks[1], (*lead, H, K), jnp.float32)
    k = (k / jnp.linalg.norm(k, axis=-1, keepdims=True)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (*lead, H, K), jnp.bfloat16)
    log_a = -5.0 * jax.nn.sigmoid(
        jax.random.normal(ks[3], (*lead, H, K), jnp.float32) - 4.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (*lead, H), jnp.float32))
    return q, k, v, log_a, beta


lines = []
key = jax.random.PRNGKey(0)
for part in parts:
    for size in (ROWS if part.startswith("step") else TOKENS):
        line = {"part": part, "size": size, "device_kind": device.device_kind,
                "heads": H, "head_dim": K}
        try:
            if part.startswith("step"):
                B = size
                q, k, v, log_a, beta = inputs(key, B)
                slots = (jnp.arange(B, dtype=jnp.int32) % (SLOTS - 1)) + 1
                states = jnp.zeros((LAYERS, SLOTS, H, K, K), jnp.float32)

                def loop(states, q, k, v, log_a, beta, slots):
                    def body(i, carry):
                        states, bump = carry
                        layer = i % LAYERS
                        if part == "step":
                            o, states = kda.kda_step_pallas(
                                q + bump, k, v, log_a, beta, states, layer,
                                slots)
                        else:
                            o, after = kda.kda_step(
                                q + bump, k, v, log_a, beta,
                                states[layer, slots])
                            states = states.at[layer, slots].set(after)
                        # always 0, and depends on the call's output
                        return states, jnp.where(
                            o[0, 0, 0] > 3e38, 1, 0).astype(q.dtype)
                    return jax.lax.fori_loop(
                        0, REPS, body, (states, jnp.zeros((), q.dtype)))[0]

                t = timed(jax.jit(loop, donate_argnums=0), states, q, k, v,
                          log_a, beta, slots)
                nbytes = kda.step_bytes(B, H, K, K)
                line["mb"] = nbytes / 1e6
                if not rehearse:
                    line.update(call_us=t * 1e6, gb_per_s=nbytes / t / 1e9,
                                hbm_pct=nbytes / t / 1e9 / HBM_GB_S * 100)
            elif part.startswith("chunk"):
                S = size
                q, k, v, log_a, beta = inputs(key, 1, S)
                valid = jnp.ones((1, S), bool)
                state = jnp.zeros((1, H, K, K), jnp.float32)

                def loop(state, q, k, v, log_a, beta):
                    def body(_, state):
                        o, state = kda.kda_chunk(q, k, v, log_a, beta, state,
                                                 valid)
                        return state + jnp.where(
                            o[0, 0, 0, 0] > 3e38, 1.0, 0.0)
                    return jax.lax.fori_loop(0, REPS, body, state)

                hi = (jax.lax.Precision.DEFAULT if part == "chunk_bf16"
                      else kda._HI)
                jax.clear_caches()
                with mock.patch.object(kda, "_HI", hi):
                    t = timed(jax.jit(loop, donate_argnums=0), state, q, k,
                              v, log_a, beta)
                flops = kda.chunk_flops(S, H, K, K)
                line["gflop"] = flops / 1e9
                if not rehearse:
                    line.update(call_us=t * 1e6,
                                tflops=flops / t / 1e12,
                                mxu_pct=flops / t / 1e12 / MXU_TFLOPS * 100)
            else:
                raise ValueError(f"no part {part!r}")
        except Exception as e:  # noqa: BLE001 — a part that fails is a line
            line["error"] = str(e)[-400:]
        print(json.dumps(line), flush=True)
        lines.append(line)
os.makedirs("chiprun_out", exist_ok=True)
with open("chiprun_out/kda_time.json", "a") as f:
    for line in lines:
        f.write(json.dumps(line) + "\n")
