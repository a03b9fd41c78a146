"""The Mamba-2 mixer's two forms timed alone on the chip, at the shapes of
the Falcon-H1 cell (docs/MICROBENCHMARKS.md, PERF.md PR 58):

    chiprun -- python3 ray_tpu/benchmarks/ssd_time.py [part,...]

A part is

- ``step``: the kernel ``ssd_step`` (ops/ssd.py ``ssd_step_pallas``) over the
  slots' array ``[5, 97, 32, 128, 256]`` float32 at ROWS rows (96: the
  cell's decode step; ``ROWS=16,96``), the state aliased in and out, at
  HEADS heads a block (``HEADS=8,16``; 8: ``ops.ssd.STEP_HEADS``);
- ``step_xla``: XLA's form of the same step (gather, update, scatter);
- ``chunk``: ``ssd_chunk`` over one row of TOKENS tokens (1,024: the cell's
  prefill chunk; ``TOKENS=1024,2048``) from a carried state.

One JSON line a (part, size): microseconds a call (the host's clock around
ONE program that makes REPS calls in a row, each fed the state the one
before left, so that the host's ~240 us a dispatch is not in it), and the
call against its roofline: ``step`` GB/s over ``ops.ssd.step_bytes`` and the
share of 819 GB/s, ``chunk`` TFLOP/s over ``ops.ssd.chunk_flops`` and the
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

from ray_tpu.ops import ssd  # noqa: E402

device = jax.devices()[0]
if device.platform != "tpu" and not rehearse:
    sys.exit(f"ssd_time: {device.platform} is no TPU: a time from it would "
             "mean nothing (REHEARSE=1 checks the script alone)")

parts = (sys.argv[1] if len(sys.argv) > 1
         else "step,step_xla,chunk").split(",")
LAYERS, SLOTS, H, P, N, G = ((2, 5, 4, 16, 32, 2) if rehearse
                             else (5, 97, 32, 128, 256, 2))
env = lambda name, tiny, real: [int(n) for n in os.environ.get(  # noqa: E731
    name, tiny if rehearse else real).split(",")]
ROWS = env("ROWS", "4", "96")
TOKENS = env("TOKENS", "48", "1024")
HEADS = env("HEADS", "2", str(ssd.STEP_HEADS))
REPS = 1 if rehearse else 20
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
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (*lead, H, P), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (*lead, H)) - 4.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.7))
    Bm = jax.random.normal(ks[3], (*lead, G, N), jnp.bfloat16)
    Cm = jax.random.normal(ks[4], (*lead, G, N), jnp.bfloat16)
    return x, dt, A, Bm, Cm, jnp.ones((H,), jnp.float32)


key = jax.random.PRNGKey(0)
for part in parts:
    sizes = [(r, h) for r in ROWS
             for h in (HEADS if part == "step" else HEADS[:1])] \
        if part.startswith("step") else [(t, 0) for t in TOKENS]
    for size, heads in sizes:
        line = {"part": part, "size": size, "device_kind": device.device_kind,
                "heads": H, "head_dim": P, "d_state": N, "groups": G}
        try:
            if part.startswith("step"):
                B = size
                x, dt, A, Bm, Cm, D = inputs(key, B)
                slots = (jnp.arange(B, dtype=jnp.int32) % (SLOTS - 1)) + 1
                states = jnp.zeros((LAYERS, SLOTS, H, P, N), jnp.float32)

                def loop(states, x, dt, A, Bm, Cm, D, slots):
                    def body(i, carry):
                        states, bump = carry
                        layer = i % LAYERS
                        if part == "step":
                            y, states = ssd.ssd_step_pallas(
                                x + bump, dt, A, Bm, Cm, D, states, layer,
                                slots)
                        else:
                            y, after = ssd.ssd_step(
                                x + bump, dt, A, Bm, Cm, D,
                                states[layer, slots])
                            states = states.at[layer, slots].set(after)
                        # always 0, and depends on the call's output
                        return states, jnp.where(
                            y[0, 0, 0] > 3e38, 1, 0).astype(x.dtype)
                    return jax.lax.fori_loop(
                        0, REPS, body, (states, jnp.zeros((), x.dtype)))[0]

                jax.clear_caches()
                with mock.patch.object(ssd, "STEP_HEADS", heads or
                                       ssd.STEP_HEADS):
                    t = timed(jax.jit(loop, donate_argnums=0), states, x, dt,
                              A, Bm, Cm, D, slots)
                nbytes = ssd.step_bytes(B, H, P, N, G)
                line.update(mb=nbytes / 1e6, heads_a_block=heads or None)
                if not rehearse:
                    line.update(call_us=t * 1e6, gb_per_s=nbytes / t / 1e9,
                                hbm_pct=nbytes / t / 1e9 / HBM_GB_S * 100)
            elif part == "chunk":
                S = size
                x, dt, A, Bm, Cm, D = inputs(key, 1, S)
                valid = jnp.ones((1, S), bool)
                state = jnp.zeros((1, H, P, N), jnp.float32)

                def loop(state, x, dt, A, Bm, Cm, D):
                    def body(_, state):
                        y, state = ssd.ssd_chunk(x, dt, A, Bm, Cm, D, state,
                                                 valid)
                        return state + jnp.where(
                            y[0, 0, 0, 0] > 3e38, 1.0, 0.0)
                    return jax.lax.fori_loop(0, REPS, body, state)

                t = timed(jax.jit(loop, donate_argnums=0), state, x, dt, A,
                          Bm, Cm, D)
                flops = ssd.chunk_flops(S, H, P, N, G)
                line["gflop"] = flops / 1e9
                if not rehearse:
                    line.update(call_us=t * 1e6,
                                tflops=flops / t / 1e12,
                                mxu_pct=flops / t / 1e12 / MXU_TFLOPS * 100)
            else:
                raise ValueError(f"no part {part!r}")
        except Exception as e:  # noqa: BLE001 — a part that fails is a line
            line["error"] = f"{type(e).__name__}: {e}"[:400]
        print(json.dumps(line), flush=True)
