"""What a serving cell's warm `setup_s` costs in Python: the benchmark's own
warm-up, with every step program TRACED and LOWERED and none compiled or run.

    python3 warmup_trace_time.py <workload of BENCHMARK.json> [<checkout>]

A warm `setup_s` is mostly this (PERF.md, PR 27 and 28), the persistent cache
plays no part in it, and a run takes under a minute, so two checkouts can be
compared on the chip's host for a chip-minute each (`chiprun -- python3
warmup_trace_time.py gpt2-serve-chat-saturated .scratch/parent`). The engine
is the cell's own, hand-stepped by `benchmark/runners/serve_engine.warm_up`;
each step's outputs are ones of the program's result shapes. On a host with a
TPU the programs are lowered for it; elsewhere for a described `v5e:2x2`
(tests/test_chip_compile.py), with JAX held to the CPU. One line comes out:
seconds of the warm-up, of tracing, of the Pallas kernels' traces within that,
of lowering; user and system seconds and minor page faults of the process. A
number from a host without the chip is not a number of the chip's host.
"""
from __future__ import annotations

import os
import resource
import sys
import time


def measure(workload: str, device=None) -> dict:
    """The warm-up of ``workload`` walked in this process, from this
    checkout (the working directory). ``device``: what to lower for; None
    takes the host's TPU, or describes a ``v5e:2x2`` where there is none.
    Leaves the process as it found it but for what JAX has cached."""
    from ray_tpu._private.node import autodetect_tpu_chips

    on_chip = device is None and autodetect_tpu_chips() > 0 and (
        os.environ.get("JAX_PLATFORMS", "") != "cpu")
    import jax
    import numpy as np
    from jax._src.pallas import pallas_call
    from jax.sharding import SingleDeviceSharding

    if on_chip:
        from ray_tpu._private.compile_cache import enable_compile_cache

        enable_compile_cache()  # weights and pools are made by small programs
        device = jax.devices()[0]
    elif device is None:
        from jax.experimental import topologies

        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_enable_compilation_cache", False)
        device = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
    one = SingleDeviceSharding(device)

    from benchmark import common
    from benchmark.runners import serve_engine
    from ray_tpu.serve.llm import decode

    spent = {"trace": 0.0, "kernel": 0.0, "lower": 0.0, "programs": 0}
    kernel_trace = pallas_call._trace_kernel_to_jaxpr

    def timed_kernel_trace(*args, **kwargs):
        t = time.perf_counter()
        try:
            return kernel_trace(*args, **kwargs)
        finally:
            spent["kernel"] += time.perf_counter() - t

    class LoweredOnly:
        """A jitted step that lowers each new signature and runs nothing."""

        def __init__(self, jitted):
            self.jitted, self.results = jitted, {}

        def __call__(self, *args, **kwargs):
            structs = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
                (args, kwargs))
            key = str(structs)
            if key not in self.results:
                t0 = time.perf_counter()
                traced = self.jitted.trace(*structs[0], **structs[1])
                t1 = time.perf_counter()
                self.results[key] = traced.lower().out_info
                spent["trace"] += t1 - t0
                spent["lower"] += time.perf_counter() - t1
                spent["programs"] += 1
            out, *rest = self.results[key]
            out = jax.tree.map(lambda s: np.ones(s.shape, s.dtype), out)
            if on_chip:
                out = jax.device_put(out)
            # the pools, and the state where there is one, go back as they came
            return (out, args[1], args[2],
                    *[kwargs.get("state")] * (len(rest) - 2))

    jit_named, jit_cache = decode._jit_named, dict(decode._jit_cache)
    interpret = os.environ.pop("RAY_TPU_PALLAS_INTERPRET", None)
    decode._jit_cache.clear()
    decode._jit_named = lambda *a: LoweredOnly(jit_named(*a))
    pallas_call._trace_kernel_to_jaxpr = timed_kernel_trace
    try:
        spec = common.resolve_cell(common.load_manifest(), workload)
        spec["traffic"]["engine"] = dict(
            spec["traffic"]["engine"], attention_backend="pallas")
        cfg = common.model_config(spec["config"])
        seed = 12345
        params = jax.block_until_ready(
            serve_engine.make_params(spec, cfg, seed))
        before = resource.getrusage(resource.RUSAGE_SELF)
        t = time.perf_counter()
        engine = serve_engine.make_engine(spec, cfg, params, auto_step=False)
        serve_engine.warm_up(engine, spec, cfg, seed)
        wall = time.perf_counter() - t
        after = resource.getrusage(resource.RUSAGE_SELF)
        shapes = engine.fns.num_compiled_shapes
        engine.shutdown()
    finally:
        pallas_call._trace_kernel_to_jaxpr = kernel_trace
        decode._jit_named = jit_named
        decode._jit_cache.clear()
        decode._jit_cache.update(jit_cache)
        if interpret is not None:
            os.environ["RAY_TPU_PALLAS_INTERPRET"] = interpret
    return {
        "device": device.device_kind, "on_chip": on_chip, "shapes": shapes,
        "programs": spent["programs"], "warm_up_s": wall,
        "trace_s": spent["trace"], "kernel_trace_s": spent["kernel"],
        "lower_s": spent["lower"],
        "user_s": after.ru_utime - before.ru_utime,
        "system_s": after.ru_stime - before.ru_stime,
        "minor_faults": after.ru_minflt - before.ru_minflt,
    }


def main(workload: str, root: str) -> None:
    root = os.path.abspath(root)
    sys.path.insert(0, root)
    os.chdir(root)
    m = measure(workload)
    print(
        f"{workload} at {root} for {m['device']}"
        f"{'' if m['on_chip'] else ' (described; this host has no chip)'}: "
        f"{m['shapes']} shapes, {m['programs']} programs; warm-up "
        f"{m['warm_up_s']:.2f} s = trace {m['trace_s']:.2f} (Pallas kernels "
        f"{m['kernel_trace_s']:.2f}) + lower {m['lower_s']:.2f} + other "
        f"{m['warm_up_s'] - m['trace_s'] - m['lower_s']:.2f}; user "
        f"{m['user_s']:.2f} s, system {m['system_s']:.2f} s, minor faults "
        f"{m['minor_faults']}", flush=True)
    os._exit(0)  # the runtime's threads have nothing to flush


if __name__ == "__main__":
    if len(sys.argv) < 2:
        raise SystemExit(__doc__)
    main(sys.argv[1], sys.argv[2] if len(sys.argv) > 2 else
         os.path.dirname(os.path.abspath(__file__)))
