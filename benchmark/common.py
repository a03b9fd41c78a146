"""What ``run.py`` and ``sweep.py`` share: the manifest, files found by
name, the model configuration, the device report and the rehearsal hook.

Nothing here is specific to one cell, one configuration, one traffic mix or
one metric: those are data files and small modules of their own, found by
the names ``BENCHMARK.json`` gives, so that a later PR adds them without
editing a file that is there.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# The one explicit test hook: every cell end to end on the CPU at the tiny
# sizes its data files give under "rehearsal". A rehearsal's last line
# names the CPU, never a device it did not run on.
REHEARSAL_ENV = "BENCHMARK_REHEARSAL"


def rehearsal() -> bool:
    return os.environ.get(REHEARSAL_ENV) == "1"


def say(*parts) -> None:
    """An earlier line of stdout: information, never the result."""
    print("[benchmark]", *parts, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def merged(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on top, group by group."""
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merged(out[k], v)
        else:
            out[k] = v
    return out


def resolve_cell(manifest: dict, name: str) -> dict:
    """The cell ``name`` with its configuration and traffic files read:
    ``{"cell", "config", "traffic", "end_to_end", "per_layer"}``. In a
    rehearsal each file's ``rehearsal`` group is laid over it."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(
            f"no workload {name!r} in BENCHMARK.json; it has {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    traffic = load_json(
        os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    if rehearsal():
        config = merged(config, config.get("rehearsal", {}))
        traffic = merged(traffic, traffic.get("rehearsal", {}))

    def mine(metric: dict) -> bool:
        return "workloads" not in metric or name in metric["workloads"]

    return {
        "cell": cell, "config": config, "traffic": traffic,
        "end_to_end": [m for m in manifest["end_to_end"] if mine(m)],
        "per_layer": [m for m in manifest["per_layer"] if mine(m)],
    }


def load_named(group: str, name: str):
    """Import ``benchmark/<group>/<name>.py`` by the name the data gives.
    A metric's name may hold dots, so the file is loaded by its path."""
    path = os.path.join(HERE, group, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(
            f"benchmark/{group}/{name}.py does not exist: add the module "
            f"that the data names")
    mod_name = f"benchmark.{group}.{name.replace('.', '_')}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_layer_metric(name: str):
    """A per-layer metric's reader, ``layer_metrics/<name>.py``. A quantity
    split by what it moves (``decode_step_ms.sat``, ``decode_step_ms.steady``)
    is read by one reader under the name before the last dot, unless a
    variant has a file of its own."""
    base = name.rsplit(".", 1)[0]
    full = os.path.join(HERE, "layer_metrics", name + ".py")
    return load_named("layer_metrics",
                      name if os.path.isfile(full) else base)


def model_config(config: dict, overrides: dict | None = None):
    """The program's dataclass for the configuration's ``family`` (named by
    ``reference/<family>.py``), built from the file's ``keys`` (and a job's
    ``model_overrides``). A key called ``dtype`` names a jax.numpy type."""
    import jax.numpy as jnp

    keys = dict(config["keys"], **(overrides or {}))
    if "dtype" in keys:
        keys["dtype"] = getattr(jnp, keys["dtype"])
    cls = load_named("reference", config["family"]).config_class()
    return cls(**keys)


def peaks_for(device_kind: str) -> dict:
    """Published peaks of the device; one that is not listed is an error."""
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    kind = device_kind.lower()
    for entry in table:
        if any(s in kind for s in entry["device_kind_contains"]):
            return entry
    raise SystemExit(
        f"no published peak for device kind {device_kind!r} in "
        f"benchmark/peaks.json: add it with its source")


def device_report(chips: int) -> dict:
    """The device as JAX reports it. Fails (SystemExit) when it is not the
    accelerator the cell asks for; a rehearsal runs on the CPU and says so."""
    import jax

    devs = jax.devices()
    report = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if rehearsal():
        if report["platform"] != "cpu":
            raise SystemExit(
                f"a rehearsal runs on the CPU, JAX found {report}")
        return report
    if report["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX found {report}")
    if report["count"] < chips:
        raise SystemExit(
            f"the cell asks for {chips} chips, JAX found {report}")
    peaks_for(report["kind"])
    return report


def memory_peak_bytes(stats: dict) -> int:
    """Peak bytes of one chip from its ``memory_stats()``: the peak of live
    arrays (``peak_bytes_in_use``) plus the peak of what running programs
    reserved for their temporaries (``peak_bytes_reserved``). On the TPU
    the first leaves the second out (probed on the chip, PR 23: a program
    with 3.22 GB of temporaries over a 1.07 GB argument left
    ``peak_bytes_in_use`` at 1.08 GB and ``peak_bytes_reserved`` at
    3.22 GB), so alone it would call a train step that fills the chip a
    1.7 GB job. The two peaks need not fall together, so the sum is an
    upper bound of the true peak. 0 where the backend keeps no count."""
    return int(stats.get("peak_bytes_in_use", 0)) + int(
        stats.get("peak_bytes_reserved", 0))


def fullest_chip_memory_stats() -> dict:
    """``memory_stats()`` of the local chip with the highest peak."""
    import jax

    best: dict = {}
    for d in jax.local_devices():
        stats = dict(d.memory_stats() or {})
        if not best or memory_peak_bytes(stats) > memory_peak_bytes(best):
            best = stats
    return best


class Tracing:
    """The profiler over a slice of the window: python tracer off (it is
    the costly one), and the slice marked on the profiler's own clock so
    that ``trace_reduce`` clips device events to it."""

    def __init__(self, trace_dir: str):
        self.trace_dir = trace_dir
        self.t0 = None

    def start(self) -> None:
        import jax

        from benchmark import trace_reduce

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(trace_reduce.MARK_OPEN):
            pass
        self.t0 = time.perf_counter()

    def stop(self) -> dict:
        import jax

        from benchmark import trace_reduce

        traced_s = time.perf_counter() - self.t0
        with jax.profiler.TraceAnnotation(trace_reduce.MARK_CLOSE):
            pass
        jax.profiler.stop_trace()
        return {"dir": self.trace_dir, "traced_s": traced_s,
                "with_stop_s": time.perf_counter() - self.t0}
