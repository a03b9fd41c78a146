"""THE command: one run of one cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything specific to a cell is data, found by name: the cell in
``BENCHMARK.json``; its configuration in ``benchmark/configs/``; its traffic
mix or training job in ``benchmark/traffic/``, which names its runner
(``benchmark/runners/``) and generator (``benchmark/generators/``); the
configuration's family names its plain reference
(``benchmark/reference/``); each per-layer metric has a reader of its own
(``benchmark/layer_metrics/<name>.py``, ``read(ctx) -> number | None``).
A name that resolves to no file is an error, said loudly.

The last line of stdout is the result: one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and, with
``--trace 1``, ``breakdown``). With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics. No
accelerator, or fewer chips than the cell asks for: a nonzero exit and no
result. ``BENCHMARK_REHEARSAL=1`` runs the cell end to end on the CPU at
the tiny sizes its data files give; its result names the CPU.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import common  # noqa: E402
from benchmark.common import say  # noqa: E402


def read_layer_metrics(spec: dict, ctx: dict) -> dict:
    """Each per-layer metric of the cell from its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in spec["per_layer"]:
        value = common.load_layer_metric(m["name"]).read(ctx)
        if value is None:
            say(f"layer metric {m['name']}: nothing to read, left out")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import ray_tpu  # noqa: F401 — fails here in a directory without the repo

    spec = common.resolve_cell(common.load_manifest(), args.workload)
    args.out_dir = os.path.join(common.ROOT, ".benchmark_out", args.workload)
    os.makedirs(args.out_dir, exist_ok=True)
    args.trace_dir = None
    if args.trace:
        args.trace_dir = os.path.join(args.out_dir, "trace")
        shutil.rmtree(args.trace_dir, ignore_errors=True)
    if common.rehearsal():
        say("REHEARSAL: tiny sizes on the CPU; no number below is a "
            "device's")
    runner = common.load_named("runners", spec["traffic"]["runner"])
    result = runner.run(spec, args, T_PROCESS_START)
    ctx = result["ctx"]
    ctx["cell"] = spec["cell"]
    device = dict(result["device"],
                  memory_peak_bytes=result["memory_peak_bytes"])
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"]}
    e2e = {m["name"]: {"value": float(result["end_to_end"][m["name"]]),
                       "unit": m["unit"]} for m in spec["end_to_end"]}
    say(f"end to end: {json.dumps(e2e)}")
    if args.trace:
        from benchmark import trace_reduce

        if ctx.get("trace") is None and ctx.get("trace_run"):
            path = trace_reduce.find_xplane(ctx["trace_run"]["dir"])
            if path:
                ctx["trace"] = trace_reduce.reduce_file(
                    path, stand_in_cpu=common.rehearsal())
        reduced = ctx.get("trace")
        if reduced is None:
            raise SystemExit("the traced run found no device in its trace")
        if not reduced["busy_s"] > 0:
            raise SystemExit("no operation ran on the device in the trace")
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        say(f"trace: {ctx['trace_run']}; busy {reduced['busy_s']:.4f}s of "
            f"{reduced['window_s']:.4f}s; programs "
            f"{json.dumps(reduced['modules'])}")
        line["metrics"] = read_layer_metrics(spec, ctx)
        line["breakdown"] = trace_reduce.breakdown(reduced)
    else:
        line["metrics"] = e2e
    line["device"] = device
    with open(os.path.join(
            args.out_dir, f"run-seed{args.seed}-trace{args.trace}.json"),
            "w") as f:
        json.dump({"result": line, "end_to_end": e2e,
                   "info": result["info"]}, f, indent=1, default=str)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
