"""Several arrival rates in ONE process, for DEFINING a cell's fixed rate.

    python3 benchmark/sweep.py --workload <open-loop cell> --rates 6,8,10 \\
        --seconds 20 [--seed 1]

Not the benchmark's command and not run by any check: the rate a cell runs
at is a number in its traffic file, and this is the tool that was used,
once, to choose it (PERF.md records the points). One set-up (weights,
warm-up, reference check), then one serving-mode engine and one window per
rate. At a sustainable rate the waiting queue does not grow through the
window and the generator's lateness stays small against TTFT.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import common  # noqa: E402
from benchmark.common import say  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="requests/s, comma-separated")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    spec = common.resolve_cell(common.load_manifest(), args.workload)
    if spec["traffic"]["arrivals"]["mode"] != "open":
        raise SystemExit("a sweep is over the rate of an open-loop cell")
    runner = common.load_named("runners", spec["traffic"]["runner"])
    up = runner.set_up(spec, args.seed)
    cfg = up["cfg"]
    points = []
    for rate in (float(r) for r in args.rates.split(",")):
        engine = runner.make_engine(spec, cfg, up["params"], auto_step=True)
        raw = runner.measure(engine, spec, cfg, args.seed, args.seconds,
                             None, T_PROCESS_START, rate_per_s=rate)
        s = runner.summarise(raw)
        point = dict(rate_per_s=rate, **s["values"], **s["info"])
        point.pop("setup_s", None)
        point.pop("compile_cache", None)
        say(f"rate {rate}: {json.dumps(point)}")
        points.append(point)
        del engine, raw
        gc.collect()
    print(json.dumps({"device": up["device"], "reference_check": up["check"],
                      "seconds": args.seconds, "points": points}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
