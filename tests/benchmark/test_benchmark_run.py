"""``run.py`` as the driver runs it: no accelerator means no result; the
rehearsal hook runs a cell end to end on the CPU and names the CPU; a cell
and a per-layer metric are added as new files and new manifest entries,
with no edit to a file that is there."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run(args, env_extra, cwd=ROOT, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("BENCH_RUN", None)
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def result_line(stdout):
    lines = [ln for ln in stdout.strip().splitlines() if ln.strip()]
    return json.loads(lines[-1]) if lines and lines[-1].startswith("{") \
        else None


@pytest.mark.timeout(120)
def test_no_tpu_and_no_rehearsal_hook_is_a_failed_run():
    out = run(["benchmark/run.py", "--workload", "mistral7b-chat-saturated",
               "--seed", "1", "--seconds", "1", "--trace", "0"],
              {"BENCHMARK_REHEARSAL": "0"}, timeout=100)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert result_line(out.stdout) is None
    assert '"correct"' not in out.stdout


@pytest.mark.timeout(120)
def test_an_unknown_workload_is_a_failed_run():
    out = run(["benchmark/run.py", "--workload", "no-such-cell", "--seed",
               "1", "--seconds", "1", "--trace", "0"],
              {"BENCHMARK_REHEARSAL": "1"}, timeout=100)
    assert out.returncode != 0 and result_line(out.stdout) is None


@pytest.mark.timeout(300)
def test_rehearsal_runs_a_serving_cell_end_to_end_on_the_cpu():
    # the one tiny-engine run of tier-1: a large seed, a traced run
    out = run(["benchmark/run.py", "--workload", "mistral7b-chat-saturated",
               "--seed", str(2**31 + 12345), "--seconds", "2", "--trace",
               "1"], {"BENCHMARK_REHEARSAL": "1"})
    assert out.returncode == 0, out.stderr[-3000:]
    assert "REHEARSAL" in out.stdout
    line = result_line(out.stdout)
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    # a rehearsal never names a device it did not run on
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    # counters are readable on the CPU; device-only metrics are left out
    assert line["metrics"]["decode_batch_mean"]["value"] > 0
    assert "hbm_peak_gb.serve" not in line["metrics"]
    assert "decode_step_ms.sat" not in line["metrics"]
    assert len(line["breakdown"]["device_ops"]) <= 10


DUMMY_METRIC = '''"""A dummy per-layer metric: requests seen."""


def read(ctx):
    return len(ctx["records"])
'''

PROBE = '''
import json, sys
sys.path.insert(0, ".")
from benchmark import common, run
spec = common.resolve_cell(common.load_manifest(), "dummy-cell")
gen = common.load_named("generators", spec["traffic"]["generator"])
sched = gen.build(spec["traffic"], 5, 512)
ctx = {"records": [sched.request(i) for i in range(3)]}
cfg = common.model_config(spec["config"])
print(json.dumps({"metrics": run.read_layer_metrics(spec, ctx),
                  "clients": spec["traffic"]["arrivals"]["clients"],
                  "n_layer": cfg.n_layer,
                  "e2e": [m["name"] for m in spec["end_to_end"]]}))
'''


@pytest.mark.timeout(120)
def test_a_cell_and_a_layer_metric_are_added_as_new_files(tmp_path):
    """In a temporary copy: a new ``workloads`` entry with a new traffic
    file, and a new per-layer metric with a module of its own. No file
    that was there is edited except the manifest, which gains entries."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*")
              if p.is_file()}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(ROOT, "benchmark/traffic/chat-closed.json")) as f:
        traffic = json.load(f)
    traffic["name"] = "dummy-closed"
    traffic["rehearsal"]["arrivals"]["clients"] = 3
    (tmp_path / "benchmark/traffic/dummy-closed.json").write_text(
        json.dumps(traffic))
    (tmp_path / "benchmark/layer_metrics/dummy_requests.py").write_text(
        DUMMY_METRIC)
    manifest["workloads"].append({
        "name": "dummy-cell", "config": "gpt2-small",
        "traffic": "dummy-closed", "chips": 1, "why": "a test's dummy"})
    for m in manifest["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("dummy-cell")
    manifest["per_layer"].append({
        "name": "dummy_requests", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "serve_tokens_per_s", "workloads": ["dummy-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    out = run(["-c", PROBE], {"BENCHMARK_REHEARSAL": "1",
                              "PYTHONPATH": ROOT}, cwd=str(tmp_path),
              timeout=100)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["metrics"] == {
        "dummy_requests": {"value": 3.0, "unit": "requests"}}
    assert got["clients"] == 3 and got["n_layer"] == 2
    assert got["e2e"] == ["setup_s", "serve_tokens_per_s"]
    after = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert {p: b for p, b in after.items() if p in before} == before
    assert len(after) == len(before) + 2
