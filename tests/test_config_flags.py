"""Every declared config flag must be READ somewhere outside config.py —
a flag table that lies is worse than a short one.
Plus behavior tests for the round-4 wired flags."""
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_no_dead_flags():
    """grep the package: each Config field name must appear in at least one
    non-config source file."""
    from dataclasses import fields

    from ray_tpu._private.config import Config

    src = {}
    for root, _dirs, files in os.walk(os.path.join(REPO, "ray_tpu")):
        if "__pycache__" in root:
            continue
        for f in files:
            if f.endswith((".py", ".cpp")):
                p = os.path.join(root, f)
                with open(p, errors="ignore") as fh:
                    src[p] = fh.read()
    config_py = os.path.join(REPO, "ray_tpu", "_private", "config.py")
    dead = []
    for f in fields(Config()):
        used = any(f.name in text for p, text in src.items() if p != config_py)
        if not used:
            dead.append(f.name)
    assert not dead, f"declared but never read outside config.py: {dead}"


def test_fake_tpu_hosts_topology():
    """config.fake_tpu_hosts presents an n-host pod slice: n extra nodes,
    each with tpu_chips_per_host_default chips, one shared ici-domain —
    and a TPU placement group lands on the slice. Subprocess: init() with
    a custom _system_config needs a fresh runtime."""
    code = """
import ray_tpu
ray_tpu.init(num_cpus=2, _system_config={
    "fake_tpu_hosts": 2, "tpu_chips_per_host_default": 4})
import time
deadline = time.monotonic() + 30
while time.monotonic() < deadline:
    nodes = [n for n in ray_tpu.nodes() if n["alive"]]
    if len(nodes) >= 3:
        break
    time.sleep(0.2)
assert len(nodes) == 3, nodes
tpu_nodes = [n for n in nodes if n["resources"].get("TPU", 0) > 0]
assert len(tpu_nodes) == 2
assert all(n["resources"]["TPU"] == 4.0 for n in tpu_nodes)
doms = {n.get("labels", {}).get("ici-domain") for n in tpu_nodes}
assert doms == {"fake-slice-0"}, doms
total = ray_tpu.cluster_resources().get("TPU", 0)
assert total == 8.0, total
pg = ray_tpu.util.placement_group([{"TPU": 4}, {"TPU": 4}],
                                  strategy="STRICT_SPREAD")
assert pg.ready(timeout=60)
print("FAKE_TOPOLOGY_OK")
ray_tpu.shutdown()
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=180,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "FAKE_TOPOLOGY_OK" in r.stdout


def test_max_actor_restarts_default_applies(ray_start):
    """An actor created WITHOUT max_restarts= picks up the cluster default
    at creation time."""
    import ray_tpu
    from ray_tpu._private.config import global_config

    @ray_tpu.remote
    class Crashy:
        def __init__(self):
            self.n = 0

        def pid(self):
            return os.getpid()

        def die(self):
            os._exit(1)

    old = global_config().max_actor_restarts_default
    global_config().max_actor_restarts_default = 1
    try:
        a = Crashy.remote()
        pid1 = ray_tpu.get(a.pid.remote(), timeout=120)
        a.die.remote()
        import time

        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            try:
                pid2 = ray_tpu.get(a.pid.remote(), timeout=10)
                if pid2 != pid1:
                    break
            except Exception:
                time.sleep(0.5)
        else:
            raise AssertionError(
                "actor with default restart budget never came back")
    finally:
        global_config().max_actor_restarts_default = old


def test_ici_bandwidth_gates_slice_affinity():
    """With ici_bandwidth_gbps below the DCN assumption, TPU bundle
    placement stops preferring a shared ici-domain."""
    from ray_tpu._private.config import global_config
    from ray_tpu._private.scheduler import schedule_bundles

    nodes = {
        b"a": {"resources": {"TPU": 4.0}, "available": {"TPU": 4.0},
               "labels": {"ici-domain": "s0"}, "alive": True},
        b"b": {"resources": {"TPU": 4.0}, "available": {"TPU": 4.0},
               "labels": {"ici-domain": "s0"}, "alive": True},
        b"c": {"resources": {"TPU": 4.0}, "available": {"TPU": 4.0},
               "labels": {"ici-domain": "s1"}, "alive": True},
    }
    bundles = [{"TPU": 4.0}, {"TPU": 4.0}]
    cfg = global_config()
    old = cfg.ici_bandwidth_gbps
    try:
        cfg.ici_bandwidth_gbps = 400.0
        placement = schedule_bundles(bundles, "SPREAD", nodes)
        doms = {nodes[nid]["labels"]["ici-domain"] for nid in placement}
        assert doms == {"s0"}, "fast ICI must keep the gang on one slice"
        cfg.ici_bandwidth_gbps = 10.0  # DCN as fast as ICI: no constraint
        placement = schedule_bundles(bundles, "SPREAD", nodes)
        assert placement is not None  # placement works, affinity-free
    finally:
        cfg.ici_bandwidth_gbps = old


def test_metrics_report_loop_publishes_node_gauges(ray_start):
    """The raylet's periodic reporter lands node gauges in the registry at
    the configured cadence."""
    import time

    pytest.importorskip("prometheus_client")
    from ray_tpu.util.metrics import collect

    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        snap = collect()
        if any(k.startswith("ray_tpu_node_resource_available") for k in snap):
            return
        time.sleep(0.5)
    raise AssertionError(
        f"node gauges never appeared; have {sorted(collect())[:10]}")
