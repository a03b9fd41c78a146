"""Serve layer: deploy, route, batch, reconcile, autoscale, HTTP
(model: reference python/ray/serve/tests — test_deploy, test_batching,
test_autoscaling_policy, test_proxy)."""
from __future__ import annotations

import json
import time
import urllib.request

import pytest

from conftest import shutdown_if_setup_fails

from ray_tpu.serve.autoscaling_policy import (
    AutoscalingDecider,
    calculate_desired_num_replicas,
)
from ray_tpu.serve.batching import pad_to_bucket
from ray_tpu.serve.config import AutoscalingConfig


# ---------- pure-policy unit tests (no cluster) ----------

def test_autoscaling_policy_math():
    cfg = AutoscalingConfig(min_replicas=1, max_replicas=10, target_ongoing_requests=2)
    # at target → no change
    assert calculate_desired_num_replicas(cfg, total_ongoing_requests=4, current_num_replicas=2) == 2
    # double the load → scale up
    assert calculate_desired_num_replicas(cfg, 8, 2) == 4
    # no load → floor at min
    assert calculate_desired_num_replicas(cfg, 0, 4) >= cfg.min_replicas
    # clamp to max
    assert calculate_desired_num_replicas(cfg, 1000, 2) == 10
    # scale from zero
    assert calculate_desired_num_replicas(cfg, 5, 0) == 3


def test_autoscaling_decider_debounce():
    cfg = AutoscalingConfig(
        min_replicas=1, max_replicas=10, target_ongoing_requests=1,
        upscale_delay_periods=2, downscale_delay_periods=3,
        downscale_smoothing_factor=1.0,
    )
    d = AutoscalingDecider(cfg)
    # first upscale signal is held back, second acts
    assert d.decide(10, 2) == 2
    assert d.decide(10, 2) > 2
    # downscale needs 3 consecutive periods
    d2 = AutoscalingDecider(cfg)
    assert d2.decide(0, 4) == 4
    assert d2.decide(0, 4) == 4
    assert d2.decide(0, 4) < 4


def test_pad_to_bucket():
    assert pad_to_bucket(1, (2, 4, 8)) == 2
    assert pad_to_bucket(3, (2, 4, 8)) == 4
    assert pad_to_bucket(9, (2, 4, 8)) == 8


# ---------- integration (one cluster for the whole module) ----------

@pytest.fixture(scope="module")
def serve_cluster():
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=6)
    with shutdown_if_setup_fails():
        serve.start(http_options={"port": 18123})
    yield ray_tpu, serve
    serve.shutdown()
    ray_tpu.shutdown()


def test_function_deployment_and_handle(serve_cluster):
    ray_tpu, serve = serve_cluster

    @serve.deployment
    def echo(payload):
        return {"echo": payload}

    handle = serve.run(echo.bind(), name="echo_app", timeout_s=180)
    assert handle.remote("hi").result(timeout=60) == {"echo": "hi"}
    serve.delete("echo_app")


def test_class_deployment_composition_and_http(serve_cluster):
    ray_tpu, serve = serve_cluster

    @serve.deployment
    class Doubler:
        def __call__(self, x):
            return x * 2

    @serve.deployment
    class Ingress:
        def __init__(self, doubler):
            self.doubler = doubler

        def __call__(self, payload):
            return self.doubler.remote(payload["x"]).result(timeout=60) + 1

    app = Ingress.bind(Doubler.bind())
    handle = serve.run(app, name="compose", route_prefix="/compose", timeout_s=240)
    assert handle.remote({"x": 20}).result(timeout=60) == 41

    # HTTP path through the aiohttp proxy
    req = urllib.request.Request(
        "http://127.0.0.1:18123/compose",
        data=json.dumps({"x": 5}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=60) as r:
        body = json.load(r)
    assert body["result"] == 11
    serve.delete("compose")


def test_batched_method(serve_cluster):
    ray_tpu, serve = serve_cluster

    @serve.deployment
    class Batcher:
        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.2)
        def __call__(self, items):
            assert isinstance(items, list)
            return [{"n": x, "batch_size": len(items)} for x in items]

    handle = serve.run(Batcher.bind(), name="batch_app", timeout_s=180)
    responses = [handle.remote(i) for i in range(4)]
    results = [r.result(timeout=60) for r in responses]
    assert [r["n"] for r in results] == [0, 1, 2, 3]
    # at least some calls must have been coalesced into one model call
    assert max(r["batch_size"] for r in results) >= 2
    serve.delete("batch_app")


def test_replica_death_reconciled(serve_cluster):
    ray_tpu, serve = serve_cluster

    @serve.deployment
    class Fragile:
        def pid(self):
            import os

            return os.getpid()

        def die(self):
            import os

            os._exit(1)

    handle = serve.run(Fragile.bind(), name="fragile", timeout_s=180)
    pid1 = handle.pid.remote().result(timeout=60)
    try:
        handle.die.remote().result(timeout=30)
    except Exception:
        pass  # the dying call may surface an actor-death error
    # reconciler must start a fresh replica; new calls succeed
    deadline = time.monotonic() + 120
    pid2 = None
    while time.monotonic() < deadline:
        try:
            pid2 = handle.pid.remote().result(timeout=30)
            break
        except Exception:
            time.sleep(0.5)
    assert pid2 is not None and pid2 != pid1
    serve.delete("fragile")


def test_failing_deployment_marked_unhealthy(serve_cluster):
    ray_tpu, serve = serve_cluster

    @serve.deployment
    class Broken:
        def __init__(self):
            raise RuntimeError("boom at startup")

        def __call__(self, _):
            return None

    with pytest.raises((RuntimeError, TimeoutError)) as ei:
        serve.run(Broken.bind(), name="broken", timeout_s=120)
    assert "died before becoming ready" in str(ei.value) or "unhealthy" in str(
        ei.value
    ).lower()
    serve.delete("broken")


def test_redeploy_replaces_replicas(serve_cluster):
    ray_tpu, serve = serve_cluster

    def make(version):
        @serve.deployment(name="Versioned")
        class Versioned:
            def __call__(self, _):
                return version

        return Versioned

    h1 = serve.run(make(1).bind(), name="redeploy", timeout_s=180)
    assert h1.remote(None).result(timeout=60) == 1
    h2 = serve.run(make(2).bind(), name="redeploy", timeout_s=180)
    assert h2.remote(None).result(timeout=60) == 2
    # old replica must be gone: exactly one RUNNING replica serving v2
    st = serve.status()
    assert st["redeploy"]["Versioned"]["running_replicas"] == 1
    serve.delete("redeploy")


def test_status_and_multi_replica(serve_cluster):
    ray_tpu, serve = serve_cluster

    @serve.deployment(num_replicas=2)
    class Who:
        def __call__(self, _):
            import os

            return os.getpid()

    handle = serve.run(Who.bind(), name="who", timeout_s=240)
    st = serve.status()
    assert st["who"]["Who"]["status"] == "HEALTHY"
    assert st["who"]["Who"]["running_replicas"] == 2
    pids = {handle.remote(None).result(timeout=60) for _ in range(12)}
    assert len(pids) >= 2  # power-of-two routing spreads load
    serve.delete("who")
    assert "who" not in serve.status()


def test_batching_is_replica_side_cross_caller(serve_cluster):
    """Requests from DIFFERENT caller processes (driver handle + HTTP proxy
    actor) coalesce into ONE padded batch — the queue lives in the replica
    (reference: serve/batching.py:337), not per-handle."""
    import threading
    import urllib.request

    ray_tpu, serve = serve_cluster

    @serve.deployment
    class B:
        @serve.batch(max_batch_size=8, batch_wait_timeout_s=3.0,
                     size_buckets=(4, 8))
        def __call__(self, items):
            # padded to a bucket: items includes None fill
            n_real = sum(1 for i in items if i is not None)
            return [{"batch": n_real, "padded": len(items)} for i in items]

    handle = serve.run(B.bind(), name="xbatch", route_prefix="/xbatch",
                       timeout_s=240)
    out_http = {}

    def via_http():
        import json

        req = urllib.request.Request(
            "http://127.0.0.1:18123/xbatch", data=json.dumps(7).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as r:
            out_http.update(json.load(r)["result"])

    t = threading.Thread(target=via_http)
    t.start()
    time.sleep(0.2)  # both requests inside the same generous batch window
    out_handle = handle.remote(3).result(timeout=120)
    t.join(timeout=120)
    # the two callers (proxy actor process + this driver process) shared one
    # model call, padded to the 4-bucket
    assert out_handle["batch"] == 2 and out_http["batch"] == 2, (
        out_handle, out_http,
    )
    assert out_handle["padded"] == 4
    serve.delete("xbatch")
