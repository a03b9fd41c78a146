"""Per-node Serve proxy actors: controller-managed ingress with health
states (reference: serve/_private/proxy_state.py ProxyStateManager)."""
from __future__ import annotations

import json
import time
import urllib.request

import pytest

import ray_tpu


def _http_get(host: str, port: int, path: str, timeout: float = 30.0):
    try:
        with urllib.request.urlopen(
                f"http://{host}:{port}{path}", timeout=timeout) as r:
            return json.loads(r.read())
    except urllib.error.HTTPError as e:  # error statuses carry JSON too
        return json.loads(e.read())


def _wait(cond, timeout_s: float, what: str):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        v = cond()
        if v:
            return v
        time.sleep(0.2)
    raise TimeoutError(f"timed out waiting for {what}")


def test_per_node_proxies_serve_and_survive_proxy_kill(ray_cluster):
    """Each node gets its own proxy actor; every proxy serves the app;
    killing one proxy degrades (that node only, briefly) instead of
    outaging, and the controller replaces it."""
    ray_cluster.add_node(num_cpus=2)
    time.sleep(1.2)  # heartbeat: head must see the second node

    from ray_tpu import serve

    @serve.deployment
    class Echo:
        def __call__(self, payload):
            return {"echo": payload}

    try:
        serve.start(http_options={"port": 0}, proxy_location="EveryNode")
        serve.run(Echo.bind(), name="app", route_prefix="/echo")

        addrs = _wait(
            lambda: (a := serve.proxy_addresses()) and len(a) >= 2 and a,
            60, "2 healthy per-node proxies")
        assert len(addrs) == 2, addrs
        # ports are ephemeral and distinct on one host
        ports = [tuple(v["http"]) for v in addrs.values()]
        assert len(set(ports)) == 2, ports

        # EVERY node's proxy serves the app through its own ingress
        for host, port in ports:
            out = _wait(
                lambda h=host, p=port: _maybe_echo(h, p), 30,
                f"route sync on {host}:{port}")
            assert out == {"result": {"echo": {"x": 1}}}, out

        # kill one proxy: the OTHER keeps serving immediately (degrade,
        # not outage), and the controller brings a replacement up
        victim_nid = sorted(addrs)[0]
        victim = ray_tpu.get_actor(f"RT_SERVE_PROXY:{victim_nid[:12]}")
        survivor_host, survivor_port = tuple(addrs[sorted(addrs)[1]]["http"])
        ray_tpu.kill(victim)
        out = _http_get(survivor_host, survivor_port, "/echo")
        assert "result" in out

        def replaced():
            a = serve.proxy_addresses(timeout_s=1)
            return (victim_nid in a
                    and tuple(a[victim_nid]["http"]) != tuple(
                        addrs[victim_nid]["http"]) and a)

        new_addrs = _wait(replaced, 60, "controller to replace dead proxy")
        nh, np_ = tuple(new_addrs[victim_nid]["http"])
        out = _wait(lambda: _maybe_echo(nh, np_), 30, "replacement route sync")
        assert out == {"result": {"echo": {"x": 1}}}
    finally:
        serve.shutdown()


def _maybe_echo(host, port):
    try:
        req = urllib.request.Request(
            f"http://{host}:{port}/echo", data=json.dumps({"x": 1}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as r:
            out = json.loads(r.read())
        return out if "result" in out else None
    except Exception:
        return None


def test_request_timeout_is_configurable(ray_start):
    """The 120s proxy result timeout moved into HTTPOptions: a short
    request_timeout_s must cut off a slow deployment."""
    from ray_tpu import serve

    @serve.deployment
    class Slow:
        def __call__(self, payload):
            time.sleep(5.0)
            return "done"

    try:
        serve.start(http_options={"port": 0, "request_timeout_s": 1.0})
        serve.run(Slow.bind(), name="slow", route_prefix="/slow")
        from ray_tpu.serve import api as serve_api

        port = serve_api._proxy.port
        t0 = time.monotonic()
        out = _http_get("127.0.0.1", port, "/slow")
        assert "error" in out, out
        assert time.monotonic() - t0 < 4.0  # cut off well before the 5s
    finally:
        serve.shutdown()
