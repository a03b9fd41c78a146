"""Control-plane fault tolerance: the checkpointed Serve controller.

Three layers, cheapest first:

- Pure codec tests: the checkpoint envelope round-trips byte-exactly,
  unknown versions and corrupt payloads are rejected loudly (recovery
  must refuse to guess — a misread roster would reap live replicas).
- In-process controller tests (fresh single-node cluster, controller
  object driven directly): recovery is idempotent run twice, an
  unknown-version checkpoint boots fresh instead of raising, and a
  checkpoint-write fault degrades to warn-and-retry with the KV blob
  always whole.
- The tier-1 chaos storyline: a real serve cluster where the controller
  is killed mid-upscale (in the replica-created-but-not-checkpointed
  window — the deterministic orphan) and again mid-drain. Streams stay
  byte-identical to an unfaulted local reference, the proxy's /healthz
  answers without a controller, the restarted controller reaps the
  orphan and converges, and the resumed drain retires its replica.
"""
from __future__ import annotations

import json
import logging
import threading
import time
import urllib.request

import pytest

from conftest import serve_http_url, shutdown_if_setup_fails

from ray_tpu._private import chaos
from ray_tpu._private.chaos import Fault, FaultPlan
from ray_tpu.serve.controller import (
    CHECKPOINT_KEY,
    CHECKPOINT_NS,
    CHECKPOINT_VERSION,
    CONTROLLER_NAME,
    ServeController,
    decode_checkpoint,
    decode_spec,
    encode_checkpoint,
    encode_spec,
)

APP = "llm-ft"
DEP = "LLMDeployment"


def _wait_for(predicate, timeout_s=60.0, interval=0.1):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


def _echo_spec(app_name: str) -> dict:
    from ray_tpu.serve.deployment import deployment

    # defined locally so cloudpickle ships the class by VALUE — replica
    # worker processes cannot import this test module by name
    class _Echo:
        def __call__(self, x):
            return x

    return deployment(_Echo).bind().build_spec(app_name)


# ---------------- checkpoint codec (no cluster) ----------------

def _sample_payload() -> tuple[dict, dict]:
    spec = _echo_spec("app")
    payload = {
        "version": CHECKPOINT_VERSION,
        "seq": 7,
        "written_at": 1234.5,
        "restarts": 1,
        "reconciler_version": 42,
        "apps": {
            "app": {
                "ingress": "_Echo",
                "route_prefix": "/echo",
                "deployments": {
                    "_Echo": {
                        "spec_blob": encode_spec(spec),
                        "target": 2,
                        "status": "HEALTHY",
                        "shed": False,
                        "signal_capable": True,
                        "drain_capable": True,
                        "batch_configs": {"__call__": {"max_batch_size": 4}},
                        "stream_methods": ["stream"],
                        "replicas": [
                            {"actor_id": "ab" * 16, "state": "RUNNING",
                             "drain_remaining_s": None},
                            {"actor_id": "cd" * 16, "state": "DRAINING",
                             "drain_remaining_s": 1.25},
                        ],
                    }
                },
            }
        },
        "proxy_cfg": [{"port": 0}, None],
    }
    return spec, payload


def test_checkpoint_round_trip_is_identical():
    spec, payload = _sample_payload()
    restored = decode_checkpoint(encode_checkpoint(payload))
    assert restored == payload
    # the one non-JSON island: the pickled spec survives base64 intact,
    # including bytes blobs, tuples, and the DeploymentConfig dataclass
    spec2 = decode_spec(
        restored["apps"]["app"]["deployments"]["_Echo"]["spec_blob"])
    assert spec2["name"] == spec["name"]
    assert spec2["callable_blob"] == spec["callable_blob"]
    assert spec2["init_args"] == spec["init_args"]
    assert spec2["config"] == spec["config"]


def test_checkpoint_unknown_version_rejected_loudly():
    blob = encode_checkpoint({"version": 99, "seq": 1, "apps": {}})
    with pytest.raises(ValueError, match="version"):
        decode_checkpoint(blob)


@pytest.mark.parametrize("blob", [
    b"\xff\x00 not json",
    b"[1, 2, 3]",                                  # not an object
    b'{"seq": 1, "apps": {}}',                     # version missing
    b'{"version": 1, "apps": {}}',                 # seq missing
    b'{"version": 1, "seq": 1}',                   # apps missing
])
def test_checkpoint_corrupt_payloads_rejected(blob):
    with pytest.raises(ValueError):
        decode_checkpoint(blob)


# ---------------- in-process controller (single-node cluster) ----------------

def _kv_checkpoint() -> dict | None:
    from ray_tpu._private.gcs import kv_get

    blob = kv_get(CHECKPOINT_KEY, ns=CHECKPOINT_NS)
    return decode_checkpoint(bytes(blob)) if blob is not None else None


def _roster(ctrl: ServeController) -> dict:
    with ctrl._lock:
        return {
            (app, dep): sorted(
                (r.actor_id.hex(), r.state) for r in ds.replicas)
            for app, a in ctrl._apps.items()
            for dep, ds in a["deployments"].items()
        }


@pytest.mark.timeout(120)
def test_checkpoint_write_fault_degrades_to_warn_and_retry(ray_start):
    ctrl = ServeController(reconcile_period_s=0.05)
    try:
        chaos.install(FaultPlan(faults=(
            Fault(point="controller.checkpoint", action="raise", times=1),
        )))
        ctrl._checkpoint("unit")  # the faulted write
        assert ctrl._ckpt_dirty, "failed write must mark dirty for retry"
        # the reconcile loop retries every pass; the fault is spent, so
        # the next attempt lands
        assert _wait_for(lambda: not ctrl._ckpt_dirty, timeout_s=15)
        ckpt = _kv_checkpoint()
        assert ckpt is not None, "retry must persist a checkpoint"
        # never half-written: the blob that landed is a complete,
        # decodable envelope
        assert ckpt["version"] == CHECKPOINT_VERSION
        assert ckpt["apps"] == {}
    finally:
        chaos.clear()
        ctrl.shutdown()


@pytest.mark.timeout(120)
def test_recovery_rejects_unknown_version_and_boots_fresh(ray_start, caplog):
    from ray_tpu._private.gcs import kv_get, kv_put

    stale = encode_checkpoint({"version": 99, "seq": 3, "apps": {}})
    kv_put(CHECKPOINT_KEY, stale, ns=CHECKPOINT_NS)
    with caplog.at_level(logging.ERROR, logger="ray_tpu.serve.controller"):
        ctrl = ServeController(reconcile_period_s=0.05)
    try:
        assert any("checkpoint rejected" in r.message for r in caplog.records)
        st = ctrl.status()["_controller"]
        assert st["restarts"] == 0 and st["recovered_at"] is None
        with ctrl._lock:
            assert ctrl._apps == {}
        # the stale blob is left for inspection, not overwritten blindly
        assert kv_get(CHECKPOINT_KEY, ns=CHECKPOINT_NS) == stale
    finally:
        ctrl.shutdown()


@pytest.mark.timeout(180)
def test_recovery_is_idempotent_run_twice(ray_start):
    app = "ft-unit"
    a = ServeController(reconcile_period_s=0.05)
    b = None
    try:
        a.deploy_application(app, [_echo_spec(app)], ingress="_Echo",
                             route_prefix=None)

        def _ckpt_running():
            ckpt = _kv_checkpoint()
            reps = (ckpt or {})["apps"].get(app, {}).get(
                "deployments", {}).get("_Echo", {}).get("replicas", [])
            return len(reps) == 1 and reps[0]["state"] == "RUNNING"

        assert _wait_for(_ckpt_running, timeout_s=90), \
            "checkpoint never recorded the RUNNING replica"
        # "crash" controller A: stop its loop without teardown (shutdown
        # would delete the checkpoint — that is the intentional path)
        a._stopped.set()

        b = ServeController(reconcile_period_s=0.05)
        st1 = b.status()
        roster1 = _roster(b)
        assert st1["_controller"]["restarts"] == 1
        assert st1["_controller"]["recovered_at"] is not None
        assert st1[app]["_Echo"]["running_replicas"] == 1
        assert len(roster1[(app, "_Echo")]) == 1

        b._recover()  # second run must converge to the same state
        st2 = b.status()
        roster2 = _roster(b)
        assert roster2 == roster1, "re-running recovery changed the roster"
        assert st2[app] == st1[app]
        assert st2["_controller"]["restarts"] == 2
        # the adopted replica was never reaped: same actor, still alive
        assert _wait_for(
            lambda: b.status()[app]["_Echo"]["running_replicas"] == 1,
            timeout_s=30)
    finally:
        a._stopped.set()
        if b is not None:
            b.shutdown()
        else:
            a.shutdown()


# ---------------- cluster chaos storyline (tier-1) ----------------

def _model_config():
    import dataclasses

    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    return dataclasses.replace(
        LlamaConfig.tiny(), dtype=jnp.float32, attention="xla")


def _engine(**kw):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    return LLMEngine(
        EngineConfig(model="llama", model_config=_model_config(), **kw),
        auto_step=False,
    )


def _stream(handle, payload):
    from ray_tpu.serve.llm import stream_tokens

    return stream_tokens(handle, payload)


def _status(ctrl) -> dict:
    import ray_tpu

    try:
        return ray_tpu.get(ctrl.status.remote(), timeout=30)
    except Exception:  # noqa: BLE001 — controller mid-restart
        return {}


def _dep(ctrl) -> dict:
    return _status(ctrl).get(APP, {}).get(DEP, {})


def _ctrl_meta(ctrl) -> dict:
    return _status(ctrl).get("_controller", {})


def _alive_replica_actors() -> int:
    import ray_tpu

    actors = ray_tpu.worker.global_worker().gcs.call("list_actors")["actors"]
    return sum(
        1 for a in actors
        if a.get("class_name") == "ReplicaActor" and a.get("state") != "DEAD"
    )


def _replica_pools_clean(handle) -> bool:
    stats = [s for s in handle.broadcast("stats") if s]
    return bool(stats) and all(
        s["running"] == 0 and s["waiting"] == 0 and s["kv_used_blocks"] == 0
        for s in stats
    )


@pytest.fixture(scope="module")
def ft_cluster():
    """One LLM app (fixed num_replicas, operator-driven scaling) under a
    chaos plan that kills the controller twice:

    - mid-upscale, in the replica-created-but-not-yet-checkpointed
      window of the SECOND replica start (the first start is the initial
      deploy) — the deterministic orphan-replica scenario;
    - mid-drain, right after the drain_start checkpoint lands in the
      restarted controller (chaos counters are per-process, so the
      spent-in-incarnation-1 kill fault does not mask this one).

    Every _recover() is stretched ~1-3 s (seeded jitter) so the tests
    can probe the data plane while the control plane is provably down.
    """
    import os

    plan = FaultPlan(seed=11, faults=(
        Fault(point="controller.kill", action="kill", after=2, times=1,
              when={"reason": "replica_starting"}),
        Fault(point="controller.kill", action="kill", times=1,
              when={"reason": "drain_start"}),
        Fault(point="controller.recover", action="delay", arg=2.0,
              times=None),
        # tagged streams are throttled ~20-60 ms/chunk so they straddle
        # the outage + the 2 s drain deadline instead of finishing early
        Fault(point="llm.token", action="delay", arg=0.04, times=None,
              when={"tag": "slowme"}),
    ))
    prev = os.environ.get(chaos.ENV_VAR)
    os.environ[chaos.ENV_VAR] = plan.to_json()
    chaos.clear()

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import EngineConfig, build_llm_app

    ray_tpu.init(num_cpus=8)
    with shutdown_if_setup_fails():
        serve.start(http_options={"port": 0})
        handle = serve.run(
            build_llm_app(
                EngineConfig(
                    model="llama", model_config=_model_config(), seed=0,
                    max_batch_size=2, max_prefill_batch=2, max_waiting=4,
                    block_size=16, num_blocks=256,
                ),
                num_replicas=1,
                graceful_shutdown_timeout_s=2.0,
            ),
            name=APP, route_prefix="/ft", timeout_s=300,
        )
        ctrl = ray_tpu.get_actor(CONTROLLER_NAME)
    yield {"handle": handle, "ctrl": ctrl, "serve": serve, "ray": ray_tpu}
    serve.shutdown()
    ray_tpu.shutdown()
    chaos.clear()
    if prev is None:
        os.environ.pop(chaos.ENV_VAR, None)
    else:
        os.environ[chaos.ENV_VAR] = prev


@pytest.mark.chaos
@pytest.mark.timeout(300)
def test_controller_killed_mid_upscale_orphan_reaped_data_plane_serves(
        ft_cluster):
    """Scale 1 -> 2; the controller dies after creating the new replica
    but before checkpointing it. The data plane keeps serving from the
    cached routing table (fresh stream byte-identical, /healthz 200),
    and the restarted controller reaps the unknowable orphan and
    converges to target 2 without leaking an actor."""
    handle, ctrl = ft_cluster["handle"], ft_cluster["ctrl"]
    ray_tpu = ft_cluster["ray"]

    ref = _engine(seed=0)
    warm = {"prompt": [3, 1, 4], "request_id": "warm-0",
            "max_new_tokens": 8, "temperature": 0.7, "seed": 21}
    outage = {"prompt": [2, 7, 1, 8], "request_id": "outage-0",
              "max_new_tokens": 10, "temperature": 0.7, "seed": 22,
              "chaos_tag": "slowme"}
    want_warm = ref.generate([3, 1, 4], max_new_tokens=8,
                             temperature=0.7, seed=21)
    want_outage = ref.generate([2, 7, 1, 8], max_new_tokens=10,
                               temperature=0.7, seed=22)
    ref.shutdown()

    # warm the router's cached table BEFORE the outage + baseline bytes
    assert [c["token"] for c in _stream(handle, warm)] == want_warm
    assert _ctrl_meta(ctrl).get("restarts") == 0

    assert ray_tpu.get(
        ctrl.scale_deployment.remote(APP, DEP, 2), timeout=30)
    time.sleep(1.0)  # let the reconcile pass reach the kill window

    # controller down (or restarting): the data plane must not notice —
    # a FRESH stream serves from the cached table, byte-identical
    assert [c["token"] for c in _stream(handle, outage)] == want_outage
    # and the proxy's liveness endpoint never depended on the controller
    hz = json.loads(urllib.request.urlopen(
        serve_http_url("/healthz"), timeout=10).read())
    assert hz["status"] == "ok"

    # the restarted controller recovers, reaps the orphan, and converges
    assert _wait_for(
        lambda: _dep(ctrl).get("running_replicas") == 2, timeout_s=180), \
        f"never converged to 2 replicas: {_status(ctrl)}"
    meta = _ctrl_meta(ctrl)
    assert meta.get("restarts", 0) >= 1, "the chaos kill never happened"
    assert meta.get("recovered_at") is not None
    assert meta.get("recovery_seconds") is not None
    # no leaked actors: exactly the fleet survives (orphan was reaped)
    assert _wait_for(lambda: _alive_replica_actors() == 2, timeout_s=60), \
        f"leaked replica actors: {_alive_replica_actors()}"


@pytest.mark.chaos
@pytest.mark.timeout(300)
def test_controller_killed_mid_drain_resumes_and_stream_survives(ft_cluster):
    """Scale 2 -> 1 with a slow stream in flight; the controller dies the
    instant the drain_start checkpoint lands (before prepare_drain is
    even dispatched). Recovery re-latches the drain with the
    checkpointed remaining time, the stream completes byte-identical,
    and the drained replica retires — final fleet of one, pools clean."""
    handle, ctrl = ft_cluster["handle"], ft_cluster["ctrl"]
    ray_tpu = ft_cluster["ray"]

    ref = _engine(seed=0)
    want = ref.generate([9, 2, 6, 5], max_new_tokens=60,
                        temperature=0.8, seed=33)
    ref.shutdown()
    payload = {"prompt": [9, 2, 6, 5], "request_id": "drain-0",
               "max_new_tokens": 60, "temperature": 0.8, "seed": 33,
               "chaos_tag": "slowme"}

    result: dict = {}

    def run():
        gen = _stream(handle, payload)
        result["chunks"] = list(gen)
        result["failovers"] = gen.failovers

    t = threading.Thread(target=run)
    t.start()
    time.sleep(0.5)  # stream in flight before the drain begins
    assert ray_tpu.get(
        ctrl.scale_deployment.remote(APP, DEP, 1), timeout=30)
    t.join(timeout=240)
    assert "chunks" in result, "the in-flight stream never finished"
    assert [c["token"] for c in result["chunks"]] == want, \
        "stream diverged across the controller outage/drain"

    # the resumed drain retires its replica; the fleet converges to 1
    assert _wait_for(
        lambda: (_dep(ctrl).get("running_replicas") == 1
                 and _dep(ctrl).get("draining_replicas") == 0),
        timeout_s=180), f"drain never completed: {_status(ctrl)}"
    meta = _ctrl_meta(ctrl)
    assert meta.get("restarts", 0) >= 2, \
        "the mid-drain kill never happened"
    assert meta.get("checkpoint_version") == CHECKPOINT_VERSION
    assert meta.get("checkpoint_seq", 0) > 0
    assert _wait_for(lambda: _alive_replica_actors() == 1, timeout_s=60), \
        "the drained replica leaked"
    assert _wait_for(lambda: _replica_pools_clean(handle), timeout_s=60), \
        "KV blocks leaked across the outage"
