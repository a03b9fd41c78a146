"""Fleet metrics plane (ISSUE 13): FleetAggregator merge semantics,
the serving goodput/MFU gauges, and the end-to-end scrape surface —
two LLM replicas report per-replica-labeled series to the controller,
the dashboard exposes one ``/metrics/fleet`` target, and a scaled-down
replica's series stay queryable from the ring-buffer history.

Unit tests drive ``metrics.FleetAggregator`` directly with hand-built
``collect_families()``-shaped snapshots (the merge contract must hold
exactly: summed counters, bucket-preserving histogram merges, last-write
gauges). Cluster tests run a real 2-replica app under the controller.
"""
from __future__ import annotations

import dataclasses
import json
import time
import urllib.request

import pytest

from conftest import shutdown_if_setup_fails

from ray_tpu.util import metrics
from ray_tpu.util.metrics import FleetAggregator, sample_key

DASH_PORT = 18267
APP = "llm-fleet"
DEP = "LLMDeployment"


def _wait_for(predicate, timeout_s=60.0, interval=0.1):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


def _model_config():
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    return dataclasses.replace(
        LlamaConfig.tiny(), dtype=jnp.float32, attention="xla")


# ------------------------------------------------- aggregator units


def _counter_fam(name: str, value: float, **labels) -> dict:
    return {name: {"type": "counter", "help": "h", "samples": [
        {"name": f"{name}_total", "labels": dict(labels),
         "value": float(value)},
    ]}}


def _gauge_fam(name: str, value: float) -> dict:
    return {name: {"type": "gauge", "help": "h", "samples": [
        {"name": name, "labels": {}, "value": float(value)},
    ]}}


def _hist_fam(name: str, buckets: dict[str, float], total: float,
              count: float) -> dict:
    samples = [
        {"name": f"{name}_bucket", "labels": {"le": le}, "value": v}
        for le, v in buckets.items()
    ]
    samples.append({"name": f"{name}_sum", "labels": {}, "value": total})
    samples.append({"name": f"{name}_count", "labels": {}, "value": count})
    return {name: {"type": "histogram", "help": "h", "samples": samples}}


def _ids(app="demo", dep="d", rid="a") -> dict:
    return {"app": app, "deployment": dep, "replica_id": rid}


def test_counter_rollup_equals_sum_of_per_replica_values():
    agg = FleetAggregator()
    agg.ingest("replica:a", _counter_fam("llm_x", 3.0), _ids(rid="a"), 1.0)
    agg.ingest("replica:b", _counter_fam("llm_x", 4.0), _ids(rid="b"), 2.0)
    samples = agg.fleet_families()["llm_x"]["samples"]
    per = {
        s["labels"]["replica_id"]: s["value"]
        for s in samples if "replica_id" in s["labels"]
    }
    assert per == {"a": 3.0, "b": 4.0}
    rollup = [s for s in samples if "replica_id" not in s["labels"]]
    assert len(rollup) == 1
    assert rollup[0]["value"] == sum(per.values())
    assert rollup[0]["labels"] == {"app": "demo", "deployment": "d"}
    # re-ingesting a source REPLACES its snapshot (no double count)
    agg.ingest("replica:a", _counter_fam("llm_x", 5.0), _ids(rid="a"), 3.0)
    samples = agg.fleet_families()["llm_x"]["samples"]
    rollup = [s for s in samples if "replica_id" not in s["labels"]]
    assert rollup[0]["value"] == 9.0


def test_histogram_merge_preserves_bucket_counts():
    agg = FleetAggregator()
    agg.ingest(
        "replica:a",
        _hist_fam("llm_lat", {"0.1": 1.0, "1.0": 3.0, "+Inf": 4.0},
                  total=2.5, count=4.0),
        _ids(rid="a"), 1.0)
    agg.ingest(
        "replica:b",
        _hist_fam("llm_lat", {"0.1": 2.0, "1.0": 2.0, "+Inf": 5.0},
                  total=9.0, count=5.0),
        _ids(rid="b"), 2.0)
    samples = agg.fleet_families()["llm_lat"]["samples"]
    rollup = {
        (s["name"], s["labels"].get("le")): s["value"]
        for s in samples if "replica_id" not in s["labels"]
    }
    # bucket-wise sums, still cumulative per le
    assert rollup[("llm_lat_bucket", "0.1")] == 3.0
    assert rollup[("llm_lat_bucket", "1.0")] == 5.0
    assert rollup[("llm_lat_bucket", "+Inf")] == 9.0
    assert rollup[("llm_lat_sum", None)] == 11.5
    assert rollup[("llm_lat_count", None)] == 9.0


def test_gauge_rollup_is_last_write_by_stamp_not_ingest_order():
    agg = FleetAggregator()
    agg.ingest("replica:a", _gauge_fam("llm_g", 10.0), _ids(rid="a"), 5.0)
    # ingested LATER but stamped EARLIER — must not win
    agg.ingest("replica:b", _gauge_fam("llm_g", 99.0), _ids(rid="b"), 2.0)
    samples = agg.fleet_families()["llm_g"]["samples"]
    rollup = [s for s in samples if "replica_id" not in s["labels"]]
    assert len(rollup) == 1 and rollup[0]["value"] == 10.0
    # both per-replica series still visible individually
    per = {
        s["labels"]["replica_id"]: s["value"]
        for s in samples if "replica_id" in s["labels"]
    }
    assert per == {"a": 10.0, "b": 99.0}


def test_rollup_skipped_when_no_replica_id_label():
    """A source without any ROLLUP_DROP label (the controller's own
    registry) must not emit a duplicate rollup series."""
    agg = FleetAggregator()
    agg.ingest(
        "controller", _counter_fam("serve_restarts", 1.0),
        {"deployment": "_controller"}, 1.0)
    samples = agg.fleet_families()["serve_restarts"]["samples"]
    assert len(samples) == 1
    assert samples[0]["labels"] == {"deployment": "_controller"}


def test_history_ring_bounded_and_outlives_its_source():
    agg = FleetAggregator(history_samples=5)
    for i in range(8):
        agg.ingest("replica:a", _counter_fam("llm_x", float(i)),
                   _ids(rid="a"), stamp=float(i))
    key = sample_key("llm_x_total", _ids(rid="a"))
    ring = agg.history(series=key)[key]
    assert len(ring) == 5  # bounded: oldest 3 points dropped
    assert ring[0] == (3.0, 3.0) and ring[-1] == (7.0, 7.0)
    # the source dies (never reports again); another one keeps going
    agg.ingest("replica:b", _counter_fam("llm_x", 100.0),
               _ids(rid="b"), stamp=9.0)
    # dead replica: series still in history AND in the fleet view, so
    # the counter rollup stays monotonic across replica death
    assert agg.history(series=key)[key][-1] == (7.0, 7.0)
    samples = agg.fleet_families()["llm_x"]["samples"]
    rollup = [s for s in samples if "replica_id" not in s["labels"]]
    assert rollup[0]["value"] == 107.0
    assert agg.history(prefix="llm_x") != {}
    assert agg.history(prefix="nope") == {}
    assert "replica:a" in agg.sources()


def test_render_prometheus_text_exposition():
    agg = FleetAggregator()
    agg.ingest("replica:a", _counter_fam("llm_x", 3.0), _ids(rid="a"), 1.0)
    text = metrics.render_prometheus(agg.fleet_families())
    assert "# TYPE llm_x counter" in text
    assert (
        'llm_x_total{app="demo",deployment="d",replica_id="a"} 3'
        in text
    )
    # label values are escaped per the exposition format
    weird = metrics.render_prometheus({
        "f": {"type": "gauge", "help": "a\nb", "samples": [
            {"name": "f", "labels": {"k": 'x"y\n'}, "value": float("inf")},
        ]},
    })
    assert r'f{k="x\"y\n"} +Inf' in weird
    assert r"# HELP f a\nb" in weird


# ------------------------------------------------- engine goodput


@pytest.mark.timeout(300)
def test_engine_goodput_and_mfu_nonzero_per_step_kind(jax_cpu):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    eng = LLMEngine(
        EngineConfig(model="llama", model_config=_model_config(),
                     block_size=8, num_blocks=64),
        auto_step=True,
    )
    # the CPU has no published peak (chip_peak_tflops raises, the executor
    # reports None and the engine no MFU): hand the gauge one of our own
    assert eng.executor.peak_tflops is None
    eng.executor.peak_tflops = 0.5
    try:
        out = eng.generate([1, 2, 3], max_new_tokens=8)
        assert len(out) == 8
        good = eng.stats()["goodput"]
        assert "decode" in good
        assert any(k.startswith("prefill") for k in good)
        for kind, g in good.items():
            assert g["tokens_per_sec"] > 0.0, (kind, g)
            assert g["mfu"] > 0.0, (kind, g)
            assert g["window_tokens"] > 0 and g["window_steps"] > 0
        snap = metrics.collect(prefix="llm_goodput_tokens_per_sec")
        assert snap["llm_goodput_tokens_per_sec{kind=decode}"] > 0.0
        snap = metrics.collect(prefix="llm_serving_mfu")
        assert snap["llm_serving_mfu{kind=decode}"] > 0.0
    finally:
        eng.shutdown()


# ------------------------------------------------- cluster integration


@pytest.fixture(scope="module")
def fleet_cluster():
    """2-replica LLM app under the controller + a dashboard on the same
    cluster — the whole fleet plane, end to end."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.dashboard import start_dashboard
    from ray_tpu.serve.controller import CONTROLLER_NAME
    from ray_tpu.serve.llm import EngineConfig, build_llm_app

    ray_tpu.init(num_cpus=8)
    with shutdown_if_setup_fails():
        # EveryNode: per-node proxy ACTORS, so the fleet plane has a
        # "proxy:" source to poll (Driver mode hosts the proxy in this
        # process, which the controller cannot reach)
        serve.start(http_options={"port": 0}, proxy_location="EveryNode")
        handle = serve.run(
            build_llm_app(
                EngineConfig(model="llama", model_config=_model_config(),
                             seed=0),
                num_replicas=2,
                graceful_shutdown_timeout_s=2.0,
            ),
            name=APP, route_prefix="/fleet", timeout_s=300,
        )
        ctrl = ray_tpu.get_actor(CONTROLLER_NAME)
        dash = start_dashboard(port=DASH_PORT)
    yield {"handle": handle, "ctrl": ctrl, "ray": ray_tpu}
    dash.stop()
    serve.shutdown()
    ray_tpu.shutdown()


def _fleet(ctrl) -> dict:
    import ray_tpu

    return ray_tpu.get(ctrl.fleet_metrics.remote(), timeout=30)


def _replica_sources(fleet: dict) -> dict[str, dict]:
    return {
        src: rec for src, rec in fleet["sources"].items()
        if src.startswith("replica:")
    }


@pytest.mark.timeout(300)
def test_two_replicas_report_relabeled_series_and_rollups(fleet_cluster):
    from ray_tpu.serve.llm import stream_tokens

    handle, ctrl = fleet_cluster["handle"], fleet_cluster["ctrl"]
    for i in range(4):
        chunks = list(stream_tokens(handle, {
            "prompt": [1, 2, 3], "request_id": f"fleet-{i}",
            "max_new_tokens": 4,
        }))
        assert len(chunks) == 4
    assert _wait_for(
        lambda: len(_replica_sources(_fleet(ctrl))) >= 2, timeout_s=60
    ), "controller never ingested both replicas' metrics_report"

    assert _wait_for(
        lambda: any(
            s.startswith("proxy:") for s in _fleet(ctrl)["sources"]
        ),
        timeout_s=60,
    ), "no proxy source ever reported"

    def _tokens_landed():
        fams = _fleet(ctrl)["families"]
        fam = fams.get("llm_engine_tokens_generated", {"samples": []})
        return any(
            "replica_id" not in s["labels"] and s["value"] >= 16.0
            for s in fam["samples"]
            if s["labels"].get("deployment") == DEP
        )

    # the poll cadence is _FLEET_PERIOD_S — wait for the post-stream
    # reports (with all 16 generated tokens) to reach the aggregator
    assert _wait_for(_tokens_landed, timeout_s=60), \
        "fleet rollup never caught up with the generated tokens"
    fleet = _fleet(ctrl)
    assert "controller" in fleet["sources"]

    samples = fleet["families"]["llm_engine_tokens_generated"]["samples"]
    per = {
        s["labels"]["replica_id"]: s["value"]
        for s in samples
        if s["labels"].get("deployment") == DEP
        and "replica_id" in s["labels"]
    }
    assert len(per) == 2, f"expected 2 per-replica series, got {per}"
    rollup = [
        s for s in samples
        if s["labels"].get("deployment") == DEP
        and "replica_id" not in s["labels"]
    ]
    assert len(rollup) == 1
    # THE acceptance identity: fleet counter rollup == sum of the
    # per-replica collect() values it was merged from
    assert rollup[0]["value"] == pytest.approx(sum(per.values()))
    assert rollup[0]["value"] >= 16.0  # 4 streams x 4 tokens landed
    assert rollup[0]["labels"]["app"] == APP

    # the latency histograms crossed it as well, and the fleet saw no
    # fewer first tokens and gaps than the clients did (4 streams x 4
    # tokens): the rollup's count is the sum of the replicas' own
    for family, seen in (("llm_ttft_seconds", 4),
                         ("llm_time_per_output_token_seconds", 12)):
        counts = [
            s for s in fleet["families"][family]["samples"]
            if s["name"] == f"{family}_count"
            and s["labels"].get("deployment") == DEP
        ]
        per_replica = [s["value"] for s in counts
                       if "replica_id" in s["labels"]]
        rolled = [s["value"] for s in counts
                  if "replica_id" not in s["labels"]]
        assert len(rolled) == 1 and rolled[0] >= seen, (family, counts)
        assert rolled[0] == pytest.approx(sum(per_replica)), (family, counts)

    # the serving goodput gauges crossed the fleet plane too
    good = fleet["families"]["llm_goodput_tokens_per_sec"]["samples"]
    decode = [
        s for s in good
        if s["labels"].get("kind") == "decode"
        and s["labels"].get("deployment") == DEP
    ]
    assert decode and any(s["value"] > 0.0 for s in decode)


@pytest.mark.timeout(300)
def test_dashboard_fleet_scrape_and_history_endpoints(fleet_cluster):
    base = f"http://127.0.0.1:{DASH_PORT}"
    text = urllib.request.urlopen(
        f"{base}/metrics/fleet", timeout=30).read().decode()
    assert "# TYPE llm_engine_tokens_generated counter" in text
    assert 'replica_id="' in text and f'app="{APP}"' in text

    with urllib.request.urlopen(
            f"{base}/api/metrics/fleet", timeout=30) as r:
        fleet = json.load(r)
    assert "llm_engine_tokens_generated" in fleet["families"]
    assert len(_replica_sources(fleet)) >= 2

    with urllib.request.urlopen(
            f"{base}/api/metrics/fleet/history"
            "?prefix=llm_engine_tokens_generated", timeout=30) as r:
        hist = json.load(r)["series"]
    assert hist, "no history rings under llm_engine_tokens_generated"
    for points in hist.values():
        assert points and all(len(p) == 2 for p in points)
        stamps = [p[0] for p in points]
        assert stamps == sorted(stamps)


@pytest.mark.timeout(300)
def test_scaled_down_replica_series_survive_in_history(fleet_cluster):
    """Scale 2 -> 1: the retired replica stops reporting, but its series
    stay queryable from the history rings and its last counter values
    keep the fleet rollup monotonic."""
    import ray_tpu

    ctrl = fleet_cluster["ctrl"]
    before = _replica_sources(_fleet(ctrl))
    assert len(before) >= 2
    assert ray_tpu.get(
        ctrl.scale_deployment.remote(APP, DEP, 1), timeout=30)

    def _converged():
        st = ray_tpu.get(ctrl.status.remote(), timeout=30)
        dep = st.get(APP, {}).get(DEP, {})
        return (dep.get("running_replicas") == 1
                and dep.get("draining_replicas") == 0)

    assert _wait_for(_converged, timeout_s=120), "drain never completed"

    # the dead source's stamp stops advancing; live ones keep reporting
    time.sleep(2.0)
    s1 = _fleet(ctrl)["sources"]
    time.sleep(2.0)
    s2 = _fleet(ctrl)["sources"]
    dead = [
        src for src in before
        if s1[src]["stamp"] == s2[src]["stamp"]
    ]
    assert len(dead) == 1, f"expected exactly one retired source: {dead}"
    dead_rid = s2[dead[0]]["labels"]["replica_id"]

    # still a source, still in the fleet families, still in history
    fleet = _fleet(ctrl)
    assert dead[0] in fleet["sources"]
    samples = fleet["families"]["llm_engine_tokens_generated"]["samples"]
    assert any(
        s["labels"].get("replica_id") == dead_rid for s in samples)
    hist = ray_tpu.get(
        ctrl.fleet_history.remote(None, "llm_engine_tokens_generated"),
        timeout=30)
    dead_keys = [k for k in hist if f"replica_id={dead_rid}" in k]
    assert dead_keys, f"retired replica vanished from history: {dead_rid}"
    assert hist[dead_keys[0]], "empty ring for the retired replica"
