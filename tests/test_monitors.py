"""FileSystemMonitor, event_stats, debug dumps
(model: reference src/ray/common/file_system_monitor.h tests +
instrumented_io_context stats)."""
import time

import ray_tpu


def test_disk_usage_readable():
    from ray_tpu._private.file_system_monitor import disk_usage

    r = disk_usage("/tmp")
    assert r is not None
    used, total = r
    assert 0 <= used <= total


def test_fs_monitor_threshold_injectable():
    from ray_tpu._private.file_system_monitor import FileSystemMonitor

    readings = {"full": (99, 100), "ok": (10, 100)}
    m = FileSystemMonitor(["full", "ok"], 0.95,
                          read_fn=lambda p: readings[p])
    assert m.usage_fraction() == 0.99
    assert m.over_capacity()
    readings["full"] = (50, 100)
    assert not m.over_capacity()
    # threshold 0 disables
    m0 = FileSystemMonitor(["full"], 0.0, read_fn=lambda p: (100, 100))
    assert not m0.over_capacity()


def test_raylet_holds_work_when_disk_full(ray_start):
    """Over-capacity node stops STARTING tasks; restoring capacity drains
    the queue (reference: raylet refuses leases over capacity)."""
    node = ray_tpu._node_handle
    raylet = node.raylet
    orig = raylet._fs_monitor
    full = {"v": True}

    class _Fake:
        def over_capacity(self):
            return full["v"]

        def usage_fraction(self):
            return 0.99 if full["v"] else 0.10

    raylet._fs_monitor = _Fake()
    try:
        @ray_tpu.remote
        def f():
            return 42

        ref = f.remote()
        ready, _ = ray_tpu.wait([ref], timeout=1.0)
        assert ready == []  # held: disk full
        full["v"] = False
        assert ray_tpu.get(ref, timeout=30) == 42
    finally:
        raylet._fs_monitor = orig


def test_event_stats_record_and_snapshot():
    from ray_tpu._private import event_stats as es

    es.reset()
    with es.timed("unit.block"):
        time.sleep(0.01)
    es.record("unit.manual", 0.002)
    es.record("unit.manual", 0.004)
    snap = es.snapshot()
    assert snap["unit.block"]["count"] == 1
    assert snap["unit.block"]["max_ms"] >= 5
    assert snap["unit.manual"]["count"] == 2
    assert 2.5 < snap["unit.manual"]["mean_ms"] < 3.5
    assert "unit.manual" in es.summary_string()


def test_event_stats_cover_rpc_and_dispatch(ray_start):
    from ray_tpu._private import event_stats as es
    from ray_tpu.util import state

    @ray_tpu.remote
    def f():
        return 1

    assert ray_tpu.get(f.remote(), timeout=30) == 1
    snap = es.snapshot()
    # gcs handlers and the raylet dispatch loop both recorded
    assert any(k.startswith("rpc.gcs.") for k in snap), snap.keys()
    assert "raylet.dispatch" in snap
    dump = state.debug_state()
    assert "event_stats" in dump


def test_heartbeat_carries_disk_fraction(ray_start):
    from ray_tpu.util import state

    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        nodes = state.list_nodes()
        if any("disk_used_frac" in n for n in nodes):
            frac = [n["disk_used_frac"] for n in nodes
                    if "disk_used_frac" in n][0]
            assert 0.0 <= frac <= 1.0
            # gossiped in hundredths: the GCS re-versions a node whose
            # report changed, and the unrounded fraction moves with every
            # write to the disk (test_scale_cluster's O(changes) assertion
            # saw 40 of 40 nodes in a settled cluster's delta)
            assert frac == round(frac, 2)
            return
        time.sleep(0.5)
    raise AssertionError("no heartbeat carried disk_used_frac")
