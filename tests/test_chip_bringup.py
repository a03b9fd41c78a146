"""What the v5e bring-up (PR 21) taught the runtime, pinned on the CPU:
how chips are found and bound, where compiled programs are kept, that no
peak is invented for an unknown device, and that a failure detector which
was itself stalled does not declare nodes dead."""
from __future__ import annotations

import os
import sys
import types

import pytest


# ------------------------------------------------------------ chips


@pytest.mark.parametrize("dev, vfio, want", [
    (["accel0", "accel1", "null"], [], 2),           # v2-v4 hosts
    (["null", "vfio"], ["1", "vfio"], 1),            # the one-chip v5e host
    (["null", "vfio"], ["0", "1", "2", "3", "vfio"], 4),
    (["null"], None, 0),                             # no chip at all
])
def test_autodetect_tpu_chips(monkeypatch, dev, vfio, want):
    from ray_tpu._private import node

    def listdir(path):
        if path == "/dev":
            return dev
        if path == "/dev/vfio" and vfio is not None:
            return vfio
        raise FileNotFoundError(path)

    monkeypatch.delenv("RT_NUM_TPUS", raising=False)
    monkeypatch.setattr(node.os, "listdir", listdir)
    assert node.autodetect_tpu_chips() == want
    monkeypatch.setenv("RT_NUM_TPUS", "7")
    assert node.autodetect_tpu_chips() == 7


def test_bind_chips_holds_a_chipless_task_to_the_cpu(monkeypatch):
    """An empty TPU_VISIBLE_CHIPS hides nothing (libtpu ignores it), so a
    task that was assigned no chip gets no such variable and the CPU
    platform; one that was assigned chips gets them listed and the
    platform choice the process was started with."""
    from ray_tpu._private import worker

    monkeypatch.setattr(worker, "_JAX_PLATFORMS_AT_START", "tpu,cpu")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "3")
    # keep this process's real jax config out of it
    monkeypatch.setitem(sys.modules, "jax", None)
    worker._bind_chips([])
    assert "TPU_VISIBLE_CHIPS" not in os.environ
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    worker._bind_chips([0, 2])
    assert os.environ["TPU_VISIBLE_CHIPS"] == "0,2"
    assert os.environ["JAX_PLATFORMS"] == "tpu,cpu"


# ---------------------------------------------------- compile cache


@pytest.fixture
def fresh_cache_module(monkeypatch):
    from ray_tpu._private import compile_cache

    monkeypatch.setattr(compile_cache, "_stats", None)
    updates = []

    class _Config:
        def update(self, key, value):
            updates.append((key, value))

    class _Monitoring:
        def register_event_listener(self, fn):
            updates.append(("listener", fn))

    class _Jax:
        config = _Config()
        monitoring = _Monitoring()

    monkeypatch.setitem(sys.modules, "jax", _Jax())
    return compile_cache, updates


def test_compile_cache_leaves_the_environments_directory_alone(
        fresh_cache_module, monkeypatch):
    compile_cache, updates = fresh_cache_module
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    stats = compile_cache.enable_compile_cache()
    assert stats["dir"] == "/somewhere/else"
    assert not [u for u in updates if u[0] == "jax_compilation_cache_dir"]
    # idempotent: one listener however often it is called
    assert compile_cache.enable_compile_cache() is stats
    assert len([u for u in updates if u[0] == "listener"]) == 1


def test_compile_cache_defaults_into_the_checkout(
        fresh_cache_module, monkeypatch):
    compile_cache, updates = fresh_cache_module
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    stats = compile_cache.enable_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert stats["dir"] == os.path.join(repo, ".jax_cache")
    assert ("jax_compilation_cache_dir", stats["dir"]) in updates
    # the listener counts hits and misses into the same record
    listener = next(u[1] for u in updates if u[0] == "listener")
    listener("/jax/compilation_cache/cache_hits")
    listener("/jax/compilation_cache/cache_misses")
    listener("/jax/compilation_cache/cache_misses")
    assert (stats["hits"], stats["misses"]) == (1, 2)


# ------------------------------------------------------------ peaks


def test_no_peak_is_invented_for_an_unlisted_device():
    from ray_tpu.serve.llm.executor import chip_peak_tflops

    class _Dev:
        def __init__(self, kind, platform):
            self.device_kind, self.platform = kind, platform

    assert chip_peak_tflops(_Dev("TPU v5 lite", "tpu")) == 197.0
    for dev in (_Dev("cpu", "cpu"), _Dev("TPU v9", "tpu")):
        with pytest.raises(ValueError, match="no published peak"):
            chip_peak_tflops(dev)


# ------------------------------------------------- failure detector


def test_gcs_health_loop_credits_its_own_stall(monkeypatch):
    """A TPU runtime start or stop stalls the whole host for 4-5 s
    (measured on a v5e host): the GCS's health loop and the raylet's
    heartbeat thread both oversleep. The detector must not count the time
    it was not watching — and must still catch a node that stays silent."""
    from ray_tpu._private import gcs as gcs_mod

    svc = gcs_mod.GcsService()
    node = {"alive": True, "last_heartbeat": 0.0}
    svc.nodes[b"n"] = node
    clock = {"now": 0.0}
    # a clock of the module's own: the real `time` module is everybody's
    monkeypatch.setattr(gcs_mod, "time", types.SimpleNamespace(
        monotonic=lambda: clock["now"]))
    # wake-up times of the 1 s loop: on time, on time, 5.2 s late, then on
    # time while the node stays silent
    wakes = iter([1.0, 2.0, 8.2] + [9.2 + i for i in range(8)])
    heartbeats = {1.0: 0.9, 2.0: 1.9}      # the node reported just before

    class _Stopped:
        def wait(self, interval):
            t = next(wakes, None)
            if t is None:
                return True
            clock["now"] = t
            if t in heartbeats:
                node["last_heartbeat"] = heartbeats[t]
            return False

    monkeypatch.setattr(svc, "_stopped", _Stopped())
    died_at = []
    monkeypatch.setattr(
        svc, "_on_node_death", lambda node_id: died_at.append(clock["now"]))
    svc._health_loop()
    # at 8.2 the last heartbeat was 6.3 s old, 5.2 s of it unwatched: alive;
    # silent from then on: dead once 5 watched seconds have passed
    assert len(died_at) == 1 and 8.2 < died_at[0] <= 13.2
