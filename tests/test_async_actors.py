"""Async actors / max_concurrency.

Reference model: threaded actors via max_concurrency
(src/ray/core_worker/transport/concurrency_group_manager.cc) — up to N
methods in flight on a per-actor thread pool; default actors stay strictly
ordered and serial.
"""
from __future__ import annotations

import time

import pytest

from conftest import shutdown_if_setup_fails


def test_concurrent_actor_overlaps_methods(ray_start):
    """N slow methods on a max_concurrency=N actor finish in ~1x the
    single-method latency."""
    rt = ray_start

    @rt.remote(max_concurrency=4)
    class Slow:
        def work(self, i):
            time.sleep(1.0)
            return i

    a = Slow.remote()
    rt.get(a.work.remote(-1), timeout=120)  # warm: worker spawned, cls loaded
    t0 = time.monotonic()
    refs = [a.work.remote(i) for i in range(4)]
    out = rt.get(refs, timeout=120)
    dt = time.monotonic() - t0
    assert sorted(out) == [0, 1, 2, 3]
    assert dt < 3.0, f"4x 1s methods took {dt:.1f}s — not overlapping"


def test_serial_actor_still_strictly_ordered(ray_start):
    rt = ray_start

    @rt.remote
    class Ordered:
        def __init__(self):
            self.log = []

        def add(self, i, delay):
            time.sleep(delay)
            self.log.append(i)
            return i

        def get_log(self):
            return list(self.log)

    a = Ordered.remote()
    # first call sleeps longest: only serial in-order execution preserves
    # submission order in the log
    refs = [a.add.remote(0, 0.3), a.add.remote(1, 0.1), a.add.remote(2, 0.0)]
    rt.get(refs, timeout=120)
    assert rt.get(a.get_log.remote(), timeout=60) == [0, 1, 2]


def test_concurrent_actor_state_shared(ray_start):
    """Concurrent methods run on one instance (threads, not copies)."""
    rt = ray_start

    @rt.remote(max_concurrency=4)
    class Counter:
        def __init__(self):
            import threading

            self.lock = threading.Lock()
            self.n = 0

        def bump(self):
            import time as _t

            with self.lock:
                self.n += 1
            _t.sleep(0.1)
            return self.n

        def total(self):
            return self.n

    c = Counter.remote()
    rt.get([c.bump.remote() for _ in range(8)], timeout=120)
    assert rt.get(c.total.remote(), timeout=60) == 8


def test_concurrent_actor_death_fails_all_inflight(ray_start):
    rt = ray_start

    @rt.remote(max_concurrency=4)
    class Doomed:
        def slow(self):
            time.sleep(30)

        def die(self):
            import os

            os._exit(1)

    a = Doomed.remote()
    slow_refs = [a.slow.remote() for _ in range(3)]
    time.sleep(2)  # let them start
    a.die.remote()
    for r in slow_refs:
        with pytest.raises(rt.exceptions.ActorDiedError):
            rt.get(r, timeout=120)


def test_concurrent_actor_error_isolated(ray_start):
    """One failing method must not poison its siblings."""
    rt = ray_start

    @rt.remote(max_concurrency=3)
    class Mixed:
        def ok(self, i):
            time.sleep(0.2)
            return i

        def bad(self):
            raise ValueError("nope")

    a = Mixed.remote()
    good = [a.ok.remote(i) for i in range(2)]
    bad = a.bad.remote()
    assert sorted(rt.get(good, timeout=120)) == [0, 1]
    with pytest.raises(ValueError):
        rt.get(bad, timeout=60)


@pytest.fixture
def serve_cluster():
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=6)
    with shutdown_if_setup_fails():
        serve.start(http_options={"port": 18127})
    yield serve
    serve.shutdown()
    ray_tpu.shutdown()


def test_serve_replica_concurrent_requests(serve_cluster):
    """A replica serves N concurrent slow requests in ~1x the latency
    (reference: max_ongoing_requests async replicas)."""
    serve = serve_cluster

    @serve.deployment(max_ongoing_requests=4)
    class SlowModel:
        def __call__(self, x):
            time.sleep(1.0)
            return x * 2

    handle = serve.run(SlowModel.bind(), name="slow_app", timeout_s=240)
    handle.remote(0).result(timeout=120)  # warm
    t0 = time.monotonic()
    responses = [handle.remote(i) for i in range(4)]
    out = [r.result(timeout=120) for r in responses]
    dt = time.monotonic() - t0
    assert sorted(out) == [0, 2, 4, 6]
    assert dt < 3.0, f"4x 1s requests took {dt:.1f}s — replica not concurrent"
