"""Inter-node object plane: per-node store daemons, GCS object directory,
chunked raylet pull/push, and the real multi-host bootstrap CLI.

Reference model: src/ray/object_manager/object_manager.h:117 (push/pull
chunked transfer), pull_manager.h:52 (pull management),
ownership_based_object_directory.cc:551 (location resolution — here
GCS-resolved), python/ray/scripts/scripts.py:548 (`ray start`).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest


@pytest.fixture
def two_node_cluster():
    """Cluster with two SEPARATE store daemons + a driver on the head."""
    import ray_tpu
    from ray_tpu._private.ids import JobID
    from ray_tpu._private.node import Cluster
    from ray_tpu._private.worker import CoreWorker, set_global_worker

    cluster = Cluster(head_resources={"CPU": 1})
    remote = cluster.add_node(num_cpus=2, resources={"remote_res": 2.0})
    job_id = JobID(cluster.head.raylet.gcs.call("next_job_id")["job_id"])
    core = CoreWorker(
        mode="driver",
        gcs_address=cluster.gcs_address,
        raylet_address=cluster.head.raylet.address,
        store_socket=cluster.head.store_socket,
        job_id=job_id,
        node_id=cluster.head.node_id,
    )
    set_global_worker(core)
    time.sleep(1.5)  # heartbeat propagation: head sees the second node
    yield cluster, remote
    core.shutdown()
    set_global_worker(None)
    cluster.shutdown()


def test_cluster_nodes_have_separate_stores(two_node_cluster):
    cluster, remote = two_node_cluster
    assert remote.store_socket != cluster.head.store_socket
    assert os.path.exists(remote.store_socket)


def test_cross_node_get(two_node_cluster):
    """Node B's task creates an object; the driver (head store) gets it
    through two separate store daemons."""
    import ray_tpu

    @ray_tpu.remote(resources={"remote_res": 1.0})
    def make():
        return np.arange(4096, dtype=np.int64)

    val = ray_tpu.get(make.remote(), timeout=120)
    assert int(val.sum()) == 4096 * 4095 // 2


def test_cross_node_dependency_multichunk(two_node_cluster):
    """A driver put (head store) larger than one pull chunk feeds a task on
    node B: the dep resolver must pull it chunk-by-chunk."""
    import ray_tpu
    from ray_tpu._private.config import global_config

    big = np.ones(3_000_000, dtype=np.float64)  # ~24 MB
    assert big.nbytes > global_config().object_pull_chunk_bytes

    @ray_tpu.remote(resources={"remote_res": 1.0})
    def consume(x):
        return int(x.sum())

    assert ray_tpu.get(consume.remote(ray_tpu.put(big)), timeout=120) == 3_000_000


def test_cross_node_wait(two_node_cluster):
    import ray_tpu

    @ray_tpu.remote(resources={"remote_res": 1.0})
    def f(i):
        return i * 2

    refs = [f.remote(i) for i in range(4)]
    ready, pending = ray_tpu.wait(refs, num_returns=4, timeout=120)
    assert len(ready) == 4 and not pending
    assert sorted(ray_tpu.get(ready, timeout=60)) == [0, 2, 4, 6]


def test_object_directory_tracks_locations(two_node_cluster):
    import ray_tpu
    from ray_tpu._private.worker import global_worker

    @ray_tpu.remote(resources={"remote_res": 1.0})
    def make():
        return b"x" * 1024

    ref = make.remote()
    ray_tpu.get(ref, timeout=120)
    w = global_worker()
    deadline = time.monotonic() + 10
    locs = []
    while time.monotonic() < deadline:
        r = w.gcs.call(
            "get_object_locations", {"object_id": ref.object_id.binary()}
        )
        locs = r["nodes"]
        # after the driver's get, BOTH stores hold the object
        if len(locs) >= 2:
            break
        time.sleep(0.1)
    assert len(locs) >= 2, f"directory saw {locs}"


def test_remote_eviction_reports_lost(two_node_cluster):
    """All holders evict → the directory tombstones → a fetch reports
    'evicted' so owners lineage-reconstruct."""
    cluster, remote = two_node_cluster
    import ray_tpu
    from ray_tpu._private.worker import global_worker

    @ray_tpu.remote(resources={"remote_res": 1.0})
    def make():
        return b"y" * 512

    ref = make.remote()
    # wait for the seal to land in the directory (don't get(): that would
    # copy it into the head store too)
    w = global_worker()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        r = w.gcs.call("get_object_locations", {"object_id": ref.object_id.binary()})
        if r["nodes"]:
            break
        time.sleep(0.05)
    assert r["nodes"], "object never appeared in the directory"
    # evict at the only holder
    remote.store.delete(ref.object_id)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        r = w.gcs.call("get_object_locations", {"object_id": ref.object_id.binary()})
        if r["evicted"]:
            break
        time.sleep(0.05)
    assert r["evicted"]
    # the owner still recovers the value via lineage reconstruction
    assert ray_tpu.get(ref, timeout=120) == b"y" * 512


def test_directory_repopulated_after_gcs_restart(two_node_cluster):
    """A GCS restart wipes the in-memory object directory; raylets must
    re-publish their store contents on reregister so remote gets still
    resolve (reference: raylets resync state after HandleNotifyGCSRestart)."""
    cluster, remote = two_node_cluster
    import ray_tpu
    from ray_tpu._private.worker import global_worker

    @ray_tpu.remote(resources={"remote_res": 1.0})
    def make():
        return b"survivor" * 64

    ref = make.remote()
    w = global_worker()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        r = w.gcs.call("get_object_locations", {"object_id": ref.object_id.binary()})
        if r["nodes"]:
            break
        time.sleep(0.05)
    assert r["nodes"]

    # restart the GCS in place on the same port (in-memory store: the
    # object directory is lost)
    gcs = cluster.head.gcs
    addr = cluster.gcs_address
    port = int(addr.rsplit(":", 1)[1])
    gcs.stop()
    time.sleep(0.3)
    from ray_tpu._private.gcs import GcsService

    gcs2 = GcsService()
    assert gcs2.start(port=port) == addr
    cluster.head.gcs = gcs2

    # the driver's get must succeed: raylets reregister AND republish
    # their store contents into the fresh directory. Wipe the lineage so
    # reconstruction can't mask a directory hole.
    w._lineage.clear()
    assert ray_tpu.get(ref, timeout=120) == b"survivor" * 64


def test_store_event_subscription(tmp_path):
    """Seal/evict events stream to subscribers (plasma-notification analog)."""
    from ray_tpu._private import object_store as osmod
    from ray_tpu._private.ids import ObjectID
    from ray_tpu._private.object_store import (
        ObjectStoreClient,
        StoreEventSubscriber,
        start_store,
    )

    sock = str(tmp_path / "store.sock")
    proc = start_store(sock, 16 * 1024 * 1024)
    events = []
    try:
        sub = StoreEventSubscriber(sock, lambda ev, oid: events.append((ev, oid)))
        client = ObjectStoreClient(sock)
        oid = ObjectID(b"a" * 28)
        buf = client.create(oid, 4)
        buf[:4] = b"data"
        client.seal(oid)
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and not events:
            time.sleep(0.01)
        assert (osmod.EV_SEALED, oid.binary()) in events
        client.delete(oid)
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and len(events) < 2:
            time.sleep(0.01)
        assert (osmod.EV_EVICTED, oid.binary()) in events
        sub.close()
        client.close()
    finally:
        proc.terminate()


def test_store_abort_leaves_no_tombstone(tmp_path):
    from ray_tpu._private.ids import ObjectID
    from ray_tpu._private.object_store import ObjectStoreClient, start_store

    sock = str(tmp_path / "store.sock")
    proc = start_store(sock, 16 * 1024 * 1024)
    try:
        client = ObjectStoreClient(sock)
        oid = ObjectID(b"b" * 28)
        client.create(oid, 8)
        client.abort(oid)
        assert client.status(oid) == "missing"  # NOT 'evicted'
        buf = client.create(oid, 8)  # clean re-create works
        buf[:8] = b"12345678"
        client.seal(oid)
        assert bytes(client.get(oid)) == b"12345678"
        client.close()
    finally:
        proc.terminate()


CLI = [sys.executable, "-m", "ray_tpu.scripts.cli"]


def _start_node(tmp_path, name, *args):
    env = dict(os.environ)
    proc = subprocess.Popen(
        CLI + ["start", *args, "--info-file", str(tmp_path / f"{name}.json")],
        stdout=subprocess.PIPE,
        env=env,
    )
    line = proc.stdout.readline().decode()
    assert "started" in line, line
    with open(tmp_path / f"{name}.json") as f:
        return json.load(f)


def test_cli_multihost_bootstrap(tmp_path):
    """Two separate node PROCESSES formed via the CLI + a third driver
    process connecting by GCS address — the real `ray start` flow."""
    head = worker = None
    try:
        head = _start_node(tmp_path, "head", "--head", "--num-cpus", "1",
                           "--num-tpus", "0")
        gcs = head["gcs_address"]
        worker = _start_node(
            tmp_path, "worker", "--address", gcs, "--num-cpus", "2",
            "--num-tpus", "0", "--resources", '{"worker_res": 2}',
        )
        assert worker["pid"] != head["pid"]

        driver_code = f"""
import time
import ray_tpu
ray_tpu.init(address="{gcs}")
time.sleep(1.5)

@ray_tpu.remote(resources={{"worker_res": 1}})
def where():
    import os
    return os.getpid()

@ray_tpu.remote(resources={{"worker_res": 1}})
def double(x):
    return x * 2

pid = ray_tpu.get(where.remote(), timeout=120)
assert pid not in ({head["pid"]}, {worker["pid"]})  # a spawned worker proc
ref = ray_tpu.put(21)
assert ray_tpu.get(double.remote(ref), timeout=120) == 42
alive = [n for n in ray_tpu.nodes() if n["alive"]]
assert len(alive) == 2, alive
print("DRIVER_OK")
"""
        r = subprocess.run(
            [sys.executable, "-c", driver_code],
            capture_output=True, text=True, timeout=240,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        assert "DRIVER_OK" in r.stdout
    finally:
        for name, info in (("worker", worker), ("head", head)):
            if info is not None:
                subprocess.run(
                    CLI + ["stop", "--info-file", str(tmp_path / f"{name}.json")],
                    capture_output=True,
                )


def test_cli_stop_kills_node(tmp_path):
    head = _start_node(tmp_path, "head", "--head", "--num-cpus", "1",
                       "--num-tpus", "0")
    subprocess.run(CLI + ["stop", "--info-file", str(tmp_path / "head.json")],
                   check=True, capture_output=True)
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        try:
            os.kill(head["pid"], 0)
            time.sleep(0.1)
        except ProcessLookupError:
            return
    pytest.fail("node process survived ray_tpu stop")
