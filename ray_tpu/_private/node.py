"""Node bootstrap: object store daemon + GCS + raylet for one host.

Equivalent of the reference's node bootstrap
(reference: python/ray/_private/node.py — Node.start_head_processes:1395
spawns gcs_server, start_ray_processes:1424 spawns the raylet which embeds
plasma). The store is always a real subprocess (C++ daemon), ONE PER NODE;
GCS and raylet run as threads in the hosting process — same protocol, fewer
processes. Standalone node processes (`ray_tpu start --head` /
`--address=<gcs>`) are hosted by _private/node_main.py; the `Cluster`
harness stacks extra in-process raylets, each with its own store daemon,
for multi-node tests (reference: python/ray/cluster_utils.py:108).
"""
from __future__ import annotations

import atexit
import os
import tempfile
from typing import Any

from ray_tpu._private.config import global_config
from ray_tpu._private.gcs import GcsService
from ray_tpu._private.ids import NodeID
from ray_tpu._private.object_store import start_store
from ray_tpu._private.raylet import Raylet


def autodetect_tpu_chips() -> int:
    """Detect local TPU chips without initializing JAX.

    Reference: python/ray/_private/accelerator.py:153 _autodetect_num_tpus.
    ``RT_NUM_TPUS`` overrides (tests). Otherwise one chip per
    ``/dev/accel*`` device file (v2-v4 hosts) or, where there are none,
    per numbered VFIO group ``/dev/vfio/<n>`` — how a v5e host exposes
    its chips (one such file on the one-chip machine; /dev/vfio/vfio is
    the container device, not a chip). 0 when neither exists.
    """
    override = os.environ.get("RT_NUM_TPUS")
    if override:
        return int(override)

    def _ls(path: str) -> list[str]:
        try:
            return os.listdir(path)
        except OSError:
            return []

    accel = [d for d in _ls("/dev") if d.startswith("accel")]
    return len(accel) or len([d for d in _ls("/dev/vfio") if d.isdigit()])


class NodeHandle:
    def __init__(self, *, gcs: GcsService | None, gcs_address: str,
                 raylet: Raylet, store_proc, store_socket: str, session_dir: str):
        self.gcs = gcs
        self.gcs_address = gcs_address
        self.raylet = raylet
        self.store_proc = store_proc
        self.store_socket = store_socket
        self.session_dir = session_dir
        self.node_id = raylet.node_id
        # fake multi-host TPU topology (config.fake_tpu_hosts): extra
        # in-process raylets + their store daemons, torn down with the head
        self.fake_nodes: list[tuple[Raylet, Any]] = []

    def shutdown(self) -> None:
        for raylet, store_proc in self.fake_nodes:
            try:
                raylet.stop()
            except Exception:
                pass
            if store_proc is not None:
                try:
                    store_proc.terminate()
                    store_proc.wait(timeout=5)
                except Exception:
                    pass
        self.fake_nodes = []
        self.raylet.stop()
        if self.gcs is not None:
            self.gcs.stop()
        if self.store_proc is not None:
            try:
                self.store_proc.terminate()
                self.store_proc.wait(timeout=5)
            except Exception:
                pass


def start_fake_tpu_hosts(head: NodeHandle, n_hosts: int,
                         chips_per_host: int) -> None:
    """SURVEY §4.3 fake-accelerator harness: present an n-host TPU pod
    slice on one machine. Each fake host is a real in-process raylet with
    its own store daemon, `TPU: chips_per_host` resources, and pod-slice
    labels (one shared ici-domain — scheduler slice-affinity sees a real
    topology). Enabled by config.fake_tpu_hosts > 0; chips per host come
    from config.tpu_chips_per_host_default."""
    cfg = global_config()
    for i in range(n_hosts):
        store_socket = os.path.join(head.session_dir, f"fake-tpu-{i}.sock")
        store_proc = start_store(
            store_socket, cfg.object_store_memory_bytes,
            spill_dir=cfg.object_spilling_dir or None,
        )
        raylet = Raylet(
            NodeID.from_random(),
            head.gcs_address,
            store_socket,
            {"CPU": 1.0, "TPU": float(chips_per_host),
             "memory": float(2 * 1024**3)},
            {"ici-domain": "fake-slice-0", "fake-tpu-host": str(i)},
        )
        head.fake_nodes.append((raylet, store_proc))


def _default_node_resources(
    num_cpus: float | None,
    num_tpus: float | None,
    resources: dict[str, float] | None,
    labels: dict[str, str] | None,
) -> tuple[dict[str, float], dict[str, str]]:
    node_resources = dict(resources or {})
    node_resources.setdefault(
        "CPU", float(num_cpus if num_cpus is not None else os.cpu_count() or 1)
    )
    node_resources.setdefault(
        "TPU", float(num_tpus if num_tpus is not None else autodetect_tpu_chips())
    )
    node_resources.setdefault("memory", float(2 * 1024**3))
    node_labels = dict(labels or {})
    if node_resources["TPU"] > 0:
        node_labels.setdefault("ici-domain", "slice-0")
    return node_resources, node_labels


def start_head(
    *,
    num_cpus: float | None = None,
    num_tpus: float | None = None,
    resources: dict[str, float] | None = None,
    labels: dict[str, str] | None = None,
    object_store_memory: int | None = None,
    gcs_port: int = 0,
) -> NodeHandle:
    cfg = global_config()
    session_dir = tempfile.mkdtemp(prefix="ray_tpu_session_")
    store_socket = os.path.join(session_dir, "store.sock")
    store_proc = start_store(
        store_socket,
        object_store_memory or cfg.object_store_memory_bytes,
        spill_dir=cfg.object_spilling_dir or None,
    )
    # build+load the native scheduling core NOW so the first dispatch never
    # stalls on a synchronous g++ compile
    from ray_tpu._private import scheduler as _sched

    _sched._load_native()

    gcs = GcsService()
    gcs_address = gcs.start(port=gcs_port)

    node_resources, node_labels = _default_node_resources(
        num_cpus, num_tpus, resources, labels
    )
    raylet = Raylet(
        NodeID.from_random(), gcs_address, store_socket, node_resources, node_labels
    )
    handle = NodeHandle(
        gcs=gcs,
        gcs_address=gcs_address,
        raylet=raylet,
        store_proc=store_proc,
        store_socket=store_socket,
        session_dir=session_dir,
    )
    atexit.register(handle.shutdown)
    return handle


def start_worker_node(
    gcs_address: str,
    *,
    num_cpus: float | None = None,
    num_tpus: float | None = None,
    resources: dict[str, float] | None = None,
    labels: dict[str, str] | None = None,
    object_store_memory: int | None = None,
) -> NodeHandle:
    """Join an existing cluster as a new node: own store daemon + raylet
    (reference: `ray start --address=<gcs>`, scripts.py:548 worker path)."""
    cfg = global_config()
    session_dir = tempfile.mkdtemp(prefix="ray_tpu_session_")
    store_socket = os.path.join(session_dir, "store.sock")
    store_proc = start_store(
        store_socket,
        object_store_memory or cfg.object_store_memory_bytes,
        spill_dir=cfg.object_spilling_dir or None,
    )
    node_resources, node_labels = _default_node_resources(
        num_cpus, num_tpus, resources, labels
    )
    raylet = Raylet(
        NodeID.from_random(), gcs_address, store_socket, node_resources, node_labels
    )
    handle = NodeHandle(
        gcs=None,
        gcs_address=gcs_address,
        raylet=raylet,
        store_proc=store_proc,
        store_socket=store_socket,
        session_dir=session_dir,
    )
    atexit.register(handle.shutdown)
    return handle


class Cluster:
    """In-process fake multi-node cluster for tests.

    Reference: python/ray/cluster_utils.py:108 Cluster — extra raylets in one
    process against one GCS. Every node runs its OWN store daemon; objects
    move between nodes through the raylet pull/push object plane, exactly as
    they would across physical hosts.
    """

    def __init__(self, head_resources: dict[str, float] | None = None):
        self.head = start_head(
            num_cpus=(head_resources or {}).get("CPU", 2),
            num_tpus=(head_resources or {}).get("TPU", 0),
            resources={
                k: v for k, v in (head_resources or {}).items() if k not in ("CPU", "TPU")
            },
        )
        self.nodes: list[Raylet] = [self.head.raylet]
        self._store_procs: dict[bytes, Any] = {}

    @property
    def gcs_address(self) -> str:
        return self.head.gcs_address

    def add_node(
        self,
        *,
        num_cpus: float = 1,
        num_tpus: float = 0,
        resources: dict[str, float] | None = None,
        labels: dict[str, str] | None = None,
        object_store_memory: int | None = None,
    ) -> Raylet:
        cfg = global_config()
        node_resources = dict(resources or {})
        node_resources["CPU"] = float(num_cpus)
        node_resources["TPU"] = float(num_tpus)
        node_resources.setdefault("memory", float(2 * 1024**3))
        node_labels = dict(labels or {})
        if num_tpus > 0:
            node_labels.setdefault("ici-domain", f"slice-{len(self.nodes)}")
        store_socket = os.path.join(
            self.head.session_dir, f"store-{len(self.nodes)}.sock"
        )
        store_proc = start_store(
            store_socket,
            object_store_memory or cfg.object_store_memory_bytes,
            spill_dir=cfg.object_spilling_dir or None,
        )
        raylet = Raylet(
            NodeID.from_random(),
            self.head.gcs_address,
            store_socket,
            node_resources,
            node_labels,
        )
        self.nodes.append(raylet)
        self._store_procs[raylet.node_id.binary()] = store_proc
        return raylet

    def remove_node(self, raylet: Raylet) -> None:
        raylet.stop()
        self.nodes.remove(raylet)
        proc = self._store_procs.pop(raylet.node_id.binary(), None)
        if proc is not None:
            try:
                proc.terminate()
            except Exception:
                pass
        try:
            self.head.gcs.rpc_drain_node(None, 0, {"node_id": raylet.node_id.binary()})
        except Exception:
            pass

    def shutdown(self) -> None:
        for raylet in self.nodes[1:]:
            try:
                raylet.stop()
            except Exception:
                pass
        for proc in self._store_procs.values():
            try:
                proc.terminate()
            except Exception:
                pass
        self._store_procs.clear()
        self.head.shutdown()
