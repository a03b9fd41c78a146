"""Raylet — the per-node manager: worker pool, local scheduling, actors.

Equivalent of the reference's raylet daemon
(reference: src/ray/raylet/ — NodeManager RPC surface (node_manager.h:125),
WorkerPool fork/register/reuse (worker_pool.h:80), LocalTaskManager dispatch
+ spillback (local_task_manager.cc:105), DependencyManager, placement-group
bundle resources (placement_group_resource_manager.h), and the 2-phase PG
prepare/commit handlers (node_manager.cc:1832,1848)).

Differences from the reference, deliberate for round 1:
  * Tasks are pushed raylet→worker over the worker's registered control
    connection rather than leased-then-pushed owner→worker; the raylet stays
    on the dispatch path (the reference takes it off the data path via
    worker leases, direct_task_transport.cc:134 — planned optimization).
  * Worker-crash retries run raylet-side using the spec's max_retries
    (the reference drives retries from the owner's TaskManager).
  * Completion signaling rides the shared object store: results (or error
    payloads) are sealed into the return objects, unblocking any getter.

TPU-first: ``TPU`` is a predefined resource with per-chip assignment — a
dispatched task gets ``TPU_VISIBLE_CHIPS`` set the way the reference sets
``CUDA_VISIBLE_DEVICES`` (reference: python/ray/_private/utils.py:462
TPU_VISIBLE_CHIPS handling; worker.py:430 GPU analog).
"""
from __future__ import annotations

import heapq
import os
import subprocess
import sys
import threading
import time
from typing import Any

from ray_tpu._private import object_store as osmod
from ray_tpu._private import scheduler as sched
from ray_tpu._private import serialization as ser
from ray_tpu._private import task_spec as ts
from ray_tpu._private.config import global_config
from ray_tpu._private.ids import NodeID, ObjectID, TaskID, WorkerID
from ray_tpu._private.object_ref import _ErrorPayload
from ray_tpu._private.object_store import ObjectStoreClient, StoreEventSubscriber
from ray_tpu._private.rpc import RpcClient, RpcServer
from ray_tpu.exceptions import ActorDiedError, WorkerCrashedError


class WorkerHandle:
    def __init__(self, worker_id: bytes, proc: subprocess.Popen | None):
        self.worker_id = worker_id
        self.proc = proc
        self.conn = None  # set at registration
        self.registered = threading.Event()
        self.current_task: dict | None = None
        self.is_actor_worker = False
        self.actor_id: bytes | None = None
        self.last_idle = time.monotonic()
        self.task_started = 0.0  # dispatch time of current_task
        self.assigned_chips: list[int] = []
        # memory-monitor kill attribution: (reason, task_id it was running)
        self.oom_killed: tuple[str, bytes] | None = None


_node_gauges_cache = None
_node_gauges_lock = threading.Lock()


def _node_gauges():
    """Process-singleton node gauge families: in-process Cluster tests run
    several raylets per process and prometheus_client rejects duplicate
    registrations — nodes are distinguished by the `node` label instead."""
    global _node_gauges_cache
    with _node_gauges_lock:
        if _node_gauges_cache is None:
            try:
                from ray_tpu.util.metrics import Gauge

                _node_gauges_cache = (
                    Gauge("ray_tpu_node_resource_available",
                          "available per resource", ("node", "resource")),
                    Gauge("ray_tpu_node_tasks_queued",
                          "tasks waiting for dispatch", ("node",)),
                    Gauge("ray_tpu_node_workers",
                          "live worker processes", ("node",)),
                )
            except Exception:  # noqa: BLE001 — prometheus_client missing
                _node_gauges_cache = False
        return _node_gauges_cache or None


class Raylet:
    # strict-mode wire validation against schema.SCHEMAS["raylet"] (rpc.py)
    schema_service = "raylet"

    def __init__(
        self,
        node_id: NodeID,
        gcs_address: str,
        store_socket: str,
        resources: dict[str, float],
        labels: dict[str, str] | None = None,
    ):
        self.node_id = node_id
        self.gcs_address = gcs_address
        self.store_socket = store_socket
        self.resources = dict(resources)
        self.labels = labels or {}
        self.available = dict(resources)
        cfg = global_config()
        self._soft_limit = (
            cfg.num_workers_soft_limit
            if cfg.num_workers_soft_limit > 0
            else max(1, int(resources.get("CPU", 1)))
        )

        self._lock = threading.RLock()
        self._dispatch_cv = threading.Condition(self._lock)
        # TPU chip slots for assignment
        self._free_chips = list(range(int(resources.get("TPU", 0))))
        self._idle_workers: list[WorkerHandle] = []
        self._all_workers: dict[bytes, WorkerHandle] = {}
        self._queued: list[dict] = []  # task specs waiting for deps/resources
        self._missing_deps: dict[bytes, set[bytes]] = {}  # task_id -> dep oids
        # actor_id -> actor record
        self._actors: dict[bytes, dict] = {}
        # pg_id -> bundle_index -> {"resources", "state", "used"}
        self._bundles: dict[bytes, dict[int, dict]] = {}
        self._peer_clients: dict[str, RpcClient] = {}
        self._actor_seq = 0  # tie-breaker for the per-actor method heap
        self._cluster_view: dict[bytes, dict] = {}
        self._cluster_seq = 0  # highest node-table version applied (delta sync)
        self._stopped = threading.Event()
        # disk-full protection: when the session filesystem crosses the
        # threshold the dispatch loop stops STARTING work (queued tasks
        # wait; running ones finish) — reference file_system_monitor.h
        from ray_tpu._private.file_system_monitor import FileSystemMonitor

        self._fs_monitor = FileSystemMonitor(
            [os.path.dirname(store_socket) if store_socket else ""],
            cfg.local_fs_capacity_threshold,
            cache_ttl_s=0.25,  # dispatch runs per task wakeup: amortize
        )
        # inter-node object plane state
        self._fetching: set[bytes] = set()  # pulls in flight
        self._dep_fetch_ts: dict[bytes, float] = {}  # dep oid -> last fetch req
        self._fetch_neg_ts: dict[bytes, float] = {}  # oid -> last unknown-result
        # primary-copy pinning (reference: raylet pins objects for live refs,
        # node_manager.cc:2416 PinObjectIDs): objects SEALED on this node are
        # pinned until the owner frees them; objects PULLED here are
        # secondary copies and stay LRU-evictable
        self._secondary: set[bytes] = set()  # oids being pulled (skip pin)
        self._pinned: set[bytes] = set()
        # pending directory updates: ordered ("s"|"e", oid) pairs — order
        # matters (evict-then-reseal within one batch must end as present)
        self._dir_pending: list[tuple[str, bytes]] = []
        self._dir_event = threading.Event()

        self.store = ObjectStoreClient(store_socket)
        self.gcs = RpcClient(gcs_address)
        self.server = RpcServer(self)
        self.address = self.server.address
        # Feed the GCS object directory from the store's seal/evict stream
        # (reference: the raylet learns sealed objects from plasma's
        # notification socket and the directory resolves locations,
        # object_manager/ownership_based_object_directory.cc:551).
        self._store_events = StoreEventSubscriber(store_socket, self._on_store_event)

        self.gcs.call(
            "register_node",
            {
                "node_id": node_id.binary(),
                "address": self.address,
                "resources": self.resources,
                "labels": self.labels,
                "store_socket": store_socket,
            },
        )
        # push-path of the delta syncer: node-table changes arrive the
        # moment the GCS applies them; the 1 Hz heartbeat pull stays as
        # the gap-filling reconciliation (reference: ray_syncer.h:86 —
        # bidirectional pushed deltas, not poll-only)
        self._delta_sub: RpcClient | None = None
        self._subscribe_node_deltas()
        # immediate baseline pull: pushes are gap-guarded against the local
        # version, so without this the push channel stays inert until the
        # first 1 Hz heartbeat tick establishes a base
        try:
            reply = self.gcs.call("heartbeat", {
                "node_id": node_id.binary(), "seen_seq": 0,
            })
            if reply.get("ok"):
                self._apply_cluster_delta(reply)
        except Exception:  # noqa: BLE001 — the pull loop reconciles anyway
            pass
        self._threads = [
            threading.Thread(target=self._heartbeat_loop, daemon=True, name="raylet-hb"),
            threading.Thread(target=self._dep_loop, daemon=True, name="raylet-deps"),
            threading.Thread(target=self._dispatch_loop, daemon=True, name="raylet-dispatch"),
            threading.Thread(target=self._dir_flush_loop, daemon=True, name="raylet-objdir"),
            threading.Thread(target=self._idle_reaper_loop, daemon=True, name="raylet-reaper"),
            threading.Thread(target=self._memory_monitor_loop, daemon=True, name="raylet-oom"),
            threading.Thread(target=self._metrics_report_loop, daemon=True, name="raylet-metrics"),
        ]
        for t in self._threads:
            t.start()

    # ------------- lifecycle -------------

    def stop(self) -> None:
        self._stopped.set()
        with self._dispatch_cv:
            self._dispatch_cv.notify_all()
        self._dir_event.set()
        for w in list(self._all_workers.values()):
            if w.proc is not None:
                w.proc.terminate()
        self.server.stop()
        self._store_events.close()
        if self._delta_sub is not None:
            try:
                self._delta_sub.close()
            except Exception:  # noqa: BLE001
                pass
        self.gcs.close()
        self.store.close()

    def _heartbeat_loop(self) -> None:
        cfg = global_config()
        interval = cfg.gcs_heartbeat_interval_ms / 1000.0
        while not self._stopped.wait(interval):
            try:
                if self._delta_sub is None:
                    # push channel lost (GCS flap, failed subscribe):
                    # retry — pull-only is correct but slower
                    self._subscribe_node_deltas()
                with self._lock:
                    avail = dict(self.available)
                    load = len(self._queued)
                    # resource shapes of queued work — the autoscaler
                    # bin-packs these onto node types (reference:
                    # resource_demand_scheduler.py:102 get_nodes_to_launch)
                    shapes = [dict(s["resources"]) for s in self._queued[:100]]
                hb = {
                    "node_id": self.node_id.binary(),
                    "available": avail,
                    "load": load,
                    "pending_shapes": shapes,
                    # delta sync: ask only for node-table changes since the
                    # last tick (reference: ray_syncer.h versioned deltas)
                    "seen_seq": self._cluster_seq,
                }
                disk = self._fs_monitor.usage_fraction()
                if disk is not None:
                    # in hundredths, as the threshold is stated and as
                    # state.py prints it: the GCS re-versions a node whose
                    # report changed, and the unrounded fraction changes
                    # with every write to the disk — a settled cluster's
                    # delta was then all N nodes every tick
                    hb["disk_used_frac"] = round(disk, 2)
                reply = self.gcs.call("heartbeat", hb)
                if reply.get("reregister"):
                    # the GCS restarted and lost the node table — re-announce
                    # (reference: node_manager.cc:1168 HandleNotifyGCSRestart)
                    self.gcs.call(
                        "register_node",
                        {
                            "node_id": self.node_id.binary(),
                            "address": self.address,
                            "resources": self.resources,
                            "labels": self.labels,
                            "store_socket": self.store_socket,
                        },
                    )
                    # ...and its store contents: the object directory is
                    # in-memory GCS state and died with the old incarnation
                    self._republish_store_contents()
                    with self._lock:
                        # the new GCS incarnation restarts its version
                        # counter — drop the stale view entirely and resync
                        # from zero (nodes that died during the outage have
                        # no tombstone in the new incarnation)
                        self._cluster_seq = 0
                        self._cluster_view = {}
                self._apply_cluster_delta(reply)
            except Exception:
                if self._stopped.is_set():
                    return
                # GCS may be restarting: rebuild the client connection and
                # retry next tick (reference: gcs reconnect timeout,
                # ray_config_def.h:65)
                try:
                    self.gcs.close()
                except Exception:  # noqa: BLE001
                    pass
                try:
                    self.gcs = RpcClient(self.gcs_address)
                    # the push subscription died with the old GCS conn
                    self._subscribe_node_deltas()
                except Exception:  # noqa: BLE001
                    pass

    def _subscribe_node_deltas(self) -> None:
        if self._delta_sub is not None:
            try:
                self._delta_sub.close()
            except Exception:  # noqa: BLE001
                pass
            self._delta_sub = None
        client = None
        try:
            client = RpcClient(
                self.gcs_address, notify_handler=self._on_node_delta_push)
            client.call("subscribe", {"topic": "node_delta"})
            self._delta_sub = client
        except Exception:  # noqa: BLE001 — pull sync still covers us; the
            # heartbeat loop retries the subscription next tick
            if client is not None:
                try:
                    client.close()
                except Exception:  # noqa: BLE001
                    pass

    def _on_node_delta_push(self, topic: str, payload: dict) -> None:
        """Pushed node-table change. Applied only when it is the NEXT
        version — a push stream with gaps (late subscribe, dropped conn)
        must not leapfrog intermediate changes; the heartbeat pull
        reconciles those by asking with seen_seq."""
        if topic != "node_delta":
            return
        with self._lock:  # RLock: atomic check-then-apply vs the pull path
            if payload.get("seq") != self._cluster_seq + 1:
                return
            self._apply_cluster_delta(payload)

    def _apply_cluster_delta(self, reply: dict) -> None:
        """Merge one heartbeat reply's node-table changes into the local
        cluster view. Tombstones FIRST: a node that died and revived within
        one sync window appears in both lists, and its delta entry is always
        newer than its tombstone — applying delta last keeps the revived
        node visible (reference: ray_syncer versioned merge semantics)."""
        with self._lock:
            if reply.get("full"):
                self._cluster_view = {}
            for nid in reply.get("removed", ()):
                self._cluster_view.pop(nid, None)
            for n in reply.get("delta", ()):
                self._cluster_view[n["node_id"]] = n
            if "seq" in reply:
                self._cluster_seq = reply["seq"]

    def _metrics_report_loop(self) -> None:
        """Periodic node-level gauge refresh at
        config.metrics_report_interval_ms (reference: per-node metrics
        agent push cadence, metrics_report_interval_ms in
        ray_config_def.h). Gauges land in the in-process Prometheus
        registry served by util.metrics.start_metrics_server."""
        gauges = _node_gauges()
        if gauges is None:  # prometheus_client unavailable: skip quietly
            return
        avail_g, queued_g, workers_g = gauges
        interval = global_config().metrics_report_interval_ms / 1000.0
        short_id = self.node_id.hex()[:12]
        while not self._stopped.wait(interval):
            try:
                with self._lock:
                    avail = dict(self.available)
                    n_queued = len(self._queued)
                    n_workers = len(self._all_workers)
                for res, val in avail.items():
                    avail_g.set(val, {"node": short_id, "resource": res})
                queued_g.set(n_queued, {"node": short_id})
                workers_g.set(n_workers, {"node": short_id})
            except Exception:  # noqa: BLE001 — metrics must never kill a raylet
                pass

    def _idle_reaper_loop(self) -> None:
        """Reap long-idle task workers down to one warm worker so an idle
        node releases memory (reference: worker_pool.cc idle worker killing,
        kill_idle_workers_interval_ms / idle_worker_killing_time_threshold)."""
        cfg = global_config()
        interval = cfg.kill_idle_workers_interval_ms / 1000.0
        threshold = cfg.idle_worker_killing_time_threshold_ms / 1000.0
        while not self._stopped.wait(interval):
            now = time.monotonic()
            victims = []
            with self._lock:
                if len(self._idle_workers) <= 1:
                    continue
                # oldest-idle first; always keep one warm worker (cold spawn
                # costs seconds)
                for w in sorted(self._idle_workers, key=lambda w: w.last_idle):
                    if len(self._idle_workers) - len(victims) <= 1:
                        break
                    if now - w.last_idle > threshold:
                        victims.append(w)
                for w in victims:
                    self._idle_workers.remove(w)
                    self._all_workers.pop(w.worker_id, None)
            for w in victims:
                try:
                    if w.conn is not None:
                        w.conn.close()
                    if w.proc is not None:
                        w.proc.terminate()
                except Exception:  # noqa: BLE001
                    pass

    def _memory_monitor_loop(self) -> None:
        """Kill workers under memory pressure instead of letting the kernel
        OOM-killer take down the raylet (reference: memory_monitor.h:52 +
        worker_killing_policy.cc:116 — retriable tasks first, newest
        first)."""
        from ray_tpu._private.memory_monitor import MemoryMonitor

        cfg = global_config()
        if cfg.memory_usage_threshold <= 0:
            return
        monitor = MemoryMonitor(cfg.memory_usage_threshold)
        self._memory_monitor = monitor  # tests may swap the read function
        interval = cfg.memory_monitor_refresh_ms / 1000.0
        while not self._stopped.wait(interval):
            try:
                frac = monitor.usage_fraction()
                if frac is None or frac <= cfg.memory_usage_threshold:
                    continue
                victim = self._pick_oom_victim(
                    f"worker killed by the memory monitor: node memory usage "
                    f"{frac:.0%} > threshold {cfg.memory_usage_threshold:.0%}"
                )
                if victim is None:
                    continue
                if victim.proc is not None:
                    victim.proc.terminate()
                elif victim.conn is not None:
                    victim.conn.close()
            except Exception:  # noqa: BLE001 — monitoring must never die
                pass

    def _pick_oom_victim(self, reason: str) -> WorkerHandle | None:
        """Policy (reference: worker_killing_policy.cc retriable-LIFO):
        among busy TASK workers prefer one whose task can retry, NEWEST
        dispatch first (least progress lost); actor workers are spared
        (they carry state). Selection and kill-attribution are marked under
        the lock so a task that finishes before terminate() lands is not
        mislabeled as OOM-killed."""
        with self._lock:
            busy = [
                w for w in self._all_workers.values()
                if not w.is_actor_worker and w.current_task is not None
            ]
            if not busy:
                return None
            retriable = [
                w for w in busy
                if w.current_task["retry_count"] < w.current_task["max_retries"]
            ]
            pool = retriable or busy
            victim = max(pool, key=lambda w: w.task_started)
            victim.oom_killed = (reason, victim.current_task["task_id"])
            return victim

    # ------------- inter-node object plane -------------

    def _on_store_event(self, ev: int, oid: bytes) -> None:
        """Store seal/evict notification (runs on the subscriber thread)."""
        resolved = False
        with self._lock:
            self._dir_pending.append(
                ("s" if ev == osmod.EV_SEALED else "e", oid)
            )
            if ev == osmod.EV_SEALED:
                if oid in self._secondary:
                    self._secondary.discard(oid)  # pulled copy: evictable
                else:
                    # primary copies pin themselves atomically at seal
                    # (seal(pin=True)); track so free_object unpins once
                    self._pinned.add(oid)
                # PUSH-based dependency resolution: a seal is exactly the
                # event the dep manager waits for (reference: the raylet's
                # DependencyManager subscribes to object availability) — the
                # slow _dep_loop poll remains only for remote fetches and
                # eviction detection
                for task_id, deps in list(self._missing_deps.items()):
                    if oid in deps:
                        deps.discard(oid)
                        self._dep_fetch_ts.pop(oid, None)
                        if not deps:
                            del self._missing_deps[task_id]
                            resolved = True
            else:
                self._pinned.discard(oid)
        self._dir_event.set()
        if resolved:
            with self._dispatch_cv:
                self._dispatch_cv.notify_all()

    def _republish_store_contents(self) -> None:
        """After a GCS restart the (in-memory) object directory is empty:
        re-announce every object this node's store still holds, like the
        node re-registration itself."""
        try:
            oids = self.store.list_objects()
        except Exception:  # noqa: BLE001 — store unreachable mid-shutdown
            return
        with self._lock:
            self._dir_pending.extend(("s", o.binary()) for o in oids)
        self._dir_event.set()

    def _dir_flush_loop(self) -> None:
        """Batch location updates to the GCS directory: one RPC per burst of
        seal/evict events instead of one per object."""
        while not self._stopped.is_set():
            self._dir_event.wait(timeout=1.0)
            self._dir_event.clear()
            if self._stopped.is_set():
                return
            with self._lock:
                events, self._dir_pending = self._dir_pending, []
            if not events:
                continue
            try:
                self.gcs.call(
                    "object_location_update",
                    {
                        "node_id": self.node_id.binary(),
                        "events": [[ev, oid] for ev, oid in events],
                    },
                )
            except Exception:
                if self._stopped.is_set():
                    return
                # GCS restarting: requeue and retry next tick (heartbeat
                # loop heals the connection)
                with self._lock:
                    self._dir_pending = events + self._dir_pending
                time.sleep(0.2)
                self._dir_event.set()

    def rpc_pull_object(self, conn, msgid, p):
        """Serve one chunk of a local object to a pulling peer raylet
        (reference: ObjectManager::Push chunked transfer,
        object_manager.h:117 / object_buffer_pool.cc)."""
        view = self.store.get(ObjectID(p["object_id"]), timeout_ms=0)
        if view is None or view is osmod.EVICTED:
            return {"ok": False}
        total = len(view)
        off = int(p.get("offset", 0))
        length = int(p.get("length", total))
        return {"ok": True, "size": total, "data": bytes(view[off : off + length])}

    def rpc_fetch_object(self, conn, msgid, p):
        """Worker/driver asks its raylet to pull an object into the local
        store. Non-blocking: the caller keeps (blocking-)polling its local
        store; the seal wakes it (reference: PullManager, pull_manager.h:52)."""
        return {"status": self._request_fetch(p["object_id"])}

    def _request_fetch(self, oid: bytes) -> str:
        st = self.store.status(ObjectID(oid))
        if st == "present":
            return "present"
        # st is "missing" OR "evicted": a LOCAL tombstone (e.g. an LRU-evicted
        # secondary copy) does not mean the object is gone cluster-wide —
        # consult the directory; re-pulling clears the tombstone via create()
        now = time.monotonic()
        neg = self._fetch_neg_ts.get(oid)
        if neg is not None and now - neg < 0.5:
            return "evicted" if st == "evicted" else "unknown"
        try:
            r = self.gcs.call("get_object_locations", {"object_id": oid})
        except Exception:
            return "evicted" if st == "evicted" else "unknown"
        if not r.get("known"):
            self._fetch_neg_ts[oid] = now
            if len(self._fetch_neg_ts) > 10_000:
                cutoff = now - 0.5
                self._fetch_neg_ts = {
                    k: v for k, v in self._fetch_neg_ts.items() if v > cutoff
                }
            # no directory entry: trust local knowledge (it existed and died)
            return "evicted" if st == "evicted" else "unknown"
        self._fetch_neg_ts.pop(oid, None)
        locs = [l for l in r.get("nodes", ()) if l["node_id"] != self.node_id.binary()]
        if not locs:
            # directory tombstone (or every holder dead) → owners should
            # lineage-reconstruct; no entry → producer hasn't sealed yet
            return "evicted" if (r.get("evicted") or st == "evicted") else "unknown"
        with self._lock:
            if oid in self._fetching:
                return "fetching"
            self._fetching.add(oid)
        threading.Thread(
            target=self._pull_object, args=(oid, locs), daemon=True,
            name="raylet-pull",
        ).start()
        return "fetching"

    def _pull_object(self, oid: bytes, locations: list[dict]) -> None:
        """Pull one object chunk-by-chunk from a holder into the local store."""
        cfg = global_config()
        chunk = cfg.object_pull_chunk_bytes
        obj = ObjectID(oid)
        try:
            for loc in locations:
                created = False
                try:
                    peer = self._peer(loc["address"])
                    r = peer.call(
                        "pull_object", {"object_id": oid, "offset": 0, "length": chunk}
                    )
                    if not r.get("ok"):
                        continue
                    total = r["size"]
                    with self._lock:
                        # mark BEFORE create/seal so the seal event sees a
                        # secondary copy and does not pin it
                        self._secondary.add(oid)
                    try:
                        buf = self.store.create(obj, total)
                    except ValueError:
                        return  # landed locally already (racing seal/pull)
                    created = True
                    data = r["data"]
                    if total:
                        buf[: len(data)] = data
                    off = len(data)
                    while off < total:
                        r = peer.call(
                            "pull_object",
                            {"object_id": oid, "offset": off, "length": chunk},
                        )
                        if not r.get("ok") or not r["data"]:
                            raise ConnectionError("holder dropped object mid-pull")
                        data = r["data"]
                        buf[off : off + len(data)] = data
                        off += len(data)
                    self.store.seal(obj)  # seal event publishes the location
                    return
                except Exception:  # noqa: BLE001 — try the next holder
                    if created:
                        try:
                            self.store.abort(obj)
                        except Exception:  # noqa: BLE001
                            pass
                    with self._lock:
                        # no seal event will clear it; a later PRIMARY seal
                        # of this oid must not be mistaken for a pulled copy
                        self._secondary.discard(oid)
                    continue
        finally:
            with self._lock:
                self._fetching.discard(oid)
            with self._dispatch_cv:
                self._dispatch_cv.notify_all()

    def rpc_free_object(self, conn, msgid, p):
        """Owner's refs hit zero: UNPIN the local copy so it becomes
        LRU-evictable (routed via the GCS directory; reference:
        ReferenceCounter zero-ref → plasma objects become evictable,
        reference_count.h:61-115). Deliberately NOT an immediate delete:
        the owner cannot see borrowers (refs deserialized elsewhere), so
        reclamation happens lazily under memory pressure — a borrower of a
        freed ref keeps working unless pressure evicts it first, and task
        results remain lineage-reconstructible."""
        oid = p["object_id"]
        with self._lock:
            pinned = oid in self._pinned
            self._pinned.discard(oid)
        if pinned:
            try:
                self.store.unpin(ObjectID(oid))
            except Exception:  # noqa: BLE001 — store tearing down
                pass
        return {"ok": True}

    # ------------- dependency resolution -------------

    def _dep_loop(self) -> None:
        """Slow safety-net sweep over missing deps: LOCAL seals resolve
        instantly via the store event stream (_on_store_event); this loop
        only triggers remote pulls and detects cluster-wide eviction, so a
        100ms cadence suffices (was a 5ms contains-poll)."""
        from ray_tpu.exceptions import ObjectLostError

        while not self._stopped.wait(0.1):
            resolved_any = False
            with self._lock:
                items = [(tid, set(deps)) for tid, deps in self._missing_deps.items()]
            for task_id, deps in items:
                done = set()
                evicted = None
                for d in deps:
                    st = self.store.status(ObjectID(d))
                    if st == "present":
                        done.add(d)
                        continue
                    # missing (or tombstoned) locally: pull it if a peer
                    # holds a copy (throttled — _request_fetch dedups
                    # in-flight pulls); only a CLUSTER-WIDE "evicted" fails
                    # the task so a local tombstone never masks a live copy
                    now = time.monotonic()
                    if now - self._dep_fetch_ts.get(d, 0.0) > 0.2:
                        self._dep_fetch_ts[d] = now
                        if self._request_fetch(d) == "evicted":
                            evicted = d
                            break
                if evicted is not None:
                    # Fail the task with ObjectLostError; the owner's get()
                    # reconstructs from lineage and resubmits (worker.py
                    # _get_one handles the ObjectLostError payload).
                    with self._lock:
                        self._missing_deps.pop(task_id, None)
                        spec = next(
                            (s for s in self._queued if s["task_id"] == task_id), None
                        )
                        if spec is not None:
                            self._queued.remove(spec)
                    if spec is not None:
                        self._seal_error(
                            spec,
                            ObjectLostError(
                                f"dependency {ObjectID(evicted)} of task "
                                f"{spec['name']} was evicted"
                            ),
                        )
                    continue
                if done:
                    with self._lock:
                        for d in done:
                            self._dep_fetch_ts.pop(d, None)
                        remaining = self._missing_deps.get(task_id)
                        if remaining is not None:
                            remaining -= done
                            if not remaining:
                                del self._missing_deps[task_id]
                                resolved_any = True
            if resolved_any:
                with self._dispatch_cv:
                    self._dispatch_cv.notify_all()

    # ------------- worker pool -------------

    def _spawn_worker(self) -> WorkerHandle:
        worker_id = WorkerID.from_random()
        env = dict(os.environ)
        env.update(
            {
                "RT_RAYLET_ADDR": self.address,
                "RT_STORE_SOCK": self.store_socket,
                "RT_GCS_ADDR": self.gcs_address,
                "RT_NODE_ID": self.node_id.hex(),
                "RT_WORKER_ID": worker_id.hex(),
            }
        )
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.worker_main"],
            env=env,
            stdout=None,
            stderr=None,
        )
        handle = WorkerHandle(worker_id.binary(), proc)
        with self._lock:
            self._all_workers[worker_id.binary()] = handle
        return handle

    def rpc_register_worker(self, conn, msgid, p):
        wid = bytes.fromhex(p["worker_id"]) if isinstance(p["worker_id"], str) else p["worker_id"]
        with self._lock:
            handle = self._all_workers.get(wid)
            if handle is None:
                handle = WorkerHandle(wid, None)
                self._all_workers[wid] = handle
            handle.conn = conn
            conn.meta["worker_id"] = wid
            handle.registered.set()
            if not handle.is_actor_worker:
                self._idle_workers.append(handle)
        conn.on_close.append(self._on_worker_disconnect)
        with self._dispatch_cv:
            self._dispatch_cv.notify_all()
        return {"ok": True, "node_id": self.node_id.hex()}

    def _on_worker_disconnect(self, conn) -> None:
        wid = conn.meta.get("worker_id")
        if wid is None:
            return
        with self._lock:
            handle = self._all_workers.pop(wid, None)
            if handle is None:
                return
            if handle in self._idle_workers:
                self._idle_workers.remove(handle)
            spec = handle.current_task
        if handle.assigned_chips:
            # the chips go back to the pool below: the next claimant must
            # not start before this process has let go of them
            self._reap(handle)
        if handle.is_actor_worker and handle.actor_id is not None:
            self._on_actor_worker_death(handle, spec)
        else:
            self._release_task_resources(handle)
            if spec is not None:
                oom_reason = None
                if (
                    handle.oom_killed is not None
                    and handle.oom_killed[1] == spec["task_id"]
                ):
                    # attribute the kill only to the task the monitor saw;
                    # a task that finished in the selection→terminate window
                    # dies as an ordinary worker crash instead
                    oom_reason = handle.oom_killed[0]
                self._on_task_worker_death(spec, oom_reason=oom_reason)

    @staticmethod
    def _reap(handle: WorkerHandle, timeout: float = 30.0) -> None:
        """Stop a worker and wait until its process has exited. A chip
        belongs to one process at a time (libtpu holds it, and its lock,
        until the process is gone), so chips are returned to the pool
        only after their holder was reaped."""
        proc = handle.proc
        if proc is None:
            return
        if proc.poll() is None:
            proc.terminate()
        try:
            proc.wait(timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()

    def _on_task_worker_death(self, spec: dict, oom_reason: str | None = None) -> None:
        from ray_tpu.exceptions import OutOfMemoryError

        if spec["retry_count"] < spec["max_retries"]:
            spec = dict(spec, retry_count=spec["retry_count"] + 1)
            delay = global_config().task_retry_delay_ms / 1000.0

            def _requeue():
                if delay > 0 and self._stopped.wait(delay):
                    return
                with self._dispatch_cv:
                    self._enqueue_locked(spec)
                    self._dispatch_cv.notify_all()

            # backoff before the retry so a crash-looping task doesn't spin
            # the dispatch path (reference: task_retry_delay_ms)
            threading.Thread(target=_requeue, daemon=True).start()
        elif oom_reason is not None:
            self._seal_error(
                spec,
                OutOfMemoryError(
                    f"task {spec['name']} failed: {oom_reason} "
                    f"(retries exhausted: {spec['max_retries']})"
                ),
            )
        else:
            self._seal_error(
                spec,
                WorkerCrashedError(
                    f"worker died executing {spec['name']} "
                    f"(retries exhausted: {spec['max_retries']})"
                ),
            )

    def _on_actor_worker_death(self, handle: WorkerHandle, spec: dict | None) -> None:
        aid = handle.actor_id
        with self._lock:
            actor = self._actors.get(aid)
            if actor is None:
                return
            # snapshot + reset ATOMICALLY: a racing _pump_actor either ran
            # before (its spec is in the snapshot and gets sealed; its
            # failed notify finds the inflight entry gone and skips the
            # requeue) or runs after and sees worker=None
            inflight = list(actor["inflight"].values())
            actor["inflight"].clear()
            actor["executing"] = 0
            actor["worker"] = None
        if spec is not None and spec["type"] == ts.ACTOR_CREATION:
            self._seal_error(spec, ActorDiedError(aid.hex(), "worker process died"))
        for fspec in inflight:
            # every method in flight died with the worker
            self._seal_error(fspec, ActorDiedError(aid.hex(), "worker process died"))
        creation_spec = actor["creation_spec"]
        if actor["num_restarts"] < creation_spec.get("max_restarts", 0):
            actor["num_restarts"] += 1
            self.gcs.call(
                "update_actor",
                {"actor_id": aid, "state": "RESTARTING", "increment_restarts": True},
            )
            # fail queued calls submitted before restart? keep them — they run
            # against the restarted instance (at-least-once actor semantics
            # when max_restarts > 0).
            self._start_actor_worker(aid, creation_spec)
        else:
            with self._lock:
                actor["state"] = "DEAD"
                pending = list(actor["queue"])
                actor["queue"].clear()
                self._return_actor_resources_locked(actor)
            for *_ignore, pspec in pending:
                self._seal_error(pspec, ActorDiedError(aid.hex(), "actor died"))
            self.gcs.call("update_actor", {"actor_id": aid, "state": "DEAD"})
            with self._dispatch_cv:
                self._dispatch_cv.notify_all()

    # ------------- resource accounting -------------

    def _acquire(self, spec: dict) -> dict | None:
        """Try to acquire resources for spec; returns assignment or None."""
        res = spec["resources"]
        placement = spec.get("placement")
        with self._lock:
            if placement is not None:
                pg = self._bundles.get(placement["pg"], {})
                bundle = pg.get(placement["bundle"])
                if bundle is None or bundle["state"] != "COMMITTED":
                    return None
                if not sched.fits(res, bundle["available"]):
                    return None
                sched.subtract(bundle["available"], res)
            else:
                if not sched.fits(res, self.available):
                    return None
                sched.subtract(self.available, res)
            chips: list[int] = []
            n_tpu = int(res.get("TPU", 0))
            if n_tpu > 0:
                chips = self._free_chips[:n_tpu]
                del self._free_chips[:n_tpu]
            return {"chips": chips}

    def _release_task_resources(self, handle: WorkerHandle) -> None:
        spec = handle.current_task
        if spec is None:
            return
        res = spec["resources"]
        placement = spec.get("placement")
        with self._lock:
            if placement is not None:
                pg = self._bundles.get(placement["pg"], {})
                bundle = pg.get(placement["bundle"])
                if bundle is not None:
                    sched.add(bundle["available"], res)
            else:
                sched.add(self.available, res)
            self._free_chips.extend(handle.assigned_chips)
            handle.assigned_chips = []
            handle.current_task = None

    # ------------- task submission -------------

    def rpc_submit_task(self, conn, msgid, p):
        spec = p["spec"]
        if spec["type"] == ts.ACTOR_TASK:
            return self._submit_actor_task(spec)
        with self._dispatch_cv:
            self._enqueue_locked(spec)
            self._dispatch_cv.notify_all()
        return {"ok": True, "queued_on": self.node_id.hex()}

    def _enqueue_locked(self, spec: dict) -> None:
        deps = {d for d in spec["arg_deps"] if not self.store.contains(ObjectID(d))}
        if deps:
            self._missing_deps[spec["task_id"]] = deps
        self._queued.append(spec)

    def _submit_actor_task(self, spec: dict) -> dict:
        aid = spec["actor_id"]
        with self._lock:
            actor = self._actors.get(aid)
            if actor is None or actor["state"] == "DEAD":
                pass  # fall through to error below
            else:
                self._actor_seq += 1
                heapq.heappush(actor["queue"], (spec["seqno"], self._actor_seq, spec))
                self._pump_actor(aid)
                return {"ok": True}
        self._seal_error(spec, ActorDiedError(aid.hex(), "actor not on this node or dead"))
        return {"ok": False, "reason": "actor dead"}

    # ------------- dispatch -------------

    def _dispatch_loop(self) -> None:
        from ray_tpu._private import event_stats

        while not self._stopped.is_set():
            with self._dispatch_cv:
                self._dispatch_cv.wait(timeout=0.05)
                if self._stopped.is_set():
                    return
            with event_stats.timed("raylet.dispatch"):
                self._dispatch_once()

    def _dispatch_once(self) -> None:
        if self._fs_monitor.over_capacity():
            # out-of-disk node: hold queued work (running tasks finish);
            # reference raylet likewise stops granting leases over capacity
            return
        while True:
            dispatched = False
            with self._lock:
                queue = list(self._queued)
            for spec in queue:
                tid = spec["task_id"]
                with self._lock:
                    if tid in self._missing_deps:
                        continue
                if self._maybe_spill(spec):
                    with self._lock:
                        if spec in self._queued:
                            self._queued.remove(spec)
                    dispatched = True
                    continue
                if spec["type"] == ts.ACTOR_CREATION:
                    assignment = self._acquire(spec)
                    if assignment is None:
                        continue  # stay queued until resources free up
                    with self._lock:
                        if spec in self._queued:
                            self._queued.remove(spec)
                    self._create_actor(spec, assignment)
                    dispatched = True
                    continue
                assignment = self._acquire(spec)
                if assignment is None:
                    continue
                worker = self._get_idle_worker()
                if worker is None:
                    self._undo_acquire(spec, assignment)
                    continue
                with self._lock:
                    if spec in self._queued:
                        self._queued.remove(spec)
                    worker.current_task = spec
                    worker.task_started = time.monotonic()
                    worker.assigned_chips = assignment["chips"]
                self._push_task(worker, spec, assignment)
                dispatched = True
            if not dispatched:
                return

    def _undo_acquire(self, spec: dict, assignment: dict) -> None:
        res = spec["resources"]
        placement = spec.get("placement")
        with self._lock:
            if placement is not None:
                pg = self._bundles.get(placement["pg"], {})
                bundle = pg.get(placement["bundle"])
                if bundle is not None:
                    sched.add(bundle["available"], res)
            else:
                sched.add(self.available, res)
            self._free_chips.extend(assignment["chips"])

    def _get_idle_worker(self) -> WorkerHandle | None:
        with self._lock:
            while self._idle_workers:
                w = self._idle_workers.pop()
                if w.conn is not None and not w.conn.closed:
                    return w
            n_task_workers = sum(
                1 for w in self._all_workers.values() if not w.is_actor_worker
            )
            if n_task_workers < self._soft_limit:
                pass  # spawn below, outside the lock
            else:
                return None
        self._spawn_worker()
        return None  # dispatched on registration wake-up

    def _push_task(self, worker: WorkerHandle, spec: dict, assignment: dict) -> None:
        ok = worker.conn.notify(
            "execute_task",
            {"spec": spec, "chips": assignment["chips"]},
        )
        if not ok:
            self._on_worker_disconnect(worker.conn)

    def _maybe_spill(self, spec: dict) -> bool:
        """Spillback: forward to a peer raylet when it's the better target
        (reference: lease spillback in HandleRequestWorkerLease +
        hybrid_scheduling_policy)."""
        if spec.get("spilled") or spec.get("placement") is not None:
            return False
        strategy = spec.get("scheduling", {})
        stype = strategy.get("type", ts.SCHED_DEFAULT)
        with self._lock:
            view = {
                nid: dict(n, available=dict(n.get("available", n["resources"])))
                for nid, n in self._cluster_view.items()
            }
            me = self.node_id.binary()
            if me in view:
                view[me]["available"] = dict(self.available)
        if not view:
            return False
        affinity = strategy.get("node_id")
        target = sched.pick_node(
            spec["resources"],
            view,
            strategy=stype,
            local_node_id=me,
            affinity_node_id=affinity,
            soft=strategy.get("soft", False),
        )
        if target is None or target == me:
            # infeasible locally AND nowhere else: if local total can never
            # fit it, error out rather than hang forever
            if target is None and not sched.fits(spec["resources"], self.resources):
                feasible_somewhere = any(
                    sched.fits(spec["resources"], n["resources"]) for n in view.values()
                )
                if not feasible_somewhere:
                    self._seal_error(
                        spec,
                        ValueError(
                            f"task {spec['name']} requires {spec['resources']} "
                            "which no node in the cluster can ever satisfy"
                        ),
                    )
                    return True
            return False
        # local fits and hybrid prefers local — pick_node returns local above;
        # here target is remote
        spec = dict(spec, spilled=True)
        try:
            self._peer(view[target]["address"]).call("submit_task", {"spec": spec})
            return True
        except Exception:
            return False

    def _peer(self, address: str) -> RpcClient:
        with self._lock:
            c = self._peer_clients.get(address)
            if c is None:
                c = RpcClient(address)
                self._peer_clients[address] = c
            return c

    # ------------- actors -------------

    def _return_actor_resources_locked(self, actor: dict) -> None:
        """Release the actor's lifetime reservation to its origin — PG
        bundle when placement-group-scheduled, node pool otherwise. Caller
        holds self._lock; idempotent."""
        if actor.get("resources_returned"):
            return
        actor["resources_returned"] = True
        creation = actor["creation_spec"]
        res = creation["resources"]
        placement = creation.get("placement")
        if placement is not None:
            bundle = self._bundles.get(placement["pg"], {}).get(placement["bundle"])
            if bundle is not None:
                sched.add(bundle["available"], res)
        else:
            sched.add(self.available, res)
        self._free_chips.extend(actor["assignment"]["chips"])
        actor["assignment"] = {"chips": []}

    def _create_actor(self, spec: dict, assignment: dict) -> None:
        aid = spec["actor_id"]
        with self._lock:
            self._actors[aid] = {
                "state": "STARTING",
                "creation_spec": spec,
                "queue": [],
                # up to max_concurrency methods run at once on the worker's
                # thread pool (reference: concurrency_group_manager.cc /
                # threaded actors); in-flight specs tracked for death sealing
                "max_concurrency": max(1, int(spec.get("max_concurrency", 1))),
                "executing": 0,
                "inflight": {},  # task_id -> spec
                "worker": None,
                "num_restarts": 0,
                "assignment": assignment,
            }
        self._start_actor_worker(aid, spec, assignment)

    def _start_actor_worker(self, aid: bytes, spec: dict, assignment: dict | None = None) -> None:
        if assignment is None:
            assignment = self._actors[aid]["assignment"]
        handle = self._spawn_worker()
        handle.is_actor_worker = True
        handle.actor_id = aid
        handle.assigned_chips = assignment["chips"]
        handle.current_task = None

        def finish_registration():
            if not handle.registered.wait(global_config().worker_register_timeout_s):
                # worker never connected: reap it, free the reservation,
                # mark the actor dead
                self._seal_error(spec, ActorDiedError(aid.hex(), "worker failed to start"))
                if handle.proc is not None:
                    handle.proc.terminate()
                with self._lock:
                    actor = self._actors.get(aid)
                    if actor is not None:
                        actor["state"] = "DEAD"
                        self._return_actor_resources_locked(actor)
                self.gcs.call("update_actor", {"actor_id": aid, "state": "DEAD"})
                with self._dispatch_cv:
                    self._dispatch_cv.notify_all()
                return
            with self._lock:
                actor = self._actors.get(aid)
                if actor is None:
                    return
                if actor["state"] == "DEAD":
                    # killed while restarting: do not resurrect
                    if handle.proc is not None:
                        handle.proc.terminate()
                    self._return_actor_resources_locked(actor)
                    return
                actor["worker"] = handle
                if handle in self._idle_workers:
                    self._idle_workers.remove(handle)
            handle.current_task = spec
            handle.conn.notify(
                "execute_task", {"spec": spec, "chips": assignment["chips"]}
            )

        threading.Thread(target=finish_registration, daemon=True).start()

    def _pump_actor(self, aid: bytes) -> None:
        """Dispatch queued methods while capacity allows: strictly in seqno
        order (reference: actor_scheduling_queue.cc sequential ordering),
        up to max_concurrency in flight at once (threaded-actor semantics —
        ordering of EXECUTION is lost beyond 1, as in the reference)."""
        while True:
            with self._lock:
                actor = self._actors.get(aid)
                if (
                    actor is None
                    or actor["state"] != "ALIVE"
                    or actor["executing"] >= actor["max_concurrency"]
                    or not actor["queue"]
                ):
                    return
                if actor["worker"] is None or actor["worker"].conn is None:
                    return  # restarting; rpc_actor_started will pump
                seqno, _tie, spec = heapq.heappop(actor["queue"])
                actor["executing"] += 1
                actor["inflight"][spec["task_id"]] = spec
                handle = actor["worker"]
            if not handle.conn.notify(
                "execute_task", {"spec": spec, "chips": handle.assigned_chips}
            ):
                # Dead connection: requeue the method and let the disconnect
                # path (or an already-started restart) re-pump; retry shortly
                # in case actor_started raced ahead of this requeue. If the
                # death handler already swept this spec out of inflight it
                # was sealed with ActorDiedError — do NOT also requeue.
                with self._lock:
                    if actor["inflight"].pop(spec["task_id"], None) is not None:
                        actor["executing"] = max(0, actor["executing"] - 1)
                        self._actor_seq += 1
                        heapq.heappush(
                            actor["queue"], (seqno, self._actor_seq, spec)
                        )

                def _retry():
                    time.sleep(0.1)
                    self._pump_actor(aid)

                threading.Thread(target=_retry, daemon=True).start()
                return

    def rpc_actor_started(self, conn, msgid, p):
        """Worker reports actor __init__ finished."""
        aid = p["actor_id"]
        with self._lock:
            actor = self._actors.get(aid)
            if actor is None:
                return {"ok": False}
            if actor["state"] == "DEAD":
                # killed while starting/restarting — do not resurrect
                handle = actor.get("worker")
                if handle is not None and handle.proc is not None:
                    handle.proc.terminate()
                return {"ok": False, "reason": "actor killed"}
            actor["state"] = "ALIVE"
            handle = actor["worker"]
            if handle is not None:
                handle.current_task = None
        self.gcs.call(
            "update_actor",
            {
                "actor_id": aid,
                "state": "ALIVE",
                "node_id": self.node_id.binary(),
                "raylet_address": self.address,
                "worker_id": p["worker_id"],
            },
        )
        self._pump_actor(aid)
        return {"ok": True}

    def rpc_kill_actor(self, conn, msgid, p):
        aid = p["actor_id"]
        with self._lock:
            actor = self._actors.get(aid)
            if actor is None:
                return {"ok": False}
            actor["state"] = "DEAD"
            # prevent restart path from resurrecting it
            actor["creation_spec"] = dict(actor["creation_spec"], max_restarts=0)
            handle = actor["worker"]
            pending = list(actor["queue"])
            actor["queue"].clear()
            if handle is None:
                # no live worker (e.g. mid-restart): the disconnect path
                # won't fire, release the reservation here
                self._return_actor_resources_locked(actor)
        for *_ignore, pspec in pending:
            self._seal_error(pspec, ActorDiedError(aid.hex(), "actor was killed"))
        if handle is not None and handle.proc is not None:
            handle.proc.terminate()
        self.gcs.call("update_actor", {"actor_id": aid, "state": "DEAD"})
        return {"ok": True}

    # ------------- task completion -------------

    def rpc_task_done(self, conn, msgid, p):
        wid = conn.meta.get("worker_id")
        with self._lock:
            handle = self._all_workers.get(wid)
        if handle is None:
            return {"ok": False}
        if handle.is_actor_worker:
            # Actor methods run on the actor's lifetime reservation — no
            # per-method resource release (reference: actor creation task
            # holds the resources; methods are zero-cost by default).
            aid = handle.actor_id
            with self._lock:
                handle.current_task = None
                actor = self._actors.get(aid)
                if actor is not None:
                    tid = p.get("task_id") if isinstance(p, dict) else None
                    # only a task we actually dispatched occupies a slot —
                    # the actor-creation task's task_done must NOT decrement
                    # (it never went through _pump_actor)
                    if tid is not None and actor["inflight"].pop(tid, None) is not None:
                        actor["executing"] = max(0, actor["executing"] - 1)
            self._pump_actor(aid)
        elif handle.assigned_chips:
            # A task worker that was handed chips keeps them (its JAX
            # backend stays initialized) for as long as it lives: retire
            # it instead of pooling it, and free the chips once it is gone.
            # Off the RPC thread — the worker is waiting for this reply.
            with self._lock:
                self._all_workers.pop(wid, None)

            def _retire():
                self._reap(handle)
                self._release_task_resources(handle)
                with self._dispatch_cv:
                    self._dispatch_cv.notify_all()

            threading.Thread(target=_retire, daemon=True).start()
        else:
            self._release_task_resources(handle)
            with self._lock:
                handle.last_idle = time.monotonic()
                self._idle_workers.append(handle)
            with self._dispatch_cv:
                self._dispatch_cv.notify_all()
        return {"ok": True}

    def _seal_error(self, spec: dict, error: Exception) -> None:
        """Write an error payload into every return object of the task."""
        for oid in ts.return_object_ids(spec):
            try:
                chunks = ser.serialize(_ErrorPayload(error))
                size = ser.serialized_size(chunks)
                buf = self.store.create(oid, size)
                ser.write_chunks(chunks, buf)
                self.store.seal(oid, pin=True)  # primary copy
            except ValueError:
                pass  # already exists (duplicate failure path) — keep first
            except Exception:
                try:
                    self.store.discard_pending(oid)
                except Exception:  # noqa: BLE001 — connection already gone
                    pass
                if self._stopped.is_set():
                    return  # store already torn down; nobody will get() this
                # e.g. store full: dropping the error would hang the owner's
                # get() forever — log loudly, it indicates store pressure
                import traceback

                print(
                    f"[raylet] FAILED to seal error for task {spec['name']}: "
                    f"{traceback.format_exc()}",
                    flush=True,
                )

    # ------------- placement group bundles -------------

    def rpc_prepare_bundle(self, conn, msgid, p):
        """Phase 1: reserve resources (reference: node_manager.cc:1832)."""
        res = p["resources"]
        with self._lock:
            if not sched.fits(res, self.available):
                return {"ok": False}
            sched.subtract(self.available, res)
            self._bundles.setdefault(p["pg_id"], {})[p["bundle_index"]] = {
                "resources": dict(res),
                "available": dict(res),
                "state": "PREPARED",
            }
        return {"ok": True}

    def rpc_commit_bundle(self, conn, msgid, p):
        """Phase 2 (reference: node_manager.cc:1848)."""
        with self._lock:
            bundle = self._bundles.get(p["pg_id"], {}).get(p["bundle_index"])
            if bundle is None:
                return {"ok": False}
            bundle["state"] = "COMMITTED"
        with self._dispatch_cv:
            self._dispatch_cv.notify_all()
        return {"ok": True}

    def rpc_cancel_bundle(self, conn, msgid, p):
        return self.rpc_return_bundle(conn, msgid, p)

    def rpc_return_bundle(self, conn, msgid, p):
        with self._lock:
            pg = self._bundles.get(p["pg_id"], {})
            bundle = pg.pop(p["bundle_index"], None)
            if bundle is not None:
                sched.add(self.available, bundle["resources"])
        return {"ok": True}

    # ------------- introspection -------------

    def rpc_node_stats(self, conn, msgid, p):
        with self._lock:
            return {
                "node_id": self.node_id.hex(),
                "resources": self.resources,
                "available": dict(self.available),
                "num_workers": len(self._all_workers),
                "num_idle": len(self._idle_workers),
                "queued": len(self._queued),
                "actors": {
                    aid.hex() if isinstance(aid, bytes) else aid: a["state"]
                    for aid, a in self._actors.items()
                },
            }
