"""GCS — the global control service (cluster metadata + coordination).

Equivalent of the reference's GCS server
(reference: src/ray/gcs/gcs_server/gcs_server.h:79 composing GcsNodeManager,
GcsActorManager (actor FT state machine, gcs_actor_manager.h:281),
GcsPlacementGroupManager with its 2-phase scheduler
(gcs_placement_group_scheduler.cc:884), internal KV (gcs_kv_manager.h:138),
health checks (gcs_health_check_manager.h:39), and pubsub). Here it is one
Python service object behind an RpcServer, storing state in process memory
(the reference's default InMemoryStoreClient) — a Redis-like external store
can be slotted in behind the same table dicts later.

Placement groups use the same 2-phase reserve/commit protocol as the
reference: prepare on every chosen raylet, commit only if all prepared,
else cancel (node_manager.cc:1832,1848 equivalents live in raylet.py).
"""
from __future__ import annotations

import bisect
import threading
import time
from collections import defaultdict
from typing import Any

from ray_tpu._private import scheduler as sched
from ray_tpu._private.config import global_config
from ray_tpu._private.ids import PlacementGroupID
from ray_tpu._private.rpc import RpcClient, RpcServer


class GcsService:
    # strict-mode wire validation against schema.SCHEMAS["gcs"] (rpc.py)
    schema_service = "gcs"

    def __init__(self, store=None):
        """store: a StoreClient (store_client.py). File-backed stores give
        head-restart tolerance — the reference's Redis-backed GCS mode
        (redis_store_client.h:33); None/in-memory is the default mode."""
        from ray_tpu._private.store_client import InMemoryStoreClient

        self._store = store or InMemoryStoreClient()
        self._dirty = 0
        self._persisted = 0
        self._lock = threading.RLock()
        # namespace -> key -> value
        self._kv: dict[str, dict[bytes, bytes]] = defaultdict(dict)
        # node_id(bytes) -> {address, resources, labels, alive, last_heartbeat}
        self.nodes: dict[bytes, dict] = {}
        # delta-sync state: monotonically versioned node-table mutations
        # (reference: ray_syncer.h:86 version-stamped delta gossip)
        self._node_seq = 0
        self._node_tombstones: list[tuple[int, bytes]] = []
        self._tombstone_floor = 0  # removals below this seq were trimmed
        # seq-ordered log of CHANGED nodes so a settled heartbeat's delta
        # read is O(changes since seen), not an O(N) scan of the node
        # table per tick — at N nodes x N heartbeats/s that scan was the
        # control plane's fan-in ceiling
        self._node_change_log: list[tuple[int, bytes]] = []
        self._change_floor = 0  # changes below this seq were trimmed
        # pushed node_delta ordering: seq-ordered outbox (appended under
        # _lock) + a single-flusher lock so publishes can't reorder
        self._delta_outbox: list[dict] = []
        self._delta_pub_lock = threading.Lock()
        # actor_id(bytes) -> {state, class_name, node_id, raylet_address,
        #                     num_restarts, max_restarts, spec}
        self.actors: dict[bytes, dict] = {}
        # pg_id(bytes) -> {bundles, strategy, state, allocations}
        self.placement_groups: dict[bytes, dict] = {}
        self._job_counter = 0
        # object directory: object_id(bytes) -> {"nodes": set[node_id],
        # "evicted": bool}. Locations are runtime state fed by store
        # seal/evict notifications via each raylet; NOT persisted (stores
        # don't survive a head restart either). Reference: the object
        # directory role of ownership_based_object_directory.cc:551, here
        # GCS-resolved (round-3 simplification, owner-resolution later).
        self.object_dir: dict[bytes, dict] = {}
        # tombstoned entries age out (health loop) so the directory doesn't
        # grow with every object ever created; live-location entries are
        # real state and stay
        self._dir_tombstone_ts: dict[bytes, float] = {}
        self._dir_tombstone_ttl_s = 300.0
        # topic -> set of conns
        self._subs: dict[str, set] = defaultdict(set)
        self._raylet_clients: dict[bytes, RpcClient] = {}
        self._task_events: list[dict] = []
        self.server: RpcServer | None = None
        self._health_thread = threading.Thread(
            target=self._health_loop, daemon=True, name="gcs-health"
        )
        self._stopped = threading.Event()

    def start(self, host: str = "127.0.0.1", port: int = 0) -> str:
        self._restore()
        self.server = RpcServer(self, host, port)
        self._health_thread.start()
        # snapshotting every table under the lock is pure overhead when the
        # store is the no-op in-memory default — only run it for real stores
        if getattr(self._store, "persistent", True):
            self._persist_thread = threading.Thread(
                target=self._persist_loop, daemon=True, name="gcs-persist"
            )
            self._persist_thread.start()
        return self.server.address

    def stop(self) -> None:
        self._stopped.set()
        self._persist_now()
        for c in self._raylet_clients.values():
            c.close()
        if self.server:
            self.server.stop()

    # ---------------- persistence (GCS FT) ----------------

    def _mark_dirty(self) -> None:
        self._dirty += 1

    def _snapshot(self) -> dict:
        with self._lock:
            return {
                "kv": {ns: dict(d) for ns, d in self._kv.items()},
                # connections don't survive a restart; nodes re-register on
                # their next heartbeat (raylet reregister path)
                "actors": {
                    aid: dict(a) for aid, a in self.actors.items()
                },
                "placement_groups": {
                    pid: dict(p) for pid, p in self.placement_groups.items()
                },
                "job_counter": self._job_counter,
                "task_events": list(self._task_events),
            }

    def _persist_now(self) -> None:
        if not getattr(self._store, "persistent", True):
            return
        with self._lock:
            version = self._dirty
            if version == self._persisted:
                return
        try:
            self._store.save(self._snapshot())
            with self._lock:
                self._persisted = version
        except Exception:  # noqa: BLE001 — persistence must not kill the GCS
            pass

    def _persist_loop(self) -> None:
        while not self._stopped.wait(0.2):
            self._persist_now()

    def _restore(self) -> None:
        snap = self._store.load()
        if not snap:
            return
        with self._lock:
            for ns, d in snap.get("kv", {}).items():
                self._kv[ns].update(d)
            self.actors.update(snap.get("actors", {}))
            self.placement_groups.update(snap.get("placement_groups", {}))
            self._job_counter = snap.get("job_counter", 0)
            self._task_events = list(snap.get("task_events", []))

    # ---------------- internal helpers ----------------

    def _raylet(self, node_id: bytes) -> RpcClient:
        with self._lock:
            client = self._raylet_clients.get(node_id)
            if client is None:
                client = RpcClient(self.nodes[node_id]["address"])
                self._raylet_clients[node_id] = client
            return client

    def _publish(self, topic: str, payload: Any) -> None:
        with self._lock:
            conns = list(self._subs.get(topic, ()))
        for conn in conns:
            if not conn.notify(topic, payload):
                with self._lock:
                    self._subs[topic].discard(conn)

    def _queue_node_delta_locked(self, payload: dict) -> None:
        """Called under self._lock at the seq-assignment site: appending
        while holding the lock keeps the outbox in seq order, so the
        flusher (outside the lock) can never publish deltas out of order —
        a reordered push would hit subscribers' seq gap guard and stall
        the push channel until their next pull."""
        self._delta_outbox.append(payload)

    def _flush_node_deltas(self) -> None:
        while True:
            with self._delta_pub_lock:
                with self._lock:
                    if not self._delta_outbox:
                        return
                    payload = self._delta_outbox.pop(0)
                self._publish("node_delta", payload)

    def _health_loop(self) -> None:
        cfg = global_config()
        interval = cfg.gcs_heartbeat_interval_ms / 1000.0
        threshold = cfg.health_check_failure_threshold
        last_tick = time.monotonic()
        while not self._stopped.wait(interval):
            now = time.monotonic()
            # A detector that was not running cannot judge: when THIS loop
            # overslept (the whole host stalls for 4-5 s while a process
            # brings the TPU runtime up or down — measured on a v5e host,
            # CHANGES.md PR 21 — and a raylet in the same process or on
            # the same host was stalled with it), credit every node the
            # time nobody was watching instead of declaring it dead.
            stalled = (now - last_tick) - interval
            last_tick = now
            dead = []
            with self._lock:
                for node_id, info in self.nodes.items():
                    if not info["alive"]:
                        continue
                    if stalled > interval:
                        info["last_heartbeat"] += stalled
                    if now - info["last_heartbeat"] > interval * threshold:
                        info["alive"] = False
                        dead.append(node_id)
                # sweep aged object-directory tombstones (getters that still
                # care learned "evicted" long ago and reconstructed). PENDING
                # frees (freed before any seal, not yet applied) are exempt:
                # their marker must survive until the late seal arrives.
                cutoff = now - self._dir_tombstone_ttl_s
                expired = [
                    oid for oid, ts in self._dir_tombstone_ts.items()
                    if ts < cutoff
                ]
                for oid in expired:
                    e = self.object_dir.get(oid)
                    if e is not None and e.get("freed") and not e.get("free_applied"):
                        continue
                    del self._dir_tombstone_ts[oid]
                    self.object_dir.pop(oid, None)
            for node_id in dead:
                self._on_node_death(node_id)

    def _on_node_death(self, node_id: bytes) -> None:
        """Broadcast death; fail actors on that node (restart handled by owner
        resubmission in round 1 — reference restarts centrally via
        GcsActorManager::RestartActor)."""
        self._publish("node_death", {"node_id": node_id})
        with self._lock:
            self._node_seq += 1
            tomb_seq = self._node_seq
            self._queue_node_delta_locked(
                {"delta": [], "removed": [node_id], "seq": tomb_seq})
            self._node_tombstones.append((self._node_seq, node_id))
            if len(self._node_tombstones) > 1000:
                # clients older than the trimmed horizon get a full resync
                self._tombstone_floor = self._node_tombstones[-1000][0]
                del self._node_tombstones[:-1000]
            affected = [
                aid for aid, a in self.actors.items() if a.get("node_id") == node_id
            ]
            for aid in affected:
                self.actors[aid]["state"] = "DEAD"
        for aid in affected:
            self._publish("actor:" + aid.hex(), {"state": "DEAD", "reason": "node died"})
        # push-path of the delta syncer: subscribers learn of the removal
        # NOW; the 1 Hz heartbeat pull remains the reconciliation backstop
        self._flush_node_deltas()

    # ---------------- RPC: KV ----------------

    def rpc_kv_put(self, conn, msgid, p):
        with self._lock:
            ns = self._kv[p.get("ns", "default")]
            existed = p["key"] in ns
            if p.get("overwrite", True) or not existed:
                ns[p["key"]] = p["value"]
            self._mark_dirty()
        return {"added": not existed}

    def rpc_kv_get(self, conn, msgid, p):
        with self._lock:
            return {"value": self._kv[p.get("ns", "default")].get(p["key"])}

    def rpc_kv_del(self, conn, msgid, p):
        with self._lock:
            deleted = self._kv[p.get("ns", "default")].pop(p["key"], None) is not None
            self._mark_dirty()
            return {"deleted": deleted}

    def rpc_kv_keys(self, conn, msgid, p):
        prefix = p.get("prefix", b"")
        with self._lock:
            return {"keys": [k for k in self._kv[p.get("ns", "default")] if k.startswith(prefix)]}

    # ---------------- RPC: nodes ----------------

    def _bump_node_seq_locked(self, info: dict) -> None:
        """Version-stamp a node-table mutation for the delta syncer
        (reference: ray_syncer.h:86 — components exchange version-stamped
        deltas, not full snapshots)."""
        self._node_seq += 1
        info["_seq"] = self._node_seq
        nid = info.get("node_id")
        if nid is not None:
            self._node_change_log.append((self._node_seq, nid))
            cap = max(1000, 4 * len(self.nodes))
            if len(self._node_change_log) > cap:
                # trim the oldest half; readers older than the floor get a
                # full resync (same protocol as tombstone trimming)
                keep = cap // 2
                self._change_floor = self._node_change_log[-keep][0]
                del self._node_change_log[:-keep]

    def _node_view_locked(self, nid: bytes, n: dict) -> dict:
        view = {
            "node_id": nid,
            "address": n["address"],
            "resources": n["resources"],
            "labels": n["labels"],
            "alive": n["alive"],
            "available": n.get("available", n["resources"]),
            "load": n.get("load", 0),
            "pending_shapes": n.get("pending_shapes", []),
            "store_socket": n.get("store_socket", ""),
        }
        if "disk_used_frac" in n:
            view["disk_used_frac"] = n["disk_used_frac"]
        return view

    def rpc_register_node(self, conn, msgid, p):
        with self._lock:
            self.nodes[p["node_id"]] = info = {
                "node_id": p["node_id"],  # self-identifying for change log
                "address": p["address"],
                "resources": p["resources"],
                "labels": p.get("labels", {}),
                "store_socket": p.get("store_socket", ""),
                "alive": True,
                "last_heartbeat": time.monotonic(),
            }
            self._bump_node_seq_locked(info)
            self._queue_node_delta_locked({
                "delta": [self._node_view_locked(p["node_id"], info)],
                "removed": [], "seq": info["_seq"],
            })
        self._publish("node_added", {"node_id": p["node_id"], "address": p["address"]})
        self._flush_node_deltas()
        return {"ok": True}

    def rpc_heartbeat(self, conn, msgid, p):
        """Periodic resource report — the RaySyncer-gossip analog
        (reference: src/ray/common/ray_syncer/ray_syncer.h:86). With a
        `seen_seq`, the reply carries the DELTA of the node table since
        that version (changed node views + removed ids) instead of the
        raylet re-pulling the full table every tick."""
        with self._lock:
            info = self.nodes.get(p["node_id"])
            if info is None:
                return {"ok": False, "reregister": True}
            info["last_heartbeat"] = time.monotonic()
            # a REVIVAL (health-loop death then the node resumed
            # heartbeating) must re-version the entry even when no value
            # changed: peers popped it on the tombstone and only a newer
            # _seq ever re-adds it to their deltas
            changed = not info["alive"]
            info["alive"] = True
            # ...otherwise bump the sync version ONLY when a reported value
            # actually changed — every-tick bumps would degenerate each
            # delta to a full table
            for k in ("available", "load", "pending_shapes", "disk_used_frac"):
                if k in p and info.get(k) != p[k]:
                    info[k] = p[k]
                    changed = True
            if changed:
                self._bump_node_seq_locked(info)
                # push-path: peers see the new view without waiting for
                # their own next pull tick (reference: RaySyncer's pushed
                # version-stamped deltas, ray_syncer.h:86)
                self._queue_node_delta_locked({
                    "delta": [self._node_view_locked(p["node_id"], info)],
                    "removed": [], "seq": info["_seq"],
                })
            reply = {"ok": True}
            if "seen_seq" in p:
                seen = p["seen_seq"]
                reply["seq"] = self._node_seq
                if seen < self._tombstone_floor or seen < self._change_floor:
                    # history trimmed past this client: full resync
                    seen = 0
                    reply["full"] = True
                if reply.get("full"):
                    reply["delta"] = [
                        self._node_view_locked(nid, n)
                        for nid, n in self.nodes.items()
                        if n["alive"]
                    ]
                else:
                    # O(changes) read off the seq-ordered change log — a
                    # settled cluster's heartbeat must not scan N nodes
                    i = bisect.bisect_left(self._node_change_log,
                                           (seen + 1, b""))
                    seen_nids = set()
                    reply["delta"] = []
                    for _s, nid in self._node_change_log[i:]:
                        if nid in seen_nids:
                            continue
                        seen_nids.add(nid)
                        n = self.nodes.get(nid)
                        if n is not None and n["alive"] and \
                                n.get("_seq", 0) > seen:
                            reply["delta"].append(
                                self._node_view_locked(nid, n))
                j = bisect.bisect_left(self._node_tombstones, (seen + 1, b""))
                reply["removed"] = [
                    nid for _seq, nid in self._node_tombstones[j:]
                ]
        self._flush_node_deltas()
        return reply

    def rpc_drain_node(self, conn, msgid, p):
        with self._lock:
            info = self.nodes.get(p["node_id"])
            if info is not None:
                info["alive"] = False
        self._on_node_death(p["node_id"])
        return {"ok": True}

    def rpc_get_nodes(self, conn, msgid, p):
        with self._lock:
            return {
                "nodes": [
                    self._node_view_locked(nid, n)
                    for nid, n in self.nodes.items()
                ]
            }

    def rpc_cluster_resources(self, conn, msgid, p):
        total: dict[str, float] = defaultdict(float)
        available: dict[str, float] = defaultdict(float)
        with self._lock:
            for n in self.nodes.values():
                if not n["alive"]:
                    continue
                for k, v in n["resources"].items():
                    total[k] += v
                for k, v in n.get("available", n["resources"]).items():
                    available[k] += v
        return {"total": dict(total), "available": dict(available)}

    # ---------------- RPC: object directory ----------------

    def rpc_object_location_update(self, conn, msgid, p):
        """Batched, ORDERED location updates from a raylet's store-event
        stream. p: {node_id, events: [["s"|"e", oid], ...]} — order matters:
        evict-then-reseal within one batch must end as present."""
        nid = p["node_id"]
        now = time.monotonic()
        late_frees: list[tuple[bytes, bytes]] = []  # (node_id, oid)
        with self._lock:
            for ev, oid in p["events"]:
                e = self.object_dir.get(oid)
                if ev == "s":
                    if e is None:
                        e = self.object_dir[oid] = {"nodes": set(), "evicted": False}
                    e["nodes"].add(nid)
                    e["evicted"] = False
                    self._dir_tombstone_ts.pop(oid, None)
                    if e.get("freed"):
                        # owner freed this object before it was ever sealed
                        # (fire-and-forget task result): free it now
                        e["free_applied"] = True
                        self._dir_tombstone_ts[oid] = now  # sweepable again
                        late_frees.append((nid, oid))
                else:
                    if e is None:
                        continue
                    e["nodes"].discard(nid)
                    if not e["nodes"]:
                        e["evicted"] = True  # tombstone: owners reconstruct
                        self._dir_tombstone_ts[oid] = now
        for nid_, oid in late_frees:
            self._free_on_node(nid_, oid)
        return {"ok": True}

    def _free_on_node(self, node_id: bytes, oid: bytes) -> None:
        try:
            self._raylet(node_id).call_async("free_object", {"object_id": oid})
        except Exception:  # noqa: BLE001 — holder died; nothing to free
            pass

    def rpc_free_object(self, conn, msgid, p):
        """Owner reports zero references: release the object's copies
        everywhere (reference: zero-ref plasma free driven by the owner's
        ReferenceCounter). Idempotent; copies sealed later are freed on
        arrival via the 'freed' flag."""
        oid = p["object_id"]
        with self._lock:
            e = self.object_dir.get(oid)
            if e is None:
                e = self.object_dir[oid] = {"nodes": set(), "evicted": False}
            e["freed"] = True
            holders = list(e["nodes"])
            if holders:
                # applied now: the entry may age out via the tombstone sweep
                e["free_applied"] = True
                self._dir_tombstone_ts.setdefault(oid, time.monotonic())
            # else: PENDING free (result not sealed yet) — the sweep skips
            # unapplied frees so a late seal still gets unpinned, however
            # late (bounded by in-flight fire-and-forget tasks)
        for nid in holders:
            self._free_on_node(nid, oid)
        return {"ok": True}

    def rpc_get_object_locations(self, conn, msgid, p):
        oid = p["object_id"]
        with self._lock:
            e = self.object_dir.get(oid)
            if e is None:
                return {"nodes": [], "evicted": False, "known": False}
            alive = [
                {"node_id": nid, "address": self.nodes[nid]["address"]}
                for nid in e["nodes"]
                if nid in self.nodes and self.nodes[nid]["alive"]
            ]
            # every holder died: the object is lost (reconstructible only
            # via lineage) — report it as evicted
            lost = not alive and (e["evicted"] or bool(e["nodes"]))
            return {"nodes": alive, "evicted": lost, "known": True}

    # ---------------- RPC: jobs ----------------

    def rpc_next_job_id(self, conn, msgid, p):
        with self._lock:
            self._job_counter += 1
            self._mark_dirty()
            return {"job_id": self._job_counter.to_bytes(4, "little")}

    # ---------------- RPC: actors ----------------

    def rpc_register_actor(self, conn, msgid, p):
        with self._lock:
            self.actors[p["actor_id"]] = {
                "state": "PENDING_CREATION",
                "class_name": p.get("class_name", ""),
                "name": p.get("name"),
                "node_id": None,
                "raylet_address": None,
                "num_restarts": 0,
                "max_restarts": p.get("max_restarts", 0),
            }
            self._mark_dirty()
        return {"ok": True}

    def rpc_update_actor(self, conn, msgid, p):
        aid = p["actor_id"]
        with self._lock:
            actor = self.actors.get(aid)
            if actor is None:
                return {"ok": False}
            actor.update(
                {k: p[k] for k in ("state", "node_id", "raylet_address", "worker_id") if k in p}
            )
            if p.get("increment_restarts"):
                actor["num_restarts"] += 1
            self._mark_dirty()
            snapshot = dict(actor)
        self._publish("actor:" + aid.hex(), snapshot)
        return {"ok": True}

    def rpc_get_actor(self, conn, msgid, p):
        with self._lock:
            actor = self.actors.get(p["actor_id"])
            return {"actor": dict(actor) if actor else None}

    def rpc_get_named_actor(self, conn, msgid, p):
        with self._lock:
            for aid, a in self.actors.items():
                if a.get("name") == p["name"] and a["state"] != "DEAD":
                    return {"actor_id": aid, "actor": dict(a)}
        return {"actor_id": None, "actor": None}

    def rpc_list_actors(self, conn, msgid, p):
        with self._lock:
            return {
                "actors": [
                    dict(a, actor_id=aid) for aid, a in self.actors.items()
                ]
            }

    # ---------------- RPC: placement groups ----------------

    def rpc_create_placement_group(self, conn, msgid, p):
        """Two-phase bundle reservation across raylets
        (reference: gcs_placement_group_scheduler.cc:884)."""
        pg_id = p["pg_id"]
        bundles: list[dict[str, float]] = p["bundles"]
        strategy = p.get("strategy", "PACK")
        with self._lock:
            nodes = {
                nid: dict(n) for nid, n in self.nodes.items() if n["alive"]
            }
        placement = sched.schedule_bundles(bundles, strategy, nodes)
        if placement is None:
            with self._lock:
                self.placement_groups[pg_id] = {
                    "bundles": bundles,
                    "strategy": strategy,
                    "state": "PENDING",
                    "allocations": None,
                }
                self._mark_dirty()
            return {"ok": False, "state": "PENDING",
                    "reason": "infeasible or insufficient resources"}

        # Phase 1: prepare on each raylet.
        prepared: list[tuple[bytes, int]] = []
        ok = True
        for bundle_index, node_id in enumerate(placement):
            try:
                r = self._raylet(node_id).call(
                    "prepare_bundle",
                    {"pg_id": pg_id, "bundle_index": bundle_index,
                     "resources": bundles[bundle_index]},
                    timeout=10,
                )
                if not r.get("ok"):
                    ok = False
                    break
                prepared.append((node_id, bundle_index))
            except Exception:
                ok = False
                break
        if not ok:
            for node_id, bundle_index in prepared:
                try:
                    self._raylet(node_id).call(
                        "cancel_bundle", {"pg_id": pg_id, "bundle_index": bundle_index}
                    )
                except Exception:
                    pass
            return {"ok": False, "state": "PENDING", "reason": "prepare failed"}
        # Phase 2: commit. A node dying mid-commit rolls back the whole
        # group so no prepared reservation leaks.
        committed: list[tuple[bytes, int]] = []
        try:
            for node_id, bundle_index in prepared:
                self._raylet(node_id).call(
                    "commit_bundle", {"pg_id": pg_id, "bundle_index": bundle_index}
                )
                committed.append((node_id, bundle_index))
        except Exception:
            for node_id, bundle_index in prepared:
                try:
                    self._raylet(node_id).call(
                        "cancel_bundle",
                        {"pg_id": pg_id, "bundle_index": bundle_index},
                    )
                except Exception:
                    pass
            return {"ok": False, "state": "PENDING", "reason": "commit failed"}
        with self._lock:
            self.placement_groups[pg_id] = {
                "bundles": bundles,
                "strategy": strategy,
                "state": "CREATED",
                "allocations": [
                    {"node_id": nid, "bundle_index": bi} for nid, bi in prepared
                ],
            }
            self._mark_dirty()
        self._publish("pg:" + pg_id.hex(), {"state": "CREATED"})
        return {"ok": True, "state": "CREATED",
                "allocations": self.placement_groups[pg_id]["allocations"]}

    def rpc_remove_placement_group(self, conn, msgid, p):
        pg_id = p["pg_id"]
        with self._lock:
            pg = self.placement_groups.get(pg_id)
        if pg and pg.get("allocations"):
            for alloc in pg["allocations"]:
                try:
                    self._raylet(alloc["node_id"]).call(
                        "return_bundle",
                        {"pg_id": pg_id, "bundle_index": alloc["bundle_index"]},
                    )
                except Exception:
                    pass
        with self._lock:
            if pg_id in self.placement_groups:
                self.placement_groups[pg_id]["state"] = "REMOVED"
            self._mark_dirty()
        return {"ok": True}

    def rpc_get_placement_group(self, conn, msgid, p):
        with self._lock:
            pg = self.placement_groups.get(p["pg_id"])
            return {"pg": dict(pg) if pg else None}

    # ---------------- RPC: pubsub ----------------

    def rpc_subscribe(self, conn, msgid, p):
        with self._lock:
            self._subs[p["topic"]].add(conn)
        conn.on_close.append(lambda c: self._unsub_all(c))
        return {"ok": True}

    def rpc_unsubscribe(self, conn, msgid, p):
        with self._lock:
            self._subs[p["topic"]].discard(conn)
        return {"ok": True}

    def _unsub_all(self, conn) -> None:
        with self._lock:
            for subs in self._subs.values():
                subs.discard(conn)

    def rpc_publish(self, conn, msgid, p):
        self._publish(p["topic"], p["payload"])
        return {"ok": True}

    # ---------------- RPC: task events (observability) ----------------

    def rpc_add_task_events(self, conn, msgid, p):
        cfg = global_config()
        with self._lock:
            self._task_events.extend(p["events"])
            overflow = len(self._task_events) - cfg.task_events_buffer_size
            if overflow > 0:
                del self._task_events[:overflow]
            self._mark_dirty()
        return {"ok": True}

    def rpc_list_task_events(self, conn, msgid, p):
        with self._lock:
            events = list(self._task_events)
        if p and p.get("job_id"):
            events = [e for e in events if e.get("job_id") == p["job_id"]]
        if p and p.get("trace_id"):
            # server-side trace filter: one trace's fetch cost no longer
            # scales with total task-event volume (tracing.get_trace)
            events = [e for e in events if e.get("trace_id") == p["trace_id"]]
        if p and p.get("limit"):
            # newest-first cap — a post-mortem wants the tail, not the head
            events = events[-int(p["limit"]):]
        return {"events": events}


# ---------------- client-side internal-KV helpers ----------------
#
# The internal KV has always been server-complete (rpc_kv_* above,
# persisted with the rest of the GCS tables when the store is durable)
# but had no Python client path; the Serve controller's crash-recovery
# checkpoints are the first consumer (reference:
# gcs_kv_manager.h:138 InternalKVInterface — every Ray component stores
# restart-survivable state there rather than in process memory).
# Keys and values are bytes on the wire; ``ns`` scopes independent
# consumers into separate keyspaces.


def kv_put(key: bytes, value: bytes, *, ns: str = "default") -> bool:
    """Store ``key`` -> ``value`` in the GCS internal KV. One RPC, one
    atomic dict assignment server-side — a reader sees the old value or
    the new one, never a torn write. Returns True when the key is new."""
    from ray_tpu._private.worker import global_worker

    r = global_worker().gcs.call(
        "kv_put", {"key": key, "value": value, "ns": ns, "overwrite": True}
    )
    return bool(r.get("added"))


def kv_get(key: bytes, *, ns: str = "default") -> bytes | None:
    """Fetch a value from the GCS internal KV (None when absent)."""
    from ray_tpu._private.worker import global_worker

    return global_worker().gcs.call("kv_get", {"key": key, "ns": ns})["value"]


def kv_del(key: bytes, *, ns: str = "default") -> bool:
    """Delete a key from the GCS internal KV; True if it existed."""
    from ray_tpu._private.worker import global_worker

    r = global_worker().gcs.call("kv_del", {"key": key, "ns": ns})
    return bool(r.get("deleted"))
