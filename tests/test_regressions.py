"""Regression tests for the round-2 advisor findings:
(a) ActorPool leaks the actor when a task fails in get_next_unordered;
(b) CoreWorker's GCS client latches dead after a GCS restart-in-place;
(c) stale committed native binaries gated on mtime could be loaded;
(d) gpt/llama loss applied a token-aligned mask unshifted to shifted targets.
"""
from __future__ import annotations

import os
import socket
import tempfile
import time

import pytest


# ---------------------------------------------------------------- (a)


def test_actor_pool_failed_task_does_not_leak_actor(ray_start):
    rt = ray_start
    from ray_tpu.util.actor_pool import ActorPool

    @rt.remote
    class Worker:
        def f(self, x):
            if x == 1:
                raise ValueError("boom")
            return x * 10

    pool = ActorPool([Worker.remote()])  # single actor: a leak deadlocks it
    for v in (1, 2):
        pool.submit(lambda a, v: a.f.remote(v), v)
    results, errors = [], 0
    while pool._future_to_actor or pool._pending:
        try:
            results.append(pool.get_next_unordered(timeout=30))
        except ValueError:
            errors += 1
    assert errors == 1
    assert results == [20]
    # the actor must be back in the idle set and reusable
    pool.submit(lambda a, v: a.f.remote(v), 3)
    assert pool.get_next_unordered(timeout=30) == 30


def test_actor_pool_failed_task_ordered_returns_actor(ray_start):
    rt = ray_start
    from ray_tpu.util.actor_pool import ActorPool

    @rt.remote
    class Worker:
        def f(self, x):
            if x == 0:
                raise RuntimeError("first fails")
            return x

    pool = ActorPool([Worker.remote()])
    pool.submit(lambda a, v: a.f.remote(v), 0)
    pool.submit(lambda a, v: a.f.remote(v), 5)
    with pytest.raises(RuntimeError):
        pool.get_next(timeout=30)
    assert pool.get_next(timeout=30) == 5


# ---------------------------------------------------------------- (b)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_worker_gcs_client_heals_after_gcs_restart(tmp_path):
    """The worker's own GCS client (not just the raylet's) must reconnect
    after a GCS restart-in-place — actor resolution and task events flow
    through it (reference: raylet reconnect, node_manager.cc:1168)."""
    import ray_tpu
    from ray_tpu._private.gcs import GcsService
    from ray_tpu._private.ids import JobID, NodeID
    from ray_tpu._private.object_store import start_store
    from ray_tpu._private.raylet import Raylet
    from ray_tpu._private.store_client import FileStoreClient
    from ray_tpu._private.worker import CoreWorker, set_global_worker

    snap_path = str(tmp_path / "gcs.pkl")
    port = _free_port()
    sock = os.path.join(tempfile.mkdtemp(), "store.sock")
    store_proc = start_store(sock, 64 * 1024 * 1024)

    gcs1 = GcsService(store=FileStoreClient(snap_path))
    gcs_address = gcs1.start(port=port)
    raylet = Raylet(
        NodeID.from_random(), gcs_address, sock,
        {"CPU": 2.0, "TPU": 0.0, "memory": 2.0 * 1024**3},
    )
    core = CoreWorker(
        mode="driver", gcs_address=gcs_address, raylet_address=raylet.address,
        store_socket=sock, job_id=JobID(b"\x01\x00\x00\x00"),
        node_id=raylet.node_id,
    )
    set_global_worker(core)
    try:
        core.gcs.call("kv_put", {"key": b"cfg", "value": b"v1"})

        gcs1.stop()
        time.sleep(0.3)
        gcs2 = GcsService(store=FileStoreClient(snap_path))
        assert gcs2.start(port=port) == gcs_address

        # SAME client object, no manual replacement: the call must heal
        # itself via auto-reconnect
        assert core.gcs.call("kv_get", {"key": b"cfg"})["value"] == b"v1"

        # actor resolution (worker.gcs path) works after the restart: wait
        # for the raylet to re-register, then run an actor end-to-end
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            nodes = [n for n in core.gcs.call("get_nodes")["nodes"] if n["alive"]]
            if nodes:
                break
            time.sleep(0.2)
        assert nodes, "raylet never re-registered"

        @ray_tpu.remote
        class A:
            def ping(self):
                return "pong"

        a = A.remote()
        assert ray_tpu.get(a.ping.remote(), timeout=120) == "pong"
        gcs2.stop()
    finally:
        set_global_worker(None)
        try:
            core.shutdown()
        except Exception:
            pass
        raylet.stop()
        store_proc.terminate()


def test_rpc_client_reconnect_inplace():
    """reconnect() restores the same client object after the server bounces
    on the same port; a superseded reader can't kill new pending calls."""
    from ray_tpu._private.rpc import RpcClient, RpcServer

    class Svc:
        def rpc_echo(self, conn, msgid, payload):
            return payload

    port = _free_port()
    srv1 = RpcServer(Svc(), port=port)
    cli = RpcClient(srv1.address, auto_reconnect=True, reconnect_window=15.0)
    assert cli.call("echo", 1) == 1
    srv1.stop()
    time.sleep(0.2)
    srv2 = RpcServer(Svc(), port=port)
    assert cli.call("echo", 2) == 2  # heals within the reconnect window
    cli.close()
    srv2.stop()


# ---------------------------------------------------------------- (c)


def test_native_build_is_content_hashed(tmp_path):
    from ray_tpu._private.native_build import build_native

    src = tmp_path / "lib.cpp"
    src.write_text('extern "C" int f() { return 1; }\n')
    out1 = build_native(str(src), "lib.so", ["-O2", "-shared", "-fPIC"])
    assert os.path.exists(out1)

    import ctypes

    assert ctypes.CDLL(out1).f() == 1

    # change the source: the artifact PATH must change (a stale binary at
    # the old path can never be picked up again). build_native keeps no
    # in-process memo by design — the digest is recomputed per call — so
    # an immediate rebuild must already see the edit.
    src.write_text('extern "C" int f() { return 2; }\n')
    out2 = build_native(str(src), "lib.so", ["-O2", "-shared", "-fPIC"])
    assert out2 != out1
    assert ctypes.CDLL(out2).f() == 2


def test_no_native_binaries_in_git():
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tracked = subprocess.run(
        ["git", "ls-files", "ray_tpu/cpp"], cwd=repo,
        capture_output=True, text=True,
    ).stdout.splitlines()
    binaries = [f for f in tracked
                if not f.endswith((".cpp", ".hpp", ".h"))]
    assert binaries == [], f"compiled artifacts tracked in git: {binaries}"


# ---------------------------------------------------------------- (d)


def test_gpt_llama_loss_accepts_token_aligned_mask(jax_cpu):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from ray_tpu.models.llama import LlamaConfig, llama_init, llama_loss

    B, S1 = 2, 9  # tokens are [B, S+1]
    tokens = jax.random.randint(jax.random.PRNGKey(0), (B, S1), 0, 64)
    mask_full = jnp.ones((B, S1), jnp.float32).at[:, 5:].set(0.0)

    gcfg = GPTConfig(vocab_size=64, n_layer=1, n_head=2, d_model=16,
                     max_seq_len=16)
    gp = gpt_init(jax.random.PRNGKey(1), gcfg)
    # [B, S+1] mask must not shape-error and must equal the explicitly
    # shifted [B, S] form
    loss_full = gpt_loss(gp, {"tokens": tokens, "mask": mask_full}, gcfg)
    loss_shifted = gpt_loss(
        gp,
        {"inputs": tokens[:, :-1], "targets": tokens[:, 1:],
         "mask": mask_full[:, 1:]},
        gcfg,
    )
    assert jnp.allclose(loss_full, loss_shifted, atol=1e-5)

    lcfg = LlamaConfig(vocab_size=64, n_layer=1, n_head=2, n_kv_head=2,
                       d_model=16, d_mlp=32, max_seq_len=16,
                       attention="xla")
    lp = llama_init(jax.random.PRNGKey(2), lcfg)
    l_full = llama_loss(lp, {"tokens": tokens, "mask": mask_full}, lcfg)
    l_shift = llama_loss(
        lp,
        {"inputs": tokens[:, :-1], "targets": tokens[:, 1:],
         "mask": mask_full[:, 1:]},
        lcfg,
    )
    assert jnp.allclose(l_full, l_shift, atol=1e-5)
