"""Test harness: virtual 8-device CPU mesh + cluster fixtures.

Mirrors the reference's strategy (reference: python/ray/tests/conftest.py:410
ray_start_regular / :491 ray_start_cluster fixtures; fake accelerators per
SURVEY.md §4.3): all distributed logic is testable on one machine — JAX tests
run on an 8-device virtual CPU mesh, cluster tests on the in-process
multi-raylet harness.
"""
from __future__ import annotations

import contextlib
import os

# Must be set before the first jax backend initialization.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# Persistent XLA compilation cache, shared across test processes AND the
# worker/replica subprocesses they spawn (env vars inherit; config calls
# would not). The suite compiles the same tiny models dozens of times —
# every serve-cluster fixture pays the full jit chain per replica process —
# and the tier-1 wall-clock budget is tight enough that those duplicate
# compiles matter. Keyed by jax version + backend + program hash, so hits
# return byte-identical executables; thresholds are zeroed because the
# tiny-model compiles this suite repeats are individually sub-second.
# It stays ONE directory for every worker: an entry's key holds the
# directory's path, and ``--dist load`` hands a worker other tests every
# run, so a directory a worker never warms (PR 56: 270-340 s for six
# modules that take 116-143 s on the shared one).
_cache_dir = os.path.join(
    os.environ.get("TMPDIR", "/tmp"), "ray_tpu_jax_test_cache"
)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", _cache_dir)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
# The one test hook for the Pallas interpreter (ops/attention.py
# pallas_interpret): kernels are compiled unless this is set, and the CPU
# backend cannot compile them. Worker/replica subprocesses inherit it.
os.environ.setdefault("RAY_TPU_PALLAS_INTERPRET", "1")
# Strict wire-schema validation (schema.py): GCS rejects malformed payloads
# in tests so message drift fails loudly at the RPC boundary.
os.environ.setdefault("RAY_TPU_STRICT_SCHEMA", "1")

import pytest

# `kill -USR1 <pid>` makes a test process (an xdist worker that hangs, say)
# write every thread's stack to its stderr — under pytest's capture that is
# the deleted file behind /proc/<pid>/fd/2, which can still be read.
import faulthandler
import signal

faulthandler.register(signal.SIGUSR1, all_threads=True)

# Force the CPU platform for the WHOLE test process now, before any test
# module touches jax: backend selection is one-shot, and a test that
# device_puts on an attached TPU first would leave the session fixture with
# the chip's devices instead of the 8-device virtual mesh.
try:
    import jax

    jax.config.update("jax_platforms", "cpu")
except Exception:  # noqa: BLE001 — jax missing or already initialized
    pass


@contextlib.contextmanager
def shutdown_if_setup_fails():
    """For fixtures that start a runtime of their own: wrap what comes
    between ``ray_tpu.init()`` and the ``yield``. When that part raises
    (a port that is taken, a deployment that never gets healthy) the
    runtime is shut down before the error propagates — a leaked one fails
    every later test on the worker with "init() called twice"."""
    try:
        yield
    except BaseException:
        import ray_tpu
        from ray_tpu import serve

        try:
            serve.shutdown()
        except Exception:  # noqa: BLE001 — serve may not have started
            pass
        ray_tpu.shutdown()
        raise


def serve_http_url(path: str) -> str:
    """URL of ``path`` on this process's HTTP proxy. Cluster fixtures start
    it on port 0: under ``--dist load`` two workers can each hold one
    module's cluster at once, and a fixed port fails the second's set-up
    ("address already in use")."""
    from ray_tpu.serve import api

    return f"http://127.0.0.1:{api._proxy.port}{path}"


def _force_cpu_jax():
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass


@pytest.fixture(scope="session")
def jax_cpu():
    """8 virtual CPU devices for mesh/sharding tests."""
    _force_cpu_jax()
    import jax

    devices = jax.devices()
    assert len(devices) >= 8, f"need 8 virtual devices, got {len(devices)}"
    return jax


def _mappings() -> int:
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:  # no procfs: nothing to count, nothing to guard
        return 0


try:
    with open("/proc/sys/vm/max_map_count") as _f:
        _MAX_MAPPINGS = int(_f.read())
except (OSError, ValueError):
    _MAX_MAPPINGS = 65530  # the kernel's default


@pytest.hookimpl(trylast=True)
def pytest_runtest_teardown(item, nextitem):
    """Keep a test process under the kernel's limit of memory mappings.

    A loaded XLA:CPU executable holds about 60 mappings and jax's caches
    keep every one a process ever ran; an xdist worker of the whole suite
    loads over a thousand. At ``vm.max_map_count`` (65,530) the next
    ``mmap`` fails inside ``backend.deserialize_executable`` and the worker
    dies there (``Segmentation fault`` / ``Aborted`` under
    ``compilation_cache.get_executable_and_time``): five or six workers a
    run, each once, a different test each time (PR 56 sampled the workers:
    those lost had last stood at 64,625, 65,045 and 65,440). So past half
    the limit the caches are dropped; what is needed again comes back from
    the persistent cache above. Between modules, after the last one's
    fixtures are gone (``trylast``), since a test that counts compiles does
    so within its module; past three quarters, wherever it is."""
    same_module = nextitem is not None and (
        getattr(nextitem, "module", None) is getattr(item, "module", None))
    if _mappings() > (0.75 if same_module else 0.5) * _MAX_MAPPINGS:
        import gc

        import jax

        jax.clear_caches()
        gc.collect()


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Per-test wall-clock ceiling: ``@pytest.mark.timeout(seconds)``.

    The fault-tolerance tests intentionally wedge engines; a bug in the
    watchdog/failover path must fail THAT test fast, not eat the tier-1
    budget. Implemented here (pytest-timeout is not in the image): the
    test body runs on a daemon thread and an expiry fails the test. The
    abandoned thread keeps running — acceptable for a test process,
    matching pytest-timeout's "thread" method semantics.
    """
    import threading

    marker = pyfuncitem.get_closest_marker("timeout")
    if marker is None:
        return None
    seconds = float(marker.args[0]) if marker.args else 60.0
    args = {
        name: pyfuncitem.funcargs[name]
        for name in pyfuncitem._fixtureinfo.argnames
    }
    result: dict = {}

    def run():
        try:
            pyfuncitem.obj(**args)
        except BaseException as e:  # noqa: BLE001 — re-raised on main thread
            result["error"] = e

    t = threading.Thread(target=run, daemon=True, name=f"timeout-{pyfuncitem.name}")
    t.start()
    t.join(seconds)
    if t.is_alive():
        pytest.fail(
            f"test exceeded timeout marker ({seconds}s)", pytrace=False
        )
    if "error" in result:
        raise result["error"]
    return True


@pytest.fixture
def chaos_plan():
    """Install a deterministic fault plan for this test.

    Usage: ``chaos_plan(FaultPlan(faults=(Fault(...),)))`` — activates
    in-process (for direct engine tests) AND exports RAY_TPU_CHAOS_PLAN so
    worker processes spawned AFTER the call inherit it (cluster tests must
    therefore install the plan before ``ray_tpu.init``/``serve.run``).
    Cleared on teardown either way.
    """
    from ray_tpu._private import chaos

    prev = os.environ.get(chaos.ENV_VAR)

    def _install(plan):
        os.environ[chaos.ENV_VAR] = plan.to_json()
        return chaos.install(plan)

    yield _install
    chaos.clear()
    if prev is None:
        os.environ.pop(chaos.ENV_VAR, None)
    else:
        os.environ[chaos.ENV_VAR] = prev


@pytest.fixture
def ray_start(request):
    """Fresh single-node cluster per test; params override init kwargs."""
    import ray_tpu

    kwargs = getattr(request, "param", {}) or {}
    kwargs.setdefault("num_cpus", 4)
    if ray_tpu.is_initialized():
        # a runtime leaked by an earlier test's failed setup must not
        # fail every later test on this worker with "init() called twice"
        ray_tpu.shutdown()
    ray_tpu.init(**kwargs)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def ray_cluster():
    """In-process multi-node cluster harness."""
    from ray_tpu._private.node import Cluster
    import ray_tpu
    from ray_tpu._private.ids import JobID
    from ray_tpu._private.worker import CoreWorker, set_global_worker

    cluster = Cluster(head_resources={"CPU": 2})
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
    yield cluster
    core.shutdown()
    set_global_worker(None)
    cluster.shutdown()


# ---------------------------------------------------------------------------
# The widened dispatch-ahead pipeline (serve/llm/engine.py), one schedule for
# every model family: tests/test_serve_llm_decode.py and the family files run
# it on their own tiny engines.
# ---------------------------------------------------------------------------

PIPELINE_LENS = (3, 11, 5, 40, 7, 19)
PIPELINE_NEWS = (4, 9, 6, 12, 3, 10)  # staggered: a row leaves every few steps


def watch_pipeline(eng) -> dict:
    """Count, on a hand-stepped engine, the step programs launched and not
    yet synced (``most``: never above two), and hold every flush of the
    block quarantine to its rule: what a flush ``upto`` a step's number
    gives back was freed with no NEWER step queued, what it keeps was
    (the engine frees nothing between a launch and the reconcile of the
    step before it, so it keeps none today: the cache's own test does)."""
    seen = {"out": 0, "most": 0, "flushes": 0}
    ex, cache = eng.executor, eng.cache

    def launching(fn):
        def run(*a, **kw):
            seen["out"] += 1
            seen["most"] = max(seen["most"], seen["out"])
            return fn(*a, **kw)
        return run

    for name in ("prefill", "prefill_chunk", "decode_step"):
        setattr(ex, name, launching(getattr(ex, name)))
    sync = ex.sync_tokens

    def synced(tokens):
        seen["out"] -= 1
        return sync(tokens)

    ex.sync_tokens = synced
    flush = cache.flush_quarantine

    def flushed(upto=None):
        before = list(cache._quarantine)
        n = flush(upto)
        seen["flushes"] += 1
        kept = cache._quarantine
        assert before[n:] == kept, "a flush gives back the oldest first"
        if upto is not None:
            assert all(fence <= upto for fence, _ in before[:n])
            assert all(fence > upto for fence, _ in kept)
        free = set(cache._free)
        assert not free & {b for _, b in kept}, "a quarantined block is free"
        return n

    cache.flush_quarantine = flushed
    return seen


def assert_pool_clean(eng) -> None:
    """Every block back exactly once; what stays reserved is the room a
    family with windowed layers sets aside for prefill, whole again."""
    snap = eng.cache.debug_snapshot()
    for key in ("used_blocks", "quarantined_blocks", "live_sequences"):
        assert snap[key] == 0, snap
    assert snap["reserved_blocks"] == eng._kv_room, snap
    assert snap["freed_total"] == snap["allocated_total"], snap


def run_widened_schedule(make, vocab: int, **sampling) -> dict:
    """Three requests, three steps, three more that join in mid-stream, all
    with staggered ``max_new_tokens``, and one cancelled in flight.
    ``make(**kw)`` builds a hand-stepped engine. Streams must be the bytes
    of solo runs, the pipeline must have
    been kept across every finish and every join (``steady`` records over
    another batch than the step before, ids gathered on the device), every
    prefill synced behind the next launch, never three programs in flight,
    every block back exactly once. -> the engine's ``stats()``."""
    import numpy as np

    from ray_tpu.exceptions import RequestCancelledError

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, vocab, size=n).tolist() for n in PIPELINE_LENS]
    solo = []
    for p, n in zip(prompts, PIPELINE_NEWS):
        e = make()
        solo.append(e.generate(p, max_new_tokens=n, **sampling))
        e.shutdown()
    eng = make(max_batch_size=4)
    seen = watch_pipeline(eng)
    streams = [eng.submit(p, max_new_tokens=n, **sampling)
               for p, n in zip(prompts[:3], PIPELINE_NEWS[:3])]
    for _ in range(3):
        eng.step()
    streams += [eng.submit(p, max_new_tokens=n, **sampling)
                for p, n in zip(prompts[3:], PIPELINE_NEWS[3:])]
    # and one that is cancelled with a step program that holds it in flight
    victim = eng.submit(prompts[1][::-1], max_new_tokens=30, **sampling)
    for _ in range(2):
        eng.step()
    assert eng.stats()["decode_inflight"] == 1
    assert eng.cancel(victim.request_id) is True
    for _ in range(2000):
        if all(s.done for s in streams):
            break
        eng.step()
    while eng.step():
        pass
    assert [list(s) for s in streams] == solo
    with pytest.raises(RequestCancelledError):
        list(victim)

    st = eng.stats()
    assert seen["most"] == 2 == st["steps_inflight_high_water"]
    assert seen["out"] == 0 == st["decode_inflight"]
    assert seen["flushes"] >= st["decode_steps"] + st["prefill_steps"]
    steps = [r for r in eng.debug_dump()["steps"] if r["kind"] != "compile"]
    decodes = [r for r in steps if r["kind"] == "decode" and r["batch"]]
    prefills = [r for r in steps if r["kind"].startswith("prefill")]
    # nothing in this traffic syncs before a launch: every decode step was
    # launched behind a step in flight, the first behind its rows' prefill
    assert all(r["steady"] for r in decodes), decodes
    assert st["decode_steps_steady"] == st["decode_steps"] == len(decodes)
    shrank = [b for a, b in zip(decodes, decodes[1:])
              if b["batch"] < a["batch"]]
    grew = [b for a, b in zip(decodes, decodes[1:])
            if b["batch"] > a["batch"]]
    assert shrank and grew, "the schedule has a finish and a join"
    assert all(r["remapped"] for r in shrank + grew)
    assert st["decode_steps_remapped"] == sum(r["remapped"] for r in decodes)
    assert 0 < st["decode_steps_remapped"] < st["decode_steps"]
    # a prefill's ids reach the host behind the launch that follows it
    assert all(r["sync_lag"] == 1 for r in prefills), prefills
    assert st["prefill_syncs_deferred"] == st["prefill_steps"] == len(prefills)
    # a decode record's sync is the step before it: one launch sat between
    assert {r["sync_lag"] for r in decodes if "sync_lag" in r} == {1}
    assert_pool_clean(eng)
    eng.shutdown()
    return st
