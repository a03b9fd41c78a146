"""Race/memory-sanitizer builds of the native components (SURVEY.md §5.2;
reference: Ray's CI runs TSAN/ASAN build configs over the C++ core rather
than shipping sanitizer code in-tree — same approach here: the SAME
sources compile under -fsanitize and run a concurrency-heavy workload;
any data race or heap error fails the test through the sanitizer's
report."""
from __future__ import annotations

import os
import pathlib
import subprocess
import threading
import time

import numpy as np
import pytest

from ray_tpu._private.ids import ObjectID
from ray_tpu._private.native_build import build_native
from ray_tpu._private.object_store import _CPP_DIR, ObjectStoreClient

STORE_SRC = os.path.join(_CPP_DIR, "store.cpp")
SCHED_SRC = os.path.join(_CPP_DIR, "sched.cpp")


def _run_store_workload(binary: str, tmp_path, env_extra: dict) -> str:
    """Spawn the (sanitized) store daemon, hammer it from concurrent
    clients with create/seal/get/wait/delete under LRU pressure, then
    shut down cleanly. Returns the daemon's captured stderr."""
    sock = str(tmp_path / "store.sock")
    errfile = open(tmp_path / "store.err", "wb")
    proc = subprocess.Popen(
        [binary, sock, str(4 * 1024 * 1024), str(tmp_path / "spill"), "1024"],
        stdout=subprocess.PIPE, stderr=errfile,
        env={**os.environ, **env_extra},
    )
    try:
        assert b"READY" in proc.stdout.readline()

        def worker(seed: int):
            rng = np.random.default_rng(seed)
            client = ObjectStoreClient(sock)
            for i in range(120):
                oid = ObjectID(bytes([seed]) + rng.bytes(ObjectID.SIZE - 1))
                size = int(rng.integers(1024, 256 * 1024))
                try:
                    buf = client.create(oid, size)
                    buf[:8] = b"x" * 8
                    client.seal(oid)
                    if i % 3 == 0:
                        got = client.get(oid, timeout_ms=100)
                        del got
                    if i % 5 == 0:
                        client.wait_objects([oid], 1, timeout_ms=50)
                    if i % 4 == 0:
                        client.delete(oid)
                except Exception:
                    # pressure-evicted/failed creates are fine; the test's
                    # subject is the sanitizer report, not the workload
                    pass
            client.close()

        threads = [threading.Thread(target=worker, args=(s,))
                   for s in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)
        errfile.close()
    time.sleep(0.2)
    return (tmp_path / "store.err").read_bytes().decode(errors="replace")


@pytest.mark.slow
def test_store_daemon_clean_under_tsan(tmp_path):
    binary = build_native(
        STORE_SRC, "ray_tpu_store_tsan",
        ["-O1", "-g", "-std=c++17", "-pthread", "-fsanitize=thread"],
        ["-lrt"])
    err = _run_store_workload(
        binary, tmp_path,
        {"TSAN_OPTIONS": "halt_on_error=0 exitcode=66"})
    assert "ThreadSanitizer" not in err, f"data race(s):\n{err[:4000]}"


@pytest.mark.slow
def test_store_daemon_clean_under_asan(tmp_path):
    binary = build_native(
        STORE_SRC, "ray_tpu_store_asan",
        ["-O1", "-g", "-std=c++17", "-pthread", "-fsanitize=address"],
        ["-lrt"])
    err = _run_store_workload(
        binary, tmp_path,
        {"ASAN_OPTIONS": "detect_leaks=0 exitcode=66"})
    assert "AddressSanitizer" not in err, f"heap error(s):\n{err[:4000]}"


def test_no_bare_except_in_serving_path():
    """Failure-semantics lint (ISSUE 2): the LLM serving path and the
    chaos harness must never swallow exceptions with a bare ``except:`` —
    fault propagation (EngineDiedError fan-out, failover retry
    classification) depends on errors reaching their handlers typed."""
    import ast
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[1]
    targets = sorted((root / "ray_tpu" / "serve" / "llm").rglob("*.py"))
    targets.append(root / "ray_tpu" / "_private" / "chaos.py")
    assert targets, "serving path sources not found"
    offenders = []
    for path in targets:
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if isinstance(node, ast.ExceptHandler) and node.type is None:
                offenders.append(f"{path.relative_to(root)}:{node.lineno}")
    assert not offenders, f"bare except clauses: {offenders}"


def test_device_values_cross_host_only_in_host_tokens():
    """Serving-perf lint (ISSUE 3/5/6): the engine's device->host traffic
    is ONE O(batch) int32 token sync per step, in ``_host_tokens``
    (executor.py — enforced for BOTH executors, single-device and
    sharded; the engine goes through ``executor.sync_tokens``). Any other
    ``np.asarray``/``np.array``/``.item()``/``device_get`` in serve/llm
    is a hidden device sync (or a smuggled O(vocab) transfer) in the
    scheduler hot loop, and under the dispatch-ahead pipeline a stray
    sync also collapses the lag — under a sharded executor it would
    additionally serialize every chip in the mesh. The speculative path
    (ISSUE 9) is held to the same bar: the drafter proposes from host
    Python ints it already has (``drafter.py`` must stay device-free)
    and the verify step's packed verdicts come back through the same
    ``_host_tokens`` funnel (``executor.sync_verify``). Allowlist:
    ``_host_tokens`` (THE sync point), ``_host_blocks`` (the
    disaggregated-handoff KV export — an explicit bulk pull OFF the
    emit path, ISSUE 11 — and, since ISSUE 15, the host-tier demote
    capture), and kv_cache's ``_block_key`` (hashes host-side Python
    int lists — never touches a device value).

    The host KV tier (ISSUE 15) is additionally pinned to the executor
    funnel by construction: serve/llm code outside executor.py/engine.py
    must never call the executor's device-boundary methods
    (``export_blocks``/``land_blocks``/``copy_blocks``/``sync_tokens``/
    ``sync_verify``) directly. kv_cache.py stages demotes through the
    engine-installed ``demote_fn`` indirection and queues promotions
    for the engine's ONE batched ``land_blocks`` drain per step — a
    direct call from the cache (or the drafter, or api.py) would be a
    new device sync point outside the dispatch funnel."""
    import ast
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[1]
    targets = sorted((root / "ray_tpu" / "serve" / "llm").rglob("*.py"))
    assert targets, "serving path sources not found"
    # executor.py (the single/sharded executor seam) must be among the
    # lint targets — it owns the device<->host boundary now
    assert any(p.name == "executor.py" for p in targets), (
        "executor.py missing from serve/llm lint targets"
    )
    # the speculative-decoding drafter must be covered too: it runs in
    # the scheduler hot loop before every decode dispatch, so a device
    # pull (or even a numpy materialization) there stalls every step
    assert any(p.name == "drafter.py" for p in targets), (
        "drafter.py missing from serve/llm lint targets"
    )
    # grammar-constrained decoding (ISSUE 16) is covered by the same
    # bar: FSM cursors advance on the already-synced host ids from
    # _host_tokens and the mask table is pure numpy — structured.py
    # must never pull a device value (zero new sync points)
    assert any(p.name == "structured.py" for p in targets), (
        "structured.py missing from serve/llm lint targets"
    )
    allowed = {
        ("executor.py", "_host_tokens"),
        ("executor.py", "_host_blocks"),
        ("kv_cache.py", "_block_key"),
    }

    offenders = []
    for path in targets:
        tree = ast.parse(path.read_text(), filename=str(path))
        # map each node to its enclosing function name
        parents: dict[ast.AST, str] = {}

        def tag(node, fn):
            for child in ast.iter_child_nodes(node):
                name = fn
                if isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    name = child.name
                parents[child] = name
                tag(child, name)

        tag(tree, "<module>")
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not isinstance(f, ast.Attribute):
                continue
            sync_like = (
                # np.asarray(x)/np.array(x) materializes x on host
                f.attr in ("asarray", "array")
                and isinstance(f.value, ast.Name)
                and f.value.id == "np"
            ) or (
                # x.item() / jax.device_get(x) are scalar/array pulls
                f.attr in ("item", "device_get")
            )
            if not sync_like:
                continue
            fn = parents.get(node, "<module>")
            if (path.name, fn) in allowed:
                continue
            offenders.append(f"{path.relative_to(root)}:{node.lineno} ({fn})")
    assert not offenders, (
        f"device->host sync outside executor._host_tokens: {offenders}"
    )

    # second pass: the executor's device-boundary methods are callable
    # only from the funnel modules themselves (executor.py defines them,
    # engine.py drives them under the dispatch lock)
    funnel_methods = {
        "export_blocks", "land_blocks", "copy_blocks",
        "sync_tokens", "sync_verify",
    }
    funnel_files = {"executor.py", "engine.py"}
    boundary_offenders = []
    for path in targets:
        if path.name in funnel_files:
            continue
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in funnel_methods):
                boundary_offenders.append(
                    f"{path.relative_to(root)}:{node.lineno} "
                    f"({node.func.attr})")
    assert not boundary_offenders, (
        "executor device-boundary methods called outside the "
        f"executor/engine funnel: {boundary_offenders}"
    )


def test_handoff_retry_paths_never_swallow_silently():
    """Failure-semantics lint (ISSUE 11): the KV-handoff state machine is
    built out of typed ``except`` fallbacks — seal retries on a survivor,
    fetch falls back to decode-local prefill, sweeps shrug off a dead
    store — and each one is only safe because the failure is OBSERVABLE.
    An except handler in those retry paths that neither re-raises nor
    logs turns a chaos fault into a silent behavior change (the stream
    still completes, so nothing downstream notices the handoff quietly
    stopped working). Every handler in the handoff functions (api.py)
    and the mid-stream RESUME loop (handle.py — outside serve/llm, so
    the serving-path bare-except lint doesn't reach it) must contain a
    ``raise`` or a logging/metrics call; handle.py additionally must
    have no bare excepts anywhere. The controller's crash-recovery and
    checkpoint paths (ISSUE 12) are held to the same bar: every typed
    fallback there (checkpoint write failed -> retry, replica dead ->
    drop, orphan kill raced) changes cluster state, so a handler that
    neither raises nor logs turns a recovery decision invisible.

    The host KV tier's demote/promote paths (ISSUE 15) join the scope:
    a failed demote is a lost cache entry (counted, never a correctness
    event) and a corrupt host record is dropped and re-filled by
    recompute — both are only safe because the drop is observable. The
    router's prompt-digest computation (handle.py ``_prompt_digests``)
    degrades to plain load balancing on any error, which likewise must
    leave a trace or prefix routing can silently stop working
    fleet-wide.

    Grammar-constrained decoding (ISSUE 16) adds two degradation
    paths: a grammar compile failure (structured.py
    ``compile_grammar``) must surface as the client-visible
    GrammarError — swallowed, the request would silently run
    UNCONSTRAINED — and an FSM-advance failure (engine.py
    ``_advance_fsm_locked``) terminates the stream early, which is
    only diagnosable if the rejection is logged.

    Priority preemption (ISSUE 17) adds the pause/resume paths: a
    demote failure in ``demote_chain`` means a parked stream resumes
    by recompute instead of host-tier promote (correct but slow — must
    be counted and logged), and an error swallowed inside
    ``_preempt_one_locked`` / ``_maybe_resume_locked`` could strand a
    stream in ``preempted`` forever with blocks half-released."""
    import ast
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[1]
    observable_attrs = {
        "debug", "info", "warning", "error", "exception", "critical",  # log
        "inc", "set", "observe",  # metrics
    }
    scopes = {
        root / "ray_tpu" / "serve" / "llm" / "api.py": frozenset({
            "prefill_export", "_sweep_sealed", "_land_handoff",
            "_seal_handoff", "_sweep_attempts",
        }),
        root / "ray_tpu" / "serve" / "handle.py": frozenset({
            "__next__", "resume_backoff_s", "_refresh",
            "_prompt_digests",
        }),
        root / "ray_tpu" / "serve" / "llm" / "kv_cache.py": frozenset({
            "_demote_evicted", "_host_lookup", "demote_chain",
        }),
        root / "ray_tpu" / "serve" / "controller.py": frozenset({
            "_recover", "_checkpoint", "_adopt_replica",
            "_reap_orphans", "_readopt_proxies",
            # the trace plane (ISSUE 19): a span drain that fails to
            # ingest must be counted+logged, or the trace just silently
            # never assembles and the operator blames the replica
            "_ingest_trace_report",
        }),
        # TraceStore assembly: malformed spans are skipped by shape
        # check, never by a swallowed exception — any handler added to
        # these functions later must stay observable
        root / "ray_tpu" / "serve" / "trace_store.py": frozenset({
            "ingest", "_classify", "assemble",
        }),
        root / "ray_tpu" / "serve" / "llm" / "structured.py": frozenset({
            "compile_grammar",
        }),
        root / "ray_tpu" / "serve" / "llm" / "engine.py": frozenset({
            "_advance_fsm_locked", "_preempt_one_locked",
            "_maybe_resume_locked",
        }),
        # Quantized serving (ISSUE 20): the wire-format validation paths
        # must fail LOUD. A swallowed layout mismatch in unpack would
        # land int8 bytes into an f32 pool (or vice versa) and the
        # stream would keep decoding garbage; same for the quantization
        # knob itself — a typo'd kind must refuse the engine, never
        # silently fall back to f32. Name-pinning these functions also
        # guards against a rename un-linting them.
        root / "ray_tpu" / "serve" / "llm" / "kv_transfer.py": frozenset({
            "unpack_blocks", "_check_layout_match", "_record_payload",
        }),
        root / "ray_tpu" / "ops" / "quantization.py": frozenset({
            "resolve_quantization",
        }),
    }
    offenders = []
    for path, fns in scopes.items():
        src = path.read_text()
        # the scoped functions must exist — a rename would un-lint them
        for fn in fns - {"resume_backoff_s", "__next__"}:
            assert f"def {fn}(" in src, f"{path.name} lost {fn}()"
        tree = ast.parse(src, filename=str(path))
        chains: dict[ast.AST, frozenset] = {}

        def tag(node, chain):
            for child in ast.iter_child_nodes(node):
                c = chain
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    c = chain | {child.name}
                chains[child] = c
                tag(child, c)

        tag(tree, frozenset())
        for node in ast.walk(tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if path.name == "handle.py" and node.type is None:
                offenders.append(
                    f"{path.relative_to(root)}:{node.lineno} (bare except)")
                continue
            if not (chains.get(node, frozenset()) & fns):
                continue
            observable = False
            for sub in ast.walk(node):
                if isinstance(sub, ast.Raise):
                    observable = True
                    break
                if (isinstance(sub, ast.Call)
                        and isinstance(sub.func, ast.Attribute)
                        and sub.func.attr in observable_attrs):
                    observable = True
                    break
            if not observable:
                offenders.append(
                    f"{path.relative_to(root)}:{node.lineno} "
                    "(handler neither raises nor logs)")
    assert not offenders, f"silent drops in handoff retry paths: {offenders}"


def test_one_clock_in_llm_serving_path():
    """Observability lint (ISSUE 4): every duration/timestamp in
    serve/llm flows through obs.clock / obs.wall — a stray
    ``time.time()``, ``time.perf_counter()`` or ``time.thread_time()``
    (obs.thread_cpu) elsewhere in the engine produces step records,
    histograms, and timelines that disagree about what was measured. ``time.monotonic``/``time.sleep`` stay allowed
    (deadline math and the watchdog poll are not measurements). The
    preemption scheduler (ISSUE 17) raises the stakes: queue-wait
    pressure, starvation aging, and parked-time histograms all compare
    engine-stamped clocks — a second clock source would make an aged
    request look young (or vice versa) and break the starvation floor."""
    import ast
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[1]
    targets = sorted((root / "ray_tpu" / "serve" / "llm").rglob("*.py"))
    assert targets, "serving path sources not found"
    forbidden = {"time", "perf_counter", "thread_time"}
    offenders = []
    for path in targets:
        if path.name == "obs.py":
            continue  # THE clock module
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if (
                isinstance(f, ast.Attribute)
                and f.attr in forbidden
                and isinstance(f.value, ast.Name)
                and f.value.id == "time"
            ):
                offenders.append(f"{path.relative_to(root)}:{node.lineno}")
    assert not offenders, (
        f"raw clock reads outside serve/llm/obs.py: {offenders}"
    )


def test_one_clock_in_autoscaling_control_plane():
    """Autoscaling lint (ISSUE 10): scale decisions and snapshot freshness
    must be judged on the SAME clock the engine stamps its snapshots with
    (obs.clock / obs.wall). A bare ``time.time()``/``time.monotonic()``/
    ``time.perf_counter()`` in the policy module or in the controller's
    aggregation path silently compares engine clock stamps against a
    different timebase, so snapshot TTLs (and therefore up/down decisions)
    drift. Scope: all of serve/autoscaling_policy.py, plus the
    controller's snapshot-aggregation functions — lifecycle deadline math
    elsewhere in the controller legitimately uses time.monotonic.

    The crash-recovery paths (ISSUE 12) are pinned the same way: the
    checkpoint persists drain deadlines as remaining-time measured on
    obs.clock and stamps written_at/recovered_at with obs.wall, so a
    stray raw clock in _checkpoint/_recover would resume a drain
    against a timebase the checkpoint was never measured on.

    The fleet metrics plane (ISSUE 13) rides the same rule: ingest
    stamps order last-write gauges and the history ring, so the polling
    functions must stamp with the controller's obs.clock — a raw clock
    there would interleave history samples from two timebases.

    The trace plane and SLO monitor (ISSUE 19) extend the scope: the
    TraceStore orders eviction by ingest stamp and the burn-rate
    evaluator slices the SAME history rings by window — a raw clock in
    trace ingest/push, in ``_evaluate_slos``, or anywhere in
    serve/slo.py or serve/trace_store.py would compare ring stamps
    against a timebase they were never measured on, shifting every
    window edge."""
    import ast
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[1]
    banned = {"time", "monotonic", "perf_counter"}
    aggregation_fns = frozenset(
        {"_aggregate_inflight", "_aggregate_signals", "_poll_snapshots",
         "_poll_fleet_metrics", "_poll_proxy_metrics",
         "_ingest_self_metrics"})
    recovery_fns = frozenset(
        {"_recover", "_checkpoint", "_build_checkpoint_locked",
         "_adopt_replica"})
    trace_slo_fns = frozenset(
        {"_ingest_trace_report", "trace_push", "_evaluate_slos"})

    def raw_clock_calls(path, within=None):
        tree = ast.parse(path.read_text(), filename=str(path))
        chains: dict[ast.AST, frozenset] = {}

        def tag(node, chain):
            for child in ast.iter_child_nodes(node):
                c = chain
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    c = chain | {child.name}
                chains[child] = c
                tag(child, c)

        tag(tree, frozenset())
        out = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if within is not None and not (
                chains.get(node, frozenset()) & within
            ):
                continue
            f = node.func
            if (
                isinstance(f, ast.Attribute)
                and f.attr in banned
                and isinstance(f.value, ast.Name)
                and f.value.id == "time"
            ):
                out.append(f"{path.relative_to(root)}:{node.lineno}")
        return out

    policy = root / "ray_tpu" / "serve" / "autoscaling_policy.py"
    controller = root / "ray_tpu" / "serve" / "controller.py"
    # the scoped functions must exist — a rename would silently un-lint them
    ctrl_src = controller.read_text()
    for fn in aggregation_fns | recovery_fns | trace_slo_fns:
        assert f"def {fn}(" in ctrl_src, f"controller lost {fn}()"
    offenders = raw_clock_calls(policy)
    offenders += raw_clock_calls(
        controller, within=aggregation_fns | recovery_fns | trace_slo_fns)
    offenders += raw_clock_calls(root / "ray_tpu" / "serve" / "slo.py")
    offenders += raw_clock_calls(
        root / "ray_tpu" / "serve" / "trace_store.py")
    assert not offenders, (
        f"raw clock reads in the autoscaling control plane: {offenders}"
    )


def test_decode_attention_path_never_materializes_kv():
    """Decode- and prefill-perf lint (ISSUE 8, extended by ISSUE 18): the
    paged attention call graphs must stay fused. ``gather_kv``
    materializes [B, NB*bs, Hkv, hd] per layer per step and
    ``jnp.repeat`` blows compact GQA KV heads up rep x — either one
    silently reintroduces the O(T) HBM traffic the paged kernels exist to
    avoid. Scope: all of ops/paged_attention.py (the Pallas kernels and
    both dispatchers), all of models/cached.py (the cached steps' one
    skeleton, the cache side of every attention layer included) and
    everything lexically inside what a family hands that skeleton —
    ``_cached_embed``, ``_cached_layer`` and the q/k/v helper the layer
    calls, in gpt.py, llama.py and lfm2_moe.py — where calling kv_cache's
    ``paged_prefill_attention`` directly is ALSO banned: it would bypass
    the ``prefill_attention`` backend dispatcher, silently pinning the
    path to the gather formulation —, and — for the XLA fallback's GQA
    math — the repeat ban alone in kv_cache's paged attention functions
    (``gather_kv`` is the dense formulation's legitimate core)."""
    import ast
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[1]

    def offending_calls(path, banned, within=None):
        """(lineno, name) of calls to `banned` names in `path` — restricted,
        when `within` is given, to calls whose ANCESTOR function chain
        touches one of those names (decode steps nest closures, so tagging
        only the innermost function would miss the scan body)."""
        tree = ast.parse(path.read_text(), filename=str(path))
        chains: dict[ast.AST, frozenset] = {}

        def tag(node, chain):
            for child in ast.iter_child_nodes(node):
                c = chain
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    c = chain | {child.name}
                chains[child] = c
                tag(child, c)

        tag(tree, frozenset())
        out = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if within is not None and not (chains.get(node, frozenset()) & within):
                continue
            f = node.func
            if isinstance(f, ast.Name):
                name = f.id
            elif isinstance(f, ast.Attribute):
                name = f.attr
            else:
                continue
            if name in banned:
                out.append(f"{path.relative_to(root)}:{node.lineno} ({name})")
        return out

    # the dispatcher module must exist under its linted name and keep
    # exporting both dispatchers — a rename would silently un-lint it
    dispatcher = root / "ray_tpu" / "ops" / "paged_attention.py"
    dispatcher_src = dispatcher.read_text()
    for fn in ("decode_attention", "prefill_attention"):
        assert f"def {fn}(" in dispatcher_src, (
            f"ops/paged_attention.py lost the {fn}() dispatcher"
        )

    offenders = []
    offenders += offending_calls(
        dispatcher, banned={"gather_kv", "repeat"},
    )
    # what each family hands the skeleton (models/cached.py): a rename
    # must not un-lint it, so every scoped name has to be a function there
    cached_paths = {
        "gpt.py": {"_cached_embed", "_cached_layer", "_attn_qkv"},
        "llama.py": {"_cached_embed", "_cached_layer", "_attn_qkv"},
        "lfm2_moe.py": {"_cached_embed", "_cached_layer", "_qkv"},
    }
    for model, within in cached_paths.items():
        path = root / "ray_tpu" / "models" / model
        defined = {
            n.name for n in ast.walk(ast.parse(path.read_text()))
            if isinstance(n, ast.FunctionDef)
        }
        assert within <= defined, (
            f"models/{model} lost {sorted(within - defined)}: the lint "
            f"below would pass without reading the family's cached path"
        )
        # compact GQA heads reach ``attend``; the prefill / verify paths
        # go through the backend dispatcher, never the XLA fallback
        offenders += offending_calls(
            path,
            banned={"gather_kv", "repeat", "paged_prefill_attention"},
            within=within,
        )
    # the cached steps' shared part is models/cached.py: all of it
    offenders += offending_calls(
        root / "ray_tpu" / "models" / "cached.py",
        banned={"gather_kv", "repeat", "paged_prefill_attention"},
    )
    offenders += offending_calls(
        root / "ray_tpu" / "ops" / "kv_cache.py",
        banned={"repeat"},
        within={"paged_attention", "paged_prefill_attention",
                "_paged_prefill_streaming"},
    )
    assert not offenders, (
        f"materializing ops in the paged attention paths: {offenders}"
    )


@pytest.mark.parametrize("name", [
    "write_kv", "prefill_attention", "decode_attention", "sample_tokens",
    "verify_tokens",
])
def test_cached_step_is_written_once(name):
    """ISSUE 28: the cache side of an attention layer (``write_kv``, then
    ``prefill_attention`` or ``decode_attention``) and the sampling
    epilogue (``sample_tokens`` / ``verify_tokens``) are called from
    models/cached.py and from no family's file: a family supplies its
    embedding, ONE layer function, norm and head, and the step's skeleton
    is not copied again. Neither called nor imported elsewhere under
    ray_tpu/models/."""
    import ast
    import pathlib

    models = pathlib.Path(__file__).resolve().parents[1] / "ray_tpu" / "models"

    def uses(path):
        out = []
        for node in ast.walk(ast.parse(path.read_text(), str(path))):
            if isinstance(node, ast.Call):
                f = node.func
                called = f.id if isinstance(f, ast.Name) else getattr(
                    f, "attr", None)
                if called == name:
                    out.append(f"{path.name}:{node.lineno}")
            elif isinstance(node, ast.ImportFrom):
                if any(a.name == name for a in node.names):
                    out.append(f"{path.name}:{node.lineno} (import)")
        return out

    # the one caller must exist under its linted name: a rename of the
    # file or the op would silently un-lint the families
    assert len(uses(models / "cached.py")) >= 2, name
    offenders = [u for path in sorted(models.glob("*.py"))
                 if path.name != "cached.py" for u in uses(path)]
    assert not offenders, f"{name} outside models/cached.py: {offenders}"


def _model_imports():
    """``(file, line, module, names)`` of every import under
    ray_tpu/models/ that names a ``ray_tpu`` module."""
    import ast
    import pathlib

    models = pathlib.Path(__file__).resolve().parents[1] / "ray_tpu" / "models"
    out = []
    for path in sorted(models.glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text(), str(path))):
            if isinstance(node, ast.ImportFrom) and node.module and \
                    node.module.startswith("ray_tpu"):
                out.append((path.name, node.lineno, node.module,
                            [a.name for a in node.names]))
            elif isinstance(node, ast.Import):
                out.extend((path.name, node.lineno, a.name, [])
                           for a in node.names if a.name.startswith("ray_tpu"))
    return out


def test_families_share_through_parts():
    """ISSUE 56: what two families compute the same way lives ONCE in
    models/parts.py (beside the cached step of models/cached.py), under a
    public name. A file under ray_tpu/models/ takes no underscored name
    from another file there and imports no family's module whole (its
    private names would be an attribute away): only ``cached`` and
    ``parts`` are imported as modules, a family's file at most for a
    public config or state function."""
    shared = {"cached", "parts"}
    imports = _model_imports()
    assert any(mod == "ray_tpu.models.parts" for _, _, mod, _ in imports)
    offenders = []
    for name, line, mod, names in imports:
        if mod == "ray_tpu.models":
            bad = [n for n in names if n not in shared]
        elif mod.startswith("ray_tpu.models.") and name != "__init__.py":
            bad = [n for n in names if n.startswith("_")]
            if not names:  # ``import ray_tpu.models.<file>``
                bad = [mod]
        else:
            continue
        offenders += [f"{name}:{line} {mod} {n}" for n in bad]
    assert not offenders, offenders


def test_models_import_nothing_of_serve():
    """ISSUE 56: the layering is one way. serve/llm/decode.py READS a
    family from its model file; no file under ray_tpu/models/ imports
    ``ray_tpu.serve``."""
    offenders = [f"{name}:{line} {mod}" for name, line, mod, _ in
                 _model_imports() if mod.startswith("ray_tpu.serve")]
    assert not offenders, offenders


def test_no_full_pool_dequant_outside_attention_kernels():
    """Quantized-serving lint (ISSUE 20): a quantized KV pool must be
    dequantized IN-REGISTER inside the attention paths — the Pallas
    kernels (ops/paged_attention.py, excluded from this lint: in-kernel
    dequant is the point) and the two sanctioned XLA fallbacks in
    ops/kv_cache.py (``gather_kv``, the dense formulation's legitimate
    core, and ``_paged_prefill_streaming``'s per-slab dequant). An
    ``astype``/``convert_element_type`` applied to a pool reference
    anywhere else materializes an f32 copy of cache bytes in HBM —
    silently giving back the 2-4x capacity and bandwidth win the
    quantized pool exists for. Scope: all of serve/llm, both LLM model
    families, and ops/kv_cache.py outside its allowlisted functions.
    Pool references are receivers that mention the pool parameter names
    (``cache_k``/``cache_v``/``k_layer``/``v_layer``) or a ``.k``/``.v``
    attribute of a cache-like object (``self.cache.k`` etc.)."""
    import ast
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[1]
    pool_names = {"cache_k", "cache_v", "k_layer", "v_layer"}
    allowed = {
        ("kv_cache.py", "gather_kv"),
        ("kv_cache.py", "_paged_prefill_streaming"),
    }
    targets = sorted((root / "ray_tpu" / "serve" / "llm").rglob("*.py"))
    targets += [
        root / "ray_tpu" / "models" / "cached.py",  # where the pool is
        root / "ray_tpu" / "models" / "gpt.py",
        root / "ray_tpu" / "models" / "llama.py",
        root / "ray_tpu" / "ops" / "kv_cache.py",
    ]
    # the sanctioned fallbacks must exist under their allowlisted names —
    # a rename would silently re-scope the lint
    kv_src = (root / "ray_tpu" / "ops" / "kv_cache.py").read_text()
    for _, fn in allowed:
        assert f"def {fn}(" in kv_src, f"ops/kv_cache.py lost {fn}()"

    def mentions_pool(node) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name) and sub.id in pool_names:
                return True
            if (isinstance(sub, ast.Attribute) and sub.attr in ("k", "v")
                    and isinstance(sub.value, ast.Attribute)
                    and "cache" in sub.value.attr):
                return True
            if (isinstance(sub, ast.Attribute) and sub.attr in ("k", "v")
                    and isinstance(sub.value, ast.Name)
                    and "cache" in sub.value.id):
                return True
        return False

    offenders = []
    for path in targets:
        tree = ast.parse(path.read_text(), filename=str(path))
        parents: dict[ast.AST, str] = {}

        def tag(node, fn):
            for child in ast.iter_child_nodes(node):
                name = fn
                if isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    name = child.name
                parents[child] = name
                tag(child, name)

        tag(tree, "<module>")
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            dequant_like = False
            if isinstance(f, ast.Attribute) and f.attr == "astype":
                dequant_like = mentions_pool(f.value)
            elif ((isinstance(f, ast.Attribute)
                   and f.attr == "convert_element_type")
                  or (isinstance(f, ast.Name)
                      and f.id == "convert_element_type")):
                dequant_like = any(mentions_pool(a) for a in node.args)
            if not dequant_like:
                continue
            fn = parents.get(node, "<module>")
            if (path.name, fn) in allowed:
                continue
            offenders.append(f"{path.relative_to(root)}:{node.lineno} ({fn})")
    assert not offenders, (
        "full-pool dequantization outside the attention kernels "
        f"(materializes f32 cache bytes in HBM): {offenders}"
    )


def test_metrics_registry_matches_observability_docs():
    """Metrics↔docs drift lint (ISSUE 13): the table in
    docs/OBSERVABILITY.md § Metrics claims to be the COMPLETE registry of
    metric names registered under ray_tpu/serve/. Hold both sides to it:
    every string literal passed to a ``counter``/``gauge``/``histogram``
    factory in serve code must have a table row, and every ``llm_*`` /
    ``serve_*`` name a table row documents must be registered by code —
    an undocumented metric is invisible to operators, a documented ghost
    sends them querying a series that never exists."""
    import ast
    import pathlib
    import re

    root = pathlib.Path(__file__).resolve().parents[1]

    registered: dict[str, str] = {}  # name -> first registration site
    for path in sorted((root / "ray_tpu" / "serve").rglob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            fname = f.attr if isinstance(f, ast.Attribute) else (
                f.id if isinstance(f, ast.Name) else None)
            if fname not in ("counter", "gauge", "histogram"):
                continue
            if not (node.args and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            name = node.args[0].value
            if re.match(r"^(llm|serve)_", name):
                registered.setdefault(
                    name, f"{path.relative_to(root)}:{node.lineno}")
    assert registered, "no metric registrations found under ray_tpu/serve/"

    doc = root / "docs" / "OBSERVABILITY.md"
    documented: set[str] = set()
    for line in doc.read_text().splitlines():
        if not line.lstrip().startswith("|"):
            continue  # only table rows document metrics
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        m = re.match(r"^`((?:llm|serve)_[a-z0-9_]+)(?:\{[^}]*\})?`$",
                     cells[0]) if cells else None
        if m:
            documented.add(m.group(1))
    assert documented, "no metric rows found in docs/OBSERVABILITY.md"

    undocumented = {
        n: site for n, site in registered.items() if n not in documented
    }
    ghosts = documented - set(registered)
    assert not undocumented, (
        "metrics registered without a docs/OBSERVABILITY.md row: "
        f"{undocumented}"
    )
    assert not ghosts, (
        "docs/OBSERVABILITY.md documents metrics no serve code registers: "
        f"{sorted(ghosts)}"
    )


def test_program_modules_import_nothing_from_benchmarks():
    """The layers' arrows point one way (ISSUE 44): ``ray_tpu/benchmarks/``
    holds tools that time a kernel alone on the chip and may import the
    program; no module of the program imports them back. A benchmark
    module on the serving executor's import path (as one was, for a table
    of peaks) makes every cell depend on a script nobody serves with."""
    import ast
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[1]
    pkg = root / "ray_tpu"
    offenders = []
    for path in sorted(pkg.rglob("*.py")):
        if path.is_relative_to(pkg / "benchmarks"):
            continue
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                # `from ray_tpu import benchmarks`, and relative forms
                # (`from ..benchmarks import x`, `from .. import benchmarks`)
                names = [node.module or ""] + [
                    f"{node.module or ''}.{a.name}" for a in node.names]
            else:
                continue
            if any("benchmarks" in n.split(".") for n in names):
                offenders.append(f"{path.relative_to(root)}:{node.lineno}")
    assert not offenders, (
        f"program modules import ray_tpu.benchmarks: {offenders}")


# the how-to-add-a-family text of docs/SERVING_LLM.md names files its
# reader is about to write
DOC_PLACEHOLDERS = {"models/myfam.py", "ray_tpu/models/myfam.py",
                    "config.json"}
# where a document's abbreviated path may start from
DOC_PATH_BASES = ("", "ray_tpu", "ray_tpu/serve/llm", "ray_tpu/ops",
                  "ray_tpu/models", "tests", "benchmark")


_ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("doc", [
    "README.md", "PERF.md",
    *sorted(f"docs/{p.name}" for p in (_ROOT / "docs").glob("*.md")),
])
def test_documents_name_only_files_that_exist(doc):
    """A document that sends its reader to a file that is gone (a deleted
    benchmark, a renamed test) is evidence nobody can check (ISSUE 44).
    Every backticked path with a source or record suffix, with or without
    a ``::test`` tail, must exist under the root or one of the package
    directories the documents abbreviate."""
    import re

    dead = set()
    for token in re.findall(r"`([^`\s]+)`", (_ROOT / doc).read_text()):
        m = re.match(
            r"^([\w./-]+\.(?:py|md|jsonl|json|cpp))(?:::[\w:\[\]-]+)?$",
            token)
        if not m or m.group(1) in DOC_PLACEHOLDERS:
            continue
        if not any((_ROOT / base / m.group(1)).exists()
                   for base in DOC_PATH_BASES):
            dead.add(m.group(1))
    assert not dead, f"{doc} names files that do not exist: {sorted(dead)}"


def test_head_sampling_uses_seeded_rng():
    """Trace-plane lint (ISSUE 19): head sampling in the ingress proxies
    must draw from a SEEDED ``random.Random`` instance (the repo-wide
    ``random.Random(zlib.crc32(seed))`` idiom) — never the process-global
    module functions. A bare ``random.random()`` makes the sampled share
    of traffic non-reproducible run to run (and shared global RNG state
    couples sampling to any other module-level draw in the process), so
    a trace-dependent test or incident replay can never pin down which
    requests were sampled. Scope: proxy.py and grpc_proxy.py — any call
    ``random.<fn>(...)`` on the module object other than the ``Random``
    constructor (and ``SystemRandom``, which is seeded by the OS and
    not reproducible — also banned) fails."""
    import ast
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[1]
    proxy = root / "ray_tpu" / "serve" / "proxy.py"
    grpc_proxy = root / "ray_tpu" / "serve" / "grpc_proxy.py"
    # the shared sampler factory must exist and be what the gRPC proxy
    # imports — a rename (or a second ad-hoc sampler) would un-lint it
    assert "def head_sampler(" in proxy.read_text(), (
        "proxy.py lost head_sampler()")
    assert "head_sampler" in grpc_proxy.read_text(), (
        "grpc_proxy.py no longer uses the shared head_sampler")

    offenders = []
    for path in (proxy, grpc_proxy):
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if (isinstance(f, ast.Attribute)
                    and isinstance(f.value, ast.Name)
                    and f.value.id == "random"
                    and f.attr != "Random"):
                offenders.append(
                    f"{path.relative_to(root)}:{node.lineno} "
                    f"(random.{f.attr})")
    assert not offenders, (
        f"unseeded module-global RNG in proxy head sampling: {offenders}"
    )


SCHED_DRIVER = r"""
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <vector>
extern "C" int rt_pick_node(const double*, int, const double*, const double*,
                            const uint8_t*, int, int, int, int);
int main() {
    srand(7);
    for (int trial = 0; trial < 2000; trial++) {
        int n = 1 + rand() % 64, r = 1 + rand() % 8;
        std::vector<double> avail(n * r), total(n * r), demand(r);
        std::vector<uint8_t> alive(n);
        for (int i = 0; i < n * r; i++) {
            total[i] = rand() % 16;
            avail[i] = total[i] ? rand() % (int)(total[i] + 1) : 0;
        }
        for (int i = 0; i < r; i++) demand[i] = rand() % 4;
        for (int i = 0; i < n; i++) alive[i] = rand() % 2;
        int cpu_col = (rand() % (r + 2)) - 1;      // covers -1 AND >= r
        int strategy = rand() % 3;
        int local_index = (rand() % (n + 1)) - 1;  // -1 = no local node
        int pick = rt_pick_node(demand.data(), r, avail.data(), total.data(),
                                alive.data(), n, cpu_col, strategy,
                                local_index);
        if (pick < -1 || pick >= n) { printf("BAD %d\n", pick); return 2; }
    }
    printf("SCHED_OK\n");
    return 0;
}
"""


@pytest.mark.slow
def test_scheduler_core_clean_under_asan(tmp_path):
    """The C++ scheduler kernel fuzzed under ASAN+UBSAN: out-of-bounds
    indexing on the packed resource matrices is exactly the bug class
    this core risks."""
    driver = tmp_path / "driver.cpp"
    driver.write_text(SCHED_DRIVER)
    out = tmp_path / "sched_asan"
    subprocess.run(
        ["g++", "-O1", "-g", "-fsanitize=address,undefined",
         str(driver), SCHED_SRC, "-o", str(out)],
        check=True, capture_output=True)
    r = subprocess.run([str(out)], capture_output=True, text=True,
                       timeout=120,
                       env={**os.environ, "ASAN_OPTIONS": "detect_leaks=0"})
    assert r.returncode == 0, (r.stdout, r.stderr[-3000:])
    assert "SCHED_OK" in r.stdout
    assert "AddressSanitizer" not in r.stderr and "runtime error" not in r.stderr, r.stderr[:3000]
