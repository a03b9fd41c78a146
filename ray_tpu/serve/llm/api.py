"""LLMDeployment — the engine wrapped as a streaming Serve deployment.

One engine per replica; each HTTP/gRPC/handle call becomes one engine
request, and because the replica runs up to max_ongoing_requests method
threads concurrently (serve/replica.py), concurrent callers' sequences
CONTINUOUSLY BATCH inside the shared engine — the scheduler interleaves
them at the decode-step level, not the request level. Tokens stream out
through every existing ingress: the DeploymentHandle generator path, HTTP
server-sent events, and the gRPC server-streaming RPC (all three are
exercised by examples/serve_streaming_llm.py).

Prompts are token-id lists, or strings encoded with the built-in
byte-level tokenizer (token = UTF-8 byte value; any vocab >= 256 works) —
a real BPE vocabulary plugs in by passing token ids directly.

Repeat traffic with shared prompt prefixes (system prompts, few-shot
headers) is served from the engine's block-granular KV prefix cache —
``stats()`` exposes ``prefix_hit_tokens`` / ``prefix_hit_rate`` /
``prefix_cached_blocks`` / ``prefix_evicted_blocks`` / ``cow_blocks`` per
replica alongside the PR 1/2 fields (docs/SERVING_LLM.md "Prefix caching
& chunked prefill").

Failure semantics (docs/SERVING_LLM.md): every chunk carries
``(request_id, index)`` where ``index`` is the ABSOLUTE token position,
so a client (``stream_tokens`` / ``DeploymentHandle.stream_with_failover``)
can resume a stream on a surviving replica after this one dies: it
re-submits ``prompt`` plus ``prior_tokens`` (the tokens it already has)
and the engine re-prefills; sampling is keyed per (seed, absolute
position) on device, so the resumed stream is byte-identical to an
uninterrupted one by construction — no RNG state to replay.
"""
from __future__ import annotations

import logging
import os
import uuid
from collections import OrderedDict
from typing import Any

from ray_tpu._private import chaos
from ray_tpu._private.compile_cache import enable_compile_cache
from ray_tpu.exceptions import EngineOverloadedError
from ray_tpu.serve.deployment import Application, deployment
from ray_tpu.serve.llm import obs
from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.util import metrics, tracing

logger = logging.getLogger("ray_tpu.serve.llm")

# external request ids whose engine-internal id we remember after the
# stream finished, so request_timeline() works post-hoc
_RECENT_REQUESTS = 512

# Disaggregated prefill/decode handoff knobs (docs/SERVING_LLM.md
# "Disaggregated prefill/decode"): how long a prefill replica keeps a
# sealed-but-unclaimed KV object before its periodic sweep deletes it
# (clients sweep their own attempts when the stream ends; this TTL is
# the backstop for clients that died mid-handoff), how long the decode
# side waits on a fetch before falling back to local prefill, and how
# long the client waits on one seal attempt.
_HANDOFF_TTL_S = 120.0
_HANDOFF_FETCH_TIMEOUT_S = 10.0
_HANDOFF_SEAL_TIMEOUT_S = 30.0


def encode_text(prompt: str, vocab_size: int) -> list[int]:
    """Byte-level encoding: one token per UTF-8 byte (folded into the
    vocab for the tiny test configs)."""
    return [b % vocab_size for b in prompt.encode("utf-8")]


def decode_token(token: int) -> str:
    """Inverse of encode_text for printable bytes; empty otherwise."""
    return chr(token) if 32 <= token < 127 else ""


@deployment(max_ongoing_requests=8)
class LLMDeployment:
    """Streaming LLM deployment. Bind with an EngineConfig (or dict of its
    fields): ``serve.run(LLMDeployment.bind(EngineConfig(...)))``.

    Multi-chip replicas: pass ``mesh=`` (a ``ModelParallelConfig``, a
    ``parallel.MeshSpec``, a built ``jax.sharding.Mesh``, or a dict of
    axis sizes) — or set ``tp``/``fsdp`` on the EngineConfig itself — and
    the replica's engine runs the tp/fsdp ShardedExecutor over that mesh
    (docs/SERVING_LLM.md "Sharded serving"). Defaults stay single-device;
    request payloads, streaming, failover, and the prefix cache are
    identical either way — a stream started on a sharded replica resumes
    byte-identically on a single-chip one and vice versa."""

    def __init__(
        self,
        engine_config: EngineConfig | dict | None = None,
        mesh: Any = None,
        prefill: Any = None,
    ):
        if isinstance(engine_config, dict):
            engine_config = EngineConfig(**engine_config)
        if mesh is not None:
            import dataclasses

            engine_config = dataclasses.replace(
                engine_config or EngineConfig(), mesh=mesh
            )
        # the replica compiles every bucket's prefill/chunk/decode step:
        # keep them in the one persistent cache
        self._compile_cache = enable_compile_cache()
        self.engine = LLMEngine(engine_config)
        # Disaggregated serving: binding a prefill Application here makes
        # serve.run deploy both pools as one app (Application.flatten);
        # the handle itself is only introspected — the handoff state
        # machine runs client-side in stream_tokens.
        self._prefill = prefill
        # sealed handoff objects this (prefill) replica still owns:
        # object-id hex -> obs.clock() seal time, swept by TTL
        self._sealed: OrderedDict[str, float] = OrderedDict()
        self._handoff_sealed_total = 0
        self._handoff_landed_blocks = 0
        self._handoff_fallbacks = 0
        self._m_handoff_blocks = metrics.counter(
            "llm_handoff_blocks",
            "KV blocks landed on this replica from handoff payloads",
        )
        self._m_handoff_retries = metrics.counter(
            "llm_handoff_retries",
            "Handoff attempts that were retried or fell back to "
            "decode-local prefill",
        )
        # external request_id -> engine-internal id, for cancel()
        self._active: dict[str, Any] = {}
        # same mapping, kept (bounded) after completion for
        # request_timeline() lookups on finished streams
        self._recent: OrderedDict[str, Any] = OrderedDict()
        self._resumed_total = 0
        self._m_resumed = metrics.counter(
            "llm_requests_resumed",
            "Streams resumed on this replica after another replica died",
        )
        # graceful-drain latch (controller-driven scale-down): a draining
        # replica admits nothing new; in-flight streams finish or hand off
        self._draining = False

    def __call__(self, payload: dict | None):
        """Generator: one chunk per generated token.

        payload: {"prompt": str | [int], "max_new_tokens"?, "temperature"?,
        "top_k"?, "top_p"?, "seed"?, "request_id"?, "deadline_s"?,
        "prior_tokens"?, "response_format"?, "stop"?, "priority"?}.
        ``priority`` is the scheduling class ("interactive" | "default" |
        "batch" — docs/SERVING_LLM.md "Priority & preemption"); the
        proxies inject it from the ``x-ray-tpu-priority`` header/metadata
        key. It orders preemption and class-aware shedding and never
        changes tokens.
        ``response_format`` selects grammar-constrained decoding
        (serve/llm/structured.py): ``"json"``/``"json_object"`` or an
        OpenAI-shaped dict ({"type": "json_schema", "schema": ...} /
        {"type": "regex", "pattern": ...}); invalid or unsatisfiable
        grammars fail the request with a ``ValueError`` (HTTP 400 /
        gRPC INVALID_ARGUMENT at the proxies). ``stop`` is a list of
        stop sequences — strings (byte-level encoded like the prompt)
        or token-id lists — that terminate the stream once emitted.
        Chunks: {"request_id": str, "token": id, "index": i, "text": str}
        where ``index`` is absolute — a resumed stream continues the
        numbering of the stream it replaces.
        """
        if self._draining:
            # Scale-down marked this replica draining; the routing table
            # already excludes it, so only a dispatch racing the table
            # refresh lands here. EngineOverloadedError is the retryable
            # "go elsewhere" signal: failover resumes re-dispatch to a
            # survivor, fresh requests get 503 + Retry-After.
            raise EngineOverloadedError(
                "replica is draining for scale-down; retry another replica"
            )
        payload = payload or {}
        prompt = payload.get("prompt", "")
        if isinstance(prompt, str):
            prompt = encode_text(prompt, self.engine.model_cfg.vocab_size)
        prompt = [int(t) for t in prompt]
        request_id = str(payload.get("request_id") or uuid.uuid4().hex)
        prior = [int(t) for t in payload.get("prior_tokens") or ()]
        max_new = int(payload.get("max_new_tokens", 16))
        if prior:
            self._resumed_total += 1
            self._m_resumed.inc()
            if len(prior) >= max_new:
                return  # the dead replica already delivered everything
        handoff = payload.get("kv_handoff")
        if handoff:
            # Land prefilled KV blocks from the object plane BEFORE
            # submit, so admission sees the prefix hit. Failure of any
            # kind degrades to decode-local chunked prefill — a torn
            # handoff must never become a dead stream.
            self._land_handoff(
                prompt, handoff, tag=payload.get("chaos_tag")
            )
        deadline_s = payload.get("deadline_s")
        stop = []
        for seq in payload.get("stop") or ():
            if isinstance(seq, str):
                seq = encode_text(seq, self.engine.model_cfg.vocab_size)
            stop.append(tuple(int(t) for t in seq))
        sampling = SamplingParams(
            max_new_tokens=max_new - len(prior),
            temperature=float(payload.get("temperature", 0.0)),
            top_k=int(payload.get("top_k", 0)),
            top_p=float(payload.get("top_p", 1.0)),
            seed=int(payload.get("seed", 0)),
            deadline_s=float(deadline_s) if deadline_s is not None else None,
            start_index=len(prior),
            structured=payload.get("response_format"),
            stop=tuple(stop),
            priority=str(payload.get("priority", "default")),
        )
        # the replica method runs inside a task_span when the caller was
        # traced — hand that context to the engine so its phase spans join
        # the same trace, and stamp the trace id on every chunk so a
        # resumed stream can assert trace continuity across replicas
        trace_ctx = tracing.current_context()
        trace_id = trace_ctx["trace_id"] if trace_ctx else None
        stream = self.engine.submit(
            prompt + prior, sampling, trace_ctx=trace_ctx
        )
        self._active[request_id] = stream.request_id
        self._recent[request_id] = stream.request_id
        while len(self._recent) > _RECENT_REQUESTS:
            self._recent.popitem(last=False)
        try:
            for i, tok in enumerate(stream):
                index = len(prior) + i
                chunk = {
                    "request_id": request_id,
                    "token": int(tok),
                    "index": index,
                    "text": decode_token(tok),
                }
                if trace_id is not None:
                    chunk["trace_id"] = trace_id
                yield chunk
                chaos.fire(
                    "llm.token",
                    index=index,
                    resumed=bool(prior),
                    tag=payload.get("chaos_tag"),
                )
        finally:
            self._active.pop(request_id, None)

    def cancel(self, request_id: str) -> bool:
        """Evict ``request_id`` and free its KV blocks now. Idempotent and
        safe to broadcast: replicas not serving the stream return False."""
        internal = self._active.get(str(request_id))
        if internal is None:
            return False
        return self.engine.cancel(internal)

    def check_health(self) -> None:
        """Controller health-check hook: a failed engine (step raised or
        watchdog fired) reports unhealthy so the replica gets replaced."""
        if self.engine.failed:
            raise RuntimeError("llm engine failed; replica must be replaced")

    def stats(self) -> dict:
        """Engine introspection (unary method — callable via handle)."""
        out = self.engine.stats()
        out["requests_resumed"] = self._resumed_total
        out["compile_signatures"] = sorted(self.engine.fns.signatures)
        out["compile_cache"] = dict(self._compile_cache)
        out["pid"] = os.getpid()
        return out

    def request_timeline(self, request_id: str) -> dict | None:
        """Phase timeline of one EXTERNAL request id — live or recently
        finished on this replica; None if this replica never served it
        (broadcast to find the owner, like cancel)."""
        internal = self._active.get(str(request_id))
        if internal is None:
            internal = self._recent.get(str(request_id))
        if internal is None:
            return None
        return self.engine.request_timeline(internal)

    def debug_dump(self) -> dict:
        """Flight-recorder ring + engine/cache stats (the payload behind
        the proxy's /debug/llm endpoint)."""
        out = self.engine.debug_dump()
        out["requests_resumed"] = self._resumed_total
        out["draining"] = self._draining
        out["handoff"] = self.handoff_stats()
        return out

    # ---------------- autoscaling & graceful drain ----------------

    def autoscaling_snapshot(self) -> dict:
        """Engine saturation signals for the controller's autoscaler
        (docs/SERVING_LLM.md "Autoscaling & graceful drain"). The
        ``llm.snapshot`` chaos point sits here so the load harness can
        delay/jitter snapshot reporting deterministically."""
        chaos.fire("llm.snapshot")
        out = self.engine.autoscaling_snapshot()
        out["draining"] = self._draining
        out["active_streams"] = len(self._active)
        return out

    def prepare_drain(self) -> dict:
        """Controller scale-down hook: stop admitting, keep serving.

        After this returns, new ``__call__`` dispatches are refused with
        ``EngineOverloadedError`` while every in-flight stream keeps
        decoding; the controller polls ``drain_status`` and finishes (or
        kills — the failover path hands the streams to survivors
        byte-identically) once the replica is idle or the drain deadline
        expires. Idempotent."""
        self._draining = True
        chaos.fire("replica_drain", active=len(self._active))
        return self.drain_status()

    def drain_status(self) -> dict:
        return {
            "draining": self._draining,
            "active_streams": len(self._active),
        }

    def finish_drain(self) -> dict:
        """Terminal drain step, called by the controller once no streams
        are active: returns every KV block (allocations, reservations,
        quarantine, prefix cache) to the pool via the engine's
        ``release_all`` shutdown path and reports the final accounting so
        the caller can assert the pool is leak-free before the actor is
        killed."""
        self.engine.shutdown()
        snap = self.engine.cache.debug_snapshot()
        return {
            "released": True,
            "leaked_blocks": snap["used_blocks"],
            "cache": snap,
        }

    # ---------------- disaggregated prefill/decode handoff ----------------

    def prefill_export(self, payload: dict | None) -> dict | None:
        """PREFILL-pool entrypoint: run the payload's prompt through
        normal (chunked, prefix-cached) prefill, serialize its full
        prompt blocks with the kv_transfer wire format, seal them into
        the object store under a deterministic per-attempt id, and
        return the manifest the client forwards to the decode pool.

        Returns None when there is nothing worth handing off (prompt
        shorter than one block, or no blocks resident after prefill) —
        the client then simply dispatches without ``kv_handoff`` and the
        decode replica prefills locally. Idempotent per (request_id,
        attempt): re-driving a seal writes the same object id, and an
        already-sealed object is left as-is."""
        from ray_tpu._private.worker import global_worker_or_none
        from ray_tpu.serve.llm import kv_transfer

        if self._draining:
            raise EngineOverloadedError(
                "replica is draining for scale-down; retry another replica"
            )
        payload = payload or {}
        prompt = payload.get("prompt", "")
        if isinstance(prompt, str):
            prompt = encode_text(prompt, self.engine.model_cfg.vocab_size)
        prompt = [int(t) for t in prompt]
        request_id = str(payload.get("request_id") or uuid.uuid4().hex)
        attempt = int(payload.get("attempt", 0))
        if attempt > 0:
            self._m_handoff_retries.inc()
        self._sweep_sealed()
        bs = self.engine.cache.cfg.block_size
        worker = global_worker_or_none()
        if len(prompt) < bs or worker is None:
            return None
        # Normal engine path with a 1-token budget: chunked prefill at
        # true positions writes the prompt's KV and registers every full
        # block in the prefix cache; the sampled token is discarded.
        # Traced callers (this method runs inside the rt_call task span)
        # get a handoff.seal span covering prefill through store put —
        # and the engine submit below inherits the active span, so the
        # PREFILL pool's engine.* spans join the same trace tree.
        sampling = SamplingParams(
            max_new_tokens=1, seed=int(payload.get("seed", 0))
        )
        with tracing.span_if_active(
            "handoff.seal", request_id=request_id, attempt=attempt,
        ):
            stream = self.engine.submit(prompt, sampling)
            for _ in stream:
                pass
            chaos.fire(
                "llm.handoff.seal",
                request_id=request_id,
                attempt=attempt,
                tag=payload.get("chaos_tag"),
            )
            records = self.engine.export_prefix(prompt)
            if not records:
                return None
            wire = kv_transfer.pack_blocks(
                self.engine.kv_layout(), records,
                prefix_tokens=len(records) * bs,
            )
            oid = kv_transfer.handoff_object_id(request_id, attempt)
            # pin=False: an orphaned handoff object stays LRU-evictable in
            # the store even if every sweeper dies
            worker.put_object(oid, wire, pin=False)
        self._sealed[oid.hex()] = obs.clock()
        self._handoff_sealed_total += 1
        return {
            "object_id": oid.hex(),
            "request_id": request_id,
            "attempt": attempt,
            "prefix_tokens": len(records) * bs,
            "num_blocks": len(records),
        }

    def _sweep_sealed(self) -> int:
        """Delete sealed handoff objects older than the TTL (leak sweep
        for clients that died between seal and stream end). Runs at the
        top of every ``prefill_export``; -> objects swept."""
        from ray_tpu._private.ids import ObjectID
        from ray_tpu._private.worker import global_worker_or_none

        worker = global_worker_or_none()
        if worker is None:
            return 0
        now = obs.clock()
        swept = 0
        while self._sealed:
            oid_hex, sealed_at = next(iter(self._sealed.items()))
            if now - sealed_at < _HANDOFF_TTL_S:
                break
            self._sealed.popitem(last=False)
            try:
                worker.store.delete(ObjectID.from_hex(oid_hex))
            except (ConnectionError, OSError) as e:
                # store daemon gone — nothing to leak into, but the
                # sweep must never take a prefill replica down
                logger.warning("handoff sweep of %s failed: %s", oid_hex, e)
            swept += 1
        return swept

    def _land_handoff(self, prompt, manifest: dict, tag=None) -> int:
        """DECODE-pool half: fetch the manifest's object, verify it, and
        adopt its blocks into this engine's prefix cache so the upcoming
        submit scores a full prefix hit. Every failure mode — evicted or
        lost object, fetch timeout, wire corruption, layout mismatch,
        injected chaos — degrades to decode-local prefill (return 0),
        never a dead stream."""
        import ray_tpu
        from ray_tpu._private.ids import ObjectID
        from ray_tpu._private.object_ref import ObjectRef
        from ray_tpu._private.worker import global_worker_or_none
        from ray_tpu.exceptions import GetTimeoutError, ObjectLostError
        from ray_tpu.serve.llm import kv_transfer

        request_id = manifest.get("request_id") or "?"
        attempt = int(manifest.get("attempt", 0))
        try:
            # traced requests see the decode-side handoff halves as
            # handoff.fetch / handoff.land spans (attempt-tagged, so a
            # retried handoff is visibly attempt>0 in the trace tree)
            with tracing.span_if_active(
                "handoff.fetch", request_id=request_id, attempt=attempt,
            ):
                chaos.fire("llm.handoff.fetch", attempt=attempt, tag=tag)
                if global_worker_or_none() is None:
                    raise kv_transfer.KVTransferError(
                        "no object plane in this process"
                    )
                oid = ObjectID.from_hex(str(manifest["object_id"]))
                wire = ray_tpu.get(
                    ObjectRef(oid), timeout=_HANDOFF_FETCH_TIMEOUT_S
                )
            with tracing.span_if_active(
                "handoff.land", request_id=request_id, attempt=attempt,
            ):
                chaos.fire("llm.handoff.land", attempt=attempt, tag=tag)
                layout, _, records = kv_transfer.unpack_blocks(wire)
                if layout != self.engine.kv_layout():
                    raise kv_transfer.KVTransferError(
                        f"layout mismatch: payload {layout} vs engine "
                        f"{self.engine.kv_layout()}"
                    )
                landed = self.engine.adopt_prefix(prompt, records)
            self._handoff_landed_blocks += landed
            if landed:
                self._m_handoff_blocks.inc(landed)
            return landed
        except (
            ObjectLostError,
            GetTimeoutError,
            kv_transfer.KVTransferError,
            chaos.ChaosFault,
            ConnectionError,
            KeyError,
            ValueError,
        ) as e:
            self._handoff_fallbacks += 1
            self._m_handoff_retries.inc()
            logger.warning(
                "KV handoff for request %s failed (%s: %s); falling back "
                "to decode-local prefill", request_id, type(e).__name__, e,
            )
            return 0

    def handoff_stats(self) -> dict:
        """Per-replica handoff accounting (unary, broadcastable): sealed
        objects still owned, blocks landed, fallbacks taken."""
        return {
            "sealed_live": len(self._sealed),
            "sealed_total": self._handoff_sealed_total,
            "landed_blocks": self._handoff_landed_blocks,
            "fallbacks": self._handoff_fallbacks,
            "adopted_blocks": self.engine.cache.stats.adopted_blocks,
        }


def stream_tokens(handle, payload: dict, *, max_failovers: int = 2,
                  prefill_handle=None, handoff_retries: int = 2):
    """Stream token chunks from an LLMDeployment handle with automatic
    mid-stream failover: if the serving replica dies, re-submit to a
    survivor with ``prior_tokens`` set to everything already received.
    Deterministic sampling makes the joined stream byte-identical to an
    uninterrupted run. Returns an iterator of chunk dicts.

    Disaggregated serving: pass ``prefill_handle`` (the LLMPrefill pool)
    and the prompt is prefilled there first — the sealed KV manifest
    rides in the payload as ``kv_handoff`` and the decode replica lands
    the blocks instead of prefilling. The seal loop is an idempotent
    retry state machine: a prefill replica killed mid-handoff is
    excluded and the next attempt (a NEW deterministic object id) runs
    on a survivor; when the pool is overloaded or ``handoff_retries``
    attempts die, the stream degrades to decode-local prefill. Every
    attempt's object id — delivered or not — is swept from the store
    when the stream ends, so dead handoffs cannot leak sealed objects.
    Byte-identity is unconditional: landed blocks are bit-exact KV for
    the same tokens, and sampling is keyed (seed, position)."""
    payload = dict(payload)
    payload.setdefault("request_id", uuid.uuid4().hex)
    attempt_oids: list[str] = []
    if prefill_handle is not None:
        manifest = _seal_handoff(
            prefill_handle, payload, attempt_oids, retries=handoff_retries
        )
        if manifest is not None:
            payload["kv_handoff"] = manifest

    def resume(chunks):
        # the resumed payload keeps kv_handoff: a decode survivor
        # re-lands the same sealed blocks (adopt is idempotent) before
        # re-prefilling whatever is missing
        resumed = dict(payload)
        resumed["prior_tokens"] = [c["token"] for c in chunks]
        return resumed

    stream = handle.stream_with_failover(
        payload, resume=resume, max_failovers=max_failovers
    )
    if not attempt_oids:
        return stream
    return _sweeping_stream(stream, attempt_oids)


def _seal_handoff(prefill_handle, payload: dict, attempt_oids: list[str],
                  *, retries: int = 2) -> dict | None:
    """Drive prefill_export attempts until one seals, the pool sheds, or
    the attempts run out. Records every attempt's deterministic object
    id in ``attempt_oids`` (even for attempts that died before replying)
    so the caller can leak-sweep them all; returns the manifest or None
    for decode-local fallback."""
    from ray_tpu.exceptions import ActorError, WorkerCrashedError
    from ray_tpu.serve.llm import kv_transfer

    request_id = str(payload["request_id"])
    req = {
        k: v for k, v in payload.items()
        if k not in ("prior_tokens", "kv_handoff")
    }
    exclude: set[str] = set()
    for attempt in range(max(1, retries + 1)):
        req = dict(req, attempt=attempt)
        attempt_oids.append(
            kv_transfer.handoff_object_id(request_id, attempt).hex()
        )
        resp = None
        try:
            resp = prefill_handle._router.call(
                "prefill_export", (req,), {}, exclude=frozenset(exclude)
            )
            return resp.result(timeout=_HANDOFF_SEAL_TIMEOUT_S)
        except EngineOverloadedError:
            # prefill pool saturated or draining — decode-local prefill
            # is the designed pressure valve, not an error
            logger.debug(
                "prefill pool overloaded for request %s; using "
                "decode-local prefill", request_id,
            )
            return None
        except (ActorError, WorkerCrashedError, ConnectionError,
                TimeoutError) as e:
            aid = getattr(resp, "replica_actor_id", None)
            if aid:
                exclude.add(aid)
            logger.warning(
                "prefill handoff attempt %d for request %s failed "
                "(%s: %s); %s", attempt, request_id, type(e).__name__, e,
                "retrying on a survivor" if attempt < retries
                else "falling back to decode-local prefill",
            )
    return None


def _sweeping_stream(stream, attempt_oids: list[str]):
    """Yield the stream, then delete every handoff attempt object —
    delivered, orphaned by a killed prefill replica, or never created
    (delete is idempotent). Runs on normal completion AND on failure/
    generator close, so a dead client path can't leak sealed objects."""
    try:
        yield from stream
    finally:
        _sweep_attempts(attempt_oids)


def _sweep_attempts(attempt_oids: list[str]) -> None:
    from ray_tpu._private.ids import ObjectID
    from ray_tpu._private.worker import global_worker_or_none

    worker = global_worker_or_none()
    if worker is None:
        return
    for oid_hex in attempt_oids:
        try:
            worker.store.delete(ObjectID.from_hex(oid_hex))
        except (ConnectionError, OSError) as e:
            logger.debug("handoff sweep of %s failed: %s", oid_hex, e)


def build_llm_app(
    engine_config: EngineConfig | dict | None = None,
    *,
    mesh: Any = None,
    tp: int = 1,
    fsdp: int = 1,
    speculative_k: int | None = None,
    drafter: Any = None,
    prefill_replicas: int = 0,
    prefill_options: dict | None = None,
    **deployment_options: Any,
) -> Application:
    """Convenience: ``serve.run(build_llm_app(EngineConfig(...)))``.
    ``deployment_options`` forward to ``.options(...)`` (num_replicas,
    ray_actor_options for TPU chips, ...).

    ``mesh``/``tp``/``fsdp`` select the per-replica model-parallel
    layout (they override the EngineConfig fields of the same names);
    the defaults keep every replica single-device. ``speculative_k`` /
    ``drafter`` likewise override the engine's speculative-decoding
    knobs (docs/SERVING_LLM.md "Speculative decoding") — committed
    streams stay byte-identical with speculation on or off, so mixed
    fleets (some replicas speculative, some not) fail over freely.

    ``prefill_replicas > 0`` builds DISAGGREGATED serving: a second
    deployment named ``LLMPrefill`` (``pool_role="prefill"``) joins the
    decode deployment (named ``LLMDecode``, ``pool_role="decode"``) in
    the same app, and clients pass
    ``serve.get_deployment_handle("LLMPrefill", app)`` as
    ``stream_tokens(..., prefill_handle=)`` to route prefill there.
    ``prefill_options`` overrides the prefill pool's deployment config
    (e.g. its own ``autoscaling_config`` — typically
    ``signal_mode="prefill"``, with the decode pool on
    ``signal_mode="decode"`` — so the two pools scale on disjoint
    signals and drain independently)."""
    overrides: dict = {}
    if mesh is not None or tp != 1 or fsdp != 1:
        overrides.update(mesh=mesh, tp=tp, fsdp=fsdp)
    if speculative_k is not None:
        overrides["speculative_k"] = int(speculative_k)
    if drafter is not None:
        overrides["drafter"] = drafter
    if overrides:
        import dataclasses

        if isinstance(engine_config, dict):
            engine_config = EngineConfig(**engine_config)
        engine_config = dataclasses.replace(
            engine_config or EngineConfig(), **overrides
        )
    if prefill_replicas > 0:
        popts = {
            "name": "LLMPrefill",
            "num_replicas": int(prefill_replicas),
            "pool_role": "prefill",
            **(prefill_options or {}),
        }
        prefill_app = LLMDeployment.options(**popts).bind(engine_config)
        dopts = {"name": "LLMDecode", "pool_role": "decode",
                 **deployment_options}
        return LLMDeployment.options(**dopts).bind(
            engine_config, prefill=prefill_app
        )
    dep = LLMDeployment
    if deployment_options:
        dep = dep.options(**deployment_options)
    return dep.bind(engine_config)
