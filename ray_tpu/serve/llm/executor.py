"""ModelExecutor — the seam between engine scheduling and model steps.

The engine (engine.py) is a host-side scheduler: admission, block tables,
bucketing, COW bookkeeping, timelines. Everything device-side — weights,
the paged KV pool arrays, the jitted prefill/decode calls, the single
token sync — lives behind the `ModelExecutor` interface in this module,
so "how many chips run the model" is an executor choice the scheduler
never sees. Two interchangeable implementations:

- `SingleDeviceExecutor` — exactly the PR 1-5 behavior: one chip, host
  staging arrays handed to the jitted call as they are, unsharded weights
  and KV pool. The default.
- `ShardedExecutor` — a tp/fsdp mesh over several chips (models larger
  than one chip's HBM). It builds a mesh from
  `ray_tpu.parallel.mesh.MeshSpec`, shards the weights with the same
  logical-axis rules training uses (parallel/sharding.py DEFAULT_RULES:
  heads/mlp/vocab -> tp, embed -> fsdp), and shards the paged KV pool
  along its HEAD axis over tp. Sharding propagates into the jitted steps
  GSPMD-style from the committed inputs — the process-shared jit
  wrappers in decode.py are reused as-is, so the compile-count contract
  ((prefill, prefill_chunk, decode) x bucket shapes) is frozen exactly
  as on one chip.

What stays host-side under sharding — deliberately: block tables, the
free list, prefix hashing, COW pair lists, and the quarantine are plain
Python/numpy state in kv_cache.py; only `cache.k` / `cache.v` are device
arrays, and only their placement changes. The engine's lag-1
dispatch-ahead pipeline, keyed (seed, position) sampling, and the single
O(batch) int32 `_host_tokens` sync point are executor-agnostic, so
failover resume stays byte-identical on any mesh shape — a stream begun
on a tp=2/fsdp=2 replica resumes bit-for-bit on a single-chip one.

The sanitizer lint (tests/test_sanitizers.py) enforces the sync-point
contract here exactly as it did in engine.py: `_host_tokens` below is
the ONE place in serve/llm allowed to materialize a device value.
"""
from __future__ import annotations

import logging
import math
from typing import Any

import numpy as np

from ray_tpu.serve.llm import obs
from ray_tpu.serve.llm.decode import (
    DecodeFns,
    family_param_axes,
    get_family,
)

logger = logging.getLogger("ray_tpu.serve.llm")


def _host_tokens(tokens) -> np.ndarray:
    """The ONE device->host sync point on the emit path: materialize a
    step's sampled token ids as O(batch) int32 numpy — [B] for plain
    decode/prefill, [B, W+1] packed verdicts for a speculative verify
    step (still O(batch * k) int32, never logits). All other serve/llm
    code must stay on-device (tests/test_sanitizers.py lints this) —
    for every executor, sharded included."""
    return np.asarray(tokens, np.int32)


_feed_ids = None


def feed_ids(source, feed):
    """A decode step's input ids, put together ON THE DEVICE: row ``i`` is
    ``source[feed[0, i]]`` where ``feed[0, i] >= 0`` (``source``: the
    sampled ids of a step program still in flight, never synced), else
    ``feed[1, i]``, an id the host already holds. One tiny jitted program,
    ``jit_feed_ids`` (no step program's name is part of its own), a shape
    a pair of row buckets ``(len(source), feed.shape[1])``: what lets a
    decode step be launched before the step that samples its inputs has
    been synced, whatever rows joined or left in between."""
    global _feed_ids
    if _feed_ids is None:
        import jax
        import jax.numpy as jnp

        def feed_ids(source, feed):
            index, held = feed[0], feed[1]
            taken = jnp.take(source, jnp.maximum(index, 0), mode="clip")
            return jnp.where(index >= 0, taken, held)

        _feed_ids = jax.jit(feed_ids)
    return _feed_ids(source, feed)


_pad_ids = None


def pad_ids(ids, width: int):
    """A step's sampled ids ``[rows]`` padded with zeros to ``[width]``, on
    the device (``jit_pad_ids``, a shape a pair). What keeps ``feed_ids``'
    shapes few: a packed prefill step's ids come a row a PIECE, at the row
    counts of a ladder of the engine's own, and the decode step behind it
    gathers from them, a program a (source width, row bucket) pair; handed
    on at the width of a decode row bucket they add no pair to the ones
    decode steps feed each other with."""
    global _pad_ids
    if _pad_ids is None:
        import jax
        import jax.numpy as jnp

        def pad_ids(ids, width):
            return jnp.pad(ids, (0, width - ids.shape[0]))

        _pad_ids = jax.jit(pad_ids, static_argnums=1)
    return _pad_ids(ids, width)


_feed_rows = None


def feed_rows(source, feed):
    """``feed_ids`` for a family whose decode step carries a BLOCK a row
    (decode.py ``Family.block_steps``): row ``i`` of the step's input
    ``[B, W + 1]`` (a block's ids, then the bits of its masked positions)
    is ``source[feed[i, 0]]`` where ``feed[i, 0] >= 0`` (``source``: what
    the pass in flight gives back, never synced), else ``feed[i, 1:]``,
    the row as the host holds it. ``jit_feed_rows``, a shape a pair of
    row buckets."""
    global _feed_rows
    if _feed_rows is None:
        import jax
        import jax.numpy as jnp

        def feed_rows(source, feed):
            index, held = feed[:, 0], feed[:, 1:]
            taken = jnp.take(
                source, jnp.maximum(index, 0), axis=0, mode="clip")
            return jnp.where(index[:, None] >= 0, taken, held)

        _feed_rows = jax.jit(feed_rows)
    return _feed_rows(source, feed)


def _host_blocks(kv) -> np.ndarray:
    """The SECOND allowed device->host sync, off the emit path entirely:
    materialize a handful of finished KV blocks for a disaggregated
    prefill handoff (serve/llm/kv_transfer.py wire format). This runs
    once per handed-off request on the PREFILL replica — never inside
    the decode scheduler loop — and moves O(blocks) cache bytes, which
    is the whole point of the transfer. Allowlisted by name in
    tests/test_sanitizers.py next to ``_host_tokens``. Quantized pools
    export ``QuantizedKV`` slabs — data and scale planes cross together,
    still O(blocks) bytes (2-4x fewer of them)."""
    from ray_tpu.ops.quantization import QuantizedKV

    if isinstance(kv, QuantizedKV):
        return QuantizedKV(np.asarray(kv.data), np.asarray(kv.scale))
    return np.asarray(kv)


class ModelExecutor:
    """Device-side half of the LLM engine.

    The engine stages every input as numpy (its bucketed scratch pool;
    the all-ones allow-mask of an unconstrained batch is ``ones_mask``'s
    resident array) and calls one of the methods below; the executor owns
    placement:
    where the weights live, how the paged KV pool arrays (`cache.k` /
    `cache.v`) are laid out, and which devices the jitted step runs on.
    Shared base implementation = the single-device datapath; subclasses
    change placement in ``__init__``, never the call path — GSPMD infers
    the sharded programs from the committed inputs, which is what keeps
    the compile-signature set identical across executors.

    Interface consumed by engine.py:

    - ``prefill(tokens, lengths, tables, sample=)`` — monolithic
      whole-prompt prefill; returns on-device [B] sampled token ids and
      updates ``cache.k``/``cache.v`` in place (the pools are donated to
      every step program and rebound from its outputs: ``_run``).
    - ``prefill_chunk(tokens, lengths, starts, tables, sample=)`` — the
      chunked/prefix path at true positions.
    - ``decode_step(tokens, positions, tables, sample=, feed=)`` — one
      decode step; ``tokens`` is either a host staging array (cold
      dispatch, nothing in flight: put on the device before the call, so
      that the program sees ONE form of ids) or the previous step's
      on-device array (the lag-1 steady
      feed, the same rows in the same order). With ``feed=`` (a ``[2, B]``
      host array) ``tokens`` is the on-device ids of whatever step is in
      flight and the step's ids are gathered from it on the device
      (``feed_ids``): rows joined or left, nothing is synced. The
      gather is compiled where a step shape is first run (``_warm_feed``).
    - ``verify_step(tokens, starts, draft_len, tables, sample=)`` — one
      speculative draft-and-verify step over a [B, W] window (column 0 =
      last committed token, then drafts); returns on-device packed
      [B, W+1] verdicts (ops/sampling.py ``verify_tokens``).
    - ``copy_blocks(pairs)`` — fused on-device COW block copies.
    - ``sync_tokens(tokens_dev)`` — THE O(batch) int32 host sync.
    - ``sync_verify(packed_dev)`` — the same sync point for a verify
      step's packed verdicts ([B, W+1] int32 through ``_host_tokens``).
    - ``on_new_signature`` — compile-event hook, forwarded to DecodeFns.

    The four step methods also take ``span=``, the attributes of the
    step's ``executor.dispatch`` phase, and book their two host phases
    into ``phases`` (``_run``). ``cache.state`` (decode.py
    ``Family.init_state``: what a family keeps per sequence beside the
    pool; None for the others) is passed through every step and rebound
    from its outputs like ``cache.k`` / ``cache.v``, but never donated;
    the prefill and decode methods
    take ``slots=``, each row's slot in it (None: left out of the call).
    """

    kind = "single"
    # set by build_executor from EngineConfig when speculation is on;
    # surfaced via describe() -> stats()/debug_dump()
    speculative: dict | None = None
    # ShardedExecutor defers the weights' build step until after
    # shard_params (the axes tree must match the RAW param structure;
    # quantizing or casting committed sharded arrays lets GSPMD place
    # the scale shards next to their data and keeps each leaf's sharding).
    _defer_quantize = False

    def __init__(self, family: str, model_cfg, cache, *,
                 params: dict | None = None, seed: int = 0):
        import jax

        self.family = family
        self.model_cfg = model_cfg
        self.cache = cache
        # where the step's host phases are booked (``obs.phase``); the
        # engine hands over its own table
        self.phases: dict = {}
        # ... and the span inside ``executor.stage`` (``executor.feed``),
        # in a table of its own: phases of a step add up, and this one
        # lies within another. What ``_run``'s launches moved host ->
        # device: arrays, their bytes, and the launches that moved a mask
        self.spans: dict = {}
        self.stage_transfers = 0
        self.stage_bytes = 0
        self.stage_masks = 0
        self._ones_masks: dict[tuple, Any] = {}
        # ``_warm_feed``: a step's ids by width, and the decode row
        # buckets run, whose pairs have their id gather compiled
        self._ids_seen: dict[int, Any] = {}
        self._feed_rows: set[int] = set()
        # how a decode step's ids are put together from the step in
        # flight, and the host array that says so: a row's one id
        # (``feed_ids``, ``[2, B]``), or for a family that steps by blocks
        # its block's ids and bits (``feed_rows``, ``[B, W + 2]``);
        # settled here, once
        self._gather, self._feed_shape = feed_ids, lambda B: (2, B)
        self._ids_ndim = 1  # a decode step's ids: [B], or [B, W + 1]
        if get_family(family).block_steps:
            width = model_cfg.block_length + 2
            self._gather, self._feed_shape = feed_rows, lambda B: (B, width)
            self._ids_ndim = 2
        self.fns = DecodeFns(
            family, model_cfg, platform=self._devices()[0].platform)
        self.params = (
            params
            if params is not None
            else self.fns.init(jax.random.PRNGKey(seed), model_cfg)
        )
        if not self._defer_quantize:
            self._maybe_quantize_params()
            self._store_compute_dtype()

    def _maybe_quantize_params(self) -> None:
        """Quantize the serving weights per ``model_cfg.quantization``
        (ops/quantization.quantize_params over the family's quant-axes
        tree). Init always produces f32 masters — quantization is an
        executor-build step, so the training paths and the family init
        functions never see a QuantizedTensor. No-op when the knob is
        unset or the params are already quantized (pre-built params
        handed across replicas must not double-quantize)."""
        kind = getattr(self.model_cfg, "quantization", None)
        if kind is None:
            return
        import jax

        from ray_tpu.ops.quantization import QuantizedTensor, quantize_params
        from ray_tpu.serve.llm.decode import family_quant_axes

        already = any(
            isinstance(t, QuantizedTensor)
            for t in jax.tree.leaves(
                self.params,
                is_leaf=lambda t: isinstance(t, QuantizedTensor),
            )
        )
        if already:
            return
        self.params = quantize_params(
            self.params,
            family_quant_axes(self.family, self.model_cfg),
            kind,
        )

    def _weights_by_axis(self):
        """The weights tree flattened beside the family's quant-axes
        tree: ``(leaves, axes, treedef)``, a QuantizedTensor counting as
        one leaf. An axis >= 0 marks a matmul weight; ``-1`` leaves are
        norm scales, biases and the MoE tables ``moe_forward`` reads raw."""
        import jax

        from ray_tpu.ops.quantization import QuantizedTensor
        from ray_tpu.serve.llm.decode import family_quant_axes

        leaves, treedef = jax.tree.flatten(
            self.params, is_leaf=lambda t: isinstance(t, QuantizedTensor))
        axes = treedef.flatten_up_to(
            family_quant_axes(self.family, self.model_cfg))
        return leaves, axes, treedef

    def _store_compute_dtype(self) -> None:
        """Store every matmul weight ONCE in ``model_cfg.dtype``, so the
        ``.astype(cfg.dtype)`` seams of the step programs are no-ops and
        no prefill, decode or verify call casts the weights again; the
        ``-1`` leaves stay float32. Runs after ``_maybe_quantize_params``
        and with its ordering: with ``quantization`` set it does nothing
        (int8 / fp8 come from the float32 masters), and a leaf already
        in ``cfg.dtype`` (pre-built params, float32 configs) or already
        quantized passes through. Leaf by leaf, with the tree taken off
        the executor meanwhile, so that ``leaves`` holds the executor's
        only reference to each master and lets it go as its cast is
        dispatched: a caller that does not hold the float32 tree never
        has both whole trees on the device. Whatever stops the loop, the
        executor gets a whole tree back (partly cast, and a second call
        finishes it)."""
        if getattr(self.model_cfg, "quantization", None) is not None:
            return
        import jax.numpy as jnp

        from ray_tpu.ops.quantization import QuantizedTensor

        dtype = jnp.dtype(self.model_cfg.dtype)
        leaves, axes, treedef = self._weights_by_axis()
        self.params = None
        try:
            for i, axis in enumerate(axes):
                if (axis >= 0 and not isinstance(leaves[i], QuantizedTensor)
                        and leaves[i].dtype != dtype):
                    leaves[i] = leaves[i].astype(dtype)
        finally:
            self.params = treedef.unflatten(leaves)

    # ---------------- compile-event hooks (DecodeFns pass-through) ----

    @property
    def on_new_signature(self):
        return self.fns.on_new_signature

    @on_new_signature.setter
    def on_new_signature(self, hook) -> None:
        self.fns.on_new_signature = hook

    @property
    def num_compiled_shapes(self) -> int:
        return self.fns.num_compiled_shapes

    @property
    def signatures(self) -> frozenset:
        return self.fns.signatures

    # ---------------- staging ----------------

    def ones_mask(self, shape: tuple):
        """The grammar allow-mask of a step none of whose rows is
        constrained: all ones, ``[B, words]`` uint32 (a verify window's
        ``[B, W, words]``), RESIDENT on the device — made once a shape and
        handed to every such launch, so the 0.4-0.8 MB a 64-row step's
        mask is at a real vocabulary are neither filled nor moved again.
        It takes the ``mask`` leaf's place in the ``sample`` pytree with
        the shape and dtype a staged mask has: data, not signature, the
        same program. ``sample`` is donated to no program, and nothing
        writes the array. Uncommitted, like a host array: under a mesh
        the call itself copies it to where the executable wants it."""
        mask = self._ones_masks.get(shape)
        if mask is None:
            import jax

            mask = self._ones_masks[shape] = jax.device_put(
                np.full(shape, 0xFFFFFFFF, np.uint32))
        return mask

    # ---------------- the step interface ----------------

    def _run(self, fn, arrays, sample, span, feed=None, put_first=False,
             **staged):
        """One jitted step, in the two host phases it has (obs.phase).
        The engine's numpy staging arrays (``arrays`` in the call's order,
        ``staged`` by keyword — a None is left out —, and the ``sample``
        pytree) are handed to the jitted call AS THEY ARE: its own
        argument path moves them, in the one crossing into the runtime,
        with no Python conversion a leaf, to wherever the executable
        wants them (uncommitted: the SAME code serves one chip and a
        mesh). An array already on the device (the lag-1 token feed, the
        resident all-ones mask) is passed through untouched. A staging
        array may be aliased by the call (the CPU backend), so it is not
        rewritten until the launch has provably run (engine.py
        ``_scratch_buf``).

        ``executor.stage`` is what is left to do before the call: it
        counts the host arrays the launch moves (``stage_transfers``,
        ``stage_bytes``; ``stage_masks``: the launches whose mask is one
        of them, those with a constrained row), checks the grammar
        allow-mask at the seam — ``[B, ceil(V/32)]`` uint32 for a decode
        step, ``[B, W, words]`` for a verify window (one allow-set a
        column); a wrongly-typed mask would silently allow everything
        after the kernel's bit unpack — and with ``feed`` gathers the
        first of ``arrays`` from itself (``feed_ids``: the
        ``executor.feed`` span INSIDE the stage phase, booked into
        ``spans``; no ``executor.dispatch`` of its own); with
        ``put_first`` the first of ``arrays`` is put on the device here
        where it is still the host's (``decode_step``).
        ``executor.dispatch`` is the jitted call until it returns — the
        transfers are booked THERE — under the attributes the engine
        gives in ``span`` (``kind``, ``seq``; ``kv_tokens`` for decode
        and verify).

        Updates ``cache.k`` / ``cache.v`` in place, literally: the step
        programs donate both pools (decode.py ``_jit_named``), so the
        arrays passed in are deleted by the call and the ones bound here
        are the same device buffers with the step's rows written. Under
        lag-1 dispatch the pool handed to step n + 1 is step n's output,
        still being computed: donating a pending buffer is ordinary
        stream order. Whatever else reads the pool (``export_blocks``,
        ``copy_blocks``, ``land_blocks``) takes ``cache.k`` / ``cache.v``
        as they stand when it runs and holds nothing across a step.
        ``cache.state`` (None where the family keeps none) is rebound too
        but NOT donated: ``counter_state()`` hands out a reference to
        it."""
        with obs.phase(self.phases, "executor.stage"):
            staged = {k: v for k, v in staged.items() if v is not None}
            moved = [a for a in (*arrays, feed, *staged.values(),
                                 *(sample or {}).values())
                     if isinstance(a, np.ndarray)]
            self.stage_transfers += len(moved)
            self.stage_bytes += sum(a.nbytes for a in moved)
            mask = (sample or {}).get("mask")
            if mask is not None:
                assert mask.dtype == np.uint32 and mask.ndim in (2, 3), (
                    "grammar allow-mask must be packed uint32 [B, words] "
                    f"or [B, W, words], got {mask.dtype}/{mask.shape}"
                )
                self.stage_masks += isinstance(mask, np.ndarray)
            if feed is not None:
                with obs.phase(self.spans, "executor.feed"):
                    arrays = (self._gather(arrays[0], feed), *arrays[1:])
            elif put_first and isinstance(arrays[0], np.ndarray):
                import jax

                arrays = (jax.device_put(arrays[0]), *arrays[1:])
        with obs.phase(self.phases, "executor.dispatch", **(span or {})):
            out, self.cache.k, self.cache.v, self.cache.state = fn(
                self.params, self.cache.k, self.cache.v, *arrays,
                sample=sample, state=self.cache.state, **staged,
            )
        return out

    def prefill(self, tokens, lengths, tables, sample=None, span=None,
                slots=None):
        ids = self._run(self.fns.prefill, (tokens, lengths, tables),
                        sample, span, slots=slots)
        self._warm_feed(ids, sample)
        return ids

    def prefill_chunk(self, tokens, lengths, starts, tables, sample=None,
                      span=None, slots=None, ids_width=None):
        """``ids_width``: hand the ids on padded to that many
        (``pad_ids``; a packed step's, whose rows are pieces)."""
        ids = self._run(self.fns.prefill, (tokens, lengths, tables),
                        sample, span, start=starts, slots=slots)
        return self._hand_on(ids, sample, ids_width)

    def _hand_on(self, ids, sample, ids_width):
        if ids_width is not None and sample is not None:
            ids = pad_ids(ids, ids_width)
        self._warm_feed(ids, sample)
        return ids

    def warm_prefill_chunk(self, tokens, lengths, starts, tables,
                           sample, ids_width=None, slots=None) -> None:
        """``prefill_chunk`` over padding rows alone (length 1 at position
        0 under an all-zero table: block 0 is the garbage sink), for the
        shape's sake: the program exists afterwards, and ``_warm_feed``'s
        gathers from ids of its width. The engine runs the row counts of
        its packed ladder so before its first prefill step. No step of the
        scheduler's: under no phase and in no staging counter. The
        arguments are of the kinds a step's are (a jitted call's fast path
        is keyed by them: ``decode_step``); ``slots`` all zero where the
        family keeps state (slot 0: padding, counted nowhere)."""
        ids, self.cache.k, self.cache.v, self.cache.state = self.fns.prefill(
            self.params, self.cache.k, self.cache.v, tokens, lengths, tables,
            start=starts, sample=sample, state=self.cache.state, slots=slots)
        self._hand_on(ids, sample, ids_width)

    def decode_step(self, tokens, positions, tables, sample=None, span=None,
                    slots=None, feed=None):
        """``tokens`` reaches the program ON THE DEVICE, always: the ids
        of the step in flight, ``feed_ids``' gather from them, or (nothing
        in flight: the host holds every id) the host's array put there
        first. A jitted call's fast path is keyed by its arguments' KINDS,
        and a call that leaves it with compiler options set builds the
        executable's wrapper anew (``MeshComputation.compile``: ~0.9 s for
        a 5-layer unrolled program on a v5e, the interpreter lock held
        all through): with two forms of ids the first decode step behind
        a chunk that is not its row's last stalled every stream for that
        long, once a row bucket, under traffic (PERF.md section 6, PR 39).
        A warm-up that runs a row bucket once has now run its only form."""
        ids = self._run(self.fns.decode, (tokens, positions, tables),
                        sample, span, feed=feed, put_first=True, slots=slots)
        self._warm_feed(ids, sample, rows=ids.shape[0])
        return ids

    def _warm_feed(self, ids, sample, rows: int | None = None) -> None:
        """Compile ``feed_ids`` for every pair (a step's ids of a width
        this executor has produced, a decode step of a row bucket it has
        run) as soon as the pair exists: called with the ids a prefill or
        decode program just returned (``rows``: the decode step's own
        bucket; without ``sample`` a step returns logits, and nothing is
        done). A warm-up that reaches every step shape so reaches every
        gather, and none is compiled under traffic. The sources are the
        steps' OWN arrays (a few bytes, kept), so placement and commitment,
        which are part of a jitted call's cache key, are the real ones."""
        if sample is None or ids.ndim != self._ids_ndim:
            return  # logits; or a block family's prefill, which chooses none
        width = ids.shape[0]
        pairs = set()
        if width not in self._ids_seen:
            self._ids_seen[width] = ids
            pairs |= {(width, B) for B in self._feed_rows}
        if rows is not None and rows not in self._feed_rows:
            self._feed_rows.add(rows)
            pairs |= {(w, rows) for w in self._ids_seen}
        for w, B in pairs:
            self._gather(
                self._ids_seen[w], np.zeros(self._feed_shape(B), np.int32))

    def verify_step(self, tokens, starts, draft_len, tables, sample=None,
                    span=None):
        return self._run(self.fns.verify,
                         (tokens, starts, draft_len, tables), sample, span)

    def copy_blocks(self, pairs: list[tuple[int, int]]) -> None:
        """Clone shared KV blocks on device (COW) before a write lands.
        The (src, dst) list pads to a pow2 bucket with (0, 0) — copying
        the garbage block onto itself — so the jitted shape set stays
        closed. Runs sharded for free: the pool arrays carry their mesh
        sharding and block indices are head-axis-invariant. The pools are
        donated here as to the steps, so the first COW does not stand a
        second pool beside the one the steps update in place."""
        if not pairs:
            return
        from ray_tpu.ops.kv_cache import copy_blocks

        width = 1 << (len(pairs) - 1).bit_length()
        src = np.zeros((width,), np.int32)
        dst = np.zeros((width,), np.int32)
        for i, (s, d) in enumerate(pairs):
            src[i] = s
            dst[i] = d
        self.cache.k, self.cache.v = copy_blocks(
            self.cache.k, self.cache.v, src, dst
        )

    def export_blocks(
        self, block_ids: list[int]
    ) -> tuple[np.ndarray, np.ndarray]:
        """Materialize the given physical blocks host-side for a
        disaggregated handoff, or as the host-tier demote capture
        (``PagedKVCache.demote_fn`` — the engine installs this method, so
        spill traffic flows through the same allowlisted ``_host_blocks``
        funnel instead of growing a new device->host sync point): returns
        (k, v) each [n_layer, len(block_ids), block_size, H_kv, hd]
        numpy, in the given order. The gather pads to a pow2 bucket with
        block 0 so the traced shape set stays closed (same discipline as
        ``copy_blocks``); padding rows are sliced off host-side. On a
        mesh the gather output is unsharded along heads by the transfer
        itself — the wire format is mesh-agnostic, which is also what
        makes a host-tier entry demoted under tp=1 byte-identical to one
        demoted under tp=4."""
        if not block_ids:
            import jax

            def _empty(a):
                return np.zeros(
                    (a.shape[0], 0) + tuple(a.shape[2:]), a.dtype
                )

            return (
                self._by_heads(jax.tree.map(_empty, self.cache.k)),
                self._by_heads(jax.tree.map(_empty, self.cache.v)),
            )
        width = 1 << (len(block_ids) - 1).bit_length()
        ids = np.zeros((width,), np.int32)
        for i, b in enumerate(block_ids):
            ids[i] = b
        k = self._by_heads(_host_blocks(self.cache.k[:, ids]))
        v = self._by_heads(_host_blocks(self.cache.v[:, ids]))
        return k[:, : len(block_ids)], v[:, : len(block_ids)]

    def _by_heads(self, blocks):
        """Host blocks as they leave the pool -> ``[n_layer, n, block_size,
        H_kv, hd]``, whatever shape the pool is stored in (a lane-dense
        pool's rows split into heads: a reshape of a host array, the same
        bytes). What the wire, the host tier and a peer's pool see never
        depends on the stored shape."""
        cfg = self.cache.cfg

        def split(a):
            return a.reshape(a.shape[:3] + (cfg.n_kv_head, cfg.head_dim))

        if isinstance(blocks, np.ndarray):
            return split(blocks)
        return type(blocks)(split(blocks.data), blocks.scale)

    def _as_stored(self, blocks):
        """The inverse of ``_by_heads``, for blocks about to land."""
        import jax

        return jax.tree.map(
            lambda a, pool: np.reshape(a, a.shape[:2] + pool.shape[2:]),
            blocks, self.cache.k)

    def land_blocks(
        self, block_ids: list[int], k_new: np.ndarray, v_new: np.ndarray
    ) -> None:
        """Scatter externally-produced KV blocks (a fetched handoff
        payload, or a batch of host-tier promotions drained by
        ``engine._apply_promotions_locked``) into this executor's pool at
        ``block_ids``, all layers fused (ops/kv_cache.land_blocks). Pads
        the id list to a pow2 bucket targeting garbage block 0 with zero
        payload rows, so the jitted shape set stays closed — promotion
        traffic therefore adds no compile kinds; host->device staging is
        ONE batched transfer per call. On a mesh the committed inputs
        re-shard along kv heads automatically (same GSPMD inference as
        every other call), so both executors serve promotions through
        this one method. The pools are donated, as to the steps."""
        if not block_ids:
            return
        import jax

        from ray_tpu.ops.kv_cache import land_blocks

        width = 1 << (len(block_ids) - 1).bit_length()
        ids = np.zeros((width,), np.int32)
        for i, b in enumerate(block_ids):
            ids[i] = b
        k_new, v_new = self._as_stored(k_new), self._as_stored(v_new)
        if width != len(block_ids):

            def _pad(a):
                pad = ((0, 0), (0, width - len(block_ids))) + tuple(
                    (0, 0) for _ in range(a.ndim - 2)
                )
                return np.pad(a, pad)

            k_new = jax.tree.map(_pad, k_new)
            v_new = jax.tree.map(_pad, v_new)
        self.cache.k, self.cache.v = land_blocks(
            self.cache.k, self.cache.v, ids, k_new, v_new)

    def sync_tokens(self, tokens_dev) -> np.ndarray:
        """THE device->host transfer: one step's sampled ids as [B] int32
        numpy. On a mesh the ids are replicated (every shard computes the
        full vocab argmax/pick after the logits all-reduce), so the
        transfer is the same O(batch) int32 regardless of device count."""
        toks = _host_tokens(tokens_dev)
        # [B]: a prefill's ids, a decode step's; a block family's pass
        # gives [B, W + 1], a block's ids and the bits of its masked
        assert toks.dtype == np.int32 and toks.ndim in (
            1, self._ids_ndim), (
            "sync path must move O(batch) int32, got "
            f"{toks.dtype}/{toks.shape}"
        )
        return toks

    def sync_verify(self, packed_dev) -> np.ndarray:
        """The SAME host sync point for a speculative verify step: one
        packed [B, W+1] int32 array (committed count + the window's
        target tokens) — O(batch * (k+2)) int32, still no logits and
        still exactly one transfer per step."""
        packed = _host_tokens(packed_dev)
        assert packed.dtype == np.int32 and packed.ndim == 2, (
            "verify sync path must move O(batch * k) int32, got "
            f"{packed.dtype}/{packed.shape}"
        )
        return packed

    # ---------------- introspection ----------------

    @property
    def num_params(self) -> int:
        """Parameter count of the weights THIS executor serves, summed
        from the params pytree's shape metadata (no device sync) — the
        analytic-FLOPs input for serving MFU (2*n_params FLOPs/token,
        forward-only; cf. the training side's 6*n_params in
        docs/ROOFLINE.md). QuantizedTensor
        leaves count their DATA elements only — the per-channel scale
        planes are bookkeeping, not model capacity — so MFU and the
        goodput gauges stay comparable between a quantized engine and
        its f32 twin."""
        import jax

        from ray_tpu.ops.quantization import QuantizedTensor

        if getattr(self, "_num_params", None) is None:
            leaves = jax.tree_util.tree_leaves(
                self.params,
                is_leaf=lambda t: isinstance(t, QuantizedTensor),
            )
            self._num_params = int(sum(
                t.data.size if isinstance(t, QuantizedTensor) else t.size
                for t in leaves
            ))
        return self._num_params

    def _weights_report(self) -> dict:
        """What the build step left on the device: ``weight_dtype`` is the
        quantization kind, else the dtype the matmul weights are stored
        in; ``weight_bytes`` sums the whole tree (scale planes and the
        float32 leaves included, all shards of a mesh). From shape
        metadata alone, as ``num_params`` — no device sync; read anew on
        every call, so it follows a tree that was assigned since."""
        import jax

        kind = getattr(self.model_cfg, "quantization", None)
        if kind is None:
            leaves, axes, _ = self._weights_by_axis()
            kind = str(next(
                t.dtype for t, axis in zip(leaves, axes) if axis >= 0))
        return {
            "weight_dtype": kind,
            "weight_bytes": int(sum(
                t.size * t.dtype.itemsize
                for t in jax.tree.leaves(self.params))),
        }

    @property
    def peak_tflops(self) -> float | None:
        """Aggregate published peak bf16 TFLOP/s across this executor's
        devices — the MFU denominator, from the one per-chip table
        (``CHIP_PEAK_TFLOPS`` at the end of this module, where adding a
        row moves no line of a step's call chain and so no compile
        cache key). None for a device that
        has no published peak (the CPU included): the engine then reports
        no MFU instead of one against an invented ceiling. Settable — a
        test of the gauge hands the executor a peak of its own."""
        if not hasattr(self, "_peak_tflops"):
            try:
                self._peak_tflops = (
                    chip_peak_tflops(self._devices()[0])
                    * float(self.num_devices)
                )
            except ValueError:
                self._peak_tflops = None
        return self._peak_tflops

    @peak_tflops.setter
    def peak_tflops(self, value: float | None) -> None:
        self._peak_tflops = value

    def _devices(self):
        import jax

        return jax.devices()

    def _device_report(self) -> dict:
        """What the steps actually run on, as JAX reports it."""
        dev = self._devices()[0]
        return {"platform": dev.platform, "device_kind": dev.device_kind}

    @property
    def attention_backend(self) -> str:
        """The RESOLVED decode-attention backend the jitted model steps
        traced with ("xla" | "pallas") — the model config's knob with
        "auto" collapsed to the platform default."""
        from ray_tpu.ops.paged_attention import resolve_backend

        return resolve_backend(
            getattr(self.model_cfg, "attention_backend", "xla")
        )

    @property
    def num_devices(self) -> int:
        return 1

    def counter_state(self):
        """The device arrays ``read_counters`` reads, as they stand now
        (immutable, never donated: a later step leaves this reference
        whole; for a family whose steps donate ``state``, decode.py
        ``Family.donated_state_counters``, copies of the counters' few
        words), or None for a family that keeps no counters. Costs
        nothing, or one small dispatch: the engine takes it while it
        holds its lock."""
        from ray_tpu.serve.llm.decode import get_family

        fam = get_family(self.family)
        if fam.counters is None:
            return None
        if fam.donated_state_counters is not None:
            # the next step DONATES ``state``: copies of the counters'
            # leaves, enqueued behind the step in flight
            import jax.numpy as jnp

            return {name: jnp.copy(self.cache.state[name])
                    for name in fam.donated_state_counters}
        return self.cache.state

    def read_counters(self, state) -> dict:
        """``counter_state()``'s counters as plain integers (decode.py
        ``Family.counters``); {} for None. A device->host read that waits
        for the step that was in flight when the reference was taken: for
        ``stats()`` OUTSIDE the engine's lock, never for a step."""
        from ray_tpu.serve.llm.decode import get_family

        if state is None:
            return {}
        return get_family(self.family).counters(state)

    def state_bytes(self) -> int:
        """Bytes ``state`` holds on the device, every slot and counter (0
        for a family that keeps none)."""
        import jax

        return int(sum(t.size * t.dtype.itemsize
                       for t in jax.tree.leaves(self.cache.state)))

    def _state_report(self) -> dict:
        """What the family holds per sequence: how many layers the paged
        pool spans, the state slots beside it (None: the pool is all),
        whether a prefix hit can be reused, and the groups of layers that
        have a table each, where the family names them."""
        cfg = self.cache.cfg
        state = None
        if self.cache.state is not None:
            state = {
                # slot 0 is the garbage sink; 0: counters, no row a sequence
                "slots": max(cfg.state_slots - 1, 0),
                "bytes": self.state_bytes(),
                "arrays": {k: list(v.shape)
                           for k, v in self.cache.state.items()},
            }
        # the shape the pool is STORED in (lane-dense: kv_cache.py)
        report = {"kv_layers": cfg.n_layer,
                  "kv_pool_shape": list(self.cache.k.shape), "state": state,
                  "prefix_reuse": cfg.prefix_reuse,
                  # what a token's row is (kind "latent": ONE plane, the
                  # one array the step programs carry)
                  "kv_pool": cfg.describe_pool()}
        if cfg.planes:
            report["kv_pool"]["shapes"] = [list(self.cache.k.shape)]
        if cfg.groups:
            # tables by group: ``kv_layers`` is then a GROUP's layers (the
            # pool's layer axis; all the model's under a ring and a slot
            # table), and a group its kind, its window and the model's
            # layers whose K/V it holds
            from ray_tpu.serve.llm.kv_cache import describe_group

            report["kv_groups"] = [
                describe_group(window, layers)
                for window, layers in cfg.groups]
        return report

    def _generation_report(self) -> dict:
        """``{"generation": ..}`` for a family that generates by diffusion
        over blocks, with the model configuration's settings (a request
        may override the steps and the order) and the positions a row the
        decode program is TRACED with (two blocks: a finished block rides
        its next block's first pass; a row that carries one block has the
        other's columns as padding); nothing for a family that yields one
        token a row a decode step."""
        if not get_family(self.family).block_steps:
            return {}
        cfg = self.model_cfg
        return {"generation": {
            "kind": "block_diffusion", "block_length": cfg.block_length,
            "denoising_steps": cfg.denoising_steps or cfg.block_length,
            "remasking": cfg.remasking,
            "confidence_threshold": cfg.confidence_threshold,
            "step_positions": 2 * cfg.block_length}}

    def describe(self) -> dict:
        """Stable summary for stats()/debug_dump()/benchmarks: which
        executor is serving, over how many devices, which decode
        attention backend the model steps compiled with, and what state
        the family holds per sequence."""
        return {"executor": self.kind, "devices": self.num_devices,
                "mesh": None, **self._device_report(),
                "attention_backend": self.attention_backend,
                "quantization": getattr(
                    self.model_cfg, "quantization", None),
                **self._weights_report(), **self._state_report(),
                "speculative": self.speculative,
                **self._generation_report()}


class SingleDeviceExecutor(ModelExecutor):
    """Exactly the single-chip engine of PRs 1-5: default-device weights
    and KV pool, including the lag-1 dispatch-ahead pipeline feed and
    fused sampling (both of which live in the shared call path above)."""

    kind = "single"


def _resolve_mesh(mesh, tp: int, fsdp: int):
    """Normalize the EngineConfig mesh surface to a jax Mesh.

    Accepts a built ``jax.sharding.Mesh``, a ``parallel.MeshSpec``, a
    ``serve.config.ModelParallelConfig`` (anything with tp/fsdp ints), a
    dict of MeshSpec axis sizes, or None + (tp, fsdp) ints. A spec with
    no wildcard may use FEWER devices than are visible — the mesh takes
    the first tp*fsdp — so differently-shaped replicas can coexist on
    one host (and in tests, on one virtual-device process)."""
    import jax
    from jax.sharding import Mesh

    from ray_tpu.parallel import MeshSpec, build_mesh

    if isinstance(mesh, Mesh):
        return mesh
    if mesh is None:
        spec = MeshSpec(tp=tp, fsdp=fsdp)
    elif isinstance(mesh, MeshSpec):
        spec = mesh
    elif isinstance(mesh, dict):
        spec = MeshSpec(**mesh)
    elif hasattr(mesh, "tp") and hasattr(mesh, "fsdp"):
        spec = MeshSpec(tp=int(mesh.tp), fsdp=int(mesh.fsdp))
    else:
        raise TypeError(
            "mesh must be a jax.sharding.Mesh, MeshSpec, "
            "ModelParallelConfig, dict of axis sizes, or None; got "
            f"{type(mesh).__name__}"
        )
    devices = jax.devices()
    sizes = spec.sizes()
    if all(v != -1 for v in sizes.values()):
        n = math.prod(sizes.values())
        if n > len(devices):
            raise ValueError(
                f"mesh {({k: v for k, v in sizes.items() if v > 1})} "
                f"needs {n} devices but only {len(devices)} are visible"
            )
        devices = devices[:n]
    return build_mesh(spec, devices)


def _in_mesh(name: str):
    """``ModelExecutor``'s step ``name``, traced and run with the
    executor's mesh set (``jax.set_mesh``): GSPMD needs no context, but
    the Pallas attention kernel is an opaque custom call it cannot
    partition, so ops/paged_attention.py reads the mesh from the context
    and runs the kernel in a ``shard_map`` over the head axis. The mesh is
    part of jit's trace key — the process-shared wrappers in decode.py
    keep one program per (shape, mesh)."""
    base = getattr(ModelExecutor, name)

    def step(self, *args, **kwargs):
        import jax

        with jax.set_mesh(self.mesh):
            return base(self, *args, **kwargs)

    step.__name__ = name
    return step


class ShardedExecutor(ModelExecutor):
    """tp/fsdp execution over a device mesh.

    Placement (all decided here, in ``__init__``):

    - weights: `parallel.sharding.shard_params` with the family's
      logical-axis tree (models/{gpt,llama}.py ``*_param_axes``) under
      DEFAULT_RULES — heads/mlp/vocab shard over tp (Megatron), embed
      over fsdp (ZeRO-3); exactly the layout the training side proves.
    - paged KV pool: ``cache.k``/``cache.v``
      (lane-dense, [layer, block, slot, kv_head * head_dim]) shard along
      the KV-HEAD axis, the row into contiguous heads a device, over tp
      and replicate over fsdp. Block granularity, tables,
      prefix hashes, COW and quarantine bookkeeping stay host-side in
      kv_cache.py, byte-for-byte the single-chip code.

    The step functions themselves are the process-shared jit wrappers
    from decode.py: sharding flows from the committed params/pool inputs
    (GSPMD), so no pjit re-wrap, no new compile kinds, and the engine's
    signature accounting is unchanged. The one thing GSPMD cannot split
    is the compiled Pallas attention kernel, which therefore runs in a
    ``shard_map`` over the head axis (see ``_in_mesh``). Requires ``n_kv_head % tp == 0``
    (the pool's head axis must split evenly) and a tp/fsdp-only mesh —
    dp/sp/pp/ep serving is future roadmap, not silently wrong."""

    kind = "sharded"
    _defer_quantize = True  # quantize after shard_params (see base attr)

    def __init__(self, family: str, model_cfg, cache, *,
                 mesh=None, tp: int = 1, fsdp: int = 1,
                 params: dict | None = None, seed: int = 0):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from ray_tpu.parallel import AxisNames
        from ray_tpu.parallel.sharding import ShardingRules, shard_params

        self.mesh = _resolve_mesh(mesh, tp, fsdp)
        for axis in (AxisNames.DATA, AxisNames.PIPE, AxisNames.SEQ,
                     AxisNames.EXPERT):
            if self.mesh.shape[axis] != 1:
                raise ValueError(
                    "the serving executor shards tp/fsdp only; mesh axis "
                    f"{axis!r} has size {self.mesh.shape[axis]} (batch is "
                    "scheduled host-side, not dp-sharded)"
                )
        tp_size = self.mesh.shape[AxisNames.TENSOR]
        n_kv = getattr(model_cfg, "n_kv_head", model_cfg.n_head)
        if n_kv % tp_size != 0:
            raise ValueError(
                f"tp={tp_size} cannot shard the paged KV pool: the pool "
                f"splits along its head axis and n_kv_head={n_kv} is not "
                f"divisible by tp (choose tp from the divisors of "
                f"{n_kv})"
            )
        super().__init__(family, model_cfg, cache, params=params, seed=seed)
        self.rules = ShardingRules()
        self.params = shard_params(
            self.params, family_param_axes(family, model_cfg),
            self.mesh, self.rules,
        )
        # Quantization runs AFTER shard_params: the axes tree matches the
        # raw param structure, and quantizing committed sharded arrays
        # lets GSPMD keep each scale shard colocated with its data shard
        # (the amax reduction is over an axis, so the result is the same
        # on any mesh). The cast to the compute dtype is elementwise on
        # the committed shards and keeps their sharding.
        self._maybe_quantize_params()
        self._store_compute_dtype()
        # Axis 3 is the tp shard axis of every leaf: a pool's lane-dense
        # row of heads (contiguous heads a device) and the KV heads of a
        # quantized pool's scale plane — one spec serves them all.
        kv_spec = PartitionSpec(None, None, None, AxisNames.TENSOR)
        sh = NamedSharding(self.mesh, kv_spec)
        cache.k = jax.tree.map(lambda a: jax.device_put(a, sh), cache.k)
        cache.v = jax.tree.map(lambda a: jax.device_put(a, sh), cache.v)

    prefill = _in_mesh("prefill")
    prefill_chunk = _in_mesh("prefill_chunk")
    warm_prefill_chunk = _in_mesh("warm_prefill_chunk")
    decode_step = _in_mesh("decode_step")
    verify_step = _in_mesh("verify_step")

    @property
    def num_devices(self) -> int:
        return self.mesh.devices.size

    def _devices(self):
        return list(self.mesh.devices.flat)

    def describe(self) -> dict:
        # only the non-trivial axes — {"tp": 2, "fsdp": 2} reads as the
        # operator-facing mesh shape
        return {**super().describe(),
                "mesh": {a: int(s) for a, s in self.mesh.shape.items()
                         if int(s) > 1}}


def build_executor(cfg, model_cfg, cache, *, params=None) -> ModelExecutor:
    """EngineConfig -> executor. Single-device unless the config names a
    mesh (``mesh=``) or widens an axis (``tp``/``fsdp`` > 1) — the
    default path constructs byte-for-byte the pre-seam engine."""
    if cfg.mesh is None and cfg.tp == 1 and cfg.fsdp == 1:
        ex = SingleDeviceExecutor(
            cfg.model, model_cfg, cache, params=params, seed=cfg.seed
        )
    else:
        ex = ShardedExecutor(
            cfg.model, model_cfg, cache, mesh=cfg.mesh, tp=cfg.tp,
            fsdp=cfg.fsdp, params=params, seed=cfg.seed,
        )
    k = int(getattr(cfg, "speculative_k", 0) or 0)
    if k > 0:
        drafter = getattr(cfg, "drafter", None)
        ex.speculative = {
            "speculative_k": k,
            "drafter": (drafter if isinstance(drafter, str)
                        else type(drafter).__name__ if drafter is not None
                        else None),
        }
    return ex


# Published per-chip peak bf16 TFLOP/s by device_kind substring (Google
# Cloud TPU documentation, one page per generation; ordering matters —
# first substring match wins). A device that is not listed is an error,
# never a default.
CHIP_PEAK_TFLOPS = [
    ("v6", 918.0),
    ("v5p", 459.0),
    ("v5 lite", 197.0),
    ("v5e", 197.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
]


def chip_peak_tflops(device) -> float:
    kind = getattr(device, "device_kind", "").lower()
    for sub, peak in CHIP_PEAK_TFLOPS:
        if sub in kind:
            return peak
    raise ValueError(
        f"no published peak for device kind {kind!r} (platform "
        f"{device.platform!r}); add it to CHIP_PEAK_TFLOPS with its source"
    )
