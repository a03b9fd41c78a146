"""Continuous-batching scheduler: admission, chunked-prefill/decode
interleave, prefix-cache reuse, per-step join/evict, bucketed shapes.

The loop is the Orca/vLLM iteration-level scheduler: every step is EITHER
one batched prefill CHUNK (new admissions, or the next slice of a long
prompt) or one batched decode step over all running sequences — new
requests join the decode batch at the step after their prefill completes,
finished sequences leave it the step they complete, and their KV blocks
return to the pool immediately. A decode step yields ONE token a row for
an autoregressive family; for a family that generates by diffusion over
blocks (``sdar_moe``) it is one PASS over a block a row, a reconciled step
emits 0 or up to a block's tokens a row, and prefill emits none
(``_decode_blocks_locked``; what follows says "token" where such a family
has a block).

Two serving-throughput optimizations sit on top of PR 1/2's engine:

- **Prefix caching** (SGLang's RadixAttention idea at block granularity):
  admission maps the longest content-addressed full-block prefix of a new
  prompt onto blocks already resident in the paged cache, so shared system
  prompts / few-shot headers cost ZERO prefill compute on repeat traffic.
  Shared blocks are refcounted and copy-on-write; unreferenced cached
  blocks are evicted LRU when the free list runs dry (kv_cache.py).
- **Chunked prefill**: a prompt's uncached suffix is prefilled in
  ``prefill_chunk_tokens``-sized bucketed slices, and the scheduler
  ALTERNATES prefill chunks with decode steps, so a long new prompt never
  head-of-line-blocks tokens streaming from running sequences.

TPU-first constraint: every jitted call's shape is drawn from a closed
set. Batch sizes pad to ``batch_buckets`` and token/context lengths to
``length_buckets`` (serve/_shapes.py pad_to_bucket — the same rule the
@serve.batch router uses), so compiled programs stay bounded no matter
the traffic mix (arxiv 2011.03641: static-shape batching to stay inside
the compile cache). Chunk prefills reuse the SAME length buckets for both
the chunk width and the context extent, so they add at most one more
bounded signature family ("prefill_chunk") next to the monolithic
"prefill" and "decode" kinds. `DecodeFns.num_compiled_shapes` reports the
realized count. Where a sequence is K/V pages under one table (the dense
families) a prefill step is filled by TOKENS instead: its rows are pieces
of prompts, one q tile of the kernel each, and its programs the rungs of
one row ladder, all "prefill_chunk" (``_prefill_chunk_locked``).

Sampling is FUSED into the jitted model step (ops/sampling.py): greedy,
temperature, top-k and top-p all run on device, so the per-token
device->host transfer is O(batch) int32 token ids instead of
O(batch x vocab) float32 logits. Per-token randomness is keyed, not
stateful: token position p draws from
``fold_in(PRNGKey(request_seed), p)``, making every sampled token a pure
function of (logits, seed, position). A sequence's output is therefore
identical whether it ran solo or continuously batched with arbitrary
neighbors, and mid-stream failover is byte-identical BY CONSTRUCTION — a
resumed request re-prefills ``prompt + delivered`` and the keyed draws
at the remaining positions are unchanged (this replaces the old
host-side "burn one numpy uniform per token" RNG contract).

The step loop is pipelined (dispatch-ahead, arXiv 2011.03641): NO step
waits for its own tokens. A decode step is launched behind whatever step
program is in flight — the last decode step, or a prefill whose final
rows have just joined — with each row's input id taken where it is: on
the host, or in that step's on-device id array at an index the engine
knows (the same rows in the same order: the array itself; rows joined or
left: one gather on the device, ``executor.feed_ids``). A prefill step's
sync waits behind the next launch too. Only after a launch is the OLDER
step reconciled (synced, its tokens emitted, its flight record written),
so bucketing, block-table/COW assembly, emission and scheduler work hide
under device compute via JAX async dispatch, across a join or a finish
as in the steady state. At most TWO step programs are ever in flight;
three things rest on that bound: the staging buffers alternate in pairs
(``_scratch_buf``), a block freed with programs queued waits in the
cache's quarantine for the sync of the NEWEST of them
(kv_cache.flush_quarantine: the fence), and a reader of the profiler's
trace pairs launches with runs at most two apart
(benchmark/span_reduce.py). Terminal conditions (EOS, max_tokens, cancel,
deadline) are reconciled when the lagged tokens arrive — at most one
wasted speculative row per just-finished request. What still syncs
everything in flight BEFORE its launch, on purpose: a batch with a
grammar-constrained row (its allow-mask needs the last id on the host), a
verify step (drafts are made from committed tokens), preemption and the
handoff export, a step with nothing to launch (the drain), and a fatal
path. A first token reaches its stream when its prefill's run ends, as
with an immediate sync: the launch behind it costs the host a few ms and
the prefill the device tens.

Everything device-side sits behind the ModelExecutor seam (executor.py):
the scheduler stages numpy, the executor owns weights, the paged KV pool
arrays, and the jitted calls. Single-device by default; EngineConfig
``tp``/``fsdp``/``mesh`` select the tp/fsdp-sharded executor without any
scheduler change (docs/SERVING_LLM.md "Sharded serving").

Failure semantics (docs/SERVING_LLM.md "Failure semantics"):

- ``submit`` applies admission control: a bounded waiting queue
  (``max_waiting``) and an optional worst-case block budget for queued
  work (``max_waiting_blocks``), rejecting with ``EngineOverloadedError``
  rather than queueing unboundedly. When the HEAD of the queue doesn't
  fit, admission probes up to ``admission_probe`` smaller requests behind
  it (bounded skip-ahead), with an aging cap (``admission_max_skips``) so
  a large prompt cannot be starved forever.
- per-request deadlines (``SamplingParams.deadline_s``) are enforced at
  the top of every step; expired sequences are evicted and their streams
  fail with ``DeadlineExceededError``.
- ``cancel(request_id)`` evicts a waiting, prefilling, or running
  sequence and returns its KV blocks (allocation AND leftover
  reservation): at once, or at the reconcile of the step program in
  flight that still holds the row.
- if a step raises, or wedges past ``step_timeout_s`` (watchdog thread),
  the engine fails closed: every in-flight stream gets an
  ``EngineDiedError`` (an ``ActorError`` — clients treat it exactly like
  replica death and fail over) instead of blocking forever.
"""
from __future__ import annotations

import functools
import itertools
import logging
import math
import queue
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass
from typing import Any, Sequence

import numpy as np

from ray_tpu._private import chaos
from ray_tpu.exceptions import (
    DeadlineExceededError,
    EngineDiedError,
    EngineOverloadedError,
    RequestCancelledError,
)
from ray_tpu.serve._shapes import (
    pad_to_bucket,
    pow2_buckets,
    stepped_buckets,
)
from ray_tpu.serve.llm import obs, structured
from ray_tpu.serve.llm.executor import build_executor
from ray_tpu.serve.llm.kv_cache import (
    KVCacheConfig,
    PagedKVCache,
    _block_key,
    group_kind,
    is_composed,
)
from ray_tpu.util import metrics, tracing

logger = logging.getLogger("ray_tpu.serve.llm")

_DONE = object()  # stream sentinel

# Window (obs.clock seconds) over which autoscaling_snapshot() turns
# deadline-miss / rejection event timestamps into rates.
_SIGNAL_RATE_WINDOW_S = 30.0

# Window (obs.clock seconds) of per-step (device-time, tokens) samples
# behind the llm_goodput_tokens_per_sec / llm_serving_mfu gauges.
_GOODPUT_WINDOW_S = 30.0
_GOODPUT_WINDOW_STEPS = 1024


def _pctile(samples, q: float) -> float:
    """Nearest-rank percentile of a small sample window; 0.0 when empty."""
    if not samples:
        return 0.0
    xs = sorted(samples)
    return xs[min(len(xs) - 1, int(q * len(xs)))]


def _window_rate(clocks: deque, now: float) -> float:
    """Events/second over the trailing window; prunes expired entries."""
    while clocks and now - clocks[0] > _SIGNAL_RATE_WINDOW_S:
        clocks.popleft()
    return len(clocks) / _SIGNAL_RATE_WINDOW_S


# sanity ceiling for max_new_tokens: far above any model's max_seq_len
# (which submit() checks against anyway) but low enough to catch sign
# bugs and unit mistakes at construction time, where the field is named
_MAX_NEW_TOKENS_CAP = 1 << 20

# Priority classes, lowest rank first. Preemption pauses low-rank running
# streams to make room for high-rank waiting ones; shedding degrades in
# the same order (batch sheds before default sheds before interactive).
_PRIORITIES = ("batch", "default", "interactive")
PRIORITY_RANK = {name: rank for rank, name in enumerate(_PRIORITIES)}


@dataclass(frozen=True)
class SamplingParams:
    max_new_tokens: int = 16
    temperature: float = 0.0  # 0 -> greedy
    top_k: int = 0            # 0 or -1 -> full distribution
    top_p: float = 1.0        # nucleus mass in (0, 1]; 1.0 -> disabled
    seed: int = 0
    deadline_s: float | None = None  # wall-clock budget from submit()
    start_index: int = 0      # tokens already delivered (failover resume)
    # grammar constraint (serve/llm/structured.py): None, "json" /
    # "json_object", a response_format dict, or a GrammarSpec
    structured: Any = None
    # stop sequences: token-id sequences that terminate the stream when
    # they appear as a suffix of the generated tokens (the matched stop
    # tokens ARE emitted, like EOS). Normalized to a tuple of tuples.
    stop: Any = ()
    # priority class: "interactive" | "default" | "batch". Orders both
    # preemption (batch pauses first) and class-aware shedding. Never
    # changes tokens — only scheduling order.
    priority: str = "default"
    # a family that generates by diffusion over blocks (models/sdar_moe.py):
    # the passes that fill a block and the order they fill it in (one of
    # ops/sampling.py ``REMASKING``); None: the model configuration's.
    # Quality against steps is the knob such a family's users turn.
    # Refused for a family that yields a token a step.
    denoising_steps: int | None = None
    remasking: str | None = None

    def __post_init__(self):
        if self.denoising_steps is not None and self.denoising_steps < 1:
            raise ValueError(
                f"denoising_steps must be >= 1, got {self.denoising_steps}")
        if self.remasking is not None:
            from ray_tpu.ops.sampling import REMASKING

            if self.remasking not in REMASKING:
                raise ValueError(
                    f"remasking must be one of {REMASKING}, got "
                    f"{self.remasking!r}")
        if self.priority not in _PRIORITIES:
            raise ValueError(
                f"priority must be one of {_PRIORITIES}, "
                f"got {self.priority!r}"
            )
        if not (1 <= self.max_new_tokens <= _MAX_NEW_TOKENS_CAP):
            raise ValueError(
                f"max_new_tokens must be in [1, {_MAX_NEW_TOKENS_CAP}], "
                f"got {self.max_new_tokens}"
            )
        if self.start_index < 0:
            raise ValueError("start_index must be >= 0")
        if not math.isfinite(self.temperature) or self.temperature < 0:
            raise ValueError(
                f"temperature must be finite and >= 0, got "
                f"{self.temperature}"
            )
        if self.top_k < -1:
            raise ValueError(
                f"top_k must be >= -1 (0 or -1 disables), got {self.top_k}"
            )
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(
                f"top_p must be in (0, 1], got {self.top_p}"
            )
        norm = []
        for seq in self.stop:
            if isinstance(seq, int):
                seq = (seq,)
            seq = tuple(int(t) for t in seq)
            if not seq:
                raise ValueError("stop sequences must be non-empty")
            norm.append(seq)
        object.__setattr__(self, "stop", tuple(norm))


@dataclass(frozen=True)
class EngineConfig:
    model: str = "llama"          # a key of decode.py FAMILIES
    model_config: Any = None      # the family's config; None -> its tiny one
    block_size: int = 16
    num_blocks: int = 64
    max_batch_size: int = 8       # max concurrently-running sequences
    max_prefill_batch: int = 4    # max requests coalesced into one prefill
    batch_buckets: tuple[int, ...] | None = None   # None -> pow2 ladder
    length_buckets: tuple[int, ...] | None = None  # None -> pow2 ladder
    eos_id: int | None = None
    seed: int = 0                 # param init seed (when params not given)
    max_waiting: int = 128        # admission queue bound (overload beyond)
    max_waiting_blocks: int | None = None  # worst-case block budget queued
    step_timeout_s: float | None = None    # watchdog: wedged-step ceiling
    prefix_caching: bool = True   # map prompts onto resident KV blocks
    # Host-memory KV tier capacity in bytes (0 disables). When set, LRU
    # eviction demotes full prefix blocks into a host arena instead of
    # discarding them, and prefix hits promote them back through the
    # executor's fused land_blocks scatter — see kv_cache.HostKVTier.
    host_cache_bytes: int = 0
    # Prefill one prompt in slices of at most this many tokens, alternating
    # with decode steps. None -> the whole uncached suffix in one call (the
    # monolithic PR 1 behavior for cold prompts).
    prefill_chunk_tokens: int | None = None
    admission_probe: int = 4      # skip-ahead width when the head won't fit
    admission_max_skips: int = 16  # aging cap: stop skipping a starved head
    # Flight recorder: ring of the last N step records, dumped as JSON on
    # EngineDiedError / watchdog timeout / shutdown(dump=...). Dir: None
    # -> $RAY_TPU_FLIGHT_DIR -> <tmp>/ray_tpu_flight (obs.dump_dir).
    flight_recorder_steps: int = 256
    flight_recorder_dir: str | None = None
    # Finished-request timelines kept for request_timeline() lookups.
    timeline_history: int = 256
    # ---- multi-chip sharded serving (executor.py) ----
    # Defaults are single-device (SingleDeviceExecutor — byte-for-byte
    # the pre-seam engine). Widening tp/fsdp, or naming a mesh, selects
    # ShardedExecutor: weights shard tp/fsdp with the training-side
    # rules, the paged KV pool shards along its head axis over tp, and
    # block tables/prefix cache/COW stay host-side.
    # mesh: None | jax.sharding.Mesh | parallel.MeshSpec |
    #       serve.config.ModelParallelConfig | dict of axis sizes.
    mesh: Any = None
    tp: int = 1      # tensor-parallel ways (heads/mlp/vocab + KV heads)
    fsdp: int = 1    # fsdp ways (embed axis of every weight)
    # ---- decode attention backend (ops/paged_attention.py) ----
    # None -> respect the model config's attention_backend (default
    # "auto": the fused Pallas paged-attention kernel on TPU, the XLA
    # gather formulation elsewhere). "xla" | "pallas" force a backend;
    # "auto" forces the platform default. The knob is STATIC in the
    # jitted step (it rides the frozen model config), so switching it
    # never adds a compile kind — signatures stay
    # (prefill, prefill_chunk, decode) x buckets, and token streams are
    # byte-identical across backends (tests/test_paged_attention.py).
    attention_backend: str | None = None
    # ---- quantized serving (ops/quantization.py) ----
    # None -> f32 weights + f32 paged KV (every prior PR's behavior,
    # byte-identical). "int8" | "fp8" quantize BOTH the serving weights
    # (per-channel scales, dequantized lazily at each use site) and the
    # paged KV pool (per-(token, kv-head) scales, dequantized in-register
    # inside the Pallas kernels — the pool never materializes f32 in
    # HBM). STATIC: the knob lands in the frozen model config, so a
    # quantized engine is one compile-kind set of its own — no
    # mixed-precision traffic, and streams stay byte-identical WITHIN a
    # config across failover/handoff/demote-promote/preempt-resume. The
    # cross-config contract is agreement-rate, not byte-identity
    # (docs/SERVING_LLM.md "Quantized serving").
    quantization: str | None = None
    # ---- speculative decoding (drafter.py + executor.verify_step) ----
    # speculative_k > 0 turns on draft-and-verify: a host-side Drafter
    # proposes up to k tokens per sequence and the target model scores
    # the whole [B, k+1] window in ONE jitted "verify" call, committing
    # an accepted prefix plus one corrected token per step (1..k+1
    # tokens). LOSSLESS by construction: acceptance is exact-match
    # against the keyed (seed, position) sampler, so streams are
    # byte-identical to speculative_k=0 for greedy AND temperature/
    # top-k/top-p (docs/SERVING_LLM.md "Speculative decoding"). The
    # window width k+1 is frozen per engine — per-row draft availability
    # is data, not shape — so speculation adds exactly one compile kind
    # ("verify") x the existing buckets.
    speculative_k: int = 0
    # Drafter | "ngram" | None. "ngram" = the model-free prompt-lookup
    # drafter (drafter.NGramDrafter); None drafts nothing (every
    # speculative step degenerates to a 1-token verify). Only consulted
    # when speculative_k > 0.
    drafter: Any = "ngram"
    # ---- priority preemption (None disables) ----
    # PreemptionConfig (or a dict of its fields). When set, the scheduler
    # may PAUSE the lowest-priority running streams under KV-pool pressure
    # or queue-wait pressure: their full KV block chains demote through
    # the host tier funnel, the request parks in a "preempted" lifecycle
    # state with cursor/timeline/FSM intact, and it resumes automatically
    # (byte-identical, by keyed (seed, position) sampling) when pressure
    # clears or the starvation-aging floor trips.
    preemption: Any = None


@dataclass(frozen=True)
class PreemptionConfig:
    """Thresholds for priority preemption (EngineConfig.preemption).

    Pressure is the fraction of usable KV blocks in use (reservations
    included); all times are engine-clock seconds (obs.clock)."""

    kv_pressure: float = 0.90   # pause when pool pressure crosses this
    queue_wait_s: float = 0.25  # ... or a higher-priority wait exceeds this
    resume_pressure: float = 0.75  # resume parked streams below this
    aging_s: float = 30.0       # starvation floor: waiting/parked this long
    # is boosted above interactive and becomes non-preemptible
    max_preempted: int = 64     # cap on concurrently parked streams


class TokenStream:
    """Iterator over one request's generated token ids, delivered as the
    engine produces them (blocks between tokens; ends at completion)."""

    def __init__(self, request: "_Request"):
        self._request = request

    @property
    def request_id(self):
        return self._request.id

    @property
    def done(self) -> bool:
        return self._request.done

    def __iter__(self):
        while True:
            item = self._request.out.get()
            if item is _DONE:
                return
            if isinstance(item, BaseException):
                raise item
            yield item


class _Request:
    __slots__ = (
        "id", "prompt", "sampling", "out", "generated",
        "reserved_blocks", "drawn_blocks", "prefill_done", "cached_tokens",
        "started", "skips", "table_np", "table_key", "done", "deadline",
        # dispatch-ahead decode: dispatched-but-unreconciled device steps
        # that include this row, and whether its KV blocks went back to
        # the pool (exactly-once release under the lag)
        "inflight", "blocks_released",
        # grammar-constrained decoding: the request's FSM cursor
        # (structured.FSMCursor) or None when unconstrained
        "fsm",
        # lifecycle observability (ISSUE 4): the phase timeline rides the
        # request, and a stored trace context turns it into spans on finish
        "trace_ctx", "timeline", "submitted_clock", "first_token_clock",
        "last_token_clock", "finish_reason",
        # priority preemption: when paused, the full token chain
        # (prompt + generated) to re-prefill on resume; the park
        # timestamp; how many times this stream has been paused
        "pending_resume", "preempted_clock", "preempt_count",
        # generation by diffusion over blocks: the row's block and its
        # place in the block's schedule (``_BlockRow``), else None
        "blk",
    )

    def __init__(self, req_id, prompt, sampling: SamplingParams,
                 trace_ctx: dict | None = None):
        self.id = req_id
        self.prompt = list(prompt)
        self.sampling = sampling
        self.trace_ctx = trace_ctx
        # [{"event", "ts"(wall), ...}] — submitted/admitted/prefill chunks/
        # first_token/token/terminal; bounded by the request's own lifetime
        self.timeline: list[dict] = []
        self.submitted_clock: float | None = None
        self.first_token_clock: float | None = None
        self.last_token_clock: float | None = None
        self.finish_reason: str | None = None
        # tokens, then _DONE (an exception first where the stream failed):
        # the interpreter's C queue, so a put is one call and a blocked
        # reader wakes having taken the interpreter once
        self.out: queue.SimpleQueue = queue.SimpleQueue()
        self.generated: list[int] = []
        # sampling is keyed by (seed, absolute position) on device — no
        # RNG state to carry or fast-forward; start_index only offsets
        # the stream's public token numbering on failover resume
        self.inflight = 0
        self.blocks_released = False
        self.reserved_blocks = 0
        # blocks this request has consumed from its reservation so far:
        # prefix-cache hits + appended blocks + copy-on-write copies. The
        # leftover (reserved - drawn) is what eviction/completion releases.
        self.drawn_blocks = 0
        self.prefill_done = 0     # prompt tokens whose KV is resident
        self.cached_tokens = 0    # of those, tokens served by prefix hits
        self.started = False      # ran at least one prefill chunk
        self.skips = 0            # admissions that jumped over this head
        self.table_np: np.ndarray | None = None  # cached host block table
        self.table_key: tuple | None = None      # (nb, table_version)
        self.fsm = None  # structured.FSMCursor when grammar-constrained
        self.done = False
        self.pending_resume: list[int] | None = None
        self.preempted_clock: float | None = None
        self.preempt_count = 0
        self.blk: _BlockRow | None = None
        self.deadline = (
            time.monotonic() + sampling.deadline_s
            if sampling.deadline_s is not None
            else None
        )

    @property
    def total_len(self) -> int:
        return len(self.prompt) + len(self.generated)

    @property
    def prefill_tokens(self) -> list[int]:
        """The token chain prefill must make KV-resident: the prompt, or
        prompt + generated-so-far when resuming from preemption."""
        return (self.pending_resume if self.pending_resume is not None
                else self.prompt)


class _BlockRow:
    """A row of a family that generates by diffusion over blocks: where
    its block is and how far the block's schedule has been LAUNCHED (the
    host's view moves on at a launch, as a prefill's does).

    ``counts``: positions a pass fills (ops/sampling.py ``fill_counts``,
    the request's steps or the model's); ``mode``: the index of its order
    in ``REMASKING`` there.
    ``start``: the first position of the block the row's next pass
    CHOOSES in: the committed frontier, in whole blocks; it moves by a
    block at the launch of the pass that commits one (a fold, which
    carries the finished block from ``start`` AND the fresh one behind
    it, or a last block's commit). ``lead``: the block's leading
    positions that are the prompt's tail (the first block's ``len(prompt)
    % W``). ``fills`` / ``p``: what each denoising pass of this block
    fills under a static order (``whole``: of a block that is all masks),
    and the passes launched for it (``p == len(fills)``: the block is
    finished; a fold leaves ``p`` at 1, being the fresh block's first
    pass). ``x``: the ids and the bits of the masked positions ``[W +
    1]`` of the block that chose in the LAST RECONCILED pass, as it gave
    them back (at first: the prompt's tail, then masks): what a pass is
    fed from where the row rides no pass in flight, and, kept from the
    reconcile before, what a committing pass's reconcile emits. ``due``:
    the tokens the committing passes launched so far deliver."""

    __slots__ = ("counts", "mode", "start", "lead", "fills", "whole", "p",
                 "x", "due")

    def __init__(self, prompt: list, counts: tuple, mode: int, width: int,
                 mask_id: int, pass_fills):
        self.counts, self.mode = counts, mode
        self.lead = len(prompt) % width
        self.start = len(prompt) - self.lead
        self.whole = pass_fills(width, counts)  # a later block's
        self.fills = pass_fills(width - self.lead, counts)
        self.p = 0
        self.x = (prompt[self.start:] + [mask_id] * (width - self.lead)
                  + [sum(1 << o for o in range(self.lead, width))])
        self.due = 0

    @property
    def dynamic(self) -> bool:
        """The order under which the DEVICE says when a block is full."""
        return self.mode == 2

    @property
    def finished(self) -> bool:
        """Whether the block holds no masked position: under a static
        order the schedule says (every pass launched), under the dynamic
        one the bits the last pass gave back do (the lag was collapsed)."""
        return self.x[-1] == 0 if self.dynamic else self.p >= len(self.fills)


class _StepTokens:
    """What the rows of one reconciled step share while their tokens go to
    the streams: the ONE reading of both clocks (the rows came off the
    device in one sync) and the values the TTFT and TPOT histograms get
    for them, together, when the pass is over."""

    __slots__ = ("now", "wall", "ttfts", "gaps")

    def __init__(self):
        self.now = obs.clock()
        self.wall = obs.wall()
        self.ttfts: list[float] = []
        self.gaps: list[float] = []


@dataclass
class _InFlight:
    """One launched-but-unsynced step program: its on-device sampled ids
    ``[B]`` int32 (row i belongs to ``batch[i]``; padding rows are
    garbage; a block family's pass: ``[B, W + 1]``, a block's ids and the
    bits of its masked positions, with ``passes`` saying what each row's
    pass was), the exact batch list it was launched over, and ``seq``, its
    number among the step programs this engine launched (what a sync's
    ``lag`` and the block quarantine's fence are counted in). A decode
    step needs no more; a prefill step also carries what its reconcile
    books once its tokens are on the host: each row's chunk length, token
    chain and ``prefill_done`` after the chunk, whether the chunk was the
    row's last (its id is then the row's first token), and the step's
    clocks and shape for the one flight record it gets. ``ids_at``: where
    a PACKED prefill step's requests have their ids (``batch[j]``'s at row
    ``ids_at[j]``, its last piece's; None: at row j, as in every other
    step)."""

    kind: str            # "decode" | "prefill" | "prefill_chunk"
    tokens: Any          # jax [B] int32, still on device
    batch: list          # the _Request rows of this launch, in order
    seq: int = 0         # set at the launch (``_launched_locked``)
    rows: list | None = None    # prefill: (n, chain, done_after, final)
    t0: float = 0.0
    t0_wall: float = 0.0
    fields: dict | None = None  # prefill: the flight record's shape fields
    index: dict | None = None   # request -> the row of its id, on demand
    ids_at: list | None = None  # packed prefill: the rows of ``batch``'s ids
    # a block family's decode step: (does the pass commit a block?, the
    # block's leading prompt positions, the tokens it delivers) a row
    passes: list | None = None

    def row_of(self, r) -> int:
        if self.index is None:
            self.index = dict(zip(
                self.batch, self.ids_at or range(len(self.batch))))
        return self.index[r]


class LLMEngine:
    """Continuous-batching inference engine over a paged KV cache.

    ``auto_step=True`` (the serving mode) runs the scheduler on a
    background thread; ``auto_step=False`` lets tests drive ``step()``
    deterministically. Only one thread may step at a time — all scheduler
    and cache state is guarded by one lock.
    """

    def __init__(
        self,
        cfg: EngineConfig | None = None,
        *,
        params: dict | None = None,
        auto_step: bool = True,
        **overrides,
    ):
        if cfg is None:
            cfg = EngineConfig(**overrides)
        elif overrides:
            import dataclasses

            cfg = dataclasses.replace(cfg, **overrides)
        from ray_tpu.serve.llm.decode import get_family

        family = get_family(cfg.model)  # raises on a name it does not serve
        model_cfg = cfg.model_config
        if model_cfg is None:
            model_cfg = family.default_config()
        # thread the decode-attention backend into the (static) model
        # config: EngineConfig wins, then a ModelParallelConfig-style
        # mesh object's knob, else the model config keeps its own
        backend = cfg.attention_backend
        if backend is None:
            backend = getattr(cfg.mesh, "attention_backend", None)
        if backend is None:
            backend = getattr(model_cfg, "attention_backend", "xla")
        # Resolve "auto" to the platform's concrete backend HERE (also
        # validates the knob): the resolved value lands in the frozen
        # model config, so engines that spell the same effective backend
        # differently ("auto" on CPU vs explicit "xla") share one
        # decode.py _jit_cache entry instead of compiling twice.
        from ray_tpu.ops.paged_attention import resolve_backend

        backend = resolve_backend(backend)
        if getattr(model_cfg, "attention_backend", None) != backend:
            import dataclasses

            model_cfg = dataclasses.replace(
                model_cfg, attention_backend=backend
            )
        # thread quantization the same way: EngineConfig wins, else the
        # model config keeps its own. Validated + normalized here so the
        # frozen model config carries the canonical spelling — it is part
        # of the decode.py _jit_cache key, which is exactly what makes a
        # quantized engine its OWN compile-kind set (never mixed traffic
        # with an f32 twin).
        from ray_tpu.ops.quantization import resolve_quantization

        quant = cfg.quantization
        if quant is None:
            quant = getattr(model_cfg, "quantization", None)
        quant = resolve_quantization(quant)
        if getattr(model_cfg, "quantization", None) != quant:
            import dataclasses

            model_cfg = dataclasses.replace(model_cfg, quantization=quant)
        self.cfg = cfg
        self.model_cfg = model_cfg
        # A family that keeps per-sequence state beside the pool
        # (decode.py ``Family.init_state``): what cannot carry that state yet
        # is refused here, by name, never served silently wrong.
        self._stateful = family.init_state is not None
        # ... of which only ROWS a sequence (a conv state) stand in the way
        # of a prefix hit, a pause or a handoff; counters alone do not
        self._state_rows = self._stateful and family.state_rows
        # A family whose layers keep their K/V by GROUP (``kv_table_groups``:
        # kv_cache.py "Tables by group"): one table a group, windowed
        # groups give blocks back. What cannot carry that is refused too.
        groups = tuple(getattr(model_cfg, "kv_table_groups", ()))
        # ... or keeps a ring of one window's K/V and a table of chunk
        # summaries that every layer reads, COMPOSED into a step's table
        # (kv_cache.py "A ring and a table of slots"): its own refusals
        composed = is_composed(groups)
        # ... or caches ONE row a token for all heads, in one plane
        # (``kv_planes``: kv_cache.py "A pool in planes"): what describes a
        # block by heads, or splits the pool along them, is refused
        planes = tuple(getattr(model_cfg, "kv_planes", ()))
        # ... or generates by diffusion over BLOCKS (decode.py
        # ``Family.block_steps``): a decode step is a pass over a block of
        # this many positions a row (0: a token a row a step)
        self._block_len = model_cfg.block_length if family.block_steps else 0
        self._refuse_for_state(
            cfg, quant, self._state_rows, bool(groups) and not composed,
            composed, bool(planes), bool(self._block_len))
        if self._block_len:
            self._check_whole_blocks(cfg, self._block_len)
        # why no prompt prefix is reused (None: it is), for ``stats()``
        self._prefix_reuse_why = self._no_prefix_reuse(
            self._state_rows, bool(groups), composed)
        if planes:  # one row of its parts' widths, no head axis
            n_kv, head_dim = 1, sum(width for _, width, _ in planes)
        else:
            n_kv = getattr(model_cfg, "n_kv_head", None) or model_cfg.n_head
            head_dim = model_cfg.head_dim
        # one slot per running sequence, and slot 0, the garbage sink:
        # for state ROWS. Counters alone are no row a sequence: such a
        # family holds no slot, and its steps are told only which rows are
        # real (``_slots_buf_locked``)
        slots = cfg.max_batch_size + 1 if self._state_rows else 0
        # ... and whose ``state`` also holds an array by BLOCK ID (a layer
        # that selects its pages keeps their compressed keys so)
        by_block = family.block_state_bytes is not None
        if by_block:
            self._check_selected_pages(cfg, *model_cfg.kv_selected_pages)
        # what the family says of a step from the positions its rows query,
        # for the step's ``executor.dispatch`` span (decode.py
        # ``Family.step_attrs``); None: nothing of its own
        self._step_attrs = family.step_attrs
        # the form an expert family's grouped product takes in a program
        # of so many rows (``Family.gmm_form``; None: no expert layer)
        self._gmm_form = (None if family.gmm_form is None else
                          functools.partial(family.gmm_form, model_cfg))
        self.cache = PagedKVCache(
            KVCacheConfig(
                # the pool spans the layers that cache K/V: all of them,
                # unless the family says otherwise
                n_layer=getattr(model_cfg, "n_kv_layer", model_cfg.n_layer),
                n_kv_head=n_kv,
                head_dim=head_dim,
                num_blocks=cfg.num_blocks,
                block_size=cfg.block_size,
                dtype=model_cfg.dtype,
                host_cache_bytes=cfg.host_cache_bytes,
                quantization=quant,
                state_slots=slots,
                block_state_bytes=(family.block_state_bytes(model_cfg)
                                   if by_block else 0),
                # a prefix hit would need the recurrent state as it stood
                # at the block boundary, or a windowed group's blocks there,
                # which were given back: no reuse for such a family
                prefix_reuse=self._prefix_reuse_why is None,
                groups=groups,
                planes=planes,
            ),
            state=(family.init_state(
                model_cfg, slots, *([cfg.num_blocks] if by_block else []))
                if self._stateful else None),
        )
        # What the rows of a prefill step hold past their reservations
        # while a chunk is written (kv_cache.py ``prefill_room``), set
        # aside once: 0 without windowed groups.
        # the window of the sliding layers (0: none): what their calls
        # attend of a row, for ``executor.dispatch``'s ``kv_tokens_window``
        self._kv_window = max(
            (window for window, _ in groups
             if group_kind(window) == "sliding"), default=0)
        # a composed family's (window, chunk): a decode row attends ``t mod
        # W + 1`` exact rows and ``W / C`` summaries a closed window
        # (``kv_tokens_window``, ``kv_chunks``), and its steps are counted
        # by the chunks and windows they complete
        self._kv_ring = self.cache.cfg.window_chunk
        self._eva_counts = dict.fromkeys(
            ("chunks_written_prefill", "chunks_written_decode",
             "windows_closed_prefill", "windows_closed_decode"), 0)
        if composed:
            self._check_composed_chunks(cfg, *self._kv_ring)
        self._kv_room = self.cache.cfg.prefill_room(
            cfg.max_prefill_batch,
            min(cfg.prefill_chunk_tokens or model_cfg.max_seq_len,
                model_cfg.max_seq_len))
        if self._kv_room:
            if not self.cache.can_reserve(self._kv_room):
                raise ValueError(
                    f"the windowed layers of model {cfg.model!r} need "
                    f"{self._kv_room} blocks set aside for prefill "
                    f"(max_prefill_batch x windowed groups x blocks of a "
                    f"chunk) and the pool has "
                    f"{self.cache.cfg.usable_blocks}: set "
                    "prefill_chunk_tokens, or a larger num_blocks")
            self.cache.reserve(self._kv_room)
        # the ModelExecutor seam (executor.py): the engine schedules on
        # host state only; weights, the KV pool arrays, and the jitted
        # step calls live behind the executor — single-device by
        # default, tp/fsdp-sharded when the config names a mesh
        self.executor = build_executor(
            cfg, model_cfg, self.cache, params=params
        )
        # Host-tier demote capture goes through the executor's existing
        # bulk-export funnel (the allowlisted _host_blocks path) — the
        # cache itself never touches the device.
        if cfg.host_cache_bytes > 0:
            self.cache.demote_fn = self.executor.export_blocks
        # speculative decoding: host-side drafter + acceptance accounting
        if cfg.speculative_k < 0:
            raise ValueError("speculative_k must be >= 0")
        if cfg.speculative_k > 0:
            from ray_tpu.serve.llm.drafter import build_drafter

            self._drafter = build_drafter(cfg.drafter)
        else:
            self._drafter = None
        self._spec_steps = 0            # verify steps run
        self._spec_drafted_total = 0    # draft tokens proposed
        self._spec_accepted_total = 0   # draft tokens accepted by verify
        self._spec_committed_total = 0  # tokens emitted by verify steps
        self._batch_buckets = cfg.batch_buckets or pow2_buckets(
            1, cfg.max_batch_size
        )
        self._length_buckets = cfg.length_buckets or pow2_buckets(
            cfg.block_size, model_cfg.max_seq_len
        )
        for b in self._length_buckets:
            if b % cfg.block_size:
                raise ValueError(
                    f"length bucket {b} is not a multiple of "
                    f"block_size={cfg.block_size}"
                )
        # A composed table has ONE width whatever the context's bucket,
        # that of the longest: its width follows the windows closed, which
        # no step's time follows (the kernel copies the pages it attends),
        # so a width a bucket would only be programs.
        self._composed_nb = self.cache.cfg.composed_blocks(
            min(self._length_buckets[-1], model_cfg.max_seq_len)
        ) if composed else None
        # A prefill step is filled by TOKENS where the cache manager says
        # a sequence may be split over the rows of one step
        # (``one_table``: pages under one table are all it carries, by
        # heads or in planes): a row is then a PIECE of a prompt, as many
        # tokens as the kernel gives one q tile (or the whole chunk where
        # that is shorter), at its true first position under its
        # sequence's table; a step holds up to ``_piece_rows[-1]`` of
        # them: one chunk's worth (the longest bucket, or
        # ``prefill_chunk_tokens``) and never fewer than
        # ``max_prefill_batch``, so as many short prompts go together as
        # ever. The rows are padded to a ladder of the engine's own
        # (``stepped_buckets``) and every row carries ONE table width, the
        # widest context's: the kernel's cost is the pages a row attends.
        # ``_piece`` None: a row is a request, padded to the longest
        # row's bucket (``_prefill_chunk_locked`` says who keeps that).
        self._piece: int | None = None
        if self.cache.cfg.one_table:
            from ray_tpu.ops.paged_attention import Q_TILE

            top = min(self._length_buckets[-1], model_cfg.max_seq_len)
            chunk = min(cfg.prefill_chunk_tokens or top, top)
            self._piece = min(Q_TILE, chunk)
            # A rung is a program, made at every start of a process. Over
            # a layer stack that is UNROLLED (a list of layers:
            # models/cached.py ``_walk``) its text is as long as the
            # layers are many: ~1.3 s of set-up a rung and ~18 MB of the
            # device's memory where a scanned stack's costs a quarter
            # (PERF.md section 6, PR 47), so such an engine takes the
            # ladder of wider steps: 3 points of fill for a third fewer
            # programs.
            unrolled = any(isinstance(leaf, list)
                           for leaf in self.executor.params.values())
            self._piece_rows = stepped_buckets(
                max(-(-chunk // self._piece), cfg.max_prefill_batch),
                wide=unrolled)
            self._piece_nb = self._table_blocks(self._length_buckets[-1])
        self._lock = threading.RLock()
        self._work = threading.Condition(self._lock)
        self._waiting: deque[_Request] = deque()
        self._waiting_blocks = 0  # worst-case blocks held by the queue
        self._prefilling: list[_Request] = []  # admitted, prefill incomplete
        self._running: list[_Request] = []
        # ---- priority preemption (ISSUE 17) ----
        if isinstance(cfg.preemption, dict):
            self._preemption: PreemptionConfig | None = PreemptionConfig(
                **cfg.preemption
            )
        else:
            self._preemption = cfg.preemption
        # paused streams: zero KV blocks held, cursor/timeline/FSM intact,
        # token chain re-prefills (host tier serving the hashed full
        # blocks) when pressure clears
        self._preempted: list[_Request] = []
        self._preempted_total = 0
        # True while pressure holds AND no lower-priority victim remains —
        # the point where per-class shedding (autoscaling_policy) kicks in
        self._preempt_exhausted = False
        self._next_id = 0
        self._auto_step = auto_step
        self._thread: threading.Thread | None = None
        self._watchdog: threading.Thread | None = None
        self._stopped = False
        # Set by _fail_engine / the watchdog; read WITHOUT the lock (the
        # whole point is surviving a step that wedged while holding it).
        self._failed: EngineDiedError | None = None
        # perf_counter() at step entry, None when no step is in flight —
        # plain attribute so the watchdog can read it lock-free.
        self._step_begin: float | None = None
        self._rejected_total = 0
        self._cancelled_total = 0
        self._deadline_total = 0
        self._prefill_tokens_total = 0  # tokens actually run through prefill
        # ... and the slots its launches held for them, rows x row length,
        # padding included; the launches that were packed (``_piece``)
        self._prefill_slots = 0
        self._prefill_steps_packed = 0
        # the prefill launches' dispatch spans added up: the (query, key)
        # pairs their attention covered, and of a latent family (its
        # ``step_attrs``) those that went through the EXPANDED form and
        # the key blocks of resident prefixes up-projected a layer
        self._prefill_pairs = {
            "qk_pairs": 0, "expanded_pairs": 0, "prefix_blocks": 0}
        # "prefill" | "decode" | None — drives prefill/decode alternation
        # and gives tests a step-order trace.
        self.last_step_kind: str | None = None
        # ---- dispatch-ahead pipeline ----
        # the step programs launched and not yet synced, oldest first:
        # never more than TWO (``_launched_locked``), and one or none
        # between steps (a step launches, then reconciles what is older)
        self._inflight: list[_InFlight] = []
        self._launched = 0            # step programs launched, ever
        self._inflight_high_water = 0
        # Reusable numpy scratch, keyed (name, shape): shapes come from
        # the closed bucket ladders so the pool is bounded. Each key holds
        # TWO buffers used alternately — the jitted call they are handed to
        # can alias host memory zero-copy on the CPU backend, so a buffer
        # must not be mutated until the dispatch that consumed it has
        # provably executed; with at most two programs in flight, the
        # launch before last has always synced by the time its buffer
        # comes around again.
        self._scratch: dict[tuple, list] = {}
        self._sync_seconds_total = 0.0
        self._sync_bytes_total = 0
        self._last_sync: dict | None = None  # merged into flight records
        # last cache-stat values already exported to the monotonic counters
        self._exported = {
            "hit": 0, "evict": 0, "cow": 0, "prefill": 0,
            "demote": 0, "promote": 0,
        }
        # ---- observability plane (ISSUE 4) ----
        self._flight = obs.FlightRecorder(cfg.flight_recorder_steps)
        # finished-request timelines, newest-last, bounded
        self._timelines: OrderedDict[Any, dict] = OrderedDict()
        # per-step admission/expiry counts for the flight record (set by
        # step(), read by the phase that runs in the same iteration)
        self._step_admitted = 0
        self._step_expired = 0
        # cache-stat values as of the previous flight record (deltas)
        self._flight_prev = {"cow": 0, "evict": 0, "demote": 0, "promote": 0}
        # ... and the stepping thread's lock waits, the collector's
        # seconds and the thread's CPU as of it (with the thread whose
        # CPU that is): a record says what of each fell between the
        # record before it and itself
        self._lock_wait_s = 0.0
        self._flight_host_prev = (0.0, obs.gc_watch.total_seconds(), 0.0)
        self._flight_thread: int | None = None
        self._dumped = False  # one post-mortem dump per engine
        # Host phases (obs.phase): the step in progress books into
        # ``_step_phases`` (an ``obs.PhaseTable``; the executor books its
        # two phases there too), and ``step()`` folds that into
        # ``_phases[kind]`` (count and seconds: ``stats()["phases"]``) and
        # ``_phase_cpu[kind]`` ([cpu_seconds, cpu_measured_seconds]) under
        # the kind the step went on to run ("none": nothing ran; the
        # loop's ``engine.wait`` goes there too). ``_host_spans``: the
        # spans that are no phase of a step, the wait for this lock
        # before one (``engine.lock``) and the executor's id gather inside
        # ``executor.stage`` (``executor.feed``): ``stats()["host"]``.
        # The phases of one step in ``obs.CPU_EVERY`` read the CPU clock
        # (``_steps``); the two spans always do (a wait's share on the
        # core says nothing of the next wait's).
        self._step_phases = obs.PhaseTable()
        self._phases: dict[str, dict[str, list]] = {}
        self._phase_cpu: dict[str, dict[str, list]] = {}
        self._host_spans = obs.PhaseTable()
        self._steps = 0
        self._step_kind = "none"
        self._decode_steps = 0  # decode dispatches ...
        # ... launched with a step in flight and nothing synced first ...
        self._decode_steps_steady = 0
        # ... of those, over another batch than that step's (ids gathered)
        self._decode_steps_remapped = 0
        # the rows of the decode dispatches of a family with sliding
        # layers, and those whose context exceeded the window (0 without)
        self._decode_rows = 0
        self._decode_rows_past_window = 0
        self._prefill_steps = 0  # prefill dispatches ...
        self._prefill_syncs_deferred = 0  # ... synced behind a later launch
        # tokens the ``engine.emit`` passes put on streams
        self._emit_rows = 0
        # ---- autoscaling signal windows (ISSUE 10) ----
        # Bounded sample/event rings feeding autoscaling_snapshot(): the
        # controller's policy wants recent-tail saturation (queue-wait
        # p95, decode-step p50, miss/reject rates), not lifetime totals.
        self._queue_wait_window: deque[float] = deque(maxlen=256)
        self._decode_step_window: deque[float] = deque(maxlen=256)
        self._reject_clocks: deque[float] = deque(maxlen=512)
        self._deadline_clocks: deque[float] = deque(maxlen=512)
        self._last_snapshot: dict | None = None

        self._m_tokens = metrics.counter(
            "llm_engine_tokens_generated",
            "Tokens generated by the serve/llm engine",
        )
        self._m_queue = metrics.gauge(
            "llm_engine_queue_depth", "Requests waiting for admission"
        )
        self._m_util = metrics.gauge(
            "llm_engine_kv_block_utilization",
            "Fraction of usable KV blocks allocated",
        )
        self._m_latency = metrics.histogram(
            "llm_engine_step_latency_seconds",
            "Engine step latency by kind (prefill/decode)",
            boundaries=(0.001, 0.005, 0.02, 0.1, 0.5, 2.0, 10.0),
            tag_keys=("kind",),
        )
        self._m_rejected = metrics.counter(
            "llm_requests_rejected",
            "Requests rejected by engine admission control (overload)",
        )
        self._m_cancelled = metrics.counter(
            "llm_requests_cancelled",
            "Requests cancelled (client disconnect / explicit cancel)",
        )
        self._m_deadline = metrics.counter(
            "llm_deadline_exceeded",
            "Requests evicted because deadline_s expired mid-generation",
        )
        self._m_finished = metrics.counter(
            "llm_requests_finished",
            "Requests that completed generation normally (availability "
            "SLO denominator)",
        )
        self._m_hit_tokens = metrics.counter(
            "llm_prefix_hit_tokens",
            "Prompt tokens served from the KV prefix cache (zero compute)",
        )
        self._m_evicted = metrics.counter(
            "llm_prefix_evicted_blocks",
            "Cached KV blocks evicted LRU to satisfy new allocations",
        )
        self._m_cow = metrics.counter(
            "llm_cow_blocks",
            "Copy-on-write block copies (writes into shared KV blocks)",
        )
        self._m_prefill_tokens = metrics.counter(
            "llm_prefill_tokens",
            "Prompt tokens actually computed by prefill (cache misses)",
        )
        self._m_spec_drafted = metrics.counter(
            "llm_spec_drafted_tokens",
            "Draft tokens proposed to speculative verify steps",
        )
        self._m_spec_accepted = metrics.counter(
            "llm_spec_accepted_tokens",
            "Draft tokens accepted by speculative verify steps",
        )
        self._m_spec_committed = metrics.counter(
            "llm_spec_committed_tokens",
            "Tokens committed by speculative verify steps (accepted + "
            "corrected/bonus)",
        )
        self._m_demoted = metrics.counter(
            "llm_kv_demoted_blocks",
            "KV blocks demoted from the device pool into the host cache "
            "tier on LRU eviction",
        )
        self._m_promoted = metrics.counter(
            "llm_kv_promoted_blocks",
            "Host-tier KV blocks promoted back into the device pool on "
            "prefix hits",
        )
        self._m_host_blocks = metrics.gauge(
            "llm_host_cache_blocks",
            "Demoted KV blocks resident in the host cache tier",
        )
        self._m_structured = metrics.counter(
            "llm_structured_requests",
            "Requests admitted with a grammar constraint "
            "(response_format / SamplingParams.structured)",
        )
        self._m_masked_frac = metrics.histogram(
            "llm_structured_masked_fraction",
            "Fraction of the vocab banned by the grammar allow-mask at "
            "each constrained decode position",
            boundaries=(0.5, 0.9, 0.99, 0.995, 0.999, 0.9999),
        )
        self._m_ttft = obs.ttft_histogram()
        self._m_tpot = obs.tpot_histogram()
        self._m_queue_wait = obs.queue_wait_histogram()
        self._m_sync = obs.host_sync_histogram()
        self._m_sync_bytes = obs.sync_bytes_counter()
        self._m_compile = obs.compile_counter()
        self._m_devices = metrics.gauge(
            "llm_executor_devices",
            "Devices driven by this engine's model executor",
        )
        self._m_devices.set(self.executor.num_devices)
        # autoscaling-signal gauges, refreshed on every snapshot pull
        self._m_as_queue = metrics.gauge(
            "llm_queue_depth",
            "Admission queue depth as seen by the autoscaler",
        )
        self._m_as_kv_free = metrics.gauge(
            "llm_kv_free_blocks",
            "Truly free (unallocated, uncached) KV blocks in the pool",
        )
        self._m_as_kv_pressure = metrics.gauge(
            "llm_kv_pool_pressure",
            "Fraction of the usable KV pool a new admission cannot claim "
            "(allocations + reservations + quarantine)",
        )
        # priority preemption (ISSUE 17)
        self._m_preemptions = metrics.counter(
            "llm_preemptions_total",
            "Running streams paused to the host KV tier to make room for "
            "higher-priority work",
        )
        self._m_preempted_streams = metrics.gauge(
            "llm_preempted_streams",
            "Streams currently parked in the preempted state",
        )
        self._m_preempted_wait = metrics.histogram(
            "llm_preempted_wait_seconds",
            "Seconds a preempted stream spent parked before resuming",
            boundaries=(0.01, 0.05, 0.25, 1.0, 5.0, 30.0, 120.0),
        )
        # ---- serving goodput / MFU accounting (ISSUE 13) ----
        # Analytic forward FLOPs per token: 2 FLOPs per weight
        # (multiply+accumulate), the serving-side counterpart of the
        # training 6N rule (docs/ROOFLINE.md).
        self._flops_per_token = 2.0 * self.executor.num_params
        # per step kind: the window's (clock, device_s, tokens) step
        # samples with their two running sums, plus the last derived
        # rates, for stats()/the decode bench
        self._goodput_windows: dict[str, list] = {}
        self._goodput_last: dict[str, dict] = {}
        self._m_goodput = obs.goodput_gauge()
        self._m_mfu = obs.mfu_gauge()
        # count compile events by shape key as DecodeFns sees new
        # signatures (attribute hook, forwarded through the executor —
        # DecodeFns stays constructible bare)
        self.executor.on_new_signature = self._on_new_signature
        self.executor.phases = self._step_phases
        self.executor.spans = self._host_spans
        # ---- generation by diffusion over blocks ----
        # row-passes launched, those that chose no token (a request's
        # last block's commit) and those that FOLDED (a finished block
        # committed by the next block's first pass); blocks committed,
        # their tokens that reached a stream, and those generated and not
        # delivered (a last block's tail, what follows an EOS)
        self._block_counts = dict.fromkeys(
            ("block_passes", "block_passes_commit", "block_passes_folded",
             "blocks_committed", "block_tokens_committed",
             "block_tokens_cut"), 0)
        if self._block_len:
            from ray_tpu.ops.sampling import (
                REMASKING, fill_counts, pass_fills)

            # the schedule's two functions and the orders' names, for
            # ``_block_row`` (a submit imports nothing)
            self._schedule = (fill_counts, pass_fills, REMASKING)
            # Whether a family steps by blocks is settled HERE, once: such
            # an engine takes its own decode step, budget rule and emit
            # pass, and every other engine runs the lines it ran before
            # there was one.
            self._decode_locked = self._decode_blocks_locked
            self._eligible_locked = self._eligible_blocks_locked
            self._emit_decoded_locked = self._emit_blocks_locked
        obs.gc_watch.acquire()  # last: nothing above may raise past it

    @staticmethod
    def _no_prefix_reuse(stateful: bool, grouped: bool,
                         composed: bool) -> str | None:
        """Why a prompt's prefix is never mapped onto resident blocks for
        such a family (``prefix_caching`` then finds nothing, silently: it
        is on by default); None where it is reused."""
        if stateful:
            return ("a hit would need the per-sequence state as it stood "
                    "at the block's boundary")
        if composed:
            return ("a hit would need the ring as it stood at the hit's "
                    "boundary, and the summary blocks are not "
                    "content-addressed")
        if grouped:
            return ("a hit would need the windowed groups' blocks at its "
                    "boundary, which were given back")
        return None

    @staticmethod
    def _check_composed_chunks(cfg: EngineConfig, window: int,
                               chunk: int) -> None:
        """A composed family's prefill step lies inside ONE window and
        starts on a chunk's first position, so that its queries share a
        composed table and its whole chunks are summarised from the fresh
        K/V: ``prefill_chunk_tokens`` divides the window and is whole
        chunks (steps start on its multiples)."""
        cap = cfg.prefill_chunk_tokens
        if cap is None or window % cap or cap % chunk:
            raise ValueError(
                f"model {cfg.model!r} attends exactly inside windows of "
                f"{window} positions and by chunks of {chunk} behind them: "
                f"prefill_chunk_tokens must divide {window} and be a "
                f"multiple of {chunk}, so that a prefill step lies inside "
                f"one window and summarises whole chunks; it is {cap}")

    @staticmethod
    def _check_whole_blocks(cfg: EngineConfig, width: int) -> None:
        """A family that generates by blocks of ``width`` positions: a
        page of the cache and a prompt's chunk are whole blocks, so that a
        page's K/V depends on no token past the page (content-addressed
        reuse stays sound at page boundaries) and a chunk starts and ends
        on a block's edge."""
        for name, value in (("block_size", cfg.block_size),
                            ("prefill_chunk_tokens",
                             cfg.prefill_chunk_tokens)):
            if value and value % width:
                raise ValueError(
                    f"model {cfg.model!r} attends by whole blocks of "
                    f"{width} positions and commits its K/V a block at a "
                    f"time: {name} must be a multiple of {width}, it is "
                    f"{value}")

    @staticmethod
    def _check_selected_pages(cfg: EngineConfig, block: int,
                              segment: int) -> None:
        """A family whose attention selects its pages by blocks of
        ``block`` keys (the model config's ``kv_selected_pages``): a page
        of the cache is a selection block, and a prefill step starts on a
        whole ``segment`` of compressed keys (ops/sparse_select.py)."""
        if cfg.block_size != block:
            raise ValueError(
                f"model {cfg.model!r} selects the blocks of {block} keys "
                f"it attends, and a page of the cache is such a block: "
                f"block_size must be {block}, it is {cfg.block_size}")
        chunk = cfg.prefill_chunk_tokens
        if chunk and chunk % block:
            raise ValueError(
                f"model {cfg.model!r} keeps a sum of keys a segment of "
                f"{segment} tokens, set by the step that writes the "
                f"segment's first token: prefill_chunk_tokens must be "
                f"whole blocks of {block}, it is {chunk}")

    @staticmethod
    def _refuse_for_state(cfg: EngineConfig, quant, stateful: bool,
                          grouped: bool, composed: bool = False,
                          latent: bool = False,
                          blocks: bool = False) -> None:
        """Raise for each option that cannot yet carry what the family
        keeps: per-sequence state beside the pool (``lfm2_moe``: a short
        convolution's rows; ``minicpm_sala``: a lightning matrix a head, and
        compressed keys by block id; ``ling_hybrid``: a KDA matrix a head
        and a convolution's rows, BESIDE a pool in planes: the state's list
        refuses all the planes' list does and preemption besides;
        ``falcon_h1``: an SSM matrix a head and a convolution's rows in
        EVERY layer, each of which also pages), tables by group of layers
        (``laguna``), a ring and chunk summaries (``evabyte``), one latent
        row a token in planes (``pangu_ultra_moe``, ``longcat_flash``), or
        generation by diffusion over blocks (``sdar_moe``), each with why."""
        asked = {
            "speculative_k": cfg.speculative_k > 0,
            "host_cache_bytes": cfg.host_cache_bytes > 0,
            "preemption": cfg.preemption is not None,
            "quantization": quant is not None,
            "tp/fsdp/mesh": (
                cfg.mesh is not None or cfg.tp != 1 or cfg.fsdp != 1),
        }
        for keeps, what, why in (
            (stateful, "keeps per-sequence state beside the paged cache", {
                "speculative_k":
                    "rejected drafts would need the per-sequence state "
                    "rolled back, and the family has no verify step",
                "host_cache_bytes":
                    "a block promoted from the host tier restores K/V but "
                    "not the state at its boundary",
                "preemption":
                    "a paused stream's state slot is not demoted with its "
                    "blocks",
                "quantization":
                    "the weights of the layers that keep the state (expert and "
                    "conv; lightning; kda; ssm) have no quantized path, and a "
                    "quantized pool has no plane for compressed keys",
                "tp/fsdp/mesh":
                    "ShardedExecutor has no expert axis and does not place "
                    "the state arrays (nor split a state's heads)"}),
            (grouped, "keeps its K/V in tables by group of layers", {
                "speculative_k":
                    "a rejected window may reach behind blocks a windowed "
                    "group already gave back",
                "host_cache_bytes":
                    "the host tier holds one block a digest, a chain of "
                    "the one table",
                "preemption":
                    "a paused stream's chain is demoted from one table, "
                    "and the windowed groups' blocks behind it are gone",
                "quantization":
                    "the scale planes of a quantized pool are not laid "
                    "out by group",
                "tp/fsdp/mesh":
                    "ShardedExecutor places one table a step and has no "
                    "expert axis"}),
            (composed, "keeps a ring of one window's K/V and a table of "
                       "chunk summaries a sequence", {
                "speculative_k":
                    "a rejected draft's K/V may already be folded into "
                    "its chunk's summary, and the family has no verify "
                    "step",
                "host_cache_bytes":
                    "the host tier holds one block a digest of one "
                    "table; a ring block holds whatever window came "
                    "last, and a summary block has no digest",
                "preemption":
                    "a paused stream's chain is demoted from one table "
                    "of prompt blocks; neither the ring nor the "
                    "summaries are",
                "quantization":
                    "a summary is written at a token's shape in the "
                    "pool's own dtype, and the scale planes of a "
                    "quantized pool have no slot for it",
                "tp/fsdp/mesh":
                    "ShardedExecutor places one table a step"}),
            (latent, "caches one latent row a token for all heads, in "
                     "planes", {
                "speculative_k":
                    "the family has no verify step, and nothing that would "
                    "draft (a multi-token-prediction module) is held",
                "host_cache_bytes":
                    "the host tier's record (kv_transfer.KVLayout) "
                    "describes a block as n_kv_head x head_dim twice and "
                    "cannot say planes",
                "quantization":
                    "a quantized pool's scale planes are one scale a "
                    "(token, head) and a latent row has no head; the "
                    "expert weights have no quantized path either",
                "tp/fsdp/mesh":
                    "ShardedExecutor splits the pool along its head axis "
                    "and one shared row has none; it has no expert axis "
                    "either"}),
            (blocks, "generates by diffusion over blocks", {
                "speculative_k":
                    "there is nothing to draft for: a pass fills a "
                    "block's positions in any order and leaves no "
                    "next-token distribution to check, and the family has "
                    "no verify step",
                "preemption":
                    "a paused row's provisional block and its place in "
                    "the block's schedule are not parked with its "
                    "committed chain",
                "quantization":
                    "the expert weights have no quantized path",
                "tp/fsdp/mesh":
                    "ShardedExecutor has no expert axis"}),
        ):
            for option, reason in why.items():
                if keeps and asked[option]:
                    raise ValueError(
                        f"model {cfg.model!r} {what} and cannot be served "
                        f"with {option}: {reason}")

    def _refuse_handoff(self, what: str) -> None:
        if self.cache.cfg.planes:
            raise ValueError(
                f"model {self.cfg.model!r} caches one latent row a token "
                f"for all heads, in planes, and cannot {what}: the "
                "handoff's record (kv_transfer.KVLayout) describes a block "
                "as n_kv_head x head_dim twice and cannot say planes")
        if self._state_rows:
            raise ValueError(
                f"model {self.cfg.model!r} keeps per-sequence state beside "
                f"the paged cache and cannot {what}: the prefill/decode "
                "handoff moves K/V blocks, not the state at their boundary")
        if self.cache.cfg.composed:
            raise ValueError(
                f"model {self.cfg.model!r} keeps a ring of one window's K/V "
                f"and a table of chunk summaries and cannot {what}: the "
                "prefill/decode handoff moves a prompt's blocks of one "
                "table, and a ring holds only the last window's")
        if self.cache.cfg.groups:
            raise ValueError(
                f"model {self.cfg.model!r} keeps its K/V in tables by group "
                f"of layers and cannot {what}: the prefill/decode handoff "
                "moves the blocks of one table")

    # ---------------- public API ----------------

    def submit(
        self,
        prompt: Sequence[int],
        sampling: SamplingParams | None = None,
        *,
        trace_ctx: dict | None = None,
        **sampling_overrides,
    ) -> TokenStream:
        """Enqueue one request; returns a stream of generated token ids.

        ``trace_ctx`` carries the caller's trace context
        (``tracing.current_context()`` shape) across the thread boundary
        into the scheduler; when absent, the submitting thread's active
        span is captured. With a context, the request's phase timeline is
        emitted as ``engine.*`` spans on completion — one trace covers
        HTTP -> router -> replica -> engine.

        Raises ``EngineOverloadedError`` when admission control rejects
        (waiting queue full, or queued worst-case blocks over budget) and
        ``EngineDiedError`` when the engine has already failed.
        """
        if trace_ctx is None:
            trace_ctx = tracing.current_context()
        if sampling is None:
            sampling = SamplingParams(**sampling_overrides)
        elif sampling_overrides:
            import dataclasses

            sampling = dataclasses.replace(sampling, **sampling_overrides)
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("prompt must contain at least one token")
        total = len(prompt) + sampling.max_new_tokens
        if total > self.model_cfg.max_seq_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({sampling.max_new_tokens}) exceeds model max_seq_len "
                f"{self.model_cfg.max_seq_len}"
            )
        need = self.cache.cfg.request_blocks(total)
        if need > self.cache.cfg.usable_blocks - self._kv_room:
            raise ValueError(
                f"request needs {need} KV blocks "
                f"but the pool only has "
                f"{self.cache.cfg.usable_blocks - self._kv_room}"
            )
        # grammar constraint: compile (LRU-cached) and position the FSM
        # cursor OUTSIDE the scheduler lock — compile is submit-path
        # work, and a bad grammar is the client's error (GrammarError is
        # a ValueError -> the proxies answer 400, never 500)
        blk = self._block_row(prompt, sampling)
        fsm = None
        spec = structured.parse_response_format(sampling.structured)
        if spec is not None and blk is not None:
            raise ValueError(
                f"model {self.cfg.model!r} generates by diffusion over "
                "blocks and cannot be served with a grammar: a grammar's "
                "cursor advances a token at a time, left to right, and a "
                "pass fills a block's positions in any order")
        if spec is not None:
            dfa = structured.compile_grammar(
                spec, self.model_cfg.vocab_size, self.cfg.eos_id
            )
            fsm = structured.FSMCursor(dfa)
            if sampling.start_index > 0:
                # failover resume: replay the already-delivered tokens
                # (the prompt tail) so the cursor lands where the dead
                # replica's stood
                for t in prompt[-sampling.start_index:]:
                    if not fsm.advance(t):
                        raise structured.GrammarError(
                            f"resumed prefix rejected by the grammar at "
                            f"token {t} (response_format mismatch on "
                            "resume?)"
                        )
            self._m_structured.inc()
        if self._failed is not None:
            raise self._failed
        # stamped before the lock, which a step in flight holds to its end:
        # ``submitted - received`` in the timeline is the wait for it
        received = obs.wall()
        with self._lock:
            if self._stopped:
                raise RuntimeError("engine is shut down")
            if len(self._waiting) >= self.cfg.max_waiting or (
                self.cfg.max_waiting_blocks is not None
                and self._waiting_blocks + need > self.cfg.max_waiting_blocks
            ):
                self._rejected_total += 1
                self._m_rejected.inc()
                self._reject_clocks.append(obs.clock())
                raise EngineOverloadedError(
                    f"admission queue full ({len(self._waiting)} waiting, "
                    f"{self._waiting_blocks} worst-case blocks queued); "
                    "retry later"
                )
            req = _Request(self._next_id, prompt, sampling, trace_ctx)
            req.fsm = fsm
            req.blk = blk
            self._next_id += 1
            req.submitted_clock = obs.clock()
            self._tl(req, "received", ts=received)
            self._tl(req, "submitted", prompt_tokens=len(prompt),
                     max_new_tokens=sampling.max_new_tokens)
            self._waiting.append(req)
            self._waiting_blocks += need
            self._m_queue.set(len(self._waiting))
            self._work.notify_all()
        if self._auto_step:
            self._ensure_thread()
        return TokenStream(req)

    def _routed_dims(self, kind: str, ids_shape: tuple) -> tuple:
        """``(rows[, tokens a row])`` a step program routes through its
        expert layers, for ``stats()["moe_gmm_form"]``: the shape of its
        ids, but a block pass's, which is traced two blocks a row (a
        finished block and the fresh one behind it: models/cached.py
        ``_block_step``) whatever words ride beside the ids."""
        if kind == "decode" and self._block_len:
            return (ids_shape[0], 2 * self._block_len)
        return tuple(ids_shape)

    def _block_row(self, prompt: list,
                   sampling: SamplingParams) -> "_BlockRow | None":
        """The request's block and schedule where the family generates by
        diffusion over blocks (the request's steps and order, else the
        model configuration's); None for a family that yields a token a
        step, which refuses those settings by name."""
        asked = {name: getattr(sampling, name) for name in (
            "denoising_steps", "remasking")}
        if not self._block_len:
            for name, value in asked.items():
                if value is not None:
                    raise ValueError(
                        f"model {self.cfg.model!r} yields one token a "
                        f"sequence a step and has no {name}: that is a "
                        "setting of a family that generates by diffusion "
                        "over blocks")
            return None
        cfg = {name: getattr(self.model_cfg, name) if value is None
               else value for name, value in asked.items()}
        fill_counts, pass_fills, orders = self._schedule
        return _BlockRow(
            prompt, fill_counts(self._block_len, cfg["denoising_steps"]),
            orders.index(cfg["remasking"]), self._block_len,
            self.model_cfg.mask_token_id, pass_fills)

    def generate(
        self,
        prompt: Sequence[int],
        sampling: SamplingParams | None = None,
        **sampling_overrides,
    ) -> list[int]:
        """Synchronous convenience: submit and collect all tokens."""
        stream = self.submit(prompt, sampling, **sampling_overrides)
        if not self._auto_step:
            while not stream.done:
                if not self.step():
                    break  # pragma: no cover — queue drained early
        return list(stream)

    def step(self) -> bool:
        """One scheduler iteration: expire deadlines, admit what fits,
        then EITHER one prefill chunk (new admissions or the next slice of
        an in-flight prompt) OR one batched decode step. When both kinds
        of work exist the scheduler alternates, so a long chunked prefill
        never starves running sequences of decode steps. Returns False
        when idle, and once shut down."""
        self._lock_timed()
        try:
            return self._step_locked()
        finally:
            self._fold_phases_locked()
            self._lock.release()

    def _step_locked(self) -> bool:
        """``step`` with the lock held. The caller books the step's
        phases (``_fold_phases_locked``) before it lets the lock go."""
        if self._stopped:
            return False
        self._step_phases.cpu = self._steps % obs.CPU_EVERY == 0
        self._steps += 1
        self._step_begin = obs.clock()
        self._step_kind = "none"
        try:
            chaos.fire("engine.step")
            with self._phase("engine.schedule"):
                self._step_expired = self._expire_deadlines_locked()
                if self._preemption is not None:
                    self._maybe_resume_locked()
                    self._maybe_preempt_locked()
                self._step_admitted = self._admit_locked()
            # Fresh admissions prefill immediately (first token out the
            # door); CONTINUING chunks of a long prompt alternate with
            # decode so running sequences are never starved.
            if self._prefilling and (
                self.last_step_kind != "prefill"
                or not self._running
                or any(not r.started for r in self._prefilling)
            ):
                self._prefill_chunk_locked()
                self.last_step_kind = "prefill"
                return True
            if self._running or self._inflight:
                # in-flight-but-nothing-running still needs a step: the
                # lagged tokens must be reconciled (and blocks freed)
                # even when every row has since finished or evicted
                self._decode_locked()
                self.last_step_kind = "decode"
                return True
            return False
        finally:
            self._step_begin = None

    def _lock_timed(self) -> None:
        """Take the engine's lock for a step, under the ``engine.lock``
        span: from asking for it to having it. What holds it meanwhile
        is a client inside ``submit`` or ``cancel``, or a reader of
        ``stats()``. Booked once the lock is held. (The stepping loop
        opens the span earlier, where its last step ended: ``_loop``.)"""
        with obs.phase(self._host_spans, "engine.lock") as ph:
            self._lock.acquire()
        self._lock_wait_s += ph.seconds

    def _phase(self, name: str, **attrs) -> obs.phase:
        """A host phase of the step in progress (obs.phase)."""
        return obs.phase(self._step_phases, name, **attrs)

    def _fold_phases_locked(self) -> None:
        """Book the finished step's phases under the kind it ran."""
        into = self._phases.setdefault(self._step_kind, {})
        cpu_into = self._phase_cpu.setdefault(self._step_kind, {})
        for name, (count, seconds, cpu, measured) in \
                self._step_phases.items():
            rec = into.setdefault(name, [0, 0.0])
            rec[0] += count
            rec[1] += seconds
            rec = cpu_into.setdefault(name, [0.0, 0.0])
            rec[0] += cpu
            rec[1] += measured
        self._step_phases.clear()

    def cancel(self, request_id) -> bool:
        """Evict a waiting/prefilling/running request, fail its stream
        with ``RequestCancelledError``, and return its KV blocks: at once,
        or, where a step program in flight still holds the row, at that
        step's reconcile (exactly once either way). Returns False when
        the request is unknown or already finished (idempotent — safe to
        broadcast to every replica)."""
        with self._lock:
            req = self._find_locked(request_id)
            if req is None:
                return False
            self._evict_locked(req)
            self._cancelled_total += 1
            self._m_cancelled.inc()
            self._finish_obs_locked(req, "cancelled")
            req.out.put(
                RequestCancelledError(f"request {request_id!r} cancelled")
            )
            req.out.put(_DONE)
            return True

    # ------------- disaggregated prefill/decode handoff -------------

    def kv_layout(self):
        """The pool's tensor layout as a ``kv_transfer.KVLayout`` — both
        sides of a handoff compare these for exact equality before any
        block moves (a mismatched model/dtype refuses the handoff)."""
        from ray_tpu.serve.llm.kv_transfer import KVLayout

        c = self.cache.cfg
        return KVLayout(
            n_layer=c.n_layer, block_size=c.block_size,
            n_kv_head=c.n_kv_head, head_dim=c.head_dim,
            dtype=self.cache.k.dtype.name,
            quantization=getattr(c, "quantization", None),
        )

    def export_prefix(self, prompt) -> list:
        """PREFILL side of a disaggregated handoff: the resident leading
        full blocks of ``prompt`` as (chain_digest, k_np, v_np) records
        in chain order, host-side. Runs under the scheduler lock so no
        exported block can be LRU-evicted between the chain walk and the
        device gather; a partial chain (earlier eviction) yields a
        shorter — still valid — handoff. Call after prefill finished
        (e.g. a drained max_new_tokens=1 generate), when the prompt's
        blocks are content-addressed in the prefix cache."""
        self._refuse_handoff("export a prefix")
        with self._lock:
            # a prefill still in flight has not registered its blocks yet
            self._collapse_locked()
            chain = self.cache.export_chain(prompt)
            if not chain:
                return []
            ids = [b for _, b in chain]
            k, v = self.executor.export_blocks(ids)
        return [(d, k[:, i], v[:, i]) for i, (d, _) in enumerate(chain)]

    def adopt_prefix(self, prompt, records) -> int:
        """DECODE side of a handoff: verify each record's chain digest
        against THIS engine's hash of ``prompt`` (kv_cache._block_key —
        the payload's self-declared digests are never trusted), claim
        pool blocks, and land the K/V payloads with one fused scatter.
        Landed blocks enter the prefix cache as cached (refcount-0)
        entries, so the follow-up ``submit`` of the same prompt scores a
        full prefix hit and decodes as if prefilled locally.

        Idempotent and best-effort: already-resident digests are skipped
        (retries, concurrent identical prompts), a digest mismatch or a
        full pool stops the walk — earlier blocks still count. Returns
        the number of leading prompt blocks resident afterwards."""
        self._refuse_handoff("adopt a prefix")
        bs = self.cache.cfg.block_size
        with self._lock:
            # Cap adoptions at the spare (unreserved) capacity: landing
            # into reserved headroom is wasted motion — the admissions
            # holding those reservations would evict the fresh blocks
            # before the follow-up submit could hit them.
            budget = self.cache.spare_blocks
            digest = b""
            ids: list[int] = []
            ks: list[np.ndarray] = []
            vs: list[np.ndarray] = []
            resident = 0
            for i, (chain, k_blk, v_blk) in enumerate(records):
                if (i + 1) * bs > len(prompt):
                    break  # record beyond the prompt's full blocks
                digest = _block_key(digest, prompt[i * bs:(i + 1) * bs])
                if digest != chain:
                    break  # not our tokens from position 0 — refuse
                if self.cache.has_digest(digest):
                    resident += 1
                    continue
                if len(ids) >= budget:
                    break  # only reserved headroom left — partial is fine
                b = self.cache.adopt_block(digest)
                if b is None:
                    break  # pool has no claimable block — partial is fine
                ids.append(b)
                ks.append(k_blk)
                vs.append(v_blk)
                resident += 1
            if ids:
                from ray_tpu.ops.quantization import stack_blocks

                self.executor.land_blocks(
                    ids, stack_blocks(ks, axis=1), stack_blocks(vs, axis=1)
                )
        return resident

    def stats(self) -> dict:
        with self._lock:
            cs = self.cache.stats
            hit = cs.prefix_hit_tokens
            computed = self._prefill_tokens_total
            # the counters the family's programs keep on the device
            # (lfm2_moe: moe_pairs_*, moe_expert_reads_decode): only the
            # reference is taken here. Reading it waits for the step in
            # flight, so that is done below, with the lock released; a
            # failed engine's device is not asked
            counters = (None if self._failed is not None
                        else self.executor.counter_state())
            out = {
                "waiting": len(self._waiting),
                "prefilling": len(self._prefilling),
                "running": len(self._running),
                "preempted": len(self._preempted),
                "preemptions_total": self._preempted_total,
                "preempt_exhausted": self._preempt_exhausted,
                "kv_used_blocks": self.cache.used_blocks,
                "kv_high_water_blocks": cs.high_water_blocks,
                # tables by group of layers ([] for one table): a group's
                # window, layers, blocks held now and at most; the blocks
                # the windowed groups took, and gave back behind the
                # window while their sequence lived
                "kv_groups": self.cache.group_report(),
                # what a token's row in the pool is: K and V by head, or a
                # latent family's one plane, and its bytes
                "kv_pool": self.cache.cfg.describe_pool(),
                "kv_window_blocks_taken": cs.window_blocks_taken,
                "kv_window_blocks_freed": cs.window_blocks_freed,
                # per-sequence state beside the pool (0 for a family that
                # keeps none), and whether a prefix hit can be reused
                "state_slots": self.cache.used_slots,
                "state_slots_high_water": cs.state_slots_high_water,
                # bytes ``state`` holds on the device, every slot and
                # counter (a KDA or lightning family's matrices a slot)
                "state_bytes": self.executor.state_bytes(),
                # what the blocks in use hold in a third plane by block id
                # (a selecting family's compressed keys; 0 for the others)
                "kv_compressed_key_bytes": (
                    self.cache.used_blocks
                    * self.cache.cfg.block_state_bytes),
                "prefix_reuse": self.cache.cfg.prefix_reuse,
                "prefix_reuse_why_not": self._prefix_reuse_why,
                # a composed family's steps: the chunk summaries they
                # wrote and the windows they closed (0 for the others)
                **{f"eva_{name}": n
                   for name, n in self._eva_counts.items()},
                "num_compiled_shapes": self.fns.num_compiled_shapes,
                # every step program of this family and configuration the
                # PROCESS has run, by shape key: its name in a trace, the
                # seconds its first call took (trace, compile or cache
                # read, launch) and the first calls taken so far
                "programs": self.fns.programs(),
                # the grouped expert product's form (ops/moe.py
                # ``gmm_form``) by step program this engine has run:
                # ``<kind>@<rows>[x<tokens a row>]``
                **({} if self._gmm_form is None else {"moe_gmm_form": {
                    f"{kind}@{'x'.join(map(str, dims))}":
                        self._gmm_form(math.prod(dims))
                    for kind, dims in sorted(
                        (kind, self._routed_dims(kind, shape))
                        for kind, shape, _ in self.fns.signatures)}}),
                "rejected_total": self._rejected_total,
                "cancelled_total": self._cancelled_total,
                "deadline_exceeded_total": self._deadline_total,
                "prefix_hit_tokens": hit,
                "prefix_hit_blocks": cs.prefix_hit_blocks,
                "prefix_cached_blocks": self.cache.cached_blocks,
                "prefix_evicted_blocks": cs.prefix_evicted_blocks,
                "host_cache_blocks": (
                    0 if self.cache.host_tier is None
                    else self.cache.host_tier.blocks
                ),
                "kv_demoted_blocks": cs.demoted_blocks,
                "kv_promoted_blocks": cs.promoted_blocks,
                "cow_blocks": cs.cow_copies,
                "prefill_tokens_total": computed,
                # the slots the prefill launches held for those tokens
                # (rows x row length of every launch, padding included:
                # tokens / slots is how full the prefill programs ran),
                # and the launches whose rows were pieces of prompts
                "prefill_slots": self._prefill_slots,
                "prefill_steps_packed": self._prefill_steps_packed,
                **self._prefill_pairs,
                "prefix_hit_rate": hit / max(1, hit + computed),
                "host_sync_seconds_total": round(
                    self._sync_seconds_total, 6
                ),
                "host_sync_bytes_total": self._sync_bytes_total,
                # step programs launched and not yet synced (0 when
                # drained), and the most there ever were: never above 2
                "decode_inflight": len(self._inflight),
                "steps_inflight_high_water": self._inflight_high_water,
                # decode dispatches; those launched behind a step in
                # flight with nothing synced first (the pipeline kept);
                # of those, the ones over another batch than that step's
                # (ids gathered on the device). Prefill dispatches, and
                # those synced behind a later launch. Host seconds by step
                # kind and phase, {kind: {phase: [count, seconds]}}
                "decode_steps": self._decode_steps,
                "decode_steps_steady": self._decode_steps_steady,
                "decode_steps_remapped": self._decode_steps_remapped,
                # where sliding layers are served: the decode dispatches'
                # rows, and those past the window (``rows_past_window``)
                "decode_rows": self._decode_rows,
                "decode_rows_past_window": self._decode_rows_past_window,
                "prefill_steps": self._prefill_steps,
                "prefill_syncs_deferred": self._prefill_syncs_deferred,
                # generation by diffusion over blocks (0 for a family that
                # yields a token a step): row-passes launched and those
                # that were commits; blocks committed, their tokens that
                # reached a stream, and those generated and cut
                **self._block_counts,
                "phases": {
                    kind: {name: list(rec) for name, rec in table.items()}
                    for kind, table in self._phases.items()
                },
                # what the stepping thread's time is made of besides:
                # the spans that are no step's phase ([count, seconds,
                # cpu_seconds]), the CPU seconds the thread itself ran of
                # each phase above (a Python-only phase's seconds less
                # these it stood off the CPU; read on one step in
                # ``obs.CPU_EVERY`` and taken for all), the process's
                # collector pauses by generation, and what
                # ``executor.stage`` moved
                "host": {
                    "spans": {name: rec[:3] for name, rec
                              in self._host_spans.items()},
                    "phase_cpu": {
                        kind: {name: obs.cpu_estimate(
                            self._phases[kind][name][1], cpu, measured)
                            for name, (cpu, measured) in table.items()}
                        for kind, table in self._phase_cpu.items()},
                    "gc": obs.gc_watch.totals(),
                    "stage_transfers": self.executor.stage_transfers,
                    "stage_bytes": self.executor.stage_bytes,
                    "stage_masks": self.executor.stage_masks,
                    "emit_rows": self._emit_rows,
                },
                "spec_steps": self._spec_steps,
                "spec_drafted_tokens": self._spec_drafted_total,
                "spec_accepted_tokens": self._spec_accepted_total,
                "spec_committed_tokens": self._spec_committed_total,
                "spec_accept_rate": (
                    self._spec_accepted_total
                    / max(1, self._spec_drafted_total)
                ),
                "spec_committed_per_step": (
                    self._spec_committed_total / max(1, self._spec_steps)
                ),
                "structured_running": sum(
                    1 for r in self._running if r.fsm is not None
                ),
                "grammar_cache": structured.cache_stats(),
                "goodput": {
                    k: dict(v) for k, v in self._goodput_last.items()
                },
                "executor": self.executor.describe(),
                "failed": self._failed is not None,
            }
        out.update(self.executor.read_counters(counters))
        return out

    @property
    def fns(self):
        """The executor's DecodeFns (compile-signature accounting) —
        kept as an engine attribute for tests/dashboards that predate
        the executor seam."""
        return self.executor.fns

    def program_scopes(self, only=None) -> dict:
        """Which part of a layer (``obs.SCOPES``) each instruction of each
        step program belongs to (``DecodeFns.program_scopes``): what turns
        a profiler trace of this replica into device time by part. Dear
        (every program is lowered again), lazy, and never called by the
        engine; it answers after ``shutdown()`` too."""
        return self.fns.program_scopes(only)

    @property
    def params(self):
        """Model weights, wherever the executor placed them (one device,
        or sharded over its mesh)."""
        return self.executor.params

    @property
    def num_compiled_shapes(self) -> int:
        return self.fns.num_compiled_shapes

    @property
    def failed(self) -> bool:
        return self._failed is not None

    def request_timeline(self, request_id) -> dict | None:
        """Phase timeline of one request (live or recently finished):
        ``{"request_id", "trace_id", "finish_reason", "events": [...]}``
        where each event is ``{"event", "ts"(wall seconds), ...}`` for
        submitted / admitted / prefill[_chunk] / first_token / token /
        terminal. Finished timelines are kept for the last
        ``timeline_history`` requests; returns None for unknown ids."""
        with self._lock:
            r = self._find_locked(request_id)
            if r is not None:
                return self._timeline_dict(r)
            return self._timelines.get(request_id)

    def autoscaling_snapshot(self) -> dict:
        """Saturation signals for the controller's autoscaling policy
        (serve/autoscaling_policy.py desired_from_signals): queue depth +
        queue-wait p95, KV-pool block accounting collapsed into a single
        pressure fraction, deadline-miss / rejection rates over a trailing
        window, and decode-step p50. All host-side integers/floats — O(1)
        plus a sort of two bounded sample windows — so the controller can
        pull it every reconcile period. Also refreshes the
        ``llm_queue_depth`` / ``llm_kv_free_blocks`` /
        ``llm_kv_pool_pressure`` gauges and records the snapshot in the
        flight ring (``kind="autoscale_snapshot"``)."""
        with self._lock:
            return self._autoscaling_snapshot_locked()

    def _autoscaling_snapshot_locked(self, record: bool = True) -> dict:
        now = obs.clock()
        cache = self.cache
        usable = max(1, cache.cfg.usable_blocks)
        snap = cache.debug_snapshot()
        # Pressure = the fraction of the usable pool a NEW admission
        # cannot claim (see _kv_pressure_locked — the preemption trigger
        # reads the identical number).
        pressure = self._kv_pressure_locked()
        # Two-tier pressure: a pressured device pool backed by a warm
        # host tier is cheaper to miss into than one without (misses
        # promote instead of recomputing), so the host-resident block
        # count discounts the device pressure, bounded at zero. With the
        # tier disabled this equals kv_pool_pressure exactly.
        pressure_two_tier = max(0.0, pressure - snap["host_blocks"] / usable)
        out = {
            "ts_wall": obs.wall(),
            "clock": now,
            "queue_depth": len(self._waiting),
            "queue_wait_p95_s": round(
                _pctile(self._queue_wait_window, 0.95), 6
            ),
            "decode_step_p50_s": round(
                _pctile(self._decode_step_window, 0.50), 6
            ),
            "kv_free_blocks": snap["free_blocks"],
            "kv_cached_blocks": snap["cached_blocks"],
            "kv_quarantined_blocks": snap["quarantined_blocks"],
            "kv_pool_pressure": round(pressure, 4),
            "kv_host_cached_blocks": snap["host_blocks"],
            "kv_host_cache_bytes": snap["host_bytes"],
            "kv_pressure_two_tier": round(pressure_two_tier, 4),
            # Prefix-routing piggyback: the bounded digest summary rides
            # the snapshot the controller already polls, plus the two
            # constants the router needs to hash raw prompts into the
            # same chain-digest space (encode_text is ``byte % vocab``).
            "prefix_digests": cache.prefix_digest_summary(),
            "block_size": cache.cfg.block_size,
            "vocab_size": self.model_cfg.vocab_size,
            "deadline_miss_rate": round(
                _window_rate(self._deadline_clocks, now), 4
            ),
            "rejection_rate": round(
                _window_rate(self._reject_clocks, now), 4
            ),
            "running": len(self._running),
            "prefilling": len(self._prefilling),
            # per-class queue depth + preemption saturation: the
            # controller's class-aware shed policy
            # (autoscaling_policy.shed_classes) degrades batch traffic
            # first, and only once preemption itself is exhausted
            "queue_depth_by_class": {
                p: sum(
                    1 for r in self._waiting if r.sampling.priority == p
                )
                for p in _PRIORITIES
            },
            "preempted_streams": len(self._preempted),
            "preempt_exhausted": self._preempt_exhausted,
            "failed": self._failed is not None,
        }
        self._m_as_queue.set(out["queue_depth"])
        self._m_as_kv_free.set(out["kv_free_blocks"])
        self._m_as_kv_pressure.set(out["kv_pool_pressure"])
        self._last_snapshot = out
        if record:  # debug_dump() observes without touching the ring
            self._flight.record(dict(out, kind="autoscale_snapshot",
                                     ts=out["ts_wall"]))
        return out

    def debug_dump(self) -> dict:
        """One-call post-mortem/state dump: flight-recorder ring, engine
        stats, cache snapshot, the latest autoscaling snapshot, compiled
        shapes, and the process's event_stats. Exposed replica-side as
        ``LLMDeployment.debug_dump`` and proxy-side as
        ``GET /debug/llm``."""
        with self._lock:
            return self._flight.dump("debug", extra={
                "stats": self.stats(),
                "executor": self.executor.describe(),
                "cache": self.cache.debug_snapshot(),
                "autoscaling_snapshot": self._autoscaling_snapshot_locked(
                    record=False),
                "compiled_shapes": sorted(
                    obs.shape_key(s) for s in self.fns.signatures
                ),
                "archived_timelines": len(self._timelines),
                # traced requests currently live in the engine, so an
                # operator staring at a wedged dump can jump straight to
                # the matching fleet traces (/api/traces/<id>)
                "live_trace_ids": self._trace_ids_locked(
                    list(self._waiting) + self._prefilling
                    + self._running + self._preempted),
            })

    def shutdown(self, dump: bool | str | None = None) -> None:
        """Stop stepping, fail every pending stream with a clear error,
        and return ALL KV blocks (allocations, reservations, and the
        prefix cache) to the pool — repeated create/shutdown in one
        process is leak-free.

        ``dump=True`` writes a flight-recorder JSON dump to the configured
        dump dir on the way out; a string is an explicit file path."""
        with self._lock:
            if self._stopped:
                return
            if dump:
                self._dump("shutdown",
                           path=dump if isinstance(dump, str) else None)
            self._stopped = True
            err = RequestCancelledError("engine shut down")
            for r in (list(self._waiting) + self._prefilling
                      + self._running + self._preempted):
                if not r.done:
                    r.done = True
                    self._finish_obs_locked(r, "shutdown")
                    r.out.put(err)
                    r.out.put(_DONE)
            self._inflight.clear()
            self.cache.release_all()
            self._waiting.clear()
            self._waiting_blocks = 0
            self._prefilling.clear()
            self._running.clear()
            self._preempted.clear()
            self._m_preempted_streams.set(0)
            self._m_queue.set(0)
            self._m_util.set(self.cache.utilization)
            self._work.notify_all()
        obs.gc_watch.release()
        for t in (self._thread, self._watchdog):
            if t is not None:
                t.join(timeout=5)
        self._thread = None
        self._watchdog = None

    # ---------------- scheduler internals (lock held) ----------------

    def _find_locked(self, request_id) -> _Request | None:
        for r in self._running:
            if r.id == request_id:
                return r
        for r in self._prefilling:
            if r.id == request_id:
                return r
        for r in self._waiting:
            if r.id == request_id:
                return r
        for r in self._preempted:
            if r.id == request_id:
                return r
        return None

    def _release_blocks_locked(self, r: _Request) -> None:
        """Return an admitted request's blocks (allocation + leftover
        reservation) to the pool EXACTLY ONCE, respecting the dispatch
        lag: while the row still has an in-flight step (``inflight > 0``)
        release is deferred to the reconcile that retires it, and blocks
        freed while any other dispatch is in flight are quarantined until
        the sync of the NEWEST of them proves every one executed
        (kv_cache.free/flush_quarantine: the fence)."""
        if r.blocks_released or r.inflight > 0:
            return
        r.blocks_released = True
        leftover = r.reserved_blocks - r.drawn_blocks
        self.cache.free(r.id, quarantine=bool(self._inflight),
                        fence=self._launched)
        if leftover:
            # below zero for a row cut off inside a prefill step, whose
            # chunk drew on the engine's prefill room: the room gets it back
            self.cache.release_reservation(leftover)
        self._work.notify_all()  # freed blocks may unblock admissions

    def _evict_locked(self, r: _Request) -> None:
        """Remove a live request from the scheduler and return its blocks
        (allocation + leftover reservation for admitted; queued worst-case
        budget for waiting). Does NOT touch the output stream."""
        if r in self._running or r in self._prefilling:
            if r in self._running:
                self._running.remove(r)
            else:
                self._prefilling.remove(r)
            r.done = True  # before release: an inflight row defers it
            self._release_blocks_locked(r)
        elif r in self._preempted:
            # parked streams hold ZERO blocks (released at preemption) —
            # unparking is the whole eviction; the demoted chain stays
            # behind as an ordinary cache entry
            self._preempted.remove(r)
            self._m_preempted_streams.set(len(self._preempted))
        else:
            try:
                self._waiting.remove(r)
            except ValueError:  # pragma: no cover — already gone
                pass
            else:
                self._waiting_blocks -= self.cache.cfg.request_blocks(
                    len(r.prompt) + r.sampling.max_new_tokens
                )
        r.done = True
        self._m_queue.set(len(self._waiting))
        self._m_util.set(self.cache.utilization)
        self._work.notify_all()  # freed blocks may unblock admissions

    def _expire_deadlines_locked(self) -> int:
        now = time.monotonic()
        expired = 0
        for r in [
            r
            for r in list(self._waiting) + self._prefilling + self._running
            + self._preempted
            if r.deadline is not None and now >= r.deadline
        ]:
            self._evict_locked(r)
            self._deadline_total += 1
            self._m_deadline.inc()
            self._deadline_clocks.append(obs.clock())
            expired += 1
            self._finish_obs_locked(r, "expired")
            r.out.put(
                DeadlineExceededError(
                    f"request {r.id!r} deadline "
                    f"({r.sampling.deadline_s}s) expired after "
                    f"{len(r.generated)} tokens"
                )
            )
            r.out.put(_DONE)
        return expired

    # ---------------- priority preemption (ISSUE 17) ----------------

    def _kv_pressure_locked(self) -> float:
        """Fraction of the usable KV pool a new admission cannot claim:
        live allocations, reservations, and quarantined blocks count
        against it; LRU-cached prefix blocks do not (evictable on
        demand). The same definition ``autoscaling_snapshot`` exports as
        ``kv_pool_pressure`` — preemption triggers and the autoscaler
        read one number."""
        cache = self.cache
        usable = max(1, cache.cfg.usable_blocks)
        claimable = max(0, cache.available_blocks - cache.reserved_blocks)
        return min(1.0, max(0.0, 1.0 - claimable / usable))

    def _rank_locked(self, r: _Request, now: float) -> int:
        """Effective priority rank of a request at ``now`` (obs.clock):
        the class rank (batch < default < interactive), boosted ABOVE
        interactive once the request has waited or sat parked past the
        starvation-aging floor. The boost is double-duty: an aged waiter
        outranks every class for admission ordering, and an aged (or
        once-parked-long-enough) running stream stops being preemptible —
        together they guarantee batch traffic always finishes."""
        pc = self._preemption
        rank = PRIORITY_RANK[r.sampling.priority]
        ref = (r.preempted_clock if r.preempted_clock is not None
               else r.submitted_clock)
        if pc is not None and ref is not None and now - ref >= pc.aging_s:
            rank = len(_PRIORITIES)  # aged past every class
        return rank

    def _maybe_preempt_locked(self) -> None:
        """Pause the lowest-priority RUNNING stream when KV-pool pressure
        or a higher-priority waiter's queue age crosses the
        PreemptionConfig thresholds. ONE victim per step: a preemption
        frees a whole chain at once, and admission runs right after in
        the same iteration, so pausing more per step would overshoot
        before the freed headroom is even observed. While pressure holds
        but no victim outranked by a waiter remains (or the parked set is
        at its cap), ``_preempt_exhausted`` latches True — the signal
        per-class shedding (autoscaling_policy.shed_classes) keys on."""
        pc = self._preemption
        if not self._waiting:
            self._preempt_exhausted = False
            return
        now = obs.clock()
        waiter = max(
            self._waiting, key=lambda rq: self._rank_locked(rq, now)
        )
        w_rank = self._rank_locked(waiter, now)
        pressured = (
            self._kv_pressure_locked() >= pc.kv_pressure
            or now - waiter.submitted_clock >= pc.queue_wait_s
        )
        if not pressured:
            self._preempt_exhausted = False
            return
        victims = [
            r for r in self._running
            if self._rank_locked(r, now) < w_rank
        ]
        if not victims or len(self._preempted) >= pc.max_preempted:
            self._preempt_exhausted = True
            return
        self._preempt_exhausted = False
        # lowest class first; within a class the YOUNGEST stream pauses
        # (oldest streams are closest to completion — finishing them
        # releases their blocks for good)
        victim = min(
            victims,
            key=lambda r: (self._rank_locked(r, now),
                           -(r.submitted_clock or 0.0)),
        )
        self._preempt_one_locked(victim, now)

    def _preempt_one_locked(self, r: _Request, now: float) -> bool:
        """Pause one running stream: collapse the dispatch lag so nothing
        in flight references its rows, content-address its resident
        blocks in the prefix cache and demote them into the host tier
        (insurance against device LRU eviction while parked), release
        its allocation + leftover reservation exactly once, and park it
        in the ``preempted`` state with cursor/timeline/FSM intact. On
        resume the chain re-prefills — prefix hits serve the registered
        blocks from the device LRU or promote them back through the
        batched ``land_blocks`` scatter — and keyed (seed, position)
        sampling reproduces the remaining tokens byte-identically."""
        chaos.fire("llm.preempt", request=r.id,
                   priority=r.sampling.priority)
        # the victim (or a neighbor) may be in a dispatched step:
        # reconcile first so its inflight count is 0 and the free
        # below needs no quarantine. The victim may COMPLETE here —
        # its lagged token was its last — in which case there is
        # nothing left to pause.
        self._collapse_locked()
        if r.done or r not in self._running:
            return False
        chain = list(r.prompt) + list(r.generated)
        # resident KV covers [0, total_len - 1): the last emitted
        # token's K/V lands only when it is fed as the next decode input
        resident = r.total_len - 1
        if self.cfg.prefix_caching:
            self.cache.register_prefix(r.id, chain, resident)
        demoted = self.cache.demote_chain(chain, resident,
                                          trace_ctx=r.trace_ctx)
        self._running.remove(r)
        self._release_blocks_locked(r)
        # back to the pre-admission shape (the resume is a plain
        # re-admission of prompt + generated via pending_resume)
        r.blocks_released = False
        r.reserved_blocks = 0
        r.drawn_blocks = 0
        r.prefill_done = 0
        r.cached_tokens = 0
        r.started = False
        r.skips = 0
        r.table_np = None
        r.table_key = None
        r.pending_resume = chain
        r.preempted_clock = now
        r.preempt_count += 1
        self._preempted.append(r)
        self._preempted_total += 1
        self._m_preemptions.inc()
        self._m_preempted_streams.set(len(self._preempted))
        self._m_util.set(self.cache.utilization)
        self._tl(r, "preempted", generated=len(r.generated),
                 priority=r.sampling.priority, demoted_blocks=demoted)
        return True

    def _maybe_resume_locked(self) -> None:
        """Re-admit parked streams once pressure clears below
        ``resume_pressure`` — or unconditionally once a stream's aging
        floor trips (the starvation guarantee). Highest effective rank
        first, oldest park first within a class; stops at the first
        candidate that doesn't fit so resumes stay ordered. A resume is
        a normal re-admission of the full token chain; the final prefill
        chunk re-samples the next token at its true absolute position,
        so the joined stream is byte-identical to an unpaused run."""
        pc = self._preemption
        if not self._preempted:
            return
        now = obs.clock()
        while self._preempted:
            cand = max(
                self._preempted,
                key=lambda r: (self._rank_locked(r, now),
                               -(r.preempted_clock or 0.0)),
            )
            aged = now - cand.preempted_clock >= pc.aging_s
            if not aged and self._kv_pressure_locked() > pc.resume_pressure:
                break
            if (len(self._running) + len(self._prefilling)
                    >= self.cfg.max_batch_size):
                break
            if not self._try_admit_one_locked(cand):
                break
            self._preempted.remove(cand)
            self._prefilling.append(cand)
            parked = now - cand.preempted_clock
            self._m_preempted_wait.observe(parked)
            self._m_preempted_streams.set(len(self._preempted))
            chaos.fire("llm.resume_preempted", request=cand.id,
                       parked_s=parked)
            self._tl(cand, "resumed",
                     parked_ms=round(parked * 1000.0, 3),
                     cached_tokens=cand.cached_tokens)
            # preempted_clock deliberately stays set: the resumed stream
            # keeps aging from its park time, so a stream that has
            # already been paused once soon becomes non-preemptible
            # (anti-thrash) via the _rank_locked boost

    def _try_admit_one_locked(self, req: _Request) -> bool:
        """Reserve worst-case blocks for one request, allocate its table,
        and map its resident prompt prefix. Returns False (no state
        change) when the reservation doesn't fit right now.

        Reservation sizing: ``blocks_for(prompt + max_new_tokens)``, plus
        ONE extra block when the ENTIRE prompt is resident — the last
        prompt token must still be recomputed to produce first-token
        logits, and that write lands in a shared hashed block, so it
        always triggers exactly one copy-on-write copy."""
        bs = self.cfg.block_size
        if self._state_rows and not self.cache.free_slots:
            # every state slot is held (a cancelled row's goes back only
            # when its in-flight step has been reconciled)
            return False
        # Resumed-from-preemption rows prefill prompt + generated-so-far,
        # but the worst case is unchanged: len(toks) + tokens-still-to-
        # generate == len(prompt) + max_new_tokens, always.
        toks = req.prefill_tokens
        total = len(toks) + (req.sampling.max_new_tokens - len(req.generated))
        need = self.cache.cfg.request_blocks(total)
        max_hit_blocks = None
        if self.cfg.prefix_caching:
            hit_blocks = self.cache.peek_prefix(toks)
            if hit_blocks * bs >= len(toks):  # full-chain hit
                if (
                    need + 1 <= self.cache.cfg.usable_blocks
                    and self.cache.can_reserve(need + 1)
                ):
                    need += 1
                    max_hit_blocks = hit_blocks
                elif self.cache.can_reserve(need):
                    # no headroom for the COW copy: drop the last hit
                    # block and recompute it instead
                    max_hit_blocks = hit_blocks - 1
                else:
                    return False
            else:
                if not self.cache.can_reserve(need):
                    return False
                max_hit_blocks = hit_blocks
        elif not self.cache.can_reserve(need):
            return False
        self.cache.reserve(need)
        req.reserved_blocks = need
        self.cache.allocate(req.id)
        if self.cfg.prefix_caching:
            promoted0 = self.cache.stats.promoted_blocks
            hit_tokens = self.cache.assign_prefix(
                req.id, toks, max_blocks=max_hit_blocks
            )
            req.drawn_blocks += hit_tokens // bs
            # a full-chain hit still recomputes the LAST token (a 1-token
            # chunk) so the engine has logits to sample from (a block
            # family's prefill chooses no token: nothing is recomputed)
            req.prefill_done = min(
                hit_tokens, len(toks) - (0 if self._block_len else 1))
            req.cached_tokens = req.prefill_done
            if req.trace_ctx:
                # host->device promotions staged for THIS admission show
                # up on the request's trace (span rendered at finish)
                promoted = self.cache.stats.promoted_blocks - promoted0
                if promoted:
                    self._tl(req, "kv_promote", blocks=promoted,
                             hit_tokens=hit_tokens)
        return True

    def _admit_locked(self) -> int:
        """Move waiting requests into the prefilling set. FIFO first; when
        the head's reservation doesn't fit, probe up to
        ``admission_probe`` requests behind it — unless the head has
        already been skipped ``admission_max_skips`` times, in which case
        admission stalls until the head fits (no starvation). With
        preemption enabled, candidates are ordered by effective priority
        rank first (stable sort — FIFO within a class, and the starvation-
        aging boost floats a starved request above interactive). Returns
        the number admitted this step."""
        admitted = 0
        if not self._waiting:
            return 0
        if self._preemption is not None and len(self._waiting) > 1:
            now = obs.clock()
            order = sorted(
                self._waiting, key=lambda rq: -self._rank_locked(rq, now)
            )
        else:
            order = list(self._waiting)
        head = order[0]
        probe_budget = (
            self.cfg.admission_probe
            if head.skips < self.cfg.admission_max_skips
            else 0
        )
        probed = 0
        idx = 0
        while (
            idx < len(order)
            and len(self._running) + len(self._prefilling)
            < self.cfg.max_batch_size
            and admitted < self.cfg.max_prefill_batch
        ):
            req = order[idx]
            if self._try_admit_one_locked(req):
                self._waiting.remove(req)
                self._waiting_blocks -= self.cache.cfg.request_blocks(
                    len(req.prompt) + req.sampling.max_new_tokens
                )
                if req.prefill_done < self._prefill_end(req):
                    self._prefilling.append(req)
                else:
                    # a block family's prompt of less than one block, or
                    # one whose whole blocks are all resident: nothing to
                    # prefill, its first block is next
                    self._running.append(req)
                admitted += 1
                idx += 1
                wait = obs.clock() - req.submitted_clock
                self._m_queue_wait.observe(wait)
                self._queue_wait_window.append(wait)
                self._tl(req, "admitted",
                         cached_tokens=req.cached_tokens,
                         reserved_blocks=req.reserved_blocks)
            else:
                if probed >= probe_budget:
                    break
                probed += 1
                idx += 1
        if admitted:
            if head in self._waiting:
                head.skips += 1  # someone was admitted past the head
            self._m_queue.set(len(self._waiting))
        return admitted

    def _prefill_end(self, r: _Request) -> int:
        """How many of the row's tokens prefill makes resident: all of
        them, or for a family that generates by blocks the prompt's WHOLE
        blocks (the partial last one belongs to the first generated
        block)."""
        n = len(r.prefill_tokens)
        return n - n % self._block_len if self._block_len else n

    def _table_for(self, r: _Request, nb: int, pos: int = 0) -> np.ndarray:
        """Host block table for one request, rebuilt only when a block was
        appended/replaced (version bump), the padded width changed or, for
        a composed table, the step's first query at ``pos`` lies in
        another window than the last one's."""
        key = (nb, self.cache.table_version(r.id),
               self.cache.table_epoch(pos))
        if r.table_key != key:
            r.table_np = self.cache.block_table(r.id, nb, pos)
            r.table_key = key
        return r.table_np

    def _table_blocks(self, ctx: int) -> int:
        """The width of a step's tables for contexts in the bucket ``ctx``
        (a composed table's: ``_composed_nb``)."""
        return self._composed_nb or ctx // self.cfg.block_size

    def _apply_copies_locked(self, pairs: list[tuple[int, int]]) -> None:
        """Clone shared blocks on device (COW) before a write lands —
        pow2 pair-list padding and the fused on-device copy live in the
        executor (executor.copy_blocks)."""
        if not pairs:
            return
        self.executor.copy_blocks(pairs)

    def _apply_promotions_locked(self) -> None:
        """Land host-tier promotions staged by admission as ONE fused
        ``land_blocks`` scatter (the handoff-landing path — host->device
        only, no new sync point, no new compile kind). Must run at the
        TOP of a dispatch window, before ``prepare_write``/
        ``_apply_copies_locked``: a COW fork of a promoted block must
        clone landed content, and a capacity eviction in the same window
        must see the landing acked before it may demote-export."""
        staged = self.cache.take_pending_promotions()
        if not staged:
            return
        chaos.fire("llm.kv.promote", blocks=len(staged))
        from ray_tpu.ops.quantization import stack_blocks

        ids = [b for b, _, _ in staged]
        self.executor.land_blocks(
            ids,
            stack_blocks([k for _, k, _ in staged], axis=1),
            stack_blocks([v for _, _, v in staged], axis=1),
        )
        self.cache.promotions_landed(ids)

    def _prefill_chunk_locked(self) -> None:
        """Run ONE prefill call for up to ``max_prefill_batch`` admitted
        requests: each contributes its next chunk (the whole uncached
        suffix when ``prefill_chunk_tokens`` is None).

        A step is filled by TOKENS wherever a sequence may be split over
        its rows (``_piece``: K/V pages under one table are all it
        carries, kv_cache.py ``one_table``): the chunks are cut into
        pieces of the kernel's q tile, a row each, ``lengths[i]`` its real
        tokens (only a chunk's last piece is short), ``starts[i]`` its
        true first position, ``tables[i]`` its sequence's table. Row i + 1
        of a prompt sees row i's keys because a layer scatters the step's
        K/V into the pool BEFORE its kernel reads the pool through the
        table, masked by true position: the chunk program as it is
        (``prefill_chunk``), cold prompt or not, so a first prefill and a
        re-prefill go the same way. The rows are padded to the engine's
        own ladder (``_piece_rows``); a chunk that does not fit what is
        left of the step goes on in the next one at its ``prefill_done``.
        A request's first token is the id at its LAST piece's row
        (``_InFlight.ids_at``).

        Packed so: K and V by head (``llama``, ``gpt``; plain or
        quantized) and a pool in planes (``pangu_ultra_moe``,
        ``longcat_flash``: a layer writes the step's latent rows to the
        pool before its kernel reads them back through the table, and
        their ``state`` is counters, no row a sequence: ``slots`` then
        says BY ROW which rows are real, ``_slots_buf_locked``).

        Every other layout keeps a row a request, padded to the longest
        row's bucket, for the reason ``KVCacheConfig.why_not_split``
        gives: state rows beside the pool (``lfm2_moe``: a piece's short
        convolution needs the piece before it, inside the same step;
        ``minicpm_sala``: a piece's lightning state likewise),
        tables by group (``laguna``, ``smallthinker``: freeing behind a
        window), a ring and a slot table composed by position
        (``evabyte``). There a cold whole prompt takes the program without
        ``start`` (kind ``prefill``), anything mid-prompt the chunk
        program at true positions."""
        P = self._piece
        if P and not self._prefill_steps:
            self._warm_pieces_locked()
        cap = self.cfg.prefill_chunk_tokens
        batch, ns = [], []
        room = self._piece_rows[-1] if P else 0  # rows left in the step
        for r in self._prefilling[: self.cfg.max_prefill_batch]:
            remaining = self._prefill_end(r) - r.prefill_done
            n = remaining if cap is None else min(remaining, cap)
            if P:
                n = min(n, room * P)
                if not n:
                    break  # the step is full: this one waits for the next
                room -= -(-n // P)
            batch.append(r)
            ns.append(n)
        chaos.fire("engine.prefill", batch=len(batch))
        self._step_kind = "prefill"
        t0 = obs.clock()
        t0_wall = obs.wall()
        bs = self.cfg.block_size
        with self._phase("kv.reserve"):
            # staged host-tier promotions land before capacity/COW work so
            # a same-window eviction or fork of a promoted block is safe
            self._apply_promotions_locked()
            pairs: list[tuple[int, int]] = []
            for r, n in zip(batch, ns):
                r.started = True
                r.drawn_blocks -= self.cache.free_behind(
                    r.id, r.prefill_done)
                appended = self.cache.ensure_capacity(
                    r.id, r.prefill_done + n
                )
                r.drawn_blocks += appended
                cow = self.cache.prepare_write(
                    r.id, r.prefill_done, r.prefill_done + n
                )
                r.drawn_blocks += len(cow)
                pairs.extend(cow)
            self._apply_copies_locked(pairs)

        with self._phase("engine.batch"):
            legacy = not P and all(
                r.prefill_done == 0 and n == len(r.prefill_tokens)
                for r, n in zip(batch, ns)
            )
            kind = self._step_kind = "prefill" if legacy else "prefill_chunk"
            # rows first[k] to first[k + 1] are request k's: its one row,
            # or its chunk's pieces, its id at the last of them
            first = list(itertools.accumulate(
                (-(-n // P) if P else 1 for n in ns), initial=0))
            used, ids_at = first[-1], None
            if P:
                ids_at = [j - 1 for j in first[1:]]
                S, nb = P, self._piece_nb
                B = pad_to_bucket(used, self._piece_rows)
            else:
                S = pad_to_bucket(max(ns), self._length_buckets)
                B = pad_to_bucket(len(batch), self._batch_buckets)
                if legacy:
                    nb = S // bs
                else:
                    ctx = pad_to_bucket(
                        max(r.prefill_done + n for r, n in zip(batch, ns)),
                        self._length_buckets,
                    )
                    nb = self._table_blocks(ctx)
            tokens = self._scratch_buf("pf_tokens", (B, S), np.int32)
            lengths = self._scratch_buf("pf_lengths", (B,), np.int32)
            starts = self._scratch_buf("pf_starts", (B,), np.int32)
            tables = self._tables_buf("pf_tables", B, nb)
            slots = self._slots_buf_locked("pf_slots", batch, B, first)
            # reused buffers: stale padding rows/columns must be re-zeroed
            # (a stale table row could point at blocks now owned by a LIVE
            # sequence — padding writes must stay on the garbage block)
            tokens[used:] = 0
            lengths[:] = 1  # padding rows: length 1
            starts[used:] = 0
            tables[..., used:, :] = 0
            flat = tokens.reshape(-1)
            for k, (r, n) in enumerate(zip(batch, ns)):
                toks = r.prefill_tokens
                done = r.prefill_done
                # the chunk lies in its rows end to end: every piece but
                # the last is full
                i, j = first[k], first[k + 1]
                flat[i * S : i * S + n] = toks[done : done + n]
                flat[i * S + n : j * S] = 0
                lengths[i:j] = S
                lengths[j - 1] = n - (j - 1 - i) * S
                starts[i:j] = range(done, done + n, S)
                tables[..., i:j, :] = self._table_for(r, nb, done)[
                    ..., None, :]
            sample = self._sample_args_locked(batch, B, rows=ids_at)
        # the (query, key) pairs the step's attention covers: each real
        # query token at position p attends p + 1 positions
        span = {"kind": kind, "seq": self._launched + 1,
                "qk_pairs": sum(n * r.prefill_done + n * (n + 1) // 2
                                for r, n in zip(batch, ns))}
        if self._step_attrs is not None:
            span.update(self._step_attrs(
                self.model_cfg, "prefill",
                [(r.prefill_done, n) for r, n in zip(batch, ns)]))
        if self._kv_ring:
            W, C = self._kv_ring
            # what the step's summarise call is handed a layer: every
            # row's chunks, padding among them
            span["eva_chunks"] = B * (S // C)
            for r, n in zip(batch, ns):
                self._eva_counts["chunks_written_prefill"] += n // C
                self._eva_counts["windows_closed_prefill"] += (
                    (r.prefill_done + n) % W == 0)
        if legacy:
            toks_dev = self.executor.prefill(
                tokens, lengths, tables, sample=sample, span=span,
                slots=slots,
            )
        else:
            toks_dev = self.executor.prefill_chunk(
                tokens, lengths, starts, tables, sample=sample, span=span,
                slots=slots, ids_width=(
                    self._ids_width(B) if P and not self._block_len
                    else None),
            )
        self._prefill_slots += B * S
        self._prefill_steps_packed += bool(P)
        for name in self._prefill_pairs:
            self._prefill_pairs[name] += span.get(name, 0)
        self._prefill_steps += 1
        # The host's view moves on AT THE LAUNCH: the chunk is as good as
        # written (whatever touches these blocks next is a later program
        # on the one device), the rows of a FINAL chunk join the running
        # set with their first token in flight (``inflight`` 1, which the
        # budget rule and ``kv.reserve`` of the next decode step count),
        # and the sync waits behind the next launch (``_reconcile_locked``
        # books what needs the ids: the first tokens, ``register_prefix``,
        # the timeline entry and the flight record).
        rows = []
        for r, n in zip(batch, ns):
            toks = r.prefill_tokens
            r.prefill_done += n
            r.inflight += 1
            self._prefill_tokens_total += n
            final = r.prefill_done >= self._prefill_end(r)
            rows.append((n, toks, r.prefill_done, final))
            if final:
                self._prefilling.remove(r)
                # resume-from-preemption chains are fully resident
                # again: from here the row decodes exactly like an
                # unpaused one
                r.pending_resume = None
                self._running.append(r)
        if self._kv_room:
            # the chunk is written: what it put behind the window goes
            # back now, so that a row holds no more than it reserved
            # between steps and the prefill room is whole for the next.
            # Before the sync, as a decode step frees behind a step in
            # flight: ``free_behind`` rests on the device's order
            with self._phase("kv.reserve"):
                for r in batch:
                    r.drawn_blocks -= self.cache.free_behind(
                        r.id, r.prefill_done)
        rec = self._launched_locked(_InFlight(
            kind=kind, tokens=toks_dev, batch=batch, rows=rows,
            ids_at=ids_at, t0=t0, t0_wall=t0_wall, fields=dict(
                batch=len(batch), bucket_b=B, bucket_len=S, nb=nb,
                tokens=int(sum(ns)), admitted=self._step_admitted,
                expired=self._step_expired,
                trace_ids=self._trace_ids_locked(batch),
                **({"pieces": used} if P else {}))))
        # what was in flight before it: its run has ended, or ends while
        # this one runs
        self._reconcile_older_locked(rec)
        # Where another launch will follow (a chunk still to prefill, a
        # row that can decode) the sync waits behind it; else it is made
        # at once, as the first token must not wait for a step that may
        # never come
        if not self._prefilling and not self._eligible_locked():
            self._reconcile_locked(rec)

    def _warm_pieces_locked(self) -> None:
        """Run every row count of the packed ladder once, over padding
        rows alone (length 1 at position 0 under an all-zero table: block
        0 is the garbage sink), before this engine's first prefill step:
        the ladder is the engine's own, and which of its rungs a warm-up's
        prompts reach is an accident of their lengths (prompts just under
        a bucket's top are 4, 8, 16 pieces, never 1-3). So the programs,
        and ``executor._warm_feed``'s id gathers of their widths, exist
        before traffic whatever came first; programs are process-wide, so
        a second engine over the same model finds them made. These are no
        steps of the scheduler (``executor.warm_prefill_chunk``); the
        watchdog's clock restarts a launch."""
        for rows in self._piece_rows:
            self.executor.warm_prefill_chunk(
                np.zeros((rows, self._piece), np.int32),
                np.ones((rows,), np.int32), np.zeros((rows,), np.int32),
                np.zeros((rows, self._piece_nb), np.int32),
                self._sample_args_locked([], rows),
                None if self._block_len else self._ids_width(rows),
                # slot 0: padding, counted nowhere. An array of its own,
                # as the others: nothing syncs these launches, so a
                # staging buffer could be rewritten under one (CPU)
                np.zeros((rows,), np.int32) if self._stateful else None)
            self._step_begin = obs.clock()

    def _ids_width(self, rows: int) -> int | None:
        """The width a packed step of ``rows`` pieces hands its ids on at:
        the decode row bucket that holds them, so that the gather of the
        decode step behind it (a program a pair of widths) is one that
        decode steps need of each other anyway; None (as they are) where
        no bucket holds them or one fits exactly."""
        width = pad_to_bucket(rows, self._batch_buckets)
        return width if width > rows else None

    def _eligible_locked(self) -> list[_Request]:
        """The rows a decode step may be launched over. The budget counts
        the tokens in flight too: a row at ``max_new_tokens - 1`` with one
        in flight must not be dispatched again (its last token arrives at
        a reconcile), and a row a prefill step just launched holds its
        first."""
        return [
            r for r in self._running
            if len(r.generated) + r.inflight < r.sampling.max_new_tokens
        ]

    def _launched_locked(self, rec: _InFlight) -> _InFlight:
        """Book a step program that was just launched: number it, and hold
        it until its ids are synced. At most TWO are ever in flight: the
        staging buffers alternate in pairs (``_scratch_buf``), a freed
        block waits for the sync of the newer (``_release_blocks_locked``),
        and a reader of the profiler's trace pairs launches with runs a
        shift of at most two apart."""
        assert len(self._inflight) < 2, "a third step program in flight"
        self._launched += 1
        rec.seq = self._launched
        self._inflight.append(rec)
        self._inflight_high_water = max(
            self._inflight_high_water, len(self._inflight))
        return rec

    def _reconcile_older_locked(self, rec: _InFlight) -> int:
        """Reconcile what was launched before ``rec``, oldest first, now
        that ``rec`` is queued behind it: the host's work for those ids
        runs while the device runs ``rec``. -> tokens emitted."""
        emitted = 0
        while self._inflight and self._inflight[0] is not rec:
            emitted += self._reconcile_locked(self._inflight[0])
        return emitted

    def _collapse_locked(self) -> int:
        """Reconcile EVERYTHING in flight, oldest first: what a step does
        that needs its rows' ids on the host before it can be launched (a
        grammar-constrained row, a verify step), preemption, the handoff
        export and the drain. -> tokens emitted."""
        emitted = 0
        while self._inflight:
            emitted += self._reconcile_locked(self._inflight[0])
        return emitted

    def _decode_locked(self) -> None:
        """One pipelined decode iteration (the dispatch-ahead loop). With a
        step program in flight — the last decode step, or a prefill step
        whose final rows have just joined — step N+1 is launched BEFORE
        that step's ids are synced: each row's input id is either on the
        host already or in the in-flight step's on-device id array, at an
        index the engine knows, so the step's ids are put together on the
        device (the same rows in the same order: that array itself; rows
        joined or left: one gather, ``executor.feed_ids``). Only THEN is
        the older step reconciled: all the host work above the dispatch
        (bucketing, COW prep, table/position packing) and the emission of
        the older step's tokens overlap device compute, and the sync is
        near-free because that step already finished. What still
        collapses the lag first, on purpose: a grammar-constrained row
        (its allow-mask needs the last id on the host), a verify step
        (drafts are made from committed tokens) and the drain."""
        chaos.fire("engine.decode", batch=len(self._running))
        self._step_kind = "decode"
        t0 = obs.clock()
        t0_wall = obs.wall()
        bs = self.cfg.block_size
        eligible = self._eligible_locked

        with self._phase("engine.batch"):
            batch = eligible()
            # speculative draft-and-verify (cfg.speculative_k > 0) needs
            # the rows' COMMITTED tokens on host, so a verify step can
            # never be dispatched ahead: when any row has drafts, collapse
            # the lag first, re-draft on the reconciled state,
            # and run ONE synchronous verify step committing 1..k+1 tokens
            # per row. When no row drafts anything, fall through to the
            # plain pipelined decode below — drafter-hostile traffic keeps
            # the dispatch-ahead path untouched.
            proposals = (
                self._propose_drafts_locked(batch)
                if self._drafter is not None and batch else None
            )
        emitted = 0
        if proposals is not None:
            if self._inflight:
                emitted += self._collapse_locked()
                with self._phase("engine.batch"):
                    batch = eligible()
                    proposals = (
                        self._propose_drafts_locked(batch) if batch else None
                    )
            if batch and proposals is not None:
                self._verify_locked(batch, proposals, t0, t0_wall, emitted)
                return
        # Grammar-constrained rows force the lag to collapse every step:
        # the allow-mask staged for step N+1 is a function of the FSM
        # state AFTER step N's token, which only exists host-side once
        # N's ids are synced — so reconcile first, then dispatch (lag-0
        # for constrained batches, the dispatch-ahead win preserved for
        # everything else). So does a step with nothing to launch.
        if self._inflight and (
                not batch or any(r.fsm is not None for r in batch)):
            emitted += self._collapse_locked()
            batch = eligible()
        if not batch:
            # pure drain step: the reconcile above retired the last
            # in-flight tokens; record it so the flight ring shows the
            # lag collapsing rather than a mystery gap
            self._account_step_locked(
                "decode", obs.clock() - t0, t0_wall, emitted, batch=0,
                tokens=emitted,
            )
            return
        # the ONE step still in flight, if any: where the ids of the rows
        # it holds are. ``steady``: this step is launched behind it with
        # nothing synced first
        ahead = self._inflight[-1] if self._inflight else None
        steady = ahead is not None
        with self._phase("kv.reserve"):
            self._apply_promotions_locked()
            pairs: list[tuple[int, int]] = []
            kv_tokens = 0
            kv_tokens_window = 0
            rows_past_window = 0
            kv_chunks = 0
            for r in batch:
                # effective length includes the in-flight token: its K/V
                # row lands at position eff-1 during this dispatch
                eff = r.total_len + r.inflight
                r.drawn_blocks -= self.cache.free_behind(r.id, eff - 1)
                appended = self.cache.ensure_capacity(r.id, eff)
                r.drawn_blocks += appended
                cow = self.cache.prepare_write(r.id, eff - 1, eff)
                r.drawn_blocks += len(cow)
                pairs.extend(cow)
                # what the attention kernel must read for this row: its
                # context, in whole blocks
                kv_tokens += -(-eff // bs) * bs
                if self._kv_window:
                    # and what a sliding layer's call attends of it
                    kv_tokens_window += min(eff, self._kv_window)
                    # the rows whose sliding layers really slide
                    rows_past_window += eff > self._kv_window
                if self._kv_ring:
                    # a composed table's: the exact rows of the window the
                    # row's position lies in, and a summary a chunk of
                    # every window closed before it
                    W, C = self._kv_ring
                    kv_tokens_window += (eff - 1) % W + 1
                    kv_chunks += (W // C) * ((eff - 1) // W)
                    self._eva_counts["chunks_written_decode"] += eff % C == 0
                    self._eva_counts["windows_closed_decode"] += eff % W == 0
            self._apply_copies_locked(pairs)
        with self._phase("engine.batch"):
            B = pad_to_bucket(len(batch), self._batch_buckets)
            # a row can HOLD blocks past its committed frontier (a verify
            # step whose drafts were rejected appended them; they're
            # reused as the frontier advances) — the table must span
            # what's held, not just what's committed
            ctx = pad_to_bucket(
                max(
                    max(r.total_len + r.inflight,
                        self.cache.num_allocated(r.id) * bs)
                    for r in batch
                ),
                self._length_buckets,
            )
            nb = self._table_blocks(ctx)
            positions = self._scratch_buf("dec_positions", (B,), np.int32)
            tables = self._tables_buf("dec_tables", B, nb)
            slots = self._slots_buf_locked("dec_slots", batch, B)
            # reused buffers: re-zero padding rows (a stale table row
            # could point at blocks now owned by a live sequence)
            positions[len(batch):] = 0
            tables[..., len(batch):, :] = 0
            for i, r in enumerate(batch):
                positions[i] = r.total_len + r.inflight - 1
                tables[..., i, :] = self._table_for(r, nb, positions[i])
            feed = None
            same = (steady and ahead.ids_at is None
                    and batch == ahead.batch)
            if same:
                # list equality is element identity here: the same
                # _Request objects in the same order. Feed step N+1 from
                # step N's sampled ids without a host round-trip — THE
                # datapath that makes the pipeline a win (the executor
                # passes on-device arrays through untouched)
                tokens_src = ahead.tokens
            else:
                # rows joined or left: a row that rode the step in flight
                # has its id in that step's array (row ``feed[0, i]``),
                # any other on the host (``feed[1, i]``); padding rows
                # take the host's 0
                feed = self._scratch_buf("dec_feed", (2, B), np.int32)
                feed[0] = -1
                feed[1, len(batch):] = 0
                for i, r in enumerate(batch):
                    if r.inflight:
                        feed[0, i] = ahead.row_of(r)
                    else:
                        feed[1, i] = (
                            r.generated[-1] if r.generated else r.prompt[-1]
                        )
                if steady:
                    # gathered on the device even where the host holds
                    # every id (a decode behind a chunk that is not its
                    # row's last): a decode program is called with ids ON
                    # THE DEVICE always, ONE argument form (executor.py
                    # ``decode_step``)
                    tokens_src = ahead.tokens
                else:  # nothing in flight: every id is on the host
                    tokens_src, feed = feed[1], None
            sample = self._sample_args_locked(batch, B)
        # what the kernels read this step, for the dispatch span and the
        # flight record alike
        kv = {"kv_tokens": kv_tokens}
        if self._kv_window or self._kv_ring:
            kv["kv_tokens_window"] = kv_tokens_window
        if self._kv_window:
            kv["rows_past_window"] = rows_past_window
            self._decode_rows += len(batch)
            self._decode_rows_past_window += rows_past_window
        if self._kv_ring:
            kv["kv_chunks"] = kv_chunks
        if self._step_attrs is not None:
            # each row queries the position of its in-flight token
            kv.update(self._step_attrs(
                self.model_cfg, "decode",
                [(r.total_len + r.inflight - 1, 1) for r in batch]))
        span = {"kind": "decode", "seq": self._launched + 1, **kv}
        if self._kv_ring:
            span["eva_chunks"] = B  # each row's current chunk, read back
        next_dev = self.executor.decode_step(
            tokens_src, positions, tables, sample=sample, span=span,
            slots=slots, feed=feed,
        )
        remapped = steady and not same
        self._decode_steps += 1
        self._decode_steps_steady += steady
        self._decode_steps_remapped += remapped
        for r in batch:
            r.inflight += 1
        rec = self._launched_locked(
            _InFlight(kind="decode", tokens=next_dev, batch=batch))
        # reconcile what was in flight only after dispatching N+1 — the
        # host work above ran while it was still executing on device
        emitted += self._reconcile_older_locked(rec)
        dt = obs.clock() - t0
        self._decode_step_window.append(dt)
        self._account_step_locked(
            "decode", dt, t0_wall, emitted, batch=len(batch), bucket_b=B,
            bucket_len=ctx, nb=nb, tokens=emitted, **kv,
            **({} if self._gmm_form is None
               else {"gmm_form": self._gmm_form(B)}),
            steady=steady, remapped=remapped,
            trace_ids=self._trace_ids_locked(batch),
        )

    def _account_step_locked(self, kind: str, dt: float, t0_wall: float,
                             goodput_tokens: int, **fields) -> None:
        """The block that ends every step, as the ``engine.account``
        phase: gauges, the step-latency histogram, goodput and the flight
        record, all from the ONE duration ``dt``."""
        with self._phase("engine.account"):
            self._m_util.set(self.cache.utilization)
            self._sync_cache_counters_locked()
            self._m_latency.observe(dt, tags={"kind": kind})
            self._goodput_record_locked(kind, dt, goodput_tokens)
            self._flight_record_locked(kind, t0_wall, dt, **fields)

    def _reconcile_locked(self, rec: _InFlight) -> int:
        """Collapse the dispatch lag for one step program in flight, the
        OLDEST: sync its sampled ids (THE O(batch) int32 transfer: an id a
        row, or a block family's block of ids and its masked bits a row),
        flush the block quarantine up to it (a completed sync proves this
        and every earlier dispatch executed, so blocks freed while none
        newer was in flight are safe to reuse), then emit/retire per row
        (one token a row of an autoregressive family's decode step; 0 or
        up to a block's of a block family's pass). Rows
        that terminated after the dispatch (EOS raced the lag, cancel,
        deadline, failover) drop their speculative token here and release
        their blocks — exactly once, via the inflight-guarded release. A
        prefill step's reconcile also books what its step left open: the
        chunk's timeline entry, ``register_prefix``, the first tokens of
        the rows whose chunk was their last, and the step's flight record
        (``dur_ms``: from the step's start to its ids on the host). The
        host phases are booked under the RECONCILED step's kind, whatever
        step runs them. Returns the decode tokens emitted (a prefill's
        first tokens are no decode step's output)."""
        assert rec is self._inflight[0], "reconcile oldest first"
        self._inflight.pop(0)
        booked = self._step_kind
        if rec.kind != booked:
            self._fold_phases_locked()
            self._step_kind = rec.kind
        try:
            # honest: the launches that sat between this step and its sync
            lag = self._launched - rec.seq
            toks = self._sync_tokens_locked(rec.tokens, lag=lag, seq=rec.seq)
            with self._phase("engine.emit"):
                self.cache.flush_quarantine(upto=rec.seq)
                if rec.rows is None:
                    emitted = self._emit_decoded_locked(rec, toks)
                else:
                    dt = obs.clock() - rec.t0
                    self._emit_prefilled_locked(rec, toks, dt)
                    emitted = 0
                self._running = [r for r in self._running if not r.done]
            if rec.rows is not None:
                self._prefill_syncs_deferred += lag > 0
                self._account_step_locked(
                    rec.kind, dt, rec.t0_wall, rec.fields["tokens"],
                    **rec.fields)
        finally:
            if rec.kind != booked:
                self._fold_phases_locked()
                self._step_kind = booked
        return emitted

    def _emit_decoded_locked(self, rec: _InFlight, toks) -> int:
        """An autoregressive family's decode step: ONE id a row (a block
        family's pass: ``_emit_blocks_locked``, bound in ``__init__``)."""
        book = _StepTokens()
        for r, tok in zip(rec.batch, toks.tolist()):
            r.inflight -= 1
            if r.done:
                # the <=1 wasted speculative row per finished request
                self._release_blocks_locked(r)
            else:
                self._emit_token_locked(r, tok, book)
        return self._book_tokens_locked(book)

    def _emit_prefilled_locked(self, rec: _InFlight, toks,
                               dt: float) -> None:
        """``dt`` covers the chunk's real cost — COW copies, padding, the
        jitted call and THE host sync, wherever that was made. The same
        value feeds the latency histogram, the flight record and the
        per-request chunk timeline entries, so every record agrees (one
        clock)."""
        book = _StepTokens()
        dur_ms = round(dt * 1000.0, 3)
        ids = toks.tolist()
        if rec.ids_at is not None:  # packed: a request's last piece's row
            ids = [ids[i] for i in rec.ids_at]
        for r, tok, (n, chain, done, final) in zip(
                rec.batch, ids, rec.rows):
            r.inflight -= 1
            self._tl(r, rec.kind, ts=rec.t0_wall, dur_ms=dur_ms, tokens=n,
                     prefill_done=done)
            if self.cfg.prefix_caching:
                self.cache.register_prefix(r.id, chain, done)
            if r.done:
                # cancelled or expired with the chunk in flight
                self._release_blocks_locked(r)
            elif final and not self._block_len:
                # the model samples from last-VALID-token logits per
                # row — for the final chunk that is the last prompt
                # token (or, resuming, the last already-emitted token:
                # the keyed sampler reproduces the next token
                # byte-identically)
                self._emit_token_locked(r, tok, book)
        self._book_tokens_locked(book)

    # ------- generation by diffusion over blocks (models/sdar_moe.py) -------

    def _eligible_blocks_locked(self) -> list[_Request]:
        """``_eligible_locked`` where a step carries a block a row: the
        rows whose launched commits do not yet deliver all they may. A
        row whose last commit is in flight is not launched again; one
        that meets EOS inside a block is known only at the reconcile of
        the pass that commits it, and what was launched behind the
        finished block (a fold's second half, the pass after it) is
        wasted."""
        return [r for r in self._running
                if r.blk.due < r.sampling.max_new_tokens]

    def _decode_blocks_locked(self) -> None:
        """``_decode_locked`` for a family that generates by diffusion
        over blocks: ONE pass of the family's decode program a row. A row
        is in one of its block's denoising passes (the pass carries the
        block, ``W`` positions), or its block is finished. A finished
        block behind which another is due FOLDS: the row carries
        ``[finished block | next block, all masks]``, ``2 W`` positions
        from the finished block's start, and the pass that leaves the
        finished block's K/V in the cache for good is the next block's
        first denoising pass (models/cached.py ``_block_step``). Only a
        request's LAST block (``max_new_tokens`` reached: nothing to
        append) is committed by a pass of its own, which chooses no
        token. So n blocks under T steps take ``T n + 1`` row-passes.
        Which phase a row is in, and how many positions the pass fills,
        the HOST knows from the row's schedule (``_BlockRow``) without
        reading the device, so the dispatch lag stays: pass N + 1 is
        launched behind pass N with the rows' ids and masked bits taken
        where they are, in pass N's on-device result (the same rows in
        the same order: that array itself; rows joined or left: one
        gather, ``executor.feed_rows``) or on the host (a row whose
        prefill has just ended, or whose last pass has been reconciled);
        the program reads a fold from the row's own data, no bit set and
        a ``fill`` above 0. The program rewrites the K/V rows of what a
        row carries, from ``start``, every pass: they are PROVISIONAL,
        inside the row's reservation and past its committed frontier,
        and a block's stand for good once a pass has run over its
        finished ids; the frontier then moves by a block, at that pass's
        launch, and the block's tokens reach the stream at its reconcile.
        Only a row under ``low_confidence_dynamic`` (a block's length in
        passes is the device's to say) collapses the lag first, each
        step, as a grammar-constrained row does in ``_decode_locked``."""
        chaos.fire("engine.decode", batch=len(self._running))
        self._step_kind = "decode"
        t0 = obs.clock()
        t0_wall = obs.wall()
        bs, W = self.cfg.block_size, self._block_len
        with self._phase("engine.batch"):
            batch = self._eligible_locked()
        emitted = 0
        if self._inflight and (
                not batch or any(r.blk.dynamic for r in batch)):
            emitted += self._collapse_locked()
            batch = self._eligible_locked()
        if not batch:
            self._account_step_locked(
                "decode", obs.clock() - t0, t0_wall, emitted, batch=0,
                tokens=emitted,
            )
            return
        ahead = self._inflight[-1] if self._inflight else None
        steady = ahead is not None
        with self._phase("kv.reserve"):
            self._apply_promotions_locked()
            pairs: list[tuple[int, int]] = []
            kv_tokens = 0
            # a row: (the tokens its finished block delivers, None where
            # the pass denoises; whether the next block rides behind it;
            # the end of what the row carries)
            plan = []
            for r in batch:
                k = r.blk
                deliver, folds = None, False
                if k.finished:
                    due = k.due + min(
                        W - k.lead, r.sampling.max_new_tokens - k.due)
                    deliver = due - k.due
                    folds = due < r.sampling.max_new_tokens
                # what the row carries, committed or not, lies inside its
                # reservation: a page is whole blocks, and a fold's second
                # block exists only while tokens are due
                end = k.start + (2 * W if folds else W)
                plan.append((deliver, folds, end))
                r.drawn_blocks += self.cache.ensure_capacity(r.id, end)
                cow = self.cache.prepare_write(r.id, k.start, end)
                r.drawn_blocks += len(cow)
                pairs.extend(cow)
                # what the kernel reads: the context to the END of the
                # last block the row carries
                kv_tokens += -(-end // bs) * bs
            self._apply_copies_locked(pairs)
        with self._phase("engine.batch"):
            n = len(batch)
            B = pad_to_bucket(n, self._batch_buckets)
            ctx = pad_to_bucket(
                max(max(end, self.cache.num_allocated(r.id) * bs)
                    for r, (_, _, end) in zip(batch, plan)),
                self._length_buckets,
            )
            nb = self._table_blocks(ctx)
            positions = self._scratch_buf("dec_positions", (B,), np.int32)
            tables = self._tables_buf("dec_tables", B, nb)
            slots = self._slots_buf_locked("dec_slots", batch, B)
            fill = self._scratch_buf("blk_fill", (B,), np.int32)
            mode = self._scratch_buf("blk_mode", (B,), np.int32)
            # row i: where its ids are in the pass in flight (-1: on the
            # host), then the ids and bits the host holds
            feed = self._scratch_buf("blk_feed", (B, W + 2), np.int32)
            for buf in (positions, fill, mode, feed):
                buf[n:] = 0
            tables[..., n:, :] = 0
            # the ids of a row that rides the PASS in flight are in its
            # result; a prefill step in flight holds no id of any row
            riding = steady and ahead.passes is not None
            passes = []
            for i, (r, (deliver, folds, _)) in enumerate(zip(batch, plan)):
                k = r.blk
                positions[i] = k.start
                tables[..., i, :] = self._table_for(r, nb, k.start)
                mode[i] = k.mode
                if riding and r.inflight:
                    feed[i, 0] = ahead.row_of(r)
                else:
                    feed[i, 0] = -1
                    feed[i, 1:] = k.x
                if deliver is None:
                    # (dynamic: AT LEAST so many, and one where the
                    # schedule has run out)
                    fill[i] = k.fills[k.p] if k.p < len(k.fills) else 1
                    passes.append((False, 0, 0))
                    k.p += 1
                    continue
                passes.append((True, k.lead, deliver))
                # the host's view moves on at the launch: the next pass
                # chooses in the next block; a fold was its first pass
                k.due += deliver
                k.start, k.lead, k.fills = k.start + W, 0, k.whole
                fill[i] = k.fills[0] if folds else 0
                k.p = int(folds)
            same = riding and batch == ahead.batch
            if same:
                tokens_src, feed = ahead.tokens, None
            elif riding:
                tokens_src = ahead.tokens
            else:  # every row's ids are on the host
                tokens_src, feed = feed[:, 1:], None
            sample = self._sample_args_locked(batch, B)
            sample.update(fill=fill, remasking=mode)
        folded = sum(folds for _, folds, _ in plan)
        delivered = [d for d, _, _ in plan if d is not None]
        counts = self._block_counts
        counts["block_passes"] += n
        counts["block_passes_folded"] += folded
        counts["block_passes_commit"] += len(delivered) - folded
        kv = {"kv_tokens": kv_tokens, "rows": n, "block_len": W,
              "rows_commit": len(delivered) - folded, "rows_folded": folded,
              "tokens_committed": sum(delivered)}
        span = {"kind": "decode", "seq": self._launched + 1, **kv}
        next_dev = self.executor.decode_step(
            tokens_src, positions, tables, sample=sample, span=span,
            slots=slots, feed=feed,
        )
        remapped = steady and not same
        self._decode_steps += 1
        self._decode_steps_steady += steady
        self._decode_steps_remapped += remapped
        for r in batch:
            r.inflight += 1
        rec = self._launched_locked(_InFlight(
            kind="decode", tokens=next_dev, batch=batch, passes=passes))
        emitted += self._reconcile_older_locked(rec)
        dt = obs.clock() - t0
        self._decode_step_window.append(dt)
        self._account_step_locked(
            "decode", dt, t0_wall, emitted, batch=n, bucket_b=B,
            bucket_len=ctx, nb=nb, tokens=emitted, **kv,
            gmm_form=self._gmm_form(B * 2 * W), steady=steady,
            remapped=remapped, trace_ids=self._trace_ids_locked(batch),
        )

    def _emit_blocks_locked(self, rec: _InFlight, toks) -> int:
        """``_emit_decoded_locked`` for a pass over blocks: ``toks [B, W +
        1]`` is what the pass gave back a row (the ids and masked bits of
        the block that chose), kept as the row's ``x``. A row whose pass
        COMMITTED a block (a fold, or a last block's commit) puts that
        block on its stream, in one go under the step's one timestamp:
        the ids its last denoising pass left (the ``x`` kept at that
        pass's reconcile), from behind the prompt's tail, cut at
        ``max_new_tokens`` or behind an EOS or a stop sequence (what is
        cut was generated and is not delivered: ``block_tokens_cut``; a
        fold's fresh block is then dropped with the row). The gaps
        between a block's tokens are what the client sees, 0, and are
        booked so; a reconciled step emits 0 or up to W tokens a row."""
        book = _StepTokens()
        W, counts = self._block_len, self._block_counts
        for r, x, (commit, lead, deliver) in zip(
                rec.batch, toks.tolist(), rec.passes):
            r.inflight -= 1
            if r.done:
                # passes launched behind a row's last block (EOS, cancel)
                self._release_blocks_locked(r)
                continue
            k = r.blk
            if commit:
                sent = 0
                for tok in k.x[lead:lead + deliver]:
                    self._emit_token_locked(r, tok, book)
                    sent += 1
                    if r.done:
                        break
                counts["blocks_committed"] += 1
                counts["block_tokens_committed"] += sent
                counts["block_tokens_cut"] += W - lead - sent
            k.x = x
        return self._book_tokens_locked(book)

    def _propose_drafts_locked(self, batch: list) -> list[list[int]] | None:
        """Ask the drafter for up to ``speculative_k`` candidate tokens
        per row. Per-row draft length is clamped to the row's remaining
        token budget minus one — so committed tokens (accepted prefix +
        one corrected/bonus) can never exceed ``max_new_tokens``, which
        also keeps every speculative KV write inside the row's worst-case
        block reservation. Out-of-vocab proposals truncate the draft (a
        drafter is a performance hint, never a correctness input).
        Returns None when no row drafted anything."""
        k = self.cfg.speculative_k
        V = self.model_cfg.vocab_size
        out: list[list[int]] = []
        any_draft = False
        for r in batch:
            k_eff = min(
                k,
                r.sampling.max_new_tokens - len(r.generated)
                - r.inflight - 1,
            )
            clean: list[int] = []
            if k_eff > 0:
                for t in self._drafter.propose(
                    r.prompt, r.generated, k_eff
                ):
                    t = int(t)
                    if not 0 <= t < V or len(clean) >= k_eff:
                        break
                    clean.append(t)
                if r.fsm is not None and clean:
                    # constrained rows: only a grammar-valid prefix can
                    # ever be accepted, so truncate at the first token
                    # the DFA rejects — verify stays lossless, and an
                    # empty draft is the per-request spec-off fallback
                    # (that row degenerates to a 1-token verify)
                    clean = r.fsm.filter_draft(clean)
            out.append(clean)
            any_draft = any_draft or bool(clean)
        return out if any_draft else None

    def _verify_locked(self, batch: list, proposals: list[list[int]],
                       t0: float, t0_wall: float, emitted: int) -> None:
        """One synchronous speculative verify step over ``batch``: stage
        the [B, W] window (column 0 = each row's last committed token —
        exactly what a plain decode step would feed — then its drafts;
        W = speculative_k + 1 FROZEN per engine so the signature set
        stays closed under mixed traffic), run the jitted verify, sync
        the packed [B, W+1] verdicts (lag 0 — the next window's drafts
        need these tokens on host), and emit 1..draft_len+1 committed
        tokens per row. EOS landing mid-window stops that row's emission
        on the spot; the remaining verdicts are dead and its blocks
        release exactly once through the normal completion path
        (``inflight`` is 0 here — verify never runs under the lag)."""
        self._step_kind = "verify"
        bs = self.cfg.block_size
        W = self.cfg.speculative_k + 1
        draft_lens = [len(p) for p in proposals]
        with self._phase("kv.reserve"):
            self._apply_promotions_locked()
            pairs: list[tuple[int, int]] = []
            kv_tokens = 0
            for r, dl in zip(batch, draft_lens):
                # the window writes K/V at positions total_len-1 ..
                # total_len-1+dl (committed column + live draft columns;
                # padding columns redirect to the garbage block, so the
                # reservation only covers the clamped draft length)
                eff = r.total_len + dl
                appended = self.cache.ensure_capacity(r.id, eff)
                r.drawn_blocks += appended
                cow = self.cache.prepare_write(r.id, r.total_len - 1, eff)
                r.drawn_blocks += len(cow)
                pairs.extend(cow)
                kv_tokens += -(-eff // bs) * bs
            self._apply_copies_locked(pairs)
        with self._phase("engine.batch"):
            B = pad_to_bucket(len(batch), self._batch_buckets)
            # span what each row HOLDS, not just this window: an earlier
            # rejected window may have appended blocks past today's eff
            ctx = pad_to_bucket(
                max(
                    max(r.total_len + dl,
                        self.cache.num_allocated(r.id) * bs)
                    for r, dl in zip(batch, draft_lens)
                ),
                self._length_buckets,
            )
            nb = ctx // bs
            tokens = self._scratch_buf("vf_tokens", (B, W), np.int32)
            starts = self._scratch_buf("vf_starts", (B,), np.int32)
            dlen = self._scratch_buf("vf_dlen", (B,), np.int32)
            tables = self._scratch_buf("vf_tables", (B, nb), np.int32)
            # reused buffers: re-zero padding (a stale table row could
            # point at blocks now owned by a live sequence)
            tokens[len(batch):] = 0
            starts[len(batch):] = 0
            dlen[len(batch):] = 0
            tables[len(batch):] = 0
            for i, (r, props) in enumerate(zip(batch, proposals)):
                tokens[i, 0] = (
                    r.generated[-1] if r.generated else r.prompt[-1]
                )
                tokens[i, 1:1 + len(props)] = props
                tokens[i, 1 + len(props):] = 0
                starts[i] = r.total_len - 1
                dlen[i] = len(props)
                tables[i] = self._table_for(r, nb)
            # verify windows need one allow-mask PER COLUMN (column s is
            # sampled from the FSM state after consuming props[:s]): the
            # [B, W, words] leaf takes the per-row decode mask's place, all
            # ones for unconstrained rows, so the verify pytree (and the
            # compile kind) is identical for mixed batches
            sample = self._sample_args_locked(batch, B, proposals)
        packed_dev = self.executor.verify_step(
            tokens, starts, dlen, tables, sample=sample,
            span={"kind": "verify", "seq": self._launched + 1,
                  "kv_tokens": kv_tokens},
        )
        self._launched += 1  # synced at once: never held in flight
        packed = self._sync_verify_locked(packed_dev)
        with self._phase("engine.emit"):
            # a completed sync proves every earlier dispatch executed
            self.cache.flush_quarantine()
            drafted = sum(draft_lens)
            accepted = 0
            step_tokens = 0
            book = _StepTokens()
            for r, dl, row in zip(batch, draft_lens, packed.tolist()):
                # device contract: 1 <= committed <= draft_len + 1; clamp
                # anyway so a bad verdict can never overrun the budget
                committed = max(1, min(row[0], dl + 1))
                accepted += committed - 1
                if r.trace_ctx:
                    # traced rows carry the speculation outcome per window
                    # — rendered as an engine.verify span at finish (host
                    # list append only; untraced rows skip even that)
                    self._tl(r, "verify_window", ts=t0_wall,
                             dur_ms=round((book.now - t0) * 1000.0, 3),
                             drafted=dl, accepted=committed - 1, window=W)
                for tok in row[1:1 + committed]:
                    self._emit_token_locked(r, tok, book)
                    step_tokens += 1
                    if r.done:
                        break
            self._book_tokens_locked(book)
            self._running = [r for r in self._running if not r.done]
        self._spec_steps += 1
        self._spec_drafted_total += drafted
        self._spec_accepted_total += accepted
        self._spec_committed_total += step_tokens
        if drafted:
            self._m_spec_drafted.inc(drafted)
        if accepted:
            self._m_spec_accepted.inc(accepted)
        self._m_spec_committed.inc(step_tokens)
        dt = obs.clock() - t0
        self._decode_step_window.append(dt)
        self._account_step_locked(
            "verify", dt, t0_wall, emitted + step_tokens, batch=len(batch),
            bucket_b=B, bucket_len=ctx, nb=nb, window=W, drafted=drafted,
            accepted=accepted, tokens=emitted + step_tokens,
            kv_tokens=kv_tokens, steady=False,
            trace_ids=self._trace_ids_locked(batch),
        )

    def _sync_verify_locked(self, packed_dev) -> np.ndarray:
        """The verify-step host sync: one packed [B, W+1] int32 array
        through the same blessed channel (executor.sync_verify ->
        _host_tokens), timed and metered exactly like the token sync."""
        with self._phase("engine.sync", lag=0, seq=self._launched) as ph:
            packed = self.executor.sync_verify(packed_dev)
        dt = ph.seconds
        self._m_sync.observe(dt)
        self._m_sync_bytes.inc(packed.nbytes)
        self._sync_seconds_total += dt
        self._sync_bytes_total += packed.nbytes
        self._last_sync = {
            "sync_ms": round(dt * 1000.0, 3),
            "sync_bytes": int(packed.nbytes),
            "sync_lag": 0,
        }
        return packed

    def _sync_tokens_locked(self, tokens_dev, *, lag: int,
                            seq: int) -> np.ndarray:
        """THE device->host sync: O(batch) int32 token ids, timed and
        metered. ``lag`` says how many dispatches sat between this
        array's producing step and now (0 = nothing was launched behind
        it: a collapse, a prefill with nothing to follow; 1 = the
        pipelined path, decode or prefill); it lands in the flight record
        so lagged token timestamps are explainable
        (docs/OBSERVABILITY.md). ``seq`` is that step's launch number,
        the one its ``executor.dispatch`` span carries: the sync cannot
        end before the run of launch ``seq`` does, whatever the lag.
        The transfer itself is the executor's ``sync_tokens``
        (executor._host_tokens — THE allowed host sync)."""
        with self._phase("engine.sync", lag=lag, seq=seq) as ph:
            toks = self.executor.sync_tokens(tokens_dev)
        dt = ph.seconds
        self._m_sync.observe(dt)
        self._m_sync_bytes.inc(toks.nbytes)
        self._sync_seconds_total += dt
        self._sync_bytes_total += toks.nbytes
        self._last_sync = {
            "sync_ms": round(dt * 1000.0, 3),
            "sync_bytes": int(toks.nbytes),
            "sync_lag": lag,
        }
        return toks

    def _goodput_record_locked(self, kind: str, dt: float,
                               tokens: int) -> None:
        """Fold one step's (device-time, tokens) sample into the windowed
        ``llm_goodput_tokens_per_sec`` / ``llm_serving_mfu`` gauges for
        its kind. ``dt`` is the step's one-clock duration. For a decode
        step that is launch to launch ON THE HOST: one step's host work
        with the sync of the step before it, which waits only where the
        device's step is the longer. So it approximates ONE device step
        where the device sets the pace, which is the attribution a
        utilization gauge wants, and the host's own step where the host
        does (the gauge then reads low by the device's idle share). For a
        prefill it runs from the step's start to its ids on the host,
        behind the next launch where one followed; for a verify step or
        a drain it includes the blocking sync (docs/OBSERVABILITY.md,
        "lag-1 caveat"). MFU is goodput times the analytic 2N forward
        FLOPs/token over the executor's peak FLOP rate. O(1) a step: the
        window's two sums are kept as samples enter and leave it (at
        most ``_GOODPUT_WINDOW_STEPS``, none older than the horizon)."""
        now = obs.clock()
        win = self._goodput_windows.get(kind)
        if win is None:
            # [samples, their device seconds, their tokens]
            win = self._goodput_windows[kind] = [deque(), 0.0, 0]
        samples = win[0]
        samples.append((now, float(dt), int(tokens)))
        win[1] += float(dt)
        win[2] += int(tokens)
        horizon = now - _GOODPUT_WINDOW_S
        while (len(samples) > _GOODPUT_WINDOW_STEPS
               or samples[0][0] < horizon):
            _, gone_s, gone_tokens = samples.popleft()
            win[1] -= gone_s
            win[2] -= gone_tokens
        _, dev_s, toks = win
        if dev_s <= 0.0 or toks <= 0:
            return
        tps = toks / dev_s
        # a device with no published peak has no MFU: 0.0, never a guess
        peak_flops = (self.executor.peak_tflops or 0.0) * 1e12
        mfu = (
            tps * self._flops_per_token / peak_flops
            if peak_flops > 0.0
            else 0.0
        )
        self._m_goodput.set(tps, tags={"kind": kind})
        self._m_mfu.set(mfu, tags={"kind": kind})
        self._goodput_last[kind] = {
            "tokens_per_sec": round(tps, 3),
            "mfu": round(mfu, 6),
            "window_steps": len(samples),
            "window_device_s": round(dev_s, 6),
            "window_tokens": toks,
        }

    def _sample_args_locked(self, batch: list, B: int,
                            proposals: list[list[int]] | None = None,
                            rows: list[int] | None = None) -> dict:
        """Per-row sampling controls as [B] host staging arrays — the
        ``sample`` pytree consumed by ops/sampling.py inside the jitted
        step (they ride the jitted call to the device). Padding rows are
        greedy (temperature 0) so the batch-wide all-greedy fast path
        stays available whenever every REAL row is greedy. ``proposals``:
        a verify window's drafts, a row each. ``rows``: the row each
        request's controls go to (a packed prefill step: where its id is
        sampled, every other row as padding); None: row i."""
        seeds = self._scratch_buf("sp_seeds", (B,), np.uint32)
        temp = self._scratch_buf("sp_temp", (B,), np.float32)
        top_k = self._scratch_buf("sp_top_k", (B,), np.int32)
        top_p = self._scratch_buf("sp_top_p", (B,), np.float32)
        n = 0 if rows is not None else len(batch)
        seeds[n:] = 0
        temp[n:] = 0.0
        top_k[n:] = 0
        top_p[n:] = 1.0
        for i, r in zip(rows or range(len(batch)), batch):
            sp = r.sampling
            seeds[i] = sp.seed & 0xFFFFFFFF
            temp[i] = sp.temperature
            top_k[i] = sp.top_k
            top_p[i] = sp.top_p
        return {
            "seeds": seeds,
            "temperature": temp,
            "top_k": top_k,
            "top_p": top_p,
            "mask": self._allow_mask_locked(batch, B, proposals, rows),
        }

    def _allow_mask_locked(self, batch: list, B: int,
                           proposals: list[list[int]] | None,
                           rows: list[int] | None = None):
        """The grammar allow-mask leaf, ALWAYS part of ``sample`` (all
        ones = no constraint): mask is data, not signature, so constrained
        and unconstrained rows share one step program and the compile
        kind set never grows (ops/sampling.apply_allow_mask is a bitwise
        identity on all-ones rows). ``[B, words]`` uint32, a verify
        window's ``[B, W, words]``. Where no row of the batch is
        constrained it is the executor's RESIDENT all-ones array of that
        shape: nothing is filled and nothing moved (at a real vocabulary
        the mask is 97% of a step's staged bytes). Only a batch with a
        constrained row stages one from the host."""
        words = (self.model_cfg.vocab_size + 31) // 32
        shape = ((B, words) if proposals is None
                 else (B, self.cfg.speculative_k + 1, words))
        if all(r.fsm is None for r in batch):
            return self.executor.ones_mask(shape)
        mask = self._scratch_buf("sp_mask", shape, np.uint32)
        mask[:] = 0xFFFFFFFF
        for i, r in zip(rows or range(len(batch)), batch):
            if r.fsm is None:
                continue
            if proposals is None:
                mask[i] = r.fsm.allow_row()
                self._m_masked_frac.observe(r.fsm.masked_fraction())
            else:
                r.fsm.stage_verify_masks(mask[i], proposals[i])
        return mask

    def _slots_buf_locked(self, name: str, batch: list, B: int,
                          first: list | None = None) -> np.ndarray | None:
        """[B] int32, BY ROW: rows ``first[k]`` to ``first[k + 1]`` are
        request k's (None: row k is; a packed step's rows are pieces).
        Where the family keeps state rows, each row's state slot; where
        it keeps only counters, 1 on every real row: all such a step
        program reads of its slots is which rows are padding (slot 0, the
        garbage sink, either way). None for a family that keeps no
        state."""
        if not self._stateful:
            return None
        slots = self._scratch_buf(name, (B,), np.int32)
        if first is None:
            first = range(len(batch) + 1)
        slots[first[-1]:] = 0
        for k, r in enumerate(batch):
            slots[first[k]:first[k + 1]] = (
                self.cache.slot(r.id) if self._state_rows else 1)
        return slots

    def _tables_buf(self, name: str, B: int, nb: int) -> np.ndarray:
        """A step's block tables, to be filled: ``[B, nb]``, or with
        tables by group ``[G, B, nb]`` (``tables[..., i, :]`` is row i's
        either way)."""
        groups = len(self.cache.cfg.groups)
        return self._scratch_buf(
            name, (groups, B, nb) if groups else (B, nb), np.int32)

    def _scratch_buf(self, name: str, shape: tuple, dtype) -> np.ndarray:
        """Reusable numpy staging buffer for one (name, shape) slot. TWO
        buffers alternate per slot: the jitted call a staging array is
        handed to may alias it zero-copy (the CPU backend does) and runs
        after it returns, so a buffer must not be rewritten until the
        dispatch consuming it has provably executed — under the lag-1
        pipeline a slot comes around again only after the intervening
        sync, which is exactly that proof. Callers must overwrite every
        element they use and re-zero padding tails (buffers are dirty)."""
        key = (name, shape)
        slot = self._scratch.get(key)
        if slot is None:
            slot = [np.zeros(shape, dtype), np.zeros(shape, dtype), 0]
            self._scratch[key] = slot
        slot[2] ^= 1
        return slot[slot[2]]

    def _emit_token_locked(self, r: _Request, tok: int,
                           book: _StepTokens) -> None:
        """Put one synced id on its request's stream, stamped with the
        step's clocks (``book``); the histograms and counters get the
        step's rows together (``_book_tokens_locked``). Only a request
        with a grammar pays for its cursor, only one with stop sequences
        for their match."""
        is_eos = tok == self.cfg.eos_id
        fsm = r.fsm
        if fsm is not None and not is_eos:
            # advance the grammar cursor on the already-synced id BEFORE
            # emitting: a rejection (only reachable if on-device masking
            # degraded) terminates the stream WITHOUT the bad token, so
            # every prefix a client ever sees is grammar-valid
            if not self._advance_fsm_locked(r, tok):
                self._complete_locked(r)
                return
        generated = r.generated
        generated.append(tok)
        sampling = r.sampling
        n = len(generated)
        now = book.now
        if r.first_token_clock is None:
            r.first_token_clock = now
            book.ttfts.append(now - r.submitted_clock)
            event = "first_token"
        else:
            book.gaps.append(now - r.last_token_clock)
            event = "token"
        r.timeline.append({"event": event, "ts": book.wall,
                           "index": sampling.start_index + n - 1})
        r.last_token_clock = now
        r.out.put(tok)
        if (
            n >= sampling.max_new_tokens
            or is_eos
            or (fsm is not None and fsm.must_stop)
            or (sampling.stop and self._hits_stop_locked(r))
        ):
            self._complete_locked(r)

    def _book_tokens_locked(self, book: _StepTokens) -> int:
        """One reconciled step's tokens into the TTFT and TPOT histograms,
        the tokens counter and ``stats()["host"]["emit_rows"]``: every
        value observed, every token counted, once a step. Returns the
        tokens the step put on streams."""
        n = len(book.ttfts) + len(book.gaps)
        self._m_ttft.observe_many(book.ttfts)
        self._m_tpot.observe_many(book.gaps)
        self._m_tokens.inc(n)
        self._emit_rows += n
        return n

    def _advance_fsm_locked(self, r: _Request, tok: int) -> bool:
        """Advance one request's grammar cursor on an emitted token id
        (host ints from the blessed sync — never a device value). With
        on-device masking a rejection here is a degradation path, so it
        is LOUD by contract: log and terminate, never emit silently."""
        try:
            ok = r.fsm.advance(tok)
        except (IndexError, TypeError, ValueError) as e:
            logger.error(
                "grammar FSM advance failed for %r on token %d: %r",
                r.id, tok, e,
            )
            return False
        if not ok:
            logger.warning(
                "grammar rejected sampled token %d for %r "
                "(state=%d, dead=%s) — terminating the stream early",
                tok, r.id, r.fsm.state, r.fsm.dead,
            )
        return ok

    def _hits_stop_locked(self, r: _Request) -> bool:
        """True when the just-emitted token completes one of the
        request's stop sequences. The match window spans the failover
        resume boundary: a resumed request's already-delivered tokens
        are its prompt tail (start_index of them), so a stop sequence
        straddling the kill point still fires on the survivor."""
        stops = r.sampling.stop
        if not stops:
            return False
        gen = r.generated
        si = r.sampling.start_index
        for seq in stops:
            L = len(seq)
            if L <= len(gen):
                if tuple(gen[-L:]) == seq:
                    return True
            else:
                need = L - len(gen)
                if si >= need and (
                    tuple(r.prompt[-need:]) + tuple(gen) == seq
                ):
                    return True
        return False

    def _complete_locked(self, r: _Request) -> None:
        r.done = True
        self._finish_obs_locked(r, "finished")
        r.out.put(_DONE)
        # last: a row completing while its next token is still in flight
        # defers the free to that step's reconcile (exactly-once release)
        self._release_blocks_locked(r)

    def _sync_cache_counters_locked(self) -> None:
        """Export cache-stat deltas to the monotonic Prometheus counters
        (cache stats are plain ints; counters are process-shared)."""
        cs = self.cache.stats
        for key, value, counter in (
            ("hit", cs.prefix_hit_tokens, self._m_hit_tokens),
            ("evict", cs.prefix_evicted_blocks, self._m_evicted),
            ("cow", cs.cow_copies, self._m_cow),
            ("prefill", self._prefill_tokens_total, self._m_prefill_tokens),
            ("demote", cs.demoted_blocks, self._m_demoted),
            ("promote", cs.promoted_blocks, self._m_promoted),
        ):
            delta = value - self._exported[key]
            if delta > 0:
                counter.inc(delta)
                self._exported[key] = value
        self._m_host_blocks.set(
            0 if self.cache.host_tier is None else self.cache.host_tier.blocks
        )

    # ---------------- observability (ISSUE 4) ----------------

    def _tl(self, r: _Request, event: str, ts: float | None = None,
            **attrs) -> None:
        """Append one phase event to a request's timeline (host list
        append — always on; the expensive part, span emission, only
        happens for traced requests at finish)."""
        e = {"event": event, "ts": obs.wall() if ts is None else ts}
        if attrs:
            e.update(attrs)
        r.timeline.append(e)

    def _timeline_dict(self, r: _Request) -> dict:
        return {
            "request_id": r.id,
            "trace_id": r.trace_ctx["trace_id"] if r.trace_ctx else None,
            "finish_reason": r.finish_reason,
            "events": list(r.timeline),
        }

    def _finish_obs_locked(self, r: _Request, reason: str) -> None:
        """Terminal bookkeeping for one request: stamp the terminal
        timeline event, archive the timeline for request_timeline(), and
        — when the submitter carried a trace context — emit the whole
        lifecycle as engine.* spans. Idempotent (failover/cancel races)."""
        if r.finish_reason is not None:
            return
        r.finish_reason = reason
        if reason == "finished":
            self._m_finished.inc()
        self._tl(r, reason, tokens=len(r.generated))
        self._timelines[r.id] = self._timeline_dict(r)
        while len(self._timelines) > self.cfg.timeline_history:
            self._timelines.popitem(last=False)
        if r.trace_ctx:
            try:
                self._emit_spans(r)
            except Exception as e:  # noqa: BLE001 — spans are best-effort
                logger.warning("span emission failed for %r: %r", r.id, e)

    def _emit_spans(self, r: _Request) -> None:
        """Turn a finished request's timeline into spans on the tracing
        plane: one ``engine.request`` parent under the submitter's span,
        with ``engine.queued``, per-chunk ``engine.prefill[_chunk]``, a
        zero-length ``engine.first_token`` marker, and one aggregate
        ``engine.decode`` child."""
        tid = r.trace_ctx["trace_id"]
        events = r.timeline
        # spans run from ``submitted``, inside the lock ("submit ->
        # terminal event", docs/OBSERVABILITY.md); the wait for the lock
        # before it, from ``received``, is the timeline's alone
        start = next(e["ts"] for e in events if e["event"] == "submitted")
        end = events[-1]["ts"]
        ttft_ts = next(
            (e["ts"] for e in events if e["event"] == "first_token"), None)
        root = tracing.record_span(
            "engine.request", trace_id=tid,
            parent_span_id=r.trace_ctx.get("parent_span_id"),
            start=start, end=end, kind="engine",
            attrs={
                "request_id": str(r.id),
                "finish_reason": r.finish_reason,
                "prompt_tokens": len(r.prompt),
                "cached_tokens": r.cached_tokens,
                "tokens": len(r.generated),
                "preempt_count": r.preempt_count,
                "ttft_s": (round(ttft_ts - start, 6)
                           if ttft_ts is not None else None),
            },
        )
        first_ts = last_ts = None
        decode_tokens = 0
        preempted_at: dict | None = None
        verify_windows = drafted = v_accepted = 0
        v_start = v_end = None
        for e in events:
            ev = e["event"]
            if ev == "admitted":
                tracing.record_span(
                    "engine.queued", trace_id=tid, parent_span_id=root,
                    start=start, end=e["ts"], kind="engine", attrs={},
                )
            elif ev in ("prefill", "prefill_chunk"):
                tracing.record_span(
                    f"engine.{ev}", trace_id=tid, parent_span_id=root,
                    start=e["ts"],
                    end=e["ts"] + e.get("dur_ms", 0.0) / 1000.0,
                    kind="engine",
                    attrs={"tokens": e.get("tokens"),
                           "prefill_done": e.get("prefill_done")},
                )
            elif ev == "first_token":
                first_ts = last_ts = e["ts"]
                tracing.record_span(
                    "engine.first_token", trace_id=tid,
                    parent_span_id=root, start=e["ts"], end=e["ts"],
                    kind="marker", attrs={"index": e.get("index")},
                )
            elif ev == "token":
                last_ts = e["ts"]
                decode_tokens += 1
            elif ev == "preempted":
                preempted_at = e
            elif ev == "resumed" and preempted_at is not None:
                tracing.record_span(
                    "engine.preempted", trace_id=tid, parent_span_id=root,
                    start=preempted_at["ts"], end=e["ts"], kind="engine",
                    attrs={"parked_ms": e.get("parked_ms"),
                           "priority": preempted_at.get("priority"),
                           "demoted_blocks":
                               preempted_at.get("demoted_blocks"),
                           "cached_tokens": e.get("cached_tokens")},
                )
                preempted_at = None
            elif ev == "verify_window":
                # speculation windows aggregate into ONE engine.verify
                # span (per-window spans would dwarf the decode span)
                verify_windows += 1
                drafted += e.get("drafted", 0)
                v_accepted += e.get("accepted", 0)
                if v_start is None:
                    v_start = e["ts"]
                v_end = e["ts"] + e.get("dur_ms", 0.0) / 1000.0
            elif ev == "kv_promote":
                tracing.record_span(
                    "kv.promote", trace_id=tid, parent_span_id=root,
                    start=e["ts"], end=e["ts"], kind="kv",
                    attrs={"blocks": e.get("blocks"),
                           "hit_tokens": e.get("hit_tokens")},
                )
        if preempted_at is not None:
            # still parked at finish (cancel/shutdown while preempted)
            tracing.record_span(
                "engine.preempted", trace_id=tid, parent_span_id=root,
                start=preempted_at["ts"], end=end, kind="engine",
                attrs={"priority": preempted_at.get("priority"),
                       "resumed": False},
            )
        if verify_windows:
            tracing.record_span(
                "engine.verify", trace_id=tid, parent_span_id=root,
                start=v_start, end=v_end, kind="engine",
                attrs={"windows": verify_windows, "drafted": drafted,
                       "accepted": v_accepted},
            )
        if first_ts is not None and last_ts > first_ts:
            tracing.record_span(
                "engine.decode", trace_id=tid, parent_span_id=root,
                start=first_ts, end=last_ts, kind="engine",
                attrs={"tokens": decode_tokens},
            )

    def _trace_ids_locked(self, batch) -> list[str]:
        """Trace ids of the traced requests in a step's batch (bounded),
        so a flight-recorder post-mortem links a slow step straight to
        the fleet traces that rode it. Empty for untraced traffic."""
        out = []
        for r in batch:
            if r.trace_ctx:
                out.append(r.trace_ctx["trace_id"])
                if len(out) >= 8:
                    break
        return out

    def _flight_record_locked(self, kind: str, t_wall: float, dt: float,
                              **fields) -> None:
        """One ring-buffer record per work step. O(1): a handful of int
        reads and one bounded deque append — no device access."""
        cs = self.cache.stats
        rec = {
            "kind": kind,
            "ts": round(t_wall, 6),
            "dur_ms": round(dt * 1000.0, 3),
            "admitted": self._step_admitted,
            "expired": self._step_expired,
            "cow": cs.cow_copies - self._flight_prev["cow"],
            "evicted_blocks": (
                cs.prefix_evicted_blocks - self._flight_prev["evict"]
            ),
            "kv_util": round(self.cache.utilization, 4),
            "waiting": len(self._waiting),
            "prefilling": len(self._prefilling),
            "running": len(self._running),
            # host-tier view: absolute occupancy + per-step spill churn,
            # so a post-mortem dump shows BOTH cache tiers per step
            "host_blocks": (
                0 if self.cache.host_tier is None
                else self.cache.host_tier.blocks
            ),
            "host_bytes": (
                0 if self.cache.host_tier is None
                else self.cache.host_tier.nbytes
            ),
            "demotions": cs.demoted_blocks - self._flight_prev["demote"],
            "promotions": cs.promoted_blocks - self._flight_prev["promote"],
        }
        rec.update(fields)
        if not rec.get("trace_ids"):
            rec.pop("trace_ids", None)  # untraced steps stay compact
        if self._last_sync is not None:
            # the step that PAID for a host sync carries its cost + lag
            rec.update(self._last_sync)
            self._last_sync = None
        # where a long step's time went that ``sync_ms`` does not say:
        # what the stepping thread waited for this lock, what the
        # collector held the process, and what the thread itself ran,
        # each since the record before this one
        host = (self._lock_wait_s, obs.gc_watch.total_seconds(),
                obs.thread_cpu())
        rec["lock_ms"], rec["gc_ms"], rec["cpu_ms"] = (
            round((now - prev) * 1000.0, 3)
            for now, prev in zip(host, self._flight_host_prev))
        if self._flight_thread != threading.get_ident():
            # the record before was another thread's (or there is none):
            # its CPU clock is no base for this thread's
            self._flight_thread = threading.get_ident()
            rec["cpu_ms"] = 0.0
        self._flight_host_prev = host
        self._flight_prev["cow"] = cs.cow_copies
        self._flight_prev["evict"] = cs.prefix_evicted_blocks
        self._flight_prev["demote"] = cs.demoted_blocks
        self._flight_prev["promote"] = cs.promoted_blocks
        self._flight.record(rec)

    def _on_new_signature(self, sig: tuple) -> dict:
        """DecodeFns hook: a shape this engine has not run before — i.e.
        a compile event (programs are process-shared; this counts first
        use per engine). Tagged by shape key; also marked in the flight
        ring so a latency spike next to a compile explains itself. The
        mark is made BEFORE the call (a watchdog's dump of a step wedged
        in a compile holds it) and handed back: ``DecodeFns`` writes
        ``ms``, what the first call took, into it as the call returns."""
        key = obs.shape_key(sig)
        self._m_compile.inc(tags={"shape": key})
        rec = {"kind": "compile", "ts": obs.wall(), "shape": key}
        self._flight.record(rec)
        return rec

    def _dump(self, reason: str, *, path: str | None = None,
              lock_free: bool = False) -> str | None:
        """Write the flight recorder to disk. ``lock_free=True`` is the
        watchdog path: the wedged stepper may hold the lock, so only
        lock-free state goes in (the ring snapshot is GIL-atomic)."""
        extra: dict = {}
        if not lock_free:
            extra["stats"] = self.stats()
            extra["cache"] = self.cache.debug_snapshot()
        if self._last_snapshot is not None:
            # plain-attribute read: safe on the lock-free watchdog path
            extra["autoscaling_snapshot"] = self._last_snapshot
        out = obs.write_dump(
            self._flight.dump(reason, extra=extra),
            dir=self.cfg.flight_recorder_dir, path=path,
        )
        if out is not None:
            logger.warning(
                "llm engine flight recorder (%s) dumped to %s", reason, out
            )
        return out

    # ---------------- failure handling ----------------

    def _fail_engine(self, e: BaseException) -> None:
        """A step raised: fail closed. Every in-flight stream gets an
        EngineDiedError (= ActorError, so handles fail over exactly as on
        replica death) and the cache is reset best-effort."""
        if isinstance(e, EngineDiedError):
            err = e
        else:
            err = EngineDiedError(f"engine step failed: {e!r}")
            err.__cause__ = e
        with self._lock:
            self._failed = err
            if not self._dumped:
                self._dumped = True
                self._dump("engine_died")
            self._fan_out_failure(err)
        # the controller will replace this replica as soon as
        # check_health() runs — push the post-mortem spans out NOW or
        # they die in the task-event buffer with the worker
        self._flush_task_events()

    @staticmethod
    def _flush_task_events() -> None:
        from ray_tpu._private.worker import global_worker_or_none

        try:
            w = global_worker_or_none()
            if w is not None and getattr(w, "task_events", None) is not None:
                w.task_events.flush()
        except Exception as e:  # noqa: BLE001 — never fail the failure path
            logger.warning("task-event flush on engine failure: %r", e)

    def _fan_out_failure(self, err: EngineDiedError) -> None:
        for r in (list(self._waiting) + self._prefilling + self._running
                  + self._preempted):
            if not r.done:
                r.done = True
                self._finish_obs_locked(r, "failed")
                r.out.put(err)
                r.out.put(_DONE)
        self._waiting.clear()
        self._waiting_blocks = 0
        self._prefilling = []
        self._running = []
        self._preempted = []
        self._m_preempted_streams.set(0)
        self._inflight.clear()  # in-flight steps die with the engine
        self.cache.release_all()

    # ---------------- background stepping ----------------

    def _ensure_thread(self) -> None:
        with self._lock:
            if self._stopped or self._failed is not None:
                return
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._loop, name="llm-engine-step", daemon=True
                )
                self._thread.start()
            if self._watchdog is None and self.cfg.step_timeout_s:
                self._watchdog = threading.Thread(
                    target=self._watchdog_loop,
                    name="llm-engine-watchdog",
                    daemon=True,
                )
                self._watchdog.start()

    def _loop(self) -> None:
        """The stepping thread. It is under a span all the time: a step's
        phases; where nothing is to be done the wait for work
        (``engine.wait``, inside the step's hold: the condition lets the
        lock go); and between two steps ``engine.lock``, opened where a
        step's last phase ended and closed where the next step has the
        lock. Inside it the loop books the step, lets the lock go, makes
        its stop check in a hold of its own and asks again: the same
        holds as ever, so a client waiting in ``submit`` meets the lock
        as often as it did. What that span's seconds are made of is a
        client or a reader holding the lock, or another thread holding
        the interpreter."""
        turn = obs.phase(self._host_spans, "engine.lock")
        turn.__enter__()
        error = None
        while error is None:
            with self._lock:
                if self._stopped:
                    break
            if self._failed is not None:
                break
            self._lock.acquire()
            turn.__exit__(None, None, None)
            self._lock_wait_s += turn.seconds
            try:
                if not self._step_locked() and not (
                    self._stopped
                    or self._waiting
                    or self._prefilling
                    or self._running
                    or self._preempted
                ):
                    with self._phase("engine.wait"):
                        self._work.wait(timeout=0.05)
            except Exception as e:  # noqa: BLE001 — fail closed, fan out
                error = e
            finally:
                turn = obs.phase(self._host_spans, "engine.lock")
                turn.__enter__()
                self._fold_phases_locked()
                self._lock.release()
        turn.__exit__(None, None, None)
        if error is not None:
            self._fail_engine(error)

    def _watchdog_loop(self) -> None:
        """Detect a wedged step. Deliberately LOCK-FREE: the failure mode
        is a jitted call stuck while holding the scheduler lock, so the
        watchdog reads ``_step_begin`` as a plain attribute and fans the
        failure out through the (thread-safe) per-request queues. The
        wedged thread still holds the lock; clients stop waiting anyway
        and the controller replaces the replica via check_health()."""
        timeout = self.cfg.step_timeout_s
        poll = max(0.005, min(0.05, timeout / 10.0))
        while not self._stopped and self._failed is None:
            begin = self._step_begin
            if begin is not None and obs.clock() - begin > timeout:
                err = EngineDiedError(
                    f"engine step wedged for > {timeout}s; "
                    "failing all in-flight streams"
                )
                self._failed = err
                if not self._dumped:
                    # lock-free by design (the wedged stepper may hold the
                    # lock): ring snapshot only, no stats()
                    self._dumped = True
                    self._dump("watchdog_timeout", lock_free=True)
                for r in (
                    list(self._waiting) + self._prefilling + self._running
                    + self._preempted
                ):
                    if not r.done:
                        r.done = True
                        r.out.put(err)
                        r.out.put(_DONE)
                self._flush_task_events()
                return
            time.sleep(poll)
