"""Paged KV-cache manager: preallocated block pool + per-sequence tables
+ block-granular prefix cache (content-addressed blocks, COW, LRU evict).

vLLM-style paging (PAPERS.md: serving Gemma on Cloud TPU uses the same
structure): the cache is ONE preallocated array pair per model, a token's
heads one lane-dense row, whatever their count and size —

    k, v: [n_layer, num_blocks, block_size, n_kv_head * head_dim]

(ops/paged_attention.py ``pool_shape``: the order such a pool rests in on
the chip is the order written, the step programs read and write it where
it stands, and a block of it is the tile the kernel multiplies as it
lies; a ``tp`` mesh splits the row into contiguous heads a device).
The stored shape stays on the device: what leaves the pool for the host
(export, the host tier, the RTKV wire) is by heads, byte for byte. Sequences
own logical-position-ordered lists of physical block ids.
Fragmentation-free growth (append one block at a time), O(1) free, and
blocks returned on sequence completion are immediately reusable, so the
steady-state footprint is set by CONCURRENT tokens, not total traffic.

Block 0 is reserved as the garbage sink: padding rows and masked writes
are redirected there (ops/kv_cache.py), which keeps every jitted scatter
shape-static. The allocator therefore hands out blocks [1, num_blocks).

Admission control is reservation-based: the engine reserves a sequence's
WORST-CASE block count (prompt + max_new_tokens) before prefill, so a
running sequence can never fail a mid-flight append — the simple analog of
vLLM's preemption machinery, traded for a little capacity headroom
(docs/SERVING_LLM.md discusses the trade).

Prefix caching (the SGLang RadixAttention idea at block granularity):
every FULL prompt block is content-addressed by the chain hash of all
token ids up to and including it, so a new request whose prompt shares a
prefix with earlier traffic maps the shared blocks into its table instead
of recomputing their K/V. A block is then in one of three states:

  free        in ``_free``          — no meaningful content
  referenced  refcount >= 1         — mapped by one or more live tables
  cached      in ``_lru``           — refcount 0 but content-addressed;
                                      resurrectable by a future hit,
                                      evicted LRU when ``_free`` runs dry

Writes never land in a content-addressed or shared block: ``prepare_write``
redirects them copy-on-write onto a fresh private block (the device-side
clone is ``ops.kv_cache.copy_blocks``). Reservations draw uniformly from
hits, appends and COW copies, so the no-mid-flight-failure invariant is
unchanged; ``release_all`` also drops the content-addressed set, keeping
engine create/shutdown cycles leak-free.

Per-sequence state slots (``state_slots > 0``): a family whose layers keep
state of FIXED size per running sequence (a short convolution's last rows;
models/lfm2_moe.py) holds it beside the pool, in ``state`` — a pytree the
family builds, with a slot axis. The manager hands out the slot ids as it
hands out blocks: ``allocate`` takes a slot with the sequence's table,
``free`` / ``release_all`` return it, exactly once; slot 0 is the garbage
sink of padding rows, as block 0 is. A freed slot is reusable at once, with
no quarantine: every program that touches ``state`` takes the array the
last one returned, so a step still in flight is ordered before the reuse,
and a sequence's first chunk starts from zeros whatever its slot held.
``prefix_reuse=False`` turns the prefix cache's LOOKUPS off for such a
family (a hit would need the state as it stood at the block boundary):
``peek_prefix`` answers as a miss and nothing is content-addressed. A
family whose ``state`` holds only counters (decode.py ``Family.state_rows``
False) keeps no row a sequence and is given no slots: ``state`` rides the
step programs all the same, and what it is handed as ``slots`` only tells
a step's real rows (1) from its padding (0).

A third plane by block id (``block_state_bytes > 0``): a family whose
attention SELECTS its pages (models/minicpm_sala.py) keeps, for every
block of K, a few rows of compressed keys (ops/sparse_select.py: the sums
of the block's segments, ``segments`` rows of ``n_kv_head x head_dim``
float32 a layer). They live in the family's ``state`` under the SAME block
ids as K and V: written by the step that writes the block's tokens, read
through the same table, and given back with the block because nothing but
its id names them (a segment's first token SETS its row, so a reused block
needs no clearing and no quarantine beyond the block's own). The manager
only counts them (``debug_snapshot()["compressed_key_bytes"]``).

Tables by group (``groups``): a family whose layers do not all keep the
same tokens (models/laguna.py: full layers keep every one, sliding layers
the last ``window``) names GROUPS of layers, each of ``n_layer`` layers, so
that a block id means ``n_layer`` slots whatever its group and all groups
draw on this ONE pool, one free list, one ``num_blocks``. A sequence then
holds one table a group, ``block_table`` gives ``[G, pad_to]``, and
``free_behind`` returns a windowed group's blocks that lie wholly behind
the window of every query still to come; their table entries become block
0, which the windowed kernel never copies (its walk starts at the page of
the window's floor). Memory moves between the kinds by demand: nothing is
split for a user to size. A reservation stays its sequence's own for its
whole life (``free_behind`` credits it back, the next block draws on it
again): ``KVCacheConfig.request_blocks`` is what a request needs reserved,
``prefill_room`` what the engine sets aside once for the rows of a prefill
step, whose chunk is written before the blocks behind it go back. Groups
carry neither the prefix cache nor the host tier (``prefix_reuse`` is off:
a hit would need the sliding groups' blocks at the hit's boundary, which
were freed); the engine refuses what else cannot carry them.

A ring and a table of slots (groups of kind ``("ring", W)`` and
``("slots", C)``, models/evabyte.py): a family whose EVERY layer reads two
tables. The ring holds the exact K/V of the sequence's current window of
``W`` positions: ``W / block_size`` blocks, position ``t`` at ``t mod W``,
reused in place when a window closes, so it grows to a window and stays.
The slot table holds one slot a chunk of ``C`` positions (a chunk's
summary has a token's shape), chunk ``c`` at slot ``c``: it grows a block
every ``C x block_size`` positions. Both draw on the one pool and free
list; the pool's layer axis spans all the model's layers and both tables
index it alike. A step gets them COMPOSED (``block_table(.., pos=)``): row
0 is ``[the slot table's blocks for the chunks of closed windows | the
ring]``, the table a query at ``pos`` attends causally, and row 1 the slot
table, where the step writes the summaries of the chunks it completes.
``request_blocks`` is ``min(ceil(n / block_size), W / block_size) +
ceil(ceil(n / C) / block_size)``. Such groups carry no prefix cache and no
host tier either, and the engine refuses what else cannot carry them.

A pool in planes (``planes``): a family that caches of a token not K and V
by head but ONE row that every head reads (latent attention,
models/pangu_ultra_moe.py: a 512-wide latent vector, key and value at
once, and the key's 64-wide rotary rest) says so in ONE description,
``(name, width, stored width)`` a plane, that the family's config owns
(``kv_planes``) and this manager, ``ops/kv_cache.py write_kv``, the
executor's report and the refusals read. The parts of a row rest side by
side in ONE plane, and the step programs carry and donate ONE array,

    k: [n_layer, num_blocks, block_size, the parts' stored widths]
    v: None

a token's row ``[c | k_rope]`` with each part at whole lanes of 128 (the
rotary 64 as 128, zeros behind: ops/paged_attention.py ``latent_row``), so
a page is whole tiles, rests as written and is ONE contiguous copy of the
kernel's where it stands, read there by lane-aligned views (two planes
were two copies a page, and starting a copy is what bound the kernel: PR
53); ``row_bytes`` says what the layer's mathematics needs, 1,152 B,
``stored_row_bytes`` what the pool holds, 1,280 B: +11%). One
table, every layer keeps every token: blocks, reservations, the prefix
cache, copy-on-write and preemption are what they are for K and V by head,
since a block's bytes are all they touch, and a prompt may be split over
the rows of one prefill step as theirs may (``one_table``: a step's rows
are written to the pool before the kernel reads them back through the
table). The host tier and the RTKV
record describe a block as ``n_kv_head x head_dim`` twice and cannot say
"planes" yet: the engine refuses them for such a family.

Host-memory tier (``host_cache_bytes > 0``): LRU eviction DEMOTES a full
prefix block into a pinned host-side arena instead of discarding it —
the plasma spill model from the Ray object store, applied to KV. Each
arena entry is one RTKV v1 per-block record (kv_transfer.py): chain
digest + content digest + the raw k||v payload, so promotion re-verifies
bytes before they ever touch the device pool. ``peek_prefix`` /
``assign_prefix`` consult the arena after a device miss and PROMOTE hits
back: the block is claimed like an append (same reservation accounting)
and its payload is queued; the engine drains the queue as ONE fused
``land_blocks`` scatter per step through the executor seam — no new sync
points, no new compile kinds. This module stays device-free: the
device->host capture at demote time goes through ``demote_fn`` (the
engine installs ``executor.export_blocks``, the allowlisted
``_host_blocks`` funnel), and promotion payloads are plain numpy.
"""
from __future__ import annotations

import hashlib
import logging
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any

import numpy as np

logger = logging.getLogger("ray_tpu.serve.llm")


def _block_key(prev: bytes, block_tokens) -> bytes:
    """Chain hash for one full block: digest of (parent digest, the
    block's token ids). Identifying a block by the chain rather than its
    own tokens makes equal-content blocks at different prompt offsets
    distinct — a hit therefore always means 'same tokens from position
    0', never a mid-prompt coincidence."""
    h = hashlib.blake2b(prev, digest_size=16)
    h.update(np.asarray(block_tokens, np.int64).tobytes())
    return h.digest()


def group_kind(window) -> str:
    """The kind of table a group's first entry names: None, every token is
    kept (``full``); an int, the last ``window`` (``sliding``); ``("ring",
    W)`` and ``("slots", C)``, see the module docstring."""
    if window is None:
        return "full"
    return "sliding" if isinstance(window, int) else window[0]


def is_composed(groups) -> bool:
    """Whether ``groups`` hold a ring or a slot table: a step's table is
    then composed of the two."""
    return any(group_kind(window) in ("ring", "slots")
               for window, _ in groups)


def describe_group(window, layers) -> dict:
    """A group for a report: its kind, its window (a sliding group's, a
    ring's; None otherwise), a slot table's positions a slot, its layers."""
    kind = group_kind(window)
    out = {"window": None, "layers": list(layers), "kind": kind}
    if kind == "sliding":
        out["window"] = window
    elif kind == "ring":
        out["window"] = window[1]
    elif kind == "slots":
        out["every"] = window[1]
    return out


@dataclass(frozen=True)
class KVCacheConfig:
    n_layer: int
    n_kv_head: int
    head_dim: int
    num_blocks: int = 64
    block_size: int = 16
    dtype: Any = None  # jnp dtype; None -> bfloat16
    # Host-memory cache tier capacity. 0 disables the tier: LRU eviction
    # discards content exactly as before. When > 0, evicted prefix blocks
    # demote into a host arena of at most this many bytes (RTKV wire
    # size, so header + digests count against the cap — and a quantized
    # pool's 2-4x smaller records buy proportionally more entries).
    host_cache_bytes: int = 0
    # "int8" | "fp8" | None: store the pool quantized with per-(token,
    # head) scale planes (ops/quantization.QuantizedKV). Static — set
    # once at engine build (EngineConfig.quantization); dtype is then
    # the scale/compute reference dtype and the pool data dtype comes
    # from the kind.
    quantization: str | None = None
    # Slots of per-sequence state beside the pool, slot 0 (the garbage
    # sink) included; 0: the family keeps no ROW a sequence (counters its
    # step programs keep in ``state`` ask for none). See the module
    # docstring.
    state_slots: int = 0
    # Bytes the family's ``state`` holds a BLOCK ID beside K and V (the
    # compressed keys of a layer that selects its pages: a third plane
    # addressed by the ids this manager hands out, so it needs no
    # allocator of its own and a block's rows go back with the block). 0:
    # nothing. Only counted here (``debug_snapshot``); the array is the
    # family's (decode.py ``Family.block_state_bytes``).
    block_state_bytes: int = 0
    # False: no block is content-addressed and every prefix lookup misses
    # (a family whose recurrent state a mapped block would not restore).
    prefix_reuse: bool = True
    # Groups of layers, each with a table of its own: ``(window, layers)``
    # a group, ``window`` None for layers that keep every token, ``layers``
    # the model's layer indices (for reports). Empty: one table for all
    # layers, as ever. See the module docstring.
    groups: tuple = ()
    # A pool in planes: ``(name, width, stored width)`` of each PART of a
    # row of the one array, in the row's order, from the family's
    # ``kv_planes``. Empty: K and V by head, ``n_kv_head x head_dim``
    # each. See the module docstring.
    planes: tuple = ()

    def __post_init__(self):
        if self.planes and (
                self.quantization is not None
                or self.host_cache_bytes or self.groups):
            raise ValueError(
                "a pool in planes is the step programs' ONE array, plain, "
                "under one table and without a host tier; got "
                f"planes={self.planes}, quantization={self.quantization}, "
                f"host_cache_bytes={self.host_cache_bytes}, "
                f"groups={self.groups}")
        if not self.composed:
            return
        kinds = [group_kind(window) for window, _ in self.groups]
        if kinds != ["ring", "slots"]:
            raise ValueError(
                f"a composed table is one ring and one slot table, in that "
                f"order; the groups are {kinds}")
        W, C = self.window_chunk
        if W % self.block_size or (W // C) % self.block_size:
            raise ValueError(
                f"block_size {self.block_size} must divide the window "
                f"({W}) and its {W // C} chunks: the composed table joins "
                f"whole blocks of summaries to whole blocks of the ring")

    @property
    def composed(self) -> bool:
        """Whether a step's table is composed of a ring and a slot table."""
        return is_composed(self.groups)

    @property
    def one_table(self) -> bool:
        """Whether all a sequence carries from token to token is pages
        under ONE table, written before they are read, by position. Only
        then may the scheduler split a sequence over ROWS of one prefill
        step, each row at its true positions under the same table
        (engine.py "a prefill step is filled by tokens"). ``why_not_split``
        gives the reason where it may not."""
        return self.why_not_split is None

    @property
    def why_not_split(self) -> str | None:
        """Why a sequence may NOT be split over rows of one prefill step
        (None: it may). K and V by head, plain or quantized, and a pool in
        planes are pages under one table and nothing else; a family whose
        ``state`` holds counters alone asks for no slots and is not held
        back by them."""
        if self.composed:
            return ("a ring and a slot table, composed into a step's "
                    "table by position")
        if self.groups:
            return ("tables by group: a window's blocks go back behind a "
                    "position")
        if self.state_slots:
            return ("state rows beside the pool: a piece's short convolution "
                    "and its matrix state need the piece before it")
        return None

    @property
    def window_chunk(self) -> tuple[int, int] | None:
        """A composed cache's ``(W, C)``: the ring's window and the slot
        table's positions a slot; None for any other."""
        if not self.composed:
            return None
        return self.groups[0][0][1], self.groups[1][0][1]

    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - 1  # block 0 is the garbage sink

    def _itemsize(self) -> int:
        if self.quantization is not None:
            return 1
        return 2 if self.dtype is None else np.dtype(self.dtype).itemsize

    @property
    def row_bytes(self) -> int:
        """Bytes of pool data a token costs ONE layer by the widths of
        what is cached (scale planes of a quantized pool apart): K and V
        by head, or the widths of a row's parts."""
        widths = (sum(width for _, width, _ in self.planes) if self.planes
                  else 2 * self.n_kv_head * self.head_dim)
        return widths * self._itemsize()

    @property
    def stored_row_bytes(self) -> int:
        """... and as the pool STORES it: a plane at whole lanes."""
        if not self.planes:
            return self.row_bytes
        return sum(stored for _, _, stored in self.planes) * self._itemsize()

    @property
    def block_bytes(self) -> int:
        """What one block id holds across the pool's layers, as stored."""
        return self.block_size * self.n_layer * self.stored_row_bytes

    def describe_pool(self) -> dict:
        """What a token's row in the pool is, for ``describe()`` and
        ``stats()``: its kind, the ONE plane of a latent family (its row's
        parts inside it), the copies a page costs the kernel, and the
        bytes above."""
        out = {"kind": "latent" if self.planes else "heads",
               "row_bytes": self.row_bytes,
               "stored_row_bytes": self.stored_row_bytes,
               "block_bytes": self.block_bytes,
               # copies the attention kernel starts a page: one array's
               # page, or K's and V's
               "page_copies": 1 if self.planes else 2}
        if self.planes:
            # ONE plane, a row's parts side by side in it
            names, widths, stored = zip(*self.planes)
            out["planes"] = [{
                "name": "+".join(names), "width": sum(widths),
                "stored_width": sum(stored),
                "parts": [{"name": name, "width": width,
                           "stored_width": at}
                          for name, width, at in self.planes]}]
        return out

    def blocks_for(self, num_tokens: int) -> int:
        return -(-num_tokens // self.block_size)  # ceil

    def window_blocks(self, window: int) -> int:
        """The most blocks a windowed group holds for a row between steps:
        ``window`` tokens at any offset in their blocks, and one block of
        slack."""
        return self.blocks_for(window) + 2

    def table_blocks(self, window, num_tokens: int) -> int:
        """Entries the table of a group ``window`` names has once its
        sequence holds ``num_tokens`` (a sliding group's count from
        position 0; ``free_behind`` says which are still held)."""
        kind = group_kind(window)
        if kind == "ring":
            return self.blocks_for(min(num_tokens, window[1]))
        if kind == "slots":
            return self.blocks_for(-(-num_tokens // window[1]))
        return self.blocks_for(num_tokens)

    def request_blocks(self, num_tokens: int) -> int:
        """What admission reserves for a request that may reach
        ``num_tokens``: every block of each group that keeps all tokens,
        of a windowed group what a decoding row holds, a ring's window and
        a slot table's slots."""
        full = self.blocks_for(num_tokens)
        if not self.groups:
            return full
        return sum(
            min(full, self.window_blocks(window))
            if group_kind(window) == "sliding"
            else self.table_blocks(window, num_tokens)
            for window, _ in self.groups)

    def prefill_room(self, rows: int, chunk_tokens: int) -> int:
        """What the engine reserves ONCE for the rows of a prefill step: a
        windowed group holds a step's whole chunk until the step is
        written, ``blocks_for(chunk)`` past what the row reserved."""
        return rows * self.blocks_for(chunk_tokens) * sum(
            group_kind(window) == "sliding" for window, _ in self.groups)

    def composed_blocks(self, num_tokens: int) -> int:
        """The width of the composed table of a query at ``num_tokens -
        1``: the slot table's blocks for the chunks of closed windows, then
        the ring."""
        W, C = self.window_chunk
        return (W // C // self.block_size) * ((num_tokens - 1) // W) \
            + self.table_blocks(self.groups[0][0], num_tokens)


@dataclass
class CacheStats:
    high_water_blocks: int = 0
    allocated_total: int = 0
    freed_total: int = 0
    prefix_hit_blocks: int = 0
    prefix_hit_tokens: int = 0
    prefix_evicted_blocks: int = 0
    cow_copies: int = 0
    adopted_blocks: int = 0  # handoff blocks landed from another replica
    demoted_blocks: int = 0      # device blocks spilled into the host tier
    promoted_blocks: int = 0     # host-tier hits claimed back into the pool
    host_evicted_blocks: int = 0  # arena entries dropped to fit the byte cap
    promotion_drops: int = 0     # queued promotions invalidated before landing
    demote_drops: int = 0        # demote captures that failed (content lost)
    host_corrupt_drops: int = 0  # arena entries failing RTKV verification
    state_slots_high_water: int = 0  # most state slots held at once
    # windowed groups: blocks taken, and blocks given back behind the
    # window while their sequence lived
    window_blocks_taken: int = 0
    window_blocks_freed: int = 0
    tables: dict = field(default_factory=dict)


class HostKVTier:
    """Pinned host-memory arena for demoted prefix blocks.

    Pure container: an LRU ``OrderedDict`` keyed by chain digest whose
    values are RTKV v1 wire payloads (kv_transfer.pack_blocks with exactly
    one record), byte-capacity-capped. Packing on the way in and
    unpacking on the way out reuses the transfer module's content
    addressing verbatim, so a bit flipped while a block sat in host RAM
    fails the content digest at promote time instead of corrupting the
    device pool. No device access, no policy — PagedKVCache owns when to
    demote, promote and verify.
    """

    def __init__(self, capacity_bytes: int, layout) -> None:
        self.capacity_bytes = int(capacity_bytes)
        self.layout = layout  # kv_transfer.KVLayout of the device pool
        self._wire: OrderedDict[bytes, bytes] = OrderedDict()
        self._nbytes = 0

    def __contains__(self, digest: bytes) -> bool:
        return digest in self._wire

    @property
    def blocks(self) -> int:
        return len(self._wire)

    @property
    def nbytes(self) -> int:
        return self._nbytes

    def digests(self):
        """Resident chain digests, most-recently-used first."""
        return reversed(self._wire)

    def touch(self, digest: bytes) -> None:
        if digest in self._wire:
            self._wire.move_to_end(digest)

    def put(self, digest: bytes, k_block, v_block) -> tuple[bool, int]:
        """Store one demoted block; -> (stored, arena entries evicted to
        make room). A payload larger than the whole cap is refused; a
        digest already resident is refreshed, not re-packed."""
        from ray_tpu.serve.llm import kv_transfer

        if digest in self._wire:
            self._wire.move_to_end(digest)
            return True, 0
        wire = kv_transfer.pack_blocks(
            self.layout, [(digest, k_block, v_block)], prefix_tokens=0
        )
        if len(wire) > self.capacity_bytes:
            return False, 0
        evicted = 0
        while self._nbytes + len(wire) > self.capacity_bytes:
            _, old = self._wire.popitem(last=False)  # oldest first
            self._nbytes -= len(old)
            evicted += 1
        self._wire[digest] = wire
        self._nbytes += len(wire)
        return True, evicted

    def get(self, digest: bytes):
        """Unpack + verify one entry; -> (k_block, v_block) numpy arrays.
        Raises kv_transfer.KVTransferError on any corruption — the caller
        must treat that as a miss and ``discard`` the entry."""
        from ray_tpu.serve.llm import kv_transfer

        wire = self._wire[digest]
        # expect= turns a layout/quantization mismatch into a loud,
        # field-naming error instead of an opaque digest failure.
        _, _, records = kv_transfer.unpack_blocks(wire, expect=self.layout)
        chain, k_block, v_block = records[0]
        if chain != digest:
            raise kv_transfer.KVTransferError(
                "host-tier entry chain digest mismatch"
            )
        self._wire.move_to_end(digest)
        return k_block, v_block

    def discard(self, digest: bytes) -> None:
        wire = self._wire.pop(digest, None)
        if wire is not None:
            self._nbytes -= len(wire)

    def clear(self) -> None:
        self._wire.clear()
        self._nbytes = 0


class PagedKVCache:
    """Host-side block accounting + the device cache arrays.

    Not thread-safe by itself — the engine serializes all access under its
    scheduler lock (one stepper at a time).
    """

    def __init__(self, cfg: KVCacheConfig, state=None):
        import jax.numpy as jnp

        self.cfg = cfg
        # the family's per-sequence state arrays (device side, like k / v;
        # the executor passes them through its steps) and their slot ids
        self.state = state
        self._free_slots: list[int] = list(range(cfg.state_slots - 1, 0, -1))
        self._slots: dict[Any, int] = {}
        dtype = cfg.dtype if cfg.dtype is not None else jnp.bfloat16
        shape = self.pool_shape()
        scales = shape[:3] + (cfg.n_kv_head,)
        if cfg.planes:
            # ONE array, a token's row for all heads (its parts side by
            # side at their stored widths); no second pool
            self.k, self.v = jnp.zeros(
                shape[:3] + (sum(at for _, _, at in cfg.planes),),
                dtype), None
        elif cfg.quantization is not None:
            from ray_tpu.ops.quantization import (
                QuantizedKV,
                quant_dtype,
                resolve_quantization,
            )

            kind = resolve_quantization(cfg.quantization)
            qdt = quant_dtype(kind)
            # data in the kind's storage dtype + per-(slot, head) f32
            # scale planes — write_kv quantizes at exactly this
            # granularity, so appends never re-quantize a block.
            self.k = QuantizedKV(
                jnp.zeros(shape, qdt), jnp.zeros(scales, jnp.float32)
            )
            self.v = QuantizedKV(
                jnp.zeros(shape, qdt), jnp.zeros(scales, jnp.float32)
            )
        else:
            self.k = jnp.zeros(shape, dtype)
            self.v = jnp.zeros(shape, dtype)
        # LIFO free list: a just-freed (cache-warm) block is reused first
        self._free: list[int] = list(range(1, cfg.num_blocks))
        # Lag-aware release (dispatch-ahead decode): blocks freed while a
        # device step is still in flight park here instead of the free
        # list, so they cannot be handed to a new allocation until the
        # engine's next token sync PROVES the in-flight step (and any
        # speculative write it carries) has executed. flush_quarantine()
        # moves them to the free list at that sync. Each entry is
        # ``(fence, block)``: the engine numbers its step programs as it
        # launches them and may have TWO in flight, so a block freed then
        # waits for the sync of the NEWER one (``free(fence=)``), not for
        # whichever sync comes next. Fences never decrease down the list.
        self._quarantine: list[tuple[int, int]] = []
        # group 0's table (the only one without ``cfg.groups``): what every
        # path below that knows nothing of groups reads and writes
        self._tables: dict[Any, list[int]] = {}
        # the other groups' tables, in group order; a table is indexed by
        # logical block, and a windowed group's entries behind ``_floor``
        # (one floor a group) are 0: given back
        self._more: dict[Any, list[list[int]]] = {}
        self._floor: dict[Any, list[int]] = {}
        # each group's window (None: it keeps every token); one table
        # that keeps everything where no group is named
        self._windows = [window for window, _ in cfg.groups] or [None]
        self._group_held = [0] * len(self._windows)
        self._group_high = [0] * len(self._windows)
        self._reserved = 0
        # prefix cache state
        self._ref: dict[int, int] = {}            # block -> live references
        self._lru: OrderedDict[int, None] = OrderedDict()  # refcount-0 cached
        self._hash_to_block: dict[bytes, int] = {}
        self._block_hash: dict[int, bytes] = {}
        # seq -> (chain digest so far, number of blocks hashed into it)
        self._chain: dict[Any, tuple[bytes, int]] = {}
        # bumped whenever a sequence's table CONTENT changes (append / COW /
        # prefix mapping) — lets the engine cache host-side numpy tables
        self._versions: dict[Any, int] = {}
        # --- host tier (plasma-style spill of evicted prefix blocks) ---
        # The engine installs the device->host capture funnel after it
        # builds the executor (``cache.demote_fn = executor.export_blocks``);
        # until then — and whenever the tier is disabled — eviction
        # discards content exactly as before.
        self.demote_fn = None
        if cfg.host_cache_bytes > 0:
            from ray_tpu.serve.llm import kv_transfer

            self.host_tier = HostKVTier(
                cfg.host_cache_bytes,
                kv_transfer.KVLayout(
                    n_layer=cfg.n_layer,
                    block_size=cfg.block_size,
                    n_kv_head=cfg.n_kv_head,
                    head_dim=cfg.head_dim,
                    dtype=self.k.dtype.name,
                    quantization=cfg.quantization,
                ),
            )
        else:
            self.host_tier = None
        # Promotions staged by assign_prefix, drained by the engine as ONE
        # fused land_blocks scatter at the top of the next dispatch window:
        # (chain digest, block id, k payload, v payload).
        self._pending_promotions: list[tuple[bytes, int, Any, Any]] = []
        # Blocks claimed for promotion whose payload has NOT landed on
        # device yet. Such a block must never be demote-exported (the
        # device content is still garbage); its bytes are safe — the host
        # tier keeps the entry through promotion precisely so eviction
        # before landing loses nothing.
        self._unlanded: set[int] = set()
        self.stats = CacheStats()

    # ---------------- the pools' stored shape ----------------

    def pool_shape(self) -> tuple[int, ...]:
        """The shape ``k`` / ``v`` (a quantized pool's data) are stored in:
        lane-dense (ops/paged_attention.py ``pool_shape``)."""
        from ray_tpu.ops.paged_attention import pool_shape

        cfg = self.cfg
        return pool_shape(cfg.n_layer, cfg.num_blocks, cfg.block_size,
                          cfg.n_kv_head, cfg.head_dim)

    # ---------------- reservation (admission control) ----------------

    @property
    def available_blocks(self) -> int:
        """Blocks an admission may claim: truly free + evictable cached."""
        return len(self._free) + len(self._lru)

    @property
    def spare_blocks(self) -> int:
        """Claimable blocks beyond outstanding reservations — the most a
        handoff landing can adopt without live admissions immediately
        evicting the freshly-landed payloads back out of the pool."""
        return max(0, self.available_blocks - self._reserved)

    @property
    def reserved_blocks(self) -> int:
        """Outstanding admission reservations — the engine's preemption
        trigger and the autoscaling snapshot subtract these from
        ``available_blocks`` to get what a new admission can claim."""
        return self._reserved

    def can_reserve(self, n_blocks: int) -> bool:
        return n_blocks <= self.available_blocks - self._reserved

    def reserve(self, n_blocks: int) -> None:
        if not self.can_reserve(n_blocks):
            raise RuntimeError(
                f"cannot reserve {n_blocks} blocks: "
                f"{self.available_blocks} available "
                f"({len(self._lru)} cached), {self._reserved} already reserved"
            )
        self._reserved += n_blocks

    def release_reservation(self, n_blocks: int) -> None:
        self._reserved -= n_blocks
        assert self._reserved >= 0, "reservation accounting went negative"

    # ---------------- allocate / append / free ----------------

    @property
    def free_slots(self) -> int:
        """State slots an admission may take (0 without state slots)."""
        return len(self._free_slots)

    @property
    def used_slots(self) -> int:
        return len(self._slots)

    def slot(self, seq_id) -> int:
        """The sequence's state slot (its row of ``state``'s slot axis)."""
        return self._slots[seq_id]

    def allocate(self, seq_id) -> None:
        """Register a sequence with an empty block table and, where the
        family keeps state slots, take one for it."""
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id!r} already allocated")
        if self.cfg.state_slots:
            if not self._free_slots:
                raise RuntimeError(
                    "no free state slot: admission must check free_slots")
            self._slots[seq_id] = self._free_slots.pop()
            self.stats.state_slots_high_water = max(
                self.stats.state_slots_high_water, len(self._slots))
        self._tables[seq_id] = []
        if self.cfg.groups:
            self._more[seq_id] = [[] for _ in self.cfg.groups[1:]]
            self._floor[seq_id] = [0] * len(self.cfg.groups)
        self._chain[seq_id] = (b"", 0)
        self._versions[seq_id] = 0

    def _take_block(self, *, reserved: bool) -> int:
        """Claim one writable block: from the free list, else by evicting
        the LRU-oldest content-addressed block (its hash entry dies; with
        the host tier enabled its content demotes instead of dying)."""
        if self._free:
            b = self._free.pop()
        elif self._lru:
            b, _ = self._lru.popitem(last=False)  # oldest first
            h = self._block_hash.pop(b)
            del self._hash_to_block[h]
            self.stats.prefix_evicted_blocks += 1
            self._demote_evicted(h, b)
        else:
            raise RuntimeError(
                "KV block pool exhausted — reservation accounting bug"
            )
        if reserved:
            self._reserved -= 1
        self.stats.allocated_total += 1
        return b

    def ensure_capacity(self, seq_id, num_tokens: int, *, reserved=True) -> int:
        """Append blocks until the sequence can hold ``num_tokens``.
        Draws from this sequence's reservation when ``reserved``.
        Returns the number of blocks appended."""
        appended = 0
        for g, table in enumerate(self._group_tables(seq_id)):
            grown = 0
            want = self.cfg.table_blocks(self._windows[g], num_tokens)
            while len(table) < want:
                b = self._take_block(reserved=reserved)
                self._ref[b] = 1
                table.append(b)
                grown += 1
            if grown:
                self._group_held[g] += grown
                self._group_high[g] = max(
                    self._group_high[g], self._group_held[g])
                if group_kind(self._windows[g]) == "sliding":
                    self.stats.window_blocks_taken += grown
            appended += grown
        if appended:
            self._versions[seq_id] += 1
            self.stats.high_water_blocks = max(
                self.stats.high_water_blocks, self.used_blocks
            )
        return appended

    def _group_tables(self, seq_id) -> list[list[int]]:
        """The sequence's tables in group order (one without groups)."""
        return [self._tables[seq_id], *self._more.get(seq_id, ())]

    def free_behind(self, seq_id, next_pos: int) -> int:
        """Give back the blocks of the windowed groups that no query at
        ``next_pos`` or later can see: a query at ``q`` sees ``t > q -
        window``, so a block goes when its last position is at or below
        ``next_pos - window``. Its table entry becomes block 0. The
        sequence's reservation is credited with what went back (it is the
        sequence's own for its whole life; the block the frontier needs
        next draws on it again), so the caller lowers its count of drawn
        blocks by the result. No quarantine, as for a state slot: every
        program that touches the pool takes the buffer the last one
        returned (the pools are donated down the chain of steps), so a
        step in flight that still reads the block is ordered before
        whatever writes it next. -> blocks given back."""
        if not self.cfg.groups:
            return 0
        freed = 0
        floors = self._floor[seq_id]
        bs = self.cfg.block_size
        for g, window in enumerate(self._windows):
            # every decode step asks; a floor moves once a block
            if group_kind(window) != "sliding" \
                    or (next_pos - window + 1) // bs <= floors[g]:
                continue
            table = self._tables[seq_id] if g == 0 else \
                self._more[seq_id][g - 1]
            upto = min(len(table), (next_pos - window + 1) // bs)
            for i in range(floors[g], upto):
                self._deref(table[i])
                table[i] = 0
            if upto > floors[g]:
                self._group_held[g] -= upto - floors[g]
                freed += upto - floors[g]
                floors[g] = upto
        if freed:
            self._reserved += freed
            self._versions[seq_id] += 1
            self.stats.window_blocks_freed += freed
            self.stats.freed_total += freed
        return freed

    def group_report(self) -> list[dict]:
        """A group: its kind, window and layers (``describe_group``), the
        blocks live tables hold in it now and the most they ever held. []
        without groups."""
        return [
            dict(describe_group(window, layers),
                 blocks=self._group_held[g],
                 high_water_blocks=self._group_high[g])
            for g, (window, layers) in enumerate(self.cfg.groups)]

    def _deref(self, b: int, *, quarantine: bool = False,
               fence: int = 0) -> None:
        self._ref[b] -= 1
        if self._ref[b] == 0:
            del self._ref[b]
            if b in self._block_hash:
                # content survives, resurrectable until evicted. Never
                # quarantined: hashed blocks are full PROMPT blocks and
                # speculative decode writes land past the prompt (COW'd
                # onto private blocks by prepare_write), so no in-flight
                # step can scribble on them.
                self._lru[b] = None  # appended at the MRU end
            elif quarantine:
                self._quarantine.append((fence, b))
            else:
                self._free.append(b)

    def free(self, seq_id, *, quarantine: bool = False,
             fence: int = 0) -> int:
        """Drop a finished sequence's references; -> table length. Blocks
        it shared with live sequences stay put; sole-owned blocks return
        to the free list, except content-addressed ones, which park in the
        LRU set (still resurrectable by a future prefix hit).

        ``quarantine=True`` (the engine's dispatch-ahead path): sole-owned
        blocks park in the quarantine instead of the free list until
        ``flush_quarantine`` — see the field comment in ``__init__``.
        ``fence`` is the number of the newest step program in flight: the
        blocks stay parked until a flush ``upto`` that number or past it."""
        table = self._tables.pop(seq_id)
        self._chain.pop(seq_id, None)
        self._versions.pop(seq_id, None)
        if seq_id in self._slots:
            self._free_slots.append(self._slots.pop(seq_id))
        # a windowed group's entries behind its floor are 0: given back
        floors = self._floor.pop(seq_id, (0,))
        held = 0
        for g, t in enumerate([table, *self._more.pop(seq_id, ())]):
            for b in reversed(t[floors[g]:]):  # LIFO: newest reused first
                self._deref(b, quarantine=quarantine, fence=fence)
            self._group_held[g] -= len(t) - floors[g]
            held += len(t) - floors[g]
        self.stats.freed_total += held
        return held

    def flush_quarantine(self, upto: int | None = None) -> int:
        """Return quarantined blocks to the free list; -> count. The
        engine calls this right after a token sync: completing the sync
        of step program ``upto`` proves it and every program launched
        before it have executed, so blocks freed while none newer was in
        flight (``fence <= upto``) are safe to reuse; one freed with a
        newer program queued stays for that program's sync. ``None``:
        nothing is in flight any more, everything goes back."""
        q = self._quarantine
        n = len(q)
        if upto is not None:
            n = 0
            while n < len(q) and q[n][0] <= upto:
                n += 1
        if n:
            self._free.extend(b for _, b in q[:n])
            del q[:n]
        return n

    def release_all(self) -> int:
        """Free every sequence, drop all reservations AND the whole prefix
        cache (engine failure / shutdown path); -> blocks returned.
        Afterwards the free list is full again, so repeated engine
        create/shutdown cannot leak."""
        returned = 0
        for seq_id in list(self._tables):
            returned += self.free(seq_id)
        self.flush_quarantine()
        self._free.extend(self._lru)
        self._lru.clear()
        self._hash_to_block.clear()
        self._block_hash.clear()
        self._reserved = 0
        # Host tier dies with the device cache: a queued promotion landing
        # after release could scribble on a re-allocated block, and a
        # shutdown that kept arena bytes would leak across engine
        # create/shutdown cycles.
        self._pending_promotions.clear()
        self._unlanded.clear()
        if self.host_tier is not None:
            self.host_tier.clear()
        return returned

    # ---------------- prefix cache ----------------

    def peek_prefix(self, tokens) -> int:
        """Number of LEADING full blocks of ``tokens`` currently servable
        without recompute — resident on device (referenced or cached) OR
        demoted into the host tier. A pure lookup, no state change. The
        engine uses it to size the reservation before committing; a host
        hit that later fails RTKV verification in ``assign_prefix`` just
        shortens the assigned prefix, which the over-sized reservation
        already covers."""
        if not self.cfg.prefix_reuse:
            return 0
        digest = b""
        bs = self.cfg.block_size
        hits = 0
        for i in range(len(tokens) // bs):
            digest = _block_key(digest, tokens[i * bs:(i + 1) * bs])
            if digest not in self._hash_to_block and not (
                self.host_tier is not None and digest in self.host_tier
            ):
                break
            hits += 1
        return hits

    def export_chain(self, tokens) -> list[tuple[bytes, int]]:
        """(chain digest, physical block) for each LEADING full block of
        ``tokens`` currently resident — ``peek_prefix`` that also names
        the blocks. The prefill side of a disaggregated handoff walks
        this to know WHICH pool blocks to ship and under which chain
        digests; a partial walk (some blocks already evicted) is still a
        valid, shorter handoff."""
        digest = b""
        bs = self.cfg.block_size
        out: list[tuple[bytes, int]] = []
        for i in range(len(tokens) // bs):
            digest = _block_key(digest, tokens[i * bs:(i + 1) * bs])
            b = self._hash_to_block.get(digest)
            if b is None:
                break
            out.append((digest, b))
        return out

    def has_digest(self, digest: bytes) -> bool:
        """Whether a chain digest is resident (referenced or cached) —
        lets the handoff landing path tell 'already here, skip' apart
        from 'pool full, stop' when ``adopt_block`` returns None."""
        return digest in self._hash_to_block

    def adopt_block(self, digest: bytes) -> int | None:
        """Claim one block for a handoff landing and content-address it
        under ``digest`` as a CACHED (refcount-0, LRU) entry — after the
        caller scatters the fetched payload into the returned id, a
        plain ``assign_prefix`` scores a local prefix hit on it.

        Idempotent and best-effort by design (the handoff retry state
        machine re-drives): returns None without side effects when the
        digest is already resident (a concurrent identical prompt — or
        this same handoff, retried) or when the pool has no claimable
        block. Adoption moves a block free -> cached (or recycles a
        cached one), so ``available_blocks`` — and therefore admission
        accounting — is unchanged."""
        if digest in self._hash_to_block:
            return None
        if not self._free and not self._lru:
            return None
        b = self._take_block(reserved=False)
        self._hash_to_block[digest] = b
        self._block_hash[b] = digest
        self._lru[b] = None  # MRU end: just-landed blocks evict last
        self.stats.adopted_blocks += 1
        return b

    def assign_prefix(self, seq_id, tokens, max_blocks: int | None = None) -> int:
        """Map the longest resident prefix of ``tokens`` (full blocks
        only, at most ``max_blocks``) into ``seq_id``'s table, taking one
        reference per block. Each mapped block draws one unit from the
        reservation — identical accounting to an append, so the caller's
        worst-case reservation covers hits and computes uniformly.
        Returns the number of PROMPT TOKENS covered (hits * block_size).
        Must run right after ``allocate`` (empty table)."""
        table = self._tables[seq_id]
        assert not table, "assign_prefix requires an empty table"
        if not self.cfg.prefix_reuse:
            return 0
        digest = b""
        bs = self.cfg.block_size
        limit = len(tokens) // bs
        if max_blocks is not None:
            limit = min(limit, max_blocks)
        hits = 0
        for i in range(limit):
            nxt = _block_key(digest, tokens[i * bs:(i + 1) * bs])
            b = self._hash_to_block.get(nxt)
            if b is not None:
                if b in self._lru:  # resurrect: cached -> referenced
                    del self._lru[b]
                    self._ref[b] = 1
                else:
                    self._ref[b] += 1
                self._reserved -= 1
            else:
                # Device miss — promote from the host tier. The block is
                # claimed exactly like an append (one reservation unit),
                # content-addressed immediately, and its payload staged
                # for the engine's next batched land_blocks scatter. The
                # arena keeps its entry: that provenance is what makes
                # the block safe to evict again before landing.
                payload = self._host_lookup(nxt)
                if payload is None:
                    break
                b = self._take_block(reserved=True)
                self._ref[b] = 1
                self._hash_to_block[nxt] = b
                self._block_hash[b] = nxt
                self._pending_promotions.append((nxt, b, payload[0], payload[1]))
                self._unlanded.add(b)
                self.stats.promoted_blocks += 1
            table.append(b)
            digest = nxt
            hits += 1
        if hits:
            self._chain[seq_id] = (digest, hits)
            self._versions[seq_id] += 1
            self.stats.prefix_hit_blocks += hits
            self.stats.prefix_hit_tokens += hits * bs
            self.stats.high_water_blocks = max(
                self.stats.high_water_blocks, self.used_blocks
            )
        return hits * bs

    def register_prefix(self, seq_id, tokens, upto_tokens: int) -> int:
        """Content-address ``seq_id``'s full prompt blocks whose tokens
        [0, upto_tokens) are now fully written (engine calls this after
        each prefill chunk). Blocks whose chain hash is already claimed
        (a concurrent identical prompt) stay private. -> newly registered
        block count."""
        if not self.cfg.prefix_reuse:
            return 0
        digest, hashed = self._chain[seq_id]
        table = self._tables[seq_id]
        bs = self.cfg.block_size
        nfull = min(upto_tokens // bs, len(tokens) // bs, len(table))
        registered = 0
        while hashed < nfull:
            digest = _block_key(
                digest, tokens[hashed * bs:(hashed + 1) * bs]
            )
            b = table[hashed]
            if digest not in self._hash_to_block and b not in self._block_hash:
                self._hash_to_block[digest] = b
                self._block_hash[b] = digest
                registered += 1
            hashed += 1
        self._chain[seq_id] = (digest, hashed)
        return registered

    def prepare_write(self, seq_id, start_pos: int, end_pos: int,
                      *, reserved=True) -> list[tuple[int, int]]:
        """Make positions [start_pos, end_pos) of ``seq_id`` writable.
        Any already-allocated block in that range that is shared
        (refcount > 1) or content-addressed gets a fresh private block in
        the table; the returned (src, dst) pairs must be applied on device
        with ``ops.kv_cache.copy_blocks`` BEFORE the write lands. The
        shared source keeps its hash entry (and its other readers), so a
        sequence appending into a shared tail block diverges without
        corrupting the cached prefix."""
        if end_pos <= start_pos:
            return []
        table = self._tables[seq_id]
        bs = self.cfg.block_size
        lo = start_pos // bs
        hi = min(len(table) - 1, (end_pos - 1) // bs)
        pairs: list[tuple[int, int]] = []
        for idx in range(lo, hi + 1):
            b = table[idx]
            if self._ref.get(b, 0) > 1 or b in self._block_hash:
                dst = self._take_block(reserved=reserved)
                self._ref[dst] = 1
                table[idx] = dst
                self._deref(b)
                pairs.append((b, dst))
                self.stats.cow_copies += 1
        if pairs:
            self._versions[seq_id] += 1
            self.stats.high_water_blocks = max(
                self.stats.high_water_blocks, self.used_blocks
            )
        return pairs

    # ---------------- host tier (demote / promote) ----------------

    def _demote_evicted(self, digest: bytes, block: int) -> None:
        """Spill one LRU-evicted prefix block into the host tier (no-op
        with the tier disabled or no ``demote_fn`` installed). Best-effort
        by design: a failed capture loses a CACHE entry, never
        correctness, so failures are counted + logged, not raised. A
        block whose promotion payload has not landed yet is never
        exported — its device bytes are still garbage; the arena kept the
        real content through the promotion, so nothing is lost unless the
        arena has meanwhile evicted that entry too."""
        tier = self.host_tier
        if tier is None:
            return
        if block in self._unlanded:
            # the queued landing is now stale (its hash mapping just
            # died); the drain filter drops it by digest mismatch
            self._unlanded.discard(block)
            if digest in tier:
                tier.touch(digest)
            else:
                self.stats.demote_drops += 1
                logger.warning(
                    "unlanded promoted block %d evicted after its arena "
                    "entry %s was dropped — content lost",
                    block, digest.hex(),
                )
            return
        if digest in tier:
            tier.touch(digest)  # already backed: refresh recency, skip export
            return
        if self.demote_fn is None:
            return
        from ray_tpu._private import chaos

        try:
            chaos.fire("llm.kv.demote", block=block)
            k, v = self.demote_fn([block])
            stored, evicted = tier.put(digest, k[:, 0], v[:, 0])
            if stored:
                self.stats.demoted_blocks += 1
                self.stats.host_evicted_blocks += evicted
            else:
                self.stats.demote_drops += 1
                logger.warning(
                    "host tier refused demoted block %d (payload exceeds "
                    "host_cache_bytes=%d)", block, tier.capacity_bytes,
                )
        except Exception as exc:
            self.stats.demote_drops += 1
            logger.warning(
                "host-tier demotion of block %d failed: %r", block, exc
            )

    def demote_chain(self, tokens, upto_tokens: int,
                     trace_ctx: dict | None = None) -> int:
        """Proactively back the leading full blocks of ``tokens`` (first
        ``upto_tokens`` of them) into the host tier — the preemption
        pause path (engine._preempt_one_locked): the paused stream's
        chain must survive device LRU eviction while it is parked, so
        its resume re-prefills from cache instead of recomputing. One
        batched ``demote_fn`` export for all missing blocks (the same
        engine-installed indirection ``_demote_evicted`` uses — the
        cache never touches the device itself). Best-effort like every
        demote: a failed capture costs recompute on resume, never
        correctness, so failures are counted + logged, not raised.
        Returns the number of blocks newly captured.

        ``trace_ctx`` (the paused request's stored trace context) makes
        the demote visible on the request's trace as a ``kv.demote``
        span — only traced preemptions pay for the span record."""
        import time as _time

        t0 = _time.time() if trace_ctx else 0.0
        captured = self._demote_chain(tokens, upto_tokens)
        if trace_ctx:
            from ray_tpu.util import tracing

            tracing.record_span(
                "kv.demote", trace_id=trace_ctx["trace_id"],
                parent_span_id=trace_ctx.get("parent_span_id"),
                start=t0, end=_time.time(), kind="kv",
                attrs={"blocks": captured,
                       "upto_tokens": min(upto_tokens, len(tokens))},
            )
        return captured

    def _demote_chain(self, tokens, upto_tokens: int) -> int:
        tier = self.host_tier
        if tier is None or self.demote_fn is None:
            return 0
        bs = self.cfg.block_size
        digest = b""
        todo: list[tuple[bytes, int]] = []
        for i in range(min(upto_tokens, len(tokens)) // bs):
            digest = _block_key(digest, tokens[i * bs:(i + 1) * bs])
            b = self._hash_to_block.get(digest)
            if b is None:
                break  # not registered (or already evicted): chain ends
            if digest in tier:
                tier.touch(digest)  # already backed: refresh recency
                continue
            if b in self._unlanded:
                # device bytes are still garbage (promotion queued but
                # not landed); the arena already holds the real content
                continue
            todo.append((digest, b))
        if not todo:
            return 0
        from ray_tpu._private import chaos

        captured = 0
        try:
            chaos.fire("llm.kv.demote", blocks=len(todo))
            k, v = self.demote_fn([b for _, b in todo])
            for i, (d, b) in enumerate(todo):
                stored, evicted = tier.put(d, k[:, i], v[:, i])
                if stored:
                    captured += 1
                    self.stats.demoted_blocks += 1
                    self.stats.host_evicted_blocks += evicted
                else:
                    self.stats.demote_drops += 1
                    logger.warning(
                        "host tier refused preemption-demoted block %d "
                        "(payload exceeds host_cache_bytes=%d)",
                        b, tier.capacity_bytes,
                    )
        except Exception as exc:
            self.stats.demote_drops += len(todo) - captured
            logger.warning(
                "host-tier chain demotion of %d blocks failed: %r",
                len(todo), exc,
            )
        return captured

    def _host_lookup(self, digest: bytes):
        """Fetch + verify one host-tier entry; -> (k, v) numpy blocks or
        None. Verification failure (bit rot in host RAM, a truncated
        write) is a miss: the entry is dropped, counted and logged —
        corrupt bytes must never land in the device pool."""
        tier = self.host_tier
        if tier is None or digest not in tier:
            return None
        try:
            return tier.get(digest)
        except Exception as exc:
            tier.discard(digest)
            self.stats.host_corrupt_drops += 1
            logger.warning(
                "host-tier entry %s failed verification, dropped: %r",
                digest.hex(), exc,
            )
            return None

    def take_pending_promotions(self) -> list[tuple[int, Any, Any]]:
        """Drain staged host->device promotions for the engine to land as
        ONE fused ``land_blocks`` scatter; -> (block id, k, v) records.
        Exactly-once: each staged record is returned at most once, and a
        record whose block lost its content address before landing (its
        sequence was cancelled and a racing admission evicted the block)
        is dropped here — the arena still holds the bytes, so the drop
        costs a future re-promotion, not content. Callers MUST follow a
        successful scatter with ``promotions_landed``."""
        if not self._pending_promotions:
            return []
        staged, self._pending_promotions = self._pending_promotions, []
        out: list[tuple[int, Any, Any]] = []
        for digest, b, k_block, v_block in staged:
            if self._block_hash.get(b) != digest:
                self._unlanded.discard(b)
                self.stats.promotion_drops += 1
                logger.debug(
                    "promotion of block %d dropped: evicted before landing", b
                )
                continue
            out.append((b, k_block, v_block))
        return out

    def promotions_landed(self, block_ids) -> None:
        """Ack that the payloads for ``block_ids`` (returned by
        ``take_pending_promotions``) are on device — they become ordinary
        resident prefix blocks, eligible for demote-export again."""
        for b in block_ids:
            self._unlanded.discard(b)

    def prefix_digest_summary(self, limit: int = 32) -> list[str]:
        """Bounded routing-key summary for the fleet router: hex chain
        digests of prefix blocks this cache can serve without recompute —
        device-resident entries newest-registered first, then host-tier
        entries most-recently-used first. Piggybacked on the autoscaling
        snapshot, so router staleness is bounded by the controller's poll
        period."""
        out: list[str] = []
        seen: set[bytes] = set()
        for digest in reversed(self._hash_to_block):
            if len(out) >= limit:
                return out
            out.append(digest.hex())
            seen.add(digest)
        if self.host_tier is not None:
            for digest in self.host_tier.digests():
                if len(out) >= limit:
                    break
                if digest not in seen:
                    out.append(digest.hex())
        return out

    # ---------------- views ----------------

    @property
    def used_blocks(self) -> int:
        """Blocks referenced by live tables (cached-but-unreferenced
        blocks are reclaimable, so they don't count as used)."""
        return self.cfg.usable_blocks - len(self._free) - len(self._lru)

    @property
    def cached_blocks(self) -> int:
        """Content-addressed blocks with no live reference (the LRU set)."""
        return len(self._lru)

    @property
    def utilization(self) -> float:
        return self.used_blocks / max(1, self.cfg.usable_blocks)

    def table_epoch(self, pos: int) -> int:
        """What of a step's position changes a COMPOSED table besides the
        table's version: the window ``pos`` lies in (0 for any other)."""
        if not self.cfg.composed:
            return 0
        return pos // self.cfg.window_chunk[0]

    def block_table(self, seq_id, pad_to: int, pos: int = 0) -> np.ndarray:
        """[pad_to] int32 table, unallocated tail padded with garbage
        block 0 (those positions are always masked); with groups
        ``[G, pad_to]``, one row a group (a windowed group's entries behind
        its floor are block 0 too). A ring and a slot table give ``[2,
        pad_to]`` for the step whose queries start at ``pos``: row 0 the
        composed table (the slot table's blocks for the chunks of the
        windows closed before ``pos``, then the ring), row 1 the slot
        table."""
        table = self._tables[seq_id]
        if self.cfg.composed:
            slots = self._more[seq_id][0]
            W, C = self.cfg.window_chunk
            closed = (W // C // self.cfg.block_size) * (pos // W)
            if closed > len(slots) or max(
                    closed + len(table), len(slots)) > pad_to:
                raise ValueError(
                    f"sequence {seq_id!r} at position {pos} composes "
                    f"{closed} of {len(slots)} summary blocks and "
                    f"{len(table)} of the ring into a table of {pad_to}")
            out = np.zeros((2, pad_to), np.int32)
            out[0, :closed] = slots[:closed]
            out[0, closed: closed + len(table)] = table
            out[1, : len(slots)] = slots
            return out
        if len(table) > pad_to:
            raise ValueError(
                f"sequence {seq_id!r} holds {len(table)} blocks, "
                f"table was asked to fit in {pad_to}"
            )
        if self.cfg.groups:
            out = np.zeros((len(self.cfg.groups), pad_to), np.int32)
            for g, t in enumerate(self._group_tables(seq_id)):
                out[g, : len(t)] = t
            return out
        out = np.zeros((pad_to,), np.int32)
        out[: len(table)] = table
        return out

    def table_version(self, seq_id) -> int:
        """Monotonic per-sequence counter, bumped on any table-content
        change — cache key for host-side materialized block tables."""
        return self._versions[seq_id]

    def debug_snapshot(self) -> dict:
        """JSON-safe accounting snapshot for the engine's flight-recorder
        / debug dumps — block-pool state plus the cumulative CacheStats
        counters, no device arrays."""
        s = self.stats
        return {
            "num_blocks": self.cfg.num_blocks,
            "block_size": self.cfg.block_size,
            "used_blocks": self.used_blocks,
            "free_blocks": len(self._free),
            "quarantined_blocks": len(self._quarantine),
            "cached_blocks": self.cached_blocks,
            "reserved_blocks": self._reserved,
            "live_sequences": len(self._tables),
            "state_slots": self.used_slots,
            "state_slots_high_water": s.state_slots_high_water,
            # what the blocks in use hold in the family's plane by block id
            "compressed_key_bytes": (
                self.used_blocks * self.cfg.block_state_bytes),
            "groups": self.group_report(),
            "window_blocks_taken": s.window_blocks_taken,
            "window_blocks_freed": s.window_blocks_freed,
            "utilization": round(self.utilization, 4),
            "high_water_blocks": s.high_water_blocks,
            "allocated_total": s.allocated_total,
            "freed_total": s.freed_total,
            "prefix_hit_blocks": s.prefix_hit_blocks,
            "prefix_hit_tokens": s.prefix_hit_tokens,
            "prefix_evicted_blocks": s.prefix_evicted_blocks,
            "cow_copies": s.cow_copies,
            "adopted_blocks": s.adopted_blocks,
            "host_blocks": 0 if self.host_tier is None else self.host_tier.blocks,
            "host_bytes": 0 if self.host_tier is None else self.host_tier.nbytes,
            "demotions": s.demoted_blocks,
            "promotions": s.promoted_blocks,
            "host_evicted_blocks": s.host_evicted_blocks,
            "promotion_drops": s.promotion_drops,
            "demote_drops": s.demote_drops,
            "host_corrupt_drops": s.host_corrupt_drops,
        }

    def num_allocated(self, seq_id) -> int:
        return len(self._tables[seq_id])
