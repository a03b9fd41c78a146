"""The cached (serving) step of every family, written once.

A served family runs these kinds of step against the paged K/V pool
(ops/kv_cache.py; serve/llm drives them): ``prefill`` of a right-padded
prompt chunk, ``decode_step`` of one token a row (an autoregressive family)
or of one BLOCK a row (``_block_steps``, at the file's end), ``verify_step``
of a speculative window. They are ONE step: tokens ``[B, S]`` at true
positions, every layer writing the chunk's K/V rows into the pool at its
own layer index and attending over the paged context there, then the head
on some of the rows and a sampling epilogue. The pool is a buffer the step
OWNS: the step programs donate it (serve/llm/decode.py ``_jit_named``),
each layer scatters B x S rows into it where it stands and the kernel
reads the whole pool at a layer index (``attend_layer``). This file owns
that step; a family's file (models/gpt.py, llama.py, lfm2_moe.py) holds
only what is the family's own, in a ``CachedFamily``:

- ``embed(params, tokens, step, cfg) -> (x [B, S, D], aux)``: the token
  (and position) embedding, its table lookups through ``step.take``, and
  whatever the layers need per position (rotary cos / sin; None for gpt),
  which reaches them as ``step.aux``;
- ``layer(x, lp, attend, step, state, cfg) -> (x, state)``: one layer over
  the chunk. ``attend(q, k, v)`` (q ``[B, S, Hq, hd]``; k, v ``[B, S, Hkv,
  hd]``, the compact GQA heads) is the cache side of the layer, written
  below: it returns the attention output ``[B, S, Hq * hd]``. A layer
  that does not attend does not call it. In a LIST of layers a layer may
  name where its K/V lives, ``attend(q, k, v, group=g, slot=s,
  window=w)``: the step's block tables are then ``[G, B, NB]``, one table
  a GROUP of layers (serve/llm/kv_cache.py: a sliding layer's group gives
  back the blocks behind its window), ``slot`` is the layer's index in the
  pool (its ordinal in its group) and ``window`` makes the attention
  sliding. Without them: the one table ``[B, NB]``, the pool's layer the
  attending layer's ordinal, full attention. A LATENT layer (one row a
  token that every head reads as key and as value, the pool in planes:
  models/pangu_ultra_moe.py) calls ``attend(q, row, rope, latent=scale)``
  with ONE ``row [B, S, C]`` and ``rope [B, S, R]``: q absorbed ``[B, S,
  H, C + R]`` for ``[B, S, H * C]`` back (decode), or with ``up=(W_uk,
  W_uv)`` q as projected for the heads' outputs (prefill). A layer that
  SELECTS (models/minicpm_sala.py): ``attend(q, k, v, select=...)``;
- ``final_norm(params, x, cfg)`` and ``head(params, h, cfg)`` (float32
  logits over ``[..., D]``);
- ``stack``: the key of ``params`` that holds the layers. A tree whose
  leaves lead with the layer axis is a stack of like layers: one
  ``lax.scan`` that carries the pool and takes the layer's index as xs.
  A list of per-layer trees is a stack of unlike layers: a Python loop,
  the pool's layer the attending layer's ordinal;
- ``open_state`` / ``close_state``: for a family that keeps per-sequence
  state BESIDE the pool (``state``, rows addressed by ``slots``), the
  step's working form of it, threaded through ``layer``, and the next
  state made of that. The working form may hold Python values (a layer
  ordinal, a growing list), so these two go with a LIST of layers only:
  a scanned stack carries what ``layer`` returns, which has to be arrays.
  Absent: what ``layer`` returns is the next state (None where None was
  given).

Beside how its step is built, the record says what the serving layer asks
of the family and could only copy from here. serve/llm/decode.py READS its
``Family`` from the module's record (``FAMILY``) and the module's names
(``<name>_init``, ``_prefill``, ``_decode_step``, ``_verify_step``,
``_param_axes``, ``_quant_axes``, ``_init_state``, ``_counters``), and its
``Family`` docstring says what each of these means to the engine:
``config`` (the config class; its ``tiny()`` is the default config),
``no_verify`` (WHY the family has no verify step, for which its module
then keeps no name; None: it has one), ``state_rows``,
``block_state_bytes``, ``step_attrs``, ``gmm_form``,
``donated_state_counters`` and ``block_steps`` (``steps`` then builds
``_block_step``'s, at the file's end).

Every step takes ``state=None, slots=None`` by keyword and returns ``(out,
cache_k', cache_v', state')``: None is an empty pytree to ``jax.jit``, so
a family without state has neither among its program's parameters.

The step NAMES its parts (``jax.named_scope``; the vocabulary is
serve/llm/obs.py ``SCOPES``): ``embed``, ``layer_stack``, ``attn_cache``,
``attn_kernel``, ``head``, ``sample`` and ``counters`` here, for every
family at once; ``attn_proj`` and ``ffn`` in a family's ``layer``, deeper
names in ops/. A name is metadata of the compiled program and costs a
served step nothing; ``DecodeFns.program_scopes()`` reads it back.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import mha_reference
from ray_tpu.ops.kv_cache import write_kv
from ray_tpu.ops.paged_attention import (
    decode_attention,
    latent_attention,
    prefill_attention,
    resolve_backend,
)
from ray_tpu.ops.sampling import sample_tokens, unmask_tokens, verify_tokens
from ray_tpu.ops.sparse_select import (
    sparse_decode_attention,
    sparse_prefill_attention,
)


@dataclass(frozen=True)
class CachedFamily:
    name: str  # the programs are jit_<name>_prefill / _decode_step / ...
    config: type
    stack: str
    embed: Callable
    layer: Callable
    final_norm: Callable
    head: Callable
    open_state: Callable | None = None
    close_state: Callable | None = None
    place: Callable | None = None
    no_verify: str | None = None
    state_rows: bool = True
    block_state_bytes: Callable | None = None
    step_attrs: Callable | None = None
    gmm_form: Callable | None = None
    donated_state_counters: tuple | None = None
    block_steps: bool = False


class Step(NamedTuple):
    """One cached step as data. ``kind``: ``fresh`` (a prompt from
    position 0: positions are an ``arange``, nothing is resident yet),
    ``chunk`` (a prompt chunk whose row b starts at ``start[b]``, earlier
    positions resident), ``decode`` (S = 1) or ``verify`` (S = W: the last
    committed token, then the drafts)."""

    kind: str
    pos: jax.Array             # [B, S] each token's true position
    valid: jax.Array | None    # [B, S] the real tokens; None: every one
    block_tables: jax.Array    # [B, NB]; by group of layers [G, B, NB]
    # [B], the one such array every kind has: the rows' real tokens
    # (fresh, chunk), positions (decode) or first positions (verify)
    rows: jax.Array
    start: jax.Array | None    # [B] (chunk)
    slots: jax.Array | None    # [B] the rows' slots in ``state``
    aux: Any = None            # ``embed``'s second result
    # [B, S] each token's index in the step's table, where its K/V is
    # written and the mask stands (``CachedFamily.place``); None: ``pos``
    at: jax.Array | None = None

    def table_pos(self, n: int) -> jax.Array:
        """``pos`` made safe to index a table of ``n`` rows: a padding
        column can run past it (it is masked anyway); a decode row has no
        padding columns."""
        if self.kind == "decode":
            return self.pos
        return jnp.minimum(self.pos, n - 1)

    def take(self, table: jax.Array, index: jax.Array) -> jax.Array:
        """``table[index]`` for ``index`` [B, S]: [B, S, D]. A decode
        step's [B, 1] is gathered as [B] and lifted, which is the program
        served so far (a [B, 1] gather compiles to another)."""
        if self.kind == "decode":
            return table[index[:, 0]][:, None]
        return table[index]


def _plan(kind, tokens, rows, block_tables, start, draft_len, slots) -> Step:
    if kind == "decode":
        pos, valid = rows[:, None], None
    else:
        B, S = tokens.shape
        cols = jnp.arange(S, dtype=jnp.int32)[None, :]
        if kind == "verify":
            pos, valid = rows[:, None] + cols, cols <= draft_len[:, None]
        else:
            pos = (jnp.broadcast_to(cols, (B, S)) if kind == "fresh"
                   else start[:, None] + cols)
            valid = cols < rows[:, None]
    return Step(kind, pos, valid, block_tables, rows, start, slots)


def attend_layer(step: Step, cache_k, cache_v, layer, q, k, v, cfg,
                 tables=None, window=None, then=None, latent=None,
                 select=None, up=None):
    """The cache side of one attention layer, on the WHOLE pools and the
    layer's index in them (an int32 scalar, traced under the scan): the
    chunk's K/V rows are scattered into the pools at ``[layer, blk,
    slot]``, then the attention call the kind asks for reads the pools at
    that layer. Nothing slices a layer's slab out of a pool or writes one
    back, whatever the heads: every pool is stored lane-dense, a token's
    heads one row (ops/paged_attention.py ``pool_shape``), so every
    pool rests in the order the scatter and the kernel read. Returns
    (attention output [B, S, Hq * hd], cache_k', cache_v'). ``tables``
    [B, NB]: the layer's own table (None: ``step``'s); ``window``: sliding
    attention over the last ``window`` positions; ``then``: what else the
    layer writes into the pools, after its K/V and before it attends.

    ``latent``: the softmax scale of a LATENT layer over a pool in one
    plane (ops/paged_attention.py ``latent_attention``): ``k`` is then the
    token's latent vector ``[B, S, C]`` and ``v`` the key's rotary rest
    ``[B, S, R]``, one of each for all heads, written to ``cache_k`` as
    ONE row ``[k | v]`` (``cache_v`` is None); ``q`` is ``[B, S, H, C +
    R]`` and what comes back ``[B, S, H * C]``; with ``up`` the expanded
    form of a prefill step (``_attend_latent``).

    ``select``: the layer SELECTS the pages it attends
    (ops/sparse_select.py ``Selection``; models/minicpm_sala.py): K and V
    are written as ever, then a decode row attends the pages of its list
    and nothing else of its context (``paged_attention_sparse``), and a
    chunk's queries switch, each by its own position, between every key
    and the blocks chosen for it (``sparse_prefill_attention``)."""
    B, S = q.shape[:2]
    backend = cfg.attention_backend
    if tables is None:
        tables = step.block_tables
    if select is not None:
        return _attend_selected(
            step, cache_k, cache_v, layer, q, k, v, tables, backend, select)
    if latent is not None:
        at = step.pos if step.at is None else step.at
        one = step.kind == "decode"  # its rows are written as [B, .]
        with jax.named_scope("attn_cache"):
            cache_k, cache_v = write_kv(
                cache_k, cache_v, k[:, 0] if one else k,
                v[:, 0] if one else v, at[:, 0] if one else at, tables,
                valid=step.valid, layer=layer)
        with jax.named_scope("attn_kernel"):
            # ``up``: the layer hands q NOT absorbed and its W_uk, W_uv: a
            # prefill step attends in the expanded form (at the file's
            # end: no line of the other branches' call chains moves)
            attn = _attend_latent(step, cache_k, layer, q, k, v, tables,
                                  at, backend, latent, up)
            return attn.reshape(B, S, -1), cache_k, cache_v
    if step.kind == "decode":
        at = step.rows if step.at is None else step.at[:, 0]
        with jax.named_scope("attn_cache"):
            cache_k, cache_v = write_kv(
                cache_k, cache_v, k[:, 0], v[:, 0], at, tables, layer=layer)
        if then is not None:
            cache_k, cache_v = then(cache_k, cache_v, layer)
        with jax.named_scope("attn_kernel"):
            attn = decode_attention(
                q[:, 0], cache_k, cache_v, tables, at, backend=backend,
                layer=layer, window=window)
            return attn.reshape(B, S, -1), cache_k, cache_v
    at = step.pos if step.at is None else step.at
    with jax.named_scope("attn_cache"):
        cache_k, cache_v = write_kv(
            cache_k, cache_v, k, v, at, tables, valid=step.valid,
            layer=layer)
    if then is not None:
        cache_k, cache_v = then(cache_k, cache_v, layer)
    # The fresh-prompt shortcut attends over the UNQUANTIZED just-computed
    # k / v, the chunk alone (a prompt is prefilled once, at bucketed
    # shapes, where a kernel's grid buys nothing). Under a quantized pool
    # it must not run: a chunked re-prefill (failover resume) reads the
    # quantized pool back, and resumed streams stay byte-identical only if
    # the first prefill saw the same quantized values. Under pallas the
    # fused kernel reads the just-written pool (the padded context never
    # exists in HBM).
    with jax.named_scope("attn_kernel"):
        if (
            step.kind == "fresh"
            and window is None  # the shortcut's mask is causal, no more
            and cfg.quantization is None
            and resolve_backend(backend) != "pallas"
        ):
            attn = mha_reference(  # repeats GQA kv heads internally
                q.transpose(0, 2, 1, 3),
                k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3),
                causal=True,
            ).transpose(0, 2, 1, 3)
        else:
            attn = prefill_attention(
                q, cache_k, cache_v, tables, _query_limits(step, at, cfg),
                backend=backend, layer=layer, window=window)
        return attn.reshape(B, S, -1), cache_k, cache_v


def _attend_selected(step, cache_k, cache_v, layer, q, k, v, tables, backend,
                     select):
    """``attend_layer`` for a layer that selects its pages."""
    B, S = q.shape[:2]
    if step.kind == "decode":
        with jax.named_scope("attn_cache"):
            cache_k, cache_v = write_kv(
                cache_k, cache_v, k[:, 0], v[:, 0], step.rows, tables,
                layer=layer)
        with jax.named_scope("attn_kernel"):
            attn = sparse_decode_attention(
                q[:, 0], cache_k, cache_v, select.pages, select.vpos, layer,
                backend=backend)
            return attn.reshape(B, S, -1), cache_k, cache_v
    with jax.named_scope("attn_cache"):
        cache_k, cache_v = write_kv(
            cache_k, cache_v, k, v, step.pos, tables, valid=step.valid,
            layer=layer)
    with jax.named_scope("attn_kernel"):
        attn = sparse_prefill_attention(
            q, cache_k, cache_v, tables, step.pos, step.valid,
            select.seg_rows, layer, select.cfg, backend=backend)
        return attn.reshape(B, S, -1), cache_k, cache_v


def _walk(fam, x, layers, cache_k, cache_v, step, state, cfg):
    """``x`` through the stack, every attending layer updating the pools
    where they stand (the step programs donate them: serve/llm/decode.py).
    Returns (x, cache_k', cache_v', state)."""
    if not isinstance(layers, list):
        # the pools ride the scan as CARRY, never as xs -> ys: a layer
        # scatters its rows into them and reads its pages out of them
        def body(carry, xs):
            x, state, *kv = carry
            lp, layer = xs

            def attend(q, k, v, *, group=None, then=None):
                attn, kv[0], kv[1] = attend_layer(
                    step, *kv, layer, q, k, v, cfg,
                    None if group is None else step.block_tables[group],
                    then=then)
                return attn

            x, state = fam.layer(x, lp, attend, step, state, cfg)
            return (x, state, *kv), None

        # the loop's own operations (a layer's weights sliced out of the
        # stack) are ``layer_stack``; the layers' lie deeper
        with jax.named_scope("layer_stack"):
            (x, state, cache_k, cache_v), _ = jax.lax.scan(
                body, (x, state, cache_k, cache_v),
                (layers, jnp.arange(cache_k.shape[0], dtype=jnp.int32)))
        return x, cache_k, cache_v, state

    attended = 0  # the pool spans the attending layers only

    def attend(q, k, v, *, group=None, slot=None, window=None, latent=None,
               select=None, up=None):
        nonlocal cache_k, cache_v, attended
        attn, cache_k, cache_v = attend_layer(
            step, cache_k, cache_v, attended if slot is None else slot,
            q, k, v, cfg,
            None if group is None else step.block_tables[group], window,
            latent=latent, select=select, up=up)
        attended += 1
        return attn

    for lp in layers:
        x, state = fam.layer(x, lp, attend, step, state, cfg)
    return x, cache_k, cache_v, state


def _step(fam, kind, params, cache_k, cache_v, tokens, rows, block_tables,
          cfg, *, start=None, draft_len=None, sample=None, state=None,
          slots=None):
    with jax.named_scope("embed"):
        step = _plan(
            kind, tokens, rows, block_tables, start, draft_len, slots)
        if fam.place is not None:
            step = step._replace(at=fam.place(step.pos, cfg))
        x, aux = fam.embed(params, tokens, step, cfg)
        step = step._replace(aux=aux)
        work = state if fam.open_state is None else fam.open_state(
            state, step, cfg)
    x, cache_k, cache_v, work = _walk(
        fam, x, params[fam.stack], cache_k, cache_v, step, work, cfg)
    with jax.named_scope("counters"):
        state = work if fam.close_state is None else fam.close_state(
            state, work, step, cfg)
    with jax.named_scope("head"):
        # the rows that reach the head: a decode step's one, a prompt's
        # last real token, every column of a verify window
        if kind == "decode":
            x = x[:, 0]
        h = fam.final_norm(params, x, cfg)
        if kind in ("fresh", "chunk"):
            h = h[jnp.arange(tokens.shape[0]), rows - 1]
        logits = fam.head(params, h, cfg)
    if sample is None:
        return logits, cache_k, cache_v, state
    with jax.named_scope("sample"):
        if kind == "verify":
            out = verify_tokens(logits, rows, tokens, draft_len, sample)
        else:
            # the new token lands right after the row's last real one
            if kind == "decode":
                new_pos = rows + 1
            else:
                new_pos = (rows if start is None else start + rows).astype(
                    jnp.int32)
            out = sample_tokens(logits, new_pos, sample)
    return out, cache_k, cache_v, state


def steps(fam: CachedFamily):
    """The family's (prefill, decode_step, verify_step), each named
    ``<fam.name>_<step>``: a jitted program takes its name from there.
    A family that says why it has no verify step (``fam.no_verify``) keeps
    no name for the third; a family that generates by diffusion over blocks
    (``fam.block_steps``) gets ``_block_steps``'s two and None.

    All take ``(params, cache_k, cache_v, ...)``, the pool lane-dense
    ``[n_kv_layer, num_blocks, block_size, n_kv_head * head_dim]`` (a
    test's own, by heads ``[.., n_kv_head, head_dim]``, is taken too;
    ops/paged_attention.py ``pool_shape``; block 0 is the garbage sink), ``block_tables [B, NB]`` (``[G, B, NB]`` for a family whose
    layers name their group), the static ``cfg``, and by keyword
    ``sample`` (an ops/sampling.py pytree: sampling then runs inside the
    program and token ids come back, not logits), ``state`` and ``slots``.
    All return ``(out, cache_k', cache_v', state')``: the pools with the
    step's rows written, and as the executor jits a step (pools donated)
    the very buffers that came in. Shapes are static in (batch, padded
    length, blocks a row), so the engine's bucketing bounds the compiled
    set.

    ``prefill(..., tokens [B, S], lengths [B], block_tables, cfg,
    start=None)``: right-padded prompts (a padding row has length 1 and an
    all-garbage table). Every real position's K/V is written; ``out`` is
    the last real token's logits ``[B, V]`` float32, or the sampled first
    tokens ``[B]`` int32. ``start=None``: each prompt starts at position 0.
    ``start [B]`` (chunked prefill, prefix-cache hits): row b's tokens sit
    at true positions ``start[b]..`` and attention covers what is already
    resident in the paged cache.

    ``decode_step(..., tokens [B], positions [B], block_tables, cfg)``:
    each sequence's newest token at its position; writes its K/V, attends
    over the paged context (itself included). A padding row points at the
    garbage block with position 0. ``out``: next-token logits ``[B, V]`` or
    sampled tokens ``[B]``.

    ``verify_step(..., tokens [B, W], starts [B], draft_len [B],
    block_tables, cfg)``: speculative decoding's verify pass. Column 0 is
    row b's last COMMITTED token (true position ``starts[b]``; its K/V is
    not yet cached, exactly as in a decode step), columns 1..W-1 are
    drafted candidates; columns past ``draft_len`` are padding. Valid
    columns write K/V at their own positions: for accepted drafts that IS
    the correct entry (accepted prefix => identical context => identical
    K/V); rejected drafts leave garbage only BEYOND the committed
    frontier, where the causal mask keeps it unattended until the
    frontier's next window overwrites it, so no rollback pass is needed.
    Padding columns go to the garbage block, so reservations only need to
    cover ``draft_len`` positions past the frontier. ``out``: the packed
    verdicts ``[B, W + 1]`` int32 of ``verify_tokens``, or with
    ``sample=None`` the window's logits ``[B, W, V]`` float32."""

    if fam.block_steps:
        return (*_block_steps(fam), None)

    def prefill(params, cache_k, cache_v, tokens, lengths, block_tables,
                cfg, start=None, sample=None, *, state=None, slots=None):
        return _step(
            fam, "fresh" if start is None else "chunk", params, cache_k,
            cache_v, tokens, lengths, block_tables, cfg, start=start,
            sample=sample, state=state, slots=slots)

    def decode_step(params, cache_k, cache_v, tokens, positions,
                    block_tables, cfg, sample=None, *, state=None,
                    slots=None):
        return _step(
            fam, "decode", params, cache_k, cache_v, tokens[:, None],
            positions, block_tables, cfg, sample=sample, state=state,
            slots=slots)

    def verify_step(params, cache_k, cache_v, tokens, starts, draft_len,
                    block_tables, cfg, sample=None, *, state=None,
                    slots=None):
        return _step(
            fam, "verify", params, cache_k, cache_v, tokens, starts,
            block_tables, cfg, draft_len=draft_len, sample=sample,
            state=state, slots=slots)

    for fn in (prefill, decode_step, verify_step):
        fn.__name__ = fn.__qualname__ = f"{fam.name}_{fn.__name__}"
    return prefill, decode_step, verify_step


def _attend_latent(step, pool, layer, q, k, v, tables, at, backend, scale,
                   up):
    """A latent layer's attention over the pool its rows were just
    written to. Without ``up`` the ABSORBED form, every kind of step
    through the one call (``latent_attention``: q ``[B, S, H, C + R]``,
    ``[B, S, H, C]`` back, which the layer un-absorbs). With ``up = (W_uk
    [C, H, N], W_uv [C, H, V])`` the EXPANDED form of a prefill step
    (ops/latent_prefill.py: q ``[B, S, H, N + R]`` as projected, the heads'
    outputs ``[B, S, H * V]`` back): the step's own keys in hand, the
    resident prefix block by block from the pool."""
    if up is None:
        return latent_attention(
            q, pool, tables,
            at if step.valid is None else jnp.where(step.valid, at, 0),
            latent_dim=k.shape[-1], scale=scale, backend=backend,
            layer=layer)
    from ray_tpu.ops.latent_prefill import expanded_prefill_attention

    return expanded_prefill_attention(
        q, k, v, pool, tables, step.valid, step.start, *up,
        scale=scale, backend=backend, layer=layer)


# ----------------------------------------------------------------------------
# A family that generates by diffusion over BLOCKS (models/sdar_moe.py):
# attention is full inside a block of ``cfg.block_length`` positions and
# causal from block to block, and a decode step carries a block a row (a
# finished block AND the fresh one behind it, where a row folds).
# Appended here, every line above as it was: no line of another family's
# kernel call chain moves.
# ----------------------------------------------------------------------------


def _query_limits(step, at, cfg):
    """The last position each query of a prompt-kind step attends, ``[B,
    S]``, padding columns at 0: its own (causal), or under the kinds of a
    block family the last of its BLOCK. The paged kernel masks a key by
    its query's limit (``prefill_attention``'s ``positions``), so the
    block mask costs no kernel of its own; every query of a block pass has
    one limit, which is the decode kernel's situation at ``block_length``
    times the query rows a K/V head."""
    if step.kind in BLOCK_KINDS:
        at = at - at % cfg.block_length + (cfg.block_length - 1)
    return jnp.where(step.valid, at, 0)


# ``Step.kind`` of a block family: a prompt chunk under the block mask, and
# a pass over one block a row. Neither is ``fresh`` (whose shortcut's mask
# is causal and no more) nor ``decode`` (one token a row)
BLOCK_KINDS = ("block_chunk", "block")


def _block_step(fam, kind, params, cache_k, cache_v, tokens, rows,
                block_tables, cfg, *, start, sample, state, slots):
    """``_step`` for a block family. ``block_chunk``: a prompt's whole
    blocks ``tokens [B, S]`` at ``start``, ``rows`` real tokens a row;
    their K/V is written and NOTHING is chosen (a block family has no
    token to give before its first block is denoised): ``out`` is zeros
    ``[B]`` int32, or with ``sample=None`` the logits of every position
    ``[B, S, V]``.

    ``block``: one pass a row, ``tokens [B, W + 1]`` the row's block (its
    ids and the bits of its masked positions), ``rows`` the blocks' first
    positions. A row whose block holds NO masked position and whose
    schedule still fills (``sample["fill"] > 0``: another block is due)
    FOLDS: it carries ``[finished block | next block, all MASK]``, 2W
    positions from the finished block's start, so the pass that leaves
    the finished block's K/V in the cache for good IS the next block's
    first denoising pass. Every other row carries its block alone (a
    denoising pass; or, no bit set and ``fill`` 0, the commit of a
    request's LAST block) and its other W columns are padding: written to
    the garbage block, routed to no expert, counted nowhere. The step is
    a prompt chunk of ``[B, 2W]`` under the block mask: the K/V rows of
    the valid positions are (re)written, a query attends up to ITS
    block's end (the finished block's never see the fresh one's keys, so
    their K/V is what a commit pass of their own leaves; the fresh
    block's see them as this pass's own scatter wrote them, a layer at a
    time). The head runs on W positions a row, the ones that CHOOSE (a
    folding row's fresh block, else the row's block), and the epilogue
    fills what the row's schedule says (ops/sampling.py
    ``unmask_tokens``): ``out [B, W + 1]``, the ids and bits of the block
    that chose, or with ``sample=None`` (no row folds) its logits ``[B,
    W, V]``."""
    W = cfg.block_length
    ids = masked = fold = None
    if kind == "block":
        ids, masked = tokens[:, :W], tokens[:, W]
        fill = 0 if sample is None else sample["fill"]
        fold = (masked == 0) & (fill > 0)
        tokens = jnp.concatenate(
            [ids, jnp.full_like(ids, cfg.mask_token_id)], axis=1)
        start, rows = rows, jnp.where(fold, 2 * W, W).astype(rows.dtype)
    elif start is None:
        start = jnp.zeros_like(rows)
    with jax.named_scope("embed"):
        step = _plan("chunk", tokens, rows, block_tables, start, None,
                     slots)._replace(kind=kind)
        x, aux = fam.embed(params, tokens, step, cfg)
        step = step._replace(aux=aux)
        work = fam.open_state(state, step, cfg)
    x, cache_k, cache_v, work = _walk(
        fam, x, params[fam.stack], cache_k, cache_v, step, work, cfg)
    with jax.named_scope("counters"):
        state = fam.close_state(state, work, step, cfg)
    if kind == "block_chunk" and sample is not None:
        return jnp.zeros(rows.shape, jnp.int32), cache_k, cache_v, state
    pos = step.pos
    with jax.named_scope("head"):
        if kind == "block":
            # the W positions a row that choose, BEFORE the head: its
            # product and the float32 logits stay ``rows x W``
            pick = fold[:, None]
            x = jnp.where(pick[..., None], x[:, W:], x[:, :W])
            pos = jnp.where(pick, pos[:, W:], pos[:, :W])
            tokens = jnp.where(pick, cfg.mask_token_id, ids)
            masked = jnp.where(fold, (1 << W) - 1, masked)
        logits = fam.head(params, fam.final_norm(params, x, cfg), cfg)
    if sample is None:
        return logits, cache_k, cache_v, state
    with jax.named_scope("sample"):
        out = unmask_tokens(
            logits, tokens, masked, pos, sample, cfg.mask_token_id,
            cfg.confidence_threshold)
    return out, cache_k, cache_v, state


def _block_steps(fam: CachedFamily):
    """``steps`` for a family that generates by diffusion over blocks:
    ``(prefill, decode_step)`` under the names ``<fam.name>_prefill`` /
    ``_decode_step`` and with the arguments ``steps`` gives them
    (``_block_step`` says what differs: ``decode_step``'s ``tokens`` are
    ``[B, block_length + 1]`` at the blocks' first ``positions``, and the
    step it traces is ``[B, 2 * block_length]`` wide: a row whose block is
    finished carries the next one behind it). No
    verify step: there is nothing to draft for. The family's layers are a
    LIST with ``open_state`` / ``close_state``."""

    def prefill(params, cache_k, cache_v, tokens, lengths, block_tables,
                cfg, start=None, sample=None, *, state=None, slots=None):
        return _block_step(
            fam, "block_chunk", params, cache_k, cache_v, tokens, lengths,
            block_tables, cfg, start=start, sample=sample, state=state,
            slots=slots)

    def decode_step(params, cache_k, cache_v, tokens, positions,
                    block_tables, cfg, sample=None, *, state=None,
                    slots=None):
        return _block_step(
            fam, "block", params, cache_k, cache_v, tokens, positions,
            block_tables, cfg, start=None, sample=sample, state=state,
            slots=slots)

    for fn in (prefill, decode_step):
        fn.__name__ = fn.__qualname__ = f"{fam.name}_{fn.__name__}"
    return prefill, decode_step
