"""MiniCPM-SALA family: lightning (linear) attention layers that keep a
matrix of state a head a sequence, and NoPE attention layers that past
``dense_len`` tokens SELECT the blocks of keys they read, for serving.

Follows the public ``minicpm_sala`` configuration (openbmb MiniCPM-SALA's
``config.json``), whose ``mixer_types`` names two published layers:
``minicpm4`` (InfLLM-V2: the MiniCPM4 report, arXiv:2506.07900 section 2.2;
arXiv:2509.24663) and ``lightning-attn`` (Lightning Attention,
arXiv:2401.04658, as MiniMax-01 builds it). With MiniCPM's scalings::

    x0 = E[token] * scale_emb                a = scale_depth / sqrt(L_published)
    h <- h + a * Mixer_l(RMSNorm(h));        h <- h + a * SwiGLU(RMSNorm(h))
    logits = W_head . (RMSNorm(h) / (hidden_size / dim_model_base))

``lightning-attn`` (ops/lightning.py): q, k, v, g = x Wq, x Wk, x Wv, x Wg;
RMSNorm over each head of q and k (``qk_norm``), rotary at the true
position over the whole head; per head ``S_t = lam_h S_{t-1} + k_t^T v_t``,
``o_t = q_t S_t / sqrt(hd)``; ``y = Wo(RMSNorm(o) * sigmoid(g))``, the norm
over the heads joined. ``lam_h = exp(-s_h)`` with the slopes of the
layer's PUBLISHED index (``layer_index``, kept when the depth is cut).

``minicpm4`` (ops/sparse_select.py): 32 query heads over 2 K/V heads, q
and k through the same per-head RMSNorm, NO positional encoding, ``y =
Wo(Attn * sigmoid(g))``; ``Attn`` a causal softmax over every key below
``dense_len`` and, for a query at or past it, over the ``topk`` blocks a
K/V head that its pooled scores against the compressed keys choose. The
switch is by the QUERY's position, so a position's output never depends
on what comes after it (the published code switches by the length of the
sequence it was handed; ``assumed.dense_len_rule`` in
benchmark/configs/minicpm-sala-8l.json).

What the configuration does not say is listed in that file's ``assumed``
(the seven ``sparse_config`` integers, the decay slopes, the output norm
over the joined heads, QK-norm on both mixers), each with its other
reading.

Same conventions as models/lfm2_moe.py (a LIST of per-layer trees, float32
masters, activations in ``cfg.dtype``, the prefill / decode-step contract
of models/cached.py, ``open_state`` / ``close_state``), with what this
family forces:

- The paged pool spans the ``minicpm4`` layers only (``n_kv_layer``);
  ``block_size`` of the cache IS the selection's block.
- ``state`` holds three kinds of thing: ``lightning`` ``[n_lightning,
  slots, H, hd, hd]`` float32, a slot a sequence (slot 0 the garbage sink;
  a row whose chunk starts its sequence begins from zeros whatever the
  slot held); ``ckeys`` ``[n_sparse, num_blocks, segments, Hkv * hd]``
  float32, the compressed keys' segment sums addressed by BLOCK ID like K
  and V (ops/sparse_select.py); and counters (``steps``, ``blocks``: (low,
  high) uint32 words, models/parts.py ``count_value``).
- ``attend(q, k, v, select=...)`` (models/cached.py): the layer hands the
  cache side its selection, a page list a (row, K/V head) in decode, the
  row's segment sums in prefill.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models import cached
from ray_tpu.models.parts import (
    count_add,
    count_value,
    head_untied,
    leaf_tree,
    swiglu,
)
from ray_tpu.ops.layers import rms_norm, rope
from ray_tpu.ops.lightning import (
    lightning_chunk,
    lightning_slopes,
    lightning_step,
    lightning_step_pallas,
)
from ray_tpu.ops.paged_attention import resolve_backend
from ray_tpu.ops.sparse_select import (
    Selection,
    SparseConfig,
    gather_segments,
    select_decode,
    write_segments,
)

MIXERS = ("minicpm4", "lightning-attn")


@dataclass(frozen=True)
class MiniCPMSALAConfig:
    vocab_size: int = 73448
    max_seq_len: int = 524288
    d_model: int = 4096
    n_head: int = 32                # minicpm4: query heads
    n_kv_head: int = 2              # minicpm4: K/V heads (what the pool holds)
    head_dim: int = 128
    lightning_n_head: int = 32      # = lightning_nkv: no grouping
    lightning_head_dim: int = 128
    d_mlp: int = 16384
    mixer_types: tuple[str, ...] = (
        "minicpm4", "lightning-attn", "lightning-attn", "lightning-attn")
    # each layer's index in the PUBLISHED stack (its decay slopes); None:
    # the layers are the first of it
    layer_index: tuple[int, ...] | None = None
    n_layer_published: int = 32     # the residual scale and the slopes
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    # the family's sparse_config (assumed: see the module docstring)
    kernel_size: int = 32
    kernel_stride: int = 16
    sparse_block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192
    dtype: Any = jnp.bfloat16
    # decode attention backend / serving quantization: see models/gpt.py
    # GPTConfig. The engine refuses ``quantization`` for this family.
    attention_backend: str = "auto"
    quantization: str | None = None

    def __post_init__(self):
        object.__setattr__(self, "mixer_types", tuple(self.mixer_types))
        index = self.layer_index
        index = tuple(range(self.n_layer)) if index is None else tuple(index)
        object.__setattr__(self, "layer_index", index)
        bad = sorted(set(self.mixer_types) - set(MIXERS))
        if bad:
            raise ValueError(
                f"mixer_types holds {bad}; this family has {MIXERS}")
        if len(index) != self.n_layer or max(index) >= self.n_layer_published:
            raise ValueError(
                "layer_index names each layer's place among the "
                f"{self.n_layer_published} published ones, got {index}")
        if self.n_head % self.n_kv_head:
            raise ValueError("n_head must be a multiple of n_kv_head")
        self.sparse.check()

    @staticmethod
    def tiny(vocab_size: int = 512) -> "MiniCPMSALAConfig":
        return MiniCPMSALAConfig(
            vocab_size=vocab_size, max_seq_len=256, d_model=64, n_head=4,
            n_kv_head=2, head_dim=16, lightning_n_head=4,
            lightning_head_dim=16, d_mlp=128,
            mixer_types=("minicpm4", "lightning-attn", "lightning-attn",
                         "minicpm4"),
            layer_index=(9, 10, 15, 16), dim_model_base=16,
            kernel_size=4, kernel_stride=2, sparse_block_size=8, topk=4,
            init_blocks=1, window_size=16, dense_len=64,
        )

    @property
    def n_layer(self) -> int:
        return len(self.mixer_types)

    @property
    def n_kv_layer(self) -> int:
        """Layers that cache K/V: what the paged pool spans."""
        return self.mixer_types.count("minicpm4")

    @property
    def n_lightning_layer(self) -> int:
        return self.mixer_types.count("lightning-attn")

    @property
    def sparse(self) -> SparseConfig:
        return SparseConfig(
            self.kernel_size, self.kernel_stride, self.sparse_block_size,
            self.topk, self.init_blocks, self.window_size, self.dense_len)

    @property
    def kv_selected_pages(self) -> tuple[int, int]:
        """(tokens a selection block, tokens a segment of compressed
        keys): what the engine holds its page and chunk sizes to."""
        return self.sparse_block_size, self.kernel_stride

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.n_layer_published)


def minicpm_sala_init(key: jax.Array, cfg: MiniCPMSALAConfig) -> dict:
    """Float32 masters, normal from ``key``: each matmul leaf std ``fan_in
    ** -0.5``. The embedding has std ``1 / scale_emb`` (so ``x0`` is of
    unit size, as a mixer's output is) and the head ``fan_in ** -0.5 x
    hidden / dim_model_base`` (it reads ``RMSNorm(h) / 16``: logits of
    about unit spread). The norm scales are ones, but for the ``minicpm4``
    layers' ``q_norm`` / ``k_norm``, 1.4: a NoPE softmax over thousands of
    keys at scores of std 1 is nearly flat and every block's pooled score
    nearly the same; at std 2 some hundred keys carry a row and the
    selection has something to choose (models/smallthinker.py's reason for
    its 1.4 x larger Wq / Wk)."""
    D, M = cfg.d_model, cfg.d_mlp
    Hq, Hkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    LH, lhd = cfg.lightning_n_head, cfg.lightning_head_dim

    def norm(key, *shape, fan_in, gain=1.0):
        return jax.random.normal(key, shape, jnp.float32) * (
            gain * fan_in ** -0.5)

    keys = jax.random.split(key, cfg.n_layer + 2)
    layers = []
    for i, kind in enumerate(cfg.mixer_types):
        k = iter(jax.random.split(keys[i], 8))
        lp: dict = {"mixer_norm": jnp.ones((D,), jnp.float32),
                    "ffn_norm": jnp.ones((D,), jnp.float32)}
        if kind == "lightning-attn":
            lp["lightning_wq"] = norm(next(k), D, LH * lhd, fan_in=D)
            lp["lightning_wk"] = norm(next(k), D, LH * lhd, fan_in=D)
            lp["lightning_wv"] = norm(next(k), D, LH * lhd, fan_in=D)
            lp["lightning_wg"] = norm(next(k), D, LH * lhd, fan_in=D)
            lp["lightning_wo"] = norm(next(k), LH * lhd, D, fan_in=LH * lhd)
            lp["q_norm"] = jnp.ones((lhd,), jnp.float32)
            lp["k_norm"] = jnp.ones((lhd,), jnp.float32)
            lp["lightning_out_norm"] = jnp.ones((LH * lhd,), jnp.float32)
        else:
            lp["wq"] = norm(next(k), D, Hq * hd, fan_in=D)
            lp["wk"] = norm(next(k), D, Hkv * hd, fan_in=D)
            lp["wv"] = norm(next(k), D, Hkv * hd, fan_in=D)
            lp["wg"] = norm(next(k), D, Hq * hd, fan_in=D)
            lp["wo"] = norm(next(k), Hq * hd, D, fan_in=Hq * hd)
            lp["q_norm"] = jnp.full((hd,), 1.4, jnp.float32)
            lp["k_norm"] = jnp.full((hd,), 1.4, jnp.float32)
        lp["mlp_in"] = norm(next(k), D, 2 * M, fan_in=D)  # gate, up
        lp["mlp_out"] = norm(next(k), M, D, fan_in=M)
        layers.append(lp)
    return {
        "wte": norm(keys[-2], cfg.vocab_size, D, fan_in=1,
                    gain=1.0 / cfg.scale_emb),
        "layers": layers,
        "ln_f_scale": jnp.ones((D,), jnp.float32),
        "lm_head": norm(keys[-1], D, cfg.vocab_size, fan_in=D,
                        gain=D / cfg.dim_model_base),
    }


_LEAF_AXES = {
    "mixer_norm": ("embed",), "ffn_norm": ("embed",),
    "lightning_wq": ("embed", "mlp"), "lightning_wk": ("embed", "mlp"),
    "lightning_wv": ("embed", "mlp"), "lightning_wg": ("embed", "mlp"),
    "lightning_wo": ("mlp", "embed"), "lightning_out_norm": (None,),
    "wq": ("embed", "mlp"), "wk": ("embed", "mlp"), "wv": ("embed", "mlp"),
    "wg": ("embed", "mlp"), "wo": ("mlp", "embed"),
    "q_norm": (None,), "k_norm": (None,),
    "mlp_in": ("embed", "mlp"), "mlp_out": ("mlp", "embed"),
    "wte": ("vocab", "embed"), "ln_f_scale": ("embed",),
    "lm_head": ("embed", "vocab"),
}
# the contraction axis of each matmul weight (the embedding's is its
# second); -1: kept as given (norms)
_LEAF_QUANT = {name: int(name == "wte")
               for name, axes in _LEAF_AXES.items() if len(axes) == 2}


def minicpm_sala_param_axes(cfg: MiniCPMSALAConfig) -> dict:
    """Logical axis names per leaf."""
    return leaf_tree(minicpm_sala_init, cfg, _LEAF_AXES.__getitem__)


def minicpm_sala_quant_axes(cfg: MiniCPMSALAConfig) -> dict:
    """Per leaf, the contraction axis of a matmul weight (>= 0: the
    executor stores it in ``cfg.dtype``) or -1."""
    return leaf_tree(minicpm_sala_init, cfg,
                     lambda name: _LEAF_QUANT.get(name, -1))


# ------------------------------------------------------------------ state

# counters[which]: (low, high) uint32 words
COUNTER_LEAVES = ("steps", "blocks")
_STEPS = ("sparse_row_steps", "dense_row_steps")
_BLOCKS = ("sparse_blocks_attended", "sparse_blocks_visible")


def minicpm_sala_init_state(cfg: MiniCPMSALAConfig, slots: int,
                            num_blocks: int) -> dict:
    """What the family keeps beside the pool, zeroed: ``slots`` counts
    slot 0 (the garbage sink of padding rows), ``num_blocks`` the pool's
    block ids (block 0 the garbage sink), which ``ckeys`` is addressed by."""
    H, hd = cfg.lightning_n_head, cfg.lightning_head_dim
    return {
        "lightning": jnp.zeros(
            (cfg.n_lightning_layer, slots, H, hd, hd), jnp.float32),
        "ckeys": jnp.zeros(
            (cfg.n_kv_layer, num_blocks, cfg.sparse.segments,
             cfg.n_kv_head * cfg.head_dim), jnp.float32),
        "steps": jnp.zeros((2, 2), jnp.uint32),
        "blocks": jnp.zeros((2, 2), jnp.uint32),
    }


def block_state_bytes(cfg: MiniCPMSALAConfig) -> int:
    """Bytes ``state`` holds a BLOCK ID (the compressed keys' segment sums
    of every ``minicpm4`` layer): what the cache manager's stats count."""
    return (cfg.n_kv_layer * cfg.sparse.segments * cfg.n_kv_head
            * cfg.head_dim * 4)


def step_attrs(cfg: MiniCPMSALAConfig, kind: str, rows: list) -> dict:
    """What a step's ``executor.dispatch`` span says of the selection
    (decode.py ``Family.step_attrs``), from the positions its real rows
    query, ``rows`` ``[(first position, tokens)]``. A decode step: its
    ``rows``, those at or past ``dense_len`` (``rows_sparse``) and the
    blocks ONE K/V head of one selecting layer attends, summed over rows
    (``sel_blocks``: ``topk`` for a sparse row, every block up to its own
    below). A prefill step: its real ``tokens`` and those of them that
    select (``tokens_sparse``)."""
    sp = cfg.sparse
    if kind == "decode":
        sparse = [first >= sp.dense_len for first, _ in rows]
        return {"rows": len(rows), "rows_sparse": sum(sparse),
                "sel_blocks": sum(
                    sp.topk if s else first // sp.block_size + 1
                    for (first, _), s in zip(rows, sparse))}
    return {"tokens": sum(n for _, n in rows),
            "tokens_sparse": sum(
                max(0, first + n - max(first, sp.dense_len))
                for first, n in rows)}


def minicpm_sala_counters(state: dict) -> dict:
    """``state``'s counters as plain integers (a device->host read)."""
    steps, blocks = count_value(state["steps"]), count_value(state["blocks"])
    return {**{name: int(steps[i]) for i, name in enumerate(_STEPS)},
            **{name: int(blocks[i]) for i, name in enumerate(_BLOCKS)}}


# ----------------------------------------------------------------- layers


def _final_norm(params, x, cfg: MiniCPMSALAConfig):
    h = rms_norm(x, params["ln_f_scale"], cfg.norm_eps)
    return h * jnp.asarray(cfg.dim_model_base / cfg.d_model, h.dtype)


def _cached_embed(params, tokens, step, cfg: MiniCPMSALAConfig):
    x = step.take(params["wte"].astype(cfg.dtype), tokens)
    x = x * jnp.asarray(cfg.scale_emb, x.dtype)
    hd = cfg.lightning_head_dim
    inv_freq = 1.0 / (
        cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = step.pos.astype(jnp.float32)[..., None] * inv_freq
    return x, (jnp.cos(ang), jnp.sin(ang))


def _open_state(state: dict, step, cfg: MiniCPMSALAConfig) -> dict:
    """The step's working state: the two arrays as the layers so far left
    them, the ordinals of the next layer of each kind, and the decode
    rows' selection counts, one entry a ``minicpm4`` layer."""
    return {"lightning": state["lightning"], "ckeys": state["ckeys"],
            "layer": 0, "lightning_done": 0, "sparse_done": 0, "counts": []}


def _lightning_mixer(h, lp, step, work: dict, cfg: MiniCPMSALAConfig):
    B, S, _ = h.shape
    H, hd = cfg.lightning_n_head, cfg.lightning_head_dim
    dtype = cfg.dtype
    states, li, slots = work["lightning"], work["lightning_done"], step.slots
    q = (h @ lp["lightning_wq"].astype(dtype)).reshape(B, S, H, hd)
    k = (h @ lp["lightning_wk"].astype(dtype)).reshape(B, S, H, hd)
    v = (h @ lp["lightning_wv"].astype(dtype)).reshape(B, S, H, hd)
    g = h @ lp["lightning_wg"].astype(dtype)
    q = rope(rms_norm(q, lp["q_norm"], cfg.norm_eps), *step.aux)
    k = rope(rms_norm(k, lp["k_norm"], cfg.norm_eps), *step.aux)
    slopes = lightning_slopes(
        H, cfg.layer_index[work["layer"]], cfg.n_layer_published)
    scale = 1.0 / math.sqrt(hd)
    if step.kind == "decode" and resolve_backend(
            cfg.attention_backend) == "pallas":
        # the rows' states are updated where they stand
        o, states = lightning_step_pallas(
            q[:, 0], k[:, 0], v[:, 0], states, li, slots, slopes, scale)
        o = o[:, None]
    else:
        with jax.named_scope("attn_cache"):  # the slots' rows, read
            before = states[li, slots]
        if step.kind == "decode":
            o, after = lightning_step(q[:, 0], k[:, 0], v[:, 0], before,
                                      slopes, scale)
            o = o[:, None]
        else:
            # a row whose chunk starts its sequence begins from zeros,
            # whatever the slot held
            if step.kind == "fresh":
                before = jnp.zeros_like(before)
            else:
                before = jnp.where((step.start > 0)[:, None, None, None],
                                   before, 0.0)
            o, after = lightning_chunk(q, k, v, before, step.rows, slopes,
                                       scale)
        with jax.named_scope("attn_cache"):  # ... and written back
            states = states.at[li, slots].set(after)
    work = {**work, "lightning_done": li + 1, "lightning": states}
    o = rms_norm(o.reshape(B, S, H * hd), lp["lightning_out_norm"],
                 cfg.norm_eps)
    y = (o * jax.nn.sigmoid(g.astype(jnp.float32)).astype(dtype)) \
        @ lp["lightning_wo"].astype(dtype)
    return y, work


def _sparse_mixer(h, lp, attend, step, work: dict, cfg: MiniCPMSALAConfig):
    B, S, _ = h.shape
    Hq, Hkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    dtype, sparse = cfg.dtype, cfg.sparse
    q = (h @ lp["wq"].astype(dtype)).reshape(B, S, Hq, hd)
    k = (h @ lp["wk"].astype(dtype)).reshape(B, S, Hkv, hd)
    v = (h @ lp["wv"].astype(dtype)).reshape(B, S, Hkv, hd)
    g = h @ lp["wg"].astype(dtype)
    q = rms_norm(q, lp["q_norm"], cfg.norm_eps)   # no positional encoding
    k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    si, tables = work["sparse_done"], step.block_tables
    scale = 1.0 / math.sqrt(hd)
    if step.kind == "decode":
        segs = write_segments(work["ckeys"], si, k[:, 0].reshape(B, -1),
                              step.rows, tables, sparse)
        pages, vpos, counts = select_decode(
            q[:, 0], gather_segments(segs, si, tables), step.rows, tables,
            sparse, Hkv, scale)
        work = {**work, "counts": [*work["counts"], counts]}
        select = Selection(sparse, pages=pages, vpos=vpos)
    else:
        segs = write_segments(work["ckeys"], si, k.reshape(B, S, -1),
                              step.pos, tables, sparse, step.valid)
        select = Selection(sparse,
                           seg_rows=gather_segments(segs, si, tables))
    work = {**work, "ckeys": segs, "sparse_done": si + 1}
    attn = attend(q, k, v, select=select)
    y = (attn * jax.nn.sigmoid(g.astype(jnp.float32)).astype(dtype)) \
        @ lp["wo"].astype(dtype)
    return y, work


def _cached_layer(x, lp, attend, step, work: dict, cfg: MiniCPMSALAConfig):
    a = jnp.asarray(cfg.residual_scale, cfg.dtype)
    with jax.named_scope("attn_proj"):
        h = rms_norm(x, lp["mixer_norm"], cfg.norm_eps)
        if "lightning_wq" in lp:
            y, work = _lightning_mixer(h, lp, step, work, cfg)
        else:
            y, work = _sparse_mixer(h, lp, attend, step, work, cfg)
        x = x + a * y
    with jax.named_scope("ffn"):
        h = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
        x = x + a * swiglu(h, lp["mlp_in"], lp["mlp_out"], cfg.dtype)
    return x, {**work, "layer": work["layer"] + 1}


def _close_state(state: dict, work: dict, step, cfg: MiniCPMSALAConfig):
    """The next ``state``: the arrays as the step left them, and a decode
    step's row-steps and selected blocks added to the counters (real rows
    only: a padding row sits in slot 0)."""
    steps, blocks = state["steps"], state["blocks"]
    if work["counts"]:
        real = step.slots > 0
        sparse = work["counts"][0]["sparse"] & real
        steps = count_add(steps, jnp.stack(
            [jnp.sum(sparse), jnp.sum(real & ~sparse)]))
        blocks = count_add(blocks, jnp.stack([
            sum(jnp.sum(jnp.where(sparse, c[name], 0))
                for c in work["counts"])
            for name in ("attended", "visible")]))
    return {"lightning": work["lightning"], "ckeys": work["ckeys"],
            "steps": steps, "blocks": blocks}


FAMILY = cached.CachedFamily(
    "minicpm_sala", MiniCPMSALAConfig, "layers", _cached_embed,
    _cached_layer, _final_norm, head_untied, open_state=_open_state,
    close_state=_close_state,
    no_verify="rejected drafts would need the lightning state (a matrix a "
              "head a sequence) rolled back",
    block_state_bytes=block_state_bytes, step_attrs=step_attrs,
    donated_state_counters=COUNTER_LEAVES)
minicpm_sala_prefill, minicpm_sala_decode_step, _ = cached.steps(FAMILY)
