"""Lightning (linear) attention: a matrix of state a head, a scalar decay.

The sequence operator of a ``lightning-attn`` layer (models/minicpm_sala.py;
Lightning Attention, arXiv:2401.04658, as MiniMax-01 builds it). Per head
``h`` with decay ``lam = exp(-slope_h)`` and a float32 state ``S``
``[hd, hd]`` that is zero where a sequence starts::

    S_t = lam * S_{t-1} + k_t^T v_t          o_t = (q_t S_t) * scale

The state is of FIXED size whatever the context (32 heads of 128: 2.10 MB a
sequence a layer), kept beside the paged pool in a slot a sequence.

Two forms that agree (tests/test_minicpm_sala.py holds them to each other and
to the masked-decay product, the ``O(n^2)`` definition):

- ``lightning_step``: one token a row (decode);
- ``lightning_chunk``: a chunk of a right-padded row, cut into pieces of
  ``piece`` tokens that a ``lax.scan`` walks with the state as carry. With
  ``c_i`` the count of REAL tokens of the piece up to and including ``i``
  (padding does not decay the state and adds nothing to it)::

      o_i    = scale * ( lam^{c_i} q_i S_prev
                         + sum_{j <= i} lam^{c_i - c_j} (q_i . k_j) v_j )
      S_next = lam^{c_last} S_prev + sum_j lam^{c_last - c_j} k_j^T v_j

  The decay enters as ``exp(-slope * (c_i - c_j))`` with ``c_i >= c_j``:
  no power of ``lam`` is ever inverted, so nothing overflows at any slope.

``lightning_chunk`` is plain ``jax.numpy`` under the named scope of its
name (XLA's formulation; a Pallas body is ROADMAP R6's: on the chip the
scan is 1.3 ms of a 100 ms prefill step). The decode step has both: XLA's
``lightning_step`` and the kernel ``lightning_step`` (``lightning_step_
pallas``), which updates the rows' states WHERE THEY STAND in the slots'
array (``state`` ``[n_layer, slots, H, hd, hd]`` aliased in and out, a
block a (row, group of heads) found through the rows' slots): XLA's form
gathers the rows' states, updates them and scatters them back, three
passes over bytes the kernel moves once each way. Products take the
operands' dtype with float32 accumulation; the state stays float32 and ``q
S`` is taken in float32 (it is a few per cent of a step's work and the
state's low bits are what a long context is made of).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

STEP_KERNEL_NAME = "lightning_step"
# heads a grid step of the decode kernel: a [16, 128, 128] float32 tile is
# 1 MB each way
STEP_HEADS = 16
# tokens a piece of the chunked form: intra-piece work is 4 * hd * PIECE
# flop a token a head, the carried state's 4 * hd * hd
PIECE = 128


def lightning_slopes(n_head: int, layer: int, n_layer: int):
    """The heads' decay slopes ``s_h`` [n_head] float32 (numpy: constants
    of the traced program) of the layer with PUBLISHED index ``layer`` of
    ``n_layer``: ``2 ** (-8 (h + 1) / n_head) * (1 - layer / (n_layer - 1)
    + 1e-5)``, Lightning Attention's slopes as MiniMax-01 builds them."""
    import numpy as np

    base = np.float32(2.0) ** (
        -8.0 * np.arange(1, n_head + 1, dtype=np.float32) / n_head)
    return (base * np.float32(1 - layer / (n_layer - 1) + 1e-5)).astype(
        np.float32)


def lightning_step(q: jax.Array, k: jax.Array, v: jax.Array,
                   state: jax.Array, slopes, scale: float):
    """One token a row: ``q``, ``k``, ``v`` [B, H, hd], ``state`` [B, H,
    hd, hd] float32. Returns (o [B, H, hd] in q's dtype, the next state)."""
    with jax.named_scope("lightning_step"):
        lam = jnp.exp(-jnp.asarray(slopes, jnp.float32))[None, :, None, None]
        kv = k.astype(jnp.float32)[..., :, None] * v.astype(
            jnp.float32)[..., None, :]
        state = lam * state + kv
        o = jnp.einsum("bhd,bhde->bhe", q.astype(jnp.float32), state,
                       precision=lax.Precision.HIGHEST)
        return (o * scale).astype(q.dtype), state


def _lightning_step_kernel(slots_ref, layer_ref, lam_ref, q_ref, k_ref,
                           v_ref, s_ref, o_ref, s_out_ref, *, scale: float):
    """One (row, group of heads): ``S <- lam S + k^T v`` where the state
    stands, ``o = q S * scale``. q and k arrive as COLUMNS ``[heads, hd,
    1]`` and v as a row ``[heads, 1, hd]``, so the outer product and the
    product with the query are broadcasts and a sum over sublanes."""
    del slots_ref, layer_ref  # the index maps read them
    S = lam_ref[...] * s_ref[0, 0] + k_ref[0] * v_ref[0]   # [heads, hd, hd]
    s_out_ref[0, 0] = S
    o_ref[0] = (jnp.sum(q_ref[0] * S, axis=1) * scale).astype(o_ref.dtype)


def lightning_step_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                          states: jax.Array, layer, slots: jax.Array,
                          slopes, scale: float, interpret=None):
    """The decode step over the slots' array itself: ``q``, ``k``, ``v``
    [B, H, hd], ``states`` [n_layer, n_slots, H, hd, hd] float32, the
    rows' ``slots`` [B] (padding rows share slot 0, the garbage sink).
    Returns (o [B, H, hd] in q's dtype, ``states`` with the rows' states at
    ``layer`` updated: the same buffer where the caller donated it)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.ops.attention import pallas_interpret

    if interpret is None:
        interpret = pallas_interpret()
    B, H, hd = q.shape
    hb = STEP_HEADS if H % STEP_HEADS == 0 else H
    f32 = jnp.float32
    lam = jnp.exp(-jnp.asarray(slopes, f32)).reshape(H, 1, 1)
    col = lambda a: a.astype(f32).reshape(B, H, hd, 1)  # noqa: E731
    row_map = lambda b, j, *refs: (b, j, 0, 0)  # noqa: E731

    def state_map(b, j, slots_ref, layer_ref):
        return (layer_ref[0], slots_ref[b], j, 0, 0)

    state_spec = pl.BlockSpec((1, 1, hb, hd, hd), state_map)
    o, states = pl.pallas_call(
        functools.partial(_lightning_step_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // hb),
            in_specs=[
                pl.BlockSpec((hb, 1, 1), lambda b, j, *refs: (j, 0, 0)),
                pl.BlockSpec((1, hb, hd, 1), row_map),
                pl.BlockSpec((1, hb, hd, 1), row_map),
                pl.BlockSpec((1, hb, 1, hd), row_map),
                state_spec,
            ],
            out_specs=[pl.BlockSpec((1, hb, hd), lambda b, j, *refs: (b, j, 0)),
                       state_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, hd), q.dtype),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        # operands: slots, layer, lam, q, k, v, states -> states is 6
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name=STEP_KERNEL_NAME,
        interpret=interpret,
    )(slots.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      lam, col(q), col(k), v.astype(f32).reshape(B, H, 1, hd), states)
    return o, states


def lightning_chunk(q: jax.Array, k: jax.Array, v: jax.Array,
                    state: jax.Array, lengths: jax.Array, slopes,
                    scale: float, piece: int = PIECE):
    """A chunk of right-padded rows: ``q``, ``k``, ``v`` [B, S, H, hd],
    ``state`` [B, H, hd, hd] float32 (the rows' states before the chunk:
    zeros where a sequence starts), ``lengths`` [B] the real tokens.
    Returns (o [B, S, H, hd] in q's dtype, the state after token
    ``lengths - 1``)."""
    B, S, H, hd = q.shape
    c = min(piece, S)
    n = -(-S // c)
    pad = n * c - S
    with jax.named_scope("lightning_chunk"):
        if pad:
            q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                       for a in (q, k, v))
        slopes = jnp.asarray(slopes, jnp.float32)
        cols = jnp.arange(n * c, dtype=jnp.int32)
        real = cols[None, :] < lengths[:, None]                    # [B, S]
        k = jnp.where(real[..., None, None], k, 0)
        # pieces lead: [n, B, c, H, hd]
        qs, ks, vs = (a.reshape(B, n, c, H, hd).transpose(1, 0, 2, 3, 4)
                      for a in (q, k, v))
        # real tokens of the piece up to and including each column
        count = jnp.clip(
            lengths[None, :, None] - (jnp.arange(n) * c)[:, None, None],
            0, jnp.arange(1, c + 1)[None, None, :]).astype(jnp.float32)

        def one(S_prev, xs):
            qp, kp, vp, cnt = xs                   # [B, c, H, hd]; [B, c]
            # decay between columns: exp(-s (c_i - c_j)) where j <= i
            gap = cnt[:, :, None] - cnt[:, None, :]               # [B, c, c]
            causal = jnp.tril(jnp.ones((c, c), bool))
            decay = jnp.where(
                causal[None, None],
                jnp.exp(-slopes[None, :, None, None] * gap[:, None]), 0.0)
            scores = jnp.einsum("bihd,bjhd->bhij", qp, kp,
                                preferred_element_type=jnp.float32)
            intra = jnp.einsum(
                "bhij,bjhd->bihd", (scores * decay).astype(vp.dtype), vp,
                preferred_element_type=jnp.float32)
            head = jnp.exp(-slopes[None, None, :] * cnt[:, :, None])
            inter = jnp.einsum(
                "bihd,bhde->bihe", qp.astype(jnp.float32), S_prev,
                precision=lax.Precision.HIGHEST) * head[..., None]
            last = cnt[:, -1]                                      # [B]
            tail = jnp.exp(-slopes[None, None, :]
                           * (last[:, None] - cnt)[:, :, None])   # [B,c,H]
            kd = (kp.astype(jnp.float32) * tail[..., None]).astype(vp.dtype)
            S_next = jnp.exp(-slopes[None, :] * last[:, None])[
                ..., None, None] * S_prev + jnp.einsum(
                    "bjhd,bjhe->bhde", kd, vp,
                    preferred_element_type=jnp.float32)
            return S_next, ((intra + inter) * scale).astype(q.dtype)

        state, out = lax.scan(one, state.astype(jnp.float32),
                              (qs, ks, vs, count))
        out = out.transpose(1, 0, 2, 3, 4).reshape(B, n * c, H, hd)
        return out[:, :S], state
