"""Gated short convolution: a depthwise causal filter of a few taps.

The sequence operator of the LFM2 family's ``conv`` layers
(models/lfm2_moe.py). With ``[B, C, u] = split3(W_in x)`` and
``v = B * u``::

    z_t = sum_j w[j] * v[t - (K-1) + j]      j = 0..K-1, zeros before 0
    Op(x)_t = W_out (C_t * z_t)

The projections are the model's; this file holds the filter and its gate.
Decoding needs, per sequence and layer, the last ``K - 1`` rows of ``v``:
a state of fixed size, not one that grows with the context. Both forms
take that state as ``[B, K-1, D]`` (oldest row first) and return the next.

Plain ``jax.numpy``: K is 3, so the filter is three shifted multiplies
that XLA fuses with the gate; float32 inside, the caller's dtype out.

The PLAIN form (``gate=None``; models/ling_hybrid.py's KDA layers: width
4 over ``[q | k | v]``, then SiLU): the same filter and the same state,
with ``act`` on ``z`` where the gated form multiplies by ``C``, under the
caller's ``scope``; with a ``bias`` [D] it is ``act(z + bias)``
(models/falcon_h1.py: Mamba-2's convolution over ``[x | B | C]``,
``mamba_conv_bias``). Without one nothing is added: the other callers'
programs hold the operations they held.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _named(scope: str | None):
    """The form's named scope: ``short_conv``, or the caller's (a name of
    serve/llm/obs.py ``SCOPES``)."""
    return jax.named_scope("short_conv") if scope is None \
        else jax.named_scope(scope)


def _finish(z, gate, act, bias=None):
    """``z`` float32 through the bias, then the gate or the activation of
    the form."""
    if bias is not None:
        z = z + bias.astype(jnp.float32)
    if gate is not None:
        z = gate.astype(jnp.float32) * z
    return z if act is None else act(z)


def short_conv_prefill(v: jax.Array, gate: jax.Array | None, w: jax.Array,
                       state: jax.Array | None, lengths: jax.Array, *,
                       act=None, scope: str | None = None,
                       bias: jax.Array | None = None):
    """A chunk of positions: ``v``, ``gate`` [B, S, D], filter ``w`` [K, D],
    ``state`` [B, K-1, D] holding the rows before the chunk (None: the
    chunk starts the sequence, zeros), ``lengths`` [B] the valid columns.
    Returns (``gate * z`` [B, S, D], the state after column
    ``lengths - 1`` [B, K-1, D]). ``gate=None``: the plain form, ``act(z)``."""
    B, S, D = v.shape
    K = w.shape[0]
    with _named(scope):
        if state is None:
            state = jnp.zeros((B, K - 1, D), v.dtype)
        ext = jnp.concatenate([state.astype(v.dtype), v], axis=1)
        w32 = w.astype(jnp.float32)
        z = sum(ext[:, j:j + S].astype(jnp.float32) * w32[j]
                for j in range(K))
        # row i of the next state is position lengths - (K-1) + i, which
        # is column lengths + i of ``ext``
        cols = lengths[:, None] + jnp.arange(K - 1, dtype=lengths.dtype)
        nxt = jnp.take_along_axis(ext, cols[:, :, None], axis=1)
        return _finish(z, gate, act, bias).astype(v.dtype), nxt


def short_conv_decode(v: jax.Array, gate: jax.Array | None, w: jax.Array,
                      state: jax.Array, *, act=None,
                      scope: str | None = None,
                      bias: jax.Array | None = None):
    """One position: ``v``, ``gate`` [B, D], ``state`` [B, K-1, D].
    Returns (``gate * z`` [B, D], the next state)."""
    with _named(scope):
        ext = jnp.concatenate([state.astype(v.dtype), v[:, None]], axis=1)
        w32 = w.astype(jnp.float32)
        # the same three products in the same order as the chunked form
        z = sum(ext[:, j].astype(jnp.float32) * w32[j]
                for j in range(w.shape[0]))
        return _finish(z, gate, act, bias).astype(v.dtype), ext[:, 1:]
