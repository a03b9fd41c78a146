"""The seam between models/cached.py's one cached step and the families
(CPU, float32, XLA attention backend, tiny presets, seeded weights).

Every family x kind of step gives, through the paged cache, the logits of
the family's own full-sequence forward at the same positions; and for the
families that have all three kinds, a decode step IS the one-column verify
window IS the one-token prompt chunk at the row's position: the identity
the skeleton states in code.

Tolerance: float32 on both sides, the same mathematics in another order of
sums (a paged softmax against a dense one): 1e-4 on logits of size ~1-5
(seen 3e-6). The K/V rows a step writes come from the same projections of
the same rows whatever its kind: bit for bit in the first layer, and past
it as close as the attention formulations that feed the next layer (decode
and chunk attention sum in another order: seen 4e-8).
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

BS, NB = 8, 4            # block size, blocks a row
LENGTHS = (13, 9)        # the two sequences; the bucket pads them to 16
KINDS = ("fresh", "chunk", "decode", "verify")


@pytest.fixture(scope="module")
def families(jax_cpu):
    """family -> (Family, float32 config, params, full [B][L, V] logits,
    the sequences)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import gpt_forward
    from ray_tpu.models.lfm2_moe import lfm2_moe_forward
    from ray_tpu.models.llama import llama_forward
    from ray_tpu.serve.llm.decode import get_family

    rng = np.random.default_rng(28)
    seqs = [rng.integers(1, 500, n).astype(np.int32) for n in LENGTHS]
    forwards = {"gpt": gpt_forward, "llama": llama_forward,
                "lfm2_moe": lfm2_moe_forward}
    out = {}
    for name, forward in forwards.items():
        fam = get_family(name)
        cfg = dataclasses.replace(
            fam.default_config(), dtype=jnp.float32, attention_backend="xla",
            **({} if name == "lfm2_moe" else {"attention": "xla"}))
        params = fam.init(jax.random.PRNGKey(1), cfg)
        full = [np.asarray(forward(params, jnp.asarray(s[None]), cfg))[0]
                for s in seqs]
        out[name] = fam, cfg, params, full, seqs
    return out


class _Served:
    """Two rows in a bucket of two, each with its own blocks and state slot,
    driven through the family's step functions as the executor calls them."""

    def __init__(self, fam, cfg, params):
        import jax.numpy as jnp

        self.fam, self.cfg, self.params = fam, cfg, params
        n_kv = getattr(cfg, "n_kv_head", cfg.n_head)
        self.k = self.v = jnp.zeros(
            (getattr(cfg, "n_kv_layer", cfg.n_layer), 1 + 2 * NB, BS, n_kv,
             cfg.head_dim), cfg.dtype)
        self.tables = jnp.asarray(
            [[1 + r * NB + i for i in range(NB)] for r in range(2)],
            jnp.int32)
        self.state, self.slots = None, None
        if fam.init_state is not None:
            self.state = fam.init_state(cfg, 3)
            self.slots = jnp.asarray([1, 2], jnp.int32)

    def _run(self, step, *arrays, **kw):
        import jax.numpy as jnp

        out, self.k, self.v, self.state = step(
            self.params, self.k, self.v, *map(jnp.asarray, arrays),
            self.tables, self.cfg, state=self.state, slots=self.slots, **kw)
        return np.asarray(out)

    def prefill(self, parts, start=None):
        """``parts``: each row's tokens, right-padded to the longest."""
        import jax.numpy as jnp

        tokens = np.zeros((2, max(map(len, parts))), np.int32)
        for r, part in enumerate(parts):
            tokens[r, :len(part)] = part
        lengths = np.asarray([len(p) for p in parts], np.int32)
        kw = {} if start is None else {
            "start": jnp.asarray(start, jnp.int32)}
        return self._run(self.fam.prefill, tokens, lengths, **kw)

    def decode(self, tokens, positions):
        return self._run(self.fam.decode_step, np.asarray(tokens, np.int32),
                         np.asarray(positions, np.int32))

    def verify(self, windows, starts, draft_len):
        return self._run(self.fam.verify_step, np.asarray(windows, np.int32),
                         np.asarray(starts, np.int32),
                         np.asarray(draft_len, np.int32))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("family", ["gpt", "llama", "lfm2_moe"])
def test_cached_logits_match_full_forward(families, family, kind):
    fam, cfg, params, full, seqs = families[family]
    if kind == "verify" and fam.verify_step is None:
        # its state cannot be rolled back: the engine never speculates
        assert family == "lfm2_moe"
        return
    served = _Served(fam, cfg, params)
    last = [full[r][len(s) - 1] for r, s in enumerate(seqs)]
    if kind == "fresh":
        got = served.prefill(seqs)
    elif kind == "chunk":
        cut = [8, 5]
        served.prefill([s[:c] for s, c in zip(seqs, cut)])
        got = served.prefill([s[c:] for s, c in zip(seqs, cut)], start=cut)
    elif kind == "decode":
        served.prefill([s[:-1] for s in seqs])
        got = served.decode([s[-1] for s in seqs],
                            [len(s) - 1 for s in seqs])
    else:
        # windows of 4 over each row's last tokens: row 0 all drafts, row 1
        # one draft and two padding columns
        W, draft_len = 4, [3, 1]
        starts = [len(s) - 1 - d for s, d in zip(seqs, draft_len)]
        served.prefill([s[:n] for s, n in zip(seqs, starts)])
        windows = np.zeros((2, W), np.int32)
        for r, (s, n, d) in enumerate(zip(seqs, starts, draft_len)):
            windows[r, :d + 1] = s[n:n + d + 1]
        got = served.verify(windows, starts, draft_len)
        for r, (n, d) in enumerate(zip(starts, draft_len)):
            np.testing.assert_allclose(
                got[r, :d + 1], full[r][n:n + d + 1], atol=1e-4, rtol=1e-4)
        return
    np.testing.assert_allclose(got, np.stack(last), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("as_kind", ["verify", "chunk"])
@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_decode_is_the_one_token_case(families, family, as_kind):
    """decode_step == verify_step with W = 1, draft_len = 0 ==
    prefill(start=positions) with S = 1: the same logits, and the same
    pool afterwards."""
    fam, cfg, params, _, seqs = families[family]
    tokens = [s[-1] for s in seqs]
    positions = [len(s) - 1 for s in seqs]
    ends = []
    for kind in ("decode", as_kind):
        served = _Served(fam, cfg, params)
        served.prefill([s[:-1] for s in seqs])
        if kind == "decode":
            logits = served.decode(tokens, positions)
        elif kind == "verify":
            logits = served.verify(
                [[t] for t in tokens], positions, [0, 0])[:, 0]
        else:
            logits = served.prefill([[t] for t in tokens], start=positions)
        ends.append((logits, np.asarray(served.k), np.asarray(served.v)))
    (want, k, v), (got, k2, v2) = ends
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    for a, b in ((k2, k), (v2, v)):  # block 0 is the garbage sink
        np.testing.assert_array_equal(a[0, 1:], b[0, 1:])
        np.testing.assert_allclose(a[:, 1:], b[:, 1:], atol=1e-6)
    assert np.abs(k[:, 1:]).sum() > 0
