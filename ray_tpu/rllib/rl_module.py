"""RLModule — policy/value networks as pure param pytrees.

Equivalent of the reference's RLModule (reference: rllib/core/rl_module/
rl_module.py:229; torch/tf models rllib/models/; a jax model dir exists at
rllib/models/jax/). Two forward paths over the SAME params:

  * `forward` — jax, jitted inside the Learner's update on the device mesh.
  * `forward_np` — numpy, used by CPU EnvRunner actors for action sampling
    (no jax runtime in rollout workers: sampling a 2x64 MLP is
    memory-latency-bound, and keeping jax out of the env actors keeps them
    lightweight and off the TPU — SURVEY.md §3.5 TPU mapping).
"""
from __future__ import annotations

from typing import Sequence

import numpy as np


def _init_linear(rng: np.random.Generator, n_in: int, n_out: int, scale: float):
    # orthogonal init (standard for PPO stability)
    a = rng.normal(size=(n_in, n_out))
    q, r = np.linalg.qr(a) if n_in >= n_out else np.linalg.qr(a.T)
    q = q if n_in >= n_out else q.T
    q = q[:n_in, :n_out]
    return {
        "w": (scale * q).astype(np.float32),
        "b": np.zeros(n_out, np.float32),
    }


def _mlp(xp, layers, x):
    """Backend-generic tanh-MLP forward (xp = np | jnp) — the single
    implementation behind both rollout (numpy) and learner (jax) paths."""
    for layer in layers[:-1]:
        x = xp.tanh(x @ layer["w"] + layer["b"])
    last = layers[-1]
    return x @ last["w"] + last["b"]


def _mlp_jax(layers, x):
    import jax.numpy as jnp

    return _mlp(jnp, layers, x)


class ActorCriticModule:
    """Tanh-MLP trunk with separate policy/value heads (discrete actions)."""

    def __init__(self, obs_dim: int, num_actions: int, hidden: Sequence[int] = (64, 64)):
        self.obs_dim = obs_dim
        self.num_actions = num_actions
        self.hidden = tuple(hidden)

    def init(self, seed: int = 0) -> dict:
        rng = np.random.default_rng(seed)
        params: dict = {"pi": [], "vf": []}
        for head, out_dim, out_scale in (
            ("pi", self.num_actions, 0.01),
            ("vf", 1, 1.0),
        ):
            dims = [self.obs_dim, *self.hidden]
            layers = [
                _init_linear(rng, dims[i], dims[i + 1], np.sqrt(2))
                for i in range(len(dims) - 1)
            ]
            layers.append(_init_linear(rng, dims[-1], out_dim, out_scale))
            params[head] = layers
        return params

    # -- numpy path (EnvRunner) --

    @staticmethod
    def _mlp_np(layers: list[dict], x: np.ndarray) -> np.ndarray:
        for layer in layers[:-1]:
            x = np.tanh(x @ layer["w"] + layer["b"])
        last = layers[-1]
        return x @ last["w"] + last["b"]

    def forward_np(self, params: dict, obs: np.ndarray):
        """(logits [B, A], values [B])."""
        logits = self._mlp_np(params["pi"], obs)
        values = self._mlp_np(params["vf"], obs)[:, 0]
        return logits, values

    def sample_actions_np(
        self, params: dict, obs: np.ndarray, rng: np.random.Generator
    ):
        """(actions, logp, values) — categorical sampling via Gumbel trick."""
        logits, values = self.forward_np(params, obs)
        z = logits - logits.max(axis=-1, keepdims=True)
        logp_all = z - np.log(np.exp(z).sum(axis=-1, keepdims=True))
        gumbel = -np.log(-np.log(rng.uniform(1e-10, 1.0, logits.shape)))
        actions = np.argmax(logits + gumbel, axis=-1)
        logp = np.take_along_axis(logp_all, actions[:, None], axis=-1)[:, 0]
        return actions.astype(np.int32), logp.astype(np.float32), values.astype(np.float32)

    # -- jax path (Learner) --

    def forward(self, params, obs):
        """Same math in jax; called inside the jitted learner update."""
        logits = _mlp_jax(params["pi"], obs)
        values = _mlp_jax(params["vf"], obs)[:, 0]
        return logits, values


class QModule:
    """Q-network MLP for value-based algorithms (DQN family). With
    `dueling`, the net splits into value + advantage streams recombined as
    Q = V + A - mean(A) (reference: dqn_torch_model.py dueling heads,
    Wang et al. 2016)."""

    def __init__(self, obs_dim: int, num_actions: int,
                 hidden: Sequence[int] = (64, 64), dueling: bool = False):
        self.obs_dim = obs_dim
        self.num_actions = num_actions
        self.hidden = tuple(hidden)
        self.dueling = dueling

    def init(self, seed: int = 0) -> dict:
        rng = np.random.default_rng(seed)
        if not self.dueling:
            dims = [self.obs_dim, *self.hidden, self.num_actions]
            return {
                "q": [
                    _init_linear(rng, dims[i], dims[i + 1], np.sqrt(2))
                    for i in range(len(dims) - 1)
                ]
            }
        dims = [self.obs_dim, *self.hidden]
        trunk = [
            _init_linear(rng, dims[i], dims[i + 1], np.sqrt(2))
            for i in range(len(dims) - 1)
        ]
        return {
            "trunk": trunk,
            "v": [_init_linear(rng, dims[-1], 1, 1.0)],
            "a": [_init_linear(rng, dims[-1], self.num_actions, 0.01)],
        }

    def forward_np(self, params: dict, obs: np.ndarray) -> np.ndarray:
        if not self.dueling:
            return ActorCriticModule._mlp_np(params["q"], obs)
        h = obs
        for layer in params["trunk"]:
            h = np.tanh(h @ layer["w"] + layer["b"])
        v = h @ params["v"][0]["w"] + params["v"][0]["b"]
        a = h @ params["a"][0]["w"] + params["a"][0]["b"]
        return v + a - a.mean(axis=-1, keepdims=True)

    def forward(self, params, obs):
        import jax.numpy as jnp

        if not self.dueling:
            return _mlp_jax(params["q"], obs)
        h = obs
        for layer in params["trunk"]:
            h = jnp.tanh(h @ layer["w"] + layer["b"])
        v = h @ params["v"][0]["w"] + params["v"][0]["b"]
        a = h @ params["a"][0]["w"] + params["a"][0]["b"]
        return v + a - jnp.mean(a, axis=-1, keepdims=True)


class DistributionalQModule:
    """C51 categorical value network (Bellemare et al. 2017; reference:
    dqn_torch_model.py num_atoms>1 path). The head emits per-action
    logits over `n_atoms` fixed support points z in [v_min, v_max];
    `forward`/`forward_np` collapse to the expected Q so epsilon-greedy
    EnvRunners and the target-selection code are distribution-agnostic,
    while `logits` exposes the full distribution to the C51 loss."""

    def __init__(self, obs_dim: int, num_actions: int,
                 hidden: Sequence[int] = (64, 64), n_atoms: int = 51,
                 v_min: float = -10.0, v_max: float = 10.0):
        self.obs_dim = obs_dim
        self.num_actions = num_actions
        self.hidden = tuple(hidden)
        self.n_atoms = n_atoms
        self.v_min = float(v_min)
        self.v_max = float(v_max)
        self.support = np.linspace(v_min, v_max, n_atoms).astype(np.float32)

    def init(self, seed: int = 0) -> dict:
        rng = np.random.default_rng(seed)
        dims = [self.obs_dim, *self.hidden, self.num_actions * self.n_atoms]
        return {
            "q": [
                _init_linear(rng, dims[i], dims[i + 1],
                             np.sqrt(2) if i < len(dims) - 2 else 0.01)
                for i in range(len(dims) - 1)
            ]
        }

    def logits(self, params, obs):
        """[B, num_actions, n_atoms] (jax)."""
        out = _mlp_jax(params["q"], obs)
        return out.reshape(*out.shape[:-1], self.num_actions, self.n_atoms)

    def forward(self, params, obs):
        import jax
        import jax.numpy as jnp

        probs = jax.nn.softmax(self.logits(params, obs), axis=-1)
        return jnp.sum(probs * jnp.asarray(self.support), axis=-1)

    def forward_np(self, params: dict, obs: np.ndarray) -> np.ndarray:
        out = ActorCriticModule._mlp_np(params["q"], obs)
        out = out.reshape(*out.shape[:-1], self.num_actions, self.n_atoms)
        out = out - out.max(axis=-1, keepdims=True)
        p = np.exp(out)
        p /= p.sum(axis=-1, keepdims=True)
        return (p * self.support).sum(axis=-1)


class DeterministicPolicyModule:
    """Actor-critic pair for continuous control: tanh-bounded deterministic
    actor pi(s) and twin Q(s, a) critics (reference: rllib's DDPG/TD3
    models — ddpg/ddpg_torch_model.py actor + twin critics per TD3,
    Fujimoto et al. 2018)."""

    def __init__(self, obs_dim: int, action_dim: int, action_bound: float,
                 hidden: Sequence[int] = (64, 64), twin_q: bool = True):
        self.obs_dim = obs_dim
        self.action_dim = action_dim
        self.action_bound = float(action_bound)
        self.hidden = tuple(hidden)
        self.twin_q = twin_q

    def init(self, seed: int = 0) -> dict:
        rng = np.random.default_rng(seed)
        params: dict = {}
        dims_pi = [self.obs_dim, *self.hidden]
        layers = [
            _init_linear(rng, dims_pi[i], dims_pi[i + 1], np.sqrt(2))
            for i in range(len(dims_pi) - 1)
        ]
        layers.append(_init_linear(rng, dims_pi[-1], self.action_dim, 0.01))
        params["pi"] = layers
        heads = ("q1", "q2") if self.twin_q else ("q1",)
        for head in heads:
            dims_q = [self.obs_dim + self.action_dim, *self.hidden]
            layers = [
                _init_linear(rng, dims_q[i], dims_q[i + 1], np.sqrt(2))
                for i in range(len(dims_q) - 1)
            ]
            layers.append(_init_linear(rng, dims_q[-1], 1, 1.0))
            params[head] = layers
        return params

    # -- numpy path (EnvRunner action selection) --

    def policy_np(self, params: dict, obs: np.ndarray) -> np.ndarray:
        raw = ActorCriticModule._mlp_np(params["pi"], obs)
        return np.tanh(raw) * self.action_bound

    # -- jax path (Learner) --

    def policy(self, params, obs):
        import jax.numpy as jnp

        return jnp.tanh(_mlp_jax(params["pi"], obs)) * self.action_bound

    def q_value(self, params, obs, actions, head: str = "q1"):
        import jax.numpy as jnp

        x = jnp.concatenate([obs, actions], axis=-1)
        return _mlp_jax(params[head], x)[:, 0]


def _gru_init(rng: np.random.Generator, n_in: int, hidden: int) -> dict:
    """GRU cell params: fused r/z/n gates ([n_in,3H] + [H,3H] + [3H])."""
    scale_x = np.sqrt(1.0 / n_in)
    scale_h = np.sqrt(1.0 / hidden)
    return {
        "wx": (rng.standard_normal((n_in, 3 * hidden)) * scale_x).astype(np.float32),
        "wh": (rng.standard_normal((hidden, 3 * hidden)) * scale_h).astype(np.float32),
        "b": np.zeros(3 * hidden, np.float32),
    }


def _gru_step(xp, cell, x, h):
    """One GRU step in either numpy or jax (xp = np | jnp). Gate order
    r, z, n; h' = (1-z)*n + z*h (Cho et al. 2014, the torch convention the
    reference's recurrent_net.py wraps)."""
    H = h.shape[-1]
    gx = x @ cell["wx"] + cell["b"]
    gh = h @ cell["wh"]
    r = 1.0 / (1.0 + xp.exp(-(gx[..., :H] + gh[..., :H])))
    z = 1.0 / (1.0 + xp.exp(-(gx[..., H:2 * H] + gh[..., H:2 * H])))
    n = xp.tanh(gx[..., 2 * H:] + r * gh[..., 2 * H:])
    return (1.0 - z) * n + z * h


class RecurrentQModule:
    """GRU Q-network for partially observable envs — the R2D2 model
    (reference: rllib/models/torch/recurrent_net.py LSTMWrapper;
    rllib_contrib/r2d2 uses it over the DQN head). Encoder MLP -> GRU ->
    Q head. Two paths over the same params:

      * `step_np` — one timestep, numpy, carrying explicit state
        (EnvRunner rollouts; the runner owns per-env state rows).
      * `forward_seq` — jax `lax.scan` over [B, T] sequences with
        start-of-episode state resets, used inside the jitted learner
        update (compiler-friendly: one scan, static shapes).
    """

    is_recurrent = True

    def __init__(self, obs_dim: int, num_actions: int,
                 hidden: Sequence[int] = (64,), rnn_hidden: int = 64):
        self.obs_dim = obs_dim
        self.num_actions = num_actions
        self.hidden = tuple(hidden)
        self.rnn_hidden = rnn_hidden

    def init(self, seed: int = 0) -> dict:
        rng = np.random.default_rng(seed)
        dims = [self.obs_dim, *self.hidden]
        enc = [
            _init_linear(rng, dims[i], dims[i + 1], np.sqrt(2))
            for i in range(len(dims) - 1)
        ]
        return {
            "enc": enc,
            "gru": _gru_init(rng, dims[-1], self.rnn_hidden),
            "q": [_init_linear(rng, self.rnn_hidden, self.num_actions, 0.01)],
        }

    def initial_state(self, batch_size: int) -> np.ndarray:
        return np.zeros((batch_size, self.rnn_hidden), np.float32)

    def _encode_np(self, params, obs):
        h = obs
        for layer in params["enc"]:
            h = np.tanh(h @ layer["w"] + layer["b"])
        return h

    def step_np(self, params, obs: np.ndarray, state: np.ndarray):
        """(q [B, A], next_state [B, H]) — one rollout timestep."""
        x = self._encode_np(params, obs)
        h = _gru_step(np, params["gru"], x, state)
        head = params["q"][0]
        return h @ head["w"] + head["b"], h

    # EnvRunner's epsilon-greedy branch calls forward_np; for a recurrent
    # module the runner routes through step_np instead (state threading).

    def forward_seq(self, params, obs, state0, resets):
        """jax: obs [B, T, D], state0 [B, H], resets [B, T] (True = zero the
        state BEFORE consuming step t, i.e. t starts a new episode) ->
        (q [B, T, A], final_state [B, H])."""
        import jax
        import jax.numpy as jnp

        def encode(x):
            for layer in params["enc"]:
                x = jnp.tanh(x @ layer["w"] + layer["b"])
            return x

        x_seq = encode(obs)                      # [B, T, hidden[-1]]

        def scan_step(h, inputs):
            x_t, reset_t = inputs
            h = jnp.where(reset_t[:, None], 0.0, h)
            h = _gru_step(jnp, params["gru"], x_t, h)
            return h, h

        xs = (jnp.swapaxes(x_seq, 0, 1), jnp.swapaxes(resets, 0, 1))
        h_final, h_seq = jax.lax.scan(scan_step, state0, xs)
        h_seq = jnp.swapaxes(h_seq, 0, 1)        # [B, T, H]
        head = params["q"][0]
        return h_seq @ head["w"] + head["b"], h_final


def _conv2d_np(x: np.ndarray, w: np.ndarray, b: np.ndarray) -> np.ndarray:
    """SAME-padded 3x3 conv, NHWC, via im2col — the EnvRunner numpy path
    for conv policies (rollout batches are small; matmul via BLAS)."""
    B, H, W, C = x.shape
    kh, kw, _, F = w.shape
    xp = np.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    cols = np.empty((B, H, W, kh * kw * C), x.dtype)
    k = 0
    for dy in range(kh):
        for dx in range(kw):
            cols[..., k * C:(k + 1) * C] = xp[:, dy:dy + H, dx:dx + W, :]
            k += 1
    return cols.reshape(B * H * W, -1) @ w.reshape(-1, F) + b


class ConvActorCriticModule:
    """Conv policy/value net for frame-observation envs (the Atari-class
    workload; reference: rllib VisionNetwork models/catalog defaults for
    image spaces). Obs arrive FLATTENED from the runner ([B, H*W*C]); the
    module owns the reshape. Trunk: two SAME 3x3 convs (relu) -> flatten
    -> dense(128, tanh); separate pi/vf heads. The jax path uses
    lax.conv_general_dilated NHWC (MXU-friendly layout on TPU)."""

    def __init__(self, obs_dim: int, num_actions: int,
                 frame_shape: Sequence[int] = (10, 10, 4),
                 channels: Sequence[int] = (16, 32), hidden: int = 128):
        H, W, C = frame_shape
        if H * W * C != obs_dim:
            raise ValueError(f"frame_shape {frame_shape} != obs_dim {obs_dim}")
        self.obs_dim = obs_dim
        self.num_actions = num_actions
        self.frame_shape = tuple(frame_shape)
        self.channels = tuple(channels)
        self.hidden = hidden
        # rollout-inference jit cache (lazy; never pickled with the module
        # factory — runners build their module in-process). _jit_ok caches
        # the use-jax-or-not decision so the numpy fallback never re-probes.
        self._jit_fwd = None
        self._dev_params = None
        self._jit_ok: bool | None = None

    def init(self, seed: int = 0) -> dict:
        rng = np.random.default_rng(seed)
        H, W, C = self.frame_shape
        params: dict = {"conv": []}
        c_in = C
        for c_out in self.channels:
            fan_in = 9 * c_in
            params["conv"].append({
                "w": (rng.standard_normal((3, 3, c_in, c_out)) *
                      np.sqrt(2.0 / fan_in)).astype(np.float32),
                "b": np.zeros(c_out, np.float32),
            })
            c_in = c_out
        flat = H * W * c_in
        params["trunk"] = [_init_linear(rng, flat, self.hidden, np.sqrt(2))]
        params["pi"] = [_init_linear(rng, self.hidden, self.num_actions, 0.01)]
        params["vf"] = [_init_linear(rng, self.hidden, 1, 1.0)]
        return params

    # -- numpy path (EnvRunner rollouts) --

    def _trunk_np(self, params: dict, obs: np.ndarray) -> np.ndarray:
        B = obs.shape[0]
        x = obs.reshape(B, *self.frame_shape)
        for layer in params["conv"]:
            x = _conv2d_np(x, layer["w"], layer["b"])
            x = np.maximum(x, 0.0).reshape(B, *self.frame_shape[:2], -1)
        h = x.reshape(B, -1)
        t = params["trunk"][0]
        return np.tanh(h @ t["w"] + t["b"])

    def forward_np(self, params: dict, obs: np.ndarray):
        """Rollout inference through a CPU-jitted forward: XLA's fused
        conv stack is ~10x the interpreted im2col path, which made conv
        rollouts the EnvRunner bottleneck (2.1k steps/s vs 33k for the
        MLP). The computation is pinned to the host CPU device so runner
        processes never touch the learner's TPU; params transfer once per
        weight broadcast (cached by identity), not per step. Falls back to
        the numpy im2col path wherever jax cannot be used safely (see
        _jit_usable)."""
        if self._jit_ok or (self._jit_ok is None and self._jit_usable()):
            return self._forward_jit(params, obs)
        h = self._trunk_np(params, obs)
        pi, vf = params["pi"][0], params["vf"][0]
        return h @ pi["w"] + pi["b"], (h @ vf["w"] + vf["b"])[:, 0]

    def _jit_usable(self) -> bool:
        """Decide ONCE whether this process may run the jitted path.

        Initializing jax backends is not free of side effects: on a TPU
        host, accelerator discovery exclusively seizes the learner's chip
        (libtpu is single-process) — and merely having `jax` in
        sys.modules proves nothing, because importing jax does not
        initialize a backend. Policy, decided once per module:

          * backends already initialized in this process (the learner, a
            prior jax task) -> safe: `jax.devices("cpu")` reads a cache.
          * backends uninitialized but the platform config is CPU-only
            -> safe: init cannot probe an accelerator.
          * backends uninitialized in a ray_tpu WORKER process (rollout
            actor) -> pin the process to the CPU backend first; rollout
            actors never legitimately need the TPU.
          * anything else (fresh driver/plain process with accelerator
            platforms configured) -> numpy fallback; a rollout must not
            be what initializes TPU backends.
        """
        try:
            import jax
            from jax._src import xla_bridge

            initialized = bool(getattr(xla_bridge, "_backends", None))
            if not initialized:
                plat = jax.config.jax_platforms or ""
                cpu_only = plat and set(plat.split(",")) <= {"cpu"}
                if not cpu_only:
                    from ray_tpu._private import worker as _worker_mod

                    gw = _worker_mod._global_worker
                    if gw is None or gw.mode != "worker":
                        self._jit_ok = False
                        return False
                    jax.config.update("jax_platforms", "cpu")
            self._jit_fwd = (jax.jit(self.forward), jax.devices("cpu")[0])
            self._jit_ok = True
        except Exception:  # noqa: BLE001 — any jax trouble -> numpy path
            self._jit_ok = False
        return self._jit_ok

    def _forward_jit(self, params: dict, obs: np.ndarray):
        import jax

        fwd, cpu = self._jit_fwd
        if self._dev_params is None or self._dev_params[0] is not params:
            dev = jax.tree_util.tree_map(
                lambda x: jax.device_put(np.asarray(x), cpu), params)
            self._dev_params = (params, dev)
        logits, values = fwd(self._dev_params[1],
                             jax.device_put(np.asarray(obs), cpu))
        return np.asarray(logits), np.asarray(values)

    sample_actions_np = ActorCriticModule.sample_actions_np

    # -- jax path (Learner) --

    def forward(self, params, obs):
        import jax
        import jax.numpy as jnp

        B = obs.shape[0]
        x = obs.reshape(B, *self.frame_shape)
        for layer in params["conv"]:
            x = jax.lax.conv_general_dilated(
                x, layer["w"], window_strides=(1, 1), padding="SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            ) + layer["b"]
            x = jax.nn.relu(x)
        h = x.reshape(B, -1)
        t = params["trunk"][0]
        h = jnp.tanh(h @ t["w"] + t["b"])
        pi, vf = params["pi"][0], params["vf"][0]
        return h @ pi["w"] + pi["b"], (h @ vf["w"] + vf["b"])[:, 0]
