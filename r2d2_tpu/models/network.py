"""Dueling CNN+LSTM Q-network, TPU-native.

Capability-parity with the reference's ``Network`` (model.py:27-150): Nature
conv torso → LSTM over [latent ⊕ one-hot last action ⊕ last reward] → dueling
heads, with a single-step acting path and full-sequence training paths.

TPU-first redesign:
- NHWC layout (XLA's native conv layout) instead of torch NCHW.
- The LSTM is a fused cell under ``jax.lax.scan`` with the input projection
  hoisted out of the scan into one large ``(B*T, F) @ (F, 4H)`` MXU matmul;
  only the small recurrent matmul stays sequential.
- No ``pack_padded_sequence`` emulation: the unroll is static-shape over the
  full padded T; per-sample window extraction is a masked gather done by the
  learner (r2d2_tpu/learner/step.py), replacing the reference's per-sample
  Python loops (model.py:95-111,143).
- One ``unroll`` serves all three reference forward variants (model.py:65,
  81, 122): acting is a T=1 unroll; online/target training Q are gathers at
  different time indices of the same unrolled Q sequence.
- ``impala`` torso (deep residual CNN) and stacked LSTM layers cover the
  scaled-model benchmark config; ``mlp`` torso supports fast tests.
- Optional rematerialisation of the scan body for long unrolls.

Recurrent state: one array a sequence, whose shape and dtype the model
owns (models/state.py, :func:`state_spec`) and every other module derives — for the LSTM
``(2, layers, H)`` float32 where axis 0 is (h, c); for ``core="xing4"``
(models/xing4.py) the latent cache ``(layers, W, latent)`` in the compute
dtype; for ``core="olmo_hybrid"`` (models/olmo_hybrid.py) a flat vector of
delta-rule matrices, convolution tails and stored keys and values; for
``core="ouro"`` (models/ouro.py) the stored keys and values of every loop
and layer.  Zeros are the initial state of all four.
"""
from __future__ import annotations

import importlib
import itertools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from r2d2_tpu.config import Config
from r2d2_tpu.models.state import state_spec, zero_state  # noqa: F401
from r2d2_tpu.ops import stem


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]


# The memory cores that are modules of their own, by ``cfg.core``: each
# exports ``Core`` (a flax module ``(feats, hidden) -> (outs, hidden')``
# built from ``cfg, compute_dtype, param_dtype``), ``COUNTERS`` (or
# ``counter_names(cfg)`` where their number follows the configuration)
# and ``step_buffers`` (the hooks at the end of this file).  The LSTM
# stack lives here and has none of them.
_CORE_MODULES = {"xing4": "r2d2_tpu.models.xing4",
                 "olmo_hybrid": "r2d2_tpu.models.olmo_hybrid",
                 "ouro": "r2d2_tpu.models.ouro"}


def core_module(cfg: Config):
    """The module of ``cfg.core``, or None for the LSTM stack."""
    name = _CORE_MODULES.get(cfg.core)
    return importlib.import_module(name) if name else None


class NatureTorso(nn.Module):
    """Nature-DQN conv stack (reference geometry: model.py:39-49), NHWC.

    With ``s2d_input`` the input arrives space-to-depth folded from the
    host pipeline ((21, 21, 16) for an 84×84 frame — cfg.stored_obs_shape)
    and conv1 is the equivalent 2×2 stride-1 conv: the same linear map as
    8×8 stride-4 on raw pixels (every 8×8/4 window is a 2×2 window of 4×4
    blocks; kernel entries permuted — see
    tests/test_network.py::test_space_to_depth_equals_direct_conv1), but
    with a 16-deep MXU-shaped contraction instead of the pathological
    1-channel one, and no device-side relayout (a device transform of the
    (B·T, 84, 84, 1) batch costs more than conv1 itself).
    """
    out_dim: int
    compute_dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    s2d_input: bool = False

    @nn.compact
    def __call__(self, x):  # x: (B, H, W, C) in [0, 1]
        kw = dict(padding="VALID", dtype=self.compute_dtype,
                  param_dtype=self.param_dtype)
        if self.s2d_input:
            x = nn.relu(nn.Conv(32, (2, 2), strides=(1, 1), **kw)(x))
        else:
            x = nn.relu(nn.Conv(32, (8, 8), strides=(4, 4), **kw)(x))
        x = nn.relu(nn.Conv(64, (4, 4), strides=(2, 2), **kw)(x))
        x = nn.relu(nn.Conv(64, (3, 3), strides=(1, 1), **kw)(x))
        x = x.reshape(x.shape[0], -1)
        x = nn.relu(nn.Dense(self.out_dim, dtype=self.compute_dtype,
                             param_dtype=self.param_dtype)(x))
        return x


class ConvPool(nn.Module):
    """A stage's first convolution (3 x 3, SAME) and the max-pool after it
    (3 x 3, stride 2, SAME), through ``ops/stem.conv_pool``.  Its
    parameters are ``nn.Conv``'s (``kernel``, ``bias``: the same shapes,
    initializers and draws), so it takes the name the ``nn.Conv`` had."""
    features: int
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (3, 3, x.shape[-1], self.features),
                            self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.features,), self.param_dtype)
        return stem.conv_pool(x, kernel, bias, self.dtype)


class ImpalaTorso(nn.Module):
    """IMPALA deep residual CNN (BASELINE configs[4] scaled-model stress).

    Each stage opens with a convolution and a max-pool (:class:`ConvPool`).
    Where the step is traced for a TPU, the first stage, whose convolution
    has ONE input channel, is one pass of ``ops/stem.py``'s kernels: the
    convolution's 84 x 84 x 16 output (1.73 GB at the IMPALA cell's 7,680
    frames) lives in VMEM a few rows at a time, and the backward returns
    the kernel's and bias's gradients from the pooled cotangent and the
    stored window index, the frames being data.  The later stages' 16 and
    32 input channels keep their convolutions on the matrix unit, and pool
    through ``ops/pool.max_pool``: a forward kernel that stores which of a
    window's nine positions won, one byte a pooled element, and a backward
    that routes the cotangent by it.  Both work on the ``{0,3,2,1}`` layout
    the compiler gives these activations (frames minor), as its row-major
    ``[H, W, C, N]`` view (PERF.md, Findings).  Elsewhere (the CPU,
    acting's 64 frames) it is ``nn.Conv`` and ``nn.max_pool``'s values."""
    out_dim: int
    compute_dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    channels: Tuple[int, ...] = (16, 32, 32)
    blocks_per_stage: int = 2

    @nn.compact
    def __call__(self, x):
        kw = dict(padding="SAME", dtype=self.compute_dtype,
                  param_dtype=self.param_dtype)
        # the names the convolutions have always had: Conv_0, Conv_1, ...
        names = (f"Conv_{i}" for i in itertools.count())
        for ch in self.channels:
            x = ConvPool(ch, dtype=self.compute_dtype,
                         param_dtype=self.param_dtype, name=next(names))(x)
            for _ in range(self.blocks_per_stage):
                skip = x
                x = nn.Conv(ch, (3, 3), name=next(names), **kw)(nn.relu(x))
                x = nn.Conv(ch, (3, 3), name=next(names), **kw)(nn.relu(x))
                x = x + skip
        x = nn.relu(x)
        x = x.reshape(x.shape[0], -1)
        x = nn.relu(nn.Dense(self.out_dim, dtype=self.compute_dtype,
                             param_dtype=self.param_dtype)(x))
        return x


class MlpTorso(nn.Module):
    """Small flatten+dense torso for tests and non-image observations."""
    out_dim: int
    compute_dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = x.reshape(x.shape[0], -1)
        x = nn.relu(nn.Dense(self.out_dim, dtype=self.compute_dtype,
                             param_dtype=self.param_dtype)(x))
        return x


class LSTMLayer(nn.Module):
    """Fused LSTM layer unrolled over time.

    The input projection for all T steps is one large matmul (MXU-friendly);
    only the (B, H) @ (H, 4H) recurrent matmul is sequential.  Gate
    nonlinearities and cell state stay float32 for stability; matmuls run in
    ``compute_dtype``.  Gate order (i, f, g, o); forget-gate bias init 1.

    Two recurrence implementations behind the same parameters:
    - ``impl="scan"``: ``jax.lax.scan`` — portable, works on CPU and under
      GSPMD meshes, differentiable.  The ONLY training recurrence.
    - ``impl="pallas"``: the fused inference kernel (ops/lstm.py) — the
      whole unroll is one TPU program with the recurrent weights and h/c
      held in VMEM across steps.  No-grad paths only (acting/eval):
      the backward kernel was retired in r5 after the round-4 v5e
      measurement (B=64 T=85 H=512 bf16) put fused fwd+bwd at 0.96x
      scan; the inference edge (1.07x, residual-free) is what remains.
      Differentiating this branch raises at trace time — the learner
      builds its loss networks with ``lstm_impl="scan"``
      (learner/step.py:make_train_step).
    """
    hidden_dim: int
    compute_dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    remat: bool = False
    impl: str = "scan"
    interpret: bool = False

    @nn.compact
    def __call__(self, xs, h0, c0):
        # xs: (B, T, F); h0, c0: (B, H)
        B, T, F = xs.shape
        H = self.hidden_dim
        cd = self.compute_dtype

        wi = self.param("wi", nn.initializers.xavier_uniform(), (F, 4 * H),
                        self.param_dtype)
        wh = self.param("wh", nn.initializers.orthogonal(), (H, 4 * H),
                        self.param_dtype)

        def bias_init(key, shape, dtype):
            b = jnp.zeros(shape, dtype)
            return b.at[H:2 * H].set(1.0)  # forget-gate bias

        b = self.param("b", bias_init, (4 * H,), self.param_dtype)

        x_proj = (xs.astype(cd) @ wi.astype(cd)).astype(jnp.float32) + b

        def run_pallas(xp, wh, h0, c0):
            from r2d2_tpu.ops.lstm import lstm_unroll_pallas

            hs_tm, h, c = lstm_unroll_pallas(
                xp.swapaxes(0, 1), wh, h0, c0,
                compute_dtype=cd, interpret=self.interpret)
            return hs_tm.swapaxes(0, 1), h, c

        def run_scan(xp, wh, h0, c0):
            def step(carry, x_t):
                h, c = carry
                gates = x_t + (h.astype(cd) @ wh.astype(cd)).astype(
                    jnp.float32)
                i, f, g, o = jnp.split(gates, 4, axis=-1)
                c_new = (jax.nn.sigmoid(f) * c
                         + jax.nn.sigmoid(i) * jnp.tanh(g))
                h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
                return (h_new, c_new), h_new

            if self.remat:
                step = jax.checkpoint(step)
            (h, c), hs = jax.lax.scan(step, (h0, c0), xp.swapaxes(0, 1))
            return hs.swapaxes(0, 1), h, c

        h0f, c0f = h0.astype(jnp.float32), c0.astype(jnp.float32)
        # The pallas branch only lowers on TPU (interpret=True is the CPU
        # test mode).  Callers that jit the network onto a non-TPU device —
        # actor/eval inference on the host CPU backend — must request a
        # scan-impl network instead (actor.make_act_fn builds that twin;
        # the two impls declare identical parameters).
        if self.impl == "pallas":
            hs, h, c = run_pallas(x_proj, wh, h0f, c0f)
        else:
            hs, h, c = run_scan(x_proj, wh, h0f, c0f)
        return hs, (h, c)


class DuelingHead(nn.Module):
    """q = V + A - mean(A) (reference: model.py:53-63, 75-77)."""
    hidden_dim: int
    action_dim: int
    compute_dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        kw = dict(dtype=self.compute_dtype, param_dtype=self.param_dtype)
        adv = nn.Dense(self.action_dim, name="adv_out", **kw)(
            nn.relu(nn.Dense(self.hidden_dim, name="adv_hidden", **kw)(x)))
        val = nn.Dense(1, name="val_out", **kw)(
            nn.relu(nn.Dense(self.hidden_dim, name="val_hidden", **kw)(x)))
        q = val + adv - adv.mean(axis=-1, keepdims=True)
        return q.astype(jnp.float32)


class R2D2Network(nn.Module):
    """The full Q-network.  Two entry points:

    - ``unroll``: (obs (B,T,*obs) uint8, last_action (B,T,A), last_reward
      (B,T), hidden (B,2,layers,H)) → (q (B,T,A) f32, new hidden).
    - ``act``: single-step batched inference for actors/eval.
    """
    action_dim: int
    cfg: Config

    def setup(self):
        cfg = self.cfg
        cd, pd = _dtype(cfg.compute_dtype), _dtype(cfg.param_dtype)
        torso_cls = {"nature": NatureTorso, "impala": ImpalaTorso,
                     "mlp": MlpTorso}[cfg.torso]
        torso_kw = dict(out_dim=cfg.hidden_dim, compute_dtype=cd,
                        param_dtype=pd)
        if cfg.torso == "nature":
            torso_kw["s2d_input"] = cfg.obs_space_to_depth
        self.torso = torso_cls(**torso_kw)
        module = core_module(cfg)
        if module is not None:
            self.core = module.Core(cfg=cfg, compute_dtype=cd,
                                    param_dtype=pd)
        else:
            impl = resolve_lstm_impl(cfg)
            self.lstm_layers_ = [
                LSTMLayer(hidden_dim=cfg.hidden_dim, compute_dtype=cd,
                          param_dtype=pd, remat=cfg.remat, impl=impl,
                          interpret=cfg.pallas_interpret,
                          name=f"lstm_{i}")
                for i in range(cfg.lstm_layers)
            ]
        self.head = DuelingHead(hidden_dim=cfg.hidden_dim,
                                action_dim=self.action_dim,
                                compute_dtype=cd, param_dtype=pd)

    def _lstm_stack(self, xs, hidden):
        # xs: (B, T, F); hidden: (B, 2, layers, H)
        new_h, new_c = [], []
        for i, layer in enumerate(self.lstm_layers_):
            xs, (h, c) = layer(xs, hidden[:, 0, i], hidden[:, 1, i])
            new_h.append(h)
            new_c.append(c)
        new_hidden = jnp.stack([jnp.stack(new_h, 1), jnp.stack(new_c, 1)], 1)
        return xs, new_hidden

    def _features(self, obs, last_action, last_reward):
        # obs: (B, T, *obs_shape) uint8 → latent (B, T, hidden)
        B, T = obs.shape[:2]
        cd = _dtype(self.cfg.compute_dtype)
        x = obs.reshape(B * T, *obs.shape[2:]).astype(cd) / 255.0
        latent = self.torso(x).reshape(B, T, -1)
        return jnp.concatenate(
            [latent.astype(jnp.float32), last_action.astype(jnp.float32),
             last_reward[..., None].astype(jnp.float32)], axis=-1)

    def unroll(self, obs, last_action, last_reward, hidden):
        # the three scopes a profile splits a forward (and, under
        # jvp(...) / transpose(jvp(...)), a backward) by
        with jax.named_scope("torso"):
            feats = self._features(obs, last_action, last_reward)
        with jax.named_scope("core"):
            outs, new_hidden = (self._lstm_stack(feats, hidden)
                                if core_module(self.cfg) is None
                                else self.core(feats, hidden))
        with jax.named_scope("heads"):
            B, T = outs.shape[:2]
            q = self.head(outs.reshape(B * T, -1)).reshape(B, T, -1)
        return q, new_hidden

    def act(self, obs, last_action, last_reward, hidden):
        # obs: (B, *obs_shape) uint8 — a T=1 unroll (reference model.py:65-79)
        q, new_hidden = self.unroll(obs[:, None], last_action[:, None],
                                    last_reward[:, None], hidden)
        return q[:, 0], new_hidden


def resolve_lstm_impl(cfg: Config) -> str:
    """``auto`` → the fused Pallas inference kernel on TPU, ``scan``
    elsewhere.  The resolved impl governs NO-GRAD unrolls only — any grad
    path must use a ``lstm_impl="scan"`` network (the learner builds its
    loss networks that way, learner/step.py:make_train_step; the Pallas
    kernel has no backward since r5 and raises under differentiation).

    All implementations declare identical parameters, so checkpoints and
    param pytrees are interchangeable between them (e.g. act with pallas
    on TPU, evaluate with scan on CPU).
    """
    if cfg.lstm_impl != "auto":
        return cfg.lstm_impl
    return "pallas" if jax.default_backend() == "tpu" else "scan"


def stem_fused(cfg: Config) -> Optional[float]:
    """The share of the train step's stem passes (the online forward, its
    backward, the target's forward) that take ``ops/stem.py``'s kernels,
    as the step decides when it is traced: all three see the batch's
    ``batch_size * seq_len`` frames, so it is 1 or 0.  None where the torso
    has no stem (every torso but ``impala``)."""
    if cfg.torso != "impala":
        return None
    frames = cfg.batch_size * cfg.seq_len
    return float(stem.engages((frames, *cfg.stored_obs_shape),
                              ImpalaTorso.channels[0]))


def create_network(cfg: Config, action_dim: int) -> R2D2Network:
    return R2D2Network(action_dim=action_dim, cfg=cfg)


def init_params(cfg: Config, net: R2D2Network, key: jax.Array):
    B, T = 1, 2
    obs = jnp.zeros((B, T, *cfg.stored_obs_shape), jnp.uint8)
    la = jnp.zeros((B, T, net.action_dim), jnp.float32)
    lr = jnp.zeros((B, T), jnp.float32)

    def init(key):
        variables = net.init(key, obs, la, lr, zero_hidden(cfg, B),
                             method=R2D2Network.unroll)
        # what a forward pass sows is not part of the weights
        return {k: v for k, v in variables.items() if k != "stats"}

    if core_module(cfg) is not None:
        # one program that draws the weights and nothing else: op by op,
        # the tracing pass over blocks this wide takes minutes.  The LSTM
        # networks keep drawing op by op: on the TPU one fused program
        # rounds the scaled normal draws differently (every conv, dense
        # and head kernel of both benchmark configurations came out as
        # other bytes; my chip run, PR 28), and a seed's weights are what
        # every earlier measurement of them rests on
        return jax.jit(init)(key)
    return init(key)


def zero_hidden(cfg: Config, batch: int) -> jnp.ndarray:
    shape, dtype = state_spec(cfg)
    return jnp.zeros((batch,) + shape, dtype)


# What a model keeps beside its weights.  A core may declare a "buffers"
# collection: state that the train step writes and no gradient reaches
# (learner/step.py differentiates the "params" collection alone), copied
# with the target network and checkpointed like any leaf.  Its online pass
# sows under "stats" what the step needs to write them.  The LSTM declares
# neither, and the three hooks below are then the identity, () and nothing.

def step_buffers(cfg: Config, buffers, stats):
    """The "buffers" collection after an update whose online pass sowed
    ``stats``."""
    module = core_module(cfg)
    return buffers if module is None else module.step_buffers(
        cfg, buffers, stats)


def counter_names(cfg: Config) -> tuple:
    """Names of the float32 scalars the model's train step leaves in its
    buffers for the host to log, in :func:`read_counters`' order."""
    module = core_module(cfg)
    if module is None:
        return ()
    if hasattr(module, "counter_names"):
        return module.counter_names(cfg)
    return module.COUNTERS


def read_counters(cfg: Config, variables) -> jnp.ndarray:
    """``(len(counter_names(cfg)),)`` float32 from the model's variables."""
    return variables["buffers"]["core"]["counters"]
