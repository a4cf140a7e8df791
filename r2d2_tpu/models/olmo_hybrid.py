"""The ``olmo_hybrid`` memory core: Olmo-Hybrid-7B's layers between torso and
heads — periods of three **gated-delta-rule** layers (``linear_attention``)
and one **softmax-attention** layer (``full_attention``), each followed by a
SwiGLU feed-forward, on one residual stream.

- **Block** (the OLMo 2 / 3 placement): ``h = x + RMSNorm(Mixer(x))``,
  ``y = h + RMSNorm(FFN(h))``; no biases.
- **Linear layer**, per head of ``d_k``/``d_v``: q, k, v pass a depthwise
  causal convolution over time (kernel 4) and SiLU; q and k are
  L2-normalised (q scaled by ``d_k^-1/2``); ``beta = 2 sigmoid(W_b x)``,
  ``alpha = exp(-exp(A_log) softplus(W_a x + dt_bias))``; the state is a
  matrix ``S (d_k, d_v)``: ``S' = alpha S + k (beta (v - (alpha S)^T
  k))^T``, read by ``o = S'^T q``; the output is ``W_o [RMSNorm(o) *
  silu(W_g x)]``.  Two forms that agree: :func:`delta_rule_step` (acting)
  and :func:`delta_rule_chunked` (a training window from a stored state:
  states exist at chunk boundaries only, and inside a chunk the products
  are matrix products with the inverse of a unit lower-triangular matrix).
  The recurrence, alpha, beta and S are float32 whatever the compute type.
- **Softmax layer**, heads of ``core_head_dim``: RMSNorm of the whole query
  and key projections, no rotary embedding, a query sees the
  ``core_context`` steps before it and itself, over the stored keys and
  values and the window's own (the band of ``xing4.attention``), so acting
  through the state equals the unroll.
- **The chip's share**: heads 0..``core_heads_held``-1 of both mixers; the
  feed-forward and the norms are whole.  What the absent heads would add is
  left out; the QK-norm's mean square is over the heads held unless the
  group's is handed in (:func:`softmax_attention`).

A state is one flat vector folded into whole tiles of 16 rows of 128
(models/state.olmo_hybrid_layout): packed and unpacked here, by static
offsets.  Periods are one scanned body over stacked
parameters, each block rematerialised under ``cfg.remat``.
``benchmark/reference/`` has the same equations in plain float32, stepped.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from r2d2_tpu.config import Config
from r2d2_tpu.models.state import (
    LAYER_TYPES_PERIOD,
    LINEAR_CONV_KERNEL_DIM,
    STATE_LANES,
    folded_rows,
    olmo_hybrid_layout,
)
from r2d2_tpu.models.xing4 import _mm, _rms, _swiglu, _swiglu_init

# the source's constants that no run of this core varies, under the names
# config.json gives them (a configuration's file carries them at its top
# level; tests/test_olmo_hybrid_core.py holds the two together)
RMS_NORM_EPS = 1e-6                 # rms_norm_eps
LINEAR_ALLOW_NEG_EIGVAL = True      # beta in (0, 2): 1 - beta may be < 0
LINEAR_PER_PERIOD = LAYER_TYPES_PERIOD.count("linear_attention")
# the L2 norm's epsilon (under the root's square) is the open
# implementation's, as are the draws of A_log and dt_bias (_linear_init)
L2_NORM_EPS = 1e-6
# steps a chunk of the chunked form holds: 85 pad to 96, and the C x C
# products stay a fifth of a head's d_k x d_v ones
CHUNK = 32

COUNTERS = ("state_abs_max", "decay_mean", "beta_mean")


def periods(cfg: Config) -> int:
    return cfg.core_layers // len(LAYER_TYPES_PERIOD)


# ------------------------------------------------------------- the state

def unpack(cfg: Config, hidden):
    """hidden (B, rows, 128) -> delta (B, linear layers, h, d_k, d_v)
    float32, conv (B, linear layers, kernel - 1, channels), rows (B, W,
    softmax layers, 2 h head)."""
    parts = olmo_hybrid_layout(cfg)
    B = hidden.shape[0]
    at = int(np.prod(parts["delta"]))
    whole = at + int(np.prod(parts["conv"]))
    split = folded_rows(whole)              # the snapshot part's rows
    snap = hidden[:, :split].reshape(B, -1)
    rows = hidden[:, split:].reshape(B, -1)[:, :int(np.prod(parts["rows"]))]
    return (snap[:, :at].reshape((B,) + parts["delta"]).astype(jnp.float32),
            snap[:, at:whole].reshape((B,) + parts["conv"]),
            rows.reshape((B,) + parts["rows"]))


def pack(cfg: Config, delta, conv, rows, dtype):
    """The folded state (models/state.py): the matrices and tails, padded
    to whole tiles of 16 rows of 128, then the stored keys and values,
    padded likewise."""
    B = delta.shape[0]

    def folded(*parts):
        flat = jnp.concatenate([p.reshape(B, -1).astype(dtype)
                                for p in parts], axis=1)
        rows = folded_rows(flat.shape[1])
        flat = jnp.pad(flat, ((0, 0),
                              (0, rows * STATE_LANES - flat.shape[1])))
        return flat.reshape(B, rows, STATE_LANES)

    return jnp.concatenate([folded(delta, conv), folded(rows)], axis=1)


# -------------------------------------------------------- the delta rule

def delta_rule_step(q, k, v, g, beta, S):
    """One step: q, k (B, h, d_k), v (B, h, d_v), g, beta (B, h), S (B, h,
    d_k, d_v), all float32 -> (o (B, h, d_v), S')."""
    S = jnp.exp(g)[..., None, None] * S
    u = beta[..., None] * (v - (S * k[..., None]).sum(axis=-2))
    S = S + k[..., None] * u[..., None, :]
    return (S * q[..., None]).sum(axis=-2), S


@jax.custom_vjp
def unit_lower_inverse(A):
    """``(I + A)^-1`` for strictly lower-triangular ``A`` (..., C, C), by
    forward substitution, row by row: exact, where the nilpotent product
    ``(I - A)(I + A^2)(I + A^4)...`` cancels catastrophically once a
    window's keys are nearly alike (entries of A^16 reach 1e9 at C = 32
    while the inverse's stay near 1).  Backward: ``-T^T g T^T``."""
    C = A.shape[-1]

    def row(t, T):
        a = jax.lax.dynamic_slice_in_dim(A, t, 1, axis=-2)      # (..., 1, C)
        r = jax.lax.dynamic_slice_in_dim(T, t, 1, axis=-2) - (
            jnp.swapaxes(a, -1, -2) * T).sum(axis=-2, keepdims=True)
        return jax.lax.dynamic_update_slice_in_dim(T, r, t, axis=-2)

    eye = jnp.broadcast_to(jnp.eye(C, dtype=A.dtype), A.shape)
    return jax.lax.fori_loop(1, C, row, eye)


def _inverse_fwd(A):
    T = unit_lower_inverse(A)
    return T, T


def _inverse_bwd(T, g):
    Tt = jnp.swapaxes(T, -1, -2)
    hi = jax.lax.Precision.HIGHEST
    return (-jnp.tril(jnp.matmul(jnp.matmul(Tt, g, precision=hi), Tt,
                                 precision=hi), -1),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def delta_rule_chunked(q, k, v, g, beta, S0, cd, chunk: int = CHUNK):
    """A window from the state S0: q, k (B, T, h, d_k), v (B, T, h, d_v),
    g, beta (B, T, h) float32, S0 (B, h, d_k, d_v) float32 -> (o (B, T, h,
    d_v), S_T).  With G the running sum of g inside a chunk and S the
    state at its start (arXiv:2412.06464 section 3):

        A[t, i] = beta_t exp(G_t - G_i) (k_t . k_i)        for i < t
        U = (I + A)^-1 [beta (V - exp(G) K S)]
        O = exp(G) Q S + (exp(G_t - G_i) (q_t . k_i))_{i <= t} U
        S' = exp(G_C) S + (exp(G_C - G) K)^T U

    Padded steps have beta = 0 and g = 0 and change nothing."""
    B, T, h, _ = q.shape
    C = min(chunk, T)
    n = -(-T // C)

    def chunks(x):                  # (B, T, h, ...) -> (n, B, h, C, ...)
        x = jnp.pad(x, [(0, 0), (0, n * C - T)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((B, n, C) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v = chunks(q.astype(cd)), chunks(k.astype(cd)), chunks(v)
    beta = chunks(beta)[..., None]
    G = jnp.cumsum(chunks(g), axis=-1)                      # (n, B, h, C)
    t_i = np.arange(C)
    seen = t_i[:, None] >= t_i[None, :]
    # exp only of what is kept: G_t - G_i > 0 above the diagonal
    decay = jnp.where(seen, jnp.exp(jnp.where(
        seen, G[..., :, None] - G[..., None, :], 0.0)), 0.0)

    def dots(a, b):                         # rows of a against rows of b
        return jnp.einsum("...td,...id->...ti", a, b,
                          preferred_element_type=jnp.float32)

    T_inv = unit_lower_inverse(
        jnp.where(t_i[:, None] > t_i[None, :], beta * decay * dots(k, k),
                  0.0))
    P = decay * dots(q, k)
    gamma = jnp.exp(G)[..., None]                           # (n, B, h, C, 1)
    k_out = (jnp.exp(G[..., -1:] - G)[..., None]
             * k.astype(jnp.float32)).astype(cd)

    def mm(a, b):
        return jnp.matmul(a.astype(cd), b.astype(cd),
                          preferred_element_type=jnp.float32)

    def step(S, xs):
        q, k, v, beta, T_inv, P, gamma, k_out = xs
        U = mm(T_inv, beta * (v - gamma * mm(k, S)))
        o = gamma * mm(q, S) + mm(P, U)
        S = gamma[..., -1:, :] * S + mm(jnp.swapaxes(k_out, -1, -2), U)
        return S, o

    S, o = jax.lax.scan(step, S0, (q, k, v, beta, T_inv, P, gamma, k_out))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)           # (B, n, C, h, d_v)
    return o.reshape(B, n * C, h, -1)[:, :T], S


# ------------------------------------------------------------ the mixers

def _conv(rows, w):
    """Depthwise causal convolution: rows (B, K - 1 + T, channels) — the
    K - 1 rows before the window, then its own — against w (K, channels)
    -> (B, T, channels) float32."""
    K = w.shape[0]
    T = rows.shape[1] - (K - 1)
    rows, w = rows.astype(jnp.float32), w.astype(jnp.float32)
    return sum(w[i] * rows[:, i:i + T] for i in range(K))


def _l2(x):
    return x * jax.lax.rsqrt((x * x).sum(axis=-1, keepdims=True)
                             + L2_NORM_EPS)


@jax.named_scope("linear_attention")
def linear_attention(cfg: Config, p, x, S, tail, cd, chunk: int = CHUNK):
    """x (B, T, d), S (B, h, d_k, d_v) float32, tail (B, K - 1, channels)
    -> (out (B, T, d) float32, S', tail', (max |S'|, mean alpha, mean
    beta))."""
    B, T, _ = x.shape
    h, dk, dv = (cfg.core_heads_held, cfg.core_linear_key_dim,
                 cfg.core_linear_value_dim)
    pre = jnp.concatenate([_mm(x, p[w], cd, cd)
                           for w in ("w_q", "w_k", "w_v")], axis=-1)
    rows = jnp.concatenate([tail, pre.astype(tail.dtype)], axis=1)
    qkv = jax.nn.silu(_conv(rows, jnp.concatenate(
        [p["conv_q"], p["conv_k"], p["conv_v"]], axis=-1)))
    q = _l2(qkv[..., :h * dk].reshape(B, T, h, dk)) * dk ** -0.5
    k = _l2(qkv[..., h * dk:2 * h * dk].reshape(B, T, h, dk))
    v = qkv[..., 2 * h * dk:].reshape(B, T, h, dv)
    beta = jax.nn.sigmoid(_mm(x, p["w_b"], cd))
    if LINEAR_ALLOW_NEG_EIGVAL:
        beta = 2.0 * beta
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        _mm(x, p["w_a"], cd) + p["dt_bias"].astype(jnp.float32))
    with jax.named_scope("delta_rule"):
        if T == 1:
            o, S = delta_rule_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                   beta[:, 0], S)
            o = o[:, None]
        else:
            o, S = delta_rule_chunked(q, k, v, g, beta, S, cd, chunk)
    gate = jax.nn.silu(_mm(x, p["w_g"], cd)).reshape(B, T, h, dv)
    o = _rms(o, p["o_norm"], RMS_NORM_EPS) * gate
    stats = jnp.stack([jnp.abs(S).max(), jnp.exp(g).mean(), beta.mean()])
    return (_mm(o.reshape(B, T, h * dv), p["w_o"], cd), S,
            rows[:, T:], stats)


def _qk_norm(x, weight, mean_square):
    """RMSNorm over the projection's whole width; ``mean_square`` (...,
    1), where given, stands for this chip's own."""
    x = x.astype(jnp.float32)
    if mean_square is None:
        mean_square = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(mean_square + RMS_NORM_EPS)
            * weight.astype(jnp.float32))


@jax.named_scope("softmax_attention")
def softmax_attention(cfg: Config, p, x, cache, cd, q_ms=None, k_ms=None):
    """x (B, T, d), cache (B, W, 2 h head) the stored (key, value) rows ->
    (out (B, T, d) float32, cache').  ``q_ms`` / ``k_ms`` (B, T, 1): the
    mean squares of the whole group's query and key projections, where
    this chip's heads are a share of them — the sum a mesh axis over the
    heads would exchange; None: this chip's own."""
    B, T, _ = x.shape
    W, h, hd = cfg.core_context, cfg.core_heads_held, cfg.core_head_dim
    S = W + T
    q = _qk_norm(_mm(x, p["w_q"], cd), p["q_norm"], q_ms)
    k = _qk_norm(_mm(x, p["w_k"], cd), p["k_norm"], k_ms)
    new = jnp.concatenate([k, _mm(x, p["w_v"], cd)],
                          axis=-1).astype(cache.dtype)
    slots = jnp.concatenate([cache, new], axis=1)           # (B, S, 2 h hd)
    keys = slots[..., :h * hd].reshape(B, S, h, hd)
    values = slots[..., h * hd:].reshape(B, S, h, hd)
    scores = jnp.einsum("bthd,bshd->bhts", q.reshape(B, T, h, hd).astype(cd),
                        keys.astype(cd), preferred_element_type=jnp.float32)
    # a query at window step t (slot W + t) sees slots t .. W + t
    t_i, s_i = np.arange(T)[:, None], np.arange(S)[None, :]
    seen = jnp.asarray((s_i >= t_i) & (s_i <= t_i + W))
    prob = jax.nn.softmax(
        jnp.where(seen, scores * hd ** -0.5, -jnp.inf), axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", prob.astype(cd), values.astype(cd),
                   preferred_element_type=jnp.float32)
    return _mm(o.reshape(B, T, h * hd), p["w_o"], cd), slots[:, T:]


def _add_normed(x, y, weight):
    """x + RMSNorm(y): the family's norm sits on a sublayer's output."""
    return x + _rms(y, weight, RMS_NORM_EPS).astype(x.dtype)


def _feed_forward(p, x, cd):
    with jax.named_scope("mlp"):
        B, T, d = x.shape
        y = _swiglu(x.reshape(B * T, d), p["mlp"], cd).reshape(B, T, d)
        return _add_normed(x, y, p["ffn_norm"])


def linear_block(cfg: Config, cd, p, x, S, tail):
    y, S, tail, stats = linear_attention(cfg, p, x, S, tail, cd)
    with jax.named_scope("linear_attention"):
        x = _add_normed(x, y, p["attn_norm"])
    return _feed_forward(p, x, cd), S, tail, stats


def full_block(cfg: Config, cd, p, x, cache):
    y, cache = softmax_attention(cfg, p, x, cache, cd)
    with jax.named_scope("softmax_attention"):
        x = _add_normed(x, y, p["attn_norm"])
    return _feed_forward(p, x, cd), cache


def run(cfg: Config, params, feats, hidden, cd):
    """feats (B, T, F), hidden (B, rows, 128) -> (out (B, T, d), hidden', the
    linear layers' statistics (layers, 3))."""
    B = feats.shape[0]
    x = (_mm(feats, params["in_proj"]["kernel"], cd)
         + params["in_proj"]["bias"]).astype(cd)
    delta, conv, rows = unpack(cfg, hidden)
    P, n = periods(cfg), LINEAR_PER_PERIOD

    def by_period(a):               # (B, P n, ...) -> (P, n, B, ...)
        return jnp.moveaxis(a.reshape((B, P, n) + a.shape[2:]), 0, 2)

    def remat(f):
        return jax.checkpoint(f) if cfg.remat else f

    linear = remat(functools.partial(linear_block, cfg, cd))
    full = remat(functools.partial(full_block, cfg, cd))

    def linear_step(x, xs):
        x, S, tail, stats = linear(xs[0], x, xs[1], xs[2])
        return x, (S, tail, stats)

    def period(x, xs):
        p, S, tail, cache = xs
        x, kept = jax.lax.scan(linear_step, x, (p["linear"], S, tail))
        x, cache = full(p["full"], x, cache)
        return x, kept + (cache,)

    x, (delta, conv, stats, rows) = jax.lax.scan(
        period, x, (params["periods"], by_period(delta), by_period(conv),
                    jnp.moveaxis(rows, 2, 0)))

    def by_layer(a):                # (P, n, B, ...) -> (B, P n, ...)
        a = jnp.moveaxis(a, 2, 0)
        return a.reshape((B, P * n) + a.shape[3:])

    out = _rms(x, params["final_norm"], RMS_NORM_EPS)
    return (out, pack(cfg, by_layer(delta), by_layer(conv),
                      jnp.moveaxis(rows, 0, 2), hidden.dtype),
            stats.reshape(P * n, len(COUNTERS)))


def step_buffers(cfg: Config, buffers, stats):
    """The core's buffers after an update (models/network.step_buffers):
    COUNTERS of the online pass over the update's batch — the largest
    entry of any delta-rule matrix at the window's end (a recurrence that
    blows up shows here first), the mean decay alpha and the mean beta."""
    s = stats["core"]["linear_stats"]
    return {**buffers, "core": {**buffers["core"], "counters": jnp.stack(
        [s[:, 0].max(), s[:, 1].mean(), s[:, 2].mean()])}}


# ---------------------------------------------------------- parameters

def _lecun(lead):
    """LeCun normal over the last two axes, the ``lead`` ones stacking."""
    return nn.initializers.lecun_normal(
        in_axis=-2, out_axis=-1, batch_axis=tuple(range(len(lead))))


def _linear_init(key, cfg: Config, lead, pd):
    """A_log and dt_bias as the open implementation of the layer
    (flash-linear-attention's GatedDeltaNet) draws them: A uniform in (0,
    16); dt log-uniform in (0.001, 0.1), kept as its inverse softplus."""
    d, h, dk, dv = (cfg.core_dim, cfg.core_heads_held,
                    cfg.core_linear_key_dim, cfg.core_linear_value_dim)
    k = jax.random.split(key, 13)
    ones = functools.partial(jnp.ones, dtype=pd)
    lecun = conv = _lecun(lead)     # a tap's fan-in is the kernel's length
    K = LINEAR_CONV_KERNEL_DIM
    dt = jnp.exp(jax.random.uniform(k[11], lead + (h,), jnp.float32,
                                    math.log(0.001), math.log(0.1)))
    dt = jnp.maximum(dt, 1e-4)
    return dict(
        w_q=lecun(k[0], lead + (d, h * dk), pd),
        w_k=lecun(k[1], lead + (d, h * dk), pd),
        w_v=lecun(k[2], lead + (d, h * dv), pd),
        w_g=lecun(k[3], lead + (d, h * dv), pd),
        w_o=lecun(k[4], lead + (h * dv, d), pd),
        w_a=lecun(k[5], lead + (d, h), pd),
        w_b=lecun(k[6], lead + (d, h), pd),
        conv_q=conv(k[7], lead + (K, h * dk), pd),
        conv_k=conv(k[8], lead + (K, h * dk), pd),
        conv_v=conv(k[9], lead + (K, h * dv), pd),
        A_log=jnp.log(jax.random.uniform(
            k[10], lead + (h,), jnp.float32, 1e-3, 16.0)).astype(pd),
        dt_bias=(dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
        o_norm=ones(lead + (dv,)),
        attn_norm=ones(lead + (d,)), ffn_norm=ones(lead + (d,)),
        mlp=_swiglu_init(k[12], lead, d, cfg.core_dense_dim, pd, lecun))


def _full_init(key, cfg: Config, lead, pd):
    d, width = cfg.core_dim, cfg.core_heads_held * cfg.core_head_dim
    k = jax.random.split(key, 5)
    ones = functools.partial(jnp.ones, dtype=pd)
    lecun = _lecun(lead)
    return dict(
        w_q=lecun(k[0], lead + (d, width), pd),
        w_k=lecun(k[1], lead + (d, width), pd),
        w_v=lecun(k[2], lead + (d, width), pd),
        w_o=lecun(k[3], lead + (width, d), pd),
        q_norm=ones(lead + (width,)), k_norm=ones(lead + (width,)),
        attn_norm=ones(lead + (d,)), ffn_norm=ones(lead + (d,)),
        mlp=_swiglu_init(k[4], lead, d, cfg.core_dense_dim, pd, lecun))


def init_periods(key, cfg: Config, pd):
    """Every leaf's leading axis is the period; a linear layer's next one
    is its place in the period."""
    P = periods(cfg)
    k = jax.random.split(key)
    return dict(linear=_linear_init(k[0], cfg, (P, LINEAR_PER_PERIOD), pd),
                full=_full_init(k[1], cfg, (P,), pd))


class Core(nn.Module):
    """Declares the core's parameters and its one buffer, the last
    update's COUNTERS; the arithmetic is :func:`run`."""
    cfg: Config
    compute_dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, feats, hidden):
        cfg, pd = self.cfg, self.param_dtype
        params = dict(
            in_proj=dict(
                kernel=self.param("in_proj_kernel",
                                  nn.initializers.lecun_normal(),
                                  (feats.shape[-1], cfg.core_dim), pd),
                bias=self.param("in_proj_bias", nn.initializers.zeros,
                                (cfg.core_dim,), pd)),
            periods=self.param("periods", init_periods, cfg, pd),
            final_norm=self.param("final_norm", nn.initializers.ones,
                                  (cfg.core_dim,), pd))
        self.variable("buffers", "counters", jnp.zeros,
                      (len(COUNTERS),), jnp.float32)
        out, hidden, stats = run(cfg, params, feats, hidden,
                                 self.compute_dtype)
        self.sow("stats", "linear_stats", stats,
                 reduce_fn=lambda _, new: new, init_fn=lambda: None)
        return out, hidden
