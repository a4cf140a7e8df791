"""The ``ouro`` memory core: Ouro-2.6B's looped layers between torso and
heads — ``core_layers`` layers of softmax attention and a SwiGLU
feed-forward, the whole stack run ``core_loops`` times with the same
weights (the source's ``total_ut_steps``).

- **Layer** (the "sandwich" placement: a norm before and after each
  sublayer), on a residual x of width d: ``a = RMSNorm_in(x)``; q, k, v
  of ``core_heads_held`` heads of ``core_head_dim`` (as many key and value
  heads as query heads), q and k under RoPE of ``core_rope_theta``
  (halves rotated, not pairs); a softmax over the loop and layer's stored
  keys and values and the window's own, a query seeing the
  ``core_context`` steps before it and itself (the band of
  ``xing4.attention``); ``x += RMSNorm_in2(o W_o)``; ``x +=
  RMSNorm_post2(SwiGLU(RMSNorm_post(x)))``.  No biases.
- **Loops**: after the stack's last layer ``y_u = RMSNorm_final(x)``, and
  ``y_u`` is what loop u + 1 takes in; Q comes from the last loop's.  Each
  loop's application of a layer attends over a cache of its own: the state
  keeps one a (loop, layer), as the source's cache index ``u L + l`` does.
  The early-exit gate is not applied: at ``early_exit_threshold`` 1 every
  loop runs and the last one's output is used.
- **The state**: for each (loop, layer) the W newest steps' (unrotated key,
  value) (models/state.py).  Positions are slots (stored 0..W-1, window
  step t at W + t) and RoPE is relative, so the keys are rotated at use
  and acting (T = 1 against the state) equals the unroll.

The loops are a scan whose body is the scan over the stacked layers, the
weights closed over, so that one lowering serves every loop and the
gradient of a weight is the sum over its applications; each application
is rematerialised under ``cfg.remat``.  ``benchmark/reference/`` has the
same equations in plain float32, each query over a window of its own keys.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from r2d2_tpu.config import Config
from r2d2_tpu.models.xing4 import _mm, _rms, _swiglu, _swiglu_init

# the source's constant that no run of this core varies, under the name
# config.json gives it (tests/test_ouro_core.py holds the two together)
RMS_NORM_EPS = 1e-6                 # rms_norm_eps


def counter_names(cfg: Config) -> tuple:
    """``loop_delta_rel_u`` for each loop u >= 1: the rms of ``y_u -
    y_(u-1)`` over the rms of ``y_(u-1)``, on the online pass."""
    return tuple(f"loop_delta_rel_{u}" for u in range(1, cfg.core_loops))


def rope_tables(cfg: Config, slots: int):
    """cos, sin (slots, head_dim / 2) float32 of positions 0..slots-1."""
    hd = cfg.core_head_dim
    inv = 1.0 / cfg.core_rope_theta ** (np.arange(0, hd, 2) / hd)
    ang = np.arange(slots)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _rope(x, cos, sin):
    """Rotate the halves (x[:D/2], x[D/2:]) of x (..., S, heads, D) by
    cos/sin (S, 1, D/2)."""
    x = x.astype(jnp.float32)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _add_normed(x, y, weight):
    """x + RMSNorm(y): the sandwich's second norm sits on a sublayer's
    output."""
    return x + _rms(y, weight, RMS_NORM_EPS)


def layer(cfg: Config, cd, p, x, cache):
    """One application of one layer: x (B, T, d) float32, cache (B, W, 2,
    h, head) the stored (key, value) steps -> (x', cache')."""
    B, T, _ = x.shape
    W, h, hd = cfg.core_context, cfg.core_heads_held, cfg.core_head_dim
    S = W + T
    with jax.named_scope("looped_attention"):
        a = _rms(x, p["input_layernorm"], RMS_NORM_EPS)
        q = _mm(a, p["w_q"], cd).reshape(B, T, h, hd)
        new = jnp.stack([_mm(a, p[w], cd).reshape(B, T, h, hd)
                         for w in ("w_k", "w_v")], axis=2)
    with jax.named_scope("loop_cache"):
        slots = jnp.concatenate([cache, new.astype(cache.dtype)], axis=1)
    with jax.named_scope("looped_attention"):
        cos, sin = rope_tables(cfg, S)
        keys = _rope(slots[:, :, 0], cos[:, None], sin[:, None])
        q = _rope(q, cos[W:, None], sin[W:, None])
        values = slots[:, :, 1]
        scores = jnp.einsum("bthd,bshd->bhts", q.astype(cd), keys.astype(cd),
                            preferred_element_type=jnp.float32)
        # a query at window step t (slot W + t) sees slots t .. W + t
        t_i, s_i = np.arange(T)[:, None], np.arange(S)[None, :]
        seen = jnp.asarray((s_i >= t_i) & (s_i <= t_i + W))
        prob = jax.nn.softmax(
            jnp.where(seen, scores * hd ** -0.5, -jnp.inf), axis=-1)
        o = jnp.einsum("bhts,bshd->bthd", prob.astype(cd), values.astype(cd),
                       preferred_element_type=jnp.float32)
        x = _add_normed(x, _mm(o.reshape(B, T, h * hd), p["w_o"], cd),
                        p["input_layernorm_2"])
    with jax.named_scope("looped_mlp"):
        m = _rms(x, p["post_attention_layernorm"], RMS_NORM_EPS)
        y = _swiglu(m.reshape(B * T, -1), p["mlp"], cd).reshape(x.shape)
        x = _add_normed(x, y, p["post_attention_layernorm_2"])
    with jax.named_scope("loop_cache"):
        return x, slots[:, T:]


def run(cfg: Config, params, feats, hidden, cd):
    """feats (B, T, F), hidden (B, loops, layers, W, 2, h, head) -> (the
    last loop's output (B, T, d) float32, hidden', each loop's ``rms(y_u
    - x_u) / rms(x_u)`` (loops,), x_u the loop's input)."""
    x = _mm(feats, params["in_proj"]["kernel"], cd) + params["in_proj"][
        "bias"].astype(jnp.float32)
    with jax.named_scope("loop_cache"):
        caches = jnp.moveaxis(hidden, 0, 2)         # (loops, layers, B, ...)
    apply = functools.partial(layer, cfg, cd)
    if cfg.remat:
        apply = jax.checkpoint(apply)

    def one_layer(x, xs):
        return apply(xs[0], x, xs[1])

    def one_loop(x, cache):
        y, cache = jax.lax.scan(one_layer, x, (params["layers"], cache))
        y = _rms(y, params["final_norm"], RMS_NORM_EPS)
        delta = jnp.sqrt(jnp.mean(jnp.square(y - x))
                         / jnp.mean(jnp.square(x)))
        return y, (cache, delta)

    y, (caches, deltas) = jax.lax.scan(one_loop, x, caches)
    with jax.named_scope("loop_cache"):
        return y, jnp.moveaxis(caches, 2, 0), deltas


def step_buffers(cfg: Config, buffers, stats):
    """The core's buffers after an update (models/network.step_buffers):
    the online pass's ``loop_delta_rel`` of every loop but the first,
    whose input is the input projection's, not a loop's output."""
    deltas = stats["core"]["loop_deltas"]
    return {**buffers, "core": {**buffers["core"], "counters": deltas[1:]}}


# ---------------------------------------------------------- parameters

def init_layers(key, cfg: Config, pd):
    """Every leaf's leading axis is the layer: LeCun normal matrices, norm
    gains of 1."""
    L, d = (cfg.core_layers,), cfg.core_dim
    width = cfg.core_heads_held * cfg.core_head_dim
    lecun = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                         batch_axis=(0,))
    k = jax.random.split(key, 5)
    ones = functools.partial(jnp.ones, L + (d,), pd)
    return dict(
        w_q=lecun(k[0], L + (d, width), pd),
        w_k=lecun(k[1], L + (d, width), pd),
        w_v=lecun(k[2], L + (d, width), pd),
        w_o=lecun(k[3], L + (width, d), pd),
        input_layernorm=ones(), input_layernorm_2=ones(),
        post_attention_layernorm=ones(), post_attention_layernorm_2=ones(),
        mlp=_swiglu_init(k[4], L, d, cfg.core_dense_dim, pd, lecun))


class Core(nn.Module):
    """Declares the core's parameters and its one buffer, the last
    update's counters (:func:`counter_names`); the arithmetic is
    :func:`run`."""
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
            layers=self.param("layers", init_layers, cfg, pd),
            final_norm=self.param("final_norm", nn.initializers.ones,
                                  (cfg.core_dim,), pd))
        self.variable("buffers", "counters", jnp.zeros,
                      (len(counter_names(cfg)),), jnp.float32)
        out, hidden, deltas = run(cfg, params, feats, hidden,
                                  self.compute_dtype)
        self.sow("stats", "loop_deltas", deltas,
                 reduce_fn=lambda _, new: new, init_fn=lambda: None)
        return out, hidden
