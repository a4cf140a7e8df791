"""The ``xing4`` memory core: a stack of transformer blocks between torso and
heads, each block an attention sublayer and a feed-forward sublayer wrapped
in residual streams.

- **Residual streams** (hyper-connections constrained to doubly stochastic
  maps): ``core_streams`` copies of the residual, read by a learned
  sigmoid map, written by a learned 2·sigmoid map and mixed by a
  Sinkhorn-normalised matrix, all three functions of the streams themselves.
  The expressions here define that arithmetic; a pass of at least a tile of
  tokens at a width of whole lanes runs it as the kernels of
  ``ops/streams.py``, one pass over the streams a sublayer
  (:func:`streams_fused`).
- **Latent attention**: queries through a low-rank latent; keys and values
  expanded from a shared low-rank latent plus one rotary key shared by the
  heads.  What the recurrent state keeps is that latent, not keys and
  values: for each block the ``core_context`` most recent steps' (normed
  latent, unrotated rotary key).  Positions are slots (cache 0..W-1, window
  step i at W+i); RoPE is relative, so no step counter is stored.  A query
  sees the W steps before it and itself, in the unroll (a banded causal
  mask) as in acting (T = 1 against the cache), so the two agree.
- **Routed experts**: sigmoid scores over all ``core_experts``, the top
  ``core_top_k`` chosen by score plus a correction bias (a buffer, never a
  gradient leaf), weights the chosen scores over their sum times
  ``ROUTED_SCALING_FACTOR``.  This chip holds experts 0..``core_experts_held``-1
  and computes their part of the result, as one grouped product over the
  rows routed to them (``jax.lax.ragged_dot``): no row is dropped and none
  is multiplied for an expert it was not routed to.  What absent experts
  would add is left out.  The rows are laid out at the smallest static
  count that holds those routed here (:func:`row_ladder`: all pairs, halved
  down to this chip's fair share), never for all pairs unless they are.  The leading ``core_dense_layers`` blocks have a
  dense SwiGLU instead.

Blocks of a kind are one scanned body over stacked parameters, each
rematerialised as a whole under ``cfg.remat``.  ``benchmark/reference/`` has
the same equations in plain float32.
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
from r2d2_tpu.ops import streams

HIGHEST = jax.lax.Precision.HIGHEST

# the source's constants that no run of this core varies, under the names
# config.json gives them (a configuration's file carries them at its top
# level; tests/test_xing4_core.py holds the two together)
ROPE_BETA_FAST, ROPE_BETA_SLOW = 32.0, 1.0  # rope_scaling.beta_fast, _slow
ROPE_MSCALE_ALL_DIM = 1.0                   # rope_scaling.mscale_all_dim
RMS_NORM_EPS = 1e-6                         # rms_norm_eps
HC_EPS = 1e-6                               # hc_eps: the Sinkhorn divisors
H_RES_CLAMP = 30.0                          # mhc_h_res_clamp_min / _max
ROUTED_SCALING_FACTOR = 2.0                 # routed_scaling_factor
N_SHARED_EXPERTS = 1                        # n_shared_experts


def latent_dim(cfg: Config) -> int:
    """What one step adds to a block's cache (models/state.py has the
    state's whole shape)."""
    return cfg.core_kv_rank + cfg.core_rope_dim


def yarn_inv_freq(cfg: Config) -> np.ndarray:
    """RoPE frequencies under YaRN: interpolated by ``factor`` below the
    ``beta_slow`` rotation count, untouched above ``beta_fast``, a linear
    ramp between (as DeepSeek-V3's modelling code reads the same keys)."""
    dim, theta = cfg.core_rope_dim, cfg.core_rope_theta
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations):
        return (dim * math.log(cfg.core_rope_original
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(ROPE_BETA_FAST)), 0)
    high = min(math.ceil(correction_dim(ROPE_BETA_SLOW)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (extra / cfg.core_rope_factor) * ramp + extra * (1.0 - ramp)


def softmax_scale(cfg: Config) -> float:
    m = 0.1 * ROPE_MSCALE_ALL_DIM * math.log(cfg.core_rope_factor) \
        + 1.0 if cfg.core_rope_factor > 1 else 1.0
    return (cfg.core_nope_dim + cfg.core_rope_dim) ** -0.5 * m * m


def _rope(x, cos, sin):
    """Rotate the pairs (x[2i], x[2i+1]); cos/sin (S, dim/2) broadcast
    against x (..., S, [heads,] dim)."""
    x = x.astype(jnp.float32)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _rms(x, weight, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if weight is None else y * weight.astype(jnp.float32)


def _mm(x, w, cd, out=None):
    """Product in the compute type, accumulated in float32."""
    return jnp.dot(x.astype(cd), w.astype(cd),
                   preferred_element_type=out or jnp.float32)


def _swiglu(x, p, cd):
    h = jax.nn.silu(_mm(x, p["w_gate"], cd, cd)) * _mm(x, p["w_up"], cd, cd)
    return _mm(h, p["w_down"], cd)


def sinkhorn(m, iters: int, eps: float):
    """Rows, then columns, ``iters`` times: towards doubly stochastic.
    ``m`` (n, n, ...): the maps' own axes lead, so that on the chip the
    tokens lie along the lanes and a sum over n is n adds."""
    for _ in range(iters):
        m = m / (m.sum(axis=1, keepdims=True) + eps)
        m = m / (m.sum(axis=0, keepdims=True) + eps)
    return m


@jax.named_scope("residual_mix")
def stream_maps(cfg: Config, p, X, cd):
    """The three maps of one sublayer from the streams X (n arrays (N, d)),
    token-minor: read (n, N), write (n, N), mix (n, n, N)."""
    n, d = cfg.core_streams, cfg.core_dim
    N = X[0].shape[0]
    # RMSNorm of the flattened streams, without a gain, then the three
    # linear maps: stream by stream, so that nothing (N, n d) is laid out
    xs = [x.astype(jnp.float32) for x in X]
    scale = jax.lax.rsqrt(sum(jnp.sum(x * x, axis=-1, keepdims=True)
                              for x in xs) / (n * d) + RMS_NORM_EPS)
    phi = jnp.concatenate([p["phi_pre"], p["phi_post"], p["phi_res"]], axis=1)
    z = sum(_mm(x * scale, phi[k * d:(k + 1) * d], cd)
            for k, x in enumerate(xs))
    alpha = p["alpha"].astype(jnp.float32)
    z = z.T                                                  # (n + n + n n, N)
    pre = jax.nn.sigmoid(alpha[0] * z[:n] + p["b_pre"][:, None])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * z[n:2 * n] + p["b_post"][:, None])
    logits = (alpha[2] * z[2 * n:].reshape(n, n, N)
              + p["b_res"][:, :, None])
    c = H_RES_CLAMP
    res = sinkhorn(jnp.exp(jnp.clip(logits, -c, c)),
                   cfg.core_sinkhorn_iters, HC_EPS)
    return pre, post, res


def _streams_read(pre, X):
    """H_pre X: a weighted sum of the n streams (the maps are n wide: a
    product of that shape would only be re-tiled for the MXU)."""
    with jax.named_scope("residual_mix"):
        return sum(pre[k][:, None] * x.astype(jnp.float32)
                   for k, x in enumerate(X))


def _streams_write(res, post, X, y):
    """H_res X + H_post^T y, stream by stream."""
    with jax.named_scope("residual_mix"):
        y = y.astype(jnp.float32)
        return tuple(
            (sum(res[i, j][:, None] * x.astype(jnp.float32)
                 for j, x in enumerate(X))
             + post[i][:, None] * y).astype(X[i].dtype)
            for i in range(len(X)))


@jax.named_scope("attention")
def attention(cfg: Config, p, u, cache, cd):
    """u (B, T, d), cache (B, W, latent) -> (out (B, T, d), cache')."""
    B, T, _ = u.shape
    W, h = cfg.core_context, cfg.core_heads_held
    dn, dr, dv, r = (cfg.core_nope_dim, cfg.core_rope_dim, cfg.core_v_dim,
                     cfg.core_kv_rank)
    S = W + T
    ang = np.arange(S)[:, None] * yarn_inv_freq(cfg)[None, :]
    cos, sin = (jnp.asarray(f(ang), jnp.float32) for f in (np.cos, np.sin))

    c_q = _rms(_mm(u, p["w_qa"], cd), p["q_norm"], RMS_NORM_EPS)
    q = _mm(c_q, p["w_qb"], cd).reshape(B, T, h, dn + dr)
    q_nope = q[..., :dn]
    q_rope = _rope(q[..., dn:], cos[W:, None, :], sin[W:, None, :])

    kv_a = _mm(u, p["w_kva"], cd)
    c_kv = _rms(kv_a[..., :r], p["kv_norm"], RMS_NORM_EPS)
    new = jnp.concatenate([c_kv, kv_a[..., r:]], axis=-1).astype(cache.dtype)
    slots = jnp.concatenate([cache, new], axis=1)            # (B, S, latent)
    kv = _mm(slots[..., :r], p["w_kvb"], cd).reshape(B, S, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k_rope = _rope(slots[..., r:], cos, sin)                 # (B, S, dr)

    scores = (jnp.einsum("bthd,bshd->bhts", q_nope.astype(cd),
                         k_nope.astype(cd),
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bthd,bsd->bhts", q_rope.astype(cd),
                           k_rope.astype(cd),
                           preferred_element_type=jnp.float32))
    # a query at window step t (slot W + t) sees slots t .. W + t
    t_i, s_i = np.arange(T)[:, None], np.arange(S)[None, :]
    seen = jnp.asarray((s_i >= t_i) & (s_i <= t_i + W))
    scores = jnp.where(seen, scores * softmax_scale(cfg), -jnp.inf)
    prob = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", prob.astype(cd), v.astype(cd),
                   preferred_element_type=jnp.float32)
    out = _mm(o.reshape(B, T, h * dv), p["w_o"], cd)
    return out, slots[:, T:]


# the grouped products' row tile: the TPU compiler gives ragged_dot's kernel
# 256 rows a tile on a v5e, or 512 where they divide the rows
# (``ragged_dot_tiling`` in its text; tests/test_tpu_compile.py holds the two
# together).  A rung of the row ladder is a whole number of them
ROW_TILE = 256


def row_ladder(cfg: Config, pairs: int) -> tuple:
    """The static row counts at which the held experts' rows may be laid
    out for ``pairs`` routed (token, expert) pairs, ascending: ``pairs``
    halved down to this chip's fair share ``core_experts_held /
    core_experts`` of them, each a whole number of row tiles.  The last is
    ``pairs`` itself, which holds whatever the router does; a chip that
    holds every expert has that rung alone."""
    halvings = int(math.log2(cfg.core_experts // cfg.core_experts_held))
    return tuple(sorted({
        min(-(-pairs // (ROW_TILE << j)) * ROW_TILE, pairs)
        for j in range(halvings + 1)}))


def rung_of(ladder: tuple, live):
    """Index of the smallest rung that holds ``live`` rows."""
    return sum((live > r).astype(jnp.int32) for r in ladder[:-1])


def rows_laid_out(cfg: Config, pairs: int, load):
    """The rung at which :func:`routed_experts` lays out the rows of
    ``pairs`` routed pairs whose loads by expert are ``load`` (E,)."""
    ladder = row_ladder(cfg, pairs)
    live = load[:cfg.core_experts_held].sum()
    return jnp.asarray(ladder, jnp.float32)[rung_of(ladder, live)]


def _gather_sum(y, w, at):
    """``out[n] = sum_j w[n, j] * y[slot[n, j]]`` in float32: rows (R, d)
    summed into their tokens (N, d) as k gathers of N rows, so that
    nothing (N k, d) is laid out."""
    slot = at["slot"]
    return sum(w[:, j, None].astype(jnp.float32)
               * y[slot[:, j]].astype(jnp.float32)
               for j in range(slot.shape[1]))


# Where the sorted pairs' rows lie, ``at``: of row r its flat pair ``pair``
# (R,) and token ``tok`` (R,), whether it is ``live`` (R, 1); of a token's
# k pairs their rows ``slot`` (N, k) and whether their expert is held
# ``here`` (N, k), so that they lie among the rows at all.

@jax.custom_vjp
def _token_rows(x, at):
    """Row r is the token of the sorted pair r: ``x[tok]``, (N, d) ->
    (R, d).  Backward, a token sums the rows of its own pairs: a gather
    (autodiff's transpose of a gather is a scatter-add).  The rows past
    the live ones are selected away first: no product defined them."""
    return x[at["tok"]]


def _token_rows_fwd(x, at):
    return x[at["tok"]], at


def _token_rows_bwd(at, g):
    g = jnp.where(at["live"], g, jnp.zeros((), g.dtype))
    return _gather_sum(g, at["here"], at).astype(g.dtype), None


_token_rows.defvjp(_token_rows_fwd, _token_rows_bwd)


@jax.custom_vjp
def _combine(y, w, at):
    """The tokens' results from the sorted pairs' rows: ``out[n] = sum_j
    w[n, j] * y[slot[n, j]]``, (R, d) -> (N, d) f32; ``w`` is 0 where a
    pair's expert is absent, ``y`` where no product defined a row.
    Backward, row r takes its token's cotangent times its pair's weight:
    gathers of R rows."""
    return _gather_sum(y, w, at)


def _combine_fwd(y, w, at):
    return _gather_sum(y, w, at), (y, w, at)


def _combine_bwd(saved, g):
    y, w, at = saved
    g = g.astype(y.dtype)[at["tok"]].astype(jnp.float32)
    g_w = jnp.sum(g * y.astype(jnp.float32), axis=-1)[at["slot"]]
    g_y = w.reshape(-1)[at["pair"]][:, None].astype(jnp.float32) * g
    return g_y.astype(y.dtype), g_w.astype(w.dtype), None


_combine.defvjp(_combine_fwd, _combine_bwd)


def route(cfg: Config, scores, bias):
    """(chosen experts (N, k) i32, weights (N, k) f32) from the router's
    scores (N, E): chosen by score + bias, weighted by score alone."""
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias),
                              cfg.core_top_k)
    w = jnp.take_along_axis(scores, chosen, axis=1)
    w = w / w.sum(axis=1, keepdims=True) * ROUTED_SCALING_FACTOR
    return chosen.astype(jnp.int32), w


def routed_experts(cfg: Config, p, u, bias, cd):
    """Shared expert + this chip's part of the routed experts' result for
    tokens u (N, d); also the pairs routed to every expert (E,)."""
    N = u.shape[0]
    k, E, held = cfg.core_top_k, cfg.core_experts, cfg.core_experts_held
    with jax.named_scope("router"):
        scores = jax.nn.sigmoid(jnp.dot(
            u.astype(jnp.float32), p["w_router"].astype(jnp.float32),
            precision=HIGHEST))
        chosen, weights = route(cfg, scores, bias)
        load = (chosen[:, :, None] == jnp.arange(E)).sum(
            axis=(0, 1)).astype(jnp.float32)
    with jax.named_scope("experts"):
        # pairs sorted by expert; those of absent experts go last, as one
        # group that is given no product.  The live pairs come first, so
        # the first R sorted pairs hold them all whenever there are at
        # most R: the rows are laid out at the smallest rung of the ladder
        # that does, and the last rung is every pair
        order = jnp.argsort(jnp.minimum(chosen.reshape(-1), held),
                            stable=True).astype(jnp.int32)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(N * k, dtype=jnp.int32)).reshape(N, k)
        sizes = load[:held].astype(jnp.int32)
        ladder = row_ladder(cfg, N * k)

        def laid_out(R, u, ex, w, here, order, inverse, sizes):
            # a pair of an absent expert sits past the held groups, where
            # no product defines a row, forward or backward: what a
            # grouped product returns there is selected away, never
            # multiplied by zero, so that it reaches no result and no
            # gradient
            live = (jnp.arange(R) < sizes.sum())[:, None]

            def grouped(rows, w):
                out = jax.lax.ragged_dot(rows, w.astype(cd), sizes)
                return jnp.where(live, out, jnp.zeros((), out.dtype))

            pair = order[:R]
            at = dict(pair=pair, tok=pair // k, live=live, here=here,
                      slot=jnp.minimum(inverse, R - 1))
            rows = _token_rows(u, at)
            hid = (jax.nn.silu(grouped(rows, ex["w_gate"]))
                   * grouped(rows, ex["w_up"]))
            return _combine(grouped(hid, ex["w_down"]), w, at)

        here = chosen < held
        operands = (u.astype(cd), p["experts"],
                    jnp.where(here, weights, 0.0).astype(cd), here,
                    order, inverse, sizes)
        if len(ladder) == 1:
            out = laid_out(ladder[0], *operands)
        else:
            # each rung rematerialised on its own: what a branch keeps for
            # its backward would else be laid out, as zeros, by every
            # other branch too
            out = jax.lax.switch(
                rung_of(ladder, sizes.sum()),
                [jax.checkpoint(functools.partial(laid_out, R))
                 for R in ladder], *operands)
    with jax.named_scope("shared_expert"):
        out = out + _swiglu(u, p["shared"], cd)
    return out, load


def streams_fused(cfg: Config, tokens: int) -> bool:
    """Whether a pass over ``tokens`` tokens takes the streams' kernels
    (ops/streams.py): decided by the shapes and by where the program is
    traced for, not by a knob.  The train step and the target forward of a
    width of whole lanes do; acting's few tokens, and the CPU at test
    widths unless ``pallas_interpret``, keep the expressions above."""
    return streams.fits(tokens, cfg.core_dim, cfg.core_streams) and (
        cfg.pallas_interpret or jax.default_backend() == "tpu")


def _attend(cfg: Config, cd, u, args):
    """The attention sublayer of the normed read u (N, d)."""
    p, cache = args
    y, cache = attention(cfg, p, u.reshape(cache.shape[0], -1, cfg.core_dim),
                         cache, cd)
    return y.reshape(-1, cfg.core_dim), cache


def _feed(cfg: Config, cd, u, args):
    """The feed-forward sublayer of u (N, d): dense for ``(p,)``, routed
    for ``(p, bias)``; with the pairs routed to every expert."""
    if len(args) == 1:
        with jax.named_scope("dense_ffn"):
            return (_swiglu(u, args[0], cd),
                    jnp.zeros(cfg.core_experts, jnp.float32))
    return routed_experts(cfg, args[0], u, args[1], cd)


def block(cfg: Config, p, X, cache, bias, cd):
    """One block over the streams X (n arrays (B T, d)) and its cache (B,
    W, latent); ``bias`` None marks a dense block.  Returns (X', cache',
    load (E,), rows laid out for the experts)."""
    feed_args = (p["dense"],) if bias is None else (p["moe"], bias)
    if streams_fused(cfg, X[0].shape[0]):
        # the router multiplies u in float32 at HIGHEST precision: a
        # routed feed-forward reads it unrounded
        X, cache, load = streams.block(
            streams.Spec(cfg.core_streams, cfg.core_sinkhorn_iters,
                         RMS_NORM_EPS, HC_EPS, H_RES_CLAMP, cd,
                         cfg.pallas_interpret),
            functools.partial(_attend, cfg, cd),
            functools.partial(_feed, cfg, cd),
            cd if bias is None else jnp.float32,
            {k: p[k] for k in ("attn_mix", "attn_norm", "ffn_mix",
                               "ffn_norm")},
            X, (p["attn"], cache), feed_args)
    else:
        pre, post, res = stream_maps(cfg, p["attn_mix"], X, cd)
        u = _rms(_streams_read(pre, X), p["attn_norm"], RMS_NORM_EPS)
        y, cache = _attend(cfg, cd, u, (p["attn"], cache))
        X = _streams_write(res, post, X, y)

        pre, post, res = stream_maps(cfg, p["ffn_mix"], X, cd)
        u = _rms(_streams_read(pre, X), p["ffn_norm"], RMS_NORM_EPS)
        y, load = _feed(cfg, cd, u, feed_args)
        X = _streams_write(res, post, X, y)
    rows = 0.0 if bias is None else rows_laid_out(
        cfg, X[0].shape[0] * cfg.core_top_k, load)
    return X, cache, load, rows


def run(cfg: Config, params, router_bias, feats, hidden, cd):
    """feats (B, T, F), hidden (B, layers, W, latent) -> (out (B, T, d),
    hidden', loads (moe layers, E), rows laid out (moe layers,))."""
    B, T, _ = feats.shape
    x = _mm(feats, params["in_proj"]["kernel"], cd) + params["in_proj"]["bias"]
    x = x.astype(cd).reshape(B * T, cfg.core_dim)
    X = (x,) * cfg.core_streams
    caches = hidden.swapaxes(0, 1)                   # (layers, B, W, latent)
    nd = cfg.core_dense_layers

    def body(dense):
        def step(X, xs):
            p, cache, bias = xs
            X, cache, load, rows = block(cfg, p, X, cache,
                                         None if dense else bias, cd)
            return X, (cache, load, rows)
        return jax.checkpoint(step) if cfg.remat else step

    new_caches = []
    loads, rows = jnp.zeros((0, cfg.core_experts), jnp.float32), jnp.zeros(0)
    if nd:
        X, (c, _, _) = jax.lax.scan(
            body(True), X, (params["dense_layers"], caches[:nd],
                            jnp.zeros((nd, 0), jnp.float32)))
        new_caches.append(c)
    if cfg.core_layers > nd:
        X, (c, loads, rows) = jax.lax.scan(
            body(False), X, (params["moe_layers"], caches[nd:], router_bias))
        new_caches.append(c)
    with jax.named_scope("residual_mix"):
        out = _rms(sum(x.astype(jnp.float32) for x in X),
                   params["final_norm"], RMS_NORM_EPS)
    return (out.reshape(B, T, cfg.core_dim),
            jnp.concatenate(new_caches).swapaxes(0, 1), loads, rows)


def bias_update(cfg: Config, router_bias, loads):
    """The correction bias after an update: up by ``core_bias_rate`` where
    an expert drew less than the mean load, down where more."""
    mean = loads.mean(axis=-1, keepdims=True)
    return router_bias + cfg.core_bias_rate * jnp.sign(mean - loads)


def load_counters(cfg: Config, router_bias, loads, rows, fused=0.0):
    """COUNTERS, (6,) f32: the share of routed pairs that fell to experts
    held here, the held experts' largest load over their mean, the largest
    |bias|; the rows the blocks laid out for their held pairs over all
    routed pairs (1 when every block took the last rung), and the largest
    single block's held pairs over its routed pairs, which decides that
    block's rung; and ``fused``, the share of the online pass's sublayer
    passes over the streams that took the kernels (:func:`streams_fused`:
    all of them or none, fixed when the step is traced)."""
    held = loads[:, :cfg.core_experts_held]
    pairs = jnp.maximum(loads.sum(axis=1), 1.0)
    return jnp.stack([
        held.sum() / jnp.maximum(loads.sum(), 1.0),
        (held.max(axis=1) / jnp.maximum(held.mean(axis=1), 1e-9)).max(),
        jnp.abs(router_bias).max(),
        rows.sum() / pairs.sum(),
        (held.sum(axis=1) / pairs).max(),
        jnp.asarray(fused, jnp.float32)])


COUNTERS = ("held_pair_share", "held_load_max_over_mean", "router_bias_max",
            "expert_rows_share", "held_rows_max_share", "stream_passes_fused")


def step_buffers(cfg: Config, buffers, stats):
    """The core's buffers after an update (models/network.step_buffers):
    the correction bias moved towards balance by the loads the online pass
    sowed, and that update's counters."""
    loads = stats["core"]["expert_load"]
    bias = bias_update(cfg, buffers["core"]["router_bias"], loads)
    return {**buffers, "core": {
        **buffers["core"], "router_bias": bias,
        "counters": load_counters(cfg, bias, loads,
                                  stats["core"]["expert_rows"],
                                  stats["core"]["stream_passes_fused"])}}


def _mix_init(cfg: Config, key, layers: int, pd):
    """A sublayer's stream-map parameters.  Initial values are this repo's
    (the source gives none): maps that differ by stream from the first
    block on, a read near the streams' mean, a write near 1, a mix near
    the identity."""
    n, d = cfg.core_streams, cfg.core_dim
    k = jax.random.split(key, 6)
    noise = nn.initializers.normal(0.5)
    return dict(
        phi_pre=_LECUN(k[0], (layers, n * d, n), pd),
        phi_post=_LECUN(k[1], (layers, n * d, n), pd),
        phi_res=_LECUN(k[2], (layers, n * d, n * n), pd),
        alpha=jnp.full((layers, 3), 0.1, pd),
        b_pre=noise(k[3], (layers, n), pd) - math.log(max(n - 1.0, 1.0)),
        b_post=noise(k[4], (layers, n), pd),
        b_res=noise(k[5], (layers, n, n), pd) + 3.0 * jnp.eye(n, dtype=pd))


_LECUN = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                      batch_axis=(0,))
_LECUN_EXPERTS = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                              batch_axis=(0, 1))


def _swiglu_init(key, lead, d: int, width: int, pd, init=_LECUN):
    k = jax.random.split(key, 3)
    return dict(w_gate=init(k[0], lead + (d, width), pd),
                w_up=init(k[1], lead + (d, width), pd),
                w_down=init(k[2], lead + (width, d), pd))


def init_blocks(key, cfg: Config, layers: int, dense: bool, pd):
    """The parameters of ``layers`` blocks of one kind, stacked: every
    leaf's leading axis is the block."""
    d, h, L = cfg.core_dim, cfg.core_heads_held, (layers,)
    k = jax.random.split(key, 11)
    ones = functools.partial(jnp.ones, dtype=pd)
    out = dict(
        attn_mix=_mix_init(cfg, k[0], layers, pd),
        attn_norm=ones(L + (d,)),
        attn=dict(
            w_qa=_LECUN(k[1], L + (d, cfg.core_q_rank), pd),
            q_norm=ones(L + (cfg.core_q_rank,)),
            w_qb=_LECUN(k[2], L + (cfg.core_q_rank, h * (
                cfg.core_nope_dim + cfg.core_rope_dim)), pd),
            w_kva=_LECUN(k[3], L + (d, latent_dim(cfg)), pd),
            kv_norm=ones(L + (cfg.core_kv_rank,)),
            w_kvb=_LECUN(k[4], L + (cfg.core_kv_rank, h * (
                cfg.core_nope_dim + cfg.core_v_dim)), pd),
            w_o=_LECUN(k[5], L + (h * cfg.core_v_dim, d), pd)),
        ffn_mix=_mix_init(cfg, k[6], layers, pd),
        ffn_norm=ones(L + (d,)))
    if dense:
        out["dense"] = _swiglu_init(k[7], L, d, cfg.core_dense_dim, pd)
    else:
        out["moe"] = dict(
            w_router=_LECUN(k[8], L + (d, cfg.core_experts), pd),
            shared=_swiglu_init(k[9], L, d, N_SHARED_EXPERTS
                                * cfg.core_expert_dim, pd),
            experts=_swiglu_init(k[10], L + (cfg.core_experts_held,), d,
                                 cfg.core_expert_dim, pd, _LECUN_EXPERTS))
    return out


class Core(nn.Module):
    """Declares the core's parameters (one tree a kind of block, stacked:
    the leading axis is the block) and its one buffer, the router's
    correction bias; the arithmetic is :func:`run`."""
    cfg: Config
    compute_dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, feats, hidden):
        cfg, pd = self.cfg, self.param_dtype
        nd = cfg.core_dense_layers
        nm = cfg.core_layers - nd
        params = dict(
            in_proj=dict(
                kernel=self.param("in_proj_kernel",
                                  nn.initializers.lecun_normal(),
                                  (feats.shape[-1], cfg.core_dim), pd),
                bias=self.param("in_proj_bias", nn.initializers.zeros,
                                (cfg.core_dim,), pd)),
            final_norm=self.param("final_norm", nn.initializers.ones,
                                  (cfg.core_dim,), pd))
        if nd:
            params["dense_layers"] = self.param(
                "dense_layers", init_blocks, cfg, nd, True, pd)
        if nm:
            params["moe_layers"] = self.param(
                "moe_layers", init_blocks, cfg, nm, False, pd)
        bias = self.variable("buffers", "router_bias", jnp.zeros,
                             (nm, cfg.core_experts), jnp.float32)
        # the last update's COUNTERS (step_buffers writes them)
        self.variable("buffers", "counters", jnp.zeros,
                      (len(COUNTERS),), jnp.float32)
        out, hidden, loads, rows = run(cfg, params, bias.value, feats,
                                       hidden, self.compute_dtype)
        fused = jnp.float32(streams_fused(
            cfg, feats.shape[0] * feats.shape[1]))
        for name, value in (("expert_load", loads), ("expert_rows", rows),
                            ("stream_passes_fused", fused)):
            self.sow("stats", name, value,
                     reduce_fn=lambda _, new: new, init_fn=lambda: None)
        return out, hidden
