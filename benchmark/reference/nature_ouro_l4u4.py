"""Reference of ``nature_ouro_l4u4``: the Nature-DQN torso on space-to-depth
frames, then — in place of the LSTM — the looped layers of Ouro-2.6B
(``config.json`` keys in brackets), then dueling heads and R2D2's loss.
Plain float32 ``jax.numpy``: Python loops over the loops, the layers and
the heads; each query's attention is over the W + 1 keys it sees, gathered
as a window of its own, never a masked square.  Nothing of the program is
imported; its parameter tree is read by name, its state by the layout
written out below, and the sizes no shape gives come from the
configuration's own file.

The equations, with features f (torso's 512 + one-hot last action + last
reward) and a residual x of width d [hidden_size]:

- x = f W_in + b (stands where the embedding stands).
- Loop u = 0 .. [total_ut_steps] - 1 runs layers l = 0 .. L - 1, each
  [layer_types] ``full_attention``:
  a = RMSNorm_in(x); q, k, v = a W_q, a W_k, a W_v, heads of [head_dim],
  as many key/value heads as query heads [num_key_value_heads]; q and k
  rotated by RoPE [rope_theta] at their positions (x cos + rotate_half(x)
  sin); a query at window step t softmaxes q k^T / sqrt(head_dim) over the
  keys of steps t - W .. t, the stored ones of (u, l) first, then the
  window's; x = x + RMSNorm_in2(o W_o); x = x + RMSNorm_post2(W_down(silu(
  W_gate m) * W_up m)) with m = RMSNorm_post(x) [intermediate_size,
  hidden_act]; eps [rms_norm_eps].
- After the last layer of loop u: y_u = RMSNorm_final(x), the next loop's
  x.  Q is read from y of the last loop.

One state (B, loops, layers, W, 2, heads, head_dim): for every loop and
layer the W most recent steps' (key, value), oldest first, keys unrotated;
the stored step w sits at position w and window step t at position W + t.

Departures from the source, each also in the configuration file: 4 of its
48 layers, looped on themselves (one stage of a pipeline); no vocabulary;
no early-exit gate (at threshold 1 it does not touch the output); assumed:
the loop's input being the final-normed y, the zero state.
"""
from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import r2d2_common as common
from benchmark.reference.nature_lstm512 import torso

_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "nature_ouro_l4u4.json")


def hyper_parameters(core_dim: int) -> dict:
    """The sizes no parameter's shape gives, from the configuration's own
    file: its ``config`` where the width is the published one, with its
    ``small`` overrides on top where it is the tests' small size."""
    with open(_FILE) as f:
        doc = json.load(f)
    hp = dict(doc["config"])
    if core_dim != hp["core_dim"]:
        hp.update(doc["small"])
    hp.update(eps=doc["rms_norm_eps"], theta=float(doc["rope_theta"]),
              loops=doc["total_ut_steps"])
    return hp


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def swiglu(x, p):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def rotate(x, positions, theta):
    """RoPE of x (..., S, head_dim) at ``positions`` (S,)."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, hd, 2) / hd)
    ang = np.asarray(positions)[:, None] * freqs[None, :]
    ang = np.concatenate([ang, ang], axis=-1)               # (S, hd)
    half = hd // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return (x * jnp.asarray(np.cos(ang), jnp.float32)
            + turned * jnp.asarray(np.sin(ang), jnp.float32))


def attention(hp, p, a, stored, heads):
    """a (B, T, d) the normed input, stored (B, W, 2, heads, hd) -> o W_o
    (B, T, d)."""
    B, T, _ = a.shape
    W, hd, theta = stored.shape[1], hp["core_head_dim"], hp["theta"]
    q, k, v = a @ p["w_q"], a @ p["w_k"], a @ p["w_v"]
    flat = stored.reshape(B, W, 2, heads * hd)
    keys = jnp.concatenate([flat[:, :, 0], k], axis=1)          # (B, W+T, .)
    values = jnp.concatenate([flat[:, :, 1], v], axis=1)
    window = np.arange(T)[:, None] + np.arange(W + 1)[None, :]  # (T, W+1)
    out = []
    for i in range(heads):
        part = slice(i * hd, (i + 1) * hd)
        qi = rotate(q[..., part], np.arange(W, W + T), theta)
        ki = rotate(keys[..., part], np.arange(W + T), theta)
        seen_k = ki[:, window]                      # (B, T, W + 1, hd)
        seen_v = values[..., part][:, window]
        w = jax.nn.softmax(jnp.einsum("btd,btsd->bts", qi, seen_k)
                           * hd ** -0.5, axis=-1)
        out.append(jnp.einsum("bts,btsd->btd", w, seen_v))
    return jnp.concatenate(out, axis=-1) @ p["w_o"]


def layer(hp, heads, blk, x, stored):
    """One layer applied to x (B, T, d) over the stored steps (B, W, 2,
    heads, hd) of its (loop, layer)."""
    eps = hp["eps"]
    a = rms_norm(x, blk["input_layernorm"], eps)
    x = x + rms_norm(attention(hp, blk, a, stored, heads),
                     blk["input_layernorm_2"], eps)
    m = rms_norm(x, blk["post_attention_layernorm"], eps)
    return x + rms_norm(swiglu(m, blk["mlp"]),
                        blk["post_attention_layernorm_2"], eps)


@functools.lru_cache(maxsize=None)
def compiled_layer(core_dim: int, heads: int):
    """:func:`layer` of the configuration at this width, as one program
    (compiled at the matmul precision the caller has set): a layer's 16
    heads are some 300 operations, and a run applies 16 layers in each of
    eight unrolls of each of four draws."""
    return jax.jit(functools.partial(layer, hyper_parameters(core_dim),
                                     heads))


def core(hp, p, x, hidden, heads):
    """x (B, T, d) the core's inputs, hidden (B, loops, layers, W, 2,
    heads, hd) -> the last loop's y (B, T, d).  Loop u's application of layer l
    attends over the stored steps of (u, l) alone."""
    layers = p["layers"]
    apply = compiled_layer(x.shape[-1], heads)
    for u in range(hp["loops"]):
        for l in range(layers["w_q"].shape[0]):
            blk = jax.tree.map(lambda w, l=l: w[l].astype(jnp.float32),
                               layers)
            x = apply(blk, x, hidden[:, u, l])
        x = rms_norm(x, p["final_norm"].astype(jnp.float32), hp["eps"])
    return x


def unroll(params, obs, last_action, last_reward, hidden):
    """Q over every step of the window: obs (B, T, ...) uint8, hidden (B,
    loops, layers, W, 2, heads, hd).  Returns (B, T, A)."""
    p = params["params"]
    pc = p["core"]
    hp = hyper_parameters(pc["in_proj_kernel"].shape[1])
    hidden = jnp.asarray(hidden).astype(jnp.float32)
    heads = hidden.shape[-2]
    B, T = obs.shape[:2]
    x = obs.reshape(B * T, *obs.shape[2:]).astype(jnp.float32) / 255.0
    feats = jnp.concatenate(
        [torso(p["torso"], x).reshape(B, T, -1),
         last_action.astype(jnp.float32),
         last_reward[..., None].astype(jnp.float32)], axis=-1)
    x0 = feats @ pc["in_proj_kernel"].astype(jnp.float32) + pc["in_proj_bias"]
    outs = core(hp, pc, x0, hidden, heads)
    return common.dueling_head(p["head"], outs.reshape(B * T, -1)).reshape(
        B, T, -1)


def loss(params, target_params, batch, n: int):
    """R2D2's loss as ``r2d2_common.loss`` has it (importance-weighted mean
    squared n-step double-Q TD error under the value rescaling h), over
    this configuration's unroll.  Returns (loss, q over the learning
    steps)."""
    args = (batch["obs"], batch["last_action"], batch["last_reward"],
            batch["hidden"])
    q = unroll(params, *args)
    q_target = unroll(target_params, *args)
    B, L = batch["action"].shape
    total = valid = 0.0
    q_learn = []
    for b in range(B):
        burn, learn, fwd = (int(batch[k][b]) for k in
                            ("burn_in", "learning", "forward"))
        q_learn.append(q[b, burn:burn + L])
        for i in range(learn):
            t_boot = min(burn + i + n, burn + learn + fwd - 1)
            a_star = jnp.argmax(q[b, t_boot])
            y = common.h(batch["n_step_reward"][b, i]
                         + batch["n_step_gamma"][b, i]
                         * common.h_inv(q_target[b, t_boot, a_star]))
            td = y - q[b, burn + i, batch["action"][b, i]]
            total = total + batch["is_weights"][b] * td * td
            valid += 1.0
    return total / valid, jnp.stack(q_learn)
