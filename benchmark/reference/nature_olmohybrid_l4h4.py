"""Reference of ``nature_olmohybrid_l4h4``: the Nature-DQN torso on
space-to-depth frames, then — in place of the LSTM — layers of
Olmo-Hybrid-7B (``config.json`` keys in brackets), then dueling heads and
R2D2's loss.  Plain float32 ``jax.numpy``: a Python loop over the window's
steps (each step one compiled program), over layers and over heads; the
delta rule is its one-step recurrence, stepped, never a chunked form.
Nothing of the program is imported; its parameter tree is read by name, its
state by the layout written out below, and the sizes no shape gives come
from the configuration's own file.

The equations, per step with features f (torso's 512 + one-hot last action +
last reward) and x the residual of width d [hidden_size]:

- x0 = f W_in + b (stands where the embedding stands).  Layer i is
  [layer_types][i]; every block is h = x + RMSNorm(Mixer(x)), y = h +
  RMSNorm(W_down(silu(W_gate h) * W_up h)) [intermediate_size, hidden_act],
  eps [rms_norm_eps]; after the last block one more RMSNorm.
- ``linear_attention`` (the gated delta rule, arXiv:2412.06464), per head of
  d_k [linear_key_head_dim] and d_v [linear_value_head_dim]: q~, k~, v =
  silu(conv(W_q x)), silu(conv(W_k x)), silu(conv(W_v x)) with a depthwise
  causal convolution over the last [linear_conv_kernel_dim] steps; q = q~ /
  |q~| d_k^-1/2, k = k~ / |k~|; beta = 2 sigmoid(W_b x) [the 2 is
  linear_allow_neg_eigval]; alpha = exp(-exp(A_log) softplus(W_a x +
  dt_bias)); S~ = alpha S; S' = S~ + k (beta (v - S~^T k))^T; o = S'^T q;
  Mixer(x) = W_o [RMSNorm_{d_v}(o) * silu(W_g x)].
- ``full_attention``, heads of [head_dim, hidden_size / num_attention_heads
  = 128]: q = RMSNorm(W_q x), k = RMSNorm(W_k x) over the projection's
  whole width, v = W_v x; no rotary embedding [rope_theta null]; softmax
  with scale 128^-1/2 over the W stored steps' (k, v) and the window's own,
  a query seeing the W steps before it and itself; stored zeros are
  attended like any step.

One state, a flat vector folded into tiles of 16 rows of 128
(``split_state``): every linear layer's S (h, d_k, d_v); every linear layer's
last 3 pre-convolution rows of the channels [q | k | v]; the W most recent
steps' [k | v] of every softmax layer, step-major.

Departures from the source, each also in the configuration file: this
chip's share (heads 0..h-1 of both mixers, the QK-norm's mean square over
them); no vocabulary; assumed where ``config.json`` is silent: norm
placement, the QK-norm's span, no rotary embedding, the zero state.
"""
from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp

from benchmark.reference import r2d2_common as common
from benchmark.reference.nature_lstm512 import torso

_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "nature_olmohybrid_l4h4.json")
L2_EPS = 1e-6       # flash-linear-attention's l2norm


def hyper_parameters(core_dim: int) -> dict:
    """The sizes no parameter's shape gives, from the configuration's own
    file: its ``config`` where the width is the published one, with its
    ``small`` overrides on top where it is the tests' small size."""
    with open(_FILE) as f:
        doc = json.load(f)
    hp = dict(doc["config"])
    if core_dim != hp["core_dim"]:
        hp.update(doc["small"])
    hp.update(eps=doc["rms_norm_eps"], kernel=doc["linear_conv_kernel_dim"],
              layer_types=doc["layer_types"][:hp["core_layers"]],
              beta_scale=2.0 if doc["linear_allow_neg_eigval"] else 1.0)
    return hp


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def swiglu(x, p):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def l2_normed(x):
    return x / jnp.sqrt((x * x).sum(axis=-1, keepdims=True) + L2_EPS)


def linear_attention_step(hp, p, x, S, tail, heads):
    """One step of one layer: x (B, d), S (B, h, d_k, d_v), tail (B, K - 1,
    channels) the pre-convolution rows of the steps before."""
    dk, dv = hp["core_linear_key_dim"], hp["core_linear_value_dim"]
    row = jnp.concatenate([x @ p["w_q"], x @ p["w_k"], x @ p["w_v"]], axis=-1)
    rows = jnp.concatenate([tail, row[:, None]], axis=1)        # (B, K, ch)
    taps = jnp.concatenate([p["conv_q"], p["conv_k"], p["conv_v"]], axis=-1)
    qkv = jax.nn.silu((rows * taps).sum(axis=1))
    beta = hp["beta_scale"] * jax.nn.sigmoid(x @ p["w_b"])      # (B, h)
    alpha = jnp.exp(-jnp.exp(p["A_log"])
                    * jax.nn.softplus(x @ p["w_a"] + p["dt_bias"]))
    gate = jax.nn.silu(x @ p["w_g"])
    out, kept = [], []
    for i in range(heads):
        q = l2_normed(qkv[:, i * dk:(i + 1) * dk]) * dk ** -0.5
        k = l2_normed(qkv[:, (heads + i) * dk:(heads + i + 1) * dk])
        v = qkv[:, 2 * heads * dk + i * dv:2 * heads * dk + (i + 1) * dv]
        decayed = alpha[:, i, None, None] * S[:, i]             # (B, dk, dv)
        u = beta[:, i, None] * (v - jnp.einsum("bkv,bk->bv", decayed, k))
        new = decayed + k[:, :, None] * u[:, None, :]
        o = jnp.einsum("bkv,bk->bv", new, q)
        out.append(rms_norm(o, p["o_norm"], hp["eps"])
                   * gate[:, i * dv:(i + 1) * dv])
        kept.append(new)
    return (jnp.concatenate(out, axis=-1) @ p["w_o"],
            jnp.stack(kept, axis=1), rows[:, 1:])


def full_attention_step(hp, p, x, stored, heads):
    """One step of one layer: x (B, d), stored (B, W, 2 h head) the W steps
    before, oldest first."""
    hd = hp["core_head_dim"]
    q = rms_norm(x @ p["w_q"], p["q_norm"], hp["eps"])
    k = rms_norm(x @ p["w_k"], p["k_norm"], hp["eps"])
    seen = jnp.concatenate(
        [stored, jnp.concatenate([k, x @ p["w_v"]], axis=-1)[:, None]],
        axis=1)                                             # (B, W + 1, .)
    out = []
    for i in range(heads):
        keys = seen[:, :, i * hd:(i + 1) * hd]
        values = seen[:, :, (heads + i) * hd:(heads + i + 1) * hd]
        w = jax.nn.softmax(jnp.einsum(
            "bd,bsd->bs", q[:, i * hd:(i + 1) * hd], keys) * hd ** -0.5,
            axis=-1)
        out.append(jnp.einsum("bs,bsd->bd", w, values))
    return jnp.concatenate(out, axis=-1) @ p["w_o"], seen[:, 1:]


def split_state(hp, hidden, heads):
    """The state (B, rows, 128) -> per linear layer (S, tail), per softmax
    layer the stored rows, in the layers' order.  Flat, the state is the
    matrices, then the tails, zero-padded to whole tiles of 16 rows of 128;
    then the stored keys and values, padded likewise."""
    B, _, lanes = hidden.shape
    dk, dv = hp["core_linear_key_dim"], hp["core_linear_value_dim"]
    W, hd = hp["core_context"], hp["core_head_dim"]
    linear = hp["layer_types"].count("linear_attention")
    full = len(hp["layer_types"]) - linear
    ch, K = heads * (2 * dk + dv), hp["kernel"]
    sizes = (linear * heads * dk * dv, linear * (K - 1) * ch,
             W * full * 2 * heads * hd)

    def tile_rows(size):
        return -(-size // (16 * lanes)) * 16

    first = tile_rows(sizes[0] + sizes[1])          # rows of the first part
    assert hidden.shape[1] == first + tile_rows(sizes[2]), (hidden.shape,
                                                             sizes)
    whole = hidden[:, :first].reshape(B, -1)
    S = whole[:, :sizes[0]].reshape(B, linear, heads, dk, dv)
    tails = whole[:, sizes[0]:sizes[0] + sizes[1]].reshape(
        B, linear, K - 1, ch)
    rows = hidden[:, first:].reshape(B, -1)[:, :sizes[2]].reshape(
        B, W, full, 2 * heads * hd)
    return ([(S[:, i], tails[:, i]) for i in range(linear)],
            [rows[:, :, i] for i in range(full)])


def step(kinds, eps, layer_step, blocks, x, linear_state, full_state):
    """One step of the window through every layer: x (B, d) -> (the core's
    output (B, d), the layers' states after the step)."""
    linear_state, full_state = list(linear_state), list(full_state)
    li = fi = 0
    for kind, blk in zip(kinds, blocks):
        if kind == "linear_attention":
            y, S, tail = layer_step[kind](blk, x, *linear_state[li])
            linear_state[li] = (S, tail)
            li += 1
        else:
            y, full_state[fi] = layer_step[kind](blk, x, full_state[fi])
            fi += 1
        h = x + rms_norm(y, blk["attn_norm"], eps)
        x = h + rms_norm(swiglu(h, blk["mlp"]), blk["ffn_norm"], eps)
    return x, linear_state, full_state


@functools.lru_cache(maxsize=None)
def compiled_step(core_dim: int, heads: int):
    """:func:`step` of the configuration at this width, as one program
    (compiled at the matmul precision the caller has set)."""
    hp = hyper_parameters(core_dim)
    return jax.jit(functools.partial(
        step, tuple(hp["layer_types"]), hp["eps"], dict(
            linear_attention=functools.partial(
                linear_attention_step, hp, heads=heads),
            full_attention=functools.partial(
                full_attention_step, hp, heads=heads))))


def core(hp, p, x0, hidden, heads):
    """x0 (B, T, d) the core's inputs, hidden (B, rows, 128) -> (B, T,
    d).  A Python loop over the window's steps; one step is compiled as
    one program, once, because 85 steps of four layers of four heads are
    30,000 operations dispatched one by one otherwise."""
    linear_state, full_state = split_state(hp, hidden, heads)
    per = hp["layer_types"].index("full_attention")     # linear a period
    blocks, li, fi = [], 0, 0
    for kind in hp["layer_types"]:          # each layer's own parameters
        if kind == "linear_attention":
            at, stack = (li // per, li % per), p["periods"]["linear"]
            li += 1
        else:
            at, stack = (fi,), p["periods"]["full"]
            fi += 1
        blocks.append(jax.tree.map(
            lambda v, at=at: v[at].astype(jnp.float32), stack))
    one_step = compiled_step(x0.shape[-1], heads)
    final_norm = p["final_norm"].astype(jnp.float32)
    out = []
    for t in range(x0.shape[1]):
        x, linear_state, full_state = one_step(
            blocks, x0[:, t], linear_state, full_state)
        out.append(rms_norm(x, final_norm, hp["eps"]))
    return jnp.stack(out, axis=1)


def unroll(params, obs, last_action, last_reward, hidden, heads_held=None):
    """Q over every step of the window: obs (B, T, ...) uint8, hidden (B,
    rows, 128).  Returns (B, T, A)."""
    p = params["params"]
    pc = p["core"]
    hp = hyper_parameters(pc["in_proj_kernel"].shape[1])
    heads = heads_held or hp["core_heads_held"]
    B, T = obs.shape[:2]
    x = obs.reshape(B * T, *obs.shape[2:]).astype(jnp.float32) / 255.0
    feats = jnp.concatenate(
        [torso(p["torso"], x).reshape(B, T, -1),
         last_action.astype(jnp.float32),
         last_reward[..., None].astype(jnp.float32)], axis=-1)
    x0 = feats @ pc["in_proj_kernel"].astype(jnp.float32) + pc["in_proj_bias"]
    outs = core(hp, pc, x0, jnp.asarray(hidden).astype(jnp.float32), heads)
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
