"""Reference of ``nature_xing4_l5e8h4``: the Nature-DQN torso on space-to-depth
frames, then — in place of the LSTM — a stack of Xing4.0-29B-A4B's blocks
(``config.json`` keys in brackets), then dueling heads and R2D2's loss.
Plain float32 ``jax.numpy``: loops over blocks, heads and experts, every
key and value written out per head, every expert applied to every token and
masked.  Nothing of the program is imported; its parameter tree is read by
name, and the sizes no shape gives come from the configuration's own file
(``hyper_parameters``).

The equations, per step with features f (torso's 512 + one-hot last action +
last reward):

- x0 = f W_in + b (stands where the embedding stands), copied into n
  [hc_mult] streams X (n, d).  Each sublayer F (attention, then
  feed-forward) is wrapped alike: x~ = RMSNorm(vec X) without a gain;
  H_pre = sigmoid(a_pre x~ phi_pre + b_pre), H_post = 2 sigmoid(a_post x~
  phi_post + b_post), H_res = Sinkhorn(exp(clip(a_res mat(x~ phi_res) +
  b_res, +-clamp))) with [hc_sinkhorn_iters] rounds of row then column
  normalisation and [hc_eps] in the divisors; X <- H_res X + H_post^T
  F(RMSNorm(H_pre X)).  After the last block the streams are summed and
  RMS-normed.
- Latent attention: c_q = RMSNorm(u W_qa) [q_lora_rank]; per head [q_nope
  (qk_nope_head_dim), q_rope (qk_rope_head_dim)] = c_q W_qb; [c_kv
  (kv_lora_rank), k_r] = u W_kva, c_kv <- RMSNorm(c_kv); per head [k_nope,
  v (v_head_dim)] = c_kv W_kvb; k_r is shared by the heads.  Score = (q_nope
  . k_nope + RoPE(q_rope) . RoPE(k_r)) (nope + rope)^-1/2 m^2, m = 0.1
  mscale_all_dim ln(factor) + 1 [rope_scaling]; RoPE with YaRN's
  frequencies.  The sequence a query attends over is the W stored steps'
  (c_kv, k_r) followed by the window's own; a query sees the W steps before
  it and itself; positions are slots in that sequence.  Stored zeros are
  attended like any step.
- Routed experts [n_routed_experts, num_experts_per_tok, scoring_func
  sigmoid, topk_method noaux_tc, norm_topk_prob, routed_scaling_factor]: s =
  sigmoid(u W_r); the top k by s + bias; weights s over the chosen's sum,
  times the scale; output = shared expert(u) + sum over chosen AND held of
  w_i SwiGLU_i(u).  The first [first_k_dense_replace] blocks have a dense
  SwiGLU [intermediate_size] instead.

Departures from the source, each also in the configuration file:
- this chip's share: heads 0..h-1 of the attention and experts 0..e-1 of
  the routed experts (``heads_held``, ``experts_held``, arguments below);
  what the absent ones would add is left out;
- no vocabulary, no multi-token prediction;
- assumed where ``config.json`` is silent: streams replicated in and summed
  out; where the clamp and hc_eps sit; YaRN as DeepSeek-V3's modelling code
  reads the same keys; the zero cache at an episode's start.
"""
from __future__ import annotations

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import r2d2_common as common
from benchmark.reference.nature_lstm512 import torso

_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "nature_xing4_l5e8h4.json")


def hyper_parameters(core_dim: int) -> dict:
    """The sizes no parameter's shape gives, from the configuration's own
    file: its ``config`` where the width is the published one, with its
    ``small`` overrides on top where it is the tests' small size."""
    with open(_FILE) as f:
        doc = json.load(f)
    hp = dict(doc["config"])
    if core_dim != hp["core_dim"]:
        hp.update(doc["small"])
    # the constants no field of the program carries, under the source's keys
    yarn = doc["rope_scaling"]
    hp.update(core_rope_beta_fast=yarn["beta_fast"],
              core_rope_beta_slow=yarn["beta_slow"],
              core_rope_mscale_all_dim=yarn["mscale_all_dim"],
              core_norm_eps=doc["rms_norm_eps"], core_stream_eps=doc["hc_eps"],
              core_stream_clamp=doc["mhc_h_res_clamp_max"],
              core_route_scale=doc["routed_scaling_factor"])
    return hp


def rms_norm(x, gain, eps):
    y = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if gain is None else y * gain


def swiglu(x, p):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def yarn_frequencies(hp):
    dim, theta = hp["core_rope_dim"], hp["core_rope_theta"]
    plain = np.array([theta ** (-2.0 * i / dim) for i in range(dim // 2)])

    def dim_of(rotations):      # the dimension that turns so often
        return dim * math.log(hp["core_rope_original"]
                              / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim_of(hp["core_rope_beta_fast"])), 0)
    high = min(math.ceil(dim_of(hp["core_rope_beta_slow"])), dim - 1)
    span = (high - low) or 0.001
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / span, 0.0, 1.0)
    return plain * keep + plain / hp["core_rope_factor"] * (1.0 - keep)


def rope(x, positions, freqs):
    """x (..., S, dim) as dim/2 pairs (x[2i], x[2i+1]), pair i turned by
    the angle position x frequency i."""
    angle = positions[:, None] * freqs[None, :]
    cos, sin = np.cos(angle), np.sin(angle)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def attention(hp, p, u, stored, heads_held):
    """u (B, T, d) the sublayer's input, stored (B, W, latent)."""
    T, W = u.shape[1], stored.shape[1]
    r, dr = hp["core_kv_rank"], hp["core_rope_dim"]
    dn, dv = hp["core_nope_dim"], hp["core_v_dim"]
    eps = hp["core_norm_eps"]
    c_q = rms_norm(u @ p["w_qa"], p["q_norm"], eps)
    kv_a = u @ p["w_kva"]
    latent = jnp.concatenate(
        [stored, jnp.concatenate([rms_norm(kv_a[..., :r], p["kv_norm"], eps),
                                  kv_a[..., r:]], axis=-1)], axis=1)
    freqs = yarn_frequencies(hp)
    k_rot = rope(latent[..., r:], np.arange(W + T), freqs)  # (B, W + T, dr)
    m = 0.1 * hp["core_rope_mscale_all_dim"] * math.log(
        hp["core_rope_factor"]) + 1.0
    scale = (dn + dr) ** -0.5 * m * m
    # step t sits at slot W + t and sees slots t .. W + t
    t, slot = np.arange(T)[:, None], np.arange(W + T)[None, :]
    seen = (slot >= t) & (slot <= W + t)
    out = 0.0
    for head in range(heads_held):
        q = (c_q @ p["w_qb"])[..., head * (dn + dr):(head + 1) * (dn + dr)]
        kv = (latent[..., :r] @ p["w_kvb"])[..., head * (dn + dv):
                                            (head + 1) * (dn + dv)]
        query = jnp.concatenate(
            [q[..., :dn], rope(q[..., dn:], W + np.arange(T), freqs)],
            axis=-1)
        key = jnp.concatenate([kv[..., :dn], k_rot], axis=-1)
        scores = jnp.einsum("btd,bsd->bts", query, key) * scale
        w = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        out = out + jnp.einsum("bts,bsd->btd", w, kv[..., dn:]) @ p["w_o"][
            head * dv:(head + 1) * dv]
    return out, latent[:, T:]


def routing(hp, scores, bias):
    """Chosen experts (N, k) and their weights from scores (N, E)."""
    chosen = jnp.argsort(-(scores + bias), axis=1)[:, :hp["core_top_k"]]
    w = jnp.take_along_axis(scores, chosen, axis=1)
    return chosen, w / w.sum(axis=1, keepdims=True) * hp["core_route_scale"]


def experts(hp, p, u, bias, experts_held, first_expert=0):
    scores = jax.nn.sigmoid(u @ p["w_router"])
    chosen, w = routing(hp, scores, bias)
    out = swiglu(u, p["shared"])
    for i in range(experts_held):
        gate = jnp.where(chosen == first_expert + i, w, 0.0).sum(axis=1)
        one = {k: v[i] for k, v in p["experts"].items()}
        out = out + gate[:, None] * swiglu(u, one)
    return out


def sinkhorn(m, rounds, eps):
    for _ in range(rounds):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
    return m


def wrapped(hp, mix, gain, X, sublayer):
    """One sublayer in the residual streams X (B, T, n, d)."""
    B, T, n, d = X.shape
    flat = rms_norm(X.reshape(B, T, n * d), None, hp["core_norm_eps"])
    a = mix["alpha"]
    h_pre = jax.nn.sigmoid(a[0] * (flat @ mix["phi_pre"]) + mix["b_pre"])
    h_post = 2 * jax.nn.sigmoid(a[1] * (flat @ mix["phi_post"])
                                + mix["b_post"])
    logits = (a[2] * (flat @ mix["phi_res"]).reshape(B, T, n, n)
              + mix["b_res"])
    c = hp["core_stream_clamp"]
    h_res = sinkhorn(jnp.exp(jnp.clip(logits, -c, c)),
                     hp["core_sinkhorn_iters"], hp["core_stream_eps"])
    u = rms_norm(jnp.einsum("btn,btnd->btd", h_pre, X), gain,
                 hp["core_norm_eps"])
    y = sublayer(u)
    return (jnp.einsum("btij,btjd->btid", h_res, X)
            + h_post[..., None] * y[:, :, None, :])


def core(hp, p, bias, x0, stored, heads_held, experts_held):
    """x0 (B, T, d) the core's inputs, stored (B, layers, W, latent).
    Returns the core's output (B, T, d) and the latents to store."""
    B, T, d = x0.shape
    X = jnp.repeat(x0[:, :, None, :], hp["core_streams"], axis=2)
    kept, layer = [], 0
    for kind in ("dense_layers", "moe_layers"):
        if kind not in p:
            continue
        stack = p[kind]
        for i in range(stack["attn_norm"].shape[0]):
            blk = jax.tree.map(lambda v: v[i].astype(jnp.float32), stack)

            def attend(u, blk=blk, layer=layer):
                y, latent = attention(hp, blk["attn"], u, stored[:, layer],
                                      heads_held)
                kept.append(latent)
                return y

            def feed_forward(u, blk=blk, i=i, kind=kind):
                u = u.reshape(B * T, d)
                y = (swiglu(u, blk["dense"]) if kind == "dense_layers" else
                     experts(hp, blk["moe"], u, bias[i], experts_held))
                return y.reshape(B, T, d)

            X = wrapped(hp, blk["attn_mix"], blk["attn_norm"], X, attend)
            X = wrapped(hp, blk["ffn_mix"], blk["ffn_norm"], X, feed_forward)
            layer += 1
    out = rms_norm(X.sum(axis=2), p["final_norm"].astype(jnp.float32),
                   hp["core_norm_eps"])
    return out, jnp.stack(kept, axis=1)


def unroll(params, obs, last_action, last_reward, hidden,
           heads_held=None, experts_held=None):
    """Q over every step of the window: obs (B, T, ...) uint8, hidden (B,
    layers, W, latent).  Returns (B, T, A)."""
    p = params["params"]
    pc = p["core"]
    hp = hyper_parameters(pc["in_proj_kernel"].shape[1])
    heads_held = heads_held or hp["core_heads_held"]
    experts_held = experts_held or hp["core_experts_held"]
    bias = params["buffers"]["core"]["router_bias"]
    B, T = obs.shape[:2]
    x = obs.reshape(B * T, *obs.shape[2:]).astype(jnp.float32) / 255.0
    feats = jnp.concatenate(
        [torso(p["torso"], x).reshape(B, T, -1),
         last_action.astype(jnp.float32),
         last_reward[..., None].astype(jnp.float32)], axis=-1)
    x0 = feats @ pc["in_proj_kernel"].astype(jnp.float32) + pc["in_proj_bias"]
    outs, _ = core(hp, pc, bias, x0, hidden.astype(jnp.float32),
                   heads_held, experts_held)
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
