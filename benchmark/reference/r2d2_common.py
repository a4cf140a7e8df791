"""What the configurations' references share: the LSTM layer, the dueling
head and the R2D2 loss, in plain float32 ``jax.numpy``.

Written from the papers (Hochreiter & Schmidhuber's LSTM with gates in the
order i, f, g, o; Wang et al.'s dueling head; Kapturowski et al.'s n-step
double-Q target under the value rescaling h), independent of
``r2d2_tpu/models`` and ``r2d2_tpu/learner``: a Python loop over time, no
scan, no kernels, no mixed precision.  The only thing taken from the
program is the layout of its parameter tree, which the references read by
name.  Callers wrap these in ``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-3      # the value rescaling's epsilon (R2D2, section 2.3)


def conv(x, p, stride: int, padding: str):
    """NHWC convolution with an HWIO kernel, plus bias."""
    y = jax.lax.conv_general_dilated(
        x, p["kernel"].astype(jnp.float32), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["bias"]


def dense(x, p):
    return x @ p["kernel"].astype(jnp.float32) + p["bias"]


def lstm_layer(p, xs, h, c):
    """xs (B, T, F) -> (B, T, H); one cell step per loop iteration."""
    wi, wh, b = (p[k].astype(jnp.float32) for k in ("wi", "wh", "b"))
    out = []
    for t in range(xs.shape[1]):
        gates = xs[:, t] @ wi + h @ wh + b
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        out.append(h)
    return jnp.stack(out, axis=1), h, c


def dueling_head(p, x):
    adv = dense(jax.nn.relu(dense(x, p["adv_hidden"])), p["adv_out"])
    val = dense(jax.nn.relu(dense(x, p["val_hidden"])), p["val_out"])
    return val + adv - adv.mean(axis=-1, keepdims=True)


def unroll(torso, params, obs, last_action, last_reward, hidden):
    """Q over every step of the window: obs (B, T, ...) uint8, hidden
    (B, 2, layers, H) with axis 1 = (h, c).  Returns (B, T, A)."""
    p = params["params"]
    B, T = obs.shape[:2]
    x = obs.reshape(B * T, *obs.shape[2:]).astype(jnp.float32) / 255.0
    xs = jnp.concatenate(
        [torso(p["torso"], x).reshape(B, T, -1),
         last_action.astype(jnp.float32),
         last_reward[..., None].astype(jnp.float32)], axis=-1)
    layer = 0
    while f"lstm_{layer}" in p:
        xs, _, _ = lstm_layer(p[f"lstm_{layer}"], xs,
                              hidden[:, 0, layer], hidden[:, 1, layer])
        layer += 1
    return dueling_head(p["head"], xs.reshape(B * T, -1)).reshape(B, T, -1)


def h(x):
    return jnp.sign(x) * (jnp.sqrt(jnp.abs(x) + 1.0) - 1.0) + EPS * x


def h_inv(x):
    t = (jnp.sqrt(1.0 + 4.0 * EPS * (jnp.abs(x) + 1.0 + EPS)) - 1.0) / (
        2.0 * EPS)
    return jnp.sign(x) * (t * t - 1.0)


def loss(torso, params, target_params, batch, n: int):
    """Importance-weighted mean squared n-step double-Q TD error over the
    valid learning steps.  A window is [burn_in | learning | forward];
    learning step i sits at burn_in + i and bootstraps from step
    burn_in + i + n, or from the window's last step when the episode ended
    inside the forward steps.  Returns (loss, q over the learning steps)."""
    args = (batch["obs"], batch["last_action"], batch["last_reward"],
            batch["hidden"])
    q = unroll(torso, params, *args)
    q_target = unroll(torso, target_params, *args)
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
            y = h(batch["n_step_reward"][b, i] + batch["n_step_gamma"][b, i]
                  * h_inv(q_target[b, t_boot, a_star]))
            td = y - q[b, burn + i, batch["action"][b, i]]
            total = total + batch["is_weights"][b] * td * td
            valid += 1.0
    return total / valid, jnp.stack(q_learn)
