"""Reference of ``impala_deep_lstm2``: the IMPALA "large" residual torso
(Espeholt et al. 2018, Figure 3: per section a 3x3 convolution, a 3x3
max-pool of stride 2, two residual blocks of relu-conv-relu-conv; then
relu, dense 512, relu), two LSTM-512 layers and dueling heads."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import r2d2_common as common

SECTIONS, BLOCKS = 3, 2


def max_pool_3x3_s2(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1), "SAME")


def torso(p, x):
    i = 0
    for _ in range(SECTIONS):
        x = max_pool_3x3_s2(common.conv(x, p[f"Conv_{i}"], 1, "SAME"))
        i += 1
        for _ in range(BLOCKS):
            y = common.conv(jax.nn.relu(x), p[f"Conv_{i}"], 1, "SAME")
            x = x + common.conv(jax.nn.relu(y), p[f"Conv_{i + 1}"], 1, "SAME")
            i += 2
    x = jax.nn.relu(x)
    return jax.nn.relu(common.dense(x.reshape(x.shape[0], -1), p["Dense_0"]))


def loss(params, target_params, batch, n: int):
    return common.loss(torso, params, target_params, batch, n)
