"""Reference of ``nature_lstm512``: the Nature-DQN torso (Mnih et al. 2015:
8x8/4 x 32, 4x4/2 x 64, 3x3/1 x 64, dense 512) on frames the pipeline has
folded space-to-depth by 4 — so conv1 is the same linear map as a 2x2/1
convolution over 16 channels — then LSTM-512 and dueling heads."""
from __future__ import annotations

import jax

from benchmark.reference import r2d2_common as common


def torso(p, x):
    x = jax.nn.relu(common.conv(x, p["Conv_0"], 1, "VALID"))
    x = jax.nn.relu(common.conv(x, p["Conv_1"], 2, "VALID"))
    x = jax.nn.relu(common.conv(x, p["Conv_2"], 1, "VALID"))
    return jax.nn.relu(common.dense(x.reshape(x.shape[0], -1), p["Dense_0"]))


def loss(params, target_params, batch, n: int):
    return common.loss(torso, params, target_params, batch, n)
