"""Operations the model needs, from its shapes, and the table of peaks.

``train_mfu`` is model FLOP/s over peak: the multiply-adds of the
convolutions and matrix products of torso, memory core and dueling heads,
forward and backward, at the configuration's shapes.  Recomputed
operations (remat), the ring copy, elementwise work and the fused loop's
acting forwards do not count — XLA's ``cost_analysis`` counts the first
two, which is why it is not the source.

The count of one frame's multiply-adds belongs to the configuration: it is
``model_flops/<config>.py`` exporting ``step_macs(cfg, action_dim)``, found
by the configuration's name.  A configuration without that file has no
``train_mfu`` and does not validate.  What the counts share (the torsos)
is kept once, here.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict

from benchmark.manifest import BENCH_DIR, find_module

_HERE = os.path.dirname(os.path.abspath(__file__))
NATURE = ((32, 8, 4), (64, 4, 2), (64, 3, 1))       # (channels, kernel, stride)
NATURE_S2D_CONV1 = (32, 2, 1)                       # the same map on 4x4 blocks
IMPALA_CHANNELS, IMPALA_BLOCKS = (16, 32, 32), 2


def peaks(device_kind: str) -> Dict[str, Any]:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    for prefix, row in table.items():
        if not prefix.startswith("_") and device_kind.startswith(prefix):
            return row
    raise KeyError(f"device_kind {device_kind!r} is not in peaks.json — add "
                   "its published peaks, with their source, before "
                   "benchmarking on it")


def torso_macs(cfg) -> int:
    """Multiply-adds of one frame through the torso."""
    h, w, c = cfg.stored_obs_shape
    macs = 0
    if cfg.torso == "nature":
        convs = ((NATURE_S2D_CONV1,) + NATURE[1:] if cfg.obs_space_to_depth
                 else NATURE)
        for ch, k, s in convs:                      # VALID padding
            h, w = (h - k) // s + 1, (w - k) // s + 1
            macs += h * w * ch * k * k * c
            c = ch
    elif cfg.torso == "impala":
        for ch in IMPALA_CHANNELS:                  # SAME padding
            macs += h * w * ch * 9 * c
            h, w, c = -(-h // 2), -(-w // 2), ch    # 3x3 max-pool, stride 2
            macs += 2 * IMPALA_BLOCKS * h * w * ch * 9 * ch
    elif cfg.torso == "mlp":
        return h * w * c * cfg.hidden_dim
    else:
        raise ValueError(f"no FLOP count for torso {cfg.torso!r}")
    return macs + h * w * c * cfg.hidden_dim        # the dense layer


def step_macs(config_name: str, cfg, action_dim: int,
              bench_dir: str = BENCH_DIR) -> int:
    """Multiply-adds of one frame through the whole network, as the
    configuration's own file counts them."""
    return find_module("model_flops", config_name, bench_dir).step_macs(
        cfg, action_dim)


def train_flops_per_update(config_name: str, cfg, action_dim: int,
                           bench_dir: str = BENCH_DIR) -> float:
    """Forward and backward of the online network plus the forward of the
    target network over B x T frames: (1 + 2 + 1) forwards' worth."""
    forward = (2.0 * step_macs(config_name, cfg, action_dim, bench_dir)
               * cfg.batch_size * cfg.seq_len)
    return 4.0 * forward


def train_mfu_percent(config_name: str, cfg, action_dim: int,
                      updates_per_s: float, chips: int, device_kind: str,
                      bench_dir: str = BENCH_DIR) -> float:
    peak = peaks(device_kind)["bf16_flops_per_s"]
    return (100.0
            * train_flops_per_update(config_name, cfg, action_dim, bench_dir)
            * updates_per_s / (chips * peak))
