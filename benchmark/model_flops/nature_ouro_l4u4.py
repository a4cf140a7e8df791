"""``nature_ouro_l4u4``: the Nature torso, the input projection, the looped
layers of the ouro core as THIS chip multiplies them, and the dueling heads.
A frame of the training window, forward: every loop applies every layer
again, so a layer counts ``core_loops`` times; a query's scores and values
cover the W + 1 keys it sees (the keys outside its band, recomputation
under remat and the fused loop's acting forwards do not count)."""
from benchmark import flops


def layer_macs(cfg) -> int:
    """One application of one layer: q, k, v and the output projection,
    scores and values over W + 1 keys, the SwiGLU's three products."""
    d, width = cfg.core_dim, cfg.core_heads_held * cfg.core_head_dim
    return (4 * d * width + 2 * width * (cfg.core_context + 1)
            + 3 * d * cfg.core_dense_dim)


def core_macs(cfg, action_dim: int) -> int:
    return ((cfg.hidden_dim + action_dim + 1) * cfg.core_dim
            + cfg.core_loops * cfg.core_layers * layer_macs(cfg))


def head_macs(cfg, action_dim: int) -> int:
    H = cfg.hidden_dim
    return 2 * cfg.core_dim * H + H * action_dim + H


def step_macs(cfg, action_dim: int) -> int:
    """Multiply-adds of one frame through torso, core and heads."""
    return (flops.torso_macs(cfg) + core_macs(cfg, action_dim)
            + head_macs(cfg, action_dim))
