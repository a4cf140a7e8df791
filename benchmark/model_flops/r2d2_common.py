"""The count the R2D2 configurations share: a torso (``flops.torso_macs``),
a stack of LSTM layers and the dueling heads."""
from benchmark import flops


def lstm_stack_macs(cfg, action_dim: int) -> int:
    """The input and recurrent products of every layer's four gates; the
    first layer reads the torso's features, the last action and reward."""
    H = cfg.hidden_dim
    macs, feat = 0, H + action_dim + 1
    for _ in range(cfg.lstm_layers):
        macs += (feat + H) * 4 * H
        feat = H
    return macs


def dueling_head_macs(cfg, action_dim: int) -> int:
    """Advantage and value streams: two hidden layers and their outputs."""
    H = cfg.hidden_dim
    return 2 * H * H + H * action_dim + H


def step_macs(cfg, action_dim: int) -> int:
    """Multiply-adds of one frame through torso, LSTM stack and heads."""
    return (flops.torso_macs(cfg) + lstm_stack_macs(cfg, action_dim)
            + dueling_head_macs(cfg, action_dim))
