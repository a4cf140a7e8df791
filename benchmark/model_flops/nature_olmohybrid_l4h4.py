"""``nature_olmohybrid_l4h4``: the Nature torso, the input projection, the
layers of the olmo_hybrid core as THIS chip multiplies them, and the dueling
heads.  A frame of the training window, forward: what the model needs
whatever form computes it, so the delta rule counts its one-step recurrence
(the chunked form's C x C products, its padding to whole chunks, the keys
outside a query's band, recomputation under remat and the fused loop's
acting forwards do not count)."""
from benchmark import flops

LAYER_TYPES_PERIOD = ("linear_attention", "linear_attention",
                      "linear_attention", "full_attention")
LINEAR_CONV_KERNEL_DIM = 4


def linear_attention_macs(cfg) -> int:
    """q, k, v, the output gate and the output projection at the heads
    held, the two gates' projections, the convolution's taps, and the
    recurrence: a head's S~^T k, k u^T and S'^T q are d_k x d_v each."""
    d, h = cfg.core_dim, cfg.core_heads_held
    dk, dv = cfg.core_linear_key_dim, cfg.core_linear_value_dim
    return (d * h * (2 * dk + 2 * dv) + h * dv * d + 2 * d * h
            + LINEAR_CONV_KERNEL_DIM * h * (2 * dk + dv)
            + 3 * h * dk * dv)


def full_attention_macs(cfg) -> int:
    """The four projections at the heads held, scores and values over the
    W + 1 keys a query sees."""
    d, width = cfg.core_dim, cfg.core_heads_held * cfg.core_head_dim
    return 4 * d * width + 2 * width * (cfg.core_context + 1)


def core_macs(cfg, action_dim: int) -> int:
    d = cfg.core_dim
    periods = cfg.core_layers // len(LAYER_TYPES_PERIOD)
    mixers = periods * sum(
        linear_attention_macs(cfg) if kind == "linear_attention"
        else full_attention_macs(cfg) for kind in LAYER_TYPES_PERIOD)
    return ((cfg.hidden_dim + action_dim + 1) * d + mixers
            + cfg.core_layers * 3 * d * cfg.core_dense_dim)


def head_macs(cfg, action_dim: int) -> int:
    H = cfg.hidden_dim
    return 2 * cfg.core_dim * H + H * action_dim + H


def step_macs(cfg, action_dim: int) -> int:
    """Multiply-adds of one frame through torso, core and heads."""
    return (flops.torso_macs(cfg) + core_macs(cfg, action_dim)
            + head_macs(cfg, action_dim))
