"""``nature_xing4_l5e8h4``: the Nature torso, the input projection, the
blocks of the xing4 core as THIS chip multiplies them, and the dueling
heads.  A frame of the training window, forward: what the algorithm needs,
so the keys outside a query's band, the rows the grouped product pads to
its tile, recomputation under remat and the fused loop's acting forwards do
not count."""
from benchmark import flops


def attention_macs(cfg) -> int:
    """The projections at the heads held, scores and values over the W + 1
    keys a query sees, and the expansion of keys and values from the
    latents: a window of T steps expands its own T and the W cached ones."""
    d, h, r = cfg.core_dim, cfg.core_heads_held, cfg.core_kv_rank
    dn, dr, dv = cfg.core_nope_dim, cfg.core_rope_dim, cfg.core_v_dim
    keys = cfg.core_context + 1
    T = cfg.seq_len
    return (d * cfg.core_q_rank + cfg.core_q_rank * h * (dn + dr)
            + d * (r + dr)
            + r * h * (dn + dv) * (cfg.core_context + T) // T
            + h * (dn + dr) * keys + h * dv * keys
            + h * dv * d)


def stream_mix_macs(cfg) -> int:
    """One sublayer's maps (4d x (n + n + n*n)), the read, the mix and the
    write of the streams."""
    n, d = cfg.core_streams, cfg.core_dim
    return n * d * (2 * n + n * n) + n * d + n * n * d + n * d


def routed_macs(cfg) -> int:
    """Router over every expert, the one shared expert (the source's
    n_shared_experts), and the routed experts held here: top_k x held /
    experts of them a token."""
    d, w = cfg.core_dim, cfg.core_expert_dim
    return (d * cfg.core_experts + 3 * d * w
            + cfg.core_top_k * cfg.core_experts_held * 3 * d * w
            // cfg.core_experts)


def core_macs(cfg, action_dim: int) -> int:
    d = cfg.core_dim
    dense, moe = cfg.core_dense_layers, cfg.core_layers - cfg.core_dense_layers
    per_block = attention_macs(cfg) + 2 * stream_mix_macs(cfg)
    return ((cfg.hidden_dim + action_dim + 1) * d
            + cfg.core_layers * per_block
            + dense * 3 * d * cfg.core_dense_dim
            + moe * routed_macs(cfg))


def head_macs(cfg, action_dim: int) -> int:
    H = cfg.hidden_dim
    return 2 * cfg.core_dim * H + H * action_dim + H


def step_macs(cfg, action_dim: int) -> int:
    """Multiply-adds of one frame through torso, core and heads."""
    return (flops.torso_macs(cfg) + core_macs(cfg, action_dim)
            + head_macs(cfg, action_dim))
