from r2d2_tpu.models.network import (
    R2D2Network,
    NatureTorso,
    ImpalaTorso,
    MlpTorso,
    LSTMLayer,
    DuelingHead,
    create_network,
    init_params,
    state_spec,
    zero_state,
    zero_hidden,
)
