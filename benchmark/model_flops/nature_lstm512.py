"""``nature_lstm512``: Nature torso on space-to-depth frames, one LSTM-512,
dueling heads — 9,519,616 multiply-adds a frame at four actions."""
from benchmark.model_flops.r2d2_common import step_macs  # noqa: F401
