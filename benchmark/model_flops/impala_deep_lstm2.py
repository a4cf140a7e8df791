"""``impala_deep_lstm2``: IMPALA large torso, two LSTM-512 layers, dueling
heads — 56,897,280 multiply-adds a frame at four actions."""
from benchmark.model_flops.r2d2_common import step_macs  # noqa: F401
