"""One frame's multiply-adds, a file to a configuration, found by its name:
``<config>.py`` exports ``step_macs(cfg, action_dim)``."""
