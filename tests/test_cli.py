"""CLI: config building, overrides, and the train/eval round trip."""
import json
import os

import pytest

from r2d2_tpu.cli import _parse_override, build_config, main


class _Args:
    def __init__(self, **kw):
        self.preset = kw.pop("preset", "default")
        self.game = kw.pop("game", None)
        self.actors = kw.pop("actors", None)
        self.seed = kw.pop("seed", None)
        self.training_steps = kw.pop("training_steps", None)
        self.overrides = kw.pop("overrides", None)
        assert not kw


def test_parse_override_types():
    assert _parse_override("lr=0.001") == ("lr", 0.001)
    assert _parse_override("batch_size=32") == ("batch_size", 32)
    assert _parse_override("torso=impala") == ("torso", "impala")
    assert _parse_override("remat=true") == ("remat", True)
    assert _parse_override("mesh_shape=[[\"dp\", 4]]") == (
        "mesh_shape", (("dp", 4),))


def test_parse_override_rejects_unknown():
    import argparse

    with pytest.raises(argparse.ArgumentTypeError):
        _parse_override("not_a_field=3")
    with pytest.raises(argparse.ArgumentTypeError):
        _parse_override("no_equals_sign")


def test_build_config_presets_and_overrides():
    cfg = build_config(_Args(preset="pong", actors=4,
                             overrides=[("lr", 5e-5)]))
    assert cfg.game_name == "Pong" and cfg.num_actors == 4 and cfg.lr == 5e-5
    cfg = build_config(_Args(preset="atari57", game="Breakout"))
    assert cfg.game_name == "Breakout" and cfg.num_actors == 256
    assert cfg.actor_fleets == 4
    cfg = build_config(_Args(preset="impala_deep"))
    assert cfg.torso == "impala" and cfg.lstm_layers == 2
    # scaled-down --actors must clamp a preset's fleet default, not raise
    cfg = build_config(_Args(preset="hard_exploration", actors=2))
    assert cfg.num_actors == 2 and cfg.actor_fleets == 2
    # ... but an explicit override wins
    cfg = build_config(_Args(preset="hard_exploration", actors=8,
                             overrides=[("actor_fleets", 1)]))
    assert cfg.actor_fleets == 1


def test_cli_train_then_eval_round_trip(tmp_path, capsys):
    ckpt = str(tmp_path / "ckpt")
    main(["train", "--preset", "test", "--game", "Fake", "--sync",
          "--training-steps", "2", "--ckpt-dir", ckpt])
    out = capsys.readouterr().out.strip().splitlines()[-1]
    metrics = json.loads(out)
    assert metrics["num_updates"] == 2

    out_json = str(tmp_path / "curve.json")
    main(["eval", "--preset", "test", "--game", "Fake", "--ckpt-dir", ckpt,
          "--episodes", "2", "--out-json", out_json])
    curve = json.load(open(out_json))
    assert curve and {"step", "env_frames", "minutes", "mean_reward"} <= set(
        curve[-1])
    assert curve[-1]["step"] == 2


def test_cli_eval_env_uses_noop_start(tmp_path, monkeypatch):
    """Eval protocol parity with the reference (test.py:16): eval envs must
    randomize start states via noop starts, same as training envs."""
    ckpt = str(tmp_path / "ckpt")
    main(["train", "--preset", "test", "--game", "Fake", "--sync",
          "--training-steps", "1", "--ckpt-dir", ckpt])

    import r2d2_tpu.envs as envs_pkg

    seen = []
    real_create = envs_pkg.create_env

    def spy(cfg, noop_start=True, seed=None, **kw):
        seen.append(noop_start)
        return real_create(cfg, noop_start=noop_start, seed=seed, **kw)

    monkeypatch.setattr(envs_pkg, "create_env", spy)
    main(["eval", "--preset", "test", "--game", "Fake", "--ckpt-dir", ckpt,
          "--episodes", "1"])
    assert seen and all(seen), "eval env built without noop_start=True"



def test_cli_has_no_bench_subcommand(capsys):
    """Speed is measured by `benchmark/run.py` alone (PR 30): `r2d2_tpu
    bench` is an argparse error, so a second entry cannot come back
    unnoticed."""
    with pytest.raises(SystemExit) as e:
        main(["bench"])
    assert e.value.code == 2
    assert "invalid choice: 'bench'" in capsys.readouterr().err


def test_cli_train_exit_code_reports_a_failed_run(monkeypatch, capsys):
    """A failed run fails (ISSUE 21 B3): `r2d2_tpu train` exits non-zero
    when the fabric failed or the learner stalled; a clean run — and the
    anakin wedge drill's clean abort — keep rc 0."""
    import importlib
    import json

    train_mod = importlib.import_module("r2d2_tpu.train")
    argv = ["train", "--preset", "test", "--game", "Fake", "--quiet"]
    for flags, rc in ((dict(), 0),
                      (dict(dispatch_wedged=True), 0),
                      (dict(fabric_failed=True), 1),
                      (dict(learner_stalled=True), 1)):
        metrics = dict(dict(num_updates=3, fabric_failed=False,
                            learner_stalled=False), **flags)
        monkeypatch.setattr(train_mod, "train", lambda cfg, **kw: metrics)
        assert main(argv) == rc, flags
        assert json.loads(capsys.readouterr().out) == metrics
