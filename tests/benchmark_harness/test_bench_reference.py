"""The plain float32 references against the program, at a small size on
the CPU (the chip run compares at the published widths)."""
import json
import os

import pytest

SMALL = dict(hidden_dim=32, batch_size=8, burn_in_steps=4, learning_steps=4,
             forward_steps=2, block_length=8, buffer_capacity=512)


def _cfg(name, **kw):
    from r2d2_tpu.config import impala_deep_config, pong_config

    make = dict(nature_lstm512=lambda **k: pong_config(game_name="Fake", **k),
                impala_deep_lstm2=lambda **k: impala_deep_config("Fake", **k))
    return make[name](**SMALL, **kw)


def _tolerance(name):
    from benchmark.manifest import ROOT

    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)["tolerance"]


@pytest.mark.parametrize("name", ["nature_lstm512", "impala_deep_lstm2"])
def test_program_matches_the_reference_in_float32(name):
    from benchmark import check

    out = check.compare(name, _cfg(name, compute_dtype="float32"),
                        dict(q_rel=1e-5, q_rms_rel=1e-5, loss_rel=1e-5), 4, seed=3)
    assert out["problems"] == [], out


def test_the_batch_uses_the_mask_and_the_clamped_bootstrap():
    from benchmark import check

    cfg = _cfg("nature_lstm512")
    batch = check.seeded_batch(cfg, 4, 3)
    assert batch["obs"].shape == (4, cfg.seq_len, 21, 21, 16)
    assert int(batch["learning"][-1]) == cfg.learning_steps - 3
    assert int(batch["forward"][-1]) < cfg.forward_steps


@pytest.mark.parametrize("name", ["nature_lstm512", "impala_deep_lstm2"])
def test_bfloat16_passes_the_files_tolerance_and_an_8_bit_path_fails(name):
    """The tolerance in the configuration file admits the bfloat16 the
    configuration states and refuses weights kept at 3 bits of mantissa (an
    8-bit float's): that is what makes it a check."""
    from benchmark import check

    tol = _tolerance(name)
    cfg = _cfg(name, compute_dtype="bfloat16")
    assert check.compare(name, cfg, tol, 4, seed=3)["problems"] == []
    err = check.errors(check.draws(name, cfg, 4, 3,
                                   weights=check.coarse_weights))
    assert err["q_rms_rel"] > tol["q_rms_rel"], err


def test_the_draws_are_pooled_and_seeded():
    """One far-off element in one draw moves the largest difference and
    hardly the pooled rms; the same seed gives the same draws."""
    import numpy as np

    from benchmark import check

    q = np.ones((4, 4, 4))
    pairs = [((1.0, q), (1.0, q)) for _ in range(check.DRAWS)]
    off = q.copy()
    off[0, 0, 0] = 1.5
    pairs[1] = ((1.1, off), (1.0, q))
    err = check.errors(pairs)
    assert err["q_rel"] == pytest.approx(0.5)
    assert err["q_rms_rel"] == pytest.approx(0.5 / np.sqrt(4 * 64))
    assert err["loss_rel"] == pytest.approx(0.1 / 4)
    cfg = _cfg("nature_lstm512")
    a, b = (check.seeded_batch(cfg, 4, 3)["obs"] for _ in range(2))
    assert (a == b).all()
    assert not (a == check.seeded_batch(cfg, 4, 3 + check.DRAW_STRIDE)["obs"]
                ).all()
