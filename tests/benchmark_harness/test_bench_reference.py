"""The plain float32 references against the program, at a small size on
the CPU (the chip run compares at the published widths)."""
import pytest

from benchmark.drivers import train as training
from benchmark.manifest import Manifest

MANIFEST = Manifest()
CONFIGS = sorted(MANIFEST.configs)


def _cfg(name, **kw):
    """The configuration at the small size its own file gives: its preset,
    then the file's ``small`` overrides."""
    return training.preset_config(MANIFEST.config(name), small=True, **kw)


def _tolerance(name):
    return MANIFEST.config(name)["tolerance"]


@pytest.mark.parametrize("name", CONFIGS)
def test_program_matches_the_reference_in_float32(name):
    from benchmark import check

    out = check.compare(name, _cfg(name, compute_dtype="float32"),
                        dict(q_rel=1e-5, q_rms_rel=1e-5, loss_rel=1e-5), 4, seed=3)
    assert out["problems"] == [], out


@pytest.mark.parametrize("name", CONFIGS)
def test_the_batch_uses_the_mask_and_the_clamped_bootstrap(name):
    import jax

    from benchmark import check
    from r2d2_tpu.models.network import zero_hidden

    cfg = _cfg(name)
    batch = check.seeded_batch(cfg, 4, 3)
    assert batch["obs"].shape == (4, cfg.seq_len, *cfg.stored_obs_shape)
    assert int(batch["learning"][-1]) == cfg.learning_steps - 3
    assert int(batch["forward"][-1]) < cfg.forward_steps
    # the recurrent state is the program's own, in shape and tree
    want = zero_hidden(cfg, check.SEQUENCES)
    assert jax.tree.structure(batch["hidden"]) == jax.tree.structure(want)
    for got, zero in zip(jax.tree.leaves(batch["hidden"]),
                         jax.tree.leaves(want)):
        assert (got.shape, got.dtype) == (zero.shape, zero.dtype)
        assert 0.05 < float(abs(got).max()) < 1.0


@pytest.mark.parametrize("name", CONFIGS)
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


def test_a_number_without_a_limit_is_not_compared():
    from benchmark import check

    out = dict(q_rel=0.04, q_rms_rel=0.01, loss_rel=0.5)
    every = dict(q_rel=0.03, q_rms_rel=0.02, loss_rel=0.01)
    assert [p.split(" (")[0] for p in check.over_limit(out, every)] == [
        "the largest Q-value difference is 0.04",
        "the loss differs from the reference by 0.5"]
    fewer = dict(q_rel=0.05, q_rms_rel=0.02, why="...",
                 loss_rel_not_compared="readings")
    assert check.over_limit(out, fewer) == []
    assert check.over_limit(dict(out, q_rms_rel=float("nan")), fewer) == [
        "the rms Q-value difference is nan (limit 0.02)"]


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
    cfg = _cfg(CONFIGS[0])
    a, b = (check.seeded_batch(cfg, 4, 3)["obs"] for _ in range(2))
    assert (a == b).all()
    assert not (a == check.seeded_batch(cfg, 4, 3 + check.DRAW_STRIDE)["obs"]
                ).all()
