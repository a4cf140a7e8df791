"""The FLOP functions — the torsos' shared count, each configuration's own
count found by its name — and the table of peaks."""
import pytest

from benchmark import flops
from benchmark.drivers import train as training
from benchmark.manifest import Manifest, ManifestError

MANIFEST = Manifest()
# pinned to the digit: multiply-adds a frame and FLOPs an update of the
# configurations this benchmark was accepted with (a later one pins its own)
PINNED = {"nature_lstm512": (9_519_616, 414_293_688_320),
          "impala_deep_lstm2": (56_897_280, 3_495_768_883_200)}


def _file_config(name):
    return training.config_from_file(MANIFEST.config(name)["config"])


@pytest.mark.parametrize("name", sorted(MANIFEST.configs))
def test_every_configuration_has_its_own_count_found_by_name(name):
    cfg = _file_config(name)
    macs = flops.step_macs(name, cfg, training.ACTION_DIM)
    assert isinstance(macs, int) and macs > flops.torso_macs(cfg) > 0
    per_update = flops.train_flops_per_update(name, cfg, training.ACTION_DIM)
    assert per_update == 8.0 * macs * cfg.batch_size * cfg.seq_len
    if name in PINNED:
        assert (macs, per_update) == PINNED[name]


def test_a_configuration_without_a_count_is_an_error_not_a_default():
    with pytest.raises(ManifestError, match="model_flops/no_such_config.py"):
        flops.step_macs("no_such_config", _nature(), 4)
    with pytest.raises(ManifestError, match="model_flops/../flops.py"):
        flops.step_macs("../flops", _nature(), 4)    # the file is there


def _nature():
    from r2d2_tpu.config import pong_config

    return pong_config(game_name="Fake")


def test_nature_flops_against_a_hand_count():
    # one frame, multiply-adds, by hand from the shapes:
    conv1 = 20 * 20 * 32 * (2 * 2 * 16)        # s2d 21x21x16 -> 20x20x32
    conv2 = 9 * 9 * 64 * (4 * 4 * 32)          # stride 2 -> 9x9x64
    conv3 = 7 * 7 * 64 * (3 * 3 * 64)          # -> 7x7x64
    dense = 7 * 7 * 64 * 512
    lstm = (512 + 4 + 1 + 512) * 4 * 512       # wi and wh
    head = 2 * 512 * 512 + 512 * 4 + 512       # adv + val streams
    assert (conv1, conv2, conv3, dense) == (819200, 2654208, 1806336,
                                            1605632)
    cfg = _nature()
    assert flops.torso_macs(cfg) == conv1 + conv2 + conv3 + dense
    assert flops.step_macs("nature_lstm512", cfg, 4) == 9519616 == (
        conv1 + conv2 + conv3 + dense + lstm + head)
    # B=64 windows of T=85 frames; forward + backward (2x) + target forward
    assert flops.train_flops_per_update("nature_lstm512", cfg, 4) == (
        4 * 2 * 9519616 * 64 * 85) == 414_293_688_320


def test_impala_flops_follow_its_sections():
    from r2d2_tpu.config import impala_deep_config

    cfg = impala_deep_config("Fake")
    want = (84 * 84 * 16 * 9 * 1 + 4 * 42 * 42 * 16 * 9 * 16
            + 42 * 42 * 32 * 9 * 16 + 4 * 21 * 21 * 32 * 9 * 32
            + 21 * 21 * 32 * 9 * 32 + 4 * 11 * 11 * 32 * 9 * 32
            + 11 * 11 * 32 * 512)
    assert flops.torso_macs(cfg) == want == 52165888
    assert flops.step_macs("impala_deep_lstm2", cfg, 4) == want + (
        517 + 512) * 2048 + 1024 * 2048 + 526848 == 56_897_280
    assert flops.train_flops_per_update("impala_deep_lstm2", cfg, 4) == (
        3_495_768_883_200)


def test_mfu_uses_the_published_peak_and_every_chip():
    cfg = _nature()
    one = flops.train_mfu_percent("nature_lstm512", cfg, 4, 78.7, 1,
                                  "TPU v5 lite")
    assert one == pytest.approx(100 * 414.29368832e9 * 78.7 / 197e12)
    assert flops.train_mfu_percent("nature_lstm512", cfg, 4, 78.7, 4,
                                   "TPU v5 lite") == pytest.approx(one / 4)


def test_the_peak_is_the_published_one_with_a_source():
    row = flops.peaks("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in row["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite"])
def test_a_device_that_is_not_in_the_table_is_an_error(kind):
    with pytest.raises(KeyError, match="peaks.json"):
        flops.peaks(kind)
