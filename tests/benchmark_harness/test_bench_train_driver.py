"""The training driver's stop-gaps: the names of ``r2d2_tpu.train`` it wraps
are the ones ``train()`` calls, a wrap that is never called fails the run,
and the ring's pre-fill is made of the program's own whole blocks."""
import dataclasses
import hashlib
import importlib
import inspect
import re

import numpy as np
import pytest

from benchmark import check, window
from benchmark.drivers import train as driver
from benchmark.manifest import Manifest

MANIFEST = Manifest()
WRAPPED = [("init_params", "_build"), ("init_params", "_train_anakin"),
           ("ReplayBuffer", "_build")]


@pytest.mark.parametrize("name,caller", WRAPPED)
def test_the_wrapped_name_is_a_global_that_train_calls(name, caller):
    """``train()`` reaches these through the module's globals, which is what
    the harness replaces; a rename or a local import would slip past it."""
    program = importlib.import_module("r2d2_tpu.train")
    assert callable(getattr(program, name))
    source = inspect.getsource(getattr(program, caller))
    assert re.search(rf"(?<![\w.]){name}\(", source), (name, caller)
    assert not re.search(rf"import[^\n]*\b{name}\b", source), (name, caller)
    assert re.search(rf"(?<![\w.]){caller}\(",
                     inspect.getsource(program.train))


def _facts(unused):
    from r2d2_tpu.config import test_config

    cfg = test_config(superstep_k=2)
    sink = window.DispatchSink(0, 10.0)
    for i in range(3):
        sink.complete(window.SYNC_SPAN, float(i), 0.5)
    return driver.TrainFacts(
        cfg=cfg, sink=sink, metrics=dict(mean_loss=0.1, num_updates=6),
        t_start_perf=0.0, compiles_in_window=[], unused_wraps=unused)


def test_the_driver_wraps_the_two_names_and_no_act_timer():
    """``act_span_us`` reads the program's own span of the act call (PR 25),
    so nothing of the acting path is wrapped."""
    assert '("init_params", "ReplayBuffer")' in inspect.getsource(
        driver.run_train)
    assert "make_act_fn" not in inspect.getsource(driver)
    assert not hasattr(driver, "ActTimer")
    assert "act_timer" not in {f.name for f in
                               dataclasses.fields(driver.TrainFacts)}


def test_a_wrap_that_train_never_called_fails_the_run():
    assert driver.run_facts_ok(_facts([]), {}) == []
    bad = driver.run_facts_ok(_facts(["ReplayBuffer"]), {})
    assert len(bad) == 1 and "r2d2_tpu.train.ReplayBuffer" in bad[0]


def test_dispatch_gaps_tell_a_stall_from_a_slower_run():
    sink = window.DispatchSink(0, 100.0)
    t = 0.0
    for i in range(41):
        t += 0.5 if i == 20 else 0.025      # one stall of 475 ms
        sink.complete(window.SYNC_SPAN, t, 0.01)
    got = driver.dispatch_gaps(sink)
    assert got["median_ms"] == pytest.approx(25.0)
    assert got["longest_ms"] == pytest.approx(500.0)
    assert got["over_twice_median"] == 1
    assert got["stalled_s"] == pytest.approx(0.475)
    assert driver.dispatch_gaps(window.DispatchSink(0, 1.0)) is None


def test_prefill_writes_whole_seeded_blocks_through_the_programs_writer():
    from r2d2_tpu.config import test_config
    from r2d2_tpu.replay.replay_buffer import ReplayBuffer

    cfg = test_config(obs_shape=(84, 84, 1), block_length=8,
                      buffer_capacity=8 * 16)
    blocks = driver.prefill_blocks(cfg, seed=5, count=3)
    for block, priorities in blocks:
        assert int(block.learning_steps.sum()) == cfg.block_length
        assert block.obs.shape == (cfg.burn_in_steps + cfg.block_length + 1,
                                   *cfg.stored_obs_shape)
        assert int(block.burn_in_steps[0]) == cfg.burn_in_steps
        assert (priorities[:block.num_sequences] > 0).all()
    again = driver.prefill_blocks(cfg, seed=5, count=3)
    assert all((a.obs == b.obs).all() and (pa == pb).all()
               for (a, pa), (b, pb) in zip(blocks, again))
    assert not (blocks[0][0].action == blocks[1][0].action).all()
    buffer = ReplayBuffer(cfg, driver.ACTION_DIM,
                          rng=np.random.default_rng(0))
    assert driver.prefill(buffer, cfg, 5, 1.0) == cfg.buffer_capacity
    assert buffer.ready and buffer.block_ptr == 0      # once round the ring


def test_ring_fill_is_read_from_the_programs_log_entries_in_the_window():
    facts = _facts([])
    facts.wall_minus_perf = 1000.0
    cap = facts.cfg.buffer_capacity
    facts.metrics["logs"] = [
        dict(time=1000.2, buffer_size=cap // 4),        # before the window
        dict(time=1000.9, buffer_size=cap // 2),
        dict(time=1002.1, buffer_size=cap)]
    assert facts.ring_fill() == dict(open=0.5, close=1.0)
    facts.metrics["logs"] = []
    assert facts.ring_fill() == dict(open=None, close=None)


# ---- the seeded inputs are, byte for byte, what they were before the
# recurrent state's shape came from the program (checksums taken on PR 26's
# tree): a configuration that was accepted keeps its reference readings

def _digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str((a.dtype.str, a.shape)).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def _batch_digest(cfg):
    batch = check.seeded_batch(cfg, driver.ACTION_DIM, 3)
    return _digest([batch[k] for k in sorted(batch)])


def _prefill_digest(cfg):
    out = []
    for block, priorities in driver.prefill_blocks(cfg, seed=5, count=3):
        d = dataclasses.asdict(block)
        out += [d[k] for k in sorted(d) if isinstance(d[k], np.ndarray)]
        out += [np.asarray(d["num_sequences"]), np.asarray(priorities)]
    return _digest(out)


PARENT_BYTES = {    # (configuration, size): (seeded_batch, prefill_blocks)
    ("nature_lstm512", "small"): ("85d6d631159a76eb", "1962230cdcec5d80"),
    ("nature_lstm512", "full"): ("122e08164a460a23", "9914c6397b2db07e"),
    ("impala_deep_lstm2", "small"): ("83578f83af12e883", "8c00c7379d8d3892"),
    ("impala_deep_lstm2", "full"): ("67fabcf14da4a815", "477ce02bef4ef452"),
}


@pytest.mark.parametrize("name,size", sorted(PARENT_BYTES))
def test_seeded_batch_and_prefill_are_the_bytes_they_were(name, size):
    assert name in MANIFEST.configs     # an accepted one is still there
    doc = MANIFEST.config(name)
    cfg = (driver.preset_config(doc, small=True) if size == "small"
           else driver.config_from_file(doc["config"]))
    assert (_batch_digest(cfg), _prefill_digest(cfg)) == PARENT_BYTES[
        (name, size)]


def test_the_state_is_drawn_in_the_programs_shape_and_tree(monkeypatch):
    """A memory core that keeps another state — here a pair of leaves —
    gets seeded draws of that shape, leaf after leaf, with no edit."""
    from r2d2_tpu.config import test_config
    from r2d2_tpu.models import network

    cfg = test_config()
    one = check.seeded_state(cfg, 3, np.random.default_rng(7))
    assert one.shape == network.zero_hidden(cfg, 3).shape
    assert one.dtype == np.float32

    def latent_cache(cfg, batch):
        import jax.numpy as jnp

        return dict(kv=jnp.zeros((batch, 6, 8), jnp.float32),
                    pos=jnp.zeros((batch, 2), jnp.float32))

    monkeypatch.setattr(network, "zero_hidden", latent_cache)
    state = check.seeded_state(cfg, 3, np.random.default_rng(7))
    assert {k: v.shape for k, v in state.items()} == {
        "kv": (3, 6, 8), "pos": (3, 2)}
    rng = np.random.default_rng(7)
    assert (state["kv"] == (0.1 * rng.normal(size=(3, 6, 8))).astype(
        np.float32)).all()
    assert (state["pos"] == (0.1 * rng.normal(size=(3, 2))).astype(
        np.float32)).all()
