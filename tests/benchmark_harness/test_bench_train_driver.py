"""The training driver's stop-gaps: the names of ``r2d2_tpu.train`` it wraps
are the ones ``train()`` calls, a wrap that is never called fails the run,
and the ring's pre-fill is made of the program's own whole blocks."""
import importlib
import inspect
import re

import numpy as np
import pytest

from benchmark import window
from benchmark.drivers import train as driver

WRAPPED = [("init_params", "_build"), ("init_params", "_train_anakin"),
           ("ReplayBuffer", "_build"), ("make_act_fn", "_build")]


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


def test_a_wrap_that_train_never_called_fails_the_run():
    assert driver.run_facts_ok(_facts([]), {}) == []
    bad = driver.run_facts_ok(_facts(["ReplayBuffer"]), {})
    assert len(bad) == 1 and "r2d2_tpu.train.ReplayBuffer" in bad[0]


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
