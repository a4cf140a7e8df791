"""The flagship cell's super-step compiled for a described (not attached)
v5e: what only the TPU compiler decides about the device ring, checked
without a chip.

The compiler has twice chosen a layout for the frame ring under which the
super-step copies all of it on every dispatch (36 % of device time at a
multiple of 128 blocks; PERF.md Findings, PR 21 and PR 24), and which
layout it chooses follows from how the step reads the ring.  The
benchmark's ``ring_copy_device_share`` reads such a copy on the chip; this
is the same question asked of the compiled program's text, at no chip time.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0], SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def fabric_super_step(one_chip):
    """(cfg, optimized HLO text, memory analysis) of the fabric cell's
    in-graph-PER super-step at its real shapes."""
    from benchmark.drivers.train import ACTION_DIM, build_config
    from benchmark.manifest import Manifest
    from r2d2_tpu.learner.step import create_train_state
    from r2d2_tpu.models.network import create_network, init_params
    from r2d2_tpu.parallel.mesh import trivial_mesh
    from r2d2_tpu.parallel.sharding import (
        ShardingTable,
        pjit_in_graph_per_super_step,
    )
    from r2d2_tpu.replay.device_ring import _ring_shapes

    device, _ = one_chip
    cfg = build_config(Manifest().cell("nature_lstm512.fabric"), False)
    net = create_network(cfg, ACTION_DIM)
    state = jax.eval_shape(
        lambda k: create_train_state(cfg, init_params(cfg, net, k)),
        jax.random.PRNGKey(0))
    table = ShardingTable(trivial_mesh(device), cfg)
    fn = pjit_in_graph_per_super_step(cfg, net, table, cfg.superstep_k,
                                      state_template=state)

    def sds(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    NB, K = cfg.num_blocks, cfg.seqs_per_block
    ring_sh, per = table.ring_shardings("replicated"), table.per_shardings(
        "replicated")
    args = (
        jax.tree.map(lambda x, s: sds(x.shape, x.dtype, s), state,
                     table.state_shardings(state)),
        {k: sds((NB, *shape), dtype, ring_sh[k])
         for k, (shape, dtype) in _ring_shapes(cfg, ACTION_DIM).items()},
        sds((NB * K,), jnp.float32, per["prios"]),
        sds((NB, K, 3), jnp.int32, per["seq_meta"]),
        sds((NB,), jnp.int32, per["first"]),
        sds((), jnp.uint32, table.replicated()))
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = fn.lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    return cfg, compiled.as_text(), compiled.memory_analysis()


def test_fabric_super_step_never_copies_the_frame_ring(fabric_super_step):
    from benchmark.drivers.train import ACTION_DIM
    from r2d2_tpu.replay.device_ring import _ring_shapes

    cfg, text, memory = fabric_super_step
    (rows, words), _ = _ring_shapes(cfg, ACTION_DIM)["obs"]
    ring = rf"u32\[{cfg.num_blocks},{rows},{words}\]"
    assert re.search(ring, text), "the frame ring is not in the program"
    made = re.findall(rf"= {ring}\S* (\w[\w-]*)\(", text)
    # the ring enters as a parameter and is read in place: nothing in the
    # step may produce an array of its shape
    assert [op for op in made if op not in ("parameter",
                                            "get-tuple-element")] == []
    # the block-minor layouts came with 4.3-5.6 GB of temporaries
    assert memory.temp_size_in_bytes < 1 << 30


def test_fabric_super_step_reads_the_frames_as_windows(fabric_super_step):
    """The windows survive the compiler: the batch's frames are moved as
    ``batch_size`` slices of ``seq_len`` rows of words (a loop over the
    samples), and no operation yields the batch as ``B * T`` gathered
    rows of bytes."""
    from r2d2_tpu.replay.device_ring import frame_words

    cfg, text, _ = fabric_super_step
    B, T = cfg.batch_size, cfg.seq_len
    words = frame_words(int(np.prod(cfg.stored_obs_shape)))
    assert re.search(rf"u32\[{B},1,{T},{words}\]", text)
    assert not re.search(rf"u8\[{B * T},\d+\]\S* fusion\(", text)
