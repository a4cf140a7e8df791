"""The flagship cell's super-step, and the ``xing4`` core's expert layer,
compiled for a described (not attached) v5e: what only the TPU compiler
decides about the device ring and about the routed rows' buffers, checked
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


# ------------------------------------------- the routed experts' row ladder

@pytest.fixture(scope="module")
def expert_layer(one_chip):
    """(cfg, routed pairs, optimized HLO text) of one expert block of
    ``nature_xing4_l5e8h4`` alone, forward and backward, at the cell's
    widths and tokens (models/xing4.routed_experts; ~20 s)."""
    from benchmark.drivers.train import build_config
    from benchmark.manifest import Manifest
    from r2d2_tpu.models import xing4

    _, sharding = one_chip
    cfg = build_config(Manifest().cell("nature_xing4_l5e8h4.anakin"), False)
    tokens = cfg.batch_size * cfg.seq_len

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    p = jax.tree.map(
        lambda x: sds(x.shape[1:]),
        jax.eval_shape(lambda k: xing4.init_blocks(
            k, cfg, 1, False, jnp.float32)["moe"], jax.random.PRNGKey(0)))

    def forward_backward(p, u, bias, g):
        def f(p, u):
            out, load = xing4.routed_experts(cfg, p, u, bias, jnp.bfloat16)
            return jnp.sum(out * g), load
        return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(p, u)

    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(forward_backward).lower(
            p, sds((tokens, cfg.core_dim)), sds((cfg.core_experts,)),
            sds((tokens, cfg.core_dim))).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    return cfg, tokens * cfg.core_top_k, compiled.as_text()


def _computation(text, name):
    """The body of the HLO computation ``name``."""
    m = re.search(rf"^%?{re.escape(name)} \(.*?^}}", text, re.M | re.S)
    assert m, name
    return m.group(0)


def test_the_smallest_rung_lays_out_no_worst_case_rows(expert_layer):
    """21,760 routed pairs of which this chip's experts take an eighth: in
    the branch of the smallest rung, forward and backward, nothing has
    N k rows of the core's width (each is 156 MB; PERF.md Findings, PR 29),
    and the grouped products are still the compiler's Mosaic calls, which
    skip the tiles past the live rows."""
    from r2d2_tpu.models import xing4

    cfg, pairs, text = expert_layer
    ladder = xing4.row_ladder(cfg, pairs)
    assert len(ladder) == 4 and ladder[-1] == pairs == 21760
    switches = re.findall(r"conditional\(.*branch_computations=\{([^}]*)\}",
                          text)
    assert len(switches) == 2                   # forward, backward
    worst = rf"\[{pairs},{cfg.core_dim}\]"
    for names in switches:
        branches = [_computation(text, n.strip().lstrip("%"))
                    for n in names.split(",")]
        assert len(branches) == len(ladder)
        # the last rung is every pair: there the worst case is laid out
        assert re.search(worst, branches[-1])
        for rows, branch in zip(ladder[:-1], branches):
            assert not re.search(rf"\[{pairs},\d+\]", branch), rows
            products = re.findall(
                rf"= bf16\[(?:{rows},\d+|\d+,\d+,\d+)\]\S* custom-call\("
                r".*custom_call_target=\"tpu_custom_call\".*ragged-dot",
                branch)
            # forward 3; backward those 3 again, 3 dX, 3 dW
            assert len(products) in (3, 9), (rows, len(products))
            # a rung is a whole number of the kernel's row tiles
            tiles = set(re.findall(r'ragged_dot_tiling="(\d+),', branch))
            assert tiles and all(int(t) % xing4.ROW_TILE == 0
                                 and rows % int(t) == 0 for t in tiles)
