"""Device-resident PER (cfg.in_graph_per): sampling, IS weights, and
priority feedback inside the super-step.

Covers the redesign of the reference's host-side sum-tree feedback loop
(worker.py:242-276 update, worker.py:300-316 staging lag): the sampling
distribution and index arithmetic must match the host path exactly, the
in-graph scatter must only touch sampled leaves, and the full fabric must
run with zero host priority traffic.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.config import test_config as make_test_config
from r2d2_tpu.learner.step import _in_graph_sample, create_train_state
from r2d2_tpu.models.network import create_network, init_params
from r2d2_tpu.parallel.mesh import trivial_mesh
from r2d2_tpu.parallel.sharding import (
    ShardingTable, pjit_in_graph_per_super_step)
from r2d2_tpu.replay.block import LocalBuffer
from r2d2_tpu.replay.device_ring import DeviceRing
from r2d2_tpu.replay.replay_buffer import ReplayBuffer
from r2d2_tpu.envs.fake import FakeAtariEnv

A = 4


def make_cfg(**kw):
    return make_test_config(device_replay=True, in_graph_per=True, **kw)


def ig_step(cfg, net, k, state):
    """The unified device-PER super-step on a trivial 1-device mesh — the
    single-device oracle of the same (only) entry point."""
    return pjit_in_graph_per_super_step(
        cfg, net, ShardingTable(trivial_mesh(), cfg), k,
        state_template=state)


def scripted_blocks(cfg, n_blocks, seed=0):
    rng = np.random.default_rng(seed)
    local = LocalBuffer(cfg, A)
    out = []
    obs = rng.integers(0, 256, cfg.stored_obs_shape, np.uint8)
    local.reset(obs)
    while len(out) < n_blocks:
        for _ in range(cfg.block_length):
            obs = rng.integers(0, 256, cfg.stored_obs_shape, np.uint8)
            q = rng.normal(size=A).astype(np.float32)
            hidden = rng.normal(size=(2, cfg.lstm_layers,
                                      cfg.hidden_dim)).astype(np.float32)
            local.add(int(rng.integers(A)), float(rng.normal()), obs, q,
                      hidden)
        blk, prios, _ = local.finish(rng.normal(size=A).astype(np.float32))
        out.append((blk, prios))
    return out


def filled(cfg, n_blocks=4, seed=0):
    ring = DeviceRing(cfg, A)
    buf = ReplayBuffer(cfg, A, rng=np.random.default_rng(99),
                       device_ring=ring)
    for blk, prios in scripted_blocks(cfg, n_blocks, seed):
        buf.add(blk, prios, None)
    return buf, ring


def test_per_leaves_mirror_host_tree_values():
    """commit_per must store exactly what the host tree would: td**alpha
    at the block's real sequences, zero (unsampleable) past them."""
    cfg = make_cfg()
    K = cfg.seqs_per_block
    buf, ring = filled(cfg, n_blocks=3)
    host = ReplayBuffer(cfg.replace(in_graph_per=False, device_replay=False),
                        A, rng=np.random.default_rng(99))
    for blk, prios in scripted_blocks(cfg, 3):
        host.add(blk, prios, None)

    dev_p = np.asarray(ring.take_prios())
    leaves = host.tree.nodes[host.tree.leaf_offset:
                             host.tree.leaf_offset + cfg.num_blocks * K]
    np.testing.assert_allclose(dev_p, leaves[:dev_p.size], rtol=1e-6)
    # the host tree behind the in-graph buffer stays untouched
    assert buf.tree.nodes.sum() == 0.0


def test_in_graph_sample_matches_host_index_arithmetic():
    """Sampled ints bundles must reproduce sample_meta's arithmetic
    (replay_buffer.py:372-390) and IS weights the reference formula on
    exact densities; zero-priority leaves are never sampled."""
    cfg = make_cfg()
    K, L = cfg.seqs_per_block, cfg.learning_steps
    buf, ring = filled(cfg, n_blocks=3)

    prios = np.asarray(ring.take_prios())
    meta = {k: np.asarray(v) for k, v in ring.per_meta().items()}
    idx, w, ints = jax.jit(
        lambda key, p, sm, fb: _in_graph_sample(cfg, key, p, sm, fb),
    )(jax.random.PRNGKey(3), prios, meta["seq_meta"], meta["first"])
    idx, w, ints = map(np.asarray, (idx, w, ints))

    assert (prios[idx] > 0).all()
    block_idx, seq_idx = idx // K, idx % K
    burn = buf.burn_in_steps[block_idx, seq_idx]
    start = buf.first_burn_in[block_idx] + seq_idx * L
    expected = np.stack(
        [block_idx, start - burn, seq_idx, burn,
         buf.learning_steps[block_idx, seq_idx],
         buf.forward_steps[block_idx, seq_idx]], axis=1)
    np.testing.assert_array_equal(ints, expected)

    q = prios[idx] / prios.sum()
    np.testing.assert_allclose(
        w, (q / q.min()) ** (-cfg.importance_sampling_exponent),
        rtol=1e-5)


def test_partial_block_add_keeps_padding_unsampleable():
    """A short episode's partial block (num_sequences < K) must commit
    cleanly — priorities arrive K-length zero-padded (block.py:108) and
    the padding stays zero on device."""
    cfg = make_cfg()
    K = cfg.seqs_per_block
    ring = DeviceRing(cfg, A)
    buf = ReplayBuffer(cfg, A, rng=np.random.default_rng(1),
                       device_ring=ring)
    rng = np.random.default_rng(5)
    local = LocalBuffer(cfg, A)
    local.reset(rng.integers(0, 256, cfg.stored_obs_shape, np.uint8))
    for _ in range(max(1, cfg.block_length // 2 - 1)):
        local.add(int(rng.integers(A)), 0.5,
                  rng.integers(0, 256, cfg.stored_obs_shape, np.uint8),
                  rng.normal(size=A).astype(np.float32),
                  rng.normal(size=(2, cfg.lstm_layers,
                                   cfg.hidden_dim)).astype(np.float32))
    blk, prios, _ = local.finish(None)  # episode end -> partial block
    assert blk.num_sequences < K
    buf.add(blk, prios, 1.0)
    dev_p = np.asarray(ring.take_prios())
    assert (dev_p[blk.num_sequences:K] == 0).all()
    assert (dev_p[:blk.num_sequences] > 0).any()


def test_in_graph_sampling_distribution_is_proportional():
    """Empirical draw frequencies track priorities (the sum-tree's
    proportional contract) within sampling noise."""
    cfg = make_cfg()
    buf, ring = filled(cfg, n_blocks=3)
    prios = np.asarray(ring.take_prios())
    meta = ring.per_meta()
    pj = jnp.asarray(prios)
    f = jax.jit(lambda key: _in_graph_sample(cfg, key, pj,
                                             meta["seq_meta"],
                                             meta["first"])[0])
    counts = np.zeros(prios.size)
    draws = 400
    for s in range(draws):
        np.add.at(counts, np.asarray(f(jax.random.PRNGKey(s))), 1)
    expect = prios / prios.sum() * counts.sum()
    live = expect > 20  # only well-populated bins are statistically firm
    assert live.any()
    np.testing.assert_allclose(counts[live], expect[live], rtol=0.35)
    assert counts[prios == 0].sum() == 0


def test_in_graph_super_step_trains_and_scatters_feedback():
    cfg = make_cfg(superstep_k=2)
    buf, ring = filled(cfg, n_blocks=3)
    net = create_network(cfg, A)
    state = create_train_state(cfg, init_params(cfg, net,
                                                jax.random.PRNGKey(0)))
    p0 = np.asarray(ring.take_prios())
    meta = ring.per_meta()
    step0 = int(state.step)
    fn = ig_step(cfg, net, 2, state)
    state2, new_prios, losses = fn(state, ring.snapshot(),
                                   ring.take_prios(), meta["seq_meta"],
                                   meta["first"], jnp.asarray(7, jnp.uint32))
    losses = np.asarray(losses)
    assert losses.shape == (2,) and np.isfinite(losses).all()
    assert int(state2.step) == step0 + 2
    p1 = np.asarray(new_prios)
    changed = np.nonzero(p1 != p0)[0]
    assert changed.size > 0, "no priority feedback scattered"
    assert (p0[changed] > 0).all(), "scatter touched an invalid leaf"
    assert (p1[changed] >= 0).all()
    # padding/empty leaves stay unsampleable
    assert (p1[p0 == 0] == 0).all()


@pytest.mark.slow
def test_in_graph_scatter_writes_host_equivalent_priorities():
    """The in-scan priority scatter must write exactly what the host
    feedback path would: td**alpha of the mixed-TD priorities the train
    step computes for the same sampled batch.  Cross-checked by
    replaying the (deterministic) stratified draw on the host and
    running the plain train step on the identically gathered batch."""
    from r2d2_tpu.parallel.sharding import pjit_train_step
    from r2d2_tpu.replay.device_ring import gather_batch

    cfg = make_cfg(superstep_k=1)
    buf, ring = filled(cfg, n_blocks=3)
    net = create_network(cfg, A)
    state = create_train_state(cfg, init_params(cfg, net,
                                                jax.random.PRNGKey(0)))
    meta = ring.per_meta()
    p0 = jnp.asarray(np.asarray(ring.take_prios()))
    dispatch_idx = jnp.asarray(3, jnp.uint32)

    # replay the super-step's exact key schedule for k=1, step 0
    key0 = jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(cfg.seed), dispatch_idx),
        1)[0]
    idx, w, ints = map(np.asarray, _in_graph_sample(
        cfg, key0, p0, meta["seq_meta"], meta["first"]))

    # plain train step on the identically gathered batch
    batch = gather_batch(cfg, ring.snapshot(), jnp.asarray(ints),
                         jnp.asarray(w))
    _, _, prios_ref = pjit_train_step(cfg, net, state_template=state)(
        state, batch)

    # the in-graph super-step (fresh state: the first one was donated;
    # snapshot p0 to host BEFORE the call donates it)
    p0_np = np.asarray(p0).copy()
    state2 = create_train_state(cfg, init_params(cfg, net,
                                                 jax.random.PRNGKey(0)))
    fn = ig_step(cfg, net, 1, state2)
    _, new_prios, _ = fn(state2, ring.snapshot(), p0, meta["seq_meta"],
                         meta["first"], dispatch_idx)

    expected = p0_np
    expected[idx] = np.asarray(prios_ref) ** cfg.prio_exponent
    np.testing.assert_allclose(np.asarray(new_prios), expected,
                               rtol=1e-5, atol=1e-7)


@pytest.mark.slow
def test_in_graph_per_sharded_matches_single_device():
    """dp=8 mesh device-PER super-step == single-device: same losses,
    same scattered priorities, same params (sampling is deterministic
    given the fold_in key, so the mesh run draws identical strata)."""
    from r2d2_tpu.parallel.mesh import make_mesh

    cfg = make_cfg(superstep_k=2)
    buf, ring = filled(cfg, n_blocks=3)
    net = create_network(cfg, A)
    params = init_params(cfg, net, jax.random.PRNGKey(0))
    meta = ring.per_meta()
    p_start = np.asarray(ring.take_prios())
    idx7 = jnp.asarray(7, jnp.uint32)

    s0 = create_train_state(cfg, params)
    s1, p1, l1 = ig_step(cfg, net, 2, s0)(
        s0, ring.snapshot(),
        jnp.asarray(p_start), meta["seq_meta"], meta["first"], idx7)

    table = ShardingTable(make_mesh(cfg), cfg)
    sN0 = create_train_state(cfg, params)
    stepN = pjit_in_graph_per_super_step(cfg, net, table, 2,
                                         state_template=sN0)
    sN, pN, lN = stepN(
        table.place_state(sN0),
        ring.snapshot(), jnp.asarray(p_start), meta["seq_meta"],
        meta["first"], idx7)

    np.testing.assert_allclose(np.asarray(l1), np.asarray(lN), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(pN),
                               rtol=1e-4, atol=1e-7)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(sN.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


import pytest


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.slow
def test_train_end_to_end_in_graph_per(fused):
    """Full threaded fabric with device PER, at both loss paths (the
    default two-unroll and the fused double unroll — orthogonal
    features: sampling plane vs loss path): updates advance, losses are
    finite, and the log plane's counters stay live through note_updates
    (priority feedback never crosses the host)."""
    from r2d2_tpu.train import train

    cfg = make_cfg(game_name="Fake", superstep_k=2, training_steps=8,
                   fused_double_unroll=fused, log_interval=0.2)
    metrics = train(
        cfg,
        env_factory=lambda c, seed: FakeAtariEnv(
            obs_shape=c.stored_obs_shape, action_dim=A, seed=seed),
        verbose=False)
    assert metrics["num_updates"] >= cfg.training_steps
    assert np.isfinite(metrics["mean_loss"])
    assert metrics["buffer_training_steps"] == metrics["num_updates"]
    assert not metrics["fabric_failed"]


def test_compensated_cumsum_matches_f64():
    """_compensated_cumsum's f32 prefixes must agree with a float64
    oracle at stratum-boundary resolution across flagship-scale leaf
    arrays — the host SumTree accumulates in f64 (replay/sum_tree.py),
    and a plain f32 cumsum drifts enough to shift boundaries."""
    from r2d2_tpu.learner.step import _compensated_cumsum

    fn = jax.jit(_compensated_cumsum)
    diffs = plain_diffs = 0
    for seed in range(8):
        rng = np.random.default_rng(seed)
        x = (rng.random(50_000) * rng.exponential(1, 50_000)).astype(
            np.float32)
        x[rng.random(50_000) < 0.3] = 0.0   # padding slots
        ref = np.cumsum(x.astype(np.float64))
        hi = np.asarray(fn(jnp.asarray(x)))
        u = rng.random(64)
        t64 = (np.arange(64) + u) * (ref[-1] / 64)
        t32 = ((np.arange(64, dtype=np.float32) + u.astype(np.float32))
               * (hi[-1].astype(np.float32) / np.float32(64)))
        diffs += int(np.sum(np.searchsorted(ref, t64, side="right")
                            != np.searchsorted(hi, t32, side="right")))
        plain_diffs += int(np.sum(
            np.searchsorted(ref, t64, side="right")
            != np.searchsorted(np.cumsum(x), t32, side="right")))
    assert diffs == 0
    assert plain_diffs > 0  # the plain-f32 drift this guards against


def test_compensated_cumsum_adversarial_spread_per_slab():
    """The in-graph sampler's worst case (VERDICT r5 #7): the largest
    per-slab leaf count a v5e ring supports, under adversarial mixed
    priority spreads (1e-6 leaves sprinkled among 1e3 leaves, with
    padding zeros) — 0 stratum disagreements vs the f64 oracle.

    A plain f32 cumsum accumulates O(n·eps·total) drift here (~5
    absolute at these magnitudes), swallowing the tiny leaves' mass and
    shifting large-leaf boundaries; the compensated scan must hold every
    stratum boundary at oracle resolution."""
    from r2d2_tpu.config import pong_config
    from r2d2_tpu.learner.step import _compensated_cumsum
    from r2d2_tpu.replay.replay_buffer import data_bytes

    # leaf capacity of one v5e chip (16 GB HBM, 80% budget — the ring
    # guard's own threshold) at flagship Pong shapes: ~40k leaves/slab
    cfg = pong_config()
    per_block = data_bytes(cfg, 6) // cfg.num_blocks
    n_blocks = int(0.8 * 16e9) // per_block
    N = int(n_blocks * cfg.seqs_per_block)
    assert N >= 30_000  # sanity: flagship scale, not a toy

    fn = jax.jit(_compensated_cumsum)
    diffs = 0
    for seed in range(8):
        rng = np.random.default_rng(100 + seed)
        x = np.full(N, 1e-6, np.float32)      # near-converged TD errors
        x[rng.random(N) < 0.05] = 1e3         # fresh high-surprise blocks
        x[rng.random(N) < 0.3] = 0.0          # padding / empty slots
        ref = np.cumsum(x.astype(np.float64))
        hi = np.asarray(fn(jnp.asarray(x)))
        u = rng.random(64)                    # one stratum per batch row
        t64 = (np.arange(64) + u) * (ref[-1] / 64)
        t32 = ((np.arange(64, dtype=np.float32) + u.astype(np.float32))
               * (hi[-1].astype(np.float32) / np.float32(64)))
        diffs += int(np.sum(np.searchsorted(ref, t64, side="right")
                            != np.searchsorted(hi, t32, side="right")))
    assert diffs == 0


def dp_filled(cfg, n_blocks=8, seed=0):
    """A dp-layout ring + buffer with every slab populated."""
    from r2d2_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(cfg)
    ring = DeviceRing(cfg, A, table=ShardingTable(mesh, cfg), layout="dp")
    buf = ReplayBuffer(cfg, A, rng=np.random.default_rng(99),
                       device_ring=ring)
    for blk, prios in scripted_blocks(cfg, n_blocks, seed):
        buf.add(blk, prios, None)
    return mesh, buf, ring


def test_in_graph_sample_raw_matches_host_per_slab():
    """The grouped sampler's building block (_in_graph_sample_raw) on
    each dp slab: indices stay slab-local and positive-priority, the
    ints bundle reproduces the host arithmetic for the slab's physical
    slots, and densities are exactly prio/mass_slab — the host
    _grouped_densities contract (replay_buffer.py)."""
    from r2d2_tpu.learner.step import _in_graph_sample_raw

    cfg = make_cfg(mesh_shape=(("dp", 4), ("tp", 2)),
                   device_ring_layout="dp")
    K, L = cfg.seqs_per_block, cfg.learning_steps
    mesh, buf, ring = dp_filled(cfg)
    G, bpg = ring.num_groups, ring.blocks_per_group
    S, Bg = bpg * K, cfg.batch_size // G
    prios = np.asarray(ring.take_prios())
    meta = {k: np.asarray(v) for k, v in ring.per_meta().items()}
    assert buf.ready or buf.size < cfg.learning_starts

    fn = jax.jit(lambda key, p, sm, fb: _in_graph_sample_raw(
        cfg, key, p, sm, fb, Bg))
    for g in range(G):
        p_g = prios[g * S:(g + 1) * S]
        assert p_g.sum() > 0, "fixture must populate every slab"
        idx, q, ints = map(np.asarray, fn(
            jax.random.PRNGKey(g), p_g,
            meta["seq_meta"][g * bpg:(g + 1) * bpg],
            meta["first"][g * bpg:(g + 1) * bpg]))
        assert (idx >= 0).all() and (idx < S).all()
        assert (p_g[idx] > 0).all()
        blk_l, seq_idx = idx // K, idx % K
        blk_phys = g * bpg + blk_l          # physical slot in the ring
        burn = buf.burn_in_steps[blk_phys, seq_idx]
        start = buf.first_burn_in[blk_phys] + seq_idx * L
        expected = np.stack(
            [blk_l, start - burn, seq_idx, burn,
             buf.learning_steps[blk_phys, seq_idx],
             buf.forward_steps[blk_phys, seq_idx]], axis=1)
        np.testing.assert_array_equal(ints, expected)
        np.testing.assert_allclose(q, p_g[idx] / p_g.sum(), rtol=1e-5)


@pytest.mark.slow
def test_in_graph_per_dp_super_step_trains_and_guards_padding():
    """The dp-layout device-PER super-step (the SAME table-driven pjit
    step — PER leaves shard with the ring slabs, the stratified draw is
    global under GSPMD): finite losses, params advance, and the priority
    scatter can only touch positive leaves — zero (padding / empty-slot)
    leaves stay exactly zero, so padding never becomes sampleable."""
    cfg = make_cfg(superstep_k=2, mesh_shape=(("dp", 4), ("tp", 2)),
                   device_ring_layout="dp")
    mesh, buf, ring = dp_filled(cfg, n_blocks=6)  # some slots stay empty
    net = create_network(cfg, A)
    params = init_params(cfg, net, jax.random.PRNGKey(0))
    table = ShardingTable(mesh, cfg)
    state0 = create_train_state(cfg, params)
    state = table.place_state(state0)
    step = pjit_in_graph_per_super_step(
        cfg, net, table, 2, state_template=state0, layout="dp")

    p_before = np.asarray(ring.take_prios())
    params_before = [np.asarray(x) for x in jax.tree.leaves(state.params)]
    meta = ring.per_meta()
    st, p_after, losses = step(state, ring.snapshot(), ring.take_prios(),
                               meta["seq_meta"], meta["first"],
                               jnp.asarray(3, jnp.uint32))
    losses, p_after = np.asarray(losses), np.asarray(p_after)
    assert np.isfinite(losses).all() and losses.shape == (2,)
    assert (p_after[p_before == 0] == 0).all()
    assert (p_after != p_before).any(), "scatter must write feedback"
    changed = np.flatnonzero(p_after != p_before)
    assert (p_before[changed] > 0).all()
    # params actually moved
    moved = any(
        not np.allclose(a, np.asarray(b))
        for a, b in zip(params_before, jax.tree.leaves(st.params)))
    assert moved


def test_in_graph_per_dp_layout_matches_single_device():
    """The dp-sharded layout is a pure layout choice: over the SAME
    global ring content, the dp=4-sharded run of the (only) entry point
    and a single-device trivial-mesh run draw identical strata and agree
    on losses, scattered priorities, and params at reduction-order
    round-off.  (Block→slab ROUTING does depend on the dp size — rings
    filled under different dp hold the same blocks in permuted global
    slots — so the invariant is content-for-content, not
    fill-for-fill.)"""
    cfg = make_cfg(superstep_k=2, mesh_shape=(("dp", 4), ("tp", 2)),
                   device_ring_layout="dp")
    mesh, buf, ring = dp_filled(cfg, n_blocks=6)
    net = create_network(cfg, A)
    params = init_params(cfg, net, jax.random.PRNGKey(0))
    meta = ring.per_meta()
    p_start = np.asarray(ring.take_prios())
    snap_host = jax.device_get(ring.snapshot())
    seq_meta = np.asarray(meta["seq_meta"])
    first = np.asarray(meta["first"])
    idx5 = jnp.asarray(5, jnp.uint32)

    s0 = create_train_state(cfg, params)
    s1, p1, l1 = ig_step(cfg, net, 2, s0)(
        s0, snap_host, jnp.asarray(p_start), seq_meta, first, idx5)

    table = ShardingTable(mesh, cfg)
    sN0 = create_train_state(cfg, params)
    stepN = pjit_in_graph_per_super_step(
        cfg, net, table, 2, state_template=sN0, layout="dp")
    sN, pN, lN = stepN(
        table.place_state(sN0), ring.snapshot(), ring.take_prios(),
        meta["seq_meta"], meta["first"], idx5)

    np.testing.assert_allclose(np.asarray(l1), np.asarray(lN), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(pN),
                               rtol=1e-4, atol=1e-7)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(sN.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


@pytest.mark.slow
def test_train_end_to_end_in_graph_per_dp_layout():
    """Full threaded fabric: device PER over a dp-sharded ring on a
    dp=4 x tp=2 mesh — the capacity-scaling composition (pod-size
    replay + zero-host-round-trip priorities) the round-4 guard
    forbade."""
    from r2d2_tpu.train import train

    cfg = make_cfg(game_name="Fake", superstep_k=2, training_steps=8,
                   device_ring_layout="dp", log_interval=0.2,
                   mesh_shape=(("dp", 4), ("tp", 2)))
    metrics = train(
        cfg,
        env_factory=lambda c, seed: FakeAtariEnv(
            obs_shape=c.stored_obs_shape, action_dim=A, seed=seed),
        use_mesh=True, verbose=False)
    assert metrics["num_updates"] >= cfg.training_steps
    assert np.isfinite(metrics["mean_loss"])
    assert not metrics["fabric_failed"]


def test_in_graph_per_without_ring_fails_fast():
    """in_graph_per on the ring-less host fallback must fail at buffer
    construction with the remedy — not as an AttributeError in an actor
    thread at the first block commit."""
    cfg = make_cfg()
    with pytest.raises(ValueError, match="in_graph_per=False"):
        ReplayBuffer(cfg, A, rng=np.random.default_rng(0),
                     device_ring=None)


def test_train_sync_accepts_in_graph_preset():
    """train_sync force-disables device_replay; it must drop in_graph_per
    with it (the pair is validated together) so the deterministic
    debug trainer accepts the flagship presets unchanged."""
    from r2d2_tpu.train import train_sync

    cfg = make_cfg(game_name="Fake", training_steps=3)
    out = train_sync(cfg, env_factory=lambda c, seed: FakeAtariEnv(
        obs_shape=c.stored_obs_shape, action_dim=A, seed=seed))
    assert out["num_updates"] >= 3
    assert np.isfinite(out["mean_loss"])


@pytest.mark.slow
def test_train_end_to_end_in_graph_per_dp_fused():
    """The full composition stack at once: dp-sharded ring + device PER
    + fused double unroll on a dp=4 x tp=2 mesh — every r4/r5 throughput
    feature live in one fabric."""
    from r2d2_tpu.train import train

    cfg = make_cfg(game_name="Fake", superstep_k=2, training_steps=8,
                   device_ring_layout="dp", fused_double_unroll=True,
                   log_interval=0.2, mesh_shape=(("dp", 4), ("tp", 2)))
    metrics = train(
        cfg,
        env_factory=lambda c, seed: FakeAtariEnv(
            obs_shape=c.stored_obs_shape, action_dim=A, seed=seed),
        use_mesh=True, verbose=False)
    assert metrics["num_updates"] >= cfg.training_steps
    assert np.isfinite(metrics["mean_loss"])
    assert not metrics["fabric_failed"]
