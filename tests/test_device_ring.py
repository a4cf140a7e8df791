"""Device-resident replay (replay/device_ring.py) + super-stepped learner.

The device data plane must be a semantic twin of the host path: same index
arithmetic, same batch contents, same training trajectory — only the
location of the bytes changes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.config import test_config as make_test_config
from r2d2_tpu.envs.fake import FakeAtariEnv
from r2d2_tpu.learner.step import create_train_state
from r2d2_tpu.models.network import create_network, init_params
from r2d2_tpu.parallel.mesh import trivial_mesh
from r2d2_tpu.parallel.sharding import (
    ShardingTable, pjit_super_step, pjit_train_step)
from r2d2_tpu.replay.device_ring import (
    DeviceRing,
    device_bytes,
    frame_words,
    gather_batch,
    pack_frames,
    unpack_frames,
    window_tail,
)
from r2d2_tpu.replay.replay_buffer import ReplayBuffer
from r2d2_tpu.replay.block import LocalBuffer

A = 4


def make_cfg(**kw):
    return make_test_config(**kw)


def single_super_step(cfg, net, k, state):
    """The unified super-step on a trivial 1-device mesh — the
    single-device oracle of the same (only) entry point."""
    return pjit_super_step(cfg, net, ShardingTable(trivial_mesh(), cfg), k,
                           state_template=state)


def scripted_blocks(cfg, n_blocks, seed=0):
    """Deterministic wellformed blocks via a LocalBuffer on scripted data."""
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


def paired_buffers(cfg, n_blocks=4, seed=0):
    """A host-path buffer and a device-ring buffer fed identical blocks,
    with identically-seeded samplers."""
    host = ReplayBuffer(cfg, A, rng=np.random.default_rng(99))
    ring = DeviceRing(cfg, A)
    dev = ReplayBuffer(cfg, A, rng=np.random.default_rng(99),
                       device_ring=ring)
    for blk, prios in scripted_blocks(cfg, n_blocks, seed):
        host.add(blk, prios, None)
        dev.add(blk, prios, None)
    return host, dev, ring


def test_device_bytes_matches_ring_allocation():
    """The capacity guard budgets exactly what the ring allocates —
    including the frame-row axis padded to 32 rows, the frames packed
    into words and the time fields' spare rows."""
    cfg = make_cfg()
    ring = DeviceRing(cfg, A)
    assert ring.nbytes() == device_bytes(cfg, A)
    rows, width = ring.arrays["obs"].shape[1:]
    assert rows % 32 == 0 and rows >= cfg.max_block_steps
    assert ring.arrays["obs"].dtype == jnp.uint32
    assert width == frame_words(int(np.prod(cfg.stored_obs_shape)))
    for k in ("last_action", "last_reward"):
        assert ring.arrays[k].shape[1] == (cfg.max_block_steps
                                           + window_tail(cfg))


def test_device_gather_matches_host_sample_batch():
    """Same tree seed → same sampled leaves; the in-graph gather must
    reproduce every field of the host-assembled batch exactly."""
    cfg = make_cfg()
    host, dev, ring = paired_buffers(cfg, n_blocks=4)

    host_batch = host.sample_batch(8)
    meta = dev.sample_meta(k=1, batch_size=8)
    np.testing.assert_array_equal(meta["idxes"][0], host_batch["idxes"])

    got = jax.jit(lambda arrs, ints, w: gather_batch(cfg, arrs, ints, w))(
        ring.snapshot(), jnp.asarray(meta["ints"][0]),
        jnp.asarray(meta["is_weights"][0]))
    for key in ("obs", "last_action", "last_reward", "hidden", "action",
                "n_step_reward", "n_step_gamma", "burn_in", "learning",
                "forward", "is_weights"):
        np.testing.assert_array_equal(
            np.asarray(got[key]), np.asarray(host_batch[key]),
            err_msg=f"field {key} diverged")


def test_device_gather_after_ring_overwrite():
    """After the ring wraps, gathers must see the new slot contents (and
    the host/device paths must still agree)."""
    cfg = make_cfg()
    n = cfg.num_blocks + 2  # wrap: overwrite slots 0 and 1
    host, dev, ring = paired_buffers(cfg, n_blocks=n)
    assert host.block_ptr == dev.block_ptr == 2

    host_batch = host.sample_batch(8)
    meta = dev.sample_meta(k=1, batch_size=8)
    np.testing.assert_array_equal(meta["idxes"][0], host_batch["idxes"])
    got = gather_batch(cfg, ring.snapshot(), jnp.asarray(meta["ints"][0]),
                       jnp.asarray(meta["is_weights"][0]))
    np.testing.assert_array_equal(np.asarray(got["obs"]), host_batch["obs"])
    np.testing.assert_array_equal(np.asarray(got["action"]),
                                  host_batch["action"])


# the two benchmark cells' window geometries at a small frame size: the
# flagship's 441 stored rows are not a multiple of the ring's 32-row
# padding (7 spare rows hold the 4-row overrun), the deep torso's 416 are
# (no spare row: the late window is moved back and repaired)
CELL_GEOMETRIES = dict(
    fabric=dict(burn_in_steps=40, learning_steps=40, forward_steps=5,
                block_length=400),
    impala=dict(burn_in_steps=40, learning_steps=75, forward_steps=5,
                block_length=375),
)


def ints_for(buf, idxes):
    """``sample_meta``'s index arithmetic for chosen leaves."""
    cfg = buf.cfg
    K, L = cfg.seqs_per_block, cfg.learning_steps
    block_idx, seq_idx = idxes // K, idxes % K
    burn = buf.burn_in_steps[block_idx, seq_idx].astype(np.int64)
    start = buf.first_burn_in[block_idx] + seq_idx * L
    return np.stack(
        [block_idx, start - burn, seq_idx, burn,
         buf.learning_steps[block_idx, seq_idx],
         buf.forward_steps[block_idx, seq_idx]], axis=1).astype(np.int32)


@pytest.mark.parametrize("mode", ["jit", "scan"])
@pytest.mark.parametrize("geometry", sorted(CELL_GEOMETRIES))
def test_windowed_gather_equals_host_where_windows_overrun(geometry, mode):
    """Every field of the device gather equals the host's ``sample_batch``
    arithmetic for windows that run past the block's last stored row —
    the last sequence of a short first block (no burn-in prefix) and of
    full blocks — jitted, and inside a ``lax.scan`` of k = 2 like the
    super-step's."""
    cfg = make_cfg(buffer_capacity=4 * CELL_GEOMETRIES[geometry][
        "block_length"], **CELL_GEOMETRIES[geometry])
    host, dev, ring = paired_buffers(cfg, n_blocks=3)
    K, T, MS = cfg.seqs_per_block, cfg.seq_len, cfg.max_block_steps
    spare = ring.arrays["obs"].shape[1] - MS
    assert (spare >= window_tail(cfg)) == (geometry == "fabric")
    assert host.first_burn_in[0] == 0 and (
        host.first_burn_in[1] == cfg.burn_in_steps)

    # first, middle and LAST sequence of each block, twice over for k = 2
    idxes = np.array([[b * K + q for b in range(3) for q in (0, K // 2,
                                                             K - 1)],
                      [b * K + q for b in (2, 0, 1) for q in (K - 1, 1,
                                                              K - 2)]])
    ints = np.stack([ints_for(dev, i) for i in idxes])
    assert (ints[..., 1] + T).max() == MS + window_tail(cfg) > MS
    w = np.random.default_rng(3).random(idxes.shape).astype(np.float32)

    if mode == "jit":
        fn = jax.jit(jax.vmap(
            lambda arrs, i, ww: gather_batch(cfg, arrs, i, ww),
            in_axes=(None, 0, 0)))
    else:
        fn = jax.jit(lambda arrs, i, ww: jax.lax.scan(
            lambda c, x: (c, gather_batch(cfg, arrs, *x)), 0, (i, ww))[1])
    got = jax.device_get(fn(ring.snapshot(), ints, w))
    for j in range(idxes.shape[0]):
        want = host._gather_rows(idxes[j])
        for key in ("obs", "last_action", "last_reward", "hidden", "action",
                    "n_step_reward", "n_step_gamma", "burn_in", "learning",
                    "forward"):
            np.testing.assert_array_equal(
                got[key][j], np.asarray(want[key]),
                err_msg=f"{geometry}/{mode}: field {key}, bundle {j}")
        np.testing.assert_array_equal(got["is_weights"][j], w[j])


def _gathers(jaxpr):
    """Every ``gather`` equation of a jaxpr, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _gathers(sub)


def test_gather_batch_fetches_windows_not_rows():
    """What the gather's speed rests on (PERF.md, PR 26): every ring field
    is fetched with one index a SAMPLE (a window of rows, a sequence's
    learning entries, a stored state), never one a row — no gather of the
    traced function has B * T or B * L index rows."""
    cfg = make_cfg(buffer_capacity=4 * 400, **CELL_GEOMETRIES["fabric"])
    B = 8
    ring = DeviceRing(cfg, A)
    jaxpr = jax.make_jaxpr(
        lambda arrs, i, w: gather_batch(cfg, arrs, i, w))(
            ring.snapshot(), jnp.zeros((B, 6), jnp.int32),
            jnp.ones((B,), jnp.float32))
    seen = list(_gathers(jaxpr.jaxpr))
    # obs, last_action, last_reward, hidden, action, two n-step fields
    assert len(seen) >= 7
    for eqn in seen:
        index_rows = int(np.prod(eqn.invars[1].aval.shape[:-1]))
        assert index_rows <= B, (
            f"a gather with {index_rows} index rows: {eqn}")


@pytest.mark.parametrize("n_bytes", [144, 7056, 50])
def test_frame_words_round_trip(n_bytes):
    """``unpack_frames`` undoes ``pack_frames`` for widths that are and
    are not whole 16-byte groups."""
    frames = np.random.default_rng(n_bytes).integers(
        0, 256, (3, 5, n_bytes), np.uint8)
    words = pack_frames(jnp.asarray(frames))
    assert words.dtype == jnp.uint32
    assert words.shape == (3, 5, frame_words(n_bytes))
    np.testing.assert_array_equal(
        np.asarray(unpack_frames(words, n_bytes)), frames)


def test_sample_batch_raises_on_device_buffer():
    cfg = make_cfg()
    _, dev, _ = paired_buffers(cfg, n_blocks=2)
    with pytest.raises(RuntimeError, match="device_replay"):
        dev.sample_batch(4)


@pytest.mark.slow
def test_super_step_equals_sequential_steps():
    """k fused steps (scan + in-graph gather) must reproduce k sequential
    jit_train_step calls on host-assembled batches: same params, same
    losses, same priorities."""
    cfg = make_cfg()
    k = 3
    host, dev, ring = paired_buffers(cfg, n_blocks=4)
    net = create_network(cfg, A)
    params = init_params(cfg, net, jax.random.PRNGKey(1))

    meta = dev.sample_meta(k=k, batch_size=cfg.batch_size)

    # sequential host-path reference trajectory on the same indices
    state_a = create_train_state(cfg, params)
    step = pjit_train_step(cfg, net, state_template=state_a)
    seq_losses, seq_prios = [], []
    for j in range(k):
        batch = host.sample_batch(cfg.batch_size)
        np.testing.assert_array_equal(batch["idxes"], meta["idxes"][j])
        dev_batch = {kk: jnp.asarray(v) for kk, v in batch.items()
                     if kk not in ("idxes", "block_ptr", "env_steps")}
        state_a, loss, prios = step(state_a, dev_batch)
        seq_losses.append(float(loss))
        seq_prios.append(np.asarray(prios))

    state_b = create_train_state(cfg, params)
    super_fn = single_super_step(cfg, net, k, state_b)
    state_b, losses, prios = super_fn(state_b, ring.snapshot(),
                                      jnp.asarray(meta["ints"]),
                                      jnp.asarray(meta["is_weights"]))

    np.testing.assert_allclose(np.asarray(losses), np.asarray(seq_losses),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(prios), np.stack(seq_prios),
                               rtol=1e-5)
    assert int(state_b.step) == k
    for pa, pb in zip(jax.tree.leaves(state_a.params),
                      jax.tree.leaves(state_b.params)):
        np.testing.assert_allclose(np.asarray(pa), np.asarray(pb),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_train_end_to_end_with_device_replay():
    """The full threaded fabric on the device data plane: updates advance,
    loss is finite, priority feedback reaches the buffer."""
    from r2d2_tpu.train import train

    cfg = make_cfg(game_name="Fake", device_replay=True, superstep_k=2,
                   training_steps=8, log_interval=0.2)
    metrics = train(
        cfg,
        env_factory=lambda c, seed: FakeAtariEnv(
            obs_shape=c.stored_obs_shape, action_dim=A, seed=seed),
        verbose=False)
    assert metrics["num_updates"] >= cfg.training_steps
    assert np.isfinite(metrics["mean_loss"])
    assert metrics["buffer_training_steps"] == metrics["num_updates"]
    assert not metrics["fabric_failed"]


@pytest.mark.slow
def test_sharded_super_step_matches_single_device():
    """The mesh-compiled super-step (replicated ring, dp-sharded index
    bundles, GSPMD grad psums) must reproduce the single-device super-step
    trajectory."""
    from r2d2_tpu.parallel.mesh import make_mesh

    cfg = make_cfg(mesh_shape=(("dp", 4), ("tp", 2)))
    k = 2
    _, dev, ring = paired_buffers(cfg, n_blocks=4)
    net = create_network(cfg, A)
    params = init_params(cfg, net, jax.random.PRNGKey(2))
    meta = dev.sample_meta(k=k, batch_size=cfg.batch_size)

    state_a = create_train_state(cfg, params)
    super_a = single_super_step(cfg, net, k, state_a)
    state_a, losses_a, prios_a = super_a(state_a, ring.snapshot(),
                                         jnp.asarray(meta["ints"]),
                                         jnp.asarray(meta["is_weights"]))

    table = ShardingTable(make_mesh(cfg), cfg)
    # mesh-replicated ring holding the same data
    ring_b = DeviceRing(cfg, A, placement=table.replicated())
    ring_b.arrays = {kk: jax.device_put(np.asarray(v), table.replicated())
                     for kk, v in ring.snapshot().items()}
    state_b = create_train_state(cfg, params)
    super_b = pjit_super_step(cfg, net, table, k, state_template=state_b)
    state_b = table.place_state(state_b)
    state_b, losses_b, prios_b = super_b(state_b, ring_b.snapshot(),
                                         jnp.asarray(meta["ints"]),
                                         jnp.asarray(meta["is_weights"]))

    np.testing.assert_allclose(np.asarray(losses_b), np.asarray(losses_a),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(prios_b), np.asarray(prios_a),
                               rtol=1e-5, atol=1e-6)
    for pa, pb in zip(jax.tree.leaves(state_a.params),
                      jax.tree.leaves(state_b.params)):
        np.testing.assert_allclose(np.asarray(pb), np.asarray(pa),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_train_end_to_end_device_replay_under_mesh():
    """Full fabric: device plane + mesh (single process) trains."""
    from r2d2_tpu.train import train

    cfg = make_cfg(game_name="Fake", device_replay=True, superstep_k=2,
                   training_steps=6, log_interval=0.2,
                   mesh_shape=(("dp", 4),))
    metrics = train(
        cfg,
        env_factory=lambda c, seed: FakeAtariEnv(
            obs_shape=c.stored_obs_shape, action_dim=A, seed=seed),
        use_mesh=True, verbose=False)
    assert metrics["num_updates"] >= cfg.training_steps
    assert np.isfinite(metrics["mean_loss"])
    assert not metrics["fabric_failed"]


# ---------------------------------------------------------------------------
# dp-sharded ring layout: capacity scales with the mesh
# ---------------------------------------------------------------------------

def dp_buffers(cfg, mesh, n_blocks, seed=0, layout="dp"):
    ring = DeviceRing(cfg, A, table=ShardingTable(mesh, cfg), layout=layout)
    buf = ReplayBuffer(cfg, A, rng=np.random.default_rng(99),
                       device_ring=ring)
    for blk, prios in scripted_blocks(cfg, n_blocks, seed):
        buf.add(blk, prios, None)
    return buf, ring


def test_dp_ring_round_robin_fill():
    """Logical FIFO positions land round-robin across the group slabs, so
    every dp group has data after the first G blocks."""
    from r2d2_tpu.parallel.mesh import make_mesh

    cfg = make_cfg(mesh_shape=(("dp", 4),))
    mesh = make_mesh(cfg)
    buf, ring = dp_buffers(cfg, mesh, n_blocks=4)
    bpg = ring.blocks_per_group
    assert ring.num_groups == buf.G == 4
    # block n → slot (n % 4)·bpg + n//4: first block of each slab occupied
    for g in range(4):
        assert buf.block_learning_total[g * bpg] > 0
        assert buf.block_learning_total[g * bpg + 1] == 0
    # bijection over the whole ring
    n = np.arange(cfg.num_blocks)
    assert np.array_equal(buf._log_block(buf._phys_block(n)), n)
    assert sorted(buf._phys_block(n)) == list(n)


def test_dp_sample_meta_rows_stay_in_own_group():
    """Row chunk g of every sampled bundle must reference only group g's
    slot slab — what keeps GSPMD's partitioned gather local in practice
    (no cross-slab batch traffic under the table's ring.* dp layout)."""
    from r2d2_tpu.parallel.mesh import make_mesh

    cfg = make_cfg(mesh_shape=(("dp", 4),))
    mesh = make_mesh(cfg)
    buf, ring = dp_buffers(cfg, mesh, n_blocks=8)
    B, G = cfg.batch_size, 4
    meta = buf.sample_meta(k=3, batch_size=B)
    per, bpg = B // G, ring.blocks_per_group
    for j in range(3):
        blocks = meta["ints"][j, :, 0]
        for g in range(G):
            rows = blocks[g * per:(g + 1) * per]
            assert np.all((rows >= g * bpg) & (rows < (g + 1) * bpg)), (
                f"bundle {j} group {g} rows {rows} escaped slab")


def test_dp_sample_meta_rejects_indivisible_batch():
    from r2d2_tpu.parallel.mesh import make_mesh

    cfg = make_cfg(mesh_shape=(("dp", 4),))
    buf, _ = dp_buffers(cfg, make_mesh(cfg), n_blocks=4)
    with pytest.raises(ValueError, match="divisible"):
        buf.sample_meta(k=1, batch_size=6)


@pytest.mark.slow
def test_dp_sharded_super_step_matches_single_device():
    """The dp-sharded data plane (slot-sharded ring, GSPMD-partitioned
    gather) must reproduce the single-device super-step on the same index
    bundles — only the byte placement changes, never the math."""
    from r2d2_tpu.parallel.mesh import make_mesh

    cfg = make_cfg(mesh_shape=(("dp", 4), ("tp", 2)))
    mesh = make_mesh(cfg)
    k = 2
    buf, ring = dp_buffers(cfg, mesh, n_blocks=6)
    net = create_network(cfg, A)
    params = init_params(cfg, net, jax.random.PRNGKey(7))
    meta = buf.sample_meta(k=k, batch_size=cfg.batch_size)

    # single-device reference on the same physical slot arrangement
    arrays_host = {kk: np.asarray(jax.device_get(v))
                   for kk, v in ring.snapshot().items()}
    state_a = create_train_state(cfg, params)
    super_a = single_super_step(cfg, net, k, state_a)
    state_a, losses_a, prios_a = super_a(
        state_a, {kk: jnp.asarray(v) for kk, v in arrays_host.items()},
        jnp.asarray(meta["ints"]), jnp.asarray(meta["is_weights"]))

    table = ShardingTable(mesh, cfg)
    state_b = create_train_state(cfg, params)
    super_b = pjit_super_step(cfg, net, table, k,
                              state_template=state_b, layout="dp")
    state_b = table.place_state(state_b)
    state_b, losses_b, prios_b = super_b(state_b, ring.snapshot(),
                                         jnp.asarray(meta["ints"]),
                                         jnp.asarray(meta["is_weights"]))

    np.testing.assert_allclose(np.asarray(losses_b), np.asarray(losses_a),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(prios_b), np.asarray(prios_a),
                               rtol=1e-5, atol=1e-6)
    for pa, pb in zip(jax.tree.leaves(state_a.params),
                      jax.tree.leaves(state_b.params)):
        np.testing.assert_allclose(np.asarray(pb), np.asarray(pa),
                                   rtol=1e-5, atol=1e-6)


def test_dp_stale_priority_masking_uses_logical_walk():
    """Feedback for slots overwritten since sampling must be dropped; with
    G > 1 the overwritten set is an interval of the LOGICAL walk that maps
    to non-contiguous physical slots."""
    from r2d2_tpu.parallel.mesh import make_mesh

    cfg = make_cfg(mesh_shape=(("dp", 2),))
    mesh = make_mesh(cfg)
    NB, K = cfg.num_blocks, cfg.seqs_per_block
    buf, ring = dp_buffers(cfg, mesh, n_blocks=NB)  # full ring, ptr wraps to 0
    assert buf.block_ptr == 0
    old_ptr = buf.block_ptr

    for blk, prios in scripted_blocks(cfg, 3, seed=5):
        buf.add(blk, prios, None)  # overwrites logical 0,1,2
    assert buf.block_ptr == 3

    before = buf.tree.nodes[buf.tree.leaf_offset:
                            buf.tree.leaf_offset + NB * K].copy()
    idxes = np.arange(NB * K, dtype=np.int64)
    buf.update_priorities(idxes, np.full(NB * K, 5.0), old_ptr, loss=0.0)
    after = buf.tree.nodes[buf.tree.leaf_offset:
                           buf.tree.leaf_offset + NB * K]

    stale_slots = buf._phys_block(np.arange(3))           # logical 0,1,2
    assert set(stale_slots) == {0, NB // 2, 1}            # non-contiguous
    expected = 5.0 ** cfg.prio_exponent
    for slot in range(NB):
        leaves = slice(slot * K, (slot + 1) * K)
        if slot in stale_slots:
            np.testing.assert_array_equal(after[leaves], before[leaves])
        else:
            np.testing.assert_allclose(after[leaves], expected, rtol=1e-12)


def test_dp_is_weights_use_per_group_densities():
    """IS weights must correct for the realised inclusion probabilities:
    prio/mass_of_own_group, min-normalised across the whole batch."""
    from r2d2_tpu.parallel.mesh import make_mesh

    cfg = make_cfg(mesh_shape=(("dp", 2),))
    mesh = make_mesh(cfg)
    ring = DeviceRing(cfg, A, table=ShardingTable(mesh, cfg), layout="dp")
    buf = ReplayBuffer(cfg, A, rng=np.random.default_rng(3),
                       device_ring=ring)
    blocks = scripted_blocks(cfg, 2)
    K = cfg.seqs_per_block
    assert K == 2
    # non-uniform priorities WITHIN each group so densities (and therefore
    # weights) actually vary — uniform priorities would make every weight
    # exactly 1.0 and the assertions vacuous
    buf.add(blocks[0][0], np.array([1.0, 3.0]), None)    # → group 0
    buf.add(blocks[1][0], np.array([4.0, 12.0]), None)   # → group 1

    meta = buf.sample_meta(k=1, batch_size=cfg.batch_size)
    idx, w = meta["idxes"][0], meta["is_weights"][0]
    leaf_prio = buf.tree.nodes[buf.tree.leaf_offset + idx]
    span = (cfg.num_blocks // 2) * K
    group = idx // span
    mass = np.array([buf.tree.prefix_mass(span),
                     buf.tree.prefix_mass(2 * span)
                     - buf.tree.prefix_mass(span)])
    q = leaf_prio / mass[group]
    expected = (q / q.min()) ** (-cfg.importance_sampling_exponent)
    np.testing.assert_allclose(w, expected, rtol=1e-6)
    assert w.min() < 1.0 - 1e-6 and w.max() == pytest.approx(1.0)
    # group 1's priorities are group 0's scaled by 4, so the per-group
    # normalisation must cancel the scale: both groups produce the SAME
    # density set {1^α/m0, 3^α/m0} — the cross-group fairness property
    q0 = np.unique(np.round(q[group == 0], 12))
    q1 = np.unique(np.round(q[group == 1], 12))
    assert np.intersect1d(q0, q1).size > 0


def test_grouped_sampling_is_unbiased_at_full_correction():
    """At β=1 the IS-weighted visitation E[count_i · w_i] must be uniform
    across ALL leaves — including across groups with very different
    masses.  This is the end-to-end statistical pin of the per-group
    density math: a sampler that normalised by the wrong mass (e.g. the
    total tree mass) would systematically over/under-weight one group."""
    from r2d2_tpu.parallel.mesh import make_mesh

    cfg = make_cfg(mesh_shape=(("dp", 2),),
                   importance_sampling_exponent=1.0)
    mesh = make_mesh(cfg)
    buf, ring = dp_buffers(cfg, mesh, n_blocks=cfg.num_blocks)
    NB, K = cfg.num_blocks, cfg.seqs_per_block
    rng = np.random.default_rng(11)
    # wildly skewed priorities: group 1's slab ~20x group 0's mass
    prios = rng.random(NB * K) + 0.5
    prios[NB * K // 2:] *= 20.0
    buf.tree.update(np.arange(NB * K), prios)

    B, draws = cfg.batch_size, 6000
    totals = np.zeros(NB * K)
    for _ in range(draws):
        idx, q = buf._grouped_densities(B)
        np.add.at(totals, idx, 1.0 / q)  # β=1 correction, constant dropped
    # E[count_i · (1/q_i)] = rows_per_group — identical for every leaf
    expected = draws * (B // 2)
    np.testing.assert_allclose(totals, expected, rtol=0.15)


def test_resolve_layout():
    from r2d2_tpu.parallel.mesh import make_mesh
    from r2d2_tpu.replay.device_ring import resolve_layout

    cfg = make_cfg(mesh_shape=(("dp", 4),))
    mesh = make_mesh(cfg)
    GB = 10 ** 9
    # auto: fits on one device → replicate; doesn't fit → shard
    assert resolve_layout(cfg, mesh, GB, 16 * GB) == "replicated"
    assert resolve_layout(cfg, mesh, 15 * GB, 16 * GB) == "dp"
    # auto but shapes indivisible → stay replicated (guard falls back)
    cfg_bad = make_cfg(mesh_shape=(("dp", 4),), batch_size=6)
    assert resolve_layout(cfg_bad, mesh, 15 * GB, 16 * GB) == "replicated"
    # explicit requests
    assert resolve_layout(cfg.replace(device_ring_layout="replicated"),
                          mesh, 15 * GB, 16 * GB) == "replicated"
    assert resolve_layout(cfg.replace(device_ring_layout="dp"),
                          mesh, GB, 16 * GB) == "dp"
    with pytest.raises(ValueError, match="dp"):
        resolve_layout(cfg_bad.replace(device_ring_layout="dp"),
                       mesh, GB, 16 * GB)
    with pytest.raises(ValueError, match="mesh"):
        resolve_layout(cfg.replace(device_ring_layout="dp"), None,
                       GB, 16 * GB)
    # auto + in_graph_per: shards exactly like the host-PER ring — the
    # global in-graph sampler reads dp slabs through GSPMD
    # (parallel/sharding.py)
    cfg_ig = make_cfg(mesh_shape=(("dp", 4),), device_replay=True,
                      in_graph_per=True)
    assert resolve_layout(cfg_ig, mesh, 15 * GB, 16 * GB) == "dp"
    assert resolve_layout(cfg_ig, mesh, GB, 16 * GB) == "replicated"


@pytest.mark.slow
def test_train_end_to_end_device_replay_dp_layout():
    """Full fabric on the dp-sharded device data plane."""
    from r2d2_tpu.train import train

    cfg = make_cfg(game_name="Fake", device_replay=True, superstep_k=2,
                   training_steps=6, log_interval=0.2,
                   mesh_shape=(("dp", 4),), device_ring_layout="dp")
    metrics = train(
        cfg,
        env_factory=lambda c, seed: FakeAtariEnv(
            obs_shape=c.stored_obs_shape, action_dim=A, seed=seed),
        use_mesh=True, verbose=False)
    assert metrics["num_updates"] >= cfg.training_steps
    assert np.isfinite(metrics["mean_loss"])
    assert not metrics["fabric_failed"]


def test_device_replay_ring_too_big_is_an_error(monkeypatch):
    """A device_replay ring that does not fit the device must stop the
    bring-up with an error naming the ring's bytes, the device's limit
    and a buffer_capacity that fits — never warn and run host replay
    under the device drivetrain's name (ISSUE 21 finding 1)."""
    import importlib
    import re

    # (the package re-exports train() over the submodule attribute)
    train_mod = importlib.import_module("r2d2_tpu.train")

    cfg = make_cfg(game_name="Fake", device_replay=True, in_graph_per=True)
    need = device_bytes(cfg, A)
    limit = need  # the ring may take 80% of the limit: this is too small
    monkeypatch.setattr(train_mod, "_device_memory_bytes", lambda: limit)
    factory = lambda c, seed: FakeAtariEnv(  # noqa: E731
        obs_shape=c.stored_obs_shape, action_dim=A, seed=seed)
    with pytest.raises(ValueError, match="buffer_capacity=") as ex:
        train_mod._build(cfg, factory, False, None, False)
    msg = str(ex.value)
    assert f"{need / 1e9:.2f} GB" in msg and f"{limit / 1e9:.2f} GB" in msg
    # the capacity it names really passes the same guard, and is the
    # largest whole-block capacity that does
    fits = int(re.search(r"buffer_capacity=(\d+) fits", msg).group(1))
    assert 0 < fits < cfg.buffer_capacity and fits % cfg.block_length == 0
    assert train_mod._checked_ring_layout(
        cfg.replace(buffer_capacity=fits), A, None) == "replicated"
    with pytest.raises(ValueError):
        train_mod._checked_ring_layout(
            cfg.replace(buffer_capacity=fits + cfg.block_length), A, None)
    # the anakin trainer shares the guard
    with pytest.raises(ValueError, match="buffer_capacity="):
        train_mod.train(cfg.replace(actor_transport="anakin"),
                        verbose=False)


def test_run_device_cadences_and_drain(tmp_path):
    """run_device must fire weight publication and checkpoint cadences on
    interval crossings even when k doesn't divide them, and harvest the
    pipelined pending super-step on exit (all priorities reach the sink)."""
    from r2d2_tpu.checkpoint import Checkpointer
    from r2d2_tpu.learner.learner import Learner
    from r2d2_tpu.utils.store import ParamStore

    cfg = make_cfg(training_steps=12, superstep_k=3,
                   weight_publish_interval=4, save_interval=5)
    _, dev, ring = paired_buffers(cfg, n_blocks=4)
    net = create_network(cfg, A)
    params = init_params(cfg, net, jax.random.PRNGKey(5))
    store = ParamStore()
    learner = Learner(cfg, net, create_train_state(cfg, params),
                      param_store=store,
                      checkpointer=Checkpointer(str(tmp_path)))

    sunk = []
    metrics = learner.run_device(
        dev, ring,
        priority_sink=lambda i, p, ptr, l: sunk.append((i.copy(), p.copy())))

    assert metrics["num_updates"] == 12  # k=3 divides 12: exact
    # every dispatched sub-batch's priorities were harvested (incl. the
    # final pending super-step)
    assert len(sunk) == 12 // 3 * 3
    # publish crossings at 4, 8, 12 (+1 initial publish at construction)
    assert store.get()[0] == 4
    # checkpoint crossings at 5, 10 + the final save
    ck = Checkpointer(str(tmp_path))
    assert 12 in ck.steps() and len(ck.steps()) >= 2


@pytest.mark.slow
@pytest.mark.parametrize("depth", [0, 3])
def test_run_device_pipeline_depths(depth):
    """The super-step pipeline must deliver every dispatched sub-batch's
    priorities exactly once at any depth — 0 (fully synchronous harvest)
    and deeper-than-default (more in-flight dispatches than the drain at
    exit, exercising the final drain loop)."""
    from r2d2_tpu.learner.learner import Learner

    cfg = make_cfg(training_steps=12, superstep_k=2,
                   superstep_pipeline=depth)
    _, dev, ring = paired_buffers(cfg, n_blocks=4)
    net = create_network(cfg, A)
    learner = Learner(cfg, net, create_train_state(
        cfg, init_params(cfg, net, jax.random.PRNGKey(7))))

    sunk = []
    metrics = learner.run_device(
        dev, ring,
        priority_sink=lambda i, p, ptr, l: sunk.append((i.copy(), p.copy())))

    assert metrics["num_updates"] == 12
    assert len(sunk) == 12  # one sink call per update, none stranded
    assert all(np.all(np.isfinite(p)) for _, p in sunk)
    assert np.isfinite(metrics["mean_loss"])


def test_run_device_stop_midway():
    """A stop() between super-steps exits promptly and still harvests the
    in-flight super-step."""
    from r2d2_tpu.learner.learner import Learner

    cfg = make_cfg(training_steps=1000, superstep_k=2)
    _, dev, ring = paired_buffers(cfg, n_blocks=4)
    net = create_network(cfg, A)
    learner = Learner(cfg, net, create_train_state(
        cfg, init_params(cfg, net, jax.random.PRNGKey(6))))

    calls = []
    sunk = []
    metrics = learner.run_device(
        dev, ring, priority_sink=lambda i, p, ptr, l: sunk.append(1),
        stop=lambda: len(calls) >= 3 or calls.append(1))

    assert metrics["num_updates"] == 2 * 3
    assert len(sunk) == 2 * 3  # nothing stranded in the pipeline
