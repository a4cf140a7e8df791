"""ReplayBuffer behavior tests (VERDICT r1 item 5).

Covers the subtlest host-plane logic: sample-window alignment against the
stored wire format, ring-overwrite size accounting, stale-index masking on
priority feedback across ring wraparound (reference semantics:
worker.py:242-258), the clamp-padding invariant for short sequences, and
readiness/zero-leaf guards.
"""
import numpy as np
import pytest

from r2d2_tpu.config import test_config as make_test_config
from r2d2_tpu.learner.step import _window_indices
from r2d2_tpu.replay.block import LocalBuffer
from r2d2_tpu.replay.replay_buffer import ReplayBuffer

A = 4


def make_cfg(**kw):
    # burn_in=4, learning=4, forward=2 → T=10; block_length=8 → K=2;
    # capacity 160 → 20 blocks, 40 leaves
    return make_test_config(**kw)


def scripted_block(cfg, local, tag, steps, terminal, reset=False):
    """Drive ``steps`` env steps through a LocalBuffer with recognisable
    content: obs pixels = (tag + global step) % 256, action = step % A,
    reward = step.  Returns finish() output."""
    if reset:
        obs0 = np.full(cfg.obs_shape, tag % 256, np.uint8)
        local.reset(obs0)
    base = local.curr_burn_in_steps
    for s in range(steps):
        t = tag + base + s + 1
        obs = np.full(cfg.obs_shape, t % 256, np.uint8)
        q = np.arange(A, dtype=np.float32) + s
        hidden = np.full((2, cfg.lstm_layers, cfg.hidden_dim),
                         (t % 100) / 100.0, np.float32)
        local.add(s % A, float(s), obs, q, hidden)
    return local.finish(None if terminal else np.zeros(A, np.float32))


def fill(buffer, cfg, num_blocks, steps=None, start_tag=0):
    """Add ``num_blocks`` fresh-episode blocks; returns the Block objects."""
    blocks = []
    for b in range(num_blocks):
        local = LocalBuffer(cfg, A)
        blk, prios, _ = scripted_block(
            cfg, local, tag=start_tag + 1000 * b,
            steps=steps or cfg.block_length, terminal=True, reset=True)
        buffer.add(blk, prios, episode_reward=1.0)
        blocks.append(blk)
    return blocks


def test_sample_alignment_matches_stored_blocks():
    cfg = make_cfg()
    rng = np.random.default_rng(0)
    buf = ReplayBuffer(cfg, A, rng=rng)
    blocks = fill(buf, cfg, 6)

    L, K, T = cfg.learning_steps, cfg.seqs_per_block, cfg.seq_len
    for _ in range(20):
        batch = buf.sample_batch(8)
        for i in range(8):
            b_idx = int(batch["idxes"][i]) // K
            s_idx = int(batch["idxes"][i]) % K
            blk = blocks[b_idx]
            burn_in = int(batch["burn_in"][i])
            learning = int(batch["learning"][i])
            forward = int(batch["forward"][i])
            assert burn_in == blk.burn_in_steps[s_idx]
            assert learning == blk.learning_steps[s_idx]
            assert forward == blk.forward_steps[s_idx]

            t0 = int(blk.burn_in_steps[0]) + s_idx * L - burn_in
            valid = burn_in + learning + forward
            np.testing.assert_array_equal(
                batch["obs"][i, :valid], blk.obs[t0:t0 + valid])
            np.testing.assert_array_equal(
                batch["last_action"][i, :valid],
                blk.last_action[t0:t0 + valid].astype(np.float32))
            np.testing.assert_array_equal(
                batch["last_reward"][i, :valid],
                blk.last_reward[t0:t0 + valid])
            np.testing.assert_array_equal(
                batch["action"][i, :learning],
                blk.action[s_idx * L:s_idx * L + learning])
            np.testing.assert_array_equal(
                batch["n_step_reward"][i, :learning],
                blk.n_step_reward[s_idx * L:s_idx * L + learning])
            np.testing.assert_array_equal(
                batch["hidden"][i], blk.hidden[s_idx])
            assert 0.0 < batch["is_weights"][i] <= 1.0 + 1e-9


def test_ring_overwrite_size_accounting():
    cfg = make_cfg()
    buf = ReplayBuffer(cfg, A, rng=np.random.default_rng(1))
    NB = cfg.num_blocks  # 20

    fill(buf, cfg, NB + 5)  # 5 slots overwritten
    # every live slot holds a full block of block_length learning steps
    assert len(buf) == NB * cfg.block_length
    assert buf.block_ptr == 5

    # overwrite slot 5 (next) with a short terminal block: size shrinks by
    # the difference
    local = LocalBuffer(cfg, A)
    blk, prios, _ = scripted_block(cfg, local, tag=9_000_000, steps=3,
                                   terminal=True, reset=True)
    buf.add(blk, prios, episode_reward=None)
    assert len(buf) == (NB - 1) * cfg.block_length + 3


def test_update_priorities_masks_overwritten_no_wrap():
    cfg = make_cfg()
    buf = ReplayBuffer(cfg, A, rng=np.random.default_rng(2))
    fill(buf, cfg, 6)
    K = cfg.seqs_per_block

    batch = buf.sample_batch(8)
    old_ptr = batch["block_ptr"]  # == 6
    fill(buf, cfg, 2, start_tag=500_000)  # overwrites slots 6, 7
    new_ptr = buf.block_ptr  # == 8

    sentinel = np.full(8, 123.0, np.float32)
    before = buf.tree.nodes[buf.tree.leaf_offset:].copy()
    buf.update_priorities(batch["idxes"], sentinel, old_ptr, loss=0.0)
    after = buf.tree.nodes[buf.tree.leaf_offset:]

    stale = (batch["idxes"] >= old_ptr * K) & (batch["idxes"] < new_ptr * K)
    expected = 123.0 ** cfg.prio_exponent
    for idx, is_stale in zip(batch["idxes"], stale):
        if is_stale:
            assert after[idx] == before[idx], "stale leaf must be untouched"
        else:
            assert after[idx] == pytest.approx(expected)


def test_update_priorities_masks_overwritten_wraparound():
    cfg = make_cfg()
    buf = ReplayBuffer(cfg, A, rng=np.random.default_rng(3))
    NB, K = cfg.num_blocks, cfg.seqs_per_block
    fill(buf, cfg, NB - 2)  # ptr at NB-2

    batch = buf.sample_batch(8)
    old_ptr = batch["block_ptr"]  # NB-2
    fill(buf, cfg, 4, start_tag=700_000)  # wraps: overwrites NB-2, NB-1, 0, 1
    new_ptr = buf.block_ptr
    assert new_ptr == 2 and new_ptr < old_ptr

    sentinel = np.full(8, 77.0, np.float32)
    before = buf.tree.nodes[buf.tree.leaf_offset:].copy()
    buf.update_priorities(batch["idxes"], sentinel, old_ptr, loss=0.0)
    after = buf.tree.nodes[buf.tree.leaf_offset:]

    # live leaves are [new_ptr*K, old_ptr*K); everything else was overwritten
    live = (batch["idxes"] >= new_ptr * K) & (batch["idxes"] < old_ptr * K)
    expected = 77.0 ** cfg.prio_exponent
    for idx, is_live in zip(batch["idxes"], live):
        if is_live:
            assert after[idx] == pytest.approx(expected)
        else:
            assert after[idx] == before[idx]


def test_same_ptr_after_full_cycle_updates_everything():
    """old_ptr == new_ptr is treated as 'nothing overwritten' (matching the
    reference worker.py:242-258, which cannot distinguish a full cycle —
    documents that known approximation)."""
    cfg = make_cfg()
    buf = ReplayBuffer(cfg, A, rng=np.random.default_rng(4))
    fill(buf, cfg, 3)
    batch = buf.sample_batch(4)
    buf.update_priorities(batch["idxes"], np.full(4, 5.0, np.float32),
                          batch["block_ptr"], loss=0.5)
    after = buf.tree.nodes[buf.tree.leaf_offset:]
    for idx in batch["idxes"]:
        assert after[idx] == pytest.approx(5.0 ** cfg.prio_exponent)
    assert buf.training_steps == 1
    assert buf.sum_loss == pytest.approx(0.5)


def test_short_block_clamp_tail_never_reaches_learner_window():
    """The clamp-padding invariant (ADVICE r1): a short terminal block
    overwriting a long one leaves stale bytes in the slot tail; every index
    the learner gathers must sit strictly before them."""
    cfg = make_cfg()
    buf = ReplayBuffer(cfg, A, rng=np.random.default_rng(5))
    fill(buf, cfg, cfg.num_blocks)  # all slots hold full 8-step blocks

    # overwrite slot 0 with a 3-step terminal episode
    local = LocalBuffer(cfg, A)
    short, prios, _ = scripted_block(cfg, local, tag=42_000, steps=3,
                                     terminal=True, reset=True)
    buf.add(short, prios, episode_reward=None)

    # force sampling of slot 0 sequence 0 by zeroing all other leaves
    all_leaves = np.arange(cfg.num_sequences)
    buf.tree.update(all_leaves, np.zeros(cfg.num_sequences, np.float32))
    buf.tree.update(np.array([0]), np.array([1.0], np.float32))

    batch = buf.sample_batch(4)
    assert (batch["idxes"] == 0).all()
    burn_in = int(batch["burn_in"][0])   # 0: fresh episode
    learning = int(batch["learning"][0])  # 3
    forward = int(batch["forward"][0])   # min(n, 1) == 1
    assert (burn_in, learning, forward) == (0, 3, 1)

    valid = burn_in + learning + forward
    # valid region matches the short block (stale-tail contents beyond it
    # are unspecified by design)
    np.testing.assert_array_equal(batch["obs"][0, :valid], short.obs[:valid])

    # every index the learner gathers (within the loss mask) must be < valid
    import jax.numpy as jnp
    idx_online, idx_target, mask = _window_indices(
        cfg, jnp.asarray(batch["burn_in"]), jnp.asarray(batch["learning"]),
        jnp.asarray(batch["forward"]))
    masked_online = np.where(np.asarray(mask), np.asarray(idx_online), 0)
    masked_target = np.where(np.asarray(mask), np.asarray(idx_target), 0)
    assert masked_online.max() < valid
    assert masked_target.max() < valid


def test_sample_empty_raises():
    cfg = make_cfg()
    buf = ReplayBuffer(cfg, A, rng=np.random.default_rng(6))
    assert not buf.ready
    with pytest.raises(RuntimeError, match="empty buffer"):
        buf.sample_batch(4)


def test_zero_priority_leaves_never_sampled():
    """A partial block fills only 1 of K=2 leaves; the empty leaf has
    priority 0 and must never be returned by stratified sampling."""
    cfg = make_cfg()
    buf = ReplayBuffer(cfg, A, rng=np.random.default_rng(7))
    local = LocalBuffer(cfg, A)
    blk, prios, _ = scripted_block(cfg, local, tag=0, steps=3,
                                   terminal=True, reset=True)
    assert blk.num_sequences == 1 and prios[1] == 0.0
    buf.add(blk, prios, episode_reward=None)
    for _ in range(50):
        batch = buf.sample_batch(4)
        assert (batch["idxes"] == 0).all()


def test_cross_block_burn_in_carryover_alignment():
    """Second block of the same episode carries a burn-in prefix; sampling
    its first sequence must reach back into carried obs."""
    cfg = make_cfg()
    buf = ReplayBuffer(cfg, A, rng=np.random.default_rng(8))
    local = LocalBuffer(cfg, A)
    blk1, prios1, _ = scripted_block(cfg, local, tag=0,
                                     steps=cfg.block_length, terminal=False,
                                     reset=True)
    blk2, prios2, _ = scripted_block(cfg, local, tag=0,
                                     steps=cfg.block_length, terminal=True)
    assert blk2.burn_in_steps[0] == cfg.burn_in_steps
    buf.add(blk1, prios1, None)
    buf.add(blk2, prios2, 1.0)

    # force sampling of block 1 sequence 0 (leaf K)
    K = cfg.seqs_per_block
    buf.tree.update(np.arange(cfg.num_sequences),
                    np.zeros(cfg.num_sequences, np.float32))
    buf.tree.update(np.array([K]), np.array([1.0], np.float32))
    batch = buf.sample_batch(2)
    assert (batch["idxes"] == K).all()
    burn_in = int(batch["burn_in"][0])
    assert burn_in == cfg.burn_in_steps
    valid = burn_in + int(batch["learning"][0]) + int(batch["forward"][0])
    np.testing.assert_array_equal(batch["obs"][0, :valid], blk2.obs[:valid])
    # the carried prefix equals the tail of the previous block's obs stream
    np.testing.assert_array_equal(
        blk2.obs[:cfg.burn_in_steps + 1],
        blk1.obs[-(cfg.burn_in_steps + 1):])


def test_ring_bytes_matches_actual_allocation():
    from r2d2_tpu.replay.replay_buffer import _ring_spec, ring_bytes

    cfg = make_cfg()
    buf = ReplayBuffer(cfg, action_dim=4)
    actual = sum(getattr(buf, name).nbytes
                 for name, _, _ in _ring_spec(cfg, 4))
    assert ring_bytes(cfg, 4) == actual
    # every spec'd array exists with the spec'd shape/dtype
    for name, shape, dtype in _ring_spec(cfg, 4):
        arr = getattr(buf, name)
        assert arr.shape == shape and arr.dtype == np.dtype(dtype)


def test_ram_guard_raises_before_allocating(monkeypatch):
    import r2d2_tpu.replay.replay_buffer as rb

    monkeypatch.setattr(rb, "_available_host_bytes", lambda: 1024)
    with pytest.raises(MemoryError, match="replay ring needs"):
        ReplayBuffer(make_cfg(), action_dim=4)


# ------------------------------------------------------------ spans (PR 25)

def _device_buffer(cfg, tracer):
    from r2d2_tpu.replay.device_ring import DeviceRing

    ring = DeviceRing(cfg, A)
    return ReplayBuffer(cfg, A, rng=np.random.default_rng(0),
                        device_ring=ring, tracer=tracer), ring


@pytest.mark.parametrize("in_graph_per", [False, True])
def test_add_records_one_stage_and_one_commit_a_block(in_graph_per):
    """A ReplayBuffer given a Tracer records replay.stage (outside the
    lock) and replay.commit (the lock held) once a block; without a tracer
    — and on a host ring, where nothing is staged — it runs as before."""
    from _span_log import SpanLog
    from r2d2_tpu.utils.trace import Tracer

    cfg = make_cfg(device_replay=True, in_graph_per=in_graph_per)
    log = SpanLog()
    buffer, _ = _device_buffer(cfg, Tracer(events=log))
    fill(buffer, cfg, 5)
    assert log.count("replay.stage") == log.count("replay.commit") == 5
    for (s0, sd), (c0, cd) in zip(log.spans["replay.stage"],
                                  log.spans["replay.commit"]):
        assert s0 + sd <= c0            # staged before the lock is taken
    assert not buffer.lock.locked()

    plain, _ = _device_buffer(cfg, None)
    fill(plain, cfg, 5)
    assert len(plain) == len(buffer) and plain.block_ptr == buffer.block_ptr

    host_log = SpanLog()
    host = ReplayBuffer(make_cfg(), A, tracer=Tracer(events=host_log))
    fill(host, make_cfg(), 3)
    assert host_log.count("replay.commit") == 3
    assert host_log.count("replay.stage") == 0


@pytest.mark.parametrize("in_graph_per,parent", [
    (True, "learner.step_dispatch"),    # the lock is taken inside the span
    (False, "learner.sample_meta"),     # sample_meta takes it, then dispatches
])
def test_the_dispatch_threads_lock_wait_is_a_span(in_graph_per, parent):
    """Every drivetrain that dispatches under buffer.lock records its wait
    for the lock as learner.lock_wait, once a dispatch, nested in time
    inside the span that asks for the lock — learner.step_dispatch itself
    in the in-graph-PER drivetrain."""
    import jax
    from _span_log import SpanLog
    from r2d2_tpu.learner.learner import Learner
    from r2d2_tpu.learner.step import create_train_state
    from r2d2_tpu.models.network import create_network, init_params
    from r2d2_tpu.utils.trace import Tracer

    cfg = make_cfg(device_replay=True, in_graph_per=in_graph_per,
                   superstep_k=2, training_steps=8, learning_starts=8)
    log = SpanLog()
    tracer = Tracer(events=log)
    buffer, ring = _device_buffer(cfg, tracer)
    fill(buffer, cfg, 6)
    net = create_network(cfg, A)
    learner = Learner(cfg, net, create_train_state(
        cfg, init_params(cfg, net, jax.random.PRNGKey(1))))
    metrics = learner.run_device(buffer, ring, tracer=tracer)
    dispatches = metrics["num_updates"] // cfg.superstep_k
    assert dispatches == 4
    assert log.count("learner.step_dispatch") == dispatches
    assert log.count("learner.lock_wait") == dispatches
    assert log.inside("learner.lock_wait", parent)
    assert not buffer.lock.locked()
