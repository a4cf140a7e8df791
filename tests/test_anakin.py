"""Anakin fused on-device training loop (ISSUE 6).

Four layers of guarantees, matching the issue's acceptance criteria:

1. **Env parity** — the pure-JAX env (envs/anakin.py) is step-for-step
   bit-exact against the numpy ``FakeAtariEnv`` oracle across episode
   boundaries (obs bytes, reward incl. the +2 truncation bonus,
   truncation flags).  Reset phases come from the anakin env's
   counter-based stream and are replayed into the oracle through its
   resumable-state surface (the RNG *source* is the one documented
   divergence; the *dynamics* are what this pins).
2. **Block parity** — anakin-cut blocks (in-graph assembly + ring/PER
   scatters) match host ``LocalBuffer``-cut blocks for the same
   trajectory: integer fields, obs streams, gamma tails and stored
   hiddens bit-exact; n-step returns and priorities to f32 round-off
   (the host accumulates those in float64 — learner/anakin.py docstring).
3. **Host-freedom** — HOST_TRANSFERS per fused super-step is a small
   constant (one result-vector fetch), independent of lane count, k and
   step count; the programs stay within their RETRACES budgets.
4. **Recovery** — the full on-device loop state (ring, PER, env phase,
   RNG streams, LSTM carry, local buffers) snapshots and resumes
   BIT-EXACT: an interrupted run continues to the same params as an
   uninterrupted one; SIGTERM→--resume continues warm end to end.
"""
import os
import signal

import jax
import numpy as np
import pytest

from r2d2_tpu.config import Config, test_config as make_test_config
from r2d2_tpu.envs import FakeAtariEnv
from r2d2_tpu.envs.anakin import AnakinFakeEnv
from r2d2_tpu.learner.anakin import (
    AnakinPlane,
    make_anakin_state,
    make_debug_rollout,
    run_anakin_loop,
)
from r2d2_tpu.learner.learner import Learner
from r2d2_tpu.learner.step import create_train_state
from r2d2_tpu.models.network import create_network, init_params
from r2d2_tpu.replay.block import LocalBuffer
from r2d2_tpu.replay.device_ring import (
    TIME_KEYS,
    DeviceRing,
    unpack_frames,
)
from r2d2_tpu.train import train

A = 4


def anakin_config(**kw):
    base = dict(game_name="Fake", actor_transport="anakin",
                device_replay=True, in_graph_per=True,
                num_actors=2, superstep_k=2, anakin_episode_len=12,
                training_steps=24, learning_starts=16)
    base.update(kw)
    return make_test_config(**base)


def build_plane(cfg, seed=0):
    net = create_network(cfg, A)
    params = init_params(cfg, net, jax.random.PRNGKey(seed))
    state = create_train_state(cfg, params)
    ring = DeviceRing(cfg, A)
    plane = AnakinPlane(cfg, net, A, ring)
    learner = Learner(cfg, net, state)
    return net, plane, learner


# --------------------------------------------------------------- satellite

def test_fake_env_reset_seed_reseeds_action_space():
    """Regression (ISSUE 6 satellite): ``reset(seed=...)`` rebinds the env
    RNG *and* the action space's — exploration sampling must replay."""
    env = FakeAtariEnv(obs_shape=(12, 12, 1), action_dim=A, seed=0)
    env.reset(seed=123)
    first = [env.action_space.sample() for _ in range(20)]
    env.reset(seed=123)
    again = [env.action_space.sample() for _ in range(20)]
    assert first == again
    # the generators are the SAME object again (the bug left the action
    # space on the pre-reseed generator)
    assert env.action_space._rng is env._rng


# -------------------------------------------------------------- env parity

def test_anakin_env_bit_exact_vs_numpy_oracle():
    """obs/reward/truncation bit-exact vs FakeAtariEnv across >= 2 episode
    boundaries per lane, with the anakin phase stream replayed into the
    oracle at each reset."""
    N, ep_len = 3, 5
    env = AnakinFakeEnv(obs_shape=(12, 12, 1), action_dim=A,
                        episode_len=ep_len, num_lanes=N)
    st = env.init_state(jax.random.PRNGKey(7))
    step = jax.jit(env.step)
    reset_lanes = jax.jit(env.reset_lanes)

    def force_phase(oracle, phase):
        oracle.reset()
        oracle.restore_state(dict(rng=oracle._rng.bit_generator.state,
                                  phase=int(phase), t=0))

    oracles = []
    for lane in range(N):
        o = FakeAtariEnv(obs_shape=(12, 12, 1), action_dim=A,
                         episode_len=ep_len, seed=lane)
        force_phase(o, st["phase"][lane])
        oracles.append(o)
        np.testing.assert_array_equal(np.asarray(env.observe(st)[lane]),
                                      o._obs())

    rng = np.random.default_rng(1)
    for t in range(3 * ep_len + 2):
        actions = rng.integers(0, A, size=N)
        st, reward, trunc = step(st, jax.numpy.asarray(actions))
        obs = np.asarray(env.observe(st))
        for lane in range(N):
            oo, orr, oterm, otr, _ = oracles[lane].step(int(actions[lane]))
            np.testing.assert_array_equal(obs[lane], oo)
            assert float(reward[lane]) == orr  # f32-exact: {0,1,2,3}
            assert bool(trunc[lane]) == otr and not oterm
        if bool(trunc.any()):
            st = reset_lanes(st, trunc)
            obs = np.asarray(env.observe(st))
            for lane in range(N):
                if bool(trunc[lane]):
                    force_phase(oracles[lane], st["phase"][lane])
                    np.testing.assert_array_equal(obs[lane],
                                                  oracles[lane]._obs())


def test_anakin_grid_env_bit_exact_vs_numpy_oracle():
    """The second jittable env (ISSUE 15): the gridworld twin is
    step-for-step bit-exact against the numpy GridWorldEnv oracle across
    episode boundaries — obs bytes, rewards, truncation flags.  Reset
    agent/goal draws come from the anakin env's per-lane streams and are
    replayed into the oracle through its resumable-state surface (the
    RNG source is the one documented divergence; in-episode dynamics are
    fully deterministic, so the replay covers whole episodes)."""
    from r2d2_tpu.envs import GridWorldEnv
    from r2d2_tpu.envs.anakin import AnakinGridEnv

    N, ep_len = 3, 6
    env = AnakinGridEnv(obs_shape=(12, 12, 1), action_dim=A,
                        episode_len=ep_len, num_lanes=N)
    st = env.init_state(jax.random.PRNGKey(7))
    step = jax.jit(env.step)
    reset_lanes = jax.jit(env.reset_lanes)

    def force(oracle, lane_state, lane):
        oracle.reset()
        oracle.restore_state(dict(
            rng=oracle._rng.bit_generator.state,
            agent=int(lane_state["agent"][lane]),
            goal=int(lane_state["goal"][lane]), t=0))

    oracles = []
    for lane in range(N):
        o = GridWorldEnv(obs_shape=(12, 12, 1), action_dim=A,
                         episode_len=ep_len, seed=lane)
        force(o, st, lane)
        np.testing.assert_array_equal(np.asarray(env.observe(st)[lane]),
                                      o._obs())
        oracles.append(o)

    rng = np.random.default_rng(1)
    for t in range(3 * ep_len + 2):
        actions = rng.integers(0, A, size=N)
        st, reward, trunc = step(st, jax.numpy.asarray(actions))
        obs = np.asarray(env.observe(st))
        for lane in range(N):
            oo, orr, oterm, otr, _ = oracles[lane].step(int(actions[lane]))
            np.testing.assert_array_equal(obs[lane], oo)
            assert float(reward[lane]) == orr  # f32-exact: {0, 1}
            assert bool(trunc[lane]) == otr and not oterm
        if bool(trunc.any()):
            st = reset_lanes(st, trunc)
            obs = np.asarray(env.observe(st))
            for lane in range(N):
                if bool(trunc[lane]):
                    force(oracles[lane], st, lane)
                    np.testing.assert_array_equal(obs[lane],
                                                  oracles[lane]._obs())
    # the host mirror of one reset draw matches the in-graph one
    k0 = np.asarray(jax.random.PRNGKey(5), np.uint32)
    k1, agent, goal = env.host_reset_draw(k0)
    st1 = env.reset_lanes(
        dict(agent=jax.numpy.zeros(1, jax.numpy.int32),
             goal=jax.numpy.ones(1, jax.numpy.int32),
             t=jax.numpy.zeros(1, jax.numpy.int32),
             key=jax.numpy.asarray(k0)[None]),
        jax.numpy.ones(1, bool))
    assert int(st1["agent"][0]) == agent and int(st1["goal"][0]) == goal
    np.testing.assert_array_equal(np.asarray(st1["key"][0]), k1)


# ------------------------------------------------------------ block parity

@pytest.mark.parametrize("mode", ["burn_in_start", "seq_start"])
def test_anakin_blocks_match_local_buffer_oracle(mode):
    """Drive the fused actor for T steps, then replay the EXACT recorded
    trajectory (obs/q/hidden/action/reward streams from the in-graph
    trace) into host LocalBuffers and compare every emitted block against
    the ring slot the fused loop wrote — boundary cuts with bootstrap Q,
    episode-end cuts, burn-in carry-over, windows, stored hiddens,
    priorities and the PER leaf/metadata state."""
    cfg = anakin_config(num_actors=3, anakin_episode_len=13,
                        buffer_capacity=30 * 8, stored_hidden_mode=mode)
    N, K = cfg.num_actors, cfg.seqs_per_block
    net = create_network(cfg, A)
    params = init_params(cfg, net, jax.random.PRNGKey(0))
    ring = DeviceRing(cfg, A)
    env = AnakinFakeEnv(obs_shape=cfg.stored_obs_shape, action_dim=A,
                        episode_len=cfg.anakin_episode_len, num_lanes=N)
    ast = make_anakin_state(cfg, A, env, jax.random.PRNGKey(11))
    init_obs = np.asarray(ast["obs"])

    T = 40
    roll = make_debug_rollout(cfg, net, env, A, T)
    meta0 = ring.per_meta()
    (_, arrays, prios, seq_meta, first), tr = roll(
        params, ast, ring.snapshot(), ring.take_prios(),
        meta0["seq_meta"], meta0["first"])
    tr = jax.device_get(tr)
    arrays = jax.device_get(arrays)
    prios = np.asarray(prios)
    seq_meta = np.asarray(seq_meta)
    first = np.asarray(first)

    lbs = [LocalBuffer(cfg, A) for _ in range(N)]
    for i in range(N):
        lbs[i].reset(init_obs[i])
    host_blocks = []  # (block, priorities) in ring-slot emission order
    for t in range(T):
        for i in range(N):           # boundary cuts first, lane order
            if tr["pending"][t][i]:
                host_blocks.append(lbs[i].finish(tr["q"][t][i]))
        for i in range(N):
            lbs[i].add(int(tr["actions"][t][i]),
                       float(tr["reward"][t][i]), tr["obs_step"][t][i],
                       tr["q"][t][i], tr["hidden"][t][i])
        for i in range(N):           # then episode-end cuts, lane order
            if tr["truncated"][t][i]:
                host_blocks.append(lbs[i].finish(None))
                lbs[i].reset(tr["obs_next"][t][i])

    assert len(host_blocks) > 6, "trajectory produced too few cuts"
    assert len(host_blocks) <= cfg.num_blocks, "test must not wrap the ring"
    for slot, (blk, pri, _ep) in enumerate(host_blocks):
        n_obs, n_steps = blk.obs.shape[0], blk.action.shape[0]
        k = blk.num_sequences
        # a cut fills the time fields' spare rows with copies of the last
        # row a block can store (replay/device_ring._slot_shapes)
        MS = cfg.max_block_steps
        for key in TIME_KEYS:
            rows = arrays[key][slot]
            assert rows.shape[0] > MS
            np.testing.assert_array_equal(
                rows[MS:], np.broadcast_to(rows[MS - 1], rows[MS:].shape))
        # the device ring stores frames as flat rows packed into words
        np.testing.assert_array_equal(
            blk.obs.reshape(n_obs, -1),
            np.asarray(unpack_frames(arrays["obs"][slot][:n_obs],
                                     int(np.prod(cfg.stored_obs_shape)))))
        np.testing.assert_array_equal(blk.last_action,
                                      arrays["last_action"][slot][:n_obs])
        np.testing.assert_array_equal(blk.last_reward,
                                      arrays["last_reward"][slot][:n_obs])
        np.testing.assert_array_equal(blk.action,
                                      arrays["action"][slot][:n_steps])
        np.testing.assert_array_equal(blk.n_step_gamma,
                                      arrays["n_step_gamma"][slot][:n_steps])
        np.testing.assert_array_equal(blk.hidden,
                                      arrays["hidden"][slot][:k])
        np.testing.assert_allclose(blk.n_step_reward,
                                   arrays["n_step_reward"][slot][:n_steps],
                                   rtol=0, atol=2e-5)
        want_meta = np.stack([blk.burn_in_steps, blk.learning_steps,
                              blk.forward_steps], 1).astype(np.int32)
        np.testing.assert_array_equal(want_meta, seq_meta[slot][:k])
        assert first[slot] == int(blk.burn_in_steps[0])
        want_prios = (np.asarray(pri, np.float64)
                      ** cfg.prio_exponent).astype(np.float32)
        np.testing.assert_allclose(want_prios,
                                   prios[slot * K:(slot + 1) * K],
                                   rtol=0, atol=2e-5)


def cut_cond_cfg(core, episode):
    """The fast-path pin's configurations.  ``long``: episodes of 13 steps
    over blocks of 8 with a burn-in of 4, so a boundary cut keeps its full
    ``burn_in_steps + 1`` entries.  ``short``: a burn-in of 12 and episodes
    of 11 steps, shorter than ``burn_in_steps + 1``: the one boundary cut
    of an episode finds 9 entries, keeps them all (``keep < keep_max``)
    and the select inside the written window leaves its last rows as
    they were."""
    kw = dict(num_actors=3, buffer_capacity=30 * 8,
              **(dict(anakin_episode_len=13) if episode == "long" else
                 dict(anakin_episode_len=11, burn_in_steps=12)))
    if core == "lstm":
        return anakin_config(**kw)
    from test_xing4_core import TINY_CFGS  # the cores' widths at test size

    return TINY_CFGS[core](actor_transport="anakin", device_replay=True,
                           in_graph_per=True, **kw)


@pytest.mark.parametrize("core,episode", [
    ("lstm", "long"), ("lstm", "short"), ("xing4", "long"),
    ("xing4", "short"),
    # a state with a part kept whole needs burn_in_steps <= block_length
    ("olmo_hybrid", "long")])
def test_anakin_cut_cond_fast_path_bit_exact(core, episode):
    """The r9 lax.cond fast path (skip block emit/retention and the cut
    lanes' stream resets on no-cut steps — the (block_length-1)/
    block_length majority) must be BIT-EXACT vs the always-emit variant
    across a trajectory containing both boundary and episode-end cuts:
    identical final actor state, ring arrays, PER state, and per-step
    traces — for the LSTM's stream of whole states, for the ``xing4``
    core's row stream, which keeps a history in front of its entries, and
    for the ``olmo_hybrid`` core's rows and snapshot slots."""
    cfg = cut_cond_cfg(core, episode)
    net = create_network(cfg, A)
    params = init_params(cfg, net, jax.random.PRNGKey(0))
    env = AnakinFakeEnv(obs_shape=cfg.stored_obs_shape, action_dim=A,
                        episode_len=cfg.anakin_episode_len,
                        num_lanes=cfg.num_actors)
    T = 40
    outs = []
    for cut_cond in (True, False):
        ring = DeviceRing(cfg, A)
        ast = make_anakin_state(cfg, A, env, jax.random.PRNGKey(11))
        meta0 = ring.per_meta()
        carry, tr = make_debug_rollout(cfg, net, env, A, T,
                                       cut_cond=cut_cond)(
            params, ast, ring.snapshot(), ring.take_prios(),
            meta0["seq_meta"], meta0["first"])
        outs.append(jax.device_get((carry, tr)))
    fast, slow = outs
    # the trajectory must actually exercise both cut sites
    assert np.asarray(slow[1]["pending"]).any()
    assert np.asarray(slow[1]["truncated"]).any()
    assert (cfg.anakin_episode_len < cfg.burn_in_steps + 1) \
        == (episode == "short")
    # ... and leave something in the streams to compare
    assert np.asarray(slow[0][0]["buf_hidden"]).any()
    flat_f, tdef_f = jax.tree_util.tree_flatten(fast)
    flat_s, tdef_s = jax.tree_util.tree_flatten(slow)
    assert tdef_f == tdef_s
    for a, b in zip(flat_f, flat_s):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------- host-freedom guarantees

def test_anakin_host_transfers_constant_per_superstep():
    """The hot loop's device→host crossings are ONE result-vector fetch
    per dispatch — the count does not scale with lane count, k, or the
    number of env steps inside the dispatch."""
    from r2d2_tpu.utils.trace import HOST_TRANSFERS, RETRACES

    for kw in (dict(num_actors=2, superstep_k=2,
                    anakin_env_steps_per_update=4),
               dict(num_actors=4, superstep_k=3,
                    anakin_env_steps_per_update=2)):
        cfg = anakin_config(training_steps=10 ** 9, **kw)
        net, plane, learner = build_plane(cfg)
        while not plane.ready:
            plane.rollout_step(learner.state.params)
        warmups = plane.dispatch_no  # 0: rollouts don't consume the stream
        assert warmups == 0
        rollouts = HOST_TRANSFERS.get("anakin.result_fetch")

        before = HOST_TRANSFERS.get("anakin.result_fetch")
        dispatches = 5
        for _ in range(dispatches):
            learner.state, flat = plane.dispatch(learner.state)
            plane.harvest(flat)
        delta = HOST_TRANSFERS.get("anakin.result_fetch") - before
        assert delta == dispatches, (kw, delta)
        assert rollouts > 0  # warm-up fetches were also counted/bounded
        RETRACES.assert_within_budgets()


def test_anakin_loop_arms_transfer_guard_from_config():
    """cfg.transfer_guard=True (the ``--transfer-guard`` CLI knob) arms
    the process guard for the TRAINING phase of run_anakin_loop —
    windows book on the jax-enforced side (they only count while
    armed), the run completes clean, and the guard is disarmed again on
    exit so later code in the process is unaffected."""
    from r2d2_tpu.utils.trace import TRANSFER_GUARD

    cfg = anakin_config(transfer_guard=True, training_steps=8)
    net, plane, learner = build_plane(cfg)
    w0 = TRANSFER_GUARD.snapshot().get("window.anakin.dispatch", 0)
    m = run_anakin_loop(learner, plane)
    assert m["num_updates"] >= 8
    assert not m["dispatch_wedged"]
    assert not TRANSFER_GUARD.armed
    assert TRANSFER_GUARD.snapshot().get("window.anakin.dispatch", 0) > w0


def test_anakin_host_transfers_jax_enforced_when_armed():
    """The armed variant (r19): the same one-fetch-per-dispatch budget,
    but now JAX-enforced — dispatch and harvest run inside
    ``transfer_guard("disallow")`` windows (the plane's own
    TRANSFER_GUARD.disallow sites), so the declared crossings (the
    dispatch-index H2D inside ``anakin.dispatch_put``, the result fetch
    inside ``anakin.result_fetch``) are the ONLY ones that pass.  An
    undeclared implicit transfer sneaking into the hot loop raises
    TransferGuardTripped rather than surviving until a real
    accelerator run.  Armed AFTER warm-up, the production order."""
    from r2d2_tpu.utils.trace import (
        HOST_TRANSFERS,
        RETRACES,
        TRANSFER_GUARD,
    )

    cfg = anakin_config(training_steps=10 ** 9, num_actors=2,
                        superstep_k=2, anakin_env_steps_per_update=4)
    net, plane, learner = build_plane(cfg)
    while not plane.ready:
        plane.rollout_step(learner.state.params)

    fetch0 = HOST_TRANSFERS.get("anakin.result_fetch")
    put0 = HOST_TRANSFERS.get("anakin.dispatch_put")
    dispatches = 5
    with TRANSFER_GUARD.arm():
        for _ in range(dispatches):
            learner.state, flat = plane.dispatch(learner.state)
            plane.harvest(flat)
    # budgets unchanged under enforcement: one D2H fetch and one H2D
    # index put per dispatch, nothing else crossed
    assert HOST_TRANSFERS.get("anakin.result_fetch") - fetch0 \
        == dispatches
    assert HOST_TRANSFERS.get("anakin.dispatch_put") - put0 == dispatches
    snap = TRANSFER_GUARD.snapshot()
    for w in ("anakin.dispatch", "anakin.harvest"):
        assert snap.get(f"trip.{w}", 0) == 0, snap
        assert snap.get(f"window.{w}", 0) >= dispatches, snap
    RETRACES.assert_within_budgets()


# --------------------------------------------------------------- training

def test_anakin_train_fast_plumbing():
    """Unmarked fast e2e: the full train() branch (telemetry, log loop,
    cadences) completes, counters are consistent, guards hold."""
    cfg = anakin_config(training_steps=24, log_interval=0.2,
                        save_interval=10 ** 8, target_net_update_interval=5)
    m = train(cfg, verbose=False, max_wall_seconds=240)
    assert m["num_updates"] >= 24
    assert np.isfinite(m["mean_loss"])
    assert m["buffer_training_steps"] == m["num_updates"]
    assert m["env_steps"] > 0 and m["anakin_frames"] > 0
    assert m["episodes"] > 0
    assert not m["fabric_failed"]
    assert len(m["logs"]) > 0
    last = m["logs"][-1]
    assert last["anakin"]["super_steps"] == m["anakin_super_steps"]
    # the updates that took the sync's copying branch, by the host's count
    for e in m["logs"]:
        assert e["target_syncs"] == e["training_steps"] // 5
        assert e["trace"]["gauge.learner.target_syncs"] == e["target_syncs"]
    from r2d2_tpu.utils.trace import RETRACES

    RETRACES.assert_within_budgets()


@pytest.mark.slow
def test_anakin_trains_and_policy_beats_random():
    """The acceptance run: anakin training reduces loss and the trained
    greedy policy beats a random one on the NUMPY fake env — the
    cross-check that the on-device env taught a policy that transfers to
    the host oracle env."""
    from r2d2_tpu.evaluate import evaluate_params

    cfg = anakin_config(training_steps=2000, superstep_k=4, num_actors=2,
                        anakin_episode_len=32, log_interval=1.0)
    m = train(cfg, verbose=False, max_wall_seconds=600)
    assert m["num_updates"] >= 2000
    losses = np.asarray(m["losses"])
    assert np.isfinite(losses).all()
    assert losses[-100:].mean() < losses[:100].mean(), \
        "loss must decrease over anakin training"

    def env_factory(c, seed):
        return FakeAtariEnv(obs_shape=c.obs_shape, action_dim=A, seed=seed,
                            episode_len=c.anakin_episode_len)

    net = create_network(cfg, A)
    params0 = init_params(cfg, net, jax.random.PRNGKey(3))
    rand_score = evaluate_params(cfg, net, params0, env_factory,
                                 episodes=5, epsilon=1.0, seed=11)
    score = evaluate_params(cfg, net, m["final_params"], env_factory,
                            episodes=5, epsilon=cfg.test_epsilon, seed=11)
    assert score > rand_score, (score, rand_score)
    # mean return improved over the run (telemetry gauge curve)
    rets = [(e["interval_episodes"], e["mean_episode_return"])
            for e in m["logs"] if e["interval_episodes"]]
    assert len(rets) >= 2
    early = rets[0][1]
    late = rets[-1][1]
    assert late > early, (early, late)


@pytest.mark.slow
def test_anakin_grid_trains_and_policy_beats_random():
    """The "fast path for free" acceptance run (ISSUE 15): the gridworld
    env through the UNCHANGED fused program learns a goal-seeking policy
    that decisively beats random on the NUMPY oracle env, and the
    in-graph eval lane's greedy curve (no host env) improves over the
    run."""
    from r2d2_tpu.envs import GridWorldEnv
    from r2d2_tpu.evaluate import evaluate_params

    cfg = anakin_config(training_steps=6000, superstep_k=4, num_actors=4,
                        anakin_episode_len=32, anakin_env="grid",
                        anakin_eval_interval=100, learning_starts=32,
                        gamma=0.95, lr=3e-4, buffer_capacity=320,
                        log_interval=2.0)
    m = train(cfg, verbose=False, max_wall_seconds=600)
    assert m["num_updates"] >= 6000
    assert np.isfinite(np.asarray(m["losses"])).all()

    def env_factory(c, seed):
        return GridWorldEnv(obs_shape=c.obs_shape, action_dim=A, seed=seed,
                            episode_len=c.anakin_episode_len)

    net = create_network(cfg, A)
    params0 = init_params(cfg, net, jax.random.PRNGKey(3))
    rand_score = evaluate_params(cfg, net, params0, env_factory,
                                 episodes=5, epsilon=1.0, seed=11)
    score = evaluate_params(cfg, net, m["final_params"], env_factory,
                            episodes=5, epsilon=cfg.test_epsilon, seed=11)
    assert score > rand_score + 2.0, (score, rand_score)
    # the eval LANE saw the same improvement without any host env
    assert m["eval_episodes"] > 0
    evals = [e["anakin"]["eval_return"] for e in m["logs"]
             if e["anakin"]["eval_episodes"] > 0
             and np.isfinite(e["anakin"]["eval_return"])]
    assert len(evals) >= 3
    assert max(evals[len(evals) // 2:]) > evals[0] + 2.0, evals


# --------------------------------------------------------------- recovery

def test_anakin_snapshot_resume_bit_exact(tmp_path):
    """The gold-standard recovery property the fused design makes
    possible: the ENTIRE training loop is deterministic device state, so
    snapshot → restore → continue reproduces an uninterrupted run
    BIT-EXACTLY (params, opt state, ring bytes, PER leaves, env phase,
    RNG streams, LSTM carry)."""
    cfg = anakin_config(training_steps=10 ** 9)

    def drive(learner, plane, dispatches):
        while not plane.ready:
            plane.rollout_step(learner.state.params)
        for _ in range(dispatches):
            learner.state, flat = plane.dispatch(learner.state)
            plane.harvest(flat)

    # uninterrupted: 4 super-steps
    net, plane_a, learner_a = build_plane(cfg)
    drive(learner_a, plane_a, 4)

    # interrupted: 2 super-steps, full-state snapshot, fresh objects,
    # restore, 2 more
    net, plane_b, learner_b = build_plane(cfg)
    drive(learner_b, plane_b, 2)
    path = os.path.join(tmp_path, "anakin.bin")
    meta = plane_b.write_state(path)
    saved_learner = jax.device_get(learner_b.state)

    net, plane_c, learner_c = build_plane(cfg)
    plane_c.read_state(path, meta)
    learner_c.state = jax.device_put(saved_learner)
    assert plane_c.dispatch_no == plane_b.dispatch_no
    assert plane_c.env_steps == plane_b.env_steps
    drive(learner_c, plane_c, 2)

    for a, b in zip(jax.tree.leaves(jax.device_get(learner_a.state)),
                    jax.tree.leaves(jax.device_get(learner_c.state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the on-device loop state converged to the same bytes too
    snap_a = plane_a._payload()
    snap_c = plane_c._payload()
    assert sorted(snap_a) == sorted(snap_c)
    for k in snap_a:
        np.testing.assert_array_equal(snap_a[k], snap_c[k], err_msg=k)


def test_anakin_snapshot_rejects_geometry_mismatch(tmp_path):
    cfg = anakin_config()
    net, plane, learner = build_plane(cfg)
    while not plane.ready:
        plane.rollout_step(learner.state.params)
    path = os.path.join(tmp_path, "anakin.bin")
    meta = plane.write_state(path)

    cfg2 = anakin_config(num_actors=4)
    _, plane2, _ = build_plane(cfg2)
    with pytest.raises(ValueError, match="layout mismatch"):
        plane2.read_state(path, meta)
    with pytest.raises(ValueError, match="not an anakin"):
        plane2.read_state(path, dict(meta, kind="replay"))


@pytest.mark.slow
def test_anakin_sigterm_resume_end_to_end(tmp_path):
    """SIGTERM a live anakin run mid-stream; --resume continues the loop
    state (ring fill, env phase/RNGs, counters) warm instead of cold-
    restarting — the ISSUE 6 acceptance path."""
    ck_dir = str(tmp_path / "ck")
    cfg = anakin_config(training_steps=10 ** 8, log_interval=0.2,
                        save_interval=10 ** 8)

    def sink(entry):
        if entry["training_steps"] >= 8:
            os.kill(os.getpid(), signal.SIGTERM)

    m = train(cfg, checkpoint_dir=ck_dir, verbose=False, log_sink=sink,
              max_wall_seconds=240)
    assert 0 < m["num_updates"] < 10 ** 8
    assert not m["fabric_failed"]

    from r2d2_tpu.checkpoint import Checkpointer

    ck = Checkpointer(ck_dir)
    assert ck.latest_step() is not None
    assert ck.replay_steps(), "no anakin full-state snapshot landed"
    meta, _, _ = ck.restore_replay()
    assert meta["kind"] == "anakin"
    assert meta["counters"]["env_steps"] == m["env_steps"] > 0
    assert meta["counters"]["fill"] == m["buffer_size"] > 0

    m2 = train(cfg.replace(training_steps=m["num_updates"]
                           + 2 * cfg.superstep_k),
               checkpoint_dir=ck_dir, resume=True, verbose=False,
               max_wall_seconds=240)
    assert m2["restored_replay"], "resume must restore the anakin loop"
    assert m2["num_updates"] >= m["num_updates"] + 2 * cfg.superstep_k
    # warm continuation: no cold refill — env_steps/episodes CONTINUE
    assert m2["env_steps"] > m["env_steps"]
    assert np.isfinite(m2["mean_loss"])


# ------------------------------------------------------------------- misc

def test_anakin_config_validation():
    with pytest.raises(ValueError, match="anakin_episode_len"):
        anakin_config(anakin_episode_len=100, max_episode_steps=50)
    with pytest.raises(ValueError, match="anakin_env_steps_per_update"):
        anakin_config(anakin_env_steps_per_update=0)
    with pytest.raises(ValueError, match="actor_transport"):
        Config(actor_transport="anakim")
    # serve inference composes only with process transport
    with pytest.raises(ValueError, match="serve"):
        anakin_config(actor_inference="serve")
    # the masked ring scatter needs a slot per lane in the worst case
    cfg = anakin_config(num_actors=4, buffer_capacity=16, block_length=8,
                        learning_starts=8)
    net = create_network(cfg, A)
    with pytest.raises(ValueError, match="num_blocks"):
        AnakinPlane(cfg, net, A, DeviceRing(cfg, A))


def test_cli_accepts_anakin_transport():
    from r2d2_tpu.cli import build_config

    import argparse

    ns = argparse.Namespace(preset="test", game="Fake", actors=2,
                            actor_transport="anakin", actor_inference=None,
                            training_steps=8, seed=0, overrides=[])
    cfg = build_config(ns)
    assert cfg.actor_transport == "anakin"
