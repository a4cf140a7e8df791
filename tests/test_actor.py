"""Actor / AgentState / env integration tests against the fake env."""
import time

import jax
import numpy as np
import pytest

from r2d2_tpu.actor import Actor, AgentState, VectorActor, make_act_fn
from r2d2_tpu.config import test_config as make_test_config
from r2d2_tpu.envs import FakeAtariEnv, create_env
from r2d2_tpu.models.network import create_network, init_params
from r2d2_tpu.utils.math import epsilon_ladder
from r2d2_tpu.utils.store import ParamStore

A = 4


def build(cfg):
    net = create_network(cfg, A)
    params = init_params(cfg, net, jax.random.PRNGKey(0))
    store = ParamStore(params)
    return net, params, store, make_act_fn(cfg, net)


def make_env(cfg, seed=0):
    return FakeAtariEnv(obs_shape=cfg.obs_shape, action_dim=A, seed=seed,
                        episode_len=20)


def test_epsilon_ladder_endpoints():
    # reference train.py:15-17: i=0 → 0.4; i=N-1 → 0.4^(1+alpha)
    assert epsilon_ladder(0, 8) == pytest.approx(0.4)
    assert epsilon_ladder(7, 8) == pytest.approx(0.4 ** 8)
    assert epsilon_ladder(0, 1) == pytest.approx(0.4)
    eps = [epsilon_ladder(i, 8) for i in range(8)]
    assert all(a > b for a, b in zip(eps, eps[1:]))  # strictly decreasing


def test_agent_state_carrier():
    cfg = make_test_config()
    st = AgentState.initial(cfg, np.ones(cfg.obs_shape, np.uint8), A)
    assert st.last_reward == 0.0 and st.last_action.sum() == 0.0
    hidden = np.full((2, cfg.lstm_layers, cfg.hidden_dim), 0.5, np.float32)
    st.update(np.zeros(cfg.obs_shape, np.uint8), action=2, reward=1.5,
              hidden=hidden)
    assert st.last_action[2] == 1.0 and st.last_action.sum() == 1.0
    assert st.last_reward == 1.5
    np.testing.assert_array_equal(st.hidden, hidden)


@pytest.mark.slow
def test_actor_produces_wellformed_blocks():
    cfg = make_test_config(game_name="Fake")
    net, params, store, act_fn = build(cfg)
    out = []
    env = make_env(cfg)
    actor = Actor(cfg, env, epsilon=0.3, act_fn=act_fn, param_store=store,
                  sink=lambda b, p, r: out.append((b, p, r)),
                  rng=np.random.default_rng(0))
    actor.run(max_steps=100)

    assert len(out) >= 5
    episode_rewards = [r for _, _, r in out if r is not None]
    assert episode_rewards, "terminal blocks must report episode reward"
    total_steps = 0
    for blk, prios, _ in out:
        k = blk.num_sequences
        assert blk.forward_steps[k - 1] == 1  # worker.py:474 invariant
        assert blk.action.shape[0] == blk.learning_steps.sum()
        assert blk.obs.shape[0] == blk.burn_in_steps[0] + blk.action.shape[0] + 1
        assert prios.shape == (cfg.seqs_per_block,)
        assert (prios[:k] > 0).all() and (prios[k:] == 0).all()
        total_steps += int(blk.learning_steps.sum())
    # every env step lands in exactly one block (episode_len 20 divides
    # evenly into finished episodes; trailing unfinished steps stay local)
    assert total_steps <= 100 and total_steps >= 80


def test_actor_block_carryover_continuity():
    """Blocks cut at block_length within one episode must chain: next block's
    obs stream starts with the previous block's trailing burn_in+1 obs."""
    cfg = make_test_config(game_name="Fake")
    net, params, store, act_fn = build(cfg)
    out = []
    env = FakeAtariEnv(obs_shape=cfg.obs_shape, action_dim=A, seed=0,
                       episode_len=500)  # long episode → many block cuts
    actor = Actor(cfg, env, epsilon=0.5, act_fn=act_fn, param_store=store,
                  sink=lambda b, p, r: out.append(b),
                  rng=np.random.default_rng(1))
    actor.run(max_steps=30)  # block_length=8 → ~3 cuts

    assert len(out) >= 2
    for prev, nxt in zip(out, out[1:]):
        keep = cfg.burn_in_steps + 1
        np.testing.assert_array_equal(nxt.obs[:keep], prev.obs[-keep:])
        assert nxt.burn_in_steps[0] == min(cfg.burn_in_steps,
                                           prev.obs.shape[0] - 1)


def test_vector_actor_lanes_and_weight_refresh():
    cfg = make_test_config(game_name="Fake", actor_update_interval=10)
    net, params, store, act_fn = build(cfg)
    envs = [make_env(cfg, seed=i) for i in range(3)]
    out = []
    actor = VectorActor(cfg, envs, [0.9, 0.5, 0.1], act_fn, store,
                        sink=lambda b, p, r: out.append(b),
                        rng=np.random.default_rng(2))
    actor.run(max_steps=25)
    v0 = actor._param_version
    assert v0 == 1
    # publish new params; actor picks them up at the next refresh cadence
    store.publish(jax.tree.map(lambda x: x + 0.0, params))
    actor.run(max_steps=10)
    assert actor._param_version == 2
    assert len(out) >= 3  # all lanes produced blocks (episode_len 20 < 35)


def test_create_env_fake_fallback():
    cfg = make_test_config(game_name="Fake")
    env = create_env(cfg, seed=3)
    assert isinstance(env, FakeAtariEnv)
    obs, _ = env.reset()
    assert obs.shape == cfg.obs_shape and obs.dtype == np.uint8
    obs2, r, term, trunc, _ = env.step(0)
    assert obs2.shape == cfg.obs_shape
    # deterministic by seed
    env_b = create_env(cfg, seed=3)
    obs_b, _ = env_b.reset()
    np.testing.assert_array_equal(obs, obs_b)


def _block_key(blk):
    """Canonical content key for comparing block multisets across runs."""
    return (blk.obs.tobytes(), blk.action.tobytes(),
            blk.n_step_reward.tobytes(), blk.hidden.tobytes(),
            blk.burn_in_steps.tobytes(), blk.learning_steps.tobytes())


def test_parallel_env_stepping_matches_serial():
    """env_workers>1 must produce exactly the serial trajectories: lane
    state, RNG draws, and block contents are identical; only sink arrival
    order may differ."""
    def run(workers):
        cfg = make_test_config(game_name="Fake")
        net, params, store, act_fn = build(cfg)
        envs = [FakeAtariEnv(obs_shape=cfg.obs_shape, action_dim=A, seed=i,
                             episode_len=13) for i in range(6)]
        out = []
        actor = VectorActor(cfg, envs, [0.8, 0.5, 0.3, 0.2, 0.1, 0.05],
                            act_fn, store,
                            sink=lambda b, p, r: out.append((b, p, r)),
                            rng=np.random.default_rng(7),
                            env_workers=workers)
        actor.run(max_steps=60)
        actor.close()
        return actor, out

    a_ser, out_ser = run(0)
    a_par, out_par = run(4)

    np.testing.assert_array_equal(a_ser.obs, a_par.obs)
    np.testing.assert_array_equal(a_ser.hidden, a_par.hidden)
    np.testing.assert_array_equal(a_ser.episode_steps, a_par.episode_steps)
    assert len(out_ser) == len(out_par)
    assert (sorted(_block_key(b) for b, _, _ in out_ser)
            == sorted(_block_key(b) for b, _, _ in out_par))
    rewards = lambda out: sorted(r for _, _, r in out if r is not None)
    assert rewards(out_ser) == rewards(out_par)


def test_vector_actor_256_lanes_lifecycle():
    """Preset-scale fleet (atari57/hard-exploration num_actors=256):
    resets, block cuts, and the episode cap must all fire correctly with
    pooled env stepping."""
    cfg = make_test_config(game_name="Fake", max_episode_steps=11)
    net, params, store, act_fn = build(cfg)
    N = 256
    # mixed episode lengths: some terminate (len 9 < cap), some hit the
    # 11-step cap (len 50), all cut blocks at block_length=8
    envs = [FakeAtariEnv(obs_shape=cfg.obs_shape, action_dim=A, seed=i,
                         episode_len=(9 if i % 2 else 50))
            for i in range(N)]
    from r2d2_tpu.utils.math import epsilon_ladder
    eps = [epsilon_ladder(i, N) for i in range(N)]
    out = []
    actor = VectorActor(cfg, envs, eps, act_fn, store,
                        sink=lambda b, p, r: out.append((b, p, r)),
                        rng=np.random.default_rng(3), env_workers=8)
    actor.run(max_steps=30)
    actor.close()

    assert actor.actor_steps == 30
    # every lane kept stepping: after 30 steps each lane's episode counter
    # is within [0, cap]
    assert (actor.episode_steps >= 0).all()
    assert (actor.episode_steps <= cfg.max_episode_steps).all()
    # terminating lanes (odd) produced episode rewards; capped lanes (even)
    # produced capped blocks with bootstrap (reward None)
    rewards = [r for _, _, r in out if r is not None]
    assert len(rewards) >= N // 2  # each odd lane terminated >= once
    assert len(out) > N  # block cuts + terminals across the fleet
    for blk, prios, _ in out:
        k = blk.num_sequences
        assert blk.forward_steps[k - 1] == 1
        assert blk.action.shape[0] == blk.learning_steps.sum()


def test_act_fn_cpu_f32_twin_matches_bf16_net():
    """With a bf16 compute dtype and CPU inference, make_act_fn builds a
    float32 twin (bf16 matmuls are emulated on CPU).  The twin shares the
    (float32) param pytree and must agree with the bf16 network's act
    output to bf16 tolerance — the actor's policy is unchanged."""
    from r2d2_tpu.models.network import R2D2Network

    cfg = make_test_config(compute_dtype="bfloat16")
    net_bf16 = create_network(cfg, A)
    params = init_params(cfg, net_bf16, jax.random.PRNGKey(9))
    act = make_act_fn(cfg, net_bf16)  # CPU platform -> f32 twin

    rng = np.random.default_rng(4)
    B = 5
    obs = rng.integers(0, 256, (B, *cfg.stored_obs_shape), dtype=np.uint8)
    la = np.zeros((B, A), np.float32)
    la[np.arange(B), rng.integers(A, size=B)] = 1.0
    lr = rng.normal(size=B).astype(np.float32)
    hid = rng.normal(size=(B, 2, cfg.lstm_layers,
                           cfg.hidden_dim)).astype(np.float32) * 0.1

    q_twin, h_twin = act(params, obs, la, lr, hid)
    q_ref, h_ref = net_bf16.apply(params, obs, la, lr, hid,
                                  method=R2D2Network.act)
    np.testing.assert_allclose(np.asarray(q_twin), np.asarray(q_ref),
                               rtol=0.05, atol=0.05)
    np.testing.assert_allclose(np.asarray(h_twin), np.asarray(h_ref),
                               rtol=0.05, atol=0.05)


def test_seed_first_reset_wrapper():
    """SeedFirstReset threads the lane seed into only the FIRST reset:
    two wrappers with the same seed produce identical first episodes
    (reproducibility), and later resets pass no seed (no episode replay)."""
    from r2d2_tpu.envs.atari import SeedFirstReset

    cfg = make_test_config()

    def rollout_obs(env):
        obs, _ = env.reset()
        return [obs] + [env.step(1)[0] for _ in range(3)]

    a = SeedFirstReset(make_env(cfg, seed=0), seed=123)
    b = SeedFirstReset(make_env(cfg, seed=1), seed=123)
    for oa, ob in zip(rollout_obs(a), rollout_obs(b)):
        np.testing.assert_array_equal(oa, ob)

    # second reset: no seed forwarded — FakeAtariEnv would otherwise be
    # re-seeded to the identical episode, which reset() randomizes away
    first = a.reset()[0]
    phases = {a.reset()[0].tobytes() for _ in range(8)} | {first.tobytes()}
    assert len(phases) > 1  # episodes vary after the seeded first reset
    # delegation still works
    assert a.action_space.n == 4


def test_make_act_fn_resolves_net_for_the_given_device():
    """One decision, one place: the act net is resolved FOR the device the
    act will run on, and the returned callable carries that decision
    (callers commit params to ``.device``; runs report ``.lstm_impl`` /
    ``.compute_dtype``)."""
    class FakeTpu:
        platform = "tpu"

    cfg = make_test_config(compute_dtype="bfloat16", lstm_impl="pallas")
    net = create_network(cfg, A)
    on_tpu = make_act_fn(cfg, net, device=FakeTpu())
    assert (on_tpu.lstm_impl, on_tpu.compute_dtype) == ("pallas", "bfloat16")
    cpu = jax.local_devices()[0]
    on_cpu = make_act_fn(cfg, net, device=cpu)
    assert on_cpu.device == cpu
    assert (on_cpu.lstm_impl, on_cpu.compute_dtype) == ("scan", "float32")
    # default: cfg.act_device through resolve_act_device
    assert make_act_fn(cfg, net).device == cpu


def test_resolve_act_device_refuses_a_missing_cpu_backend(monkeypatch):
    """ISSUE 21 finding 7: "auto"/"cpu" on an accelerator process whose
    JAX_PLATFORMS leaves the CPU backend out used to mean "leave placement
    alone" — thread-actor inference silently moved onto the chip.  It is
    an error that says so; "default" still names the first device."""
    from r2d2_tpu.actor import resolve_act_device

    class FakeTpu:
        platform = "tpu"

    tpu = FakeTpu()

    def local_devices(backend=None):
        if backend == "cpu":
            raise RuntimeError("Unknown backend cpu")
        return [tpu]

    monkeypatch.setattr(jax, "local_devices", local_devices)
    assert resolve_act_device("default") is tpu
    for spec in ("auto", "cpu"):
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS"):
            resolve_act_device(spec)


@pytest.mark.parametrize("env_workers", [0, 2])
def test_vector_actor_spans_split_the_lockstep_step(env_workers):
    """Given a Tracer, a VectorActor records one actor.act, actor.env_step
    and actor.record a lockstep step and one actor.cut a finished block
    (pending, done and capped lanes alike), serial or pooled; the cut
    spans cover the sink calls."""
    from _span_log import SpanLog
    from r2d2_tpu.utils.trace import Tracer

    cfg = make_test_config(game_name="Fake", max_episode_steps=12)
    net, params, store, act_fn = build(cfg)
    log = SpanLog()
    sunk = []
    actor = VectorActor(cfg, [make_env(cfg, seed=i) for i in range(3)],
                        [0.9, 0.5, 0.1], act_fn, store,
                        sink=lambda b, p, r: sunk.append(time.perf_counter()),
                        rng=np.random.default_rng(2),
                        env_workers=env_workers,
                        tracer=Tracer(events=log))
    steps = 30      # block_length 8, episode cap 12: all three kinds of cut
    actor.run(max_steps=steps)
    actor.close()
    for name in ("actor.act", "actor.env_step", "actor.record"):
        assert log.count(name) == steps, name
    assert len(sunk) > 6 and log.count("actor.cut") == len(sunk)
    cuts = log.spans["actor.cut"]
    assert all(any(t0 <= t <= t0 + dt for t0, dt in cuts) for t in sunk)
    # the parts of a step lie one after another inside the run
    for a, e, r in zip(log.spans["actor.act"], log.spans["actor.env_step"],
                       log.spans["actor.record"]):
        assert a[0] + a[1] <= e[0] and e[0] + e[1] <= r[0]


def test_vector_actor_without_a_tracer_makes_the_same_blocks():
    """The spans change nothing: with and without a tracer the same seeds
    give the same blocks."""
    from r2d2_tpu.utils.trace import Tracer

    cfg = make_test_config(game_name="Fake")
    net, params, store, act_fn = build(cfg)

    def blocks(tracer):
        out = []
        actor = VectorActor(cfg, [make_env(cfg, seed=i) for i in range(2)],
                            [0.4, 0.1], act_fn, store,
                            sink=lambda b, p, r: out.append((b, p)),
                            rng=np.random.default_rng(3), tracer=tracer)
        assert actor.tracer is tracer
        actor.run(max_steps=40)
        return out

    plain, traced = blocks(None), blocks(Tracer(events=None))
    assert len(plain) == len(traced) > 0
    for (b0, p0), (b1, p1) in zip(plain, traced):
        np.testing.assert_array_equal(b0.obs, b1.obs)
        np.testing.assert_array_equal(b0.action, b1.action)
        np.testing.assert_array_equal(p0, p1)
