"""Offline evaluation: checkpoint sweep → learning curve.

Capability-parity with the reference evaluator (test.py:14-88): walk
checkpoints in save order, run ``eval_episodes`` rollouts at
``test_epsilon`` per checkpoint, report env frames (env_steps ×
frameskip — test.py:36), wall-clock time, and mean reward; optionally plot
the reward-vs-frames / reward-vs-time curves.

TPU-first redesign: the reference forks an ``mp.Pool`` of 5 CPU rollout
workers (test.py:18,33); here the episodes run **in lockstep as one
batched jitted act** (the same inference-server pattern as the actor
fleet), so evaluation uses one device efficiently instead of 5 forked
torch processes.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from r2d2_tpu.actor import make_act_fn
from r2d2_tpu.checkpoint import Checkpointer
from r2d2_tpu.config import Config
from r2d2_tpu.models.network import R2D2Network, create_network
from r2d2_tpu.models.state import zero_state


def run_episodes(cfg: Config, net: R2D2Network, params: Any,
                 envs: List[Any], epsilon: Optional[float] = None,
                 rng: Optional[np.random.Generator] = None,
                 act_fn=None) -> List[float]:
    """One episode per env, stepped in lockstep with batched inference
    (the batched analogue of test.py:60-81).  Returns per-env returns."""
    epsilon = cfg.test_epsilon if epsilon is None else epsilon
    rng = rng or np.random.default_rng(cfg.seed)
    act_fn = act_fn or make_act_fn(cfg, net)
    # commit the params where the act fn was resolved to run (host trees
    # from a checkpoint restore and a learner's device trees alike)
    params = jax.device_put(params, act_fn.device)
    N = len(envs)
    action_dim = envs[0].action_space.n

    obs = np.zeros((N, *cfg.stored_obs_shape), np.uint8)
    last_action = np.zeros((N, action_dim), np.float32)
    last_reward = np.zeros(N, np.float32)
    hidden = zero_state(cfg, N)
    for i, env in enumerate(envs):
        o, _ = env.reset()
        obs[i] = np.asarray(o, np.uint8)

    returns = np.zeros(N, np.float64)
    done = np.zeros(N, bool)
    steps = 0
    while not done.all() and steps < cfg.max_episode_steps:
        q, new_hidden = act_fn(params, obs, last_action, last_reward, hidden)
        q = np.asarray(q)
        new_hidden = np.asarray(new_hidden)
        explore = rng.random(N) < epsilon
        actions = np.where(explore, rng.integers(action_dim, size=N),
                           q.argmax(axis=1))
        for i, env in enumerate(envs):
            if done[i]:
                continue
            a = int(actions[i])
            o, r, terminated, truncated, _ = env.step(a)
            obs[i] = np.asarray(o, np.uint8)
            last_action[i] = 0.0
            last_action[i, a] = 1.0
            last_reward[i] = r
            hidden[i] = new_hidden[i]
            returns[i] += r
            done[i] = bool(terminated or truncated)
        steps += 1
    return [float(x) for x in returns]


def evaluate_params(cfg: Config, net: R2D2Network, params: Any,
                    env_factory: Callable[[Config, int], Any],
                    episodes: Optional[int] = None,
                    epsilon: Optional[float] = None,
                    seed: int = 0, act_fn=None) -> float:
    """Mean return over ``episodes`` rollouts (test.py:33,38 semantics)."""
    episodes = episodes or cfg.eval_episodes
    envs = [env_factory(cfg, seed + i) for i in range(episodes)]
    returns = run_episodes(cfg, net, params, envs, epsilon=epsilon,
                           rng=np.random.default_rng(seed), act_fn=act_fn)
    return float(np.mean(returns))


def evaluate_sweep(cfg: Config,
                   checkpoint_dir: str,
                   env_factory: Callable[[Config, int], Any],
                   episodes: Optional[int] = None,
                   out_json: Optional[str] = None,
                   out_plot: Optional[str] = None,
                   action_dim: Optional[int] = None,
                   follow: bool = False,
                   follow_timeout: Any = "default",
                   poll_interval: float = 2.0,
                   stop: Optional[Callable[[], bool]] = None
                   ) -> List[Dict[str, float]]:
    """Walk every checkpoint in save order (test.py:26-40) and produce the
    learning curve: one record per checkpoint with training step, env
    frames (env_steps × frameskip), wall-clock minutes, mean reward.

    With ``follow=True`` the sweep trails a concurrent training run the way
    the reference evaluator does (test.py:26-27's poll-the-next-file walk):
    after draining the checkpoints already on disk it keeps polling for new
    ones, evaluating each as it appears, and exits when ``stop()`` reports
    training finished (with one final drain) or when no new checkpoint has
    appeared for ``follow_timeout`` seconds.  The timeout defaults to 600
    when no ``stop`` callback is given (a bare follow call must not poll
    forever) and to ``None`` — poll until ``stop()`` — when one is: a
    live training run with a slow checkpoint cadence must not be cut
    short.  ``out_json`` is rewritten
    after every record in follow mode so the curve file trails the run too.
    A step is only picked up once its metadata sidecar exists — process 0
    writes that after the orbax save, so its presence marks a finished save.
    """
    if follow_timeout == "default":
        follow_timeout = 600.0 if stop is None else None
    ckpt = Checkpointer(checkpoint_dir)
    if action_dim is None:
        action_dim = env_factory(cfg, 0).action_space.n
    net = create_network(cfg, action_dim)
    act_fn = make_act_fn(cfg, net)

    def _eval_step(step: int) -> Dict[str, float]:
        from r2d2_tpu.checkpoint import check_arch_compat

        check_arch_compat(cfg, ckpt.peek_meta(step))
        raw, meta = ckpt.restore(None, step=step)
        params = raw["params"]  # the flax variables dict of the online net
        mean_reward = evaluate_params(cfg, net, params, env_factory,
                                      episodes=episodes, seed=cfg.seed,
                                      act_fn=act_fn)
        return dict(
            step=step,
            env_frames=int(meta.get("env_steps", 0)) * cfg.frameskip,
            minutes=float(meta.get("minutes", 0.0)),
            mean_reward=mean_reward,
        )

    def _write(curve: List[Dict[str, float]]) -> None:
        if out_json:
            # atomic replace: follow mode invites concurrent readers, who
            # must never observe a truncated file mid-rewrite
            tmp = f"{out_json}.tmp{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(curve, f, indent=2)
            os.replace(tmp, out_json)

    curve: List[Dict[str, float]] = []
    seen: set = set()
    last_new = time.monotonic()
    while True:
        stopping = stop() if (follow and stop is not None) else False
        fresh = [s for s in ckpt.steps() if s not in seen]
        if follow:
            # gate on the sidecar: a step dir may be visible mid-save
            fresh = [s for s in fresh if ckpt.has_meta(s)]
        for step in fresh:
            seen.add(step)
            curve.append(_eval_step(step))
            if follow:
                _write(curve)
        if fresh:
            last_new = time.monotonic()
        if not follow:
            break
        if stopping and not fresh:
            break  # training done and the final drain found nothing new
        if (follow_timeout is not None and not fresh
                and time.monotonic() - last_new > follow_timeout):
            break
        if not fresh:
            time.sleep(poll_interval)

    _write(curve)
    if out_plot:
        _plot_curve(cfg, curve, out_plot)
    return curve


def _plot_curve(cfg: Config, curve: List[Dict[str, float]],
                path: str) -> None:
    """Reward-vs-frames and reward-vs-hours dual plot (test.py:42-58).
    Matplotlib is optional in this image; silently skips if missing."""
    try:
        import matplotlib
        matplotlib.use("Agg")
        import matplotlib.pyplot as plt
    except ImportError:
        return
    frames = [c["env_frames"] for c in curve]
    hours = [c["minutes"] / 60.0 for c in curve]
    rewards = [c["mean_reward"] for c in curve]
    fig, axes = plt.subplots(1, 2, figsize=(12, 6))
    fig.suptitle(cfg.game_name)
    axes[0].plot(frames, rewards)
    axes[0].set_xlabel("environment frames")
    axes[0].set_ylabel("average reward")
    axes[1].plot(hours, rewards)
    axes[1].set_xlabel("wall-clock hours")
    axes[1].set_ylabel("average reward")
    fig.savefig(path)
    plt.close(fig)
