"""The host environment the fabric traffic drives: Atari-shaped, seeded,
and counted.

The dynamics are those of the program's ``envs/fake.FakeAtariEnv`` (a hidden
phase shown as a bright band; the rewarded action is the phase) — copied
here so that the traffic the benchmark generates cannot change under it.
Two things are the benchmark's own:

- episode lengths come from the traffic file, not the fake env's 32-step
  test size: an Atari episode runs to thousands of steps, so a lane fills
  whole ``block_length`` blocks.  Every lane's *first* episode is shorter,
  by an offset spread evenly over one block length and dealt to the lanes
  by the seed, so the lanes cut their blocks one after another (as lanes
  with real, unequal episodes do in steady state) and not all in one burst;
- every env counts its steps, so env frames are read at the same two
  instants as the updates.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np


class _Box:
    def __init__(self, shape, dtype):
        self.shape, self.dtype = shape, dtype


class _Discrete:
    def __init__(self, n: int):
        self.n = n


class TrafficEnv:
    ACTIONS = 4

    def __init__(self, obs_shape: Tuple[int, ...], episode_len: int,
                 first_episode_len: int, seed):
        self.observation_space = _Box(tuple(obs_shape), np.uint8)
        self.action_space = _Discrete(self.ACTIONS)
        self._rng = np.random.default_rng(seed)
        self._episode_len = episode_len
        self._limit = first_episode_len
        self._phase = self._t = 0
        self.steps = 0          # lifetime env steps (read by the harness)

    def _obs(self) -> np.ndarray:
        shape = self.observation_space.shape
        obs = np.zeros(shape, np.uint8)
        rows = max(1, shape[0] // self.ACTIONS)
        r0 = (self._phase % self.ACTIONS) * rows
        obs[r0:r0 + rows] = 255
        return obs

    def reset(self, *, seed: Optional[int] = None, **kwargs):
        if seed is not None:
            self._rng = np.random.default_rng(seed)
        if self._t:             # every episode after the first
            self._limit = self._episode_len
        self._phase = int(self._rng.integers(self.ACTIONS))
        self._t = 0
        return self._obs(), {}

    def step(self, action: int):
        reward = 1.0 if int(action) == self._phase % self.ACTIONS else 0.0
        self._phase += 1
        self._t += 1
        self.steps += 1
        truncated = self._t >= self._limit
        if truncated:
            reward += 2.0
        return self._obs(), reward, False, truncated, {}

    def close(self) -> None:
        pass


class EnvFleet:
    """Factory handed to ``train(env_factory=...)``; keeps the envs it made
    so the harness can total their step counters."""

    def __init__(self, run_seed: int, lanes: int, block_length: int,
                 episode_len: int):
        self._run_seed, self._lanes = run_seed, lanes
        self._episode_len = episode_len
        # the same set of offsets for every seed, in another order
        order = np.random.default_rng(run_seed).permutation(lanes)
        self._first = [1 + int(order[i]) * block_length // lanes
                       for i in range(lanes)]
        self.envs: List[TrafficEnv] = []

    def __call__(self, cfg, seed: int) -> TrafficEnv:
        """``train()`` asks for lane i's env with ``cfg.seed + i``; the
        env's own stream comes from the run's seed and the lane."""
        lane = (seed - cfg.seed) % self._lanes
        env = TrafficEnv(cfg.stored_obs_shape, self._episode_len,
                         self._first[lane], (self._run_seed, lane))
        self.envs.append(env)
        return env

    def total_steps(self) -> int:
        return sum(e.steps for e in self.envs)
