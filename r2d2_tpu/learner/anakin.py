"""Anakin-mode fused on-device training loop (``actor_transport="anakin"``).

The Podracer architectures paper (PAPERS.md) observes that when the
environment itself is jittable, the actor/replay/learner split collapses:
env-step → act → block-cut → replay-write → train-step become ONE compiled
program, and the host's only jobs are dispatching it and reading a few
scalars back.  This module is that program for the R2D2 stack:

- the env is a pure-JAX four-method env (``cfg.anakin_env`` →
  :func:`~r2d2_tpu.envs.anakin.make_anakin_env`: the vmapped
  FakeAtariEnv twin or the gridworld — any env on that surface inherits
  this whole fast path);
- the actor is an in-graph twin of :class:`~r2d2_tpu.actor.VectorActor`'s
  hot loop — per-lane ladder epsilons, LSTM carry, deferred block-boundary
  cuts with bootstrap Q, episode lifecycle — over a device-resident twin
  of :class:`~r2d2_tpu.replay.block.VectorLocalBuffer`;
- block cutting reproduces :func:`~r2d2_tpu.replay.block.assemble_block`'s
  math (window sizes, stored-hidden selection, n-step targets, actor-side
  initial priorities) as masked static-shape jnp ops, and writes finished
  blocks straight into the existing device ring
  (:class:`~r2d2_tpu.replay.device_ring.DeviceRing` arrays + its
  ``in_graph_per`` leaf/metadata state) via donated masked scatters — a
  lane that did not cut this step scatters to the out-of-bounds sentinel
  slot and is dropped (``mode="drop"``), so the write is one fixed-shape
  op regardless of how many lanes cut;
- training is the unchanged :func:`~r2d2_tpu.learner.step.make_train_step`
  fed by the unchanged in-graph PER sampler
  (:func:`~r2d2_tpu.learner.step._in_graph_sample` + ``gather_batch``).

Each dispatch of the fused super-step runs ``k × (E env/actor steps + 1
optimizer step)`` under ``jax.lax.scan`` (E =
``cfg.anakin_env_steps_per_update``), crossing the host boundary exactly
twice: one uint32 dispatch counter up, one small flat float vector
(k losses + counter deltas, then the eval pair / learnhealth rows when
armed) down.  Both crossings are ticked on
``HOST_TRANSFERS`` and the e2e tests pin them to a constant per dispatch,
independent of lane count, batch size and k — the "zero host crossings"
acceptance gate of ROADMAP open item 2.

Numerical parity with the host block cutter (pinned by
tests/test_anakin.py): integer fields, observation bytes, gamma tails
(host-precomputed float32 power tables, so XLA's ``pow`` never enters)
and stored hiddens are bit-exact vs :class:`LocalBuffer`; n-step returns
and priorities match to float32 round-off (the host accumulates those in
float64, which CPU-jax cannot reproduce without x64 mode — the divergence
is ≤ a few f32 ulps and covered by tolerance assertions).

What a lane keeps of its recurrent states is the model's to say
(models/state.stream_spec): one entry a step of the part that is a window
of rows (``buf_hidden``), and — where a state has a part that is no such
window, as a delta-rule matrix is not — whole snapshots of that part at the
only steps a stored sequence can start (``buf_snapshot``,
:func:`_snapshot_slots`), never a whole state a step.

Unlike the host ring writer, block slots keep whatever bytes the lane's
stream buffer held past the used window instead of zero-padding: the
sampling clamp invariant (replay_buffer.py) already guarantees those
positions are loss-masked, and skipping the zero-fill keeps the write a
pure scatter.
"""
from __future__ import annotations

import logging
import threading
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from r2d2_tpu.config import Config
from r2d2_tpu.envs.anakin import make_anakin_env
from r2d2_tpu.learner.step import (
    TrainState,
    _in_graph_sample,
    _loss_net,
    make_train_step,
)
from r2d2_tpu.models.network import (
    R2D2Network,
    counter_names,
    read_counters,
    zero_hidden,
)
from r2d2_tpu.models.state import (
    lanes_like,
    reset_stream,
    stream_entry,
    stream_snapshot,
    stream_spec,
    stream_states,
)
from r2d2_tpu.replay.device_ring import gather_batch, ring_slots
from r2d2_tpu.utils.math import epsilon_ladder
from r2d2_tpu.utils.resilience import Deadline
from r2d2_tpu.utils.trace import (
    HOST_TRANSFERS,
    RETRACES,
    TRANSFER_GUARD,
    put_scalar,
)

log = logging.getLogger(__name__)

# host-facing stats appended to the losses in the per-dispatch result
# vector, in this order (all float32; the deltas are per-dispatch)
STATS_FIELDS = ("env_steps", "fill", "episodes", "reward_sum", "blocks")

# in-graph greedy eval lane fields, appended after STATS_FIELDS when
# cfg.anakin_eval_interval > 0 (zeros on off-cadence dispatches)
EVAL_FIELDS = ("eval_episodes", "eval_return_sum")
# the counters the model's own train step leaves for the host
# (models/network.counter_names, none under the LSTM) follow the eval pair:
# those of the dispatch's last update


def _mesh_hooks(table):
    """The fused program's layout-invariance hooks over one table:
    ``rep`` pins a value replicated (threefry draws, the stratified
    draw's cumsum input — the PR 8 pins extended to the fused program),
    ``rows`` pins sampled batch rows to dp (so the gather and the
    forward/backward shard exactly as the pjit drivetrains')."""
    rep_sh = table.replicated()
    dp_sh = NamedSharding(table.mesh, P("dp"))

    def rep(x):
        return jax.lax.with_sharding_constraint(x, rep_sh)

    def rows(x):
        return jax.lax.with_sharding_constraint(x, dp_sh)

    return rep, rows


def _make_eval_lane(cfg: Config, net: R2D2Network, env: Any,
                    action_dim: int):
    """The in-graph greedy eval lane: every ``cfg.anakin_eval_interval``
    dispatches (``lax.cond``-gated — off-cadence dispatches pay a zeros
    fill, not the rollout), run ONE truncation-length episode per lane
    with epsilon = 0 from a fresh env state (stream: a distinct
    ``fold_in`` derivation over the dispatch index, so eval episodes are
    reproducible and never perturb the training streams), and return
    ``(2,)`` f32 ``[episodes, return_sum]`` riding the existing
    per-dispatch result vector — anakin learning curves without a host
    env.  Greedy argmax + per-lane env draws are elementwise in the lane
    axis, so the lane needs no extra layout pins."""
    N, A = cfg.num_actors, action_dim
    act_net = _loss_net(cfg, net)
    interval = cfg.anakin_eval_interval
    steps = cfg.anakin_episode_len

    def eval_rollout(params, dispatch_idx):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(cfg.seed), 0x45564C),
            dispatch_idx)
        est = env.init_state(key)
        carry0 = (est, env.observe(est),
                  jnp.zeros((N, A), jnp.float32), jnp.zeros(N, jnp.float32),
                  zero_hidden(cfg, N),
                  jnp.zeros(N, jnp.float32), jnp.zeros(N, bool))

        def estep(c, _):
            est, obs, la, lr, hidden, ret, done = c
            q, h2 = act_net.apply(params, obs, la, lr, hidden,
                                  method=R2D2Network.act)
            a = jnp.argmax(q, axis=1).astype(jnp.int32)
            est2, reward, trunc = env.step(est, a)
            # the truncating step's reward still counts (it ends the
            # episode); anything after a lane's done flag does not
            ret = ret + jnp.where(done, 0.0, reward)
            done = done | trunc
            one_hot = jnp.zeros((N, A), jnp.float32).at[
                jnp.arange(N), a].set(1.0)
            return (est2, env.observe(est2), one_hot, reward, h2, ret,
                    done), None

        carry, _ = jax.lax.scan(estep, carry0, None, length=steps)
        ret, done = carry[5], carry[6]
        return jnp.stack([done.sum().astype(jnp.float32), ret.sum()])

    def eval_lane(params, dispatch_idx):
        do = (dispatch_idx % jnp.uint32(interval)) == 0
        return jax.lax.cond(do,
                            lambda _: eval_rollout(params, dispatch_idx),
                            lambda _: jnp.zeros(2, jnp.float32), 0)

    return eval_lane


def _gamma_tables(cfg: Config):
    """Host-precomputed float32 discount constants, bit-identical to
    ``utils.math.n_step_gamma_tail``'s values: ``tail[e]`` is numpy's
    float32 ``gamma ** e`` (the tail entries), ``interior`` is the python
    ``gamma ** n`` cast to f32 (the interior fill), ``kernel[i]`` is the
    f32-rounded f64 ``gamma ** i`` for the n-step return sum."""
    n, g = cfg.forward_steps, cfg.gamma
    tail = g ** np.arange(0, n + 1, dtype=np.float32)
    interior = np.float32(g ** n)
    kernel = (g ** np.arange(0, n, dtype=np.float64)).astype(np.float32)
    return jnp.asarray(tail), jnp.asarray(interior), kernel


def _make_assemble(cfg: Config, action_dim: int, done: bool):
    """Single-lane block assembly (vmapped by the emitter): the jnp twin
    of :func:`replay.block.assemble_block` over the lane's preallocated
    stream/window buffers, with every per-sequence quantity computed at
    the static maximum K and masked past ``num_sequences``.

    ``done`` is static — the two call sites are statically terminal
    (episode-end cuts) or statically bootstrapped (boundary cuts), exactly
    like the host actor's two ``finish`` calls."""
    BL, L, n = cfg.block_length, cfg.learning_steps, cfg.forward_steps
    K, cap = cfg.seqs_per_block, cfg.max_block_steps
    burn_max = cfg.burn_in_steps
    seq_start_mode = cfg.stored_hidden_mode == "seq_start"
    tail_tbl, interior, kernel = _gamma_tables(cfg)

    def assemble(bufs: Dict[str, jnp.ndarray], prefix, size, last_q):
        s, c = size, prefix
        boot = (jnp.zeros(action_dim, jnp.float32) if done else last_q)
        qv = jax.lax.dynamic_update_index_in_dim(bufs["qval"], boot, s, 0)

        t = jnp.arange(BL, dtype=jnp.int32)
        tmask = t < s
        r = jnp.where(tmask, bufs["reward"], 0.0)

        # n-step returns: sum_{i<n} gamma^i * r[t+i] (utils.math
        # n_step_return; f32 here vs the host's f64 accumulate — ulp-level)
        r_ext = jnp.concatenate([r, jnp.zeros(n - 1, jnp.float32)]) \
            if n > 1 else r
        nstep = jnp.zeros(BL, jnp.float32)
        for i in range(n):  # static unroll, n is small (<= ~5)
            nstep = nstep + kernel[i] * jax.lax.slice_in_dim(r_ext, i, i + BL)

        # bootstrap discount tail (utils.math n_step_gamma_tail, exact:
        # table lookups of the host's own f32 values)
        steps_left = s - t                     # >= 1 wherever tmask
        e = jnp.clip(steps_left, 0, n)
        tail_val = (jnp.float32(0.0) if done else tail_tbl[e])
        gtail = jnp.where(steps_left > n, interior, tail_val)
        gtail = jnp.where(tmask, gtail, 0.0)

        # per-sequence windows (worker.py:471-474 invariants)
        seq = jnp.arange(K, dtype=jnp.int32)
        num_seq = (s + L - 1) // L
        valid = seq < num_seq
        burn = jnp.where(valid, jnp.minimum(seq * L + c, burn_max), 0)
        learn = jnp.where(valid, jnp.minimum(L, s - seq * L), 0)
        fwd = jnp.where(valid,
                        jnp.minimum(n, s + 1 - jnp.cumsum(learn)), 0)

        # stored recurrent state at each sequence's burn-in start (or the
        # reference's seq-start indexing under the compat switch)
        hidx = seq * L if seq_start_mode else c + seq * L - burn
        hidx = jnp.clip(hidx, 0, cap - 1)
        # sequence j's snapshot, where the state has a part kept whole,
        # is slot j: _snapshot_positions' first K are these hidx
        with jax.named_scope("state_snapshot"):
            hiddens = stream_states(
                cfg, bufs["hidden"], hidx,
                bufs["snapshot"][:K] if "snapshot" in bufs else None)
        hiddens = jnp.where(lanes_like(valid, hiddens), hiddens,
                            jnp.zeros((), hiddens.dtype))

        # actor-side initial priorities (block.py:104-110: plain max-Q
        # n-step TD, replicating the reference's asymmetry vs the learner)
        qmax = qv.max(axis=1)                                  # (BL+1,)
        mf = jnp.minimum(s, n)
        maxq_t = qmax[jnp.minimum(t + mf, s)]
        q_taken = qv[t, bufs["action"].astype(jnp.int32)]
        td = jnp.abs(nstep + gtail * maxq_t - q_taken)
        td = jnp.where(tmask, td, 0.0)
        td2 = td.reshape(K, L)
        lmask = jnp.arange(L)[None, :] < learn[:, None]
        seg_max = jnp.where(lmask, td2, 0.0).max(axis=1)
        seg_mean = jnp.where(lmask, td2, 0.0).sum(axis=1) \
            / jnp.maximum(learn, 1)
        prios = jnp.where(valid, 0.9 * seg_max + 0.1 * seg_mean, 0.0)

        return dict(
            slot=dict(obs=bufs["obs"], last_action=bufs["last_action"],
                      last_reward=bufs["last_reward"],
                      action=bufs["action"], n_step_reward=nstep,
                      n_step_gamma=gtail, hidden=hiddens),
            priorities=prios,
            meta=jnp.stack([burn, learn, fwd], axis=1).astype(jnp.int32),
            first_burn=burn[0].astype(jnp.int32),
            learning_total=learn.sum().astype(jnp.int32),
        )

    return assemble


def _make_emit(cfg: Config, action_dim: int, done: bool):
    """Batched cut-and-write: assemble every lane's candidate block, then
    scatter the ``cut`` lanes' blocks into ring slots ``ptr..`` (logical
    FIFO order preserved: cut lanes take consecutive slots in lane order,
    exactly the order the host actor's per-lane sink calls would land).
    Non-cut lanes scatter to the sentinel slot ``num_blocks`` and are
    dropped, so the write is one fixed-shape donated update."""
    NB, K = cfg.num_blocks, cfg.seqs_per_block
    alpha = cfg.prio_exponent
    assemble = jax.vmap(_make_assemble(cfg, action_dim, done))

    @jax.named_scope("ring_write")
    def emit(ast, arrays, prios, seq_meta, first, cut, last_q):
        bufs = dict(obs=ast["buf_obs"], last_action=ast["buf_last_action"],
                    last_reward=ast["buf_last_reward"],
                    hidden=ast["buf_hidden"], action=ast["buf_action"],
                    reward=ast["buf_reward"], qval=ast["buf_qval"])
        if "buf_snapshot" in ast:
            bufs["snapshot"] = ast["buf_snapshot"]
        blocks = assemble(bufs, ast["prefix"], ast["size"], last_q)

        cut_i = cut.astype(jnp.int32)
        offs = jnp.cumsum(cut_i) - cut_i              # rank among cut lanes
        slot = jnp.where(cut, (ast["ptr"] + offs) % NB, NB)   # NB = dropped

        # the ring's own slot format (replay/device_ring._slot_shapes):
        # frame rows packed into words, and the time fields' spare rows
        # holding copies of a block's last row
        cut_blocks = ring_slots(blocks["slot"], arrays)
        arrays = {k: arrays[k].at[slot].set(cut_blocks[k], mode="drop")
                  for k in arrays}
        leaf = (slot * K)[:, None] + jnp.arange(K)[None, :]
        prios = prios.at[leaf.reshape(-1)].set(
            (blocks["priorities"] ** alpha).reshape(-1), mode="drop")
        seq_meta = seq_meta.at[slot].set(blocks["meta"], mode="drop")
        first = first.at[slot].set(blocks["first_burn"], mode="drop")

        # fill accounting mirrors ReplayBuffer.add: subtract the
        # overwritten slot's learning total, add the new one
        slot_safe = jnp.minimum(slot, NB - 1)
        old_tot = jnp.where(cut, ast["block_learning_total"][slot_safe], 0)
        new_tot = jnp.where(cut, blocks["learning_total"], 0)
        blt = ast["block_learning_total"].at[slot].set(
            blocks["learning_total"], mode="drop")
        ast = {**ast,
               "ptr": (ast["ptr"] + cut_i.sum()) % NB,
               "block_learning_total": blt,
               "fill": ast["fill"] + (new_tot - old_tot).sum(),
               "env_steps_d": ast["env_steps_d"] + new_tot.sum(),
               "blocks_d": ast["blocks_d"] + cut_i.sum()}
        return ast, arrays, prios, seq_meta, first

    return emit


def _make_actor_step(cfg: Config, net: R2D2Network, env: Any,
                     action_dim: int, cut_cond: bool = True,
                     replicate=None):
    """One fused env/actor step for the whole fleet — the jnp twin of one
    ``VectorActor.run`` iteration, same sub-step order (boundary cuts with
    this step's bootstrap Q first, then act/step/record, then episode-end
    cuts and lane resets).  Returns ``(carry', trace)``; the production
    scan discards ``trace`` (XLA dead-code-eliminates it), the parity
    tests keep it to drive the host LocalBuffer oracle.

    ``cut_cond`` (default on) wraps each cut — emit and retention at a
    block boundary, emit and the cut lanes' stream resets at an episode's
    end — in a ``lax.cond`` on ``jnp.any(cut)``: on the
    (block_length-1)/block_length majority of steps where NO lane cuts,
    the full-buffer block assembly, the retention and the ring scatters
    are skipped entirely instead of executing as all-masked no-ops.
    Bit-exact by construction — a no-cut emit writes only to the dropped
    sentinel slot, a no-cut retention or reset is the identity — and
    pinned vs the ``cut_cond=False`` path in tests/test_anakin.py.

    What the conditionals guarantee the compiler: both branches hand back
    their operand's own buffer for every lane buffer and for the ring.
    The false branch is the identity; the true branch changes a lane
    buffer only by in-place updates of a few rows (a boundary cut writes
    ``burn_in_steps + 1`` rows (+ the state stream's history) and the
    snapshot slots a block hands the next, :func:`_retain_prefix`; an
    episode's end writes row 0, that history and as many slots) that are
    ordered AFTER every read of it
    (:func:`_after_reads`).  An update of a whole buffer in a true branch,
    or a reset left outside where nothing orders it against the cut's
    read, costs a copy of the whole buffer on EVERY env step, cut or no
    cut (four of 186-199 MB a step in the benchmark's widest fused cell
    before PR 31; tests/test_tpu_compile.py asks the compiled rollout).

    ``replicate`` (mesh mode) pins the fleet-wide exploration draws to a
    replicated layout: with non-partitionable threefry, GSPMD
    back-propagating a dp sharding onto a counter-based ``(N,)`` draw
    changes the generated BITS (the PR 8 finding on the stratified
    draw's uniforms), so without the pin a dp=2 run would explore
    differently than dp=1.  Per-lane vmapped draws (the env's reset
    streams) are elementwise in the lane axis and need no pin."""
    N, A, BL = cfg.num_actors, action_dim, cfg.block_length
    cap = cfg.max_block_steps
    eps = jnp.asarray([epsilon_ladder(i, cfg.num_actors, cfg.base_eps,
                                      cfg.eps_alpha)
                       for i in range(cfg.num_actors)], jnp.float32)
    act_net = _loss_net(cfg, net)  # the scan recurrence, grad-safe twin
    hist = stream_spec(cfg).history
    emit_boundary = _make_emit(cfg, action_dim, done=False)
    emit_done = _make_emit(cfg, action_dim, done=True)
    env_keys = tuple(env.STATE_KEYS)
    lanes = jnp.arange(N)

    def actor_step(params, ast, arrays, prios, seq_meta, first):
        with jax.named_scope("act"):
            q, new_hidden = act_net.apply(
                params, ast["obs"], ast["last_action"], ast["last_reward"],
                ast["hidden"], method=R2D2Network.act)

        # 1) deferred block-boundary cuts: this step's Q at the new state
        #    is the bootstrap (worker.py:550-554 semantics, no 2nd forward)
        pend = ast["finish_pending"]

        def _boundary(ops):
            a, arr, p, sm, fb = ops
            a, arr, p, sm, fb = emit_boundary(a, arr, p, sm, fb, pend, q)
            a, arr = _after_reads(a, arr)
            return _retain_prefix(cfg, a, pend), arr, p, sm, fb

        if cut_cond:
            ast, arrays, prios, seq_meta, first = jax.lax.cond(
                jnp.any(pend), _boundary, lambda ops: ops,
                (ast, arrays, prios, seq_meta, first))
        else:
            ast, arrays, prios, seq_meta, first = _boundary(
                (ast, arrays, prios, seq_meta, first))
        ast = {**ast, "finish_pending": jnp.zeros(N, bool)}

        # 2) ladder-epsilon exploration
        key, k1, k2 = jax.random.split(ast["act_key"], 3)
        u = jax.random.uniform(k1, (N,))
        rand_a = jax.random.randint(k2, (N,), 0, A, dtype=jnp.int32)
        if replicate is not None:
            # layout-invariance pin: see the factory docstring
            u, rand_a = replicate(u), replicate(rand_a)
        explore = u < eps
        actions = jnp.where(explore, rand_a,
                            jnp.argmax(q, axis=1).astype(jnp.int32))

        # 3) env step (no auto-reset: the post-step obs is recorded first)
        env_state = {k: ast["env_" + k] for k in env_keys}
        with jax.named_scope("env_step"):
            env_state, reward, truncated = env.step(env_state, actions)
            obs_step = env.observe(env_state)

        # 4) batched bookkeeping + local-buffer add (VectorLocalBuffer
        #    .add_batch, one scatter per field)
        one_hot = jnp.zeros((N, A), bool).at[lanes, actions].set(True)
        p = ast["prefix"] + ast["size"] + 1
        s = ast["size"]
        ast = {**ast,
               "buf_obs": ast["buf_obs"].at[lanes, p].set(
                   obs_step.reshape(N, -1)),
               "buf_last_action":
                   ast["buf_last_action"].at[lanes, p].set(one_hot),
               "buf_last_reward":
                   ast["buf_last_reward"].at[lanes, p].set(reward),
               "buf_hidden": ast["buf_hidden"].at[lanes, p + hist].set(
                   stream_entry(cfg, new_hidden)),
               "buf_action":
                   ast["buf_action"].at[lanes, s].set(
                       actions.astype(jnp.uint8)),
               "buf_reward": ast["buf_reward"].at[lanes, s].set(reward),
               "buf_qval": ast["buf_qval"].at[lanes, s].set(q),
               "obs": obs_step,
               "last_action": one_hot.astype(jnp.float32),
               "last_reward": reward,
               "hidden": new_hidden,
               "size": s + 1,
               "sum_reward": ast["sum_reward"] + reward,
               "episode_steps": ast["episode_steps"] + 1,
               "act_key": key,
               **{f"env_{k}": env_state[k] for k in env_keys}}
        if "buf_snapshot" in ast:
            ast = _write_snapshots(cfg, ast, p, new_hidden)

        # 5) episode-end cuts (terminal: zero bootstrap), and the reset of
        #    the cut lanes' streams (vbuf.reset_lane) AFTER the cut has
        #    read them; same cond fast path — episode ends are rarer
        #    still than block boundaries
        tr = truncated
        trc = tr[:, None]
        env_state = env.reset_lanes(env_state, tr)
        obs_reset = env.observe(env_state)

        def _done_cut(ops):
            a, arr, p, sm, fb = emit_done(
                *ops, tr, jnp.zeros((N, A), jnp.float32))
            a, arr = _after_reads(a, arr)
            # row 0 of the cut lanes' streams, as the step's own kind of
            # update (one row a lane, the others' dropped)
            row0 = jnp.where(tr, 0, cap)
            noop = jnp.zeros((N, A), bool).at[:, 0].set(True)
            a = {**a,
                 "buf_obs": a["buf_obs"].at[lanes, row0].set(
                     obs_reset.reshape(N, -1), mode="drop"),
                 "buf_last_action": a["buf_last_action"].at[
                     lanes, row0].set(noop, mode="drop"),
                 "buf_last_reward": a["buf_last_reward"].at[
                     lanes, row0].set(0.0, mode="drop"),
                 "buf_hidden": reset_stream(cfg, a["buf_hidden"], tr)}
            if "buf_snapshot" in a:
                a = _reset_snapshots(cfg, a, tr)
            return a, arr, p, sm, fb

        if cut_cond:
            ast, arrays, prios, seq_meta, first = jax.lax.cond(
                jnp.any(tr), _done_cut, lambda ops: ops,
                (ast, arrays, prios, seq_meta, first))
        else:
            ast, arrays, prios, seq_meta, first = _done_cut(
                (ast, arrays, prios, seq_meta, first))

        # 6) episode accounting, env reset, lane reset (VectorActor
        #    ._reset_lane: fresh obs, zero agent state)
        ast = {**ast,
               "episodes_d": ast["episodes_d"] + tr.sum(),
               "reward_d": ast["reward_d"]
               + jnp.where(tr, ast["sum_reward"], 0.0).sum()}
        obs_next = jnp.where(tr.reshape((N,) + (1,) * (obs_step.ndim - 1)),
                             obs_reset, obs_step)
        ast = {**ast,
               "obs": obs_next,
               "last_action": jnp.where(trc, 0.0, ast["last_action"]),
               "last_reward": jnp.where(tr, 0.0, ast["last_reward"]),
               "hidden": jnp.where(lanes_like(tr, ast["hidden"]),
                                   jnp.zeros((), ast["hidden"].dtype),
                                   ast["hidden"]),
               "episode_steps": jnp.where(tr, 0, ast["episode_steps"]),
               "sum_reward": jnp.where(tr, 0.0, ast["sum_reward"]),
               "prefix": jnp.where(tr, 0, ast["prefix"]),
               "size": jnp.where(tr, 0, ast["size"]),
               **{f"env_{k}": env_state[k] for k in env_keys}}

        # 7) deferred boundary cut next step (worker.py block-cut rule)
        ast = {**ast,
               "finish_pending": (ast["size"] == BL) & ~tr
               & (ast["episode_steps"] < cfg.max_episode_steps)}

        trace = dict(pending=pend, q=q, hidden=new_hidden, actions=actions,
                     reward=reward, truncated=tr, obs_step=obs_step,
                     obs_next=obs_next)
        return (ast, arrays, prios, seq_meta, first), trace

    return actor_step


# the per-lane streams a cut reads whole and then updates in a few rows
# (``buf_snapshot`` only where the state has a part kept whole)
LANE_BUFFERS = ("buf_obs", "buf_last_action", "buf_last_reward",
                "buf_hidden", "buf_snapshot")


def _after_reads(ast: dict, arrays: dict):
    """Order the in-place updates that follow after a cut's reads of the
    lane buffers.  The ring a cut has written holds everything the cut
    read from them, and past this barrier neither is there before the
    other: without it nothing orders an update of a few rows against the
    block assembly's read of the same buffer, and the compiler protects
    the read with a copy of the whole buffer."""
    bufs, arrays = jax.lax.optimization_barrier(
        ({k: ast[k] for k in LANE_BUFFERS if k in ast}, arrays))
    return {**ast, **bufs}, arrays


def _snapshot_slots(cfg: Config):
    """(slots a lane keeps, how many of them one block hands the next).
    A stored sequence can start its burn-in at the episode steps ``m *
    learning_steps - burn_in_steps`` (and at 0) and nowhere else.  Slot m
    of a block is that step of the block's own sequence m
    (:func:`_make_assemble`'s ``hidx``); the slots from K on lie in the
    rows that :func:`_retain_prefix` makes the next block's warm prefix,
    where that block's first ``burn_in_steps // learning_steps + 1``
    sequences start."""
    carried = cfg.burn_in_steps // cfg.learning_steps + 1
    return cfg.seqs_per_block + carried, carried


def _snapshot_positions(cfg: Config, prefix):
    """The stream positions (N, slots) at which a lane whose block began
    with ``prefix`` (N,) warm entries keeps a snapshot."""
    m = jnp.arange(_snapshot_slots(cfg)[0], dtype=jnp.int32)
    return jnp.maximum(
        prefix[:, None] + m[None, :] * cfg.learning_steps
        - cfg.burn_in_steps, 0)


@jax.named_scope("state_snapshot")
def _write_snapshots(cfg: Config, ast: dict, p, states) -> dict:
    """Keep the snapshot part of ``states`` (N, ...), the lanes' states at
    stream positions ``p`` (N,), in the slot whose position that is: one
    row a lane, dropped where ``p`` is none of the lane's positions."""
    hit = _snapshot_positions(cfg, ast["prefix"]) == p[:, None]
    slot = jnp.where(hit.any(axis=1), jnp.argmax(hit, axis=1),
                     hit.shape[1])
    return {**ast, "buf_snapshot": ast["buf_snapshot"].at[
        jnp.arange(p.shape[0]), slot].set(
            stream_snapshot(cfg, states), mode="drop")}


@jax.named_scope("state_snapshot")
def _reset_snapshots(cfg: Config, ast: dict, reset) -> dict:
    """Zero, for the ``reset`` (N,) lanes, the slots whose position in an
    episode's first block is row 0 (the initial state): as many as a block
    hands the next.  A step writes every other slot before a cut reads
    it."""
    n0 = _snapshot_slots(cfg)[1]
    old = ast["buf_snapshot"][:, :n0]
    return {**ast, "buf_snapshot": ast["buf_snapshot"].at[:, :n0].set(
        jnp.where(lanes_like(reset, old), jnp.zeros((), old.dtype), old))}


def _retain_prefix(cfg: Config, ast: dict, cut: jnp.ndarray) -> dict:
    """Post-boundary-cut retention: keep the trailing ``burn_in + 1``
    stream entries in place as the next block's warm prefix
    (VectorLocalBuffer.finish).  A cut lane keeps ``keep <= keep_max =
    burn_in_steps + 1`` entries from row ``lo`` on, so only the first
    ``keep_max`` rows of a stream can change (``keep_max + hist`` of the
    state stream, whose history lies in front of the same entries,
    models/state.stream_spec): the window of that many rows from ``lo`` is
    read first (it may overlap the rows written), selected per lane and
    per row against the old first rows (``cut`` lanes, ``j < keep``), and
    set as ONE slice update on the stream's own buffer.  Rows past the
    window are not touched, and the result is the operand's buffer updated
    in place — which is what lets the cut's ``lax.cond`` hand its operand
    back uncopied on the steps where no lane cuts."""
    keep_max = cfg.burn_in_steps + 1
    hist = stream_spec(cfg).history
    entries = ast["prefix"] + ast["size"] + 1
    keep = jnp.minimum(keep_max, entries)
    lo = entries - keep         # lo + keep_max <= max_block_steps: no clamp

    def shift(name, extra=0):
        arr = ast[name]
        width = keep_max + extra
        window = jax.vmap(lambda a, l: jax.lax.dynamic_slice_in_dim(
            a, l, width, 0))(arr, lo)
        take = cut[:, None] & (jnp.arange(width)[None, :]
                               < (keep + extra)[:, None])
        take = take.reshape(take.shape + (1,) * (arr.ndim - 2))
        return arr.at[:, :width].set(
            jnp.where(take, window, arr[:, :width]))

    if "buf_snapshot" in ast:
        # slot K of a full block is its row ``lo``, the next block's row
        # 0, and so on (_snapshot_slots)
        with jax.named_scope("state_snapshot"):
            snaps, n = ast["buf_snapshot"], _snapshot_slots(cfg)[1]
            ast = {**ast, "buf_snapshot": snaps.at[:, :n].set(jnp.where(
                lanes_like(cut, snaps), snaps[:, -n:], snaps[:, :n]))}
    return {**ast,
            "buf_obs": shift("buf_obs"),
            "buf_last_action": shift("buf_last_action"),
            "buf_last_reward": shift("buf_last_reward"),
            "buf_hidden": shift("buf_hidden", hist),
            "prefix": jnp.where(cut, keep - 1, ast["prefix"]),
            "size": jnp.where(cut, 0, ast["size"])}


def _zero_deltas(ast: dict) -> dict:
    """Per-dispatch counters start at zero inside the program, so the
    returned values ARE the dispatch's deltas — the host accumulates them
    in Python ints (no on-device counter can wrap)."""
    return {**ast,
            "env_steps_d": jnp.zeros((), jnp.int32),
            "episodes_d": jnp.zeros((), jnp.int32),
            "reward_d": jnp.zeros((), jnp.float32),
            "blocks_d": jnp.zeros((), jnp.int32)}


def _stats_vec(ast: dict) -> jnp.ndarray:
    """(5,) float32, ordered as :data:`STATS_FIELDS`."""
    return jnp.stack([
        ast["env_steps_d"].astype(jnp.float32),
        ast["fill"].astype(jnp.float32),
        ast["episodes_d"].astype(jnp.float32),
        ast["reward_d"],
        ast["blocks_d"].astype(jnp.float32)])


def make_anakin_state(cfg: Config, action_dim: int, env: Any,
                      key: jax.Array) -> dict:
    """The fused loop's full device-resident carry (host-built, one
    device_put): env state (whatever pytree ``env.STATE_KEYS`` names),
    batched agent state, the VectorLocalBuffer twin, ring
    pointer/accounting, and the exploration RNG."""
    N, A, BL = cfg.num_actors, action_dim, cfg.block_length
    cap = cfg.max_block_steps
    obs_shape = cfg.stored_obs_shape
    hist, entry_shape, state_dtype, snapshot_shape = stream_spec(cfg)

    env_key, act_key = jax.random.split(key)
    env_state = env.init_state(env_key)
    obs0 = env.observe(env_state)

    buf_la = np.zeros((N, cap, A), bool)
    buf_la[:, 0, 0] = True                    # noop one-hot at stream start
    ast = dict(
        **{f"env_{k}": env_state[k] for k in env.STATE_KEYS},
        obs=obs0,
        last_action=jnp.zeros((N, A), jnp.float32),
        last_reward=jnp.zeros(N, jnp.float32),
        hidden=zero_hidden(cfg, N),
        # frames as flat byte rows, a staged slot's format
        # (replay/device_ring._slot_shapes): the cut packs them into the
        # ring's words
        buf_obs=jnp.zeros((N, cap, int(np.prod(obs_shape))), jnp.uint8
                          ).at[:, 0].set(obs0.reshape(N, -1)),
        buf_last_action=jnp.asarray(buf_la),
        buf_last_reward=jnp.zeros((N, cap), jnp.float32),
        buf_hidden=jnp.zeros((N, cap + hist) + entry_shape, state_dtype),
        buf_action=jnp.zeros((N, BL), jnp.uint8),
        buf_reward=jnp.zeros((N, BL), jnp.float32),
        buf_qval=jnp.zeros((N, BL + 1, A), jnp.float32),
        prefix=jnp.zeros(N, jnp.int32),
        size=jnp.zeros(N, jnp.int32),
        sum_reward=jnp.zeros(N, jnp.float32),
        episode_steps=jnp.zeros(N, jnp.int32),
        finish_pending=jnp.zeros(N, bool),
        act_key=act_key,
        ptr=jnp.zeros((), jnp.int32),
        block_learning_total=jnp.zeros(cfg.num_blocks, jnp.int32),
        fill=jnp.zeros((), jnp.int32),
    )
    if snapshot_shape is not None:
        if cfg.burn_in_steps > cfg.block_length:
            raise ValueError(
                "a state with a part kept whole needs burn_in_steps <= "
                "block_length: a block's last snapshot is the next "
                "block's first (learner/anakin._snapshot_slots)")
        ast["buf_snapshot"] = jnp.zeros(
            (N, _snapshot_slots(cfg)[0]) + snapshot_shape, state_dtype)
    return _zero_deltas(ast)


def _anakin_shardings(table, state_template, ast_template, layout: str):
    """(state, ast, ring, prios, seq_meta, first) sharding trees for the
    fused entry points — every piece resolved through the ONE sharding
    table (parallel/sharding.py): params/moments per the param-path
    patterns (fsdp/tp), lane state per ``anakin.lane.*`` (dp), ring/PER
    per ``ring.*``/``per.*`` under the ring layout."""
    per = table.per_shardings(layout)
    return (table.state_shardings(state_template),
            table.anakin_state_shardings(ast_template, layout),
            table.ring_shardings(layout),
            per["prios"], per["seq_meta"], per["first"])


def make_anakin_super_step(cfg: Config, net: R2D2Network,
                           env: Any, action_dim: int,
                           cut_cond: bool = True, table=None,
                           state_template=None, ast_template=None,
                           layout: str = "replicated"):
    """The fused program: ``k × (E env/actor steps + 1 train step)`` in one
    dispatch.  Signature::

        super_step(train_state, anakin_state, ring_arrays, prios,
                   seq_meta, first_burn, dispatch_idx u32)
          -> (train_state', anakin_state', ring_arrays', prios',
              seq_meta', first_burn', flat f32)

    All six state arguments are donated; ``flat`` is the per-inner-step
    losses followed by the :data:`STATS_FIELDS` deltas (then the
    :data:`EVAL_FIELDS` pair when ``cfg.anakin_eval_interval > 0``, then
    the learnhealth diagnostic rows when armed) — the dispatch's ONLY
    device→host payload at every mesh shape.  The sampling stream is
    ``fold_in(PRNGKey(cfg.seed), dispatch_idx)``, matching the
    ``in_graph_per`` drivetrain's scheme (learner/step.py).

    ``table`` (mesh mode) makes this THE one
    ``jax.jit(in_shardings=..., out_shardings=..., donate_argnums=...)``
    entry point over the dp × fsdp × tp mesh: lanes/carry/local buffers
    shard over dp, params/moments per the table's patterns, ring/PER per
    ``layout``; the stratified draw and the fleet-wide exploration
    draws are pinned replicated (the PR 8 cumsum/threefry pins), and
    sampled batch rows are pinned to dp so the train step shards exactly
    as the pjit drivetrains'.  ``table=None`` is the single-device path
    — the same program, default placement."""
    k, E = cfg.superstep_k, cfg.anakin_env_steps_per_update
    lh = getattr(cfg, "learnhealth_interval", 0) > 0
    rep = rows = None
    if table is not None:
        if state_template is None or ast_template is None:
            raise ValueError(
                "mesh-mode make_anakin_super_step needs state_template "
                "and ast_template to resolve the table shardings — "
                "compiling without them would silently bypass the layout")
        rep, rows = _mesh_hooks(table)
    step = make_train_step(cfg, net, learnhealth=lh)
    actor_step = _make_actor_step(cfg, net, env, action_dim,
                                  cut_cond=cut_cond, replicate=rep)
    eval_lane = (_make_eval_lane(cfg, net, env, action_dim)
                 if cfg.anakin_eval_interval > 0 else None)

    def super_step(train_state: TrainState, ast, arrays, prios, seq_meta,
                   first, dispatch_idx):
        ast = _zero_deltas(ast)
        keys = jax.random.split(
            jax.random.fold_in(jax.random.PRNGKey(cfg.seed), dispatch_idx),
            k)

        def update(carry, key_t):
            ts, ast, arrays, prios, seq_meta, first = carry

            def env_it(c, _):
                c2, _trace = actor_step(ts.params, *c)
                return c2, None

            (ast, arrays, prios, seq_meta, first), _ = jax.lax.scan(
                env_it, (ast, arrays, prios, seq_meta, first), None,
                length=E)
            # mesh mode: the draw reads a REPLICATED view of the leaves
            # and its uniforms are pinned replicated (learner/step.py's
            # in_graph_per rationale — associative_scan partitioning
            # changes final-ulp rounding, threefry partitioning changes
            # bits); the sampled rows then pin to dp so gather/forward
            # shard over the mesh
            p_draw = prios if rep is None else rep(prios)
            idx, w, ints = _in_graph_sample(cfg, key_t, p_draw, seq_meta,
                                            first, constrain_rep=rep)
            if rows is not None:
                ints, w = rows(ints), rows(w)
            batch = gather_batch(cfg, arrays, ints, w)
            if lh:
                ts, loss, new_p, diag = step(ts, batch)
            else:
                ts, loss, new_p = step(ts, batch)
            # same feedback exponentiation as the in_graph_per super-step
            with jax.named_scope("per_scatter"):
                prios = prios.at[idx].set(new_p ** cfg.prio_exponent)
            return ((ts, ast, arrays, prios, seq_meta, first),
                    ((loss, diag) if lh else loss))

        (train_state, ast, arrays, prios, seq_meta, first), ys = (
            jax.lax.scan(update, (train_state, ast, arrays, prios,
                                  seq_meta, first), keys))
        if lh:
            losses, diags = ys
        else:
            losses, diags = ys, None
        parts = [losses, _stats_vec(ast)]
        if eval_lane is not None:
            parts.append(eval_lane(train_state.params, dispatch_idx))
        if counter_names(cfg):
            parts.append(read_counters(cfg, train_state.params))
        if diags is not None:
            parts.append(diags.reshape(-1))
        flat = jnp.concatenate(parts)
        return train_state, ast, arrays, prios, seq_meta, first, flat

    wrapped = RETRACES.wrap("learner.anakin_super_step", super_step)
    if table is None:
        return jax.jit(wrapped, donate_argnums=(0, 1, 2, 3, 4, 5))
    from r2d2_tpu.parallel.sharding import (
        _check_batch,
        _silence_benign_donation_warning,
    )

    _silence_benign_donation_warning()
    _check_batch(cfg, table.mesh)
    sh = _anakin_shardings(table, state_template, ast_template, layout)
    return jax.jit(wrapped,
                   in_shardings=sh + (table.replicated(),),
                   out_shardings=sh + (table.replicated(),),
                   donate_argnums=(0, 1, 2, 3, 4, 5))


def make_anakin_rollout(cfg: Config, net: R2D2Network, env: Any,
                        action_dim: int, steps: int, table=None,
                        state_template=None, ast_template=None,
                        layout: str = "replicated"):
    """The warm-up program: ``steps`` fused env/actor steps with ring/PER
    writes but NO train step — dispatched until the in-graph fill counter
    reaches ``learning_starts``.  Params are read-only (not donated).
    ``table`` shards it exactly like :func:`make_anakin_super_step`."""
    rep = None
    if table is not None:
        rep, _ = _mesh_hooks(table)
    actor_step = _make_actor_step(cfg, net, env, action_dim, replicate=rep)

    def rollout(params, ast, arrays, prios, seq_meta, first):
        ast = _zero_deltas(ast)

        def env_it(c, _):
            c2, _trace = actor_step(params, *c)
            return c2, None

        (ast, arrays, prios, seq_meta, first), _ = jax.lax.scan(
            env_it, (ast, arrays, prios, seq_meta, first), None,
            length=steps)
        return ast, arrays, prios, seq_meta, first, _stats_vec(ast)

    wrapped = RETRACES.wrap("learner.anakin_rollout", rollout)
    if table is None:
        return jax.jit(wrapped, donate_argnums=(1, 2, 3, 4, 5))
    st_sh, ast_sh, ring_sh, pr_sh, sm_sh, fb_sh = _anakin_shardings(
        table, state_template, ast_template, layout)
    return jax.jit(wrapped,
                   in_shardings=(st_sh.params, ast_sh, ring_sh, pr_sh,
                                 sm_sh, fb_sh),
                   out_shardings=(ast_sh, ring_sh, pr_sh, sm_sh, fb_sh,
                                  table.replicated()),
                   donate_argnums=(1, 2, 3, 4, 5))


def make_debug_rollout(cfg: Config, net: R2D2Network, env: Any,
                       action_dim: int, steps: int, cut_cond: bool = True):
    """Parity-test harness: like :func:`make_anakin_rollout` but keeps the
    per-step trace (q, hidden, actions, rewards, cut masks, observations)
    so tests can replay the exact trajectory into the host LocalBuffer
    oracle.  ``cut_cond=False`` builds the pre-r9 always-emit variant for
    the fast-path bit-exactness pin.  Not retrace-guarded or donated —
    test-only."""
    actor_step = _make_actor_step(cfg, net, env, action_dim,
                                  cut_cond=cut_cond)

    def rollout(params, ast, arrays, prios, seq_meta, first):
        def env_it(c, _):
            return actor_step(params, *c)

        return jax.lax.scan(env_it, (ast, arrays, prios, seq_meta, first),
                            None, length=steps)

    return jax.jit(rollout)  # graftlint: disable=donation-discipline -- test-only parity harness: the host oracle replays the same inputs after the call, so nothing may be donated


# --------------------------------------------------------------------------
# host-side driver
# --------------------------------------------------------------------------

class AnakinPlane:
    """Owns the fused loop's device state and its dispatch/harvest cycle.

    The host's entire job: dispatch the compiled program, read back the
    small flat result vector, and keep Python-int mirrors of the
    counters (no on-device counter can overflow that way).  Every
    device→host crossing ticks ``HOST_TRANSFERS`` (``anakin.result_fetch``
    once per dispatch; ``anakin.snapshot_fetch`` per full-state snapshot)
    so the "host-free hot loop" claim is an assertable invariant.

    The ring handles live in the :class:`DeviceRing` passed in — the fused
    program donates them and the plane stores the returned generation back
    after every dispatch, so the ring object stays the single owner (same
    handle discipline as the ``in_graph_per`` drivetrain).

    ``table`` (a :class:`~r2d2_tpu.parallel.sharding.ShardingTable`, with
    ``state_template`` = the run's TrainState or its avals) makes the
    plane mesh-native: the carry/ring/PER state places per the table, the
    compiled programs are the sharded entry points, and the snapshot path
    stays LAYOUT-FREE (``write_state`` host-gathers, ``read_state``
    re-places under the CURRENT table — a dp=2 snapshot resumes on a
    dp=1 mesh and vice versa, the checkpoint-resharding contract).
    """

    def __init__(self, cfg: Config, net: R2D2Network, action_dim: int,
                 ring: Any, start_env_steps: int = 0, table=None,
                 state_template=None):
        if not getattr(cfg, "in_graph_per", False):
            raise ValueError("the anakin plane requires in_graph_per=True "
                             "(train._train_anakin flips it on)")
        if cfg.num_blocks < cfg.num_actors:
            raise ValueError(
                f"anakin needs num_blocks ({cfg.num_blocks}) >= num_actors "
                f"({cfg.num_actors}): every lane may cut a block in the "
                "same fused step and the masked scatter writes them to "
                "distinct slots")
        if cfg.anakin_episode_len > cfg.max_episode_steps:
            raise ValueError(
                f"anakin_episode_len ({cfg.anakin_episode_len}) must be "
                f"<= max_episode_steps ({cfg.max_episode_steps}): the "
                "fused loop relies on truncation firing before the "
                "episode-step cap (the cap path needs a second forward "
                "the fused program does not run)")
        self.cfg = cfg
        self.ring = ring
        self.action_dim = action_dim
        # learnhealth plane: with a nonzero cadence the fused program's
        # flat result vector carries the per-inner-step diagnostic rows;
        # train._train_anakin attaches the run's LearnHealthMonitor
        self._lh = getattr(cfg, "learnhealth_interval", 0) > 0
        self._eval = cfg.anakin_eval_interval > 0
        self.monitor = None
        self.table = table
        self._layout = getattr(ring, "layout", "replicated")
        self.env = make_anakin_env(cfg, action_dim)
        # double fold_in: the PER sampling stream is the SINGLE-fold
        # fold_in(PRNGKey(seed), dispatch_idx) over the full u32 range
        # (learner/step.py), so a single-fold plane root would collide
        # with one dispatch's stream — two folds is a distinct
        # derivation path for the env/exploration streams
        self.state = make_anakin_state(
            cfg, action_dim, self.env,
            jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(cfg.seed), 0x414B),
                1))
        self._ast_sh = self._ring_sh = self._per_sh = None
        if table is not None:
            # mesh mode: place the carry per the table and compile the
            # sharded entry points.  The lane axis falls back to
            # replication via the table's divisibility guard when
            # num_actors does not divide dp — semantics identical either
            # way, the layout is a pure perf choice.
            self._ast_sh = table.anakin_state_shardings(self.state,
                                                        self._layout)
            self._ring_sh = table.ring_shardings(self._layout)
            self._per_sh = table.per_shardings(self._layout)
            self.state = jax.device_put(self.state, self._ast_sh)
        self.super_step = make_anakin_super_step(
            cfg, net, self.env, action_dim, table=table,
            state_template=state_template, ast_template=self.state,
            layout=self._layout)
        self.roll_steps = cfg.superstep_k * cfg.anakin_env_steps_per_update
        self.rollout = make_anakin_rollout(
            cfg, net, self.env, action_dim, steps=self.roll_steps,
            table=table, state_template=state_template,
            ast_template=self.state, layout=self._layout)
        self._frames_per_dispatch = self.roll_steps * cfg.num_actors

        # host-int counter mirrors (absolute; deltas arrive per dispatch).
        # The lock covers them: the dispatch thread folds deltas in while
        # the log thread's stats() does a read-and-reset of the interval
        # accumulators — same contract (and remedy) as ReplayBuffer.stats
        self._stats_lock = threading.Lock()
        self.env_steps = int(start_env_steps)
        self.fill = 0
        self.frames = 0
        self.super_steps = 0
        self.blocks = 0
        self.episodes_total = 0
        self.reward_total = 0.0
        self.training_steps = 0
        self.dispatch_no = 0
        # in-graph greedy eval lane (cfg.anakin_eval_interval): totals
        # accumulate across resumes, last_eval_return is the most recent
        # dispatch's mean greedy return (the learning-curve gauge)
        self.eval_episodes_total = 0
        self.eval_return_total = 0.0
        self.last_eval_return = float("nan")
        # the newest dispatch's model counters, by name
        self.model_counters: Dict[str, float] = {}
        # interval accumulators, reset by stats() (ReplayBuffer.stats
        # semantics so the log loop code is shared-shaped)
        self._interval_episodes = 0
        self._interval_reward = 0.0
        self._interval_loss = 0.0
        self._interval_eval_episodes = 0

    def release(self) -> None:
        """Delete every device buffer the loop holds (carry, ring, PER
        leaves): after the last harvest, so that a caller that goes on to
        use the device finds it empty."""
        meta = self.ring.per_meta()
        for leaf in jax.tree.leaves((self.state, self.ring.arrays,
                                     self.ring.take_prios(), meta)):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                leaf.delete()

    # ----------------------------------------------------------- dispatch
    def _handles(self):
        meta = self.ring.per_meta()
        return (self.ring.snapshot(), self.ring.take_prios(),
                meta["seq_meta"], meta["first"])

    def _store(self, arrays, prios, seq_meta, first) -> None:
        self.ring.arrays = arrays
        self.ring.put_prios(prios)
        self.ring.put_per_meta(seq_meta, first)

    def rollout_step(self, params) -> None:
        """One warm-up dispatch (env/actor/ring-write only), harvested
        synchronously — the fill counter gates the switch to training."""
        with TRANSFER_GUARD.disallow("anakin.rollout"):
            ast, arrays, prios, seq_meta, first, stats = self.rollout(
                params, self.state, *self._handles())
            self.state = ast
            self._store(arrays, prios, seq_meta, first)
            with self._stats_lock:
                self.frames += self._frames_per_dispatch
            with HOST_TRANSFERS.allowed("anakin.result_fetch"):
                stats_np = np.asarray(jax.device_get(stats))
        self._absorb(stats_np)

    def dispatch(self, train_state: TrainState):
        """One fused super-step dispatch.  Returns ``(train_state', flat)``
        with the result vector's D2H copy already started — harvest later
        (pipelined) via :meth:`harvest`."""
        with TRANSFER_GUARD.disallow("anakin.dispatch"):
            # the loop's ONE recurring H2D: the dispatch index scalar
            with HOST_TRANSFERS.allowed("anakin.dispatch_put"):
                idx = put_scalar(self.dispatch_no & 0xFFFFFFFF, np.uint32)
            self.dispatch_no += 1
            train_state, ast, arrays, prios, seq_meta, first, flat = (
                self.super_step(train_state, self.state,
                                *self._handles(), idx))
            self.state = ast
            self._store(arrays, prios, seq_meta, first)
            with self._stats_lock:
                self.frames += self._frames_per_dispatch
                self.super_steps += 1
            flat.copy_to_host_async()  # explicit: guard-exempt
        return train_state, flat

    def harvest(self, flat) -> np.ndarray:
        """Fetch one dispatch's result vector — the loop's ONLY recurring
        device→host crossing — and fold its deltas into the host
        counters.  Returns the k inner-step losses."""
        with TRANSFER_GUARD.disallow("anakin.harvest"):
            with HOST_TRANSFERS.allowed("anakin.result_fetch"):
                v = np.asarray(jax.device_get(flat))
        k = self.cfg.superstep_k
        losses = v[:k]
        stats = v[k:k + len(STATS_FIELDS)]
        off = k + len(STATS_FIELDS)
        if self._eval:
            # the eval lane's [episodes, return_sum] pair rides the same
            # vector; zeros on off-cadence dispatches
            ep, rsum = float(v[off]), float(v[off + 1])
            off += len(EVAL_FIELDS)
            if ep > 0:
                with self._stats_lock:
                    self.eval_episodes_total += int(ep)
                    self.eval_return_total += rsum
                    self.last_eval_return = rsum / ep
                    self._interval_eval_episodes += int(ep)
        names = counter_names(self.cfg)
        if names:
            self.model_counters = dict(zip(
                names, v[off:off + len(names)].tolist()))
            off += len(names)
        if self.monitor is not None:
            # the monitor owns non-finite handling (trips a clean fabric
            # stop + the nonfinite alert) and absorbs the diag rows the
            # fused program appended to the same flat vector
            self.monitor.note_losses(losses)
            if self._lh:
                self.monitor.absorb_diags(v[off:].reshape(k, -1))
        else:
            assert np.isfinite(losses).all(), (
                f"non-finite loss in anakin super-step: {losses}")
        self._absorb(stats)
        with self._stats_lock:
            self.training_steps += k
            self._interval_loss += float(losses.sum())
        return losses

    def _absorb(self, s: np.ndarray) -> None:
        d = dict(zip(STATS_FIELDS, s.tolist()))
        with self._stats_lock:
            self.env_steps += int(d["env_steps"])
            self.fill = int(d["fill"])
            self.blocks += int(d["blocks"])
            self.episodes_total += int(d["episodes"])
            self.reward_total += float(d["reward_sum"])
            self._interval_episodes += int(d["episodes"])
            self._interval_reward += float(d["reward_sum"])

    @property
    def ready(self) -> bool:
        return self.fill >= self.cfg.learning_starts

    def stats(self) -> Dict[str, float]:
        """ReplayBuffer.stats()-shaped snapshot for the log loop (the
        interval accumulators reset on read, like the buffer's)."""
        with self._stats_lock:
            out = dict(size=self.fill, env_steps=self.env_steps,
                       training_steps=self.training_steps,
                       num_episodes=self._interval_episodes,
                       episode_reward=self._interval_reward,
                       sum_loss=self._interval_loss,
                       frames=self.frames, super_steps=self.super_steps,
                       blocks=self.blocks,
                       episodes_total=self.episodes_total,
                       eval_episodes=self.eval_episodes_total,
                       interval_eval_episodes=self._interval_eval_episodes,
                       eval_return=self.last_eval_return,
                       model_counters=dict(self.model_counters))
            self._interval_episodes = 0
            self._interval_reward = 0.0
            self._interval_loss = 0.0
            self._interval_eval_episodes = 0
        return out

    # ----------------------------------------------------------- snapshot
    _COUNTER_FIELDS = ("env_steps", "fill", "frames", "super_steps",
                       "blocks", "episodes_total", "reward_total",
                       "training_steps", "dispatch_no",
                       "eval_episodes_total", "eval_return_total")

    def _payload(self) -> Dict[str, np.ndarray]:
        """Host copies of the ENTIRE on-device loop state: anakin carry
        (env phase/t/keys, agent obs/LSTM carry, local buffers), ring
        arrays, and the PER leaf/metadata state.  Call only with no
        dispatch in flight (the driver drains its pipeline first)."""
        arrays, prios, seq_meta, first = self._handles()
        with HOST_TRANSFERS.allowed("anakin.snapshot_fetch"):
            host = jax.device_get(dict(state=self.state, ring=arrays,
                                       prios=prios, seq_meta=seq_meta,
                                       first=first))
        flat: Dict[str, np.ndarray] = {}
        for k, v in host["state"].items():
            flat[f"state_{k}"] = np.asarray(v)
        for k, v in host["ring"].items():
            flat[f"ring_{k}"] = np.asarray(v)
        flat["per_prios"] = np.asarray(host["prios"])
        flat["per_seq_meta"] = np.asarray(host["seq_meta"])
        flat["per_first"] = np.asarray(host["first"])
        return flat

    def write_state(self, path: str) -> Dict[str, Any]:
        """Serialise the full anakin loop state into ``path`` (the
        ``Checkpointer.save_replay`` writer contract — same atomic
        tmp-dir/rename machinery as host-ring replay snapshots).  Returns
        the JSON-able meta ``read_state`` validates against."""
        flat = self._payload()
        with open(path, "wb") as f:  # file handle: savez must not append .npz
            np.savez(f, **flat)
        return dict(
            kind="anakin",
            layout=[[k, list(v.shape), v.dtype.name]
                    for k, v in sorted(flat.items())],
            counters={k: getattr(self, k) for k in self._COUNTER_FIELDS},
        )

    def read_state(self, path: str, meta: Dict[str, Any]) -> None:
        """Restore the state :meth:`write_state` captured.  Raises
        ``ValueError`` on a geometry/config mismatch (the caller warns and
        resumes cold).  The snapshot is LAYOUT-FREE (host-gathered
        global arrays), so it restores under ANY mesh shape — each array
        is re-placed per the CURRENT table here, the same resharding
        contract as learner checkpoints (docs/SHARDING.md)."""
        if meta.get("kind") != "anakin":
            raise ValueError("snapshot is not an anakin loop snapshot")
        with np.load(path) as z:
            flat = {k: z[k] for k in z.files}
        want = [[k, list(v.shape), v.dtype.name]
                for k, v in sorted(flat.items())]
        have = [[k, list(v.shape), np.dtype(v.dtype).name]
                for k, v in sorted(self._payload_template().items())]
        if want != have:
            raise ValueError(
                "anakin snapshot layout mismatch — written under a "
                "different config geometry; resuming cold")

        def place(v, sh):
            return (jax.device_put(v, sh) if sh is not None
                    else jnp.asarray(v))

        self.state = {
            k[len("state_"):]: place(
                v, None if self._ast_sh is None
                else self._ast_sh[k[len("state_"):]])
            for k, v in flat.items() if k.startswith("state_")}
        self.ring.arrays = {
            k[len("ring_"):]: place(
                v, None if self._ring_sh is None
                else self._ring_sh[k[len("ring_"):]])
            for k, v in flat.items() if k.startswith("ring_")}
        per = self._per_sh
        self.ring.put_prios(place(flat["per_prios"],
                                  None if per is None else per["prios"]))
        self.ring.put_per_meta(
            place(flat["per_seq_meta"],
                  None if per is None else per["seq_meta"]),
            place(flat["per_first"],
                  None if per is None else per["first"]))
        c = meta.get("counters", {})
        for k in self._COUNTER_FIELDS:
            if k in c:
                setattr(self, k, type(getattr(self, k))(c[k]))

    def _payload_template(self) -> Dict[str, Any]:
        """Shape/dtype template of :meth:`_payload` WITHOUT fetching
        device bytes (for layout validation before overwriting state)."""
        arrays, prios, seq_meta, first = self._handles()
        out: Dict[str, Any] = {}
        for k, v in self.state.items():
            out[f"state_{k}"] = jax.ShapeDtypeStruct(jnp.shape(v), v.dtype)
        for k, v in arrays.items():
            out[f"ring_{k}"] = jax.ShapeDtypeStruct(jnp.shape(v), v.dtype)
        out["per_prios"] = jax.ShapeDtypeStruct(jnp.shape(prios),
                                                prios.dtype)
        out["per_seq_meta"] = jax.ShapeDtypeStruct(jnp.shape(seq_meta),
                                                   seq_meta.dtype)
        out["per_first"] = jax.ShapeDtypeStruct(jnp.shape(first),
                                                first.dtype)
        return out


def run_anakin_loop(learner: Any, plane: AnakinPlane,
                    stop: Optional[Any] = None, tracer: Optional[Any] = None,
                    max_steps: Optional[int] = None,
                    snapshot_fn: Optional[Any] = None,
                    chaos: Optional[Any] = None) -> Dict[str, Any]:
    """The anakin drivetrain: warm-up rollouts until the in-graph ring
    fill passes ``learning_starts``, then pipelined fused super-steps with
    the publish/save cadences of the other device drivetrains
    (:meth:`Learner._superstep_loop` semantics; updates advance by k per
    dispatch).  ``snapshot_fn(step)``, when given, is called at
    ``cfg.replay_snapshot_interval``-second crossings ON this thread (the
    dispatch thread owns the device handles, so periodic full-state
    snapshots cannot race a dispatch).  Returns summary metrics incl. the
    full per-update loss curve.

    ``cfg.dispatch_deadline`` (> 0) bounds each harvest — the loop's one
    blocking device wait — by fetching on a helper thread with a bounded
    join, so even a device wait that NEVER returns cannot hang the loop.
    Two wedge grades, both ending in a clean abort
    (``metrics["dispatch_wedged"]``) instead of hammering a flaky device
    or hanging forever — the Podracer stance: preemption/failure is
    routine, so park the state where ``--resume`` finds it and get out
    of the way:

    - *slow* (the fetch completed but blew the budget — it gets one
      extra budget of grace to come back): drain the pipeline, write a
      full resumable snapshot via ``snapshot_fn``, abort;
    - *hard* (the fetch did not return within twice the budget; the
      chaos ``wedge_dispatch`` site drills this by stalling the fetch
      thread past the grace window):
      abandon the fetch thread — a device wait cannot be interrupted,
      only walked away from — skip the drain (it would block on the same
      device), attempt the snapshot on a BOUNDED helper thread, and
      abort; if even the snapshot attempt times out, the last periodic
      snapshot remains the resume point."""
    import time

    cfg = learner.cfg
    if tracer is None:
        from r2d2_tpu.utils.trace import Tracer
        tracer = Tracer()
    k = cfg.superstep_k
    t0 = time.time()
    updates = learner.num_updates
    target = cfg.training_steps if max_steps is None else updates + max_steps
    losses_all: list = []
    pending: list = []
    last_snap = time.time()
    wedged = False
    hard_wedged = False
    abandoned = threading.Event()   # set when a hard wedge walks away

    def harvest_one() -> None:
        nonlocal wedged, hard_wedged
        flat = pending.pop(0)

        def fetch():
            # the chaos stall lives INSIDE the fetch so the drill
            # exercises the real hard-wedge path: a device wait that
            # does not come back within the budget
            if chaos is not None:
                stall = chaos.dispatch_wedge_seconds()
                if stall > 0:
                    log.warning("chaos: wedging the anakin dispatch "
                                "harvest for %.1fs", stall)
                    time.sleep(stall)
            if abandoned.is_set():
                # the loop declared a hard wedge and may be mid-snapshot:
                # a late harvest would fold this dispatch's counters into
                # state the snapshot thread is reading (while its losses
                # are discarded anyway) — never mutate after abandonment
                return None
            return plane.harvest(flat)

        if cfg.dispatch_deadline <= 0:           # unbounded: fetch inline
            losses_all.extend(fetch().tolist())
            return
        budget = Deadline(cfg.dispatch_deadline)
        box: list = []

        def run():
            try:
                box.append(("ok", fetch()))
            except BaseException as e:           # re-raised on the loop
                box.append(("err", e))

        t = threading.Thread(target=run, name="anakin-harvest",  # graftlint: disable=thread-discipline -- bounded-join fetch; abandoned on a hard wedge BY DESIGN, a Supervisor restart would re-block on the dead device
                             daemon=True)
        t.start()
        t.join(budget.remaining())
        if t.is_alive():
            # over budget — grant one extra budget of grace so a
            # slow-but-COMPLETING fetch lands in the slow grade below
            # (drain + full snapshot) instead of being abandoned
            t.join(cfg.dispatch_deadline)
        if t.is_alive():
            # HARD wedge: the device wait never returned.  It cannot be
            # interrupted, only abandoned — this dispatch's losses are
            # lost, and the drain/snapshot paths must not touch the
            # device unbounded (see the caller)
            log.error(
                "anakin dispatch harvest exceeded its %.1fs budget and "
                "has not returned after as much grace — treating the "
                "device as hard-wedged: abandoning the fetch, "
                "best-effort snapshot, aborting cleanly (resume with "
                "--resume)", cfg.dispatch_deadline)
            abandoned.set()
            wedged = hard_wedged = True
            return
        tag, val = box[0]
        if tag == "err":
            raise val
        losses_all.extend(val.tolist())
        if budget.expired:
            log.error(
                "anakin dispatch harvest took %.1fs (budget %.1fs) — "
                "treating the device as wedged: draining, snapshotting "
                "and aborting cleanly (resume with --resume)",
                budget.elapsed(), cfg.dispatch_deadline)
            wedged = True

    # cfg.transfer_guard: arm the process guard once warm-up ends, so
    # every disallow window in dispatch/harvest/rollout actually runs
    # jax.transfer_guard("disallow") — an undeclared implicit crossing
    # raises TransferGuardTripped instead of silently stalling the
    # stream.  Armed AFTER the rollout warm-up: compile-time constant
    # staging belongs to bring-up, not the steady-state budget.
    from contextlib import ExitStack

    guard_stack = ExitStack()
    guard_armed = False
    try:
        while updates < target and not wedged:
            if stop is not None and stop():
                break
            if not plane.ready:
                plane.rollout_step(learner.state.params)
                continue
            if cfg.transfer_guard and not guard_armed:
                guard_stack.enter_context(TRANSFER_GUARD.arm())
                guard_armed = True
            with tracer.span("learner.step_dispatch", plane.dispatch_no):
                learner.state, flat = plane.dispatch(learner.state)
            pending.append(flat)
            while len(pending) > cfg.superstep_pipeline and not wedged:
                with tracer.span("learner.result_sync"):
                    harvest_one()

            prev, updates = updates, updates + k
            if (learner.param_store is not None
                    and updates // cfg.weight_publish_interval
                    > prev // cfg.weight_publish_interval):
                with tracer.span("learner.publish"):
                    learner._publish()
            if (learner.checkpointer is not None
                    and updates // cfg.save_interval
                    > prev // cfg.save_interval):
                learner.env_steps = plane.env_steps
                learner._save(updates, t0)
            if (snapshot_fn is not None
                    and cfg.replay_snapshot_interval > 0
                    and time.time() - last_snap
                    > cfg.replay_snapshot_interval):
                while pending and not hard_wedged:
                    harvest_one()   # snapshots need no dispatch in flight
                if not hard_wedged:
                    snapshot_fn(updates)
                    last_snap = time.time()
        while pending and not hard_wedged:
            harvest_one()
    finally:
        guard_stack.close()
    if wedged and snapshot_fn is not None:
        # the resumable artifact of the clean abort: full loop state,
        # parked where --resume restores it bit-exact.  On a HARD wedge
        # the snapshot itself reads device handles and can block on the
        # same dead device — bound the attempt instead of trading a hang
        # for a hang (if it times out, the last periodic snapshot stays
        # the resume point)
        if not hard_wedged:
            snapshot_fn(updates)
        else:
            snapped = threading.Event()

            def snap():
                try:
                    snapshot_fn(updates)
                    snapped.set()
                except Exception:
                    log.exception("hard-wedge snapshot attempt failed")

            st = threading.Thread(target=snap, name="anakin-wedge-snap",  # graftlint: disable=thread-discipline -- one best-effort bounded-join snapshot at abort; nothing to supervise after it
                                  daemon=True)
            st.start()
            st.join(max(10.0, 10.0 * cfg.dispatch_deadline))
            if not snapped.is_set():
                log.error("hard-wedge snapshot did not complete in time "
                          "— aborting without a fresh snapshot")

    learner.env_steps = plane.env_steps
    if hard_wedged:
        # the shared epilogue's final checkpoint save device_gets params
        # from the SAME wedged device — bound it like the snapshot above
        # so a dead device cannot turn the clean abort back into a hang
        # (on a timeout the last complete step checkpoint stays the
        # params half of the resume pair)
        fin_box: dict = {}

        def fin():
            try:
                fin_box["metrics"] = learner._finish_device_run(
                    losses_all[-100:], t0, "anakin")
            except Exception:
                log.exception("hard-wedge epilogue save failed")

        ft = threading.Thread(target=fin, name="anakin-wedge-fin",  # graftlint: disable=thread-discipline -- one bounded-join epilogue save at abort; nothing to supervise after it
                              daemon=True)
        ft.start()
        ft.join(max(10.0, 10.0 * cfg.dispatch_deadline))
        metrics = fin_box.get("metrics")
        if metrics is None:
            log.error("hard-wedge final save did not complete in time — "
                      "summarizing without it")
            metrics = dict(
                drivetrain="anakin",
                num_updates=learner.num_updates,
                env_steps=learner.env_steps,
                minutes=learner.start_minutes + (time.time() - t0) / 60.0,
                mean_loss=(float(np.mean(losses_all[-100:]))
                           if losses_all else float("nan")))
    else:
        metrics = learner._finish_device_run(losses_all[-100:], t0,
                                             "anakin")
    metrics["losses"] = losses_all
    metrics["dispatch_wedged"] = wedged
    metrics["env_steps"] = plane.env_steps
    metrics["anakin_frames"] = plane.frames
    metrics["anakin_super_steps"] = plane.super_steps
    metrics["episodes"] = plane.episodes_total
    metrics["mean_episode_return"] = (
        plane.reward_total / plane.episodes_total
        if plane.episodes_total else float("nan"))
    # in-graph greedy eval lane totals (cfg.anakin_eval_interval)
    metrics["eval_episodes"] = plane.eval_episodes_total
    metrics["mean_eval_return"] = (
        plane.eval_return_total / plane.eval_episodes_total
        if plane.eval_episodes_total else float("nan"))
    return metrics
