"""Actors: experience generation against the environment.

Capability-parity with the reference actor (worker.py:500-575) and its
``AgentState`` carrier (model.py:9-24): ε-greedy acting on the recurrent
Q-network, LocalBuffer block assembly with bootstrap Q at truncation,
periodic weight refresh, per-actor ε ladder (train.py:15-17).

TPU-first redesign — the **lockstep vector actor**: instead of N CPU
processes each running an unbatched torch forward (worker.py:528-529), one
driver steps N environments in lockstep and issues a single batched
``act`` call per step.  Batched inference amortizes device dispatch and
keeps the MXU busy (N×512 matmuls instead of N separate 1×512), which is
the standard TPU inference-server architecture.  Each env keeps its own
ε, LocalBuffer, and episode lifecycle, so the learning semantics are
unchanged from the reference fleet.

The bootstrap Q at a block boundary (worker.py:550-554 runs a *second*
forward) is obtained for free here: a boundary finish is deferred one
iteration, and the next iteration's batched Q at the new state is used —
one forward per env step total.

Env stepping can be parallelised across a thread pool (``env_workers``):
each worker owns a contiguous shard of lanes, matching the genuine
CPU-parallelism of the reference's N actor *processes* (train.py:30-34).
ALE releases the GIL inside ``step``, so threads scale for real Atari;
every lane's state (env, LocalBuffer, batched-array row ``i``) is touched
by exactly one worker per iteration, and the block sink is lock-protected
by the replay buffer, so no extra synchronisation is needed.  Block arrival
order at the sink becomes nondeterministic across lanes — use
``env_workers=0`` (serial, the default) where determinism matters.
"""
from __future__ import annotations

import dataclasses
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, List, Optional, Sequence

import jax
import numpy as np

from r2d2_tpu.config import Config
from r2d2_tpu.models.network import R2D2Network
from r2d2_tpu.models.state import zero_state
from r2d2_tpu.replay.block import Block, VectorLocalBuffer
from r2d2_tpu.telemetry.tracing import EVENTS
from r2d2_tpu.utils.store import ParamStore
from r2d2_tpu.utils.trace import maybe_span

# sink(block, priorities, episode_reward_or_None) — direct buffer.add in the
# single-process trainer, queue.put in the process fabric.
BlockSink = Callable[[Block, np.ndarray, Optional[float]], None]


@dataclasses.dataclass
class AgentState:
    """Recurrent-inference state for ONE env (reference: model.py:9-24).

    Arrays are unbatched host numpy; the vector actor keeps the batched
    (N, ...) stack of these instead.
    """
    obs: np.ndarray            # (*obs_shape) uint8
    last_action: np.ndarray    # (A,) float32 one-hot
    last_reward: float
    hidden: np.ndarray         # one state: models.network.state_spec(cfg)

    @classmethod
    def initial(cls, cfg: Config, obs: np.ndarray, action_dim: int
                ) -> "AgentState":
        la = np.zeros(action_dim, np.float32)
        hidden = zero_state(cfg)
        return cls(obs=np.asarray(obs, np.uint8), last_action=la,
                   last_reward=0.0, hidden=hidden)

    def update(self, obs: np.ndarray, action: int, reward: float,
               hidden: np.ndarray) -> None:
        self.obs = np.asarray(obs, np.uint8)
        self.last_action = np.zeros_like(self.last_action)
        self.last_action[action] = 1.0
        self.last_reward = float(reward)
        self.hidden = np.asarray(hidden, self.hidden.dtype)


def fleet_shards(cfg: Config):
    """``([(lo, hi), ...], env_workers_per_fleet)`` — the single
    definition of the fleet split, shared by the thread transport
    (train._build) and the process transport (parallel/actor_procs) so
    lane→fleet assignment and the global ladder-epsilon slices can never
    diverge between transports.  Lanes split contiguously over
    ``cfg.actor_fleets``; the env-worker budget is a per-HOST tuning
    knob, split across the fleets rather than letting each fleet spawn
    its own full pool."""
    F = cfg.actor_fleets
    bounds = np.linspace(0, cfg.num_actors, F + 1).astype(int)
    shards = [(int(lo), int(hi))
              for lo, hi in zip(bounds[:-1], bounds[1:]) if lo < hi]
    workers = (cfg.env_workers + F - 1) // F if cfg.env_workers else 0
    return shards, workers


def resolve_act_device(spec: str):
    """THE decision of where actor inference runs: the one local Device
    an act jit is resolved for (network twin) AND its params are
    committed to (so the un-pinned jit executes there).

    "auto": the host CPU backend when the default backend is an
    accelerator (params get copied host-side once per refresh; every env
    step's dispatch + q fetch then stays on-host) — a deliberate design
    choice for thread/process actors, not a fallback.  "cpu": force it.
    "default": the process's first local device — inference shares the
    learner's chip.

    "auto"/"cpu" in a process whose ``JAX_PLATFORMS`` leaves the CPU
    backend out is an error, not a quiet move onto the accelerator.
    """
    default = jax.local_devices()[0]
    if spec == "default" or (spec == "auto" and default.platform == "cpu"):
        return default
    try:
        return jax.local_devices(backend="cpu")[0]
    except RuntimeError as e:
        raise RuntimeError(
            f"act_device={spec!r} runs actor inference on the host CPU "
            "backend, which this process does not have (JAX_PLATFORMS="
            f"{jax.config.jax_platforms!r}) — add 'cpu' to JAX_PLATFORMS, "
            "or set act_device='default' to act on the "
            f"{default.platform}") from e


def make_act_fn(cfg: Config, net: R2D2Network, *, device=None,
                retrace_name: str = "actor.act",
                retrace_budget: Optional[int] = None):
    """Jitted batched single-step inference:
    (params, obs (B,*obs) u8, last_action (B,A) f32, last_reward (B,) f32,
    hidden (B,2,layers,H)) → (q (B,A) f32, new hidden).

    ``device`` is where the act runs (default: :func:`resolve_act_device`
    of ``cfg.act_device``); planes that own their placement — the session
    tier, the centralized inference service — pass theirs.  The network
    is resolved FOR that device, and the returned callable carries the
    decision so callers commit params where it points and a run can say
    what acted: ``.device``, ``.lstm_impl``, ``.compute_dtype``.

    ``retrace_name``/``retrace_budget`` override the RETRACES guard entry
    (default: one fixed lane batch, budget 2) — the session tier's
    continuous batcher (serving/batcher.py) legitimately traces once per
    bucket shape, so it registers under its own name with a bucket-count
    budget.

    When ``device`` is not a TPU but the learner's network resolved the
    fused Pallas LSTM (TPU-only lowering), acting uses a **scan-impl
    twin** of the network: the two implementations declare identical
    parameters (models/network.py:resolve_lstm_impl), so the published
    param snapshots apply unchanged — the recurrence engine is just
    re-chosen for the platform the jit lowers on.  A CPU act twin also
    computes in float32 regardless of ``cfg.compute_dtype`` (bf16 is
    emulated on CPU; params are float32 either way)."""
    from r2d2_tpu.models.network import create_network, resolve_lstm_impl

    if device is None:
        device = resolve_act_device(cfg.act_device)
    twin = {}
    if (resolve_lstm_impl(cfg) == "pallas"
            and not cfg.pallas_interpret and device.platform != "tpu"):
        twin["lstm_impl"] = "scan"
    if device.platform == "cpu" and cfg.compute_dtype == "bfloat16":
        # bf16 matmuls are emulated (slow) on CPU and params are f32
        # anyway; the f32 twin is ~30% faster per inference call — material
        # when the whole fleet shares one host core with the learner loop
        twin["compute_dtype"] = "float32"
    act_cfg = cfg.replace(**twin) if twin else cfg
    act_net = create_network(act_cfg, net.action_dim) if twin else net

    def act(params, obs, last_action, last_reward, hidden):
        return act_net.apply(params, obs, last_action, last_reward, hidden,
                             method=R2D2Network.act)

    # retrace-guarded (utils/trace.py): one act-fn instance serves one
    # fixed lane batch, so a second trace means shape/dtype drift in the
    # hot loop — the e2e tests assert the budget holds
    from r2d2_tpu.utils.trace import RETRACES

    jitted = jax.jit(RETRACES.wrap(retrace_name, act,
                                   budget=retrace_budget))
    jitted.device = device
    jitted.lstm_impl = resolve_lstm_impl(act_cfg)
    jitted.compute_dtype = act_cfg.compute_dtype
    return jitted


class VectorActor:
    """Steps ``num_envs`` environments in lockstep with batched inference.

    ``epsilons`` gives each lane its ladder ε; lanes run independent
    episode lifecycles (reset, block cut, episode-step cap) exactly as N
    reference actors would (worker.py:516-561).

    ``tracer`` (utils/trace.Tracer, optional) splits a lockstep step into
    spans: ``actor.act`` (the batched act call to its fetched outputs),
    ``actor.env_step`` (the env pool), ``actor.record`` (the vectorised
    bookkeeping) and, once per finished block, ``actor.cut`` (block
    assembly and the sink, i.e. the buffer's ``add``).
    """

    def __init__(self, cfg: Config, envs: Sequence[Any],
                 epsilons: Sequence[float], act_fn, param_store: ParamStore,
                 sink: BlockSink, rng: Optional[np.random.Generator] = None,
                 env_workers: Optional[int] = None, tracer=None):
        assert len(envs) == len(epsilons)
        self.cfg = cfg
        self.tracer = tracer
        self.envs = list(envs)
        self.epsilons = np.asarray(epsilons, np.float64)
        self.act_fn = act_fn
        # serve mode (parallel/inference_service.RemoteActClient, duck-
        # typed to avoid the import cycle): acting is an RPC to the
        # trainer's InferenceService — params and recurrent state live
        # server-side, and lane resets must reach the server so it can
        # zero that lane's hidden.  ``peek`` (when the act fn offers it)
        # is the no-state-advance bootstrap forward the episode-step cap
        # needs; local act fns are pure, so the plain call doubles as it.
        self._act_client = act_fn if hasattr(act_fn, "note_reset") else None
        self._peek_fn = getattr(act_fn, "peek", act_fn)
        self.param_store = param_store
        self.sink = sink
        self.rng = rng or np.random.default_rng(cfg.seed)

        self.N = len(envs)
        if env_workers is None:
            env_workers = cfg.env_workers
        self._pool: Optional[ThreadPoolExecutor] = None
        self._shards: List[range] = [range(self.N)]
        if env_workers > 1 and self.N > 1:
            w = min(env_workers, self.N)
            bounds = np.linspace(0, self.N, w + 1).astype(int)
            self._shards = [range(bounds[j], bounds[j + 1])
                            for j in range(w) if bounds[j] < bounds[j + 1]]
            self._pool = ThreadPoolExecutor(max_workers=len(self._shards),
                                            thread_name_prefix="env")
        self.action_dim = envs[0].action_space.n
        # one preallocated array set for all lanes: per-step recording is a
        # few vectorized writes instead of N×(list appends + array builds)
        self.vbuf = VectorLocalBuffer(cfg, self.action_dim, self.N)
        self.episode_steps = np.zeros(self.N, np.int64)
        self.finish_pending = np.zeros(self.N, bool)  # deferred boundary cut
        # per-lane block start (perf_counter): the cut event's slice spans
        # the block's whole env-step phase, so "env step → cut" renders as
        # one slice on this process's trace track (telemetry/tracing.py)
        self._block_start = np.full(self.N, time.perf_counter())
        self.actor_steps = 0
        self._param_version = 0
        self._params = None
        # where acting was OBSERVED to run (the first act output's device
        # platform; None until then, and in serve mode, where the
        # trainer's InferenceService acts) — train() reports it
        self.act_platform: Optional[str] = None

        # batched AgentState
        self.obs = np.zeros((self.N, *cfg.stored_obs_shape), np.uint8)
        self.last_action = np.zeros((self.N, self.action_dim), np.float32)
        self.last_reward = np.zeros(self.N, np.float32)
        self.hidden = zero_state(cfg, self.N)
        # per-iteration env-step scratch, filled by the (possibly pooled)
        # env stepping and consumed by the vectorized batched update
        self._step_reward = np.zeros(self.N, np.float32)
        self._step_done = np.zeros(self.N, bool)
        for i in range(self.N):
            self._reset_lane(i)

    def _reset_lane(self, i: int) -> None:
        obs, _ = self.envs[i].reset()
        self.obs[i] = np.asarray(obs, np.uint8)
        self.last_action[i] = 0.0
        self.last_reward[i] = 0.0
        self.hidden[i] = 0.0
        self.vbuf.reset_lane(i, self.obs[i])
        self.episode_steps[i] = 0
        self.finish_pending[i] = False
        self._block_start[i] = time.perf_counter()
        if self._act_client is not None:
            self._act_client.note_reset(i)

    def _refresh_params(self) -> None:
        if self._act_client is not None:
            return  # serve mode: weights never leave the trainer
        # params are committed where the act fn was resolved to run
        # (make_act_fn) — with "auto" on an accelerator learner that is
        # the host CPU backend: the reference's actors hold CPU model
        # copies (worker.py:504-507), and it keeps the per-env-step
        # dispatch+q-fetch off the device interconnect entirely.  One
        # params transfer per refresh (every actor_update_interval steps)
        # replaces a round trip per env step — and the placed copy is
        # CACHED per published version, so a multi-fleet actor plane pays
        # the device→host transfer once per publish, not once per fleet.
        # (Multi-host publishes HOST arrays, learner._publish; the same
        # call commits them to this process's act device.)
        version, params = self.param_store.get_placed(self.act_fn.device)
        if params is not None and version != self._param_version:
            self._params = params
            self._param_version = version

    # ------------------------------------------------------------- snapshot
    def snapshot(self) -> dict:
        """Resumable actor state for the full-state checkpoint: exploration
        RNG, per-lane episode lifecycle, batched agent state, the local
        block-assembly buffers, and — for envs that support ALE-style
        ``clone_state()`` — the env emulator state itself.

        Call only while the actor is quiescent (between :meth:`run` bursts
        / after the fabric stopped): the arrays are not lock-protected.
        Lanes whose env cannot snapshot are restored by reset — their
        in-progress episode is the only loss."""
        env_states = []
        for e in self.envs:
            fn = getattr(e, "clone_state", None)
            try:
                env_states.append(fn() if callable(fn) else None)
            except Exception:
                env_states.append(None)
        return dict(
            num_lanes=self.N,
            rng=self.rng.bit_generator.state,
            actor_steps=int(self.actor_steps),
            episode_steps=self.episode_steps.copy(),
            finish_pending=self.finish_pending.copy(),
            agent=dict(obs=self.obs.copy(), last_action=self.last_action.copy(),
                       last_reward=self.last_reward.copy(),
                       hidden=self.hidden.copy()),
            vbuf=self.vbuf.snapshot(),
            env_states=env_states,
        )

    def restore(self, snap: dict) -> None:
        """Resume from a :meth:`snapshot`.  Lanes with a captured env state
        continue their episode (and in-progress block) mid-stream; the
        rest are reset.  Raises ValueError on a lane-count mismatch (the
        caller warns and resumes cold)."""
        if int(snap["num_lanes"]) != self.N:
            raise ValueError(
                f"actor snapshot has {snap['num_lanes']} lanes, this actor "
                f"has {self.N} — resuming cold")
        if self._act_client is not None:
            # lanes resuming mid-episode must not request a server-side
            # hidden zero — the restored server state is authoritative;
            # non-resumable lanes re-note themselves via _reset_lane below
            self._act_client.clear_reset_notes()
        self.rng.bit_generator.state = snap["rng"]
        self.actor_steps = int(snap["actor_steps"])
        self.episode_steps[:] = snap["episode_steps"]
        self.finish_pending[:] = snap["finish_pending"]
        # belt over the sink-unwind ordering above: a deferred cut is only
        # meaningful for a lane with an unfinished block
        self.finish_pending &= np.asarray(snap["vbuf"]["size"]) > 0
        agent = snap["agent"]
        self.obs[:] = agent["obs"]
        self.last_action[:] = agent["last_action"]
        self.last_reward[:] = agent["last_reward"]
        self.hidden[:] = agent["hidden"]
        self.vbuf.load_snapshot(snap["vbuf"])
        for i, st in enumerate(snap["env_states"]):
            fn = getattr(self.envs[i], "restore_state", None)
            if st is not None and callable(fn):
                fn(st)
            else:
                self._reset_lane(i)  # env can't resume: fresh episode

    def _note_cut(self, i: int, block: Block) -> None:
        """Block-lineage hook at every cut: under an armed capture window
        (telemetry/tracing.py) the block gets a fabric-unique trace id
        and the cut emits the lineage flow START — a slice covering the
        block's env-step phase on this process's track.  Disarmed cost:
        one attribute check and one clock read per BLOCK (not per
        step)."""
        now = time.perf_counter()
        if EVENTS.armed:
            block.trace_id = EVENTS.next_trace_id()
            EVENTS.complete("block.env_steps+cut",
                            float(self._block_start[i]),
                            now - float(self._block_start[i]),
                            flow=block.trace_id, fph="s", arg=i)
        self._block_start[i] = now

    def _cut(self, i: int, bootstrap_q: Optional[np.ndarray],
             reset: bool) -> None:
        """Finish lane ``i``'s block and hand it to the sink.  ``reset``
        (episode end, step cap) starts the lane's next episode BEFORE the
        sink call: the finished Block owns copies, never vbuf storage,
        and a sink that unwinds mid-delivery (FleetStopped during
        shutdown) must leave the lane consistent for the shutdown
        snapshot."""
        with maybe_span(self.tracer, "actor.cut"):
            item = self.vbuf.finish(i, bootstrap_q)
            self._note_cut(i, item[0])
            if reset:
                self._reset_lane(i)
            self.sink(*item)

    def _step_shard(self, lanes: range, actions: np.ndarray) -> None:
        """Env-step a contiguous lane shard (the only per-lane Python left
        in the hot loop — the gym API is per-env; ALE releases the GIL in
        ``step`` so shards scale across the thread pool).  Results land in
        the batched scratch arrays; all bookkeeping is vectorized later."""
        for i in lanes:
            obs, reward, terminated, truncated, _ = self.envs[i].step(
                int(actions[i]))
            self.obs[i] = np.asarray(obs, np.uint8)
            self._step_reward[i] = reward
            self._step_done[i] = terminated or truncated

    def close(self) -> None:
        """Shut down the env-worker pool (no-op for serial actors).  The
        actor remains usable afterwards — it falls back to serial stepping
        over ALL lanes."""
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None
            self._shards = [range(self.N)]

    def run(self, max_steps: int, stop: Optional[Callable[[], bool]] = None
            ) -> None:
        """Run ``max_steps`` lockstep iterations (= per-actor env steps)."""
        cfg = self.cfg
        self._refresh_params()
        assert self._params is not None or self._act_client is not None, \
            "ParamStore must hold initial params"

        tr = self.tracer
        for _ in range(max_steps):
            if stop is not None and stop():
                return
            with maybe_span(tr, "actor.act"):
                q, new_hidden = self.act_fn(self._params, self.obs,
                                            self.last_action,
                                            self.last_reward, self.hidden)
                if self.act_platform is None and self._act_client is None:
                    self.act_platform = next(iter(q.devices())).platform
                q = np.asarray(q)
                new_hidden = np.asarray(new_hidden)

            # deferred block-boundary cuts: this iteration's Q at the new
            # state is the bootstrap value (worker.py:550-554 semantics,
            # without the second forward)
            for i in np.nonzero(self.finish_pending)[0]:
                # clear BEFORE the sink call, for the same reason _cut
                # resets before it: vbuf already finished and the flag
                # still set would re-finish an empty lane at resume
                self.finish_pending[i] = False
                self._cut(i, q[i], reset=False)

            explore = self.rng.random(self.N) < self.epsilons
            actions = np.where(explore,
                               self.rng.integers(self.action_dim, size=self.N),
                               q.argmax(axis=1)).astype(np.int64)

            # env stepping: per-lane (gym API), possibly pooled
            with maybe_span(tr, "actor.env_step"):
                if self._pool is None:
                    self._step_shard(self._shards[0], actions)
                else:
                    futures = [self._pool.submit(self._step_shard, shard,
                                                 actions)
                               for shard in self._shards]
                    for f in futures:
                        f.result()

            # all per-step bookkeeping, vectorized over the whole fleet
            # (reference actor body worker.py:537-554, batched)
            with maybe_span(tr, "actor.record"):
                lanes = np.arange(self.N)
                self.last_action[:] = 0.0
                self.last_action[lanes, actions] = 1.0
                self.last_reward[:] = self._step_reward
                np.copyto(self.hidden, new_hidden)
                self.episode_steps += 1
                self.vbuf.add_batch(lanes, actions, self._step_reward,
                                    self.obs, q, new_hidden)

            for i in np.nonzero(self._step_done)[0]:
                self._cut(i, None, reset=True)

            capped = np.nonzero(~self._step_done
                                & (self.episode_steps >= cfg.max_episode_steps)
                                )[0]
            boundary = ~self._step_done & (self.vbuf.sizes()
                                           == cfg.block_length)
            self.finish_pending |= boundary & (self.episode_steps
                                               < cfg.max_episode_steps)
            self._step_done[:] = False

            if capped.size:
                # episode-step cap (rare): the bootstrap must be Q at the
                # post-step state (worker.py:550-554 runs a second forward);
                # one extra batched forward covers all capped lanes; the
                # peek variant (serve mode) must not advance server state
                q_fresh, _ = self._peek_fn(self._params, self.obs,
                                           self.last_action,
                                           self.last_reward, self.hidden)
                q_fresh = np.asarray(q_fresh)
                for i in capped:
                    self._cut(i, q_fresh[i], reset=True)

            self.actor_steps += 1
            if self.actor_steps % cfg.actor_update_interval == 0:
                self._refresh_params()


class Actor(VectorActor):
    """A single-env actor — the reference's unit of deployment
    (worker.py:500-515), as a 1-lane vector actor.  Used by the process
    fabric where each actor owns a thread, and by tests."""

    def __init__(self, cfg: Config, env: Any, epsilon: float, act_fn,
                 param_store: ParamStore, sink: BlockSink,
                 rng: Optional[np.random.Generator] = None):
        super().__init__(cfg, [env], [epsilon], act_fn, param_store, sink,
                         rng=rng)
