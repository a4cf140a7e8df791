"""Training orchestration.

Capability-parity with the reference's process topology (train.py:20-44 and
worker.py:77-138): an actor fleet generating blocks, a replay data plane
with three concurrent planes (block ingest / batch assembly / priority
feedback), a stats log loop, and the learner driving gradient steps — plus
checkpoint/resume, which the reference lacks.

TPU-first redesign — one process, many threads, one device program:

- The reference needs N+2 *processes* because CPython+torch actors are
  GIL-bound.  Here actor inference is a single batched jitted call per
  fleet (r2d2_tpu/actor.py), so ``cfg.actor_fleets`` threads (default 1)
  cover the whole lane set; JAX releases the GIL during device execution,
  so actor inference, env stepping, host batch assembly, H2D prefetch,
  and the learner step genuinely overlap.
- Queues are ``queue.Queue`` handoffs between threads rather than pickle
  pipes between processes — blocks move by reference, zero-copy.
- Weight flow is the versioned ParamStore (no shared-memory mutation).
- Multi-host scaling is the learner mesh (parallel/mesh.py), not more
  host processes: the data plane stays host-local per slice, the gradient
  collectives ride ICI.

``train()`` is the threaded fabric; ``train_sync()`` is a deterministic
single-thread interleaving of the same components (the reference's
semantics with ``num_actors`` lanes and no concurrency) used by the
integration tests and useful for debugging.

``cfg.actor_transport = "process"`` swaps the in-process actor threads
for subprocess fleets (parallel/actor_procs.py): blocks come back over a
preallocated shared-memory channel and weights go out on a versioned
publication queue — the reference's N-process acting topology
(train.py:30-34) for GIL-bound envs / multi-core hosts; the rest of the
fabric (replay, learner, supervision) is unchanged.  On top of it,
``cfg.actor_inference = "serve"`` centralizes acting (Sebulba/Seed-RL):
the fleets stop running the network and every env step becomes an RPC to
an InferenceService fabric thread that batches across all fleets and
runs one device act per step (parallel/inference_service.py).
"""
from __future__ import annotations

import collections
import functools
import logging
import os
import queue
import signal
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from r2d2_tpu.actor import VectorActor, fleet_shards, make_act_fn
from r2d2_tpu.checkpoint import Checkpointer
from r2d2_tpu.config import Config
from r2d2_tpu.envs import create_env
from r2d2_tpu.learner.learner import Learner
from r2d2_tpu.learner.step import create_train_state, target_syncs
from r2d2_tpu.models.network import (create_network, init_params,
                                     stem_fused)
from r2d2_tpu.parallel.mesh import make_mesh
from r2d2_tpu.replay.replay_buffer import ReplayBuffer
from r2d2_tpu.telemetry import Telemetry, format_entry
from r2d2_tpu.utils.math import epsilon_ladder
from r2d2_tpu.utils.store import ParamStore
from r2d2_tpu.utils.supervisor import Heartbeat, Supervisor
from r2d2_tpu.utils.trace import (
    SetupClock,
    Tracer,
    device_memory,
    device_profile,
    maybe_phase,
)

log = logging.getLogger(__name__)

EnvFactory = Callable[[Config, int], Any]


def _default_env_factory(cfg: Config, seed: int):
    return create_env(cfg, noop_start=True, seed=seed)


def _build(cfg: Config, env_factory: EnvFactory, use_mesh: bool,
           checkpoint_dir: Optional[str], resume: bool,
           tracer: Optional[Tracer] = None,
           setup: Optional[SetupClock] = None):
    """Common bring-up: envs, net, state (maybe restored), buffer, stores.
    ``tracer`` goes to the in-process buffer and the thread actors, whose
    spans split a block's write and a lockstep step; ``setup`` times the
    state and the ring as the phases ``setup.state`` and ``setup.ring``.

    The single-process drivetrain is exactly the one ``cfg`` names: a
    ``device_replay`` ring that does not fit the device is a ValueError
    (:func:`_checked_ring_layout`), never a quiet move to host replay.
    """
    if cfg.actor_transport == "process":
        # the fleets own the envs in their subprocesses; the trainer only
        # needs the action space to size the network/replay layouts
        probe = env_factory(cfg, cfg.seed)
        action_dim = probe.action_space.n
        try:
            probe.close()
        except Exception:
            pass
        envs = []
    else:
        envs = [env_factory(cfg, cfg.seed + i) for i in range(cfg.num_actors)]
        action_dim = envs[0].action_space.n
    with maybe_phase(setup, "setup.state"):
        net = create_network(cfg, action_dim)
        params = init_params(cfg, net, jax.random.PRNGKey(cfg.seed))
        state = create_train_state(cfg, params)

        checkpointer = (Checkpointer(checkpoint_dir,
                                     keep=cfg.keep_checkpoints)
                        if checkpoint_dir else None)
        start_env_steps, start_minutes = 0, 0.0
        if (checkpointer is not None and resume
                and checkpointer.latest_step() is not None):
            from r2d2_tpu.checkpoint import check_arch_compat

            check_arch_compat(cfg, checkpointer.peek_meta())
            state, meta = checkpointer.restore(jax.device_get(state))
            start_env_steps = int(meta.get("env_steps", 0))
            start_minutes = float(meta.get("minutes", 0.0))

    mesh = make_mesh(cfg) if use_mesh else None
    # ONE sharding table per bring-up: every sharding constructor (the
    # pjit steps, the DeviceRing slot/PER layouts, checkpoint
    # re-placement) resolves through it (parallel/sharding.py).  On a
    # 1-device trivial mesh it degenerates to all-replicated.
    from r2d2_tpu.parallel.mesh import trivial_mesh
    from r2d2_tpu.parallel.sharding import ShardingTable

    table = ShardingTable(mesh if mesh is not None else trivial_mesh(), cfg)
    if mesh is not None:
        from r2d2_tpu.parallel.distributed import host_batch_size

        # cfg.batch_size is the GLOBAL batch; this host samples only its
        # dp-axis share from its local buffer (single-process: the whole
        # batch)
        host_bs = host_batch_size(cfg, mesh)
    else:
        host_bs = cfg.batch_size
    param_store = ParamStore()
    ring = None
    if cfg.device_replay and jax.process_count() == 1:
        from r2d2_tpu.replay.device_ring import DeviceRing

        with maybe_phase(setup, "setup.ring"):
            layout = _checked_ring_layout(cfg, action_dim, mesh)
            ring = (DeviceRing(cfg, action_dim, table=table, layout=layout)
                    if mesh is not None else DeviceRing(cfg, action_dim))
    elif cfg.device_replay:
        # multi-host: each host owns the slot slabs of its dp groups — a
        # dp-layout ring over its LOCAL submesh.  The learner stitches the
        # global ring view per super-step (Learner._run_device_multihost).
        import warnings

        if mesh is None or cfg.device_ring_layout == "replicated":
            warnings.warn(
                "multi-host device_replay needs the global mesh and a "
                "sharded ring (device_ring_layout 'auto'/'dp'); using "
                "host staging instead", stacklevel=2)
        else:
            from r2d2_tpu.parallel.distributed import local_mesh, sync_counter
            from r2d2_tpu.replay.device_ring import DeviceRing
            from r2d2_tpu.replay.replay_buffer import data_bytes

            lmesh = local_mesh(mesh)
            dp_local = lmesh.shape["dp"]
            need, cap = data_bytes(cfg, action_dim), _device_memory_bytes()
            shapes_ok = not (cfg.num_blocks % dp_local
                             or cfg.batch_size % mesh.shape["dp"]
                             or host_bs % dp_local)
            fits = cap is None or need // dp_local <= 0.8 * cap
            # COLLECTIVE decision: run_device's multi-host loop and run's
            # host staging issue different collective sequences, so every
            # process must pick the same path — one host failing its local
            # guard (heterogeneous HBM headroom, uneven device counts)
            # must push the whole pod to host staging, not deadlock it
            ok = sync_counter(int(shapes_ok and fits), reduce="min") > 0
            if ok:
                with maybe_phase(setup, "setup.ring"):
                    ring = DeviceRing(cfg, action_dim,
                                      table=ShardingTable(lmesh, cfg),
                                      layout="dp")
            else:
                warnings.warn(
                    "multi-host device_replay disabled (on at least one "
                    f"host): shapes_ok={shapes_ok} (num_blocks "
                    f"{cfg.num_blocks} vs local dp {dp_local}, batch "
                    f"{cfg.batch_size} vs dp {mesh.shape['dp']}), "
                    f"fits={fits} (ring {need / dp_local / 1e9:.1f} GB "
                    "per device); using host staging instead",
                    stacklevel=2)
    learner = Learner(cfg, net, state, mesh=mesh, param_store=param_store,
                      checkpointer=checkpointer,
                      start_env_steps=start_env_steps,
                      start_minutes=start_minutes, table=table)
    replay_plane = None
    if cfg.replay_transport == "socket":
        # cross-host replay fabric (parallel/replay_net.py): the shard
        # RPCs travel as length-framed CRC'd TCP messages, so the K
        # shards may be remote `r2d2_tpu replay-shard` servers
        # (cfg.replay_hosts) or plane-spawned loopback processes (the
        # tier-1-testable default).  Same facade as the shm plane;
        # config validation already rejected device_replay/anakin here.
        from r2d2_tpu.parallel.replay_net import NetShardedReplayPlane

        with maybe_phase(setup, "setup.ring"):
            buffer = NetShardedReplayPlane(
                cfg, action_dim, rng=np.random.default_rng(cfg.seed))
        replay_plane = buffer
    elif cfg.replay_shards > 1:
        # sharded replay plane (parallel/replay_shards.py): K owner
        # processes each run the ReplayBuffer core over their slot
        # slice; this coordinator facade fills the buffer role in the
        # fabric (add/ready/sample_batch/update_priorities/stats/
        # snapshots).  Processes spawn in train() at plane start, like
        # the fleet plane.  Config validation already rejected
        # device_replay here, so `ring` is None on this path.
        from r2d2_tpu.parallel.replay_shards import ShardedReplayPlane

        with maybe_phase(setup, "setup.ring"):
            buffer = ShardedReplayPlane(
                cfg, action_dim, rng=np.random.default_rng(cfg.seed))
        replay_plane = buffer
    else:
        with maybe_phase(setup, "setup.ring"):
            buffer = ReplayBuffer(cfg, action_dim,
                                  rng=np.random.default_rng(cfg.seed),
                                  device_ring=ring, tracer=tracer)
    buffer.env_steps = start_env_steps
    epsilons = [epsilon_ladder(i, cfg.num_actors, cfg.base_eps, cfg.eps_alpha)
                for i in range(cfg.num_actors)]
    members = None
    if cfg.population_spec:
        # population plane (league/population.py; Config validation
        # already pinned actor_transport="process" and one fleet per
        # member): member configs resolve here, the global epsilon list
        # becomes per-member ladder slices, and every member env is
        # probed for action-space parity — one Q-head serves the whole
        # population, so a member env with a different action set is a
        # config error, not a runtime shape crash
        from r2d2_tpu.league.population import (
            build_members,
            population_epsilons,
        )

        members = build_members(cfg)
        epsilons = population_epsilons(cfg, members)
        for m in members:
            if m.cfg.game_name == cfg.game_name:
                continue
            probe = env_factory(m.cfg, m.cfg.seed)
            member_dim = probe.action_space.n
            try:
                probe.close()
            except Exception:
                pass
            if member_dim != action_dim:
                raise ValueError(
                    f"population member {m.member_id} ({m.name}): env "
                    f"{m.cfg.game_name!r} has action_dim {member_dim} "
                    f"but the base env has {action_dim} — one Q-head "
                    "serves the whole population")
    plane = None
    if cfg.actor_transport == "process":
        # subprocess fleets (parallel/actor_procs): constructed here, but
        # processes only spawn in train() once the fabric is up
        from r2d2_tpu.parallel.actor_procs import ProcessFleetPlane

        plane = ProcessFleetPlane(cfg, action_dim, env_factory, epsilons,
                                  members=members)
        actors: List[VectorActor] = []
    else:
        act_fn = make_act_fn(cfg, net)
        # actor_fleets independent lockstep fleets over contiguous lane
        # slices (actor.fleet_shards — the split shared with the process
        # transport): the ladder epsilons stay GLOBAL (lane i keeps
        # epsilon_ladder(i, N) regardless of fleet count — the reference's
        # per-actor ladder, train.py:15-17), and each fleet gets its own
        # RNG stream and thread so one fleet's env stepping overlaps
        # another's batched inference
        shards, fleet_workers = fleet_shards(cfg)
        actors = [
            VectorActor(cfg, envs[lo:hi], epsilons[lo:hi], act_fn,
                        param_store, sink=buffer.add,
                        env_workers=fleet_workers,
                        rng=np.random.default_rng(
                            cfg.seed + 7919 + 104729 * f),
                        tracer=tracer)
            for f, (lo, hi) in enumerate(shards)
        ]
    # full-state resume: a warm replay ring + resumable actor state saved
    # by a previous run's drain-then-save exit (checkpoint.save_replay).
    # Loaded AFTER everything is built so a failure here degrades to the
    # plain learner-state resume above instead of killing bring-up.
    restored_replay = False
    if checkpointer is not None and resume:
        rep = checkpointer.restore_replay()
        if rep is not None and ring is None:
            import warnings

            meta_r, ring_path, actor_snaps = rep
            try:
                buffer.read_state(ring_path, meta_r)
                restored_replay = True
            except (ValueError, OSError) as e:
                warnings.warn(f"replay snapshot not restored: {e}",
                              stacklevel=2)
            if restored_replay and actor_snaps:
                if plane is not None:
                    plane.set_restore_snapshots(actor_snaps)
                else:
                    for a, snap in zip(actors, actor_snaps):
                        if snap is None:
                            continue
                        try:
                            a.restore(snap)
                        except ValueError as e:
                            warnings.warn(f"actor snapshot skipped: {e}",
                                          stacklevel=2)
        elif rep is not None:
            import warnings

            warnings.warn(
                "a replay snapshot exists but this run uses device_replay "
                "— replay state lives in HBM and is not restored (resuming "
                "with a cold ring)", stacklevel=2)
    return dict(envs=envs, action_dim=action_dim, net=net,
                learner=learner, buffer=buffer, actors=actors,
                actor=actors[0] if actors else None, plane=plane,
                replay_plane=replay_plane, param_store=param_store,
                restored_replay=restored_replay,
                checkpointer=checkpointer, host_bs=host_bs, ring=ring)


def _device_memory_bytes() -> Optional[int]:
    """The smallest ``bytes_limit`` over this process's devices, or None
    when the backend keeps no memory stats (the CPU client)."""
    limits = [m["bytes_limit"] for m in device_memory()]
    return min(limits) if limits else None


def _tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _checked_ring_layout(cfg: Config, action_dim: int, mesh,
                         state_bytes: int = 0) -> str:
    """Resolve ``cfg.device_ring_layout`` for this bring-up and REFUSE a
    ring that does not fit: the caller asked for the device-replay
    drivetrain, and quietly running host replay under its name would
    hide the device from every metric the run reports.  The budget is 80%
    of the device's limit (headroom for params, activations and staged
    slots), less what a train state that is itself most of a chip takes
    with its gradients (``state_bytes``: weights, target, Adam moments;
    gradients a quarter more) — with the LSTM networks that is under a
    hundredth of the device and 80% stands."""
    from r2d2_tpu.replay.device_ring import device_bytes, resolve_layout
    from r2d2_tpu.replay.replay_buffer import _available_host_bytes

    need, dev_cap = device_bytes(cfg, action_dim), _device_memory_bytes()
    # "auto" shards the slot axis over dp when the ring outgrows one
    # device's HBM.  Only genuine per-device stats (dev_cap) may trigger
    # auto-sharding or split the accounting: a backend without memory
    # stats (the CPU client) keeps "device" memory in host RAM, where
    # every shard shares one memory and the whole ring is the burden
    layout = resolve_layout(cfg, mesh, need, dev_cap)
    shards = (mesh.shape["dp"]
              if layout == "dp" and dev_cap is not None else 1)
    cap = dev_cap if dev_cap is not None else _available_host_bytes()
    budget = None if cap is None else min(0.8 * cap,
                                          cap - 1.25 * state_bytes)
    if cap is not None and need // shards > budget:
        fits = (int(max(budget, 0) * shards // (need // cfg.num_blocks))
                * cfg.block_length)
        raise ValueError(
            f"device_replay ring needs {need // shards / 1e9:.2f} GB per "
            f"device (layout={layout}, buffer_capacity="
            f"{cfg.buffer_capacity}) but the device's limit is "
            f"{cap / 1e9:.2f} GB and the ring may take "
            f"{max(budget, 0) / 1e9:.2f} GB of it (80%, less a train "
            f"state of {state_bytes / 1e9:.2f} GB with its gradients) — "
            f"buffer_capacity={fits} fits (or shard the ring over more "
            "devices with --mesh)")
    return layout


class _HostScaffold:
    """Host-side scaffolding shared by every trainer variant (the
    extraction ROADMAP item 2 flagged, done before a third variant
    appears).

    Owns the pieces ``train()`` and ``_train_anakin`` used to duplicate:
    the stop predicate (event + wall-clock deadline + supervisor failure),
    the SIGTERM/SIGINT drain-then-save handlers, the learner Heartbeat and
    its stall-watchdog loop, the bounded in-memory log ring, the telemetry
    plane (registry/JSONL/exporter) with the supervisor's give-up stamping
    wired in, and the quiesce/teardown order.  Trainer-specific policy —
    the /healthz verdict, the log-loop body, extra fabric loops, chaos
    wiring — stays in the trainer; the scaffold only runs what it is
    handed."""

    def __init__(self, cfg: Config, checkpoint_dir: Optional[str],
                 max_wall_seconds: Optional[float] = None,
                 max_thread_restarts: int = 3,
                 signal_msg: str = "draining fabric, then saving full state",
                 watch_label: str = "learner",
                 stop_fn: Optional[Callable[[], bool]] = None):
        self.cfg = cfg
        # optional caller-provided stop predicate (embedders, tests, the
        # sweep driver): polled alongside the event/deadline/supervisor
        # checks — a programmatic drain-then-save without a signal
        self._stop_fn = stop_fn
        self.checkpoint_dir = checkpoint_dir
        self.telemetry = Telemetry(cfg, checkpoint_dir)
        # learning-health plane (telemetry/learnhealth.py): the alert
        # engine owns the declarative rule set, the learnhealth.alert
        # counters, the durable alerts.jsonl stream and /alertz; the
        # monitor absorbs harvested losses + in-graph diag vectors on
        # the learner thread and trips a clean fabric stop on
        # non-finite numerics (stop() below polls it)
        from r2d2_tpu.telemetry.learnhealth import (
            AlertEngine,
            LearnHealthMonitor,
        )

        self.alerts = AlertEngine(
            cfg, self.telemetry.registry,
            log_dir=(os.path.join(checkpoint_dir, "telemetry")
                     if checkpoint_dir else None))
        self.learnhealth = LearnHealthMonitor(cfg, engine=self.alerts)
        # on-demand capture plane (telemetry/tracing.py), armed by
        # tracing_loops(); exporter_loops() then exposes its /tracez +
        # /profilez trigger routes next to /alertz
        self.trace_slab = None
        self.trace_ctl = None
        self.profile_ctl = None
        self.trace_routes: Dict[str, Any] = {"/alertz": self.alerts.route}
        # a thread exhausting its restart budget is stamped straight into
        # the registry by the supervisor itself — the log loop (the usual
        # absorption path) may be the very thread that died
        self.supervisor = Supervisor(
            max_restarts=max_thread_restarts,
            on_giveup=lambda name: self.telemetry.registry.inc(
                "supervisor.gaveup", thread=name))
        self.stop_event = threading.Event()
        self.deadline = (time.time() + max_wall_seconds
                         if max_wall_seconds else None)
        # learner liveness: the learner beats through every stop poll
        # (loop iterations AND queue waits), so a stale heartbeat means a
        # genuinely frozen thread — wedged collective, dead interconnect,
        # chaos freeze — not a slow batch
        self.heartbeat = Heartbeat()
        self.stall = {"stalled": False}
        # bounded ring (cfg.log_history_cap): the JSONL run log is the
        # durable record; this is the in-memory tail metrics["logs"]
        # returns
        self.logs: collections.deque = collections.deque(
            maxlen=cfg.log_history_cap)
        self._signal_msg = signal_msg
        self._watch_label = watch_label
        self._prev_handlers: Dict[int, Any] = {}

    def stop(self) -> bool:
        return (self.stop_event.is_set() or self.supervisor.any_failed
                or (self.deadline is not None
                    and time.time() > self.deadline)
                # non-finite loss/grads: stop cleanly (drain-then-save)
                # instead of training on through poisoned numerics —
                # the nonfinite alert already fired at trip time
                or self.learnhealth.tripped
                or (self._stop_fn is not None and self._stop_fn()))

    def record_learnhealth(self, entry: Dict[str, Any],
                           replay_health: Optional[Dict[str, Any]] = None
                           ) -> None:
        """The log loops' shared learnhealth step: stamp the monitor
        snapshot (+ replay data-health) into the entry, then run the
        alert engine over it; the entry carries the cumulative alert
        counts for /statusz, the JSONL record and r2d2_top."""
        entry["learnhealth"] = self.learnhealth.snapshot()
        if replay_health is not None:
            entry["replay_health"] = replay_health
        self.alerts.evaluate(dict(
            learnhealth=entry["learnhealth"], replay=replay_health,
            training_steps=entry.get("training_steps", 0)))
        entry["alerts"] = self.alerts.counts()

    def install_signals(self) -> None:
        """SIGTERM/SIGINT request a drain-then-save shutdown.  Signals
        only reach the main thread; a trainer driven from a worker thread
        (tests, sweep) skips the hook.  Handlers stay installed through
        the post-drain save — a second SIGTERM during the drain must keep
        requesting a clean stop, not kill the process mid-write — and
        :meth:`close` restores them on every exit path."""
        if threading.current_thread() is not threading.main_thread():
            return

        def _on_signal(signum, frame):
            log.warning("signal %d: %s", signum, self._signal_msg)
            self.stop_event.set()

        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                self._prev_handlers[sig] = signal.signal(sig, _on_signal)
            except (ValueError, OSError):  # exotic embedding: no signals
                pass

    def _learner_watch(self) -> None:
        cfg = self.cfg
        poll = min(0.05, cfg.learner_stall_timeout / 4)
        while not self.stop():
            time.sleep(poll)
            if self.heartbeat.age() > cfg.learner_stall_timeout:
                self.stall["stalled"] = True
                log.error("%s heartbeat stale for %.1fs (budget %.1fs): "
                          "declaring a stall and stopping the fabric",
                          self._watch_label, self.heartbeat.age(),
                          cfg.learner_stall_timeout)
                self.stop_event.set()
                return

    def watch_loops(self) -> List[Any]:
        """The heartbeat stall-watchdog loop (empty when disabled)."""
        return ([("learner_watch", self._learner_watch)]
                if self.cfg.learner_stall_timeout > 0 else [])

    def _telemetry_dir(self) -> str:
        """Where trace/profile dumps land: next to the JSONL run log, or
        a one-shot temp dir for checkpoint-less runs."""
        if self.checkpoint_dir:
            return os.path.join(self.checkpoint_dir, "telemetry")
        if not hasattr(self, "_tmp_telemetry_dir"):
            import tempfile

            self._tmp_telemetry_dir = tempfile.mkdtemp(
                prefix="r2d2_telemetry_")
        return self._tmp_telemetry_dir

    def tracing_loops(self, num_slots: int,
                      step_fn: Callable[[], int]) -> List[Any]:
        """Build the run's cross-process trace slab (one event-ring slot
        per fabric process — trainer + fleets + replay shards), attach
        the process-wide recorder to slot 0, arm the capture controllers
        (``/tracez`` trace windows, ``/profilez`` device profiles,
        ``cfg.trace_steps`` boot-time capture), and return the
        supervised capture loop.  Call BEFORE :meth:`exporter_loops` so
        the trigger routes are registered on the exporter."""
        from r2d2_tpu.telemetry.tracing import (
            EVENTS,
            ProfileController,
            TraceController,
            TraceSlab,
        )

        cfg = self.cfg
        self.trace_slab = TraceSlab(num_slots, cfg.trace_buffer_events)
        EVENTS.attach(self.trace_slab.writer_info(0, 0, "trainer"))
        out_dir = self._telemetry_dir()
        self.trace_ctl = TraceController(self.trace_slab, step_fn, out_dir,
                                         tracer=EVENTS)
        self.profile_ctl = ProfileController(out_dir)

        def tracez(params: Dict[str, str]):
            if "steps" in params:
                res = self.trace_ctl.arm(int(params["steps"]))
                return (409 if "error" in res else 200), res
            return 200, self.trace_ctl.status()

        def profilez(params: Dict[str, str]):
            if "secs" in params:
                res = self.profile_ctl.arm(float(params["secs"]))
                return (409 if "error" in res else 200), res
            return 200, self.profile_ctl.status()

        self.trace_routes.update({"/tracez": tracez,
                                  "/profilez": profilez})
        if cfg.trace_steps > 0:
            self.trace_ctl.arm(cfg.trace_steps)

        def capture_loop():
            while not self.stop():
                self.trace_ctl.poll()
                self.profile_ctl.poll()
                EVENTS.flush()       # trainer ring publishes like any
                time.sleep(0.1)      # other writer's cadence
            # a window still open at shutdown (short run, stop mid-
            # capture) is force-closed so its dump is never lost
            self.trace_ctl.poll(force=True)

        return [("capture", capture_loop)]

    def exporter_loops(self, healthz: Callable[[], Dict[str, Any]]
                       ) -> List[Any]:
        """Arm the HTTP exporter around the trainer's healthz verdict.
        The loop is close-driven, NOT stop-driven: a stalled/stopping run
        must stay scrapeable (that is when /healthz matters most); quiesce
        closes the exporter before joining it."""
        exporter = self.telemetry.serve(healthz, routes=self.trace_routes)
        if exporter is None:    # telemetry_port == 0
            return []

        def telemetry_loop():
            while not exporter.closed:
                try:
                    exporter.handle_once()
                except (OSError, ValueError):
                    return        # server closed under a late poll

        return [("telemetry", telemetry_loop)]

    def start(self, loops) -> None:
        for name, loop in loops:
            self.supervisor.start(name, loop)

    def quiesce(self) -> None:
        """Stop, then close the exporter BEFORE join_all — the telemetry
        loop exits on close, and a joined-but-serving exporter would stall
        the teardown — then reap the fabric threads."""
        self.stop_event.set()
        self.telemetry.close_exporter()
        self.supervisor.join_all(timeout=5.0)

    def close(self) -> None:
        self.alerts.close()
        self.telemetry.close()
        if self.trace_slab is not None:
            # after the planes' shutdown (train's finally order): every
            # subprocess writer is gone, so the unlink is safe
            from r2d2_tpu.telemetry.tracing import EVENTS

            EVENTS.detach()
            self.trace_slab.close()
        for sig, handler in self._prev_handlers.items():
            try:
                signal.signal(sig, handler)
            except (ValueError, OSError):
                pass


# --------------------------------------------------------------------------
# deterministic single-thread trainer (integration-test / debug path)
# --------------------------------------------------------------------------

def train_sync(cfg: Config, env_factory: EnvFactory = _default_env_factory,
               checkpoint_dir: Optional[str] = None, resume: bool = False,
               actor_steps_per_update: int = 4,
               use_mesh: bool = False) -> Dict[str, Any]:
    """Deterministic interleaving: fill the buffer to ``learning_starts``,
    then alternate ``actor_steps_per_update`` lockstep actor iterations
    with one learner update, applying priority feedback inline.

    Returns metrics incl. the per-update loss curve and episode returns.
    """
    # prefetch would run batch_source (which steps the actor) on a thread,
    # and env workers / multiple fleets would make block arrival order racy
    # — all break the deterministic interleaving this function promises;
    # device_replay's k-step dispatch granularity likewise, and a nonzero
    # result pipeline would defer priority feedback (this path applies it
    # after every single update)
    cfg = cfg.replace(prefetch_batches=0, env_workers=0, actor_fleets=1,
                      device_replay=False, in_graph_per=False,
                      superstep_pipeline=0, actor_transport="thread",
                      actor_inference="local", replay_shards=1,
                      # population members are process fleets and the
                      # eval sidecar is a fabric subprocess — neither
                      # exists in the deterministic single-thread path
                      population_spec="", league_eval=False,
                      # no monitor/alert engine exists here either:
                      # armed diagnostics would pay the in-graph ΔQ
                      # re-unroll only to be discarded at harvest
                      learnhealth_interval=0)
    sys = _build(cfg, env_factory, use_mesh, checkpoint_dir, resume)
    actor: VectorActor = sys["actor"]
    buffer: ReplayBuffer = sys["buffer"]
    learner: Learner = sys["learner"]

    while not buffer.ready:
        actor.run(max_steps=cfg.block_length)

    losses: List[float] = []
    episode_returns: List[float] = []

    def batch_source():
        actor.run(max_steps=actor_steps_per_update)
        return buffer.sample_batch(sys["host_bs"])

    def priority_sink(idxes, priorities, old_ptr, loss):
        buffer.update_priorities(idxes, priorities, old_ptr, loss)
        losses.append(loss)
        s = buffer.stats()
        if s["num_episodes"]:
            episode_returns.append(s["episode_reward"] / s["num_episodes"])

    metrics = learner.run(batch_source, priority_sink)
    metrics.update(losses=losses, episode_returns=episode_returns,
                   buffer_size=len(buffer),
                   final_params=learner.state.params)
    return metrics


# --------------------------------------------------------------------------
# anakin trainer: ONE compiled on-device program (learner/anakin.py)
# --------------------------------------------------------------------------

def _train_anakin(cfg: Config, tracer: Tracer, setup: SetupClock,
                  checkpoint_dir: Optional[str] = None,
                  resume: bool = False, use_mesh: bool = False,
                  max_wall_seconds: Optional[float] = None,
                  verbose: bool = True,
                  log_sink: Optional[Callable[[Dict[str, Any]], None]] = None,
                  profile_dir: Optional[str] = None,
                  stop_fn: Optional[Callable[[], bool]] = None
                  ) -> Dict[str, Any]:
    """``actor_transport="anakin"``: the whole training loop — pure-JAX
    batched env, in-graph actor, in-graph replay writes, train steps —
    is one jitted program (the Podracer "Anakin" architecture,
    learner/anakin.py).  The host dispatches it and reads a (k + 5)-float
    result vector back; there are no actor/sample/priority threads at all
    (the transport is single-process by construction).

    What carries over from the threaded fabric: the telemetry plane
    (registry + JSONL run log + HTTP exporter + the shared console line),
    SIGTERM/SIGINT drain-then-save with full-state resume (the snapshot
    holds the ENTIRE on-device loop state: ring, PER leaves, env
    phase/RNGs, agent LSTM carry, local buffers — ``--resume`` continues
    bit-exact), the learner heartbeat watchdog, and checkpoint cadences.
    Chaos: the fleet/shm fault sites don't exist in this mode, but the
    ``wedge_dispatch`` site does — it stalls one fused-dispatch harvest,
    and ``cfg.dispatch_deadline`` (> 0) turns a dispatch that blows its
    budget into a snapshot-then-clean-abort
    (``metrics["dispatch_wedged"]``) instead of training on through a
    flaky device.  Not supported in this mode (documented in
    docs/OPERATIONS.md): meshes (single-device v1) and custom env
    factories (the env must be jittable; v1 ships the fake env — any
    future jittable env plugs in at ``envs/anakin.AnakinFakeEnv``'s
    four-method surface).
    """
    from r2d2_tpu.learner.anakin import AnakinPlane, run_anakin_loop
    from r2d2_tpu.replay.device_ring import DeviceRing

    if cfg.game_name != "Fake":
        import warnings

        warnings.warn(
            f"anakin transport needs a jittable env; substituting the "
            f"pure-JAX {cfg.anakin_env!r} env for {cfg.game_name!r} "
            "(cfg.anakin_env selects it)", stacklevel=2)
    # the fused program IS device replay with in-graph PER — flip the
    # flags so the ring/PER state and the train-step composition build
    # exactly as the in_graph_per drivetrain's (effective-config pattern)
    cfg = cfg.replace(device_replay=True, in_graph_per=True)
    action_dim = 4  # both anakin envs' action set (envs/anakin.py)
    with setup.phase("setup.state"):
        net = create_network(cfg, action_dim)
        params = init_params(cfg, net, jax.random.PRNGKey(cfg.seed))
        state = create_train_state(cfg, params)
        del params  # the state holds copies; a large model's would not fit
        checkpointer = (Checkpointer(checkpoint_dir,
                                     keep=cfg.keep_checkpoints)
                        if checkpoint_dir else None)
        start_env_steps, start_minutes = 0, 0.0
        if (checkpointer is not None and resume
                and checkpointer.latest_step() is not None):
            from r2d2_tpu.checkpoint import check_arch_compat

            check_arch_compat(cfg, checkpointer.peek_meta())
            state, meta = checkpointer.restore(jax.device_get(state))
            start_env_steps = int(meta.get("env_steps", 0))
            start_minutes = float(meta.get("minutes", 0.0))

    # multi-chip anakin (ROADMAP item 2): under --mesh the fused program
    # compiles through the ONE table-driven sharded entry point — lanes,
    # carry and local buffers over dp, params/moments per the table,
    # ring/PER per the resolved ring layout (the Podracer
    # replicate-the-fused-program scale-out).  Without --mesh the
    # single-device path is unchanged.
    mesh = make_mesh(cfg) if use_mesh else None
    table = None
    with setup.phase("setup.ring"):
        layout = _checked_ring_layout(cfg, action_dim, mesh,
                                      state_bytes=_tree_bytes(state))
        if mesh is not None:
            from r2d2_tpu.parallel.sharding import ShardingTable

            table = ShardingTable(mesh, cfg)
            ring = DeviceRing(cfg, action_dim, table=table, layout=layout)
        else:
            ring = DeviceRing(cfg, action_dim)
    # no ParamStore: the fused loop acts on the CURRENT params in-graph
    # and nothing else consumes published snapshots in this mode (no
    # fleets, pump, or inference service) — publishing would just run a
    # jitted whole-tree param copy per cadence for no reader
    learner = Learner(cfg, net, state, mesh=mesh, table=table,
                      checkpointer=checkpointer,
                      start_env_steps=start_env_steps,
                      start_minutes=start_minutes)
    plane = AnakinPlane(cfg, net, action_dim, ring,
                        start_env_steps=start_env_steps, table=table,
                        state_template=learner.state)

    restored_anakin = False
    if checkpointer is not None and resume:
        rep = checkpointer.restore_replay()
        if rep is not None:
            import warnings

            meta_r, ring_path, _ = rep
            if meta_r.get("kind") == "anakin":
                try:
                    plane.read_state(ring_path, meta_r)
                    restored_anakin = True
                except (ValueError, OSError) as e:
                    warnings.warn(f"anakin snapshot not restored: {e}",
                                  stacklevel=2)
            else:
                warnings.warn(
                    "a replay snapshot exists but it is not an anakin "
                    "loop snapshot (different transport) — resuming with "
                    "a cold ring", stacklevel=2)

    scaffold = _HostScaffold(
        cfg, checkpoint_dir, max_wall_seconds=max_wall_seconds,
        signal_msg="draining the anakin loop, then saving full "
                   "on-device state",
        watch_label="anakin loop", stop_fn=stop_fn)
    telemetry, supervisor = scaffold.telemetry, scaffold.supervisor
    heartbeat, stall, logs = (scaffold.heartbeat, scaffold.stall,
                              scaffold.logs)
    stop_event, stop = scaffold.stop_event, scaffold.stop
    # learnhealth: the plane's harvest absorbs losses + the in-graph
    # diag rows riding the fused program's flat result vector
    plane.monitor = scaffold.learnhealth
    chaos = None
    if cfg.chaos_spec:
        from r2d2_tpu.utils.chaos import ChaosInjector

        # only the wedge_dispatch site exists in this transport; other
        # armed kinds simply never reach an opportunity
        chaos = ChaosInjector(cfg.chaos_spec, seed=cfg.seed)
        if checkpointer is not None:
            checkpointer.chaos = chaos
    scaffold.install_signals()

    def learner_stop() -> bool:
        heartbeat.beat()
        return stop()

    def healthz() -> Dict[str, Any]:
        age = heartbeat.age()
        stale = (cfg.learner_stall_timeout > 0
                 and age > cfg.learner_stall_timeout)
        ok = not (supervisor.any_failed or stall["stalled"] or stale)
        # the nonfinite alert rule is the ONE learnhealth signal that
        # degrades /healthz: the checkpoint stream is numerically
        # suspect and an operator must look (docs/OBSERVABILITY.md)
        degraded = ok and scaffold.alerts.nonfinite_active
        return dict(ok=ok,
                    degraded=degraded,
                    status=("failing" if not ok
                            else "degraded" if degraded else "ok"),
                    learner_heartbeat_age=age,
                    learner_stalled=stall["stalled"] or stale,
                    threads=supervisor.health())

    def log_loop():
        last_steps, last_frames, last_time = 0, 0, time.time()
        stem_share = stem_fused(cfg)
        while not stop():
            time.sleep(min(cfg.log_interval, 0.5))
            now = time.time()
            if now - last_time < cfg.log_interval:
                continue
            s = plane.stats()
            dt = now - last_time
            lc = s["model_counters"]
            # the model's own counters (models/network.counter_names) of
            # the newest harvested dispatch: they ride its result vector
            for name, value in lc.items():
                tracer.gauge("core." + name, value)  # graftlint: disable=telemetry-discipline -- the names are the model's own closed set (a core module's COUNTERS), not data
            syncs = target_syncs(cfg, s["training_steps"])
            tracer.gauge("learner.target_syncs", syncs)
            if stem_share is not None:
                tracer.gauge("torso.stem_fused", stem_share)
            entry = dict(
                time=now, buffer_size=s["size"], env_steps=s["env_steps"],
                training_steps=s["training_steps"], target_syncs=syncs,
                updates_per_sec=(s["training_steps"] - last_steps) / dt,
                mean_episode_return=(s["episode_reward"] / s["num_episodes"]
                                     if s["num_episodes"] else float("nan")),
                mean_loss=(s["sum_loss"]
                           / max(1, s["training_steps"] - last_steps)),
                interval_episodes=s["num_episodes"],
                trace=tracer.snapshot(),
                health=supervisor.health(),
                learner_heartbeat_age=heartbeat.age(),
                telemetry_port=telemetry.port,
                anakin=dict(super_steps=s["super_steps"],
                            frames=s["frames"],
                            frames_per_sec=(s["frames"] - last_frames) / dt,
                            blocks=s["blocks"],
                            episodes_total=s["episodes_total"],
                            # in-graph greedy eval lane
                            # (cfg.anakin_eval_interval): the learning
                            # curve without a host env
                            eval_episodes=s["eval_episodes"],
                            eval_return=s["eval_return"]),
            )
            if lc:
                entry["core"] = lc
            if stem_share is not None:
                entry["torso"] = dict(stem_fused=stem_share)
            # learnhealth + alerts: the anakin PER leaves live in-graph
            # (no host tree to walk), so no replay data-health here —
            # the in-graph diag bundle covers the learner side
            scaffold.record_learnhealth(entry)
            logs.append(entry)
            telemetry.record(entry)
            if log_sink is not None:
                log_sink(entry)
            if verbose:
                print(format_entry(entry), flush=True)
            last_steps, last_frames, last_time = (
                s["training_steps"], s["frames"], now)

    want_full_save = checkpointer is not None and cfg.replay_snapshot

    def save_anakin_snapshot(step: int) -> None:
        """Persist the ENTIRE on-device loop state (ring + PER + env/agent
        carry + counters) through the atomic replay-snapshot machinery —
        what ``--resume`` restores via ``plane.read_state``."""
        try:
            checkpointer.save_replay(step, plane.write_state)
        except Exception as e:  # never fail the run over snapshot I/O
            log.warning("anakin full-state snapshot failed: %s", e)

    # tracing: the fused loop is one process, so the capture plane is a
    # single-slot slab — trainer-track spans (dispatch/result-sync) and
    # the /tracez + /profilez triggers work unchanged; block lineage
    # does not exist here (blocks never leave the device)
    loops = ([("log", log_loop)] + scaffold.watch_loops()
             + scaffold.tracing_loops(1, lambda: plane.training_steps)
             + scaffold.exporter_loops(healthz))

    try:
        try:
            scaffold.start(loops)
            with device_profile(profile_dir):
                setup.begin_fill()
                metrics = run_anakin_loop(
                    learner, plane, stop=learner_stop, tracer=tracer,
                    snapshot_fn=(save_anakin_snapshot if want_full_save
                                 else None), chaos=chaos)
        finally:
            # final health verdict BEFORE quiesce (same rule as the
            # threaded trainer): post-quiesce the heartbeat stops
            # beating and the epilogue snapshot below can outlast the
            # stall budget — a clean run must not misread as failing
            try:
                final_health = healthz()
            except Exception:
                final_health = {}
            scaffold.quiesce()

        # drain-then-save epilogue: the learner state was saved by
        # run_anakin_loop's final _save; persist the on-device loop state
        # next to it so --resume continues warm (ring, RNGs, env phase,
        # LSTM carry — no cold restart).  A wedged abort already parked
        # its snapshot inside the loop (bounded, on a hard wedge) —
        # re-saving here would read the same wedged device UNBOUNDED on
        # the main thread, trading the clean abort back for a hang
        if want_full_save and not metrics.get("dispatch_wedged"):
            save_anakin_snapshot(learner.num_updates)

        # the run is over: hand the weights back on the host and leave the
        # device empty (the loop's carry and ring, the train state), so
        # that what the caller does next has the chip to itself — a model
        # whose train state is most of a chip leaves room for nothing else
        final_params = jax.device_get(learner.state.params)
        plane.release()
        for leaf in jax.tree.leaves(learner.state):
            if not leaf.is_deleted():
                leaf.delete()
        metrics.update(buffer_size=plane.fill, logs=list(logs),
                       buffer_training_steps=plane.training_steps,
                       final_params=final_params,
                       # acting is in-graph: it ran where the loss did
                       act_platform=jax.local_devices()[0].platform,
                       device_memory=device_memory(),
                       restored_replay=restored_anakin,
                       learner_stalled=stall["stalled"],
                       trace=tracer.snapshot(), health=supervisor.health(),
                       telemetry_port=telemetry.port,
                       fabric_failed=supervisor.any_failed,
                       learnhealth=scaffold.learnhealth.snapshot(),
                       alerts=scaffold.alerts.counts(),
                       healthz=final_health)
        if chaos is not None:
            metrics["chaos"] = chaos.counts()
        return metrics
    finally:
        scaffold.close()


# --------------------------------------------------------------------------
# threaded fabric trainer (the reference's process topology, thread-native)
# --------------------------------------------------------------------------

def _timed_setup(trainer):
    """``trainer`` with its set-up timed phase by phase
    (``utils/trace.SetupClock``, docs/OBSERVABILITY.md "Set-up"): the
    run's ``Tracer`` and the clock exist from the call's first line, the
    clock's ``jax.monitoring`` listeners live until the first training
    dispatch returns or, on every other way out (a configuration refused
    in set-up included), until the ``finally`` here, and
    ``metrics["setup"]`` is what the clock read."""

    @functools.wraps(trainer)
    def timed(cfg: Config, *args, tracer: Optional[Tracer] = None,
              **kwargs) -> Dict[str, Any]:
        tracer = tracer or Tracer()
        setup = SetupClock(tracer)
        try:
            metrics = trainer(cfg, *args, tracer=tracer, _setup=setup,
                              **kwargs)
        finally:
            setup.close()
        metrics["setup"] = setup.seconds
        return metrics

    return timed


@_timed_setup
def train(cfg: Config, env_factory: EnvFactory = _default_env_factory,
          checkpoint_dir: Optional[str] = None, resume: bool = False,
          use_mesh: bool = False, max_wall_seconds: Optional[float] = None,
          verbose: bool = True,
          log_sink: Optional[Callable[[Dict[str, Any]], None]] = None,
          tracer: Optional[Tracer] = None,
          profile_dir: Optional[str] = None,
          max_thread_restarts: int = 3,
          stop_fn: Optional[Callable[[], bool]] = None,
          _setup: Optional[SetupClock] = None) -> Dict[str, Any]:
    """The full concurrent system (reference train.py:20-44 equivalent).

    Threads and their reference analogues:
      actor[0..F]  — the N actor processes (worker.py:516-561), regrouped
                     into ``cfg.actor_fleets`` lockstep fleet threads with
                     batched inference (one fleet's env stepping overlaps
                     another's inference on multi-core hosts)
      sample       — ReplayBuffer.prepare_data (worker.py:113-122)
      priority     — ReplayBuffer.update_data (worker.py:131-138)
      log          — the buffer process's stats loop (worker.py:89-106)
      prefetch     — Learner.prepare_data (worker.py:309-316), inside
                     Learner.run
      main thread  — the learner hot loop (worker.py:318-381)

    Block ingest (add_data, worker.py:124-129) needs no thread: the actor
    sink calls ``buffer.add`` directly — same-process, lock-protected.

    Beyond the reference: fabric threads run under a Supervisor (crashes
    recorded and restarted up to ``max_thread_restarts``; an exhausted
    budget stops the run instead of hanging — SURVEY §5.3), a Tracer
    records per-stage timings and queue-depth gauges (SURVEY §5.1), and
    ``profile_dir`` captures a ``jax.profiler`` device trace of the run.

    Preemption-safe: SIGTERM/SIGINT trigger a drain-then-save shutdown —
    the learner checkpoints its final state and (``cfg.replay_snapshot``,
    host-ring runs) the replay ring, sum-tree, counters and actor RNG/env
    state are snapshotted atomically so ``resume=True`` restarts warm
    (``cfg.replay_snapshot_interval`` adds periodic mid-run snapshots
    against kill -9).  ``cfg.learner_stall_timeout`` arms a heartbeat
    watchdog that stops the fabric when the learner thread freezes, and
    ``cfg.chaos_spec`` (utils/chaos.py) injects deterministic faults for
    recovery drills.

    Telemetry (r2d2_tpu/telemetry, docs/OBSERVABILITY.md): every log
    interval the stats entry is absorbed into a shared
    :class:`~r2d2_tpu.telemetry.registry.MetricsRegistry` (spans, guard
    counters, replay stats, chaos fires, supervisor/fleet health — the
    process-fleet plane additionally merges actor-side counters
    published through a shared-memory stats slab) and appended to the
    persistent JSONL run log under ``<checkpoint_dir>/telemetry/``
    (append-on-resume: a SIGTERM→resume soak yields one continuous
    curve).  ``cfg.telemetry_port`` arms an HTTP exporter serving
    ``/metrics`` (Prometheus text), ``/healthz`` and ``/statusz`` as a
    supervised fabric thread.  The in-memory ``metrics["logs"]`` list is
    a ``cfg.log_history_cap`` ring — the JSONL file is the durable
    record.

    ``tracer`` and ``_setup`` come from :func:`_timed_setup` (a caller's
    own ``tracer`` passes through); ``metrics["setup"]`` splits the run's
    set-up into its phases and JAX's compile work.
    """
    if cfg.actor_transport == "anakin":
        # the Podracer fused on-device loop (learner/anakin.py): env,
        # actor, replay and learner are ONE jitted program — none of the
        # thread/process fabric below applies
        if env_factory is not _default_env_factory:
            # hard error, not a warning: with two jittable envs behind
            # cfg.anakin_env a custom factory here is a config mistake a
            # silent fallback would hide — host env factories cannot run
            # inside the fused program
            raise ValueError(
                "anakin transport cannot run a host env_factory — the "
                "env must be jnp ops.  Select a jittable env with "
                "cfg.anakin_env ('fake' or 'grid'), or implement the "
                "envs/anakin.py four-method surface "
                "(init_state/observe/step/reset_lanes + STATE_KEYS) and "
                "register it in make_anakin_env")
        if cfg.league_eval:
            import warnings

            warnings.warn(
                "league_eval is not wired into the anakin transport "
                "(the fused loop has its own on-device eval-lane "
                "follow-on, ROADMAP item 2) — running without the eval "
                "sidecar", stacklevel=2)
        return _train_anakin(cfg, tracer, _setup,
                             checkpoint_dir=checkpoint_dir,
                             resume=resume, use_mesh=use_mesh,
                             max_wall_seconds=max_wall_seconds,
                             verbose=verbose, log_sink=log_sink,
                             profile_dir=profile_dir, stop_fn=stop_fn)
    sys = _build(cfg, env_factory, use_mesh, checkpoint_dir, resume,
                 tracer=tracer, setup=_setup)
    actors: List[VectorActor] = sys["actors"]
    buffer: ReplayBuffer = sys["buffer"]
    learner: Learner = sys["learner"]
    checkpointer = sys["checkpointer"]
    plane = sys["plane"]
    replay_plane = sys["replay_plane"]
    scaffold = _HostScaffold(cfg, checkpoint_dir,
                             max_wall_seconds=max_wall_seconds,
                             max_thread_restarts=max_thread_restarts,
                             stop_fn=stop_fn)
    telemetry, supervisor = scaffold.telemetry, scaffold.supervisor
    heartbeat, stall, logs = (scaffold.heartbeat, scaffold.stall,
                              scaffold.logs)
    stop_event, stop = scaffold.stop_event, scaffold.stop
    # learnhealth: the learner's harvests absorb losses + the in-graph
    # diag vectors (cfg.learnhealth_interval); a non-finite observation
    # fires the nonfinite alert and trips scaffold.stop
    learner.monitor = scaffold.learnhealth

    chaos = None
    if cfg.chaos_spec:
        from r2d2_tpu.utils.chaos import ChaosInjector

        chaos = ChaosInjector(cfg.chaos_spec, seed=cfg.seed)
        if checkpointer is not None:
            checkpointer.chaos = chaos
    # cross-process tracing (telemetry/tracing.py): one event-ring slot
    # per fabric process — trainer (slot 0) + fleets + replay shards —
    # armed fabric-wide by /tracez, --trace-steps, or chaos_soak's
    # --trace round.  Built before the planes spawn so every worker
    # attaches at birth.
    num_trace_slots = (1 + (plane.num_fleets if plane is not None else 0)
                       + (replay_plane.K if replay_plane is not None
                          else 0))
    tracing_loops = scaffold.tracing_loops(
        num_trace_slots, lambda: buffer.training_steps)
    if plane is not None:
        plane.trace_slab = scaffold.trace_slab
        plane.trace_slot_base = 1
    if replay_plane is not None:
        replay_plane.trace_slab = scaffold.trace_slab
        replay_plane.trace_slot_base = 1 + (plane.num_fleets
                                            if plane is not None else 0)

    if plane is not None:
        # CRC-failed blocks dropped at ingest surface in buffer.stats()
        plane.on_corrupt = buffer.note_corrupt_block
        # the plane's counters (respawns, ingest histogram, serve shard
        # resets, slab-merged actor stats) land in the run's namespace
        plane.set_registry(telemetry.registry)
        # fault sites owned by the plane's own loops (freeze_service /
        # stall_pump) and the service's scatter (drop/garble response)
        plane.chaos = chaos
        if plane.service is not None:
            # serve loop spans (assemble/act/scatter) + batch-size gauge
            # land in the same tracer snapshot as every other stage
            plane.service.tracer = tracer
            plane.service.chaos = chaos

    # preemption hook: SIGTERM/SIGINT request a drain-then-save shutdown —
    # the learner exits at its next stop poll, the fabric quiesces, and
    # the epilogue below writes the full-state snapshot (learner state via
    # Learner.run's own final save; replay ring + actor state via
    # checkpointer.save_replay)
    scaffold.install_signals()

    # full-state snapshots need the host ring (device_replay state lives
    # in HBM) and a single process (per-host snapshot dirs would collide)
    want_full_save = (checkpointer is not None and cfg.replay_snapshot
                      and sys["ring"] is None and jax.process_count() == 1)

    if replay_plane is not None:
        # shard counters land in the run's namespace (replay.shard.*);
        # the Checkpointer lets the watchdog restore a respawned shard's
        # slots from the latest committed replay snapshot; the chaos
        # injector arms the garble_sample_response receipt-side site
        replay_plane.set_registry(telemetry.registry)
        if want_full_save:
            replay_plane.checkpointer = checkpointer
        replay_plane.chaos = chaos

    # standing evaluation sidecar (league/eval_service.py): follows this
    # run's checkpoints from a supervised subprocess, scores every
    # population member on its held-out suite, publishes league.jsonl +
    # the /statusz league table.  Its death only ever DEGRADES /healthz
    # — the watchdog loop respawns it (cursor resumed from league.jsonl)
    # and an exhausted budget stops evaluation, never training.
    sidecar = None
    if cfg.league_eval:
        if checkpoint_dir is None:
            log.warning("league_eval requested without a checkpoint_dir "
                        "— the eval sidecar follows checkpoints; "
                        "running without it")
        else:
            from r2d2_tpu.league.eval_service import EvalSidecar

            sidecar = EvalSidecar(cfg, checkpoint_dir, sys["action_dim"],
                                  registry=telemetry.registry)

    def learner_stop() -> bool:
        if chaos is not None:
            freeze = chaos.learner_freeze_seconds()
            if freeze > 0:
                time.sleep(freeze)
            if chaos.poison_params_now():
                # learnhealth NaN-sentry drill: runs ON the learner
                # thread (this predicate is only polled there), so the
                # state handle cannot race an in-flight donation
                log.warning("chaos: poisoning learner params with NaN")
                learner.poison_params()
        heartbeat.beat()
        return stop()

    batch_queue: "queue.Queue" = queue.Queue(maxsize=8)
    priority_queue: "queue.Queue" = queue.Queue(maxsize=8)
    # sample→feedback latency pairing: batches and their priority
    # feedback move through FIFO queues in order, so a deque of enqueue
    # stamps pairs each feedback with its batch without widening the
    # priority-sink signature (bounded: a drained stop drops stragglers)
    sample_ts: collections.deque = collections.deque(maxlen=64)

    def make_actor_loop(a: VectorActor):
        def actor_loop():
            while not stop():
                with tracer.span("actor.run256"):
                    a.run(max_steps=256, stop=stop)
        return actor_loop

    def sample_loop():
        registry = telemetry.registry
        while not stop():
            if not buffer.ready:
                time.sleep(0.05)
                continue
            with tracer.span("buffer.sample_batch"):
                if replay_plane is not None:
                    # the scatter/gather sample RPC; None = every shard
                    # suspect/empty this draw (all RPC deadlines are
                    # bounded) — retry, the watchdog respawns the dead
                    batch = buffer.sample_batch(sys["host_bs"], stop=stop)
                    if batch is None:
                        continue
                else:
                    batch = buffer.sample_batch(sys["host_bs"])
            # block-lineage latency decomposition (docs/OBSERVABILITY.md):
            # per-row ages stamped where the data lives (the K=1 ring or
            # the shard process), observed here where the registry lives.
            # Measured at batch assembly — the learner consumes within
            # the bounded staging window (queue 8 + prefetch), which is
            # the train-time envelope the histogram name promises.
            ages = batch.pop("ages", None)
            if ages is not None:
                ages = np.asarray(ages)
                cut, add = ages[:, 0], ages[:, 1]
                registry.observe_many("pipeline.block_age_at_train_s",
                                      cut[cut >= 0])
                registry.observe_many("pipeline.hop.ingest_to_sample_s",
                                      add[add >= 0])
            while not stop():
                try:
                    batch_queue.put(batch, timeout=0.1)
                    sample_ts.append(time.perf_counter())
                    break
                except queue.Full:
                    continue

    def priority_loop():
        registry = telemetry.registry
        while not stop():
            try:
                idxes, priorities, old_ptr, loss = priority_queue.get(
                    timeout=0.1)
            except queue.Empty:
                continue
            if sample_ts:
                # FIFO pairing with the batch this feedback came from
                try:
                    registry.observe(
                        "pipeline.hop.sample_to_feedback_s",
                        time.perf_counter() - sample_ts.popleft())
                except IndexError:
                    pass   # raced the deque's bound — skip the sample
            with tracer.span("buffer.update_priorities"):
                buffer.update_priorities(idxes, priorities, old_ptr, loss)

    def healthz() -> Dict[str, Any]:
        """The /healthz verdict — three states (docs/OBSERVABILITY.md):
        ``ok`` (everything green), ``degraded`` (still serving HTTP 200,
        but a plane is running on its fallback path — an open act
        circuit, params stale past the budget), and ``failing`` (HTTP
        503: supervisor giveup, failed fleet plane, heartbeat past its
        stall budget).  The exporter keeps answering while the learner
        is merely frozen, so an external prober sees the stall the
        moment it exceeds the budget — before the watchdog has
        necessarily fired."""
        age = heartbeat.age()
        stale = (cfg.learner_stall_timeout > 0
                 and age > cfg.learner_stall_timeout)
        out = dict(
            ok=not (supervisor.any_failed or stall["stalled"] or stale
                    or (plane is not None and plane.failed)
                    or (replay_plane is not None and replay_plane.failed)),
            learner_heartbeat_age=age,
            learner_stalled=stall["stalled"] or stale,
            threads=supervisor.health(),
        )
        degraded = False
        if plane is not None:
            h = plane.health()
            out["fleet"] = dict(fleets=h["fleets"], alive=h["alive"],
                                restarts=h["restarts"], failed=h["failed"],
                                resilience=h["resilience"])
            degraded = bool(h["resilience"].get("degraded"))
        if replay_plane is not None:
            rh = replay_plane.health()
            out["replay_shards"] = dict(shards=rh["shards"],
                                        alive=rh["alive"],
                                        respawns=rh["respawns"],
                                        failed=rh["failed"])
            if "net" in rh:
                # socket transport: surface the per-link verdicts —
                # connection, circuit state, reconnects, epoch drops —
                # so a prober sees WHICH link is partitioned
                out["replay_shards"]["net"] = dict(
                    connected=rh["net"]["connected"],
                    reconnects=rh["net"]["reconnects"],
                    epoch_drops=rh["net"]["epoch_drops"],
                    circuits=[row["circuit"]
                              for row in rh["net"]["links"]])
            # a dead/partitioned shard mid-heal: the plane keeps serving
            # from the survivors (redistributed strata) — degraded, not
            # failing
            degraded = degraded or bool(rh["degraded"])
        if sidecar is not None:
            lh = sidecar.health()
            out["league"] = lh
            # a dead/failed evaluator blinds the run to policy quality
            # but touches nothing on the training path: degraded, never
            # failing — an orchestrator must not evict a training run
            # because its scoreboard died
            degraded = degraded or bool(lh["degraded"])
        # learnhealth: the nonfinite alert rule (and only it) degrades
        # the verdict — the checkpoint stream is numerically suspect
        degraded = degraded or scaffold.alerts.nonfinite_active
        out["degraded"] = degraded and out["ok"]
        out["status"] = ("failing" if not out["ok"]
                         else "degraded" if degraded else "ok")
        return out

    def log_loop():
        last_steps, last_time = 0, time.time()
        stem_share = stem_fused(cfg)
        while not stop():
            time.sleep(min(cfg.log_interval, 0.5))
            now = time.time()
            if now - last_time < cfg.log_interval:
                continue
            s = buffer.stats()
            dt = now - last_time
            tracer.gauge("batch_queue_depth", batch_queue.qsize())
            tracer.gauge("priority_queue_depth", priority_queue.qsize())
            syncs = target_syncs(cfg, s["training_steps"])
            tracer.gauge("learner.target_syncs", syncs)
            if stem_share is not None:
                tracer.gauge("torso.stem_fused", stem_share)
            entry = dict(
                time=now, buffer_size=s["size"], env_steps=s["env_steps"],
                training_steps=s["training_steps"], target_syncs=syncs,
                updates_per_sec=(s["training_steps"] - last_steps) / dt,
                mean_episode_return=(s["episode_reward"] / s["num_episodes"]
                                     if s["num_episodes"] else float("nan")),
                mean_loss=(s["sum_loss"] / max(1, s["training_steps"] - last_steps)),
                interval_episodes=s["num_episodes"],
                trace=tracer.snapshot(),
                health=supervisor.health(),
                learner_heartbeat_age=heartbeat.age(),
                telemetry_port=telemetry.port,
            )
            if stem_share is not None:
                entry["torso"] = dict(stem_fused=stem_share)
            if chaos is not None:
                entry["chaos"] = chaos.counts()
            if plane is not None:
                entry["fleet"] = plane.health()
            if replay_plane is not None:
                entry["replay_shards"] = replay_plane.health()
            if sidecar is not None:
                # the league standings ride the entry → /statusz
                # last_entry + the JSONL run log + the league.* registry
                # absorption (telemetry/plane.py)
                entry["league"] = sidecar.status()
            # shard-health drive-bys ride the base stats schema (zeros on
            # the in-process path) so r2d2_top renders one line format
            entry["corrupt_blocks"] = s["corrupt_blocks"]
            entry["shard_respawns"] = s.get("shard_respawns", 0)
            # learnhealth: monitor snapshot + replay data-health (ESS /
            # priority histogram / replay ratio / member fractions),
            # then the alert engine's interval evaluation
            try:
                replay_health = buffer.data_health()
            except Exception:   # telemetry must never kill the log loop
                replay_health = None
            scaffold.record_learnhealth(entry, replay_health)
            logs.append(entry)
            # registry absorption + the persistent JSONL record
            telemetry.record(entry)
            if log_sink is not None:
                log_sink(entry)
            if verbose:
                print(format_entry(entry), flush=True)
            last_steps, last_time = s["training_steps"], now

    def chaos_loop():
        # process-plane fault sites (fleet kill, slab garbling, replay
        # shard kill/stall, eval-sidecar kill); learner freeze fires from
        # learner_stop, checkpoint truncation from the Checkpointer
        # itself, sample-response garbling from the replay plane's
        # receipt path
        while not stop():
            time.sleep(0.05)
            if plane is not None:
                chaos.maybe_kill_fleet(plane)
                chaos.maybe_garble_block(plane)
            if replay_plane is not None:
                chaos.maybe_kill_replay_shard(replay_plane)
                chaos.maybe_stall_shard(replay_plane)
            if sidecar is not None:
                chaos.maybe_kill_eval_sidecar(sidecar)

    def snapshot_loop():
        # periodic insurance against kill -9 (no drain possible): the
        # buffer snapshot is lock-consistent; thread-transport actor state
        # is only captured by the quiesced shutdown save
        last = time.time()
        while not stop():
            time.sleep(0.2)
            if time.time() - last < cfg.replay_snapshot_interval:
                continue
            try:
                sys["checkpointer"].save_replay(buffer.training_steps,
                                                buffer.write_state)
            except Exception as e:
                # a snapshot is insurance, not the run: a replay shard
                # dying mid-fan-out (chaos kill) fails THIS save — warn
                # and retry next cadence instead of burning the loop's
                # supervisor restart budget (the shutdown save is
                # equally tolerant)
                log.warning("periodic replay snapshot failed: %s", e)
            last = time.time()

    loops = [(f"actor{f}" if len(actors) > 1 else "actor",
              make_actor_loop(a)) for f, a in enumerate(actors)]
    loops += scaffold.watch_loops()
    if chaos is not None and (
            (plane is not None and (chaos.enabled("kill_fleet")
                                    or chaos.enabled("garble_block")))
            or (replay_plane is not None
                and (chaos.enabled("kill_replay_shard")
                     or chaos.enabled("stall_shard")))
            or (sidecar is not None
                and chaos.enabled("kill_eval_sidecar"))):
        loops.append(("chaos", chaos_loop))
    if want_full_save and cfg.replay_snapshot_interval > 0:
        loops.append(("snapshot", snapshot_loop))
    if plane is not None:
        # process transport: fleets are subprocesses; their trainer-side
        # plumbing (block ingest, weight pump, process watchdog) runs as
        # supervised fabric threads just like the actor threads would
        loops += plane.make_loops(stop, buffer.add)
    if sidecar is not None:
        # the eval sidecar's watchdog (respawn-with-cursor-resume): its
        # budget exhausting degrades health, never the fabric
        loops += sidecar.make_loops(stop)
    if replay_plane is not None:
        # sharded replay: the shard-process watchdog (respawn + restore)
        loops += replay_plane.make_loops(stop)
    loops += [("sample", sample_loop), ("priority", priority_loop),
              ("log", log_loop)]
    loops += tracing_loops
    loops += scaffold.exporter_loops(healthz)
    if sys["ring"] is not None:
        # device replay: the learner samples index bundles itself (cheap,
        # coupled to its dispatch) — no host batch-staging thread
        loops = [(n, f) for n, f in loops if n != "sample"]
    if cfg.in_graph_per:
        # priority feedback never crosses the host (the super-step
        # scatters it on-device) — nothing would ever feed this queue
        loops = [(n, f) for n, f in loops if n != "priority"]

    # both run on the learner thread, so their waits poll learner_stop:
    # the heartbeat keeps beating through a legitimately slow batch (the
    # watchdog only fires on a FROZEN thread), and a chaos freeze bites
    # wherever the learner happens to be waiting
    def batch_source():
        while not learner_stop():
            try:
                return batch_queue.get(timeout=0.1)
            except queue.Empty:
                continue
        return None

    def priority_sink(idxes, priorities, old_ptr, loss):
        while not learner_stop():
            try:
                priority_queue.put((idxes, priorities, old_ptr, loss),
                                   timeout=0.1)
                return
            except queue.Full:
                continue
        # stopped: the learner's exit drain still delivers its pipelined
        # pending results through this sink, and the priority thread may
        # already be gone — apply directly (lock-protected, order-free)
        # instead of silently dropping them
        buffer.update_priorities(idxes, priorities, old_ptr, loss)

    # everything that launches concurrent machinery (fleet subprocesses,
    # fabric threads) lives INSIDE the try: a failure anywhere in bring-up
    # must still reach the teardown below, or a caller catching the
    # exception is left with orphaned processes and /dev/shm slabs
    # handlers stay installed through the post-drain full-state save:
    # a second SIGTERM during the drain/snapshot must keep requesting a
    # clean stop, not kill the process mid-write (the save is atomic
    # either way, but the snapshot would be lost); restored on EVERY
    # exit path, including exceptions
    try:
        fleet_snaps = None
        try:
            if replay_plane is not None:
                # shard processes first: every other plane's ingest path
                # routes into them (restores armed by _build apply here)
                replay_plane.start()
            if plane is not None:
                plane.start(sys["param_store"])
            if sidecar is not None:
                sidecar.start()
            scaffold.start(loops)
            with device_profile(profile_dir):
                _setup.begin_fill()
                if sys["ring"] is not None:
                    metrics = learner.run_device(buffer, sys["ring"],
                                                 priority_sink,
                                                 stop=learner_stop,
                                                 tracer=tracer)
                else:
                    metrics = learner.run(batch_source, priority_sink,
                                          stop=learner_stop, tracer=tracer)
        finally:
            # the run's final health verdict, sampled while every plane
            # still exists (post-shutdown a plane reports alive=0, which
            # would misread as degraded) — metrics["healthz"] below
            try:
                final_health = healthz()
            except Exception:
                final_health = {}
            scaffold.quiesce()
            league_final = None
            if sidecar is not None:
                # status sampled pre-shutdown so metrics report the
                # verdict the run actually served with, then stop the
                # child before the fleet plane: eval is pure overhead
                # during a drain, and a sidecar mid-restore must not
                # race the retention GC the epilogue save may trigger
                league_final = sidecar.status()
                sidecar.shutdown()
            if plane is not None:
                # drain-then-save: collect resumable actor snapshots from the
                # dying fleets (answered by their shutdown handshake)
                fleet_snaps = plane.shutdown(snapshot=want_full_save)
            for a in actors:
                a.close()

        # drain remaining priority feedback so buffer counters are final
        while True:
            try:
                idxes, priorities, old_ptr, loss = priority_queue.get_nowait()
            except queue.Empty:
                break
            buffer.update_priorities(idxes, priorities, old_ptr, loss)

        # full-state snapshot, AFTER the drain so ring priorities/counters are
        # final: the learner state was already saved by Learner.run's epilogue;
        # this persists the warm replay ring + sum-tree + actor RNG/env state
        # next to it, atomically — what --resume restores through _build
        if want_full_save:
            try:
                actor_snaps = (fleet_snaps if plane is not None
                               else [a.snapshot() for a in actors])
                try:
                    step = learner.num_updates
                except Exception:  # learner died mid-dispatch: tag host-side
                    step = buffer.training_steps
                checkpointer.save_replay(step, buffer.write_state,
                                         actors=actor_snaps)
            except Exception as e:  # never fail the run over snapshot I/O
                log.warning("full-state replay snapshot failed: %s", e)

        if actors:
            # observed on the first act's output (None: no act ran)
            act_platform = actors[0].act_platform
        elif plane.service is not None:
            act_platform = plane.service.act_device.platform
        else:
            act_platform = "cpu"  # local-inference fleets pin the CPU
        from r2d2_tpu import native

        metrics.update(buffer_size=len(buffer), logs=list(logs),
                       buffer_training_steps=buffer.training_steps,
                       final_params=learner.state.params,
                       # what actually ran, beside metrics["drivetrain"]
                       # (stamped by the learner path that returned): the
                       # platform actor inference executed on and the
                       # host sum-tree implementation
                       act_platform=act_platform,
                       host_sum_tree=("native" if native.available()
                                      else "numpy"),
                       device_memory=device_memory(),
                       restored_replay=sys["restored_replay"],
                       learner_stalled=stall["stalled"],
                       trace=tracer.snapshot(), health=supervisor.health(),
                       telemetry_port=telemetry.port,
                       fabric_failed=(supervisor.any_failed
                                      or (plane is not None and plane.failed)),
                       learnhealth=scaffold.learnhealth.snapshot(),
                       alerts=scaffold.alerts.counts(),
                       healthz=final_health)
        if chaos is not None:
            metrics["chaos"] = chaos.counts()
        if plane is not None:
            metrics["fleet_health"] = plane.health()
        if replay_plane is not None:
            metrics["replay_shard_health"] = replay_plane.health()
        if sidecar is not None:
            # pre-shutdown verdict + a final table re-read (rows the
            # sidecar committed during its own drain still count)
            metrics["league"] = dict(sidecar.status(max_age=0.0),
                                     health=(league_final or {}).get(
                                         "health",
                                         sidecar.health()))
        # member-tagged experience flow ({0: n} outside a population;
        # the sharded facade reports {} — its per-member counts live
        # shard-side, the plane's population rows cover the trainer view)
        metrics["blocks_per_member"] = buffer.stats().get(
            "blocks_per_member", {})
        return metrics
    finally:
        # AFTER the epilogue: the priority drain and the full-state
        # snapshot fan-out above both need live shard processes
        if replay_plane is not None:
            replay_plane.shutdown()
        scaffold.close()
