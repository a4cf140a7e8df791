"""Learner host loop: the drivetrain around the jitted train step.

Capability-parity with the reference learner's ``run`` (worker.py:300-381):
staged batch prefetch, periodic weight publication, periodic checkpointing.
Target-net sync is already *inside* the jitted step (in-graph select), so
the host loop only drives data and cadences.

TPU-first redesign:
- The prefetch thread moves batches host→device (``jax.device_put`` with
  the mesh sharding) **ahead of** the compute stream, so H2D overlaps the
  previous step — the async analogue of the reference's host-side staging
  list (worker.py:309-316).
- Weight publication is a versioned immutable snapshot (ParamStore), not a
  shared-memory mutation (worker.py:306-307).
- Multi-device: pass a Mesh and the same loop drives the GSPMD-sharded
  step; the loop code is identical.
- Checkpointing saves the full TrainState with resume (checkpoint.py),
  beating the reference's save-only ``torch.save`` (worker.py:380-381).
"""
from __future__ import annotations

import queue
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from r2d2_tpu.checkpoint import Checkpointer
from r2d2_tpu.config import Config
from r2d2_tpu.learner.step import TrainState
from r2d2_tpu.models.network import R2D2Network
from r2d2_tpu.parallel.mesh import trivial_mesh
from r2d2_tpu.parallel.sharding import (
    DEVICE_BATCH_KEYS,
    ShardingTable,
    pjit_train_step,
)
from r2d2_tpu.utils.store import ParamStore
from r2d2_tpu.utils.trace import (
    HOST_TRANSFERS,
    TRANSFER_GUARD,
    held,
    maybe_span,
    put_scalar,
)

def _aval_tree(tree):
    """ShapeDtypeStruct avals (shape/dtype/sharding) for every leaf —
    for AOT-lowering a super-step WITHOUT touching live device buffers.
    Call under the buffer lock when the leaves are donated ring handles:
    a concurrent actor commit donates them, and lowering from a live
    array could read a deleted buffer (ADVICE r4)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            jnp.shape(x), x.dtype, sharding=getattr(x, "sharding", None)),
        tree)


# batch_source() -> host batch dict (blocking); returns None to stop early.
BatchSource = Callable[[], Optional[Dict[str, np.ndarray]]]
# priority_sink(idxes, priorities, old_ptr, loss)
PrioritySink = Callable[[np.ndarray, np.ndarray, int, float], None]


class Learner:
    def __init__(self, cfg: Config, net: R2D2Network, state: TrainState,
                 mesh: Optional[Any] = None,
                 param_store: Optional[ParamStore] = None,
                 checkpointer: Optional[Checkpointer] = None,
                 start_env_steps: int = 0, start_minutes: float = 0.0,
                 table: Optional[ShardingTable] = None):
        self.cfg = cfg
        self.net = net
        self.mesh = mesh  # None = single-device (a trivial 1x1x1 mesh)
        self.param_store = param_store
        self.checkpointer = checkpointer
        self.env_steps = start_env_steps
        self.start_minutes = start_minutes
        self._replicate_params = None  # lazily-built multihost resharder
        self._copy_params = None       # lazily-built one-dispatch snapshotter
        self._saved_steps: set = set()  # steps THIS run saved (see _save)
        # learnhealth plane (telemetry/learnhealth.py): with a nonzero
        # cadence every drivetrain's compiled step carries the in-graph
        # diagnostic vector, folded into the existing result fetch; the
        # trainer attaches a LearnHealthMonitor to absorb it
        self._lh = getattr(cfg, "learnhealth_interval", 0) > 0
        self.monitor: Optional[Any] = None

        # ONE train-step entry point for every topology: the table-driven
        # pjit step (parallel/sharding.py).  A 1-device trivial mesh makes
        # the single-device learner the degenerate case of the same code
        # path — no separate jit variant, no mesh branches.
        self.table = table if table is not None else ShardingTable(
            mesh if mesh is not None else trivial_mesh(), cfg)
        self._step_fn = pjit_train_step(cfg, net, self.table,
                                        state_template=state)
        self._shardings = self.table.batch_shardings()
        self.state = self.table.place_state(state)

        if self.param_store is not None:
            self._publish()

    def _publish(self) -> None:
        # deep-copy: the jitted step donates the state, so a published
        # snapshot must not alias state buffers or the next update would
        # delete it out from under the actors
        if jax.process_count() > 1 and self.mesh is not None:
            # Multi-host: the state lives on the GLOBAL mesh, and any jit
            # on global arrays is an SPMD launch every process must make
            # in lockstep.  The actor thread consumes published params at
            # arbitrary times, so handing it global arrays would let it
            # issue unsynchronised collective launches that corrupt the
            # collective stream (observed as a pod-wide deadlock in the
            # learner's own allgathers).  Publish HOST arrays instead:
            # reshard to replicated in-graph (a lockstep collective, made
            # here on the learner thread — mp-sharded leaves live on
            # other hosts) and fetch; actors then re-commit them to a
            # local device and their inference jits stay process-local.
            if self._replicate_params is None:
                from jax.sharding import NamedSharding, PartitionSpec

                rep = NamedSharding(self.mesh, PartitionSpec())
                # built once: a fresh jit per publish would re-trace (and
                # without a compile cache, re-compile) the reshard program
                # on the learner hot loop every publish
                def publish_replicate_params(p):
                    return p

                self._replicate_params = jax.jit(publish_replicate_params,
                                                 out_shardings=rep)
            self.param_store.publish(jax.device_get(
                self._replicate_params(self.state.params)))
        else:
            if self._copy_params is None:
                # one jitted executable for the whole-tree copy: a bare
                # tree_map of jnp.copy issues one dispatch PER LEAF on
                # the dispatch path every publish (and k=4 publishes once
                # per super-step dispatch)
                def publish_copy_params(p):
                    return jax.tree.map(jnp.copy, p)

                self._copy_params = jax.jit(publish_copy_params)
            self.param_store.publish(self._copy_params(self.state.params))

    @property
    def num_updates(self) -> int:
        return int(jax.device_get(self.state.step))

    def _note_results(self, losses_np: np.ndarray,
                      diags_np: Optional[np.ndarray] = None,
                      strict: bool = True) -> None:
        """Route harvested losses (+ learnhealth diagnostics) to the
        attached monitor.  Without a monitor, ``strict`` preserves the
        historical fail-fast on a non-finite loss; with one, the monitor
        trips the fabric's clean stop and fires the ``nonfinite`` alert
        instead of crashing the learner thread mid-donation."""
        m = self.monitor
        if m is not None:
            m.note_losses(losses_np)
            if diags_np is not None and diags_np.size:
                m.absorb_diags(diags_np)
            return
        if strict:
            assert np.isfinite(losses_np).all(), (
                f"non-finite loss in super-step: {losses_np}")

    def poison_params(self) -> None:
        """Chaos drill hook (``poison_params`` site, utils/chaos.py):
        overwrite the first param leaf with NaN so the next step's loss
        and grads go non-finite — the learnhealth NaN-sentry e2e.  Must
        run on the learner thread (the state handle is donated per
        dispatch)."""
        leaves, treedef = jax.tree.flatten(self.state.params)
        leaves[0] = leaves[0] * jnp.nan  # multiply keeps the sharding
        self.state = self.state.replace(
            params=jax.tree.unflatten(treedef, leaves))

    def _stage(self, batch: Dict[str, np.ndarray]
               ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        """Split host bookkeeping from device fields and start the H2D copy.

        Multi-host: each process's batch holds only its dp rows, assembled
        into one global sharded array (parallel/distributed.py) — batch
        data never crosses DCN."""
        host = {k: batch[k] for k in batch if k not in DEVICE_BATCH_KEYS}
        if jax.process_count() > 1 and self.mesh is not None:
            from r2d2_tpu.parallel.distributed import host_local_batch

            dev = host_local_batch(
                self.mesh, {k: batch[k] for k in DEVICE_BATCH_KEYS},
                shardings=self._shardings)
        else:
            with TRANSFER_GUARD.disallow("learner.stage"):
                # explicit device_put with a sharding: guard-exempt —
                # the window catches any *implicit* H2D sneaking in
                dev = {k: jax.device_put(batch[k], self._shardings[k])
                       for k in DEVICE_BATCH_KEYS}
        return dev, host

    def run(self, batch_source: BatchSource,
            priority_sink: Optional[PrioritySink] = None,
            max_steps: Optional[int] = None,
            stop: Optional[Callable[[], bool]] = None,
            tracer: Optional[Any] = None) -> Dict[str, float]:
        """Drive training until ``cfg.training_steps`` (or ``max_steps`` more
        updates, or ``stop()``).  Returns summary metrics.

        Results (loss + priorities) are harvested behind up to
        ``cfg.superstep_pipeline`` in-flight steps with their D2H copies
        started at dispatch time — same latency-hiding scheme as the
        device-replay driver (:meth:`_superstep_loop`); priority feedback
        lags ≤ pipeline steps (0 = fully synchronous, the train_sync
        setting).

        ``tracer`` (utils/trace.Tracer) records per-stage spans: batch wait,
        jitted step dispatch, and the device→host result sync."""
        cfg = self.cfg
        if tracer is None:
            from r2d2_tpu.utils.trace import Tracer
            tracer = Tracer()
        t0 = time.time()
        target = cfg.training_steps if max_steps is None else (
            self.num_updates + max_steps)

        # prefetch_batches == 0 → fully synchronous staging (deterministic;
        # used by train_sync and tests).  Otherwise a Supervisor-managed
        # thread keeps up to ``prefetch_batches`` device-resident batches
        # ahead of compute.  Supervision (vs the former bare daemon
        # thread): a transient staging crash — an H2D hiccup, a flaky
        # batch source — restarts the loop and the run continues, and only
        # an exhausted restart budget ends the stream; the loop is
        # re-enterable because its whole state is the bounded queue.
        pf_sup = None
        if cfg.prefetch_batches > 0:
            from r2d2_tpu.utils.supervisor import Supervisor

            staged: "queue.Queue" = queue.Queue(maxsize=cfg.prefetch_batches)
            done = threading.Event()

            def prefetch():
                while not done.is_set():
                    batch = batch_source()
                    item = None if batch is None else self._stage(batch)
                    # bounded put that re-checks done: when the learner
                    # stops consuming with the queue full, the thread
                    # must exit rather than park in put() forever (and
                    # pin device-resident staged batches).  A None item is
                    # the end-of-stream sentinel — delivered through the
                    # queue, so a supervised restart after a crash can
                    # never fabricate one.
                    while not done.is_set():
                        try:
                            staged.put(item, timeout=0.1)
                            break
                        except queue.Full:
                            continue
                    if batch is None:
                        return

            pf_sup = Supervisor(max_restarts=2, backoff=0.1)
            pf_thread = pf_sup.start("learner_prefetch", prefetch)

            def next_item():
                # timeout + liveness check: a producer that exhausted its
                # restart budget with the queue empty can never enqueue
                # its sentinel — only then give up (between a crash and
                # its supervised restart the thread is briefly not alive,
                # which must NOT end the stream)
                while True:
                    try:
                        return staged.get(timeout=0.5)
                    except queue.Empty:
                        if pf_sup.any_failed or (not pf_thread.alive
                                                 and done.is_set()):
                            return None
        else:
            done = threading.Event()

            def next_item():
                batch = batch_source()
                return None if batch is None else self._stage(batch)

        # multi-host: stop decisions (wall-clock deadlines, fabric
        # failures) are host-local, but leaving the step loop early on one
        # host would deadlock the others' collectives — sync the flag so
        # all hosts break at the same step boundary
        if jax.process_count() > 1:
            from r2d2_tpu.parallel.distributed import sync_counter

            def any_host(flag: bool) -> bool:
                """True iff the condition holds on any host (collective —
                every host must call it once per loop iteration)."""
                return sync_counter(int(flag), reduce="max") > 0
        else:
            def any_host(flag: bool) -> bool:
                return flag

        def should_stop() -> bool:
            return any_host(bool(stop()) if stop is not None else False)

        # bounded to exactly the reported window: an unbounded list grows
        # ~1 MB/min at fabric rates (measured on a 30-min soak)
        losses: deque = deque(maxlen=100)

        def harvest(pending_item) -> None:
            """Fetch one in-flight step's results and feed them back.
            The copies were started at dispatch time, so behind a nonzero
            pipeline the fetch usually finds host-resident bytes instead
            of paying a fresh interconnect round trip."""
            host, loss, priorities = pending_item
            with tracer.span("learner.result_sync"), \
                    TRANSFER_GUARD.disallow("learner.harvest"), \
                    HOST_TRANSFERS.allowed("learner.result_fetch"):
                if self._lh:
                    # the learnhealth diag rides the same flat fetch
                    flat = np.asarray(jax.device_get(loss))
                    loss, diag = float(flat[0]), flat[1:]
                else:
                    loss, diag = float(jax.device_get(loss)), None
                # loss is replicated (addressable everywhere); priorities
                # are dp-sharded, so under a mesh read back only this
                # host's rows — they pair with the idxes this host sampled
                if self.mesh is not None:
                    from r2d2_tpu.parallel.distributed import local_rows

                    priorities = local_rows(priorities)
                else:
                    priorities = np.asarray(jax.device_get(priorities))
            self._note_results(np.asarray([loss]), diag, strict=False)
            losses.append(loss)
            self.env_steps = int(host.get("env_steps", self.env_steps))
            if priority_sink is not None:
                priority_sink(host["idxes"], priorities,
                              host["block_ptr"], loss)

        # track the update count host-side: self.num_updates is a device
        # fetch of state.step — one interconnect round trip per read, so
        # reading it every iteration would serialise the loop on latency
        updates = self.num_updates
        # NOTE: this pending/harvest/drain shape mirrors _superstep_loop
        # (the device-replay driver) deliberately rather than sharing it:
        # this loop is queue-fed with per-item host metadata and a
        # collective batch-exhaustion break, which don't fit the
        # gate/sample contract there.  A pipeline-logic fix in one loop
        # likely applies to the other — check both.
        pending: deque = deque()
        try:
            while updates < target:
                if should_stop():
                    break
                with tracer.span("learner.batch_wait"):
                    item = next_item()
                # batch exhaustion is also a host-local condition (the
                # host-local stop() can fire between the synced
                # should_stop() and the queue read) — sync it too, or one
                # host breaks out while its peers block in the collective
                # step / the _save allgather
                if any_host(item is None):
                    break
                dev_batch, host = item
                with tracer.span("learner.step_dispatch"), \
                        TRANSFER_GUARD.disallow("learner.dispatch"):
                    if self._lh:
                        (self.state, loss, priorities,
                         diag) = self._step_fn(self.state, dev_batch)
                        # fold loss + diag into ONE flat replicated
                        # vector so the harvest's result fetch count is
                        # unchanged by the diagnostics
                        loss = jnp.concatenate(
                            [jnp.reshape(loss, (1,)), diag])
                    else:
                        self.state, loss, priorities = self._step_fn(
                            self.state, dev_batch)
                    for arr in (loss, priorities):
                        arr.copy_to_host_async()  # explicit: exempt
                pending.append((host, loss, priorities))
                while len(pending) > cfg.superstep_pipeline:
                    harvest(pending.popleft())

                updates += 1
                if (self.param_store is not None
                        and updates % cfg.weight_publish_interval == 0):
                    # spanned: cadence work is the classic source of
                    # learner hiccups, and an armed trace capture should
                    # show a publish/save slice, not an unexplained gap
                    with tracer.span("learner.publish"):
                        self._publish()
                if (self.checkpointer is not None
                        and updates % cfg.save_interval == 0):
                    with tracer.span("learner.checkpoint_save"):
                        self._save(updates, t0)
            while pending:
                harvest(pending.popleft())
        finally:
            done.set()
            if pf_sup is not None:
                # stop supervision (cancels any pending backoff timer) and
                # reap the prefetch thread; it exits at its next done poll
                pf_sup.join_all(timeout=2.0)

        if self.checkpointer is not None:
            self._save(self.num_updates, t0)
        mins = self.start_minutes + (time.time() - t0) / 60.0
        if jax.process_count() > 1:
            from r2d2_tpu.parallel.distributed import sync_counter

            self.env_steps = sync_counter(self.env_steps, reduce="sum")
        return dict(
            drivetrain="host_staged",
            num_updates=self.num_updates,
            env_steps=self.env_steps,
            minutes=mins,
            mean_loss=float(np.mean(losses)) if losses else float("nan"),
        )

    def run_device(self, buffer: Any, ring: Any,
                   priority_sink: Optional[PrioritySink] = None,
                   max_steps: Optional[int] = None,
                   stop: Optional[Callable[[], bool]] = None,
                   tracer: Optional[Any] = None) -> Dict[str, float]:
        """Drive training from the device-resident replay ring
        (replay/device_ring.py): ``superstep_k`` optimizer steps per
        dispatch, batches gathered in-graph, one small H2D (index bundles)
        and one small D2H (stacked losses+priorities) per super-step.

        Replaces the queued host staging of :meth:`run` when
        ``cfg.device_replay`` — batch bytes never cross the host↔device
        boundary, so throughput is immune to interconnect latency (the
        reference's `.to(device)` per step, worker.py:330-342, is the cost
        this removes).

        The update counter advances by k per dispatch, so the loop may
        overshoot ``training_steps`` by up to k-1 updates.

        Under a mesh (single process): the ring is mesh-replicated (or
        dp-sharded, ``ring.layout``) and the super-step is the table-driven
        pjit program (parallel/sharding.pjit_super_step) — index bundles
        shard their batch axis over dp, grads psum over ICI.

        Multi-host: dispatches to :meth:`_run_device_multihost` — each
        host owns the slot slabs of its dp groups (a dp-layout ring over
        its *local* submesh) and the global ring view is stitched from
        the per-host device shards with zero data movement.
        """
        cfg = self.cfg
        if jax.process_count() > 1 and not cfg.in_graph_per:
            return self._run_device_multihost(buffer, ring, priority_sink,
                                              max_steps, stop, tracer)
        if tracer is None:
            from r2d2_tpu.utils.trace import Tracer
            tracer = Tracer()
        from r2d2_tpu.parallel.sharding import pjit_super_step

        k = cfg.superstep_k
        t0 = time.time()
        updates = self.num_updates
        target = cfg.training_steps if max_steps is None else updates + max_steps
        if cfg.in_graph_per:
            # single-process (any ring layout) AND multi-host (dp slabs):
            # the drivetrain handles both — see its docstring
            return self._run_device_in_graph_per(buffer, ring, k, target,
                                                 t0, stop, tracer)
        # AOT-compile outside the buffer lock: the first dispatch happens
        # under it (sample_meta couples sampling + dispatch), and tracing a
        # fresh jit there would stall actor add()s for the whole compile
        super_fn = pjit_super_step(
            cfg, self.net, self.table, k, state_template=self.state,
            layout=getattr(ring, "layout", "replicated"))
        B = cfg.batch_size
        # Lower from avals, not live ring handles: actor commits donate
        # the ring arrays (DeviceRing._write_slot), so a concurrent
        # commit could delete a handle mid-lowering.  Metadata is
        # snapshotted under the buffer lock; lowering touches no device
        # memory (same discipline as _run_device_in_graph_per).
        with buffer.lock:
            snap_avals = _aval_tree((self.state, ring.snapshot()))
        compiled = super_fn.lower(
            *snap_avals,
            np.zeros((k, B, 6), np.int32),
            np.zeros((k, B), np.float32)).compile()

        losses_hist: deque = deque(maxlen=100)  # bounded, see run()

        def prepare(item):
            """Called at enqueue time: dispatch the (tiny) result flatten
            and start its device→host copy NOW, so by harvest time —
            ``superstep_pipeline`` dispatches later — the bytes are already
            host-resident and the blocking fetch is cheap.  Without this
            the transfer would only start inside harvest, putting one full
            interconnect round trip on the loop per dispatch regardless of
            pipeline depth."""
            meta, losses, priorities = item
            if self._lh:
                # the learnhealth diag rows ride the SAME flat result
                # vector — one fetch per dispatch, unchanged
                (losses, diags) = losses
                flat = jnp.concatenate([losses, priorities.reshape(-1),
                                        diags.reshape(-1)])
            else:
                flat = jnp.concatenate([losses, priorities.reshape(-1)])
            flat.copy_to_host_async()
            return (meta, flat)

        def harvest(item) -> None:
            """Fetch a finished super-step's results and feed them back."""
            meta, flat = item
            with tracer.span("learner.result_sync"), \
                    TRANSFER_GUARD.disallow("learner.harvest"):
                # one D2H fetch for everything the host needs (usually
                # already prefetched by prepare())
                with HOST_TRANSFERS.allowed("learner.result_fetch"):
                    flat = np.asarray(jax.device_get(flat))
            diags = (flat[k + k * B:].reshape(k, -1) if self._lh else None)
            self._feed_back(meta, flat[:k], flat[k:k + k * B].reshape(k, B),
                            priority_sink, losses_hist, diags)

        def dispatch(ints, weights):
            with tracer.span("learner.step_dispatch"), \
                    TRANSFER_GUARD.disallow("learner.dispatch"):
                # the dispatch's declared H2D: the sampled idx/weight rows
                with HOST_TRANSFERS.allowed("learner.dispatch_put"):
                    d_ints = jnp.asarray(ints)
                    d_w = jnp.asarray(weights)
                out = compiled(self.state, ring.snapshot(), d_ints, d_w)
                if self._lh:
                    st, losses, priorities, diags = out
                    return st, (losses, diags), priorities
                return out

        def sample():
            with tracer.span("learner.sample_meta"):
                return buffer.sample_meta(k, dispatch=dispatch)

        self._superstep_loop(k, target, t0, self._ready_gate(buffer, stop),
                             sample, harvest, prepare=prepare,
                             tracer=tracer)
        return self._finish_device_run(losses_hist, t0, "device_ring")

    def _ready_gate(self, buffer, stop):
        """The device drivetrains' shared gate(): stop-aware, waits for
        ``learning_starts``."""
        def gate() -> str:
            if stop is not None and stop():
                return "break"
            return "go" if buffer.ready else "wait"
        return gate

    def _collective_gate(self, buffer, stop):
        """Multi-host gate(): the dispatch is a lockstep SPMD launch, so
        the decision to make it must be collective.  One allgather
        carries both flags (min-reduced, so "stop" travels inverted)."""
        from r2d2_tpu.parallel.distributed import sync_min_array

        def gate() -> str:
            flags = sync_min_array(np.array([
                0.0 if (stop is not None and stop()) else 1.0,
                1.0 if buffer.ready else 0.0,
            ]))
            if flags[0] == 0.0:   # some host wants to stop
                return "break"
            if flags[1] == 0.0:   # some host's buffer not ready
                return "wait"
            return "go"
        return gate

    def _finish_device_run(self, losses_hist, t0: float,
                           drivetrain: str) -> Dict[str, Any]:
        """Shared epilogue of the device drivetrains: final save + summary
        (which names the drivetrain that ran, so a run's metrics cannot
        pass one path off as another)."""
        if self.checkpointer is not None:
            self._save(self.num_updates, t0)
        mins = self.start_minutes + (time.time() - t0) / 60.0
        if jax.process_count() > 1:
            from r2d2_tpu.parallel.distributed import sync_counter

            self.env_steps = sync_counter(self.env_steps, reduce="sum")
        return dict(
            drivetrain=drivetrain,
            num_updates=self.num_updates,
            env_steps=self.env_steps,
            minutes=mins,
            mean_loss=(float(np.mean(losses_hist))
                       if losses_hist else float("nan")),
        )

    def _run_device_in_graph_per(self, buffer, ring, k: int, target: int,
                                 t0: float, stop, tracer
                                 ) -> Dict[str, float]:
        """Device-PER drivetrain (``cfg.in_graph_per``): sampling, IS
        weights, and priority feedback all execute inside the super-step
        (learner/step.py:make_in_graph_per_super_step), so each dispatch
        is ONE H2D scalar (the seed) and ONE small D2H (the losses, for
        logging) — the ``learner.result_sync`` priority round trip of
        :meth:`run_device` leaves the training path entirely, and the k
        inner steps sample from priorities the previous inner step wrote
        (tighter feedback than the reference's 8+4-batch queue lag,
        worker.py:300-316).

        The priorities array is a donated carry: the dispatch consumes
        the ring's current handle and the returned one is stored back
        before the buffer lock is released, so actor block commits
        (``DeviceRing.commit_per``, same lock) always target the newest
        generation.  Any mesh layout runs the SAME table-driven pjit step
        (parallel/sharding.pjit_in_graph_per_super_step): the stratified
        draw is global regardless of layout — under a dp-sharded ring the
        PER leaves shard with the slabs and GSPMD inserts the collectives,
        so over the same ring content a dp-sharded run draws the same
        strata as a single-device one (pinned by
        test_in_graph_per_dp_layout_matches_single_device).

        Multi-host (ring layout "dp" over each host's local submesh, as
        built by train.py): per dispatch the global ring + PER views are
        stitched from the per-host device shards with zero data movement
        (``assemble_global``), every process launches the same SPMD
        super-step in lockstep (collective gate), and the returned
        global priorities array — whose addressable shards are exactly
        this host's slabs, updated in place — is relabelled back to the
        local view and stored, so the host's actor commits keep writing
        the newest generation.  The reference's priority feedback
        (worker.py:242-276) at pod scale, with zero host round trips."""
        cfg = self.cfg
        multihost = jax.process_count() > 1
        layout = getattr(ring, "layout", "replicated")
        from r2d2_tpu.parallel.sharding import pjit_in_graph_per_super_step

        super_fn = pjit_in_graph_per_super_step(
            cfg, self.net, self.table, k, state_template=self.state,
            layout=layout)

        if multihost:
            from r2d2_tpu.parallel.distributed import assemble_global

            if layout != "dp":
                raise RuntimeError(
                    "multi-host in_graph_per needs a dp-layout ring "
                    "(train.py builds one per host over its local "
                    "submesh)")
            K = cfg.seqs_per_block
            bpg = ring.blocks_per_group
            GB = self.mesh.shape["dp"] * bpg       # global slot count
            gsh_ring = self.table.ring_shardings("dp")
            gsh_per = self.table.per_shardings("dp")
            # the ring's own table IS the local-submesh table train._build
            # gave it — resolve the local prios layout through it rather
            # than rebuilding one that could drift from the ring's
            lsh_prios = ring.table.per_shardings("dp")["prios"]
            local_leaves = cfg.num_blocks * K

            def ring_args():
                """Global views of the per-host shards (metadata-only
                stitch; caller holds the buffer lock)."""
                meta = ring.per_meta()
                per = assemble_global(
                    {"seq_meta": gsh_per["seq_meta"],
                     "first": gsh_per["first"]},
                    {"seq_meta": meta["seq_meta"], "first": meta["first"]},
                    GB)
                prios_v = assemble_global(
                    {"prios": gsh_per["prios"]},
                    {"prios": ring.take_prios()}, GB * K)["prios"]
                return (assemble_global(gsh_ring, ring.snapshot(), GB),
                        prios_v, per["seq_meta"], per["first"])

            def store_prios(new_global):
                """Relabel the returned global priorities to this host's
                local view — same device buffers, local coordinates —
                so commit_per targets the newest generation."""
                ring.put_prios(jax.make_array_from_single_device_arrays(
                    (local_leaves,), lsh_prios,
                    [s.data for s in new_global.addressable_shards]))

            gate = self._collective_gate(buffer, stop)
        else:
            def ring_args():
                meta = ring.per_meta()
                return (ring.snapshot(), ring.take_prios(),
                        meta["seq_meta"], meta["first"])

            store_prios = ring.put_prios
            gate = self._ready_gate(buffer, stop)

        seed0 = put_scalar(0, np.uint32)
        # AOT-compile from avals, not live ring handles: actor threads
        # are already committing blocks, and a concurrent commit_per
        # donates the priorities handle — lowering from the live array
        # could read a deleted buffer.  Metadata (shape/dtype/sharding)
        # is snapshotted under the buffer lock; the lowering itself then
        # touches no device memory.
        with buffer.lock:
            avals = _aval_tree((self.state, *ring_args(), seed0))
        compiled = super_fn.lower(*avals).compile()
        losses_hist: deque = deque(maxlen=100)
        dispatch_no = [0]

        def sample():
            with tracer.span("learner.step_dispatch", dispatch_no[0]), \
                    TRANSFER_GUARD.disallow("learner.dispatch"):
                with held(buffer.lock, tracer, "learner.lock_wait"):
                    # fold_in(PRNGKey(cfg.seed), idx) happens in-graph;
                    # the u32 counter wraps harmlessly after 2^32.
                    # Multi-host: every process dispatches in lockstep
                    # (collective gate), so the counters — and with them
                    # the in-graph sampling streams — stay identical.
                    # ONE declared H2D per dispatch: the index scalar
                    with HOST_TRANSFERS.allowed("learner.dispatch_put"):
                        idx = put_scalar(dispatch_no[0] & 0xFFFFFFFF,
                                         np.uint32)
                    dispatch_no[0] += 1
                    out = compiled(self.state, *ring_args(), idx)
                    if self._lh:
                        st, new_prios, losses, diags = out
                        losses = (losses, diags)
                    else:
                        st, new_prios, losses = out
                    store_prios(new_prios)
                    env_steps = buffer.env_steps
            # losses ride the pipeline; priorities never leave the device
            return dict(dispatched=(st, losses, None),
                        env_steps=env_steps)

        def prepare(item):
            meta, losses, _ = item
            if self._lh:
                # fold losses + diag rows into the dispatch's ONE D2H
                losses, diags = losses
                losses = jnp.concatenate([losses, diags.reshape(-1)])
            losses.copy_to_host_async()
            return (meta, losses)

        def harvest(item) -> None:
            meta, losses = item
            with tracer.span("learner.result_sync"), \
                    TRANSFER_GUARD.disallow("learner.harvest"):
                with HOST_TRANSFERS.allowed("learner.result_fetch"):
                    flat = np.asarray(jax.device_get(losses))
            losses_np = flat[:k]
            diags = flat[k:].reshape(k, -1) if self._lh else None
            self._note_results(losses_np, diags)
            self.env_steps = int(meta["env_steps"])
            buffer.note_updates(losses_np.shape[0], losses_np.sum())
            losses_hist.extend(losses_np.tolist())

        self._superstep_loop(k, target, t0, gate, sample, harvest,
                             prepare=prepare, tracer=tracer)
        return self._finish_device_run(losses_hist, t0,
                                       "device_ring_in_graph_per")

    def _superstep_loop(self, k: int, target: int, t0: float,
                        gate: Callable[[], str],
                        sample: Callable[[], Dict[str, Any]],
                        harvest: Callable[[Any], None],
                        prepare: Optional[Callable[[Any], Any]] = None,
                        tracer: Optional[Any] = None) -> None:
        """The pipelined super-step driver shared by the single-process
        and multi-host device-replay paths: keep up to
        ``cfg.superstep_pipeline`` dispatches in flight beyond the one
        being harvested.  ``prepare`` runs at enqueue time and starts the
        result D2H transfer immediately (copy_to_host_async), so a
        harvest ``superstep_pipeline`` dispatches later finds the bytes
        host-resident — the dispatch cadence is then bounded by device
        compute, not by the interconnect round trip.  Priority feedback
        lags
        ≤ (pipeline+1)·k updates — at the defaults, comparable to the
        reference's 8-batch queue + 4-batch staging lag
        (worker.py:300-316).  Cadences fire on interval crossings
        (updates advance by k per dispatch).

        ``gate()`` → "break" | "wait" | "go" decides each iteration;
        ``sample()`` must return a meta dict whose ``dispatched`` holds
        the in-flight (state, losses, priorities).
        """
        cfg = self.cfg
        updates = self.num_updates
        pending: deque = deque()
        while updates < target:
            g = gate()
            if g == "break":
                break
            if g == "wait":
                time.sleep(0.02)
                continue
            meta = sample()
            self.state, losses, priorities = meta["dispatched"]
            item = (meta, losses, priorities)
            pending.append(prepare(item) if prepare is not None else item)
            while len(pending) > cfg.superstep_pipeline:
                harvest(pending.popleft())

            prev, updates = updates, updates + k
            if (self.param_store is not None
                    and updates // cfg.weight_publish_interval
                    > prev // cfg.weight_publish_interval):
                with maybe_span(tracer, "learner.publish"):
                    self._publish()
            if (self.checkpointer is not None
                    and updates // cfg.save_interval
                    > prev // cfg.save_interval):
                with maybe_span(tracer, "learner.checkpoint_save"):
                    self._save(updates, t0)
        while pending:
            harvest(pending.popleft())

    def _feed_back(self, meta, losses_np: np.ndarray, prios_np: np.ndarray,
                   priority_sink: Optional[PrioritySink],
                   losses_hist: deque,
                   diags_np: Optional[np.ndarray] = None) -> None:
        """Route one harvested super-step's results to the host side."""
        self._note_results(losses_np, diags_np)
        self.env_steps = int(meta["env_steps"])
        if priority_sink is not None:
            for j in range(losses_np.shape[0]):
                priority_sink(meta["idxes"][j], prios_np[j],
                              meta["block_ptr"], float(losses_np[j]))
        losses_hist.extend(losses_np.tolist())

    def _run_device_multihost(self, buffer: Any, ring: Any,
                              priority_sink: Optional[PrioritySink],
                              max_steps: Optional[int],
                              stop: Optional[Callable[[], bool]],
                              tracer: Optional[Any]) -> Dict[str, float]:
        """Device-resident replay across hosts — the pod-scale data plane.

        Layout: the global ring's slot axis is the concatenation of every
        host's slabs.  Host h's ReplayBuffer/DeviceRing (built over its
        *local* submesh, layout="dp") owns the dp groups its devices hold;
        its writes and sampling are process-local.  Per super-step, every
        host:

        1. agrees the fleet is ready / not stopped (sync_counter — the
           dispatch below is a lockstep SPMD launch, so the decision to
           make it must be collective);
        2. samples its rows (raw per-group inclusion densities), agrees
           the global min density (sync_min_array) so IS weights keep the
           reference's min-of-the-whole-batch normalisation across the
           pod, offsets its slot indices into global coordinates, and
           uploads its rows of the (k, B, 6) bundle;
        3. stitches the global ring view from the per-host device shards
           (assemble_global — metadata only, no data movement) and
           dispatches the SAME sharded super-step as the single-process
           dp layout;
        4. harvests its dp rows of the priorities (local_rows axis=1) and
           feeds its own buffer — feedback never crosses hosts.

        Batch bytes never touch host RAM, and never cross DCN: the sampled
        rows reference only their own host's slabs (sample_meta's
        per-group quotas), so GSPMD's partitioned gather stays local in
        practice; only grad psums (ICI/DCN) and the tiny index/min-density
        collectives leave the host.  Steps 2-3 run under the buffer lock
        (the device_ring concurrency contract: a ring write donates the
        buffers a pending dispatch would read).
        """
        import jax.numpy as _jnp

        from jax.sharding import NamedSharding, PartitionSpec as P

        from r2d2_tpu.parallel.distributed import (
            assemble_global, global_from_local_rows, host_batch_size,
            local_rows, owned_dp_groups, sync_min_array)
        from r2d2_tpu.parallel.sharding import pjit_super_step

        cfg = self.cfg
        assert self.mesh is not None, "multi-host device replay needs a mesh"
        if tracer is None:
            from r2d2_tpu.utils.trace import Tracer
            tracer = Tracer()

        k = cfg.superstep_k
        t0 = time.time()
        updates = self.num_updates
        target = (cfg.training_steps if max_steps is None
                  else updates + max_steps)

        dp_local = ring.num_groups
        bpg = ring.blocks_per_group
        owned = owned_dp_groups(self.mesh)
        if owned.stop - owned.start != dp_local:
            raise RuntimeError(
                f"ring has {dp_local} local groups but this process owns "
                f"{owned.stop - owned.start} dp groups of the global mesh")
        slot_offset = owned.start * bpg
        global_blocks = self.mesh.shape["dp"] * bpg
        B, B_host = cfg.batch_size, host_batch_size(cfg, self.mesh)
        beta = cfg.importance_sampling_exponent

        super_fn = pjit_super_step(cfg, self.net, self.table, k,
                                   state_template=self.state, layout="dp")
        ring_sh = self.table.ring_shardings("dp")
        dp_b = NamedSharding(self.mesh, P(None, "dp"))
        # AOT with shape specs — the global ring is far too big to
        # zero-fill host-side just to trace
        ring_spec = {
            kk: jax.ShapeDtypeStruct((global_blocks, *v.shape[1:]),
                                     v.dtype, sharding=ring_sh[kk])
            for kk, v in ring.snapshot().items()}
        compiled = super_fn.lower(
            self.state, ring_spec,
            jax.ShapeDtypeStruct((k, B, 6), _jnp.int32, sharding=dp_b),
            jax.ShapeDtypeStruct((k, B), _jnp.float32, sharding=dp_b),
        ).compile()

        losses_hist: deque = deque(maxlen=100)  # bounded, see run()

        def prepare(item):
            """Start the result D2H copies at enqueue time (addressable
            shards only) so the later harvest finds them host-resident —
            see :meth:`_superstep_loop`."""
            _, losses, priorities = item
            for arr in (losses, priorities):
                try:
                    arr.copy_to_host_async()
                except Exception:
                    pass  # any prefetch failure: harvest pays the trip
            return item

        def harvest(item) -> None:
            # dispatch() folded losses (+ learnhealth diag rows) into
            # one flat replicated vector — ONE fetch either way
            meta, flat, priorities = item
            with tracer.span("learner.result_sync"):
                flat_np = np.asarray(jax.device_get(flat))
                prios_np = local_rows(priorities, axis=1)       # (k, B_host)
            losses_np = flat_np[:k]
            diags_np = flat_np[k:].reshape(k, -1) if self._lh else None
            self._feed_back(meta, losses_np, prios_np, priority_sink,
                            losses_hist, diags_np)

        gate = self._collective_gate(buffer, stop)

        def dispatch(ints, q):
            """Runs under the buffer lock (sample_meta couples sampling
            with dispatch).  All hosts execute this in lockstep."""
            with tracer.span("learner.step_dispatch"):
                gmin = sync_min_array(q.min(axis=1))           # (k,)
                w = (q / gmin[:, None]) ** (-beta)
                g_ints = ints.astype(np.int32, copy=True)
                g_ints[:, :, 0] += slot_offset
                g_ints = global_from_local_rows(
                    dp_b, g_ints, (k, B, 6), axis=1,
                    offset=owned.start * (B // self.mesh.shape["dp"]))
                g_w = global_from_local_rows(
                    dp_b, w.astype(np.float32), (k, B), axis=1,
                    offset=owned.start * (B // self.mesh.shape["dp"]))
                ring_view = assemble_global(ring_sh, ring.snapshot(),
                                            global_blocks)
                out = compiled(self.state, ring_view, g_ints, g_w)
                if self._lh:
                    # fold losses + diag rows into ONE flat replicated
                    # vector so the harvest's result sync stays a
                    # single fetch with diagnostics armed
                    st, losses, priorities, diags = out
                    return (st,
                            jnp.concatenate([losses, diags.reshape(-1)]),
                            priorities)
                return out

        def sample():
            with tracer.span("learner.sample_meta"):
                return buffer.sample_meta(k, batch_size=B_host,
                                          dispatch=dispatch,
                                          raw_densities=True)

        self._superstep_loop(k, target, t0, gate, sample, harvest,
                             prepare=prepare, tracer=tracer)
        return self._finish_device_run(losses_hist, t0,
                                       "device_ring_multihost")

    def _save(self, updates: int, t0: float) -> None:
        if updates in self._saved_steps:
            # THIS RUN already saved this step completely (the epilogue
            # save lands on the same step as the last cadence save
            # whenever training_steps % save_interval == 0).  Re-saving
            # would have orbax delete-and-rewrite the payload under a
            # sidecar that still marks it complete — a follow-mode
            # evaluator restoring that step mid-rewrite sees a torn
            # checkpoint.  Tracked per-run (not via has_meta): a fresh
            # run reusing an old checkpoint dir must still overwrite the
            # previous run's steps, and every pod process makes the same
            # local decision so orbax's save barriers stay in sync.
            return
        minutes = self.start_minutes + (time.time() - t0) / 60.0
        if jax.process_count() > 1:
            # Gather mp-sharded leaves that may live on other hosts by
            # resharding the state to fully-replicated IN-GRAPH (XLA
            # allgathers over ICI) — the host-side process_allgather can't
            # express arbitrary shardings (it only tiles along axis 0).
            # Every process then calls checkpointer.save: orbax is
            # multihost-aware (internal sync barriers, primary-host-only
            # file writes), so skipping non-zero processes here would
            # desync its barriers.  The meta sidecar is process-0-gated
            # inside Checkpointer.save.
            from jax.sharding import NamedSharding, PartitionSpec

            rep = NamedSharding(self.mesh, PartitionSpec())
            state = jax.device_get(
                jax.jit(lambda s: s, out_shardings=rep)(self.state))
        else:
            state = jax.device_get(self.state)
        from r2d2_tpu.checkpoint import arch_meta

        self.checkpointer.save(updates, state,
                               meta=dict(env_steps=self.env_steps,
                                         minutes=minutes,
                                         game=self.cfg.game_name,
                                         **arch_meta(self.cfg)))
        self._saved_steps.add(updates)
