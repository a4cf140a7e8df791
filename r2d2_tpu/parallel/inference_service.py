"""Centralized batched inference for the process actor plane.

With ``cfg.actor_transport="process"`` and the default
``actor_inference="local"``, every fleet subprocess runs its own CPU-jitted
copy of the acting network — the accelerator does zero acting work and N
fleets burn N host cores re-running the same forward at batch ≈ lanes/F.
The Podracer (Sebulba) and Seed-RL architectures centralize instead:
actors ship observations to one server that batches across ALL of them and
runs a single large-batch device ``act`` — exactly the "batched inference
amortizes device dispatch" design the lockstep :class:`~r2d2_tpu.actor.
VectorActor` already implements *within* one process, lifted across the
process boundary.  ``cfg.actor_inference="serve"`` wires it:

- **Act slab**: each fleet owns one preallocated shared-memory
  request/response slot (:func:`act_slot_spec`, laid out by the replay
  ring's own :func:`~r2d2_tpu.replay.block.slot_layout`).  Every env step
  the fleet writes ``(obs, last_action, last_reward, reset_mask)`` for its
  lane shard, posts a sequence token on its request queue, and blocks on
  the response queue; the reply carries ``(q, new_hidden)`` views into the
  same slab.  A CRC32 integrity word — written last, covering the payload
  plus the token header, the block channel's own convention — lets the
  server detect a garbled request (counted + logged + DROPPED; the
  fleet's bounded retry resends it clean, so the lockstep fleet no
  longer wedges on a lost reply).
- **Server-resident recurrent state**: ONE ``(num_actors, 2, layers, H)``
  hidden array lives in the :class:`InferenceService`, indexed by global
  lane id via the fleet shards, zeroed by each request's reset mask, and
  zeroed shard-wide when the watchdog respawns a fleet (no stale LSTM
  state can survive a crash).  The response carries the post-step hidden
  rows so the fleet can record the R2D2 stored-state scheme into its
  blocks (replay needs hidden at each sequence's burn-in start) — but the
  server's copy is authoritative: the client never sends hidden, and the
  full-state snapshot restores the server array bit-exact from the
  per-fleet actor snapshots (``ProcessFleetPlane._spawn``).
- **Zero-staleness weights**: the service reads params straight from the
  trainer's ParamStore each batch — the serving path has no pump lag.
  (The per-fleet weight pump still runs under serve mode, purely as the
  degraded-mode param feed: the fallback weights a fleet's local act
  twin uses when its circuit opens.)
- **Peek requests**: the episode-step-cap bootstrap needs Q at the
  post-step state *without* advancing recurrent state (the VectorActor
  calls act twice that iteration).  A ``mode=MODE_PEEK`` request
  computes q but neither applies reset masks nor scatters hidden.

Intentional divergence from a strict Seed-RL split: the ε-greedy draw
stays fleet-side (the response carries the full q row, tiny at Atari
action counts) so the exploration RNG remains part of the resumable actor
snapshot — the recovery machinery's bit-exact resume guarantees survive
serve mode unchanged.

**Degraded-mode failover** (utils/resilience.py): the act RPC is no
longer allowed to kill a fleet.  Every attempt is bounded by
``cfg.act_response_timeout`` and verified by a response CRC; a timeout or
a garbled response retries bounded (jittered backoff, each retry sent as
a *resync* request — see below — so a half-served predecessor can never
double-advance server state), and exhausting the retries opens the
fleet's :class:`~r2d2_tpu.utils.resilience.CircuitBreaker`.  While the
circuit is open the fleet **degrades to fleet-local inference**: a
lazily-built local act twin (the same executable local mode runs) acting
on the fleet's last pumped weight snapshot — serve fleets now receive the
param pump for exactly this — against the fleet's own authoritative
hidden carry.  Every cooldown the breaker admits one half-open *probe*:
a commit request in **resync mode**, which ships the fleet's current
hidden carry in the slab's ``sync_hidden`` region; the server loads it
over the shard's server-resident rows before acting, so the re-attached
path continues bit-exact from wherever local inference left the carry.
A probe success closes the circuit (re-attach), a failure re-opens it.
The fleet-side counters (retries, circuit opens, local acts, state)
publish through the telemetry stats slab as ``resilience.*``.

Request modes on the token queue — ``(seq, mode)``: ``0`` peek (no state
advance), ``1`` commit, ``2`` resync+commit (load ``sync_hidden`` first).
A ``req_seq`` slab word lets the server drop tokens superseded by a
retry (the fleet only waits on its newest seq), and the response CRC —
written last, over the q row plus (for commits) the response hidden —
closes the torn/garbled-reply window the request CRC never covered.
A request failing its own CRC is *dropped*, not served (counted in
``service.requests_corrupt``): acting on a garbled slab — worst, loading
a torn ``sync_hidden`` over the shard — would stamp a valid response CRC
over a poisoned reply the fleet cannot detect; the bounded retry resends
it clean instead.

The service loop runs as a supervised fabric thread
(``ProcessFleetPlane.make_loops`` → ``inference_serve``); ``serve_once``
is re-enterable (pending requests survive a supervisor restart).  Device
placement follows ``cfg.act_device``, with ``"auto"`` resolving to the
**default backend** (the learner's accelerator) rather than the local-mode
CPU twin — centralizing inference exists to put the accelerator back on
the acting path.  On a CPU-only host (tier-1 tests under
``JAX_PLATFORMS=cpu``) that same resolution lands on the CPU act twin.
"""
from __future__ import annotations

import logging
import threading
import time
from multiprocessing import shared_memory
from queue import Empty
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from r2d2_tpu.config import Config
from r2d2_tpu.models.state import state_spec, zero_state
from r2d2_tpu.parallel.actor_procs import FleetStopped
from r2d2_tpu.replay.block import payload_crc32, slot_layout, slot_views
from r2d2_tpu.telemetry.tracing import EVENTS
from r2d2_tpu.utils.resilience import (
    CLOSED,
    OPEN,
    CircuitBreaker,
    Deadline,
    RetryPolicy,
)
from r2d2_tpu.utils.trace import HOST_TRANSFERS, TRANSFER_GUARD, maybe_span

log = logging.getLogger(__name__)

# request payload fields, in CRC order (shared by producer + verifier)
_REQ_FIELDS = ("obs", "last_action", "last_reward", "reset_mask")

# act-request modes on the token queue (``(seq, mode)``)
MODE_PEEK = 0     # q only; no reset application, no hidden scatter
MODE_COMMIT = 1   # normal act: advance server-resident hidden
MODE_RESYNC = 2   # commit, but FIRST load the shard's hidden from the
                  # slab's sync_hidden region (retries + re-attach probes:
                  # the fleet's carry is authoritative, so a half-served
                  # predecessor attempt can never double-advance state)


class ActTimeout(Exception):
    """One act RPC attempt exceeded ``cfg.act_response_timeout``."""


class ActGarbled(Exception):
    """A response arrived but failed its CRC32 integrity check."""


def act_slot_spec(cfg: Config, action_dim: int, num_lanes: int):
    """(name, shape, dtype) of ONE fleet's act request/response slot.

    Request region (fleet-written): the batched AgentState the act fn
    consumes, minus hidden (server-resident), plus the reset mask, the
    resync hidden rows (only meaningful for MODE_RESYNC requests), the
    ``req_seq`` word (lets the server drop tokens superseded by a retry)
    and the CRC32 integrity word.  Response region (server-written): the
    q row per lane, the post-step hidden rows for block recording, and
    the response CRC32 (written last)."""
    n = num_lanes
    state_shape, state_dtype = state_spec(cfg)
    return (
        ("obs", (n, *cfg.stored_obs_shape), np.uint8),
        ("last_action", (n, action_dim), np.float32),
        ("last_reward", (n,), np.float32),
        ("reset_mask", (n,), np.uint8),
        ("sync_hidden", (n,) + state_shape, state_dtype),
        ("req_seq", (1,), np.int64),
        ("req_crc", (1,), np.uint32),
        ("q", (n, action_dim), np.float32),
        ("rsp_hidden", (n,) + state_shape, state_dtype),
        ("rsp_crc", (1,), np.uint32),
    )


def act_request_crc(views: dict, seq: int, mode: int) -> int:
    """CRC32 over the request payload plus the queue token header, so a
    slab/token mismatch is caught along with a torn or garbled write.
    Resync requests additionally cover the sync_hidden rows they carry.
    The convention (header words, payload order, mask) is replay.block's
    — one definition across every shm channel."""
    fields = [views[name] for name in _REQ_FIELDS]
    if int(mode) == MODE_RESYNC:
        fields.append(views["sync_hidden"])
    return payload_crc32((seq, int(mode)), fields)


def act_response_crc(views: dict, seq: int, mode: int) -> int:
    """CRC32 over the response region (q row; plus the hidden rows for
    commit-mode replies, which are the only ones that carry them).
    Written LAST by the server; the fleet verifies before consuming, and
    a mismatch is a bounded-retry failure, not a wedge."""
    fields = [views["q"]]
    if int(mode) != MODE_PEEK:
        fields.append(views["rsp_hidden"])
    return payload_crc32((seq, int(mode)), fields)


class ActChannel:
    """Trainer-side end of ONE fleet's inference RPC transport: the act
    slab plus the two token queues.  Fleet-private and retired wholesale
    on respawn, exactly like the block channel — a SIGKILLed process can
    die holding a queue's pipe lock, and corruption must not outlive the
    process that caused it."""

    def __init__(self, cfg: Config, action_dim: int, num_lanes: int, ctx):
        self.num_lanes = num_lanes
        self.spec = act_slot_spec(cfg, action_dim, num_lanes)
        self.nbytes, self.offsets = slot_layout(self.spec)
        self.shm = shared_memory.SharedMemory(create=True, size=self.nbytes)
        self.req_q = ctx.Queue()
        self.rsp_q = ctx.Queue()
        self.views = slot_views(self.shm.buf, self.spec, self.offsets,
                                self.nbytes, 0)

    def producer_info(self) -> Tuple[str, Any, Any]:
        """The picklable handle the fleet child needs to attach
        (:class:`RemoteActClient`)."""
        return (self.shm.name, self.req_q, self.rsp_q)

    def close(self) -> None:
        self.views = None
        try:
            self.shm.close()
        except BufferError:
            # a straggler thread still holds slab views; the mapping dies
            # with the process — unlinking below still frees the name
            pass
        try:
            self.shm.unlink()
        except FileNotFoundError:
            pass


class RemoteActClient:
    """Fleet-side act function: each call is one RPC over the act slab.

    Conforms to the ``make_act_fn`` signature ``(params, obs, last_action,
    last_reward, hidden) → (q, new_hidden)`` so it plugs straight into a
    VectorActor — ``params`` is ignored (the server reads the ParamStore;
    the local fallback path reads the fleet's own pumped store) and
    ``hidden`` is the fleet's authoritative carry, normally mirrored back
    from the server's replies and consumed directly by the degraded-mode
    local act path.  The returned arrays are views into the slab (remote)
    or fresh host arrays (local fallback), valid until the next call.
    Waiting polls ``stop_event`` so shutdown never hangs a fleet mid-step
    (raises FleetStopped, like the block producer).

    Failure handling (module docstring): every attempt is bounded by
    ``cfg.act_response_timeout`` and CRC-verified; retries are resync
    requests; exhausted retries open the circuit breaker and the client
    degrades to the lazily-built local act twin until a half-open probe
    re-attaches.  ``stats`` holds the slab-published ``resilience.*``
    counters."""

    def __init__(self, cfg: Config, action_dim: int, num_lanes: int,
                 info: Tuple[str, Any, Any], stop_event, src: int = 0,
                 param_store=None, local_act_factory=None,
                 retry: Optional[RetryPolicy] = None,
                 breaker: Optional[CircuitBreaker] = None):
        name, self.req_q, self.rsp_q = info
        self.cfg = cfg
        self.shm = shared_memory.SharedMemory(name=name)
        self.spec = act_slot_spec(cfg, action_dim, num_lanes)
        nbytes, offsets = slot_layout(self.spec)
        self.views = slot_views(self.shm.buf, self.spec, offsets, nbytes, 0)
        self.num_lanes = num_lanes
        self.stop_event = stop_event
        self.src = src
        self._seq = 0
        self.timeout = float(cfg.act_response_timeout)
        # the degraded-mode kit: a param feed (the fleet's pumped store)
        # plus a factory for the local act twin, built only if ever needed
        self.param_store = param_store
        self._local_act_factory = local_act_factory
        self._local_act = None
        self.retry = retry if retry is not None else RetryPolicy(
            attempts=3, base=0.05, max_delay=1.0,
            seed=cfg.seed + 7_577 * (src + 1))
        self.breaker = breaker if breaker is not None else CircuitBreaker(
            name=f"fleet{src}.act",
            cooldown=max(0.5, min(5.0, self.timeout)),
            on_transition=self._on_transition)
        # slab-published resilience counters (FLEET_STAT_FIELDS names)
        self.stats = dict(act_retries=0, circuit_opens=0, local_acts=0,
                          circuit_state=float(CLOSED))
        # lanes whose server-side hidden must be zeroed at the next commit
        # request; starts all-pending (a fresh incarnation's lanes all
        # begin a new episode, and a respawn must never inherit state)
        self._pending_resets = set(range(num_lanes))

    # ------------------------------------------------------------- breaker
    def _on_transition(self, bname: str, old: int, new: int) -> None:
        self.stats["circuit_state"] = float(new)
        if new == OPEN:
            self.stats["circuit_opens"] += 1
            log.warning(
                "fleet%d: act circuit OPEN (service unresponsive) — "
                "degrading to fleet-local inference on the last pumped "
                "weights; half-open probe every %.1fs", self.src,
                self.breaker.cooldown)
        elif new == CLOSED:
            log.warning("fleet%d: act circuit CLOSED — re-attached to the "
                        "inference service (hidden resynced from the "
                        "fleet's carry)", self.src)

    # --------------------------------------------------- VectorActor hooks
    def note_reset(self, lane: int) -> None:
        """VectorActor._reset_lane: lane ``lane`` starts a fresh episode —
        its server-resident hidden is zeroed at the next commit request.
        (Local-fallback commits clear these too: the reset is already
        reflected in the fleet's carry, which is what a later re-attach
        probe resyncs to the server.)"""
        self._pending_resets.add(int(lane))

    def clear_reset_notes(self) -> None:
        """VectorActor.restore: lanes resuming mid-episode must NOT zero
        the server hidden the snapshot just restored; non-resumable lanes
        re-note themselves through their reset."""
        self._pending_resets.clear()

    def __call__(self, params, obs, last_action, last_reward, hidden):
        return self._rpc(obs, last_action, last_reward, hidden,
                         MODE_COMMIT)

    def peek(self, params, obs, last_action, last_reward, hidden):
        """Bootstrap forward (episode-step cap): q at the given inputs
        WITHOUT advancing server state — no reset application, no hidden
        scatter.  Returns ``(q, None)``."""
        return self._rpc(obs, last_action, last_reward, hidden, MODE_PEEK)

    # ---------------------------------------------------------- local path
    def _await_params(self):
        """Latest pumped params for the local act twin, committed to the
        device it was resolved for once per version.  Blocks (stop-aware)
        until the param feed delivers the first snapshot — the pump
        primes each fleet's queue at spawn, so in practice this returns
        immediately."""
        if self.param_store is None:
            raise RuntimeError(
                f"fleet{self.src}: circuit open but no local fallback "
                "was provisioned (no param feed)")
        while True:
            _, params = self.param_store.get_placed(self._local_act.device)
            if params is not None:
                return params
            if self.stop_event.is_set():
                raise FleetStopped
            time.sleep(0.05)

    def _local(self, obs, last_action, last_reward, hidden, mode: int):
        """Degraded-mode act: the fleet's own jitted twin over its last
        pumped weights and its authoritative hidden carry — the exact
        executable local-inference mode runs, so blocks stay bit-exact
        with what a local-mode fleet would produce from those weights."""
        if self._local_act is None:
            if self._local_act_factory is None:
                raise RuntimeError(
                    f"fleet{self.src}: circuit open but no local act "
                    "factory was provisioned")
            log.warning("fleet%d: building the local act twin for "
                        "degraded-mode inference", self.src)
            self._local_act = self._local_act_factory()
        params = self._await_params()
        q, new_hidden = self._local_act(params, obs, last_action,
                                        last_reward, hidden)
        self.stats["local_acts"] += 1
        if mode == MODE_PEEK:
            return np.asarray(q), None
        # the reset is already reflected in the fleet's carry — the next
        # resync probe transfers it wholesale, so the server-side mask
        # notes are spent exactly like after a remote commit
        self._pending_resets.clear()
        return np.asarray(q), np.asarray(new_hidden)

    # ---------------------------------------------------------- remote rpc
    def _write_request(self, obs, last_action, last_reward, hidden,
                       mode: int) -> None:
        v = self.views
        v["obs"][:] = obs
        v["last_action"][:] = last_action
        v["last_reward"][:] = last_reward
        mask = np.zeros(self.num_lanes, np.uint8)
        if mode != MODE_PEEK and self._pending_resets:
            mask[sorted(self._pending_resets)] = 1
        v["reset_mask"][:] = mask
        if mode == MODE_RESYNC:
            v["sync_hidden"][:] = hidden
        self._seq += 1
        v["req_seq"][0] = self._seq
        # CRC last: the slab is only valid once the integrity word matches
        v["req_crc"][0] = act_request_crc(v, self._seq, mode)
        self.req_q.put((self._seq, int(mode)))

    def _await_response(self, mode: int,
                        timeout: Optional[float] = None) -> None:
        """Wait (bounded, stop-aware) for the reply to ``self._seq`` and
        verify its CRC.  Raises ActTimeout / ActGarbled — both retryable
        failures, never fleet-killing errors."""
        budget = self.timeout if timeout is None else timeout
        deadline = Deadline(budget)
        while True:
            if self.stop_event.is_set():
                raise FleetStopped
            try:
                seq = self.rsp_q.get(timeout=deadline.poll_timeout(0.2))
            except Empty:
                if deadline.expired:
                    raise ActTimeout(
                        f"fleet{self.src}: no inference response within "
                        f"{budget:.1f} s (seq {self._seq})")
                continue
            if seq != self._seq:
                continue   # stale token from a superseded attempt: ignore
            v = self.views
            if int(v["rsp_crc"][0]) != act_response_crc(v, seq, mode):
                raise ActGarbled(
                    f"fleet{self.src}: response {seq} failed CRC32")
            return

    def _attempt(self, obs, last_action, last_reward, hidden, mode: int,
                 timeout: Optional[float] = None):
        self._write_request(obs, last_action, last_reward, hidden, mode)
        self._await_response(mode, timeout=timeout)
        v = self.views
        if mode == MODE_PEEK:
            return v["q"], None
        self._pending_resets.clear()
        return v["q"], v["rsp_hidden"]

    def _rpc(self, obs, last_action, last_reward, hidden, mode: int):
        state = self.breaker.state
        if state != CLOSED:
            # peeks never probe: a peek cannot resync hidden, so closing
            # the circuit off one would re-attach with stale server state
            if (mode == MODE_PEEK or state == OPEN
                    or not self.breaker.allow_attempt()):
                return self._local(obs, last_action, last_reward, hidden,
                                   mode)
            # the half-open probe: ONE attempt, in resync mode, so a
            # success re-attaches bit-exact from the fleet's carry.
            # Probe with the COOLDOWN as its deadline, not the full RPC
            # budget — a probe that blocks act_response_timeout (60 s
            # default) every cooldown window would starve degraded-mode
            # acting to a sliver of wall-clock during a long outage
            try:
                out = self._attempt(obs, last_action, last_reward, hidden,
                                    MODE_RESYNC,
                                    timeout=min(self.timeout,
                                                self.breaker.cooldown))
            except (ActTimeout, ActGarbled) as e:
                log.warning("fleet%d: re-attach probe failed (%s) — "
                            "circuit re-opens", self.src, e)
                self.breaker.record_failure()
                return self._local(obs, last_action, last_reward, hidden,
                                   mode)
            self.breaker.record_success()
            return out
        # circuit closed: bounded retries; any retry after a miss runs in
        # resync mode because the failed attempt may have half-advanced
        # the server state (served late, response lost)
        eff = mode
        for attempt in range(1, self.retry.attempts + 1):
            try:
                out = self._attempt(obs, last_action, last_reward, hidden,
                                    eff)
            except (ActTimeout, ActGarbled) as e:
                if attempt >= self.retry.attempts:
                    log.warning(
                        "fleet%d: act RPC failed after %d attempts (%s)",
                        self.src, attempt, e)
                    self.breaker.record_failure()   # -> OPEN
                    return self._local(obs, last_action, last_reward,
                                       hidden, mode)
                self.stats["act_retries"] += 1
                if mode != MODE_PEEK:
                    eff = MODE_RESYNC
                time.sleep(self.retry.backoff(attempt))
                continue
            self.breaker.record_success()
            return out

    def close(self) -> None:
        try:
            self.views = None
            self.shm.close()
        except Exception:
            pass


class InferenceService:
    """The trainer-side act server for every serve-mode fleet.

    Owns the per-fleet :class:`ActChannel`\\ s (created/retired by
    ``ProcessFleetPlane._spawn``), the server-resident hidden array, and
    the jitted act function on the resolved device.  ``serve_once`` is the
    supervised fabric loop body: drain pending request tokens, give the
    other lockstep fleets ``cfg.inference_batch_window`` seconds to catch
    up (cross-fleet batching), run ONE full-batch act, scatter replies.

    The act always runs at the full ``num_actors`` batch (non-pending
    lanes carry stale scratch rows whose outputs are discarded): one
    compiled executable regardless of which fleet subset is pending, and
    the common case — lockstep fleets all pending — wastes nothing.
    """

    def __init__(self, cfg: Config, action_dim: int, specs: Sequence[Any],
                 ctx, registry=None):
        self.cfg = cfg
        self.action_dim = action_dim
        self.specs = list(specs)          # per-fleet (fleet_id, lo, hi)
        self.ctx = ctx
        # shared metric namespace (telemetry/registry.py); the owning
        # plane swaps in the run's registry via set_registry
        if registry is None:
            from r2d2_tpu.telemetry.registry import MetricsRegistry

            registry = MetricsRegistry()
        self.registry = registry
        F = len(self.specs)
        self.channels: List[Optional[ActChannel]] = [None] * F
        self._graveyard: List[ActChannel] = []
        N = cfg.num_actors
        self.hidden = zero_state(cfg, N)
        self._hidden_lock = threading.Lock()
        # full-batch request scratch, indexed by global lane id
        self.obs = np.zeros((N, *cfg.stored_obs_shape), np.uint8)
        self.last_action = np.zeros((N, action_dim), np.float32)
        self.last_reward = np.zeros(N, np.float32)
        # fleet -> (seq, commit, channel): drained-but-unanswered requests;
        # kept as service state so a supervisor restart of the serve loop
        # resumes and answers instead of wedging the blocked fleets
        self._pending: dict = {}
        self.param_store = None
        self._act = None
        self._params = None
        self._param_version = 0
        self.tracer = None                # set by train(); spans optional
        self.chaos = None                 # set by train(): the drop/garble
                                          # response fault sites live here
        self.batches = 0
        self.lanes_served = 0
        self.last_batch_lanes = 0
        self.peeks = 0
        self.requests_corrupt = 0
        self.shard_resets = 0
        self.partial_batches = 0          # batches serving < all attached
                                          # fleets (a dead/slow/degraded
                                          # fleet never holds the window
                                          # hostage — the rest act on)
        self.stale_requests = 0           # tokens superseded by a retry
        self.resyncs = 0                  # MODE_RESYNC requests honoured
        self.dropped_responses = 0        # chaos drop_act_response fires
        self.garbled_responses = 0        # chaos garble_act_response fires

    # ------------------------------------------------------------ channels
    def make_channel(self, f: int) -> ActChannel:
        """Fresh act channel for fleet ``f``, retiring any predecessor
        (unlink now, keep mapped — the serve loop may hold views; same
        discipline as the block channels)."""
        old = self.channels[f]
        if old is not None:
            try:
                old.shm.unlink()
            except FileNotFoundError:
                pass
            self._graveyard.append(old)
        self._pending.pop(f, None)   # the dead incarnation's request
        spec = self.specs[f]
        ch = ActChannel(self.cfg, self.action_dim, spec.hi - spec.lo,
                        self.ctx)
        self.channels[f] = ch
        return ch

    # -------------------------------------------------------- hidden state
    def reset_shard(self, f: int) -> None:
        """Zero fleet ``f``'s server-resident hidden lanes — the watchdog
        respawn path: a replacement fleet must never act on its dead
        predecessor's recurrent state."""
        spec = self.specs[f]
        with self._hidden_lock:
            self.hidden[spec.lo:spec.hi] = 0.0
        self.shard_resets += 1
        # a telemetry-visible record of every zeroing, per fleet — the
        # chaos respawn drill polls/asserts this instead of sleeping
        self.registry.inc("serve.shard_resets", fleet=str(f))

    def load_shard_hidden(self, f: int, hidden: np.ndarray) -> None:
        """Restore fleet ``f``'s hidden lanes from its actor snapshot
        (full-state --resume).  A geometry mismatch zeroes instead — the
        lanes resume cold, consistent with the actor-side fallback."""
        spec = self.specs[f]
        with self._hidden_lock:
            if hidden.shape != self.hidden[spec.lo:spec.hi].shape:
                log.warning(
                    "fleet%d: snapshot hidden %s does not match shard %s — "
                    "zeroing", f, hidden.shape,
                    self.hidden[spec.lo:spec.hi].shape)
                self.hidden[spec.lo:spec.hi] = 0.0
            else:
                self.hidden[spec.lo:spec.hi] = hidden

    # ---------------------------------------------------------------- act
    def start(self, param_store) -> None:
        self.param_store = param_store
        if self._act is None:
            from r2d2_tpu.actor import make_act_fn, resolve_act_device
            from r2d2_tpu.models.network import create_network

            # "auto" resolves to the DEFAULT device here (the learner's
            # accelerator — centralized inference exists to use it), not
            # local mode's CPU twin; "cpu" still forces the CPU twin, and
            # on a CPU-only host both land on the same scan/f32 twin
            device = resolve_act_device(
                "default" if self.cfg.act_device == "auto"
                else self.cfg.act_device)
            self._act = make_act_fn(
                self.cfg, create_network(self.cfg, self.action_dim),
                device=device)

    @property
    def act_device(self):
        """The device the service acts on (resolved by :meth:`start`)."""
        return self._act.device

    def _refresh_params(self) -> None:
        """Adopt the newest ParamStore publication, committed to the
        device the act was resolved for.  Acting on the learner's own
        device, the placement is a no-op on its published arrays — zero
        copies, ~zero staleness; host arrays (multi-host publishes) and a
        forced CPU twin pay one transfer per version."""
        version, params = self.param_store.get_placed(self._act.device)
        if params is None or version == self._param_version:
            return
        self._params = params
        self._param_version = version

    # --------------------------------------------------------------- serve
    def _drain(self, f: int) -> bool:
        """Pull one pending request token from fleet ``f`` (non-blocking).
        The channel is captured WITH the token: a watchdog respawn may
        retire it concurrently, and the reply must go to the slab the
        request was written into, not its replacement's."""
        ch = self.channels[f]
        if ch is None or f in self._pending:
            return False
        try:
            seq, mode = ch.req_q.get_nowait()
        except Empty:
            return False
        except Exception:
            return False   # retired channel / corrupted pipe: respawn path
        if int(ch.views["req_seq"][0]) != seq:
            # superseded by a retry: the fleet bumped its seq and is only
            # waiting on the newest one — answering this token would act
            # on a half-overwritten slab for a reply nobody consumes
            self.stale_requests += 1
            self.registry.inc("serve.stale_requests", fleet=str(f))
            return True    # progress: the retry token is behind it
        if int(ch.views["req_crc"][0]) != act_request_crc(ch.views, seq,
                                                          mode):
            # garbled slab (chaos, or a retry tearing the slab under a
            # stale in-flight token): DROP it.  Serving would act on
            # garbage — and for a resync, load the corrupt sync_hidden
            # over the shard — then stamp a VALID response CRC over the
            # poisoned reply, which the fleet would adopt undetected.
            # The fleet's bounded retry times out and resends clean
            self.requests_corrupt += 1
            log.warning("fleet%d: act request %d failed CRC32 — dropped "
                        "(fleet retry resends clean)", f, seq)
            return True
        self._pending[f] = (seq, int(mode), ch)
        return True

    def serve_once(self, idle_sleep: float = 0.001) -> int:
        """One service iteration: gather pending requests, act, scatter.
        Returns the number of lanes served (0 when idle)."""
        import jax

        F = len(self.specs)
        for f in range(F):
            self._drain(f)
        if not self._pending:
            if idle_sleep > 0:
                time.sleep(idle_sleep)
            return 0
        # batch window: lockstep peers post within microseconds of each
        # other in steady state — a short wait turns F singleton batches
        # into one cross-fleet batch.  The window is a hard per-batch
        # deadline: a dead, slow, or circuit-open fleet that never posts
        # cannot hold the others' acting hostage — the batch dispatches
        # with its lanes masked (counted in serve.partial_batches)
        if len(self._pending) < F and self.cfg.inference_batch_window > 0:
            window = Deadline(self.cfg.inference_batch_window)
            while len(self._pending) < F and not window.expired:
                if not any(self._drain(f) for f in range(F)):
                    time.sleep(0.0002)
        self._refresh_params()
        if self._params is None:   # no publication yet: keep requests
            time.sleep(idle_sleep)
            return 0
        tr = self.tracer
        pend = sorted(self._pending)
        with maybe_span(tr, "serve.assemble"):
            with self._hidden_lock:
                for f in list(pend):
                    item = self._pending.get(f)
                    if item is None:
                        # the watchdog retired this fleet (make_channel
                        # pops its pending request) between our snapshot
                        # and now — the requester is dead, skip it
                        pend.remove(f)
                        continue
                    _seq, mode, ch = item
                    spec = self.specs[f]
                    lo, hi = spec.lo, spec.hi
                    v = ch.views
                    self.obs[lo:hi] = v["obs"]
                    self.last_action[lo:hi] = v["last_action"]
                    self.last_reward[lo:hi] = v["last_reward"]
                    if mode == MODE_RESYNC:
                        # re-attach/retry: the fleet's carry is the
                        # authoritative recurrent state — load it over
                        # the shard BEFORE the reset mask so the served
                        # step continues bit-exact from wherever the
                        # fleet (local path included) left off
                        self.hidden[lo:hi] = v["sync_hidden"]
                        self.resyncs += 1
                        self.registry.inc("serve.resyncs", fleet=str(f))
                    if mode != MODE_PEEK:
                        resets = np.nonzero(v["reset_mask"])[0]
                        if resets.size:
                            self.hidden[lo + resets] = 0.0
                # consistent snapshot: a concurrent reset_shard (watchdog
                # respawn) must not tear mid-act
                hidden_in = self.hidden.copy()
        if not pend:
            return 0
        attached = sum(1 for ch in self.channels if ch is not None)
        if len(pend) < attached:
            self.partial_batches += 1
            self.registry.inc("serve.partial_batches")
        with maybe_span(tr, "serve.act"), \
                TRANSFER_GUARD.disallow("serve.act"):
            # the batch's declared H2D: the assembled lane slabs ride the
            # dispatch as implicit transfers of the numpy args
            with HOST_TRANSFERS.allowed("serve.act_put"):
                q, new_hidden = self._act(self._params, self.obs,
                                          self.last_action,
                                          self.last_reward, hidden_in)
            # ONE device→host fetch per cross-fleet batch, regardless of
            # how many fleets were pending — the guard counter makes the
            # serve e2e test assert exactly that (utils/trace.py).
            # Audit r19: ONE explicit device_get for both outputs (was
            # two implicit np.asarray syncs — same values, guard-exempt)
            with HOST_TRANSFERS.allowed("serve.act_fetch"):
                q, new_hidden = jax.device_get((q, new_hidden))
        lanes = 0
        with maybe_span(tr, "serve.scatter"):
            with self._hidden_lock:
                for f in pend:
                    item = self._pending.pop(f, None)
                    if item is None:   # fleet retired mid-batch; see above
                        continue
                    seq, mode, ch = item
                    spec = self.specs[f]
                    lo, hi = spec.lo, spec.hi
                    ch.views["q"][:] = q[lo:hi]
                    if mode != MODE_PEEK:
                        ch.views["rsp_hidden"][:] = new_hidden[lo:hi]
                        # only pending lanes advance; idle fleets' state
                        # is untouched by the full-batch act
                        self.hidden[lo:hi] = new_hidden[lo:hi]
                    else:
                        self.peeks += 1
                    # response CRC LAST — the reply is only valid once
                    # the integrity word matches (the fleet retries on a
                    # mismatch instead of consuming a torn reply)
                    ch.views["rsp_crc"][0] = act_response_crc(
                        ch.views, seq, mode)
                    lanes += hi - lo
                    chaos = self.chaos
                    if chaos is not None and chaos.garble_response():
                        # chaos: flip response bytes AFTER the CRC landed
                        # — the fleet's verification must catch it
                        ch.views["q"][0, 0] = np.float32(
                            ch.views["q"][0, 0]) + 1.0
                        self.garbled_responses += 1
                        self.registry.inc("serve.garbled_responses")
                    if chaos is not None and chaos.drop_response():
                        # chaos: lose the wakeup — the fleet's bounded
                        # retry must re-request and get answered
                        self.dropped_responses += 1
                        self.registry.inc("serve.dropped_responses")
                        continue
                    try:
                        ch.rsp_q.put(seq)
                    except Exception:
                        pass   # fleet died mid-rpc; the watchdog respawns
        self.batches += 1
        self.lanes_served += lanes
        self.last_batch_lanes = lanes
        if tr is not None:
            tr.gauge("serve.batch_lanes", lanes)
        if EVENTS.armed:
            # capture-window marker: one instant per served cross-fleet
            # batch with the lane count — the assemble/act/scatter spans
            # above already ride the Tracer→event bridge, this pins the
            # batch boundary + size on the trainer track
            EVENTS.instant("serve.batch", arg=lanes)
        return lanes

    # --------------------------------------------------------------- misc
    def health(self) -> dict:
        """Service stats for fleet health / train logs — the cross-fleet
        batch size is the headline (acceptance: observable per round)."""
        return dict(
            batches=self.batches,
            lanes_served=self.lanes_served,
            last_batch_lanes=self.last_batch_lanes,
            mean_batch_lanes=round(self.lanes_served / self.batches, 2)
            if self.batches else 0.0,
            peeks=self.peeks,
            requests_corrupt=self.requests_corrupt,
            shard_resets=self.shard_resets,
            param_version=self._param_version,
            partial_batches=self.partial_batches,
            stale_requests=self.stale_requests,
            resyncs=self.resyncs,
            dropped_responses=self.dropped_responses,
            garbled_responses=self.garbled_responses,
        )

    def close(self) -> None:
        for ch in list(self.channels) + self._graveyard:
            if ch is not None:
                ch.close()
