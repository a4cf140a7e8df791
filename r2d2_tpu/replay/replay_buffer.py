"""Prioritised sequence replay buffer (host-side data plane).

Capability-parity with the reference's ``ReplayBuffer`` (worker.py:38-261):
a ring of blocks with one PER leaf per learning sequence, stratified
prioritised sampling, IS weights, stale-index masking when leaves are
overwritten between sampling and the learner's priority feedback, and
size/env-step/episode-return accounting.

TPU-first redesign vs the reference: blocks live in **preallocated
contiguous ring arrays** instead of a Python list of ragged objects, so a
64-sequence batch is assembled by a handful of vectorised fancy-index
gathers into fixed-shape ``(B, T, ...)`` numpy arrays (replacing the
per-sample Python slicing loop + ``pad_sequence`` at worker.py:176-214).
Fixed shapes mean the jitted learner step compiles once; the gather is the
whole batch cost, which is what lets the host feed a TPU-rate learner.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional, Tuple

import numpy as np

from r2d2_tpu.config import Config
from r2d2_tpu.models.state import state_spec
from r2d2_tpu.replay.block import Block, slot_layout, slot_views
from r2d2_tpu.replay.sum_tree import SumTree
from r2d2_tpu.telemetry.tracing import EVENTS
from r2d2_tpu.utils.trace import held, maybe_span

# at most this many lineage flow points per sampled batch / feedback
# call: a B=64 batch touching 64 distinct blocks must not dump 64 flow
# records into the ring per draw — a few complete chains per capture is
# what the timeline needs
_FLOW_CAP = 8


def _emit_flows(name: str, trace_ids: np.ndarray, fph: str) -> None:
    """Flow points for the distinct nonzero capture-window trace ids in
    ``trace_ids`` (capped) — no-op unless a capture is armed."""
    if not EVENTS.armed:
        return
    seen = 0
    for tid in np.unique(trace_ids):
        if tid == 0:
            continue
        EVENTS.instant(name, flow=int(tid), fph=fph)  # graftlint: disable=telemetry-discipline -- pass-through helper; call sites pass literal names
        seen += 1
        if seen >= _FLOW_CAP:
            break


def _data_spec(cfg: Config, action_dim: int):
    """(name, shape, dtype) of the bulk experience arrays.  These are the
    arrays that can live on-device instead (replay/device_ring.py)."""
    NB, K, MS = cfg.num_blocks, cfg.seqs_per_block, cfg.max_block_steps
    BL = cfg.block_length
    state_shape, state_dtype = state_spec(cfg)
    return (
        ("obs", (NB, MS, *cfg.stored_obs_shape), np.uint8),
        ("last_action", (NB, MS, action_dim), bool),
        ("last_reward", (NB, MS), np.float32),
        ("action", (NB, BL), np.uint8),
        ("n_step_reward", (NB, BL), np.float32),
        ("n_step_gamma", (NB, BL), np.float32),
        ("hidden", (NB, K) + state_shape, state_dtype),
    )


def _count_spec(cfg: Config):
    """(name, shape, dtype) of the per-sequence/per-block accounting arrays
    — always host-side (they drive index computation and sampling)."""
    NB, K = cfg.num_blocks, cfg.seqs_per_block
    return (
        ("burn_in_steps", (NB, K), np.uint8),
        ("learning_steps", (NB, K), np.uint8),
        ("forward_steps", (NB, K), np.uint8),
        ("first_burn_in", (NB,), np.int64),
        ("block_learning_total", (NB,), np.int64),
    )


def _ring_spec(cfg: Config, action_dim: int):
    """(name, shape, dtype) of every preallocated host ring array — the
    single source of truth for both the allocation loop and the RAM
    guard."""
    return _data_spec(cfg, action_dim) + _count_spec(cfg)


def data_bytes(cfg: Config, action_dim: int) -> int:
    """Bytes of the bulk experience arrays alone (what a DeviceRing puts
    in HBM)."""
    return sum(int(np.prod(shape)) * np.dtype(dtype).itemsize
               for _, shape, dtype in _data_spec(cfg, action_dim))


def ring_bytes(cfg: Config, action_dim: int) -> int:
    """Total bytes the preallocated ring arrays will occupy.

    Dominated by ``obs``: at flagship defaults (5,000 blocks × 441 steps ×
    84·84 space-to-depth bytes) the obs ring alone is ~15.5 GB, allocated
    eagerly in ``ReplayBuffer.__init__`` — same transition count as the
    reference's 2M-transition buffer (config.py:16) but contiguous instead
    of lazily-held ragged blocks."""
    return sum(int(np.prod(shape)) * np.dtype(dtype).itemsize
               for _, shape, dtype in _ring_spec(cfg, action_dim))


def _layout_fingerprint(spec) -> list:
    """JSON-able (name, shape, dtype) list identifying a snapshot layout."""
    return [[name, list(shape), np.dtype(dtype).name]
            for name, shape, dtype in spec]


def _available_host_bytes() -> Optional[int]:
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except OSError:  # non-Linux host: skip the guard
        pass
    return None


class ReplayBuffer:
    """Synchronous core. Thread-safe via one lock; process/queue plumbing
    lives in :mod:`r2d2_tpu.train` so this class stays directly testable."""

    def __init__(self, cfg: Config, action_dim: int,
                 rng: Optional[np.random.Generator] = None,
                 device_ring: Optional[Any] = None,
                 tracer: Optional[Any] = None):
        """``device_ring`` (replay/device_ring.DeviceRing): when given, the
        bulk experience arrays live in HBM — ``add`` streams each block to
        the device once, ``sample_meta`` yields index bundles for the
        in-graph gather, and the big host data arrays are NOT allocated
        (``sample_batch`` then raises).

        ``tracer`` (utils/trace.Tracer): ``add`` records ``replay.stage``
        (zero-pad and H2D, outside the lock) and ``replay.commit`` (the
        time the lock is held against the dispatch thread);
        ``sample_meta`` records its wait for the lock as
        ``learner.lock_wait``."""
        self.cfg = cfg
        self.action_dim = action_dim
        self.device_ring = device_ring
        self.tracer = tracer
        if getattr(cfg, "in_graph_per", False) and device_ring is None:
            # fail HERE with the remedy, not with an AttributeError in an
            # actor thread at the first block commit: device PER cannot
            # run on the host-staged fallback (the host tree is never
            # populated and the priority loop is stripped, train.py)
            raise ValueError(
                "in_graph_per requires a device ring, but none was built "
                "— the ring did not fit the device budget or the "
                "multi-host shape checks failed (see the warning above); "
                "shrink buffer_capacity or set in_graph_per=False")

        # Slot groups (dp-sharded device ring): the ring's slot axis is
        # partitioned into G contiguous slabs, one per dp mesh group.  The
        # logical FIFO walk maps onto physical slots round-robin across the
        # slabs (see _phys_block) so every group fills from the first
        # block, and sampling draws each group's batch rows from its own
        # slab (sample_meta) so the in-graph gather never crosses shards.
        # G == 1 (host ring / replicated device ring) makes every mapping
        # the identity.
        self.G = (getattr(device_ring, "num_groups", 1)
                  if device_ring is not None else 1)
        assert cfg.num_blocks % self.G == 0  # DeviceRing validated this
        self._blocks_per_group = cfg.num_blocks // self.G
        # in-graph PER + dp slabs: host-side record of which slabs have
        # ever received a block with positive mass (the `ready` gate —
        # the host tree stays empty in that mode)
        self._group_filled = np.zeros(self.G, bool)

        spec = _count_spec(cfg) if device_ring is not None else _ring_spec(
            cfg, action_dim)
        # Fail fast with an actionable message instead of letting the
        # allocator OOM partway through the allocation loop (or, worse,
        # later as the lazily-committed pages fill).  Cap at 90% of
        # MemAvailable: the model, staged batches, and XLA host buffers
        # need their own headroom.
        need = sum(int(np.prod(shape)) * np.dtype(dtype).itemsize
                   for _, shape, dtype in spec)
        avail = _available_host_bytes()
        if avail is not None and need > 0.9 * avail:
            raise MemoryError(
                f"replay ring needs {need / 1e9:.1f} GB but only "
                f"{avail / 1e9:.1f} GB of host memory is available "
                "(guard requires 10% headroom) — reduce buffer_capacity / "
                "block_length / obs size (flagship defaults need ~16 GB; "
                "see README)")

        for name, shape, dtype in spec:
            setattr(self, name, np.zeros(shape, dtype))

        self.tree = SumTree(cfg.num_sequences, cfg.prio_exponent,
                            cfg.importance_sampling_exponent, rng=rng)

        # data-health sidecar (telemetry/learnhealth.py): the resident
        # block's member id per physical slot + cumulative sampled-row
        # counts per member — the replay-side proof that every
        # population member's experience is actually being TRAINED on,
        # not just stored.  Not part of the snapshot layout (a resume
        # recounts from its warm ring's new adds/draws).
        self._slot_member = np.zeros(cfg.num_blocks, np.int32)
        self.samples_per_member: Dict[int, int] = {}

        # block-lineage sidecar (telemetry/tracing.py): per PHYSICAL slot,
        # the resident block's cut/add wall-clock stamps (feed the
        # pipeline.block_age_at_train_s / pipeline.hop.* histograms) and
        # its capture-window trace id (0 in steady state).  Deliberately
        # NOT part of the snapshot layout: after a restore the stamps are
        # zero and age observation skips those slots.
        self._slot_cut_ts = np.zeros(cfg.num_blocks)
        self._slot_add_ts = np.zeros(cfg.num_blocks)
        self._slot_trace = np.zeros(cfg.num_blocks, np.int64)

        self.lock = threading.Lock()
        self.block_ptr = 0
        self.size = 0            # total learning steps stored (reference "size")
        self.env_steps = 0
        self.num_episodes = 0
        self.episode_reward = 0.0
        self.training_steps = 0
        self.sum_loss = 0.0
        self.corrupt_blocks = 0  # wire-format CRC mismatches, never reset
        # member-tagged experience flow (league/population.py): blocks
        # added per Block.member_id — cumulative, telemetry-only (not in
        # the replay snapshot: a resume recounts from its warm ring's
        # NEW adds).  {0: n} outside a population run
        self.blocks_per_member: Dict[int, int] = {}

    def __len__(self) -> int:
        return self.size

    def _phys_block(self, n):
        """Logical ring position → physical slot (round-robin over the G
        group slabs; identity for G == 1).  Bijection on [0, num_blocks)."""
        return (n % self.G) * self._blocks_per_group + n // self.G

    def _log_block(self, p):
        """Physical slot → logical ring position (inverse of
        :meth:`_phys_block`)."""
        return (p % self._blocks_per_group) * self.G + p // self._blocks_per_group

    @property
    def ready(self) -> bool:
        if self.size < self.cfg.learning_starts:
            return False
        if self.G > 1:
            # per-group sampling needs every slab non-empty; round-robin
            # fill reaches all slabs within the first G blocks, long before
            # any realistic learning_starts, but guard the degenerate case.
            if getattr(self.cfg, "in_graph_per", False):
                # priorities live on-device (the host tree stays empty):
                # gate on the host-side ever-filled record instead — a
                # slab counts filled once a block with positive mass
                # landed in it (add() below)
                return bool(self._group_filled.all())
            # Unlike the GIL-atomic `size` read above, the mass walk spans
            # many tree nodes — take the lock so a concurrent update's
            # level-order repair can't produce a torn (spuriously positive)
            # difference.
            K = self.cfg.seqs_per_block
            span = self._blocks_per_group * K
            with self.lock:
                if any(self.tree.prefix_mass((g + 1) * span)
                       - self.tree.prefix_mass(g * span) <= 0.0
                       for g in range(self.G)):
                    return False
        return True

    # ------------------------------------------------------------------ add
    def add(self, block: Block, priorities: np.ndarray,
            episode_reward: Optional[float]) -> None:
        """Overwrite the ring slot at ``block_ptr`` (worker.py:141-161)."""
        cfg = self.cfg
        K = cfg.seqs_per_block
        # Stage the device copy OUTSIDE the lock: the zero-pad + H2D
        # transfers are the expensive part of a device-ring write, and the
        # learner's sample+dispatch serialises on this same lock.  Only the
        # donated commit (one async dispatch) needs the ordering the lock
        # provides.
        staged = None
        if self.device_ring is not None:
            with maybe_span(self.tracer, "replay.stage"):
                staged = self.device_ring.stage(block)
        in_graph = getattr(cfg, "in_graph_per", False)
        if in_graph:
            # device-PER leaves: td**alpha — ``priorities`` arrives
            # K-length zero-padded past the block's real sequences
            # (block.py:108), and 0**alpha keeps the padding zero ==
            # unsampleable for the in-graph categorical; the metadata
            # bundle is per real sequence (k_seq-length)
            k_seq = block.num_sequences
            prios_alpha = (np.asarray(priorities, np.float64)
                           ** cfg.prio_exponent).astype(np.float32)
            meta = np.zeros((K, 3), np.int32)
            meta[:k_seq, 0] = block.burn_in_steps
            meta[:k_seq, 1] = block.learning_steps
            meta[:k_seq, 2] = block.forward_steps
        with self.lock, maybe_span(self.tracer, "replay.commit"):
            ptr = self.block_ptr
            # every array (and the PER leaves) is keyed by the PHYSICAL
            # slot; the logical ptr only orders the FIFO walk
            slot = self._phys_block(ptr)
            if in_graph:
                # priorities live on-device; the host tree stays empty
                self.device_ring.commit_per(slot, prios_alpha, meta,
                                            int(block.burn_in_steps[0]))
                if prios_alpha.max() > 0:
                    self._group_filled[slot // self._blocks_per_group] = True
            else:
                leaf_idxes = np.arange(slot * K, (slot + 1) * K,
                                       dtype=np.int64)
                self.tree.update(leaf_idxes, priorities)

            self.size -= int(self.block_learning_total[slot])

            k = block.num_sequences
            if staged is not None:
                # bulk data goes straight to HBM (once per block); the
                # stream-order/donation contract is upheld because we hold
                # self.lock, the same lock sample_meta dispatches under
                self.device_ring.commit(staged, slot)
            else:
                n_obs = block.obs.shape[0]
                n_steps = block.action.shape[0]
                self.obs[slot, :n_obs] = block.obs
                self.last_action[slot, :n_obs] = block.last_action
                self.last_reward[slot, :n_obs] = block.last_reward
                self.action[slot, :n_steps] = block.action
                self.n_step_reward[slot, :n_steps] = block.n_step_reward
                self.n_step_gamma[slot, :n_steps] = block.n_step_gamma
                self.hidden[slot, :k] = block.hidden
            self.burn_in_steps[slot] = 0
            self.learning_steps[slot] = 0
            self.forward_steps[slot] = 0
            self.burn_in_steps[slot, :k] = block.burn_in_steps
            self.learning_steps[slot, :k] = block.learning_steps
            self.forward_steps[slot, :k] = block.forward_steps
            self.first_burn_in[slot] = int(block.burn_in_steps[0])

            total = int(block.learning_steps.sum())
            self.block_learning_total[slot] = total
            self.size += total
            self.env_steps += total

            self.block_ptr = (ptr + 1) % cfg.num_blocks
            self._slot_cut_ts[slot] = block.cut_ts
            self._slot_add_ts[slot] = time.time()
            self._slot_trace[slot] = block.trace_id
            m = int(block.member_id)
            self._slot_member[slot] = m
            self.blocks_per_member[m] = self.blocks_per_member.get(m, 0) + 1
            if episode_reward is not None:
                self.episode_reward += episode_reward
                self.num_episodes += 1
        if block.trace_id:
            # lineage hop (armed capture only): the block landed in a ring
            # — the same event whether this buffer is the K=1 in-process
            # ring or a shard owner process's slice
            _emit_flows("replay.add_block", np.array([block.trace_id]),
                        "t")

    # --------------------------------------------------------------- sample
    def sample_batch(self, batch_size: Optional[int] = None) -> Dict[str, np.ndarray]:
        """Assemble one fixed-shape training batch.

        Returns a dict of arrays (B = batch, T = seq_len, L = learning_steps):
        obs (B,T,*obs) u8 · last_action (B,T,A) f32 · last_reward (B,T) f32 ·
        hidden (B,2,layers,H) · action (B,L) i32 · n_step_reward/gamma (B,L) ·
        burn_in/learning/forward (B,) i32 · is_weights (B,) f32, plus host-only
        bookkeeping: idxes, block_ptr snapshot, env_steps (worker.py:219-238).
        """
        cfg = self.cfg
        if self.device_ring is not None:
            raise RuntimeError(
                "sample_batch needs host data arrays; this buffer runs "
                "device_replay — use sample_meta + the in-graph gather")
        B = batch_size or cfg.batch_size
        with self.lock:
            if self.size == 0:
                raise RuntimeError(
                    "sample_batch on an empty buffer; wait for add() (use "
                    "`ready` to gate on learning_starts)")
            idxes, is_weights = self.tree.sample(B)
            self._note_sampled(idxes)
            batch = dict(
                self._gather_rows(idxes),
                is_weights=is_weights.astype(np.float32),
                idxes=idxes,
                block_ptr=self.block_ptr,
                env_steps=self.env_steps,
                ages=self._row_ages(idxes),
            )
        if EVENTS.armed:
            _emit_flows("replay.sample",
                        self._slot_trace[idxes // cfg.seqs_per_block], "t")
        return batch

    def _note_sampled(self, idxes: np.ndarray) -> None:
        """Count sampled rows per resident member (caller holds the
        lock) — the per-member sample fractions of the data-health
        surface."""
        members = self._slot_member[idxes // self.cfg.seqs_per_block]
        for m, c in zip(*np.unique(members, return_counts=True)):
            m = int(m)
            self.samples_per_member[m] = (
                self.samples_per_member.get(m, 0) + int(c))

    def _row_ages(self, idxes: np.ndarray) -> np.ndarray:
        """(n, 2) float32 per-row block ages at gather time — seconds
        since the block was cut (column 0: the end-to-end freshness the
        learner trains on) and since it landed in this ring (column 1:
        the replay-residency hop).  Rows whose slot has no stamp (a
        restored snapshot — the sidecar is not persisted) carry -1 and
        the observers skip them.  Caller holds the lock."""
        slots = idxes // self.cfg.seqs_per_block
        now = time.time()
        cut, add = self._slot_cut_ts[slots], self._slot_add_ts[slots]
        ages = np.empty((idxes.shape[0], 2), np.float32)
        ages[:, 0] = np.where(cut > 0, np.maximum(0.0, now - cut), -1.0)
        ages[:, 1] = np.where(add > 0, np.maximum(0.0, now - add), -1.0)
        return ages

    def _gather_rows(self, idxes: np.ndarray,
                     out: Optional[Dict[str, np.ndarray]] = None
                     ) -> Dict[str, np.ndarray]:
        """The vectorised fancy-index gather of the per-row batch fields
        for leaf ``idxes`` — the assembly core shared by
        :meth:`sample_batch` (K=1 in-process path) and
        :meth:`serve_sample` (a sharded-plane owner process gathering its
        preassembled response rows).  Caller holds the lock.

        ``out``: destination views (the sharded plane's response slab,
        each already sliced to ``len(idxes)`` rows) — the dominant
        ``obs`` gather then runs as ONE ``np.take(..., out=)`` pass
        straight into the slab instead of materialising an intermediate
        batch-sized array first (tens of MB per RPC at pong scale).

        INVARIANT (load-bearing): the clamp below pads short sequences
        with whatever bytes previously occupied the ring slot.  This is
        safe because every index the learner gathers is
        < burn_in + learning + forward (learner/step.py:_window_indices
        clamps to that bound), i.e. strictly before the stale region,
        and loss/priorities are masked to the learning window.  The
        stale tail does flow through the LSTM scan, but only *after*
        the last gathered timestep, so it cannot affect any used
        output.  Tested in tests/test_replay_buffer.py.
        """
        cfg = self.cfg
        K, L, T = cfg.seqs_per_block, cfg.learning_steps, cfg.seq_len
        block_idx = idxes // K
        seq_idx = idxes % K

        burn_in = self.burn_in_steps[block_idx, seq_idx].astype(np.int64)
        learning = self.learning_steps[block_idx, seq_idx].astype(np.int64)
        forward = self.forward_steps[block_idx, seq_idx].astype(np.int64)

        # obs-coordinate window start: first burn-in prefix + k full
        # learning windows (worker.py:186), reaching back over this
        # sequence's own burn-in.
        start = self.first_burn_in[block_idx] + seq_idx * L
        t0 = start - burn_in
        time_idx = np.minimum(t0[:, None] + np.arange(T),
                              cfg.max_block_steps - 1)
        bcol = block_idx[:, None]
        widx = np.minimum(seq_idx[:, None] * L + np.arange(L),
                          cfg.block_length - 1)
        if out is None:
            return dict(
                obs=self.obs[bcol, time_idx],
                last_action=self.last_action[bcol, time_idx].astype(
                    np.float32),
                last_reward=self.last_reward[bcol, time_idx],
                hidden=self.hidden[block_idx, seq_idx],
                action=self.action[bcol, widx].astype(np.int32),
                n_step_reward=self.n_step_reward[bcol, widx],
                n_step_gamma=self.n_step_gamma[bcol, widx],
                burn_in=burn_in.astype(np.int32),
                learning=learning.astype(np.int32),
                forward=forward.astype(np.int32),
            )
        n = idxes.shape[0]
        # obs dominates the batch bytes: one flat-index take straight
        # into the destination (same [block, time] pairs as the fancy
        # gather above — bit-identical rows, one fewer full pass)
        flat_t = (block_idx[:, None] * cfg.max_block_steps
                  + time_idx).ravel()
        np.take(self.obs.reshape(cfg.num_blocks * cfg.max_block_steps, -1),
                flat_t, axis=0, out=out["obs"].reshape(n * T, -1))
        # the rest is small relative to obs: plain gathers/casts into out
        out["last_action"][...] = self.last_action[bcol, time_idx]
        out["last_reward"][...] = self.last_reward[bcol, time_idx]
        out["hidden"][...] = self.hidden[block_idx, seq_idx]
        out["action"][...] = self.action[bcol, widx]
        out["n_step_reward"][...] = self.n_step_reward[bcol, widx]
        out["n_step_gamma"][...] = self.n_step_gamma[bcol, widx]
        out["burn_in"][...] = burn_in
        out["learning"][...] = learning
        out["forward"][...] = forward
        return out

    def serve_sample(self, n: int,
                     out: Optional[Dict[str, np.ndarray]] = None):
        """One shard-side sample service call (the sharded replay plane's
        owner processes, parallel/replay_shards.py): a stratified draw of
        ``n`` rows over THIS buffer's own tree plus the gathered row
        fields.  Returns ``(rows, idxes, raw_prios, block_ptr,
        env_steps)`` — priorities travel RAW (no zero-clamp, no IS
        normalisation) because the trainer-side coordinator normalises by
        the min across ALL shards' rows at once, preserving the K=1
        min-of-the-whole-batch scheme; ``block_ptr`` is this buffer's
        local FIFO pointer, which the shard's own
        :meth:`update_priorities` stale-mask needs at feedback time.
        ``out``: response-slab destination views (already sliced to
        ``n`` rows) the gather writes straight into.  The trailing
        ``ages`` element is the :meth:`_row_ages` lineage decomposition
        the trainer-side coordinator feeds into the ``pipeline.*``
        histograms (the shard process has no registry of its own)."""
        with self.lock:
            if self.size == 0 or self.tree.total <= 0:
                # the coordinator's mass vector can be one publish stale —
                # answer empty instead of raising so the trainer
                # redistributes the rows over the shards that have mass
                return None
            idxes, prios = self.tree.sample(n, raw=True)
            self._note_sampled(idxes)
            rows = self._gather_rows(idxes, out=out)
            ages = self._row_ages(idxes)
        if EVENTS.armed:
            _emit_flows("replay.sample",
                        self._slot_trace[idxes // self.cfg.seqs_per_block],
                        "t")
        return rows, idxes, prios, self.block_ptr, self.env_steps, ages

    # ---------------------------------------------------------- sample (meta)
    def sample_meta(self, k: int, batch_size: Optional[int] = None,
                    dispatch=None,
                    raw_densities: bool = False) -> Dict[str, np.ndarray]:
        """Sample ``k`` index bundles for the in-graph device gather
        (replay/device_ring.gather_batch) — the index arithmetic of
        ``sample_batch`` without touching any data array.

        The k bundles are drawn without intermediate priority feedback,
        mirroring the prefetch depth of the queued host path (the reference
        stages up to 8+4 batches ahead of the learner, worker.py:300-316).

        ``dispatch``, when given, is called as ``dispatch(ints, weights)``
        while the buffer lock is still held and its result returned under
        ``meta["dispatched"]`` — this orders the train-step dispatch before
        any later ring write (the device_ring concurrency contract).

        dp-sharded rings (G > 1): batch rows [g·B/G, (g+1)·B/G) are drawn
        from group g's slab via :meth:`SumTree.sample_range`, so row chunk
        g — which a ``P(None, "dp")`` sharding places on dp-index g — only
        references slots that device group holds.  Priorities still drive
        selection *within* each group; the fixed B/G per-group allocation
        is the one deviation from global stratified sampling (group
        assignment is round-robin, i.e. priority-independent, so group
        masses stay near-equal).  IS weights are exact for the realised
        distribution: row inclusion density is prio/mass_group, and weights
        are ``(q/min_q)^-beta`` min-normalised across the WHOLE batch —
        the reference scheme applied to the true per-group probabilities.

        ``raw_densities=True`` returns the inclusion densities q in the
        ``is_weights`` slots instead of normalised weights — the
        multi-host device-replay plane samples per host and normalises by
        the min across ALL hosts' rows (learner/learner.py), keeping the
        min-of-the-whole-batch scheme across the pod.

        Returns ints (k,B,6) i32 · is_weights (k,B) f32 · idxes (k,B) i64 ·
        block_ptr · env_steps.
        """
        cfg = self.cfg
        B = batch_size or cfg.batch_size
        K, L = cfg.seqs_per_block, cfg.learning_steps
        if B % self.G:
            raise ValueError(
                f"batch_size {B} not divisible by the ring's {self.G} "
                "slot groups")
        ints = np.empty((k, B, 6), np.int32)
        weights = np.empty((k, B), np.float32)
        idxes = np.empty((k, B), np.int64)
        with held(self.lock, self.tracer, "learner.lock_wait"):
            if self.size == 0:
                raise RuntimeError(
                    "sample_meta on an empty buffer; wait for add() (use "
                    "`ready` to gate on learning_starts)")
            for j in range(k):
                if raw_densities:
                    idx, w = self._grouped_densities(B)
                elif self.G == 1:
                    idx, w = self.tree.sample(B)
                else:
                    idx, w = self._sample_grouped(B)
                block_idx = idx // K
                seq_idx = idx % K
                burn_in = self.burn_in_steps[block_idx, seq_idx].astype(
                    np.int64)
                start = self.first_burn_in[block_idx] + seq_idx * L
                ints[j, :, 0] = block_idx
                ints[j, :, 1] = start - burn_in          # t0, always >= 0
                ints[j, :, 2] = seq_idx
                ints[j, :, 3] = burn_in
                ints[j, :, 4] = self.learning_steps[block_idx, seq_idx]
                ints[j, :, 5] = self.forward_steps[block_idx, seq_idx]
                weights[j] = w
                idxes[j] = idx
                self._note_sampled(idx)
            meta = dict(ints=ints, is_weights=weights, idxes=idxes,
                        block_ptr=self.block_ptr, env_steps=self.env_steps)
            if dispatch is not None:
                meta["dispatched"] = dispatch(ints, weights)
        return meta

    def _grouped_densities(self, B: int) -> Tuple[np.ndarray, np.ndarray]:
        """One B-row draw (B/G rows per group slab) returning the raw
        per-row inclusion densities prio/mass_group (caller holds the
        lock).  Zero-density leaves (a descent landing on a zero leaf
        through float error) are clamped to the smallest positive sampled
        density, mirroring SumTree.sample's guard."""
        K = self.cfg.seqs_per_block
        span = self._blocks_per_group * K
        per = B // self.G
        idx_parts, q_parts = [], []
        for g in range(self.G):
            lo, hi = g * span, (g + 1) * span
            part, prios, mass = self.tree.sample_range(per, lo, hi)
            idx_parts.append(part)
            q_parts.append(prios / mass)
        idx = np.concatenate(idx_parts)
        q = np.concatenate(q_parts)
        pos = q[q > 0]
        q = np.maximum(q, pos.min() if pos.size else 1.0)
        return idx, q

    def _sample_grouped(self, B: int) -> Tuple[np.ndarray, np.ndarray]:
        """One B-row draw for a G-group ring with IS weights normalised by
        the minimum sampled density (caller holds the lock)."""
        idx, q = self._grouped_densities(B)
        w = (q / q.min()) ** (-self.tree.is_exponent)
        return idx, w

    # ------------------------------------------------------- priority update
    def update_priorities(self, idxes: np.ndarray, priorities: np.ndarray,
                          old_ptr: int, loss: float) -> None:
        """Write back learner priorities, discarding indices whose ring slots
        were overwritten since the batch was sampled (worker.py:242-261).

        The overwritten set is the interval [old_ptr, new_ptr) of the
        LOGICAL ring walk (with wraparound); leaf indices are physical, so
        they map back through :meth:`_log_block` first (identity for
        G == 1, where this reduces to the reference's pointer arithmetic).
        """
        K = self.cfg.seqs_per_block
        with self.lock:
            new_ptr = self.block_ptr
            n = self._log_block(idxes // K)
            if new_ptr > old_ptr:
                mask = (n < old_ptr) | (n >= new_ptr)
            elif new_ptr < old_ptr:
                mask = (n < old_ptr) & (n >= new_ptr)
            else:
                mask = np.ones_like(idxes, dtype=bool)
            self.tree.update(idxes[mask], priorities[mask])
            self.training_steps += 1
            self.sum_loss += float(loss)
            traces = (self._slot_trace[idxes[mask] // K]
                      if EVENTS.armed and mask.any() else None)
        if traces is not None:
            # lineage terminus (armed capture only): priority feedback
            # landed back on the owning ring — the end of the flow chain
            _emit_flows("replay.priority_feedback", traces, "f")

    def note_corrupt_block(self) -> None:
        """A wire-format integrity check failed and the block was dropped
        (actor_procs.ingest_once): count it so the log plane surfaces a
        garbling transport instead of silently thinning the data."""
        with self.lock:
            self.corrupt_blocks += 1

    def note_updates(self, n: int, loss_sum: float) -> None:
        """Learner-side update accounting when priority feedback never
        crosses the host (``cfg.in_graph_per`` — the scatter happens
        inside the super-step), so the log plane's ``stats()`` counters
        stay live without :meth:`update_priorities`."""
        with self.lock:
            self.training_steps += n
            self.sum_loss += float(loss_sum)

    # ------------------------------------------------------------- snapshot
    # scalar state that rides the replay snapshot's JSON meta (arrays ride
    # the binary payload); order is the wire order of the restore loop
    STATE_COUNTERS = ("block_ptr", "size", "env_steps", "num_episodes",
                      "episode_reward", "training_steps", "sum_loss",
                      "corrupt_blocks")

    def state_spec(self):
        """(name, shape, dtype) of the on-disk replay-snapshot payload: the
        ring arrays (the block.py slot layout reused at whole-ring scale)
        plus the PER leaf vector."""
        return _ring_spec(self.cfg, self.action_dim) + (
            ("tree_leaves", (self.tree.capacity,), np.float64),)

    def write_state(self, path: str) -> Dict[str, Any]:
        """Serialise the full replay state into ``path`` — one flat binary
        laid out by :func:`~r2d2_tpu.replay.block.slot_layout` over
        :meth:`state_spec` (the shm wire format's own layout scheme, so the
        on-disk format cannot drift from the ring a future field change
        lands in).  Returns the JSON-able meta (counters + sampling RNG +
        layout fingerprint) that :meth:`read_state` validates against.

        Host-ring buffers only: a device ring's bulk arrays live in HBM
        (and under ``in_graph_per`` so do the priorities) — those runs
        save learner state alone (documented in docs/OPERATIONS.md)."""
        if self.device_ring is not None:
            raise RuntimeError(
                "replay snapshot requires the host ring; device_replay "
                "runs persist learner state only")
        spec = self.state_spec()
        nbytes, offsets = slot_layout(spec)
        mm = np.memmap(path, np.uint8, "w+", shape=(nbytes,))
        views = slot_views(mm, spec, offsets, nbytes, 0)
        # the lock covers only the RAM-speed copy into the page cache (a
        # consistent ring+tree+counter cut); the msync below — the
        # disk-bound part, seconds at flagship ring sizes — runs with the
        # lock RELEASED so periodic snapshots don't flatline actor ingest
        # and batch staging for the duration of the write
        with self.lock:
            for name, _, _ in spec:
                views[name][:] = (self.tree.leaf_values()
                                  if name == "tree_leaves"
                                  else getattr(self, name))
            meta = dict(
                layout=_layout_fingerprint(spec),
                nbytes=nbytes,
                counters={k: getattr(self, k) for k in self.STATE_COUNTERS},
                rng_state=self.tree.rng.bit_generator.state,
                tree_total=self.tree.total,
            )
        del views
        mm.flush()
        del mm
        return meta

    def read_state(self, path: str, meta: Dict[str, Any]) -> None:
        """Restore the state :meth:`write_state` captured.  Raises
        ``ValueError`` when the snapshot was written under a different
        buffer geometry (the caller warns and resumes cold instead of
        ingesting a misaligned ring)."""
        spec = self.state_spec()
        nbytes, offsets = slot_layout(spec)
        want = _layout_fingerprint(spec)
        if meta.get("layout") != want:
            raise ValueError(
                "replay snapshot layout mismatch — written under a "
                "different buffer geometry/config; resuming with a cold "
                f"buffer (snapshot {meta.get('layout')} vs config {want})")
        mm = np.memmap(path, np.uint8, "r", shape=(nbytes,))
        views = slot_views(mm, spec, offsets, nbytes, 0)
        with self.lock:
            for name, _, _ in spec:
                if name == "tree_leaves":
                    self.tree.load_leaves(views[name])
                else:
                    getattr(self, name)[:] = views[name]
            c = meta["counters"]
            self.block_ptr = int(c["block_ptr"])
            self.size = int(c["size"])
            self.env_steps = int(c["env_steps"])
            self.num_episodes = int(c["num_episodes"])
            self.episode_reward = float(c["episode_reward"])
            self.training_steps = int(c["training_steps"])
            self.sum_loss = float(c["sum_loss"])
            self.corrupt_blocks = int(c.get("corrupt_blocks", 0))
            if meta.get("rng_state") is not None:
                self.tree.rng.bit_generator.state = meta["rng_state"]
        del views
        del mm

    # ---------------------------------------------------------- data health
    def data_health(self) -> Dict[str, Any]:
        """Learning-health view of the replay plane (telemetry/
        learnhealth.py; docs/OBSERVABILITY.md `learnhealth.replay.*`):
        the PER distribution's effective sample size + fixed-bucket
        priority histogram over the sum-tree leaves, the cumulative
        replay-ratio gauge (samples consumed per transition inserted),
        and per-member sampled-row counts (the ``member_id`` stamp).

        Under ``in_graph_per`` the priority leaves live on-device (the
        host tree stays empty) — ``priorities`` is then None; fetching
        the leaf vector per log interval would race the dispatch loop's
        donated handles, so the device-PER plane reports ratio/member
        flow only (documented in docs/OBSERVABILITY.md)."""
        from r2d2_tpu.telemetry.learnhealth import (
            priority_health,
            replay_ratio,
        )

        cfg = self.cfg
        in_graph = (getattr(cfg, "in_graph_per", False)
                    and self.device_ring is not None)
        with self.lock:
            leaves = None if in_graph else self.tree.leaf_values()
            training_steps = self.training_steps
            env_steps = self.env_steps
            samples = dict(self.samples_per_member)
        out: Dict[str, Any] = dict(
            replay_ratio=replay_ratio(cfg, training_steps, env_steps),
            samples_per_member=samples,
            priorities=None if leaves is None else priority_health(leaves),
        )
        return out

    # ---------------------------------------------------------------- stats
    def stats(self) -> Dict[str, float]:
        with self.lock:
            s = dict(
                size=self.size, env_steps=self.env_steps,
                training_steps=self.training_steps,
                num_episodes=self.num_episodes,
                episode_reward=self.episode_reward,
                sum_loss=self.sum_loss,
                corrupt_blocks=self.corrupt_blocks,
                # the in-process buffer has no owner processes to lose;
                # the key exists so the log plane / r2d2_top render one
                # schema whether replay is sharded
                # (parallel/replay_shards.py reports real counts) or not
                shard_respawns=0,
                # member-tagged blocks (population runs tag via the wire
                # format's member_id word; {0: n} otherwise) — the
                # replay-side proof that every member's experience is
                # actually flowing
                blocks_per_member=dict(self.blocks_per_member),
            )
            self.episode_reward = 0.0
            self.num_episodes = 0
            self.sum_loss = 0.0
        return s
