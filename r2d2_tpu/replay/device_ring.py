"""Device-resident replay ring: replay data lives in HBM, not host RAM.

The reference's data plane moves every training batch across the host↔device
boundary (worker.py:330-342 `.to(device)` per step).  At flagship shapes that
is ~40 MB per batch — the dominant system cost on any real interconnect.
The TPU-first redesign inverts the flow:

- Each experience block crosses H2D **once**, when the actor produces it
  (~3 MB, at block-production rate — orders of magnitude less traffic than
  per-batch staging).
- The ring arrays (same layout as the host ring, replay_buffer.py) live on
  the device; batch assembly is an in-graph gather executed at HBM
  bandwidth inside the jitted train step.
- The host keeps what it is good at: the sum-tree, priorities, ring
  accounting, and stale-index masking.  Only tiny index/weight arrays cross
  per batch.

Writes are donated ``dynamic_update_index_in_dim`` updates — the ring is
updated in place on device, never reallocated.

Capacity envelope — two mesh layouts (``layout=``):

- ``"replicated"``: every device holds the full ring; gathers need no
  collectives, capacity is bounded by ONE chip's HBM.
- ``"dp"``: the slot axis shards over the ``dp`` mesh axis, so capacity
  scales with the mesh — e.g. the flagship 2M-transition buffer
  (~15.5 GB) does not fit a single v5e chip (16 GB) next to params, but
  dp=8 holds ~2 GB/chip.  The ReplayBuffer walks ring slots round-robin
  across the dp groups' contiguous slot slabs (every group fills from the
  first block; replay_buffer._phys_block), samples each group's batch
  rows from its own leaf slice (``SumTree.sample_range``, IS weights
  min-normalised across the whole batch), and maps physical slots back to
  the logical FIFO walk for stale-feedback masking.  The in-graph gather
  uses GLOBAL slot indices under GSPMD — the sharding table declares the
  slot-axis layout (``ring.*`` entries, parallel/sharding.py) and XLA
  partitions the gather; because each dp group's sampled rows reference
  only its own slab (sample_meta's per-group quota), the partitioned
  gather stays local in practice, with no hand-written shard_map.

Multi-host meshes compose the same layout across processes: each host
builds a dp ring over its LOCAL submesh (its dp groups' slabs) and fills
it with its own actors' experience; the learner stitches the per-host
device shards into the global ring view with zero data movement and
dispatches the same sharded super-step in SPMD lockstep
(``Learner._run_device_multihost``) — replay capacity scales with the
pod, batch bytes never touch host RAM or DCN.

CONCURRENCY CONTRACT: ``write`` and ``snapshot``+train-step-dispatch must
be externally serialised (the ReplayBuffer's lock is the coordination
point — add() writes under it, the learner samples indices and dispatches
under it).  Two reasons: a ``write`` donates the current handles, so a
racing dispatch could hand XLA a deleted buffer; and an index bundle
computed from the host accounting must be dispatched before any later
write lands, or the on-device gather could read a slot newer than the
indices describe.  Device-stream ordering guarantees the rest: dispatches
execute in order, so a bundle dispatched before a write reads pre-write
data.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from r2d2_tpu.config import Config
from r2d2_tpu.replay.block import Block

# data arrays mirrored on device; the count arrays (burn_in/learning/
# forward, first_burn_in) stay host-only — they are needed for *index
# computation*, which is host work.  Single-sourced from the sharding
# table's RING_DATA_KEYS so the ring's slabs and the table's `ring.*`
# sharding entries can never drift.
from r2d2_tpu.parallel.sharding import RING_DATA_KEYS as _DATA_KEYS


# rows per u8 tile on the TPU: 8 sublanes x 4 bytes packed per 32-bit word
_OBS_ROW_TILE = 32


def _slot_shapes(cfg: Config, action_dim: int) -> Dict[str, Any]:
    """Per-slot shapes of the DEVICE ring.  Same fields as the host ring,
    except for how a block's frames are laid out — two repairs found
    bringing the super-step up on a v5e (PERF.md Findings, PR 21):

    - a frame is one FLAT byte row.  XLA answers a gather from a
      ``(NB, MS, 21, 21, 16)`` ring that feeds a conv by re-laying-out the
      WHOLE ring for the conv (a padded copy 7x the ring: 21.7 GB for a
      3.1 GB ring — the super-step does not compile), while gathering
      flat rows and reshaping the gathered batch costs a 141 MB temp;
    - the frame-row axis is padded to a whole number of u8 tiles
      (441 -> 448 rows).  With a ragged row count the compiler lays the
      ring out block-minor to save the padding, and the gather it emits
      for that layout inside the k-step loop reads out of bounds — the
      core halts with an HBM page fault on the first dispatch whose
      window reaches a block's late rows.  Pad rows are never read
      (:func:`gather_batch` clamps to ``max_block_steps - 1``).

    :func:`gather_batch` restores ``cfg.stored_obs_shape``."""
    MS, BL = cfg.max_block_steps, cfg.block_length
    K, layers, H = cfg.seqs_per_block, cfg.lstm_layers, cfg.hidden_dim
    obs_rows = -(-MS // _OBS_ROW_TILE) * _OBS_ROW_TILE
    return dict(
        obs=((obs_rows, int(np.prod(cfg.stored_obs_shape))), np.uint8),
        last_action=((MS, action_dim), np.bool_),
        last_reward=((MS,), np.float32),
        action=((BL,), np.uint8),
        n_step_reward=((BL,), np.float32),
        n_step_gamma=((BL,), np.float32),
        hidden=((K, 2, layers, H), np.float32),
    )


def device_bytes(cfg: Config, action_dim: int) -> int:
    """Logical bytes of the device ring's arrays (what the capacity guard
    budgets; HBM holds them at ~1.03x on the v5e)."""
    return cfg.num_blocks * sum(
        int(np.prod(shape)) * np.dtype(dtype).itemsize
        for shape, dtype in _slot_shapes(cfg, action_dim).values())


def _write_slot_fn(arrays: Dict[str, jnp.ndarray],
                   slot: Dict[str, jnp.ndarray], ptr: jnp.ndarray):
    return {k: jax.lax.dynamic_update_index_in_dim(arrays[k], slot[k], ptr,
                                                   axis=0)
            for k in arrays}


_write_slot = jax.jit(_write_slot_fn, donate_argnums=(0,))


@jax.named_scope("ring_gather")
def gather_batch(cfg: Config, arrays: Dict[str, jnp.ndarray],
                 ints: jnp.ndarray, is_weights: jnp.ndarray
                 ) -> Dict[str, jnp.ndarray]:
    """In-graph batch assembly — the device twin of
    ``ReplayBuffer.sample_batch`` (replay_buffer.py), same index arithmetic,
    same clamp invariant (stale/padded bytes can only occupy positions the
    loss masks out; see the INVARIANT note there).

    ``ints`` is (B, 6) int32: [block_idx, t0, seq_idx, burn_in, learning,
    forward] computed host-side under the buffer lock.
    """
    L, T = cfg.learning_steps, cfg.seq_len
    block_idx, t0 = ints[:, 0], ints[:, 1]
    seq_idx = ints[:, 2]

    time_idx = jnp.minimum(t0[:, None] + jnp.arange(T),
                           cfg.max_block_steps - 1)          # (B, T)
    bcol = block_idx[:, None]
    widx = jnp.minimum(seq_idx[:, None] * L + jnp.arange(L),
                       cfg.block_length - 1)                 # (B, L)
    return dict(
        # flat frame rows in the ring (see _slot_shapes); the network's
        # frame shape is restored on the gathered batch only
        obs=arrays["obs"][bcol, time_idx].reshape(
            *time_idx.shape, *cfg.stored_obs_shape),
        last_action=arrays["last_action"][bcol, time_idx].astype(jnp.float32),
        last_reward=arrays["last_reward"][bcol, time_idx],
        hidden=arrays["hidden"][block_idx, seq_idx],
        action=arrays["action"][bcol, widx].astype(jnp.int32),
        n_step_reward=arrays["n_step_reward"][bcol, widx],
        n_step_gamma=arrays["n_step_gamma"][bcol, widx],
        burn_in=ints[:, 3],
        learning=ints[:, 4],
        forward=ints[:, 5],
        is_weights=is_weights,
    )


def resolve_layout(cfg: Config, mesh, need_bytes: int,
                   cap_bytes: Optional[int]) -> str:
    """Resolve ``cfg.device_ring_layout`` to a concrete mesh layout.

    ``"auto"`` shards the ring over dp exactly when the full ring would
    not fit one device's HBM budget (80%, leaving headroom for params,
    activations and staged slots) AND the shapes allow it (num_blocks and
    batch_size divisible by dp).  Explicit ``"dp"`` raises when the
    shapes or mesh make it impossible — silent fallback would defeat the
    reason the user asked for sharding (review: a knob that validates but
    does nothing).
    """
    requested = cfg.device_ring_layout
    has_dp = (mesh is not None and "dp" in mesh.axis_names
              and mesh.shape["dp"] > 1)
    if not has_dp:
        if requested == "dp":
            raise ValueError(
                "device_ring_layout='dp' needs a mesh with a dp axis > 1")
        return "replicated"
    dp = mesh.shape["dp"]
    can_dp = (cfg.num_blocks % dp == 0) and (cfg.batch_size % dp == 0)
    if requested == "dp":
        if not can_dp:
            raise ValueError(
                f"device_ring_layout='dp' needs num_blocks "
                f"({cfg.num_blocks}) and batch_size ({cfg.batch_size}) "
                f"divisible by dp={dp}")
        return "dp"
    if requested == "replicated":
        return "replicated"
    # "auto": replicate if it fits, shard if it must and can
    if can_dp and cap_bytes is not None and need_bytes > 0.8 * cap_bytes:
        return "dp"
    return "replicated"


def _ring_write_per_fn(K: int):
    """The PER write for a ring of ``K`` sequences a block, under the name
    the device's timeline shows it by (``jit_ring_write_per``)."""

    def ring_write_per(prios: jnp.ndarray, seq_meta: jnp.ndarray,
                       first_burn: jnp.ndarray, prios_slot: jnp.ndarray,
                       meta_slot: jnp.ndarray, first_val: jnp.ndarray,
                       slot: jnp.ndarray):
        """Donated in-place write of one block's PER leaves + sampling
        metadata (in-graph-PER mode, see :class:`DeviceRing`)."""
        prios = jax.lax.dynamic_update_slice(prios, prios_slot, (slot * K,))
        seq_meta = jax.lax.dynamic_update_index_in_dim(seq_meta, meta_slot,
                                                       slot, 0)
        first_burn = jax.lax.dynamic_update_index_in_dim(
            first_burn, first_val, slot, 0)
        return prios, seq_meta, first_burn

    return ring_write_per


class DeviceRing:
    """Owns the device-resident ring arrays and their write path.

    ``placement`` may be a Device (single-chip) or a Sharding; use
    ``table=..., layout=...`` (a :class:`~r2d2_tpu.parallel.sharding.
    ShardingTable`) instead to derive it — the ring's slot-axis layout is
    a sharding-table decision (``ring.*`` / ``per.*`` entries), not a
    local heuristic.  ``layout="dp"`` additionally sets ``num_groups`` —
    the replay buffer then walks ring slots round-robin across the dp
    groups' slot ranges and samples each group's batch rows from its own
    slots.
    """

    def __init__(self, cfg: Config, action_dim: int,
                 placement: Optional[Any] = None,
                 table: Optional[Any] = None, layout: str = "replicated"):
        self.cfg = cfg
        self.action_dim = action_dim
        self.layout = layout
        self.num_groups = 1
        self.table = table
        self._slot_placement = placement  # incoming slots: device or repl.
        self._write_fn = _write_slot
        if table is not None:
            if layout == "dp":
                dp = table.mesh.shape["dp"]
                if cfg.num_blocks % dp:
                    raise ValueError(
                        f"device_ring_layout='dp' needs num_blocks "
                        f"({cfg.num_blocks}) divisible by dp={dp}")
                self.num_groups = dp
            sharding = table.ring_shardings(layout)
            placement = sharding["obs"]
            self._slot_placement = table.replicated()
            # pin the write's output layout: GSPMD would usually preserve
            # the donated input sharding, but with a dp-sharded slot axis
            # the partitioner must not be left free to re-lay-out the ring
            self._write_fn = jax.jit(
                _write_slot_fn, donate_argnums=(0,),
                out_shardings={k: sharding[k] for k in _DATA_KEYS})
        self._placement = placement
        NB = cfg.num_blocks
        self.blocks_per_group = NB // self.num_groups
        self._slot_shapes = _slot_shapes(cfg, action_dim)
        self.arrays = {
            k: self._put(np.zeros((NB, *shape), dtype))
            for k, (shape, dtype) in self._slot_shapes.items()}

        # --- in-graph PER state (cfg.in_graph_per) ---------------------
        # Leaf priorities (td**alpha; 0 = never-sampleable) plus the
        # per-sequence window metadata the in-graph sampler needs to
        # build index bundles without the host (learner/step.py
        # _in_graph_sample).  Replicated under a mesh; dp layout shards
        # the leaf axis with the ring slabs (the table's per.* entries).
        # The priorities handle is READ-WRITE from the learner's super
        # step (donated carry) AND written by actor block commits —
        # both sides mutate it only under the module's coordinating
        # lock, via take_prios()/put_prios() and commit_per().
        self._per_write = None
        if getattr(cfg, "in_graph_per", False):
            K = cfg.seqs_per_block
            if self.num_groups > 1:
                # dp layout: the PER leaves shard with the ring slabs —
                # the global stratified sampler reads them through GSPMD
                # (parallel/sharding.pjit_in_graph_per_super_step)
                psh = table.per_shardings("dp")
                self._per_prios = jax.device_put(
                    np.zeros((NB * K,), np.float32), psh["prios"])
                self._per_seq_meta = jax.device_put(
                    np.zeros((NB, K, 3), np.int32), psh["seq_meta"])
                self._per_first = jax.device_put(
                    np.zeros((NB,), np.int32), psh["first"])
                self._per_write = jax.jit(
                    _ring_write_per_fn(K), donate_argnums=(0, 1, 2),
                    out_shardings=(psh["prios"], psh["seq_meta"],
                                   psh["first"]))
            else:
                self._per_prios = self._put_slot(
                    np.zeros((NB * K,), np.float32))
                self._per_seq_meta = self._put_slot(
                    np.zeros((NB, K, 3), np.int32))
                self._per_first = self._put_slot(np.zeros((NB,), np.int32))
                self._per_write = jax.jit(
                    _ring_write_per_fn(K), donate_argnums=(0, 1, 2))

    def _put(self, x):
        return (jax.device_put(x, self._placement)
                if self._placement is not None else jax.device_put(x))

    def _put_slot(self, x):
        return (jax.device_put(x, self._slot_placement)
                if self._slot_placement is not None else jax.device_put(x))

    def nbytes(self) -> int:
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in self.arrays.values())

    def stage(self, block: Block) -> Dict[str, jnp.ndarray]:
        """Host-side half of a ring write: zero-pad the block to the fixed
        slot shape and start its H2D transfers.  Needs NO lock — staging
        touches no ring state, so callers should do it *outside* the
        coordinating lock (the transfers are the expensive part of a
        write; holding the lock across them would stall a concurrent
        sample+dispatch for the full H2D latency).

        Short blocks are zero-padded; the padding occupies exactly the
        positions the host ring would leave stale, which the sampling
        clamp invariant already guarantees are loss-masked.
        """
        slot = {}
        for k, (shape, dtype) in self._slot_shapes.items():
            arr = np.zeros(shape, dtype)
            src = getattr(block, k)
            if k == "hidden":
                arr[:block.num_sequences] = src
            elif k == "obs":     # flat frame rows (see _slot_shapes)
                arr[:src.shape[0]] = src.reshape(src.shape[0], -1)
            else:
                arr[:src.shape[0]] = src
            slot[k] = self._put_slot(arr)
        return slot

    def commit(self, slot: Dict[str, jnp.ndarray], ptr: int) -> None:
        """Device-side half of a ring write: the donated in-place update
        into (physical) slot ``ptr``.  Caller holds the coordinating lock
        (see the module contract) — this is just one async dispatch, so
        the lock hold is microseconds."""
        # numpy scalars, here and in commit_per: jnp.asarray(int, dtype)
        # runs a convert program of its own on the device for every
        # scalar (utils/trace.put_scalar)
        self.arrays = self._write_fn(self.arrays, slot, np.int32(ptr))

    def write(self, block: Block, ptr: int) -> None:
        """stage + commit in one call (caller holds the coordinating
        lock — see the module contract)."""
        self.commit(self.stage(block), ptr)

    def snapshot(self) -> Dict[str, jnp.ndarray]:
        """Current ring handles, safe to pass to a train-step dispatch
        (caller holds the coordinating lock — see the module contract)."""
        return self.arrays

    # ------------------------------------------------- in-graph PER state
    def commit_per(self, slot: int, prios_alpha: np.ndarray,
                   meta: np.ndarray, first_burn: int) -> None:
        """Write one block's PER leaves (td**alpha, (K,) f32, zero-padded
        past num_sequences = unsampleable) + sampling metadata ((K, 3)
        i32 [burn, learn, fwd]; first_burn scalar).  Caller holds the
        coordinating lock."""
        self._per_prios, self._per_seq_meta, self._per_first = (
            self._per_write(
                self._per_prios, self._per_seq_meta, self._per_first,
                np.asarray(prios_alpha, np.float32),
                np.asarray(meta, np.int32),
                np.int32(first_burn), np.int32(slot)))

    def take_prios(self) -> jnp.ndarray:
        """The current priorities handle, for a super-step dispatch that
        DONATES it (the dispatch's returned handle must be stored back
        with :meth:`put_prios` before the lock is released)."""
        return self._per_prios

    def put_prios(self, handle: jnp.ndarray) -> None:
        self._per_prios = handle

    def per_meta(self) -> Dict[str, jnp.ndarray]:
        """Read-only sampling metadata handles for a dispatch."""
        return dict(seq_meta=self._per_seq_meta, first=self._per_first)

    def put_per_meta(self, seq_meta: jnp.ndarray,
                     first: jnp.ndarray) -> None:
        """Store back PER sampling-metadata handles returned by a dispatch
        that DONATED them.  Host-side commits (:meth:`commit_per`) write
        these in place, but the anakin fused loop (learner/anakin.py)
        writes them in-graph instead — its dispatches consume the current
        handles and this stores the returned generation, the same
        discipline as :meth:`take_prios`/:meth:`put_prios`."""
        self._per_seq_meta = seq_meta
        self._per_first = first
