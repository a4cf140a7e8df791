"""Device-resident replay ring: replay data lives in HBM, not host RAM.

The reference's data plane moves every training batch across the host↔device
boundary (worker.py:330-342 `.to(device)` per step).  At flagship shapes that
is ~40 MB per batch — the dominant system cost on any real interconnect.
The TPU-first redesign inverts the flow:

- Each experience block crosses H2D **once**, when the actor produces it
  (~3 MB, at block-production rate — orders of magnitude less traffic than
  per-batch staging).
- The ring arrays (same fields as the host ring, replay_buffer.py) live on
  the device; batch assembly is an in-graph gather inside the jitted train
  step: one contiguous time window a sample and field
  (:func:`gather_batch`), not one fetch a row.  On a v5e the 64 frame
  windows of a flagship batch (38.4 MB) take 0.12 ms against the 0.094 ms
  of reading and writing them once at 819 GB/s, the frames transposed
  and unpacked for the convolution 0.31 ms, and the whole gather 0.44 ms
  (the row gather it replaced: 0.50 ms for the frames, 0.69 ms in all;
  PERF.md, PR 26).
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
from r2d2_tpu.models.state import state_spec
from r2d2_tpu.replay.block import Block

# data arrays mirrored on device; the count arrays (burn_in/learning/
# forward, first_burn_in) stay host-only — they are needed for *index
# computation*, which is host work.  Single-sourced from the sharding
# table's RING_DATA_KEYS so the ring's slabs and the table's `ring.*`
# sharding entries can never drift.
from r2d2_tpu.parallel.sharding import RING_DATA_KEYS as _DATA_KEYS


# rows of the frame ring are kept a multiple of this (see _slot_shapes)
_OBS_ROW_TILE = 32
# fields with a time axis of max_block_steps stored rows; gather_batch reads
# them as windows of seq_len rows
TIME_KEYS = ("obs", "last_action", "last_reward")
# bytes of a frame that share four ring words (see pack_frames)
_FRAME_GROUP = 16


def window_tail(cfg: Config) -> int:
    """Rows by which the latest window runs past a block's last stored row
    (``max_block_steps - 1``).  The samplers give ``t0 = first_burn_in +
    seq_idx * learning_steps - burn_in <= block_length - learning_steps``
    (replay_buffer.sample_meta, learner/step._in_graph_sample_raw), so the
    longest overrun is ``forward_steps - 1`` rows: the last sequence of a
    block whose episode went on."""
    return max(0, cfg.block_length - cfg.learning_steps + cfg.seq_len
               - cfg.max_block_steps)


def frame_words(n_bytes: int) -> int:
    """Ring words a frame of ``n_bytes`` bytes takes."""
    return -(-n_bytes // _FRAME_GROUP) * (_FRAME_GROUP // 4)


def pack_frames(frames: jnp.ndarray) -> jnp.ndarray:
    """``u8[..., n_bytes]`` -> ``u32[..., frame_words(n_bytes)]``, the frame
    ring's row format.  Of every 16 bytes, word ``j`` holds bytes ``j``,
    ``j + 4``, ``j + 8``, ``j + 12``, lowest first, so that
    :func:`unpack_frames` gets four byte planes of four consecutive bytes
    each from shifts alone and rebuilds the frame by laying the planes
    side by side — the one order of those tried in which the compiler
    unpacks with the frames already on the minor axis, where the
    convolution wants them (PERF.md, PR 26)."""
    *lead, n = frames.shape
    padded = frame_words(n) * 4
    if padded != n:
        frames = jnp.pad(frames, [(0, 0)] * len(lead) + [(0, padded - n)])
    p = frames.reshape(*lead, padded // _FRAME_GROUP, 4, 4).astype(jnp.uint32)
    words = (p[..., 0, :] | (p[..., 1, :] << 8) | (p[..., 2, :] << 16)
             | (p[..., 3, :] << 24))
    return words.reshape(*lead, padded // 4)


def unpack_frames(words: jnp.ndarray, n_bytes: int) -> jnp.ndarray:
    """Inverse of :func:`pack_frames`: ``u32[..., words]`` ->
    ``u8[..., n_bytes]``."""
    *lead, n = words.shape
    w = words.reshape(*lead, n // 4, 4)
    planes = [((w >> (8 * k)) & jnp.uint32(0xFF)).astype(jnp.uint8)
              for k in range(4)]
    frames = jnp.concatenate(planes, axis=-1).reshape(*lead, n * 4)
    return frames if n * 4 == n_bytes else frames[..., :n_bytes]


def _slot_shapes(cfg: Config, action_dim: int) -> Dict[str, Any]:
    """Per-slot shapes of a STAGED block — what :meth:`DeviceRing.stage`
    puts on the device and the write program takes.  Same fields as the
    host ring, except for how a block's frames and time rows are laid
    out:

    - a frame is one FLAT row (PERF.md Findings, PR 21).  XLA answers a
      gather from a ``(NB, MS, 21, 21, 16)`` ring that feeds a conv by
      re-laying-out the WHOLE ring for the conv (a padded copy 7x the
      ring: 21.7 GB for a 3.1 GB ring — the super-step does not compile);
    - the frame-row axis is padded to a multiple of 32 rows (441 -> 448).
      With a ragged row count the compiler lays the ring out block-minor
      to save the padding, and the gather it emits for that layout inside
      the k-step loop reads out of bounds — the core halts with an HBM
      page fault on the first dispatch whose window reaches a block's
      late rows;
    - ``last_action`` and ``last_reward`` carry :func:`window_tail` spare
      rows.  Every spare row of the three time fields holds a COPY of row
      ``max_block_steps - 1`` (``stage`` and the fused loop's cut write
      them so): a window of ``seq_len`` rows may touch the first
      ``window_tail`` of them, only in the positions past the block's
      last stored row, which the host path fills with that same row by
      clamping its index — and those positions lie past every index the
      loss gathers (the INVARIANT note in
      ``ReplayBuffer._gather_rows``).

    In the ring itself (:func:`_ring_shapes`) a frame row is packed into
    32-bit words (:func:`pack_frames`).  A u8 array's tiles pack four
    consecutive ROWS into every word, so a window that starts at an
    arbitrary row has to be re-aligned byte by byte: gathered from a u8
    ring, rows cost 73 ns each and whole windows no less (0.40 and 0.49 ms
    a flagship batch).  Words keep a frame's bytes together and rows
    apart: a window is whole words at a row offset, 0.12 ms a batch
    (my chip runs, PR 26).  :func:`gather_batch` restores
    ``cfg.stored_obs_shape`` bytes."""
    MS, BL = cfg.max_block_steps, cfg.block_length
    K = cfg.seqs_per_block
    state_shape, state_dtype = state_spec(cfg)
    obs_rows = -(-MS // _OBS_ROW_TILE) * _OBS_ROW_TILE
    rows = MS + window_tail(cfg)
    return dict(
        obs=((obs_rows, int(np.prod(cfg.stored_obs_shape))), np.uint8),
        last_action=((rows, action_dim), np.bool_),
        last_reward=((rows,), np.float32),
        action=((BL,), np.uint8),
        n_step_reward=((BL,), np.float32),
        n_step_gamma=((BL,), np.float32),
        hidden=((K,) + state_shape, state_dtype),
    )


def _ring_shapes(cfg: Config, action_dim: int) -> Dict[str, Any]:
    """Per-slot shapes of the ring's own arrays: a staged slot with its
    frame rows packed into words."""
    shapes = _slot_shapes(cfg, action_dim)
    (rows, n_bytes), _ = shapes["obs"]
    shapes["obs"] = ((rows, frame_words(n_bytes)), np.uint32)
    return shapes


def device_bytes(cfg: Config, action_dim: int) -> int:
    """Logical bytes of the device ring's arrays, spare rows included
    (what the capacity guard budgets; HBM holds them at ~1.03x on the
    v5e)."""
    return cfg.num_blocks * sum(
        int(np.prod(shape)) * np.dtype(dtype).itemsize
        for shape, dtype in _ring_shapes(cfg, action_dim).values())


def ring_slots(blocks: Dict[str, jnp.ndarray],
               arrays: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Blocks of N lanes cut on the device (learner/anakin.py; fields
    (N, ...), frames as flat byte rows, time fields ``max_block_steps``
    rows long) in the format of the ring ``arrays`` they are written
    into: frames packed into words, and every time field made as many
    rows long as its slot, the spare rows copies of the last one (see
    :func:`_slot_shapes`)."""
    out = dict(blocks, obs=pack_frames(blocks["obs"]))
    for k in TIME_KEYS:
        spare = arrays[k].shape[1] - out[k].shape[1]
        if spare:
            out[k] = jnp.concatenate(
                [out[k], jnp.repeat(out[k][:, -1:], spare, axis=1)], axis=1)
    return out


def _write_slot_fn(arrays: Dict[str, jnp.ndarray],
                   slot: Dict[str, jnp.ndarray], ptr: jnp.ndarray):
    """One staged slot into the ring: the frames cross the host's link as
    the bytes they are and are packed into words here, on the device."""
    slot = dict(slot, obs=pack_frames(slot["obs"]))
    return {k: jax.lax.dynamic_update_index_in_dim(arrays[k], slot[k], ptr,
                                                   axis=0)
            for k in arrays}


_write_slot = jax.jit(_write_slot_fn, donate_argnums=(0,))


def _windows(arr: jnp.ndarray, block_idx: jnp.ndarray, start: jnp.ndarray,
             length: int) -> jnp.ndarray:
    """``arr[block_idx[i], start[i]:start[i] + length]`` for every sample:
    (NB, rows, ...) -> (B, length, ...), one slice a sample — a gather
    whose slice is the whole window, so the compiler moves B windows of
    known length and not B * length rows."""
    tail = arr.shape[2:]

    def one(b, s):
        return jax.lax.dynamic_slice(arr, (b, s) + (0,) * len(tail),
                                     (1, length) + tail)[0]

    return jax.vmap(one)(block_idx, start)


def _time_windows(cfg: Config, arr: jnp.ndarray, block_idx: jnp.ndarray,
                  t0: jnp.ndarray) -> jnp.ndarray:
    """(B, seq_len, ...): row ``min(t0 + i, max_block_steps - 1)`` of a
    time field at position ``i``, as the host path's clamp gives it, for
    ``t0 <= block_length - learning_steps`` (:func:`window_tail`; a later
    ``t0`` stays inside the array but reads other rows).  Which of two
    ways follows from the array's row count alone."""
    T, MS = cfg.seq_len, cfg.max_block_steps
    rows, tail = arr.shape[1], arr.shape[2:]
    overrun = window_tail(cfg)
    pos = t0[:, None] + jnp.arange(T)                         # (B, T)

    def per_row(mask):
        return mask.reshape(mask.shape + (1,) * len(tail))

    if rows - MS >= overrun:
        # the longest window ends inside the slot, and what it reads past
        # row MS - 1 are that row's copies (_slot_shapes): no repair.
        # The select never takes its zero for such a t0.  It is here for
        # the compiler: with an elementwise step between the windows and
        # the unpacking it transposes the frames as words and unpacks them
        # frames-minor (0.19 ms a flagship batch); without it, it unpacks
        # first and transposes four byte planes (0.66 ms; PERF.md, PR 26)
        w = _windows(arr, block_idx, t0, T)
        return jnp.where(per_row(pos < rows), w, jnp.zeros((), arr.dtype))
    # no spare rows (a frame ring whose max_block_steps is a multiple of
    # 32): a late window is taken from where it still fits, moved back to
    # its place, and its positions past the block's last row repaired
    # from that row
    start = jnp.minimum(t0, rows - T)
    most = MS + overrun - rows                # the largest t0 - start
    w = _windows(arr, block_idx, start, T)
    w = jnp.pad(w, ((0, 0), (0, most)) + ((0, 0),) * len(tail))
    w = jax.vmap(lambda x, s: jax.lax.dynamic_slice(
        x, (s,) + (0,) * len(tail), (T,) + tail))(w, t0 - start)
    last = arr[block_idx, MS - 1]
    return jnp.where(per_row(pos > MS - 1), last[:, None], w)


@jax.named_scope("ring_gather")
def gather_batch(cfg: Config, arrays: Dict[str, jnp.ndarray],
                 ints: jnp.ndarray, is_weights: jnp.ndarray
                 ) -> Dict[str, jnp.ndarray]:
    """In-graph batch assembly — the device twin of
    ``ReplayBuffer.sample_batch`` (replay_buffer.py): the same bytes in
    the same positions, read as one contiguous window a sample and field
    where the host indexes row by row (stale/padded bytes can only occupy
    positions the loss masks out; see the INVARIANT note there).

    ``ints`` is (B, 6) int32: [block_idx, t0, seq_idx, burn_in, learning,
    forward] computed host-side under the buffer lock or by the in-graph
    sampler.
    """
    L, K = cfg.learning_steps, cfg.seqs_per_block
    block_idx, t0, seq_idx = ints[:, 0], ints[:, 1], ints[:, 2]

    def learning_window(arr):
        # entries [seq_idx * L, (seq_idx + 1) * L) of a block: with
        # seq_idx < K the host's clamp to block_length - 1 never acts, so
        # a sequence's window is one fetch, like its stored hidden state
        return arr.reshape(arr.shape[0], K, L)[block_idx, seq_idx]

    n_bytes = int(np.prod(cfg.stored_obs_shape))
    obs = unpack_frames(_time_windows(cfg, arrays["obs"], block_idx, t0),
                        n_bytes)
    return dict(
        # flat frame rows in the ring (see _slot_shapes); the network's
        # frame shape is restored on the gathered batch only
        obs=obs.reshape(*obs.shape[:2], *cfg.stored_obs_shape),
        last_action=_time_windows(cfg, arrays["last_action"], block_idx,
                                  t0).astype(jnp.float32),
        last_reward=_time_windows(cfg, arrays["last_reward"], block_idx, t0),
        hidden=arrays["hidden"][block_idx, seq_idx],
        action=learning_window(arrays["action"]).astype(jnp.int32),
        n_step_reward=learning_window(arrays["n_step_reward"]),
        n_step_gamma=learning_window(arrays["n_step_gamma"]),
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
            for k, (shape, dtype) in _ring_shapes(cfg, action_dim).items()}

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
        clamp invariant already guarantees are loss-masked.  The spare
        rows of the time fields get copies of the last row a block can
        store (see :func:`_slot_shapes`).
        """
        MS = self.cfg.max_block_steps
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
            if k in TIME_KEYS:
                arr[MS:] = arr[MS - 1]
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
