"""The recurrent state's shape and dtype: the model owns them, and every
buffer that carries states — the actors, the block cutter, the ring, the
fused loop, eval, the session pool, the act slabs, the net frames — derives
its own from :func:`state_spec`.  Importing it does not import jax, so
that host-only modules (replay/block.py, the wire formats) can; the stream
functions at the end run on device arrays and import it when called.

One state is one array whose zeros are the initial state:

- ``core="lstm"``: ``(2, layers, H)`` float32, axis 0 = (h, c);
- ``core="xing4"`` (models/xing4.py): the latent cache ``(layers, W,
  kv_rank + rope_dim)`` in the compute dtype — for each block the W most
  recent steps' (normed key/value latent, unrotated rotary key);
- ``core="olmo_hybrid"`` (models/olmo_hybrid.py): a flat vector in the
  compute dtype folded into rows of ``STATE_LANES`` = 128 —
  :func:`olmo_hybrid_layout`'s parts one after another: every linear
  layer's delta-rule matrix and convolution tail, zero-padded to whole
  tiles of ``STATE_TILE_ROWS`` = 16 rows, then the W most recent steps'
  keys and values of the softmax layers, padded likewise (at the
  published widths 4 rows of padding in 2,352).  Folded into whole tiles,
  because the chip's compiler lays a ring of states out by what pads
  least: flat vectors ``(blocks, 10, 300544)`` it pads to 16 sequences a
  block, rows that fill no whole tile ``(blocks, 10, 2348, 128)`` it
  lays out blocks-minor — and either way copies the ring whole at every
  dispatch's start and end (1.2-1.7 GB; tests/test_tpu_compile.py
  compiles the step for a described v5e).

A per-step **stream** from which the state at a step can be cut (the fused
loop keeps one a lane, learner/anakin.py) has two parts,
:func:`stream_spec`: **rows**, the part of a state that is the W newest of
what every step adds, kept as one entry a step with W-1 entries of history;
and **snapshots**, the part that is no window of rows and is kept whole,
only at the steps a stored sequence can start.  One lookup per core
(``_KINDS``) says which part holds what.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import ml_dtypes
import numpy as np

from r2d2_tpu.config import Config

_DTYPES = {"float32": np.dtype(np.float32),
           "bfloat16": np.dtype(ml_dtypes.bfloat16)}

# core="olmo_hybrid": the two constants of the source that size its state,
# under config.json's keys (models/olmo_hybrid.py has the others)
LAYER_TYPES_PERIOD = ("linear_attention", "linear_attention",
                      "linear_attention", "full_attention")
LINEAR_CONV_KERNEL_DIM = 4
STATE_LANES = 128       # the minor axis of a folded state: a TPU tile's
STATE_TILE_ROWS = 16    # ... and a bfloat16 tile's rows (float32: 8)


class StreamSpec(NamedTuple):
    history: int                        # entries kept before the first step
    entry: Tuple[int, ...]              # what one step adds to the rows
    dtype: np.dtype
    snapshot: Optional[Tuple[int, ...]]  # the part kept whole, if any


def olmo_hybrid_layout(cfg: Config) -> Dict[str, Tuple[int, ...]]:
    """The parts of one ``olmo_hybrid`` state, in the order they lie in its
    flat vector: ``delta`` (linear layers, heads, d_k, d_v) the delta-rule
    matrices, ``conv`` (linear layers, kernel - 1, channels) the last
    pre-convolution rows of q, k and v, ``rows`` (W, softmax layers,
    heads x 2 x head size) the stored keys and values, step-major so that a
    window of W stream entries IS this part."""
    period = len(LAYER_TYPES_PERIOD)
    full = cfg.core_layers // period
    linear = cfg.core_layers - full
    h, dk, dv = (cfg.core_heads_held, cfg.core_linear_key_dim,
                 cfg.core_linear_value_dim)
    return dict(
        delta=(linear, h, dk, dv),
        conv=(linear, LINEAR_CONV_KERNEL_DIM - 1, h * (2 * dk + dv)),
        rows=(cfg.core_context, full, 2 * h * cfg.core_head_dim))


def _size(shape) -> int:
    return int(np.prod(shape))


class _Lstm:
    """An entry is the whole state and the stream needs no history."""

    @staticmethod
    def state(cfg):
        return (2, cfg.lstm_layers, cfg.hidden_dim), _DTYPES["float32"]

    @classmethod
    def stream(cls, cfg):
        shape, dtype = cls.state(cfg)
        return StreamSpec(0, shape, dtype, None)

    @staticmethod
    def entry(cfg, state):
        return state

    @staticmethod
    def states(cfg, stream, idx, snapshots):
        return stream[idx]


class _Xing4:
    """A state is the W newest rows of every block's cache, so an entry is
    the one row a step adds ``(layers, latent)`` and the state at step p is
    entries p-W+1..p: the stream keeps W-1 entries of history before its
    first step (zeros at an episode's start) — 441 whole states a lane
    would be 10 GB at the published widths."""

    @staticmethod
    def state(cfg):
        return ((cfg.core_layers, cfg.core_context,
                 cfg.core_kv_rank + cfg.core_rope_dim),
                _DTYPES[cfg.compute_dtype])

    @classmethod
    def stream(cls, cfg):
        shape, dtype = cls.state(cfg)
        return StreamSpec(cfg.core_context - 1, (shape[0], shape[2]), dtype,
                          None)

    @staticmethod
    def entry(cfg, state):
        return state[:, :, -1, :]       # the newest row of every cache

    @staticmethod
    def states(cfg, stream, idx, snapshots):
        import jax

        W = cfg.core_context            # entries idx-W+1 .. idx, at
        rows = jax.vmap(                # buffer rows idx .. idx+W-1
            lambda i: jax.lax.dynamic_slice_in_dim(stream, i, W, 0))(idx)
        return rows.swapaxes(1, 2)      # (K, layers, W, latent)


def folded_rows(size: int) -> int:
    """Rows of 128 that a folded part of ``size`` values takes: whole
    tiles."""
    tile = STATE_LANES * STATE_TILE_ROWS
    return -(-size // tile) * STATE_TILE_ROWS


class _OlmoHybrid:
    """The stored keys and values are rows as ``xing4``'s are.  A
    delta-rule matrix is no window of rows: matrices and convolution tails
    (470 KB a state at the published widths, 207 MB a lane if kept at each
    of its 441 steps) are the snapshot part, the folded state's first
    rows."""

    @staticmethod
    def sizes(cfg):
        """Values in (the snapshot part, the rows part)."""
        parts = olmo_hybrid_layout(cfg)
        return (_size(parts["delta"]) + _size(parts["conv"]),
                _size(parts["rows"]))

    @classmethod
    def state(cls, cfg):
        return ((sum(folded_rows(n) for n in cls.sizes(cfg)), STATE_LANES),
                _DTYPES[cfg.compute_dtype])

    @classmethod
    def stream(cls, cfg):
        return StreamSpec(
            cfg.core_context - 1,
            (_size(olmo_hybrid_layout(cfg)["rows"][1:]),),
            cls.state(cfg)[1], (folded_rows(cls.sizes(cfg)[0]), STATE_LANES))

    @classmethod
    def entry(cls, cfg, state):
        snap, rows = cls.sizes(cfg)
        entry = cls.stream(cfg).entry
        flat = state[:, folded_rows(snap):].reshape(state.shape[0], -1)
        return flat[:, rows - _size(entry):rows].reshape((-1,) + entry)

    @classmethod
    def snapshot(cls, cfg, state):
        return state[:, :folded_rows(cls.sizes(cfg)[0])]

    @classmethod
    def states(cls, cfg, stream, idx, snapshots):
        import jax
        import jax.numpy as jnp

        W, rows = cfg.core_context, cls.sizes(cfg)[1]
        windows = jax.vmap(
            lambda i: jax.lax.dynamic_slice_in_dim(stream, i, W, 0))(idx)
        folded = jnp.pad(
            windows.reshape(idx.shape[0], rows),
            ((0, 0), (0, folded_rows(rows) * STATE_LANES - rows)))
        return jnp.concatenate(
            [snapshots, folded.reshape(idx.shape[0], -1, STATE_LANES)],
            axis=1)


_KINDS = {"lstm": _Lstm, "xing4": _Xing4, "olmo_hybrid": _OlmoHybrid}


def state_spec(cfg: Config) -> Tuple[Tuple[int, ...], np.dtype]:
    """(shape, numpy dtype) of ONE recurrent state."""
    return _KINDS[cfg.core].state(cfg)


def zero_state(cfg: Config, *lead: int) -> np.ndarray:
    """Host zeros of ``lead`` states (``zero_state(cfg)`` is one)."""
    shape, dtype = state_spec(cfg)
    return np.zeros(tuple(lead) + shape, dtype)


def stream_spec(cfg: Config) -> StreamSpec:
    """(history, entry shape, dtype, snapshot shape or None) of a per-step
    stream from which the state at a step can be cut: the rows keep
    ``history`` entries before the stream's first step; the snapshot part,
    where a core has one, is kept at chosen steps only."""
    return _KINDS[cfg.core].stream(cfg)


def stream_entry(cfg: Config, state):
    """What the states (N, ...) after a step add to their lanes' rows."""
    return _KINDS[cfg.core].entry(cfg, state)


def stream_snapshot(cfg: Config, state):
    """The snapshot part (N, ...) of the states (N, ...)."""
    return _KINDS[cfg.core].snapshot(cfg, state)


def stream_states(cfg: Config, stream, idx, snapshots=None):
    """The states at steps ``idx`` (K,) of one lane's row stream;
    ``snapshots`` (K, ...) are the snapshot parts taken at those steps."""
    return _KINDS[cfg.core].states(cfg, stream, idx, snapshots)


def lanes_like(mask, like, drop: int = 0):
    """``mask`` (N,) shaped to select whole lanes of ``like`` (N, ...), or
    of ``like`` less ``drop`` of its axes."""
    import jax

    return jax.lax.expand_dims(mask, range(mask.ndim, like.ndim - drop))


def reset_stream(cfg: Config, stream, reset):
    """The lanes' row streams (N, steps, ...) with the first step of the
    ``reset`` (N,) lanes zeroed, and the history before it."""
    import jax.numpy as jnp

    hist = stream_spec(cfg).history
    mask = lanes_like(reset, stream, drop=0 if hist else 1)
    zero = jnp.zeros((), stream.dtype)
    if hist:
        return stream.at[:, :hist + 1].set(
            jnp.where(mask, zero, stream[:, :hist + 1]))
    return stream.at[:, 0].set(jnp.where(mask, zero, stream[:, 0]))
