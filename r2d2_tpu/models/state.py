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
  recent steps' (normed key/value latent, unrotated rotary key).
"""
from __future__ import annotations

from typing import Tuple

import ml_dtypes
import numpy as np

from r2d2_tpu.config import Config

_DTYPES = {"float32": np.dtype(np.float32),
           "bfloat16": np.dtype(ml_dtypes.bfloat16)}


def state_spec(cfg: Config) -> Tuple[Tuple[int, ...], np.dtype]:
    """(shape, numpy dtype) of ONE recurrent state."""
    if cfg.core == "xing4":
        return ((cfg.core_layers, cfg.core_context,
                 cfg.core_kv_rank + cfg.core_rope_dim),
                _DTYPES[cfg.compute_dtype])
    return (2, cfg.lstm_layers, cfg.hidden_dim), _DTYPES["float32"]


def zero_state(cfg: Config, *lead: int) -> np.ndarray:
    """Host zeros of ``lead`` states (``zero_state(cfg)`` is one)."""
    shape, dtype = state_spec(cfg)
    return np.zeros(tuple(lead) + shape, dtype)


def stream_spec(cfg: Config) -> Tuple[int, Tuple[int, ...], np.dtype]:
    """(history, entry shape, dtype) of a per-step stream from which the
    state at any of its steps can be cut (the fused loop keeps one a lane,
    learner/anakin.py).  For the LSTM an entry is the whole state and the
    stream needs no history.  For ``xing4`` a state is the W newest rows
    of every block's cache, so an entry is the one row a step adds
    ``(layers, latent)`` and the state at step p is entries p-W+1..p: the
    stream keeps W-1 entries of history before its first step (zeros at an
    episode's start) — 441 whole states a lane would be 10 GB at the
    published widths."""
    shape, dtype = state_spec(cfg)
    if cfg.core == "xing4":
        return cfg.core_context - 1, (shape[0], shape[2]), dtype
    return 0, shape, dtype


def stream_entry(cfg: Config, state):
    """What the states (N, ...) after a step add to their lanes' streams."""
    if cfg.core == "xing4":
        return state[:, :, -1, :]       # the newest row of every cache
    return state


def stream_states(cfg: Config, stream, idx):
    """The states at steps ``idx`` (K,) of one lane's stream."""
    if cfg.core == "xing4":
        import jax

        W = cfg.core_context            # entries idx-W+1 .. idx, at
        rows = jax.vmap(                # buffer rows idx .. idx+W-1
            lambda i: jax.lax.dynamic_slice_in_dim(stream, i, W, 0))(idx)
        return rows.swapaxes(1, 2)      # (K, layers, W, latent)
    return stream[idx]


def reset_stream(cfg: Config, stream, reset):
    """The lanes' streams (N, steps, ...) with the first step of the
    ``reset`` (N,) lanes zeroed, and the history before it."""
    import jax.numpy as jnp

    hist = stream_spec(cfg)[0]
    mask = reset[:, None, None, None]
    zero = jnp.zeros((), stream.dtype)
    if hist:
        return stream.at[:, :hist + 1].set(
            jnp.where(mask, zero, stream[:, :hist + 1]))
    return stream.at[:, 0].set(jnp.where(mask, zero, stream[:, 0]))
