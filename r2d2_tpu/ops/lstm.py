"""Pallas fused LSTM **inference** unroll for TPU.

Why: under ``jax.lax.scan`` each of the T unroll steps is its own XLA loop
iteration that re-reads the (H, 4H) recurrent kernel from HBM and pays
per-step kernel overhead.  This kernel runs the whole unroll as one Pallas
program: a sequential grid over T with the recurrent weights, h, and c held
in VMEM across steps, so HBM traffic per step is just the (B, 4H)
input-projection slice in and the (B, H) hidden slice out.  It is the
TPU-native stand-in for the implicit cuDNN fused LSTM the reference gets
for free on the acting path (reference model.py:51,65-79).

**Inference-only — the backward kernel was retired in round 5.**  A
round-4 on-chip observation (v5e, B=64 T=85 H=512 bf16; builder-logged,
its record is gone, never reproduced) put the fused forward+backward at
0.96x the scan recurrence and the forward-only path at 1.07x, so only the
inference half stayed.  Whether it earns its keep at the T=1 shape
production actually runs is not measured (ROADMAP Design 4);
chip_smoke.py's ``kernel`` leg only proves Mosaic compiles it and it
matches scan.  Training always runs the scan (learner/step.py builds its
loss networks with ``lstm_impl="scan"``); differentiating through this
kernel is unsupported and raises at trace time.

Numerics: matmul operands are cast to ``compute_dtype`` (bfloat16 in the
flagship config) with float32 accumulation — one rounding *less* than the
scan path's bf16-output matmul, so results match the scan reference to
bf16 tolerance (exactly, in float32 mode).  See tests/test_lstm_pallas.py.
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM


def _sigmoid(x):
    return jax.nn.sigmoid(x)


def _fwd_infer_kernel(xp_ref, wh_ref, h0_ref, c0_ref,
                      hs_ref, cT_ref, h_scr, c_scr, *, compute_dtype):
    """Residual-free forward: per step ``gates = xp[t] + h @ wh`` (MXU,
    float32 accumulation), gate nonlinearities on the VPU (order i,f,g,o),
    h/c carried in VMEM scratch across the sequential grid."""
    t = pl.program_id(0)
    T = pl.num_programs(0)

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:].astype(jnp.float32)
        c_scr[:] = c0_ref[:].astype(jnp.float32)

    h = h_scr[:]
    c = c_scr[:]
    H = h.shape[-1]
    gates = xp_ref[0] + jnp.dot(h.astype(compute_dtype), wh_ref[:],
                                preferred_element_type=jnp.float32)
    si = _sigmoid(gates[:, 0 * H:1 * H])
    sf = _sigmoid(gates[:, 1 * H:2 * H])
    tg = jnp.tanh(gates[:, 2 * H:3 * H])
    so = _sigmoid(gates[:, 3 * H:4 * H])
    c_new = sf * c + si * tg
    h_new = so * jnp.tanh(c_new)

    hs_ref[0] = h_new
    h_scr[:] = h_new
    c_scr[:] = c_new

    @pl.when(t == T - 1)
    def _():
        cT_ref[:] = c_new


@functools.lru_cache(maxsize=None)
def make_lstm_infer(compute_dtype: Any, interpret: bool):
    """Build the fused inference unroll for one (dtype, interpret) combo.

    Returned fn: ``(xp, wh, h0, c0) -> (hs, h_T, c_T)`` with
    - ``xp``: (T, B, 4H) float32 — hoisted input projection (x@wi + b),
    - ``wh``: (H, 4H) in ``compute_dtype``,
    - ``h0``/``c0``: (B, H) float32,
    - ``hs``: (T, B, H) float32 hidden states, ``h_T``/``c_T`` finals.

    NOT differentiable (the backward kernel was retired; see module
    docstring) — use the scan recurrence for any grad path.
    """
    cd = compute_dtype

    def _scratch(shape):
        return pltpu.VMEM(shape, jnp.float32)

    def _infer_call(xp, wh, h0, c0):
        T, B, H4 = xp.shape
        H = H4 // 4
        f32 = jnp.float32
        kernel = functools.partial(_fwd_infer_kernel, compute_dtype=cd)
        mem = {} if interpret else dict(memory_space=_VMEM)
        hs, cT = pl.pallas_call(
            kernel,
            grid=(T,),
            in_specs=[
                pl.BlockSpec((1, B, H4), lambda t: (t, 0, 0), **mem),
                pl.BlockSpec((H, H4), lambda t: (0, 0), **mem),
                pl.BlockSpec((B, H), lambda t: (0, 0), **mem),
                pl.BlockSpec((B, H), lambda t: (0, 0), **mem),
            ],
            out_specs=[
                pl.BlockSpec((1, B, H), lambda t: (t, 0, 0), **mem),
                pl.BlockSpec((B, H), lambda t: (0, 0), **mem),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((T, B, H), f32),
                jax.ShapeDtypeStruct((B, H), f32),
            ],
            scratch_shapes=[
                _scratch((B, H)),
                _scratch((B, H)),
            ],
            interpret=interpret,
        )(xp, wh, h0.astype(f32), c0.astype(f32))
        return hs, hs[-1], cT

    return _infer_call


def lstm_unroll_pallas(xp_tm: jnp.ndarray, wh: jnp.ndarray, h0: jnp.ndarray,
                       c0: jnp.ndarray, *, compute_dtype: Any = jnp.bfloat16,
                       interpret: bool = False
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused inference unroll: see :func:`make_lstm_infer` for shapes."""
    fn = make_lstm_infer(compute_dtype, interpret)
    return fn(xp_tm, wh.astype(compute_dtype), h0, c0)
