"""The IMPALA torso's stem: its first convolution (3 x 3, SAME, ONE input
channel) and the max-pool after it (3 x 3, stride 2, SAME), as one pass
that never writes the convolution's output.

Why fused.  At the IMPALA cell's first stage the convolution turns 7,680
frames of 84 x 84 x 1 into 84 x 84 x 16 in bfloat16, 1.73 GB, which the
pool then reads back to keep a quarter of it.  In the step that tensor was
written by the online and the target forward, read by both pools, and its
cotangent of the same size written and read again by the convolution's
weight gradient (PERF.md, Findings).  :func:`forward` computes the
convolution where the pool wants it, in VMEM, a pair of rows at a time,
and writes only the pooled array and (when differentiated) ops/pool.py's
window index.  :func:`backward` routes the pooled cotangent by that index
to the winning positions, as ops/pool.py's backward does, into VMEM, and
contracts it there with the frames: it returns the kernel's and the
bias's gradients, and no ``[N, 84, 84, 16]`` cotangent at all.  The frames
are data: no gradient reaches them.

The matrix unit does the arithmetic.  With one input channel a value is
nine multiply-adds, and on the vector unit those nine (the pixel
broadcast over the channels, each tap's weight over the frames) cost more
than the pass they replace (PERF.md, Findings).  Along a row,
the convolution of :data:`BLOCK` output columns is one product: the
frames on the lanes, the three input rows' :data:`SLAB` columns under them
(and a row of ones for the bias) as the contraction, and a banded matrix of
the nine taps, ``[BLOCK * C, K]``, whose rows are (column, channel) pairs,
the order the pool reads.  The backward's is the same product turned over:
the routed cotangent of a block, ``[BLOCK * C, lanes]``, against the same
slabs, contracted over the frames, summed into a ``[BLOCK * C, K]``
float32 total whose bands are the nine taps' gradients (and its ones
column the bias's).

Arithmetic.  The input, the kernel and the bias are cast to the compute
dtype, as ``nn.Conv`` casts them; the products are exact and summed in
float32; the window's maximum of those sums is rounded once to the compute
dtype.  Rounding is monotone, so that equals ``nn.max_pool`` of the rounded
values; the index is the first maximum of the float32 sums (ops/pool.py's
rule on those values).  The backward rounds each routed cotangent (the
float32 sum of up to four pooled ones) to the compute dtype, as ops/pool.py's
backward hands it to XLA's weight gradient, and sums the products in
float32.

Layout.  The pooled array and the index are ops/pool.py's ``[Ho, Wo, C, N]``
view of the step's ``{0,3,2,1}`` layout, the array ``Conv_1`` reads.  The
frames enter as the ``[H, W, N]`` view of the layout the compiler gives the
torso's input (frames minor): a bitcast, no pass of its own.  A program
holds :data:`pool.LANES` frames and steps down the rows; at its first step
it copies its frames into VMEM inside a zero border (the convolution's
padding), wide enough for whole slabs.

Where it engages (:func:`engages`): a TPU backend (or ``interpret``), one
input channel, even height and width, whole programs of frames.  Anywhere
else :func:`conv_pool` is ``nn.Conv`` then ``ops/pool.max_pool``: acting's
64 frames a step, the CPU, and the torso's later stages, whose 16 and 32
input channels are the convolutions' own contraction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from r2d2_tpu.ops import pool

BLOCK = 16                  # output columns a product
SLAB = BLOCK + 16           # input columns under them, whole bf16 tiles
ONES = 16                   # contraction rows of ones: the first is the bias's
K = 3 * SLAB + ONES         # the products' contraction
VMEM = 64 << 20             # bytes: a program's frames, bordered copy, rows
f32 = jnp.float32


def engages(shape, features: int, *, interpret: bool = False) -> bool:
    """Whether :func:`conv_pool` of ``x: shape`` (``[N, H, W, 1]``) into
    ``features`` channels takes the kernels."""
    n, h, w, c = shape
    return ((interpret or jax.default_backend() == "tpu") and c == 1
            and features % 8 == 0 and h % 2 == 0 and w % 2 == 0
            and n % pool.LANES == 0)


def plain(x, kernel, bias, dtype):
    """``nn.Conv(features, (3, 3), padding="SAME")`` with these parameters,
    then ``ops/pool.max_pool``."""
    conv = nn.Conv(kernel.shape[-1], (3, 3), padding="SAME", dtype=dtype,
                   param_dtype=kernel.dtype, parent=None)
    return pool.max_pool(conv.apply({"params": {"kernel": kernel,
                                                "bias": bias}}, x))


def conv_pool(x, kernel, bias, dtype, *, interpret: bool = False):
    """``max_pool(nn.Conv(...)(x))`` of ``x: [N, H, W, C_in]`` with the
    convolution's ``kernel: [3, 3, C_in, C]`` and ``bias: [C]``, computed in
    ``dtype``.  Where :func:`engages`, one pass of the kernels whose
    gradient reaches ``kernel`` and ``bias`` alone."""
    if not engages(x.shape, kernel.shape[-1], interpret=interpret):
        return plain(x, kernel, bias, dtype)
    # as nn.Conv casts its input; float32 parameters in and out of the
    # rule: the gradients are float32 sums
    return _stem(x.astype(dtype), kernel.astype(f32), bias.astype(f32),
                 interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _stem(x, kernel, bias, interpret):
    y, _ = forward(frames(x), kernel, bias, index=False,
                   interpret=interpret)
    return y


def _stem_fwd(x, kernel, bias, interpret):
    view = frames(x)
    y, index = forward(view, kernel, bias, interpret=interpret)
    return y, (view, index)


def _stem_bwd(interpret, residuals, g):
    dk, db = backward(*residuals, g, interpret=interpret)
    return None, dk, db


_stem.defvjp(_stem_fwd, _stem_bwd)


def frames(x):
    """``x: [N, H, W, 1]`` -> the kernels' ``[H, W, N]`` view."""
    return jnp.transpose(x[..., 0], (1, 2, 0))


def _blocks(width: int) -> int:
    return -(-width // BLOCK)


def _band(kernel, bias, dtype):
    """The taps as the products' left side, ``[BLOCK * C, K]`` in the
    compute dtype: row ``(j, c)`` holds ``kernel[kh, u - j, 0, c]`` at
    column ``kh * SLAB + u`` (slab column ``u`` is the block's input column
    ``u - 1``), and ``bias[c]`` at column ``3 * SLAB``."""
    c = kernel.shape[-1]
    kw = jnp.arange(SLAB)[None, :] - jnp.arange(BLOCK)[:, None]
    taps = kernel[:, jnp.clip(kw, 0, 2), 0, :]          # [3, BLOCK, SLAB, C]
    taps = jnp.where(((kw >= 0) & (kw <= 2))[None, :, :, None], taps, 0.0)
    taps = taps.transpose(1, 3, 0, 2).reshape(BLOCK * c, 3 * SLAB)
    ones = jnp.zeros((BLOCK * c, ONES), f32).at[:, 0].set(
        jnp.tile(bias, BLOCK))
    return jnp.concatenate([taps, ones], axis=1).astype(dtype)


def _fill(x_ref, xp_ref):
    """The program's frames ``x_ref: (H, W, lanes)`` into ``xp_ref: (H + 2,
    >= W + 2, lanes)`` inside a zero border."""
    H, W = x_ref.shape[:2]
    xp_ref[...] = jnp.zeros(xp_ref.shape, xp_ref.dtype)

    def row(r, _):
        xp_ref[r + 1, pl.ds(1, W), :] = x_ref[r]
        return 0

    jax.lax.fori_loop(0, H, row, 0)


def _slabs(xp_ref, top, m):
    """The products' right side for the convolution's row ``top`` (in the
    bordered rows) and block ``m``: ``[K, lanes]``."""
    rows = [xp_ref[top + kh, pl.ds(BLOCK * m, SLAB), :] for kh in range(3)]
    ones = jnp.ones((ONES, xp_ref.shape[-1]), xp_ref.dtype)
    return jnp.concatenate(rows + [ones], axis=0)


def _precision(dtype):
    """float32 sums of float32 products where the compute dtype is float32;
    bfloat16 operands are exact in one pass."""
    return (jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == f32
            else jax.lax.Precision.DEFAULT)


# ----------------------------------------------------------------- forward

def _forward_kernel(t_ref, x_ref, y_ref, *refs, H, W, Wo):
    """Step ``s`` computes the convolution's rows ``2 s`` and ``2 s + 1``
    (none at the last, which has only the pool's padding row), a product
    a block of columns, into ``pre_ref``, and pools them as ops/pool.py's
    forward pools its input's."""
    if len(refs) == 5:
        k_ref, xp_ref, pre_ref, best_ref, arg_ref = refs
    else:
        (xp_ref, pre_ref, best_ref), k_ref, arg_ref = refs, None, None
    s = pl.program_id(1)
    c = pre_ref.shape[2]

    @pl.when(s == 0)
    def _():
        _fill(x_ref, xp_ref)

    @pl.when(2 * s < H)
    def _():
        band = t_ref[...]
        for r in (0, 1):
            for m in range(_blocks(W)):
                # the last block's rows past the width are left out
                cols = min(BLOCK, W - BLOCK * m)
                out = jnp.dot(band[:c * cols], _slabs(xp_ref, 2 * s + r, m),
                              preferred_element_type=f32,
                              precision=_precision(band.dtype))
                for j in range(cols):
                    pre_ref[r, BLOCK * m + j] = out[c * j:c * (j + 1)]

    pool.pool_rows(lambda r, col: pre_ref[r, col], y_ref, k_ref, best_ref,
                   arg_ref, H=H, W=W, Wo=Wo, lr=0, lc=0)


def forward(view, kernel, bias, *, index: bool = True,
            interpret: bool = False):
    """(the pooled ``[N, Ho, Wo, C]`` in the frames' dtype, the window
    index ``int8[Ho, Wo, C, N]`` of ops/pool.py or None) of the frames'
    ``[H, W, N]`` view (:func:`frames`)."""
    H, W, N = view.shape
    C, dtype = kernel.shape[-1], view.dtype
    Ho, Wo = H // 2, W // 2
    nb = min(N, pool.LANES)
    pooled = pl.BlockSpec((1, Wo, C, nb),
                          lambda n, s: (jnp.maximum(s - 1, 0), 0, 0, n))
    outs = [jax.ShapeDtypeStruct((Ho, Wo, C, N), dtype)]
    scratch = [pltpu.VMEM((H + 2, BLOCK * _blocks(W) + 16, nb), dtype),
               pltpu.VMEM((2, W, C, nb), f32), pltpu.VMEM((Wo, C, nb), f32)]
    if index:
        outs.append(jax.ShapeDtypeStruct((Ho, Wo, C, N), jnp.int8))
        scratch.append(pltpu.VMEM((Wo, C, nb), jnp.int32))
    # the pool's padding row ends the last window a step late
    out = pool.call(
        functools.partial(_forward_kernel, H=H, W=W, Wo=Wo),
        (N // nb, Ho + 1), [_band(kernel, bias, dtype), view],
        [pl.BlockSpec((BLOCK * C, K), lambda n, s: (0, 0)),
         pl.BlockSpec((H, W, nb), lambda n, s: (0, 0, n))],
        outs, [pooled] * len(outs), scratch, name="stem_forward",
        interpret=interpret, flops=2 * 9 * C * H * W * N, vmem_limit=VMEM)
    return pool.from_view(out[0]), (out[1] if index else None)


# ---------------------------------------------------------------- backward

def _backward_kernel(x_ref, g_ref, k_ref, dw_ref, xp_ref, gr_ref, gp_ref,
                     kp_ref, *, W, Wo):
    """Step ``s`` reads window row ``s`` (and has row ``s - 1`` in
    ``gp_ref`` / ``kp_ref``) and routes its cotangent, as ops/pool.py's
    backward with no low padding, into ``gr_ref``: the convolution's rows
    ``2 s`` (position row 0, and row ``s - 1``'s position row 2) and
    ``2 s + 1`` (position row 1), window column by window column.  Then a
    product a block of columns adds each row's routed cotangent against the
    frames under it to ``dw_ref``."""
    s = pl.program_id(1)
    c = gr_ref.shape[2]

    @pl.when(s == 0)
    def _():
        _fill(x_ref, xp_ref)
        kp_ref[...] = jnp.full(kp_ref.shape, pool.NONE, jnp.int32)
        dw_ref[...] = jnp.zeros(dw_ref.shape, f32)

    def terms(g, k, row):
        return [jnp.where(k == 3 * row + pc, g, 0.0) for pc in range(3)]

    def step(b, carry):
        even2, odd2 = carry
        g = g_ref[0, b].astype(f32)
        k = k_ref[0, b].astype(jnp.int32)
        gp, kp = gp_ref[b].astype(f32), kp_ref[b]
        even = [a + d for a, d in zip(terms(g, k, 0), terms(gp, kp, 2))]
        odd = terms(g, k, 1)
        # column 2 b also takes window column b - 1's position column 2
        gr_ref[0, 2 * b] = even[0] + even2
        gr_ref[0, 2 * b + 1] = even[1]
        gr_ref[1, 2 * b] = odd[0] + odd2
        gr_ref[1, 2 * b + 1] = odd[1]
        gp_ref[b] = g_ref[0, b]
        kp_ref[b] = k
        return even[2], odd[2]

    zero = jnp.zeros(gp_ref.shape[1:], f32)
    jax.lax.fori_loop(0, Wo, step, (zero, zero))
    dtype = xp_ref.dtype
    total = dw_ref[0]
    for r in (0, 1):
        for m in range(_blocks(W)):
            cols = min(BLOCK, W - BLOCK * m)
            routed = gr_ref[r, pl.ds(BLOCK * m, cols)]
            routed = routed.reshape(cols * c, routed.shape[-1]).astype(dtype)
            part = jax.lax.dot_general(
                routed, _slabs(xp_ref, 2 * s + r, m),
                (((1,), (1,)), ((), ())), preferred_element_type=f32,
                precision=_precision(dtype))
            if cols < BLOCK:
                part = jnp.concatenate([part, jnp.zeros(
                    (total.shape[0] - part.shape[0], K), f32)])
            total = total + part
    dw_ref[0] = total


def backward(view, index, g, *, interpret: bool = False):
    """(the kernel's gradient ``[3, 3, 1, C]``, the bias's ``[C]``), float32,
    from the frames' view (:func:`frames`), :func:`forward`'s index and the
    pooled cotangent ``g: [N, Ho, Wo, C]``."""
    N, Ho, Wo, C = g.shape
    H, W, _ = view.shape
    nb = min(N, pool.LANES)
    window = pl.BlockSpec((1, Wo, C, nb), lambda n, s: (s, 0, 0, n))
    (dw,) = pool.call(
        functools.partial(_backward_kernel, W=W, Wo=Wo), (N // nb, Ho),
        [view, pool.to_view(g), index],
        [pl.BlockSpec((H, W, nb), lambda n, s: (0, 0, n)), window, window],
        [jax.ShapeDtypeStruct((N // nb, BLOCK * C, K), f32)],
        [pl.BlockSpec((1, BLOCK * C, K), lambda n, s: (n, 0, 0))],
        [pltpu.VMEM((H + 2, BLOCK * _blocks(W) + 16, nb), view.dtype),
         pltpu.VMEM((2, W, C, nb), f32),
         pltpu.VMEM((Wo, C, nb), g.dtype), pltpu.VMEM((Wo, C, nb), jnp.int32)],
        name="stem_backward", interpret=interpret,
        flops=2 * 9 * C * H * W * N, vmem_limit=VMEM)
    # row (j, c), column kh * SLAB + j + kw: tap (kh, kw) of channel c
    total = dw.sum(axis=0).reshape(BLOCK, C, K)
    j = jnp.arange(BLOCK)
    taps = jnp.stack([total[j, :, kh * SLAB + j + kw].sum(axis=0)
                      for kh in range(3) for kw in range(3)])
    return taps.reshape(3, 3, 1, C), total[:, :, 3 * SLAB].sum(axis=0)
