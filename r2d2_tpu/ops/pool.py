"""The IMPALA torso's max-pool (3 x 3 windows, stride 2, SAME padding) with
a backward that reads the pooled cotangent and the window index the
forward stored, never the activation that was pooled.

Which stage takes what: on a TPU the second and third stages, whose
convolutions have 16 and 32 input channels and run on the matrix unit,
pool through :func:`max_pool`'s kernels; the first stage, whose
convolution has one input channel, is ops/stem.py's one pass, which
computes the convolution in VMEM and pools it with :func:`pool_rows`, this
module's forward step, so the 1.73 GB it would pool is never written.

Why an index.  Differentiated through ``nn.max_pool``, XLA keeps each
pre-pool activation alive until the backward and hands it to a
``select-and-scatter``, which reads it again beside the cotangent to find
each window's maximum a second time: at the IMPALA cell's first stage
(7,680 frames of 84 x 84 x 16 in bfloat16) that was 1.73 GB kept and
3.90 GB moved to route 0.43 GB of cotangent (PERF.md, Findings).
What the backward needs of a window is only which of its nine positions
won: :func:`forward` records it, one byte a pooled element, and
:func:`backward` sends each cotangent to that position.  The pooled
values are ``nn.max_pool``'s own, bit for bit; the index is the
row-major position of the window's FIRST maximum, the element that
``select-and-scatter`` (whose select is ``>=``) routes to, so gradients
under ties are the same; the up to four cotangents that meet in one input
element are summed in float32 and rounded once.  The price is the forward:
XLA folds ``nn.max_pool``'s forward into the convolutions that read its
result, and :func:`forward` is a pass of its own over the activation, so
what the step gains is the backward's bytes less that pass (PERF.md,
Findings).

Layout.  The step's convolutions lay these activations out ``{0,3,2,1}``:
frames minor, on the lanes, then channels, then width and height as major
dimensions.  The kernels take the row-major ``[H, W, C, N]`` view of that
layout, which the transposes below are to the compiler: bitcasts, not
copies (tests/test_tpu_compile.py asserts it).  A program holds
:data:`LANES` frames and walks down the rows; width, a major dimension,
is walked one window column at a time, so every shift between windows is
a whole ``(C, LANES)`` slab.  A window's rows are the input rows
``2 a - lo .. 2 a + 2 - lo``, ``lo`` the low padding (0 for 84 -> 42 and
42 -> 21, 1 for 21 -> 11); each step takes one pair of rows in the
forward (one window row in the backward) and carries what it shares with
the next in VMEM, so each input is read from HBM once.

Where the backend is not a TPU, :func:`max_pool` is ``nn.max_pool`` and its
gradient XLA's own; tests/test_pool_kernel.py runs the kernels interpreted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

WINDOW, STRIDE = 3, 2
LANES = 256                 # frames a program
NONE = WINDOW * WINDOW      # an index no window position has
f32 = jnp.float32


def plain(x):
    """The pool as the torso defines it: ``nn.max_pool``."""
    return nn.max_pool(x, (WINDOW, WINDOW), strides=(STRIDE, STRIDE),
                       padding="SAME")


def geometry(size: int):
    """(pooled size, low padding) of one spatial dimension under SAME."""
    out = -(-size // STRIDE)
    return out, max((out - 1) * STRIDE + WINDOW - size, 0) // 2


def fits(shape) -> bool:
    """Whether the kernels take ``[N, H, W, C]``: whole programs of frames,
    or fewer frames than one program, which then holds them all."""
    return shape[0] % LANES == 0 or shape[0] < LANES


def max_pool(x, *, interpret: bool = False):
    """``nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")`` of
    ``x: [N, H, W, C]``.  Its value is always ``nn.max_pool``'s; on a TPU
    (or ``interpret``) its gradient comes from the stored window index."""
    if not (interpret or jax.default_backend() == "tpu") or not fits(x.shape):
        return plain(x)
    return _pool(x, x.shape[1:3], interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _pool(x, hw, interpret):
    return plain(x)


def _pool_fwd(x, hw, interpret):
    return forward(x, interpret=interpret)


def _pool_bwd(hw, interpret, index, g):
    return (backward(index, g, hw, interpret=interpret),)


_pool.defvjp(_pool_fwd, _pool_bwd)


def to_view(a):
    """``[N, H, W, C]`` -> the kernels' ``[H, W, C, N]``."""
    return jnp.transpose(a, (1, 2, 3, 0))


def from_view(a):
    return jnp.transpose(a, (3, 0, 1, 2))


def _columns(width: int, out: int, lo: int, pc: int):
    """Window columns ``b`` whose column ``pc`` is the input column
    ``2 b - lo + pc`` inside ``[0, width)``: (first b, last b)."""
    first = max(-(-(lo - pc) // 2), 0)
    last = min((width - 1 + lo - pc) // 2, out - 1)
    return first, last


def call(kernel, grid, ins, in_specs, outs, out_specs, scratch, *, name,
         interpret, flops=0, vmem_limit=None):
    """``pallas_call`` over (programs of frames, steps down the rows): the
    steps carry rows in VMEM, so they run in order.  ``vmem_limit``, in
    bytes, raises the compiler's default for a kernel that holds more."""
    nbytes = sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
                 for a in list(ins) + list(outs))
    params = {} if interpret else dict(compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=vmem_limit))
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=outs, scratch_shapes=scratch, interpret=interpret,
        name=name, cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=0, bytes_accessed=nbytes),
        **params)(*ins)


# ----------------------------------------------------------------- forward

def _forward_kernel(x_ref, y_ref, k_ref, best_ref, arg_ref, **geometry):
    pool_rows(lambda r, col: x_ref[r, col].astype(f32), y_ref, k_ref,
              best_ref, arg_ref, **geometry)


def pool_rows(read, y_ref, k_ref, best_ref, arg_ref, *, H, W, Wo, lr, lc):
    """The forward's step ``s``: it holds input rows ``2 s`` and
    ``2 s + 1``, which ``read(r, col)`` gives (``r`` 0 or 1, one input
    column as float32 ``(C, lanes)``).  With ``lr`` (the rows' low
    padding) 0 it finishes window row ``s - 1``, whose last row is
    ``2 s``, and starts row ``s`` from both; with ``lr`` 1 it finishes
    window row ``s``, whose first row, ``2 s - 1``, was the last step's
    second.  ``best_ref`` / ``arg_ref`` carry the started window row
    between steps.  Without ``k_ref`` (and ``arg_ref``) it pools the values
    alone and stores no index."""
    s = pl.program_id(1)
    ninf = jnp.array(-jnp.inf, f32)
    first_kept = s > 0
    indexed = k_ref is not None
    # rows past the input: 2 Ho with lr 0 (its last step), H with H odd
    row0_real = 2 * s < H
    row1_real = 2 * s + 1 < H

    def column(r, b, pc):
        """Input column ``2 b - lc + pc`` of the step's row ``r``, -inf in
        the padding."""
        v = read(r, jnp.clip(2 * b - lc + pc, 0, W - 1))
        first, last = _columns(W, Wo, lc, pc)
        if (first, last) == (0, Wo - 1):
            return v
        return jnp.where((b >= first) & (b <= last), v, ninf)

    def row_max(r, b, real):
        """(max over a window row's three columns, its first column); -inf
        where the row is past the input."""
        best = column(r, b, 0)
        arg = jnp.zeros(best.shape, jnp.int32) if indexed else None
        for pc in (1, 2):
            best, arg = after(best, arg, column(r, b, pc), pc)
        return jnp.where(real, best, ninf), arg

    def after(best, arg, v, pos):
        """A later position of the same window: it wins only if greater."""
        if not indexed:
            return jnp.maximum(best, v), None
        take = v > best
        return jnp.where(take, v, best), jnp.where(take, pos, arg)

    def plus(arg, rows):
        return None if arg is None else arg + rows

    def emit(b, best, arg):
        y_ref[0, b] = best.astype(y_ref.dtype)
        if indexed:
            k_ref[0, b] = arg.astype(k_ref.dtype)

    def keep(b, best, arg):
        best_ref[b] = best
        if indexed:
            arg_ref[b] = arg

    def step(b, _):
        v0, p0 = row_max(0, b, row0_real)
        v1, p1 = row_max(1, b, row1_real)
        if lr == 0:
            # window row s - 1 ends with input row 2 s, its position row 2
            best = best_ref[b]
            arg = arg_ref[b] if indexed else None
            emit(b, *after(best, arg, v0, plus(p0, 6)))
            # window row s: rows 0, 1
            keep(b, *after(v0, p0, v1, plus(p1, 3)))
        else:
            # window row s: the carried position row 0, then rows 1 and 2
            best = jnp.where(first_kept, best_ref[b], ninf)
            arg = jnp.where(first_kept, arg_ref[b], 0) if indexed else None
            best, arg = after(best, arg, v0, plus(p0, 3))
            emit(b, *after(best, arg, v1, plus(p1, 6)))
            keep(b, v1, p1)             # window row s + 1's position row 0
        return 0

    jax.lax.fori_loop(0, Wo, step, 0)


def forward(x, *, interpret: bool = False):
    """(``nn.max_pool(x)``, the index of each window's first maximum,
    ``int8[Ho, Wo, C, N]`` in the kernels' view, row-major in the window:
    ``3 * row + column``)."""
    N, H, W, C = x.shape
    (Ho, lr), (Wo, lc) = geometry(H), geometry(W)
    nb = min(N, LANES)
    last = -(-H // 2) - 1       # the last pair of input rows
    kernel = functools.partial(_forward_kernel, H=H, W=W, Wo=Wo, lr=lr, lc=lc)
    pooled = pl.BlockSpec((1, Wo, C, nb),
                          lambda n, s: (jnp.maximum(s - 1 + lr, 0), 0, 0, n))
    # lr 0 ends its last window a step late
    y, index = call(
        kernel, (N // nb, Ho + 1 - lr),
        [to_view(x)],
        [pl.BlockSpec((2, W, C, nb),
                      lambda n, s: (jnp.minimum(s, last), 0, 0, n))],
        [jax.ShapeDtypeStruct((Ho, Wo, C, N), x.dtype),
         jax.ShapeDtypeStruct((Ho, Wo, C, N), jnp.int8)], [pooled, pooled],
        [pltpu.VMEM((Wo, C, nb), f32), pltpu.VMEM((Wo, C, nb), jnp.int32)],
        name="max_pool_forward", interpret=interpret)
    return from_view(y), index


# ---------------------------------------------------------------- backward

def _backward_kernel(g_ref, k_ref, dx_ref, gp_ref, kp_ref, *, Ho, W, Wo, lr,
                     lc):
    """Step ``s`` reads window row ``s`` (``cur``) and has the last step's
    (``prev``) in ``gp_ref`` / ``kp_ref``.  Input row ``2 a - lr`` takes
    window row ``a``'s position row 0 and row ``a - 1``'s position row 2;
    row ``2 a + 1 - lr`` takes row ``a``'s position row 1.  With ``lr`` 0
    the step writes rows ``2 s`` and ``2 s + 1`` (cur row 0 + prev row 2,
    then cur row 1); with ``lr`` 1 rows ``2 s - 2`` and ``2 s - 1`` (prev
    row 1, then cur row 0 + prev row 2), a step late.  Columns follow the
    same rule, window column by window column, with ``lc``."""
    s = pl.program_id(1)
    even_row, odd_row = (0, 1) if lr == 0 else (1, 0)
    e_first, _ = _columns(W, Wo, lc, 0)

    @pl.when(s == 0)
    def _():
        kp_ref[...] = jnp.full(kp_ref.shape, NONE, jnp.int32)

    def terms(g, k, row):
        """The cotangents of one window row that go to its position row
        ``row``, by position column: three ``(C, lanes)`` slabs."""
        return [jnp.where(k == 3 * row + pc, g, 0.0) for pc in range(3)]

    def store(r, b, e, o):
        """Input row ``r``: column ``2 b - lc`` (``e``: position column 0
        of window column b and 2 of b - 1) and ``2 b + 1 - lc`` (``o``)."""
        j = 2 * b - lc

        @pl.when(b >= e_first)
        def _():
            dx_ref[r, jnp.maximum(j, 0)] = e.astype(dx_ref.dtype)

        dx_ref[r, j + 1] = o.astype(dx_ref.dtype)

    def step(b, carry):
        even2, odd2 = carry
        g = g_ref[0, b].astype(f32)
        k_read = k_ref[0, b].astype(jnp.int32)
        # lr 1's last step has no window row s: its block is row s - 1's
        k = jnp.where(s < Ho, k_read, NONE) if lr else k_read
        gp, kp = gp_ref[b].astype(f32), kp_ref[b]
        even = [a + c for a, c in zip(terms(g, k, 0), terms(gp, kp, 2))]
        odd = terms(g, k, 1) if lr == 0 else terms(gp, kp, 1)
        store(even_row, b, even[0] + even2, even[1])
        store(odd_row, b, odd[0] + odd2, odd[1])
        gp_ref[b] = g_ref[0, b]
        kp_ref[b] = k_read
        return even[2], odd[2]

    zero = jnp.zeros(gp_ref.shape[1:], f32)
    jax.lax.fori_loop(0, Wo, step, (zero, zero))


def backward(index, g, hw, *, interpret: bool = False):
    """The cotangent of ``x: [N, H, W, C]`` (``hw = (H, W)``) from the
    pooled cotangent ``g`` and :func:`forward`'s index."""
    N, Ho, Wo, C = g.shape
    H, W = hw
    lr, lc = geometry(H)[1], geometry(W)[1]
    nb = min(N, LANES)
    kernel = functools.partial(_backward_kernel, Ho=Ho, W=W, Wo=Wo, lr=lr,
                               lc=lc)
    window = pl.BlockSpec((1, Wo, C, nb),
                          lambda n, s: (jnp.minimum(s, Ho - 1), 0, 0, n))
    # lr 1 writes its last rows a step late
    (dx,) = call(
        kernel, (N // nb, Ho + lr),
        [to_view(g), index], [window, window],
        [jax.ShapeDtypeStruct((H, W, C, N), g.dtype)],
        [pl.BlockSpec((2, W, C, nb),
                      lambda n, s: (jnp.maximum(s - lr, 0), 0, 0, n))],
        [pltpu.VMEM((Wo, C, nb), g.dtype), pltpu.VMEM((Wo, C, nb), jnp.int32)],
        name="max_pool_backward", interpret=interpret)
    return from_view(dx)
