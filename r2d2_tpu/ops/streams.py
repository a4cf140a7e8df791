"""Pallas kernels for the ``xing4`` core's residual streams: one pass over
the streams a sublayer, forward and backward.

Every quantity of a sublayer's stream arithmetic is per token — the
flattened RMS norm's scale, the ``n + n + n n`` map logits, the Sinkhorn
rounds over an ``n x n`` matrix, the read ``sum_k pre_k x_k``, its RMS norm,
the write ``H_res X + H_post y`` — so a kernel that holds a tile of
:data:`TILE` tokens in VMEM does the whole chain on one load, where XLA
makes a trip through HBM for every dependency it cannot fuse across (the
24-column product stands between the reductions).  The plain expressions
in ``models/xing4.py`` (``stream_maps``, ``_streams_read``, ``_rms``,
``_streams_write``) stay the definition: :func:`block` computes what
``xing4.block`` computes with them, at the same rounding points (streams
stored in the compute type, float32 inside, the maps' product on
compute-type operands with float32 accumulation), and
tests/test_streams_kernel.py holds the two together.

A block is three launches forward (:func:`_forward`: *read*;
*write-then-read*, the attention sublayer's write and the feed-forward
sublayer's read on the streams it has just formed; *write*) and two a
sublayer backward (a ``jax.custom_vjp`` over the whole block: :func:`_dy`,
the write's cotangent for ``y``, which the sublayer's own backward has to
have before it can hand back ``u``'s; then :func:`_backward`.  Forming the
next ``dy`` inside that pass, from the cotangent it has just made, was
slower on the chip than the two apart: PERF.md Findings, PR 34).  A
backward pass reads the streams, ``y``, the streams' cotangent and ``u``'s
cotangent once and writes the streams' cotangent once; it recomputes the
maps from the streams, reverses the Sinkhorn rounds on values it kept in
VMEM and accumulates the parameter gradients over the token tiles.  The sublayers' own functions (attention,
feed-forward) run between the launches through ``jax.vjp``, whose closure
the forward rule keeps as a residual: nothing is computed twice that the
block's ``jax.checkpoint`` does not already recompute.

Layout.  A tile is ``(TILE, d)``: tokens on sublanes, the width on lanes.
The per-token maps are computed token-minor, ``(ROWS, TILE)`` with each
group of n rows on its own 8 sublanes (pre at row 0, post at 8, row i of
the mix at ``16 + 8 i``), so that a Sinkhorn step is a sublane reduction or
a sum of n arrays; one ``(128, TILE)`` transpose turns them into columns
``(TILE, 128)`` that scale the tile's rows, and the forward leaves those
columns in HBM (``cols``, 512 bytes a token) for the write and the backward.
What touches the width runs over the tile in groups of :data:`GROUP` rows
(:func:`_groups`), each from its loads to its stores in registers; only the
products with phi take the whole tile, from the normed streams kept in VMEM.
The last tile is partial wherever ``TILE`` does not divide the tokens: its
rows past the end hold whatever the DMA left, what is computed from them is
dropped on the way out, and the backward selects them away before anything
is summed over tokens.  No padded copy of the streams is laid out.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 128                  # tokens a grid step
GROUP = 16                  # rows a step inside it: one bf16 sublane tile
PRE, POST, RES = 0, 8, 16   # first token-minor row of each group of maps
f32 = jnp.float32


class Spec(NamedTuple):
    """What is static about a block's stream arithmetic."""
    n: int                  # streams
    iters: int              # Sinkhorn rounds
    rms_eps: float
    hc_eps: float
    clamp: float
    cd: Any                 # compute type: the streams' and the product's
    interpret: bool


def fits(tokens: int, width: int, n: int) -> bool:
    """Whether the kernels take these shapes: at least one tile of tokens,
    a width of whole lanes, a group of maps within 8 sublanes."""
    return tokens >= TILE and width % 128 == 0 and 1 <= n <= 8


def _rows(n: int) -> int:
    """Token-minor rows, a whole number of bf16 sublane tiles."""
    return -(-(RES + 8 * n) // 16) * 16


def _row_of(n: int) -> np.ndarray:
    """The token-minor row of each of phi's ``n + n + n n`` columns."""
    return np.array([PRE + k for k in range(n)] + [POST + k for k in range(n)]
                    + [RES + 8 * i + j for i in range(n) for j in range(n)])


# ------------------------------------------------------- inside a kernel

def _col(c, k):
    """Column k of the maps' columns ``c`` (TILE, 128) -> (TILE, 1)."""
    return c[:, k:k + 1]


def _live(n: int, tile: int):
    """The sublanes of a group of maps (8, tile) that hold an entry."""
    return jax.lax.broadcasted_iota(jnp.int32, (8, tile), 0) < n


def _maps(zT, alpha, beta, spec: Spec, keep=None):
    """Token-minor maps from the logits' product ``zT`` (ROWS, TILE):
    (logits a, pre (8, TILE), post (8, TILE), mix: n arrays (8, TILE), row
    i's entries on sublanes 0..n-1 and zeros below).  ``keep(t, m)`` is
    handed the mix before the first and after every Sinkhorn half-step."""
    n = spec.n
    a = zT * alpha + beta
    pre = jax.nn.sigmoid(a[PRE:PRE + 8])
    post = 2.0 * jax.nn.sigmoid(a[POST:POST + 8])
    live = _live(n, a.shape[1])
    m = [jnp.where(live, jnp.exp(jnp.clip(
        a[RES + 8 * i:RES + 8 * i + 8], -spec.clamp, spec.clamp)), 0.0)
        for i in range(n)]
    keep = keep or (lambda t, m: None)
    keep(0, m)

    def sinkhorn_round(t, m):
        m = [mi / (jnp.sum(mi, axis=0, keepdims=True) + spec.hc_eps)
             for mi in m]
        keep(2 * t + 1, m)
        den = _column_sums(m, live, spec)
        m = [mi / den for mi in m]
        keep(2 * t + 2, m)
        return m

    m = jax.lax.fori_loop(0, spec.iters, sinkhorn_round, m)
    return a, pre, post, m


def _column_sums(m, live, spec: Spec):
    """``sum_i m_i + eps`` on the live sublanes and 1 below them, where the
    entries are zeros: a chain of 0 / eps is one rewrite ((a / b) / c to
    a / (b c), which XLA's CPU compiler makes of an interpreted kernel) away
    from 0 / 0."""
    return jnp.where(live, functools.reduce(jnp.add, m) + spec.hc_eps, 1.0)


def _columns(tm_ref, pre, post, mix):
    """The maps as columns (TILE, 128): through ``tm_ref`` (128, TILE)."""
    tm_ref[PRE:PRE + 8] = pre
    tm_ref[POST:POST + 8] = post
    for i, mi in enumerate(mix):
        tm_ref[RES + 8 * i:RES + 8 * i + 8] = mi
    top = RES + 8 * len(mix)
    tm_ref[top:] = jnp.zeros((tm_ref.shape[0] - top, tm_ref.shape[1]), f32)
    return tm_ref[...].T


def _groups(tile: int, body):
    """``body(rows)`` for each group of :data:`GROUP` rows of a tile: what
    is computed of a group stays in registers from its loads to its
    stores, where an expression over the whole tile makes a trip through
    VMEM for every operation."""
    def step(g, _):
        body(pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP))
        return 0
    jax.lax.fori_loop(0, tile // GROUP, step, 0)


def _normed(xs, rows, xn_ref, sv_ref, spec: Spec):
    """A group's flattened RMS scale into column 0 of ``sv_ref`` and its
    normed streams, in the compute type, into ``xn_ref``."""
    n, d = spec.n, xs[0].shape[1]
    ss = functools.reduce(jnp.add, [
        jnp.sum(x * x, axis=-1, keepdims=True) for x in xs])
    scale = jax.lax.rsqrt(ss / (n * d) + spec.rms_eps)
    sv_ref[rows, 0:1] = scale
    for k, x in enumerate(xs):
        xn_ref[k, rows, :] = (x * scale).astype(spec.cd)


def _logits(phiT_ref, xn_ref, spec: Spec):
    """The normed streams' product with phi, token-minor (ROWS, TILE)."""
    return functools.reduce(jnp.add, [
        jax.lax.dot_general(phiT_ref[k], xn_ref[k], (((1,), (1,)), ((), ())),
                            preferred_element_type=f32)
        for k in range(spec.n)])


def _read(c, xs, spec: Spec):
    """(the read ``sum_k pre_k x_k``, the reciprocal of its RMS)."""
    r = functools.reduce(jnp.add, [
        _col(c, PRE + k) * x for k, x in enumerate(xs)])
    inv = jax.lax.rsqrt(jnp.mean(r * r, axis=-1, keepdims=True)
                        + spec.rms_eps)
    return r, inv


def _forward_kernel(*refs, spec: Spec, write: bool, read: bool):
    n = spec.n
    it = iter(refs)
    x = [next(it) for _ in range(n)]
    if write:
        cin, y = next(it), next(it)
    if read:
        phiT, alpha, beta, gain = (next(it) for _ in range(4))
    if write:
        xo = [next(it) for _ in range(n)]
    if read:
        u, cout, tm, sv, xn = (next(it) for _ in range(5))
    tile = x[0].shape[0]

    def first(rows):
        xs = [xk[rows, :].astype(f32) for xk in x]
        if write:
            c, yv = cin[rows, :], y[rows, :].astype(f32)
            for i in range(n):
                acc = functools.reduce(jnp.add, [
                    _col(c, RES + 8 * i + j) * xs[j] for j in range(n)])
                acc = acc + _col(c, POST + i) * yv
                xo[i][rows, :] = acc.astype(xo[i].dtype)
            # the streams as stored: rounded to the compute type
            xs = [xk[rows, :].astype(f32) for xk in xo]
        if read:
            _normed(xs, rows, xn, sv, spec)

    _groups(tile, first)
    if read:
        _, pre, post, mix = _maps(_logits(phiT, xn, spec), alpha[...],
                                  beta[...], spec)
        cout[...] = _columns(tm, pre, post, mix)
        src = xo if write else x

        def second(rows):
            xs = [xk[rows, :].astype(f32) for xk in src]
            r, inv = _read(cout[rows, :], xs, spec)
            u[rows, :] = (r * inv * gain[...]).astype(u.dtype)

        _groups(tile, second)


def _dy_kernel(*refs, n: int):
    """``dy = sum_i post_i dX'_i``: the write's cotangent for ``y``."""
    cols, dxo, dy = refs[0], refs[1:1 + n], refs[1 + n]

    def group(rows):
        c = cols[rows, :]
        dy[rows, :] = functools.reduce(jnp.add, [
            _col(c, POST + i) * dxo[i][rows, :].astype(f32)
            for i in range(n)])

    _groups(dy.shape[0], group)


def _backward_kernel(*refs, spec: Spec, tokens: int):
    """One sublayer's backward over a tile: from the streams, ``y``, the
    cotangents of the streams written and of ``u`` to the cotangent of the
    streams read, and the parameter gradients summed into their
    accumulators."""
    n, iters = spec.n, spec.iters
    it = iter(refs)
    x = [next(it) for _ in range(n)]
    y = next(it)
    dxo = [next(it) for _ in range(n)]
    du, phiT, alpha, beta, gain = (next(it) for _ in range(5))
    dx = [next(it) for _ in range(n)]
    dphiT, dab, dgain = next(it), next(it), next(it)
    tm, tc, td, sv, xn, drs, kept = (next(it) for _ in range(7))
    tile, d = x[0].shape
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _():
        dphiT[...] = jnp.zeros(dphiT.shape, f32)
        dab[...] = jnp.zeros(dab.shape, f32)
        dgain[...] = jnp.zeros(dgain.shape, f32)

    # rows past the last token hold whatever the DMA left: the streams and
    # u's cotangent are selected away there, and so is every per-token
    # cotangent, before anything is summed over tokens
    first = step * tile

    def streams_of(rows):
        here = (jax.lax.broadcasted_iota(jnp.int32, (GROUP, 1), 0)
                + first + rows.start < tokens)
        return here, [jnp.where(here, xk[rows, :].astype(f32), 0.0)
                      for xk in x]

    # the forward again, keeping every Sinkhorn state
    _groups(tile, lambda rows: _normed(streams_of(rows)[1], rows, xn, sv,
                                       spec))
    zT = _logits(phiT, xn, spec)

    def keep(t, m):
        for i, mi in enumerate(m):
            kept[t, i] = mi

    a, pre, post, mix = _maps(zT, alpha[...], beta[...], spec, keep)
    tc[...] = _columns(tm, pre, post, mix)

    # u = r inv gain, backward, and the maps' cotangents: n + n + n n dot
    # products over the width, as columns
    td[...] = jnp.zeros(td.shape, f32)

    def through_the_read(rows):
        here, xs = streams_of(rows)
        r, inv = _read(tc[rows, :], xs, spec)
        duv = jnp.where(here, du[rows, :].astype(f32), 0.0)
        dgain[...] += duv * (r * inv)
        gdu = duv * gain[...]
        dr = inv * gdu - r * (inv * inv * inv
                              * jnp.mean(gdu * r, axis=-1, keepdims=True))
        drs[rows, :] = dr
        yv = y[rows, :].astype(f32)
        for k in range(n):
            td[rows, PRE + k:PRE + k + 1] = jnp.sum(
                dr * xs[k], axis=-1, keepdims=True)
        for i in range(n):
            g = dxo[i][rows, :].astype(f32)
            td[rows, POST + i:POST + i + 1] = jnp.sum(
                g * yv, axis=-1, keepdims=True)
            for j in range(n):
                td[rows, RES + 8 * i + j:RES + 8 * i + j + 1] = jnp.sum(
                    g * xs[j], axis=-1, keepdims=True)

    _groups(tile, through_the_read)
    here_tm = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1) \
        + first < tokens
    dm = jnp.where(here_tm, td[...].T, 0.0)                 # (128, TILE)

    # through the sigmoids, and through the Sinkhorn half-steps reversed:
    # out = m / (sum m + eps) gives dm = (g - sum(g out)) / (sum m + eps)
    rows_tm = a.shape[0]
    tm[PRE:PRE + 8] = dm[PRE:PRE + 8] * pre * (1.0 - pre)
    tm[POST:POST + 8] = dm[POST:POST + 8] * post * (1.0 - 0.5 * post)
    g = [dm[RES + 8 * i:RES + 8 * i + 8] for i in range(n)]
    live = _live(n, tile)
    def sinkhorn_round_reversed(back, g):
        t = iters - 1 - back
        start, half, end = ([kept[2 * t + h, i] for i in range(n)]
                            for h in range(3))
        dot = functools.reduce(jnp.add, [gi * oi for gi, oi in zip(g, end)])
        den = _column_sums(half, live, spec)
        g = [(gi - dot) / den for gi in g]
        return [
            jnp.where(live, (gi - jnp.sum(gi * oi, axis=0, keepdims=True))
                      / (jnp.sum(wi, axis=0, keepdims=True) + spec.hc_eps),
                      0.0)
            for gi, oi, wi in zip(g, half, start)]

    g = jax.lax.fori_loop(0, iters, sinkhorn_round_reversed, g)
    for i in range(n):
        logit = a[RES + 8 * i:RES + 8 * i + 8]
        inside = (logit > -spec.clamp) & (logit < spec.clamp)
        tm[RES + 8 * i:RES + 8 * i + 8] = jnp.where(
            inside, g[i] * kept[0, i], 0.0)
    da = tm[0:rows_tm]                                      # (ROWS, TILE)
    dab[0] += da
    dab[1] += da * zT
    dzT = (da * alpha[...]).astype(spec.cd)

    # z = sum_k xn_k phi_k, backward: phi's gradient over the tile's tokens
    # on the MXU, and the normed streams' cotangent, rounded as the
    # compute-type operand's cotangent is, in the normed streams' place
    for k in range(n):
        dphiT[k] += jnp.dot(dzT, xn[k], preferred_element_type=f32)
        xn[k] = jax.lax.dot_general(
            dzT, phiT[k], (((0,), (0,)), ((), ())),
            preferred_element_type=f32).astype(spec.cd)

    def to_the_streams(rows):
        _, xs = streams_of(rows)
        c, dr, scale = tc[rows, :], drs[rows, :], sv[rows, 0:1]
        dxn = [xn[k, rows, :].astype(f32) for k in range(n)]
        dscale = functools.reduce(jnp.add, [
            jnp.sum(dk * xk, axis=-1, keepdims=True)
            for dk, xk in zip(dxn, xs)])
        dss2 = -dscale * (scale * scale * scale) / (n * d)  # 2 d(sum sq)
        for j in range(n):
            acc = _col(c, PRE + j) * dr + scale * dxn[j] + dss2 * xs[j]
            for i in range(n):
                acc = acc + (_col(c, RES + 8 * i + j)
                             * dxo[i][rows, :].astype(f32))
            dx[j][rows, :] = acc.astype(dx[j].dtype)

    _groups(tile, to_the_streams)


# ------------------------------------------------------------ the launches

def _call(kernel, spec: Spec, tokens, ins, outs, scratch=(), *, name,
          accumulates=False):
    """``pallas_call`` over the token tiles.  ``ins`` / ``outs``: (array or
    ShapeDtypeStruct, tiled) pairs; a tiled one is cut along its leading
    axis, another is whole at every step."""
    mem = {} if spec.interpret else dict(memory_space=pltpu.VMEM)

    def block_spec(a, tiled):
        if tiled:
            return pl.BlockSpec((TILE,) + a.shape[1:],
                                lambda i: (i,) + (0,) * (a.ndim - 1), **mem)
        return pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim, **mem)

    def nbytes(a, tiled=False):
        shape = (TILE,) + a.shape[1:] if tiled else a.shape
        return int(np.prod(shape)) * jnp.dtype(a.dtype).itemsize

    # scoped VMEM: both buffers of every block, the scratch, and room for
    # what the body spills (the default, 16 MB, is under one step's blocks
    # at the cell's width)
    vmem = (2 * sum(nbytes(*a) for a in list(ins) + list(outs))
            + sum(nbytes(a) for a in scratch) + (16 << 20))
    params = {} if spec.interpret else dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if accumulates else "parallel",),
            vmem_limit_bytes=min(vmem, 112 << 20)))
    return pl.pallas_call(
        kernel, grid=(pl.cdiv(tokens, TILE),),
        in_specs=[block_spec(*a) for a in ins],
        out_specs=[block_spec(*a) for a in outs],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a, _ in outs],
        scratch_shapes=list(scratch), interpret=spec.interpret, name=name,
        **params)(*[a for a, _ in ins])


def _operands(p, spec: Spec, d: int):
    """A sublayer's map parameters as the kernels read them: phi transposed,
    one ``(ROWS, d)`` slab a stream in the compute type with each column on
    its token-minor row; alpha and the biases spread over ``(ROWS, TILE)``."""
    n, rows, at = spec.n, _rows(spec.n), _row_of(spec.n)
    phi = jnp.concatenate([p["phi_pre"], p["phi_post"], p["phi_res"]], axis=1)
    phiT = jnp.zeros((n, rows, d), spec.cd).at[:, at].set(
        phi.astype(spec.cd).reshape(n, d, -1).swapaxes(1, 2))
    alpha = p["alpha"].astype(f32)
    beta = jnp.concatenate([p["b_pre"], p["b_post"],
                            p["b_res"].reshape(-1)]).astype(f32)
    spread = functools.partial(jnp.broadcast_to, shape=(rows, TILE))
    return (phiT,
            spread(jnp.zeros(rows, f32).at[at].set(
                jnp.repeat(alpha, np.array([n, n, n * n])))[:, None]),
            spread(jnp.zeros(rows, f32).at[at].set(beta)[:, None]))


def _gradients(p, spec: Spec, dphiT, dab):
    """The accumulators back in the parameters' own shapes and types."""
    n, at = spec.n, _row_of(spec.n)
    d = dphiT.shape[2]
    dphi = dphiT[:, at].swapaxes(1, 2).reshape(n * d, -1)
    da, daz = dab[0].sum(axis=1)[at], dab[1].sum(axis=1)[at]
    out = dict(
        phi_pre=dphi[:, :n], phi_post=dphi[:, n:2 * n], phi_res=dphi[:, 2 * n:],
        alpha=jnp.stack([daz[:n].sum(), daz[n:2 * n].sum(),
                         daz[2 * n:].sum()]),
        b_pre=da[:n], b_post=da[n:2 * n], b_res=da[2 * n:].reshape(n, n))
    return {k: v.astype(p[k].dtype) for k, v in out.items()}


def _forward(spec: Spec, X, *, wrote=None, reads=None):
    """One forward launch over the streams ``X`` (n arrays (N, d)).
    ``wrote`` (cols, y): first the write ``H_res X + H_post y`` under the
    maps' columns ``cols``; ``reads`` (mix parameters, norm gain, u's
    type): then the maps, the read and its norm on the streams as they
    then stand.  Returns (streams if written, u and columns if read)."""
    N, d = X[0].shape
    ins = [(x, True) for x in X]
    outs, scratch = [], []
    if wrote:
        ins += [(a, True) for a in wrote]
        outs += [(jax.ShapeDtypeStruct(x.shape, x.dtype), True) for x in X]
    if reads:
        p, gain, u_type = reads
        ins += [(a, False) for a in _operands(p, spec, d)]
        ins.append((gain.astype(f32).reshape(1, d), False))
        outs += [(jax.ShapeDtypeStruct((N, d), u_type), True),
                 (jax.ShapeDtypeStruct((N, 128), f32), True)]
        scratch += [pltpu.VMEM((128, TILE), f32), pltpu.VMEM((TILE, 128), f32),
                    pltpu.VMEM((spec.n, TILE, d), spec.cd)]
    kernel = functools.partial(_forward_kernel, spec=spec,
                               write=bool(wrote), read=bool(reads))
    with jax.named_scope("residual_mix"):
        out = _call(kernel, spec, N, ins, outs, scratch,
                    name="streams_" + "_".join(
                        w for w, on in (("write", wrote), ("read", reads))
                        if on))
    return (tuple(out[:spec.n]) if wrote else None,
            tuple(out[-2:]) if reads else None)


def _dy(spec: Spec, cols, dXo):
    N, d = dXo[0].shape
    with jax.named_scope("residual_mix"):
        return _call(
            functools.partial(_dy_kernel, n=spec.n), spec, N,
            [(cols, True)] + [(g, True) for g in dXo],
            [(jax.ShapeDtypeStruct((N, d), f32), True)],
            name="streams_dy")[0]


def _backward(spec: Spec, p, gain, X, y, dXo, du):
    """One sublayer's backward launch: (streams' cotangent, the mix
    parameters' gradients, the gain's)."""
    n, rows = spec.n, _rows(spec.n)
    N, d = X[0].shape
    ins = [(x, True) for x in X] + [(y, True)] + [(g, True) for g in dXo]
    ins.append((du, True))
    ins += [(a, False) for a in _operands(p, spec, d)]
    ins.append((gain.astype(f32).reshape(1, d), False))
    outs = [(jax.ShapeDtypeStruct(x.shape, x.dtype), True) for x in X]
    outs += [(jax.ShapeDtypeStruct((n, rows, d), f32), False),
             (jax.ShapeDtypeStruct((2, rows, TILE), f32), False),
             (jax.ShapeDtypeStruct((GROUP, d), f32), False)]
    scratch = [pltpu.VMEM((128, TILE), f32)] + [
        pltpu.VMEM((TILE, 128), f32) for _ in range(3)] + [
        pltpu.VMEM((n, TILE, d), spec.cd), pltpu.VMEM((TILE, d), f32),
        pltpu.VMEM((2 * spec.iters + 1, n, 8, TILE), f32)]
    kernel = functools.partial(_backward_kernel, spec=spec, tokens=N)
    with jax.named_scope("residual_mix"):
        out = _call(kernel, spec, N, ins, outs, scratch,
                    name="streams_backward", accumulates=True)
        dX, (dphiT, dab, dgain) = tuple(out[:n]), out[n:]
        return (dX, _gradients(p, spec, dphiT, dab),
                dgain.sum(axis=0).astype(gain.dtype))


# ---------------------------------------------------------------- a block

def _sublayers(spec, attend, feed, feed_type, p, X, attend_args, feed_args,
               call):
    """The block's three launches around its two sublayers, each sublayer
    run as ``call(function, u, its arguments) -> ((y, aux), its vjp)``:
    (results, what the backward needs)."""
    _, (u, cols_a) = _forward(spec, X, reads=(p["attn_mix"], p["attn_norm"],
                                              spec.cd))
    (y_a, aux_a), attend_vjp = call(attend, u, attend_args)
    X1, (u, cols_f) = _forward(spec, X, wrote=(cols_a, y_a),
                               reads=(p["ffn_mix"], p["ffn_norm"], feed_type))
    (y_f, aux_f), feed_vjp = call(feed, u, feed_args)
    X2, _ = _forward(spec, X1, wrote=(cols_f, y_f))
    return (X2, aux_a, aux_f), (p, X, y_a, cols_a, attend_vjp,
                                X1, y_f, cols_f, feed_vjp)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def block(spec: Spec, attend: Callable, feed: Callable, feed_type, p, X,
          attend_args, feed_args):
    """One block's stream arithmetic around its two sublayers.

    ``attend(u, attend_args) -> (y, aux)`` and ``feed(u, feed_args) -> (y,
    aux)`` are the sublayers' own functions of the normed read ``u`` (N, d)
    — in the compute type for ``attend``, in ``feed_type`` for ``feed`` —
    and close over nothing traced; ``p`` holds ``attn_mix``, ``attn_norm``,
    ``ffn_mix``, ``ffn_norm``.  Returns (streams, attend's aux, feed's)."""
    return _sublayers(spec, attend, feed, feed_type, p, X, attend_args,
                      feed_args, lambda f, *args: (f(*args), None))[0]


def _block_fwd(spec, attend, feed, feed_type, p, X, attend_args, feed_args):
    return _sublayers(spec, attend, feed, feed_type, p, X, attend_args,
                      feed_args, jax.vjp)


def _block_bwd(spec, attend, feed, feed_type, kept, cotangents):
    p, X, y_a, cols_a, attend_vjp, X1, y_f, cols_f, feed_vjp = kept
    dX2, daux_a, daux_f = cotangents
    du, dfeed_args = feed_vjp((_dy(spec, cols_f, dX2), daux_f))
    dX1, dmix_f, dnorm_f = _backward(
        spec, p["ffn_mix"], p["ffn_norm"], X1, y_f, dX2, du)
    du, dattend_args = attend_vjp((_dy(spec, cols_a, dX1), daux_a))
    dX, dmix_a, dnorm_a = _backward(
        spec, p["attn_mix"], p["attn_norm"], X, y_a, dX1, du)
    dp = dict(attn_mix=dmix_a, attn_norm=dnorm_a,
              ffn_mix=dmix_f, ffn_norm=dnorm_f)
    return dp, dX, dattend_args, dfeed_args


block.defvjp(_block_fwd, _block_bwd)
