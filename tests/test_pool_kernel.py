"""The max-pool's kernels (ops/pool.py), interpreted on the CPU, against
``nn.max_pool`` and XLA's own gradient of it at the IMPALA torso's three
stage shapes: the stored index is each window's first maximum, the pooled
values are ``nn.max_pool``'s bit for bit, and the gradient routes every
cotangent where XLA's does, summed in float32 and rounded once; the torso
built on them has today's parameters and Q-values."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from r2d2_tpu.models.network import ImpalaTorso
from r2d2_tpu.ops import pool

FRAMES = 8
# (input size, channels) of the torso's three stages: 84 -> 42, 42 -> 21
# (no low padding), 21 -> 11 (one row and column of it on each side)
STAGES = [(84, 16), (42, 32), (21, 32)]
CASES = [(h, c, d) for h, c in STAGES for d in ("float32", "bfloat16")]
IDS = [f"{h}x{c}-{d}" for h, c, d in CASES]


def tied(key, shape, dtype):
    """Values on a few levels, so windows hold ties, and whole windows of
    zeros in a corner."""
    x = jnp.round(jax.random.uniform(key, shape) * 4) / 4
    return x.at[:, :6, :6].set(0.0).astype(dtype)


@functools.lru_cache(maxsize=None)
def case(h, c, dtype):
    """(x, g, the kernels' pooled values and index, their dx for g and for
    a positive cotangent)."""
    kx, kg = jax.random.split(jax.random.PRNGKey(h + c))
    x = tied(kx, (FRAMES, h, h, c), dtype)
    ho = pool.geometry(h)[0]
    g = jax.random.normal(kg, (FRAMES, ho, ho, c)).astype(dtype)
    y, index = pool.forward(x, interpret=True)
    dx = pool.backward(index, g, (h, h), interpret=True)
    routed = pool.backward(index, jnp.abs(g) + 1, (h, h), interpret=True)
    return x, g, y, index, dx, routed


def xla_grad(x, g):
    return jax.vjp(pool.plain, x)[1](g)[0]


@pytest.mark.parametrize("h,c,dtype", CASES, ids=IDS)
def test_the_index_is_the_first_maximum(h, c, dtype):
    x, _, _, index, _, _ = case(h, c, dtype)
    ho, lo = pool.geometry(h)
    hi = 2 * (ho - 1) + 3 - h - lo
    padded = np.pad(np.asarray(x, np.float32), ((0, 0), (lo, hi), (lo, hi),
                                                (0, 0)),
                    constant_values=-np.inf)
    windows = np.stack([padded[:, r:r + 2 * ho:2, q:q + 2 * ho:2]
                        for r in range(3) for q in range(3)], axis=-1)
    first = np.argmax(windows, axis=-1)             # numpy's: the first
    assert index.dtype == jnp.int8
    assert np.array_equal(np.asarray(index).transpose(3, 0, 1, 2), first)
    # the planted ties are there: windows whose maximum is not unique
    ties = (windows == windows.max(-1, keepdims=True)).sum(-1) > 1
    assert ties.mean() > 0.05


@pytest.mark.parametrize("h,c,dtype", CASES, ids=IDS)
def test_the_pooled_values_are_nn_max_pool_bit_for_bit(h, c, dtype):
    x, _, y, _, _, _ = case(h, c, dtype)
    assert y.dtype == x.dtype
    assert np.array_equal(np.asarray(y), np.asarray(pool.plain(x)))


@pytest.mark.parametrize("h,c,dtype", CASES, ids=IDS)
def test_the_gradient_is_xlas_to_its_rounding(h, c, dtype):
    """Against ``jax.grad`` through ``nn.max_pool``: the same positions
    take a cotangent, and the values agree to the rounding of the sums.
    Up to four cotangents meet in an input element; XLA adds them in the
    type, the kernel in float32 in another order, three additions each
    way: within two ulps of the type at the magnitude of what meets there
    (so where XLA's bfloat16 sum cancels to 0, the kernel's need not).  In
    bfloat16 the kernel's is the float32 sum rounded once, exactly."""
    x, g, _, _, dx, routed = case(h, c, dtype)
    assert dx.dtype == x.dtype
    ref = np.asarray(xla_grad(x, g), np.float32)
    ours = np.asarray(dx, np.float32)
    # where a cotangent goes: a positive one cancels nowhere
    assert np.array_equal(np.asarray(routed) != 0,
                          np.asarray(xla_grad(x, jnp.abs(g) + 1)) != 0)
    mass = np.asarray(xla_grad(x.astype(jnp.float32),
                               jnp.abs(g).astype(jnp.float32)))
    bits = {"float32": 23, "bfloat16": 7}[dtype]
    ulp = np.exp2(np.floor(np.log2(np.maximum(mass, 1e-30))) - bits)
    assert (np.abs(ours - ref) <= 2 * ulp).all()
    if dtype == "bfloat16":
        once = xla_grad(x.astype(jnp.float32), g.astype(jnp.float32))
        assert np.array_equal(ours, np.asarray(once.astype(dtype),
                                               np.float32))


@pytest.mark.parametrize("h,c,dtype", CASES[:2], ids=IDS[:2])
def test_the_custom_rule_is_what_grad_takes(h, c, dtype):
    """``max_pool(interpret=True)`` differentiates through the kernels;
    without it, on the CPU, it is ``nn.max_pool`` and XLA's gradient."""
    x, g, _, _, dx, _ = case(h, c, dtype)

    def pull(**kw):
        return jax.vjp(lambda x: pool.max_pool(x, **kw), x)[1](g)[0]

    assert np.array_equal(np.asarray(pull(interpret=True)), np.asarray(dx))
    kernels = str(jax.make_jaxpr(lambda x: pull(interpret=True))(x))
    assert "max_pool_backward" in kernels and "max_pool_forward" in kernels
    assert "pallas_call" not in str(jax.make_jaxpr(lambda x: pull())(x))


class TorsoWithNnPool(ImpalaTorso):
    """The torso as it was written before the kernels: ``nn.max_pool``."""

    @nn.compact
    def __call__(self, x):
        kw = dict(padding="SAME", dtype=self.compute_dtype,
                  param_dtype=self.param_dtype)
        for ch in self.channels:
            x = nn.Conv(ch, (3, 3), **kw)(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
            for _ in range(self.blocks_per_stage):
                skip = x
                x = nn.Conv(ch, (3, 3), **kw)(nn.relu(x))
                x = nn.Conv(ch, (3, 3), **kw)(nn.relu(x))
                x = x + skip
        x = nn.relu(x)
        x = x.reshape(x.shape[0], -1)
        return nn.relu(nn.Dense(self.out_dim, dtype=self.compute_dtype,
                                param_dtype=self.param_dtype)(x))


def rel(a, b):
    """The largest relative difference of two trees, leaf by leaf, in
    norm."""
    return max(float(np.linalg.norm(np.asarray(u, np.float32)
                                    - np.asarray(v, np.float32))
                     / np.linalg.norm(np.asarray(v, np.float32)))
               for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_torso_keeps_its_parameters_and_values(dtype, monkeypatch):
    """For a fixed key: the same parameters, the same outputs bit for bit
    (the forward is ``nn.max_pool``'s on every backend), and with the
    kernels interpreted the same gradient: in float32 to its rounding; in
    bfloat16, where a rounding of a pooled cotangent travels through every
    convolution's backward, as near the float32 gradient as XLA's."""
    x = jax.random.uniform(jax.random.PRNGKey(1), (FRAMES, 84, 84, 1))
    key = jax.random.PRNGKey(0)

    def torsos(dtype):
        kw = dict(out_dim=32, compute_dtype=jnp.dtype(dtype))
        return ImpalaTorso(**kw), TorsoWithNnPool(**kw)

    torso, before = torsos(dtype)
    params = torso.init(key, x)
    jax.tree.map(np.testing.assert_array_equal, params, before.init(key, x))
    out = torso.apply(params, x)
    assert np.array_equal(np.asarray(out), np.asarray(before.apply(params, x)))

    def grad(module):
        return jax.grad(lambda p: jnp.sum(module.apply(p, x)))(params)

    plain, exact = grad(before), grad(torsos("float32")[1])
    monkeypatch.setattr(pool, "max_pool", functools.partial(
        pool.max_pool, interpret=True))
    with_kernels = grad(torso)
    assert np.array_equal(np.asarray(torso.apply(params, x)),
                          np.asarray(out))
    if dtype == "float32":
        assert rel(with_kernels, plain) < 1e-5
    else:
        assert rel(with_kernels, exact) < 1.25 * rel(plain, exact)
