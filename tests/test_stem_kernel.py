"""The IMPALA torso's stem (ops/stem.py), its first convolution and pool in
one pass, interpreted on the CPU at small sizes: the pooled values are
``nn.max_pool(nn.Conv(...))``'s, the index is ops/pool.py's on the same
convolution, the kernel's and bias's gradients are ``jax.grad``'s of the
plain path, the primal is the forward's values without an index, and the
torso built on it keeps the parameters it had."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from r2d2_tpu.config import impala_deep_config, pong_config
from r2d2_tpu.models import network
from r2d2_tpu.ops import pool, stem

FRAMES = 128                # one block of lanes: the kernels' smallest program
C = 16
H, W = 10, 20               # even, not square, a block and a part


def levels(key, shape, n):
    """Values on a few levels of 1/n in [0, 1]: with the kernel's, every
    convolution sums exactly in float32, whatever its order, and windows
    hold ties; a corner of zeros holds whole windows of the bias alone."""
    x = jnp.round(jax.random.uniform(key, shape) * n) / n
    return x.at[:, :4, :4].set(0.0)


@functools.lru_cache(maxsize=None)
def case(h, w, dtype):
    """(x, kernel, bias, g, the kernels' pooled values, index and
    gradients, the convolution in float32)."""
    kx, kk, kb, kg = jax.random.split(jax.random.PRNGKey(h * w), 4)
    x = levels(kx, (FRAMES, h, w, 1), 2).astype(dtype)
    kernel = jnp.round(jax.random.normal(kk, (3, 3, 1, C)) * 16) / 64
    bias = jnp.round(jax.random.normal(kb, (C,)) * 4) / 16
    g = jax.random.normal(kg, (FRAMES, h // 2, w // 2, C)).astype(dtype)
    fr = stem.frames(x)
    y, index = stem.forward(fr, kernel, bias, interpret=True)
    dk, db = stem.backward(fr, index, g, interpret=True)
    pre = conv(x.astype(jnp.float32), kernel, bias, jnp.float32)
    return x, kernel, bias, g, y, index, dk, db, pre


def conv(x, kernel, bias, dtype):
    return nn.Conv(C, (3, 3), padding="SAME", dtype=dtype, parent=None).apply(
        {"params": {"kernel": kernel, "bias": bias}}, x)


def routed_sums(x, index, g):
    """The kernel's and bias's gradients by their definition, in numpy:
    each pooled cotangent added, in float64, to the window position its
    index names, the sums rounded to the cotangent's dtype (as ops/pool.py's
    backward hands them on), times the pixels under the nine taps."""
    n, h, w, _ = x.shape
    xp = np.pad(np.asarray(x, np.float64)[..., 0], ((0, 0), (1, 1), (1, 1)))
    k = np.asarray(index).transpose(3, 0, 1, 2)          # [N, Ho, Wo, C]
    a, b = np.meshgrid(np.arange(h // 2), np.arange(w // 2), indexing="ij")
    i = 2 * a[None, :, :, None] + k // 3                  # winning row
    j = 2 * b[None, :, :, None] + k % 3
    frame = np.arange(n)[:, None, None, None]
    chan = np.arange(k.shape[-1])[None, None, None, :]
    routed = np.zeros((n, h + 1, w + 1, k.shape[-1]))
    np.add.at(routed, (frame, i, j, chan), np.asarray(g, np.float64))
    routed = np.asarray(jnp.asarray(routed[:, :h, :w], jnp.float32).astype(
        g.dtype), np.float64)
    dk = np.zeros((3, 3, 1, k.shape[-1]))
    for kh in range(3):
        for kw in range(3):
            dk[kh, kw, 0] = np.einsum("nijc,nij->c", routed,
                                      xp[:, kh:kh + h, kw:kw + w])
    return dk, routed.sum((0, 1, 2))


def windows(pre):
    """The nine positions of every pooled window of ``pre: [N, H, W, C]``,
    the pool's padding -inf: ``[N, Ho, Wo, C, 9]``."""
    padded = np.pad(np.asarray(pre), ((0, 0), (0, 1), (0, 1), (0, 0)),
                    constant_values=-np.inf)
    return np.stack([padded[:, r:r + H:2, q:q + W:2]
                     for r in range(3) for q in range(3)], axis=-1)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_stem_pools_the_convolution_and_routes_by_the_index(dtype):
    """The pooled values are each window's maximum of the float32 sums
    rounded once: in float32 ``nn.max_pool(nn.Conv(x))`` bit for bit; in
    bfloat16 ``nn.max_pool`` of the rounded convolution (rounding is
    monotone), which ``nn.Conv`` in bfloat16 reaches within its own
    rounding of the bias add.  The index is ops/pool.py's first maximum of
    the same convolution, with planted ties and both edges' padding (the
    convolution's zeros, the pool's high row and column of -inf).  The
    kernel's and bias's gradients are their definition from that index,
    summed in float64: float32's rounding of the sums."""
    x, kernel, bias, g, y, index, dk, db, pre = case(H, W, dtype)
    assert y.dtype == jnp.dtype(dtype) and index.dtype == jnp.int8
    ours = np.asarray(y, np.float32)
    assert np.array_equal(ours, np.asarray(pool.plain(pre).astype(dtype),
                                           np.float32))
    xla = np.asarray(nn.max_pool(conv(x, kernel, bias, jnp.dtype(dtype)),
                                 (3, 3), strides=(2, 2), padding="SAME"),
                     np.float32)
    if dtype == "float32":
        assert np.array_equal(ours, xla)
    else:
        ulp = np.exp2(np.floor(np.log2(np.maximum(np.abs(ours), 1e-30))) - 7)
        assert (np.abs(ours - xla) <= ulp).all()
    assert np.array_equal(np.asarray(index),
                          np.asarray(pool.forward(pre, interpret=True)[1]))
    win = windows(pre)
    assert ((win == win.max(-1, keepdims=True)).sum(-1) > 1).mean() > 0.05
    want_k, want_b = routed_sums(x, index, g)
    assert dk.dtype == db.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(dk), want_k, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(db), want_b, rtol=1e-5, atol=1e-4)


def test_the_gradient_is_jax_grad_of_the_plain_path():
    """Through ``conv_pool``'s rule, at a whole program of frames: the
    kernel's and bias's gradients are ``jax.grad`` of ``nn.Conv`` then
    ``nn.max_pool`` (XLA's routing, the same under ties) to float32's
    rounding, and the primal's values are the forward's, with no index
    stored."""
    x = levels(jax.random.PRNGKey(3), (2 * FRAMES, H, W, 1), 8)
    kernel = jnp.round(jax.random.normal(jax.random.PRNGKey(5),
                                         (3, 3, 1, C)) * 16) / 64
    bias = jnp.round(jax.random.normal(jax.random.PRNGKey(6), (C,)) * 4) / 16
    g = jax.random.normal(jax.random.PRNGKey(4), (2 * FRAMES, H // 2, W // 2,
                                                  C))
    assert stem.engages(x.shape, C, interpret=True)
    assert not stem.engages(x.shape, C)            # the CPU's backend

    def loss(fn):
        return lambda k, b: jnp.sum(fn(k, b) * g)

    fused = functools.partial(stem.conv_pool, x, dtype=jnp.float32,
                              interpret=True)
    plain = lambda k, b: nn.max_pool(conv(x, k, b, jnp.float32), (3, 3),
                                     strides=(2, 2), padding="SAME")
    ours = jax.grad(loss(fused), argnums=(0, 1))(kernel, bias)
    want = jax.grad(loss(plain), argnums=(0, 1))(kernel, bias)
    for u, v in zip(ours, want):
        np.testing.assert_allclose(np.asarray(u), np.asarray(v), rtol=1e-5,
                                   atol=1e-4)
    y = fused(kernel, bias)
    assert np.array_equal(np.asarray(y), np.asarray(plain(kernel, bias)))
    primal = str(jax.make_jaxpr(fused)(kernel, bias))
    assert "stem_forward" in primal and "int8" not in primal
    assert "pallas_call" not in str(jax.make_jaxpr(functools.partial(
        stem.conv_pool, x, dtype=jnp.float32))(kernel, bias))


class TorsoBefore(network.ImpalaTorso):
    """The torso as it was written before the stem: every convolution an
    ``nn.Conv`` under its automatic name, each pool ``ops/pool.max_pool``."""

    @nn.compact
    def __call__(self, x):
        kw = dict(padding="SAME", dtype=self.compute_dtype,
                  param_dtype=self.param_dtype)
        for ch in self.channels:
            x = nn.Conv(ch, (3, 3), **kw)(x)
            x = pool.max_pool(x)
            for _ in range(self.blocks_per_stage):
                skip = x
                x = nn.Conv(ch, (3, 3), **kw)(nn.relu(x))
                x = nn.Conv(ch, (3, 3), **kw)(nn.relu(x))
                x = x + skip
        x = nn.relu(x)
        x = x.reshape(x.shape[0], -1)
        return nn.relu(nn.Dense(self.out_dim, dtype=self.compute_dtype,
                                param_dtype=self.param_dtype)(x))


def test_the_impala_network_draws_the_parameters_it_drew(monkeypatch):
    """``init_params`` of ``impala_deep_lstm2``'s network at a seed: the
    same tree, names and values as with the torso before the stem."""
    cfg = impala_deep_config()
    net = network.create_network(cfg, 6)
    key = jax.random.PRNGKey(2024)
    now = network.init_params(cfg, net, key)
    monkeypatch.setattr(network, "ImpalaTorso", TorsoBefore)
    before = network.init_params(cfg, network.create_network(cfg, 6), key)
    assert (jax.tree_util.tree_structure(now)
            == jax.tree_util.tree_structure(before))
    assert "Conv_0" in now["params"]["torso"]
    jax.tree.map(np.testing.assert_array_equal, now, before)


def test_the_torso_takes_the_stem_where_it_engages(monkeypatch):
    """The torso at a whole program of frames with the stem interpreted:
    the features and every parameter's gradient of the torso before the
    stem, to float32's rounding of the stem's sums against ``nn.Conv``'s."""
    x = jax.random.uniform(jax.random.PRNGKey(1), (2 * FRAMES, 8, 8, 1))
    torso, before = (network.ImpalaTorso(out_dim=32),
                     TorsoBefore(out_dim=32))
    params = torso.init(jax.random.PRNGKey(0), x)

    def grad(module):
        return jax.grad(lambda p: jnp.sum(module.apply(p, x)))(params)

    want_out, want_grad = before.apply(params, x), grad(before)
    monkeypatch.setattr(stem, "conv_pool", functools.partial(
        stem.conv_pool, interpret=True))
    jaxpr = str(jax.make_jaxpr(lambda: grad(torso))())
    assert "stem_forward" in jaxpr and "stem_backward" in jaxpr
    np.testing.assert_allclose(np.asarray(torso.apply(params, x)),
                               np.asarray(want_out), rtol=1e-5, atol=1e-6)
    for u, v in zip(jax.tree.leaves(grad(torso)), jax.tree.leaves(want_grad)):
        np.testing.assert_allclose(np.asarray(u), np.asarray(v), rtol=1e-4,
                                   atol=1e-5)


def test_the_stem_counter_is_fixed_by_backend_and_frames(monkeypatch):
    """``torso.stem_fused``: 1 where the train step's frames take the
    kernels (a TPU backend, the IMPALA cell's 64 x 120 frames), 0 on the
    CPU, and no counter where the torso has no stem."""
    cfg = impala_deep_config()
    assert cfg.batch_size * cfg.seq_len % pool.LANES == 0
    assert network.stem_fused(cfg) == 0.0
    assert network.stem_fused(pong_config()) is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert network.stem_fused(cfg) == 1.0
    assert network.stem_fused(cfg.replace(batch_size=63)) == 0.0
