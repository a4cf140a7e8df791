"""The residual streams' kernels (ops/streams.py) against the plain
expressions of models/xing4.py, which stay their definition: a whole block
— attention sublayer, feed-forward sublayer — through ``xing4.block``,
once by the expressions and once by the kernels interpreted on the CPU,
its outputs and every gradient; which shapes take which path; and the
counter that says so."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.models import xing4
from r2d2_tpu.models.network import R2D2Network, create_network, init_params
from r2d2_tpu.ops import streams
from test_xing4_core import A, check, shaken, tiny_cfg


def rel(a, b, scale=None):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / max(scale or np.abs(b).max(), 1e-30))


def block_and_gradients(cfg, p, X, cache, bias, G):
    """(streams, load, gradients of a fixed contraction of the block's
    results by its parameters and by the streams)."""
    cd = jnp.dtype(cfg.compute_dtype)

    def loss(p, X):
        out, cache2, load, _ = xing4.block(cfg, p, X, cache, bias, cd)
        return (sum(jnp.sum(o.astype(jnp.float32) * g)
                    for o, g in zip(out, G))
                + jnp.sum(cache2.astype(jnp.float32)), (out, load))

    (_, (out, load)), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True))(p, X)
    return out, load, grads


# (feed-forward, compute type, lanes, width, one stream four times), 40
# steps a lane: 4 lanes are 160 tokens, a tile and a quarter; 16 are 640
CASES = [
    ("dense", "float32", 16, 128, False),   # 640 tokens: five whole tiles
    ("routed", "float32", 4, 128, False),   # 160: the second tile a quarter
    ("dense", "bfloat16", 4, 256, False),
    ("routed", "bfloat16", 16, 128, False),
    ("dense", "float32", 4, 128, True),     # the first sublayer's streams
    ("routed", "bfloat16", 4, 128, True),
]


@pytest.mark.parametrize(
    "feed,dtype,lanes,width,repeated", CASES,
    ids=["-".join(map(str, c)) for c in CASES])
def test_a_block_by_the_kernels_equals_the_block_by_the_expressions(
        feed, dtype, lanes, width, repeated):
    """Attention sublayer (read; its write and the next read in one
    launch) and feed-forward sublayer (u in the compute type before a
    dense one, in float32 before a routed one; the closing write):
    the streams out, the loads, and the gradient of every parameter — the
    maps' ``phi_*``, ``alpha``, ``b_*``, the norms' gains, and through
    ``y`` the sublayers' own — and of the streams."""
    steps, dense = 40, feed == "dense"
    cd = jnp.dtype(dtype)
    plain = tiny_cfg(core_dim=width, compute_dtype=dtype, batch_size=lanes)
    fused = plain.replace(pallas_interpret=True)
    N = lanes * steps
    assert (N % streams.TILE == 0) == (lanes == 16)
    assert xing4.streams_fused(fused, N) and not xing4.streams_fused(plain, N)

    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 8)
    p = jax.tree.map(lambda x: x[0], shaken(
        xing4.init_blocks(key, plain, 1, dense, jnp.float32), 1))
    if repeated:
        X = (jax.random.normal(ks[0], (N, width)).astype(cd),) * 4
    else:
        X = tuple(jax.random.normal(k, (N, width)).astype(cd)
                  for k in ks[:4])
    cache = (0.5 * jax.random.normal(ks[4], (
        lanes, plain.core_context, xing4.latent_dim(plain)))).astype(cd)
    bias = None if dense else 0.1 * jax.random.normal(
        ks[5], (plain.core_experts,))
    G = tuple(jax.random.normal(k, (N, width))
              for k in jax.random.split(ks[6], 4))

    want, load, (gp, gx) = block_and_gradients(plain, p, X, cache, bias, G)
    got, load_k, (gp_k, gx_k) = block_and_gradients(fused, p, X, cache,
                                                    bias, G)
    # float32: the same arithmetic in another order.  bfloat16: the
    # streams are rounded at the same points, so they differ by a last
    # place here and there; the expressions round each of a stream's three
    # cotangents to bfloat16 before they are summed, the kernel their sum
    tol_out, tol_grad = (1e-5, 1e-4) if dtype == "float32" else (1e-2, 3e-2)
    np.testing.assert_array_equal(load_k, load)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype == cd and rel(a, b) < tol_out
    scale = max(float(jnp.abs(g).max()) for g in gx)
    assert max(rel(a, b, scale) for a, b in zip(gx_k, gx)) < tol_grad
    # an error is measured against the largest gradient of the leaf's own
    # group: with one stream four times, u does not depend on the scale of
    # the read and ``b_pre``'s gradient is rounding on both sides
    for group in gp:
        scale = max(float(jnp.abs(g).max()) for g in jax.tree.leaves(
            gp[group]))
        for (path, b), a in zip(jax.tree_util.tree_leaves_with_path(
                gp[group]), jax.tree.leaves(gp_k[group])):
            assert a.shape == b.shape and a.dtype == b.dtype
            assert rel(a, b, scale) < tol_grad, (
                group, jax.tree_util.keystr(path))


@pytest.mark.parametrize("tokens,width,interpret,fused", [
    (5440, 3584, True, True),      # the cell's train step, interpreted
    (5440, 3584, False, False),    # the same shapes traced for the CPU
    (64, 3584, True, False),       # acting: under one tile of tokens
    (5440, 3600, True, False),     # a width that is no whole lanes
    (160, 32, True, False),        # the tests' own width
])
def test_the_shapes_choose_the_path(tokens, width, interpret, fused):
    cfg = tiny_cfg(core_dim=width, pallas_interpret=interpret)
    assert jax.default_backend() == "cpu"
    assert xing4.streams_fused(cfg, tokens) == fused


def test_the_counter_says_which_path_the_online_pass_took():
    """``stream_passes_fused`` is sown by the pass itself and written by
    ``step_buffers`` beside the routed experts' counters."""
    for interpret in (False, True):
        cfg = tiny_cfg(core_dim=128, core_layers=2, core_dense_layers=1,
                       burn_in_steps=16, learning_steps=16, block_length=32,
                       pallas_interpret=interpret)
        net = create_network(cfg, A)
        variables = init_params(cfg, net, jax.random.PRNGKey(0))
        batch = check.seeded_batch(cfg, A, 5)
        assert batch["obs"].shape[0] * batch["obs"].shape[1] >= streams.TILE
        _, sown = net.apply(
            variables, batch["obs"], batch["last_action"],
            batch["last_reward"], batch["hidden"],
            method=R2D2Network.unroll, mutable=["stats"])
        stats = sown["stats"]
        assert float(stats["core"]["stream_passes_fused"]) == interpret
        new = xing4.step_buffers(cfg, variables["buffers"], stats)
        counters = dict(zip(xing4.COUNTERS, np.asarray(
            new["core"]["counters"])))
        assert counters["stream_passes_fused"] == interpret
