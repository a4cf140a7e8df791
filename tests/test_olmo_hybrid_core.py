"""The ``olmo_hybrid`` memory core (models/olmo_hybrid.py): the delta rule's
chunked form against its one-step form, the core against its plain reference
(benchmark/reference/nature_olmohybrid_l4h4.py) at the small size of the
configuration's own file, in float32 on the CPU; the chip's share adding up
to the uncut layer; and the stream's second kind — snapshots of the part of
a state that is no window of rows — through the fused loop.  The paths the
cores share (the thread fabric, checkpoints, the refusals, the sharding
table) are parametrised over both in tests/test_xing4_core.py."""
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check, flops  # noqa: E402
from benchmark.drivers import train as training  # noqa: E402
from benchmark.reference import nature_olmohybrid_l4h4 as ref  # noqa: E402
from r2d2_tpu.config import olmo_hybrid_core_config  # noqa: E402
from r2d2_tpu.learner.step import loss_and_priorities  # noqa: E402
from r2d2_tpu.models import olmo_hybrid as oh  # noqa: E402
from r2d2_tpu.models import state as state_mod  # noqa: E402
from r2d2_tpu.models.network import (  # noqa: E402
    R2D2Network,
    create_network,
    init_params,
    zero_hidden,
)
from r2d2_tpu.models.state import (  # noqa: E402
    olmo_hybrid_layout,
    state_spec,
    stream_spec,
)

A = 4
NAME = "nature_olmohybrid_l4h4"
with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
    DOC = json.load(f)
# the widths of the file's ``small``, on the tests' 12x12 frames
TINY = dict({k: v for k, v in DOC["small"].items()
             if k.startswith("core_")},
            obs_shape=(12, 12, 1), torso="mlp", obs_space_to_depth=False,
            hidden_dim=16, batch_size=4, burn_in_steps=4, learning_steps=4,
            forward_steps=2, block_length=8, buffer_capacity=160,
            learning_starts=16, num_actors=2, max_episode_steps=50,
            training_steps=8, compute_dtype="float32", remat=False)


def small_cfg(**kw):
    """The configuration at its file's small size (Nature torso, 84x84)."""
    return training.preset_config(DOC, small=True, compute_dtype="float32",
                                  **kw)


def tiny_cfg(**kw):
    return olmo_hybrid_core_config(game="Fake", **dict(TINY, **kw))


def shaken(tree, seed, scale=0.1):
    """Every leaf moved off its initial value, so that no gain is 1 and no
    bias 0."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(treedef, [
        x + scale * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def small():
    cfg = small_cfg()
    net = create_network(cfg, A)
    params = shaken(init_params(cfg, net, jax.random.PRNGKey(1)), 2)
    target = shaken(init_params(cfg, net, jax.random.PRNGKey(3)), 4)
    return cfg, net, params, target, check.seeded_batch(cfg, A, 5)


def rel(a, b):
    return float(np.abs(np.asarray(a) - np.asarray(b)).max()
                 / max(np.abs(np.asarray(b)).max(), 1e-30))


# -------------------------------------------------- the delta rule's forms

def recurrence_inputs(seed, B=2, T=11, h=2, dk=8, dv=16):
    """A window whose keys are nearly alike from step to step, as a game's
    consecutive frames make them, a beta that passes 1, a decay that is
    not 1 and a state that is not zero."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    base = jax.random.normal(ks[0], (B, 1, h, dk))
    k = oh._l2(base + 0.3 * jax.random.normal(ks[1], (B, T, h, dk)))
    q = oh._l2(jax.random.normal(ks[2], (B, T, h, dk))) * dk ** -0.5
    v = jax.random.normal(ks[3], (B, T, h, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[4], (B, T, h)))
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[5], (B, T, h)))
    S0 = 0.3 * jax.random.normal(ks[6], (B, h, dk, dv))
    return q, k, v, g, beta, S0


def stepped(q, k, v, g, beta, S):
    out = []
    for t in range(q.shape[1]):
        o, S = oh.delta_rule_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                  beta[:, t], S)
        out.append(o)
    return jnp.stack(out, axis=1), S


@pytest.mark.parametrize("chunk", [4, 8, 32])
def test_the_chunked_form_is_the_one_step_form_stepped(chunk):
    """Values and every gradient, over a window of 11 steps that is no
    multiple of the chunk (4, 8) and one that is a part of one (32), from a
    non-zero state."""
    args = recurrence_inputs(0)
    w_o = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    w_s = jax.random.normal(jax.random.PRNGKey(10), args[5].shape)

    def scalar(form):
        def f(*a):
            o, S = form(*a)
            return (o * w_o).sum() + (S * w_s).sum()
        return f

    def chunked(*a):
        return oh.delta_rule_chunked(*a, jnp.float32, chunk)

    for got, want in zip(chunked(*args), stepped(*args)):
        assert rel(got, want) < 1e-5
    got = jax.grad(scalar(chunked), argnums=range(6))(*args)
    want = jax.grad(scalar(stepped), argnums=range(6))(*args)
    for a, b in zip(got, want):
        assert rel(a, b) < 1e-5


def test_the_inverse_is_exact_where_the_nilpotent_product_is_not():
    """Keys all alike and beta 1 make A the strictly lower matrix of ones,
    whose (I + A)^-1 is the bidiagonal I - shift; forward substitution
    gives it to the digit at C = 32, where A^16's entries pass 1e8."""
    C = 32
    A_ = jnp.tril(jnp.ones((C, C), jnp.float32), -1)
    want = np.eye(C) - np.eye(C, k=-1)
    np.testing.assert_allclose(oh.unit_lower_inverse(A_[None])[0], want,
                               atol=1e-6)
    assert float(jnp.linalg.matrix_power(A_, 16).max()) > 1e8
    # and its gradient is the inverse's: d tr(W T) / dA = -(T W T)^T
    W = jax.random.normal(jax.random.PRNGKey(0), (C, C))
    A_ = 0.2 * jnp.tril(jax.random.normal(jax.random.PRNGKey(1), (C, C)), -1)
    g = jax.grad(lambda a: (oh.unit_lower_inverse(a) * W).sum())(A_)
    T = np.linalg.inv(np.eye(C) + np.asarray(A_, np.float64))
    np.testing.assert_allclose(g, np.tril(-(T.T @ np.asarray(W) @ T.T), -1),
                               atol=1e-4)


def linear_layer(cfg, seed):
    """One linear layer's parameters, shaken, with inputs and a state."""
    p = jax.tree.map(lambda v: v[0, 0], oh.init_periods(
        jax.random.PRNGKey(seed), cfg, jnp.float32)["linear"])
    p = shaken(p, seed + 1)
    B, T = 2, 11
    h, dk, dv = (cfg.core_heads_held, cfg.core_linear_key_dim,
                 cfg.core_linear_value_dim)
    ks = jax.random.split(jax.random.PRNGKey(seed + 2), 3)
    x = jax.random.normal(ks[0], (B, T, cfg.core_dim))
    S = 0.3 * jax.random.normal(ks[1], (B, h, dk, dv))
    tail = jax.random.normal(ks[2], (B, 3, h * (2 * dk + dv)))
    return p, x, S, tail


def test_a_linear_layer_through_its_chunks_is_the_layer_stepped():
    """The whole mixer — projections, convolution over the stored tail,
    norms, gates, recurrence — over a window in chunks of 4 against T = 1
    calls through the state, with every parameter's gradient."""
    cfg = tiny_cfg()
    p, x, S, tail = linear_layer(cfg, 0)
    w = jax.random.normal(jax.random.PRNGKey(5), x.shape)

    def window(p):
        y, S1, tail1, _ = oh.linear_attention(cfg, p, x, S, tail,
                                              jnp.float32, chunk=4)
        return (y * w).sum() + S1.sum() + tail1.sum(), (y, S1, tail1)

    def steps(p):
        ys, S1, tail1 = [], S, tail
        for t in range(x.shape[1]):
            y, S1, tail1, _ = oh.linear_attention(
                cfg, p, x[:, t:t + 1], S1, tail1, jnp.float32)
            ys.append(y)
        y = jnp.concatenate(ys, axis=1)
        return (y * w).sum() + S1.sum() + tail1.sum(), (y, S1, tail1)

    (_, got), g_got = jax.value_and_grad(window, has_aux=True)(p)
    (_, want), g_want = jax.value_and_grad(steps, has_aux=True)(p)
    for a, b in zip(got, want):
        assert rel(a, b) < 1e-5
    for name in set(p) - {"attn_norm", "ffn_norm", "mlp"}:   # the mixer's
        assert rel(g_got[name], g_want[name]) < 1e-5, name
        assert float(jnp.abs(g_want[name]).max()) > 0, name


def test_beta_spans_0_to_2_and_a_transition_can_flip_its_key():
    """``linear_allow_neg_eigval``: beta = 2 sigmoid(.), so the
    transition's eigenvalue along its key, alpha (1 - beta), is negative
    once beta passes 1 — one step with beta = 2 and alpha = 1 mirrors what
    S holds along k."""
    cfg = tiny_cfg()
    p, x, S, tail = linear_layer(cfg, 3)
    *_, stats = oh.linear_attention(cfg, p, 5.0 * x, S, tail, jnp.float32)
    assert 0.0 < float(stats[2]) < 2.0          # the mean beta
    for bias, lo, hi in ((-30.0, 0.0, 1e-6), (30.0, 2.0 - 1e-6, 2.0)):
        big = dict(p, w_b=jnp.zeros_like(p["w_b"]))
        beta = 2.0 * jax.nn.sigmoid(x @ big["w_b"] + bias)
        assert lo <= float(beta.min()) <= float(beta.max()) <= hi
    k = oh._l2(jax.random.normal(jax.random.PRNGKey(0), (1, 1, 8)))
    S0 = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 8, 16))
    zero = jnp.zeros((1, 1))
    _, S1 = oh.delta_rule_step(k, k, jnp.zeros((1, 1, 16)), zero,
                               2.0 + zero, S0)
    along = lambda M: jnp.einsum("bhkv,bhk->bhv", M, k)     # noqa: E731
    np.testing.assert_allclose(along(S1), -along(S0), rtol=1e-4, atol=1e-5)


# ------------------------------------------------- against the reference

def test_unroll_loss_and_every_gradient_match_the_reference(small):
    cfg, net, params, target, batch = small
    q, _ = net.apply(params, batch["obs"], batch["last_action"],
                     batch["last_reward"], batch["hidden"],
                     method=R2D2Network.unroll)
    q_ref = ref.unroll(params, batch["obs"], batch["last_action"],
                       batch["last_reward"], jnp.asarray(batch["hidden"]))
    assert rel(q, q_ref) < 1e-5

    rest = {k: v for k, v in params.items() if k != "params"}

    def program(p):
        return loss_and_priorities(cfg, net, {**rest, "params": p}, target,
                                   batch)[0]

    def reference(p):
        return ref.loss({**rest, "params": p}, target, batch,
                        cfg.forward_steps)[0]

    loss, grads = jax.value_and_grad(program)(params["params"])
    loss_ref, grads_ref = jax.value_and_grad(reference)(params["params"])
    assert abs(float(loss) - float(loss_ref)) < 1e-5 * abs(float(loss_ref))
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    flat_ref = jax.tree.leaves(grads_ref)
    scale = max(float(jnp.abs(g).max()) for g in flat_ref)
    for (path, g), g_ref in zip(flat, flat_ref):
        assert float(jnp.abs(g - g_ref).max()) < 2e-5 * scale, path
        assert float(jnp.abs(g_ref).max()) > 0, path


def test_acting_through_the_state_equals_the_unroll_and_the_reference(small):
    cfg, net, params, _, batch = small
    args = (batch["obs"], batch["last_action"], batch["last_reward"])
    hidden = jnp.asarray(batch["hidden"])
    q, final = net.apply(params, *args, hidden, method=R2D2Network.unroll)
    qs = []
    for t in range(cfg.seq_len):
        q_t, hidden = net.apply(params, *(a[:, t] for a in args), hidden,
                                method=R2D2Network.act)
        qs.append(q_t)
    assert rel(jnp.stack(qs, axis=1), q) < 1e-5
    assert rel(hidden, final) < 1e-5
    q_ref = ref.unroll(params, *args, jnp.asarray(batch["hidden"]))
    assert rel(jnp.stack(qs, axis=1), q_ref) < 1e-5


def test_a_state_cut_from_a_longer_episode_reproduces_its_q_values():
    """The state after step p of an episode, used as a window's stored
    state, gives the Q-values the whole episode's unroll has from p + 1 on:
    the matrices, the convolution tails and the stored keys and values
    carry everything the steps before p matter for (W = 4 < p)."""
    cfg = tiny_cfg()
    net = create_network(cfg, A)
    params = shaken(init_params(cfg, net, jax.random.PRNGKey(0)), 1)
    rng = np.random.default_rng(0)
    B, T, p = 2, 19, 9
    obs = rng.integers(0, 256, (B, T, *cfg.stored_obs_shape), np.uint8)
    la = np.eye(A, dtype=np.float32)[rng.integers(A, size=(B, T))]
    lr = rng.random((B, T)).astype(np.float32)
    whole, _ = net.apply(params, obs, la, lr, zero_hidden(cfg, B),
                         method=R2D2Network.unroll)
    _, cut = net.apply(params, obs[:, :p], la[:, :p], lr[:, :p],
                       zero_hidden(cfg, B), method=R2D2Network.unroll)
    assert float(jnp.abs(cut).max()) > 0
    rest, _ = net.apply(params, obs[:, p:], la[:, p:], lr[:, p:], cut,
                        method=R2D2Network.unroll)
    assert rel(rest, whole[:, p:]) < 1e-5


# ------------------------------------------------------ the chip's share

def head_slices(cfg, heads):
    """A config that holds ``heads`` heads, and the function that cuts a
    linear or a softmax layer's parameters to heads ``lo .. lo + heads``."""
    full = cfg.core_heads_held
    dk, dv, hd = (cfg.core_linear_key_dim, cfg.core_linear_value_dim,
                  cfg.core_head_dim)

    def cut(p, lo):
        def cols(x, width):         # the heads' columns of the last axis
            return x.reshape(x.shape[:-1] + (full, width))[
                ..., lo:lo + heads, :].reshape(x.shape[:-1] + (-1,))

        def rows(x, width):
            return x.reshape((full, width) + x.shape[1:])[
                lo:lo + heads].reshape((-1,) + x.shape[1:])

        out = dict(p)
        if "w_g" in p:
            for name, width in (("w_q", dk), ("w_k", dk), ("w_v", dv),
                                ("w_g", dv), ("conv_q", dk), ("conv_k", dk),
                                ("conv_v", dv), ("w_a", 1), ("w_b", 1),
                                ("A_log", 1), ("dt_bias", 1)):
                out[name] = cols(p[name], width)
            out["w_o"] = rows(p["w_o"], dv)
        else:
            for name in ("w_q", "w_k", "w_v", "q_norm", "k_norm"):
                out[name] = cols(p[name], hd)
            out["w_o"] = rows(p["w_o"], hd)
        return out

    return cfg.replace(core_heads_held=heads), cut


def test_the_linear_layers_head_shares_add_up_to_the_uncut_layer():
    """Every part of the delta-rule mixer is a head's own (projections,
    convolution channels, norms, gates, the matrix, the rows of W_o; the
    output norm's one weight is shared): the shares of heads 0-1 and 2-3
    add up to the layer of all four, exactly."""
    cfg = tiny_cfg(core_heads_held=4)
    p, x, S, tail = linear_layer(cfg, 7)
    dk, dv = cfg.core_linear_key_dim, cfg.core_linear_value_dim
    whole, S1, tail1, _ = oh.linear_attention(cfg, p, x, S, tail,
                                              jnp.float32)
    part_cfg, cut = head_slices(cfg, 2)
    total = 0.0
    for lo in (0, 2):
        tails = tail.reshape(2, 3, -1)
        mine = jnp.concatenate([
            tail[..., :4 * dk].reshape(2, 3, 4, dk)[:, :, lo:lo + 2]
            .reshape(2, 3, -1),
            tail[..., 4 * dk:8 * dk].reshape(2, 3, 4, dk)[:, :, lo:lo + 2]
            .reshape(2, 3, -1),
            tail[..., 8 * dk:].reshape(2, 3, 4, dv)[:, :, lo:lo + 2]
            .reshape(2, 3, -1)], axis=-1)
        assert tails.shape[-1] == 2 * mine.shape[-1]
        y, S_part, _, _ = oh.linear_attention(
            part_cfg, cut(p, lo), x, S[:, lo:lo + 2], mine, jnp.float32)
        assert rel(S_part, S1[:, lo:lo + 2]) < 1e-5
        total = total + y
    assert rel(total, whole) < 1e-5


def test_the_softmax_layers_head_shares_add_up_given_the_groups_norm():
    """The QK-norm spans the whole projection, so a share needs the
    group's mean squares — the one exchange a mesh axis over the heads
    would add; handed them, the shares add up to the uncut layer, and
    without them (its own heads' mean square) a share is another function."""
    cfg = tiny_cfg(core_heads_held=4)
    p = shaken(jax.tree.map(lambda v: v[0], oh.init_periods(
        jax.random.PRNGKey(0), cfg, jnp.float32)["full"]), 1)
    B, T, W, hd = 2, 7, cfg.core_context, cfg.core_head_dim
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    x = jax.random.normal(ks[0], (B, T, cfg.core_dim))
    cache = jax.random.normal(ks[1], (B, W, 2 * 4 * hd))
    whole, _ = oh.softmax_attention(cfg, p, x, cache, jnp.float32)
    q_ms = jnp.mean((x @ p["w_q"]) ** 2, axis=-1, keepdims=True)
    k_ms = jnp.mean((x @ p["w_k"]) ** 2, axis=-1, keepdims=True)
    part_cfg, cut = head_slices(cfg, 2)
    total = alone = 0.0
    for lo in (0, 2):
        mine = cache.reshape(B, W, 2, 4, hd)[:, :, :, lo:lo + 2].reshape(
            B, W, -1)
        total = total + oh.softmax_attention(
            part_cfg, cut(p, lo), x, mine, jnp.float32, q_ms, k_ms)[0]
        alone = alone + oh.softmax_attention(
            part_cfg, cut(p, lo), x, mine, jnp.float32)[0]
    assert rel(total, whole) < 1e-5
    assert rel(alone, whole) > 1e-3


# ---------------------------------------------- the state and its stream

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_state_spec_is_one_folded_vector_of_the_layouts_parts(dtype):
    cfg = tiny_cfg(compute_dtype=dtype)
    parts = olmo_hybrid_layout(cfg)
    assert parts == dict(delta=(3, 2, 16, 32), conv=(3, 3, 2 * (32 + 32)),
                         rows=(4, 1, 2 * 2 * 16))
    shape, np_dtype = state_spec(cfg)
    # 4,224 values in 3 tiles of 16 rows of 128, then 256 in one
    assert shape == (48 + 16, 128) and np_dtype.name == dtype
    assert stream_spec(cfg) == (3, (64,), np_dtype, (48, 128))
    # pack and unpack are each other's inverse, part by part, and what
    # pads a part to whole tiles comes back as zeros
    state = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 128))
    delta, conv, rows = oh.unpack(cfg, state)
    assert (delta.shape, conv.shape, rows.shape) == tuple(
        (2,) + parts[k] for k in ("delta", "conv", "rows"))
    again = oh.pack(cfg, delta, conv, rows, jnp.float32)
    flat, back = state.reshape(2, -1), again.reshape(2, -1)
    np.testing.assert_array_equal(back[:, :4224], flat[:, :4224])
    assert not back[:, 4224:48 * 128].any()
    np.testing.assert_array_equal(back[:, 48 * 128:48 * 128 + 256],
                                  flat[:, 48 * 128:48 * 128 + 256])
    assert not back[:, 48 * 128 + 256:].any()
    # the published widths: 602 KB a sequence in bfloat16, 470 KB of them
    # the part kept whole
    full = training.config_from_file(DOC["config"])
    (rows, lanes), stored = state_spec(full)
    assert 221_184 + 13_824 + 65_536 == 300_544
    assert (rows, lanes) == (1840 + 512, 128)       # 235,008 -> 115 tiles
    assert rows * lanes * stored.itemsize == 602_112
    spec = stream_spec(full)
    assert spec.snapshot == (1840, 128)
    assert spec.entry == (1024,) and spec.history == 63


@pytest.mark.parametrize("sizes", [
    {}, dict(core_linear_key_dim=6, core_linear_value_dim=12,
             core_head_dim=6)], ids=["whole_rows", "padded"])
def test_the_stream_functions_take_a_state_apart_and_put_it_together(sizes):
    """rows + snapshots = the state: the newest row and the snapshot part
    of a state, kept as the fused loop keeps them, give the state back."""
    cfg = tiny_cfg(**sizes)
    hist, entry, _, snap_shape = stream_spec(cfg)
    parts = olmo_hybrid_layout(cfg)
    n_snap = int(np.prod(parts["delta"]) + np.prod(parts["conv"]))
    rng = np.random.default_rng(0)
    W, steps = cfg.core_context, 9

    def folded(flat):
        flat = np.pad(flat, (0, -len(flat) % (16 * 128)))
        return flat.reshape(-1, 128)

    # a lane's states over 9 steps whose rows parts are windows of one
    # stream of entries
    entries = rng.normal(size=(hist + steps,) + entry).astype(np.float32)
    snaps = np.stack([folded(rng.normal(size=n_snap).astype(np.float32))
                      for _ in range(steps)])
    assert snaps.shape[1:] == snap_shape
    states = np.stack([np.concatenate(
        [snaps[t], folded(entries[t:t + W].reshape(-1))])
        for t in range(steps)])
    assert states.shape[1:] == state_spec(cfg)[0]
    np.testing.assert_array_equal(
        state_mod.stream_entry(cfg, jnp.asarray(states)), entries[hist:])
    np.testing.assert_array_equal(
        state_mod.stream_snapshot(cfg, jnp.asarray(states)), snaps)
    idx = jnp.asarray([0, 3, 8])
    np.testing.assert_array_equal(
        state_mod.stream_states(cfg, jnp.asarray(entries), idx,
                                jnp.asarray(snaps[np.asarray(idx)])),
        states[np.asarray(idx)])
    # a reset zeroes a lane's first row and its history, whatever an
    # entry's shape
    stream = jnp.ones((3, hist + steps) + entry)
    out = state_mod.reset_stream(cfg, stream, jnp.asarray([True, False,
                                                           True]))
    assert not out[0, :hist + 1].any() and out[0, hist + 1:].all()
    assert out[1].all() and not out[2, :hist + 1].any()


@pytest.mark.parametrize("burn_in", [4, 6])
def test_the_fused_loop_cuts_the_states_the_host_cutter_cuts(burn_in):
    """The fused loop keeps, a lane, one row of keys and values a step and
    K + 1 snapshots of the matrices and tails; the states it writes into
    the ring are those the host's cutter takes from a whole state kept at
    every step — over episodes' first blocks (prefix 0), blocks after a
    carry-over (prefix burn_in), short last blocks and resets.  burn_in =
    learning_steps puts two sequences' starts on row 0; burn_in 6 puts
    starts between the rows a block's sequences begin at."""
    from r2d2_tpu.envs.anakin import AnakinFakeEnv
    from r2d2_tpu.learner.anakin import make_anakin_state, make_debug_rollout
    from r2d2_tpu.replay.block import LocalBuffer
    from r2d2_tpu.replay.device_ring import DeviceRing

    cfg = tiny_cfg(actor_transport="anakin", device_replay=True,
                   in_graph_per=True, num_actors=3, anakin_episode_len=21,
                   buffer_capacity=30 * 8, burn_in_steps=burn_in)
    N, K = cfg.num_actors, cfg.seqs_per_block
    net = create_network(cfg, A)
    params = shaken(init_params(cfg, net, jax.random.PRNGKey(0)), 1)
    ring = DeviceRing(cfg, A)
    env = AnakinFakeEnv(obs_shape=cfg.stored_obs_shape, action_dim=A,
                        episode_len=cfg.anakin_episode_len, num_lanes=N)
    ast = make_anakin_state(cfg, A, env, jax.random.PRNGKey(11))
    spec = stream_spec(cfg)
    assert ast["buf_hidden"].shape == (
        N, cfg.max_block_steps + spec.history) + spec.entry
    assert ast["buf_snapshot"].shape == (N, K + 2) + spec.snapshot
    init_obs = np.asarray(ast["obs"])
    T = 60
    meta0 = ring.per_meta()
    (_, arrays, *_), tr = make_debug_rollout(cfg, net, env, A, T)(
        params, ast, ring.snapshot(), ring.take_prios(),
        meta0["seq_meta"], meta0["first"])
    tr, arrays = jax.device_get(tr), jax.device_get(arrays)
    lbs = [LocalBuffer(cfg, A) for _ in range(N)]
    for i in range(N):
        lbs[i].reset(init_obs[i])
    blocks, kinds = [], set()
    for t in range(T):
        for i in range(N):
            if tr["pending"][t][i]:
                kinds.add(("boundary", lbs[i].curr_burn_in_steps > 0))
                blocks.append(lbs[i].finish(tr["q"][t][i])[0])
        for i in range(N):
            lbs[i].add(int(tr["actions"][t][i]), float(tr["reward"][t][i]),
                       tr["obs_step"][t][i], tr["q"][t][i],
                       tr["hidden"][t][i])
        for i in range(N):
            if tr["truncated"][t][i]:
                kinds.add(("episode_end", lbs[i].curr_burn_in_steps > 0))
                blocks.append(lbs[i].finish(None)[0])
                lbs[i].reset(tr["obs_next"][t][i])
    # a first block cut at its boundary, one after a carry-over cut at its
    # boundary, and a short one cut by the episode's end after a carry-over
    assert kinds >= {("boundary", False), ("boundary", True),
                     ("episode_end", True)}
    assert 8 < len(blocks) <= cfg.num_blocks
    snap = spec.snapshot[0]
    whole = rows = 0
    for slot, blk in enumerate(blocks):
        k = blk.num_sequences
        np.testing.assert_array_equal(blk.hidden, arrays["hidden"][slot][:k])
        assert not arrays["hidden"][slot][k:].any()
        whole += int(np.abs(blk.hidden[:, :snap]).sum() > 0)
        rows += int(np.abs(blk.hidden[:, snap:]).sum() > 0)
    assert whole > len(blocks) // 2 and rows > len(blocks) // 2


def test_snapshot_positions_are_the_cutters_sequence_starts():
    """A sequence's burn-in can start at the episode steps m L - burn_in
    and at 0 only.  A block keeps those of its own K sequences and those
    that lie in the rows it hands the next block as warm prefix: with
    burn_in >= L that is more than one (the next block's second sequence
    starts its burn-in at or before this block's last state)."""
    from r2d2_tpu.learner.anakin import (
        _snapshot_positions,
        _snapshot_slots,
    )

    for burn_in, carried in ((2, 1), (4, 2), (6, 2), (8, 3)):
        cfg = tiny_cfg(burn_in_steps=burn_in)
        L, K, BL = cfg.learning_steps, cfg.seqs_per_block, cfg.block_length
        assert _snapshot_slots(cfg) == (K + carried, carried)
        pos = np.asarray(_snapshot_positions(cfg, jnp.asarray([0, burn_in])))
        for c, row in zip((0, burn_in), pos):
            assert list(row) == [max(0, c + m * L - burn_in)
                                 for m in range(K + carried)]
            # the slots handed on are, seen from the next block (whose row
            # 0 is this block's row c + BL - burn_in), its first sequences'
            lo = c + BL - burn_in
            assert list(row[K:] - lo) == [m * L for m in range(carried)]
            assert row[-1] <= c + BL
        # an episode's first block starts as many sequences on row 0
        assert (pos[0] == 0).sum() == carried
    with pytest.raises(ValueError, match="burn_in_steps <= block_length"):
        from r2d2_tpu.envs.anakin import make_anakin_env
        from r2d2_tpu.learner.anakin import make_anakin_state

        bad = tiny_cfg(burn_in_steps=12, actor_transport="anakin",
                       device_replay=True, in_graph_per=True)
        make_anakin_state(bad, A, make_anakin_env(bad, A),
                          jax.random.PRNGKey(0))


# -------------------------------------------- through the program's paths

def test_the_cores_constants_are_the_sources():
    """What the modules keep as constants is what the configuration's file
    carries under config.json's own keys, and the file's program fields
    are its catalog keys."""
    assert oh.RMS_NORM_EPS == DOC["rms_norm_eps"]
    assert oh.LINEAR_ALLOW_NEG_EIGVAL is DOC["linear_allow_neg_eigval"]
    assert state_mod.LINEAR_CONV_KERNEL_DIM == DOC["linear_conv_kernel_dim"]
    period = list(state_mod.LAYER_TYPES_PERIOD)
    assert DOC["layer_types"] == period * (len(DOC["layer_types"]) // 4)
    assert len(DOC["layer_types"]) == DOC["published"]["num_hidden_layers"]
    assert DOC["rope_parameters"] == {"rope_theta": None}
    assert DOC["hidden_act"] == "silu" and DOC["attention_bias"] is False
    full = training.config_from_file(DOC["config"])
    assert (full.core_dim, full.core_dense_dim, full.core_layers) == (
        DOC["hidden_size"], DOC["intermediate_size"],
        DOC["num_hidden_layers"])
    assert full.core_heads_held == DOC["num_attention_heads"] == DOC[
        "num_key_value_heads"] == DOC["linear_num_key_heads"] == DOC[
        "linear_num_value_heads"]
    assert (full.core_linear_key_dim, full.core_linear_value_dim) == (
        DOC["linear_key_head_dim"], DOC["linear_value_head_dim"])
    published = DOC["published"]
    assert full.core_head_dim == DOC["hidden_size"] // published[
        "num_attention_heads"]
    whole = training.preset_config(DOC)
    assert (whole.core_layers, whole.core_heads_held) == (
        published["num_hidden_layers"], published["num_attention_heads"])


def test_the_new_metrics_are_files_the_harness_resolves():
    from benchmark import readers
    from benchmark.manifest import Manifest

    cell = Manifest().cell(NAME + ".anakin")
    specs = {s["name"]: s for s in cell.per_layer}
    for name, scope in (("linear_attention_device_share", "linear_attention"),
                        ("delta_rule_device_share", "delta_rule"),
                        ("softmax_attention_device_share",
                         "softmax_attention"),
                        ("mlp_device_share", "mlp"),
                        ("state_snapshot_device_share", "state_snapshot")):
        assert specs[name]["kind"] == "scope_anywhere"
        assert specs[name]["scopes"] == [scope]
        assert callable(readers.resolve(specs[name]))
    # the xing4 cell's own shares are not this cell's, and the metrics
    # that list no cells are
    assert "attention_device_share" not in specs
    assert {"step_device_ms", "train_mfu", "core_device_share"} <= set(specs)


def test_the_scopes_are_on_the_paths_of_the_lowered_step():
    """Every scope the core's metrics read stands on an operation's path
    in the compiled train step."""
    from r2d2_tpu.learner.step import create_train_state, make_train_step

    cfg = tiny_cfg()
    net = create_network(cfg, A)
    state = create_train_state(cfg, init_params(cfg, net,
                                                jax.random.PRNGKey(0)))
    text = jax.jit(make_train_step(cfg, net)).lower(
        state, check.seeded_batch(cfg, A, 1)).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("linear_attention", "delta_rule", "softmax_attention",
                  "mlp"):
        assert any(f"/{scope}/" in path for path in paths), scope
    assert any("/linear_attention/delta_rule/" in path for path in paths)
    # forward under jvp(...), backward under transpose(jvp(...)), and the
    # target network's pass under its own scope
    mlp = [path for path in paths if "/mlp/" in path]
    assert any("transpose(jvp(" in path for path in mlp)
    assert any("target_forward" in path for path in mlp)


def test_model_flops_count_what_this_chip_multiplies():
    cfg = training.config_from_file(DOC["config"])
    d, f, h = 3840, 11008, 4
    linear = (d * h * (96 + 96 + 192 + 192) + h * 192 * d + 2 * d * h
              + 4 * h * (96 + 96 + 192) + 3 * h * 96 * 192)
    full = 4 * d * h * 128 + 2 * h * 128 * 65
    assert (linear, full) == (12_054_528, 7_930_880)
    want = (6_885_376 + 517 * d + 3 * linear + full + 4 * 3 * d * f
            + 2 * d * 512 + 512 * 4 + 512)
    assert flops.step_macs(NAME, cfg, A) == want == 564_148_480
    assert flops.train_flops_per_update(NAME, cfg, A) == 8 * want * 64 * 85
    # the feed-forward is whole where the mixers are 4 heads of 30: 90 % of
    # the multiply-adds here, 61 % in the whole period
    mlp = 4 * 3 * d * f
    assert 0.895 < mlp / want < 0.905
    whole = training.preset_config(DOC, core_layers=4)
    assert 0.59 < mlp / flops.step_macs(NAME, whole, A) < 0.62
