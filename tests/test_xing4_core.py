"""The ``xing4`` memory core (models/xing4.py) against its plain reference
(benchmark/reference/nature_xing4_l5e8h4.py), at the small size of the
configuration's own file, in float32 on the CPU; the chip's share adding up
to the uncut layer; and the core through the program's normal paths: the
state's shape derived everywhere, the thread fabric, the fused loop,
checkpoints and eval."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check  # noqa: E402
from benchmark.drivers import train as training  # noqa: E402
from benchmark.reference import nature_xing4_l5e8h4 as ref  # noqa: E402
from r2d2_tpu.config import (  # noqa: E402
    impala_deep_config,
    pong_config,
    test_config as make_test_config,
    xing4_core_config,
)
from r2d2_tpu.learner.step import (  # noqa: E402
    create_train_state,
    loss_and_priorities,
    make_train_step,
)
from r2d2_tpu.models import xing4  # noqa: E402
from r2d2_tpu.models.network import (  # noqa: E402
    R2D2Network,
    create_network,
    init_params,
    zero_hidden,
)
from r2d2_tpu.models.state import state_spec, stream_spec  # noqa: E402

A = 4
NAME = "nature_xing4_l5e8h4"
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
    return xing4_core_config(game="Fake", **dict(TINY, **kw))


def olmo_tiny_cfg(**kw):
    from test_olmo_hybrid_core import tiny_cfg as make

    return make(**kw)


# the cores that are modules of their own, at the tests' widths: what the
# program's paths do with a state that is not the LSTM's
TINY_CFGS = {"xing4": tiny_cfg, "olmo_hybrid": olmo_tiny_cfg}


def shaken(tree, seed, scale=0.1):
    """Every leaf moved off its initial value, so that no gain is 1, no
    bias 0 and no two streams alike."""
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
    flat = jax.tree_util.tree_leaves_with_path(grads)
    flat_ref = jax.tree.leaves(grads_ref)
    largest = max(float(jnp.abs(g).max()) for g in flat_ref)
    assert len(flat) == len(flat_ref) > 40
    for (path, g), g_ref in zip(flat, flat_ref):
        assert float(jnp.abs(g_ref).max()) > 0, path    # every leaf is used
        err = float(jnp.abs(g - g_ref).max())
        assert err < 1e-5 * max(float(jnp.abs(g_ref).max()),
                                1e-2 * largest), (path, err)


def test_acting_through_the_cache_equals_the_unroll_and_the_reference(small):
    cfg, net, params, _, batch = small
    args = (batch["obs"], batch["last_action"], batch["last_reward"])
    q, hidden_end = net.apply(params, *args, batch["hidden"],
                              method=R2D2Network.unroll)
    act = jax.jit(lambda *a: net.apply(params, *a, method=R2D2Network.act))
    hidden, steps = jnp.asarray(batch["hidden"]), []
    for t in range(cfg.seq_len):
        q_t, hidden = act(*(x[:, t] for x in args), hidden)
        steps.append(q_t)
    assert rel(jnp.stack(steps, axis=1), q) < 1e-5
    assert rel(hidden, hidden_end) < 1e-5
    q_ref = ref.unroll(params, *args, jnp.asarray(batch["hidden"]))
    assert rel(jnp.stack(steps, axis=1), q_ref) < 1e-5


def test_a_cache_cut_from_a_longer_episode_reproduces_its_q_values():
    """What the ring stores at a sequence's burn-in start is enough: the
    unroll from the stored cache gives the Q-values acting gave."""
    cfg = tiny_cfg()
    net = create_network(cfg, A)
    params = shaken(init_params(cfg, net, jax.random.PRNGKey(0)), 1)
    rng = np.random.default_rng(0)
    n, T, cut = 30, cfg.seq_len, 13
    obs = rng.integers(0, 256, (2, n, 12, 12, 1), dtype=np.uint8)
    la = np.eye(A, dtype=np.float32)[rng.integers(0, A, (2, n))]
    lr = rng.random((2, n)).astype(np.float32)
    act = jax.jit(lambda *a: net.apply(params, *a, method=R2D2Network.act))
    hidden, qs, stored = zero_hidden(cfg, 2), [], None
    for t in range(n):
        if t == cut:
            stored = hidden
        q_t, hidden = act(obs[:, t], la[:, t], lr[:, t], hidden)
        qs.append(q_t)
    q_window, _ = net.apply(params, obs[:, cut:cut + T], la[:, cut:cut + T],
                            lr[:, cut:cut + T], stored,
                            method=R2D2Network.unroll)
    assert rel(q_window, jnp.stack(qs[cut:cut + T], axis=1)) < 1e-5
    # and the zero cache is the state an episode starts from
    q0, _ = net.apply(params, obs[:, :T], la[:, :T], lr[:, :T],
                      zero_hidden(cfg, 2), method=R2D2Network.unroll)
    assert rel(q0, jnp.stack(qs[:T], axis=1)) < 1e-5


# ------------------------------------------------------------- routing

def moe_params(cfg, seed, experts=None):
    """One expert block's ``moe`` parameters, ``experts`` of them held."""
    held = cfg.replace(core_experts_held=experts or cfg.core_experts_held)
    blocks = xing4.init_blocks(jax.random.PRNGKey(seed), held, 1, False,
                               jnp.float32)
    return jax.tree.map(lambda x: x[0], blocks["moe"])


def test_a_bias_changes_the_choice_and_never_the_weights():
    cfg = tiny_cfg()
    rng = np.random.default_rng(0)
    scores = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(64, 8)), jnp.float32))
    bias = jnp.asarray(rng.normal(size=8), jnp.float32)
    plain, w_plain = xing4.route(cfg, scores, jnp.zeros(8))
    chosen, w = xing4.route(cfg, scores, bias)
    assert (np.sort(plain, 1) != np.sort(chosen, 1)).any()
    # the weights are the chosen experts' own scores, normalised and scaled
    s = np.take_along_axis(np.asarray(scores), np.asarray(chosen), 1)
    np.testing.assert_allclose(
        w, s / s.sum(1, keepdims=True) * xing4.ROUTED_SCALING_FACTOR, rtol=1e-6)
    np.testing.assert_array_equal(
        np.sort(chosen, 1),
        np.sort(np.argsort(-(np.asarray(scores) + np.asarray(bias)),
                           axis=1)[:, :cfg.core_top_k], 1))
    # the same choice gives the same weights whatever bias made it
    same = (np.sort(plain, 1) == np.sort(chosen, 1)).all(1)
    assert same.any()
    np.testing.assert_allclose(np.sort(w_plain[same], 1),
                               np.sort(w[same], 1), rtol=1e-6)


def test_no_routed_pair_is_lost_when_every_token_goes_to_one_held_expert():
    cfg = tiny_cfg()
    p = moe_params(cfg, 0)
    u = jnp.asarray(np.random.default_rng(1).normal(size=(40, cfg.core_dim)),
                    jnp.float32)
    bias = jnp.zeros(cfg.core_experts).at[2].set(10.0)   # expert 2 is held
    out, load = xing4.routed_experts(cfg, p, u, bias, jnp.float32)
    assert float(load[2]) == 40 and float(load.sum()) == 40 * cfg.core_top_k
    hp = ref.hyper_parameters(cfg.core_dim)
    want = ref.experts(hp, p, u, bias, cfg.core_experts_held)
    assert rel(out, want) < 1e-5
    # every token got expert 2's share: dropping it changes every row
    without = ref.experts(hp, p, u, bias.at[2].set(-10.0),
                          cfg.core_experts_held)
    assert (np.abs(np.asarray(out - without)).max(axis=1) > 1e-4).all()


def ladder_case(held, tokens, experts=None):
    """(cfg with ``held`` of ``experts`` experts, 8 for each held unless
    given; one block's parameters; tokens u; a router matrix of zeros):
    with such a router every score is 0.5, so the bias alone chooses,
    whatever the weights of the experts are."""
    cfg = tiny_cfg(core_experts=experts or 8 * held, core_experts_held=held)
    p = dict(moe_params(cfg, 6), w_router=jnp.zeros(
        (cfg.core_dim, cfg.core_experts), jnp.float32))
    u = jnp.asarray(np.random.default_rng(7).normal(
        size=(tokens, cfg.core_dim)), jnp.float32)
    return cfg, p, u


def routed_by(cfg, live, tokens):
    """A (tokens, E) bias under which exactly ``live`` of the tokens * k
    routed pairs fall to held experts: token n sends ``per[n]`` of its k
    pairs to the held experts (the lowest first), the rest to absent
    ones."""
    k, held, E = cfg.core_top_k, cfg.core_experts_held, cfg.core_experts
    per = np.full(tokens, live // tokens)
    per[:live % tokens] += 1
    assert per.max() <= min(k, held) and per.sum() == live
    bias = np.zeros((tokens, E), np.float32)
    for n, c in enumerate(per):
        # staggered, so that the held experts' groups differ in size
        bias[n, (n + np.arange(c)) % held] = 10.0
        bias[n, held + (n + np.arange(k - c)) % (E - held)] = 10.0
    return jnp.asarray(bias)


def layer_and_gradients(cfg, p, u, bias):
    """(out, load, gradient of every parameter and of u) of one expert
    layer under a fixed cotangent."""
    g = jnp.asarray(np.random.default_rng(8).normal(size=u.shape),
                    jnp.float32)

    def f(p, u):
        out, load = xing4.routed_experts(cfg, p, u, bias, jnp.float32)
        return jnp.sum(out * g), (out, load)

    (_, (out, load)), grads = jax.jit(jax.value_and_grad(
        f, argnums=(0, 1), has_aux=True))(p, u)
    return out, load, grads


# one chip of 8 (the cell's share: four rungs), holding 2 experts or 4
LADDERS = [(2, 1024, (256, 512, 1024, 2048)), (4, 384, (256, 512, 768))]


@pytest.mark.parametrize("held,tokens,ladder", LADDERS,
                         ids=["2_of_16", "4_of_32"])
def test_the_ladder_halves_down_to_the_fair_share(held, tokens, ladder):
    cfg = tiny_cfg(core_experts=8 * held, core_experts_held=held)
    assert xing4.row_ladder(cfg, tokens * cfg.core_top_k) == ladder
    # a chip that holds every expert, and a layer too small to halve, lay
    # out every pair: the traced program has no branch
    whole = cfg.replace(core_experts_held=cfg.core_experts)
    assert xing4.row_ladder(whole, 4096) == (4096,)
    assert xing4.row_ladder(cfg, 80) == (80,)
    for c, pairs in ((cfg, 80), (whole, tokens * cfg.core_top_k)):
        _, p, u = ladder_case(c.core_experts_held, pairs // c.core_top_k,
                              c.core_experts)
        text = str(jax.make_jaxpr(lambda u: xing4.routed_experts(
            c, p, u, jnp.zeros(c.core_experts), jnp.float32))(u))
        assert "cond[" not in text
    # the full-width cell: 5,440 tokens, top 4, 8 experts of 64; its 64
    # lanes acting are too few to halve
    full = training.config_from_file(DOC["config"])
    cell = (2816, 5632, 11008, 21760)
    assert xing4.row_ladder(full, 5440 * 4) == cell
    assert xing4.row_ladder(full, 64 * 4) == (256,)
    live = jnp.asarray([0, 2816, 2817, 5632, 5633, 11009, 21760])
    np.testing.assert_array_equal(
        jax.vmap(lambda n: xing4.rung_of(cell, n))(live),
        [0, 0, 1, 1, 2, 3, 3])


def ladder_edges():
    """(held, tokens, ladder, live) at every rung's edges: no live pair,
    R - 1, R, R + 1 for each rung R below the last, and every pair on a
    held expert."""
    for held, tokens, ladder in LADDERS:
        most = tokens * min(TINY["core_top_k"], held)
        lives = {0, most} | {R + e for R in ladder[:-1] for e in (-1, 0, 1)}
        for live in sorted(n for n in lives if n <= most):
            yield pytest.param(held, tokens, ladder, live,
                               id=f"{held}_of_{8 * held}-live_{live}")


@pytest.mark.parametrize("held,tokens,ladder,live", ladder_edges())
def test_every_rung_equals_the_last_rung_alone(held, tokens, ladder, live,
                                               monkeypatch):
    """Whatever rung the live count chooses, the layer's output, its load
    and the gradient of every parameter and of u are those of the N k
    rung alone: no pair is dropped or re-routed at any live count."""
    cfg, p, u = ladder_case(held, tokens)
    assert xing4.row_ladder(cfg, tokens * cfg.core_top_k) == ladder
    bias = routed_by(cfg, live, tokens)
    out, load, grads = layer_and_gradients(cfg, p, u, bias)
    assert float(load[:held].sum()) == live
    assert float(xing4.rows_laid_out(cfg, ladder[-1], load)) == next(
        R for R in ladder if R >= live)
    monkeypatch.setattr(xing4, "row_ladder", lambda cfg, pairs: ladder[-1:])
    want_out, want_load, want = layer_and_gradients(cfg, p, u, bias)
    np.testing.assert_array_equal(load, want_load)
    assert rel(out, want_out) < 1e-6
    flat, flat_want = jax.tree.leaves(grads), jax.tree.leaves(want)
    assert len(flat) == len(flat_want) == 8
    for g, g_want in zip(flat, flat_want):
        assert np.isfinite(np.asarray(g)).all()
        assert rel(g, g_want) < 1e-5
    # and both are the plain reference's
    hp = ref.hyper_parameters(cfg.core_dim)
    assert rel(out, ref.experts(hp, p, u, bias, held)) < 1e-5


def test_the_row_counters_read_what_the_routing_implies():
    cfg = tiny_cfg(core_experts_held=1)
    E, pairs, ladder = cfg.core_experts, 2048, (256, 512, 1024, 2048)
    assert xing4.row_ladder(cfg, pairs) == ladder
    # three expert blocks: 100, 600 and 2,000 of 2,048 pairs on the one
    # held expert, the others' pairs spread over the absent ones
    held = np.array([100., 600., 2000.])
    loads = np.concatenate(
        [held[:, None], np.repeat((pairs - held)[:, None] / (E - 1),
                                  E - 1, axis=1)], axis=1)
    rows = jnp.stack([xing4.rows_laid_out(cfg, pairs, jnp.asarray(load))
                      for load in loads])
    np.testing.assert_array_equal(rows, [256, 1024, 2048])
    counters = dict(zip(xing4.COUNTERS, np.asarray(xing4.load_counters(
        cfg, jnp.zeros((3, E)), jnp.asarray(loads, jnp.float32), rows))))
    assert counters["expert_rows_share"] == pytest.approx(
        (256 + 1024 + 2048) / (3 * pairs))
    assert counters["held_rows_max_share"] == pytest.approx(2000 / pairs)
    assert counters["held_pair_share"] == pytest.approx(2700 / (3 * pairs))
    # a chip that holds every expert lays out every pair
    whole = tiny_cfg(core_experts_held=E)
    assert float(xing4.rows_laid_out(whole, pairs,
                                     jnp.asarray(loads[0]))) == pairs


def test_the_bias_moves_towards_balance_and_takes_no_gradient(small):
    cfg, net, params, target, batch = small
    loads = jnp.asarray([[9., 1., 4., 4., 0., 6., 4., 4.]])
    moved = xing4.bias_update(cfg, jnp.zeros((1, 8)), loads)
    np.testing.assert_allclose(
        moved, cfg.core_bias_rate * np.array([[-1, 1, 0, 0, 1, -1, 0, 0.]]))
    # through the train step: the bias is no gradient leaf (the optimizer
    # leaves it where the balance rule put it) and the target copies it
    state = create_train_state(cfg, params)
    bias0 = np.asarray(params["buffers"]["core"]["router_bias"])
    state, _, _ = jax.jit(make_train_step(cfg, net))(state, batch)
    new = state.params["buffers"]["core"]
    step = np.asarray(new["router_bias"]) - bias0
    assert set(np.unique(np.round(step / cfg.core_bias_rate))) <= {-1, 0, 1}
    assert np.abs(step).max() > 0
    counters = dict(zip(xing4.COUNTERS, np.asarray(new["counters"])))
    assert 0 < counters["held_pair_share"] < 1
    assert counters["held_load_max_over_mean"] >= 1
    assert counters["router_bias_max"] == pytest.approx(
        np.abs(new["router_bias"]).max())
    grads = jax.grad(lambda v: loss_and_priorities(
        cfg, net, v, target, batch)[0])(params)
    assert float(jnp.abs(grads["buffers"]["core"]["router_bias"]).max()) == 0


# ---------------------------------------------------- the share adds up

def test_the_expert_shares_add_up_to_the_uncut_layer():
    """Guide section 4: over the chips that share a layer (here 8 shares of
    one expert each, and 2 of four), the routed parts, with the shared
    expert counted once, sum to the uncut reference layer's output."""
    cfg = tiny_cfg()
    E = cfg.core_experts
    full = moe_params(cfg, 3, experts=E)
    hp = ref.hyper_parameters(cfg.core_dim)
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.normal(size=(24, cfg.core_dim)), jnp.float32)
    bias = jnp.asarray(0.1 * rng.normal(size=E), jnp.float32)
    whole = ref.experts(hp, full, u, bias, E)
    shared = ref.swiglu(u, full["shared"])
    for held in (1, 4):
        share_cfg = cfg.replace(core_experts_held=held)
        total = shared
        for i in range(E // held):
            # chip i holds experts i*held ..: bring them to the front
            order = np.roll(np.arange(E), -i * held)
            p = dict(full, w_router=full["w_router"][:, order],
                     experts={k: v[i * held:(i + 1) * held]
                              for k, v in full["experts"].items()})
            out, _ = xing4.routed_experts(share_cfg, p, u, bias[order],
                                          jnp.float32)
            total = total + (out - shared)
        assert rel(total, whole) < 1e-5, held


def test_the_head_shares_add_up_to_the_uncut_attention():
    cfg = tiny_cfg()
    heads, held = 4, cfg.core_heads_held
    blocks = xing4.init_blocks(jax.random.PRNGKey(5),
                               cfg.replace(core_heads_held=heads), 1, True,
                               jnp.float32)
    full = jax.tree.map(lambda x: x[0], blocks["attn"])
    hp = ref.hyper_parameters(cfg.core_dim)
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.normal(size=(2, 9, cfg.core_dim)), jnp.float32)
    cache = jnp.asarray(0.1 * rng.normal(
        size=(2, cfg.core_context, xing4.latent_dim(cfg))), jnp.float32)
    whole, latents = ref.attention(hp, full, u, cache, heads)
    dq = cfg.core_nope_dim + cfg.core_rope_dim
    dkv = cfg.core_nope_dim + cfg.core_v_dim
    total = 0.0
    for i in range(heads // held):
        h = slice(i * held, (i + 1) * held)
        p = dict(full,
                 w_qb=full["w_qb"].reshape(-1, heads, dq)[:, h].reshape(
                     -1, held * dq),
                 w_kvb=full["w_kvb"].reshape(-1, heads, dkv)[:, h].reshape(
                     -1, held * dkv),
                 w_o=full["w_o"].reshape(heads, cfg.core_v_dim, -1)[h]
                 .reshape(held * cfg.core_v_dim, -1))
        out, new_cache = xing4.attention(cfg, p, u, cache, jnp.float32)
        total = total + out
        assert rel(new_cache, latents) < 1e-5   # every chip caches alike
    assert rel(total, whole) < 1e-5


def test_the_residual_map_is_doubly_stochastic():
    """The published 20 rounds end on a column normalisation: columns sum
    to 1 to 1e-5 and rows, at the initial values, to a part in a thousand
    (a map near a permutation converges slowly); carried on, the same
    iteration reaches 1e-5 on both."""
    cfg = tiny_cfg()
    blocks = xing4.init_blocks(jax.random.PRNGKey(7), cfg, 1, True,
                               jnp.float32)
    mix = jax.tree.map(lambda x: x[0], blocks["attn_mix"])
    X = tuple(jnp.asarray(np.random.default_rng(4).normal(
        size=(cfg.core_streams, 50, cfg.core_dim)), jnp.float32))
    pre, post, res = xing4.stream_maps(cfg, mix, X, jnp.float32)
    assert res.shape == (cfg.core_streams, cfg.core_streams, 50)
    res = jnp.moveaxis(res, -1, 0)              # (tokens, rows, columns)
    np.testing.assert_allclose(res.sum(axis=-2), 1.0, atol=1e-5)
    np.testing.assert_allclose(res.sum(axis=-1), 1.0, atol=5e-3)
    assert float(res.min()) > 0
    assert 0 < float(pre.min()) and float(pre.max()) < 1
    assert 0 < float(post.min()) and float(post.max()) < 2
    on = jnp.moveaxis(xing4.sinkhorn(jnp.moveaxis(res, 0, -1), 2000,
                                     xing4.HC_EPS), -1, 0)
    np.testing.assert_allclose(on.sum(axis=-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(on.sum(axis=-2), 1.0, atol=1e-5)
    # the reference's iteration is the same (rows, then columns)
    np.testing.assert_allclose(
        ref.sinkhorn(jnp.exp(mix["b_res"]), cfg.core_sinkhorn_iters,
                     xing4.HC_EPS),
        xing4.sinkhorn(jnp.exp(mix["b_res"]), cfg.core_sinkhorn_iters,
                       xing4.HC_EPS), rtol=1e-6)


# -------------------------------------------- through the program's paths

@pytest.mark.parametrize("cfg", [
    make_test_config(), make_test_config(lstm_layers=2, hidden_dim=24),
    pong_config(), impala_deep_config()],
    ids=["test", "test_2_layers", "pong", "impala_deep"])
def test_state_spec_is_the_lstm_state_bit_for_bit(cfg):
    shape, dtype = state_spec(cfg)
    assert shape == (2, cfg.lstm_layers, cfg.hidden_dim)
    assert dtype == np.float32
    assert stream_spec(cfg) == (0, shape, dtype, None)
    zero = zero_hidden(cfg, 3)
    assert zero.shape == (3,) + shape and zero.dtype == jnp.float32


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_state_spec_of_the_latent_cache(dtype):
    cfg = tiny_cfg(compute_dtype=dtype)
    shape, np_dtype = state_spec(cfg)
    assert shape == (3, 4, 16 + 4) and np_dtype.name == dtype
    assert stream_spec(cfg) == (3, (3, 20), np_dtype, None)
    full = training.config_from_file(DOC["config"])
    assert state_spec(full)[0] == (5, 64, 576)
    assert state_spec(full)[1].itemsize * int(np.prod(state_spec(full)[0])) \
        == 368640


def env_factory(cfg, seed):
    from r2d2_tpu.envs import FakeAtariEnv

    return FakeAtariEnv(obs_shape=cfg.obs_shape, action_dim=A, seed=seed,
                        episode_len=32)


@pytest.mark.parametrize("drivetrain", ["thread_fabric", "fused_loop"])
@pytest.mark.parametrize("core", ["lstm", "xing4", "olmo_hybrid"])
def test_each_core_trains_through_the_fabric_and_the_fused_loop(
        core, drivetrain):
    """A few updates end to end: host thread actors cutting blocks into the
    device ring, and the fused loop, with each memory core."""
    from r2d2_tpu.train import train

    kw = dict(game_name="Fake", device_replay=True, in_graph_per=True,
              superstep_k=2, log_interval=0.2, save_interval=10 ** 8)
    if drivetrain == "fused_loop":
        kw.update(actor_transport="anakin", anakin_episode_len=12)
    cfg = (TINY_CFGS[core](**kw) if core in TINY_CFGS else
           make_test_config(training_steps=8, **kw))
    m = train(cfg, verbose=False, max_wall_seconds=240,
              **({} if drivetrain == "fused_loop"
                 else dict(env_factory=env_factory)))
    assert m["num_updates"] >= 8 and np.isfinite(m["mean_loss"])
    assert m["buffer_training_steps"] == m["num_updates"]
    assert not m["fabric_failed"]
    assert m["drivetrain"] == ("anakin" if drivetrain == "fused_loop"
                               else m["drivetrain"])
    if core == "xing4" and drivetrain == "fused_loop":
        last = m["logs"][-1]
        assert set(last["core"]) == set(xing4.COUNTERS)
        assert 0 < last["core"]["held_pair_share"] < 1
        for name in xing4.COUNTERS:
            assert last["trace"]["gauge.core." + name] == last["core"][name]
        # a layer too small to halve lays out every pair
        assert last["core"]["expert_rows_share"] == 1
        assert last["core"]["held_pair_share"] <= \
            last["core"]["held_rows_max_share"] <= 1
        # and a width of 32 keeps the streams' plain expressions
        assert last["core"]["stream_passes_fused"] == 0
    if core == "olmo_hybrid" and drivetrain == "fused_loop":
        from r2d2_tpu.models import olmo_hybrid

        # its three counters ride the dispatch's result vector likewise
        last = m["logs"][-1]
        assert set(last["core"]) == set(olmo_hybrid.COUNTERS)
        assert 0 < last["core"]["state_abs_max"] < 100
        assert 0 < last["core"]["decay_mean"] < 1
        assert 0 < last["core"]["beta_mean"] < 2
        for name in olmo_hybrid.COUNTERS:
            assert last["trace"]["gauge.core." + name] == last["core"][name]


def test_the_fused_loop_cuts_the_states_the_host_cutter_cuts():
    """The fused loop keeps one cache ROW a step and a lane, not one cache
    (models/state.stream_spec); the states it writes into the ring are
    those the host's cutter takes from whole per-step states."""
    from r2d2_tpu.envs.anakin import AnakinFakeEnv
    from r2d2_tpu.learner.anakin import make_anakin_state, make_debug_rollout
    from r2d2_tpu.replay.block import LocalBuffer
    from r2d2_tpu.replay.device_ring import DeviceRing

    cfg = tiny_cfg(actor_transport="anakin", device_replay=True,
                   in_graph_per=True, num_actors=3, anakin_episode_len=21,
                   buffer_capacity=30 * 8)
    N = cfg.num_actors
    net = create_network(cfg, A)
    params = shaken(init_params(cfg, net, jax.random.PRNGKey(0)), 1)
    ring = DeviceRing(cfg, A)
    env = AnakinFakeEnv(obs_shape=cfg.stored_obs_shape, action_dim=A,
                        episode_len=cfg.anakin_episode_len, num_lanes=N)
    ast = make_anakin_state(cfg, A, env, jax.random.PRNGKey(11))
    assert ast["buf_hidden"].shape == (
        N, cfg.max_block_steps + cfg.core_context - 1, cfg.core_layers,
        xing4.latent_dim(cfg))
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
    blocks = []
    for t in range(T):
        for i in range(N):
            if tr["pending"][t][i]:
                blocks.append(lbs[i].finish(tr["q"][t][i])[0])
        for i in range(N):
            lbs[i].add(int(tr["actions"][t][i]), float(tr["reward"][t][i]),
                       tr["obs_step"][t][i], tr["q"][t][i],
                       tr["hidden"][t][i])
        for i in range(N):
            if tr["truncated"][t][i]:
                blocks.append(lbs[i].finish(None)[0])
                lbs[i].reset(tr["obs_next"][t][i])
    assert 8 < len(blocks) <= cfg.num_blocks
    nonzero = 0
    for slot, blk in enumerate(blocks):
        k = blk.num_sequences
        np.testing.assert_array_equal(blk.hidden, arrays["hidden"][slot][:k])
        assert not arrays["hidden"][slot][k:].any()
        nonzero += int(np.abs(blk.hidden).sum() > 0)
    assert nonzero > len(blocks) // 2


def test_the_learner_asks_the_model_for_what_it_keeps_not_for_its_name():
    """learner/ derives the state's stream, the buffers and the counters
    from models/ (state.py, network.step_buffers / counter_names): neither
    module names a core or reaches into its tree."""
    sources = [("learner", name) for name in ("step.py", "anakin.py",
                                              "learner.py")]
    sources += [("replay", "device_ring.py"), ("replay", "block.py"),
                (".", "actor.py"), (".", "train.py")]
    for folder, name in sources:
        with open(os.path.join(ROOT, "r2d2_tpu", folder, name)) as f:
            source = f.read()
        assert "xing4" not in source and "cfg.core" not in source, name
        assert "olmo_hybrid" not in source, name
        assert "router_bias" not in source and "expert_load" not in source
        assert "linear_stats" not in source, name


def test_the_cores_constants_are_the_sources():
    """What models/xing4.py keeps as module constants is what the
    configuration's file carries under config.json's own keys."""
    yarn = DOC["rope_scaling"]
    assert (xing4.ROPE_BETA_FAST, xing4.ROPE_BETA_SLOW,
            xing4.ROPE_MSCALE_ALL_DIM) == (
        yarn["beta_fast"], yarn["beta_slow"], yarn["mscale_all_dim"])
    assert xing4.RMS_NORM_EPS == DOC["rms_norm_eps"]
    assert xing4.HC_EPS == DOC["hc_eps"]
    assert xing4.H_RES_CLAMP == DOC["mhc_h_res_clamp_max"] \
        == -DOC["mhc_h_res_clamp_min"]
    assert xing4.ROUTED_SCALING_FACTOR == DOC["routed_scaling_factor"]
    assert xing4.N_SHARED_EXPERTS == DOC["n_shared_experts"]
    full = training.config_from_file(DOC["config"])
    assert (full.core_rope_theta, full.core_rope_factor,
            full.core_rope_original) == (
        DOC["rope_theta"], yarn["factor"],
        yarn["original_max_position_embeddings"])
    assert (full.core_streams, full.core_sinkhorn_iters) == (
        DOC["hc_mult"], DOC["hc_sinkhorn_iters"])


@pytest.mark.parametrize("core,stored", [
    ("xing4", [[3, 4, 20], "float32"]),
    ("olmo_hybrid", [[64, 128], "float32"])])
def test_checkpoint_restore_and_eval_with_a_cores_state(tmp_path, core,
                                                         stored):
    from r2d2_tpu.checkpoint import (
        Checkpointer,
        arch_meta,
        check_arch_compat,
    )
    from r2d2_tpu.evaluate import evaluate_params

    cfg = TINY_CFGS[core]()
    net = create_network(cfg, A)
    params = init_params(cfg, net, jax.random.PRNGKey(0))
    state = create_train_state(cfg, params)
    state, _, _ = jax.jit(make_train_step(cfg, net))(
        state, check.seeded_batch(cfg, A, 1))
    ck = Checkpointer(str(tmp_path / "ck"))
    ck.save(1, jax.device_get(state), meta=dict(env_steps=8,
                                                **arch_meta(cfg)))
    template = jax.device_get(create_train_state(cfg, params))
    restored, meta = Checkpointer(str(tmp_path / "ck")).restore(template)
    for a, b in zip(jax.tree.leaves(jax.device_get(state)),
                    jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert meta["core"] == core
    assert meta["recurrent_state"] == stored
    check_arch_compat(cfg, meta)
    with pytest.raises(ValueError, match="recurrent_state"):
        check_arch_compat(cfg.replace(core_context=64), meta)
    with pytest.raises(ValueError, match="core"):
        check_arch_compat(make_test_config(), meta)
    assert np.isfinite(evaluate_params(cfg, net, restored.params,
                                       env_factory, episodes=2,
                                       epsilon=0.0, seed=0))


@pytest.mark.parametrize("core", sorted(TINY_CFGS))
def test_the_paths_not_built_refuse_the_core_by_name(tmp_path, core):
    make = TINY_CFGS[core]
    with pytest.raises(ValueError, match=f"{core}.*inference_service"):
        make(actor_transport="process")
    with pytest.raises(ValueError, match=f"{core}.*netwire"):
        make(replay_transport="socket", replay_shards=2,
             device_replay=False, in_graph_per=False)
    with pytest.raises(ValueError, match=f"{core}.*netwire"):
        make(replay_shards=2, device_replay=False, in_graph_per=False)
    with pytest.raises(ValueError, match=f"{core}.*fused_double_unroll"):
        make(fused_double_unroll=True)
    with pytest.raises(ValueError, match=f"{core}.*stored_hidden_mode"):
        make(stored_hidden_mode="seq_start")
    from r2d2_tpu.serving.server import run_server

    with pytest.raises(ValueError, match=f"session tier.*{core}"):
        run_server(make(), str(tmp_path))


@pytest.mark.parametrize("core", sorted(TINY_CFGS))
def test_the_sharding_table_resolves_every_leaf_of_the_core(core):
    from jax.sharding import PartitionSpec as P

    from r2d2_tpu.parallel.mesh import make_mesh
    from r2d2_tpu.parallel.sharding import ShardingTable

    cfg = TINY_CFGS[core]()
    net = create_network(cfg, A)
    state = jax.eval_shape(lambda: create_train_state(
        cfg, init_params(cfg, net, jax.random.PRNGKey(0))))
    table = ShardingTable(make_mesh(cfg.replace(mesh_shape=(("dp", 2),))),
                          cfg)
    shardings = table.state_shardings(state)
    core = shardings.params["params"]["core"]
    assert all(s.spec == P() or set(s.spec) == {None}
               for s in jax.tree.leaves(core))
    assert jax.tree.structure(shardings) == jax.tree.structure(state)


# ------------------------------------------ what reads the core's scopes

TRAIN = ("jit(super_step)/while/body/closed_call/transpose(jvp("
         "R2D2Network.unroll))/core/core/while/body/closed_call/checkpoint/"
         "rematted_computation/experts/gather")
ACT = ("jit(super_step)/while/body/while/body/act/R2D2Network.act/core/"
       "core/while/body/attention/dot_general:")
TARGET = ("jit(super_step)/while/body/closed_call/target_forward/"
          "R2D2Network.unroll/core/core/while/body/router/top_k")
TORSO = "jit(super_step)/while/body/jvp(R2D2Network.unroll)/torso/conv"


class _Slice:
    """What a reader kind is handed of a traced run, for four operations of
    10, 20, 30 and 40 ns that follow each other."""

    def __init__(self, paths):
        self.cache = {}
        self.events = [dict(name=f"%op.{i} = f32[1]{{0}} fusion()",
                            start_ns=100 * i, dur_ns=10 * (i + 1), path=p)
                       for i, p in enumerate(paths)]

    def device_ops(self):
        return self.events


@pytest.mark.parametrize("scopes,share", [
    (["attention"], 20.0), (["router", "experts", "shared_expert"], 40.0),
    (["residual_mix"], None), (["core"], 60.0)])
def test_scope_anywhere_reads_a_scope_wherever_it_lies(scopes, share):
    from benchmark.reader_kinds import scope_anywhere

    ctx = _Slice([TRAIN, ACT, TARGET, TORSO])
    got = scope_anywhere.read(dict(scopes=scopes), ctx)
    assert got == (pytest.approx(share) if share is not None else None)
    # a program without these scopes (the parent's) gives nothing to read
    assert scope_anywhere.read(dict(scopes=["attention"]),
                               _Slice([TORSO, None])) is None
    assert scope_anywhere.read(dict(scopes=scopes), _Slice([])) is None


def test_the_new_metrics_are_files_the_harness_resolves():
    from benchmark import readers
    from benchmark.manifest import Manifest

    cell = Manifest().cell(NAME + ".anakin")
    mine = {m["name"]: m for m in cell.per_layer
            if m.get("kind") == "scope_anywhere"}
    assert set(mine) == {"attention_device_share", "experts_device_share",
                         "residual_mix_device_share",
                         "dense_ffn_device_share", "act_device_share"}
    for spec in mine.values():
        assert callable(readers.resolve(spec))
    assert all(m["name"] not in mine
               for m in Manifest().cell("impala_deep_lstm2.anakin").per_layer)


def test_step_split_inner_gives_an_operation_to_the_cores_own_scope():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "step_split", os.path.join(ROOT, "tools", "step_split.py"))
    ss = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ss)
    assert ss.scope_of(TRAIN) == "core.bwd"
    assert ss.inner_scope_of(TRAIN) == "experts.bwd"
    assert (ss.scope_of(ACT), ss.inner_scope_of(ACT)) == ("act", "attention")
    assert ss.inner_scope_of(TARGET) == "router"
    assert ss.inner_scope_of(TORSO) == ss.scope_of(TORSO) == "torso"
    events = _Slice([TRAIN, ACT, TARGET, TORSO, None]).events
    shares = ss.split(events, scope_of=ss.inner_scope_of)
    assert sum(shares.values()) == pytest.approx(100.0)
    assert shares["attention"] == pytest.approx(100 * 20 / 150)
    assert set(shares) == {"experts.bwd", "attention", "router", "torso",
                           "(none)"}


def test_model_flops_count_what_this_chip_multiplies():
    from benchmark import flops
    from benchmark.model_flops import nature_xing4_l5e8h4 as count

    cfg = training.config_from_file(DOC["config"])
    # the projections at 4 heads: q_a 2.75 M, q_b 0.59, kv_a 2.06, kv_b
    # 0.52 (x 149/85 slots a frame), o 1.84; scores and values over 65 keys
    assert count.attention_macs(cfg) == (
        3584 * 768 + 768 * 4 * 192 + 3584 * 576
        + 512 * 4 * 256 * 149 // 85 + 4 * 192 * 65 + 4 * 128 * 65
        + 4 * 128 * 3584)
    # 4 x 8 / 64 = half a routed expert a token, beside router and shared
    assert count.routed_macs(cfg) == 3584 * 64 + 1.5 * 3 * 3584 * 1024
    total = count.step_macs(cfg, A)
    assert 2.2e8 < total < 2.3e8
    assert flops.train_flops_per_update(NAME, cfg, A) == pytest.approx(
        8 * total * 64 * 85)
