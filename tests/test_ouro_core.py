"""The ``ouro`` memory core (models/ouro.py): the core against its plain
reference (benchmark/reference/nature_ouro_l4u4.py) at the small size of the
configuration's own file, in float32 on the CPU; the loops as one pass of
the same weights applied again, and the gradient of a shared weight as the
sum over its applications; and the state, kept whole at the steps a stored
sequence can start, through the fused loop.  The paths the cores share (the
thread fabric, checkpoints, the refusals, the sharding table) are
parametrised over all of them in tests/test_xing4_core.py."""
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
from benchmark.reference import nature_ouro_l4u4 as ref  # noqa: E402
from r2d2_tpu.config import OURO_HEADS, ouro_core_config  # noqa: E402
from r2d2_tpu.learner.step import loss_and_priorities  # noqa: E402
from r2d2_tpu.models import ouro  # noqa: E402
from r2d2_tpu.models.network import (  # noqa: E402
    R2D2Network,
    counter_names,
    create_network,
    init_params,
    zero_hidden,
)
from r2d2_tpu.models.state import state_spec, stream_spec  # noqa: E402

A = 4
NAME = "nature_ouro_l4u4"
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
# Ouro-2.6B's config.json as published, the keys of its shape (the
# model-configs catalog's row): the file carries each at its top level,
# unchanged unless ``reduced`` names it
PUBLISHED = dict(
    head_dim=128, hidden_act="silu", hidden_size=2048,
    intermediate_size=5632, layer_types=["full_attention"] * 48,
    max_position_embeddings=65536, max_window_layers=48, model_type="ouro",
    num_attention_heads=16, num_hidden_layers=48, num_key_value_heads=16,
    rms_norm_eps=1e-06, rope_scaling=None, rope_theta=1000000,
    sliding_window=None, tie_word_embeddings=False, total_ut_steps=4,
    early_exit_threshold=1, use_sliding_window=False, vocab_size=49152)


def small_cfg(**kw):
    """The configuration at its file's small size (Nature torso, 84x84)."""
    return training.preset_config(DOC, small=True, compute_dtype="float32",
                                  **kw)


def tiny_cfg(**kw):
    return ouro_core_config(game="Fake", **dict(TINY, **kw))


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


# ------------------------------------------------- against the reference

def test_unroll_loss_and_every_gradient_match_the_reference(small):
    """Q-values, the loss and the gradient of every leaf against the plain
    float32 reference, whose loops are a Python loop over the same arrays:
    so a shared weight's gradient is the sum over its 16 applications on
    both sides.  1e-5 of the largest value (Q) and 2e-5 of the largest
    gradient: float32 sums taken in another order, through 16 sandwiched
    layers and 4 final norms."""
    cfg, net, params, target, batch = small
    q, _ = net.apply(params, batch["obs"], batch["last_action"],
                     batch["last_reward"], batch["hidden"],
                     method=R2D2Network.unroll)
    q_ref = ref.unroll(params, batch["obs"], batch["last_action"],
                       batch["last_reward"], batch["hidden"])
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
    """One step at a time through the state (T = 1 against the stored keys
    of every loop and layer) gives the unroll's Q-values and its final
    state: 1e-5, float32 sums in another order."""
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
    q_ref = ref.unroll(params, *args, batch["hidden"])
    assert rel(jnp.stack(qs, axis=1), q_ref) < 1e-5


def test_a_state_cut_from_a_longer_episode_reproduces_its_q_values():
    """The state after step p of an episode, used as a window's stored
    state, gives the Q-values the whole episode's unroll has from p + 1 on:
    every loop's stored keys and values carry what the steps before p
    matter for (W = 4 < p).  1e-5, as above."""
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
    # every loop's and every layer's cache holds something of its own
    assert bool((jnp.abs(cut).max(axis=(0, 3, 4)) > 0).all())
    rest, _ = net.apply(params, obs[:, p:], la[:, p:], lr[:, p:], cut,
                        method=R2D2Network.unroll)
    assert rel(rest, whole[:, p:]) < 1e-5


# ------------------------------------------------------------ the loops

def _core_params(params):
    p = params["params"]["core"]
    return dict(in_proj=dict(kernel=p["in_proj_kernel"],
                             bias=p["in_proj_bias"]),
                layers=p["layers"], final_norm=p["final_norm"])


def test_the_loops_are_one_pass_of_the_same_weights_applied_again():
    """U loops are U applications of a one-loop core with the same layers,
    the final norm after each, the state of loop u being the one-loop
    core's state over loop u's caches: so the weights are shared and every
    loop attends over a cache of its own.  The counters are the rms of
    ``y_u - y_(u-1)`` over that of ``y_(u-1)``.  1e-5 of the largest
    value: float32 through the same operations."""
    cfg = tiny_cfg()
    one = cfg.replace(core_loops=1)
    net = create_network(cfg, A)
    core = _core_params(shaken(init_params(cfg, net, jax.random.PRNGKey(0)),
                               1))
    B, T, d = 2, 7, cfg.core_dim
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    feats = jax.random.normal(ks[0], (B, T, 21))
    hidden = 0.3 * jax.random.normal(ks[1], zero_hidden(cfg, B).shape)
    out, state, deltas = ouro.run(cfg, core, feats, hidden, jnp.float32)

    again = dict(core, in_proj=dict(kernel=jnp.eye(d), bias=jnp.zeros(d)))
    y = feats @ core["in_proj"]["kernel"] + core["in_proj"]["bias"]
    ys, states = [y], []
    for u in range(cfg.core_loops):
        y, s, _ = ouro.run(one, again, y, hidden[:, u:u + 1], jnp.float32)
        ys.append(y)
        states.append(s)
    assert rel(out, y) < 1e-5
    assert rel(state, jnp.concatenate(states, axis=1)) < 1e-5

    def rms(x):
        return jnp.sqrt(jnp.mean(x * x))

    want = [rms(ys[u + 1] - ys[u]) / rms(ys[u])
            for u in range(cfg.core_loops)]
    np.testing.assert_allclose(deltas, want, rtol=1e-5)
    buffers = ouro.step_buffers(cfg, {"core": {"counters": None}},
                                {"core": {"loop_deltas": deltas}})
    np.testing.assert_allclose(buffers["core"]["counters"], want[1:],
                               rtol=1e-5)
    assert counter_names(cfg) == ("loop_delta_rel_1", "loop_delta_rel_2",
                                  "loop_delta_rel_3")


def test_a_shared_weights_gradient_is_the_sum_over_its_loops():
    """The gradient of the stacked layers, which every loop applies, is the
    sum of the gradients that a copy of them a loop would take: one copy a
    loop, each loop run through the one-loop core, differentiated apart
    and summed.  2e-5 of the largest entry: float32 sums in another
    order."""
    cfg = tiny_cfg()
    one = cfg.replace(core_loops=1)
    net = create_network(cfg, A)
    core = _core_params(shaken(init_params(cfg, net, jax.random.PRNGKey(0)),
                               1))
    B, T, d = 2, 7, cfg.core_dim
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    feats = jax.random.normal(ks[0], (B, T, 21))
    hidden = 0.3 * jax.random.normal(ks[1], zero_hidden(cfg, B).shape)
    probe = jax.random.normal(ks[2], (B, T, d))

    def looped(layers):
        out, _, _ = ouro.run(cfg, dict(core, layers=layers), feats, hidden,
                             jnp.float32)
        return (out * probe).sum()

    def untied(copies):
        y = feats @ core["in_proj"]["kernel"] + core["in_proj"]["bias"]
        eye = dict(kernel=jnp.eye(d), bias=jnp.zeros(d))
        for u, layers in enumerate(copies):
            y, _, _ = ouro.run(one, dict(core, in_proj=eye, layers=layers),
                               y, hidden[:, u:u + 1], jnp.float32)
        return (y * probe).sum()

    tied = jax.grad(looped)(core["layers"])
    per_loop = jax.grad(untied)([core["layers"]] * cfg.core_loops)
    summed = jax.tree.map(lambda *g: sum(g), *per_loop)
    scale = max(float(jnp.abs(g).max()) for g in jax.tree.leaves(tied))
    for g, s in zip(jax.tree.leaves(tied), jax.tree.leaves(summed)):
        assert float(jnp.abs(g - s).max()) < 2e-5 * scale
    # and no loop's share is zero: every application reaches every weight
    for copy in per_loop:
        assert all(float(jnp.abs(g).max()) > 0
                   for g in jax.tree.leaves(copy))


# ------------------------------------------------------------- the state

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_state_is_a_cache_a_loop_and_a_layer_kept_whole(dtype):
    cfg = tiny_cfg(compute_dtype=dtype)
    shape, np_dtype = state_spec(cfg)
    assert shape == (4, 4, 4, 2, 2, 16) and np_dtype.name == dtype
    # the fused loop keeps the whole state at a sequence's start and no
    # per-step rows: an entry holds no value
    assert stream_spec(cfg) == (0, (0,), np_dtype, shape)
    # the published widths: 2 MiB a sequence in bfloat16, heads and head
    # size the minor pair, one whole bfloat16 tile (16 rows of 128)
    full = training.config_from_file(DOC["config"])
    shape, stored = state_spec(full)
    assert shape == (4, 4, 16, 2, 16, 128)
    assert int(np.prod(shape)) * stored.itemsize == 2 * 2 ** 20


@pytest.mark.parametrize("burn_in", [4, 6])
def test_the_fused_loop_cuts_the_states_the_host_cutter_cuts(burn_in):
    """The fused loop keeps, a lane, the whole state at the steps a stored
    sequence can start and nothing a step; the states it writes into the
    ring are those the host's cutter takes from a whole state kept at every
    step, which is the acting state at that step — over episodes' first
    blocks, blocks after a carry-over, short last blocks and resets."""
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
    assert ast["buf_hidden"].size == 0
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
    assert kinds >= {("boundary", False), ("boundary", True),
                     ("episode_end", True)}
    assert 8 < len(blocks) <= cfg.num_blocks
    nonzero = 0
    for slot, blk in enumerate(blocks):
        k = blk.num_sequences
        np.testing.assert_array_equal(blk.hidden, arrays["hidden"][slot][:k])
        assert not arrays["hidden"][slot][k:].any()
        nonzero += int(np.abs(blk.hidden).sum() > 0)
    assert nonzero > len(blocks) // 2


# -------------------------------------------- through the program's paths

def test_the_configuration_is_the_published_one_but_for_what_it_reduces():
    """Every key of the source's config.json is at the file's top level, as
    published unless ``reduced`` names it; the program's fields are those
    keys, and the preset is the whole published model."""
    reduced = set(DOC["reduced"])
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert DOC[key] != value and DOC["published"][key] == value, key
        else:
            assert DOC[key] == value, key
    assert reduced == {"num_hidden_layers", "core_layers", "core_context",
                       "buffer_capacity"}
    assert DOC["num_hidden_layers"] == 4
    assert ouro.RMS_NORM_EPS == DOC["rms_norm_eps"]
    full = training.config_from_file(DOC["config"])
    assert (full.core_dim, full.core_dense_dim, full.core_layers) == (
        DOC["hidden_size"], DOC["intermediate_size"],
        DOC["num_hidden_layers"])
    assert full.core_heads_held == DOC["num_attention_heads"] == DOC[
        "num_key_value_heads"] == OURO_HEADS
    assert full.core_head_dim == DOC["head_dim"] == DOC["hidden_size"] // \
        DOC["num_attention_heads"]
    assert (full.core_rope_theta, full.core_loops) == (
        DOC["rope_theta"], DOC["total_ut_steps"])
    whole = training.preset_config(DOC)
    assert (whole.core_layers, whole.core_heads_held, whole.core_loops) == (
        PUBLISHED["num_hidden_layers"], PUBLISHED["num_attention_heads"],
        PUBLISHED["total_ut_steps"])


@pytest.mark.parametrize("bad,match", [
    (dict(core_loops=0), "core_loops must be >= 1"),
    (dict(core_heads_held=0), r"core_heads_held must lie in \[1, 16\]"),
    (dict(core_heads_held=17), r"core_heads_held must lie in \[1, 16\]")])
def test_the_config_refuses_what_the_core_cannot_run(bad, match):
    with pytest.raises(ValueError, match=match):
        tiny_cfg(**bad)


def test_the_new_metrics_are_files_the_harness_resolves():
    from benchmark import readers
    from benchmark.manifest import Manifest

    cell = Manifest().cell(NAME + ".anakin")
    specs = {s["name"]: s for s in cell.per_layer}
    for scope in ("looped_attention", "looped_mlp", "loop_cache"):
        spec = specs[scope + "_device_share"]
        assert spec["kind"] == "scope_anywhere"
        assert spec["scopes"] == [scope]
        assert callable(readers.resolve(spec))
    # the other cores' own shares are not this cell's, and the metrics
    # that list no cells are
    assert "mlp_device_share" not in specs
    assert "attention_device_share" not in specs
    assert {"step_device_ms", "train_mfu", "core_device_share"} <= set(specs)


def test_the_scopes_are_on_the_paths_of_the_lowered_step():
    """Every scope the core's metrics read stands on an operation's path
    in the compiled train step: forward, backward and the target
    network's pass."""
    from r2d2_tpu.learner.step import create_train_state, make_train_step

    cfg = tiny_cfg(remat=True)
    net = create_network(cfg, A)
    state = create_train_state(cfg, init_params(cfg, net,
                                                jax.random.PRNGKey(0)))
    text = jax.jit(make_train_step(cfg, net)).lower(
        state, check.seeded_batch(cfg, A, 1)).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("looped_attention", "looped_mlp", "loop_cache"):
        assert any(f"/{scope}/" in path for path in paths), scope
    for scope in ("looped_attention", "looped_mlp"):
        mine = [path for path in paths if f"/{scope}/" in path]
        assert any("transpose(jvp(" in path for path in mine), scope
        assert any("target_forward" in path for path in mine), scope


def test_model_flops_count_what_this_chip_multiplies():
    """16 applications of a layer a frame: 99 % of the multiply-adds, two
    thirds of each the feed-forward."""
    cfg = training.config_from_file(DOC["config"])
    d, f, w = 2048, 5632, 16 * 128
    layer = 4 * d * w + 2 * w * 17 + 3 * d * f
    assert layer == 51_449_856
    want = (6_885_376 + 517 * d + 16 * layer + 2 * d * 512 + 512 * 4 + 512)
    assert flops.step_macs(NAME, cfg, A) == want == 833_241_600
    assert flops.train_flops_per_update(NAME, cfg, A) == 8 * want * 64 * 85
    assert 0.98 < 16 * layer / want < 0.99
    assert 0.67 < 3 * d * f / layer < 0.68
