import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.config import test_config as make_test_config
from r2d2_tpu.learner import step as step_module
from r2d2_tpu.learner.step import (
    TrainState, create_train_state, loss_and_priorities,
    make_super_step_fn, make_train_step,
    _window_indices, value_rescale, inverse_value_rescale,
)
from r2d2_tpu.models.network import R2D2Network, create_network, init_params
from r2d2_tpu.parallel.sharding import pjit_train_step
from r2d2_tpu.utils import math as hmath

A = 4


def reference_target_indices(b, l, f, n):
    """The reference's target-window construction (model.py:102-109): slice
    [b+n : b+l+f], then edge-pad min(n-f, l) copies of the final element."""
    idxs = list(range(b + n, b + l + f))
    pad = min(n - f, l)
    idxs = idxs + [b + l + f - 1] * pad
    return idxs[:l]


def test_window_indices_match_reference_semantics():
    cfg = make_test_config()  # L=4, n=2
    n, L = cfg.forward_steps, cfg.learning_steps
    cases = []
    for b in range(0, cfg.burn_in_steps + 1):
        for l in range(1, L + 1):
            for f in range(1, n + 1):
                cases.append((b, l, f))
    burn = jnp.array([c[0] for c in cases])
    learn = jnp.array([c[1] for c in cases])
    fwd = jnp.array([c[2] for c in cases])
    idx_online, idx_target, mask = _window_indices(cfg, burn, learn, fwd)
    for row, (b, l, f) in enumerate(cases):
        expected_online = [b + i for i in range(l)]
        expected_target = reference_target_indices(b, l, f, n)
        got_online = np.asarray(idx_online[row])[:l].tolist()
        got_target = np.asarray(idx_target[row])[:l].tolist()
        assert got_online == expected_online, (b, l, f)
        assert got_target == expected_target, (b, l, f)
        assert np.asarray(mask[row]).sum() == l


def test_value_rescale_matches_numpy():
    x = jnp.linspace(-100, 100, 201)
    np.testing.assert_allclose(np.asarray(value_rescale(x)),
                               hmath.value_rescale(np.asarray(x)), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(inverse_value_rescale(x)),
                               hmath.inverse_value_rescale(np.asarray(x)),
                               rtol=1e-5, atol=1e-5)


def make_batch(cfg, rng, B):
    T, L = cfg.seq_len, cfg.learning_steps
    n = cfg.forward_steps
    learning = rng.integers(1, L + 1, B).astype(np.int32)
    burn_in = rng.integers(0, cfg.burn_in_steps + 1, B).astype(np.int32)
    forward = np.where(learning == L, rng.integers(1, n + 1, B), 1).astype(np.int32)
    return dict(
        obs=rng.integers(0, 255, (B, T, *cfg.obs_shape), dtype=np.uint8),
        last_action=rng.random((B, T, A)).astype(np.float32),
        last_reward=rng.random((B, T)).astype(np.float32),
        hidden=rng.normal(size=(B, 2, cfg.lstm_layers, cfg.hidden_dim)).astype(np.float32),
        action=rng.integers(0, A, (B, L)).astype(np.int32),
        n_step_reward=rng.normal(size=(B, L)).astype(np.float32),
        n_step_gamma=np.full((B, L), cfg.gamma ** n, np.float32),
        burn_in=burn_in, learning=learning, forward=forward,
        is_weights=rng.uniform(0.2, 1.0, B).astype(np.float32),
    )


def numpy_oracle(cfg, net, params, target_params, batch):
    """Reference learner semantics (worker.py:344-359) recomputed with plain
    numpy ragged loops on top of the network's unrolled Q sequences."""
    to_j = lambda x: jnp.asarray(x)
    q_online, _ = net.apply(params, to_j(batch["obs"]), to_j(batch["last_action"]),
                            to_j(batch["last_reward"]), to_j(batch["hidden"]),
                            method=R2D2Network.unroll)
    q_target, _ = net.apply(target_params, to_j(batch["obs"]),
                            to_j(batch["last_action"]), to_j(batch["last_reward"]),
                            to_j(batch["hidden"]), method=R2D2Network.unroll)
    q_online, q_target = np.asarray(q_online), np.asarray(q_target)

    B = q_online.shape[0]
    n = cfg.forward_steps
    total_loss, total_count = 0.0, 0
    td_all, ls_all = [], []
    for i in range(B):
        b, l, f = int(batch["burn_in"][i]), int(batch["learning"][i]), int(batch["forward"][i])
        tgt_idx = reference_target_indices(b, l, f, n)
        q_taken = q_online[i, b:b + l, :][np.arange(l), batch["action"][i, :l]]
        a_star = q_online[i, tgt_idx, :].argmax(-1)
        q_boot = q_target[i, tgt_idx, :][np.arange(l), a_star]
        target = hmath.value_rescale(
            batch["n_step_reward"][i, :l]
            + batch["n_step_gamma"][i, :l] * hmath.inverse_value_rescale(q_boot))
        td = target - q_taken
        total_loss += (batch["is_weights"][i] * td ** 2).sum()
        total_count += l
        td_all.append(np.abs(td))
        ls_all.append(l)
    loss = total_loss / total_count
    prios = hmath.mixed_td_errors(np.concatenate(td_all).astype(np.float32),
                                  np.array(ls_all))
    return loss, prios


def test_loss_and_priorities_match_reference_oracle():
    cfg = make_test_config()
    net = create_network(cfg, A)
    params = init_params(cfg, net, jax.random.PRNGKey(0))
    target_params = init_params(cfg, net, jax.random.PRNGKey(1))
    rng = np.random.default_rng(7)
    batch = make_batch(cfg, rng, B=8)

    loss, prios = loss_and_priorities(
        cfg, net, params, target_params,
        {k: jnp.asarray(v) for k, v in batch.items()})
    exp_loss, exp_prios = numpy_oracle(cfg, net, params, target_params, batch)

    np.testing.assert_allclose(float(loss), exp_loss, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(prios), exp_prios, rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_fused_double_unroll_matches_unfused():
    """cfg.fused_double_unroll (one vmapped unroll over stacked
    online+target params) must be a pure scheduling change: identical
    loss, priorities, AND parameter gradients to the two-unroll path."""
    cfg = make_test_config()
    net = create_network(cfg, A)
    params = init_params(cfg, net, jax.random.PRNGKey(0))
    target_params = init_params(cfg, net, jax.random.PRNGKey(1))
    rng = np.random.default_rng(11)
    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, rng, B=8).items()}

    fused_cfg = cfg.replace(fused_double_unroll=True)

    def run(c):
        def loss_fn(p):
            return loss_and_priorities(c, net, p, target_params, batch)

        (loss, prios), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        return loss, prios, grads

    loss_a, prios_a, grads_a = run(cfg)
    loss_b, prios_b, grads_b = run(fused_cfg)
    np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(prios_a), np.asarray(prios_b),
                               rtol=1e-5, atol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6),
        grads_a, grads_b)
    # no gradient leaks into the target side: the fused stack must not
    # create a path around the stop_gradient
    gnorm = sum(float(jnp.abs(g).sum()) for g in jax.tree.leaves(grads_b))
    assert np.isfinite(gnorm) and gnorm > 0


def test_train_step_reduces_loss_and_syncs_target():
    cfg = make_test_config(target_net_update_interval=5)
    net = create_network(cfg, A)
    params = init_params(cfg, net, jax.random.PRNGKey(2))
    state = create_train_state(cfg, params)
    # the ONE train-step entry point (trivial 1-device mesh); host numpy
    # batches — the step donates its batch arg, so a device batch could
    # not be re-stepped
    step_fn = pjit_train_step(cfg, net, state_template=state)
    rng = np.random.default_rng(8)
    batch = make_batch(cfg, rng, B=8)

    losses = []
    for i in range(10):
        state, loss, prios = step_fn(state, batch)
        losses.append(float(loss))
        assert np.isfinite(losses[-1])
        assert np.asarray(prios).shape == (8,)
        if i + 1 == 5:
            # hard sync just happened (step counter == interval)
            diff = jax.tree.map(lambda p, t: float(jnp.abs(p - t).max()),
                                state.params, state.target_params)
            assert max(jax.tree.leaves(diff)) == 0.0
    assert losses[-1] < losses[0]
    assert int(state.step) == 10


def _select_sync(sync, params, target_params):
    """The oracle of ``step._sync_target``: the select over every leaf
    that the step held before its sync became a conditional copy."""
    return jax.tree.map(lambda p, t: jnp.where(sync, p, t), params,
                        target_params)


@pytest.mark.parametrize("interval,updates,k,learnhealth", [
    (1000, 6, 1, False),   # an interval the run never reaches
    (1, 4, 1, False),      # every update syncs
    (5, 12, 4, False),     # syncs at updates 5 and 10, inside super-steps
    (3, 7, 1, True),       # the diagnostics read the new target
], ids=["never", "every_update", "inside_a_super_step", "learnhealth"])
def test_conditional_target_sync_is_the_select_bit_for_bit(
        monkeypatch, interval, updates, k, learnhealth):
    """The state after N updates under the conditional copy equals, leaf
    for leaf and bit for bit, the state under the select, as do the
    losses, priorities and diagnostics on the way."""
    cfg = make_test_config(target_net_update_interval=interval,
                           learnhealth_interval=2 if learnhealth else 0)
    net = create_network(cfg, A)
    rng = np.random.default_rng(12)
    batches = [make_batch(cfg, rng, B=4) for _ in range(3)]

    def run():
        state = create_train_state(
            cfg, init_params(cfg, net, jax.random.PRNGKey(5)))
        outs = []
        if k == 1:
            fn = jax.jit(make_train_step(cfg, net, learnhealth=learnhealth))
            for i in range(updates):
                state, *out = fn(state, batches[i % len(batches)])
                outs.append(out)
        else:
            stacked = {key: jnp.stack([b[key] for b in batches])
                       for key in batches[0] if key != "is_weights"}
            fn = jax.jit(make_super_step_fn(
                cfg, net, k, gather=lambda arrays, ints_t, w_t: {
                    **jax.tree.map(lambda a: a[ints_t[0, 0]], arrays),
                    "is_weights": w_t}))
            for d in range(updates // k):
                pick = (np.arange(k) + d * k) % len(batches)
                ints = np.broadcast_to(
                    pick[:, None, None], (k, 4, 6)).astype(np.int32)
                w = np.stack([batches[i]["is_weights"] for i in pick])
                state, *out = fn(state, stacked, ints, w)
                outs.append(out)
        return jax.tree.map(np.asarray, (state, outs))

    got = run()
    monkeypatch.setattr(step_module, "_sync_target", _select_sync)
    want = run()
    assert int(got[0].step) == updates
    jax.tree.map(np.testing.assert_array_equal, got, want)
    synced = max(jax.tree.leaves(jax.tree.map(
        lambda p, t: float(np.abs(p - t).max()),
        got[0].params, got[0].target_params))) == 0.0
    assert synced == (updates % interval == 0)
    if interval > updates:     # the target is still the initial network
        first = init_params(cfg, net, jax.random.PRNGKey(5))
        jax.tree.map(np.testing.assert_array_equal, got[0].target_params,
                     jax.tree.map(np.asarray, first))


def test_gradients_do_not_flow_into_target_selection():
    """Value semantics check: perturbing target params changes loss, but the
    double-Q argmax path must be stop-gradiented — grads wrt target params of
    the loss are identically zero."""
    cfg = make_test_config()
    net = create_network(cfg, A)
    params = init_params(cfg, net, jax.random.PRNGKey(3))
    target_params = init_params(cfg, net, jax.random.PRNGKey(4))
    rng = np.random.default_rng(9)
    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, rng, B=4).items()}

    def loss_wrt_target(tp):
        loss, _ = loss_and_priorities(cfg, net, params, tp, batch)
        return loss

    grads = jax.grad(loss_wrt_target)(target_params)
    assert max(jax.tree.leaves(jax.tree.map(
        lambda g: float(jnp.abs(g).max()), grads))) == 0.0


def test_published_snapshot_survives_state_donation():
    """Learner._publish's one-dispatch jitted tree-copy must produce
    buffers genuinely distinct from the (donated) train state: a later
    step reusing the donated buffers must not clobber what actors hold."""
    import jax
    import jax.numpy as jnp

    copy_fn = jax.jit(lambda p: jax.tree.map(jnp.copy, p))
    x = {"w": jnp.arange(8, dtype=jnp.float32)}
    snap = copy_fn(x)
    step = jax.jit(lambda p: jax.tree.map(lambda a: a * 0 - 1, p),
                   donate_argnums=0)
    step(x)  # donates x's buffers — snap must be unaffected
    np.testing.assert_array_equal(np.asarray(snap["w"]),
                                  np.arange(8, dtype=np.float32))
