"""Pallas fused LSTM (inference-only) vs the lax.scan reference,
in interpreter mode on CPU.

The oracle is an independent pure-jnp scan with the same gate math as
models/network.py:LSTMLayer (gates i,f,g,o; float32 cell state).  Checks
forward values and final state; the backward kernel was retired in r5
(on-chip fwd+bwd measured 0.96x scan), so the contract tested here is:
no-grad paths match the scan exactly, grad paths always run the scan
(learner/step.py:_loss_net), and differentiating the kernel fails
loudly rather than silently.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.ops.lstm import lstm_unroll_pallas

T, B, H = 7, 4, 16


def scan_oracle(xp_tm, wh, h0, c0):
    """xp_tm: (T, B, 4H) f32; wh: (H, 4H) f32; h0/c0: (B, H) f32."""
    def step(carry, x_t):
        h, c = carry
        gates = x_t + h @ wh
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c_new = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
        return (h_new, c_new), h_new

    (h, c), hs = jax.lax.scan(step, (h0, c0), xp_tm)
    return hs, h, c


@pytest.fixture(scope="module")
def inputs():
    rng = np.random.default_rng(0)
    xp = jnp.asarray(rng.normal(size=(T, B, 4 * H)), jnp.float32) * 0.5
    wh = jnp.asarray(rng.normal(size=(H, 4 * H)), jnp.float32) * 0.3
    h0 = jnp.asarray(rng.normal(size=(B, H)), jnp.float32)
    c0 = jnp.asarray(rng.normal(size=(B, H)), jnp.float32)
    return xp, wh, h0, c0


def pallas_fn(xp, wh, h0, c0):
    return lstm_unroll_pallas(xp, wh, h0, c0, compute_dtype=jnp.float32,
                              interpret=True)


def test_forward_matches_oracle(inputs):
    xp, wh, h0, c0 = inputs
    hs_p, hT_p, cT_p = pallas_fn(xp, wh, h0, c0)
    hs_o, hT_o, cT_o = scan_oracle(xp, wh, h0, c0)
    np.testing.assert_allclose(hs_p, hs_o, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(hT_p, hT_o, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(cT_p, cT_o, rtol=1e-5, atol=1e-5)


def test_t1_unroll_acting_shape(inputs):
    """The act path is a T=1 unroll — the kernel must handle grid=(1,)."""
    xp, wh, h0, c0 = inputs
    hs, hT, cT = pallas_fn(xp[:1], wh, h0, c0)
    hs_o, hT_o, cT_o = scan_oracle(xp[:1], wh, h0, c0)
    np.testing.assert_allclose(hs, hs_o, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(cT, cT_o, rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_network_pallas_matches_scan_end_to_end():
    """Full R2D2Network with impl=pallas (interpreted) vs impl=scan:
    same params → same q/hidden on the no-grad unroll (drop-in
    interchangeable, incl. checkpoints), and the TRAIN STEP built from a
    pallas config matches the scan config exactly — make_train_step
    must route every grad path through the scan loss net (_loss_net)."""
    from r2d2_tpu.config import test_config
    from r2d2_tpu.learner.step import create_train_state
    from r2d2_tpu.models.network import R2D2Network, create_network, init_params
    from r2d2_tpu.parallel.sharding import pjit_train_step
    from r2d2_tpu.utils.batch import synthetic_batch

    cfg_scan = test_config(lstm_impl="scan", lstm_layers=2)
    cfg_pl = cfg_scan.replace(lstm_impl="pallas", pallas_interpret=True)
    A = 4
    net_s = create_network(cfg_scan, A)
    net_p = create_network(cfg_pl, A)
    params = init_params(cfg_scan, net_s, jax.random.PRNGKey(3))
    rng = np.random.default_rng(1)
    b = synthetic_batch(cfg_scan, A, rng)

    def q_of(net, params):
        q, hid = net.apply(params, b["obs"], b["last_action"],
                           b["last_reward"], b["hidden"],
                           method=R2D2Network.unroll)
        return q, hid

    q_s, hid_s = q_of(net_s, params)
    q_p, hid_p = q_of(net_p, params)
    np.testing.assert_allclose(q_p, q_s, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(hid_p, hid_s, rtol=1e-4, atol=1e-4)

    # the grad path: a train step from the pallas config must equal the
    # scan config's step bit-for-bit (both run the scan loss net).  Host
    # batches: the unified step donates its batch arg, so one device
    # batch could not feed both steps.
    st0_s = create_train_state(cfg_scan, params)
    st_s, loss_s, pr_s = pjit_train_step(
        cfg_scan, net_s, state_template=st0_s)(st0_s, dict(b))
    st0_p = create_train_state(cfg_pl, params)
    st_p, loss_p, pr_p = pjit_train_step(
        cfg_pl, net_p, state_template=st0_p)(st0_p, dict(b))
    np.testing.assert_allclose(float(loss_p), float(loss_s), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(pr_p), np.asarray(pr_s),
                               rtol=1e-6)


def test_pallas_unroll_is_not_differentiable(inputs):
    """The retired-backward contract must fail loudly: differentiating
    the inference kernel raises instead of silently producing zeros."""
    xp, wh, h0, c0 = inputs

    def fwd_sum(w):
        return jnp.sum(pallas_fn(xp, w, h0, c0)[0])

    # the primal itself must be valid — otherwise the raises() below
    # would pass vacuously on a signature/shape error
    assert np.isfinite(float(fwd_sum(wh)))
    with pytest.raises(Exception):
        jax.grad(fwd_sum)(wh)


def test_act_fn_uses_scan_twin_off_tpu():
    """Regression: on a TPU default backend the learner's network resolves
    impl=pallas, but actor inference jits onto the host CPU backend
    (actor.py:resolve_act_device) where compiled pallas cannot lower
    ("Only interpret mode is supported on CPU backend").  make_act_fn must
    therefore build a scan-impl twin whenever the resolved act device is
    not a TPU — reproduced here with an explicit impl=pallas config and
    act_device="cpu" (the exact combination the fabric cell hits on the
    chip with lstm_impl="auto", act_device="auto")."""
    from r2d2_tpu.actor import make_act_fn
    from r2d2_tpu.config import test_config
    from r2d2_tpu.models.network import R2D2Network, create_network, init_params
    from r2d2_tpu.utils.batch import synthetic_batch

    cfg = test_config(lstm_impl="pallas", act_device="cpu")  # interpret=False
    A = 4
    net_p = create_network(cfg, A)
    net_s = create_network(cfg.replace(lstm_impl="scan"), A)
    params = init_params(cfg, net_s, jax.random.PRNGKey(5))
    b = synthetic_batch(cfg, A, np.random.default_rng(2))

    act = make_act_fn(cfg, net_p)
    # without the twin this raises at lowering time on the CPU backend
    q, hid = act(params, b["obs"][:, 0], b["last_action"][:, 0],
                 b["last_reward"][:, 0], b["hidden"])
    q_s, hid_s = net_s.apply(params, b["obs"][:, 0], b["last_action"][:, 0],
                             b["last_reward"][:, 0], b["hidden"],
                             method=R2D2Network.act)
    np.testing.assert_allclose(q, q_s, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(hid, hid_s, rtol=1e-5, atol=1e-5)


def test_bf16_compute_close_to_f32(inputs):
    """bf16 matmul with f32 accumulation stays within bf16 tolerance."""
    xp, wh, h0, c0 = inputs
    hs_bf, _, _ = lstm_unroll_pallas(xp, wh, h0, c0,
                                     compute_dtype=jnp.bfloat16,
                                     interpret=True)
    hs_o, _, _ = scan_oracle(xp, wh, h0, c0)
    np.testing.assert_allclose(hs_bf, hs_o, rtol=0.05, atol=0.05)
