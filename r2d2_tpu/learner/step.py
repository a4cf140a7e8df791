"""The jitted R2D2 train step.

Capability-parity with the reference learner's gradient path
(worker.py:318-390): burn-in + stored-state LSTM unroll, n-step **double-Q**
targets under value rescaling, importance-weighted MSE over the learning
window, grad-clip-40 Adam, mixed max/mean per-sequence priorities, periodic
hard target-net sync.

TPU-first redesign:
- The reference runs three packed-sequence forwards per step (online no-grad,
  target no-grad, online grad — worker.py:346-352).  Here a single unroll per
  network suffices: the full-T Q sequence is gathered at the online window
  indices (grad path) and at the n-step-shifted target indices (stop-grad
  path), which is mathematically identical and ~⅓ cheaper.
- Window selection is static-shape: per-sample ``(burn_in, learning,
  forward)`` become gather indices and a validity mask, replacing the
  per-sample Python slice loops of model.py:102-111,143.  The edge-padding
  semantics for episodes that end inside the n-step window (model.py:103-109)
  are reproduced by clamping target indices to ``burn_in+learning+forward-1``.
- Priorities (worker.py:268-276, a host-side Python loop in the reference,
  forcing a device→host sync every step) are computed inside the jit as
  masked segment max/mean and returned as one small array.
- Target sync (worker.py:376-377) happens in-graph as a conditional copy on
  the step counter, so the whole training loop state lives on device.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import struct

from r2d2_tpu.config import Config
from r2d2_tpu.models.network import R2D2Network, step_buffers


def value_rescale(x: jnp.ndarray, eps: float = 1e-3) -> jnp.ndarray:
    """h(x) = sign(x)(sqrt(|x|+1)-1) + eps*x (worker.py:383-385)."""
    return jnp.sign(x) * (jnp.sqrt(jnp.abs(x) + 1.0) - 1.0) + eps * x


def inverse_value_rescale(x: jnp.ndarray, eps: float = 1e-3) -> jnp.ndarray:
    t = (jnp.sqrt(1.0 + 4.0 * eps * (jnp.abs(x) + 1.0 + eps)) - 1.0) / (2.0 * eps)
    return jnp.sign(x) * (jnp.square(t) - 1.0)


class TrainState(struct.PyTreeNode):
    step: jnp.ndarray
    params: Any
    target_params: Any
    opt_state: Any


def make_optimizer(cfg: Config) -> optax.GradientTransformation:
    """Adam(lr, eps) + global-norm clip 40 (worker.py:289,364)."""
    return optax.chain(
        optax.clip_by_global_norm(cfg.grad_norm),
        optax.adam(cfg.lr, eps=cfg.adam_eps),
    )


def create_train_state(cfg: Config, params) -> TrainState:
    opt = make_optimizer(cfg)
    # copy params into the state: the jitted step donates its input state,
    # so the state must not alias buffers the caller still holds
    params = jax.tree.map(jnp.copy, params)
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        target_params=jax.tree.map(jnp.copy, params),
        opt_state=opt.init(params),
    )


def _window_indices(cfg: Config, burn_in, learning, forward):
    """Gather indices into the unrolled (B, T, A) Q sequence.

    Sample layout along T is [burn_in | learning | forward] from t=0
    (replay assembles windows that way; see replay_buffer.sample_batch).

    - online index for learning step i:  burn_in + i
    - target index for learning step i:  min(burn_in + n + i,
                                             burn_in + learning + forward - 1)
      reproducing model.py:102-109 (start at burn_in + max_forward_steps,
      edge-pad when the episode ended inside the forward window).
    """
    L, n = cfg.learning_steps, cfg.forward_steps
    steps = jnp.arange(L)[None, :]                       # (1, L)
    b = burn_in[:, None]
    idx_online = b + steps                                # (B, L)
    last_valid = (burn_in + learning + forward - 1)[:, None]
    idx_target = jnp.minimum(b + n + steps, last_valid)
    mask = steps < learning[:, None]                      # (B, L)
    return idx_online, idx_target, mask


def _gather_time(q_seq, idx):
    # q_seq: (B, T, A); idx: (B, L) → (B, L, A)
    return jnp.take_along_axis(q_seq, idx[:, :, None], axis=1)


def mixed_priorities(abs_td, mask, learning, eta=0.9):
    """Masked per-sequence 0.9·max + 0.1·mean of |TD| (worker.py:268-276)."""
    masked = jnp.where(mask, abs_td, 0.0)
    seg_max = masked.max(axis=1)
    seg_mean = masked.sum(axis=1) / jnp.maximum(learning, 1)
    return eta * seg_max + (1.0 - eta) * seg_mean


def _double_unroll(cfg: Config, net: R2D2Network, params, target_params,
                   batch) -> tuple:
    """(q_online, q_target_seq, stats): the Q sequences, each (B, T, A),
    and what the online pass sowed under "stats" for the model's own
    ``step_buffers`` (models/network.py) — an empty tree where the model
    sows nothing.

    Default: two independent unrolls (reference semantics — worker.py's
    separate online/target forwards).  With ``cfg.fused_double_unroll``,
    ONE unroll vmapped over the stacked (online, target) param pytrees:
    the recurrence walks T sequential steps once instead of twice, at
    double per-step batch — on the round-4 v5e measurement a B=128 unroll
    costs only 1.30x a B=64 one, so the fusion trades a free batch
    doubling for half the latency-bound scan chain.

    ``net`` must be a scan-recurrence network — callers go through
    :func:`_loss_net`, which enforces it (the Pallas kernel is
    inference-only since r5 and would fail under the surrounding
    grad / vmap)."""
    if not cfg.fused_double_unroll:
        (q_online, _), sown = net.apply(
            params, batch["obs"], batch["last_action"],
            batch["last_reward"], batch["hidden"],
            method=R2D2Network.unroll, mutable=["stats"])       # (B, T, A)
        with jax.named_scope("target_forward"):
            q_target_seq, _ = net.apply(target_params, batch["obs"],
                                        batch["last_action"],
                                        batch["last_reward"],
                                        batch["hidden"],
                                        method=R2D2Network.unroll)
        return (q_online, jax.lax.stop_gradient(q_target_seq),
                sown.get("stats", {}))

    stacked = jax.tree.map(
        lambda p, t: jnp.stack([p, t]),
        params, jax.lax.stop_gradient(target_params))
    q_both, _ = jax.vmap(
        lambda p: net.apply(p, batch["obs"], batch["last_action"],
                            batch["last_reward"], batch["hidden"],
                            method=R2D2Network.unroll))(stacked)
    return q_both[0], jax.lax.stop_gradient(q_both[1]), {}


def _loss_net(cfg: Config, net: R2D2Network) -> R2D2Network:
    """The network the LOSS must unroll: the scan recurrence, always.

    Built once per step-factory call (NOT per trace — the r4 advisor
    flagged the shadow-network-inside-the-loss trap).  The Pallas
    inference kernel resolves for acting/eval nets on TPU but has no
    backward (ops/lstm.py, retired r5); all impls share one param
    pytree, so swapping the engine is free."""
    from r2d2_tpu.models.network import create_network, resolve_lstm_impl

    if resolve_lstm_impl(cfg) == "scan":
        return net
    return create_network(cfg.replace(lstm_impl="scan"), net.action_dim)


def loss_and_priorities(cfg: Config, net: R2D2Network, params, target_params,
                        batch: Dict[str, jnp.ndarray], with_aux: bool = False):
    """``with_aux`` additionally returns the forward-pass intermediates
    the learnhealth diagnostics consume ``(td, mask, q_learn, max_abs_q)``
    — stop-gradiented values, never a second forward."""
    q_online, q_target_seq, _ = _double_unroll(cfg, net, params,
                                               target_params, batch)
    return _td_loss(cfg, batch, q_online, q_target_seq, with_aux)


def _loss_and_stats(cfg: Config, net: R2D2Network, params, target_params,
                    batch, with_aux: bool):
    """:func:`loss_and_priorities` with what the online pass sowed beside
    its auxiliary output: ``(loss, (priorities[, aux], stats))``."""
    q_online, q_target_seq, stats = _double_unroll(
        cfg, net, params, target_params, batch)
    loss, out = _td_loss(cfg, batch, q_online, q_target_seq, with_aux)
    return loss, (out, stats)


@jax.named_scope("loss")
def _td_loss(cfg: Config, batch, q_online, q_target_seq, with_aux: bool):
    """The loss's own arithmetic, after the two unrolls: window gathers,
    double-Q target, weighted TD error, priorities."""
    idx_online, idx_target, mask = _window_indices(
        cfg, batch["burn_in"], batch["learning"], batch["forward"])

    # online Q(s_t, a_t) over the learning window — the grad path
    q_learn = _gather_time(q_online, idx_online)                  # (B, L, A)
    q_taken = jnp.take_along_axis(
        q_learn, batch["action"][:, :, None], axis=2)[:, :, 0]    # (B, L)

    # double-Q: online argmax at t+n, target evaluates (worker.py:345-347)
    q_online_tn = jax.lax.stop_gradient(_gather_time(q_online, idx_target))
    a_star = jnp.argmax(q_online_tn, axis=-1)                     # (B, L)
    q_boot = jnp.take_along_axis(
        _gather_time(q_target_seq, idx_target),
        a_star[:, :, None], axis=2)[:, :, 0]                      # (B, L)

    # rescaled n-step target (worker.py:349)
    target = value_rescale(
        batch["n_step_reward"] + batch["n_step_gamma"]
        * inverse_value_rescale(q_boot))

    td = target - q_taken
    weighted_sq = batch["is_weights"][:, None] * jnp.square(td)
    valid = mask.sum()
    loss = jnp.where(mask, weighted_sq, 0.0).sum() / jnp.maximum(valid, 1)

    priorities = mixed_priorities(jnp.abs(td), mask, batch["learning"])
    if not with_aux:
        return loss, priorities
    aux = jax.lax.stop_gradient(
        (td, mask, q_learn, jnp.abs(q_online).max()))
    return loss, (priorities, aux)


def _sync_target(sync, params, target_params):
    """The hard target sync (worker.py:376-377): the new parameters on the
    update that syncs, a copy; the target network as it is, untouched, on
    every other.  A ``jnp.where`` over the leaves gives the same values
    and reads and writes every target leaf on every update."""
    return jax.lax.cond(sync, lambda p, t: p, lambda p, t: t,
                        params, target_params)


def target_syncs(cfg: Config, updates: int) -> int:
    """How many of the first ``updates`` updates took the sync's copying
    branch: the host's reckoning, from its own count of updates."""
    return updates // cfg.target_net_update_interval


def make_train_step(cfg: Config, net: R2D2Network,
                    learnhealth: bool = False):
    """Returns ``train_step(state, batch) -> (state, loss, priorities)``
    — the pure function.  The ONE place it is jitted is
    ``parallel/sharding.pjit_train_step`` (table-driven shardings,
    state+batch donation); a 1-device mesh is the single-device case.

    ``learnhealth`` (and ``cfg.learnhealth_interval > 0``) appends the
    in-graph diagnostic vector (telemetry/learnhealth.py) to the
    signature: ``-> (state, loss, priorities, diag (DIAG_SIZE,) f32)``.
    The diagnostics — including the paper's ΔQ zero-state re-unroll —
    run under ``lax.cond`` on the step counter, so the
    ``learnhealth_interval - 1`` disarmed steps between cadence points
    pay only a zeros fill."""
    opt = make_optimizer(cfg)
    net = _loss_net(cfg, net)  # grad paths always run the scan recurrence
    lh = learnhealth and getattr(cfg, "learnhealth_interval", 0) > 0
    if lh:
        from r2d2_tpu.telemetry.learnhealth import DIAG_SIZE, make_diag_fn

        diag_fn = make_diag_fn(cfg, net)

    def train_step(state: TrainState, batch: Dict[str, jnp.ndarray]):
        # only the "params" collection is differentiated.  What else a
        # model keeps (models/network.py: "buffers", state that is no
        # gradient leaf) the optimizer sees zero gradients for, and the
        # model's own step_buffers writes it after the update
        rest = {c: v for c, v in state.params.items() if c != "params"}
        grad_fn = jax.value_and_grad(
            lambda p: _loss_and_stats(
                cfg, net, {**rest, "params": p}, state.target_params,
                batch, with_aux=lh), has_aux=True)
        (loss, (priorities, stats)), grads = grad_fn(state.params["params"])
        grads = {**jax.tree.map(jnp.zeros_like, rest), "params": grads}
        if lh:
            priorities, aux = priorities
        with jax.named_scope("optimizer"):
            updates, new_opt_state = opt.update(grads, state.opt_state,
                                                state.params)
            new_params = optax.apply_updates(state.params, updates)
            if "buffers" in rest:
                new_params = {**new_params, "buffers": step_buffers(
                    cfg, rest["buffers"], stats)}

            step = state.step + 1
            sync = (step % cfg.target_net_update_interval) == 0
            new_target = _sync_target(sync, new_params, state.target_params)

        new_state = TrainState(step=step, params=new_params,
                               target_params=new_target,
                               opt_state=new_opt_state)
        if not lh:
            return new_state, loss, priorities
        armed = (step % cfg.learnhealth_interval) == 0
        diag = jax.lax.cond(
            armed,
            lambda op: diag_fn(*op),
            lambda op: jnp.zeros((DIAG_SIZE,), jnp.float32),
            (state.params, batch, loss, grads, updates, new_params,
             new_target, aux))
        return new_state, loss, priorities, diag

    return train_step


def make_super_step_fn(cfg: Config, net: R2D2Network, k: int, gather=None,
                       learnhealth: bool = False):
    """The unjitted ``k``-fused-steps function — batches gathered in-graph
    from the device-resident replay ring (replay/device_ring.py).

    This is the latency-immune learner drivetrain: one dispatch + one small
    H2D (the (k, B, 6) index bundle) + one small D2H (stacked losses and
    priorities) amortise host↔device round trips over ``k`` optimizer
    steps, while batch bytes never cross the boundary at all.  The inner
    step is exactly ``make_train_step`` — target sync and the step counter
    advance per inner step, so k super-steps ≡ k·1 plain steps.

    ``gather(arrays, ints_t (B,6), w_t (B,)) -> batch`` defaults to the
    plain in-graph gather (GSPMD partitions it under a dp-sharded ring —
    no hand-written shard_map variant since r9).

    Signature: ``super_step(state, ring_arrays, ints (k,B,6) i32,
    is_weights (k,B) f32) -> (state, losses (k,), priorities (k,B))``
    (``learnhealth``: ``+ diags (k, DIAG_SIZE)`` — the per-inner-step
    diagnostic vectors, zeros off-cadence).  Jitted only by
    ``parallel/sharding.pjit_super_step`` (table-driven shardings; a
    1-device mesh is the single-device case).
    """
    from r2d2_tpu.replay.device_ring import gather_batch

    if gather is None:
        gather = functools.partial(gather_batch, cfg)
    lh = learnhealth and getattr(cfg, "learnhealth_interval", 0) > 0
    step = make_train_step(cfg, net, learnhealth=lh)

    def super_step(state: TrainState, arrays, ints, is_weights):
        def body(st, x):
            ints_t, w_t = x
            batch = gather(arrays, ints_t, w_t)
            if lh:
                st, loss, priorities, diag = step(st, batch)
                return st, (loss, priorities, diag)
            st, loss, priorities = step(st, batch)
            return st, (loss, priorities)

        state, ys = jax.lax.scan(body, state, (ints, is_weights))
        if lh:
            losses, priorities, diags = ys
            return state, losses, priorities, diags
        losses, priorities = ys
        return state, losses, priorities

    return super_step


def _compensated_cumsum(x):
    """Prefix sums of ``x`` (f32) with double-float (two-sum) carries —
    near-f64 accuracy, validated against an f64 oracle.  (Not "correctly
    rounded": the compensated operator is not exactly associative, so
    ``associative_scan``'s tree shapes can differ from a sequential
    double-float sum by a final-rounding ulp or two — far below stratum
    -boundary resolution, which is what the oracle tests pin.)

    The host SumTree accumulates node sums in float64
    (replay/sum_tree.py); a plain f32 ``jnp.cumsum`` over the ~50k-leaf
    flagship array accumulates O(n·eps) drift that can shift stratum
    boundaries relative to the host tree's.  Carrying the rounding error
    in a second f32 lane (error-free two-sum, folded back each step)
    removes the accumulated drift while staying pure f32 — portable to
    TPU, where f64 support is not guaranteed.  Verified 0 stratum
    -boundary disagreements vs an np.float64 oracle across seeds, incl.
    adversarial 1e-6/1e3 mixed-priority spreads at the largest per-slab
    leaf count a v5e ring holds
    (tests/test_in_graph_per.py::test_compensated_cumsum_matches_f64,
    ::test_compensated_cumsum_adversarial_spread_per_slab)."""

    def dd_add(a, b):
        ah, al = a
        bh, bl = b
        s = ah + bh
        bb = s - ah
        err = (ah - (s - bb)) + (bh - bb)
        lo = err + al + bl
        hi = s + lo
        return hi, lo - (hi - s)

    hi, _ = jax.lax.associative_scan(dd_add, (x, jnp.zeros_like(x)))
    return hi


def _in_graph_sample_raw(cfg: Config, key, prios, seq_meta, first_burn,
                         n_rows: int, constrain_rep=None):
    """``n_rows`` stratified proportional draws from a leaf slab:
    (idx (n,), q (n,) f32 inclusion densities, ints (n, 6) i32).
    The density q = prio/mass is the *raw* per-row inclusion
    probability scale — the caller turns it into IS weights (min-
    normalised over whatever scope it owns: the whole batch here, the
    pod-wide batch in the grouped/multi-host samplers).  Host twin:
    ``ReplayBuffer._grouped_densities`` (same q definition)."""
    K, L = cfg.seqs_per_block, cfg.learning_steps
    cum = _compensated_cumsum(prios)   # f64-accurate prefixes in f32
    total = cum[-1]
    u = jax.random.uniform(key, (n_rows,))
    if constrain_rep is not None:
        # mesh mode: with non-partitionable threefry, the generated BITS
        # change when GSPMD back-propagates a dp sharding onto this
        # output — pinning it replicated keeps the draw bit-identical to
        # the single-device one under every layout
        u = constrain_rep(u)
    targets = (jnp.arange(n_rows, dtype=jnp.float32) + u) * (total / n_rows)
    idx = jnp.searchsorted(cum, targets, side="right")
    idx = jnp.minimum(idx, prios.shape[0] - 1)
    idx = jnp.where(prios[idx] > 0, idx, jnp.argmax(prios))
    block_idx = idx // K
    seq_idx = (idx % K).astype(jnp.int32)
    meta = seq_meta[block_idx, seq_idx]                         # (n, 3)
    burn = meta[:, 0]
    start = first_burn[block_idx] + seq_idx * L
    ints_t = jnp.stack(
        [block_idx.astype(jnp.int32), start - burn, seq_idx, burn,
         meta[:, 1], meta[:, 2]], axis=1)
    # an all-zero slab (violates the ready-gate precondition) must not
    # emit NaN densities — clamp to 1.0; the gathered rows are zero
    # padding whose loss contribution the window masks bound anyway
    q = jnp.where(total > 0, prios[idx] / total, 1.0)
    return idx, q, ints_t


@jax.named_scope("per_sample")
def _in_graph_sample(cfg: Config, key, prios, seq_meta, first_burn,
                     constrain_rep=None):
    """One prioritized batch draw on-device: (idx (B,), is_weights (B,)
    f32, ints (B, 6) i32).

    STRATIFIED proportional sampling, the host sum-tree's exact joint
    scheme (replay/sum_tree.py:sample): the total mass splits into B
    equal strata with one uniform draw each — same variance-reduced
    batch composition, not just matching marginals — realised in-graph
    as cumsum + searchsorted instead of B tree descents.  Zero-priority
    leaves (empty slots, block padding) are zero-width cumsum bins,
    unreachable with side='right'; the float-edge fallback snaps to the
    max-priority leaf (the host's clamp guard analogue) so a scatter can
    never make padding sampleable.  IS weights are the reference scheme:
    w = (p/min sampled p)^-beta (identical to the host's, the mass
    normalisation cancels).  The ints bundle reproduces ``sample_meta``'s
    index arithmetic (replay_buffer.py:372-390) from the device-resident
    metadata, so ``gather_batch`` sees identical inputs either way."""
    idx, q, ints_t = _in_graph_sample_raw(
        cfg, key, prios, seq_meta, first_burn, cfg.batch_size,
        constrain_rep=constrain_rep)
    w = (q / q.min()) ** (-cfg.importance_sampling_exponent)
    return idx, w.astype(jnp.float32), ints_t


def make_in_graph_per_super_step_fn(cfg: Config, net: R2D2Network, k: int,
                                    constrain=None,
                                    replicate_for_draw=None,
                                    learnhealth: bool = False):
    """``k`` fused steps with DEVICE-side PER: sample → gather → step →
    priority scatter, all inside one dispatch.

    vs :func:`make_super_step_fn` (host-sampled bundles): the learner
    loop no longer round-trips priorities through the host at all, so
    the dispatch cadence becomes pure device compute.  It is also *tighter*
    feedback than the reference's queue (worker.py:300-316 lags 8+4
    batches) or our host path (lags ≥ k): step j+1 samples from the
    priorities step j just wrote.

    Signature: ``super_step(state, ring_arrays, prios (NB*K,) f32
    [donated], seq_meta (NB,K,3) i32, first_burn (NB,) i32,
    dispatch_idx u32) -> (state, prios', losses (k,))``
    (``learnhealth``: ``+ diags (k, DIAG_SIZE)``).  The sampling
    stream is ``fold_in(PRNGKey(cfg.seed), dispatch_idx)`` — distinct per
    dispatch with no seed/counter bit-packing to alias or overflow.
    Jitted only by ``parallel/sharding.pjit_in_graph_per_super_step``.
    """
    from r2d2_tpu.replay.device_ring import gather_batch

    lh = learnhealth and getattr(cfg, "learnhealth_interval", 0) > 0
    step = make_train_step(cfg, net, learnhealth=lh)

    def super_step(state: TrainState, arrays, prios, seq_meta, first_burn,
                   dispatch_idx):
        keys = jax.random.split(
            jax.random.fold_in(jax.random.PRNGKey(cfg.seed), dispatch_idx),
            k)

        def body(carry, key_t):
            st, p = carry
            # mesh mode: the draw runs over a REPLICATED view of the
            # priority leaves — _compensated_cumsum's associative_scan
            # changes tree shape (and so its final-ulp rounding) when
            # GSPMD partitions it, and an ulp at a stratum boundary
            # flips which slot that stratum draws.  Replicating the
            # (leaves,)-sized scan makes the draw bit-identical under
            # every layout for pennies; the gather/forward stay sharded.
            p_draw = p if replicate_for_draw is None else (
                replicate_for_draw(p))
            idx, w, ints_t = _in_graph_sample(
                cfg, key_t, p_draw, seq_meta, first_burn,
                constrain_rep=replicate_for_draw)
            if constrain is not None:
                # mesh mode: the (replicated) sampled bundle's batch rows
                # are pinned to dp here, so GSPMD shards the gather and
                # the forward/backward over the mesh exactly as the
                # host-sampled path's dp-sharded H2D bundles do
                ints_t, w = constrain(ints_t, w)
            batch = gather_batch(cfg, arrays, ints_t, w)
            if lh:
                st, loss, new_p, diag = step(st, batch)
            else:
                st, loss, new_p = step(st, batch)
            # feedback: same exponentiation the host tree applies
            # (sum_tree.py:60); duplicate-idx writes resolve arbitrarily,
            # as does the host's sequential last-wins — both harmless
            with jax.named_scope("per_scatter"):
                p = p.at[idx].set(new_p ** cfg.prio_exponent)
            return (st, p), ((loss, diag) if lh else loss)

        (state, prios), ys = jax.lax.scan(body, (state, prios), keys)
        if lh:
            losses, diags = ys
            return state, prios, losses, diags
        return state, prios, ys

    return super_step
