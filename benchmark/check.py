"""The comparison that decides ``correct``: the program's Q-values and loss
against the configuration's plain float32 reference, at the published
widths, on the device the cell ran on, outside the measured window.

A run compares ``DRAWS`` seeded draws of weights and batch and pools them:
at random weights a Q-value is a small difference of large terms, and one
draw's relative error swings with the draw (0.4 to 2.9 % rms over six seeds
in ``impala_deep_lstm2``; my chip runs, PR 24), which a limit can only cover
by standing far off.  ``python3 benchmark/check.py --config <name>`` prints
what the limits stand on."""
from __future__ import annotations

from typing import Any, Dict, List

from benchmark.manifest import BENCH_DIR, find_module

SEQUENCES = 4           # windows in a draw's batch
DRAWS = 4               # seeded draws of weights and batch pooled in a run
DRAW_STRIDE = 104729    # between the draws' seeds


def seeded_state(cfg, n: int, rng):
    """``n`` small seeded recurrent states, in the shape and tree of the
    program's own ``zero_hidden(cfg, n)``: whatever the network's memory
    core keeps, the harness does not restate it.  Leaves are drawn in the
    tree's order."""
    import jax
    import numpy as np

    from r2d2_tpu.models.network import zero_hidden

    return jax.tree.map(
        lambda leaf: (0.1 * rng.normal(size=leaf.shape)).astype(
            np.dtype(leaf.dtype)),
        jax.eval_shape(lambda: zero_hidden(cfg, n)))


def seeded_batch(cfg, action_dim: int, seed: int) -> Dict[str, Any]:
    """Four windows of the configuration's own lengths; the last one's
    episode ends early, so the mask and the clamped bootstrap are used."""
    import numpy as np

    rng = np.random.default_rng(seed)
    B, T, L = SEQUENCES, cfg.seq_len, cfg.learning_steps
    la = np.zeros((B, T, action_dim), np.float32)
    la[np.arange(B)[:, None], np.arange(T)[None, :],
       rng.integers(action_dim, size=(B, T))] = 1.0
    learning = np.full(B, L, np.int32)
    forward = np.full(B, cfg.forward_steps, np.int32)
    learning[-1], forward[-1] = L - 3, max(1, cfg.forward_steps // 2)
    return dict(
        obs=rng.integers(0, 256, (B, T, *cfg.stored_obs_shape), np.uint8),
        last_action=la,
        last_reward=rng.integers(0, 2, (B, T)).astype(np.float32),
        hidden=seeded_state(cfg, B, rng),
        action=rng.integers(action_dim, size=(B, L)).astype(np.int32),
        n_step_reward=rng.random((B, L)).astype(np.float32) * 3.0,
        n_step_gamma=np.full((B, L), cfg.gamma ** cfg.forward_steps,
                             np.float32),
        is_weights=rng.random(B).astype(np.float32) + 0.5,
        burn_in=np.full(B, cfg.burn_in_steps, np.int32),
        learning=learning, forward=forward)


def network(cfg, action_dim: int):
    """The network the loss unrolls (the scan recurrence, as the learner's
    own loss does)."""
    from r2d2_tpu.models.network import create_network

    return create_network(cfg.replace(lstm_impl="scan"), action_dim)


def seeded_params(cfg, net, seed: int):
    """Online and target weights from a seed."""
    import jax

    from r2d2_tpu.models.network import init_params

    key = jax.random.PRNGKey(seed)
    return (init_params(cfg, net, key),
            init_params(cfg, net, jax.random.fold_in(key, 1)))


def make_program(cfg, net):
    """``(params, target, batch) -> (loss, Q over the learning steps)``
    through the program's own network and loss, in the configuration's
    compute type; jitted once for all draws."""
    import jax

    from r2d2_tpu.learner.step import loss_and_priorities
    from r2d2_tpu.models.network import R2D2Network

    @jax.jit
    def program(params, target, batch):
        q, _ = net.apply(params, batch["obs"], batch["last_action"],
                         batch["last_reward"], batch["hidden"],
                         method=R2D2Network.unroll)
        loss, _ = loss_and_priorities(cfg, net, params, target, batch)
        b = cfg.burn_in_steps
        return loss, q[:, b:b + cfg.learning_steps]

    return program


def reference_outputs(config_name: str, cfg, params, target, batch,
                      bench_dir: str = BENCH_DIR):
    """The configuration's plain reference, ``reference/<config>.py``,
    over the same weights and batch."""
    import jax
    import jax.numpy as jnp

    ref = find_module("reference", config_name, bench_dir)
    with jax.default_matmul_precision("highest"):
        dev = {k: (v if k in ("burn_in", "learning", "forward")
                   else jax.tree.map(jnp.asarray, v))
               for k, v in batch.items()}
        return jax.device_get(ref.loss(params, target, dev,
                                       cfg.forward_steps))


def coarse_weights(x):
    """A weight kept at 3 bits of mantissa, an 8-bit float's (e4m3): the
    mildest 8-bit path there is — activations and sums stay as they were."""
    import jax.numpy as jnp

    m, e = jnp.frexp(x.astype(jnp.float32))
    return jnp.ldexp(jnp.round(m * 16) / 16, e)


def errors(pairs) -> Dict[str, float]:
    """Pooled over the draws, ``pairs`` of ``((loss, q) of the program,
    (loss, q) of the reference)``.  Q-values: the largest difference over
    the largest value (``q_rel``) and the root of the summed squared
    differences over the root of the summed squared values (``q_rms_rel``,
    which one outlying element does not move); loss: summed absolute
    differences over summed absolute values."""
    import numpy as np

    diffs = [np.asarray(q_p, np.float64) - q_r
             for (_, q_p), (_, q_r) in pairs]
    refs = [np.asarray(q_r, np.float64) for _, (_, q_r) in pairs]
    return dict(
        q_rel=float(max(np.abs(d).max() for d in diffs)
                    / max(np.abs(r).max() for r in refs)),
        q_rms_rel=float(np.sqrt(sum((d ** 2).sum() for d in diffs)
                                / sum((r ** 2).sum() for r in refs))),
        loss_rel=float(sum(abs(float(p[0]) - float(r[0])) for p, r in pairs)
                       / sum(abs(float(r[0])) for _, r in pairs)))


def draws(config_name: str, cfg, action_dim: int, seed: int,
          weights=None, count: int = DRAWS, bench_dir: str = BENCH_DIR):
    """``count`` seeded draws of weights and batch, each through the program
    and the reference; ``weights`` (optional) is applied to the program's
    copy of the weights only."""
    import jax

    net = network(cfg, action_dim)
    program = make_program(cfg, net)
    pairs = []
    for i in range(count):
        s = (seed + DRAW_STRIDE * i) % (2 ** 31 - 1)
        params, target = seeded_params(cfg, net, s)
        batch = seeded_batch(cfg, action_dim, s)
        shown = ((params, target) if weights is None
                 else jax.tree.map(weights, (params, target)))
        pairs.append((jax.device_get(program(*shown, batch)),
                      reference_outputs(config_name, cfg, params, target,
                                        batch, bench_dir)))
    return pairs


COMPARED = (("q_rel", "the largest Q-value difference is"),
            ("q_rms_rel", "the rms Q-value difference is"),
            ("loss_rel", "the loss differs from the reference by"))


def over_limit(out: Dict[str, float], tolerance: Dict[str, Any]) -> List[str]:
    """The numbers that pass their limit.  A number the configuration's
    ``tolerance`` gives no limit is not compared: one whose sound readings
    and whose control's do not lie three times apart can only fail sound
    runs, and the file says so with the readings (PERF.md §6, PR 27)."""
    return [f"{what} {out[key]:.4g} (limit {tolerance[key]})"
            for key, what in COMPARED
            if key in tolerance and not out[key] <= tolerance[key]]


def compare(config_name: str, cfg, tolerance: Dict[str, Any],
            action_dim: int, seed: int,
            bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    """``{"problems": [...], "q_rel": ..., "loss_rel": ...}``."""
    import numpy as np

    pairs = draws(config_name, cfg, action_dim, seed, bench_dir=bench_dir)
    out = errors(pairs)
    problems: List[str] = []
    if not all(np.isfinite(q).all() and np.isfinite(loss)
               for (loss, q), _ in pairs):
        problems.append("the program's Q-values or loss are not finite")
    problems += over_limit(out, tolerance)
    return dict(out, problems=problems)


def main(argv=None) -> int:
    """What the tolerance stands on: the configuration as it is run, and
    with its weights kept at 8 bits, against the reference, at the published
    widths on the device at hand.  One JSON line."""
    import argparse
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    p = argparse.ArgumentParser(description=main.__doc__)
    p.add_argument("--config", required=True)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    import jax

    from benchmark.drivers.train import ACTION_DIM, config_from_file, seed32
    from benchmark.manifest import Manifest

    doc = Manifest(root).config(args.config)
    cfg = config_from_file(doc["config"])
    seed = seed32(args.seed)
    print(json.dumps(dict(
        config=args.config, seed=args.seed, draws=DRAWS,
        platform=jax.devices()[0].platform, tolerance=doc["tolerance"],
        as_configured=errors(draws(args.config, cfg, ACTION_DIM, seed)),
        weights_at_3_mantissa_bits=errors(draws(
            args.config, cfg, ACTION_DIM, seed, weights=coarse_weights)))))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
