"""Learnhealth overhead A/B: disarmed-vs-armed, baseline-vs-PR.

The learnhealth plane's contract is "free when off, quantified when on":

- **disarmed** (``learnhealth_interval=0``, the default) must compile
  the exact pre-learnhealth program (no diag outputs at all) and cost
  nothing — verified here by interleaved baseline-vs-PR cells where the
  baseline side is a ``git worktree`` of HEAD (the pre-PR tree, the
  TRACE_r11 A/B convention);
- **armed** cadences pay the ΔQ re-unroll + norms only on armed steps —
  the ``interval=8`` / ``interval=64`` cells quantify that cost against
  the disarmed cell of the SAME tree.

Cells (each a fresh subprocess so XLA state never leaks across sides,
interleaved base/PR/base/PR so host-load drift hits both sides):

- ``pjit``   — the unified pjit train step (a B=64 MLP geometry), median
  ms/step over fenced reps;
- ``anakin`` — the fused on-device super-step, updates/s.

Output: ``artifacts/r14/LEARNHEALTH_AB_r14.json`` (cells + medians +
ratios).  The cells run on whatever platform the environment selects
(``JAX_PLATFORMS``) and every cell names it; the recorded r14 numbers
are CPU-host timings, not device numbers.

Run from the repo root with the PR in the working tree and the pre-PR
commit at HEAD:  ``python tools/learnhealth_ab.py [--reps N]``
"""
import datetime
import json
import os
import statistics
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

OUT = os.path.join(REPO, "artifacts/r14/LEARNHEALTH_AB_r14.json")


# one cell per subprocess.  argv: <kind> <interval>  (interval "-1" =
# the tree has no learnhealth knob, i.e. the baseline worktree).  The
# script only touches APIs both trees share.
_CELL_SRC = r"""
import json, os, sys, time
import jax, numpy as np, jax.numpy as jnp
kind, interval = sys.argv[1], int(sys.argv[2])
from r2d2_tpu.config import test_config
from r2d2_tpu.models.network import create_network, init_params
from r2d2_tpu.learner.step import create_train_state
A = 4
kw = {}
if interval >= 0:
    kw["learnhealth_interval"] = interval
if kind == "pjit":
    from r2d2_tpu.parallel.mesh import make_mesh
    from r2d2_tpu.parallel.sharding import (ShardingTable, pjit_train_step,
                                            shard_batch)
    from r2d2_tpu.utils.batch import synthetic_batch
    cfg = test_config(batch_size=64, hidden_dim=128, torso="mlp",
                      obs_shape=(24, 24, 1), burn_in_steps=8,
                      learning_steps=8, forward_steps=2, **kw)
    net = create_network(cfg, A)
    params = init_params(cfg, net, jax.random.PRNGKey(0))
    state = create_train_state(cfg, params)
    mesh = make_mesh(cfg)
    table = ShardingTable(mesh, cfg)
    step = pjit_train_step(cfg, net, table, state_template=state,
                           donate_batch=False)
    st = table.place_state(state)
    batch = shard_batch(table, synthetic_batch(
        cfg, A, np.random.default_rng(0)))
    for _ in range(5):
        out = step(st, batch)
        st, loss = out[0], out[1]
    float(jax.device_get(loss))
    times = []
    for _ in range(30):
        t0 = time.perf_counter()
        out = step(st, batch)
        st, loss = out[0], out[1]
        float(jax.device_get(loss))     # fence: full fwd/bwd data-dep
        times.append(time.perf_counter() - t0)
    ms = float(np.median(times)) * 1000
    print(json.dumps(dict(kind=kind, interval=interval,
                          platform=jax.devices()[0].platform,
                          step_ms=round(ms, 3),
                          steps_per_sec=round(1000.0 / ms, 2))))
else:
    from r2d2_tpu.envs.anakin import AnakinFakeEnv
    from r2d2_tpu.learner.anakin import (make_anakin_state,
                                         make_anakin_super_step)
    from r2d2_tpu.replay.device_ring import DeviceRing
    cfg = test_config(
        game_name="Fake", actor_transport="anakin", num_actors=8,
        device_replay=True, in_graph_per=True, superstep_k=4,
        block_length=64, max_episode_steps=10 ** 9,
        anakin_episode_len=512, buffer_capacity=64 * 32,
        burn_in_steps=8, learning_steps=8, forward_steps=2,
        batch_size=16, hidden_dim=64, torso="mlp", obs_shape=(24, 24, 1),
        **kw)
    net = create_network(cfg, A)
    params = init_params(cfg, net, jax.random.PRNGKey(0))
    state = create_train_state(cfg, params)
    ring = DeviceRing(cfg, A)
    env = AnakinFakeEnv(obs_shape=cfg.stored_obs_shape, action_dim=A,
                        episode_len=cfg.anakin_episode_len,
                        num_lanes=cfg.num_actors)
    ast = make_anakin_state(cfg, A, env, jax.random.PRNGKey(1))
    fn = make_anakin_super_step(cfg, net, env, A)
    meta = ring.per_meta()
    args = (state, ast, ring.snapshot(), ring.take_prios(),
            meta["seq_meta"], meta["first"])
    WARM, REPS = 5, 25
    n_disp, t0, flat = 0, None, None
    for i in range(WARM + REPS):
        out = fn(*args, jnp.uint32(i))
        args, flat = out[:-1], out[-1]
        if i + 1 == WARM:
            np.asarray(flat)
            t0 = time.perf_counter()
        elif i >= WARM:
            n_disp += 1
    np.asarray(flat)
    dt = time.perf_counter() - t0
    ups = n_disp * cfg.superstep_k / dt
    print(json.dumps(dict(kind=kind, interval=interval,
                          platform=jax.devices()[0].platform,
                          updates_per_sec=round(ups, 2),
                          dispatch_ms=round(dt / n_disp * 1000, 2))))
"""


def run_cell(tree: str, kind: str, interval: int) -> dict:
    env = dict(os.environ, PYTHONPATH=tree)
    p = subprocess.run([sys.executable, "-c", _CELL_SRC, kind,
                       str(interval)], cwd=tree, env=env, timeout=900,
                       capture_output=True, text=True)
    if p.returncode != 0:
        raise RuntimeError(f"cell {kind}/{interval} in {tree} failed:\n"
                           + p.stderr[-4000:])
    out = json.loads(p.stdout.strip().splitlines()[-1])
    print(f"  {os.path.basename(tree) or 'repo'} {kind} "
          f"interval={interval}: {out}", flush=True)
    return out


def main() -> int:
    reps = 3
    if "--reps" in sys.argv:
        reps = int(sys.argv[sys.argv.index("--reps") + 1])
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with tempfile.TemporaryDirectory(prefix="lh_base_") as base_tree:
        subprocess.run(["git", "worktree", "add", "--detach",
                        base_tree, "HEAD"], cwd=REPO, check=True,
                       capture_output=True)
        try:
            # (tree, label, interval): baseline has no learnhealth knob
            variants = [
                (base_tree, "base_off", -1),
                (REPO, "pr_off", 0),
                (REPO, "pr_armed_64", 64),
                (REPO, "pr_armed_8", 8),
            ]
            cells = {f"{kind}.{label}": []
                     for kind in ("pjit", "anakin")
                     for _, label, _ in variants}
            for rep in range(reps):
                print(f"rep {rep + 1}/{reps}", flush=True)
                for kind in ("pjit", "anakin"):
                    # interleaved: every variant runs inside the same
                    # host-load window each rep
                    for tree, label, interval in variants:
                        cells[f"{kind}.{label}"].append(
                            run_cell(tree, kind, interval))
        finally:
            subprocess.run(["git", "worktree", "remove", "--force",
                            base_tree], cwd=REPO, capture_output=True)

    def med(name, field):
        return statistics.median(c[field] for c in cells[name])

    summary = dict(
        generated_at=datetime.datetime.now().strftime(
            "%Y-%m-%d %H:%M:%S"),
        host_cpus=os.cpu_count(), reps=reps,
        cells=cells,
        medians=dict(
            pjit_ms={lbl: med(f"pjit.{lbl}", "step_ms")
                     for _, lbl, _ in
                     (("", "base_off", 0), ("", "pr_off", 0),
                      ("", "pr_armed_64", 0), ("", "pr_armed_8", 0))},
            anakin_ups={lbl: med(f"anakin.{lbl}", "updates_per_sec")
                        for lbl in ("base_off", "pr_off", "pr_armed_64",
                                    "pr_armed_8")},
        ),
    )
    m = summary["medians"]
    summary["ratios"] = dict(
        # disarmed PR vs pre-PR baseline — must be ~1.0 (below noise)
        pjit_disarmed_vs_base=round(
            m["pjit_ms"]["pr_off"] / m["pjit_ms"]["base_off"], 4),
        anakin_disarmed_vs_base=round(
            m["anakin_ups"]["base_off"] / m["anakin_ups"]["pr_off"], 4),
        # armed cadence cost vs the disarmed PR program
        pjit_armed8_vs_off=round(
            m["pjit_ms"]["pr_armed_8"] / m["pjit_ms"]["pr_off"], 4),
        pjit_armed64_vs_off=round(
            m["pjit_ms"]["pr_armed_64"] / m["pjit_ms"]["pr_off"], 4),
        anakin_armed8_vs_off=round(
            m["anakin_ups"]["pr_off"]
            / m["anakin_ups"]["pr_armed_8"], 4),
        anakin_armed64_vs_off=round(
            m["anakin_ups"]["pr_off"]
            / m["anakin_ups"]["pr_armed_64"], 4),
    )
    with open(OUT, "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(dict(medians=summary["medians"],
                          ratios=summary["ratios"]), indent=1))
    print(f"wrote {OUT}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
