"""Single-chip benchmark: learner step, actor plane, and the full system.

Three measurements on the default JAX platform, which must be an
accelerator — a CPU is refused (a CPU timing is not a device number):

1. **Learner micro-bench** — the jitted R2D2 train step on the flagship
   config (Nature torso, LSTM-512, batch 64, T=85 — reference scale knobs,
   config.py:7,27-33) with a pre-staged device batch.  This is the
   compute ceiling.  XLA's compiled-module cost analysis grounds it in
   hardware terms (``achieved_tflops``, ``mfu``).
2. **Actor-plane bench** — a 64-lane VectorActor (pong preset scale,
   BASELINE configs[1]) stepping fake envs with batched TPU inference;
   must sustain at least the learner's env-frame consumption rate to not
   starve it (the reference gets this from N actor processes,
   train.py:30-34).
3. **System bench** — the full threaded fabric (``train.train``: actors →
   replay → prioritized sampling → H2D prefetch → learner, priority
   feedback) on fake envs for a fixed wall budget; reports steady-state
   ``updates/s × batch × learning_steps`` and the busiest tracer spans so
   the bottleneck is named, not guessed.

Prints ONE JSON line; the headline metric stays
``learner_env_frames_per_sec`` (vs the 50k frames/s/chip north star),
with the system/actor/MFU numbers as additional fields and the device
(platform, ``device_kind``, count) it ran on.  Any phase error makes the
exit code non-zero, on both entry paths.
"""
from __future__ import annotations

import json
import os
import sys
import time
from typing import Optional

import numpy as np

from r2d2_tpu.utils.batch import synthetic_batch as make_batch

NORTH_STAR_FPS = 50_000.0

# bf16 peak TFLOPS by device_kind prefix (public spec sheets); used for MFU.
# A device missing from this table is an error, not a default.
_PEAK_TFLOPS = (
    ("TPU v5 lite", 197.0),   # v5e
    ("TPU v5p", 459.0),
    ("TPU v4", 275.0),
    ("TPU v6", 918.0),        # Trillium
)


# the flagship system-bench cell (the learning presets' knobs — k=4 after
# the CURVES_AB_PIPELINE_r04 lag A/B); shared by both bench entry paths so
# script-mode and import-mode always measure the same fabric
FLAGSHIP_SYSTEM_KNOBS = dict(device_replay=True, superstep_k=4,
                             superstep_pipeline=2, num_actors=64,
                             env_workers=0)


def _peak_tflops(kind: str) -> float:
    for prefix, peak in _PEAK_TFLOPS:
        if kind.startswith(prefix):
            return peak
    raise ValueError(
        f"device_kind {kind!r} is not in bench._PEAK_TFLOPS — add its "
        "published bf16 peak (with its source) before benchmarking on it")


def _device_facts() -> dict:
    """The device as JAX reports it; every result names it."""
    import jax

    devices = jax.devices()
    return dict(platform=devices[0].platform,
                device_kind=devices[0].device_kind,
                device_count=len(devices))


def _learner_micro_bench(steps: int, warmup: int, fused: bool = False):
    """(frames/s, steps/s, flops_per_step_or_0) for the flagship step.

    ``fused=True`` times the same step with ``fused_double_unroll`` — the
    single double-batch online+target unroll (learner/step.py) — so the
    feature's value is a measured train-step cell, not an extrapolation
    from the B=64/B=128 unroll ratio."""
    import jax

    from r2d2_tpu.config import Config
    from r2d2_tpu.learner.step import create_train_state
    from r2d2_tpu.models.network import create_network, init_params
    from r2d2_tpu.parallel.sharding import pjit_train_step

    cfg = Config(fused_double_unroll=fused)
    action_dim = 9  # MsPacman minimal action set
    net = create_network(cfg, action_dim)
    params = init_params(cfg, net, jax.random.PRNGKey(0))
    state = create_train_state(cfg, params)
    # donate_batch=False: this timing loop deliberately re-steps ONE
    # device-resident batch; the training drivetrains always donate
    step_fn = pjit_train_step(cfg, net, state_template=state,
                              donate_batch=False)

    rng = np.random.default_rng(0)
    batch = {k: jax.device_put(v) for k, v in make_batch(cfg, action_dim,
                                                         rng).items()}

    # AOT compile once; the timing loops run the same executable (jit
    # __call__ would compile a second copy of this multi-second module).
    # cost_analysis gives XLA's own FLOP count for it — grounded, not hand
    # derived.
    step_fn = step_fn.lower(state, batch).compile()
    flops = float(step_fn.cost_analysis().get("flops", 0.0))

    for _ in range(warmup):
        state, loss, priorities = step_fn(state, batch)
    jax.block_until_ready(state)

    t0 = time.perf_counter()
    for _ in range(steps):
        state, loss, priorities = step_fn(state, batch)
    # the last loss data-depends on every chained step through the
    # donated state: blocking on it (and the state) fences the window
    jax.block_until_ready((state, loss))
    dt = time.perf_counter() - t0
    final_loss = float(loss)
    assert np.isfinite(final_loss), f"non-finite loss {final_loss}"

    steps_per_sec = steps / dt
    frames_per_sec = cfg.batch_size * cfg.learning_steps * steps_per_sec
    return frames_per_sec, steps_per_sec, flops


def _actor_plane_bench(iterations: int = 400, num_lanes: int = 64,
                       env_workers: Optional[int] = None,
                       act_device: Optional[str] = None,
                       fleets: int = 1):
    """env-frames/s of a pong-scale lockstep fleet on fake envs.

    ``env_workers``/``act_device``/``fleets`` override the preset so
    tools/actor_scaling.py and the measurement battery can sweep the
    env-stepping pool width, CPU-twin vs on-device acting, and the number
    of independent lockstep fleets (lanes split contiguously, each fleet
    its own thread — exactly train.py's actor_fleets split)."""
    import threading

    import jax

    from r2d2_tpu.actor import VectorActor, make_act_fn
    from r2d2_tpu.config import pong_config
    from r2d2_tpu.envs.fake import FakeAtariEnv
    from r2d2_tpu.models.network import create_network, init_params
    from r2d2_tpu.utils.math import epsilon_ladder
    from r2d2_tpu.utils.store import ParamStore

    over = {}
    if env_workers is not None:
        over["env_workers"] = env_workers
    if act_device is not None:
        over["act_device"] = act_device
    cfg = pong_config(game_name="Fake", num_actors=num_lanes, **over)
    net = create_network(cfg, 4)
    params = init_params(cfg, net, jax.random.PRNGKey(0))
    store = ParamStore(params)
    act_fn = make_act_fn(cfg, net)
    sunk = []
    per = num_lanes // fleets
    actors = []
    for f in range(fleets):
        lanes = range(f * per, (f + 1) * per)
        envs = [FakeAtariEnv(obs_shape=cfg.stored_obs_shape, action_dim=4,
                             seed=i, episode_len=500) for i in lanes]
        eps = [epsilon_ladder(i, num_lanes) for i in lanes]
        actors.append(VectorActor(cfg, envs, eps, act_fn, store,
                                  sink=lambda b, p, r: sunk.append(1),
                                  rng=np.random.default_rng(1 + f)))
    for a in actors:
        a.run(max_steps=20)  # warmup: compile act fn, prime pools
    # bare Threads by design: these are bounded measurement workers, started
    # and joined inside this one timed window — a Supervisor restart would
    # silently rerun part of the workload and corrupt the timing
    threads = [threading.Thread(target=a.run,  # graftlint: disable=thread-discipline -- bounded, joined below; a restart would corrupt the measurement
                                kwargs=dict(max_steps=iterations))
               for a in actors[1:]]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    actors[0].run(max_steps=iterations)
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    for a in actors:
        a.close()
    return fleets * per * iterations / dt


def _bench_env_factory(cfg, seed):
    """Module-level (picklable) fake-env factory: the process-transport
    bench's spawn children unpickle it by reference."""
    from r2d2_tpu.envs.fake import FakeAtariEnv

    return FakeAtariEnv(obs_shape=cfg.stored_obs_shape, action_dim=4,
                        seed=seed, episode_len=500)


def _actor_plane_bench_process(num_lanes: int = 64, fleets: int = 2,
                               env_workers: int = 0,
                               budget_s: float = 300.0,
                               actor_inference: str = "local"):
    """env-frames/s of the PROCESS-fleet actor plane on fake envs — the
    same pong-scale workload as :func:`_actor_plane_bench`, through
    ``parallel/actor_procs`` instead of in-process threads, so
    tools/actor_scaling.py can put the thread-vs-process per-core slopes
    side by side.  ``actor_inference="serve"`` measures the centralized
    InferenceService path (ISSUE 3): fleets RPC a trainer-side act server
    that batches across all of them, driven here by a dedicated serve
    thread standing in for the fabric's ``inference_serve`` loop.

    The trainer only observes block-granular arrivals, and a lockstep
    fleet cuts ALL its lanes' blocks in the same iteration — arrivals are
    periodic BURSTS (strictly alternating 400-step boundary cuts and
    episode-truncation cuts at the fake env's 500-step episodes), so a
    fixed wall window aliases against the burst phase.  Instead, per
    fleet, frames are timed from the start of burst 0 to the start of
    burst 2 — a stride of 2 spans exactly one full 500-step cut cycle —
    which is phase-exact; the fleet rates sum to the plane rate.  Burst
    boundaries are identified by COUNT, not wall-clock gaps (every burst
    is exactly one block per lane, in order), so the alignment holds at
    any host speed.  Children's jax-import + act-fn compile happens
    before their first burst and is never charged."""
    import jax

    from r2d2_tpu.config import pong_config
    from r2d2_tpu.models.network import create_network, init_params
    from r2d2_tpu.parallel.actor_procs import ProcessFleetPlane
    from r2d2_tpu.utils.math import epsilon_ladder
    from r2d2_tpu.utils.store import ParamStore

    import threading

    cfg = pong_config(game_name="Fake", num_actors=num_lanes,
                      env_workers=env_workers, actor_fleets=fleets,
                      actor_transport="process",
                      actor_inference=actor_inference)
    net = create_network(cfg, 4)
    store = ParamStore(init_params(cfg, net, jax.random.PRNGKey(0)))
    eps = [epsilon_ladder(i, num_lanes) for i in range(num_lanes)]
    plane = ProcessFleetPlane(cfg, 4, _bench_env_factory, eps)
    F = plane.num_fleets
    serve_stop = threading.Event()
    # Supervisor-managed stand-in for the fabric's ``inference_serve``
    # loop: serve_once is re-enterable (pending requests live in service
    # state), so a crash restarts cleanly instead of wedging every
    # blocked fleet — same discipline train() gives the real loop
    serve_sup = None
    if plane.service is not None:
        from r2d2_tpu.utils.supervisor import Supervisor

        serve_sup = Supervisor(max_restarts=3)

        def _serve_loop():
            while not serve_stop.is_set():
                plane.service.serve_once()
    # a burst = one block per lane, so burst k starts at event index k*L
    lanes = [spec.hi - spec.lo for spec in plane.specs]
    need = [2 * L + 1 for L in lanes]     # through burst 2's first block
    events = [[] for _ in range(F)]       # per fleet: (t, frames)

    def noop_sink(block, prios, episode_reward):
        pass

    try:
        plane.start(store)
        if serve_sup is not None:
            serve_sup.start("bench_serve", _serve_loop)
        deadline = time.time() + budget_s
        while (time.time() < deadline
               and any(len(ev) < n for ev, n in zip(events, need))):
            got = plane.ingest_once(noop_sink, timeout=0.2)
            if got is None:
                continue
            src, n = got
            events[src].append((time.perf_counter(), n))
    finally:
        # stop and JOIN the serve thread BEFORE plane.shutdown closes the
        # act channels: a mid-iteration serve_once still holds slab views,
        # and SharedMemory.close under live views raises BufferError
        serve_stop.set()
        if serve_sup is not None:
            serve_sup.join_all(10)
        plane.shutdown()

    rate = 0.0
    for src in range(F):
        ev, L = events[src], lanes[src]
        if len(ev) < need[src]:
            raise RuntimeError(
                f"fleet{src} produced {len(ev)}/{need[src]} blocks in "
                f"{budget_s:.0f} s; need one full cut cycle for a "
                "phase-exact window")
        frames = sum(n for _, n in ev[0:2 * L])
        rate += frames / (ev[2 * L][0] - ev[0][0])
    return rate


def _system_bench(wall_seconds: float, *, device_replay: bool = True,
                  superstep_k: int = 4, num_actors: int = 64,
                  env_workers: int = 0, superstep_pipeline: int = 2,
                  in_graph_per: bool = False):
    """Steady-state env-frames/s of the full threaded fabric on fake envs.

    Returns (frames/s, top_spans, num_updates) where top_spans names the
    busiest tracer stages (the measured bottleneck).  The keyword knobs
    let tools/tune_system.py sweep the same measurement over a grid."""
    from r2d2_tpu.config import Config
    from r2d2_tpu.train import train

    cfg = Config().replace(
        game_name="Fake",
        num_actors=num_actors,
        env_workers=env_workers,
        buffer_capacity=200_000,   # 500-block ring ≈ 1.6 GB (in HBM)
        learning_starts=10_000,
        training_steps=1_000_000_000,  # wall-clock bound, not step bound
        log_interval=5.0,
        save_interval=1_000_000_000,
        device_replay=device_replay,  # HBM-resident ring + in-graph gather
        superstep_k=superstep_k,      # optimizer steps per dispatch — the
                                      # pong/hard-exploration presets' value
                                      # (k=4 since the CURVES_AB_PIPELINE_r04
                                      # lag A/B), so the system number
                                      # measures what the learning configs
                                      # actually run; tools/tune_system.py
                                      # sweeps the grid for the ceiling
        in_graph_per=in_graph_per,    # device-resident PER: zero host
                                      # round trips on the training path
        superstep_pipeline=superstep_pipeline,  # in-flight dispatches:
                                      # result copies start at enqueue, so
                                      # >=2 keeps the device busy while
                                      # results trail
    )
    metrics = train(cfg, max_wall_seconds=wall_seconds, verbose=False)

    # steady state: median updates/s over the logged entries after the
    # buffer reached learning_starts (those report nonzero rates)
    rates = [e["updates_per_sec"] for e in metrics.get("logs", [])
             if e["updates_per_sec"] > 0]
    ups = float(np.median(rates[-6:])) if rates else 0.0
    frames_per_sec = ups * cfg.batch_size * cfg.learning_steps

    trace = metrics.get("trace", {})
    spans = sorted(
        ((name[len("span."):-len(".mean_ms")],
          trace[name] * trace.get(name.replace(".mean_ms", ".count"), 0))
         for name in trace if name.endswith(".mean_ms")),
        key=lambda kv: -kv[1])
    top_spans = {name: round(total_ms, 1) for name, total_ms in spans[:5]}
    return frames_per_sec, top_spans, metrics.get("num_updates", 0)


def _device_probe(timeout_s: float = 240.0):
    """Find the accelerator from a bounded subprocess, so that the parent
    never initialises a backend (one process holds a chip at a time, and
    the phases are children).

    Returns ``(device, reason)``: ``device`` is :func:`_device_facts` of
    the child, or None with the reason — a probe that failed, timed out,
    or found only a CPU (JAX falls through to the CPU when no accelerator
    initialises; a benchmark must not), or found a ``device_kind`` with no
    published peak in :data:`_PEAK_TFLOPS`."""
    import subprocess

    code = ("import json; from r2d2_tpu.bench import _device_facts; "
            "print(json.dumps(_device_facts()))")
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        proc = subprocess.run([sys.executable, "-c", code], cwd=repo_root,
                              capture_output=True, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return None, f"device probe timed out after {timeout_s:.0f}s"
    tail = proc.stderr.decode(errors="replace").strip().splitlines()
    if proc.returncode != 0:
        return None, (f"device probe failed (rc={proc.returncode}): "
                      + " | ".join(tail[-3:]))
    device = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    if device["platform"] == "cpu":
        return None, "JAX found no accelerator (platform 'cpu')"
    try:
        _peak_tflops(device["device_kind"])
    except ValueError as e:
        return None, str(e)
    return device, ""


def _run_phase(phase: str, timeout_s: float, extra=(), label=None):
    """Run one bench phase as a bounded subprocess; (result_dict, reason).

    Each phase holds the chip alone and releases it on exit; a phase that
    hangs is killed at ``timeout_s`` and reported, instead of hanging the
    whole bench run with no artifact.  Phases run strictly one at a time
    — a chip belongs to one process at a time."""
    import subprocess

    label = label or phase
    cmd = [sys.executable, "-m", "r2d2_tpu.bench", "--phase", phase,
           *map(str, extra)]
    # the package is run from a source tree, not installed: the child can
    # only import r2d2_tpu with the repo root as cwd, wherever the parent
    # was launched from
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, cwd=repo_root)
        try:
            out, err = proc.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            try:
                # bounded reap: a child stuck in an uninterruptible
                # device call may be unkillable — leak it rather than
                # hang here too
                proc.communicate(timeout=10.0)
            except Exception:
                pass
            return None, (f"{label} phase hung (no result after "
                          f"{timeout_s:.0f}s; child killed)")
    except Exception as e:
        return None, f"{label} phase spawn error: {type(e).__name__}: {e}"
    tail = (err or b"").decode(errors="replace").strip().splitlines()
    if proc.returncode != 0:
        return None, (f"{label} phase failed (rc={proc.returncode}): "
                      + " | ".join(tail[-3:]))
    for line in reversed((out or b"").decode(errors="replace").splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line), ""
            except Exception:
                break
    return None, f"{label} phase emitted no JSON: " + " | ".join(tail[-3:])


def _phase_main(argv) -> int:
    """Child entry for one isolated phase; prints ONE JSON line."""
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--phase", required=True,
                   choices=("micro", "actor", "system"))
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--warmup", type=int, default=5)
    p.add_argument("--seconds", type=float, default=75.0)
    p.add_argument("--knobs", type=str, default="{}")
    p.add_argument("--fused", action="store_true")
    a = p.parse_args(argv)

    from r2d2_tpu.utils.compile_cache import enable as enable_compile_cache

    enable_compile_cache()
    if a.phase == "micro":
        fps, sps, flops = _learner_micro_bench(a.steps, a.warmup,
                                               fused=a.fused)
        out = dict(learner_fps=fps, steps_per_sec=sps, flops=flops)
    elif a.phase == "actor":
        out = dict(actor_fps=_actor_plane_bench())
    else:
        fps, spans, ups = _system_bench(a.seconds, **json.loads(a.knobs))
        out = dict(system_fps=fps, top_spans=spans, updates=ups)
    print(json.dumps(dict(out, **_device_facts())), flush=True)
    return 0


def _main_isolated(steps: int, warmup: int, system_seconds: float) -> None:
    """Driver-facing bench: every phase in its own bounded subprocess.

    Ordering is by evidential value: the headline learner micro first (a
    later failure can no longer zero it), then the system fabric, then
    the actor plane.  The parent composes the same one-line JSON as the
    in-process path and never initializes a backend itself.  Exits
    non-zero when any phase erred (the JSON still carries what ran)."""
    device, reason = _device_probe()
    if device is None:
        _print_unreachable_artifact(reason)
        sys.exit(1)

    system_knobs = dict(FLAGSHIP_SYSTEM_KNOBS)
    ig_knobs = dict(FLAGSHIP_SYSTEM_KNOBS, in_graph_per=True)
    # compile slack + 1 s/step: a deliberately long `bench.py 20000` run
    # must not be misreported as a hang
    micro, m_err = _run_phase("micro", 900.0 + (steps + warmup) * 1.0,
                              ("--steps", steps, "--warmup", warmup))
    # the same micro cell through the fused double unroll (one
    # double-batch online+target pass): the feature's measured value,
    # reported side by side with the two-unroll headline
    micro_fused, mf_err = _run_phase(
        "micro", 900.0 + (steps + warmup) * 1.0,
        ("--steps", steps, "--warmup", warmup, "--fused"),
        label="micro_fused")
    system, s_err = _run_phase(
        "system", system_seconds + 900.0,
        ("--seconds", system_seconds, "--knobs", json.dumps(system_knobs)))
    # the same cell on the device-PER drivetrain (in_graph_per): zero
    # host round trips on the training path — reported side by side
    system_ig, ig_err = _run_phase(
        "system", system_seconds + 900.0,
        ("--seconds", system_seconds, "--knobs", json.dumps(ig_knobs)),
        label="system_ingraph")
    actor, a_err = _run_phase("actor", 600.0)

    errors = {k: v for k, v in (("micro", m_err), ("system", s_err),
                                ("micro_fused", mf_err),
                                ("system_ingraph", ig_err),
                                ("actor", a_err)) if v}
    print(json.dumps(_compose_result(
        device, system_knobs, errors,
        learner_fps=micro["learner_fps"] if micro else -1.0,
        steps_per_sec=micro["steps_per_sec"] if micro else 0.0,
        flops=micro["flops"] if micro else 0.0,
        fused_fps=micro_fused["learner_fps"] if micro_fused else -1.0,
        system_fps=system["system_fps"] if system else -1.0,
        system_ig_fps=system_ig["system_fps"] if system_ig else -1.0,
        actor_fps=actor["actor_fps"] if actor else -1.0)))
    if micro:
        print(f"# learner_steps/s={micro['steps_per_sec']:.2f} "
              f"flops/step={micro['flops']:.3e} "
              f"system_updates={system['updates'] if system else -1} "
              "busiest_spans_total_ms="
              f"{json.dumps(system['top_spans'] if system else {})}",
              file=sys.stderr)
    if errors:
        sys.exit(1)


def _compose_result(device: dict, system_knobs: dict, errors: dict, *,
                    learner_fps: float, steps_per_sec: float, flops: float,
                    fused_fps: float, system_fps: float,
                    system_ig_fps: float, actor_fps: float) -> dict:
    """The one-line artifact, shared by both entry paths (-1 = that phase
    erred; ``phase_errors`` says why)."""
    def vs(fps: float) -> float:
        return round(fps / NORTH_STAR_FPS, 3) if fps >= 0 else -1.0

    result = {
        "metric": "learner_env_frames_per_sec",
        "value": round(learner_fps, 1),
        "unit": "frames/s",
        "vs_baseline": vs(learner_fps),
        "device": device,
        "system_env_frames_per_sec": round(system_fps, 1),
        "system_vs_baseline": vs(system_fps),
        # the exact fabric knobs behind the system number (the learning
        # presets' cell — CURVES_AB_PIPELINE_r04's k=4 choice), so the
        # artifact documents what was measured
        "system_knobs": system_knobs,
        "system_ingraph_env_frames_per_sec": round(system_ig_fps, 1),
        "learner_fused_env_frames_per_sec": round(fused_fps, 1),
        "actor_env_frames_per_sec": round(actor_fps, 1),
        # the actor/system planes are host-CPU-bound work: their numbers
        # only compare across machines with this context attached
        "host_cpus": os.cpu_count() or 0,
    }
    if errors:
        result["phase_errors"] = errors
    if flops > 0:
        achieved = flops * steps_per_sec / 1e12
        result["achieved_tflops"] = round(achieved, 2)
        result["mfu"] = round(
            achieved / _peak_tflops(device["device_kind"]), 4)
    return result


def _print_unreachable_artifact(reason: str) -> None:
    print(json.dumps({
        "metric": "learner_env_frames_per_sec",
        "value": -1.0, "unit": "frames/s", "vs_baseline": -1.0,
        "error": f"no benchmarkable accelerator ({reason})",
    }))


def main(steps: int = 100, warmup: int = 5,
         system_seconds: float = 75.0) -> None:
    """The same phases in ONE process (importers; `python bench.py` takes
    the phase-isolated path).  A phase that raises is recorded as -1 with
    its error and the rest still run, then the exit code is non-zero."""
    import traceback

    device, reason = _device_probe()
    if device is None:
        _print_unreachable_artifact(reason)
        sys.exit(1)

    from r2d2_tpu.utils.compile_cache import enable as enable_compile_cache

    enable_compile_cache()  # repeat bench runs skip the multi-second compiles

    errors = {}

    def phase(name, fn, failed):
        try:
            return fn()
        except Exception as e:
            traceback.print_exc()
            errors[name] = f"{type(e).__name__}: {e}"
            return failed

    system_knobs = dict(FLAGSHIP_SYSTEM_KNOBS)
    learner_fps, steps_per_sec, flops = phase(
        "micro", lambda: _learner_micro_bench(steps, warmup),
        (-1.0, 0.0, 0.0))
    fused_fps, _, _ = phase(
        "micro_fused",
        lambda: _learner_micro_bench(steps, warmup, fused=True),
        (-1.0, 0.0, 0.0))
    actor_fps = phase("actor", _actor_plane_bench, -1.0)
    system_fps, top_spans, sys_updates = phase(
        "system", lambda: _system_bench(system_seconds, **system_knobs),
        (-1.0, {}, 0))
    # same cell on the device-PER drivetrain — schema parity with the
    # script-mode (phase-isolated) artifact
    system_ig_fps, _, _ = phase(
        "system_ingraph",
        lambda: _system_bench(system_seconds,
                              **dict(system_knobs, in_graph_per=True)),
        (-1.0, {}, 0))

    print(json.dumps(_compose_result(
        device, system_knobs, errors, learner_fps=learner_fps,
        steps_per_sec=steps_per_sec, flops=flops, fused_fps=fused_fps,
        system_fps=system_fps, system_ig_fps=system_ig_fps,
        actor_fps=actor_fps)))
    print(f"# learner_steps/s={steps_per_sec:.2f} flops/step={flops:.3e} "
          f"system_updates={sys_updates} "
          f"busiest_spans_total_ms={json.dumps(top_spans)}",
          file=sys.stderr)
    if errors:
        sys.exit(1)


def _script_main(argv) -> int:
    """Shared script entry for `python bench.py`, `python -m r2d2_tpu.bench`,
    and `r2d2 bench` — one place for the phase dispatch and the default
    steps/warmup/system_seconds, so every entry measures the same thing."""
    if "--phase" in argv:
        return _phase_main(argv)
    _main_isolated(steps=int(argv[0]) if argv else 100,
                   warmup=5, system_seconds=75.0)
    return 0


if __name__ == "__main__":
    sys.exit(_script_main(sys.argv[1:]))
