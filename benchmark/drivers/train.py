"""The training driver (``"driver": "train"`` in a traffic file): one
in-process call of the program's own ``train()`` with the benchmark's event
sink, stop predicate and (in a traced run) a profiler slice, reduced to the
result ``run.py`` prints.  Host actors (``"host_envs": true``) step the
benchmark's envs; otherwise the traffic file's overrides pick the fused loop.

The program is taken as it is — entry point, spans, counters, kernel names.
Two names of ``r2d2_tpu.train`` are wrapped for the length of the call, a
stop-gap each until the program offers the seam (PERF.md, Open questions).
A run in which ``train()`` never called a name that was wrapped is not
``correct``, so a rename in the program cannot pass unseen:

- ``init_params``, so that the weights come from ``--seed``.  The program
  takes one ``cfg.seed`` and folds it into its compiled super-step as a
  constant, so a new seed is a new program (50 s of compiling in the fused
  IMPALA cell; my chip runs, PR 24).  The harness therefore gives every run
  the same ``cfg.seed`` and hands the run's own key to the initialiser,
  which takes it as an argument: every program is in the cache after a
  cell's first run, whatever the seed;
- ``ReplayBuffer``, where the traffic file asks for a pre-filled ring
  (``prefill_ring_share``): the window has to open on a ring as full as a
  deployment's, and 64 host actors would need two minutes to fill it.  The
  buffer ``train()`` builds is filled, before ``train()`` starts its actors,
  with seeded blocks cut by the program's own ``assemble_block`` and written
  through its own ``ReplayBuffer.add``.
"""
from __future__ import annotations

import dataclasses
import math
import os
import shutil
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from benchmark import check, window

# what a CPU rehearsal changes besides the configuration file's ``small``
# sizes (the model's widths and lengths are the file's to cut): control
# flow and shapes-by-name only, never a speed
REHEARSAL = dict(compute_dtype="float32", pallas_interpret=True)
REHEARSAL_BLOCKS = 64
REHEARSAL_LANES = 4
PROGRAM_SEED = 0        # cfg.seed of every run: see the module docstring
ACTION_DIM = 4          # the fake envs' action set (envs/fake.py, envs/anakin.py)
BACKSTOP_SECONDS = 900  # train()'s own limit; stop_fn ends the run long before
PREFILL_TEMPLATES = 8   # distinct seeded blocks the pre-fill cycles through
# Config fields build_config works out from the traffic file's shares and
# block counts, whatever the configuration file or the preset says
DERIVED_KEYS = ("learning_starts", "anakin_episode_len")


def seed32(seed: int) -> int:
    """The driver's seeds pass 2**31; the program's PRNG keys are 32-bit."""
    return seed % (2 ** 31 - 1)


def _tuples(x):
    if isinstance(x, list):
        return tuple(_tuples(v) for v in x)
    return x


def config_from_file(config: Dict[str, Any]):
    """The program's ``Config`` from a configuration file's ``config``."""
    from r2d2_tpu.config import Config

    return Config(**{k: _tuples(v) for k, v in config.items()},
                  seed=PROGRAM_SEED)


def preset_config(doc: Dict[str, Any], small: bool = False, **kw):
    """The program's own preset that a configuration file names under
    ``preset`` (``{"function": <a function of r2d2_tpu.config>, "kwargs":
    {...}}``), with the file's ``small`` overrides if asked and then
    ``kw``: what the file's ``config`` is held against, and the sizes the
    CPU tests run a configuration at."""
    from r2d2_tpu import config as program_config

    preset = doc["preset"]
    kwargs = dict(preset.get("kwargs", {}))
    if small:
        kwargs.update(doc["small"])
    kwargs.update(kw)
    return getattr(program_config, preset["function"])(
        **{k: _tuples(v) for k, v in kwargs.items()})


def build_config(cell, rehearsal: bool):
    """The program's ``Config`` for this cell: the configuration file, then
    the traffic file's overrides."""
    traffic = cell.traffic
    kw = dict(cell.config["config"])
    kw.update(traffic.get("config_overrides", {}))
    if rehearsal:
        kw.update(cell.config["small"])
        kw.update(REHEARSAL,
                  num_actors=min(kw["num_actors"], REHEARSAL_LANES),
                  env_workers=min(kw.get("env_workers", 0), 2),
                  buffer_capacity=REHEARSAL_BLOCKS * kw["block_length"])
    # lengths the traffic file gives as shares of the ring or in blocks, so
    # that one file serves configurations of different sizes
    kw["learning_starts"] = int(traffic["learning_starts_ring_share"]
                                * kw["buffer_capacity"])
    if kw.get("actor_transport") == "anakin":
        kw["anakin_episode_len"] = (traffic["episode_len_blocks"]
                                    * kw["block_length"])
    return config_from_file(kw)


@dataclasses.dataclass
class TrainFacts:
    """One run of ``train()``, as the benchmark saw it."""
    cfg: Any
    sink: window.DispatchSink
    metrics: Dict[str, Any]                 # what train() returned
    t_start_perf: float                     # process start, perf_counter
    compiles_in_window: List[str]           # JAX's log lines, if any
    trace_dir: Optional[str] = None         # the profiler slice, if traced
    t_mark: Optional[float] = None          # clock-sync annotation, perf
    unused_wraps: List[str] = dataclasses.field(default_factory=list)
    wall_minus_perf: float = 0.0            # time.time() - perf_counter()

    def ring_fill(self) -> Dict[str, Optional[float]]:
        """Transitions in the ring over its capacity at the window's two
        ends, from the program's own 1 s log entries (``buffer_size``): the
        first entry inside the window and the last one."""
        win = self.sink.window()
        if win is None:
            return dict(open=None, close=None)
        lo, hi = (self.sink.ends[i] + self.wall_minus_perf for i in win)
        sizes = [e["buffer_size"] for e in self.metrics.get("logs", ())
                 if lo <= e["time"] <= hi]
        cap = float(self.cfg.buffer_capacity)
        return dict(open=sizes[0] / cap if sizes else None,
                    close=sizes[-1] / cap if sizes else None)


class CompileLog:
    """Counts what JAX compiles, with times (JAX's own compile log, as
    ``chip_smoke.py`` parses it from stderr — read in-process here)."""

    MARK = "Finished XLA compilation of"

    def __init__(self):
        import logging

        self.events: List[tuple] = []       # (perf_counter, message)
        self._logging = logging

    NOISE = ("Finished ", "Compiling ", "ompilation cache")

    def filter(self, record) -> bool:
        msg = record.getMessage()
        if self.MARK in msg:
            self.events.append((time.perf_counter(), msg))
        # the compile log itself stays out of stderr; all else passes
        return not any(n in msg for n in self.NOISE)

    def install(self) -> None:
        import jax

        jax.config.update("jax_log_compiles", True)
        for name in ("jax._src.dispatch", "jax._src.interpreters.pxla",
                     "jax._src.compiler"):
            self._logging.getLogger(name).addFilter(self)

    def between(self, t_lo: float, t_hi: float) -> List[str]:
        return [m for t, m in self.events if t_lo <= t <= t_hi]


def prefill_blocks(cfg, seed: int, count: int = PREFILL_TEMPLATES):
    """``count`` seeded full blocks of mid-episode experience, cut by the
    program's own block math: frames from the traffic env under seeded
    actions, seeded Q-values (so the initial priorities differ and the
    sampler draws from all over the ring) and small recurrent states."""
    import numpy as np

    from benchmark.traffic_env import TrafficEnv
    from r2d2_tpu.replay.block import assemble_block

    prefix, size = cfg.burn_in_steps, cfg.block_length
    n = prefix + size + 1
    out = []
    for i in range(count):
        rng = np.random.default_rng((seed, 0x50F1, i))
        env = TrafficEnv(cfg.stored_obs_shape, n + 1, n + 1, (seed, i))
        obs, rewards = [env.reset()[0]], [0.0]
        actions = rng.integers(ACTION_DIM, size=n - 1)
        for a in actions:
            o, r, *_ = env.step(int(a))
            obs.append(o)
            rewards.append(r)
        last_action = np.zeros((n, ACTION_DIM), bool)
        last_action[0, 0] = True
        last_action[np.arange(1, n), actions] = True
        out.append(assemble_block(
            cfg, obs=np.stack(obs), last_action=last_action,
            last_reward=np.asarray(rewards, np.float32),
            hidden_stream=check.seeded_state(cfg, n, rng),
            actions=actions[prefix:].astype(np.uint8),
            rewards=np.asarray(rewards[prefix + 1:], np.float32),
            qvals=rng.normal(size=(size + 1, ACTION_DIM)).astype(np.float32),
            prefix=prefix, size=size, done=False))
    return out


def prefill(buffer, cfg, seed: int, share: float) -> int:
    """Write ``share`` of the ring's blocks through the program's own
    writer; returns the transitions now in the ring."""
    blocks = prefill_blocks(cfg, seed)
    for i in range(int(round(share * cfg.num_blocks))):
        block, priorities = blocks[i % len(blocks)]
        buffer.add(block, priorities, None)
    return len(buffer)


def _profile_slice(sink: window.DispatchSink, spec: Dict[str, Any],
                   out: Dict[str, Any], done: threading.Event) -> None:
    """Profile a slice of the open window: start ``start_after_s`` into it,
    stop after ``min_dispatches`` completions or ``max_seconds``."""
    import jax

    while sink.t_open is None:
        if done.wait(0.05):
            return
    if done.wait(spec["start_after_s"]):
        return
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # 64 actor threads of Python: no
    opts.host_tracer_level = 2
    n0 = len(sink.ends)
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    # one annotation on both clocks: the host spans are placed on the
    # profiler's clock through it
    with jax.profiler.TraceAnnotation("bench_clock_sync"):
        t_mark = time.perf_counter()
        time.sleep(0.002)
    while (len(sink.ends) - n0 < spec["min_dispatches"]
           and time.perf_counter() - t0 < spec["max_seconds"]
           and not done.is_set()):
        time.sleep(0.01)
    jax.profiler.stop_trace()
    out.update(trace_dir=trace_dir, t_mark=t_mark)


def run_train(cell, args, cfg, t_start_perf: float,
              env_factory: Optional[Callable] = None,
              counter: Optional[Callable[[], float]] = None) -> TrainFacts:
    import importlib

    from r2d2_tpu.utils.trace import Tracer

    traffic = cell.traffic
    traced = bool(args.trace)
    warmup = traffic["warmup_dispatches"]
    if args.rehearsal:      # a CPU dispatch is slow; keep the first log entry out
        warmup = min(warmup, 12)
    sink = window.DispatchSink(warmup, args.seconds,
                               counter=counter, keep_spans=traced)
    compiles = CompileLog()
    compiles.install()
    train_mod = importlib.import_module("r2d2_tpu.train")
    real = {n: getattr(train_mod, n)
            for n in ("init_params", "ReplayBuffer")}
    calls: Dict[str, int] = {}      # wrapped name -> times train() called it

    def seeded_init(cfg, net, key):
        import jax

        calls["init_params"] += 1
        return real["init_params"](cfg, net,
                                   jax.random.PRNGKey(seed32(args.seed)))

    calls["init_params"] = 0
    train_mod.init_params = seeded_init
    if traffic.get("prefill_ring_share"):
        def prefilled_buffer(*a, **kw):
            calls["ReplayBuffer"] += 1
            buffer = real["ReplayBuffer"](*a, **kw)
            prefill(buffer, cfg, seed32(args.seed),
                    traffic["prefill_ring_share"])
            return buffer

        calls["ReplayBuffer"] = 0
        train_mod.ReplayBuffer = prefilled_buffer
    prof: Dict[str, Any] = {}
    done = threading.Event()
    slicer = None
    if traced:
        # joined below, bounded; it only starts and stops the profiler
        slicer = threading.Thread(  # graftlint: disable=thread-discipline -- joined in the finally below; nothing to restart
            target=_profile_slice, name="bench-profile-slice",
            args=(sink, traffic["trace"], prof, done), daemon=True)
        slicer.start()
    kwargs: Dict[str, Any] = dict(
        use_mesh=bool(traffic.get("mesh")), verbose=False,
        tracer=Tracer(events=sink), stop_fn=sink.stop,
        max_wall_seconds=BACKSTOP_SECONDS)
    if env_factory is not None:
        kwargs["env_factory"] = env_factory
    wall_minus_perf = time.time() - time.perf_counter()
    try:
        metrics = train_mod.train(cfg, **kwargs)
    finally:
        done.set()
        if slicer is not None:
            slicer.join(timeout=120)    # stop_trace writes the slice out
        for name, fn in real.items():
            setattr(train_mod, name, fn)
    win = sink.window()
    return TrainFacts(
        cfg=cfg, sink=sink, metrics=metrics, t_start_perf=t_start_perf,
        compiles_in_window=(compiles.between(sink.ends[win[0]],
                                             sink.ends[win[1]])
                            if win else []),
        trace_dir=prof.get("trace_dir"),
        t_mark=prof.get("t_mark"),
        unused_wraps=sorted(n for n, c in calls.items() if not c),
        wall_minus_perf=wall_minus_perf)


def run_facts_ok(facts: TrainFacts, expect: Dict[str, Any]) -> List[str]:
    """The run facts ``correct`` needs; returns what failed."""
    m, cfg, sink = facts.metrics, facts.cfg, facts.sink
    bad = []
    for key, want in expect.items():
        if m.get(key) != want:
            bad.append(f"{key} is {m.get(key)!r}, not {want!r}")
    for flag in ("fabric_failed", "learner_stalled", "dispatch_wedged"):
        if m.get(flag):
            bad.append(f"{flag} is set")
    if not math.isfinite(m.get("mean_loss", math.nan)):
        bad.append(f"mean_loss {m.get('mean_loss')} is not finite")
    if (m.get("learnhealth") or {}).get("nonfinite"):
        bad.append("a dispatch returned a non-finite loss")
    # every result_sync span closed one dispatch of k updates; the drain at
    # the end of the anakin loop harvests up to `pipeline` more outside one
    k = cfg.superstep_k
    missing = m.get("num_updates", -1) - len(sink.ends) * k
    if not 0 <= missing <= cfg.superstep_pipeline * k:
        bad.append(f"num_updates {m.get('num_updates')} does not match "
                   f"{len(sink.ends)} dispatches of {k}")
    if facts.compiles_in_window:
        bad.append(f"{len(facts.compiles_in_window)} compilations inside "
                   "the window: " + "; ".join(facts.compiles_in_window[:3]))
    if sink.window() is None:
        bad.append("no two dispatches completed inside the window")
    for name in facts.unused_wraps:
        bad.append(f"train() never called r2d2_tpu.train.{name}, which the "
                   "harness wraps: the run is not the one the cell describes")
    return bad


def end_to_end(facts: TrainFacts) -> Dict[str, float]:
    """The training cells' end-to-end numbers from the sink alone."""
    sink, cfg = facts.sink, facts.cfg
    i0, i1 = sink.window()
    frames_per_dispatch = (cfg.superstep_k * cfg.batch_size
                           * cfg.learning_steps)
    out = dict(
        learner_frames_per_s=window.rate(sink.ends, i0, i1,
                                         frames_per_dispatch),
        setup_s=sink.ends[i0] - facts.t_start_perf)
    if sink.counts:
        out["env_frames_per_s"] = window.counter_rate(
            sink.ends, sink.counts, i0, i1)
    return out


def dispatch_gaps(sink: window.DispatchSink) -> Optional[Dict[str, float]]:
    """How evenly the window's dispatches completed: the median time from
    one completion to the next, the longest, and the seconds by which the
    gaps over twice the median passed it — the time a stall took out of
    the window.  Not a metric: it rides on the line so that a run that reads
    far off says whether it stalled or ran slower throughout (PERF.md §6,
    PR 27)."""
    import statistics

    win = sink.window()
    if win is None:
        return None
    ends = sink.ends[win[0]:win[1] + 1]
    gaps = [b - a for a, b in zip(ends, ends[1:])]
    median = statistics.median(gaps)
    long = [g for g in gaps if g > 2 * median]
    return dict(median_ms=1e3 * median, longest_ms=1e3 * max(gaps),
                over_twice_median=len(long),
                stalled_s=sum(g - median for g in long))


def ring_obs(cfg, chips: int = 1):
    """The frame ring on one chip as the trace names an array, dtype and
    dimensions, from the program's own ring: ``("u32", (blocks, rows, words
    a frame))`` since PR 26."""
    from benchmark import xplane
    from r2d2_tpu.replay.device_ring import _ring_shapes

    slot, dtype = _ring_shapes(cfg, ACTION_DIM)["obs"]
    return xplane.hlo_dtype(dtype), (cfg.num_blocks // chips, *slot)


def traced_parts(cell, facts: TrainFacts, device: Dict[str, Any]):
    """Per-layer metrics, the device's busy time and the breakdown of a
    traced run."""
    from benchmark import readers, xplane

    sink, cfg = facts.sink, facts.cfg
    i0, i1 = sink.window()
    trace, seconds = None, 0.0
    if facts.trace_dir:
        path = xplane.find_xplane(facts.trace_dir)
        if path:
            trace = xplane.load(path)
            # the slice as the device saw it: the profiler starts and stops
            # a little inside the host's calls
            seconds = xplane.device_extent_seconds(trace)
    ctx = readers.ReadContext(
        cfg=cfg, config_name=cell.config_name, action_dim=ACTION_DIM,
        chips=cell.chips,
        device_kind=device["kind"], t_open=sink.ends[i0],
        t_close=sink.ends[i1],
        updates_per_s=(i1 - i0) * cfg.superstep_k
        / (sink.ends[i1] - sink.ends[i0]),
        span_mean_ms=sink.span_mean_ms, trace=trace, trace_seconds=seconds,
        memory_peak_bytes=device.get("memory_peak_bytes"),
        ring_obs=ring_obs(cfg, device["count"] if cell.traffic.get("mesh")
                          else 1),
        ring_fill_open=facts.ring_fill()["open"], bench_dir=cell.bench_dir)
    metrics = readers.read_all(cell.per_layer, ctx)
    busy = ctx.busy_seconds()
    breakdown = None
    if trace is not None and busy:
        ops = ctx.device_ops()
        totals = sorted(xplane.op_totals(ops).items(),
                        key=lambda kv: -kv[1])[:10]
        offset = xplane.clock_offset(trace, facts.t_mark)
        gaps = (xplane.idle_gaps(ops, sink.spans, offset)
                if offset is not None else [])
        breakdown = dict(device_ops=[[n, s] for n, s in totals],
                         idle_gaps=gaps)
    return metrics, busy, seconds, breakdown


def memory_peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest chip; None where the backend keeps
    no memory stats (the CPU client)."""
    from r2d2_tpu.utils.trace import device_memory

    return max((m["peak_bytes_in_use"] for m in device_memory()),
               default=None)


def run(cell, args, t_start_perf: float,
        device: Dict[str, Any]) -> Dict[str, Any]:
    """One run of a training cell: ``correct``, ``attempted``, ``failed``,
    ``metrics``, ``breakdown`` and what else the line carries (``extra``).
    ``device`` gains the memory peak and, traced, the busy time."""
    traffic = cell.traffic
    cfg = build_config(cell, args.rehearsal)
    env_factory = counter = None
    if traffic.get("host_envs"):
        from benchmark.traffic_env import EnvFleet

        env_factory = EnvFleet(
            seed32(args.seed), cfg.num_actors, cfg.block_length,
            traffic["episode_len_blocks"] * cfg.block_length)
        counter = env_factory.total_steps
    facts = run_train(cell, args, cfg, t_start_perf, env_factory, counter)
    try:
        device["memory_peak_bytes"] = memory_peak_bytes()
        problems = run_facts_ok(facts, {} if args.rehearsal
                                else traffic["expect"])
        win = facts.sink.window()
        attempted = (win[1] - win[0]) if win else 0
        nonfinite = int((facts.metrics.get("learnhealth") or {})
                        .get("nonfinite", 0))
        failed = min(attempted, -(-nonfinite // cfg.superstep_k))
        cmp = check.compare(cell.config_name, cfg, cell.config["tolerance"],
                            ACTION_DIM, seed32(args.seed), cell.bench_dir)
        problems += cmp["problems"]
        metrics: Dict[str, Any] = {}
        breakdown = None
        if win is not None and args.trace:
            metrics, busy, seconds, breakdown = traced_parts(
                cell, facts, device)
            if busy:
                device.update(busy_s=busy, window_s=seconds)
            elif not args.rehearsal:    # a CPU has no device plane
                problems.append("no device operation in the traced slice")
        elif win is not None:
            values = end_to_end(facts)
            metrics = {m["name"]: dict(value=values[m["name"]],
                                       unit=m["unit"])
                       for m in cell.end_to_end if m["name"] in values}
        return dict(
            correct=not problems, attempted=attempted, failed=failed,
            metrics=metrics, breakdown=breakdown,
            extra=dict(problems=problems, ring_fill=facts.ring_fill(),
                       dispatch_gaps=dispatch_gaps(facts.sink),
                       reference={k: cmp[k] for k in
                                  ("q_rel", "q_rms_rel", "loss_rel")},
                       compared={k: dict(value=cmp[k],
                                         limit=cell.config["tolerance"][k])
                                 for k, _ in check.COMPARED
                                 if k in cell.config["tolerance"]}))
    finally:
        if facts.trace_dir and os.path.isdir(facts.trace_dir):
            shutil.rmtree(facts.trace_dir, ignore_errors=True)
