"""Set-up, phase by phase (``utils/trace.SetupClock``, docs/OBSERVABILITY.md
"Set-up"): ``train()`` records its set-up as ``setup.*`` spans of its
tracer — host phases back to back, and JAX's own trace, lower and compile
events — through ``jax.monitoring`` listeners that live only until the first
training dispatch returns.  The instrument must not change what it
measures: no second lowering of an entry point, no listener left behind."""
import collections
import time

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring as jax_monitoring

from r2d2_tpu.config import test_config as make_test_config
from r2d2_tpu.envs import FakeAtariEnv
from r2d2_tpu.train import train
from r2d2_tpu.utils.trace import SetupClock, Tracer, union_seconds

PHASES = ("setup.state", "setup.ring", "setup.drivetrain", "setup.fill",
          "setup.first_dispatch")
KINDS = ("setup.trace", "setup.lower", "setup.compile", "setup.cache_load")
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"


class KeepAll:
    """A tracer's event sink that keeps every span."""
    armed = True

    def __init__(self):
        self.spans = []

    def complete(self, name, t0, dt):
        self.spans.append((name, t0, dt))


def listeners():
    return (jax_monitoring.get_event_time_span_listeners(),
            jax_monitoring.get_event_duration_listeners())


def env_factory(cfg, seed):
    return FakeAtariEnv(obs_shape=cfg.obs_shape, action_dim=4, seed=seed,
                        episode_len=32)


def fused(**kw):
    return make_test_config(**dict(
        dict(game_name="Fake", actor_transport="anakin", device_replay=True,
             in_graph_per=True, num_actors=2, superstep_k=2,
             anakin_episode_len=12, training_steps=8, learning_starts=16),
        **kw)), {}


def fabric(**kw):
    return make_test_config(**dict(
        dict(game_name="Fake", device_replay=True, in_graph_per=True,
             superstep_k=2, training_steps=8), **kw)), dict(
                 env_factory=env_factory)


def host_staged(**kw):
    return make_test_config(**dict(
        dict(game_name="Fake", training_steps=8, prefetch_batches=2),
        **kw)), dict(env_factory=env_factory)


# how often train() lowers each entry point it builds, as it did before
# set-up was timed: the fused loop's warm-up rollout is lowered twice (its
# first call takes the freshly made lane state uncommitted, the second the
# first call's outputs, committed and laid out by the table), a lead for
# set-up's own speed.  A jit of module scope (the ring's slot write) is
# left out: an earlier test in the process may have lowered it already
CASES = {
    "fused": (fused, {"jit(super_step)": 1, "jit(rollout)": 2}),
    "fabric": (fabric, {"jit(super_step)": 1, "jit(act)": 1,
                        "jit(publish_copy_params)": 1}),
    "host_staged": (host_staged, {"jit(train_step)": 1, "jit(act)": 1,
                                  "jit(publish_copy_params)": 1}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_set_up_is_split_at_its_first_dispatch_and_jax_is_left_as_found(
        case):
    make, lowered = CASES[case]
    cfg, kw = make()
    sink = KeepAll()
    lowerings = collections.Counter()
    alive_after_set_up = []

    def count(event, start, end, fun_name=None, **_):
        if event == LOWER:
            lowerings[fun_name] += 1

    def stop_fn():
        # polled all through the run, from every fabric thread: once set-up
        # is recorded, no listener of the program's is left
        if any(n == "setup.train" for n, _, _ in sink.spans):
            alive_after_set_up.append(
                len(jax_monitoring.get_event_time_span_listeners()))
        return False

    before = listeners()
    jax.monitoring.register_event_time_span_listener(count)
    try:
        m = train(cfg, verbose=False, max_wall_seconds=240, stop_fn=stop_fn,
                  tracer=Tracer(events=sink), **kw)
    finally:
        jax.monitoring.unregister_event_time_span_listener(count)
    assert listeners() == before
    assert alive_after_set_up and set(alive_after_set_up) == {
        len(before[0]) + 1}                     # the test's own counter

    spans = collections.defaultdict(list)
    for name, t0, dt in sink.spans:
        spans[name].append((t0, t0 + dt))
    setup = {n: v for n, v in spans.items() if n.startswith("setup.")}
    assert sorted(setup) == sorted(PHASES + KINDS + ("setup.train",))
    assert all(len(v) == 1 for v in setup.values()), setup
    (lo, hi), = setup["setup.train"]
    phases = [setup[n][0] for n in PHASES]
    # the phases lie inside setup.train, in order, without overlap
    assert lo <= phases[0][0]
    for (_, end), (start, _) in zip(phases, phases[1:]):
        assert end <= start
    assert phases[-1][1] == pytest.approx(hi, abs=1e-6)
    assert sum(b - a for a, b in phases) <= hi - lo
    for name in ("setup.trace", "setup.lower", "setup.compile"):
        (t0, t1), = setup[name]
        assert t1 > t0 >= lo
    # set-up ends at the return of the first training dispatch call: that
    # call's span is setup.first_dispatch, and nothing of set-up ends
    # after the second one begins
    first, second = sorted(spans["learner.step_dispatch"])[:2]
    assert setup["setup.first_dispatch"][0] == pytest.approx(first, abs=1e-6)
    assert max(b for v in setup.values() for _, b in v) <= second[0]
    # the clock lowers nothing: every entry point as often as without it
    for fun_name, n in lowered.items():
        assert lowerings[fun_name] == n, (fun_name, lowerings)
    # metrics["setup"] is what the spans say, with the programs counted
    got = m["setup"]
    assert got["train_s"] == pytest.approx(hi - lo)
    for name in PHASES + KINDS:
        t0, t1 = setup[name][0]
        assert got[name[len("setup."):] + "_s"] == pytest.approx(t1 - t0)
    assert got["programs_compiled"] + got["programs_loaded"] >= len(lowered)
    assert got["cache_load_s"] <= got["compile_s"]


@pytest.mark.parametrize("case", ["fused", "fabric"])
def test_a_configuration_refused_in_set_up_leaves_no_listener(case):
    """The ring does not fit: ``train()`` raises after ``setup.state``,
    and the listeners go with it."""
    cfg, kw = CASES[case][0](buffer_capacity=10 ** 12)
    before = listeners()
    with pytest.raises(ValueError, match="device_replay ring needs"):
        train(cfg, verbose=False, **kw)
    assert listeners() == before


def test_record_is_what_span_does_with_the_body_it_timed():
    timed, recorded = KeepAll(), KeepAll()
    a, b = Tracer(events=timed), Tracer(events=recorded)
    for _ in range(3):
        with a.span("stage"):
            time.sleep(0.001)
    for name, t0, dt in timed.spans:
        b.record(name, t0, dt)
    assert recorded.spans == timed.spans
    assert b.snapshot() == a.snapshot()
    # an unarmed sink sees neither
    timed.armed = recorded.armed = False
    with a.span("stage"):
        pass
    b.record("stage", 1.0, 0.5)
    assert len(timed.spans) == len(recorded.spans) == 3
    assert b.snapshot()["span.stage.count"] == 4


def test_the_union_counts_a_nested_trace_once():
    """A jit traced inside another's trace records its own event inside
    the outer one.  Both functions are made here, so neither is in any
    trace cache of the process yet."""
    events = []

    def keep(event, start, end, fun_name=None, **_):
        if event == TRACE:
            events.append((fun_name, start, end))

    @jax.jit
    def nested_inner(x):
        return jnp.sin(x) * 2.0

    @jax.jit
    def nested_outer(x):
        return nested_inner(x) @ x

    x = jnp.ones((3, 3))
    jax.monitoring.register_event_time_span_listener(keep)
    try:
        nested_outer(x)
    finally:
        jax.monitoring.unregister_event_time_span_listener(keep)
    spans = collections.defaultdict(list)
    for name, s, e in events:
        spans[name].append((s, e))
    (outer_lo, outer_hi), = spans.pop("nested_outer")
    (inner_lo, inner_hi), = spans["nested_inner"]
    assert outer_lo <= inner_lo <= inner_hi <= outer_hi
    assert all(outer_lo <= s <= e <= outer_hi
               for v in spans.values() for s, e in v)
    intervals = [(s, e) for _, s, e in events]
    assert union_seconds(intervals) == pytest.approx(outer_hi - outer_lo)
    assert sum(e - s for s, e in intervals) > union_seconds(intervals)


@pytest.mark.parametrize("intervals,want", [
    ([], 0.0),
    ([(0.0, 2.0), (1.0, 3.0), (5.0, 6.0)], 4.0),
    ([(0.0, 10.0), (1.0, 2.0), (3.0, 4.0)], 10.0),
    ([(4.0, 5.0), (0.0, 1.0), (1.0, 2.0)], 3.0),
])
def test_union_seconds(intervals, want):
    assert union_seconds(intervals) == pytest.approx(want)


def test_a_program_loaded_from_the_cache_is_counted_as_loaded():
    """A backend compile with a cache load inside it is a load; one
    without is a compile.  The events come as JAX records them."""
    sink = KeepAll()
    before = listeners()
    tracer = Tracer(events=sink)
    clock = SetupClock(tracer)
    try:
        t = time.time()
        jax_monitoring.record_event_time_span(COMPILE, t - 10.0, t - 9.5,
                                              fun_name="jit(compiled)")
        jax_monitoring.record_event_time_span(COMPILE, t - 9.75, t - 9.25,
                                              fun_name="jit(compiled2)")
        t = time.time()
        jax_monitoring.record_event_duration_secs(CACHE_LOAD, 0.2)
        jax_monitoring.record_event_time_span(COMPILE, t - 0.3,
                                              time.time() + 0.01,
                                              fun_name="jit(loaded)")
        clock.begin_fill()
        with tracer.span("learner.step_dispatch"):
            pass
        assert listeners() == before            # closed at the dispatch
    finally:
        clock.close()
    assert clock.seconds["programs_compiled"] == 2
    assert clock.seconds["programs_loaded"] == 1
    assert clock.seconds["cache_load_s"] == pytest.approx(0.2)
    # the two compiles overlap by a quarter second: their union, not
    # their sum, beside the load's 0.31 s
    assert 1.0 < clock.seconds["compile_s"] < 1.1
    names = [n for n, _, _ in sink.spans]
    assert sorted(names) == sorted(
        ("learner.step_dispatch", "setup.drivetrain", "setup.fill",
         "setup.first_dispatch", "setup.train") + KINDS)
    with tracer.span("learner.step_dispatch"):  # set-up ends once only
        pass
    clock.dispatched(0.0, 1.0)
    assert len(sink.spans) == len(names) + 1
