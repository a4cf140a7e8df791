"""The measured window: rates between completion times of whole dispatches."""
import pytest

from benchmark import window
from benchmark.traffic_env import EnvFleet


def _ends(period, n, jitter=()):
    t, out = 100.0, []
    for i in range(n):
        t += period + (jitter[i % len(jitter)] if jitter else 0.0)
        out.append(t)
    return out


def test_window_opens_after_warmup_and_closes_on_a_completion():
    ends = _ends(0.5, 100)
    i0, i1 = window.window_indices(ends, warmup=4, seconds=10.0)
    assert (i0, i1) == (4, 24)
    assert ends[i1] - ends[i0] == pytest.approx(10.0)
    # 20 dispatches of k=4 updates of 4,800 frames in exactly 10 s
    assert window.rate(ends, i0, i1, 4 * 4800) == pytest.approx(38400.0)


@pytest.mark.parametrize("shift", [-0.49, -0.2, 0.0, 0.2, 0.49])
def test_a_shifted_nominal_window_does_not_change_the_rate(shift):
    """A polled count over a nominal 10 s is off by up to a dispatch at each
    end (±2.5 % here); between whole dispatches the rate is the same."""
    ends = _ends(0.5, 100, jitter=(0.01, -0.02, 0.015, -0.005))
    base = window.rate(ends, *window.window_indices(ends, 4, 10.2), 19200)
    i0, i1 = window.window_indices(ends, 4, 10.2 + shift)
    assert abs(i1 - window.window_indices(ends, 4, 10.2)[1]) <= 1
    assert window.rate(ends, i0, i1, 19200) == pytest.approx(base, rel=2e-3)
    polled = (10.2 + shift) // 0.5 * 19200 / 10.2
    assert abs(polled / base - 1) > abs(
        window.rate(ends, i0, i1, 19200) / base - 1)


def test_one_dispatch_more_or_less_is_the_same_rate_on_even_spacing():
    ends = _ends(0.05, 1000)
    rates = {round(window.rate(ends, *window.window_indices(ends, 20, s), 1),
                   9) for s in (29.95, 30.0, 30.05)}
    assert len(rates) == 1


def test_too_few_completions_is_no_window():
    assert window.window_indices(_ends(0.5, 4), 4, 10.0) is None
    assert window.window_indices(_ends(0.5, 5), 4, 10.0) is None
    assert window.window_indices(_ends(0.5, 6), 4, 10.0) == (4, 5)


def test_sink_records_dispatches_counts_and_stops_on_its_clock():
    now = [0.0]
    frames = [0]
    sink = window.DispatchSink(warmup=2, seconds=1.0,
                               counter=lambda: frames[0], keep_spans=True,
                               clock=lambda: now[0])
    assert sink.armed and not sink.stop()
    for i in range(8):
        frames[0] += 64
        sink.complete("learner.step_dispatch", 10 + 0.25 * i, 0.001)
        sink.complete(window.SYNC_SPAN, 10 + 0.25 * i, 0.2)
    assert sink.t_open == pytest.approx(10.7)        # third completion
    now[0] = 11.69
    assert not sink.stop()
    now[0] = 11.71
    assert sink.stop()
    i0, i1 = sink.window()
    assert (i0, i1) == (2, 6)
    assert window.counter_rate(sink.ends, sink.counts, i0, i1) == (
        pytest.approx(4 * 64 / 1.0))
    assert sink.span_mean_ms(window.SYNC_SPAN, 10.0, 20.0) == (
        pytest.approx(200.0))
    assert sink.span_mean_ms("learner.step_dispatch", 10.0, 20.0,
                             divisor=2) == pytest.approx(0.5)
    assert sink.span_mean_ms("no.such.span", 0.0, 99.0) is None


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 - 2])
def test_every_seed_deals_the_same_offsets_in_another_order(seed):
    class Cfg:
        stored_obs_shape = (21, 21, 16)
        seed = 0

    fleet = EnvFleet(seed, lanes=64, block_length=400, episode_len=1600)
    envs = [fleet(Cfg, Cfg.seed + i) for i in range(64)]
    firsts = sorted(e._limit for e in envs)
    assert firsts == [1 + i * 400 // 64 for i in range(64)]
    env = envs[0]
    obs, _ = env.reset()
    assert obs.shape == (21, 21, 16) and obs.dtype.name == "uint8"
    n, done = 0, False
    while not done:
        _, _, _, done, _ = env.step(0)
        n += 1
    assert n == env._limit
    env.reset()
    assert env._limit == 1600          # whole episodes from then on
    assert fleet.total_steps() == n
