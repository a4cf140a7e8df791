"""The reduction from the profiler's trace to metrics: on hand-made events,
and on a few steps cut from a chip run (fixtures/)."""
import os

import pytest

from benchmark import readers, xplane

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
MS = 1_000_000


def ev(name, start_ms, dur_ms, hlo=""):
    """An event as the trace names it: the instruction's whole text."""
    return dict(name=f"%{name} = {hlo}" if hlo else name,
                start_ns=int(start_ms * MS), dur_ns=int(dur_ms * MS))


RING = "u8[1152,448,7056]{2,1,0:T(8,128)(4,1)}"
OPS = [
    ev("while.3", 0, 40),                                   # a container
    ev("fusion.7", 1, 9, "bf16[5440,512]{1,0} fusion(...)"),
    ev("copy.219", 10, 20, f"{RING} copy(u8[1152,448,7056] %p)"),
    ev("copy.5", 30, 2, "f32[64,2048]{1,0} copy(...)"),
    ev("all-reduce.1", 32, 8, "f32[512,2048]{1,0} all-reduce(...)"),
    ev("fusion.7", 50, 10, "bf16[5440,512]{1,0} fusion(...)"),      # after a gap
]
MODULES = [ev("jit_super_step(123)", 0, 40), ev("jit_super_step(123)", 50, 10),
           ev("jit__publish(9)", 60, 1)]


def trace_of(ops, modules, devices=1):
    return dict(planes=[dict(name=f"/device:TPU:{d}", lines=[
        dict(name="XLA Modules", events=modules),
        dict(name="XLA Ops", events=ops)]) for d in range(devices)] + [
        dict(name="/host:CPU", lines=[dict(name="python3", events=[
            ev("bench_clock_sync", 5, 2)])])])


def test_busy_is_the_union_of_intervals():
    assert xplane.busy_intervals(OPS) == [(0, 40 * MS), (50 * MS, 60 * MS)]
    assert xplane.busy_seconds(OPS) == pytest.approx(0.050)
    assert xplane.busy_seconds([]) == 0.0


def test_the_slice_is_as_long_as_the_device_saw_it():
    assert xplane.device_extent_seconds(trace_of(OPS, MODULES, 2)) == (
        pytest.approx(0.060))
    assert xplane.device_extent_seconds(dict(planes=[])) == 0.0


def test_a_container_does_not_count_its_body_twice():
    selfs = {(xplane.op_name(e), e["start_ns"]): ns
             for e, ns in xplane.self_times(OPS)}
    assert selfs[("while.3", 0)] == 1 * MS      # 40 - (9 + 20 + 2 + 8)
    assert selfs[("copy.219", 10 * MS)] == 20 * MS
    totals = xplane.op_totals(OPS)
    assert sum(totals.values()) == pytest.approx(xplane.busy_seconds(OPS))
    assert totals["fusion.7_bf16_5440_512_"] == pytest.approx(0.019)


def test_operations_carry_their_shape_in_their_label():
    assert xplane.op_shape(OPS[2]) == ("u8", (1152, 448, 7056))
    assert xplane.op_label(OPS[2]) == "copy.219_u8_1152_448_7056_"
    assert xplane.op_shape(ev("x", 0, 1)) is None
    assert xplane.op_label(ev("while.3", 0, 1)) == "while.3"
    assert xplane.op_shape(ev("r", 0, 1, "f32[] reduce(...)")) == ("f32", ())
    # a tuple result is named by its first element; operands do not count
    fused = ev("fusion.765", 0, 1, "(bf16[32]{0:T(256)}, bf16[5440,2]{1,0}) "
               "fusion(u8[1152,448,7056]{2,1,0} %p), kind=kLoop")
    assert xplane.op_label(fused) == "fusion.765_bf16_32_"


def test_the_ring_copy_is_matched_by_shape_not_by_number():
    ring = ("u8", (1152, None, 7056))       # rows: the ring's own padding
    assert xplane.select_seconds(OPS, "^copy", ring) == pytest.approx(0.020)
    assert xplane.select_seconds(OPS, "^copy") == pytest.approx(0.022)
    assert xplane.select_seconds(OPS, "^copy", ("u8", (1250, None, 7056))) == 0
    assert not xplane.shape_matches(("f32", (1152, 448, 7056)), ring)
    assert not xplane.shape_matches(("u8", (1152, 7056)), ring)
    assert not xplane.shape_matches(None, ring)
    renumbered = [dict(e, name=e["name"].replace("copy.219", "copy.17"))
                  for e in OPS]
    assert xplane.select_seconds(renumbered, "^copy", ring) == (
        pytest.approx(0.020))


def test_gaps_go_to_the_host_span_that_covered_them():
    # trace clock = host clock + 100 s; the 10 ms gap at 40..50 ms
    spans = {"learner.result_sync": [(-99.9595, 0.008)],
             "learner.step_dispatch": [(-99.9500, 0.002)]}
    assert xplane.idle_gaps(OPS, spans, 100.0) == [
        ["learner.result_sync", pytest.approx(0.010)]]
    assert xplane.idle_gaps(OPS, {}, 0.0) == [
        ["no_host_span", pytest.approx(0.010)]]


def test_the_one_annotation_puts_host_spans_on_the_traces_clock():
    trace = trace_of(OPS, MODULES)
    assert xplane.clock_offset(trace, t_mark=12.0) == pytest.approx(-11.995)
    assert xplane.clock_offset(dict(planes=[]), 12.0) is None


def _ctx(trace, seconds, blocks=1152, **kw):
    class Cfg:
        superstep_k = 4

    return readers.ReadContext(
        cfg=Cfg, action_dim=4, chips=1, device_kind="TPU v5 lite",
        t_open=0.0, t_close=1.0, updates_per_s=80.0,
        span_mean_ms=lambda *a: None, trace=trace, trace_seconds=seconds,
        memory_peak_bytes=8_189_600_000, ring_obs_shape=(blocks, None, 7056),
        ring_fill_open=0.97, **kw)


# a metric of a cell this benchmark does not hold yet (four chips): what a
# later PR adds as layer_metrics/collective_device_share.json
COLLECTIVES = dict(
    name="collective_device_share", unit="%", kind="xplane_ops", select="ops",
    match="^(all-reduce|all-gather|reduce-scatter|collective-permute|"
          "all-to-all)", reduce="share_of_busy")


def _spec(name):
    from benchmark.manifest import Manifest

    if name == COLLECTIVES["name"]:
        return COLLECTIVES

    return Manifest().layer_metric(name)


def test_the_metric_files_read_the_trace():
    ctx = _ctx(trace_of(OPS, MODULES, devices=2), 0.0625)
    got = readers.read_all([_spec(n) for n in (
        "device_idle_share.train", "step_device_ms", "ring_copy_device_share",
        "collective_device_share", "peak_hbm_bytes", "ring_fill_share")],
        ctx)
    assert got["device_idle_share.train"]["value"] == pytest.approx(20.0)
    # two super-step programs, 50 ms, 4 updates each
    assert got["step_device_ms"] == dict(value=pytest.approx(6.25), unit="ms")
    assert got["ring_copy_device_share"]["value"] == pytest.approx(40.0)
    assert got["collective_device_share"]["value"] == pytest.approx(16.0)
    assert got["peak_hbm_bytes"]["value"] == 8_189_600_000
    assert got["ring_fill_share"] == dict(value=pytest.approx(97.0), unit="%")
    # a ring of another size is not this copy; no trace, no device metric
    other = readers.read_all([_spec("ring_copy_device_share")],
                             _ctx(trace_of(OPS, MODULES), 0.0625, blocks=1250))
    assert other["ring_copy_device_share"]["value"] == 0.0
    assert readers.read_all([_spec("device_idle_share.train"),
                             _spec("step_device_ms")], _ctx(None, 0.0)) == {}


def test_programs_cut_short_by_the_slices_edges_do_not_count():
    cut = [ev("jit_super_step(1)", 0, 11), ev("jit_super_step(1)", 11, 40),
           ev("jit_super_step(1)", 51, 44), ev("jit_super_step(1)", 95, 3)]
    got = readers.read_all([_spec("step_device_ms")],
                           _ctx(trace_of(OPS, cut), 0.098))
    assert got["step_device_ms"]["value"] == pytest.approx((40 + 44) / 2 / 4)


def test_act_timer_is_read_only_on_the_platform_its_file_names():
    class Dev:
        platform = "cpu"

    class Timer:
        device = Dev

        @staticmethod
        def mean_us(lo, hi):
            return 850.0

    spec = _spec("act_call_us")
    assert readers.read_all([spec], _ctx(None, 0.0, act_timer=Timer))[
        "act_call_us"] == dict(value=850.0, unit="us")
    Dev.platform = "tpu"
    assert readers.read_all([spec], _ctx(None, 0.0, act_timer=Timer)) == {}


# ---- a few steps cut from this PR's own chip run (TPU v5 lite, the cell
# nature_lstm512.fabric, 70 ms = two and a half super-steps; 25,000 events)

@pytest.fixture(scope="module")
def chip_slice():
    import gzip
    import json

    with gzip.open(os.path.join(FIXTURES, "fabric_slice.json.gz"), "rt") as f:
        return json.load(f)


def test_chip_slice_busy_idle_and_operation_totals(chip_slice):
    trace = chip_slice["trace"]
    ops = xplane.line_events(xplane.device_planes(trace)[0], xplane.OPS_LINE)
    assert len(ops) == 25000
    busy, extent = xplane.busy_seconds(ops), xplane.device_extent_seconds(trace)
    assert busy == pytest.approx(0.069818761) and busy < extent < 0.0701
    totals = xplane.op_totals(ops)
    # nothing nests on this line, so self times add up to the busy time
    assert sum(totals.values()) == pytest.approx(busy)
    top = max(totals, key=totals.get)
    assert top == "constant_dynamic-slice_fusion.10_f32_1_64_2048_"
    assert totals[top] == pytest.approx(0.005989838)
    assert "fusion.730_u8_5440_7056_" in totals      # the gathered frames


def test_chip_slice_through_the_metric_files(chip_slice):
    trace = chip_slice["trace"]
    ctx = _ctx(trace, xplane.device_extent_seconds(trace), blocks=1472)
    got = readers.read_all([_spec(n) for n in (
        "device_idle_share.train", "step_device_ms", "ring_copy_device_share",
        "collective_device_share")], ctx)
    assert 0 < got["device_idle_share.train"]["value"] < 0.5
    # two whole super-steps of k=4 updates: 26.0 and 28.0 ms
    assert got["step_device_ms"]["value"] == pytest.approx(
        (25995444 + 27973157) / 2 / 4 / 1e6)
    # 1,472 blocks: the compiler makes no copy of the ring, on one chip
    # nothing is exchanged
    assert got["ring_copy_device_share"]["value"] == 0.0
    assert got["collective_device_share"]["value"] == 0.0
    # ... but the gather's u8[5440,7056] result is there to be found by shape
    ops = ctx.device_ops()
    assert xplane.select_seconds(ops, "^fusion", ("u8", (5440, 7056))) == (
        pytest.approx(0.004002367))


def test_chip_slice_gaps_fall_under_the_learners_spans(chip_slice):
    trace = chip_slice["trace"]
    offset = xplane.clock_offset(trace, chip_slice["t_mark"])
    assert offset == pytest.approx(-1268.62204321)
    ops = xplane.line_events(xplane.device_planes(trace)[0], xplane.OPS_LINE)
    gaps = dict(map(tuple, xplane.idle_gaps(ops, chip_slice["spans"], offset)))
    assert set(gaps) <= {"learner.step_dispatch", "learner.result_sync",
                         "learner.publish", "no_host_span"}
    assert sum(gaps.values()) == pytest.approx(
        xplane.device_extent_seconds(trace) - xplane.busy_seconds(ops),
        rel=0.05)
