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


# the frame ring as the program has it since PR 26: rows of 32-bit words
# (7,056 bytes a frame in 1,764 words), 448 padded rows a block — here at
# 1,536 = 12 x 128 blocks, where the compiler has copied all of it (PR 24)
RING = "u32[1536,448,1764]{2,1,0:T(8,128)}"
OPS = [
    ev("while.3", 0, 40),                                   # a container
    ev("fusion.7", 1, 9, "bf16[5440,512]{1,0} fusion(...)"),
    ev("copy.219", 10, 20, f"{RING} copy(u32[1536,448,1764] %p)"),
    ev("copy.297", 30, 2, "u8[5440,7056]{0,1} copy(...)"),   # a batch's frames
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
    assert xplane.op_shape(OPS[2]) == ("u32", (1536, 448, 1764))
    assert xplane.op_label(OPS[2]) == "copy.219_u32_1536_448_1764_"
    assert xplane.op_label(OPS[3]) == "copy.297_u8_5440_7056_"
    assert xplane.op_shape(ev("x", 0, 1)) is None
    assert xplane.op_label(ev("while.3", 0, 1)) == "while.3"
    assert xplane.op_shape(ev("r", 0, 1, "f32[] reduce(...)")) == ("f32", ())
    # a tuple result is named by its first element; operands do not count
    fused = ev("fusion.765", 0, 1, "(bf16[32]{0:T(256)}, bf16[5440,2]{1,0}) "
               "fusion(u32[1536,448,1764]{2,1,0} %p), kind=kLoop")
    assert xplane.op_label(fused) == "fusion.765_bf16_32_"


def test_the_ring_copy_is_matched_by_shape_not_by_number():
    ring = ("u32", (1536, 448, 1764))
    assert xplane.select_seconds(OPS, "^copy", ring) == pytest.approx(0.020)
    assert xplane.select_seconds(OPS, "^copy") == pytest.approx(0.022)
    assert xplane.select_seconds(OPS, "^copy", ("u32", (1472, 448, 1764))) == 0
    # rows left open: the ring's own padding
    assert xplane.select_seconds(OPS, "^copy", ("u32", (1536, None, 1764))) == (
        pytest.approx(0.020))
    # the ring as it was before PR 26, which no program has any more
    assert xplane.select_seconds(OPS, "^copy", ("u8", (1536, None, 7056))) == 0
    assert not xplane.shape_matches(("f32", (1536, 448, 1764)), ring)
    assert not xplane.shape_matches(("u32", (1536, 1764)), ring)
    assert not xplane.shape_matches(None, ring)
    renumbered = [dict(e, name=e["name"].replace("copy.219", "copy.17"))
                  for e in OPS]
    assert xplane.select_seconds(renumbered, "^copy", ring) == (
        pytest.approx(0.020))


@pytest.mark.parametrize("name,cut", [("nature_lstm512", 1),
                                      ("impala_deep_lstm2", 4)])
def test_the_ring_looked_for_is_the_programs_own(name, cut):
    """Dtype, rows and width come from the program's ring, not from the
    frame's height, width and channels: ``u32[blocks, rows, 1764]``."""
    from benchmark.drivers import train as training
    from benchmark.manifest import Manifest
    from r2d2_tpu.replay.device_ring import DeviceRing

    doc = Manifest().config(name)
    cfg = training.config_from_file(doc["config"])
    dtype, dims = training.ring_obs(cfg, cut)
    assert (dtype, dims[0], dims[2]) == ("u32", cfg.num_blocks // cut, 1764)
    assert dims[1] >= cfg.max_block_steps and dims[1] % 32 == 0
    # ... and at a size a test can allocate it is the array train() builds
    small = training.preset_config(doc, small=True)
    ring = DeviceRing(small, training.ACTION_DIM)
    dtype, dims = training.ring_obs(small)
    assert dims == ring.arrays["obs"].shape
    assert dtype == xplane.hlo_dtype(ring.arrays["obs"].dtype) == "u32"


def test_a_dtype_is_named_as_an_instructions_text_names_it():
    import numpy as np

    assert [xplane.hlo_dtype(d) for d in (
        np.uint8, np.uint32, np.int32, np.float32, np.bool_)] == [
        "u8", "u32", "s32", "f32", "pred"]
    import jax.numpy as jnp

    assert xplane.hlo_dtype(jnp.bfloat16) == "bf16"


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


def _ctx(trace, seconds, blocks=1536, **kw):
    class Cfg:
        superstep_k = 4

    return readers.ReadContext(
        cfg=Cfg, config_name="nature_lstm512", action_dim=4, chips=1,
        device_kind="TPU v5 lite", t_open=0.0, t_close=1.0,
        updates_per_s=80.0, span_mean_ms=lambda *a: None, trace=trace,
        trace_seconds=seconds, memory_peak_bytes=8_189_600_000,
        ring_fill_open=0.97,
        **dict(dict(ring_obs=("u32", (blocks, 448, 1764))), **kw))


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
    # a ring of another size is not this copy, and nor is a batch of u8
    # frames copied on its own; no trace, no device metric
    other = readers.read_all([_spec("ring_copy_device_share")],
                             _ctx(trace_of(OPS, MODULES), 0.0625, blocks=1472))
    assert other["ring_copy_device_share"]["value"] == 0.0
    batch_only = [e for e in OPS if "copy.219" not in e["name"]]
    assert readers.read_all(
        [_spec("ring_copy_device_share")],
        _ctx(trace_of(batch_only, MODULES), 0.0625))[
        "ring_copy_device_share"]["value"] == 0.0
    assert readers.read_all(
        [_spec("ring_copy_device_share")],
        _ctx(trace_of(OPS, MODULES), 0.0625, ring_obs=None)) == {}
    assert readers.read_all([_spec("device_idle_share.train"),
                             _spec("step_device_ms")], _ctx(None, 0.0)) == {}


def test_programs_cut_short_by_the_slices_edges_do_not_count():
    cut = [ev("jit_super_step(1)", 0, 11), ev("jit_super_step(1)", 11, 40),
           ev("jit_super_step(1)", 51, 44), ev("jit_super_step(1)", 95, 3)]
    got = readers.read_all([_spec("step_device_ms")],
                           _ctx(trace_of(OPS, cut), 0.098))
    assert got["step_device_ms"]["value"] == pytest.approx((40 + 44) / 2 / 4)


def test_act_call_us_is_retired_with_its_kind():
    """``act_span_us`` reads the same call from the program's own span."""
    from benchmark.manifest import Manifest, ManifestError

    m = Manifest()
    names = {e["name"] for e in m.doc["per_layer"]}
    assert "act_call_us" not in names and "act_span_us" in names
    with pytest.raises(ManifestError):
        m.layer_metric("act_call_us")
    with pytest.raises(ManifestError, match="reader_kinds/act_timer.py"):
        readers.resolve(dict(kind="act_timer"))
    assert "act_timer" not in {f.name for f in __import__(
        "dataclasses").fields(readers.ReadContext)}


# ---- device time by the program's named scopes

def scoped(name, start_ms, dur_ms, path):
    return dict(ev(name, start_ms, dur_ms, "f32[8]{0} fusion(...)"),
                path=path)


STEP = "jit(super_step)/jit(main)/while/body/"
SCOPED_OPS = [
    ev("while.3", 0, 100),                                  # no path at all
    scoped("fusion.1", 0, 10, STEP + "ring_gather/gather:"),
    scoped("fusion.2", 10, 20, STEP + "jvp(torso)/conv_general_dilated:"),
    scoped("fusion.3", 30, 10, STEP + "jvp(core)/while/body/dot_general:"),
    scoped("fusion.4", 40, 5, STEP + "target_forward/torso/conv:"),
    scoped("fusion.5", 45, 15,
           STEP + "transpose(jvp(core))/while/body/dot_general:"),
    scoped("fusion.6", 60, 30, STEP + "transpose(jvp(torso))/conv:"),
    scoped("copy.9", 90, 5, STEP + "copy:"),                 # under no scope
]


@pytest.mark.parametrize("path,scope", [
    (STEP + "jvp(core)/while/body/dot_general:", "core"),
    (STEP + "transpose(jvp(core))/while/body/dot_general:", "core.bwd"),
    (STEP + "transpose(jvp(vmap(heads)))/dot_general:", "heads.bwd"),
    # the outermost scope takes it: the target net's torso is the target's
    (STEP + "target_forward/torso/conv_general_dilated:", "target_forward"),
    (STEP + "transpose(jvp(loss))/torso/mul:", "loss.bwd"),
    (STEP + "copy:", xplane.NO_SCOPE),
    ("jit(super_step)/core_of_something/dot:", xplane.NO_SCOPE),
    (None, xplane.NO_SCOPE),
    ("", xplane.NO_SCOPE),
])
def test_an_operation_goes_to_the_outermost_scope_of_its_path(path, scope):
    assert xplane.scope_of(path) == scope


def test_the_scope_split_sums_to_the_busy_time():
    shares = xplane.scope_split(SCOPED_OPS)
    assert sum(shares.values()) == pytest.approx(100.0)
    assert shares == {
        "torso.bwd": pytest.approx(30.0), "torso": pytest.approx(20.0),
        "core.bwd": pytest.approx(15.0), "core": pytest.approx(10.0),
        "ring_gather": pytest.approx(10.0),
        xplane.NO_SCOPE: pytest.approx(10.0),     # copy.9 and the while
        "target_forward": pytest.approx(5.0)}
    assert list(shares)[0] == "torso.bwd"           # largest first
    assert xplane.scope_split([]) == {}


SCOPE_METRICS = {"torso_device_share": 50.0, "core_device_share": 25.0,
                 "target_forward_device_share": 5.0,
                 "ring_gather_device_share": 10.0}


def test_the_scope_metrics_read_forward_and_backward_together():
    specs = [_spec(n) for n in SCOPE_METRICS]
    assert all(s["kind"] == "scope_share" and s["unit"] == "%"
               and s["layer"] == "train step"
               and s["moves"] == "learner_frames_per_s" for s in specs)
    assert all(s["scope"] in xplane.SCOPES for s in specs)
    got = readers.read_all(specs, _ctx(trace_of(SCOPED_OPS, MODULES), 0.1))
    assert got == {n: dict(value=pytest.approx(v), unit="%")
                   for n, v in SCOPE_METRICS.items()}
    # a scope no operation of the slice lies under: nothing to read
    heads = dict(specs[0], name="heads_device_share", scope="heads")
    assert readers.read_all([heads], _ctx(trace_of(SCOPED_OPS, MODULES),
                                          0.1)) == {}
    # operations without paths (a compile cache older than the scopes, no
    # xplane_pb2): no metric, never a 0
    assert readers.read_all(specs, _ctx(trace_of(OPS, MODULES), 0.06)) == {}
    assert readers.read_all(specs, _ctx(None, 0.0)) == {}


def test_the_four_scope_metrics_are_read_in_every_training_cell():
    from benchmark.manifest import Manifest

    m = Manifest()
    entries = {e["name"]: e for e in m.doc["per_layer"]}
    for name in SCOPE_METRICS:
        assert "workloads" not in entries[name]
        assert entries[name]["source"] == "device_trace"
    for cell in m.workloads:
        assert set(SCOPE_METRICS) <= {s["name"]
                                      for s in m.cell(cell).per_layer}


def _write_xspace(path, pb2):
    """A profile as the chip writes it, by hand: one device plane whose
    ``XLA Ops`` events carry their path as the METADATA's ``tf_op`` stat —
    as a reference to a stat's name and as a string."""
    space = pb2.XSpace()
    plane = space.planes.add(name="/device:TPU:0")
    plane.stat_metadata[1].id, plane.stat_metadata[1].name = 1, "tf_op"
    plane.stat_metadata[2].id = 2
    plane.stat_metadata[2].name = STEP + "jvp(core)/dot_general:"
    plane.stat_metadata[3].id, plane.stat_metadata[3].name = 3, "flops"
    texts = {1: "%fusion.3 = f32[8]{0} fusion(...)",
             2: "%fusion.6 = f32[8]{0} fusion(...)",
             3: "%while.3 = (s32[]) while(...)"}
    for mid, text in texts.items():
        plane.event_metadata[mid].id = mid
        plane.event_metadata[mid].name = text
    plane.event_metadata[1].stats.add(metadata_id=1, ref_value=2)
    plane.event_metadata[1].stats.add(metadata_id=3, uint64_value=99)
    plane.event_metadata[2].stats.add(
        metadata_id=1, str_value=STEP + "transpose(jvp(torso))/conv:")
    modules = plane.lines.add(name="XLA Modules", timestamp_ns=1000)
    plane.event_metadata[4].id = 4
    plane.event_metadata[4].name = "jit_super_step(1)"
    modules.events.add(metadata_id=4, offset_ps=0, duration_ps=9_000_000)
    ops = plane.lines.add(name="XLA Ops", timestamp_ns=1000)
    for mid, offset_ns, dur_ns in ((3, 0, 9000), (1, 0, 3000), (2, 3000, 6000),
                                   (1, 9000, 1000)):
        ops.events.add(metadata_id=mid, offset_ps=offset_ns * 1000,
                       duration_ps=dur_ns * 1000)
    host = space.planes.add(name="/host:CPU")
    line = host.lines.add(name="python3", timestamp_ns=1000)
    host.event_metadata[1].id, host.event_metadata[1].name = 1, "bench_clock_sync"
    line.events.add(metadata_id=1, offset_ps=0, duration_ps=2_000_000)
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


def test_load_keeps_the_path_of_an_operation(tmp_path):
    pb2 = xplane._xplane_pb2()
    if pb2 is None:
        pytest.skip("no xplane_pb2 is installed here")
    path = str(tmp_path / "hand.xplane.pb")
    _write_xspace(path, pb2)
    trace = xplane.load(path)
    device, = xplane.device_planes(trace)
    ops = xplane.line_events(device, xplane.OPS_LINE)
    assert [(xplane.op_name(e), e["start_ns"], e["dur_ns"], e.get("path"))
            for e in ops] == [
        ("while.3", 1000, 9000, None),
        ("fusion.3", 1000, 3000, STEP + "jvp(core)/dot_general:"),
        ("fusion.6", 4000, 6000, STEP + "transpose(jvp(torso))/conv:"),
        ("fusion.3", 10000, 1000, STEP + "jvp(core)/dot_general:")]
    # the other lines keep a name, a start and a duration and nothing else
    assert xplane.line_events(device, xplane.MODULES_LINE) == [
        dict(name="jit_super_step(1)", start_ns=1000, dur_ns=9000)]
    assert xplane.clock_offset(trace, 0.0) == pytest.approx(1e-6)
    shares = xplane.scope_split(ops)
    assert shares == {"torso.bwd": pytest.approx(60.0),
                      "core": pytest.approx(40.0), xplane.NO_SCOPE: 0.0}
    # two readings of the file that do not show the same operations in the
    # same order attach nothing
    events = [dict(name="%other = f32[] add()", start_ns=0, dur_ns=1)]
    xplane._attach_paths(events, [("%fusion.3 = ...", "a/path:")])
    assert "path" not in events[0]


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


# ---- one whole super-step (k=4 updates, 25 ms, 11,708 operations) cut
# from PR 27's own traced run of nature_lstm512.fabric on the chip, in the
# form ``xplane.load`` returns: the operations keep their ``path``

@pytest.fixture(scope="module")
def scoped_slice():
    import gzip
    import json

    with gzip.open(os.path.join(FIXTURES, "fabric_scopes.json.gz"), "rt") as f:
        return json.load(f)["trace"]


def test_chip_slice_splits_by_scope(scoped_slice):
    plane, = xplane.device_planes(scoped_slice)
    ops = xplane.line_events(plane, xplane.OPS_LINE)
    assert len(ops) == 11708
    assert sum("path" in e for e in ops) == 7295
    step, = xplane.line_events(plane, xplane.MODULES_LINE)
    assert step["name"].startswith("jit_super_step(")
    assert xplane.busy_seconds(ops) == pytest.approx(0.024974632)
    shares = xplane.scope_split(ops)
    assert sum(shares.values()) == pytest.approx(100.0)
    assert list(shares)[:4] == ["torso.bwd", "target_forward", "core.bwd",
                                xplane.NO_SCOPE]
    assert shares["torso.bwd"] == pytest.approx(26.554849737)
    assert shares["ring_gather"] == pytest.approx(3.548464698)
    assert shares[xplane.NO_SCOPE] == pytest.approx(12.094296324)
    # every scope of the train step is there, the fused loop's are not
    assert {s.split(".")[0] for s in shares} - {xplane.NO_SCOPE} == {
        "torso", "core", "heads", "target_forward", "ring_gather",
        "per_sample", "per_scatter", "loss", "optimizer"}
    # the frames move as windows of words since PR 26; no copy of the ring
    assert xplane.select_seconds(ops, "^copy", ("u32", (1472, 448, 1764))) == 0


def test_chip_slice_through_the_scope_metric_files(scoped_slice):
    ctx = _ctx(scoped_slice, xplane.device_extent_seconds(scoped_slice),
               blocks=1472)
    got = readers.read_all([_spec(n) for n in (
        *SCOPE_METRICS, "ring_copy_device_share", "step_device_ms")], ctx)
    assert got["torso_device_share"]["value"] == pytest.approx(
        26.554849737 + 10.653578399)
    assert got["core_device_share"]["value"] == pytest.approx(
        15.444159498 + 8.094841998)
    assert got["target_forward_device_share"]["value"] == pytest.approx(
        16.135220731)
    assert got["ring_gather_device_share"]["value"] == pytest.approx(
        3.548464698)
    assert got["ring_copy_device_share"]["value"] == 0.0
    # one whole program in the slice: 24.98 ms for four updates
    assert got["step_device_ms"]["value"] == pytest.approx(24.982415 / 4)
    # the older fixture was cut before events kept their paths: no metric
    import gzip
    import json

    with gzip.open(os.path.join(FIXTURES, "fabric_slice.json.gz"), "rt") as f:
        old = json.load(f)["trace"]
    assert readers.read_all([_spec(n) for n in SCOPE_METRICS],
                            _ctx(old, 0.07, blocks=1472)) == {}
