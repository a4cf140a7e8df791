"""Cross-process event tracing (ISSUE 10): ring/slab mechanics, the
clock-offset merge, incarnation-tagged flow ids, torn-slab rejection,
capture-controller windows, span percentiles, block-lineage wire stamps,
and the train() acceptance e2e — a /tracez capture of a live
process-transport + 2-replay-shard run producing a Perfetto-loadable
Chrome trace with trainer/fleet/shard tracks and a complete
cut→feedback lineage flow, with pipeline.* histograms in /metrics.
"""
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from r2d2_tpu.config import test_config as make_test_config
from r2d2_tpu.telemetry.registry import MetricsRegistry
from r2d2_tpu.telemetry.tracing import (
    EVENT_DTYPE,
    EventTracer,
    TraceController,
    TraceSlab,
    merge_tracks,
)
from r2d2_tpu.utils.trace import Tracer

A = 4


def _attach(slab, slot, incarnation, name):
    w = EventTracer()
    w.attach(slab.writer_info(slot, incarnation, name))
    w.poll()
    return w


# ------------------------------------------------------- ring mechanics

def test_disarmed_ring_records_nothing():
    slab = TraceSlab(1, 128)
    try:
        w = _attach(slab, 0, 0, "t")
        assert not w.armed
        w.instant("x.y")
        w.complete("a.b", time.perf_counter(), 0.01)
        w.flush()
        tracks, dropped = slab.harvest()
        assert dropped == 0
        assert sum(len(t["events"]) for t in tracks) == 0
        w.detach()
    finally:
        slab.close()


def test_ring_overflow_keeps_newest_in_order():
    slab = TraceSlab(1, 64)
    try:
        w = _attach(slab, 0, 0, "t")
        slab.set_armed(True, capture_id=1)
        w.poll()
        for i in range(100):
            w.complete("x.y", float(i), 0.5, arg=i)
        w.flush()
        tracks, dropped = slab.harvest()
        assert dropped == 0 and len(tracks) == 1
        t = tracks[0]
        assert t["overflow"] == 100 - 64
        args = [int(e["arg"]) for e in t["events"]]
        assert args == list(range(36, 100))       # newest, in order
        w.detach()
    finally:
        slab.close()


def test_capture_id_bump_resets_ring():
    slab = TraceSlab(1, 64)
    try:
        w = _attach(slab, 0, 0, "t")
        slab.set_armed(True, capture_id=1)
        w.poll()
        w.instant("old.event")
        slab.set_armed(True, capture_id=2)
        w.poll()                       # new capture: ring resets
        w.instant("new.event")
        w.flush()
        tracks, _ = slab.harvest()
        names = [e["name"].decode() for e in tracks[0]["events"]]
        assert names == ["new.event"]
        w.detach()
    finally:
        slab.close()


# ------------------------------------------- clock model / merge / CRC

def test_merge_is_monotone_per_track_under_clock_offsets():
    """Two writers with wildly different local clock origins: after the
    per-writer affine mapping each track's event order (and spacing) is
    preserved, and the cross-track alignment uses the wall handshake."""
    slab = TraceSlab(2, 64)
    try:
        w0 = _attach(slab, 0, 0, "trainer")
        w1 = _attach(slab, 1, 0, "fleet0")
        slab.set_armed(True, capture_id=1)
        w0.poll(), w1.poll()
        # fake divergent clock origins via the slab header handshake
        w0._views["clock"][0] = 0.0       # t0_perf
        w0._views["clock"][1] = 1000.0    # t0_wall
        w1._views["clock"][0] = 500.0
        w1._views["clock"][1] = 1000.0    # same wall origin, offset perf
        for i in range(5):
            w0._record(f"a{i}", b"X", 1.0 + i, 0.1, 0, "", 0)
            w1._record(f"b{i}", b"X", 501.0 + i, 0.1, 0, "", 0)
        w0.flush(), w1.flush()
        tracks, dropped = slab.harvest()
        assert dropped == 0 and len(tracks) == 2
        doc = merge_tracks(tracks)
        by_pid = {}
        for e in doc["traceEvents"]:
            if e.get("ph") == "X":
                by_pid.setdefault(e["pid"], []).append(e["ts"])
        for pid, ts in by_pid.items():
            assert ts == sorted(ts), f"track {pid} not monotone"
        # the two writers' events describe the SAME wall instants —
        # after the offset handshake they land interleaved, not shifted
        # by the 500 s perf-origin difference
        a, b = by_pid[0], by_pid[1]
        assert abs(a[0] - b[0]) < 1.0          # µs-scale, same origin
        w0.detach(), w1.detach()
    finally:
        slab.close()


def test_torn_slab_dropped_and_counted():
    slab = TraceSlab(2, 64)
    try:
        w0 = _attach(slab, 0, 0, "good")
        w1 = _attach(slab, 1, 0, "torn")
        slab.set_armed(True, capture_id=1)
        w0.poll(), w1.poll()
        w0.instant("ok.event")
        w1.instant("doomed.event")
        w0.flush(), w1.flush()
        # garble bytes inside slot 1's event region AFTER its CRC landed
        buf = np.frombuffer(slab.shm.buf, np.uint8)
        off = slab.ctrl_nbytes + slab.slot_nbytes \
            + slab.offsets["events"] + 8
        buf[off:off + 32] ^= 0xFF
        del buf           # release the exported pointer before close()
        tracks, dropped = slab.harvest()
        assert dropped == 1
        assert [t["name"] for t in tracks] == ["good"]
        w0.detach(), w1.detach()
    finally:
        slab.close()


def test_flow_ids_are_incarnation_tagged_across_respawn():
    """A respawned fleet re-attaches to the SAME slab slot with a bumped
    incarnation: its trace ids must never collide with its dead
    predecessor's (stale ids from the old stream survive in OTHER
    processes' rings and would otherwise stitch two different blocks
    into one flow)."""
    slab = TraceSlab(1, 64)
    try:
        w0 = _attach(slab, 0, 0, "fleet0")
        slab.set_armed(True, capture_id=1)
        w0.poll()
        ids0 = {w0.next_trace_id() for _ in range(50)}
        w0.detach()                      # the SIGKILLed predecessor
        w1 = _attach(slab, 0, 1, "fleet0")   # watchdog respawn, inc=1
        w1.poll()
        ids1 = {w1.next_trace_id() for _ in range(50)}
        assert not (ids0 & ids1)
        # the respawned writer's track carries the new incarnation
        w1.instant("x.y")
        w1.flush()
        tracks, _ = slab.harvest()
        assert tracks[0]["incarnation"] == 1
        w1.detach()
    finally:
        slab.close()


# ---------------------------------------------------- capture controller

def test_trace_controller_window_closes_on_step_target(tmp_path):
    slab = TraceSlab(1, 64)
    step = dict(n=0)
    ctl = TraceController(slab, lambda: step["n"], str(tmp_path))
    ctl.GRACE_SECONDS = 0.0
    try:
        w = _attach(slab, 0, 0, "trainer")
        ctl.tracer = w
        res = ctl.arm(3)
        assert res["armed"] and w.armed
        # a second arm while open is refused
        assert "error" in ctl.arm(1)
        assert ctl.poll() is None        # target not reached
        w.instant("in.window")
        step["n"] = 3
        path = ctl.poll()
        assert path and os.path.exists(path)
        assert not w.armed
        doc = json.load(open(path))
        names = {e["name"] for e in doc["traceEvents"]}
        assert "in.window" in names
        assert ctl.status()["last"]["events"] == 1
        # events after the window closed are not recorded
        w.instant("after.window")
        assert ctl.last["events"] == 1
        w.detach()
    finally:
        ctl.close()


def test_trace_controller_numbers_on_from_existing_dumps(tmp_path):
    """A resumed run (or a later soak round reusing the ckpt dir) must
    never overwrite an earlier capture — and a per-round dump check
    must never false-pass on a stale trace_1.json."""
    (tmp_path / "trace_3.json").write_text("{}")
    slab = TraceSlab(1, 64)
    ctl = TraceController(slab, lambda: 0, str(tmp_path))
    ctl.GRACE_SECONDS = 0.0
    try:
        w = _attach(slab, 0, 0, "trainer")
        ctl.tracer = w
        ctl.arm(1)
        path = ctl.poll(force=True)
        assert os.path.basename(path) == "trace_4.json"
        assert (tmp_path / "trace_3.json").read_text() == "{}"
        w.detach()
    finally:
        ctl.close()


def test_trace_controller_force_close_dumps_partial(tmp_path):
    slab = TraceSlab(1, 64)
    ctl = TraceController(slab, lambda: 0, str(tmp_path))
    ctl.GRACE_SECONDS = 0.0
    try:
        w = _attach(slab, 0, 0, "trainer")
        ctl.tracer = w
        ctl.arm(10 ** 9)
        w.instant("partial.event")
        assert ctl.poll() is None            # nowhere near the target
        path = ctl.poll(force=True)          # the shutdown path
        assert path and os.path.exists(path)
        w.detach()
    finally:
        ctl.close()


# ----------------------------------------- span percentiles / registry

def test_tracer_span_percentiles_monotone_and_sane():
    tr = Tracer(events=EventTracer())     # detached sink: no capture
    for ms in (1, 1, 1, 1, 1, 1, 1, 1, 1, 100):
        with tr.span("stage"):
            pass
        # inject exact durations instead of sleeping: reach into the
        # stat (the public span() path is exercised above)
        tr._spans["stage"].update(ms / 1e3, 0.05)
    snap = tr.snapshot()
    p50, p95, p99 = (snap["span.stage.p50_ms"], snap["span.stage.p95_ms"],
                     snap["span.stage.p99_ms"])
    assert p50 <= p95 <= p99
    # ~half the samples are 1 ms, the tail is 100 ms: the quantile
    # buckets must separate them (log buckets: answers are approximate)
    assert p50 < 5.0
    assert p99 > 50.0


def test_registry_observe_many_matches_observe_oracle():
    a, b = MetricsRegistry(), MetricsRegistry()
    vals = np.abs(np.random.default_rng(0).normal(0.05, 0.2, 500))
    for v in vals:
        a.observe("pipeline.block_age_at_train_s", float(v))
    b.observe_many("pipeline.block_age_at_train_s", vals)
    ha = a.snapshot()["histograms"]["pipeline.block_age_at_train_s"]
    hb = b.snapshot()["histograms"]["pipeline.block_age_at_train_s"]
    assert ha["counts"] == hb["counts"] and ha["count"] == hb["count"]
    assert ha["sum"] == pytest.approx(hb["sum"])   # summation order


# ------------------------------------------------- lineage wire stamps

def test_block_wire_format_carries_lineage_stamps():
    from r2d2_tpu.replay.block import (
        block_slot_spec,
        slot_layout,
        slot_views,
        write_block,
        read_block,
        slot_crc,
    )
    from test_actor_procs import scripted_blocks

    cfg = make_test_config()
    blocks = scripted_blocks(cfg, 1)
    block, prios, _ = blocks[0]
    block.trace_id = 0xDEAD
    assert block.cut_ts > 0                  # stamped at assembly
    spec = block_slot_spec(cfg, A)
    nbytes, offsets = slot_layout(spec)
    buf = bytearray(nbytes)
    views = slot_views(memoryview(buf), spec, offsets, nbytes, 0)
    k, n_obs, n_steps = write_block(views, block, prios)
    rb, _ = read_block(views, k, n_obs, n_steps)
    assert rb.trace_id == 0xDEAD
    assert rb.cut_ts == block.cut_ts
    # the stamps live OUTSIDE the CRC: garbling them must not cost the
    # block (telemetry, not experience)
    views["trace_id"][0] = 1234
    assert int(views["crc32"][0]) == slot_crc(views, k, n_obs, n_steps)


def test_replay_buffer_ages_and_flow_meta():
    from r2d2_tpu.replay.replay_buffer import ReplayBuffer
    from test_actor_procs import scripted_blocks

    cfg = make_test_config(learning_starts=8)
    buf = ReplayBuffer(cfg, A, rng=np.random.default_rng(0))
    for block, prios, ep in scripted_blocks(cfg, 4, partial_last=False):
        block.cut_ts = time.time() - 5.0     # a 5 s old block
        buf.add(block, prios, ep)
    batch = buf.sample_batch(8)
    ages = batch["ages"]
    assert ages.shape == (8, 2)
    assert (ages[:, 0] >= 4.0).all() and (ages[:, 0] < 60.0).all()
    assert (ages[:, 1] >= 0.0).all() and (ages[:, 1] < 5.0).all()


# ------------------------------------------------------- train() e2e

# slow: ~30 s multi-process capture on the tier-1 wall budget (ISSUE 15
# rebalance).  The controller/merge/lineage/incarnation claims stay
# pinned by the unit layer above, and chaos_soak --trace verifies a
# live capture (dump parsed, new-incarnation events) every soak round.
@pytest.mark.slow
@pytest.mark.timeout(600)
def test_train_e2e_tracez_capture_process_transport_sharded(tmp_path):
    """Acceptance (ISSUE 10): a /tracez capture of a live
    actor_transport="process" + replay_shards=2 run produces a Chrome
    trace that parses, carries trainer + fleet + shard process tracks
    (≥3), contains at least one COMPLETE block-lineage flow (env-step/
    cut through priority feedback), and /metrics shows
    pipeline.block_age_at_train_s populated."""
    from test_actor_procs import make_fake_env
    from r2d2_tpu.train import train

    cfg = make_test_config(
        game_name="Fake", training_steps=150, num_actors=2,
        actor_fleets=1, actor_transport="process", replay_shards=2,
        buffer_capacity=160, learning_starts=16, log_interval=0.2,
        telemetry_port=-1, save_interval=10 ** 6)
    seen = dict(port=0, armed=False, metrics=None)

    def sink(entry):
        seen["port"] = entry["telemetry_port"]
        base = f"http://127.0.0.1:{seen['port']}"
        if not seen["armed"] and entry.get("training_steps", 0) > 0:
            # arm past the run's end: the shutdown force-close dumps a
            # window spanning every remaining block lifecycle
            with urllib.request.urlopen(
                    base + "/tracez?steps=1000000", timeout=10) as r:
                assert json.load(r)["armed"]
            seen["armed"] = True
        elif seen["armed"] and seen["metrics"] is None:
            with urllib.request.urlopen(base + "/metrics",
                                        timeout=10) as r:
                seen["metrics"] = r.read().decode()

    m = train(cfg, env_factory=make_fake_env,
              checkpoint_dir=str(tmp_path), verbose=False, log_sink=sink,
              max_wall_seconds=420)
    assert m["num_updates"] > 0 and not m.get("fabric_failed")
    assert seen["armed"], "run ended before /tracez could arm"

    # pipeline histograms reached /metrics during the run
    assert seen["metrics"] is not None
    count = [ln for ln in seen["metrics"].splitlines()
             if ln.startswith("r2d2_pipeline_block_age_at_train_s_count")]
    assert count and float(count[0].split()[-1]) > 0
    assert "r2d2_pipeline_hop_cut_to_ingest_s_count" in seen["metrics"]

    dumps = [f for f in os.listdir(tmp_path / "telemetry")
             if f.startswith("trace_") and f.endswith(".json")]
    assert dumps, "force-closed capture left no dump"
    doc = json.load(open(tmp_path / "telemetry" / dumps[0]))
    evs = doc["traceEvents"]
    tracks = sorted(e["args"]["name"] for e in evs
                    if e.get("ph") == "M" and e["name"] == "process_name")
    assert "trainer" in tracks and "fleet0" in tracks
    assert {"shard0", "shard1"} <= set(tracks)
    assert len(tracks) >= 3
    flows = {}
    for e in evs:
        if e.get("ph") in ("s", "t", "f"):
            flows.setdefault(e["id"], set()).add(e["ph"])
    complete = [i for i, phs in flows.items() if {"s", "f"} <= phs]
    assert complete, "no complete cut→feedback lineage flow in the dump"
    names = {e["name"] for e in evs}
    assert {"block.env_steps+cut", "ingest.block", "replay.route",
            "replay.sample", "replay.priority_feedback"} <= names


# ------------------------------------- the profiler's clock (PR 25, §2)

class _FakeAnnotation:
    """Stands in for jax.profiler's annotation classes: no profiler runs
    on the CPU here; the test needs to see what a span constructs."""

    made = []

    def __init__(self, name, **kw):
        self.made.append((type(self).__name__, name, kw))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class _FakeStepAnnotation(_FakeAnnotation):
    pass


@pytest.fixture
def fake_annotations(monkeypatch):
    import jax

    _FakeAnnotation.made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation",
                        _FakeStepAnnotation)
    return _FakeAnnotation.made


def test_span_annotates_only_under_an_open_device_profile(
        fake_annotations, monkeypatch):
    """Closed, Tracer.span constructs no annotation; under the flag an
    open device_profile() sets it opens a TraceAnnotation of its own name,
    and a span given a step also the profiler's step marker."""
    from r2d2_tpu.utils import trace

    tr = Tracer(events=None)
    assert trace._profile_open is False
    with tr.span("actor.act"):
        pass
    with tr.span("learner.step_dispatch", 7):
        pass
    assert fake_annotations == []

    monkeypatch.setattr(trace, "_profile_open", True)
    with tr.span("actor.act"):
        pass
    with tr.span("learner.step_dispatch", 7):
        pass
    assert fake_annotations == [
        ("_FakeAnnotation", "actor.act", {}),
        ("_FakeStepAnnotation", "dispatch", {"step_num": 7}),
        ("_FakeAnnotation", "learner.step_dispatch", {})]
    # the spans themselves are recorded either way
    assert tr.snapshot()["span.actor.act.count"] == 2


def test_device_profile_sets_the_flag_and_leaves_a_clock_mark(
        fake_annotations, monkeypatch, tmp_path):
    """device_profile() opens the flag for its body only (cleared on an
    exception too), writes one sync annotation and records the
    perf_counter read inside it beside the dump."""
    import jax

    from r2d2_tpu.utils import trace

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls.append(("start", d, kw)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    t_before = time.perf_counter()
    with pytest.raises(RuntimeError):
        with trace.device_profile(str(tmp_path)):
            assert trace._profile_open is True
            with Tracer(events=None).span("learner.publish"):
                pass
            raise RuntimeError("x")
    assert trace._profile_open is False
    assert [c[0] for c in calls] == ["start", "stop"]
    # the profiler's own Python tracer stays off: the spans are the
    # host's timeline
    assert calls[0][2]["profiler_options"].python_tracer_level == 0
    assert fake_annotations == [
        ("_FakeAnnotation", trace.PROFILE_SYNC, {}),
        ("_FakeAnnotation", "learner.publish", {})]
    with open(tmp_path / trace.PROFILE_SYNC_FILE) as f:
        mark = json.load(f)
    assert mark["annotation"] == trace.PROFILE_SYNC
    assert t_before <= mark["perf_counter"] <= time.perf_counter()


def test_a_real_profile_holds_the_spans_on_their_own_threads(tmp_path):
    """End to end on the CPU backend's profiler: spans of two threads land
    on two lines of the dump's host plane, beside the sync annotation."""
    from jax.profiler import ProfileData

    from r2d2_tpu.utils.trace import PROFILE_SYNC, device_profile

    tr = Tracer(events=None)

    def actor():
        for _ in range(3):
            with tr.span("actor.act"):
                time.sleep(0.001)

    with device_profile(str(tmp_path)):
        t = threading.Thread(target=actor)
        t.start()
        for n in range(3):
            with tr.span("learner.step_dispatch", n):
                time.sleep(0.001)
        t.join(timeout=30)
        assert not t.is_alive()
    (path,) = (tmp_path / "plugins" / "profile").glob("*/*.xplane.pb")
    lines = {}      # span name -> the lines of the host plane that hold it
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                lines.setdefault(ev.name, set()).add(i)
    assert PROFILE_SYNC in lines
    assert len(lines["actor.act"]) == len(lines["learner.step_dispatch"]) == 1
    assert lines["actor.act"] != lines["learner.step_dispatch"]
    assert lines["dispatch"] == lines["learner.step_dispatch"]


def test_maybe_span_and_held():
    """The shared helpers: no tracer, no span (one shared no-op); held()
    takes the lock, records the wait, and releases on an exception."""
    from r2d2_tpu.utils.trace import held, maybe_span

    assert maybe_span(None, "a.b") is maybe_span(None, "c.d")
    with maybe_span(None, "a.b"):
        pass
    tr = Tracer(events=None)
    with maybe_span(tr, "a.b"):
        pass
    assert tr.snapshot()["span.a.b.count"] == 1

    lock = threading.Lock()
    with pytest.raises(ValueError):
        with held(lock, tr, "x.wait"):
            assert lock.locked()
            raise ValueError("x")
    assert not lock.locked()
    assert tr.snapshot()["span.x.wait.count"] == 1
    with held(lock, None, "x.wait"):
        assert lock.locked()
    assert not lock.locked()

    # a contended lock: the wait span covers the time the holder kept it
    lock.acquire()
    threading.Timer(0.05, lock.release).start()
    with held(lock, tr, "y.wait"):
        pass
    assert tr.snapshot()["span.y.wait.mean_ms"] >= 30.0


# ----------------------------------- tools/step_split.py (PR 25, §3)

def _step_split():
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "step_split.py")
    spec = importlib.util.spec_from_file_location("step_split", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("path,scope", [
    ("jit(super_step)/jit(main)/while/body/ring_gather/gather:", "ring_gather"),
    ("jit(super_step)/while/body/jvp(R2D2Network.unroll)/torso/"
     "R2D2Network._features/torso/conv_general_dilated:", "torso"),
    ("jit(super_step)/while/body/transpose(jvp(R2D2Network.unroll))/core/"
     "R2D2Network._lstm_stack/lstm_0/while/body/dot_general:", "core.bwd"),
    ("jit(super_step)/while/body/transpose(jvp(loss))/mul:", "loss.bwd"),
    # the outermost scope wins: the target net's torso is target_forward,
    # the fused loop's act forward is act
    ("jit(super_step)/while/body/jvp(target_forward)/R2D2Network.unroll/"
     "torso/relu:", "target_forward"),
    ("jit(super_step)/while/body/while/body/act/R2D2Network.act/core/"
     "lstm_0/dot_general:", "act"),
    ("jit(super_step)/while/body/optimizer/jit(_where)/select_n:",
     "optimizer"),
    # a scope is a whole component: `core_details` is not `core`
    ("jit(super_step)/while/body/core_details/add:", "(none)"),
    ("jit(super_step)/while/body/dynamic_slice:", "(none)"),
    ("", "(none)"),
    (None, "(none)"),
])
def test_step_split_files_an_operation_under_its_outermost_scope(path, scope):
    assert _step_split().scope_of(path) == scope


def test_step_split_shares_sum_to_the_busy_time():
    """The reduction on a hand-made line: a container keeps only its self
    time, an operation with no path is (none), shares are of the busy
    union and sum to 100."""
    ss = _step_split()
    ms = 1_000_000

    def ev(start, dur, path=None):
        return dict(name=f"%op.{start}", start_ns=start * ms,
                    dur_ns=dur * ms, path=path)

    events = [
        ev(0, 40, "jit(super_step)/while:"),                  # container
        ev(0, 10, "jit(super_step)/while/body/ring_gather/gather:"),
        ev(10, 20, "jit(super_step)/while/body/jvp(x)/core/dot_general:"),
        ev(30, 6, "jit(super_step)/while/body/transpose(jvp(x))/core/dot:"),
        ev(36, 2),                                            # no metadata
        ev(50, 12, "jit(super_step)/while/body/optimizer/add:"),  # a gap before
    ]
    shares = ss.split(events)
    assert shares == pytest.approx({
        "core": 100 * 20 / 52, "optimizer": 100 * 12 / 52,
        "ring_gather": 100 * 10 / 52, "core.bwd": 100 * 6 / 52,
        "(none)": 100 * (2 + 2) / 52})
    assert list(shares)[0] == "core"            # largest first
    assert sum(shares.values()) == pytest.approx(100.0)
    assert ss.split([]) == {}


def test_step_split_reads_the_scope_from_the_events_metadata(tmp_path):
    """A hand-made XSpace as the TPU writes it (probed on the chip, PR 25):
    the op_name path is the stat ``tf_op`` of the event's METADATA, times
    are picoseconds from the line's timestamp."""
    ss = _step_split()
    pb = ss._xplane_pb2()
    space = pb.XSpace()
    host = space.planes.add(name="/host:CPU")
    host.lines.add(name="python")
    plane = space.planes.add(name="/device:TPU:0")
    plane.stat_metadata[1].name = "tf_op"
    plane.stat_metadata[2].name = "hlo_category"
    for mid, (name, path) in enumerate([
            ("%fusion.1 = bf16[8] fusion(...)",
             "jit(super_step)/while/body/jvp(x)/torso/conv:"),
            ("%fusion.2 = f32[8] fusion(...)",
             "jit(super_step)/while/body/optimizer/add:"),
            ("%copy.3 = u8[8] copy(...)", None)], start=1):
        md = plane.event_metadata[mid]
        md.id, md.name = mid, name
        md.stats.add(metadata_id=2, str_value="fusion")
        if path:
            md.stats.add(metadata_id=1, str_value=path)
    modules = plane.lines.add(name="XLA Modules", timestamp_ns=1000)
    modules.events.add(metadata_id=1, offset_ps=0, duration_ps=9_000_000)
    ops = plane.lines.add(name="XLA Ops", timestamp_ns=1000)
    for mid, off_ns, dur_ns in [(1, 0, 6000), (2, 6000, 3000), (3, 9000, 1000),
                                (1, 20000, 10000)]:
        ops.events.add(metadata_id=mid, offset_ps=off_ns * 1000,
                       duration_ps=dur_ns * 1000)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())

    events = ss.load_ops(str(path))
    assert [(e["start_ns"], e["dur_ns"]) for e in events] == [
        (1000, 6000), (7000, 3000), (10000, 1000), (21000, 10000)]
    assert events[2]["path"] is None
    assert ss.split(events) == pytest.approx(
        {"torso": 80.0, "optimizer": 15.0, "(none)": 5.0})
    assert ss.load_ops(str(path), device=1) == []
    assert ss.main([str(path), "--json"]) == 0
