"""The per-layer metrics that read the program's own spans and program names
(PR 25): each is one file in ``layer_metrics/`` read by a reader that was
there, and what each reads exists in the program under that very name — a
span as a literal in ``r2d2_tpu/``, a program as the module name it lowers
to, a scope in the lowered super-step's metadata."""
import gzip
import json
import os
import re

import pytest

from benchmark import readers, window, xplane
from benchmark.drivers import train as training
from benchmark.manifest import Manifest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FABRIC, ANAKIN = "nature_lstm512.fabric", "impala_deep_lstm2.anakin"

SPAN_METRICS = {    # metric -> (span, microseconds?)
    "act_span_us": ("actor.act", True),
    "env_step_host_ms": ("actor.env_step", False),
    "actor_record_host_ms": ("actor.record", False),
    "block_cut_host_ms": ("actor.cut", False),
    "ring_stage_host_ms": ("replay.stage", False),
    "ring_commit_host_ms": ("replay.commit", False),
    "dispatch_lock_wait_ms": ("learner.lock_wait", False),
    # not ISSUE 25's `publish_host_ms`: test_bench_manifest.py adds a metric
    # of that name to a copy of the manifest, and may not be edited
    "param_publish_host_ms": ("learner.publish", False),
}
TRACE_METRICS = ("ring_write_device_share", "aux_programs_device_share")
STEP_SCOPES = ("torso", "core", "heads", "target_forward", "ring_gather",
               "per_sample", "per_scatter", "loss", "optimizer")
LOOP_SCOPES = ("env_step", "act", "ring_write")


def test_the_manifest_validates_with_the_ten_entries():
    """PR 25's ten; the cells are run here as examples."""
    m = Manifest()
    m.validate()
    new = [e for e in m.doc["per_layer"]
           if e["name"] in SPAN_METRICS or e["name"] in TRACE_METRICS]
    assert len(new) == 10
    # every new entry lists its cells, so a later cell is not held to it
    assert all(e["workloads"] for e in new)
    fabric = {s["name"] for s in m.cell(FABRIC).per_layer}
    anakin = {s["name"] for s in m.cell(ANAKIN).per_layer}
    assert set(SPAN_METRICS) | set(TRACE_METRICS) <= fabric
    assert anakin & (set(SPAN_METRICS) | set(TRACE_METRICS)) == {
        "aux_programs_device_share"}
    for name, (span, _) in SPAN_METRICS.items():
        spec = m.layer_metric(name)
        assert (spec["kind"], spec["span"], spec["source"]) == (
            "span", span, "program_span")
    for name in TRACE_METRICS:
        spec = m.layer_metric(name)
        assert (spec["kind"], spec["select"], spec["reduce"],
                spec["source"]) == ("xplane_ops", "modules",
                                    "share_of_busy", "device_trace")


def _ctx(sink, trace=None):
    return readers.ReadContext(
        cfg=None, config_name="", action_dim=4, chips=1,
        device_kind="TPU v5 lite",
        t_open=0.0, t_close=100.0, updates_per_s=1.0,
        span_mean_ms=sink.span_mean_ms, trace=trace,
        trace_seconds=xplane.device_extent_seconds(trace) if trace else 0.0,
        memory_peak_bytes=None)


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_a_span_metric_reads_its_span_through_the_reader_that_exists(name):
    span, micro = SPAN_METRICS[name]
    spec = Manifest().layer_metric(name)
    sink = window.DispatchSink(0, 1.0, keep_spans=True)
    # a program without the span (the parent commit): nothing, no error
    assert readers.read_all([spec], _ctx(sink)) == {}
    sink.complete(span, 10.0, 0.004)
    sink.complete(span, 20.0, 0.006)
    sink.complete(span, 200.0, 1.0)           # ended outside the window
    sink.complete("learner.step_dispatch", 30.0, 0.5)     # another span
    got = readers.read_all([spec], _ctx(sink))
    assert got == {name: dict(value=pytest.approx(5000.0 if micro else 5.0),
                              unit="us" if micro else "ms")}


@pytest.fixture(scope="module")
def chip_slice():
    with gzip.open(os.path.join(HERE, "fixtures", "fabric_slice.json.gz"),
                   "rt") as f:
        return json.load(f)["trace"]


def test_the_program_shares_read_the_chip_slice(chip_slice):
    """The fixture is a slice of PR 24's fabric cell: its programs still
    carry the names of that commit (``jit__lambda``, ``jit__unknown``)."""
    m = Manifest()
    sink = window.DispatchSink(0, 1.0, keep_spans=True)
    got = readers.read_all([m.layer_metric(n) for n in TRACE_METRICS],
                           _ctx(sink, chip_slice))
    modules = xplane.line_events(xplane.device_planes(chip_slice)[0],
                                 xplane.MODULES_LINE)
    by_name = {}
    for ev in modules:
        key = ev["name"].split("(")[0]
        by_name[key] = by_name.get(key, 0) + ev["dur_ns"]
    assert by_name["jit__write_slot_fn"] == 65971
    busy_ns = 1e9 * xplane.busy_seconds(xplane.line_events(
        xplane.device_planes(chip_slice)[0], xplane.OPS_LINE))
    # the slot write is found under the name it still has; the PER write
    # of that commit was a bare partial (jit__unknown) and is not
    assert got["ring_write_device_share"]["value"] == pytest.approx(
        100.0 * 65971 / busy_ns)
    aux = sum(ns for k, ns in by_name.items() if k != "jit_super_step")
    assert aux == 109342 + 65971 + 4362 + 3179
    assert got["aux_programs_device_share"]["value"] == pytest.approx(
        100.0 * aux / busy_ns)
    assert 0.0 < got["ring_write_device_share"]["value"] < (
        got["aux_programs_device_share"]["value"]) < 1.0


def test_every_span_a_metric_file_names_is_a_literal_of_the_program():
    """A rename in the program cannot pass unseen: a span metric's span
    occurs in ``r2d2_tpu/`` as the string literal a call site passes."""
    source = ""
    for folder, _, files in os.walk(os.path.join(ROOT, "r2d2_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(folder, f)) as fh:
                    source += fh.read()
    metrics_dir = os.path.join(ROOT, "benchmark", "layer_metrics")
    spans = set()
    for f in os.listdir(metrics_dir):
        with open(os.path.join(metrics_dir, f)) as fh:
            spec = json.load(fh)
        if spec["kind"] == "span":
            spans.add(spec["span"])
    assert {s for s, _ in SPAN_METRICS.values()} <= spans
    for span in sorted(spans):
        assert f'"{span}"' in source, span


# ---- the names the device's timeline shows: what the two program shares
# match, and what the scope metrics (kind scope_share) split the step by

def _scope_metric_scopes():
    metrics_dir = os.path.join(ROOT, "benchmark", "layer_metrics")
    out = set()
    for f in os.listdir(metrics_dir):
        with open(os.path.join(metrics_dir, f)) as fh:
            spec = json.load(fh)
        if spec["kind"] == "scope_share":
            out.add(spec["scope"])
    return out


def test_every_scope_a_metric_file_names_is_a_scope_of_the_program():
    """A rename in the program cannot pass unseen: a scope metric's scope
    is one the reduction knows and a ``named_scope`` literal in
    ``r2d2_tpu/``; the two tests of the lowered super-steps below find
    each in the programs' metadata."""
    scopes = _scope_metric_scopes()
    assert {"torso", "core", "target_forward", "ring_gather"} <= scopes
    assert scopes <= set(STEP_SCOPES + LOOP_SCOPES) == set(xplane.SCOPES)
    source = ""
    for folder, _, files in os.walk(os.path.join(ROOT, "r2d2_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(folder, f)) as fh:
                    source += fh.read()
    for scope in sorted(scopes):
        assert re.search(rf'named_scope\(\s*"{scope}"\s*\)', source), scope


def _program(cell_name):
    """The cell's program at rehearsal sizes: config, network, state."""
    import jax

    from r2d2_tpu.learner.step import create_train_state
    from r2d2_tpu.models.network import create_network, init_params

    cfg = training.build_config(Manifest().cell(cell_name), rehearsal=True)
    net = create_network(cfg, training.ACTION_DIM)
    params = init_params(cfg, net, jax.random.PRNGKey(0))
    return cfg, net, create_train_state(cfg, params)


def _module_name(lowered) -> str:
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


def _scopes_of(lowered) -> set:
    """What the scope reduction (``benchmark/xplane.scope_of``) would file
    the lowered program's operations under: its own reading of every
    operation's name path."""
    return {xplane.scope_of(path) for path in re.findall(
        r'loc\("([^"]+)"', lowered.as_text(debug_info=True))}


@pytest.fixture(scope="module")
def fabric_programs():
    """Every program the fabric cell dispatches in the steady state,
    lowered: the super-step, the two halves of a block write, the publish
    copy."""
    import jax.numpy as jnp
    import numpy as np

    from r2d2_tpu.learner.learner import Learner
    from r2d2_tpu.parallel.mesh import trivial_mesh
    from r2d2_tpu.parallel.sharding import (
        ShardingTable,
        pjit_in_graph_per_super_step,
    )
    from r2d2_tpu.replay import device_ring
    from r2d2_tpu.utils.store import ParamStore

    cfg, net, state = _program(FABRIC)
    ring = device_ring.DeviceRing(cfg, training.ACTION_DIM)
    meta = ring.per_meta()
    super_step = pjit_in_graph_per_super_step(
        cfg, net, ShardingTable(trivial_mesh(), cfg), cfg.superstep_k,
        state_template=state).lower(
            state, ring.snapshot(), ring.take_prios(), meta["seq_meta"],
            meta["first"], jnp.uint32(0))
    slot = {k: np.zeros(shape, dtype)
            for k, (shape, dtype) in ring._slot_shapes.items()}
    K = cfg.seqs_per_block
    learner = Learner(cfg, net, state, param_store=ParamStore())
    return dict(
        super_step=super_step,
        write_slot=device_ring._write_slot.lower(ring.arrays, slot,
                                                 np.int32(0)),
        write_per=ring._per_write.lower(
            ring.take_prios(), meta["seq_meta"], meta["first"],
            np.zeros((K,), np.float32), np.zeros((K, 3), np.int32),
            np.int32(0), np.int32(0)),
        publish=learner._copy_params.lower(learner.state.params))


@pytest.mark.parametrize("program,module", [
    ("super_step", "jit_super_step"),
    ("write_slot", "jit__write_slot_fn"),
    ("write_per", "jit_ring_write_per"),
    ("publish", "jit_publish_copy_params"),
])
def test_a_steady_state_program_lowers_under_its_stable_name(
        fabric_programs, program, module):
    name = _module_name(fabric_programs[program])
    assert name == module
    specs = {n: Manifest().layer_metric(n) for n in TRACE_METRICS}
    is_aux = re.search(specs["aux_programs_device_share"]["match"], name)
    is_write = re.search(specs["ring_write_device_share"]["match"], name)
    assert bool(is_aux) == (program != "super_step")
    assert bool(is_write) == program.startswith("write_")
    assert "lambda" not in name and "unknown" not in name


def test_the_fabric_super_step_carries_every_scope(fabric_programs):
    found = _scopes_of(fabric_programs["super_step"])
    assert set(STEP_SCOPES) <= found
    assert _scope_metric_scopes() <= found
    # forward and backward separate themselves
    assert {"torso.bwd", "core.bwd", "heads.bwd", "loss.bwd"} <= found
    assert not found & {s + ".bwd" for s in (
        "target_forward", "ring_gather", "per_sample", "per_scatter",
        "optimizer")}


@pytest.fixture(scope="module")
def fused_super_step():
    from r2d2_tpu.learner.anakin import AnakinPlane
    from r2d2_tpu.replay.device_ring import DeviceRing

    import jax.numpy as jnp

    cfg, net, state = _program(ANAKIN)
    # as train() does for the fused loop
    cfg = cfg.replace(device_replay=True, in_graph_per=True)
    plane = AnakinPlane(cfg, net, training.ACTION_DIM,
                        DeviceRing(cfg, training.ACTION_DIM))
    return plane.super_step.lower(state, plane.state, *plane._handles(),
                                  jnp.uint32(0))


def test_the_fused_loop_lowers_as_super_step_too(fused_super_step):
    assert _module_name(fused_super_step) == "jit_super_step"


def test_the_fused_super_step_carries_every_scope(fused_super_step):
    found = _scopes_of(fused_super_step)
    assert set(STEP_SCOPES + LOOP_SCOPES) <= found
    assert _scope_metric_scopes() <= found
    assert {"torso.bwd", "core.bwd"} <= found
    assert not found & {"act.bwd", "env_step.bwd", "ring_write.bwd"}
