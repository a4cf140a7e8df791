"""The ten set-up metrics: each is one file in ``layer_metrics/`` read by
the reader kind ``setup_span``, which takes a one-shot ``setup.*`` span of
the program that ended before the window opened, in seconds."""
import os

import pytest

from benchmark import readers, window
from benchmark.manifest import Manifest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SETUP_METRICS = {
    "setup_train_s": "setup.train",
    "setup_state_s": "setup.state",
    "setup_ring_s": "setup.ring",
    "setup_drivetrain_s": "setup.drivetrain",
    "setup_fill_s": "setup.fill",
    "setup_first_dispatch_s": "setup.first_dispatch",
    "setup_trace_s": "setup.trace",
    "setup_lower_s": "setup.lower",
    "setup_compile_s": "setup.compile",
    "setup_cache_load_s": "setup.cache_load",
}


def _ctx(sink):
    return readers.ReadContext(
        cfg=None, config_name="", action_dim=4, chips=1,
        device_kind="TPU v5 lite", t_open=100.0, t_close=200.0,
        updates_per_s=1.0, span_mean_ms=sink.span_mean_ms, trace=None,
        trace_seconds=0.0, memory_peak_bytes=None)


def _program_source():
    source = ""
    for folder, _, files in os.walk(os.path.join(ROOT, "r2d2_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(folder, f)) as fh:
                    source += fh.read()
    return source


@pytest.mark.parametrize("name", sorted(SETUP_METRICS))
def test_a_setup_metric_reads_its_span_from_before_the_window(name):
    m = Manifest()
    spec = m.layer_metric(name)
    assert (spec["kind"], spec["span"], spec["divisor"], spec["unit"],
            spec["layer"], spec["moves"], spec["source"]) == (
        "setup_span", SETUP_METRICS[name], 1000, "s", "set-up", "setup_s",
        "program_span")
    entry, = [e for e in m.doc["per_layer"] if e["name"] == name]
    assert "workloads" not in entry             # every cell reads it
    assert all(spec in m.cell(c).per_layer for c in m.workloads)
    # the span is a literal of the program: a rename cannot pass unseen
    assert f'"{spec["span"]}"' in _program_source()
    sink = window.DispatchSink(0, 1.0, keep_spans=True)
    # a program without the span (an older commit): nothing, no error
    assert readers.read_all([spec], _ctx(sink)) == {}
    sink.complete(spec["span"], 150.0, 2.0)     # ended inside the window
    sink.complete("learner.step_dispatch", 10.0, 0.5)   # another span
    assert readers.read_all([spec], _ctx(sink)) == {}
    sink.complete(spec["span"], 10.0, 31.25)
    assert readers.read_all([spec], _ctx(sink)) == {
        name: dict(value=pytest.approx(31.25), unit="s")}


def test_a_span_that_ends_as_the_window_opens_is_read():
    spec = Manifest().layer_metric("setup_train_s")
    sink = window.DispatchSink(0, 1.0, keep_spans=True)
    sink.complete("setup.train", 70.0, 30.0)
    assert readers.read_all([spec], _ctx(sink))["setup_train_s"][
        "value"] == pytest.approx(30.0)
