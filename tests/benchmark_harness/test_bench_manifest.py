"""BENCHMARK.json and the files it names: legal, resolvable by name, and
extendable without an edit."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import run as bench_run
from benchmark.drivers import train as training
from benchmark.manifest import NAME_RE, ROOT, UNIT_RE, Manifest, ManifestError

MANIFEST = Manifest()
CELLS = sorted(MANIFEST.workloads)


def test_manifest_validates():
    MANIFEST.validate()
    doc = MANIFEST.doc
    assert doc["command"] == ["python3", "benchmark/run.py"]
    assert set(doc["paths"]) == {"benchmark", "tests/benchmark_harness"}
    assert len(json.dumps(doc)) < 64 * 1024


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_every_name_and_unit_is_legal(section):
    for entry in MANIFEST.doc[section]:
        assert NAME_RE.match(entry["name"]), entry["name"]
        if "unit" in entry:
            assert UNIT_RE.match(entry["unit"]), entry["unit"]
        for key in ("why", "layer", "source"):
            if key in entry:
                assert 1 <= len(entry[key]) <= 200 and "\t" not in entry[key]


def test_the_cells_are_the_issues_first_two_in_its_order():
    assert [w["name"] for w in MANIFEST.doc["workloads"]] == [
        "nature_lstm512.fabric", "impala_deep_lstm2.anakin"]
    assert all(w["chips"] == 1 for w in MANIFEST.doc["workloads"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_its_files_by_name(name):
    cell = MANIFEST.cell(name)
    assert cell.config["config"]["hidden_dim"] == 512     # no width is cut
    assert cell.traffic["driver"] == "train"
    assert os.path.isfile(os.path.join(
        ROOT, "benchmark", "drivers", cell.traffic["driver"] + ".py"))
    assert os.path.isfile(os.path.join(
        ROOT, "benchmark", "reference", cell.config_name + ".py"))
    assert {m["name"] for m in cell.end_to_end} >= {
        "setup_s", "learner_frames_per_s"}
    assert cell.per_layer and all("kind" in m for m in cell.per_layer)
    # the program accepts the configuration the files describe
    cfg = training.build_config(cell, rehearsal=False)
    # the window opens on a ring as full as a deployment's: pre-filled by
    # the harness (host actors) or filled by the loop's own rollouts
    assert cfg.learning_starts >= 0.9 * cfg.buffer_capacity
    assert (cell.traffic.get("prefill_ring_share") == 1.0) == bool(
        cell.traffic.get("host_envs"))
    # one cfg.seed for every run (the program compiles it in); the run's
    # own seed is folded to the 32 bits a PRNG key holds
    assert cfg.seed == training.PROGRAM_SEED
    assert 0 <= training.seed32(2 ** 31 + 5) < 2 ** 31


@pytest.mark.parametrize("name,preset,reduced", [
    ("nature_lstm512", "pong_config", {"buffer_capacity"}),
    ("impala_deep_lstm2", "impala_deep_config", {"buffer_capacity"}),
])
def test_config_file_is_the_preset_but_for_what_reduced_lists(
        name, preset, reduced):
    from r2d2_tpu import config as program_config

    entry = MANIFEST.configs[name]
    assert set(entry["reduced"]) == reduced
    with open(os.path.join(ROOT, entry["file"])) as f:
        doc = json.load(f)
    assert set(doc["reduced"]) == reduced
    want = getattr(program_config, preset)("Fake") if (
        preset == "impala_deep_config") else getattr(
        program_config, preset)(game_name="Fake")
    for key, value in doc["config"].items():
        if key not in reduced:
            got = getattr(want, key)
            assert (list(got) if isinstance(got, tuple) else got) == value, key
    # and nothing that shapes the model is left to a default that differs
    shaped = {"torso", "hidden_dim", "lstm_layers", "obs_shape",
              "obs_space_to_depth", "batch_size", "burn_in_steps",
              "learning_steps", "forward_steps", "block_length", "remat",
              "compute_dtype", "param_dtype"}
    assert shaped <= set(doc["config"])


def _copy_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(ROOT, "benchmark", sub),
                        tmp_path / "benchmark" / sub)
    return tmp_path


def test_a_cell_and_a_metric_added_as_files_are_found_with_no_edit(tmp_path):
    """What a later PR does: one config file, one traffic file, one metric
    file, and entries in the manifest — no code, no edited file."""
    root = _copy_benchmark(tmp_path)
    bench = root / "benchmark"
    cfg = json.loads((bench / "configs" / "nature_lstm512.json").read_text())
    cfg["config"]["lstm_layers"] = 3
    (bench / "configs" / "nature_lstm3.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "anakin.json").read_text())
    mix["config_overrides"]["num_actors"] = 128
    (bench / "traffic" / "anakin_128.json").write_text(json.dumps(mix))
    (bench / "layer_metrics" / "publish_host_ms.json").write_text(json.dumps(
        dict(kind="span", span="learner.publish", unit="ms",
             layer="learner drivetrain", moves="learner_frames_per_s",
             source="program_span", better="lower")))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append(dict(
        name="nature_lstm3", source="a test", reduced=[], why="a test",
        file="benchmark/configs/nature_lstm3.json"))
    doc["workloads"].append(dict(
        name="nature_lstm3.anakin_128", config="nature_lstm3",
        traffic="anakin_128", chips=1, why="a test"))
    doc["per_layer"].append(dict(
        name="publish_host_ms", unit="ms", better="lower",
        source="program_span", layer="learner drivetrain",
        moves="learner_frames_per_s",
        workloads=["nature_lstm3.anakin_128"]))
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    m = Manifest(str(root))
    m.validate()
    cell = m.cell("nature_lstm3.anakin_128")
    built = training.build_config(cell, rehearsal=False)
    assert (built.lstm_layers, built.num_actors) == (3, 128)
    assert "publish_host_ms" in {s["name"] for s in cell.per_layer}
    # ... and it reads through the same reader as the metrics that exist
    from benchmark import readers, window

    sink = window.DispatchSink(0, 1.0, keep_spans=True)
    sink.complete("learner.publish", 10.0, 0.004)
    ctx = readers.ReadContext(
        cfg=built, action_dim=4, chips=1, device_kind="cpu", t_open=0.0,
        t_close=20.0, updates_per_s=1.0, span_mean_ms=sink.span_mean_ms,
        trace=None, trace_seconds=0.0, memory_peak_bytes=None)
    got = readers.read_all(cell.per_layer, ctx)
    assert got["publish_host_ms"] == dict(value=pytest.approx(4.0), unit="ms")
    # a reader that finds nothing to read leaves its metric out
    assert "step_device_ms" not in got and "peak_hbm_bytes" not in got


@pytest.mark.parametrize("breakage,message", [
    (lambda d: d["workloads"][0].update(name="has space"), "illegal"),
    (lambda d: d["end_to_end"][0].update(unit="frames per s"), "unit"),
    (lambda d: d["end_to_end"][0].update(bound=0.5), "bound"),
    (lambda d: d["workloads"][0].update(chips=2), "chips"),
    (lambda d: d["workloads"].append(dict(d["workloads"][0], name="twice")),
     "repeated"),
    (lambda d: d["per_layer"][0].update(moves="act_p95_ms"), "moves"),
    (lambda d: d["workloads"][0].update(traffic="no_such_mix"), "no_such_mix"),
])
def test_validate_refuses_what_the_driver_would(tmp_path, breakage, message):
    root = _copy_benchmark(tmp_path)
    doc = json.loads((root / "BENCHMARK.json").read_text())
    breakage(doc)
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    with pytest.raises(ManifestError, match=message):
        Manifest(str(root)).validate()


def test_result_line_has_exactly_the_contracts_keys():
    device = dict(platform="tpu", kind="TPU v5 lite", count=1,
                  memory_peak_bytes=8_000_000_000)
    line = bench_run.result_line(
        True, 400, 0, {"setup_s": dict(value=52.5, unit="s")}, device)
    assert "\n" not in line
    doc = json.loads(line)
    assert set(doc) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(doc["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert doc["metrics"]["setup_s"] == {"value": 52.5, "unit": "s"}
    traced = json.loads(bench_run.result_line(
        True, 400, 0, {}, dict(device, busy_s=3.9, window_s=4.0),
        breakdown=dict(device_ops=[["copy.1", 0.2]], idle_gaps=[])))
    assert set(traced) - set(doc) == {"breakdown"}
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_cpu_is_refused_outside_rehearsal():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 1
    assert proc.stdout.strip() == ""
    assert "only run under --rehearsal" in proc.stderr


def test_rehearsal_shrinks_sizes_and_never_a_frame():
    cell = MANIFEST.cell("impala_deep_lstm2.anakin")
    cfg = training.build_config(cell, rehearsal=True)
    real = training.build_config(cell, rehearsal=False)
    assert cfg.hidden_dim < real.hidden_dim and cfg.num_blocks < 100
    assert cfg.obs_shape == real.obs_shape and cfg.torso == real.torso
    assert dataclasses.asdict(real)["anakin_episode_len"] == 4 * 375
