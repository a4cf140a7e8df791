"""BENCHMARK.json and the files it names: legal, resolvable by name, and
extendable without an edit."""
import dataclasses
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import readers
from benchmark import run as bench_run
from benchmark.drivers import train as training
from benchmark.manifest import (
    NAME_RE,
    ROOT,
    UNIT_RE,
    Manifest,
    ManifestError,
    find_module,
)

MANIFEST = Manifest()
CELLS = sorted(MANIFEST.workloads)
CONFIGS = sorted(MANIFEST.configs)


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


# the cells this benchmark has been accepted with, and the chips each was
# accepted with: they stay.  A cell that comes later needs no line here.
ACCEPTED = {"nature_lstm512.fabric": 1, "impala_deep_lstm2.anakin": 1}


def test_the_accepted_cells_are_still_there_and_the_rest_is_within_limits():
    cells = {w["name"]: w["chips"] for w in MANIFEST.doc["workloads"]}
    assert ACCEPTED.items() <= cells.items()
    assert 1 <= len(cells) <= 24
    four = sum(chips == 4 for chips in cells.values())
    assert four <= max(1, len(cells) // 4)
    assert len(MANIFEST.configs) <= 24


def _differs_from_default(cfg):
    default = type(cfg)()
    return {f.name for f in dataclasses.fields(cfg)
            if getattr(cfg, f.name) != getattr(default, f.name)}


def assert_no_width_is_cut(manifest, config_name):
    """True of any configuration: its file's ``config`` is the preset its
    ``preset`` names, key for key, but for what ``reduced`` lists; and
    nothing in which that preset departs from the program's defaults is
    left out, unless every traffic file the configuration runs under
    overrides it or the driver derives it from one."""
    entry = manifest.configs[config_name]
    doc = manifest.config(config_name)
    assert set(doc["reduced"]) == set(entry["reduced"])
    preset = training.preset_config(doc)
    for key, value in doc["config"].items():
        if key not in entry["reduced"]:
            got = getattr(preset, key)
            assert (list(got) if isinstance(got, tuple) else got) == value, key
    overridden = [
        set(manifest.cell(w["name"]).traffic.get("config_overrides", {}))
        for w in manifest.doc["workloads"] if w["config"] == config_name]
    assert overridden
    carried = (set(doc["config"]) | set.intersection(*overridden)
               | set(training.DERIVED_KEYS))
    assert _differs_from_default(preset) <= carried
    # the small sizes are overrides of fields the program has, and they
    # make the configuration smaller, never another model
    small = training.preset_config(doc, small=True)
    assert set(doc["small"]) <= {f.name for f in dataclasses.fields(small)}
    assert (small.torso, small.obs_shape) == (preset.torso, preset.obs_shape)
    return doc


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_its_files_by_name(name):
    cell = MANIFEST.cell(name)
    # what a cell brings as code exists as files found by its names
    for sub, module in (("drivers", cell.traffic["driver"]),
                        ("reference", cell.config_name),
                        ("model_flops", cell.config_name)):
        assert os.path.isfile(MANIFEST.module_file(sub, module))
    assert callable(find_module("model_flops", cell.config_name).step_macs)
    assert callable(find_module("reference", cell.config_name).loss)
    assert callable(find_module("drivers", cell.traffic["driver"]).run)
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for spec in cell.per_layer:
        assert callable(readers.resolve(spec))
        assert spec["moves"] in reported
    assert_no_width_is_cut(MANIFEST, cell.config_name)
    if cell.traffic["driver"] != "train":
        return
    # the program accepts the configuration the files describe
    cfg = training.build_config(cell, rehearsal=False)
    for key, value in cell.config["config"].items():
        if key not in cell.traffic.get("config_overrides", {}):
            got = getattr(cfg, key)
            assert (list(got) if isinstance(got, tuple) else got) == value
    # the window opens on a ring as full as a deployment's: pre-filled by
    # the harness (host actors) or filled by the loop's own rollouts
    assert cfg.learning_starts >= 0.9 * cfg.buffer_capacity
    assert (cell.traffic.get("prefill_ring_share") == 1.0) == bool(
        cell.traffic.get("host_envs"))
    # one cfg.seed for every run (the program compiles it in); the run's
    # own seed is folded to the 32 bits a PRNG key holds
    assert cfg.seed == training.PROGRAM_SEED
    assert 0 <= training.seed32(2 ** 31 + 5) < 2 ** 31


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file_is_the_preset_but_for_what_reduced_lists(name):
    doc = assert_no_width_is_cut(MANIFEST, name)
    # the preset is something a test can resolve: a function of the
    # program's config module and its arguments
    from r2d2_tpu import config as program_config

    assert callable(getattr(program_config, doc["preset"]["function"]))
    assert set(doc["preset"]) <= {"function", "kwargs", "note"}
    # the numbers the 8-bit control fails have limits; one that has none
    # says why it is not compared
    from benchmark import check

    compared = {k for k, _ in check.COMPARED}
    assert {"q_rel", "q_rms_rel"} <= doc["tolerance"].keys() & compared
    for key in compared - doc["tolerance"].keys():
        assert len(doc["tolerance"][key + "_not_compared"]) > 100


def _copy_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def _digests(root):
    out = {}
    for folder, _, files in os.walk(root):
        for f in files:
            path = os.path.join(folder, f)
            if "__pycache__" not in path:
                with open(path, "rb") as fh:
                    out[os.path.relpath(path, root)] = hashlib.sha256(
                        fh.read()).hexdigest()
    return out


NEW_REFERENCE = '''"""Reference of a configuration a later PR brings: the Nature torso on
space-to-depth frames, one LSTM and dueling heads at its own widths."""
from benchmark.reference import r2d2_common as common
from benchmark.reference.nature_lstm512 import torso


def loss(params, target_params, batch, n):
    return common.loss(torso, params, target_params, batch, n)
'''
NEW_FLOPS = '''from benchmark import flops


def step_macs(cfg, action_dim):
    H = cfg.hidden_dim
    core = (H + action_dim + 1 + H) * 4 * H
    return flops.torso_macs(cfg) + core + 2 * H * H + H * action_dim + H
'''
NEW_KIND = '''def read(spec, ctx):
    """Updates a second between whole dispatches, scaled."""
    if ctx.updates_per_s <= 0:
        return None
    return spec["scale"] * ctx.updates_per_s
'''


def _add_a_configuration_a_cell_and_a_reader(root):
    """What the next ``model_config`` PR does: new files and manifest
    entries, nothing else."""
    from r2d2_tpu.config import low_resource_config

    bench = root / "benchmark"
    preset = low_resource_config("Fake")
    keys = ("game_name", "obs_shape", "obs_space_to_depth", "torso",
            "hidden_dim", "compute_dtype", "param_dtype", "remat",
            "batch_size", "burn_in_steps", "learning_steps", "forward_steps",
            "block_length", "buffer_capacity", "gamma", "base_eps",
            "eps_alpha")
    config = {k: getattr(preset, k) for k in keys}
    config["obs_shape"] = list(config["obs_shape"])
    config["buffer_capacity"] = 400 * 200
    (bench / "configs" / "low_resource_256.json").write_text(json.dumps(dict(
        source="a test", config=config,
        preset=dict(function="low_resource_config", kwargs=dict(game="Fake")),
        small=dict(hidden_dim=16, batch_size=4, burn_in_steps=2,
                   learning_steps=4, forward_steps=2, block_length=8,
                   buffer_capacity=256),
        reduced=dict(buffer_capacity="a test"),
        tolerance=dict(q_rel=1e-5, q_rms_rel=1e-5, loss_rel=1e-5))))
    (bench / "reference" / "low_resource_256.py").write_text(NEW_REFERENCE)
    (bench / "model_flops" / "low_resource_256.py").write_text(NEW_FLOPS)
    (bench / "reader_kinds" / "update_rate.py").write_text(NEW_KIND)
    (bench / "layer_metrics" / "updates_per_min.json").write_text(json.dumps(
        dict(kind="update_rate", scale=60.0, unit="1/min",
             layer="learner drivetrain", moves="learner_frames_per_s",
             source="program_span", better="higher")))
    mix = json.loads((bench / "traffic" / "anakin.json").read_text())
    mix["config_overrides"].update(num_actors=128, env_workers=0,
                                   actor_fleets=1)
    (bench / "traffic" / "anakin_128.json").write_text(json.dumps(mix))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append(dict(
        name="low_resource_256", source="a test",
        reduced=["buffer_capacity"], why="a test",
        file="benchmark/configs/low_resource_256.json"))
    doc["workloads"].append(dict(
        name="low_resource_256.anakin_128", config="low_resource_256",
        traffic="anakin_128", chips=1, why="a test"))
    doc["per_layer"].append(dict(
        name="updates_per_min", unit="1/min", better="higher",
        source="program_span", layer="learner drivetrain",
        moves="learner_frames_per_s",
        workloads=["low_resource_256.anakin_128"]))
    (root / "BENCHMARK.json").write_text(json.dumps(doc))


def test_a_configuration_a_cell_and_a_reader_added_as_files_need_no_edit(
        tmp_path):
    """A configuration file with its preset and small sizes, its reference,
    its count of multiply-adds, a reader kind as a module, a metric file
    that uses it, a traffic file, and the manifest entries: found by name,
    validated, built, compared and read with no file of the copy edited."""
    from benchmark import check, flops, window

    root = _copy_benchmark(tmp_path)
    before = _digests(root / "benchmark")
    _add_a_configuration_a_cell_and_a_reader(root)
    after = _digests(root / "benchmark")
    assert {k: after[k] for k in before} == before
    assert len(after) == len(before) + 6

    m = Manifest(str(root))
    m.validate()
    cell = m.cell("low_resource_256.anakin_128")
    assert cell.bench_dir == str(root / "benchmark")
    assert_no_width_is_cut(m, "low_resource_256")
    built = training.build_config(cell, rehearsal=False)
    assert (built.hidden_dim, built.num_actors, built.batch_size) == (
        256, 128, 32)
    assert built.learning_starts == int(0.95 * 400 * 200)
    rehearsed = training.build_config(cell, rehearsal=True)
    assert (rehearsed.hidden_dim, rehearsed.block_length) == (16, 8)
    # the configuration's own count, found by its name in the copy
    macs = 819200 + 2654208 + 1806336 + 7 * 7 * 64 * 256 + (
        256 + 4 + 1 + 256) * 4 * 256 + 2 * 256 * 256 + 256 * 4 + 256
    assert flops.step_macs("low_resource_256", built, 4,
                           cell.bench_dir) == macs
    # the timed path's comparison, through the copy's reference, at the
    # file's small size in float32
    small = training.preset_config(cell.config, small=True,
                                   compute_dtype="float32")
    out = check.compare(cell.config_name, small, cell.config["tolerance"],
                        4, seed=3, bench_dir=cell.bench_dir)
    assert out["problems"] == [], out
    # ... and the metric reads through the kind that came as a file, beside
    # the kinds that were there
    assert "updates_per_min" in {s["name"] for s in cell.per_layer}
    sink = window.DispatchSink(0, 1.0, keep_spans=True)
    sink.complete("learner.step_dispatch", 10.0, 0.004)
    ctx = readers.ReadContext(
        cfg=built, config_name=cell.config_name, action_dim=4, chips=1,
        device_kind="TPU v5 lite", t_open=0.0, t_close=20.0,
        updates_per_s=2.0, span_mean_ms=sink.span_mean_ms, trace=None,
        trace_seconds=0.0, memory_peak_bytes=None, bench_dir=cell.bench_dir)
    got = readers.read_all(cell.per_layer, ctx)
    assert got["updates_per_min"] == dict(value=120.0, unit="1/min")
    assert got["dispatch_host_ms"] == dict(value=pytest.approx(4.0),
                                           unit="ms")
    assert got["train_mfu"]["value"] == pytest.approx(
        100 * 8 * macs * 32 * built.seq_len * 2.0 / 197e12)
    # a reader that finds nothing to read leaves its metric out
    assert "step_device_ms" not in got and "peak_hbm_bytes" not in got
    assert "core_device_share" not in got
    # none of it is found from the harness the copy was made of
    with pytest.raises(ManifestError, match="low_resource_256"):
        find_module("model_flops", "low_resource_256")
    with pytest.raises(ManifestError, match="update_rate"):
        readers.resolve(dict(kind="update_rate"))


def _drop(root, relative):
    os.remove(os.path.join(root, relative))


@pytest.mark.parametrize("breakage,message", [
    (lambda root: _drop(root, "benchmark/model_flops/low_resource_256.py"),
     "model_flops/low_resource_256.py"),
    (lambda root: _drop(root, "benchmark/reference/low_resource_256.py"),
     "reference/low_resource_256.py"),
    (lambda root: _drop(root, "benchmark/reader_kinds/update_rate.py"),
     "reader_kinds/update_rate.py"),
    (lambda root: (root / "benchmark" / "layer_metrics" /
                   "updates_per_min.json").write_text(json.dumps(dict(
                       kind="formula", formula="no_such_formula",
                       unit="1/min", layer="learner drivetrain",
                       moves="learner_frames_per_s", source="program_span"))),
     "formulas/no_such_formula.py"),
])
def test_validate_refuses_a_name_whose_file_is_nowhere(tmp_path, breakage,
                                                       message):
    """A configuration without a count of its multiply-adds or without a
    reference, a metric whose kind or formula is nowhere: refused before a
    run, never read through a default."""
    root = _copy_benchmark(tmp_path)
    _add_a_configuration_a_cell_and_a_reader(root)
    Manifest(str(root)).validate()
    breakage(root)
    with pytest.raises(ManifestError, match=message):
        Manifest(str(root)).validate()


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


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_shrinks_sizes_and_never_a_frame(name):
    cell = MANIFEST.cell(name)
    cfg = training.build_config(cell, rehearsal=True)
    real = training.build_config(cell, rehearsal=False)
    for key, value in cell.config["small"].items():
        if key != "buffer_capacity":
            assert getattr(cfg, key) == value
    assert cfg.num_blocks == training.REHEARSAL_BLOCKS
    assert cfg.obs_shape == real.obs_shape and cfg.torso == real.torso
    # nothing of a model's width is the driver's to cut
    assert set(training.REHEARSAL) == {"compute_dtype", "pallas_interpret"}
    if real.actor_transport == "anakin":
        assert real.anakin_episode_len == (
            cell.traffic["episode_len_blocks"] * real.block_length)
