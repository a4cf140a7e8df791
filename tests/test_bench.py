"""bench.py failure reporting: the headline learner metric still lands in
the one JSON line when a later phase crashes — but ANY phase error, a
CPU-only host, or a device with no published peak makes the exit code
non-zero, on both entry paths (ISSUE 21 B3)."""
import json
import sys

import pytest

import numpy as np

V5E = dict(platform="tpu", device_kind="TPU v5 lite", device_count=1)


def test_bench_main_records_phase_crashes_and_exits_nonzero(monkeypatch,
                                                            capsys):
    from r2d2_tpu import bench

    # the real probe would spawn a subprocess against the default backend
    monkeypatch.setattr(bench, "_device_probe", lambda *a, **k: (V5E, ""))
    monkeypatch.setattr(bench, "_learner_micro_bench",
                        lambda steps, warmup, fused=False:
                        (123456.0, 42.0, 1e9))

    def boom(*a, **k):
        raise RuntimeError("injected bench fault")

    monkeypatch.setattr(bench, "_actor_plane_bench", boom)
    monkeypatch.setattr(bench, "_system_bench", boom)

    with pytest.raises(SystemExit) as ex:
        bench.main(steps=1, warmup=0, system_seconds=0.1)
    assert ex.value.code == 1
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[0])
    assert result["metric"] == "learner_env_frames_per_sec"
    assert result["value"] == 123456.0
    assert result["vs_baseline"] == round(123456.0 / bench.NORTH_STAR_FPS, 3)
    assert result["actor_env_frames_per_sec"] == -1.0
    assert result["system_env_frames_per_sec"] == -1.0
    assert result["system_vs_baseline"] == -1.0
    assert set(result["phase_errors"]) == {"actor", "system",
                                           "system_ingraph"}
    assert "injected bench fault" in result["phase_errors"]["actor"]


def test_bench_json_line_is_first_stdout_line_and_names_device(monkeypatch,
                                                               capsys):
    """The driver parses stdout for ONE JSON line; nothing may precede it,
    and it names the device the numbers came from."""
    from r2d2_tpu import bench

    monkeypatch.setattr(bench, "_device_probe", lambda *a, **k: (V5E, ""))
    monkeypatch.setattr(bench, "_learner_micro_bench",
                        lambda steps, warmup, fused=False:
                        (50000.0, 10.0, 0.0))
    monkeypatch.setattr(bench, "_actor_plane_bench", lambda: 1.0)
    monkeypatch.setattr(bench, "_system_bench",
                        lambda s, **kw: (2.0, {}, 3))
    bench.main(steps=1, warmup=0, system_seconds=0.1)   # clean: returns
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    parsed = json.loads(lines[0])
    assert parsed["vs_baseline"] == 1.0
    assert np.isclose(parsed["system_env_frames_per_sec"], 2.0)
    assert parsed["device"] == V5E
    assert "phase_errors" not in parsed


def test_bench_refuses_a_missing_accelerator(monkeypatch, capsys):
    """No accelerator must yield a parseable JSON line saying so and a
    nonzero exit — on both entry paths, with no phase run."""
    from r2d2_tpu import bench

    monkeypatch.setattr(
        bench, "_device_probe",
        lambda *a, **k: (None, "JAX found no accelerator (platform 'cpu')"))
    monkeypatch.setattr(bench, "_run_phase", None)           # never called
    monkeypatch.setattr(bench, "_learner_micro_bench", None)
    for entry in (lambda: bench.main(steps=1, warmup=0, system_seconds=0.1),
                  lambda: bench._main_isolated(1, 0, 0.1)):
        with pytest.raises(SystemExit) as ex:
            entry()
        assert ex.value.code == 1
        result = json.loads(capsys.readouterr().out.strip().splitlines()[0])
        assert result["value"] == -1.0
        assert "platform 'cpu'" in result["error"]


def test_device_probe_requires_a_known_accelerator(monkeypatch):
    """The probe's verdicts: a CPU is not a device to benchmark (JAX falls
    through to it when the accelerator fails to initialise), and a
    device_kind missing from the peak table is an error, not MFU 0."""
    import subprocess

    from r2d2_tpu import bench

    def probe_seeing(facts, rc=0):
        monkeypatch.setattr(
            subprocess, "run",
            lambda *a, **k: subprocess.CompletedProcess(
                a, rc, stdout=json.dumps(facts).encode() + b"\n",
                stderr=b"boom\n"))
        return bench._device_probe()

    assert probe_seeing(V5E) == (V5E, "")
    dev, why = probe_seeing(dict(platform="cpu", device_kind="cpu",
                                 device_count=8))
    assert dev is None and "no accelerator" in why
    dev, why = probe_seeing(dict(V5E, device_kind="TPU v9 mystery"))
    assert dev is None and "_PEAK_TFLOPS" in why
    dev, why = probe_seeing(V5E, rc=3)
    assert dev is None and "rc=3" in why and "boom" in why
    with pytest.raises(ValueError, match="TPU v9 mystery"):
        bench._peak_tflops("TPU v9 mystery")


def test_isolated_bench_composes_phase_results(monkeypatch, capsys):
    """Script-mode bench (phase-per-subprocess): a hung system phase
    surfaces as -1 + phase_errors while the already-banked micro headline
    survives in the JSON — and the exit code is non-zero."""
    from r2d2_tpu import bench

    monkeypatch.setattr(bench, "_device_probe", lambda *a, **k: (V5E, ""))

    def fake_run_phase(phase, timeout_s, extra=(), label=None):
        if phase == "micro":
            return (dict(learner_fps=100000.0, steps_per_sec=40.0,
                         flops=2e9, **V5E), "")
        if phase == "system":
            return None, "system phase hung (no result after 975s; " \
                         "child killed)"
        return dict(actor_fps=2400.0, **V5E), ""

    monkeypatch.setattr(bench, "_run_phase", fake_run_phase)
    with pytest.raises(SystemExit) as ex:
        bench._main_isolated(steps=1, warmup=0, system_seconds=0.1)
    assert ex.value.code == 1
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[0])
    assert result["value"] == 100000.0
    assert result["device"] == V5E
    assert result["system_env_frames_per_sec"] == -1.0
    assert "hung" in result["phase_errors"]["system"]
    assert result["actor_env_frames_per_sec"] == 2400.0
    # MFU from the micro child's flops + the probed device kind (v5e 197)
    assert result["mfu"] == round(2e9 * 40.0 / 1e12 / 197.0, 4)


def test_isolated_bench_all_phases_failing(monkeypatch, capsys):
    from r2d2_tpu import bench

    monkeypatch.setattr(bench, "_device_probe", lambda *a, **k: (V5E, ""))
    monkeypatch.setattr(bench, "_run_phase",
                        lambda phase, t, extra=(), label=None: (None, f"{label or phase} died"))
    with pytest.raises(SystemExit) as ex:
        bench._main_isolated(steps=1, warmup=0, system_seconds=0.1)
    assert ex.value.code == 1
    result = json.loads(capsys.readouterr().out.strip().splitlines()[0])
    assert result["value"] == -1.0
    assert set(result["phase_errors"]) == {"micro", "micro_fused",
                                           "system",
                                           "system_ingraph", "actor"}


def test_run_phase_parses_last_json_line(monkeypatch):
    """_run_phase must pick the child's JSON result even when warnings
    or log lines surround it, and report rc!=0 / no-JSON as a reason."""
    import subprocess

    from r2d2_tpu import bench

    class FakeProc:
        def __init__(self, out, rc):
            self._out, self.returncode = out, rc

        def communicate(self, timeout=None):
            return self._out.encode(), b"some warning\n"

    def fake_popen(cmd, **kw):
        assert "--phase" in cmd
        return FakeProc('log line\n{"actor_fps": 7.0}\n', 0)

    monkeypatch.setattr(subprocess, "Popen", fake_popen)
    res, err = bench._run_phase("actor", 5.0)
    assert res == {"actor_fps": 7.0} and err == ""

    monkeypatch.setattr(subprocess, "Popen",
                        lambda cmd, **kw: FakeProc("no json here\n", 0))
    res, err = bench._run_phase("actor", 5.0)
    assert res is None and "no JSON" in err

    monkeypatch.setattr(subprocess, "Popen",
                        lambda cmd, **kw: FakeProc("", 3))
    res, err = bench._run_phase("actor", 5.0)
    assert res is None and "rc=3" in err


@pytest.mark.slow
def test_actor_plane_bench_fleet_split_counts_all_lanes(monkeypatch):
    """The fleets/env_workers/act_device knobs (tools/actor_scaling.py's
    sweep surface) must keep the frames accounting exact: every lane lands
    in exactly one fleet and every fleet runs exactly ``iterations`` timed
    steps (plus the fixed warmup)."""
    import r2d2_tpu.actor as actor_mod
    from r2d2_tpu import bench

    created = []
    real = actor_mod.VectorActor

    class Recording(real):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            created.append(self)

    monkeypatch.setattr(actor_mod, "VectorActor", Recording)
    for fleets, workers in ((1, 0), (2, 2)):
        created.clear()
        fps = bench._actor_plane_bench(iterations=6, num_lanes=8,
                                       env_workers=workers, fleets=fleets,
                                       act_device="cpu")
        assert fps > 0
        assert len(created) == fleets
        assert sum(a.N for a in created) == 8  # no lane dropped
        # warmup (20) + timed window (6) lockstep iterations per fleet
        assert all(a.actor_steps == 26 for a in created)
