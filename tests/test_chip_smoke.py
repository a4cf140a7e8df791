"""chip_smoke.py (ISSUE 21): the on-chip smoke's CPU rehearsal.

The real run needs the chip; what tier-1 can hold is the script itself —
the fabric leg end to end through ``python -m r2d2_tpu train`` in the
explicitly named rehearsal mode, the JSON document's schema, and the
contract's refusal: the default mode on a CPU exits non-zero, names the
platform it found and prints no result.
"""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(tmp_path, *args):
    proc = subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path), *args],
        capture_output=True, text=True, timeout=280)
    with open(tmp_path / "chip_smoke.json") as f:
        return proc, json.load(f)


def test_rehearsal_fabric_leg_and_document_schema(tmp_path):
    proc, doc = _run(tmp_path, "--rehearsal", "--legs", "fabric")
    # a rehearsal never prints the success line — stdout stays empty
    assert proc.stdout == ""
    assert doc["rehearsal"] is True and doc["claim"] is None
    assert list(doc)[-1] == "claim"
    assert doc["device"]["platform"] == "cpu"
    assert {"device_kind", "device_count", "jax", "jaxlib", "libtpu",
            "bytes_limit", "cache_dir"} <= set(doc["device"])
    assert {"dir", "from_env", "entries"} == set(doc["cache"])

    fabric = doc["legs"]["fabric"]
    assert fabric["ok"], fabric["failures"]
    assert {"rc", "wall_s", "first_dispatch_s", "xla_compile_s",
            "cache_entries_added", "peak_bytes_in_use", "result",
            "host_sum_tree", "failures", "ok"} <= set(fabric)
    m = fabric["result"]
    # the leg ran the drivetrain it names, and says where acting ran
    assert m["drivetrain"] == "device_ring_in_graph_per"
    assert m["act_platform"] == "cpu"
    assert m["buffer_training_steps"] == m["num_updates"] >= 8
    assert fabric["first_dispatch_s"] > 0   # JAX's own compile log parsed
    assert fabric["host_sum_tree"] in ("native", "numpy")

    # a partial run is honest about it: the other legs are reported as
    # skipped, and skipped legs cannot pass
    skipped = {k for k, leg in doc["legs"].items() if "skipped" in leg}
    assert {"anakin", "serve", "kernel"} <= skipped
    assert doc["ok"] is False and proc.returncode == 1


def test_default_mode_refuses_a_cpu(tmp_path):
    """JAX_PLATFORMS=cpu (conftest's pin for subprocesses) and no
    --rehearsal: exit non-zero, name the platform found, print no
    result, run no leg."""
    proc, doc = _run(tmp_path)
    assert proc.returncode == 1
    assert proc.stdout == ""
    assert "JAX found platform 'cpu', not 'tpu'" in proc.stderr
    assert doc["ok"] is False and doc["rehearsal"] is False
    assert all("skipped" in leg for name, leg in doc["legs"].items()
               if name != "device")
