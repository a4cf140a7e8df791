"""One cell through ``benchmark/run.py`` end to end, as the driver calls it,
in rehearsal mode (CPU, tiny sizes): the control flow, the traced path, and
the one line it prints."""
import json
import os
import subprocess
import sys

from benchmark.manifest import ROOT

CONTRACT = {"correct", "attempted", "failed", "metrics", "device"}


def test_traced_rehearsal_prints_one_line_and_no_device_number():
    env = dict(os.environ, BENCH_RUN="ignored")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "nature_lstm512.fabric", "--seed", str(2 ** 31 + 12345),
         "--seconds", "3", "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=280)
    assert proc.returncode == 0, proc.stderr[-3000:]
    doc = json.loads(proc.stdout.strip().splitlines()[-1])
    assert CONTRACT <= set(doc)
    assert doc["rehearsal"] is True and doc["correct"] is True, doc
    assert doc["device"]["platform"] == "cpu" and doc["device"]["count"] >= 1
    # nothing measured on a CPU is printed under a device metric's name
    assert doc["metrics"] == {}
    # (the acting metrics need actor steps inside the window, which 3 s on
    # a busy CPU do not always hold)
    assert {"dispatch_host_ms", "result_sync_host_ms"} <= set(
        doc["rehearsal_metrics"])
    assert doc["attempted"] >= 1 and doc["failed"] == 0
    assert doc["reference"]["q_rel"] < 1e-4
    # train() called every name the harness wraps (else: not correct), and
    # the wrapped buffer came pre-filled through the program's own writer
    assert doc["problems"] == []
    assert doc["ring_fill"]["open"] >= 0.9
    assert "ring_fill_share" in doc["rehearsal_metrics"]
