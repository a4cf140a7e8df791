"""Tests for the auxiliary subsystems: tracing (utils/trace.py) and
failure detection / supervised threads (utils/supervisor.py)."""
import threading
import time

import pytest

from r2d2_tpu.utils.supervisor import Supervisor
from r2d2_tpu.utils.trace import Tracer, device_facts, device_profile


def test_tracer_spans_and_gauges():
    tr = Tracer()
    for _ in range(3):
        with tr.span("work"):
            time.sleep(0.002)
    tr.gauge("queue_depth", 5)
    tr.gauge("queue_depth", 3)
    snap = tr.snapshot()
    assert snap["span.work.count"] == 3
    assert snap["span.work.mean_ms"] >= 1.0
    assert snap["span.work.ewma_ms"] > 0
    assert snap["gauge.queue_depth"] == 3      # a gauge keeps the newest
    # counters went with their last call site (PR 25): counts live in
    # the telemetry registry, RETRACES and HOST_TRANSFERS
    assert not any(k.startswith("counter.") for k in snap)


def test_tracer_span_records_on_exception():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("x")
    assert tr.snapshot()["span.boom.count"] == 1


def test_tracer_thread_safety():
    tr = Tracer()

    def worker():
        for _ in range(200):
            with tr.span("s"):
                pass
            tr.gauge("last", 1)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = tr.snapshot()
    assert snap["span.s.count"] == 800
    assert snap["gauge.last"] == 1


def test_device_profile_noop_without_dir():
    with device_profile(None):
        pass  # must not touch jax at all


def test_supervisor_restarts_crashing_thread():
    crashes = []
    done = threading.Event()

    def loop():
        if len(crashes) < 2:
            crashes.append(1)
            raise RuntimeError("transient")
        done.set()

    sup = Supervisor(max_restarts=3, backoff=0.01)
    sup.start("flaky", loop)
    assert done.wait(5.0), "thread was not restarted to completion"
    assert not sup.any_failed
    h = sup.health()["flaky"]
    assert h["restarts"] == 2
    assert "transient" in h["last_error"]


def test_supervisor_gives_up_after_budget():
    def loop():
        raise RuntimeError("permanent")

    sup = Supervisor(max_restarts=2, backoff=0.01)
    sup.start("dead", loop)
    deadline = time.time() + 5.0
    while not sup.any_failed and time.time() < deadline:
        time.sleep(0.01)
    assert sup.any_failed
    h = sup.health()["dead"]
    assert h["gave_up"] and h["restarts"] == 2


def test_supervisor_join_all_cancels_pending_restart():
    """A crash during shutdown must not resurrect the loop after join_all."""
    runs = []

    def loop():
        runs.append(1)
        raise RuntimeError("crash at shutdown")

    sup = Supervisor(max_restarts=5, backoff=0.2)
    sup.start("late", loop)
    time.sleep(0.05)  # first run crashed; a 0.2s restart timer is pending
    sup.join_all(timeout=2.0)
    n = len(runs)
    time.sleep(0.5)  # well past the backoff — no restart may fire
    assert len(runs) == n
    assert not sup.threads["late"].alive


def test_config_pallas_composes_with_remat_and_rejects_spmd():
    """Since r5 the pallas impl is inference-only, so remat (a training
    -scan concern) composes freely; the retired pallas_spmd impl must
    fail with the retirement message, not pass silently."""
    from r2d2_tpu.config import test_config

    cfg = test_config(lstm_impl="pallas", remat=True)  # no longer an error
    assert cfg.remat and cfg.lstm_impl == "pallas"
    with pytest.raises(ValueError, match="retired"):
        test_config(lstm_impl="pallas_spmd")


def test_supervisor_healthy_thread_runs_clean():
    stop = threading.Event()

    def loop():
        stop.wait(5.0)

    sup = Supervisor()
    sup.start("ok", loop)
    time.sleep(0.05)
    h = sup.health()["ok"]
    assert h["alive"] and h["restarts"] == 0 and h["last_error"] is None
    stop.set()
    sup.join_all(timeout=2.0)
    assert not sup.any_failed


def test_checkpoint_arch_compat_guard(tmp_path):
    """A checkpoint written under one network architecture must refuse to
    restore under another, with an actionable message — not an opaque
    orbax shape error."""
    from r2d2_tpu.checkpoint import (
        Checkpointer, arch_meta, check_arch_compat)
    from r2d2_tpu.config import test_config as make_test_config

    cfg = make_test_config()
    ck = Checkpointer(str(tmp_path))
    ck.save(5, {"x": [1.0, 2.0]}, meta=dict(env_steps=1, **arch_meta(cfg)))

    check_arch_compat(cfg, ck.peek_meta())  # same arch: fine
    check_arch_compat(cfg, {})              # pre-guard meta: fine

    other = cfg.replace(hidden_dim=cfg.hidden_dim * 2)
    with pytest.raises(ValueError, match="hidden_dim"):
        check_arch_compat(other, ck.peek_meta())
    s2d = make_test_config(obs_shape=(84, 84, 1), torso="nature",
                           obs_space_to_depth=True)
    with pytest.raises(ValueError, match="obs_space_to_depth"):
        check_arch_compat(s2d, ck.peek_meta())


def test_compile_cache_placement(tmp_path, monkeypatch):
    """compile_cache.enable places the cache by one rule: a CPU-pinned
    process gets none; with JAX_COMPILATION_CACHE_DIR set the code sets
    no directory (JAX honours the variable itself); unset, the directory
    is the one fixed in-checkout path."""
    import os

    import jax

    from r2d2_tpu.utils import compile_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.CACHE_ROOT == os.path.join(repo, ".jax_cache")

    # jax.config mutations outlive monkeypatch: restore them explicitly,
    # on failure paths too (a leaked cache dir would turn the XLA:CPU
    # cache on for every later test)
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        # explicitly-CPU-pinned processes (this test session) get no
        # cache: XLA:CPU AOT reloads can mismatch host machine features
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable() is None
        assert jax.config.jax_compilation_cache_dir == prev_dir

        monkeypatch.setattr(compile_cache, "_configured_platform",
                            lambda: "tpu")
        # env set: reported, but nothing assigned in code
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev_dir
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0

        # env unset: the fixed in-checkout path, created and assigned
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.enable() == compile_cache.CACHE_ROOT
        assert os.path.isdir(compile_cache.CACHE_ROOT)
        assert (jax.config.jax_compilation_cache_dir
                == compile_cache.CACHE_ROOT)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_min)


# --- supervised-thread lifecycle races (ISSUE 2 satellites) ---------------

def test_supervised_thread_stop_cancels_pending_backoff_timer():
    """stop() during the backoff window must cancel the pending restart
    timer: the loop may never run again — the stop()-vs-timer race, only
    indirectly exercised via join_all before."""
    from r2d2_tpu.utils.supervisor import SupervisedThread

    runs = []

    def loop():
        runs.append(1)
        raise RuntimeError("crash")

    t = SupervisedThread("racy", loop, max_restarts=5, backoff=0.3)
    t.start()
    deadline = time.time() + 5.0
    while not runs and time.time() < deadline:
        time.sleep(0.005)
    t.join(2.0)           # first incarnation dead, 0.3s timer pending
    assert runs == [1]
    t.stop()              # must cancel the timer
    assert t._pending_timer is None
    time.sleep(0.6)       # well past the backoff
    assert runs == [1], "a cancelled backoff timer still restarted the loop"
    assert not t.alive


def test_supervised_thread_stop_beats_fired_timer():
    """The other side of the race: the timer FIRES first, then stop()
    lands before the new thread launches — start() must observe _stopping
    and refuse to resurrect the loop."""
    from r2d2_tpu.utils.supervisor import SupervisedThread

    t = SupervisedThread("racy2", lambda: None, max_restarts=5, backoff=0.1)
    t.stop()
    t.start()             # the fired timer calls start() post-stop
    assert t._thread is None and not t.alive


def test_supervised_thread_restart_counting_across_multiple_crashes():
    """Every induced crash must be counted and recorded exactly once, and
    the thread must keep recovering while budget remains."""
    from r2d2_tpu.utils.supervisor import SupervisedThread

    crashes = 3
    runs = []
    done = threading.Event()

    def loop():
        runs.append(1)
        if len(runs) <= crashes:
            raise RuntimeError(f"induced crash {len(runs)}")
        done.set()

    t = SupervisedThread("crashy", loop, max_restarts=5, backoff=0.01)
    t.start()
    assert done.wait(10.0), "thread never recovered through its crashes"
    assert t.restarts == crashes
    assert len(t.errors) == crashes
    assert [e["message"] for e in t.errors] == [
        f"induced crash {i}" for i in range(1, crashes + 1)]
    assert not t.gave_up


def test_supervisor_start_duplicate_name_raises():
    """Silently overwriting self.threads[name] would orphan the old
    SupervisedThread (and its pending backoff timer) outside supervision
    — start() must refuse instead."""
    stop = threading.Event()
    sup = Supervisor()
    sup.start("worker", lambda: stop.wait(5.0))
    try:
        with pytest.raises(ValueError, match="already supervised"):
            sup.start("worker", lambda: None)
        assert sup.threads["worker"].alive  # original untouched
    finally:
        stop.set()
        sup.join_all(timeout=2.0)


def test_device_facts_names_platform_kind_and_count():
    """What `chip_smoke.py` names its device by, on the CPU client the
    tests run on (conftest provides the virtual devices)."""
    import jax

    facts = device_facts()
    assert set(facts) == {"platform", "device_kind", "device_count"}
    assert facts["platform"] == "cpu"
    assert facts["device_kind"] == jax.devices()[0].device_kind
    assert facts["device_count"] == jax.device_count() >= 1
