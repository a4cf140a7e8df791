"""Test harness: force an 8-device virtual CPU mesh.

This is the JAX-native way to test multi-chip sharding without hardware
(SURVEY.md §4): all tests run on CPU with 8 fake devices so pjit/Mesh code
paths execute real collectives.

- ``XLA_FLAGS`` must be in the environment before the CPU backend
  initialises (it is read at backend-init time, which happens lazily at the
  first jax op inside a test).
- ``JAX_PLATFORMS=cpu`` pins the tests — and every subprocess they spawn —
  to the CPU; the ``jax.config`` update covers an interpreter that imported
  jax before this file ran.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"  # for any subprocesses tests may spawn

import jax

jax.config.update("jax_platforms", "cpu")

# --- per-test timeout fallback ------------------------------------------
# pyproject.toml sets `timeout = 300` for pytest-timeout; when the plugin
# is not installed (this image cannot pip install), emulate its "thread"
# method with faulthandler: a test exceeding the budget dumps EVERY
# thread's stack and kills the run — a queue-wedge bug fails fast with a
# diagnosis instead of silently eating the CI wall clock.
try:
    import pytest_timeout  # noqa: F401

    _HAVE_TIMEOUT_PLUGIN = True
except ImportError:
    _HAVE_TIMEOUT_PLUGIN = False

if not _HAVE_TIMEOUT_PLUGIN:
    import faulthandler
    import sys
    import threading

    import pytest

    def pytest_addoption(parser):
        parser.addini("timeout",
                      "fallback per-test timeout in seconds (0 disables); "
                      "normally owned by pytest-timeout", default="0")

    @pytest.fixture(autouse=True)
    def _fallback_test_timeout(request):
        try:
            budget = float(request.config.getini("timeout") or 0)
        except (TypeError, ValueError):
            budget = 0.0
        marker = request.node.get_closest_marker("timeout")
        if marker and marker.args:
            budget = float(marker.args[0])
        if budget <= 0:
            yield
            return

        def on_timeout():
            # suspend capture first (pytest-timeout's thread method does
            # the same) or the dump lands in a discarded capture tempfile
            capman = request.config.pluginmanager.getplugin(
                "capturemanager")
            if capman is not None:
                try:
                    capman.suspend_global_capture(in_=True)
                except Exception:
                    pass
            sys.stderr.write(
                f"\n+++ timeout: {request.node.nodeid} exceeded "
                f"{budget:.0f}s — dumping all thread stacks +++\n")
            faulthandler.dump_traceback(file=sys.stderr)
            sys.stderr.flush()
            os._exit(1)

        timer = threading.Timer(budget, on_timeout)
        timer.daemon = True
        timer.start()
        try:
            yield
        finally:
            timer.cancel()
