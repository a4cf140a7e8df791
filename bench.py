"""Repo-root benchmark shim for the driver: delegates to r2d2_tpu.bench.

Script runs use the phase-isolated path (each phase in its own bounded
subprocess holding the chip alone, so a hung phase times out instead of
hanging the driver with no artifact); importing ``main`` keeps the
in-process path.  Either way a CPU-only host or any phase error exits
non-zero.
"""
import sys

from r2d2_tpu.bench import _script_main, main, make_batch  # noqa: F401

if __name__ == "__main__":
    sys.exit(_script_main(sys.argv[1:]))
