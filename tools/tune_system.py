"""Sweep fabric knobs for the full-system benchmark on the real chip.

Runs short ``train()`` sessions on fake envs across a small grid of the
knobs that govern the system's steady state — ``superstep_k`` (learner
dispatch granularity), ``num_actors``/``env_workers`` (experience supply),
``device_replay`` on/off — and prints a table of steady-state
env-frames/s with the busiest tracer span per cell, so the flagship
bench.py settings are chosen from measurements instead of guesses.

Each cell IS bench.py's ``_system_bench`` measurement (same config base,
same steady-state estimator) with the knobs overridden, so the sweep's
numbers are directly comparable to what bench.py reports.

Run on the TPU host:
    python tools/tune_system.py [seconds_per_cell] [--short]
        [--out OUT.json] [--slack SECONDS]

``--short`` sweeps only SHORT_GRID (the three decisive cells);
``--slack`` sets the per-cell subprocess timeout slack beyond the
measurement wall.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


GRID = [
    # (device_replay, superstep_k, num_actors, env_workers, pipeline
    #  [, in_graph_per])
    (True, 4, 64, 0, 2),    # the learning presets' cell (k=4 since the
                            # CURVES_AB_PIPELINE_r04 lag A/B)
    (True, 4, 64, 0, 2, True),   # same cell, device-resident PER
    (True, 8, 64, 0, 2),
    (True, 8, 64, 0, 2, True),
    (True, 16, 64, 0, 2),   # throughput-ceiling cells: how much system
    (True, 16, 64, 0, 2, True),  # frames/s does the k=4 learning choice
    (True, 32, 64, 0, 2),   # give up vs the raw maximum?
    (False, 1, 64, 0, 1),   # host-staged baseline
]

# the three decisive cells (--short): the learning presets' cell, the
# same cell on device PER, and the device-PER throughput ceiling —
# derived from GRID so the two can never drift
SHORT_GRID = [GRID[0], GRID[1], GRID[5]]


def main(seconds: float = 60.0, grid=None,
         out: str = "tune_system_results.json",
         cell_timeout_slack: float = 900.0) -> None:
    """Each cell runs as a bounded subprocess via the bench phase CLI (one
    process holds the chip at a time; this parent never touches JAX): a
    cell hung in an uninterruptible device call costs
    ``seconds + cell_timeout_slack``, not the sweep."""
    from r2d2_tpu.bench import _run_phase

    print(f"{'replay':>7} {'k':>3} {'actors':>6} {'workers':>7} {'pipe':>4} "
          f"{'frames/s':>12} {'updates':>8}  busiest_span")
    results = []
    for cell in (GRID if grid is None else grid):
        device_replay, k, actors, workers, pipe = cell[:5]
        in_graph = bool(cell[5]) if len(cell) > 5 else False
        knobs = dict(device_replay=device_replay, superstep_k=k,
                     num_actors=actors, env_workers=workers,
                     superstep_pipeline=pipe, in_graph_per=in_graph)
        res, err = _run_phase(
            "system", seconds + cell_timeout_slack,
            ("--seconds", seconds, "--knobs", json.dumps(knobs)))
        if res is None:  # keep sweeping; report the failure
            print(f"{'dev' if device_replay else 'host':>7} {k:>3} "
                  f"{actors:>6} {workers:>7} {pipe:>4} {'FAILED':>12} "
                  f"{err}")
            continue
        fps, top_spans, updates = (res["system_fps"], res["top_spans"],
                                   res["updates"])
        top = next(iter(top_spans), "-")
        results.append(dict(platform=res["platform"],
                            device_kind=res["device_kind"],
                            device_replay=device_replay, superstep_k=k,
                            num_actors=actors, env_workers=workers,
                            superstep_pipeline=pipe, in_graph_per=in_graph,
                            frames_per_sec=round(fps, 1), updates=updates,
                            busiest=top))
        tag = "dev+ig" if in_graph else ("dev" if device_replay else "host")
        print(f"{tag:>7} {k:>3} {actors:>6} "
              f"{workers:>7} {pipe:>4} {fps:>12,.0f} {updates:>8}  {top}")
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"→ {out}")


if __name__ == "__main__":
    _argv = sys.argv[1:]
    _kw = {}
    if "--short" in _argv:
        _argv.remove("--short")
        _kw["grid"] = SHORT_GRID
    for _flag, _key, _cast in (("--out", "out", str),
                               ("--slack", "cell_timeout_slack", float)):
        if _flag in _argv:
            _i = _argv.index(_flag)
            if _i + 1 >= len(_argv) or _argv[_i + 1].startswith("--"):
                sys.exit(f"usage: tune_system.py [seconds] [--short] "
                         f"[--out OUT.json] [--slack SECONDS] "
                         f"({_flag} needs a value)")
            _kw[_key] = _cast(_argv[_i + 1])
            _argv = _argv[:_i] + _argv[_i + 2:]
    main(float(_argv[0]) if _argv else 60.0, **_kw)
