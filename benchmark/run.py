#!/usr/bin/env python3
"""Run one cell of the benchmark once and print one JSON line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (from the program's spans over the whole window and a
profiler slice of it) with ``breakdown``.  Without an accelerator, or with
fewer chips than the cell asks for, nothing is printed and the exit code is
1 — except under ``--rehearsal``, which runs the same control flow on the
CPU at tiny sizes, marks its line ``"rehearsal": true`` and prints no
metric under a device metric's name.
"""
import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None,
                   help="default: the manifest's run_seconds")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearsal", action="store_true",
                   help="CPU, tiny sizes, virtual devices: finds wrong "
                        "paths, never a speed")
    return p.parse_args(argv)


def device_facts() -> dict:
    import jax

    d = jax.devices()
    return dict(platform=d[0].platform, kind=d[0].device_kind, count=len(d))


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, breakdown=None, extra=None) -> str:
    """The one JSON object a run prints last."""
    doc = dict(correct=bool(correct), attempted=int(attempted),
               failed=int(failed), metrics=metrics, device=device)
    if breakdown is not None:
        doc["breakdown"] = breakdown
    doc.update(extra or {})
    return json.dumps(doc)


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark.manifest import Manifest

    manifest = Manifest(ROOT)
    cell = manifest.cell(args.workload)
    if args.seconds is None:
        args.seconds = float(manifest.run_seconds)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}")
    # the program's own cache rule: JAX_COMPILATION_CACHE_DIR if set, else
    # the fixed <checkout>/.jax_cache
    from r2d2_tpu.utils.compile_cache import enable

    enable()
    device = device_facts()
    if not args.rehearsal and device["platform"] != "tpu":
        print(f"benchmark: JAX found platform {device['platform']!r}, not "
              "a TPU; a CPU is only run under --rehearsal", file=sys.stderr)
        return 1
    if device["count"] < cell.chips:
        print(f"benchmark: {cell.name} needs {cell.chips} chips, JAX found "
              f"{device['count']}", file=sys.stderr)
        return 1
    if not args.rehearsal:
        from benchmark import flops

        flops.peaks(device["kind"])     # an unknown device is an error

    # the traffic file names the driver; everything about the run but the
    # printing is the driver's
    from benchmark.manifest import find_module

    driver = find_module("drivers", cell.traffic["driver"], cell.bench_dir)
    out = driver.run(cell, args, T_START, device)
    metrics, problems = out["metrics"], out["extra"].get("problems", [])
    extra = dict(out["extra"], workload=cell.name, seed=args.seed,
                 seconds=args.seconds)
    if args.rehearsal:
        # a CPU's numbers are not device numbers: names only
        extra.update(rehearsal=True, rehearsal_metrics=sorted(metrics))
        metrics = {}
    for p in problems:
        print(f"benchmark: {p}", file=sys.stderr)
    # each number compared beside its limit: the last lines on standard
    # error and the last key of the line
    compared = extra.pop("compared", None)
    if compared is not None:
        for name, c in compared.items():
            print(f"benchmark: compared {name} = {c['value']:.6g} "
                  f"(limit {c['limit']})", file=sys.stderr)
        extra["compared"] = compared
    sys.stderr.flush()
    sys.stdout.flush()
    print(result_line(out["correct"], out["attempted"], out["failed"],
                      metrics, device, out.get("breakdown"), extra),
          flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the program (actor pools, a quiesced fabric) must
    # not hold the exit
    os._exit(code)
