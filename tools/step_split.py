#!/usr/bin/env python3
"""Share of device busy time per top-level scope of the step.

    python tools/step_split.py <profile dir | file.xplane.pb> [--json]

Reads an xplane that ``/profilez`` or ``utils/trace.device_profile()`` wrote
and gives every device operation's self time to the outermost
``jax.named_scope`` of the program that covers it (``torso``, ``core``,
``heads``, ``target_forward``, ``ring_gather``, ``per_sample``,
``per_scatter``, ``loss``, ``optimizer``; in the fused loop also
``env_step``, ``act``, ``ring_write``).  A backward pass separates itself:
an operation under ``transpose(jvp(core))`` counts as ``core.bwd``.
Operations under no scope (the loop's own control flow, copies the compiler
added) are ``(none)``, so the shares sum to 100 % of busy time.

Where the scope comes from (TPU v5 lite, jax 0.9.0; probed on the chip,
PERF.md PR 25): the ``XLA Ops`` event's METADATA carries a stat ``tf_op``
holding the operation's ``op_name`` path, ``jit(super_step)/.../jvp(core)/
.../dot_general:``.  ``jax.profiler.ProfileData`` shows an event's own stats
only, so the file is read as the XSpace protocol buffer it is, with the
``xplane_pb2`` that ships beside the installed profiler plugin.  Self times
and the busy union are ``benchmark/xplane.py``'s.

A program loaded from a compile cache written before the scopes existed
carries none (the cache key leaves metadata out): clear the cache once.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import re
import sys
from typing import Any, Collection, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import xplane  # noqa: E402

SCOPES = ("torso", "core", "heads", "target_forward", "ring_gather",
          "per_sample", "per_scatter", "loss", "optimizer",
          "env_step", "act", "ring_write")
SCOPE_STAT = "tf_op"
NO_SCOPE = "(none)"
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")


def scope_of(path: Optional[str],
             scopes: Collection[str] = frozenset(SCOPES)) -> str:
    """The outermost component of an ``op_name`` path that names a scope,
    with ``.bwd`` where a ``transpose(...)`` wraps it or a component
    outside it; :data:`NO_SCOPE` where none does."""
    if not path:
        return NO_SCOPE
    backward = False
    for part in path.split(":")[0].split("/"):
        m = _WRAPPED.match(part)
        while m:                      # jvp(x), transpose(jvp(x)), vmap(x)
            backward = backward or m.group(1) == "transpose"
            part = m.group(2)
            m = _WRAPPED.match(part)
        if part in scopes:
            return part + (".bwd" if backward else "")
    return NO_SCOPE


def split(events: List[Dict[str, Any]],
          scopes: Collection[str] = frozenset(SCOPES)) -> Dict[str, float]:
    """Percent of the busy time by scope.  ``events``: one device's
    ``XLA Ops`` line as ``benchmark.xplane`` events, each with the
    operation's ``op_name`` path under ``"path"``."""
    busy_ns = 1e9 * xplane.busy_seconds(events)
    if busy_ns <= 0:
        return {}
    by_path: Dict[Optional[str], float] = {}   # a step runs each op often
    for ev, ns in xplane.self_times(events):
        path = ev.get("path")
        by_path[path] = by_path.get(path, 0.0) + ns
    by_scope: Dict[str, float] = {}
    for path, ns in by_path.items():
        key = scope_of(path, scopes)
        by_scope[key] = by_scope.get(key, 0.0) + ns
    return {k: 100.0 * ns / busy_ns
            for k, ns in sorted(by_scope.items(), key=lambda kv: -kv[1])}


def _xplane_pb2():
    """``xplane_pb2`` of the installed tsl, loaded from its file: importing
    the package around it would start all of TensorFlow."""
    found = importlib.util.find_spec("tensorflow")
    if found is None or not found.submodule_search_locations:
        raise SystemExit("step_split: no xplane_pb2 is installed here "
                         "(it ships with the profiler plugin's tensorflow)")
    path = os.path.join(found.submodule_search_locations[0],
                        "tsl", "profiler", "protobuf", "xplane_pb2.py")
    spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_ops(path: str, device: int = 0) -> List[Dict[str, Any]]:
    """The ``XLA Ops`` events of one chip, each with its scope path."""
    space = _xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    for plane in space.planes:
        m = xplane.DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) != device:
            continue
        stat_ids = {i for i, s in plane.stat_metadata.items()
                    if s.name == SCOPE_STAT}
        paths: Dict[int, str] = {}
        for mid, md in plane.event_metadata.items():
            for st in md.stats:
                if st.metadata_id not in stat_ids:
                    continue
                if st.WhichOneof("value") == "ref_value":
                    paths[mid] = plane.stat_metadata[st.ref_value].name
                else:
                    paths[mid] = st.str_value
        for line in plane.lines:
            if line.name != xplane.OPS_LINE:
                continue
            t0 = line.timestamp_ns
            return [dict(name=plane.event_metadata[ev.metadata_id].name,
                         start_ns=t0 + ev.offset_ps // 1000,
                         dur_ns=ev.duration_ps // 1000,
                         path=paths.get(ev.metadata_id))
                    for ev in line.events]
    return []


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("profile", help="a profile directory or an .xplane.pb")
    p.add_argument("--device", type=int, default=0)
    p.add_argument("--json", action="store_true")
    args = p.parse_args(argv)
    path = (xplane.find_xplane(args.profile)
            if os.path.isdir(args.profile) else args.profile)
    if not path:
        print(f"step_split: no .xplane.pb under {args.profile}",
              file=sys.stderr)
        return 1
    events = load_ops(path, args.device)
    shares = split(events)
    if not shares:
        print("step_split: no device operation in the profile",
              file=sys.stderr)
        return 1
    busy_s = xplane.busy_seconds(events)
    if args.json:
        print(json.dumps(dict(busy_s=busy_s, events=len(events),
                              shares=shares)))
        return 0
    print(f"{len(events)} operations, busy {busy_s:.4f} s")
    for name, share in shares.items():
        print(f"{share:7.2f} %  {name}")
    print(f"{sum(shares.values()):7.2f} %  total")
    return 0


if __name__ == "__main__":
    sys.exit(main())
