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

``--inner`` splits the memory core further: an operation that lies under
one of the core's own scopes (``attention``, ``residual_mix``, ``router``,
``experts``, ``shared_expert``, ``dense_ffn``: models/xing4.py) is given to
the innermost of them, whichever pass it runs in (online, target, acting),
and the outer scopes keep what is left; the shares still sum to 100 %.

A program loaded from a compile cache written before the scopes existed
carries none (the cache key leaves metadata out): clear the cache once.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Callable, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import xplane  # noqa: E402
from benchmark.reader_kinds.scope_anywhere import components  # noqa: E402

# the reduction is the benchmark's own (benchmark/xplane.py): the scope
# metrics and this tool read the same split
SCOPES, NO_SCOPE = xplane.SCOPES, xplane.NO_SCOPE
scope_of = xplane.scope_of
_xplane_pb2 = xplane._xplane_pb2
INNER = ("attention", "residual_mix", "router", "experts", "shared_expert",
         "dense_ffn")


def inner_scope_of(path: Optional[str]) -> str:
    """The innermost component that names one of :data:`INNER`, with
    ``.bwd`` as :func:`scope_of` gives it; else :func:`scope_of`'s
    answer."""
    outer = scope_of(path)
    inner = [c for c in components(path or "") if c in INNER]
    if not inner:
        return outer
    return inner[-1] + (".bwd" if outer.endswith(".bwd") else "")


def split(events: List[Dict[str, Any]],
          scope_of: Optional[Callable[[Optional[str]], str]] = None
          ) -> Dict[str, float]:
    """Percent of the busy time by scope (``xplane.scope_split``).
    ``events``: one device's ``XLA Ops`` line, each with the operation's
    ``op_name`` path under ``"path"``.  Another ``scope_of`` files the
    operations its own way: each is handed on under the one-component path
    of the scope it names."""
    if scope_of is None:
        return xplane.scope_split(events)

    def as_path(scope: str) -> Optional[str]:
        if scope == NO_SCOPE:
            return None
        name, _, bwd = scope.partition(".")
        return f"transpose({name})" if bwd else name

    return xplane.scope_split(
        [dict(ev, path=as_path(scope_of(ev.get("path")))) for ev in events],
        frozenset(SCOPES) | frozenset(INNER))


def load_ops(path: str, device: int = 0) -> List[Dict[str, Any]]:
    """The ``XLA Ops`` events of one chip, each with its scope path (None
    where the operation carries none)."""
    trace = xplane.load(path, planes=r"^/device:", lines=f"^{xplane.OPS_LINE}$")
    for plane in xplane.device_planes(trace):
        if int(xplane.DEVICE_PLANE.match(plane["name"]).group(1)) == device:
            return [dict(ev, path=ev.get("path"))
                    for ev in xplane.line_events(plane, xplane.OPS_LINE)]
    return []


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("profile", help="a profile directory or an .xplane.pb")
    p.add_argument("--device", type=int, default=0)
    p.add_argument("--json", action="store_true")
    p.add_argument("--inner", action="store_true",
                   help="split the memory core by its own scopes")
    args = p.parse_args(argv)
    path = (xplane.find_xplane(args.profile)
            if os.path.isdir(args.profile) else args.profile)
    if not path:
        print(f"step_split: no .xplane.pb under {args.profile}",
              file=sys.stderr)
        return 1
    events = load_ops(path, args.device)
    shares = split(events, inner_scope_of if args.inner else None)
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
