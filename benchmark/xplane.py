"""From the profiler's trace to numbers.

``load`` turns an ``.xplane.pb`` into plain dicts (the form the test
fixture is kept in); everything else reduces that form: the busy union,
the idle share, per-operation self times, operations picked by shape, and
idle gaps attributed to the host span that covered them.

What a TPU trace looks like (TPU v5 lite, jax 0.9.0; my chip runs, PR 24):
one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` holds one
event per executed HLO operation, named by the instruction's whole text
(``%copy.219 = u8[1536,448,7056]{2,1,0:T(8,128)(4,1)} copy(...)``; a
container such as ``while`` holds its body's events nested inside it on the
same line), and whose line ``XLA Modules`` holds one event per executed
program (``jit_super_step(<fingerprint>)``).  A second of a train step is
some 350,000 operation events, so events keep a name, a start, a duration
and, on the ``XLA Ops`` line, the operation's ``path`` and nothing else.

The ``path`` is the operation's ``op_name`` — ``jit(super_step)/.../
jvp(core)/.../dot_general:`` — with the program's ``jax.named_scope`` names
in it.  The trace carries it as the stat ``tf_op`` of the event's METADATA
(probed on the chip, PR 25), which ``jax.profiler.ProfileData`` does not
show, so ``load`` reads it from the XSpace protocol buffer with the
``xplane_pb2`` that ships beside the installed profiler plugin.  A program
loaded from a compile cache written before the scopes existed carries none
(the cache key leaves metadata out).
"""
from __future__ import annotations

import glob
import importlib.util
import os
import re
from typing import Any, Collection, Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SCOPE_STAT = "tf_op"
Event = Dict[str, Any]      # name, start_ns, dur_ns; XLA Ops: path
# the program's top-level scopes (docs/OBSERVABILITY.md): the train step's,
# then the fused loop's
SCOPES = ("torso", "core", "heads", "target_forward", "ring_gather",
          "per_sample", "per_scatter", "loss", "optimizer",
          "env_step", "act", "ring_write")
NO_SCOPE = "(none)"


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str, planes: str = r"^/device:|^/host:CPU$",
         lines: Optional[str] = None) -> Dict[str, Any]:
    """The trace as plain dicts: ``{"planes": [{"name", "lines": [{"name",
    "events": [{"name", "start_ns", "dur_ns"}]}]}]}``; an event of a
    device's ``XLA Ops`` line whose operation has one also keeps its
    ``path``."""
    from jax.profiler import ProfileData

    keep_plane, keep_line = re.compile(planes), lines and re.compile(lines)
    paths = op_paths(path)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not keep_plane.search(plane.name):
            continue
        plines = []
        for line in plane.lines:
            if keep_line and not keep_line.search(line.name):
                continue
            events = [dict(name=e.name, start_ns=int(e.start_ns),
                           dur_ns=int(e.duration_ns)) for e in line.events]
            if line.name == OPS_LINE:
                _attach_paths(events, paths.get(plane.name))
            plines.append(dict(name=line.name, events=events))
        out.append(dict(name=plane.name, lines=plines))
    return dict(planes=out)


def _xplane_pb2():
    """``xplane_pb2`` of the installed tsl, loaded from its file (importing
    the package around it would start all of TensorFlow); None where it is
    not installed."""
    found = importlib.util.find_spec("tensorflow")
    if found is None or not found.submodule_search_locations:
        return None
    path = os.path.join(found.submodule_search_locations[0],
                        "tsl", "profiler", "protobuf", "xplane_pb2.py")
    if not os.path.isfile(path):
        return None
    spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def op_paths(path: str) -> Dict[str, List[Tuple[str, Optional[str]]]]:
    """By device plane, ``(name, op_name path or None)`` of every event of
    its ``XLA Ops`` line, in the line's order.  Empty where no
    ``xplane_pb2`` is installed."""
    pb2 = _xplane_pb2()
    if pb2 is None:
        return {}
    space = pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = {}
    for plane in space.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        stat_ids = {i for i, s in plane.stat_metadata.items()
                    if s.name == SCOPE_STAT}
        by_meta: Dict[int, Tuple[str, Optional[str]]] = {}
        for mid, md in plane.event_metadata.items():
            found = None
            for st in md.stats:
                if st.metadata_id in stat_ids:
                    found = (plane.stat_metadata[st.ref_value].name
                             if st.WhichOneof("value") == "ref_value"
                             else st.str_value)
            by_meta[mid] = (md.name, found)
        for line in plane.lines:
            if line.name == OPS_LINE:
                out[plane.name] = [by_meta[ev.metadata_id]
                                   for ev in line.events]
    return out


def _attach_paths(events: List[Event],
                  paths: Optional[List[Tuple[str, Optional[str]]]]) -> None:
    """``path`` onto the events of one ``XLA Ops`` line, where the two
    readings of the file show the same operations in the same order."""
    if not paths or len(paths) != len(events) or any(
            ev["name"] != name for ev, (name, _) in zip(events, paths)):
        return
    for ev, (_, found) in zip(events, paths):
        if found:
            ev["path"] = found


def device_planes(trace: Dict[str, Any]) -> List[Dict[str, Any]]:
    return sorted((p for p in trace["planes"]
                   if DEVICE_PLANE.match(p["name"])),
                  key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1)))


def line_events(plane: Dict[str, Any], line: str) -> List[Event]:
    for ln in plane["lines"]:
        if ln["name"] == line:
            return ln["events"]
    return []


def busy_intervals(events: Iterable[Event]) -> List[Tuple[int, int]]:
    """The union of the events' intervals, merged and ascending (ns)."""
    merged: List[List[int]] = []
    for s, e in sorted((ev["start_ns"], ev["start_ns"] + ev["dur_ns"])
                       for ev in events):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_seconds(events: Iterable[Event]) -> float:
    return sum(e - s for s, e in busy_intervals(events)) / 1e9


def device_extent_seconds(trace: Dict[str, Any]) -> float:
    """From the first device operation's start to the last one's end, over
    all chips: the length of the traced slice on the device's clock."""
    spans = [(ev["start_ns"], ev["start_ns"] + ev["dur_ns"])
             for p in device_planes(trace)
             for ev in line_events(p, OPS_LINE)]
    if not spans:
        return 0.0
    return (max(e for _, e in spans) - min(s for s, _ in spans)) / 1e9


def self_times(events: List[Event]) -> List[Tuple[Event, int]]:
    """Each event with its self time in ns: its duration less the events
    nested inside it, so a ``while`` does not count its body twice."""
    out: List[List[Any]] = []
    stack: List[int] = []           # indices into out, innermost last
    for ev in sorted(events, key=lambda e: (e["start_ns"], -e["dur_ns"])):
        end = ev["start_ns"] + ev["dur_ns"]
        while stack:
            top = out[stack[-1]][0]
            if ev["start_ns"] >= top["start_ns"] + top["dur_ns"]:
                stack.pop()
            else:
                break
        if stack and end <= (out[stack[-1]][0]["start_ns"]
                             + out[stack[-1]][0]["dur_ns"]):
            out[stack[-1]][1] -= ev["dur_ns"]
        out.append([ev, ev["dur_ns"]])
        stack.append(len(out) - 1)
    return [(ev, max(0, ns)) for ev, ns in out]


_HLO = re.compile(r"^%(\S+) = (.*)$", re.S)
_SHAPE = re.compile(r"\b(pred|[usf]\d+|bf16)\[([0-9,]*)\]")


def op_name(ev: Event) -> str:
    """``copy.219`` of ``%copy.219 = u8[...] copy(...)``."""
    m = _HLO.match(ev["name"])
    return m.group(1) if m else ev["name"]


def op_shape(ev: Event) -> Optional[Tuple[str, Tuple[int, ...]]]:
    """``(dtype, dims)`` of the operation's result (of a tuple, its first
    element): the first shape after the ``=`` of the instruction's text."""
    m = _HLO.match(ev["name"])
    shape = _SHAPE.search(m.group(2)) if m else None
    if shape is None:
        return None
    return shape.group(1), tuple(int(d) for d in shape.group(2).split(",")
                                 if d)


def hlo_dtype(dtype) -> str:
    """A numpy dtype under the name an instruction's text gives it:
    ``u32``, ``s8``, ``f32``, ``bf16``, ``pred``."""
    import numpy as np

    dt = np.dtype(dtype)
    if dt.name == "bfloat16":
        return "bf16"
    if dt.kind == "b":
        return "pred"
    return {"u": "u", "i": "s", "f": "f"}[dt.kind] + str(8 * dt.itemsize)


def op_label(ev: Event) -> str:
    """The operation under the name the trace gives it, with its shape:
    ``copy.219_u8_1152_448_7056_``."""
    shape = op_shape(ev)
    if shape is None:
        return op_name(ev)
    return f"{op_name(ev)}_{shape[0]}_" + "".join(f"{d}_" for d in shape[1])


def op_totals(events: List[Event]) -> Dict[str, float]:
    """Self seconds by operation label."""
    totals: Dict[str, float] = {}
    for ev, ns in self_times(events):
        label = op_label(ev)
        totals[label] = totals.get(label, 0.0) + ns / 1e9
    return totals


def shape_matches(got, want) -> bool:
    """``want`` is ``(dtype, dims)`` with None for a dimension left open."""
    return (got is not None and got[0] == want[0]
            and len(got[1]) == len(want[1])
            and all(w is None or w == g for g, w in zip(got[1], want[1])))


def selected(events: List[Event], name: str = "",
             shape: Optional[Tuple[str, Tuple[Optional[int], ...]]] = None
             ) -> List[Tuple[Event, int]]:
    """The events whose name matches ``name`` (regex) and, if given, whose
    result has ``shape``, each with its self time in ns."""
    pat = re.compile(name)
    return [(ev, ns) for ev, ns in self_times(events)
            if pat.search(op_name(ev))
            and (shape is None or shape_matches(op_shape(ev), shape))]


def select_seconds(events: List[Event], name: str = "", shape=None) -> float:
    return sum(ns for _, ns in selected(events, name, shape)) / 1e9


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


def scope_split(events: List[Event],
                scopes: Collection[str] = frozenset(SCOPES)
                ) -> Dict[str, float]:
    """Percent of the busy time by scope, largest first: every operation's
    self time goes to the outermost scope of its ``path``, a backward pass
    apart (``core.bwd``), operations under no scope (the loop's own control
    flow, copies the compiler added) to :data:`NO_SCOPE`, so the shares sum
    to 100.  ``events``: one device's ``XLA Ops`` line."""
    busy_ns = 1e9 * busy_seconds(events)
    if busy_ns <= 0:
        return {}
    by_path: Dict[Optional[str], float] = {}   # a step runs each op often
    for ev, ns in self_times(events):
        path = ev.get("path")
        by_path[path] = by_path.get(path, 0.0) + ns
    by_scope: Dict[str, float] = {}
    for path, ns in by_path.items():
        key = scope_of(path, scopes)
        by_scope[key] = by_scope.get(key, 0.0) + ns
    return {k: 100.0 * ns / busy_ns
            for k, ns in sorted(by_scope.items(), key=lambda kv: -kv[1])}


def idle_gaps(events: List[Event], spans: Dict[str, List[Tuple[float, float]]],
              offset_s: float, top: int = 10, longest: int = 200
              ) -> List[List[Any]]:
    """The ``longest`` gaps between device operations, each under the name
    of the host span that covered most of it (``offset_s`` places a host
    ``perf_counter`` time on the trace's clock: trace = host + offset).
    Gaps under one name are summed; the ``top`` names are returned as
    ``[name, seconds]``."""
    busy = busy_intervals(events)
    gaps = sorted(((s1 - e0, e0, s1) for (_, e0), (s1, _)
                   in zip(busy, busy[1:])), reverse=True)[:longest]
    flat = [(t0 + offset_s, t0 + dt + offset_s, name)
            for name, items in spans.items() for t0, dt in items]
    by_name: Dict[str, float] = {}
    for _, e0, s1 in gaps:
        g0, g1 = e0 / 1e9, s1 / 1e9
        best, cover = "no_host_span", 0.0
        for s, e, name in flat:
            c = min(e, g1) - max(s, g0)
            if c > cover:
                best, cover = name, c
        by_name[best] = by_name.get(best, 0.0) + (g1 - g0)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return [[n, s] for n, s in ranked]


def clock_offset(trace: Dict[str, Any], t_mark: float,
                 marker: str = "bench_clock_sync") -> Optional[float]:
    """Seconds to add to a host ``perf_counter`` time to land on the
    trace's clock, from the one annotation the harness wrote on both."""
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for ev in line["events"]:
                if ev["name"] == marker:
                    return ev["start_ns"] / 1e9 - t_mark
    return None
