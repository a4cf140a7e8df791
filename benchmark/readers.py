"""Per-layer metrics: one small data file each (``layer_metrics/<name>.json``)
naming a ``kind`` of reader and its parameters.  A kind is one of those
below or, found by its name, a module ``reader_kinds/<kind>.py`` exporting
``read(spec, ctx)``; a ``formula`` likewise one of those below or
``formulas/<formula>.py``.  A reader that finds nothing to read returns
None, and the harness leaves the metric out."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

from benchmark import flops, xplane
from benchmark.manifest import BENCH_DIR, find_module


@dataclasses.dataclass
class ReadContext:
    """What a traced run offers the readers."""
    cfg: Any
    config_name: str                    # whose model_flops/<name>.py counts
    action_dim: int
    chips: int
    device_kind: str
    t_open: float                       # window, perf_counter
    t_close: float
    updates_per_s: float
    span_mean_ms: Callable[..., Optional[float]]
    trace: Optional[Dict[str, Any]]     # xplane.load(...) of the slice
    trace_seconds: float                # length of the traced slice
    memory_peak_bytes: Optional[int]
    # the program's frame ring on one chip as the trace names an array:
    # ("u32", (blocks, rows, words a frame)); None leaves a dimension open
    ring_obs: Optional[Tuple[str, Tuple[Optional[int], ...]]] = None
    ring_fill_open: Optional[float] = None  # share of the ring, window open
    bench_dir: str = BENCH_DIR          # where kinds and counts are found
    # what one reader worked out for the next (a reduction of the whole
    # slice that several metrics share)
    cache: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def device_line(self, line: str, device: int = 0) -> List[xplane.Event]:
        planes = xplane.device_planes(self.trace) if self.trace else []
        if device >= len(planes):
            return []
        return xplane.line_events(planes[device], line)

    def device_ops(self) -> List[xplane.Event]:
        return self.device_line(xplane.OPS_LINE)

    def busy_seconds(self) -> Optional[float]:
        """Seconds an operation ran on the device, averaged over chips."""
        planes = xplane.device_planes(self.trace) if self.trace else []
        busy = [xplane.busy_seconds(
            xplane.line_events(p, xplane.OPS_LINE)) for p in planes]
        busy = [b for b in busy if b > 0]
        return sum(busy) / len(busy) if busy else None


def read_span(spec, ctx: ReadContext) -> Optional[float]:
    """Mean host milliseconds of a program span inside the window."""
    return ctx.span_mean_ms(spec["span"], ctx.t_open, ctx.t_close,
                            spec.get("divisor", 1.0))


def read_xplane_idle(spec, ctx: ReadContext) -> Optional[float]:
    busy = ctx.busy_seconds()
    if busy is None or ctx.trace_seconds <= 0:
        return None
    return 100.0 * (1.0 - busy / ctx.trace_seconds)


def read_xplane_ops(spec, ctx: ReadContext) -> Optional[float]:
    """Device time of selected operations or programs of chip 0.

    ``select``: ``"ops"`` (line XLA Ops) or ``"modules"`` (XLA Modules);
    ``match``: regex on the event name; ``shape``: ``"ring_obs"`` keeps
    only operations whose result is the whole per-chip frame ring;
    ``reduce``: ``"share_of_busy"`` (% of the chip's busy time) or
    ``"ms_per_update"`` (device time of the matching events over the
    updates they ran, ``superstep_k`` to an event)."""
    events = ctx.device_line(xplane.MODULES_LINE if spec["select"] == "modules"
                             else xplane.OPS_LINE)
    shape = None
    if spec.get("shape") == "ring_obs":
        if ctx.ring_obs is None:
            return None
        shape = ctx.ring_obs
    picked = xplane.selected(events, spec["match"], shape)
    if spec["reduce"] == "ms_per_update":
        if len(picked) > 2:
            # the slice's edges cut the first and the last event short
            picked = sorted(picked, key=lambda p: p[0]["start_ns"])[1:-1]
        if not picked:
            return None
        return (sum(ns for _, ns in picked) / 1e6
                / (len(picked) * ctx.cfg.superstep_k))
    seconds = sum(ns for _, ns in picked) / 1e9
    busy = xplane.busy_seconds(ctx.device_ops())
    return 100.0 * seconds / busy if busy > 0 else None


def read_memory_stats(spec, ctx: ReadContext) -> Optional[float]:
    return ctx.memory_peak_bytes


def read_ring_fill(spec, ctx: ReadContext) -> Optional[float]:
    """How full the ring was when the window opened, in percent."""
    if ctx.ring_fill_open is None:
        return None
    return 100.0 * ctx.ring_fill_open


def formula_train_mfu(spec, ctx: ReadContext) -> Optional[float]:
    if ctx.device_kind == "cpu":        # a rehearsal: a CPU has no MFU
        return None
    return flops.train_mfu_percent(ctx.config_name, ctx.cfg, ctx.action_dim,
                                   ctx.updates_per_s, ctx.chips,
                                   ctx.device_kind, ctx.bench_dir)


FORMULAS = dict(train_mfu=formula_train_mfu)


KINDS = dict(span=read_span, xplane_idle=read_xplane_idle,
             xplane_ops=read_xplane_ops, memory_stats=read_memory_stats,
             ring_fill=read_ring_fill)   # and "formula": see resolve()


def resolve(spec: Dict[str, Any], bench_dir: str = BENCH_DIR
            ) -> Callable[[Dict[str, Any], ReadContext], Optional[float]]:
    """The function that reads ``spec``: its ``kind`` among those here and
    otherwise ``reader_kinds/<kind>.py``; for the kind ``formula`` its
    ``formula`` among those here and otherwise ``formulas/<formula>.py``.
    One that is nowhere is a :class:`~benchmark.manifest.ManifestError`."""
    kind = spec.get("kind", "")
    if kind == "formula":
        name = spec.get("formula", "")
        return (FORMULAS.get(name)
                or find_module("formulas", name, bench_dir).read)
    return KINDS.get(kind) or find_module("reader_kinds", kind,
                                          bench_dir).read


def read_all(specs: List[Dict[str, Any]], ctx: ReadContext
             ) -> Dict[str, Dict[str, Any]]:
    out = {}
    for spec in specs:
        value = resolve(spec, ctx.bench_dir)(spec, ctx)
        if value is not None:
            out[spec["name"]] = dict(value=float(value), unit=spec["unit"])
    return out
