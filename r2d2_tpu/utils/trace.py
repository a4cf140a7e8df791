"""Tracing / profiling instrumentation (SURVEY.md §5.1).

The reference has no tracing at all — only the buffer process's 10-second
stdout stats (worker.py:89-106).  This module supplies the TPU-native hooks
the survey calls for:

- :class:`Tracer` — in-process stage timers and gauges.  Spans record
  wall-time per pipeline stage (actor inference, batch assembly, H2D
  staging, learner step, priority feedback) as exponential moving averages
  with counts AND a fixed log-bucket histogram per span (p50/p95/p99
  surfaced in ``snapshot()``, hence /statusz and the console line).  A
  ``snapshot()`` is a plain dict, cheap enough to attach to every log
  line.  Each span call site also doubles as a structured trace event
  whenever a capture window is armed (telemetry/tracing.py — the
  cross-process Perfetto timeline).
- :func:`device_profile` — a context manager around ``jax.profiler`` trace
  capture, producing a TensorBoard-loadable trace of the XLA device
  timeline for any region of the training loop.  While one is open every
  ``Tracer.span`` also opens a ``jax.profiler.TraceAnnotation`` of the
  same name, so the dump shows the host spans, per thread, on the
  device's clock; :data:`PROFILE_SYNC` says how to map the two clocks.
- :func:`maybe_span` / :func:`held` — the one "span or nothing" for call
  sites whose tracer is optional, and a ``with lock:`` whose wait is a
  span.
- :class:`SetupClock` — ``train()``'s set-up as ``setup.*`` spans of its
  tracer: host phases back to back, and JAX's own trace, lower and
  compile events, kept by ``jax.monitoring`` listeners that live only
  until the first training dispatch returns.
- :class:`RetraceGuard` — compile-boundary discipline made checkable
  (Podracer, PAPERS.md): every jitted entry point wraps its Python
  function in :data:`RETRACES`.wrap(name, fn, budget), so each XLA trace
  (the Python body runs exactly once per compilation) increments a
  per-instance counter.  A function that silently retraces per step —
  shape drift, weak-type flapping, a host value captured as a tracer —
  blows its budget, and the train/serve e2e tests assert
  ``RETRACES.assert_within_budgets()`` instead of a reviewer eyeballing
  compile logs.
- :class:`TransferCounter` — :data:`HOST_TRANSFERS` counts the
  device↔host crossings of the ingest and inference-service hot loops,
  so "the serve loop fetches once per batch, not once per lane" is an
  assertable invariant rather than a hope.
- :class:`TransferGuard` — :data:`TRANSFER_GUARD` upgrades the counted
  contract to an *enforced* one: when armed, each dispatch/fetch hot
  window runs under a scoped ``jax.transfer_guard("disallow")`` so any
  device↔host crossing that is not a declared site (an explicit
  ``device_put``/``device_get``/``copy_to_host_async``, or an implicit
  fetch inside a ``HOST_TRANSFERS.allowed(...)`` span) raises instead
  of silently stalling the loop.  Disarmed (the default) every window
  is a no-op, so production call sites are unconditional.

Everything is thread-safe and allocation-light: spans cost two
``perf_counter`` calls and a lock-free float update per use, so they can
sit in the hot loop.
"""
from __future__ import annotations

import bisect
import contextlib
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

# fixed log-spaced span-duration buckets (seconds, 4 per decade from
# 10 µs to 100 s): every span shares them, so the per-update cost is one
# bisect + one int increment and the percentile read needs no samples
_SPAN_BOUNDS = tuple(10.0 ** (e / 4.0) for e in range(-20, 9))

# True only while a device_profile() is open (it sets and clears this):
# spans then also open a profiler annotation.  Closed, a span pays one
# read of it and jax is never imported (fleet subprocesses use Tracer
# without jax).
_profile_open = False

# what device_profile() leaves beside its dump to join the two clocks:
# the annotation it writes once on the profiler's timeline, and the file
# that holds the ``perf_counter`` value read inside that annotation
PROFILE_SYNC = "tracer_clock_sync"
PROFILE_SYNC_FILE = "clock_sync.json"


def _annotate(stack: contextlib.ExitStack, name: str,
              step: Optional[int]) -> None:
    """Open the profiler's twin of a span on ``stack`` (open branch of
    :meth:`Tracer.span` only).  A span given a ``step`` is a dispatch:
    it also opens the profiler's step marker."""
    import jax

    if step is not None:
        stack.enter_context(
            jax.profiler.StepTraceAnnotation("dispatch", step_num=step))
    stack.enter_context(jax.profiler.TraceAnnotation(name))


class _Stat:
    __slots__ = ("count", "total", "ewma", "last", "buckets")

    def __init__(self) -> None:
        self.count = 0
        self.total = 0.0
        self.ewma = 0.0
        self.last = 0.0
        self.buckets = [0] * (len(_SPAN_BOUNDS) + 1)

    def update(self, dt: float, alpha: float) -> None:
        self.count += 1
        self.total += dt
        self.last = dt
        self.ewma = dt if self.count == 1 else (
            alpha * dt + (1.0 - alpha) * self.ewma)
        self.buckets[bisect.bisect_left(_SPAN_BOUNDS, dt)] += 1

    def percentile(self, q: float) -> float:
        """Approximate quantile from the fixed buckets: linear
        interpolation inside the bucket the rank lands in (the +Inf
        bucket answers its finite lower edge — conservative)."""
        rank = q * self.count
        cum = 0
        for i, c in enumerate(self.buckets):
            if c == 0:
                continue
            if cum + c >= rank:
                lo = _SPAN_BOUNDS[i - 1] if i > 0 else 0.0
                hi = (_SPAN_BOUNDS[i] if i < len(_SPAN_BOUNDS)
                      else _SPAN_BOUNDS[-1])
                frac = min(1.0, max(0.0, (rank - cum) / c))
                return lo + (hi - lo) * frac
            cum += c
        return 0.0


class Tracer:
    """Stage timers + gauges for the training fabric.

    >>> tracer = Tracer()
    >>> with tracer.span("learner_step"):
    ...     ...
    >>> tracer.gauge("batch_queue", 5)
    >>> tracer.snapshot()["span.learner_step.ewma_ms"]
    """

    def __init__(self, alpha: float = 0.05, events=None):
        self._alpha = alpha
        self._spans: Dict[str, _Stat] = {}
        self._gauges: Dict[str, float] = {}
        self._lock = threading.Lock()
        if events is None:
            # the process-wide structured event recorder
            # (telemetry/tracing.py): every span call site doubles as a
            # Chrome-trace slice whenever a capture window is armed —
            # zero extra instrumentation in the stage code
            from r2d2_tpu.telemetry.tracing import EVENTS

            events = EVENTS
        self._event_sink = events
        # the SetupClock timing this tracer's run until its first dispatch
        self._setup: Optional[SetupClock] = None

    @contextlib.contextmanager
    def span(self, name: str, step: Optional[int] = None) -> Iterator[None]:
        """Time the body under ``name``.  ``step`` marks a dispatch span
        with its number: under an open :func:`device_profile` it becomes
        the profiler's ``StepTraceAnnotation``; otherwise it is unused."""
        annotations = None
        if _profile_open:
            annotations = contextlib.ExitStack()
            _annotate(annotations, name, step)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            if annotations is not None:
                annotations.close()
            self.record(name, t0, dt)

    def record(self, name: str, t0: float, dt: float) -> None:
        """A finished span: ``dt`` seconds from ``t0`` on the
        ``perf_counter`` clock, taken into the statistics and handed to
        the event sink exactly as :meth:`span` does with the body it
        timed."""
        with self._lock:
            stat = self._spans.get(name)
            if stat is None:
                stat = self._spans[name] = _Stat()
            stat.update(dt, self._alpha)
        events = self._event_sink
        if events is not None and events.armed:
            # pass-through into the armed capture window; every call site
            # of span() and record() passes a literal name
            events.complete(name, t0, dt)  # graftlint: disable=telemetry-discipline -- pass-through bridge; span() call sites pass literal names
        setup = self._setup
        if setup is not None and name == "learner.step_dispatch":
            # every drivetrain's first training dispatch ends set-up
            setup.dispatched(t0, t0 + dt)

    def gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = float(value)

    def snapshot(self) -> Dict[str, float]:
        """Flat dict: span.<name>.{ewma_ms,mean_ms,count,p50_ms,p95_ms,
        p99_ms}, gauge.<name>.  The percentiles come
        from each span's fixed log-bucket histogram — visible per log
        interval in /statusz and the console line without a trace
        dump."""
        out: Dict[str, float] = {}
        with self._lock:
            for name, s in self._spans.items():
                out[f"span.{name}.ewma_ms"] = s.ewma * 1e3
                out[f"span.{name}.mean_ms"] = (s.total / s.count) * 1e3
                out[f"span.{name}.count"] = s.count
                out[f"span.{name}.p50_ms"] = s.percentile(0.50) * 1e3
                out[f"span.{name}.p95_ms"] = s.percentile(0.95) * 1e3
                out[f"span.{name}.p99_ms"] = s.percentile(0.99) * 1e3
            for name, v in self._gauges.items():
                out[f"gauge.{name}"] = v
        return out


_NO_SPAN = contextlib.nullcontext()


def maybe_span(tracer: Optional[Tracer], name: str,
               step: Optional[int] = None):
    """``tracer.span(name)``, or nothing where the caller was given no
    tracer — one ``if`` at a call site whose tracer is optional."""
    if tracer is None:
        return _NO_SPAN
    return tracer.span(name, step)  # graftlint: disable=telemetry-discipline -- nullable-tracer pass-through; every call site passes a literal


@contextlib.contextmanager
def held(lock: Any, tracer: Optional[Tracer], wait_name: str
         ) -> Iterator[None]:
    """``with lock:`` whose wait is a span: ``wait_name`` runs from asking
    for the lock to having it."""
    with maybe_span(tracer, wait_name):
        lock.acquire()
    try:
        yield
    finally:
        lock.release()


# JAX's own compile events (jax._src.dispatch, jax._src.compiler) as
# jax.monitoring hands them to a listener, and the set-up span that each
# kind becomes; start and end come on time.time()'s clock
_JAX_COMPILE_SPANS = (
    ("/jax/core/compile/jaxpr_trace_duration", "setup.trace"),
    ("/jax/core/compile/jaxpr_to_mlir_module_duration", "setup.lower"),
    ("/jax/core/compile/backend_compile_duration", "setup.compile"),
)
_JAX_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_BACKEND_COMPILE = _JAX_COMPILE_SPANS[2][0]


def union_seconds(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals.  A jit traced
    inside another's trace records its own event inside the outer one,
    so a plain sum of the events would count it twice."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


class SetupClock:
    """``train()``'s set-up, phase by phase, as spans of the run's
    :class:`Tracer` (docs/OBSERVABILITY.md, "Set-up").

    A phase is a host interval: ``perf_counter`` read at its two edges,
    never a wait for the device, so the device work a phase starts (the
    ring's upload, ``init_params``' programs) runs on beside the next one
    as it does without the clock.  ``setup.state`` and ``setup.ring`` are
    the bodies of :meth:`phase`; ``setup.drivetrain`` runs from the end of
    the last phase to :meth:`begin_fill` (the trainer, as it enters the
    drivetrain), ``setup.fill`` from there to the start of the run's
    first ``learner.step_dispatch`` span, ``setup.first_dispatch`` is that
    span, and ``setup.train`` runs from the clock's construction to the
    span's end.  The tracer hands the span over as it records it
    (:meth:`Tracer.record`), so every drivetrain ends set-up by the span
    it already has.

    From construction until :meth:`close`, one ``jax.monitoring``
    time-span listener and one duration listener keep JAX's own compile
    events; the first dispatch closes the clock, the trainer's ``finally``
    every other way out.  Each kind becomes one span: ``setup.trace``,
    ``setup.lower`` and ``setup.compile`` the union of their events'
    intervals, ``setup.cache_load`` the sum of the persistent cache's
    loads.  They overlap the phases by design: a second view of the same
    interval.  Every span is recorded when set-up ends, with the start
    and the length it had; :attr:`seconds` is ``metrics["setup"]``."""

    def __init__(self, tracer: Tracer):
        from jax import monitoring

        self._tracer = tracer
        self._lock = threading.Lock()
        self._events: List[Tuple[str, float, float]] = []
        self._phases: Dict[str, List[float]] = {}   # name -> [t0, seconds]
        self._fill_t0: Optional[float] = None
        self.seconds: Dict[str, float] = {}
        self.t0 = self._last_end = time.perf_counter()
        self._wall_to_perf = self.t0 - time.time()
        self._listening = True
        monitoring.register_event_time_span_listener(self._on_span)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        tracer._setup = self

    def _on_span(self, event: str, start: float, end: float,
                 **kwargs: Any) -> None:
        with self._lock:
            self._events.append((event, start, end))

    def _on_duration(self, event: str, duration: float,
                     **kwargs: Any) -> None:
        if event == _JAX_CACHE_LOAD:
            end = time.time()
            with self._lock:
                self._events.append((event, end - duration, end))

    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        """Time the body as phase ``name``.  A phase entered twice (the
        fabric builds its ``Learner`` between the ring and the buffer)
        keeps its first start and adds up the seconds."""
        t0 = time.perf_counter()
        yield
        self._last_end = time.perf_counter()
        got = self._phases.setdefault(name, [t0, 0.0])
        got[1] += self._last_end - t0

    def begin_fill(self) -> None:
        """The trainer enters its drivetrain: ``setup.drivetrain`` ends,
        ``setup.fill`` begins."""
        self._fill_t0 = time.perf_counter()
        self._phases["setup.drivetrain"] = [
            self._last_end, self._fill_t0 - self._last_end]

    def dispatched(self, start: float, end: float) -> None:
        """The first training dispatch ran from ``start`` to ``end``:
        set-up is over.  Closes the clock and records every span."""
        if not self.close():
            return
        fill_t0 = start if self._fill_t0 is None else self._fill_t0
        self._phases["setup.fill"] = [fill_t0, start - fill_t0]
        self._phases["setup.first_dispatch"] = [start, end - start]
        self._phases["setup.train"] = [self.t0, end - self.t0]
        with self._lock:
            events = list(self._events)
        spans = dict(self._phases)
        for event, name in _JAX_COMPILE_SPANS:
            got = [(s, e) for ev, s, e in events if ev == event]
            spans[name] = [self._first_start(got), union_seconds(got)]
        loads = [(s, e) for ev, s, e in events if ev == _JAX_CACHE_LOAD]
        spans["setup.cache_load"] = [self._first_start(loads),
                                     sum((e - s for s, e in loads), 0.0)]
        for name, (t0, dt) in spans.items():
            self._tracer.record(name, t0, dt)
            self.seconds[name[len("setup."):] + "_s"] = dt
        # a program loaded from the persistent cache is a backend compile
        # with a cache load inside it
        compiles = [(s, e) for ev, s, e in events if ev == _BACKEND_COMPILE]
        load_ends = sorted(e for _, e in loads)
        loaded = sum(bisect.bisect_right(load_ends, e)
                     > bisect.bisect_left(load_ends, s) for s, e in compiles)
        self.seconds.update(programs_compiled=len(compiles) - loaded,
                            programs_loaded=loaded)

    def _first_start(self, intervals) -> float:
        """The earliest start on the ``perf_counter`` clock (the clock's
        own start where there is none)."""
        if not intervals:
            return self.t0
        return min(s for s, _ in intervals) + self._wall_to_perf

    def close(self) -> bool:
        """Unregister the listeners and leave the tracer; False where the
        clock was closed already."""
        with self._lock:
            if not self._listening:
                return False
            self._listening = False
        from jax import monitoring

        self._tracer._setup = None
        monitoring.unregister_event_time_span_listener(self._on_span)
        monitoring.unregister_event_duration_listener(self._on_duration)
        return True


def maybe_phase(clock: Optional[SetupClock], name: str):
    """``clock.phase(name)``, or nothing where set-up is not timed."""
    if clock is None:
        return _NO_SPAN
    return clock.phase(name)


class RetraceBudgetExceeded(AssertionError):
    """A jitted entry point traced more often than its declared budget."""


class _RetraceEntry:
    __slots__ = ("name", "budget", "traces")

    def __init__(self, name: str, budget: int):
        self.name = name
        self.budget = budget
        self.traces = 0


class RetraceGuard:
    """Counts XLA traces per jitted-function *instance*.

    ``wrap(name, fn, budget)`` returns a wrapper to hand to ``jax.jit``;
    because jax runs the Python body once per compilation (and never on a
    cache hit), the wrapper's call count IS the trace count.  Each wrap
    call creates a fresh entry, so two learners built in one process do
    not share a counter — the budget is "traces per compiled instance",
    which for the fabric's static-shape entry points is 1 (plus slack).

    The process-wide :data:`RETRACES` instance is what production entry
    points register with; tests that deliberately provoke retraces use a
    private ``RetraceGuard()`` so they never trip the global assertion.
    """

    def __init__(self, default_budget: int = 2):
        self.default_budget = default_budget
        self._entries: List[_RetraceEntry] = []
        self._lock = threading.Lock()

    def wrap(self, name: str, fn, budget: Optional[int] = None):
        entry = _RetraceEntry(name, self.default_budget
                              if budget is None else budget)
        with self._lock:
            self._entries.append(entry)

        def traced(*args, **kwargs):
            entry.traces += 1  # int += is GIL-atomic enough for a counter
            return fn(*args, **kwargs)

        traced.__name__ = getattr(fn, "__name__", name)
        traced.__qualname__ = traced.__name__
        traced.__wrapped__ = fn
        return traced

    def counts(self) -> Dict[str, int]:
        """name → max traces observed on any single instance."""
        out: Dict[str, int] = {}
        with self._lock:
            for e in self._entries:
                out[e.name] = max(out.get(e.name, 0), e.traces)
        return out

    def over_budget(self) -> List[Tuple[str, int, int]]:
        """(name, traces, budget) for every instance past its budget."""
        with self._lock:
            return [(e.name, e.traces, e.budget)
                    for e in self._entries if e.traces > e.budget]

    def assert_within_budgets(self) -> None:
        bad = self.over_budget()
        if bad:
            raise RetraceBudgetExceeded(
                "jitted entry points exceeded their retrace budgets: "
                + "; ".join(f"{n} traced {t}x (budget {b})"
                            for n, t, b in bad))

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()


class TransferCounter:
    """Named counters for device↔host crossings on the hot loops."""

    def __init__(self):
        self._counts: Dict[str, int] = {}
        self._lock = threading.Lock()

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + n

    @contextlib.contextmanager
    def allowed(self, name: str, n: int = 1) -> Iterator[None]:
        """A declared-transfer span: tick the counter AND open a
        ``jax.transfer_guard("allow")`` window (via the process-wide
        :data:`TRANSFER_GUARD`), so the one sanctioned fetch/put inside
        a ``disallow`` window neither trips the guard nor escapes the
        budget book-keeping.  Disarmed, this is exactly ``count()``."""
        self.count(name, n)
        with TRANSFER_GUARD.allow():
            yield

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def get(self, name: str) -> int:
        with self._lock:
            return self._counts.get(name, 0)

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()


class TransferGuardTripped(RuntimeError):
    """An undeclared device↔host transfer inside a disallow window.

    Raised by :meth:`TransferGuard.disallow` wrapping jax's own guard
    error so call sites (and the OPERATIONS failure matrix) have one
    stable exception type with the window name attached."""


class TransferGuard:
    """Scoped ``jax.transfer_guard`` enforcement for the hot loops.

    The declared-transfer budget (one H2D per dispatch, one D2H per
    harvest — Podracer, PAPERS.md) has always been *counted* by
    :data:`HOST_TRANSFERS`; this makes JAX itself reject what the count
    would only reveal after the fact.  Each dispatch/fetch window wraps
    its body in ``disallow(where)``; the declared crossings inside run
    under ``HOST_TRANSFERS.allowed(name)`` (or are explicit
    ``device_put``/``device_get`` calls, which jax's ``disallow`` level
    permits by design — only *implicit* transfers trip it).

    Disarmed (the default) every window is a no-op with no jax import,
    so the guard costs one attribute read on production paths.  Tests
    and ``cfg.transfer_guard`` arm it; arming nests.  Arm AFTER the
    first compile of an entry point: trace-time constant materialization
    during compilation is outside the steady-state budget contract.

    jax's transfer guards are thread-local by design; ``arm`` flips a
    process-wide flag but each window only guards the thread that enters
    it — which is exactly the dispatch/harvest thread the budget is
    about.
    """

    def __init__(self):
        self._armed = 0
        self._windows: Dict[str, int] = {}
        self._trips: Dict[str, int] = {}
        self._lock = threading.Lock()

    @property
    def armed(self) -> bool:
        return self._armed > 0

    @contextlib.contextmanager
    def arm(self) -> Iterator[None]:
        with self._lock:
            self._armed += 1
        try:
            yield
        finally:
            with self._lock:
                self._armed -= 1

    @contextlib.contextmanager
    def disallow(self, where: str) -> Iterator[None]:
        """Enforcement window: armed, any *implicit* device↔host
        transfer inside raises :class:`TransferGuardTripped` naming the
        window.  Disarmed: free pass-through."""
        if not self.armed:
            yield
            return
        with self._lock:
            self._windows[where] = self._windows.get(where, 0) + 1
        import jax

        try:
            with jax.transfer_guard("disallow"):
                yield
        except Exception as e:  # jax raises a plain RuntimeError/ValueError
            if "transfer" not in str(e).lower():
                raise
            with self._lock:
                self._trips[where] = self._trips.get(where, 0) + 1
            raise TransferGuardTripped(
                f"undeclared device<->host transfer inside guard window "
                f"{where!r}: {e}") from e

    @contextlib.contextmanager
    def allow(self) -> Iterator[None]:
        """A sanctioned-transfer span inside a ``disallow`` window
        (normally entered via :meth:`TransferCounter.allowed`, which
        also books the crossing)."""
        if not self.armed:
            yield
            return
        import jax

        with jax.transfer_guard("allow"):
            yield

    def snapshot(self) -> Dict[str, int]:
        """``window.<name>`` = disallow windows entered while armed,
        ``trip.<name>`` = undeclared transfers caught (should be 0 —
        a non-zero trip counter is the OPERATIONS failure-matrix
        signal)."""
        with self._lock:
            out = {f"window.{k}": v for k, v in self._windows.items()}
            out.update({f"trip.{k}": v for k, v in self._trips.items()})
            return out

    def reset(self) -> None:
        with self._lock:
            self._windows.clear()
            self._trips.clear()


def put_scalar(value: int, dtype) -> Any:
    """A host integer as a device scalar in ONE transfer — a dispatch's
    declared H2D.  ``jnp.asarray(int, dtype)`` costs a program besides:
    it binds ``convert_element_type`` eagerly on the scalar, which the
    device's timeline shows as a ``jit_convert_element_type`` of its own
    for every call (PERF.md, PR 25).  Uncommitted on the default device,
    as ``jnp.asarray`` left it, so a jit places it like any host value —
    no sharding here: a put onto a multi-host sharding is a collective."""
    import jax
    import numpy as np

    return jax.device_put(np.asarray(value, dtype))


# process-wide instances: jitted entry points register with RETRACES at
# build time; the ingest / inference-service loops tick HOST_TRANSFERS
# and open TRANSFER_GUARD windows around their dispatch/fetch bodies.
# Subprocess fleets get their own (fresh) instances after spawn.
RETRACES = RetraceGuard()
HOST_TRANSFERS = TransferCounter()
TRANSFER_GUARD = TransferGuard()


@contextlib.contextmanager
def device_profile(log_dir: Optional[str]) -> Iterator[None]:
    """Capture a ``jax.profiler`` device trace into ``log_dir`` (viewable
    in TensorBoard / Perfetto).  No-op when ``log_dir`` is None, so call
    sites can be unconditional.

    While it is open every ``Tracer.span`` of the process also writes a
    profiler annotation, so the dump holds the host spans, each on its
    own thread, beside the device's operations.  One :data:`PROFILE_SYNC`
    annotation and ``<log_dir>/clock_sync.json`` (the ``perf_counter``
    and wall time read inside it) let a reader place anything else timed
    on the host's clock — the ``/tracez`` events — on the same
    timeline."""
    global _profile_open
    if not log_dir:
        yield
        return
    import json
    import os

    import jax

    # the program's spans are the host's timeline; the profiler's own
    # Python tracer would record every call of every actor thread
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    _profile_open = True
    try:
        with jax.profiler.TraceAnnotation(PROFILE_SYNC):
            mark = dict(annotation=PROFILE_SYNC,
                        perf_counter=time.perf_counter(), time=time.time())
        with open(os.path.join(log_dir, PROFILE_SYNC_FILE), "w") as f:
            json.dump(mark, f)
        yield
    finally:
        _profile_open = False
        jax.profiler.stop_trace()


def device_facts() -> dict:
    """The default backend as JAX reports it: platform, device kind and
    device count."""
    import jax

    devices = jax.devices()
    return dict(platform=devices[0].platform,
                device_kind=devices[0].device_kind,
                device_count=len(devices))


def device_memory() -> list:
    """Per local device, what the backend reports of its memory:
    ``[{id, bytes_in_use, peak_bytes_in_use, bytes_limit}, ...]`` — how a
    run shows where its ring, batch and lanes actually live (one device,
    or spread over a mesh).  Empty on a backend that keeps no memory
    stats (the CPU client)."""
    import jax

    return [dict(id=d.id, bytes_in_use=int(s["bytes_in_use"]),
                 peak_bytes_in_use=int(s["peak_bytes_in_use"]),
                 bytes_limit=int(s["bytes_limit"]))
            for d, s in ((d, d.memory_stats()) for d in jax.local_devices())
            if s]
