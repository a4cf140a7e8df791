"""The measured window: rates taken between completion times of whole
dispatches, never a count divided by a nominal length.

The program's own ``Tracer`` hands every finished span to an event sink
(``utils/trace.py``: anything with ``armed`` and ``complete(name, t0, dt)``).
:class:`DispatchSink` is that sink.  Each ``learner.result_sync`` span closes
one dispatch of ``superstep_k`` updates; its end time is when those updates
are known to have completed.  The window opens at the first such end after
the warm-up dispatches and closes at the last one inside ``seconds``.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

SYNC_SPAN = "learner.result_sync"


def window_indices(ends: List[float], warmup: int,
                   seconds: float) -> Optional[Tuple[int, int]]:
    """``(i_open, i_close)`` into ``ends`` (completion times, ascending):
    open at the first completion after ``warmup`` of them, close at the
    last completion no later than ``seconds`` after the opening.  None
    when fewer than two completions fall inside."""
    if len(ends) <= warmup:
        return None
    i_open = warmup
    limit = ends[i_open] + seconds
    i_close = i_open
    while i_close + 1 < len(ends) and ends[i_close + 1] <= limit:
        i_close += 1
    return (i_open, i_close) if i_close > i_open else None


def rate(ends: List[float], i_open: int, i_close: int,
         units_per_event: float) -> float:
    """Units per second between two completions: the events after the
    opening one, over the exact time between the two."""
    return ((i_close - i_open) * units_per_event
            / (ends[i_close] - ends[i_open]))


def counter_rate(ends: List[float], counts: List[float], i_open: int,
                 i_close: int) -> float:
    """Rate of a counter sampled at each completion."""
    return ((counts[i_close] - counts[i_open])
            / (ends[i_close] - ends[i_open]))


class DispatchSink:
    """Event sink for ``Tracer(events=...)`` and the run's stop predicate.

    ``counter`` (optional) is sampled at every dispatch completion, on the
    thread that completed it — how the fabric cell counts env frames
    between the same two instants as the updates.  ``keep_spans`` keeps
    every span's ``(t0, dt)`` for the per-layer readers and the idle-gap
    attribution of a traced run.
    """

    armed = True

    def __init__(self, warmup: int, seconds: float,
                 counter: Optional[Callable[[], float]] = None,
                 keep_spans: bool = False,
                 clock: Callable[[], float] = time.perf_counter):
        self.warmup, self.seconds = warmup, seconds
        self._counter, self._clock = counter, clock
        self._lock = threading.Lock()
        self.ends: List[float] = []
        self.counts: List[float] = []
        self.spans: Optional[Dict[str, List[Tuple[float, float]]]] = (
            {} if keep_spans else None)
        self.t_open: Optional[float] = None

    def complete(self, name: str, t0: float, dt: float) -> None:
        with self._lock:
            if self.spans is not None:
                self.spans.setdefault(name, []).append((t0, dt))
            if name != SYNC_SPAN:
                return
            self.ends.append(t0 + dt)
            if self._counter is not None:
                self.counts.append(self._counter())
            if self.t_open is None and len(self.ends) > self.warmup:
                self.t_open = self.ends[self.warmup]

    def stop(self) -> bool:
        """True once the window has been open for ``seconds``."""
        t = self.t_open
        return t is not None and self._clock() >= t + self.seconds

    def window(self) -> Optional[Tuple[int, int]]:
        with self._lock:
            return window_indices(self.ends, self.warmup, self.seconds)

    def span_mean_ms(self, name: str, t_lo: float, t_hi: float,
                     divisor: float = 1.0) -> Optional[float]:
        """Mean duration of the spans that ended inside ``[t_lo, t_hi]``."""
        with self._lock:
            durs = [dt for t0, dt in (self.spans or {}).get(name, ())
                    if t_lo <= t0 + dt <= t_hi]
        if not durs:
            return None
        return 1e3 * sum(durs) / len(durs) / divisor
