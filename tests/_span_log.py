"""Test helper: a recording event sink for ``Tracer(events=...)``."""
import threading


class SpanLog:
    """An event sink for ``Tracer(events=...)`` that keeps every finished
    span as ``(t0, dt)`` under its name (utils/trace.py: anything with
    ``armed`` and ``complete``)."""

    armed = True

    def __init__(self):
        self.spans = {}
        self._lock = threading.Lock()

    def complete(self, name, t0, dt):
        with self._lock:
            self.spans.setdefault(name, []).append((t0, dt))

    def count(self, name):
        return len(self.spans.get(name, ()))

    def inside(self, child, parent):
        """Every ``child`` span lies within some ``parent`` span in time."""
        return all(any(p0 <= c0 and c0 + cd <= p0 + pd
                       for p0, pd in self.spans.get(parent, ()))
                   for c0, cd in self.spans[child])
