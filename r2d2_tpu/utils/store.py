"""Versioned immutable parameter publication.

Replaces the reference's shared-memory model mutation
(``train.py:23``, ``worker.py:306-307``, pulled at ``worker.py:564-566``),
which tolerates torn reads across tensors while the learner writes.  Here
the learner publishes an immutable pytree snapshot under a lock and actors
pull by version — the torn-read race is structurally impossible
(SURVEY.md §5.2).
"""
from __future__ import annotations

import threading
from typing import Any, Optional, Tuple


class ParamStore:
    def __init__(self, params: Optional[Any] = None):
        self._lock = threading.Lock()
        self._version = 0 if params is None else 1
        self._params = params
        self._placed: dict = {}  # device -> (version, placed params)

    def publish(self, params: Any) -> int:
        """Swap in a new snapshot; returns its version (monotonic from 1)."""
        with self._lock:
            self._params = params
            self._version += 1
            # drop the previous generation's placements: entries for devices
            # whose consumers have exited would otherwise pin a full placed
            # param copy each, forever
            self._placed.clear()
            return self._version

    def get(self) -> Tuple[int, Any]:
        """Latest ``(version, params)``; params is None until first publish."""
        with self._lock:
            return self._version, self._params

    def get_placed(self, device: Any) -> Tuple[int, Any]:
        """Latest ``(version, params placed on device)``, computing the
        placement once per (version, device) and sharing it.

        Consumers that need the snapshot on a specific backend — actor
        fleets pulling learner weights to the host CPU — would otherwise
        each pay the same device→host transfer per refresh.  The transfer
        runs outside the lock so a slow interconnect never blocks
        ``publish``/``get``; concurrent same-version callers
        may race the transfer (placing twice, last one cached) rather
        than serialise on it.
        """
        import jax

        with self._lock:
            version, params = self._version, self._params
            cached = self._placed.get(device)
            if cached is not None and cached[0] == version:
                return cached
        if params is not None:
            params = jax.device_put(params, device)
        entry = (version, params)
        with self._lock:
            if self._version == version:  # don't cache a stale snapshot
                self._placed[device] = entry
        return entry
