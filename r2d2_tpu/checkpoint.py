"""Checkpoint / resume.

The reference saves ``(state_dict, num_updates, env_steps, minutes)`` every
500 updates (worker.py:380-381) and has **no resume path** — training always
restarts from scratch.  This module beats that (SURVEY.md §5.4): orbax
checkpoints of the full :class:`TrainState` (params, target params, opt
state, step counter) plus a metadata sidecar, with true bit-exact resume.

Preemption-safe on top (ISSUE 2): restore only ever selects COMPLETE
steps (the sidecar commits last, so a crash mid-save is invisible);
``save_replay``/``restore_replay`` persist the full replay plane — ring
bytes, sum-tree leaves, counters, actor snapshots — atomically
(tmp dir + rename, ``meta.json`` commits last); ``keep`` bounds disk via
retention GC that never touches in-progress saves; and a chaos hook lets
drills truncate a save mid-write to prove the skip path
(docs/OPERATIONS.md runbook).
"""
from __future__ import annotations

import json
import os
import pickle
import re
import shutil
from typing import Any, Callable, Dict, Optional, Tuple

import orbax.checkpoint as ocp

_STEP_RE = re.compile(r"^step_(\d+)$")
_REPLAY_RE = re.compile(r"^step_(\d+)\.replay$")


class Checkpointer:
    """Saves/restores TrainState pytrees under ``directory/step_N``.

    Metadata (env_steps, wall minutes — the reference's checkpoint-tuple
    extras) lives in a JSON sidecar ``step_N.meta.json`` so the evaluator
    can sweep checkpoints without touching device state.
    """

    def __init__(self, directory: str, keep: int = 0):
        """``keep`` > 0: after each successful save, garbage-collect all
        but the newest ``keep`` COMPLETE checkpoints (their replay
        snapshots with them).  In-progress saves — step dirs whose sidecar
        has not landed yet — are never collected.  0 keeps everything."""
        self.directory = os.path.abspath(directory)
        self.keep = keep
        # optional utils.chaos.ChaosInjector: lets drills/soaks simulate a
        # crash mid-save ("truncate_ckpt") — the orbax dir is truncated and
        # the sidecar never written, exercising the restore-skip path
        self.chaos = None
        os.makedirs(self.directory, exist_ok=True)
        # Explicit Checkpointer+handler composition instead of the
        # deprecated ``PyTreeCheckpointer`` shortcut.  NOT
        # ``StandardCheckpointer``: its array-metadata store is broken in
        # this image (orbax 0.11.32 — any ``StandardCheckpointer().save``
        # dies with "cannot schedule new futures after shutdown" inside
        # ``array_metadata_store.read``; the PyTree handler path does not
        # touch that store and works).
        self._ckptr = ocp.Checkpointer(ocp.PyTreeCheckpointHandler())

    def _path(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step}")

    def _meta_path(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step}.meta.json")

    def _replay_path(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step}.replay")

    def save(self, step: int, state: Any,
             meta: Optional[Dict[str, Any]] = None) -> None:
        """Multihost: call from EVERY process — orbax coordinates its own
        sync barriers and primary-host-only writes; the JSON sidecar is
        written by process 0 alone."""
        path = self._path(step)
        self._ckptr.save(path, state, force=True)
        import jax

        if jax.process_index() == 0:
            if self.chaos is not None and self.chaos.fire("truncate_ckpt"):
                # injected crash mid-save: chop the payload and skip the
                # sidecar — restore must never select this step
                truncate_checkpoint_dir(path)
                return
            # atomic: the follow-mode evaluator gates on this file's
            # existence and reads it immediately — it must never observe
            # a partially written sidecar
            meta_path = self._meta_path(step)
            tmp = f"{meta_path}.tmp{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(dict(meta or {}, step=step), f)
            os.replace(tmp, meta_path)
            self._gc()

    def steps(self, complete: bool = True) -> list:
        """Checkpointed steps, ascending.  ``complete=True`` (default)
        lists only steps whose meta sidecar exists: the sidecar commits
        last, so a crash mid-save leaves a ``step_N/`` dir with no sidecar
        that must never be selected for restore (it would fail on — or
        silently load — a torn orbax payload)."""
        out = []
        for name in os.listdir(self.directory):
            m = _STEP_RE.match(name)
            if m and os.path.isdir(os.path.join(self.directory, name)):
                step = int(m.group(1))
                if complete and not self.has_meta(step):
                    continue
                out.append(step)
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        """Newest COMPLETE step (sidecar present), or None."""
        steps = self.steps()
        return steps[-1] if steps else None

    def _gc(self) -> None:
        """Retention: drop all but the newest ``keep`` complete
        checkpoints.  Only complete steps are candidates — a dir without a
        sidecar is an in-progress save (possibly another process's) and is
        never collected."""
        if self.keep <= 0:
            return
        for step in self.steps()[:-self.keep]:
            # sidecar FIRST: once it is gone the step can no longer be
            # selected for restore, so a crash mid-GC can't leave a
            # selectable half-deleted checkpoint
            for p in (self._meta_path(step),):
                try:
                    os.remove(p)
                except FileNotFoundError:
                    pass
            shutil.rmtree(self._path(step), ignore_errors=True)
            shutil.rmtree(self._replay_path(step), ignore_errors=True)

    def has_meta(self, step: int) -> bool:
        """Whether ``step``'s metadata sidecar exists.  Process 0 writes it
        after the orbax save, so its presence marks a finished save — the
        live-follow evaluator gates on this."""
        return os.path.exists(self._meta_path(step))

    def peek_meta(self, step: Optional[int] = None) -> Dict[str, Any]:
        """Read a checkpoint's metadata sidecar without touching the state
        (for pre-restore validation)."""
        if step is None:
            step = self.latest_step()
        if step is None or not os.path.exists(self._meta_path(step)):
            return {}
        with open(self._meta_path(step)) as f:
            return json.load(f)

    def restore(self, state_template: Any, step: Optional[int] = None
                ) -> Tuple[Any, Dict[str, Any]]:
        """Restore ``step`` (default latest) shaped like ``state_template``."""
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(f"no checkpoints in {self.directory}")
        state = self._ckptr.restore(self._path(step), item=state_template)
        meta: Dict[str, Any] = {}
        if os.path.exists(self._meta_path(step)):
            with open(self._meta_path(step)) as f:
                meta = json.load(f)
        return state, meta

    # ------------------------------------------------------ replay snapshot
    def save_replay(self, step: int, writer: Callable[[str], Dict[str, Any]],
                    actors: Optional[Any] = None) -> None:
        """Write the full replay snapshot for ``step`` atomically.

        ``writer(ring_path)`` serialises the payload (ReplayBuffer
        .write_state) and returns its JSON-able meta; ``actors`` is the
        per-fleet actor snapshot list (pickled alongside — checkpoint
        artifact, not a hot-path transport).  Everything lands in a tmp
        dir with ``meta.json`` committed last INSIDE it, then one rename
        publishes the dir — a crash at any point leaves either the old
        snapshot or an ignorable ``*.tmp*`` dir, never a torn snapshot
        (restore_replay only considers dirs whose meta.json exists)."""
        final = self._replay_path(step)
        tmp = f"{final}.tmp{os.getpid()}"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        try:
            meta = dict(writer(os.path.join(tmp, "ring.bin")), step=step,
                        has_actors=actors is not None)
            if actors is not None:
                with open(os.path.join(tmp, "actors.pkl"), "wb") as f:
                    pickle.dump(actors, f)
            if self.chaos is not None and self.chaos.fire("truncate_ckpt"):
                return  # injected crash: the partial tmp dir IS the drill
            mtmp = os.path.join(tmp, "meta.json.tmp")
            with open(mtmp, "w") as f:
                json.dump(meta, f)
            os.replace(mtmp, os.path.join(tmp, "meta.json"))
            shutil.rmtree(final, ignore_errors=True)
            os.replace(tmp, final)
            # replay snapshots are ring-sized (GBs at flagship scale):
            # keep only the newest ``max(1, keep)`` — periodic cadence
            # snapshots must never accumulate unboundedly, and restore
            # always takes the latest anyway.  Ordered by COMMIT TIME,
            # not step: step counters regress across runs sharing a dir
            # (fresh run, failed replay restore), and a step-ordered
            # prune would delete the snapshot it just wrote while
            # keeping a stale high-step one
            for _, _, path in self._replay_entries()[:-max(1, self.keep)]:
                shutil.rmtree(path, ignore_errors=True)
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise

    def _replay_entries(self) -> list:
        """COMPLETE replay snapshots as ``(commit mtime, step, path)``,
        oldest first.  meta.json commits last, so its mtime is the
        snapshot's publication time."""
        out = []
        for name in os.listdir(self.directory):
            m = _REPLAY_RE.match(name)
            if not m:
                continue
            meta = os.path.join(self.directory, name, "meta.json")
            try:
                mtime = os.path.getmtime(meta)
            except OSError:  # partial snapshot: no meta.json
                continue
            out.append((mtime, int(m.group(1)),
                        os.path.join(self.directory, name)))
        return sorted(out)

    def replay_steps(self) -> list:
        """Steps with a COMPLETE replay snapshot (meta.json present),
        ascending."""
        return sorted(s for _, s, _ in self._replay_entries())

    def restore_replay(self, step: Optional[int] = None
                       ) -> Optional[Tuple[Dict[str, Any], str, Any]]:
        """Latest (or ``step``'s) complete replay snapshot as
        ``(meta, ring_path, actor_snapshots_or_None)``, or None when no
        complete snapshot exists.  "Latest" means most recently COMMITTED
        (meta.json mtime), which stays correct when step counters regress
        across runs sharing a checkpoint dir.  Partial snapshots (no
        meta.json — a crash mid-write) are never selected."""
        entries = self._replay_entries()
        if step is None:
            if not entries:
                return None
            step = entries[-1][1]
        elif step not in [s for _, s, _ in entries]:
            return None
        path = self._replay_path(step)
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        actors = None
        if meta.get("has_actors"):
            with open(os.path.join(path, "actors.pkl"), "rb") as f:
                actors = pickle.load(f)
        return meta, os.path.join(path, "ring.bin"), actors


    # ---------------------------------------------------- session snapshot
    def _sessions_path(self) -> str:
        return os.path.join(self.directory, "sessions.snap")

    def save_sessions(self, writer: Callable[[str], Dict[str, Any]]
                      ) -> Optional[Dict[str, Any]]:
        """Persist the session tier's live-episode store (serving/
        store.py) atomically — the replay-snapshot discipline at session
        scale: ``writer(payload_path)`` serialises the hidden pool +
        per-session meta and returns its JSON-able meta; everything
        lands in a tmp dir with ``meta.json`` committed last, then one
        rename publishes it.  One snapshot, latest-wins (a server
        restart only ever resumes the newest state; the chaos truncate
        drill rides the same hook as the replay snapshot)."""
        final = self._sessions_path()
        tmp = f"{final}.tmp{os.getpid()}"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        try:
            meta = dict(writer(os.path.join(tmp, "sessions.bin")))
            if self.chaos is not None and self.chaos.fire("truncate_ckpt"):
                return  # injected crash: the partial tmp dir IS the drill
            mtmp = os.path.join(tmp, "meta.json.tmp")
            with open(mtmp, "w") as f:
                json.dump(meta, f)
            os.replace(mtmp, os.path.join(tmp, "meta.json"))
            # two renames, never a window with NO committed snapshot:
            # the predecessor steps aside to ``.old`` (restore's
            # fallback), the new one lands, the fallback is collected.
            # A crash between the renames still restores the old state
            old = f"{final}.old"
            shutil.rmtree(old, ignore_errors=True)
            if os.path.isdir(final):
                os.replace(final, old)
            os.replace(tmp, final)
            shutil.rmtree(old, ignore_errors=True)
            return meta
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise

    def restore_sessions(self) -> Optional[Tuple[Dict[str, Any], str]]:
        """``(meta, payload_path)`` of the committed session snapshot,
        or None (no snapshot, or a torn one whose meta.json never
        landed — never selected).  Falls back to the ``.old`` snapshot a
        crash mid-publish may have left as the only committed state."""
        for path in (self._sessions_path(), f"{self._sessions_path()}.old"):
            meta_path = os.path.join(path, "meta.json")
            if not os.path.exists(meta_path):
                continue
            with open(meta_path) as f:
                meta = json.load(f)
            return meta, os.path.join(path, "sessions.bin")
        return None


def truncate_checkpoint_dir(path: str) -> None:
    """Simulate a crash mid-save: truncate the largest file under ``path``
    to half its size (the torn-payload shape a real preemption leaves).
    Chaos drills only — the restore path must skip such a step because its
    sidecar never landed."""
    largest, size = None, -1
    for root, _, files in os.walk(path):
        for name in files:
            p = os.path.join(root, name)
            try:
                s = os.path.getsize(p)
            except OSError:
                continue
            if s > size:
                largest, size = p, s
    if largest is not None:
        with open(largest, "r+b") as f:
            f.truncate(max(0, size // 2))


# config fields that change parameter shapes; recorded in the checkpoint
# metadata sidecar and validated before restore so a mismatch fails with an
# actionable message instead of an opaque orbax shape error
ARCH_FIELDS = ("obs_space_to_depth", "obs_shape", "torso", "hidden_dim",
               "lstm_layers", "core", "recurrent_state")


def _arch_value(cfg: Any, field: str) -> Any:
    """``recurrent_state`` is not a field but what the memory core's
    fields add up to: the shape and dtype of one state, as the model owns
    them (models/state.py) — it covers every core_* field that sizes it."""
    if field == "recurrent_state":
        from r2d2_tpu.models.state import state_spec

        shape, dtype = state_spec(cfg)
        return [list(shape), dtype.name]
    return getattr(cfg, field)


def arch_meta(cfg: Any) -> Dict[str, Any]:
    return {f: _arch_value(cfg, f) for f in ARCH_FIELDS}


def check_arch_compat(cfg: Any, meta: Dict[str, Any]) -> None:
    """Raise if the checkpoint was written under a different network
    architecture than ``cfg`` describes.  Metas from before this guard
    (no recorded fields) pass through."""
    mismatches = []
    for f in ARCH_FIELDS:
        if f in meta:
            want, have = meta[f], _arch_value(cfg, f)
            if isinstance(have, tuple):
                have = list(have)
            if want != have:
                mismatches.append(f"{f}: checkpoint={want!r} config={have!r}")
    if mismatches:
        raise ValueError(
            "checkpoint/config architecture mismatch — restore would fail "
            "or load garbage. Align the config (e.g. --set "
            "obs_space_to_depth=False) or use a fresh checkpoint dir:\n  "
            + "\n  ".join(mismatches))
