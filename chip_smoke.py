#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the system's main paths once, through the entry points a user would
call (``python -m r2d2_tpu train|serve``), at the full width of the flagship
model: Nature torso with space-to-depth, LSTM-512 x 1, bf16 compute / f32
params, 84x84 frames, B=64, T=40+40+5=85 (the ``pong`` preset on the fake
env).  Depth of the run is cut (a few dozen updates, a small ring); widths
are not.  Weights are random, from the config's seed.

Legs, each ONE child process that holds the chip alone, strictly in turn
(this parent never initialises a JAX backend — a parent that has touched JAX
holds the chip and its children would fail or hang):

  device   what JAX found.  Anything but a TPU ends the smoke non-zero.
  fabric   host-actor trainer on the device ring with in-graph PER.
  anakin   the fused on-device loop (env + actor + replay + learner).
  serve    the session tier over the fabric leg's checkpoint; this parent is
           the client (sockets and numpy only).
  kernel   ops/lstm.py compiled by Mosaic (interpret=False) vs the scan
           recurrence at B in {1, 64, 256}, bf16 tolerance; and the device
           ring's in-graph gather vs numpy, exact.
  fabric_mesh / anakin_mesh   added when >= 4 devices are found: the same
           two trainers with --mesh and a dp-sharded ring; per-device memory
           must show the spread.

Each leg passes only if it ran the path it names (the drivetrain, the acting
platform, the resolved LSTM implementation are read back from the child and
compared).  The full JSON document goes to ``<out>/chip_smoke.json`` and to
stdout; on success the LAST stdout line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and the exit code is 0.  On any failure nothing is printed to stdout (the
document goes to stderr) and the exit code is 1.

``--rehearsal`` is the explicitly named CPU mode at ``--preset test`` sizes
(Pallas interpreted): it debugs this script in a sandbox with no chip, its
JSON says ``"rehearsal": true``, and it never prints the success line.
"""
from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
BUDGET_S = 1100.0          # the driver allows 1200 s, compilation included
LEGS = ("device", "fabric", "anakin", "serve", "kernel")
MESH_LEGS = ("fabric_mesh", "anakin_mesh")

_children: list = []       # live Popen objects, for the exit sweep


# --------------------------------------------------------------------------
# child bodies (run with --child NAME; these DO touch JAX)
# --------------------------------------------------------------------------

def _child_device() -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    from r2d2_tpu.utils.compile_cache import enable
    from r2d2_tpu.utils.trace import device_facts, device_memory

    cache_dir = enable()
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    mem = device_memory()
    return dict(device_facts(),
                bytes_limit=mem[0]["bytes_limit"] if mem else None,
                jax=jax.__version__, jaxlib=jaxlib.__version__,
                libtpu=libtpu, flax=md.version("flax"),
                jax_platforms=jax.config.jax_platforms,
                cache_dir=cache_dir)


def _ring_gather_check(cfg, A: int) -> dict:
    """The device ring's in-graph gather against numpy indexing of the same
    slots, inside a k-step scan like the super-step's: random full-length
    blocks, staged as ``DeviceRing.stage`` stages them, in the first, a
    middle and the LAST slot of the smoke's ring, windows reaching as far
    past the blocks' last rows as the sampler's windows can.  Pure data
    movement, so the comparison is exact.  (Bring-up found a ring layout
    whose gather read out of bounds only for late rows — finite losses
    alone cannot see a gather that reads the wrong bytes.)"""
    import jax
    import numpy as np

    from r2d2_tpu.replay.device_ring import TIME_KEYS, DeviceRing, gather_batch

    ring = DeviceRing(cfg, A)
    rng = np.random.default_rng(1)
    NB, MS, BL = cfg.num_blocks, cfg.max_block_steps, cfg.block_length
    K, L, T = cfg.seqs_per_block, cfg.learning_steps, cfg.seq_len
    slots = sorted({0, NB // 2, NB - 1})
    host = {}
    for ptr in slots:
        blk = {}
        for name, (shape, dt) in ring._slot_shapes.items():
            blk[name] = (rng.integers(0, 2, shape).astype(bool)
                         if dt == np.bool_ else
                         rng.integers(0, 256, shape).astype(dt)
                         if dt == np.uint8 else
                         rng.normal(size=shape).astype(dt))
            if name in TIME_KEYS:       # spare rows: copies of the last row
                blk[name][MS:] = blk[name][MS - 1]
        host[ptr] = blk
        ring.commit({n: jax.device_put(v) for n, v in blk.items()}, ptr)
    k, B = 2, 64
    ints = np.zeros((k, B, 6), np.int32)
    ints[..., 0] = rng.choice(slots, (k, B))
    ints[..., 1] = rng.integers(0, BL - L + 1, (k, B))  # t0, late rows too
    ints[:, 0, 1] = BL - L                  # the latest window a sampler gives
    ints[..., 2] = rng.integers(0, K, (k, B))
    w = rng.random((k, B)).astype(np.float32)

    @jax.jit
    def gather_k(arrays, ints, w):
        return jax.lax.scan(
            lambda c, x: (c, gather_batch(cfg, arrays, *x)), 0, (ints, w))[1]

    got = jax.device_get(gather_k(ring.snapshot(), ints, w))
    mismatched = []
    for j in range(k):
        for i in range(B):
            b, t0, seq = (int(x) for x in ints[j, i, :3])
            t = np.minimum(t0 + np.arange(T), MS - 1)
            wi = np.minimum(seq * L + np.arange(L), BL - 1)
            h = host[b]
            want = dict(
                obs=h["obs"][t].reshape(T, *cfg.stored_obs_shape),
                last_action=h["last_action"][t].astype(np.float32),
                last_reward=h["last_reward"][t], hidden=h["hidden"][seq],
                action=h["action"][wi].astype(np.int32),
                n_step_reward=h["n_step_reward"][wi],
                n_step_gamma=h["n_step_gamma"][wi])
            mismatched += [name for name, v in want.items()
                           if not np.array_equal(got[name][j, i], v)]
    return dict(ok=not mismatched, slots=slots, rows_checked=k * B,
                ring_obs_shape=list(ring.arrays["obs"].shape),
                mismatched_fields=sorted(set(mismatched)))


def _child_kernel(rehearsal: bool) -> dict:
    """The Pallas LSTM against the scan recurrence behind the same params,
    through the act entry point of the full flagship network; then the
    device ring's gather against numpy."""
    import jax
    import numpy as np

    from r2d2_tpu.config import pong_config, test_config
    from r2d2_tpu.envs import create_env
    from r2d2_tpu.models.network import (
        R2D2Network,
        create_network,
        init_params,
    )
    from r2d2_tpu.models.state import state_spec
    from r2d2_tpu.utils.compile_cache import enable
    from r2d2_tpu.utils.trace import device_memory

    enable()
    # rehearsal: the CPU cannot lower Mosaic — the kernel is INTERPRETED,
    # and the leg says so
    cfg = (test_config(game_name="Fake", pallas_interpret=True) if rehearsal
           else pong_config(game_name="Fake"))
    A = int(create_env(cfg, seed=0).action_space.n)
    nets = {impl: create_network(cfg.replace(lstm_impl=impl), A)
            for impl in ("pallas", "scan")}
    params = init_params(cfg, nets["scan"], jax.random.PRNGKey(cfg.seed))
    rng = np.random.default_rng(0)
    cases, ok = [], True
    for B in ((1, 4) if rehearsal else (1, 64, 256)):
        obs = rng.integers(0, 256, (B, *cfg.stored_obs_shape), np.uint8)
        la = np.zeros((B, A), np.float32)
        la[np.arange(B), rng.integers(A, size=B)] = 1.0
        lr = rng.normal(size=B).astype(np.float32)
        hid = (rng.normal(size=(B,) + state_spec(cfg)[0]) * 0.1).astype(
            state_spec(cfg)[1])
        out, case = {}, dict(B=B)
        for impl, net in nets.items():
            fn = jax.jit(lambda p, *a, net=net: net.apply(
                p, *a, method=R2D2Network.act))
            t0 = time.perf_counter()
            lowered = fn.lower(params, obs, la, lr, hid)
            compiled = lowered.compile()
            case[f"{impl}_compile_s"] = round(time.perf_counter() - t0, 3)
            if impl == "pallas":
                # proof of no substitution: Mosaic's custom call is IN the
                # program the chip runs
                case["tpu_custom_call"] = ("tpu_custom_call"
                                           in lowered.as_text())
            out[impl] = jax.block_until_ready(
                compiled(params, obs, la, lr, hid))
        q_p, h_p = (np.asarray(x) for x in out["pallas"])
        q_s, h_s = (np.asarray(x) for x in out["scan"])
        case.update(q_shape=list(q_p.shape),
                    q_max_abs_diff=float(np.abs(q_p - q_s).max()),
                    hidden_max_abs_diff=float(np.abs(h_p - h_s).max()))
        # bf16 tolerance (the kernel rounds once less than scan's
        # bf16-output matmul; exact in float32)
        case["ok"] = bool(
            q_p.shape == (B, A) and np.isfinite(q_p).all()
            and np.isfinite(h_p).all()
            and np.allclose(q_p, q_s, rtol=2e-2, atol=2e-2)
            and np.allclose(h_p, h_s, rtol=2e-2, atol=2e-2)
            and (rehearsal or case["tpu_custom_call"]))
        ok = ok and case["ok"]
        cases.append(case)
    ring = _ring_gather_check(
        cfg if rehearsal else cfg.replace(buffer_capacity=400_000), A)
    return dict(ok=ok and ring["ok"], interpret=bool(cfg.pallas_interpret),
                compute_dtype=cfg.compute_dtype, hidden_dim=cfg.hidden_dim,
                torso=cfg.torso, action_dim=A, cases=cases,
                ring_gather=ring, device_memory=device_memory())


# --------------------------------------------------------------------------
# parent: process plumbing (never touches a JAX backend)
# --------------------------------------------------------------------------

def _kill(proc: subprocess.Popen) -> None:
    """Stop a child and everything it started (its own process group)."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        pass  # unkillable (stuck in a device call): nothing more to do


def _spawn(name: str, argv: list, out_dir: str, env: dict
           ) -> subprocess.Popen:
    """Start one child with its streams in files (a full pipe must never
    stall a child mid-dispatch), in its own process group."""
    proc = subprocess.Popen(
        argv, cwd=REPO, env=env, start_new_session=True,
        stdout=open(os.path.join(out_dir, f"{name}.stdout"), "w"),
        stderr=open(os.path.join(out_dir, f"{name}.stderr"), "w"))
    _children.append(proc)
    return proc


def _read(out_dir: str, name: str, stream: str) -> str:
    with open(os.path.join(out_dir, f"{name}.{stream}"),
              errors="replace") as f:
        return f.read()


def _last_json(text: str):
    for line in reversed(text.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    return None


_COMPILE_RE = re.compile(
    r"Finished (tracing \+ transforming|jaxpr to MLIR module conversion|"
    r"XLA compilation of) (.*?) in ([0-9.]+) sec")


def _compile_facts(stderr: str) -> dict:
    """What JAX itself logged (JAX_LOG_COMPILES) about getting programs
    ready in the child — trace + lower + XLA compile-or-cache-load
    seconds, summed over every program and thread (so it can exceed the
    leg's wall time).  Set-up facts for sizing cells — not a speed."""
    total = xla = 0.0
    modules, biggest = 0, ("", 0.0)
    for kind, what, secs in _COMPILE_RE.findall(stderr):
        secs = float(secs)
        total += secs
        if kind.startswith("XLA"):
            xla += secs
            modules += 1
            if secs > biggest[1]:
                biggest = (what, secs)
    return dict(first_dispatch_s=round(total, 2),
                xla_compile_s=round(xla, 2), xla_modules=modules,
                slowest_module=biggest[0],
                slowest_module_s=round(biggest[1], 2))


def _cache_entries(cache_dir) -> int:
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return sum(os.path.isfile(p)
               for p in glob.glob(os.path.join(cache_dir, "*")))


def _strict(x):
    """Non-finite floats as strings: the document stays strict JSON."""
    if isinstance(x, float) and not math.isfinite(x):
        return repr(x)
    if isinstance(x, dict):
        return {k: _strict(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_strict(v) for v in x]
    return x


def _peak_bytes(result: dict):
    mem = (result or {}).get("device_memory") or []
    return max((m["peak_bytes_in_use"] for m in mem), default=None)


class Smoke:
    def __init__(self, rehearsal: bool, out_dir: str, work_dir: str):
        self.rehearsal = rehearsal
        self.out = out_dir      # small: child logs + chip_smoke.json
        self.work = work_dir    # big, removed at exit: the checkpoints
        self.t0 = time.monotonic()
        self.env = dict(os.environ, JAX_LOG_COMPILES="1",
                        PYTHONUNBUFFERED="1")
        if rehearsal:
            self.env["JAX_PLATFORMS"] = "cpu"
        self.preset = "test" if rehearsal else "pong"
        self.cache_dir = None     # learned from the device leg
        self.device = None

    def left(self) -> float:
        return BUDGET_S - (time.monotonic() - self.t0)

    # ------------------------------------------------------------- running
    def run(self, name: str, argv: list, cap_s: float) -> dict:
        """One child to completion; the leg's common facts."""
        before = _cache_entries(self.cache_dir)
        t0 = time.monotonic()
        proc = _spawn(name, argv, self.out, self.env)
        try:
            rc = proc.wait(timeout=max(1.0, min(cap_s, self.left())))
        except subprocess.TimeoutExpired:
            rc = None
        finally:
            _kill(proc)
        return self._facts(name, rc, t0, before)

    def _facts(self, name: str, rc, t0: float, before: int) -> dict:
        stderr = _read(self.out, name, "stderr")
        result = _last_json(_read(self.out, name, "stdout"))
        leg = dict(rc=rc, wall_s=round(time.monotonic() - t0, 1),
                   **_compile_facts(stderr),
                   cache_entries_added=(_cache_entries(self.cache_dir)
                                        - before),
                   peak_bytes_in_use=_peak_bytes(result),
                   result=result, failures=[])
        if rc is None:
            leg["failures"].append("timed out; child killed")
        elif rc != 0:
            leg["failures"].append(
                f"exit code {rc}: "
                + " | ".join(stderr.strip().splitlines()[-3:]))
        elif result is None:
            leg["failures"].append("child printed no JSON result")
        return leg

    def module(self, *args) -> list:
        return [sys.executable, "-m", "r2d2_tpu", *map(str, args)]

    def child(self, name: str) -> list:
        return ([sys.executable, os.path.abspath(__file__), "--child", name]
                + (["--rehearsal"] if self.rehearsal else []))

    @staticmethod
    def expect(leg: dict, what: str, cond: bool) -> None:
        if not cond:
            leg["failures"].append(what)

    # ---------------------------------------------------------------- legs
    def leg_device(self) -> dict:
        leg = self.run("device", self.child("device"), 180)
        d = leg["result"] or {}
        if not leg["failures"]:
            self.device = d
            self.cache_dir = d["cache_dir"]
            want = "cpu" if self.rehearsal else "tpu"
            self.expect(leg, f"JAX found platform {d['platform']!r}, not "
                        f"{want!r}", d["platform"] == want)
        return leg

    def _train_args(self, name: str, mesh: bool) -> list:
        """The sizing both trainers share.  Chip: a 1,000-block ring
        (3.2 GB of the 16.9 GB the device reports — it PASSES the 80%
        guard beside the step's working set; the preset's own 2M
        transitions would be refused by name)."""
        # the final learner checkpoint is kept (the serve leg reads the
        # fabric leg's); the full-state replay snapshot — the whole ring,
        # gigabytes — is depth this smoke cuts
        sets = ["replay_snapshot=false"]
        if self.rehearsal:
            sets += ["device_replay=true", "in_graph_per=true",
                     "superstep_k=2", "superstep_pipeline=2"]
        else:
            sets += ["buffer_capacity=400000"]
        if mesh:
            # the capacity-scaling layout: slot axis sharded over dp
            sets.append("device_ring_layout=dp")
        args = ["--preset", self.preset, "--game", "Fake", "--quiet",
                "--ckpt-dir", os.path.join(self.work, f"ck_{name}"),
                "--max-wall-seconds", 420]
        for s in sets:
            args += ["--set", s]
        return args + (["--mesh"] if mesh else [])

    def _check_trainer(self, leg: dict, drivetrain: str, act: str,
                       steps: int) -> dict:
        m = leg["result"] or {}
        if leg["failures"]:
            return m
        self.expect(leg, f"drivetrain {m.get('drivetrain')!r} ran, not "
                    f"{drivetrain!r}", m.get("drivetrain") == drivetrain)
        self.expect(leg, f"acting ran on {m.get('act_platform')!r}, not "
                    f"{act!r}", m.get("act_platform") == act)
        self.expect(leg, f"num_updates {m.get('num_updates')} < {steps}",
                    m.get("num_updates", 0) >= steps)
        self.expect(leg, f"mean_loss {m.get('mean_loss')} not finite",
                    math.isfinite(m.get("mean_loss", math.nan)))
        self.expect(leg, "buffer_training_steps != num_updates",
                    m.get("buffer_training_steps") == m.get("num_updates"))
        for flag in ("fabric_failed", "learner_stalled"):
            self.expect(leg, f"{flag} is not false", m.get(flag) is False)
        return m

    def _check_spread(self, leg: dict, m: dict) -> None:
        """--mesh with a dp-sharded ring: every device holds its share,
        none holds the lot (not everything on device 0)."""
        if self.rehearsal:   # the CPU client keeps no memory stats
            leg["spread"] = "not measured (CPU rehearsal)"
            return
        from r2d2_tpu.config import pong_config
        from r2d2_tpu.replay.device_ring import device_bytes

        ring = device_bytes(pong_config(game_name="Fake",
                                        buffer_capacity=400_000), 4)
        used = [d["bytes_in_use"] for d in m.get("device_memory") or []]
        leg["bytes_in_use_per_device"] = used
        n = self.device["device_count"]
        self.expect(leg, f"memory stats for {len(used)} devices, not {n}",
                    len(used) == n)
        if used:
            self.expect(leg, f"uneven spread over devices: {used}",
                        min(used) > 0 and max(used) < 1.5 * min(used))
            # one device must hold about 1/n of the ring, not the lot
            self.expect(leg, f"device 0 holds {used[0] / 1e9:.2f} GB of a "
                        f"{ring / 1e9:.2f} GB ring — it is not sharded",
                        used[0] < 0.6 * ring)

    def leg_fabric(self, mesh: bool = False) -> dict:
        name = "fabric_mesh" if mesh else "fabric"
        steps = 8 if self.rehearsal else 48
        args = self._train_args(name, mesh)
        # learning_starts >= one block cut per lane (64 x 400)
        args += ["--training-steps", steps]
        if not self.rehearsal:
            args += ["--set", "learning_starts=25600"]
        leg = self.run(name, self.module("train", *args), 480)
        # the host CPU act twin is the thread actors' design (act_device
        # "auto"); the leg names it so the benchmark can judge it
        m = self._check_trainer(leg, "device_ring_in_graph_per", "cpu",
                                steps)
        leg["host_sum_tree"] = m.get("host_sum_tree")
        if mesh and not leg["failures"]:
            self._check_spread(leg, m)
        return leg

    def leg_anakin(self, mesh: bool = False) -> dict:
        name = "anakin_mesh" if mesh else "anakin"
        steps = 8 if self.rehearsal else 24
        lanes = (4 if mesh else 2) if self.rehearsal else 64
        args = self._train_args(name, mesh)
        args += ["--actor-transport", "anakin", "--actors", lanes,
                 "--training-steps", steps]
        if not self.rehearsal:
            # 64 lanes x 128 steps: four 32-step episodes per lane
            args += ["--set", "learning_starts=8192"]
        leg = self.run(name, self.module("train", *args), 480)
        platform = "cpu" if self.rehearsal else "tpu"
        m = self._check_trainer(leg, "anakin", platform, steps)
        if not leg["failures"]:
            self.expect(leg, "dispatch_wedged is not false",
                        m.get("dispatch_wedged") is False)
            self.expect(leg, f"env_steps {m.get('env_steps')} did not "
                        "advance", m.get("env_steps", 0) > 0)
            self.expect(leg, "no episode completed on the in-graph env",
                        m.get("episodes", 0) > 0)
            if mesh:
                self._check_spread(leg, m)
        return leg

    def leg_serve(self) -> dict:
        """Serve the fabric leg's checkpoint; this process is the client."""
        import numpy as np

        from r2d2_tpu.config import pong_config, test_config
        from r2d2_tpu.envs import create_env
        from r2d2_tpu.serving.client import SessionClient
        from r2d2_tpu.serving.wire import STATUS_OK

        cfg = (test_config if self.rehearsal else pong_config)(
            game_name="Fake")
        A = int(create_env(cfg, seed=0).action_space.n)
        ports = []
        for _ in range(2):
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                ports.append(s.getsockname()[1])
        port, mport = ports
        before = _cache_entries(self.cache_dir)
        t0 = time.monotonic()
        proc = _spawn("serve", self.module(
            "serve", "--preset", self.preset, "--game", "Fake",
            "--ckpt-dir", os.path.join(self.work, "ck_fabric"),
            "--port", port, "--metrics-port", mport, "--quiet",
            "--max-wall-seconds", 400), self.out, self.env)
        sent = answered = 0
        traffic_failures, buckets_hit = [], {}
        try:
            # /healthz answers once warm-up compiled every act bucket
            ready_by = time.monotonic() + min(300.0, self.left())
            while True:
                if proc.poll() is not None:
                    raise RuntimeError("server exited before /healthz")
                if time.monotonic() > ready_by:
                    raise RuntimeError("server never answered /healthz")
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{mport}/healthz",
                            timeout=2) as r:
                        if r.status == 200:
                            break
                except OSError:
                    time.sleep(0.5)
            ready_s = round(time.monotonic() - t0, 1)
            client = SessionClient(cfg, A, "127.0.0.1", port, timeout=60.0)
            rng = np.random.default_rng(0)
            sids = list(range(1, 65))
            for sid in sids:
                if client.open_session(sid) != STATUS_OK:
                    traffic_failures.append(f"open {sid} refused")
            # waves of pipelined acts, one per session in the wave: wave
            # sizes aim at buckets 1, 4, 16 and 64 (the batch loop may
            # split a wave; the buckets actually hit are read back from
            # the server's own batch-size histogram below)
            first = {sid: True for sid in sids}
            for wave in (1, 1, 1, 4, 4, 16, 16, 64, 64):
                pending = []
                for sid in sids[:wave]:
                    la = np.zeros(A, np.float32)
                    la[rng.integers(A)] = 1.0
                    obs = rng.integers(0, 256, cfg.stored_obs_shape,
                                       dtype=np.uint8)
                    pending.append((sid, client.send_act(
                        sid, obs, la, float(rng.normal()),
                        reset=first[sid])))
                    first[sid] = False
                    sent += 1
                for sid, seq in pending:
                    status, q = client.recv(sid, seq)
                    if (status == STATUS_OK and q is not None
                            and q.shape == (A,) and np.isfinite(q).all()):
                        answered += 1
                    else:
                        traffic_failures.append(
                            f"act sid={sid}: status {status}, q {q}")
            for sid in sids:
                client.close_session(sid)
            client.close()
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{mport}/metrics", timeout=5) as r:
                cum = [(float(le), float(n)) for le, n in re.findall(
                    r'r2d2_serving_batch_size_bucket\{le="([0-9.]+)"\} '
                    r'([0-9.]+)', r.read().decode())]
            prev = 0.0
            for le, n in sorted(cum):
                if n > prev:
                    buckets_hit[int(le)] = int(n - prev)
                prev = n
            proc.send_signal(signal.SIGTERM)   # drain, snapshot, exit JSON
            try:
                rc = proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                rc = None
        except (RuntimeError, OSError) as e:
            traffic_failures.append(f"{type(e).__name__}: {e}")
            ready_s, rc = None, proc.poll()
        finally:
            _kill(proc)
        leg = self._facts("serve", rc, t0, before)
        leg.update(ready_s=ready_s, acts_sent=sent, acts_answered=answered,
                   batch_buckets_hit=buckets_hit)
        leg["failures"] += traffic_failures
        s = leg["result"] or {}
        if not leg["failures"]:
            want = (("cpu", "scan", cfg.compute_dtype) if self.rehearsal
                    else ("tpu", "pallas", "bfloat16"))
            got = (s.get("act_platform"), s.get("act_lstm_impl"),
                   s.get("act_compute_dtype"))
            self.expect(leg, f"acts ran as {got}, not {want}", got == want)
            self.expect(leg, f"{answered}/{sent} acts answered OK",
                        answered == sent > 0)
            self.expect(leg, f"server counted {s.get('requests')} acts, "
                        f"client sent {sent}", s.get("requests") == sent)
            self.expect(
                leg, "session accounting broken",
                s.get("admitted") == (s.get("completed", 0)
                                      + s.get("reaped", 0)
                                      + s.get("evicted", 0)
                                      + s.get("live", 0)) == len(sids))
            self.expect(leg, f"batch buckets hit {sorted(buckets_hit)}: "
                        "need >= 3 including B=1",
                        len(buckets_hit) >= 3 and 1 in buckets_hit)
            self.expect(leg, f"health {s.get('health')!r}",
                        s.get("health") in ("ok", "degraded"))
        return leg

    def leg_kernel(self) -> dict:
        leg = self.run("kernel", self.child("kernel"), 300)
        k = leg["result"] or {}
        if not leg["failures"]:
            self.expect(leg, "pallas and scan disagree (see cases)",
                        all(c["ok"] for c in k.get("cases", [])))
            self.expect(leg, "the ring gather read the wrong bytes: "
                        f"{k.get('ring_gather')}",
                        (k.get("ring_gather") or {}).get("ok") is True)
            self.expect(leg, "kernel ran interpreted",
                        k.get("interpret") is self.rehearsal)
        return leg


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU mode at --preset test sizes; never the "
                         "default, never prints the success line")
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out",
                                                  "chip_smoke"),
                    help="where child logs and chip_smoke.json go "
                         "(checkpoints live in a temp dir, removed at "
                         "exit)")
    ap.add_argument("--legs", default=None,
                    help="comma-separated subset to run (debugging); a "
                         "partial run reports the rest as skipped and "
                         "cannot pass")
    ap.add_argument("--child", choices=("device", "kernel"),
                    help=argparse.SUPPRESS)
    a = ap.parse_args(argv)

    if a.child:
        body = (_child_device() if a.child == "device"
                else _child_kernel(a.rehearsal))
        print(json.dumps(body), flush=True)
        return 0

    os.makedirs(a.out, exist_ok=True)
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    smoke = Smoke(a.rehearsal, a.out, work)
    wanted = set(a.legs.split(",")) if a.legs else None
    unknown = (wanted or set()) - set(LEGS + MESH_LEGS)
    if unknown:
        ap.error(f"unknown legs {sorted(unknown)}")
    legs: dict = {}
    try:
        legs["device"] = smoke.leg_device()
        plan = list(LEGS[1:])
        if smoke.device and smoke.device["device_count"] >= 4:
            plan += MESH_LEGS
        for name in plan:
            if legs["device"]["failures"]:
                legs[name] = dict(skipped="no usable device", failures=[
                    "skipped: the device leg failed"])
            elif wanted is not None and name not in wanted:
                legs[name] = dict(skipped="not selected", failures=[
                    "skipped: not in --legs"])
            elif name == "serve" and legs["fabric"]["failures"]:
                legs[name] = dict(skipped="no checkpoint", failures=[
                    "skipped: the fabric leg left no checkpoint"])
            elif smoke.left() < 30:
                legs[name] = dict(skipped="out of time", failures=[
                    f"skipped: {BUDGET_S:.0f}s budget spent"])
            else:
                base = name.removesuffix("_mesh")
                fn = getattr(smoke, f"leg_{base}")
                legs[name] = (fn(mesh=True) if name.endswith("_mesh")
                              else fn())
    finally:
        for proc in _children:
            _kill(proc)
        shutil.rmtree(work, ignore_errors=True)

    for leg in legs.values():
        leg["ok"] = not leg["failures"]
    ok = all(leg["ok"] for leg in legs.values())
    doc = dict(
        ok=ok, rehearsal=a.rehearsal,
        device=smoke.device,
        model=("test preset (rehearsal)" if a.rehearsal else
               "pong preset: Nature torso + space-to-depth, LSTM-512 x 1, "
               "bf16 compute / f32 params, 84x84, B=64, T=85"),
        wall_s=round(time.monotonic() - smoke.t0, 1),
        cache=dict(dir=smoke.cache_dir,
                   from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
                   entries=_cache_entries(smoke.cache_dir)),
        legs=legs,
        claim=None)
    text = json.dumps(_strict(doc))
    with open(os.path.join(a.out, "chip_smoke.json"), "w") as f:
        f.write(text + "\n")
    if not ok or a.rehearsal:
        # no result on stdout: a failed smoke and a rehearsal both must
        # not be mistaken for a pass on the chip
        print(text, file=sys.stderr)
        for name, leg in legs.items():
            for why in leg["failures"]:
                print(f"chip_smoke: leg {name}: {why}", file=sys.stderr)
        return 0 if ok else 1
    print(text)
    d = smoke.device
    print(json.dumps(dict(ok=True, device=dict(
        platform=d["platform"], kind=d["device_kind"],
        count=d["device_count"]))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
