"""Continuous batching over a small set of pre-compiled act entry points.

The training serve plane batches a FIXED window — ``num_actors`` lanes,
every fleet posting in lockstep — so one compiled executable covers every
batch.  External sessions have no lockstep: whatever requests are pending
when the batch loop turns is the batch, and its size is ragged from 1 to
``cfg.serve_max_batch``.  Compiling an executable per observed size would
retrace unboundedly (exactly what the RETRACES guard exists to catch);
padding everything to ``serve_max_batch`` wastes most of the batch at low
load.  The standard middle path is **bucket shaping**: round the ragged
size up to the next power of two, pad the tail rows with zeros (their
outputs are discarded, and pad rows never touch session state), and run
one of ``log2(serve_max_batch)+1`` pre-compiled entry points.  The
RETRACES budget is exactly the bucket count — a trace beyond it means
shape drift, not load.

Quantized serving (``cfg.serve_dtype``, QuaRL): ``"bfloat16"`` quantizes
the published params at publish time — each float32 leaf is rounded
through bfloat16 (the mantissa truncation IS the quantization) and
widened back so the same executable serves both dtypes bit-comparably.
This is the ``param_pump_dtype`` pattern lifted from the pump wire to the
serving tier, and the greedy-action-parity test
(tests/test_serving.py) gates it the same way.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from r2d2_tpu.config import Config
from r2d2_tpu.models.state import state_spec, zero_state
from r2d2_tpu.utils.trace import HOST_TRANSFERS, TRANSFER_GUARD


def bucket_sizes(max_batch: int) -> Tuple[int, ...]:
    """The pre-compiled batch shapes: powers of two below ``max_batch``,
    then ``max_batch`` itself (so the largest bucket is exactly the
    configured cap, power of two or not)."""
    sizes = []
    b = 1
    while b < max_batch:
        sizes.append(b)
        b *= 2
    sizes.append(int(max_batch))
    return tuple(sizes)


class ContinuousBatcher:
    """Ragged-batch act over bucket-shaped jitted entry points."""

    def __init__(self, cfg: Config, action_dim: int):
        import jax

        from r2d2_tpu.actor import make_act_fn
        from r2d2_tpu.models.network import create_network

        self.cfg = cfg
        self.action_dim = action_dim
        self.buckets = bucket_sizes(cfg.serve_max_batch)
        net = create_network(cfg, action_dim)
        # the session tier serves from this process's first device — the
        # accelerator when there is one — and its act net is resolved FOR
        # that device (cfg.act_device is the training actors' knob: its
        # "auto" means a host-CPU twin, which must not be what the chip
        # executes).  One jitted instance; each bucket shape is one
        # deliberate trace (+1 slack for a weak-type wobble on the very
        # first call)
        self._act = make_act_fn(cfg, net, device=jax.local_devices()[0],
                                retrace_name="serving.act",
                                retrace_budget=len(self.buckets) + 1)
        # where an act output was OBSERVED to live (set by the first act)
        self.act_platform: Optional[str] = None
        self._params = None
        self.version = 0
        # per-bucket padded scratch, allocated on first use of each size
        self._scratch: dict = {}

    # ------------------------------------------------------------- params
    @staticmethod
    def _quantize(params):
        """The bf16 weights-only round-trip (mantissa truncation IS the
        quantization) — shared by :meth:`publish` and the re-runnable
        :meth:`greedy_parity_ok` gate so the gate tests exactly what
        publish ships."""
        import jax
        import jax.numpy as jnp

        return jax.tree.map(
            lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
            if hasattr(x, "dtype") and x.dtype == jnp.float32 else x,
            params)

    def publish(self, params) -> int:
        """Adopt a new param snapshot for serving.  ``serve_dtype=
        "bfloat16"`` quantizes every float32 leaf through bfloat16 at
        publish (weights-only post-training quantization; the act math
        stays the executable's own compute dtype), exactly like
        ``param_pump_dtype`` narrows the pump wire."""
        import jax

        if self.cfg.serve_dtype == "bfloat16":
            params = self._quantize(params)
        # commit once per publish to the device the act was resolved for
        # (host trees from a checkpoint restore and device trees alike)
        self._params = jax.device_put(params, self._act.device)
        self.version += 1
        return self.version

    def greedy_parity_ok(self, params, probe: int = 32,
                         seed: int = 0) -> bool:
        """The greedy-action-parity gate, re-runnable per publish: on a
        seeded probe batch, the bf16-quantized params must pick the same
        greedy actions as the full-precision ones.  Follow-mode serving
        runs this before EVERY republish (a trained policy can drift
        into bf16-sensitive logit margins long after the initial gate
        passed); trivially True when ``serve_dtype`` is float32.  The
        probe batch is bucket-shaped so the gate never costs an extra
        trace."""
        if self.cfg.serve_dtype != "bfloat16":
            return True
        import jax

        cfg = self.cfg
        n = self.bucket(min(probe, self.buckets[-1]))
        rng = np.random.default_rng(seed)
        obs = rng.integers(0, 256, (n, *cfg.stored_obs_shape), np.uint8)
        la = np.zeros((n, self.action_dim), np.float32)
        la[np.arange(n), rng.integers(self.action_dim, size=n)] = 1.0
        lr = rng.normal(size=n).astype(np.float32)
        hid = (rng.normal(size=(n,) + state_spec(cfg)[0]) * 0.1).astype(
            state_spec(cfg)[1])
        params = jax.device_put(params, self._act.device)
        q_ref, _ = self._act(params, obs, la, lr, hid)
        q_bf16, _ = self._act(self._quantize(params), obs, la, lr, hid)
        return bool((np.asarray(q_ref).argmax(axis=1)
                     == np.asarray(q_bf16).argmax(axis=1)).all())

    @property
    def ready(self) -> bool:
        return self._params is not None

    def act_info(self) -> dict:
        """What serves: the platform an act output was observed on (None
        before the first act) and the network resolved for it."""
        return dict(act_platform=self.act_platform,
                    act_lstm_impl=self._act.lstm_impl,
                    act_compute_dtype=self._act.compute_dtype)

    # ---------------------------------------------------------------- act
    def bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"batch of {n} exceeds serve_max_batch="
                         f"{self.buckets[-1]}")

    def _pad(self, b: int):
        s = self._scratch.get(b)
        if s is None:
            cfg = self.cfg
            s = self._scratch[b] = dict(
                obs=np.zeros((b, *cfg.stored_obs_shape), np.uint8),
                last_action=np.zeros((b, self.action_dim), np.float32),
                last_reward=np.zeros(b, np.float32),
                hidden=zero_state(cfg, b))
        return s

    def act(self, obs: np.ndarray, last_action: np.ndarray,
            last_reward: np.ndarray, hidden: np.ndarray
            ) -> Tuple[np.ndarray, np.ndarray]:
        """One continuous batch: ``n`` ragged rows in, ``(q, new_hidden)``
        rows out.  Pads to the covering bucket (pad rows carry zeros —
        stale garbage would still be discarded, zeros keep the scratch
        deterministic) and pays ONE device→host fetch per batch
        regardless of size, the serve plane's own invariant."""
        if self._params is None:
            raise RuntimeError("no params published yet")
        import jax

        n = len(obs)
        b = self.bucket(n)
        s = self._pad(b)
        s["obs"][:n] = obs
        s["last_action"][:n] = last_action
        s["last_reward"][:n] = last_reward
        s["hidden"][:n] = hidden
        if n < b:
            s["obs"][n:] = 0
            s["last_action"][n:] = 0.0
            s["last_reward"][n:] = 0.0
            s["hidden"][n:] = 0.0
        with TRANSFER_GUARD.disallow("serving.act"):
            # the batch's declared H2D: the padded scratch rows ride the
            # dispatch as implicit transfers of numpy args
            with HOST_TRANSFERS.allowed("serving.act_put"):
                q, new_hidden = self._act(self._params, s["obs"],
                                          s["last_action"],
                                          s["last_reward"], s["hidden"])
            if self.act_platform is None:
                self.act_platform = next(iter(q.devices())).platform
            # ONE explicit D2H for both outputs (audit r19: was two
            # implicit np.asarray syncs — same values, one blocking
            # fetch, and explicit transfers stay guard-exempt)
            with HOST_TRANSFERS.allowed("serving.act_fetch"):
                q, new_hidden = jax.device_get((q, new_hidden))
        return q[:n], new_hidden[:n]

    def warmup(self) -> None:
        """Pre-compile every bucket entry point (server start-up, before
        traffic): the first real request must not eat a multi-second XLA
        compile inside its deadline."""
        cfg = self.cfg
        for b in self.buckets:
            s = self._pad(b)
            self._act(self._params, s["obs"], s["last_action"],
                      s["last_reward"], s["hidden"])
