"""Declarative per-parameter sharding for the unified pjit learner.

The r1-r8 learner carried an axis-variant surface: a ``_param_spec``
heuristic for the retired ``mp`` axis, shard_map-wrapped super-step
variants for dp-sharded rings, and mesh-vs-no-mesh branches through the
learner.  This module collapses all of it into the GSPMD-native shape the
Podracer/pjit lineage uses (SNIPPETS.md [2], [3]): ONE
``jax.jit(in_shardings=..., out_shardings=..., donate_argnums=...)``
train step per drivetrain, whose entire layout comes from a declarative
**sharding table** over a 3-axis mesh:

- ``dp``  — data parallelism: the batch's leading axis, the replay ring's
  slot axis, gradient psums inserted by XLA.
- ``fsdp`` — parameter/moment sharding for memory: kernels (and their
  optimizer moments, which inherit the param layout by construction —
  adam's ``mu``/``nu`` subtrees carry the same trailing key paths) shard
  a large dim, XLA inserting the allgather/reduce-scatter pairs.
- ``tp``  — Megatron-style tensor parallelism: the LSTM 4H gate kernels
  and dense output dims column-split; gate nonlinearities and dueling
  heads are elementwise/tiny in the split dim.

The table maps **param-path patterns** to per-dim axis assignments.
Integer layer indices are wildcarded (``lstm_0`` → ``lstm_*`` — the
SNIPPETS.md [3] ``sharding_map`` convention), patterns match the
*trailing* tokens of a leaf's path (so ``params``, ``target_params`` and
the optax moments all resolve through one entry), a per-dim divisibility
guard falls back to replication when a dim does not divide its mesh
axis, and an **unresolved leaf is an error** — a new model family must
extend the table (docs/SHARDING.md) rather than silently replicate at
pod scale.

Scalars (0-d leaves: the step counter, adam's ``count``) always
replicate; no table entry is needed or consulted.

``cfg.sharding_table`` overrides/extends the default table from the CLI
(``pattern=axis,axis;pattern2=...`` — empty slots replicate that dim).
"""
from __future__ import annotations

import warnings
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

# the override grammar lives in config.py (jax-free, so Config can
# validate it at construction); re-exported here as the table's home
from r2d2_tpu.config import normalize_token, parse_table  # noqa: F401
from r2d2_tpu.parallel.mesh import trivial_mesh
from r2d2_tpu.utils.trace import RETRACES

# device-batch fields (everything else in a replay batch is host-only
# bookkeeping: idxes, block_ptr, env_steps); replay/device_ring.py's
# gather emits exactly these keys
DEVICE_BATCH_KEYS = (
    "obs", "last_action", "last_reward", "hidden", "action",
    "n_step_reward", "n_step_gamma", "burn_in", "learning", "forward",
    "is_weights",
)

# device-ring data arrays (replay/device_ring.py imports these as its
# _DATA_KEYS — one definition, no drift); named here so the ring's
# sharding constructors resolve through the table
RING_DATA_KEYS = ("obs", "last_action", "last_reward", "action",
                  "n_step_reward", "n_step_gamma", "hidden")
PER_KEYS = ("prios", "seq_meta", "first")


class UnresolvedShardingError(ValueError):
    """A TrainState leaf matched no sharding-table pattern.

    Silent replication of an unmatched leaf would hide a missing table
    entry until a new model family OOMs at pod scale — new families must
    extend the table (docs/SHARDING.md's add-a-model-family workflow)."""


# pattern → per-dim axis names (None = replicated dim; missing trailing
# dims replicate).  Keys are dot-joined NORMALIZED path suffixes: integer
# layer indices already wildcarded, "*" matches any single token.
DEFAULT_TABLE: Dict[str, Tuple[Optional[str], ...]] = {
    # conv torsos (nature/impala): compute is batch-dominated and dp
    # shards it; fsdp takes the output-channel dim purely for memory
    "torso.Conv_*.kernel": (None, None, None, "fsdp"),
    "torso.Conv_*.bias": (),
    # torso FC (nature flatten->512 dominates param count): fsdp on the
    # huge input dim, tp on the output dim
    "torso.Dense_*.kernel": ("fsdp", "tp"),
    "torso.Dense_*.bias": ("tp",),
    # LSTM: the 4H gate kernels take the Megatron column split over tp
    # (gate math is elementwise in the 4H dim); fsdp shards the input dim
    "lstm_*.wi": ("fsdp", "tp"),
    "lstm_*.wh": ("fsdp", "tp"),
    "lstm_*.b": ("tp",),
    # dueling head: hidden kernels split like the torso FC; the tiny
    # output dims (action_dim, 1) fall back to replication via the
    # divisibility guard wherever tp does not divide them
    "head.*.kernel": ("fsdp", "tp"),
    "head.*.bias": ("tp",),
    # the memory cores that are modules of their own (models/xing4.py,
    # models/olmo_hybrid.py): every leaf replicated — parameters stacked
    # by block or by period, and the cores' buffers.  A chip holds its
    # share of a layer's heads and experts by configuration
    # (core_heads_held, core_experts_held); there is no expert axis, no
    # axis over the heads and no exchange yet (ROADMAP Queue 2), so a
    # mesh only adds dp.  One entry a depth of a core's tree
    "core.*": (),
    "core.*.*": (),
    "core.*.*.*": (),
    "core.*.*.*.*": (),
    # device-replay plane: ring slots and PER leaves shard over dp when
    # the ring layout asks for it (DeviceRing consumes these entries)
    "ring.*": ("dp",),
    "per.*": ("dp",),
    # anakin fused loop (learner/anakin.py): per-lane carry arrays — env
    # state, agent obs/LSTM carry, local stream buffers — shard their
    # lane axis over dp (the Podracer replicate-the-program axis);
    # anakin_state_shardings resolves every lane-batched leaf through
    # this one entry (scalars/fleet-wide RNG keys replicate, the ring
    # slot-axis accounting follows the ring.* entries)
    "anakin.lane.*": ("dp",),
}

def _path_token(entry: Any) -> str:
    """One pytree KeyPath entry → its string token (DictKey.key,
    GetAttrKey.name, SequenceKey.idx, FlattenedIndexKey.key)."""
    for attr in ("key", "name"):
        v = getattr(entry, attr, None)
        if v is not None:
            return str(v)
    v = getattr(entry, "idx", None)
    if v is not None:
        return str(v)
    return str(entry)


def normalize_path(tokens: Sequence[str]) -> Tuple[str, ...]:
    return tuple(normalize_token(t) for t in tokens)


class ShardingTable:
    """The resolved sharding rules over one mesh.

    One instance is built per trainer bring-up (``train._build``) and
    consumed by every sharding constructor: the unified train/super
    steps' in/out shardings, the Learner's batch staging, the DeviceRing
    slot/PER layouts, and checkpoint re-placement.
    """

    def __init__(self, mesh, cfg: Any = None,
                 rules: Optional[Dict[str, Tuple[Optional[str], ...]]]
                 = None):
        if isinstance(cfg, dict):
            # ShardingTable(mesh, {...}) would silently treat a rules
            # dict as cfg (getattr(dict, "sharding_table", "") == "")
            # and ignore it — the caller meant rules=
            raise TypeError(
                "ShardingTable's second positional arg is cfg; pass "
                "extra pattern rules via the rules= keyword")
        self.mesh = mesh
        self.rules = dict(DEFAULT_TABLE)
        if rules:
            self.rules.update(rules)
        if cfg is not None and getattr(cfg, "sharding_table", ""):
            self.rules.update(parse_table(cfg.sharding_table))
        # longest pattern wins, and at equal length the entry with fewer
        # "*" tokens wins (a fully-specified override must beat a wildcard
        # default — "*" sorts before letters, so raw lexicographic order
        # would silently shadow it); lexicographic tiebreak last keeps
        # resolution deterministic
        self._patterns = sorted(
            ((tuple(p.split(".")), spec) for p, spec in self.rules.items()),
            key=lambda kv: (-len(kv[0]),
                            sum(t == "*" for t in kv[0]), kv[0]))

    # ------------------------------------------------------------ resolve
    def lookup(self, tokens: Sequence[str]
               ) -> Optional[Tuple[Optional[str], ...]]:
        """The first (longest) pattern matching the normalized path's
        trailing tokens, or None."""
        norm = normalize_path(tokens)
        for pat, spec in self._patterns:
            n = len(pat)
            if n <= len(norm) and all(
                    p == "*" or p == t for p, t in zip(pat, norm[-n:])):
                return spec
        return None

    def spec(self, tokens: Sequence[str],
             shape: Optional[Tuple[int, ...]] = None) -> P:
        """PartitionSpec for one leaf: 0-d leaves replicate, otherwise the
        table entry with the per-dim divisibility guard applied.  Raises
        :class:`UnresolvedShardingError` when no pattern matches."""
        if shape is not None and len(shape) == 0:
            return P()
        entry = self.lookup(tokens)
        if entry is None:
            raise UnresolvedShardingError(
                f"no sharding-table entry matches param path "
                f"{'.'.join(tokens)!r} (normalized "
                f"{'.'.join(normalize_path(tokens))!r}). Extend the table "
                f"— cfg.sharding_table override or "
                f"parallel/sharding.DEFAULT_TABLE; see docs/SHARDING.md "
                f"for the add-a-model-family workflow.")
        if shape is None:
            return P(*entry)
        if len(entry) > len(shape):
            raise ValueError(
                f"sharding-table entry {entry} for "
                f"{'.'.join(tokens)!r} names more dims than the leaf's "
                f"shape {shape}")
        dims = []
        for i, size in enumerate(shape):
            axis = entry[i] if i < len(entry) else None
            # divisibility guard: an indivisible dim replicates — the
            # layout is a pure perf choice, semantics are identical
            if axis is not None and size % self.mesh.shape[axis] != 0:
                axis = None
            dims.append(axis)
        return P(*dims)

    # --------------------------------------------------------- shardings
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def state_shardings(self, state) -> Any:
        """A TrainState-shaped tree of NamedShardings under the table.

        Works for ``params``, ``target_params`` and the optimizer
        moments without special-casing optax internals: patterns match
        trailing path tokens, and adam's ``mu``/``nu`` subtrees carry
        the same trailing key paths as the params they mirror — moments
        MUST share their param's layout or every update would reshard.
        ``state`` may hold live arrays or ``jax.ShapeDtypeStruct`` avals.
        """
        def leaf(path, x):
            tokens = [_path_token(k) for k in path]
            return NamedSharding(self.mesh,
                                 self.spec(tokens, tuple(np.shape(x))))
        return jax.tree_util.tree_map_with_path(leaf, state)

    def batch_shardings(self) -> Dict[str, NamedSharding]:
        """Leading-axis ``dp`` sharding for every device-batch field."""
        dp = NamedSharding(self.mesh, P("dp"))
        return {k: dp for k in DEVICE_BATCH_KEYS}

    def ring_shardings(self, layout: str = "replicated") -> Dict[str, Any]:
        """Device-ring array shardings: ``"replicated"`` pins the full
        ring on every device; ``"dp"`` resolves the slot axis through the
        table's ``ring.*`` entries (capacity scales with the mesh)."""
        if layout not in ("replicated", "dp"):
            raise ValueError(f"unknown device-ring layout {layout!r} "
                             "(expected 'replicated' or 'dp')")
        if layout == "replicated":
            return {k: self.replicated() for k in RING_DATA_KEYS}
        return {k: NamedSharding(self.mesh, self.spec(("ring", k)))
                for k in RING_DATA_KEYS}

    def per_shardings(self, layout: str = "replicated") -> Dict[str, Any]:
        """In-graph PER state shardings (prios/seq_meta/first), aligned
        with the ring slabs under ``"dp"`` (leaf axis splits exactly at
        slab boundaries because seqs_per_block divides each shard)."""
        if layout == "replicated":
            return {k: self.replicated() for k in PER_KEYS}
        return {k: NamedSharding(self.mesh, self.spec(("per", k)))
                for k in PER_KEYS}

    def anakin_state_shardings(self, ast, layout: str = "replicated"
                               ) -> Dict[str, Any]:
        """NamedShardings for the anakin fused loop's carry dict
        (learner/anakin.py ``make_anakin_state``): per-lane arrays
        resolve through the table's ``anakin.lane.*`` entry (lane axis
        over dp, with the divisibility guard's replication fallback),
        the ring-slot-axis accounting (``block_learning_total``) follows
        the ``ring.*`` entries under a ``"dp"`` ring layout, and
        scalars / the fleet-wide exploration key replicate.  ``ast`` may
        hold live arrays or ShapeDtypeStructs."""
        out: Dict[str, Any] = {}
        for k, v in ast.items():
            shape = tuple(np.shape(v))
            if k == "block_learning_total":
                out[k] = (NamedSharding(self.mesh,
                                        self.spec(("ring", k), shape))
                          if layout == "dp" else self.replicated())
            elif k == "act_key" or len(shape) == 0:
                out[k] = self.replicated()
            else:
                out[k] = NamedSharding(
                    self.mesh, self.spec(("anakin", "lane", k), shape))
        return out

    def place_state(self, state):
        """Place a host/any-layout TrainState onto the mesh with the
        table layout (used at bring-up and after checkpoint restore —
        the resharding half of the save/restore roundtrip).

        Multi-host: every process holds the same host value (same-seed
        init or a restored checkpoint), and a plain ``device_put`` cannot
        target non-addressable devices — build each global leaf from its
        index map instead."""
        shardings = self.state_shardings(state)
        if jax.process_count() == 1:
            return jax.device_put(state, shardings)

        def leaf(x, sh):
            x = np.asarray(x)
            return jax.make_array_from_callback(
                x.shape, sh, lambda idx: x[idx])
        return jax.tree.map(leaf, state, shardings)


# ---------------------------------------------------------------------------
# the unified jitted drivetrain entry points
# ---------------------------------------------------------------------------

_donation_warning_silenced = False


def _silence_benign_donation_warning() -> None:
    """The drivetrains donate the whole replay batch/index bundles by
    design (the buffers are dead after the gather/forward — donation
    frees them at dispatch even when XLA cannot ALIAS them to an
    output).  The int/uint8 leaves (obs, actions) can never alias the
    f32/scalar outputs, so every compile of a batch-donating step would
    log a multi-line "donated buffers were not usable" UserWarning that
    drowns real signal; the donation itself is correct, so silence
    exactly that message.

    Installed (once) from the factories that compile the batch-donating
    steps, NOT at module import.  Python's warning filters are global,
    so once any factory runs the message IS suppressed process-wide —
    and every trainer builds one (even the anakin path constructs a
    Learner, whose __init__ compiles pjit_train_step), so in practice
    all training processes filter it.  What factory-scoped install buys
    is the absence of an import side effect: host tools that import this
    module just to parse tables or resolve layouts do not have their
    warning state mutated."""
    global _donation_warning_silenced
    if _donation_warning_silenced:
        return
    _donation_warning_silenced = True
    warnings.filterwarnings(
        "ignore", message="Some donated buffers were not usable",
        category=UserWarning)


def _check_batch(cfg, mesh) -> None:
    if cfg.batch_size % mesh.shape["dp"] != 0:
        raise ValueError(
            f"batch_size {cfg.batch_size} not divisible by "
            f"dp={mesh.shape['dp']}")


def pjit_train_step(cfg, net, table: Optional[ShardingTable] = None,
                    state_template=None, donate_batch: bool = True):
    """THE train-step entry point — the only place a train step is jitted.

    One ``jax.jit`` whose layout comes entirely from the table: the
    TrainState shards per :meth:`ShardingTable.state_shardings`, the
    replay batch keeps its leading-axis ``dp`` sharding, and BOTH are
    donated — the state because the update consumes it, the batch
    because its buffers are dead after the gather/forward and XLA can
    reuse them for outputs (the (B,) priorities can alias is_weights).
    On a 1-device (trivial) mesh this IS the single-device step; there
    is no separate variant.

    ``donate_batch=False`` keeps the batch alive across calls — ONLY for
    checks that deliberately re-step one device-resident batch
    (tests/_mp_worker.py); the training drivetrains always donate.

    ``state_template`` (a live TrainState or its avals) derives the
    per-leaf shardings; retrace-guarded as ``learner.train_step``.

    ``cfg.learnhealth_interval > 0`` appends the replicated in-graph
    diagnostic vector to the outputs (telemetry/learnhealth.py) — the
    drivetrains fold it into their existing result fetch; with the
    default 0 the compiled program is unchanged.
    """
    from r2d2_tpu.learner.step import make_train_step

    if table is None:
        table = ShardingTable(trivial_mesh(), cfg)
    if state_template is None:
        raise ValueError("pjit_train_step needs a state_template (a "
                         "TrainState or its ShapeDtypeStruct avals) to "
                         "resolve per-leaf shardings from the table")
    _silence_benign_donation_warning()
    _check_batch(cfg, table.mesh)
    lh = cfg.learnhealth_interval > 0
    st_sh = table.state_shardings(state_template)
    dp_rows = NamedSharding(table.mesh, P("dp"))
    out_sh = (st_sh, table.replicated(), dp_rows)
    if lh:
        out_sh = out_sh + (table.replicated(),)
    return jax.jit(
        RETRACES.wrap("learner.train_step",
                      make_train_step(cfg, net, learnhealth=lh)),
        in_shardings=(st_sh, table.batch_shardings()),
        out_shardings=out_sh,
        donate_argnums=(0, 1) if donate_batch else (0,),
    )


def pjit_super_step(cfg, net, table: ShardingTable, k: int,
                    state_template=None, layout: str = "replicated"):
    """The device-replay super-step (k fused optimizer steps, batches
    gathered in-graph from the HBM ring), jitted once with table-driven
    shardings: the ring follows ``layout`` (``ring.*`` table entries
    under ``"dp"`` — XLA partitions the gather, no hand-written
    shard_map), the (k, B, 6) index bundles and IS weights shard their
    batch axis over dp and are donated with the state.
    """
    from r2d2_tpu.learner.step import make_super_step_fn

    if state_template is None:
        raise ValueError("pjit_super_step needs a state_template (a "
                         "TrainState or its ShapeDtypeStruct avals) to "
                         "resolve per-leaf shardings from the table — "
                         "compiling without one would silently bypass "
                         "the table layout")
    _silence_benign_donation_warning()
    _check_batch(cfg, table.mesh)
    lh = cfg.learnhealth_interval > 0
    st_sh = table.state_shardings(state_template)
    dp_b = NamedSharding(table.mesh, P(None, "dp"))
    out_sh = (st_sh, table.replicated(), dp_b)
    if lh:
        # the (k, DIAG_SIZE) learnhealth diagnostic rows, replicated
        out_sh = out_sh + (table.replicated(),)
    return jax.jit(
        RETRACES.wrap("learner.super_step",
                      make_super_step_fn(cfg, net, k, learnhealth=lh)),
        in_shardings=(st_sh, table.ring_shardings(layout), dp_b, dp_b),
        out_shardings=out_sh,
        donate_argnums=(0, 2, 3),
    )


def pjit_in_graph_per_super_step(cfg, net, table: ShardingTable, k: int,
                                 state_template=None,
                                 layout: str = "replicated"):
    """The device-PER super-step (sample → gather → step → priority
    scatter inside one dispatch), jitted once with table-driven
    shardings.  Sampling is the global stratified draw regardless of
    layout — under a dp-sharded ring the PER leaves shard with the slabs
    and XLA inserts the cumsum/gather collectives, so over the same
    global ring content a dp-sharded run draws IDENTICAL strata to a
    single-device one (layout is a pure layout choice;
    test_in_graph_per_dp_layout_matches_single_device pins it —
    note block→slab ROUTING does depend on the dp size, so rings filled
    under different dp hold the same blocks in permuted global slots).
    The sampled bundle's batch rows are pinned to dp so
    the forward/backward shards exactly as the host-sampled path's.
    The priorities array is a donated carry, as before.
    """
    from r2d2_tpu.learner.step import make_in_graph_per_super_step_fn

    if state_template is None:
        raise ValueError("pjit_in_graph_per_super_step needs a "
                         "state_template (a TrainState or its "
                         "ShapeDtypeStruct avals) to resolve per-leaf "
                         "shardings from the table — compiling without "
                         "one would silently bypass the table layout")
    _silence_benign_donation_warning()
    _check_batch(cfg, table.mesh)
    st_sh = table.state_shardings(state_template)
    dp_rows = NamedSharding(table.mesh, P("dp"))

    def constrain(ints_t, w_t):
        return (jax.lax.with_sharding_constraint(ints_t, dp_rows),
                jax.lax.with_sharding_constraint(w_t, dp_rows))

    rep = table.replicated()

    def replicate_for_draw(p):
        return jax.lax.with_sharding_constraint(p, rep)

    per = table.per_shardings(layout)
    lh = cfg.learnhealth_interval > 0
    out_sh = (st_sh, per["prios"], table.replicated())
    if lh:
        # the (k, DIAG_SIZE) learnhealth diagnostic rows, replicated
        out_sh = out_sh + (table.replicated(),)
    return jax.jit(
        RETRACES.wrap(
            "learner.in_graph_per_super_step",
            make_in_graph_per_super_step_fn(
                cfg, net, k, constrain=constrain,
                replicate_for_draw=replicate_for_draw, learnhealth=lh)),
        in_shardings=(st_sh, table.ring_shardings(layout), per["prios"],
                      per["seq_meta"], per["first"], table.replicated()),
        out_shardings=out_sh,
        donate_argnums=(0, 2),
    )


def shard_batch(table: ShardingTable,
                batch: Dict[str, np.ndarray]) -> Dict[str, Any]:
    """Host batch → device batch: strip host-only fields, place dp shards
    (the H2D analogue of worker.py:330-342, minus the fields the step
    never needs)."""
    shardings = table.batch_shardings()
    return {k: jax.device_put(batch[k], shardings[k])
            for k in DEVICE_BATCH_KEYS}
