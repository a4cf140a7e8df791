"""The flagship cell's super-step, the ``xing4`` core's expert layer, its
blocks' residual streams, the IMPALA torso's gradient and the fused cells'
rollout, compiled for a described (not attached) v5e: what only the TPU
compiler decides about the device ring, about the optimizer's passes over
the weights' state, about the routed rows' buffers, about the passes over
the streams, about the max-pool's layouts and about the fused loop's lane
buffers, checked without a chip.

The compiler has twice chosen a layout for the frame ring under which the
super-step copies all of it on every dispatch (36 % of device time at a
multiple of 128 blocks; PERF.md Findings, PR 21 and PR 24), and which
layout it chooses follows from how the step reads the ring.  The
benchmark's ``ring_copy_device_share`` reads such a copy on the chip; this
is the same question asked of the compiled program's text, at no chip time.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0], SingleDeviceSharding(topo.devices[0])


def compile_uncached(lowered):
    """Compile for the described chip with the persistent cache off: what
    is compiled for a chip that is not attached can be written to it and
    never read back."""
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)


def _computations(text):
    """({name: its instructions' lines}, the entry computation's name)."""
    comps, entry, name = {}, None, None
    for line in text.splitlines():
        m = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if m:
            name = m.group(2)
            comps[name] = []
            entry = name if m.group(1) else entry
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps, entry


def _operations(lines):
    """(name, result's type, operation, operands' names, ``op_name``) of a
    computation's instructions, in order."""
    out = []
    for line in lines:
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$",
                     line)
        if not m:
            continue
        rest, depth, end = m.group(4), 1, 0
        while depth and end < len(rest):
            depth += (rest[end] == "(") - (rest[end] == ")")
            end += 1
        path = re.search(r'op_name="([^"]*)"', line)
        out.append((m.group(1), m.group(2), m.group(3),
                    re.findall(r"%([\w.\-]+)", rest[:end]),
                    path.group(1) if path else ""))
    return out


def _arrays(result):
    """[(``f32[512,2048]``, its bytes)] of a result's type, a tuple's
    elements apart, layouts left out."""
    sizes = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "u8": 1, "pred": 1}
    return [(f"{dtype}[{dims}]",
             sizes[dtype] * int(np.prod([int(d) for d in dims.split(",")
                                         if d])))
            for dtype, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]", result)
            if dtype in sizes]


def assert_the_target_sync_copies_only_when_it_syncs(text):
    """The hard target sync is a conditional on the step counter
    (learner/step._sync_target), and the compiler carries the target
    network through it in place: its first branch (``lax.cond``'s false
    one, 1,999 updates of 2,000) holds nothing but its parameter, its
    second the copies; no operation under ``optimizer`` outside it reads a
    target leaf; and no pass under ``optimizer`` yields more than three
    arrays of the largest leaf's shape — Adam's two moments and the
    weights, not the target network written back beside them as the
    select over every leaf did (PERF.md Findings, PR 36)."""
    comps, _ = _computations(text)
    syncs = [(lines, line) for lines in comps.values() for line in lines
             if " conditional(" in line and '/optimizer/cond"' in line]
    assert len(syncs) == 1, [line for _, line in syncs]
    lines, line = syncs[0]
    identity, copying = (
        _operations(comps[name]) for name in re.search(
            r"branch_computations=\{%?([\w.\-]+), %?([\w.\-]+)\}",
            line).groups())
    assert [kind for _, _, kind, _, _ in identity] == ["parameter"]
    assert "copy" in {kind for _, _, kind, _, _ in copying}
    ops = _operations(lines)
    by_name = {op[0]: op for op in ops}
    # conditional(predicate, the first branch's operands, the second's)
    kept = _operations([line])[0][3][1]
    target = set(by_name[kept][3])
    assert by_name[kept][2] == "tuple" and len(target) > 1
    assert [name for name, _, _, operands, path in ops
            if "/optimizer/" in path and target & set(operands)
            and name != kept] == []
    largest = max((a for leaf in target for a in _arrays(by_name[leaf][1])),
                  key=lambda a: a[1])[0]
    passes = {name: [a for a, _ in _arrays(result)].count(largest)
              for name, result, kind, _, path in ops
              if "/optimizer/" in path and kind == "fusion"}
    assert max(passes.values()) == 3, (largest, passes)


@pytest.fixture(scope="module")
def fabric_super_step(one_chip):
    """(cfg, optimized HLO text, memory analysis) of the fabric cell's
    in-graph-PER super-step at its real shapes."""
    from benchmark.drivers.train import ACTION_DIM, build_config
    from benchmark.manifest import Manifest
    from r2d2_tpu.learner.step import create_train_state
    from r2d2_tpu.models.network import create_network, init_params
    from r2d2_tpu.parallel.mesh import trivial_mesh
    from r2d2_tpu.parallel.sharding import (
        ShardingTable,
        pjit_in_graph_per_super_step,
    )
    from r2d2_tpu.replay.device_ring import _ring_shapes

    device, _ = one_chip
    cfg = build_config(Manifest().cell("nature_lstm512.fabric"), False)
    net = create_network(cfg, ACTION_DIM)
    state = jax.eval_shape(
        lambda k: create_train_state(cfg, init_params(cfg, net, k)),
        jax.random.PRNGKey(0))
    table = ShardingTable(trivial_mesh(device), cfg)
    fn = pjit_in_graph_per_super_step(cfg, net, table, cfg.superstep_k,
                                      state_template=state)

    def sds(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    NB, K = cfg.num_blocks, cfg.seqs_per_block
    ring_sh, per = table.ring_shardings("replicated"), table.per_shardings(
        "replicated")
    args = (
        jax.tree.map(lambda x, s: sds(x.shape, x.dtype, s), state,
                     table.state_shardings(state)),
        {k: sds((NB, *shape), dtype, ring_sh[k])
         for k, (shape, dtype) in _ring_shapes(cfg, ACTION_DIM).items()},
        sds((NB * K,), jnp.float32, per["prios"]),
        sds((NB, K, 3), jnp.int32, per["seq_meta"]),
        sds((NB,), jnp.int32, per["first"]),
        sds((), jnp.uint32, table.replicated()))
    compiled = compile_uncached(fn.lower(*args))
    return cfg, compiled.as_text(), compiled.memory_analysis()


def test_fabric_super_step_never_copies_the_frame_ring(fabric_super_step):
    from benchmark.drivers.train import ACTION_DIM
    from r2d2_tpu.replay.device_ring import _ring_shapes

    cfg, text, memory = fabric_super_step
    (rows, words), _ = _ring_shapes(cfg, ACTION_DIM)["obs"]
    ring = rf"u32\[{cfg.num_blocks},{rows},{words}\]"
    assert re.search(ring, text), "the frame ring is not in the program"
    made = re.findall(rf"= {ring}\S* (\w[\w-]*)\(", text)
    # the ring enters as a parameter and is read in place: nothing in the
    # step may produce an array of its shape
    assert [op for op in made if op not in ("parameter",
                                            "get-tuple-element")] == []
    # the block-minor layouts came with 4.3-5.6 GB of temporaries
    assert memory.temp_size_in_bytes < 1 << 30


def test_fabric_super_step_reads_the_frames_as_windows(fabric_super_step):
    """The windows survive the compiler: the batch's frames are moved as
    ``batch_size`` slices of ``seq_len`` rows of words (a loop over the
    samples), and no operation yields the batch as ``B * T`` gathered
    rows of bytes."""
    from r2d2_tpu.replay.device_ring import frame_words

    cfg, text, _ = fabric_super_step
    B, T = cfg.batch_size, cfg.seq_len
    words = frame_words(int(np.prod(cfg.stored_obs_shape)))
    assert re.search(rf"u32\[{B},1,{T},{words}\]", text)
    assert not re.search(rf"u8\[{B * T},\d+\]\S* fusion\(", text)


def test_fabric_super_step_copies_the_target_only_when_it_syncs(
        fabric_super_step):
    assert_the_target_sync_copies_only_when_it_syncs(fabric_super_step[1])


# ------------------------------------------- the routed experts' row ladder

@pytest.fixture(scope="module")
def expert_layer(one_chip):
    """(cfg, routed pairs, optimized HLO text) of one expert block of
    ``nature_xing4_l5e8h4`` alone, forward and backward, at the cell's
    widths and tokens (models/xing4.routed_experts; ~20 s)."""
    from benchmark.drivers.train import build_config
    from benchmark.manifest import Manifest
    from r2d2_tpu.models import xing4

    _, sharding = one_chip
    cfg = build_config(Manifest().cell("nature_xing4_l5e8h4.anakin"), False)
    tokens = cfg.batch_size * cfg.seq_len

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    p = jax.tree.map(
        lambda x: sds(x.shape[1:]),
        jax.eval_shape(lambda k: xing4.init_blocks(
            k, cfg, 1, False, jnp.float32)["moe"], jax.random.PRNGKey(0)))

    def forward_backward(p, u, bias, g):
        def f(p, u):
            out, load = xing4.routed_experts(cfg, p, u, bias, jnp.bfloat16)
            return jnp.sum(out * g), load
        return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(p, u)

    compiled = compile_uncached(jax.jit(forward_backward).lower(
        p, sds((tokens, cfg.core_dim)), sds((cfg.core_experts,)),
        sds((tokens, cfg.core_dim))))
    return cfg, tokens * cfg.core_top_k, compiled.as_text()


def test_the_smallest_rung_lays_out_no_worst_case_rows(expert_layer):
    """21,760 routed pairs of which this chip's experts take an eighth: in
    the branch of the smallest rung, forward and backward, nothing has
    N k rows of the core's width (each is 156 MB; PERF.md Findings, PR 29),
    and the grouped products are still the compiler's Mosaic calls, which
    skip the tiles past the live rows."""
    from r2d2_tpu.models import xing4

    cfg, pairs, text = expert_layer
    comps, _ = _computations(text)
    ladder = xing4.row_ladder(cfg, pairs)
    assert len(ladder) == 4 and ladder[-1] == pairs == 21760
    switches = re.findall(r"conditional\(.*branch_computations=\{([^}]*)\}",
                          text)
    assert len(switches) == 2                   # forward, backward
    worst = rf"\[{pairs},{cfg.core_dim}\]"
    for names in switches:
        branches = ["\n".join(comps[n.strip().lstrip("%")])
                    for n in names.split(",")]
        assert len(branches) == len(ladder)
        # the last rung is every pair: there the worst case is laid out
        assert re.search(worst, branches[-1])
        for rows, branch in zip(ladder[:-1], branches):
            assert not re.search(rf"\[{pairs},\d+\]", branch), rows
            products = re.findall(
                rf"= bf16\[(?:{rows},\d+|\d+,\d+,\d+)\]\S* custom-call\("
                r".*custom_call_target=\"tpu_custom_call\".*ragged-dot",
                branch)
            # forward 3; backward those 3 again, 3 dX, 3 dW
            assert len(products) in (3, 9), (rows, len(products))
            # a rung is a whole number of the kernel's row tiles
            tiles = set(re.findall(r'ragged_dot_tiling="(\d+),', branch))
            assert tiles and all(int(t) % xing4.ROW_TILE == 0
                                 and rows % int(t) == 0 for t in tiles)


# ------------------------------------------- the residual streams' passes

def _entry_operations(text):
    """(name, result's type, operation, ``op_name``) of the entry
    computation's instructions: what runs once a call, in order."""
    comps, entry = _computations(text)
    return [(name, result, kind, path)
            for name, result, kind, _, path in _operations(comps[entry])]


@pytest.fixture(scope="module")
def xing4_block(one_chip):
    """``compiled(dense, backward)``: the optimized HLO text of one block
    of ``nature_xing4_l5e8h4`` alone at the cell's widths and tokens,
    ``xing4.block`` forward or ``jax.grad`` of its ``jax.checkpoint``
    (dense: ~15 s and ~35 s; an expert block forward ~55 s).  The program
    asks ``jax.default_backend()`` whether its kernels can be lowered, and
    here that is the CPU: the test answers for the described chip."""
    from benchmark.drivers.train import build_config
    from benchmark.manifest import Manifest
    from r2d2_tpu.models import xing4

    _, sharding = one_chip
    cfg = build_config(Manifest().cell("nature_xing4_l5e8h4.anakin"), False)
    tokens, d, cd = cfg.batch_size * cfg.seq_len, cfg.core_dim, jnp.bfloat16

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    X = tuple(sds((tokens, d), cd) for _ in range(cfg.core_streams))
    cache = sds((cfg.batch_size, cfg.core_context, xing4.latent_dim(cfg)), cd)

    def compiled(dense, backward):
        p = jax.tree.map(
            lambda x: sds(x.shape[1:]),
            jax.eval_shape(lambda k: xing4.init_blocks(
                k, cfg, 1, dense, jnp.float32), jax.random.PRNGKey(0)))
        bias = None if dense else sds((cfg.core_experts,))

        def forward(p, X, cache, bias):
            return xing4.block(cfg, p, X, cache, bias, cd)

        def gradient(p, X, cache, bias):
            def loss(p, X):
                out = jax.checkpoint(forward)(p, X, cache, bias)[0]
                return sum(jnp.sum(o.astype(jnp.float32)) for o in out)
            return jax.grad(loss, argnums=(0, 1))(p, X)

        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(jax, "default_backend", lambda: "tpu")
            assert xing4.streams_fused(cfg, tokens)
            lowered = jax.jit(gradient if backward else forward).lower(
                p, X, cache, bias)
        return compile_uncached(lowered).as_text()

    return cfg, tokens, compiled


@pytest.mark.parametrize("feed,backward,launches", [
    ("dense", False, ["streams_read", "streams_write_read", "streams_write"]),
    # the checkpoint's forward again without its last write, whose result
    # is no residual; then, a sublayer, the write's dy and the one pass
    ("dense", True, ["streams_read", "streams_write_read",
                     "streams_dy", "streams_backward",
                     "streams_dy", "streams_backward"]),
    ("routed", False, ["streams_read", "streams_write_read",
                       "streams_write"]),
])
def test_a_block_passes_over_its_streams_once_a_sublayer(
        xing4_block, feed, backward, launches):
    """5,440 tokens of four streams of 3,584: one stream is 39 MB, and the
    plain expressions' forward reads all four five times a sublayer and
    lays ``u`` out in float32 twice (PERF.md Findings, PR 34).  Under
    ``residual_mix`` the compiled block holds the kernels' launches and
    nothing else that yields a stream; the only float32 arrays of that
    size there are ``y``'s cotangents, in the type its product returns,
    and before a routed feed-forward the ``u`` its router multiplies."""
    cfg, tokens, compiled = xing4_block
    ops = _entry_operations(compiled(feed == "dense", backward))
    mix = [op for op in ops if "residual_mix" in op[3]
           and op[2] not in ("get-tuple-element", "bitcast")]
    calls = [name.split(".")[0] for name, _, kind, _ in mix
             if kind == "custom-call"]
    assert calls == launches
    stream = f"bf16[{tokens},{cfg.core_dim}]"
    wide = f"f32[{tokens},{cfg.core_dim}]"
    assert any(stream in kind for _, kind, _, _ in mix)
    for name, result, kind, _ in mix:
        if stream in result or wide in result:
            assert kind == "custom-call", (name, result)
    made_wide = [name.split(".")[0] for name, result, _, _ in mix
                 if wide in result]
    assert made_wide == (["streams_dy"] * 2 if backward else
                         [] if feed == "dense" else ["streams_write_read"])
    # and u's norm is inside: no reduction over a read stands under no
    # scope, as the expressions' ``multiply_reduce_fusion`` did
    assert not [name for name, result, _, path in ops
                if wide in result and path.endswith("reduce_sum")]


# ------------------------------------------------- the IMPALA torso's pools

def _pool_shapes(cfg):
    """The pre-pool and pooled shapes of the IMPALA torso's three stages,
    ``[N, H, W, C]`` and the kernels' ``[H, W, C, N]`` view of each, as the
    compiled text writes them (``bf16[7680,84,84,16]``, ...)."""
    from r2d2_tpu.ops import pool

    n, size, shapes = cfg.batch_size * cfg.seq_len, cfg.obs_shape[0], []
    for c in (16, 32, 32):
        for h in (size, pool.geometry(size)[0]):
            shapes += [f"{n},{h},{h},{c}", f"{h},{h},{c},{n}"]
        size = pool.geometry(size)[0]
    return shapes


def _lowered_on_tpu(build, *args):
    """``build().lower(*args)`` with the program asking the backend, as the
    chip's would, which paths it takes (the pools' kernels; the learner's
    scan network beside acting's Pallas LSTM): here the backend is the
    CPU."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        return build().lower(*args)


def assert_the_pools_keep_an_index_in_the_step_layout(text, cfg):
    """The later stages' max-pool backward is the kernels' (ops/pool.py),
    not XLA's ``select-and-scatter`` over the pre-pool activations (the
    first stage's pool is the stem's, below); the kernels read and write
    the step's own ``{0,3,2,1}`` arrays as their ``[H, W, C, N]`` view, so
    no ``copy`` or ``transpose`` of a pool's array stands beside them; and
    each pre-pool activation is read by its forward kernel alone — nothing
    keeps it for the backward (PERF.md, Findings)."""
    assert "select-and-scatter(" not in text
    comps, _ = _computations(text)
    ops = [op for lines in comps.values() for op in _operations(lines)]
    by_name = {op[0]: op for op in ops}
    calls = {kind: [op for op in ops if op[0].startswith(kind + ".")
                    and op[2] == "custom-call"]
             for kind in ("max_pool_forward", "max_pool_backward")}
    assert [len(c) for c in calls.values()] == [2, 2]
    shapes = _pool_shapes(cfg)
    relaid = [(name, result) for name, result, kind, _, _ in ops
              if kind in ("copy", "transpose")
              and any(f"[{s}]" in result for s in shapes)]
    assert relaid == []
    for name, result, _, operands, _ in calls["max_pool_forward"]:
        (pre,) = operands
        while by_name[pre][2] == "bitcast":
            pre = by_name[pre][3][0]
        readers = [op[0] for op in ops if pre in op[3]]
        assert by_name[pre][2] == "fusion" and readers == [name], \
            (name, pre, readers)


def assert_the_stem_never_writes_its_convolution(text, cfg, forwards):
    """The first stage's convolution and pool are ops/stem.py's kernels:
    ``forwards`` ``stem_forward`` calls (the online pass, and the target
    network's in the whole step) and one ``stem_backward``, each reading
    the frames as the same ``[H, W, N]`` array; no array of the
    convolution's output shape, ``[N, 84, 84, 16]`` in any layout, is
    left anywhere: not the forwards' outputs, not the cotangent that
    ``Conv_0``'s weight gradient was summed over (PERF.md, Findings)."""
    n, size = cfg.batch_size * cfg.seq_len, cfg.obs_shape[0]
    comps, _ = _computations(text)
    ops = [op for lines in comps.values() for op in _operations(lines)]
    by_name = {op[0]: op for op in ops}
    calls = {kind: [op for op in ops if op[0].startswith(kind + ".")
                    and op[2] == "custom-call"]
             for kind in ("stem_forward", "stem_backward")}
    assert [len(c) for c in calls.values()] == [forwards, 1]
    views = ([op[3][-1] for op in calls["stem_forward"]]
             + [op[3][0] for op in calls["stem_backward"]])
    assert {by_name[v][1].split("{")[0] for v in views} == {
        f"bf16[{size},{size},{n}]"}
    roots = set()
    for view in views:
        while by_name[view][2] == "bitcast":
            view = by_name[view][3][0]
        roots.add(view)
    assert len(roots) == 1, roots
    pre = [f"[{n},{size},{size},16]", f"[{size},{size},16,{n}]"]
    assert [s for s in pre if s in text] == []


@pytest.fixture(scope="module")
def impala_cell(one_chip):
    from benchmark.drivers.train import build_config
    from benchmark.manifest import Manifest

    return build_config(Manifest().cell("impala_deep_lstm2.anakin"), False)


@pytest.fixture(scope="module")
def impala_torso_gradient(one_chip, impala_cell):
    """The compiled text of the torso's gradient alone at the cell's 7,680
    frames (~25 s)."""
    from r2d2_tpu.models.network import ImpalaTorso

    _, sharding = one_chip
    cfg = impala_cell
    n = cfg.batch_size * cfg.seq_len
    torso = ImpalaTorso(out_dim=cfg.hidden_dim, compute_dtype=jnp.bfloat16)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params = jax.tree.map(lambda a: sds(a.shape), jax.eval_shape(
        lambda k: torso.init(k, jnp.zeros((1, *cfg.obs_shape))),
        jax.random.PRNGKey(0)))

    def gradient(p, x, g):
        return jax.grad(lambda p: jnp.sum(
            torso.apply(p, x).astype(jnp.float32) * g))(p)

    return compile_uncached(_lowered_on_tpu(
        lambda: jax.jit(gradient), params,
        sds((n, *cfg.obs_shape), jnp.bfloat16),
        sds((n, cfg.hidden_dim)))).as_text()


def test_the_impala_torso_pools_by_the_stored_index(impala_torso_gradient,
                                                    impala_cell):
    """Where the step spent 10.9 ms an update in two ``select-and-scatter``
    ops over the pre-pool tensors (PERF.md, Findings), the torso's gradient
    holds the later pools' forward and backward kernels, on the step's own
    layout."""
    assert_the_pools_keep_an_index_in_the_step_layout(impala_torso_gradient,
                                                      impala_cell)


def test_the_impala_stem_never_writes_its_convolution(impala_torso_gradient,
                                                      impala_cell):
    """Where the first stage's convolution wrote 1.73 GB, its pool read it
    back and its weight gradient was summed over a cotangent of the same
    size (six of the step's ten largest operations, PERF.md §5), the
    torso's gradient holds one ``stem_forward`` and one ``stem_backward``
    and no array of that shape."""
    assert_the_stem_never_writes_its_convolution(impala_torso_gradient,
                                                 impala_cell, forwards=1)


@pytest.mark.slow
def test_the_impala_fused_step_pools_by_the_stored_index(one_chip,
                                                         impala_cell):
    """The same of ``impala_deep_lstm2.anakin``'s whole super-step (~60 s):
    the online pass's later two pools take ops/pool.py's kernels and its
    first stage the stem's; the target network's forward takes the stem's
    forward too, and keeps ``nn.max_pool``'s ``reduce-window`` at the later
    stages, as acting does at all three (64 frames), storing no index; no
    ``reduce-window`` pools the first stage's 7,680 frames."""
    from benchmark.drivers.train import ACTION_DIM
    from r2d2_tpu.learner.anakin import make_anakin_super_step
    from r2d2_tpu.learner.step import create_train_state

    _, sharding = one_chip
    cfg, net, env, (params, *loop) = _fused_cell(
        one_chip, "impala_deep_lstm2.anakin")
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        jax.eval_shape(lambda p: create_train_state(cfg, p), params))
    text = compile_uncached(_lowered_on_tpu(
        lambda: make_anakin_super_step(cfg, net, env, ACTION_DIM),
        state, *loop,
        jax.ShapeDtypeStruct((), jnp.uint32, sharding=sharding))).as_text()
    assert_the_pools_keep_an_index_in_the_step_layout(text, cfg)
    assert_the_stem_never_writes_its_convolution(text, cfg, forwards=2)
    pooled = re.findall(r"= bf16\[([\d,]+)\]\S* reduce-window\(", text)
    assert len(pooled) >= 3
    n = cfg.batch_size * cfg.seq_len
    assert f"{n},42,42,16" not in pooled and f"42,42,16,{n}" not in pooled


# ------------------------------------------------ the fused loop's lane buffers

def _every_step(text):
    """The computations that run on every env step, cut or no cut: all
    that the entry computation reaches through loop bodies, fusions and
    calls and through the FIRST branch of each conditional (a
    ``lax.cond``'s false branch: the identity of a cut that did not
    happen), without the entry computation itself, which runs once a
    dispatch."""
    comps, entry = _computations(text)
    seen, stack = set(), [entry]
    while stack:
        name = stack.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            called = re.findall(
                r"\b(?:calls|body|condition|to_apply|false_computation)"
                r"=%?([\w.\-]+)", line)
            m = re.search(r"branch_computations=\{%?([\w.\-]+)", line)
            if m:
                called.append(m.group(1))
            stack.extend(c for c in called if c in comps)
    return {name: comps[name] for name in seen - {entry}}


def _fused_cell(one_chip, cell):
    """(cfg, net, env, abstract on-chip (params, carry, ring, prios,
    seq_meta, first)) of a fused cell at its real shapes."""
    from benchmark.drivers.train import ACTION_DIM, build_config
    from benchmark.manifest import Manifest
    from r2d2_tpu.envs.anakin import make_anakin_env
    from r2d2_tpu.learner.anakin import make_anakin_state
    from r2d2_tpu.models.network import create_network, init_params
    from r2d2_tpu.replay.device_ring import _ring_shapes

    _, sharding = one_chip
    cfg = build_config(Manifest().cell(cell), False)
    net = create_network(cfg, ACTION_DIM)
    env = make_anakin_env(cfg, ACTION_DIM)

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    key = jax.random.PRNGKey(0)
    NB, K = cfg.num_blocks, cfg.seqs_per_block
    sds = jax.ShapeDtypeStruct
    return cfg, net, env, on_chip((
        jax.eval_shape(lambda k: init_params(cfg, net, k), key),
        jax.eval_shape(
            lambda k: make_anakin_state(cfg, ACTION_DIM, env, k), key),
        {k: sds((NB, *shape), dtype)
         for k, (shape, dtype) in _ring_shapes(cfg, ACTION_DIM).items()},
        sds((NB * K,), jnp.float32), sds((NB, K, 3), jnp.int32),
        sds((NB,), jnp.int32)))


@pytest.mark.parametrize("cell", ["nature_xing4_l5e8h4.anakin",
                                  "impala_deep_lstm2.anakin",
                                  "nature_olmohybrid_l4h4.anakin",
                                  "nature_ouro_l4u4.anakin"])
def test_no_env_step_copies_a_lane_buffer(one_chip, cell):
    """A cut writes only the rows it keeps and a reset follows its cut's
    read, both in place (learner/anakin._retain_prefix, ``_done_cut``):
    so in the cell's 4-step rollout no ``copy`` of the state stream's or
    the frame stream's shape — 186 MB and 199 MB a piece in the ``xing4``
    cell, 2.96 % of its device time before PR 31 (PERF.md Findings) —
    stands in the env-step loop's body or in the identity branch of a
    cut's conditional; nor, in the ``olmo_hybrid`` and ``ouro`` cells, one
    of the snapshot slots' (331 MB and 1.61 GB; the ``ouro`` state is
    snapshots alone, and its rows hold no value).  (~30 s, ~12 s, ~40 s
    and ~30 s.)"""
    from benchmark.drivers.train import ACTION_DIM
    from r2d2_tpu.learner.anakin import make_anakin_rollout

    cfg, net, env, args = _fused_cell(one_chip, cell)
    ast = args[1]
    every_step = _every_step(compile_uncached(make_anakin_rollout(
        cfg, net, env, ACTION_DIM, 4).lower(*args)).as_text())

    names = {"bfloat16": "bf16", "float32": "f32", "uint8": "u8"}
    lines = [(name, line) for name, body in every_step.items()
             for line in body]
    copies = []
    for buf in ("buf_hidden", "buf_obs", "buf_snapshot"):
        # only a state with a part kept whole has snapshots, and only one
        # with a window of rows has rows of any size
        if buf not in ast or not ast[buf].size:
            continue
        shape = "{}[{}]".format(names[ast[buf].dtype.name],
                                ",".join(map(str, ast[buf].shape)))
        assert any(shape in line for _, line in lines), \
            f"{buf} {shape} is not in the env-step loop"
        made = re.compile(rf"= {re.escape(shape)}\S* copy\(")
        copies += [(buf, name, line.split("=")[0].strip())
                   for name, line in lines if made.search(line)]
    assert copies == []


@pytest.mark.slow
def test_the_olmo_hybrid_fused_step_holds_within_the_chip(one_chip):
    """``nature_olmohybrid_l4h4.anakin``'s whole super-step at the ring its
    file names compiles for the described v5e: the compiler itself refuses
    a program that passes the chip's 15.75 GiB (it did, by 499 MB, while
    the stored states were flat vectors that it padded 1.6 times and
    copied whole, PERF.md Findings, PR 35; and it refuses 384 blocks by
    1.53 GB).  Nor does the step copy the ring of stored states or the
    snapshot slots: nothing but a parameter and the loops' own tuples has
    their shapes.  The target network rides the step's k updates in place
    (2.23 GB that the select read and wrote on each of them), and the
    conditional that carries it costs no buffer: the program's temporaries
    are no more than they were under the select.  (~75 s on every core of
    the host: ``slow``, so that tier-1's 3-second rehearsal windows are
    not starved beside it.)"""
    from benchmark.drivers.train import ACTION_DIM
    from r2d2_tpu.learner.anakin import make_anakin_super_step
    from r2d2_tpu.learner.step import create_train_state

    _, sharding = one_chip
    cfg, net, env, (params, *loop) = _fused_cell(
        one_chip, "nature_olmohybrid_l4h4.anakin")
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        jax.eval_shape(lambda p: create_train_state(cfg, p), params))
    compiled = compile_uncached(make_anakin_super_step(
        cfg, net, env, ACTION_DIM).lower(
            state, *loop, jax.ShapeDtypeStruct(
                (), jnp.uint32, sharding=sharding)))
    memory = compiled.memory_analysis()
    assert cfg.num_blocks == 192
    # weights, target, moments, ring and carry: 10.6 GiB of arguments,
    # every byte of which the step hands back in place
    assert 10.4 * 2 ** 30 < memory.argument_size_in_bytes < 10.8 * 2 ** 30
    assert memory.alias_size_in_bytes > 0.999 * memory.argument_size_in_bytes
    text = compiled.as_text()
    for name, arr in (("hidden", loop[1]["hidden"]),
                      ("buf_snapshot", loop[0]["buf_snapshot"])):
        shape = "bf16[{}]".format(",".join(map(str, arr.shape)))
        assert shape in text, (name, shape)
        assert not re.findall(rf"= {re.escape(shape)}\S* copy\(", text), name
    assert_the_target_sync_copies_only_when_it_syncs(text)
    assert memory.temp_size_in_bytes <= 5_985_007_616


@pytest.mark.slow
def test_the_ouro_fused_step_holds_within_the_chip(one_chip):
    """``nature_ouro_l4u4.anakin``'s whole super-step at the ring its file
    names compiles for the described v5e, with neither the ring of stored
    states (192 blocks of 10 states of 2 MiB) nor the lanes' snapshot
    slots (64 lanes of 12) copied, whole or in pieces: nothing but a
    parameter and the loops' own tuples has their shapes, and the batch's
    gather reads its 64 states alone.  Kept as a window of rows a step instead
    (128 KiB a step of every loop and layer), the lanes' streams alone
    are 3.8 GB and the compiler refuses the step by 15 MB; at 320 blocks
    it refuses this one by 463 MB (PERF.md, Findings).  The four
    loops run the four stacked layers through one lowering, so the step
    holds no more than the stack's weights, target and moments.  (~60 s
    on every core of the host: ``slow``, as the ``olmo_hybrid`` cell's.)"""
    from benchmark.drivers.train import ACTION_DIM
    from r2d2_tpu.learner.anakin import make_anakin_super_step
    from r2d2_tpu.learner.step import create_train_state

    _, sharding = one_chip
    cfg, net, env, (params, *loop) = _fused_cell(
        one_chip, "nature_ouro_l4u4.anakin")
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        jax.eval_shape(lambda p: create_train_state(cfg, p), params))
    compiled = compile_uncached(make_anakin_super_step(
        cfg, net, env, ACTION_DIM).lower(
            state, *loop, jax.ShapeDtypeStruct(
                (), jnp.uint32, sharding=sharding)))
    memory = compiled.memory_analysis()
    assert cfg.num_blocks == 192
    # weights, target, moments (3.4 GB), the ring's frames and states
    # (0.61 + 4.03 GB) and the lanes' snapshots (1.61 GB): 9.28 GiB of
    # arguments, every byte of which the step hands back in place
    assert 9.1 * 2 ** 30 < memory.argument_size_in_bytes < 9.5 * 2 ** 30
    assert memory.alias_size_in_bytes > 0.999 * memory.argument_size_in_bytes
    text = compiled.as_text()
    for name, arr in (("hidden", loop[1]["hidden"]),
                      ("buf_snapshot", loop[0]["buf_snapshot"])):
        shape = "bf16[{}]".format(",".join(map(str, arr.shape)))
        assert shape in text, (name, shape)
        assert not re.findall(rf"= {re.escape(shape)}\S* copy\(", text), name
        # nor a piece of either: with heads and head size folded into one
        # minor axis of 4,096 the compiler split the batch's gather into
        # four, each behind a slice of the whole ring of states
        # (`mini-gather-slice`, 4 GB an update on the chip)
        lead = "bf16[{},{},".format(*arr.shape[:2])
        pieces = {m for m in re.findall(rf"{re.escape(lead)}[\d,]*\]", text)
                  if m != shape}
        assert pieces == set(), (name, pieces)
    assert "mini-gather" not in text
    assert_the_target_sync_copies_only_when_it_syncs(text)
