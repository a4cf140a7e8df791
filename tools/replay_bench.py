"""Replay-plane throughput: in-process vs K shm shards vs K socket shards.

The r10 tentpole's go/no-go measurement — does splitting the host replay
plane (ring + sum-tree + batch gather) across ``replay_shards=K`` owner
processes (parallel/replay_shards.py) raise aggregate ingest+sample
throughput past what ONE process's core can do? — extended at r15 with
SOCKET cells (``replay_transport="socket"``, parallel/replay_net.py over
loopback TCP): the same K shards behind the cross-host wire, so the
shm-vs-socket transport tax is measured on identical content.  Three
burst-aligned cells per K ∈ {1, 2, 4} and transport, against the
in-process ReplayBuffer baseline:

- **ingest**: blocks/s from the first ``add`` to the last block
  CONSUMED (sharded cells count shard-side ingestion through the shm
  block channel, not just the route-side memcpy — burst-aligned, so
  queue depth can't flatter the number);
- **sample**: preassembled batches/s over a filled ring (sharded cells
  pay the RPC round trip but fan the gather out across shard cores);
- **combined**: a producer thread ingests continuously while the main
  thread samples — the steady-state contention case the learner
  actually lives in, where the K=1 buffer serialises both on one lock
  and one core.

Blocks are pre-built outside the timed region.  Writes
``artifacts/r15/REPLAY_BENCH_r15.json`` and renders
``docs/perf/REPLAY_r15.md``.  Single-host CPU caveat (the BENCH_r05
convention): this host has few cores AND the socket cells run over
loopback (the kernel's TCP path, not a NIC), so the K-scaling slope is
a floor and the socket tax an upper bound on same-host overhead — the
design point is a many-core replay host feeding an accelerator learner
across a real link.
"""
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from r2d2_tpu.config import Config  # noqa: E402
from r2d2_tpu.models.state import state_spec  # noqa: E402
from r2d2_tpu.parallel.replay_net import NetShardedReplayPlane  # noqa: E402
from r2d2_tpu.parallel.replay_shards import ShardedReplayPlane  # noqa: E402
from r2d2_tpu.replay.block import LocalBuffer  # noqa: E402
from r2d2_tpu.replay.replay_buffer import ReplayBuffer  # noqa: E402

A = 6
PATH = "artifacts/r15/REPLAY_BENCH_r15.json"
DOC = "docs/perf/REPLAY_r15.md"

INGEST_BLOCKS = 192
SAMPLE_BATCHES = 120
COMBINED_SECONDS = 8.0


def bench_cfg(**kw):
    # pong-scale windows over real 84x84 (space-to-depth) frames so the
    # gathers/memcpys are representative; 64 blocks divide by K ∈ {2,4}
    base = dict(game_name="Pong", obs_shape=(84, 84, 1),
                burn_in_steps=40, learning_steps=40, forward_steps=5,
                block_length=80, buffer_capacity=80 * 64, batch_size=64,
                learning_starts=80, replay_sample_timeout=30.0)
    base.update(kw)
    return Config(**base)


def build_blocks(cfg, n, seed=0):
    # obs at the STORED shape (envs apply the space-to-depth fold at
    # emission; the ring only ever sees stored_obs_shape)
    rng = np.random.default_rng(seed)
    out = []
    local = LocalBuffer(cfg, A)
    for b in range(n):
        local.reset(rng.integers(0, 256, cfg.stored_obs_shape, np.uint8))
        for s in range(cfg.block_length):
            local.add(int(rng.integers(A)), float(rng.normal()),
                      rng.integers(0, 256, cfg.stored_obs_shape, np.uint8),
                      rng.normal(size=A).astype(np.float32),
                      rng.normal(size=state_spec(cfg)[0]).astype(
                          state_spec(cfg)[1]))
        block, prios, ep = local.finish(None)
        out.append((block, prios, ep))
    return out


class _InprocPlane:
    """The baseline behind the same mini-interface the cells drive."""

    def __init__(self, cfg):
        self.buf = ReplayBuffer(cfg, A, rng=np.random.default_rng(0))

    def add(self, block, prios, ep):
        self.buf.add(block, prios, ep)

    def consumed_blocks(self):
        # in-process add() is synchronous: consumed == added
        return None

    def sample(self, B):
        return self.buf.sample_batch(B)

    def close(self):
        pass


class _ShardPlaneCell:
    def __init__(self, cfg):
        self.plane = ShardedReplayPlane(cfg, A,
                                        rng=np.random.default_rng(0))
        self.plane.start()

    def add(self, block, prios, ep):
        self.plane.add(block, prios, ep)

    def consumed_blocks(self):
        t = self.plane.poll_shard_stats()["totals"]
        return int(t.get("blocks", 0))

    def sample(self, B):
        out = self.plane.sample_batch(B)
        assert out is not None
        return out

    def close(self):
        self.plane.shutdown()


class _NetPlaneCell:
    """The socket plane over managed loopback shards — the identical
    content through real TCP frames (encode + kernel loopback + decode
    + frame CRC both ways), so shm-vs-socket is a pure transport A/B."""

    def __init__(self, cfg):
        self.plane = NetShardedReplayPlane(cfg, A,
                                           rng=np.random.default_rng(0))
        self.plane.start()

    def add(self, block, prios, ep):
        self.plane.add(block, prios, ep)

    def consumed_blocks(self):
        t = self.plane.poll_shard_stats()["totals"]
        return int(t.get("blocks", 0))

    def sample(self, B):
        out = self.plane.sample_batch(B)
        if out is None:            # a transient redistribution round
            out = self.plane.sample_batch(B)
        assert out is not None
        return out

    def close(self):
        self.plane.shutdown()


def run_cell(name, make_plane, cfg, blocks):
    plane = make_plane(cfg)
    try:
        # --- ingest burst: first add → last block CONSUMED ------------
        t0 = time.perf_counter()
        for i in range(INGEST_BLOCKS):
            plane.add(*blocks[i % len(blocks)])
        if plane.consumed_blocks() is not None:
            while plane.consumed_blocks() < INGEST_BLOCKS:
                time.sleep(0.002)
        ingest_s = time.perf_counter() - t0
        # --- sample burst over the (now full) ring --------------------
        t0 = time.perf_counter()
        for _ in range(SAMPLE_BATCHES):
            plane.sample(cfg.batch_size)
        sample_s = time.perf_counter() - t0
        # --- combined: continuous ingest thread + sampling main thread
        stop = threading.Event()
        added = [0]

        def producer():
            i = 0
            while not stop.is_set():
                plane.add(*blocks[i % len(blocks)])
                added[0] += 1
                i += 1

        th = threading.Thread(target=producer, daemon=True)  # graftlint: disable=thread-discipline -- bounded measured bench producer, stop-event + joined before the cell exits
        th.start()
        batches = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < COMBINED_SECONDS:
            plane.sample(cfg.batch_size)
            batches += 1
        combined_s = time.perf_counter() - t0
        stop.set()
        th.join(10.0)
        cell = dict(
            cell=name,
            ingest_blocks_per_sec=round(INGEST_BLOCKS / ingest_s, 1),
            sample_batches_per_sec=round(SAMPLE_BATCHES / sample_s, 1),
            combined_sample_batches_per_sec=round(batches / combined_s, 1),
            combined_ingest_blocks_per_sec=round(added[0] / combined_s, 1),
        )
        print(json.dumps(cell), flush=True)
        return cell
    finally:
        plane.close()


def render_doc(data):
    lines = [
        "# Replay plane — r15: in-process vs K shm shards vs K socket "
        "shards",
        "",
        f"Host: {data['host_cpus']} CPUs (single-host CPU caveat, the "
        "BENCH_r05 convention: with this few cores the K-scaling slope "
        "is a floor, not the design point — the plane exists so replay "
        "capacity and sampling throughput scale past one process's "
        "memory and cores on a many-core host feeding an accelerator "
        "learner).  The socket cells run the cross-host fabric "
        "(parallel/replay_net.py) over LOOPBACK, so their tax is the "
        "frame encode/CRC/kernel-TCP path with zero propagation delay — "
        "an upper bound on same-host overhead and a lower bound on "
        "nothing: a real link adds wire latency the pipelined draw must "
        "hide.",
        "",
        f"Burst-aligned cells: ingest = {data['ingest_blocks']} "
        "pre-built pong-scale blocks (80 steps, 84×84 frames), first "
        "add → last block *consumed*; sample = "
        f"{data['sample_batches']} batch-64 draws; combined = "
        "continuous producer thread + sampling main thread for "
        f"{data['combined_seconds']} s (the steady-state contention "
        "case).",
        "",
        "| cell | ingest blocks/s | sample batches/s | combined "
        "batches/s | combined ingest blocks/s |",
        "|---|---|---|---|---|",
    ]
    for r in data["results"]:
        lines.append(
            f"| {r['cell']} | {r['ingest_blocks_per_sec']} "
            f"| {r['sample_batches_per_sec']} "
            f"| {r['combined_sample_batches_per_sec']} "
            f"| {r['combined_ingest_blocks_per_sec']} |")
    by = {r["cell"]: r for r in data["results"]}
    base = by.get("inprocess")
    if base:
        lines += ["", "## combined-cell aggregate vs in-process", ""]
        for name, r in by.items():
            if name == "inprocess":
                continue
            agg = (r["combined_sample_batches_per_sec"]
                   / max(1e-9, base["combined_sample_batches_per_sec"]))
            ing = (r["combined_ingest_blocks_per_sec"]
                   / max(1e-9, base["combined_ingest_blocks_per_sec"]))
            lines.append(f"- {name}: sample {agg:.2f}x, ingest {ing:.2f}x")
    k1, k2 = by.get("sharded_k1"), by.get("sharded_k2")
    if k1 and k2:
        lines += ["", "## K-slope within the sharded family (K=1 → K=2)",
                  ""]
        for key, label in (
                ("sample_batches_per_sec", "sample burst"),
                ("combined_sample_batches_per_sec", "combined sample"),
                ("combined_ingest_blocks_per_sec", "combined ingest")):
            lines.append(f"- {label}: "
                         f"{k2[key] / max(1e-9, k1[key]):.2f}x")
    # shm → socket at matched K: the transport tax on identical content
    taxes = [(K, by.get(f"sharded_k{K}"), by.get(f"socket_k{K}"))
             for K in (1, 2, 4)]
    if any(shm and sock for _, shm, sock in taxes):
        lines += ["", "## Socket tax at matched K (shm → socket, same "
                      "content)", ""]
        for K, shm, sock in taxes:
            if not (shm and sock):
                continue
            lines.append(
                f"- K={K}: sample burst "
                f"{sock['sample_batches_per_sec'] / max(1e-9, shm['sample_batches_per_sec']):.2f}x, "
                f"combined sample "
                f"{sock['combined_sample_batches_per_sec'] / max(1e-9, shm['combined_sample_batches_per_sec']):.2f}x, "
                f"combined ingest "
                f"{sock['combined_ingest_blocks_per_sec'] / max(1e-9, shm['combined_ingest_blocks_per_sec']):.2f}x")
        lines += [
            "",
            "The socket cells pay, per batch, one ~`B·T·obs`-sized "
            "frame encode (a full payload copy), a CRC32 over it on "
            "EACH side, and the kernel loopback TCP path — where the "
            "shm plane hands the trainer a zero-copy slab view.  Per "
            "ingest they pay the same for a ~1 MB block frame.  On a "
            "2-core host every one of those cycles is stolen from the "
            "shards themselves, so treat the socket numbers as the "
            "worst-case tax: the design point is shards on OTHER "
            "hosts' cores, where the tax buys horizontal capacity and "
            "the pipelined draw (two requests in flight per link) "
            "hides one rtt behind the learner's consume.  Honest "
            "limits of this measurement: loopback (no real NIC/wire "
            "latency), fixed-size response frames (a short-serving "
            "shard ships full geometry), and 2 cores under-subscribe "
            "every K>1 cell.",
        ]
    lines += [
        "",
        "Reading: the sharded cells pay a fixed coordination tax per "
        "batch — one RPC round trip, a second block memcpy per ingest, "
        "and the trainer-side response-CRC verify + slab→batch copy — "
        "in exchange for moving the gathers, sum-tree work and ingest "
        "copies onto OTHER processes' cores (the trainer thread only "
        "concatenates K preassembled slab views).  On this CPU-share-"
        "throttled ~2-core host the tax dominates: the in-process "
        "baseline stays faster in absolute terms, the K=1→K=2 slope "
        "within the sharded family is the (weak, positive) scaling "
        "signal, and K=4 oversubscribes the cores outright.  The "
        "number to re-measure on a many-core host is the combined "
        "cell's K-slope — that is where capacity and throughput scale "
        "past one process, which is the feature's design point.",
        "",
    ]
    return "\n".join(lines)


def main():
    if "--render" in sys.argv[1:]:
        # re-render the doc from the committed artifact (no remeasure)
        with open(PATH) as f:
            data = json.load(f)
        with open(DOC, "w") as f:
            f.write(render_doc(data))
        print(f"→ {DOC}", flush=True)
        return
    cfg1 = bench_cfg(replay_shards=1)
    print("building blocks...", flush=True)
    blocks = build_blocks(cfg1, 64)
    results = [run_cell("inprocess", _InprocPlane, cfg1, blocks)]
    for K in (1, 2, 4):
        cfg = bench_cfg(replay_shards=K)
        results.append(run_cell(f"sharded_k{K}", _ShardPlaneCell, cfg,
                                blocks))
    for K in (1, 2, 4):
        cfg = bench_cfg(replay_shards=K, replay_transport="socket",
                        replay_net_send_budget=30.0)
        results.append(run_cell(f"socket_k{K}", _NetPlaneCell, cfg,
                                blocks))
    data = dict(host_cpus=os.cpu_count() or 0,
                ingest_blocks=INGEST_BLOCKS,
                sample_batches=SAMPLE_BATCHES,
                combined_seconds=COMBINED_SECONDS,
                batch_size=cfg1.batch_size,
                block_length=cfg1.block_length,
                measure="burst-aligned (ingest timed to last consumed "
                        "block; blocks pre-built outside the timed "
                        "region)",
                results=results)
    os.makedirs(os.path.dirname(PATH), exist_ok=True)
    with open(PATH, "w") as f:
        json.dump(data, f, indent=1)
    print(f"→ {PATH}", flush=True)
    os.makedirs(os.path.dirname(DOC), exist_ok=True)
    with open(DOC, "w") as f:
        f.write(render_doc(data))
    print(f"→ {DOC}", flush=True)


if __name__ == "__main__":
    main()
