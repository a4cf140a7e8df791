"""Session-serving tier (ISSUE 11 tentpole, r2d2_tpu/serving): the
SessionStore's LRU/reap/snapshot edge cases, the wire format's CRC
discipline, the continuous batcher's bucket shaping (bit-exact vs the
direct act fn, retrace-budgeted), the server's lifecycle/admission
behaviour over a real loopback socket, quantized-serving greedy parity,
restart-with-restore, and the load-gen acceptance e2e (hundreds of
concurrent sessions, accounting conserved, p99 on /metrics).

Everything runs tier-1-safe under ``JAX_PLATFORMS=cpu`` on the tiny
test-config geometry; waits poll with deadlines, never bare sleeps.
"""
import contextlib
import importlib.util
import json
import os
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from r2d2_tpu.actor import make_act_fn
from r2d2_tpu.checkpoint import Checkpointer
from r2d2_tpu.config import test_config as make_test_config
from r2d2_tpu.models.network import create_network, init_params
from r2d2_tpu.serving import (
    ContinuousBatcher,
    SessionClient,
    SessionServer,
    SessionStore,
    bucket_sizes,
)
from r2d2_tpu.serving.wire import (
    EMPTY_SPEC,
    FLAG_RESET,
    MSG_ACT,
    MSG_RSP,
    STATUS_GONE,
    STATUS_OK,
    STATUS_SHED,
    WireGarbled,
    decode_frame,
    encode_frame,
    peek_kind,
    session_request_spec,
)

A = 4


def _cfg(**kw):
    base = dict(serve_max_sessions=8, serve_max_batch=8,
                serve_session_idle_s=30.0)
    base.update(kw)
    return make_test_config(**base)


def _net_params(cfg, seed=0):
    net = create_network(cfg, A)
    return net, init_params(cfg, net, jax.random.PRNGKey(seed))


@contextlib.contextmanager
def _server(cfg, params, start=True):
    srv = SessionServer(cfg, A)
    srv.publish_params(params)
    if start:
        srv.start()
    try:
        yield srv
    finally:
        srv.stop()
        srv.close()


def _poll(predicate, budget=20.0, step=0.01, msg="condition"):
    """Poll-with-deadline (the test_chaos deflake pattern): never a bare
    sleep-then-assert."""
    deadline = time.time() + budget
    while time.time() < deadline:
        if predicate():
            return
        time.sleep(step)
    raise AssertionError(f"timed out waiting for: {msg}")


def _assert_accounting(counts):
    assert counts["admitted"] == (counts["completed"] + counts["reaped"]
                                  + counts["evicted"] + counts["live"]), \
        counts


# ------------------------------------------------------------ SessionStore

def test_store_lru_eviction_order_respects_reuse():
    """LRU under reuse: touching (gathering for) a session moves it to
    the back of the eviction order, so the victim is the genuinely
    least-recently-used one."""
    store = SessionStore(_cfg(serve_max_sessions=3))
    for sid in (1, 2, 3):
        assert store.admit(sid)[0] == "ok"
    # touch 1: eviction order becomes 2, 3, 1
    store.gather([1], np.array([False]))
    verdict, victim = store.admit(4)
    assert (verdict, victim) == ("ok", 2)
    verdict, victim = store.admit(5)
    assert (verdict, victim) == ("ok", 3)
    assert store.counts()["evicted"] == 2
    _assert_accounting(store.counts())


def test_store_never_evicts_pending_sessions():
    """Evict-while-pending is the one corruption the store must never
    emit (the request would act on a zeroed slot): in-flight sessions
    are skipped by the LRU scan, and a store full of in-flight sessions
    sheds the admit instead."""
    store = SessionStore(_cfg(serve_max_sessions=2))
    assert store.admit(1)[0] == "ok"
    assert store.admit(2)[0] == "ok"
    assert store.mark_pending(1) and store.mark_pending(2)
    assert store.admit(3) == ("shed", None)          # nothing evictable
    store.clear_pending(2)
    # 1 is older but pinned; the scan must skip it and take 2
    assert store.admit(3) == ("ok", 2)
    assert store.mark_pending(1)                     # still live
    _assert_accounting(store.counts())


def test_store_idle_reap_vs_active_race():
    """The idle reaper must never take a session that is active (fresh
    last_used) or in flight (pending pin) — the race goes to the active
    side; a genuinely idle one goes."""
    store = SessionStore(_cfg(serve_max_sessions=4))
    for sid in (1, 2, 3):
        store.admit(sid, now=0.0)
    store.gather([1], np.array([False]), now=100.0)   # 1 is active
    store.mark_pending(2)                             # 2 is in flight
    reaped = store.reap_idle(10.0, now=101.0)
    assert reaped == [3]
    c = store.counts()
    assert c["reaped"] == 1 and c["live"] == 2
    _assert_accounting(c)
    # after the reply lands, 2 becomes reapable (1 is still fresh)
    store.clear_pending(2)
    assert store.reap_idle(10.0, now=105.0) == [2]
    assert store.counts()["live"] == 1
    _assert_accounting(store.counts())


def test_store_snapshot_restore_with_evicted_and_live_sessions():
    """Snapshot a store holding live sessions AND an eviction history;
    the restore must bring the hidden rows back bit-exact and carry the
    lifetime counters so the accounting invariant spans the restart."""
    cfg = _cfg(serve_max_sessions=2, lstm_layers=1, hidden_dim=16)
    store = SessionStore(cfg)
    rng = np.random.default_rng(0)
    store.admit(1)
    store.admit(2)
    h = rng.normal(size=(2, 2, cfg.lstm_layers, cfg.hidden_dim)
                   ).astype(np.float32)
    store.scatter([1, 2], h)
    assert store.admit(3) == ("ok", 1)   # evict 1; history now non-trivial
    store.scatter([3], h[:1] * 2.0)
    store.release(2, "completed")
    store.admit(4)
    snap = store.state()

    fresh = SessionStore(cfg)
    fresh.load_state(snap)
    assert fresh.counts() == store.counts()
    _assert_accounting(fresh.counts())
    # hidden rows bit-exact for the live sessions (3 carries its state)
    _, got = fresh.gather([3], np.array([False]))
    np.testing.assert_array_equal(got[0], h[0] * 2.0)
    # steps metadata survived too
    assert fresh.session_steps(3) == store.session_steps(3)
    # geometry mismatch fails loudly instead of loading garbage
    with pytest.raises(ValueError, match="does not match"):
        SessionStore(_cfg(serve_max_sessions=2, hidden_dim=32)
                     ).load_state(snap)


def test_store_reap_owner_and_adopt():
    store = SessionStore(_cfg())
    store.admit(1, owner=7)
    store.admit(2, owner=7)
    store.admit(3, owner=8)
    assert sorted(store.reap_owner(7)) == [1, 2]
    c = store.counts()
    assert c["reaped"] == 2 and c["live"] == 1
    # restored sessions are owner-less until adopted
    snap = store.state()
    fresh = SessionStore(_cfg())
    fresh.load_state(snap)
    assert fresh.reap_owner(8) == []     # old owner id means nothing now
    fresh.adopt(3, 9)
    assert fresh.reap_owner(9) == [3]
    _assert_accounting(fresh.counts())


# ------------------------------------------------------------- wire format

def test_wire_roundtrip_and_crc_gate():
    cfg = _cfg()
    spec = session_request_spec(cfg, A)
    rng = np.random.default_rng(1)
    obs = rng.integers(0, 256, cfg.stored_obs_shape).astype(np.uint8)
    la = rng.random(A).astype(np.float32)
    frame = encode_frame(spec, (MSG_ACT, 42, 7, FLAG_RESET),
                         dict(obs=obs, last_action=la,
                              last_reward=np.asarray([0.5], np.float32)))
    body = frame[4:]                      # strip the length word
    assert peek_kind(body) == MSG_ACT
    header, views = decode_frame(spec, body)
    assert header == (MSG_ACT, 42, 7, FLAG_RESET)
    np.testing.assert_array_equal(views["obs"], obs)
    np.testing.assert_array_equal(views["last_action"], la)
    assert views["last_reward"][0] == np.float32(0.5)
    # flip one payload byte AFTER the CRC landed: the gate must catch it
    garbled = bytearray(body)
    garbled[40] ^= 0xFF
    with pytest.raises(WireGarbled):
        decode_frame(spec, bytes(garbled))
    # a header garble (kind/session words) is caught too
    garbled = bytearray(body)
    garbled[0] ^= 0x01
    with pytest.raises(WireGarbled):
        decode_frame(spec, bytes(garbled))
    # payload-free frames round-trip as well
    f2 = encode_frame(EMPTY_SPEC, (MSG_RSP, 42, 7, STATUS_SHED))
    header, views = decode_frame(EMPTY_SPEC, f2[4:])
    assert header == (MSG_RSP, 42, 7, STATUS_SHED) and views == {}


# ---------------------------------------------------------------- batcher

def test_bucket_sizes_cover_and_cap():
    assert bucket_sizes(1) == (1,)
    assert bucket_sizes(8) == (1, 2, 4, 8)
    assert bucket_sizes(6) == (1, 2, 4, 6)
    assert bucket_sizes(256)[-1] == 256 and len(bucket_sizes(256)) == 9


def test_batcher_bucket_padding_bit_exact_and_retrace_budget():
    """The tier's core numeric invariant: a ragged batch served through
    bucket padding is BIT-EXACT vs the direct act fn on the exact rows
    (row-wise network math is batch-size independent), and driving every
    bucket stays inside the declared retrace budget."""
    from r2d2_tpu.utils.trace import RETRACES

    cfg = _cfg(serve_max_batch=8)
    net, params = _net_params(cfg)
    b = ContinuousBatcher(cfg, A)
    b.publish(params)
    # the REFERENCE fn is deliberately traced once per ragged size (the
    # very cost bucket shaping exists to avoid) — budget it accordingly
    act = make_act_fn(cfg, net, retrace_budget=8)
    rng = np.random.default_rng(0)
    for n in (1, 3, 5, 8):
        obs = rng.integers(0, 256,
                           (n, *cfg.stored_obs_shape)).astype(np.uint8)
        la = rng.random((n, A)).astype(np.float32)
        lr = rng.random(n).astype(np.float32)
        h = (rng.normal(size=(n, 2, cfg.lstm_layers, cfg.hidden_dim))
             * 0.1).astype(np.float32)
        q1, h1 = b.act(obs, la, lr, h)
        q2, h2 = act(params, obs, la, lr, h)
        np.testing.assert_array_equal(q1, np.asarray(q2))
        np.testing.assert_array_equal(h1, np.asarray(h2))
    with pytest.raises(ValueError, match="exceeds serve_max_batch"):
        b.bucket(9)
    RETRACES.assert_within_budgets()


def test_batcher_acts_where_its_params_live(monkeypatch):
    """ISSUE 21 finding 2: the session tier must resolve its act net for
    the device its params are committed to.  The training actors' knob
    (``cfg.act_device``: "auto" = a host-CPU twin) is never consulted —
    the old code built that CPU twin and then ran it on the accelerator
    the params were committed to."""
    import r2d2_tpu.actor as actor_mod

    home = jax.local_devices()[3]   # "the first local device", movable
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: [home])

    def never(spec):
        raise AssertionError("the session tier consulted cfg.act_device")

    monkeypatch.setattr(actor_mod, "resolve_act_device", never)
    for spec in ("auto", "cpu", "default"):
        cfg = _cfg(act_device=spec)
        _, params = _net_params(cfg)
        b = ContinuousBatcher(cfg, A)
        assert b._act.device == home
        assert b.act_info()["act_platform"] is None   # nothing acted yet
        b.publish(jax.device_get(params))             # a host tree
        assert {d for leaf in jax.tree.leaves(b._params)
                for d in leaf.devices()} == {home}
        n = 3
        q, h = b.act(np.zeros((n, *cfg.stored_obs_shape), np.uint8),
                     np.zeros((n, A), np.float32), np.zeros(n, np.float32),
                     np.zeros((n, 2, cfg.lstm_layers, cfg.hidden_dim),
                              np.float32))
        assert q.shape == (n, A) and np.isfinite(q).all()
        # observed from the output array, and the net resolved for it
        assert b.act_info() == dict(act_platform=home.platform,
                                    act_lstm_impl="scan",
                                    act_compute_dtype=cfg.compute_dtype)


def test_batcher_act_under_armed_transfer_guard():
    """The serve path's declared-transfer contract, JAX-enforced (r19):
    after warm-up, ``act()`` runs inside ``disallow("serving.act")`` —
    the padded-scratch H2D rides the ``serving.act_put`` allow span and
    the ONE result fetch is an explicit ``jax.device_get`` inside
    ``serving.act_fetch``.  Results stay bit-exact vs the unarmed path,
    one fetch per batch regardless of ragged size, zero trips."""
    from r2d2_tpu.utils.trace import HOST_TRANSFERS, TRANSFER_GUARD

    cfg = _cfg(serve_max_batch=8)
    net, params = _net_params(cfg)
    b = ContinuousBatcher(cfg, A)
    b.publish(params)
    b.warmup()  # every bucket compiled before arming

    rng = np.random.default_rng(7)
    batches = []
    for n in (1, 3, 8):
        batches.append((
            rng.integers(0, 256,
                         (n, *cfg.stored_obs_shape)).astype(np.uint8),
            rng.random((n, A)).astype(np.float32),
            rng.random(n).astype(np.float32),
            (rng.normal(size=(n, 2, cfg.lstm_layers, cfg.hidden_dim))
             * 0.1).astype(np.float32)))
    unarmed = [b.act(*args) for args in batches]

    fetch0 = HOST_TRANSFERS.get("serving.act_fetch")
    with TRANSFER_GUARD.arm():
        armed = [b.act(*args) for args in batches]
    assert HOST_TRANSFERS.get("serving.act_fetch") - fetch0 \
        == len(batches)
    snap = TRANSFER_GUARD.snapshot()
    assert snap.get("trip.serving.act", 0) == 0, snap
    assert snap.get("window.serving.act", 0) >= len(batches)
    for (q1, h1), (q2, h2) in zip(unarmed, armed):
        np.testing.assert_array_equal(q1, q2)
        np.testing.assert_array_equal(h1, h2)


def test_serve_dtype_bf16_quantizes_with_greedy_parity():
    """QuaRL gate (the param_pump_dtype pattern on the serving tier):
    bf16 publish must actually quantize (params differ) while greedy
    actions on a pinned request stream match float32 exactly."""
    cfg32 = _cfg(serve_max_batch=8)
    cfg16 = _cfg(serve_max_batch=8, serve_dtype="bfloat16")
    _, params = _net_params(cfg32)
    b32 = ContinuousBatcher(cfg32, A)
    b32.publish(params)
    b16 = ContinuousBatcher(cfg16, A)
    b16.publish(params)
    # the quantization is real: at least one leaf changed
    l32 = jax.tree.leaves(b32._params)
    l16 = jax.tree.leaves(b16._params)
    assert any(not np.array_equal(np.asarray(a), np.asarray(x))
               for a, x in zip(l32, l16))
    rng = np.random.default_rng(7)
    n = 8
    obs = rng.integers(0, 256, (n, *cfg32.stored_obs_shape)
                       ).astype(np.uint8)
    la = np.zeros((n, A), np.float32)
    lr = np.zeros(n, np.float32)
    h = (rng.normal(size=(n, 2, cfg32.lstm_layers, cfg32.hidden_dim))
         * 0.1).astype(np.float32)
    q32, _ = b32.act(obs, la, lr, h)
    q16, _ = b16.act(obs, la, lr, h)
    np.testing.assert_allclose(q32, q16, atol=5e-2, rtol=5e-2)
    np.testing.assert_array_equal(q32.argmax(axis=1), q16.argmax(axis=1))


# ------------------------------------------------------------------ server

def test_server_sessions_bit_exact_vs_local_act():
    """Two interleaved sessions driven over the real socket must produce
    the exact q stream a client-side unrolled act fn produces — the
    session-resident hidden is carried server-side bit-exact, episode
    resets included."""
    cfg = _cfg()
    net, params = _net_params(cfg)
    act = make_act_fn(cfg, net)
    rng = np.random.default_rng(3)
    steps = 6
    streams = {sid: [rng.integers(0, 256, cfg.stored_obs_shape
                                  ).astype(np.uint8) for _ in range(steps)]
               for sid in (1, 2)}
    with _server(cfg, params) as srv:
        cl = SessionClient(cfg, A, srv.host, srv.port, timeout=30)
        try:
            ref_hidden = {sid: np.zeros(
                (1, 2, cfg.lstm_layers, cfg.hidden_dim), np.float32)
                for sid in (1, 2)}
            la = {sid: np.zeros(A, np.float32) for sid in (1, 2)}
            assert cl.open_session(1) == STATUS_OK
            assert cl.open_session(2) == STATUS_OK
            for t in range(steps):
                for sid in (1, 2):
                    obs = streams[sid][t]
                    st, q = cl.act(sid, obs, la[sid], 0.125 * t,
                                   reset=t == 0)
                    assert st == STATUS_OK
                    if t == 0:
                        ref_hidden[sid][:] = 0.0
                    qr, hr = act(params, obs[None], la[sid][None],
                                 np.asarray([0.125 * t], np.float32),
                                 ref_hidden[sid])
                    np.testing.assert_array_equal(q, np.asarray(qr)[0])
                    ref_hidden[sid] = np.asarray(hr)
                    la[sid] = np.zeros(A, np.float32)
                    la[sid][int(np.argmax(q))] = 1.0
            # the server-resident hidden equals the client-side unroll
            _, got = srv.store.gather([1, 2], np.array([False, False]))
            np.testing.assert_array_equal(got[0], ref_hidden[1][0])
            np.testing.assert_array_equal(got[1], ref_hidden[2][0])
            assert cl.close_session(1) == STATUS_OK
            assert cl.close_session(2) == STATUS_OK
        finally:
            cl.close()
        _assert_accounting(srv.store.counts())


def test_server_eviction_answers_gone_then_reopen():
    """LRU eviction under a budget of 1: the evicted session's next act
    answers STATUS_GONE (never an act on a zeroed slot); a re-open
    readmits it fresh."""
    cfg = _cfg(serve_max_sessions=1)
    _, params = _net_params(cfg)
    obs = np.zeros(cfg.stored_obs_shape, np.uint8)
    la = np.zeros(A, np.float32)
    with _server(cfg, params) as srv:
        cl = SessionClient(cfg, A, srv.host, srv.port, timeout=30)
        try:
            assert cl.open_session(1) == STATUS_OK
            st, _ = cl.act(1, obs, la, 0.0, reset=True)
            assert st == STATUS_OK
            assert cl.open_session(2) == STATUS_OK    # evicts idle 1
            st, _ = cl.act(1, obs, la, 0.0)
            assert st == STATUS_GONE
            assert cl.open_session(1) == STATUS_OK    # evicts 2, readmits
            st, _ = cl.act(1, obs, la, 0.0, reset=True)
            assert st == STATUS_OK
            c = srv.store.counts()
            assert c["evicted"] == 2
            _assert_accounting(c)
            assert srv.registry.get_counter("serving.gone") >= 1
        finally:
            cl.close()


def test_server_bounded_queue_sheds_429():
    """The bounded pending queue: with the batch loop held still and
    serve_pending_max=1, a second pipelined act sheds IMMEDIATELY with
    STATUS_SHED (counted in serving.rejected) — the client never waits
    on a queue that cannot drain."""
    cfg = _cfg(serve_pending_max=1)
    _, params = _net_params(cfg)
    with _server(cfg, params, start=False) as srv:
        # readers only — serve_once is driven by hand, so the queue
        # genuinely backs up
        srv._started = True
        srv.supervisor.start("session_accept", srv._accept_loop)
        cl = SessionClient(cfg, A, srv.host, srv.port, timeout=30)
        try:
            assert cl.open_session(1) == STATUS_OK
            assert cl.open_session(2) == STATUS_OK
            obs = np.zeros(cfg.stored_obs_shape, np.uint8)
            la = np.zeros(A, np.float32)
            s1 = cl.send_act(1, obs, la, 0.0, reset=True)
            s2 = cl.send_act(2, obs, la, 0.0, reset=True)
            # the second act overflows the bound and sheds now
            st2, _ = cl.recv(2, s2)
            assert st2 == STATUS_SHED
            assert srv.registry.get_counter("serving.rejected") == 1
            # the queued one serves once the batch loop turns
            assert srv.serve_once(idle_sleep=0.0) == 1
            st1, q = cl.recv(1, s1)
            assert st1 == STATUS_OK and q is not None
            assert srv.healthz()["status"] == "degraded"   # shed window
        finally:
            cl.close()


def test_server_disconnect_reaps_sessions():
    """kill_session_client shape: an abrupt disconnect mid-episode must
    reap every session the connection owned — hidden slots never leak."""
    cfg = _cfg()
    _, params = _net_params(cfg)
    with _server(cfg, params) as srv:
        cl = SessionClient(cfg, A, srv.host, srv.port, timeout=30)
        assert cl.open_session(1) == STATUS_OK
        assert cl.open_session(2) == STATUS_OK
        obs = np.zeros(cfg.stored_obs_shape, np.uint8)
        la = np.zeros(A, np.float32)
        st, _ = cl.act(1, obs, la, 0.0, reset=True)
        assert st == STATUS_OK
        cl.abandon()
        _poll(lambda: srv.store.counts()["reaped"] == 2,
              msg="disconnect reap")
        c = srv.store.counts()
        assert c["live"] == 0
        _assert_accounting(c)
        assert srv.healthz()["status"] in ("ok", "degraded")


def test_server_idle_reap_frees_abandoned_sessions():
    cfg = _cfg(serve_session_idle_s=0.2)
    _, params = _net_params(cfg)
    with _server(cfg, params) as srv:
        cl = SessionClient(cfg, A, srv.host, srv.port, timeout=30)
        try:
            assert cl.open_session(1) == STATUS_OK
            obs = np.zeros(cfg.stored_obs_shape, np.uint8)
            st, _ = cl.act(1, obs, np.zeros(A, np.float32), 0.0,
                           reset=True)
            assert st == STATUS_OK
            # stop sending; the reaper must claim the session (the
            # connection stays open — idle, not disconnected)
            _poll(lambda: srv.store.counts()["reaped"] == 1,
                  msg="idle reap")
            _assert_accounting(srv.store.counts())
        finally:
            cl.close()


def test_server_restart_restores_sessions_bit_exact(tmp_path):
    """Restart-with-restore: k steps, snapshot through the Checkpointer,
    a FRESH server restores, the client reconnects and continues by
    session id — the q stream must equal an uninterrupted run's."""
    cfg = _cfg()
    _, params = _net_params(cfg)
    rng = np.random.default_rng(5)
    steps = 8
    stream = [rng.integers(0, 256, cfg.stored_obs_shape).astype(np.uint8)
              for _ in range(steps)]
    la = np.zeros(A, np.float32)

    def drive(cl, lo, hi, last_action):
        out = []
        for t in range(lo, hi):
            st, q = cl.act(1, stream[t], last_action, 0.0, reset=t == 0)
            assert st == STATUS_OK
            out.append(np.array(q))
            last_action = np.zeros(A, np.float32)
            last_action[int(np.argmax(q))] = 1.0
        return out, last_action

    # uninterrupted reference
    with _server(cfg, params) as srv:
        cl = SessionClient(cfg, A, srv.host, srv.port, timeout=30)
        assert cl.open_session(1) == STATUS_OK
        want, _ = drive(cl, 0, steps, la)
        cl.close()

    # interrupted: serve, snapshot at the midpoint, restore, continue
    ckpt = Checkpointer(str(tmp_path))
    with _server(cfg, params) as srv:
        cl = SessionClient(cfg, A, srv.host, srv.port, timeout=30)
        assert cl.open_session(1) == STATUS_OK
        got, la_mid = drive(cl, 0, steps // 2, la)
        # shutdown order matters: stop FIRST so the connection teardown
        # is a server shutdown (sessions survive into the snapshot), not
        # a client abandon (which would reap them)
        srv.stop()
        srv.close()
        cl.close()
        meta = srv.save_sessions(ckpt)
        assert meta["live"] == 1
    with _server(cfg, params, start=False) as srv2:
        assert srv2.restore_sessions(ckpt)
        srv2.start()
        cl = SessionClient(cfg, A, srv2.host, srv2.port, timeout=30)
        more, _ = drive(cl, steps // 2, steps, la_mid)
        got += more
        cl.close()
        # a reconnect binds the restored session to the new connection,
        # so its disconnect reaps normally (no leaked slot)
        _poll(lambda: srv2.store.counts()["live"] == 0,
              msg="restored session reaped on disconnect")
        _assert_accounting(srv2.store.counts())
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)

    # no snapshot at all → clean cold start
    empty = Checkpointer(str(tmp_path / "empty"))
    with _server(cfg, params, start=False) as srv3:
        assert not srv3.restore_sessions(empty)


# ----------------------------------------------------------- chaos kinds

def test_session_chaos_kinds_parse_and_fire():
    from r2d2_tpu.utils.chaos import ChaosInjector, parse_spec

    spec = "kill_session_client:at=2;slow_session_client:at=1,dur=0.5"
    assert set(parse_spec(spec)) == {"kill_session_client",
                                     "slow_session_client"}
    chaos = ChaosInjector(spec)
    assert chaos.session_client_slow_seconds() == 0.5
    assert chaos.session_client_slow_seconds() == 0.0   # at=1: once
    assert not chaos.session_client_kill()
    assert chaos.session_client_kill()                  # opportunity 2
    assert not chaos.session_client_kill()
    # config validation accepts the new kinds
    make_test_config(chaos_spec=spec)


# ------------------------------------------------------------- validation

def test_serve_config_validation():
    for bad in (dict(serve_dtype="int8"), dict(serve_max_sessions=0),
                dict(serve_max_batch=0), dict(serve_session_idle_s=0.0),
                dict(serve_pending_max=0),
                dict(serve_request_deadline=0.0),
                dict(serve_port=65536)):
        with pytest.raises(ValueError):
            make_test_config(**bad)
    cfg = make_test_config(serve_dtype="bfloat16", serve_port=-1)
    assert cfg.serve_dtype == "bfloat16"


def test_cli_serve_parser():
    from r2d2_tpu.cli import main

    # serve without --ckpt-dir fails loudly at the parser
    with pytest.raises(SystemExit):
        main(["serve", "--preset", "test", "--game", "Fake"])


def test_checkpointer_session_snapshot_roundtrip(tmp_path):
    ckpt = Checkpointer(str(tmp_path))
    assert ckpt.restore_sessions() is None

    def writer(path):
        with open(path, "wb") as f:
            f.write(b"payload")
        return dict(live=3)

    meta = ckpt.save_sessions(writer)
    assert meta["live"] == 3
    got, payload = ckpt.restore_sessions()
    assert got["live"] == 3
    with open(payload, "rb") as f:
        assert f.read() == b"payload"
    # overwrite: the second save replaces the first, no .old left behind
    def writer2(path):
        with open(path, "wb") as f:
            f.write(b"payload2")
        return dict(live=4)

    assert ckpt.save_sessions(writer2)["live"] == 4
    assert ckpt.restore_sessions()[0]["live"] == 4
    assert not os.path.isdir(ckpt._sessions_path() + ".old")
    # crash-between-renames shape: only the .old snapshot exists —
    # restore must fall back to it, never come up empty
    os.replace(ckpt._sessions_path(), ckpt._sessions_path() + ".old")
    got, payload = ckpt.restore_sessions()
    assert got["live"] == 4 and payload.endswith("sessions.bin")
    os.replace(ckpt._sessions_path() + ".old", ckpt._sessions_path())
    # a torn snapshot (no meta.json) is never selected
    os.remove(os.path.join(ckpt._sessions_path(), "meta.json"))
    assert ckpt.restore_sessions() is None


# ------------------------------------------------------------- acceptance

# slow: ~20 s 200-session run on the tier-1 wall budget (ISSUE 15
# rebalance).  Tier-1 keeps the bit-exact server-vs-local socket test,
# eviction/reap/admission units and the wire layer; the committed
# session soak (chaos_soak --sessions) covers the full-load composition.
@pytest.mark.slow
@pytest.mark.timeout(600)
def test_acceptance_200_sessions_end_to_end():
    """The ISSUE's load-gen acceptance: >= 200 concurrent synthetic
    sessions through the tier under an LRU budget that FORCES evictions,
    zero unbounded waits (every client call deadline-bounded), the
    accounting invariant conserved, and the p99 act latency visible on
    /metrics."""
    spec = importlib.util.spec_from_file_location(
        "session_load_gen",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "session_load_gen.py"))
    slg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(slg)

    cfg = _cfg(serve_max_sessions=128, serve_max_batch=32,
               serve_session_idle_s=20.0)
    _, params = _net_params(cfg)
    with _server(cfg, params, start=False) as srv:
        for name, loop in srv.exporter_loops(-1):
            srv.supervisor.start(name, loop)
        srv.start()
        summary = slg.run_load(cfg, A, srv.host, srv.port, sessions=200,
                               workers=4, steps_mean=6, think_s=0.0,
                               run_seconds=120.0, seed=3)
        assert not summary["workers_failed"]
        assert summary["completed"] > 0 and summary["acts"] > 200
        # the budget (128 < 200) really forced the LRU path
        c = srv.store.counts()
        assert c["evicted"] > 0
        _assert_accounting(c)
        # every admitted session left through a counted exit: the
        # client saw the evictions as GONE and retired those sessions
        assert summary["completed"] + summary["gone"] \
            + summary["abandoned"] <= c["admitted"]
        assert srv.healthz()["status"] in ("ok", "degraded")
        # p99 act latency reported through /metrics (histogram + gauge)
        _poll(lambda: srv.registry.get_gauge("serving.act_latency_p99_s")
              is not None, msg="p99 gauge")
        port = srv.exporter.port
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ).read().decode()
        assert "r2d2_serving_act_latency_s_bucket" in body
        assert "r2d2_serving_act_latency_p99_s" in body
        assert "r2d2_serving_batch_size_bucket" in body
        # and the three-state healthz contract answers over HTTP
        hz = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10).read())
        assert hz["status"] in ("ok", "degraded")
        # continuous batching genuinely coalesced ragged requests
        assert srv.stats()["mean_batch"] > 1.0
