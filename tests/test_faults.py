"""Chaos plane + crash-safe session recovery (ISSUE 9).

Covers the resilience contracts the chaos CI gate rests on, at unit
grain: the seeded fault schedule's byte-replayability, input hardening
at the wire (NaN/Inf costs, ragged columns, dtype-mangled TensorBlobs
refused at decode, BEFORE a session arena can be poisoned), deadline
propagation (the matcher sizes per-RPC deadlines to the tick budget;
the servicer refuses dead/burned contexts before dispatching a solve),
graceful drain (stop admitting, flush checkpoints, restart resumes
warm), and the client fallback ladder under DIRTY failures —
mid-stream connection reset during OpenSession, a truncated snapshot
chunk, and a delta answered then dropped before the response — with
the shadow-column state asserted equal to the server's after every
recovery. The end-to-end seeded drill (kill + drop + delay + blackout
over the committed golden trace) lives in ``perf_gate.py --chaos``.
"""

import numpy as np
import pytest

import grpc

from protocol_tpu import native
from protocol_tpu.faults.inject import FaultInjectedError, corrupt_request
from protocol_tpu.faults.plan import ChaosConfig, FaultSchedule, NO_FAULT
from protocol_tpu.fleet.fabric import FleetConfig
from protocol_tpu.proto import scheduler_pb2 as pb
from protocol_tpu.proto import wire
from protocol_tpu.services.scheduler_grpc import (
    RemoteBatchMatcher,
    SchedulerBackendClient,
    drain,
    serve,
)
from protocol_tpu.trace import format as tfmt

from tests.test_scheduler_grpc import _pool_world

NATIVE = native.available()


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------- the seeded fault schedule ----------------


class TestFaultSchedule:
    def test_same_seed_replays_the_identical_fault_train(self):
        cfg = ChaosConfig(
            seed=7, drop_rate=0.1, delay_rate=0.1, corrupt_rate=0.05,
            truncate_rate=0.05, duplicate_rate=0.1,
        )
        a = [
            FaultSchedule(cfg).decide("client", "AssignDelta", i)
            for i in range(300)
        ]
        b = [
            FaultSchedule(cfg).decide("client", "AssignDelta", i)
            for i in range(300)
        ]
        assert a == b
        assert any(not act.clean for act in a)
        assert any(act.clean for act in a)

    def test_seed_changes_the_train(self):
        mk = lambda seed: [
            FaultSchedule(
                ChaosConfig(seed=seed, drop_rate=0.2)
            ).decide("client", "AssignDelta", i)
            for i in range(200)
        ]
        assert mk(1) != mk(2)

    def test_inert_default_decides_no_fault(self):
        sched = FaultSchedule(ChaosConfig())
        assert not ChaosConfig().active()
        assert all(
            sched.decide("client", m, i) == NO_FAULT
            for m in ("AssignDelta", "OpenSession")
            for i in range(50)
        )

    def test_spec_roundtrip_and_rejections(self):
        cfg = ChaosConfig(
            seed=3, drop_rate=0.05, delay_rate=0.05, delay_ms=2.0,
            kill_at_tick=4, blackout_shard=1,
        )
        assert ChaosConfig.from_spec(cfg.spec()) == cfg
        assert ChaosConfig.from_env({"PROTOCOL_TPU_CHAOS": ""}) is None
        assert ChaosConfig.from_env(
            {"PROTOCOL_TPU_CHAOS": "seed=9,drop=0.5"}
        ) == ChaosConfig(seed=9, drop_rate=0.5)
        with pytest.raises(ValueError, match="unknown chaos knob"):
            ChaosConfig.from_spec("seed=1,warp=0.5")
        with pytest.raises(ValueError, match="not key=value"):
            ChaosConfig.from_spec("drop")

    def test_corrupt_byte_is_in_range_with_nonzero_mask(self):
        sched = FaultSchedule(ChaosConfig(seed=5, corrupt_rate=1.0))
        for i in range(64):
            off, mask = sched.corrupt_byte("client", "AssignDelta", i, 37)
            assert 0 <= off < 37
            assert mask != 0  # a no-op flip is not a fault


# ---------------- input hardening at the wire ----------------


def _market_cols(seed=0, P=16, T=12):
    import bench

    rng = np.random.default_rng(seed)
    ep = bench.synth_providers(rng, P)
    er = bench.synth_requirements(rng, T)
    p_cols = wire.canon_columns(ep, wire.P_WIRE_DTYPES)
    r_cols = wire.canon_columns(er, wire.R_WIRE_DTYPES)
    return p_cols, r_cols


class TestInputHardening:
    def test_nan_cost_refused_at_decode(self):
        p_cols, _ = _market_cols()
        p_cols["price"] = p_cols["price"].copy()
        p_cols["price"][3] = np.nan
        msg = wire.encode_providers_v2(tfmt._as_ns(p_cols))
        with pytest.raises(ValueError, match="non-finite"):
            wire.decode_providers_v2(msg)

    def test_inf_cost_refused_at_decode(self):
        _, r_cols = _market_cols()
        r_cols["priority"] = r_cols["priority"].copy()
        r_cols["priority"][0] = np.inf
        msg = wire.encode_requirements_v2(tfmt._as_ns(r_cols))
        with pytest.raises(ValueError, match="non-finite"):
            wire.decode_requirements_v2(msg)

    def test_ragged_columns_refused_at_decode(self):
        p_cols, _ = _market_cols()
        msg = wire.encode_providers_v2(tfmt._as_ns(p_cols))
        for col in msg.columns:
            if col.name == "price":
                short = np.asarray(p_cols["price"][:-2], np.float32)
                col.tensor.CopyFrom(wire.blob(short, np.float32))
        with pytest.raises(ValueError, match="row-count mismatch"):
            wire.decode_providers_v2(msg)

    def test_dtype_mangled_blob_refused_at_decode(self):
        p_cols, _ = _market_cols()
        msg = wire.encode_providers_v2(tfmt._as_ns(p_cols))
        for col in msg.columns:
            if col.name == "price":
                col.tensor.dtype = "float64"  # mangled in transit
        with pytest.raises(ValueError, match="dtype mismatch"):
            wire.decode_providers_v2(msg)

    def test_corrupt_request_mutates_a_copy_not_the_original(self):
        p_cols, r_cols = _market_cols()
        req = pb.AssignRequestV2(
            providers=wire.encode_providers_v2(tfmt._as_ns(p_cols)),
            requirements=wire.encode_requirements_v2(tfmt._as_ns(r_cols)),
            kernel="native-mt", top_k=8,
        )
        before = req.SerializeToString()
        sched = FaultSchedule(ChaosConfig(seed=11, corrupt_rate=1.0))
        mutated = corrupt_request(req, sched, "client", "AssignV2", 0)
        assert mutated is not None
        assert mutated.SerializeToString() != before
        assert req.SerializeToString() == before  # sender's buffer intact
        # the contract: a corrupted frame is REFUSABLE at decode — a
        # poison that decoded to valid finite values would silently
        # apply into carried state instead
        with pytest.raises(ValueError):
            wire.decode_providers_v2(mutated.providers)
        # an int-only message shears a blob instead: size mismatch
        rows_only = pb.AssignDeltaRequest(
            session_id="x",
            provider_rows=wire.blob(np.arange(4, dtype=np.int32)),
        )
        sheared = corrupt_request(
            rows_only, sched, "client", "AssignDelta", 1
        )
        assert sheared is not None
        with pytest.raises(ValueError, match="size mismatch"):
            wire.unblob(sheared.provider_rows, np.int32)
        # an empty message carries no blob bytes: nothing to corrupt
        assert corrupt_request(
            pb.AssignDeltaRequest(session_id="x"), sched, "client",
            "AssignDelta", 0,
        ) is None


@pytest.mark.skipif(not NATIVE, reason="no native toolchain")
class TestHardeningProtectsSessionState:
    """The refusal must land BEFORE the arena: a poisoned delta aborts
    INVALID_ARGUMENT and the session's tick cursor + columns move not
    one bit."""

    def test_poisoned_delta_cannot_reach_carried_state(self):
        port = _free_port()
        addr = f"127.0.0.1:{port}"
        server = serve(addr)
        store = _pool_world()
        m = RemoteBatchMatcher(
            store, addr, min_solve_interval=0.0, wire="v2",
            native_fallback=True, native_engine="native-mt",
            native_threads=2,
        )
        try:
            m.refresh()
            st = m._session
            assert st is not None and st["tick"] == 0
            session = _server_session(server, st["id"])
            clean_price = np.array(session.p_cols["price"], copy=True)

            # a NaN-poisoned one-row delta, sent out-of-band (as a
            # mangled-in-transit frame would arrive)
            poison = wire.take_rows(st["p_cols"], np.array([0]))
            poison.price = np.array([np.nan], np.float32)
            req = pb.AssignDeltaRequest(
                session_id=st["id"], epoch_fingerprint=st["fp"], tick=1,
                provider_rows=wire.blob(np.array([0]), np.int32),
                providers=wire.encode_providers_v2(poison),
            )
            raw = SchedulerBackendClient(addr)
            try:
                with pytest.raises(grpc.RpcError) as exc:
                    raw.assign_delta(req, timeout=30)
                assert exc.value.code() == grpc.StatusCode.INVALID_ARGUMENT
            finally:
                raw.close()

            # nothing moved: cursor still 0, columns bit-identical
            assert session.tick == 0
            np.testing.assert_array_equal(
                session.p_cols["price"], clean_price
            )
            # and the session still serves: the next clean tick lands
            m.refresh()
            assert m._session["tick"] == 1
            _assert_shadow_matches_server(m, server)
        finally:
            m.client.close()
            server.stop(grace=None)

    def test_matcher_resends_once_on_corrupted_in_transit_delta(self):
        """The ladder's INVALID_ARGUMENT rung: a frame mangled on the
        wire is refused at decode (no state moved), so the matcher
        resends the SAME delta once — counted, then back to normal."""
        port = _free_port()
        addr = f"127.0.0.1:{port}"
        server = serve(addr)
        store = _pool_world()
        m = RemoteBatchMatcher(
            store, addr, min_solve_interval=0.0, wire="v2",
            native_fallback=True, native_engine="native-mt",
            native_threads=2,
        )
        try:
            m.refresh()
            m.client = _CorruptDeltaOnce(m.client)
            m.refresh()
            assert m.seam.snapshot().get("session_corrupt_resend") == 1
            assert m._session["tick"] == 1
            assert _server_session(server, m._session["id"]).tick == 1
            _assert_shadow_matches_server(m, server)
        finally:
            m.client.close()
            server.stop(grace=None)


# ---------------- wrappers (dirty-failure injectors) ----------------


class _ClientShim:
    """Pass-through client wrapper with the ``rebind`` hook, so the
    matcher's reconnect path swaps the channel UNDER the shim instead
    of discarding it (exactly what faults.inject.ChaosClient does)."""

    def __init__(self, real):
        self._real = real
        self.address = real.address

    def rebind(self, fresh) -> None:
        old, self._real = self._real, fresh
        try:
            old.close()
        except Exception:
            pass

    def assign(self, *a, **k):
        return self._real.assign(*a, **k)

    def assign_v2(self, *a, **k):
        return self._real.assign_v2(*a, **k)

    def assign_delta(self, *a, **k):
        return self._real.assign_delta(*a, **k)

    def open_session(self, *a, **k):
        return self._real.open_session(*a, **k)

    def health(self, *a, **k):
        return self._real.health(*a, **k)

    def close(self):
        self._real.close()


class _ResetMidStreamOnce(_ClientShim):
    """Mid-stream connection reset during OpenSession: the server sees
    a half-open stream die; the client sees UNAVAILABLE after having
    already shipped part of the snapshot."""

    def __init__(self, real):
        super().__init__(real)
        self.resets = 0

    def open_session(self, chunks, **k):
        if self.resets == 0:
            self.resets += 1
            next(iter(chunks))  # part of the stream left the client
            raise FaultInjectedError(details="injected mid-stream reset")
        return self._real.open_session(chunks, **k)


class _TruncateSnapshotOnce(_ClientShim):
    """A torn stream: the final snapshot chunk never arrives. The
    server must refuse (short stream), and the refusal is TRANSIENT —
    the ladder degrades one tick, never demotes permanently."""

    def __init__(self, real):
        super().__init__(real)
        self.truncated = 0

    def open_session(self, chunks, **k):
        if self.truncated == 0:
            self.truncated += 1
            chunk_list = list(chunks)[:-1]
            assert chunk_list, "need a multi-chunk snapshot to truncate"
            return self._real.open_session(iter(chunk_list), **k)
        return self._real.open_session(chunks, **k)


class _DropDeltaResponseOnce(_ClientShim):
    """The crash-protocol window in miniature: the server APPLIES the
    delta, the response dies on the wire. The retransmit must be
    answered idempotently (replayed twin), never re-applied."""

    def __init__(self, real):
        super().__init__(real)
        self.dropped = 0

    def assign_delta(self, req, **k):
        resp = self._real.assign_delta(req, **k)
        if self.dropped == 0 and resp.session_ok:
            self.dropped += 1
            raise FaultInjectedError(details="injected response drop")
        return resp


class _CorruptDeltaOnce(_ClientShim):
    """Mangle the first delta in transit: splice a NaN-poisoned
    provider row into a COPY of the request (the sender's buffer stays
    intact, like a real bit flip)."""

    def __init__(self, real):
        super().__init__(real)
        self.corrupted = 0

    def assign_delta(self, req, **k):
        if self.corrupted == 0:
            self.corrupted += 1
            mangled = pb.AssignDeltaRequest()
            mangled.CopyFrom(req)
            bad = np.full(1, np.nan, np.float32)
            mangled.provider_rows.CopyFrom(wire.blob(
                np.array([0]), np.int32
            ))
            mangled.providers.columns.add(
                name="price"
            ).tensor.CopyFrom(wire.blob(bad, np.float32))
            return self._real.assign_delta(mangled, **k)
        return self._real.assign_delta(req, **k)


def _server_session(server, session_id: str):
    for session in server.servicer.sessions.snapshot_sessions():
        if session.session_id == session_id:
            return session
    raise AssertionError(f"session {session_id} not on the server")


def _assert_shadow_matches_server(m, server) -> None:
    """The satellite's acceptance bar: after any recovery, the client's
    shadow columns must be bit-identical to the server session's
    (valid prefix — the server pads; the client shadow is stripped)."""
    st = m._session
    session = _server_session(server, st["id"])
    assert session.tick == st["tick"]
    for name, client_col in st["p_cols"].items():
        n = client_col.shape[0]
        np.testing.assert_array_equal(
            np.asarray(session.p_cols[name])[:n], client_col,
            err_msg=f"provider column {name!r} diverged",
        )
    for name, client_col in st["r_cols"].items():
        n = client_col.shape[0]
        np.testing.assert_array_equal(
            np.asarray(session.r_cols[name])[:n], client_col,
            err_msg=f"task column {name!r} diverged",
        )


# ---------------- the fallback ladder under dirty failures ----------------


@pytest.mark.skipif(not NATIVE, reason="no native toolchain")
class TestDirtyFailureLadder:
    def _matcher(self, addr, n_nodes=12, n_tasks=5, **kw):
        store = _pool_world(n_nodes=n_nodes, n_tasks=n_tasks)
        return RemoteBatchMatcher(
            store, addr, min_solve_interval=0.0, wire="v2",
            native_fallback=True, native_engine="native-mt",
            native_threads=2, retry_base_s=0.01, **kw,
        )

    def test_mid_stream_reset_during_open_session(self):
        port = _free_port()
        addr = f"127.0.0.1:{port}"
        server = serve(addr)
        m = self._matcher(addr)
        shim = _ResetMidStreamOnce(m.client)
        m.client = shim
        try:
            m.refresh()
            assert shim.resets == 1
            assert m.seam.snapshot().get("session_retry", 0) >= 1
            assert m._session is not None and m._session["tick"] == 0
            assert m._assignment
            m.refresh()  # the session is healthy: deltas advance
            assert m._session["tick"] == 1
            _assert_shadow_matches_server(m, server)
        finally:
            m.client.close()
            server.stop(grace=None)

    def test_truncated_snapshot_chunk_is_a_transient_refusal(self):
        port = _free_port()
        addr = f"127.0.0.1:{port}"
        server = serve(addr)
        # small uncompressed chunks so the snapshot spans several and
        # losing the last one is a genuinely torn stream
        m = self._matcher(
            addr, n_nodes=64, n_tasks=8, chunk_bytes=1024,
            gzip_snapshots=False,
        )
        shim = _TruncateSnapshotOnce(m.client)
        m.client = shim
        try:
            m.refresh()
            assert shim.truncated == 1
            snap = m.seam.snapshot()
            assert snap.get("session_session_transient_refusal") == 1
            # degraded THIS tick to unary — but not demoted for good
            assert m._session is None
            assert not m._session_refused
            assert m._assignment
            m.refresh()
            assert m._session is not None and m._session["tick"] == 0
            _assert_shadow_matches_server(m, server)
        finally:
            m.client.close()
            server.stop(grace=None)

    def test_delta_applied_but_response_dropped_replays_idempotently(self):
        port = _free_port()
        addr = f"127.0.0.1:{port}"
        server = serve(addr)
        m = self._matcher(addr)
        try:
            m.refresh()
            shim = _DropDeltaResponseOnce(m.client)
            m.client = shim
            m.refresh()
            assert shim.dropped == 1
            # the retransmit was answered from the dedup cache: applied
            # exactly once on the server, advanced exactly once on the
            # client, counted on both sides
            assert m.seam.snapshot().get("session_delta_replayed") == 1
            assert m.last_solve_stats.get("replayed_ticks") == 1
            seam = server.servicer.seam.snapshot()
            assert seam.get("session_delta_replayed", 0) >= 1
            assert m._session["tick"] == 1
            assert _server_session(server, m._session["id"]).tick == 1
            _assert_shadow_matches_server(m, server)
            m.refresh()
            assert m._session["tick"] == 2
            _assert_shadow_matches_server(m, server)
        finally:
            m.client.close()
            server.stop(grace=None)


# ---------------- deadline propagation ----------------


class _RecordTimeouts(_ClientShim):
    def __init__(self, real):
        super().__init__(real)
        self.timeouts: dict = {}

    def open_session(self, chunks, timeout=300.0, **k):
        self.timeouts["OpenSession"] = timeout
        return self._real.open_session(chunks, timeout=timeout, **k)

    def assign_delta(self, req, timeout=60.0, **k):
        self.timeouts["AssignDelta"] = timeout
        return self._real.assign_delta(req, timeout=timeout, **k)


class _FakeAbort(Exception):
    pass


class _FakeContext:
    """A bare gRPC context: alive or not, deadline burned or not."""

    def __init__(self, active=True, remaining=None):
        self._active = active
        self._remaining = remaining
        self.abort_code = None

    def is_active(self):
        return self._active

    def time_remaining(self):
        return self._remaining

    def abort(self, code, details):
        self.abort_code = code
        raise _FakeAbort(details)


@pytest.mark.skipif(not NATIVE, reason="no native toolchain")
def test_matcher_sizes_delta_deadline_to_the_tick_budget():
    port = _free_port()
    addr = f"127.0.0.1:{port}"
    server = serve(addr)
    store = _pool_world()
    m = RemoteBatchMatcher(
        store, addr, min_solve_interval=0.0, wire="v2",
        native_fallback=True, native_engine="native-mt",
        native_threads=2, tick_timeout_s=7.5,
    )
    rec = _RecordTimeouts(m.client)
    m.client = rec
    try:
        m.refresh()  # cold: the snapshot stream keeps the long timeout
        assert rec.timeouts["OpenSession"] == m.request_timeout
        m.refresh()  # steady state: deltas carry the TICK budget
        assert rec.timeouts["AssignDelta"] == 7.5
    finally:
        m.client.close()
        server.stop(grace=None)


def test_servicer_refuses_dead_or_burned_contexts_before_solving():
    """A client that hung up (or whose deadline is already spent) must
    not consume engine threads — refused BEFORE the solve dispatch."""
    port = _free_port()
    addr = f"127.0.0.1:{port}"
    server = serve(addr)
    servicer = server.servicer
    try:
        import bench

        rng = np.random.default_rng(0)
        from protocol_tpu.services.scheduler_grpc import encoded_to_proto_v2

        req = encoded_to_proto_v2(
            bench.synth_providers(rng, 16),
            bench.synth_requirements(rng, 12),
            kernel="greedy", top_k=8,
        )
        dead = _FakeContext(active=False)
        with pytest.raises(_FakeAbort):
            servicer.AssignV2(req, dead)
        assert dead.abort_code == grpc.StatusCode.CANCELLED

        burned = _FakeContext(active=True, remaining=0.0)
        with pytest.raises(_FakeAbort):
            servicer.AssignV2(req, burned)
        assert burned.abort_code == grpc.StatusCode.DEADLINE_EXCEEDED

        seam = servicer.seam.snapshot()
        assert seam.get("session_deadline_refused") == 2

        # a live context with budget left solves normally
        alive = _FakeContext(active=True, remaining=30.0)
        resp = servicer.AssignV2(req, alive)
        assert resp.num_assigned > 0
    finally:
        server.stop(grace=None)


# ---------------- graceful drain + warm restart ----------------


@pytest.mark.skipif(not NATIVE, reason="no native toolchain")
class TestDrainAndWarmRestart:
    def test_draining_refusal_is_transient_on_the_ladder(self, tmp_path):
        port = _free_port()
        addr = f"127.0.0.1:{port}"
        server = serve(
            addr, fleet=FleetConfig(shards=2, ckpt_dir=str(tmp_path))
        )
        store = _pool_world()
        m = RemoteBatchMatcher(
            store, addr, min_solve_interval=0.0, wire="v2",
            native_fallback=True, native_engine="native-mt",
            native_threads=2,
        )
        try:
            server.servicer.draining = True
            m.refresh()  # refused -> unary rung for THIS tick only
            snap = m.seam.snapshot()
            assert snap.get("session_session_transient_refusal") == 1
            assert m._session is None and not m._session_refused
            assert m._assignment
            seam = server.servicer.seam.snapshot()
            assert seam.get("session_drain_refused") == 1

            server.servicer.draining = False  # the replacement admits
            m.refresh()
            assert m._session is not None and m._session["tick"] == 0
        finally:
            m.client.close()
            server.stop(grace=None)

    def test_drain_flushes_and_restart_resumes_warm(self, tmp_path):
        port = _free_port()
        addr = f"127.0.0.1:{port}"
        fleet = FleetConfig(shards=2, ckpt_dir=str(tmp_path))
        server = serve(addr, fleet=fleet)
        store = _pool_world()
        m = RemoteBatchMatcher(
            store, addr, min_solve_interval=0.0, wire="v2",
            native_fallback=True, native_engine="native-mt",
            native_threads=2, retry_base_s=0.01,
        )
        try:
            m.refresh()
            m.refresh()
            assert m._session["tick"] == 1

            flushed = drain(server)  # the SIGTERM path minus the signal
            assert flushed == 1
            assert list(tmp_path.glob("**/*.ckpt"))

            # rolling restart: a fresh servicer on the same port
            # rehydrates from the checkpoint directory
            server = serve(addr, fleet=fleet)
            seam = server.servicer.seam.snapshot()
            assert seam.get("session_session_restored") == 1

            # the channel transparently reconnects to the same port;
            # the delta RESUMES against the rehydrated session
            m.refresh()
            snap = m.seam.snapshot()
            assert m._session["tick"] == 2
            assert "session_session_reopen" not in snap  # warm: no herd
            assert m._assignment
            _assert_shadow_matches_server(m, server)

            # checkpoint GC: a client-dropped session's file goes with
            # it (its client is gone — the file would only resurrect a
            # dead session at every restart); ckpt_dir stays bounded
            server.servicer.sessions.drop(m._session["id"])
            assert not list(tmp_path.glob("**/*.ckpt"))
        finally:
            m.client.close()
            server.stop(grace=None)


# ---------------- checkpoint + codec resilience ----------------


def test_pack_arrays_roundtrip_and_torn_payload_refused():
    named = {
        "cand_p": np.arange(12, dtype=np.int32).reshape(3, 4),
        "price": np.linspace(0, 1, 5).astype(np.float32),
        "f": None,
        "scalar_shaped": np.zeros((), np.float64),
    }
    payload = tfmt.pack_arrays(named)
    out = tfmt.unpack_arrays(payload)
    assert out["f"] is None
    for name in ("cand_p", "price", "scalar_shaped"):
        assert out[name].dtype == named[name].dtype
        np.testing.assert_array_equal(out[name], named[name])
    # a torn tail must fail loudly at load, never decode at the wrong
    # widths (the checkpoint loader turns this into a skipped file)
    with pytest.raises(ValueError, match="truncated"):
        tfmt.unpack_arrays(payload[:-3])
    with pytest.raises(ValueError, match="too short"):
        tfmt.unpack_arrays(b"\x01")
    # layout (ISSUE 27): the buffers named ``last`` (what a solve
    # writes) lie after all others, the manifest stays sorted by name,
    # and readers go by offset, so either order unpacks the same
    import json
    import struct

    last = ("price", "f")
    packed = tfmt.pack_arrays(named, last)
    (n,) = struct.unpack_from("<I", packed)
    manifest = json.loads(packed[4:4 + n])
    assert list(manifest) == sorted(named)
    offsets = {k: m["offset"] for k, m in manifest.items() if m is not None}
    assert offsets["price"] == max(offsets.values())
    assert offsets["price"] + named["price"].nbytes == len(packed) - 4 - n
    assert offsets["cand_p"] < offsets["scalar_shaped"] < offsets["price"]
    head, arrays = tfmt.pack_plan(named, last)
    assert [name for name, _ in arrays] == [
        "cand_p", "scalar_shaped", "price"
    ]
    assert packed == head + b"".join(a.tobytes() for _, a in arrays)
    old_order = tfmt.unpack_arrays(payload)
    new_order = tfmt.unpack_arrays(packed)
    assert old_order.keys() == new_order.keys()
    for name in ("cand_p", "price", "scalar_shaped"):
        np.testing.assert_array_equal(new_order[name], old_order[name])
    assert payload != packed and len(payload) == len(packed)


@pytest.mark.skipif(not NATIVE, reason="no native toolchain")
@pytest.mark.parametrize("mode", ["crash", "drain"])
def test_loadgen_restart_driver_recovers_warm(mode):
    """The loadgen restart drill (the SIGTERM-drain satellite's test
    vehicle, plus the crash twin): servicer taken down mid-run, a fresh
    one rehydrates on the same port, every session resumes WARM — zero
    full-snapshot reopens, no failed session."""
    from protocol_tpu.fleet.loadgen import run_load

    rep = run_load(
        sessions=2, tenants=1, providers=96, tasks=64, ticks=5,
        shards=2, max_workers=8, check_endpoint=False,
        restart_at_tick=2, restart_mode=mode,
    )
    assert not rep["errors"]
    rs = rep["restart"]
    assert rs["restarted"]
    assert rs["sessions_restored"] == 2
    assert rs["reopens_total"] == 0  # recovery was warm, not a herd
    assert rs["transport_retries_total"] >= 1
    if mode == "drain":
        assert rs["flushed"] == 2  # the drain tail flushed every session
    for tenant in rep["tenants"].values():
        # every session completed its full life: tick 0 (snapshot) + 5
        # recorded deltas, across the outage
        assert tenant["ticks_done"] == 2 * 6
        assert tenant["min_assigned_frac"] >= 0.9


@pytest.mark.skipif(not NATIVE, reason="no native toolchain")
def test_chaos_harness_end_to_end_kill_and_deadline(tmp_path):
    """run_chaos in miniature (the CI gate runs the committed golden
    trace; this keeps the harness itself under test): a servicer kill
    mid-run must reconverge warm and bit-identical, and a starved tick
    deadline must degrade explicitly and boundedly."""
    from protocol_tpu.faults.harness import run_chaos
    from protocol_tpu.trace.synth import synth_trace

    trace = synth_trace(
        str(tmp_path / "tiny.trace"), n_providers=96, n_tasks=64,
        ticks=5, churn=0.05, seed=2, kernel="native-mt:1", top_k=16,
    )
    rep = run_chaos(trace, seed=1, kill_at_tick=2, duplicate_rate=0.2)
    assert rep["restarted"]
    assert rep["client"]["reopens"] == 0
    assert rep["client"]["replayed_served"] >= 1
    assert rep["fresh_ticks_identical"] and rep["final_tick_identical"]
    assert not rep["stale_ticks"]

    rep_d = run_chaos(trace, seed=1, tick_deadline_ms=0.01,
                      max_stale_ticks=2)
    assert rep_d["stale_ticks"], "starved deadline produced no staleness"
    assert rep_d["max_stale_streak"] <= 2  # the bounded-staleness contract
    # degraded answers are explicit end to end: flagged on the wire
    # (client count), counted in the obs plane (per tenant)
    assert rep_d["client"]["stale_served"] == len(rep_d["stale_ticks"])
    assert sum(rep_d["server_stale_obs"].values()) == len(
        rep_d["stale_ticks"]
    )
    # staleness trades identity for latency by CONTRACT (a fresh solve
    # after skipped ticks continues a different warm path than the
    # solve-every-tick baseline) — what it must never trade away is
    # the answer's quality floor
    assert rep_d["assigned_frac_min"] >= 0.97


def test_unloadable_checkpoints_are_skipped_not_fatal(tmp_path):
    from protocol_tpu.faults.checkpoint import SessionCheckpointer

    ckpt = SessionCheckpointer(str(tmp_path))
    # journals live in the checkpointer's own (proc id) namespace
    import pathlib

    ns = pathlib.Path(ckpt.directory)
    (ns / "torn.ckpt").write_bytes(b"PTTRACE1garbage")
    (ns / "empty.ckpt").write_bytes(b"")
    # recovery is an optimization, never a new failure mode
    assert ckpt.load_all() == []
    assert ckpt.due(0) and ckpt.due(1)
    every3 = SessionCheckpointer(str(tmp_path), every=3)
    assert [t for t in range(7) if every3.due(t)] == [0, 3, 6]


# ---------------- asymmetric partition (ISSUE 14) ----------------


class TestDirectionalDrops:
    def test_decisions_are_one_directional(self):
        """With only drop_response_rate set, the schedule must never
        lose a request (and vice versa): the partition is ASYMMETRIC
        by construction — A→B flows while B→A drops."""
        sched = FaultSchedule(ChaosConfig(seed=5, drop_response_rate=0.3))
        acts = [sched.decide("client", "AssignDelta", i) for i in range(64)]
        assert any(a.drop_response for a in acts)
        assert not any(a.drop_request or a.drop for a in acts)
        rev = FaultSchedule(ChaosConfig(seed=5, drop_request_rate=0.3))
        acts = [rev.decide("client", "AssignDelta", i) for i in range(64)]
        assert any(a.drop_request for a in acts)
        assert not any(a.drop_response or a.drop for a in acts)

    def test_new_knobs_parse_and_roundtrip(self):
        cfg = ChaosConfig.from_spec(
            "seed=9,dropreq=0.1,dropresp=0.2,slow_proc=1,slow_ms=40,"
            "slow_rate=0.5,pause_proc_at_tick=3,pause_proc=2"
        )
        assert cfg.drop_request_rate == 0.1
        assert cfg.drop_response_rate == 0.2
        assert cfg.slow_proc == 1 and cfg.slow_ms == 40.0
        assert cfg.pause_proc_at_tick == 3 and cfg.pause_proc == 2
        assert cfg.active()
        assert ChaosConfig.from_spec(cfg.spec()) == cfg
        # every new knob alone arms the plane
        assert ChaosConfig(drop_response_rate=0.1).active()
        assert ChaosConfig(slow_proc=0).active()
        assert ChaosConfig(pause_proc_at_tick=1).active()

    @pytest.mark.skipif(not NATIVE, reason="no native toolchain")
    @pytest.mark.parametrize("kernel", ["native-mt:1", "sinkhorn-mt:1"])
    def test_response_drop_rides_retransmit_dedup_bit_identical(
        self, tmp_path, kernel
    ):
        """The asymmetric-partition site end to end, on BOTH engines:
        requests flow, responses drop (seed 5 kills delta answers at
        call indices 1 and 5). The server APPLIES each dropped tick;
        the client's resend must be served the replayed twin — zero
        reopens, every plan bit-identical to the fault-free replay."""
        from protocol_tpu.faults.harness import _Driver
        from protocol_tpu.trace.replay import iter_input_ticks, replay
        from protocol_tpu.trace.synth import synth_trace

        trace_path = str(tmp_path / f"part_{kernel.split(':')[0]}.trace")
        synth_trace(
            trace_path, n_providers=64, n_tasks=64, ticks=6,
            churn=0.05, seed=3, kernel=kernel,
        )
        trace = tfmt.read_trace(trace_path)
        baseline = replay(
            trace_path, engine=kernel, verify=False, keep_p4t=True
        )["p4ts"]
        schedule = FaultSchedule(
            ChaosConfig(seed=5, drop_response_rate=0.3)
        )
        address = f"127.0.0.1:{_free_port()}"
        server = serve(address, fleet=FleetConfig(shards=2))
        driver = _Driver(
            address, schedule, "t0@partition", kernel, trace.snapshot
        )
        try:
            for tick, p_cols, r_cols, delta in iter_input_ticks(trace):
                if tick == 0:
                    p4t = driver.open(p_cols, r_cols)
                else:
                    p4t, stale = driver.tick(delta, p_cols, r_cols)
                    assert not stale
                assert np.array_equal(p4t, baseline[tick]), (
                    f"tick {tick} diverged under response drops"
                )
            assert driver.client.counters.get("drop_response", 0) >= 1
            assert "drop_request" not in driver.client.counters
            assert driver.counters["replayed_served"] >= 1
            assert driver.counters["reopens"] == 0
            seam = server.servicer.seam.snapshot()
            assert seam.get("session_delta_replayed", 0) >= 1
        finally:
            driver.close()
            server.stop(grace=None)


class TestSlowNodeInterceptor:
    def test_slow_proc_targets_one_process(self):
        """The gray slow-node site: the interceptor inflates responses
        ONLY in the targeted process — the same schedule in any other
        proc_id leaves the handler untouched."""
        cfg = ChaosConfig(seed=1, slow_proc=1, slow_ms=1.0)
        sched = FaultSchedule(cfg)
        from protocol_tpu.faults.inject import ChaosServerInterceptor

        calls = []

        class _Details:
            method = "/pkg.Svc/AssignDelta"

        def handler_fn(request, context):
            calls.append(request)
            return "ok"

        def continuation(details):
            return grpc.unary_unary_rpc_method_handler(handler_fn)

        slow = ChaosServerInterceptor(sched, proc_id="p1")
        fast = ChaosServerInterceptor(sched, proc_id="p0")
        wrapped = slow.intercept_service(continuation, _Details())
        assert wrapped.unary_unary is not handler_fn  # wrapped: delays
        assert wrapped.unary_unary("req", None) == "ok"
        assert slow.counters.get("slow") == 1
        untouched = fast.intercept_service(continuation, _Details())
        assert untouched.unary_unary is handler_fn  # pass-through
        assert "slow" not in fast.counters
