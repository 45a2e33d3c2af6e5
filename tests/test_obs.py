"""Observability plane (protocol_tpu/obs): span tracer semantics,
HDR-histogram quantiles, the per-session registry's prometheus-OPTIONAL
degradation contract (dict snapshot authoritative, scrape endpoint 503s
cleanly), span-ID propagation across a wire-v2 session, and the
trace-native flame/phase report."""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

import protocol_tpu.obs as obs
from protocol_tpu.obs import metrics as obs_metrics
from protocol_tpu.obs.endpoint import MetricsEndpoint
from protocol_tpu.obs.metrics import (
    LatencyHistogram,
    ObsRegistry,
    percentiles_ms,
    tenant_of,
)
from protocol_tpu.obs.spans import METADATA_KEY, SpanTracer


class TestLatencyHistogram:
    def test_quantiles_bounded_relative_error(self):
        h = LatencyHistogram()
        values = [float(v) for v in range(1000, 2_000_000, 1117)]
        for v in values:
            h.observe_ns(v)
        values.sort()
        for q in (0.5, 0.9, 0.99):
            exact = values[min(len(values) - 1, int(q * len(values)))]
            est = h.quantile_ns(q)
            assert abs(est - exact) / exact < 0.10, (q, est, exact)

    def test_empty_and_below_floor(self):
        h = LatencyHistogram()
        assert h.snapshot_ms() == {"count": 0}
        assert h.quantile_ns(0.99) == 0.0
        h.observe_ns(5)  # below the 1 µs resolution floor: bucket 0
        assert h.count == 1
        assert h.quantile_ns(0.5) > 0

    def test_merge(self):
        a, b = LatencyHistogram(), LatencyHistogram()
        for v in (1e6, 2e6, 3e6):
            a.observe_ns(v)
        for v in (10e6, 20e6):
            b.observe_ns(v)
        a.merge(b)
        assert a.count == 5
        assert a.snapshot_ms()["max_ms"] == 20.0

    def test_percentiles_ms_helper(self):
        p = percentiles_ms([1.0, 2.0, 3.0, 100.0])
        assert p["count"] == 4
        assert p["p99_ms"] > 50

    def test_tenant_of(self):
        assert tenant_of("acme@pool-7") == "acme"
        assert tenant_of("bare-session") == "bare-session"
        assert tenant_of("") == "unknown"


class TestSpanTracer:
    def test_nesting_and_explicit_ids(self):
        tr = SpanTracer()
        with tr.span("root") as root:
            with tr.span("child") as child:
                assert child["trace"] == root["trace"]
                assert child["parent"] == root["span"]
        spans = tr.drain()
        assert [s["name"] for s in spans] == ["child", "root"]
        # counter-allocated ids, no randomness
        assert spans[1]["span"] < spans[0]["span"]

    def test_ring_bounded(self):
        tr = SpanTracer(capacity=8)
        for i in range(50):
            with tr.span(f"s{i}"):
                pass
        assert len(tr.snapshot()) == 8
        assert tr.snapshot()[-1]["name"] == "s49"

    def test_since_mark_and_trace_filter(self):
        tr = SpanTracer()
        with tr.span("before"):
            pass
        mark = tr.mark()
        with tr.span("a") as a:
            pass
        with tr.span("b"):
            pass
        got = tr.since(mark, trace=a["trace"])
        assert [s["name"] for s in got] == ["a"]

    def test_header_inject_extract(self):
        tr = SpanTracer()
        assert tr.header() == ""
        assert tr.inject(None) is None  # no open span: nothing to inject
        with tr.span("tick") as f:
            h = tr.header()
            assert h == f"{f['trace']}/{f['span']}"
            md = tr.inject([("other", "1")])
            assert (METADATA_KEY, h) in md
        assert SpanTracer.extract(md) == h
        assert SpanTracer.extract([("x", "y")]) is None

    def test_remote_parent_adoption(self):
        tr = SpanTracer()
        with tr.span("client") as c:
            header = tr.header()
        with tr.span("server-rpc", remote_parent=header) as s:
            assert s["trace"] == c["trace"]
            assert s["parent"] == c["span"]

    def test_disabled_is_noop(self):
        tr = SpanTracer(enabled=False)
        with tr.span("x") as f:
            assert f is None
        tr.point("y")
        tr.record_span("z", 0, 10)
        assert tr.snapshot() == []

    def test_point_and_record_span(self):
        tr = SpanTracer()
        with tr.span("root") as r:
            tr.point("evict", reason="lru")
            tr.record_span("region", 100, 50, kind="gen")
        spans = {s["name"]: s for s in tr.drain()}
        assert spans["evict"]["dur_ns"] == 0
        assert spans["evict"]["parent"] == r["span"]
        assert spans["region"]["dur_ns"] == 50
        assert spans["region"]["trace"] == r["trace"]


class TestObsRegistry:
    def _filled(self):
        reg = ObsRegistry(role="server")
        reg.observe_tick(
            "t1@pool", 5.0, 100, 97,
            arena_stats={"cold": True, "changed_rows": 100},
        )
        reg.observe_tick(
            "t1@pool", 2.0, 100, 99,
            arena_stats={"cold": False, "changed_rows": 10},
            delta_rows=4,
        )
        return reg

    def test_snapshot_authoritative(self):
        snap = self._filled().snapshot()
        s = snap["sessions"]["t1@pool"]
        assert s["tenant"] == "t1"
        assert s["tick"]["count"] == 1  # one warm tick
        assert s["cold_tick"]["count"] == 1
        assert s["assigned_frac"] == 0.99
        assert s["min_assigned_frac"] == 0.97
        # reuse ratio: (200 - 110 changed) / 200 rows
        assert s["arena_reuse_ratio"] == pytest.approx(0.45)
        assert s["delta_rows"] == 4

    def test_render_with_prometheus(self):
        if not obs_metrics.prometheus_available():
            pytest.skip("prometheus_client not installed")
        text = self._filled().render().decode()
        assert "scheduler_obs_tick_latency_ms" in text
        assert 'tenant="t1"' in text

    def test_reuse_ratio_padded_rows_stay_in_range(self):
        """The arena reports row counts over its PADDED pow2 batch; the
        ratio must stay a fraction for non-pow2 real task counts."""
        reg = ObsRegistry()
        reg.observe_tick("s", 1.0, 100, 100, arena_stats={
            "cold": True, "rows": 128, "changed_rows": 128})
        reg.observe_tick("s", 1.0, 100, 100, arena_stats={
            "cold": False, "rows": 128, "changed_rows": 5})
        s = reg.snapshot()["sessions"]["s"]
        assert 0.0 <= s["arena_reuse_ratio"] <= 1.0
        assert s["arena_reuse_ratio"] == pytest.approx(
            1 - 133 / 256, abs=1e-4
        )

    def test_stateless_kernel_is_cold_with_no_reuse(self):
        """No arena_stats = a stateless kernel: classified cold, no
        reuse credit, assigned fraction clamped (the 'best' kernel
        counts assigned PROVIDERS, which can exceed the task count)."""
        reg = ObsRegistry()
        reg.observe_tick("unary:v1", 3.0, 100, 256)
        s = reg.snapshot()["sessions"]["unary:v1"]
        assert s["cold_tick"]["count"] == 1 and s["tick"] == {"count": 0}
        assert s["arena_reuse_ratio"] == 0.0
        assert s["assigned_frac"] == 1.0  # clamped, never > 1

    def test_lru_bounded_sessions(self):
        """Client-minted session ids churn (uuids per process): the
        registry must stay bounded and keep the RECENT sessions."""
        reg = ObsRegistry(max_sessions=4)
        for i in range(10):
            reg.observe_tick(f"s{i}", 1.0, 10, 10)
        sessions = reg.snapshot()["sessions"]
        assert len(sessions) == 4
        assert "s9" in sessions and "s0" not in sessions
        # re-observing an old-but-surviving session refreshes recency
        reg.observe_tick("s6", 1.0, 10, 10)
        reg.observe_tick("new", 1.0, 10, 10)
        sessions = reg.snapshot()["sessions"]
        assert "s6" in sessions and "s7" not in sessions

    def test_kill_switch_gates_servicer_registry(self):
        """PROTOCOL_TPU_OBS=0 must silence the per-session registry too,
        not just spans/engine stats (the documented whole-plane off)."""
        pytest.importorskip("grpc")
        pytest.importorskip("jax")
        from protocol_tpu.services.scheduler_grpc import (
            SchedulerBackendServicer,
        )

        servicer = SchedulerBackendServicer()
        try:
            obs.set_enabled(False)
            servicer._observe_tick("s", 0.0, 10, 10)
            assert servicer.obs.snapshot()["sessions"] == {}
        finally:
            obs.set_enabled(True)
        servicer._observe_tick("s", 0.0, 10, 10)
        assert "s" in servicer.obs.snapshot()["sessions"]

    def test_prometheus_absent_degradation(self, monkeypatch):
        """The new registries must keep the SeamMetrics contract: no
        prometheus_client => the dict snapshot stays authoritative and
        only the prometheus render degrades (ImportError)."""
        monkeypatch.setattr(obs_metrics, "CollectorRegistry", None)
        reg = self._filled()
        snap = reg.snapshot()  # still fully functional
        assert snap["sessions"]["t1@pool"]["tick"]["count"] == 1
        with pytest.raises(ImportError):
            reg.render()


class TestEndpointDegradation:
    def _get(self, url):
        try:
            r = urllib.request.urlopen(url, timeout=10)
            return r.status, r.read().decode()
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode()

    def test_scrape_503s_cleanly_without_prometheus(self, monkeypatch):
        monkeypatch.setattr(obs_metrics, "CollectorRegistry", None)
        reg = ObsRegistry()
        reg.observe_tick("s", 1.0, 10, 10)
        ep = MetricsEndpoint(
            prom_sources=[reg], json_sources={"obs": reg}
        )
        try:
            code, text = self._get(
                f"http://127.0.0.1:{ep.port}/metrics"
            )
            assert code == 503
            assert "metrics.json" in text  # points at the snapshot
            # the authoritative snapshot stays served
            code, text = self._get(
                f"http://127.0.0.1:{ep.port}/metrics.json"
            )
            assert code == 200
            assert json.loads(text)["obs"]["sessions"]["s"]
        finally:
            ep.stop()

    def test_scrape_200_with_prometheus(self):
        if not obs_metrics.prometheus_available():
            pytest.skip("prometheus_client not installed")
        reg = ObsRegistry()
        reg.observe_tick("s", 1.0, 10, 10)
        ep = MetricsEndpoint(
            prom_sources=[reg], json_sources={"obs": reg}
        )
        try:
            code, text = self._get(f"http://127.0.0.1:{ep.port}/metrics")
            assert code == 200
            assert "scheduler_obs_assigned_frac" in text
            code, _ = self._get(f"http://127.0.0.1:{ep.port}/healthz")
            assert code == 200
        finally:
            ep.stop()


grpc = pytest.importorskip("grpc")


class TestSpanPropagationWireV2:
    """A client tick's span context must ride the gRPC metadata and
    stitch the servicer's spans (rpc root, decode, solve, session
    lookup, budget grant, arena) into ONE causal trace across a full
    wire-v2 session (open + delta)."""

    def test_wire_v2_session_stitches_one_trace(self, tmp_path):
        pytest.importorskip("jax")
        from protocol_tpu import native

        if not native.available():
            pytest.skip("no native toolchain")
        import socket

        from protocol_tpu.obs.spans import TRACER
        from protocol_tpu.ops.cost import CostWeights
        from protocol_tpu.proto import scheduler_pb2 as pbs
        from protocol_tpu.proto import wire as wirelib
        from protocol_tpu.services.scheduler_grpc import (
            SchedulerBackendClient,
            encoded_to_proto_v2,
            serve,
        )
        from protocol_tpu.trace.synth import (
            synth_providers,
            synth_requirements,
        )

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        server = serve(f"127.0.0.1:{port}")
        client = SchedulerBackendClient(f"127.0.0.1:{port}")
        try:
            rng = np.random.default_rng(0)
            ep = synth_providers(rng, 128)
            er = synth_requirements(rng, 128)
            w = CostWeights()
            p_cols = wirelib.canon_columns(ep, wirelib.P_WIRE_DTYPES)
            r_cols = wirelib.canon_columns(er, wirelib.R_WIRE_DTYPES)
            fp = wirelib.epoch_fingerprint(
                p_cols, r_cols, w, "native-mt:1", 32, 0.02, 0
            )
            req = encoded_to_proto_v2(
                ep, er, w, kernel="native-mt:1", top_k=32, eps=0.02
            )
            with TRACER.span("client-tick") as tick:
                resp = client.open_session(
                    wirelib.chunk_snapshot("prop@t", fp, req)
                )
                assert resp.ok, resp.error
                p_cols["price"][:3] = 7.5
                rows = np.arange(3, dtype=np.int32)
                dreq = pbs.AssignDeltaRequest(
                    session_id="prop@t", epoch_fingerprint=fp, tick=1
                )
                dreq.provider_rows.CopyFrom(wirelib.blob(rows, np.int32))
                dreq.providers.CopyFrom(
                    wirelib.encode_providers_v2(
                        wirelib.take_rows(p_cols, rows)
                    )
                )
                dresp = client.assign_delta(dreq)
                assert dresp.session_ok, dresp.error
            trace_id = tick["trace"]
            spans = [
                s for s in TRACER.snapshot() if s["trace"] == trace_id
            ]
            names = {s["name"] for s in spans}
            # servicer-side spans adopted the client's trace id
            assert {
                "rpc.OpenSession", "rpc.AssignDelta", "wire.decode",
                "engine.solve", "session.lookup", "budget.grant",
                "arena.solve",
            } <= names
            roots = [s for s in spans if s["name"].startswith("rpc.")]
            assert all(s["parent"] is not None for s in roots)
            # per-session metrics landed under the session id
            snap = server.servicer.obs.snapshot()
            sess = snap["sessions"]["prop@t"]
            assert sess["tenant"] == "prop"
            assert sess["tick"]["count"] >= 1  # the delta tick
            assert sess["cold_tick"]["count"] >= 1  # the open solve
            assert snap["budget"]["grants"] >= 2
        finally:
            client.close()
            server.stop(grace=None)


class TestReport:
    def _recorded_trace(self, tmp_path) -> str:
        pytest.importorskip("jax")
        from protocol_tpu import native

        if not native.available():
            pytest.skip("no native toolchain")
        from protocol_tpu.trace.replay import replay
        from protocol_tpu.trace.synth import synth_trace

        src = str(tmp_path / "in.trace")
        synth_trace(src, n_providers=128, n_tasks=128, ticks=3,
                    churn=0.05, kernel="native-mt")
        out = str(tmp_path / "golden.trace")
        rep = replay(src, engine="native-mt", threads=1, record_path=out)
        assert rep["divergence"] is None
        return out

    def test_report_renders_native_phases(self, tmp_path):
        from protocol_tpu.obs.report import render

        text = render(self._recorded_trace(tmp_path))
        # per-tick table with native-engine INTERNAL phases
        assert "per-tick phase breakdown" in text
        assert "rounds" in text and "bids" in text
        # percentile table + flame
        assert "p99" in text
        assert "arena.engine" in text

    def test_report_json(self, tmp_path):
        from protocol_tpu.obs.report import report_dict

        d = report_dict(self._recorded_trace(tmp_path))
        assert len(d["ticks"]) == 4  # snapshot + 3 deltas
        assert d["warm"]["count"] == 3
        assert d["ticks"][1]["eng_rounds"] > 0

    def test_report_cli_smoke(self, tmp_path, capsys):
        from protocol_tpu.obs.__main__ import main

        rc = main(["report", self._recorded_trace(tmp_path)])
        assert rc == 0
        outp = capsys.readouterr().out
        assert "obs report" in outp and "rounds" in outp


class TestObsToggle:
    def test_arena_stats_follow_toggle(self):
        pytest.importorskip("jax")
        from protocol_tpu import native

        if not native.available():
            pytest.skip("no native toolchain")
        from protocol_tpu.native.arena import NativeSolveArena
        from protocol_tpu.ops.cost import CostWeights
        from tests.test_sparse import encode_random_marketplace

        ep, er = encode_random_marketplace(2, 128, 128)
        on = NativeSolveArena(threads=1)
        p_on = on.solve(ep, er, CostWeights())
        assert any(k.startswith("eng_") for k in on.last_stats)
        assert obs.enabled()
        try:
            obs.set_enabled(False)
            off = NativeSolveArena(threads=1)
            p_off = off.solve(ep, er, CostWeights())
            assert not any(k.startswith("eng_") for k in off.last_stats)
        finally:
            obs.set_enabled(True)
        # observability must observe, never perturb
        np.testing.assert_array_equal(p_on, p_off)

    def _jax_chain(self, monkeypatch, rows=256):
        """Cold solve and two warm ticks of a jax arena: the plans and
        the stats of the last tick."""
        import dataclasses

        monkeypatch.setenv("PROTOCOL_TPU_JIT_WITNESS", "1")
        from protocol_tpu.ops.cost import CostWeights
        from protocol_tpu.parallel.jax_arena import JaxSolveArena
        from tests.test_sparse import encode_random_marketplace

        ep, er = encode_random_marketplace(5, rows, rows)
        arena = JaxSolveArena(devices=1)
        plans = [arena.solve(ep, er, CostWeights())]
        rng = np.random.default_rng(5)
        price = np.array(ep.price, copy=True)
        for _ in range(2):
            price[rng.choice(rows, 3, replace=False)] += 0.25
            ep = dataclasses.replace(ep, price=price.copy())
            plans.append(arena.solve(ep, er, CostWeights()))
        return plans, arena.last_stats

    def test_jax_arena_plans_identical_and_no_warm_compile(
        self, monkeypatch
    ):
        """The span tree, its counters and the device scope names
        (ISSUE 26) observe and never perturb: the same plans with the
        plane off, and nothing built on the second warm tick."""
        pytest.importorskip("jax")
        from protocol_tpu.obs.spans import TRACER

        on, stats_on = self._jax_chain(monkeypatch)
        assert stats_on["cold"] is False
        assert stats_on["eng_segments"] >= 1
        assert stats_on["jit_compiles_delta"] == {}
        assert obs.enabled()
        try:
            obs.set_enabled(False)
            mark = TRACER.mark()
            off, stats_off = self._jax_chain(monkeypatch)
            assert TRACER.since(mark) == []
        finally:
            obs.set_enabled(True)
        assert not any(k.startswith("eng_") for k in stats_off)
        # the stage counters ride with the plane on or off, like gen_ms
        for key in ("dirty_ms", "diff_ms", "rep_enter_ms", "rep_forward_ms",
                    "rep_tiles_ms", "rep_merge_ms", "gen_ms", "solve_ms"):
            assert key in stats_off, key
        for a, b in zip(on, off):
            np.testing.assert_array_equal(a, b)
