"""The span tree of one acknowledged tick (ISSUE 26): a warm jax tick
through the servicer with checkpoint-before-ack on is ONE trace rooted
at ``rpc.AssignDelta`` and complete down to the places where the host
blocks; the counters beside the spans land in ``last_stats`` and in
Health; the spans reach the profiler's host plane; the device programs
carry their scope names; and the thirteen per-layer metrics of
``benchmarks/metrics/`` read those counters through the benchmark's
generic reader. (That the plane never perturbs a plan is
``test_obs.py::TestObsToggle``'s.) CPU, 256 rows."""

import glob
import json
import os
import signal
import socket

import numpy as np
import pytest

pytest.importorskip("jax")
pytest.importorskip("grpc")

import protocol_tpu.obs as obs  # noqa: E402
from protocol_tpu.obs.spans import TRACER  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 256
TEST_SECONDS = 240

# name -> parent's name, the tree of ISSUE 26 §1 (a warm repair tick),
# with ISSUE 27's ``ckpt.prefix``, ISSUE 29's ``auction.reverse``,
# ISSUE 33's ``auction.queue`` and ISSUE 40's ``auction.open_count``,
# ``quality.gap``, ``ckpt.encode`` and ``auction.upload`` (the candidate
# lists going up, in ``arena.engine``'s self time on the chip)
# (``session.lock_wait`` went: its seam phase stays)
TREE = {
    "rpc.AssignDelta": None,
    "session.lookup": "rpc.AssignDelta",
    "wire.decode": "rpc.AssignDelta",
    "engine.solve": "rpc.AssignDelta",
    "session.apply_delta": "engine.solve",
    "arena.solve": "engine.solve",
    "arena.dirty": "arena.solve",
    "arena.candidates": "arena.solve",
    "repair.enter_scan": "arena.candidates",
    "repair.forward_rows": "arena.candidates",
    "repair.tile_contrib": "arena.candidates",
    "repair.merge": "arena.candidates",
    "arena.diff": "arena.candidates",
    "arena.engine": "arena.solve",
    "auction.upload": "arena.engine",
    "auction.seed": "arena.engine",
    "auction.segment": "arena.engine",
    "auction.open_count": "auction.segment",
    "auction.reverse": "arena.engine",
    "auction.queue": "arena.engine",
    "auction.cleanup": "arena.engine",
    "arena.readback": "arena.engine",
    "arena.quality": "arena.solve",
    "quality.gap": "arena.quality",
    "ckpt.prefix": "arena.solve",
    "ckpt.encode": "ckpt.prefix",
    "ckpt.flush": "engine.solve",
    "ckpt.export": "ckpt.flush",
    "ckpt.frame": "ckpt.flush",
    "obs.observe_tick": "rpc.AssignDelta",
    "wire.encode": "rpc.AssignDelta",
}
# spans of another thread than their parent's: they start inside it and
# run beside its other children
CONCURRENT = {"ckpt.prefix"}
STAT_KEYS = (
    "dirty_ms", "diff_ms", "rep_enter_ms", "rep_forward_ms",
    "rep_tiles_ms", "rep_merge_ms",
)
# the repair's counters (ISSUE 32): bytes its stages copied from the
# device, and the times the host waited for it (one a stage)
REPAIR_COUNTERS = ("rep_readback_bytes", "rep_syncs")
SEAM_PHASES = (
    "lock_wait", "apply", "ckpt_flush", "ckpt_export", "ckpt_deflate",
    "ckpt_overlap", "ckpt_join", "ckpt_worker", "ckpt_encode",
)


@pytest.fixture(autouse=True)
def time_limit():
    """Each test of this file has a time limit of its own."""
    def late(signum, frame):
        raise TimeoutError(f"test ran past {TEST_SECONDS} s")

    before = signal.signal(signal.SIGALRM, late)
    signal.alarm(TEST_SECONDS)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, before)


class _Served:
    """A loopback servicer with checkpoint-before-ack on and one
    session of ``rows`` rows (``kernel``: jax); ``tick()`` sends the
    next warm delta (1% of providers re-priced, ``self.rows``) under a
    client span and returns that span's trace id."""

    def __init__(self, ckpt_dir: str, kernel: str = "jax", rows: int = ROWS):
        from protocol_tpu.fleet.fabric import FleetConfig
        from protocol_tpu.ops.cost import CostWeights
        from protocol_tpu.proto import wire
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
        address = f"127.0.0.1:{s.getsockname()[1]}"
        s.close()
        self.ckpt_dir = ckpt_dir
        self.server = serve(
            address, fleet=FleetConfig(ckpt_dir=ckpt_dir, ckpt_every=1)
        )
        self.client = SchedulerBackendClient(address)
        self.rng = np.random.default_rng(26)
        self.n_rows = rows
        ep = synth_providers(self.rng, rows)
        er = synth_requirements(self.rng, rows)
        w = CostWeights()
        self.p_cols = wire.canon_columns(ep, wire.P_WIRE_DTYPES)
        r_cols = wire.canon_columns(er, wire.R_WIRE_DTYPES)
        self.sid = "tree@t"
        self.fp = wire.epoch_fingerprint(
            self.p_cols, r_cols, w, kernel, 64, 0.02, 0
        )
        req = encoded_to_proto_v2(
            ep, er, w, kernel=kernel, top_k=64, eps=0.02
        )
        resp = self.client.open_session(
            wire.chunk_snapshot(self.sid, self.fp, req)
        )
        assert resp.ok, resp.error
        self.n = 0

    def tick(self) -> str:
        from protocol_tpu.proto import scheduler_pb2 as pb
        from protocol_tpu.proto import wire

        self.n += 1
        rows = self.rows = np.sort(
            self.rng.choice(self.n_rows, 3, replace=False)
        ).astype(np.int32)
        price = self.p_cols["price"]
        price[rows] = self.rng.uniform(0.5, 9.0, 3).astype(price.dtype)
        req = pb.AssignDeltaRequest(
            session_id=self.sid, epoch_fingerprint=self.fp, tick=self.n
        )
        req.provider_rows.CopyFrom(wire.blob(rows, np.int32))
        req.providers.CopyFrom(
            wire.encode_providers_v2(wire.take_rows(self.p_cols, rows))
        )
        with TRACER.span("client.tick") as root:
            resp = self.client.assign_delta(req)
        assert resp.session_ok, resp.error
        self.plan = wire.unblob(resp.result.provider_for_task, np.int32)
        return root["trace"]

    def stats(self) -> dict:
        session, _why = self.server.servicer.sessions.get(self.sid, self.fp)
        return dict(session.arena.last_stats)

    def seam(self) -> dict:
        return {m.name: m.value for m in self.client.health().seam_metrics}

    def journal_bytes(self) -> int:
        (path,) = glob.glob(os.path.join(self.ckpt_dir, "p0", "*"))
        return os.path.getsize(path)

    def close(self) -> None:
        self.client.close()
        self.server.stop(grace=None)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Two ticks behind it (the first builds the repair programs), the
    third observed: its spans, stats, and Health on both sides of it."""
    assert obs.enabled()
    s = _Served(str(tmp_path_factory.mktemp("ckpt")))
    try:
        s.tick()
        s.tick()
        s.seam_before = s.seam()
        mark = TRACER.mark()
        trace = s.tick()
        s.spans = TRACER.since(mark, trace=trace)
        s.seam_after = s.seam()
        s.journal_size = s.journal_bytes()
        yield s
    finally:
        s.close()


def _end(span: dict) -> int:
    return span["t0_ns"] + span["dur_ns"]


class TestSpanTree:
    def test_one_trace_holds_every_name_under_its_parent(self, served):
        by_id = {s["span"]: s for s in served.spans}
        names = {s["name"] for s in served.spans}
        assert set(TREE) <= names, sorted(set(TREE) - names)
        for span in served.spans:
            want = TREE.get(span["name"])
            if want is not None:
                assert by_id[span["parent"]]["name"] == want, span
        (root,) = [s for s in served.spans if s["name"] == "rpc.AssignDelta"]
        # the client's tick adopted: the root's parent is the client's
        # seam span, in the same trace
        assert root["parent"] is not None
        kinds = sorted(
            s["attrs"]["kind"] for s in served.spans
            if s["name"] == "ckpt.frame"
        )
        assert kinds == ["arena", "outcome", "snapshot"]
        flush = next(s for s in served.spans if s["name"] == "ckpt.flush")
        assert flush["attrs"]["bytes_out"] == served.journal_size
        assert flush["attrs"]["bytes_raw"] > flush["attrs"]["bytes_out"]
        assert flush["attrs"]["prefix"] == "hit"
        assert flush["attrs"]["join_ms"] >= 0
        prefix = next(s for s in served.spans if s["name"] == "ckpt.prefix")
        assert 0 < prefix["attrs"]["bytes_raw"] < flush["attrs"]["bytes_raw"]
        assert prefix["attrs"]["deflate_ms"] > 0
        for s in served.spans:
            if s["name"] in ("auction.seed", "auction.upload"):
                assert s["attrs"]["dispatch_only"] is True

    def test_children_lie_inside_their_parents(self, served):
        by_id = {s["span"]: s for s in served.spans}
        under: dict = {}
        for span in served.spans:
            parent = by_id.get(span["parent"])
            if parent is None or span["name"] not in TREE:
                continue
            assert span["t0_ns"] >= parent["t0_ns"], (span, parent)
            if span["name"] in CONCURRENT:
                continue
            assert _end(span) <= _end(parent), (span, parent)
            under.setdefault(parent["span"], []).append(span)
        # the worker's span starts when the candidates are final and is
        # joined by the flush
        (prefix,) = [s for s in served.spans if s["name"] == "ckpt.prefix"]
        before = next(
            s for s in served.spans if s["name"] == "arena.candidates"
        )
        flush = next(s for s in served.spans if s["name"] == "ckpt.flush")
        assert _end(before) <= prefix["t0_ns"]
        assert _end(prefix) <= _end(flush)
        for pid, kids in under.items():
            assert sum(k["dur_ns"] for k in kids) <= by_id[pid]["dur_ns"]

    def test_span_budget_and_ring_room(self, served):
        # ISSUE 26: at most 120 spans a warm ack, and the ring holds at
        # least 30 acks of them
        assert len(served.spans) <= 120
        segments = sum(
            1 for s in served.spans if s["name"] == "auction.segment"
        )
        # at the benchmark's 8,192 rows an ack has ~17 segments
        at_cell_size = len(served.spans) - segments + 18
        assert 30 * at_cell_size <= TRACER.capacity

    def test_segment_spans_count_the_solve(self, served):
        stats = served.stats()
        segs = [s for s in served.spans if s["name"] == "auction.segment"]
        assert len(segs) == stats["eng_segments"] >= 1
        assert (
            sum(s["attrs"]["rounds"] for s in segs)
            == stats["eng_rounds_total"]
        )
        assert stats["eng_segments"] * 256 >= stats["eng_rounds_total"]
        read = next(s for s in served.spans if s["name"] == "arena.readback")
        waited = sum(s["attrs"]["wait_ms"] for s in segs)
        assert stats["eng_wait_ms"] == pytest.approx(
            waited + read["dur_ns"] / 1e6, abs=0.5
        )
        assert 0 < stats["eng_wait_ms"] <= stats["solve_ms"]

    def test_segment_rows_count_the_frontier(self, served):
        """Every segment span says how many frontier rows its rounds
        ran at, and the solve's sum rides ``last_stats``."""
        from protocol_tpu.ops.sparse import _FRONTIER_RUNGS

        stats = served.stats()
        segs = [
            s for s in served.spans
            if s["name"] in ("auction.segment", "auction.reverse")
            and "rounds" in s["attrs"]
        ]
        assert any(s["name"] == "auction.segment" for s in segs)
        for s in segs:
            a = s["attrs"]
            low = min(_FRONTIER_RUNGS[0], a["frontier"])
            assert a["rounds"] * low <= a["rows"] <= a["rounds"] * a["frontier"]
            # a round that scanned [T] ran at the top width
            assert 0 <= a["scans"] <= a["rounds"]
            assert a["rows"] >= a["scans"] * a["frontier"]
        assert isinstance(stats["eng_frontier_rows"], int)
        assert stats["eng_frontier_rows"] == sum(
            s["attrs"]["rows"] for s in segs
        ) > 0

    def test_segment_spans_count_the_scans(self, served):
        """Every segment span says how many of its rounds scanned
        ``[T]`` for their open tasks (more of them than the open list
        holds), and the solve's sum rides ``last_stats``."""
        stats = served.stats()
        segs = [
            s["attrs"] for s in served.spans
            if s["name"] in ("auction.segment", "auction.reverse",
                             "auction.queue")
            and "rounds" in s["attrs"]
        ]
        assert segs and all("scans" in a for a in segs)
        assert isinstance(stats["eng_scan_rounds"], int)
        assert stats["eng_scan_rounds"] == sum(a["scans"] for a in segs)
        assert stats["eng_scan_rounds"] < sum(a["rounds"] for a in segs)

    def test_reverse_spans_count_the_pass(self, served):
        """Every solve checks for stranded providers and for slack (one
        ``auction.reverse`` span, step ``check``, closed at its one
        read); where it finds both, the seed, the reverse segments and
        the finish are spans of the same name (a full pool, as here,
        has no slack: ``tests/test_pool_slack.py`` drives the pass), and
        the counters beside them ride ``last_stats``."""
        stats = served.stats()
        rev = [s for s in served.spans if s["name"] == "auction.reverse"]
        checks = [s for s in rev if s["attrs"].get("step") == "check"]
        assert len(checks) == 1 and checks[0]["attrs"]["stranded"] >= 0
        segs = [s for s in rev if "rounds" in s["attrs"]]
        assert (
            sum(s["attrs"]["rounds"] for s in segs)
            == stats["eng_reverse_rounds"]
        )
        steps = {s["attrs"].get("step") for s in rev}
        ran = "seed" in steps
        assert ("finish" in steps) == ran
        # no stranded provider, or no slack: nothing but the check
        if min(checks[0]["attrs"][k] for k in ("stranded", "slack")) <= 0:
            assert not ran
        assert (stats["eng_free_repriced"] > 0) <= ran
        assert 0 < stats["eng_reverse_ms"] <= stats["solve_ms"]
        assert stats["eng_reverse_ms"] >= sum(
            s["dur_ns"] for s in rev
        ) / 1e6 - 0.5
        plan = served.plan
        assert stats["eng_free_providers"] == ROWS - int((plan >= 0).sum())

    def test_queue_spans_count_the_pass(self, served):
        """Every solve reads whether its pool has a queue (one
        ``auction.queue`` span, step ``regime``, closed at its one
        read); a full pool, as here, has none, so nothing else of the
        pass runs, and its four counters ride ``last_stats`` all the
        same."""
        stats = served.stats()
        queue = [s for s in served.spans if s["name"] == "auction.queue"]
        assert [s["attrs"].get("step") for s in queue] == ["regime"]
        assert queue[0]["attrs"]["seatable"] <= ROWS
        assert queue[0]["attrs"]["listed"] <= ROWS
        assert stats["eng_queue_rounds"] == 0
        assert 0 < stats["eng_queue_ms"] <= stats["solve_ms"]
        assert stats["eng_queue_ms"] >= queue[0]["dur_ns"] / 1e6 - 0.5
        assert stats["eng_waiting_tasks"] == ROWS - int(
            (served.plan >= 0).sum()
        )
        assert stats["waiting_excess"] == 0.0

    def test_queue_spans_in_a_pool_with_a_queue(self):
        """Where tasks outnumber providers the pass runs: ``check``
        (closed at its read of the free providers), and with any free
        ``seed``, the pass's segments (the segment attrs) and
        ``finish``, all under ``arena.engine``; the counters add up."""
        from tests.test_pool_slack import open_session

        gen, arena, session = open_session(256, 320)
        for tick in range(3):
            mark = TRACER.mark()
            with session.lock:
                if tick:
                    session.apply_delta(*gen.next_delta())
                session.solve()
            spans = TRACER.since(mark)
            stats = dict(arena.last_stats)
            by_id = {s["span"]: s for s in spans}
            queue = [s for s in spans if s["name"] == "auction.queue"]
            assert all(
                by_id[s["parent"]]["name"] == "arena.engine" for s in queue
            )
            steps = [s["attrs"].get("step") for s in queue]
            assert steps[0] == "regime" and steps.count("regime") == 1
            assert steps.count("check") == (5 if tick == 0 else 1)
            assert steps.count("seed") == steps.count("finish") >= 1
            segs = [s["attrs"] for s in queue if "rounds" in s["attrs"]]
            assert sum(a["rounds"] for a in segs) == stats["eng_queue_rounds"]
            assert stats["eng_queue_rounds"] > 0
            forward = [
                s["attrs"] for s in spans if s["name"] == "auction.segment"
            ]
            assert sum(a["rounds"] for a in forward) == (
                stats["eng_rounds_total"]
            )
            assert stats["eng_frontier_rows"] == sum(
                a["rows"] for a in segs + forward
            )
            assert stats["eng_scan_rounds"] == sum(
                a["scans"] for a in segs + forward
            )
            assert stats["eng_queue_ms"] >= sum(
                s["dur_ns"] for s in queue
            ) / 1e6 - 0.5
            assert stats["eng_queue_ms"] <= stats["solve_ms"]
            assert stats["eng_waiting_tasks"] == 320 - 256
            assert 0.0 <= stats["waiting_excess"] <= 0.02 * 64
            assert stats["eng_reverse_rounds"] == 0


    def test_the_regime_span_and_counters_on_a_crossing(self):
        """A pool that breathes crosses P = T: the solve that enters or
        leaves a queue re-grounds in one ``auction.regime`` span
        (``step="cross"``, ``from``, ``to``) under ``arena.engine``,
        whose wall and rounds ``eng_cross_ms`` / ``eng_cross_rounds``
        count; every warm tick names its crossing, its transposed
        rounds, the rows that left or came back and the seats emptied,
        0 where nothing happened."""
        from tests.test_pool_breathing import open_breathing

        gen, arena, session = open_breathing()
        regime, crossings = None, []
        for tick in range(14):
            mark = TRACER.mark()
            with session.lock:
                if tick:
                    session.apply_delta(*gen.next_delta())
                session.solve()
            spans = TRACER.since(mark)
            stats = dict(arena.last_stats)
            by_id = {s["span"]: s for s in spans}
            cross = [s for s in spans if s["name"] == "auction.regime"]
            if tick:
                for key in ("eng_regime_change", "eng_cross_ms",
                            "eng_cross_rounds", "eng_transposed_rounds",
                            "arena_rows_left", "arena_rows_joined",
                            "arena_seats_vacated"):
                    assert key in stats, (tick, key)
                assert stats["eng_regime_change"] == int(
                    arena._regime != regime)
                assert stats["eng_transposed_rounds"] == (
                    stats["eng_reverse_rounds"] + stats["eng_queue_rounds"])
            if cross:
                (span,) = cross
                assert by_id[span["parent"]]["name"] == "arena.engine"
                assert span["attrs"]["step"] == "cross"
                assert (span["attrs"]["from"], span["attrs"]["to"]) == (
                    regime, arena._regime)
                assert "queue" in (regime, arena._regime)
                assert stats["eng_cross_ms"] >= span["dur_ns"] / 1e6 - 0.5
                assert stats["eng_cross_ms"] <= stats["solve_ms"]
                inside = [
                    s["attrs"] for s in spans
                    if s["name"] in ("auction.segment", "auction.reverse",
                                     "auction.queue")
                    and "rounds" in s["attrs"]
                ]
                assert stats["eng_cross_rounds"] == sum(
                    a["rounds"] for a in inside) > 0
                crossings.append(tick)
            else:
                assert stats.get("eng_cross_ms", 0.0) == 0.0
                assert stats.get("eng_cross_rounds", 0) == 0
            regime = arena._regime
        assert len(crossings) >= 2

    def test_the_regime_span_is_read_by_the_solves_idle_metric(self):
        import re

        readers_of = [
            m["name"] for m in _idle_split()["metrics"]
            if any(re.fullmatch(p, "auction.regime")
                   for p in m["read"]["spans"])
        ]
        assert readers_of == ["idle_in_solve_ms_per_ack"]


class TestCounters:
    def test_last_stats_split_the_stage_walls(self, served):
        stats = served.stats()
        for key in STAT_KEYS + ("eng_wait_ms",):
            assert isinstance(stats[key], float) and stats[key] >= 0, key
        assert isinstance(stats["eng_segments"], int)
        parts = sum(stats[k] for k in STAT_KEYS if k != "dirty_ms")
        # the stages tile the repair wall but for the call's prologue:
        # 2% at the benchmark's size on the chip (PERF.md); here, on
        # a CPU other tests share, a tenth or a few milliseconds
        assert 0 <= stats["gen_ms"] - parts <= max(
            0.1 * stats["gen_ms"], 5.0
        )
        assert "pad_hw" not in stats

    def test_last_stats_count_the_repairs_reads(self, served):
        """A provider-only tick: the enter scan, the forward rows, the
        tile contributions and the merge each wait for the device once,
        and what they copy back is the lists they keep, not a cost
        block."""
        stats = served.stats()
        for key in REPAIR_COUNTERS:
            assert isinstance(stats[key], int), key
        assert stats["rep_syncs"] == 4
        lists = 2 * 4 * stats["repair_rows"] * 64
        merged = 2 * 4 * ROWS * 80
        assert merged <= stats["rep_readback_bytes"] <= 4 * (lists + merged)

    def test_last_stats_name_the_open_count_and_the_gap(self, served):
        """ISSUE 40: the open counts' reads after full segments are a
        part of the solve's wait, the gap a part of the quality pass;
        each counter is the wall of its spans."""
        stats = served.stats()
        by_id = {s["span"]: s for s in served.spans}
        reads = [
            s for s in served.spans if s["name"] == "auction.open_count"
            and by_id[s["parent"]]["name"] == "auction.segment"
        ]
        assert reads
        assert isinstance(stats["eng_open_read_ms"], float)
        assert stats["eng_open_read_ms"] == pytest.approx(
            sum(s["dur_ns"] for s in reads) / 1e6, abs=0.5
        )
        assert 0 < stats["eng_open_read_ms"] <= stats["eng_wait_ms"]
        (gap,) = [s for s in served.spans if s["name"] == "quality.gap"]
        assert stats["q_gap_ms"] == pytest.approx(gap["dur_ns"] / 1e6, abs=0.5)
        assert 0 < stats["q_gap_ms"] <= stats["quality_ms"]

    def test_health_carries_the_new_phases(self, served):
        before, after = served.seam_before, served.seam_after
        for phase in SEAM_PHASES:
            assert after[f"{phase}_count"] == before[f"{phase}_count"] + 1
            assert after[f"{phase}_ms_sum"] >= before[f"{phase}_ms_sum"]
        assert (
            after["bytes_ckpt"] - before["bytes_ckpt"] == served.journal_size
        )
        took = {
            p: after[f"{p}_ms_sum"] - before[f"{p}_ms_sum"]
            for p in SEAM_PHASES
        }
        assert took["ckpt_deflate"] + took["ckpt_export"] <= (
            took["ckpt_flush"] + 0.01
        )
        # the warm tick's flush used its prefix job: most of the zlib
        # time lay beside the solve, not in the flush
        assert took["ckpt_overlap"] > took["ckpt_deflate"]
        # the flush's wait for the worker is the span's own attribute
        flush = next(s for s in served.spans if s["name"] == "ckpt.flush")
        assert took["ckpt_join"] == pytest.approx(
            flush["attrs"]["join_ms"], abs=1e-6
        )
        assert took["ckpt_join"] <= took["ckpt_flush"] + 0.01
        assert (
            after["session_ckpt_prefix_hit"]
            == before["session_ckpt_prefix_hit"] + 1
        )
        # the cold open's flush had no solve to hide behind
        assert after["session_ckpt_prefix_miss"] == 1
        assert before["session_ckpt_prefix_miss"] == 1
        # ISSUE 40: the worker's whole wall and its SNAPSHOT message, for
        # every flush that found its job run and for no other (the
        # miss observes nothing, never a made-up 0)
        prefix = next(s for s in served.spans if s["name"] == "ckpt.prefix")
        encode = next(s for s in served.spans if s["name"] == "ckpt.encode")
        assert took["ckpt_worker"] == pytest.approx(
            prefix["dur_ns"] / 1e6, abs=0.5
        )
        assert took["ckpt_encode"] == pytest.approx(
            encode["dur_ns"] / 1e6, abs=0.5
        )
        assert took["ckpt_encode"] + took["ckpt_overlap"] <= (
            took["ckpt_worker"] + 0.01
        )
        assert after["ckpt_worker_count"] == after["session_ckpt_prefix_hit"]


@pytest.fixture(scope="module")
def captured(served, tmp_path_factory):
    """One more warm tick under a CPU profiler capture, as the
    benchmark's traced run takes it: the trace, read back."""
    import jax

    if not hasattr(jax.profiler, "ProfileData"):
        pytest.skip("this jax has no ProfileData")
    where = str(tmp_path_factory.mktemp("xplane"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(where, profiler_options=options)
    try:
        served.tick()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        os.path.join(where, "**", "*.xplane.pb"), recursive=True
    )
    return jax.profiler.ProfileData.from_file(path)


class TestProfilerClock:
    def test_spans_land_on_the_profilers_host_plane(self, captured):
        profile = captured
        found: dict = {}
        for plane in profile.planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name in TREE:
                        found.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns)
                        )
        for name in ("rpc.AssignDelta", "engine.solve", "arena.solve",
                     "auction.segment", "ckpt.flush", "auction.open_count",
                     "arena.quality", "quality.gap", "ckpt.prefix",
                     "ckpt.encode"):
            assert name in found, sorted(found)
        # every span of the tree is a `with` block, so every one is here
        assert set(TREE) <= set(found), sorted(set(TREE) - set(found))
        (outer,) = found["engine.solve"]
        (solve,) = found["arena.solve"]
        (flush,) = found["ckpt.flush"]
        assert outer[0] <= solve[0] and solve[1] <= flush[0]
        assert flush[1] <= outer[1]
        for seg in found["auction.segment"]:
            assert solve[0] <= seg[0] and seg[1] <= solve[1]
        # ISSUE 40's three, each inside its parent
        for child, parent in (("auction.open_count", "auction.segment"),
                              ("quality.gap", "arena.quality"),
                              ("ckpt.encode", "ckpt.prefix")):
            for c in found[child]:
                assert any(
                    p[0] <= c[0] and c[1] <= p[1] for p in found[parent]
                ), (child, c)

    def test_the_tracer_module_does_not_import_jax(self):
        import subprocess
        import sys

        code = (
            "import sys; import protocol_tpu.obs.spans as s; "
            "assert 'jax' not in sys.modules; "
            "t = s.SpanTracer()\n"
            "with t.span('x'): pass\n"
            "assert 'jax' not in sys.modules and len(t.snapshot()) == 1"
        )
        subprocess.run(
            [sys.executable, "-c", code], check=True, cwd=REPO, timeout=120,
        )


class TestScopeNames:
    """The device programs carry their scope names (op metadata: a
    later reader can sum device time by scope, whatever the jitted
    functions are called)."""

    T, K, P = 64, 8, 64

    def _cands(self):
        import jax.numpy as jnp

        return (
            jnp.zeros((self.T, self.K), jnp.int32),
            jnp.ones((self.T, self.K), jnp.float32),
        )

    def test_the_phase_and_clean_up_programs(self):
        import jax.numpy as jnp

        from protocol_tpu.ops import sparse

        cp, cc = self._cands()
        text = sparse._sparse_auction_phase.lower(
            cp, cc, self.P, None, eps=0.02, max_iters=16, frontier=32,
            retire=True, stall_limit=0,
        ).as_text(debug_info=True)
        for scope in ("auction.bid", "auction.resolve", "auction.commit"):
            assert scope in text
        owner = jnp.zeros(self.P, jnp.int32)
        p4t = jnp.zeros(self.T, jnp.int32)
        text = sparse._unassign_unhappy.lower(
            cp, cc, jnp.zeros(self.P), owner, p4t, 0.02
        ).as_text(debug_info=True)
        assert "auction.unassign_unhappy" in text
        text = sparse._greedy_cleanup_compacted.lower(
            cp, cc, owner, p4t, budget=16
        ).as_text(debug_info=True)
        assert "auction.greedy_cleanup" in text
        rstate = (jnp.int32(0), jnp.zeros(self.T), p4t, owner,
                  jnp.zeros(self.P, bool))
        for lowered in (
            sparse._transpose_candidates.lower(
                cp, cc, num_providers=self.P, width=16),
            sparse._stranded.lower(cp, jnp.zeros(self.P), owner, p4t),
            sparse._reverse_seed.lower(
                cp, cc, jnp.zeros(self.P), owner, p4t,
                jnp.ones(self.P, bool), jnp.float32(0.0)),
            sparse._reverse_finish.lower(
                cp, cc, jnp.zeros(self.P), jnp.zeros(self.T), rstate,
                jnp.float32(0.0)),
        ):
            assert "auction.reverse" in lowered.as_text(debug_info=True)
        for lowered in (
            sparse._queue_free.lower(cp, owner, num_providers=self.P),
            sparse._queue_seed.lower(
                cp, cc, jnp.zeros(self.P), owner, p4t, jnp.float32(-30.0)),
        ):
            assert "auction.queue" in lowered.as_text(debug_info=True)
        # the names queue_roofline finds the queue pass's programs by
        assert sparse._queue_free.__name__ == "_queue_free"
        assert sparse._queue_seed.__name__ == "_queue_seed"
        # the names reverse_roofline finds the pass's own programs by
        assert sparse._transpose_candidates.__name__ == "_transpose_candidates"
        assert sparse._reverse_seed.__name__ == "_reverse_seed"
        assert sparse._reverse_finish.__name__ == "_reverse_finish"
        assert sparse._stranded.__name__ == "_stranded"
        # the names the accepted solve_roofline finds the solve by
        assert sparse._sparse_auction_phase.__name__ == "_sparse_auction_phase"
        assert sparse._unassign_unhappy.__name__ == "_unassign_unhappy"
        assert (
            sparse._greedy_cleanup_compacted.__name__
            == "_greedy_cleanup_compacted"
        )

    def test_the_generation_and_repair_programs(self):
        import dataclasses

        import jax
        import jax.numpy as jnp

        from protocol_tpu.ops import sparse
        from protocol_tpu.ops.cost import CostWeights
        from protocol_tpu.parallel import sparse as psparse
        from tests.test_sparse import encode_random_marketplace

        ep, er = encode_random_marketplace(3, self.P, self.T)
        w = dataclasses.astuple(CostWeights())
        text = sparse.candidates_topk_reverse.lower(
            ep, er, CostWeights(), k=self.K, tile=32, reverse_r=4,
            with_pools=True,
        ).as_text(debug_info=True)
        assert "gen.forward" in text and "gen.reverse" in text
        cp, cc = self._cands()
        rev_t = jnp.zeros((self.P, 4), jnp.int32)
        rev_c = jnp.ones((self.P, 4), jnp.float32)
        for scope in ("gen.merge", "repair.merge"):
            text = sparse.merge_reverse_candidates.lower(
                cp, cc, rev_t, rev_c, extra=4, scope=scope
            ).as_text(debug_info=True)
            assert scope in text
        ep_def, er_def = jax.tree.structure(ep), jax.tree.structure(er)
        pad = 8
        ep_rows = psparse._gather_rows(ep, np.arange(3), pad)
        ids = jnp.zeros(pad, jnp.uint32)
        flags = jnp.zeros(pad, bool)
        programs = {
            "repair.enter_scan": psparse._build_repair_enter(
                w, 32, self.T // 32, pad, ep_def, er_def
            ).lower(ep_rows, ids, flags, er, jnp.zeros(self.T)),
            "repair.forward_rows": psparse._build_repair_forward(
                w, self.P, self.K, pad, 32, self.T // 32, ep_def, er_def
            ).lower(
                ep, er, ids, flags,
                jnp.zeros((self.P, self.T // 32), jnp.float32),
            ),
            "repair.tile_contrib": psparse._build_repair_tile(
                w, 32, 2, pad, ep_def, er_def
            ).lower(ep, ids, er, jnp.uint32(0)),
            "repair.refold": psparse._build_repair_refold(
                self.P, 2, 2, 4, 1
            ).lower(
                jnp.zeros((self.P, 4), jnp.int32),
                jnp.ones((self.P, 4), jnp.float32),
            ),
        }
        for scope, lowered in programs.items():
            assert scope in lowered.as_text(debug_info=True), scope


# ---- the thirteen per-layer metrics of ISSUE 26, ISSUE 27's two, ISSUE 29's six, ISSUE 30's one, ISSUE 32's two, ISSUE 33's five, ISSUE 39's one and ISSUE 40's four that read counters,
# read through the benchmark's own generic reader from canned contexts (data files only: no reader code)

_ACKS = [
    {"wall_ms": 4000.0, "gen_ms": 500.0, "solve_ms": 3000.0,
     "dirty_ms": 10.0, "diff_ms": 40.0, "rep_enter_ms": 100.0,
     "rep_forward_ms": 200.0, "rep_tiles_ms": 60.0, "rep_merge_ms": 90.0,
     "eng_segments": 17, "eng_wait_ms": 2900.0,
     "gap_per_task": 0.010, "idle_price": 0.0, "eng_free_providers": 3277,
     "eng_free_repriced": 100, "eng_reverse_rounds": 40,
     "eng_reverse_ms": 30.0, "eng_frontier_rows": 300000,
     "rep_readback_bytes": 6000000, "rep_syncs": 4,
     "eng_waiting_tasks": 1638, "eng_queue_rounds": 30,
     "eng_queue_ms": 60.0, "waiting_excess": 4.0, "eng_scan_rounds": 20,
     "eng_open_read_ms": 30.0, "q_gap_ms": 8.0,
     "eng_regime_change": 1, "eng_cross_ms": 500.0,
     "eng_cross_rounds": 3000, "eng_transposed_rounds": 70,
     "arena_rows_moved": 60, "arena_seats_vacated": 160},
    {"wall_ms": 4200.0, "gen_ms": 520.0, "solve_ms": 3100.0,
     "dirty_ms": 14.0, "diff_ms": 44.0, "rep_enter_ms": 110.0,
     "rep_forward_ms": 210.0, "rep_tiles_ms": 64.0, "rep_merge_ms": 94.0,
     "eng_segments": 18, "eng_wait_ms": 2980.0,
     "gap_per_task": 0.012, "idle_price": 1.0, "eng_free_providers": 3277,
     "eng_free_repriced": 140, "eng_reverse_rounds": 60,
     "eng_reverse_ms": 50.0, "eng_frontier_rows": 340000,
     "rep_readback_bytes": 7000000, "rep_syncs": 3,
     "eng_waiting_tasks": 1640, "eng_queue_rounds": 50,
     "eng_queue_ms": 80.0, "waiting_excess": 6.0, "eng_scan_rounds": 40,
     "eng_open_read_ms": 50.0, "q_gap_ms": 12.0,
     "eng_regime_change": 0, "eng_cross_ms": 0.0,
     "eng_cross_rounds": 0, "eng_transposed_rounds": 110,
     "arena_rows_moved": 70, "arena_seats_vacated": 170},
]
_SEAM_BEFORE = {
    "apply_ms_sum": 1.0, "ckpt_flush_ms_sum": 100.0,
    "ckpt_deflate_ms_sum": 80.0, "bytes_ckpt": 1000.0,
    "ckpt_overlap_ms_sum": 700.0, "session_ckpt_prefix_hit": 7.0,
    "ckpt_join_ms_sum": 30.0, "ckpt_worker_ms_sum": 100.0,
    "ckpt_encode_ms_sum": 20.0, "ckpt_chunks_sum": 112.0,
}
_SEAM_AFTER = {
    "apply_ms_sum": 5.0, "ckpt_flush_ms_sum": 1300.0,
    "ckpt_deflate_ms_sum": 1080.0, "bytes_ckpt": 7001000.0,
    "ckpt_overlap_ms_sum": 1600.0, "session_ckpt_prefix_hit": 9.0,
    "ckpt_join_ms_sum": 54.0, "ckpt_worker_ms_sum": 500.0,
    "ckpt_encode_ms_sum": 120.0, "ckpt_chunks_sum": 144.0,
}
# metric -> (layer, unit, source, the key whose absence silences it,
#            expected value on the canned context[, better])
METRICS = {
    "apply_delta_ms_per_ack": (
        "session, arena bookkeeping and checkpoint", "ms", "program_span",
        "apply_ms_sum", 2.0),
    "dirty_detect_ms_per_ack": (
        "session, arena bookkeeping and checkpoint", "ms", "program_span",
        "dirty_ms", 12.0),
    "ckpt_flush_ms_per_ack": (
        "session, arena bookkeeping and checkpoint", "ms", "program_span",
        "ckpt_flush_ms_sum", 600.0),
    "ckpt_deflate_ms_per_ack": (
        "session, arena bookkeeping and checkpoint", "ms", "program_span",
        "ckpt_deflate_ms_sum", 500.0),
    "ckpt_bytes_per_ack": (
        "session, arena bookkeeping and checkpoint", "bytes",
        "program_counter", "bytes_ckpt", 3500000.0),
    "repair_enter_ms_per_ack": (
        "candidate repair", "ms", "program_span", "rep_enter_ms", 105.0),
    "repair_forward_ms_per_ack": (
        "candidate repair", "ms", "program_span", "rep_forward_ms", 205.0),
    "repair_tiles_ms_per_ack": (
        "candidate repair", "ms", "program_span", "rep_tiles_ms", 62.0),
    "repair_merge_ms_per_ack": (
        "candidate repair", "ms", "program_span", "rep_merge_ms", 92.0),
    "repair_diff_ms_per_ack": (
        "candidate repair", "ms", "program_span", "diff_ms", 42.0),
    "solve_segments_per_ack": (
        "auction solve", "segments", "program_counter", "eng_segments",
        17.5),
    "solve_wait_ms_per_ack": (
        "auction solve", "ms", "program_span", "eng_wait_ms", 2940.0),
    "solve_host_ms_per_ack": (
        "auction solve", "ms", "program_span", "eng_wait_ms", 110.0),
    "ckpt_overlap_ms_per_ack": (
        "session, arena bookkeeping and checkpoint", "ms", "program_span",
        "ckpt_overlap_ms_sum", 450.0),
    "ckpt_prefix_hits_per_ack": (
        "session, arena bookkeeping and checkpoint", "hits",
        "program_counter", "session_ckpt_prefix_hit", 1.0, "higher"),
    "ckpt_join_ms_per_ack": (
        "session, arena bookkeeping and checkpoint", "ms", "program_span",
        "ckpt_join_ms_sum", 12.0),
    "certified_gap_per_task": (
        "quality pass", "cost/task", "program_counter", "gap_per_task",
        0.011),
    "idle_price_per_ack": (
        "quality pass", "cost", "program_counter", "idle_price", 0.5),
    "free_providers_per_ack": (
        "auction solve", "providers", "program_counter",
        "eng_free_providers", 3277.0),
    "free_repriced_per_ack": (
        "auction solve", "providers", "program_counter",
        "eng_free_repriced", 120.0),
    "reverse_rounds_per_ack": (
        "auction solve", "rounds", "program_counter", "eng_reverse_rounds",
        50.0),
    "reverse_ms_per_ack": (
        "auction solve", "ms", "program_span", "eng_reverse_ms", 40.0),
    "frontier_rows_per_ack": (
        "auction solve", "rows", "program_counter", "eng_frontier_rows",
        320000.0),
    "repair_readback_bytes_per_ack": (
        "candidate repair", "bytes", "program_counter",
        "rep_readback_bytes", 6500000.0),
    "repair_syncs_per_ack": (
        "candidate repair", "reads", "program_counter", "rep_syncs", 3.5),
    "waiting_tasks_per_ack": (
        "auction solve", "tasks", "program_counter", "eng_waiting_tasks",
        1639.0),
    "queue_rounds_per_ack": (
        "auction solve", "rounds", "program_counter", "eng_queue_rounds",
        40.0),
    "queue_ms_per_ack": (
        "auction solve", "ms", "program_span", "eng_queue_ms", 70.0),
    "waiting_excess_per_ack": (
        "quality pass", "cost", "program_counter", "waiting_excess", 5.0),
    "queued_gap_per_task": (
        "quality pass", "cost/task", "program_counter", "gap_per_task",
        0.011),
    "scan_rounds_per_ack": (
        "auction solve", "rounds", "program_counter", "eng_scan_rounds",
        30.0),
    "solve_open_read_ms_per_ack": (
        "auction solve", "ms", "program_span", "eng_open_read_ms", 40.0),
    "quality_gap_ms_per_ack": (
        "quality pass", "ms", "program_span", "q_gap_ms", 10.0),
    "ckpt_worker_ms_per_ack": (
        "session, arena bookkeeping and checkpoint", "ms", "program_span",
        "ckpt_worker_ms_sum", 200.0),
    "ckpt_encode_ms_per_ack": (
        "session, arena bookkeeping and checkpoint", "ms", "program_span",
        "ckpt_encode_ms_sum", 50.0),
    "ckpt_chunks_per_ack": (
        "session, arena bookkeeping and checkpoint", "chunks",
        "program_counter", "ckpt_chunks_sum", 16.0, "higher"),
    "cross_ms_per_ack": (
        "auction solve", "ms", "program_span", "eng_cross_ms", 250.0),
    "cross_rounds_per_ack": (
        "auction solve", "rounds", "program_counter", "eng_cross_rounds",
        1500.0),
    "regime_changes_per_ack": (
        "auction solve", "changes", "program_counter", "eng_regime_change",
        0.5),
    "transposed_rounds_per_ack": (
        "auction solve", "rounds", "program_counter",
        "eng_transposed_rounds", 90.0),
    "rows_moved_per_ack": (
        "session, arena bookkeeping and checkpoint", "rows",
        "program_counter", "arena_rows_moved", 65.0),
    "seats_vacated_per_ack": (
        "session, arena bookkeeping and checkpoint", "seats",
        "program_counter", "arena_seats_vacated", 165.0),
}
# the cells a metric is declared for, where not ``pool-large.ticks``
CELLS = {
    name: ["pool-slack.ticks"] for name in (
        "certified_gap_per_task", "idle_price_per_ack",
        "free_providers_per_ack", "free_repriced_per_ack",
        "reverse_rounds_per_ack", "reverse_ms_per_ack",
    )
}
CELLS.update(dict.fromkeys(
    ("frontier_rows_per_ack", "repair_readback_bytes_per_ack",
     "repair_syncs_per_ack"),
    ["pool-large.ticks", "pool-slack.ticks"],
))
CELLS.update(dict.fromkeys(
    ("waiting_tasks_per_ack", "queue_rounds_per_ack", "queue_ms_per_ack",
     "waiting_excess_per_ack", "queued_gap_per_task"),
    ["pool-queued.ticks"],
))
CELLS.update(dict.fromkeys(
    ("cross_ms_per_ack", "cross_rounds_per_ack", "regime_changes_per_ack",
     "transposed_rounds_per_ack", "rows_moved_per_ack",
     "seats_vacated_per_ack"),
    ["pool-breathing.life"],
))
CELLS.update(dict.fromkeys(
    ("ckpt_join_ms_per_ack", "scan_rounds_per_ack",
     "solve_open_read_ms_per_ack", "quality_gap_ms_per_ack",
     "ckpt_worker_ms_per_ack", "ckpt_encode_ms_per_ack",
     "ckpt_chunks_per_ack"),
    ["pool-large.ticks", "pool-slack.ticks", "pool-queued.ticks"],
))


def _without(key: str) -> dict:
    return {
        "records": [{k: v for k, v in a.items() if k != key} for a in _ACKS],
        "seam_before": {k: v for k, v in _SEAM_BEFORE.items() if k != key},
        "seam_after": {k: v for k, v in _SEAM_AFTER.items() if k != key},
        "trace": None, "shape": {}, "peaks": {},
    }


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_new_metric_reads_its_counter_through_the_generic_reader(name):
    from benchmarks.lib import readers

    layer, unit, source, key, want, better = (*METRICS[name], "lower")[:6]
    with open(os.path.join(REPO, "benchmarks", "metrics", name + ".json")) as fh:
        spec = json.load(fh)
    assert spec["name"] == name
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    for field, value in (("layer", layer), ("unit", unit),
                         ("source", source), ("moves", "ack_p50_ms"),
                         ("better", better)):
        assert spec[field] == entry[field] == value, field
    assert entry["workloads"] == CELLS.get(name, ["pool-large.ticks"])
    assert readers.read_metric(spec, _without("")) == pytest.approx(want)
    # the parent commit has no such counter: nothing is read, nothing
    # is raised, and the line leaves the metric out
    assert readers.read_metric(spec, _without(key)) is None


def test_queue_roofline_reads_the_pass_programs_from_a_canned_trace():
    """``queue_roofline`` through the generic reader: the device time
    of the queue pass's four programs in a slice, against the least
    bytes they must move at the cell's shapes; a trace without them
    (the parent commit: the pass never runs) reads nothing."""
    from benchmarks.lib import readers

    path = os.path.join(REPO, "benchmarks", "metrics", "queue_roofline.json")
    with open(path) as fh:
        spec = json.load(fh)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "queue_roofline"]
    assert entry == {
        "name": "queue_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels", "moves": "ack_p50_ms",
        "workloads": ["pool-queued.ticks"],
    }
    shape = {"n_tasks": 8192, "n_providers": 6554, "k_eff": 80}
    trace = {
        "busy_s": 1.0, "window_s": 5.0, "acks_in_slice": 5,
        "module_s": {
            "jit__queue_free": 0.004, "jit__queue_seed": 0.006,
            "jit__transpose_candidates": 0.170, "jit__reverse_finish": 0.020,
            "jit__sparse_auction_phase": 0.5, "jit__reverse_seed": 0.3,
        },
    }
    ctx = {"trace": trace, "shape": shape, "peaks": {"hbm_bytes_per_s": 819e9}}
    need = (36 * 8192 * 80 + 2068 * 6554) * 5
    assert readers.read_metric(spec, ctx) == pytest.approx(
        100.0 * need / 819e9 / 0.2
    )
    assert readers.read_metric(spec, ctx) < 100.0
    trace["module_s"] = {"jit__sparse_auction_phase": 0.5}
    assert readers.read_metric(spec, ctx) is None
    assert readers.read_metric(spec, {**ctx, "trace": None}) is None


def test_the_stage_metrics_add_up_to_the_outside_ones():
    """``rep_* + diff`` is ``repair_ms_per_ack`` and ``wait + host`` is
    ``solve_ms_per_ack``, on the reader's own arithmetic."""
    from benchmarks.lib import readers

    def read(name):
        path = os.path.join(REPO, "benchmarks", "metrics", name + ".json")
        with open(path) as fh:
            return readers.read_metric(json.load(fh), _without(""))

    parts = sum(read(f"repair_{p}_ms_per_ack") for p in (
        "enter", "forward", "tiles", "merge", "diff"))
    assert parts == pytest.approx(read("repair_ms_per_ack"), rel=0.02)
    assert read("solve_wait_ms_per_ack") + read(
        "solve_host_ms_per_ack"
    ) == pytest.approx(read("solve_ms_per_ack"))


# ---- ISSUE 30: the frontier's width is fitted inside the phase kernel

class TestFrontierRows:
    @pytest.mark.parametrize("n_tasks", [512, 358])
    def test_a_warm_chain_runs_one_phase_executable_a_shape(
        self, n_tasks, monkeypatch
    ):
        """512 providers, a full pool and one with slack (whose reverse
        pass runs the kernel in its second shape): the open counts of a
        warm chain cross several rungs, and every segment of every tick
        is the one executable its shape has; the solve builds nothing
        on a warm tick, whatever the open counts."""
        from protocol_tpu.ops import sparse
        from protocol_tpu.utils import jitwitness
        from tests.test_pool_slack import open_session

        monkeypatch.setenv("PROTOCOL_TPU_JIT_WITNESS", "1")
        P = 512
        gen, arena, session = open_session(P, n_tasks)
        phase = sparse._sparse_auction_phase
        traced_before = jitwitness.counts()
        widths, built = [], []
        for tick in range(6):
            mark = TRACER.mark()
            with session.lock:
                if tick:
                    session.apply_delta(*gen.next_delta())
                session.solve()
            stats = dict(arena.last_stats)
            segs = [
                s["attrs"] for s in TRACER.since(mark)
                if s["name"] in ("auction.segment", "auction.reverse")
                and s["attrs"].get("rounds")
            ]
            assert stats["eng_frontier_rows"] == sum(a["rows"] for a in segs)
            widths += [a["rows"] / a["rounds"] for a in segs]
            built.append(phase._cache_size())
            if tick >= 2:
                # (the repair builds its tile programs by the churn's
                # bucket, another module's business)
                assert not [
                    name for name in stats["jit_compiles_delta"]
                    if name.startswith("protocol_tpu.ops.")
                ], (tick, stats["jit_compiles_delta"])
        # traced for a phase entered cold (no state) and for one entered
        # on carried state, and for the reverse pass's shape where there
        # is slack: no executable a width, and no new entry in the jit's
        # own cache after the first warm tick
        traced = jitwitness.delta(traced_before)
        (name,) = [n for n in traced if n.endswith(":_sparse_auction_phase")]
        assert traced[name] <= 2 + (n_tasks < P)
        assert built[-1] == built[1]
        # a segment's mean width between two rungs: its rounds ran at
        # more than one; and some ran at the narrowest alone
        rungs = sparse._FRONTIER_RUNGS
        assert any(w not in rungs + (P,) for w in widths), widths
        assert min(widths) == rungs[0], widths


# ---- ISSUE 40: the device's idle time split by the program span the
# host was in (``benchmarks/lib/idle_split.py``; wired into the trace
# reduction and the generic reader by a later ``benchmark`` PR)

def _idle_split():
    with open(os.path.join(REPO, "benchmarks", "lib", "idle_split.json")) as fh:
        return json.load(fh)


def _layout() -> dict:
    path = os.path.join(REPO, "benchmarks", "lib", "trace_layout.json")
    with open(path) as fh:
        return {**json.load(fh), **_idle_split()["layout"]}


def _ev(name, start, end):
    from types import SimpleNamespace as NS

    return NS(name=name, start_ns=float(start), duration_ns=float(end - start))


def _canned(host_lines, ops=()):
    """A profile as ``ProfileData`` reads: one device plane (``ops``,
    ``(start, end)`` of XLA ops) and the host plane's thread lines, each
    a list of ``(name, start, end)``."""
    from types import SimpleNamespace as NS

    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[_ev("fusion", s, e) for s, e in ops]),
        NS(name="XLA Modules", events=[
            _ev("jit__sparse_auction_phase(1)", s, e) for s, e in ops]),
    ])
    host = NS(name="/host:CPU", lines=[
        NS(name=f"thread {i}", events=[_ev(*e) for e in line])
        for i, line in enumerate(host_lines)
    ])
    return NS(planes=[device, host])


# one request thread: the root 0-100, arena.solve 10-90 and in it
# arena.candidates 10-30, arena.engine 30-80 > auction.segment 40-60 >
# auction.open_count 55-60; the checkpoint's worker on a line of its own
# with no root, 20-95; a line of the harness's own, not the program's
_REQUEST = [
    ("rpc.AssignDelta", 0, 100), ("arena.solve", 10, 90),
    ("arena.candidates", 10, 30), ("arena.engine", 30, 80),
    ("auction.segment", 40, 60), ("auction.open_count", 55, 60),
    ("bench.request", 0, 120),
]
_WORKER = [("ckpt.prefix", 20, 95), ("ckpt.encode", 20, 50)]

IDLE_CASES = {
    # the innermost open span takes the piece, not its parents
    "nested": ([(56, 59)], {"auction.open_count": 3}),
    # a stretch across span boundaries is split piecewise, both sides
    # (and every span between) counted
    "split": ([(50, 70)], {"auction.segment": 5, "auction.open_count": 5,
                           "arena.engine": 10}),
    # no root open: the transport and the client's loop
    "outside": ([(100, 130)], {"(outside)": 30}),
    "straddles_the_root": ([(-10, 5)], {"(outside)": 10,
                                        "rpc.AssignDelta": 5}),
    # the worker's spans never take the request thread's idle time
    "worker_line": ([(25, 35), (85, 95)], {
        "arena.candidates": 5, "arena.engine": 5, "rpc.AssignDelta": 5,
        "arena.solve": 5}),
    "many": ([(0, 10), (30, 40), (60, 80)], {
        "rpc.AssignDelta": 10, "arena.engine": 30}),
}


@pytest.mark.parametrize("case", sorted(IDLE_CASES))
def test_idle_by_span_on_a_canned_trace(case):
    from benchmarks.lib import idle_split

    stretches, want = IDLE_CASES[case]
    got = idle_split.idle_by_span(
        _canned([_REQUEST, _WORKER]), _layout(), stretches
    )
    assert got == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert sum(got.values()) == pytest.approx(
        sum(e - s for s, e in stretches) * 1e-9
    )


@pytest.mark.parametrize("order", ["deeper_second", "deeper_first"])
def test_two_request_threads_at_once_the_deepest_span_wins(order):
    """Two root lines open at once: the deeper span takes the piece,
    and at equal depth the line that comes first in the trace."""
    from benchmarks.lib import idle_split

    shallow = [("rpc.AssignDelta", 0, 100), ("engine.solve", 0, 100)]
    deep = [("rpc.AssignDelta", 50, 150), ("wire.decode", 50, 150),
            ("arena.solve", 60, 90)]
    lines = [shallow, deep] if order == "deeper_second" else [deep, shallow]
    got = idle_split.idle_by_span(_canned(lines), _layout(), [(0, 160)])
    # 0-50 the shallow line alone, 50-60 and 90-100 a tie at equal
    # depth (the first line's), 60-90 the deeper span, 100-150 the deep
    # line alone, 150-160 no root
    tie = "engine.solve" if order == "deeper_second" else "wire.decode"
    want = {"engine.solve": 50, "wire.decode": 50, "arena.solve": 30,
            "(outside)": 10}
    want[tie] += 20
    assert got == pytest.approx({k: v * 1e-9 for k, v in want.items()})


def _reduced(monkeypatch, host_lines, ops):
    """``trace_reduce.reduce_trace`` of a canned trace with its
    ``idle_by_span`` from the very stretches it found (as a traced run
    would give it once the reduction holds it), and the accepted keys
    with and without the split."""
    from benchmarks.lib import idle_split, trace_reduce

    profile = _canned(host_lines, ops)
    layout = _layout()
    plain = trace_reduce.reduce_trace(profile, layout, 1)
    seen = []
    gaps = trace_reduce.gaps

    def keep(merged, lo, hi):
        seen.append(gaps(merged, lo, hi))
        return seen[-1]

    monkeypatch.setattr(trace_reduce, "gaps", keep)
    reduced = trace_reduce.reduce_trace(profile, layout, 1)
    (stretches,) = seen
    reduced["idle_by_span"] = idle_split.idle_by_span(
        profile, layout, stretches
    )
    return plain, reduced


_OPS = [(5, 12), (20, 52), (58, 75), (101, 110)]


def test_the_split_adds_up_to_the_idle_time_and_leaves_the_rest(
    monkeypatch
):
    plain, reduced = _reduced(monkeypatch, [_REQUEST, _WORKER], _OPS)
    # every idle nanosecond of the slice, once
    assert sum(reduced["idle_by_span"].values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"]
    )
    # the accepted keys are what the reduction gives without the split
    assert {k: v for k, v in reduced.items() if k != "idle_by_span"} == plain
    assert set(plain) == {
        "busy_s", "window_s", "module_s", "device_ops", "idle_gaps"
    }


def test_the_six_metrics_add_up_to_device_idle(monkeypatch):
    """On one chip the six sum to ``device_idle_pct`` / 100 x
    ``window_s`` / ``acks_in_slice`` x 1000: the same stretches, the
    same slice, the same divisor."""
    from benchmarks.lib import idle_split, readers

    _, reduced = _reduced(monkeypatch, [_REQUEST, _WORKER], _OPS)
    reduced["acks_in_slice"] = 2
    six = [
        idle_split.read_idle_ms_per_ack(m["read"], reduced)
        for m in _idle_split()["metrics"]
    ]
    idle = readers.read_trace({"kind": "idle_pct"}, reduced, {}, {})
    assert sum(six) == pytest.approx(
        idle / 100 * reduced["window_s"] / 2 * 1e3
    )
    assert all(v >= 0 for v in six)


def test_every_span_of_the_tree_is_read_by_exactly_one_idle_metric():
    import re

    metrics = _idle_split()["metrics"]
    assert len(metrics) == 6
    span = re.compile(_idle_split()["layout"]["program_span"])
    for name in (*TREE, "(outside)"):
        if name != "(outside)":
            assert span.search(name), name
        readers_of = [
            m["name"] for m in metrics
            if any(re.fullmatch(p, name) for p in m["read"]["spans"])
        ]
        assert len(readers_of) == 1, (name, readers_of)


@pytest.mark.parametrize(
    "name", [m["name"] for m in _idle_split()["metrics"]]
)
def test_an_idle_metric_reads_nothing_without_the_split(name):
    """The parent's reduction has no ``idle_by_span``: nothing is read
    and nothing is raised; declared as the accepted entries are."""
    from benchmarks.lib import idle_split

    (spec,) = [m for m in _idle_split()["metrics"] if m["name"] == name]
    assert spec["source"] == "device_trace" and spec["unit"] == "ms"
    assert spec["better"] == "lower" and spec["moves"] == "ack_p50_ms"
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        layers = {m["layer"] for m in json.load(fh)["per_layer"]}
    assert spec["layer"] in layers
    bare = {"busy_s": 1.0, "window_s": 2.0, "module_s": {},
            "device_ops": [], "idle_gaps": [], "acks_in_slice": 3}
    assert idle_split.read_idle_ms_per_ack(spec["read"], bare) is None
    assert idle_split.read_idle_ms_per_ack(spec["read"], None) is None
    split = {**bare, "idle_by_span": {}}
    assert idle_split.read_idle_ms_per_ack(spec["read"], split) == 0.0


def test_the_split_of_a_real_capture_names_the_request_thread(captured):
    """A CPU capture of one warm tick: the request thread's innermost
    spans are the tree's, the worker's never show, and a stretch over
    the whole tick splits into pieces that add up to it."""
    import re

    from benchmarks.lib import idle_split

    layout = _layout()
    pieces = idle_split.timeline(captured, layout)
    names = {n for _, _, n in pieces}
    assert {"rpc.AssignDelta", "engine.solve", "auction.segment",
            "quality.gap", "ckpt.flush"} <= names
    assert not names & {"ckpt.prefix", "ckpt.encode"}
    assert all(a[1] <= b[0] for a, b in zip(pieces, pieces[1:]))
    lo, hi = pieces[0][0] - 1e6, pieces[-1][1] + 1e6
    got = idle_split.idle_by_span(captured, layout, [(lo, hi)])
    assert sum(got.values()) == pytest.approx((hi - lo) * 1e-9)
    assert got["(outside)"] >= 2e-3 * 0.999
    # whatever the tick opened is read by exactly one of the six
    for name in got:
        assert sum(
            any(re.fullmatch(p, name) for p in m["read"]["spans"])
            for m in _idle_split()["metrics"]
        ) == 1, name
