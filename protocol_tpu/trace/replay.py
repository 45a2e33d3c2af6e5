"""Deterministic trace replay: feed a recorded workload through any
engine and transport, verify recorded outcomes bit-for-bit, and localize
the first divergence.

The replayer is the flight recorder's other half. A trace fixes the
exact solve inputs per tick (epoch snapshot + churned-row deltas); the
engines are bit-identical for every thread count (the -mt determinism
contract) and the session/unary seams solve the same padded columns, so
replaying a trace through

  * ``native-mt`` / ``sinkhorn-mt`` in-process (the arena),
  * the v1 unary wire (full snapshot per tick, servicer warm arena), or
  * the v2 session wire (streamed snapshot + AssignDelta ticks)

must reproduce the recorded ``provider_for_task`` bit-for-bit. When it
does not, the report names the first divergent tick and the exact row
set — a solver regression localizes to "tick 12, rows [841, 2207]"
instead of "the bench got slower". ``engine="jax"`` replays through the
accelerator-path warm arena (parallel/jax_arena.py) on every transport:
bit-identical against a jax-recorded golden, honest divergence + the
``compare()`` tolerance table against a native recording.

``compare()`` replays the same trace under two configs side by side —
the A/B harness every perf PR can now cite instead of hand-rolled bench
deltas.
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

from protocol_tpu.obs.spans import TRACER as _tracer, span_dicts_compact
from protocol_tpu.proto import scheduler_pb2 as pb
from protocol_tpu.proto import wire
from protocol_tpu.trace import format as tfmt

_ENGINES = ("native-mt", "sinkhorn-mt", "jax")
_TRANSPORTS = ("inproc", "wire-v1", "wire-v2")
_ARENA_ENGINE = {
    "native-mt": "auction",
    "sinkhorn-mt": "sinkhorn",
    "jax": "jax",
}


def parse_engine(kernel: str) -> tuple[str, int]:
    """``native-mt[:N]`` / ``sinkhorn-mt[:N]`` / ``jax[:D]`` ->
    (engine, threads — sharded-gen devices for the jax engine)."""
    base, _, suffix = kernel.partition(":")
    if base not in _ENGINES:
        raise ValueError(
            f"engine must be one of {_ENGINES}, got {kernel!r}"
        )
    return base, (int(suffix) if suffix else 0)


def _kernel_str(engine: str, threads: int) -> str:
    return f"{engine}:{threads}" if threads else engine


def iter_input_ticks(trace: tfmt.Trace):
    """Yield ``(tick, p_cols, r_cols, delta_or_None)`` with the columns
    updated through each recorded delta (tick 0 = the snapshot itself).
    Columns are fresh copies per churned column (copy-on-write), so
    callers may hold references across ticks."""
    snap = trace.snapshot
    if snap is None:
        raise ValueError(f"{trace.path}: no snapshot frame (empty trace?)")
    p_cols = dict(snap.p_cols)
    r_cols = dict(snap.r_cols)
    yield 0, p_cols, r_cols, None
    for i, d in enumerate(trace.deltas, start=1):
        # fresh dicts BEFORE mutating: the previously-yielded dicts must
        # never change under a caller holding them
        p_cols, r_cols = dict(p_cols), dict(r_cols)
        for rows, delta, cols in (
            (d.provider_rows, d.p_cols, p_cols),
            (d.task_rows, d.r_cols, r_cols),
        ):
            if not rows.size:
                continue
            for name, vals in delta.items():
                col = cols[name].copy()
                col[rows] = vals
                cols[name] = col
        yield i, p_cols, r_cols, d


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class _InprocArena:
    """Transport "inproc": the session path minus the wire — identical
    pow2 padding (session_store._pad_cols) and arena construction, so
    in-process and wire-v2 replays are bit-identical by construction.
    ``engine="jax"`` gets the warm accelerator-path arena through the
    same factory the servicer uses (threads = sharded-gen devices)."""

    def __init__(self, snap: tfmt.Snapshot, engine: str, threads: int):
        from protocol_tpu.services.session_store import make_solve_arena

        self.engine = engine
        self.threads = threads
        self.top_k = max(int(snap.top_k) or 64, 1)
        self.arena = make_solve_arena(
            _ARENA_ENGINE[engine], k=self.top_k, threads=threads
        )
        self.weights = None  # set per solve

    def solve(self, snap, p_cols, r_cols) -> tuple[np.ndarray, dict]:
        from protocol_tpu.services.session_store import _pad_cols

        from protocol_tpu.ops.cost import CostWeights

        n_p, n_t = snap.n_providers, snap.n_tasks
        pp = _pad_cols(p_cols, n_p)
        rp = _pad_cols(r_cols, n_t)
        w = CostWeights(*snap.weights)
        p4t = self.arena.solve(tfmt._as_ns(pp), tfmt._as_ns(rp), w)
        return np.asarray(p4t, np.int32)[:n_t], self.arena.last_stats

    def close(self) -> None:
        pass


class _WireTransport:
    """Loopback gRPC replay: "wire-v1" ships a full v1 snapshot per tick
    (the servicer's warm unary arena solves the churn); "wire-v2" runs
    the real session protocol (streamed snapshot + AssignDelta)."""

    def __init__(self, snap: tfmt.Snapshot, engine: str, threads: int,
                 wire_version: str):
        from protocol_tpu.services.scheduler_grpc import (
            SchedulerBackendClient,
            serve,
        )

        self.kernel = _kernel_str(engine, threads)
        self.top_k = max(int(snap.top_k) or 64, 1)
        self.wire_version = wire_version
        port = _free_port()
        self.server = serve(f"127.0.0.1:{port}")
        self.client = SchedulerBackendClient(f"127.0.0.1:{port}")
        self._fp: Optional[str] = None
        self._tick = 0
        self.bytes_out = 0
        self.bytes_in = 0

    def _request_v2(self, snap, p_cols, r_cols) -> pb.AssignRequestV2:
        return pb.AssignRequestV2(
            providers=wire.encode_providers_v2(tfmt._as_ns(p_cols)),
            requirements=wire.encode_requirements_v2(tfmt._as_ns(r_cols)),
            weights=pb.CostWeights(
                price=snap.weights[0], load=snap.weights[1],
                proximity=snap.weights[2], priority=snap.weights[3],
            ),
            kernel=self.kernel, top_k=self.top_k, eps=snap.eps,
            max_iters=snap.max_iters,
        )

    def solve(self, snap, p_cols, r_cols, delta=None):
        if self.wire_version == "v1":
            from protocol_tpu.services.scheduler_grpc import encoded_to_proto

            from protocol_tpu.ops.cost import CostWeights

            req = encoded_to_proto(
                tfmt._as_ns(p_cols), tfmt._as_ns(r_cols),
                CostWeights(*snap.weights),
                kernel=self.kernel, top_k=self.top_k, eps=snap.eps,
                max_iters=snap.max_iters,
            )
            resp = self.client.assign(req, timeout=600)
            self.bytes_out += req.ByteSize()
            self.bytes_in += resp.ByteSize()
            p4t = np.fromiter(
                resp.provider_for_task, np.int32,
                count=len(resp.provider_for_task),
            )
            return p4t, {"solve_ms": resp.solve_ms}

        # ---- v2 session protocol
        if self._fp is None:
            w = tfmt._as_ns(
                dict(zip(
                    ("price", "load", "proximity", "priority"), snap.weights
                ))
            )
            self._fp = wire.epoch_fingerprint(
                p_cols, r_cols, w, self.kernel, self.top_k, snap.eps,
                snap.max_iters,
            )
            req = self._request_v2(snap, p_cols, r_cols)
            chunks = list(
                wire.chunk_snapshot("replay", self._fp, req)
            )
            resp = self.client.open_session(iter(chunks), timeout=600)
            if not resp.ok:
                raise RuntimeError(f"OpenSession refused: {resp.error}")
            self.bytes_out += sum(len(c.payload) for c in chunks)
            self.bytes_in += resp.ByteSize()
            self._tick = 0
            p4t = wire.unblob(resp.result.provider_for_task, np.int32)
            return p4t, {"solve_ms": resp.result.solve_ms}

        self._tick += 1
        req = pb.AssignDeltaRequest(
            session_id="replay", epoch_fingerprint=self._fp, tick=self._tick
        )
        if delta is not None and delta.provider_rows.size:
            req.provider_rows.CopyFrom(
                wire.blob(delta.provider_rows, np.int32)
            )
            req.providers.CopyFrom(
                wire.encode_providers_v2(tfmt._as_ns(delta.p_cols))
            )
        if delta is not None and delta.task_rows.size:
            req.task_rows.CopyFrom(wire.blob(delta.task_rows, np.int32))
            req.requirements.CopyFrom(
                wire.encode_requirements_v2(tfmt._as_ns(delta.r_cols))
            )
        resp = self.client.assign_delta(req, timeout=600)
        if not resp.session_ok:
            raise RuntimeError(
                f"AssignDelta tick {self._tick} refused: {resp.error}"
            )
        self.bytes_out += req.ByteSize()
        self.bytes_in += resp.ByteSize()
        p4t = wire.unblob(resp.result.provider_for_task, np.int32)
        return p4t, {"solve_ms": resp.result.solve_ms}

    def close(self) -> None:
        self.client.close()
        self.server.stop(grace=None)


def replay(
    trace_path: str,
    engine: Optional[str] = None,
    threads: Optional[int] = None,
    transport: str = "inproc",
    verify: bool = True,
    record_path: Optional[str] = None,
    max_ticks: Optional[int] = None,
    keep_p4t: bool = False,
) -> dict:
    """Replay a trace. Returns the report dict; ``report["divergence"]``
    is None when every verified tick reproduced the recorded assignments
    bit-for-bit (the empty divergence report), else it names the first
    divergent tick and row set.

    ``engine``/``threads`` default to the trace's recorded kernel string;
    ``transport`` is inproc | wire-v1 | wire-v2. ``record_path`` writes a
    new trace with this replay's outcomes (how golden traces are made).
    """
    if transport not in _TRANSPORTS:
        raise ValueError(
            f"transport must be one of {_TRANSPORTS}, got {transport!r}"
        )
    trace = tfmt.read_trace(trace_path)
    snap = trace.snapshot
    if snap is None:
        raise ValueError(f"{trace_path}: no snapshot frame")
    if engine:
        eng, eng_threads = parse_engine(engine)
    else:
        try:
            eng, eng_threads = parse_engine(snap.kernel or "native-mt")
        except ValueError:
            # captured from a kernel with no replay engine (e.g. the jax
            # "auction"/"greedy" unary kernels): refuse with direction
            # instead of a bare parse error — replaying through a
            # different engine cannot verify bit-for-bit anyway
            raise ValueError(
                f"{trace_path} records kernel {snap.kernel!r}, which has "
                f"no replay engine; pass engine= (one of {_ENGINES}) to "
                "replay it through an explicit engine (outcome "
                "verification will then report honest divergence)"
            )
    n_threads = eng_threads if threads is None else int(threads)

    # Pin the float pipeline to the one that PRODUCED the trace:
    # bit-for-bit outcome verification is only meaningful under the same
    # per-ISA pipeline (the determinism contract is within-ISA). Pre-ISA
    # traces carry no tag and were recorded by the historical scalar
    # pipeline. A host that cannot run the recorded ISA clamps down and
    # verification reports honest divergence (never a crash). The jax
    # engine never touches the native pipeline — no pin.
    pinned_isa: Optional[str] = None
    prev_isa_env: Optional[str] = None
    prev_isa_eff: Optional[str] = None
    effective_isa: Optional[str] = None
    if eng != "jax":
        import os as _os

        from protocol_tpu import native as _native

        pinned_isa = str(trace.meta.get("recorded_isa", "scalar"))
        prev_isa_env = _os.environ.get("PROTOCOL_TPU_NATIVE_ISA")
        try:
            prev_isa_eff = _native.current_isa()
            effective_isa = _native.set_isa(pinned_isa)
        except _native.NativeBuildError:
            pinned_isa = None  # no toolchain: backends will fail honestly

    if transport == "inproc":
        backend = _InprocArena(snap, eng, n_threads)
    else:
        backend = _WireTransport(
            snap, eng, n_threads, transport.split("-")[1]
        )

    writer = None
    if record_path is not None:
        meta = dict(trace.meta)
        meta.pop("version", None)
        meta.update(
            recorded_engine=eng, recorded_threads=n_threads,
            recorded_transport=transport, source_trace=trace_path,
        )
        if effective_isa is not None:
            # provenance for the NEXT replay's pin (and the CI
            # replay-identity job's audit of committed goldens)
            meta["recorded_isa"] = effective_isa
        writer = tfmt.TraceWriter(record_path, meta=meta)
        # the recorded epoch carries the kernel that actually solved it
        rsnap = tfmt.Snapshot(
            trace_id=snap.trace_id, fingerprint="", p_cols=snap.p_cols,
            r_cols=snap.r_cols, weights=snap.weights,
            kernel=_kernel_str(eng, n_threads), top_k=snap.top_k,
            eps=snap.eps, max_iters=snap.max_iters,
        )
        fp = wire.epoch_fingerprint(
            snap.p_cols, snap.r_cols,
            tfmt._as_ns(dict(zip(
                ("price", "load", "proximity", "priority"), snap.weights
            ))),
            rsnap.kernel, max(int(snap.top_k) or 64, 1), snap.eps,
            snap.max_iters,
        )
        writer.write_snapshot(snap.trace_id, fp, rsnap.request_v2())

    report: dict = {
        "trace": trace_path,
        "engine": eng,
        "threads": n_threads,
        "transport": transport,
        "recorded_kernel": snap.kernel,
        "providers": snap.n_providers,
        "tasks": snap.n_tasks,
        "ticks": 0,
        "verified_ticks": 0,
        "divergence": None,
        "tick_wall_ms": [],
        "assigned": [],
    }
    if eng == "jax":
        # the backend the jax engine's walls and plans came from (a
        # golden recorded under jax:cpu is not expected to verify on
        # another float pipeline)
        from protocol_tpu.utils.platform import device_summary

        report.update(device_summary())
    p4ts: list = []
    tick_stats: list = []  # scalar per-tick stats (quality plane)
    try:
        for tick, p_cols, r_cols, delta in iter_input_ticks(trace):
            if max_ticks is not None and tick >= max_ticks:
                break
            t0 = time.perf_counter()
            # root span per tick: the arena/servicer/client spans this
            # solve produces stitch under it, and a recording replay
            # lands them in the OUTCOME frame for the obs report
            mark = _tracer.mark()
            with _tracer.span("replay.tick", tick=tick) as root:
                if isinstance(backend, _WireTransport):
                    p4t, stats = backend.solve(snap, p_cols, r_cols, delta)
                else:
                    p4t, stats = backend.solve(snap, p_cols, r_cols)
            wall_ms = (time.perf_counter() - t0) * 1e3
            report["ticks"] += 1
            report["tick_wall_ms"].append(round(wall_ms, 3))
            report["assigned"].append(int((p4t >= 0).sum()))
            tick_stats.append({
                k: v for k, v in (stats or {}).items()
                if isinstance(v, (int, float, bool))
            })
            if keep_p4t:
                p4ts.append(p4t)
            if writer is not None:
                if delta is not None:
                    writer.write_delta_cols(
                        tick, delta.provider_rows, delta.p_cols,
                        delta.task_rows, delta.r_cols, events=delta.events,
                    )
                metrics = {"wall_ms": round(wall_ms, 3)}
                metrics.update(
                    {k: v for k, v in (stats or {}).items()
                     if isinstance(v, (int, float, bool, str))}
                )
                if root is not None:
                    spans = _tracer.since(mark, trace=root["trace"])
                    if spans:
                        metrics["trace_id"] = root["trace"]
                        metrics["spans"] = span_dicts_compact(spans)
                writer.write_outcome(tick, p4t, metrics=metrics)
            if verify:
                rec = trace.outcome_for(tick)
                if rec is not None:
                    report["verified_ticks"] += 1
                    if not np.array_equal(p4t, rec.provider_for_task):
                        rows = np.flatnonzero(
                            p4t != rec.provider_for_task
                        )
                        report["divergence"] = {
                            "tick": tick,
                            "n_rows": int(rows.size),
                            "rows": rows[:64].tolist(),
                            "recorded_assigned": rec.num_assigned,
                            "replayed_assigned": int((p4t >= 0).sum()),
                        }
                        break  # localized: first divergent tick + rows
    finally:
        backend.close()
        if writer is not None:
            writer.close()
        if pinned_isa is not None:
            # restore the caller's ISA selection (the pin is scoped to
            # this replay, not the process): the env var goes back to
            # its prior state and the engine back to its prior
            # EFFECTIVE isa (which may be a baked variant default, not
            # scalar)
            import os as _os

            from protocol_tpu import native as _native

            if prev_isa_env is None:
                _os.environ.pop("PROTOCOL_TPU_NATIVE_ISA", None)
            else:
                _os.environ["PROTOCOL_TPU_NATIVE_ISA"] = prev_isa_env
            try:
                if prev_isa_eff is not None:
                    _native._apply_isa(_native.load(), prev_isa_eff)
            except _native.NativeBuildError:
                pass

    walls = report["tick_wall_ms"]
    if walls:
        report["cold_ms"] = walls[0]
        if len(walls) > 1:
            report["warm_mean_ms"] = round(float(np.mean(walls[1:])), 3)
            report["warm_median_ms"] = round(
                float(np.median(walls[1:])), 3
            )
            # true distribution numbers (obs plane): what the fleet/
            # streaming gates will hold, not just means
            from protocol_tpu.obs.metrics import percentiles_ms

            report["warm_percentiles"] = percentiles_ms(walls[1:])
    quality = _aggregate_quality(tick_stats)
    if quality is not None:
        report["quality"] = quality
    if isinstance(backend, _WireTransport):
        report["wire_bytes_out"] = backend.bytes_out
        report["wire_bytes_in"] = backend.bytes_in
    if keep_p4t:
        report["p4ts"] = p4ts
    return report


def _aggregate_quality(tick_stats: list) -> Optional[dict]:
    """Roll the per-tick quality scalars (arena last_stats through the
    inproc backends; wire replays report quality server-side) into the
    replay report — the shared canonical roll-up (certified gap, plan
    churn over warm ticks, starvation, outcome-cause totals with the
    zero-unexplained invariant the CI quality gate holds)."""
    from protocol_tpu.obs.quality import aggregate_quality

    return aggregate_quality(tick_stats)


def compare(
    trace_path: str,
    config_a: dict,
    config_b: dict,
    max_ticks: Optional[int] = None,
) -> dict:
    """Replay one trace under two configs side by side (the A/B perf
    harness). Each config is {engine, threads, transport}. Reports both
    replays' timing/assignment stats plus a tick-wise matching diff."""
    a = replay(
        trace_path, verify=False, keep_p4t=True, max_ticks=max_ticks,
        **config_a,
    )
    b = replay(
        trace_path, verify=False, keep_p4t=True, max_ticks=max_ticks,
        **config_b,
    )
    n = min(len(a["p4ts"]), len(b["p4ts"]))
    first_diff = None
    diff_rows = 0
    for t in range(n):
        d = int((a["p4ts"][t] != b["p4ts"][t]).sum())
        diff_rows += d
        if d and first_diff is None:
            first_diff = t
    out = {
        "trace": trace_path,
        "a": {k: v for k, v in a.items() if k != "p4ts"},
        "b": {k: v for k, v in b.items() if k != "p4ts"},
        "identical": first_diff is None,
        "first_divergent_tick": first_diff,
        "divergent_rows_total": diff_rows,
    }
    if a.get("warm_mean_ms") and b.get("warm_mean_ms"):
        out["warm_speedup_b_over_a"] = round(
            a["warm_mean_ms"] / b["warm_mean_ms"], 3
        )
    # quality deltas, not just bit-identity: the A/B answer for "the
    # plans differ — by how MUCH, and who pays" (the streaming roadmap
    # item gates its bounded-staleness contract on exactly this)
    qa, qb = a.get("quality"), b.get("quality")
    if qa and qb:
        delta = {
            "gap_per_task_delta": round(
                qb["gap_per_task_mean"] - qa["gap_per_task_mean"], 6
            ),
            "starve_max_delta": qb["starve_max"] - qa["starve_max"],
        }
        if qa.get("plan_cost_mean"):
            delta["plan_cost_ratio_b_over_a"] = round(
                qb["plan_cost_mean"] / qa["plan_cost_mean"], 6
            )
        if (
            qa.get("churn_ratio_mean") is not None
            and qb.get("churn_ratio_mean") is not None
        ):
            delta["churn_ratio_delta"] = round(
                qb["churn_ratio_mean"] - qa["churn_ratio_mean"], 6
            )
        out["quality_delta"] = delta
    if a.get("assigned") and b.get("assigned"):
        out["assigned_min_delta"] = min(b["assigned"]) - min(a["assigned"])
    return out
