"""Deterministic event-stream replay + the batch shadow harness.

A *stream trace* is an ordinary PTTRACE1 file whose DELTA frames each
carry ONE churn event (rows + full-state values) with the stream meta
(``{kind, source, seq, at_us}``) in the frame's events list — the synth
factory (``trace.synth.synth_event_trace``) writes them, and
``stream_replay`` feeds them through a :class:`StreamEngine` event by
event:

  * outcomes recorded per EVENT (tick 0 = the priming cold solve), so
    replay verification localizes a divergence to the first EVENT, not
    the first batch tick;
  * ``chaos=`` runs the same trace through a seeded drop/dup/reorder
    delivery schedule (``faults.plan.event_delivery_order``) — dropped
    events are retransmitted later, duplicates and overtaken events hit
    the dedup ladder, and the FINAL reconciled plan must still be
    bit-identical to the fault-free replay's (the convergence gate);
  * ``batch_shadow_replay`` solves the SAME trace with a fresh
    always-cold arena at each reconcile boundary: the reconciliation
    bit-identity oracle ("a full solve on the accumulated columns"),
    which the stream engine's reconcile must match bit-for-bit.
"""

from __future__ import annotations

import contextlib
import time
from typing import Optional

import numpy as np

from protocol_tpu.obs.metrics import percentiles_ms
from protocol_tpu.stream.engine import StreamEngine
from protocol_tpu.stream.events import event_from_delta
from protocol_tpu.trace import format as tfmt

_ARENA_ENGINE = {"native-mt": "auction", "sinkhorn-mt": "sinkhorn"}


@contextlib.contextmanager
def _pin_recorded_isa(meta: dict):
    """Pin the native float pipeline to the one that PRODUCED the
    trace for the duration of a replay — the same contract as the batch
    replay (trace/replay.py): bit-for-bit outcome verification is only
    meaningful under the same per-ISA pipeline, and pre-ISA traces were
    recorded by the historical scalar pipeline. A host that cannot run
    the recorded ISA clamps down and verification reports honest
    divergence. Yields the EFFECTIVE isa (None when no native
    toolchain) and restores the caller's env var + effective ISA on
    exit — the pin is scoped to the replay, not the process."""
    import os as _os

    from protocol_tpu import native as _native

    pinned = str(meta.get("recorded_isa", "scalar"))
    prev_env = _os.environ.get("PROTOCOL_TPU_NATIVE_ISA")
    prev_eff: Optional[str] = None
    effective: Optional[str] = None
    try:
        prev_eff = _native.current_isa()
        effective = _native.set_isa(pinned)
    except _native.NativeBuildError:
        pass  # no toolchain: arena construction will fail honestly
    try:
        yield effective
    finally:
        if prev_env is None:
            _os.environ.pop("PROTOCOL_TPU_NATIVE_ISA", None)
        else:
            _os.environ["PROTOCOL_TPU_NATIVE_ISA"] = prev_env
        try:
            if prev_eff is not None:
                _native._apply_isa(_native.load(), prev_eff)
        except _native.NativeBuildError:
            pass


def _open_arena(snap: tfmt.Snapshot, engine: str, threads: int):
    """Prime a padded arena from a trace snapshot — identical padding
    and construction to the session/in-proc replay paths, so stream and
    batch replays share bit-identity by construction. Returns
    (arena, weights, padded p_cols, padded r_cols)."""
    from protocol_tpu.native.arena import NativeSolveArena
    from protocol_tpu.ops.cost import CostWeights
    from protocol_tpu.services.session_store import _pad_cols

    if engine not in _ARENA_ENGINE:
        raise ValueError(
            f"stream replay engine must be one of "
            f"{tuple(_ARENA_ENGINE)}, got {engine!r}"
        )
    top_k = max(int(snap.top_k) or 64, 1)
    arena = NativeSolveArena(
        k=top_k, threads=threads, engine=_ARENA_ENGINE[engine]
    )
    pp = _pad_cols(snap.p_cols, snap.n_providers)
    rp = _pad_cols(snap.r_cols, snap.n_tasks)
    w = CostWeights(*snap.weights)
    arena.solve(tfmt._as_ns(pp), tfmt._as_ns(rp), w)
    return arena, w, pp, rp


def _events_of(trace: tfmt.Trace) -> list:
    evs = []
    for d in trace.deltas:
        ev = event_from_delta(d)
        if ev is None:
            raise ValueError(
                f"{trace.path}: delta tick {d.tick} carries no stream "
                "event meta — not a stream trace (synth one with "
                "`python -m protocol_tpu.stream synth`)"
            )
        evs.append(ev)
    return evs


def stream_replay(
    trace_path: str,
    engine: Optional[str] = None,
    threads: Optional[int] = None,
    reconcile_every: Optional[int] = None,
    gap_ceiling: Optional[float] = None,
    verify: bool = True,
    record_path: Optional[str] = None,
    chaos=None,
    final_reconcile: bool = True,
    keep_recon_p4ts: bool = False,
    extra_events: Optional[list] = None,
) -> dict:
    """Replay a stream trace event by event. Returns the report dict;
    ``report["divergence"]`` is None when every verified event
    reproduced the recorded plan bit-for-bit.

    ``chaos`` is a ``faults.plan.ChaosConfig`` (or None): events are
    delivered in the chaos'd order with duplicates injected; recorded-
    outcome verification is skipped (intermediate plans legitimately
    differ) and the caller compares final reconciled plans instead.

    ``extra_events`` are :class:`StreamEvent`s applied IN ORDER after
    the trace's events (never chaos'd) — the distributed firehose
    driver's storm/pad injections, so its fault-free baseline replays
    the exact event multiset a drilled fleet session absorbed."""
    trace = tfmt.read_trace(trace_path)
    with _pin_recorded_isa(trace.meta) as effective_isa:
        return _stream_replay(
            trace, trace_path, engine, threads, reconcile_every,
            gap_ceiling, verify, record_path, chaos, final_reconcile,
            keep_recon_p4ts, effective_isa, extra_events,
        )


def _stream_replay(
    trace: tfmt.Trace,
    trace_path: str,
    engine: Optional[str],
    threads: Optional[int],
    reconcile_every: Optional[int],
    gap_ceiling: Optional[float],
    verify: bool,
    record_path: Optional[str],
    chaos,
    final_reconcile: bool,
    keep_recon_p4ts: bool,
    effective_isa: Optional[str],
    extra_events: Optional[list] = None,
) -> dict:
    from protocol_tpu.trace.replay import parse_engine

    snap = trace.snapshot
    if snap is None:
        raise ValueError(f"{trace_path}: no snapshot frame")
    if engine:
        eng, eng_threads = parse_engine(engine)
    else:
        eng, eng_threads = parse_engine(snap.kernel or "native-mt")
    n_threads = eng_threads if threads is None else int(threads)
    n_recon = int(
        reconcile_every
        if reconcile_every is not None
        else trace.meta.get("reconcile_every", 64)
    )

    arena, weights, _pp, _rp = _open_arena(snap, eng, n_threads)
    se = StreamEngine(
        arena, weights,
        reconcile_every=n_recon,
        gap_ceiling=gap_ceiling,
    )
    n_t = snap.n_tasks

    events = _events_of(trace)
    order = list(range(len(events)))
    if chaos is not None and chaos.active():
        from protocol_tpu.faults.plan import (
            FaultSchedule,
            event_delivery_order,
        )

        order = event_delivery_order(FaultSchedule(chaos), len(events))
    if extra_events:
        # injected (storm/pad) events are appended AFTER the trace's
        # delivery order, always in-order: their sentinel seq tiers
        # make the converged columns order-independent anyway (see
        # dstream.fanout), but recorded-outcome verification only
        # covers the trace prefix either way
        base = len(events)
        events = events + list(extra_events)
        order = order + list(range(base, len(events)))

    writer = None
    if record_path is not None:
        meta = dict(trace.meta)
        meta.pop("version", None)
        meta.update(
            stream=True,
            reconcile_every=n_recon,
            recorded_engine=eng,
            recorded_threads=n_threads,
            source_trace=trace_path,
        )
        if effective_isa is not None:
            # provenance for the NEXT replay's pin (and the CI
            # replay-identity job's audit of committed goldens)
            meta["recorded_isa"] = effective_isa
        writer = tfmt.TraceWriter(record_path, meta=meta)
        writer.write_snapshot(
            snap.trace_id, snap.fingerprint, snap.request_v2()
        )
        writer.write_outcome(
            0, np.asarray(arena._p4t, np.int32)[:n_t],
            metrics={
                k: v for k, v in arena.last_stats.items()
                if isinstance(v, (int, float, bool, str))
            },
        )

    report: dict = {
        "trace": trace_path,
        "engine": eng,
        "threads": n_threads,
        "reconcile_every": n_recon,
        "providers": snap.n_providers,
        "tasks": n_t,
        "events": 0,
        "extra_events": len(extra_events or ()),
        "verified_events": 0,
        "divergence": None,
        "deduped": 0,
        "reconciles": 0,
        "gap_max": 0.0,
        "divergence_rows_max": 0,
        "cand_cold_passes": 0,
        "event_wall_ms": [],
        "reconcile_wall_ms": [],
        "recon_ticks": [],
    }
    if eng == "jax":
        from protocol_tpu.utils.platform import device_summary

        report.update(device_summary())
    recon_p4ts: list = []
    gap_every_event: list = []
    delivered = 0
    try:
        for idx in order:
            ev = events[idx]
            t0 = time.perf_counter()
            res = se.apply(ev)
            wall_ms = (time.perf_counter() - t0) * 1e3
            delivered += 1
            report["events"] += 1
            report["cand_cold_passes"] += int(
                res.stats.get("cand_cold_passes", 0)
            )
            if res.reconciled:
                report["reconcile_wall_ms"].append(round(wall_ms, 3))
                report["recon_ticks"].append(delivered)
                if keep_recon_p4ts:
                    recon_p4ts.append(res.plan[:n_t].copy())
            elif not res.deduped:
                report["event_wall_ms"].append(round(wall_ms, 3))
            gap_every_event.append(res.gap_per_task)
            if writer is not None:
                writer.write_delta_cols(
                    delivered, ev.provider_rows, ev.p_cols or None,
                    ev.task_rows, ev.r_cols or None, events=[ev.meta()],
                )
                writer.write_outcome(
                    delivered, res.plan[:n_t],
                    metrics={
                        "apply_ms": round(res.apply_ms, 3),
                        "gap_per_task": res.gap_per_task,
                        "divergence_rows": res.divergence_rows,
                        "reconciled": res.reconciled,
                        "deduped": res.deduped,
                        "repair_rows": res.repair_rows,
                        "kind": ev.kind,
                    },
                )
            if verify and chaos is None:
                rec = trace.outcome_for(delivered)
                if rec is not None:
                    report["verified_events"] += 1
                    got = res.plan[:n_t]
                    if not np.array_equal(got, rec.provider_for_task):
                        rows = np.flatnonzero(
                            got != rec.provider_for_task
                        )
                        report["divergence"] = {
                            "event": delivered,
                            "kind": ev.kind,
                            "n_rows": int(rows.size),
                            "rows": rows[:64].tolist(),
                        }
                        break
        if final_reconcile and se.events_since_reconcile > 0 and (
            report["divergence"] is None
        ):
            res = se.reconcile()
            report["reconciles_final"] = True
            report["recon_ticks"].append(delivered)
            report["reconcile_wall_ms"].append(round(res.apply_ms, 3))
            if keep_recon_p4ts:
                recon_p4ts.append(res.plan[:n_t].copy())
    finally:
        if writer is not None:
            writer.close()

    snap_eng = se.snapshot()
    report["deduped"] = snap_eng["events_deduped"]
    report["reconciles"] = snap_eng["reconciles"]
    report["events_stale"] = snap_eng["events_stale"]
    report["gap_max"] = snap_eng["gap_max"]
    report["gap_served_max"] = snap_eng["gap_served_max"]
    report["divergence_rows_max"] = snap_eng["divergence_max"]
    report["gap_per_event"] = [round(g, 6) for g in gap_every_event]
    report["assigned_last"] = int((arena._p4t[:n_t] >= 0).sum())
    if report["event_wall_ms"]:
        report["event_percentiles"] = percentiles_ms(
            report["event_wall_ms"]
        )
    if keep_recon_p4ts:
        report["recon_p4ts"] = recon_p4ts
    return report


def batch_shadow_replay(
    trace_path: str,
    boundaries: list,
    engine: Optional[str] = None,
    threads: Optional[int] = None,
) -> dict:
    """The reconciliation oracle: apply the trace's events cumulatively
    to the snapshot columns and run a FULL COLD batch solve at each
    boundary (event counts, 1-based) with a fresh always-cold arena —
    "the equivalent batch replay" the stream engine's reconcile must be
    bit-identical to. Returns {"p4ts": [plan per boundary], ...}."""
    trace = tfmt.read_trace(trace_path)
    # the oracle must solve under the SAME recorded pipeline as the
    # stream replay it is compared against, or the bit-identity gate
    # would report cross-ISA float noise as a reconcile bug
    with _pin_recorded_isa(trace.meta):
        return _batch_shadow_replay(trace, trace_path, boundaries,
                                    engine, threads)


def _batch_shadow_replay(
    trace: tfmt.Trace,
    trace_path: str,
    boundaries: list,
    engine: Optional[str],
    threads: Optional[int],
) -> dict:
    from protocol_tpu.trace.replay import parse_engine

    snap = trace.snapshot
    if snap is None:
        raise ValueError(f"{trace_path}: no snapshot frame")
    if engine:
        eng, eng_threads = parse_engine(engine)
    else:
        eng, eng_threads = parse_engine(snap.kernel or "native-mt")
    n_threads = eng_threads if threads is None else int(threads)

    from protocol_tpu.native.arena import NativeSolveArena
    from protocol_tpu.ops.cost import CostWeights
    from protocol_tpu.services.session_store import _pad_cols

    top_k = max(int(snap.top_k) or 64, 1)
    # cold_every=0: every solve re-grounds — the batch-shadow arena is
    # the "full batch solve on the accumulated columns" oracle, with no
    # warm path dependence on intermediate windows
    arena = NativeSolveArena(
        k=top_k, threads=n_threads, engine=_ARENA_ENGINE[eng],
        cold_every=0,
    )
    w = CostWeights(*snap.weights)
    p_cols = {n: a.copy() for n, a in snap.p_cols.items()}
    r_cols = {n: a.copy() for n, a in snap.r_cols.items()}
    events = _events_of(trace)
    n_t = snap.n_tasks
    p4ts: list = []
    walls: list = []
    want = sorted(int(b) for b in boundaries)
    for i, ev in enumerate(events, start=1):
        for rows, vals, cols in (
            (ev.provider_rows, ev.p_cols, p_cols),
            (ev.task_rows, ev.r_cols, r_cols),
        ):
            if rows is None or not np.asarray(rows).size:
                continue
            for name, v in vals.items():
                cols[name][np.asarray(rows)] = v
        if want and i == want[0]:
            want.pop(0)
            t0 = time.perf_counter()
            pp = _pad_cols(p_cols, snap.n_providers)
            rp = _pad_cols(r_cols, snap.n_tasks)
            p4t = arena.solve(tfmt._as_ns(pp), tfmt._as_ns(rp), w)
            walls.append(round((time.perf_counter() - t0) * 1e3, 3))
            p4ts.append(np.asarray(p4t, np.int32)[:n_t].copy())
    return {
        "trace": trace_path,
        "engine": eng,
        "threads": n_threads,
        "boundaries": sorted(int(b) for b in boundaries),
        "p4ts": p4ts,
        "solve_wall_ms": walls,
    }
