"""Concurrent-trace load harness: H sessions x T tenants over real gRPC.

``python -m protocol_tpu.fleet.loadgen`` replays recorded (or
synthesized — trace/synth is the single workload home) traces
CONCURRENTLY against one servicer over a real localhost gRPC seam: each
session runs the full wire-v2 session protocol (streamed snapshot, then
per-tick ``AssignDelta`` with only churned rows), handles
RESOURCE_EXHAUSTED-style refusals exactly like the production client
(bounded retry, then re-open from its own authoritative columns), and
records client-observed per-tick walls.

The report joins three views:

  * client side — per-tenant p50/p99 warm-tick latency (true merged
    histograms), min assigned fraction, refusal/reopen counts;
  * server side — the obs plane's snapshot (per-tenant histograms,
    shard occupancy, admission counters, budget fairness gauge), the
    same data the /metrics endpoint scrapes;
  * fairness — Jain's index over per-session warm throughput
    (demand-normalized: every session wants the same tick rate, so a
    starved session drags the index below 1 regardless of which tenant
    it belongs to).

The scaling model extrapolates the measured aggregate warm throughput
from this host's core count to real machines: the solve is CPU-bound,
the engines are thread-count invariant, and session locks are sharded,
so steady-state throughput scales ~linearly with cores until the wire
or the delta codec saturates — the model states its assumption instead
of hiding it.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import tempfile
import threading
import time
from typing import Optional

import numpy as np

from protocol_tpu.fleet.admission import jain_index
from protocol_tpu.obs.metrics import LatencyHistogram, tenant_of


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _server_device(address: str) -> dict:
    """``platform`` / ``device_kind`` / ``device_count`` as the SERVER
    reports them (Health RPC): every report names the device its walls
    were taken on, so a run that came up on the CPU says so."""
    from protocol_tpu.services.scheduler_grpc import SchedulerBackendClient

    client = SchedulerBackendClient(address)
    try:
        h = client.health()
    finally:
        client.close()
    return {
        "platform": h.platform,
        "device_kind": h.device_kind,
        "device_count": int(h.device_count),
    }


class _SessionStats:
    __slots__ = (
        "sid", "tenant", "cold_ms", "warm", "assigned_frac_min",
        "ticks_done", "refused", "reopens", "wall_s", "error",
        "transport_retries", "stale", "replayed",
        "moved_redirects", "failovers", "handoff_waits",
        "plan_mismatches", "verify_stopped",
    )

    def __init__(self, sid: str):
        self.sid = sid
        self.tenant = tenant_of(sid)
        self.cold_ms: list[float] = []
        self.warm: list[float] = []
        self.assigned_frac_min = 1.0
        self.ticks_done = 0
        self.refused = 0
        self.reopens = 0
        self.wall_s = 0.0
        self.error: Optional[str] = None
        # resilience ladder counters (the restart drill reads these)
        self.transport_retries = 0
        self.stale = 0
        self.replayed = 0
        # dfleet ladder counters (the multi-process drills read these)
        self.moved_redirects = 0
        self.failovers = 0
        self.handoff_waits = 0
        # plan verification against the fault-free in-process replay
        # (the zombie-resume gate's "zero double-applied ticks" proof:
        # a double-apply diverges the plan stream)
        self.plan_mismatches = 0
        self.verify_stopped = False


def _request_v2(snap, p_cols, r_cols, kernel: str):
    from protocol_tpu.proto import scheduler_pb2 as pb
    from protocol_tpu.proto import wire
    from protocol_tpu.trace import format as tfmt

    return pb.AssignRequestV2(
        providers=wire.encode_providers_v2(tfmt._as_ns(p_cols)),
        requirements=wire.encode_requirements_v2(tfmt._as_ns(r_cols)),
        weights=pb.CostWeights(
            price=snap.weights[0], load=snap.weights[1],
            proximity=snap.weights[2], priority=snap.weights[3],
        ),
        kernel=kernel, top_k=snap.top_k, eps=snap.eps,
        max_iters=snap.max_iters,
    )


def _open(
    client, snap, p_cols, r_cols, sid: str, kernel: str,
    reconcile_every: Optional[int] = None, timeout: float = 600,
):
    """OpenSession from the current cumulative columns; returns the
    server-acknowledged fingerprint (None = refused).
    ``reconcile_every`` opens a STREAM-mode session with that
    full-solve cadence (event-typed deltas are refused otherwise)."""
    from protocol_tpu.proto import wire
    from protocol_tpu.trace import format as tfmt

    w = tfmt._as_ns(dict(zip(
        ("price", "load", "proximity", "priority"), snap.weights
    )))
    fp = wire.epoch_fingerprint(
        p_cols, r_cols, w, kernel, max(int(snap.top_k) or 64, 1),
        snap.eps, snap.max_iters,
    )
    req = _request_v2(snap, p_cols, r_cols, kernel)
    if reconcile_every is not None:
        req.stream_mode = True
        req.reconcile_every = int(reconcile_every)
    chunks = list(wire.chunk_snapshot(sid, fp, req))
    resp = client.open_session(iter(chunks), timeout=timeout)
    if not resp.ok:
        return None, resp.error, None
    p4t = wire.unblob(resp.result.provider_for_task, np.int32)
    return fp, "", p4t


def _delta_request(
    sid: str, fp: str, tick: int,
    provider_rows, p_cols, task_rows, r_cols, event=None,
):
    """One ``AssignDelta`` wire message carrying the churned rows of a
    batch tick, or of ``event`` (a stream event, whose source/seq/kind
    ride along so the server routes it through the stream engine)."""
    from protocol_tpu.proto import scheduler_pb2 as pb
    from protocol_tpu.proto import wire
    from protocol_tpu.trace import format as tfmt

    req = pb.AssignDeltaRequest(
        session_id=sid, epoch_fingerprint=fp, tick=tick,
    )
    if event is not None:
        req.event_source = event.source
        req.event_seq = int(event.seq)
        req.event_kind = event.kind
    if provider_rows.size:
        req.provider_rows.CopyFrom(wire.blob(provider_rows, np.int32))
        req.providers.CopyFrom(
            wire.encode_providers_v2(tfmt._as_ns(p_cols))
        )
    if task_rows.size:
        req.task_rows.CopyFrom(wire.blob(task_rows, np.int32))
        req.requirements.CopyFrom(
            wire.encode_requirements_v2(tfmt._as_ns(r_cols))
        )
    return req


def _drive_session(
    address,
    trace,
    sid: str,
    kernel: str,
    stats: _SessionStats,
    max_retries: int = 20,
    rpc_timeout_s: float = 600.0,
    baseline=None,
) -> None:
    """One session's whole life against the servicer: snapshot open,
    then every recorded delta as a lockstep tick. Refusals follow the
    production ladder: bounded backoff-retry for RESOURCE_EXHAUSTED,
    re-open from the current cumulative columns for evicted/unknown,
    and — the restart drill's rung — transport failures (a servicer
    dying or draining mid-tick) reconnect and retry the SAME call, so
    a kill+restart shows up as retries and warm resumes, never as a
    failed session.

    ``address`` may be an ORDERED endpoint list (the dfleet failover
    ladder): transport failures past the first reconnect rotate to the
    next endpoint, a ``moved:<endpoint>`` refusal rebinds straight to
    the session's new home, and an "unknown session" right after a
    failover rides a bounded handoff-wait (the journal rename may still
    be in flight) before conceding to a reopen.

    ``rpc_timeout_s`` sizes the per-delta deadline: the pause (zombie)
    drill needs a SHORT one so a delta parked inside a SIGSTOPped
    process trips the transport ladder instead of hanging the session
    on a frozen socket. ``baseline`` (the fault-free replay's per-tick
    plans) arms bit-identity verification: every fresh warm tick's
    plan is compared; verification stops at the first reopen (a cold
    re-ground legitimately re-derives duals)."""
    import grpc

    from protocol_tpu.proto import wire
    from protocol_tpu.services.scheduler_grpc import SchedulerBackendClient
    from protocol_tpu.trace.replay import iter_input_ticks

    endpoints = (
        [str(a) for a in address]
        if isinstance(address, (list, tuple)) else [str(address)]
    )
    ep_i = 0
    client = SchedulerBackendClient(endpoints[ep_i])

    def rebind(endpoint: Optional[str] = None):
        nonlocal client, ep_i
        if endpoint:
            if endpoint not in endpoints:
                endpoints.append(endpoint)
            ep_i = endpoints.index(endpoint)
        try:
            client.close()
        except Exception:
            pass
        client = SchedulerBackendClient(endpoints[ep_i])

    def send(call, transport_attempts: int = 60):
        """Run ``call(client)`` with reconnect-and-retry on transport
        failure (the restart window): bounded, deterministic backoff.
        The first retry reconnects the SAME endpoint (transient blip);
        later retries fail over down the endpoint list."""
        nonlocal ep_i
        for attempt in range(transport_attempts):
            try:
                return call(client)
            except grpc.RpcError:
                if attempt + 1 >= transport_attempts:
                    raise
                stats.transport_retries += 1
                time.sleep(0.02 * min(attempt + 1, 10))
                if attempt >= 1 and len(endpoints) > 1:
                    ep_i = (ep_i + 1) % len(endpoints)
                    stats.failovers += 1
                rebind()

    t_run = time.perf_counter()
    try:
        snap = trace.snapshot
        fp = None
        server_tick = 0
        for tick, p_cols, r_cols, delta in iter_input_ticks(trace):
            t0 = time.perf_counter()
            if tick == 0:
                fp, err, p4t = send(lambda c: _open(
                    c, snap, p_cols, r_cols, sid, kernel
                ))
                if fp is None:
                    stats.error = f"OpenSession refused: {err}"
                    return
                server_tick = 0
                stats.cold_ms.append((time.perf_counter() - t0) * 1e3)
            else:
                req = _delta_request(
                    sid, fp, server_tick + 1,
                    delta.provider_rows, delta.p_cols,
                    delta.task_rows, delta.r_cols,
                )
                p4t = None
                reopened = False
                evict_retried = False
                served_stale = False
                for retry in range(max_retries):
                    resp = send(
                        lambda c: c.assign_delta(
                            req, timeout=rpc_timeout_s
                        )
                    )
                    if resp.session_ok:
                        server_tick += 1
                        if resp.stale:
                            stats.stale += 1
                            served_stale = True
                        if resp.replayed:
                            stats.replayed += 1
                        p4t = wire.unblob(
                            resp.result.provider_for_task, np.int32
                        )
                        break
                    stats.refused += 1
                    if "RESOURCE_EXHAUSTED" in resp.error:
                        # admission/backpressure/blackout: back off and
                        # retry the SAME tick (deterministic per-retry
                        # delay; many sessions desync naturally on
                        # server service order)
                        time.sleep(0.01 * (retry + 1))
                        continue
                    if resp.error.startswith("moved:"):
                        # live migration redirect: the session is WARM
                        # at its new home — rebind and resend the SAME
                        # tick (a reopen here would throw the warm
                        # arena away, the opposite of the migration's
                        # point)
                        stats.moved_redirects += 1
                        rebind(resp.error[len("moved:"):].strip())
                        continue
                    if (
                        "session evicted" in resp.error
                        and not evict_retried
                    ):
                        # a migration racing this in-flight tick lands
                        # as "session evicted"; ONE resend turns it
                        # into the moved redirect (a genuine eviction
                        # answers "unknown session" and re-opens)
                        evict_retried = True
                        continue
                    if (
                        "unknown session" in resp.error
                        and len(endpoints) > 1
                        and retry + 1 < max_retries
                    ):
                        # failover handoff window: the dead process's
                        # journal rename may still be in flight — OR a
                        # double transport blip rotated us away from
                        # the session's LIVE home. Rotate while
                        # waiting: the owner (live session or
                        # re-routed journal) is always somewhere in
                        # the endpoint list, so the walk converges
                        # warm instead of parking on a non-owner until
                        # the budget forces a reopen
                        stats.handoff_waits += 1
                        time.sleep(0.02 * (retry + 1))
                        ep_i = (ep_i + 1) % len(endpoints)
                        rebind()
                        continue
                    # tick mismatch / exhausted rungs: re-open from
                    # our authoritative cumulative columns (ladder);
                    # a "draining" refusal is transient — the
                    # replacement server admits, so keep trying
                    stats.reopens += 1
                    reopened = True
                    for dr in range(max_retries):
                        fp, err, p4t = send(lambda c: _open(
                            c, snap, p_cols, r_cols, sid, kernel
                        ))
                        if fp is not None or "draining" not in (
                            err or ""
                        ):
                            break
                        time.sleep(0.05 * (dr + 1))
                    if fp is None:
                        stats.error = f"re-open refused: {err}"
                        return
                    server_tick = 0
                    break
                if p4t is None:
                    stats.error = (
                        f"tick {tick} still refused after "
                        f"{max_retries} retries: {resp.error}"
                    )
                    return
                # a tick served via re-open paid a full snapshot COLD
                # solve — mislabeling it warm would inflate the warm
                # p99 the CI fleet gate floors on
                (stats.cold_ms if reopened else stats.warm).append(
                    (time.perf_counter() - t0) * 1e3
                )
                if reopened:
                    stats.verify_stopped = True
                if (
                    baseline is not None
                    and not stats.verify_stopped
                    and not served_stale
                    and tick < len(baseline)
                    and not np.array_equal(p4t, baseline[tick])
                ):
                    stats.plan_mismatches += 1
            stats.ticks_done += 1
            n_live = int(np.asarray(r_cols["valid"], bool).sum())
            if n_live > 0:
                stats.assigned_frac_min = min(
                    stats.assigned_frac_min,
                    float((p4t >= 0).sum()) / n_live,
                )
    except Exception as e:  # surfaced in the report, never swallowed
        stats.error = f"{type(e).__name__}: {e}"
    finally:
        stats.wall_s = time.perf_counter() - t_run
        client.close()


def run_load(
    sessions: int = 8,
    tenants: int = 2,
    providers: int = 512,
    tasks: int = 512,
    ticks: int = 8,
    churn: float = 0.02,
    kernel: str = "native-mt:1",
    shards: int = 4,
    skew: bool = False,
    traces: Optional[list] = None,
    admit_rate: Optional[float] = None,
    max_bytes: Optional[int] = None,
    queue_depth: int = 8,
    max_workers: int = 16,
    max_sessions: Optional[int] = None,
    seed: int = 0,
    check_endpoint: bool = True,
    restart_at_tick: Optional[int] = None,
    restart_mode: str = "crash",
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 1,
    processes: int = 1,
    chaos: Optional[str] = None,
    detect: bool = False,
    detector_period_s: float = 0.25,
    rpc_timeout_s: float = 600.0,
    max_retries: int = 20,
    verify_plans: bool = False,
) -> dict:
    """Run the harness; returns the report dict (see module docstring).

    ``skew=True`` gives tenant 0 exactly ONE session and spreads the
    rest over the remaining tenants — the "a tenant hammering 50
    sessions can't starve a tenant with 1" drill. ``traces`` replays
    recorded trace files (cycled over tenants) instead of synthesizing.

    ``restart_at_tick`` arms the RESTART DRILL: once every session has
    completed that many ticks, the servicer is taken down —
    ``restart_mode="crash"`` hard-stops it (the kill path; recovery
    rests on the per-tick flush-before-ack checkpoints),
    ``restart_mode="drain"`` runs the SIGTERM drain (stop admitting,
    finish in-flight ticks, flush checkpoints + trace tails) — and a
    fresh servicer on the same port rehydrates from ``ckpt_dir``
    (a temp dir when None). Sessions ride the production ladder
    through the outage; with checkpoints on, they resume WARM (zero
    reopens, counted in the report).

    ``processes > 1`` runs the DISTRIBUTED fleet instead: N real
    servicer subprocesses behind the consistent-hash endpoint ring,
    sessions routed (with ordered failover lists) by
    :class:`~protocol_tpu.dfleet.topology.FleetTopology` over a shared
    journal root. ``restart_at_tick`` then arms the PROCESS drill —
    ``crash`` SIGKILLs one process (``ChaosConfig.kill_proc`` via the
    ``chaos`` spec; default process 1) and re-routes its orphaned
    journals along the ring; ``drain`` live-migrates its sessions off
    first (Migrate RPC + "moved:" redirects), then SIGTERMs it. The
    report adds per-process scrape summaries and migration counters.

    ``chaos`` with ``pause_proc_at_tick`` arms the ZOMBIE drill
    (processes > 1 only): the target is SIGSTOPped — frozen, not dead
    — and recovery is AUTONOMOUS: the armed failure detector
    (``detect=True``, forced on for this drill) must promote it
    suspect→dead, re-route its journals, and bump the ring with ZERO
    driver-owned kill events; the zombie is then resumed and must be
    fence-refused. ``verify_plans`` compares every fresh warm tick's
    plan against the fault-free in-process replay (the zero-double-
    applied-ticks proof); ``rpc_timeout_s``/``max_retries`` size the
    client ladder for the freeze window. The report grows a
    ``detector`` section: time-to-detect, suspect flaps, fence
    refusals, false-positive ejections."""
    from protocol_tpu.fleet.fabric import FleetConfig
    from protocol_tpu.services.scheduler_grpc import serve
    from protocol_tpu.trace import format as tfmt
    from protocol_tpu.trace.synth import synth_trace

    if restart_mode not in ("crash", "drain"):
        raise ValueError(
            f"restart_mode must be crash|drain, got {restart_mode!r}"
        )
    if int(processes) <= 1 and (detect or verify_plans):
        # refusing beats a vacuous pass: the single-process path arms
        # no detector and builds no baseline, so accepting these flags
        # would report "verified" work that never ran
        raise ValueError(
            "detect/verify_plans require the distributed fleet "
            "(processes > 1)"
        )
    if int(processes) > 1:
        return _run_load_processes(
            sessions=sessions, tenants=tenants, providers=providers,
            tasks=tasks, ticks=ticks, churn=churn, kernel=kernel,
            shards=shards, skew=skew, traces=traces,
            max_workers=max_workers, max_sessions=max_sessions,
            seed=seed, restart_at_tick=restart_at_tick,
            restart_mode=restart_mode, ckpt_dir=ckpt_dir,
            ckpt_every=ckpt_every, processes=int(processes),
            chaos=chaos, admit_rate=admit_rate, max_bytes=max_bytes,
            queue_depth=queue_depth, detect=detect,
            detector_period_s=detector_period_s,
            rpc_timeout_s=rpc_timeout_s, max_retries=max_retries,
            verify_plans=verify_plans,
        )
    sessions = int(sessions)
    tenants = max(1, min(int(tenants), sessions))
    tmpdir = None
    if traces is None:
        tmpdir = tempfile.TemporaryDirectory(prefix="fleet_loadgen_")
        traces = [
            synth_trace(
                os.path.join(tmpdir.name, f"tenant{t}.trace"),
                n_providers=providers, n_tasks=tasks, ticks=ticks,
                churn=churn, seed=seed + t, kernel=kernel,
            )
            for t in range(tenants)
        ]
    parsed = [tfmt.read_trace(p) for p in traces]

    # session -> tenant assignment
    sids: list[tuple[str, object]] = []
    for i in range(sessions):
        if skew and tenants > 1:
            t = 0 if i == 0 else 1 + (i - 1) % (tenants - 1)
        else:
            t = i % tenants
        trace = parsed[t % len(parsed)]
        sids.append((f"t{t}@s{i}", trace))

    ckpt_tmp = None
    if restart_at_tick is not None and ckpt_dir is None:
        ckpt_tmp = tempfile.TemporaryDirectory(prefix="loadgen_ckpt_")
        ckpt_dir = ckpt_tmp.name
    if restart_at_tick is not None:
        # the first server dies mid-run, taking its metrics endpoint
        # with it: the scrape check would report a false negative
        check_endpoint = False
    cfg = FleetConfig(
        shards=shards,
        admit_rate=admit_rate,
        max_bytes=max_bytes,
        delta_queue_depth=queue_depth,
        ckpt_dir=ckpt_dir,
        ckpt_every=ckpt_every,
    )
    port = _free_port()
    address = f"127.0.0.1:{port}"
    serve_kwargs = dict(
        max_workers=max_workers,
        # every concurrent session must be pinnable: the default
        # max_sessions=8 would LRU-thrash 64 concurrent sessions
        max_sessions=max_sessions or max(sessions, 8),
        fleet=cfg,
    )
    server_box = [serve(
        address,
        metrics_port=0 if check_endpoint else None,
        **serve_kwargs,
    )]
    all_stats = [_SessionStats(sid) for sid, _ in sids]
    restart_report: dict = {}

    def _restart_controller(driver_threads):
        """Take the servicer down once every session has ticked past
        ``restart_at_tick``, then bring a fresh one up on the same
        port (rehydrating from ckpt_dir). Driver threads ride their
        retry ladders through the outage."""
        from protocol_tpu.services.scheduler_grpc import drain

        while True:
            # snapshot the live set once per pass: a driver flipping
            # its error flag mid-check must not empty the min() below
            live = [st for st in all_stats if not st.error]
            if not live:
                return  # everybody already failed; nothing to drill
            if min(st.ticks_done for st in live) >= restart_at_tick:
                break
            if not any(th.is_alive() for th in driver_threads):
                # the run finished before any session reached the drill
                # tick (restart_at_tick beyond the trace): exit instead
                # of spinning forever — the smoke gate reports the
                # never-fired drill as the explicit failure it is
                return
            time.sleep(0.01)
        old = server_box[0]
        if restart_mode == "drain":
            restart_report["flushed"] = drain(old, grace_s=10.0)
        else:
            old.stop(grace=None)  # the kill path: no drain, no flush
        server_box[0] = serve(address, metrics_port=None, **serve_kwargs)
        restart_report["restarted"] = True
        restart_report["sessions_restored"] = int(
            server_box[0].servicer.seam.snapshot().get(
                "session_session_restored", 0
            )
        )

    try:
        device = _server_device(address)
        t_wall = time.perf_counter()
        threads = [
            threading.Thread(
                target=_drive_session,
                args=(address, trace, st.sid, kernel, st),
                kwargs=dict(
                    max_retries=max_retries,
                    rpc_timeout_s=rpc_timeout_s,
                ),
                name=f"loadgen-{st.sid}",
            )
            for (_, trace), st in zip(sids, all_stats)
        ]
        if restart_at_tick is not None:
            threads.append(threading.Thread(
                target=_restart_controller, args=(list(threads),),
                name="loadgen-restart",
            ))
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        wall_s = time.perf_counter() - t_wall
        server = server_box[0]
        obs_snapshot = server.servicer.obs.snapshot()
        endpoint_json = None
        if check_endpoint and server.metrics is not None:
            import urllib.request

            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{server.metrics.port}"
                    "/metrics.json",
                    timeout=10,
                ) as r:
                    endpoint_json = json.loads(r.read().decode())
            except Exception:
                # metrics_endpoint_ok=False IS the report for a dead
                # endpoint — crashing here would hide it behind a
                # traceback instead of a named gate failure
                endpoint_json = None
    finally:
        server = server_box[0]
        if server.metrics is not None:
            server.metrics.stop()
        server.stop(grace=None)
        if tmpdir is not None:
            tmpdir.cleanup()
        if ckpt_tmp is not None:
            ckpt_tmp.cleanup()

    # ---------------- aggregation ----------------
    by_tenant: dict[str, dict] = {}
    session_rates = []
    errors = []
    total_warm_ticks = 0
    for st in all_stats:
        if st.error:
            errors.append({"session": st.sid, "error": st.error})
        agg = by_tenant.setdefault(
            st.tenant,
            {
                "sessions": 0,
                "warm_hist": LatencyHistogram(),
                "cold_hist": LatencyHistogram(),
                "min_assigned_frac": 1.0,
                "ticks_done": 0,
                "refused": 0,
                "reopens": 0,
                "transport_retries": 0,
                "stale": 0,
                "replayed": 0,
            },
        )
        agg["sessions"] += 1
        for w in st.warm:
            agg["warm_hist"].observe_ms(w)
        for c in st.cold_ms:
            agg["cold_hist"].observe_ms(c)
        agg["min_assigned_frac"] = min(
            agg["min_assigned_frac"], st.assigned_frac_min
        )
        agg["ticks_done"] += st.ticks_done
        agg["refused"] += st.refused
        agg["reopens"] += st.reopens
        agg["transport_retries"] += st.transport_retries
        agg["stale"] += st.stale
        agg["replayed"] += st.replayed
        total_warm_ticks += len(st.warm)
        if st.wall_s > 0:
            # zero-warm sessions contribute rate 0: a starved session
            # (every tick refused or reopen-served) must pull the Jain
            # index down, not silently vanish from it
            session_rates.append(len(st.warm) / st.wall_s)

    obs_tenants = obs_snapshot.get("tenants", {})
    slo_snap = obs_snapshot.get("slo", {})

    def _tenant_quality(t: str) -> dict:
        """WHO was unassigned and WHY, not just the assigned fraction:
        per-tenant max starvation age + the unassigned-cause counters
        from the server's quality plane (empty dict for traces recorded
        before the plane existed)."""
        q = (obs_tenants.get(t) or {}).get("quality")
        out: dict = {}
        if q:
            out["starve_max_age"] = q["starvation"]["max_age"]
            causes = dict(q.get("outcomes") or {})
            causes.pop("assigned", None)
            out["unassigned_causes"] = causes
            gap = q.get("gap_per_task")
            if gap:
                out["gap_per_task_max"] = gap["max"]
        fired = (slo_snap.get("fired_by_tenant") or {}).get(t)
        if fired:
            out["slo_alerts_fired"] = fired
        return out

    tenants_out = {
        t: {
            "sessions": a["sessions"],
            "warm_tick": a["warm_hist"].snapshot_ms(),
            "cold_tick": a["cold_hist"].snapshot_ms(),
            "min_assigned_frac": round(a["min_assigned_frac"], 4),
            "ticks_done": a["ticks_done"],
            "refused": a["refused"],
            "reopens": a["reopens"],
            "transport_retries": a["transport_retries"],
            "stale": a["stale"],
            "replayed": a["replayed"],
            **_tenant_quality(t),
        }
        for t, a in sorted(by_tenant.items())
    }

    cores = os.cpu_count() or 1
    agg_warm_per_s = (
        total_warm_ticks / wall_s if wall_s > 0 else 0.0
    )
    # linear-in-cores extrapolation: CPU-bound thread-invariant solves
    # behind sharded locks; holds until the wire/codec saturates
    scaling = {
        "model": "linear in cores (CPU-bound solve, sharded locks); "
                 "valid until the wire or delta codec saturates",
        "measured_cores": cores,
        "measured_warm_ticks_per_s": round(agg_warm_per_s, 2),
        "projected_warm_ticks_per_s": {
            str(c): round(agg_warm_per_s * c / cores, 1)
            for c in (2, 4, 8, 16, 32, 64, 128)
        },
        "projected_sessions_at_1hz": {
            str(c): int(agg_warm_per_s * c / cores)
            for c in (2, 4, 8, 16, 32, 64, 128)
        },
    }

    report = {
        "config": {
            "sessions": sessions,
            "tenants": tenants,
            "providers": providers,
            "tasks": tasks,
            "ticks": ticks,
            "churn": churn,
            "kernel": kernel,
            "shards": shards,
            "skew": skew,
            "admit_rate": admit_rate,
            "max_bytes": max_bytes,
            "queue_depth": queue_depth,
            "seed": seed,
            "restart_at_tick": restart_at_tick,
            "restart_mode": (
                restart_mode if restart_at_tick is not None else None
            ),
            "ckpt_every": (
                ckpt_every if ckpt_dir is not None else None
            ),
            "traces": [str(p) for p in traces] if tmpdir is None else
                      "synth (ephemeral)",
        },
        **device,
        "wall_s": round(wall_s, 3),
        "total_warm_ticks": total_warm_ticks,
        "aggregate_warm_ticks_per_s": round(agg_warm_per_s, 2),
        "fairness_index_sessions": jain_index(session_rates),
        "tenants": tenants_out,
        "errors": errors,
        "server_obs": {
            "tenants": obs_snapshot.get("tenants", {}),
            "fleet": obs_snapshot.get("fleet", {}),
            "admission": obs_snapshot.get("admission", {}),
            "budget": obs_snapshot.get("budget", {}),
        },
        "metrics_endpoint_ok": endpoint_json is not None,
        "scaling": scaling,
    }
    if restart_at_tick is not None:
        report["restart"] = {
            "mode": restart_mode,
            "at_tick": restart_at_tick,
            **restart_report,
            "reopens_total": sum(st.reopens for st in all_stats),
            "transport_retries_total": sum(
                st.transport_retries for st in all_stats
            ),
            "replayed_total": sum(st.replayed for st in all_stats),
        }
    return report


def _probe_zombie(proc, sid: str) -> dict:
    """Deterministic fence proof against a RESUMED zombie: any delta it
    answers must be a ``moved:`` redirect (the fence check precedes the
    session lookup), and its seam must count the refusal. Returns the
    drill-report fragment; a zombie that cannot be reached within the
    budget reports ``zombie_fence_refused=False`` and the gate fails —
    an unreachable zombie proves nothing."""
    import grpc

    from protocol_tpu.proto import scheduler_pb2 as pb
    from protocol_tpu.services.scheduler_grpc import (
        SchedulerBackendClient,
    )

    out = {"zombie_fence_refused": False}
    client = SchedulerBackendClient(proc.address)
    try:
        for attempt in range(40):
            try:
                resp = client.assign_delta(
                    pb.AssignDeltaRequest(
                        session_id=sid, epoch_fingerprint="probe",
                        tick=1,
                    ),
                    timeout=5.0,
                )
            except grpc.RpcError:
                time.sleep(0.25)
                continue
            out["zombie_fence_refused"] = (
                not resp.session_ok
                and (
                    resp.error.startswith("moved:")
                    or "fence superseded" in resp.error
                )
            )
            out["zombie_answer"] = resp.error
            break
        try:
            health = client.health(timeout=5.0)
            seam = {m.name: m.value for m in health.seam_metrics}
            out["zombie_fence_refusals"] = int(
                seam.get("session_fence_refused", 0)
            )
            out["zombie_fence_epoch"] = int(
                seam.get("ckpt_fence_epoch", 0)
            )
        except Exception:
            pass
    finally:
        client.close()
    return out


def _run_load_processes(
    sessions: int,
    tenants: int,
    providers: int,
    tasks: int,
    ticks: int,
    churn: float,
    kernel: str,
    shards: int,
    skew: bool,
    traces,
    max_workers: int,
    max_sessions,
    seed: int,
    restart_at_tick,
    restart_mode: str,
    ckpt_dir,
    ckpt_every: int,
    processes: int,
    chaos,
    admit_rate=None,
    max_bytes=None,
    queue_depth: int = 8,
    detect: bool = False,
    detector_period_s: float = 0.25,
    rpc_timeout_s: float = 600.0,
    max_retries: int = 20,
    verify_plans: bool = False,
) -> dict:
    """The distributed-fleet harness behind ``run_load(processes=N)``:
    real subprocesses, ring routing, the process-level kill/migrate
    drills, per-process scrape in the report. Client-side driving is
    the SAME ``_drive_session`` as the single-process harness — the
    failover/moved/handoff rungs are the only additions, and they are
    inert at one endpoint."""
    from protocol_tpu.dfleet.manager import ProcessFleet
    from protocol_tpu.faults.plan import ChaosConfig
    from protocol_tpu.trace import format as tfmt
    from protocol_tpu.trace.synth import synth_trace

    chaos_cfg = (
        ChaosConfig.from_spec(chaos) if isinstance(chaos, str)
        else (chaos or ChaosConfig())
    )
    # drill selection: an explicit --restart-at-tick uses restart_mode;
    # otherwise the CHAOS KNOB that armed the tick picks the action —
    # kill_proc_at_tick is always the crash drill and migrate_at_tick
    # always the live-migrate+drain drill, regardless of the
    # restart_mode default
    if restart_at_tick is not None:
        drill_tick = restart_at_tick
        drill_mode = restart_mode
        drill_proc = (
            chaos_cfg.migrate_proc if drill_mode == "drain"
            else chaos_cfg.kill_proc
        )
    elif chaos_cfg.kill_proc_at_tick is not None:
        drill_tick = chaos_cfg.kill_proc_at_tick
        drill_mode = "crash"
        drill_proc = chaos_cfg.kill_proc
    elif chaos_cfg.migrate_at_tick is not None:
        drill_tick = chaos_cfg.migrate_at_tick
        drill_mode = "drain"
        drill_proc = chaos_cfg.migrate_proc
    elif chaos_cfg.pause_proc_at_tick is not None:
        # the zombie drill: SIGSTOP the target and let the DETECTOR do
        # the rest (zero driver-owned kill events is part of the bar)
        drill_tick = chaos_cfg.pause_proc_at_tick
        drill_mode = "pause"
        drill_proc = chaos_cfg.pause_proc
    else:
        drill_tick = None
        drill_mode = restart_mode
        drill_proc = chaos_cfg.kill_proc
    detect = detect or drill_mode == "pause"
    sessions = int(sessions)
    tenants = max(1, min(int(tenants), sessions))
    tmpdir = None
    if traces is None:
        tmpdir = tempfile.TemporaryDirectory(prefix="dfleet_loadgen_")
        traces = [
            synth_trace(
                os.path.join(tmpdir.name, f"tenant{t}.trace"),
                n_providers=providers, n_tasks=tasks, ticks=ticks,
                churn=churn, seed=seed + t, kernel=kernel,
            )
            for t in range(tenants)
        ]
    parsed = [tfmt.read_trace(p) for p in traces]

    sids: list[tuple[str, object]] = []
    trace_idx: list[int] = []
    for i in range(sessions):
        if skew and tenants > 1:
            t = 0 if i == 0 else 1 + (i - 1) % (tenants - 1)
        else:
            t = i % tenants
        sids.append((f"t{t}@s{i}", parsed[t % len(parsed)]))
        trace_idx.append(t % len(parsed))

    env_extra = {}
    if isinstance(chaos, str) and chaos:
        # rate faults (drop/delay/...) fire inside every process's own
        # seeded interceptor; the scripted process events stay DRIVER-
        # owned here (a process cannot kill -9 itself cleanly)
        env_extra["PROTOCOL_TPU_CHAOS"] = chaos
    # admission/budget knobs ride the FleetConfig env surface into each
    # process (proc.py builds from_env then overrides only identity
    # fields) — a CLI knob accepted next to --processes must configure
    # the fleet, not silently measure against defaults
    if admit_rate is not None:
        env_extra["PROTOCOL_TPU_FLEET_ADMIT_RATE"] = str(admit_rate)
    if max_bytes is not None:
        env_extra["PROTOCOL_TPU_FLEET_MAX_BYTES"] = str(int(max_bytes))
    if queue_depth != 8:
        env_extra["PROTOCOL_TPU_FLEET_QUEUE_DEPTH"] = str(
            int(queue_depth)
        )
    fleet = ProcessFleet(
        processes=processes,
        journal_root=ckpt_dir,
        shards=shards,
        max_sessions=max_sessions or max(sessions, 8),
        max_workers=max_workers,
        ckpt_every=ckpt_every,
        env_extra=env_extra,
        discovery=True,
    )
    all_stats = [_SessionStats(sid) for sid, _ in sids]
    drill_report: dict = {}

    def _drill_controller(driver_threads):
        while True:
            live = [st for st in all_stats if not st.error]
            if not live:
                return
            if min(st.ticks_done for st in live) >= drill_tick:
                break
            if not any(th.is_alive() for th in driver_threads):
                return  # drill tick unreachable: reported, not spun on
            time.sleep(0.01)
        # if the configured target serves ZERO sessions (ring luck with
        # few sessions and ephemeral ports), retarget to the busiest
        # process — a drill that kills/migrates an idle process proves
        # nothing about recovery
        target = drill_proc
        topo = fleet.topology
        by_ep: dict = {}
        for st in all_stats:
            ep = topo.endpoint_for(st.sid)
            by_ep[ep] = by_ep.get(ep, 0) + 1
        if by_ep and not by_ep.get(fleet.proc_at(target).address):
            busiest = max(by_ep, key=lambda e: by_ep[e])
            target = next(
                p.index for p in fleet.procs if p.address == busiest
            )
            drill_report["retargeted"] = True
        drill_report["proc"] = fleet.proc_at(target).proc_id
        if drill_mode == "pause":
            # SIGSTOP, then HANDS OFF: the detector must promote
            # suspect->dead and run the ejection (topology bump, fence
            # supersession, journal re-route) with zero driver-owned
            # kill events — that autonomy is the thing under test
            pid = fleet.proc_at(target).proc_id
            t_pause = time.perf_counter()
            fleet.pause(target)
            drill_report["paused"] = True
            eject = None
            deadline = t_pause + 120.0
            while time.perf_counter() < deadline:
                eject = next(
                    (e for e in list(fleet.ejections)
                     if e["proc"] == pid), None,
                )
                if eject is not None:
                    break
                time.sleep(0.02)
            if eject is not None:
                drill_report["ejected_by_detector"] = True
                drill_report["time_to_detect_s"] = round(
                    eject["at"] - t_pause, 3
                )
                drill_report["journals_rerouted"] = eject[
                    "journals_rerouted"
                ]
                drill_report["generation"] = eject["generation"]
            # resume the zombie AFTER the ejection: its parked deltas
            # and anything clients still send it must be fence-refused
            fleet.resume(target)
            drill_report["resumed"] = True
            if eject is not None:
                drill_report.update(_probe_zombie(
                    fleet.proc_at(target), sids[0][0]
                ))
            return
        if drill_mode == "drain":
            # LIVE migration first (the source keeps answering with
            # "moved:" redirects while sessions rehydrate at the
            # target), then the graceful SIGTERM
            drill_report["migrated"] = fleet.migrate_all(target)
            fleet.drain(target)
            drill_report["drained"] = True
        else:
            fleet.kill(target)
            drill_report["killed"] = True
            moved = fleet.handoff_dead(target)
            drill_report["journals_rerouted"] = len(moved)
        drill_report["proc"] = fleet.proc_at(target).proc_id
        drill_report["generation"] = fleet.topology.generation

    baselines = None
    if verify_plans:
        # fault-free ground truth per trace: the in-process replay's
        # per-tick plans (bit-identical to the wire path by the
        # replay-identity gate) — what "zero double-applied ticks"
        # is asserted against
        from protocol_tpu.trace.replay import replay

        baselines = [
            replay(str(p), engine=kernel, verify=False, keep_p4t=True)[
                "p4ts"
            ]
            for p in traces
        ]

    t_wall = time.perf_counter()
    try:
        fleet.start()
        if detect:
            fleet.start_detector(period_s=detector_period_s)
        topo = fleet.topology
        threads = [
            threading.Thread(
                target=_drive_session,
                args=(
                    topo.failover_order(st.sid), trace, st.sid, kernel,
                    st,
                ),
                kwargs=dict(
                    max_retries=max_retries,
                    rpc_timeout_s=rpc_timeout_s,
                    baseline=(
                        baselines[trace_idx[i]] if baselines else None
                    ),
                ),
                name=f"dfleet-loadgen-{st.sid}",
            )
            for i, ((_, trace), st) in enumerate(
                zip(sids, all_stats)
            )
        ]
        if drill_tick is not None:
            threads.append(threading.Thread(
                target=_drill_controller, args=(list(threads),),
                name="dfleet-loadgen-drill",
            ))
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        wall_s = time.perf_counter() - t_wall
        # stop the detector BEFORE draining survivors: a drain's
        # SIGTERM window reads exactly like a dying process, and an
        # ejection fired at a DELIBERATELY drained proc would pollute
        # the false-positive ledger
        fleet.stop_detector()
        detector_snap = (
            fleet.detector.snapshot() if fleet.detector else None
        )
        ejection_events = list(fleet.ejections)
        scrapes = fleet.scrape()
        topology_out = fleet.topology.to_dict()
        # drain (don't kill) the survivors: each dumps its lock-witness
        # verdict at SIGTERM — reading witness files before this would
        # make the "zero violations in surviving processes" bar vacuous
        # (a SIGKILLed process writes nothing)
        for p in list(fleet.live()):
            try:
                fleet.drain(p.index)
            except Exception:
                pass
        witness = fleet.witness_violations()
    finally:
        fleet.stop()
        if tmpdir is not None:
            tmpdir.cleanup()

    # ---------------- aggregation (client-side) ----------------
    by_tenant: dict[str, dict] = {}
    session_rates = []
    errors = []
    total_warm_ticks = 0
    fleet_warm = LatencyHistogram()
    for st in all_stats:
        if st.error:
            errors.append({"session": st.sid, "error": st.error})
        agg = by_tenant.setdefault(
            st.tenant,
            {
                "sessions": 0,
                "warm_hist": LatencyHistogram(),
                "cold_hist": LatencyHistogram(),
                "min_assigned_frac": 1.0,
                "ticks_done": 0, "refused": 0, "reopens": 0,
                "transport_retries": 0, "stale": 0, "replayed": 0,
                "moved_redirects": 0, "failovers": 0,
                "handoff_waits": 0, "plan_mismatches": 0,
            },
        )
        agg["sessions"] += 1
        for w in st.warm:
            agg["warm_hist"].observe_ms(w)
            fleet_warm.observe_ms(w)
        for c in st.cold_ms:
            agg["cold_hist"].observe_ms(c)
        agg["min_assigned_frac"] = min(
            agg["min_assigned_frac"], st.assigned_frac_min
        )
        for key in (
            "ticks_done", "refused", "reopens", "transport_retries",
            "stale", "replayed", "moved_redirects", "failovers",
            "handoff_waits", "plan_mismatches",
        ):
            agg[key] += getattr(st, key)
        total_warm_ticks += len(st.warm)
        if st.wall_s > 0:
            session_rates.append(len(st.warm) / st.wall_s)

    def _proc_summary(snap) -> Optional[dict]:
        """The per-process slice the fleet report needs: migration/
        restore counters plus that process's own warm-tick view."""
        if snap is None:
            return None
        seam = snap.get("seam") or {}
        obs = snap.get("obs") or {}
        out = {
            k.replace("session_", "", 1): int(v)
            for k, v in seam.items()
            if k.startswith("session_") and any(
                m in k for m in (
                    "open", "restored", "rehydrated", "migrated",
                    "moved", "reopen", "hit", "replayed", "stale",
                    "fence",
                )
            )
        }
        for entry in (obs.get("sessions") or {}).values():
            tick = entry.get("tick") or {}
            if tick.get("count"):
                # scrape gives quantiles, not raw observations: carry
                # p50/p99 per session and report the worst-case p99
                out.setdefault("session_p99s_ms", []).append(
                    tick.get("p99_ms", 0.0)
                )
        if "session_p99s_ms" in out:
            p99s = out.pop("session_p99s_ms")
            out["warm_tick_p99_ms_max"] = max(p99s)
            out["sessions_observed"] = len(p99s)
        return out

    tenants_out = {
        t: {
            "sessions": a["sessions"],
            "warm_tick": a["warm_hist"].snapshot_ms(),
            "cold_tick": a["cold_hist"].snapshot_ms(),
            "min_assigned_frac": round(a["min_assigned_frac"], 4),
            **{k: a[k] for k in (
                "ticks_done", "refused", "reopens",
                "transport_retries", "stale", "replayed",
                "moved_redirects", "failovers", "handoff_waits",
                "plan_mismatches",
            )},
        }
        for t, a in sorted(by_tenant.items())
    }

    report = {
        "config": {
            "sessions": sessions, "tenants": tenants,
            "providers": providers, "tasks": tasks, "ticks": ticks,
            "churn": churn, "kernel": kernel, "shards": shards,
            "skew": skew, "seed": seed, "processes": processes,
            "chaos": chaos if isinstance(chaos, str) else None,
            "restart_at_tick": restart_at_tick,
            "restart_mode": (
                drill_mode if drill_tick is not None else None
            ),
            "ckpt_every": ckpt_every,
        },
        "wall_s": round(wall_s, 3),
        "total_warm_ticks": total_warm_ticks,
        "aggregate_warm_ticks_per_s": round(
            total_warm_ticks / wall_s if wall_s > 0 else 0.0, 2
        ),
        "fleet_warm_tick": fleet_warm.snapshot_ms(),
        "fairness_index_sessions": jain_index(session_rates),
        "tenants": tenants_out,
        "errors": errors,
        "topology": topology_out,
        "processes": {
            pid: _proc_summary(snap) for pid, snap in scrapes.items()
        },
        "witness_violations": witness,
        "migration": {
            "moved_redirects": sum(
                st.moved_redirects for st in all_stats
            ),
            "failovers": sum(st.failovers for st in all_stats),
            "handoff_waits": sum(st.handoff_waits for st in all_stats),
            "reopens_total": sum(st.reopens for st in all_stats),
            "replayed_total": sum(st.replayed for st in all_stats),
            "stale_total": sum(st.stale for st in all_stats),
            "plan_mismatches_total": sum(
                st.plan_mismatches for st in all_stats
            ),
        },
    }
    if verify_plans:
        report["verify_plans"] = True
    if detector_snap is not None:
        # detector observability (ISSUE 14 satellite): time-to-detect
        # (fault injection -> ejection), suspect flaps, fence refusals
        # (zombie probe + survivor scrapes), and the false-positive
        # ledger — an ejection of a process that was never faulted is
        # a drill failure, not noise
        expected = (
            {drill_report.get("proc")} if drill_mode == "pause"
            else set()
        )
        fence_refusals = drill_report.get("zombie_fence_refusals", 0)
        for snap in scrapes.values():
            if snap:
                fence_refusals += int(
                    (snap.get("seam") or {}).get(
                        "session_fence_refused", 0
                    )
                )
        report["detector"] = {
            "snapshot": detector_snap,
            "ejections": ejection_events,
            "suspect_flaps": detector_snap["totals"]["flaps"],
            "suspects_entered": detector_snap["totals"][
                "suspects_entered"
            ],
            "time_to_detect_s": drill_report.get("time_to_detect_s"),
            "fence_refusals": fence_refusals,
            "false_positive_ejections": [
                e for e in ejection_events if e["proc"] not in expected
            ],
        }
    if drill_tick is not None:
        report["drill"] = {
            "mode": drill_mode, "at_tick": drill_tick,
            **drill_report,
        }
    return report


class _EventDrillCtl:
    """Shared state between the event drill controller and the event
    drivers: per-driver progress (the drill trigger), the client-side
    chaos'd delivery schedule, and the storm ledger — fleet-level
    events (detector ejections, mass blackouts) the controller posts
    and every driver fans into its own session's firehose as leave
    events at the sentinel seq tier (``dstream.fanout``)."""

    def __init__(self, schedule=None, topology=None):
        self.schedule = schedule      # FaultSchedule (client delivery)
        self.topology = topology      # initial ring (source homing)
        self._lock = threading.Lock()
        self._storms: list[dict] = []
        self.events_done: dict[str, int] = {}

    def post(self, storm: dict) -> None:
        with self._lock:
            self._storms.append(dict(storm))

    def storms_from(self, cursor: int) -> list:
        with self._lock:
            return list(self._storms[cursor:])

    def progress(self, sid: str, n: int) -> None:
        self.events_done[sid] = n

    def min_progress(self, sids) -> int:
        done = [self.events_done.get(s, 0) for s in sids]
        return min(done) if done else 0


def _drive_event_session(
    address,
    trace,
    sid: str,
    kernel: str,
    rate_hz: float,
    reconcile_every: int,
    out: dict,
    rpc_timeout_s: float = 600.0,
    ctl=None,
    max_retries: int = 20,
    capture_final: bool = False,
) -> None:
    """One OPEN-LOOP event stream over a real wire session: events are
    sent at their trace-scheduled ``at_us`` offsets (never gated on the
    previous answer's completion — lateness is measured, not absorbed),
    through the stream session protocol (stream_mode OpenSession +
    event-typed AssignDelta ticks).

    ``address`` may be an ORDERED endpoint list (the dfleet failover
    ladder): the full ``_drive_session`` refusal ladder applies per
    event — RESOURCE_EXHAUSTED backoff, ``moved:`` rebind (live stream
    migration), evicted resend, handoff-wait rotate, reopen as the
    last rung (the drill bar is ZERO reopens: the checkpointed stream
    state must make every failover warm).

    ``ctl`` arms the distributed drill plane: its chaos schedule
    yields a chaos'd client-side DELIVERY order (drops→retransmits,
    dups, reorders — every re-delivery is a fresh wire tick, so the
    server's event-seq dedup, not tick CRC, must absorb it), and its
    storm ledger injects fleet-level leave events (ejection storms,
    mass blackouts) at the head of the remaining queue. Injected
    storms and their seqs are recorded in ``out["injected"]`` in
    first-send order so the fault-free baseline replay can apply the
    identical event multiset.

    ``capture_final`` pads the tail to the next reconcile boundary
    (``dstream.pad_event`` no-ops) and records the final RECONCILED
    plan in ``out["final_p4t"]`` — the bit-identity witness."""
    import grpc as _grpc

    from protocol_tpu.dstream import fanout as _fan
    from protocol_tpu.proto import wire
    from protocol_tpu.services.scheduler_grpc import (
        SchedulerBackendClient,
    )
    from protocol_tpu.stream.events import event_from_delta

    endpoints = (
        [str(a) for a in address]
        if isinstance(address, (list, tuple)) else [str(address)]
    )
    ep_i = 0
    client = SchedulerBackendClient(endpoints[ep_i])

    def rebind(endpoint: Optional[str] = None):
        nonlocal client, ep_i
        if endpoint:
            if endpoint not in endpoints:
                endpoints.append(endpoint)
            ep_i = endpoints.index(endpoint)
        try:
            client.close()
        except Exception:
            pass
        client = SchedulerBackendClient(endpoints[ep_i])

    def send(call, transport_attempts: int = 60):
        nonlocal ep_i
        for attempt in range(transport_attempts):
            try:
                return call(client)
            except _grpc.RpcError:
                if attempt + 1 >= transport_attempts:
                    raise
                out["transport_retries"] = (
                    out.get("transport_retries", 0) + 1
                )
                time.sleep(0.02 * min(attempt + 1, 10))
                if attempt >= 1 and len(endpoints) > 1:
                    ep_i = (ep_i + 1) % len(endpoints)
                    out["failovers"] = out.get("failovers", 0) + 1
                rebind()

    snap = trace.snapshot
    events = [event_from_delta(d) for d in trace.deltas]
    if any(ev is None for ev in events):
        out["error"] = "trace is not a stream trace"
        client.close()
        return
    # cumulative column state (events are full-state for their rows):
    # the reopen rung's authority, and the payload source for storm
    # leave events (snapshot values, valid=False)
    p_cum = {k: np.array(v, copy=True) for k, v in snap.p_cols.items()}
    r_cum = {k: np.array(v, copy=True) for k, v in snap.r_cols.items()}

    def _open_stream(p_cols, r_cols):
        new_fp, err, _p4t = send(lambda c: _open(
            c, snap, p_cols, r_cols, sid, kernel,
            reconcile_every=reconcile_every, timeout=rpc_timeout_s,
        ))
        return new_fp, err

    # client-side chaos'd delivery order: drops become retransmits,
    # dups second copies, reorders late arrivals — every index is
    # delivered at least once, and every delivery is a fresh tick
    if ctl is not None and ctl.schedule is not None:
        from protocol_tpu.faults.plan import event_delivery_order

        order = event_delivery_order(
            ctl.schedule, len(events), site=f"events/{sid}"
        )
    else:
        order = list(range(len(events)))

    try:
        fp, err = _open_stream(snap.p_cols, snap.r_cols)
        if fp is None:
            out["error"] = f"open refused: {err}"
            return
        t_start = time.perf_counter()
        server_tick = 0
        walls_us: list = []
        injected: list = []
        lag_us_max = 0.0
        gap_max = 0.0
        reconciles = deduped = late = 0
        window_max = 0
        window_last = 0
        storm_cursor = 0
        storm_events = 0
        pad_i = 0
        first_sent: set = set()
        last_recon_p4t = None

        def _mint(storm) -> list:
            if storm.get("kind") == "ejection":
                rows = _fan.affected_rows(
                    ctl.topology, sid, storm["dead_proc"],
                    len(next(iter(p_cum.values()))),
                )
                return _fan.ejection_leave_events(
                    storm["generation"], rows, snap.p_cols
                )
            rows = np.asarray(storm.get("rows", ()), np.int32)
            return _fan.mass_leave_events(
                int(storm.get("mass_index", 0)), rows, snap.p_cols
            )

        def _send_event(ev):
            """Full refusal ladder for ONE event delivery. Returns the
            response, or None after an irrecoverable refusal (error is
            set). Folds applied full-state rows into the cumulative
            columns (dedup-ACKed deliveries are NOT folded: a reordered
            stale event would regress the authority)."""
            nonlocal server_tick, fp
            nonlocal reconciles, deduped, gap_max
            nonlocal window_max, window_last, last_recon_p4t
            evict_retried = False
            for retry in range(max_retries):
                dreq = _delta_request(
                    sid, fp, server_tick + 1,
                    ev.provider_rows, ev.p_cols,
                    ev.task_rows, ev.r_cols, event=ev,
                )
                r = send(lambda c: c.assign_delta(
                    dreq, timeout=rpc_timeout_s
                ))
                if r.session_ok:
                    server_tick += 1
                    if r.replayed:
                        out["replayed"] = out.get("replayed", 0) + 1
                    reconciles += int(r.reconciled)
                    deduped += int(r.event_deduped)
                    gap_max = max(gap_max, float(r.gap_per_task))
                    window_last = int(r.events_since_reconcile)
                    window_max = max(window_max, window_last)
                    if not r.event_deduped:
                        if ev.provider_rows.size:
                            for name, a in ev.p_cols.items():
                                p_cum[name][ev.provider_rows] = (
                                    np.asarray(a)
                                )
                        if ev.task_rows.size:
                            for name, a in ev.r_cols.items():
                                r_cum[name][ev.task_rows] = (
                                    np.asarray(a)
                                )
                    if r.reconciled:
                        last_recon_p4t = wire.unblob(
                            r.result.provider_for_task, np.int32
                        )
                    out["assigned_last"] = int(r.result.num_assigned)
                    return r
                out["refused"] = out.get("refused", 0) + 1
                if "RESOURCE_EXHAUSTED" in r.error:
                    time.sleep(0.01 * (retry + 1))
                    continue
                if r.error.startswith("moved:"):
                    # live stream migration: the engine is re-armed
                    # WARM at the new home (dedup cursors + cadence
                    # travel in the checkpoint) — rebind and resend
                    out["moved_redirects"] = (
                        out.get("moved_redirects", 0) + 1
                    )
                    rebind(r.error[len("moved:"):].strip())
                    continue
                if "session evicted" in r.error and not evict_retried:
                    evict_retried = True
                    continue
                if (
                    "unknown session" in r.error
                    and len(endpoints) > 1
                    and retry + 1 < max_retries
                ):
                    out["handoff_waits"] = (
                        out.get("handoff_waits", 0) + 1
                    )
                    time.sleep(0.02 * (retry + 1))
                    rebind_idx()
                    continue
                # last rung: reopen from the cumulative columns (the
                # drill bar is zero of these — stream state travels)
                out["reopens"] = out.get("reopens", 0) + 1
                out["verify_stopped"] = True
                fp2, err2 = None, ""
                for dr in range(max_retries):
                    fp2, err2 = _open_stream(p_cum, r_cum)
                    if fp2 is not None or "draining" not in (
                        err2 or ""
                    ):
                        break
                    time.sleep(0.05 * (dr + 1))
                if fp2 is None:
                    out["error"] = f"re-open refused: {err2}"
                    return None
                fp = fp2
                server_tick = 0
                # fall through: the next retry resends this event as
                # tick 1 of the re-grounded session
            out["error"] = (
                f"event still refused after {max_retries} "
                f"retries: {r.error}"
            )
            return None

        def rebind_idx():
            nonlocal ep_i
            ep_i = (ep_i + 1) % len(endpoints)
            rebind()

        from collections import deque as _deque

        pending: "_deque" = _deque()
        i = 0
        sent = 0
        while i < len(order) or pending:
            if ctl is not None:
                storms = ctl.storms_from(storm_cursor)
                if storms:
                    storm_cursor += len(storms)
                    for storm in storms:
                        leaves = _mint(storm)
                        pending.extend(leaves)
                        injected.extend(leaves)
            if pending:
                ev = pending.popleft()
                storm_events += 1
            else:
                idx = order[i]
                i += 1
                ev = events[idx]
                if idx not in first_sent:
                    first_sent.add(idx)
                    # open-loop: wait for the scheduled arrival —
                    # lateness is recorded, never absorbed. Chaos
                    # re-deliveries (dups/retransmits) go immediately.
                    target = t_start + ev.at_us / 1e6
                    now = time.perf_counter()
                    if now < target:
                        time.sleep(target - now)
                    else:
                        lag_us_max = max(
                            lag_us_max, (now - target) * 1e6
                        )
                        late += 1
            t0 = time.perf_counter()
            r = _send_event(ev)
            if r is None:
                return
            if not r.reconciled:
                walls_us.append((time.perf_counter() - t0) * 1e6)
            sent += 1
            if ctl is not None:
                ctl.progress(sid, sent)
        if capture_final:
            # pad to the next reconcile boundary: the final answer
            # must be a RECONCILED plan (full solve of the converged
            # columns) for the bit-identity comparison
            while window_last > 0 and pad_i <= reconcile_every + 2:
                r = _send_event(_fan.pad_event(pad_i))
                if r is None:
                    return
                pad_i += 1
                sent += 1
            out["final_p4t"] = last_recon_p4t
        out["wall_s"] = time.perf_counter() - t_start
        out["events"] = sent
        out["storm_events"] = storm_events
        out["pad_events"] = pad_i
        out["injected"] = injected
        out["walls_us"] = walls_us
        out["reconciles"] = reconciles
        out["deduped"] = deduped
        out["gap_max"] = gap_max
        out["window_max"] = window_max
        out["late_events"] = late
        out["lag_us_max"] = round(lag_us_max, 1)
    except Exception as e:  # surfaced in the report, never swallowed
        out["error"] = f"{type(e).__name__}: {e}"
    finally:
        client.close()


_EVENT_LADDER_KEYS = (
    "refused", "transport_retries", "failovers", "moved_redirects",
    "handoff_waits", "reopens", "replayed",
)


def _aggregate_event_outs(sids, outs):
    """Per-tenant join of the event drivers' ``out`` dicts (shared by
    the single-process and distributed harnesses)."""
    from protocol_tpu.obs.metrics import LatencyHistogram, tenant_of as _t

    by_tenant: dict[str, dict] = {}
    errors = []
    total_events = 0
    for sid, out in zip(sids, outs):
        if out.get("error"):
            errors.append({"session": sid, "error": out["error"]})
            continue
        t = _t(sid)
        agg = by_tenant.setdefault(t, {
            "hist": LatencyHistogram(lowest_ns=100.0),
            "events": 0, "reconciles": 0, "deduped": 0,
            "gap_max": 0.0, "window_max": 0, "late_events": 0,
            "storm_events": 0, "assigned_last_min": None,
            **{k: 0 for k in _EVENT_LADDER_KEYS},
        })
        for us in out.get("walls_us", ()):
            agg["hist"].observe_ns(us * 1e3)
        agg["events"] += out.get("events", 0)
        agg["reconciles"] += out.get("reconciles", 0)
        agg["deduped"] += out.get("deduped", 0)
        agg["gap_max"] = max(agg["gap_max"], out.get("gap_max", 0.0))
        agg["window_max"] = max(
            agg["window_max"], out.get("window_max", 0)
        )
        agg["late_events"] += out.get("late_events", 0)
        agg["storm_events"] += out.get("storm_events", 0)
        for k in _EVENT_LADDER_KEYS:
            agg[k] += out.get(k, 0)
        a = out.get("assigned_last")
        if a is not None:
            prev = agg["assigned_last_min"]
            agg["assigned_last_min"] = (
                a if prev is None else min(prev, a)
            )
        total_events += out.get("events", 0)
    tenants_out = {}
    for t, agg in sorted(by_tenant.items()):
        tenants_out[t] = {
            "events": agg["events"],
            "event_rpc": agg["hist"].snapshot_us(),
            "reconciles": agg["reconciles"],
            "deduped": agg["deduped"],
            "gap_max": round(agg["gap_max"], 6),
            "events_since_reconcile_max": agg["window_max"],
            "late_events": agg["late_events"],
            "storm_events": agg["storm_events"],
            "assigned_last_min": agg["assigned_last_min"],
            **{k: agg[k] for k in _EVENT_LADDER_KEYS},
        }
    return tenants_out, errors, total_events


def _event_baseline_p4t(
    trace_path, kernel: str, reconcile_every: int, extra_events
):
    """Fault-free ground truth for a chaos'd / storm-injected stream
    session: the in-process replay of the SAME trace with the SAME
    injected events appended in-order, final full-solve reconcile.
    Per-source latest-wins plus storms at the sentinel seq tier make
    the converged columns — and therefore the reconciled plan —
    independent of where chaos interleaved the deliveries."""
    from protocol_tpu.stream.replay import stream_replay

    eng, _, th = str(kernel).partition(":")
    rep = stream_replay(
        str(trace_path), engine=eng,
        threads=int(th) if th else None,
        reconcile_every=int(reconcile_every), verify=False,
        final_reconcile=True, keep_recon_p4ts=True,
        extra_events=list(extra_events or ()),
    )
    p4ts = rep.get("recon_p4ts") or []
    return p4ts[-1] if p4ts else None


def _event_bit_identity(paths, sids, outs, kernel, reconcile_every):
    """Compare every driver's final reconciled plan against the
    fault-free baseline. Baselines are cached by (trace, injected
    seqs): the injected payloads are pure functions of (trace
    snapshot, source, seq), so equal keys mean equal baselines."""
    checked = mismatches = skipped = 0
    mismatched = []
    cache: dict = {}
    for tp, sid, out in zip(paths, sids, outs):
        if (
            out.get("error") or out.get("verify_stopped")
            or out.get("final_p4t") is None
        ):
            skipped += 1
            continue
        key = (str(tp), tuple(
            (e.source, int(e.seq)) for e in out.get("injected") or ()
        ))
        if key not in cache:
            cache[key] = _event_baseline_p4t(
                tp, kernel, reconcile_every, out.get("injected")
            )
        base = cache[key]
        checked += 1
        if base is None or not np.array_equal(out["final_p4t"], base):
            mismatches += 1
            mismatched.append(sid)
    return {
        "checked": checked,
        "mismatches": mismatches,
        "skipped": skipped,
        "mismatched_sessions": mismatched,
    }


def _trace_sources(trace) -> int:
    """Distinct event sources in a stream trace (the denominator of
    the zero-dropped-sources acceptance bar)."""
    from protocol_tpu.stream.events import event_from_delta

    srcs = set()
    for d in trace.deltas:
        ev = event_from_delta(d)
        if ev is not None:
            srcs.add(ev.source)
    return len(srcs)


def _run_events_processes(
    sessions: int,
    tenants: int,
    providers: int,
    tasks: int,
    events: int,
    rate_hz: float,
    kernel: str,
    reconcile_every: int,
    shards: int,
    max_workers: int,
    seed: int,
    rpc_timeout_s: float,
    processes: int,
    chaos=None,
    detect: bool = False,
    detector_period_s: float = 0.25,
    ckpt_dir=None,
    ckpt_every: int = 1,
    max_retries: int = 20,
    trace_path=None,
    mass_at_event=None,
    mass_frac: float = 0.1,
) -> dict:
    """The DISTRIBUTED event firehose (``--events --processes N``):
    every session is a stream-mode wire session homed by the ring on
    one of N real servicer subprocesses; drivers run the full failover
    ladder per event. The chaos spec arms three planes at once —
    client-side chaos'd DELIVERY (drop/dup/reorder of event sends,
    absorbed by server-side event-seq dedup), the scripted process
    drill (``kill_proc_at_tick`` = SIGKILL after that many EVENTS per
    session, ``migrate_at_tick`` = live migration + drain), and each
    process's own seeded interceptor. A kill translates into an
    EJECTION STORM: one leave event per source homed on the corpse,
    injected into every surviving session's firehose at the sentinel
    seq tier and absorbed online (O(churned rows) per event). A
    ``mass_at_event`` trigger composes the ``faults/`` blackout shape
    into a fleet-wide mass leave event. The report carries fleet-wide
    events/sec, per-event p99 µs, the stream rollup joined from every
    process's scrape, and the bit-identity verdict of every session's
    final reconciled plan against its fault-free baseline replay."""
    from protocol_tpu.dfleet.manager import ProcessFleet
    from protocol_tpu.dstream import fanout as _fan
    from protocol_tpu.dstream.rollup import stream_rollup
    from protocol_tpu.faults.plan import ChaosConfig, FaultSchedule
    from protocol_tpu.trace import format as tfmt
    from protocol_tpu.trace.synth import synth_event_trace

    chaos_cfg = (
        ChaosConfig.from_spec(chaos) if isinstance(chaos, str)
        else (chaos or ChaosConfig())
    )
    if chaos_cfg.kill_proc_at_tick is not None:
        drill_event, drill_mode = chaos_cfg.kill_proc_at_tick, "crash"
        drill_proc = chaos_cfg.kill_proc
    elif chaos_cfg.migrate_at_tick is not None:
        drill_event, drill_mode = chaos_cfg.migrate_at_tick, "drain"
        drill_proc = chaos_cfg.migrate_proc
    else:
        drill_event, drill_mode = None, None
        drill_proc = chaos_cfg.kill_proc
    schedule = FaultSchedule(chaos_cfg) if chaos_cfg.active() else None

    sessions = int(sessions)
    tenants = max(1, min(int(tenants), sessions))
    tmpdir = tempfile.TemporaryDirectory(prefix="dstream_loadgen_")
    try:
        paths = []
        for i in range(sessions):
            if trace_path:
                # the gate's golden-trace mode: every session replays
                # the SAME committed trace (identical baselines)
                paths.append(str(trace_path))
            else:
                paths.append(synth_event_trace(
                    os.path.join(tmpdir.name, f"s{i}.trace"),
                    n_providers=providers, n_tasks=tasks,
                    events=events, seed=seed + i, kernel=kernel,
                    rate_hz=rate_hz, reconcile_every=reconcile_every,
                ))
        parsed_cache: dict = {}
        traces = []
        for p in paths:
            if p not in parsed_cache:
                parsed_cache[p] = tfmt.read_trace(p)
            traces.append(parsed_cache[p])
        sids = [f"t{i % tenants}@es{i}" for i in range(sessions)]
        outs = [dict() for _ in range(sessions)]
        sources_per_session = [
            _trace_sources(parsed_cache[p]) for p in paths
        ]

        env_extra = {}
        if isinstance(chaos, str) and chaos:
            env_extra["PROTOCOL_TPU_CHAOS"] = chaos
        fleet = ProcessFleet(
            processes=int(processes),
            journal_root=ckpt_dir,
            shards=shards,
            max_sessions=max(sessions, 8),
            max_workers=max_workers,
            ckpt_every=ckpt_every,
            env_extra=env_extra,
            discovery=True,
        )
        drill_report: dict = {}
        mass_report: dict = {}
        ctl = _EventDrillCtl(schedule=schedule)

        def _wait_for_event(at, driver_threads) -> bool:
            while True:
                live = [
                    s for s, o in zip(sids, outs) if not o.get("error")
                ]
                if not live:
                    return False
                if ctl.min_progress(live) >= at:
                    return True
                if not any(th.is_alive() for th in driver_threads):
                    return False
                time.sleep(0.01)

        def _drill_controller(driver_threads):
            triggers = []
            if mass_at_event is not None:
                triggers.append((int(mass_at_event), "mass"))
            if drill_event is not None:
                triggers.append((int(drill_event), drill_mode))
            for at, mode in sorted(triggers):
                if not _wait_for_event(at, driver_threads):
                    return
                if mode == "mass":
                    sched = _fan.blackout_storm_schedule(
                        seed, chaos_cfg.blackout_shard or 1,
                        providers, mass_frac,
                    )
                    ctl.post({
                        "kind": "mass",
                        "mass_index": sched["mass_index"],
                        "rows": sched["rows"],
                    })
                    mass_report.update(
                        at_event=at, rows=len(sched["rows"]),
                        shard=sched["shard"],
                    )
                    continue
                # retarget to the busiest process if ring luck left
                # the configured target idle (same rule as batch mode)
                target = drill_proc
                topo = fleet.topology
                by_ep: dict = {}
                for s in sids:
                    ep = topo.endpoint_for(s)
                    by_ep[ep] = by_ep.get(ep, 0) + 1
                if by_ep and not by_ep.get(
                    fleet.proc_at(target).address
                ):
                    busiest = max(by_ep, key=lambda e: by_ep[e])
                    target = next(
                        p.index for p in fleet.procs
                        if p.address == busiest
                    )
                    drill_report["retargeted"] = True
                pid = fleet.proc_at(target).proc_id
                drill_report["proc"] = pid
                if mode == "drain":
                    # LIVE stream migration: sessions re-arm warm at
                    # the ring successor (full stream state travels in
                    # the checkpoint) — no storm, the sources flow on
                    drill_report["migrated"] = fleet.migrate_all(
                        target
                    )
                    fleet.drain(target)
                    drill_report["drained"] = True
                    continue
                t_kill = time.perf_counter()
                gen = None
                if detect:
                    # SIGKILL withOUT telling the fleet: the DETECTOR
                    # must notice the silence and run the autonomous
                    # ejection (topology bump + fence supersession +
                    # journal re-route) — a scripted fleet.kill would
                    # be removed from its watch and prove nothing
                    fleet.kill_unannounced(target)
                    drill_report["killed"] = True
                    eject = None
                    deadline = t_kill + 60.0
                    while time.perf_counter() < deadline:
                        eject = next(
                            (e for e in list(fleet.ejections)
                             if e["proc"] == pid), None,
                        )
                        if eject is not None:
                            break
                        time.sleep(0.02)
                    if eject is not None:
                        drill_report["ejected_by_detector"] = True
                        drill_report["time_to_detect_s"] = round(
                            eject["at"] - t_kill, 3
                        )
                        drill_report["journals_rerouted"] = eject[
                            "journals_rerouted"
                        ]
                        gen = eject["generation"]
                if gen is None:
                    # no detector (or it never fired): driver-owned
                    # takedown + journal re-route, the batch-mode shape
                    fleet.kill(target)
                    drill_report["killed"] = True
                    moved = fleet.handoff_dead(target)
                    drill_report["journals_rerouted"] = len(moved)
                    gen = fleet.topology.generation
                drill_report["generation"] = gen
                # the ejection storm: every source homed on the corpse
                # leaves, fanned into every session's firehose at the
                # storm seq tier (generation-keyed, deterministic)
                ctl.post({
                    "kind": "ejection", "dead_proc": pid,
                    "generation": gen,
                })
                drill_report["storm_posted"] = True

        t_wall = time.perf_counter()
        try:
            fleet.start()
            if detect:
                fleet.start_detector(period_s=detector_period_s)
            ctl.topology = fleet.topology
            topo = fleet.topology
            threads = [
                threading.Thread(
                    target=_drive_event_session,
                    args=(
                        topo.failover_order(sid), trace, sid, kernel,
                        rate_hz, reconcile_every, out,
                    ),
                    kwargs=dict(
                        rpc_timeout_s=rpc_timeout_s, ctl=ctl,
                        max_retries=max_retries, capture_final=True,
                    ),
                    name=f"dstream-{sid}",
                )
                for trace, sid, out in zip(traces, sids, outs)
            ]
            if drill_event is not None or mass_at_event is not None:
                threads.append(threading.Thread(
                    target=_drill_controller, args=(list(threads),),
                    name="dstream-drill",
                ))
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            wall_s = time.perf_counter() - t_wall
            fleet.stop_detector()
            detector_snap = (
                fleet.detector.snapshot() if fleet.detector else None
            )
            ejection_events = list(fleet.ejections)
            scrapes = fleet.scrape()
            rollup = stream_rollup(scrapes)
            topology_out = fleet.topology.to_dict()
            for p in list(fleet.live()):
                try:
                    fleet.drain(p.index)
                except Exception:
                    pass
            witness = fleet.witness_violations()
        finally:
            fleet.stop()

        # fault-free baselines replay INSIDE the try: synth traces
        # live in the tmpdir
        bit = _event_bit_identity(
            paths, sids, outs, kernel, reconcile_every
        )
    finally:
        tmpdir.cleanup()

    tenants_out, errors, total_events = _aggregate_event_outs(
        sids, outs
    )
    dropped = sum(
        n for n, o in zip(sources_per_session, outs)
        if o.get("error")
    )
    report = {
        "mode": "events",
        "config": {
            "sessions": sessions, "tenants": tenants,
            "providers": providers, "tasks": tasks,
            "events_per_session": events, "rate_hz": rate_hz,
            "reconcile_every": reconcile_every, "kernel": kernel,
            "shards": shards, "seed": seed,
            "processes": int(processes),
            "chaos": chaos if isinstance(chaos, str) else None,
            "detect": bool(detect),
            "trace_path": str(trace_path) if trace_path else None,
            "mass_at_event": mass_at_event,
        },
        "sessions": sessions,
        "tenants": tenants_out,
        "wall_s": round(wall_s, 3),
        "events_total": total_events,
        "events_per_s": round(total_events / max(wall_s, 1e-9), 1),
        "storm_events_total": sum(
            o.get("storm_events", 0) for o in outs
        ),
        "pad_events_total": sum(o.get("pad_events", 0) for o in outs),
        "ladder": {
            k: sum(o.get(k, 0) for o in outs)
            for k in _EVENT_LADDER_KEYS
        },
        "sources": {
            "total": sum(sources_per_session),
            "dropped": dropped,
        },
        "bit_identity": bit,
        "errors": errors,
        "topology": topology_out,
        "stream_rollup": rollup,
        "fleet_events_per_s": round(
            rollup.get("events", 0) / max(wall_s, 1e-9), 1
        ),
        "witness_violations": witness,
    }
    if detector_snap is not None:
        expected = (
            {drill_report.get("proc")} if drill_report.get("killed")
            else set()
        )
        report["detector"] = {
            "snapshot": detector_snap,
            "ejections": ejection_events,
            "false_positive_ejections": [
                e for e in ejection_events if e["proc"] not in expected
            ],
        }
    if drill_event is not None or mass_at_event is not None:
        report["drill"] = {
            "mode": drill_mode, "at_event": drill_event,
            **drill_report,
        }
    if mass_report:
        report["mass"] = mass_report
    return report


def run_events(
    sessions: int = 4,
    tenants: int = 2,
    providers: int = 512,
    tasks: int = 512,
    events: int = 128,
    rate_hz: float = 200.0,
    kernel: str = "native-mt:1",
    reconcile_every: int = 64,
    shards: int = 4,
    max_workers: int = 16,
    seed: int = 0,
    rpc_timeout_s: float = 600.0,
    processes: int = 1,
    chaos=None,
    detect: bool = False,
    ckpt_dir=None,
    ckpt_every: int = 1,
    max_retries: int = 20,
    trace_path=None,
    mass_at_event=None,
    mass_frac: float = 0.1,
    blackout_shard: int = 1,
    blackout_refusals: int = 2,
) -> dict:
    """The open-loop EVENT arrival mode (``--events``): H concurrent
    stream sessions each replaying a seeded synthetic event trace
    against real servicer(s) at its deterministic arrival schedule.
    Reports events/sec, per-event p50/p99 µs (client-observed RPC wall,
    reconcile answers excluded — they are full solves and reported
    separately), and the divergence/reconcile counters per tenant.

    ``processes > 1`` switches to the DISTRIBUTED firehose harness
    (:func:`_run_events_processes`): ring-routed sessions over N real
    servicer subprocesses, chaos'd delivery, the kill/migrate drills,
    ejection storms, and per-session bit-identity verdicts.

    ``mass_at_event`` composes the ``faults/`` blackout with the
    stream plane in-process: once every session has sent that many
    events, the harness arms ``SessionFabric.blackout`` on
    ``blackout_shard`` WITH a seeded leave-storm schedule, drains it,
    and fans the mass leave events into every session's firehose —
    the blackout drill exercises the stream path, not just the
    RESOURCE_EXHAUSTED retry ladder."""
    if int(processes) > 1:
        return _run_events_processes(
            sessions, tenants, providers, tasks, events, rate_hz,
            kernel, reconcile_every, shards, max_workers, seed,
            rpc_timeout_s, int(processes), chaos=chaos, detect=detect,
            ckpt_dir=ckpt_dir, ckpt_every=ckpt_every,
            max_retries=max_retries, trace_path=trace_path,
            mass_at_event=mass_at_event, mass_frac=mass_frac,
        )
    from protocol_tpu.dstream import fanout as _fan
    from protocol_tpu.fleet.fabric import FleetConfig
    from protocol_tpu.services.scheduler_grpc import serve
    from protocol_tpu.trace import format as tfmt
    from protocol_tpu.trace.synth import synth_event_trace

    sessions = int(sessions)
    tenants = max(1, min(int(tenants), sessions))
    tmpdir = tempfile.TemporaryDirectory(prefix="fleet_events_")
    mass_armed = mass_at_event is not None
    ctl = _EventDrillCtl() if mass_armed else None
    mass_report: dict = {}
    try:
        paths, traces = [], []
        for i in range(sessions):
            p = synth_event_trace(
                os.path.join(tmpdir.name, f"s{i}.trace"),
                n_providers=providers, n_tasks=tasks, events=events,
                seed=seed + i, kernel=kernel, rate_hz=rate_hz,
                reconcile_every=reconcile_every,
            ) if not trace_path else str(trace_path)
            paths.append(p)
            traces.append(tfmt.read_trace(p))
        port = _free_port()
        address = f"127.0.0.1:{port}"
        server = serve(
            address,
            max_workers=max_workers,
            max_sessions=max(sessions, 8),
            fleet=FleetConfig(shards=shards),
        )
        outs = [dict() for _ in range(sessions)]
        sids = [f"t{i % tenants}@es{i}" for i in range(sessions)]

        def _mass_controller(driver_threads):
            while True:
                live = [
                    s for s, o in zip(sids, outs) if not o.get("error")
                ]
                if not live:
                    return
                if ctl.min_progress(live) >= int(mass_at_event):
                    break
                if not any(th.is_alive() for th in driver_threads):
                    return
                time.sleep(0.005)
            # arm the blackout WITH its leave-storm schedule, then
            # drain and fan out — the full satellite composition path
            sched = _fan.blackout_storm_schedule(
                seed, blackout_shard, providers, mass_frac
            )
            server.servicer.sessions.blackout(
                blackout_shard, blackout_refusals, storm=sched
            )
            for storm in server.servicer.sessions.drain_storms():
                ctl.post({
                    "kind": "mass",
                    "mass_index": storm["mass_index"],
                    "rows": storm["rows"],
                })
            mass_report.update(
                at_event=int(mass_at_event),
                rows=len(sched["rows"]), shard=sched["shard"],
                refusals_armed=blackout_refusals,
            )

        try:
            device = _server_device(address)
            t_wall = time.perf_counter()
            threads = [
                threading.Thread(
                    target=_drive_event_session,
                    args=(
                        address, trace, sid, kernel, rate_hz,
                        reconcile_every, out,
                    ),
                    kwargs=dict(
                        rpc_timeout_s=rpc_timeout_s, ctl=ctl,
                        max_retries=max_retries,
                        capture_final=mass_armed,
                    ),
                    name=f"events-{sid}",
                )
                for trace, sid, out in zip(traces, sids, outs)
            ]
            if mass_armed:
                threads.append(threading.Thread(
                    target=_mass_controller, args=(list(threads),),
                    name="events-mass",
                ))
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            wall_s = time.perf_counter() - t_wall
            obs_snapshot = server.servicer.obs.snapshot()
            fabric_snapshot = server.servicer.sessions.snapshot()
        finally:
            server.stop(grace=None)
        bit = (
            _event_bit_identity(
                paths, sids, outs, kernel, reconcile_every
            ) if mass_armed else None
        )
    finally:
        tmpdir.cleanup()

    tenants_out, errors, total_events = _aggregate_event_outs(
        sids, outs
    )
    report = {
        "mode": "events",
        "sessions": sessions,
        "tenants": tenants_out,
        "providers": providers,
        "tasks": tasks,
        "events_per_session": events,
        "rate_hz": rate_hz,
        "reconcile_every": reconcile_every,
        "kernel": kernel,
        **device,
        "wall_s": round(wall_s, 3),
        "events_total": total_events,
        "events_per_s": round(total_events / max(wall_s, 1e-9), 1),
        "errors": errors,
        "server_obs": {
            sid: v.get("stream")
            for sid, v in obs_snapshot.get("sessions", {}).items()
            if v.get("stream")
        },
        "fabric": fabric_snapshot,
    }
    if mass_armed:
        report["mass"] = mass_report
        report["bit_identity"] = bit
        report["storm_events_total"] = sum(
            o.get("storm_events", 0) for o in outs
        )
    return report


def _print_report(rep: dict) -> None:
    cfg = rep["config"]
    print(
        f"fleet loadgen: {cfg['sessions']} sessions / {cfg['tenants']} "
        f"tenants @ {cfg['providers']}x{cfg['tasks']}, "
        f"{cfg['ticks']} ticks, kernel {cfg['kernel']}, "
        f"{cfg['shards']} shards"
    )
    if "platform" in rep:
        print(
            f"  server platform {rep['platform']} "
            f"({rep['device_kind']} x{rep['device_count']})"
        )
    print(
        f"  wall {rep['wall_s']}s, {rep['total_warm_ticks']} warm ticks "
        f"({rep['aggregate_warm_ticks_per_s']}/s aggregate), "
        f"session fairness (Jain) {rep['fairness_index_sessions']}"
    )
    hdr = (
        f"  {'tenant':<8} {'sess':>4} {'p50ms':>8} {'p99ms':>8} "
        f"{'min-assigned':>12} {'refused':>8} {'reopens':>8}"
    )
    print(hdr)
    for t, a in rep["tenants"].items():
        warm = a["warm_tick"]
        quality = ""
        if "starve_max_age" in a:
            causes = a.get("unassigned_causes") or {}
            cause_s = " ".join(
                f"{k}={v}" for k, v in sorted(causes.items()) if v
            )
            quality = (
                f"  starve<={a['starve_max_age']}"
                + (f" [{cause_s}]" if cause_s else "")
            )
        if a.get("slo_alerts_fired"):
            quality += f"  SLO-fired={a['slo_alerts_fired']}"
        print(
            f"  {t:<8} {a['sessions']:>4} "
            f"{warm.get('p50_ms', 0):>8} {warm.get('p99_ms', 0):>8} "
            f"{a['min_assigned_frac']:>12} {a['refused']:>8} "
            f"{a['reopens']:>8}{quality}"
        )
    fl = rep.get("server_obs", {}).get("fleet", {})
    if fl:
        print(
            f"  shards {fl.get('shards')} | arena "
            f"{fl.get('total_bytes', 0) / 1e6:.1f} MB | pressure "
            f"evictions {fl.get('pressure_evictions', 0)}"
        )
    bud = rep.get("server_obs", {}).get("budget", {})
    if bud:
        print(
            f"  thread budget: grants {bud.get('grants')} "
            f"(degraded {bud.get('degraded_grants')}), fairness gauge "
            f"{bud.get('fairness_index')}"
        )
    mig = rep.get("migration")
    if mig:
        print(
            f"  dfleet: failovers {mig['failovers']} | moved redirects "
            f"{mig['moved_redirects']} | handoff waits "
            f"{mig['handoff_waits']} | replayed {mig['replayed_total']}"
            f" | stale {mig['stale_total']} | reopens "
            f"{mig['reopens_total']}"
            + (
                f" | plan mismatches {mig['plan_mismatches_total']}"
                if rep.get("verify_plans") else ""
            )
        )
        det = rep.get("detector")
        if det:
            ttd = det.get("time_to_detect_s")
            print(
                "  detector: "
                + (f"time-to-detect {ttd}s | " if ttd is not None
                   else "")
                + f"suspects {det['suspects_entered']} | flaps "
                f"{det['suspect_flaps']} | fence refusals "
                f"{det['fence_refusals']} | false-positive ejections "
                f"{len(det['false_positive_ejections'])}"
            )
        for pid, p in sorted((rep.get("processes") or {}).items()):
            if p is None:
                print(f"  {pid}: (down)")
                continue
            line = " ".join(
                f"{k}={v}" for k, v in sorted(p.items())
                if not isinstance(v, float)
            )
            p99 = p.get("warm_tick_p99_ms_max")
            if p99 is not None:
                line += f" warm_p99_max={p99}ms"
            print(f"  {pid}: {line}")
        drill = rep.get("drill")
        if drill:
            print(f"  drill: {drill}")
    rs = rep.get("restart")
    if rs:
        print(
            f"  restart drill: mode={rs['mode']} at tick "
            f"{rs['at_tick']} | restored "
            f"{rs.get('sessions_restored', 0)} session(s) | reopens "
            f"{rs['reopens_total']} | transport retries "
            f"{rs['transport_retries_total']} | replayed "
            f"{rs['replayed_total']}"
            + (f" | drain-flushed {rs['flushed']}" if "flushed" in rs
               else "")
        )
    sc = rep.get("scaling")
    if sc:
        print(
            f"  scaling ({sc['model']}): measured "
            f"{sc['measured_warm_ticks_per_s']}/s on "
            f"{sc['measured_cores']} cores -> "
            + ", ".join(
                f"{c}c: {v}/s"
                for c, v in sc["projected_warm_ticks_per_s"].items()
            )
        )
    if rep["errors"]:
        print(f"  ERRORS ({len(rep['errors'])}):")
        for e in rep["errors"][:8]:
            print(f"    {e['session']}: {e['error']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m protocol_tpu.fleet.loadgen",
        description="Concurrent-trace load harness for the scheduler "
                    "fleet (see module docstring).",
    )
    ap.add_argument("--sessions", type=int, default=8)
    ap.add_argument("--tenants", type=int, default=2)
    ap.add_argument("--providers", type=int, default=512)
    ap.add_argument("--tasks", type=int, default=512)
    ap.add_argument("--ticks", type=int, default=8)
    ap.add_argument("--churn", type=float, default=0.02)
    ap.add_argument("--kernel", default="native-mt:1")
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--skew", action="store_true",
                    help="tenant 0 gets exactly one session")
    ap.add_argument("--trace", action="append", default=None,
                    help="recorded trace file(s); cycled over tenants")
    ap.add_argument("--admit-rate", type=float, default=None)
    ap.add_argument("--max-bytes", type=int, default=None)
    ap.add_argument("--queue-depth", type=int, default=8)
    ap.add_argument("--max-workers", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--restart-at-tick", type=int, default=None,
                    help="restart drill: take the servicer down once "
                         "every session passed this tick, bring a "
                         "fresh one up on the same port (warm "
                         "checkpoint rehydration)")
    ap.add_argument("--restart-mode", choices=("crash", "drain"),
                    default="crash")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=1)
    ap.add_argument("--processes", type=int, default=1,
                    help="N > 1 runs the DISTRIBUTED fleet: N real "
                         "servicer subprocesses behind the endpoint "
                         "ring over a shared journal root; the restart "
                         "drill becomes the process kill/migrate drill. "
                         "A chip belongs to ONE process, so the forked "
                         "fleet is pinned to the CPU backend; only "
                         "--processes 1 runs on whatever device jax "
                         "finds (named in the report)")
    ap.add_argument("--chaos", default=None,
                    help="seeded chaos spec (faults.plan.ChaosConfig): "
                         "rate faults arm every process's interceptor; "
                         "kill_proc_at_tick/migrate_at_tick/"
                         "pause_proc_at_tick script the driver-owned "
                         "process drills (pause = the zombie drill: "
                         "detector ejection + fence refusal)")
    ap.add_argument("--detect", action="store_true",
                    help="arm the autonomous failure detector "
                         "(forced on by the pause drill)")
    ap.add_argument("--rpc-timeout", type=float, default=600.0,
                    help="per-delta RPC deadline seconds (size small "
                         "for the pause drill so frozen sockets fail "
                         "over instead of hanging)")
    ap.add_argument("--max-retries", type=int, default=20)
    ap.add_argument("--verify-plans", action="store_true",
                    help="compare every fresh warm tick's plan against "
                         "the fault-free in-process replay "
                         "(bit-identity = zero double-applied ticks)")
    ap.add_argument("--events", type=int, default=None,
                    help="EVENT MODE: open-loop per-event arrival "
                         "instead of batch ticks — each session "
                         "replays N single-churn events through a "
                         "stream-mode wire session at the seeded "
                         "deterministic schedule; reports events/sec, "
                         "per-event p50/p99 µs, and divergence/"
                         "reconcile counts per tenant")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="event mode: target open-loop arrival rate "
                         "per session (Hz)")
    ap.add_argument("--reconcile-every", type=int, default=64,
                    help="event mode: full-solve reconciliation "
                         "cadence (events)")
    ap.add_argument("--mass-at-event", type=int, default=None,
                    help="event mode: once every session has sent "
                         "this many events, arm a shard blackout WITH "
                         "its seeded leave-storm schedule and fan the "
                         "mass leave events into every session's "
                         "firehose (faults x stream composition)")
    ap.add_argument("--mass-frac", type=float, default=0.1,
                    help="fraction of provider rows a mass event "
                         "takes down")
    ap.add_argument("--out", default=None, help="write the JSON report")
    ap.add_argument("--smoke", action="store_true",
                    help="exit non-zero unless every session completed "
                         "with assigned fraction >= 0.9 (with a "
                         "restart drill armed: also zero reopens — "
                         "recovery must be warm)")
    args = ap.parse_args(argv)

    from protocol_tpu.utils.platform import place_compile_cache

    place_compile_cache()
    if args.processes > 1:
        # a chip belongs to one process: the forked fleet's servicers are
        # pinned to the CPU (dfleet/manager.py), and this driver replays
        # their baselines in-process, so it runs the same float pipeline
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if args.events is not None:
        rep = run_events(
            sessions=args.sessions, tenants=args.tenants,
            providers=args.providers, tasks=args.tasks,
            events=args.events, rate_hz=args.rate,
            kernel=args.kernel, reconcile_every=args.reconcile_every,
            shards=args.shards, max_workers=args.max_workers,
            seed=args.seed, rpc_timeout_s=args.rpc_timeout,
            processes=args.processes, chaos=args.chaos,
            detect=args.detect, ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every, max_retries=args.max_retries,
            trace_path=(args.trace[0] if args.trace else None),
            mass_at_event=args.mass_at_event,
            mass_frac=args.mass_frac,
        )
        print(json.dumps(rep, indent=1, sort_keys=True))
        if args.out:
            with open(args.out, "w") as fh:
                json.dump(rep, fh, indent=1, sort_keys=True)
            print(f"report written: {args.out}")
        if args.smoke:
            bad = list(rep["errors"])
            for t, a in rep["tenants"].items():
                if not a["events"]:
                    bad.append({"tenant": t, "error": "no events ran"})
                if a["assigned_last_min"] is not None and (
                    # small synth populations seat ~90% even COLD
                    # (infeasible tasks); the smoke bar is "the stream
                    # did not bleed assignments", not "the marketplace
                    # is saturated". When a bit-identity verdict
                    # exists the final plan IS the fault-free plan —
                    # that bar subsumes this one (storms legitimately
                    # unseat the stormed rows' tasks).
                    a["assigned_last_min"] < 0.85 * args.tasks
                    and rep.get("bit_identity") is None
                    and rep.get("storm_events_total", 0) == 0
                ):
                    bad.append(
                        {"tenant": t, "error": "assigned < 0.85"}
                    )
            ladder = rep.get("ladder") or {}
            reopens = ladder.get("reopens", sum(
                a.get("reopens", 0) for a in rep["tenants"].values()
            ))
            if reopens:
                bad.append({"error": (
                    f"{reopens} full-snapshot reopens — stream "
                    "failover was not warm"
                )})
            bit = rep.get("bit_identity")
            if bit and bit["mismatches"]:
                bad.append({"error": (
                    f"{bit['mismatches']} final plans diverged from "
                    "the fault-free baseline: "
                    f"{bit['mismatched_sessions']}"
                )})
            drill = rep.get("drill")
            if drill and drill.get("mode") and not (
                drill.get("killed") or drill.get("drained")
            ):
                bad.append({"error": "process drill never fired"})
            src = rep.get("sources")
            if src and src["dropped"]:
                bad.append({"error": (
                    f"{src['dropped']} event sources dropped"
                )})
            det = rep.get("detector") or {}
            if det.get("false_positive_ejections"):
                bad.append({"error": (
                    "detector ejected never-faulted process(es): "
                    f"{det['false_positive_ejections']}"
                )})
            for pid, viols in (
                rep.get("witness_violations") or {}
            ).items():
                if viols:
                    bad.append({"proc": pid, "error": (
                        f"{len(viols)} lock-order witness violation(s)"
                    )})
            if bad:
                print(f"SMOKE FAIL: {bad}")
                return 1
            print("events smoke OK")
        return 0
    rep = run_load(
        sessions=args.sessions, tenants=args.tenants,
        providers=args.providers, tasks=args.tasks, ticks=args.ticks,
        churn=args.churn, kernel=args.kernel, shards=args.shards,
        skew=args.skew, traces=args.trace, admit_rate=args.admit_rate,
        max_bytes=args.max_bytes, queue_depth=args.queue_depth,
        max_workers=args.max_workers, seed=args.seed,
        restart_at_tick=args.restart_at_tick,
        restart_mode=args.restart_mode,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        processes=args.processes, chaos=args.chaos,
        detect=args.detect, rpc_timeout_s=args.rpc_timeout,
        max_retries=args.max_retries, verify_plans=args.verify_plans,
    )
    _print_report(rep)
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(rep, fh, indent=1, sort_keys=True)
        print(f"report written: {args.out}")
    if args.smoke:
        bad = list(rep["errors"])
        for t, a in rep["tenants"].items():
            if a["min_assigned_frac"] < 0.9:
                bad.append(
                    {"tenant": t, "error": "assigned frac < 0.9"}
                )
        rs = rep.get("restart")
        if rs and rs["reopens_total"] > 0:
            bad.append({
                "restart": rs["mode"],
                "error": f"{rs['reopens_total']} full-snapshot "
                         "reopens after restart — recovery was not "
                         "warm",
            })
        if rs and not rs.get("restarted"):
            bad.append({
                "restart": rs["mode"],
                "error": "restart controller never fired",
            })
        drill = rep.get("drill")
        if drill:
            mig = rep["migration"]
            if mig["reopens_total"] > 0:
                bad.append({
                    "drill": drill["mode"],
                    "error": f"{mig['reopens_total']} full-snapshot "
                             "reopens after the process drill — "
                             "recovery was not warm",
                })
            if not (
                drill.get("killed") or drill.get("drained")
                or drill.get("paused")
            ):
                bad.append({
                    "drill": drill["mode"],
                    "error": "process drill never fired",
                })
            if drill.get("paused") and not drill.get(
                "ejected_by_detector"
            ):
                bad.append({
                    "drill": drill["mode"],
                    "error": "paused process was never ejected by the "
                             "detector",
                })
            if mig.get("plan_mismatches_total"):
                bad.append({
                    "drill": drill["mode"],
                    "error": f"{mig['plan_mismatches_total']} plans "
                             "diverged from the fault-free replay",
                })
            det = rep.get("detector") or {}
            if det.get("false_positive_ejections"):
                bad.append({
                    "drill": drill["mode"],
                    "error": "detector ejected never-faulted "
                             f"process(es): "
                             f"{det['false_positive_ejections']}",
                })
            for pid, viols in (
                rep.get("witness_violations") or {}
            ).items():
                if viols:
                    bad.append({
                        "proc": pid,
                        "error": f"{len(viols)} lock-order witness "
                                 "violation(s)",
                    })
        if bad:
            print(f"SMOKE FAIL: {bad}")
            return 1
        print("loadgen smoke OK")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
