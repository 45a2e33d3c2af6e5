"""Scheduler gRPC backend service.

The seam from BASELINE.json's north star: a control plane (the reference's
Rust orchestrator, or this repo's Python one) calls ``Assign`` with columnar
provider/requirement batches; the backend builds the cost structure on the
accelerator and returns the matching. Columnar fixed-width payloads keep the
(de)serialization cost linear in P+T — no per-entity JSON on the hot path
(SURVEY.md §7 hard part #6).

Wire revisions (the fallback ladder, newest first):

  v2 sessions  ``OpenSession`` (client-streamed snapshot) + ``AssignDelta``
               (churned rows only): the server pins the warm arena behind a
               ``(session_id, epoch_fingerprint)`` key and per-tick wire
               cost is O(churn). Refused deltas (unknown session, epoch or
               tick mismatch, evicted) drop the client one rung down.
  v2 unary     ``AssignV2``: tensor-frame batches (``TensorBlob`` columns,
               ``tobytes``/``frombuffer``, zero per-element Python work),
               full snapshot per call, stateless.
  v1 unary     ``Assign``: repeated-scalar proto fields. Frozen contract —
               old clients keep working against new servers.

Service stubs are hand-wired with grpc generic handlers (no protoc grpc
plugin needed); messages come from protocol_tpu.proto.scheduler_pb2.

Kernels: "greedy" (first-fit scan), "auction" (dense Bertsekas),
"sinkhorn" (entropic OT + rounding), "topk" (streaming candidates + sparse
frontier auction — the scale path), "native"/"native-mt" (the C++ CPU
engine; native-mt solves ride the servicer's persistent warm arena).
"""

from __future__ import annotations

import os
import time
import uuid
from concurrent import futures
from typing import NamedTuple, Optional

import grpc
import numpy as np

from protocol_tpu import obs as obs_pkg
from protocol_tpu.obs.metrics import ObsRegistry, tenant_of
from protocol_tpu.obs.spans import TRACER as _tracer, span_dicts_compact
from protocol_tpu.ops.cost import CostWeights, cost_matrix
from protocol_tpu.ops.encoding import EncodedProviders, EncodedRequirements
from protocol_tpu.proto import scheduler_pb2 as pb
from protocol_tpu.proto.wire import (
    P_WIRE_DTYPES,
    R_WIRE_DTYPES,
    assemble_snapshot,
    canon_columns,
    chunk_snapshot,
    decode_providers_v2,
    decode_requirements_v2,
    dirty_rows,
    encode_providers_v2,
    encode_requirements_v2,
    epoch_fingerprint,
    strip_padding,
    take_rows,
    unblob,
    blob,
)
from protocol_tpu.sched.tpu_backend import TpuBatchMatcher
from protocol_tpu.services.session_store import (
    SolveSession,
    make_solve_arena,
    parse_native_threads,
    parse_session_kernel,
    _pad_cols,
)
from protocol_tpu.utils.metrics import SeamMetrics

SERVICE_NAME = "protocol_tpu.scheduler.v1.SchedulerBackend"


def _np(arr, dtype):
    # repeated-scalar containers support the sequence protocol: fromiter
    # fills the destination buffer directly, no intermediate Python list
    return np.fromiter(arr, dtype=dtype, count=len(arr))


def providers_from_proto(msg: pb.ProviderBatch) -> EncodedProviders:
    n = len(msg.gpu_count)
    return EncodedProviders(
        gpu_count=_np(msg.gpu_count, np.int32),
        gpu_mem_mb=_np(msg.gpu_mem_mb, np.int32),
        gpu_model_id=_np(msg.gpu_model_id, np.int32),
        has_gpu=_np(msg.has_gpu, bool),
        has_cpu=_np(msg.has_cpu, bool),
        cpu_cores=_np(msg.cpu_cores, np.int32),
        ram_mb=_np(msg.ram_mb, np.int32),
        storage_gb=_np(msg.storage_gb, np.int32),
        lat=_np(msg.lat, np.float32),
        lon=_np(msg.lon, np.float32),
        has_location=_np(msg.has_location, bool),
        price=_np(msg.price, np.float32),
        load=_np(msg.load, np.float32),
        valid=np.ones(n, bool),
    )


def requirements_from_proto(msg: pb.RequirementBatch) -> EncodedRequirements:
    t = len(msg.cpu_cores)
    k = max(int(msg.max_gpu_options), 1)
    w = max(int(msg.model_words), 1)
    return EncodedRequirements(
        cpu_required=_np(msg.cpu_required, bool),
        cpu_cores=_np(msg.cpu_cores, np.int32),
        ram_mb=_np(msg.ram_mb, np.int32),
        storage_gb=_np(msg.storage_gb, np.int32),
        gpu_opt_valid=_np(msg.gpu_opt_valid, bool).reshape(t, k),
        gpu_count=_np(msg.gpu_count, np.int32).reshape(t, k),
        gpu_mem_min=_np(msg.gpu_mem_min, np.int32).reshape(t, k),
        gpu_mem_max=_np(msg.gpu_mem_max, np.int32).reshape(t, k),
        gpu_total_mem_min=_np(msg.gpu_total_mem_min, np.int32).reshape(t, k),
        gpu_total_mem_max=_np(msg.gpu_total_mem_max, np.int32).reshape(t, k),
        gpu_model_mask=_np(msg.gpu_model_mask, np.uint32).reshape(t, k, w),
        gpu_model_constrained=_np(msg.gpu_model_constrained, bool).reshape(t, k),
        lat=_np(msg.lat, np.float32),
        lon=_np(msg.lon, np.float32),
        has_location=_np(msg.has_location, bool),
        priority=_np(msg.priority, np.float32),
        valid=np.ones(t, bool),
    )


def _pad_pow2(enc, n_real: int):
    """Pad an encoded batch to the next pow2 bucket with valid=False rows:
    the wire carries only real rows (no valid mask), while bucketed shapes
    keep the backend's jit cache from recompiling per batch size."""
    import dataclasses

    if n_real <= 0:
        return enc
    target = 1 << (n_real - 1).bit_length()
    if target == n_real:
        return enc
    out = {}
    for f in dataclasses.fields(enc):
        a = np.asarray(getattr(enc, f.name))
        pad = [(0, target - n_real)] + [(0, 0)] * (a.ndim - 1)
        out[f.name] = np.pad(a, pad)
    out["valid"] = np.concatenate(
        [np.ones(n_real, bool), np.zeros(target - n_real, bool)]
    )
    return dataclasses.replace(enc, **out)


def _delta_crc(request: "pb.AssignDeltaRequest") -> int:
    """Byte-exact identity of one delta tick WITHOUT re-serializing the
    just-deserialized message (that would add O(delta bytes) of encode
    work to every tick inside the session lock): CRC over the tick
    cursor plus every blob's already-materialized raw bytes — the only
    payload a retransmitted delta can differ in. The idempotent-
    retransmit dedup (and the checkpointed cursor it survives restarts
    through) rests on this identity."""
    import zlib

    crc = zlib.crc32(int(request.tick).to_bytes(8, "little"))  # lint: unlocked-ok (protobuf field, not session state)
    for b in (request.provider_rows, request.task_rows):
        crc = zlib.crc32(b.data, crc)
    for batch in (request.providers, request.requirements):
        for nt in batch.columns:
            crc = zlib.crc32(nt.name.encode(), crc)
            crc = zlib.crc32(nt.tensor.data, crc)
    return crc


def _stream_gap_ceiling() -> Optional[float]:
    """Server-side certified-gap ceiling for streaming sessions
    (PROTOCOL_TPU_STREAM_GAP_CEILING): when the streamed plan's
    certified optimality gap crosses it, the engine reconciles inline
    instead of serving the drifted plan. Unset = cadence-only
    reconciliation."""
    raw = os.environ.get("PROTOCOL_TPU_STREAM_GAP_CEILING", "").strip()
    return float(raw) if raw else None


class _SolveOut(NamedTuple):
    """Kernel output over the REAL (unpadded) row counts."""

    p4t: np.ndarray  # [T] i32, -1 = unassigned
    t4p: np.ndarray  # [P] i32, -1 = idle
    num_assigned: int
    price: Optional[np.ndarray]  # [P] f32 (sparse/native kernels)
    # the warm arena's last_stats, COPIED under the arena lock (reading
    # it later would race the next unary solve) — obs/trace provenance
    arena_stats: Optional[dict] = None


class SchedulerBackendServicer:
    def __init__(
        self,
        max_sessions: int = 8,
        session_ttl_s: float = 900.0,
        fleet=None,
        slo=None,
    ):
        from protocol_tpu.sched.cand_cache import CandidateMemo

        self._cand_memo = CandidateMemo()
        # persistent warm arena for the unary "native-mt"/"sinkhorn-mt"
        # kernels: steady-state Assign repeats (the heartbeat loop's
        # byte-identical or lightly churned fleets) reuse the candidate
        # structure + solver duals and recompute only dirty rows — the
        # native twin of _cand_memo's delta-awareness, but incremental
        # rather than exact-repeat-only.
        #
        # Locking is SHARDED, not global: this lock guards only the unary
        # path's shared arena (which mutates carried state in place — one
        # arena, necessarily serialized). Session solves take their OWN
        # ``session.lock`` (services/session_store.py), so two delta
        # sessions never serialize each other; what they share instead is
        # the bounded EngineThreadBudget below, which keeps N concurrent
        # solves from oversubscribing the host by N x "all hardware
        # threads" (grants are thread-count invariant by the engines'
        # determinism contract, so borrowing fewer threads never changes
        # a matching).
        self._native_arena = None
        from protocol_tpu.utils.lockwitness import make_lock

        self._unary_arena_lock = make_lock("arena")
        # ---- fleet layer (always on; the defaults are transparent):
        # sessions live in a consistent-hash sharded fabric (each shard
        # its own lock domain, global count/byte budgets enforced by
        # cross-shard LRU pressure), engine threads come from the
        # weighted-fair budget (bit-compatible with the base budget for
        # a sole tenant), and per-tenant token buckets gate admission
        # (rate=None admits everything but still counts). ``fleet`` is
        # a FleetConfig; None reads PROTOCOL_TPU_FLEET_* from the env.
        from protocol_tpu.fleet import (
            FairThreadBudget,
            FleetConfig,
            SessionFabric,
            TenantAdmission,
        )

        cfg = fleet if fleet is not None else FleetConfig.from_env()
        self.fleet_config = cfg
        self._engine_budget = FairThreadBudget(weights=cfg.tenant_weights)
        self.sessions = SessionFabric(
            shards=cfg.shards,
            max_sessions=max_sessions,
            ttl_s=session_ttl_s,
            max_bytes=cfg.max_bytes,
            tenant_max_bytes=cfg.tenant_max_bytes,
            vnodes=cfg.vnodes,
        )
        self.admission = TenantAdmission(
            rate=cfg.admit_rate, burst=cfg.admit_burst
        )
        self.seam = SeamMetrics(role="server")
        # observability plane: per-session tick histograms (true
        # p50/p99/p999), assigned fraction, arena reuse ratio, plus
        # budget/store gauges read at scrape time. The dict snapshot is
        # authoritative; /metrics is wired by serve(metrics_port=...).
        self.obs = ObsRegistry(role="server")
        # SLO engine (obs/slo.py): declarative per-tenant objectives
        # evaluated with tick-indexed multi-window burn rates inside
        # observe_tick; ``slo`` is an SLOConfig, None reads the
        # PROTOCOL_TPU_SLO_* env vars (all-unset = inert)
        from protocol_tpu.obs.slo import SLOConfig, SLOEngine

        self.slo = SLOEngine(
            slo if slo is not None else SLOConfig.from_env()
        )
        self.obs.attach(
            budget=self._engine_budget,
            store=self.sessions,
            fleet=self.sessions,
            admission=self.admission,
            slo=self.slo,
            proc_id=cfg.proc_id,
        )
        # flight recorder (PROTOCOL_TPU_TRACE=<path>): any solve served by
        # this backend records its exact inputs + outcomes — unary calls
        # via the column differ, the session protocol via its own wire
        # frames (see protocol_tpu/trace/recorder.py). Best-effort: a
        # capture failure never fails an RPC.
        self.trace = None
        if os.environ.get("PROTOCOL_TPU_TRACE"):
            from protocol_tpu.trace.recorder import TraceRecorder

            self.trace = TraceRecorder.from_env("server")
        # ---- resilience layer (chaos plane). With ``ckpt_dir`` set,
        # every session keeps a crash-atomic on-disk twin (flushed on
        # the tick cadence BEFORE the tick is acknowledged), and a
        # fresh servicer REHYDRATES them here: after a crash+restart
        # the client's next AssignDelta resumes at the checkpointed
        # cursor instead of being refused into a full-snapshot reopen
        # herd. ``draining`` is the SIGTERM drain flag: OpenSession
        # stops admitting, in-flight ticks finish, checkpoints flush.
        self.draining = False
        self.ckpt = None
        # ---- distributed fleet (dfleet) router state. ``_moved`` maps
        # a migrated-away session to the endpoint now serving it — the
        # "moved:<endpoint>" redirect answer the client ladder follows
        # warm. ``_no_rehydrate`` tombstones sessions this process
        # itself evicted (lru/pressure/chaos): eviction exists to
        # RELEASE memory, so the lazy journal rehydrate below must not
        # resurrect the victim on its next delta — the PR 9 contract
        # (eviction = one counted reopen) stands. Both are bounded and
        # guarded by the leaf ``router`` lock (dict ops only, safely
        # acquirable from under a shard lock in the eviction callback).
        from collections import OrderedDict as _ODict

        self._router_lock = make_lock("router")
        self._moved: "_ODict[str, str]" = _ODict()
        self._no_rehydrate: "_ODict[str, bool]" = _ODict()
        self._rehydrating: set = set()
        self._migrating: set = set()
        self.proc_id = cfg.proc_id
        self.endpoint = cfg.endpoint
        if cfg.ckpt_dir:
            from protocol_tpu.faults.checkpoint import SessionCheckpointer

            self.ckpt = SessionCheckpointer(
                cfg.ckpt_dir, every=cfg.ckpt_every, proc_id=cfg.proc_id
            )
            # newest-first, capped at the session budget: stale files
            # must never crowd the restore past max_sessions (the put
            # pressure below would then LRU-evict restored sessions)
            for session in self.ckpt.load_all(
                budget=self._engine_budget, limit=max_sessions
            ):
                self.sessions.put(session)
                self.seam.count("session_restored")
            # checkpoint GC: a ttl-expired or client-dropped session's
            # client is GONE — its file would only resurrect a dead
            # session at every restart, growing ckpt_dir without bound.
            # lru/pressure/replace keep their files: the session is
            # alive client-side (or the file already belongs to the
            # same-id successor, which flushed over it at open). Every
            # OTHER involuntary let-go additionally tombstones the
            # session against LAZY rehydration (see _router_lock note).
            def _ckpt_gc(session, reason: str) -> None:
                self.ckpt.forget(session.session_id)
                if reason in ("ttl", "drop"):
                    self.ckpt.drop(session.session_id)
                elif reason not in ("migrate", "replace"):
                    self._router_tombstone(session.session_id)

            self.sessions.on_let_go = _ckpt_gc

    # ---------------- dfleet router surface ----------------

    _ROUTER_CAP = 4096  # bound for the moved/tombstone maps (client-
    # minted session ids; same rationale as fabric._MAX_TENANT_KEYS)

    def _router_tombstone(self, session_id: str) -> None:
        with self._router_lock:
            self._no_rehydrate[session_id] = True
            while len(self._no_rehydrate) > self._ROUTER_CAP:
                self._no_rehydrate.popitem(last=False)

    def _router_adopt(self, session_id: str) -> None:
        """A session was (re)opened or rehydrated HERE: this process
        owns it now — clear any stale redirect/tombstone so its deltas
        are served, not bounced."""
        with self._router_lock:
            self._moved.pop(session_id, None)
            self._no_rehydrate.pop(session_id, None)

    def _moved_to(self, session_id: str) -> Optional[str]:
        """Where this session was migrated to, or None. The JOURNAL'S
        LOCATION is the authority and the redirect map only a cache: if
        the journal is back in OUR namespace (the target died and the
        ring re-routed it here), the stale redirect would bounce
        clients at a corpse forever — adopt the session back instead."""
        with self._router_lock:
            moved = self._moved.get(session_id)
            in_flight = session_id in self._migrating
        if moved is None:
            return None
        # in-flight migration: the journal is legitimately still here
        # (flush happens after the redirect is recorded) — the redirect
        # stands, and the client's handoff-wait rung covers the rename
        if not in_flight and self.ckpt is not None and os.path.exists(
            self.ckpt.path_for(session_id)
        ):
            self._router_adopt(session_id)
            return None
        return moved

    def _fence_route(self, session_id: str) -> Optional[str]:
        """None = this process's journal fence is intact (the normal
        case — one stat call). Otherwise the namespace's fencing epoch
        was SUPERSEDED while this process wasn't looking (SIGSTOP
        zombie resuming after a detector ejection, partitioned node):
        its journals were re-routed along the ring, so it must neither
        ack nor admit — split-brain is refused by construction. Returns
        the session's new home endpoint per the fence-stamped topology,
        or "" when the stamp carries no usable route (the client
        re-opens down the ladder, counted)."""
        if self.ckpt is None or not self.ckpt.fence_superseded():
            return None
        self.seam.count("fence_refused")
        topo = self.ckpt.fence_state().get("topology")
        if topo:
            try:
                from protocol_tpu.dfleet.topology import FleetTopology

                ep = FleetTopology.from_dict(topo).endpoint_for(
                    session_id
                )
                if ep and ep != self.endpoint:
                    return ep
            except Exception:  # torn/foreign stamp: fall through
                pass
        return ""

    def _rehydrate(self, session_id: str, fingerprint: str):
        """Lazy warm restore behind a delta miss: if this process's
        journal namespace holds the session (a migration handoff landed
        it here, or a crash-restart's boot cap skipped it), load and
        adopt it. None = nothing to restore (the caller answers the
        miss normally). Single-flight per session id: a concurrent miss
        returns None and rides the client's bounded handoff-wait rung."""
        if self.ckpt is None:
            return None
        with self._router_lock:
            if (
                session_id in self._no_rehydrate
                or session_id in self._moved
                or session_id in self._rehydrating
            ):
                return None
            self._rehydrating.add(session_id)
        try:
            loaded = self.ckpt.load_one(
                session_id, budget=self._engine_budget
            )
            if loaded is None:
                return None
            self.sessions.put(loaded)
            self.seam.count("session_rehydrated")
        finally:
            with self._router_lock:
                self._rehydrating.discard(session_id)
        session, _ = self.sessions.get(session_id, fingerprint)
        return session

    def migrate_out(
        self,
        target_endpoint: str,
        target_proc_id: str,
        session_ids=None,
    ) -> int:
        """Live-drain sessions onto another process: record the
        redirect FIRST (a delta racing the eviction is answered
        "moved:", never "unknown"), evict (in-flight solves refuse via
        the evicted flag), flush the journal at its final tick, and
        hand it off atomically into the target's namespace. The target
        rehydrates each session warm on its first redirected delta —
        zero client reopens, and the tick-cursor/CRC dedup carries the
        retransmit guarantee across the boundary."""
        if self.ckpt is None:
            return 0
        wanted = set(session_ids) if session_ids else None
        moved = 0
        for session in self.sessions.snapshot_sessions():
            sid = session.session_id
            if wanted is not None and sid not in wanted:
                continue
            with self._router_lock:
                self._moved[sid] = target_endpoint
                self._migrating.add(sid)
                while len(self._moved) > self._ROUTER_CAP:
                    self._moved.popitem(last=False)
            try:
                self.sessions.shard_of(sid).evict(sid, reason="migrate")
                with session.lock:
                    flushed = self._flush_locked(session)
                if not flushed or not self.ckpt.handoff(
                    sid, target_proc_id
                ):
                    # no journal to move (flush failed / never
                    # flushed): drop the redirect — the client's ladder
                    # re-opens at the target instead of chasing a
                    # journal that is not there (counted, explicit, the
                    # pre-dfleet contract)
                    with self._router_lock:
                        self._moved.pop(sid, None)
                    continue
            finally:
                with self._router_lock:
                    self._migrating.discard(sid)
            moved += 1
            self.seam.count("session_migrated_out")
        return moved

    def Migrate(
        self, request: pb.MigrateRequest, context
    ) -> pb.MigrateResponse:
        """Admin surface for live migration (the dfleet manager and
        rolling-upgrade drills call this; it is not on any client hot
        path)."""
        with self._rpc_span("rpc.Migrate", context):
            if not request.target_endpoint or not request.target_proc_id:
                return pb.MigrateResponse(
                    ok=False,
                    error="UNAVAILABLE: migrate needs target_endpoint "
                          "and target_proc_id",
                )
            if self.ckpt is None:
                return pb.MigrateResponse(
                    ok=False,
                    error="UNAVAILABLE: no checkpoint journal "
                          "configured (ckpt_dir unset) — nothing to "
                          "hand off",
                )
            moved = self.migrate_out(
                request.target_endpoint,
                request.target_proc_id,
                list(request.session_ids) or None,
            )
            return pb.MigrateResponse(ok=True, moved=moved)

    # ---------------- shared kernel dispatch ----------------

    def _solve(
        self,
        ep: EncodedProviders,
        er: EncodedRequirements,
        weights: CostWeights,
        kernel: str,
        top_k: int,
        eps: float,
        max_iters: int,
        warm_price: Optional[np.ndarray],
        seed_p4t: Optional[np.ndarray],
        context,
    ) -> _SolveOut:
        """One solve over unpadded encoded batches: pads to the pow2
        bucket, dispatches the kernel, slices back to real row counts.
        Shared verbatim by the v1 and v2 surfaces — wire parity is a
        property of the codec, never of the kernel path."""
        P = int(np.asarray(ep.gpu_count).shape[0])
        T = int(np.asarray(er.cpu_cores).shape[0])
        if P == 0 or T == 0:
            # degenerate batches are legal: nothing to match
            return _SolveOut(
                np.full(T, -1, np.int32), np.full(P, -1, np.int32), 0, None
            )
        # bucket the batch (valid=False padding rows) so repeat calls reuse
        # the jit cache; replies are sliced back to the real row counts, and
        # padding rows are infeasible by mask so they never win assignments
        ep = _pad_pow2(ep, P)
        er = _pad_pow2(er, T)

        if kernel == "best":
            # per-provider argmin over compatible tasks: the one-to-many
            # unbounded phase of the batch matcher (many providers may pick
            # the same task, so this is not a matching kernel)
            from protocol_tpu.sched.tpu_backend import _solve_unbounded

            best, _feas = _solve_unbounded(ep, er, weights)
            t4p = np.asarray(best)[:P].astype(np.int32)
            return _SolveOut(
                np.full(T, -1, np.int32), t4p, int((t4p >= 0).sum()), None
            )

        if kernel == "native" or kernel.startswith(
            ("native-mt", "sinkhorn-mt", "jax")
        ):
            # the engines behind the seam: "native" is the
            # single-threaded Gauss-Seidel solve, "native-mt[:N]" the
            # multi-threaded auction engine, "sinkhorn-mt[:N]" the
            # sparse entropic engine, and "jax[:D]" the accelerator-path
            # arena (D sharded-gen devices), all but "native" through
            # the servicer's persistent warm arena (N threads; absent/0
            # = all hardware threads / all visible devices — the suffix
            # spelling keeps the wire message unchanged)
            from protocol_tpu import native as native_mod

            p_padded = int(np.asarray(ep.gpu_count).shape[0])
            if kernel == "native":
                cand_p, cand_c = native_mod.fused_topk_candidates(
                    ep, er, weights,
                    k=min(max(top_k or 64, 1), p_padded),
                )
                p4t_full = native_mod.auction_sparse(
                    cand_p, cand_c, num_providers=p_padded
                )
                price_full = np.zeros(p_padded, np.float32)
            else:
                parsed = parse_session_kernel(kernel)
                if parsed is None:
                    context.abort(
                        grpc.StatusCode.INVALID_ARGUMENT,
                        f"bad native engine thread suffix {kernel!r}",
                    )
                engine, threads = parsed
                requested_k = max(top_k or 64, 1)
                # thread grant is borrowed INSIDE the arena lock: the
                # unary arena is one serialized resource, so a request
                # parked on the lock must hold NOTHING — a pre-lock grant
                # would reserve idle threads for the whole duration of
                # the running solve, starving concurrent session solves
                # (which draw on the same budget from their own locks).
                # No deadlock: budget holders never need this lock.
                with self._unary_arena_lock:
                    if (
                        self._native_arena is None
                        or self._native_arena.k != requested_k
                        or self._native_arena.engine != engine
                    ):
                        # a changed k or engine changes the whole
                        # carried structure: a fresh arena (cold
                        # solve) is the only honest answer
                        from protocol_tpu.services.session_store import (
                            make_solve_arena,
                        )

                        self._native_arena = make_solve_arena(
                            engine, k=requested_k, threads=threads,
                        )
                    grant = self._engine_budget.acquire(threads, "unary")
                    try:
                        self._native_arena.threads = grant
                        p4t_full = self._native_arena.solve(
                            ep, er, weights
                        )
                        price_full = self._native_arena.price
                        arena_stats = dict(self._native_arena.last_stats)
                    finally:
                        self._engine_budget.release(grant, "unary")
            if kernel == "native":
                arena_stats = None
            p4t = np.asarray(p4t_full)[:T]
            t4p = np.full(P, -1, np.int32)
            seated = np.flatnonzero((p4t >= 0) & (p4t < P))
            t4p[p4t[seated]] = seated.astype(np.int32)
            return _SolveOut(
                p4t, t4p, int((p4t >= 0).sum()),
                np.asarray(price_full)[:P].astype(np.float32),
                arena_stats,
            )

        if kernel == "topk":
            from protocol_tpu.ops.sparse import (
                assign_auction_sparse_scaled,
                assign_auction_sparse_warm,
            )

            # tile must divide the (padded, pow2) T
            t_padded = int(np.asarray(er.cpu_cores).shape[0])
            tile = min(1024, t_padded)
            while t_padded % tile != 0:
                tile -= 1
            p_padded = int(np.asarray(ep.gpu_count).shape[0])
            # bidirectional: same coverage-safe generator as the in-process
            # matcher (_bounded_t4p_sparse) — remote/in-process parity.
            # Content-hash memoized: the steady-state heartbeat loop sends
            # a byte-identical fleet, and the stateless seam must not
            # re-pay the O(P*T) generation for it (VERDICT r4 item 3)
            cand_p, cand_c = self._cand_memo.get(
                ep, er, weights,
                k=max(top_k or 64, 1), tile=tile,
                reverse_r=8, extra=16,
            )
            if (
                warm_price is not None and seed_p4t is not None
                and len(warm_price) == P and len(seed_p4t) == T
            ):
                # stateless incremental solve: warm state rode the wire.
                # Wire input is untrusted: clamp out-of-range seeds and
                # drop duplicates (the warm kernel requires injectivity
                # over >= 0 — a duplicated provider index would produce a
                # corrupt two-tasks-one-provider "matching").
                price0 = np.zeros(p_padded, np.float32)
                price0[:P] = np.nan_to_num(
                    np.asarray(warm_price, np.float32),
                    nan=0.0, posinf=0.0, neginf=0.0,
                )
                p4t0 = np.full(t_padded, -1, np.int32)
                seeds = np.asarray(seed_p4t, np.int32).copy()
                seeds = np.where((seeds >= 0) & (seeds < P), seeds, -1)
                pos = seeds >= 0
                _, first = np.unique(seeds[pos], return_index=True)
                keep = np.zeros(int(pos.sum()), bool)
                keep[first] = True
                seeds[np.flatnonzero(pos)[~keep]] = -1
                p4t0[:T] = seeds
                res, price = assign_auction_sparse_warm(
                    cand_p, cand_c, p_padded,
                    price0=price0, p4t0=p4t0,
                    eps=eps or 0.02,
                    max_iters=max_iters or 20000,
                )
            else:
                res, price = assign_auction_sparse_scaled(
                    cand_p, cand_c, p_padded,
                    eps_end=eps or 0.02,
                    max_iters_per_phase=max_iters or 4000,
                    with_prices=True,
                )
            p4t = np.asarray(res.provider_for_task)[:T]
            t4p = np.asarray(res.task_for_provider)[:P]
            return _SolveOut(
                p4t, t4p, int((p4t >= 0).sum()),
                np.asarray(price)[:P].astype(np.float32),
            )

        from protocol_tpu.ops.assign import (
            assign_auction,
            assign_greedy,
            assign_sinkhorn,
        )

        cost, _ = cost_matrix(ep, er, weights)
        if kernel == "greedy":
            res = assign_greedy(cost)
        elif kernel == "sinkhorn":
            res = assign_sinkhorn(
                cost,
                eps=eps or 0.05,
                num_iters=max_iters or 200,
            )
        elif kernel == "auction":
            from protocol_tpu.ops.cost import with_tie_jitter

            # same degeneracy breaker as the in-process dense solve
            # (sched/tpu_backend._solve_bounded) — identical jitter is
            # what RemoteBatchMatcher's parity with TpuBatchMatcher
            # rests on
            res = assign_auction(
                with_tie_jitter(cost),
                eps=eps or 0.01,
                max_iters=max_iters or 500,
            )
        else:
            context.abort(
                grpc.StatusCode.INVALID_ARGUMENT, f"unknown kernel {kernel!r}"
            )
        p4t = np.asarray(res.provider_for_task)[:T]
        t4p = np.asarray(res.task_for_provider)[:P]
        return _SolveOut(p4t, t4p, int((p4t >= 0).sum()), None)

    @staticmethod
    def _weights_of(request) -> CostWeights:
        if request.HasField("weights"):
            # submessage presence is real in proto3: a set weights message
            # is used verbatim, so a legitimate 0.0 weight survives the wire
            return CostWeights(
                price=request.weights.price,
                load=request.weights.load,
                proximity=request.weights.proximity,
                priority=request.weights.priority,
            )
        return CostWeights()

    # ---------------- observability helpers ----------------

    def _rpc_span(self, name: str, context, **attrs):
        """Root span for one RPC, adopting the client's trace context
        from the ``x-pt-span`` metadata header so a client tick stitches
        into one causal trace across the seam. Tolerates a None/bare
        context (tests drive servicer methods directly)."""
        md = (
            context.invocation_metadata()
            if context is not None
            and hasattr(context, "invocation_metadata")
            else None
        )
        return _tracer.span(
            name, remote_parent=_tracer.extract(md), **attrs,
        )

    @staticmethod
    def _enrich_metrics(
        base: dict, arena_stats: Optional[dict], mark: int, root,
    ) -> dict:
        """Outcome-frame metrics: the base phase numbers plus the
        arena's scalar stats (incl. the flattened ``eng_*`` native
        phase stats) and the spans this RPC completed — what the obs
        report renders offline."""
        m = dict(base)
        if arena_stats:
            for k, v in arena_stats.items():
                # base keys (the RPC-level decode/solve walls) win over
                # arena keys of the same name (stage-level walls): the
                # stage split still rides in gen_ms + the eng_* phases
                if k not in m and isinstance(v, (int, float, bool, str)):
                    m[k] = v
        if root is not None:
            sp = _tracer.since(mark, trace=root["trace"])
            if sp:
                m["trace_id"] = root["trace"]
                m["spans"] = span_dicts_compact(sp)
        return m

    def _flush_locked(self, session) -> bool:
        """Checkpoint ``session`` (caller holds ``session.lock``) and
        record what the flush cost on the seam — phases ``ckpt_flush``,
        ``ckpt_export``, ``ckpt_deflate`` (zlib time inside the flush),
        ``ckpt_join`` (the flush's wait for the tick's prefix job on the
        worker; 0.0 where there was none to wait for), for a flush that
        found a job run (hit or stale) ``ckpt_worker`` and
        ``ckpt_encode`` (the job's wall, and of it the SNAPSHOT
        message), and the journal's bytes on disk; a flush that used the tick's
        prefix job counts ``ckpt_prefix_hit`` and the worker's zlib
        time as phase ``ckpt_overlap``, any other ``ckpt_prefix_miss``
        — so Health carries them with no field of its own."""
        if not self.ckpt.flush_locked(session):
            return False
        took = self.ckpt.last_flush
        self.seam.observe_ms("ckpt_flush", took["flush_ms"])
        self.seam.observe_ms("ckpt_export", took["export_ms"])
        self.seam.observe_ms("ckpt_deflate", took["deflate_ms"])
        self.seam.observe_ms("ckpt_join", took["join_ms"])
        if "worker_ms" in took:
            self.seam.observe_ms("ckpt_worker", took["worker_ms"])
            self.seam.observe_ms("ckpt_encode", took["encode_ms"])
        self.seam.add_bytes("ckpt", took["bytes_out"])
        if took["prefix"] == "hit":
            self.seam.observe_ms("ckpt_overlap", took["overlap_ms"])
            self.seam.count("ckpt_prefix_hit")
        else:
            self.seam.count("ckpt_prefix_miss")
        return True

    def _observe_tick(
        self,
        session_id: str,
        t0: float,
        n_tasks: int,
        num_assigned: int,
        arena_stats: Optional[dict] = None,
        delta_rows: int = 0,
        trace_tick: Optional[int] = None,
    ) -> list:
        """Returns the SLO alert events this tick fired/cleared (empty
        without a configured SLO engine or a breach) — the caller lands
        them in the trace as event frames. ``trace_tick`` anchors the
        EVENT frame at the caller's wire tick (session paths MUST pass
        it: this runs after the session lock is released, so a pipelined
        delta may already have advanced the recorder's stream tick)."""
        from protocol_tpu import obs

        if not obs.enabled():
            # PROTOCOL_TPU_OBS=0 turns the WHOLE plane off — per-session
            # registries included, not just spans and engine stats
            return []
        alerts = self.obs.observe_tick(
            session_id, (time.perf_counter() - t0) * 1e3, n_tasks,
            num_assigned, arena_stats=arena_stats, delta_rows=delta_rows,
        )
        if alerts and self.trace is not None:
            from protocol_tpu.trace.recorder import safe as _trace_safe

            # structured breach events ride the flight recorder too, so
            # replay/report can show WHEN the quality plane paged. The
            # unary registry keys ("unary:v1"/"unary:v2") are NOT trace
            # stream owners — column-mode streams are unowned (None);
            # the recorder drops events whose owner doesn't match its
            # stream, so alerts never land in a different workload's
            # trace
            _trace_safe(
                self.trace.record_events, alerts,
                session_id=(
                    None if session_id.startswith("unary:") else session_id
                ),
                tick=trace_tick,
            )
        return alerts

    # ---------------- v1 unary (frozen contract) ----------------

    def Assign(self, request: pb.AssignRequest, context) -> pb.AssignResponse:
        mark = _tracer.mark()
        with self._rpc_span("rpc.Assign", context, wire="v1") as root:
            return self._assign_v1(request, context, mark, root)

    def _admit_unary(self, context) -> None:
        """Admission gate for the stateless rungs. Without this, a
        tenant refused on the session protocol would fall to unary and
        run UNTHROTTLED — the fallback ladder would bypass admission.
        Unary carries no session id, so all unary traffic shares one
        "unary" bucket (coarse by design; rate=None, the default, is a
        no-op). Refusal is a gRPC RESOURCE_EXHAUSTED status — an
        explicit throttle the caller sees, never a silent drop."""
        if not self.admission.admit("unary"):
            self.seam.count("admission_refused")
            context.abort(
                grpc.StatusCode.RESOURCE_EXHAUSTED,
                "unary admission rate exceeded",
            )

    def _check_deadline(self, context, where: str) -> None:
        """Honor the caller's gRPC deadline/cancellation BEFORE a solve
        is dispatched: a client that hung up (or whose deadline is
        already burned) must not keep consuming engine threads — its
        answer is undeliverable either way. Tolerates bare/fake
        contexts (tests drive servicer methods directly)."""
        if context is None:
            return
        is_active = getattr(context, "is_active", None)
        if callable(is_active) and not context.is_active():
            self.seam.count("deadline_refused")
            context.abort(
                grpc.StatusCode.CANCELLED,
                f"client cancelled before the {where} solve",
            )
        time_remaining = getattr(context, "time_remaining", None)
        if callable(time_remaining):
            remaining = context.time_remaining()
            if remaining is not None and remaining <= 0:
                self.seam.count("deadline_refused")
                context.abort(
                    grpc.StatusCode.DEADLINE_EXCEEDED,
                    f"deadline burned before the {where} solve",
                )

    def _assign_v1(
        self, request: pb.AssignRequest, context, mark: int, root
    ) -> pb.AssignResponse:
        self._admit_unary(context)
        t0 = time.perf_counter()
        with _tracer.span("wire.decode", wire="v1"):
            ep = providers_from_proto(request.providers)
            er = requirements_from_proto(request.requirements)
        t_dec = time.perf_counter()
        warm = seeds = None
        if len(request.warm_price) or len(request.seed_provider_for_task):
            warm = _np(request.warm_price, np.float32)
            seeds = _np(request.seed_provider_for_task, np.int32)
        kernel = request.kernel or "auction"
        self._check_deadline(context, "v1 unary")
        with _tracer.span("engine.solve", kernel=kernel):
            out = self._solve(
                ep, er, self._weights_of(request), kernel,
                int(request.top_k), request.eps, int(request.max_iters),
                warm, seeds, context,
            )
        t_solve = time.perf_counter()
        self.seam.observe_ms("decode", (t_dec - t0) * 1e3)
        self.seam.observe_ms("solve", (t_solve - t_dec) * 1e3)
        self.seam.add_bytes("in", request.ByteSize())
        with _tracer.span("wire.encode", wire="v1"):
            resp = pb.AssignResponse(
                provider_for_task=out.p4t.astype(np.int32),
                task_for_provider=out.t4p.astype(np.int32),
                num_assigned=out.num_assigned,
                solve_ms=(time.perf_counter() - t0) * 1e3,
            )
            if out.price is not None:
                resp.price.extend(out.price)
        self.seam.add_bytes("out", resp.ByteSize())
        arena_stats = out.arena_stats
        self._observe_tick(
            "unary:v1", t0, out.p4t.shape[0], out.num_assigned, arena_stats
        )
        if self.trace is not None:
            from protocol_tpu.trace.recorder import safe as _trace_safe

            _trace_safe(
                self.trace.record_solve, ep, er, self._weights_of(request),
                kernel, int(request.top_k),
                request.eps, int(request.max_iters), out.p4t, out.price,
                metrics=self._enrich_metrics({
                    "decode_ms": round((t_dec - t0) * 1e3, 3),
                    "solve_ms": round((t_solve - t_dec) * 1e3, 3),
                    "bytes_in": request.ByteSize(),
                    "bytes_out": resp.ByteSize(),
                    "wire": "v1",
                }, arena_stats, mark, root),
            )
        return resp

    # ---------------- v2 unary: tensor frames ----------------

    def AssignV2(
        self, request: pb.AssignRequestV2, context
    ) -> pb.AssignResponseV2:
        mark = _tracer.mark()
        with self._rpc_span("rpc.AssignV2", context, wire="v2") as root:
            return self._assign_v2(request, context, mark, root)

    def _assign_v2(
        self, request: pb.AssignRequestV2, context, mark: int, root
    ) -> pb.AssignResponseV2:
        self._admit_unary(context)
        t0 = time.perf_counter()
        try:
            with _tracer.span("wire.decode", wire="v2"):
                ep = decode_providers_v2(request.providers)
                er = decode_requirements_v2(request.requirements)
                warm = (
                    unblob(request.warm_price, np.float32)
                    if request.HasField("warm_price") else None
                )
                seeds = (
                    unblob(request.seed_provider_for_task, np.int32)
                    if request.HasField("seed_provider_for_task") else None
                )
        except ValueError as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        t_dec = time.perf_counter()
        kernel = request.kernel or "auction"
        self._check_deadline(context, "v2 unary")
        with _tracer.span("engine.solve", kernel=kernel):
            out = self._solve(
                ep, er, self._weights_of(request), kernel,
                int(request.top_k), request.eps, int(request.max_iters),
                warm, seeds, context,
            )
        t_solve = time.perf_counter()
        self.seam.observe_ms("decode", (t_dec - t0) * 1e3)
        self.seam.observe_ms("solve", (t_solve - t_dec) * 1e3)
        self.seam.add_bytes("in", request.ByteSize())
        with _tracer.span("wire.encode", wire="v2"):
            resp = self._result_v2(out, t0, t_dec - t0)
        self.seam.add_bytes("out", resp.ByteSize())
        arena_stats = out.arena_stats
        self._observe_tick(
            "unary:v2", t0, out.p4t.shape[0], out.num_assigned, arena_stats
        )
        if self.trace is not None:
            from protocol_tpu.trace.recorder import safe as _trace_safe

            _trace_safe(
                self.trace.record_solve, ep, er, self._weights_of(request),
                kernel, int(request.top_k),
                request.eps, int(request.max_iters), out.p4t, out.price,
                metrics=self._enrich_metrics({
                    "decode_ms": round((t_dec - t0) * 1e3, 3),
                    "solve_ms": round((t_solve - t_dec) * 1e3, 3),
                    "bytes_in": request.ByteSize(),
                    "bytes_out": resp.ByteSize(),
                    "wire": "v2",
                }, arena_stats, mark, root),
            )
        return resp

    @staticmethod
    def _result_v2(
        out: _SolveOut, t0: float, decode_s: float
    ) -> pb.AssignResponseV2:
        resp = pb.AssignResponseV2(
            provider_for_task=blob(out.p4t, np.int32),
            task_for_provider=blob(out.t4p, np.int32),
            num_assigned=out.num_assigned,
            solve_ms=(time.perf_counter() - t0) * 1e3,
            decode_ms=decode_s * 1e3,
        )
        if out.price is not None:
            resp.price.CopyFrom(blob(out.price, np.float32))
        return resp

    # ---------------- v2 sessions: streamed snapshot + deltas ----------

    def OpenSession(self, request_iterator, context) -> pb.OpenSessionResponse:
        mark = _tracer.mark()
        with self._rpc_span("rpc.OpenSession", context) as root:
            return self._open_session(request_iterator, context, mark, root)

    def _open_session(
        self, request_iterator, context, mark: int, root
    ) -> pb.OpenSessionResponse:
        t0 = time.perf_counter()
        try:
            with _tracer.span("wire.decode", wire="v2-session"):
                session_id, claimed_fp, req, wire_bytes = assemble_snapshot(
                    request_iterator
                )
        except ValueError as e:
            return pb.OpenSessionResponse(ok=False, error=str(e))
        self.seam.add_bytes("in", wire_bytes)
        if self.draining:
            # SIGTERM drain: stop ADMITTING — in-flight sessions keep
            # ticking until the server stops. A transient refusal on
            # the protocol surface, not a capability one: the client
            # ladder degrades this tick to unary and keeps the session
            # protocol available for the replacement server.
            self.seam.count("drain_refused")
            return pb.OpenSessionResponse(
                ok=False,
                error="UNAVAILABLE: draining, not admitting new "
                      "sessions (retry against the replacement)",
            )
        if session_id:
            fenced = self._fence_route(session_id)
            if fenced is not None:
                # this process was EJECTED (fence superseded): it must
                # not admit sessions against a namespace it no longer
                # owns — even a zombie that resumed serving
                if fenced:
                    return pb.OpenSessionResponse(
                        ok=False, error=f"moved:{fenced}"
                    )
                return pb.OpenSessionResponse(
                    ok=False,
                    error="unknown session (journal fence superseded)",
                )
            moved = self._moved_to(session_id)
            if moved is not None:
                # dfleet: this session was live-migrated away — even a
                # re-open belongs at its new home (opening it HERE would
                # fork ownership: two processes each believing they hold
                # the authoritative arena)
                self.seam.count("moved_refused")
                return pb.OpenSessionResponse(
                    ok=False, error=f"moved:{moved}"
                )
        # tenant admission BEFORE the expensive decode + cold solve: an
        # over-rate tenant costs the server one token-bucket check, not
        # a snapshot decode. The refusal is a protocol answer on the
        # existing surface — the client's ladder falls to unary v2.
        tenant = tenant_of(session_id) if session_id else "unknown"
        if not self.admission.admit(tenant):
            self.seam.count("admission_refused")
            return pb.OpenSessionResponse(
                ok=False,
                error=f"RESOURCE_EXHAUSTED: tenant {tenant!r} over "
                      "admission rate (OpenSession)",
            )
        kernel = req.kernel or "native-mt"
        parsed = parse_session_kernel(kernel)
        if parsed is None:
            # the session protocol's warm state lives in the native arena;
            # other kernels stay on the stateless unary rungs
            return pb.OpenSessionResponse(
                ok=False,
                error=f"kernel {kernel!r} is not session-servable "
                      "(want native-mt[:N] | sinkhorn-mt[:N] | jax[:D])",
            )
        engine, threads = parsed
        try:
            ep = decode_providers_v2(req.providers)
            er = decode_requirements_v2(req.requirements)
        except ValueError as e:
            return pb.OpenSessionResponse(ok=False, error=str(e))
        weights = self._weights_of(req)
        top_k = max(int(req.top_k) or 64, 1)
        p_cols = canon_columns(ep, P_WIRE_DTYPES)
        r_cols = canon_columns(er, R_WIRE_DTYPES)
        fp = epoch_fingerprint(
            p_cols, r_cols, weights, kernel, top_k, req.eps,
            int(req.max_iters),
        )
        if claimed_fp and claimed_fp != fp:
            self.seam.count("fingerprint_mismatch")
            return pb.OpenSessionResponse(
                ok=False,
                error="epoch fingerprint mismatch between client and "
                      "server codecs",
            )
        n_p = p_cols["gpu_count"].shape[0]
        n_t = r_cols["cpu_cores"].shape[0]
        from protocol_tpu.fleet import estimate_arena_bytes

        padded_p = _pad_cols(p_cols, n_p)
        padded_r = _pad_cols(r_cols, n_t)
        session = SolveSession(
            session_id=session_id or uuid.uuid4().hex,
            fingerprint=fp,
            weights=weights,
            kernel=kernel,
            threads=threads,
            top_k=top_k,
            p_cols=padded_p,
            r_cols=padded_r,
            n_providers=n_p,
            n_tasks=n_t,
            arena=make_solve_arena(engine, k=top_k, threads=threads),
            budget=self._engine_budget,
            # fleet arena budget: rows x dtype widths, estimated once
            arena_bytes=estimate_arena_bytes(padded_p, padded_r, top_k),
        )
        t_dec = time.perf_counter()
        self._check_deadline(context, "session-open")
        with _tracer.span("engine.solve", kernel=kernel, cold=True):
            with session.lock:
                p4t, t4p, price = session.solve()
                arena_stats = dict(session.arena.last_stats)
                # idempotence cache + warm checkpoint for tick 0: a
                # crash before the first delta must restore the session
                # (flush-before-ack, same as every delta tick)
                session.last_p4t = np.asarray(p4t, np.int32)
                if req.stream_mode:
                    # streaming session: bind the online engine to the
                    # just-primed arena — event-typed deltas route
                    # through per-event localized repair from here on
                    from protocol_tpu.stream.engine import StreamEngine

                    session.stream = StreamEngine(
                        session.arena, weights,
                        reconcile_every=(
                            int(req.reconcile_every) or 256
                        ),
                        gap_ceiling=_stream_gap_ceiling(),
                    )
                if self.ckpt is not None:
                    self._flush_locked(session)
        # post-flush fence re-check (same freeze-window argument as the
        # delta path): an open that raced an ejection must not be acked
        # — the client re-opens at the new home instead of holding a
        # session whose journal can never exist here
        fenced = self._fence_route(session_id) if session_id else None
        if fenced is not None:
            if fenced:
                return pb.OpenSessionResponse(
                    ok=False, error=f"moved:{fenced}"
                )
            return pb.OpenSessionResponse(
                ok=False,
                error="unknown session (journal fence superseded)",
            )
        t_solve = time.perf_counter()
        self.sessions.put(session)
        self._router_adopt(session.session_id)
        self.seam.count("session_open")
        self.seam.observe_ms("decode", (t_dec - t0) * 1e3)
        self.seam.observe_ms("solve", (t_solve - t_dec) * 1e3)
        self._observe_tick(
            session.session_id, t0, session.n_tasks,
            int((p4t >= 0).sum()), arena_stats, trace_tick=0,
        )
        if self.trace is not None:
            # flight recorder, session mode: the snapshot frame is the
            # session's own wire message, deltas land from apply_delta
            # (one session claims the stream; later sessions are not
            # recorded — one trace, one session)
            try:
                if self.trace.record_session_open(
                    session.session_id, fp, req
                ):
                    session.trace = self.trace
                    self.trace.record_outcome(
                        0, p4t, price,
                        metrics=self._enrich_metrics({
                            "decode_ms": round((t_dec - t0) * 1e3, 3),
                            "solve_ms": round((t_solve - t_dec) * 1e3, 3),
                            "bytes_in": wire_bytes,
                            "wire": "v2-session",
                        }, arena_stats, mark, root),
                        session_id=session.session_id,
                    )
            except Exception:  # pragma: no cover - capture must not fail RPCs
                import logging

                logging.getLogger(__name__).warning(
                    "trace capture failed at OpenSession", exc_info=True
                )
        out = _SolveOut(p4t, t4p, int((p4t >= 0).sum()), price)
        resp = pb.OpenSessionResponse(
            ok=True,
            session_id=session.session_id,
            epoch_fingerprint=fp,
            result=self._result_v2(out, t0, t_dec - t0),
        )
        self.seam.add_bytes("out", resp.ByteSize())
        return resp

    def AssignDelta(
        self, request: pb.AssignDeltaRequest, context
    ) -> pb.AssignDeltaResponse:
        mark = _tracer.mark()
        with self._rpc_span(
            "rpc.AssignDelta", context,
            session=request.session_id,
            tick=int(request.tick),  # lint: unlocked-ok (wire message field, not session state)
        ) as root:
            return self._assign_delta(request, context, mark, root)

    def _assign_delta(
        self, request: pb.AssignDeltaRequest, context, mark: int, root
    ) -> pb.AssignDeltaResponse:
        t0 = time.perf_counter()
        # fence first (one stat call): an EJECTED process must refuse
        # every delta outright — before it consumes a tenant's
        # admission tokens or a store lookup — because its journal
        # namespace (and therefore the authority to ack) moved on
        fenced = self._fence_route(request.session_id)
        if fenced is not None:
            if fenced:
                return pb.AssignDeltaResponse(
                    session_ok=False, error=f"moved:{fenced}"
                )
            return pb.AssignDeltaResponse(
                session_ok=False,
                error="unknown session (journal fence superseded)",
            )
        # tenant admission next (cheapest stateful check): an over-rate
        # tenant is refused before it costs a store lookup or a decode
        if not self.admission.admit(tenant_of(request.session_id)):
            self.seam.count("admission_refused")
            return pb.AssignDeltaResponse(
                session_ok=False,
                error="RESOURCE_EXHAUSTED: tenant over admission rate "
                      "(AssignDelta)",
            )
        session, reason = self.sessions.get(
            request.session_id, request.epoch_fingerprint
        )
        if session is None and reason == "unknown session":
            # dfleet: a migrated-away session answers with its new home
            # (the client rebinds and resends the SAME delta — warm);
            # a session whose journal was handed TO us rehydrates here
            # lazily and the delta proceeds as if it never moved
            moved = self._moved_to(request.session_id)
            if moved is not None:
                self.seam.count("moved_refused")
                return pb.AssignDeltaResponse(
                    session_ok=False, error=f"moved:{moved}"
                )
            session = self._rehydrate(
                request.session_id, request.epoch_fingerprint
            )
        if session is None:
            self.seam.count("session_miss")
            return pb.AssignDeltaResponse(session_ok=False, error=reason)
        # delta-stream backpressure: the queued-tick depth bound must be
        # checked BEFORE parking on the session lock — over-depth means
        # this session is already stacked with waiting ticks, and
        # admitting one more would just grow the invisible lock queue
        if not session.enter_tick(self.fleet_config.delta_queue_depth):
            self.seam.count("backpressure_refused")
            return pb.AssignDeltaResponse(
                session_ok=False,
                error="RESOURCE_EXHAUSTED: session delta queue over "
                      f"depth {self.fleet_config.delta_queue_depth}",
            )
        try:
            return self._assign_delta_admitted(
                request, context, mark, root, t0, session
            )
        finally:
            session.exit_tick()

    def _assign_delta_admitted(
        self,
        request: pb.AssignDeltaRequest,
        context,
        mark: int,
        root,
        t0: float,
        session: SolveSession,
    ) -> pb.AssignDeltaResponse:
        self.seam.count("session_hit")
        self.seam.add_bytes("in", request.ByteSize())
        try:
            with _tracer.span("wire.decode", wire="v2-session"):
                prow = (
                    unblob(request.provider_rows, np.int32)
                    if request.HasField("provider_rows")
                    else np.zeros(0, np.int32)
                )
                trow = (
                    unblob(request.task_rows, np.int32)
                    if request.HasField("task_rows")
                    else np.zeros(0, np.int32)
                )
                p_delta = (
                    canon_columns(
                        decode_providers_v2(request.providers),
                        P_WIRE_DTYPES,
                    )
                    if prow.size else {}
                )
                r_delta = (
                    canon_columns(
                        decode_requirements_v2(request.requirements),
                        R_WIRE_DTYPES,
                    )
                    if trow.size else {}
                )
        except ValueError as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        # decode ends HERE: with sharded session locks and a shared thread
        # budget, a delta can legitimately park on the lock — stamping
        # decode after it would misattribute contention to the codec and
        # point seam tuning at the wrong phase (lock/budget wait + delta
        # apply land in "solve" instead, where the contention actually is)
        t_dec = time.perf_counter()
        # "engine.solve" is everything between decode and the reply's
        # bookkeeping: the wait for the session lock, apply_delta, the
        # arena's solve, the flight recorder's outcome frame, the
        # checkpoint flush and the fence re-check. Its children name
        # the parts; its SELF time is the bookkeeping nobody named.
        with _tracer.span(
            "engine.solve", kernel=session.kernel,
            delta_rows=int(prow.size + trow.size),
        ), session.lock:
            self.seam.observe_ms(
                "lock_wait", (time.perf_counter() - t_dec) * 1e3
            )
            if session.evicted:
                # lost the race with LRU/TTL eviction (or a same-id
                # re-open) between the store lookup and this lock: refuse
                # rather than solve against — and advance the tick of — an
                # arena the store no longer owns. The client re-opens from
                # its authoritative state (the standard fallback ladder).
                self.seam.count("session_evicted_inflight")
                return pb.AssignDeltaResponse(
                    session_ok=False, error="session evicted"
                )
            if (
                int(request.tick) == session.tick
                and session.tick > 0
                and session.last_p4t is not None
            ):
                # idempotent retransmit: the client re-sent a tick this
                # session already applied — its response died on the
                # wire, or the servicer crashed after the
                # flush-before-ack checkpoint and the client retried
                # against the restart. The CRC proves it is the SAME
                # delta (byte-identical retransmit); the cached answer
                # replays and the tick is applied exactly once. A
                # same-tick request with DIFFERENT bytes is genuine
                # divergence and refuses below.
                if _delta_crc(request) == session.last_delta_crc:
                    self.seam.count("delta_replayed")
                    cached = np.asarray(session.last_p4t, np.int32)
                    return pb.AssignDeltaResponse(
                        session_ok=True,
                        replayed=True,
                        result=pb.AssignResponseV2(
                            provider_for_task=blob(cached, np.int32),
                            num_assigned=int((cached >= 0).sum()),
                            solve_ms=(time.perf_counter() - t0) * 1e3,
                        ),
                    )
            if int(request.tick) != session.tick + 1:
                # replayed or skipped tick: the client's shadow copy and
                # this session's columns have diverged — refuse, never
                # guess (the client re-opens from authoritative state)
                self.seam.count("tick_mismatch")
                return pb.AssignDeltaResponse(
                    session_ok=False,
                    error=f"tick cursor mismatch (have {session.tick}, "
                          f"got {int(request.tick)})",
                )
            # honor the caller's gRPC deadline/cancellation BEFORE the
            # delta is applied: an abort after apply_delta (but before
            # the tick cursor + dedup CRC advance) would let the
            # client's retry DOUBLE-APPLY this tick — the exact bug the
            # retransmit protocol exists to refuse
            self._check_deadline(context, "delta")
            is_event = bool(request.event_source)
            ev_deduped = ev_reconciled = False
            ev_gap = 0.0
            ev_window = 0
            if is_event and session.stream is None:
                # event-typed deltas need the stream engine the session
                # opted into at open; a batch session refuses with a
                # ladder-recognizable capability marker (the client
                # re-opens with stream_mode or stays on batch ticks)
                self.seam.count("stream_refused")
                return pb.AssignDeltaResponse(
                    session_ok=False,
                    error="not stream-servable (session opened without "
                          "stream_mode)",
                )
            if is_event and session.stream.stale_event(
                request.event_source, int(request.event_seq)
            ):
                # idempotence under chaos: a duplicated or reordered
                # (superseded) event is ACKED without applying — the
                # columns, arena, and plan are exactly as if it never
                # arrived, and the per-source high-water mark makes a
                # double-apply impossible by construction. The tick
                # cursor still advances (the wire stream consumed a
                # tick), which is what keeps the client's lockstep
                # cursor and the dedup CRC consistent.
                ev_deduped = True
                staleness = 0
                p4t_out = np.array(session.last_p4t, np.int32)
                ev_window = session.stream.events_since_reconcile
                # the served plan's HONEST certificate: the engine's
                # last computed bound, never a false 0.0 "optimal"
                ev_gap = float(session.stream.gap_last)
                price = None
                arena_stats = {
                    "cold": False, "event": True, "deduped": True,
                    "assigned": int((p4t_out >= 0).sum()),
                }
            else:
                t_apply = time.perf_counter()
                try:
                    with _tracer.span("session.apply_delta"):
                        session.apply_delta(
                            prow, p_delta, trow, r_delta,
                            events=(
                                [{
                                    "kind": request.event_kind or "event",
                                    "source": request.event_source,
                                    "seq": int(request.event_seq),
                                }]
                                if is_event else None
                            ),
                        )
                except ValueError as e:
                    context.abort(
                        grpc.StatusCode.INVALID_ARGUMENT, str(e)
                    )
                self.seam.observe_ms(
                    "apply", (time.perf_counter() - t_apply) * 1e3
                )
                if self.ckpt is not None:
                    # the columns are final for this tick: the arena
                    # may start the checkpoint's solve-independent part
                    # while the device solves
                    self.ckpt.arm_locked(session)
            if is_event and not ev_deduped:
                from protocol_tpu.stream.events import StreamEvent

                res = session.stream.apply(StreamEvent(
                    kind=request.event_kind or "event",
                    source=request.event_source,
                    seq=int(request.event_seq),
                    provider_rows=prow,
                    p_cols=p_delta,
                    task_rows=trow,
                    r_cols=r_delta,
                ))
                staleness = 0
                p4t_out = np.asarray(res.plan, np.int32)[
                    : session.n_tasks
                ]
                price = None
                ev_reconciled = res.reconciled
                ev_gap = float(res.gap_per_task)
                ev_window = res.events_since_reconcile
                arena_stats = dict(session.arena.last_stats)
                arena_stats["stream_divergence_rows"] = (
                    res.divergence_rows
                )
                arena_stats["stream_repair_rows"] = res.repair_rows
                arena_stats["gap_per_task"] = ev_gap
                if res.stale:
                    # starved reconcile past the bound: flagged +
                    # counted, same contract as the tick watchdog
                    arena_stats["stale"] = True
                    arena_stats["stale_streak"] = (
                        res.events_since_reconcile
                        - session.stream.max_stale_events
                    )
            if not is_event:
                # ---- graceful degradation: the per-tick solve watchdog.
                # When the tick's deadline budget is already burned (lock
                # wait + decode + the EWMA of recent solve walls would
                # overrun it), serve the PREVIOUS plan with an explicit
                # stale flag instead of starting a solve whose answer will
                # arrive too late to act on. The delta was still APPLIED —
                # columns stay client-consistent — and the streak is
                # hard-bounded by ``max_stale_ticks``: past it the solve
                # runs regardless, so staleness is a contract, never an
                # escape hatch. (The native solve is uninterruptible C++;
                # the watchdog is predictive, which is the only honest kind
                # here.)
                deadline_ms = self.fleet_config.tick_deadline_ms
                stale = (
                    deadline_ms is not None
                    and session.last_p4t is not None
                    and session.stale_streak
                    < self.fleet_config.max_stale_ticks
                    and (time.perf_counter() - t0) * 1e3
                    + session.solve_ewma_ms > deadline_ms
                )
                if stale:
                    session.stale_streak += 1
                    staleness = session.stale_streak
                    p4t_out = np.array(session.last_p4t, np.int32)
                    price = None
                    arena_stats = {
                        "cold": False,  # served from carried state: a
                        # stale tick must not read as a cold solve in obs
                        "stale": True, "stale_streak": staleness,
                        "assigned": int((p4t_out >= 0).sum()),
                    }
                    self.seam.count("stale_served")
                else:
                    # (the deadline was already honored before apply_delta;
                    # re-checking here would abort AFTER state moved)
                    staleness = 0
                    t_s0 = time.perf_counter()
                    p4t_out, t4p, price = session.solve()
                    solve_ms = (time.perf_counter() - t_s0) * 1e3
                    # EWMA of solve walls feeds the watchdog's prediction
                    session.solve_ewma_ms = (
                        solve_ms if session.solve_ewma_ms == 0.0
                        else 0.5 * session.solve_ewma_ms + 0.5 * solve_ms
                    )
                    session.stale_streak = 0
                    arena_stats = dict(session.arena.last_stats)
                    del t4p  # derivable client-side; stays server-side
            session.tick += 1
            tick_no = session.tick  # this delta's wire tick, for the
            # post-lock obs/event hooks (== int(request.tick), checked
            # above)
            # idempotence cache: what a retransmit of THIS tick replays
            session.last_p4t = p4t_out
            session.last_delta_crc = _delta_crc(request)
            if session.evicted:
                # eviction landed DURING the solve (the store flags
                # without taking session.lock — coupling store eviction
                # to a potentially long solve would be worse): the solve
                # ran against a disowned arena, so do not ack it. The
                # pre-lock check above catches the common race; this one
                # closes the in-solve window.
                self.seam.count("session_evicted_inflight")
                return pb.AssignDeltaResponse(
                    session_ok=False, error="session evicted"
                )
            if session.trace is not None:
                from protocol_tpu.trace.recorder import safe as _trace_safe

                # outcome for the tick whose delta apply_delta recorded;
                # inside the lock so tick/outcome numbering can't race a
                # concurrent delta on the same session
                _trace_safe(
                    session.trace.record_outcome, session.tick, p4t_out,
                    price,
                    metrics=self._enrich_metrics({
                        "decode_ms": round((t_dec - t0) * 1e3, 3),
                        "solve_ms": round(
                            (time.perf_counter() - t_dec) * 1e3, 3
                        ),
                        "bytes_in": request.ByteSize(),
                        "delta_rows": int(prow.size + trow.size),
                        "wire": "v2-session",
                        **(
                            {"stale": True, "staleness_ticks": staleness}
                            if staleness else {}
                        ),
                    }, arena_stats, mark, root),
                    session_id=session.session_id,
                )
            if self.ckpt is not None and self.ckpt.due(session.tick):
                # flush-before-ack: the checkpoint lands on disk BEFORE
                # the client sees this tick acknowledged, so a crash at
                # any instant leaves the cursor at-or-one-behind the
                # client's — either the restart resumes at the next
                # tick, or the client's retransmit hits the dedup path.
                self._flush_locked(session)
            # fence re-check AFTER the flush attempt, immediately
            # before the ack: a SIGSTOP can freeze this thread at ANY
            # instruction and the ejection (fence bump + journal
            # re-route) happen while it was frozen. Checking here —
            # after the flush, which itself refuses on a superseded
            # fence — closes every freeze window: whatever instant the
            # freeze hit, either the flushed journal traveled with the
            # re-route (the resend dedups as the replayed twin) or the
            # flush was fence-refused and this ack is WITHHELD (the
            # client resends at the new home, which holds the pre-tick
            # journal — applied exactly once, split-brain refused).
            fenced = self._fence_route(request.session_id)
            if fenced is not None:
                if fenced:
                    return pb.AssignDeltaResponse(
                        session_ok=False, error=f"moved:{fenced}"
                    )
                return pb.AssignDeltaResponse(
                    session_ok=False,
                    error="unknown session (journal fence superseded)",
                )
        self.seam.observe_ms("decode", (t_dec - t0) * 1e3)
        self.seam.observe_ms(
            "solve", (time.perf_counter() - t_dec) * 1e3
        )
        with _tracer.span("obs.observe_tick"):
            self._observe_tick(
                session.session_id, t0, session.n_tasks,
                int((p4t_out >= 0).sum()), arena_stats,
                delta_rows=int(prow.size + trow.size),
                trace_tick=tick_no,
            )
        if is_event and obs_pkg.enabled():
            # per-event stream metrics ride NEXT TO the tick roll-up:
            # event latency (µs-scale HDR), dedup/reconcile counters,
            # divergence + repair scope
            self.obs.observe_event(
                session.session_id,
                (time.perf_counter() - t0) * 1e3,
                deduped=ev_deduped,
                reconciled=ev_reconciled,
                divergence_rows=int(
                    arena_stats.get("stream_divergence_rows", 0)
                ),
                repair_rows=int(
                    arena_stats.get("stream_repair_rows", 0)
                ),
            )
        del price  # session state: stays server-side
        # SLIM response: p4t only. task_for_provider is derivable from it
        # (the client scatters), and prices/retirement are session state —
        # shipping them back every tick would spend O(P) wire bytes on
        # data the delta protocol exists to keep off the wire
        with _tracer.span("wire.encode", wire="v2-session"):
            resp = pb.AssignDeltaResponse(
                session_ok=True,
                stale=bool(staleness),
                staleness_ticks=staleness,
                result=pb.AssignResponseV2(
                    provider_for_task=blob(p4t_out, np.int32),
                    num_assigned=int((p4t_out >= 0).sum()),
                    solve_ms=(time.perf_counter() - t0) * 1e3,
                    decode_ms=(t_dec - t0) * 1e3,
                ),
            )
        if is_event:
            resp.event_deduped = ev_deduped
            resp.reconciled = ev_reconciled
            resp.gap_per_task = ev_gap
            resp.events_since_reconcile = ev_window
        self.seam.add_bytes("out", resp.ByteSize())
        return resp

    def finish_drain(self) -> int:
        """The drain tail: flush every live session's checkpoint and the
        trace recorder's tail frames. Called AFTER the server stopped
        accepting RPCs (in-flight ticks have finished), so each session
        lock is uncontended. Returns the number of sessions flushed."""
        flushed = 0
        if self.ckpt is not None:
            for session in self.sessions.snapshot_sessions():
                with session.lock:
                    if not session.evicted and self._flush_locked(
                        session
                    ):
                        flushed += 1
        if self.trace is not None:
            self.trace.close()
        return flushed

    def Health(self, request: pb.HealthRequest, context) -> pb.HealthResponse:
        from protocol_tpu.utils.platform import device_summary

        # deterministic fleet sweep: health probes are the periodic
        # traffic every deployment already has, so idle expired sessions
        # release their arena bytes here instead of waiting for the next
        # data-path touch (the fabric also sweeps under budget pressure)
        self.sessions.sweep()
        resp = pb.HealthResponse(status="ok", **device_summary())
        seam = dict(self.seam.snapshot())
        seam["sessions_active"] = float(len(self.sessions))
        seam["session_evictions"] = float(self.sessions.evictions)
        seam["session_expirations"] = float(self.sessions.expirations)
        seam["draining"] = 1.0 if self.draining else 0.0
        if self.ckpt is not None:
            seam["ckpt_flushes"] = float(self.ckpt.flushes)
            seam["ckpt_flush_failures"] = float(
                self.ckpt.flush_failures
            )
            seam["ckpt_handoffs"] = float(self.ckpt.handoffs)
            seam["ckpt_fence_epoch"] = float(self.ckpt.fence_epoch)
            seam["ckpt_fence_refusals"] = float(
                self.ckpt.fence_refusals
            )
            seam["ckpt_journals_skipped"] = float(
                self.ckpt.journals_skipped
            )
            # the DEFLATE chunks of every journal flushed
            seam["ckpt_chunks_sum"] = float(self.ckpt.chunks)
        with self._router_lock:
            seam["sessions_moved_out"] = float(len(self._moved))
        for name in sorted(seam):
            resp.seam_metrics.add(name=name, value=seam[name])
        return resp


def _handlers(servicer: SchedulerBackendServicer) -> grpc.GenericRpcHandler:
    return grpc.method_handlers_generic_handler(
        SERVICE_NAME,
        {
            "Assign": grpc.unary_unary_rpc_method_handler(
                servicer.Assign,
                request_deserializer=pb.AssignRequest.FromString,
                response_serializer=pb.AssignResponse.SerializeToString,
            ),
            "AssignV2": grpc.unary_unary_rpc_method_handler(
                servicer.AssignV2,
                request_deserializer=pb.AssignRequestV2.FromString,
                response_serializer=pb.AssignResponseV2.SerializeToString,
            ),
            "OpenSession": grpc.stream_unary_rpc_method_handler(
                servicer.OpenSession,
                request_deserializer=pb.SnapshotChunk.FromString,
                response_serializer=pb.OpenSessionResponse.SerializeToString,
            ),
            "AssignDelta": grpc.unary_unary_rpc_method_handler(
                servicer.AssignDelta,
                request_deserializer=pb.AssignDeltaRequest.FromString,
                response_serializer=pb.AssignDeltaResponse.SerializeToString,
            ),
            "Health": grpc.unary_unary_rpc_method_handler(
                servicer.Health,
                request_deserializer=pb.HealthRequest.FromString,
                response_serializer=pb.HealthResponse.SerializeToString,
            ),
            "Migrate": grpc.unary_unary_rpc_method_handler(
                servicer.Migrate,
                request_deserializer=pb.MigrateRequest.FromString,
                response_serializer=pb.MigrateResponse.SerializeToString,
            ),
        },
    )


# Columnar batches scale with the population: ~60 B/provider means the
# 4 MB gRPC default tops out near 70k providers. 1 GiB covers the 1M-scale
# ladder with headroom for the v1 unary path; it is a cap, not an
# allocation. (v2 streams snapshots in bounded chunks, so only v1 and the
# per-tick delta messages ever approach it.)
MAX_MESSAGE_BYTES = 1 << 30
_CHANNEL_OPTIONS = [
    ("grpc.max_send_message_length", MAX_MESSAGE_BYTES),
    ("grpc.max_receive_message_length", MAX_MESSAGE_BYTES),
]


def drain(server: grpc.Server, grace_s: float = 5.0) -> int:
    """Graceful drain (the SIGTERM path): stop admitting OpenSession,
    stop taking new RPCs and let in-flight ticks finish (``grace_s``),
    then flush every session checkpoint and the trace tail. Returns the
    number of sessions flushed; after this the process can exit 0 and a
    restarted servicer rehydrates every session warm."""
    servicer = server.servicer
    servicer.draining = True
    server.stop(grace=grace_s).wait()
    if server.metrics is not None:
        server.metrics.stop()
    return servicer.finish_drain()


def serve(
    address: str = "127.0.0.1:50061",
    max_workers: int = 4,
    metrics_port: Optional[int] = None,
    max_sessions: int = 8,
    session_ttl_s: float = 900.0,
    fleet=None,
    slo=None,
    chaos=None,
) -> grpc.Server:
    """Start the backend server (non-blocking; call .wait_for_termination()).
    The servicer rides on the returned server as ``.servicer`` (tests and
    diagnostics reach the session store / seam metrics through it).

    ``fleet`` is a :class:`~protocol_tpu.fleet.FleetConfig` (shard
    count, arena byte budgets, admission rate, delta queue depth);
    None reads ``PROTOCOL_TPU_FLEET_*`` from the environment, and the
    defaults are transparent for single-session use.

    ``slo`` is an :class:`~protocol_tpu.obs.slo.SLOConfig` (per-tenant
    quality/latency objectives with multi-window burn-rate alerting);
    None reads ``PROTOCOL_TPU_SLO_*`` — all unset leaves the engine
    inert.

    ``metrics_port`` starts the consolidated observability scrape
    endpoint (``/metrics`` prometheus text merging SeamMetrics + the
    per-session obs registry + store/budget gauges; ``/metrics.json``
    the authoritative snapshot) on that port (0 = ephemeral; the bound
    endpoint rides on the server as ``.metrics`` with its ``.port``).
    ``PROTOCOL_TPU_METRICS_PORT`` enables it from the environment. None
    and no env var: no HTTP listener (the Health RPC still serves the
    seam snapshot).

    ``chaos`` arms the server-side fault interceptor (drop/delay before
    the servicer) — a :class:`~protocol_tpu.faults.plan.ChaosConfig` or
    ``FaultSchedule``; None reads ``PROTOCOL_TPU_CHAOS`` from the
    environment (unset = no interceptor, zero overhead)."""
    interceptors: tuple = ()
    if chaos is None:
        from protocol_tpu.faults.plan import ChaosConfig

        chaos = ChaosConfig.from_env()
    if chaos is not None:
        from protocol_tpu.faults.inject import ChaosServerInterceptor
        from protocol_tpu.faults.plan import ChaosConfig, FaultSchedule

        schedule = (
            chaos if isinstance(chaos, FaultSchedule)
            else FaultSchedule(chaos)
        )
        if schedule.config.active():
            # the interceptor needs this process's identity so the
            # slow-node gray failure (slow_proc=K) can target ONE fleet
            # process while the rest stay fast
            proc_id = (
                fleet.proc_id if fleet is not None
                else os.environ.get("PROTOCOL_TPU_FLEET_PROC_ID", "p0")
            )
            interceptors = (
                ChaosServerInterceptor(schedule, proc_id=proc_id),
            )
    server = grpc.server(
        futures.ThreadPoolExecutor(max_workers=max_workers),
        options=_CHANNEL_OPTIONS,
        interceptors=interceptors,
    )
    servicer = SchedulerBackendServicer(
        max_sessions=max_sessions,
        session_ttl_s=session_ttl_s,
        fleet=fleet,
        slo=slo,
    )
    server.add_generic_rpc_handlers((_handlers(servicer),))
    server.servicer = servicer
    server.add_insecure_port(address)
    if metrics_port is None and os.environ.get("PROTOCOL_TPU_METRICS_PORT"):
        metrics_port = int(os.environ["PROTOCOL_TPU_METRICS_PORT"])
    server.metrics = None
    if metrics_port is not None:
        from protocol_tpu.obs.endpoint import start_for_servicer

        server.metrics = start_for_servicer(servicer, port=metrics_port)
    server.start()
    return server


class SchedulerBackendClient:
    """Thin client stub (what a non-Python control plane would generate)."""

    def __init__(self, address: str = "127.0.0.1:50061"):
        self.address = address
        self.channel = grpc.insecure_channel(address, options=_CHANNEL_OPTIONS)
        self._assign = self.channel.unary_unary(
            f"/{SERVICE_NAME}/Assign",
            request_serializer=pb.AssignRequest.SerializeToString,
            response_deserializer=pb.AssignResponse.FromString,
        )
        self._assign_v2 = self.channel.unary_unary(
            f"/{SERVICE_NAME}/AssignV2",
            request_serializer=pb.AssignRequestV2.SerializeToString,
            response_deserializer=pb.AssignResponseV2.FromString,
        )
        self._open_session = self.channel.stream_unary(
            f"/{SERVICE_NAME}/OpenSession",
            request_serializer=pb.SnapshotChunk.SerializeToString,
            response_deserializer=pb.OpenSessionResponse.FromString,
        )
        self._assign_delta = self.channel.unary_unary(
            f"/{SERVICE_NAME}/AssignDelta",
            request_serializer=pb.AssignDeltaRequest.SerializeToString,
            response_deserializer=pb.AssignDeltaResponse.FromString,
        )
        self._health = self.channel.unary_unary(
            f"/{SERVICE_NAME}/Health",
            request_serializer=pb.HealthRequest.SerializeToString,
            response_deserializer=pb.HealthResponse.FromString,
        )
        self._migrate = self.channel.unary_unary(
            f"/{SERVICE_NAME}/Migrate",
            request_serializer=pb.MigrateRequest.SerializeToString,
            response_deserializer=pb.MigrateResponse.FromString,
        )

    @staticmethod
    def _md(metadata):
        """Outgoing metadata with the caller's span context injected
        (``x-pt-span``), so the servicer's RPC spans stitch into the
        client tick's trace. No open span / tracing off: pass-through."""
        return _tracer.inject(metadata)

    def assign(
        self, request: pb.AssignRequest, timeout: float = 60.0,
        metadata=None,
    ) -> pb.AssignResponse:
        return self._assign(
            request, timeout=timeout, metadata=self._md(metadata)
        )

    def assign_v2(
        self, request: pb.AssignRequestV2, timeout: float = 60.0,
        metadata=None,
    ) -> pb.AssignResponseV2:
        return self._assign_v2(
            request, timeout=timeout, metadata=self._md(metadata)
        )

    def open_session(
        self, chunks, timeout: float = 300.0, metadata=None
    ) -> pb.OpenSessionResponse:
        return self._open_session(
            chunks, timeout=timeout, metadata=self._md(metadata)
        )

    def assign_delta(
        self, request: pb.AssignDeltaRequest, timeout: float = 60.0,
        metadata=None,
    ) -> pb.AssignDeltaResponse:
        return self._assign_delta(
            request, timeout=timeout, metadata=self._md(metadata)
        )

    def health(self, timeout: float = 10.0) -> pb.HealthResponse:
        return self._health(pb.HealthRequest(), timeout=timeout)

    def migrate(
        self, request: pb.MigrateRequest, timeout: float = 120.0,
    ) -> pb.MigrateResponse:
        return self._migrate(request, timeout=timeout)

    def close(self) -> None:
        self.channel.close()


def encoded_to_proto(
    ep: EncodedProviders, er: EncodedRequirements, weights: Optional[CostWeights] = None,
    kernel: str = "topk", top_k: int = 64, eps: float = 0.01, max_iters: int = 0,
) -> pb.AssignRequest:
    """Host-side helper: pack numpy-backed encodings into an AssignRequest.

    Columns go to protobuf as numpy arrays directly (upb consumes any
    iterable of scalars): dtypes are asserted/narrowed ONCE here via an
    ascontiguousarray cast, and the per-element Python list round-trip the
    old ``.tolist()`` spelling paid on every column is gone."""

    def _c(a, dtype):
        return np.ascontiguousarray(np.asarray(a), dtype)

    w = weights or CostWeights()
    t, k = np.asarray(er.gpu_opt_valid).shape
    words = np.asarray(er.gpu_model_mask).shape[-1]
    return pb.AssignRequest(
        providers=pb.ProviderBatch(
            gpu_count=_c(ep.gpu_count, np.int32),
            gpu_mem_mb=_c(ep.gpu_mem_mb, np.int32),
            gpu_model_id=_c(ep.gpu_model_id, np.int32),
            has_gpu=_c(ep.has_gpu, bool),
            has_cpu=_c(ep.has_cpu, bool),
            cpu_cores=_c(ep.cpu_cores, np.int32),
            ram_mb=_c(ep.ram_mb, np.int32),
            storage_gb=_c(ep.storage_gb, np.int32),
            lat=_c(ep.lat, np.float32),
            lon=_c(ep.lon, np.float32),
            has_location=_c(ep.has_location, bool),
            price=_c(ep.price, np.float32),
            load=_c(ep.load, np.float32),
        ),
        requirements=pb.RequirementBatch(
            cpu_required=_c(er.cpu_required, bool),
            cpu_cores=_c(er.cpu_cores, np.int32),
            ram_mb=_c(er.ram_mb, np.int32),
            storage_gb=_c(er.storage_gb, np.int32),
            max_gpu_options=k,
            model_words=words,
            gpu_opt_valid=_c(er.gpu_opt_valid, bool).reshape(-1),
            gpu_count=_c(er.gpu_count, np.int32).reshape(-1),
            gpu_mem_min=_c(er.gpu_mem_min, np.int32).reshape(-1),
            gpu_mem_max=_c(er.gpu_mem_max, np.int32).reshape(-1),
            gpu_total_mem_min=_c(er.gpu_total_mem_min, np.int32).reshape(-1),
            gpu_total_mem_max=_c(er.gpu_total_mem_max, np.int32).reshape(-1),
            gpu_model_mask=_c(er.gpu_model_mask, np.uint32).reshape(-1),
            gpu_model_constrained=_c(er.gpu_model_constrained, bool).reshape(-1),
            lat=_c(er.lat, np.float32),
            lon=_c(er.lon, np.float32),
            has_location=_c(er.has_location, bool),
            priority=_c(er.priority, np.float32),
        ),
        weights=pb.CostWeights(
            price=float(w.price), load=float(w.load),
            proximity=float(w.proximity), priority=float(w.priority),
        ),
        kernel=kernel,
        top_k=top_k,
        eps=eps,
        max_iters=max_iters,
    )


def encoded_to_proto_v2(
    ep: EncodedProviders, er: EncodedRequirements,
    weights: Optional[CostWeights] = None,
    kernel: str = "topk", top_k: int = 64, eps: float = 0.01,
    max_iters: int = 0,
) -> pb.AssignRequestV2:
    """v2 twin of :func:`encoded_to_proto`: tensor-frame columns."""
    w = weights or CostWeights()
    return pb.AssignRequestV2(
        providers=encode_providers_v2(ep),
        requirements=encode_requirements_v2(er),
        weights=pb.CostWeights(
            price=float(w.price), load=float(w.load),
            proximity=float(w.proximity), priority=float(w.priority),
        ),
        kernel=kernel,
        top_k=top_k,
        eps=eps,
        max_iters=max_iters,
    )


class _WireResult(NamedTuple):
    """Version-independent view of an assign response."""

    p4t: np.ndarray
    t4p: np.ndarray
    price: Optional[np.ndarray]
    solve_ms: float


def _res_v1(resp: pb.AssignResponse) -> _WireResult:
    return _WireResult(
        _np(resp.provider_for_task, np.int32),
        _np(resp.task_for_provider, np.int32),
        _np(resp.price, np.float32) if len(resp.price) else None,
        resp.solve_ms,
    )


def _res_v2(
    resp: pb.AssignResponseV2, n_providers: Optional[int] = None
) -> _WireResult:
    p4t = unblob(resp.provider_for_task, np.int32)
    if resp.HasField("task_for_provider"):
        t4p = unblob(resp.task_for_provider, np.int32)
    else:
        # slim delta response: the inverse matching is a local scatter
        t4p = np.full(int(n_providers), -1, np.int32)
        seated = np.flatnonzero((p4t >= 0) & (p4t < int(n_providers)))
        t4p[p4t[seated]] = seated.astype(np.int32)
    return _WireResult(
        p4t,
        t4p,
        unblob(resp.price, np.float32)
        if resp.HasField("price") else None,
        resp.solve_ms,
    )


_RETRYABLE = (
    grpc.StatusCode.UNAVAILABLE,
    grpc.StatusCode.DEADLINE_EXCEEDED,
)

# OpenSession refusal markers that are CAPABILITY answers (the server
# will never serve this session protocol for these parameters): only
# these may demote the client's ladder permanently. Anything else —
# torn streams, draining servers, corrupted frames — is transient.
_PERMANENT_REFUSALS = (
    "not session-servable",
    "fingerprint mismatch",
)


class RemoteBatchMatcher(TpuBatchMatcher):
    """TpuBatchMatcher whose device solves go through the gRPC scheduler
    backend (``scheduler_backend=remote``): the control plane stays a thin
    host process while the kernels run wherever the backend's accelerator
    lives. This is the load-bearing form of the BASELINE.json north-star
    seam — the same columnar batches the in-process matcher feeds its
    jitted kernels are packed into AssignRequests instead, so control
    plane and backend can be scaled and deployed independently (the
    reference's Rust-orchestrator-calls-TPU-service shape).

    ``wire="v1"`` speaks the frozen repeated-scalar contract.
    ``wire="v2"`` speaks tensor frames, and for the native-mt engine runs
    the session protocol: one streamed snapshot, then per-tick
    ``AssignDelta`` messages carrying only rows whose encoded values
    changed since the previous solve (a vectorized column diff against
    the client's shadow copy — the wire twin of the CandidateCache /
    arena dirty-row bookkeeping). A refused delta re-opens the session
    from a fresh snapshot; an UNIMPLEMENTED v2 RPC (old server) drops the
    client to v1 permanently. Transient transport failures
    (UNAVAILABLE / DEADLINE_EXCEEDED) retry with bounded exponential
    backoff and a channel reconnect — one flaky RPC must not fail a
    whole scheduler tick.

    Round-trip cost shows up in ``last_solve_stats`` as
    ``remote_rtt_ms`` (client-observed) next to the backend-reported
    ``solve_ms`` per call; the difference is the columnar seam's cost
    (SURVEY.md §7 hard part #6 wants it cheap — measured, not asserted).
    """

    # candidates are generated behind the seam; the in-process candidate
    # cache cannot hold them (warm prices still ride the wire)
    use_candidate_cache = False

    def attach_groups(self, plugin) -> None:
        # The group solve is tiny (groups x tasks) and runs in-process even
        # on the remote matcher — but this control-plane process must not
        # claim the chip the scheduler pod owns (a chip belongs to one
        # process at a time). Pin jax to the host CPU first; every LARGE
        # solve still rides the gRPC seam.
        import jax

        jax.config.update("jax_platforms", "cpu")
        super().attach_groups(plugin)

    def __init__(
        self,
        store,
        address="127.0.0.1:50061",
        request_timeout: float = 300.0,
        wire: str = "v1",
        chunk_bytes: int = 1 << 20,
        gzip_snapshots: bool = True,
        retries: int = 3,
        retry_base_s: float = 0.05,
        retry_max_s: float = 2.0,
        tick_timeout_s: Optional[float] = None,
        **kwargs,
    ):
        super().__init__(store, **kwargs)
        if wire not in ("v1", "v2"):
            raise ValueError(f"wire must be v1|v2, got {wire!r}")
        # ``address`` accepts one endpoint, a comma-separated list, or a
        # sequence: an ORDERED endpoint list is the dfleet failover
        # ladder — transport failures past the first reconnect rotate
        # to the next endpoint, and a "moved:<endpoint>" refusal
        # rebinds directly (see rebind()).
        if isinstance(address, (list, tuple)):
            endpoints = [str(a) for a in address]
        else:
            endpoints = [a.strip() for a in str(address).split(",")]
        self.endpoints = [e for e in endpoints if e] or [
            "127.0.0.1:50061"
        ]
        self._endpoint_i = 0
        self.request_timeout = request_timeout
        # per-RPC deadline sized to the tick budget: steady-state solve
        # RPCs (unary + AssignDelta) carry this deadline so a wedged
        # server fails THIS tick fast instead of parking the scheduler
        # loop for request_timeout; the cold OpenSession stream keeps
        # the long timeout (a snapshot solve legitimately takes it).
        # None = no tick budget (fall back to request_timeout).
        self.tick_timeout_s = tick_timeout_s
        self.wire = wire
        self.chunk_bytes = chunk_bytes
        self.gzip_snapshots = gzip_snapshots
        self.retries = retries
        self.retry_base_s = retry_base_s
        self.retry_max_s = retry_max_s
        self.client = SchedulerBackendClient(self.endpoints[0])
        # generation-monotonic topology adoption (dfleet): the highest
        # FleetTopology generation this client ever adopted — a stale
        # /fleet.json poll racing a detector ejection must LOSE
        self._topology_generation: Optional[int] = None
        self.seam = SeamMetrics(role="client")
        self._rtt_ms: list[float] = []
        self._backend_ms: list[float] = []
        self._bytes_out = 0
        self._bytes_in = 0
        # client half of the session protocol: shadow columns of the last
        # snapshot/delta the server acknowledged, keyed by solve params
        self._session: Optional[dict] = None
        self._session_uid = uuid.uuid4().hex
        self._session_refused = False
        # resilience counters for the current refresh (degraded answers
        # are explicit all the way up: the matcher's stats name them)
        self._stale_ticks = 0
        self._replayed_ticks = 0

    def refresh(self) -> None:
        self._rtt_ms, self._backend_ms = [], []
        self._bytes_out = self._bytes_in = 0
        self._stale_ticks = self._replayed_ticks = 0
        # one causal trace per scheduler tick: every RPC this refresh
        # issues injects this span's context, and the servicer's spans
        # adopt it — "where did the tick go" is answerable end to end
        with _tracer.span("seam.tick", wire=self.wire):
            super().refresh()  # replaces last_solve_stats; re-attach remote cost
        if self._rtt_ms:
            self.last_solve_stats["wire"] = self.wire
            self.last_solve_stats["remote_calls"] = len(self._rtt_ms)
            self.last_solve_stats["remote_rtt_ms"] = round(sum(self._rtt_ms), 3)
            self.last_solve_stats["remote_backend_ms"] = round(
                sum(self._backend_ms), 3
            )
            self.last_solve_stats["remote_bytes_out"] = self._bytes_out
            self.last_solve_stats["remote_bytes_in"] = self._bytes_in
            if self._stale_ticks:
                self.last_solve_stats["stale_ticks"] = self._stale_ticks
            if self._replayed_ticks:
                self.last_solve_stats["replayed_ticks"] = (
                    self._replayed_ticks
                )

    @staticmethod
    def _strip_padding(enc):
        return strip_padding(enc)

    # ---------------- transport: retry + reconnect ----------------

    def rebind(self, endpoint: Optional[str] = None) -> None:
        """Reconnect the channel — to ``endpoint`` when given (a
        "moved:<endpoint>" migration redirect, inserted into the
        failover list if new), else to the current endpoint. A chaos
        shim (faults.inject.ChaosClient) keeps its injector and fault
        cursors: only the dead channel under it is swapped."""
        if endpoint:
            if endpoint not in self.endpoints:
                self.endpoints.append(endpoint)
            self._endpoint_i = self.endpoints.index(endpoint)
        fresh = SchedulerBackendClient(self.endpoints[self._endpoint_i])
        shim_rebind = getattr(self.client, "rebind", None)
        if callable(shim_rebind):
            shim_rebind(fresh)
            return
        try:
            self.client.close()
        except Exception:
            pass
        self.client = fresh

    def adopt_topology(self, topology, session_id=None) -> bool:
        """Adopt a fleet topology (a discovery poll / manager push):
        the failover endpoint list becomes the ring's ordered walk for
        this client's session. GENERATION-MONOTONIC: a topology no
        newer than the one already adopted is refused (returns False,
        counted) — a stale ``/fleet.json`` poll racing a detector
        ejection must never resurrect an ejected endpoint into the
        ladder. If the currently-bound endpoint was ejected, the
        channel rebinds to the new home immediately."""
        gen = int(getattr(topology, "generation", 0))
        if (
            self._topology_generation is not None
            and gen <= self._topology_generation
        ):
            self.seam.count("stale_topology_refused")
            return False
        self._topology_generation = gen
        sid = session_id or (
            (self._session or {}).get("id") or self._session_uid
        )
        current = self.endpoints[self._endpoint_i]
        self.endpoints = list(topology.failover_order(sid))
        if current in self.endpoints:
            self._endpoint_i = self.endpoints.index(current)
        else:
            # our endpoint was ejected from the ring: fail over now
            self._endpoint_i = 0
            self.seam.count("endpoint_failover")
            self.rebind()
        self.seam.count("topology_adopted")
        return True

    def _reconnect(self, failover: bool = False) -> None:
        """Fresh channel; with ``failover`` (a retry that already
        reconnected once and failed again) rotate to the next endpoint
        in the ordered list — a dead process's clients spread over the
        survivors instead of hammering the corpse."""
        if failover and len(self.endpoints) > 1:
            self._endpoint_i = (
                self._endpoint_i + 1
            ) % len(self.endpoints)
            self.seam.count("endpoint_failover")
        self.rebind()

    def _backoff_s(self, attempt: int) -> float:
        """Bounded exponential backoff with deterministic jitter for
        retry ``attempt`` (0-based): ``retry_base_s * 2^attempt`` capped
        at ``retry_max_s``, scaled into [0.5x, 1.5x) by a hash of this
        client's session uid + the attempt number. H clients restarting
        against a recovered server therefore spread their retries over
        the backoff window instead of thundering-herding it in lockstep
        — and the schedule is a pure function of (uid, attempt), so
        tests replay it exactly (no ``random``: the determinism lint's
        spirit holds even off the kernel paths)."""
        base = min(self.retry_base_s * (2.0 ** attempt), self.retry_max_s)
        import hashlib

        digest = hashlib.sha1(
            f"{self._session_uid}:{attempt}".encode()
        ).digest()
        frac = int.from_bytes(digest[:8], "big") / 2.0 ** 64
        return min(base * (0.5 + frac), self.retry_max_s)

    def _rpc(self, make_call):
        """Run ``make_call()`` (a zero-arg closure issuing one RPC) with
        bounded, jittered exponential backoff on transient transport
        failures (see :meth:`_backoff_s`); each retry reconnects the
        channel (a dead server that came back gets a fresh HTTP/2
        connection instead of a wedged one). A RESOURCE_EXHAUSTED abort
        (the fleet's unary admission gate) backs off the same way but
        WITHOUT reconnecting — the server is healthy, its token bucket
        is just empty, and the refill is what the wait buys. Sustained
        throttle past the retry budget surfaces as the explicit error
        it is."""
        for attempt in range(self.retries + 1):
            try:
                return make_call()
            except grpc.RpcError as e:
                code = e.code()
                if attempt >= self.retries:
                    raise
                if code == grpc.StatusCode.RESOURCE_EXHAUSTED:
                    self.seam.count("throttled_retry")
                    time.sleep(self._backoff_s(attempt))
                    continue
                if code not in _RETRYABLE:
                    raise
                self.seam.count("retry")
                time.sleep(self._backoff_s(attempt))
                # first retry reconnects the SAME endpoint (transient
                # blip); later retries fail over down the endpoint list
                self._reconnect(failover=attempt >= 1)

    # ---------------- v1/v2 unary ----------------

    def _timed(self, make_call, bytes_out: int):
        t0 = time.perf_counter()
        with _tracer.span("seam.rpc", wire=self.wire):
            resp = self._rpc(make_call)
        self._rtt_ms.append((time.perf_counter() - t0) * 1e3)
        self._bytes_out += bytes_out
        self._bytes_in += resp.ByteSize()
        return resp

    def _call(
        self, ep, er, kernel: str, eps: float, max_iters: int,
        warm_price=None, seed_p4t=None, top_k: int = 64,
    ) -> _WireResult:
        sp = self._strip_padding(ep)
        sr = self._strip_padding(er)
        if self.wire == "v2":
            try:
                return self._call_v2(
                    sp, sr, kernel, eps, max_iters, warm_price, seed_p4t,
                    top_k,
                )
            except grpc.RpcError as e:
                if e.code() != grpc.StatusCode.UNIMPLEMENTED:
                    raise
                # old server: drop to the frozen v1 contract for good
                self.wire = "v1"
                self.seam.count("fallback_v1")
        t0 = time.perf_counter()
        req = encoded_to_proto(
            sp, sr, self.weights,
            kernel=kernel, top_k=top_k, eps=eps, max_iters=max_iters,
        )
        if warm_price is not None and seed_p4t is not None:
            req.warm_price.extend(np.asarray(warm_price, np.float32))
            req.seed_provider_for_task.extend(
                np.asarray(seed_p4t, np.int32)
            )
        _t_ser = time.perf_counter()
        _tracer.record_span(
            "wire.encode", int(t0 * 1e9), int((_t_ser - t0) * 1e9),
            wire=self.wire,
        )
        self.seam.observe_ms("serialize", (_t_ser - t0) * 1e3)
        resp = self._timed(
            lambda: self.client.assign(req, timeout=self.request_timeout),
            req.ByteSize(),
        )
        self._backend_ms.append(resp.solve_ms)
        return _res_v1(resp)

    def _call_v2(
        self, sp, sr, kernel, eps, max_iters, warm_price, seed_p4t, top_k,
    ) -> _WireResult:
        if (
            parse_native_threads(kernel) is not None
            and not self._session_refused
        ):
            res = self._session_call(sp, sr, kernel, eps, max_iters, top_k)
            if res is not None:
                return res
        t0 = time.perf_counter()
        req = encoded_to_proto_v2(
            sp, sr, self.weights,
            kernel=kernel, top_k=top_k, eps=eps, max_iters=max_iters,
        )
        if warm_price is not None and seed_p4t is not None:
            req.warm_price.CopyFrom(blob(warm_price, np.float32))
            req.seed_provider_for_task.CopyFrom(blob(seed_p4t, np.int32))
        _t_ser = time.perf_counter()
        _tracer.record_span(
            "wire.encode", int(t0 * 1e9), int((_t_ser - t0) * 1e9),
            wire=self.wire,
        )
        self.seam.observe_ms("serialize", (_t_ser - t0) * 1e3)
        resp = self._timed(
            lambda: self.client.assign_v2(req, timeout=self.request_timeout),
            req.ByteSize(),
        )
        self._backend_ms.append(resp.solve_ms)
        return _res_v2(resp)

    # ---------------- v2 session protocol (client half) ----------------

    def _session_call(
        self, sp, sr, kernel, eps, max_iters, top_k,
    ) -> Optional[_WireResult]:
        """Session-protocol solve: delta tick against the open session, or
        a fresh streamed snapshot when there is none / the population
        reshaped / the server lost it. Returns None when the server
        refuses the session protocol (caller falls to unary v2)."""
        t0 = time.perf_counter()
        p_cols = canon_columns(sp, P_WIRE_DTYPES)
        r_cols = canon_columns(sr, R_WIRE_DTYPES)
        params = (
            kernel, int(top_k), float(eps), int(max_iters),
            float(self.weights.price), float(self.weights.load),
            float(self.weights.proximity), float(self.weights.priority),
            p_cols["gpu_count"].shape[0], r_cols["cpu_cores"].shape[0],
        )
        st = self._session
        if st is None or st["params"] != params:
            return self._open_session(
                p_cols, r_cols, kernel, eps, max_iters, top_k, params, t0
            )
        prow = dirty_rows(p_cols, st["p_cols"])
        trow = dirty_rows(r_cols, st["r_cols"])
        n_total = params[-2] + params[-1]
        if (prow.size + trow.size) > 0.5 * n_total:
            # a mostly-new marketplace: the delta message would carry more
            # than a snapshot's worth of rows — re-epoch instead
            return self._open_session(
                p_cols, r_cols, kernel, eps, max_iters, top_k, params, t0
            )
        req = pb.AssignDeltaRequest(
            session_id=st["id"],
            epoch_fingerprint=st["fp"],
            tick=st["tick"] + 1,
        )
        if prow.size:
            req.provider_rows.CopyFrom(blob(prow, np.int32))
            req.providers.CopyFrom(
                encode_providers_v2(take_rows(p_cols, prow))
            )
        if trow.size:
            req.task_rows.CopyFrom(blob(trow, np.int32))
            req.requirements.CopyFrom(
                encode_requirements_v2(take_rows(r_cols, trow))
            )
        _t_ser = time.perf_counter()
        _tracer.record_span(
            "wire.encode", int(t0 * 1e9), int((_t_ser - t0) * 1e9),
            wire=self.wire,
        )
        self.seam.observe_ms("serialize", (_t_ser - t0) * 1e3)
        # delta RPCs carry the TICK deadline (the budget this answer is
        # useful within), not the long snapshot timeout
        tick_timeout = self.tick_timeout_s or self.request_timeout
        try:
            resp = self._timed(
                lambda: self.client.assign_delta(
                    req, timeout=tick_timeout
                ),
                req.ByteSize(),
            )
        except grpc.RpcError as e:
            if e.code() != grpc.StatusCode.INVALID_ARGUMENT:
                raise
            # the frame was mangled in transit (the hardening layer
            # refused it at decode, BEFORE any session state moved):
            # resending the same delta is safe — once. A persistent
            # INVALID_ARGUMENT is a real contract violation and raises.
            self.seam.count("corrupt_resend")
            resp = self._timed(
                lambda: self.client.assign_delta(
                    req, timeout=tick_timeout
                ),
                req.ByteSize(),
            )
        if not resp.session_ok:
            resp = self._delta_refusal_ladder(resp, req, tick_timeout)
        if not resp.session_ok:
            # evicted / expired / served by a replica that never saw the
            # snapshot (or still throttled after the bounded retries):
            # re-open from our authoritative state, don't error the
            # scheduler tick
            self.seam.count("session_reopen")
            self._session = None
            return self._open_session(
                p_cols, r_cols, kernel, eps, max_iters, top_k, params, t0
            )
        st["p_cols"], st["r_cols"] = p_cols, r_cols
        st["tick"] += 1
        if resp.stale:
            # DEGRADED answer: the server burned its tick deadline and
            # served the previous plan, explicitly flagged. The delta
            # was still applied server-side (shadow update above is
            # correct); the staleness is bounded by the server's
            # max_stale_ticks contract and surfaced in solve stats.
            self.seam.count("stale_served")
            self._stale_ticks += 1
        if resp.replayed:
            # idempotent retransmit answer (our original send was
            # answered but the response died): counted, not an error
            self.seam.count("delta_replayed")
            self._replayed_ticks += 1
        self._backend_ms.append(resp.result.solve_ms)
        return _res_v2(resp.result, n_providers=params[-2])

    def _delta_refusal_ladder(self, resp, req, tick_timeout):
        """Refusal handling for one delta, each rung bounded; returns
        the final response (still not session_ok => the caller
        re-opens, the pre-dfleet last resort).

        throttle   RESOURCE_EXHAUSTED: admission/backpressure — retry
                   the SAME delta after jittered backoff (re-opening
                   would AMPLIFY an over-rate tenant's load into full
                   snapshot solves, the opposite of what the refusal
                   asked for).
        moved      "moved:<endpoint>": live migration redirect — rebind
                   to the new home and resend the SAME delta; the
                   session rehydrates warm there from its handed-off
                   journal (zero reopens is the whole point).
        evicted    one same-delta resend: a migration races an
                   in-flight delta as "session evicted"; the resend is
                   answered "moved:" (follow it warm) — a GENUINE
                   eviction answers "unknown session" and re-opens.
        handoff    "unknown session" with >1 endpoint: the journal
                   rename may still be in flight after a failover —
                   or a double transport blip failed us over AWAY from
                   the session's live home. Bounded backoff, rotating
                   an endpoint per wait (the owner — live session or
                   re-routed journal — is somewhere in the list), then
                   concede to a reopen.
        """
        throttles = redirects = waits = 0
        evict_retried = False
        # snapshot the redirect budget BEFORE the loop: rebind() grows
        # self.endpoints with each fresh redirect target, so a bound
        # read inside the loop would chase a split-brain map forever
        redirect_limit = len(self.endpoints) + 1
        while not resp.session_ok:
            err = resp.error
            if "RESOURCE_EXHAUSTED" in err:
                if throttles >= self.retries:
                    break
                self.seam.count("throttled_retry")
                time.sleep(self._backoff_s(throttles))
                throttles += 1
            elif err.startswith("moved:"):
                if redirects >= redirect_limit:
                    break  # redirect loop (split-brain maps): re-open
                self.seam.count("moved_redirect")
                self.rebind(err[len("moved:"):].strip())
                redirects += 1
            elif "session evicted" in err and not evict_retried:
                evict_retried = True
            elif "unknown session" in err and len(self.endpoints) > 1:
                if waits >= max(self.retries, len(self.endpoints)):
                    break
                self.seam.count("handoff_wait")
                time.sleep(self._backoff_s(waits))
                waits += 1
                self._reconnect(failover=True)
            else:
                break
            resp = self._timed(
                lambda: self.client.assign_delta(
                    req, timeout=tick_timeout
                ),
                req.ByteSize(),
            )
        return resp

    def _open_session(
        self, p_cols, r_cols, kernel, eps, max_iters, top_k, params, t0,
    ) -> Optional[_WireResult]:
        fp = epoch_fingerprint(
            p_cols, r_cols, self.weights, kernel, int(top_k), eps,
            int(max_iters),
        )
        req = encoded_to_proto_v2(
            take_rows(p_cols, slice(None)), take_rows(r_cols, slice(None)),
            self.weights, kernel=kernel, top_k=top_k, eps=eps,
            max_iters=max_iters,
        )
        chunks = list(
            chunk_snapshot(
                self._session_uid, fp, req,
                chunk_bytes=self.chunk_bytes,
                use_gzip=self.gzip_snapshots,
            )
        )
        n_bytes = sum(len(c.payload) for c in chunks)
        _t_ser = time.perf_counter()
        _tracer.record_span(
            "wire.encode", int(t0 * 1e9), int((_t_ser - t0) * 1e9),
            wire=self.wire,
        )
        self.seam.observe_ms("serialize", (_t_ser - t0) * 1e3)
        resp = self._timed(
            lambda: self.client.open_session(
                iter(chunks), timeout=self.request_timeout
            ),
            n_bytes,
        )
        redirects = 0
        redirect_limit = len(self.endpoints) + 1  # pre-loop snapshot:
        # rebind() appends fresh targets, a live bound never trips
        while (
            not resp.ok
            and resp.error.startswith("moved:")
            and redirects < redirect_limit
        ):
            # live-migration redirect on the OPEN itself: the session's
            # journal lives at the new home — opening here would fork
            # ownership, so the server bounced us there instead
            self.seam.count("moved_redirect")
            self.rebind(resp.error[len("moved:"):].strip())
            redirects += 1
            resp = self._timed(
                lambda: self.client.open_session(
                    iter(chunks), timeout=self.request_timeout
                ),
                n_bytes,
            )
        if not resp.ok:
            if "RESOURCE_EXHAUSTED" in resp.error:
                # admission throttle, NOT a capability refusal: this
                # tick degrades to the unary rung, but the session
                # protocol stays available — setting _session_refused
                # here would demote a briefly-throttled tenant to
                # unthrottled full-snapshot unary solves FOREVER
                self.seam.count("session_throttled")
                self._session = None
                return None
            if not any(
                marker in resp.error for marker in _PERMANENT_REFUSALS
            ):
                # transient refusal (torn/truncated snapshot stream, a
                # draining server, a corrupted frame the hardening
                # layer bounced): degrade THIS tick to unary and try
                # the session protocol again next tick — only a
                # capability answer may demote the ladder permanently
                self.seam.count("session_transient_refusal")
                self._session = None
                return None
            # server-side capability refusal is a protocol answer, not
            # a transport failure: remember it so every later tick goes
            # straight to the unary rung
            self.seam.count("session_refused")
            self._session_refused = True
            self._session = None
            return None
        self._session = {
            "id": resp.session_id,
            "fp": resp.epoch_fingerprint,
            "tick": 0,
            "p_cols": p_cols,
            "r_cols": r_cols,
            "params": params,
        }
        self._backend_ms.append(resp.result.solve_ms)
        return _res_v2(resp.result)

    # ---------------- matcher integration ----------------

    def _native_kernel(self) -> str:
        if self.native_engine in ("native-mt", "sinkhorn-mt"):
            return self.native_engine + (
                f":{self.native_threads}" if self.native_threads else ""
            )
        if self.native_engine.partition(":")[0] == "jax":
            # first-class engine, same suffix convention (jax[:D], D =
            # sharded-gen devices; a bare "jax" picks the suffix up from
            # native_threads like the native engines do). NEVER demoted
            # to "native" — a silent cross-engine swap would invalidate
            # every replay A/B keyed on the session kernel string.
            if ":" in self.native_engine or not self.native_threads:
                return self.native_engine
            return f"jax:{self.native_threads}"
        return "native"

    def _bounded_t4p(self, ep, er) -> np.ndarray:
        if self.native_fallback:
            # engine=native-mt rides the wire as a kernel-string suffix so
            # the backend's warm arena (and its thread pool) do the work;
            # on wire=v2 it rides the session protocol instead and only
            # churned rows hit the wire
            res = self._call(
                ep, er, self._native_kernel(), eps=0.02, max_iters=0
            )
            return np.asarray(res.t4p, np.int32)
        res = self._call(ep, er, "auction", eps=0.05, max_iters=300)
        return np.asarray(res.t4p, np.int32)

    def _bounded_t4p_sparse(
        self, ep, er, price0: np.ndarray, p4s0: np.ndarray, warm: bool
    ) -> tuple[np.ndarray, np.ndarray]:
        """Scale path over the wire: the backend's "topk" kernel, with the
        incremental-solve state (prices + previous matching) riding the
        request/response so the backend stays stateless across replicas."""
        n_p = int(np.asarray(ep.valid).sum())
        n_s = int(np.asarray(er.valid).sum())
        warm_price = seed = None
        if warm:
            warm_price = np.asarray(price0[:n_p], np.float32)
            seed = np.asarray(p4s0[:n_s], np.int32)
        res = self._call(
            ep, er, "topk", eps=0.02, max_iters=0,
            warm_price=warm_price, seed_p4t=seed, top_k=self.top_k,
        )
        price = (
            res.price if res.price is not None
            else np.zeros(n_p, np.float32)
        )
        return (
            np.asarray(res.t4p, np.int32),
            np.asarray(price, np.float32),
        )

    def _unbounded_best(self, ep, er) -> np.ndarray:
        res = self._call(ep, er, "best", eps=0.0, max_iters=0)
        return np.asarray(res.t4p, np.int32)
