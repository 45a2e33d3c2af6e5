"""TPU batch matcher: the ``scheduler_backend=tpu`` hot path.

Replaces the reference's per-heartbeat O(tasks) greedy walk
(crates/orchestrator/src/scheduler/mod.rs:26-74) with one batched solve per
population change: encode every schedulable node and every task once,
build the cost tensor on-device, and resolve contention with the auction
kernel. Per-heartbeat lookups then hit a host-side dict.

Task semantics: the reference's matcher hands the *same* (newest) task to
every node — tasks are unbounded swarms. This framework generalizes with a
``replicas`` bound read from the task's scheduling config
(``plugins["tpu_scheduler"]["replicas"] = ["<N>"]``; absent = unbounded,
matching the reference). Requirements come from
``plugins["tpu_scheduler"]["compute_requirements"] = ["<DSL>"]`` in the same
requirements DSL the pools use (shared/src/models/node.rs:180-374).

Solve structure:
  - bounded tasks are unit-expanded into replica slots -> auction over
    [nodes x slots] (contended, price-mediated);
  - unassigned nodes then take their cheapest compatible unbounded task
    (row argmin — contention-free, exactly the swarm semantics).

Shapes are padded to power-of-two buckets so jit re-traces only on bucket
growth, not on every membership change.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import time
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

# must precede this module's @jax.jit decorators (the ops import below
# also installs it; stated here because this file jits directly)
from protocol_tpu.utils import jitwitness as _jitwitness

_jitwitness.install()

from protocol_tpu.models.node import ComputeRequirements
from protocol_tpu.models.task import Task
from protocol_tpu.ops.assign import assign_auction
from protocol_tpu.ops.cost import (
    INFEASIBLE,
    CostWeights,
    cost_matrix,
    with_tie_jitter,
)
from protocol_tpu.ops.encoding import FeatureEncoder
from protocol_tpu.ops.sparse import (
    assign_auction_sparse_scaled,
    assign_auction_sparse_warm,
    candidates_topk,
)
from protocol_tpu.sched.cand_cache import (
    CandidateCache,
    CandidateMemo,
    ProviderItem,
    TaskItem,
)
from protocol_tpu.store.context import StoreContext
from protocol_tpu.store.domains.node_store import NodeStatus, OrchestratorNode

SCHEDULABLE = (NodeStatus.HEALTHY, NodeStatus.WAITING_FOR_HEARTBEAT)

from protocol_tpu.utils.lockwitness import LazyLock, make_lock

# LazyLock: module-global (the witness decision must wait for first use);
# jax.profiler.trace is process-global
_PROFILE_LOCK = LazyLock("profile")


def _pow2_bucket(n: int, floor: int = 8) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def task_replicas(task: Task) -> Optional[int]:
    cfg = task.scheduling_config
    if cfg and cfg.plugins:
        vals = cfg.plugins.get("tpu_scheduler", {}).get("replicas")
        if vals:
            r = int(vals[0])
            if r <= 0:
                raise ValueError(f"replicas must be positive, got {r}")
            return r
    return None


def task_requirements(task: Task) -> ComputeRequirements:
    cfg = task.scheduling_config
    if cfg and cfg.plugins:
        vals = cfg.plugins.get("tpu_scheduler", {}).get("compute_requirements")
        if vals:
            return ComputeRequirements.parse(vals[0])
    return ComputeRequirements()


def task_anti_affinity(task: Task) -> Optional[str]:
    """Replica-spread constraint (BASELINE ladder #5's anti-affinity term):
    ``"task"`` = replicas on distinct providers (the matching already
    guarantees this; declared form documents intent), ``"location"`` =
    replicas on distinct geographic locations (failure-domain spread the
    reference cannot express — its matcher hands every node the same
    task, scheduler/mod.rs:26-74)."""
    cfg = task.scheduling_config
    if cfg and cfg.plugins:
        vals = cfg.plugins.get("tpu_scheduler", {}).get("anti_affinity")
        if vals:
            mode = str(vals[0])
            if mode not in ("task", "location"):
                raise ValueError(f"anti_affinity must be task|location, got {mode!r}")
            return mode
    return None


def task_colocate(task: Task) -> bool:
    """Capacity-sharing opt-in (BASELINE ladder #5's core semantics:
    "several tasks land on one provider while capacity holds"). Colocated
    task replicas route through the vector bin-pack (ops/binpack.py) over
    the providers' real multi-resource capacity (GPU count, total VRAM,
    cpu cores, ram, storage) instead of the one-task-per-provider auction
    — a 2-GPU provider can hold two 1-GPU tasks concurrently. The
    reference cannot express this at all (one node, one task:
    crates/orchestrator/src/scheduler/mod.rs:26-74)."""
    cfg = task.scheduling_config
    if cfg and cfg.plugins:
        vals = cfg.plugins.get("tpu_scheduler", {}).get("colocate")
        if vals:
            v = str(vals[0]).lower()
            if v not in ("true", "false"):
                raise ValueError(f"colocate must be true|false, got {vals[0]!r}")
            return v == "true"
    return False


def validate_tpu_scheduler_config(task: Task) -> None:
    """Reject malformed tpu_scheduler plugin config at task-creation time so
    user input can never break the batch solve (raises ValueError)."""
    try:
        replicas = task_replicas(task)
        task_requirements(task)
        if task_anti_affinity(task) is not None and replicas is None:
            raise ValueError(
                "anti_affinity requires a replicas bound (unbounded swarm "
                "tasks have no replica set to spread)"
            )
        if task_colocate(task):
            if replicas is None:
                raise ValueError(
                    "colocate requires a replicas bound (the capacity "
                    "bin-pack places a finite replica set)"
                )
            if task_anti_affinity(task) is not None:
                raise ValueError(
                    "colocate and anti_affinity are mutually exclusive "
                    "(stacking vs spreading)"
                )
    except Exception as e:
        raise ValueError(f"invalid tpu_scheduler config: {e}") from e


@jax.jit
def _solve_bounded(ep, er, weights) -> jax.Array:
    cost, _ = cost_matrix(ep, er, weights)
    # with_tie_jitter: without it, identically-specced providers make every
    # open slot bid the SAME provider each round — one assignment per
    # round, so the solve seats exactly max_iters replicas (observed
    # 300/400 live)
    return assign_auction(
        with_tie_jitter(cost), eps=0.05, max_iters=300
    ).task_for_provider


@jax.jit
def _cost_only(ep, er, weights) -> jax.Array:
    return cost_matrix(ep, er, weights)[0]


@jax.jit
def _solve_unbounded(ep, er, weights) -> tuple[jax.Array, jax.Array]:
    cost, _ = cost_matrix(ep, er, weights)
    best = jnp.argmin(cost, axis=1).astype(jnp.int32)  # [P]
    feas = jnp.take_along_axis(cost, best[:, None], axis=1)[:, 0] < INFEASIBLE * 0.5
    return jnp.where(feas, best, -1), feas


class TpuBatchMatcher:
    # the candidate cache is an in-process structure; RemoteBatchMatcher
    # (whose candidates live behind the gRPC seam) turns it off
    use_candidate_cache = True

    def __init__(
        self,
        store: StoreContext,
        weights: Optional[CostWeights] = None,
        min_solve_interval: float = 1.0,
        max_replica_slots: int = 1 << 20,
        dense_cell_budget: int = 1 << 24,
        top_k: int = 64,
        warm_start: bool = True,
        native_fallback: bool = False,
        native_engine: str = "native",
        native_threads: int = 0,
        use_mesh: bool = False,
        approx_recall: Optional[float] = None,
        time_fn=time.monotonic,
    ):
        self.store = store
        self.weights = weights or CostWeights(priority=1.0)
        self.min_solve_interval = min_solve_interval
        self.max_replica_slots = max_replica_slots
        # [providers x slots] cost cells above which phase 1 switches from
        # the dense auction to the streaming top-K + sparse frontier auction
        # (the only viable shape at 1M scale — ops/sparse.py). 2^24 cells =
        # 64 MB f32: comfortably dense below, pointlessly so above.
        self.dense_cell_budget = dense_cell_budget
        self.top_k = top_k
        # carry auction prices + the previous matching across solves so
        # population churn re-bids only the delta frontier (SURVEY §7 hard
        # part 4) instead of cold-solving the full population
        self.warm_start = warm_start
        self._warm_price_by_addr: dict[str, float] = {}
        # retirement mask carried between warm solves, keyed to the slot
        # layout it was computed under (see _solve_slots_cached)
        self._warm_retired: np.ndarray | None = None
        self._warm_retired_fp: tuple | None = None
        self._warm_reserve: float | None = None
        # claim-masked slot rows (anti-affinity/colocation) of the current
        # and previous solve: both dirty the carried retirement mask
        self._claim_rows_now: np.ndarray | None = None
        self._claim_rows_prev: np.ndarray | None = None
        # forward auctions never LOWER prices: carried prices ratchet
        # within a warm chain. Three bounds keep that safe: the warm
        # kernel caps entry prices below its retirement floor
        # (ops/sparse.py assign_auction_sparse_warm), the CandidateCache
        # rebuilds ADAPTIVELY when measured base drift has re-ranked more
        # than max_stale_frac of the fleet (cand_cache._stale_fraction —
        # staleness bounded by measurement, not schedule), and
        # ``cold_every`` remains the schedule BACKSTOP for drift the
        # measurement can't see (e.g. price ratchet on the uncached wire
        # path, which has no selection cache to measure).
        self.cold_every = 256
        self._warm_solves_since_cold = 0
        # degraded mode: solve with the native C++ engine instead of the
        # jitted kernels (for deployments whose accelerator is absent or
        # unreachable — the engine is this framework's CPU backend, not an
        # external dependency). Opt-in so tests keep covering the jax path.
        self.native_fallback = native_fallback
        # native engine selection: "native" is the single-threaded
        # Gauss-Seidel engine; "native-mt" runs the multi-threaded fused
        # pass + deterministic Jacobi auction THROUGH the persistent solve
        # arena (protocol_tpu/native/arena.py), so steady-state solves
        # recompute only churned rows; "sinkhorn-mt" rides the same arena
        # but solves with the O(nnz) sparse entropic engine (warm (f, g)
        # potential carry + auction-referee rounding) — the soft/
        # relaxation twin the combinatorial solver is refereed against.
        # native_threads: 0 = all hardware threads. "jax[:D]" selects
        # the accelerator-path warm arena (parallel/jax_arena.py) as a
        # PEER of the native engines — same persistent-arena semantics,
        # sharded candidate generation over D devices (0/absent = all
        # visible). It is not gated on native_fallback: under fallback
        # the process is pinned to CPU and the jax engine runs there,
        # single-device — degraded inside the engine, never silently
        # swapped for a native one.
        self._jax_devices = 0
        if native_engine.partition(":")[0] == "jax":
            suffix = native_engine.partition(":")[2]
            try:
                self._jax_devices = int(suffix) if suffix else 0
            except ValueError:
                raise ValueError(
                    f"bad jax device suffix in {native_engine!r} "
                    "(want jax[:D])"
                )
        elif native_engine not in ("native", "native-mt", "sinkhorn-mt"):
            raise ValueError(
                "native_engine must be native|native-mt|sinkhorn-mt|"
                f"jax[:D], got {native_engine!r}"
            )
        self.native_engine = native_engine
        self._jax_engine = native_engine.partition(":")[0] == "jax"
        self.native_threads = int(native_threads)
        self._native_arena = None
        self._last_arena_stats: dict = {}
        # multi-chip hosts: shard phase 1's candidate generation over
        # the visible devices (parallel/sparse.py; bit-identical lists,
        # so the plan does not depend on it); the solve runs on one
        # device. Opt-in (deploy sets PROTOCOL_TPU_USE_MESH=1 via serve).
        self.use_mesh = use_mesh
        # stage-A selection via lax.approx_max_k (TPU PartialReduce)
        # instead of exact lax.top_k — the measured stage-A bottleneck's
        # mitigation (SCALING.md); e.g. 0.95. None = exact.
        self.approx_recall = approx_recall
        self._mesh = None
        self._last_gen_sharded = False
        if native_fallback:
            # pin the process to the host platform NOW: this matcher was
            # asked for the CPU engine, so its process must not claim the
            # chip the scheduler pod owns (one process per chip). MUST
            # precede the mesh probe below — jax.devices() initializes the
            # default backend, which is exactly the claim being avoided.
            jax.config.update("jax_platforms", "cpu")
        if use_mesh and not native_fallback:
            import jax as _jax

            if len(_jax.devices()) > 1:
                from protocol_tpu.parallel import make_mesh

                self._mesh = make_mesh(len(_jax.devices()))
            else:
                logging.getLogger(__name__).warning(
                    "use_mesh requested but only one device is visible; "
                    "generating single-device"
                )
        self._time = time_fn
        self._dirty = True
        self._last_solve = float("-inf")
        self._assignment: dict[str, str] = {}  # node address -> task id
        # colocated nodes hold SEVERAL tasks concurrently (phase 0.5
        # capacity bin-pack); _assignment keeps the first for the
        # one-task lookup surface, this holds the full ordered list
        self._assignment_multi: dict[str, list[str]] = {}
        self._covered: set[str] = set()  # addresses the last solve considered
        # heartbeats arrive from worker threads (asyncio.to_thread): one lock
        # serializes solves and makes (_assignment, _covered) swaps atomic
        self._solve_lock = make_lock("solve")
        self.encoder = FeatureEncoder()
        self._cache = CandidateCache(self.encoder, self.weights, k=top_k)
        # content-hash memo for the UNCACHED wire path (stateless repeats)
        self._cand_memo = CandidateMemo()
        self._last_warm_used = False
        self._last_warm_seeded = 0
        self._last_stall: dict = {}
        # flight recorder (PROTOCOL_TPU_TRACE=<path>): the native-arena
        # solve path records its exact encoded inputs + matching, so any
        # live or bench run yields a replayable trace
        # (protocol_tpu/trace/). Lazy: the trace package (and its pb2
        # import) loads only when capture is requested.
        self.trace_recorder = None
        if os.environ.get("PROTOCOL_TPU_TRACE"):
            from protocol_tpu.trace.recorder import TraceRecorder

            self.trace_recorder = TraceRecorder.from_env("matcher")
        self._groups_plugin = None
        self._group_assignment: dict[str, str] = {}  # group id -> task id
        self._group_covered: set[str] = set()
        self.last_solve_stats: dict = {}
        self._solve_seq = 0

    # ----- invalidation hooks (wire to TaskStore observers + node changes)

    def mark_dirty(self) -> None:
        self._dirty = True

    def attach_observers(self) -> None:
        self.store.task_store.subscribe_created(lambda t: self.mark_dirty())
        self.store.task_store.subscribe_deleted(lambda t: self.mark_dirty())

    def attach_groups(self, plugin) -> None:
        """Compose with a NodeGroupsPlugin (SURVEY §7 hard part 5): grouped
        nodes leave the individual solve (their work arrives group-wise),
        groups become pseudo-providers in a topology-masked cost solve, and
        the plugin's group<->task selection goes through
        :meth:`rank_task_for_group` instead of ``rng.choice`` — while ALL
        of the plugin's race-safe commit machinery (SET-NX group task,
        compare-and-delete cleanup, dissolved-group recovery) stays in
        charge of the actual assignment."""
        self._groups_plugin = plugin
        plugin.task_ranker = self.rank_task_for_group
        for hook_name in ("on_group_created", "on_group_dissolved"):
            prev = getattr(plugin, hook_name)

            def chained(group, prev=prev):
                self.mark_dirty()
                if prev is not None:
                    prev(group)

            setattr(plugin, hook_name, chained)

    # ----- lookup

    def lookup(self, node: OrchestratorNode) -> tuple[Optional[Task], bool]:
        """Returns (task, covered). ``covered`` means the last batch solve
        considered this node, so an empty assignment is a deliberate verdict
        (infeasible or capacity-excluded), not a gap to paper over."""
        self._ensure_fresh()
        covered = node.address in self._covered
        tid = self._assignment.get(node.address)
        task = self.store.task_store.get_task(tid) if tid else None
        return task, covered

    def task_for_node(self, node: OrchestratorNode) -> Optional[Task]:
        return self.lookup(node)[0]

    def assigned_task_ids(self, address: str) -> list[str]:
        """Multi-assignment ids from the LAST solve, no refresh — a plain
        dict read for callers that already resolved the node this beat
        (the heartbeat path calls get_task_for_node first). [] for
        non-colocated nodes."""
        return list(self._assignment_multi.get(address, ()))

    def tasks_for_node(self, node: OrchestratorNode) -> list[Task]:
        """ALL tasks assigned to this node in the last solve: one for
        auction/unbounded nodes, several for colocated nodes (ladder #5
        capacity sharing). Order is placement order — the first entry is
        what the one-task ``lookup`` surface serves."""
        self._ensure_fresh()
        tids = self._assignment_multi.get(node.address)
        if not tids:
            task, _ = self.lookup(node)
            return [task] if task is not None else []
        found = (self.store.task_store.get_task(t) for t in tids)
        return [t for t in found if t is not None]

    def _ensure_fresh(self) -> None:
        # Re-solve only when something changed, and never more often than
        # min_solve_interval — population churn must not turn back into a
        # per-heartbeat O(solve) cost. The lock keeps concurrent heartbeat
        # threads from solving twice or observing a half-swapped assignment.
        if self._dirty and self._time() - self._last_solve >= self.min_solve_interval:
            with self._solve_lock:
                if self._dirty and (
                    self._time() - self._last_solve >= self.min_solve_interval
                ):
                    self.refresh()

    # ----- device solves (overridden by RemoteBatchMatcher to route the
    # same columnar batches through the gRPC scheduler backend)

    def _native_cost(self, ep, er) -> np.ndarray:
        # module-level jit: re-traces per shape bucket, not per solve
        cpu = jax.devices("cpu")[0]
        with jax.default_device(cpu):
            return np.asarray(_cost_only(ep, er, self.weights))

    def _bounded_t4p(self, ep, er) -> np.ndarray:
        if self._jax_engine:
            # the accelerator-path peer of the native arenas: persistent
            # candidate structure + warm auction duals, sharded gen over
            # the device mesh — checked BEFORE native_fallback so a
            # CPU-pinned process still runs the jax engine (on CPU
            # devices), never a silent native swap
            n_providers = int(np.asarray(ep.gpu_count).shape[0])
            if self._native_arena is None:
                from protocol_tpu.parallel.jax_arena import JaxSolveArena

                self._native_arena = JaxSolveArena(
                    cold_every=self.cold_every,
                    devices=self._jax_devices,
                    approx_recall=self.approx_recall,
                )
            p4s = self._native_arena.solve(ep, er, self.weights)
            self._last_arena_stats = {
                f"arena_{k}": v
                for k, v in self._native_arena.last_stats.items()
            }
            if self.trace_recorder is not None:
                from protocol_tpu.trace.recorder import safe as _trace_safe

                _trace_safe(
                    self.trace_recorder.record_solve, ep, er,
                    self.weights, self.native_engine,
                    self._native_arena.k, self._native_arena.eps_end,
                    0, p4s, self._native_arena.price,
                    metrics=dict(self._last_arena_stats),
                )
            t4p = np.full(n_providers, -1, np.int32)
            for s_idx, p_idx in enumerate(p4s):
                if p_idx >= 0:
                    t4p[p_idx] = s_idx
            return t4p
        if self.native_fallback:
            from protocol_tpu import native

            n_providers = int(np.asarray(ep.gpu_count).shape[0])
            self._last_arena_stats = {}
            if self.native_engine in ("native-mt", "sinkhorn-mt"):
                # persistent warm-solve arena: candidate structure, solver
                # duals (auction prices+retirement, or sinkhorn potentials)
                # survive between solves; only churned rows are recomputed
                # (tentpole semantics of the CandidateCache, on the native
                # path)
                if self._native_arena is None:
                    from protocol_tpu.native.arena import NativeSolveArena

                    self._native_arena = NativeSolveArena(
                        threads=self.native_threads,
                        cold_every=self.cold_every,
                        engine=(
                            "sinkhorn"
                            if self.native_engine == "sinkhorn-mt"
                            else "auction"
                        ),
                    )
                p4s = self._native_arena.solve(ep, er, self.weights)
                self._last_arena_stats = {
                    f"arena_{k}": v
                    for k, v in self._native_arena.last_stats.items()
                }
                if self.trace_recorder is not None:
                    from protocol_tpu.trace.recorder import (
                        safe as _trace_safe,
                    )

                    kernel = self.native_engine + (
                        f":{self.native_threads}"
                        if self.native_threads else ""
                    )
                    _trace_safe(
                        self.trace_recorder.record_solve, ep, er,
                        self.weights, kernel, self._native_arena.k,
                        self._native_arena.eps_end, 0, p4s,
                        self._native_arena.price,
                        metrics=dict(self._last_arena_stats),
                    )
            else:
                # fused feature->cost->top-k: the [P, T] tensor never
                # exists (same streaming shape as the sparse TPU path)
                cand_p, cand_c = native.fused_topk_candidates(
                    ep, er, self.weights, k=min(64, n_providers)
                )
                p4s = native.auction_sparse(
                    cand_p, cand_c, num_providers=n_providers
                )
            t4p = np.full(n_providers, -1, np.int32)
            for s_idx, p_idx in enumerate(p4s):
                if p_idx >= 0:
                    t4p[p_idx] = s_idx
            return t4p
        return np.asarray(_solve_bounded(ep, er, self.weights))

    def _bounded_t4p_sparse(
        self, ep, er, price0: np.ndarray, p4s0: np.ndarray, warm: bool
    ) -> tuple[np.ndarray, np.ndarray]:
        """Phase 1 at scale: streaming top-K candidates + frontier auction
        (ops/sparse.py — the 1M-shape architecture, now the live path above
        dense_cell_budget). Returns (slot per provider [P_pad], prices [P_pad]).

        ``warm=True`` runs the single-phase incremental solve seeded with the
        previous solve's prices + matching; cold solves use the eps-scaling
        ladder."""
        s_bucket = int(np.asarray(er.cpu_cores).shape[0])
        tile = min(1024, s_bucket)  # pow2 buckets: tile always divides
        # bidirectional candidates: reverse (provider->slot) edges keep every
        # provider reachable when forward top-k windows pile onto the same
        # cheap providers (coverage-capped matchings at scale — see
        # ops/sparse.py candidates_topk_reverse). Content-hash memoized:
        # an unchanged fleet between heartbeats skips the O(P*T) pass
        # (the wire path's delta-awareness, VERDICT r4 item 3)
        gen = None
        D = self._mesh.shape["p"] if self._mesh is not None else 0
        if D > 1 and s_bucket % D == 0:
            # generation is the stage where the mesh pays (zero per-round
            # collectives — SCALING.md mesh economics); bit-identical to
            # the single-device generator, so it shares the memo key
            from protocol_tpu.parallel import candidates_topk_bidir_sharded

            tile = min(tile, s_bucket // D)

            def gen(ep_, er_, w_, **kw):
                return candidates_topk_bidir_sharded(
                    ep_, er_, w_, mesh=self._mesh, **kw
                )

        misses_before = self._cand_memo.misses
        cand_p, cand_c = self._cand_memo.get(
            ep, er, self.weights, k=self.top_k, tile=tile,
            reverse_r=8, extra=16, approx_recall=self.approx_recall,
            gen=gen,
        )
        # "sharded generation RAN", not "was configured": a memo hit
        # generated nothing
        self._last_gen_sharded = (
            gen is not None and self._cand_memo.misses > misses_before
        )
        num_providers = int(np.asarray(ep.gpu_count).shape[0])
        res, price, _retired, self._warm_reserve = self._sparse_solve(
            cand_p, cand_c, num_providers, warm,
            jnp.asarray(price0), jnp.asarray(p4s0),
            reserve0=self._warm_reserve if warm else None,
        )
        return np.asarray(res.task_for_provider), np.asarray(price)

    def _sparse_solve(self, cand_p, cand_c, num_providers, warm, price0, p4t0,
                      stats_out=None, retired0=None, reserve0=None):
        """Phase 1's solve dispatch: warm solve vs cold ladder. Always
        returns (result, prices, retired, reserve) — the full dual
        state, so chained warm solves can skip re-fighting priced-out
        slots (ops/sparse.py: retirement carry) and keep the anchor of
        a pool with a queue."""
        if warm:
            return assign_auction_sparse_warm(
                cand_p, cand_c, num_providers,
                price0=price0, p4t0=p4t0, stats_out=stats_out,
                retired0=retired0, with_state=True, reserve0=reserve0,
            )
        return assign_auction_sparse_scaled(
            cand_p, cand_c, num_providers, stats_out=stats_out,
            with_state=True,
        )

    def _seed_slots(
        self, p4s0: np.ndarray, row_of_addr: dict, tasks, bounded, slot_range
    ) -> int:
        """Seat the previous solve's holders back into their task's replica
        slots (indices in ``row_of_addr``'s space). Seeds that no longer
        satisfy eps-CS are evicted by the warm kernel's repair pass — the
        remainder is the delta frontier that actually re-bids."""
        tidx_by_id = {tasks[i].id: i for i, _ in bounded}
        prev_by_task: dict[int, list[int]] = {}
        for addr, tid in self._assignment.items():
            row = row_of_addr.get(addr)
            i = tidx_by_id.get(tid)
            if row is not None and i is not None and i in slot_range:
                prev_by_task.setdefault(i, []).append(row)
        for i, holders in prev_by_task.items():
            start, take = slot_range[i]
            for j, row in enumerate(holders[:take]):
                p4s0[start + j] = row
        return int((p4s0 >= 0).sum())

    def _solve_anti_affinity(
        self, ep, N: int, aa, tasks, prio, idx_addrs, loc_by_addr
    ) -> dict[int, int]:
        """Phase 0: place anti-affinity task replicas via the bin-pack
        kernel (ops/binpack.py) with unit capacity — one replica per
        provider — and exclusion groups over the declared domain:
        providers ("task") or geographic locations ("location").

        Cost stays bounded at scale by solving over the UNION of each
        slot's top-K candidates rather than all N providers. Returns
        {provider row -> task idx}."""
        import dataclasses as _dc

        from protocol_tpu.ops.binpack import assign_binpack_ffd

        results: dict[int, int] = {}
        for mode in ("task", "location"):
            items = [(i, take, m) for (i, take, m) in aa if m == mode]
            if not items:
                continue
            slot_task: list[int] = []
            groups: list[int] = []
            for gi, (i, r, _m) in enumerate(items):
                take = min(r, N, 4096)
                if take < min(r, N):
                    # same never-a-silent-cap rule as the phase-1 slot cap
                    self._aa_truncated += min(r, N) - take
                    logging.getLogger(__name__).warning(
                        "anti-affinity replica demand for task %s capped at "
                        "4096 slots (%d dropped this solve)",
                        tasks[i].id, min(r, N) - take,
                    )
                slot_task.extend([i] * take)
                groups.extend([gi] * take)
            S = len(slot_task)
            if S == 0:
                continue
            s_pad = _pow2_bucket(S)
            er = self.encoder.encode_requirements(
                [task_requirements(tasks[i]) for i in slot_task],
                priorities=[float(prio[i]) for i in slot_task],
                pad_to=s_pad,
            )
            cand_p, _ = candidates_topk(
                ep, er, self.weights, k=self.top_k, tile=min(1024, s_pad)
            )
            rows = np.unique(np.asarray(cand_p))
            rows = rows[rows >= 0].astype(np.int64)
            if rows.size == 0:
                continue
            rpad = _pow2_bucket(len(rows))
            gather = np.concatenate(
                [rows, np.zeros(rpad - len(rows), np.int64)]
            )
            sub_ep = jax.tree.map(
                lambda a: jnp.take(a, jnp.asarray(gather), axis=0), ep
            )
            sub_valid = np.zeros(rpad, bool)
            sub_valid[: len(rows)] = np.asarray(ep.valid)[rows]
            sub_ep = _dc.replace(sub_ep, valid=jnp.asarray(sub_valid))
            cost = np.asarray(_cost_only(sub_ep, er, self.weights)).copy()
            # rows claimed by a previous mode pass are taken
            taken_local = np.isin(rows, np.fromiter(results, np.int64, len(results)))
            cost[: len(rows)][taken_local] = INFEASIBLE
            if mode == "location":
                loc_local, L = self._location_classes(rows, idx_addrs, loc_by_addr)
                loc = np.zeros(rpad, np.int32)
                loc[: len(rows)] = loc_local
            else:
                loc = np.arange(rpad, dtype=np.int32)
                L = rpad
            res = assign_binpack_ffd(
                jnp.asarray(cost),
                jnp.ones((s_pad, 1), jnp.float32),
                jnp.ones((rpad, 1), jnp.float32),
                anti_group=jnp.asarray(
                    np.concatenate(
                        [np.asarray(groups, np.int32),
                         np.full(s_pad - S, -1, np.int32)]
                    )
                ),
                loc_id=jnp.asarray(loc),
                # pow2 buckets: L and G size the jitted [L, G] carry, and
                # unbucketed values would retrace on every population drift
                num_locations=_pow2_bucket(int(L)),
                num_groups=_pow2_bucket(len(items)),
            )
            p4s = np.asarray(res.provider_for_task)[:S]
            for s, r_local in enumerate(p4s):
                if 0 <= r_local < len(rows):
                    results[int(rows[r_local])] = slot_task[s]
        return results

    def _solve_colocation(
        self, ep, N: int, colo, tasks, prio, taken_rows
    ) -> dict[int, list[int]]:
        """Phase 0.5: capacity-sharing placement (ladder #5's core
        semantics, live). Colocate-flagged task replicas route through the
        vector bin-pack (ops/binpack.py) with the providers' REAL
        multi-resource capacity — [gpu count, total VRAM, cpu cores, ram,
        storage] from the encoded columns — so several replicas (of one or
        several tasks) stack on one provider while capacity holds.

        Cost stays bounded at scale the same way as the anti-affinity
        phase: solve over the union of each slot's top-K candidates.
        Returns {provider row -> [task idx, ...]} in placement order."""
        import dataclasses as _dc

        from protocol_tpu.ops.binpack import assign_binpack_ffd

        slot_task: list[int] = []
        for i, r in colo:
            take = min(r, 4096)
            if take < r:
                self._colo_truncated += r - take
                logging.getLogger(__name__).warning(
                    "colocate replica demand for task %s capped at 4096 "
                    "slots (%d dropped this solve)", tasks[i].id, r - take,
                )
            slot_task.extend([i] * take)
        S = len(slot_task)
        self._colo_requested = S
        if S == 0:
            return {}
        s_pad = _pow2_bucket(S)
        reqs = [task_requirements(tasks[i]) for i in slot_task]
        # Compat relaxation for capacity sharing: the DSL's gpu count gate
        # is EXACT (reference node.rs:445-459 parity) — a 1-GPU slice
        # would never match a 2-GPU provider. Colocated slots claim a
        # SLICE, so drop count (and the full-provider total-memory max)
        # from the compat side; the bin-pack's demand vector (built from
        # the ORIGINAL requirement below) enforces the real reservation
        # against remaining capacity. Model/per-GPU-memory gates still
        # bind unchanged.
        relaxed = [
            dataclasses.replace(
                r,
                gpu=[
                    dataclasses.replace(
                        g, count=None, total_memory_max=None
                    )
                    for g in r.gpu
                ],
            )
            for r in reqs
        ]
        er = self.encoder.encode_requirements(
            relaxed,
            priorities=[float(prio[i]) for i in slot_task],
            pad_to=s_pad,
        )
        # bidirectional selection: forward-only top-k would cap the row
        # pool at ~k cheap providers on price-dominated fleets (the same
        # coverage cap candidates_topk_reverse's docstring measures),
        # stranding replicas while feasible providers idle
        cand_p, _ = self._cand_memo.get(
            ep, er, self.weights, k=self.top_k, tile=min(1024, s_pad),
            reverse_r=8, extra=16,
        )
        rows = np.unique(np.asarray(cand_p))
        rows = rows[rows >= 0].astype(np.int64)
        if taken_rows:
            rows = rows[~np.isin(rows, np.fromiter(taken_rows, np.int64))]
        if rows.size == 0:
            return {}
        rpad = _pow2_bucket(len(rows))
        gather = np.concatenate([rows, np.zeros(rpad - len(rows), np.int64)])
        sub_ep = jax.tree.map(
            lambda a: jnp.take(a, jnp.asarray(gather), axis=0), ep
        )
        sub_valid = np.zeros(rpad, bool)
        sub_valid[: len(rows)] = np.asarray(ep.valid)[rows]
        sub_ep = _dc.replace(sub_ep, valid=jnp.asarray(sub_valid))
        cost = np.asarray(_cost_only(sub_ep, er, self.weights))

        # capacity from the encoded provider columns (-1 = unreported = 0:
        # can't host what you don't report)
        pg = np.maximum(np.asarray(sub_ep.gpu_count, np.float32)[:rpad], 0.0)
        pvram = pg * np.maximum(
            np.asarray(sub_ep.gpu_mem_mb, np.float32)[:rpad], 0.0
        )
        pc = np.maximum(np.asarray(sub_ep.cpu_cores, np.float32)[:rpad], 0.0)
        pm = np.maximum(np.asarray(sub_ep.ram_mb, np.float32)[:rpad], 0.0)
        ps = np.maximum(np.asarray(sub_ep.storage_gb, np.float32)[:rpad], 0.0)
        capacity = np.stack([pg, pvram, pc, pm, ps], axis=1)

        # demand from the ORIGINAL (unrelaxed) requirements: this is the
        # reservation the bin-pack subtracts from remaining capacity.
        # With GPU OR-alternatives, compat can match a provider via ANY
        # option while the worker may run the largest — reserve the
        # elementwise MAX across options (over-reserving blocks a
        # placement; under-reserving oversubscribes a provider's GPUs,
        # the strictly worse failure)
        demand = np.zeros((s_pad, 5), np.float32)
        for s, r in enumerate(reqs):
            gcount = vram = 0.0
            for g in r.gpu:
                c = float(g.count or 0)
                if g.total_memory_min is not None:
                    v = float(g.total_memory_min)
                else:
                    v = c * float(g.memory_mb or g.memory_mb_min or 0)
                gcount = max(gcount, c)
                vram = max(vram, v)
            demand[s] = (
                gcount,
                vram,
                float(r.cpu.cores or 0) if r.cpu else 0.0,
                float(r.ram_mb or 0),
                float(r.storage_gb or 0),
            )

        res = assign_binpack_ffd(
            jnp.asarray(cost),
            jnp.asarray(demand),
            jnp.asarray(capacity),
        )
        p4s = np.asarray(res.provider_for_task)[:S]
        placed: dict[int, list[int]] = {}
        for s, r_local in enumerate(p4s):
            if 0 <= r_local < len(rows):
                placed.setdefault(int(rows[r_local]), []).append(slot_task[s])
        return placed

    def _location_classes(
        self, rows: np.ndarray, idx_addrs, loc_by_addr
    ) -> tuple[np.ndarray, int]:
        """Location class id per subset row: nodes sharing a (rounded)
        lat/lon coordinate share a class; nodes without a location are
        each their own failure domain (they cannot be proven co-located,
        so spreading treats them as distinct)."""
        keys = []
        for r in rows:
            loc = loc_by_addr.get(idx_addrs[r]) if r < len(idx_addrs) else None
            if loc is not None:
                keys.append((round(loc.latitude, 3), round(loc.longitude, 3)))
            else:
                keys.append(("solo", int(r)))
        uniq = {k: i for i, k in enumerate(dict.fromkeys(keys))}
        return np.asarray([uniq[k] for k in keys], np.int32), len(uniq)

    def _solve_groups(
        self, groups, tasks, prio
    ) -> tuple[dict[str, str], set[str]]:
        """Group <-> task solve through the real cost machinery.

        Groups become pseudo-providers: aggregate price/load (member means)
        and centroid location feed the same cost_matrix the node solve
        uses, with compatibility supplied as an explicit topology mask
        (group's configuration name in the task's allowed_topologies)
        instead of the spec algebra. Replica-BOUNDED topology tasks are
        unit-expanded and matched with the dense auction — their replica
        count now bounds how many groups run them, which rng.choice could
        never express; unassigned groups then take the best applicable
        unbounded task (topology-matched, or unrestricted — the
        reference's any-group-may-run-it semantics,
        node_groups/mod.rs:1122-1188) by row argmin.

        Returns ({group id -> task id}, covered group ids). The plugin's
        SET-NX machinery commits assignments; this solve only ranks.
        """
        gcov = {g.id for g in groups}
        if not groups or not tasks:
            return {}, gcov
        topo_bounded: list[tuple[int, int]] = []
        pool_unbounded: list[int] = []  # phase-B candidates
        for i, t in enumerate(tasks):
            topos = t.allowed_topologies()
            r = task_replicas(t)
            if topos:
                if r is None:
                    pool_unbounded.append(i)
                else:
                    topo_bounded.append((i, r))
            elif r is None:
                # unrestricted unbounded: any group may run it
                pool_unbounded.append(i)

        G = len(groups)
        g_pad = _pow2_bucket(G)
        prices, loads, locs = [], [], []
        for g in groups:
            members = [self.store.node_store.get_node(a) for a in g.nodes]
            members = [m for m in members if m is not None]
            prices.append(
                float(np.mean([m.price or 0.0 for m in members])) if members else 0.0
            )
            loads.append(
                float(np.mean([m.load or 0.0 for m in members])) if members else 0.0
            )
            with_loc = [m.location for m in members if m.location is not None]
            if with_loc:
                from protocol_tpu.models.node import NodeLocation

                locs.append(
                    NodeLocation(
                        latitude=float(np.mean([l.latitude for l in with_loc])),
                        longitude=float(np.mean([l.longitude for l in with_loc])),
                    )
                )
            else:
                locs.append(None)
        ep_g = self.encoder.encode_providers(
            [None] * G, locations=locs, prices=prices, loads=loads, pad_to=g_pad
        )

        result: dict[str, str] = {}
        taken = np.zeros(G, bool)

        # ---- phase A: replica-bounded topology tasks -> dense auction
        if topo_bounded:
            slot_task: list[int] = []
            for i, r in topo_bounded:
                slot_task.extend([i] * min(r, G, 4096))
            S = len(slot_task)
            s_pad = _pow2_bucket(S)
            er = self.encoder.encode_requirements(
                [ComputeRequirements()] * S,
                priorities=[float(prio[i]) for i in slot_task],
                pad_to=s_pad,
            )
            mask = np.zeros((g_pad, s_pad), bool)
            for s, i in enumerate(slot_task):
                topos = set(tasks[i].allowed_topologies())
                for gi, g in enumerate(groups):
                    mask[gi, s] = g.configuration_name in topos
            cost, _ = cost_matrix(ep_g, er, self.weights, mask=jnp.asarray(mask))
            res = assign_auction(with_tie_jitter(cost), eps=0.05, max_iters=300)
            t4g = np.asarray(res.task_for_provider)[:G]
            for gi, s_idx in enumerate(t4g):
                if 0 <= s_idx < S:
                    result[groups[gi].id] = tasks[slot_task[s_idx]].id
                    taken[gi] = True

        # ---- phase B: remaining groups -> best applicable unbounded task.
        # Topology-restricted tasks outrank unrestricted ones regardless of
        # cost: groups are the ONLY venue a topology task can run, while an
        # unrestricted task also reaches every ungrouped node — letting a
        # newer unrestricted task outbid a topology task would starve the
        # gang workload (observed live before this tiering).
        if pool_unbounded and not taken.all():
            T2 = len(pool_unbounded)
            t_pad = _pow2_bucket(T2)
            er = self.encoder.encode_requirements(
                [ComputeRequirements()] * T2,
                priorities=[float(prio[i]) for i in pool_unbounded],
                pad_to=t_pad,
            )
            mask = np.zeros((g_pad, t_pad), bool)
            for c, i in enumerate(pool_unbounded):
                topos = tasks[i].allowed_topologies()
                for gi, g in enumerate(groups):
                    mask[gi, c] = (not topos) or (g.configuration_name in topos)
            cost, _ = cost_matrix(ep_g, er, self.weights, mask=jnp.asarray(mask))
            cost_np = np.asarray(cost)[:G, :T2]
            is_topo = np.asarray(
                [bool(tasks[i].allowed_topologies()) for i in pool_unbounded]
            )
            # tier the argmin: feasible topo columns first
            tiered = np.where(is_topo[None, :], cost_np, cost_np + INFEASIBLE * 0.25)
            tiered = np.where(cost_np < INFEASIBLE * 0.5, tiered, INFEASIBLE)
            best = tiered.argmin(axis=1)
            feas = tiered[np.arange(G), best] < INFEASIBLE * 0.5
            for gi in range(G):
                if not taken[gi] and feas[gi]:
                    result[groups[gi].id] = tasks[pool_unbounded[best[gi]]].id
        return result, gcov

    def rank_task_for_group(self, group, applicable):
        """The NodeGroupsPlugin's task_ranker hook: serve the group solve's
        choice. A group the solve covered but left unassigned deliberately
        gets None (e.g. a bounded topology task's replica budget went to
        other groups); a group formed after the last solve triggers a
        re-solve."""
        self._ensure_fresh()
        if group.id not in self._group_covered:
            self.mark_dirty()
            self._ensure_fresh()
        tid = self._group_assignment.get(group.id)
        match = next((t for t in applicable if t.id == tid), None)
        if match is not None:
            return match
        if group.id in self._group_covered:
            return None
        # Not covered even after a re-solve (e.g. solve throttled). Only
        # UNBOUNDED tasks are safe to hand out here: a replica-bounded
        # task's budget is accounted inside the solve, and _task_for_group
        # commits choices sticky via SET-NX — an uncovered-group fallback
        # grabbing a bounded task could exceed its replica bound
        # permanently. Bounded-only groups wait one beat instead.
        unbounded = [t for t in applicable if task_replicas(t) is None]
        if not unbounded:
            return None
        return max(unbounded, key=lambda t: t.created_at)

    def _warm_gate(self, seeded: int, rebuilt: bool = False) -> bool:
        """Single source of truth for warm eligibility + the periodic-cold
        counter (both the cached and the wire sparse paths go through it —
        drift between duplicated gates is how warm bugs hide)."""
        warm = (
            self.warm_start
            and seeded > 0
            and not rebuilt
            and self._warm_solves_since_cold < self.cold_every
        )
        if warm:
            self._warm_solves_since_cold += 1
        else:
            self._warm_solves_since_cold = 0
        return warm

    def _solve_slots_cached(self, prepared, tasks, bounded, slot_range) -> np.ndarray:
        """Phase 1 over the candidate cache's persistent structure: warm
        single-phase auction when seeds exist, eps-scaling ladder otherwise.
        Prices are stored back per-row so the NEXT solve re-bids only its
        delta."""
        p4s0 = np.full(prepared.cand_p.shape[0], -1, np.int32)
        seeded = self._seed_slots(
            p4s0, prepared.row_of_addr, tasks, bounded, slot_range
        )
        warm = self._warm_gate(seeded, rebuilt=prepared.rebuilt)
        # (as NumPy: the solve reads the pool's regime off the lists on
        # the host before it uploads them)
        cand_p, cand_c = prepared.cand_p, prepared.cand_c
        # retirement carry: valid only while the slot layout (task ids ->
        # slot ranges) and the cached candidate structure are unchanged —
        # any rebuild or task churn invalidates the mask (slots renumber)
        slot_fp = (
            tuple(sorted((tasks[i].id,) + tuple(slot_range[i]) for i, _ in bounded)),
            int(p4s0.shape[0]),
        )
        retired0 = None
        if warm and self._warm_retired is not None and self._warm_retired_fp == slot_fp:
            carried = np.asarray(self._warm_retired)
            if prepared.dirty_slots is None:
                # unknown provenance (first prepare after a relayout the
                # slot_fp missed): drop the whole mask rather than carry
                # flags over changed candidates
                carried = None
            else:
                # the warm kernel's contract: rows whose candidates changed
                # must be cleared by the caller — otherwise a task stays
                # retired after a newly-feasible provider churns into its
                # list and sits unassigned until the next cold solve
                # (ADVICE r5). dirty_slots is the cache-side signal;
                # claim-masking (this solve's AND last solve's — a released
                # claim restores candidates) edits lists after the cache
                # compared, so those rows are dirty too.
                dirty = prepared.dirty_slots.copy()
                for claim_rows in (
                    self._claim_rows_now, self._claim_rows_prev
                ):
                    if claim_rows is not None:
                        if claim_rows.shape == dirty.shape:
                            dirty |= claim_rows
                        else:
                            carried = None
                if carried is not None and dirty.shape == carried.shape:
                    carried = carried & ~dirty
                else:
                    carried = None
            if carried is not None:
                retired0 = jnp.asarray(carried)
        self._claim_rows_prev = self._claim_rows_now
        stall_stats: dict = {}
        res, price, retired, self._warm_reserve = self._sparse_solve(
            cand_p, cand_c, prepared.p_bucket, warm,
            jnp.asarray(prepared.price0), jnp.asarray(p4s0),
            stats_out=stall_stats, retired0=retired0,
            reserve0=self._warm_reserve if warm else None,
        )
        self._cache.store_prices(np.asarray(price))
        self._warm_retired = np.asarray(retired)
        self._warm_retired_fp = slot_fp
        self._last_warm_used = warm
        self._last_warm_seeded = seeded
        self._last_stall = stall_stats
        return np.asarray(res.task_for_provider)[: prepared.num_rows]

    def _unbounded_best(self, ep, er) -> np.ndarray:
        if self.native_fallback:
            cost = self._native_cost(ep, er)
            best = cost.argmin(axis=1).astype(np.int32)
            feas = cost[np.arange(cost.shape[0]), best] < INFEASIBLE * 0.5
            return np.where(feas, best, -1).astype(np.int32)
        best, _feas = _solve_unbounded(ep, er, self.weights)
        return np.asarray(best)

    # ----- batch solve

    def refresh(self) -> None:
        """One batch solve; with PROTOCOL_TPU_PROFILE_DIR set, each solve
        is captured as an xprof trace (SURVEY §5's stated tracing plan:
        JAX profiler instead of the reference's log-line timing)."""
        profile_dir = os.environ.get("PROTOCOL_TPU_PROFILE_DIR", "")
        if profile_dir:
            # jax.profiler.trace is process-global and cannot nest: one
            # lock across ALL matcher instances (devnet runs several)
            with _PROFILE_LOCK, jax.profiler.trace(profile_dir):
                self._refresh()
            return
        self._refresh()

    def _refresh(self) -> None:
        t_start = time.perf_counter()
        # clear the dirty flag BEFORE reading state: a concurrent mark_dirty
        # landing mid-read must trigger another solve, not be erased
        self._dirty = False
        self._last_solve = self._time()
        nodes = [
            n for n in self.store.node_store.get_nodes() if n.status in SCHEDULABLE
        ]
        tasks = self.store.task_store.get_all_tasks()
        # Drop tasks with malformed plugin config (validated at creation via
        # validate_tpu_scheduler_config; this guards direct store writes).
        ok_tasks = []
        for t in tasks:
            try:
                task_replicas(t)
                task_requirements(t)
                task_anti_affinity(t)
                task_colocate(t)
            except Exception:
                continue
            ok_tasks.append(t)
        tasks = ok_tasks
        # newest-first priority, matching NewestTaskPlugin ordering:
        # normalize created_at to [0, 1] so the priority cost term dominates
        # ties in the same direction as the reference's sort.
        if tasks:
            created = np.asarray([t.created_at for t in tasks], np.float64)
            span = max(created.max() - created.min(), 1.0)
            prio = ((created - created.min()) / span).astype(np.float32)
        else:
            prio = np.zeros(0, np.float32)

        # ---- group phase (composed gang scheduling): groups are
        # pseudo-providers in a topology-masked cost solve; grouped nodes
        # leave the individual solve entirely
        if self._groups_plugin is not None:
            groups = self._groups_plugin.get_groups()
            try:
                self._group_assignment, self._group_covered = (
                    self._solve_groups(groups, tasks, prio)
                )
            except Exception:
                logging.getLogger(__name__).exception("group solve failed")
                self._group_assignment, self._group_covered = {}, set()
            grouped = {a for g in groups for a in g.nodes}
            nodes = [n for n in nodes if n.address not in grouped]

        # build the new solution locally and swap at the end so concurrent
        # readers never observe a half-built assignment
        assignment: dict[str, str] = {}
        covered = {n.address for n in nodes}
        if not nodes or not tasks:
            self._assignment_multi = {}
            self._assignment, self._covered = assignment, covered
            self._solve_seq += 1
            self.last_solve_stats = {
                "nodes": len(nodes),
                "tasks": len(tasks),
                "group_assignments": len(self._group_assignment),
                "seq": self._solve_seq,
            }
            return

        bounded: list[tuple[int, int]] = []  # (task idx, replicas)
        unbounded: list[int] = []
        aa: list[tuple[int, int, str]] = []  # (task idx, replicas, mode)
        colo: list[tuple[int, int]] = []  # (task idx, replicas), capacity-sharing
        for i, t in enumerate(tasks):
            if t.allowed_topologies() and self._groups_plugin is not None:
                # topology-restricted tasks are group-only when gang
                # scheduling is active: handing one to an individual node
                # would violate the gang contract. Without a groups plugin
                # (no gang semantics in this deployment) they stay
                # individually schedulable as before.
                continue
            r = task_replicas(t)
            if r is None:
                unbounded.append(i)
            elif task_colocate(t):
                colo.append((i, r))
            else:
                mode = task_anti_affinity(t)
                if mode:
                    aa.append((i, r, mode))
                else:
                    bounded.append((i, r))

        P = len(nodes)
        p_bucket = _pow2_bucket(P)

        truncated_slots = 0
        kernel_used = "none"
        warm_used = False
        warm_seeded = 0
        cache_stats: dict = {}

        # ---- replica-slot expansion for bounded tasks (cheap, host-side)
        slot_task: list[int] = []
        slot_range: dict[int, tuple[int, int]] = {}  # task idx -> (start, n)
        req_by_task: dict[int, ComputeRequirements] = {}
        if bounded:
            req_by_task = {i: task_requirements(tasks[i]) for i, _ in bounded}
            # the native degraded-mode engine solves dense on the host: it
            # keeps the old 4096-slot envelope regardless of the (much
            # larger) sparse-path default
            slot_cap = (
                min(self.max_replica_slots, 4096)
                if self.native_fallback
                else self.max_replica_slots
            )
            for i, r in bounded:
                take = min(min(r, P), slot_cap - len(slot_task))
                slot_range[i] = (len(slot_task), take)
                slot_task.extend([i] * take)
                if len(slot_task) >= slot_cap:
                    break
            # arithmetic, not loop iterations: demand can be ~1M slots
            truncated_slots = sum(min(r, P) for _, r in bounded) - len(slot_task)
            if truncated_slots:
                # never a silent cap: at 1M-scale demand, dropped replica
                # slots are a capacity decision the operator must see
                logging.getLogger(__name__).warning(
                    "replica demand exceeds max_replica_slots=%d: "
                    "%d slots dropped this solve",
                    self.max_replica_slots,
                    truncated_slots,
                )
        self._last_arena_stats = {}  # set by _bounded_t4p on the native path
        self._claim_rows_now = None  # set by the claim-masking block below
        s_bucket = _pow2_bucket(len(slot_task)) if slot_task else 0
        use_sparse = bool(slot_task) and (
            not self.native_fallback
            # the jax engine owns phase 1 through its arena (which IS
            # the sparse pipeline, warm): the stateless sparse_topk
            # rung would re-pay cold generation every solve
            and not self._jax_engine
            and p_bucket * s_bucket > self.dense_cell_budget
        )
        # The candidate cache owns the provider index space on the cached
        # path: rows are stable across solves (dead rows masked invalid), so
        # per-solve encoding is O(churn) and candidate structure persists.
        cached_path = (
            use_sparse and self.warm_start and self.use_candidate_cache
        )

        prepared = None
        if cached_path:
            if self._warm_solves_since_cold >= self.cold_every:
                # periodic full re-ground: fresh candidate selection AND
                # fresh prices (bounds both selection staleness from base
                # drift and the warm chain's monotone price ratchet)
                self._cache.invalidate()
            pitems = [
                ProviderItem(
                    addr=n.address,
                    specs=n.compute_specs,
                    location=n.location,
                    price=n.price or 0.0,
                    load=n.load or 0.0,
                )
                for n in nodes
            ]
            titems = [
                TaskItem(
                    task_id=tasks[i].id,
                    requirement=req_by_task[i],
                    take=slot_range[i][1],
                    prio=float(prio[i]),
                )
                for i, _ in bounded
                if i in slot_range and slot_range[i][1] > 0
            ]
            prepared = self._cache.prepare(pitems, titems)
            ep = prepared.ep
            idx_addrs = prepared.addr_of_row
            N = prepared.num_rows
            cache_stats = {
                "cache_rebuilt": prepared.rebuilt,
                "cache_delta_rows": prepared.delta_rows,
                "cache_delta_tasks": prepared.delta_tasks,
                "cache_uncovered_rows": prepared.uncovered_rows,
                "cache_stale_frac": round(prepared.stale_frac, 4),
            }
        else:
            specs = [n.compute_specs for n in nodes]
            locs = [n.location for n in nodes]
            ep = self.encoder.encode_providers(
                specs,
                locations=locs,
                prices=[n.price or 0.0 for n in nodes],
                loads=[n.load or 0.0 for n in nodes],
                pad_to=p_bucket,
            )
            idx_addrs = [n.address for n in nodes]
            N = P

        assigned = np.zeros(N, bool)

        # ---- phase 0: anti-affinity tasks -> bin-pack with exclusion
        # domains (ladder #5's anti-affinity term, live): replicas spread
        # across distinct providers/locations via ops/binpack; claimed
        # providers are then excluded from the auction and phase 2.
        aa_assigned = 0
        self._aa_truncated = 0
        claims: dict[int, int] = {}
        if aa:
            loc_by_addr = {n.address: n.location for n in nodes}
            claims = self._solve_anti_affinity(
                ep, N, aa, tasks, prio, idx_addrs, loc_by_addr
            )
            for row, i in claims.items():
                assignment[idx_addrs[row]] = tasks[i].id
                assigned[row] = True
            aa_assigned = len(claims)

        # ---- phase 0.5: colocation -> capacity bin-pack (ladder #5 live:
        # several replicas stack on one provider while its GPU/VRAM/cpu/
        # ram/storage capacity holds — see _solve_colocation)
        colo_slots = 0
        self._colo_truncated = 0
        self._colo_requested = 0
        assignment_multi: dict[str, list[str]] = {}
        placed: dict[int, list[int]] = {}
        if colo:
            placed = self._solve_colocation(
                ep, N, colo, tasks, prio, set(claims)
            )
            for row, tidxs in placed.items():
                addr = idx_addrs[row]
                assignment[addr] = tasks[tidxs[0]].id
                # several replicas of the SAME task stacking on one
                # provider reserve that many capacity slots, but execution
                # is one instance per distinct task per node (the worker
                # dedups by task id; reference semantics) — the wire list
                # carries distinct ids only
                assignment_multi[addr] = list(
                    dict.fromkeys(tasks[j].id for j in tidxs)
                )
                assigned[row] = True
                colo_slots += len(tidxs)
            if colo_slots < self._colo_requested:
                # never a silent cap: unplaced colocated replicas are a
                # capacity verdict the operator must see
                logging.getLogger(__name__).warning(
                    "colocation placed %d/%d replica slots (insufficient "
                    "fleet capacity for the rest)",
                    colo_slots, self._colo_requested,
                )

        claimed_rows = list(claims) + list(placed)
        if claimed_rows:
            claimed = np.zeros(int(np.asarray(ep.valid).shape[0]), bool)
            claimed[claimed_rows] = True
            # the auction must not re-assign a claimed provider: drop
            # them from the compatibility domain (ep.valid gates
            # compat_mask) and from any pre-assembled candidate lists
            import dataclasses as _dc

            ep = _dc.replace(
                ep, valid=jnp.asarray(np.asarray(ep.valid) & ~claimed)
            )
            if prepared is not None:
                cp = prepared.cand_p
                masked = (cp >= 0) & claimed[np.maximum(cp, 0)]
                prepared.cand_p = np.where(masked, -1, cp)
                self._claim_rows_now = masked.any(axis=1)

        # ---- phase 1: bounded tasks -> replica slots -> auction
        if slot_task:
            if cached_path:
                kernel_used = "sparse_topk"
                t4p = self._solve_slots_cached(
                    prepared, tasks, bounded, slot_range
                )
                warm_used = self._last_warm_used
                warm_seeded = self._last_warm_seeded
            elif use_sparse:
                kernel_used = "sparse_topk"
                er = self.encoder.encode_requirements(
                    [req_by_task[i] for i in slot_task],
                    priorities=[prio[i] for i in slot_task],
                    pad_to=s_bucket,
                )
                price0 = np.zeros(p_bucket, np.float32)
                p4s0 = np.full(s_bucket, -1, np.int32)
                addrs = idx_addrs
                if self.warm_start:
                    get_price = self._warm_price_by_addr.get
                    price0[:P] = np.fromiter(
                        (get_price(a, 0.0) for a in addrs), np.float32, count=P
                    )
                    warm_seeded = self._seed_slots(
                        p4s0, {a: i for i, a in enumerate(addrs)},
                        tasks, bounded, slot_range,
                    )
                warm_used = self._warm_gate(warm_seeded)
                t4p, price = self._bounded_t4p_sparse(
                    ep, er, price0, p4s0, warm=warm_used
                )
                t4p = t4p[:P]
                if self.warm_start:
                    self._warm_price_by_addr = dict(
                        zip(addrs, np.asarray(price[:P], np.float64).tolist())
                    )
            else:
                if self._jax_engine:
                    kernel_used = "jax_arena"
                elif not self.native_fallback:
                    kernel_used = "dense_auction"
                elif self.native_engine == "sinkhorn-mt":
                    kernel_used = "native_cpu_sinkhorn_mt"
                elif self.native_engine == "native-mt":
                    kernel_used = "native_cpu_mt"
                else:
                    kernel_used = "native_cpu"
                er = self.encoder.encode_requirements(
                    [req_by_task[i] for i in slot_task],
                    priorities=[prio[i] for i in slot_task],
                    pad_to=s_bucket,
                )
                t4p = self._bounded_t4p(ep, er)[:N]
            for p_idx, s_idx in enumerate(t4p):
                if s_idx >= 0 and s_idx < len(slot_task):
                    assignment[idx_addrs[p_idx]] = tasks[slot_task[s_idx]].id
                    assigned[p_idx] = True

        # ---- phase 2: remaining nodes -> cheapest compatible unbounded task
        if unbounded and not assigned.all():
            reqs = [task_requirements(tasks[i]) for i in unbounded]
            prios = [prio[i] for i in unbounded]
            t_bucket = _pow2_bucket(len(unbounded))
            er = self.encoder.encode_requirements(
                reqs, priorities=prios, pad_to=t_bucket
            )
            best = self._unbounded_best(ep, er)[:N]
            for p_idx in range(N):
                if not assigned[p_idx] and best[p_idx] >= 0 and best[p_idx] < len(unbounded):
                    assignment[idx_addrs[p_idx]] = tasks[unbounded[best[p_idx]]].id

        # store order matters for lock-free readers (tasks_for_node checks
        # _assignment_multi FIRST, then falls back to _assignment): writing
        # multi before the main map means a reader racing the swap serves
        # the previous solve wholesale — never a new-solve/old-multi mix
        # that would hand a no-longer-colocated node a stale task list
        self._assignment_multi = assignment_multi
        self._assignment, self._covered = assignment, covered
        self._solve_seq += 1
        self.last_solve_stats = {
            "nodes": P,
            "tasks": len(tasks),
            "bounded_tasks": len(bounded),
            "assigned": len(assignment),
            "colocated_slots": colo_slots,
            "colocated_unplaced": self._colo_requested - colo_slots,
            "truncated_colocate_slots": self._colo_truncated,
            "solve_ms": (time.perf_counter() - t_start) * 1e3,
            "truncated_replica_slots": truncated_slots,
            "kernel": kernel_used,  # dense_auction | sparse_topk | native_cpu
            # True when phase 1's candidates came from the task-sharded
            # mesh generator this solve (engaged, not merely requested)
            "mesh_gen_sharded": self._last_gen_sharded,
            "warm": warm_used,
            "warm_seeded_slots": warm_seeded,
            # binding-phase stall circuit breaker (ops/sparse.py): True
            # means tail quality fell to greedy cleanup this solve
            "stall_exit": self._last_stall.get("stall_exit", False),
            # the widths the sparse auction's rounds ran at, summed (its
            # device cost driver; ops/sparse.py)
            "frontier_rows": self._last_stall.get("frontier_rows", 0),
            "anti_affinity_assigned": aa_assigned,
            "truncated_aa_slots": self._aa_truncated,
            "group_assignments": len(self._group_assignment),
            "seq": self._solve_seq,  # monotone id for scrape-side dedup
            **cache_stats,
            # native-mt only: what the persistent arena reused vs recomputed
            **self._last_arena_stats,
        }
