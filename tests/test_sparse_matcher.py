"""The sparse top-K pipeline as the production matcher path.

VERDICT r2 item 2: above ``dense_cell_budget`` the live matcher must route
phase 1 through streaming candidate generation + the frontier auction
(ops/sparse.py) instead of the dense auction — locally and over the gRPC
seam — and item 3: consecutive solves must warm-start from carried prices
and the previous matching (the delta-frontier incremental path).
"""

import numpy as np
import pytest

from protocol_tpu.models import (
    ComputeSpecs,
    CpuSpecs,
    GpuSpecs,
    SchedulingConfig,
    Task,
    TaskState,
)
from protocol_tpu.sched import TpuBatchMatcher
from protocol_tpu.store import NodeStatus, OrchestratorNode, StoreContext


def mk_node(addr, gpu_model="H100", gpu_count=8):
    return OrchestratorNode(
        address=addr,
        status=NodeStatus.HEALTHY,
        compute_specs=ComputeSpecs(
            gpu=GpuSpecs(count=gpu_count, model=gpu_model, memory_mb=80000),
            cpu=CpuSpecs(cores=32),
            ram_mb=65536,
            storage_gb=1000,
        ),
    )


def mk_bounded_task(name, created_at, replicas, requirements=None):
    plugins = {"tpu_scheduler": {"replicas": [str(replicas)]}}
    if requirements:
        plugins["tpu_scheduler"]["compute_requirements"] = [requirements]
    return Task(
        name=name,
        image="img",
        created_at=created_at,
        state=TaskState.PENDING,
        scheduling_config=SchedulingConfig(plugins=plugins),
    )


def populate(ctx, n_nodes, tasks):
    for i in range(n_nodes):
        ctx.node_store.add_node(mk_node(f"0x{i:040x}"))
    for t in tasks:
        ctx.task_store.add_task(t)


class TestSparseProductionPath:
    def test_sparse_path_engages_above_budget(self):
        ctx = StoreContext.new_test()
        populate(ctx, 24, [mk_bounded_task("t", 100, replicas=16)])
        m = TpuBatchMatcher(ctx, dense_cell_budget=0, min_solve_interval=0)
        m.refresh()
        assert m.last_solve_stats["kernel"] == "sparse_topk"
        assert m.last_solve_stats["assigned"] == 16

    def test_dense_path_below_budget(self):
        ctx = StoreContext.new_test()
        populate(ctx, 24, [mk_bounded_task("t", 100, replicas=16)])
        m = TpuBatchMatcher(ctx, min_solve_interval=0)  # default budget
        m.refresh()
        assert m.last_solve_stats["kernel"] == "dense_auction"
        assert m.last_solve_stats["assigned"] == 16

    def test_sparse_dense_same_count(self):
        tasks = [
            mk_bounded_task("a", 100, replicas=10),
            mk_bounded_task("b", 200, replicas=7),
        ]
        counts = {}
        for label, budget in (("dense", 1 << 24), ("sparse", 0)):
            ctx = StoreContext.new_test()
            populate(ctx, 32, tasks)
            m = TpuBatchMatcher(
                ctx, dense_cell_budget=budget, min_solve_interval=0
            )
            m.refresh()
            counts[label] = m.last_solve_stats["assigned"]
        assert counts["dense"] == counts["sparse"] == 17

    def test_requirements_respected_on_sparse_path(self):
        ctx = StoreContext.new_test()
        for i in range(8):
            ctx.node_store.add_node(mk_node(f"0xa{i:039x}", gpu_model="H100"))
        for i in range(8):
            ctx.node_store.add_node(mk_node(f"0xb{i:039x}", gpu_model="RTX4090"))
        ctx.task_store.add_task(
            mk_bounded_task(
                "h100only", 100, replicas=12, requirements="gpu:model=H100"
            )
        )
        m = TpuBatchMatcher(ctx, dense_cell_budget=0, min_solve_interval=0)
        m.refresh()
        # only the 8 H100 nodes are eligible despite 12 requested replicas
        assert m.last_solve_stats["assigned"] == 8
        for addr in m._assignment:
            assert addr.startswith("0xa")


class TestWarmStart:
    def test_second_solve_is_warm_and_stable(self):
        ctx = StoreContext.new_test()
        populate(ctx, 24, [mk_bounded_task("t", 100, replicas=16)])
        m = TpuBatchMatcher(ctx, dense_cell_budget=0, min_solve_interval=0)
        m.refresh()
        first = dict(m._assignment)
        assert m.last_solve_stats["warm"] is False
        m.mark_dirty()
        m.refresh()
        assert m.last_solve_stats["warm"] is True
        assert m.last_solve_stats["warm_seeded_slots"] == 16
        # unchanged population: the warm solve keeps everyone seated
        assert m._assignment == first

    def test_warm_solve_after_churn_assigns_new_node(self):
        ctx = StoreContext.new_test()
        populate(ctx, 16, [mk_bounded_task("t", 100, replicas=17)])
        m = TpuBatchMatcher(ctx, dense_cell_budget=0, min_solve_interval=0)
        m.refresh()
        assert m.last_solve_stats["assigned"] == 16  # supply-capped
        ctx.node_store.add_node(mk_node("0x" + "f" * 40))
        m.mark_dirty()
        m.refresh()
        assert m.last_solve_stats["warm"] is True
        assert m.last_solve_stats["assigned"] == 17
        assert "0x" + "f" * 40 in m._assignment

    @pytest.mark.parametrize("cache", [True, False])
    def test_a_pool_with_a_queue_stays_warm(self, cache, monkeypatch):
        """More slots than usable nodes is a pool with a queue: the warm
        solve needs the reserve the previous one anchored, or it
        re-grounds with the whole cold ladder on every refresh. Both
        sparse paths carry it (the candidate cache's and the stateless
        one)."""
        from protocol_tpu.ops import sparse

        ctx = StoreContext.new_test()
        for i in range(16):
            ctx.node_store.add_node(mk_node(f"0xa{i:039x}", gpu_model="H100"))
        for i in range(8):
            ctx.node_store.add_node(mk_node(f"0xb{i:039x}", gpu_model="RTX4090"))
        ctx.task_store.add_task(mk_bounded_task(
            "h100only", 100, replicas=24, requirements="gpu:model=H100"
        ))
        m = TpuBatchMatcher(ctx, dense_cell_budget=0, min_solve_interval=0)
        m.use_candidate_cache = cache
        m.refresh()
        # 24 slots bid for the 16 nodes they can use: 8 wait
        assert m.last_solve_stats["assigned"] == 16
        reserve = m._warm_reserve
        assert reserve is not None and reserve < -10.0
        ladders = []
        cold = sparse.assign_auction_sparse_scaled
        monkeypatch.setattr(
            sparse, "assign_auction_sparse_scaled",
            lambda *a, **k: ladders.append(1) or cold(*a, **k),
        )
        first = dict(m._assignment)
        m.mark_dirty()
        m.refresh()
        assert m.last_solve_stats["warm"] is True
        assert m._warm_reserve == reserve
        assert ladders == []
        assert m._assignment == first

    def test_warm_disabled(self):
        ctx = StoreContext.new_test()
        populate(ctx, 24, [mk_bounded_task("t", 100, replicas=16)])
        m = TpuBatchMatcher(
            ctx, dense_cell_budget=0, min_solve_interval=0, warm_start=False
        )
        m.refresh()
        m.mark_dirty()
        m.refresh()
        assert m.last_solve_stats["warm"] is False

    def test_task_deleted_frees_nodes_for_remaining_task(self):
        ctx = StoreContext.new_test()
        a = mk_bounded_task("a", 100, replicas=12)
        b = mk_bounded_task("b", 200, replicas=12)
        populate(ctx, 12, [a, b])
        m = TpuBatchMatcher(ctx, dense_cell_budget=0, min_solve_interval=0)
        m.attach_observers()
        m.refresh()
        ctx.task_store.delete_task(a.id)
        m.refresh()
        assert m.last_solve_stats["assigned"] == 12
        assert set(m._assignment.values()) == {b.id}


class TestRemoteSparsePath:
    @pytest.fixture()
    def backend(self):
        from protocol_tpu.services import scheduler_grpc

        server = scheduler_grpc.serve(address="127.0.0.1:50071")
        yield "127.0.0.1:50071"
        server.stop(grace=None)

    def test_remote_topk_and_warm(self, backend):
        from protocol_tpu.services.scheduler_grpc import RemoteBatchMatcher

        ctx = StoreContext.new_test()
        populate(ctx, 24, [mk_bounded_task("t", 100, replicas=16)])
        m = RemoteBatchMatcher(
            ctx, address=backend, dense_cell_budget=0, min_solve_interval=0
        )
        m.refresh()
        assert m.last_solve_stats["kernel"] == "sparse_topk"
        assert m.last_solve_stats["assigned"] == 16
        assert m.last_solve_stats["remote_calls"] >= 1
        first = dict(m._assignment)
        m.mark_dirty()
        m.refresh()
        assert m.last_solve_stats["warm"] is True
        assert m._assignment == first

    def test_remote_matches_local(self, backend):
        from protocol_tpu.services.scheduler_grpc import RemoteBatchMatcher

        tasks = [
            mk_bounded_task("a", 100, replicas=9),
            mk_bounded_task("b", 200, replicas=6),
        ]
        ctx_l = StoreContext.new_test()
        populate(ctx_l, 20, tasks)
        local = TpuBatchMatcher(ctx_l, dense_cell_budget=0, min_solve_interval=0)
        local.refresh()

        ctx_r = StoreContext.new_test()
        populate(ctx_r, 20, tasks)
        remote = RemoteBatchMatcher(
            ctx_r, address=backend, dense_cell_budget=0, min_solve_interval=0
        )
        remote.refresh()
        assert (
            remote.last_solve_stats["assigned"]
            == local.last_solve_stats["assigned"]
            == 15
        )


class TestAntiAffinityMatcher:
    def _nodes_with_locations(self, ctx, n_per_loc=4, locs=((10.0, 10.0), (50.0, 50.0))):
        from protocol_tpu.models import NodeLocation

        idx = 0
        for lat, lon in locs:
            for _ in range(n_per_loc):
                n = mk_node(f"0x{idx:040x}")
                n.location = NodeLocation(latitude=lat, longitude=lon)
                ctx.node_store.add_node(n)
                idx += 1

    def _aa_task(self, name, created_at, replicas, mode):
        t = mk_bounded_task(name, created_at, replicas)
        t.scheduling_config.plugins["tpu_scheduler"]["anti_affinity"] = [mode]
        return t

    def test_location_spread_caps_at_distinct_locations(self):
        from protocol_tpu.models import Task
        from protocol_tpu.store import StoreContext

        ctx = StoreContext.new_test()
        self._nodes_with_locations(ctx)  # 8 nodes, 2 locations
        ctx.task_store.add_task(self._aa_task("spread", 100, 5, "location"))
        m = TpuBatchMatcher(ctx, min_solve_interval=0)
        m.refresh()
        st = m.last_solve_stats
        # only 2 distinct locations exist: 5 replicas cap at 2
        assert st["anti_affinity_assigned"] == 2
        locs = set()
        for addr in m._assignment:
            n = ctx.node_store.get_node(addr)
            locs.add((n.location.latitude, n.location.longitude))
        assert len(locs) == 2

    def test_task_spread_uses_distinct_providers(self):
        from protocol_tpu.store import StoreContext

        ctx = StoreContext.new_test()
        populate(ctx, 6, [])
        ctx.task_store.add_task(self._aa_task("spread", 100, 4, "task"))
        m = TpuBatchMatcher(ctx, min_solve_interval=0)
        m.refresh()
        assert m.last_solve_stats["anti_affinity_assigned"] == 4
        assert len(m._assignment) == 4  # distinct providers by construction

    def test_claimed_providers_excluded_from_auction(self):
        from protocol_tpu.store import StoreContext

        ctx = StoreContext.new_test()
        populate(ctx, 6, [])
        ctx.task_store.add_task(self._aa_task("spread", 100, 3, "task"))
        ctx.task_store.add_task(mk_bounded_task("auction", 200, 6))
        m = TpuBatchMatcher(ctx, min_solve_interval=0)
        m.refresh()
        st = m.last_solve_stats
        assert st["anti_affinity_assigned"] == 3
        # 6 nodes total: 3 claimed by spread, auction takes the other 3;
        # no provider double-assigned (the dict can't express it — the
        # invariant is the auction filled exactly the free nodes)
        assert st["assigned"] == 6
        by_task = {}
        for addr, tid in m._assignment.items():
            by_task.setdefault(tid, []).append(addr)
        assert sorted(len(v) for v in by_task.values()) == [3, 3]

    def test_claimed_excluded_on_cached_sparse_path(self):
        from protocol_tpu.store import StoreContext

        ctx = StoreContext.new_test()
        populate(ctx, 8, [])
        ctx.task_store.add_task(self._aa_task("spread", 100, 4, "task"))
        ctx.task_store.add_task(mk_bounded_task("auction", 200, 8))
        m = TpuBatchMatcher(ctx, min_solve_interval=0, dense_cell_budget=0)
        m.refresh()
        st = m.last_solve_stats
        assert st["kernel"] == "sparse_topk"
        assert st["anti_affinity_assigned"] == 4
        assert st["assigned"] == 8
        # warm second solve stays consistent
        m.mark_dirty()
        m.refresh()
        assert m.last_solve_stats["assigned"] == 8

    def test_invalid_mode_rejected(self):
        import pytest

        from protocol_tpu.sched.tpu_backend import validate_tpu_scheduler_config

        t = self._aa_task("bad", 100, 2, "rack")
        with pytest.raises(ValueError):
            validate_tpu_scheduler_config(t)


class TestMeshMatcher:
    """use_mesh=True shards phase 1's candidate generation over the
    virtual 8-device mesh; the solve is the single-device one, so the
    plan does not depend on the switch."""

    def test_mesh_solve_seats_all_replicas_and_warms(self):
        ctx = StoreContext.new_test()
        n = 64
        populate(ctx, n, [
            mk_bounded_task("a", 1.0, 24, "gpu:count=8;gpu:model=H100"),
            mk_bounded_task("b", 2.0, 24, "gpu:count=8;gpu:model=H100"),
        ])
        m = TpuBatchMatcher(
            ctx, min_solve_interval=0.0, dense_cell_budget=1,
            use_mesh=True,
        )
        assert m._mesh is not None  # conftest provides 8 virtual devices
        m.mark_dirty()
        m._ensure_fresh()
        s = m.last_solve_stats
        assert s["kernel"] == "sparse_topk"
        assert s["assigned"] == 48  # every replica of both tasks seated
        # second solve warm-starts (seeded from the first)
        m.mark_dirty()
        m._ensure_fresh()
        assert m.last_solve_stats["warm"] is True
        assert m.last_solve_stats["assigned"] == 48

    def test_mesh_solve_reports_frontier_rows(self):
        """The one driver fills the solve's cost driver under a mesh
        too (the mesh twin kept no count of its rows)."""
        ctx = StoreContext.new_test()
        populate(ctx, 64, [
            mk_bounded_task("a", 1.0, 24, "gpu:count=8;gpu:model=H100"),
        ])
        m = TpuBatchMatcher(
            ctx, min_solve_interval=0.0, dense_cell_budget=1, use_mesh=True,
        )
        m.mark_dirty()
        m._ensure_fresh()
        assert m.last_solve_stats["kernel"] == "sparse_topk"
        assert m.last_solve_stats["frontier_rows"] > 0

    def test_mesh_plan_matches_unsharded(self):
        def solve(use_mesh):
            ctx = StoreContext.new_test()
            populate(ctx, 96, [
                mk_bounded_task("a", 1.0, 40, "gpu:count=8;gpu:model=H100"),
            ])
            m = TpuBatchMatcher(
                ctx, min_solve_interval=0.0, dense_cell_budget=1,
                use_mesh=use_mesh,
            )
            m.mark_dirty()
            m._ensure_fresh()
            assert (m._mesh is not None) is use_mesh
            assert m.last_solve_stats["assigned"] == 40
            return sorted(m._assignment)  # one task: the seated nodes

        assert solve(True) == solve(False)


    def test_mesh_wire_path_shards_generation(self):
        """warm_start=False disables the candidate cache, sending the
        solve down the wire path — with a mesh, candidate GENERATION
        itself shards (candidates_topk_bidir_sharded; bit-identical to
        the single-device generator, so the plans are equal)."""
        def solve(use_mesh):
            ctx = StoreContext.new_test()
            populate(ctx, 96, [
                mk_bounded_task("a", 1.0, 40, "gpu:count=8;gpu:model=H100"),
            ])
            m = TpuBatchMatcher(
                ctx, min_solve_interval=0.0, dense_cell_budget=1,
                use_mesh=use_mesh, warm_start=False,
            )
            m.mark_dirty()
            m._ensure_fresh()
            s = m.last_solve_stats
            assert s["kernel"] == "sparse_topk"
            assert s["mesh_gen_sharded"] is use_mesh
            assert s["assigned"] == 40
            return sorted(m._assignment)  # one task: the seated nodes

        assert solve(True) == solve(False)


class TestWarmRetirementInvalidation:
    """ADVICE r5 (tpu_backend warm-retirement carry): incremental churn
    updates cached candidate lists without renumbering slots, so the
    carried retirement mask used to survive with stale flags — a task
    stayed retired after a newly-feasible provider appeared, until the
    next cold solve. The CandidateCache's dirty_slots now clears exactly
    the churned rows. The carried mask is injected directly (organic
    give-up retirement needs a long price war; the kernel's own
    retirement behavior is covered by the sparse kernel tests) — what's
    under test is the carry/invalidation plumbing."""

    def _spy_retired0(self, m, captured):
        orig = m._sparse_solve

        def spy(*args, **kwargs):
            captured.append(kwargs.get("retired0"))
            return orig(*args, **kwargs)

        m._sparse_solve = spy

    def _cold_solved_matcher(self):
        ctx = StoreContext.new_test()
        # demand 4 replicas on a 2-node fleet: two slots stay unseated
        populate(ctx, 2, [mk_bounded_task("t", 100, replicas=4)])
        m = TpuBatchMatcher(ctx, dense_cell_budget=0, min_solve_interval=0)
        m.refresh()
        assert m.last_solve_stats["assigned"] == 2
        return ctx, m

    def test_unchanged_population_keeps_carried_retirement(self):
        ctx, m = self._cold_solved_matcher()
        m._warm_retired = np.ones_like(np.asarray(m._warm_retired))
        captured = []
        self._spy_retired0(m, captured)
        m.mark_dirty()
        m.refresh()
        assert m.last_solve_stats["warm"] is True
        # clean population: the carry is the whole point — flags survive
        retired0 = captured[0]
        assert retired0 is not None
        assert bool(np.asarray(retired0).all())

    def test_churn_clears_carried_retirement(self):
        ctx, m = self._cold_solved_matcher()
        m._warm_retired = np.ones_like(np.asarray(m._warm_retired))
        # a new node churns into every slot's candidate list (k > fleet)
        ctx.node_store.add_node(mk_node("0xnew"))
        captured = []
        self._spy_retired0(m, captured)
        m.mark_dirty()
        m.refresh()
        assert m.last_solve_stats["warm"] is True
        # the mask handed to the warm kernel must not carry flags over
        # slots whose candidates changed (here: all of them) — pre-fix,
        # slot_fp matched and the stale mask rode through unchanged
        assert len(captured) == 1
        retired0 = captured[0]
        assert retired0 is None or not bool(np.asarray(retired0).any())
        # and the newly-feasible node is assigned THIS solve, not after
        # the next cold one
        assert m.last_solve_stats["assigned"] == 3
