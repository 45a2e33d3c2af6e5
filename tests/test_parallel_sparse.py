"""Task-sharded sparse auction on the virtual 8-device CPU mesh: Jacobi
parity with the single-device kernel and feasibility under contention."""

import numpy as np
import pytest

import jax.numpy as jnp

from protocol_tpu.ops.cost import INFEASIBLE
from protocol_tpu.ops.sparse import assign_auction_sparse
from protocol_tpu.parallel import assign_auction_sparse_sharded, make_mesh

from tests.test_assign import check_feasible, random_cost


def build_candidates(cost: np.ndarray, k: int):
    order = np.argsort(cost, axis=0, kind="stable").T[:, :k]
    cand_c = np.take_along_axis(cost.T, order, axis=1).astype(np.float32)
    cand_p = np.where(cand_c < INFEASIBLE * 0.5, order.astype(np.int32), -1)
    return cand_p, cand_c


@pytest.mark.parametrize("seed,P,T,D", [(0, 48, 64, 8), (1, 64, 64, 4), (2, 32, 96, 2)])
def test_sharded_jacobi_parity(seed, P, T, D):
    rng = np.random.default_rng(seed)
    cost = random_cost(rng, P, T, p_infeasible=0.15)
    cand_p, cand_c = build_candidates(cost, k=min(16, P))
    mesh = make_mesh(D)
    # full frontier + no retirement = Jacobi schedule on both sides
    res_sharded = assign_auction_sparse_sharded(
        jnp.asarray(cand_p), jnp.asarray(cand_c), num_providers=P, mesh=mesh,
        eps=0.05, max_iters=4000, frontier=T, retire=False,
    )
    res_single = assign_auction_sparse(
        jnp.asarray(cand_p), jnp.asarray(cand_c), num_providers=P,
        eps=0.05, max_iters=4000, frontier=T, retire=False,
    )
    check_feasible(res_sharded, cost)
    np.testing.assert_array_equal(
        np.asarray(res_sharded.provider_for_task),
        np.asarray(res_single.provider_for_task),
    )


def test_sharded_contention_with_retirement():
    rng = np.random.default_rng(5)
    cost = random_cost(rng, 16, 64, p_infeasible=0.2)  # oversubscribed
    cand_p, cand_c = build_candidates(cost, k=16)
    mesh = make_mesh(8)
    res = assign_auction_sparse_sharded(
        jnp.asarray(cand_p), jnp.asarray(cand_c), num_providers=16, mesh=mesh,
        eps=0.05,
    )
    p4t = check_feasible(res, cost)
    assert (p4t >= 0).sum() > 0


def test_divisibility_enforced():
    mesh = make_mesh(8)
    with pytest.raises(ValueError):
        assign_auction_sparse_sharded(
            jnp.zeros((10, 4), jnp.int32), jnp.zeros((10, 4)), 4, mesh
        )


class TestScaledSharded:
    """The eps-scaling ladder + warm solve over the mesh (VERDICT r3
    item 3's sharded-parity leg): same phase discipline as the
    single-device twins, exact parity under the Jacobi schedule."""

    @pytest.mark.parametrize("seed,P,T,D", [(0, 64, 64, 8), (3, 96, 128, 4)])
    def test_scaled_jacobi_parity_with_single_device(self, seed, P, T, D):
        from protocol_tpu.ops.sparse import assign_auction_sparse_scaled
        from protocol_tpu.parallel import assign_auction_sparse_scaled_sharded

        rng = np.random.default_rng(seed)
        cost = random_cost(rng, P, T, p_infeasible=0.1)
        cand_p, cand_c = build_candidates(cost, k=min(16, P))
        mesh = make_mesh(D)
        kw = dict(
            num_providers=P, eps_start=2.0, eps_end=0.02,
            max_iters_per_phase=4000, frontier=T, with_prices=True,
        )
        res_sh, price_sh = assign_auction_sparse_scaled_sharded(
            jnp.asarray(cand_p), jnp.asarray(cand_c), mesh=mesh, **kw
        )
        # frontier_ladder off: exact-Jacobi comparison against the
        # fixed-frontier mesh kernel
        res_sg, price_sg = assign_auction_sparse_scaled(
            jnp.asarray(cand_p), jnp.asarray(cand_c),
            frontier_ladder=False, **kw
        )
        check_feasible(res_sh, cost)
        np.testing.assert_array_equal(
            np.asarray(res_sh.provider_for_task),
            np.asarray(res_sg.provider_for_task),
        )
        # float dust of the mesh kernel's bid sums, as before; absolute
        # too since the reverse pass brings prices down by the floor
        # (P > T here), which leaves the dust and shrinks the prices
        np.testing.assert_allclose(
            np.asarray(price_sh), np.asarray(price_sg), rtol=1e-6, atol=2e-5
        )

    def test_warm_jacobi_parity_with_single_device(self):
        from protocol_tpu.ops.sparse import (
            assign_auction_sparse_scaled,
            assign_auction_sparse_warm,
        )
        from protocol_tpu.parallel import assign_auction_sparse_warm_sharded

        rng = np.random.default_rng(7)
        P = T = 64
        cost = random_cost(rng, P, T, p_infeasible=0.1)
        cand_p, cand_c = build_candidates(cost, k=16)
        mesh = make_mesh(8)
        res0, price0 = assign_auction_sparse_scaled(
            jnp.asarray(cand_p), jnp.asarray(cand_c), num_providers=P,
            with_prices=True, frontier=T,
        )
        # 10% churn: first tasks re-open
        p4t0 = jnp.asarray(np.asarray(res0.provider_for_task)).at[:6].set(-1)
        kw = dict(
            num_providers=P, price0=price0, p4t0=p4t0,
            eps=0.02, max_iters=20000, frontier=T,
        )
        res_sh, price_sh = assign_auction_sparse_warm_sharded(
            jnp.asarray(cand_p), jnp.asarray(cand_c), mesh=mesh, **kw
        )
        res_sg, price_sg = assign_auction_sparse_warm(
            jnp.asarray(cand_p), jnp.asarray(cand_c),
            frontier_ladder=False, **kw
        )
        check_feasible(res_sh, cost)
        np.testing.assert_array_equal(
            np.asarray(res_sh.provider_for_task),
            np.asarray(res_sg.provider_for_task),
        )
        np.testing.assert_allclose(
            np.asarray(price_sh), np.asarray(price_sg), rtol=1e-6
        )

    def test_sharded_completeness_with_bidir_candidates(self):
        """Stage-B completeness composes with the mesh: bidir candidates +
        the sharded ladder assign every task at a production-sparse shape
        (the single-device 65k twin of this test is bench_scaling B2)."""
        from tests.test_sparse import TestBidirCandidates
        from protocol_tpu.ops.sparse import candidates_topk_bidir
        from protocol_tpu.parallel import assign_auction_sparse_scaled_sharded

        P = T = 1024
        ep, er = TestBidirCandidates._priced_marketplace(P, T)
        bp, bc = candidates_topk_bidir(
            ep, er, k=8, tile=256, reverse_r=8, extra=16
        )
        mesh = make_mesh(8)
        res = assign_auction_sparse_scaled_sharded(
            bp, bc, num_providers=P, mesh=mesh, frontier=1024,
        )
        p4t = np.asarray(res.provider_for_task)
        assigned = int((p4t >= 0).sum())
        assert assigned >= T * 0.99, f"sharded bidir assigned {assigned}/{T}"
        pos = p4t[p4t >= 0]
        assert np.unique(pos).size == pos.size

    def test_adaptive_ladder_sharded_matches_quality(self):
        """frontier_ladder=True on the mesh: same assignment count as the
        fixed-frontier schedule (a different, equally valid auction
        order), full completeness on the bidir graph."""
        from tests.test_sparse import TestBidirCandidates
        from protocol_tpu.ops.sparse import candidates_topk_bidir
        from protocol_tpu.parallel import assign_auction_sparse_scaled_sharded

        P = T = 1024
        ep, er = TestBidirCandidates._priced_marketplace(P, T)
        bp, bc = candidates_topk_bidir(
            ep, er, k=8, tile=256, reverse_r=8, extra=16
        )
        mesh = make_mesh(8)
        counts = {}
        for ladder in (False, True):
            res = assign_auction_sparse_scaled_sharded(
                bp, bc, num_providers=P, mesh=mesh, frontier=1024,
                frontier_ladder=ladder,
            )
            p4t = np.asarray(res.provider_for_task)
            counts[ladder] = int((p4t >= 0).sum())
            pos = p4t[p4t >= 0]
            assert np.unique(pos).size == pos.size
        assert counts[True] >= T * 0.99
        assert counts[True] >= counts[False] - 2


class TestShardedGeneration:
    """candidates_topk_bidir_sharded: bit-exact parity with the
    single-device generator (same global tiling, same jitter keys, same
    tile-pooled reverse contract) — the collective-free sharding of the
    measured wall-clock dominator."""

    def _marketplace(self, P, T, seed=5):
        import jax
        from tests.test_sparse import encode_random_marketplace

        ep, er = encode_random_marketplace(seed, P, T)
        return jax.tree.map(jnp.asarray, ep), jax.tree.map(jnp.asarray, er)

    @pytest.mark.parametrize(
        "P,T,D,tile,k,r,extra",
        [
            (512, 1024, 8, 64, 16, 8, 8),
            (256, 512, 4, 128, 8, 4, 4),   # rt > 1 branch (2 local tiles)
            (128, 256, 2, 128, 8, 1, 2),   # rt == 1 argmin branch
        ],
    )
    def test_bit_parity_with_single_device(self, P, T, D, tile, k, r, extra):
        from protocol_tpu.ops.cost import CostWeights
        from protocol_tpu.ops.sparse import candidates_topk_bidir
        from protocol_tpu.parallel import candidates_topk_bidir_sharded

        ep, er = self._marketplace(P, T)
        w = CostWeights()
        cp1, cc1 = candidates_topk_bidir(
            ep, er, w, k=k, tile=tile, reverse_r=r, extra=extra
        )
        cp2, cc2 = candidates_topk_bidir_sharded(
            ep, er, w, mesh=make_mesh(D), k=k, tile=tile, reverse_r=r,
            extra=extra,
        )
        np.testing.assert_array_equal(np.asarray(cp1), np.asarray(cp2))
        np.testing.assert_array_equal(np.asarray(cc1), np.asarray(cc2))

    def test_divisibility_enforced(self):
        from protocol_tpu.parallel import candidates_topk_bidir_sharded

        ep, er = self._marketplace(64, 96)  # 96 not divisible by 64-tile
        with pytest.raises(ValueError):
            candidates_topk_bidir_sharded(
                ep, er, mesh=make_mesh(8), k=8, tile=64
            )

    def test_feeds_sharded_solve_end_to_end(self):
        """The sharded pipeline composes: sharded generation -> sharded
        ladder, matching the fully single-device pipeline bit-for-bit
        under the Jacobi schedule."""
        from protocol_tpu.ops.cost import CostWeights
        from protocol_tpu.ops.sparse import (
            assign_auction_sparse_scaled,
            candidates_topk_bidir,
        )
        from protocol_tpu.parallel import (
            assign_auction_sparse_scaled_sharded,
            candidates_topk_bidir_sharded,
        )

        P = T = 512
        ep, er = self._marketplace(P, T, seed=9)
        w = CostWeights()
        mesh = make_mesh(8)
        bp_s, bc_s = candidates_topk_bidir_sharded(
            ep, er, w, mesh=mesh, k=8, tile=64, reverse_r=4, extra=8
        )
        bp_1, bc_1 = candidates_topk_bidir(
            ep, er, w, k=8, tile=64, reverse_r=4, extra=8
        )
        kw = dict(num_providers=P, frontier=T, with_prices=True)
        res_s, _ = assign_auction_sparse_scaled_sharded(
            bp_s, bc_s, mesh=mesh, **kw
        )
        res_1, _ = assign_auction_sparse_scaled(
            bp_1, bc_1, frontier_ladder=False, **kw
        )
        np.testing.assert_array_equal(
            np.asarray(res_s.provider_for_task),
            np.asarray(res_1.provider_for_task),
        )


class TestAdversarialParity:
    """VERDICT r4 item 8: the sharded-parity contract under the shapes
    that break naive SPMD ports — degenerate all-equal prices (every bid
    ties), churn mid-chain, uneven tails at several sizes, non-dividing
    mesh fallback, warm-after-rebuild."""

    def test_degenerate_all_equal_costs(self):
        """All-equal feasible costs: every round is a pure tie-break.
        Global win_task = pmin over shard-local minima must reproduce the
        single-device lowest-task-index rule exactly."""
        from protocol_tpu.ops.sparse import assign_auction_sparse

        P = T = 64
        cost = np.full((P, T), 3.0, np.float32)
        cand_p, cand_c = build_candidates(cost, k=16)
        mesh = make_mesh(8)
        res_sh = assign_auction_sparse_sharded(
            jnp.asarray(cand_p), jnp.asarray(cand_c), num_providers=P,
            mesh=mesh, eps=0.05, max_iters=4000, frontier=T, retire=False,
        )
        res_sg = assign_auction_sparse(
            jnp.asarray(cand_p), jnp.asarray(cand_c), num_providers=P,
            eps=0.05, max_iters=4000, frontier=T, retire=False,
        )
        np.testing.assert_array_equal(
            np.asarray(res_sh.provider_for_task),
            np.asarray(res_sg.provider_for_task),
        )
        # all-equal costs make every top-k window identical, so the
        # forward-only graph covers exactly k providers — the matching
        # caps there (the coverage phenomenon bidir candidates repair)
        assert int((np.asarray(res_sh.provider_for_task) >= 0).sum()) == 16

    @pytest.mark.parametrize("T_real,D", [(97, 8), (505, 8), (1000, 4)])
    def test_uneven_tail_padding(self, T_real, D):
        """Pow2/bucket padding with an uneven real tail: padded rows must
        never assign, real rows must match single-device exactly."""
        from protocol_tpu.ops.sparse import assign_auction_sparse_scaled
        from protocol_tpu.parallel import (
            assign_auction_sparse_scaled_sharded,
            pad_to_multiple,
        )

        rng = np.random.default_rng(T_real)
        P = 128
        T_pad = pad_to_multiple(T_real, D * 16)
        cost = random_cost(rng, P, T_real, p_infeasible=0.1)
        cand_p, cand_c = build_candidates(cost, k=16)
        cand_p = np.concatenate(
            [cand_p, np.full((T_pad - T_real, 16), -1, np.int32)]
        )
        cand_c = np.concatenate(
            [cand_c,
             np.full((T_pad - T_real, 16), np.float32(INFEASIBLE))]
        )
        mesh = make_mesh(D)
        kw = dict(
            num_providers=P, eps_start=2.0, eps_end=0.02,
            max_iters_per_phase=4000, frontier=T_pad,
        )
        res_sh = assign_auction_sparse_scaled_sharded(
            jnp.asarray(cand_p), jnp.asarray(cand_c), mesh=mesh, **kw
        )
        res_sg = assign_auction_sparse_scaled(
            jnp.asarray(cand_p), jnp.asarray(cand_c),
            frontier_ladder=False, **kw
        )
        got = np.asarray(res_sh.provider_for_task)
        np.testing.assert_array_equal(
            got, np.asarray(res_sg.provider_for_task)
        )
        assert not (got[T_real:] >= 0).any(), "padded tail must stay open"

    def test_non_dividing_mesh_rejected_everywhere(self):
        """Every sharded kernel must refuse a non-dividing T loudly (the
        matcher's fallback path depends on this contract, and a silent
        mis-shard would corrupt the matching)."""
        from protocol_tpu.parallel import (
            assign_auction_sparse_scaled_sharded,
            assign_auction_sparse_warm_sharded,
        )

        mesh = make_mesh(8)
        cp = jnp.zeros((12, 4), jnp.int32)
        cc = jnp.zeros((12, 4), jnp.float32)
        with pytest.raises(ValueError):
            assign_auction_sparse_scaled_sharded(cp, cc, 4, mesh)
        with pytest.raises(ValueError):
            assign_auction_sparse_warm_sharded(
                cp, cc, 4, mesh,
                price0=jnp.zeros(4), p4t0=jnp.full(12, -1, jnp.int32),
            )

    def test_warm_chain_with_churn_and_rebuild(self):
        """A 4-solve chain on the mesh: cold -> warm(churn) ->
        REBUILD (new candidate structure, seeds re-expressed, prices
        carried, retirement dropped) -> warm again. Every step must match
        the single-device twin bit-for-bit."""
        from protocol_tpu.ops.sparse import (
            assign_auction_sparse_scaled,
            assign_auction_sparse_warm,
        )
        from protocol_tpu.parallel import (
            assign_auction_sparse_scaled_sharded,
            assign_auction_sparse_warm_sharded,
        )

        rng = np.random.default_rng(11)
        P = T = 64
        cost = random_cost(rng, P, T, p_infeasible=0.1)
        cand_p, cand_c = build_candidates(cost, k=16)
        mesh = make_mesh(8)
        kw0 = dict(
            num_providers=P, eps_start=2.0, eps_end=0.02,
            max_iters_per_phase=4000, frontier=T, with_state=True,
        )
        res_sh, price_sh, ret_sh = assign_auction_sparse_scaled_sharded(
            jnp.asarray(cand_p), jnp.asarray(cand_c), mesh=mesh, **kw0
        )
        res_sg, price_sg, ret_sg = assign_auction_sparse_scaled(
            jnp.asarray(cand_p), jnp.asarray(cand_c),
            frontier_ladder=False, **kw0
        )
        np.testing.assert_array_equal(
            np.asarray(ret_sh), np.asarray(ret_sg)
        )

        # warm 1: 10% churn, retirement carried
        p4t1 = jnp.asarray(res_sh.provider_for_task).at[:6].set(-1)
        kw1 = dict(
            num_providers=P, price0=price_sh, p4t0=p4t1, eps=0.02,
            max_iters=20000, frontier=T, retired0=ret_sh, with_state=True,
        )
        w_sh, wp_sh, wret_sh = assign_auction_sparse_warm_sharded(
            jnp.asarray(cand_p), jnp.asarray(cand_c), mesh=mesh, **kw1
        )
        w_sg, wp_sg, wret_sg = assign_auction_sparse_warm(
            jnp.asarray(cand_p), jnp.asarray(cand_c),
            frontier_ladder=False, **kw1
        )
        np.testing.assert_array_equal(
            np.asarray(w_sh.provider_for_task),
            np.asarray(w_sg.provider_for_task),
        )
        np.testing.assert_array_equal(
            np.asarray(wret_sh), np.asarray(wret_sg)
        )

        # rebuild: costs drift, candidate structure regenerated; carried
        # prices survive, the retirement mask must NOT (stale w.r.t. the
        # new graph) — the caller drops it, kernels treat seeds as fresh
        cost2 = cost + rng.uniform(0, 0.2, cost.shape).astype(np.float32)
        cost2[cost >= INFEASIBLE * 0.5] = INFEASIBLE
        cand_p2, cand_c2 = build_candidates(cost2, k=16)
        p4t2 = jnp.asarray(w_sh.provider_for_task)
        kw2 = dict(
            num_providers=P, price0=wp_sh, p4t0=p4t2, eps=0.02,
            max_iters=20000, frontier=T,
        )
        f_sh, _ = assign_auction_sparse_warm_sharded(
            jnp.asarray(cand_p2), jnp.asarray(cand_c2), mesh=mesh, **kw2
        )
        f_sg, _ = assign_auction_sparse_warm(
            jnp.asarray(cand_p2), jnp.asarray(cand_c2),
            frontier_ladder=False, **kw2
        )
        np.testing.assert_array_equal(
            np.asarray(f_sh.provider_for_task),
            np.asarray(f_sg.provider_for_task),
        )
        check_feasible(f_sh, cost2)


class TestCandidateRepair:
    """repair_topk_bidir_sharded: the warm-path repaired==regen oracle
    contract — a churn-masked repair of the persistent parts lands the
    bit-identical structure a from-scratch bidirectional pass produces
    on the current features, at every device count (ISSUE 18)."""

    def _marketplace(self, P, T, seed=5):
        import jax
        from tests.test_sparse import encode_random_marketplace

        ep, er = encode_random_marketplace(seed, P, T)
        return jax.tree.map(jnp.asarray, ep), jax.tree.map(jnp.asarray, er)

    @staticmethod
    def _bump_price(ep, rows, delta=0.25):
        import dataclasses

        price = np.array(ep.price, copy=True)
        price[list(rows)] += delta
        return dataclasses.replace(ep, price=jnp.asarray(price))

    @staticmethod
    def _bump_req(er, rows, delta=1.0):
        import dataclasses

        cc = np.array(er.cpu_cores, copy=True)
        cc[list(rows)] = np.maximum(1.0, cc[list(rows)] + delta)
        return dataclasses.replace(er, cpu_cores=jnp.asarray(cc))

    def _full(self, ep, er, w, mesh, k, tile, r, extra):
        from protocol_tpu.ops.sparse import (
            candidates_topk_reverse,
            merge_reverse_candidates,
        )
        from protocol_tpu.parallel import candidates_topk_bidir_sharded

        if mesh is None:
            fp, fc, rt_, rc, pt, pc = candidates_topk_reverse(
                ep, er, w, k=k, tile=tile, reverse_r=r, with_pools=True
            )
            mp, mc = merge_reverse_candidates(fp, fc, rt_, rc, extra=extra)
            return [np.asarray(a) for a in (mp, mc, fp, fc, pt, pc)]
        return [
            np.asarray(a)
            for a in candidates_topk_bidir_sharded(
                ep, er, w, mesh=mesh, k=k, tile=tile, reverse_r=r,
                extra=extra, with_parts=True,
            )
        ]

    @pytest.mark.parametrize("D", [None, 1, 4])
    @pytest.mark.parametrize(
        "dirty_p,dirty_t",
        [
            ([5, 17, 40], []),            # provider-side churn only
            ([], [3, 60, 100, 101]),      # requirement-side churn only
            ([2, 90], [0, 127]),          # both sides
            ([], []),                     # empty event: repair is a no-op
        ],
    )
    def test_repair_matches_regen_bit_for_bit(self, D, dirty_p, dirty_t):
        from protocol_tpu.ops.cost import CostWeights
        from protocol_tpu.parallel.sparse import repair_topk_bidir_sharded

        P, T, k, tile, r, extra = 96, 128, 16, 16, 8, 8
        mesh = None if D is None else make_mesh(D)
        ep, er = self._marketplace(P, T)
        w = CostWeights()
        _, _, fwd_p, fwd_c, pool_t, pool_c = self._full(
            ep, er, w, mesh, k, tile, r, extra
        )
        ep2 = self._bump_price(ep, dirty_p) if dirty_p else ep
        er2 = self._bump_req(er, dirty_t) if dirty_t else er
        oracle = self._full(ep2, er2, w, mesh, k, tile, r, extra)
        got = repair_topk_bidir_sharded(
            ep2, er2, w, fwd_p=fwd_p, fwd_c=fwd_c, pool_t=pool_t,
            pool_c=pool_c, dirty_p=np.asarray(dirty_p, np.int64),
            dirty_t=np.asarray(dirty_t, np.int64), reverse_r=r,
            mesh=mesh, tile=tile, extra=extra,
        )
        stats = got[-1]
        order = ["cand_p", "cand_c", "fwd_p", "fwd_c", "pool_t", "pool_c"]
        for name, g, o in zip(order, got[:6], oracle):
            np.testing.assert_array_equal(g, o, err_msg=name)
        if not dirty_p and not dirty_t:
            assert stats["repair_rows"] == 0
            assert stats["repair_providers"] == 0
            assert stats["repair_blocks"] == 0
            assert stats["visited_cells_frac"] == 0.0
        else:
            # repair scope is honest churn-bounded work, not a rebuild
            assert stats["visited_cells_frac"] < 1.0

    def test_repair_scope_is_churn_bounded(self):
        """Requirement-side churn (the heartbeat steady state) repairs
        O(churned rows): the forward scope is exactly the dirty tasks
        and the visited-cell fraction stays near churn/T."""
        from protocol_tpu.ops.cost import CostWeights
        from protocol_tpu.parallel.sparse import repair_topk_bidir_sharded

        P, T, k, tile, r, extra = 96, 256, 16, 16, 8, 8
        ep, er = self._marketplace(P, T)
        w = CostWeights()
        _, _, fwd_p, fwd_c, pool_t, pool_c = self._full(
            ep, er, w, None, k, tile, r, extra
        )
        dirty_t = np.asarray([10, 77], np.int64)
        er2 = self._bump_req(er, dirty_t)
        *_, stats = repair_topk_bidir_sharded(
            ep, er2, w, fwd_p=fwd_p, fwd_c=fwd_c, pool_t=pool_t,
            pool_c=pool_c, dirty_p=np.zeros(0, np.int64),
            dirty_t=dirty_t, reverse_r=r, mesh=None, tile=tile,
            extra=extra,
        )
        assert stats["repair_rows"] == dirty_t.size
        assert stats["repair_enter_rows"] == 0  # no dirty providers
        assert stats["visited_cells_frac"] < 0.5

    def test_rt_one_and_clamped_k_branches(self):
        """The argmin reverse branch (rt == 1: many tiles) and k
        clamped at P both honor the oracle contract."""
        from protocol_tpu.ops.cost import CostWeights
        from protocol_tpu.parallel.sparse import repair_topk_bidir_sharded

        P, T, k, tile, r, extra = 24, 256, 64, 16, 4, 8
        ep, er = self._marketplace(P, T, seed=11)
        w = CostWeights()
        kk = min(k, P)
        _, _, fwd_p, fwd_c, pool_t, pool_c = self._full(
            ep, er, w, None, kk, tile, r, extra
        )
        ep2 = self._bump_price(ep, [1, 20])
        er2 = self._bump_req(er, [4, 200])
        oracle = self._full(ep2, er2, w, None, kk, tile, r, extra)
        got = repair_topk_bidir_sharded(
            ep2, er2, w, fwd_p=fwd_p, fwd_c=fwd_c, pool_t=pool_t,
            pool_c=pool_c, dirty_p=np.asarray([1, 20], np.int64),
            dirty_t=np.asarray([4, 200], np.int64), reverse_r=r,
            mesh=None, tile=tile, extra=extra,
        )
        for g, o in zip(got[:6], oracle):
            np.testing.assert_array_equal(g, o)
