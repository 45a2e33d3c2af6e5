"""Sparse top-K pipeline tests: candidate generation vs brute force, full-K
parity with the dense auction, restricted-graph quality vs scipy optimum."""

from functools import partial

import numpy as np
import pytest
from scipy.optimize import linear_sum_assignment

import jax
import jax.numpy as jnp

from protocol_tpu.ops.assign import assign_auction
from protocol_tpu.ops.cost import INFEASIBLE, CostWeights, cost_matrix
from protocol_tpu.ops.sparse import assign_auction_sparse, assign_topk, candidates_topk
from protocol_tpu.ops.encoding import FeatureEncoder, compat_mask

from tests.test_assign import check_feasible, matching_cost, random_cost
from tests.test_encoding import random_requirements, random_specs


def encode_random_marketplace(seed, P, T):
    import random

    rng = random.Random(seed)
    enc = FeatureEncoder()
    ep = enc.encode_providers([random_specs(rng) for _ in range(P)])
    er = enc.encode_requirements([random_requirements(rng) for _ in range(T)])
    return ep, er


def sorted_candidates(cost: np.ndarray, k: int | None = None):
    """Each task's k cheapest providers of a [P, T] cost matrix, sorted
    by cost as top-k would give them (-1 where infeasible)."""
    order = np.argsort(cost, axis=0, kind="stable").T[:, :k]
    cand_c = np.take_along_axis(cost.T, order, axis=1).astype(np.float32)
    cand_p = np.where(cand_c < INFEASIBLE * 0.5, order.astype(np.int32), -1)
    return cand_p, cand_c


def jittered_cost(cost: np.ndarray) -> np.ndarray:
    """Replicates the kernel's deterministic tie-breaking jitter."""
    P, T = cost.shape
    p = np.arange(P, dtype=np.uint32)[:, None]
    t = np.arange(T, dtype=np.uint32)[None, :]
    h = (p * np.uint32(2654435761)) ^ (t * np.uint32(40503))
    jit = (h & np.uint32(1023)).astype(np.float32) * np.float32(1e-7)
    return np.where(cost < INFEASIBLE * 0.5, cost + jit, cost).astype(np.float32)


class TestCandidates:
    def test_matches_bruteforce_topk(self):
        ep, er = encode_random_marketplace(0, 32, 16)
        cand_p, cand_c = candidates_topk(ep, er, k=8, tile=8)
        cost = jittered_cost(np.asarray(cost_matrix(ep, er, CostWeights())[0]))
        for t in range(16):
            order = np.argsort(cost[:, t], kind="stable")[:8]
            expected = [int(p) if cost[p, t] < INFEASIBLE * 0.5 else -1 for p in order]
            got = list(np.asarray(cand_p)[t])
            assert got == expected, f"task {t}: {got} vs {expected}"
            feas = [i for i, p in enumerate(expected) if p >= 0]
            np.testing.assert_allclose(
                np.asarray(cand_c)[t][feas], cost[order, t][feas], rtol=1e-6
            )

    def test_identical_providers_not_capped_at_k(self):
        """Degenerate marketplace: N identical providers must not collapse
        every task's candidate list to the same k entries."""
        from protocol_tpu.models.node import ComputeRequirements, ComputeSpecs, CpuSpecs, GpuSpecs
        from protocol_tpu.ops.sparse import assign_topk

        enc = FeatureEncoder()
        spec = ComputeSpecs(
            gpu=GpuSpecs(count=8, model="H100", memory_mb=80000),
            cpu=CpuSpecs(cores=32), ram_mb=65536, storage_gb=1000,
        )
        ep = enc.encode_providers([spec] * 16)
        er = enc.encode_requirements(
            [ComputeRequirements.parse("gpu:count=8;gpu:model=H100")] * 8
        )
        res = assign_topk(ep, er, k=4, tile=8, eps=0.01)
        assert int(np.asarray(res.provider_for_task >= 0).sum()) == 8

    def test_tile_divisibility_enforced(self):
        ep, er = encode_random_marketplace(1, 8, 10)
        with pytest.raises(ValueError):
            candidates_topk(ep, er, k=4, tile=4)

    def test_approx_recall_selection(self):
        """approx_recall routes selection through lax.approx_max_k (the
        TPU-native PartialReduce targeting the measured stage-A top_k
        bottleneck). On CPU the lowering is exact, so the candidate sets
        must match lax.top_k's bit-for-bit; the real win is measured
        on-chip (SCALING.md)."""
        import jax

        if jax.devices()[0].platform != "cpu":
            pytest.skip("set-equality only holds on the exact CPU lowering")
        ep, er = encode_random_marketplace(7, 64, 32)
        exact_p, exact_c = candidates_topk(ep, er, k=8, tile=8)
        approx_p, approx_c = candidates_topk(
            ep, er, k=8, tile=8, approx_recall=0.95
        )
        # same candidate SETS per task (row order may differ between the
        # two reduction algorithms)
        for t in range(32):
            assert set(np.asarray(exact_p)[t].tolist()) == set(
                np.asarray(approx_p)[t].tolist()
            ), f"task {t}"
        # feasibility downstream: the approx sets drive a full solve
        res = assign_auction_sparse(
            approx_p, approx_c, num_providers=64, eps=0.05, max_iters=3000
        )
        assert int(np.asarray(res.provider_for_task >= 0).sum()) > 0


class TestSparseAuction:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_full_k_parity_with_dense(self, seed):
        rng = np.random.default_rng(seed)
        P, T = 32, 32
        cost = random_cost(rng, P, T, p_infeasible=0.2)
        # build full candidate lists (k = P) sorted by cost, as topk would
        order = np.argsort(cost, axis=0, kind="stable").T  # [T, P]
        cand_c = np.take_along_axis(cost.T, order, axis=1).astype(np.float32)
        cand_p = np.where(cand_c < INFEASIBLE * 0.5, order.astype(np.int32), -1)

        # frontier >= T + no retirement = the dense Jacobi schedule exactly
        res_sparse = assign_auction_sparse(
            jnp.asarray(cand_p), jnp.asarray(cand_c), num_providers=P,
            eps=0.05, max_iters=5000, frontier=T, retire=False,
        )
        res_dense = assign_auction(jnp.asarray(cost), eps=0.05, max_iters=5000)
        check_feasible(res_sparse, cost)
        np.testing.assert_array_equal(
            np.asarray(res_sparse.provider_for_task),
            np.asarray(res_dense.provider_for_task),
        )

    def test_degenerate_all_equal_costs(self):
        """All-equal feasible costs: every round is a pure tie-break,
        and the lowest task index wins each provider. Every top-k window
        is the same k providers, so the matching caps at k (the coverage
        phenomenon bidir candidates repair) and goes to tasks 0..k-1."""
        P = T = 64
        k = 16
        cand_p = np.tile(np.arange(k, dtype=np.int32), (T, 1))
        cand_c = np.full((T, k), 3.0, np.float32)
        res = assign_auction_sparse(
            jnp.asarray(cand_p), jnp.asarray(cand_c), num_providers=P,
            eps=0.05, max_iters=4000, frontier=T, retire=False,
        )
        p4t = np.asarray(res.provider_for_task)
        seated = np.flatnonzero(p4t >= 0)
        assert seated.size == k
        assert sorted(p4t[seated]) == list(range(k))  # injective, in the lists
        np.testing.assert_array_equal(seated, np.arange(k))

    @pytest.mark.parametrize("seed", [0, 1])
    def test_restricted_quality(self, seed):
        """k=16 of 64 providers: matching cost within a few % of optimal."""
        rng = np.random.default_rng(seed)
        n = 64
        cost = rng.uniform(0.0, 10.0, size=(n, n)).astype(np.float32)
        order = np.argsort(cost, axis=0, kind="stable").T[:, :16]
        cand_c = np.take_along_axis(cost.T, order, axis=1).astype(np.float32)
        cand_p = order.astype(np.int32)
        res = assign_auction_sparse(
            jnp.asarray(cand_p), jnp.asarray(cand_c), num_providers=n,
            eps=0.01, max_iters=5000, frontier=16,
        )
        p4t = check_feasible(res, cost)
        assert (p4t >= 0).sum() >= n - 2  # near-perfect matching on 25% graph
        ri, ci = linear_sum_assignment(cost)
        opt = cost[ri, ci].sum()
        got = matching_cost(cost, p4t)
        assert got <= opt * 1.10 + n * 0.011, f"sparse {got} vs optimal {opt}"


class TestScaledAuction:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_near_optimal(self, seed):
        from protocol_tpu.ops.sparse import assign_auction_sparse_scaled

        rng = np.random.default_rng(seed)
        n = 64
        cost = rng.uniform(0, 10, size=(n, n)).astype(np.float32)
        order = np.argsort(cost, axis=0, kind="stable").T
        cand_c = np.take_along_axis(cost.T, order, axis=1).astype(np.float32)
        cand_p = order.astype(np.int32)
        res = assign_auction_sparse_scaled(
            jnp.asarray(cand_p), jnp.asarray(cand_c), num_providers=n,
            eps_end=0.005,
        )
        p4t = check_feasible(res, cost)
        assert (p4t >= 0).all()
        ri, ci = linear_sum_assignment(cost)
        opt = cost[ri, ci].sum()
        got = matching_cost(cost, p4t)
        assert got <= opt + n * 0.006, f"scaled auction {got} vs optimal {opt}"

    def test_contention_full_utilization(self):
        from protocol_tpu.ops.sparse import assign_auction_sparse_scaled

        rng = np.random.default_rng(7)
        cost = random_cost(rng, 16, 64, p_infeasible=0.3)  # oversubscribed
        order = np.argsort(cost, axis=0, kind="stable").T
        cand_c = np.take_along_axis(cost.T, order, axis=1).astype(np.float32)
        cand_p = np.where(cand_c < INFEASIBLE * 0.5, order.astype(np.int32), -1)
        res = assign_auction_sparse_scaled(
            jnp.asarray(cand_p), jnp.asarray(cand_c), num_providers=16,
        )
        p4t = check_feasible(res, cost)
        assert (p4t >= 0).sum() == 16  # every provider seated


    @pytest.mark.parametrize("T_real,mult", [(97, 128), (505, 128), (1000, 64)])
    def test_uneven_tail_padding(self, T_real, mult):
        """Bucket padding with an uneven real tail (what the arena's pow2
        buckets rest on): rows padded with -1 / INFEASIBLE never seat,
        and the real rows get the plan the unpadded solve gives them."""
        from protocol_tpu.ops.sparse import assign_auction_sparse_scaled

        rng = np.random.default_rng(T_real)
        P, k = 128, 16
        T_pad = -(-T_real // mult) * mult
        cost = random_cost(rng, P, T_real, p_infeasible=0.1)
        cand_p, cand_c = sorted_candidates(cost, k)
        pad = T_pad - T_real
        padded_p = np.concatenate([cand_p, np.full((pad, k), -1, np.int32)])
        padded_c = np.concatenate(
            [cand_c, np.full((pad, k), np.float32(INFEASIBLE))]
        )
        kw = dict(
            num_providers=P, eps_start=2.0, eps_end=0.02,
            max_iters_per_phase=4000,
        )
        res_pad = assign_auction_sparse_scaled(
            jnp.asarray(padded_p), jnp.asarray(padded_c), frontier=T_pad, **kw
        )
        res_real = assign_auction_sparse_scaled(
            jnp.asarray(cand_p), jnp.asarray(cand_c), frontier=T_real, **kw
        )
        got = np.asarray(res_pad.provider_for_task)
        assert not (got[T_real:] >= 0).any(), "padded tail must stay open"
        # (an injective, feasible plan: so the padded one is, seat for seat)
        np.testing.assert_array_equal(
            got[:T_real], check_feasible(res_real, cost)
        )


class TestEndToEndTopk:
    def test_pipeline_feasibility_and_compat(self):
        ep, er = encode_random_marketplace(3, 48, 32)
        res = assign_topk(ep, er, k=8, tile=8, eps=0.05, max_iters=3000)
        mask = np.asarray(compat_mask(ep, er))
        p4t = np.asarray(res.provider_for_task)
        used = set()
        for t, p in enumerate(p4t):
            if p >= 0:
                assert mask[p, t], f"incompatible assignment t={t} p={p}"
                assert p not in used
                used.add(p)


class TestStallDetection:
    def test_unfillable_tail_ends_phase_early(self):
        """Per-task retirement cannot stop an unfillable tail (the open
        'hole' wanders the graph via eviction chains), so phases used to
        grind to max_iters with one open task. stall_limit ends the phase
        after N no-progress rounds instead."""
        from protocol_tpu.ops.sparse import _sparse_auction_phase

        # 3 tasks fighting over 2 providers: one permanent hole
        cand_p = jnp.asarray([[0, 1], [0, 1], [0, 1]], jnp.int32)
        cand_c = jnp.asarray([[1.0, 2.0], [1.1, 2.1], [1.2, 2.2]], jnp.float32)
        state, stall, _rows, _scans = _sparse_auction_phase(
            cand_p, cand_c, 2, None, eps=0.5, max_iters=5000,
            frontier=4, retire=False, stall_limit=16,
        )
        rounds = int(state[0])
        assigned = int(np.asarray(state[3] >= 0).sum())
        assert assigned == 2  # both providers seated
        assert rounds < 200, f"phase should stall out early, ran {rounds}"
        assert int(stall) >= 16  # the exit is observable, not silent

    def test_stall_disabled_by_default(self):
        """stall_limit=0 preserves the run-to-cap semantics the plain
        kernel's callers rely on."""
        from protocol_tpu.ops.sparse import _sparse_auction_phase

        cand_p = jnp.asarray([[0, 1], [0, 1], [0, 1]], jnp.int32)
        cand_c = jnp.asarray([[1.0, 2.0], [1.1, 2.1], [1.2, 2.2]], jnp.float32)
        state, _stall, _rows, _scans = _sparse_auction_phase(
            cand_p, cand_c, 2, None, eps=0.5, max_iters=300,
            frontier=4, retire=False, stall_limit=0,
        )
        assert int(state[0]) == 300  # ground to the cap, as before


class TestBidirCandidates:
    """Bidirectional candidate generation (stage-B completeness, VERDICT r3
    item 3): forward top-k alone coverage-caps the matching when costs are
    price-dominated — every task's window holds the same cheap providers
    and expensive rows get NO edges. Reverse (provider->task) edges
    guarantee every provider a path into the graph."""

    @staticmethod
    def _priced_marketplace(P, T, seed=0):
        """Identical specs, wide price spread: the adversarial shape for
        forward-only coverage (all tasks rank providers identically up to
        tie jitter)."""
        from protocol_tpu.models.node import (
            ComputeRequirements, ComputeSpecs, CpuSpecs, GpuSpecs,
        )

        enc = FeatureEncoder()
        spec = ComputeSpecs(
            gpu=GpuSpecs(count=8, model="H100", memory_mb=80000),
            cpu=CpuSpecs(cores=32), ram_mb=65536, storage_gb=1000,
        )
        rng = np.random.default_rng(seed)
        prices = rng.uniform(0.1, 10.0, size=P).tolist()
        ep = enc.encode_providers([spec] * P, prices=prices)
        er = enc.encode_requirements(
            [ComputeRequirements.parse("gpu:count=8;gpu:model=H100")] * T
        )
        return ep, er

    def test_reverse_edges_match_bruteforce(self):
        """Mirrors the tile-POOLED reverse semantics exactly: each tile
        contributes its per-provider top-ceil(r/n_tiles), the final edges
        are the best r of the pool (with the first edge therefore the
        true global best)."""
        from protocol_tpu.ops.sparse import candidates_topk_reverse

        P, T, tile, r = 24, 16, 8, 3
        ep, er = encode_random_marketplace(11, P, T)
        _, _, rev_t, rev_c = candidates_topk_reverse(
            ep, er, k=4, tile=tile, reverse_r=r
        )
        cost = jittered_cost(np.asarray(cost_matrix(ep, er, CostWeights())[0]))
        rev_t, rev_c = np.asarray(rev_t), np.asarray(rev_c)
        n_tiles = T // tile
        rt = -(-r // n_tiles)
        for p in range(P):
            pool = []
            for g in range(n_tiles):
                seg = cost[p, g * tile:(g + 1) * tile]
                for j in np.argsort(seg, kind="stable")[:rt]:
                    pool.append((float(seg[j]), g * tile + int(j)))
            pool.sort(key=lambda e: e[0])
            expected = [
                t if c < INFEASIBLE * 0.5 else -1 for c, t in pool[:r]
            ]
            assert rev_t[p].tolist() == expected, f"provider {p}"
            feas = [i for i, t in enumerate(expected) if t >= 0]
            np.testing.assert_allclose(
                rev_c[p][feas], [pool[i][0] for i in feas], rtol=1e-6
            )
            # the first edge is the true global best (exactness property
            # the pooling preserves)
            if expected and expected[0] >= 0:
                assert expected[0] == int(
                    np.argsort(cost[p], kind="stable")[0]
                )

    def test_merge_scatter_exact_and_deduped(self):
        """Per task, the merged extra columns hold the cheapest <=extra
        reverse edges targeting it — minus edges duplicating a forward
        candidate (a dup makes v1==v2 in the bid math, collapsing bid
        increments to +eps; measured slower AND worse at 4k)."""
        from protocol_tpu.ops.sparse import merge_reverse_candidates

        T, K, P, r, extra = 6, 2, 8, 4, 2
        rng = np.random.default_rng(3)
        cand_p = rng.integers(0, P, size=(T, K)).astype(np.int32)
        cand_c = rng.uniform(0, 1, size=(T, K)).astype(np.float32)
        rev_t = rng.integers(-1, T, size=(P, r)).astype(np.int32)
        rev_c = rng.uniform(0, 1, size=(P, r)).astype(np.float32)
        mp, mc = merge_reverse_candidates(
            jnp.asarray(cand_p), jnp.asarray(cand_c),
            jnp.asarray(rev_t), jnp.asarray(rev_c), extra=extra,
        )
        mp, mc = np.asarray(mp), np.asarray(mc)
        assert mp.shape == (T, K + extra)
        np.testing.assert_array_equal(mp[:, :K], cand_p)
        for t in range(T):
            edges = sorted(
                (float(rev_c[p, j]), int(p))
                for p in range(P)
                for j in range(r)
                if rev_t[p, j] == t and p not in cand_p[t]
            )[:extra]
            got = [
                (round(float(mc[t, K + i]), 6), int(mp[t, K + i]))
                for i in range(extra)
                if mp[t, K + i] >= 0
            ]
            expected = [(round(c, 6), p) for c, p in edges]
            assert got == expected, f"task {t}: {got} vs {expected}"

    def test_bidir_restores_coverage_and_completeness(self):
        """P=T with k<<P and price-dominated costs: forward-only coverage
        (and therefore assignment) caps at ~k; bidir restores full
        coverage AND the auction achieves the graph's maximum matching
        (100% here — production defaults at a production-sparse size;
        below ~1k the matcher routes through the dense solver anyway).
        Mirrors the measured 65k result: 99.98% vs forward-only 66.5%."""
        import scipy.sparse as _sp
        from scipy.sparse.csgraph import maximum_bipartite_matching

        from protocol_tpu.ops.sparse import (
            assign_auction_sparse_scaled,
            candidates_topk,
            candidates_topk_bidir,
        )

        P = T = 1024
        k = 8
        ep, er = self._priced_marketplace(P, T)
        fp, _ = candidates_topk(ep, er, k=k, tile=256)
        fwd_cov = np.unique(np.asarray(fp)[np.asarray(fp) >= 0]).size
        assert fwd_cov < P * 0.25, f"forward coverage {fwd_cov} not capped"

        bp, bc = candidates_topk_bidir(
            ep, er, k=k, tile=256, reverse_r=8, extra=16
        )
        bpn = np.asarray(bp)
        bidir_cov = np.unique(bpn[bpn >= 0]).size
        assert bidir_cov == P, f"bidir coverage {bidir_cov} != {P}"

        # graph capacity: the bidir candidate graph must admit a (near-)
        # perfect matching — this is what reverse_r buys
        rows, cols = np.nonzero(bpn >= 0)[0], bpn[bpn >= 0]
        g = _sp.csr_matrix(
            (np.ones(len(rows)), (rows, cols)), shape=(T, P)
        )
        maxm = int((maximum_bipartite_matching(g, perm_type="column") >= 0).sum())
        assert maxm >= T * 0.99, f"graph max matching only {maxm}/{T}"

        res = assign_auction_sparse_scaled(bp, bc, num_providers=P)
        p4t = np.asarray(res.provider_for_task)
        assigned = int((p4t >= 0).sum())
        # the auction must realize the graph's capacity, not just beat a bar
        assert assigned >= maxm - 2, f"auction {assigned} vs max {maxm}"
        assert assigned >= T * 0.99, f"bidir assigned only {assigned}/{T}"
        pos = p4t[p4t >= 0]
        assert np.unique(pos).size == pos.size  # injective matching


class TestAdaptiveFrontierLadder:
    """_phase_adaptive: the phase in 256-round segments of one kernel
    executable with host-side stall accounting (the per-segment
    stall_limit static would re-trace the kernel every boundary); the
    frontier's width is fitted inside the kernel, round by round
    (TestFrontierRungs)."""

    def test_breaker_accumulates_across_segments(self):
        """With retirement off, an unfillable hole stalls forever; the
        host-side breaker must accumulate whole-segment stalls and trip
        at a limit LARGER than one segment (a single 256-round segment
        alone can never reach it), and report the ACCUMULATED count."""
        from protocol_tpu.ops.sparse import _phase_adaptive

        cand_p = jnp.asarray([[0, 1], [0, 1], [0, 1]], jnp.int32)
        cand_c = jnp.asarray(
            [[1.0, 2.0], [1.1, 2.1], [1.2, 2.2]], jnp.float32
        )
        state, stall, _rows, _scans = _phase_adaptive(
            cand_p, cand_c, 2, None, eps=0.5, max_iters=100_000,
            frontier=4, retire=False, stall_limit=600,
        )
        rounds = int(state[0])
        assert int(np.asarray(state[3] >= 0).sum()) == 2  # seated
        assert rounds < 100_000, "breaker must trip before the cap"
        assert int(stall) >= 600, "accumulated (not per-segment) stall"

    def test_quality_parity_with_fixed_frontier(self):
        """Segments are a schedule change, not a semantics change: the
        ladder stays within n * eps_end of the exact optimum."""
        from scipy.optimize import linear_sum_assignment

        from protocol_tpu.ops.sparse import assign_auction_sparse_scaled

        rng = np.random.default_rng(3)
        n = 128
        cost = rng.uniform(0, 10, size=(n, n)).astype(np.float32)
        order = np.argsort(cost, axis=0, kind="stable").T
        cand_c = np.take_along_axis(cost.T, order, axis=1).astype(np.float32)
        cand_p = order.astype(np.int32)
        ri, ci = linear_sum_assignment(cost)
        opt = cost[ri, ci].sum()
        res = assign_auction_sparse_scaled(
            jnp.asarray(cand_p), jnp.asarray(cand_c), num_providers=n,
            eps_end=0.005,
        )
        p4t = np.asarray(res.provider_for_task)
        assert (p4t >= 0).all()
        got = sum(cost[p4t[t], t] for t in range(n))
        assert got <= opt + n * 0.006, f"{got} vs {opt}"

    @pytest.mark.parametrize("solve", ["scaled", "warm"])
    def test_stats_out_holds_what_the_metrics_read(self, solve):
        """The one driver's contract with the per-layer metrics: the
        cold ladder and the warm solve fill the same keys of
        ``stats_out`` (the arena forwards them as ``eng_*``)."""
        from protocol_tpu.ops.sparse import (
            assign_auction_sparse_scaled,
            assign_auction_sparse_warm,
        )

        rng = np.random.default_rng(5)
        P, T = 96, 64  # idle providers: the reverse pass's keys too
        cost = rng.uniform(0, 10, size=(P, T)).astype(np.float32)
        cand_p, cand_c = map(jnp.asarray, sorted_candidates(cost, 16))
        stats: dict = {}
        res, price = assign_auction_sparse_scaled(
            cand_p, cand_c, num_providers=P, with_prices=True, stats_out=stats
        )
        if solve == "warm":
            stats = {}
            assign_auction_sparse_warm(
                cand_p, cand_c, num_providers=P, price0=price,
                p4t0=jnp.asarray(res.provider_for_task).at[:8].set(-1),
                stats_out=stats,
            )
        assert set(stats) == {
            "rounds_total", "segments", "wait_ms", "frontier_rows",
            "stall_exit", "stall_rounds", "reverse_rounds", "reverse_ms",
            "free_repriced", "queue_rounds", "queue_ms", "scan_rounds",
            "open_read_ms", "regime_change", "cross_ms", "cross_rounds",
            "transposed_rounds",
        }
        assert stats["queue_rounds"] == 0  # (no queue in this pool)
        # nothing carried a regime in, so nothing crossed; the transposed
        # passes' rounds in one counter
        assert stats["regime_change"] == stats["cross_rounds"] == 0
        assert stats["cross_ms"] == 0.0
        assert stats["transposed_rounds"] == stats["reverse_rounds"]
        # the open counts' reads after full segments: a part of the wait
        assert 0 <= stats["open_read_ms"] <= stats["wait_ms"]
        assert stats["segments"] >= 1 and stats["rounds_total"] >= 1
        # every round runs at one of the kernel's widths, 32 the least
        assert stats["frontier_rows"] >= 32 * (
            stats["rounds_total"] + stats["reverse_rounds"]
        )
        assert stats["stall_exit"] is False
        assert 0 <= stats["scan_rounds"] <= (
            stats["rounds_total"] + stats["reverse_rounds"]
        )

    def test_phase_stops_within_a_segment_of_max_iters(self):
        """The budget is honoured at segment granularity: a phase that
        never converges (retirement and breaker off) stops at the first
        segment boundary at or past ``max_iters``."""
        from protocol_tpu.ops.sparse import _phase_adaptive

        cand_p = jnp.asarray([[0, 1], [0, 1], [0, 1]], jnp.int32)
        cand_c = jnp.asarray(
            [[1.0, 2.0], [1.1, 2.1], [1.2, 2.2]], jnp.float32
        )
        stats: dict = {}
        state, _stall, rows, scans = _phase_adaptive(
            cand_p, cand_c, 2, None, eps=0.5, max_iters=300,
            frontier=4, retire=False, stall_limit=0, stats_out=stats,
        )
        assert 300 <= int(state[0]) < 300 + 256
        assert stats["segments"] == 2
        assert rows == 3 * int(state[0])  # T = 3 is the only width
        # no rung below it, so no open list: every round scans [T]
        assert scans == int(state[0])


@partial(jax.jit, static_argnames=("num_providers", "width", "retire"))
def _plain_round(
    cand_provider, cand_cost, num_providers, loop, eps, width, retire,
    reserve=None,
):
    """One auction round at ONE static width: the phase kernel's body as
    it was before the width was fitted inside it (commit 807580a), kept
    here as the reference. Returns (loop, open tasks the round saw)."""
    from protocol_tpu.ops.sparse import _NEG, frontier_bids

    T, K = cand_cost.shape
    P = num_providers
    B = width
    cand_valid = cand_provider >= 0
    value_base = jnp.where(cand_valid, -cand_cost, _NEG)
    task_feasible = jnp.any(cand_valid, axis=1)
    cand_safe = jnp.where(cand_valid, cand_provider, 0)
    finite_max = jnp.max(jnp.where(cand_valid, cand_cost, 0.0))
    give_up = -(2.0 * finite_max + 10.0) if retire else _NEG
    if reserve is not None:
        give_up = reserve + eps
    state, best, stall = loop
    it, price, owner, p4t, retired = state
    open_mask = (p4t < 0) & task_feasible & ~retired
    f_idx = jnp.flatnonzero(open_mask, size=B, fill_value=T).astype(jnp.int32)
    f_ok = f_idx < T
    p1, v1, v2 = frontier_bids(cand_safe, value_base, price, f_idx, f_ok, K)
    newly_retired = f_ok & (v1 < give_up)
    bidding = f_ok & ~newly_retired & (v1 > _NEG * 0.5)
    if reserve is None:
        bid_amt = price[p1] + (v1 - v2) + eps
    else:
        bid_amt = price[p1] + jnp.minimum((v1 - v2) + eps, v1 - reserve)
    tgt = jnp.where(bidding, p1, P)
    win_bid = jnp.full(P, _NEG).at[tgt].max(
        jnp.where(bidding, bid_amt, _NEG), mode="drop"
    )
    is_winner_bid = bidding & (bid_amt >= win_bid[p1])
    win_task = jnp.full(P, T, jnp.int32).at[tgt].min(
        jnp.where(is_winner_bid, f_idx, T), mode="drop"
    )
    got_bid = (win_bid > _NEG * 0.5) & (win_task < T)
    retired = retired.at[jnp.where(newly_retired, f_idx, T)].set(
        True, mode="drop"
    )
    evict_t = jnp.where(got_bid & (owner >= 0), owner, T)
    p4t = p4t.at[evict_t].set(-1, mode="drop")
    p_idx = jnp.arange(P, dtype=jnp.int32)
    win_t_safe = jnp.where(got_bid, win_task, T)
    p4t = p4t.at[win_t_safe].set(jnp.where(got_bid, p_idx, -1), mode="drop")
    owner = jnp.where(got_bid, win_task, owner)
    price = jnp.where(got_bid, win_bid, price)
    n_now = jnp.sum(p4t >= 0)
    improved = n_now > best
    best = jnp.maximum(best, n_now)
    stall = jnp.where(improved, 0, stall + 1)
    loop = ((it + 1, price, owner, p4t, retired), best, stall)
    return loop, jnp.sum(open_mask)


def _plain_phase(
    cand_provider, cand_cost, num_providers, state, eps, max_iters,
    frontier, retire, stall_limit, reserve=None,
):
    """The phase as a host loop over :func:`_plain_round` at the one
    width ``min(frontier, T)``, under the kernel's own loop condition.
    Returns (state, stall, open tasks at the start of every round)."""
    T = cand_cost.shape[0]
    P = num_providers
    if state is None:
        state = (
            jnp.int32(0), jnp.zeros(P, jnp.float32),
            jnp.full(P, -1, jnp.int32), jnp.full(T, -1, jnp.int32),
            jnp.zeros(T, bool),
        )
    state = (jnp.int32(0),) + tuple(state[1:])
    feasible = np.asarray(jnp.any(cand_provider >= 0, axis=1))
    loop = (state, jnp.sum(state[3] >= 0), jnp.int32(0))
    opens = []
    while True:
        (it, _price, _owner, p4t, retired), _best, stall = loop
        n_open = int(
            ((np.asarray(p4t) < 0) & feasible & ~np.asarray(retired)).sum()
        )
        if int(it) >= max_iters or n_open == 0 or (
            stall_limit > 0 and int(stall) >= stall_limit
        ):
            return loop[0], loop[2], opens
        loop, seen = _plain_round(
            cand_provider, cand_cost, P, loop, eps, width=min(frontier, T),
            retire=retire, reserve=reserve,
        )
        assert int(seen) == n_open
        opens.append(n_open)


def _uniform_graph(seed, P, T, K, spread=0.0):
    """Each task's K cheapest of P providers under costs drawn uniformly
    (top-K costs of 0-12), plus ``spread`` x a per-provider price that
    makes the tasks' lists overlap."""
    rng = np.random.default_rng(seed)
    cost = rng.uniform(0, 1000.0 * K / P, (T, P))
    cost = (cost + spread * rng.uniform(0, 1, P)[None, :]).astype(np.float32)
    order = np.argsort(cost, axis=1, kind="stable")[:, :K]
    return (
        jnp.asarray(order.astype(np.int32)),
        jnp.asarray(np.take_along_axis(cost, order, axis=1)),
    )


def _rung_case(name):
    """(cand_provider, cand_cost, num_providers, state, phase kwargs):
    the four shapes the phase kernel is entered in."""
    from protocol_tpu.ops import sparse

    if name == "reverse":
        # providers bid for tasks over the transposed graph, against the
        # floor: the pass of a pool with slack (P > T), seeded from a
        # coarse phase and a finer one that strand bid-up providers
        P, T = 1024, 600
        cp, cc = _uniform_graph(2, P, T, 12, spread=2.0)
        price = owner = p4t = None
        state = None
        for eps in (4.0, 0.25):
            if state is not None:
                owner, p4t = sparse._unassign_unhappy(
                    cp, cc, price, owner, p4t, eps
                )
                state = (jnp.int32(0), price, owner, p4t, jnp.zeros(T, bool))
            state, _stall, _rows, _scans = sparse._sparse_auction_phase(
                cp, cc, P, state, eps=eps, max_iters=4000, frontier=4096,
            )
            _, price, owner, p4t, _ = state
        rev_t, rev_c = sparse._transpose_candidates(cp, cc, P, 32)
        listed, floor, _, _ = sparse._stranded(cp, price, owner, p4t)
        rstate, floor = sparse._reverse_seed(
            cp, cc, price, owner, p4t, listed, floor
        )
        return rev_t, rev_c, T, rstate, dict(
            eps=0.25, max_iters=600, frontier=P, retire=True, stall_limit=0,
            reserve=floor,
        )
    P, T = 2000, 2048
    cp, cc = _uniform_graph(1, P, T, 12)
    if name == "cold":
        # more open tasks than ``frontier``: the top rung is the plain
        # round, the first ``frontier`` open tasks in index order
        return cp, cc, P, None, dict(
            eps=0.25, max_iters=300, frontier=1024, retire=True,
            stall_limit=0,
        )
    # a converged phase, then a handful of tasks unseated on its prices
    state, _stall, _rows, _scans = sparse._sparse_auction_phase(
        cp, cc, P, None, eps=0.02, max_iters=20000, frontier=4096,
    )
    _, price, _owner, p4t, retired = state
    p4t = p4t.at[jnp.asarray([3, 77, 500, 1200, 2000])].set(-1)
    state = (jnp.int32(0), price, sparse._invert(p4t, P), p4t, retired)
    if name == "warm":
        return cp, cc, P, state, dict(
            eps=0.02, max_iters=600, frontier=4096, retire=True,
            stall_limit=0,
        )
    if name == "wide":
        # 1,500 tasks unseated on converged prices, the list's size
        # (1,024 at B = 2,048) and more: the first rounds scan [T], and
        # the list is built from the state a scan leaves
        p4t = p4t.at[jnp.arange(0, 2048, 4)].set(-1).at[1::3].set(-1)
        state = (jnp.int32(0), price, sparse._invert(p4t, P), p4t,
                 jnp.zeros(T, bool))
        return cp, cc, P, state, dict(
            eps=0.02, max_iters=600, frontier=4096, retire=True,
            stall_limit=0,
        )
    if name == "retired_seat":
        # every third seated task flagged retired, as a carried mask may
        # be: one that is evicted is not open, so not listed
        flags = jnp.zeros(T, bool).at[::3].set(True) & (p4t >= 0)
        p4t = p4t.at[1::16].set(-1)
        state = (jnp.int32(0), price + 0.0, sparse._invert(p4t, P), p4t,
                 flags & (p4t >= 0))
        return cp, cc, P, state, dict(
            eps=0.02, max_iters=600, frontier=4096, retire=True,
            stall_limit=0,
        )
    if name == "reserve":
        # the forward phase of a pool with a queue: bids capped by the
        # reserve, a task whose best seat is not worth eps more waits;
        # the seats the unseated tasks left are priced up, so that they
        # bid for seats that are held
        gone = jnp.arange(0, T, 8)
        price = price.at[jnp.maximum(p4t[gone], 0)].add(3.0)
        p4t = p4t.at[gone].set(-1)
        level = float(jnp.median(price))
        state = (jnp.int32(0), price, sparse._invert(p4t, P), p4t,
                 jnp.zeros(T, bool))
        return cp, cc, P, state, dict(
            eps=0.02, max_iters=600, frontier=4096, retire=True,
            stall_limit=0, reserve=jnp.float32(-(level + 0.1)),
        )
    if name == "no_candidates":
        # 96 rows list no provider (a session's padded rows): never
        # open, so never listed, beside the open ones
        empty = jnp.arange(7, 2048, 21)[:96]
        cp = cp.at[empty].set(-1)
        p4t = p4t.at[empty].set(-1).at[::8].set(-1)
        state = (jnp.int32(0), price, sparse._invert(p4t, P), p4t,
                 jnp.zeros(T, bool))
        return cp, cc, P, state, dict(
            eps=0.02, max_iters=600, frontier=4096, retire=True,
            stall_limit=0,
        )
    assert name == "stall"
    # every eighth task unseated too, nobody retires and nothing is
    # retired: 48 tasks have no seat, and the phase ends by its stall
    # limit
    p4t = p4t.at[::8].set(-1)
    state = (jnp.int32(0), price, sparse._invert(p4t, P), p4t,
             jnp.zeros(T, bool))
    return cp, cc, P, state, dict(
        eps=0.02, max_iters=4000, frontier=4096, retire=False,
        stall_limit=48,
    )


class TestFrontierRungs:
    """The phase kernel fits its frontier to the open set every round
    (``_FRONTIER_RUNGS``); a width that holds every open task must be
    invisible in the state."""

    @pytest.mark.parametrize("name", [
        "cold", "warm", "stall", "reverse", "wide", "retired_seat",
        "reserve", "no_candidates",
    ])
    def test_state_equals_the_single_width_round(self, name):
        from protocol_tpu.ops import sparse

        cp, cc, P, state, kw = _rung_case(name)
        want, want_stall, opens = _plain_phase(cp, cc, P, state, **kw)
        got, got_stall, rows, scans = sparse._sparse_auction_phase(
            cp, cc, P, state, **kw
        )
        assert len(opens) == int(got[0]) > 0
        for field, a, b in zip(
            ("it", "price", "owner", "p4t", "retired"), want, got
        ):
            # exactly: no tolerance on the prices either
            assert np.array_equal(np.asarray(a), np.asarray(b)), field
        assert int(got_stall) == int(want_stall)
        top = min(kw["frontier"], cc.shape[0])
        widths = [w for w in sparse._FRONTIER_RUNGS if w < top] + [top]
        ran_at = [next((w for w in widths if n <= w), top) for n in opens]
        assert int(rows) == sum(ran_at)
        # the rounds whose open tasks the list could not hold scanned [T]
        cap = widths[-2] if len(widths) > 1 else 0
        assert int(scans) == sum(n > cap for n in opens)
        p4t1 = np.asarray(got[3])
        p4t0 = p4t1 if state is None else np.asarray(state[3])
        if name in ("cold", "wide"):
            # a scan first, then the list it built
            assert opens[0] > cap >= min(opens), (opens[0], cap)
            assert 0 < int(scans) < len(opens)
        if name == "wide":
            assert len([n for n in opens if n <= cap]) >= 3
        if name == "retired_seat":
            # some flagged seat was taken, and its task stays shut out
            flags = np.asarray(state[4])
            evicted = flags & (p4t0 >= 0) & (p4t1 < 0)
            assert evicted.any() and np.asarray(got[4])[evicted].all()
        if name == "reserve":
            # bids evicted seated tasks, and some tasks wait
            assert ((p4t0 >= 0) & (p4t1 != p4t0)).any()
            assert (p4t1 < 0).sum() > 0
        if name == "no_candidates":
            empty = ~np.asarray(jnp.any(cp >= 0, axis=1))
            assert empty.sum() == 96 and (p4t1[empty] < 0).all()
            assert not np.asarray(got[4])[empty].any()
            assert opens[0] == int(((p4t0 < 0) & ~empty).sum())
        # the case is the shape it says it is, and crosses rungs (but
        # the warm one: a handful open, the narrowest rung all along)
        assert len(set(ran_at)) >= (1 if name == "warm" else 2), set(ran_at)
        if name == "cold":
            assert opens[0] > top
        if name == "warm":
            assert set(ran_at) == {sparse._FRONTIER_RUNGS[0]}
            assert int(np.asarray(got[4]).sum()) > 0
        if name == "stall":
            assert int(got_stall) >= kw["stall_limit"] and opens[-1] > 0
        if name == "reverse":
            assert opens[-1] > 0 or len(opens) < kw["max_iters"]


class TestWarmColdRegression:
    """VERDICT r4 item 2: the warm (incremental) solve must actually be
    cheaper than the cold ladder in the contended T=P geometry — r4
    measured warm 5.5x SLOWER at 65k. Root causes, both pinned here:
    (a) the carried-price clamp flattened the top of the price
    distribution (65,535/65,536 prices clipped), so the eps-CS repair
    evicted ~60k seeds for 655 churned tasks — fixed by a uniform
    downshift that preserves every price difference; (b) auction winners
    sit EXACTLY on the eps-CS boundary (value = v2 - eps by bid
    construction), so a tolerance-free repair at the same eps evicted
    ~half the matching on float dust — fixed by a float-scale tolerance
    in _unassign_unhappy."""

    def _contended_instance(self, T=2048, k=8):
        from protocol_tpu.ops.sparse import candidates_topk_bidir

        ep, er = TestBidirCandidates._priced_marketplace(T, T)
        return candidates_topk_bidir(ep, er, k=k, tile=256, reverse_r=8, extra=16)

    def test_warm_chain_mechanisms_after_churn(self):
        """The three warm-chain mechanisms, each deterministic at CI size.
        The headline warm-vs-cold WALL bar (>= 2x at 16k/65k) lives in the
        gated scale suite (test_scale_matcher.py) and the per-round
        scaling artifact -- at T=2048 the cold ladder is only a few
        hundred rounds and the warm path's fixed stall budget dominates,
        so a wall comparison here would measure the breaker, not the
        incremental machinery."""
        from protocol_tpu.ops.sparse import (
            assign_auction_sparse_scaled,
            assign_auction_sparse_warm,
        )

        bp, bc = self._contended_instance()
        T = bc.shape[0]
        stats_cold: dict = {}
        res, price, retired, _reserve = assign_auction_sparse_scaled(
            bp, bc, num_providers=T, with_state=True, stats_out=stats_cold
        )
        cold_assigned = int(np.asarray(res.provider_for_task >= 0).sum())
        # this instance has an unfillable tail -- the retired mask must be
        # non-trivial for the carry assertion below to mean anything
        assert int(np.asarray(retired).sum()) > 0

        p4t0 = jnp.asarray(res.provider_for_task).at[: T // 100].set(-1)

        def warm(**kw):
            stats: dict = {}
            r, _ = assign_auction_sparse_warm(
                bp, bc, num_providers=T, price0=price, p4t0=p4t0,
                stats_out=stats, **kw,
            )
            return int(np.asarray(r.provider_for_task >= 0).sum()), stats

        a_plain, s_plain = warm()
        a_carry, s_carry = warm(retired0=retired)

        # 1. retirement carry strictly cuts the re-fought tail
        assert s_carry["rounds_total"] < s_plain["rounds_total"], (
            f"carry {s_carry['rounds_total']} !< plain {s_plain['rounds_total']}"
        )
        # 2. quality parity: the incremental solve matches the cold ladder
        assert a_carry >= cold_assigned - 2
        assert a_plain >= cold_assigned - 2
        # 3. the warm cost is bounded by delta work + one stall budget --
        #    NOT by a from-scratch fine-eps solve (the r4 regression was
        #    11k+ rounds here-equivalent); segment granularity adds < 256
        assert s_carry["rounds_total"] <= stats_cold["rounds_total"] + 512 + 256, (
            f"warm {s_carry['rounds_total']} vs cold {stats_cold['rounds_total']}"
        )

    def test_repair_keeps_boundary_seeds_at_same_eps(self):
        """A converged solve re-admitted at the SAME eps must evict ZERO
        unchurned seeds: winners sit exactly on the eps-CS boundary, and
        only float dust separates them from 'unhappy'."""
        from protocol_tpu.ops.sparse import (
            _invert,
            _unassign_unhappy,
            assign_auction_sparse_scaled,
        )

        bp, bc = self._contended_instance(T=1024)
        T = bc.shape[0]
        res, price = assign_auction_sparse_scaled(
            bp, bc, num_providers=T, with_prices=True
        )
        p4t = jnp.asarray(res.provider_for_task)
        _, kept = _unassign_unhappy(bp, bc, price, _invert(p4t, T), p4t, 0.02)
        evicted = int((np.asarray(p4t) >= 0).sum()) - int(
            (np.asarray(kept) >= 0).sum()
        )
        assert evicted == 0, f"{evicted} seeds evicted at unchanged eps"

    def test_downshift_preserves_price_order(self):
        """Carried prices far above the retirement guard must arrive
        shifted, not clamped: relative order intact, max at the guard
        level."""
        from protocol_tpu.ops.sparse import assign_auction_sparse_warm

        cand_p = jnp.asarray([[0, 1], [1, 0], [2, -1]], jnp.int32)
        cand_c = jnp.asarray([[1.0, 2.0], [1.0, 2.0], [1.5, 0.0]], jnp.float32)
        # wildly ratcheted prices with distinct gaps, chosen so every
        # seed stays eps-CS happy in relative terms (nothing re-bids)
        price0 = jnp.asarray([1000.0, 1001.0, 1000.5], jnp.float32)
        p4t0 = jnp.asarray([0, 1, 2], jnp.int32)
        res, price = assign_auction_sparse_warm(
            cand_p, cand_c, num_providers=3, price0=price0, p4t0=p4t0
        )
        # seeds were eps-CS-consistent in RELATIVE terms; nothing re-bids,
        # so the returned prices are exactly the downshifted carries
        pr = np.asarray(price)
        np.testing.assert_allclose(pr[1] - pr[0], 1.0, atol=1e-4)
        np.testing.assert_allclose(pr[2] - pr[0], 0.5, atol=1e-4)
        assert pr.max() <= 2.0 + 5.0 + 1e-4  # finite_max + 5 guard
        assert (np.asarray(res.provider_for_task) == [0, 1, 2]).all()


def _plain_transpose(cand_provider, cand_cost, P, width):
    """The candidate graph from the providers' side, as a loop: tasks in
    order, each appending itself to every provider it lists, a
    provider's list cut at ``width``."""
    rev_t = np.full((P, width), -1, np.int32)
    rev_c = np.full((P, width), INFEASIBLE, np.float32)
    n = np.zeros(P, np.int64)
    for t, (row_p, row_c) in enumerate(zip(cand_provider, cand_cost)):
        for p, c in zip(row_p, row_c):
            if p >= 0 and n[p] < width:
                rev_t[p, n[p]] = t
                rev_c[p, n[p]] = c
                n[p] += 1
    return rev_t, rev_c


@partial(jax.jit, static_argnames=("num_providers", "width"))
def _argsort_transpose(cand_provider, cand_cost, num_providers, width):
    """The transposition as one stable argsort of the flattened provider
    ids, a scatter-add of the counts and two gathers of every [P, width]
    slot (``_transpose_candidates`` before it kept the sorted keys),
    kept here as the second reference."""
    T, K = cand_cost.shape
    P = num_providers
    prov = jnp.where(cand_provider >= 0, cand_provider, P).ravel()
    order = jnp.argsort(prov, stable=True).astype(jnp.int32)
    count = jnp.zeros(P + 1, jnp.int32).at[prov].add(1)[:P]
    start = jnp.cumsum(count) - count
    slot = jnp.arange(width, dtype=jnp.int32)[None, :]
    ok = slot < count[:, None]
    flat = order[jnp.minimum(start[:, None] + slot, T * K - 1)]
    return (
        jnp.where(ok, flat // K, -1),
        jnp.where(ok, cand_cost.ravel()[flat], INFEASIBLE),
    )


def _transpose_case(T, P, K, seed, dead=0.0, listed=None, hot=False):
    """Lists of K providers a task drawn from the first ``listed`` of P
    (duplicates allowed), a ``dead`` share of rows all -1, and with
    ``hot`` provider 0 listed by every live task."""
    rng = np.random.default_rng(seed)
    cp = rng.integers(0, listed or P, (T, K)).astype(np.int32)
    cc = rng.uniform(0.0, 12.0, (T, K)).astype(np.float32)
    if hot:
        cp[:, 0] = 0
    gone = rng.random(T) < dead
    cp[gone] = -1
    cc[gone] = INFEASIBLE
    # a row that lists fewer than K: its tail is empty
    cp[1, K // 2:] = -1
    cc[1, K // 2:] = INFEASIBLE
    return cp, cc


class TestTransposeCandidates:
    """``_transpose_candidates`` gives each provider's takers in task
    order, the first ``width`` kept, -1 / INFEASIBLE in the empty slots,
    the same arrays bit for bit as a plain loop and as the argsort
    formulation it replaced."""

    @staticmethod
    def _same(got, want):
        assert np.array_equal(np.asarray(got[0]), want[0])
        assert np.array_equal(
            np.asarray(got[1]).view(np.int32),
            np.asarray(want[1], np.float32).view(np.int32),
        )

    @pytest.mark.parametrize("T,P,K,width,kw", [
        # slack (T < P), P not a power of two, dead rows, providers past
        # 470 listed by none
        (300, 500, 12, 16, dict(dead=0.3, listed=470)),
        # a queue (T > P): most providers listed by more than ``width``
        (600, 250, 10, 16, dict(dead=0.1)),
        # the served width, one provider listed by every live task
        (700, 300, 40, 256, dict(dead=0.2, hot=True)),
        # a queue at the served width, P not a power of two
        (900, 131, 30, 256, dict(listed=100, hot=True)),
        # every row dead: nothing is listed
        (64, 96, 8, 16, dict(dead=1.0)),
    ])
    def test_matches_plain_loop(self, T, P, K, width, kw):
        from protocol_tpu.ops import sparse

        cp, cc = _transpose_case(T, P, K, seed=T + P, **kw)
        got = sparse._transpose_candidates(
            jnp.asarray(cp), jnp.asarray(cc), num_providers=P, width=width
        )
        want = _plain_transpose(cp, cc, P, width)
        self._same(got, want)
        self._same(_argsort_transpose(
            jnp.asarray(cp), jnp.asarray(cc), num_providers=P, width=width
        ), want)

    def test_matches_argsort_at_served_shape(self):
        """8,192 tasks x 80 -> [8,192, 256], 40% of the rows dead, as a
        pool with slack sends them."""
        from protocol_tpu.ops import sparse

        cp, cc = _transpose_case(8192, 8192, 80, seed=44, dead=0.4)
        cp, cc = jnp.asarray(cp), jnp.asarray(cc)
        got = sparse._transpose_candidates(cp, cc, num_providers=8192,
                                           width=256)
        want = _argsort_transpose(cp, cc, num_providers=8192, width=256)
        self._same(got, [np.asarray(a) for a in want])
