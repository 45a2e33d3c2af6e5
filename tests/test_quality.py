"""Decision-quality plane: the per-task outcome taxonomy, winner
margins, the certified duality-gap bound, churn/starvation signals, and
the tick-indexed SLO burn-rate engine.

The taxonomy tests are ORACLE tests: each population is seeded so a
specific cause (no candidates at all, outbid under capacity pressure,
a carried stale retirement) is known by construction, and the engine's
code must name exactly that cause — at every thread count, for both
engines. The null-buffer tests pin the zero-overhead contract: passing
no outcome buffer must change nothing, bit for bit.
"""

import numpy as np
import pytest

from protocol_tpu import native, obs
from protocol_tpu.obs import quality
from protocol_tpu.obs.slo import SLOConfig, SLOEngine
from protocol_tpu.ops.cost import CostWeights

from tests.test_sparse import encode_random_marketplace

pytestmark = pytest.mark.skipif(
    not native.available(), reason="no native toolchain"
)

INF = np.float32(1e9)


def _unique_candidates(seed, T, P, K):
    """[T, K] candidate rows with UNIQUE providers per row (margin
    oracles need an unambiguous seat slot)."""
    rng = np.random.default_rng(seed)
    cand_p = np.empty((T, K), np.int32)
    for t in range(T):
        cand_p[t] = rng.choice(P, size=K, replace=False)
    cand_c = rng.uniform(0.0, 10.0, size=(T, K)).astype(np.float32)
    return cand_p, cand_c


def _margin_oracle(cand_p, cand_c, p4t, price):
    """Reference winner margin at final prices: value(seat) minus the
    best value over the task's OTHER candidates (floored at -1e8)."""
    T, K = cand_p.shape
    out = np.zeros(T, np.float32)
    for t in range(T):
        seat = p4t[t]
        if seat < 0:
            continue
        vseat = vother = -np.inf
        for j in range(K):
            p = cand_p[t, j]
            if p < 0:
                continue
            v = -cand_c[t, j] - price[p]
            if p == seat:
                vseat = max(vseat, v)
            else:
                vother = max(vother, v)
        out[t] = vseat - max(vother, -1e8)
    return out


class TestAuctionTaxonomy:
    @pytest.mark.parametrize("threads", [1, 2, 4])
    def test_no_candidates_cause(self, threads):
        """Rows seeded with NO feasible candidate must come back
        unassigned:no_candidates — and only those rows."""
        cand_p, cand_c = _unique_candidates(0, 64, 128, 8)
        # candidate generation writes p = -1 for every infeasible slot
        # (cost is kInfeasible only on -1 slots) — the no-candidates
        # class is exactly the all-empty rows
        empty = [3, 9, 17, 40, 50]
        cand_p[empty] = -1
        cand_c[empty] = INF
        outs = {}
        p4t, _, _ = native.auction_sparse_mt(
            cand_p, cand_c, num_providers=128, threads=threads,
            outcomes=outs,
        )
        codes = outs["codes"]
        for t in empty:
            assert p4t[t] < 0
            assert codes[t] == native.OUTCOME_NO_CANDIDATES
        rest = np.setdiff1d(np.arange(64), empty)
        assert (codes[rest] == native.OUTCOME_ASSIGNED).all()
        assert (p4t[rest] >= 0).all()
        assert (outs["margin"][p4t < 0] == 0.0).all()

    @pytest.mark.parametrize("threads", [1, 2, 4])
    def test_outbid_under_capacity_pressure(self, threads):
        """T tasks fighting over P < T providers: exactly T - P tasks
        lose, and every loser's cause is outbid/give-up — capacity
        pressure, not a candidate problem."""
        T, P, K = 96, 64, 8
        rng = np.random.default_rng(1)
        cand_p = np.empty((T, K), np.int32)
        for t in range(T):
            cand_p[t] = rng.choice(P, size=K, replace=False)
        cand_c = rng.uniform(0.0, 10.0, size=(T, K)).astype(np.float32)
        outs = {}
        p4t, _, retired = native.auction_sparse_mt(
            cand_p, cand_c, num_providers=P, threads=threads,
            outcomes=outs,
        )
        codes = outs["codes"]
        lost = p4t < 0
        assert int(lost.sum()) == T - P
        assert (codes[lost] == native.OUTCOME_OUTBID).all()
        assert (codes[~lost] == native.OUTCOME_ASSIGNED).all()

    @pytest.mark.parametrize("threads", [1, 2, 4])
    def test_stale_retired_cause(self, threads):
        """A task that ENTERS a warm solve retired (carried flag,
        nothing re-opened it) must be named unassigned:retired — the
        stale class the PR 1 dirty-slot fix exists for — not lumped
        with the tick's fresh give-ups."""
        T, P, K = 96, 64, 8
        rng = np.random.default_rng(2)
        cand_p = np.empty((T, K), np.int32)
        for t in range(T):
            cand_p[t] = rng.choice(P, size=K, replace=False)
        cand_c = rng.uniform(0.0, 10.0, size=(T, K)).astype(np.float32)
        cold_p4t, price, cold_retired = native.auction_sparse_mt(
            cand_p, cand_c, num_providers=P, threads=threads,
        )
        # the carried flag stays set on cleanup-seated tasks by design
        # (PR 1): the stale-unassigned class is retired AND seatless
        stale = np.flatnonzero(cold_retired & (cold_p4t < 0))
        assert int((cold_p4t >= 0).sum()) == P  # saturated marketplace
        assert stale.size == T - P
        # warm re-solve, nothing churned: the carried flags stay set and
        # the losers are the STALE class this tick (cause recorded in a
        # PREVIOUS solve, not this one)
        outs = {}
        p4t, _, retired_out = native.auction_sparse_mt(
            cand_p, cand_c, num_providers=P, threads=threads,
            eps_start=0.02, eps_end=0.02, price=price.copy(),
            retired=cold_retired.copy(),
            seed_provider_for_task=cold_p4t,
            outcomes=outs,
        )
        codes = outs["codes"]
        np.testing.assert_array_equal(p4t, cold_p4t)
        for t in stale:
            assert p4t[t] < 0 and retired_out[t]
            assert codes[t] == native.OUTCOME_RETIRED
        seated = p4t >= 0
        assert (codes[seated] == native.OUTCOME_ASSIGNED).all()

    @pytest.mark.parametrize("threads", [1, 2, 4])
    def test_margins_match_oracle(self, threads):
        cand_p, cand_c = _unique_candidates(3, 128, 256, 8)
        outs = {}
        p4t, price, _ = native.auction_sparse_mt(
            cand_p, cand_c, num_providers=256, threads=threads,
            outcomes=outs,
        )
        oracle = _margin_oracle(cand_p, cand_c, p4t, price)
        np.testing.assert_allclose(
            outs["margin"], oracle, rtol=1e-5, atol=1e-5
        )
        # eps-CS at convergence: winner margins sit above -eps
        assert float(outs["margin"][p4t >= 0].min()) >= -0.02 - 1e-5

    @pytest.mark.parametrize("threads", [1, 2, 4])
    def test_outcomes_thread_invariant(self, threads):
        ep, er = encode_random_marketplace(11, 256, 256)
        cand_p, cand_c = native.fused_topk_candidates(
            ep, er, CostWeights(), k=16, reverse_r=8, extra=16
        )
        ref = {}
        native.auction_sparse_mt(
            cand_p, cand_c, num_providers=256, threads=1, outcomes=ref,
        )
        got = {}
        native.auction_sparse_mt(
            cand_p, cand_c, num_providers=256, threads=threads,
            outcomes=got,
        )
        np.testing.assert_array_equal(got["codes"], ref["codes"])
        np.testing.assert_array_equal(got["margin"], ref["margin"])

    def test_null_buffer_changes_nothing(self):
        """The zero-overhead contract: no outcome buffer, no stats dict
        — bit-identical matching, prices, and retirement either way."""
        ep, er = encode_random_marketplace(4, 256, 256)
        cand_p, cand_c = native.fused_topk_candidates(
            ep, er, CostWeights(), k=16, reverse_r=8, extra=16
        )
        bare = native.auction_sparse_mt(
            cand_p, cand_c, num_providers=256, threads=2,
        )
        outs, stats = {}, {}
        instrumented = native.auction_sparse_mt(
            cand_p, cand_c, num_providers=256, threads=2,
            outcomes=outs, stats=stats,
        )
        for a, b in zip(bare, instrumented):
            np.testing.assert_array_equal(a, b)
        assert "codes" in outs and "plan_cost" in stats


class TestSinkhornTaxonomy:
    def _candidates(self, seed=5, T=128, P=128, K=8):
        return _unique_candidates(seed, T, P, K)

    @pytest.mark.parametrize("threads", [1, 2, 4])
    def test_support_taxonomy_and_invariance(self, threads):
        cand_p, cand_c = self._candidates()
        unsupported = [2, 77]
        cand_p[unsupported] = -1
        ref_out = {}
        f1, g1, _, _ = native.sinkhorn_sparse_mt(
            cand_p, cand_c, num_providers=128, eps=0.05,
            max_iters=200, threads=1, outcomes=ref_out,
        )
        outs = {}
        f, g, _, _ = native.sinkhorn_sparse_mt(
            cand_p, cand_c, num_providers=128, eps=0.05,
            max_iters=200, threads=threads, outcomes=outs,
        )
        np.testing.assert_array_equal(f, f1)
        np.testing.assert_array_equal(g, g1)
        np.testing.assert_array_equal(outs["codes"], ref_out["codes"])
        np.testing.assert_array_equal(outs["margin"], ref_out["margin"])
        codes = outs["codes"]
        for t in unsupported:
            assert codes[t] == native.OUTCOME_NO_CANDIDATES
            assert outs["margin"][t] == 0.0
        supported = np.setdiff1d(np.arange(128), unsupported)
        assert (codes[supported] == native.OUTCOME_ASSIGNED).all()

    def test_margin_is_entropic_argmax_margin(self):
        cand_p, cand_c = self._candidates(seed=6)
        outs = {}
        f, _, _, _ = native.sinkhorn_sparse_mt(
            cand_p, cand_c, num_providers=128, eps=0.05,
            max_iters=200, threads=2, outcomes=outs,
        )
        for t in [0, 17, 99]:
            vals = np.sort(f[cand_p[t]] - cand_c[t])[::-1]
            assert outs["margin"][t] == pytest.approx(
                vals[0] - vals[1], rel=1e-5, abs=1e-5
            )

    def test_null_buffer_identity(self):
        cand_p, cand_c = self._candidates(seed=7)
        f0, g0, i0, e0 = native.sinkhorn_sparse_mt(
            cand_p, cand_c, num_providers=128, eps=0.05,
            max_iters=200, threads=2,
        )
        outs = {}
        f1, g1, i1, e1 = native.sinkhorn_sparse_mt(
            cand_p, cand_c, num_providers=128, eps=0.05,
            max_iters=200, threads=2, outcomes=outs,
        )
        np.testing.assert_array_equal(f0, f1)
        np.testing.assert_array_equal(g0, g1)
        assert (i0, e0) == (i1, e1)


class TestGapCertificate:
    def test_engine_certificate_matches_reference_scan(self):
        """gap_from_certificate (O(T) from the engine's margin pass)
        and duality_gap (the O(T*K) numpy reference) must agree — same
        certificate, two derivations."""
        cand_p, cand_c = _unique_candidates(8, 256, 256, 8)
        outs, stats = {}, {}
        p4t, price, _ = native.auction_sparse_mt(
            cand_p, cand_c, num_providers=256, threads=2,
            outcomes=outs, stats=stats,
        )
        ref = quality.duality_gap(cand_p, cand_c, p4t, price)
        # the engine's margin pass counts no queue (tasks that have to
        # wait: this instance has a few); the scan's addend for them is
        # handed over, as ``tick_quality`` does
        cert = quality.gap_from_certificate(
            p4t, stats["plan_cost"], stats["cs_slack"],
            stats["idle_price"], ref["waiting_excess"],
        )
        assert cert["plan_cost"] == pytest.approx(
            ref["plan_cost"], rel=1e-5
        )
        assert cert["gap_total"] == pytest.approx(
            ref["gap_total"], rel=1e-3, abs=1e-3
        )
        assert cert["idle_price"] == pytest.approx(
            ref["idle_price"], rel=1e-5, abs=1e-5
        )

    def test_gap_is_a_certificate(self):
        """The bound must be SOUND: plan cost minus the optimal
        assignment cost (brute-forced on a small instance) is <= the
        reported gap."""
        from scipy.optimize import linear_sum_assignment

        T = P = K = 16
        rng = np.random.default_rng(9)
        cost = rng.uniform(0.0, 10.0, size=(T, P)).astype(np.float32)
        cand_p = np.tile(np.arange(P, dtype=np.int32), (T, 1))
        cand_c = cost.copy()
        p4t, price, _ = native.auction_sparse_mt(
            cand_p, cand_c, num_providers=P, threads=1,
        )
        gap = quality.duality_gap(cand_p, cand_c, p4t, price)
        plan = sum(cost[t, p4t[t]] for t in range(T) if p4t[t] >= 0)
        rows, cols = linear_sum_assignment(cost)
        opt = float(cost[rows, cols].sum())
        assert plan - opt <= gap["gap_total"] + 1e-4
        assert gap["dual_bound"] <= opt + 1e-4

    def test_converged_gap_within_2eps(self):
        """The acceptance bound: on a saturated marketplace (the synth
        population the golden trace and the CI gate run) the certified
        per-task gap at auction convergence sits within 2x the engine
        eps."""
        from protocol_tpu.trace.synth import (
            synth_providers, synth_requirements,
        )

        ep = synth_providers(np.random.default_rng(10), 512)
        er = synth_requirements(np.random.default_rng(11), 512)
        cand_p, cand_c = native.fused_topk_candidates(
            ep, er, CostWeights(), k=16, reverse_r=8, extra=16
        )
        p4t, price, _ = native.auction_sparse_mt(
            cand_p, cand_c, num_providers=512, threads=2,
        )
        assert int((p4t >= 0).sum()) == 512
        gap = quality.duality_gap(cand_p, cand_c, p4t, price)
        assert gap["gap_per_task"] <= 2 * 0.02


class TestWaitingExcess:
    """The certificate's third addend (ISSUE 33): where tasks have to
    wait, the bound covers every plan that seats as many tasks,
    whichever they are. Hand-built pools with every task listing every
    provider, so the optimum is plain to see."""

    @staticmethod
    def _pool(task_cost, provider_cost):
        """cost[t, p] = task_cost[t] + provider_cost[p]: every plan that
        seats all providers costs their sum plus the seated tasks'."""
        a = np.asarray(task_cost, np.float32)
        q = np.asarray(provider_cost, np.float32)
        cand_p = np.tile(np.arange(q.size, dtype=np.int32), (a.size, 1))
        cand_c = a[:, None] + q[None, :]
        # duals at which every provider is worth the same to a task
        price = (q.max() - q).astype(np.float32)
        return cand_p, cand_c, price

    @pytest.mark.parametrize("task_cost, provider_cost, right, wrong", [
        # 3 providers x 5 tasks: tasks 0, 1, 2 are the cheapest
        ([1.0, 2.0, 3.0, 4.5, 7.0], [0.0, 0.0, 0.0],
         [0, 1, 2, -1, -1], [0, 1, -1, 2, -1]),
        # 4 providers x 6 tasks, providers that differ: tasks 1, 2, 4, 5
        ([5.0, 1.0, 2.0, 9.0, 2.5, 4.0], [0.5, 1.5, 0.0, 3.0],
         [-1, 0, 1, -1, 2, 3], [3, 0, 1, -1, 2, -1]),
    ], ids=["3x5", "4x6"])
    def test_a_wrong_task_waiting_reads_the_exact_gap(
        self, task_cost, provider_cost, right, wrong
    ):
        from scipy.optimize import linear_sum_assignment

        cand_p, cand_c, price = self._pool(task_cost, provider_cost)
        rows, cols = linear_sum_assignment(cand_c)
        best = float(cand_c[rows, cols].sum())

        def plan_cost(p4t):
            p4t = np.asarray(p4t)
            t = np.flatnonzero(p4t >= 0)
            return float(cand_c[t, p4t[t]].sum())

        assert plan_cost(right) == pytest.approx(best)
        true_gap = plan_cost(wrong) - best
        assert true_gap > 0.4
        good = quality.duality_gap(cand_p, cand_c, np.asarray(right), price)
        bad = quality.duality_gap(cand_p, cand_c, np.asarray(wrong), price)
        # the right tasks wait: nothing to add, the plan is certified
        assert good["waiting_excess"] == 0.0
        assert good["gap_total"] == pytest.approx(0.0, abs=1e-5)
        # a wrong one waits: the addend is what the plan is off by
        assert bad["waiting_excess"] == pytest.approx(true_gap, abs=1e-5)
        assert bad["cs_slack"] == pytest.approx(0.0, abs=1e-5)
        assert bad["idle_price"] == 0.0
        assert bad["gap_total"] == pytest.approx(true_gap, abs=1e-5)
        n = int((np.asarray(wrong) >= 0).sum())
        assert bad["gap_per_task"] == pytest.approx(true_gap / n, abs=1e-5)
        # the O(1) assembly follows
        cert = quality.gap_from_certificate(
            np.asarray(wrong), bad["plan_cost"], bad["cs_slack"],
            bad["idle_price"], bad["waiting_excess"],
        )
        assert cert["gap_total"] == bad["gap_total"]
        assert cert["waiting_excess"] == bad["waiting_excess"]

    def test_nobody_waiting_reads_todays_values(self):
        """3 x 3, everyone seated, one seat an eps off its best, and
        4 x 3 with a free provider at a stranded price: slack and idle
        price as they always were, the addend 0; so too for two waiting
        tasks beside two free providers (a full pool's unseatable tail:
        no queue)."""
        cand_p = np.tile(np.arange(3, dtype=np.int32), (3, 1))
        cand_c = np.array(
            [[1.0, 2.0, 3.0], [2.0, 1.0, 3.0], [3.0, 3.0, 1.5]], np.float32
        )
        price = np.array([0.0, 0.5, 0.0], np.float32)
        out = quality.duality_gap(
            cand_p, cand_c, np.array([0, 1, 2]), price
        )
        assert out == {
            "plan_cost": 3.5, "dual_bound": 3.5, "gap_total": 0.0,
            "gap_per_task": 0.0, "cs_slack": 0.0, "idle_price": 0.0,
            "waiting_excess": 0.0,
        }
        out = quality.duality_gap(
            cand_p, cand_c, np.array([1, 0, 2]), price
        )
        # task 0 holds provider 1 at 2.5 against a best of 1.0, task 1
        # provider 0 at 2.0 against 1.5
        assert out["cs_slack"] == pytest.approx(2.0)
        assert out["gap_total"] == pytest.approx(2.0)
        assert out["waiting_excess"] == 0.0

        cand_p4 = np.tile(np.arange(4, dtype=np.int32), (3, 1))
        cand_c4 = np.concatenate(
            [cand_c, np.full((3, 1), 2.0, np.float32)], axis=1
        )
        price4 = np.array([0.0, 0.0, 0.0, 0.75], np.float32)
        out = quality.duality_gap(
            cand_p4, cand_c4, np.array([0, 1, 2]), price4
        )
        assert out["idle_price"] == 0.75 and out["cs_slack"] == 0.0
        assert out["gap_total"] == 0.75 and out["waiting_excess"] == 0.0
        assert quality.gap_from_certificate(
            np.array([0, 1, 2]), 3.5, 0.0, 0.75
        )["gap_total"] == 0.75

        # an unseatable tail: tasks 3 and 4 list providers 0 and 1 only,
        # which are taken, and providers 3 and 4 stay free beside them
        tail_p = np.array(
            [[0, 1, 2, 3, 4]] * 3 + [[0, 1, -1, -1, -1]] * 2, np.int32
        )
        tail_c = np.where(tail_p >= 0, 1.0, 1e9).astype(np.float32)
        out = quality.duality_gap(
            tail_p, tail_c, np.array([0, 1, 2, -1, -1]),
            np.zeros(5, np.float32),
        )
        assert out["waiting_excess"] == 0.0 and out["gap_total"] == 0.0
        assert quality.queue_rows(
            tail_p, tail_c, np.array([0, 1, 2, -1, -1]), 5
        )[0].size == 0

    @pytest.mark.parametrize("n_tasks,n_seated", [(40, 24), (24, 22), (24, 24)])
    def test_the_providers_a_caller_covered_are_not_read_again(
        self, n_tasks, n_seated
    ):
        """``queue_rows`` with ``covered`` (some rows and the providers
        they list, as ``duality_gap`` has them) returns what it returns
        reading every list itself: a queue, a full pool's tail, nobody
        waiting."""
        cand_p, cand_c = _unique_candidates(7, n_tasks, 24, 6)
        cand_p[:, 5] = -1                      # an empty slot a row
        cand_c[::3, 0] = quality._INFEASIBLE   # an infeasible candidate
        p4t = np.full(n_tasks, -1, np.int32)
        p4t[:n_seated] = np.arange(n_seated)
        rows = np.arange(0, n_seated, 2)
        feas = (cand_p[rows] >= 0) & (cand_c[rows] < quality._INFEASIBLE * 0.5)
        mask = np.zeros(24, bool)
        mask[cand_p[rows][feas]] = True
        whole = quality.queue_rows(cand_p, cand_c, p4t, 24)
        given = quality.queue_rows(cand_p, cand_c, p4t, 24, covered=(rows, mask))
        np.testing.assert_array_equal(whole[0], given[0])
        np.testing.assert_array_equal(whole[1], given[1])
        assert (whole[0].size > 0) == (n_tasks == 40)

    def test_the_addend_is_least_over_the_value_of_waiting(self):
        """``_waiting_excess`` against a brute-force search of theta."""
        rng = np.random.default_rng(33)
        for _ in range(20):
            seated = rng.normal(size=rng.integers(1, 12))
            waiting = rng.normal(size=rng.integers(1, 12))
            values = np.concatenate([seated, waiting])
            brute = min(
                np.maximum(th - seated, 0).sum()
                + np.maximum(waiting - th, 0).sum()
                for th in values
            )
            assert quality._waiting_excess(seated, waiting) == (
                pytest.approx(brute, abs=1e-12)
            )
        assert quality._waiting_excess(np.array([1.0, 2.0]),
                                       np.array([0.5, 1.0])) == 0.0
        assert quality._waiting_excess(np.array([1.0]), np.zeros(0)) == 0.0


class TestQualitySignals:
    def test_plan_churn(self):
        prev = np.array([0, 1, 2, -1, 4], np.int32)
        cur = np.array([0, 2, 2, 3, -1], np.int32)
        rows, ratio = quality.plan_churn(prev, cur, None)
        assert (rows, ratio) == (3, 0.6)
        valid = np.array([1, 1, 1, 1, 0], bool)
        rows, ratio = quality.plan_churn(prev, cur, valid)
        assert (rows, ratio) == (2, 0.5)

    def test_starvation_ages_and_hist(self):
        p4t = np.array([-1, 0, -1, 1], np.int32)
        age = quality.starvation_update(None, p4t, None)
        np.testing.assert_array_equal(age, [1, 0, 1, 0])
        age = quality.starvation_update(age, p4t, None)
        np.testing.assert_array_equal(age, [2, 0, 2, 0])
        p4t2 = np.array([-1, 0, 5, 1], np.int32)
        age = quality.starvation_update(age, p4t2, None)
        np.testing.assert_array_equal(age, [3, 0, 0, 0])
        hist = quality.starvation_hist(age)
        assert sum(hist) == 1
        assert hist[quality.STARVE_BUCKETS.index(4)] == 1  # bucket (2,4]
        # invalid rows never starve
        age = quality.starvation_update(
            None, np.array([-1, -1]), np.array([True, False])
        )
        np.testing.assert_array_equal(age, [1, 0])

    def test_tick_quality_unexplained_invariant(self):
        """An unassigned valid task whose code claims "assigned" is the
        one inconsistency the CI gate hunts — tick_quality must count
        it."""
        cand_p, cand_c = _unique_candidates(12, 8, 16, 4)
        p4t = np.array([0, 1, -1, 2, -1, 3, 4, 5], np.int32)
        codes = np.zeros(8, np.uint8)
        codes[2] = native.OUTCOME_OUTBID  # explained
        # task 4 unassigned but coded "assigned": unexplained
        stats, _ = quality.tick_quality(
            cand_p, cand_c, p4t, None,
            outcomes={"codes": codes, "margin": np.zeros(8, np.float32)},
        )
        assert stats["outcome_unexplained"] == 1
        assert stats["outcome_outbid"] == 1


class TestArenaQuality:
    def _solve_chain(self, engine="auction"):
        import dataclasses

        from protocol_tpu.native.arena import NativeSolveArena

        ep, er = encode_random_marketplace(13, 192, 256)  # tasks > slots
        arena = NativeSolveArena(threads=2, engine=engine)
        arena.solve(ep, er, CostWeights())
        stats = [dict(arena.last_stats)]
        for i in range(3):
            price = np.array(ep.price, copy=True)
            price[[i, i + 7]] += 0.25
            ep = dataclasses.replace(ep, price=price)
            arena.solve(ep, er, CostWeights())
            stats.append(dict(arena.last_stats))
        return stats

    @pytest.mark.parametrize("engine", ["auction", "sinkhorn"])
    def test_last_stats_carries_quality(self, engine):
        assert obs.enabled()
        stats = self._solve_chain(engine)
        for s in stats:
            assert "gap_per_task" in s
            assert s["outcome_unexplained"] == 0
            assert "starve_hist" in s
            total = sum(
                s[k] for _, k in quality.OUTCOME_STAT_KEYS
            )
            assert total == 256  # every valid task classified
        # warm ticks carry churn; the cold tick cannot
        assert "churn_ratio" not in stats[0]
        assert all("churn_ratio" in s for s in stats[1:])

    def test_starvation_persists_across_warm_ticks(self):
        stats = self._solve_chain()
        # 256 tasks / 192 providers: ~64 tasks starve every tick, and
        # the age of the persistent losers must climb tick over tick
        assert stats[0]["starving"] > 0
        assert stats[-1]["starve_max"] >= 3

    def test_short_circuit_tick_advances_starvation(self):
        from protocol_tpu.native.arena import NativeSolveArena

        ep, er = encode_random_marketplace(14, 192, 256)
        arena = NativeSolveArena(threads=2)
        arena.solve(ep, er, CostWeights())
        m0 = arena.last_stats["starve_max"]
        arena.solve(ep, er, CostWeights())  # byte-identical: short-circuit
        s = arena.last_stats
        assert s["changed_rows"] == 0
        assert s["churn_ratio"] == 0.0
        assert s["starve_max"] == m0 + 1  # ages advance, plan reused
        assert s["gap_per_task"] is not None  # carried certificate reused

    def test_obs_disabled_skips_quality(self):
        from protocol_tpu.native.arena import NativeSolveArena

        ep, er = encode_random_marketplace(15, 128, 128)
        obs.set_enabled(False)
        try:
            arena = NativeSolveArena(threads=2)
            arena.solve(ep, er, CostWeights())
            assert "gap_per_task" not in arena.last_stats
        finally:
            obs.set_enabled(True)


class TestSLOEngine:
    def _cfg(self, **kw):
        kw.setdefault("min_assigned_frac", 0.95)
        return SLOConfig(**kw)

    def test_inert_without_objectives(self):
        eng = SLOEngine(SLOConfig())
        assert eng.observe("s", "t", 0, {"assigned_frac": 0.0}) == []
        assert eng.snapshot()["fired_total"] == 0

    def test_multi_window_fire_and_clear(self):
        """Sustained badness fires once both windows fill and burn past
        the threshold; recovery clears the alert — and the whole
        sequence is a pure function of the tick-indexed inputs."""
        eng = SLOEngine(self._cfg())
        events = []
        for tick in range(32):
            events += eng.observe(
                "s", "ten", tick, {"assigned_frac": 0.5}
            )
        assert [e["state"] for e in events] == ["fire"]
        assert events[0]["slo"] == "assigned_frac"
        # the fast pair (8, 32) fires the moment its LONG window fills
        # (a half-filled window must not page); the slow pair's 128-tick
        # window never fills in 32 ticks
        assert events[0]["tick"] == 31
        assert events[0]["window"] == [8, 32]
        assert eng.fired_total == 1
        cleared = []
        for tick in range(32, 64):
            cleared += eng.observe(
                "s", "ten", tick, {"assigned_frac": 1.0}
            )
        assert {e["state"] for e in cleared} == {"clear"}
        assert eng.active_alerts() == []

    def test_one_tick_blip_does_not_page(self):
        eng = SLOEngine(self._cfg())
        events = []
        for tick in range(64):
            frac = 0.5 if tick == 10 else 1.0
            events += eng.observe("s", "t", tick, {"assigned_frac": frac})
        assert events == []

    def test_deterministic_replay(self):
        rng = np.random.default_rng(16)
        seq = rng.uniform(0.8, 1.0, size=200)
        runs = []
        for _ in range(2):
            eng = SLOEngine(self._cfg(min_assigned_frac=0.9))
            ev = []
            for tick, frac in enumerate(seq):
                ev += eng.observe("s", "t", tick, {"assigned_frac": float(frac)})
            runs.append(ev)
        assert runs[0] == runs[1]

    def test_cold_ticks_skip_latency_objective(self):
        eng = SLOEngine(SLOConfig(p99_warm_tick_ms=1.0))
        for tick in range(64):
            assert eng.observe(
                "s", "t", tick, {"wall_ms": 50.0}, cold=True
            ) == []

    def test_registry_integration_and_trace_events(self):
        """ObsRegistry feeds the SLO engine under its lock and returns
        the fired events; the snapshot carries config + recent alerts."""
        from protocol_tpu.obs.metrics import ObsRegistry

        reg = ObsRegistry(role="test")
        reg.attach(slo=SLOEngine(self._cfg()))
        fired = []
        for _ in range(32):  # fast pair: long window is 32 ticks
            fired += reg.observe_tick(
                "ten@sess", 1.0, 100, 10, arena_stats={"cold": False}
            )
        assert any(e["state"] == "fire" for e in fired)
        assert fired[0]["tenant"] == "ten"
        snap = reg.snapshot()
        assert snap["slo"]["fired_total"] >= 1
        assert snap["slo"]["recent"]
        assert snap["slo"]["config"]["min_assigned_frac"] == 0.95

    def test_slo_breach_lands_event_frames_in_trace(
        self, tmp_path, monkeypatch
    ):
        """End to end over a live wire-v2 session: an impossible
        assigned-frac objective must fire, the breach must land in the
        flight recorder as a tick-anchored EVENT frame, and the obs
        report must surface it — replay ignores the frame (events are
        observational, never solve inputs)."""
        import bench
        from protocol_tpu.obs import report as obs_report
        from protocol_tpu.obs.slo import SLOConfig
        from protocol_tpu.ops.cost import CostWeights
        from protocol_tpu.proto import scheduler_pb2 as pb
        from protocol_tpu.proto import wire
        from protocol_tpu.services.scheduler_grpc import (
            SchedulerBackendClient,
            serve,
        )
        from protocol_tpu.trace import format as tfmt

        path = str(tmp_path / "slo.trace")
        monkeypatch.setenv("PROTOCOL_TPU_TRACE", path)
        # assigned_frac > 1 is unsatisfiable: every tick is bad, so the
        # fast (8, 32) pair must fire the moment 32 ticks land
        server = serve(
            "127.0.0.1:50981", slo=SLOConfig(min_assigned_frac=1.1)
        )
        client = SchedulerBackendClient("127.0.0.1:50981")
        try:
            rng = np.random.default_rng(0)
            ep = bench.synth_providers(rng, 96)
            er = bench.synth_requirements(rng, 96)
            w = CostWeights()
            p_cols = wire.canon_columns(ep, wire.P_WIRE_DTYPES)
            r_cols = wire.canon_columns(er, wire.R_WIRE_DTYPES)
            fp = wire.epoch_fingerprint(
                p_cols, r_cols, w, "native-mt:1", 32, 0.02, 0
            )
            req = pb.AssignRequestV2(
                providers=wire.encode_providers_v2(ep),
                requirements=wire.encode_requirements_v2(er),
                weights=pb.CostWeights(
                    price=w.price, load=w.load, proximity=w.proximity,
                    priority=w.priority,
                ),
                kernel="native-mt:1", top_k=32, eps=0.02,
            )
            resp = client.open_session(wire.chunk_snapshot("ten@s", fp, req))
            assert resp.ok, resp.error
            churn = np.random.default_rng(1)
            for tick in range(1, 36):
                rows = np.sort(
                    churn.choice(96, 2, replace=False).astype(np.int32)
                )
                price = p_cols["price"].copy()
                price[rows] = churn.uniform(0.5, 4.0, rows.size).astype(
                    np.float32
                )
                p_cols["price"] = price
                d = pb.AssignDeltaRequest(
                    session_id="ten@s", epoch_fingerprint=fp, tick=tick
                )
                d.provider_rows.CopyFrom(wire.blob(rows, np.int32))
                d.providers.CopyFrom(
                    wire.encode_providers_v2(wire.take_rows(p_cols, rows))
                )
                dr = client.assign_delta(d)
                assert dr.session_ok, dr.error
            snap = server.servicer.obs.snapshot()
            assert snap["slo"]["fired_total"] >= 1
            assert snap["slo"]["fired_by_tenant"].get("ten") >= 1
        finally:
            client.close()
            server.stop(grace=None)
        t = tfmt.read_trace(path)
        fired = [
            e for frame in t.events for e in frame["events"]
            if e["kind"] == "slo" and e["state"] == "fire"
        ]
        assert fired and fired[0]["slo"] == "assigned_frac"
        assert fired[0]["tenant"] == "ten"
        rendered = "\n".join(
            obs_report.quality_table(t.outcomes, t.events)
        )
        assert "SLO alert events in trace" in rendered

    def test_env_config(self):
        cfg = SLOConfig.from_env({
            "PROTOCOL_TPU_SLO_MIN_ASSIGNED": "0.97",
            "PROTOCOL_TPU_SLO_MAX_GAP": "0.04",
        })
        assert cfg.min_assigned_frac == 0.97
        assert cfg.max_gap_per_task == 0.04
        assert cfg.p99_warm_tick_ms is None
        assert cfg.active()
