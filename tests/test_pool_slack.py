"""A pool with idle nodes (ISSUE 29): with fewer tasks than providers
the plan is optimal only if the providers left free are the ones that
should be free, and the forward auction alone does not see to that. The
solve's reverse pass (``ops/sparse.py:_forward_reverse``) does; these
tests hold the served jax path to the exact optimum of the whole
rectangular problem, on the CPU at small sizes.

The marketplaces and the churn are the benchmark's own
(``benchmarks/lib/population.py``, ``population_seed`` 25001, 1% of
providers re-priced and 0.2% of tasks re-rolled a tick), driven through
a ``SolveSession`` as the servicer drives it. The optimum is
``scipy.optimize.linear_sum_assignment`` over the repo's dense
``ops/cost.cost_matrix``; nothing of the sparse path judges itself.

Without the pass (the parent, commit 3feb816; here the same chain with
every eps phase the forward phase alone) every case below is
10 to 30 times outside the 0.025 a task it is held to: cost per task
above the optimum 0.596-0.646 at 512 x 358 (0.0001-0.0003 with the
pass), 0.269-0.314 at 512 x 461 (0.0000-0.0001), 0.711-0.783 at 1,024 x
614 (0.0001-0.0003); the program's own ``idle_price`` 949-966, 503-522
and 2,089-2,105 (0.0 with it), its ``gap_per_task`` 2.66-2.71, 1.10-1.14
and 3.42-3.44 (0.008-0.011).
"""

import copy
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

pytest.importorskip("jax")
pytest.importorskip("scipy")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from lib import harness, population  # noqa: E402

from protocol_tpu.ops import sparse  # noqa: E402
from protocol_tpu.ops.cost import CostWeights, cost_matrix  # noqa: E402
from protocol_tpu.services.session_store import (  # noqa: E402
    SolveSession,
    _pad_cols,
    make_solve_arena,
)

WEIGHTS = {"price": 1.0, "load": 1.0, "proximity": 0.001, "priority": 0.0}
GAP_LIMIT = 0.025          # pool-slack's own limit, cost per task
TICKS = 8
SLACK_CASES = [(512, 358), (512, 461), (1024, 614)]
UNSEATABLE = 1e6


def _optimum(p_cols: dict, r_cols: dict):
    """(dense cost [T, P] with ``UNSEATABLE`` where infeasible, cost of
    the exact optimum, tasks it seats)."""
    from scipy.optimize import linear_sum_assignment

    cost, mask = cost_matrix(
        SimpleNamespace(**p_cols), SimpleNamespace(**r_cols),
        CostWeights(**WEIGHTS),
    )
    dense = np.where(np.asarray(mask), np.asarray(cost, np.float64),
                     UNSEATABLE).T
    rows, picks = linear_sum_assignment(dense)
    seated = dense[rows, picks] < UNSEATABLE
    return dense, float(dense[rows, picks][seated].sum()), int(seated.sum())


def open_session(n_providers: int, n_tasks: int):
    """(the benchmark's marketplace generator, a jax arena, a session
    over both): what the servicer holds after ``OpenSession``."""
    gen = population.Pool(
        np.random.default_rng([25001, 0]), n_providers, n_tasks, 0.01, 0.002,
    )
    arena = make_solve_arena("jax", k=64, threads=0)
    session = SolveSession(
        session_id="slack@t", fingerprint="fp",
        weights=CostWeights(**WEIGHTS), kernel="jax", threads=0, top_k=64,
        p_cols=_pad_cols(copy.deepcopy(gen.p_cols), n_providers),
        r_cols=_pad_cols(copy.deepcopy(gen.r_cols), n_tasks),
        n_providers=n_providers, n_tasks=n_tasks, arena=arena,
    )
    return gen, arena, session


def _chain(n_providers: int, n_tasks: int, blocked: int = 0,
           ticks: int = TICKS):
    """Cold open and ``ticks`` warm ticks; one dict per solve. With
    ``blocked``, the first two warm ticks are the harness's warm-up
    pair instead: that many tasks made unassignable, then put back."""
    gen, arena, session = open_session(n_providers, n_tasks)
    none = np.zeros(0, np.int32)
    special = dict(enumerate(gen.block_tasks(blocked), 1)) if blocked else {}
    out = []
    for tick in range(ticks + 1):
        with session.lock:
            if tick in special:
                rows, vals = special[tick]
                for name, col in gen.r_cols.items():
                    col[rows] = vals[name]
                session.apply_delta(none, {}, rows, vals)
            elif tick:
                session.apply_delta(*gen.next_delta())
            plan = np.asarray(session.solve()[0])
        dense, best, seatable = _optimum(gen.p_cols, gen.r_cols)
        seated = np.flatnonzero(plan >= 0)
        pair = dense[seated, plan[seated]]
        stats = dict(arena.last_stats)
        out.append({
            "tick": tick, "plan": plan.copy(), "seated": int(seated.size),
            "seatable": seatable,
            "dup": int(seated.size - np.unique(plan[seated]).size),
            "infeasible": int((pair >= UNSEATABLE).sum()),
            "gap": (float(pair.sum()) - best) / max(seated.size, 1),
            "stats": stats, "price": np.array(arena.price),
        })
    return out


@pytest.fixture(scope="module")
def chains():
    """Each case's chain, computed once: with the pass, and with every
    eps phase the forward phase alone (the parent's solve)."""
    cache: dict = {}

    def get(case, with_pass=True):
        key = (case, with_pass)
        if key not in cache:
            if with_pass:
                cache[key] = _chain(*case)
            else:
                original = sparse._forward_reverse

                def forward_only(cand_p, cand_c, n_providers, state, eps,
                                 max_iters, frontier, stall_limit,
                                 stats_out, transposed, reserve=None,
                                 band=False):
                    state, stall, _rows, _scans = sparse._phase_adaptive(
                        cand_p, cand_c, n_providers, state, eps=eps,
                        max_iters=max_iters, frontier=frontier, retire=True,
                        stall_limit=stall_limit, stats_out=stats_out,
                    )
                    return state, stall, int(state[0])

                sparse._forward_reverse = forward_only
                try:
                    cache[key] = _chain(*case)
                finally:
                    sparse._forward_reverse = original
        return cache[key]
    return get


@pytest.mark.parametrize("case", SLACK_CASES, ids=lambda c: f"{c[0]}x{c[1]}")
def test_every_tick_is_within_the_limit_of_the_exact_optimum(chains, case):
    for t in chains(case):
        assert t["dup"] == 0 and t["infeasible"] == 0, t["tick"]
        assert t["seated"] == t["seatable"] == case[1], t["tick"]
        assert t["gap"] <= GAP_LIMIT, (t["tick"], t["gap"])
    warm = chains(case)[1:]
    assert all(t["stats"]["cold"] is False for t in warm)
    assert all(
        t["stats"]["eng_free_providers"] == case[0] - case[1] for t in warm
    )
    # the pass worked on every warm tick of a pool with this much slack,
    # and its counters say so
    assert all(t["stats"]["eng_reverse_ms"] > 0 for t in warm)
    assert sum(t["stats"]["eng_free_repriced"] for t in warm) > 0
    assert sum(t["stats"]["eng_reverse_rounds"] for t in warm) > 0


@pytest.mark.parametrize("case", SLACK_CASES, ids=lambda c: f"{c[0]}x{c[1]}")
def test_the_same_chain_without_the_pass_is_outside_the_limit(chains, case):
    without = chains(case, with_pass=False)
    assert all(t["dup"] == 0 and t["infeasible"] == 0 for t in without)
    assert min(t["gap"] for t in without) > 4 * GAP_LIMIT
    assert max(t["gap"] for t in chains(case)) < min(
        t["gap"] for t in without
    ) / 10


@pytest.mark.parametrize("case", SLACK_CASES, ids=lambda c: f"{c[0]}x{c[1]}")
def test_the_certificate_bounds_the_true_gap_and_idle_price_falls(
    chains, case
):
    """``gap_per_task`` is a certificate (a feasible dual point: every
    price at or above 0, the free providers at 0), so it is never under
    the true gap; the pass takes ``idle_price`` from hundreds to 0 by
    moving the dual, the formula of ``obs/quality.py`` is as it was."""
    for t, old in zip(chains(case), chains(case, with_pass=False)):
        s = t["stats"]
        assert s["gap_per_task"] >= t["gap"] - 1e-6, t["tick"]
        assert s["gap_per_task"] <= 0.04, t["tick"]   # perf_floor.json's
        assert 0.0 <= s["idle_price"] <= 1e-3, t["tick"]
        assert old["stats"]["idle_price"] > 100.0, t["tick"]
        assert old["stats"]["gap_per_task"] > 1.0, t["tick"]


@pytest.mark.parametrize("case", [(512, 512), (512, 512, 4)],
                         ids=["512x512", "512x512-4-unservable"])
def test_a_full_pool_keeps_the_parents_plans_bit_for_bit(chains, case):
    """P = T control. A full pool strands providers too (the ones its
    unseatable tail leaves: 1 to 7 a tick on this chain), but it has no
    slack: its free providers are matched by open tasks, or outnumber
    them by the few that nobody can serve (the second case: the
    harness's warm-up pair, four tasks asking for three GPUs), far
    under one in 64 of the pool. So the pass stays out: the plans and
    the program's certificate are the parent's, bit for bit, on every
    tick of the chain, and so on a replay."""
    ours, parents = chains(case), chains(case, with_pass=False)
    again = _chain(*case)
    for x, y, z in zip(ours, parents, again):
        np.testing.assert_array_equal(x["plan"], y["plan"])
        np.testing.assert_array_equal(x["plan"], z["plan"])
        assert x["stats"]["gap_per_task"] == y["stats"]["gap_per_task"]
        assert x["stats"]["eng_reverse_rounds"] == 0
        assert x["stats"]["eng_free_repriced"] == 0
        assert x["stats"]["eng_free_providers"] == 512 - x["seated"]
    if len(case) == 3:
        assert ours[1]["seated"] <= 512 - 4 < ours[2]["seated"]


def test_a_whole_run_of_the_new_cell_is_correct_on_the_cpu():
    """``pool-slack.ticks`` through the benchmark's own harness at 256 x
    154 (two pools, the small size's limits as ``benchmarks/tests``
    sets them): correct, nothing compiled in the window."""
    cell = copy.deepcopy(harness.load_cell(REPO, "pool-slack.ticks"))
    assert cell["config"]["n_tasks"] == 4915
    assert cell["config"]["limits"]["subpool_gap"] == GAP_LIMIT
    cell["config"].update(n_providers=256, n_tasks=154, pools=2)
    cell["config"]["check"].update(acks=6, subpool_tasks=256)
    cell["config"]["limits"].update(subpool_gap=0.2, unassigned_frac=0.05)
    cell["traffic"].update(task_churn=0.02)
    r = harness.run_cell(cell, 2**31 + 7, 3.0, False, require_chip=False)
    assert r["correct"] is True, r["checks"]
    assert r["checks"]["window_compiles"]["value"] == 0
    assert r["checks"]["subpool_gap"]["value"] <= GAP_LIMIT
    assert r["failed"] == 0 and r["attempted"] >= 2
