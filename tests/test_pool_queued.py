"""A full pool with a queue (ISSUE 33): with more tasks than providers
some tasks have to wait, and the plan is optimal only if they are the
right ones. A forward auction cannot end there (the tasks that must wait
keep bidding until every price has climbed to the give-up level), so
the solve's queue phase (``ops/sparse.py:_queue_phase``) seats the free
providers by their own bids and lets the tasks bid under a reserve, the
value of waiting; these tests hold the served jax path to the exact
optimum of the whole rectangular problem, on the CPU at small sizes.

The marketplaces, the churn, the session and the optimum are
``tests/test_pool_slack.py``'s (the benchmark's population,
``population_seed`` 25001, 1% of providers re-priced and 0.2% of tasks
re-rolled a tick; ``scipy.optimize.linear_sum_assignment`` over the
dense ``ops/cost.cost_matrix``; nothing of the sparse path judges
itself).

Without the phase (the parent, commit d96c344; here the same chain with
``_queue_reserve`` saying "no queue") every plan is injective, feasible
and seats all P providers, and which tasks wait is decided by where the
stall breaker stops: cost per seated task above the optimum 1.422 at
the cold open of 512 x 640 and 0.411, 0.434, 0.441, 0.110, 0.175, 0.281
on its warm ticks, 768 rounds each, ``eng_stall_exit`` true on every
one; 0.824 and 0.242, 0.224, 0.213, 0.211 at 2,048 x 2,560; 0.536 and
0.139, 0.131 at 6,554 x 8,192, the cell's size (ISSUE 33's readings),
while the parent's certificate read 0.0096-0.0106. With it: 0.0000-0.0004
on every tick of every case below, 3-114 forward rounds a warm tick.
"""

import copy
import hashlib
import os
import sys

from types import SimpleNamespace

import numpy as np
import pytest

pytest.importorskip("jax")
pytest.importorskip("scipy")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
sys.path.insert(0, os.path.join(REPO, "tests"))

from lib import harness  # noqa: E402
from test_pool_slack import (  # noqa: E402
    GAP_LIMIT, UNSEATABLE, _chain, _optimum, open_session,
)

from protocol_tpu.faults.checkpoint import SessionCheckpointer  # noqa: E402
from protocol_tpu.ops import sparse  # noqa: E402

TICKS = 8
# (providers, tasks): a quarter more tasks than providers as in the
# cell, and BASELINE.json's 4:1 ("64 workers x 256 tasks")
QUEUE_CASES = [(512, 640), (410, 512), (1024, 1280), (256, 1024)]
CERT_LIMIT = 0.04              # scripts/perf_floor.json's, as pool-slack's
PARENT_ROUNDS = 768            # a warm tick of the parent, every case


def _ids(case):
    return "x".join(str(n) for n in case)


@pytest.fixture(scope="module")
def chains():
    """Each case's chain, computed once: with the queue phase, and as
    the parent solved it (no pool is ever said to have a queue)."""
    cache: dict = {}

    def get(case, with_phase=True, **kw):
        key = (case, with_phase, tuple(sorted(kw.items())))
        if key not in cache:
            if with_phase:
                cache[key] = _chain(*case, ticks=TICKS, **kw)
            else:
                original = sparse._queue_reserve
                sparse._queue_reserve = lambda *a, **k: None
                try:
                    cache[key] = _chain(*case, ticks=TICKS, **kw)
                finally:
                    sparse._queue_reserve = original
        return cache[key]
    return get


def _held(t, case):
    """What every tick of a pool with a queue is held to."""
    s = t["stats"]
    assert t["dup"] == 0 and t["infeasible"] == 0, t["tick"]
    assert t["seated"] == t["seatable"], (t["tick"], t["seated"])
    assert t["gap"] <= GAP_LIMIT, (t["tick"], t["gap"])
    # the certificate is one: never under the true gap
    assert s["gap_per_task"] >= t["gap"] - 1e-6, t["tick"]
    assert s["gap_per_task"] <= CERT_LIMIT, t["tick"]
    assert s["waiting_excess"] <= 0.02 * s["eng_waiting_tasks"] + 1e-6


@pytest.mark.parametrize("case", QUEUE_CASES, ids=_ids)
def test_every_tick_is_within_the_limit_of_the_exact_optimum(chains, case):
    P, T = case
    for t in chains(case):
        _held(t, case)
        assert t["seated"] == P, t["tick"]
        assert t["stats"]["eng_waiting_tasks"] == T - P, t["tick"]
        assert t["stats"]["eng_free_providers"] == 0, t["tick"]
    warm = chains(case)[1:]
    assert all(t["stats"]["cold"] is False for t in warm)
    # no warm tick ends by the stall breaker: the queue does not
    # circulate, it waits (the parent: 768 rounds on every one)
    assert not any(t["stats"]["eng_stall_exit"] for t in warm)
    assert max(t["stats"]["eng_rounds_total"] for t in warm) < (
        PARENT_ROUNDS // 4
    )
    # the pass worked, and its counters say so
    assert all(t["stats"]["eng_queue_ms"] > 0 for t in warm)
    assert sum(t["stats"]["eng_queue_rounds"] for t in warm) > 0
    assert all(t["stats"]["eng_reverse_rounds"] == 0 for t in warm)


@pytest.mark.parametrize("case", [(512, 640), (256, 1024)], ids=_ids)
def test_the_parent_lets_the_breaker_decide_and_the_certificate_now_sees_it(
    chains, case
):
    """The same chain as the parent solved it: complete, injective,
    feasible, and 2.7 to 100 times outside the limit; with a queue a
    quarter of the pool every warm tick ends by the breaker (with one
    three times the pool the give-up level is reached first, and decides
    no better). Its certificate said 0.010; with the waiting tasks'
    addend it says what the plan is off by, or more."""
    ours, parents = chains(case), chains(case, with_phase=False)
    assert all(t["dup"] == 0 and t["infeasible"] == 0 for t in parents)
    assert all(t["seated"] == t["seatable"] for t in parents)
    assert min(t["gap"] for t in parents) > 2.5 * GAP_LIMIT
    assert max(t["gap"] for t in parents) > 40 * GAP_LIMIT
    assert max(t["gap"] for t in ours) < min(t["gap"] for t in parents) / 50
    for t in parents[1:] if case == (512, 640) else []:
        s = t["stats"]
        assert s["eng_stall_exit"] is True, t["tick"]
        assert s["eng_rounds_total"] >= PARENT_ROUNDS, t["tick"]
    for t in parents:
        s = t["stats"]
        assert s["gap_per_task"] >= t["gap"] - 1e-6, t["tick"]
        assert s["waiting_excess"] > 50 * max(
            x["stats"]["waiting_excess"] for x in ours
        )
        # what the parent's certificate read on a warm tick: the same
        # sum less the addend, 0.008-0.011 whatever the plan was off by
        old = s["gap_per_task"] - s["waiting_excess"] / t["seated"]
        assert t["tick"] == 0 or old < t["gap"] / 4, (t["tick"], old)


def test_the_harness_warm_up_pair_stays_within_the_limit(chains):
    """Set-up's first two warm ticks (``population.Pool.block_tasks``:
    four tasks made unassignable, then put back) in a pool with a
    queue: four tasks leave it and come back, nobody else moves out of
    the limit."""
    case = (512, 640)
    chain = chains(case, blocked=4)
    for t in chain:
        _held(t, case)
        assert t["seated"] == 512
    assert chain[1]["stats"]["eng_waiting_tasks"] == 640 - 512
    assert not any(t["stats"]["eng_stall_exit"] for t in chain[1:])


def _serve(session, gen_delta):
    with session.lock:
        if gen_delta is not None:
            session.apply_delta(*gen_delta)
        return np.asarray(session.solve()[0]).copy()


def test_a_restored_session_continues_with_the_same_plans(tmp_path):
    """The reserve is dual state: it rides the journal in
    ``SOLVE_STATE`` (one f32, NaN where the pool has no queue) and
    ``restore_state`` puts it back, so a session restored from its
    checkpoint serves the plans and carries the prices of the one that
    never stopped, bit for bit."""
    gen, arena, session = open_session(512, 640)
    assert "queue_reserve" in arena.SOLVE_STATE
    for tick in range(4):
        _serve(session, gen.next_delta() if tick else None)
    assert arena._reserve is not None and arena._reserve < -10.0
    ckpt = SessionCheckpointer(str(tmp_path / "a"))
    with session.lock:
        assert ckpt.flush_locked(session)
    loaded = SessionCheckpointer(str(tmp_path / "a")).load_one(
        session.session_id
    )
    assert loaded is not None
    assert loaded.arena._reserve == arena._reserve
    state = loaded.arena.export_state()
    assert state["queue_reserve"].dtype == np.float32
    assert state["queue_reserve"].shape == (1,)
    for _ in range(4):
        delta = gen.next_delta()
        a = _serve(session, delta)
        b = _serve(loaded, copy.deepcopy(delta))
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(arena.price, loaded.arena.price)
        assert loaded.arena.last_stats["cold"] is False
        assert loaded.arena._reserve == arena._reserve
        for key in ("eng_rounds_total", "eng_queue_rounds",
                    "eng_frontier_rows", "waiting_excess"):
            assert loaded.arena.last_stats[key] == arena.last_stats[key]


def test_restoring_a_journal_without_a_queue_clears_a_carried_reserve():
    """``restore_state`` sets the reserve to what the journal holds, and
    NaN is "no queue": an arena that carried a reserve and is handed a
    full pool's state must not take it into the next warm solve (it
    would drop the carried retirement mask there)."""
    gen, arena, session = open_session(128, 128)
    _serve(session, None)
    assert arena._reserve is None
    state = arena.export_state()
    assert np.isnan(state["queue_reserve"][0])
    arena._reserve = -30.0
    arena.restore_state(
        SimpleNamespace(**session.p_cols), SimpleNamespace(**session.r_cols),
        state,
    )
    assert arena._reserve is None
    _serve(session, gen.next_delta())
    assert arena.last_stats["cold"] is False


def test_a_pool_that_gains_and_loses_its_queue_stays_within_the_limit():
    """One session, 512 providers, 640 task rows of which 200 are not
    live at the open (440 tasks: a pool with slack, the reverse pass's),
    then all live (a queue: the duals of the other regime are re-grounded
    by the cold ladder, once), then 200 leave again. Every tick is held
    to the exact optimum of the pool as it stands; the reserve is
    carried while the queue lasts and dropped with it."""
    P, T, away = 512, 640, np.arange(200, dtype=np.int32)
    gen, arena, session = open_session(P, T)
    none = np.zeros(0, np.int32)

    def set_live(live: bool):
        gen.r_cols["valid"][away] = live
        vals = {n: a[away] for n, a in gen.r_cols.items()}
        return none, {}, away, vals

    script = {0: False, 3: True, 6: False}
    reserves, regimes = [], []
    for tick in range(9):
        if tick in script:
            delta = set_live(script[tick])
            if tick == 0:
                session.r_cols["valid"][away] = False
                delta = None
        else:
            delta = gen.next_delta()
        plan = _serve(session, delta)
        dense, best, seatable = _optimum(gen.p_cols, gen.r_cols)
        seated = np.flatnonzero(plan >= 0)
        pair = dense[seated, plan[seated]]
        assert np.unique(plan[seated]).size == seated.size, tick
        assert int((pair >= UNSEATABLE).sum()) == 0, tick
        assert seated.size == seatable, (tick, seated.size, seatable)
        gap = (float(pair.sum()) - best) / seated.size
        assert gap <= GAP_LIMIT, (tick, gap)
        s = arena.last_stats
        assert s["gap_per_task"] >= gap - 1e-6, tick
        reserves.append(arena._reserve)
        regimes.append((s["eng_waiting_tasks"], s["eng_free_providers"]))
    assert regimes[0] == (0, P - (T - 200)) and regimes[3] == (T - P, 0)
    assert [r is None for r in reserves] == [
        True, True, True, False, False, False, True, True, True,
    ]
    assert reserves[3] == reserves[4] == reserves[5]


@pytest.mark.parametrize("where", ["host", "device"])
def test_the_reserve_is_anchored_once_and_carried_while_the_anchor_holds(where):
    """``_queue_reserve`` on hand-built lists, as the arena holds them
    (NumPy: counted where they lie) and as a device array: no queue, no
    reserve (whatever was carried); a queue anchors at the give-up level
    ``-(2 max cost + 10)``; a carried reserve is kept while costs stay
    within the anchor's band and replaced once they have left it (the
    warm solve then re-grounds its duals, ``assign_auction_sparse_warm``)."""
    import jax.numpy as jnp

    put = np.asarray if where == "host" else jnp.asarray

    def lists(n_tasks, n_providers, top):
        cand_p = np.tile(np.arange(n_providers, dtype=np.int32), (n_tasks, 1))
        cand_c = np.full(cand_p.shape, 1.0, np.float32)
        cand_c[0, 0] = top
        return put(cand_p), put(cand_c)

    square = lists(8, 8, 10.0)
    assert sparse._queue_reserve(*square, 8, None, None) is None
    assert sparse._queue_reserve(*square, 8, -30.0, None) is None
    # one task too many of eight is a queue
    queued = lists(9, 8, 10.0)
    assert sparse._queue_reserve(*queued, 8, None, None) == -30.0
    assert sparse._queue_reserve(*queued, 8, -28.5, None) == -28.5
    stats: dict = {}
    assert sparse._queue_reserve(*lists(9, 8, 13.0), 8, -30.0, stats) == -30.0
    assert stats["queue_rounds"] == 0 and stats["queue_ms"] > 0
    # costs doubled, or fell to a fifth: the anchor no longer holds them
    assert sparse._queue_reserve(*lists(9, 8, 20.0), 8, -30.0, None) == -50.0
    assert sparse._queue_reserve(*lists(9, 8, 2.0), 8, -30.0, None) == -14.0
    # no margin: 65 tasks over 64 providers is a queue, and so is 129
    # over 128 (the band next to P = T: the queue's forward
    # phase runs its chains to their end); 128 over 128 is none
    assert sparse._queue_reserve(*lists(65, 64, 1.0), 64, None, None) == -12.0
    assert sparse._queue_reserve(*lists(129, 128, 1.0), 128, None, None) == -12.0
    assert sparse._queue_reserve(*lists(128, 128, 1.0), 128, None, None) is None
    # the count is of tasks that list a provider and of providers some
    # task lists: an empty slot is nobody's, a task with an empty list
    # does not queue, and an empty slot's cost anchors nothing
    cand_p, cand_c = (np.array(a) for a in lists(10, 8, 10.0))
    cand_p[9] = -1
    cand_c[9] = 99.0
    assert sparse._queue_reserve(put(cand_p), put(cand_c), 8, None, None) == -30.0
    cand_p[8] = -1
    assert sparse._queue_reserve(put(cand_p), put(cand_c), 8, None, None) is None
    cand_p[:, 7] = -1        # provider 7 is on no list: 8 tasks, 7 seats
    assert sparse._queue_reserve(put(cand_p), put(cand_c), 8, None, None) == -30.0


PINNED = {
    # sha256 over every tick's plan (i32) and carried prices (f32), cold
    # open and eight warm ticks, as the parent (commit d96c344) serves
    # them on XLA:CPU
    (512, 512):
        "88e6eaba944928dd093bb7a95310a4f2c97db1332ff5f46779a4f683ac8b6f5a",
    (512, 358):
        "f025ccb439c3000f92a6fb3f01df5af5e62ddfee9ba2a3b4a1f0c35796fb6535",
}


@pytest.mark.parametrize("case", sorted(PINNED), ids=_ids)
def test_a_pool_without_a_queue_keeps_the_parents_plans_and_prices(case):
    """Where nobody has to wait (a full pool, with its tail of a few
    tasks no free seat can take) and where providers do (a pool with
    slack), nothing changed: plans and prices are the parent's, bit
    for bit, and the queue pass never ran."""
    gen, arena, session = open_session(*case)
    h = hashlib.sha256()
    for tick in range(9):
        plan = _serve(session, gen.next_delta() if tick else None)
        h.update(np.ascontiguousarray(plan, np.int32).tobytes())
        h.update(np.ascontiguousarray(arena.price, np.float32).tobytes())
        assert arena.last_stats["eng_queue_rounds"] == 0
        assert arena._reserve is None
        assert arena.last_stats["waiting_excess"] == 0.0
    assert h.hexdigest() == PINNED[case]


def test_a_whole_run_of_the_new_cell_is_correct_on_the_cpu():
    """``pool-queued.ticks`` through the benchmark's own harness at 205 x
    256 (two pools, the small size's limits as ``benchmarks/tests`` sets
    them): correct, nothing compiled in the window, a fifth of the
    tasks waiting on every judged ack."""
    cell = copy.deepcopy(harness.load_cell(REPO, "pool-queued.ticks"))
    cfg = cell["config"]
    assert (cfg["n_providers"], cfg["n_tasks"]) == (6554, 8192)
    assert cfg["limits"]["subpool_gap"] == GAP_LIMIT
    # every provider seated reads 1,638 / 8,192; the limit lies between
    # that and the two free providers of the one faulted reading
    assert 1638 / 8192 < cfg["limits"]["unassigned_frac"] < 1640 / 8192
    assert cfg["server"]["ckpt_every"] == 1
    cfg.update(n_providers=205, n_tasks=256, pools=2)
    cfg["check"].update(acks=6, subpool_tasks=256)
    cfg["limits"].update(subpool_gap=0.2, unassigned_frac=0.25)
    cell["traffic"].update(task_churn=0.02)
    r = harness.run_cell(cell, 2**31 + 11, 3.0, False, require_chip=False)
    assert r["correct"] is True, r["checks"]
    assert r["checks"]["window_compiles"]["value"] == 0
    assert r["checks"]["subpool_gap"]["value"] <= GAP_LIMIT
    assert 0.19 <= r["checks"]["unassigned_frac"]["value"] <= 0.21
    assert r["failed"] == 0 and r["attempted"] >= 2
