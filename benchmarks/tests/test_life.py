"""Tests of the life events in the tick stream (arrivals, completions,
joins, leaves), of set-up's visit to both regimes, and of what
``correct`` holds a pool to whose live size moves or that has a queue.

    python -m pytest benchmarks/tests -q
"""

import copy
import json
import os
import zlib

import numpy as np
import pytest

from lib import faults, harness, population, reference

ROOT = os.path.dirname(harness.BENCH_DIR)
WEIGHTS = {"price": 1.0, "load": 1.0, "proximity": 0.001, "priority": 0.0}
HARDWARE = ("gpu_count", "gpu_mem_mb", "gpu_model_id", "has_gpu", "has_cpu",
            "cpu_cores", "ram_mb", "storage_gb", "lat", "lon",
            "has_location")
LIFE = {"provider_leave": 0.01, "provider_join": 0.05, "task_end": 0.05,
        "task_arrive": 0.04, "arrive_wave_ticks": 12, "arrive_wave_amp": 0.8}


def _crc(delta, crc=0):
    prow, p_vals, trow, r_vals = delta
    crc = zlib.crc32(prow.tobytes(), crc)
    for name in sorted(p_vals):
        crc = zlib.crc32(p_vals[name].tobytes(), crc)
    crc = zlib.crc32(trow.tobytes(), crc)
    for name in sorted(r_vals):
        crc = zlib.crc32(r_vals[name].tobytes(), crc)
    return crc


def _pool(n_p=500, n_t=500, live_p=400, live_t=400, life=LIFE, seed=38):
    return population.Pool(
        np.random.default_rng([seed, 0]), n_p, n_t, 0.01, 0.01,
        life=population.life_of(life),
        life_rng=np.random.default_rng([seed, 0, population.LIFE_STREAM]),
        providers_live=live_p, tasks_live=live_t,
    )


# The first 50 deltas of pool 0 of each accepted cell as the parent's
# population.py (PR 36's, before life events) gave them: the oversized
# one first, as set-up sends it. crc32 chained over rows and columns,
# read after deltas 1, 10 and 50.
PARENT_STREAM = {
    "pool-large.ticks": {1: 185388628, 10: 2907277642, 50: 922019833},
    "pool-slack.ticks": {1: 1747701943, 10: 1500134170, 50: 3503827395},
    "pool-queued.ticks": {1: 3236545448, 10: 579829008, 50: 3148699148},
}


@pytest.mark.parametrize("workload", sorted(PARENT_STREAM))
def test_a_mix_without_life_events_sends_the_parents_stream(workload):
    cell = harness.load_cell(ROOT, workload)
    cfg, traffic = cell["config"], cell["traffic"]
    assert population.life_of(traffic) == {}
    assert population.life_of(dict(traffic, task_end=0, task_arrive=0.0,
                                   arrive_wave_ticks=48)) == {}
    gen = population.Pool(
        np.random.default_rng([cfg["population_seed"], 0]),
        cfg["n_providers"], cfg["n_tasks"],
        traffic["provider_churn"], traffic["task_churn"],
        life=population.life_of(traffic),
        life_rng=np.random.default_rng(
            [cfg["population_seed"], 0, population.LIFE_STREAM]),
        providers_live=cfg.get("providers_live"),
        tasks_live=cfg.get("tasks_live"),
    )
    crc, got = 0, {}
    for i in range(1, 51):
        crc = _crc(gen.next_delta(
            harness.WARMUP_OVERSIZE if i == 1 else 1.0), crc)
        if i in PARENT_STREAM[workload]:
            got[i] = crc
    assert got == PARENT_STREAM[workload]
    assert gen.arrivals_dropped == 0
    assert gen.p_cols["valid"].all() and gen.r_cols["valid"].all()


def test_life_events_send_the_same_stream_on_every_run():
    a, b = _pool(), _pool()
    assert int(a.p_cols["valid"].sum()) == int(a.r_cols["valid"].sum()) == 400
    assert (a.p_cols["valid"] == b.p_cols["valid"]).all()
    assert [_crc(a.next_delta()) for _ in range(30)] == [
        _crc(b.next_delta()) for _ in range(30)]
    # which rows are live at the open is the life generator's draw: the
    # columns themselves are the ones a pool without life events holds
    plain = population.Pool(np.random.default_rng([38, 0]), 500, 500,
                            0.01, 0.01)
    fresh = _pool()
    for mine, theirs in ((fresh.p_cols, plain.p_cols),
                         (fresh.r_cols, plain.r_cols)):
        for name in mine:
            if name != "valid":
                assert (mine[name] == theirs[name]).all(), name


def test_a_dead_row_is_left_alone_and_a_returning_provider_is_the_same():
    gen = _pool()
    hardware = {n: gen.p_cols[n].copy() for n in HARDWARE}
    returned = refilled = 0
    for _ in range(60):
        p_before = {n: a.copy() for n, a in gen.p_cols.items()}
        r_before = {n: a.copy() for n, a in gen.r_cols.items()}
        prow, p_vals, trow, r_vals = gen.next_delta()
        for before, cols, rows, vals in (
                (p_before, gen.p_cols, prow, p_vals),
                (r_before, gen.r_cols, trow, r_vals)):
            assert (np.diff(rows) > 0).all()        # sorted, each once
            untouched = np.ones(before["valid"].shape[0], bool)
            untouched[rows] = False
            for name, col in cols.items():
                assert (vals[name] == col[rows]).all()
                # the delta is everything that changed
                assert (col[untouched] == before[name][untouched]).all()
            # a row that was dead and stays dead is in no delta
            assert (before["valid"][rows] | cols["valid"][rows]).all()
        back = prow[~p_before["valid"][prow] & gen.p_cols["valid"][prow]]
        returned += back.size
        changed = (gen.p_cols["price"][back] != p_before["price"][back]) | (
            gen.p_cols["load"][back] != p_before["load"][back])
        assert changed.all()                        # drawn anew
        refilled += int(
            (~r_before["valid"][trow] & gen.r_cols["valid"][trow]).sum())
        for name in HARDWARE:                       # the same machines
            assert (gen.p_cols[name] == hardware[name]).all(), name
    assert returned > 50 and refilled > 500


def _wave(tick, life=LIFE):
    return 1.0 + life["arrive_wave_amp"] * np.sin(
        2 * np.pi * tick / life["arrive_wave_ticks"])


def test_live_counts_follow_the_wave_and_cross_the_providers():
    """How many rows are live is arithmetic on the mix's shares, whatever
    the generator draws: the test works the counts out tick by tick and
    finds the pool at them, the live tasks crossing the live providers
    on the ticks the arithmetic says."""
    gen = _pool()
    here, live, n_p, n_t = 400, 400, 500, 500
    crossings, found, over = [], [], False
    for tick in range(1, 61):
        here += -int(here * LIFE["provider_leave"]) + int(
            (n_p - here) * LIFE["provider_join"])
        arrive = int(n_t * LIFE["task_arrive"] * _wave(tick))
        assert arrive <= n_t - live                 # nothing dropped
        live += -int(live * LIFE["task_end"]) + arrive
        gen.next_delta()
        assert int(gen.p_cols["valid"].sum()) == here
        assert int(gen.r_cols["valid"].sum()) == live
        if (live > here) != over:
            over = live > here
            crossings.append(tick)
        if (gen.r_cols["valid"].sum() > gen.p_cols["valid"].sum()) != (
                len(found) % 2 == 1):
            found.append(tick)
    assert found == crossings and len(crossings) >= 6
    assert gen.arrivals_dropped == 0 and gen.ticks == 60


def test_an_arrival_without_a_dead_row_is_dropped_and_counted():
    life = {"task_arrive": 0.1}
    gen = _pool(n_p=64, n_t=64, live_p=None, live_t=60, life=life)
    gen.next_delta()                    # 6 arrive, 4 rows are dead
    assert int(gen.r_cols["valid"].sum()) == 64
    assert gen.arrivals_dropped == 2
    gen.next_delta()
    gen.next_delta()
    assert gen.arrivals_dropped == 14 and gen.r_cols["valid"].all()


def test_the_oversized_tick_scales_the_life_events():
    def moved(scale):
        """Rows that left, joined, ended and arrived in one tick."""
        gen = _pool()
        before = gen.p_cols["valid"].copy(), gen.r_cols["valid"].copy()
        gen.next_delta(scale)
        return [
            int((was & ~now).sum()) for was, now in (
                (before[0], gen.p_cols["valid"]),
                (~before[0], ~gen.p_cols["valid"]),
                (before[1], gen.r_cols["valid"]),
                (~before[1], ~gen.r_cols["valid"]))
        ]

    # leave 1% of 400, join 5% of 100, end 5% of 400, arrive 4% of 500
    # at the wave's first tick (1 + 0.8 sin(2 pi / 12) = 1.4)
    assert moved(1.0) == [4, 5, 20, 28]
    assert moved(2.0) == [8, 10, 40, 56]


def test_set_ups_visit_takes_the_pool_through_both_regimes_and_back():
    gen = _pool()
    valid = gen.r_cols["valid"].copy()
    held = {n: a.copy() for n, a in gen.r_cols.items()}
    seen = valid.copy()
    counts = []
    for rows, vals in gen.regime_visits(1 / 32):
        assert (np.diff(rows) > 0).all()
        for name, col in vals.items():
            if name != "valid":
                assert (col == gen.r_cols[name][rows]).all()
        seen[rows] = vals["valid"]
        counts.append(int(seen.sum()))
    # 400 providers: 13 under, 13 over, and back where the stream is
    assert counts == [387, 413, 400] and (seen == valid).all()
    for name, col in gen.r_cols.items():            # nothing applied
        assert (col == held[name]).all()
    # a pool already far under is taken no further, and one that has no
    # dead rows left stays as full as it is
    slack = _pool(live_t=300)
    low, high, back = slack.regime_visits(1 / 32)
    assert low[0].size == 0 and high[0].size == 113 and back[0].size == 113
    full = _pool(n_t=405, live_t=405)
    low, high, back = full.regime_visits(1 / 32)
    assert low[0].size == 18 and high[0].size == 18 and back[0].size == 0


# ---- what correct holds a plan to where counts move ------------------

def _optimum(p, r):
    from scipy.optimize import linear_sum_assignment

    n_p, n_t = p["valid"].shape[0], r["valid"].shape[0]
    cost, ok = reference.block_costs(
        p, r, np.arange(n_p), np.arange(n_t), WEIGHTS)
    rows, cols = linear_sum_assignment(
        np.where(ok, cost, reference.UNSEATABLE))
    plan = np.full(n_t, -1, np.int32)
    plan[rows] = np.where(ok[rows, cols], cols, -1)
    return plan, cost, ok


def _judge(p, r, plan, subpool=10_000):
    return reference.judge_plan(
        p, r, plan, WEIGHTS, np.random.default_rng(0), subpool)


def test_unseated_excess_counts_only_tasks_a_live_provider_was_left_for():
    rng = np.random.default_rng(4)
    p, r = population.providers(rng, 24), population.requirements(rng, 40)
    # every provider takes every task: the counts alone decide who sits
    r["gpu_count"][:] = -1
    r["gpu_mem_min"][:] = -1
    r["gpu_model_constrained"][:] = False
    r["cpu_cores"][:] = r["ram_mb"][:] = r["storage_gb"][:] = -1
    p["valid"][:4] = False              # 20 live providers
    r["valid"][30:] = False             # 30 live tasks: a queue of 10
    plan, _, ok = _optimum(p, r)
    assert ok[:30, 4:].all() and int((plan >= 0).sum()) == 20
    got = _judge(p, r, plan)
    assert got["queued_tasks"] == 10 and got["idle_providers"] == 0
    assert got["unassigned_frac"] == pytest.approx(10 / 30)
    assert got["unseated_excess_frac"] == 0.0       # a queue fully seated
    assert got["subpool_gap"] == pytest.approx(0.0, abs=1e-9)
    one_free = plan.copy()
    one_free[np.flatnonzero(plan >= 0)[0]] = -1
    got = _judge(p, r, one_free)
    assert got["unseated_excess_frac"] == pytest.approx(1 / 30)
    # square: every unseated task is one a provider was left for
    r["valid"][20:] = False
    plan, _, _ = _optimum(p, r)
    got = _judge(p, r, plan)
    assert got["queued_tasks"] == got["idle_providers"] == 0
    assert got["unassigned_frac"] == got["unseated_excess_frac"] == 0.0
    open_two = plan.copy()
    open_two[:2] = -1
    got = _judge(p, r, open_two)
    assert got["unassigned_frac"] == got["unseated_excess_frac"] == 0.1
    # idle nodes: the same
    r["valid"][12:] = False
    plan, _, _ = _optimum(p, r)
    got = _judge(p, r, plan)
    assert got["idle_providers"] == 8 and got["unseated_excess_frac"] == 0.0


def test_the_reference_sees_a_wrong_task_waiting():
    """In a full pool with a queue the sub-pool is drawn from every live
    task: seating a task that should wait in the place of one that
    should sit is a permutation among the seated no longer, and reads
    as cost above the optimum at the same number of seats."""
    rng = np.random.default_rng(11)
    p, r = population.providers(rng, 48), population.requirements(rng, 64)
    plan, cost, ok = _optimum(p, r)
    seated = np.flatnonzero(plan >= 0)
    waiting = np.flatnonzero(plan < 0)
    assert seated.size < 64 and _judge(p, r, plan)["subpool_gap"] == (
        pytest.approx(0.0, abs=1e-9))
    # the dearest swap: a waiting task takes a seated task's provider
    gain = np.where(ok[np.ix_(waiting, plan[seated])],
                    cost[np.ix_(waiting, plan[seated])]
                    - cost[seated, plan[seated]][None, :], -np.inf)
    w, s = np.unravel_index(np.argmax(gain), gain.shape)
    wrong = plan.copy()
    wrong[waiting[w]], wrong[seated[s]] = plan[seated[s]], -1
    got = _judge(p, r, wrong)
    assert got["dup_providers"] == got["infeasible_pairs"] == 0
    assert got["unseated_excess_frac"] == _judge(
        p, r, plan)["unseated_excess_frac"]           # as many seated
    assert got["subpool_gap"] == pytest.approx(gain[w, s] / seated.size)
    assert got["subpool_gap"] > 0.01
    # a sample still holds a quarter of the tasks to it, whoever waits
    part = _judge(p, r, wrong, subpool=16)
    assert part["subpool_gap"] >= 0.0
    # an optimum that seats more of the sub-pool than the plan did is
    # compared at the plan's count of seats, without its dearest pairs:
    # a plan that left just those open reads no gap (the seats it is
    # short are the other number's), one that left cheap ones open does
    dearest = seated[np.argsort(cost[seated, plan[seated]])[-3:]]
    fewer = plan.copy()
    fewer[dearest] = -1
    got = _judge(p, r, fewer)
    assert got["unseated_excess_frac"] == pytest.approx(3 / 64)
    assert got["subpool_gap"] == pytest.approx(0.0, abs=1e-9)
    cheapest = seated[np.argsort(cost[seated, plan[seated]])[:3]]
    fewer = plan.copy()
    fewer[cheapest] = -1
    assert _judge(p, r, fewer)["subpool_gap"] > 0.01


# ---- whole runs on the CPU -------------------------------------------

def _breathing():
    """A cut-down breathing pool: 256 providers, all there; 512 task
    rows of which 256 are live at the open; a fifth of the live tasks
    end a tick and as many arrive on average, in a wave of eight ticks
    that takes the pool from ~60 idle nodes to a queue of ~60 and back,
    twice in a list of 16 ticks. (Providers stay: with a dead provider
    row the program's reverse pass runs to its budget at this size,
    PERF.md s7, and the test is of the yardstick.)"""
    cell = copy.deepcopy(harness.load_cell(ROOT, "pool-large.ticks"))
    cell["ticks"] = 16
    cell["config"].update(n_providers=256, n_tasks=512, tasks_live=148,
                          pools=1, population_seed=38001)
    cell["config"]["check"].update(acks=8, subpool_tasks=256)
    limits = cell["config"]["limits"]
    del limits["unassigned_frac"]
    limits.update(subpool_gap=0.2, unseated_excess_frac=0.05,
                  arrivals_dropped=0)
    cell["traffic"].update(
        task_churn=0.02, task_end=0.2, task_arrive=0.1,
        arrive_wave_ticks=8, arrive_wave_amp=0.9)
    return cell


def _run_and_list(cell, seed, monkeypatch):
    """A whole run on the CPU: its result line, and every tick sent
    from the cold open on as (tick, waiting tasks, free providers,
    plan's crc)."""
    sent = []
    send_tick = harness.send_tick

    def listing(rs, pool, due_s, delta=None):
        rec = send_tick(rs, pool, due_s, delta)
        # (a delta with no rows is answered from the retransmit cache)
        sent.append((rec["tick"],
                     rec["stats"].get("eng_waiting_tasks", 0),
                     rec["stats"].get("eng_free_providers", 0),
                     zlib.crc32(rec["plan"].tobytes())))
        return rec

    with monkeypatch.context() as patch:
        patch.setattr(harness, "send_tick", listing)
        return harness.run_cell(cell, seed, 120.0, False,
                                require_chip=False), sent


def test_a_whole_run_with_life_events_is_correct_in_both_regimes(
        monkeypatch):
    r, sent = _run_and_list(_breathing(), 2**31 + 38, monkeypatch)
    assert r["correct"] is True, r["checks"]
    checks = {k: v["value"] for k, v in r["checks"].items()}
    assert checks["window_compiles"] == 0 and checks["arrivals_dropped"] == 0
    assert checks["unseated_excess_frac"] <= 0.05
    assert "unassigned_frac" not in checks
    assert list(r)[-1] == "checks"
    # set-up: two blocked-task ticks, the oversized one, then pool 0
    # with idle nodes, with a queue, and back; three rounds; the list
    assert r["window"]["first_tick"] == 10 and r["window"]["ticks_short"] == 0
    by_tick = {t: (waiting, free) for t, waiting, free, _ in sent}
    assert by_tick[4][1] >= 9 and by_tick[4][0] == 0       # idle nodes
    assert by_tick[5][0] >= 9 and by_tick[5][1] == 0       # a queue
    window = [(w, f) for t, w, f, _ in sent if t >= 10]
    assert len(window) == 16
    assert sum(w >= 8 for w, f in window) >= 4      # ticks with a queue
    assert sum(f >= 8 for w, f in window) >= 4      # ticks with idle nodes
    # and the reference judged plans of both
    assert checks["judged_queue_acks"] >= 1
    assert checks["judged_slack_acks"] >= 1
    assert checks["judged_queue_acks"] + checks["judged_slack_acks"] <= 8
    json.dumps(r)
    # another --seed is sent the same stream and answers the same plans
    again, sent_again = _run_and_list(_breathing(), 7, monkeypatch)
    assert again["correct"] is True, again["checks"]
    assert [(t, crc) for t, *_, crc in sent] == [
        (t, crc) for t, *_, crc in sent_again]


def _queued():
    """``pool-queued.ticks`` at a size a test run can hold: two pools of
    205 providers and 256 tasks, 51 waiting; the reference re-solves
    the whole pool."""
    cell = copy.deepcopy(harness.load_cell(ROOT, "pool-queued.ticks"))
    cell["ticks"] = 4
    cell["config"].update(n_providers=205, n_tasks=256, pools=2)
    cell["config"]["check"].update(acks=6, subpool_tasks=256)
    cell["traffic"].update(task_churn=0.02)
    return cell


@pytest.mark.parametrize("fault,correct", [
    ("sound", True), ("queue_pass_skipped", False)])
def test_a_queue_left_to_the_stall_breaker_is_not_correct(fault, correct):
    """The solve before PR 33, which the reference's old draw (seated
    tasks only) read as correct: sound runs read a gap of 0.0002-0.0004
    here, the fault 0.28-0.40 (three seeds each, CPU)."""
    with faults.planted(fault, _queued()) as cell:
        r = harness.run_cell(cell, 11, 60.0, False, require_chip=False)
    gap = r["checks"]["subpool_gap"]
    assert r["correct"] is correct, r["checks"]
    assert r["checks"]["judged_queue_acks"]["value"] == 6
    assert (gap["value"] <= gap["limit"]) is correct
    if not correct:
        assert gap["value"] > 4 * gap["limit"]
