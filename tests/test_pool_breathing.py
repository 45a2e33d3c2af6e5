"""A pool that breathes: providers leave and come back, tasks
end and arrive in a wave, so the live tasks cross the live providers
both ways and the solve crosses between its regimes: a queue (the queue
pass and the reserve), idle nodes (the reverse pass) and the band
between. These tests hold the served jax path to the exact optimum of
the pool as it stands on every tick, on the CPU at 256 provider rows.

The marketplace, the churn and the life events are the benchmark's own
(``benchmarks/lib/population.py``, ``population_seed`` 38001), driven
through a ``SolveSession`` as the servicer drives it; the optimum is
``scipy.optimize.linear_sum_assignment`` over the dense
``ops/cost.cost_matrix`` of the live rows (``tests/test_pool_slack.py``'s
``_optimum``); nothing of the sparse path judges itself.

What the parent (commit 529b96d) did on the first chain below (205 of
256 provider rows live, 2 leaving and 2 coming back a tick): the reverse
pass ran its whole budget, 20,224 rounds, on the ticks that left a queue
(certificate 16.5-21.0); two providers coming back a tick at a stale
price freed ~55 seats, and the queue pass ran 1,900-5,707 rounds for
them; the band's ticks read 0.028-0.32 a seated task off the optimum
and a certificate of -0.51 to -5,670,096 (under the true gap).
"""

import copy
import hashlib
import os
import sys

import numpy as np
import pytest

pytest.importorskip("jax")
pytest.importorskip("scipy")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
sys.path.insert(0, os.path.join(REPO, "tests"))

from lib import harness, population  # noqa: E402
from test_pool_slack import GAP_LIMIT, UNSEATABLE, WEIGHTS, _optimum  # noqa: E402

from protocol_tpu.faults.checkpoint import SessionCheckpointer  # noqa: E402
from protocol_tpu.ops.cost import CostWeights  # noqa: E402
from protocol_tpu.services.session_store import (  # noqa: E402
    SolveSession,
    _pad_cols,
    make_solve_arena,
)

N_P, N_T, SEED = 256, 512, 38001
# a fifth of the live tasks end a tick and a tenth of the rows arrive,
# in a wave of eight ticks: the live tasks cross the live providers
# every few ticks; 1% of the live providers leave and 4% of the dead
# ones come back (about two each a tick)
LIFE = {"provider_leave": 0.01, "provider_join": 0.04, "task_end": 0.2,
        "task_arrive": 0.1, "arrive_wave_ticks": 8, "arrive_wave_amp": 0.9}
STILL = dict(LIFE, provider_leave=0.0, provider_join=0.0)
TICKS = 24
CERT_LIMIT = 0.04              # scripts/perf_floor.json's, as pool-slack's
CROSS_ROUNDS = 2000            # the parent's crossing: 20,224


def open_breathing(live_p=205, live_t=148, life=LIFE):
    """(the generator, a jax arena, a session over both): a pool of
    ``N_P`` x ``N_T`` rows, ``live_p`` / ``live_t`` of them live at the
    open."""
    gen = population.Pool(
        np.random.default_rng([SEED, 0]), N_P, N_T, 0.01, 0.02,
        life=population.life_of(life),
        life_rng=np.random.default_rng([SEED, 0, population.LIFE_STREAM]),
        providers_live=live_p, tasks_live=live_t,
    )
    return (gen, *_open_session(gen))


def _open_session(gen):
    """(a jax arena, a session over the generator's rows as they are)."""
    arena = make_solve_arena("jax", k=64, threads=0)
    session = SolveSession(
        session_id="breathing@t", fingerprint="fp",
        weights=CostWeights(**WEIGHTS), kernel="jax", threads=0, top_k=64,
        p_cols=_pad_cols(copy.deepcopy(gen.p_cols), N_P),
        r_cols=_pad_cols(copy.deepcopy(gen.r_cols), N_T),
        n_providers=N_P, n_tasks=N_T, arena=arena,
    )
    return arena, session


def _serve(session, delta):
    with session.lock:
        if delta is not None:
            session.apply_delta(*delta)
        return np.asarray(session.solve()[0]).copy()


def _chain(live_p=205, live_t=148, life=LIFE, ticks=TICKS):
    """Cold open and ``ticks`` warm ticks; one dict per solve."""
    gen, arena, session = open_breathing(live_p, live_t, life)
    out = []
    for tick in range(ticks + 1):
        plan = _serve(session, gen.next_delta() if tick else None)
        dense, best, seatable = _optimum(gen.p_cols, gen.r_cols)
        seated = np.flatnonzero(plan >= 0)
        pair = dense[seated, plan[seated]]
        out.append({
            "tick": tick, "plan": plan, "seated": int(seated.size),
            "seatable": seatable,
            "live": (int(gen.p_cols["valid"].sum()),
                     int(gen.r_cols["valid"].sum())),
            "dup": int(seated.size - np.unique(plan[seated]).size),
            "infeasible": int((pair >= UNSEATABLE).sum()),
            "dead_seated": int(
                (~gen.p_cols["valid"][plan[seated]]).sum()
                + (~gen.r_cols["valid"][seated]).sum()
            ),
            "gap": (float(pair.sum()) - best) / max(seated.size, 1),
            "stats": dict(arena.last_stats), "price": np.array(arena.price),
            "regime": arena._regime,
        })
    return out


@pytest.fixture(scope="module")
def chains():
    cache: dict = {}

    def get(name):
        if name not in cache:
            cache[name] = _chain(**CHAINS[name])
        return cache[name]
    return get


CHAINS = {
    # 51 dead provider rows at the open, two leave and two come
    # back a tick
    "breathing": {},
    # every provider row live and staying: the reference for the queue
    # pass's rounds
    "providers_stay": {"live_p": N_P, "life": STILL},
    # tasks breathe under the providers (idle nodes on every tick) and
    # over them (a queue on every tick), no provider row dead: nothing
    # crosses, nothing breathes on the providers' side
    "idle_nodes": {"live_p": N_P, "live_t": 100,
                   "life": dict(STILL, task_arrive=0.04)},
    "queued": {"live_p": N_P, "live_t": 400,
               "life": dict(STILL, task_arrive=0.16)},
}


def test_every_tick_of_a_breathing_pool_is_within_the_limit(chains):
    """Every plan injective and feasible, seating only live rows, as
    many as the exact optimum does (all live providers where tasks
    outnumber them, all seatable tasks where they do not), within 0.025
    a seated task of its cost, under a certificate that bounds the true
    gap; the reverse pass on a tick that leaves a queue ends by itself,
    far inside its budget, and the queue pass stays within four times
    its rounds in the same chain with every provider row staying."""
    chain = chains("breathing")
    crossings = sum(
        (a["live"][1] > a["live"][0]) != (b["live"][1] > b["live"][0])
        for a, b in zip(chain, chain[1:])
    )
    assert crossings >= 4
    queue_ref = max(
        t["stats"].get("eng_queue_rounds", 0)
        for t in chains("providers_stay")
    )
    regimes = set()
    for t in chain:
        s = t["stats"]
        assert t["dup"] == t["infeasible"] == t["dead_seated"] == 0, t["tick"]
        assert t["seated"] == t["seatable"], (t["tick"], t["seated"])
        assert t["gap"] <= GAP_LIMIT, (t["tick"], t["gap"])
        assert np.isfinite(s["gap_per_task"]), t["tick"]
        assert s["gap_per_task"] >= t["gap"] - 1e-6, t["tick"]
        assert s["gap_per_task"] <= CERT_LIMIT, t["tick"]
        assert s["eng_reverse_rounds"] < CROSS_ROUNDS, t["tick"]
        assert s.get("eng_queue_rounds", 0) <= 4 * queue_ref, t["tick"]
        regimes.add(t["regime"])
    assert regimes == {"queue", "slack", "band"}
    warm = chain[1:]
    assert sum(t["stats"]["eng_regime_change"] for t in warm) >= crossings
    # every crossing that left a queue was re-grounded in one step, whose
    # rounds the counters name, and only a crossing is
    left = [t for a, t in zip(chain, warm)
            if a["regime"] == "queue" != t["regime"]]
    assert left and all(t["stats"]["eng_cross_rounds"] > 0 for t in left)
    assert all(t["stats"]["eng_cross_ms"] == 0.0 for t in warm
               if not t["stats"]["eng_regime_change"])
    for t in warm:
        s = t["stats"]
        assert s["eng_transposed_rounds"] == (
            s["eng_reverse_rounds"] + s["eng_queue_rounds"])
        assert s["arena_rows_moved"] == (
            s["arena_rows_left"] + s["arena_rows_joined"])
    assert sum(t["stats"]["arena_rows_moved"] for t in warm) >= 2 * TICKS


# ticks of the breathing chain in the band between the regimes: a few
# live tasks fewer than the live providers (202 or 203 of 205), the pool
# come there from idle nodes or from a queue
BAND_TICKS = (1, 6, 16)


@pytest.mark.parametrize("tick", BAND_TICKS)
def test_a_dual_refresh_in_the_band_runs_the_band_pass(chains, tick):
    """The ladder of a dual refresh (every ``dual_refresh_every`` warm
    solves) is handed the carried regime, so a refresh that falls in
    the band runs the reverse pass there as the warm solve does: the
    plan is within the limit of the exact optimum, seating every
    seatable task (the ladder without it: 0.026-0.061 a seated task off
    on these ticks)."""
    t = chains("breathing")[tick]
    assert t["regime"] == "band" and t["live"][1] < t["live"][0], t["live"]
    gen, arena, session = open_breathing()
    for k in range(tick):
        _serve(session, gen.next_delta() if k else None)
    arena._dual_age = arena.dual_refresh_every
    plan = _serve(session, gen.next_delta())
    s = arena.last_stats
    assert s["dual_refresh"] and arena._regime == "band"
    dense, best, seatable = _optimum(gen.p_cols, gen.r_cols)
    seated = np.flatnonzero(plan >= 0)
    assert seated.size == seatable == t["seatable"]
    gap = (float(dense[seated, plan[seated]].sum()) - best) / seated.size
    assert gap <= GAP_LIMIT, gap
    assert s["eng_reverse_rounds"] > 0


# sha256 over every tick's plan (i32) and its four round counters, cold
# open and 24 warm ticks, as the parent (commit 529b96d) serves them on
# XLA:CPU
PARENT_PINNED = {
    "idle_nodes":
        "4afc49a02b08fc65cb4bb0fb6fe2ef14d0800c68786c3f2f2bb92fb5cbba4cc8",
    "queued":
        "446b4030124407d732a4cbe41021753ce6f89c21f6823ae29dc715d154734991",
}


@pytest.mark.parametrize("name", sorted(PARENT_PINNED))
def test_where_no_provider_breathes_nothing_crosses_and_nothing_changes(
        chains, name):
    """Tasks end and arrive, every provider row stays live, and the pool
    stays on one side of P = T: the plans and the rounds are the
    parent's, tick for tick, and no crossing is counted."""
    h = hashlib.sha256()
    for t in chains(name):
        s = t["stats"]
        h.update(np.ascontiguousarray(t["plan"], np.int32).tobytes())
        h.update(repr([s.get(k) for k in (
            "eng_rounds_total", "eng_reverse_rounds", "eng_queue_rounds",
            "eng_frontier_rows")]).encode())
        assert s.get("eng_regime_change", 0) == 0, t["tick"]
        assert t["gap"] <= GAP_LIMIT, t["tick"]
    assert h.hexdigest() == PARENT_PINNED[name]


def _set_valid(cols, side_rows, live):
    rows = np.asarray(side_rows, np.int32)
    cols["valid"][rows] = live
    return rows, {n: a[rows] for n, a in cols.items()}


@pytest.mark.parametrize("who", ["provider_leaves", "task_ends"])
def test_a_seat_whose_provider_left_or_whose_task_ended_is_emptied(who):
    """The seat guard: a seated provider whose row goes dead loses its
    task, which is seated elsewhere or waits; a task that ends leaves
    its provider free. The arena counts the seat and the row."""
    gen, arena, session = open_breathing(life=STILL)
    plan = _serve(session, None)
    task = int(np.flatnonzero(plan >= 0)[0])
    provider = int(plan[task])
    none = np.zeros(0, np.int32)
    if who == "provider_leaves":
        rows, vals = _set_valid(gen.p_cols, [provider], False)
        delta = (rows, vals, none, {})
    else:
        rows, vals = _set_valid(gen.r_cols, [task], False)
        delta = (none, {}, rows, vals)
    after = _serve(session, delta)
    s = arena.last_stats
    assert s["arena_seats_vacated"] == 1
    assert s["arena_rows_left"] == (who == "provider_leaves")
    assert s["arena_rows_joined"] == 0
    assert provider not in after[gen.r_cols["valid"]] or (
        who == "task_ends" and after[task] == -1)
    if who == "provider_leaves":
        assert after[task] != provider
        dense, best, seatable = _optimum(gen.p_cols, gen.r_cols)
        assert int((after >= 0).sum()) == seatable
    else:
        assert after[task] == -1
    # and back: the row comes back, and is counted
    rows, vals = _set_valid(
        gen.p_cols if who == "provider_leaves" else gen.r_cols,
        [provider if who == "provider_leaves" else task], True,
    )
    _serve(session, (rows, vals, none, {}) if who == "provider_leaves"
           else (none, {}, rows, vals))
    assert arena.last_stats["arena_rows_joined"] == (who == "provider_leaves")


def test_a_restored_session_continues_bit_for_bit_across_a_crossing(
        tmp_path):
    """The regime is dual state: it rides the journal in ``SOLVE_STATE``
    (one i8, its index in ``REGIMES``) beside the reserve, and a session
    restored from its checkpoint serves the plans, carries the prices
    and counts the crossings of the one that never stopped."""
    gen, arena, session = open_breathing()
    assert "regime" in arena.SOLVE_STATE
    for tick in range(4):
        _serve(session, gen.next_delta() if tick else None)
    ckpt = SessionCheckpointer(str(tmp_path / "a"))
    with session.lock:
        assert ckpt.flush_locked(session)
    loaded = SessionCheckpointer(str(tmp_path / "a")).load_one(
        session.session_id
    )
    assert loaded is not None
    assert loaded.arena._regime == arena._regime is not None
    state = loaded.arena.export_state()
    assert state["regime"].dtype == np.int8 and state["regime"].shape == (1,)
    crossed = 0
    for _ in range(10):
        delta = gen.next_delta()
        a = _serve(session, delta)
        b = _serve(loaded, copy.deepcopy(delta))
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(arena.price, loaded.arena.price)
        assert loaded.arena._regime == arena._regime
        assert loaded.arena._reserve == arena._reserve
        for key in ("eng_rounds_total", "eng_reverse_rounds",
                    "eng_queue_rounds", "eng_regime_change",
                    "eng_cross_rounds", "arena_seats_vacated",
                    "gap_per_task"):
            assert loaded.arena.last_stats[key] == arena.last_stats[key], key
        crossed += arena.last_stats["eng_regime_change"]
    assert crossed >= 2


def test_a_whole_run_of_the_new_cell_is_correct_on_the_cpu():
    """``pool-breathing.life`` through the benchmark's own harness at 256
    x 512 rows, 205 provider rows live, with the life events of the
    chains above (the cell's own are too slow to cross P = T in a list
    of 16 ticks at this size): correct, both regimes judged, nothing
    compiled in the window."""
    cell = copy.deepcopy(harness.load_cell(REPO, "pool-breathing.life"))
    cfg = cell["config"]
    assert (cfg["n_providers"], cfg["n_tasks"]) == (8192, 8192)
    assert (cfg["providers_live"], cfg["tasks_live"]) == (6554, 6554)
    assert cfg["limits"]["subpool_gap"] == GAP_LIMIT
    assert "unassigned_frac" not in cfg["limits"]
    assert cfg["server"]["ckpt_every"] == 1
    cell["ticks"] = 16
    cfg.update(n_providers=N_P, n_tasks=N_T, providers_live=205,
               tasks_live=148)
    cfg["check"].update(acks=8, subpool_tasks=256)
    cell["traffic"].update(task_churn=0.02, **LIFE)
    r = harness.run_cell(cell, 2**31 + 43, 120.0, False, require_chip=False)
    assert r["correct"] is True, r["checks"]
    checks = {k: v["value"] for k, v in r["checks"].items()}
    assert checks["window_compiles"] == 0
    assert checks["subpool_gap"] <= GAP_LIMIT
    assert checks["unseated_excess_frac"] == 0
    assert checks["judged_queue_acks"] >= 1
    assert checks["judged_slack_acks"] >= 1
    assert r["window"]["ticks_short"] == 0
