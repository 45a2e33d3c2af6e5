"""Decision-quality plane: certified optimality gaps, plan churn, and
per-task starvation over the scheduler seam.

PR 6 made the seam's *latency* observable; this module makes its
*decisions* observable. Everything here is computed from state the
engines already carry — the candidate structure, the carried dual
prices, the previous plan — so the quality signals are nearly free and
NEVER feed solver state (the replay-identity gate runs with the plane
on; matchings are bit-for-bit either way).

  * :func:`duality_gap` — a **certified** upper bound on how far the
    plan's cost sits above the optimal assignment on the same candidate
    support, from LP duality: with prices ``pi`` (the auction's carried
    duals, or the Sinkhorn referee's derived prices), the dual point
    ``y_p = pi_p`` (over providers reachable from assigned tasks),
    ``g_t = min_q (c(t,q) + pi_q)`` is feasible for the LP that covers
    exactly the plan's assigned task set, so

        gap = plan_cost - dual_bound
            = sum_t eps-CS slack(t) + sum_{reachable idle p} pi_p

    Where tasks have to wait (a pool with a queue: more of them wait
    than the free providers they list could seat), the LP is the one
    that seats as many tasks as the plan does, whichever they are; its
    multiplier ``theta`` is the value of waiting, and the bound gains a
    third addend, ``waiting_excess``: what the waiting tasks' best
    candidates are worth to them above ``theta``, plus what the seated
    tasks' are worth below it, at the ``theta`` that makes the sum
    least. It is 0 when the right tasks wait. The result

    is a certificate, not an estimate: the true optimum lies within
    ``gap`` of the plan, whatever the engine did to get there. The
    certificate's dual point caps prices at the give-up magnitude
    (2*max_cost + 10) — any nonnegative dual certifies, and the cap
    strips the single-option bid floor's price spikes without
    loosening converged marketplaces. At auction convergence every
    slack is <= the engine eps and (on saturated marketplaces) no
    reachable provider idles, so ``gap_per_task <= eps`` — the CI gate
    holds ``<= 2x eps``.
  * :func:`plan_churn` — fraction of (valid) tasks whose provider
    changed tick-over-tick: the stability price of each warm solve,
    and the number the streaming-assignment roadmap item will gate its
    bounded-staleness contract on.
  * :func:`starvation_update` / :func:`starvation_hist` — per-task
    consecutive-ticks-unassigned ages (max + a log2-bucket histogram):
    which tasks are quietly never seated, not just how many.
  * :func:`tick_quality` — the one arena entry point folding all of the
    above plus the native outcome taxonomy
    (:data:`protocol_tpu.native.OUTCOME_NAMES`) into flat scalars that
    ride ``last_stats`` -> ObsRegistry -> OUTCOME frames -> the obs
    report.

Determinism contract: pure functions of (candidates, plan, duals) —
no clocks, no randomness (the determinism lint covers this module).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from protocol_tpu.obs.spans import TRACER as _tracer

# mirrors ops/cost.py INFEASIBLE without importing the jax-backed module
# (the quality pass runs in control-plane processes with no backend)
_INFEASIBLE = 1e9

# outcome code -> last_stats scalar key (order matters: it is the
# report's cause-table column order)
OUTCOME_STAT_KEYS = (
    (0, "outcome_assigned"),
    (1, "outcome_no_candidates"),
    (2, "outcome_outbid"),
    (3, "outcome_retired"),
)

# starvation-age histogram bucket upper bounds (ticks); the last bucket
# is open-ended. Log2-spaced: ages are a heavy-tailed signal and the
# interesting question is "how LONG has the tail been starving".
STARVE_BUCKETS = (1, 2, 4, 8, 16, 32, 64)


def duality_gap(
    cand_p: np.ndarray,
    cand_c: np.ndarray,
    p4t: np.ndarray,
    price: np.ndarray,
) -> dict:
    """Certified duality gap of a plan on its candidate support.

    ``cand_p``/``cand_c``: [T, K] candidate lists (provider id / cost,
    -1 = empty slot); ``p4t``: [T] plan (provider per task, -1 =
    unassigned); ``price``: [P] dual prices the engine carried out of
    the solve. Returns plan_cost, dual_bound, gap_total, gap_per_task
    (gap normalized by assigned count), plus the certificate's three
    addends (cs_slack, idle_price, waiting_excess) for diagnosis.
    """
    cand_p = np.asarray(cand_p)
    cand_c = np.asarray(cand_c)
    p4t = np.asarray(p4t)
    price = np.asarray(price, np.float64)
    feas = (cand_p >= 0) & (cand_c < _INFEASIBLE * 0.5)
    # the certificate may use ANY nonnegative dual point; capping
    # prices at the engine's give-up magnitude (2*max_cost + 10, the
    # most any bidder would ever pay) strips the single-option bid
    # floor's ~1e8 price spikes without loosening converged
    # marketplaces, where every price already sits below the cap —
    # same dual point the engine's in-solve certificate pass uses
    cmax = float(cand_c[feas].max()) if feas.any() else 0.0
    price = np.minimum(price, 2.0 * cmax + 10.0)
    # ... and NONNEGATIVE it has to be: where a provider some task lists
    # carries a price under 0 (a pool whose floor went below 0, one
    # whose free providers sit at the least seated price), every price
    # is raised by as much. A uniform shift is a dual point too, never a
    # lower bound than the optimum; with no such price it is no shift
    # at all. A provider no task lists (a row that left) is in no sum.
    safe_p = np.maximum(cand_p, 0)
    listed = price[safe_p]
    if price.size and price.min() < 0.0:
        listed_lo = float(np.where(feas, listed, 0.0).min())
        if listed_lo < 0.0:
            price = price - listed_lo
            listed = listed - listed_lo
    adj = np.where(feas, cand_c.astype(np.float64) + listed, np.inf)
    best = adj.min(axis=1)

    rows = np.flatnonzero(p4t >= 0)
    if rows.size == 0:
        return {
            "plan_cost": 0.0, "dual_bound": 0.0, "gap_total": 0.0,
            "gap_per_task": 0.0, "cs_slack": 0.0, "idle_price": 0.0,
            "waiting_excess": 0.0,
        }
    seat = p4t[rows]
    seat_m = (cand_p[rows] == seat[:, None]) & feas[rows]
    has_seat = seat_m.any(axis=1)
    rows = rows[has_seat]
    seat = seat[has_seat]
    j = seat_m[has_seat].argmax(axis=1)
    seat_c = cand_c[rows, j].astype(np.float64)
    seat_adj = seat_c + price[seat]
    slack = np.maximum(seat_adj - best[rows], 0.0)

    # reachable providers: any feasible candidate edge out of an
    # assigned task's row; the idle ones are the certificate's second
    # addend (a pumped price on a reachable-but-unused provider is a
    # real optimality question, not noise)
    reach = np.zeros(price.shape[0], bool)
    fr = feas[rows]
    reach[cand_p[rows][fr]] = True
    used = np.zeros(price.shape[0], bool)
    used[seat] = True

    # where tasks have to wait, the LP covers them and the providers
    # they list too (see :func:`queue_rows`)
    waiting_excess = 0.0
    waiting, listed = queue_rows(
        cand_p, cand_c, p4t, price.shape[0], covered=(rows, reach)
    )
    if waiting.size:
        reach |= listed
        waiting_excess = _waiting_excess(-seat_adj, -best[waiting])
    idle_price = float(price[reach & ~used].sum())

    plan_cost = float(seat_c.sum())
    cs_slack = float(slack.sum())
    gap_total = cs_slack + idle_price + waiting_excess
    n = int(rows.size)
    return {
        "plan_cost": round(plan_cost, 4),
        "dual_bound": round(plan_cost - gap_total, 4),
        "gap_total": round(gap_total, 6),
        "gap_per_task": round(gap_total / max(n, 1), 6),
        "cs_slack": round(cs_slack, 6),
        "idle_price": round(idle_price, 6),
        "waiting_excess": round(waiting_excess, 6),
    }


def queue_rows(
    cand_p: np.ndarray, cand_c: np.ndarray, p4t: np.ndarray, n_p: int,
    covered: Optional[tuple[np.ndarray, np.ndarray]] = None,
) -> tuple[np.ndarray, np.ndarray]:
    """(the rows of the tasks that wait, a [P] mask of the providers
    any task lists), where the plan has a queue; (no rows, no
    providers) where it has none. A queue: more tasks wait than there
    are free providers on anybody's list, so some task waits whatever
    the plan, and which ones do is the plan's choice (the solve's own
    reading of the regime, ``ops/sparse.py:_queue_reserve``, less its
    margin). A handful of waiting tasks beside as many free providers
    (a full pool's unseatable tail) is none, and the certificate stays
    the one over the assigned task set. ``covered``: (rows, the [P]
    mask of the providers those rows list) where the caller has them
    (:func:`duality_gap`'s reachable providers), so only the other
    rows' lists are read: O(T) where nobody waits, O(waiting x K) in a
    full pool, one O(T*K) scatter without ``covered``."""
    cand_p = np.asarray(cand_p)
    cand_c = np.asarray(cand_c)
    p4t = np.asarray(p4t)
    none = np.zeros(0, np.intp), np.zeros(n_p, bool)
    open_rows = np.flatnonzero(p4t < 0)
    if open_rows.size == 0:
        return none
    waiting = open_rows[(
        (cand_p[open_rows] >= 0) & (cand_c[open_rows] < _INFEASIBLE * 0.5)
    ).any(axis=1)]
    if waiting.size == 0:
        return none
    if covered is None:
        listed = np.zeros(n_p, bool)
        rest_p, rest_c = cand_p, cand_c
    else:
        rest = np.ones(p4t.shape[0], bool)
        rest[covered[0]] = False
        listed = covered[1].copy()
        rest_p, rest_c = cand_p[rest], cand_c[rest]
    listed[rest_p[(rest_p >= 0) & (rest_c < _INFEASIBLE * 0.5)]] = True
    used = np.zeros(n_p, bool)
    used[p4t[p4t >= 0]] = True
    if waiting.size > int((listed & ~used).sum()):
        return waiting, listed
    return none


def _waiting_excess(seated: np.ndarray, waiting: np.ndarray) -> float:
    """min over theta of sum(max(0, theta - seated)) + sum(max(0,
    waiting - theta)), over what its seat is worth to each seated task
    and its best candidate to each waiting one, at the certificate's
    prices: the addend for the tasks that wait (the dual of "seat this
    many tasks, whichever they are" has one multiplier theta, the value
    of waiting; a seat worth less than theta and a waiting task's best
    worth more each loosen the bound by the difference). 0 with nobody
    waiting, and whenever no waiting task's best is worth more than the
    least seat. The sum is convex and piecewise linear in theta, so its
    minimum lies at one of the values."""
    if waiting.size == 0 or seated.size == 0:
        return 0.0
    if waiting.max() <= seated.min():
        return 0.0
    values = np.concatenate([seated, waiting])
    order = np.argsort(values, kind="stable")
    v = values[order]
    is_seated = order < seated.size
    # the slope right of v[i]: seated at or under it, less waiting above
    slope = np.cumsum(is_seated) - (waiting.size - np.cumsum(~is_seated))
    theta = v[min(int(np.argmax(slope >= 0)), v.size - 1)]
    return float(
        np.maximum(theta - seated, 0.0).sum()
        + np.maximum(waiting - theta, 0.0).sum()
    )


def plan_churn(
    prev_p4t: np.ndarray, p4t: np.ndarray, valid: Optional[np.ndarray]
) -> tuple[int, float]:
    """(rows changed, churn ratio) between two consecutive plans over
    the valid task rows — any seat change counts, including a task
    gaining or losing its seat."""
    prev_p4t = np.asarray(prev_p4t)
    p4t = np.asarray(p4t)
    changed = prev_p4t != p4t
    if valid is not None:
        v = np.asarray(valid, bool)
        changed = changed & v
        n = int(v.sum())
    else:
        n = int(p4t.shape[0])
    rows = int(changed.sum())
    return rows, round(rows / max(n, 1), 6)


def starvation_update(
    age: Optional[np.ndarray], p4t: np.ndarray, valid: Optional[np.ndarray]
) -> np.ndarray:
    """Advance the per-task consecutive-ticks-unassigned ages by one
    tick: assigned (or invalid) rows reset to 0, starving rows
    increment. ``age=None`` starts from zeros (cold solve)."""
    p4t = np.asarray(p4t)
    if age is None or np.asarray(age).shape[0] != p4t.shape[0]:
        age = np.zeros(p4t.shape[0], np.int32)
    starving = p4t < 0
    if valid is not None:
        starving = starving & np.asarray(valid, bool)
    return np.where(starving, np.asarray(age, np.int32) + 1, 0).astype(
        np.int32
    )


def starvation_hist(age: np.ndarray) -> list[int]:
    """Counts of starving tasks per :data:`STARVE_BUCKETS` age bucket
    (last bucket open-ended); zeros-only rows (not starving) excluded."""
    age = np.asarray(age)
    ages = age[age > 0]
    out: list[int] = []
    lo = 0
    for hi in STARVE_BUCKETS:
        out.append(int(((ages > lo) & (ages <= hi)).sum()))
        lo = hi
    out.append(int((ages > lo).sum()))
    return out


def aggregate_quality(tick_stats: list) -> Optional[dict]:
    """Canonical roll-up of per-tick quality scalar dicts (the
    ``tick_quality`` vocabulary, as carried by ``last_stats`` / OUTCOME
    frame metrics) — THE one implementation every surface shares
    (replay report, ``obs report``, bench): certified gap mean/max,
    plan churn mean/max over the ticks that carried it, starvation max,
    the zero-unexplained invariant the CI gate holds, and the
    outcome-cause totals (always all four taxonomy columns). ``None``
    when no tick carried quality scalars (a trace/run predating the
    plane, or obs off)."""
    qs = [s for s in tick_stats if s and s.get("gap_per_task") is not None]
    if not qs:
        return None
    gaps = [float(s["gap_per_task"]) for s in qs]
    churns = [
        float(s["churn_ratio"]) for s in qs
        if s.get("churn_ratio") is not None
    ]
    out: dict = {
        "ticks": len(qs),
        "gap_per_task_mean": round(float(np.mean(gaps)), 6),
        "gap_per_task_max": round(float(np.max(gaps)), 6),
        "plan_cost_mean": round(float(np.mean(
            [float(s.get("plan_cost", 0.0)) for s in qs]
        )), 4),
        "starve_max": int(max(int(s.get("starve_max", 0)) for s in qs)),
        "unexplained_unassigned": int(sum(
            int(s.get("outcome_unexplained", 0)) for s in qs
        )),
        "causes": {
            key.removeprefix("outcome_"): int(
                sum(int(s.get(key, 0)) for s in qs)
            )
            for _, key in OUTCOME_STAT_KEYS
        },
    }
    if churns:
        out["churn_ratio_mean"] = round(float(np.mean(churns)), 6)
        out["churn_ratio_max"] = round(float(np.max(churns)), 6)
    return out


def gap_from_certificate(
    p4t: np.ndarray,
    plan_cost: float,
    cs_slack: float,
    idle_price: float,
    waiting_excess: float = 0.0,
) -> dict:
    """Assemble the certified duality gap from the scalars the ENGINE's
    margin pass accumulated (plan cost, eps-CS slack, reachable-idle
    price, and the waiting tasks' excess where the engine counts a
    queue — capped-price dual point) — O(1) here instead of re-scanning
    the [T, K] candidate structure. Numerically equal to
    :func:`duality_gap` up to f32 rounding (the tests cross-check the
    two)."""
    p4t = np.asarray(p4t)
    cs_slack = float(cs_slack)
    waiting_excess = float(waiting_excess)
    gap_total = cs_slack + float(idle_price) + waiting_excess
    n = int((p4t >= 0).sum())
    return {
        "plan_cost": round(float(plan_cost), 4),
        "dual_bound": round(float(plan_cost) - gap_total, 4),
        "gap_total": round(gap_total, 6),
        "gap_per_task": round(gap_total / max(n, 1), 6),
        "cs_slack": round(cs_slack, 6),
        "idle_price": round(float(idle_price), 6),
        "waiting_excess": round(waiting_excess, 6),
    }


def tick_quality(
    cand_p: np.ndarray,
    cand_c: np.ndarray,
    p4t: np.ndarray,
    price: Optional[np.ndarray],
    valid: Optional[np.ndarray] = None,
    prev_p4t: Optional[np.ndarray] = None,
    starve_age: Optional[np.ndarray] = None,
    outcomes: Optional[dict] = None,
    eng: Optional[dict] = None,
    took: Optional[dict] = None,
) -> tuple[dict, np.ndarray]:
    """One tick's full quality record: (flat stats dict, new starvation
    ages). The arena calls this once per solve with the obs plane on;
    everything lands as scalars (plus the small ``starve_hist`` list)
    next to the tick's phase stats in ``last_stats``.

    When the engine's certificate scalars (``plan_cost`` /
    ``cs_slack`` / ``idle_price`` in ``eng``) are in hand the gap is
    assembled in O(1) from them; otherwise the O(T*K) reference
    :func:`duality_gap` scan runs (the jax path, tests). Either is a
    ``quality.gap`` span whose wall lands in ``took["q_gap_ms"]``
    (a dict of the caller's: the record itself holds no clock).
    """
    stats: dict = {}
    have_cert = (
        eng is not None
        and "plan_cost" in eng
        and "idle_price" in eng
        and "cs_slack" in eng
    )
    with _tracer.stage("quality.gap", {} if took is None else took,
                       "q_gap_ms"):
        if have_cert:
            stats.update(gap_from_certificate(
                p4t, eng["plan_cost"], eng["cs_slack"], eng["idle_price"],
                eng.get("waiting_excess", 0.0),
            ))
            # an engine whose margin pass does not count a queue: where
            # the plan has one, the scan's certificate takes the place
            # of the engine's (O(T*K) there, O(waiting x K) to find out)
            if (
                price is not None and "waiting_excess" not in eng
                and queue_rows(
                    cand_p, cand_c, p4t, np.asarray(price).shape[0]
                )[0].size
            ):
                stats.update(duality_gap(cand_p, cand_c, p4t, price))
        elif price is not None:
            stats.update(duality_gap(cand_p, cand_c, p4t, price))
    if prev_p4t is not None and np.asarray(prev_p4t).shape == np.asarray(
        p4t
    ).shape:
        rows, ratio = plan_churn(prev_p4t, p4t, valid)
        stats["churn_rows"] = rows
        stats["churn_ratio"] = ratio
    new_age = starvation_update(starve_age, p4t, valid)
    stats["starve_max"] = int(new_age.max()) if new_age.size else 0
    stats["starving"] = int((new_age > 0).sum())
    stats["starve_hist"] = starvation_hist(new_age)

    if outcomes is not None and "codes" in outcomes:
        codes = np.asarray(outcomes["codes"])
        v = (
            np.asarray(valid, bool)
            if valid is not None
            else np.ones(codes.shape[0], bool)
        )
        for code, key in OUTCOME_STAT_KEYS:
            stats[key] = int(((codes == code) & v).sum())
        # the completeness invariant the CI gate holds: every valid
        # unassigned task carries a cause code (assigned tasks are code
        # 0 by construction, so unexplained == valid unassigned rows
        # whose code claims "assigned")
        unassigned = (np.asarray(p4t) < 0) & v
        stats["outcome_unexplained"] = int(
            (unassigned & (codes == 0)).sum()
        )
        margin = outcomes.get("margin")
        if margin is not None:
            m = np.asarray(margin)[v & (np.asarray(p4t) >= 0)]
            if m.size:
                stats["win_margin_mean"] = round(float(m.mean()), 6)
                stats["win_margin_min"] = round(float(m.min()), 6)
    return stats, new_age
