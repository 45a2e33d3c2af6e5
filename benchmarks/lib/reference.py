"""The plain reference: what a plan has to satisfy, worked out with
NumPy and SciPy from the client's own copy of the marketplace.

It imports nothing of the program and takes nothing the program made.
The semantics are the published ones (``ops/cost.py`` and
``ops/encoding.py:compat_mask`` docstrings, restated in float64):

    cost[p, t] = w.price * price[p] + w.load * load[p]
               + w.proximity * haversine_km(p, t)   (both located)
               - w.priority * priority[t]
    feasible[p, t] = every scalar minimum met, some valid GPU option met
                     (exact count, memory window, total-memory window,
                     model bitmask), both rows valid

A plan maps each task to a provider or -1. It is judged on: no provider
used twice, no infeasible pair, the share of live tasks left without a
provider (of all of them, and beyond those that outnumber the live
providers), and how much dearer it is than the optimum of a seeded
sub-pool re-solved exactly (``subpool_gap``).
"""

from __future__ import annotations

import numpy as np

EARTH_RADIUS_KM = 6371.0
UNSEATABLE = 1e6     # an infeasible pair's cost in a re-solve


def _ge_min(have, need):
    return (need < 0) | (have >= need)


def _le_max(have, need):
    return (need < 0) | (have <= need)


def _pair(p: dict, r: dict, weights: dict, words):
    """Cost and feasibility of provider rows ``p`` against task rows
    ``r``. Scalar columns of both broadcast to the result's shape;
    ``r``'s GPU-option columns carry one more trailing axis K, and
    ``words`` is the task's model-mask word selected by the provider's
    model id, shaped like the result plus K."""
    ok = ~r["cpu_required"] | (
        p["has_cpu"] & _ge_min(p["cpu_cores"], r["cpu_cores"])
    )
    ok = ok & _ge_min(p["ram_mb"], r["ram_mb"])
    ok = ok & _ge_min(p["storage_gb"], r["storage_gb"])

    pc = p["gpu_count"][..., None].astype(np.int64)
    pm = p["gpu_mem_mb"][..., None].astype(np.int64)
    rc = r["gpu_count"]
    count_ok = (rc < 0) | np.where(pc < 0, rc == 0, pc == rc)
    mem_ok = _ge_min(pm, r["gpu_mem_min"]) & _le_max(pm, r["gpu_mem_max"])
    total = pc * pm
    have_total = (pc >= 0) & (pm >= 0)
    tmin, tmax = r["gpu_total_mem_min"], r["gpu_total_mem_max"]
    tot_ok = ((tmin < 0) | ~have_total | (total >= tmin)) & (
        (tmax < 0) | ~have_total | (total <= tmax)
    )
    model = p["gpu_model_id"][..., None]
    bit = (np.maximum(model, 0) & 31).astype(np.uint32)
    hit = ((words >> bit) & np.uint32(1)).astype(bool)
    model_ok = ~r["gpu_model_constrained"] | ((model >= 0) & hit)
    opt_ok = count_ok & mem_ok & tot_ok & model_ok & r["gpu_opt_valid"]
    any_opt = r["gpu_opt_valid"].any(axis=-1)
    ok = ok & np.where(any_opt, p["has_gpu"] & opt_ok.any(axis=-1), True)
    ok = ok & p["valid"] & r["valid"]

    lat1, lon1 = p["lat"].astype(np.float64), p["lon"].astype(np.float64)
    lat2, lon2 = r["lat"].astype(np.float64), r["lon"].astype(np.float64)
    a = (
        np.sin((lat2 - lat1) / 2) ** 2
        + np.cos(lat1) * np.cos(lat2) * np.sin((lon2 - lon1) / 2) ** 2
    )
    dist = 2.0 * EARTH_RADIUS_KM * np.arcsin(np.sqrt(np.clip(a, 0.0, 1.0)))
    cost = (
        weights["price"] * p["price"].astype(np.float64)
        + weights["load"] * p["load"].astype(np.float64)
        + np.where(
            p["has_location"] & r["has_location"],
            weights["proximity"] * dist, 0.0,
        )
        - weights["priority"] * r["priority"].astype(np.float64)
    )
    return cost, ok


def pair_costs(p_cols, r_cols, plan, weights):
    """``(cost[T], feasible[T])`` of each task with its planned provider
    (row 0 stands in where the plan says -1; mask those out)."""
    prov = np.maximum(plan, 0)
    p = {n: a[prov] for n, a in p_cols.items()}
    word = np.maximum(p["gpu_model_id"], 0) >> 5
    words = np.take_along_axis(
        r_cols["gpu_model_mask"], word[:, None, None], axis=2
    )[..., 0]
    return _pair(p, r_cols, weights, words)


def block_costs(p_cols, r_cols, providers, tasks, weights):
    """``(cost[S, Q], feasible[S, Q])`` of tasks ``tasks`` against
    providers ``providers``."""
    p = {n: a[providers][None, ...] for n, a in p_cols.items()}
    r = {
        n: a[tasks][:, None, ...] for n, a in r_cols.items()
        if n != "gpu_model_mask"  # enters as ``words`` below
    }
    word = np.maximum(p_cols["gpu_model_id"][providers], 0) >> 5
    # mask [S, K, W] -> the provider's word -> [S, Q, K]
    words = np.moveaxis(r_cols["gpu_model_mask"][tasks][:, :, word], 2, 1)
    return _pair(p, r, weights, words)


def judge_plan(p_cols, r_cols, plan, weights, rng, subpool_tasks):
    """The numbers one acknowledged plan is held to, as a dict:
    ``dup_providers``, ``out_of_range``, ``infeasible_pairs`` (counts),
    ``unassigned_frac`` (of live tasks), ``unseated_excess_frac`` (live
    tasks left unseated beyond those that outnumber the live providers,
    of live tasks), ``queued_tasks`` and ``idle_providers`` (live tasks
    over live providers and the reverse, whichever is not 0: the
    pool's regime at this tick) and ``subpool_gap``: cost per seated
    task above the exact optimum of a sub-pool of ``subpool_tasks``
    tasks drawn by ``rng``, over the providers the plan gave them and
    every live provider it left free. In a pool with idle providers or
    none to spare the sub-pool is drawn from the tasks the plan seated.
    Where live tasks outnumber live providers it is drawn from ALL live
    tasks, seated or waiting, so that the re-solve may seat a waiting
    task in a seated one's place: a wrong task waiting shows."""
    from scipy.optimize import linear_sum_assignment

    plan = np.asarray(plan)
    n_p = p_cols["valid"].shape[0]
    out = {"out_of_range": int(
        (plan.shape[0] != r_cols["valid"].shape[0])
        or (plan >= n_p).sum() + (plan < -1).sum()
    )}
    if out["out_of_range"]:
        return out
    seated = plan >= 0
    used = plan[seated]
    out["dup_providers"] = int(used.size - np.unique(used).size)
    cost, ok = pair_costs(p_cols, r_cols, plan, weights)
    out["infeasible_pairs"] = int((seated & ~ok).sum())
    live = r_cols["valid"].astype(bool)
    here = p_cols["valid"].astype(bool)
    n_live, n_here = int(live.sum()), int(here.sum())
    unseated = int((live & ~seated).sum())
    out["queued_tasks"] = max(n_live - n_here, 0)
    out["idle_providers"] = max(n_here - n_live, 0)
    out["unassigned_frac"] = float(unseated) / max(n_live, 1)
    out["unseated_excess_frac"] = float(
        max(unseated - out["queued_tasks"], 0)
    ) / max(n_live, 1)
    good = np.flatnonzero(seated & ok)
    if out["dup_providers"] or good.size == 0:
        return out
    drawn_from = np.flatnonzero(live) if out["queued_tasks"] else good
    tasks = (
        drawn_from if drawn_from.size <= subpool_tasks
        else np.sort(rng.choice(drawn_from, subpool_tasks, replace=False))
    )
    mine = tasks[seated[tasks] & ok[tasks]]
    if mine.size == 0:
        return out
    free = np.ones(n_p, bool)
    free[used] = False
    free &= here
    cols = np.concatenate([plan[mine], np.flatnonzero(free)])
    c, feas = block_costs(p_cols, r_cols, cols, tasks, weights)
    c = np.where(feas, c, UNSEATABLE)
    rows, picks = linear_sum_assignment(c)
    # at equal cardinality: an optimum that seats more of the sub-pool
    # than the plan did is compared without its dearest pairs beyond
    # the plan's count, which can only flatter the plan (the seats it
    # is short are unseated_excess_frac's to hold). The plan's own
    # pairs are feasible, so the pairs kept are.
    best = c[rows, picks]
    if best.size > mine.size:
        best = np.sort(best)[: mine.size]
    out["subpool_gap"] = float(cost[mine].sum() - best.sum()) / mine.size
    return out
