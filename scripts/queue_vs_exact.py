"""A pool with a queue beside the exact optimum of the whole pool.

A reading, not a gate (ISSUE 33, tentpole 6 ii): the benchmark's
reference (``benchmarks/lib/reference.judge_plan``) re-solves a sample
of the SEATED tasks, so it cannot see a wrong task waiting; this script
can. It opens the benchmark's marketplace (``benchmarks/lib/
population.py``, ``population_seed`` 25001, 1% of providers re-priced
and 0.2% of tasks re-rolled a tick) in a ``SolveSession`` as the
servicer holds it, serves the cold open and ``--ticks`` warm ticks
through the jax arena on whatever device jax finds, and for the ticks
named by ``--judge`` solves the WHOLE rectangular problem exactly:
``scipy.optimize.linear_sum_assignment`` over a float64 cost built by
``benchmarks/lib/reference.block_costs`` (plain NumPy, nothing of the
program). Prints one JSON line a judged tick: cost per seated task
above the optimum (``gap``), seated against seatable, and the program's
own certificate (``gap_per_task``, ``waiting_excess``) beside them; a
last line says whether every judged tick held: gap <= ``--limit``,
seated = seatable, certificate >= gap. Exits non-zero where one did
not.

    python scripts/queue_vs_exact.py --shape 6554x8192 --ticks 10 --judge 1,5,10
    (rehearse on the CPU: JAX_PLATFORMS=cpu ... --shape 410x512)

``--no-queue`` serves the same chain as the parent commit solved it
(no pool is ever said to have a queue), for the reading beside it.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

WEIGHTS = {"price": 1.0, "load": 1.0, "proximity": 0.001, "priority": 0.0}
UNSEATABLE = 1e6
STATS = (
    "gap_per_task", "waiting_excess", "cs_slack", "idle_price",
    "eng_rounds_total", "eng_queue_rounds", "eng_queue_ms",
    "eng_stall_exit", "eng_frontier_rows", "eng_waiting_tasks",
    "eng_free_providers", "solve_ms", "gen_ms",
)


def exact(p_cols: dict, r_cols: dict, plan: np.ndarray) -> dict:
    """The plan against the exact optimum of the whole pool, by the
    benchmark's plain reference alone."""
    from lib import reference
    from scipy.optimize import linear_sum_assignment

    n_p, n_t = p_cols["valid"].shape[0], r_cols["valid"].shape[0]
    t0 = time.perf_counter()
    dense = np.empty((n_t, n_p), np.float64)
    for t in range(0, n_t, 1024):   # a block of tasks at a time: memory
        tasks = np.arange(t, min(t + 1024, n_t))
        cost, ok = reference.block_costs(
            p_cols, r_cols, np.arange(n_p), tasks, WEIGHTS
        )
        dense[tasks] = np.where(ok, cost.astype(np.float64), UNSEATABLE)
    rows, picks = linear_sum_assignment(dense)
    pair = dense[rows, picks]
    best = float(pair[pair < UNSEATABLE].sum())
    seatable = int((pair < UNSEATABLE).sum())
    seated = np.flatnonzero(plan >= 0)
    mine = dense[seated, plan[seated]]
    return {
        "seated": int(seated.size), "seatable": seatable,
        "dup": int(seated.size - np.unique(plan[seated]).size),
        "infeasible": int((mine >= UNSEATABLE).sum()),
        "plan_cost": float(mine.sum()), "optimum": best,
        "gap": (float(mine.sum()) - best) / max(seated.size, 1),
        "exact_s": round(time.perf_counter() - t0, 1),
    }


def chain(n_providers: int, n_tasks: int, ticks: int, judge: set) -> list:
    from lib import population

    from protocol_tpu.ops.cost import CostWeights
    from protocol_tpu.services.session_store import (
        SolveSession,
        _pad_cols,
        make_solve_arena,
    )

    gen = population.Pool(
        np.random.default_rng([25001, 0]), n_providers, n_tasks, 0.01, 0.002,
    )
    arena = make_solve_arena("jax", k=64, threads=0)
    session = SolveSession(
        session_id="queue@t", fingerprint="fp",
        weights=CostWeights(**WEIGHTS), kernel="jax", threads=0, top_k=64,
        p_cols=_pad_cols(copy.deepcopy(gen.p_cols), n_providers),
        r_cols=_pad_cols(copy.deepcopy(gen.r_cols), n_tasks),
        n_providers=n_providers, n_tasks=n_tasks, arena=arena,
    )
    out = []
    for tick in range(ticks + 1):
        with session.lock:
            if tick:
                session.apply_delta(*gen.next_delta())
            plan = np.asarray(session.solve()[0])[:n_tasks]
        line = {"tick": tick, "shape": f"{n_providers}x{n_tasks}"}
        line.update({k: arena.last_stats.get(k) for k in STATS})
        if tick in judge:
            line.update(exact(gen.p_cols, gen.r_cols, plan))
        print(json.dumps(line), flush=True)
        out.append(line)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="6554x8192", help="PROVIDERSxTASKS")
    ap.add_argument("--ticks", type=int, default=10)
    ap.add_argument("--judge", default="1,5,10",
                    help="ticks solved exactly, comma separated (0 = cold)")
    ap.add_argument("--limit", type=float, default=0.025)
    ap.add_argument("--no-queue", action="store_true",
                    help="the parent's solve: no pool has a queue")
    args = ap.parse_args()

    from protocol_tpu.utils.platform import place_compile_cache

    place_compile_cache()
    import jax

    if args.no_queue:
        from protocol_tpu.ops import sparse

        sparse._queue_reserve = lambda *a, **k: None
    device = jax.devices()[0]
    n_p, n_t = (int(x) for x in args.shape.split("x"))
    judge = {int(t) for t in args.judge.split(",") if t != ""}
    lines = [t for t in chain(n_p, n_t, args.ticks, judge) if "gap" in t]
    held = bool(lines) and all(
        t["gap"] <= args.limit and t["seated"] == t["seatable"]
        and t["dup"] == 0 and t["infeasible"] == 0
        and (args.no_queue or t["gap_per_task"] >= t["gap"] - 1e-6)
        for t in lines
    )
    print(json.dumps({
        "held": held, "limit": args.limit, "queue_phase": not args.no_queue,
        "device": f"{device.platform}:{device.device_kind}",
        "gaps": [round(t["gap"], 6) for t in lines],
    }), flush=True)
    return 0 if held else 1


if __name__ == "__main__":
    sys.exit(main())
