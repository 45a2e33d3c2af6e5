#!/usr/bin/env python
"""Ladder-#4 COLD re-measurement on the modern stack (ISSUE 16).

SCALING.md's 1M x 1M story predates both the persistent candidate
structure (PR 13) and the ISA-dispatched vector pipeline (ISSUE 16):
the stale rows extrapolate stage A from the 793 s jax-on-CPU generation
wall at 65k. This script retires them with MEASURED rows:

  rung rows   bucketed cold candidate generation (fused + capability
              pruner + block-skip) at 65k / 262k — scalar AND widest
              vector at 65k so the speedup over the old wall is a row,
              not a claim
  cold 1M     a NativeSolveArena cold solve at the full 1M x 1M shape:
              bucketed vector gen + bounded eps-ladder auction
              (eps 4.0 -> 1.0, the convention of every earlier 1M
              artifact)
  warm 1M     ONE 1%-churn batch tick on the same arena (the repair
              kernel's transposed pass at shape; zero cold passes)
  stream 1M   single-provider heartbeat events through the
              StreamEngine on the same 1M arena (p50/p99 apply+repair
              latency, zero cold passes, closing reconcile)

Every row is APPENDED to the artifact as it completes (kill-proof, as
in PR 1) and tagged with the runtime ISA. The ladder1m_* floors in
perf_floor.json are checked HERE — the run is far too long for the CI
perf-gate job, so this script is the gate for its own rows.

Population: bench.synth_providers(rng(2)) x synth_requirements(rng(3))
— the same basis as every cand_*/simd_* floor.

    PROTOCOL_TPU_NATIVE_ISA=auto python scripts/cold_ladder_1m.py
    python scripts/cold_ladder_1m.py --rungs 65536 --size 0   # rungs only
"""
import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

import bench  # noqa: E402
from protocol_tpu import native  # noqa: E402
from protocol_tpu.ops.cost import CostWeights  # noqa: E402
from protocol_tpu.utils.artifacts import append_jsonl  # noqa: E402

FLOOR_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "perf_floor.json")


def _pop(n: int):
    ep = bench.synth_providers(np.random.default_rng(2), n)
    er = bench.synth_requirements(np.random.default_rng(3), n)
    return ep, er


def _gen_row(ep, er, w, n: int, isa: str, emit) -> float:
    """One bucketed cold-generation rung at the given ISA; returns wall."""
    eff = native.set_isa(isa)
    st: dict = {}
    t0 = time.perf_counter()
    native.fused_topk_candidates(
        ep, er, w, k=64, threads=1, bucketed=True, stats=st
    )
    wall = time.perf_counter() - t0
    cells = float(n) * n
    emit({
        "kind": "gen", "n": n, "isa": eff, "threads": 1,
        "wall_s": round(wall, 1),
        "visited_frac": round(st["gen_visited"] / cells, 4),
        "visited_cells_per_s": int(st["gen_visited"] / wall),
        "pruned_rows": st["gen_pruned_rows"],
        "fallback_rows": st["gen_fallback_rows"],
    })
    return wall


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--rungs", default="65536,262144",
        help="comma-separated gen-only rung sizes (vector ISA; the "
        "first rung also runs the scalar referee for the speedup row)",
    )
    ap.add_argument("--size", type=int, default=1_000_000,
                    help="full arena shape (0 skips the 1M phases)")
    ap.add_argument("--events", type=int, default=256,
                    help="heartbeat events for the stream phase")
    ap.add_argument("--churn", type=float, default=0.01)
    ap.add_argument(
        "--artifact", default="artifacts/cold_ladder_rows.jsonl",
        help="JSONL file each row is APPENDED to as it completes "
        "(kill-proof). Empty string disables.",
    )
    args = ap.parse_args()

    with open(FLOOR_PATH) as fh:
        floors = json.load(fh)
    failures: list = []

    def emit(row: dict) -> None:
        print(json.dumps(row), flush=True)
        append_jsonl(args.artifact, row)

    native.load()
    vec = native.set_isa(native.isa_request() or "auto")
    w = CostWeights()
    print(f"# cold ladder: vector isa={vec}", file=sys.stderr, flush=True)

    # ---- gen-only rungs: the candidate-generation wall vs shape
    rungs = [int(r) for r in args.rungs.split(",") if r]
    for i, n in enumerate(rungs):
        t0 = time.perf_counter()
        ep, er = _pop(n)
        print(f"# rung {n}: population built {time.perf_counter()-t0:.1f}s",
              file=sys.stderr, flush=True)
        wall_v = _gen_row(ep, er, w, n, vec, emit)
        if i == 0 and vec != "scalar":
            wall_s = _gen_row(ep, er, w, n, "scalar", emit)
            emit({
                "kind": "gen_speedup", "n": n, "vector_isa": vec,
                "scalar_s": round(wall_s, 1), "vector_s": round(wall_v, 1),
                "speedup": round(wall_s / max(wall_v, 1e-9), 2),
            })
            native.set_isa(vec)
        del ep, er

    if args.size <= 0:
        return _verdict(failures)

    # ---- the full shape: one arena, three measurements
    from protocol_tpu.native.arena import NativeSolveArena
    from protocol_tpu.proto import wire
    from protocol_tpu.stream.engine import StreamEngine
    from protocol_tpu.stream.events import StreamEvent
    from protocol_tpu.trace import format as tfmt

    n = args.size
    t0 = time.perf_counter()
    ep, er = _pop(n)
    print(f"# {n}: population built {time.perf_counter()-t0:.1f}s",
          file=sys.stderr, flush=True)

    # eps 4.0 -> 1.0: the bounded cold ladder every prior 1M artifact
    # used — completeness evidence at this eps is the 1M smoke's 99.97%
    # (SCALING.md)
    arena = NativeSolveArena(threads=0, eps_start=4.0, eps_end=1.0,
                             event_max_bids=4096)
    t0 = time.perf_counter()
    p4t = arena.solve(ep, er, w)
    wall = time.perf_counter() - t0
    st = arena.last_stats
    cells = float(n) * n
    gen_s = st["gen_ms"] / 1e3
    visited = st.get("eng_gen_visited")
    cold_row = {
        "kind": "cold", "n": n, "isa": st["native_isa"],
        "wall_s": round(wall, 1),
        "gen_s": round(gen_s, 1),
        "solve_s": round(st["solve_ms"] / 1e3, 1),
        "visited_frac":
            round(visited / cells, 4) if visited is not None else None,
        "assigned": int((p4t >= 0).sum()),
        "assigned_frac": round(int((p4t >= 0).sum()) / n, 4),
    }
    emit(cold_row)
    if gen_s > floors["ladder1m_cold_gen_s_max"]:
        failures.append(
            f"1M cold gen {gen_s:.0f}s above ceiling "
            f"{floors['ladder1m_cold_gen_s_max']}s"
        )
    if cold_row["assigned_frac"] < floors["ladder1m_min_assigned_frac"]:
        failures.append(
            f"1M cold assigned frac {cold_row['assigned_frac']} below "
            f"{floors['ladder1m_min_assigned_frac']}"
        )

    # ---- one 1%-churn warm batch tick (the repair kernel at shape)
    rng = np.random.default_rng(4)
    rows = rng.choice(n, max(int(n * args.churn), 1), replace=False)
    price = np.array(ep.price, copy=True)
    load = np.array(ep.load, copy=True)
    price[rows] = rng.uniform(0.5, 4.0, rows.size).astype(np.float32)
    load[rows] = rng.uniform(0, 1, rows.size).astype(np.float32)
    ep2 = dataclasses.replace(ep, price=price, load=load)
    t0 = time.perf_counter()
    p4t = arena.solve(ep2, er, w)
    wall = time.perf_counter() - t0
    st = arena.last_stats
    warm_row = {
        "kind": "warm", "n": n, "isa": st["native_isa"],
        "churn": args.churn,
        "wall_s": round(wall, 1),
        "repair_s": round(st["gen_ms"] / 1e3, 1),
        "solve_s": round(st["solve_ms"] / 1e3, 1),
        "cold_passes": st["cand_cold_passes"],
        "assigned_frac": round(int((p4t >= 0).sum()) / n, 4),
    }
    emit(warm_row)
    if warm_row["cold_passes"] != 0:
        failures.append(
            f"1M warm tick ran {warm_row['cold_passes']} full-matrix "
            "candidate passes (want 0)"
        )
    if wall > floors["ladder1m_warm_tick_s_max"]:
        failures.append(
            f"1M warm tick {wall:.0f}s above ceiling "
            f"{floors['ladder1m_warm_tick_s_max']}s"
        )

    # ---- streamed single-provider heartbeats on the same 1M arena
    se = StreamEngine(arena, w, reconcile_every=10 ** 9)
    p_cols = wire.canon_columns(ep2, tfmt.P_TRACE_DTYPES)
    # canon may hand back views of ep2's columns: copy before mutating
    p_cols["price"] = p_cols["price"].copy()
    p_cols["load"] = p_cols["load"].copy()
    hb = rng.choice(n, args.events, replace=False)
    walls = []
    cold_passes = 0
    for i, r in enumerate(hb.tolist()):
        rr = np.asarray([r], np.int32)
        p_cols["price"][rr] = rng.uniform(0.5, 4.0, 1).astype(np.float32)
        p_cols["load"][rr] = rng.uniform(0, 1, 1).astype(np.float32)
        ev = StreamEvent(
            kind="heartbeat", source=f"p{r}", seq=0,
            provider_rows=rr,
            p_cols={nm: a[rr] for nm, a in p_cols.items()},
            task_rows=np.zeros(0, np.int32), r_cols={},
        )
        t0 = time.perf_counter()
        res = se.apply(ev)
        walls.append((time.perf_counter() - t0) * 1e3)
        cold_passes += int(res.stats.get("cand_cold_passes", 0))
    walls_a = np.asarray(walls)
    t0 = time.perf_counter()
    recon = se.reconcile()
    recon_s = time.perf_counter() - t0
    p99 = float(np.percentile(walls_a, 99))
    stream_row = {
        "kind": "stream", "n": n, "isa": native.current_isa(),
        "events": args.events,
        "apply_p50_ms": round(float(np.percentile(walls_a, 50)), 1),
        "apply_p99_ms": round(p99, 1),
        "apply_max_ms": round(float(walls_a.max()), 1),
        "cold_passes": cold_passes,
        "reconcile_s": round(recon_s, 1),
        "assigned_frac": round(int((recon.plan >= 0).sum()) / n, 4),
    }
    emit(stream_row)
    if cold_passes != 0:
        failures.append(
            f"1M stream ran {cold_passes} full-matrix passes (want 0)"
        )
    if p99 > floors["ladder1m_stream_p99_ms_max"]:
        failures.append(
            f"1M stream apply p99 {p99:.0f}ms above ceiling "
            f"{floors['ladder1m_stream_p99_ms_max']}ms"
        )
    if stream_row["assigned_frac"] < floors["ladder1m_min_assigned_frac"]:
        failures.append(
            f"1M stream reconcile assigned frac "
            f"{stream_row['assigned_frac']} below "
            f"{floors['ladder1m_min_assigned_frac']}"
        )
    return _verdict(failures)


def _verdict(failures: list) -> int:
    if failures:
        for f in failures:
            print(f"LADDER FLOOR FAIL: {f}", file=sys.stderr)
        return 1
    print("cold ladder floors OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
