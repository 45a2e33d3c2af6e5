#!/usr/bin/env python
"""Scheduler-kernel benchmark. Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Measures the batched job<->worker matching throughput on the live accelerator
(the orchestrator hot path: BASELINE.md ladder) against the reference's
algorithmic envelope — a host-side greedy first-fit matcher equivalent to
crates/orchestrator/src/scheduler/mod.rs:26-74 (numpy-vectorized per-task
argmin, which is *generous* to the baseline: the reference re-fetches and
filters all tasks per node heartbeat).

Problem: synthetic marketplace, P providers x T tasks, multi-resource
feature vectors (GPU class/count/memory, CPU, RAM, storage, geo, price),
~uniform compatibility structure from the real compat_mask encoding.

The default mode and ``engine=jax[:D]`` measure the accelerator and FAIL
when JAX finds no TPU: a number from XLA's CPU backend is never printed
under a device metric's name. A CPU measurement is asked for by name
(key=value args):

    python bench.py engine=native-mt threads=4

``engine=native`` measures the single-threaded C++ engine;
``engine=native-mt`` the multi-threaded engine with a PIPELINED stage
overlap — the next solve's fused cost-build runs on a worker thread
while the current solve's auction runs (ctypes releases the GIL for the
duration of each native call, so the overlap is real). The reported
matching is checked bit-identical against threads=1. ``quality=`` and
``wire=`` are CPU-engine modes as well.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

import jax

from protocol_tpu.ops.assign import assign_auction, assign_greedy
from protocol_tpu.ops.cost import INFEASIBLE, CostWeights, cost_matrix
from protocol_tpu.ops.encoding import EncodedProviders, EncodedRequirements
from protocol_tpu.ops.sparse import (
    assign_auction_sparse_scaled,
    candidates_topk_bidir,
)

P, T = 32768, 32768
TOPK = 64
TILE = 2048

# The synthetic marketplace generators live in the flight-recorder
# subsystem (the single source of synthetic populations); re-exported
# here because every bench/script/test historically reaches them as
# ``bench.synth_providers``.
from protocol_tpu.trace.synth import (  # noqa: E402
    MAX_GPU_OPTS,
    MODEL_CLASSES,
    MODEL_WORDS,
    synth_providers,
    synth_requirements,
)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def tpu_match(ep: EncodedProviders, er: EncodedRequirements):
    """Full hot path: streaming BIDIRECTIONAL candidate generation over the
    featurized cost tensor (never materializing [P, T]) + eps-scaled sparse
    frontier auction with cleanup. Reverse (provider->task) edges guarantee
    every provider appears in the candidate graph — forward-only top-k left
    ~9% of providers unreachable at 32k (coverage-capped matching). Host
    loop over jitted phases — each phase executable is cached after warmup."""

    cand_p, cand_c = candidates_topk_bidir(
        ep, er, CostWeights(), k=TOPK, tile=TILE, reverse_r=8, extra=16
    )
    res = assign_auction_sparse_scaled(
        cand_p, cand_c, num_providers=ep.gpu_count.shape[0],
        eps_start=4.0, eps_end=0.05, max_iters_per_phase=400,
    )
    return res.provider_for_task, res.num_assigned()


def cpu_greedy_baseline(cost: np.ndarray) -> tuple[np.ndarray, float]:
    """Reference-equivalent greedy: each task in arrival order takes the
    cheapest free compatible provider."""
    t0 = time.perf_counter()
    avail = np.ones(cost.shape[0], bool)
    out = np.full(cost.shape[1], -1, np.int64)
    for t in range(cost.shape[1]):
        col = np.where(avail, cost[:, t], INFEASIBLE)
        p = int(np.argmin(col))
        if col[p] < INFEASIBLE * 0.5:
            out[t] = p
            avail[p] = False
    return out, time.perf_counter() - t0


def bench_native_mt(ep, er, threads: int, iters: int, st_total: float) -> dict:
    """engine=native-mt: multi-threaded fused pass + deterministic Jacobi
    auction, with the stage boundary OVERLAPPED — iteration i+1's fused
    cost-build runs on a worker thread while iteration i's auction runs on
    the main thread (both native calls drop the GIL). Steady-state
    pipelined wall-clock per solve is the metric; the matching is checked
    bit-identical against the same engine at threads=1."""
    import os
    from concurrent.futures import ThreadPoolExecutor

    from protocol_tpu import native
    from protocol_tpu.ops.cost import CostWeights

    n_threads = threads or (os.cpu_count() or 1)
    w = CostWeights()

    def gen():
        return native.fused_topk_candidates(
            ep, er, w, k=TOPK, threads=n_threads
        )

    with ThreadPoolExecutor(max_workers=1) as ex:
        t0 = time.perf_counter()
        fut = ex.submit(gen)
        for i in range(iters):
            cand_p, cand_c = fut.result()
            if i + 1 < iters:
                fut = ex.submit(gen)  # next cost-build overlaps this auction
            p4t, _, _ = native.auction_sparse_mt(
                cand_p, cand_c, num_providers=P, threads=n_threads
            )
        wall = (time.perf_counter() - t0) / iters
    n_assigned = int((p4t >= 0).sum())
    # determinism referee: the same engine, single thread, must reproduce
    # the matching bit-for-bit (cand structure identity is covered by the
    # parity tests; the auction is the order-sensitive half)
    p4t_ref, _, _ = native.auction_sparse_mt(
        cand_p, cand_c, num_providers=P, threads=1
    )
    bit_identical = bool(np.array_equal(p4t, p4t_ref))
    log(
        f"native-mt pipelined end-to-end ({n_threads} threads): "
        f"{wall * 1e3:.1f} ms/solve ({n_assigned / wall:,.0f} assignments/s; "
        f"{st_total / wall:.2f}x single-threaded engine; "
        f"bit-identical to threads=1: {bit_identical})"
    )
    return {
        "wall_s": wall,
        "assigned": n_assigned,
        "threads": n_threads,
        "bit_identical": bit_identical,
    }


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _churn_providers(p_cols, rng, churn: float) -> None:
    """Mutate ~churn of the provider rows in place (price + load — the
    per-heartbeat drift every real fleet reports)."""
    n = p_cols["price"].shape[0]
    rows = rng.choice(n, max(1, int(n * churn)), replace=False)
    p_cols["price"][rows] = rng.uniform(0.5, 4.0, rows.size).astype(np.float32)
    p_cols["load"][rows] = rng.uniform(0, 1, rows.size).astype(np.float32)


def run_wire_bench(
    P: int = 16384,
    T: int = 16384,
    churn: float = 0.01,
    ticks: int = 5,
    warmup: int = 3,
    threads: int = 0,
    seed: int = 0,
    chunk_bytes: int = 1 << 20,
    modes: tuple = ("v1", "v2"),
    trace_path: str = "",
) -> dict:
    """Loopback wire-path benchmark: the scheduler seam end-to-end
    (client serialize + RPC + server decode + warm native-mt solve) under
    steady-state churn, v1 full-snapshot unary vs v2 delta sessions.

    Both modes run against a FRESH server with the same synthetic
    marketplace and the same churn sequence (same rng seeds): one untimed
    cold tick, then ``warmup`` untimed churn ticks (the post-cold
    adaptation transient, where contested near-tie seats price out), then
    ``ticks`` timed steady-state ticks. The difference between modes is
    pure wire protocol — the warm solve behind both is the same arena.
    Returns per-tick wall/bytes/assigned per mode plus the v1/v2 speedup
    and bytes ratio, and the server-side seam metrics scraped from
    Health.

    With ``trace_path`` set, the population AND the per-tick churn come
    from a recorded/synthetic flight-recorder trace instead of the
    inline generator — the same captured workload both modes (and every
    future bench run) consume, instead of an unshareable rng sequence."""
    from protocol_tpu.ops.cost import CostWeights
    from protocol_tpu.proto import scheduler_pb2 as pbs
    from protocol_tpu.proto import wire as wirelib
    from protocol_tpu.services.scheduler_grpc import (
        SchedulerBackendClient,
        encoded_to_proto,
        encoded_to_proto_v2,
        serve,
    )

    kernel = f"native-mt:{threads}" if threads else "native-mt"
    w = CostWeights()
    trace_deltas = None
    if trace_path:
        from protocol_tpu.trace import format as tfmt

        tr = tfmt.read_trace(trace_path)
        if tr.snapshot is None:
            raise SystemExit(f"{trace_path}: no snapshot frame")
        P, T = tr.snapshot.n_providers, tr.snapshot.n_tasks
        trace_deltas = tr.deltas
        if not trace_deltas:
            raise SystemExit(
                f"{trace_path} holds no delta ticks (snapshot only) — "
                "the wire bench measures steady-state ticks; synth a "
                "trace with --ticks >= 1"
            )
        if warmup + ticks > len(trace_deltas):
            ticks = max(len(trace_deltas) - warmup, 1)
            warmup = max(min(warmup, len(trace_deltas) - ticks), 0)
            log(
                f"trace holds {len(trace_deltas)} ticks: clamped to "
                f"warmup={warmup} ticks={ticks}"
            )
    out: dict = {
        "P": P, "T": T, "churn": churn, "ticks": ticks,
        "kernel": kernel, "modes": {},
    }
    if trace_path:
        out["trace"] = trace_path

    def _apply_tick(i: int, p_cols, r_cols, churn_rng) -> None:
        """Mutate the columns for tick i (1-based): the trace's recorded
        delta when one is loaded, else synthetic price/load churn."""
        if trace_deltas is not None:
            d = trace_deltas[i - 1]
            for rows, delta, cols in (
                (d.provider_rows, d.p_cols, p_cols),
                (d.task_rows, d.r_cols, r_cols),
            ):
                for name, vals in delta.items():
                    cols[name][rows] = vals
        else:
            _churn_providers(p_cols, churn_rng, churn)

    for mode in modes:
        port = _free_port()
        server = serve(f"127.0.0.1:{port}")
        client = SchedulerBackendClient(f"127.0.0.1:{port}")
        if trace_deltas is not None:
            p_cols = {k: v.copy() for k, v in tr.snapshot.p_cols.items()}
            r_cols = {k: v.copy() for k, v in tr.snapshot.r_cols.items()}
        else:
            rng = np.random.default_rng(seed)
            ep = synth_providers(rng, P)
            er = synth_requirements(rng, T)
            p_cols = wirelib.canon_columns(ep, wirelib.P_WIRE_DTYPES)
            r_cols = wirelib.canon_columns(er, wirelib.R_WIRE_DTYPES)
        full = wirelib.take_rows  # ns view over all rows
        churn_rng = np.random.default_rng(seed + 1)
        tick_ms: list[float] = []
        tick_bytes: list[int] = []
        tick_assigned: list[int] = []
        if mode == "v1":
            # untimed cold tick: arena build + jit-free native warmup
            req = encoded_to_proto(
                full(p_cols, slice(None)), full(r_cols, slice(None)), w,
                kernel=kernel, top_k=64, eps=0.02,
            )
            client.assign(req, timeout=600)
            for i in range(warmup + ticks):
                _apply_tick(i + 1, p_cols, r_cols, churn_rng)
                t0 = time.perf_counter()
                req = encoded_to_proto(
                    full(p_cols, slice(None)), full(r_cols, slice(None)),
                    w, kernel=kernel, top_k=64, eps=0.02,
                )
                resp = client.assign(req, timeout=600)
                if i < warmup:
                    continue
                tick_ms.append((time.perf_counter() - t0) * 1e3)
                tick_bytes.append(req.ByteSize() + resp.ByteSize())
                tick_assigned.append(int(resp.num_assigned))
        else:
            fp = wirelib.epoch_fingerprint(
                p_cols, r_cols, w, kernel, 64, 0.02, 0
            )
            reqv2 = encoded_to_proto_v2(
                full(p_cols, slice(None)), full(r_cols, slice(None)), w,
                kernel=kernel, top_k=64, eps=0.02,
            )
            resp = client.open_session(
                wirelib.chunk_snapshot(
                    "bench", fp, reqv2, chunk_bytes=chunk_bytes
                ),
                timeout=600,
            )
            assert resp.ok, resp.error
            prev = {k: v.copy() for k, v in p_cols.items()}
            prev_r = {k: v.copy() for k, v in r_cols.items()}
            for tick in range(1, warmup + ticks + 1):
                _apply_tick(tick, p_cols, r_cols, churn_rng)
                t0 = time.perf_counter()
                # the timed tick includes the client-side churn scan: the
                # column diff is part of what v2 pays that v1 does not
                rows = wirelib.dirty_rows(p_cols, prev)
                trows = wirelib.dirty_rows(r_cols, prev_r)
                dreq = pbs.AssignDeltaRequest(
                    session_id="bench", epoch_fingerprint=fp, tick=tick
                )
                if rows.size:
                    dreq.provider_rows.CopyFrom(wirelib.blob(rows, np.int32))
                    dreq.providers.CopyFrom(
                        wirelib.encode_providers_v2(
                            wirelib.take_rows(p_cols, rows)
                        )
                    )
                if trows.size:
                    dreq.task_rows.CopyFrom(wirelib.blob(trows, np.int32))
                    dreq.requirements.CopyFrom(
                        wirelib.encode_requirements_v2(
                            wirelib.take_rows(r_cols, trows)
                        )
                    )
                dresp = client.assign_delta(dreq, timeout=600)
                assert dresp.session_ok, dresp.error
                prev = {k: v.copy() for k, v in p_cols.items()}
                prev_r = {k: v.copy() for k, v in r_cols.items()}
                if tick <= warmup:
                    continue
                tick_ms.append((time.perf_counter() - t0) * 1e3)
                tick_bytes.append(dreq.ByteSize() + dresp.ByteSize())
                tick_assigned.append(int(dresp.result.num_assigned))
        h = client.health()
        seam = {s.name: s.value for s in h.seam_metrics}
        # latency DISTRIBUTION per tick, not just means: headline p50/p99
        # are exact (the raw walls are in hand — np.percentile), and the
        # obs LatencyHistogram snapshot rides alongside (the same
        # estimator the per-session registries use at fleet scale, where
        # raw samples can't be kept)
        from protocol_tpu.obs.metrics import percentiles_ms

        pct = percentiles_ms(tick_ms)
        p50 = round(float(np.percentile(tick_ms, 50)), 2)
        p99 = round(float(np.percentile(tick_ms, 99)), 2)
        out["modes"][mode] = {
            "tick_ms": [round(x, 2) for x in tick_ms],
            "mean_tick_ms": round(sum(tick_ms) / len(tick_ms), 2),
            "median_tick_ms": round(float(np.median(tick_ms)), 2),
            "min_tick_ms": round(min(tick_ms), 2),
            "p50_tick_ms": p50,
            "p99_tick_ms": p99,
            "tick_percentiles": pct,
            "mean_tick_bytes": int(sum(tick_bytes) / len(tick_bytes)),
            "tick_assigned": tick_assigned,
            "server_seam": seam,
        }
        log(
            f"wire={mode}: mean {out['modes'][mode]['mean_tick_ms']:.1f} "
            f"ms/tick (p50 {p50}, p99 {p99}), "
            f"{out['modes'][mode]['mean_tick_bytes']:,} B/tick"
        )
        client.close()
        server.stop(grace=None)
    if "v1" in out["modes"] and "v2" in out["modes"]:
        # the headline (and CI-gated) speedup is MEDIAN tick vs median
        # tick: the warm arena's dual-refresh cycle makes individual
        # ticks bimodal (fast shielded ticks vs post-refresh adaptation
        # ticks), and a mean over a short window is noisy about where
        # the cycle landed. The mean-based number rides along.
        v1md = out["modes"]["v1"]["median_tick_ms"]
        v2md = out["modes"]["v2"]["median_tick_ms"]
        out["v2_speedup"] = round(v1md / v2md, 2)
        out["v2_speedup_mean"] = round(
            out["modes"]["v1"]["mean_tick_ms"]
            / out["modes"]["v2"]["mean_tick_ms"],
            2,
        )
        out["v2_bytes_ratio"] = round(
            out["modes"]["v1"]["mean_tick_bytes"]
            / max(out["modes"]["v2"]["mean_tick_bytes"], 1),
            1,
        )
        log(
            f"wire v2 delta tick: {out['v2_speedup']}x faster (median; "
            f"mean {out['v2_speedup_mean']}x), "
            f"{out['v2_bytes_ratio']}x fewer bytes than v1 full snapshot"
        )
    return out


def run_quality_bench(
    P: int = 4096,
    T: int = 4096,
    churn: float = 0.01,
    ticks: int = 12,
    warmup: int = 2,
    threads: int = 0,
    engine: str = "auction",
    seed: int = 0,
) -> dict:
    """Warm-chain arena bench WITH the decision-quality plane on: one
    cold solve, ``warmup`` untimed churn ticks, then ``ticks`` timed
    ticks at ``churn`` provider churn — reporting headline p50/p99 tick
    walls, assigned fraction, and the quality scalars (certified
    duality gap, plan churn ratio, starvation, unassigned causes) the
    r06 bench round joins on."""
    import dataclasses

    from protocol_tpu.native.arena import NativeSolveArena
    from protocol_tpu.obs.metrics import percentiles_ms

    rng = np.random.default_rng(seed)
    ep = synth_providers(rng, P)
    er = synth_requirements(rng, T)
    arena = NativeSolveArena(
        threads=threads, engine="sinkhorn" if engine == "sinkhorn" else
        "auction",
    )
    churn_rng = np.random.default_rng(seed + 1)

    def _tick(e):
        price = np.array(e.price, copy=True)
        load = np.array(e.load, copy=True)
        rows = churn_rng.choice(P, max(1, int(P * churn)), replace=False)
        price[rows] = np.round(
            np.clip(price[rows] + churn_rng.uniform(-0.5, 0.5, rows.size),
                    0.05, None), 4
        ).astype(price.dtype)
        load[rows] = np.clip(
            load[rows] + churn_rng.uniform(-0.2, 0.2, rows.size)
            .astype(load.dtype), 0.0, 1.0
        )
        return dataclasses.replace(e, price=price, load=load)

    t0 = time.perf_counter()
    p4t = arena.solve(ep, er, CostWeights())
    cold_ms = (time.perf_counter() - t0) * 1e3
    for _ in range(warmup):
        ep = _tick(ep)
        arena.solve(ep, er, CostWeights())
    walls, quality_ticks = [], []
    for _ in range(ticks):
        ep = _tick(ep)
        t0 = time.perf_counter()
        p4t = arena.solve(ep, er, CostWeights())
        walls.append((time.perf_counter() - t0) * 1e3)
        quality_ticks.append({
            k: v for k, v in arena.last_stats.items()
            if isinstance(v, (int, float, bool))
        })
    assigned = int((p4t[:T] >= 0).sum())
    from protocol_tpu.obs.quality import aggregate_quality

    pct = percentiles_ms(walls)
    return {
        "P": P, "T": T, "churn": churn, "ticks": ticks,
        "engine": engine, "threads": arena.threads,
        "cold_ms": round(cold_ms, 3),
        "p50_tick_ms": pct["p50_ms"],
        "p99_tick_ms": pct["p99_ms"],
        "mean_tick_ms": round(float(np.mean(walls)), 3),
        "assigned_frac": round(assigned / T, 6),
        # the shared canonical roll-up (same vocabulary as replay
        # reports and obs report — cross-round joins stay schema-stable)
        "quality": aggregate_quality(quality_ticks) or {},
    }


def run_jax_arena_bench(
    n: int = 16384,
    devices: int = 0,
    churn: float = 0.01,
    ticks: int = 3,
    seed: int = 0,
) -> dict:
    """``engine=jax[:D]`` bench: the first-class jax arena's cold solve
    (compiled — compile is paid once untimed, like every other row) and
    a warm chain at ``churn`` REQUIREMENT churn riding the churn-masked
    structure repair (ISSUE 18 — warm ticks pay O(churn) repair, never
    a regen: asserted via ``cand_cold_passes``). Requirement-side churn
    is the informative warm case for this engine: provider repricing at
    k=64 honestly dirties ~half the candidate rows (every row listing a
    repriced provider) — that case is covered by the ``--cand`` gate's
    native rows and the repair-parity tests. Every tick reports its
    gen/solve wall split (cold and warm) in the artifact JSON."""
    import dataclasses

    from protocol_tpu.parallel.jax_arena import JaxSolveArena

    rng = np.random.default_rng(seed)
    ep = synth_providers(rng, n)
    er = synth_requirements(rng, n)
    w = CostWeights()
    arena = JaxSolveArena(devices=devices)
    arena.solve(ep, er, w)  # compile pass, untimed
    arena.invalidate()
    t0 = time.perf_counter()
    p4t = arena.solve(ep, er, w)
    cold_s = time.perf_counter() - t0
    cold_solve_ms = arena.last_stats["solve_ms"]
    cold_gen_ms = arena.last_stats["gen_ms"]
    sharded = bool(arena.last_stats.get("gen_sharded"))
    churn_rng = np.random.default_rng(seed + 1)
    walls, gens, solves, tick_detail = [], [], [], []
    cold_passes_warm = 0
    for _ in range(ticks):
        rows = churn_rng.choice(n, max(1, int(n * churn)), replace=False)
        ram = np.array(er.ram_mb, copy=True)
        ram[rows] = np.maximum(
            256,
            (ram[rows] * churn_rng.uniform(0.8, 1.25, rows.size)).astype(
                ram.dtype
            ),
        )
        er = dataclasses.replace(er, ram_mb=ram)
        t0 = time.perf_counter()
        p4t = arena.solve(ep, er, w)
        walls.append((time.perf_counter() - t0) * 1e3)
        s = arena.last_stats
        gens.append(s["gen_ms"])
        solves.append(s["solve_ms"])
        cold_passes_warm += int(s.get("cand_cold_passes", 0))
        tick_detail.append({
            "wall_ms": round(walls[-1], 3),
            "gen_ms": s["gen_ms"],
            "solve_ms": s["solve_ms"],
            "cand_cold_passes": s.get("cand_cold_passes"),
            "repair_rows": s.get("repair_rows"),
            "repair_providers": s.get("repair_providers"),
            "visited_cells_frac": s.get("visited_cells_frac"),
            "changed_rows": s.get("changed_rows"),
        })
    warm_ms = float(np.median(walls))
    return {
        "n": n,
        "devices": arena._devices_effective,
        "gen_sharded": sharded,
        "cold_ms": round(cold_s * 1e3, 3),
        "cold_gen_ms": cold_gen_ms,
        "cold_solve_ms": cold_solve_ms,
        "warm_median_ms": round(warm_ms, 3),
        "warm_gen_median_ms": round(float(np.median(gens)), 3),
        "warm_solve_median_ms": round(float(np.median(solves)), 3),
        "warm_wall_speedup": round(cold_s * 1e3 / max(warm_ms, 1e-9), 2),
        "warm_gen_speedup": round(
            cold_gen_ms / max(float(np.median(gens)), 1e-9), 2
        ),
        "warm_solve_speedup": round(
            cold_solve_ms / max(float(np.median(solves)), 1e-9), 2
        ),
        "warm_cand_cold_passes": cold_passes_warm,
        "warm_ticks": tick_detail,
        "assigned_frac": round(int((p4t >= 0).sum()) / n, 6),
    }


def require_tpu(what: str) -> dict:
    """The device modes measure the accelerator or nothing: exit
    non-zero, before any result line, unless JAX's default backend is a
    TPU. Returns ``utils.platform.device_summary()``."""
    from protocol_tpu.utils.platform import device_summary

    device = device_summary()
    if device["platform"] != "tpu":
        raise SystemExit(
            f"{what} measures the TPU, but jax found platform "
            f"{device['platform']!r} ({device['device_kind']} "
            f"x{device['device_count']}); ask for a CPU measurement by "
            "name: engine=native | engine=native-mt | quality=1 | wire=both"
        )
    return device


def parse_kv_args(argv: list[str]) -> dict[str, str]:
    """``engine=native-mt threads=4``-style arguments (ignores flags)."""
    out: dict[str, str] = {}
    for a in argv:
        k, sep, v = a.partition("=")
        if sep:
            out[k] = v
    return out


def main() -> None:
    global P, T, TILE
    from protocol_tpu.utils.platform import place_compile_cache

    place_compile_cache()
    args = parse_kv_args(sys.argv[1:])
    if args.get("quality"):
        # quality=1 [p= t= churn= ticks= threads= engine= out=]: the
        # r06 bench round — warm-chain arena ticks with the decision-
        # quality plane on. Stable metric name, platform field per the
        # PR 3 convention, quality scalars nested so cross-round joins
        # (BENCH_r0*.json) survive schema growth.
        jax.config.update("jax_platforms", "cpu")
        res = run_quality_bench(
            P=int(args.get("p", "4096")),
            T=int(args.get("t", "4096")),
            churn=float(args.get("churn", "0.01")),
            ticks=int(args.get("ticks", "12")),
            threads=int(args.get("threads", "0") or 0),
            engine=args.get("engine", "auction"),
        )
        headline = {
            "metric": (
                f"warm_tick_quality_{res['P']}x{res['T']}_"
                f"churn{res['churn']}"
            ),
            "platform": "native_cpu_engine_requested",
            "value": res["p50_tick_ms"],
            "unit": "ms_per_warm_tick_p50",
            "p50_tick_ms": res["p50_tick_ms"],
            "p99_tick_ms": res["p99_tick_ms"],
            "assigned_frac": res["assigned_frac"],
            "quality": res["quality"],
        }
        out_path = args.get("out")
        if out_path:
            with open(out_path, "w") as fh:
                json.dump({**headline, "detail": res}, fh, indent=1)
                fh.write("\n")
            log(f"wrote {out_path}")
        print(json.dumps(headline))
        return
    wire = args.get("wire")
    if wire:
        # wire=v1|v2|both: loopback wire-path bench (the scheduler seam
        # itself, not the kernel) — steady-state churn ticks over gRPC
        if wire not in ("v1", "v2", "both"):
            raise SystemExit(f"unknown wire mode {wire!r} (want v1|v2|both)")
        jax.config.update("jax_platforms", "cpu")
        modes = ("v1", "v2") if wire == "both" else (wire,)
        res = run_wire_bench(
            P=int(args.get("p", "16384")),
            T=int(args.get("t", "16384")),
            churn=float(args.get("churn", "0.01")),
            ticks=int(args.get("ticks", "5")),
            warmup=int(args.get("warmup", "3")),
            threads=int(args.get("threads", "0") or 0),
            modes=modes,
            # trace=<path>: consume a flight-recorder trace (population +
            # churn sequence) instead of generating inline
            trace_path=args.get("trace", ""),
        )
        out_path = args.get("out")
        if out_path:
            with open(out_path, "w") as fh:
                json.dump(res, fh, indent=1)
            log(f"wrote {out_path}")
        if wire == "both":
            print(json.dumps({
                "metric": (
                    f"wire_v2_delta_tick_speedup_{res['P']}x{res['T']}_"
                    f"churn{res['churn']}"
                ),
                "value": res["v2_speedup"],
                "unit": "x_vs_v1_full_snapshot",
                "bytes_ratio": res["v2_bytes_ratio"],
                "v1_mean_tick_ms": res["modes"]["v1"]["mean_tick_ms"],
                "v2_mean_tick_ms": res["modes"]["v2"]["mean_tick_ms"],
                "v1_p50_tick_ms": res["modes"]["v1"]["p50_tick_ms"],
                "v1_p99_tick_ms": res["modes"]["v1"]["p99_tick_ms"],
                "v2_p50_tick_ms": res["modes"]["v2"]["p50_tick_ms"],
                "v2_p99_tick_ms": res["modes"]["v2"]["p99_tick_ms"],
            }))
        else:
            m = res["modes"][wire]
            print(json.dumps({
                "metric": (
                    f"wire_{wire}_tick_{res['P']}x{res['T']}_"
                    f"churn{res['churn']}"
                ),
                "value": m["mean_tick_ms"],
                "unit": "ms_per_tick",
                "p50_tick_ms": m["p50_tick_ms"],
                "p99_tick_ms": m["p99_tick_ms"],
                "mean_tick_bytes": m["mean_tick_bytes"],
            }))
        return
    engine = args.get("engine", "")
    if engine.partition(":")[0] == "jax":
        # engine=jax[:D] [n= churn= ticks= out=]: the first-class jax
        # arena. Provenance (backend platform + effective device count)
        # rides in the "platform" field per the PR 3 convention; the
        # metric NAME stays stable across hosts and meshes.
        suffix = engine.partition(":")[2]
        if suffix and not suffix.isdigit():
            raise SystemExit(
                f"bad jax device suffix {suffix!r} (want jax[:D])"
            )
        device = require_tpu("engine=jax")
        churn = float(args.get("churn", "0.01"))
        res = run_jax_arena_bench(
            n=int(args.get("n", args.get("p", "16384"))),
            devices=int(suffix or 0),
            churn=churn,
            ticks=int(args.get("ticks", "3")),
        )
        headline = {
            "metric": f"jax_arena_cold_warm_{res['n']}x{res['n']}_"
                      f"churn{churn}_top{TOPK}",
            "platform": (
                f"jax {device['platform']} d{res['devices']}"
                + ("" if res["gen_sharded"] else " unsharded")
            ),
            "device_kind": device["device_kind"],
            "device_count": device["device_count"],
            "value": res["warm_median_ms"],
            "unit": "ms_per_warm_tick_median",
            **{k: v for k, v in res.items() if k != "n"},
        }
        out_path = args.get("out")
        if out_path:
            with open(out_path, "w") as fh:
                json.dump(headline, fh, indent=1)
                fh.write("\n")
            log(f"wrote {out_path}")
        print(json.dumps(headline))
        return
    if engine not in ("", "native", "native-mt"):
        raise SystemExit(
            f"unknown engine {engine!r} (want native|native-mt|jax[:D])"
        )
    threads = int(args.get("threads", "0") or 0)
    rng = np.random.default_rng(0)
    # engine=native[-mt] is an explicit request to measure the CPU engine;
    # with no engine named this is the accelerator bench, and it fails
    # rather than measure anything else
    native_requested = bool(engine)
    if native_requested:
        log(f"engine={engine} requested: measuring the native CPU engine")
        jax.config.update("jax_platforms", "cpu")
        # 16k: large enough that the greedy baseline's O(P*T) scan and
        # cost build bite, small enough that the whole CPU bench stays
        # ~1 min (the fused native engine solves it COMPLETE in ~1 s)
        P = T = 16384
        TILE = 1024
    else:
        device = require_tpu("the default accelerator bench")
    log(f"devices: {jax.devices()}")
    log(f"building synthetic marketplace P={P} T={T}")
    ep = synth_providers(rng, P)  # numpy-backed, host-side
    er = synth_requirements(rng, T)

    # ---- CPU baseline first, on the host backend: cost matrix, then the
    # reference-equivalent greedy matcher over it.
    log("computing cost matrix + greedy baseline on host CPU...")
    cpu = jax.devices("cpu")[0]
    cost_fn = jax.jit(lambda e, r: cost_matrix(e, r, CostWeights())[0])
    cost_build_time = 0.0
    with jax.default_device(cpu):
        cost_np = np.asarray(cost_fn(ep, er))
        if native_requested:
            # timed second build (cheap at this scale) for the fair
            # end-to-end comparison; the accelerator path never rebuilds
            # the multi-GB tensor just to decorate a log line
            t0 = time.perf_counter()
            cost_np = np.asarray(cost_fn(ep, er))
            cost_build_time = time.perf_counter() - t0
    _, cpu_time = cpu_greedy_baseline(cost_np)
    log(
        f"cpu greedy wall: {cpu_time * 1e3:.1f} ms "
        f"(+{cost_build_time * 1e3:.1f} ms cost build)"
    )

    # the native C++ engine: this framework's own CPU backend
    # (TpuBatchMatcher(native_fallback=True) solves with it when the
    # accelerator is absent). The fused engine computes cost from the
    # encoded features internally — [P, T] never materializes (the twin
    # of the sparse TPU path's streaming candidates_topk). A build or
    # load failure is this bench's failure, not a skipped row.
    from protocol_tpu import native

    t0 = time.perf_counter()
    cand_p, cand_c = native.fused_topk_candidates(ep, er, CostWeights(), k=TOPK)
    p4t_native = native.auction_sparse(cand_p, cand_c, num_providers=P)
    native_time = time.perf_counter() - t0
    log(
        f"native C++ fused cost+topk+auction wall: {native_time * 1e3:.1f} ms "
        f"({int((p4t_native >= 0).sum())} assigned)"
    )

    if native_requested:
        # The CPU engine end-to-end from encoded features (its cost
        # computation happens inside the kernel, so each timed iteration
        # pays the full cost+candidates+auction).
        iters = 5
        t0 = time.perf_counter()
        for _ in range(iters):
            cand_p, cand_c = native.fused_topk_candidates(
                ep, er, CostWeights(), k=TOPK
            )
            p4t_native = native.auction_sparse(cand_p, cand_c, num_providers=P)
        total = (time.perf_counter() - t0) / iters
        n_assigned = int((p4t_native >= 0).sum())
        # equal footing: both sides pay the cost-tensor build (the greedy
        # baseline above was handed a prebuilt matrix)
        baseline_total = cost_build_time + cpu_time
        log(
            f"native engine end-to-end: {total * 1e3:.1f} ms/solve "
            f"({n_assigned / total:,.0f} assignments/s; greedy end-to-end "
            f"{baseline_total * 1e3:.1f} ms)"
        )
        # Platform provenance rides in a dedicated "platform" field, NOT
        # in the metric name: a provenance-suffixed name made the same
        # measurement land under different metric keys depending on the
        # host, corrupting cross-round joins over the BENCH_r0*.json
        # series. The metric NAME is stable.
        if engine == "native-mt":
            mt = bench_native_mt(ep, er, threads, iters, total)
            print(
                json.dumps(
                    {
                        "metric": (
                            f"sparse_top{TOPK}_{P}x{T}_native_mt_engine_"
                            "match_throughput"
                        ),
                        "platform": "native_cpu_engine_requested",
                        "value": round(mt["assigned"] / mt["wall_s"], 1),
                        "unit": "assignments/sec",
                        "vs_baseline": round(baseline_total / mt["wall_s"], 2),
                        "threads": mt["threads"],
                        "vs_single_thread": round(total / mt["wall_s"], 2),
                        "bit_identical_to_threads1": mt["bit_identical"],
                    }
                )
            )
            return
        print(
            json.dumps(
                {
                    "metric": (
                        f"sparse_top{TOPK}_{P}x{T}_native_engine_match_"
                        "throughput"
                    ),
                    "platform": "native_cpu_engine_requested",
                    "value": round(n_assigned / total, 1),
                    "unit": "assignments/sec",
                    "vs_baseline": round(baseline_total / total, 2),
                }
            )
        )
        return
    del cost_np

    # ---- TPU path: ship features (O(P+T) bytes), compile, time
    accel = jax.devices()[0]
    ep = jax.tree.map(lambda x: jax.device_put(x, accel), ep)
    er = jax.tree.map(lambda x: jax.device_put(x, accel), er)
    log("compiling + warmup...")
    p4t, n_assigned = tpu_match(ep, er)
    n_assigned = int(n_assigned)
    log(f"warmup done, assigned {n_assigned}/{T}")

    iters = 5
    t0 = time.perf_counter()
    for _ in range(iters):
        p4t, na = tpu_match(ep, er)
        jax.block_until_ready((p4t, na))
    tpu_time = (time.perf_counter() - t0) / iters
    n_assigned = int(na)
    log(f"tpu full-match wall: {tpu_time * 1e3:.1f} ms  ({n_assigned / tpu_time:,.0f} assignments/s)")

    # stable metric name; provenance in the platform/device fields (see
    # the CPU-engine emitters above for why)
    print(
        json.dumps(
            {
                "metric": f"sparse_top{TOPK}_{P}x{T}_auction_match_throughput",
                **device,
                "value": round(n_assigned / tpu_time, 1),
                "unit": "assignments/sec",
                "vs_baseline": round(cpu_time / tpu_time, 2),
            }
        )
    )


if __name__ == "__main__":
    main()
