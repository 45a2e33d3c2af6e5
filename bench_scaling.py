"""Scaling table for the 1M x 1M provider-sharded configuration (ladder #4).

Produces BASELINE.md's missing evidence: MEASURED per-shard numbers for the
two stages of the sparse pipeline —

  stage A  candidates_topk   streaming top-K candidate generation,
                             peak memory O(P_shard * tile)
  stage B  sparse auction    frontier auction over [T, K] candidates
                             (one device)

— plus compile-time HBM envelopes from XLA's buffer assignment at the FULL
ladder-#4 shapes (P_shard = 1M/8 per v5e-8 chip, T = 1M, K = 64), which do
not require executing at that scale.

Measures the TPU and fails without one; ``--cpu`` asks for the virtual
8-device CPU mesh by name. Every row is labeled with the platform it was
measured on, and a stage that raises makes the run exit non-zero.
Usage: python bench_scaling.py [--full] [--cpu]  (--full uses ladder-#4
tile/K and larger measurement shapes; default is a quick pass).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--full", action="store_true")
    parser.add_argument("--cpu", action="store_true", help="pin the CPU backend")
    parser.add_argument(
        "--artifact",
        default="artifacts/bench_scaling_rows.jsonl",
        help="JSONL file each stage row is APPENDED to as it completes — "
        "a timeout/kill preserves every finished stage's evidence "
        "(VERDICT r5 'what's weak' #4). Empty string disables.",
    )
    parser.add_argument(
        "--trace",
        default="",
        help="flight-recorder trace whose snapshot supplies the measured "
        "populations (sliced to each stage's shape) instead of the "
        "inline generator — the same captured fleet every run measures",
    )
    args = parser.parse_args()

    import os

    from protocol_tpu.utils.platform import (
        device_summary,
        force_host_cpu,
        place_compile_cache,
    )

    place_compile_cache()
    if args.cpu:
        force_host_cpu(8)

    import bench  # synth data (host-side) + the jax-arena stage
    import jax

    platform = device_summary()["platform"]
    if not args.cpu and platform != "tpu":
        raise SystemExit(
            f"bench_scaling measures the TPU, but jax found platform "
            f"{platform!r}; pass --cpu to measure the virtual CPU mesh"
        )
    import jax.numpy as jnp

    from protocol_tpu.ops.cost import CostWeights
    from protocol_tpu.ops.encoding import FeatureEncoder
    from protocol_tpu.ops.sparse import assign_auction_sparse, candidates_topk

    n_dev = len(jax.devices())
    log(f"platform={platform} devices={n_dev}")

    # ---- shapes
    K = 64
    TILE = 1024
    LADDER_P_SHARD = 1_000_000 // 8  # per-chip provider shard on v5e-8
    LADDER_T = 1_000_000
    if args.full:
        P_MEAS, T_MEAS = 131_072, 8_192  # measured stage-A shard
        T_AUCTION = 65_536  # measured stage-B frontier set
    else:
        P_MEAS, T_MEAS = 16_384, 2_048
        T_AUCTION = 8_192

    rng = np.random.default_rng(0)
    enc = FeatureEncoder()
    weights = CostWeights()

    # population source: the shared generators (trace/synth.py), or a
    # recorded trace's snapshot sliced to each stage's measurement shape
    if args.trace:
        from protocol_tpu.ops.encoding import (
            EncodedProviders,
            EncodedRequirements,
        )
        from protocol_tpu.trace import format as tfmt

        snap = tfmt.read_trace(args.trace).snapshot
        if snap is None:
            raise SystemExit(f"{args.trace}: no snapshot frame")

        def population(rng_, n_p, n_t):
            if n_p > snap.n_providers or n_t > snap.n_tasks:
                raise SystemExit(
                    f"{args.trace} holds {snap.n_providers}x{snap.n_tasks} "
                    f"rows; stage needs {n_p}x{n_t}"
                )
            return (
                EncodedProviders(
                    **{k: v[:n_p] for k, v in snap.p_cols.items()}
                ),
                EncodedRequirements(
                    **{k: v[:n_t] for k, v in snap.r_cols.items()}
                ),
            )
    else:
        def population(rng_, n_p, n_t):
            return (
                bench.synth_providers(rng_, n_p),
                bench.synth_requirements(rng_, n_t),
            )

    rows: list[dict] = []

    from protocol_tpu.utils.artifacts import append_jsonl

    def emit(row: dict) -> None:
        # kill-proof evidence: every completed stage lands on disk NOW
        rows.append(row)
        append_jsonl(args.artifact, row)

    # stages that raised: logged, the remaining stages still run (every
    # finished row is already on disk), and the run exits non-zero
    failed: list[str] = []

    def stage_failed(stage: str, exc: Exception) -> None:
        log(f"  {stage} failed: {type(exc).__name__}: {exc}")
        failed.append(stage)

    # per-iteration walls of the most recent measure() call — the obs
    # histograms turn them into p50/p99 fields on the artifact rows
    # (distribution numbers instead of means only)
    last_walls_s: list[float] = []

    def measure(fn, warmup=1, iters=3):
        for _ in range(warmup):
            jax.block_until_ready(fn())
        last_walls_s.clear()
        t_all = time.perf_counter()
        for _ in range(iters):
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn())
            last_walls_s.append(time.perf_counter() - t0)
        return (time.perf_counter() - t_all) / iters, out

    def tick_pct() -> dict:
        """p50/p99 (seconds) of the most recent measure()'s iterations —
        exact (np.percentile over the retained walls; the obs histograms
        are for streams whose samples can't be kept)."""
        if not last_walls_s:
            return {"p50_s": 0.0, "p99_s": 0.0, "iters": 0}
        return {
            "p50_s": round(float(np.percentile(last_walls_s, 50)), 4),
            "p99_s": round(float(np.percentile(last_walls_s, 99)), 4),
            "iters": len(last_walls_s),
        }

    # ---------------- stage A: candidate generation ----------------
    log(f"stage A: candidates_topk P={P_MEAS} T={T_MEAS} K={K} tile={TILE}")
    ep_np, er_np = population(rng, P_MEAS, T_MEAS)
    ep_dev = jax.tree.map(jnp.asarray, ep_np)
    er_dev = jax.tree.map(jnp.asarray, er_np)
    secs, (cand_p, cand_c) = measure(
        lambda: candidates_topk(ep_dev, er_dev, weights, k=K, tile=TILE)
    )
    cells = P_MEAS * T_MEAS
    emit(
        {
            "stage": "A candidates_topk (measured)",
            "platform": platform,
            "shape": f"P={P_MEAS} T={T_MEAS} K={K} tile={TILE}",
            "wall_s": round(secs, 3),
            "cells_per_s": round(cells / secs / 1e9, 3),  # Gcell/s
            **tick_pct(),
        }
    )
    log(f"  {secs:.3f}s  ({cells / secs / 1e9:.2f} Gcells/s)")

    # full ladder-#4 stage-A cost model: (P_shard x T) cells per chip
    ladder_cells = LADDER_P_SHARD * LADDER_T
    emit(
        {
            "stage": "A candidates_topk (extrapolated per chip)",
            "platform": f"{platform} rate -> v5e-8 shard",
            "shape": f"P_shard={LADDER_P_SHARD} T={LADDER_T} K={K}",
            "wall_s": round(ladder_cells / (cells / secs), 1),
            "note": "linear in cells at fixed tile; v5e MXU rate is the "
            "open factor (measure on-chip when healthy)",
        }
    )

    # ---- stage-boundary overlap: stage B's BIDIRECTIONAL candidate
    # generation (the wire-path default's dominant cost-build) starts on a
    # worker thread NOW, while stage A's compile-time envelope analysis
    # runs — the generation wall is still timed inside the thread and
    # reported in its own row, but the artifact run's total wall-clock
    # (the thing timeouts kill) no longer pays the two stages in sequence.
    from concurrent.futures import ThreadPoolExecutor

    from protocol_tpu.ops.sparse import candidates_topk_bidir

    P_B = T_AUCTION
    epb, erb = population(rng, P_B, T_AUCTION)

    def _gen_bidir():
        t0 = time.perf_counter()
        cpb, ccb = candidates_topk_bidir(
            epb, erb, weights, k=K, tile=TILE, reverse_r=8, extra=16
        )
        jax.block_until_ready((cpb, ccb))
        return cpb, ccb, time.perf_counter() - t0

    overlap_pool = ThreadPoolExecutor(max_workers=1)
    bidir_future = overlap_pool.submit(_gen_bidir)

    # compile-time HBM envelope at FULL shard shape (no execution)
    log("stage A: HBM envelope via XLA buffer assignment at full shard shape")
    try:
        import dataclasses

        def _struct_like(obj, n):
            out = {}
            for f in dataclasses.fields(obj):
                a = np.asarray(getattr(obj, f.name))
                shape = (n,) + a.shape[1:]
                out[f.name] = jax.ShapeDtypeStruct(shape, a.dtype)
            return dataclasses.replace(obj, **out)

        ep_s = _struct_like(ep_np, LADDER_P_SHARD)
        # T enters via the tile scan; the envelope is dominated by P*tile
        lowered = jax.jit(
            lambda ep, er: candidates_topk(ep, er, weights, k=K, tile=TILE)
        ).lower(ep_s, _struct_like(er_np, TILE * 2))
        ma = lowered.compile().memory_analysis()
        hbm_gb = (ma.temp_size_in_bytes + ma.argument_size_in_bytes) / 1e9
        emit(
            {
                "stage": "A candidates_topk (HBM envelope, compile-time)",
                "platform": f"{platform} buffer assignment",
                "shape": f"P_shard={LADDER_P_SHARD} tile={TILE} K={K}",
                "hbm_gb": round(hbm_gb, 2),
                "fits_16gb": hbm_gb < 16,
            }
        )
        log(f"  {hbm_gb:.2f} GB (fits 16 GB: {hbm_gb < 16})")
    except Exception as e:
        stage_failed("stage A HBM envelope", e)

    # ---------------- stage B: sparse frontier auction ----------------
    # The BIDIRECTIONAL-candidate row comes first: it is the wire-path
    # default (every production matcher path generates bidir candidates),
    # so a run killed mid-stage-B leaves the row that matters on disk
    # (VERDICT r5 "what's weak" #4's ordering half).
    cpb, ccb, gen_bidir = bidir_future.result()
    overlap_pool.shutdown(wait=False)
    cov_bd = int(np.unique(np.asarray(cpb)[np.asarray(cpb) >= 0]).size)
    log(
        f"stage B: sparse auction T={T_AUCTION} K={K} single-device "
        f"(bidir wire-path default; gen overlapped stage A: {gen_bidir:.2f}s)"
    )
    secs_b, res = measure(
        lambda: assign_auction_sparse(
            cpb, ccb, num_providers=P_B, eps=0.05, max_iters=2000,
            frontier=min(T_AUCTION, 8192), retire=True,
        ).provider_for_task
    )
    assigned = int((np.asarray(res) >= 0).sum())
    emit(
        {
            "stage": "B sparse auction (measured, 1 device, bidir wire-path default)",
            "platform": platform,
            "shape": f"T={T_AUCTION} K={K} reverse_r=8 extra=16",
            "wall_s": round(secs_b, 3),
            "assignments_per_s": round(assigned / secs_b, 0),
            "assigned": assigned,
            "bidir_gen_s": round(gen_bidir, 2),
            "coverage": cov_bd,
        }
    )
    log(f"  {secs_b:.3f}s, {assigned}/{T_AUCTION} assigned "
        f"({assigned / secs_b:,.0f} assignments/s)")

    # stage B memory envelope at T=1M
    try:
        cp_s = jax.ShapeDtypeStruct((LADDER_T, K), jnp.int32)
        cc_s = jax.ShapeDtypeStruct((LADDER_T, K), jnp.float32)
        lowered = jax.jit(
            lambda p, c: assign_auction_sparse(
                p, c, num_providers=LADDER_P_SHARD, eps=0.05,
                max_iters=2000, frontier=8192, retire=True,
            ).provider_for_task
        ).lower(cp_s, cc_s)
        ma = lowered.compile().memory_analysis()
        hbm_gb = (ma.temp_size_in_bytes + ma.argument_size_in_bytes) / 1e9
        emit(
            {
                "stage": "B sparse auction (HBM envelope, compile-time)",
                "platform": f"{platform} buffer assignment",
                "shape": f"T={LADDER_T} K={K}",
                "hbm_gb": round(hbm_gb, 2),
                "fits_16gb": hbm_gb < 16,
            }
        )
        log(f"  T=1M envelope: {hbm_gb:.2f} GB (fits 16 GB: {hbm_gb < 16})")
    except Exception as e:
        stage_failed("stage B HBM envelope", e)

    # ---------------- stage B2: assignment completeness -------------------
    # VERDICT r3 item 3's done-bar: >=99% assignment at T>=65k in bounded
    # wall-clock. Forward-only top-k coverage-caps the matching (every
    # task's window holds the same cheap providers; at 65k only 49,813 of
    # 65,536 providers appear in ANY list -> 66.5% assigned no matter how
    # long the auction runs). Bidirectional candidates (per-provider
    # reverse edges, ops/sparse.candidates_topk_bidir) restore coverage
    # and the eps-scaled solve completes: 99.98% measured at 65k.
    from protocol_tpu.ops.sparse import assign_auction_sparse_scaled

    log(f"stage B2: completeness, forward vs bidir candidates T={T_AUCTION}")
    cp, cc = candidates_topk(epb, erb, weights, k=K, tile=TILE)
    jax.block_until_ready((cp, cc))
    cov_fwd = int(np.unique(np.asarray(cp)[np.asarray(cp) >= 0]).size)
    res_fwd = assign_auction_sparse_scaled(cp, cc, num_providers=P_B)
    a_fwd = int((np.asarray(res_fwd.provider_for_task) >= 0).sum())
    t0 = time.perf_counter()
    res_bd = assign_auction_sparse_scaled(cpb, ccb, num_providers=P_B)
    solve_bidir = time.perf_counter() - t0
    a_bd = int((np.asarray(res_bd.provider_for_task) >= 0).sum())
    emit(
        {
            "stage": "B2 completeness: forward vs bidir candidates",
            "platform": platform,
            "shape": f"T={T_AUCTION} K={K} reverse_r=8 extra=16",
            "fwd_assigned": a_fwd,
            "fwd_coverage": cov_fwd,
            "bidir_assigned": a_bd,
            "bidir_coverage": cov_bd,
            "bidir_gen_s": round(gen_bidir, 2),
            "bidir_solve_s": round(solve_bidir, 2),
            "complete_pct": round(100.0 * a_bd / T_AUCTION, 2),
        }
    )
    log(
        f"  forward: {a_fwd}/{T_AUCTION} assigned (coverage {cov_fwd}) -> "
        f"bidir: {a_bd}/{T_AUCTION} ({100.0 * a_bd / T_AUCTION:.2f}%, "
        f"coverage {cov_bd})"
    )

    # ---------------- stage C: incremental (warm) vs cold solve ----------
    # VERDICT r2 item 3's done-bar: the warm path re-bids only the delta
    # frontier from carried prices + the previous matching. At kernel level
    # the candidate structure is shared, so this isolates the auction's
    # warm win; the matcher-level win (which also skips candidate
    # regeneration via the CandidateCache) is larger — see
    # tests/test_scale_matcher.py.
    from protocol_tpu.ops.sparse import assign_auction_sparse_warm

    # bidir candidates from stage B2: the production path — forward-only
    # lists coverage-cap at scale and the cold ladder then "wins" by
    # stalling out at the wall, making warm-vs-cold meaningless
    log(f"stage C: warm vs cold sparse solve T={T_AUCTION} K={K} (bidir)")
    secs_cold, out_cold = measure(
        lambda: assign_auction_sparse_scaled(
            cpb, ccb, num_providers=P_B, frontier=min(T_AUCTION, 8192),
            with_state=True,
        )
    )
    cold_pct = tick_pct()
    res_cold, price_cold, retired_cold, _reserve = out_cold
    # 1% churn: drop a contiguous 1% of the matching (freed providers /
    # re-opened tasks) and re-solve warm from the carried duals — prices
    # AND the retirement mask (the production chain shape; without the
    # mask the warm solve re-fights the priced-out tail every step)
    p4t0 = jnp.asarray(res_cold.provider_for_task)
    n_churn = max(T_AUCTION // 100, 1)
    p4t0 = p4t0.at[:n_churn].set(-1)
    secs_warm, _ = measure(
        lambda: assign_auction_sparse_warm(
            cpb, ccb, num_providers=P_B,
            price0=price_cold, p4t0=p4t0, retired0=retired_cold,
            frontier=min(T_AUCTION, 8192),
        )[0].provider_for_task
    )
    warm_pct = tick_pct()
    emit(
        {
            "stage": "C warm vs cold solve (measured)",
            "platform": platform,
            "shape": f"T={T_AUCTION} K={K}, 1% churn",
            "cold_s": round(secs_cold, 4),
            "warm_s": round(secs_warm, 4),
            "speedup": round(secs_cold / max(secs_warm, 1e-9), 1),
            "cold_p50_s": cold_pct["p50_s"],
            "cold_p99_s": cold_pct["p99_s"],
            "warm_p50_s": warm_pct["p50_s"],
            "warm_p99_s": warm_pct["p99_s"],
        }
    )
    log(
        f"  cold {secs_cold * 1e3:.1f} ms -> warm {secs_warm * 1e3:.1f} ms "
        f"({secs_cold / max(secs_warm, 1e-9):.1f}x)"
    )

    # ---------------- stage D: ladder #5 vector bin-pack ------------------
    # BASELINE.md config #5: multi-resource capacity vectors + anti-affinity
    # (ops/binpack.py). Measured at the 10k-task test scale.
    from protocol_tpu.ops.binpack import assign_binpack_ffd

    P_D, T_D, R_D = 2048, 10240, 4
    log(f"stage D: vector bin-pack P={P_D} T={T_D} R={R_D} + anti-affinity")
    rng_d = np.random.default_rng(5)
    cost_d = rng_d.uniform(1.0, 10.0, (P_D, T_D)).astype(np.float32)
    cost_d[rng_d.uniform(size=(P_D, T_D)) > 0.7] = 1e9
    demand = rng_d.integers(1, 4, (T_D, R_D)).astype(np.float32)
    capacity = rng_d.integers(8, 21, (P_D, R_D)).astype(np.float32)
    n_groups = T_D // 8
    anti = np.where(
        rng_d.uniform(size=T_D) < 0.2,
        rng_d.integers(0, n_groups, T_D),
        -1,
    ).astype(np.int32)
    loc = rng_d.integers(0, 256, P_D).astype(np.int32)
    cost_d_dev, demand_dev, capacity_dev = (
        jnp.asarray(cost_d), jnp.asarray(demand), jnp.asarray(capacity)
    )
    anti_dev, loc_dev = jnp.asarray(anti), jnp.asarray(loc)
    secs_d, res_d = measure(
        lambda: assign_binpack_ffd(
            cost_d_dev, demand_dev, capacity_dev,
            anti_group=anti_dev, loc_id=loc_dev,
            num_locations=256, num_groups=n_groups,
        ).provider_for_task
    )
    packed = int((np.asarray(res_d) >= 0).sum())
    emit(
        {
            "stage": "D vector bin-pack + anti-affinity (measured)",
            "platform": platform,
            "shape": f"P={P_D} T={T_D} R={R_D} groups={n_groups}",
            "wall_s": round(secs_d, 3),
            "tasks_per_s": round(packed / max(secs_d, 1e-9), 0),
            "packed": packed,
            **tick_pct(),
        }
    )
    log(f"  {secs_d:.3f}s, {packed}/{T_D} packed")

    # ---------------- stage S: ladder #3 Sinkhorn-OT ----------------
    # BASELINE config #3 (100k x 100k soft assignment, 1 chip): matrix-
    # free log-domain potentials (ops/blocked.py — O(P*tile) peak, never
    # [P, T]) + plan-guided candidate rounding.
    from protocol_tpu.ops.blocked import sinkhorn_potentials_blocked

    P_S = T_S = T_AUCTION
    # Each Sinkhorn iteration streams 2 full [P, T] logsumexp passes:
    # 20 iterations at 65k on the 1-core CPU host is ~8 h — the reason
    # the r4 artifact died before emitting a stage-S row. The iteration
    # budget is therefore platform-aware (overridable via
    # PROTOCOL_TPU_SINKHORN_ITERS) and recorded in the row's shape
    # string; quality at few iterations is measured separately against
    # the auction referee (scripts/stage_s_100k.py: mean cost within
    # 0.02% at iters=5).
    default_iters = 20 if platform != "cpu" else 4
    sink_iters = int(
        os.environ.get("PROTOCOL_TPU_SINKHORN_ITERS", default_iters)
    )
    log(
        f"stage S: sinkhorn potentials + rounding P=T={P_S} "
        f"(matrix-free, iters={sink_iters})"
    )
    eps_sink = 0.05
    # potentials are computed ONCE and fed into the plan-guided rounding
    # (assign_sinkhorn_blocked would recompute them, doubling the
    # dominant O(P*T*iters) stage — the r4/early-r5 artifact deaths)
    t0 = time.perf_counter()
    u_s, _v_s = sinkhorn_potentials_blocked(
        epb, erb, weights, eps=eps_sink, num_iters=sink_iters, tile=TILE
    )
    jax.block_until_ready(u_s)
    secs_pot = time.perf_counter() - t0
    t0 = time.perf_counter()
    offset_s = -eps_sink * jnp.where(u_s > -5e17, u_s, 0.0)
    cand_sp, cand_sc2 = candidates_topk(
        epb, erb, weights, k=32, tile=TILE, provider_offset=offset_s
    )
    res_s = assign_auction_sparse_scaled(
        cand_sp, cand_sc2, num_providers=P_S, eps_start=1.0, eps_end=0.02
    )
    sink_assigned = int((np.asarray(res_s.provider_for_task) >= 0).sum())
    secs_s_full = secs_pot + (time.perf_counter() - t0)
    emit(
        {
            "stage": "S sinkhorn-OT potentials + rounding (measured)",
            "platform": platform,
            "shape": f"P=T={P_S} iters={sink_iters} tile={TILE}",
            "potentials_s": round(secs_pot, 3),
            "end_to_end_s": round(secs_s_full, 3),
            "assigned": sink_assigned,
        }
    )
    log(
        f"  potentials {secs_pot:.3f}s; end-to-end {secs_s_full:.3f}s "
        f"({sink_assigned}/{T_S} assigned)"
    )
    # ladder-#3 HBM envelope at the full 100k shape (compile-time)
    try:
        import dataclasses as _dc2

        def _sds(obj, n):
            out = {}
            for f in _dc2.fields(obj):
                a = np.asarray(getattr(obj, f.name))
                out[f.name] = jax.ShapeDtypeStruct((n,) + a.shape[1:], a.dtype)
            return _dc2.replace(obj, **out)

        lowered = jax.jit(
            lambda e, r: sinkhorn_potentials_blocked(
                e, r, weights, eps=eps_sink, num_iters=sink_iters, tile=TILE
            )
        ).lower(_sds(epb, 100_000), _sds(erb, 100_000 // TILE * TILE))
        ma = lowered.compile().memory_analysis()
        hbm_gb = (ma.temp_size_in_bytes + ma.argument_size_in_bytes) / 1e9
        emit(
            {
                "stage": "S sinkhorn potentials (HBM envelope, compile-time)",
                "platform": f"{platform} buffer assignment",
                "shape": f"P=T~100k tile={TILE}",
                "hbm_gb": round(hbm_gb, 2),
                "fits_16gb": hbm_gb < 16,
            }
        )
        log(f"  100k envelope: {hbm_gb:.2f} GB (fits 16 GB: {hbm_gb < 16})")
    except Exception as e:
        stage_failed("stage S HBM envelope", e)

    # ---------------- stage S (sparse): native O(nnz) sinkhorn-mt ---------
    # The ladder-#3 engine that actually completes at 100k x 100k
    # (scripts/stage_s_100k.py --engine sparse-mt): log-domain entropic OT
    # over the top-K candidate edges (nnz = T*K_eff per iteration, never
    # O(P*T)) + injective auction-referee rounding seeded from the duals.
    # Measured here at the bench shape on the SAME instance as the blocked
    # row above, so the two engines' wall-clocks are directly comparable.
    try:
        from protocol_tpu import native as native_mod

        if not native_mod.available():
            raise RuntimeError("no native toolchain")
        log(f"stage S (sparse): native sinkhorn-mt P=T={P_S}")
        t0 = time.perf_counter()
        cand_np, cand_nc = native_mod.fused_topk_candidates(
            epb, erb, weights, k=K, reverse_r=8, extra=16, threads=0
        )
        t_cand = time.perf_counter() - t0
        phase_stats: list = []
        t0 = time.perf_counter()
        f_s, _g_s = native_mod.sinkhorn_sparse_anneal(
            cand_np, cand_nc, P_S, eps_start=1.0, eps_end=0.05,
            iters_per_phase=50, tol=1e-2, threads=0,
            phase_stats=phase_stats,
        )
        t_pot_sp = time.perf_counter() - t0
        from protocol_tpu.ops.cost import INFEASIBLE as _INF

        feas = (cand_np >= 0) & (cand_nc < _INF * 0.5)
        price0 = native_mod.sinkhorn_referee_prices(f_s, cand_np, cand_nc)
        t0 = time.perf_counter()
        p4t_sp, _, _ = native_mod.auction_sparse_mt(
            cand_np, cand_nc, num_providers=P_S,
            eps_start=0.32, eps_end=0.02, threads=0, price=price0,
        )
        t_round = time.perf_counter() - t0
        emit(
            {
                "stage": "S sparse sinkhorn-mt + auction-referee rounding (measured)",
                "platform": "native_cpu",
                "shape": f"P=T={P_S} K_eff={cand_np.shape[1]} "
                         f"nnz={int(feas.sum())}",
                "cand_s": round(t_cand, 3),
                "potentials_s": round(t_pot_sp, 3),
                "rounding_s": round(t_round, 3),
                "end_to_end_s": round(t_cand + t_pot_sp + t_round, 3),
                "assigned": int((p4t_sp >= 0).sum()),
                "phases": phase_stats,
            }
        )
        log(
            f"  cand {t_cand:.2f}s + potentials {t_pot_sp:.2f}s + rounding "
            f"{t_round:.2f}s = {t_cand + t_pot_sp + t_round:.2f}s "
            f"({int((p4t_sp >= 0).sum())}/{T_S} assigned)"
        )
    except Exception as e:
        stage_failed("stage S sparse sinkhorn-mt", e)

    # ---------------- stage J: first-class jax arena (engine=jax) ---------
    # The jax engine behind the native-arena interface: sharded candidate
    # generation over the FULL visible mesh + adaptive eps-ladder solve
    # with warm dual carry. Device-count provenance rides in the platform
    # field (PR 3 convention); the sharded-gen bits are D-invariant by
    # contract (perf_gate --jax proves it), so this row measures the ICI/
    # host-mesh scaling of an identical computation, not a different one.
    try:
        log(f"stage J: jax arena cold+warm, full {n_dev}-device mesh")
        res_j = bench.run_jax_arena_bench(n=4096, devices=0)
        emit(
            {
                "stage": "J jax arena cold+warm (engine=jax, measured)",
                "platform": f"{platform} d{res_j['devices']}"
                            + ("" if res_j["gen_sharded"] else " unsharded"),
                "shape": "P=T=4096 k=64",
                "cold_s": round(res_j["cold_ms"] / 1e3, 3),
                "cold_gen_s": round(res_j["cold_gen_ms"] / 1e3, 3),
                "cold_solve_s": round(res_j["cold_solve_ms"] / 1e3, 3),
                "warm_tick_s": round(res_j["warm_median_ms"] / 1e3, 3),
                "warm_wall_speedup": res_j["warm_wall_speedup"],
                "warm_solve_speedup": res_j["warm_solve_speedup"],
                "assigned_frac": res_j["assigned_frac"],
            }
        )
        log(
            f"  cold {res_j['cold_ms'] / 1e3:.2f}s -> warm "
            f"{res_j['warm_median_ms'] / 1e3:.2f}s "
            f"({res_j['warm_wall_speedup']}x wall, "
            f"{res_j['warm_solve_speedup']}x solve stage; "
            f"sharded={res_j['gen_sharded']})"
        )
    except Exception as e:
        stage_failed("stage J jax arena", e)

    print(json.dumps({"platform": platform, "devices": n_dev, "rows": rows}, indent=1))
    if failed:
        raise SystemExit(f"stages failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
