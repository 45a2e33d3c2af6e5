"""Synthetic workload generators — the single source of synthetic
populations for every bench/script/test in this repo, and the trace
factory behind ``python -m protocol_tpu.trace synth``.

Before the flight recorder, three scripts (bench.py, bench_scaling.py
and a 1M warm-chain script since removed) each carried their own inline
copy of the marketplace generator; numbers measured on "the 16k synthetic fleet"
were never provably the SAME fleet. Now the generators live here, and
:func:`synth_trace` freezes a parameterized workload — churn rate, pool
growth/shrink via validity headroom, hotspot bursts, mass-disconnect —
into a trace file any engine can replay bit-reproducibly.

Generators are numpy-only and seeded; the same (seed, shape, knobs)
always emits byte-identical traces (the frame codec is deterministic
DEFLATE — see trace/format.py).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

MODEL_CLASSES = 12
MODEL_WORDS = 8
MAX_GPU_OPTS = 2


def synth_providers(rng: np.random.Generator, n: int):
    """Vectorized synthetic provider encodings, numpy-backed (host-side);
    device_put the tree to place it on an accelerator."""
    from protocol_tpu.ops.encoding import EncodedProviders

    model = rng.integers(0, MODEL_CLASSES, n).astype(np.int32)
    count = rng.choice([1, 2, 4, 8], n).astype(np.int32)
    mem = rng.choice([16000, 24000, 40000, 80000], n).astype(np.int32)
    return EncodedProviders(
        gpu_count=count,
        gpu_mem_mb=mem,
        gpu_model_id=model,
        has_gpu=np.ones(n, bool),
        has_cpu=np.ones(n, bool),
        cpu_cores=rng.choice([8, 16, 32, 64], n).astype(np.int32),
        ram_mb=rng.choice([32768, 65536, 131072], n).astype(np.int32),
        storage_gb=rng.choice([500, 1000, 4000], n).astype(np.int32),
        lat=np.radians(rng.uniform(-60, 60, n)).astype(np.float32),
        lon=np.radians(rng.uniform(-180, 180, n)).astype(np.float32),
        has_location=np.ones(n, bool),
        price=rng.uniform(0.5, 4.0, n).astype(np.float32),
        load=rng.uniform(0, 1, n).astype(np.float32),
        valid=np.ones(n, bool),
    )


def synth_requirements(rng: np.random.Generator, n: int):
    from protocol_tpu.ops.encoding import EncodedRequirements

    k, w = MAX_GPU_OPTS, MODEL_WORDS
    # each task accepts a random subset of model classes (OR alternatives)
    mask = np.zeros((n, k, w), np.uint32)
    accept = rng.random((n, MODEL_CLASSES)) < 0.4
    accept[np.arange(n), rng.integers(0, MODEL_CLASSES, n)] = True  # >=1 class
    for c in range(MODEL_CLASSES):
        mask[:, 0, c >> 5] |= np.where(
            accept[:, c], np.uint32(1) << np.uint32(c & 31), 0
        ).astype(np.uint32)
    opt_valid = np.zeros((n, k), bool)
    opt_valid[:, 0] = True
    count = np.full((n, k), -1, np.int32)
    count[:, 0] = rng.choice(
        [-1, 1, 2, 4, 8], n, p=[0.4, 0.15, 0.15, 0.15, 0.15]
    )
    mem_min = np.full((n, k), -1, np.int32)
    mem_min[:, 0] = rng.choice([-1, 16000, 40000], n, p=[0.5, 0.3, 0.2])
    return EncodedRequirements(
        cpu_required=np.zeros(n, bool),
        cpu_cores=rng.choice([-1, 8, 16], n, p=[0.5, 0.3, 0.2]).astype(
            np.int32
        ),
        ram_mb=rng.choice([-1, 32768], n, p=[0.6, 0.4]).astype(np.int32),
        storage_gb=rng.choice([-1, 500], n, p=[0.7, 0.3]).astype(np.int32),
        gpu_opt_valid=opt_valid,
        gpu_count=count,
        gpu_mem_min=mem_min,
        gpu_mem_max=np.full((n, k), -1, np.int32),
        gpu_total_mem_min=np.full((n, k), -1, np.int32),
        gpu_total_mem_max=np.full((n, k), -1, np.int32),
        gpu_model_mask=mask,
        gpu_model_constrained=opt_valid.copy(),
        lat=np.radians(rng.uniform(-60, 60, n)).astype(np.float32),
        lon=np.radians(rng.uniform(-180, 180, n)).astype(np.float32),
        has_location=np.ones(n, bool),
        priority=np.zeros(n, np.float32),
        valid=np.ones(n, bool),
    )


def synth_uniform_candidates(
    rng: np.random.Generator, t: int, p: int, k: int = 80,
    cost_hi: float = 10.0,
) -> tuple[np.ndarray, np.ndarray]:
    """Execution-evidence-at-shape candidate lists (the 1M warm-chain /
    stage-B smoke population): uniform random [T, K] provider ids + costs,
    no feature structure. Quality evidence belongs to the real-feature
    generators above."""
    cand_p = rng.integers(0, p, size=(t, k), dtype=np.int32)
    cand_c = rng.uniform(0.0, cost_hi, size=(t, k)).astype(np.float32)
    return cand_p, cand_c


# ---------------- trace factory ----------------


class _W:
    """Weights namespace for wire.epoch_fingerprint (CostWeights without
    the ops/cost import)."""

    def __init__(self, w: tuple):
        self.price, self.load, self.proximity, self.priority = (
            float(x) for x in w
        )


# CostWeights defaults (ops/cost.py) restated — synth stays importable
# without pulling the jax-backed cost module
DEFAULT_WEIGHTS = (1.0, 1.0, 0.001, 0.0)


def synth_trace(
    path: str,
    n_providers: int = 1024,
    n_tasks: int = 1024,
    ticks: int = 16,
    churn: float = 0.01,
    task_churn: float = 0.0,
    seed: int = 0,
    kernel: str = "native-mt",
    top_k: int = 64,
    eps: float = 0.02,
    max_iters: int = 0,
    weights: tuple = DEFAULT_WEIGHTS,
    headroom: float = 0.0,
    growth: float = 0.0,
    hotspot_every: int = 0,
    hotspot_frac: float = 0.05,
    disconnect_at: int = 0,
    disconnect_frac: float = 0.25,
    reconnect_after: int = 0,
    compresslevel: int = 6,
) -> str:
    """Write an input-only trace (no outcomes — ``replay --record`` adds
    them) for a parameterized synthetic workload.

    Knobs:
      churn           fraction of LIVE provider rows whose price/load
                      drift each tick (the per-heartbeat common case)
      task_churn      fraction of task rows re-rolled each tick
                      (requirement churn — structural, re-candidates)
      headroom        fraction of provider rows that start valid=False
                      (the join pool growth draws from; row counts are
                      fixed per epoch, so lifecycle is a validity flip)
      growth          fraction of remaining headroom activated per tick
                      (node-join events); negative = steady shrink
      hotspot_every   every N ticks, burst-load a geographic cluster
                      (hotspot_frac of providers nearest a random center)
      disconnect_at   at tick N, mass-disconnect disconnect_frac of live
                      providers (valid=False) — the failure-domain drill;
                      reconnect_after ticks later they return churned

    Returns ``path``.
    """
    from protocol_tpu.proto import scheduler_pb2 as pb
    from protocol_tpu.proto import wire
    from protocol_tpu.trace import format as tfmt

    rng = np.random.default_rng(seed)
    ep = synth_providers(rng, n_providers)
    er = synth_requirements(rng, n_tasks)
    p_cols = wire.canon_columns(ep, tfmt.P_TRACE_DTYPES)
    r_cols = wire.canon_columns(er, tfmt.R_TRACE_DTYPES)
    if headroom > 0:
        n_off = int(n_providers * headroom)
        if n_off:
            valid = p_cols["valid"].copy()
            valid[rng.choice(n_providers, n_off, replace=False)] = False
            p_cols["valid"] = valid

    wns = _W(weights)
    fp = wire.epoch_fingerprint(
        p_cols, r_cols, wns, kernel, top_k, eps, max_iters
    )
    req = pb.AssignRequestV2(
        providers=wire.encode_providers_v2(tfmt._as_ns(p_cols)),
        requirements=wire.encode_requirements_v2(tfmt._as_ns(r_cols)),
        weights=pb.CostWeights(
            price=wns.price, load=wns.load,
            proximity=wns.proximity, priority=wns.priority,
        ),
        kernel=kernel, top_k=top_k, eps=eps, max_iters=max_iters,
    )
    meta = {
        "generator": "synth_trace",
        "seed": seed,
        "n_providers": n_providers,
        "n_tasks": n_tasks,
        "ticks": ticks,
        "churn": churn,
        "task_churn": task_churn,
        "headroom": headroom,
        "growth": growth,
        "hotspot_every": hotspot_every,
        "disconnect_at": disconnect_at,
    }
    disconnected: Optional[np.ndarray] = None
    with tfmt.TraceWriter(path, meta=meta,
                          compresslevel=compresslevel) as w:
        w.write_snapshot(f"synth-{seed}", fp, req)
        for tick in range(1, ticks + 1):
            prev_p = dict(p_cols)
            prev_r = dict(r_cols)
            events: list = []

            # price/load drift on a random slice of the LIVE fleet
            live = np.flatnonzero(p_cols["valid"])
            n_drift = int(live.size * churn)
            if n_drift:
                rows = rng.choice(live, n_drift, replace=False)
                price = p_cols["price"].copy()
                load = p_cols["load"].copy()
                price[rows] = rng.uniform(0.5, 4.0, rows.size).astype(
                    np.float32
                )
                load[rows] = rng.uniform(0, 1, rows.size).astype(np.float32)
                p_cols["price"], p_cols["load"] = price, load
                events.append({"kind": "heartbeat_drift", "rows": n_drift})

            # requirement churn: re-roll a slice of tasks entirely
            n_tchurn = int(n_tasks * task_churn)
            if n_tchurn:
                rows = rng.choice(n_tasks, n_tchurn, replace=False)
                fresh = wire.canon_columns(
                    synth_requirements(rng, n_tchurn), tfmt.R_TRACE_DTYPES
                )
                for name in r_cols:
                    col = r_cols[name].copy()
                    col[rows] = fresh[name]
                    r_cols[name] = col
                events.append({"kind": "task_churn", "rows": n_tchurn})

            # pool growth/shrink via the validity headroom
            if growth > 0:
                off = np.flatnonzero(~p_cols["valid"])
                n_join = int(off.size * growth)
                if n_join:
                    rows = rng.choice(off, n_join, replace=False)
                    valid = p_cols["valid"].copy()
                    valid[rows] = True
                    p_cols["valid"] = valid
                    events.append({"kind": "node_join", "rows": n_join})
            elif growth < 0:
                on = np.flatnonzero(p_cols["valid"])
                n_leave = int(on.size * -growth)
                if n_leave:
                    rows = rng.choice(on, n_leave, replace=False)
                    valid = p_cols["valid"].copy()
                    valid[rows] = False
                    p_cols["valid"] = valid
                    events.append({"kind": "node_leave", "rows": n_leave})

            # hotspot burst: max out load around a random geo center
            if hotspot_every and tick % hotspot_every == 0:
                lat0 = rng.uniform(-1.0, 1.0)
                lon0 = rng.uniform(-np.pi, np.pi)
                d2 = (p_cols["lat"] - lat0) ** 2 + (p_cols["lon"] - lon0) ** 2
                n_hot = max(int(n_providers * hotspot_frac), 1)
                rows = np.argsort(d2, kind="stable")[:n_hot]
                load = p_cols["load"].copy()
                load[rows] = np.float32(1.0)
                p_cols["load"] = load
                events.append({"kind": "hotspot_burst", "rows": n_hot})

            # mass disconnect / delayed reconnect
            if disconnect_at and tick == disconnect_at:
                on = np.flatnonzero(p_cols["valid"])
                n_down = int(on.size * disconnect_frac)
                if n_down:
                    disconnected = rng.choice(on, n_down, replace=False)
                    valid = p_cols["valid"].copy()
                    valid[disconnected] = False
                    p_cols["valid"] = valid
                    events.append(
                        {"kind": "mass_disconnect", "rows": n_down}
                    )
            if (
                disconnected is not None
                and reconnect_after
                and tick == disconnect_at + reconnect_after
            ):
                valid = p_cols["valid"].copy()
                valid[disconnected] = True
                p_cols["valid"] = valid
                price = p_cols["price"].copy()
                price[disconnected] = rng.uniform(
                    0.5, 4.0, disconnected.size
                ).astype(np.float32)
                p_cols["price"] = price
                events.append(
                    {"kind": "mass_reconnect", "rows": int(disconnected.size)}
                )
                disconnected = None

            prow = wire.dirty_rows(p_cols, prev_p)
            trow = wire.dirty_rows(r_cols, prev_r)
            w.write_delta_cols(
                tick,
                prow,
                {n: a[prow] for n, a in p_cols.items()} if prow.size else None,
                trow,
                {n: a[trow] for n, a in r_cols.items()} if trow.size else None,
                events=events,
            )
    return path


# ---------------- event-trace factory (streaming workloads) ----------


def synth_event_trace(
    path: str,
    n_providers: int = 1024,
    n_tasks: int = 1024,
    events: int = 256,
    seed: int = 0,
    kernel: str = "native-mt",
    top_k: int = 64,
    eps: float = 0.02,
    max_iters: int = 0,
    weights: tuple = DEFAULT_WEIGHTS,
    rate_hz: float = 1000.0,
    heartbeat_w: float = 0.7,
    join_w: float = 0.1,
    leave_w: float = 0.1,
    task_w: float = 0.1,
    headroom: float = 0.1,
    mass_every: int = 0,
    mass_frac: float = 0.1,
    reconcile_every: int = 64,
    compresslevel: int = 6,
) -> str:
    """Write a STREAM trace: one DELTA frame per churn event, each
    carrying the full current row state for its rows plus the stream
    meta ``{kind, source, seq, at_us}`` (protocol_tpu/stream/events.py
    documents the taxonomy and the full-state supersession contract).

    Event sources are the churn emitters themselves — provider node
    ``p<row>`` or task submitter ``t<row>`` — with a strictly monotonic
    per-source seq, so a chaos'd delivery (drop/dup/reorder) of this
    trace converges through the dedup ladder. The arrival schedule is
    OPEN-LOOP and deterministic: ``at_us`` offsets accumulate seeded
    inter-arrival draws around ``1/rate_hz`` (no Poisson process, no
    clock — the same (seed, knobs) always writes byte-identical files).

    ``mass_every`` > 0 additionally injects a multi-row disconnect
    burst every N events (source ``m<k>``) — a latency/pressure drill
    that sits OUTSIDE the per-source supersession contract, so chaos'd
    idempotence workloads keep it at 0 (the default).
    """
    from protocol_tpu.proto import scheduler_pb2 as pb
    from protocol_tpu.proto import wire
    from protocol_tpu.trace import format as tfmt

    rng = np.random.default_rng(seed)
    ep = synth_providers(rng, n_providers)
    er = synth_requirements(rng, n_tasks)
    p_cols = wire.canon_columns(ep, tfmt.P_TRACE_DTYPES)
    r_cols = wire.canon_columns(er, tfmt.R_TRACE_DTYPES)
    n_off = int(n_providers * headroom)
    if n_off:
        valid = p_cols["valid"].copy()
        valid[rng.choice(n_providers, n_off, replace=False)] = False
        p_cols["valid"] = valid

    wns = _W(weights)
    fp = wire.epoch_fingerprint(
        p_cols, r_cols, wns, kernel, top_k, eps, max_iters
    )
    req = pb.AssignRequestV2(
        providers=wire.encode_providers_v2(tfmt._as_ns(p_cols)),
        requirements=wire.encode_requirements_v2(tfmt._as_ns(r_cols)),
        weights=pb.CostWeights(
            price=wns.price, load=wns.load,
            proximity=wns.proximity, priority=wns.priority,
        ),
        kernel=kernel, top_k=top_k, eps=eps, max_iters=max_iters,
    )
    meta = {
        "generator": "synth_event_trace",
        "stream": True,
        "seed": seed,
        "n_providers": n_providers,
        "n_tasks": n_tasks,
        "events": events,
        "rate_hz": rate_hz,
        "headroom": headroom,
        "mass_every": mass_every,
        "reconcile_every": reconcile_every,
    }
    kinds = ("heartbeat", "join", "leave", "task")
    mix = np.asarray(
        [heartbeat_w, join_w, leave_w, task_w], np.float64
    )
    mix = mix / mix.sum()
    seqs: dict = {}

    def _seq(source: str) -> int:
        seqs[source] = seqs.get(source, -1) + 1
        return seqs[source]

    def _p_state(rows: np.ndarray) -> dict:
        return {n: a[rows] for n, a in p_cols.items()}

    def _r_state(rows: np.ndarray) -> dict:
        return {n: a[rows] for n, a in r_cols.items()}

    at_us = 0
    empty = np.zeros(0, np.int32)
    with tfmt.TraceWriter(path, meta=meta,
                          compresslevel=compresslevel) as w:
        w.write_snapshot(f"synth-ev-{seed}", fp, req)
        for i in range(1, events + 1):
            at_us += int(1e6 / rate_hz * (0.5 + rng.random()))
            if mass_every and i % mass_every == 0:
                live = np.flatnonzero(p_cols["valid"])
                n_down = max(int(live.size * mass_frac), 1)
                rows = np.sort(
                    rng.choice(live, min(n_down, live.size), replace=False)
                ).astype(np.int32)
                valid = p_cols["valid"].copy()
                valid[rows] = False
                p_cols["valid"] = valid
                src = f"m{i}"
                ev_meta = {
                    "kind": "mass", "source": src, "seq": _seq(src),
                    "at_us": at_us, "rows": int(rows.size),
                }
                w.write_delta_cols(
                    i, rows, _p_state(rows), empty, None,
                    events=[ev_meta],
                )
                continue
            kind = kinds[int(rng.choice(4, p=mix))]
            live = np.flatnonzero(p_cols["valid"])
            dark = np.flatnonzero(~p_cols["valid"])
            # degrade gracefully when a kind has no eligible rows
            if kind == "join" and dark.size == 0:
                kind = "heartbeat"
            if kind in ("heartbeat", "leave") and live.size == 0:
                kind = "join" if dark.size else "task"
            if kind == "task":
                row = int(rng.integers(0, n_tasks))
                fresh = wire.canon_columns(
                    synth_requirements(rng, 1), tfmt.R_TRACE_DTYPES
                )
                for name in r_cols:
                    col = r_cols[name].copy()
                    col[row] = fresh[name][0]
                    r_cols[name] = col
                rows = np.asarray([row], np.int32)
                src = f"t{row}"
                w.write_delta_cols(
                    i, empty, None, rows, _r_state(rows),
                    events=[{
                        "kind": kind, "source": src, "seq": _seq(src),
                        "at_us": at_us, "rows": 1,
                    }],
                )
                continue
            if kind == "heartbeat":
                row = int(rng.choice(live))
                price = p_cols["price"].copy()
                load = p_cols["load"].copy()
                price[row] = np.float32(rng.uniform(0.5, 4.0))
                load[row] = np.float32(rng.uniform(0, 1))
                p_cols["price"], p_cols["load"] = price, load
            elif kind == "join":
                row = int(rng.choice(dark))
                fresh = wire.canon_columns(
                    synth_providers(rng, 1), tfmt.P_TRACE_DTYPES
                )
                for name in p_cols:
                    col = p_cols[name].copy()
                    col[row] = fresh[name][0]
                    p_cols[name] = col
            else:  # leave
                row = int(rng.choice(live))
                valid = p_cols["valid"].copy()
                valid[row] = False
                p_cols["valid"] = valid
            rows = np.asarray([row], np.int32)
            src = f"p{row}"
            w.write_delta_cols(
                i, rows, _p_state(rows), empty, None,
                events=[{
                    "kind": kind, "source": src, "seq": _seq(src),
                    "at_us": at_us, "rows": 1,
                }],
            )
    return path
