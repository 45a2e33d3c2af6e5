"""Trace-native flame/phase report: ``python -m protocol_tpu.obs report``.

Renders, offline, from any recorded or replayed flight-recorder trace:

  * a **per-tick phase table** — wall / decode / candidate-gen / engine
    walls plus the native engine's INTERNAL phases (bidding rounds, bids,
    evictions, Sinkhorn sweeps, repair passes) that ride OUTCOME-frame
    metrics as ``eng_*`` scalars,
  * a **flame breakdown** — span trees aggregated across ticks by call
    path (each OUTCOME frame's ``spans`` list), with total/self time and
    percent-of-total bars,
  * a **percentile table** — true p50/p90/p99/p999 tick latency from the
    obs histograms, split cold vs warm.

This is how "where did the 220 s go" gets answered for any recorded
engine x transport combination without re-running anything.
"""

from __future__ import annotations

from typing import Optional

from protocol_tpu.obs.metrics import LatencyHistogram
from protocol_tpu.obs.quality import aggregate_quality

# per-tick table columns pulled from OUTCOME metrics when present:
# (key, header, is_ms)
_PHASE_COLS = (
    ("wall_ms", "wall", True),
    ("decode_ms", "decode", True),
    ("gen_ms", "gen", True),
    ("solve_ms", "solve", True),
    ("eng_bid_ms", "bid", True),
    ("eng_repair_ms", "repair", True),
    ("eng_merge_ms", "merge", True),
    ("eng_sink_f_ms", "sink_f", True),
    ("eng_sink_g_ms", "sink_g", True),
    ("eng_rounds", "rounds", False),
    ("eng_bids", "bids", False),
    ("eng_evicted", "evict", False),
    ("eng_sink_iters", "sweeps", False),
    # incremental candidate maintenance (the repair kernel's phase wall
    # and row accounting; cold ticks report the cold-pass counter)
    ("eng_cand_repair_merge_ms", "cand_rep", True),
    ("eng_cand_repair_rows", "rep_rows", False),
    ("eng_cand_repair_rescans", "rescans", False),
    # the jax repair's traffic with the device: times a stage waited
    # for it, and the bytes those reads copied to the host
    ("rep_syncs", "rep_sync", False),
    ("rep_readback_bytes", "rep_rb_B", False),
    ("cand_cold_passes", "cold_gen", False),
    ("changed_rows", "dirty", False),
    ("delta_rows", "delta", False),
)


def _fmt(v, is_ms: bool) -> str:
    if v is None:
        return "-"
    if is_ms:
        return f"{float(v):.1f}"
    return str(int(v))


def _tick_wall(m: dict) -> Optional[float]:
    """Best-available end-to-end wall for a tick's outcome metrics."""
    for key in ("wall_ms",):
        if m.get(key) is not None:
            return float(m[key])
    if m.get("decode_ms") is not None or m.get("solve_ms") is not None:
        return float(m.get("decode_ms") or 0.0) + float(
            m.get("solve_ms") or 0.0
        )
    return None


def tick_table(outcomes) -> list[str]:
    """The per-tick phase breakdown (native internal phases included)."""
    cols = [
        c for c in _PHASE_COLS
        if any(o.metrics.get(c[0]) is not None for o in outcomes)
    ]
    lines = []
    header = "tick  " + "  ".join(f"{h:>8}" for _, h, _ in cols) + "  assigned"
    lines.append(header)
    lines.append("-" * len(header))
    for o in outcomes:
        m = o.metrics
        row = f"{o.tick:>4}  " + "  ".join(
            f"{_fmt(m.get(k), is_ms):>8}" for k, _, is_ms in cols
        )
        lines.append(f"{row}  {o.num_assigned:>8}")
    return lines


def _span_paths(spans: list[dict]) -> dict[tuple, tuple[float, int]]:
    """Aggregate one tick's spans into {path: (total_us, count)} where
    path is the name chain from the root."""
    by_id = {s["span"]: s for s in spans}

    def path_of(s) -> tuple:
        chain = [s["name"]]
        seen = {s["span"]}
        cur = s
        while cur.get("parent") is not None:
            parent = by_id.get(cur["parent"])
            if parent is None or parent["span"] in seen:
                break
            chain.append(parent["name"])
            seen.add(parent["span"])
            cur = parent
        return tuple(reversed(chain))

    out: dict[tuple, list] = {}
    for s in spans:
        p = path_of(s)
        cur = out.setdefault(p, [0.0, 0])
        cur[0] += float(s.get("us", 0.0))
        cur[1] += 1
    return {k: (v[0], v[1]) for k, v in out.items()}


def flame(outcomes, width: int = 32) -> list[str]:
    """Aggregate span trees across every tick into one text flame."""
    totals: dict[tuple, list] = {}
    for o in outcomes:
        for path, (us, n) in _span_paths(o.metrics.get("spans") or []).items():
            cur = totals.setdefault(path, [0.0, 0])
            cur[0] += us
            cur[1] += n
    if not totals:
        return ["(no spans recorded in this trace)"]
    roots_us = sum(us for p, (us, n) in totals.items() if len(p) == 1)
    roots_us = roots_us or max(us for us, _ in totals.values())
    lines = [
        f"{'span path':<44} {'total ms':>10} {'calls':>6}  % of root"
    ]
    lines.append("-" * len(lines[0]))
    for path in sorted(totals, key=lambda p: (p[:1], -totals[p][0])):
        us, n = totals[path]
        frac = us / roots_us if roots_us else 0.0
        bar = "#" * max(1, int(frac * width)) if us else ""
        label = "  " * (len(path) - 1) + path[-1]
        lines.append(
            f"{label:<44} {us / 1e3:>10.1f} {n:>6}  {frac:>5.1%} {bar}"
        )
    return lines


# quality-plane columns pulled from OUTCOME metrics: (key, header, fmt)
_QUALITY_COLS = (
    ("gap_per_task", "gap/task", "f6"),
    ("churn_ratio", "churn", "f4"),
    ("starve_max", "starve", "i"),
    ("outcome_no_candidates", "no_cand", "i"),
    ("outcome_outbid", "outbid", "i"),
    ("outcome_retired", "retired", "i"),
    ("outcome_unexplained", "unexpl", "i"),
)


def _fmt_q(v, fmt: str) -> str:
    if v is None:
        return "-"
    if fmt == "f6":
        return f"{float(v):.6f}"
    if fmt == "f4":
        return f"{float(v):.4f}"
    return str(int(v))


def quality_summary(outcomes, events=None) -> Optional[dict]:
    """Aggregate the quality scalars riding OUTCOME frames via the
    shared canonical roll-up (None when the trace predates the quality
    plane), plus the trace's SLO alert-event count."""
    out = aggregate_quality([o.metrics for o in outcomes])
    if out is None:
        return None
    alerts = [
        e for frame in (events or []) for e in frame.get("events", [])
        if e.get("kind") == "slo"
    ]
    if alerts:
        out["slo_alerts"] = len(alerts)
    return out


def quality_table(outcomes, events=None) -> list[str]:
    """The decision-quality section: per-tick certified gap / churn /
    starvation / unassigned-cause table plus the roll-up line (and any
    SLO alert events the trace carries)."""
    summary = quality_summary(outcomes, events)
    if summary is None:
        return ["(no quality scalars in this trace — re-record with the "
                "obs plane on)"]
    cols = [
        c for c in _QUALITY_COLS
        if any(o.metrics.get(c[0]) is not None for o in outcomes)
    ]
    lines = []
    header = "tick  " + "  ".join(f"{h:>9}" for _, h, _ in cols)
    lines.append(header)
    lines.append("-" * len(header))
    for o in outcomes:
        m = o.metrics
        if m.get("gap_per_task") is None:
            continue
        lines.append(
            f"{o.tick:>4}  " + "  ".join(
                f"{_fmt_q(m.get(k), fmt):>9}" for k, _, fmt in cols
            )
        )
    lines.append("")
    causes = summary["causes"]
    lines.append(
        f"certified gap/task mean {summary['gap_per_task_mean']:.6f} "
        f"max {summary['gap_per_task_max']:.6f}"
        + (
            f" | churn mean {summary['churn_ratio_mean']:.4f} "
            f"max {summary['churn_ratio_max']:.4f}"
            if "churn_ratio_mean" in summary else ""
        )
        + f" | starvation max {summary['starve_max']} ticks"
    )
    lines.append(
        "unassigned causes: "
        f"no_candidates={causes['no_candidates']} "
        f"outbid={causes['outbid']} retired={causes['retired']} "
        f"unexplained={summary['unexplained_unassigned']}"
        f" (assigned task-ticks: {causes['assigned']})"
    )
    if summary.get("slo_alerts"):
        lines.append(f"SLO alert events in trace: {summary['slo_alerts']}")
        for frame in events or []:
            for e in frame.get("events", []):
                if e.get("kind") != "slo":
                    continue
                lines.append(
                    f"  tick {e.get('tick'):>4} {e.get('state'):>5} "
                    f"{e.get('slo')} session={e.get('session')} "
                    f"value={e.get('value')} threshold={e.get('threshold')} "
                    f"burn={e.get('burn_short')}/{e.get('burn_long')}"
                )
    return lines


def percentile_table(outcomes) -> list[str]:
    """Cold vs warm tick-latency distribution (obs histograms)."""
    cold = LatencyHistogram()
    warm = LatencyHistogram()
    for o in outcomes:
        w = _tick_wall(o.metrics)
        if w is None:
            continue
        (cold if o.metrics.get("cold") or o.tick == 0 else warm).observe_ms(w)
    lines = [
        f"{'ticks':<6} {'count':>6} {'mean':>9} {'p50':>9} {'p90':>9} "
        f"{'p99':>9} {'p999':>9} {'max':>9}   (ms)"
    ]
    lines.append("-" * len(lines[0]))
    for name, h in (("cold", cold), ("warm", warm)):
        s = h.snapshot_ms()
        if not s.get("count"):
            lines.append(f"{name:<6} {0:>6}")
            continue
        lines.append(
            f"{name:<6} {s['count']:>6} {s['mean_ms']:>9.2f} "
            f"{s['p50_ms']:>9.2f} {s['p90_ms']:>9.2f} {s['p99_ms']:>9.2f} "
            f"{s['p999_ms']:>9.2f} {s['max_ms']:>9.2f}"
        )
    return lines


def report_dict(trace_path: str) -> dict:
    """Structured form of the report (the --json output)."""
    from protocol_tpu.trace import format as tfmt

    t = tfmt.read_trace(trace_path)
    ticks = []
    cold = LatencyHistogram()
    warm = LatencyHistogram()
    for o in t.outcomes:
        m = {
            k: v for k, v in o.metrics.items() if k != "spans"
        }
        ticks.append({
            "tick": o.tick, "num_assigned": o.num_assigned, **m,
        })
        w = _tick_wall(o.metrics)
        if w is not None:
            (cold if o.metrics.get("cold") or o.tick == 0 else warm
             ).observe_ms(w)
    out = {
        "trace": trace_path,
        "truncated": t.truncated,
        "ticks": ticks,
        "cold": cold.snapshot_ms(),
        "warm": warm.snapshot_ms(),
    }
    quality = quality_summary(t.outcomes, t.events)
    if quality is not None:
        out["quality"] = quality
    if t.snapshot is not None:
        out.update(
            providers=t.snapshot.n_providers, tasks=t.snapshot.n_tasks,
            kernel=t.snapshot.kernel,
        )
    return out


def render(trace_path: str) -> str:
    """The human-facing text report."""
    from protocol_tpu.trace import format as tfmt

    t = tfmt.read_trace(trace_path)
    lines: list[str] = []
    head = f"obs report: {trace_path}"
    if t.snapshot is not None:
        head += (
            f"  [{t.snapshot.n_providers}x{t.snapshot.n_tasks} "
            f"kernel={t.snapshot.kernel} ticks={t.ticks}]"
        )
    if t.truncated:
        head += "  (TRUNCATED TAIL)"
    lines.append(head)
    lines.append("=" * len(head))
    if not t.outcomes:
        lines.append("no OUTCOME frames — an input-only trace; replay it "
                     "(python -m protocol_tpu.trace record) to profile")
        return "\n".join(lines)
    lines.append("")
    lines.append("per-tick phase breakdown")
    lines.extend(tick_table(t.outcomes))
    lines.append("")
    lines.append("tick latency distribution")
    lines.extend(percentile_table(t.outcomes))
    lines.append("")
    lines.append("quality (decision plane)")
    lines.extend(quality_table(t.outcomes, t.events))
    lines.append("")
    lines.append("flame (span totals across ticks)")
    lines.extend(flame(t.outcomes))
    return "\n".join(lines)
