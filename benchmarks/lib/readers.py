"""One generic reader for every per-layer metric.

A metric is a JSON file under ``metrics/``; its ``read`` object says
where the number comes from and how it is reduced. Sources:

``ack``     one record per acknowledged request of the window: the
            client's ``latency_ms`` (from due), ``wall_ms`` (from sent)
            and ``late_ms`` merged with the arena's
            ``last_stats`` of that tick. ``key`` names a field,
            ``minus`` fields to subtract per record, ``reduce`` is
            ``mean`` or ``p<q>``.
``seam``    the Health RPC's counters, window's end minus its start.
            ``key`` names one; ``per`` = ``ack`` divides by the acks.
``trace``   the profiler slice reduced by ``trace_reduce``: ``kind`` is
            ``idle_pct`` or ``roofline`` (with ``modules`` patterns,
            ``bytes`` coefficients and the ``bound`` of the peaks table).

A reader that finds nothing to read returns None and the metric is left
out of the line; it never makes up a 0.
"""

from __future__ import annotations

import json
import os
import re

from . import stats


def load_dir(directory: str) -> dict:
    """Every ``*.json`` of a directory, keyed by its ``name``."""
    out = {}
    for fname in sorted(os.listdir(directory)):
        if fname.endswith(".json"):
            with open(os.path.join(directory, fname)) as fh:
                spec = json.load(fh)
            if spec.get("name") != fname[: -len(".json")]:
                raise ValueError(f"{fname}: name does not match the file")
            out[spec["name"]] = spec
    return out


def _reduce(values, how: str):
    if not values:
        return None
    if how == "mean":
        return sum(values) / len(values)
    m = re.fullmatch(r"p(\d+(?:\.\d+)?)", how)
    if not m:
        raise ValueError(f"unknown reduction {how!r}")
    return stats.percentile(values, float(m.group(1)))


def read_ack(read: dict, records: list):
    key, minus = read["key"], read.get("minus", [])
    values = [
        float(rec[key]) - sum(float(rec[n]) for n in minus)
        for rec in records
        if all(isinstance(rec.get(n), (int, float)) for n in (key, *minus))
    ]
    return _reduce(values, read["reduce"])


def read_seam(read: dict, before: dict, after: dict, acks: int):
    if read["key"] not in after:
        return None
    delta = after[read["key"]] - before.get(read["key"], 0.0)
    if read.get("per") == "ack":
        return delta / acks if acks else None
    return delta


def peaks_for(table: dict, device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind that is not in the table is
    an error, never a default."""
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}")
    return table["devices"][device_kind]


def roofline_bytes(coeff: dict, shape: dict) -> float:
    """Least bytes one ack's solve has to move, from the cell's shapes
    alone: ``per_task_candidate`` bytes for each of T x K_eff candidates
    (id, cost, gathered price) and ``per_provider`` bytes per provider
    (price and owner vectors)."""
    return (
        coeff["per_task_candidate"] * shape["n_tasks"] * shape["k_eff"]
        + coeff["per_provider"] * shape["n_providers"]
    )


def read_trace(read: dict, reduced, shape: dict, peaks: dict):
    """``reduced`` is ``trace_reduce.reduce_trace``'s result with the
    harness's ``acks_in_slice`` added, or None when the run took no
    trace."""
    if reduced is None or not reduced["busy_s"] > 0:
        return None
    if read["kind"] == "idle_pct":
        return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
    if read["kind"] == "roofline":
        pats = [re.compile(p) for p in read["modules"]]
        secs = sum(
            s for name, s in reduced["module_s"].items()
            if any(p.search(name) for p in pats)
        )
        if not secs > 0 or not reduced["acks_in_slice"] > 0:
            return None
        need = roofline_bytes(read["bytes"], shape) * reduced["acks_in_slice"]
        return 100.0 * (need / peaks[read["bound"]]) / secs
    raise ValueError(f"unknown trace reading {read['kind']!r}")


def read_metric(spec: dict, ctx: dict):
    """The value of one metric from a run's context, or None."""
    read = spec["read"]
    if read["from"] == "ack":
        return read_ack(read, ctx["records"])
    if read["from"] == "seam":
        return read_seam(
            read, ctx["seam_before"], ctx["seam_after"], len(ctx["records"])
        )
    if read["from"] == "trace":
        return read_trace(read, ctx.get("trace"), ctx["shape"], ctx["peaks"])
    raise ValueError(f"{spec['name']}: unknown source {read['from']!r}")
