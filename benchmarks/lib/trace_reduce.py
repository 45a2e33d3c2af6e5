"""From a profiler trace to device busy time, time per XLA module and
idle gaps.

Works on anything shaped like ``jax.profiler.ProfileData``: planes with
``name`` and ``lines``; lines with ``name`` and ``events``; events with
``name``, ``start_ns`` and ``duration_ns``. Which planes are devices,
which line holds the modules and which the ops is data
(``trace_layout.json``), read off a v5e trace by hand.
"""

from __future__ import annotations

import re


def _intervals(line):
    return sorted(
        (float(e.start_ns), float(e.start_ns) + float(e.duration_ns))
        for e in line.events if float(e.duration_ns) > 0
    )


def union(intervals: list) -> list:
    """Merge sorted ``(start, end)`` intervals that touch or overlap."""
    out: list = []
    for s, e in intervals:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(merged: list, lo: float, hi: float) -> list:
    """The idle ``(start, end)`` stretches of ``[lo, hi]`` that the
    merged busy intervals leave."""
    out, at = [], lo
    for s, e in merged:
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def _find_lines(plane, pattern: str):
    pat = re.compile(pattern)
    return [ln for ln in plane.lines if pat.search(ln.name)]


def reduce_trace(profile, layout: dict, n_devices: int) -> dict:
    """``busy_s`` and ``window_s`` (averaged over the device planes),
    ``module_s`` (device seconds per XLA module name, summed over
    devices), ``device_ops`` (the ten names with most device time),
    ``idle_gaps`` (the ten longest idle stretches of the busiest
    device, labelled by how many of the harness's request annotations
    were open; the profiler records only annotations that began inside
    the slice). The slice runs from the first to the last event that
    the profiler recorded on any plane's listed lines."""
    dev_pat = re.compile(layout["device_plane"])
    devices = [p for p in profile.planes if dev_pat.search(p.name)]
    if not devices:
        raise ValueError("the trace holds no device plane")
    requests = []
    req_pat = re.compile(layout["request_event"])
    host_pat = re.compile(layout["host_plane"])
    for plane in profile.planes:
        if host_pat.search(plane.name):
            for line in plane.lines:
                for e in line.events:
                    if req_pat.search(e.name):
                        requests.append((
                            float(e.start_ns),
                            float(e.start_ns) + float(e.duration_ns),
                        ))
    per_device = []
    module_s: dict = {}
    for plane in devices:
        ops = []
        for line in _find_lines(plane, layout["busy_line"]):
            ops.extend(_intervals(line))
        merged = union(sorted(ops))
        for line in _find_lines(plane, layout["module_line"]):
            for e in line.events:
                name = re.sub(layout["module_strip"], "", e.name)
                module_s[name] = (
                    module_s.get(name, 0.0) + float(e.duration_ns) * 1e-9
                )
        per_device.append(merged)
    spans = [iv for m in per_device for iv in m] + requests
    if not spans:
        raise ValueError("the trace holds no device operation")
    lo = min(s for s, _ in spans)
    hi = max(e for _, e in spans)
    busy = [sum(e - s for s, e in m) * 1e-9 for m in per_device]
    busiest = per_device[busy.index(max(busy))]
    idle = sorted(
        gaps(busiest, lo, hi), key=lambda g: g[0] - g[1]
    )[:10]

    def label(gap):
        mid = (gap[0] + gap[1]) / 2
        n = sum(1 for s, e in requests if s <= mid <= e)
        return f"requests_in_flight_{n}"

    top = sorted(module_s.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": sum(busy) / max(n_devices, 1),
        "window_s": (hi - lo) * 1e-9,
        "module_s": module_s,
        "device_ops": [[n, s] for n, s in top],
        "idle_gaps": [[label(g), (g[1] - g[0]) * 1e-9] for g in idle],
    }
