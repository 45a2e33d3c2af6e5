"""The arithmetic of the end-to-end metrics: due times, latencies,
percentiles and the rate of one window."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between the
    order statistics, as ``numpy.percentile`` gives it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def due_time(open_s: float, period_s: float, index: int,
             prev_ack_s) -> float:
    """When a client's ``index``-th request of the window is due: the
    later of its period boundary and the client's previous ack (the
    window's opening for its first)."""
    boundary = open_s + period_s * index
    return boundary if prev_ack_s is None else max(boundary, prev_ack_s)


def window_metrics(acks: list, open_s: float) -> dict:
    """End-to-end numbers of one window. ``acks`` holds one record per
    request sent: ``due_s``, ``sent_s``, ``acked_s`` and ``ok``. The
    window opens with the first request due and closes with the last
    reply, so the rate is all acks over all of the time they took, and
    a stall anywhere moves the median and the rate alike."""
    good = [a for a in acks if a["ok"]]
    if not good:
        raise ValueError("no request of the window was acknowledged")
    close_s = max(a["acked_s"] for a in acks)
    lat = [(a["acked_s"] - a["due_s"]) * 1e3 for a in good]
    return {
        "ack_p50_ms": percentile(lat, 50),
        "acks_per_s": len(good) / (close_s - open_s),
        "window_s": close_s - open_s,
        "acks": len(good),
    }
