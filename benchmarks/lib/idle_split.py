"""The device's idle time split by the program span the host was in.

``trace_reduce.reduce_trace`` finds the idle stretches of the busiest
device in the slice (``gaps(busiest, lo, hi)``, what
``device_idle_pct`` reads) and labels the ten longest by the harness's
own request annotations. The program's spans lie on the same trace: its
tracer holds a ``jax.profiler.TraceAnnotation`` open for every ``with``
span, so they are events of ``/host:CPU``, one line a thread, under the
device's clock. :func:`idle_by_span` puts every idle nanosecond of those
stretches down to the innermost program span that the request's thread
had open, and :func:`read_idle_ms_per_ack` sums that per ack over a
metric's spans.

Nothing calls this yet: wiring it in is an edit of
``trace_reduce.reduce_trace`` (``idle_by_span`` among its keys, from the
same stretches) and of ``readers.read_trace`` (the kind
``idle_ms_per_ack``), which a ``benchmark`` PR makes; ``idle_split.json``
holds the two layout keys and the six metric files it would add.
"""

from __future__ import annotations

import math
import re

OUTSIDE = "(outside)"


def _flatten(events: list, root_pat) -> list:
    """One thread's program spans, ``(start, end, name)`` sorted by
    start and, at one start, the longer first, as the pieces in which
    each is the innermost open: ``(start, end, name, depth)``, in order
    and not overlapping, and only where a root span is open among them.
    A thread's annotations nest; a child that the clock's rounding lets
    outlast its parent is cut at the parent's end."""
    out: list = []
    stack: list = []     # [end, name, is_root], outermost first
    roots = 0
    at = -math.inf

    def close_until(t: float) -> None:
        nonlocal at, roots
        while stack:
            end, name, is_root = stack[-1]
            stop = min(end, t)
            if stop > at and roots:
                out.append((at, stop, name, len(stack) - 1))
            at = max(at, stop)
            if end > t:
                break
            stack.pop()
            roots -= is_root
        at = max(at, t)

    for start, end, name in events:
        close_until(start)
        is_root = bool(root_pat.search(name))
        stack.append([min(end, stack[-1][0]) if stack else end, name,
                      is_root])
        roots += is_root
    close_until(math.inf)
    return out


def timeline(profile, layout: dict) -> list:
    """The innermost program span at every instant where a request was
    being served, over every host line that holds a root span:
    ``(start, end, name)`` in order, not overlapping. Where two such
    lines hold a root at once the deeper span wins, and at equal depth
    the line that comes first in the trace."""
    host_pat = re.compile(layout["host_plane"])
    span_pat = re.compile(layout["program_span"])
    root_pat = re.compile(layout["program_root"])
    lines = []
    for plane in profile.planes:
        if not host_pat.search(plane.name):
            continue
        for line in plane.lines:
            events = sorted(
                (
                    (float(e.start_ns),
                     float(e.start_ns) + float(e.duration_ns), e.name)
                    for e in line.events
                    if float(e.duration_ns) > 0 and span_pat.search(e.name)
                ),
                key=lambda ev: (ev[0], -ev[1]),
            )
            pieces = _flatten(events, root_pat)
            if pieces:
                lines.append(pieces)
    cuts = sorted({t for pieces in lines for p in pieces for t in p[:2]})
    at = [0] * len(lines)
    out: list = []
    for lo, hi in zip(cuts, cuts[1:]):
        best = None
        for i, pieces in enumerate(lines):
            while at[i] < len(pieces) and pieces[at[i]][1] <= lo:
                at[i] += 1
            if at[i] < len(pieces) and pieces[at[i]][0] <= lo:
                _, _, name, depth = pieces[at[i]]
                if best is None or depth > best[1]:
                    best = (name, depth)
        if best is not None:
            out.append((lo, hi, best[0]))
    return out


def idle_by_span(profile, layout: dict, stretches: list) -> dict:
    """Seconds of the idle ``stretches`` (``(start, end)`` ns, in order,
    not overlapping: ``trace_reduce.gaps``) under each program span.

    A stretch is split piecewise, never by its midpoint: each piece goes
    to the innermost ``layout["program_span"]`` event open on a
    ``layout["host_plane"]`` line (a thread) where a
    ``layout["program_root"]`` event is open. With more than one such
    line at once, the deepest span takes the piece, and at equal depth
    the line that comes first in the trace. A piece where no root is
    open goes to ``"(outside)"``: the transport, the thread hand-offs,
    the proto on both ends and the client's own loop. A span on a line
    with no root open (the checkpoint's worker) takes nothing. The
    values add up to the stretches' length."""
    spans = timeline(profile, layout)
    out: dict = {}
    j = 0
    for lo, hi in stretches:
        covered = 0.0
        while j < len(spans) and spans[j][1] <= lo:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < hi:
            s, e, name = spans[k]
            piece = min(e, hi) - max(s, lo)
            if piece > 0:
                out[name] = out.get(name, 0.0) + piece * 1e-9
                covered += piece
            k += 1
        rest = (hi - lo) - covered
        if rest > 0:
            out[OUTSIDE] = out.get(OUTSIDE, 0.0) + rest * 1e-9
    return out


def read_idle_ms_per_ack(read: dict, reduced):
    """``read["spans"]`` (patterns, each matched whole against a key of
    ``idle_by_span``) summed, in ms per ack of the slice; None where the
    run took no trace or the reduction gave no ``idle_by_span``."""
    if reduced is None or "idle_by_span" not in reduced:
        return None
    if not reduced.get("acks_in_slice", 0) > 0:
        return None
    pats = [re.compile(p) for p in read["spans"]]
    secs = sum(
        s for name, s in reduced["idle_by_span"].items()
        if any(p.fullmatch(name) for p in pats)
    )
    return secs * 1e3 / reduced["acks_in_slice"]
