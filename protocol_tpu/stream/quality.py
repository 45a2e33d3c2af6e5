"""Incremental certified optimality gap for the streamed plan — the
"certified bounded divergence" half of the stream engine.

The quality plane's :func:`protocol_tpu.obs.quality.duality_gap` is an
O(T*K) scan; per event that alone would burn the sub-tick budget. This
tracker maintains the SAME certificate incrementally: rebase exactly at
every reconcile, then per event recompute only the rows the event
touched and keep every other row's stale contribution — which is still
a sound UPPER bound, by two monotonicity arguments:

  * **Untouched rows' slack can only shrink.** Between reconciles the
    auction's prices are monotone non-decreasing, and a price move on a
    provider comes with a seat move on it (single-seat providers), so
    an untouched row has the same seat at the same price — its
    ``seat_adj`` is exact — while its ``best = min_k(c_k + price_k)``
    can only have RISEN since the stale value was computed. Stale
    ``slack = seat_adj - best_stale >= slack_true``.
  * **The idle-price addend is a superset.** The exact certificate sums
    prices over *reachable* idle providers; the tracker sums over ALL
    idle positive-price providers (an O(P) vector op — maintaining the
    reachable set incrementally would need pre-repair row snapshots).
    A superset of nonnegative terms only loosens the bound, and any
    nonnegative dual point certifies.

So ``tracker gap >= duality_gap >= plan_cost - OPT`` at every event:
the ceiling the CI gate holds on the tracker is a certified bound on
how far the streamed plan's cost can sit above the optimum — and since
the batch shadow plan's cost is itself >= OPT, it also bounds
``cost(streamed) - cost(batch)``: the certified divergence bound.

The price cap (``2*cmax + 10``, the engine's give-up magnitude) is
frozen at rebase: capping with ANY fixed value yields a valid dual
point, and a frozen cap preserves the monotone-capped-price argument
above. Sinkhorn streams re-derive referee prices per solve (not
monotone), so the stream engine runs the exact scan there instead.

Determinism contract: pure functions of (candidates, plan, duals) — no
clocks, no randomness.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from protocol_tpu.obs.quality import _INFEASIBLE, _waiting_excess, queue_rows


class GapTracker:
    """Incrementally-maintained certified duality-gap upper bound."""

    def __init__(self):
        self._cap = 0.0
        self._best: Optional[np.ndarray] = None  # f64 [T]
        self._seat_adj: Optional[np.ndarray] = None  # f64 [T]
        self._seat_c: Optional[np.ndarray] = None  # f64 [T], 0 unassigned
        self._slack: Optional[np.ndarray] = None  # f64 [T]
        self._p4t: Optional[np.ndarray] = None  # i32 [T] copy
        self._price: Optional[np.ndarray] = None  # f64 [P] capped copy
        self._waiting: Optional[np.ndarray] = None  # queue rows, or none

    @property
    def primed(self) -> bool:
        return self._slack is not None

    def _row_terms(
        self,
        cand_p: np.ndarray,
        cand_c: np.ndarray,
        p4t: np.ndarray,
        price_c: np.ndarray,
        rows: np.ndarray,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(best, seat_adj, seat_c) for the given rows at the given
        capped prices — the exact per-row certificate terms. A row
        whose seat is missing from its candidate list contributes zero
        (same exclusion rule as ``duality_gap``; the arena's seat guard
        makes that unreachable in practice)."""
        cp = cand_p[rows]
        cc = cand_c[rows].astype(np.float64)
        feas = (cp >= 0) & (cc < _INFEASIBLE * 0.5)
        adj = np.where(feas, cc + price_c[np.maximum(cp, 0)], np.inf)
        best = adj.min(axis=1)
        seat = p4t[rows]
        seat_adj = np.zeros(rows.size, np.float64)
        seat_c = np.zeros(rows.size, np.float64)
        assigned = seat >= 0
        if assigned.any():
            m = (cp == seat[:, None]) & feas
            has = m.any(axis=1) & assigned
            j = m.argmax(axis=1)
            arows = np.flatnonzero(has)
            seat_c[arows] = cc[arows, j[arows]]
            seat_adj[arows] = seat_c[arows] + price_c[seat[arows]]
        return best, seat_adj, seat_c

    def rebase(
        self,
        cand_p: np.ndarray,
        cand_c: np.ndarray,
        p4t: np.ndarray,
        price: np.ndarray,
    ) -> dict:
        """Exact full recompute (reconcile / prime time): freezes the
        price cap and rebuilds every per-row term."""
        cand_p = np.asarray(cand_p)
        cand_c = np.asarray(cand_c)
        p4t = np.asarray(p4t, np.int32)
        T = p4t.shape[0]
        feas = (cand_p >= 0) & (cand_c < _INFEASIBLE * 0.5)
        cmax = float(cand_c[feas].max()) if feas.any() else 0.0
        self._cap = 2.0 * cmax + 10.0
        self._price = np.minimum(
            np.asarray(price, np.float64), self._cap
        )
        all_rows = np.arange(T)
        self._best, self._seat_adj, self._seat_c = self._row_terms(
            cand_p, cand_c, p4t, self._price, all_rows
        )
        self._slack = np.maximum(self._seat_adj - self._best, 0.0)
        # unassigned rows (or seat-missing rows) carry no slack: the
        # certificate covers exactly the assigned task set
        self._slack[self._seat_adj == 0.0] = 0.0
        self._p4t = p4t.copy()
        self._waiting = queue_rows(
            cand_p, cand_c, p4t, self._price.shape[0]
        )[0]
        return self._report()

    def update(
        self,
        cand_p: np.ndarray,
        cand_c: np.ndarray,
        p4t: np.ndarray,
        price: np.ndarray,
        repair_mask: Optional[np.ndarray],
    ) -> dict:
        """One event's incremental refresh. ``repair_mask`` [T] flags
        rows whose candidate content moved (the arena's ``repair``
        output); seat/price-moved rows are derived here from the plan
        and price deltas."""
        if not self.primed:
            return self.rebase(cand_p, cand_c, p4t, price)
        p4t = np.asarray(p4t, np.int32)
        price_c = np.minimum(np.asarray(price, np.float64), self._cap)
        touched = (
            np.asarray(repair_mask, bool).copy()
            if repair_mask is not None
            else np.zeros(p4t.shape[0], bool)
        )
        touched |= p4t != self._p4t
        # rows whose SEAT's price moved: derived from the price delta
        # (O(T) gather + compare) rather than argued from auction
        # internals — exactness here is what keeps untouched rows'
        # seat_adj exact
        seated = p4t >= 0
        if seated.any():
            moved = price_c != self._price
            touched |= seated & moved[np.maximum(p4t, 0)]
        rows = np.flatnonzero(touched)
        if rows.size:
            best, seat_adj, seat_c = self._row_terms(
                cand_p, cand_c, p4t, price_c, rows
            )
            self._best[rows] = best
            self._seat_adj[rows] = seat_adj
            self._seat_c[rows] = seat_c
            slack = np.maximum(seat_adj - best, 0.0)
            slack[seat_adj == 0.0] = 0.0
            self._slack[rows] = slack
        self._p4t = p4t.copy()
        self._price = price_c
        self._waiting = queue_rows(cand_p, cand_c, p4t, price_c.shape[0])[0]
        return self._report()

    def _report(self) -> dict:
        p4t = self._p4t
        used = np.zeros(self._price.shape[0], bool)
        seated = p4t[p4t >= 0]
        used[seated] = True
        idle_price = float(self._price[~used & (self._price > 0)].sum())
        cs_slack = float(self._slack.sum())
        plan_cost = float(self._seat_c.sum())
        # a plan with a queue (obs.quality.queue_rows): the waiting
        # tasks' addend, over every seat's exact value and the waiting
        # rows' bests (stale ones are from lower prices, so no smaller)
        waiting_excess = 0.0
        if self._waiting.size:
            seats = self._seat_adj[self._seat_adj != 0.0]
            waiting_excess = _waiting_excess(
                -seats, -self._best[self._waiting]
            )
        gap_total = cs_slack + idle_price + waiting_excess
        n = int((p4t >= 0).sum())
        return {
            "plan_cost": round(plan_cost, 4),
            "dual_bound": round(plan_cost - gap_total, 4),
            "gap_total": round(gap_total, 6),
            "gap_per_task": round(gap_total / max(n, 1), 6),
            "cs_slack": round(cs_slack, 6),
            "idle_price": round(idle_price, 6),
            "waiting_excess": round(waiting_excess, 6),
            "incremental": True,
        }
