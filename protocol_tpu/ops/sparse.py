"""Sparse (top-K candidate) assignment: the 1M-scale architecture.

A dense [P, T] cost tensor at 1M x 1M is ~4 TB — unrepresentable. But the
matching only ever uses each task's few best compatible providers, so the
pipeline splits:

  candidates_topk   one streaming pass over the cost tensor in task tiles
                    (lax.scan; [P, tile] per step, never materializing
                    [P, T]) emitting each task's K cheapest compatible
                    providers -> cand_provider/cand_cost [T, K].
  assign_auction_sparse
                    Bertsekas auction restricted to the candidate graph:
                    per-round work is O(T*K) gathers + scatter-max winner
                    resolution over the price vector [P] — independent of
                    P*T. Deterministic ties (lowest provider / lowest task).

With K ~ 32-128 the restricted matching is near-always optimal for
marketplace-shaped costs (many similar providers), while per-iteration HBM
traffic drops from O(P*T) to O(T*K): the difference between 2 s and
milliseconds at 8k x 8k, and the only viable shape at 1M x 1M.

Replaces: the reference's O(tasks)-per-heartbeat greedy walk
(crates/orchestrator/src/scheduler/mod.rs:26-74), at the scale ladder of
BASELINE.md configs #3-#5.
"""

from __future__ import annotations

import contextlib
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from protocol_tpu.obs.spans import TRACER as _tracer
from protocol_tpu.ops.assign import AssignResult, _invert
from protocol_tpu.ops.cost import INFEASIBLE, CostWeights, cost_matrix, tie_jitter
from protocol_tpu.ops.encoding import EncodedProviders, EncodedRequirements

_NEG = -1e18


def _slice_requirements(r: EncodedRequirements, start: int, size: int) -> EncodedRequirements:
    """Static-size tile of the requirements pytree along the task axis."""
    return jax.tree.map(
        lambda leaf: lax.dynamic_slice_in_dim(leaf, start, size, axis=0), r
    )


def frontier_bids(cand_safe, value_base, price, f_idx, f_ok, num_options: int):
    """The auction's per-frontier bid computation, shared verbatim by the
    single-device kernel and the task-sharded mesh kernel — bit-identical
    math here is what the Jacobi parity guarantee between them rests on.

    Returns (p1 best provider, v1 best value, v2 runner-up value [floored]).
    """
    f_safe = jnp.where(f_ok, f_idx, 0)
    cp = cand_safe[f_safe]  # [B, K]
    value = value_base[f_safe] - price[cp]  # the only dynamic gather at scale
    k1 = jnp.argmax(value, axis=1).astype(jnp.int32)
    v1 = jnp.take_along_axis(value, k1[:, None], axis=1)[:, 0]
    v2 = jnp.max(
        jnp.where(jnp.arange(num_options)[None, :] == k1[:, None], _NEG, value),
        axis=1,
    )
    v2 = jnp.maximum(v2, jnp.float32(-1e8))  # single-option floor
    p1 = jnp.take_along_axis(cp, k1[:, None], axis=1)[:, 0]
    return p1, v1, v2


@partial(jax.jit, static_argnames=("k", "tile", "approx_recall"))
def candidates_topk(
    ep: EncodedProviders,
    er: EncodedRequirements,
    weights: CostWeights | None = None,
    k: int = 64,
    tile: int = 1024,
    provider_offset: jax.Array | None = None,
    task_offset: int | jax.Array = 0,
    approx_recall: float | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Each task's top-k cheapest compatible providers.

    Streams the cost tensor in [P, tile] blocks inside a lax.scan — peak
    memory O(P * tile), suitable for P up to ~1M with tile sized to fit.
    Returns (cand_provider i32 [T, k] with -1 padding, cand_cost f32 [T, k]).
    T must be divisible by tile (pad the requirements first).

    ``provider_offset`` [P] biases the SELECTION (e.g. -eps*u from Sinkhorn
    potentials: pick candidates by plan mass) while the returned costs stay
    the true costs, so downstream matchers optimize the real objective.

    ``task_offset`` shifts the task index used by the tie-jitter hash:
    callers that generate candidates in separate delta batches (the
    incremental CandidateCache) pass a persistent cursor so tasks from
    different batches stay decorrelated — identical jitter patterns would
    recreate the everyone-picks-the-same-k collapse the jitter prevents.

    ``approx_recall`` switches selection from exact ``lax.top_k`` (a
    sort-shaped reduction that dominates wall-clock at large P on TPU —
    measured 1.41 Gcells/s at P=131k, SCALING.md) to ``lax.approx_max_k``
    (XLA's TPU-native PartialReduce; expected severalfold faster, on-chip
    measurement pending) with the given per-row recall target. A missed
    candidate only perturbs WHICH near-tied provider a task may match —
    the same degeneracy the tie jitter above already randomizes — so
    matching quality is insensitive to recall ~0.95 for marketplace
    shapes. Deterministic for fixed inputs either way.
    """
    if weights is None:
        weights = CostWeights()
    T = er.cpu_cores.shape[0]
    if T % tile != 0:
        raise ValueError(f"T={T} not divisible by tile={tile}; pad requirements")
    n_tiles = T // tile
    k = min(k, int(ep.gpu_count.shape[0]))  # lax.top_k requires k <= P

    P = ep.gpu_count.shape[0]

    def step(carry, t0):
        provider, cost_k, _cost = _forward_tile_select(
            ep, er, weights, t0, tile, k,
            provider_offset, task_offset, approx_recall,
        )
        return carry, (provider, cost_k)

    _, (cand_p, cand_c) = lax.scan(
        step, None, jnp.arange(n_tiles, dtype=jnp.int32) * tile
    )
    return cand_p.reshape(T, k), cand_c.reshape(T, k)


def _forward_tile_select(
    ep, er, weights, t0, tile: int, k: int,
    provider_offset, task_offset, approx_recall,
):
    """One [P, tile] step of forward candidate selection, shared verbatim
    by the plain and bidirectional scans (``candidates_topk`` /
    ``candidates_topk_reverse``) — a selection-bias or jitter change must
    reach both or the cold bench/gRPC path silently diverges from the
    bidir path. Returns (provider [tile, k], true cost_k [tile, k], and
    the jittered [P, tile] cost block for the caller's reverse fold)."""
    P = ep.gpu_count.shape[0]
    r_tile = _slice_requirements(er, t0, tile)
    cost, _mask = cost_matrix(ep, r_tile, weights)  # [P, tile]
    # Degeneracy breaker: marketplaces have many identically-priced
    # providers; without jitter every task's top-k is the SAME k
    # providers, capping the matching at k regardless of supply (see
    # ops/cost.py tie_jitter).
    jitter = tie_jitter(P, tile, task_offset=t0 + jnp.uint32(task_offset))
    cost = jnp.where(cost < INFEASIBLE * 0.5, cost + jitter, cost)
    if provider_offset is None:
        selection = cost
    else:
        selection = jnp.where(
            cost < INFEASIBLE * 0.5, cost + provider_offset[:, None], cost
        )
    if approx_recall is None:
        neg_sel, idx = lax.top_k(-selection.T, k)  # [tile, k] best first
    else:
        neg_sel, idx = lax.approx_max_k(
            -selection.T, k, recall_target=approx_recall
        )
    cost_k = jnp.take_along_axis(cost.T, idx, axis=1)  # true costs
    sel_k = -neg_sel
    provider = jnp.where(sel_k < INFEASIBLE * 0.5, idx.astype(jnp.int32), -1)
    return provider, cost_k, cost


@partial(
    jax.jit,
    static_argnames=("k", "tile", "reverse_r", "approx_recall", "with_pools"),
)
def candidates_topk_reverse(
    ep: EncodedProviders,
    er: EncodedRequirements,
    weights: CostWeights | None = None,
    k: int = 64,
    tile: int = 1024,
    reverse_r: int = 8,
    provider_offset: jax.Array | None = None,
    task_offset: int | jax.Array = 0,
    approx_recall: float | None = None,
    with_pools: bool = False,
):
    """Bidirectional candidate generation: per-task top-k providers PLUS
    per-provider top-``reverse_r`` tasks, in the same streaming pass.

    Why: with price-dominated costs every task's top-k window covers the
    same cheap providers — at 32k x 32k only ~91% of providers appear in
    ANY task's list (measured), capping the maximum matching at 91% before
    the auction even starts, and 'every node gets a task' (the reference
    matcher's outcome, crates/orchestrator/src/scheduler/mod.rs:26-74) is
    unachievable. Reverse edges guarantee every provider at least
    ``reverse_r`` edges into the graph; merge them with
    :func:`merge_reverse_candidates` and the auction recovers ~100%
    assignment (stage-B completeness, SURVEY §7 hard part 2).

    Returns (cand_p [T,k], cand_c [T,k], rev_t [P,r] i32 with -1 padding,
    rev_c [P,r]). Reverse costs carry the same tie jitter as forward ones.

    Reverse selection is TILE-POOLED, not exact global top-r: each tile
    contributes its per-provider top-``ceil(r / n_tiles)`` tasks and the
    final edges are the best r of that pool. Exactness nobody needs is
    traded for the dominant cost: an exact running top-r folds a
    [P, r+tile] lax.top_k per tile (sort-shaped — measured +48% on the
    whole generation pass at 65k), while the pooled fold is an argmin-
    class reduction plus a [P, r+rt] merge. The properties completeness
    rests on survive exactly: every provider still gets r feasible-if-
    any edges into DISTINCT good tasks, and the single best edge per
    provider is the true global best (every tile's minimum is in the
    pool).

    ``with_pools=True`` additionally returns the raw per-tile
    contributions (pool_t, pool_c) as [P, n_tiles*rt] in tile order —
    the pre-fold state of the pooled selection. The warm-path candidate
    repair persists these: a provider's tile contribution depends only
    on its own cost row over that tile, so a churn-masked recompute is
    per-(provider, tile) local, and the folded rev_t/rev_c are
    re-derived by replaying this exact fold (see
    parallel/sparse.py::repair_topk_bidir_sharded).
    """
    if weights is None:
        weights = CostWeights()
    T = er.cpu_cores.shape[0]
    if T % tile != 0:
        raise ValueError(f"T={T} not divisible by tile={tile}; pad requirements")
    n_tiles = T // tile
    P = ep.gpu_count.shape[0]
    k = min(k, int(P))
    r = min(reverse_r, T)
    rt = max(1, -(-r // n_tiles))  # per-tile contribution (ceil div)

    def step(carry, t0):
        rev_c0, rev_t0 = carry  # [P, r] running best (smallest) costs/tasks
        # forward: per-task top-k providers (the exact shared step —
        # jitter, offsets, approx_max_k — of candidates_topk)
        with jax.named_scope("gen.forward"):
            provider, cost_k, cost = _forward_tile_select(
                ep, er, weights, t0, tile, k,
                provider_offset, task_offset, approx_recall,
            )
        # reverse: this tile's per-provider top-rt, then a tiny merge
        with jax.named_scope("gen.reverse"):
            tid = t0 + jnp.arange(tile, dtype=jnp.int32)
            if rt == 1:
                j = jnp.argmin(cost, axis=1)
                tile_c = jnp.take_along_axis(cost, j[:, None], axis=1)
                tile_t = tid[j][:, None]
            else:
                neg, j = lax.top_k(-cost, rt)
                tile_c = -neg
                tile_t = tid[j]
            merged_c = jnp.concatenate([rev_c0, tile_c], axis=1)  # [P, r+rt]
            merged_t = jnp.concatenate([rev_t0, tile_t], axis=1)
            neg_c, m = lax.top_k(-merged_c, r)
            rev_c1 = -neg_c
            rev_t1 = jnp.take_along_axis(merged_t, m, axis=1)
        ys = (provider, cost_k)
        if with_pools:
            ys = ys + (tile_t, tile_c)
        return (rev_c1, rev_t1), ys

    carry0 = (
        jnp.full((P, r), jnp.float32(INFEASIBLE)),
        jnp.full((P, r), -1, jnp.int32),
    )
    (rev_c, rev_t), ys = lax.scan(
        step, carry0, jnp.arange(n_tiles, dtype=jnp.int32) * tile
    )
    cand_p, cand_c = ys[0], ys[1]
    rev_t = jnp.where(rev_c < INFEASIBLE * 0.5, rev_t, -1)
    out = (cand_p.reshape(T, k), cand_c.reshape(T, k), rev_t, rev_c)
    if with_pools:
        # ys pools are [n_tiles, P, rt]: flatten to [P, n_tiles*rt] in
        # tile order — the layout the repair refold consumes
        pool_t = jnp.moveaxis(ys[2], 0, 1).reshape(P, n_tiles * rt)
        pool_c = jnp.moveaxis(ys[3], 0, 1).reshape(P, n_tiles * rt)
        out = out + (pool_t, pool_c)
    return out


@partial(jax.jit, static_argnames=("extra", "scope"))
def merge_reverse_candidates(
    cand_p: jax.Array,
    cand_c: jax.Array,
    rev_t: jax.Array,
    rev_c: jax.Array,
    extra: int = 16,
    scope: str = "gen.merge",
) -> tuple[jax.Array, jax.Array]:
    """Scatter reverse (provider -> task) edges into up to ``extra`` extra
    candidate columns per task: returns ([T, K+extra] provider ids, costs).

    Exact sort-based placement (no collision loss up to the per-task cap):
    edges sorted by (task, cost), ranked within task by a cummax trick, and
    ranks >= extra dropped — when a task is many providers' best hope, the
    cheapest ``extra`` of them are kept. Edges duplicating a forward
    candidate are dropped first: a duplicate column makes the winner's
    runner-up value equal its best (v1 == v2), collapsing every bid on that
    provider to the minimal +eps increment — measured as a slower, slightly
    WORSE matching than forward-only at 4k.

    ``scope`` names the program's ops in a device trace: cold
    generation and the warm repair both end in this merge, and each
    says which it is (``gen.merge`` / ``repair.merge``).
    """
    with jax.named_scope(scope):
        T = cand_p.shape[0]
        P, r = rev_t.shape
        t_flat = jnp.where(rev_t.reshape(-1) >= 0, rev_t.reshape(-1), T)
        p_flat = jnp.repeat(jnp.arange(P, dtype=jnp.int32), r)
        c_flat = rev_c.reshape(-1)
        dup = jnp.any(
            cand_p[jnp.minimum(t_flat, T - 1)] == p_flat[:, None], axis=1
        )
        t_flat = jnp.where(dup, T, t_flat)
        order = jnp.lexsort((c_flat, t_flat))
        t_s, p_s, c_s = t_flat[order], p_flat[order], c_flat[order]
        n = t_s.shape[0]
        pos = jnp.arange(n, dtype=jnp.int32)
        new_seg = jnp.concatenate(
            [jnp.ones(1, bool), t_s[1:] != t_s[:-1]]
        )
        run_start = lax.associative_scan(
            jnp.maximum, jnp.where(new_seg, pos, -1)
        )
        rank = pos - run_start
        keep = (t_s < T) & (rank < extra)
        ti = jnp.where(keep, t_s, T)
        ri = jnp.where(keep, rank, 0)
        extra_p = jnp.full((T + 1, extra), -1, jnp.int32).at[ti, ri].set(
            p_s, mode="drop"
        )[:T]
        extra_c = jnp.full((T + 1, extra), jnp.float32(INFEASIBLE)).at[ti, ri].set(
            c_s, mode="drop"
        )[:T]
        return (
            jnp.concatenate([cand_p, extra_p], axis=1),
            jnp.concatenate([cand_c, extra_c], axis=1),
        )


def pick_tile(n_tasks: int, cap: int = 1024) -> int:
    """Largest tile <= ``cap`` that divides ``n_tasks`` exactly — the
    task-tiling contract of :func:`candidates_topk_reverse` (the scan
    carries fixed-shape tiles, so T % tile must be 0). Callers pad task
    counts to pow2 buckets, where this returns min(cap, n_tasks); the
    divisor walk keeps odd counts (tests, unpadded replays) working
    instead of raising. One home for the loop that used to be duplicated
    per call site (trace replay, bench, the jax arena)."""
    if n_tasks <= 0:
        return 1
    tile = min(cap, n_tasks)
    while n_tasks % tile != 0:
        tile -= 1
    return tile


def candidates_topk_bidir(
    ep: EncodedProviders,
    er: EncodedRequirements,
    weights: CostWeights | None = None,
    k: int = 64,
    tile: int = 1024,
    reverse_r: int = 8,
    extra: int = 16,
    provider_offset: jax.Array | None = None,
    task_offset: int | jax.Array = 0,
    approx_recall: float | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Forward top-k + reverse top-r candidates, merged: the coverage-safe
    candidate generator for complete matchings (every provider guaranteed
    edges into the graph). Returns ([T, k+extra] provider ids, costs)."""
    cand_p, cand_c, rev_t, rev_c = candidates_topk_reverse(
        ep, er, weights, k=k, tile=tile, reverse_r=reverse_r,
        provider_offset=provider_offset, task_offset=task_offset,
        approx_recall=approx_recall,
    )
    return merge_reverse_candidates(cand_p, cand_c, rev_t, rev_c, extra=extra)


@partial(jax.jit, static_argnames=("num_providers", "max_iters", "frontier", "retire"))
def assign_auction_sparse(
    cand_provider: jax.Array,
    cand_cost: jax.Array,
    num_providers: int,
    eps: float | jax.Array = 0.01,
    max_iters: int = 10000,
    frontier: int = 4096,
    retire: bool = True,
) -> AssignResult:
    """Auction on the candidate graph, Gauss-Seidel style.

    The naive Jacobi round re-gathers prices for ALL tasks' candidates every
    iteration — a [T, K] dynamic gather that dominates wall-clock on TPU
    (~17 ms at 32k x 64; gathers can't be hoisted because prices change).
    Instead each round processes a fixed-size *frontier* of up to
    ``frontier`` unassigned tasks: total gather traffic scales with the
    number of bid events (~O(T) for marketplace costs), not rounds x T.
    Bertsekas auction is correct for any nonempty subset of unassigned
    bidders per round, so this changes which eps-optimal matching is found
    (tie outcomes), not feasibility or quality. ``frontier`` is the most
    that bid in one round, not what a round pays for: the kernel runs
    each round at the narrowest width that holds every open task
    (``_FRONTIER_RUNGS``), with the same outcome. Set ``frontier >= T``
    to recover the dense-parity Jacobi schedule (every open task bids
    every round).

    ``retire=True`` stops tasks whose best achievable value has been bid
    below -(2*max_cost + 10): economically "not worth it", and the
    termination guard against infinite eviction cycles when demand exceeds
    the candidate graph's capacity.

    For contended problems prefer :func:`assign_auction_sparse_scaled`:
    with a single small eps, every over-demanded provider's price must climb
    to the give-up level in eps-sized steps (millions of bid events at 32k);
    eps-scaling covers the same price range geometrically.
    """
    state, _stall, _rows, _scans = _sparse_auction_phase(
        cand_provider, cand_cost, num_providers, None,
        eps=eps, max_iters=max_iters, frontier=frontier, retire=retire,
    )
    p4t = state[3]
    return AssignResult(p4t, _invert(p4t, num_providers))


# Frontier widths a round can run at, below the phase's own
# ``min(frontier, T)``. Most rounds of a solve are eviction chains with
# a few dozen open tasks, and a round's price gather is [width, K]: on a
# v5e at T = P = 8,192, K = 80 a round takes 0.051 / 0.071 / 0.119 /
# 0.656 ms at 32 / 64 / 128 / 1,024 rows, bidding from the carried open
# list, and 2.40 at 4,096 rows, scanning [T] (before the list, 0.204 /
# 0.220 / 0.264 / 0.752 / 2.33); at the transposed shape (K = 256)
# 0.095 / 0.147 / 0.262 / 1.71 (0.246 / 0.297 / 0.410 / 1.81). So each
# round takes the narrowest rung that holds every open task. A warm
# tick's rounds there: 63% with at most 32 open, 34% with 33-64, 2%
# with 65-128, under 1% with more. Five branches cost the round no more
# than two did; a sixth added 0.013 ms to every round (PERF.md section
# 5, scripts/round_cost.py).
_FRONTIER_RUNGS = (32, 64, 128, 1024)

# A round of at most this many rows resolves its bids row against row
# ([w, w]), not over [P]: 1.5-10 us a round less at 32-128 rows at both
# shapes; a [1024, 1024] comparison is a million elements (PERF.md
# section 5).
_ROW_RESOLVE = 128


@partial(
    jax.jit,
    static_argnames=("num_providers", "max_iters", "frontier", "retire", "stall_limit"),
)
def _sparse_auction_phase(
    cand_provider: jax.Array,
    cand_cost: jax.Array,
    num_providers: int,
    state: tuple | None,
    eps: float | jax.Array = 0.01,
    max_iters: int = 10000,
    frontier: int = 4096,
    retire: bool = True,
    stall_limit: int = 0,
    reserve: jax.Array | None = None,
):
    """One eps phase of the frontier auction; ``state`` carries
    (it, price, owner, p4t, retired) across phases for warm starts.
    Returns (state, trailing no-progress rounds, frontier rows, scan
    rounds).

    Every round counts its open tasks and bids at the narrowest width
    that holds them: a rung of ``_FRONTIER_RUNGS``, or ``min(frontier,
    T)`` when more are open than the widest rung below it holds (then
    the first ``frontier`` of them bid, in index order). A width that
    holds every open task is invisible in the state: the fill rows are
    dropped from every scatter, the top-2 reductions are per row, and
    the winner's max and min (over [P], or row against row at most
    ``_ROW_RESOLVE`` rows) take no notice of order. The third result
    is the sum of the widths the rounds ran at.

    A round's work is indexed by its frontier, not by the pool. The
    loop carries the open tasks as a list of the widest rung's size
    ``C``, its length and the seated count, built by one pass over
    ``[T]`` at entry. A round whose open tasks fit the list bids from
    its front, commits by its rows (a winner writes its provider's
    owner and price and its own seat, and clears the seat of the owner
    it evicts) and leaves the next round's list in the same rows: each
    row's task if it is still open, else the owner it evicted if that
    one is open now; the list is in no order, which the round cannot
    see. A round with more open tasks than ``C`` scans ``[T]`` for
    them, commits over ``[P]`` and lists the open set again: the fourth
    result counts those rounds. The list path reads the state's seats
    through ``owner``, so it needs ``owner[p] = t >= 0`` only where
    ``p4t[t] >= 0`` (what every caller's state holds: the kernel's own,
    ``_invert``'s, ``_unassign_unhappy``'s and the passes' seeds).

    ``reserve`` (a scalar) is the reverse pass's floor, where the
    bidders are providers (see :func:`_forward_reverse`): a bidder whose
    best value is not eps above it gives up, and no bid takes the
    bidder's own implied price (its value less the increment) below it.

    ``stall_limit`` > 0 additionally ends the phase after that many
    consecutive rounds with NO NET assignment progress. Per-task
    retirement cannot stop an unfillable tail: the open "hole" wanders
    the graph through eviction chains, so no single neighborhood's prices
    ever reach give_up (measured: 4000/4000 rounds with one open task).
    A stalled phase is pure price circulation — the scaled ladder hands
    the leftovers to the next phase / greedy cleanup instead."""
    T, K = cand_cost.shape
    P = num_providers
    B = min(frontier, T)
    widths = tuple(w for w in _FRONTIER_RUNGS if w < B) + (B,)
    C = widths[-2] if len(widths) > 1 else 0  # the open list's size

    cand_valid = cand_provider >= 0
    value_base = jnp.where(cand_valid, -cand_cost, _NEG)  # [T, K]
    task_feasible = jnp.any(cand_valid, axis=1)
    cand_safe = jnp.where(cand_valid, cand_provider, 0)
    finite_max = jnp.max(jnp.where(cand_valid, cand_cost, 0.0))
    give_up = -(2.0 * finite_max + 10.0) if retire else _NEG
    if reserve is not None:
        give_up = reserve + eps

    def listed(p4t, retired):
        """(open list, open count, seated count): one pass over [T]."""
        open_mask = (p4t < 0) & task_feasible & ~retired
        return (
            jnp.flatnonzero(open_mask, size=C, fill_value=T).astype(jnp.int32),
            jnp.sum(open_mask, dtype=jnp.int32),
            jnp.sum(p4t >= 0, dtype=jnp.int32),
        )

    def cond(loop):
        state, _best, stall, _rows, _scans, _lst, n_open, _n_seated = loop
        go = (state[0] < max_iters) & (n_open > 0)
        if stall_limit > 0:
            go &= stall < stall_limit
        return go

    def bid(f_idx, price):
        """A frontier's bids (fill = T): (row ok, provider, newly
        retired, bidding, amount)."""
        with jax.named_scope("auction.bid"):
            f_ok = f_idx < T
            p1, v1, v2 = frontier_bids(cand_safe, value_base, price, f_idx, f_ok, K)
            newly_retired = f_ok & (v1 < give_up)
            bidding = f_ok & ~newly_retired & (v1 > _NEG * 0.5)
            if reserve is None:
                # a task with one candidate has giving up for its
                # runner-up: it bids that one up to the give-up level,
                # not by frontier_bids' floor of 1e8, which leaves a
                # price no float32 carries with eps to spare (the warm
                # shift then moves every other price by as much)
                v2 = jnp.where(v2 <= -1e8, jnp.maximum(give_up, -1e8), v2)
                bid_amt = price[p1] + (v1 - v2) + eps  # [width]
            else:
                bid_amt = price[p1] + jnp.minimum(
                    (v1 - v2) + eps, v1 - reserve
                )
        return f_ok, p1, newly_retired, bidding, bid_amt

    def resolve(f_idx, p1, bidding, bid_amt):
        """Per provider [P]: the best bid, and the lowest task index
        among those that made it."""
        with jax.named_scope("auction.resolve"):
            tgt = jnp.where(bidding, p1, P)
            win_bid = jnp.full(P, _NEG).at[tgt].max(
                jnp.where(bidding, bid_amt, _NEG), mode="drop"
            )
            is_winner_bid = bidding & (bid_amt >= win_bid[p1])
            win_task = jnp.full(P, T, jnp.int32).at[tgt].min(
                jnp.where(is_winner_bid, f_idx, T), mode="drop"
            )
        return win_bid, win_task

    def scan_round(state, lst, n_open, n_seated):
        """More open tasks than the list holds: the first B in index
        order bid, the commit runs over [P], the list is built anew."""
        it, price, owner, p4t, retired = state
        with jax.named_scope("auction.bid"):
            f_idx = jnp.flatnonzero(
                (p4t < 0) & task_feasible & ~retired, size=B, fill_value=T
            ).astype(jnp.int32)
        _, p1, newly_retired, bidding, bid_amt = bid(f_idx, price)
        win_bid, win_task = resolve(f_idx, p1, bidding, bid_amt)
        with jax.named_scope("auction.commit"):
            retired = retired.at[jnp.where(newly_retired, f_idx, T)].set(
                True, mode="drop"
            )
            got_bid = (win_bid > _NEG * 0.5) & (win_task < T)
            evict_t = jnp.where(got_bid & (owner >= 0), owner, T)
            p4t = p4t.at[evict_t].set(-1, mode="drop")
            p_idx = jnp.arange(P, dtype=jnp.int32)
            win_t_safe = jnp.where(got_bid, win_task, T)
            p4t = p4t.at[win_t_safe].set(jnp.where(got_bid, p_idx, -1), mode="drop")
            owner = jnp.where(got_bid, win_task, owner)
            price = jnp.where(got_bid, win_bid, price)
            lst, n_open, n_seated = listed(p4t, retired)
        return (it, price, owner, p4t, retired), lst, n_open, n_seated

    def resolve_rows(f_idx, p1, bidding, bid_amt):
        """Row against row ([w, w]): per row, the best bid on its
        provider, and the lowest task index among those that made it."""
        with jax.named_scope("auction.resolve"):
            rival = (
                bidding[:, None] & bidding[None, :]
                & (p1[:, None] == p1[None, :])
            )
            top = jnp.max(jnp.where(rival, bid_amt[None, :], _NEG), axis=1)
            first = jnp.min(jnp.where(
                rival & (bid_amt[None, :] >= top[:, None]), f_idx[None, :], T,
            ), axis=1)
        return top, first

    def resolve_pool_rows(f_idx, p1, bidding, bid_amt):
        """:func:`resolve` over [P], read at each row's provider."""
        win_bid, win_task = resolve(f_idx, p1, bidding, bid_amt)
        return win_bid[p1], win_task[p1]

    def list_round(width, resolve_at, state, lst, n_open, n_seated):
        """Every open task bids from the list's first ``width`` rows,
        and the round commits by those rows."""
        it, price, owner, p4t, retired = state
        f_idx = lst[:width]
        f_ok, p1, newly_retired, bidding, bid_amt = bid(f_idx, price)
        top, first = resolve_at(f_idx, p1, bidding, bid_amt)
        with jax.named_scope("auction.commit"):
            retired = retired.at[jnp.where(newly_retired, f_idx, T)].set(
                True, mode="drop"
            )
            # the row whose task took its provider, and the owner it evicts
            won = bidding & (first == f_idx) & (top > _NEG * 0.5)
            held = owner[p1]
            evict = won & (held >= 0)
            p4t = p4t.at[jnp.where(evict, held, T)].set(-1, mode="drop")
            p4t = p4t.at[jnp.where(won, f_idx, T)].set(p1, mode="drop")
            seat = jnp.where(won, p1, P)
            owner = owner.at[seat].set(f_idx, mode="drop")
            price = price.at[seat].set(top, mode="drop")
            n_seated = n_seated + jnp.sum(won & ~evict, dtype=jnp.int32)
            # next round's list, a row each: its task, still open, or
            # the owner it evicted, open now
            h = jnp.maximum(held, 0)
            keep = jnp.where(
                won, evict & task_feasible[h] & ~retired[h], f_ok & ~newly_retired
            )
            pos = jnp.cumsum(keep, dtype=jnp.int32) - 1
            packed = jnp.full(width, T, jnp.int32).at[
                jnp.where(keep, pos, width)
            ].set(jnp.where(won, held, f_idx), mode="drop")
            lst = lax.dynamic_update_slice(lst, packed, (0,))
            n_open = pos[-1] + 1
        return (it, price, owner, p4t, retired), lst, n_open, n_seated

    branches = [
        partial(list_round, w, (
            resolve_rows if w <= _ROW_RESOLVE else resolve_pool_rows
        ))
        for w in widths[:-1]
    ] + [scan_round]

    def body(loop):
        state, best, stall, rows, scans, lst, n_open, n_seated = loop
        rung = sum(
            ((n_open > w).astype(jnp.int32) for w in widths[:-1]), jnp.int32(0)
        )
        state, lst, n_open, n_seated = lax.switch(
            rung, branches, state, lst, n_open, n_seated
        )
        rows = rows + jnp.asarray(widths, jnp.int32)[rung]
        scans = scans + (rung == len(widths) - 1).astype(jnp.int32)
        improved = n_seated > best
        best = jnp.maximum(best, n_seated)
        stall = jnp.where(improved, 0, stall + 1)
        state = (state[0] + 1,) + tuple(state[1:])
        return state, best, stall, rows, scans, lst, n_open, n_seated

    if state is None:
        state = (
            jnp.int32(0),
            jnp.zeros(P, jnp.float32),
            jnp.full(P, -1, jnp.int32),
            jnp.full(T, -1, jnp.int32),
            jnp.zeros(T, bool),
        )
    else:
        # reset the iteration counter for this phase
        state = (jnp.int32(0),) + tuple(state[1:])
    lst, n_open, n_seated = listed(state[3], state[4])
    loop0 = (state, n_seated, jnp.int32(0), jnp.int32(0), jnp.int32(0),
             lst, n_open, n_seated)
    out, _best, stall, rows, scans, _lst, _n_open, _n_seated = lax.while_loop(
        cond, body, loop0
    )
    return out, stall, rows, scans


@jax.jit
@jax.named_scope("auction.unassign_unhappy")
def _unassign_unhappy(
    cand_provider, cand_cost, price, owner, p4t, eps_next, reserve=None,
    joined=None,
):
    """eps-CS repair between phases: holders whose assignment violates the
    tighter eps re-enter the auction; happy holders stay seated (avoids both
    full-reset cost and the mass-retirement pathology of pumped prices).
    In a pool with a queue (``reserve``, see :func:`_queue_phase`) waiting
    is an option like any other: a holder whose seat is worth less to it
    than the reserve leaves it too.

    The comparison carries a float-dust tolerance: a winning bid lands a
    task EXACTLY at the eps-CS boundary (its new value is v2 - eps, and v2
    becomes the new v1), so after a converged phase roughly half the
    matching sits at deficit == eps up to float32 rounding — measured at
    65k: 33,264/65,524 pairs within 1e-4 of the boundary, none beyond
    eps + 1e-3. Without the tolerance a warm restart at the SAME eps
    evicts that entire boundary population (~32k seeds for 655 churned
    tasks) and re-solves from scratch.

    ``joined`` [P] marks providers that came back since the prices were
    made (a row whose ``valid`` went True again): the price such a row
    carries is the one it left with, from another regime perhaps, and
    one far below the landscape makes every seated task that lists it
    unhappy at once (at 256 rows, two returners a tick freed ~55 seats
    and the queue pass ran 1,900-5,707 rounds for them). Each is priced
    where the seated task that values it most would just take it (no
    seat is worse off than before it came), and the prices are returned
    as a third result. The solves call it through :func:`_repair_seats`,
    which hands every argument over as an array, so that one program
    serves every regime: two, a tick with a row coming back and one
    without (the scatter over ``[T, K]`` costs the chip a few ms, which
    a tick with nobody coming back does not pay)."""
    cand_valid = cand_provider >= 0
    cand_safe = jnp.where(cand_valid, cand_provider, 0)
    value = jnp.where(cand_valid, -cand_cost - price[cand_safe], _NEG)  # [T,K]
    held = p4t  # [T]
    vcur = jnp.max(
        jnp.where(cand_safe == jnp.maximum(held, 0)[:, None], value, _NEG), axis=1
    )
    P = owner.shape[0]
    if joined is not None:
        # the most any seated task would pay for each provider, its own
        # seat's value given up: -cost - (seat value)
        offer = jnp.where(
            cand_valid & (held >= 0)[:, None], -cand_cost - vcur[:, None], _NEG
        )
        top = jnp.full(P + 1, _NEG).at[
            jnp.where(cand_valid, cand_provider, P)
        ].max(offer)[:P]
        price = jnp.where(joined & (top > _NEG * 0.5), top, price)
        value = jnp.where(cand_valid, -cand_cost - price[cand_safe], _NEG)
    v1 = jnp.max(value, axis=1)
    if reserve is not None:
        v1 = jnp.maximum(v1, reserve)
    finite_max = jnp.max(jnp.where(cand_valid, cand_cost, 0.0))
    tol = 1e-5 * (1.0 + finite_max + jnp.max(jnp.abs(price)))
    unhappy = (held >= 0) & (vcur < v1 - eps_next - tol)
    owner = owner.at[jnp.where(unhappy, held, P)].set(-1, mode="drop")
    p4t = jnp.where(unhappy, -1, p4t)
    if joined is not None:
        return owner, p4t, price
    return owner, p4t


def _repair_seats(cand_provider, cand_cost, price, owner, p4t, eps, reserve,
                  joined=None):
    """:func:`_unassign_unhappy` as every solve calls it: (owner, p4t,
    price). No reserve is a reserve of -inf (a max with it is the value
    itself)."""
    args = (
        cand_provider, cand_cost, price, owner, p4t, jnp.float32(eps),
        jnp.float32(-jnp.inf if reserve is None else reserve),
    )
    if joined is None:
        return (*_unassign_unhappy(*args), price)
    return _unassign_unhappy(*args, jnp.asarray(joined, bool))


# The reverse pass runs in a pool whose slack (:func:`_stranded`) is at
# least one in ``_SLACK_SHARE`` of the providers its tasks list. Only
# such a pool has the asymmetric problem's condition to meet. Where the
# free providers are matched by open tasks, or outnumber them by a
# handful (a full pool with its unseatable tail and a few tasks nobody
# can serve), the forward auction's give-up level decides, and a reverse
# chain would wander the whole pool, since every provider a steal
# vacates finds a taker: measured on the chip at 8,192 x 8,192, 4.7 s a
# tick for seats worth 0.004 a task (PERF.md section 6, PR 29).
_SLACK_SHARE = 64

# takers a provider's reverse bid looks at: the first ``_REVERSE_WIDTH``
# tasks that list it (the mean is T*K/P, 48-80 in the served pools; a
# provider listed by more does not see the rest, and a seat that ends
# up preferring it is re-opened by the next solve's eps-CS repair)
_REVERSE_WIDTH = 256


def _windows(x, start, width: int):
    """``x[start[p] : start[p] + width]`` for every ``p``, [P, width]:
    the whole 128-lane rows of ``x`` that each window spans, gathered
    as rows, then each shifted left by where its window starts in its
    first row, one bit of the shift at a time. Past the end of ``x``
    the window reads zeros."""
    lanes = 128
    n_rows = -(-width // lanes) + 1
    rows = -(-x.shape[0] // lanes) + n_rows
    table = jnp.pad(x, (0, rows * lanes - x.shape[0])).reshape(rows, lanes)
    spans = table[
        start[:, None] // lanes + jnp.arange(n_rows, dtype=jnp.int32)[None, :]
    ].reshape(start.shape[0], n_rows * lanes)
    shift = start % lanes
    for bit in range(lanes.bit_length() - 1):
        step = 1 << bit
        spans = jnp.where(
            ((shift & step) != 0)[:, None],
            jnp.concatenate([spans[:, step:], spans[:, :step]], axis=1),
            spans,
        )
    return spans[:, :width]


@partial(jax.jit, static_argnames=("num_providers", "width"))
@jax.named_scope("auction.reverse")
def _transpose_candidates(cand_provider, cand_cost, num_providers: int, width: int):
    """The candidate graph from the providers' side: for each provider
    the tasks that list it, in task order, as ``rev_task``/``rev_cost``
    [P, width] with -1 / INFEASIBLE in empty slots — the shape
    :func:`_sparse_auction_phase` takes when providers bid. One stable
    single-key sort of the flattened provider ids, the keys kept (what
    the TPU compiler builds fastest: a second key doubles its 20 s),
    with each entry's index and cost carried along; a binary search in
    the keys finds where each provider's run starts, and the run's first
    ``width`` entries are cut out as whole rows (:func:`_windows`). On a
    TPU v5e at 8,192 x 80 -> [8,192, 256] that is ~3.5 ms a call, where
    gathering the cost after the sort took ~8 and a scatter-add of the
    counts and two gathers of every [P, width] slot by index ~36 (PERF.md
    section 5); the cost as a third operand adds ~12 s to the compile."""
    T, K = cand_cost.shape
    P = num_providers
    prov = jnp.where(cand_provider >= 0, cand_provider, P).ravel()
    prov, flat, cost = lax.sort(
        (prov, jnp.arange(T * K, dtype=jnp.int32), cand_cost.ravel()),
        num_keys=1, is_stable=True,
    )
    start = jnp.searchsorted(
        prov, jnp.arange(P + 1, dtype=jnp.int32)
    ).astype(jnp.int32)
    ok = jnp.arange(width)[None, :] < (start[1:] - start[:P])[:, None]
    return (
        jnp.where(ok, _windows(flat // K, start[:P], width), -1),
        jnp.where(ok, _windows(cost, start[:P], width), INFEASIBLE),
    )


@jax.jit
@jax.named_scope("auction.reverse")
def _stranded(cand_provider, price, owner, p4t):
    """(listed [P], floor, the least seated price, [stranded, slack,
    listed providers]).

    Only providers some task lists (``listed``) take part: a row no
    task lists (one that left the pool, or that nobody can use) is
    never stranded and sets no floor, whatever price it carries.
    Stranded: the listed providers a converged forward phase left free
    at a price above the floor, the lowest price a listed provider
    carries (what one nobody ever bid for still has); prices within
    float dust of the floor do not count. The least price a SEATED
    provider carries is the floor of a pool in the band
    (:func:`_forward_reverse`'s ``band``): the asymmetric condition
    asks no more than that no free provider is dearer than a seated
    one. Slack: the providers left free that some task lists, less
    TWICE the tasks left open that list any; positive where most of the
    free providers are free for want of tasks and not because their
    tasks were priced out. :func:`_forward_reverse` holds it against
    the listed providers (``_SLACK_SHARE``)."""
    P = price.shape[0]
    listed = cand_provider >= 0
    reach = jnp.zeros(P + 1, jnp.int32).at[
        jnp.where(listed, cand_provider, P).ravel()
    ].add(1)[:P] > 0
    free = owner < 0
    floor = jnp.min(jnp.where(reach, price, jnp.inf))
    floor = jnp.where(jnp.isfinite(floor), floor, 0.0)
    seated = jnp.min(jnp.where(reach & ~free, price, jnp.inf))
    seated = jnp.where(jnp.isfinite(seated), seated, floor)
    stranded = free & reach & (price > floor + _dust(price))
    slack = jnp.sum(free & reach, dtype=jnp.int32) - 2 * jnp.sum(
        (p4t < 0) & jnp.any(listed, axis=1), dtype=jnp.int32
    )
    return reach, floor, seated, jnp.stack([
        jnp.sum(stranded, dtype=jnp.int32), slack,
        jnp.sum(reach, dtype=jnp.int32),
    ])


def _dust(price):
    """How far above a floor a price may lie and still be at it."""
    return 1e-5 * (1.0 + jnp.max(jnp.abs(price)))


def _task_values(cand_provider, cand_cost, price, p4t):
    """(what each candidate is worth to its task at ``price`` [T, K],
    what its seat is [T]; ``_NEG`` for an empty slot and for a task
    without a seat on its list)."""
    cand_valid = cand_provider >= 0
    cand_safe = jnp.where(cand_valid, cand_provider, 0)
    value = jnp.where(cand_valid, -cand_cost - price[cand_safe], _NEG)
    seat = jnp.max(
        jnp.where(cand_safe == jnp.maximum(p4t, 0)[:, None], value, _NEG), axis=1
    )
    return value, seat


@jax.jit
@jax.named_scope("auction.reverse")
def _reverse_seed(cand_provider, cand_cost, price, owner, p4t, listed, floor):
    """State of the reverse phase, in :func:`_sparse_auction_phase`'s
    layout with the roles swapped (providers bid, tasks are bid for):
    a task's "price" is its profit, the value of its seat (of its best
    candidate when it has none), so a provider's value for a task,
    ``-cost - profit``, is the price at which that task would just take
    it. Only the listed providers stranded above ``floor`` bid
    (:func:`_stranded`'s ``listed`` and one of its floors): one at the
    floor does not (no seated task prefers it by more than eps, or the
    forward phase would not have ended). Returns (state, floor)."""
    value, seat = _task_values(cand_provider, cand_cost, price, p4t)
    profit = jnp.where(p4t >= 0, seat, jnp.max(value, axis=1))
    stranded = (owner < 0) & listed & (price > floor + _dust(price))
    return (jnp.int32(0), profit, p4t, owner, (owner < 0) & ~stranded), floor


@jax.jit
@jax.named_scope("auction.reverse")
def _reverse_finish(cand_provider, cand_cost, price, profit0, rstate, floor):
    """Back to the forward layout: a provider that took a task is priced
    at what its bid left that task indifferent to (``-cost - profit``);
    a provider the pass leaves free goes to the floor; nobody else's
    price moves. Then every price comes down by the floor where that is
    positive (a uniform shift changes no value difference), so that the
    free providers of a pool with slack sit at 0, the lowest feasible
    dual, and the quality certificate's ``idle_price`` counts only what
    is stranded. Returns (price, owner, p4t, providers lowered)."""
    _, profit, p4t, owner, _ = rstate
    P = price.shape[0]
    cand_safe = jnp.where(cand_provider >= 0, cand_provider, 0)
    seat_cost = jnp.max(
        jnp.where(
            (cand_provider >= 0) & (cand_safe == jnp.maximum(p4t, 0)[:, None]),
            cand_cost, _NEG,
        ), axis=1,
    )
    # a task that was bid for, even by the provider it had, holds its
    # seat at a new profit
    won = (p4t >= 0) & (profit != profit0)
    out = price.at[jnp.where(won, p4t, P)].set(
        jnp.maximum(-seat_cost - profit, floor), mode="drop"
    )
    tol = 1e-5 * (1.0 + jnp.max(jnp.abs(price)))
    out = jnp.where((owner < 0) & (out > floor + tol), floor, out)
    lowered = jnp.sum(out < price, dtype=jnp.int32)
    return out - jnp.maximum(floor, 0.0), owner, p4t, lowered


def _forward_reverse(
    cand_provider, cand_cost, num_providers: int, state, eps,
    max_iters: int, frontier: int, stall_limit: int,
    stats_out: dict | None, transposed: list, reserve: float | None = None,
    band: bool = False,
):
    """One eps phase to the condition the pool's regime needs: with
    ``reserve`` (a pool with a queue) :func:`_queue_phase`'s; without,
    the condition a pool with free providers needs.

    With fewer tasks than providers a forward auction is eps-optimal
    only if no provider it leaves free is priced above a seated one
    (Bertsekas and Castanon 1992, forward/reverse auction for
    asymmetric assignment). A provider that was bid up and then
    abandoned, by a coarser rung's eviction or by churn ticks ago,
    keeps its raised price and attracts nobody, however cheap it is.
    So after the forward phase (:func:`_phase_adaptive` under
    ``max_iters``, ``frontier`` and ``stall_limit``) the stranded
    providers bid for tasks: the SAME phase kernel on the
    transposed candidate graph, each lowering its price to where its
    second-best taker would just take it (never below the floor) and
    taking the best one, whose old provider bids in turn; one that no
    task would take above the floor goes to the floor and stays free.
    No task loses its seat, so the forward phase need not run again.

    One read of three scalars says whether any provider is stranded and
    whether the pool has slack enough (:func:`_stranded`,
    ``_SLACK_SHARE``); unless both, nothing else runs and the prices
    come back bit for bit. ``band`` (a solve whose pool came into the
    band between the regimes from either side, ``REGIMES``) lets any
    slack above 0 do, and the pass then lowers the stranded providers
    only as far as the least price a seated provider carries, which is
    all the asymmetric condition asks: from there a chain ends at the
    first provider no task would take above the seated ones, where from
    the floor it would have to carry the whole landscape down (a full
    pool, ``"none"``, keeps the forward phase's plan as it always has:
    its few free providers are the ones its unseatable tail leaves,
    and a chain through its pool cost 4.7 s a tick for 0.004 a task at
    8,192 rows). ``transposed``
    caches the transposed graph for the solve. Each step of the pass is
    an ``auction.reverse`` span. ``stats_out`` gains ``free_repriced``
    (providers the pass lowered), ``reverse_rounds``, ``reverse_ms`` and
    ``frontier_rows`` (the widths every round of the phase ran at, both
    directions, summed) and ``scan_rounds`` (the rounds that scanned
    ``[T]`` for their open tasks, both directions).
    Returns (state, stall, rounds of the forward phase)."""
    if reserve is not None:
        return _queue_phase(
            cand_provider, cand_cost, num_providers, state, eps, reserve,
            max_iters, frontier, stall_limit, stats_out, transposed,
        )
    state, stall, rows, scans = _phase_adaptive(
        cand_provider, cand_cost, num_providers, state,
        eps=eps, max_iters=max_iters, frontier=frontier, retire=True,
        stall_limit=stall_limit, stats_out=stats_out,
    )
    it, price, owner, p4t, retired = state
    rounds = int(it) if stats_out is not None else 0
    t0 = time.perf_counter()
    with _tracer.span("auction.reverse", eps=eps, step="check") as sp:
        reach, floor, seated_floor, counts = _stranded(
            cand_provider, price, owner, p4t
        )
        n_stranded, slack, listed = (int(n) for n in np.asarray(counts))
        if sp is not None:
            sp["attrs"].update(stranded=n_stranded, slack=slack)
    lowered = reverse_rounds = reverse_rows = reverse_scans = 0
    band = band and slack * _SLACK_SHARE < listed
    if n_stranded > 0 and slack > 0 and (band or slack * _SLACK_SHARE >= listed):
        with _tracer.span("auction.reverse", step="seed", dispatch_only=True):
            if not transposed:
                transposed.extend(_transpose_candidates(
                    cand_provider, cand_cost, num_providers, _REVERSE_WIDTH
                ))
            rstate, floor = _reverse_seed(
                cand_provider, cand_cost, price, owner, p4t, reach,
                seated_floor if band else floor,
            )
        profit0 = rstate[1]
        # every stranded provider may bid at once: the kernel fits each
        # round's width to the ones still open, and one executable serves
        # every count of them
        rstate, _, reverse_rows, reverse_scans = _phase_adaptive(
            transposed[0], transposed[1], p4t.shape[0], rstate,
            eps=eps, max_iters=20000, frontier=num_providers, retire=True,
            stall_limit=0, reserve=floor, span="auction.reverse",
        )
        reverse_rounds = int(rstate[0])
        with _tracer.span("auction.reverse", step="finish"):
            price, owner, p4t, n_lowered = _reverse_finish(
                cand_provider, cand_cost, price, profit0, rstate, floor
            )
            lowered = int(n_lowered)
        state = (it, price, owner, p4t, retired)
    if stats_out is not None:
        for key, value in (
            ("free_repriced", lowered),
            ("reverse_rounds", reverse_rounds),
            ("reverse_ms", (time.perf_counter() - t0) * 1e3),
            ("frontier_rows", rows + reverse_rows),
            ("scan_rounds", scans + reverse_scans),
        ):
            stats_out[key] = round(stats_out.get(key, 0) + value, 3)
    return state, stall, rounds


# A pool has a queue where the tasks that list a provider outnumber the
# providers any task lists, by however few: its binding phase runs the
# chains that decide who waits to their end (:func:`_queue_phase`), so a
# handful waiting is a queue like any other (820 providers on the CPU: 7
# waiting, 0.0001 a seated task off the optimum, where the forward
# auction's give-up level left it 0.0345 off). A full pool's unseatable
# tail is no queue: the served one lists every provider and every task,
# 8,192 of each, on every tick, and a task nobody can serve lists no
# provider.


# The regimes a solve names (``auction.regime``, the arena's carried
# ``regime``): a pool with a queue, one with idle nodes (one in
# ``_SLACK_SHARE`` of the listed providers free, at least), and between
# them, a few idle nodes or none: ``"none"`` for a full pool, which
# has always been there (its unseatable tail), ``"band"`` for a pool
# that came into it from either side and is still there
# (:func:`assign_auction_sparse_warm`). The band is told from the
# carried regime and not from the pool alone: a full pool with a few
# tasks nobody can serve has a few more listed providers than tasks
# that list one, as the band has, and a reverse pass there moves seats
# the parent's solve kept (33 of 512 on ``tests/test_pool_slack.py``'s
# full pool with four unservable tasks) and cost 4.7 s a tick at 8,192
# rows for 0.004 a task.
REGIMES = ("none", "slack", "queue", "band")


def _queue_reserve(cand_provider, cand_cost, num_providers: int,
                   reserve0: float | None, stats_out: dict | None,
                   regime_out: dict | None = None,
                   regime0: str | None = None):
    """The reserve of a pool with a queue, or None where the pool has
    none; ``regime_out["regime"]`` gets the regime's name (``REGIMES``):
    a queue where tasks that list a provider outnumber the providers any
    task lists, slack where the providers outnumber the tasks by one in
    ``_SLACK_SHARE``, ``"none"`` between, which is ``"band"`` where the
    duals were made in another regime (``regime0``, the carried one: a
    pool that came there from either side and is still there). The regime
    is the candidate graph's, counted on the host
    (tasks that list a provider against providers some task lists: the
    arena and the matcher hold the lists as NumPy, so no program runs
    and nothing is read back; a device array is copied over first), an
    ``auction.queue`` span.

    The reserve is the value of waiting: a task bids for a seat only
    while the seat is worth more to it, and a provider takes a waiting
    task at any price that leaves the task that much. It is a level,
    not a quantity the solve searches for: prices find their place
    against it, so what a solve anchors (at the forward auction's own
    give-up level, ``-(2 max cost + 10)``, which keeps every price at
    or above 0) the next one carries (``reserve0``), unless the costs
    have moved so far that the anchor no longer holds them."""
    t0 = time.perf_counter()
    P = num_providers
    with _tracer.span("auction.queue", step="regime") as sp:
        ids = np.asarray(cand_provider)
        listed = ids >= 0
        seatable = int(np.count_nonzero(listed.any(axis=1)))
        reach = np.zeros(P + 1, bool)  # an empty slot's -1 lands on [P]
        reach[ids.ravel()] = True
        reach = int(np.count_nonzero(reach[:P]))
        queued = seatable - reach
        if sp is not None:
            sp["attrs"].update(seatable=seatable, listed=reach)
    if stats_out is not None:
        stats_out["queue_ms"] = round(
            stats_out.get("queue_ms", 0.0)
            + (time.perf_counter() - t0) * 1e3, 3
        )
        stats_out.setdefault("queue_rounds", 0)
    queue = queued > 0
    if regime_out is not None:
        regime_out["regime"] = (
            "queue" if queue
            else "slack" if -queued * _SLACK_SHARE >= reach > 0
            else "band" if regime0 not in (None, "none")
            else "none"
        )
    if not queue:
        return None
    finite_max = float(np.max(np.asarray(cand_cost), where=listed, initial=0.0))
    if reserve0 is not None and (
        1.5 * finite_max + 5.0 <= -reserve0 <= 4.0 * finite_max + 20.0
    ):
        return float(reserve0)
    return -(2.0 * finite_max + 10.0)


@partial(jax.jit, static_argnames=("num_providers",))
@jax.named_scope("auction.queue")
def _queue_free(cand_provider, owner, num_providers: int):
    """Free providers that some task lists: the ones a phase's queue
    pass has to seat."""
    P = num_providers
    reach = jnp.zeros(P + 1, jnp.int32).at[
        jnp.where(cand_provider >= 0, cand_provider, P).ravel()
    ].add(1)[:P] > 0
    return jnp.sum(reach & (owner < 0), dtype=jnp.int32)


@jax.jit
@jax.named_scope("auction.queue")
def _queue_seed(cand_provider, cand_cost, price, owner, p4t, reserve):
    """State of the queue pass, in :func:`_sparse_auction_phase`'s
    layout with the roles swapped as in :func:`_reverse_seed`: a seated
    task's "price" is the value of its seat, a waiting task's the
    reserve (or its best candidate's value, where that is more: such a
    task bids for itself in the forward phase that follows). Every free
    provider bids."""
    value, seat = _task_values(cand_provider, cand_cost, price, p4t)
    profit = jnp.where(
        p4t >= 0, seat, jnp.maximum(jnp.max(value, axis=1), reserve)
    )
    return (jnp.int32(0), profit, p4t, owner, jnp.zeros(owner.shape, bool))


def _queue_phase(
    cand_provider, cand_cost, num_providers: int, state, eps, reserve: float,
    max_iters: int, frontier: int, stall_limit: int,
    stats_out: dict | None, transposed: list,
):
    """One eps phase of a pool with a queue: more tasks than seats.

    A forward auction cannot end there: the tasks that must wait keep
    bidding until every price has climbed to the give-up level, eps at
    a time. The asymmetric problem's condition (Bertsekas and Castanon
    1992; the mirror of :func:`_forward_reverse`'s, with the providers
    the short side) is: every provider a task can use is seated, the
    seated are eps-optimal among themselves, and no waiting task values
    any seat above ``reserve``, which every seated task's seat is worth
    to it. Two steps reach it, both the one phase kernel:

    1. *The queue pass.* The free providers bid for tasks over the
       transposed graph, each lowering its price to where its
       second-best taker would just take it and taking the best one; a
       waiting task takes any price that leaves it ``reserve``. A seated
       task that is bid for changes provider and the old one bids in
       turn; no task loses a seat, and since tasks outnumber providers
       every chain ends at the queue. A provider nobody takes at price
       0 stays free there.
    2. *The forward phase under the reserve.* The open tasks bid as
       ever, but none pays more than leaves it ``reserve``, and one
       whose best seat is not worth eps more than that waits. Bids evict
       and never vacate, so step 1 need not run again; prices are
       bounded by the reserve, so the phase ends by itself, and the
       tasks it leaves open are the ones that wait. So the binding
       phase (the warm solve's, the ladder's last) is run with no stall
       breaker (``stall_limit`` 0): a breaker counts seats, and every
       provider a task lists is seated once step 1 is done, so under
       it the phase had a fixed budget of 512 rounds, and a queue of a
       few tasks next to P = T needs the chains that find who waits
       run to their end (CPU, 820 providers: 7 waiting, 1,180 rounds
       and 0.0001 a seated task off the optimum, 0.0117-0.0345 cut at
       512).

    Step 1 is ``auction.queue`` spans (``check``, closed at the pass's
    one read of a scalar, then ``seed``, the segments and ``finish``);
    ``stats_out`` gains ``queue_rounds`` and ``queue_ms``. Returns
    (state, stall, rounds of the forward phase), as
    :func:`_forward_reverse` does."""
    it, price, owner, p4t, _retired = state
    T = p4t.shape[0]
    t0 = time.perf_counter()
    with _tracer.span("auction.queue", eps=eps, step="check") as sp:
        n_free = int(_queue_free(cand_provider, owner, num_providers))
        if sp is not None:
            sp["attrs"].update(free=n_free)
    queue_rounds = queue_rows = queue_scans = 0
    if n_free > 0:
        floor = jnp.float32(0.0)
        with _tracer.span("auction.queue", step="seed", dispatch_only=True):
            if not transposed:
                transposed.extend(_transpose_candidates(
                    cand_provider, cand_cost, num_providers, _REVERSE_WIDTH
                ))
            rstate = _queue_seed(
                cand_provider, cand_cost, price, owner, p4t,
                jnp.float32(reserve),
            )
        profit0 = rstate[1]
        rstate, _, queue_rows, queue_scans = _phase_adaptive(
            transposed[0], transposed[1], T, rstate,
            eps=eps, max_iters=20000, frontier=num_providers, retire=True,
            stall_limit=0, reserve=floor, span="auction.queue",
        )
        queue_rounds = int(rstate[0])
        with _tracer.span("auction.queue", step="finish", dispatch_only=True):
            price, owner, p4t, _ = _reverse_finish(
                cand_provider, cand_cost, price, profit0, rstate, floor
            )
    queue_ms = (time.perf_counter() - t0) * 1e3
    # who waits is decided anew under this phase's prices
    state = (it, price, owner, p4t, jnp.zeros(T, bool))
    state, stall, rows, scans = _phase_adaptive(
        cand_provider, cand_cost, num_providers, state,
        eps=eps, max_iters=max_iters, frontier=frontier, retire=True,
        stall_limit=stall_limit, stats_out=stats_out,
        reserve=jnp.float32(reserve),
    )
    rounds = int(state[0]) if stats_out is not None else 0
    if stats_out is not None:
        for key, value in (
            ("free_repriced", 0), ("reverse_rounds", 0), ("reverse_ms", 0.0),
            ("queue_rounds", queue_rounds), ("queue_ms", queue_ms),
            ("frontier_rows", rows + queue_rows),
            ("scan_rounds", scans + queue_scans),
        ):
            stats_out[key] = round(stats_out.get(key, 0) + value, 3)
    return state, stall, rounds


@partial(jax.jit, static_argnames=("budget",))
@jax.named_scope("auction.greedy_cleanup")
def _greedy_cleanup_compacted(cand_provider, cand_cost, owner, p4t, budget: int):
    """Forward auctions never lower prices, so an unfillable tail can strand
    providers at pumped prices. Sweep the OPEN tasks greedily (cheapest free
    candidate each) — the reference matcher's semantics on the tail; no
    provider idles while a compatible task waits.

    The scan is inherently sequential, so it runs over a compacted index set
    of at most ``budget`` open tasks (static size), never all T — the caller
    skips it entirely when nothing is open."""
    T, K = cand_cost.shape
    free = owner < 0  # [P]
    cand_valid = cand_provider >= 0
    cand_safe = jnp.where(cand_valid, cand_provider, 0)

    open_idx = jnp.flatnonzero(
        (p4t < 0) & jnp.any(cand_valid, axis=1), size=budget, fill_value=T
    ).astype(jnp.int32)
    ok = open_idx < T
    safe_idx = jnp.where(ok, open_idx, 0)

    def step(free, inputs):
        t_ok, cp, cc, valid = inputs
        cost_row = jnp.where(valid & free[cp], cc, INFEASIBLE)
        j = jnp.argmin(cost_row)
        feasible = (cost_row[j] < INFEASIBLE * 0.5) & t_ok
        p = cp[j]
        free = free.at[p].set(jnp.where(feasible, False, free[p]))
        return free, jnp.where(feasible, p, -1)

    _, picks = lax.scan(
        step, free, (ok, cand_safe[safe_idx], cand_cost[safe_idx], cand_valid[safe_idx])
    )
    return p4t.at[jnp.where(ok & (picks >= 0), open_idx, T)].set(
        jnp.where(picks >= 0, picks, -1), mode="drop"
    )


def _greedy_cleanup(cand_provider, cand_cost, owner, p4t, build: bool = False):
    """Host wrapper: one scalar readback decides whether cleanup is needed;
    the compaction budget is a pow-2 bucket of the open count (of tasks
    that list a provider: a session's padded rows never do, and a pool
    with fewer tasks than its row bucket would sweep them every tick).

    ``build`` (the ladder's): sweep at the smallest bucket even where
    nothing is open, so that the program a warm tick of the band needs
    (a few tasks left open beside as many providers) is built with the
    session's cold open and not on that tick; where nothing is open the
    sweep seats nobody."""
    n_open = int(jnp.sum((p4t < 0) & jnp.any(cand_provider >= 0, axis=1)))
    if n_open == 0 and not build:
        return p4t
    budget = 1024
    while budget < n_open:
        budget *= 2
    budget = min(budget, int(cand_cost.shape[0]))
    return _greedy_cleanup_compacted(cand_provider, cand_cost, owner, p4t, budget)


def assign_auction_sparse_scaled(
    cand_provider: jax.Array,
    cand_cost: jax.Array,
    num_providers: int,
    # eps_start=0.5 is 2.1-2.5x faster at 16k-65k with equal aggregate
    # quality — but BREAKS small-instance price semantics: a lone
    # bidder's first bid pumps the winner's price by the full v1-v2 gap,
    # and without enough coarser rungs the eps-CS repair leaves the task
    # parked on the WRONG (pricier) provider
    # (tests/test_marketplace.py::TestPriceFlipsAssignment). The coarse
    # start buys repair rungs, not convergence speed. Callers solving
    # large statistical marketplaces MAY pass a finer start; the default
    # preserves the reference's cheapest-wins semantics.
    eps_start: float = 4.0,
    eps_end: float = 0.02,
    scale: float = 0.25,
    max_iters_per_phase: int = 4000,
    frontier: int = 4096,
    with_prices: bool = False,
    stall_limit: int = 64,
    stats_out: dict | None = None,
    with_state: bool = False,
    regime_out: dict | None = None,
    regime0: str | None = None,
):
    """eps-scaling auction: geometric eps ladder with warm-started prices
    (Bertsekas' eps-scaling — total bid events O(n log(1/eps)) instead of
    O(price_range / eps)).

    Phase discipline (mirrors native/assign_engine.cpp):
      - retirement runs in EVERY phase as a circuit breaker, but non-final
        retirements are REVERSED between phases (un-retire + eps-CS
        repair), so only the final phase's retirement is binding. Without
        this, an unfillable tail cycles through eviction chains until
        max_iters in every coarse phase — measured 4000/4000 rounds with
        ONE open task (50 s/phase on CPU at T=8k) vs ~tens of rounds to
        retire it. A viable task retired early by coarse-eps overshoot is
        re-opened at the next (finer) phase and re-bid correctly.
      - a final greedy cleanup seats any stranded provider/task pairs.

    The BINDING phase's stall circuit breaker (``stall_limit * 8``
    no-net-progress rounds) truncates long eviction chains that reshuffle
    without changing the assigned count; quality on such tails then falls
    to the greedy cleanup. ``stall_limit=0`` opts out (run to
    ``max_iters_per_phase``); a stall-terminated solve is reported via
    ``stats_out["stall_exit"]`` and a log line so quality regressions at
    large T stay observable.

    ``with_prices=True`` additionally returns the final price vector [P] —
    the warm-start state for the NEXT solve (see
    :func:`assign_auction_sparse_warm`). ``with_state=True`` returns
    (result, prices, retired [T]) — the retirement mask is dual state too:
    forward auctions never lower prices, so a task priced out of its whole
    candidate list STAYS priced out until a cold re-ground, and a warm
    chain that does not carry the mask re-fights the unfillable tail's
    full stall budget on every solve (measured: 1792 vs 476 rounds at a
    tail-heavy 2048).

    ``regime_out["regime"]`` gets the pool's regime (:func:`_queue_reserve`,
    which names the band from ``regime0``, the regime the carried duals
    were made in: a dual refresh passes it, a session's open has none).
    In the band the final phase runs the reverse pass
    (:func:`_forward_reverse`), as the warm solve does.
    """
    state = None
    # (lists held on the host go up while the host counts them)
    with _tracer.span("auction.upload", dispatch_only=True):
        lists = jnp.asarray(cand_provider), jnp.asarray(cand_cost)
    regime_out = {} if regime_out is None else regime_out
    reserve = _queue_reserve(
        cand_provider, cand_cost, num_providers, None, stats_out, regime_out,
        regime0,
    )
    band = regime_out.get("regime") == "band"
    cand_provider, cand_cost = lists
    if reserve is not None:
        # a pool with a queue opens with every seat priced out of
        # everyone's reach, and the providers bid their way down
        T = cand_cost.shape[0]
        state = (
            jnp.int32(0),
            jnp.full(num_providers, -reserve, jnp.float32),
            jnp.full(num_providers, -1, jnp.int32),
            jnp.full(T, -1, jnp.int32),
            jnp.zeros(T, bool),
        )
    eps = eps_start
    rounds_total = 0
    transposed: list = []
    while True:
        final = eps <= eps_end
        state, stall, rounds = _forward_reverse(
            cand_provider, cand_cost, num_providers, state, eps,
            max_iters=max_iters_per_phase, frontier=frontier,
            # the FINAL phase's retirement is binding and its eviction
            # chains (closing eps_end-sized price gaps) legitimately
            # make no net progress for long stretches — give it 8x the
            # circuit-breaker budget of the disposable coarse phases,
            # and none where tasks wait (see _queue_phase), whose
            # coarse phases get the 8x (cut at 64 rounds, the ladder
            # that seats a queue of 7 of 827 ended 0.021 a seated task
            # off the optimum on the CPU, at 512 0.0005)
            stall_limit=(
                0 if reserve is not None else stall_limit * 8
            ) if final else stall_limit * (8 if reserve is not None else 1),
            stats_out=stats_out, transposed=transposed, reserve=reserve,
            band=band and final,
        )
        # per-phase round count (read back only when asked for)
        rounds_total += rounds
        if final:
            # (a queue's forward phase runs without the breaker)
            _report_stall("scaled", stall, 0 if reserve is not None
                          else stall_limit * 8, stats_out)
            if stats_out is not None:
                # the platform-independent cost driver: wall = rounds x
                # per-round kernel cost. Exposed so frontier/eps tuning
                # has a measurable objective off-chip.
                stats_out["rounds_total"] = rounds_total
            break
        eps = max(eps * scale, eps_end)
        it, price, owner, p4t, retired = state
        with _tracer.span("auction.seed", eps=eps, dispatch_only=True):
            owner, p4t, _ = _repair_seats(
                cand_provider, cand_cost, price, owner, p4t, eps, reserve
            )
            # un-retire: coarse-phase retirement was only the circuit
            # breaker
            retired = jnp.zeros_like(retired)
        state = (it, price, owner, p4t, retired)

    _, price, owner, p4t, retired = state
    with _tracer.span("auction.cleanup"):
        # (a pool with a queue has nobody to sweep: a provider its
        # queue pass leaves free is one no waiting task lists; the
        # sweep's smallest program is built all the same, and seats
        # nobody, since no listed provider is free)
        if reserve is None:
            p4t = _greedy_cleanup(
                cand_provider, cand_cost, owner, p4t, build=True
            )
        else:
            p4t = _greedy_cleanup_compacted(
                cand_provider, cand_cost, owner, p4t,
                min(1024, int(cand_cost.shape[0])),
            )
    _regime_stats(stats_out)
    res = AssignResult(p4t, _invert(p4t, num_providers))
    if with_state:
        # a retired task the greedy cleanup managed to seat is assigned,
        # not priced out — clear its flag in the carried state
        return res, price, retired & (p4t < 0), reserve
    if with_prices:
        return res, price
    return res


def _regime_stats(stats_out: dict | None) -> None:
    """The crossing's counters, 0 on a solve that crossed nothing, and
    ``transposed_rounds``: the reverse and queue passes' rounds of the
    solve in one counter."""
    if stats_out is not None:
        stats_out.setdefault("regime_change", 0)
        stats_out.setdefault("cross_ms", 0.0)
        stats_out.setdefault("cross_rounds", 0)
        stats_out["transposed_rounds"] = int(
            stats_out.get("reverse_rounds", 0)
            + stats_out.get("queue_rounds", 0)
        )


def _phase_adaptive(
    cand_provider,
    cand_cost,
    num_providers: int,
    state,
    eps,
    max_iters: int,
    frontier: int,
    retire: bool,
    stall_limit: int,
    stats_out: dict | None = None,
    reserve=None,
    span: str = "auction.segment",
):
    """One eps phase run in SEGMENTS of the phase kernel, the host
    holding the stall breaker and the budget between them.

    Measured (16k, CPU): round count is nearly flat in the frontier size
    (4105 rounds at B=4096 vs 4731 at B=512) because most rounds are tail
    eviction chains with a SMALL open set — a wide frontier makes every
    round pay large gathers for parallelism that isn't there. The width
    is the kernel's own business (it fits every round to the open set,
    see :func:`_sparse_auction_phase`); every segment is the SAME
    executable at ``min(frontier, T)``, re-entered with carried state.

    The stall circuit breaker lives at segment granularity out here (a
    per-segment stall_limit static would re-trace the kernel every
    segment): the kernel's trailing no-progress count accumulates across
    whole-segment stalls, so a trip can land up to one segment late —
    benign, the tail then falls to greedy cleanup exactly as a true
    stall would. Segments are a FIXED size for the same retrace reason;
    the phase budget is honored at segment granularity (up to
    seg_rounds-1 extra rounds past ``max_iters``, a budget-cap semantic,
    not a correctness one).

    Each segment is one ``span`` span, ``auction.segment`` unless the
    reverse pass names its own (the finest grain the solve is traced
    at: nothing per round), with the attrs ``rows``, the widths its
    rounds ran at, summed, and ``scans``, its rounds that scanned
    ``[T]`` for their open tasks; ``reserve`` goes to the kernel as it
    is. The four scalars a segment ends in are one read; after a full
    segment the host counts the tasks still open, an eager ``[T]`` sum
    and its read, under an ``auction.open_count`` span.
    ``stats_out`` gains ``segments`` and ``wait_ms``, the time the host
    spent inside the segment's blocking scalar reads — its view of the
    device's time — and ``open_read_ms``, the open counts' share of it.
    Returns (state, accumulated stall, the phase's rows,
    its scan rounds).
    """
    seg_rounds = 256
    T = cand_cost.shape[0]
    task_feasible = jnp.any(cand_provider >= 0, axis=1)
    iters_left = max_iters
    total_it = 0
    total_rows = 0
    total_scans = 0
    B = min(frontier, T)
    carried_stall = 0
    segments = 0
    wait_s = 0.0
    open_s = 0.0
    while iters_left > 0:
        with _tracer.span(span, frontier=B) as seg:
            state, stall, rows, scans = _sparse_auction_phase(
                cand_provider, cand_cost, num_providers, state,
                eps=eps, max_iters=seg_rounds, frontier=B, retire=retire,
                stall_limit=0, reserve=reserve,
            )
            t_wait = time.perf_counter()
            it, s, seg_rows, seg_scans = (
                int(n) for n in jax.device_get((state[0], stall, rows, scans))
            )
            total_it += it
            total_rows += seg_rows
            total_scans += seg_scans
            iters_left -= it
            carried_stall = carried_stall + it if s >= it else s
            # the phase goes on only after a full segment under the
            # circuit breaker (checked at segment-boundary granularity)
            # with tasks still open; candidate-less tasks stay open
            # forever and do not count (the kernel's own open_mask
            # excludes them too)
            open_count = 0
            if it == seg_rounds and not (
                stall_limit > 0 and carried_stall >= stall_limit
            ):
                t_open = time.perf_counter()
                with _tracer.span("auction.open_count"):
                    open_count = int(
                        jnp.sum((state[3] < 0) & ~state[4] & task_feasible)
                    )
                open_s += time.perf_counter() - t_open
            waited = time.perf_counter() - t_wait
            segments += 1
            wait_s += waited
            if seg is not None:
                seg["attrs"].update(
                    rounds=it, rows=seg_rows, scans=seg_scans,
                    open_count=open_count, wait_ms=round(waited * 1e3, 3),
                )
        if open_count == 0:
            break
    if stats_out is not None:
        stats_out["segments"] = stats_out.get("segments", 0) + segments
        stats_out["wait_ms"] = stats_out.get("wait_ms", 0.0) + wait_s * 1e3
        stats_out["open_read_ms"] = (
            stats_out.get("open_read_ms", 0.0) + open_s * 1e3
        )
    # report the PHASE's total rounds in the state's counter slot (each
    # segment resets it; the ladder's rounds_total sums these) and the
    # ACCUMULATED stall so _report_stall sees breaker trips (the last
    # segment alone can never reach a limit > seg_rounds). Host scalars:
    # the callers' ``int()`` of them costs no trip to the device
    state = (np.int32(total_it),) + tuple(state[1:])
    return state, np.int32(carried_stall), total_rows, total_scans


def _report_stall(kind: str, stall, limit: int, stats_out: dict | None) -> None:
    """Record (and log) a binding-phase stall termination. One scalar
    readback — negligible next to the solve it describes."""
    stalled = bool(limit > 0 and int(stall) >= limit)
    if stats_out is not None:
        stats_out["stall_exit"] = stalled
        stats_out["stall_rounds"] = int(stall)
    if stalled:
        import logging

        logging.getLogger(__name__).info(
            "sparse auction (%s) stall-terminated after %d no-progress "
            "rounds; tail quality falls to greedy cleanup (stall_limit=0 "
            "opts out)",
            kind,
            int(stall),
        )


def assign_auction_sparse_warm(
    cand_provider: jax.Array,
    cand_cost: jax.Array,
    num_providers: int,
    price0: jax.Array,
    p4t0: jax.Array,
    eps: float = 0.02,
    max_iters: int = 20000,
    frontier: int = 4096,
    stall_limit: int = 64,
    stats_out: dict | None = None,
    retired0: jax.Array | None = None,
    with_state: bool = False,
    reserve0: float | None = None,
    joined0: np.ndarray | None = None,
    regime0: str | None = None,
    regime_out: dict | None = None,
) -> tuple[AssignResult, jax.Array]:
    """Incremental (delta-frontier) auction solve: SURVEY §7 hard part 4.

    The reference re-walks every task per heartbeat
    (crates/orchestrator/src/scheduler/mod.rs:26-74); a cold batch re-solve
    every population change would waste the batched win the same way. This
    warm start carries the auction's dual state across solves:

      ``price0`` [P]  final prices of the previous solve, a row per
                      provider row, live or not: a row no task lists
                      (one that left the pool, or that nobody can
                      use) is in no candidate list, so its price moves
                      nothing, sets no floor (:func:`_stranded`) and
                      enters no certificate; a row that comes back is
                      priced anew (``joined0``).
      ``p4t0``  [T]   previous assignment re-expressed in the new index
                      space (-1 for new/changed tasks). Must be injective
                      over >= 0.

    Seeded pairs violating eps-complementary-slackness under ``price0`` —
    including any whose seeded provider is no longer a candidate — are
    evicted by the same repair used between eps-scaling phases, so only the
    *delta frontier* (new tasks, freed providers, changed costs) re-enters
    the bidding. Forward auction from arbitrary initial prices and a
    partial eps-CS assignment terminates eps-optimal (Bertsekas), so the
    warm path's solution quality matches the cold path's final phase.

    ``retired0`` [T] carries the previous solve's retirement mask (third
    element of a ``with_state=True`` return). Retirement is a statement
    about PRICES ("best value below give-up"), and forward auctions never
    lower prices, so it stays valid across warm solves: without the mask
    every warm solve re-bids the unfillable tail until the stall breaker
    trips (512 wasted rounds per solve in a chain). Rows whose costs or
    candidates changed must be cleared by the caller (the CandidateCache
    rebuild does this wholesale). Retired-but-now-seatable pairs are still
    caught by the greedy cleanup, which ignores the mask.

    ``reserve0`` carries the previous solve's reserve, where that pool
    had a queue (fourth element of a ``with_state=True`` return, else
    None). The regime is read off the candidate graph at every solve
    (:func:`_queue_reserve`). A pool that has a queue now and carried
    none (it had no queue a tick ago, or its costs have left the
    anchor), or that carried one and has none now, has duals of another
    regime: the solve re-grounds them with the cold ladder, once, and
    carries neither their prices nor their retirement mask. In a pool
    with a queue prices are held between 0 and the reserve's level by
    the bidding itself, so the uniform shift below is left out, and
    "retired" means "waits at these prices", which every solve decides
    anew.

    ``regime0`` is the regime the carried duals were made in (a name of
    ``REGIMES``; None: the one ``reserve0`` implies), and
    ``regime_out["regime"]`` gets this solve's. A solve whose regime
    differs is a crossing, counted in ``stats_out`` (``regime_change``;
    ``cross_ms`` and ``cross_rounds``, the wall and the rounds of the
    re-ground where one runs, all rounds of its ladder; 0 elsewhere).
    A pool that LEAVES a queue carries duals made against the reserve,
    every seat priced where a waiting task would just not take it, and
    a reverse pass from there has to carry every freed provider down
    the whole landscape: 20,224 rounds, its budget, at 256 rows (8.2-12
    certificate), 37 s at 8,192. So the crossing re-grounds either way,
    as a pool that enters a queue always has, once, in an
    ``auction.regime`` span (``step="cross"``, ``from``, ``to``). A
    pool that came into the band from either side (``"band"``) runs its
    reverse pass there too, to the least seated price, for as long as
    it stays there, warm and in the ladder of a dual refresh, which is
    handed the carried regime (:func:`_forward_reverse`'s ``band``; CPU, 820
    providers: 4 idle beside a full pool, 0.0143 a seated task off the
    optimum without it; 8,192 on the chip, 83 idle, 0.048).

    ``joined0`` [P]: providers that came back since the carried prices
    were made (:func:`_unassign_unhappy`).

    Returns (AssignResult, final prices [P]), plus the final retirement
    mask [T] and the reserve (None without a queue) when
    ``with_state=True``.
    """
    # (lists held on the host go up while the host counts them)
    with _tracer.span("auction.upload", dispatch_only=True):
        lists = jnp.asarray(cand_provider), jnp.asarray(cand_cost)
    regime_out = {} if regime_out is None else regime_out
    if regime0 is None and reserve0 is not None:
        regime0 = "queue"
    reserve = _queue_reserve(
        cand_provider, cand_cost, num_providers, reserve0, stats_out,
        regime_out, regime0,
    )
    regime = regime_out.get("regime")
    crossed = None not in (regime0, regime) and regime0 != regime
    band = regime == "band"
    if (reserve is None) != (reserve0 is None) or (
        reserve is not None and reserve != reserve0
    ):
        # (the ladder counts the lists again, on the host: once, at the
        # tick a pool enters or leaves the regime)
        t0 = time.perf_counter()
        with _tracer.span(
            "auction.regime", step="cross",
            **{"from": regime0 or "none", "to": regime or "none"},
        ) if crossed else contextlib.nullcontext():
            out = assign_auction_sparse_scaled(
                cand_provider, cand_cost, num_providers, eps_end=eps,
                frontier=frontier, stall_limit=stall_limit,
                stats_out=stats_out, with_state=True, regime0=regime0,
            )
        if crossed and stats_out is not None:
            stats_out["regime_change"] = 1
            stats_out["cross_ms"] = round(
                (time.perf_counter() - t0) * 1e3, 3
            )
            stats_out["cross_rounds"] = int(
                stats_out.get("rounds_total", 0)
                + stats_out.get("transposed_rounds", 0)
            )
        return out if with_state else out[:2]
    cand_provider, cand_cost = lists
    with _tracer.span("auction.seed", eps=eps, dispatch_only=True):
        # a seed for a task with NO candidates would sail through the eps-CS
        # repair (vcur == v1 == -inf is not "unhappy") and emerge as an
        # infeasible pair in the final matching — drop such seeds outright
        task_has_cand = jnp.any(cand_provider >= 0, axis=1)
        p4t0 = jnp.where(task_has_cand, p4t0, -1)
        # Forward auctions only raise prices, and carried prices compound
        # across warm solves. The retirement floor is give_up =
        # -(2*max_cost + 10); keep the worst seeded value -max_cost - price
        # ABOVE the floor by SHIFTING all prices down uniformly until
        # max(price) <= max_cost + 5. A constant shift changes no value
        # difference, so it preserves the entire price landscape (who
        # outbids whom, who is unhappy) — unlike a clamp, which flattens the
        # top of the distribution, i.e. exactly the contended providers:
        # measured at 65k, min-clamping capped 65,535/65,536 prices and the
        # eps-CS repair then evicted 59,997 seeds for 655 churned tasks,
        # making "warm" a from-scratch fine-eps solve (the r4 0.2x
        # regression). Negative prices are fine: the auction only ever
        # compares price DIFFERENCES (values -cost - price and bid
        # increments), never absolute levels.
        finite_max = jnp.max(jnp.where(cand_provider >= 0, cand_cost, 0.0))
        price0 = jnp.asarray(price0, jnp.float32)
        if reserve is None:
            shift = jnp.maximum(jnp.max(price0) - (finite_max + 5.0), 0.0)
            price0 = price0 - shift
        owner0 = _invert(p4t0, num_providers)
        owner0, p4t0, price0 = _repair_seats(
            cand_provider, cand_cost, price0, owner0, p4t0, eps, reserve,
            joined0,
        )
        if retired0 is None:
            retired_seed = jnp.zeros(cand_cost.shape[0], bool)
        else:
            # a seeded assignment outranks a stale retirement flag
            retired_seed = jnp.asarray(retired0, bool) & (p4t0 < 0)
        state = (
            jnp.int32(0),
            jnp.asarray(price0, jnp.float32),
            owner0,
            p4t0,
            retired_seed,
        )
    state, stall, rounds = _forward_reverse(
        cand_provider, cand_cost, num_providers, state, eps,
        max_iters=max_iters, frontier=frontier,
        # the warm solve is a binding final phase: same 8x stall budget
        # as the scaled ladder's last phase (see
        # assign_auction_sparse_scaled), none where tasks wait (see
        # _queue_phase); stall_limit=0 opts out (run to max_iters)
        stall_limit=0 if reserve is not None else stall_limit * 8,
        stats_out=stats_out, transposed=[], reserve=reserve, band=band,
    )
    _report_stall("warm", stall, 0 if reserve is not None
                  else stall_limit * 8, stats_out)
    if crossed and stats_out is not None:
        stats_out["regime_change"] = 1
    if stats_out is not None:
        # same cost driver the cold ladder exposes: wall = rounds x
        # per-round kernel cost (see assign_auction_sparse_scaled)
        stats_out["rounds_total"] = rounds
    _, price, owner, p4t, retired = state
    with _tracer.span("auction.cleanup"):
        # (a pool with a queue has nobody to sweep: a provider its
        # queue pass leaves free is one no waiting task lists)
        if reserve is None:
            p4t = _greedy_cleanup(cand_provider, cand_cost, owner, p4t)
    _regime_stats(stats_out)
    res = AssignResult(p4t, _invert(p4t, num_providers))
    if with_state:
        return res, price, retired & (p4t < 0), reserve
    return res, price


def sinkhorn_potentials_sparse_np(
    cand_provider,
    cand_cost,
    num_providers: int,
    eps: float = 0.05,
    max_iters: int = 100,
    tol: float = 1e-3,
    f0=None,
    g0=None,
):
    """Pure-NumPy reference for the native sparse Sinkhorn engine
    (``native.sinkhorn_sparse_mt``): log-domain entropic OT restricted to
    the top-K candidate edges, one eps phase.

    This is the parity oracle, not a production path — it mirrors the C++
    engine's numerics exactly: balanced uniform marginals over rows/columns
    with >= 1 feasible edge (the ops/blocked.py convention), f (provider)
    update then g (task) update per iteration, float64 accumulation with
    potentials rounded to float32 after each update, edge sums accumulated
    in ascending-edge order (np.bincount's input order == the engine's CSR
    fill order), and the same provider-marginal convergence gate. Any
    remaining difference is libm exp/log ulps, bounded well under the 1e-6
    parity the tests assert.

    Returns (f [P] f32, g [T] f32, iterations_run, final_marginal_err).
    """
    import numpy as np

    cand_p = np.asarray(cand_provider, np.int32)
    cand_c = np.asarray(cand_cost, np.float32)
    T, K = cand_p.shape
    P = int(num_providers)
    valid = (cand_p >= 0) & (cand_p < P) & (cand_c < INFEASIBLE * 0.5)
    vflat = valid.ravel()
    t_idx = np.repeat(np.arange(T, dtype=np.int64), K)[vflat]
    p_idx = cand_p.ravel().astype(np.int64)[vflat]
    c = cand_c.ravel().astype(np.float64)[vflat]
    col_any = valid.any(axis=1)
    row_any = np.zeros(P, bool)
    row_any[p_idx] = True
    f = (
        np.zeros(P, np.float32)
        if f0 is None
        else np.array(f0, np.float32, copy=True)
    )
    g = (
        np.zeros(T, np.float32)
        if g0 is None
        else np.array(g0, np.float32, copy=True)
    )
    np_valid = int(row_any.sum())
    nt_valid = int(col_any.sum())
    if np_valid == 0 or nt_valid == 0:
        return f, g, 0, 0.0
    import math

    m = float(min(np_valid, nt_valid))
    log_a = math.log(m / np_valid)
    log_b = math.log(m / nt_valid)
    a_mass = m / np_valid
    inv_eps = 1.0 / float(eps)
    deps = float(eps)

    it = 0
    err = 0.0
    prev_err = float("inf")
    stall = 0
    while it < max_iters:
        it += 1
        # ---- f (provider) update: segmented logsumexp over edges by p
        val = (g.astype(np.float64)[t_idx] - c) * inv_eps
        mx = np.full(P, -np.inf)
        np.maximum.at(mx, p_idx, val)
        s = np.bincount(
            p_idx, weights=np.exp(val - mx[p_idx]), minlength=P
        )
        with np.errstate(divide="ignore", invalid="ignore"):
            lse = mx + np.log(s)
        f = np.where(
            row_any, (deps * (log_a - lse)), f.astype(np.float64)
        ).astype(np.float32)
        # ---- g (task) update: segmented logsumexp over edges by t
        val = (f.astype(np.float64)[p_idx] - c) * inv_eps
        mt = np.full(T, -np.inf)
        np.maximum.at(mt, t_idx, val)
        st = np.bincount(
            t_idx, weights=np.exp(val - mt[t_idx]), minlength=T
        )
        with np.errstate(divide="ignore", invalid="ignore"):
            lse_t = mt + np.log(st)
        g = np.where(
            col_any, (deps * (log_b - lse_t)), g.astype(np.float64)
        ).astype(np.float32)
        # ---- provider-marginal drift (task marginals exact after g)
        mass = np.bincount(
            p_idx,
            weights=np.exp(
                (f.astype(np.float64)[p_idx] + g.astype(np.float64)[t_idx] - c)
                * inv_eps
            ),
            minlength=P,
        )
        err = float(
            np.max(np.abs(mass[row_any] - a_mass) / a_mass)
        )
        if err <= tol:
            break
        # stagnation exit, mirroring the engine: infeasible uniform
        # marginals on a sparse support plateau above tol while the
        # potentials drift — two consecutive <0.5%-improvement checks
        # (after an 8-iteration settling window) stop the burn
        if it >= 8 and err >= 0.995 * prev_err:
            stall += 1
            if stall >= 2:
                break
        else:
            stall = 0
        prev_err = err
    return f, g, it, err


def assign_topk(
    ep: EncodedProviders,
    er: EncodedRequirements,
    weights: CostWeights | None = None,
    k: int = 64,
    tile: int = 1024,
    eps: float = 0.01,
    max_iters: int = 1000,
) -> AssignResult:
    """Full sparse pipeline: streaming candidate generation + sparse auction."""
    cand_p, cand_c = candidates_topk(ep, er, weights, k=k, tile=tile)
    return assign_auction_sparse(
        cand_p, cand_c, num_providers=ep.num, eps=eps, max_iters=max_iters
    )
