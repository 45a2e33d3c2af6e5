"""Task-sharded sparse auction over a device mesh.

The 1M x 1M configuration (BASELINE.md ladder #4/#5): candidate lists
[T, K] are sharded task-wise across the mesh (tasks outnumber everything
and their state is per-task), while the per-provider price/owner vectors
[P] are replicated and combined with max/min collectives each round —
P floats of ICI traffic per array, independent of T*K.

Round structure per device (mirrors ops/sparse.py's frontier auction):
  1. local frontier of open local tasks -> local bids
  2. local provider-side winner resolution (scatter-max / scatter-min)
  3. global combine: win_bid = pmax, win_task = pmin among max-bidders
     (task ids are globally formed as shard_offset + local index, so ties
     break identically to the single-device kernel)
  4. replicated price/owner update; each shard applies evictions/wins to
     the task rows it owns

With frontier >= T/D and retire=False this is the Jacobi schedule and is
exactly parity with the single-device sparse kernel — tested on the
virtual 8-device CPU mesh.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from protocol_tpu.obs.spans import TRACER as _tracer
from protocol_tpu.ops.assign import AssignResult, _invert
from protocol_tpu.ops.sparse import frontier_bids

_NEG = -1e18


def assign_auction_sparse_sharded(
    cand_provider: jax.Array,
    cand_cost: jax.Array,
    num_providers: int,
    mesh: Mesh,
    eps: float = 0.01,
    max_iters: int = 10000,
    frontier: int = 4096,
    retire: bool = True,
    axis: str = "p",
) -> AssignResult:
    """Sparse auction with tasks sharded over ``mesh`` axis ``axis``.

    cand_provider/cand_cost are [T, K] with T divisible by the mesh size.
    Returns a replicated AssignResult. A thin wrapper over the state-
    passing phase kernel with zero-initialized dual state — ONE shard_map
    body serves this, the eps ladder, and the warm solve, so the
    winner-resolution math the Jacobi parity guarantee rests on exists in
    exactly one sharded copy.
    """
    T, K = cand_cost.shape
    D = mesh.shape[axis]
    if T % D != 0:
        raise ValueError(f"T={T} not divisible by mesh size {D}; pad first")
    Pn = num_providers
    B = min(frontier, T // D)

    sharding = NamedSharding(mesh, P(axis, None))
    cand_provider = jax.device_put(cand_provider, sharding)
    cand_cost = jax.device_put(cand_cost, sharding)

    run = _build_sharded_phase(mesh, axis, Pn, B, int(max_iters), bool(retire))
    _price, _owner, p4t, _retired, _stall = run(
        cand_provider, cand_cost, jnp.float32(eps), jnp.int32(0),
        jnp.zeros(Pn, jnp.float32), jnp.full(Pn, -1, jnp.int32),
        jnp.full(T, -1, jnp.int32), jnp.zeros(T, bool),
    )
    return AssignResult(p4t, _invert(p4t, Pn))


@lru_cache(maxsize=64)
def _build_sharded_phase(
    mesh: Mesh,
    axis: str,
    Pn: int,
    B: int,
    max_iters: int,
    retire: bool,
):
    """The ONE sharded auction body: an eps PHASE that accepts carried
    dual state (prices, owner, assignment) and returns it, so the plain
    solve (zero state), the eps-scaling ladder, and the warm/incremental
    solve all compose over the mesh exactly like their single-device
    twins (ops/sparse._sparse_auction_phase). eps AND the stall limit
    ride in as traced scalars — one cached executable serves every rung
    of the ladder (limit <= 0 disables stall termination). Built once per
    static config and cached: a fresh closure per call would re-trace and
    re-compile the whole while_loop each solve (~9.5 s/call measured on
    the 8-dev CPU mesh)."""
    D = mesh.shape[axis]

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(), P(), P(), P(), P(), P()),
        out_specs=(P(), P(), P(), P(), P()),
        check_vma=False,
    )
    def run(cand_p_local, cand_c_local, eps, stall_limit, price0, owner0, p4t0,
            retired0):
        Tl, K = cand_p_local.shape
        T = Tl * D
        shard = lax.axis_index(axis)
        offset = (shard * Tl).astype(jnp.int32)
        p4t_local = lax.dynamic_slice_in_dim(p4t0, offset, Tl)
        retired_local = lax.dynamic_slice_in_dim(retired0, offset, Tl)

        cand_valid = cand_p_local >= 0
        value_base = jnp.where(cand_valid, -cand_c_local, _NEG)  # [Tl, K]
        task_feasible = jnp.any(cand_valid, axis=1)
        cand_safe = jnp.where(cand_valid, cand_p_local, 0)
        finite_max = lax.pmax(
            jnp.max(jnp.where(cand_valid, cand_c_local, 0.0)), axis
        )
        give_up = -(2.0 * finite_max + 10.0) if retire else jnp.float32(_NEG)

        def n_assigned(p4t_l):
            return lax.psum(jnp.sum(p4t_l >= 0), axis)

        def cond(loop):
            (it, price, owner, p4t_local, retired), best, stall = loop
            n_open = lax.psum(
                jnp.sum((p4t_local < 0) & task_feasible & ~retired), axis
            )
            go = (it < max_iters) & (n_open > 0)
            go &= (stall_limit <= 0) | (stall < stall_limit)
            return go

        def body(loop):
            state, best, stall = loop
            it, price, owner, p4t_local, retired = state
            open_mask = (p4t_local < 0) & task_feasible & ~retired

            f_idx = jnp.flatnonzero(open_mask, size=B, fill_value=Tl).astype(
                jnp.int32
            )
            f_ok = f_idx < Tl
            # shared bid math: bit-identical to the single-device kernel
            p1, v1, v2 = frontier_bids(
                cand_safe, value_base, price, f_idx, f_ok, K
            )

            newly_retired = f_ok & (v1 < give_up)
            retired = retired.at[jnp.where(newly_retired, f_idx, Tl)].set(
                True, mode="drop"
            )

            bidding = f_ok & ~newly_retired & (v1 > _NEG * 0.5)
            bid_amt = price[p1] + (v1 - v2) + eps
            tgt = jnp.where(bidding, p1, Pn)
            gtask = offset + f_idx  # global task ids of the frontier

            win_bid_l = jnp.full(Pn, _NEG).at[tgt].max(
                jnp.where(bidding, bid_amt, _NEG), mode="drop"
            )
            win_bid = lax.pmax(win_bid_l, axis)
            is_winner = bidding & (bid_amt >= win_bid[p1])
            win_task_l = jnp.full(Pn, T, jnp.int32).at[tgt].min(
                jnp.where(is_winner, gtask, T), mode="drop"
            )
            win_task = lax.pmin(win_task_l, axis)
            got_bid = (win_bid > _NEG * 0.5) & (win_task < T)

            evict_g = jnp.where(got_bid & (owner >= 0), owner, T)
            e_in = (evict_g >= offset) & (evict_g < offset + Tl)
            p4t_local = p4t_local.at[jnp.where(e_in, evict_g - offset, Tl)].set(
                -1, mode="drop"
            )
            p_idx = jnp.arange(Pn, dtype=jnp.int32)
            w_in = got_bid & (win_task >= offset) & (win_task < offset + Tl)
            p4t_local = p4t_local.at[jnp.where(w_in, win_task - offset, Tl)].set(
                jnp.where(w_in, p_idx, -1), mode="drop"
            )

            owner = jnp.where(got_bid, win_task, owner)
            price = jnp.where(got_bid, win_bid, price)
            n_now = n_assigned(p4t_local)
            improved = n_now > best
            best = jnp.maximum(best, n_now)
            stall = jnp.where(improved, 0, stall + 1)
            return (it + 1, price, owner, p4t_local, retired), best, stall

        state0 = (
            jnp.int32(0),
            jnp.asarray(price0, jnp.float32),
            jnp.asarray(owner0, jnp.int32),  # GLOBAL task ids
            p4t_local,
            retired_local,
        )
        loop0 = (state0, n_assigned(p4t_local), jnp.int32(0))
        (_, price, owner, p4t_local, retired_l), _best, stall = lax.while_loop(
            cond, body, loop0
        )
        return (
            price,
            owner,
            lax.all_gather(p4t_local, axis).reshape(T),
            lax.all_gather(retired_l, axis).reshape(T),
            stall,
        )

    return run


def _run_phase_sharded(
    mesh, axis, Pn, B0, max_iters, cand_p_dev, cand_c_dev,
    task_feasible, eps, stall_limit, price, owner, p4t,
    frontier_ladder, retired=None,
):
    """One sharded eps phase, optionally in fixed-size segments with the
    per-shard frontier executable direct-fit to the live open set — the
    mesh twin of ops.sparse._phase_adaptive's segments (same measured
    rationale: most rounds are tail eviction chains with a small open
    set; the single-device kernel fits its width inside the program,
    round by round, this one on the host between segments). The
    per-B executables come from the lru_cache'd builder, so the ladder
    costs at most a handful of compiles per config. The retirement mask
    threads through segments (and back to the caller) exactly like the
    single-device state tuple — resetting it per segment would re-open
    retired tasks mid-phase, a semantics drift from _phase_adaptive."""
    D = mesh.shape[axis]
    if retired is None:
        retired = jnp.zeros(p4t.shape[0], bool)
    if not frontier_ladder:
        run = _build_sharded_phase(mesh, axis, Pn, B0, int(max_iters), True)
        return run(
            cand_p_dev, cand_c_dev, jnp.float32(eps),
            jnp.int32(stall_limit), price, owner, p4t, retired,
        )
    seg_rounds = 256
    iters_left = int(max_iters)
    B = B0
    carried = 0
    floor = max(64, 512 // D)
    while iters_left > 0:
        run = _build_sharded_phase(mesh, axis, Pn, B, seg_rounds, True)
        price, owner, p4t, retired, stall = run(
            cand_p_dev, cand_c_dev, jnp.float32(eps), jnp.int32(0),
            price, owner, p4t, retired,
        )
        # the segment kernel reports only its own trailing stall; rounds
        # are bounded by seg_rounds so a whole-segment stall accumulates
        s = int(stall)
        carried = carried + seg_rounds if s >= seg_rounds else s
        iters_left -= seg_rounds
        open_count = int(jnp.sum((p4t < 0) & task_feasible & ~retired))
        if open_count == 0:
            break
        if stall_limit > 0 and carried >= int(stall_limit):
            break
        fit = floor
        while fit * D < open_count and fit < B:
            fit *= 2
        B = min(B, fit)
    return price, owner, p4t, retired, jnp.int32(carried)


def assign_auction_sparse_scaled_sharded(
    cand_provider: jax.Array,
    cand_cost: jax.Array,
    num_providers: int,
    mesh: Mesh,
    eps_start: float = 4.0,
    eps_end: float = 0.02,
    scale: float = 0.25,
    max_iters_per_phase: int = 4000,
    frontier: int = 4096,
    with_prices: bool = False,
    stall_limit: int = 64,
    axis: str = "p",
    stats_out: dict | None = None,
    frontier_ladder: bool = False,
    with_state: bool = False,
):
    """The eps-scaling ladder over the task-sharded phase kernel — the
    multi-chip twin of ops.sparse.assign_auction_sparse_scaled with the
    SAME phase discipline (disposable coarse phases whose retirements are
    reversed, eps-CS repair between rungs, binding final phase with an 8x
    stall budget, final greedy cleanup). Stage-B completeness at the 1M
    ladder shape = bidirectional candidates + this ladder over v5e-8
    (SCALING.md stage B2). The inter-phase repair and cleanup run on
    replicated arrays (O(T*K) elementwise — negligible next to the
    sharded while_loop they bracket)."""
    from protocol_tpu.ops.sparse import (
        _forward_reverse,
        _greedy_cleanup,
        _report_stall,
        _unassign_unhappy,
    )

    T, K = cand_cost.shape
    D = mesh.shape[axis]
    if T % D != 0:
        raise ValueError(f"T={T} not divisible by mesh size {D}; pad first")
    B = min(frontier, T // D)
    sharding = NamedSharding(mesh, P(axis, None))
    cand_p_dev = jax.device_put(cand_provider, sharding)
    cand_c_dev = jax.device_put(cand_cost, sharding)

    price = jnp.zeros(num_providers, jnp.float32)
    owner = jnp.full(num_providers, -1, jnp.int32)
    p4t = jnp.full(T, -1, jnp.int32)
    task_feasible = jnp.any(cand_provider >= 0, axis=1)
    eps = eps_start
    transposed: list = []
    while True:
        final = eps <= eps_end

        def run(state, eps=eps, final=final):
            # binding final phase gets 8x the disposable phases' stall
            # budget (same discipline as the single-device ladder)
            price, owner, p4t, retired, stall = _run_phase_sharded(
                mesh, axis, num_providers, B, max_iters_per_phase,
                cand_p_dev, cand_c_dev, task_feasible, eps,
                stall_limit * (8 if final else 1), *state[1:4],
                frontier_ladder,
            )
            # (the mesh kernel keeps no count of its frontier rows)
            return (jnp.int32(0), price, owner, p4t, retired), stall, 0

        # the reverse pass over the providers the phase left free runs
        # on replicated arrays, like the repair and the cleanup: the
        # single-device ladder's, call for call
        (_, price, owner, p4t, retired), stall, _ = _forward_reverse(
            run, cand_provider, cand_cost, num_providers,
            (None, price, owner, p4t, None), eps, None, transposed,
        )
        if final:
            _report_stall("scaled-sharded", stall, stall_limit * 8, stats_out)
            break
        eps = max(eps * scale, eps_end)
        owner, p4t = _unassign_unhappy(
            cand_provider, cand_cost, price, owner, p4t, eps
        )
        # coarse-phase retirement was only a circuit breaker; each
        # _run_phase_sharded call starts from a fresh retired=0 mask, so
        # un-retire needs no explicit step here — only the binding
        # phase's retirement survives into the returned state
    p4t = _greedy_cleanup(cand_provider, cand_cost, owner, p4t)
    res = AssignResult(p4t, _invert(p4t, num_providers))
    if with_state:
        return res, price, retired & (p4t < 0)
    if with_prices:
        return res, price
    return res


def assign_auction_sparse_warm_sharded(
    cand_provider: jax.Array,
    cand_cost: jax.Array,
    num_providers: int,
    mesh: Mesh,
    price0: jax.Array,
    p4t0: jax.Array,
    eps: float = 0.02,
    max_iters: int = 20000,
    frontier: int = 4096,
    stall_limit: int = 64,
    axis: str = "p",
    stats_out: dict | None = None,
    frontier_ladder: bool = False,
    retired0: jax.Array | None = None,
    with_state: bool = False,
) -> tuple[AssignResult, jax.Array]:
    """Incremental (delta-frontier) solve over the mesh: the multi-chip
    twin of ops.sparse.assign_auction_sparse_warm — same seed hygiene
    (candidate-less seeds dropped, carried prices downshifted below the
    retirement floor), same eps-CS repair admission, one binding sharded
    phase, greedy cleanup, same optional retirement carry (``retired0`` /
    ``with_state`` — see the single-device docstring for why retirement
    is dual state). Returns (AssignResult, final prices [P]), plus the
    final retirement mask when ``with_state=True``."""
    from protocol_tpu.ops.sparse import (
        _forward_reverse,
        _greedy_cleanup,
        _report_stall,
        _unassign_unhappy,
    )

    T, K = cand_cost.shape
    D = mesh.shape[axis]
    if T % D != 0:
        raise ValueError(f"T={T} not divisible by mesh size {D}; pad first")

    task_has_cand = jnp.any(cand_provider >= 0, axis=1)
    p4t0 = jnp.where(task_has_cand, jnp.asarray(p4t0, jnp.int32), -1)
    # uniform downshift, NOT a clamp — must stay bit-identical to the
    # single-device seed hygiene (see ops.sparse.assign_auction_sparse_warm
    # for the measured clamp pathology)
    finite_max = jnp.max(jnp.where(cand_provider >= 0, cand_cost, 0.0))
    price0 = jnp.asarray(price0, jnp.float32)
    price0 = price0 - jnp.maximum(jnp.max(price0) - (finite_max + 5.0), 0.0)
    owner0 = _invert(p4t0, num_providers)
    owner0, p4t0 = _unassign_unhappy(
        cand_provider, cand_cost, price0, owner0, p4t0, eps
    )

    if retired0 is None:
        retired_seed = jnp.zeros(T, bool)
    else:
        retired_seed = jnp.asarray(retired0, bool) & (p4t0 < 0)
    sharding = NamedSharding(mesh, P(axis, None))
    cand_p_dev = jax.device_put(cand_provider, sharding)
    cand_c_dev = jax.device_put(cand_cost, sharding)

    def run(state):
        price, owner, p4t, retired, stall = _run_phase_sharded(
            mesh, axis, num_providers, min(frontier, T // D), max_iters,
            cand_p_dev, cand_c_dev, task_has_cand, eps,
            stall_limit * 8, *state[1:4], frontier_ladder,
            retired=state[4],
        )
        return (jnp.int32(0), price, owner, p4t, retired), stall, 0

    (_, price, owner, p4t, retired), stall, _ = _forward_reverse(
        run, cand_provider, cand_cost, num_providers,
        (None, price0, owner0, p4t0, retired_seed), eps, None, [],
    )
    _report_stall("warm-sharded", stall, stall_limit * 8, stats_out)
    p4t = _greedy_cleanup(cand_provider, cand_cost, owner, p4t)
    res = AssignResult(p4t, _invert(p4t, num_providers))
    if with_state:
        return res, price, retired & (p4t < 0)
    return res, price


def _merge_rev_pools(
    rev_c_all: jax.Array, rev_t_all: jax.Array, r: int
) -> tuple[jax.Array, jax.Array]:
    """Final cross-shard pool merge: best r of the D per-shard [P, r]
    pools (associativity up to jitter-decorrelated ties; same multiset
    as the sequential fold). ONE home on purpose — the from-scratch
    sharded generation and the warm-path reverse repair must run the
    exact same merge ops or the repaired==regen oracle contract quietly
    decays into "usually identical". Returns (rev_t [P, r], rev_c)."""
    from protocol_tpu.ops.cost import INFEASIBLE

    D, Pn, _ = rev_c_all.shape
    rev_c_cat = jnp.moveaxis(rev_c_all, 0, 1).reshape(Pn, D * r)
    rev_t_cat = jnp.moveaxis(rev_t_all, 0, 1).reshape(Pn, D * r)
    neg_c, m = lax.top_k(-rev_c_cat, r)
    rev_c = -neg_c
    rev_t = jnp.take_along_axis(rev_t_cat, m, axis=1)
    rev_t = jnp.where(rev_c < INFEASIBLE * 0.5, rev_t, -1)
    return rev_t, rev_c


def candidates_topk_bidir_sharded(
    ep,
    er,
    weights=None,
    *,
    mesh: Mesh,
    k: int = 64,
    tile: int = 1024,
    reverse_r: int = 8,
    extra: int = 16,
    axis: str = "p",
    approx_recall: float | None = None,
    with_parts: bool = False,
):
    """Task-sharded bidirectional candidate generation — the mesh twin of
    ops.sparse.candidates_topk_bidir, and the stage where multi-chip
    actually PAYS: generation is the measured wall-clock dominator of a
    cold solve (793 s gen vs 32 s solve at 65k CPU, SCALING.md) and it is
    embarrassingly parallel over task tiles. Each device streams its own
    [P, tile] cost blocks (providers replicated: P x ~14 f32 columns,
    megabytes at 1M) with ZERO per-round collectives; the only
    communication in the whole pass is one all_gather of the [T, k]
    forward lists and the [D, P, r] reverse pools at the end — so v5e-8
    speedup on this stage is ~linear in D, unlike the solve kernel whose
    every round all-reduces the [P] price/owner vectors (see the ICI cost
    model in SCALING.md).

    Parity: the forward tile step is ops.sparse._forward_tile_select
    (shared verbatim — jitter offsets arranged so each shard computes the
    exact global tile it would own single-device), and the reverse pools
    keep the tile-pooled contract (per-tile top-ceil(r/n_tiles_GLOBAL),
    best r of the pool). Pool merging is associative up to float ties,
    which the tie jitter already decorrelates — asserted bit-exact in
    tests/test_parallel_sparse.py.

    ``with_parts=True`` additionally returns the un-merged structure
    parts — (merged_p, merged_c, fwd_p [T, k], fwd_c [T, k],
    pool_t [P, n_tiles*rt], pool_c [P, n_tiles*rt]) — the persistent
    state the warm-path repair (:func:`repair_topk_bidir_sharded`)
    maintains across ticks. The pools are the RAW per-tile reverse
    contributions in global tile order (pre-fold, no -1 masking, fully
    D-invariant: a contribution depends only on the provider's own cost
    row over that tile and the global jitter grid); the folded
    rev_t/rev_c are re-derived from them by replaying the per-shard
    fold, which is what makes reverse repair O(churned provider-tile
    blocks) instead of O(|scope| * T).
    """
    from protocol_tpu.ops.cost import INFEASIBLE, CostWeights
    from protocol_tpu.ops.sparse import (
        _forward_tile_select,
        merge_reverse_candidates,
    )

    if weights is None:
        weights = CostWeights()
    T = er.cpu_cores.shape[0]
    D = mesh.shape[axis]
    if T % D != 0:
        raise ValueError(f"T={T} not divisible by mesh size {D}; pad first")
    Tl = T // D
    if Tl % tile != 0:
        raise ValueError(
            f"local task count {Tl} not divisible by tile={tile}"
        )
    n_tiles_global = T // tile
    Pn = int(ep.gpu_count.shape[0])
    k = min(k, Pn)
    r = min(reverse_r, T)
    rt = max(1, -(-r // n_tiles_global))  # per-tile pool contribution

    er_sharded = jax.tree.map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P(axis))), er
    )
    gen = _build_sharded_gen(
        mesh, axis, dataclasses.astuple(weights), Pn, Tl, k, tile, r, rt,
        approx_recall, jax.tree.structure(er), with_parts,
    )
    if with_parts:
        cand_p, cand_c, rev_c_all, rev_t_all, tile_t_all, tile_c_all = gen(
            ep, er_sharded
        )
    else:
        cand_p, cand_c, rev_c_all, rev_t_all = gen(ep, er_sharded)
    rev_t, rev_c = _merge_rev_pools(rev_c_all, rev_t_all, r)
    merged_p, merged_c = merge_reverse_candidates(
        cand_p, cand_c, rev_t, rev_c, extra=extra
    )
    if with_parts:
        # [n_tiles, P, rt] in global tile order -> [P, n_tiles*rt]
        pool_t = jnp.moveaxis(tile_t_all, 0, 1).reshape(
            Pn, n_tiles_global * rt
        )
        pool_c = jnp.moveaxis(tile_c_all, 0, 1).reshape(
            Pn, n_tiles_global * rt
        )
        return merged_p, merged_c, cand_p, cand_c, pool_t, pool_c
    return merged_p, merged_c


@lru_cache(maxsize=32)
def _build_sharded_gen(
    mesh: Mesh,
    axis: str,
    weights_tuple: tuple,
    Pn: int,
    Tl: int,
    k: int,
    tile: int,
    r: int,
    rt: int,
    approx_recall,
    er_treedef,
    with_pools: bool = False,
):
    """Cached builder for the sharded generation executable (same
    re-trace rationale as _build_sharded_phase: a fresh jit+shard_map
    closure per call would recompile the whole scan each rebuild).
    ``with_pools`` additionally streams out each tile's raw reverse
    contribution [n_tiles, P, rt] (shard-major concatenation == global
    tile order) — the persistent pre-fold state the warm repair keeps."""
    from protocol_tpu.ops.cost import INFEASIBLE, CostWeights
    from protocol_tpu.ops.sparse import _forward_tile_select

    weights = CostWeights(*weights_tuple)
    D = mesh.shape[axis]
    er_specs = jax.tree.unflatten(
        er_treedef, [P(axis)] * er_treedef.num_leaves
    )
    out_specs = (P(axis, None), P(axis, None), P(axis, None, None),
                 P(axis, None, None))
    if with_pools:
        out_specs = out_specs + (P(axis, None, None), P(axis, None, None))

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), er_specs),
        out_specs=out_specs,
        check_vma=False,
    )
    def gen(ep_rep, er_local):
        shard = lax.axis_index(axis)
        offset = (shard * Tl).astype(jnp.uint32)

        def step(carry, t0):
            rev_c0, rev_t0 = carry
            # shared forward step: jitter keyed on the GLOBAL task index
            # via task_offset, so each shard produces exactly the columns
            # the single-device scan would at its global tile
            with jax.named_scope("gen.forward"):
                provider, cost_k, cost = _forward_tile_select(
                    ep_rep, er_local, weights, t0, tile, k,
                    None, offset, approx_recall,
                )
            with jax.named_scope("gen.reverse"):
                tid = offset.astype(jnp.int32) + t0 + jnp.arange(tile, dtype=jnp.int32)
                if rt == 1:
                    j = jnp.argmin(cost, axis=1)
                    tile_c = jnp.take_along_axis(cost, j[:, None], axis=1)
                    tile_t = tid[j][:, None]
                else:
                    neg, j = lax.top_k(-cost, rt)
                    tile_c = -neg
                    tile_t = tid[j]
                merged_c = jnp.concatenate([rev_c0, tile_c], axis=1)
                merged_t = jnp.concatenate([rev_t0, tile_t], axis=1)
                neg_c, m = lax.top_k(-merged_c, r)
            ys = (provider, cost_k)
            if with_pools:
                ys = ys + (tile_t, tile_c)
            return (-neg_c, jnp.take_along_axis(merged_t, m, axis=1)), ys

        carry0 = (
            jnp.full((Pn, r), jnp.float32(INFEASIBLE)),
            jnp.full((Pn, r), -1, jnp.int32),
        )
        (rev_c_l, rev_t_l), ys = lax.scan(
            step, carry0, jnp.arange(Tl // tile, dtype=jnp.int32) * tile
        )
        cand_p, cand_c = ys[0], ys[1]
        out = (
            cand_p.reshape(Tl, k),
            cand_c.reshape(Tl, k),
            rev_c_l[None],  # [1, P, r] -> stacked [D, P, r] across shards
            rev_t_l[None],
        )
        if with_pools:
            # [ntl, P, rt] local tiles; shard-axis concat of the leading
            # dim reassembles the global tile order
            out = out + (ys[2], ys[3])
        return out

    return gen


# --------------------------------------------------------------------
# warm-path candidate repair (ISSUE 18): churn-masked recompute of the
# persistent bidirectional structure, bit-identical to a from-scratch
# candidates_topk_bidir_sharded pass on the current features
# --------------------------------------------------------------------

# above-INFEASIBLE sentinel: padded rows/columns in the gathered repair
# batches must never win a selection or flag an enter-mask cell
_PAD_COST = 1e18


def _pow2_pad(n: int, lo: int = 8) -> int:
    """Next power of two >= max(n, lo): bounds the set of distinct
    compiled shapes the repair kernels can request (each pad size is one
    lru_cache'd executable, like the phase builders' B ladder)."""
    p = lo
    while p < n:
        p *= 2
    return p


def _gather_rows(tree, idx: "object", pad: int):
    """Host-side gather of pytree rows with clamp-padding: rows beyond
    ``idx`` repeat row 0 and are discarded by the caller's scatter."""
    import numpy as np

    full = np.zeros(pad, np.int64)
    full[: len(idx)] = idx
    return jax.tree.map(lambda a: jnp.asarray(np.asarray(a)[full]), tree)


@lru_cache(maxsize=32)
def _build_repair_enter(
    weights_tuple: tuple, tile: int, n_tiles: int, dp_pad: int,
    ep_treedef, er_treedef,
):
    """Forward enter-scan kernel: do any of the DIRTY providers' fresh
    (jittered) costs beat a stored row's k-th selection value? Rows they
    do — plus rows that LIST a dirty provider, handled host-side — are
    exactly the rows whose forward top-k can differ from a from-scratch
    pass; everything else keeps bit-identical stored entries. Streams
    [dp_pad, tile] cost blocks over the full task axis (the same memory
    envelope as generation), jitter keyed on explicit GLOBAL ids so a
    gathered provider subset lands on the exact grid the full pass
    applied. ``<=`` on the threshold over-flags exact float ties — the
    flagged row is then recomputed exactly, so ties cost a row of work,
    never a bit of drift."""
    from protocol_tpu.ops.cost import INFEASIBLE, CostWeights, cost_matrix
    from protocol_tpu.ops.cost import tie_jitter_ids
    from protocol_tpu.ops.sparse import _slice_requirements

    weights = CostWeights(*weights_tuple)

    @jax.named_scope("repair.enter_scan")
    def enter_scan(ep_dirty, p_ids, p_valid, er, thresh):
        def step(_, t0):
            r_tile = _slice_requirements(er, t0, tile)
            cost, _m = cost_matrix(ep_dirty, r_tile, weights)
            jit_grid = tie_jitter_ids(
                p_ids, t0.astype(jnp.uint32) + jnp.arange(tile, dtype=jnp.uint32)
            )
            cost = jnp.where(cost < INFEASIBLE * 0.5, cost + jit_grid, cost)
            cost = jnp.where(p_valid[:, None], cost, jnp.float32(_PAD_COST))
            th = lax.dynamic_slice_in_dim(thresh, t0, tile)
            hit = (cost <= th[None, :]) & (cost < INFEASIBLE * 0.5)
            return None, jnp.any(hit, axis=0)

        _, enter = lax.scan(
            step, None, jnp.arange(n_tiles, dtype=jnp.int32) * tile
        )
        return enter.reshape(n_tiles * tile)

    return jax.jit(enter_scan)


@lru_cache(maxsize=32)
def _build_repair_forward(
    weights_tuple: tuple, Pn: int, kk: int, c_pad: int,
    ep_treedef, er_rows_treedef,
):
    """Forward row recompute: the exact per-row selection of generation
    (_forward_tile_select with provider_offset=None) on a GATHERED task
    subset — full [Pn, c_pad] jittered cost block, stable lax.top_k, the
    same -1 erasure of infeasible slots. A row's forward list depends on
    nothing but its own cost column, so recomputed rows are bit-identical
    to the columns a from-scratch pass would produce regardless of tile
    or shard placement. Also returns the fresh cost block masked to the
    DIRTY task columns (_PAD_COST elsewhere) — the orchestrator folds it
    into the per-(provider, tile) minima that drive the reverse
    enter-mask."""
    from protocol_tpu.ops.cost import INFEASIBLE, CostWeights, cost_matrix
    from protocol_tpu.ops.cost import tie_jitter_ids

    weights = CostWeights(*weights_tuple)

    @jax.named_scope("repair.forward_rows")
    def forward_rows(ep, er_rows, t_ids, col_dirty):
        cost, _m = cost_matrix(ep, er_rows, weights)  # [Pn, c_pad]
        jit_grid = tie_jitter_ids(jnp.arange(Pn, dtype=jnp.uint32), t_ids)
        cost = jnp.where(cost < INFEASIBLE * 0.5, cost + jit_grid, cost)
        neg_sel, idx = lax.top_k(-cost.T, kk)  # [c_pad, kk] best first
        sel_k = -neg_sel
        provider = jnp.where(
            sel_k < INFEASIBLE * 0.5, idx.astype(jnp.int32), -1
        )
        cost_k = jnp.take_along_axis(cost.T, idx, axis=1)
        dirty_cost = jnp.where(
            col_dirty[None, :], cost, jnp.float32(_PAD_COST)
        )
        return provider, cost_k, dirty_cost

    return jax.jit(forward_rows)


@lru_cache(maxsize=32)
def _build_repair_enter_sharded(
    mesh: Mesh, axis: str, weights_tuple: tuple, Tl: int, tile: int,
    dp_pad: int, ep_treedef, er_treedef,
):
    """Mesh twin of _build_repair_enter: the enter-scan is the one
    repair stage whose work is O(dirty_providers * T) rather than
    O(churn), so at scale it shards over task tiles exactly like
    generation — each shard streams its local [dp_pad, tile] blocks
    (jitter keyed on GLOBAL task ids via the shard offset) and emits its
    [Tl] slice of the enter mask with zero per-round collectives."""
    from protocol_tpu.ops.cost import INFEASIBLE, CostWeights, cost_matrix
    from protocol_tpu.ops.cost import tie_jitter_ids
    from protocol_tpu.ops.sparse import _slice_requirements

    weights = CostWeights(*weights_tuple)
    er_specs = jax.tree.unflatten(
        er_treedef, [P(axis)] * er_treedef.num_leaves
    )

    @jax.named_scope("repair.enter_scan")
    def enter_scan_sharded(ep_dirty, p_ids, p_valid, er_local, thresh_local):
        shard = lax.axis_index(axis)
        offset = (shard * Tl).astype(jnp.uint32)

        def step(_, t0):
            r_tile = _slice_requirements(er_local, t0, tile)
            cost, _m = cost_matrix(ep_dirty, r_tile, weights)
            jit_grid = tie_jitter_ids(
                p_ids,
                offset + t0.astype(jnp.uint32)
                + jnp.arange(tile, dtype=jnp.uint32),
            )
            cost = jnp.where(cost < INFEASIBLE * 0.5, cost + jit_grid, cost)
            cost = jnp.where(p_valid[:, None], cost, jnp.float32(_PAD_COST))
            th = lax.dynamic_slice_in_dim(thresh_local, t0, tile)
            hit = (cost <= th[None, :]) & (cost < INFEASIBLE * 0.5)
            return None, jnp.any(hit, axis=0)

        _, enter = lax.scan(
            step, None, jnp.arange(Tl // tile, dtype=jnp.int32) * tile
        )
        return enter.reshape(Tl)

    return jax.jit(
        jax.shard_map(
            enter_scan_sharded,
            mesh=mesh,
            in_specs=(P(), P(), P(), er_specs, P(axis)),
            out_specs=P(axis),
            check_vma=False,
        )
    )


@lru_cache(maxsize=32)
def _build_repair_tile(
    weights_tuple: tuple, tile: int, rt: int, s_pad: int,
    ep_rows_treedef, er_tile_treedef,
):
    """Per-tile reverse CONTRIBUTION recompute: one tile's raw
    top-``rt`` per gathered provider — the exact per-tile half of the
    generation fold (same cost ops, same global-id jitter, same
    argmin/top_k branch), nothing folded. A contribution (p, j) depends
    on nothing but provider p's own cost row over tile j, so recomputed
    blocks are bit-identical to the blocks a from-scratch pass emits
    regardless of batch membership or device count; the fold itself is
    replayed over the persisted pools by _build_repair_refold. No -1
    masking here: pools persist raw (infeasible entries keep their
    INFEASIBLE+jitter cost), matching the gen-side emission."""
    from protocol_tpu.ops.cost import INFEASIBLE, CostWeights, cost_matrix
    from protocol_tpu.ops.cost import tie_jitter_ids

    weights = CostWeights(*weights_tuple)

    @jax.named_scope("repair.tile_contrib")
    def tile_contrib(ep_rows, p_ids, er_tile, t0):
        cost, _m = cost_matrix(ep_rows, er_tile, weights)  # [s_pad, tile]
        jit_grid = tie_jitter_ids(
            p_ids,
            t0.astype(jnp.uint32) + jnp.arange(tile, dtype=jnp.uint32),
        )
        cost = jnp.where(cost < INFEASIBLE * 0.5, cost + jit_grid, cost)
        tid = t0.astype(jnp.int32) + jnp.arange(tile, dtype=jnp.int32)
        if rt == 1:
            j = jnp.argmin(cost, axis=1)
            tile_c = jnp.take_along_axis(cost, j[:, None], axis=1)
            tile_t = tid[j][:, None]
        else:
            neg, j = lax.top_k(-cost, rt)
            tile_c = -neg
            tile_t = tid[j]
        return tile_t, tile_c

    return jax.jit(tile_contrib)


@lru_cache(maxsize=32)
def _build_repair_refold(
    Pn: int, n_tiles: int, rt: int, r: int, d_fold: int,
):
    """Fold replay: derive the per-provider best-r reverse edges from
    the persisted [P, n_tiles*rt] contribution pools by running the
    EXACT fold the from-scratch pass runs at ``d_fold`` devices — each
    fold lane owns n_tiles/d_fold consecutive tiles, folds them
    sequentially (concat carry-first, stable top_k, INFEASIBLE/-1
    init), and the lanes meet in _merge_rev_pools, the same final merge
    generation uses. Pure structure ops on ~P*(r + n_tiles*rt) floats —
    milliseconds at any churn, which is what buys reverse repair its
    O(churned blocks) cost. top_k here is selection, not arithmetic, so
    jit fusion cannot perturb a bit."""
    from protocol_tpu.ops.cost import INFEASIBLE

    ntl = n_tiles // d_fold

    @jax.named_scope("repair.refold")
    def refold(pool_t, pool_c):
        # [P, n_tiles*rt] tile order -> [ntl, D, P, rt] scan layout
        pt = jnp.moveaxis(
            pool_t.reshape(Pn, d_fold, ntl, rt), (1, 2), (1, 0)
        )
        pc = jnp.moveaxis(
            pool_c.reshape(Pn, d_fold, ntl, rt), (1, 2), (1, 0)
        )

        def step(carry, x):
            rev_c0, rev_t0 = carry  # [D, P, r]
            tile_t, tile_c = x      # [D, P, rt]
            merged_c = jnp.concatenate([rev_c0, tile_c], axis=-1)
            merged_t = jnp.concatenate([rev_t0, tile_t], axis=-1)
            neg_c, m = lax.top_k(-merged_c, r)
            return (-neg_c, jnp.take_along_axis(merged_t, m, axis=-1)), None

        carry0 = (
            jnp.full((d_fold, Pn, r), jnp.float32(INFEASIBLE)),
            jnp.full((d_fold, Pn, r), -1, jnp.int32),
        )
        (rev_c_all, rev_t_all), _ = lax.scan(step, carry0, (pt, pc))
        return _merge_rev_pools(rev_c_all, rev_t_all, r)

    return jax.jit(refold)


def repair_topk_bidir_sharded(
    ep,
    er,
    weights=None,
    *,
    fwd_p,
    fwd_c,
    pool_t,
    pool_c,
    dirty_p,
    dirty_t,
    reverse_r: int = 8,
    mesh: Mesh | None = None,
    tile: int = 1024,
    extra: int = 16,
    axis: str = "p",
    pad_floors: dict | None = None,
):
    """Churn-masked repair of the persistent bidirectional candidate
    structure — the JAX twin of the native engine's
    ``repair_topk_candidates_mt``, honoring the same oracle contract:
    the repaired (fwd, pools, merged) structure is bit-identical to a
    from-scratch :func:`candidates_topk_bidir_sharded` pass on the
    CURRENT features, at every device count (exactness argued per
    kernel above; cross-D identity is the tile-pooled D-invariance the
    generation path already certifies).

    Scope derivation (host-side numpy over the stored structure — no
    full cost pass anywhere):

      forward rows R        = dirty tasks
                            ∪ rows listing a dirty provider in their top-k
                            ∪ rows a dirty provider's fresh cost can enter
                              (enter-scan kernel vs the stored k-th value)
      reverse blocks (p, j) = all tiles of dirty providers
                            ∪ blocks whose contribution lists a dirty task
                            ∪ blocks a dirty task's fresh cost can enter
                              (per-tile min fresh dirty cost vs the
                              block's worst kept contribution)

    Rows in R and flagged (provider, tile) blocks are recomputed
    EXACTLY (full selection on their own cost columns/blocks);
    everything else keeps stored bits, and the folded reverse edges are
    re-derived by REPLAYING the generation fold over the pools
    (_build_repair_refold) — so reverse repair costs O(flagged blocks *
    tile), not O(|provider scope| * T). The block enter-test carries no
    feasibility guard on purpose: a cell flipping feasible->infeasible
    still lands INFEASIBLE+jitter in the cost grid and can displace an
    infeasible-tail entry of a half-empty block in a fresh pass, and
    bit-identity owes those tail bits too. Leave-promotion inside a
    tile cannot change an unflagged block: a tilemate promoted by a
    dirty task's exit requires the dirty task to have been IN the
    block's top-rt — which flags containment.

    ``ep``/``er`` carry the CURRENT features; stored arrays are NOT
    mutated (fresh arrays returned). ``dirty_p``/``dirty_t`` are global
    row indices. Unsupported generation modes (``provider_offset``,
    ``approx_recall``) have no repair twin — callers on those modes
    keep the regen path. Returns ``(cand_p, cand_c, fwd_p, fwd_c,
    pool_t, pool_c, stats)`` with honest scope counters
    (``repair_rows``, ``repair_providers``, ``repair_blocks``,
    ``visited_cells_frac`` — the fraction of the P*T cost grid
    re-evaluated; the refold and final merge are structure ops both
    paths pay and are excluded).

    ``pad_floors`` is the pad-bucket ratchet: a mapping of kernel
    family ("enter" / "forward" / "tile") to the largest pow-2 pad that
    family has already compiled for. Each gather pads to at least that
    floor, so the jit compile-key set is MONOTONE across a warm chain —
    a later tick can never fall into a smaller, never-traced bucket and
    stall on the tracer mid-tick. Exactness is unaffected: every repair
    kernel is per-row (no cross-row reduction), pad rows are clamp
    copies, and write-back slices ``[:n]``, so a row's bits do not
    depend on the batch pad. The new high-water marks come back in
    ``stats["pad_hw"]`` for the caller to persist alongside the parts;
    the wasted pad work is bounded by one pow-2 bucket and the floor
    only rises log-many times over a process lifetime."""
    import numpy as np

    from protocol_tpu.ops.cost import CostWeights
    from protocol_tpu.ops.sparse import merge_reverse_candidates

    if weights is None:
        weights = CostWeights()
    wtuple = dataclasses.astuple(weights)
    Pn = int(ep.gpu_count.shape[0])
    T = int(er.cpu_cores.shape[0])
    if T % tile != 0:
        raise ValueError(f"T={T} not divisible by tile={tile}")
    n_tiles = T // tile
    fwd_p = np.asarray(fwd_p)
    fwd_c = np.asarray(fwd_c)
    pool_t_np = np.array(pool_t, copy=True)
    pool_c_np = np.array(pool_c, copy=True)
    kk = fwd_p.shape[1]
    r = min(reverse_r, T)
    rt = max(1, -(-r // n_tiles))
    if pool_t_np.shape[1] != n_tiles * rt:
        raise ValueError(
            f"pool width {pool_t_np.shape[1]} != n_tiles*rt "
            f"({n_tiles}*{rt}) for reverse_r={reverse_r}"
        )
    dirty_p = np.asarray(dirty_p, np.int64).ravel()
    dirty_t = np.asarray(dirty_t, np.int64).ravel()
    ep_treedef = jax.tree.structure(ep)
    er_treedef = jax.tree.structure(er)

    pad_hw = dict(pad_floors) if pad_floors else {}

    def _padq(kind: str, n: int) -> int:
        p = max(_pow2_pad(n), pad_hw.get(kind, 0))
        pad_hw[kind] = p
        return p

    use_mesh = (
        mesh is not None and T % mesh.shape[axis] == 0
        and (T // mesh.shape[axis]) % tile == 0
    )

    # four host-sequenced stages: each a span closed at the read-back
    # that already ends it, its wall beside it in the stats
    took: dict = {}

    # ---- forward scope
    with _tracer.stage("repair.enter_scan", took, "rep_enter_ms"):
        rows = np.zeros(T, bool)
        rows[dirty_t] = True
        enter_count = 0
        if dirty_p.size:
            rows |= np.isin(fwd_p, dirty_p).any(axis=1)
            dp_pad = _padq("enter", dirty_p.size)
            ep_dirty = _gather_rows(ep, dirty_p, dp_pad)
            p_ids = np.zeros(dp_pad, np.uint32)
            p_ids[: dirty_p.size] = dirty_p
            p_valid = np.zeros(dp_pad, bool)
            p_valid[: dirty_p.size] = True
            if use_mesh:
                D = mesh.shape[axis]
                run = _build_repair_enter_sharded(
                    mesh, axis, wtuple, T // D, tile, dp_pad,
                    ep_treedef, er_treedef,
                )
                er_dev = jax.tree.map(
                    lambda a: jax.device_put(
                        a, NamedSharding(mesh, P(axis))
                    ), er,
                )
                thresh = jax.device_put(
                    jnp.asarray(fwd_c[:, -1]), NamedSharding(mesh, P(axis))
                )
            else:
                run = _build_repair_enter(
                    wtuple, tile, n_tiles, dp_pad, ep_treedef, er_treedef,
                )
                er_dev = jax.tree.map(jnp.asarray, er)
                thresh = jnp.asarray(fwd_c[:, -1])
            enter = np.asarray(
                run(
                    ep_dirty, jnp.asarray(p_ids), jnp.asarray(p_valid),
                    er_dev, thresh,
                )
            )
            enter_count = int(enter.sum())
            rows |= enter
        R = np.flatnonzero(rows)

    # ---- forward recompute (chunked at the generation tile's memory
    # envelope) + per-(provider, tile) dirty-cost minima for the
    # reverse block enter-mask
    with _tracer.stage("repair.forward_rows", took, "rep_forward_ms"):
        fwd_p_new, fwd_c_new = fwd_p, fwd_c
        min_dirty_tile = np.full((Pn, n_tiles), _PAD_COST, np.float32)
        is_dirty_t = np.zeros(T, bool)
        is_dirty_t[dirty_t] = True
        if R.size:
            fwd_p_new = fwd_p.copy()
            fwd_c_new = fwd_c.copy()
            ep_full = jax.tree.map(jnp.asarray, ep)
            chunk_cap = min(1024, tile)
            for lo in range(0, R.size, chunk_cap):
                chunk = R[lo: lo + chunk_cap]
                c_pad = _padq("forward", chunk.size)
                er_rows = _gather_rows(er, chunk, c_pad)
                t_ids = np.zeros(c_pad, np.uint32)
                t_ids[: chunk.size] = chunk
                col_dirty = np.zeros(c_pad, bool)
                col_dirty[: chunk.size] = is_dirty_t[chunk]
                run = _build_repair_forward(
                    wtuple, Pn, kk, c_pad, ep_treedef,
                    jax.tree.structure(er_rows),
                )
                prov, cost_k, dc = run(
                    ep_full, er_rows, jnp.asarray(t_ids),
                    jnp.asarray(col_dirty),
                )
                fwd_p_new[chunk] = np.asarray(prov)[: chunk.size]
                fwd_c_new[chunk] = np.asarray(cost_k)[: chunk.size]
                if col_dirty.any():
                    dc = np.asarray(dc)[:, : chunk.size]
                    tiles_of = chunk // tile
                    for j in np.unique(tiles_of[is_dirty_t[chunk]]):
                        sel = tiles_of == j
                        np.minimum(
                            min_dirty_tile[:, j], dc[:, sel].min(axis=1),
                            out=min_dirty_tile[:, j],
                        )

    # ---- reverse scope: flag (provider, tile) contribution blocks
    with _tracer.stage("repair.tile_contrib", took, "rep_tiles_ms"):
        flag = np.zeros((Pn, n_tiles), bool)
        flag[dirty_p, :] = True
        if dirty_t.size:
            pt3 = pool_t_np.reshape(Pn, n_tiles, rt)
            pc3 = pool_c_np.reshape(Pn, n_tiles, rt)
            flag |= np.isin(pt3, dirty_t).any(axis=2)
            flag |= min_dirty_tile <= pc3[:, :, -1]
        blocks = int(flag.sum())
        if blocks:
            s_cap = 4096
            for j in np.flatnonzero(flag.any(axis=0)):
                er_tile = jax.tree.map(
                    lambda a: jnp.asarray(
                        np.asarray(a)[j * tile: (j + 1) * tile]
                    ), er,
                )
                t0 = jnp.uint32(j * tile)
                sj = np.flatnonzero(flag[:, j])
                for lo in range(0, sj.size, s_cap):
                    sc = sj[lo: lo + s_cap]
                    s_pad = _padq("tile", sc.size)
                    ep_rows = _gather_rows(ep, sc, s_pad)
                    p_ids = np.zeros(s_pad, np.uint32)
                    p_ids[: sc.size] = sc
                    run = _build_repair_tile(
                        wtuple, tile, rt, s_pad,
                        jax.tree.structure(ep_rows),
                        jax.tree.structure(er_tile),
                    )
                    tt, tc = run(ep_rows, jnp.asarray(p_ids), er_tile, t0)
                    pool_t_np[sc, j * rt: (j + 1) * rt] = (
                        np.asarray(tt)[: sc.size]
                    )
                    pool_c_np[sc, j * rt: (j + 1) * rt] = (
                        np.asarray(tc)[: sc.size]
                    )

    # ---- fold replay + auction-visible merge (exact, deterministic:
    # bit-identical parts in => bit-identical merged lists out)
    with _tracer.stage("repair.merge", took, "rep_merge_ms"):
        d_fold = mesh.shape[axis] if use_mesh else 1
        refold = _build_repair_refold(Pn, n_tiles, rt, r, d_fold)
        rev_t, rev_c = refold(
            jnp.asarray(pool_t_np), jnp.asarray(pool_c_np)
        )
        cand_p, cand_c = merge_reverse_candidates(
            jnp.asarray(fwd_p_new), jnp.asarray(fwd_c_new),
            rev_t, rev_c, extra=extra, scope="repair.merge",
        )
        cand_p = np.asarray(cand_p, np.int32)
        cand_c = np.asarray(cand_c, np.float32)
    visited = R.size * Pn + blocks * tile + dirty_p.size * T
    stats = {
        "repair_rows": int(R.size),
        "repair_providers": int(flag.any(axis=1).sum()),
        "repair_blocks": blocks,
        "repair_enter_rows": enter_count,
        "visited_cells_frac": round(visited / max(Pn * T, 1), 6),
        "pad_hw": pad_hw,
        **took,
    }
    return (
        cand_p,
        cand_c,
        fwd_p_new,
        fwd_c_new,
        pool_t_np,
        pool_c_np,
        stats,
    )
