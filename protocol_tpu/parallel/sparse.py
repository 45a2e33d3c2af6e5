"""Candidate generation and repair, task-sharded over a device mesh.

What the jax arena serves across chips (and, for the repair, on one):
the bidirectional candidate pass :func:`candidates_topk_bidir_sharded`
and the warm tick's :func:`repair_topk_bidir_sharded`. Both split the
task rows over the mesh and are bit-identical to the single-device
pass, so a plan does not depend on the device count. The auction solve
does not shard: its round is latency, not bytes, and it runs on one
device (ops/sparse.py).
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from protocol_tpu.obs.spans import TRACER as _tracer


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
    forward lists and the [D, P, r] reverse pools at the end — so the
    speedup on this stage is ~linear in D (the solve that follows runs
    on one device).

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
    """Cached builder for the sharded generation executable (a fresh
    jit+shard_map closure per call would re-trace and recompile the
    whole scan each rebuild).
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
    weights_tuple: tuple, Pn: int, kk: int, c_pad: int, tile: int,
    n_tiles: int, ep_treedef, er_treedef,
):
    """Forward row recompute: the exact per-row selection of generation
    (_forward_tile_select with provider_offset=None) on a task subset
    GATHERED on the device from the full requirements by ``t_ids`` —
    full [Pn, c_pad] jittered cost block, stable lax.top_k, the same -1
    erasure of infeasible slots. A row's forward list depends on
    nothing but its own cost column, so recomputed rows are bit-identical
    to the columns a from-scratch pass would produce regardless of tile
    or shard placement. The cost block never leaves the device: what the
    reverse enter-mask needs of it, the minimum over the chunk's DIRTY
    columns of each task tile, is folded into the carried
    ``min_dirty_tile`` [Pn, n_tiles] here (_PAD_COST where no dirty
    column has fallen yet). A minimum of f32 values is exact in any
    order, so the carry after the last chunk is the fold of the whole
    block bit for bit; pad columns are never dirty."""
    from protocol_tpu.ops.cost import INFEASIBLE, CostWeights, cost_matrix
    from protocol_tpu.ops.cost import tie_jitter_ids

    weights = CostWeights(*weights_tuple)

    @jax.named_scope("repair.forward_rows")
    def forward_rows(ep, er, t_ids, col_dirty, min_dirty_tile):
        er_rows = jax.tree.map(lambda a: a[t_ids], er)
        cost, _m = cost_matrix(ep, er_rows, weights)  # [Pn, c_pad]
        jit_grid = tie_jitter_ids(jnp.arange(Pn, dtype=jnp.uint32), t_ids)
        cost = jnp.where(cost < INFEASIBLE * 0.5, cost + jit_grid, cost)
        neg_sel, idx = lax.top_k(-cost.T, kk)  # [c_pad, kk] best first
        sel_k = -neg_sel
        provider = jnp.where(
            sel_k < INFEASIBLE * 0.5, idx.astype(jnp.int32), -1
        )
        cost_k = jnp.take_along_axis(cost.T, idx, axis=1)
        # [n_tiles, c_pad]: the dirty columns of each tile
        in_tile = col_dirty[None, :] & (
            (t_ids // jnp.uint32(tile))[None, :]
            == jnp.arange(n_tiles, dtype=jnp.uint32)[:, None]
        )
        fold = jnp.min(
            jnp.where(
                in_tile[:, None, :], cost[None, :, :], jnp.float32(_PAD_COST)
            ),
            axis=2,
        )  # [n_tiles, Pn]
        return provider, cost_k, jnp.minimum(min_dirty_tile, fold.T)

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
    ep_treedef, er_treedef,
):
    """Per-tile reverse CONTRIBUTION recompute: one tile's raw
    top-``rt`` per provider of ``p_ids`` — the exact per-tile half of
    the generation fold (same cost ops, same global-id jitter, same
    argmin/top_k branch), nothing folded. Provider rows are gathered
    and the tile's requirements sliced on the device from the full
    columns, so a call uploads its ids and nothing else. A contribution
    (p, j) depends on nothing but provider p's own cost row over tile
    j, so recomputed blocks are bit-identical to the blocks a
    from-scratch pass emits regardless of batch membership or device
    count; the fold itself is replayed over the persisted pools by
    _build_repair_refold. No -1 masking here: pools persist raw
    (infeasible entries keep their INFEASIBLE+jitter cost), matching
    the gen-side emission."""
    from protocol_tpu.ops.cost import INFEASIBLE, CostWeights, cost_matrix
    from protocol_tpu.ops.cost import tie_jitter_ids
    from protocol_tpu.ops.sparse import _slice_requirements

    weights = CostWeights(*weights_tuple)

    @jax.named_scope("repair.tile_contrib")
    def tile_contrib(ep, p_ids, er, t0):
        ep_rows = jax.tree.map(lambda a: a[p_ids], ep)
        er_tile = _slice_requirements(er, t0, tile)
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
    paths pay and are excluded), the four stage walls, and what the
    stages cost in traffic with the device: ``rep_syncs`` (times the
    host waited for a device value: one a stage that ran) and
    ``rep_readback_bytes`` (bytes those reads copied to the host).

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

    # four host-sequenced stages: each dispatches its kernel calls back
    # to back and is a span closed at the ONE read-back that ends it
    # (``_read``, the only place the host waits for the device), its
    # wall beside it in the stats
    took: dict = {}
    io = {"rep_syncs": 0, "rep_readback_bytes": 0}

    def _read(tree):
        out = jax.device_get(tree)
        io["rep_syncs"] += 1
        io["rep_readback_bytes"] += sum(
            a.nbytes for a in jax.tree.leaves(out)
        )
        return out

    # ---- forward scope
    with _tracer.stage("repair.enter_scan", took, "rep_enter_ms"):
        # one upload of the current columns a tick: the kernels gather
        # and slice their rows from these on the device
        ep_full = jax.tree.map(jnp.asarray, ep)
        er_full = jax.tree.map(jnp.asarray, er)
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
                er_dev = er_full
                thresh = jnp.asarray(fwd_c[:, -1])
            enter = _read(
                run(
                    ep_dirty, jnp.asarray(p_ids), jnp.asarray(p_valid),
                    er_dev, thresh,
                )
            )
            enter_count = int(enter.sum())
            rows |= enter
        R = np.flatnonzero(rows)

    # ---- forward recompute (chunked at the generation tile's memory
    # envelope); each chunk folds its dirty columns into the per-
    # (provider, tile) minima for the reverse block enter-mask on the
    # device. Nothing a chunk needs from the host waits for a device
    # result, so the chunks queue behind one another.
    with _tracer.stage("repair.forward_rows", took, "rep_forward_ms"):
        fwd_p_new, fwd_c_new = fwd_p, fwd_c
        min_dirty_tile = np.full((Pn, n_tiles), _PAD_COST, np.float32)
        if R.size:
            fwd_p_new = fwd_p.copy()
            fwd_c_new = fwd_c.copy()
            is_dirty_t = np.zeros(T, bool)
            is_dirty_t[dirty_t] = True
            chunk_cap = min(1024, tile)
            chunks = [
                R[lo: lo + chunk_cap] for lo in range(0, R.size, chunk_cap)
            ]
            lists = []
            mdt = jnp.asarray(min_dirty_tile)
            for chunk in chunks:
                c_pad = _padq("forward", chunk.size)
                t_ids = np.zeros(c_pad, np.uint32)
                t_ids[: chunk.size] = chunk
                col_dirty = np.zeros(c_pad, bool)
                col_dirty[: chunk.size] = is_dirty_t[chunk]
                run = _build_repair_forward(
                    wtuple, Pn, kk, c_pad, tile, n_tiles,
                    ep_treedef, er_treedef,
                )
                prov, cost_k, mdt = run(
                    ep_full, er_full, t_ids, col_dirty, mdt
                )
                lists.append((prov, cost_k))
            lists, min_dirty_tile = _read((lists, mdt))
            for chunk, (prov, cost_k) in zip(chunks, lists):
                fwd_p_new[chunk] = prov[: chunk.size]
                fwd_c_new[chunk] = cost_k[: chunk.size]

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
            calls, outs = [], []
            for j in np.flatnonzero(flag.any(axis=0)):
                sj = np.flatnonzero(flag[:, j])
                for lo in range(0, sj.size, s_cap):
                    sc = sj[lo: lo + s_cap]
                    s_pad = _padq("tile", sc.size)
                    p_ids = np.zeros(s_pad, np.uint32)
                    p_ids[: sc.size] = sc
                    run = _build_repair_tile(
                        wtuple, tile, rt, s_pad, ep_treedef, er_treedef,
                    )
                    calls.append((sc, j))
                    outs.append(
                        run(ep_full, p_ids, er_full, np.uint32(j * tile))
                    )
            for (sc, j), (tt, tc) in zip(calls, _read(outs)):
                pool_t_np[sc, j * rt: (j + 1) * rt] = tt[: sc.size]
                pool_c_np[sc, j * rt: (j + 1) * rt] = tc[: sc.size]

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
        cand_p, cand_c = _read((cand_p, cand_c))
    visited = R.size * Pn + blocks * tile + dirty_p.size * T
    stats = {
        "repair_rows": int(R.size),
        "repair_providers": int(flag.any(axis=1).sum()),
        "repair_blocks": blocks,
        "repair_enter_rows": enter_count,
        "visited_cells_frac": round(visited / max(Pn * T, 1), 6),
        "pad_hw": pad_hw,
        **took,
        **io,
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
