"""Candidate generation and repair on the virtual 8-device CPU mesh:
bit parity with the single-device pass at every device count, and the
one (single-device) ladder fed by sharded lists."""

import numpy as np
import pytest

import jax.numpy as jnp

from protocol_tpu.parallel import make_mesh


class TestShardedGeneration:
    """candidates_topk_bidir_sharded: bit-exact parity with the
    single-device generator (same global tiling, same jitter keys, same
    tile-pooled reverse contract) — the collective-free sharding of the
    measured wall-clock dominator."""

    def _marketplace(self, P, T, seed=5):
        import jax
        from tests.test_sparse import encode_random_marketplace

        ep, er = encode_random_marketplace(seed, P, T)
        return jax.tree.map(jnp.asarray, ep), jax.tree.map(jnp.asarray, er)

    @pytest.mark.parametrize(
        "P,T,D,tile,k,r,extra",
        [
            (512, 1024, 8, 64, 16, 8, 8),
            (256, 512, 4, 128, 8, 4, 4),   # rt > 1 branch (2 local tiles)
            (128, 256, 2, 128, 8, 1, 2),   # rt == 1 argmin branch
        ],
    )
    def test_bit_parity_with_single_device(self, P, T, D, tile, k, r, extra):
        from protocol_tpu.ops.cost import CostWeights
        from protocol_tpu.ops.sparse import candidates_topk_bidir
        from protocol_tpu.parallel import candidates_topk_bidir_sharded

        ep, er = self._marketplace(P, T)
        w = CostWeights()
        cp1, cc1 = candidates_topk_bidir(
            ep, er, w, k=k, tile=tile, reverse_r=r, extra=extra
        )
        cp2, cc2 = candidates_topk_bidir_sharded(
            ep, er, w, mesh=make_mesh(D), k=k, tile=tile, reverse_r=r,
            extra=extra,
        )
        np.testing.assert_array_equal(np.asarray(cp1), np.asarray(cp2))
        np.testing.assert_array_equal(np.asarray(cc1), np.asarray(cc2))

    def test_divisibility_enforced(self):
        from protocol_tpu.parallel import candidates_topk_bidir_sharded

        ep, er = self._marketplace(64, 96)  # 96 not divisible by 64-tile
        with pytest.raises(ValueError):
            candidates_topk_bidir_sharded(
                ep, er, mesh=make_mesh(8), k=8, tile=64
            )

    def test_feeds_sharded_solve_end_to_end(self):
        """Sharded generation feeds the one ladder: the plan equals the
        fully single-device pipeline's bit for bit."""
        from protocol_tpu.ops.cost import CostWeights
        from protocol_tpu.ops.sparse import (
            assign_auction_sparse_scaled,
            candidates_topk_bidir,
        )
        from protocol_tpu.parallel import candidates_topk_bidir_sharded

        P = T = 512
        ep, er = self._marketplace(P, T, seed=9)
        w = CostWeights()
        bp_s, bc_s = candidates_topk_bidir_sharded(
            ep, er, w, mesh=make_mesh(8), k=8, tile=64, reverse_r=4, extra=8
        )
        bp_1, bc_1 = candidates_topk_bidir(
            ep, er, w, k=8, tile=64, reverse_r=4, extra=8
        )
        kw = dict(num_providers=P, frontier=T, with_prices=True)
        res_s, price_s = assign_auction_sparse_scaled(bp_s, bc_s, **kw)
        res_1, price_1 = assign_auction_sparse_scaled(bp_1, bc_1, **kw)
        np.testing.assert_array_equal(
            np.asarray(res_s.provider_for_task),
            np.asarray(res_1.provider_for_task),
        )
        np.testing.assert_array_equal(
            np.asarray(price_s), np.asarray(price_1)
        )
        assert int(np.asarray(res_s.provider_for_task >= 0).sum()) > T // 2


class TestCandidateRepair:
    """repair_topk_bidir_sharded: the warm-path repaired==regen oracle
    contract — a churn-masked repair of the persistent parts lands the
    bit-identical structure a from-scratch bidirectional pass produces
    on the current features, at every device count (ISSUE 18)."""

    def _marketplace(self, P, T, seed=5):
        import jax
        from tests.test_sparse import encode_random_marketplace

        ep, er = encode_random_marketplace(seed, P, T)
        return jax.tree.map(jnp.asarray, ep), jax.tree.map(jnp.asarray, er)

    @staticmethod
    def _bump_price(ep, rows, delta=0.25):
        import dataclasses

        price = np.array(ep.price, copy=True)
        price[list(rows)] += delta
        return dataclasses.replace(ep, price=jnp.asarray(price))

    @staticmethod
    def _bump_req(er, rows, delta=1.0):
        import dataclasses

        cc = np.array(er.cpu_cores, copy=True)
        cc[list(rows)] = np.maximum(1.0, cc[list(rows)] + delta)
        return dataclasses.replace(er, cpu_cores=jnp.asarray(cc))

    def _full(self, ep, er, w, mesh, k, tile, r, extra):
        from protocol_tpu.ops.sparse import (
            candidates_topk_reverse,
            merge_reverse_candidates,
        )
        from protocol_tpu.parallel import candidates_topk_bidir_sharded

        if mesh is None:
            fp, fc, rt_, rc, pt, pc = candidates_topk_reverse(
                ep, er, w, k=k, tile=tile, reverse_r=r, with_pools=True
            )
            mp, mc = merge_reverse_candidates(fp, fc, rt_, rc, extra=extra)
            return [np.asarray(a) for a in (mp, mc, fp, fc, pt, pc)]
        return [
            np.asarray(a)
            for a in candidates_topk_bidir_sharded(
                ep, er, w, mesh=mesh, k=k, tile=tile, reverse_r=r,
                extra=extra, with_parts=True,
            )
        ]

    @pytest.mark.parametrize("D", [None, 1, 4])
    @pytest.mark.parametrize(
        "dirty_p,dirty_t",
        [
            ([5, 17, 40], []),            # provider-side churn only
            ([], [3, 60, 100, 101]),      # requirement-side churn only
            ([2, 90], [0, 127]),          # both sides
            ([], []),                     # empty event: repair is a no-op
        ],
    )
    def test_repair_matches_regen_bit_for_bit(self, D, dirty_p, dirty_t):
        from protocol_tpu.ops.cost import CostWeights
        from protocol_tpu.parallel.sparse import repair_topk_bidir_sharded

        P, T, k, tile, r, extra = 96, 128, 16, 16, 8, 8
        mesh = None if D is None else make_mesh(D)
        ep, er = self._marketplace(P, T)
        w = CostWeights()
        _, _, fwd_p, fwd_c, pool_t, pool_c = self._full(
            ep, er, w, mesh, k, tile, r, extra
        )
        ep2 = self._bump_price(ep, dirty_p) if dirty_p else ep
        er2 = self._bump_req(er, dirty_t) if dirty_t else er
        oracle = self._full(ep2, er2, w, mesh, k, tile, r, extra)
        got = repair_topk_bidir_sharded(
            ep2, er2, w, fwd_p=fwd_p, fwd_c=fwd_c, pool_t=pool_t,
            pool_c=pool_c, dirty_p=np.asarray(dirty_p, np.int64),
            dirty_t=np.asarray(dirty_t, np.int64), reverse_r=r,
            mesh=mesh, tile=tile, extra=extra,
        )
        stats = got[-1]
        order = ["cand_p", "cand_c", "fwd_p", "fwd_c", "pool_t", "pool_c"]
        for name, g, o in zip(order, got[:6], oracle):
            np.testing.assert_array_equal(g, o, err_msg=name)
        if not dirty_p and not dirty_t:
            assert stats["repair_rows"] == 0
            assert stats["repair_providers"] == 0
            assert stats["repair_blocks"] == 0
            assert stats["visited_cells_frac"] == 0.0
        else:
            # repair scope is honest churn-bounded work, not a rebuild
            assert stats["visited_cells_frac"] < 1.0

    def test_repair_scope_is_churn_bounded(self):
        """Requirement-side churn (the heartbeat steady state) repairs
        O(churned rows): the forward scope is exactly the dirty tasks
        and the visited-cell fraction stays near churn/T."""
        from protocol_tpu.ops.cost import CostWeights
        from protocol_tpu.parallel.sparse import repair_topk_bidir_sharded

        P, T, k, tile, r, extra = 96, 256, 16, 16, 8, 8
        ep, er = self._marketplace(P, T)
        w = CostWeights()
        _, _, fwd_p, fwd_c, pool_t, pool_c = self._full(
            ep, er, w, None, k, tile, r, extra
        )
        dirty_t = np.asarray([10, 77], np.int64)
        er2 = self._bump_req(er, dirty_t)
        *_, stats = repair_topk_bidir_sharded(
            ep, er2, w, fwd_p=fwd_p, fwd_c=fwd_c, pool_t=pool_t,
            pool_c=pool_c, dirty_p=np.zeros(0, np.int64),
            dirty_t=dirty_t, reverse_r=r, mesh=None, tile=tile,
            extra=extra,
        )
        assert stats["repair_rows"] == dirty_t.size
        assert stats["repair_enter_rows"] == 0  # no dirty providers
        assert stats["visited_cells_frac"] < 0.5

    def test_rt_one_and_clamped_k_branches(self):
        """The argmin reverse branch (rt == 1: many tiles) and k
        clamped at P both honor the oracle contract."""
        from protocol_tpu.ops.cost import CostWeights
        from protocol_tpu.parallel.sparse import repair_topk_bidir_sharded

        P, T, k, tile, r, extra = 24, 256, 64, 16, 4, 8
        ep, er = self._marketplace(P, T, seed=11)
        w = CostWeights()
        kk = min(k, P)
        _, _, fwd_p, fwd_c, pool_t, pool_c = self._full(
            ep, er, w, None, kk, tile, r, extra
        )
        ep2 = self._bump_price(ep, [1, 20])
        er2 = self._bump_req(er, [4, 200])
        oracle = self._full(ep2, er2, w, None, kk, tile, r, extra)
        got = repair_topk_bidir_sharded(
            ep2, er2, w, fwd_p=fwd_p, fwd_c=fwd_c, pool_t=pool_t,
            pool_c=pool_c, dirty_p=np.asarray([1, 20], np.int64),
            dirty_t=np.asarray([4, 200], np.int64), reverse_r=r,
            mesh=None, tile=tile, extra=extra,
        )
        for g, o in zip(got[:6], oracle):
            np.testing.assert_array_equal(g, o)

    # ---- ISSUE 32: the forward rows' per-tile minima are folded on
    # the device, and every stage reads back once

    @pytest.mark.parametrize(
        "chunks",
        [
            # (task ids, which of them are dirty, c_pad)
            pytest.param(
                [(range(10, 26), [12, 20], 16)], id="spans_two_tiles"),
            pytest.param(
                [(range(10, 26), [], 16)], id="no_dirty_column"),
            pytest.param(
                [([3, 40, 41, 100], [3, 40, 41, 100], 8)],
                id="only_dirty_columns"),
            # pad columns repeat task 0, of tile 0, where task 1 is
            # dirty: they must not lower tile 0's minimum
            pytest.param(
                [([1, 2, 17, 18, 33], [1, 17], 8)], id="padded"),
            pytest.param(
                [(range(0, 16), [5], 16), (range(16, 40), [5, 30, 39], 32),
                 ([41, 127], [127], 8)],
                id="carried_over_three_chunks"),
        ],
    )
    def test_device_fold_matches_the_numpy_fold(self, chunks):
        """``_build_repair_forward`` folds the dirty columns of its
        cost block into ``min_dirty_tile`` on the device; the NumPy
        fold it replaces, kept here as the reference, read the whole
        block back and folded it tile by tile. Bit for bit."""
        import dataclasses

        import jax

        from protocol_tpu.ops.cost import (
            INFEASIBLE,
            CostWeights,
            cost_matrix,
            tie_jitter_ids,
        )
        from protocol_tpu.parallel import sparse as psparse

        P, T, kk, tile = 96, 128, 16, 16
        n_tiles = T // tile
        ep, er = self._marketplace(P, T)
        w = CostWeights()

        @jax.jit
        def cost_block(er_rows, t_ids):
            cost, _ = cost_matrix(ep, er_rows, w)
            grid = tie_jitter_ids(jnp.arange(P, dtype=jnp.uint32), t_ids)
            return jnp.where(cost < INFEASIBLE * 0.5, cost + grid, cost)

        want = np.full((P, n_tiles), psparse._PAD_COST, np.float32)
        got = jnp.asarray(want)
        for ids, dirty, c_pad in chunks:
            chunk = np.asarray(list(ids), np.int64)
            is_dirty = np.isin(chunk, dirty)
            t_ids = np.zeros(c_pad, np.uint32)
            t_ids[: chunk.size] = chunk
            col_dirty = np.zeros(c_pad, bool)
            col_dirty[: chunk.size] = is_dirty
            run = psparse._build_repair_forward(
                dataclasses.astuple(w), P, kk, c_pad, tile, n_tiles,
                jax.tree.structure(ep), jax.tree.structure(er),
            )
            _prov, _cost_k, got = run(ep, er, t_ids, col_dirty, got)
            # the reference: the parent's host fold of the block
            block = np.asarray(
                cost_block(psparse._gather_rows(er, chunk, c_pad), t_ids)
            )
            dc = np.where(col_dirty[None, :], block, psparse._PAD_COST)
            dc = dc.astype(np.float32)[:, : chunk.size]
            tiles_of = chunk // tile
            for j in np.unique(tiles_of[is_dirty]):
                np.minimum(
                    want[:, j], dc[:, tiles_of == j].min(axis=1),
                    out=want[:, j],
                )
        np.testing.assert_array_equal(np.asarray(got), want)
        touched = {
            int(t) // tile for _ids, dirty, _pad in chunks for t in dirty
        }
        for j in range(n_tiles):
            if j in touched:
                assert (want[:, j] < psparse._PAD_COST).all()
            else:
                assert (want[:, j] == np.float32(psparse._PAD_COST)).all()

    @pytest.fixture(scope="class")
    def two_ticks(self):
        """A large tick (a dozen providers, five tasks) and a small one
        on top of it, through the repair with its pad ratchet carried
        over; returns each tick's stats and the jit witness's delta
        over the second."""
        from protocol_tpu.ops.cost import CostWeights
        from protocol_tpu.parallel.sparse import repair_topk_bidir_sharded
        from protocol_tpu.utils import jitwitness

        P, T, k, tile, r, extra = 96, 256, 16, 16, 8, 8
        ep, er = self._marketplace(P, T)
        w = CostWeights()
        parts = self._full(ep, er, w, None, k, tile, r, extra)[2:]
        pads: dict = {}
        out = []
        for dirty_p, dirty_t in (
            (list(range(3, 90, 8)), [7, 50, 120, 121, 250]),
            ([4, 61], [9]),
        ):
            ep = self._bump_price(ep, dirty_p)
            er = self._bump_req(er, dirty_t)
            before = jitwitness.counts()
            *_, fwd_p, fwd_c, pool_t, pool_c, stats = (
                repair_topk_bidir_sharded(
                    ep, er, w, fwd_p=parts[0], fwd_c=parts[1],
                    pool_t=parts[2], pool_c=parts[3],
                    dirty_p=np.asarray(dirty_p, np.int64),
                    dirty_t=np.asarray(dirty_t, np.int64), reverse_r=r,
                    mesh=None, tile=tile, extra=extra, pad_floors=pads,
                )
            )
            parts = (fwd_p, fwd_c, pool_t, pool_c)
            pads = stats["pad_hw"]
            out.append((stats, jitwitness.delta(before)))
        oracle = self._full(ep, er, w, None, k, tile, r, extra)
        for g, o in zip(parts, oracle[2:]):
            np.testing.assert_array_equal(g, o)
        return (P, T, k, tile, extra), out

    def test_every_stage_reads_back_once(self, two_ticks):
        """A tick of several forward chunks and several tiles waits for
        the device four times, once a stage, and copies back the lists
        it keeps: never a cost block ([P, chunk] f32 a chunk)."""
        (P, T, k, tile, extra), ((stats, _), (small, _)) = two_ticks
        n_chunks = -(-stats["repair_rows"] // tile)
        assert n_chunks >= 3
        assert stats["rep_syncs"] == small["rep_syncs"] == 4
        lists = 2 * 4 * k * stats["repair_rows"]
        merged = 2 * 4 * T * (k + extra)
        minima = 4 * P * (T // tile)
        assert merged < stats["rep_readback_bytes"] < 2 * (
            lists + merged + minima
        )
        assert small["rep_readback_bytes"] < stats["rep_readback_bytes"]
        # what the parent read back for the fold alone
        assert stats["rep_readback_bytes"] < n_chunks * 4 * P * tile + merged

    def test_a_smaller_tick_builds_no_program(self, two_ticks):
        """The pad ratchet covers every compile key of the repair's
        programs: after a large tick a smaller one traces nothing."""
        _shape, ((big, _built), (small, again)) = two_ticks
        assert small["repair_rows"] < big["repair_rows"]
        assert small["repair_blocks"] < big["repair_blocks"]
        assert again == {}, again
        assert small["pad_hw"] == big["pad_hw"]
