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
