"""VERDICT r2 done-bars at full scale: 100k nodes x 10k replica slots
through the sparse production path, locally and over gRPC, plus the
warm >= 10x incremental-solve claim — measured, not asserted.

~3-4 min on the CI CPU (the cold candidate pass streams a ~2G-cell cost
tensor), so the suite gates it behind PROTOCOL_TPU_SCALE_TESTS=1:

    PROTOCOL_TPU_SCALE_TESTS=1 python -m pytest tests/test_scale_matcher.py

(`make scale-tests` runs exactly that.) The always-on reduced-scale
equivalents live in tests/test_sparse_matcher.py.
"""

import os
import time

import pytest

from protocol_tpu.sched import TpuBatchMatcher
from protocol_tpu.store import StoreContext

from tests.test_sparse_matcher import mk_bounded_task, mk_node

pytestmark = pytest.mark.skipif(
    os.environ.get("PROTOCOL_TPU_SCALE_TESTS") != "1",
    reason="scale test (~4 min CPU); set PROTOCOL_TPU_SCALE_TESTS=1",
)

N_NODES = 100_000
N_SLOTS = 10_000


def build_ctx():
    ctx = StoreContext.new_test()
    for i in range(N_NODES):
        ctx.node_store.add_node(mk_node(f"0x{i:040x}"))
    ctx.task_store.add_task(mk_bounded_task("big", 100, replicas=N_SLOTS))
    return ctx


def test_100k_nodes_10k_slots_sparse_local_and_warm_speedup():
    ctx = build_ctx()
    m = TpuBatchMatcher(ctx, min_solve_interval=0, top_k=16)
    t0 = time.perf_counter()
    m.refresh()
    cold = time.perf_counter() - t0
    st = m.last_solve_stats
    assert st["kernel"] == "sparse_topk"
    assert st["assigned"] == N_SLOTS
    assert st["truncated_replica_slots"] == 0

    # warm twice: the second excludes the one-time warm-kernel compile
    m.mark_dirty(); m.refresh()
    assert m.last_solve_stats["warm"] is True
    m.mark_dirty()
    t0 = time.perf_counter()
    m.refresh()
    warm = time.perf_counter() - t0
    assert m.last_solve_stats["assigned"] == N_SLOTS
    assert cold / warm >= 10.0, f"warm speedup only {cold / warm:.1f}x"


def test_100k_nodes_10k_slots_over_grpc():
    from protocol_tpu.services import scheduler_grpc

    server = scheduler_grpc.serve(address="127.0.0.1:50079")
    try:
        ctx = build_ctx()
        m = scheduler_grpc.RemoteBatchMatcher(
            ctx, address="127.0.0.1:50079", min_solve_interval=0, top_k=16
        )
        m.refresh()
        st = m.last_solve_stats
        assert st["kernel"] == "sparse_topk"
        assert st["assigned"] == N_SLOTS
        assert st["remote_calls"] >= 1
    finally:
        server.stop(grace=None)


def test_16k_warm_solve_at_least_2x_faster_than_cold():
    """VERDICT r4 item 2's done-bar at the kernel level: warm >= 2x faster
    than the cold ladder at a contended bench-shaped 16k instance (r4 had
    measured warm 5.5x SLOWER at 65k -- root causes and their always-on
    mechanism tests live in test_sparse.TestWarmColdRegression)."""
    import bench
    import jax
    import jax.numpy as jnp
    import numpy as np

    from protocol_tpu.ops.cost import CostWeights
    from protocol_tpu.ops.sparse import (
        assign_auction_sparse_scaled,
        assign_auction_sparse_warm,
        candidates_topk_bidir,
    )

    T = 16384
    rng = np.random.default_rng(0)
    ep = bench.synth_providers(rng, T)
    er = bench.synth_requirements(rng, T)
    bp, bc = candidates_topk_bidir(
        ep, er, CostWeights(), k=64, tile=2048, reverse_r=8, extra=16
    )
    jax.block_until_ready((bp, bc))

    def cold():
        out = assign_auction_sparse_scaled(
            bp, bc, num_providers=T, frontier=8192, with_state=True
        )
        jax.block_until_ready(out[1])
        return out

    res, price, retired, _reserve = cold()  # compile
    t0 = time.perf_counter(); res, price, retired, _reserve = cold()
    t_cold = time.perf_counter() - t0

    p4t0 = jnp.asarray(res.provider_for_task).at[: T // 100].set(-1)

    def warm():
        r, p = assign_auction_sparse_warm(
            bp, bc, num_providers=T, price0=price, p4t0=p4t0,
            retired0=retired, frontier=8192,
        )
        jax.block_until_ready(p)
        return r

    warm()  # compile
    t0 = time.perf_counter(); res_w = warm()
    t_warm = time.perf_counter() - t0

    a_cold = int(np.asarray(res.provider_for_task >= 0).sum())
    a_warm = int(np.asarray(res_w.provider_for_task >= 0).sum())
    assert a_warm >= a_cold - 2
    assert t_warm * 2.0 <= t_cold, (
        f"warm {t_warm:.2f}s not >= 2x faster than cold {t_cold:.2f}s"
    )
