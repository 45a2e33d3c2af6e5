"""Seeded repair-kernel entries (the seeded marker lines are the
oracle): the warm-path candidate-repair builder shapes from
parallel/sparse.py — an lru_cache'd builder whose kernel is returned
through a ``jax.jit(fn)`` CALL, a scan-body fold, and a
``jax.jit(shard_map(fn, ...))`` sharded twin — each hiding one host
sync inside the traced body. A repair kernel that syncs per chunk
would serialize the whole O(churn) batch loop on device round-trips,
so the lint must see through both call forms."""

from functools import lru_cache

import jax
import numpy as np
from jax import lax

from jax import shard_map


@lru_cache(maxsize=32)
def build_repair_forward(Pn, kk):
    def forward_rows(cost, t_ids):
        neg, idx = lax.top_k(-cost.T, kk)
        worst = float((-neg[:, -1]).max().item())  # SEED: jax-purity
        return idx, worst

    return jax.jit(forward_rows)


@lru_cache(maxsize=32)
def build_repair_enter(tile, n_tiles):
    def enter_scan(cost, thresh):
        def step(_, t0):
            block = lax.dynamic_slice_in_dim(cost, t0, tile, axis=1)
            hit = np.asarray(block) <= thresh  # SEED: jax-purity
            return None, hit.any(axis=0)

        _, enter = lax.scan(
            step, None, np.arange(n_tiles, dtype=np.int32) * tile
        )
        return enter

    return jax.jit(enter_scan)


@lru_cache(maxsize=32)
def build_repair_reverse_sharded(mesh, r):
    def reverse_pools(pool_c, pool_t):
        keep = pool_c.tolist()[:r]  # SEED: jax-purity
        return pool_t, keep

    return jax.jit(
        shard_map(reverse_pools, mesh=mesh, in_specs=(), out_specs=())
    )
