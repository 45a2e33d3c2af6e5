"""Clean twin of purity_repair_bad: the same repair-builder call
shapes — lru_cache'd builders returning ``jax.jit(fn)`` over scan
folds and a ``jax.jit(shard_map(fn, ...))`` twin — with trace-pure
bodies (device-side reductions, jnp sentinels, no host syncs). Must
come back silent."""

from functools import lru_cache

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map


@lru_cache(maxsize=32)
def build_repair_forward(Pn, kk):
    def forward_rows(cost, t_ids):
        neg, idx = lax.top_k(-cost.T, kk)
        worst = (-neg[:, -1]).max()  # stays on device
        return idx, worst

    return jax.jit(forward_rows)


@lru_cache(maxsize=32)
def build_repair_enter(tile, n_tiles):
    def enter_scan(cost, thresh):
        def step(_, t0):
            block = lax.dynamic_slice_in_dim(cost, t0, tile, axis=1)
            hit = block <= thresh[None, :]
            return None, jnp.any(hit, axis=0)

        _, enter = lax.scan(
            step, None, jnp.arange(n_tiles, dtype=jnp.int32) * tile
        )
        return enter

    return jax.jit(enter_scan)


@lru_cache(maxsize=32)
def build_repair_reverse_sharded(mesh, r):
    def reverse_pools(pool_c, pool_t):
        neg, m = lax.top_k(-pool_c, r)
        return jnp.take_along_axis(pool_t, m, axis=1), -neg

    return jax.jit(
        shard_map(reverse_pools, mesh=mesh, in_specs=(), out_specs=())
    )
