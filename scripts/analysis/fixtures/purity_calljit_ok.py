"""Clean twin of purity_calljit_bad: the same call-form jit shapes with
trace-pure bodies — static_argnames honored (branching on a static is
the legal specialization idiom), shape probes whitelisted, dtypes
explicit. Must come back silent."""

import jax
import jax.numpy as jnp
import numpy as np
from functools import partial

from jax import shard_map


def _pure_body(cost):
    return (cost * jnp.float32(2.0)).sum()


jit_pure = jax.jit(_pure_body)


def _specialized_body(cost, k):
    if k > 2:  # static: named in static_argnames below
        return cost[:k]
    return cost


jit_specialized = jax.jit(_specialized_body, static_argnames=("k",))


def _shape_probe_body(cost):
    if cost.ndim == 1:  # shape probing is trace-time constant
        cost = cost[None, :]
    return cost + np.zeros(cost.shape, dtype=np.float32)


jit_sharded = jax.jit(
    shard_map(_shape_probe_body, mesh=None, in_specs=(), out_specs=()),
)


def _partial_body(cost, scale):
    return cost * scale


jit_partial = jax.jit(partial(_partial_body, scale=2.0))


def build_kernel(mesh):
    def _local_body(cost):
        return cost + jnp.ones(cost.shape, dtype=jnp.float32)

    return jax.jit(_local_body)
