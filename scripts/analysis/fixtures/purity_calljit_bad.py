"""Seeded call-form jit entries (the seeded marker lines are the
oracle): kernels that are never decorated — they are wrapped by a
``jax.jit(fn)`` / ``jax.jit(shard_map(fn, ...))`` CALL at module level
or inside a builder — yet must still be treated as trace roots. Each
wrapped body carries one purity violation the decorator-only scan used
to miss entirely."""

import time

import jax
import numpy as np
from functools import partial

from jax import shard_map


def _sync_body(cost):
    return float(cost.item())  # SEED: jax-purity


jit_sync = jax.jit(_sync_body)


def _clock_body(cost):
    return cost * time.time()  # SEED: jax-purity


jit_clock = jax.jit(_clock_body)


def _branch_body(cost, eps):
    if eps > 0:  # SEED: jax-purity
        cost = cost / eps
    return cost


# static_argnames names "k" only: eps stays traced, the branch fires
jit_branch = jax.jit(_branch_body, static_argnames=("k",))


def _sharded_body(cost):
    return np.asarray(cost)  # SEED: jax-purity


jit_sharded = jax.jit(
    shard_map(_sharded_body, mesh=None, in_specs=(), out_specs=()),
)


def _partial_body(cost, scale):
    return cost + np.zeros(4)  # SEED: jax-purity


jit_partial = jax.jit(partial(_partial_body, scale=2.0))


def build_kernel(mesh):
    """Builder-local call form: the jitted closure is a nested def."""

    def _local_body(cost):
        return cost.tolist()  # SEED: jax-purity

    return jax.jit(_local_body)
