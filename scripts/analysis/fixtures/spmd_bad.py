"""Seeded shard_map contract violations (the seeded marker lines are
the oracle): a missing spec kwarg, undeclared/unresolvable axis names,
spec-arity mismatches, collectives with a bad or missing axis or
outside any sharded region, and D-invariance breaks — the mutation
class that works at D=1 and silently diverges on a real mesh."""

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

MESH = None
_mystery = object()


@jax.jit
@partial(shard_map, mesh=MESH, in_specs=(P("p", None),))  # SEED: spmd-contract
def bad_missing_out(cost):
    return lax.psum(cost, "p")


@jax.jit
@partial(
    shard_map,
    mesh=MESH,
    in_specs=(P("q", None),),  # SEED: spmd-contract
    out_specs=P(),
    check_vma=False,
)
def bad_axis_in_spec(cost):
    return jnp.sum(cost)


@jax.jit
@partial(
    shard_map,
    mesh=MESH,
    in_specs=(P(_mystery, None),),  # SEED: spmd-contract
    out_specs=P(),
)
def bad_unresolvable_spec(cost):
    return cost


@jax.jit
@partial(
    shard_map,
    mesh=MESH,
    in_specs=(P("p", None), P()),  # SEED: spmd-contract
    out_specs=P(),
)
def bad_in_arity(cost):
    return cost


@jax.jit
@partial(
    shard_map,
    mesh=MESH,
    in_specs=(P("p", None),),
    out_specs=(P(), P()),  # SEED: spmd-contract
)
def bad_out_arity(cost):
    return cost, cost, cost


@jax.jit
@partial(shard_map, mesh=MESH, in_specs=(P("p", None),), out_specs=P())
def bad_collective_axis(cost):
    return lax.psum(cost, "q")  # SEED: spmd-contract


@jax.jit
@partial(shard_map, mesh=MESH, in_specs=(P("p", None),), out_specs=P())
def bad_collective_no_axis(cost):
    return lax.psum(cost)  # SEED: spmd-contract


@jax.jit
@partial(
    shard_map, mesh=MESH, in_specs=(P("p", None), P()), out_specs=P(),
)
def bad_collective_opaque_axis(cost, which):
    return lax.pmax(cost, which)  # SEED: spmd-contract


def host_combine(cost):
    return lax.psum(cost, "p")  # SEED: spmd-contract


@jax.jit
def bad_device_read(cost):
    return cost / jax.device_count()  # SEED: spmd-contract


def pick_tile(T, cap=1024):
    return min(T, cap)


def bad_tile_policy(T):
    D = jax.local_device_count()
    return pick_tile(T, cap=T // D)  # SEED: spmd-contract
