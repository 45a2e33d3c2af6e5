"""Clean twin of spmd_bad.py: the SAME shapes, contract-honoring — the
idioms the shard_map pass must NOT flag. Full spec kwargs, axes that
resolve through literals / the ``axis`` alias / the PROVIDER_AXIS
module constant, matching spec arity, collectives only under sharded
bodies, and a tile policy that is a function of T alone."""

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

PROVIDER_AXIS = "p"


def build_phase(mesh, axis="p"):
    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis, None), P(None)),
        out_specs=(P(), P()),
        check_vma=False,
    )
    def run(cost_local, price):
        shard = lax.axis_index(axis)
        total = lax.psum(cost_local, axis)
        best = lax.pmax(price, PROVIDER_AXIS)
        return total, best + shard

    return run


def _gather_body(x):
    return lax.all_gather(x, "p")


gathered = jax.jit(
    shard_map(
        _gather_body, mesh=None, in_specs=(P("p"),), out_specs=P("p"),
    )
)


def pick_tile(T, cap=1024):
    return min(T, cap)


def plan_tiles(T):
    # tile policy is a function of T only; the device count is read
    # host-side AFTER the tile is fixed (never flows into pick_tile)
    tile = pick_tile(T, cap=max(1, T // 8))
    D = jax.device_count()
    return tile, D


@jax.jit
def traced_entry(cost):
    return jnp.sum(cost)
