"""Provider-sharded Bertsekas auction via shard_map.

Layout (BASELINE.json ladder config #4):
  - cost rows (providers) sharded over the 1-D ``p`` mesh axis; each device
    owns [P/D, T] of the value tensor — the only O(P*T) object.
  - per-provider state (price, owner) lives shard-local [P/D].
  - per-task state (assignment) is replicated [T] and updated identically on
    every device from all_gather'd per-shard candidates, so no scatter of
    task state ever crosses shards.

Per iteration the ICI traffic is 4 arrays of [D, T] (per-shard best value,
runner-up value, best provider id, best provider's price) + one [T] i32
max-combine for assignment deltas — independent of P.

Deterministic tie-breaking everywhere: argmax returns the first maximum, and
global provider ids are formed as shard_offset + local index, so lower
provider ids win ties exactly as in the dense kernel
(protocol_tpu.ops.assign.assign_auction), which is its parity oracle.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from protocol_tpu.ops.assign import AssignResult, _invert
from protocol_tpu.ops.cost import INFEASIBLE

_NEG = -1e18


@lru_cache(maxsize=64)
def _build_sharded_dense_auction(
    mesh: Mesh, axis: str, eps: float, max_iters: int
):
    # Cached per static config: a closure rebuilt per call would re-trace
    # and re-compile the while_loop on every solve (see parallel/sparse.py).
    D = mesh.shape[axis]

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis, None),),
        out_specs=P(),
        check_vma=False,
    )
    def run(cost_local: jax.Array) -> jax.Array:
        Pl, T = cost_local.shape
        shard = lax.axis_index(axis)
        offset = (shard * Pl).astype(jnp.int32)

        value_base = jnp.where(cost_local < INFEASIBLE * 0.5, -cost_local, _NEG).T  # [T, Pl]
        feas_local = jnp.any(value_base > _NEG * 0.5, axis=1)
        task_feasible = lax.psum(feas_local.astype(jnp.int32), axis) > 0  # [T]

        def cond(state):
            it, price, owner, p4t = state
            return (it < max_iters) & jnp.any((p4t < 0) & task_feasible)

        def body(state):
            it, price, owner, p4t = state
            unassigned = (p4t < 0) & task_feasible  # [T] replicated

            # ---- local top-2 per task over this shard's providers
            value = value_base - price[None, :]  # [T, Pl]
            p1l = jnp.argmax(value, axis=1).astype(jnp.int32)
            v1l = jnp.take_along_axis(value, p1l[:, None], axis=1)[:, 0]
            v2l = jnp.max(value.at[jnp.arange(T), p1l].set(_NEG), axis=1)
            price1l = price[p1l]
            p1g = jnp.where(v1l > _NEG * 0.5, offset + p1l, jnp.int32(-1))

            # ---- global top-2 combine (all_gather over the mesh axis)
            av1 = lax.all_gather(v1l, axis)  # [D, T]
            av2 = lax.all_gather(v2l, axis)
            ap1 = lax.all_gather(p1g, axis)
            apr = lax.all_gather(price1l, axis)

            # best shard: max value, ties -> lowest global provider id.
            # av1 ties across shards mean equal value; prefer lower shard
            # (== lower provider id range): argmax picks first max.
            best_shard = jnp.argmax(av1, axis=0).astype(jnp.int32)  # [T]
            gv1 = jnp.take_along_axis(av1, best_shard[None, :], axis=0)[0]
            gp1 = jnp.take_along_axis(ap1, best_shard[None, :], axis=0)[0]
            gprice1 = jnp.take_along_axis(apr, best_shard[None, :], axis=0)[0]
            # runner-up: max of (other shards' v1, best shard's v2)
            av1_masked = jnp.where(
                jnp.arange(D)[:, None] == best_shard[None, :], _NEG, av1
            )
            gv2 = jnp.maximum(jnp.max(av1_masked, axis=0), jnp.max(av2, axis=0))
            gv2 = jnp.maximum(gv2, jnp.float32(-1e8))  # single-option floor

            bid_amt = gprice1 + (gv1 - gv2) + eps  # [T]
            bidding = unassigned & (gv1 > _NEG * 0.5)

            # ---- provider-side winner resolution, local providers only
            local_target = bidding & (gp1 >= offset) & (gp1 < offset + Pl)
            tgt = jnp.where(local_target, gp1 - offset, Pl)  # [T], Pl = drop
            bids = jnp.full((T, Pl), _NEG)
            bids = bids.at[jnp.arange(T), tgt].set(
                jnp.where(local_target, bid_amt, _NEG), mode="drop"
            )
            win_bid = jnp.max(bids, axis=0)  # [Pl]
            win_task = jnp.argmax(bids, axis=0).astype(jnp.int32)  # ties: low t
            got_bid = win_bid > _NEG * 0.5

            # ---- local state updates
            evict_t = jnp.where(got_bid & (owner >= 0), owner, T)
            new_owner = jnp.where(got_bid, win_task, owner)
            new_price = jnp.where(got_bid, win_bid, price)

            # ---- replicated assignment update via max-combine:
            # encode "no change" as -2; eviction (-1) and win (p>=0) beat it.
            delta = jnp.full(T, -2, jnp.int32)
            delta = delta.at[evict_t].set(-1, mode="drop")
            pidx = offset + jnp.arange(Pl, dtype=jnp.int32)
            win_t_safe = jnp.where(got_bid, win_task, T)
            delta = delta.at[win_t_safe].set(
                jnp.where(got_bid, pidx, -2), mode="drop"
            )
            gdelta = lax.pmax(delta, axis)
            p4t = jnp.where(gdelta > -2, gdelta, p4t)
            return it + 1, new_price, new_owner, p4t

        state0 = (
            jnp.int32(0),
            jnp.zeros(Pl, jnp.float32),
            jnp.full(Pl, -1, jnp.int32),
            jnp.full(T, -1, jnp.int32),
        )
        _, _, _, p4t = lax.while_loop(cond, body, state0)
        return p4t

    return run


def assign_auction_sharded(
    cost: jax.Array,
    mesh: Mesh,
    eps: float = 0.01,
    max_iters: int = 500,
    axis: str = "p",
) -> AssignResult:
    """Auction with cost rows sharded over ``mesh`` axis ``axis``.

    ``cost`` is [P, T] with P divisible by the mesh size. Returns a fully
    replicated AssignResult identical (same ties) to the dense kernel.
    """
    Ptot, T = cost.shape
    D = mesh.shape[axis]
    if Ptot % D != 0:
        raise ValueError(f"P={Ptot} not divisible by mesh size {D}; pad first")

    cost = jax.device_put(cost, NamedSharding(mesh, P(axis, None)))
    run = _build_sharded_dense_auction(mesh, axis, float(eps), int(max_iters))
    p4t = run(cost)
    return AssignResult(p4t, _invert(p4t, Ptot))
