"""Provider-sharded blocked Sinkhorn over a device mesh.

Completes the 100k-ladder's multi-chip story (BASELINE.md config #3 on a
mesh): providers (and their potential u) are sharded over the 1-D mesh
axis; tasks (and v) are replicated. Per iteration:

  u-update:  entirely shard-local — each device streams ITS provider rows'
             logsumexp over task tiles (the blocked streaming accumulator
             of ops/blocked.py), no communication.
  v-update:  each device computes per-column partial (max, sum·exp) over
             its provider shard; the global logsumexp combines with one
             pmax + one psum per tile — the classic two-collective
             logsumexp-combine, riding ICI with O(T) traffic per
             iteration, independent of P.

Parity-tested against the single-device blocked kernel on the virtual
8-device CPU mesh.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from protocol_tpu.ops.blocked import (
    _NEG,
    feasibility_scan,
    make_k_block,
    streaming_row_logsumexp,
)
from protocol_tpu.ops.cost import CostWeights
from protocol_tpu.ops.encoding import EncodedProviders, EncodedRequirements


@lru_cache(maxsize=64)
def _build_sharded_sinkhorn(
    mesh: Mesh,
    axis: str,
    weights_key: tuple,
    eps: float,
    num_iters: int,
    tile: int,
    T: int,
):
    # Cached per static config: a closure rebuilt per call would re-trace
    # and re-compile the fori_loop on every solve (see parallel/sparse.py).
    # ``er`` is a replicated ARGUMENT (not a capture) so data churn does
    # not invalidate the cache.
    weights = CostWeights(*weights_key)
    n_tiles = T // tile
    starts = jnp.arange(n_tiles, dtype=jnp.int32) * tile

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=(P(axis), P()),
        check_vma=False,
    )
    def run(ep_local: EncodedProviders, er: EncodedRequirements):
        Pl = ep_local.gpu_count.shape[0]

        # shared streamed-kernel helpers (ops/blocked.py): bit-identical
        # math on each shard's provider rows is what parity rests on
        k_block = make_k_block(ep_local, er, weights, eps, tile)

        # feasibility pass: local row-any; column-any via psum of local anys
        row_any_l, col_any_tiles = feasibility_scan(k_block, Pl, starts)
        col_any = (
            lax.psum(col_any_tiles.reshape(T).astype(jnp.int32), axis) > 0
        )
        np_valid = jnp.maximum(
            lax.psum(jnp.sum(row_any_l.astype(jnp.int32)), axis), 1
        )
        nt_valid = jnp.maximum(jnp.sum(col_any), 1)
        m = jnp.minimum(np_valid, nt_valid).astype(jnp.float32)
        log_a = jnp.where(
            row_any_l, jnp.log(m / np_valid.astype(jnp.float32)), _NEG
        )
        log_b = jnp.where(
            col_any, jnp.log(m / nt_valid.astype(jnp.float32)), _NEG
        )

        def iteration(_i, uv):
            u_l, v = uv

            # ---- u-update: shard-local streaming logsumexp over tiles
            lse_u = streaming_row_logsumexp(k_block, v, starts, Pl, tile)
            u_l = jnp.where(row_any_l, log_a - lse_u, _NEG)

            # ---- v-update: per-tile column logsumexp with a two-collective
            # combine: global max (pmax), then psum of rescaled sum-exps
            def v_step(carry, t0):
                k = k_block(t0) + u_l[:, None]
                local_max = jnp.max(k, axis=0)  # [tile]
                gmax = lax.pmax(local_max, axis)
                local_sum = jnp.sum(jnp.exp(k - gmax[None, :]), axis=0)
                gsum = lax.psum(local_sum, axis)
                return carry, gmax + jnp.log(jnp.maximum(gsum, 1e-30))

            _, lse_tiles = lax.scan(v_step, None, starts)
            v = log_b - lse_tiles.reshape(T)
            v = jnp.where(col_any, v, _NEG)
            return u_l, v

        u0 = jnp.zeros(Pl, jnp.float32)
        v0 = jnp.zeros(T, jnp.float32)
        return lax.fori_loop(0, num_iters, iteration, (u0, v0))

    return run


def sinkhorn_potentials_sharded(
    ep: EncodedProviders,
    er: EncodedRequirements,
    mesh: Mesh,
    weights: CostWeights | None = None,
    eps: float = 0.05,
    num_iters: int = 50,
    tile: int = 1024,
    axis: str = "p",
) -> tuple[jax.Array, jax.Array]:
    """Returns (u [P] provider-sharded-then-gathered, v [T] replicated)."""
    if weights is None:
        weights = CostWeights()
    Pn = ep.gpu_count.shape[0]
    T = er.cpu_cores.shape[0]
    D = mesh.shape[axis]
    if Pn % D != 0:
        raise ValueError(f"P={Pn} not divisible by mesh size {D}; pad first")
    if T % tile != 0:
        raise ValueError(f"T={T} not divisible by tile={tile}; pad requirements")

    shard_p = NamedSharding(mesh, P(axis))
    ep = jax.tree.map(lambda x: jax.device_put(x, shard_p), ep)

    # astuple carries EVERY field in declaration order: a future CostWeights
    # field automatically reaches both the cache key and the rebuilt weights
    weights_key = tuple(
        float(v) for v in dataclasses.astuple(weights)
    )
    run = _build_sharded_sinkhorn(
        mesh, axis, weights_key, float(eps), int(num_iters), int(tile), T
    )
    return run(ep, er)
