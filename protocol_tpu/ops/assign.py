"""Batched assignment kernels (dense variants).

The measurement ladder (BASELINE.md) replaces the reference's per-heartbeat
greedy matcher (crates/orchestrator/src/scheduler/mod.rs:26-74, O(tasks) per
node, O(nodes*tasks) system-wide per interval) with one batched solve:

  assign_greedy    - vectorized first-fit(-decreasing): lax.scan over tasks,
                     masked argmin over providers per step. Bit-parity oracle
                     for the CPU greedy path given the same task order.
  assign_sinkhorn  - entropic OT in log-space (lax.while_loop), balanced via
                     equalized marginals, then rounded to a feasible matching
                     by a greedy pass over the transport plan.
  assign_auction   - Bertsekas auction: tasks bid for providers, eps-scaling
                     phases, deterministic tie-breaking (argmax picks the
                     lowest index). Near-optimal linear assignment.

Conventions:
  cost  f32 [P, T], INFEASIBLE (1e9) marks incompatibility
  out   AssignResult: provider_for_task i32 [T] (-1 = unassigned),
        task_for_provider i32 [P] (-1 = idle)

All kernels are jit-compatible with static shapes and no data-dependent
Python control flow. Dense [P, T] tensors cap out around ~30k x 30k on a
16 GB chip; the blocked/matrix-free variants for the 100k-1M ladder live in
``protocol_tpu.ops.blocked`` and ``protocol_tpu.parallel``.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from protocol_tpu.ops.cost import INFEASIBLE

# -inf stand-in that survives arithmetic. A Python float on purpose:
# a jnp scalar at module level would initialize the JAX backend at
# import time — a control-plane process importing this module would
# claim the chip the scheduler pod owns.
_NEG = -1e18


@jax.tree_util.register_dataclass
@dataclass
class AssignResult:
    provider_for_task: jax.Array  # i32 [T], -1 = unassigned
    task_for_provider: jax.Array  # i32 [P], -1 = idle

    def num_assigned(self) -> jax.Array:
        return jnp.sum(self.provider_for_task >= 0)


def _invert(provider_for_task: jax.Array, num_providers: int) -> jax.Array:
    """task_for_provider from provider_for_task (both injective over >=0)."""
    t_idx = jnp.arange(provider_for_task.shape[0], dtype=jnp.int32)
    out = jnp.full(num_providers, -1, jnp.int32)
    safe = jnp.where(provider_for_task >= 0, provider_for_task, num_providers)
    return out.at[safe].set(jnp.where(provider_for_task >= 0, t_idx, -1), mode="drop")


# --------------------------------------------------------------------------
# Greedy / first-fit-decreasing
# --------------------------------------------------------------------------


@partial(jax.jit, static_argnames=())
def assign_greedy(cost: jax.Array, task_order: jax.Array | None = None) -> AssignResult:
    """Sequential-greedy matching as a lax.scan.

    Visits tasks in ``task_order`` (default: ascending index = the reference's
    "first task in list wins" behavior); each task takes the cheapest still-
    available compatible provider. Ties break to the lowest provider index
    (jnp.argmin returns the first minimum), making the kernel a deterministic
    oracle against the host-side greedy matcher.
    """
    P, T = cost.shape
    if task_order is None:
        task_order = jnp.arange(T, dtype=jnp.int32)

    cols = jnp.take(cost.T, task_order, axis=0)  # [T, P] in visit order

    def step(avail, col):
        masked = jnp.where(avail, col, INFEASIBLE)
        p = jnp.argmin(masked).astype(jnp.int32)
        feasible = masked[p] < INFEASIBLE * 0.5
        avail = avail.at[p].set(jnp.where(feasible, False, avail[p]))
        return avail, jnp.where(feasible, p, -1)

    _, picks = lax.scan(step, jnp.ones(P, dtype=bool), cols)
    provider_for_task = (
        jnp.full(T, -1, jnp.int32).at[task_order].set(picks.astype(jnp.int32))
    )
    return AssignResult(provider_for_task, _invert(provider_for_task, P))


def ffd_order(demand: jax.Array) -> jax.Array:
    """First-fit-DECREASING visit order: biggest resource demand first.
    Stable sort => deterministic among equal demands."""
    return jnp.argsort(-demand, stable=True).astype(jnp.int32)


# --------------------------------------------------------------------------
# Sinkhorn entropic OT
# --------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("num_iters",))
def sinkhorn_plan(
    cost: jax.Array,
    eps: float | jax.Array = 0.05,
    num_iters: int = 200,
) -> jax.Array:
    """Log-domain Sinkhorn: returns the soft transport plan [P, T].

    Marginals are equalized so both sides carry mass min(P_valid, T_valid):
    a balanced problem even when P != T. Infeasible pairs carry INFEASIBLE
    cost and end up with ~zero plan mass. f32 throughout; the logsumexp
    reductions are the HBM-bound hot ops and fuse with the cost broadcast.
    """
    P, T = cost.shape
    feas_row = jnp.any(cost < INFEASIBLE * 0.5, axis=1)  # provider has any task
    feas_col = jnp.any(cost < INFEASIBLE * 0.5, axis=0)
    np_valid = jnp.maximum(jnp.sum(feas_row), 1)
    nt_valid = jnp.maximum(jnp.sum(feas_col), 1)
    m = jnp.minimum(np_valid, nt_valid).astype(jnp.float32)

    log_a = jnp.where(feas_row, jnp.log(m / np_valid.astype(jnp.float32)), _NEG)
    log_b = jnp.where(feas_col, jnp.log(m / nt_valid.astype(jnp.float32)), _NEG)

    K = jnp.where(cost < INFEASIBLE * 0.5, -cost / eps, _NEG)  # [P, T]

    def body(i, uv):
        u, v = uv
        u = log_a - jax.nn.logsumexp(K + v[None, :], axis=1)
        u = jnp.where(feas_row, u, _NEG)
        v = log_b - jax.nn.logsumexp(K + u[:, None], axis=0)
        v = jnp.where(feas_col, v, _NEG)
        return u, v

    u0 = jnp.zeros(P, jnp.float32)
    v0 = jnp.zeros(T, jnp.float32)
    u, v = lax.fori_loop(0, num_iters, body, (u0, v0))
    return jnp.exp(K + u[:, None] + v[None, :])


@partial(jax.jit, static_argnames=("num_iters",))
def assign_sinkhorn(
    cost: jax.Array,
    eps: float | jax.Array = 0.05,
    num_iters: int = 200,
) -> AssignResult:
    """Sinkhorn plan + feasible rounding.

    Rounding = greedy matching on the negated plan (take the strongest
    plan entries first), visiting tasks by their best plan mass descending.
    Guarantees a feasible matching (each provider used once, compatibility
    respected) — the constraint-satisfaction step the soft OT lacks.
    """
    plan = sinkhorn_plan(cost, eps=eps, num_iters=num_iters)
    feasible = cost < INFEASIBLE * 0.5
    # greedy wants a cost; use -plan, infeasible back to INFEASIBLE
    rounding_cost = jnp.where(feasible, -plan, INFEASIBLE)
    order = jnp.argsort(-jnp.max(plan, axis=0), stable=True).astype(jnp.int32)
    return assign_greedy(rounding_cost, task_order=order)


# --------------------------------------------------------------------------
# Bertsekas auction
# --------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("max_iters",))
def assign_auction(
    cost: jax.Array,
    eps: float | jax.Array = 0.01,
    max_iters: int = 500,
) -> AssignResult:
    """Forward auction: unassigned tasks bid for their best-value provider.

    value[t, p] = -cost[p, t] - price[p]. Each round every unassigned task
    bids price[p1] + (v1 - v2) + eps on its best provider p1; each provider
    takes the highest bid (ties -> lowest task index), evicting the previous
    owner. eps fixed per call; wrap with eps-scaling externally if needed.
    Near-optimal: within n*eps of the optimal assignment value.

    O(P*T) per round, all rounds inside one lax.while_loop — no host
    round-trips.
    """
    P, T = cost.shape
    value_base = jnp.where(cost < INFEASIBLE * 0.5, -cost, _NEG).T  # [T, P]
    task_feasible = jnp.any(value_base > _NEG * 0.5, axis=1)  # [T]

    def cond(state):
        it, price, owner, p4t = state
        unassigned = (p4t < 0) & task_feasible
        return (it < max_iters) & jnp.any(unassigned)

    def body(state):
        it, price, owner, p4t = state
        unassigned = (p4t < 0) & task_feasible  # [T]

        value = value_base - price[None, :]  # [T, P]
        p1 = jnp.argmax(value, axis=1).astype(jnp.int32)  # first max: lowest p
        v1 = jnp.take_along_axis(value, p1[:, None], axis=1)[:, 0]
        masked = value.at[jnp.arange(T), p1].set(_NEG)
        v2 = jnp.max(masked, axis=1)
        v2 = jnp.maximum(v2, jnp.float32(-1e8))  # single-option floor: finite bid

        bid_amt = price[p1] + (v1 - v2) + eps  # [T]

        # provider-side winner: dense scatter of bids, argmax per provider.
        bids = jnp.full((T, P), _NEG)
        bids = bids.at[jnp.arange(T), p1].set(jnp.where(unassigned, bid_amt, _NEG))
        win_bid = jnp.max(bids, axis=0)  # [P]
        win_task = jnp.argmax(bids, axis=0).astype(jnp.int32)  # ties: lowest t
        got_bid = win_bid > _NEG * 0.5  # [P]

        # evict previous owners of contested providers
        prev_owner = owner  # [P]
        evict_t = jnp.where(got_bid & (prev_owner >= 0), prev_owner, T)
        p4t = p4t.at[evict_t].set(-1, mode="drop")

        # install winners
        p_idx = jnp.arange(P, dtype=jnp.int32)
        win_t_safe = jnp.where(got_bid, win_task, T)
        p4t = p4t.at[win_t_safe].set(jnp.where(got_bid, p_idx, -1), mode="drop")
        owner = jnp.where(got_bid, win_task, owner)
        price = jnp.where(got_bid, win_bid, price)
        return it + 1, price, owner, p4t

    state0 = (
        jnp.int32(0),
        jnp.zeros(P, jnp.float32),
        jnp.full(P, -1, jnp.int32),
        jnp.full(T, -1, jnp.int32),
    )
    _, _, owner, p4t = lax.while_loop(cond, body, state0)
    return AssignResult(p4t, _invert(p4t, P))


def assign_auction_scaled(
    cost: jax.Array,
    eps_start: float = 1.0,
    eps_end: float = 0.01,
    scale: float = 0.2,
    max_iters_per_phase: int = 300,
) -> AssignResult:
    """eps-scaling wrapper: run auction phases with geometrically shrinking
    eps, warm-starting each phase from scratch prices (simple variant; price
    warm-starting is a planned optimization). Host-side loop over a few
    phases, device-side while_loop within each."""
    from protocol_tpu.ops.cost import with_tie_jitter

    # degeneracy breaker (see ops/cost.py tie_jitter): exact ties make
    # every open bidder target the same provider — 1 assignment/round
    cost = with_tie_jitter(cost)
    eps = eps_start
    result = None
    while True:
        result = assign_auction(cost, eps=eps, max_iters=max_iters_per_phase)
        if eps <= eps_end:
            return result
        eps = max(eps * scale, eps_end)
