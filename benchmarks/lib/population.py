"""Seeded marketplace populations and tick churn, NumPy only.

Copied from ``protocol_tpu/trace/synth.py`` (``synth_providers``,
``synth_requirements`` and the price/load drift of ``synth_trace``) at
commit 5134453, with the trace file and the program's dataclasses taken
out: a pool is two dicts of columns in the wire's dtypes, and a tick is
the rows that changed with their new values.
"""

from __future__ import annotations

import numpy as np

MODEL_CLASSES = 12
MODEL_WORDS = 8
MAX_GPU_OPTS = 2


def providers(rng: np.random.Generator, n: int) -> dict:
    model = rng.integers(0, MODEL_CLASSES, n).astype(np.int32)
    count = rng.choice([1, 2, 4, 8], n).astype(np.int32)
    mem = rng.choice([16000, 24000, 40000, 80000], n).astype(np.int32)
    return {
        "gpu_count": count,
        "gpu_mem_mb": mem,
        "gpu_model_id": model,
        "has_gpu": np.ones(n, bool),
        "has_cpu": np.ones(n, bool),
        "cpu_cores": rng.choice([8, 16, 32, 64], n).astype(np.int32),
        "ram_mb": rng.choice([32768, 65536, 131072], n).astype(np.int32),
        "storage_gb": rng.choice([500, 1000, 4000], n).astype(np.int32),
        "lat": np.radians(rng.uniform(-60, 60, n)).astype(np.float32),
        "lon": np.radians(rng.uniform(-180, 180, n)).astype(np.float32),
        "has_location": np.ones(n, bool),
        "price": rng.uniform(0.5, 4.0, n).astype(np.float32),
        "load": rng.uniform(0, 1, n).astype(np.float32),
        "valid": np.ones(n, bool),
    }


def requirements(rng: np.random.Generator, n: int) -> dict:
    k, w = MAX_GPU_OPTS, MODEL_WORDS
    # each task accepts a random subset of model classes (OR alternatives)
    mask = np.zeros((n, k, w), np.uint32)
    accept = rng.random((n, MODEL_CLASSES)) < 0.4
    accept[np.arange(n), rng.integers(0, MODEL_CLASSES, n)] = True
    for c in range(MODEL_CLASSES):
        mask[:, 0, c >> 5] |= np.where(
            accept[:, c], np.uint32(1) << np.uint32(c & 31), 0
        ).astype(np.uint32)
    opt_valid = np.zeros((n, k), bool)
    opt_valid[:, 0] = True
    count = np.full((n, k), -1, np.int32)
    count[:, 0] = rng.choice(
        [-1, 1, 2, 4, 8], n, p=[0.4, 0.15, 0.15, 0.15, 0.15]
    )
    mem_min = np.full((n, k), -1, np.int32)
    mem_min[:, 0] = rng.choice([-1, 16000, 40000], n, p=[0.5, 0.3, 0.2])
    return {
        "cpu_required": np.zeros(n, bool),
        "cpu_cores": rng.choice([-1, 8, 16], n, p=[0.5, 0.3, 0.2]).astype(
            np.int32
        ),
        "ram_mb": rng.choice([-1, 32768], n, p=[0.6, 0.4]).astype(np.int32),
        "storage_gb": rng.choice([-1, 500], n, p=[0.7, 0.3]).astype(np.int32),
        "gpu_opt_valid": opt_valid,
        "gpu_count": count,
        "gpu_mem_min": mem_min,
        "gpu_mem_max": np.full((n, k), -1, np.int32),
        "gpu_total_mem_min": np.full((n, k), -1, np.int32),
        "gpu_total_mem_max": np.full((n, k), -1, np.int32),
        "gpu_model_mask": mask,
        "gpu_model_constrained": opt_valid.copy(),
        "lat": np.radians(rng.uniform(-60, 60, n)).astype(np.float32),
        "lon": np.radians(rng.uniform(-180, 180, n)).astype(np.float32),
        "has_location": np.ones(n, bool),
        "priority": np.zeros(n, np.float32),
        "valid": np.ones(n, bool),
    }


class Pool:
    """One pool's cumulative columns and its endless tick stream. The
    client's view of the marketplace: ``p_cols``/``r_cols`` always hold
    what the server's session holds after the last delta sent."""

    def __init__(self, rng: np.random.Generator, n_providers: int,
                 n_tasks: int, provider_churn: float, task_churn: float):
        self.rng = rng
        self.p_cols = providers(rng, n_providers)
        self.r_cols = requirements(rng, n_tasks)
        self.provider_churn = float(provider_churn)
        self.task_churn = float(task_churn)

    def next_delta(self, scale: float = 1.0):
        """Advance one tick. Returns ``(provider_rows, p_vals, task_rows,
        r_vals)``: sorted row ids and the full replacement rows, after
        applying them to the cumulative columns. ``scale`` multiplies
        the churn: set-up sends one oversized tick, so that the padded
        shapes the repair ratchets up to are built before the window."""
        rng = self.rng
        prow = np.zeros(0, np.int32)
        live = np.flatnonzero(self.p_cols["valid"])
        n_drift = int(live.size * self.provider_churn * scale)
        if n_drift:
            rows = rng.choice(live, n_drift, replace=False)
            self.p_cols["price"][rows] = rng.uniform(
                0.5, 4.0, rows.size
            ).astype(np.float32)
            self.p_cols["load"][rows] = rng.uniform(
                0, 1, rows.size
            ).astype(np.float32)
            prow = np.sort(rows).astype(np.int32)
        trow = np.zeros(0, np.int32)
        n_t = self.r_cols["valid"].shape[0]
        n_tchurn = int(n_t * self.task_churn * scale)
        if n_tchurn:
            rows = rng.choice(n_t, n_tchurn, replace=False)
            fresh = requirements(rng, n_tchurn)
            for name, col in self.r_cols.items():
                col[rows] = fresh[name]
            trow = np.sort(rows).astype(np.int32)
        p_vals = {n: a[prow] for n, a in self.p_cols.items()}
        r_vals = {n: a[trow] for n, a in self.r_cols.items()}
        return prow, p_vals, trow, r_vals

    def block_tasks(self, n: int):
        """A task delta that makes the first ``n`` tasks unassignable
        (they ask for exactly 3 GPUs; providers hold 1, 2, 4 or 8), and
        the delta that puts them back. Set-up uses the pair to make the
        server run, and so build, the program that sweeps tasks left
        open, which ordinary ticks reach only now and then."""
        rows = np.arange(n, dtype=np.int32)
        before = {name: a[rows].copy() for name, a in self.r_cols.items()}
        blocked = {name: a.copy() for name, a in before.items()}
        blocked["gpu_count"][:, 0] = 3
        return (rows, blocked), (rows, before)

    def snapshot(self):
        """Copies of the cumulative columns as they stand."""
        return (
            {n: a.copy() for n, a in self.p_cols.items()},
            {n: a.copy() for n, a in self.r_cols.items()},
        )
