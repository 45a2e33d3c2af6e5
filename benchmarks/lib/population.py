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


# The life events a traffic mix may carry, each a share a tick (absent
# or 0: none), and the third word of their generator's seed.
LIFE_RATES = ("provider_leave", "provider_join", "task_end", "task_arrive")
LIFE_STREAM = 0x11FE


def life_of(traffic: dict) -> dict:
    """The mix's life events as a dict of floats, or ``{}`` where every
    rate is absent or 0: the stream is then the churn's alone."""
    rates = {k: float(traffic.get(k, 0)) for k in LIFE_RATES}
    if not any(rates.values()):
        return {}
    rates["arrive_wave_ticks"] = float(traffic.get("arrive_wave_ticks", 0))
    rates["arrive_wave_amp"] = float(traffic.get("arrive_wave_amp", 0))
    return rates


def _sorted_rows(parts: list) -> np.ndarray:
    """The rows a tick changed, each once, in order."""
    if not parts:
        return np.zeros(0, np.int32)
    return np.unique(np.concatenate(parts)).astype(np.int32)


class Pool:
    """One pool's cumulative columns and its endless tick stream. The
    client's view of the marketplace: ``p_cols``/``r_cols`` always hold
    what the server's session holds after the last delta sent.

    A row is live while its ``valid`` is True. ``life`` (``life_of`` a
    mix) makes rows come and go: providers leave and return, tasks end
    and arrive. ``providers_live`` / ``tasks_live`` leave only that many
    rows live at the open. Both draw from ``life_rng``, a generator of
    their own, so the churn's draws from ``rng`` are the same with them
    on or off; the churn touches live rows only."""

    def __init__(self, rng: np.random.Generator, n_providers: int,
                 n_tasks: int, provider_churn: float, task_churn: float,
                 life: dict | None = None,
                 life_rng: np.random.Generator | None = None,
                 providers_live: int | None = None,
                 tasks_live: int | None = None):
        self.rng = rng
        self.life_rng = life_rng
        self.p_cols = providers(rng, n_providers)
        self.r_cols = requirements(rng, n_tasks)
        self.provider_churn = float(provider_churn)
        self.task_churn = float(task_churn)
        self.life = dict(life or {})
        self.ticks = 0              # calls of next_delta: the wave's clock
        self.arrivals_dropped = 0   # arrivals that found no dead row
        for cols, n_live in ((self.p_cols, providers_live),
                             (self.r_cols, tasks_live)):
            n = cols["valid"].shape[0]
            if n_live is not None and int(n_live) < n:
                cols["valid"][life_rng.choice(
                    n, n - int(n_live), replace=False)] = False

    def _life_events(self, scale: float):
        """One tick of the mix's life events, applied to the cumulative
        columns; ``(provider rows, task rows)`` that changed. Each kind
        draws among the rows as the tick found them, so a provider that
        leaves does not return, and a row that ends is not refilled,
        within one tick."""
        life, rng = self.life, self.life_rng
        p_valid, r_valid = self.p_cols["valid"], self.r_cols["valid"]
        here, away = np.flatnonzero(p_valid), np.flatnonzero(~p_valid)
        live, dead = np.flatnonzero(r_valid), np.flatnonzero(~r_valid)
        leave = rng.choice(
            here, int(here.size * life["provider_leave"] * scale), False)
        join = rng.choice(
            away, int(away.size * life["provider_join"] * scale), False)
        end = rng.choice(
            live, int(live.size * life["task_end"] * scale), False)
        wave = 1.0
        if life["arrive_wave_ticks"]:
            wave += life["arrive_wave_amp"] * np.sin(
                2 * np.pi * self.ticks / life["arrive_wave_ticks"])
        n_arrive = int(r_valid.shape[0] * life["task_arrive"] * scale * wave)
        self.arrivals_dropped += max(n_arrive - dead.size, 0)
        arrive = rng.choice(dead, min(n_arrive, dead.size), False)
        p_valid[leave] = False
        # the same machine comes back: at a price and a load of the day
        p_valid[join] = True
        self.p_cols["price"][join] = rng.uniform(
            0.5, 4.0, join.size).astype(np.float32)
        self.p_cols["load"][join] = rng.uniform(
            0, 1, join.size).astype(np.float32)
        r_valid[end] = False
        fresh = requirements(rng, arrive.size)
        for name, col in self.r_cols.items():
            col[arrive] = fresh[name]
        return [leave, join], [end, arrive]

    def next_delta(self, scale: float = 1.0):
        """Advance one tick. Returns ``(provider_rows, p_vals, task_rows,
        r_vals)``: sorted row ids and the full replacement rows, after
        applying them to the cumulative columns. ``scale`` multiplies
        the churn and the life events: set-up sends one oversized tick,
        so that the padded shapes the repair ratchets up to are built
        before the window."""
        rng = self.rng
        self.ticks += 1
        prows, trows = self._life_events(scale) if self.life else ([], [])
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
            prows.append(rows)
        live = np.flatnonzero(self.r_cols["valid"])
        n_tchurn = int(live.size * self.task_churn * scale)
        if n_tchurn:
            rows = live[rng.choice(live.size, n_tchurn, replace=False)]
            fresh = requirements(rng, n_tchurn)
            for name, col in self.r_cols.items():
                col[rows] = fresh[name]
            trows.append(rows)
        prow, trow = _sorted_rows(prows), _sorted_rows(trows)
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

    def regime_visits(self, margin: float):
        """Three task deltas that take the pool through both regimes
        and back, none of them applied to the cumulative columns: the
        first takes the lowest live rows away until the live tasks are
        under the live providers by more than ``margin`` of them, the
        second brings those back and the lowest dead rows to life until
        they are over by as much (as far as there are dead rows), the
        third returns every row to what the stream holds. Set-up sends
        them where the mix has life events, so that the programs of
        the pool with slack, of the pool with a queue and of the
        change between them are built before the window."""
        valid = self.r_cols["valid"]
        n_p = int(self.p_cols["valid"].sum())
        step = int(n_p * margin) + 1
        live, dead = np.flatnonzero(valid), np.flatnonzero(~valid)
        away = live[: max(live.size - (n_p - step), 0)]
        woken = dead[: max(n_p + step - live.size, 0)]

        def delta(rows, live_now):
            rows = np.sort(rows).astype(np.int32)
            vals = {name: a[rows].copy() for name, a in self.r_cols.items()}
            vals["valid"][:] = live_now
            return rows, vals

        return [delta(away, False),
                delta(np.concatenate([away, woken]), True),
                delta(woken, False)]

    def snapshot(self):
        """Copies of the cumulative columns as they stand."""
        return (
            {n: a.copy() for n, a in self.p_cols.items()},
            {n: a.copy() for n, a in self.r_cols.items()},
        )
