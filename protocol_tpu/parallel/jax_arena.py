"""Persistent warm-solve arena for the JAX engine (engine="jax").

The accelerator-path peer of :class:`~protocol_tpu.native.arena.
NativeSolveArena` behind the exact same duck-typed surface (solve /
apply_rows / reconcile / export_state / restore_state / invalidate,
``.price`` / ``.retired`` / ``.last_stats``), so every consumer of the
native arena — sessions, the unary servicer, checkpoints, migration,
the stream engine, trace replay — runs unchanged with ``engine="jax"``.
Two-stage split, mirroring SCALING.md's ICI cost model:

  - **Sharded candidate generation.** The bucketed top-K + reverse-edge
    pass as the jit-compiled, task-sharded kernel
    (:func:`~protocol_tpu.parallel.sparse.candidates_topk_bidir_sharded`
    over a 1xD mesh: zero per-round collectives, one ``all_gather`` of
    per-shard top-K, deterministic reverse-edge merge). Device-count
    INVARIANT: D=1 and D=4 produce the bit-identical candidate
    structure (asserted in tests/test_parallel_sparse.py and
    ``perf_gate.py --jax``), which is why the warm carry below stays
    sound across device-count changes and why the provenance tag
    excludes D.
  - **Adaptive-ladder solve.** Cold solves run the eps-annealed auction
    ladder (:func:`~protocol_tpu.ops.sparse.assign_auction_sparse_scaled`
    — jitted ``lax.while_loop`` phases on a single chip); warm solves
    carry the dual state (prices + retirement + matching) into the
    delta-frontier kernel (:func:`assign_auction_sparse_warm`), clearing
    retirement for exactly the rows whose candidates or costs changed —
    the caller contract that kernel documents.

Like the native arena, the jax arena REPAIRS its candidate structure
incrementally on warm ticks: the generation PARTS — forward lists
[T, k] and the raw per-tile reverse contribution pools
[P, n_tiles*rt] — persist across ticks, and a dirty tick runs the
churn-masked repair kernels
(:func:`~protocol_tpu.parallel.sparse.repair_topk_bidir_sharded`)
that recompute exactly the flagged forward rows and (provider, tile)
contribution blocks, replay the generation fold over the pools, and
re-merge. The oracle contract is the same one
``repair_topk_candidates_mt`` honors in C++: the repaired structure is
bit-identical to a from-scratch ``candidates_topk_bidir_sharded`` pass
on the current features, at every device count (tie jitter is keyed on
global indices, so a recomputed subset lands on the exact cells the
full pass would produce — see the exactness notes on each repair
kernel). ``last_stats`` reports the path honestly: warm repair ticks
carry ``cand_cold_passes: 0`` plus scope counters (``repair_rows``,
``repair_providers``, ``visited_cells_frac``); only genuinely cold
ticks — first solve, shape/weights change, ``cold_every``,
``max_dirty_frac`` overflow, or ``approx_recall`` mode (approx
selection has no exactness contract, hence no repair twin) — pay a
full pass and say so. Dirty detection, the byte-identical
short-circuit, ``max_dirty_frac``/``cold_every``/
``dual_refresh_every`` cadences, the dirty-task re-seat, and the seat
feasibility guard all mirror the native arena row for row.

Missing accelerators DEGRADE INSIDE the engine, never across engines:
asking for more devices than the host exposes clamps D to what exists,
counts the event (``device_degraded_events``), and flags every
subsequent ``last_stats`` — a jax solve on one CPU device is still a
jax solve. Silent fallback to the native engine would invalidate every
cross-backend A/B the trace subsystem runs.
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from protocol_tpu import obs
from protocol_tpu.native.arena import _P_SPEC, _R_SPEC, _canon, _dirty_rows
from protocol_tpu.utils import jitwitness as _jitwitness
from protocol_tpu.obs import quality as _quality
from protocol_tpu.obs.spans import TRACER as _tracer
from protocol_tpu.ops.encoding import EncodedProviders, EncodedRequirements
from protocol_tpu.ops.sparse import (
    REGIMES,
    assign_auction_sparse_scaled,
    assign_auction_sparse_warm,
    candidates_topk_bidir,
    pick_tile,
)

# persisted candidate-structure dtypes (same durable on-disk contract as
# native.arena._CAND_STATE_DTYPES: checkpoint frames and migration
# handoffs coerce through this table on restore). Since the warm path
# became incremental repair, the generation PARTS persist alongside the
# merged lists: forward top-k (fwd_*) and the raw per-tile reverse
# contribution pools (pool_*, [P, n_tiles*rt] in global tile order) are
# what the repair kernels patch in place — the merged lists alone cannot
# be repaired (a merge is not invertible), and the FOLDED reverse edges
# are derivable (fold replay) but not invertible either, so the pre-fold
# pools are the canonical persisted form. Pool memory grows as
# P * n_tiles * ceil(r / n_tiles) — between r and 2r-1 entries per
# provider (~2x the folded form at worst), megabytes through ~131k rows.
_JAX_STATE_DTYPES = {
    "cand_p": np.int32,
    "cand_c": np.float32,
    "fwd_p": np.int32,
    "fwd_c": np.float32,
    "pool_t": np.int32,
    "pool_c": np.float32,
}


def jax_isa() -> str:
    """Float-pipeline provenance tag for the jax engine — the XLA
    backend the candidate costs were scored under (``jax:cpu`` /
    ``jax:tpu`` / ...). Plays the role ``native.current_isa()`` plays
    for the native arena: a restore under a different backend cold
    re-grounds instead of warm-continuing on costs another float
    pipeline produced. Device COUNT is deliberately excluded — sharded
    generation is D-invariant (bit-identical candidate structure for
    any D), so a warm carry across a device-count change is sound."""
    return f"jax:{jax.devices()[0].platform}"


class JaxSolveArena:
    def __init__(
        self,
        k: int = 64,
        reverse_r: int = 8,
        extra: int = 16,
        threads: int = 0,
        cold_every: int = 256,
        max_dirty_frac: float = 0.25,
        eps_start: float = 4.0,
        eps_end: float = 0.02,
        dual_refresh_every: int = 16,
        devices: int = 0,
        approx_recall: Optional[float] = None,
    ):
        self.k = k
        self.reverse_r = reverse_r
        self.extra = extra
        # accepted (and settable — EngineThreadBudget grants write it)
        # for surface parity with the native arena; the jax engine's
        # parallelism is the device mesh, so the grant never changes a
        # result or a schedule here
        self.threads = threads
        self.cold_every = cold_every
        self.max_dirty_frac = max_dirty_frac
        self.eps_start = eps_start
        self.eps_end = eps_end
        self.dual_refresh_every = dual_refresh_every
        # requested device count for sharded generation (the gRPC
        # kernel string's ``jax:D`` suffix): 0 = all visible devices
        # (the accelerator-native default — use the mesh you have, the
        # same shape as the native engines' "0 = all hardware
        # threads"), resolved lazily at the first solve so constructing
        # an arena never forces backend init. Requests beyond the host
        # clamp with a counted, non-fatal flag (see module docstring).
        self.devices = int(devices)
        self.approx_recall = approx_recall
        self.engine = "jax"
        self.device_degraded = False
        self.device_degraded_events = 0
        self._mesh = None
        self._devices_effective: Optional[int] = None
        self.last_stats: dict = {}
        # set by a session's checkpointer for one tick: called once,
        # with :meth:`live_state`, when a warm tick's candidate
        # structure is final and before its solve starts
        self.structure_hook = None
        self._jit_mark = _jitwitness.snapshot()
        self.invalidate()

    # ---------------- carried-state surface (native-arena parity) ----

    @property
    def price(self) -> Optional[np.ndarray]:
        """Carried auction prices [P] after the last solve (dual state)."""
        return self._price

    @property
    def retired(self) -> Optional[np.ndarray]:
        """Carried retirement mask [T] after the last solve."""
        return self._retired

    @property
    def potentials(self) -> tuple[Optional[np.ndarray], Optional[np.ndarray]]:
        """Sinkhorn potentials — always (None, None): the jax engine's
        ladder is the auction; the slot exists for surface parity."""
        return None, None

    def invalidate(self) -> None:
        """Drop all carried state: the next solve is cold."""
        self._p_fields: Optional[dict] = None
        self._r_fields: Optional[dict] = None
        self._weights_key: Optional[tuple] = None
        self._cand_p: Optional[np.ndarray] = None
        self._cand_c: Optional[np.ndarray] = None
        self._fwd_p: Optional[np.ndarray] = None
        self._fwd_c: Optional[np.ndarray] = None
        self._pool_t: Optional[np.ndarray] = None
        self._pool_c: Optional[np.ndarray] = None
        # pad-bucket high-water marks for the repair kernels (the
        # ratchet state behind repair_topk_bidir_sharded's pad_floors):
        # carried across warm ticks so repair gathers never shrink into
        # a fresh, never-compiled bucket and retrace mid-chain
        self._repair_pads: dict = {}
        self._price: Optional[np.ndarray] = None
        self._retired: Optional[np.ndarray] = None
        self._p4t: Optional[np.ndarray] = None
        self._warm_solves = 0
        self._dual_age = 0
        self._starve_age: Optional[np.ndarray] = None
        self._reserve: Optional[float] = None
        # the regime the carried duals were made in (ops/sparse.py:
        # REGIMES; None before a solve)
        self._regime: Optional[str] = None
        self._last_quality: dict = {}
        self.last_repair_mask: Optional[np.ndarray] = None
        self._owned_cols: set = set()

    # ---------------- export / restore (checkpoint + migration) ------

    # the exported arrays a solve writes; every other entry of
    # :meth:`export_state` is final for the tick once its
    # ``arena.candidates`` span has closed
    SOLVE_STATE = (
        "price", "retired", "p4t", "starve_age", "queue_reserve", "regime",
    )

    def live_state(self) -> dict:
        """:meth:`export_state`'s entries as the LIVE objects, no
        copies (identity is what tells a checkpoint prefix that the
        structure it compressed is still the arena's)."""
        out = {
            "cand_p": self._cand_p,
            "cand_c": self._cand_c,
            # generation parts: what the warm-path repair kernels patch.
            # None under approx_recall (no repair twin — see _gen).
            "fwd_p": self._fwd_p,
            "fwd_c": self._fwd_c,
            "pool_t": self._pool_t,
            "pool_c": self._pool_c,
            # the pool width n_tiles*ceil(r/n_tiles) does not encode r
            # (rt saturates at 1), so the config rides along explicitly
            "reverse_r": int(self.reverse_r),
            "price": self._price,
            "retired": self._retired,
            "p4t": self._p4t,
            "starve_age": self._starve_age,
            # the reserve of a pool with a queue (ops/sparse.py:
            # _queue_reserve), NaN where the pool has none: one f32
            # whatever the regime, so the journal's layout never moves
            "queue_reserve": np.array(
                [np.nan if self._reserve is None else self._reserve],
                np.float32,
            ),
            # the regime the duals were made in: its index in REGIMES,
            # -1 before a solve
            "regime": np.array(
                [-1 if self._regime is None else REGIMES.index(self._regime)],
                np.int8,
            ),
            "warm_solves": int(self._warm_solves),
            "dual_age": int(self._dual_age),
            "weights_key": tuple(self._weights_key),
            # same meta key as the native export so the checkpoint
            # layer's scalar handling is engine-blind; the tag itself
            # names the XLA backend (see jax_isa)
            "native_isa": jax_isa(),
        }
        for name, _ in _P_SPEC:
            out[f"pf_{name}"] = self._p_fields[name]
        for name, _ in _R_SPEC:
            out[f"rf_{name}"] = self._r_fields[name]
        return out

    def export_state(self) -> Optional[dict]:
        """The carried warm state as a flat dict of scalars and arrays —
        the same key classes as the native arena's export (cand_* +
        duals + matching + cadence cursors + the arena's OWN baseline
        columns), so ``faults/checkpoint.py`` journals and migration
        handoffs carry it unchanged. Returns None before any solve.
        Arrays are copies — a checkpoint must not alias live state."""
        if self._cand_p is None:
            return None
        return {
            name: np.array(v, copy=True) if isinstance(v, np.ndarray) else v
            for name, v in self.live_state().items()
        }

    def restore_state(self, ep, er, state: dict) -> None:
        """Rehydrate the warm chain from :meth:`export_state` output.
        The next ``solve`` continues it bit-identically; a carry this
        arena cannot honor — exported under a different XLA backend
        (the costs came from another float pipeline), by the native
        engine, or at a different candidate width — degrades to an
        honest cold re-ground on the first solve, never a hard error
        mid-tick."""
        self.invalidate()
        if "pf_gpu_count" in state:
            self._p_fields = {
                name: np.array(state[f"pf_{name}"], copy=True)
                for name, _ in _P_SPEC
            }
            self._r_fields = {
                name: np.array(state[f"rf_{name}"], copy=True)
                for name, _ in _R_SPEC
            }
        else:
            self._p_fields = _canon(ep, _P_SPEC)
            self._r_fields = _canon(er, _R_SPEC)
        cand_p = np.asarray(state["cand_p"])
        n_p = self._p_fields["gpu_count"].shape[0]
        n_t = self._r_fields["cpu_cores"].shape[0]
        k_eff = min(self.k, n_p)
        r_eff = min(self.reverse_r, n_t)
        if (
            state.get("native_isa") != jax_isa()
            or cand_p.ndim != 2
            or cand_p.shape != (n_t, k_eff + self.extra)
        ):
            self.invalidate()
            return
        # repair parts: a pre-repair carry (exported before the parts
        # existed) or part-shape skew (k/r config changed) degrades to a
        # cold re-ground exactly like a foreign ISA tag — the merged
        # lists alone cannot seed the repair path, and warm-continuing
        # on them while regenerating parts could pair parts and merge
        # from different feature snapshots. approx_recall arenas carry
        # no parts by design and stay on the regen path (see _gen).
        fwd_p = state.get("fwd_p")
        if self.approx_recall is None:
            # pool width follows the D-free tile policy (a function of
            # T only — the same _gen_plan law generation uses), so a
            # carry from any device count rehydrates here; skew against
            # the policy (k/r/tile config changed) degrades to cold
            tile = pick_tile(n_t, cap=min(1024, max(1, n_t // 8)))
            n_tiles = n_t // tile
            rt_eff = max(1, -(-r_eff // n_tiles))
            if (
                fwd_p is None
                or np.asarray(fwd_p).shape != (n_t, k_eff)
                or state.get("pool_t") is None
                or np.asarray(state["pool_t"]).shape
                != (n_p, n_tiles * rt_eff)
                or state.get("reverse_r") != self.reverse_r
            ):
                self.invalidate()
                return
            for name in ("fwd_p", "fwd_c", "pool_t", "pool_c"):
                setattr(
                    self, f"_{name}",
                    np.array(state[name], _JAX_STATE_DTYPES[name], copy=True),
                )
        self._cand_p = np.array(
            cand_p, _JAX_STATE_DTYPES["cand_p"], copy=True
        )
        self._cand_c = np.array(
            state["cand_c"], _JAX_STATE_DTYPES["cand_c"], copy=True
        )
        for name in ("price", "retired", "p4t", "starve_age"):
            v = state.get(name)
            setattr(
                self, f"_{name}",
                None if v is None else np.array(v, copy=True),
            )
        reserve = state.get("queue_reserve")
        self._reserve = (
            None if reserve is None or np.isnan(reserve[0])
            else float(reserve[0])
        )
        # a journal from before the regime was carried: the reserve's
        regime = state.get("regime")
        self._regime = (
            REGIMES[int(regime[0])] if regime is not None and regime[0] >= 0
            else "queue" if self._reserve is not None else None
        )
        self._warm_solves = int(state["warm_solves"])
        self._dual_age = int(state["dual_age"])
        self._weights_key = tuple(state["weights_key"])

    # ---------------- internals ----------------

    @staticmethod
    def _wkey(weights) -> tuple:
        return (
            float(weights.price), float(weights.load),
            float(weights.proximity), float(weights.priority),
        )

    def _shapes_compatible(self, pf: dict, rf: dict) -> bool:
        old_p, old_r = self._p_fields, self._r_fields
        if old_p is None or old_r is None:
            return False
        return all(
            pf[n].shape == old_p[n].shape for n, _ in _P_SPEC
        ) and all(rf[n].shape == old_r[n].shape for n, _ in _R_SPEC)

    def _ensure_devices(self) -> int:
        """Resolve the requested device count against the host, once.
        Over-asking clamps to what exists — counted and flagged, never
        fatal, never a cross-engine fallback."""
        if self._devices_effective is None:
            avail = jax.local_device_count()
            want = avail if self.devices <= 0 else self.devices
            if want > avail:
                self.device_degraded = True
                self.device_degraded_events += 1
                want = max(avail, 1)
            self._devices_effective = want
            if want > 1:
                from protocol_tpu.parallel.mesh import make_mesh

                self._mesh = make_mesh(want)
        return self._devices_effective

    def _gen(self, pf: dict, rf: dict, weights):
        """One candidate-generation pass: sharded over the device mesh
        when D > 1 and the shard/tile shapes divide, single-device
        otherwise (flagged via ``gen_sharded``). Deterministic for
        fixed inputs — the warm path diffs its output row-wise against
        the carried structure to get the exact changed set.

        The tile is a function of T ONLY — never of D. Reverse-edge
        selection is tile-POOLED (per-tile top-ceil(r/n_tiles), best r
        of the pool: see candidates_topk_reverse), so the candidate
        structure is a function of the global tiling; a D-derived tile
        would silently break the bit-exact D-invariance contract this
        arena's warm carry (and the provenance tag's D-exclusion)
        rests on. The cap keeps the tile no larger than T/8 so a mesh
        of up to 8 devices shards evenly on round task counts; a shape
        where the per-shard count doesn't divide the tile degrades to
        single-device generation with the SAME tile — same bits,
        flagged, never a different structure.

        Side effect: stores the generation PARTS (forward lists + raw
        per-tile reverse contribution pools) on the arena — the
        persistent structure the warm-path repair patches.
        ``approx_recall`` mode stores None:
        ``lax.approx_max_k`` carries no exactness guarantee, so there
        is no repaired==regen contract to honor and those arenas stay
        on the (honest, counted) full-regen path."""
        ep = EncodedProviders(**pf)
        er = EncodedRequirements(**rf)
        T = rf["cpu_cores"].shape[0]
        tile, use_mesh = self._gen_plan(T)
        with_parts = self.approx_recall is None
        fwd = None
        if use_mesh:
            from protocol_tpu.parallel.sparse import (
                candidates_topk_bidir_sharded,
            )

            out = candidates_topk_bidir_sharded(
                ep, er, weights, mesh=self._mesh, k=self.k,
                tile=tile, reverse_r=self.reverse_r,
                extra=self.extra, approx_recall=self.approx_recall,
                with_parts=with_parts,
            )
            if with_parts:
                cand_p, cand_c, *fwd = out
            else:
                cand_p, cand_c = out
            sharded = True
        else:
            if with_parts:
                from protocol_tpu.ops.sparse import (
                    candidates_topk_reverse,
                    merge_reverse_candidates,
                )

                fwd_p, fwd_c, rev_t, rev_c, pool_t, pool_c = (
                    candidates_topk_reverse(
                        ep, er, weights, k=self.k, tile=tile,
                        reverse_r=self.reverse_r, with_pools=True,
                    )
                )
                cand_p, cand_c = merge_reverse_candidates(
                    fwd_p, fwd_c, rev_t, rev_c, extra=self.extra
                )
                fwd = [fwd_p, fwd_c, pool_t, pool_c]
            else:
                cand_p, cand_c = candidates_topk_bidir(
                    ep, er, weights, k=self.k, tile=tile,
                    reverse_r=self.reverse_r, extra=self.extra,
                    approx_recall=self.approx_recall,
                )
            sharded = False
        if fwd is not None:
            self._fwd_p = np.asarray(fwd[0], np.int32)
            self._fwd_c = np.asarray(fwd[1], np.float32)
            self._pool_t = np.asarray(fwd[2], np.int32)
            self._pool_c = np.asarray(fwd[3], np.float32)
        else:
            self._fwd_p = self._fwd_c = None
            self._pool_t = self._pool_c = None
        return (
            np.asarray(cand_p, np.int32),
            np.asarray(cand_c, np.float32),
            sharded,
        )

    def _gen_plan(self, T: int) -> tuple[int, bool]:
        """(tile, use_mesh) for shape T — ONE decision shared by the
        cold generation pass and the warm repair kernels, so a repair
        can never run under a different tiling or mesh choice than the
        pass that produced the structure it is patching."""
        tile = pick_tile(T, cap=min(1024, max(1, T // 8)))
        D = self._ensure_devices()
        use_mesh = (
            self._mesh is not None and T % D == 0 and (T // D) % tile == 0
        )
        return tile, use_mesh

    def _repair(self, pf: dict, rf: dict, weights, dirty_p, dirty_t):
        """Churn-masked structure repair: patch the persistent parts for
        the given dirty global rows and rebuild the merged lists —
        bit-identical to what :meth:`_gen` would produce on the current
        columns (the repaired==regen oracle contract), at O(churn
        scope) instead of O(P*T). Stores the repaired parts and returns
        (cand_p, cand_c, repair-scope stats) for :meth:`_adopt`. Caller
        guarantees parts exist (``approx_recall is None`` and the arena
        is primed)."""
        from protocol_tpu.parallel.sparse import repair_topk_bidir_sharded

        ep = EncodedProviders(**pf)
        er = EncodedRequirements(**rf)
        T = rf["cpu_cores"].shape[0]
        tile, use_mesh = self._gen_plan(T)
        cand_p, cand_c, fwd_p, fwd_c, pool_t, pool_c, stats = (
            repair_topk_bidir_sharded(
                ep, er, weights,
                fwd_p=self._fwd_p, fwd_c=self._fwd_c,
                pool_t=self._pool_t, pool_c=self._pool_c,
                dirty_p=dirty_p, dirty_t=dirty_t,
                reverse_r=self.reverse_r,
                mesh=self._mesh if use_mesh else None,
                tile=tile, extra=self.extra,
                pad_floors=self._repair_pads,
            )
        )
        self._repair_pads = dict(stats.pop("pad_hw"))
        self._fwd_p, self._fwd_c = fwd_p, fwd_c
        self._pool_t, self._pool_c = pool_t, pool_c
        return cand_p, cand_c, stats

    def _maintain(self, pf, rf, weights, dirty_p, dirty_t, **attrs):
        """Warm candidate maintenance for the dirty global rows, shared
        by the batch tick and the single event: the churn-masked repair
        (recompute exactly the flagged forward rows and reverse pools
        and re-merge — bit-identical to a full regen on the current
        columns, without the O(P*T) pass), then :meth:`_adopt`.
        ``approx_recall`` arenas have no parts (no exactness contract
        under approx_max_k) and keep the honest, counted full regen.
        The structure is final for the tick from here on (the solve
        writes ``SOLVE_STATE`` only), which is when an armed
        ``structure_hook`` is called, once. Returns (changed mask,
        stats with the stage walls, sharded, cand_cold_passes)."""
        with _tracer.span(
            "arena.candidates", cold=False, dirty_providers=dirty_p.size,
            dirty_tasks=dirty_t.size, **attrs,
        ):
            if self._fwd_p is not None:
                cand_p, cand_c, rep = self._repair(
                    pf, rf, weights, dirty_p, dirty_t
                )
                sharded = self._gen_plan(rf["cpu_cores"].shape[0])[1]
                cold_passes = 0
            else:
                cand_p, cand_c, sharded = self._gen(pf, rf, weights)
                rep = {}
                cold_passes = 1
            changed = self._adopt(cand_p, cand_c, dirty_t, rep)
        hook, self.structure_hook = self.structure_hook, None
        if hook is not None:
            hook(self.live_state())
        return changed, rep, sharded, cold_passes

    def _adopt(self, cand_p, cand_c, dirty_t, out: dict) -> np.ndarray:
        """Take ``cand_p``/``cand_c`` as the merged lists and return the
        changed-row mask against the PREVIOUS ones (membership moved or
        any cost moved — a superset of "materially cheaper", so clearing
        retirement on it is sound, just occasionally generous). Dirty
        tasks (``dirty_t``, global rows) count as changed and lose
        their seat: it predates the new requirement. Feasibility guard:
        a seat whose provider left the row's list is unseated here (only
        changed rows can have lost one). ``out["diff_ms"]`` is the wall
        of all of it."""
        with _tracer.stage("arena.diff", out, "diff_ms"):
            changed = (
                (cand_p != self._cand_p).any(axis=1)
                | (cand_c != self._cand_c).any(axis=1)
            )
            self._cand_p, self._cand_c = cand_p, cand_c
            if dirty_t.size:
                self._p4t[dirty_t] = -1
                changed[dirty_t] = True
            seat_check = np.flatnonzero(changed & (self._p4t >= 0))
            if seat_check.size:
                in_list = (
                    self._cand_p[seat_check] == self._p4t[seat_check, None]
                ).any(axis=1)
                lost = seat_check[~in_list]
                if lost.size:
                    self._p4t[lost] = -1
        return changed

    def _life(self, p_was, r_was, pf: dict, rf: dict):
        """What the delta about to be applied did to who is there, from
        the ``valid`` columns before (``p_was`` / ``r_was``) and after:
        (stats, the providers that came back, or None). The stats are
        ``arena_rows_left`` / ``arena_rows_joined``, provider rows whose
        ``valid`` went False / True (``arena_rows_moved``: both), and
        ``arena_seats_vacated``, seats of the carried plan the seat
        guard empties (:meth:`_adopt`) because their provider left or
        their task ended. The warm solve prices the providers that came
        back (``assign_auction_sparse_warm``'s ``joined0``)."""
        was = p_was.astype(bool)
        now = pf["valid"].astype(bool)
        left, joined = was & ~now, ~was & now
        ended = r_was.astype(bool) & ~rf["valid"].astype(bool)
        vacated = (self._p4t >= 0) & (
            ended | left[np.maximum(self._p4t, 0)]
        )
        n_left, n_joined = int(left.sum()), int(joined.sum())
        return {
            "arena_rows_left": n_left,
            "arena_rows_joined": n_joined,
            "arena_rows_moved": n_left + n_joined,
            "arena_seats_vacated": int(vacated.sum()),
        }, joined if n_joined else None

    def _ladder(self, P: int, eng: Optional[dict]):
        """Cold/refresh solve stage: the eps-annealed auction ladder
        from scratch duals over the CURRENT candidate structure."""
        regime: dict = {}
        res, price, retired, self._reserve = assign_auction_sparse_scaled(
            self._cand_p, self._cand_c,
            num_providers=P, eps_start=self.eps_start,
            eps_end=self.eps_end, stats_out=eng, with_state=True,
            regime_out=regime, regime0=self._regime,
        )
        self._regime = regime.get("regime")
        return self._readback(res, price, retired, eng)

    @staticmethod
    def _readback(res, price, retired, eng: Optional[dict]):
        """The solve's three results as OWNED host copies. np.array (not
        asarray): asarray over a device buffer hands back a READ-ONLY
        view, and the arena mutates p4t in place on the seat-guard and
        dirty-row paths; the carried structure must stay writable
        across warm ticks. The copies block until the device is done,
        so their wall joins the segments' in ``eng["wait_ms"]``."""
        took: dict = {}
        with _tracer.stage("arena.readback", took, "ms"):
            out = (
                np.array(res.provider_for_task, np.int32),
                np.array(price, np.float32),
                np.array(retired, bool),
            )
        if eng is not None:
            eng["wait_ms"] = round(eng.get("wait_ms", 0.0) + took["ms"], 3)
        return out

    def _warm(
        self, P: int, p4t0: np.ndarray, changed: np.ndarray,
        eng: Optional[dict], joined: Optional[np.ndarray] = None,
    ):
        """Warm solve stage: delta-frontier auction from the carried
        duals. Retirement is cleared for exactly the ``changed`` rows
        (candidates or costs moved, or the seat was re-opened) — the
        warm kernel's documented caller contract; the kernel itself
        applies the uniform price downshift that keeps carried prices
        sound."""
        regime: dict = {}
        res, price, retired, self._reserve = assign_auction_sparse_warm(
            self._cand_p, self._cand_c,
            num_providers=P,
            price0=jnp.asarray(self._price),
            p4t0=jnp.asarray(p4t0),
            eps=self.eps_end,
            retired0=jnp.asarray(self._retired & ~changed),
            stats_out=eng, with_state=True, reserve0=self._reserve,
            joined0=joined, regime0=self._regime, regime_out=regime,
        )
        self._regime = regime.get("regime")
        return self._readback(res, price, retired, eng)

    @staticmethod
    def _count_free(
        pf: dict, rf: dict, p4t: np.ndarray, eng: Optional[dict]
    ) -> None:
        """``eng["free_providers"]``: live providers the plan leaves
        free, the ones the solve's reverse pass answers for (its
        ``free_repriced`` / ``reverse_rounds`` / ``reverse_ms`` ride
        ``eng`` beside it). ``eng["waiting_tasks"]``: live tasks it
        leaves without a provider, the queue pass's (``queue_rounds``,
        ``queue_ms``)."""
        if eng is not None:
            used = np.zeros(pf["valid"].shape[0], bool)
            used[p4t[p4t >= 0]] = True
            eng["free_providers"] = int(
                (pf["valid"].astype(bool) & ~used).sum()
            )
            eng["waiting_tasks"] = int(
                (rf["valid"].astype(bool) & (p4t < 0)).sum()
            )

    def _quality_pass(
        self, rf: dict, p4t, price, prev_p4t, eng: Optional[dict] = None
    ) -> dict:
        took: dict = {}
        with _tracer.stage("arena.quality", took, "quality_ms"):
            stats, self._starve_age = _quality.tick_quality(
                self._cand_p, self._cand_c, p4t, price,
                valid=rf["valid"].astype(bool),
                prev_p4t=prev_p4t,
                starve_age=self._starve_age,
                outcomes=None,
                eng=eng,
                took=took,
            )
        stats.update(took)
        self._last_quality = stats
        return stats

    def _base_stats(self, T: int, gen_sharded: bool) -> dict:
        base = {
            "native_isa": jax_isa(),
            "engine": "jax",
            "jax_devices": int(self._devices_effective or 1),
            "gen_sharded": gen_sharded,
            "device_degraded": self.device_degraded,
            "rows": T,
        }
        if _jitwitness.enabled():
            # compiles observed DURING this solve, per jit entry — the
            # warm-path contract is an empty dict here (perf_gate --jax
            # asserts it); plus the process-lifetime total for obs
            base["jit_compiles"] = _jitwitness.total()
            base["jit_compiles_delta"] = _jitwitness.delta(
                self._jit_mark
            )
            self._jit_mark = _jitwitness.snapshot()
        return base

    def _cold(self, weights, pf, rf, P, T) -> np.ndarray:
        eng: Optional[dict] = {} if obs.enabled() else None
        t0 = time.perf_counter()
        with _tracer.span("arena.candidates", cold=True, tasks=T):
            self._cand_p, self._cand_c, sharded = self._gen(pf, rf, weights)
        t_gen = time.perf_counter()
        with _tracer.span("arena.engine", engine="jax", cold=True):
            p4t, price, retired = self._ladder(P, eng)
        t_solve = time.perf_counter()
        self._count_free(pf, rf, p4t, eng)
        self._p_fields, self._r_fields = pf, rf
        self._owned_cols = set()
        self._weights_key = self._wkey(weights)
        self._price, self._retired, self._p4t = price, retired, p4t
        self._warm_solves = 0
        self._dual_age = 0
        self._starve_age = None
        qual = (
            self._quality_pass(rf, p4t, price, None, eng)
            if obs.enabled() else {}
        )
        self.last_stats = {
            **self._base_stats(T, sharded),
            **qual,
            "cold": True,
            "cand_cold_passes": 1,
            "dirty_providers": P,
            "dirty_tasks": T,
            "changed_rows": T,
            "warm_solves_since_cold": 0,
            "assigned": int((p4t >= 0).sum()),
            "gen_ms": round((t_gen - t0) * 1e3, 3),
            "solve_ms": round((t_solve - t_gen) * 1e3, 3),
            **({f"eng_{k}": v for k, v in eng.items()} if eng else {}),
        }
        return p4t

    # ---------------- streaming entry points ----------------

    def apply_rows(
        self,
        provider_rows: Optional[np.ndarray],
        p_rows: Optional[dict],
        task_rows: Optional[np.ndarray],
        r_rows: Optional[dict],
        weights,
        event_eps_start: Optional[float] = None,
    ) -> np.ndarray:
        """Single-event entry (the stream engine's hot path), same
        contract as the native arena: explicit churned rows, values
        equal to the current columns dropped, the arena's baseline
        updated in place for truly-dirty rows, RuntimeError/ValueError
        on an unprimed arena or a weights mismatch.

        A dirty event pays O(churned rows): the churn-masked repair
        kernels patch exactly the flagged forward rows and reverse
        pools of the persistent structure (``cand_cold_passes: 0``,
        repair-scope counters in ``last_stats``) — same oracle contract
        as the batch warm path. Only ``approx_recall`` arenas (no
        repair twin) still pay a full regen, reported honestly as
        ``cand_cold_passes: 1``. ``event_eps_start`` is accepted for
        signature parity; the jax warm kernel runs one fine-eps phase
        (its own eps-CS repair handles re-seating)."""
        if self._cand_p is None:
            raise RuntimeError(
                "arena not primed for apply_rows: run solve() first "
                "(the persistent candidate structure must exist)"
            )
        if self._weights_key != self._wkey(weights):
            raise ValueError(
                "apply_rows under different weights: the carried "
                "structure was scored under the old weights (re-prime "
                "with a batch solve)"
            )
        t_start = time.perf_counter()
        P = self._p_fields["gpu_count"].shape[0]
        T = self._r_fields["cpu_cores"].shape[0]

        def _narrow(rows, vals, fields, spec, n, side):
            if rows is None or vals is None:
                return np.zeros(0, np.int32)
            rows = np.asarray(rows, np.int64).ravel()
            if rows.size == 0:
                return np.zeros(0, np.int32)
            if rows.min() < 0 or rows.max() >= n:
                raise ValueError(f"event row index out of range [0, {n})")
            dirty = np.zeros(rows.size, bool)
            canon = {}
            for name, dtype in spec:
                v = np.ascontiguousarray(np.asarray(vals[name]), dtype)
                if v.shape[0] != rows.size:
                    raise ValueError(
                        f"event column {name!r} has {v.shape[0]} rows "
                        f"for {rows.size} row indices"
                    )
                canon[name] = v
                diff = fields[name][rows] != v
                dirty |= diff.reshape(rows.size, -1).any(axis=1)
            keep = np.flatnonzero(dirty)
            if keep.size:
                idx = rows[keep]
                for name, _ in spec:
                    key = (side, name)
                    if key not in self._owned_cols:
                        fields[name] = fields[name].copy()
                        self._owned_cols.add(key)
                    fields[name][idx] = canon[name][keep]
            return rows[keep].astype(np.int32)

        p_was = self._p_fields["valid"].copy()
        r_was = self._r_fields["valid"].copy()
        dirty_p = _narrow(
            provider_rows, p_rows, self._p_fields, _P_SPEC, P, "p"
        )
        dirty_t = _narrow(
            task_rows, r_rows, self._r_fields, _R_SPEC, T, "r"
        )
        n_dp, n_dt = int(dirty_p.size), int(dirty_t.size)
        if n_dp == 0 and n_dt == 0:
            self.last_repair_mask = None
            self.last_stats = {
                **self._base_stats(T, False),
                "cold": False, "event": True,
                "cand_cold_passes": 0, "dirty_providers": 0,
                "dirty_tasks": 0, "changed_rows": 0,
                "assigned": int((self._p4t >= 0).sum()),
            }
            return self._p4t.copy()

        eng: Optional[dict] = {} if obs.enabled() else None
        life, joined = self._life(
            p_was, r_was, self._p_fields, self._r_fields
        )
        changed, rep, sharded, cold_passes = self._maintain(
            self._p_fields, self._r_fields, weights, dirty_p, dirty_t,
            event=True,
        )
        t_gen = time.perf_counter()
        with _tracer.span("arena.engine", engine="jax", cold=False):
            p4t, price, retired = self._warm(P, self._p4t, changed, eng, joined)
        t_solve = time.perf_counter()
        self._price, self._retired, self._p4t = price, retired, p4t
        self.last_repair_mask = changed
        self.last_stats = {
            **self._base_stats(T, sharded),
            "cold": False,
            "event": True,
            "cand_cold_passes": cold_passes,
            # scope counters first: the stream-facing "repair_rows"
            # (rows whose merged lists actually changed — what the
            # certificate and EventResult count) overrides the repair
            # kernels' forward-scope counter of the same name
            **rep,
            **life,
            "dirty_providers": n_dp,
            "dirty_tasks": n_dt,
            "changed_rows": int(changed.sum()),
            "repair_rows": int(changed.sum()),
            "assigned": int((p4t >= 0).sum()),
            "gen_ms": round((t_gen - t_start) * 1e3, 3),
            "solve_ms": round((t_solve - t_gen) * 1e3, 3),
            **({f"eng_{k}": v for k, v in eng.items()} if eng else {}),
        }
        return p4t

    def reconcile(self) -> np.ndarray:
        """Full batch re-solve over the CURRENT candidate structure from
        scratch duals — the stream engine's periodic reconciliation.
        The repaired==regen oracle contract makes the current structure
        equal to a from-scratch rebuild on the current columns, so this
        is bit-identical to a cold solve without re-paying a gen pass."""
        if self._cand_p is None:
            raise RuntimeError(
                "arena not primed for reconcile: run solve() first"
            )
        t0 = time.perf_counter()
        P = self._p_fields["gpu_count"].shape[0]
        T = self._r_fields["cpu_cores"].shape[0]
        eng: Optional[dict] = {} if obs.enabled() else None
        prev_p4t = self._p4t.copy() if obs.enabled() else None
        with _tracer.span("arena.engine", engine="jax", reconcile=True):
            p4t, price, retired = self._ladder(P, eng)
        t_solve = time.perf_counter()
        self._price, self._retired, self._p4t = price, retired, p4t
        self._warm_solves = 0
        self._dual_age = 0
        self._starve_age = None
        qual = (
            self._quality_pass(self._r_fields, p4t, price, prev_p4t, eng)
            if obs.enabled() else {}
        )
        self.last_stats = {
            **self._base_stats(T, False),
            **qual,
            "cold": False,
            "reconcile": True,
            "cand_cold_passes": 0,
            "dirty_providers": 0,
            "dirty_tasks": 0,
            "changed_rows": 0,
            "assigned": int((p4t >= 0).sum()),
            "solve_ms": round((t_solve - t0) * 1e3, 3),
            **({f"eng_{k}": v for k, v in eng.items()} if eng else {}),
        }
        return p4t

    # ---------------- the solve ----------------

    def solve(self, ep, er, weights) -> np.ndarray:
        """One marketplace solve. ``ep``/``er`` are EncodedProviders /
        EncodedRequirements (numpy- or jax-backed, or any object with
        the same field names); returns provider_for_task [T] i32."""
        with _tracer.span("arena.solve", engine="jax"):
            return self._solve_impl(ep, er, weights)

    def _solve_impl(self, ep, er, weights) -> np.ndarray:
        # dirty detection: the O(P+T) canonicalisation and value diff
        # over every column that every tick pays before any stage wall
        # starts (a cold tick pays the canonicalisation alone)
        took: dict = {}
        with _tracer.stage("arena.dirty", took, "dirty_ms"):
            pf = _canon(ep, _P_SPEC)
            rf = _canon(er, _R_SPEC)
            P = pf["gpu_count"].shape[0]
            T = rf["cpu_cores"].shape[0]
            warm = (
                P > 0 and T > 0
                and self._shapes_compatible(pf, rf)
                and self._weights_key == self._wkey(weights)
                and self._warm_solves < self.cold_every
            )
            if warm:
                dirty_p = np.flatnonzero(
                    _dirty_rows(pf, self._p_fields, _P_SPEC)
                )
                dirty_t = np.flatnonzero(
                    _dirty_rows(rf, self._r_fields, _R_SPEC)
                )
        if P == 0 or T == 0:
            self.last_stats = {
                "native_isa": jax_isa(), "engine": "jax",
                "cold": True, "assigned": 0,
            }
            return np.full(T, -1, np.int32)
        if not warm:
            return self._cold(weights, pf, rf, P, T)
        n_dp, n_dt = int(dirty_p.size), int(dirty_t.size)
        if (n_dp + n_dt) / (P + T) > self.max_dirty_frac:
            return self._cold(weights, pf, rf, P, T)
        if n_dp == 0 and n_dt == 0:
            # byte-identical marketplace: the carried matching IS the
            # solve — same short-circuit as the native arena, with the
            # carried quality certificate reused verbatim
            self._warm_solves += 1
            qual: dict = {}
            if obs.enabled():
                t_q = time.perf_counter()
                self._starve_age = _quality.starvation_update(
                    self._starve_age, self._p4t,
                    rf["valid"].astype(bool),
                )
                qual = dict(self._last_quality)
                # the carried certificate: no gap was computed
                qual["q_gap_ms"] = 0.0
                qual["churn_rows"] = 0
                qual["churn_ratio"] = 0.0
                qual["starve_max"] = (
                    int(self._starve_age.max())
                    if self._starve_age.size else 0
                )
                qual["starving"] = int((self._starve_age > 0).sum())
                qual["starve_hist"] = _quality.starvation_hist(
                    self._starve_age
                )
                qual["quality_ms"] = round(
                    (time.perf_counter() - t_q) * 1e3, 3
                )
                self._last_quality = qual
            self.last_stats = {
                **self._base_stats(T, False),
                **qual,
                "cold": False,
                "cand_cold_passes": 0,
                "dirty_providers": 0,
                "dirty_tasks": 0,
                "changed_rows": 0,
                "warm_solves_since_cold": self._warm_solves,
                "assigned": int((self._p4t >= 0).sum()),
            }
            return self._p4t.copy()

        eng: Optional[dict] = {} if obs.enabled() else None
        prev_p4t = self._p4t.copy() if obs.enabled() else None
        t_start = time.perf_counter()
        life, joined = self._life(
            self._p_fields["valid"], self._r_fields["valid"], pf, rf
        )
        self._p_fields, self._r_fields = pf, rf
        self._owned_cols = set()

        changed, rep, sharded, cold_passes = self._maintain(
            pf, rf, weights, dirty_p, dirty_t
        )
        t_gen = time.perf_counter()
        dual_refresh = (
            self.dual_refresh_every > 0
            and self._dual_age >= self.dual_refresh_every
        )
        with _tracer.span("arena.engine", engine="jax", cold=False):
            if dual_refresh:
                p4t, price, retired = self._ladder(P, eng)
                self._dual_age = 0
            else:
                p4t, price, retired = self._warm(
                    P, self._p4t, changed, eng, joined
                )
                self._dual_age += 1
        t_solve = time.perf_counter()
        self._count_free(pf, rf, p4t, eng)
        self._price, self._retired, self._p4t = price, retired, p4t
        self._warm_solves += 1
        qual = (
            self._quality_pass(rf, p4t, price, prev_p4t, eng)
            if obs.enabled() else {}
        )
        self.last_stats = {
            **self._base_stats(T, sharded),
            **qual,
            "cold": False,
            "cand_cold_passes": cold_passes,
            **took,
            **rep,
            "dual_refresh": dual_refresh,
            **life,
            "dirty_providers": n_dp,
            "dirty_tasks": n_dt,
            "changed_rows": int(changed.sum()),
            "warm_solves_since_cold": self._warm_solves,
            "assigned": int((p4t >= 0).sum()),
            "gen_ms": round((t_gen - t_start) * 1e3, 3),
            "solve_ms": round((t_solve - t_gen) * 1e3, 3),
            **({f"eng_{k}": v for k, v in eng.items()} if eng else {}),
        }
        return p4t
