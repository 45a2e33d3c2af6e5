"""First-class JAX engine (ISSUE 17): the warm-solve arena behind the
native engine interface.

Contracts under test, at unit grain:

  - the arena's native-parity surface (cold/warm/short-circuit flows,
    honest ``cand_cold_passes`` reporting, heavy-churn cold fallback,
    unprimed/weights-mismatch refusals);
  - the regen-exactness contract (a warm chain's candidate structure is
    bit-identical to a from-scratch rebuild on the current columns);
  - device-count INVARIANCE of sharded generation (D=1 == D=4 == D=8,
    bit for bit, through ``jax.shard_map`` on the conftest's virtual
    8-device CPU mesh) — the property that makes the
    warm carry sound across device-count changes;
  - degradation INSIDE the engine: over-asking for devices clamps with
    a counted, non-fatal provenance flag, never a silent native
    fallback;
  - export/restore of the warm chain (checkpoint + migration seam),
    including the honest cold re-ground on a foreign backend tag;
  - engine selection through every surface: the arena factory, the
    session kernel string, the matcher kwarg, golden-trace replay, and
    the gRPC drain/restart checkpoint cycle.

The CI-grade gates (full golden replay identity, warm-carry speedup
floor, assigned-fraction floor vs native) live in ``perf_gate.py
--jax``.
"""

import dataclasses
import os

import numpy as np
import pytest

import jax

from protocol_tpu.ops.cost import CostWeights
from protocol_tpu.parallel.jax_arena import JaxSolveArena, jax_isa

from tests.test_sparse import encode_random_marketplace

GOLDEN_JAX = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "artifacts", "golden_trace_512x512_jax.trace",
)


def _unique_seats(p4t: np.ndarray) -> None:
    pos = p4t[p4t >= 0]
    assert np.unique(pos).size == pos.size


def _marketplace(seed=3, P=96, T=64):
    return encode_random_marketplace(seed, P, T)


def _bump_price(ep, rows, delta=0.25):
    price = np.array(ep.price, copy=True)
    price[list(rows)] += delta
    return dataclasses.replace(ep, price=price)


class TestJaxArenaWarmChain:
    def test_cold_solve_contract(self):
        ep, er = _marketplace()
        arena = JaxSolveArena(k=16)
        p4t = arena.solve(ep, er, CostWeights())
        _unique_seats(p4t)
        s = arena.last_stats
        assert s["engine"] == "jax"
        assert s["cold"] is True
        assert s["cand_cold_passes"] == 1
        assert s["native_isa"] == jax_isa() == "jax:cpu"
        assert s["assigned"] == int((p4t >= 0).sum()) > 0

    def test_byte_identical_marketplace_short_circuits(self):
        ep, er = _marketplace()
        arena = JaxSolveArena(k=16)
        first = arena.solve(ep, er, CostWeights())
        again = arena.solve(ep, er, CostWeights())
        np.testing.assert_array_equal(first, again)
        s = arena.last_stats
        assert s["cold"] is False
        assert s["cand_cold_passes"] == 0
        assert s["changed_rows"] == 0
        assert s["warm_solves_since_cold"] == 1

    def test_warm_churn_repairs_without_cold_pass(self):
        """A dirty provider rides the warm REPAIR path: zero full gen
        passes, and the stats carry the honest repair-scope counters
        (recomputed forward rows / reverse pools, visited-cell
        fraction) instead of a regen claim."""
        ep, er = _marketplace()
        arena = JaxSolveArena(k=16)
        arena.solve(ep, er, CostWeights())
        p4t = arena.solve(_bump_price(ep, [5]), er, CostWeights())
        _unique_seats(p4t)
        s = arena.last_stats
        assert s["cold"] is False
        assert s["cand_cold_passes"] == 0  # churn-masked repair, not regen
        assert s["dirty_providers"] == 1
        assert s["dirty_tasks"] == 0
        assert s["repair_rows"] >= 0 and s["repair_providers"] >= 1
        assert 0.0 < s["visited_cells_frac"] < 1.0

    def test_approx_recall_keeps_honest_regen_path(self):
        """approx_max_k selection has no exactness contract, so approx
        arenas carry no repair parts and a dirty warm tick still pays
        (and reports) one full gen pass."""
        ep, er = _marketplace()
        arena = JaxSolveArena(k=16, approx_recall=0.95)
        arena.solve(ep, er, CostWeights())
        assert arena._fwd_p is None
        arena.solve(_bump_price(ep, [5]), er, CostWeights())
        s = arena.last_stats
        assert s["cold"] is False
        assert s["cand_cold_passes"] == 1
        assert "visited_cells_frac" not in s

    def test_regen_equals_cold_rebuild_bit_for_bit(self):
        """The regen-exactness contract: after a churned warm tick the
        carried candidate structure equals a fresh arena's cold build
        on the same columns — no drifting cache, ever."""
        ep, er = _marketplace()
        arena = JaxSolveArena(k=16)
        arena.solve(ep, er, CostWeights())
        ep2 = _bump_price(ep, [1, 7, 11])
        arena.solve(ep2, er, CostWeights())

        fresh = JaxSolveArena(k=16)
        fresh.solve(ep2, er, CostWeights())
        np.testing.assert_array_equal(arena._cand_p, fresh._cand_p)
        np.testing.assert_array_equal(arena._cand_c, fresh._cand_c)

    def test_reconcile_matches_cold_ladder(self):
        """reconcile() re-solves the current structure from scratch
        duals: bit-identical to a cold solve on the current columns
        (regen exactness means the structures agree), without paying
        the gen pass."""
        ep, er = _marketplace()
        arena = JaxSolveArena(k=16)
        arena.solve(ep, er, CostWeights())
        arena.solve(_bump_price(ep, [2]), er, CostWeights())
        p4t = arena.reconcile()
        s = arena.last_stats
        assert s["reconcile"] is True and s["cand_cold_passes"] == 0

        fresh = JaxSolveArena(k=16)
        ref = fresh.solve(_bump_price(ep, [2]), er, CostWeights())
        np.testing.assert_array_equal(p4t, ref)

    def test_heavy_churn_falls_back_to_cold(self):
        ep, er = _marketplace()
        arena = JaxSolveArena(k=16, max_dirty_frac=0.1)
        arena.solve(ep, er, CostWeights())
        arena.solve(_bump_price(ep, range(48)), er, CostWeights())
        assert arena.last_stats["cold"] is True

    def test_weights_change_regrounds_cold(self):
        ep, er = _marketplace()
        arena = JaxSolveArena(k=16)
        arena.solve(ep, er, CostWeights())
        arena.solve(ep, er, CostWeights(price=2.0))
        assert arena.last_stats["cold"] is True

    def test_apply_rows_refusals(self):
        ep, er = _marketplace()
        arena = JaxSolveArena(k=16)
        with pytest.raises(RuntimeError, match="not primed"):
            arena.apply_rows(None, None, None, None, CostWeights())
        arena.solve(ep, er, CostWeights())
        with pytest.raises(ValueError, match="different weights"):
            arena.apply_rows(
                None, None, None, None, CostWeights(price=3.0)
            )

    def test_apply_rows_event_flow(self):
        from protocol_tpu.native.arena import _canon, _P_SPEC

        ep, er = _marketplace()
        arena = JaxSolveArena(k=16)
        base = arena.solve(ep, er, CostWeights())

        # no-op event (values equal the current columns): short-circuit
        pf = _canon(ep, _P_SPEC)
        rows = np.array([4], np.int32)
        vals = {n: np.asarray(pf[n][rows]) for n, _ in _P_SPEC}
        p4t = arena.apply_rows(rows, vals, None, None, CostWeights())
        np.testing.assert_array_equal(p4t, base)
        assert arena.last_stats["dirty_providers"] == 0
        assert arena.last_stats["cand_cold_passes"] == 0

        # a real reprice: dirty, O(churned rows) structure repair +
        # warm solve — zero full gen passes, repair mask set
        vals["price"] = np.asarray(vals["price"]) + 0.5
        p4t = arena.apply_rows(rows, vals, None, None, CostWeights())
        _unique_seats(p4t)
        s = arena.last_stats
        assert s["event"] is True and s["dirty_providers"] == 1
        assert s["cand_cold_passes"] == 0
        assert s["repair_providers"] >= 1
        assert s["visited_cells_frac"] < 1.0
        assert arena.last_repair_mask is not None
        # the event's repaired structure equals a fresh cold build on
        # the updated columns (the repaired==regen oracle contract)
        ep2 = _bump_price(ep, [4], delta=0.5)
        fresh = JaxSolveArena(k=16)
        fresh.solve(ep2, er, CostWeights())
        np.testing.assert_array_equal(arena._cand_p, fresh._cand_p)
        np.testing.assert_array_equal(arena._cand_c, fresh._cand_c)


class TestJitCacheWitness:
    """Runtime twin of the jax-retrace static pass: compilations per
    jit entry, counted by the ``protocol_tpu.utils.jitwitness`` patch
    that ``protocol_tpu/ops/__init__.py`` installs before any kernel
    decorator runs. ``perf_gate --jax`` arms it and fails on ANY
    recompile after the warm chain's warm-up boundary."""

    def test_shape_churn_counts_a_recompile_cache_hit_does_not(self):
        import jax.numpy as jnp

        from protocol_tpu.utils import jitwitness

        mark = jitwitness.snapshot()

        @jax.jit
        def _witness_probe(x):
            return x * 2

        _witness_probe(jnp.zeros(8, jnp.float32))
        _witness_probe(jnp.zeros(8, jnp.float32))  # cache hit
        d = jitwitness.delta(mark)
        entries = [k for k in d if "_witness_probe" in k]
        assert len(entries) == 1, d
        assert d[entries[0]] == 1  # one trace, not two
        _witness_probe(jnp.zeros(16, jnp.float32))  # forced shape churn
        assert jitwitness.delta(mark)[entries[0]] == 2

    def test_warm_repair_tick_is_compile_free(self):
        """The warm-path economics the witness gates: the FIRST warm
        repair tick may engage lazily-built kernels; a repeat tick with
        the same churn profile must replay the compiled cache only."""
        from protocol_tpu.utils import jitwitness

        ep, er = _marketplace()
        arena = JaxSolveArena(k=16)
        arena.solve(ep, er, CostWeights())
        arena.solve(_bump_price(ep, [5]), er, CostWeights())  # warm-up
        mark = jitwitness.snapshot()
        arena.solve(_bump_price(ep, [9]), er, CostWeights())
        assert arena.last_stats["cand_cold_passes"] == 0
        assert jitwitness.delta(mark) == {}, (
            "a settled warm repair tick hit the tracer"
        )

    def test_gate_fails_under_injected_warm_tick_retrace(self):
        """The perf_gate assertion, demonstrated without paying a 4096
        chain: a deliberately shape-churned 'warm tick' is a counted
        recompile, and the gate's failure predicate trips on it."""
        import jax.numpy as jnp

        from protocol_tpu.utils import jitwitness
        from scripts.perf_gate import _warm_recompile_failures

        @jax.jit
        def _retrace_probe(x):
            return x + 1

        _retrace_probe(jnp.zeros(8, jnp.float32))  # warm-up compile
        mark = jitwitness.snapshot()
        _retrace_probe(jnp.zeros(32, jnp.float32))  # the injected retrace
        delta = jitwitness.delta(mark)
        assert delta, "witness missed the injected retrace"
        failures = _warm_recompile_failures(delta, budget=0)
        assert failures and "hit the tracer" in failures[0]
        assert "_retrace_probe" in failures[0]
        # and the green path: an empty delta produces no failure
        assert _warm_recompile_failures({}, budget=0) == []

    def test_last_stats_surface_is_env_gated(self, monkeypatch):
        from protocol_tpu.utils import jitwitness

        monkeypatch.delenv("PROTOCOL_TPU_JIT_WITNESS", raising=False)
        ep, er = _marketplace()
        arena = JaxSolveArena(k=16)
        arena.solve(ep, er, CostWeights())
        assert "jit_compiles" not in arena.last_stats

        monkeypatch.setenv("PROTOCOL_TPU_JIT_WITNESS", "1")
        assert jitwitness.enabled()
        armed = JaxSolveArena(k=16)
        armed.solve(ep, er, CostWeights())
        s = armed.last_stats
        assert s["jit_compiles"] >= 1  # this process traced SOMETHING
        assert isinstance(s["jit_compiles_delta"], dict)
        # a byte-identical re-solve short-circuits: no tracing at all
        armed.solve(ep, er, CostWeights())
        assert armed.last_stats["jit_compiles_delta"] == {}


class TestDeviceInvarianceAndDegradation:
    """Satellite 4: sharded generation's D-invariance at arena grain,
    and the degrade-inside-the-engine contract."""

    @pytest.mark.parametrize("D", [2, 4, 8])
    def test_sharded_gen_is_device_count_invariant(self, D):
        ep, er = _marketplace(seed=9, P=128, T=64)
        ref = JaxSolveArena(k=16, devices=1)
        sharded = JaxSolveArena(k=16, devices=D)
        p_ref = ref.solve(ep, er, CostWeights())
        p_d = sharded.solve(ep, er, CostWeights())
        assert ref.last_stats["gen_sharded"] is False
        assert sharded.last_stats["gen_sharded"] is True
        assert sharded.last_stats["jax_devices"] == D
        np.testing.assert_array_equal(ref._cand_p, sharded._cand_p)
        np.testing.assert_array_equal(ref._cand_c, sharded._cand_c)
        np.testing.assert_array_equal(p_ref, p_d)
        np.testing.assert_array_equal(ref.price, sharded.price)

        # the warm tick stays on the invariant too — and both sides
        # ride the repair path, not a regen
        ep2 = _bump_price(ep, [3])
        np.testing.assert_array_equal(
            ref.solve(ep2, er, CostWeights()),
            sharded.solve(ep2, er, CostWeights()),
        )
        assert ref.last_stats["cand_cold_passes"] == 0
        assert sharded.last_stats["cand_cold_passes"] == 0
        np.testing.assert_array_equal(ref._fwd_c, sharded._fwd_c)
        np.testing.assert_array_equal(ref._pool_t, sharded._pool_t)

    @pytest.mark.parametrize("D", [2, 4])
    def test_apply_rows_rides_repair_at_many_devices(self, D):
        """Stream events over the SHARDED repair path: a dirty event on
        a D-device arena patches the structure with the sharded repair
        kernels (zero cold passes) and lands exactly the structure a
        fresh cold build at the same D produces."""
        from protocol_tpu.native.arena import _P_SPEC, _canon

        ep, er = _marketplace(seed=9, P=128, T=64)
        arena = JaxSolveArena(k=16, devices=D)
        arena.solve(ep, er, CostWeights())
        assert arena.last_stats["gen_sharded"] is True

        pf = _canon(ep, _P_SPEC)
        rows = np.array([7], np.int32)
        vals = {n: np.asarray(pf[n][rows]) for n, _ in _P_SPEC}
        vals["price"] = np.asarray(vals["price"]) + 0.5
        p4t = arena.apply_rows(rows, vals, None, None, CostWeights())
        _unique_seats(p4t)
        s = arena.last_stats
        assert s["event"] is True and s["cand_cold_passes"] == 0
        assert s["gen_sharded"] is True and s["repair_providers"] >= 1

        fresh = JaxSolveArena(k=16, devices=D)
        fresh.solve(_bump_price(ep, [7], delta=0.5), er, CostWeights())
        np.testing.assert_array_equal(arena._cand_p, fresh._cand_p)
        np.testing.assert_array_equal(arena._cand_c, fresh._cand_c)
        np.testing.assert_array_equal(arena._pool_c, fresh._pool_c)

    @pytest.mark.slow
    def test_sharded_gen_invariant_at_16k(self):
        """The acceptance shape (ISSUE 17): D=1 and D=4 produce the
        identical candidate structure at 16k. Generation only — the
        solve's D-independence is pinned by the fast tests above and
        the tick is ~30 s per side at this scale."""
        import bench
        from protocol_tpu.native.arena import _P_SPEC, _R_SPEC, _canon

        n = 16384
        ep = bench.synth_providers(np.random.default_rng(2), n)
        er = bench.synth_requirements(np.random.default_rng(3), n)
        pf, rf = _canon(ep, _P_SPEC), _canon(er, _R_SPEC)
        g1 = JaxSolveArena(devices=1)
        cp1, cc1, sh1 = g1._gen(pf, rf, CostWeights())
        g4 = JaxSolveArena(devices=4)
        cp4, cc4, sh4 = g4._gen(pf, rf, CostWeights())
        assert sh1 is False and sh4 is True
        np.testing.assert_array_equal(cp1, cp4)
        np.testing.assert_array_equal(cc1, cc4)

    def test_indivisible_task_count_degrades_to_single_device(self):
        """T % D != 0: generation runs single-device (flagged), still
        the jax engine, still the same bit-exact structure."""
        ep, er = _marketplace(seed=9, P=96, T=63)
        arena = JaxSolveArena(k=16, devices=4)
        arena.solve(ep, er, CostWeights())
        assert arena.last_stats["engine"] == "jax"
        assert arena.last_stats["gen_sharded"] is False

        ref = JaxSolveArena(k=16, devices=1)
        ref.solve(ep, er, CostWeights())
        np.testing.assert_array_equal(ref._cand_p, arena._cand_p)

    def test_device_overask_clamps_counted_never_native(self):
        """Asking for more devices than the host exposes (the 'missing
        accelerator' shape: kernel jax:64 on an 8-device host) clamps
        to what exists with a counted non-fatal flag. The solve is
        still a jax solve — bit-identical to devices=all — NEVER a
        silent fallback to the native engine."""
        avail = jax.local_device_count()
        ep, er = _marketplace(seed=9, P=128, T=64)
        arena = JaxSolveArena(k=16, devices=avail * 8)
        p4t = arena.solve(ep, er, CostWeights())
        assert arena.device_degraded is True
        assert arena.device_degraded_events == 1
        s = arena.last_stats
        assert s["engine"] == "jax"  # degraded INSIDE the engine
        assert s["device_degraded"] is True
        assert s["jax_devices"] == avail

        ref = JaxSolveArena(k=16, devices=0)  # 0 = all visible
        np.testing.assert_array_equal(
            ref.solve(ep, er, CostWeights()), p4t
        )
        assert ref.device_degraded is False

    def test_mesh_kernels_build_under_jax_shard_map(self, monkeypatch):
        """Every mesh kernel family stages through ``jax.shard_map``
        itself, with nothing version-shaped in between: a spy on the
        promoted API sees the dense auction, the sharded Sinkhorn and
        the sharded candidate generator each hand it their mesh, and
        the retired ``parallel/_compat`` seam is gone."""
        import importlib

        from protocol_tpu.parallel import auction, make_mesh, sinkhorn, sparse

        seen = []
        real = jax.shard_map

        def spy(fun, **kw):
            seen.append((kw["mesh"], kw["check_vma"]))
            return real(fun, **kw)

        monkeypatch.setattr(jax, "shard_map", spy)
        mesh = make_mesh(2)
        er_treedef = jax.tree.structure(_marketplace(P=16, T=16)[1])
        builders = (
            (auction._build_sharded_dense_auction, (mesh, "p", 0.01, 8)),
            (sinkhorn._build_sharded_sinkhorn,
             (mesh, "p", (1.0, 1.0, 0.001, 0.0), 0.05, 2, 8, 16)),
            (sparse._build_sharded_gen,
             (mesh, "p", dataclasses.astuple(CostWeights()), 16, 8, 8, 8,
              4, 1, None, er_treedef)),
        )
        try:
            for build, args in builders:
                build.cache_clear()
                assert callable(build(*args))
        finally:
            for build, _ in builders:
                build.cache_clear()  # drop the closures built over the spy
        assert [m for m, _ in seen] == [mesh] * len(builders)
        with pytest.raises(ModuleNotFoundError):
            importlib.import_module("protocol_tpu.parallel._compat")


class TestExportRestore:
    def test_roundtrip_continues_bit_identically(self):
        ep, er = _marketplace()
        arena = JaxSolveArena(k=16)
        arena.solve(ep, er, CostWeights())
        ep2 = _bump_price(ep, [5])
        arena.solve(ep2, er, CostWeights())
        state = arena.export_state()
        assert state["native_isa"] == jax_isa()

        other = JaxSolveArena(k=16)
        other.restore_state(ep2, er, state)
        ep3 = _bump_price(ep, [5, 9])
        got = other.solve(ep3, er, CostWeights())
        want = arena.solve(ep3, er, CostWeights())
        np.testing.assert_array_equal(got, want)
        assert other.last_stats["cold"] is False  # warm chain continued
        np.testing.assert_array_equal(other.price, arena.price)

    def test_export_is_a_copy_not_an_alias(self):
        ep, er = _marketplace()
        arena = JaxSolveArena(k=16)
        arena.solve(ep, er, CostWeights())
        state = arena.export_state()
        state["price"][:] = -1
        assert not np.array_equal(state["price"], arena.price)

    def test_foreign_backend_tag_regrounds_cold(self):
        """A carry exported under another float pipeline (the native
        engine, or jax on a different XLA backend) is refused into an
        honest cold re-ground — never warm-continued on costs this
        engine didn't score."""
        ep, er = _marketplace()
        arena = JaxSolveArena(k=16)
        arena.solve(ep, er, CostWeights())
        state = arena.export_state()
        state["native_isa"] = "avx2"  # a native-arena export

        other = JaxSolveArena(k=16)
        other.restore_state(ep, er, state)
        other.solve(ep, er, CostWeights())
        assert other.last_stats["cold"] is True

    def test_candidate_width_mismatch_regrounds_cold(self):
        ep, er = _marketplace()
        arena = JaxSolveArena(k=16)
        arena.solve(ep, er, CostWeights())
        state = arena.export_state()

        other = JaxSolveArena(k=8)  # narrower structure: carry invalid
        other.restore_state(ep, er, state)
        other.solve(ep, er, CostWeights())
        assert other.last_stats["cold"] is True

    def test_restored_carry_continues_on_repair_path(self):
        """The persistent parts ride export/restore: a restored warm
        chain's next dirty tick runs the churn-masked repair (zero cold
        passes), not a regen — and lands the same structure the
        exporting arena reaches."""
        ep, er = _marketplace()
        arena = JaxSolveArena(k=16)
        arena.solve(ep, er, CostWeights())
        state = arena.export_state()
        for name in ("fwd_p", "fwd_c", "pool_t", "pool_c"):
            assert state[name] is not None

        other = JaxSolveArena(k=16)
        other.restore_state(ep, er, state)
        ep2 = _bump_price(ep, [3])
        got = other.solve(ep2, er, CostWeights())
        assert other.last_stats["cand_cold_passes"] == 0
        assert other.last_stats["repair_providers"] >= 1
        want = arena.solve(ep2, er, CostWeights())
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(other._fwd_p, arena._fwd_p)
        np.testing.assert_array_equal(other._pool_c, arena._pool_c)

    def test_pre_repair_carry_regrounds_cold(self):
        """A carry exported before the repair parts existed (an old
        checkpoint: merged lists only) degrades to an honest cold
        re-ground — never a shape error, never a warm continuation
        that would regenerate parts against a stale merge."""
        ep, er = _marketplace()
        arena = JaxSolveArena(k=16)
        arena.solve(ep, er, CostWeights())
        state = arena.export_state()
        for name in ("fwd_p", "fwd_c", "pool_t", "pool_c"):
            del state[name]  # what a pre-repair export looks like

        other = JaxSolveArena(k=16)
        other.restore_state(ep, er, state)
        other.solve(ep, er, CostWeights())
        assert other.last_stats["cold"] is True

    def test_part_shape_skew_regrounds_cold(self):
        """Part-width skew (reverse_r config changed between export and
        restore) is refused like a foreign tag — cold, not a crash."""
        ep, er = _marketplace()
        arena = JaxSolveArena(k=16, reverse_r=8)
        arena.solve(ep, er, CostWeights())
        state = arena.export_state()

        other = JaxSolveArena(k=16, reverse_r=4)
        other.restore_state(ep, er, state)
        other.solve(ep, er, CostWeights())
        assert other.last_stats["cold"] is True


class TestEngineSelectionSurfaces:
    def test_arena_factory(self):
        from protocol_tpu.services.session_store import make_solve_arena

        arena = make_solve_arena("jax", k=16, threads=2)
        assert isinstance(arena, JaxSolveArena)
        assert arena.devices == 2  # the suffix is the DEVICE count
        assert arena.engine == "jax"

    def test_session_kernel_string(self):
        from protocol_tpu.services.session_store import (
            parse_session_kernel,
        )

        assert parse_session_kernel("jax") == ("jax", 0)
        assert parse_session_kernel("jax:4") == ("jax", 4)
        assert parse_session_kernel("jax:x") is None

    def test_replay_engine_string(self):
        from protocol_tpu.trace.replay import parse_engine

        assert parse_engine("jax") == ("jax", 0)
        assert parse_engine("jax:2") == ("jax", 2)

    def test_matcher_kwarg_bad_suffix_refused(self):
        from protocol_tpu.sched.tpu_backend import TpuBatchMatcher
        from protocol_tpu.store import StoreContext

        with pytest.raises(ValueError, match="jax device suffix"):
            TpuBatchMatcher(
                StoreContext.new_test(), native_engine="jax:x"
            )

    def test_matcher_engages_jax_arena(self):
        """TpuBatchMatcher(native_engine='jax') routes phase 1 through
        the jax arena as a first-class engine — no native_fallback
        required — and the steady state doesn't flap."""
        import random

        from protocol_tpu.models.task import (
            SchedulingConfig,
            Task,
            TaskRequest,
        )
        from protocol_tpu.sched.tpu_backend import TpuBatchMatcher
        from protocol_tpu.store import (
            NodeStatus,
            OrchestratorNode,
            StoreContext,
        )
        from tests.test_encoding import random_specs

        rng = random.Random(5)
        store = StoreContext.new_test()
        for i in range(12):
            store.node_store.add_node(
                OrchestratorNode(
                    address=f"0xjx{i:02d}",
                    status=NodeStatus.HEALTHY,
                    compute_specs=random_specs(rng),
                )
            )
        store.task_store.add_task(
            Task.from_request(
                TaskRequest(
                    name="jx-b",
                    image="img",
                    scheduling_config=SchedulingConfig(
                        plugins={"tpu_scheduler": {"replicas": ["4"]}}
                    ),
                )
            )
        )
        m = TpuBatchMatcher(
            store, min_solve_interval=0.0, native_engine="jax",
        )
        m.refresh()
        assert m.last_solve_stats["kernel"] == "jax_arena"
        assert m.last_solve_stats["arena_cold"] is True
        assert m.last_solve_stats["arena_engine"] == "jax"
        first = dict(m._assignment)
        m.mark_dirty()
        m.refresh()
        assert m.last_solve_stats["arena_cold"] is False
        assert m.last_solve_stats["arena_changed_rows"] == 0
        assert m._assignment == first

    @pytest.mark.skipif(
        not os.path.exists(GOLDEN_JAX), reason="no committed jax golden"
    )
    def test_golden_replay_identity_smoke(self):
        """The committed jax golden replays bit-identically under
        engine=jax (first ticks — the full 9-tick identity + floors
        run in ``perf_gate.py --jax`` and the CI replay job)."""
        from protocol_tpu.trace.replay import replay

        rep = replay(GOLDEN_JAX, engine="jax", max_ticks=3)
        assert rep["divergence"] is None
        assert rep["verified_ticks"] == rep["ticks"] == 3


class TestGrpcAndCheckpoint:
    """The gRPC kernel surface end to end: sessions solve on the jax
    arena, drain flushes its warm state through the engine-blind
    checkpoint frames, and a restarted servicer resumes the SAME warm
    chain (no cold reopen herd)."""

    def test_drain_restart_resumes_jax_warm(self, tmp_path):
        from protocol_tpu.fleet.fabric import FleetConfig
        from protocol_tpu.parallel.jax_arena import JaxSolveArena
        from protocol_tpu.services.scheduler_grpc import (
            RemoteBatchMatcher,
            drain,
            serve,
        )
        from tests.test_faults import (
            _assert_shadow_matches_server,
            _free_port,
        )
        from tests.test_scheduler_grpc import _pool_world

        port = _free_port()
        addr = f"127.0.0.1:{port}"
        fleet = FleetConfig(shards=2, ckpt_dir=str(tmp_path))
        server = serve(addr, fleet=fleet)
        store = _pool_world()
        m = RemoteBatchMatcher(
            store, addr, min_solve_interval=0.0, wire="v2",
            native_fallback=True, native_engine="jax",
            retry_base_s=0.01,
        )
        try:
            m.refresh()
            m.refresh()
            assert m._session["tick"] == 1
            sess = server.servicer.sessions.get(
                m._session["id"], m._session["fp"]
            )[0]
            assert isinstance(sess.arena, JaxSolveArena)

            flushed = drain(server)
            assert flushed == 1
            assert list(tmp_path.glob("**/*.ckpt"))

            server = serve(addr, fleet=fleet)
            seam = server.servicer.seam.snapshot()
            assert seam.get("session_session_restored") == 1

            m.refresh()
            snap = m.seam.snapshot()
            assert m._session["tick"] == 2
            assert "session_session_reopen" not in snap  # warm resume
            sess = server.servicer.sessions.get(
                m._session["id"], m._session["fp"]
            )[0]
            assert isinstance(sess.arena, JaxSolveArena)
            assert m._assignment
            _assert_shadow_matches_server(m, server)
        finally:
            m.client.close()
            server.stop(grace=None)
