"""Mutation-verified tests for the whole-program analyzer
(scripts/analysis) and the runtime lock witness.

The acceptance contract mirrors the lint engine's: every seeded
violation in the fixture corpus is caught (the `# SEED: <rule>` lines
are the oracle), the clean twins come back silent, the REAL tree is
clean, and each analyzer catches a realistic mutation injected into the
real modules — a reordered acquisition, a dropped lock, an
apply-before-deadline handler, a host-sync-in-jit, a dropped
static_argname, a renamed collective axis, and a device-count-derived
tile policy."""

import json
import pathlib
import subprocess
import sys

import pytest

from scripts.analysis import lockorder, protocolsm, purity, spmd, staging
from scripts.analysis.spec import load_spec, parse_toml_subset
from scripts.lints.base import (
    EXTERNAL_SUPPRESS_TOKENS,
    run_rules,
    stale_escapes,
)

REPO = pathlib.Path(__file__).resolve().parents[1]
FIXTURES = REPO / "scripts" / "analysis" / "fixtures"
SPEC = load_spec()


def seeded_lines(path: pathlib.Path, rule_name: str) -> set:
    return {
        i
        for i, line in enumerate(path.read_text().splitlines(), 1)
        if f"SEED: {rule_name}" in line
    }


# --------------------------------------------------------------------
# spec / toml
# --------------------------------------------------------------------


class TestSpec:
    def test_real_spec_loads_and_is_total(self):
        assert SPEC.ranks["shard"] == SPEC.ranks["session"], (
            "shard and session share a rank: neither may nest the other"
        )
        for key, dom in SPEC.classify_attr.items():
            assert dom in SPEC.ranks, key
        for key, dom in SPEC.classify_class.items():
            assert dom in SPEC.ranks, key
        assert set(SPEC.reentrant) <= set(SPEC.ranks)
        assert SPEC.ladder_markers, "ladder marker table must be committed"

    def test_documented_seam_order_is_encoded(self):
        r = SPEC.ranks
        # the ISSUE 10 ordering contract, as ranks
        assert r["shard"] < r["budget"]          # shard -> budget leaf
        assert r["session"] < r["arena"]         # session -> arena
        assert r["session"] < r["threadpool"]    # locked solve borrows
        assert r["session"] < r["trace"]         # recorder under session
        assert r["registry"] > r["budget"]       # registry is a leaf

    def test_ladder_markers_cover_the_client_contract(self):
        from protocol_tpu.services.scheduler_grpc import (
            _PERMANENT_REFUSALS,
        )

        for marker in _PERMANENT_REFUSALS:
            assert any(
                marker in m or m in marker for m in SPEC.ladder_markers
            ), marker
        assert "RESOURCE_EXHAUSTED" in SPEC.ladder_markers

    def test_toml_subset_parser_matches_shapes(self):
        doc = parse_toml_subset(
            '[a]\nx = 1\n"q.k" = "v"\nflag = true\n'
            '[b]\nitems = ["p", "q"]\nmulti = [\n  "r",\n  "s",\n]\n'
        )
        assert doc == {
            "a": {"x": 1, "q.k": "v", "flag": True},
            "b": {"items": ["p", "q"], "multi": ["r", "s"]},
        }

    def test_external_tokens_stay_in_sync_with_the_analyzer(self):
        from scripts.lints.base import EXTERNAL_SUPPRESS_SCOPES

        assert set(EXTERNAL_SUPPRESS_TOKENS) == {
            lockorder.SUPPRESS, protocolsm.SUPPRESS, purity.SUPPRESS,
            staging.SUPPRESS, spmd.SUPPRESS,
        }
        # the lint engine's scope table must mirror each analyzer's
        # actual roots, or the out-of-scope staleness check drifts
        assert EXTERNAL_SUPPRESS_SCOPES[protocolsm.SUPPRESS] == (
            protocolsm.DEFAULT_ROOTS
        )
        assert EXTERNAL_SUPPRESS_SCOPES[purity.SUPPRESS] == (
            purity.DEFAULT_ROOTS
        )
        # the jax passes share roots AND scope: one Index, one scan set
        assert EXTERNAL_SUPPRESS_SCOPES[staging.SUPPRESS] == (
            staging.DEFAULT_ROOTS
        )
        assert EXTERNAL_SUPPRESS_SCOPES[spmd.SUPPRESS] == (
            spmd.DEFAULT_ROOTS
        )
        assert staging.DEFAULT_ROOTS == purity.DEFAULT_ROOTS
        assert spmd.DEFAULT_ROOTS == purity.DEFAULT_ROOTS
        # the lock pass scans the whole walk: empty scope = everywhere
        assert EXTERNAL_SUPPRESS_SCOPES[lockorder.SUPPRESS] == ()

    def test_spmd_spec_loads_and_is_total(self):
        spec = spmd.load_spmd_spec()
        assert spec.axes == ("p",)
        assert spec.rank == 1
        # the conventional axis carrier names the builders thread
        assert "axis" in spec.axis_aliases
        assert "PROVIDER_AXIS" in spec.axis_aliases
        # the communication surface the sharded kernels actually use
        for op in ("psum", "pmax", "pmin", "all_gather", "axis_index"):
            assert op in spec.collectives, op
        # the D-invariance contract: tile policy guarded, jitter NOT
        # (the sharded gen rebuilds global ids from axis_index*Tl)
        assert "pick_tile" in spec.d_guarded
        assert "tie_jitter_ids" not in spec.d_guarded
        assert "jax.device_count" in spec.d_sources
        # the retrace pass's laundering set matches the real helpers
        from protocol_tpu.parallel import sparse as psparse
        from protocol_tpu.parallel.mesh import pad_to_multiple  # noqa: F401
        from protocol_tpu.ops.sparse import pick_tile  # noqa: F401

        assert hasattr(psparse, "_pow2_pad")
        assert "_pow2_pad" in spec.quantizers
        assert "pick_tile" in spec.quantizers
        assert "pad_to_multiple" in spec.quantizers


# --------------------------------------------------------------------
# fixture corpus: seeds caught exactly, clean twins silent
# --------------------------------------------------------------------


class TestSeededFixtures:
    @pytest.mark.parametrize(
        "runner,rule,bad,ok",
        [
            (
                lambda f: lockorder.run(roots=(str(f),), spec=SPEC),
                "lock-order", "lock_reorder_bad.py", "lock_reorder_ok.py",
            ),
            (
                lambda f: lockorder.run(roots=(str(f),), spec=SPEC),
                "lock-order", "lock_dropped_bad.py", "lock_reorder_ok.py",
            ),
            (
                lambda f: protocolsm.run(roots=(str(f),), spec=SPEC),
                "protocol-sm", "protocol_handler_bad.py",
                "protocol_handler_ok.py",
            ),
            (
                lambda f: purity.run(roots=(str(f),)),
                "jax-purity", "purity_bad.py", "purity_ok.py",
            ),
            (
                lambda f: purity.run(roots=(str(f),)),
                "jax-purity", "purity_calljit_bad.py",
                "purity_calljit_ok.py",
            ),
            (
                lambda f: purity.run(roots=(str(f),)),
                "jax-purity", "purity_repair_bad.py",
                "purity_repair_ok.py",
            ),
            (
                lambda f: staging.run(roots=(str(f),)),
                "jax-retrace", "staging_bad.py", "staging_ok.py",
            ),
            (
                lambda f: spmd.run(roots=(str(f),)),
                "spmd-contract", "spmd_bad.py", "spmd_ok.py",
            ),
        ],
        ids=[
            "lock-reorder", "lock-dropped", "protocol-sm", "jax-purity",
            "jax-purity-callform", "jax-purity-repair", "jax-retrace",
            "spmd-contract",
        ],
    )
    def test_seeds_and_clean_twin(self, runner, rule, bad, ok):
        expected = seeded_lines(FIXTURES / bad, rule)
        assert expected, f"fixture {bad} has no SEED markers"
        findings = runner(FIXTURES / bad)
        assert {f.line for f in findings} == expected
        assert len(findings) == len(expected)  # one finding per seed
        assert all(f.rule == rule for f in findings)
        assert runner(FIXTURES / ok) == []


# --------------------------------------------------------------------
# the real tree: clean, and every pass actually covers it
# --------------------------------------------------------------------


class TestRealTree:
    def test_lock_order_clean_and_graph_nonempty(self):
        an = lockorder.LockOrderAnalyzer(spec=SPEC)
        assert an.run() == []
        graph = set()
        for line in an.graph_lines():
            held, rest = line.split("->")
            graph.add((held.strip(), rest.split("(")[0].strip()))
        # the load-bearing seam edges must be OBSERVED (an empty graph
        # would mean the extractor went blind, not that the tree is
        # clean)
        assert ("shard", "budget") in graph
        assert ("session", "threadpool") in graph
        assert ("session", "trace") in graph

    def test_protocol_clean_on_the_servicer(self):
        ck = protocolsm.ProtocolChecker(spec=SPEC)
        assert ck.run() == []

    def test_purity_clean_and_closure_covers_the_kernels(self):
        pc = purity.PurityChecker()
        assert pc.run() == []
        entries = pc.jit_entries()
        assert len(entries) >= 10, "jit entry discovery went blind"
        reach = pc.closure(entries)
        rels = {pc.index.functions[q].rel for q in reach}
        assert any("ops/assign.py" in r for r in rels)
        assert any("ops/sparse.py" in r for r in rels)
        assert any("sched/tpu_backend.py" in r for r in rels)
        # the jax engine's sharded builders (nested jitted closures in
        # parallel/sparse.py) are trace roots the closure must reach —
        # the mesh kernels the JaxSolveArena generates and repairs with
        assert any(
            "parallel/sparse.py" in q and ".<locals>." in q
            for q in entries
        ), "sharded-builder jit entries went blind"
        assert any("parallel/sparse.py" in r for r in rels)
        # the warm-path repair kernels (ISSUE 18) are call-form jit
        # entries — forward rows, the enter scan (plain + shard_map
        # twin), the per-tile contribution recompute, and the fold
        # replay. A scan that stops seeing them stops guarding the warm
        # hot path.
        for want in (
            "_build_repair_enter.<locals>",
            "_build_repair_enter_sharded.<locals>",
            "_build_repair_forward.<locals>",
            "_build_repair_tile.<locals>",
            "_build_repair_refold.<locals>",
        ):
            assert any(
                "parallel/sparse.py" in q and want in q for q in entries
            ), f"repair jit entry {want} went blind"

    def test_retrace_clean_and_sees_the_compile_keys(self):
        st = staging.StagingChecker()
        assert st.run() == []
        # discovery sanity: the pass saw the same entry set purity does
        entries = st.purity.jit_entries()
        assert len(entries) >= 10
        # the lru_cached sharded builders are compile-key surfaces —
        # an empty builder map would mean R3 went blind
        builders = st._builders(entries)
        assert any(
            "parallel/sparse.py" in q and "_build_sharded" in q
            for q in builders
        ), "sharded-builder compile keys went blind"

    def test_spmd_clean_and_sees_the_sharded_kernels(self):
        sm = spmd.SpmdChecker()
        assert sm.run() == []
        sharded = sm._sharded_functions()
        # every sharded kernel family must be discovered (decorator
        # form in the builders, call form for the repair enter twin)
        rels = {sm.index.functions[q].rel for q in sharded}
        assert "protocol_tpu/parallel/sparse.py" in rels
        assert "protocol_tpu/parallel/auction.py" in rels
        assert "protocol_tpu/parallel/sinkhorn.py" in rels
        assert any("_build_repair_enter_sharded" in q for q in sharded)
        # and the region closure must reach the collective-bearing
        # helpers, or the placement rule (S4) stops meaning anything
        region = sm._sharded_region(sharded)
        assert len(region) > len(sharded)

    def test_the_phase_kernel_has_one_driver(self):
        """``_sparse_auction_phase`` is named by its host loop
        (``_phase_adaptive``) and by the one-phase ``assign_auction_sparse``
        and nowhere else in the program, the scripts or the entry
        points: the eps ladder, the warm solve and the reverse pass all
        go through the one loop. (The round-cost microbench times the
        kernel itself and drives no solve.)"""
        import ast

        from scripts.analysis.callgraph import Index

        idx = Index.build((
            "protocol_tpu", "scripts", "bench.py", "bench_scaling.py",
            "chip_smoke.py", "__graft_entry__.py",
        ))
        users = set()
        for qname, info in idx.functions.items():
            for node in ast.walk(info.node):
                name = getattr(node, "id", None) or getattr(node, "attr", None)
                if name == "_sparse_auction_phase":
                    users.add(qname.split(".<locals>.")[0])
        assert users == {
            "protocol_tpu/ops/sparse.py::_phase_adaptive",
            "protocol_tpu/ops/sparse.py::assign_auction_sparse",
            "scripts/round_cost.py::main",
        }

    def test_cli_clean_and_exit_codes(self):
        ok = subprocess.run(
            [sys.executable, "-m", "scripts.analysis"],
            cwd=REPO, capture_output=True, text=True,
        )
        assert ok.returncode == 0, ok.stdout + ok.stderr
        assert "analysis clean" in ok.stdout
        bad = subprocess.run(
            [sys.executable, "-m", "scripts.analysis", "--graph"],
            cwd=REPO, capture_output=True, text=True,
        )
        assert bad.returncode == 0
        assert "shard" in bad.stdout


# --------------------------------------------------------------------
# mutation verification against the REAL modules
# --------------------------------------------------------------------


class TestRealModuleMutations:
    def test_reordered_acquisition_in_the_fabric_is_caught(self, tmp_path):
        src = (REPO / "protocol_tpu/fleet/fabric.py").read_text()
        mutated = tmp_path / "fabric_mutated.py"
        mutated.write_text(
            src + "\n\nclass RogueFabric(SessionFabric):\n"
            "    def bad_pressure(self):\n"
            "        with self._budget_lock:\n"
            "            self.shards[0].evict('x', reason='pressure')\n"
        )
        findings = lockorder.run(
            roots=(
                str(mutated),
                "protocol_tpu/services/session_store.py",
            ),
            spec=SPEC,
        )
        assert findings, "budget->shard reorder not caught"
        assert any(
            "'shard'" in f.message and "'budget'" in f.message
            for f in findings
        ), findings

    def test_dropped_lock_in_the_store_is_caught(self, tmp_path):
        src = (
            REPO / "protocol_tpu/services/session_store.py"
        ).read_text()
        mutated = tmp_path / "session_store_mutated.py"
        mutated.write_text(
            src + "\n\nclass RogueStore(SessionStore):\n"
            "    def sweep_fast(self):\n"
            "        self._expire_locked()\n"
        )
        findings = lockorder.run(roots=(str(mutated),), spec=SPEC)
        assert any(
            "_expire_locked" in f.message and "no lock held" in f.message
            for f in findings
        ), findings

    def test_apply_before_deadline_in_the_servicer_is_caught(
        self, tmp_path
    ):
        src = (
            REPO / "protocol_tpu/services/scheduler_grpc.py"
        ).read_text()
        deadline = '            self._check_deadline(context, "delta")\n'
        apply_block = (
            "                try:\n"
            '                    with _tracer.span("session.apply_delta"):\n'
            "                        session.apply_delta(\n"
            "                            prow, p_delta, trow, r_delta,\n"
            "                            events=(\n"
            "                                [{\n"
            '                                    "kind": '
            'request.event_kind or "event",\n'
            '                                    "source": '
            "request.event_source,\n"
            '                                    "seq": '
            "int(request.event_seq),\n"
            "                                }]\n"
            "                                if is_event else None\n"
            "                            ),\n"
            "                        )\n"
            "                except ValueError as e:\n"
            "                    context.abort(\n"
            "                        grpc.StatusCode.INVALID_ARGUMENT, "
            "str(e)\n"
            "                    )\n"
        )
        assert deadline in src and apply_block in src
        # the PR 9 mutation: deadline honored after the delta applied
        # (the stream-era handler routes events between the check and
        # the apply, so the mutation MOVES the check past the apply
        # rather than swapping adjacent lines)
        mutated_src = src.replace(deadline, "").replace(
            apply_block,
            apply_block
            + '                self._check_deadline(context, "delta")\n',
        )
        assert mutated_src != src
        mutated = tmp_path / "scheduler_grpc_mutated.py"
        mutated.write_text(mutated_src)
        findings = protocolsm.run(roots=(str(mutated),), spec=SPEC)
        assert any(
            "deadline honored AFTER" in f.message for f in findings
        ), findings
        # the unmutated servicer is clean (re-checked here so this test
        # fails loudly if the needle anchors drift)
        assert protocolsm.run(spec=SPEC) == []

    def test_host_sync_in_jit_is_caught(self, tmp_path):
        src = (REPO / "protocol_tpu/ops/assign.py").read_text()
        needle = "    _, _, owner, p4t = lax.while_loop(cond, body, state0)\n"
        assert needle in src  # assign_auction body anchor
        mutated = tmp_path / "assign_mutated.py"
        mutated.write_text(src.replace(
            needle, needle + "    _host = float(p4t.sum().item())\n", 1
        ))
        findings = purity.run(roots=(str(mutated),))
        assert any(".item()" in f.message for f in findings), findings

    def test_dropped_static_argname_is_caught(self, tmp_path):
        src = (REPO / "protocol_tpu/ops/sparse.py").read_text()
        needle = 'static_argnames=("k", "tile", "approx_recall")'
        assert needle in src  # candidates_topk anchor
        mutated = tmp_path / "sparse_mutated.py"
        mutated.write_text(src.replace(
            needle, 'static_argnames=("tile", "approx_recall")', 1
        ))
        findings = staging.run(roots=(str(mutated),))
        assert any(
            "'k' outside static_argnames" in f.message
            for f in findings
        ), findings
        # the unmutated module is clean (anchor-drift tripwire)
        assert staging.run(
            roots=("protocol_tpu/ops/sparse.py",)
        ) == []

    def test_renamed_collective_axis_is_caught(self, tmp_path):
        src = (REPO / "protocol_tpu/parallel/sparse.py").read_text()
        needle = "lax.axis_index(axis)"  # the threaded axis carrier
        assert needle in src
        mutated = tmp_path / "parallel_sparse_mutated.py"
        mutated.write_text(src.replace(needle, 'lax.axis_index("q")', 1))
        findings = spmd.run(roots=(str(mutated),))
        assert any(
            "axis 'q'" in f.message and "axis_index" in f.message
            for f in findings
        ), findings

    def test_device_count_in_tile_policy_is_caught(self, tmp_path):
        src = (REPO / "protocol_tpu/parallel/jax_arena.py").read_text()
        needle = "tile = pick_tile(T, cap=min(1024, max(1, T // 8)))"
        assert needle in src  # _gen_plan computes tile before D
        mutated = tmp_path / "jax_arena_mutated.py"
        mutated.write_text(src.replace(
            needle,
            "D = self._ensure_devices()\n        "
            "tile = pick_tile(T, cap=min(1024, max(1, T // D)))",
            1,
        ))
        findings = spmd.run(roots=(str(mutated),))
        assert any(
            "derives from the device count" in f.message
            for f in findings
        ), findings
        # the unmutated arena is clean
        assert spmd.run(
            roots=("protocol_tpu/parallel/jax_arena.py",)
        ) == []


# --------------------------------------------------------------------
# runtime lock witness
# --------------------------------------------------------------------


class TestLockWitness:
    @pytest.fixture(autouse=True)
    def _armed(self, monkeypatch):
        from protocol_tpu.utils import lockwitness

        monkeypatch.setenv("PROTOCOL_TPU_LOCK_WITNESS", "1")
        lockwitness.reset()
        yield
        lockwitness.reset()

    def test_disabled_returns_plain_lock(self, monkeypatch):
        import threading

        from protocol_tpu.utils import lockwitness

        monkeypatch.delenv("PROTOCOL_TPU_LOCK_WITNESS", raising=False)
        lock = lockwitness.make_lock("shard")
        assert isinstance(lock, type(threading.Lock()))

    def test_spec_order_passes_reverse_order_records(self):
        from protocol_tpu.utils import lockwitness as lw

        shard = lw.make_lock("shard")
        budget = lw.make_lock("budget")
        with shard:
            with budget:
                pass
        assert lw.violations() == []
        with budget:
            with shard:
                pass
        v = lw.violations()
        assert len(v) == 1
        assert v[0]["acquiring"] == "shard"
        assert ("budget", SPEC.ranks["budget"]) in v[0]["held"]

    def test_same_rank_never_nests(self):
        from protocol_tpu.utils import lockwitness as lw

        a, b = lw.make_lock("shard"), lw.make_lock("shard")
        with a:
            with b:
                pass
        assert len(lw.violations()) == 1

    def test_reentrant_domain_may_reenter_itself(self):
        from protocol_tpu.utils import lockwitness as lw

        ledger = lw.make_rlock("ledger")
        with ledger:
            with ledger:  # RLock semantics: same instance, fine
                pass
        assert lw.violations() == []

    def test_bare_acquire_release_and_locked(self):
        from protocol_tpu.utils import lockwitness as lw

        lock = lw.make_lock("session")
        assert lock.acquire()
        assert lock.locked()
        lock.release()
        assert not lock.locked()
        assert lw.violations() == []

    def test_strict_mode_raises(self, monkeypatch):
        from protocol_tpu.utils import lockwitness as lw

        monkeypatch.setenv("PROTOCOL_TPU_LOCK_WITNESS", "strict")
        budget, shard = lw.make_lock("budget"), lw.make_lock("shard")
        with budget:
            with pytest.raises(lw.LockOrderViolation):
                with shard:
                    pass

    def test_fleet_locks_are_witnessed_under_env(self):
        from protocol_tpu.fleet.fabric import SessionFabric
        from protocol_tpu.utils.lockwitness import WitnessedLock

        fabric = SessionFabric(shards=2, max_sessions=4)
        assert isinstance(fabric._budget_lock, WitnessedLock)
        assert isinstance(fabric.shards[0]._lock, WitnessedLock)

    def test_lazy_module_lock_decides_at_first_use(self, monkeypatch):
        """Module-global locks (trace _claim_lock, _PROFILE_LOCK) are
        created at import time — before any fixture can arm the
        witness. LazyLock defers the decision to first acquisition, so
        arming the env AFTER import still witnesses them."""
        from protocol_tpu.utils import lockwitness as lw

        monkeypatch.delenv("PROTOCOL_TPU_LOCK_WITNESS", raising=False)
        lazy = lw.LazyLock("trace-claim")  # "import time": disarmed
        monkeypatch.setenv("PROTOCOL_TPU_LOCK_WITNESS", "1")
        with lazy:
            pass  # first use: resolves to a WitnessedLock
        assert isinstance(lazy._lock, lw.WitnessedLock)
        # and the order is asserted through the lazy shim: trace-claim
        # (38) acquired while holding tracer (52) violates
        tracer = lw.make_lock("tracer")
        with tracer:
            with lazy:
                pass
        assert len(lw.violations()) == 1

    def test_reentrant_runtime_sites_are_witnessed(self):
        # KVStore is the reentrant-domain site importable without the
        # optional cryptography dependency (the ledger mirrors it)
        from protocol_tpu.store.kv import KVStore
        from protocol_tpu.utils.lockwitness import WitnessedLock

        store = KVStore()
        assert isinstance(store._lock, WitnessedLock)
        assert store._lock.reentrant


# --------------------------------------------------------------------
# stale-escape audit + SARIF (satellites)
# --------------------------------------------------------------------


class TestStaleEscapeAudit:
    def test_stale_escape_is_reported(self, tmp_path):
        f = tmp_path / "mod.py"
        f.write_text(
            "import numpy as np\n"
            "def solve(P, T):\n"
            "    return P + T  # lint: dense-ok\n"
        )
        findings = run_rules(roots=(str(f),))
        assert [x.rule for x in findings] == ["stale-escape"]
        assert "suppresses no finding" in findings[0].message
        assert findings[0].line == 3

    def test_consumed_escape_is_not_reported(self, tmp_path):
        f = tmp_path / "mod.py"
        f.write_text(
            "import numpy as np\n"
            "def solve(P, T):\n"
            "    return np.zeros((P, T))  # lint: dense-ok\n"
        )
        assert run_rules(roots=(str(f),)) == []

    def test_unknown_token_is_reported(self, tmp_path):
        f = tmp_path / "mod.py"
        f.write_text("x = 1  # lint: bogus-ok\n")
        findings = run_rules(roots=(str(f),))
        assert [x.rule for x in findings] == ["stale-escape"]
        assert "unknown escape token" in findings[0].message

    def test_analyzer_tokens_are_not_the_lint_engines_business(self):
        lines = ["x = 1  # lint: lock-order-ok"]
        assert stale_escapes("mod.py", lines, set()) == []

    def test_out_of_scope_analyzer_token_is_stale(self):
        # a purity escape in a file the purity pass never scans: no
        # engine could ever consume it, so the lint audit reports it
        lines = ["x = 1  # lint: purity-ok"]
        findings = stale_escapes(
            "protocol_tpu/services/session_store.py", lines, set()
        )
        assert [f.rule for f in findings] == ["stale-escape"]
        assert "outside the owning analyzer's scan scope" in (
            findings[0].message
        )
        # the same escape inside the purity scope is the analyzer's
        # business, not the lint engine's
        assert stale_escapes(
            "protocol_tpu/ops/assign.py", lines, set()
        ) == []

    def test_analyzer_audits_its_own_stale_escape(self, tmp_path):
        from scripts.analysis.__main__ import _audit_own_escapes

        f = tmp_path / "mod.py"
        f.write_text("x = 1  # lint: purity-ok\n")
        rel = str(f.relative_to(f.anchor))
        # absolute path trick: _audit_own_escapes joins REPO/rel, so
        # feed it a file INSIDE the repo instead
        target = REPO / "scripts" / "analysis" / "fixtures"
        probe = target / "_stale_probe_tmp.py"
        probe.write_text("x = 1  # lint: purity-ok\n")
        try:
            rel = str(probe.relative_to(REPO))
            findings = _audit_own_escapes({rel}, "purity-ok", set())
            assert [x.rule for x in findings] == ["stale-escape"]
            consumed = {(rel, 1)}
            assert _audit_own_escapes({rel}, "purity-ok", consumed) == []
        finally:
            probe.unlink()

    def test_real_tree_audit_is_clean(self):
        # every committed escape still suppresses something — the audit
        # rides the full engine run
        assert [
            f for f in run_rules() if f.rule == "stale-escape"
        ] == []


class TestSarif:
    def test_shared_emitter_shape(self):
        from scripts.lints.base import Finding
        from scripts.lints.sarif import to_sarif

        doc = to_sarif(
            [Finding("lock-order", "a/b.py", 7, "boom")],
            "scripts.analysis",
        )
        assert doc["version"] == "2.1.0"
        run = doc["runs"][0]
        assert run["tool"]["driver"]["name"] == "scripts.analysis"
        assert run["tool"]["driver"]["rules"][0]["id"] == "lock-order"
        res = run["results"][0]
        assert res["ruleId"] == "lock-order"
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"] == "a/b.py"
        assert loc["region"]["startLine"] == 7

    def test_lints_cli_writes_sarif(self, tmp_path):
        out = tmp_path / "lints.sarif"
        proc = subprocess.run(
            [sys.executable, "-m", "scripts.lints", "--sarif", str(out)],
            cwd=REPO, capture_output=True, text=True,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        doc = json.loads(out.read_text())
        assert doc["runs"][0]["tool"]["driver"]["name"] == "scripts.lints"
        assert doc["runs"][0]["results"] == []

    def test_analysis_cli_writes_sarif_with_findings(self, tmp_path):
        out = tmp_path / "analysis.sarif"
        proc = subprocess.run(
            [sys.executable, "-m", "scripts.analysis",
             "--sarif", str(out)],
            cwd=REPO, capture_output=True, text=True,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        doc = json.loads(out.read_text())
        assert doc["runs"][0]["tool"]["driver"]["name"] == (
            "scripts.analysis"
        )
