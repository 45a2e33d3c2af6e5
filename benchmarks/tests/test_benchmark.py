"""Tests of the yardstick: the files BENCHMARK.json names, the window's
arithmetic, the readers, the trace reduction, the reference, and a whole
run on the CPU backend at a small size, sound and with each fault.

    python -m pytest benchmarks/tests -q        (about a minute)
"""

import copy
import json
import os
import re
import shutil
import subprocess
import sys
import zlib
from types import SimpleNamespace as NS

import numpy as np
import pytest

from lib import client, faults, harness, population, readers, reference
from lib import stats, trace_reduce

ROOT = os.path.dirname(harness.BENCH_DIR)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_names_and_units_use_only_the_allowed_characters():
    b = _bench()
    named = b["configs"] + b["workloads"] + b["end_to_end"] + b["per_layer"]
    for entry in named:
        assert NAME.match(entry["name"]), entry["name"]
    for w in b["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])
    assert all(0.01 <= m["bound"] <= 0.25 for m in b["end_to_end"])


def test_every_cell_finds_its_files_and_every_metric_its_cells():
    b = _bench()
    e2e = {m["name"]: m for m in b["end_to_end"]}
    layers = {}
    for w in b["workloads"]:
        cell = harness.load_cell(ROOT, w["name"])
        assert cell["config"]["name"] == w["config"]
        assert cell["traffic"]["name"] == w["traffic"]
        assert set(cell["config"]["reduced"]) == set(
            next(c for c in b["configs"] if c["name"] == w["config"])
            ["reduced"]
        )
        for m in b["per_layer"]:
            spec = cell["readers"][m["name"]]
            for key in ("layer", "unit", "moves"):
                assert spec[key] == m[key], (m["name"], key)
            layers.setdefault(m["layer"], []).append(m["name"])
            if harness._reports(m, w["name"]):
                assert harness._reports(e2e[m["moves"]], w["name"])
    assert "setup_s" in e2e and len(layers) >= 5


def test_percentiles_due_times_and_a_stall_that_moves_every_metric():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile([5], 90) == 5
    xs = list(range(1, 102))
    assert stats.percentile(xs, 90) == pytest.approx(np.percentile(xs, 90))
    # a closed loop is due at the previous ack, a periodic one at the
    # later of the boundary and the previous ack
    assert stats.due_time(10.0, 0.0, 3, 12.5) == 12.5
    assert stats.due_time(10.0, 2.0, 3, 12.5) == 16.0
    assert stats.due_time(10.0, 2.0, 3, 17.0) == 17.0
    assert stats.due_time(10.0, 2.0, 0, None) == 10.0

    def window(stall):
        acks, t = [], 0.0
        for i in range(20):
            took = 1.0 + (stall if i >= 9 else 0.0)
            acks.append({"due_s": t, "sent_s": t, "acked_s": t + took,
                         "ok": True})
            t += took
        return stats.window_metrics(acks, 0.0)

    calm, stalled = window(0.0), window(0.5)
    assert calm["acks_per_s"] == pytest.approx(1.0)
    assert stalled["ack_p50_ms"] > calm["ack_p50_ms"]
    assert stalled["acks_per_s"] < calm["acks_per_s"]
    # a failed request counts in no latency and in no rate
    acks = [{"due_s": 0.0, "sent_s": 0.0, "acked_s": 1.0, "ok": True},
            {"due_s": 1.0, "sent_s": 1.0, "acked_s": 2.0, "ok": False}]
    assert stats.window_metrics(acks, 0.0)["acks_per_s"] == 0.5


def test_the_generic_reader_on_canned_records():
    specs = readers.load_dir(os.path.join(harness.BENCH_DIR, "metrics"))
    records = [
        {"wall_ms": 100.0, "late_ms": 1.0, "latency_ms": 101.0,
         "gen_ms": 10.0, "solve_ms": 60.0,
         "eng_rounds_total": 512, "quality_ms": 2.0},
        {"wall_ms": 140.0, "late_ms": 3.0, "latency_ms": 143.0,
         "gen_ms": 20.0, "solve_ms": 80.0,
         "eng_rounds_total": 1024, "quality_ms": 4.0},
        {"wall_ms": 90.0, "late_ms": 2.0, "latency_ms": 92.0},  # no stages
    ]
    ctx = {
        "records": records, "trace": None, "shape": {}, "peaks": {},
        "seam_before": {"decode_ms_sum": 5.0},
        "seam_after": {"decode_ms_sum": 11.0},
    }
    got = {n: readers.read_metric(s, ctx) for n, s in specs.items()}
    assert got["solve_ms_per_ack"] == 70.0
    assert got["repair_ms_per_ack"] == 15.0
    assert got["auction_rounds_per_ack"] == 768.0
    assert got["quality_ms_per_ack"] == 3.0
    assert got["host_rest_ms_per_ack"] == 35.0  # (30 + 40) / 2
    assert got["wire_decode_ms_per_ack"] == 2.0
    assert got["generator_late_ms"] == pytest.approx(2.8)
    # nothing to read is nothing, never a 0
    assert got["device_idle_pct"] is None and got["solve_roofline"] is None
    with pytest.raises(KeyError):
        readers.peaks_for(harness._load(
            os.path.join(harness.BENCH_DIR, "lib", "peaks.json")), "cpu")


def _profile():
    def ev(name, start_us, dur_us):
        return NS(name=name, start_ns=start_us * 1e3, duration_ns=dur_us * 1e3)

    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[
            ev("fusion.1", 100, 50), ev("fusion.2", 120, 80),   # overlap
            ev("copy.3", 400, 100), ev("zero", 450, 0),
        ]),
        NS(name="XLA Modules", events=[
            ev("jit__sparse_auction_phase(123)", 100, 100),
            ev("jit__sparse_auction_phase(456)", 400, 50),
            ev("jit_repair(7)", 450, 50),
        ]),
    ])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.request", 0, 300), ev("bench.request", 350, 250),
        ev("other", 0, 1000),
    ])])
    return NS(planes=[device, host, NS(name="/host:metadata", lines=[])])


def test_the_trace_reduction_on_a_synthetic_profile():
    layout = harness._load(
        os.path.join(harness.BENCH_DIR, "lib", "trace_layout.json"))
    assert trace_reduce.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace_reduce.gaps([(1, 2), (4, 5)], 0, 6) == [
        (0, 1), (2, 4), (5, 6)]
    red = trace_reduce.reduce_trace(_profile(), layout, 1)
    assert red["busy_s"] == pytest.approx(200e-6)       # 100-200, 400-500
    assert red["window_s"] == pytest.approx(600e-6)     # 0 .. 600
    assert red["module_s"]["jit__sparse_auction_phase"] == pytest.approx(
        150e-6)
    assert red["device_ops"][0][0] == "jit__sparse_auction_phase"
    red["acks_in_slice"] = 2    # the harness counts them by its clock
    longest = red["idle_gaps"][0]
    assert longest == ["requests_in_flight_1", pytest.approx(200e-6)]
    # the readers on top of it: idle share, and a roofline from shapes
    idle = readers.read_trace({"kind": "idle_pct"}, red, {}, {})
    assert idle == pytest.approx(100 * (1 - 200 / 600))
    read = {"kind": "roofline", "bound": "hbm_bytes_per_s",
            "modules": ["auction"],
            "bytes": {"per_task_candidate": 12, "per_provider": 8}}
    shape = {"n_tasks": 1000, "n_providers": 1000, "k_eff": 80}
    assert readers.roofline_bytes(read["bytes"], shape) == 968000
    share = readers.read_trace(read, red, shape, {"hbm_bytes_per_s": 819e9})
    assert share == pytest.approx(100 * (2 * 968000 / 819e9) / 150e-6)
    read["modules"] = ["no_such_module"]
    assert readers.read_trace(read, red, shape, {"hbm_bytes_per_s": 1}) is None


def test_the_reference_agrees_with_the_programs_cost_and_judges_plans():
    from protocol_tpu.ops.cost import CostWeights, cost_matrix
    from protocol_tpu.ops.encoding import (
        EncodedProviders,
        EncodedRequirements,
    )

    rng = np.random.default_rng(3)
    p, r = population.providers(rng, 96), population.requirements(rng, 64)
    p["valid"][5] = r["valid"][7] = p["has_location"][3] = False
    p["gpu_model_id"][9] = -1
    w = {"price": 1.0, "load": 1.0, "proximity": 0.001, "priority": 0.0}
    cost, ok = reference.block_costs(p, r, np.arange(96), np.arange(64), w)
    theirs, mask = cost_matrix(
        EncodedProviders(**p), EncodedRequirements(**r), CostWeights())
    assert (ok == np.asarray(mask).T).all() and 0.05 < ok.mean() < 0.6
    assert np.abs(np.where(ok, cost - np.asarray(theirs).T, 0)).max() < 1e-3

    from scipy.optimize import linear_sum_assignment
    rows, cols = linear_sum_assignment(np.where(ok, cost, 1e6))
    plan = np.full(64, -1, np.int32)
    plan[rows] = np.where(ok[rows, cols], cols, -1)
    good = reference.judge_plan(p, r, plan, w, np.random.default_rng(0), 64)
    assert good["dup_providers"] == good["infeasible_pairs"] == 0
    assert good["subpool_gap"] == pytest.approx(0.0, abs=1e-9)
    seated = np.flatnonzero(plan >= 0)
    twice = plan.copy()
    twice[seated[0]] = twice[seated[1]]
    assert reference.judge_plan(
        p, r, twice, w, np.random.default_rng(0), 64)["dup_providers"] == 1
    wrong = plan.copy()
    wrong[seated[0]] = int(np.flatnonzero(~ok[seated[0]])[0])
    assert reference.judge_plan(
        p, r, wrong, w, np.random.default_rng(0), 64)["infeasible_pairs"] >= 1
    far = plan.copy()
    far[0] = 96
    assert reference.judge_plan(
        p, r, far, w, np.random.default_rng(0), 64)["out_of_range"] == 1


def test_the_journal_walk_on_written_journals(tmp_path):
    import hashlib
    import struct

    from protocol_tpu.proto import scheduler_pb2 as pb
    from protocol_tpu.proto import wire

    sid, plan = "bench@pool3", np.array([2, -1, 0], np.int32)

    def frame(kind, payload, deflate=False):
        body = zlib.compress(payload) if deflate else payload
        return client._HEADER.pack(
            kind, int(deflate), len(body), zlib.crc32(body)) + body

    meta = json.dumps({"session_id": sid, "tick": 17, "pad": "x" * 400})
    resp = pb.AssignResponseV2(
        provider_for_task=wire.blob(plan, np.int32)).SerializeToString()
    outcome = (struct.pack("<I", len(resp)) + resp
               + json.dumps({"tick": 17}).encode())
    whole = (b"PTTRACE1" + frame(1, meta.encode(), True)
             + frame(2, b"columns") + frame(6, b"arena state" * 9, True)
             + frame(4, outcome))
    d = tmp_path / "p0"
    d.mkdir()
    path = d / (hashlib.sha1(sid.encode()).hexdigest()[:24] + ".ckpt")
    path.write_bytes(whole)
    kept = client.keep_journal(
        str(tmp_path), "p0", sid, str(tmp_path / "kept.ckpt"))
    (d / "next.tmp").write_bytes(b"the next tick's journal")
    os.replace(d / "next.tmp", path)        # the kept link is unmoved
    assert client.keep_journal(
        str(tmp_path), "p0", "bench@pool4", str(tmp_path / "no")) is None
    frames = client.read_journal(kept)
    assert sorted(frames) == [1, 2, 4, 6]
    assert client.journal_holds(frames, sid, 17, plan)
    assert not client.journal_holds(frames, sid, 18, plan)          # older
    assert not client.journal_holds(frames, sid, 17, plan[::-1])    # other
    body_left_out = {k: v for k, v in frames.items() if k in (1, 4)}
    assert not client.journal_holds(body_left_out, sid, 17, plan)
    assert not client.journal_holds(None, sid, 17, plan)
    for torn in (whole[:-3], whole + b"x", whole[:40] + b"!" + whole[41:]):
        (tmp_path / "torn").write_bytes(torn)
        assert client.read_journal(str(tmp_path / "torn")) is None
    assert client.read_journal(None) is None


def _small():
    """The committed cell at a size a test run can hold: one client
    over two pools of 256 rows. The two limits that are readings of the
    cell's own size get the small size's: sound runs on the CPU read a
    gap of up to 0.047 and 0.8% unassigned there, the capped solve
    0.42-0.49 and the open tail 9-11% (eight and two seeds)."""
    cell = copy.deepcopy(harness.load_cell(ROOT, "pool-large.ticks"))
    cell["config"].update(n_providers=256, n_tasks=256, pools=2)
    cell["config"]["check"].update(acks=6, subpool_tasks=256)
    cell["config"]["limits"].update(subpool_gap=0.2, unassigned_frac=0.05)
    cell["traffic"].update(task_churn=0.02)
    return cell


@pytest.fixture(scope="module")
def sound_run():
    return harness.run_cell(
        _small(), 2**31 + 7, 3.0, False,
        require_chip=False)


def test_a_whole_run_on_the_cpu_gives_the_lines_keys(sound_run):
    r = sound_run
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "checks"
    assert r["failed"] == 0 and r["attempted"] >= 2
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"ack_p50_ms", "acks_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["platform"] == "cpu"
    json.dumps(r)


@pytest.mark.parametrize("fault,number", [
    ("control", "unflushed_acks"),
    ("journal_body_dropped", "unflushed_acks"),
    ("state_unchanged", "journal_stale_columns"),
    ("answer_altered", "infeasible_pairs"),
    ("rounds_capped", "subpool_gap"),
    ("tail_left_open", "unassigned_frac"),
])
def test_correct_comes_out_false_with_the_timed_path_broken(fault, number):
    with faults.planted(fault, _small()) as cell:
        r = harness.run_cell(cell, 11, 3.0, False, require_chip=False)
    assert r["correct"] is False
    check = r["checks"][number]
    assert check["value"] > check["limit"]


def _run_py(cwd, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"),
         "--workload", "pool-large.ticks", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout,
    )


def test_the_command_prints_no_result_without_a_tpu():
    proc = _run_py(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "benchmark FAILED" in proc.stderr


def test_the_command_fails_where_only_the_benchmark_is(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(
        harness.BENCH_DIR, tmp_path / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    proc = _run_py(str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
