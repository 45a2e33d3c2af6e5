"""One run of one cell: set-up, the timed window, the reference check.

A cut-down copy of ``chip_smoke.py``'s ``run_smoke`` and the batch half
of ``_run_pass`` (commit 5134453): one process holds the chip, starts
``services.scheduler_grpc.serve()`` on a loopback port with
checkpoint-before-ack on, and drives it over real gRPC. The smoke's
second pass, stream half and constants are gone; a window of closed-loop
requests and a check against ``reference.py`` took their place. What a
cell is comes from files: ``BENCHMARK.json`` names the configuration and
the traffic mix, ``configs/``, ``traffic/``, ``cells/`` and ``metrics/``
hold them. What a run measures is a list of ticks fixed by those files:
``cells/<cell>.json`` gives the number of ticks of each pool, the same
list on every run, every seed and every commit, and ``--seconds`` is the
cap after which a client sends nothing more.
"""

from __future__ import annotations

import gc
import glob
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

from . import client as wire_client
from . import population, readers, reference, stats, trace_reduce

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Set-up's own constants: how it gets the programs the served path
# builds lazily behind it before the window opens (see ``_run``).
WARMUP_BLOCKED_TASKS = 4    # tasks made unassignable for one tick
WARMUP_OVERSIZE = 2.0       # churn of the one oversized tick a session
# Where the mix has life events the list crosses from a pool with a
# queue to one with idle nodes and back; set-up takes pool 0 into each
# regime for one tick, this share of its live providers deep: twice the
# 1 in 64 under which the solve counts a pool as neither (PERF.md s7).
WARMUP_REGIME_MARGIN = 1 / 32
# Rounds of the window's own loop, one tick a pool, before the window
# opens. A fixed number: the list starts at the same tick on every run
# and every commit, whatever the program builds when. One would do for
# the programs; three keep the accepted cells' lists on the ticks their
# levels were read on (PERF.md, PR 36). The last one has to build
# nothing, or the run fails.
WARMUP_ROUNDS = 3
# The profiled slice of a traced run: the share of client 0's requests
# of the window before which it starts. It ends with the list, and the
# profiler is stopped once the window has closed: stopping serialises
# the trace for 10-300 s and slows every tick that runs beside it by
# 20-50% (PERF.md, PR 36), so no timed tick may. The last tenth of a
# list is about 5 s, the slice the accepted cells have had since PR 25.
TRACE_START_FRAC = 0.9


class BenchFailure(RuntimeError):
    """The run cannot give a result; the message is the one-line reason."""


def _log(t0: float, msg: str) -> None:
    print(f"[bench +{time.perf_counter() - t0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def _load(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_cell(root: str, workload: str) -> dict:
    """Everything that defines ``workload``, found by the names in
    ``BENCHMARK.json``: its entry, its configuration, its traffic mix,
    its number of ticks a pool, the metric declarations and readers, and
    the tables of the trace reduction."""
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchFailure(
            f"unknown workload {workload!r}; BENCHMARK.json has "
            f"{sorted(cells)}"
        )
    entry = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    ticks_file = os.path.join(BENCH_DIR, "cells", workload + ".json")
    if not os.path.exists(ticks_file):
        raise BenchFailure(
            f"the cell {workload!r} has no cells/{workload}.json (the "
            f"ticks of each pool that a run measures)"
        )
    ticks = _load(ticks_file).get("ticks")
    if not isinstance(ticks, int) or isinstance(ticks, bool) or ticks < 1:
        raise BenchFailure(
            f"cells/{workload}.json: \"ticks\" is {ticks!r}, not a whole "
            f"number of ticks a pool"
        )
    return {
        "entry": entry,
        "ticks": ticks,
        "config": _load(os.path.join(root, configs[entry["config"]]["file"])),
        "traffic": _load(os.path.join(
            BENCH_DIR, "traffic", entry["traffic"] + ".json"
        )),
        "end_to_end": bench["end_to_end"],
        "per_layer": bench["per_layer"],
        "readers": readers.load_dir(os.path.join(BENCH_DIR, "metrics")),
        "peaks": _load(os.path.join(BENCH_DIR, "lib", "peaks.json")),
        "layout": _load(os.path.join(BENCH_DIR, "lib", "trace_layout.json")),
    }


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


class _Worker(threading.Thread):
    """One closed-loop client: sends its pools' next ticks round-robin,
    each when the previous ack is in hand, until every one of its pools
    has had ``ticks`` of them (one in a warm-up round). Once the run's
    seconds have passed it sends nothing more: the cap cuts a run that
    is too slow for its list, and its numbers are over what it reached."""

    def __init__(self, run, index: int, pools: list, ticks: int):
        super().__init__(name=f"bench-client-{index}", daemon=True)
        self.run_state = run
        self.index = index
        self.pools = pools
        self.ticks = ticks
        self.acks: list = []
        self.error = None

    def run(self) -> None:
        try:
            self._loop()
        except BaseException as e:  # surfaced by the main thread
            self.error = e

    def _loop(self) -> None:
        import jax

        rs = self.run_state
        period = float(rs["traffic"]["period_s"])
        phase = rs["phases"][self.index]
        prev_ack = None
        for n in range(self.ticks * len(self.pools)):
            due = stats.due_time(rs["open_s"], period, n, prev_ack, phase)
            now = time.perf_counter()
            if now - rs["open_s"] >= rs["seconds"]:
                return
            if due > now:
                time.sleep(due - now)
            pool = self.pools[n % len(self.pools)]
            if self.index == 0:
                rs["tracer"].before_request(n)
            with jax.profiler.TraceAnnotation("bench.request"):
                rec = send_tick(rs, pool, due)
            self.acks.append(rec)
            prev_ack = rec["acked_s"]


def send_tick(rs: dict, pool: dict, due_s: float, delta=None) -> dict:
    """Send ``pool``'s next tick (``delta`` in its place, where set-up
    gives one), wait for its ack and return the ack's record. Once the
    ack's time is taken the journal on disk is kept by a hard link (read
    after the window) and the arena's ``last_stats`` are copied; the
    session is in lockstep with its one client, so nothing moves in
    between."""
    prow, p_vals, trow, r_vals = delta or pool["gen"].next_delta()
    pool["tick"] += 1
    tick = pool["tick"]
    req = wire_client.delta_request(
        pool["sid"], pool["fp"], tick, prow, p_vals, trow, r_vals
    )
    rec = {"pool": pool["index"], "tick": tick, "due_s": due_s, "ok": False}
    rec["sent_s"] = time.perf_counter()
    try:
        resp = pool["client"].assign_delta(req, timeout=600)
        rec["acked_s"] = time.perf_counter()
        rec["ok"] = bool(
            resp.session_ok and not resp.stale and not resp.replayed
        )
        if not rec["ok"]:
            rec["error"] = resp.error or "stale or replayed"
    except Exception as e:  # an RPC failure is a failed request
        rec["acked_s"] = time.perf_counter()
        rec["error"] = f"{type(e).__name__}: {e}"
    pool["deltas"].append((prow, p_vals, trow, r_vals))
    if rec["ok"]:
        rec["plan"] = wire_client.plan_of(resp)
        rec["delta_index"] = len(pool["deltas"])
        rec["journal"] = wire_client.keep_journal(
            rs["ckpt_dir"], rs["proc_id"], pool["sid"],
            os.path.join(rs["keep_dir"], f"{pool['index']}_{tick}.ckpt"),
        )
        session, _why = rs["server"].servicer.sessions.get(
            pool["sid"], pool["fp"]
        )
        rec["stats"] = (
            dict(session.arena.last_stats) if session is not None else {}
        )
    return rec


class _Tracer:
    """Starts the profiler before client 0's request number
    ``start_request`` of the window (a place in the list of ticks, not
    on the clock: a cell that got faster keeps its slice inside its
    window) and stops it when the window has closed, so the slice holds
    whole acks of client 0 and no request runs beside the stopping."""

    def __init__(self, enabled: bool, directory: str, start_request: int):
        self.enabled = enabled
        self.directory = directory
        self.start_request = start_request
        self.started_s = None
        self.stopped_s = None

    def before_request(self, number: int) -> None:
        if not self.enabled or self.started_s is not None:
            return
        if number < self.start_request:
            return
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.started_s = time.perf_counter()

    def finish(self):
        """Stops the profiler. Path of the trace file, or None when no
        trace was taken."""
        if self.started_s is None:
            return None
        import jax

        self.stopped_s = time.perf_counter()
        jax.profiler.stop_trace()
        paths = glob.glob(
            os.path.join(self.directory, "**", "*.xplane.pb"), recursive=True
        )
        return paths[0] if paths else None


def _seam(client) -> dict:
    return {s.name: s.value for s in client.health().seam_metrics}


def _record(rec: dict) -> dict:
    """An ack as the per-layer readers see it: the client's latency
    (from due), wall (from sent) and lateness merged with the arena's
    numeric stage stats of that tick."""
    out = {
        k: v for k, v in rec["stats"].items()
        if isinstance(v, (int, float)) and not isinstance(v, bool)
    }
    out["latency_ms"] = (rec["acked_s"] - rec["due_s"]) * 1e3
    out["wall_ms"] = (rec["acked_s"] - rec["sent_s"]) * 1e3
    out["late_ms"] = (rec["sent_s"] - rec["due_s"]) * 1e3
    return out


def _sample(good: list, n: int, rng) -> list:
    """Indices of the acks the reference judges: the last one to arrive
    and ``n - 1`` others drawn by ``rng``."""
    last = max(range(len(good)), key=lambda i: good[i]["acked_s"])
    rest = [i for i in range(len(good)) if i != last]
    extra = min(n - 1, len(rest))
    return [last] + [int(i) for i in rng.choice(rest, extra, replace=False)]


def _stale_columns(ack: dict, p_cols: dict, r_cols: dict) -> int:
    """How many of the client's columns the journal kept at this ack
    would not restore: missing, or differing in a live row. The journal
    pads its rows to a power of two; the padding must not be valid."""
    if ack.get("journal_cols") is None:
        return len(p_cols) + len(r_cols)
    stale = 0
    for mine, theirs in zip((p_cols, r_cols), ack["journal_cols"]):
        n = mine["valid"].shape[0]
        for name, col in mine.items():
            got = theirs.get(name)
            if got is None or got.shape[0] < n or not np.array_equal(
                got[:n], col
            ):
                stale += 1
        if "valid" in theirs and theirs["valid"][n:].any():
            stale += 1
    return stale


def _judge(cell, pools, good, picks, rng):
    """The reference's numbers over the sampled acks' plans and kept
    journals, each the worst over the sample (of ``queued_tasks`` and
    ``idle_providers``, the pool's regime at an ack, the number of
    sampled acks at which it was not 0)."""
    cfg = cell["config"]
    worst: dict = {}
    for i in picks:
        ack = good[i]
        pool = pools[ack["pool"]]
        p_cols = {n: a.copy() for n, a in pool["start"][0].items()}
        r_cols = {n: a.copy() for n, a in pool["start"][1].items()}
        for prow, p_vals, trow, r_vals in pool["deltas"][: ack["delta_index"]]:
            for name, vals in p_vals.items():
                p_cols[name][prow] = vals
            for name, vals in r_vals.items():
                r_cols[name][trow] = vals
        got = reference.judge_plan(
            p_cols, r_cols, ack["plan"], cfg["solve"]["weights"], rng,
            int(cfg["check"]["subpool_tasks"]),
        )
        got["journal_stale_columns"] = _stale_columns(ack, p_cols, r_cols)
        for regime, count in (("queued_tasks", "judged_queue_acks"),
                              ("idle_providers", "judged_slack_acks")):
            worst[count] = worst.get(count, 0) + (got.pop(regime, 0) > 0)
        for name, value in got.items():
            worst[name] = max(worst.get(name, 0), value)
    return worst


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, t_start: float | None = None) -> dict:
    """Run the cell once and return its result line as a dict.
    ``require_chip=False`` lets tests drive the same code on the CPU
    backend; the command never passes it."""
    t0 = time.perf_counter() if t_start is None else t_start
    # the arena counts its compilations per tick only with the witness on
    witness_before = os.environ.get("PROTOCOL_TPU_JIT_WITNESS")
    os.environ["PROTOCOL_TPU_JIT_WITNESS"] = "1"
    try:
        return _run(cell, seed, seconds, trace, require_chip, t0)
    finally:
        if witness_before is None:
            os.environ.pop("PROTOCOL_TPU_JIT_WITNESS", None)
        else:
            os.environ["PROTOCOL_TPU_JIT_WITNESS"] = witness_before


def _run(cell, seed, seconds, trace, require_chip, t0) -> dict:
    cfg, traffic, entry = cell["config"], cell["traffic"], cell["entry"]

    from protocol_tpu.utils.platform import device_summary, place_compile_cache

    place_compile_cache()
    import jax

    # cache the many sub-second executables too: without this most of
    # the served path's programs are rebuilt in every process
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        device = device_summary()
    except RuntimeError as e:
        raise BenchFailure(f"jax found no usable backend: {e}") from e
    platform, n_dev = device["platform"], device["device_count"]
    _log(t0, f"jax found {platform} ({device['device_kind']} x{n_dev})")
    if require_chip and (platform != "tpu" or n_dev < int(entry["chips"])):
        raise BenchFailure(
            f"the cell needs {entry['chips']} TPU chip(s); jax found "
            f"{platform!r} x{n_dev}"
        )

    from protocol_tpu import obs as obs_pkg
    from protocol_tpu.fleet.fabric import FleetConfig
    from protocol_tpu.services.scheduler_grpc import (
        SchedulerBackendClient,
        serve,
    )

    if not obs_pkg.enabled():
        raise BenchFailure("the observability plane is off (PROTOCOL_TPU_OBS=0)")

    n_pools = int(cfg["pools"])
    in_flight = int(traffic["in_flight"])
    server = None
    clients: list = []
    reduced = None
    with tempfile.TemporaryDirectory(prefix="bench_") as tmp:
        try:
            ckpt_dir = os.path.join(tmp, "ckpt")
            keep_dir = os.path.join(tmp, "kept")
            os.mkdir(keep_dir)
            address = f"127.0.0.1:{wire_client.free_port()}"
            srv = cfg["server"]
            server = serve(
                address, max_workers=int(srv["max_workers"]),
                max_sessions=int(srv["max_sessions"]),
                fleet=FleetConfig(
                    ckpt_dir=ckpt_dir, ckpt_every=int(srv["ckpt_every"]),
                ),
            )
            clients = [
                SchedulerBackendClient(address) for _ in range(in_flight)
            ]
            health = clients[0].health()
            if (health.platform != platform
                    or int(health.device_count) != n_dev):
                raise BenchFailure(
                    f"Health says {health.platform} x{health.device_count}, "
                    f"jax found {platform} x{n_dev}"
                )
            rs = {
                "traffic": traffic, "server": server, "ckpt_dir": ckpt_dir,
                "keep_dir": keep_dir, "proc_id": "p0",
                "seconds": float(seconds),
            }
            # Each client's period starts at a phase of its own, a share
            # of the period: orchestrators tick on independent clocks,
            # so uniform draws, and from the configuration's own seed,
            # so who falls due beside whom is the cell's, the same on
            # every run, every --seed and every commit. (All due at one
            # instant queues every pool behind every other; evenly
            # spaced keeps them apart as no independent clocks would.)
            n_clients = min(in_flight, n_pools)
            rs["phases"] = np.random.default_rng(
                [int(cfg["population_seed"]), n_clients]
            ).random(n_clients)

            # ---- data, a cold open per pool, warm-up acks
            # The marketplaces and their ticks come from the
            # configuration's own seed: relabelling the rows of one
            # marketplace moved the median ack by 20% (PERF.md), so a
            # population from --seed makes every seed another workload.
            # Pool i is marketplace i on every seed (with many pools in
            # flight, who holds which decides who waits for whom);
            # --seed draws which acks and which sub-pool the reference
            # judges, and nothing that is sent.
            # Rows that come and go (the mix's life events, and which
            # rows are live at the open) draw from a generator of their
            # own, so the churn's draws are the same with them or not.
            life = population.life_of(traffic)
            pools = []
            for i in range(n_pools):
                gen = population.Pool(
                    np.random.default_rng(
                        [int(cfg["population_seed"]), i]
                    ),
                    int(cfg["n_providers"]), int(cfg["n_tasks"]),
                    float(traffic["provider_churn"]),
                    float(traffic["task_churn"]),
                    life=life,
                    life_rng=np.random.default_rng(
                        [int(cfg["population_seed"]), i,
                         population.LIFE_STREAM]
                    ),
                    providers_live=cfg.get("providers_live"),
                    tasks_live=cfg.get("tasks_live"),
                )
                pool = {
                    "index": i, "gen": gen, "sid": f"bench@pool{i}",
                    "tick": 0, "client": clients[i % in_flight],
                    "deltas": [],
                }
                fp, err, _plan = wire_client.open_session(
                    pool["client"], cfg["solve"], gen.p_cols, gen.r_cols,
                    pool["sid"],
                )
                if fp is None:
                    raise BenchFailure(
                        f"OpenSession of pool {i} refused: {err}"
                    )
                pool["fp"] = fp
                pools.append(pool)
            _log(t0, f"{n_pools} cold open(s) done")
            def warm(pool, delta):
                rec = send_tick(rs, pool, time.perf_counter(), delta)
                if not rec["ok"]:
                    raise BenchFailure(
                        f"warm-up tick of pool {pool['index']} failed: "
                        f"{rec.get('error')}"
                    )

            def clients_of(ticks):
                return [
                    _Worker(
                        rs, w,
                        [p for p in pools if p["index"] % in_flight == w],
                        ticks,
                    )
                    for w in range(n_clients)
                ]

            def drive(workers):
                for w in workers:
                    w.start()
                for w in workers:
                    w.join()
                for w in workers:
                    if w.error is not None:
                        raise BenchFailure(
                            f"client {w.index} died: {w.error!r}"
                        ) from w.error
                return [a for w in workers for a in w.acks]

            # Programs the served path builds only now and then have to
            # be behind it before the window opens. The sweep of tasks
            # left open: four tasks made unassignable for one tick. The
            # padded shapes a session's repair ratchets up to: an
            # oversized first tick on every session. Where the mix has
            # life events, the regimes its list will visit (the reverse
            # pass's and the queue pass's programs, the cold re-ground
            # on entering the queue): pool 0 with idle nodes for one
            # tick, with a queue for one, and back. Whatever the mix's
            # own concurrency brings: rounds of the window's own loop,
            # one tick a pool.
            none = np.zeros(0, np.int32)
            for rows, vals in pools[0]["gen"].block_tasks(
                    WARMUP_BLOCKED_TASKS):
                warm(pools[0], (none, {}, rows, vals))
            for pool in pools:
                warm(pool, pool["gen"].next_delta(WARMUP_OVERSIZE))
            if life:
                for rows, vals in pools[0]["gen"].regime_visits(
                        WARMUP_REGIME_MARGIN):
                    warm(pools[0], (none, {}, rows, vals))
            rs["tracer"] = _Tracer(False, "", 0)
            for _ in range(WARMUP_ROUNDS):
                rs["open_s"] = time.perf_counter()
                acks = drive(clients_of(ticks=1))
                bad = [a for a in acks if not a["ok"]]
                if bad:
                    raise BenchFailure(
                        f"warm-up tick failed: {bad[0].get('error')}"
                    )
            built = [
                a["stats"].get("jit_compiles_delta") for a in acks
                if a["stats"].get("jit_compiles_delta") != {}
            ]
            if built:
                raise BenchFailure(
                    f"the last of {WARMUP_ROUNDS} warm-up rounds still "
                    f"built programs: {built[0]}"
                )
            for pool in pools:
                # the reference replays the window's deltas from here
                pool["deltas"].clear()
                pool["start"] = pool["gen"].snapshot()
            _log(t0, "warm-up done")
            seam_before = _seam(clients[0])

            # ---- the window: the cell's ticks of every pool
            first_tick = pools[0]["tick"] + 1
            workers = clients_of(cell["ticks"])
            rs["tracer"] = tracer = _Tracer(
                bool(trace), os.path.join(tmp, "trace"),
                int(TRACE_START_FRAC * cell["ticks"] * len(workers[0].pools)),
            )
            rs["open_s"] = open_s = time.perf_counter()
            setup_s = open_s - t0
            acks = drive(workers)
            trace_path = tracer.finish()
            if trace and tracer.started_s is None:
                _log(t0, f"no trace: the cap cut the run before client 0's "
                         f"request {tracer.start_request}, where the "
                         f"profiled slice starts")
            seam_after = _seam(clients[0])
            mem_peak = max(
                ((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.devices()), default=0,
            )
            _log(t0, f"window closed: {len(acks)} requests")
        finally:
            for c in clients:
                c.close()
            if server is not None:
                server.stop(grace=None).wait()
        # the journals kept at each ack, walked whole now that nothing
        # is timed; the judged sample's columns are decoded as well
        good = [a for a in acks if a["ok"]]
        if not good:
            raise BenchFailure(
                f"no request was acknowledged: {acks[0].get('error')}"
            )
        rng = np.random.default_rng([int(seed), 0xC0FFEE])
        picks = _sample(good, int(cfg["check"]["acks"]), rng)
        for i, a in enumerate(good):
            frames = wire_client.read_journal(a["journal"])
            a["journal_ok"] = wire_client.journal_holds(
                frames, pools[a["pool"]]["sid"], a["tick"], a["plan"]
            )
            if i in picks and frames is not None and (
                    wire_client.KIND_SNAPSHOT in frames):
                a["journal_cols"] = wire_client.journal_columns(frames)
        del frames
        if trace_path is not None:
            reduced = trace_reduce.reduce_trace(
                jax.profiler.ProfileData.from_file(trace_path),
                cell["layout"], n_dev,
            )
            # whole acks of every client that landed while the profiler
            # ran, by the host's clock: what the slice's device time paid
            reduced["acks_in_slice"] = sum(
                1 for a in acks if a["ok"]
                and tracer.started_s <= a["acked_s"] <= tracer.stopped_s
            )
            _log(t0, "trace reduced")
    # the program's state is freed before the reference runs
    for pool in pools:
        del pool["client"]
    del server, clients, rs, workers, tracer
    gc.collect()

    failed = len(acks) - len(good)
    e2e = stats.window_metrics(acks, open_s)
    e2e["setup_s"] = setup_s

    # ---- correct: every number compared beside its limit
    limits = cfg["limits"]
    want_isa = f"jax:{platform}"
    numbers = {
        "failed_requests": failed,
        "window_compiles": sum(
            1 for a in good if a["stats"].get("jit_compiles_delta") != {}
        ),
        "off_device_acks": sum(
            1 for a in good
            if a["stats"].get("native_isa") != want_isa
            or a["stats"].get("device_degraded") is not False
            or a["stats"].get("cold")
        ),
        "unflushed_acks": sum(1 for a in good if not a["journal_ok"]),
        "flush_failures": seam_after.get("ckpt_flush_failures", 1.0),
        "arrivals_dropped": sum(p["gen"].arrivals_dropped for p in pools),
    }
    for a in good:
        if a["stats"].get("jit_compiles_delta") != {}:
            _log(t0, f"pool {a['pool']} tick {a['tick']} compiled in the "
                     f"window: {a['stats'].get('jit_compiles_delta')}")
    judged = _judge(cell, pools, good, picks, rng)
    numbers.update(judged)
    _log(t0, f"reference judged {len(picks)} plans")
    checks = {}
    for name, limit in limits.items():
        value = numbers.get(name)
        checks[name] = {"value": value, "limit": limit}
    correct = all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values()
    )
    # which regimes the judged plans were of: counts, held to no limit
    for name in ("judged_queue_acks", "judged_slack_acks"):
        checks[name] = {"value": judged.get(name, 0), "limit": None}

    dev = {
        "platform": platform, "kind": device["device_kind"],
        "count": n_dev, "memory_peak_bytes": int(mem_peak),
    }
    result = {"correct": bool(correct), "attempted": len(acks),
              "failed": failed}
    if trace:
        ctx = {
            "records": [_record(a) for a in good],
            "seam_before": seam_before, "seam_after": seam_after,
            "trace": reduced,
            "shape": {
                "n_tasks": int(cfg["n_tasks"]),
                "n_providers": int(cfg["n_providers"]),
                "k_eff": int(cfg["k_eff"]),
            },
            "peaks": readers.peaks_for(cell["peaks"], device["device_kind"])
            if reduced is not None else {},
        }
        metrics = {}
        for m in cell["per_layer"]:
            if not _reports(m, entry["name"]):
                continue
            value = readers.read_metric(cell["readers"][m["name"]], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        if reduced is not None:
            dev["busy_s"] = reduced["busy_s"]
            dev["window_s"] = reduced["window_s"]
            result["breakdown"] = {
                "device_ops": reduced["device_ops"],
                "idle_gaps": reduced["idle_gaps"],
            }
    else:
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cell["end_to_end"] if _reports(m, entry["name"])
        }
    result["device"] = dev
    asked = cell["ticks"] * n_pools
    result["window"] = {
        "seconds": e2e["window_s"], "acks": e2e["acks"], "ticks": asked,
        "ticks_short": asked - e2e["acks"], "first_tick": first_tick,
    }
    result["checks"] = checks
    return result
