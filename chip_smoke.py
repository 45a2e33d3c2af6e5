#!/usr/bin/env python
"""Chip smoke: one scheduler session end to end on the accelerator.

    python chip_smoke.py [--seed N]

The quickest proof that the served path still starts on the chip. ONE
process holds the device and plays both sides: it starts the gRPC
backend with ``services.scheduler_grpc.serve()`` (what ``python -m
protocol_tpu.serve scheduler`` calls) on a loopback port with
checkpoint-before-ack on, and drives it over real gRPC with the wire-v2
session client ``fleet/loadgen.py`` uses. Traffic, in order, over one
pool of ``ROWS`` providers x ``ROWS`` tasks from ``trace/synth.py``:

    Health -> OpenSession(kernel="jax") + cold plan -> 4 warm
    AssignDelta ticks at 1% provider churn -> a stream-mode session on
    the same population: 16 single-row events, reconcile_every=8

then the same seeded traces a second time in the same process. Every
plan is checked (injective, feasible under ``ops.cost.cost_pairs`` on
the host CPU device, assigned fraction against the native-mt engine and
certified gap against ``scripts/perf_floor.json``), every tick's
``last_stats`` must name the platform JAX found, the second pass must
reproduce the first bit for bit, and warm ticks after the first may not
compile. Any failed check is a non-zero exit with one line saying why.

Without an accelerator this exits non-zero and prints no result; the
last stdout line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Timings printed here are smoke observations, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

# 32k x 32k is the only shape with any chip record and already larger
# than a Borg cell; it divides for sharded generation on 1, 4 and 8
# devices. A constant of this file, not a switch in the program.
ROWS = 32768
WARM_TICKS = 4
CHURN = 0.01
# Sized to the 1200 s the smoke is given, compilation included: on one
# v5e chip a single-row event at 32k takes ~6.7 s today and a reconcile
# ~17 s (CHANGES.md PR 21), so 16 events with a reconcile every 8 keep
# both stream code paths (repair + warm pass, full re-solve) in each of
# the two passes at ~2 min per pass.
EVENTS = 16
RECONCILE_EVERY = 8
KERNEL = "jax"

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class SmokeFailure(RuntimeError):
    """A smoke check did not hold; the message is the one-line reason.
    ``observations`` holds whatever the run had measured by then, for
    the stderr of a failing run — never printed as a result."""

    def __init__(self, message: str, observations=None):
        super().__init__(message)
        self.observations = observations


_T0 = time.perf_counter()


def _progress(msg: str) -> None:
    """One stderr line per phase: a run that is killed at its time limit
    has still said how far it got and what each step cost."""
    print(
        f"[chip_smoke +{time.perf_counter() - _T0:7.1f}s] {msg}",
        file=sys.stderr, flush=True,
    )


class _CompileMeter:
    """Seconds JAX spent in backend compilation (persistent-cache
    fetches included) and the cache's hit/miss counts, read from JAX's
    own monitoring events for as long as the meter is installed."""

    def __init__(self):
        self.seconds = 0.0
        self.executables = 0
        self.cache_hits = 0
        self.cache_misses = 0

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            self.seconds += secs
            self.executables += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1
        elif event == _CACHE_MISS_EVENT:
            self.cache_misses += 1

    def __enter__(self):
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)


def _check_plan(failures, label, p4t, p_cols, r_cols, weights, cpu):
    """Injective over assigned tasks, and every assigned pair feasible
    when re-scored by the solvers' own cost function on the host CPU
    device. Returns the assigned fraction over live tasks."""
    import jax

    from protocol_tpu.ops.cost import INFEASIBLE, cost_pairs
    from protocol_tpu.ops.encoding import (
        EncodedProviders,
        EncodedRequirements,
    )

    seated = p4t[p4t >= 0]
    if np.unique(seated).size != seated.size:
        failures.append(f"{label}: plan is not injective")
    if seated.size and (seated.max() >= p_cols["valid"].shape[0]):
        failures.append(f"{label}: plan names a provider out of range")
        return 0.0
    with jax.default_device(cpu):
        cost = np.asarray(cost_pairs(
            EncodedProviders(**p_cols), EncodedRequirements(**r_cols),
            p4t, weights,
        ))
    bad = int(((p4t >= 0) & ~(cost < INFEASIBLE * 0.5)).sum())
    if bad or not np.isfinite(cost).all():
        failures.append(f"{label}: {bad} assigned pairs are infeasible")
    live = int(np.asarray(r_cols["valid"], bool).sum())
    return float(seated.size) / max(live, 1)


def _check_stats(failures, label, stats, platform, n_dev):
    """The tick ran on the platform JAX found, on every visible device,
    undegraded — and its timed stages did real work."""
    want_isa = f"jax:{platform}"
    if stats.get("native_isa") != want_isa:
        failures.append(
            f"{label}: native_isa {stats.get('native_isa')!r}, "
            f"want {want_isa!r}"
        )
    if stats.get("device_degraded") is not False:
        failures.append(f"{label}: device_degraded is not False")
    if stats.get("jax_devices") != n_dev:
        failures.append(
            f"{label}: jax_devices {stats.get('jax_devices')}, "
            f"{n_dev} visible"
        )
    if "gen_ms" in stats and n_dev > 1 and not stats.get("gen_sharded"):
        failures.append(
            f"{label}: generation ran unsharded with {n_dev} devices"
        )
    if "solve_ms" in stats:
        # the solve wall ends in a NumPy copy of the plan, so a non-zero
        # wall with a non-zero round count is a solve that really ran
        if not stats["solve_ms"] > 0:
            failures.append(f"{label}: solve_ms is not positive")
        if not stats.get("eng_rounds_total", 0) > 0 and stats.get(
            "changed_rows", 1
        ):
            failures.append(f"{label}: eng_rounds_total is not positive")


def _arena_stats(server, sid: str, fp: str) -> dict:
    """The session arena's ``last_stats`` for the tick just answered.
    The client is in lockstep with the server, so nothing else touches
    the session between the reply and this read."""
    session, reason = server.servicer.sessions.get(sid, fp)
    if session is None:
        raise SmokeFailure(f"session {sid} vanished: {reason}")
    return dict(session.arena.last_stats)


def _run_pass(server, address, batch_trace, event_trace, tag, ctx):
    """One pass of the smoke traffic. Returns (plans, observations);
    check failures append to ``ctx["failures"]``."""
    from protocol_tpu.fleet.loadgen import _delta_request, _open
    from protocol_tpu.ops.cost import CostWeights
    from protocol_tpu.proto import wire
    from protocol_tpu.services.scheduler_grpc import SchedulerBackendClient
    from protocol_tpu.stream.events import event_from_delta
    from protocol_tpu.trace.replay import iter_input_ticks

    failures = ctx["failures"]
    floors = ctx["floors"]
    platform, n_dev, cpu = ctx["platform"], ctx["n_dev"], ctx["cpu"]
    plans: list = []
    obs: dict = {"warm_tick_ms": [], "event_us": []}
    client = SchedulerBackendClient(address)
    try:
        # ---- Health: the server names the platform this process found
        h = client.health()
        if h.platform != platform or int(h.device_count) != n_dev:
            failures.append(
                f"{tag} Health says {h.platform} x{h.device_count}, "
                f"jax found {platform} x{n_dev}"
            )

        # ---- batch session: cold open, then warm provider-churn ticks
        snap = batch_trace.snapshot
        weights = CostWeights(*snap.weights)
        sid = f"smoke@{tag}-batch"
        fp = None
        warm_stats = []
        for tick, p_cols, r_cols, delta in iter_input_ticks(batch_trace):
            label = f"{tag} tick {tick}"
            t0 = time.perf_counter()
            if tick == 0:
                fp, err, p4t = _open(client, snap, p_cols, r_cols, sid, KERNEL)
                if fp is None:
                    raise SmokeFailure(f"{label}: OpenSession refused: {err}")
            else:
                resp = client.assign_delta(_delta_request(
                    sid, fp, tick, delta.provider_rows, delta.p_cols,
                    delta.task_rows, delta.r_cols,
                ), timeout=600)
                if not resp.session_ok:
                    raise SmokeFailure(
                        f"{label}: delta refused: {resp.error}"
                    )
                if resp.stale or resp.replayed:
                    failures.append(f"{label}: served stale or replayed")
                p4t = wire.unblob(resp.result.provider_for_task, np.int32)
            wall_ms = (time.perf_counter() - t0) * 1e3
            stats = _arena_stats(server, sid, fp)
            _check_stats(failures, label, stats, platform, n_dev)
            frac = _check_plan(
                failures, label, p4t, p_cols, r_cols, weights, cpu
            )
            native_frac = ctx["native"][tick][0]
            if frac < floors["jax_min_assigned_frac_abs"] or (
                frac < floors["jax_min_assigned_vs_native"] * native_frac
            ):
                failures.append(
                    f"{label}: assigned fraction {frac:.4f} "
                    f"(native-mt {native_frac:.4f})"
                )
            gap = stats.get("gap_per_task")
            if gap is None:
                failures.append(f"{label}: no gap certificate computed")
                gap = float("inf")
            elif tick == 0 and gap > floors["quality_gap_per_task_max"]:
                failures.append(f"{label}: certified gap per task {gap}")
            if tick == 0:
                if not stats.get("cold"):
                    failures.append(f"{label}: open was not a cold solve")
                obs["cold_open_ms"] = round(wall_ms, 3)
                obs["cold_gen_ms"] = stats.get("gen_ms")
                obs["cold_solve_ms"] = stats.get("solve_ms")
                obs["cold_rounds"] = stats.get("eng_rounds_total")
            else:
                if stats.get("cold") or stats.get("cand_cold_passes"):
                    failures.append(f"{label}: warm tick paid a cold pass")
                obs["warm_tick_ms"].append(round(wall_ms, 3))
                warm_stats.append(stats)
                if tick >= 2 and stats.get("jit_compiles_delta") != {}:
                    failures.append(
                        f"{label}: warm tick compiled "
                        f"{stats.get('jit_compiles_delta')} "
                        f"(budget {floors['jax_warm_recompiles_max']})"
                    )
            obs.setdefault("assigned_frac", []).append(round(frac, 6))
            obs.setdefault("gap_per_task", []).append(gap)
            plans.append(p4t)
            _progress(
                f"{label}: {wall_ms:.0f} ms (gen {stats.get('gen_ms')} "
                f"solve {stats.get('solve_ms')} rounds "
                f"{stats.get('eng_rounds_total')}) assigned {frac:.4f} "
                f"gap {gap}"
            )
        # Warm ticks are held to the jax engine's own committed bar, the
        # A/B mean-gap delta against native-mt on the same trace: under
        # provider churn the forward-only warm auction strands a few
        # providers at carried prices (idle_price in the certificate), so
        # single warm ticks sit above quality_gap_per_task_max on every
        # backend until the next dual refresh. The per-tick values and
        # the count over that ceiling are printed, not hidden.
        gap_delta = float(
            np.mean(obs["gap_per_task"])
            - np.mean([g for _f, g in ctx["native"]])
        )
        if not gap_delta <= floors["jax_ab_gap_per_task_delta_max"]:
            failures.append(
                f"{tag}: mean certified gap {gap_delta:+.4f} over "
                f"native-mt's (max {floors['jax_ab_gap_per_task_delta_max']})"
            )
        obs["gap_per_task_mean_delta_vs_native"] = round(gap_delta, 6)
        obs["warm_ticks_over_quality_gap_max"] = sum(
            g > floors["quality_gap_per_task_max"]
            for g in obs["gap_per_task"][1:]
        )
        obs["warm_gen_ms"] = [s.get("gen_ms") for s in warm_stats]
        obs["warm_solve_ms"] = [s.get("solve_ms") for s in warm_stats]
        obs["warm_rounds"] = [s.get("eng_rounds_total") for s in warm_stats]
        obs["warm_changed_rows"] = [s.get("changed_rows") for s in warm_stats]

        # ---- stream session on the same population: single-row events
        snap = event_trace.snapshot
        sid = f"smoke@{tag}-stream"
        fp, err, p4t = _open(
            client, snap, snap.p_cols, snap.r_cols, sid, KERNEL,
            reconcile_every=RECONCILE_EVERY,
        )
        if fp is None:
            raise SmokeFailure(f"{tag} stream open refused: {err}")
        plans.append(p4t)
        p_cum = {k: np.array(v, copy=True) for k, v in snap.p_cols.items()}
        r_cum = {k: np.array(v, copy=True) for k, v in snap.r_cols.items()}
        reconciles = 0
        ev_gap_max = 0.0
        ev_frac_min = 1.0
        for i, d in enumerate(event_trace.deltas, start=1):
            ev = event_from_delta(d)
            label = f"{tag} event {i} ({ev.kind})"
            t0 = time.perf_counter()
            resp = client.assign_delta(_delta_request(
                sid, fp, i, ev.provider_rows, ev.p_cols,
                ev.task_rows, ev.r_cols, event=ev,
            ), timeout=600)
            wall_us = (time.perf_counter() - t0) * 1e6
            if not resp.session_ok:
                raise SmokeFailure(f"{label}: refused: {resp.error}")
            if resp.stale or resp.replayed or resp.event_deduped:
                failures.append(f"{label}: stale, replayed or deduped")
            for rows, vals, cum in (
                (ev.provider_rows, ev.p_cols, p_cum),
                (ev.task_rows, ev.r_cols, r_cum),
            ):
                if rows.size:
                    for name, a in vals.items():
                        cum[name][rows] = np.asarray(a)
            p4t = wire.unblob(resp.result.provider_for_task, np.int32)
            stats = _arena_stats(server, sid, fp)
            _check_stats(failures, label, stats, platform, n_dev)
            if stats.get("cold") or stats.get("cand_cold_passes"):
                failures.append(f"{label}: event paid a cold pass")
            frac = _check_plan(
                failures, label, p4t, p_cum, r_cum, weights, cpu
            )
            if frac < floors["jax_min_assigned_frac_abs"]:
                failures.append(f"{label}: assigned fraction {frac:.4f}")
            gap = float(resp.gap_per_task)
            if not gap <= floors["stream_gap_ceiling"]:
                failures.append(f"{label}: certified gap per task {gap}")
            ev_gap_max = max(ev_gap_max, gap)
            ev_frac_min = min(ev_frac_min, frac)
            reconciles += int(resp.reconciled)
            if resp.reconciled:
                obs.setdefault("reconcile_ms", []).append(
                    round(wall_us / 1e3, 3)
                )
            else:
                obs["event_us"].append(round(wall_us, 1))
                obs.setdefault("event_gen_ms", []).append(stats.get("gen_ms"))
                obs.setdefault("event_solve_ms", []).append(
                    stats.get("solve_ms")
                )
                obs.setdefault("event_rounds", []).append(
                    stats.get("eng_rounds_total")
                )
            plans.append(p4t)
            _progress(
                f"{label}: {wall_us / 1e3:.0f} ms"
                + (" reconciled" if resp.reconciled else
                   f" (gen {stats.get('gen_ms')} solve "
                   f"{stats.get('solve_ms')} rounds "
                   f"{stats.get('eng_rounds_total')})")
            )
        if reconciles != EVENTS // RECONCILE_EVERY:
            failures.append(
                f"{tag} stream: {reconciles} reconciles, want "
                f"{EVENTS // RECONCILE_EVERY}"
            )
        obs["event_gap_per_task_max"] = round(ev_gap_max, 6)
        obs["event_assigned_frac_min"] = round(ev_frac_min, 6)
        obs["reconciles"] = reconciles

        # ---- the guarantees stayed on: every ack was checkpointed first
        seam = {s.name: s.value for s in client.health().seam_metrics}
        if seam.get("ckpt_flush_failures", 1.0) != 0.0:
            failures.append(f"{tag}: checkpoint flushes failed")
        obs["ckpt_flushes_total"] = int(seam.get("ckpt_flushes", 0))
        for name in ("session_miss", "tick_mismatch", "stream_refused",
                     "backpressure_refused", "admission_refused"):
            if seam.get(f"session_{name}"):  # SeamMetrics.snapshot naming
                failures.append(f"{tag}: server counted {name}")
    finally:
        client.close()
    return plans, obs


def _native_reference(batch_trace) -> list:
    """(assigned fraction, certified gap per task) per batch tick from
    the native-mt engine on the same trace — the reference row the jax
    engine's plans are held to."""
    from protocol_tpu.ops.cost import CostWeights
    from protocol_tpu.services.session_store import (
        make_solve_arena,
        parse_session_kernel,
    )
    from protocol_tpu.trace import format as tfmt
    from protocol_tpu.trace.replay import iter_input_ticks

    snap = batch_trace.snapshot
    engine, threads = parse_session_kernel("native-mt")
    arena = make_solve_arena(
        engine, k=max(int(snap.top_k) or 64, 1), threads=threads
    )
    weights = CostWeights(*snap.weights)
    out = []
    for _tick, p_cols, r_cols, _delta in iter_input_ticks(batch_trace):
        p4t = np.asarray(arena.solve(
            tfmt._as_ns(p_cols), tfmt._as_ns(r_cols), weights
        ))
        live = int(np.asarray(r_cols["valid"], bool).sum())
        out.append((
            float((p4t >= 0).sum()) / max(live, 1),
            float(arena.last_stats["gap_per_task"]),
        ))
    return out


def run_smoke(rows: int = ROWS, require_chip: bool = True, seed: int = 0) -> dict:
    """The smoke's body. ``require_chip=False`` lets tests drive the
    same traffic and checks at a small ``rows`` on the CPU backend;
    ``__main__`` always requires the chip. Returns the observation dict
    (raises :class:`SmokeFailure` when any check does not hold)."""
    from protocol_tpu.utils.platform import device_summary, place_compile_cache

    cache_dir = place_compile_cache()

    import jax
    import jaxlib

    try:
        device = device_summary()
    except RuntimeError as e:
        raise SmokeFailure(f"jax found no usable backend: {e}") from e
    platform, n_dev = device["platform"], device["device_count"]
    _progress(f"jax found {platform} ({device['device_kind']} x{n_dev})")
    if require_chip and platform != "tpu":
        raise SmokeFailure(
            f"no accelerator: jax found platform {platform!r} "
            f"({device['device_kind']} x{n_dev}); the smoke runs on a TPU"
        )

    from protocol_tpu import native, obs as obs_pkg
    from protocol_tpu.fleet.fabric import FleetConfig
    from protocol_tpu.fleet.loadgen import _free_port
    from protocol_tpu.services.scheduler_grpc import serve
    from protocol_tpu.trace import format as tfmt
    from protocol_tpu.trace.synth import synth_event_trace, synth_trace

    if not obs_pkg.enabled():
        raise SmokeFailure(
            "the observability plane is off (PROTOCOL_TPU_OBS=0): the "
            "gap certificate and engine round counts come from it"
        )
    with open(os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts", "perf_floor.json",
    )) as fh:
        floors = json.load(fh)

    # the native reference is built from the committed source, never
    # taken from a .so that happened to be on disk
    t0 = time.perf_counter()
    try:
        native.build()
    except native.NativeBuildError as e:
        raise SmokeFailure(f"native reference engine did not build: {e}") from e
    native_build_s = time.perf_counter() - t0

    witness_before = os.environ.get("PROTOCOL_TPU_JIT_WITNESS")
    os.environ["PROTOCOL_TPU_JIT_WITNESS"] = "1"
    failures: list = []
    server = None
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp, \
                _CompileMeter() as meter:
            batch_trace = tfmt.read_trace(synth_trace(
                os.path.join(tmp, "batch.trace"), n_providers=rows,
                n_tasks=rows, ticks=WARM_TICKS, churn=CHURN, seed=seed,
                kernel=KERNEL,
            ))
            # headroom=0: the stream session opens on the batch
            # session's population (joins draw on rows that left)
            event_trace = tfmt.read_trace(synth_event_trace(
                os.path.join(tmp, "events.trace"), n_providers=rows,
                n_tasks=rows, events=EVENTS, seed=seed, kernel=KERNEL,
                headroom=0.0, reconcile_every=RECONCILE_EVERY,
            ))
            t0 = time.perf_counter()
            native_ref = _native_reference(batch_trace)
            native_s = time.perf_counter() - t0
            _progress(
                f"native-mt reference: build {native_build_s:.1f} s, "
                f"{len(native_ref)} ticks in {native_s:.1f} s"
            )
            ctx = {
                "failures": failures, "floors": floors,
                "platform": platform, "n_dev": n_dev,
                "cpu": jax.devices("cpu")[0], "native": native_ref,
            }
            address = f"127.0.0.1:{_free_port()}"
            server = serve(address, fleet=FleetConfig(
                ckpt_dir=os.path.join(tmp, "ckpt"), ckpt_every=1,
            ))
            passes = []
            for n in (0, 1):
                before = (meter.seconds, meter.executables)
                t0 = time.perf_counter()
                plans, obs = _run_pass(
                    server, address, batch_trace, event_trace,
                    f"pass{n}", ctx,
                )
                obs["wall_s"] = round(time.perf_counter() - t0, 3)
                obs["compile_s"] = round(meter.seconds - before[0], 3)
                obs["executables"] = meter.executables - before[1]
                passes.append((plans, obs))
            server.stop(grace=None).wait()
            server = None

            # replay identity on THIS backend: same trace, same plans
            first, second = passes[0][0], passes[1][0]
            diverged = [
                i for i, (a, b) in enumerate(zip(first, second))
                if not np.array_equal(a, b)
            ]
            if len(first) != len(second) or diverged:
                failures.append(
                    f"second pass diverged from the first at plans "
                    f"{diverged[:8]} of {len(first)}"
                )
            mem = [d.memory_stats() or {} for d in jax.devices()]
            result = {
                "smoke": "chip_smoke",
                "platform": platform,
                "device_kind": device["device_kind"],
                "device_count": n_dev,
                "jax": jax.__version__,
                "jaxlib": jaxlib.__version__,
                "libtpu": _libtpu_version(),
                "compile_cache_dir": cache_dir,
                "rows": rows,
                "seed": seed,
                "kernel": KERNEL,
                "compile_s_total": round(meter.seconds, 3),
                "executables_total": meter.executables,
                "compile_cache_hits": meter.cache_hits,
                "compile_cache_misses": meter.cache_misses,
                "peak_bytes_in_use": [
                    m.get("peak_bytes_in_use") for m in mem
                ],
                "native_build_s": round(native_build_s, 3),
                "native_mt_s": round(native_s, 3),
                "native_mt_assigned_frac": [
                    round(f, 6) for f, _g in native_ref
                ],
                "native_mt_gap_per_task": [g for _f, g in native_ref],
                "smoke_observations": [p[1] for p in passes],
            }
    finally:
        if server is not None:
            server.stop(grace=None).wait()
        if witness_before is None:
            os.environ.pop("PROTOCOL_TPU_JIT_WITNESS", None)
        else:
            os.environ["PROTOCOL_TPU_JIT_WITNESS"] = witness_before
    if failures:
        result["failures"] = failures
        raise SmokeFailure(
            f"{len(failures)} check(s) failed: " + "; ".join(failures[:6]),
            result,
        )
    return result


def _libtpu_version():
    try:
        import libtpu
    except ImportError:
        return None
    return getattr(libtpu, "__version__", None)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)
    try:
        result = run_smoke(ROWS, require_chip=True, seed=args.seed)
    except SmokeFailure as e:
        if e.observations is not None:
            print(json.dumps(e.observations), file=sys.stderr)
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result))
    print(json.dumps({"ok": True, "device": {
        "platform": result["platform"],
        "kind": result["device_kind"],
        "count": result["device_count"],
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
