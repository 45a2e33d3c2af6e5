"""The repair beside the regeneration it replaces, in one process.

A reading, not a switch (ROADMAP S3): open the benchmark's marketplace
(``benchmarks/lib/population.py``, ``population_seed`` 25001, 1% of
providers re-priced and 0.2% of tasks re-rolled a tick) in a
``SolveSession`` as the servicer holds it, run the harness's oversized
warm-up tick and a few plain ones so that every program is built, then
on each measured tick serve the tick and afterwards time, on that
tick's columns and the parts the tick started from:

  * ``repair``  ``repair_topk_bidir_sharded`` as this checkout has it,
  * ``parent``  the same function of another checkout's
                ``protocol_tpu/parallel/sparse.py`` (``--parent``),
                loaded beside this one under another module name,
  * ``regen``   a second ``JaxSolveArena._gen`` (no trace and no fetch
                from the compile cache: the cold open made the first),

and compare the three structures with the served tick's bit for bit.
Every timing ends in the NumPy copy of the merged lists, as the served
tick's does. Prints one JSON line a shape (ms, one figure a tick) and
exits non-zero where any bit differs.

    python scripts/repair_vs_regen.py --shape 8192x8192 --shape 8192x4915 \\
        --parent .chip_scratch/parent/protocol_tpu/parallel/sparse.py
"""

from __future__ import annotations

import argparse
import copy
import importlib.util
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

WEIGHTS = {"price": 1.0, "load": 1.0, "proximity": 0.001, "priority": 0.0}
PARTS = ("cand_p", "cand_c", "fwd_p", "fwd_c", "pool_t", "pool_c")


def _load_repair(path: str):
    spec = importlib.util.spec_from_file_location("parent_sparse", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules["parent_sparse"] = module
    spec.loader.exec_module(module)
    return module.repair_topk_bidir_sharded


def measure(n_providers: int, n_tasks: int, warm: int, ticks: int,
            parent_repair) -> dict:
    from lib import population

    from protocol_tpu.ops.cost import CostWeights
    from protocol_tpu.parallel import jax_arena
    from protocol_tpu.parallel.sparse import repair_topk_bidir_sharded
    from protocol_tpu.services.session_store import (
        SolveSession,
        _pad_cols,
        make_solve_arena,
    )

    gen = population.Pool(
        np.random.default_rng([25001, 0]), n_providers, n_tasks, 0.01, 0.002,
    )
    arena = make_solve_arena("jax", k=64, threads=0)
    weights = CostWeights(**WEIGHTS)
    session = SolveSession(
        session_id="reading@t", fingerprint="fp", weights=weights,
        kernel="jax", threads=0, top_k=64,
        p_cols=_pad_cols(copy.deepcopy(gen.p_cols), n_providers),
        r_cols=_pad_cols(copy.deepcopy(gen.r_cols), n_tasks),
        n_providers=n_providers, n_tasks=n_tasks, arena=arena,
    )

    def tick(scale: float = 1.0) -> None:
        with session.lock:
            session.apply_delta(*gen.next_delta(scale))
            session.solve()

    with session.lock:
        session.solve()
    cold_gen_ms = arena.last_stats["gen_ms"]
    tick(2.0)
    for _ in range(warm):
        tick()

    repairs = {"repair": repair_topk_bidir_sharded}
    if parent_repair is not None:
        repairs["parent"] = parent_repair
    out: dict = {name: [] for name in (*repairs, "regen", "served_gen_ms")}
    out.update(rows=[], syncs=[], readback_bytes=[], differs=[])
    # the first pass builds the other checkout's programs: not recorded
    for n in range(ticks + 1):
        prev_p, prev_r = arena._p_fields, arena._r_fields
        parts = dict(
            fwd_p=arena._fwd_p, fwd_c=arena._fwd_c,
            pool_t=arena._pool_t, pool_c=arena._pool_c,
        )
        tick()
        pf, rf = arena._p_fields, arena._r_fields
        stats = arena.last_stats
        served = (
            arena._cand_p, arena._cand_c, arena._fwd_p, arena._fwd_c,
            arena._pool_t, arena._pool_c,
        )
        dirty_p = np.flatnonzero(
            jax_arena._dirty_rows(pf, prev_p, jax_arena._P_SPEC)
        )
        dirty_t = np.flatnonzero(
            jax_arena._dirty_rows(rf, prev_r, jax_arena._R_SPEC)
        )
        ep = jax_arena.EncodedProviders(**pf)
        er = jax_arena.EncodedRequirements(**rf)
        tile, use_mesh = arena._gen_plan(rf["cpu_cores"].shape[0])
        got, took = {}, {"served_gen_ms": stats["gen_ms"]}
        for name, repair in repairs.items():
            t0 = time.perf_counter()
            got[name] = repair(
                ep, er, weights, **parts, dirty_p=dirty_p, dirty_t=dirty_t,
                reverse_r=arena.reverse_r,
                mesh=arena._mesh if use_mesh else None, tile=tile,
                extra=arena.extra, pad_floors=arena._repair_pads,
            )
            took[name] = round((time.perf_counter() - t0) * 1e3, 3)
        t0 = time.perf_counter()
        cand_p, cand_c, _sharded = arena._gen(pf, rf, weights)
        took["regen"] = round((time.perf_counter() - t0) * 1e3, 3)
        got["regen"] = (
            cand_p, cand_c, arena._fwd_p, arena._fwd_c, arena._pool_t,
            arena._pool_c,
        )
        for name, result in got.items():
            for part, a, b in zip(PARTS, result, served):
                if not np.array_equal(np.asarray(a), b):
                    out["differs"].append(f"{name}.{part}")
        if n == 0:
            continue
        for name, ms in took.items():
            out[name].append(ms)
        rep = got["repair"][-1]
        out["rows"].append(rep["repair_rows"])
        out["syncs"].append(rep.get("rep_syncs"))
        out["readback_bytes"].append(rep.get("rep_readback_bytes"))
    return {
        "shape": f"{n_providers}x{n_tasks}",
        "cold_gen_ms": cold_gen_ms, **out,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", action="append", required=True,
                    help="PROVIDERSxTASKS; may repeat")
    ap.add_argument("--parent", help="another checkout's parallel/sparse.py")
    ap.add_argument("--warm", type=int, default=4)
    ap.add_argument("--ticks", type=int, default=6)
    args = ap.parse_args()

    from protocol_tpu.utils.platform import place_compile_cache

    place_compile_cache()
    import jax

    parent = _load_repair(args.parent) if args.parent else None
    device = jax.devices()[0]
    bad = False
    for shape in args.shape:
        n_p, n_t = (int(x) for x in shape.split("x"))
        line = measure(n_p, n_t, args.warm, args.ticks, parent)
        line["device"] = f"{device.platform}:{device.device_kind}"
        print(json.dumps(line), flush=True)
        bad = bad or bool(line["differs"])
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
