"""What a session checkpoint's DEFLATE costs and buys, level by level.

A reading, run by no cell (ISSUE 34; ROADMAP S2): open the benchmark's
marketplace (``benchmarks/lib/population.py``, ``population_seed``
25001, 1% of providers re-priced and 0.2% of tasks re-rolled a tick) in
a ``SolveSession`` as the servicer holds it, serve the cold open and a
few warm ticks, and on the next warm tick take the arena's live state
where the checkpointer's worker takes it (``structure_hook``). After
that tick's solve, build the two payloads a journal holds for it, the
SNAPSHOT frame's (the session's padded columns) and the ARENA frame's
(manifest and every buffer, the solve's own last), and DEFLATE them as
``faults/checkpoint._PrefixJob`` and the flush do, one zlib stream a
frame fed buffer by buffer, at every setting:

  * levels 0 (stored) to 6 (``trace/format.COMPRESSLEVEL``, what a
    checkpoint was written at before ISSUE 34); 1 is
    ``faults/checkpoint.CKPT_COMPRESSLEVEL``,
  * ``rle``: strategy ``Z_RLE`` (no string matching: runs of one byte).

Prints one JSON line a shape: raw bytes, and for every setting the best
of ``--repeat`` timings (ms inside zlib, one core) and the bytes out;
with ``--buffers`` the same for each buffer of at least 1% of the
payload alone (a stream of its own, so their bytes do not sum to the
frame's). With ``--chunk`` the two frames again at the checkpoint's
level through ``trace/format.FrameDeflater`` with each chunk size given
in place of ``DEFLATE_CHUNK``, both fed before either is finished, as
the worker feeds them: the best wall of ``--repeat`` on the shared
pool, the time inside zlib summed over the chunks, the bytes out and
the chunks. The DEFLATE is host code: the device only serves the ticks
that make the state.

    python scripts/ckpt_deflate_levels.py --shape 8192x8192 \\
        --shape 8192x4915 --shape 6554x8192 --buffers \\
        --chunk 262144 --chunk 1048576 --chunk 4194304
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time
import zlib

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

WEIGHTS = {"price": 1.0, "load": 1.0, "proximity": 0.001, "priority": 0.0}
SETTINGS = {str(level): (level, zlib.Z_DEFAULT_STRATEGY) for level in range(7)}
SETTINGS["rle"] = (6, zlib.Z_RLE)


def _deflate(pieces: list, level: int, strategy: int) -> tuple[float, int]:
    """``pieces`` through one zlib stream: (ms inside zlib, bytes out)."""
    z = zlib.compressobj(level, zlib.DEFLATED, zlib.MAX_WBITS,
                         zlib.DEF_MEM_LEVEL, strategy)
    ms, out = 0.0, 0
    for piece in pieces:
        t0 = time.perf_counter()
        out += len(z.compress(piece))
        ms += (time.perf_counter() - t0) * 1e3
    t0 = time.perf_counter()
    out += len(z.flush())
    return ms + (time.perf_counter() - t0) * 1e3, out


def _reading(frames: list, repeat: int) -> dict:
    """Every setting over ``frames`` (a list of piece lists, a zlib
    stream each): the best of ``repeat`` total times, the bytes out."""
    out = {}
    for name, (level, strategy) in SETTINGS.items():
        runs = [
            [_deflate(pieces, level, strategy) for pieces in frames]
            for _ in range(repeat)
        ]
        out[name] = {
            "ms": round(min(sum(ms for ms, _ in run) for run in runs), 3),
            "bytes": sum(n for _, n in runs[0]),
        }
    return out


def _chunked(frames: list, chunk: int, repeat: int) -> dict:
    """``frames`` through ``FrameDeflater`` at the checkpoint's level
    in chunks of ``chunk`` bytes: the best wall of ``repeat``, and of
    that run the time inside zlib, the bytes out and the chunks."""
    from protocol_tpu.faults import checkpoint
    from protocol_tpu.trace import format as tfmt

    saved, tfmt.DEFLATE_CHUNK = tfmt.DEFLATE_CHUNK, chunk
    try:
        best = None
        for _ in range(repeat):
            t0 = time.perf_counter()
            deflaters = []
            for pieces in frames:
                d = tfmt.FrameDeflater(checkpoint.CKPT_COMPRESSLEVEL)
                for piece in pieces:
                    d.feed(piece)
                deflaters.append(d)
            bodies = [d.finish()[1] for d in deflaters]
            run = {
                "wall_ms": round((time.perf_counter() - t0) * 1e3, 3),
                "zlib_ms": round(sum(d.take_ms() for d in deflaters), 3),
                "bytes": sum(len(b) for b in bodies),
                "chunks": sum(d.chunks for d in deflaters),
            }
            if best is None or run["wall_ms"] < best["wall_ms"]:
                best = run
        return best
    finally:
        tfmt.DEFLATE_CHUNK = saved


def capture(n_providers: int, n_tasks: int, warm: int) -> tuple[list, dict]:
    """The journal payloads of one warm tick: ``[snapshot pieces, arena
    pieces]`` and the arena's buffers by name."""
    from lib import population

    from protocol_tpu.faults import checkpoint
    from protocol_tpu.ops.cost import CostWeights
    from protocol_tpu.services.session_store import (
        SolveSession,
        _pad_cols,
        make_solve_arena,
    )
    from protocol_tpu.trace import format as tfmt

    gen = population.Pool(
        np.random.default_rng([25001, 0]), n_providers, n_tasks, 0.01, 0.002,
    )
    arena = make_solve_arena("jax", k=64, threads=0)
    session = SolveSession(
        session_id="reading@t", fingerprint="fp",
        weights=CostWeights(**WEIGHTS), kernel="jax", threads=0, top_k=64,
        p_cols=_pad_cols(copy.deepcopy(gen.p_cols), n_providers),
        r_cols=_pad_cols(copy.deepcopy(gen.r_cols), n_tasks),
        n_providers=n_providers, n_tasks=n_tasks, arena=arena,
    )

    def tick() -> None:
        with session.lock:
            session.apply_delta(*gen.next_delta(1.0))
            session.solve()

    with session.lock:
        session.solve()
    for _ in range(warm):
        tick()
    handed: list = []
    arena.structure_hook = handed.append
    tick()
    (live,) = handed
    # the solve-independent buffers are the very objects the hook was
    # handed (what the worker DEFLATEs beside the solve); the solve's
    # own, read now, are what the flush feeds last
    last = tuple(arena.SOLVE_STATE)
    state = arena.live_state()
    checkpoint._pop_arena_meta(state)
    for name, a in live.items():
        if isinstance(a, np.ndarray) and name not in last:
            assert state[name] is a, name
    head, arrays = tfmt.pack_plan(state, last)
    snapshot = tfmt.snapshot_payload(
        session.session_id, session.fingerprint,
        checkpoint._snapshot_request(
            session.p_cols, session.r_cols, session.kernel, session.top_k,
        ),
    )
    buffers = {name: tfmt.raw_bytes(a) for name, a in arrays}
    return [[snapshot], [head, *buffers.values()]], {
        "snapshot": snapshot, **buffers,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", action="append", required=True,
                    help="PROVIDERSxTASKS; may repeat")
    ap.add_argument("--warm", type=int, default=2,
                    help="warm ticks before the one that is read")
    ap.add_argument("--repeat", type=int, default=2)
    ap.add_argument("--buffers", action="store_true",
                    help="every large buffer alone as well")
    ap.add_argument("--chunk", type=int, action="append", default=[],
                    help="a chunk size in bytes for the checkpoint's level "
                    "through FrameDeflater; may repeat")
    args = ap.parse_args()

    from protocol_tpu.utils.platform import place_compile_cache

    place_compile_cache()
    import jax

    device = jax.devices()[0]
    for shape in args.shape:
        n_p, n_t = (int(x) for x in shape.split("x"))
        frames, buffers = capture(n_p, n_t, args.warm)
        raw = sum(len(p) for pieces in frames for p in pieces)
        line = {
            "shape": shape, "raw_bytes": raw,
            "settings": _reading(frames, args.repeat),
        }
        if args.buffers:
            line["buffers"] = {
                name: {"raw_bytes": len(b), **_reading([[b]], args.repeat)}
                for name, b in buffers.items() if len(b) * 100 >= raw
            }
        if args.chunk:
            line["chunked"] = {
                str(size): _chunked(frames, size, args.repeat)
                for size in args.chunk
            }
        line.update(
            zlib=zlib.ZLIB_RUNTIME_VERSION, cores=os.cpu_count(),
            device=f"{device.platform}:{device.device_kind}",
        )
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
