"""What one round of the auction's phase kernel costs, by the rung its
open count picks, timed on the device this process holds.

    python scripts/round_cost.py --shape 8192x8192x80 --shape 8192x8192x256 \
        [--open 32,64,128,1024,2048] [--other parent=<checkout>/protocol_tpu/ops/sparse.py]

A round's cost is read by difference: the same state is solved for
``--rounds`` A and B rounds (``max_iters``), and (t_B - t_A) / (B - A)
is what a round adds, the call's dispatch and the kernel's entry taken
out. The state holds exactly ``n`` open tasks every round: the tasks'
lists name only the first ``T - n`` providers, every one of them is
seated, retirement is off, so each round's ``n`` bidders evict ``n``
owners or lose, and the open count never moves. Prices start at 0 and
climb; no task gives up. ``--other NAME=PATH`` (repeatable) times
another checkout's kernel beside this one in the same process (loaded
from that file), so both are read on one chip. One JSON line per (kernel, shape, open count):
ms a round, the best of ``--repeat`` timings.

``--transpose SHAPE`` (repeatable) times :func:`_transpose_candidates`
instead, at ``--width`` (the reverse and queue passes' 256), on lists
shaped as the transposing cells send them: a dimension written ``N:M``
is ``N`` rows of which the first ``M`` are used, so ``8192:4915x8192x80``
is a pool with slack (tasks past 4,915 list nothing) and
``8192x8192:6554x80`` a pool with a queue (the lists name only the
first 6,554 providers); every used task lists ``K`` distinct providers.
One JSON line per (kernel, shape): ms a call, the best of ``--repeat``,
whether its two arrays equal the change's bit for bit, and the device
time of each XLA op of one more call, read from its profiler trace
(a loop is listed whole and again as the ops of its body, each summed
over the loop's trips).

Rehearse here with ``JAX_PLATFORMS=cpu`` at ``--shape 512x512x16
--open 32,64,200`` or ``--transpose 512:300x512x16 --width 32``: the
times are the CPU's and mean nothing.
"""

from __future__ import annotations

import argparse
import importlib.util
import glob
import json
import os
import re
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _case(T: int, P: int, K: int, n_open: int, seed: int):
    """(cand_provider, cand_cost, state) with ``n_open`` tasks open."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    listed = T - n_open  # providers any task lists, all seated
    cp = np.stack([rng.choice(listed, K, replace=False) for _ in range(T)])
    cc = rng.uniform(0.0, 12.0, (T, K)).astype(np.float32)
    p4t = np.full(T, -1, np.int32)
    p4t[n_open:] = np.arange(listed, dtype=np.int32)
    owner = np.full(P, -1, np.int32)
    owner[:listed] = np.arange(n_open, T, dtype=np.int32)
    state = (
        jnp.int32(0), jnp.zeros(P, jnp.float32), jnp.asarray(owner),
        jnp.asarray(p4t), jnp.zeros(T, bool),
    )
    return jnp.asarray(cp.astype(np.int32)), jnp.asarray(cc), state


def _dim(text: str) -> tuple:
    """``N`` or ``N:M``: (rows, rows used)."""
    n, _, m = text.partition(":")
    return int(n), int(m or n)


def _transpose_case(shape: str, seed: int):
    """(cand_provider, cand_cost, P) for ``T[:live]xP[:listed]xK``."""
    import jax.numpy as jnp
    import numpy as np

    from protocol_tpu.ops.cost import INFEASIBLE

    (T, live), (P, listed), (K, _) = (_dim(d) for d in shape.split("x"))
    rng = np.random.default_rng(seed)
    cp = np.full((T, K), -1, np.int32)
    cc = np.full((T, K), INFEASIBLE, np.float32)
    # K distinct providers a task: the first K of a random order
    cp[:live] = np.argsort(rng.random((live, listed)), axis=1)[:, :K]
    cc[:live] = rng.uniform(0.0, 12.0, (live, K))
    return jnp.asarray(cp), jnp.asarray(cc), P


def _op_name(line: str) -> str:
    """The TPU names an op by its whole HLO line: keep its name, its
    (first) shape and its opcode."""
    name, eq, rest = line.partition(" = ")
    if not eq:
        return line
    parts = [name.lstrip("%")]
    shape = re.search(r"\w+\[[\d,]*\]", rest)
    if shape:
        parts.append(shape.group())
    opcode = re.search(r"\s([\w-]+)\(", rest)
    if opcode:
        parts.append(opcode.group(1))
    return " ".join(parts)


def _op_ms(call) -> dict:
    """Device ms of each XLA op in one traced ``call()``, largest first:
    the ``XLA Ops`` line of the TPU's plane, or on the CPU the host
    events that name an HLO op."""
    import jax

    with tempfile.TemporaryDirectory() as out:
        with jax.profiler.trace(out):
            jax.block_until_ready(call())
        (path,) = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)
        profile = jax.profiler.ProfileData.from_file(path)
        ms: dict = {}
        for plane in profile.planes:
            device = re.search(r"^/device:TPU:\d+$", plane.name)
            for line in plane.lines:
                if device and line.name != "XLA Ops":
                    continue
                for e in line.events:
                    if device or any(k == "hlo_op" for k, _ in e.stats):
                        name = _op_name(e.name)
                        ms[name] = ms.get(name, 0.0) + e.duration_ns * 1e-6
    return {k: round(v, 4) for k, v in sorted(ms.items(), key=lambda kv: -kv[1])}


def _time_transpose(kernels: dict, shape: str, width: int, repeat: int,
                    seed: int, device) -> None:
    import jax
    import numpy as np

    cp, cc, P = _transpose_case(shape, seed)
    want = None
    for name, kernel in kernels.items():
        def call():
            return kernel(cp, cc, num_providers=P, width=width)

        out = [np.asarray(a).view(np.int32) for a in call()]  # compile, warm
        want = out if want is None else want
        times = []
        for _ in range(repeat):
            t0 = time.perf_counter()
            jax.block_until_ready(call())
            times.append(time.perf_counter() - t0)
        print(json.dumps({
            "kernel": name, "transpose": shape, "width": width,
            "ms_a_call": round(min(times) * 1e3, 4),
            "same_as_change": all(
                np.array_equal(a, b) for a, b in zip(out, want)
            ),
            "listed_slots": int((out[0] >= 0).sum()),
            "op_ms": _op_ms(call),
            "platform": device.platform,
            "device_kind": device.device_kind,
        }), flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--shape", action="append", default=[],
                        help="TxPxK: tasks, providers, candidates a task")
    parser.add_argument("--transpose", action="append", default=[],
                        help="T[:live]xP[:listed]xK: time the transposition")
    parser.add_argument("--width", type=int, default=256)
    parser.add_argument("--open", default="32,64,128,1024,2048")
    parser.add_argument("--frontier", type=int, default=4096)
    parser.add_argument("--rounds", default="64,576")
    parser.add_argument("--repeat", type=int, default=3)
    parser.add_argument("--other", action="append", default=[])
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    import jax

    from protocol_tpu.ops import sparse
    from protocol_tpu.utils.platform import place_compile_cache

    if not args.shape and not args.transpose:
        parser.error("give --shape or --transpose")
    place_compile_cache()
    others = {
        name: _load(path, f"{name}_sparse")
        for name, path in (o.split("=", 1) for o in args.other)
    }
    device = jax.devices()[0]
    transposes = {"change": sparse._transpose_candidates}
    transposes.update(
        (name, m._transpose_candidates) for name, m in others.items()
    )
    for shape in args.transpose:
        _time_transpose(transposes, shape, args.width, args.repeat,
                        args.seed, device)
    kernels = {"change": sparse._sparse_auction_phase}
    kernels.update((name, m._sparse_auction_phase) for name, m in others.items())
    r_a, r_b = (int(r) for r in args.rounds.split(","))
    for shape in args.shape:
        T, P, K = (int(x) for x in shape.split("x"))
        for n_open in (int(n) for n in args.open.split(",")):
            cp, cc, state = _case(T, P, K, n_open, args.seed)
            for name, kernel in kernels.items():
                def run(rounds):
                    out = kernel(
                        cp, cc, P, state, eps=0.02, max_iters=rounds,
                        frontier=args.frontier, retire=False, stall_limit=0,
                    )
                    jax.block_until_ready(out)
                    return out

                best = {}
                for rounds in (r_a, r_b):
                    out = run(rounds)  # compile and warm
                    assert int(out[0][0]) == rounds, (name, int(out[0][0]))
                    times = []
                    for _ in range(args.repeat):
                        t0 = time.perf_counter()
                        run(rounds)
                        times.append(time.perf_counter() - t0)
                    best[rounds] = min(times)
                open_after = int(
                    ((out[0][3] < 0) & ~out[0][4]).sum()
                )
                print(json.dumps({
                    "kernel": name, "shape": shape, "open": n_open,
                    "open_after": open_after, "frontier": args.frontier,
                    "ms_a_round": round(
                        (best[r_b] - best[r_a]) / (r_b - r_a) * 1e3, 5
                    ),
                    "call_ms": {str(r): round(t * 1e3, 3)
                                for r, t in best.items()},
                    "rows": int(out[2]),
                    "scans": int(out[3]) if len(out) > 3 else None,
                    "platform": device.platform,
                    "device_kind": device.device_kind,
                }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
