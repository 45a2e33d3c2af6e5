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

Rehearse here with ``JAX_PLATFORMS=cpu`` at ``--shape 512x512x16
--open 32,64,200``: the times are the CPU's and mean nothing.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
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


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--shape", action="append", required=True,
                        help="TxPxK: tasks, providers, candidates a task")
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

    place_compile_cache()
    kernels = {"change": sparse._sparse_auction_phase}
    for other in args.other:
        name, path = other.split("=", 1)
        kernels[name] = _load(path, f"{name}_sparse")._sparse_auction_phase
    r_a, r_b = (int(r) for r in args.rounds.split(","))
    device = jax.devices()[0]
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
