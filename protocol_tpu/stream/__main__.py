"""CLI: ``python -m protocol_tpu.stream {synth,replay}``.

  synth    write a parameterized synthetic EVENT trace (one DELTA frame
           per churn event, deterministic open-loop arrival schedule)
  replay   feed a stream trace through the online engine event by
           event; verifies recorded outcomes bit-for-bit (non-zero exit
           on divergence), optionally under seeded event chaos, and/or
           re-records outcomes (how the golden stream trace is made)
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m protocol_tpu.stream")
    sub = ap.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("synth", help="write a synthetic event trace")
    sp.add_argument("path")
    sp.add_argument("--providers", type=int, default=1024)
    sp.add_argument("--tasks", type=int, default=1024)
    sp.add_argument("--events", type=int, default=256)
    sp.add_argument("--seed", type=int, default=0)
    sp.add_argument("--kernel", default="native-mt")
    sp.add_argument("--top-k", type=int, default=64)
    sp.add_argument("--rate-hz", type=float, default=1000.0)
    sp.add_argument("--reconcile-every", type=int, default=64)
    sp.add_argument("--headroom", type=float, default=0.1)

    rp = sub.add_parser("replay", help="replay a stream trace")
    rp.add_argument("path")
    rp.add_argument("--engine", default=None)
    rp.add_argument("--threads", type=int, default=None)
    rp.add_argument("--reconcile-every", type=int, default=None)
    rp.add_argument("--gap-ceiling", type=float, default=None)
    rp.add_argument("--record", default=None)
    rp.add_argument("--no-verify", action="store_true")
    rp.add_argument(
        "--chaos", default=None,
        help="seeded event-chaos spec, e.g. seed=3,drop=0.1,dup=0.1,"
             "reorder=0.1",
    )

    args = ap.parse_args(argv)
    if args.cmd == "synth":
        from protocol_tpu.trace.synth import synth_event_trace

        path = synth_event_trace(
            args.path,
            n_providers=args.providers,
            n_tasks=args.tasks,
            events=args.events,
            seed=args.seed,
            kernel=args.kernel,
            top_k=args.top_k,
            rate_hz=args.rate_hz,
            reconcile_every=args.reconcile_every,
            headroom=args.headroom,
        )
        print(json.dumps({"path": path, "events": args.events}))
        return 0

    from protocol_tpu.stream.replay import stream_replay
    from protocol_tpu.utils.platform import place_compile_cache

    place_compile_cache()
    chaos = None
    if args.chaos:
        from protocol_tpu.faults.plan import ChaosConfig

        chaos = ChaosConfig.from_spec(args.chaos)
    report = stream_replay(
        args.path,
        engine=args.engine,
        threads=args.threads,
        reconcile_every=args.reconcile_every,
        gap_ceiling=args.gap_ceiling,
        verify=not args.no_verify,
        record_path=args.record,
        chaos=chaos,
    )
    slim = {
        k: v for k, v in report.items()
        if k not in ("event_wall_ms", "gap_per_event", "recon_p4ts")
    }
    print(json.dumps(slim, indent=2, default=str))
    return 1 if report.get("divergence") else 0


if __name__ == "__main__":
    sys.exit(main())
