#!/usr/bin/env python
"""Regenerate protocol_tpu/proto/scheduler_pb2.py without protoc.

The container has no protoc / grpcio-tools, so the generated module is
produced from a programmatically-built FileDescriptorProto: this script
is the single source of truth for the wire contract (scheduler.proto is
the human-readable mirror — keep both in sync).

v1-compat invariant: the ProviderBatch / RequirementBatch / CostWeights /
AssignRequest / AssignResponse / HealthRequest / HealthResponse messages
and the Assign / Health methods must keep their field numbers, types and
names EXACTLY as shipped — old clients speak them against new servers.
New revisions may only append messages, fields, and RPCs.

Usage: python scripts/gen_scheduler_pb2.py   (writes the pb2 in place,
then import-checks it in a subprocess).
"""

import os
import subprocess
import sys

from google.protobuf import descriptor_pb2 as dp

F = dp.FieldDescriptorProto

OUT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "protocol_tpu", "proto", "scheduler_pb2.py",
)

PKG = "protocol_tpu.scheduler.v1"

# (name, number, type, repeated?, message type name)
_T = {
    "float": F.TYPE_FLOAT,
    "double": F.TYPE_DOUBLE,
    "int32": F.TYPE_INT32,
    "int64": F.TYPE_INT64,
    "uint32": F.TYPE_UINT32,
    "uint64": F.TYPE_UINT64,
    "bool": F.TYPE_BOOL,
    "string": F.TYPE_STRING,
    "bytes": F.TYPE_BYTES,
}


def _msg(fd, name, fields):
    m = fd.message_type.add()
    m.name = name
    for fname, num, ftype, rep in fields:
        f = m.field.add()
        f.name = fname
        f.number = num
        f.label = F.LABEL_REPEATED if rep else F.LABEL_OPTIONAL
        if ftype in _T:
            f.type = _T[ftype]
        else:  # message-typed field
            f.type = F.TYPE_MESSAGE
            f.type_name = f".{PKG}.{ftype}"
        # proto3 scalar repeated fields are packed by default; submessage
        # presence for optional message fields comes for free
    return m


def build_file() -> dp.FileDescriptorProto:
    fd = dp.FileDescriptorProto()
    fd.name = "protocol_tpu/proto/scheduler.proto"
    fd.package = PKG
    fd.syntax = "proto3"

    # ---------------- v1 (frozen: see module docstring) ----------------
    _msg(fd, "ProviderBatch", [
        ("gpu_count", 1, "int32", True),
        ("gpu_mem_mb", 2, "int32", True),
        ("gpu_model_id", 3, "int32", True),
        ("has_gpu", 4, "bool", True),
        ("has_cpu", 5, "bool", True),
        ("cpu_cores", 6, "int32", True),
        ("ram_mb", 7, "int32", True),
        ("storage_gb", 8, "int32", True),
        ("lat", 9, "float", True),
        ("lon", 10, "float", True),
        ("has_location", 11, "bool", True),
        ("price", 12, "float", True),
        ("load", 13, "float", True),
    ])
    _msg(fd, "RequirementBatch", [
        ("cpu_required", 1, "bool", True),
        ("cpu_cores", 2, "int32", True),
        ("ram_mb", 3, "int32", True),
        ("storage_gb", 4, "int32", True),
        ("max_gpu_options", 5, "uint32", False),
        ("model_words", 6, "uint32", False),
        ("gpu_opt_valid", 7, "bool", True),
        ("gpu_count", 8, "int32", True),
        ("gpu_mem_min", 9, "int32", True),
        ("gpu_mem_max", 10, "int32", True),
        ("gpu_total_mem_min", 11, "int32", True),
        ("gpu_total_mem_max", 12, "int32", True),
        ("gpu_model_mask", 13, "uint32", True),
        ("gpu_model_constrained", 14, "bool", True),
        ("lat", 15, "float", True),
        ("lon", 16, "float", True),
        ("has_location", 17, "bool", True),
        ("priority", 18, "float", True),
    ])
    _msg(fd, "CostWeights", [
        ("price", 1, "float", False),
        ("load", 2, "float", False),
        ("proximity", 3, "float", False),
        ("priority", 4, "float", False),
    ])
    _msg(fd, "AssignRequest", [
        ("providers", 1, "ProviderBatch", False),
        ("requirements", 2, "RequirementBatch", False),
        ("weights", 3, "CostWeights", False),
        ("kernel", 4, "string", False),
        ("top_k", 5, "uint32", False),
        ("eps", 6, "float", False),
        ("max_iters", 7, "uint32", False),
        ("warm_price", 8, "float", True),
        ("seed_provider_for_task", 9, "int32", True),
    ])
    _msg(fd, "AssignResponse", [
        ("provider_for_task", 1, "int32", True),
        ("task_for_provider", 2, "int32", True),
        ("num_assigned", 3, "uint32", False),
        ("solve_ms", 4, "float", False),
        ("price", 5, "float", True),
    ])
    _msg(fd, "HealthRequest", [])
    # v1 fields 1-3 frozen; 4-5 are additions old clients skip as unknown
    _msg(fd, "HealthResponse", [
        ("status", 1, "string", False),
        ("platform", 2, "string", False),
        ("device_count", 3, "uint32", False),
        ("seam_metrics", 4, "MetricSample", True),
        ("device_kind", 5, "string", False),  # jax device_kind
    ])

    # ---------------- v2: tensor frames + session epochs ----------------
    _msg(fd, "TensorBlob", [
        ("data", 1, "bytes", False),      # C-order, little-endian
        ("dtype", 2, "string", False),    # numpy dtype name, e.g. "int32"
        ("shape", 3, "int64", True),
    ])
    _msg(fd, "NamedTensor", [
        ("name", 1, "string", False),
        ("tensor", 2, "TensorBlob", False),
    ])
    _msg(fd, "ProviderBatchV2", [
        ("columns", 1, "NamedTensor", True),
    ])
    _msg(fd, "RequirementBatchV2", [
        ("columns", 1, "NamedTensor", True),
    ])
    _msg(fd, "AssignRequestV2", [
        ("providers", 1, "ProviderBatchV2", False),
        ("requirements", 2, "RequirementBatchV2", False),
        ("weights", 3, "CostWeights", False),
        ("kernel", 4, "string", False),
        ("top_k", 5, "uint32", False),
        ("eps", 6, "float", False),
        ("max_iters", 7, "uint32", False),
        ("warm_price", 8, "TensorBlob", False),
        ("seed_provider_for_task", 9, "TensorBlob", False),
        # streaming sessions (appended — old servers skip them): a
        # session opened with stream_mode accepts event-typed
        # AssignDelta ticks (per-event localized repair instead of a
        # full warm solve) and reconciles with a full batch solve every
        # reconcile_every events (0 = server default)
        ("stream_mode", 10, "bool", False),
        ("reconcile_every", 11, "uint32", False),
    ])
    _msg(fd, "AssignResponseV2", [
        ("provider_for_task", 1, "TensorBlob", False),
        ("task_for_provider", 2, "TensorBlob", False),
        ("num_assigned", 3, "uint32", False),
        ("solve_ms", 4, "float", False),
        ("price", 5, "TensorBlob", False),
        ("decode_ms", 6, "float", False),
    ])
    # client-streamed snapshot: chunk 1 carries the header fields
    # (session_id, fingerprint, codec, total_bytes); every chunk carries a
    # byte range of the serialized (optionally gzipped) AssignRequestV2
    _msg(fd, "SnapshotChunk", [
        ("session_id", 1, "string", False),
        ("epoch_fingerprint", 2, "string", False),
        ("payload", 3, "bytes", False),
        ("codec", 4, "string", False),    # "" | "gzip"
        ("total_bytes", 5, "uint64", False),
    ])
    _msg(fd, "OpenSessionResponse", [
        ("ok", 1, "bool", False),
        ("error", 2, "string", False),
        ("session_id", 3, "string", False),
        ("epoch_fingerprint", 4, "string", False),
        ("result", 5, "AssignResponseV2", False),
    ])
    _msg(fd, "AssignDeltaRequest", [
        ("session_id", 1, "string", False),
        ("epoch_fingerprint", 2, "string", False),
        ("tick", 3, "uint64", False),
        ("provider_rows", 4, "TensorBlob", False),   # i32 row indices
        ("providers", 5, "ProviderBatchV2", False),  # churned rows only
        ("task_rows", 6, "TensorBlob", False),
        ("requirements", 7, "RequirementBatchV2", False),
        # event-typed delta rows (appended): a non-empty event_source
        # marks this delta as ONE churn event — full current row state
        # for its rows, with a per-source monotonic seq the server
        # dedups on (duplicate/superseded events ack without applying).
        # Only stream-mode sessions serve them.
        ("event_source", 8, "string", False),
        ("event_seq", 9, "uint64", False),
        ("event_kind", 10, "string", False),
    ])
    _msg(fd, "AssignDeltaResponse", [
        ("session_ok", 1, "bool", False),
        ("error", 2, "string", False),
        ("result", 3, "AssignResponseV2", False),
        # resilience surface (appended fields — old clients skip them):
        # stale=True marks a DEGRADED answer (the per-tick solve
        # deadline was burned, so the previous plan was served;
        # staleness_ticks counts how many ticks old it is), replayed=
        # True marks an idempotent retransmit answer (the delta was
        # already applied; this is the cached response, not a re-solve)
        ("stale", 4, "bool", False),
        ("staleness_ticks", 5, "uint32", False),
        ("replayed", 6, "bool", False),
        # streaming surface (appended): event_deduped=True acks a
        # duplicate/superseded event WITHOUT applying it (idempotence);
        # reconciled=True marks this answer as a fresh full-solve
        # reconciliation; gap_per_task is the certified optimality-gap
        # bound of the served plan; events_since_reconcile counts the
        # streamed divergence window
        ("event_deduped", 7, "bool", False),
        ("reconciled", 8, "bool", False),
        ("gap_per_task", 9, "float", False),
        ("events_since_reconcile", 10, "uint32", False),
    ])
    _msg(fd, "MetricSample", [
        ("name", 1, "string", False),
        ("value", 2, "double", False),
    ])

    # ---------------- dfleet: live session migration (admin) ----------
    # Drain this process's sessions onto another process: flush each
    # session's checkpoint journal, hand the journal off atomically to
    # the target's namespace, and answer subsequent deltas for the
    # moved sessions with a "moved:<endpoint>" redirect. Empty
    # session_ids = every live session (whole-process drain).
    _msg(fd, "MigrateRequest", [
        ("target_endpoint", 1, "string", False),
        ("target_proc_id", 2, "string", False),
        ("session_ids", 3, "string", True),
    ])
    _msg(fd, "MigrateResponse", [
        ("ok", 1, "bool", False),
        ("error", 2, "string", False),
        ("moved", 3, "uint32", False),
    ])

    svc = fd.service.add()
    svc.name = "SchedulerBackend"
    for name, inp, out, cstream in [
        ("Assign", "AssignRequest", "AssignResponse", False),
        ("Health", "HealthRequest", "HealthResponse", False),
        ("AssignV2", "AssignRequestV2", "AssignResponseV2", False),
        ("OpenSession", "SnapshotChunk", "OpenSessionResponse", True),
        ("AssignDelta", "AssignDeltaRequest", "AssignDeltaResponse", False),
        ("Migrate", "MigrateRequest", "MigrateResponse", False),
    ]:
        m = svc.method.add()
        m.name = name
        m.input_type = f".{PKG}.{inp}"
        m.output_type = f".{PKG}.{out}"
        m.client_streaming = cstream
    return fd


TEMPLATE = '''\
# -*- coding: utf-8 -*-
# Generated by scripts/gen_scheduler_pb2.py.  DO NOT EDIT BY HAND!
# source: protocol_tpu/proto/scheduler.proto
# (no protoc in the build environment: the serialized FileDescriptorProto
#  below is produced programmatically — regenerate with
#  `python scripts/gen_scheduler_pb2.py`)
"""Generated protocol buffer code."""
from google.protobuf.internal import builder as _builder
from google.protobuf import descriptor_pool as _descriptor_pool
from google.protobuf import symbol_database as _symbol_database

_sym_db = _symbol_database.Default()


DESCRIPTOR = _descriptor_pool.Default().AddSerializedFile({blob})

_builder.BuildMessageAndEnumDescriptors(DESCRIPTOR, globals())
_builder.BuildTopDescriptorsAndMessages(
    DESCRIPTOR, 'protocol_tpu.proto.scheduler_pb2', globals()
)
'''


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--check", action="store_true",
        help="verify the committed pb2 matches this generator (CI drift "
        "gate): exit 1 without writing anything if they differ",
    )
    args = ap.parse_args()

    fd = build_file()
    blob = fd.SerializeToString()
    content = TEMPLATE.format(blob=repr(blob))
    if args.check:
        try:
            with open(OUT) as fh:
                committed = fh.read()
        except FileNotFoundError:
            committed = ""
        if committed != content:
            print(
                f"DRIFT: {OUT} does not match scripts/gen_scheduler_pb2.py "
                "— someone edited the generated file by hand, or changed "
                "the generator without regenerating. Run "
                "`python scripts/gen_scheduler_pb2.py` and commit.",
                file=sys.stderr,
            )
            return 1
        print(f"{OUT} is in sync with the generator")
        return 0
    with open(OUT, "w") as fh:
        fh.write(content)
    print(f"wrote {OUT} ({len(blob)} descriptor bytes)")
    # import-check in a clean interpreter (this process's descriptor pool
    # may already hold the previous revision of the file)
    code = (
        "from protocol_tpu.proto import scheduler_pb2 as pb;"
        "m = pb.AssignRequestV2();"
        "m.providers.columns.add().name = 'price';"
        "assert pb.AssignRequest().SerializeToString() == b'';"
        "print('pb2 import check OK:',"
        " len(pb.DESCRIPTOR.message_types_by_name), 'messages')"
    )
    return subprocess.run([sys.executable, "-c", code]).returncode


if __name__ == "__main__":
    sys.exit(main())
