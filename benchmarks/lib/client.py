"""The wire-v2 session client the harness drives the server with.

``free_port``, ``open_session`` and ``delta_request`` are copies of
``_free_port``, ``_open`` and ``_delta_request`` of
``protocol_tpu/fleet/loadgen.py`` at commit 5134453, cut to batch
sessions. They use the program's wire codecs and stubs, which are the
client library a caller of the service links against; everything that
judges the replies lives in ``reference.py`` and imports none of it.
"""

from __future__ import annotations

import glob
import hashlib
import json
import os
import socket
import struct
import zlib
from types import SimpleNamespace

import numpy as np


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def open_session(client, solve: dict, p_cols, r_cols, sid: str,
                 timeout: float = 600):
    """OpenSession from full columns. Returns ``(fingerprint, error,
    plan)``; the fingerprint is None when the server refused."""
    from protocol_tpu.proto import scheduler_pb2 as pb
    from protocol_tpu.proto import wire

    w = solve["weights"]
    weights = SimpleNamespace(**w)
    fp = wire.epoch_fingerprint(
        p_cols, r_cols, weights, solve["kernel"], solve["top_k"],
        solve["eps"], solve["max_iters"],
    )
    req = pb.AssignRequestV2(
        providers=wire.encode_providers_v2(SimpleNamespace(**p_cols)),
        requirements=wire.encode_requirements_v2(SimpleNamespace(**r_cols)),
        weights=pb.CostWeights(**w),
        kernel=solve["kernel"], top_k=solve["top_k"], eps=solve["eps"],
        max_iters=solve["max_iters"],
    )
    chunks = list(wire.chunk_snapshot(sid, fp, req))
    resp = client.open_session(iter(chunks), timeout=timeout)
    if not resp.ok:
        return None, resp.error, None
    return fp, "", wire.unblob(resp.result.provider_for_task, np.int32)


def delta_request(sid: str, fp: str, tick: int, provider_rows, p_vals,
                  task_rows, r_vals):
    """One ``AssignDelta`` message carrying a tick's churned rows."""
    from protocol_tpu.proto import scheduler_pb2 as pb
    from protocol_tpu.proto import wire

    req = pb.AssignDeltaRequest(
        session_id=sid, epoch_fingerprint=fp, tick=tick,
    )
    if provider_rows.size:
        req.provider_rows.CopyFrom(wire.blob(provider_rows, np.int32))
        req.providers.CopyFrom(
            wire.encode_providers_v2(SimpleNamespace(**p_vals))
        )
    if task_rows.size:
        req.task_rows.CopyFrom(wire.blob(task_rows, np.int32))
        req.requirements.CopyFrom(
            wire.encode_requirements_v2(SimpleNamespace(**r_vals))
        )
    return req


def plan_of(resp) -> np.ndarray:
    from protocol_tpu.proto import wire

    return wire.unblob(resp.result.provider_for_task, np.int32)


# ---- the checkpoint journal, walked with nothing of the program ------

_MAGIC = b"PTTRACE1"
_HEADER = struct.Struct("<BBII")  # kind, flags, len, crc32
KIND_META, KIND_SNAPSHOT, KIND_OUTCOME, KIND_ARENA = 1, 2, 4, 6
JOURNAL_KINDS = (KIND_META, KIND_SNAPSHOT, KIND_ARENA, KIND_OUTCOME)


def keep_journal(ckpt_dir: str, proc_id: str, sid: str, keep_as: str):
    """Hard-link ``sid``'s checkpoint journal, as it stands on disk this
    instant, to ``keep_as`` and return that path, or None when there is
    no journal. The server replaces a journal whole (``os.replace`` of a
    finished temp file), so the link keeps exactly the bytes that were
    on disk when the ack arrived, costs no copy, and is read after the
    window has closed. The layout (``<root>/<proc>/<sha1(sid)[:24]>.*``)
    is the documented one of ``faults/checkpoint.py``."""
    stem = hashlib.sha1(sid.encode()).hexdigest()[:24]
    paths = [
        p for p in glob.glob(os.path.join(ckpt_dir, proc_id, stem + ".*"))
        if not p.endswith(".tmp")
    ]
    if len(paths) != 1:
        return None
    try:
        os.link(paths[0], keep_as)
    except OSError:
        return None
    return keep_as


def read_journal(path):
    """Every frame of a kept journal, walked to the end of the file:
    ``{kind: payload}`` with the payloads inflated, or None when the
    file is missing or anything in it is short, fails its CRC or is
    left over after the last whole frame. The framing (magic, 10-byte
    header of kind, flags, length and crc32, DEFLATE when ``flags & 1``)
    is the documented one of ``trace/format.py``, restated here so that
    the check shares no code with what it checks."""
    if path is None:
        return None
    try:
        with open(path, "rb") as fh:
            data = fh.read()
    except OSError:
        return None
    if data[: len(_MAGIC)] != _MAGIC:
        return None
    frames, at = {}, len(_MAGIC)
    while at < len(data):
        if at + _HEADER.size > len(data):
            return None
        kind, flags, size, crc = _HEADER.unpack_from(data, at)
        at += _HEADER.size
        payload = data[at: at + size]
        at += size
        if len(payload) != size or zlib.crc32(payload) != crc:
            return None
        try:
            frames[kind] = zlib.decompress(payload) if flags & 1 else payload
        except zlib.error:
            return None
    return frames


def journal_holds(frames, sid: str, tick: int, plan: np.ndarray) -> bool:
    """Whether a journal, as ``read_journal`` gave it, is the whole
    checkpoint of ``sid`` at ``tick``: META, the columns, the arena's
    state and the outcome are all there, META and the outcome name that
    tick, and the plan it would replay is the plan the client was sent."""
    from protocol_tpu.proto import scheduler_pb2 as pb

    if frames is None or any(k not in frames for k in JOURNAL_KINDS):
        return False
    try:
        meta = json.loads(frames[KIND_META])
        out = frames[KIND_OUTCOME]
        (n,) = struct.unpack_from("<I", out)
        resp = pb.AssignResponseV2()
        resp.ParseFromString(out[4: 4 + n])
        tail = json.loads(out[4 + n:])
        journaled = plan_of(SimpleNamespace(result=resp))
    except Exception:  # whatever cannot be decoded is no checkpoint
        return False
    return bool(
        meta.get("session_id") == sid
        and int(meta.get("tick", -1)) == tick
        and int(tail.get("tick", -1)) == tick
        and len(frames[KIND_ARENA]) > 4
        and np.array_equal(journaled[: plan.shape[0]], plan)
    )


def journal_columns(frames):
    """The provider and task columns a journal would restore, as two
    dicts of arrays (padded rows and all), decoded with the wire's own
    codec: the SNAPSHOT frame is a ``SnapshotChunk`` holding an
    ``AssignRequestV2``."""
    from protocol_tpu.proto import scheduler_pb2 as pb
    from protocol_tpu.proto import wire

    chunk = pb.SnapshotChunk()
    chunk.ParseFromString(frames[KIND_SNAPSHOT])
    req = pb.AssignRequestV2()
    req.ParseFromString(chunk.payload)
    return (
        {c.name: wire.unblob(c.tensor) for c in req.providers.columns},
        {c.name: wire.unblob(c.tensor) for c in req.requirements.columns},
    )
